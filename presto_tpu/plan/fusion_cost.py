"""Fragment-fusion economics: per-edge fuse-vs-cut pricing with a
calibrated exchange roofline and a runtime decision memo.

Round 12 fused EVERY mesh-local exchange edge into one shard_map
program.  The committed MULTICHIP record shows that policy is wrong in
both directions: q3 fused wins (host hops deleted, dispatch amortized)
while q18 fused LOSES (2056ms vs 747ms cut warm on the 8-virtual-dev
CPU mesh) — collapsing ten independently-schedulable fragments into one
program serializes work the cut path overlaps, and the in-trace
collectives move 12MB through a slower lane than the loopback host
path.  "Accelerating Presto with GPUs" (PAPERS.md) reaches the same
conclusion for GPU offload: per-operator cost gating beats blanket
offload.

This module prices each mesh-local exchange edge BOTH ways:

    CUT(e)   = host_edge_ms + bytes/host_bw + dispatch_ms
               (PTPG pack -> host hop -> unpack, plus the per-fragment
               task dispatch / compile-amortization overhead the cut
               path pays to keep the producer a separate fragment)
    FUSED(e) = coll_edge_ms(ndev) + bytes/ici_bw(ndev) + serial(e)
               (the in-trace collective, plus the marginal
               fusion-induced serialization cost of growing the fused
               group past `serial_free` independently-schedulable
               fragments — the q18 failure mode)

and greedily contracts only net-win edges (producers-first, the same
topological order `fuse_fragments` walks).  Constants come from a
per-platform profile calibrated by `tools/roofline.py --calibrate`
(the existing `exchange` sweep, least-squares intercept+slope per
ndev), loaded from PRESTO_TPU_FUSION_PROFILE / the `fusion_profile`
session property, with baked defaults measured on the CI CPU host.

A runtime feedback loop closes the model-vs-truth gap: the coordinator
records the observed execute wall of every multi-fragment cluster
query (fused-group and cut-fragment walls, measured with the PR-8
trace clock) into a bounded per-plan-fingerprint decision memo.  When
both legs of a shape have been observed, a mispredicted edge set flips
on the NEXT execution of the same shape — hysteresis-guarded (margin +
consecutive-strike requirement), never mid-query.  `fragment_fusion=
force` reproduces the round-12 fuse-everything policy byte-identically;
`off` keeps the per-fragment HTTP path; `auto` (the default) runs this
model.

The test_lint AST rule confines profile reads and the bandwidth /
serialization constants to THIS module — distribute.py and cluster.py
consume verdicts, never prices.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

#: env var naming a calibration-profile JSON (tools/roofline.py
#: --calibrate writes it); the `fusion_profile` session property is the
#: per-session override.  Reads are confined to this module (test_lint).
PROFILE_ENV = "PRESTO_TPU_FUSION_PROFILE"
PROFILE_PROPERTY = "fusion_profile"

#: bytes assumed for an edge with no est_bytes annotation (a gathered
#: partial-aggregate output is typically this order of magnitude)
DEFAULT_EDGE_BYTES = 1 << 16

#: decision-memo hysteresis: a leg must beat the other by this factor
#: to count as a winner at all, and overturning an EXISTING override
#: takes FLIP_STRIKES consecutive winner-disagreeing observations —
#: noisy walls near parity never ping-pong the decision.
FLIP_MARGIN = 1.15
FLIP_STRIKES = 2
MEMO_MAX_ENTRIES = 256

#: baked per-platform calibration defaults.  The cpu numbers come from
#: `tools/roofline.py --calibrate` on the CI host (least-squares fit of
#: the exchange sweep: host loopback HTTP trip vs in-trace all_to_all
#: over the virtual mesh) — on CPU the "ICI" collective is a memcpy
#: through one core and LOSES to the host path per byte, which is
#: exactly why q18's 12MB of edges should cut there.  The tpu defaults
#: are order-of-magnitude priors (real ICI ~100x host bandwidth, ~ms
#: dispatch) pending an on-chip --calibrate run.
DEFAULT_PROFILES: Dict[str, dict] = {
    "cpu": {
        "platform": "cpu",
        "host_edge_ms": 3.1,
        "host_ms_per_mb": 11.9,
        "coll_edge_ms": {2: 0.1, 4: 0.1, 8: 0.1},
        "coll_ms_per_mb": {2: 31.2, 4: 29.8, 8: 25.3},
        "dispatch_ms": 9.0,
        "serial_ms": 160.0,
        "serial_free": 5,
        # sketch-state edges (plan/distribute stamps Exchange.sketch
        # _only): a fixed-width register fold (lax.pmax / tiny
        # all_gather), priced near-zero so the cost model fuses it by
        # default — the whole point of the sketch is deleting the
        # repartition; the tools/roofline.py `sketch` sweep anchors the
        # per-MB rate (the state is <= m bytes/group regardless of rows)
        "sketch_edge_ms": 0.05,
        "sketch_ms_per_mb": 0.5,
    },
    # the CROSS-HOST lane measured on the CI box (tools/roofline.py
    # --calibrate --multiproc: 2- and 4-process gloo loopback meshes,
    # committed record tools/fusion_profile_cpu-multiproc.json): the
    # dcn tables are keyed by PROCESS count and price the in-trace
    # collective when the fusion target spans processes.  The fori_loop
    # sweep amortises launch, so the intercept fits to ~0 (pinned to
    # the same 0.1ms floor as the coll lane); the slope is ~2.5x the
    # host memcpy rate, so on CPU-gloo SMALL cross-host edges fuse
    # (they dodge host_edge_ms + dispatch) and big ones cut — the memo
    # then refines that crossover per plan shape.
    "cpu-multiproc": {
        "platform": "cpu",
        "host_edge_ms": 3.1,
        "host_ms_per_mb": 11.9,
        "coll_edge_ms": {2: 0.1, 4: 0.1, 8: 0.1},
        "coll_ms_per_mb": {2: 31.2, 4: 29.8, 8: 25.3},
        "dcn_edge_ms": {2: 0.1, 4: 0.1},
        "dcn_ms_per_mb": {2: 31.9, 4: 29.6},
        "dispatch_ms": 9.0,
        "serial_ms": 160.0,
        "serial_free": 5,
        "sketch_edge_ms": 0.05,
        "sketch_ms_per_mb": 0.5,
    },
    "tpu": {
        "platform": "tpu",
        "host_edge_ms": 4.0,
        "host_ms_per_mb": 25.0,     # PTPG pack + DCN hop + unpack
        "coll_edge_ms": {2: 0.05, 4: 0.05, 8: 0.08},
        "coll_ms_per_mb": {2: 0.03, 4: 0.03, 8: 0.03},  # ~40GB/s ICI
        # documented PRIORS pending an on-pod --calibrate --multiproc
        # run: per-host DCN is ~2.5GB/s with ~1ms launch overhead, so
        # cross-host collectives beat the HTTP path (~25ms/MB pack+hop)
        # by ~60x per byte — the DrJAX composition this round targets
        "dcn_edge_ms": {2: 1.0, 4: 1.2, 8: 1.5},
        "dcn_ms_per_mb": {2: 0.4, 4: 0.4, 8: 0.45},
        "dispatch_ms": 6.0,
        "serial_ms": 2.0,           # XLA overlaps collectives on-chip
        "serial_free": 8,
        # on chip the register fold rides the same ~40GB/s ICI as the
        # coll lane but skips the variable-shape exchange machinery
        "sketch_edge_ms": 0.03,
        "sketch_ms_per_mb": 0.03,
    },
}


@dataclasses.dataclass(frozen=True)
class FusionProfile:
    """Calibrated exchange-roofline constants for one platform."""

    platform: str = "cpu"
    host_edge_ms: float = 3.1        # fixed pack+hop+unpack floor
    host_ms_per_mb: float = 11.9     # marginal host-path cost per MB
    coll_edge_ms: Dict[int, float] = dataclasses.field(
        default_factory=dict)      # per-ndev collective launch overhead
    coll_ms_per_mb: Dict[int, float] = dataclasses.field(
        default_factory=dict)      # per-ndev collective cost per MB
    dcn_edge_ms: Dict[int, float] = dataclasses.field(
        default_factory=dict)      # per-NPROC cross-host launch overhead
    dcn_ms_per_mb: Dict[int, float] = dataclasses.field(
        default_factory=dict)      # per-NPROC cross-host cost per MB
    dispatch_ms: float = 9.0         # per-fragment task overhead (cut)
    serial_ms: float = 160.0         # per extra group member past free
    serial_free: int = 5
    sketch_edge_ms: float = 0.05     # fixed-width sketch-fold launch
    sketch_ms_per_mb: float = 0.5    # marginal sketch-state cost per MB

    def _nd(self, table: Dict[int, float], ndev: int,
            default: float) -> float:
        if not table:
            return default
        keys = sorted(table)
        best = keys[0]
        for k in keys:
            if k <= ndev:
                best = k
        return float(table[best])

    def cut_ms(self, nbytes: int) -> float:
        """Price of keeping an edge on the per-fragment HTTP path."""
        return (self.host_edge_ms + self.dispatch_ms
                + nbytes / 1e6 * self.host_ms_per_mb)

    def fused_base_ms(self, nbytes: int, ndev: int,
                      nproc: int = 1) -> float:
        """Price of the edge as an in-trace collective, BEFORE the
        marginal serialization penalty of growing the fused group.
        When the fusion target spans `nproc` > 1 processes the edge
        crosses the DCN fabric — the dcn tables (keyed by process
        count) price that lane; the slower hop dominates the mesh-local
        ICI leg, so the model charges it alone."""
        if nproc > 1 and (self.dcn_edge_ms or self.dcn_ms_per_mb):
            return (self._nd(self.dcn_edge_ms, nproc, 2.0)
                    + nbytes / 1e6
                    * self._nd(self.dcn_ms_per_mb, nproc, 40.0))
        return (self._nd(self.coll_edge_ms, ndev, 1.0)
                + nbytes / 1e6 * self._nd(self.coll_ms_per_mb, ndev, 8.0))

    def sketch_ms(self, nbytes: int) -> float:
        """Price of a sketch-state edge fused: the fixed-width register
        fold (one elementwise collective / tiny gather).  Near-zero and
        independent of the input cardinality that produced the state —
        the lane exists so the model fuses sketch edges by default
        instead of pricing them like a variable-shape exchange."""
        return self.sketch_edge_ms + nbytes / 1e6 * self.sketch_ms_per_mb

    def serial_penalty_ms(self, group: int) -> float:
        """Group-size serialization potential: a fused program of
        `group` fragments pays serial_ms for every member past
        serial_free (the q18 failure mode — independently-schedulable
        fragments collapsed into one sequential trace)."""
        return self.serial_ms * max(0, group - self.serial_free)


def _profile_from_dict(d: dict) -> FusionProfile:
    def _int_keys(m):
        return {int(k): float(v) for k, v in (m or {}).items()}

    return FusionProfile(
        platform=str(d.get("platform", "cpu")),
        host_edge_ms=float(d.get("host_edge_ms", 3.1)),
        host_ms_per_mb=float(d.get("host_ms_per_mb", 11.9)),
        coll_edge_ms=_int_keys(d.get("coll_edge_ms")),
        coll_ms_per_mb=_int_keys(d.get("coll_ms_per_mb")),
        dcn_edge_ms=_int_keys(d.get("dcn_edge_ms")),
        dcn_ms_per_mb=_int_keys(d.get("dcn_ms_per_mb")),
        dispatch_ms=float(d.get("dispatch_ms", 9.0)),
        serial_ms=float(d.get("serial_ms", 160.0)),
        serial_free=int(d.get("serial_free", 5)),
        sketch_edge_ms=float(d.get("sketch_edge_ms", 0.05)),
        sketch_ms_per_mb=float(d.get("sketch_ms_per_mb", 0.5)),
    )


def load_profile(session=None, multihost: bool = False) -> FusionProfile:
    """Session `fusion_profile` (a JSON path) > PRESTO_TPU_FUSION_PROFILE
    env > baked per-platform default.  A missing/bad file degrades to
    the default — calibration is an optimization, never a failure.
    `multihost=True` (the fusion target spans processes) prefers the
    baked `<platform>-multiproc` entry, whose dcn tables carry the
    measured cross-process collective lane."""
    path = None
    if session is not None:
        try:
            path = session.properties.get(PROFILE_PROPERTY) or None
        except Exception:  # noqa: BLE001 — duck-typed sessions in tests
            path = None
    if path is None:
        path = os.environ.get(PROFILE_ENV) or None
    if path:
        try:
            with open(path, encoding="utf-8") as f:
                return _profile_from_dict(json.load(f))
        except (OSError, ValueError):
            pass
    from presto_tpu.observe import profile as OP

    plat = OP.platform()
    if multihost and f"{plat}-multiproc" in DEFAULT_PROFILES:
        return _profile_from_dict(DEFAULT_PROFILES[f"{plat}-multiproc"])
    return _profile_from_dict(default_profile(plat))


def default_profile(platform: str) -> dict:
    """The baked constants of one platform; a platform nobody priced is
    an error, never the CPU's numbers under another name."""
    try:
        return DEFAULT_PROFILES[platform]
    except KeyError:
        raise LookupError(
            f"no fusion cost profile for platform {platform!r}: calibrate "
            "one (tools/roofline.py exchange --calibrate) and point "
            f"{PROFILE_ENV} at it, or add it to DEFAULT_PROFILES") from None


def profile_from_exchange_sweep(sweep: dict, platform: str) -> dict:
    """Fit a calibration profile from the roofline `exchange` sweep
    ({"r64k": {"bytes": B, "host_nd2_ms": .., "coll_nd2_ms": ..}, ...}):
    least-squares intercept+slope of wall vs MB for the host path
    (pooled over ndev — the loopback trip doesn't scale with the mesh)
    and per-ndev for the collective path.  Returns the JSON-able dict
    `load_profile` reads."""

    def fit(points: List[Tuple[float, float]]) -> Tuple[float, float]:
        # (mb, ms) least squares; degenerate inputs fall back sanely
        n = len(points)
        if n == 0:
            return 0.0, 0.0
        if n == 1:
            mb, ms = points[0]
            return 0.0, ms / mb if mb else 0.0
        sx = sum(p[0] for p in points)
        sy = sum(p[1] for p in points)
        sxx = sum(p[0] * p[0] for p in points)
        sxy = sum(p[0] * p[1] for p in points)
        den = n * sxx - sx * sx
        if den <= 0:
            return 0.0, 0.0
        slope = (n * sxy - sx * sy) / den
        intercept = (sy - slope * sx) / n
        return max(intercept, 0.0), max(slope, 0.0)

    host_pts: List[Tuple[float, float]] = []
    coll_pts: Dict[int, List[Tuple[float, float]]] = {}
    dcn_pts: Dict[int, List[Tuple[float, float]]] = {}
    for cell in sweep.values():
        if not isinstance(cell, dict) or "bytes" not in cell:
            continue
        mb = float(cell["bytes"]) / 1e6
        for k, v in cell.items():
            if v is None:
                continue
            if k.startswith("host_nd") and k.endswith("_ms"):
                host_pts.append((mb, float(v)))
            elif k.startswith("coll_nd") and k.endswith("_ms"):
                nd = int(k[len("coll_nd"):-len("_ms")])
                coll_pts.setdefault(nd, []).append((mb, float(v)))
            elif k.startswith("dcn_np") and k.endswith("_ms"):
                np_ = int(k[len("dcn_np"):-len("_ms")])
                dcn_pts.setdefault(np_, []).append((mb, float(v)))
    h_edge, h_mb = fit(host_pts)
    prof = dict(default_profile(platform))
    prof["platform"] = platform
    if host_pts:
        prof["host_edge_ms"] = round(h_edge, 3)
        prof["host_ms_per_mb"] = round(h_mb, 3)
    if coll_pts:
        prof["coll_edge_ms"] = {}
        prof["coll_ms_per_mb"] = {}
        for nd, pts in sorted(coll_pts.items()):
            c_edge, c_mb = fit(pts)
            prof["coll_edge_ms"][nd] = round(c_edge, 3)
            prof["coll_ms_per_mb"][nd] = round(c_mb, 3)
    if dcn_pts:
        prof["dcn_edge_ms"] = {}
        prof["dcn_ms_per_mb"] = {}
        for np_, pts in sorted(dcn_pts.items()):
            d_edge, d_mb = fit(pts)
            prof["dcn_edge_ms"][np_] = round(d_edge, 3)
            prof["dcn_ms_per_mb"][np_] = round(d_mb, 3)
    return prof


# ---------------------------------------------------------------------------
# edge byte estimates (annotate_static_hints row estimates x row width)
# ---------------------------------------------------------------------------


def _row_bytes(outputs) -> int:
    """Estimated wire bytes per row of an exchange edge: 8-byte device
    columns (+1 validity) for numerics/dates, dictionary code + pooled
    string estimate for varchars, two limbs for long decimals."""
    w = 0
    for _sym, t in outputs:
        name = getattr(t, "name", "")
        if name == "HLL_STATE":
            w += int(t.params[0]) + 1  # m uint8 registers per group row
        elif name == "KLL_STATE":
            w += int(t.params[0]) * 8 + 1  # 2K float64s per group row
        elif getattr(t, "is_string", False):
            w += 4 + 16 + 1  # i32 code + amortized dictionary entry
        elif getattr(t, "is_long_decimal", False):
            w += 16 + 1  # two Int128 limbs
        else:
            w += 8 + 1
    return max(w, 1)


def annotate_exchange_bytes(plan, session) -> None:
    """Attach `est_rows_hint` / `est_bytes_hint` to every Exchange node
    of a distributed plan (called by plan/distribute.distribute after
    the exchange insertion pass).  The hints are plain ints riding the
    node __dict__, so plan serde carries them through fragment cutting
    to the coordinator's fusion decision AND to workers (the serde
    round-trip the tests assert).  Stats failures leave nodes bare —
    the model then prices DEFAULT_EDGE_BYTES."""
    from presto_tpu.plan import nodes as P
    from presto_tpu.plan import stats as S

    catalog = getattr(session, "catalog", None)
    if catalog is None:
        return
    memo: dict = {}

    def walk(node):
        for s in node.sources:
            walk(s)
        if isinstance(node, P.Exchange):
            try:
                st = S.derive(node.source, catalog, memo)
                rows = int(max(st.est_rows, 1.0))
                node.est_rows_hint = rows
                node.est_bytes_hint = rows * _row_bytes(node.outputs())
            except Exception:  # noqa: BLE001 — hints are best-effort
                pass

    try:
        walk(plan.root)
        for sub in plan.subplans.values():
            walk(sub)
    except Exception:  # noqa: BLE001
        pass


# ---------------------------------------------------------------------------
# per-edge pricing + greedy contraction
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EdgeDecision:
    """One exchange edge priced both ways.  `fuse` is the verdict;
    `reason` explains a cut ("" when fused): kind (edge kind excluded
    by fragment_fusion_kinds), cost (model: CUT cheaper), memo (the
    decision memo overrode the model), cross_host (no declared mesh —
    filled in by the caller, which owns placement)."""

    eid: int
    kind: str
    consumer: int
    producer: int
    est_bytes: int
    cut_est_ms: float
    fused_est_ms: Optional[float]
    fuse: bool
    reason: str = ""
    #: which collective fabric a FUSE verdict lowers onto: "ici" for a
    #: mesh-local edge, "dcn" when the fusion target spans processes —
    #: the cross_host_collective verdict (repartition -> all_to_all over
    #: DCN, broadcast/gather -> all_gather)
    lane: str = "ici"


def price_edges(fragments, ndev: int, profile: FusionProfile,
                kinds, nproc: int = 1) -> List[EdgeDecision]:
    """Model-only pricing pass: walk edges producers-first (the order
    `fuse_fragments` contracts them), price CUT vs FUSED with the
    marginal serialization penalty of the contraction, and greedily
    fuse net-win edges.  Union-find tracks fused-group sizes so each
    contraction is charged for the parallelism it destroys.  `nproc` >
    1 means the fusion target is a multi-process gang: the collective
    leg prices on the DCN lane."""
    parent = {f.fid: f.fid for f in fragments}
    gsize = {f.fid: 1 for f in fragments}

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    lane = "dcn" if nproc > 1 else "ici"
    out: List[EdgeDecision] = []
    for frag in fragments:
        for inp in frag.inputs:
            nb = int(getattr(inp, "est_bytes", None)
                     or DEFAULT_EDGE_BYTES)
            cut = profile.cut_ms(nb)
            if inp.kind not in kinds:
                out.append(EdgeDecision(
                    inp.eid, inp.kind, frag.fid, inp.producer, nb,
                    round(cut, 3), None, False, "kind"))
                continue
            rc, rp = find(frag.fid), find(inp.producer)
            merged = gsize[rc] + gsize[rp]
            pen = (profile.serial_penalty_ms(merged)
                   - profile.serial_penalty_ms(gsize[rc])
                   - profile.serial_penalty_ms(gsize[rp]))
            if getattr(inp, "sketch", False):
                # sketch-state edge: a fixed-width register fold, priced
                # on the near-zero sketch lane so it fuses by default
                fused = profile.sketch_ms(nb) + pen
                elane = "sketch"
            else:
                fused = profile.fused_base_ms(nb, ndev, nproc) + pen
                elane = lane
            if fused < cut:
                parent[rp] = rc
                gsize[rc] = merged
                out.append(EdgeDecision(
                    inp.eid, inp.kind, frag.fid, inp.producer, nb,
                    round(cut, 3), round(fused, 3), True, "", elane))
            else:
                out.append(EdgeDecision(
                    inp.eid, inp.kind, frag.fid, inp.producer, nb,
                    round(cut, 3), round(fused, 3), False, "cost", elane))
    return out


def fingerprint(fragments) -> str:
    """Plan-shape fingerprint the decision memo keys on: the serde
    bytes of every fragment root (cut BEFORE fusion, so forced-fused,
    forced-cut, and auto legs of the same query share one key)."""
    from presto_tpu.plan import serde as plan_serde

    h = hashlib.sha1()
    for f in fragments:
        h.update(plan_serde.dumps(f.root))
        h.update(b"|")
    return h.hexdigest()[:16]


# ---------------------------------------------------------------------------
# decision memo: runtime feedback, hysteresis-guarded
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MemoEntry:
    best_fused_ms: Optional[float] = None   # best observed WARM wall
    best_cut_ms: Optional[float] = None
    fused_runs: int = 0
    cut_runs: int = 0
    override: Optional[str] = None          # "fuse" | "cut" | None
    strikes: int = 0
    flips: int = 0
    runs: int = 0


class DecisionMemo:
    """Bounded per-plan-fingerprint memory of observed execute walls.
    `observe` records each execution's wall under the mode that ran
    (fused / cut); once BOTH legs of a shape have been seen, the better
    one (by FLIP_MARGIN) becomes the override consulted on the next
    auto execution — a misprediction flips the edge set next run, never
    mid-query.  Overturning an existing override takes FLIP_STRIKES
    consecutive disagreeing observations (hysteresis), so walls jittering
    around parity never ping-pong the plan."""

    def __init__(self, max_entries: int = MEMO_MAX_ENTRIES):
        self._entries: "OrderedDict[str, MemoEntry]" = OrderedDict()
        self._max = max_entries
        self._lock = threading.Lock()

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def entry(self, fp: str) -> Optional[MemoEntry]:
        with self._lock:
            return self._entries.get(fp)

    def verdict(self, fp: str) -> Optional[str]:
        with self._lock:
            e = self._entries.get(fp)
            return e.override if e is not None else None

    def observe(self, fp: str, mode: str, wall_ms: float) -> None:
        """Record one execution's wall.  `mode` is "fused" when the
        attempt ran any fused super-fragment, "cut" otherwise."""
        if wall_ms <= 0.0:
            return
        with self._lock:
            e = self._entries.get(fp)
            if e is None:
                e = self._entries[fp] = MemoEntry()
                while len(self._entries) > self._max:
                    self._entries.popitem(last=False)
            else:
                self._entries.move_to_end(fp)
            e.runs += 1
            # each mode's FIRST observation is cold — dominated by
            # one-time XLA compiles (a cut leg's per-fragment compile
            # bill dwarfs its steady-state wall) — so it never enters
            # the comparison; the best WARM wall is what fuse-vs-cut
            # economics are about
            if mode == "fused":
                e.fused_runs += 1
                if e.fused_runs > 1:
                    e.best_fused_ms = wall_ms if e.best_fused_ms is None \
                        else min(e.best_fused_ms, wall_ms)
            else:
                e.cut_runs += 1
                if e.cut_runs > 1:
                    e.best_cut_ms = wall_ms if e.best_cut_ms is None \
                        else min(e.best_cut_ms, wall_ms)
            f, c = e.best_fused_ms, e.best_cut_ms
            if f is None or c is None:
                return
            if f * FLIP_MARGIN < c:
                winner = "fuse"
            elif c * FLIP_MARGIN < f:
                winner = "cut"
            else:
                e.strikes = 0
                return
            if e.override is None:
                e.override = winner
                e.strikes = 0
            elif e.override != winner:
                e.strikes += 1
                if e.strikes >= FLIP_STRIKES:
                    e.override = winner
                    e.strikes = 0
                    e.flips += 1
            else:
                e.strikes = 0


#: process-wide memo, like the compile-cache executable memo: decisions
#: learned by one session serve every session executing the same shape
MEMO = DecisionMemo()


def memo_enabled(session) -> bool:
    """The feedback loop's kill switch (`fragment_fusion_memo`, default
    on): off = model-only decisions, nothing recorded."""
    try:
        return bool(session.properties.get("fragment_fusion_memo", True))
    except Exception:  # noqa: BLE001
        return True


def decide_edges(fragments, ndev: int, session, mode: str,
                 kinds, fp: str = "", nproc: int = 1) -> Tuple[
                     Dict[int, bool], Dict[str, int], int,
                     str, List[EdgeDecision]]:
    """The coordinator's one entry point: price every exchange edge and
    return (verdict {eid: fuse?}, skip-reason counts, mispredicted-edge
    count, plan fingerprint, per-edge decisions).  `fp` is the caller's
    precomputed plan fingerprint (computed here when omitted and the
    memo is on).  `nproc` is the process span of the fusion target the
    caller chose (1 = mesh-local; > 1 prices the DCN lane and stamps
    FUSE verdicts lane="dcn").

    mode "force" reproduces round 12: every kind-eligible edge fuses,
    the model prices nothing.  mode "auto" runs the greedy model, then
    applies the decision memo's override (if this shape has observed
    walls contradicting the model, the edges flip — each flipped edge
    counts as mispredicted)."""
    profile = load_profile(session, multihost=nproc > 1)
    if not fp and memo_enabled(session):
        fp = fingerprint(fragments)
    lane = "dcn" if nproc > 1 else "ici"
    if mode == "force":
        decisions = []
        for frag in fragments:
            for inp in frag.inputs:
                ok = inp.kind in kinds
                decisions.append(EdgeDecision(
                    inp.eid, inp.kind, frag.fid, inp.producer,
                    int(getattr(inp, "est_bytes", None)
                        or DEFAULT_EDGE_BYTES),
                    0.0, None, ok, "" if ok else "kind", lane))
        mispredicted = 0
    else:
        decisions = price_edges(fragments, ndev, profile, kinds, nproc)
        override = MEMO.verdict(fp) if fp else None
        mispredicted = 0
        if override is not None:
            for d in decisions:
                if d.reason == "kind":
                    continue
                if override == "cut" and d.fuse:
                    d.fuse, d.reason = False, "memo"
                    mispredicted += 1
                elif override == "fuse" and not d.fuse:
                    d.fuse, d.reason = True, ""
                    mispredicted += 1
    verdict = {d.eid: d.fuse for d in decisions}
    skips: Dict[str, int] = {}
    for d in decisions:
        if not d.fuse:
            skips[d.reason] = skips.get(d.reason, 0) + 1
    return verdict, skips, mispredicted, fp, decisions
