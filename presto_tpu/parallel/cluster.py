"""Multi-process cluster execution: coordinator + worker processes over
HTTP (the DCN control plane).

Reference parity: the full coordinator/worker split of SURVEY.md §3.1-3.3 —
SqlQueryScheduler creating one HttpRemoteTask per (fragment, worker)
(`POST /v1/task/{id}` with plan + splits + buffer layout), workers pulling
shuffle pages from upstream workers
(`GET /v1/task/{id}/results/{buffer}/{token}`), and PagesSerde framing the
wire bytes.  TPU-native adaptation: the SAME distributed plan that traces
to ICI collectives inside one shard_map (parallel/dist_executor.py) is here
cut at its Exchange nodes into fragments (PlanFragmenter analog) and
executed as BSP supersteps across OS processes — each worker runs its
fragment on its own XLA device(s), and each Exchange becomes an HTTP
shuffle over DCN instead of a collective over ICI:

    repartition -> hash-bucketed worker->worker page pull (P1)
    broadcast   -> every consumer pulls every producer's buffer (P2)
    gather      -> coordinator pulls all buffers (P5)
    range       -> sample-sort bucket exchange: consumer shard i owns
                   key range i (P11 distributed sort over DCN)

The wire format is the native PTPG page serde (native/serde.py — LZ4 +
xxh64, the PagesSerde role), with validity vectors and dictionary-decoded
strings packed alongside data columns.

Scheduling is ALL-AT-ONCE with streaming pages (reference:
AllAtOnceExecutionPolicy + ExchangeClient long-polls,
operator/ExchangeClient.java:69): every fragment's tasks are submitted
up front with pre-assigned upstream locations; leaf tasks publish a page
per split chunk as produced, and consumers pull pages with sequence
tokens + acks (at-least-once delivery with client dedup,
server/TaskResource.java:244-307) — stages overlap, P7 pipelining.
Failure handling (docs/ROBUSTNESS.md): every RPC goes through one
signed choke point (`_http`) with retry/backoff and a per-query
Deadline from parallel/retry.py; worker health is a circuit breaker
(consecutive-failure trip, probation re-admission) instead of one-shot
probes; stragglers are hedged onto healthy survivors with first-
FINISHED-wins dedup by sequence token; worker failure mid-query remaps
the dead slots onto survivors and re-executes.  All of it is
deterministically testable through parallel/faults.py.
"""

from __future__ import annotations

import dataclasses
import hashlib
import hmac
import ipaddress
import json
import os
import secrets as _pysecrets
import threading
import time
import urllib.error
import urllib.request
import uuid
from http.client import HTTPException
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple
from urllib.parse import urlsplit

import numpy as np

from presto_tpu import session_ctx as _sctx
from presto_tpu.exec import compile_cache as CC
from presto_tpu.observe import trace as TR
from presto_tpu.parallel import faults as F
from presto_tpu.parallel import journal as J
from presto_tpu.parallel import retry as R
from presto_tpu.plan import runtime_filters as DF
from presto_tpu.plan import serde as plan_serde
from presto_tpu.native import serde as pserde


# ---------------------------------------------------------------------------
# control-plane authentication
#
# Task payloads are tagged-JSON plan fragments (plan/serde.py, the
# reference's Jackson-encoded PlanFragment role) — the decoder builds
# only whitelisted plan dataclasses, never arbitrary code.  Every worker
# endpoint still requires a shared-secret HMAC (defense in depth +
# admission control).  The secret is distributed via the
# PRESTO_TPU_CLUSTER_SECRET env var (inherited by worker processes) or
# set_cluster_secret().  Binding a non-loopback host without a secret is
# refused outright.
# ---------------------------------------------------------------------------

AUTH_HEADER = "X-PrestoTPU-Auth"
_SECRET_ENV = "PRESTO_TPU_CLUSTER_SECRET"
_process_secret: Optional[bytes] = None


def set_cluster_secret(secret) -> None:
    """Set this process's cluster shared secret (str or bytes)."""
    global _process_secret
    _process_secret = (secret.encode() if isinstance(secret, str)
                       else secret)


def cluster_secret() -> Optional[bytes]:
    if _process_secret is not None:
        return _process_secret
    s = os.environ.get(_SECRET_ENV)
    return s.encode() if s else None


_AUTH_MAX_SKEW = 300.0  # seconds a signed request stays valid


def _sign(secret: bytes, method: str, path: str, body: bytes,
          ts: Optional[str] = None) -> str:
    """Header value `ts:mac` — the timestamp is signed, giving captured
    requests a bounded replay window even over plaintext DCN."""
    ts = ts if ts is not None else str(int(TR.wall_s()))
    mac = hmac.new(secret, digestmod=hashlib.sha256)
    mac.update(method.encode())
    mac.update(b"\n")
    mac.update(path.encode())
    mac.update(b"\n")
    mac.update(ts.encode())
    mac.update(b"\n")
    mac.update(body or b"")
    return ts + ":" + mac.hexdigest()


def _verify_auth(secret: bytes, header: str, method: str, path: str,
                 body: bytes) -> bool:
    ts, _, _ = header.partition(":")
    try:
        skew = abs(TR.wall_s() - int(ts))
    except ValueError:
        return False
    if skew > _AUTH_MAX_SKEW:
        return False
    want = _sign(secret, method, path, body, ts=ts)
    return hmac.compare_digest(header.encode("utf-8", "replace"),
                               want.encode())


def _is_loopback(host: str) -> bool:
    if host == "":
        return False  # '' binds INADDR_ANY — every interface
    if host == "localhost":
        return True
    try:
        return ipaddress.ip_address(host).is_loopback
    except ValueError:
        return False  # hostname — assume routable, require a secret


# ---------------------------------------------------------------------------
# wire helpers: (data, valid) column pairs <-> PTPG frames
# ---------------------------------------------------------------------------

# page encodings a producer DECLARES at publish time and the server
# echoes back as the X-Page-Encoding header.  Integrity verification on
# receipt is gated on this declaration — NOT on sniffing the PTPG magic,
# which silently waved through corrupt non-PTPG (JSON range-sample)
# pages and corrupt PTPG pages whose first bytes were damaged.
PAGE_ENC_PTPG = "ptpg"   # native frame: verified via pserde.frame_ok
PAGE_ENC_JSON = "json"   # tagged JSON (range samples): must parse
PAGE_ENC_HEADER = "X-Page-Encoding"

# orphan-task sweep slack past the query deadline: a live coordinator
# DELETEs its tasks well inside this window (the reap loop runs under
# ACK_TIMEOUT_S per task); only a DEAD coordinator's tasks survive to
# expiry, and the worker frees them itself (WorkerServer.reap_expired)
ORPHAN_GRACE_S = 5.0


def _page_ok(body: bytes, enc: str) -> bool:
    """Receipt-time integrity check by DECLARED encoding; an empty
    declaration (pre-encoding producer) falls back to the magic sniff
    for compatibility."""
    if enc == PAGE_ENC_PTPG:
        return pserde.frame_ok(body)
    if enc == PAGE_ENC_JSON:
        try:
            json.loads(body.decode("utf-8"))
            return True
        except (UnicodeDecodeError, ValueError):
            return False
    return body[:4] != pserde.MAGIC or pserde.frame_ok(body)


def pack_columns(cols: Dict[str, Tuple[np.ndarray, Optional[np.ndarray]]]
                 ) -> bytes:
    """Columns with optional validity -> one PTPG frame.  Object (string /
    container) columns are dictionary-packed: int32 codes + a tagged-
    JSON value list (strings use a compact utf-8 blob)."""
    flat: Dict[str, np.ndarray] = {}
    for name, (data, valid) in cols.items():
        data = np.asarray(data)
        if data.dtype == object or data.dtype.kind in ("U", "S"):
            vals = data.astype(object)
            if all(isinstance(v, str) for v in vals.tolist()):
                uniq, inv = np.unique(vals.astype(str), return_inverse=True)
                # offsets + utf8 bytes: values may contain ANY character
                encoded = [u.encode("utf-8") for u in uniq.tolist()]
                blob = b"".join(encoded)
                offs = np.cumsum([0] + [len(e) for e in encoded]
                                 ).astype(np.uint32)
                flat[name + "\x00scodes"] = inv.astype(np.int32)
                flat[name + "\x00soffs"] = offs
                flat[name + "\x00sdict"] = np.frombuffer(
                    blob, dtype=np.uint8).copy() if blob else np.empty(
                    0, dtype=np.uint8)
            else:  # tuples (ARRAY/MAP/ROW entries) or mixed: tagged JSON
                uniq = sorted(set(vals.tolist()), key=repr)
                cmap = {v: i for i, v in enumerate(uniq)}
                flat[name + "\x00pcodes"] = np.fromiter(
                    (cmap[v] for v in vals.tolist()), np.int32, len(vals))
                flat[name + "\x00pdict"] = np.frombuffer(
                    plan_serde.dumps(uniq), dtype=np.uint8).copy()
        else:
            flat[name + "\x00data"] = data
        if valid is not None:
            flat[name + "\x00valid"] = np.asarray(valid, dtype=np.bool_)
    return pserde.serialize_columns(flat)


def unpack_columns(buf: bytes
                   ) -> Dict[str, Tuple[np.ndarray, Optional[np.ndarray]]]:
    flat = pserde.deserialize_columns(buf)
    out: Dict[str, list] = {}
    valids: Dict[str, np.ndarray] = {}
    for key, arr in flat.items():
        name, kind = key.split("\x00", 1)
        if kind == "valid":
            valids[name] = arr.astype(bool)
        elif kind == "data":
            out[name] = arr
        elif kind in ("scodes", "pcodes"):
            out.setdefault(name, {})["codes"] = arr
        elif kind == "soffs":
            out.setdefault(name, {})["offs"] = arr
        elif kind == "sdict":
            out.setdefault(name, {})["sblob"] = arr
        elif kind == "pdict":
            out.setdefault(name, {})["pblob"] = arr
    cols = {}
    for name, v in out.items():
        if isinstance(v, dict):
            codes = v["codes"]
            if "pblob" in v:
                uniq_list = plan_serde.loads(v["pblob"].tobytes())
            else:
                blob = v["sblob"].tobytes()
                offs = v["offs"]
                uniq_list = [blob[offs[i]:offs[i + 1]].decode("utf-8")
                             for i in range(len(offs) - 1)]
            uniq = np.empty(len(uniq_list), dtype=object)
            uniq[:] = uniq_list
            data = uniq[np.clip(codes, 0, max(len(uniq) - 1, 0))] \
                if len(uniq) else np.empty(0, dtype=object)
        else:
            data = v
        cols[name] = (data, valids.get(name))
    return cols


def _mix64(v: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer — deterministic across processes."""
    with np.errstate(over="ignore"):
        v = v.astype(np.uint64)
        v ^= v >> np.uint64(33)
        v *= np.uint64(0xFF51AFD7ED558CCD)
        v ^= v >> np.uint64(33)
        v *= np.uint64(0xC4CEB9FE1A85EC53)
        v ^= v >> np.uint64(33)
    return v


def hash_partition(cols, keys, nbuckets: int) -> np.ndarray:
    """Per-row bucket index from the VALUES of the key columns (the
    PartitionFunction role).  Must agree across producer processes, so it
    hashes values, never dictionary codes."""
    n = None
    for name, (data, _) in cols.items():
        n = len(data)
        break
    h = np.zeros(n or 0, dtype=np.uint64)
    for k in keys:
        data, valid = cols[k]
        data = np.asarray(data)
        if data.dtype == object or data.dtype.kind in ("U", "S"):
            vals = data.astype(object)
            uniq, inv = np.unique(vals.astype(str), return_inverse=True)
            from presto_tpu import native

            per = np.asarray([native.xxh64(u.encode("utf-8"))
                              for u in uniq.tolist()], dtype=np.uint64)
            hv = per[inv]
        elif data.dtype.kind == "f":
            hv = _mix64(data.astype(np.float64).view(np.uint64))
        elif data.dtype.kind == "b":
            hv = _mix64(data.astype(np.uint64))
        else:
            hv = _mix64(data.astype(np.int64).view(np.uint64))
        if valid is not None:
            hv = np.where(valid, hv, np.uint64(0))
        with np.errstate(over="ignore"):
            h = h * np.uint64(31) + hv
    return (h % np.uint64(max(nbuckets, 1))).astype(np.int64)


# ---------------------------------------------------------------------------
# plan fragmentation (PlanFragmenter analog)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ExchangeInput:
    eid: int
    kind: str  # repartition | broadcast | gather | range | scatter
    keys: List[str]
    producer: int  # fragment id
    # edge-byte annotations (plan/fusion_cost.annotate_exchange_bytes
    # stamps the Exchange node at distribute() time; cut_fragments
    # carries them here so the fusion cost model prices real volumes)
    est_rows: Optional[int] = None
    est_bytes: Optional[int] = None
    # sketch-state edge (plan/distribute stamps Exchange.sketch_only):
    # fixed-width mergeable rows — fusion_cost prices it on the
    # near-zero sketch lane so the fold fuses by default
    sketch: bool = False
    # "pmax" on global all-$hll_partial gather edges: the fused splice
    # restores it onto the inline Exchange so the merge lowers to ONE
    # lax.pmax collective (parallel/dist_executor._exec_exchange)
    sketch_merge: str = ""


@dataclasses.dataclass
class Fragment:
    fid: int
    root: object  # PlanNode with Exchanges replaced by __exch_ TableScans
    inputs: List[ExchangeInput]
    has_scan: bool
    on_workers: bool = True
    # how this fragment's output is partitioned for its consumer exchange
    out_kind: str = "gather"
    out_keys: List[str] = dataclasses.field(default_factory=list)


def cut_fragments(root) -> List[Fragment]:
    """Cut the distributed plan at Exchange nodes (reference:
    PlanFragmenter.createSubPlans).  Producers appear before consumers
    (topological by construction)."""
    from presto_tpu.plan import nodes as P

    fragments: List[Fragment] = []
    eid_counter = [0]

    def build(node, out_kind: str, out_keys: List[str]) -> int:
        inputs: List[ExchangeInput] = []
        has_scan = [False]

        def rewrite(n):
            if isinstance(n, P.Exchange):
                # range exchanges carry (sym, asc, nulls_first) sort keys
                okeys = list(getattr(n, "sort_keys", None) or n.keys)
                pf = build(n.source, n.kind, okeys)
                eid = eid_counter[0]
                eid_counter[0] += 1
                inputs.append(ExchangeInput(
                    eid, n.kind, list(n.keys), pf,
                    est_rows=getattr(n, "est_rows_hint", None),
                    est_bytes=getattr(n, "est_bytes_hint", None),
                    sketch=bool(getattr(n, "sketch_only", False)),
                    sketch_merge=str(getattr(n, "sketch_merge", ""))))
                types = dict(n.outputs())
                return P.TableScan(f"__exch_{eid}",
                                   {s: s for s in types}, types)
            if isinstance(n, P.TableScan):
                has_scan[0] = True
                return n
            changed = {}
            for f in dataclasses.fields(n):
                v = getattr(n, f.name)
                if isinstance(v, P.PlanNode):
                    nv = rewrite(v)
                    if nv is not v:
                        changed[f.name] = nv
                elif isinstance(v, list) and v \
                        and all(isinstance(x, P.PlanNode) for x in v):
                    nv = [rewrite(x) for x in v]
                    if any(a is not b for a, b in zip(nv, v)):
                        changed[f.name] = nv
            if not changed:
                return n
            nn = dataclasses.replace(n, **changed)
            # carry the optimizer's static-shape hints (build_unique,
            # fanout_bound, key_stats, capacity_hint — instance attrs,
            # not dataclass fields; plan/optimizer.annotate_static_hints
            # runs BEFORE fragmentation and must survive it)
            fields = {f.name for f in dataclasses.fields(n)}
            for k, v in n.__dict__.items():
                if k not in fields and k not in nn.__dict__:
                    setattr(nn, k, v)
            return nn

        new_root = rewrite(node)
        fid = len(fragments)
        # a fragment runs on all workers if it scans base tables or
        # consumes worker-partitioned data (incl. range buckets: shard i
        # sorts key-range i locally — real distributed sort over DCN);
        # gathered inputs mean the data is collected in one place ->
        # single-node execution
        on_workers = has_scan[0] or any(
            i.kind in ("repartition", "broadcast", "scatter", "range")
            for i in inputs)
        fragments.append(Fragment(fid, new_root, inputs, has_scan[0],
                                  on_workers, out_kind, out_keys))
        return fid

    build(root, "gather", [])
    return fragments


# ---------------------------------------------------------------------------
# task execution (both worker-side and coordinator-side)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TaskSpec:
    task_id: str
    fragment: bytes  # tagged-JSON plan root (plan/serde.py)
    out_symbols: List[str]
    nworkers: int
    windex: int  # this worker's index (coordinator: 0)
    # eid -> {kind, upstreams: [(url, task_id)]}; buffer to pull is windex
    # for repartition, 0 for broadcast/gather
    inputs: List[dict]
    out_kind: str = "gather"
    out_keys: List[str] = dataclasses.field(default_factory=list)
    out_buckets: int = 1
    scalar_results: Dict[int, tuple] = dataclasses.field(default_factory=dict)
    properties: Dict[str, object] = dataclasses.field(default_factory=dict)
    # durable exchange (P12, reference: ExchangeNode.java:60
    # REMOTE_MATERIALIZED): published pages ALSO persist under
    # durable_dir/durable_key/a{attempt}/ — past acks, past task DELETE —
    # until the query ends; a retry replays completed tasks from disk
    # instead of re-executing them
    durable_dir: Optional[str] = None
    durable_key: Optional[str] = None  # f{fid}_w{windex}, attempt-stable
    attempt: int = 0
    replay: bool = False  # serve the durable pages; do not execute


plan_serde.register_class(TaskSpec)


def _signed_request(method: str, url: str,
                    body: Optional[bytes] = None) -> urllib.request.Request:
    """THE request builder: every outbound control/data-plane request is
    constructed (and HMAC-signed over the full request target) here."""
    req = urllib.request.Request(url, data=body, method=method)
    # trace-context propagation (observe/trace.py): every outbound
    # request carries this thread's trace context so worker-side task
    # spans stitch into the coordinator's trace; a stripped header
    # (PRESTO_TPU_TRACE_PROPAGATION=off) degrades the worker to a
    # worker-local trace, never an error
    tctx = TR.wire_context()
    if tctx is not None:
        req.add_header(TR.TRACE_HEADER, tctx)
    secret = cluster_secret()
    if secret is not None:
        parts = urlsplit(url)  # sign the full request target (path?query)
        path = parts.path + ("?" + parts.query if parts.query else "")
        req.add_header(AUTH_HEADER, _sign(secret, method, path, body or b""))
    return req


def _http(url: str, data: Optional[bytes] = None, method: str = "GET",
          timeout: Optional[float] = None,
          ctx: Optional[R.RunContext] = None) -> bytes:
    """One signed request (single attempt).  The per-call timeout is
    capped by the query Deadline on the ambient RunContext, so every RPC
    a query makes derives from one query-level budget."""
    ctx = ctx if ctx is not None else R.current()
    timeout = ctx.deadline.cap(
        R.RPC_TIMEOUT_S if timeout is None else timeout)
    rule = F.apply_client(method, urlsplit(url).path)  # may raise/delay
    req = _signed_request(method, url, data)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        body = r.read()
    if rule is not None and rule.action == "partial":
        body = F.corrupt_page(body)
    return body


def _transient(e: BaseException) -> bool:
    """Retryable at the RPC layer: connection trouble and 5xx — never
    4xx (auth / bad payload are deterministic)."""
    if isinstance(e, urllib.error.HTTPError):
        return e.code in (500, 502, 503)
    return isinstance(e, (urllib.error.URLError, ConnectionError,
                          TimeoutError, HTTPException, OSError))


def _http_retry(url: str, data: Optional[bytes] = None,
                method: str = "GET", timeout: Optional[float] = None,
                ctx: Optional[R.RunContext] = None) -> bytes:
    """Idempotent RPC with policy-driven backoff (task submit / status /
    range / delete — the worker endpoints are all safely re-playable:
    submit overwrites, delete is idempotent, reads are pure)."""
    ctx = ctx if ctx is not None else R.current()

    def on_retry(attempt, e, delay):
        ctx.count("http_retries", url=url, error=type(e).__name__)

    return ctx.policy.call(
        lambda: _http(url, data, method, timeout, ctx),
        retryable=_transient, deadline=ctx.deadline, on_retry=on_retry)


class UpstreamFailed(Exception):
    """Producer task failed or its worker became unreachable."""


def _task_state(url: str, task_id: str,
                ctx: Optional[R.RunContext] = None) -> Optional[str]:
    """Best-effort status peek (used to tell a transient 500 from a
    genuinely FAILED task); None when the worker can't answer."""
    try:
        st = json.loads(_http(f"{url}/v1/task/{task_id}/status",
                              timeout=R.PROBE_TIMEOUT_S, ctx=ctx))
        return st.get("state")
    except R.DeadlineExceeded:
        raise
    except Exception:  # noqa: BLE001 — probe failures are expected here
        return None


def _probe(url: str, ctx: Optional[R.RunContext] = None) -> None:
    _http(f"{url}/v1/info", timeout=R.PROBE_TIMEOUT_S, ctx=ctx)


def _get_page(url: str, task_id: str, bucket: int, token: int,
              ctx: R.RunContext) -> Tuple[int, bytes, bool, str]:
    """One results GET -> (status, body, X-Complete, declared encoding).
    Goes around _http because the caller needs the status/headers, but
    hits the same fault choke point and signs the same way."""
    path = f"/v1/task/{task_id}/results/{bucket}/{token}"
    F.apply_client("GET", path)
    req = _signed_request("GET", url + path)
    with urllib.request.urlopen(
            req, timeout=ctx.deadline.cap(R.PAGE_TIMEOUT_S)) as r:
        status = r.status
        body = r.read()
        complete = r.headers.get("X-Complete") == "1"
        enc = r.headers.get(PAGE_ENC_HEADER, "")
    if status == 200 and body:
        # the PAGE pseudo-method counts DELIVERED pages only, so a
        # partial-transfer rule's nth is deterministic (503 polls and
        # empty bodies don't consume it)
        prule = F.client_plan().match("client", "PAGE", path)
        if prule is not None:
            if prule.action == "partial":
                body = F.corrupt_page(body)
            else:  # may raise: consumer fails AFTER the page exists
                F.apply_delivered_page(prule)
    return status, body, complete, enc


def pull_pages(url: str, task_id: str, bucket: int,
               timeout: Optional[float] = None, ack: bool = True,
               max_pages: Optional[int] = None,
               ctx: Optional[R.RunContext] = None,
               slot: Optional[list] = None) -> List[bytes]:
    """Streaming page pull with sequence tokens + acks (reference:
    HttpPageBufferClient GET /v1/task/{id}/results/{buffer}/{token} +
    .../acknowledge, server/TaskResource.java:244-307).  Pages are
    published as the producer finishes each split chunk, so consumers
    overlap with production (P7 pipelining); the token makes delivery
    at-least-once with client dedup, and the ack releases server memory.

    Robustness: each page is checksum-verified on receipt (a corrupt /
    truncated body is re-requested by token); transient 500s and
    connection trouble are absorbed by seeded backoff under the retry
    policy's attempt budget; worker death is decided by the circuit
    breaker, not a one-shot probe.  When `slot` (a mutable [url,
    task_id] pair) is given, the target is re-read each iteration, so a
    straggler hedge can transparently fail the pull over to the winning
    replica — attempts execute deterministically, so page K is
    identical across replicas and the token sequence stays valid."""
    ctx = ctx if ctx is not None else R.current()
    local = R.Deadline(R.PULL_TIMEOUT_S if timeout is None else timeout)
    backoff = ctx.policy.backoff()
    pages: List[bytes] = []
    token = 0
    errors_500 = 0

    def _restarted() -> bool:
        # task-granular restart (ctx.task_restarter, set by the
        # coordinator around its own pulls): offer the dead slot to the
        # restarter BEFORE escalating to UpstreamFailed.  On success the
        # slot is repointed at a fresh replica on a survivor; attempts
        # execute deterministically, so the already-consumed token
        # prefix is identical and the pull simply continues — one task
        # re-ran, not the wave.
        rs = getattr(ctx, "task_restarter", None)
        if rs is None or slot is None:
            return False
        try:
            ok = bool(rs(slot))
        except R.DeadlineExceeded:
            raise
        except Exception:  # noqa: BLE001 — a broken restart escalates
            ok = False
        if ok:
            backoff.reset()
        return ok

    while True:
        if slot is not None:
            url, task_id = slot[0], slot[1]
        try:
            status, body, complete, enc = _get_page(url, task_id, bucket,
                                                    token, ctx)
            if status == 204:  # producer complete, no more pages
                return pages
            if status == 200:
                # integrity check gated on the DECLARED page encoding
                # (X-Page-Encoding): PTPG frames verify magic+xxh64,
                # JSON (range-sample) pages must parse — a corrupt /
                # truncated transfer of EITHER kind is re-requested by
                # token instead of sniffing the magic and waving
                # non-PTPG bodies through unverified
                if not _page_ok(body, enc):
                    ctx.count("pages_retried", url=url, token=token)
                    backoff.sleep(local)
                    continue
                pages.append(body)
                token += 1
                errors_500 = 0
                backoff.reset()
                if max_pages is not None and len(pages) >= max_pages:
                    return pages
                if ack:  # only exclusive readers may release pages
                    try:  # frees producer-side memory; best effort
                        _http(f"{url}/v1/task/{task_id}/results/{bucket}/"
                              f"{token}/ack", timeout=R.ACK_TIMEOUT_S,
                              ctx=ctx)
                    except R.DeadlineExceeded:
                        raise
                    except Exception:
                        pass
                if complete:
                    return pages
                continue
        except R.DeadlineExceeded:
            raise
        except urllib.error.HTTPError as e:
            if e.code == 503:  # not produced yet — poll
                pass
            elif e.code == 404 and slot is not None:
                # slot read raced a hedge swap (url/tid repointed
                # between the two reads) — re-read and poll again
                pass
            elif e.code == 500:
                detail = e.read()[:300]
                if b"page already released" in detail:
                    # at-least-once bookkeeping says a task retry is the
                    # only fix — no point retrying the request
                    if _restarted():
                        errors_500 = 0
                        continue
                    raise UpstreamFailed(
                        f"task {task_id} on {url} failed: {detail!r}")
                # transient (flaky server / injected fault) vs genuine
                # task failure: the status endpoint knows
                if _task_state(url, task_id, ctx) == "FAILED":
                    if _restarted():
                        errors_500 = 0
                        continue
                    raise UpstreamFailed(
                        f"task {task_id} on {url} failed: {detail!r}")
                errors_500 += 1
                if errors_500 >= ctx.policy.max_attempts:
                    if _restarted():
                        errors_500 = 0
                        continue
                    raise UpstreamFailed(
                        f"task {task_id} on {url}: {errors_500} "
                        f"consecutive 500s: {detail!r}")
                ctx.count("http_retries", url=url, code=500)
            else:
                raise
        except (urllib.error.URLError, ConnectionError, HTTPException,
                OSError) as e:
            # transient connection trouble is absorbed by the poll loop;
            # the circuit breaker decides when the worker is really gone
            # (consecutive probe failures trip it — no one-shot verdicts)
            if not ctx.health.probe(url, lambda u: _probe(u, ctx)) \
                    and ctx.health.state(url) != "closed":
                ctx.count("workers_quarantined", url=url)
                if _restarted():
                    errors_500 = 0
                    continue
                raise UpstreamFailed(f"worker {url} unreachable: {e}")
            ctx.count("http_retries", url=url, error=type(e).__name__)
        ctx.deadline.check(f"pages from {task_id}@{url}")
        if local.expired():
            raise TimeoutError(f"pages from {task_id}@{url} timed out")
        backoff.sleep(local)


class _ClusterExecutor:
    """Runs one fragment over this process's table splits + pulled
    exchange inputs, partitions the output.

    Leaf fragments STREAM: the task executes split-chunk supersteps and
    publishes each chunk's partitioned output as a page the moment it is
    ready, so downstream tasks (already scheduled, all-at-once) overlap
    with production — P7 pipeline parallelism over DCN (reference:
    PartitionedOutputOperator filling OutputBuffer pages while consumers'
    ExchangeClients stream them)."""

    # target pages per task: enough to overlap, few enough to amortize
    PAGES_PER_TASK = 4

    def __init__(self, session, spec: TaskSpec, publish=None,
                 task_state=None, faults=None):
        self.session = session
        self.spec = spec
        # multi-host fusion: fault plan threaded through so the
        # dcn:COLLECTIVE choke point can fail this member BEFORE it
        # reports ready (parallel/faults.apply_dcn)
        self.faults = faults
        # publish(bucket, page, enc=...): the producer DECLARES each
        # page's encoding so receipt-time verification never has to
        # sniff bytes (see _page_ok)
        self.publish = publish or (lambda bucket, page, enc=PAGE_ENC_PTPG:
                                   None)
        self.task_state = task_state or {}
        # dynamic-filtering accounting for this task (folded into the
        # worker's /v1/info counters / the coordinator's QueryStats)
        self.df_counts: Dict[str, float] = {}
        self._df_summaries: Dict[str, dict] = {}
        self._df_pushed: set = set()
        # fragment fusion: does this task execute a fused super-fragment
        # (plan root with inline Exchange nodes) over the local mesh?
        self._fused_ndev = int(spec.properties.get("fused_ndev") or 0)
        # exchange-economics accounting (fragment fusion, observe/stats):
        # exchange_bytes_host counts page bytes PULLED for exchange
        # edges whose producer is not the result root (result delivery
        # is paid identically by both paths and is not an exchange);
        # exchange_bytes_collective is the fused program's trace-time
        # ICI estimate (parallel/dist_executor.DistExecutor).
        self.counters: Dict[str, int] = {}
        self._pulled_host: Dict[int, dict] = {}  # eid -> host columns

    def _count(self, key: str, n: int = 1) -> None:
        self.counters[key] = self.counters.get(key, 0) + int(n)

    def _exchange_batches(self):
        inputs = {}
        push_cfg = self.spec.properties.get("df_push") or {}
        push_eids = {cfg["eid"] for cfg in push_cfg.values()}
        # pull filter-producing BUILD inputs first, push their completed
        # summaries, and only then pull the rest — a probe-side producer
        # waiting on the side channel (dynamic_filtering_wait_ms) is
        # unblocked before this task asks it for pages
        ordered = sorted(self.spec.inputs,
                         key=lambda i: 0 if i["eid"] in push_eids else 1)
        for inp in ordered:
            merged, batch = self._pull_one(inp)
            self._pulled_host[inp["eid"]] = merged
            inputs[f"__exch_{inp['eid']}"] = batch
            for fid, cfg in push_cfg.items():
                if cfg["eid"] == inp["eid"] and fid not in self._df_pushed:
                    self._df_pushed.add(fid)
                    self._df_push(fid, cfg, merged)
        return inputs

    def _pull_one(self, inp):
        """Pull + merge one exchange input; returns (host columns
        {sym: (data, valid)}, device Batch)."""
        from presto_tpu.batch import Batch, column_from_numpy
        import jax.numpy as jnp

        # trace_detail=full: each exchange pull is its own span
        full = str(self.spec.properties.get(
            "trace_detail", "basic")).lower() == "full"
        pull_cm = TR.span(f"pull eid{inp['eid']}",
                          eid=inp["eid"], kind_=inp["kind"]) \
            if full else None
        if pull_cm is not None:
            pull_cm.__enter__()
        try:
            return self._pull_one_inner(inp)
        finally:
            if pull_cm is not None:
                pull_cm.__exit__(None, None, None)

    def _pull_one_inner(self, inp):
        from presto_tpu.batch import Batch, column_from_numpy
        import jax.numpy as jnp

        gang = self._fused_ndev \
            and int(self.spec.properties.get("gang_size") or 0) > 1
        if gang:
            # multi-host fused gang: every member ingests the IDENTICAL
            # full external input (producers feeding a fused gang write
            # one gather bucket) and shards it onto the global mesh
            # itself (dist_executor._put); pages are never acked — every
            # rank reads them, and the buffer expiry reaps the leftovers
            bucket, ups = 0, inp["upstreams"]
        elif inp["kind"] in ("repartition", "range"):
            # range: consumer shard w owns key range w (sample sort)
            bucket, ups = self.spec.windex, inp["upstreams"]
        elif inp["kind"] == "scatter":
            # producers hold identical replicated copies, round-robin
            # sliced into buckets; one producer is the source of truth
            bucket, ups = self.spec.windex, inp["upstreams"][:1]
        else:  # gather / broadcast
            bucket, ups = 0, inp["upstreams"]
        parts = []
        # broadcast buckets have MANY readers: acking would release
        # pages other consumers still need
        exclusive = inp["kind"] != "broadcast" and not gang
        for up in ups:
            # coordinator-side upstreams are mutable [url, tid]
            # slots shared with the hedge monitor, so the pull
            # follows a hedge winner mid-stream; worker-side specs
            # carry deserialized copies that never mutate
            for buf in pull_pages(up[0], up[1], bucket, ack=exclusive,
                                  slot=up):
                if buf:
                    if not inp.get("result_root"):
                        # bytes that crossed the host HTTP path for an
                        # inter-stage exchange (fragment-fusion metric;
                        # result delivery is excluded — both paths pay
                        # it identically)
                        self._count("exchange_bytes_host", len(buf))
                    parts.append(unpack_columns(buf))
        merged: Dict[str, tuple] = {}
        types = inp["types"]
        for name in types:
            datas = [p[name][0] for p in parts if name in p]
            vals = [p[name][1] for p in parts if name in p]
            if datas:
                data = np.concatenate(datas)
                if any(v is not None for v in vals):
                    valid = np.concatenate(
                        [v if v is not None
                         else np.ones(len(d), dtype=bool)
                         for v, d in zip(vals, datas)])
                else:
                    valid = None
            else:
                t = types[name]
                data = np.empty(0, dtype=object if t.is_string
                                else t.numpy_dtype())
                valid = None
            merged[name] = (data, valid)
        if self._fused_ndev:
            # the fused path device-places these itself, sharded or
            # replicated over the mesh (dist_executor._ext_*_batch) —
            # building a throwaway single-device Batch here would
            # upload every external input twice
            return merged, None
        cols = {}
        n = 0
        for name, (data, valid) in merged.items():
            c = column_from_numpy(data, types[name],
                                  valid if valid is not None else None)
            cols[name] = c
            n = len(data)
        return merged, Batch(cols, jnp.ones((n,), dtype=bool))

    # ---- dynamic filtering side channel ------------------------------
    def _df_push(self, fid: str, cfg: dict, merged) -> None:
        """Producer side: summarize this task's view of the build keys
        (complete for broadcast/gather inputs, one repartition bucket
        otherwise — consumers union the parts) and POST it to every
        probe-side task the coordinator routed at schedule time.
        Strictly best-effort: a failed delivery costs nothing."""
        from presto_tpu.exec import kernels as K

        entry = merged.get(cfg["sym"])
        if entry is None:
            return
        data, valid = entry
        data = np.asarray(data)
        if data.dtype == object or data.dtype.kind not in "iub":
            return
        vals = data if valid is None else data[np.asarray(valid, bool)]
        payload = plan_serde.dumps(
            {"fid": fid, "part": int(cfg.get("part", 0)),
             **K.rf_summary_host(vals)})
        for url, tid in cfg.get("targets") or []:
            try:
                _http(f"{url}/v1/task/{tid}/dynfilter", payload,
                      method="POST", timeout=R.ACK_TIMEOUT_S)
            except R.DeadlineExceeded:
                raise
            except Exception:
                pass  # undelivered filter == filter-free probe (today)

    def _df_receive(self) -> Dict[str, dict]:
        """Probe side: wait up to dynamic_filtering_wait_ms for every
        expected filter's parts, then union them into device summaries.
        Incomplete filters are dropped — the scan runs filter-free, so a
        slow or crashed build worker can never stall the probe beyond
        the budget (0 by default: never wait at all)."""
        from presto_tpu.exec import kernels as K

        expect = self.spec.properties.get("df_expect") or {}
        if not expect:
            return {}
        budget_s = float(self.spec.properties.get(
            "dynamic_filtering_wait_ms") or 0) / 1000.0
        ev = self.task_state.get("df_event")
        store = self.task_state.get("dynfilters")

        def complete():
            return all(len((store or {}).get(fid, {})) >= int(n)
                       for fid, n in expect.items())

        t0 = time.monotonic()
        if ev is not None and store is not None and budget_s > 0:
            while not complete():
                rem = budget_s - (time.monotonic() - t0)
                if rem <= 0:
                    break
                ev.clear()
                if complete():  # re-check after clear: no lost wakeup
                    break
                ev.wait(rem)
            waited = (time.monotonic() - t0) * 1000.0
            self.df_counts["df_wait_ms"] = round(
                self.df_counts.get("df_wait_ms", 0.0) + waited, 1)
        out = {}
        for fid, n in expect.items():
            got = (store or {}).get(fid, {})
            if len(got) < int(n):
                continue  # incomplete: best-effort degrade
            merged = K.rf_union_host(list(got.values()))
            if merged is None:
                continue
            s = K.rf_host_to_device(merged)
            if s is not None:
                out[fid] = s
        return out

    def _scan_tables(self, root):
        from presto_tpu.plan import nodes as P

        out = []

        def walk(n):
            if isinstance(n, P.TableScan) \
                    and not n.table.startswith("__exch_"):
                out.append(n.table)
            for f in dataclasses.fields(n):
                v = getattr(n, f.name)
                if isinstance(v, P.PlanNode):
                    walk(v)
                elif isinstance(v, list):
                    for x in v:
                        if isinstance(x, P.PlanNode):
                            walk(x)
        walk(root)
        return list(dict.fromkeys(out))

    def _exec_once(self, root, exch, split_subset):
        """One superstep: execute the fragment with the given split
        subset per table (None = this worker's full share); returns host
        columns {sym: (data, valid)}."""
        from presto_tpu.batch import Batch, column_from_numpy
        from presto_tpu.exec.compiler import EvalContext
        from presto_tpu.exec.executor import Executor
        from presto_tpu.plan import nodes as P
        import jax
        import jax.numpy as jnp

        spec = self.spec

        kind_of_eid = {inp["eid"]: inp["kind"] for inp in self.spec.inputs}

        class FragmentExecutor(Executor):
            # split-subset scans are not whole tables: the index join's
            # natural-order layout assumption does not hold here
            allow_index_join = False

            def _rf_build_complete(ex_self, node) -> bool:
                """This task sees its SPLIT of every scanned table and
                its BUCKET of every repartition exchange — both partial
                key sets.  Only builds fed entirely by broadcast/gather
                exchange buffers (or Values) are complete here; partial
                builds reach consumers through the coordinator-routed
                side channel instead, which unions the buckets."""
                def complete(n):
                    if isinstance(n, P.TableScan):
                        if n.table.startswith("__exch_"):
                            eid = int(n.table[len("__exch_"):])
                            return kind_of_eid.get(eid) in ("broadcast",
                                                            "gather")
                        return False  # split-local rows
                    if isinstance(n, P.Values):
                        return True
                    srcs = n.sources
                    return bool(srcs) and all(complete(s) for s in srcs)

                return complete(node.right)

            def _rf_mask_pays(ex_self, node=None) -> bool:
                # a cluster task does not ship a pruned row
                return True

            def _exec_tablescan(ex_self, node: P.TableScan) -> Batch:
                if node.table in exch:
                    b = exch[node.table]
                    # remap symbols if the scan renames
                    cols = {s: b.columns[c]
                            for s, c in node.assignments.items()}
                    return Batch(cols, b.sel)
                table = ex_self.session.catalog.get(node.table)
                if split_subset is not None \
                        and node.table in split_subset:
                    mine = split_subset[node.table]
                else:
                    ranges = table.splits(spec.nworkers)
                    mine = [r for i, r in enumerate(ranges)
                            if i % spec.nworkers == spec.windex]
                needed = list(dict.fromkeys(node.assignments.values()))
                datas = [table.read(needed, split=r) for r in mine]
                cols = {}
                n = 0
                for sym, cname in node.assignments.items():
                    parts = [d[cname] for d in datas]
                    arr = np.concatenate(parts) if parts else np.empty(
                        0, dtype=object if node.types[sym].is_string
                        else node.types[sym].numpy_dtype())
                    cols[sym] = column_from_numpy(arr, node.types[sym])
                    n = len(arr)
                # dynamic filtering: locally produced + side-channel
                # injected summaries prune this split's rows before the
                # fragment's operators see them
                return ex_self._rf_apply(
                    node, Batch(cols, jnp.ones((n,), dtype=bool)))

        ex = FragmentExecutor(self.session)
        ex.ctx = EvalContext(dict(self.spec.scalar_results))
        if self._df_summaries:
            # side-channel filters (complete unions only) consumed by
            # this fragment's probe scans; locally produced filters are
            # registered by the executor's own join path
            ex.rf_inject(self._df_summaries)
        out = ex.exec_node(root)
        for k, v in ex.sort_stats.items():
            if k.startswith("df_") and v:
                self.df_counts[k] = self.df_counts.get(k, 0) + v
            elif v and (k.startswith("agg_strategy::")
                        or k in ("partial_aggs_bypassed",
                                 "partial_aggs_reenabled")):
                # adaptive-agg flip decisions + strategy counts ride the
                # task status back to the coordinator (plan/agg_strategy)
                self._count(k, v)
            elif k == "degradation_tier" and v:
                # spill tier is a high-water mark across supersteps
                self.counters[k] = max(int(self.counters.get(k, 0)),
                                       int(v))
            elif v and k.startswith("spill_"):
                # spill-tier activity on worker fragments rides the task
                # status back to the coordinator (exec/spill_exec.py)
                self._count(k, v)
            elif k == "partial_agg_ratio" and v:
                self.counters[k] = round(float(v), 4)  # gauge, not a sum
        return self._fetch_out_cols(out)

    def _fetch_out_cols(self, out):
        """Device Batch -> host {sym: (data, valid)} of live rows, with
        dictionary decode — ONE device_get for the whole batch
        (per-column fetches pay a full RPC round trip each on remote
        XLA clients; see batch.to_numpy)."""
        import jax

        pulled = jax.device_get(
            (out.sel, {sym: (out.columns[sym].data, out.columns[sym].valid)
                       for sym in self.spec.out_symbols}))
        sel, datas = pulled
        live = np.flatnonzero(np.asarray(sel))
        cols: Dict[str, tuple] = {}
        for sym in self.spec.out_symbols:
            c = out.columns[sym]
            data, valid = datas[sym]
            data = np.asarray(data)[live]
            if c.dictionary is not None:
                data = c.dictionary.values[
                    np.clip(data, 0, max(len(c.dictionary) - 1, 0))]
            valid = None if valid is None else np.asarray(valid)[live]
            cols[sym] = (data, valid)
        return cols

    # ---- multi-host gang barrier (cross-host fusion) -----------------
    def _gang_props(self):
        p = self.spec.properties
        return (str(p.get("gang_epoch") or ""), str(p.get("gang_home")
                or ""), int(p.get("gang_rank") or 0),
                int(p.get("gang_size") or 0))

    def _gang_barrier(self) -> None:
        """Report this rank ready on the gang's HTTP barrier (rank 0's
        worker, POST /v1/gang) and poll until admitted.  The barrier is
        the LAST exit before jax collectives: a member that died or hit
        the dcn:COLLECTIVE fault simply never reports, this rank times
        out with a clean task FAILURE, and the coordinator's was_fused
        fallback reruns the attempt unfused over HTTP."""
        epoch, home, rank, size = self._gang_props()
        if self.faults is not None:
            F.apply_dcn(self.faults, self.spec.task_id)
        ctx = R.current()
        local = R.Deadline(R.GANG_BARRIER_TIMEOUT_S)
        backoff = ctx.policy.backoff()
        payload = json.dumps({"op": "ready", "epoch": epoch,
                              "rank": rank, "size": size}).encode()
        while True:
            try:
                resp = json.loads(_http(
                    f"{home}/v1/gang", payload, method="POST",
                    timeout=ctx.deadline.cap(R.ACK_TIMEOUT_S)))
                if resp.get("go"):
                    return
            except R.DeadlineExceeded:
                raise
            except Exception:  # noqa: BLE001 — home may lag our start
                pass
            ctx.deadline.check(f"gang {epoch} barrier")
            if local.expired():
                raise TimeoutError(
                    f"gang {epoch} rank {rank}: barrier timed out "
                    "(mesh member missing or collective lane faulted)")
            backoff.sleep(local)

    def _gang_done(self) -> None:
        """Best-effort done-report so the board retires the epoch and
        admits the next gang without waiting out GANG_EXEC_TIMEOUT_S."""
        epoch, home, rank, _ = self._gang_props()
        try:
            _http(f"{home}/v1/gang",
                  json.dumps({"op": "done", "epoch": epoch,
                              "rank": rank}).encode(),
                  method="POST", timeout=R.ACK_TIMEOUT_S)
        except Exception:  # noqa: BLE001 — eviction deadline covers us
            pass

    def _exec_fused(self, root):
        """Fragment fusion: execute a fused super-fragment (inline
        Exchange nodes) as ONE shard_map program over this process's
        mesh (parallel/dist_executor.run_fused_fragment).  A tripped
        guard (exchange capacity overflow / static-shape violation)
        raises FusedGuardTripped -> task FAILED -> the coordinator
        retries on the per-fragment HTTP path."""
        from presto_tpu.parallel import dist_executor as DX

        ext = {inp["eid"]: {"kind": inp["kind"],
                            "cols": self._pulled_host[inp["eid"]]}
               for inp in self.spec.inputs}
        out, guard, counters = DX.run_fused_fragment(
            self.session, root, self._fused_ndev, ext,
            dict(self.spec.scalar_results), self.spec.fragment,
            profile=bool(self.spec.properties.get("profile_fragment")))
        if guard:
            raise DX.FusedGuardTripped(
                "fused super-fragment guard tripped (capacity overflow "
                "or static assumption violated)")
        self._count("tasks_fused")
        self._count("fragments_fused",
                    int(self.spec.properties.get("fragments_fused") or 0))
        self._count("exchange_bytes_collective",
                    int(counters.get("exchange_bytes_collective", 0)))
        self._count("exchange_bytes_sketch",
                    int(counters.get("exchange_bytes_sketch", 0)))
        for k in ("xla_flops", "xla_bytes_accessed"):
            if counters.get(k):  # EXPLAIN ANALYZE cost attribution
                self.counters[k] = int(counters[k])
        for k, v in counters.items():
            if k.startswith("df_") and v:
                self.df_counts[k] = self.df_counts.get(k, 0) + v
        if int(self.spec.properties.get("gang_size") or 0) > 1:
            # collective bytes that crossed process boundaries ride the
            # data-center network, not ICI — mirrored into the dcn
            # counter so QueryStats can tell the lanes apart
            self._count("exchange_bytes_dcn",
                        int(counters.get("exchange_bytes_collective", 0)))
            return self._fetch_out_cols_local(out)
        return self._fetch_out_cols(out)

    def _fetch_out_cols_local(self, out):
        """Gang variant of _fetch_out_cols: on a multi-process mesh the
        output arrays are GLOBAL — only this process's shards are
        addressable, so each rank fetches its own rows.  A replicated
        output exists in full on every rank; rank 0 publishes it and
        the other ranks publish zero rows, so the downstream union of
        gang buckets is exact either way."""
        from presto_tpu.parallel import dist_executor as DX

        def host(a):
            if getattr(a.sharding, "is_fully_replicated", False):
                return np.asarray(a.addressable_shards[0].data), True
            return DX.local_shard_rows(a), False

        rank = int(self.spec.properties.get("gang_rank") or 0)
        sel, sel_repl = host(out.sel)
        live = np.flatnonzero(np.asarray(sel))
        cols: Dict[str, tuple] = {}
        for sym in self.spec.out_symbols:
            c = out.columns[sym]
            data = host(c.data)[0][live]
            if c.dictionary is not None:
                data = c.dictionary.values[
                    np.clip(data, 0, max(len(c.dictionary) - 1, 0))]
            valid = None if c.valid is None else host(c.valid)[0][live]
            if sel_repl and rank != 0:
                data = data[:0]
                valid = None if valid is None else valid[:0]
            cols[sym] = (data, valid)
        return cols

    def _profile_cost(self, root) -> None:
        """EXPLAIN ANALYZE only: AOT-lower a STATIC trace of this cut
        fragment over the worker's scan + exchange batches and read
        XLA's cost analysis off the compiled program — the
        compiler-sourced FLOPs/bytes attribution the eager superstep
        execution can't provide.  Strictly best-effort: a fragment the
        static executor can't bound simply reports no cost block."""
        import jax.numpy as jnp

        from presto_tpu.batch import Batch, column_from_numpy
        from presto_tpu.exec.executor import Executor
        from presto_tpu.observe import profile as PR
        from presto_tpu.plan import nodes as P

        try:
            spec = self.spec
            scan_nodes: List[P.PlanNode] = []

            def walk(n):
                if isinstance(n, P.TableScan):
                    scan_nodes.append(n)
                for f in dataclasses.fields(n):
                    v = getattr(n, f.name)
                    if isinstance(v, P.PlanNode):
                        walk(v)
                    elif isinstance(v, list):
                        for x in v:
                            if isinstance(x, P.PlanNode):
                                walk(x)

            walk(root)
            exch = getattr(self, "_exch", {})
            batches = []
            for node in scan_nodes:
                if node.table in exch:
                    b = exch[node.table]
                    cols = {s: b.columns[c]
                            for s, c in node.assignments.items()}
                    batches.append(Batch(cols, b.sel))
                    continue
                table = self.session.catalog.get(node.table)
                ranges = table.splits(spec.nworkers)
                mine = [r for i, r in enumerate(ranges)
                        if i % spec.nworkers == spec.windex]
                needed = list(dict.fromkeys(node.assignments.values()))
                datas = [table.read(needed, split=r) for r in mine]
                cols = {}
                n = 0
                for sym, cname in node.assignments.items():
                    parts = [d[cname] for d in datas]
                    arr = np.concatenate(parts) if parts else np.empty(
                        0, dtype=object if node.types[sym].is_string
                        else node.types[sym].numpy_dtype())
                    cols[sym] = column_from_numpy(arr, node.types[sym])
                    n = len(arr)
                batches.append(Batch(cols, jnp.ones((n,), dtype=bool)))

            def fn(bs):
                ex = Executor(self.session, static=True,
                              scan_inputs={id(nd): b for nd, b
                                           in zip(scan_nodes, bs)})
                ex.allow_index_join = False
                ex.ctx.scalar_results = dict(spec.scalar_results)
                out = ex.exec_node(root)
                if ex.guards:
                    g = jnp.any(jnp.stack(
                        [jnp.asarray(x) for x in ex.guards]))
                else:
                    g = jnp.asarray(False)
                return out, g

            jitted = CC.build_jit(fn, example=(batches,))
            cost = PR.executable_cost(jitted)
            if cost:
                self.counters["xla_flops"] = int(cost.get("flops", 0))
                self.counters["xla_bytes_accessed"] = int(
                    cost.get("bytes_accessed", 0))
        except Exception:  # noqa: BLE001 — diagnostics must not fail tasks
            pass

    def _publish_cols(self, cols):
        """Partition one superstep's output and publish a page per
        destination bucket."""
        nb = self.spec.out_buckets
        if self.spec.out_kind == "repartition" and nb > 1:
            bucket = hash_partition(cols, self.spec.out_keys, nb)
            for b in range(nb):
                idx = np.flatnonzero(bucket == b)
                sub = {k: (d[idx], None if v is None else v[idx])
                       for k, (d, v) in cols.items()}
                self.publish(b, pack_columns(sub))
        elif self.spec.out_kind == "scatter" and nb > 1:
            # replicated -> sharded: disjoint round-robin slices (the ICI
            # "masked to one shard" semantics re-established over DCN)
            for b in range(nb):
                sub = {k: (d[b::nb], None if v is None else v[b::nb])
                       for k, (d, v) in cols.items()}
                self.publish(b, pack_columns(sub))
        else:  # gather / broadcast: one bucket everyone reads
            self.publish(0, pack_columns(cols))

    def _publish_range(self, cols):
        """Sample-sort range partitioning (P11 over DCN): publish a key
        sample on the side channel (bucket = out_buckets), wait for the
        coordinator's global boundaries, then bucket rows so consumer
        shard i holds exactly key-range i.  Equal keys share a bucket
        (side='left' on exact boundary values), so secondary sort keys
        never interleave across buckets."""
        nb = self.spec.out_buckets
        key_sym, asc, nulls_first = self.spec.out_keys[0]
        data, valid = cols[key_sym]
        live = np.ones(len(data), dtype=bool) if valid is None else valid
        sample_vals = data[live][:: max(1, int(np.sum(live)) // 256)][:256]
        self.publish(nb, plan_serde.dumps(sample_vals.tolist()),
                     enc=PAGE_ENC_JSON)
        if not self.task_state.get("range_event", threading.Event()) \
                .wait(timeout=R.RANGE_TIMEOUT_S):
            raise TimeoutError("range boundaries never arrived")
        boundaries = self.task_state["range_boundaries"]
        if len(boundaries):
            pos = np.searchsorted(boundaries, data, side="left")
            if not asc:
                pos = (len(boundaries) - pos)
        else:
            pos = np.zeros(len(data), dtype=np.int64)
        pos = np.clip(pos, 0, nb - 1)
        nf = (not asc) if nulls_first is None else nulls_first
        if valid is not None:
            pos = np.where(valid, pos, 0 if nf else nb - 1)
        for b in range(nb):
            idx = np.flatnonzero(pos == b)
            sub = {k: (d[idx], None if v is None else v[idx])
                   for k, (d, v) in cols.items()}
            self.publish(b, pack_columns(sub))

    def run(self) -> None:
        root = plan_serde.loads(self.spec.fragment)
        self._run_root(root)
        if self.spec.properties.get("profile_fragment") \
                and not self._fused_ndev:
            # EXPLAIN ANALYZE attribution for CUT fragments: the normal
            # execution above ran eagerly (host supersteps), so the XLA
            # cost analysis comes from a diagnostic static trace of the
            # same fragment over this worker's batches — an extra
            # compile paid ONLY when profiling was requested
            self._profile_cost(root)

    def _run_root(self, root) -> None:
        if self._fused_ndev:
            # fused super-fragment: pull the (rare) non-fused external
            # inputs, then run the whole pipeline as one mesh program.
            # The dynamic-filter side channel is skipped — filters whose
            # producer join lives inside the fused trace are produced
            # and applied IN-trace by the executor itself.
            self._exchange_batches()
            gang = int(self.spec.properties.get("gang_size") or 0) > 1
            if gang:
                # cross-host gang: all inputs staged, all ranks meet at
                # the HTTP barrier before the first collective — a rank
                # that never arrives fails THIS rank cleanly (timeout)
                # instead of hanging inside gloo/ICI
                self._gang_barrier()
            try:
                cols = self._exec_fused(root)
            finally:
                if gang:
                    self._gang_done()
            if self.spec.out_kind == "range":
                self._publish_range(cols)
            else:
                self._publish_cols(cols)
            return
        # dynamic filtering: bounded wait for side-channel summaries
        # BEFORE any scan executes (wait_ms=0 skips straight through)
        self._df_summaries = self._df_receive()
        exch = self._exchange_batches()
        self._exch = exch  # kept for the EXPLAIN ANALYZE cost trace
        scan_tables = self._scan_tables(root)

        if self.spec.out_kind == "range":
            self._publish_range(self._exec_once(root, exch, None))
            return
        if len(scan_tables) == 1 and self.spec.nworkers >= 1:
            # leaf fragment: stream split-chunk supersteps as pages
            table = self.session.catalog.get(scan_tables[0])
            ranges = table.splits(self.spec.nworkers * self.PAGES_PER_TASK)
            mine = [r for i, r in enumerate(ranges)
                    if i % self.spec.nworkers == self.spec.windex]
            groups = [mine[i::self.PAGES_PER_TASK]
                      for i in range(self.PAGES_PER_TASK)]
            groups = [g for g in groups if g] or [[]]
            for g in groups:
                cols = self._exec_once(root, exch, {scan_tables[0]: g})
                self._publish_cols(cols)
            return
        self._publish_cols(self._exec_once(root, exch, None))


def _warm_task(session, spec: "TaskSpec") -> None:
    """Compile-ahead analog for cluster workers (exec/compile_cache.py):
    at task-ACCEPT time, deserialize the fragment and pre-read this
    worker's table splits (generation / disk decode into the host-side
    caches, where the per-table locks make the later executor read a
    hit).  For a task whose exchange inputs are still streaming in,
    this work previously started at FIRST-PAGE time — serially behind
    the wait.  Runs on the bounded compile-ahead pool; best-effort."""
    from presto_tpu.plan import nodes as P

    root = plan_serde.loads(spec.fragment)
    scans: List[P.TableScan] = []

    def walk(n):
        if isinstance(n, P.TableScan) \
                and not n.table.startswith("__exch_"):
            scans.append(n)
        for f in dataclasses.fields(n):
            v = getattr(n, f.name)
            if isinstance(v, P.PlanNode):
                walk(v)
            elif isinstance(v, list):
                for x in v:
                    if isinstance(x, P.PlanNode):
                        walk(x)

    walk(root)
    for node in scans:
        table = session.catalog.get(node.table)
        ranges = table.splits(spec.nworkers)
        mine = [r for i, r in enumerate(ranges)
                if i % spec.nworkers == spec.windex]
        needed = list(dict.fromkeys(node.assignments.values()))
        for r in mine:
            table.read(needed, split=r)


# ---------------------------------------------------------------------------
# worker server (the worker JVM analog)
# ---------------------------------------------------------------------------


def make_catalog(spec: str):
    """Catalog from a spec string shippable to worker processes:
    'tpch:<sf>[:<cache_dir>]' | 'tpcds:<sf>[:<cache_dir>]' | 'empty'."""
    from presto_tpu.catalog import Catalog, tpch_catalog

    if spec == "empty":
        return Catalog()
    kind, _, rest = spec.partition(":")
    sf, _, cache = rest.partition(":")
    if kind == "tpch":
        return tpch_catalog(float(sf), cache or None)
    if kind == "tpcds":
        from presto_tpu.catalog import tpcds_catalog

        return tpcds_catalog(float(sf), cache or None)
    raise ValueError(f"unknown catalog spec {spec}")


class _GangBoard:
    """Barrier-epoch board a gang's rank-0 worker serves via POST
    /v1/gang (round 21 multi-host fusion).  Every gang member reports
    ready{epoch, rank, size} and polls until {"go": true}; the board
    admits ONE gang at a time — a multi-controller jax program must
    execute the same collectives in the same order on every process, so
    concurrent gangs are serialized here, oldest-fully-ready first.  An
    epoch retires when all its ranks report done; a waiting epoch whose
    barrier deadline passes (a member died or the dcn:COLLECTIVE fault
    fired before its ready report) is evicted so later gangs admit, and
    an ADMITTED epoch is evicted after GANG_EXEC_TIMEOUT_S (a member
    died mid-collective without reporting done)."""

    def __init__(self):
        self._gangs: Dict[str, dict] = {}
        self._order: List[str] = []
        self._active: Optional[str] = None
        self._lock = threading.Lock()

    def _expire(self) -> None:
        if self._active is not None:
            g = self._gangs.get(self._active)
            if g is None or g["exec_deadline"].expired():
                self._gangs.pop(self._active, None)
                self._active = None
        for e in [e for e in self._order if e in self._gangs
                  and e != self._active
                  and self._gangs[e]["barrier_deadline"].expired()]:
            self._gangs.pop(e, None)
        self._order = [e for e in self._order if e in self._gangs]

    def ready(self, epoch: str, rank: int, size: int) -> dict:
        with self._lock:
            g = self._gangs.get(epoch)
            if g is None:
                g = self._gangs[epoch] = {
                    "size": max(int(size), 1), "ready": set(),
                    "done": set(),
                    "barrier_deadline":
                        R.Deadline(R.GANG_BARRIER_TIMEOUT_S),
                    "exec_deadline": R.Deadline(R.GANG_EXEC_TIMEOUT_S)}
                self._order.append(epoch)
            g["ready"].add(int(rank))
            self._expire()
            if self._active is None:
                for e in self._order:
                    gg = self._gangs[e]
                    if len(gg["ready"]) >= gg["size"]:
                        self._active = e
                        gg["exec_deadline"] = \
                            R.Deadline(R.GANG_EXEC_TIMEOUT_S)
                        break
            go = self._active == epoch
            first = go and not g.get("announced")
            if first:
                g["announced"] = True
            return {"go": go, "admitted": first}

    def done(self, epoch: str, rank: int) -> dict:
        with self._lock:
            g = self._gangs.get(epoch)
            if g is not None:
                g["done"].add(int(rank))
                if len(g["done"]) >= g["size"]:
                    self._gangs.pop(epoch, None)
                    self._order = [e for e in self._order
                                   if e in self._gangs]
                    if self._active == epoch:
                        self._active = None
            return {"ok": True}


class WorkerServer:
    """One worker process: accepts tasks, executes fragments, serves
    result buffers (reference: SqlTaskManager + TaskResource)."""

    def __init__(self, catalog_spec: str, host: str = "127.0.0.1",
                 port: int = 0, secret: Optional[bytes] = None,
                 faults: Optional["F.FaultPlan"] = None,
                 mesh_devices: Optional[int] = None,
                 lease_board=None, dist_spec: Optional[dict] = None):
        import presto_tpu

        # scripted failures for THIS worker (tests pass a plan per
        # server; subprocess workers inherit PRESTO_TPU_FAULTS)
        self.faults = faults if faults is not None else F.FaultPlan.from_env()
        self.crashed = False
        # in-process fleets hand the worker the shared SlotLeaseBoard so
        # reap_expired can release a reaped orphan's still-held lease
        # tag (fleet.SlotLeaseBoard.reclaim_task) the moment the task
        # dies, instead of waiting for the directory's dead-coordinator
        # sweep.  Cross-process workers leave this None — the sweep
        # remains the backstop there.
        self.lease_board = lease_board
        # fragment fusion: a worker that EXCLUSIVELY owns a local device
        # mesh declares it (operator-granted: PRESTO_TPU_WORKER_MESH or
        # the constructor/--mesh arg, never inferred — an in-process
        # worker shares its process's devices with the coordinator and
        # other workers and must not claim them).  The coordinator
        # schedules fused super-fragments onto declared meshes only.
        if mesh_devices is None:
            mesh_devices = int(
                os.environ.get("PRESTO_TPU_WORKER_MESH", "0") or 0)
        self.mesh_devices = max(int(mesh_devices), 0)
        import socket as _socket

        self.mesh_id = f"{_socket.gethostname()}:{os.getpid()}"
        # multi-host collective data plane (round 21): a worker whose
        # process joined a jax.distributed mesh (parallel/mesh.py,
        # --distributed-coordinator/--process-id or PRESTO_TPU_MULTIHOST)
        # declares its process identity via /v1/info; the coordinator
        # assembles a gang from a COMPLETE declared process set.  Chaos
        # tests pass dist_spec explicitly to declare a fake identity
        # without touching the jax backend — the scripted faults then
        # exercise gang scheduling, the barrier, and the HTTP fallback
        # deterministically.
        from presto_tpu.parallel import mesh as MH

        if dist_spec is not None:
            self.dist_spec: Optional[dict] = dict(dist_spec)
        elif MH.is_multihost():
            self.dist_spec = MH.multihost_spec()
        else:
            self.dist_spec = None
        # gang barrier-epoch board (rank 0's worker is the gang home)
        self.gang_board = _GangBoard()
        self.secret = secret if secret is not None else cluster_secret()
        if self.secret is None and not _is_loopback(host):
            raise ValueError(
                f"refusing to bind non-loopback host {host!r} without a "
                f"cluster secret: task payloads are executable; set "
                f"{_SECRET_ENV} or pass secret=")
        self.session = presto_tpu.connect(make_catalog(catalog_spec))
        self.tasks: Dict[str, dict] = {}
        # per-worker work accounting (served via /v1/info): `executed`
        # counts fragment executions, `replayed` counts durable-page
        # replays — the per-bucket-retry test's evidence that survivors
        # re-execute ONLY the victim's work
        self.counters = {"executed": 0, "replayed": 0, "tasks_reaped": 0,
                         "buffered_bytes": 0, "peak_buffered_bytes": 0,
                         # compile economics (exec/compile_cache.py):
                         # per-task builds/hits aggregate here and are
                         # served via /v1/info like the work counters
                         "compiles": 0, "compile_ms": 0.0,
                         "compile_cache_hits": 0,
                         "compile_ahead_hits": 0, "tasks_warmed": 0,
                         # dynamic filtering (plan/runtime_filters.py):
                         # per-task filter activity aggregates here so
                         # tests/operators can see cluster-wide pruning
                         "df_filters_produced": 0, "df_filters_applied": 0,
                         "df_filters_declined": 0,
                         "df_rows_pruned": 0, "df_wait_ms": 0.0,
                         # fragment fusion (plan/distribute.py): fused
                         # super-fragment tasks executed here, original
                         # fragments they absorbed, exchange page bytes
                         # this worker pulled over HTTP, and the fused
                         # programs' trace-time ICI byte estimate
                         "tasks_fused": 0, "fragments_fused": 0,
                         "exchange_bytes_host": 0,
                         "exchange_bytes_collective": 0,
                         "exchange_bytes_sketch": 0,
                         # multi-host lane: trace-time bytes the fused
                         # program moved over the cross-process (DCN)
                         # fabric, and gang barrier rendezvous served
                         "exchange_bytes_dcn": 0, "gangs_admitted": 0}
        self.lock = threading.Lock()
        self.exec_lock = threading.Lock()
        handler = _make_worker_handler(self)
        self.httpd = ThreadingHTTPServer((host, port), handler)
        self.port = self.httpd.server_address[1]
        self.host = host

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def start(self):
        t = threading.Thread(target=self.httpd.serve_forever, daemon=True)
        t.start()
        return self

    def serve_forever(self):
        self.httpd.serve_forever()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()

    def reap_expired(self) -> int:
        """Orphan-task sweep: drop every resident task whose query
        deadline (plus grace) has passed without the coordinator's
        DELETE — the crash-recovery path for a dead coordinator's
        tasks, freeing their page buffers exactly like an explicit
        DELETE.  Runs opportunistically on task submission and /v1/info
        so an idle worker still converges when probed."""
        now = time.monotonic()
        reaped = 0
        freed = []
        with self.lock:
            for tid in [t for t, e in self.tasks.items()
                        if e.get("expires_at") is not None
                        and now > e["expires_at"]]:
                gone = self.tasks.pop(tid)
                self.counters["buffered_bytes"] -= sum(
                    len(p[0]) for ps in gone["pages"].values()
                    for p in ps if p is not None)
                self.counters["tasks_reaped"] += 1
                reaped += 1
                if gone.get("lease_coord"):
                    freed.append(gone["lease_coord"])
        # release the reaped tasks' slot-lease tags (the coordinator
        # that POSTed them is dead and will never DELETE): reap-freed
        # and sweep-freed leases both count as reclaimed, and a tag the
        # sweep already freed no-ops — tasks_reaped and leases_reclaimed
        # agree in the coordinator-crash chaos test
        if self.lease_board is not None:
            for coord in freed:
                self.lease_board.reclaim_task(coord, self.url)
        return reaped

    def simulate_crash(self):
        """The `crash` fault action: a subprocess worker dies for real;
        an in-process worker (chaos tests) stops serving, so every later
        request observes connection-refused — the same failure the
        coordinator sees when an OS process is killed."""
        self.crashed = True
        if os.environ.get("PRESTO_TPU_WORKER_PROC") == "1":
            os._exit(1)
        threading.Thread(target=self.stop, daemon=True).start()

    def submit(self, spec: TaskSpec, trace_ctx: Optional[str] = None):
        # a coordinator that dies mid-query never DELETEs its tasks;
        # each task therefore carries its query deadline, and the
        # sweep (reap_expired) drops residents past deadline + grace
        deadline_s = spec.properties.get("deadline_s")
        expires_at = None if deadline_s is None else \
            time.monotonic() + float(deadline_s) + ORPHAN_GRACE_S
        with self.lock:
            # pages: bucket -> list of page bytes (None = acked/pruned);
            # complete flips when the producer will publish no more
            task = {"state": "RUNNING", "error": None,
                    "pages": {}, "complete": False,
                    "range_boundaries": None,
                    "range_event": threading.Event(),
                    "expires_at": expires_at,
                    # the coordinator holding this task's slot lease
                    # (fleet fleets only): reap_expired releases the
                    # tag when it reaps the task
                    "lease_coord": spec.properties.get("lease_coord"),
                    # dynamic-filter side channel: fid -> {part: payload}
                    "dynfilters": {}, "df_event": threading.Event()}
            self.tasks[spec.task_id] = task
        # tracing (observe/trace.py): the task records its spans on a
        # worker-side tracer seeded from the X-Presto-Trace header, so
        # the coordinator can merge them into ONE query trace (they ride
        # the task status payload).  A missing/dropped header degrades
        # to a worker-LOCAL trace — fresh trace id, still well-formed —
        # which the coordinator's merge then refuses and counts.
        wtrace_id, wparent = TR.from_wire(trace_ctx)

        # task-accept warm (compile-ahead analog): a task that will wait
        # on exchange pages pre-reads its scan splits on the bounded
        # pool NOW instead of at first-page time.  Same kill switches
        # as compile-ahead; never affects results.
        if spec.inputs and not getattr(spec, "replay", False) \
                and not spec.properties.get("fused_ndev") \
                and CC.ahead_enabled(self.session):
            if CC.submit(lambda: _warm_task(self.session, spec)):
                with self.lock:
                    self.counters["tasks_warmed"] += 1

        key_dir = None
        if getattr(spec, "durable_dir", None) and \
                getattr(spec, "durable_key", None):
            key_dir = os.path.join(spec.durable_dir, spec.durable_key)
        attempt_dir = os.path.join(key_dir, f"a{spec.attempt}") \
            if key_dir else None

        def publish(bucket: int, page: bytes, enc: str = PAGE_ENC_PTPG):
            with self.lock:
                task["pages"].setdefault(bucket, []).append((page, enc))
                seq = len(task["pages"][bucket]) - 1
                self.counters["buffered_bytes"] += len(page)
                self.counters["peak_buffered_bytes"] = max(
                    self.counters["peak_buffered_bytes"],
                    self.counters["buffered_bytes"])
            if attempt_dir is not None:
                # durable copy survives acks and task DELETE; tmp+rename
                # so a torn write never reads as a page; the declared
                # encoding rides in the file name
                bdir = os.path.join(attempt_dir, f"b{bucket}")
                os.makedirs(bdir, exist_ok=True)
                # the tmp name is the task's own: two byte-identical
                # fragments of one query (q18 scans lineitem twice)
                # share a durable key and publish the same page here
                tmp = os.path.join(bdir, f".tmp{seq}.{spec.task_id}")
                with open(tmp, "wb") as f:
                    f.write(page)
                os.replace(tmp,
                           os.path.join(bdir, f"{seq:06d}.{enc}.page"))

        def replay_dir():
            """A prior attempt's completed durable output, or None."""
            if key_dir is None or not os.path.isdir(key_dir):
                return None
            for a in sorted(os.listdir(key_dir)):
                d = os.path.join(key_dir, a)
                if os.path.exists(os.path.join(d, "_DONE")):
                    return d
            return None

        def run():
            src = replay_dir() if getattr(spec, "replay", False) else None
            if src is not None:
                try:
                    for b in sorted(os.listdir(src)):
                        if not b.startswith("b"):
                            continue
                        bdir = os.path.join(src, b)
                        for pf in sorted(os.listdir(bdir)):
                            if pf.endswith(".page"):
                                with open(os.path.join(bdir, pf),
                                          "rb") as f:
                                    page = f.read()
                                parts = pf.split(".")
                                enc = parts[1] if len(parts) == 3 \
                                    else PAGE_ENC_PTPG
                                with self.lock:
                                    task["pages"].setdefault(
                                        int(b[1:]), []).append((page, enc))
                                    self.counters["buffered_bytes"] += \
                                        len(page)
                                    self.counters["peak_buffered_bytes"] = \
                                        max(self.counters[
                                            "peak_buffered_bytes"],
                                            self.counters["buffered_bytes"])
                    with self.lock:
                        task["complete"] = True
                        task["state"] = "FINISHED"
                        self.counters["replayed"] += 1
                    return
                except OSError as e:
                    with self.lock:
                        task["error"] = f"replay failed: {e}"
                        task["state"] = "FAILED"
                        task["complete"] = True
                    return
            try:
                # scripted exec faults: delay (straggler), fail (task
                # FAILED), crash (worker dies mid-wave)
                F.apply_exec(self.faults, spec.task_id, self)
                # tasks run CONCURRENTLY (producers stream to consumers
                # on the same worker), so each task executes against a
                # shallow session clone with its own properties dict —
                # no shared mutation between overlapping queries
                import copy

                task_session = copy.copy(self.session)
                task_session.properties = dict(self.session.properties)
                for k, v in spec.properties.items():
                    if k in task_session.properties:
                        task_session.properties[k] = v
                from presto_tpu import session_ctx

                # zone-dependent expressions and now() must agree with
                # the coordinator's stamped context
                session_ctx.activate_raw(
                    str(task_session.properties.get("time_zone", "UTC")),
                    spec.properties.get("query_start_us"))
                # the worker inherits the coordinator's remaining query
                # budget: every upstream pull this task makes derives
                # its timeout from the same query-level deadline
                wctx = R.RunContext(
                    deadline=R.Deadline(spec.properties.get("deadline_s")))
                bag = CC.CompileStats()
                cex = _ClusterExecutor(task_session, spec, publish=publish,
                                       task_state=task, faults=self.faults)
                tracer = TR.Tracer(trace_id=wtrace_id,
                                   lane=f"worker:{self.port}",
                                   root_parent=wparent)
                tspan = tracer.begin_root(
                    f"task {spec.task_id}", kind="task",
                    task_id=spec.task_id, windex=spec.windex,
                    attempt=spec.attempt,
                    fused=bool(spec.properties.get("fused_ndev")),
                    local_trace=wtrace_id is None)
                try:
                    with R.activate(wctx), CC.recording(bag), \
                            TR.activate(tracer):
                        cex.run()
                finally:
                    tracer.end(tspan)
                    spans = tracer.snapshot()
                    with self.lock:
                        task["spans"] = spans
                    # chaos-test observability: the last task's spans
                    # survive the coordinator's task DELETE
                    self.last_task_spans = spans
                with self.lock:
                    for k in ("compiles", "compile_cache_hits",
                              "compile_ahead_hits"):
                        self.counters[k] += getattr(bag, k)
                    self.counters["compile_ms"] = round(
                        self.counters["compile_ms"] + bag.compile_ms, 1)
                    for k, v in cex.df_counts.items():
                        if k == "df_wait_ms":
                            self.counters[k] = round(
                                self.counters.get(k, 0.0) + v, 1)
                        else:
                            self.counters[k] = \
                                self.counters.get(k, 0) + int(v)
                    for k, v in cex.counters.items():
                        self.counters[k] = \
                            self.counters.get(k, 0) + int(v)
                    # per-task exchange/fusion counters ride the status
                    # response so the coordinator can fold them into
                    # this query's QueryStats without extra endpoints
                    task["counters"] = {**{k: v for k, v
                                           in cex.df_counts.items()},
                                        **dict(cex.counters)}
                if attempt_dir is not None:
                    os.makedirs(attempt_dir, exist_ok=True)
                    with open(os.path.join(attempt_dir, "_DONE"),
                              "wb"):
                        pass  # marker AFTER every page is on disk
                with self.lock:
                    task["complete"] = True
                    task["state"] = "FINISHED"
                    self.counters["executed"] += 1
            except BaseException as e:  # noqa: BLE001 — reported to coordinator
                import traceback

                with self.lock:
                    task["error"] = (f"{type(e).__name__}: {e}\n"
                                     + traceback.format_exc(limit=8))
                    task["state"] = "FAILED"
                    task["complete"] = True

        threading.Thread(target=run, daemon=True).start()


def _make_worker_handler(server: WorkerServer):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *args):
            pass

        def _send(self, code: int, body: bytes,
                  ctype: str = "application/octet-stream"):
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _authorized(self, body: bytes = b"") -> bool:
            if server.secret is None:
                return True  # loopback-only dev mode (enforced at bind)
            got = self.headers.get(AUTH_HEADER, "")
            return _verify_auth(server.secret, got, self.command,
                                self.path, body)

        def _fault_gate(self) -> bool:
            """Scripted server-side faults (parallel/faults.py); True
            when the fault consumed the request."""
            if server.crashed:  # a "crashed" worker answers nothing
                F._abort_connection(self)
                return True
            rule = server.faults.match("server", self.command, self.path)
            return rule is not None \
                and not F.apply_server(rule, self, server)

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(n)
            if self._fault_gate():
                return
            if not self._authorized(body):
                self._send(401, b"{}", "application/json")
                return
            if self.path == "/v1/task":
                server.reap_expired()
                try:
                    spec = plan_serde.loads(body)
                    if not isinstance(spec, TaskSpec):
                        raise ValueError("body is not a TaskSpec")
                except (ValueError, TypeError, KeyError) as e:
                    self._send(400, json.dumps(
                        {"error": f"bad task payload: {e}"}).encode(),
                        "application/json")
                    return
                server.submit(spec,
                              trace_ctx=self.headers.get(TR.TRACE_HEADER))
                self._send(200, json.dumps(
                    {"taskId": spec.task_id}).encode(), "application/json")
            elif self.path.startswith("/v1/task/") \
                    and self.path.endswith("/dynfilter"):
                # dynamic-filter side channel (plan/runtime_filters.py):
                # a build-side task delivers its completed key summary;
                # the consuming task's bounded wait (_df_receive) sees it
                tid = self.path.split("/")[3]
                with server.lock:
                    task = server.tasks.get(tid)
                if task is None:
                    self._send(404, b"{}")
                    return
                try:
                    payload = plan_serde.loads(body)
                    fid = payload["fid"]
                    part = int(payload.get("part", 0))
                except (ValueError, TypeError, KeyError):
                    self._send(400, b"{}")
                    return
                with server.lock:
                    task.setdefault("dynfilters", {}) \
                        .setdefault(fid, {})[part] = payload
                ev = task.get("df_event")
                if ev is not None:
                    ev.set()
                self._send(200, b"{}", "application/json")
            elif self.path.startswith("/v1/task/") \
                    and self.path.endswith("/range"):
                # range boundaries for sample-sort partitioning
                tid = self.path.split("/")[3]
                with server.lock:
                    task = server.tasks.get(tid)
                if task is None:
                    self._send(404, b"{}")
                    return
                task["range_boundaries"] = np.asarray(
                    plan_serde.loads(body))
                task["range_event"].set()
                self._send(200, b"{}", "application/json")
            elif self.path == "/v1/gang":
                # multi-host gang barrier (rank 0's worker is the home):
                # ready{epoch,rank,size} polls until {"go":true}; done
                # {epoch,rank} retires the epoch (see _GangBoard)
                try:
                    msg = json.loads(body)
                    op = msg["op"]
                    epoch = str(msg["epoch"])
                except (ValueError, TypeError, KeyError):
                    self._send(400, b"{}")
                    return
                if op == "ready":
                    resp = server.gang_board.ready(
                        epoch, int(msg.get("rank", 0)),
                        int(msg.get("size", 1)))
                    if resp.get("admitted"):
                        with server.lock:
                            server.counters["gangs_admitted"] += 1
                else:
                    resp = server.gang_board.done(
                        epoch, int(msg.get("rank", 0)))
                self._send(200, json.dumps(resp).encode(),
                           "application/json")
            elif self.path == "/v1/shutdown":
                self._send(200, b"{}", "application/json")
                threading.Thread(target=server.stop, daemon=True).start()
            else:
                self._send(404, b"{}")

        def do_GET(self):
            if self._fault_gate():
                return
            if self.path == "/v1/metrics":
                # Prometheus scrape (observe/metrics.py): the process
                # registry — which pre-registers every QueryStats
                # counter even though workers never run whole queries —
                # plus this worker's task-accounting counters as gauges.
                # Served WITHOUT the HMAC (a scraper can't sign the
                # rolling timestamp): the payload is aggregate counters
                # only — no SQL text, no task payloads, no page data —
                # and the loopback-bind rule still applies to the
                # socket itself.
                from presto_tpu.observe import metrics as M

                with server.lock:
                    counters = dict(server.counters)
                counters["mesh_devices"] = server.mesh_devices
                body = M.render_scrape(counters).encode()
                self._send(200, body,
                           "text/plain; version=0.0.4; charset=utf-8")
                return
            if not self._authorized():
                self._send(401, b"{}", "application/json")
                return
            parts = self.path.strip("/").split("/")
            if self.path.startswith("/v1/info"):
                server.reap_expired()
                with server.lock:
                    if "reset_peak" in self.path:
                        server.counters["peak_buffered_bytes"] = \
                            max(server.counters["buffered_bytes"], 0)
                    counters = dict(server.counters)
                self._send(200, json.dumps(
                    {"nodeId": f"worker:{server.port}",
                     "state": "active",
                     # fragment fusion: the mesh this worker DECLARES
                     # it owns exclusively (0 = none; never inferred)
                     "meshDevices": server.mesh_devices,
                     "meshId": server.mesh_id,
                     # multi-host fusion: jax.distributed membership this
                     # process DECLARES (parallel/mesh.py); absent keys =
                     # single-host worker
                     **(server.dist_spec or {}),
                     "counters": counters}).encode(), "application/json")
                return
            if len(parts) >= 4 and parts[:2] == ["v1", "task"]:
                tid = parts[2]
                with server.lock:
                    task = server.tasks.get(tid)
                if task is None:
                    self._send(404, b"{}")
                    return
                if parts[3] == "status":
                    self._send(200, json.dumps(
                        {"state": task["state"],
                         "error": task["error"],
                         "counters": task.get("counters") or {},
                         # worker-side spans for the coordinator's
                         # trace merge (set when execution ends)
                         "spans": task.get("spans") or []}).encode(),
                        "application/json")
                    return
                # /v1/task/{tid}/results/{bucket}/{token}[/ack]
                if parts[3] == "results" and len(parts) >= 6:
                    bucket = int(parts[4])
                    token = int(parts[5])
                    if len(parts) == 7 and parts[6] == "ack":
                        with server.lock:
                            pages = task["pages"].get(bucket, [])
                            for i in range(min(token, len(pages))):
                                if pages[i] is not None:
                                    server.counters["buffered_bytes"] -= \
                                        len(pages[i][0])
                                pages[i] = None  # release acked pages
                        self._send(200, b"{}", "application/json")
                        return
                    # snapshot under the lock, SEND outside it — a slow
                    # consumer must not stall every other request on
                    # this worker (multi-MB page writes take a while)
                    kind, page, last, err = "wait", None, False, b""
                    enc = PAGE_ENC_PTPG
                    with server.lock:
                        if task["state"] == "FAILED":
                            kind = "failed"
                            err = (task["error"] or "").encode()
                        else:
                            pages = task["pages"].get(bucket, [])
                            complete = task["complete"]
                            if token < len(pages):
                                entry = pages[token]
                                page, enc = entry if entry is not None \
                                    else (None, PAGE_ENC_PTPG)
                                if page is None:
                                    # acked page re-requested (consumer
                                    # restarted): at-least-once means a
                                    # task retry is needed; report as
                                    # failure so the coordinator re-runs
                                    kind = "released"
                                else:
                                    kind = "page"
                                    last = complete \
                                        and token + 1 >= len(pages)
                            elif complete:
                                kind = "done"
                    if kind == "failed":
                        self._send(500, err)
                    elif kind == "released":
                        self._send(500, b"page already released")
                    elif kind == "page":
                        if getattr(self, "_fault_partial", False):
                            page = F.corrupt_page(page)
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         "application/octet-stream")
                        self.send_header("Content-Length", str(len(page)))
                        self.send_header("X-Complete", "1" if last else "0")
                        self.send_header(PAGE_ENC_HEADER, enc)
                        self.end_headers()
                        self.wfile.write(page)
                    elif kind == "done":
                        self._send(204, b"")  # no more pages
                    else:
                        self._send(503, b"")  # not produced yet — poll
                    return
            self._send(404, b"{}")

        def do_DELETE(self):
            if self._fault_gate():
                return
            if not self._authorized():
                self._send(401, b"{}", "application/json")
                return
            parts = self.path.strip("/").split("/")
            if len(parts) == 3 and parts[:2] == ["v1", "task"]:
                with server.lock:
                    gone = server.tasks.pop(parts[2], None)
                    if gone:
                        server.counters["buffered_bytes"] -= sum(
                            len(p[0]) for ps in gone["pages"].values()
                            for p in ps if p is not None)
                self._send(200, b"{}", "application/json")
            else:
                self._send(404, b"{}")

    return Handler


def _coordinator_passthrough(fragments: List[Fragment]) -> List[Fragment]:
    """When fusion absorbed the plan's ROOT fragment, the fused
    super-fragment (which ends in the Output node) must run on the mesh
    owner, not the coordinator — so the coordinator gets a trivial
    passthrough fragment that pulls the fused task's gathered result
    pages.  That pull is result DELIVERY (both execution models pay it
    identically), not an inter-stage exchange."""
    from presto_tpu.plan import nodes as P

    last = fragments[-1]
    if not getattr(last, "fused", False):
        return fragments
    eid = max([i.eid for f in fragments for i in f.inputs],
              default=-1) + 1
    types = dict(last.root.outputs())
    scan = P.TableScan(f"__exch_{eid}", {s: s for s in types}, types)
    passthrough = Fragment(
        fid=len(fragments), root=scan,
        inputs=[ExchangeInput(eid, "gather", [], last.fid)],
        has_scan=False, on_workers=False, out_kind="gather", out_keys=[])
    return fragments + [passthrough]


# ---------------------------------------------------------------------------
# coordinator (SqlQueryScheduler analog)
# ---------------------------------------------------------------------------


class _HedgeMonitor(threading.Thread):
    """Straggler mitigation: watches the coordinator-consumed wave's
    tasks; once a quantile of the wave has FINISHED, any task still
    running past max(q*factor, q+min_s) is speculatively re-submitted to
    a healthy survivor.  First FINISHED attempt wins — the mutable
    placement slot is repointed in place, and because fragment execution
    is deterministic, both attempts publish the identical page sequence,
    so the consumer's token counter carries straight over (the dedup the
    at-least-once protocol already provides).  Best-effort: any monitor
    error leaves the query exactly as unhedged execution."""

    def __init__(self, cs: "ClusterSession", watch, all_tasks, ctx):
        super().__init__(daemon=True, name="hedge-monitor")
        self.cs = cs
        self.all_tasks = all_tasks
        self.ctx = ctx
        props = cs.session.properties
        self.quantile = float(props.get("cluster_hedge_quantile", 0.5))
        self.factor = float(props.get("cluster_hedge_factor", 3.0))
        self.min_s = float(props.get("cluster_hedge_min_s", 0.25))
        self.t0 = time.monotonic()
        self.waves: Dict[int, list] = {}
        for slot, fid in watch:
            self.waves.setdefault(fid, []).append(
                {"slot": slot, "done": None, "hedge": None})
        self._halt = threading.Event()

    def stop(self):
        self._halt.set()
        self.join(timeout=R.ACK_TIMEOUT_S)

    def _state(self, url: str, tid: str) -> Optional[str]:
        return _task_state(url, tid, self.ctx)

    def run(self):
        backoff = self.ctx.policy.backoff()
        try:
            # the query tracer rides onto this thread so hedge task
            # submissions carry the trace header and hedge spans land
            # on the hedge-monitor lane of the query's trace
            with TR.activate(getattr(self.cs, "_tracer", None)):
                while not self._halt.is_set():
                    pending = sum(self._scan(entries)
                                  for entries in self.waves.values())
                    if pending == 0 or self.ctx.deadline.expired():
                        return
                    backoff.sleep(self.ctx.deadline)
        except Exception:  # noqa: BLE001 — hedging is strictly best-effort
            pass

    def _scan(self, entries) -> int:
        now = time.monotonic()
        pending = 0
        for e in entries:
            if e["done"] is not None:
                continue
            url, tid = e["slot"][0], e["slot"][1]
            if self._state(url, tid) == "FINISHED":
                e["done"] = now
                if e["hedge"] is not None:  # original won: reap the hedge
                    self.all_tasks.append(tuple(e["hedge"]))
                    self._end_span(e, won=tid, lost=e["hedge"][1])
                continue
            if e["hedge"] is not None \
                    and self._state(*e["hedge"]) == "FINISHED":
                # hedge won: keep the loser reachable for cleanup, then
                # repoint the slot atomically (single slice-assign) so
                # in-flight pulls fail over mid-stream
                self.all_tasks.append((url, tid))
                e["slot"][:] = e["hedge"]
                e["done"] = now
                self.ctx.count("hedges_won", task=tid,
                               winner=e["hedge"][1])
                self._end_span(e, won=e["hedge"][1], lost=tid)
                continue
            pending += 1
        if pending == 0:
            return 0
        n = len(entries)
        done_times = sorted(e["done"] - self.t0 for e in entries
                            if e["done"] is not None)
        need = max(int(np.ceil(self.quantile * n)), 1)
        if len(done_times) < need:
            return pending
        q = done_times[need - 1]
        threshold = max(q * self.factor, q + self.min_s)
        for e in entries:
            if e["done"] is None and e["hedge"] is None \
                    and now - self.t0 > threshold:
                self._launch(e)
        return pending

    def _launch(self, e) -> None:
        url0, tid0 = e["slot"][0], e["slot"][1]
        spec, fid = self.cs._task_specs.get(tid0, (None, None))
        if spec is None:
            return
        targets = [u for u in self.cs.workers
                   if u != url0 and self.cs.health.allow(u)]
        if not targets:
            return
        # deterministic survivor pick: stable under a fixed layout
        target = targets[(fid + spec.windex) % len(targets)]
        hspec = dataclasses.replace(spec, task_id=tid0 + "_h",
                                    replay=False)
        fleet = getattr(self.cs, "fleet", None)
        if fleet is not None and not fleet.lease_slot(target, timeout_s=0.0):
            # hedges are opportunistic: never queue for a saturated
            # worker's slot, just skip the hedge this round
            return
        try:
            _http_retry(f"{target}/v1/task", plan_serde.dumps(hspec),
                        method="POST", ctx=self.ctx)
        except Exception:  # noqa: BLE001 — failed hedge changes nothing
            if fleet is not None:
                fleet.release_slot(target)
            return
        e["hedge"] = [target, hspec.task_id]
        self.all_tasks.append((target, hspec.task_id))
        self.ctx.count("hedges_launched", task=tid0, target=target)
        # the hedged attempt is its own trace lane (the hedge-monitor
        # thread): closed by _end_span with the winning/LOSING task ids
        # marked, so a hedge race is visible in the timeline instead of
        # inferred from counters
        tracer = getattr(self.cs, "_tracer", None)
        if tracer is not None:
            e["span"] = tracer.begin(
                f"hedge {tid0}", kind="attempt", task=tid0,
                hedge_task=hspec.task_id, target=target)

    def _end_span(self, e, won: str, lost: str) -> None:
        sp = e.pop("span", None)
        if sp is not None:
            tracer = getattr(self.cs, "_tracer", None)
            if tracer is not None:
                tracer.end(sp, won=won, lost=lost)


class ClusterSession:
    """Coordinator: plans on the local session, schedules fragments over
    the worker set, returns results like Session.sql."""

    def __init__(self, session, worker_urls: List[str],
                 resource_groups=None, fleet=None):
        self.session = session
        self.workers = list(worker_urls)
        # coordinator fleet (server/fleet.py): when attached, every task
        # POST first leases the worker's slot through the shared board
        # (N coordinators never oversubscribe one worker) and HealthBoard
        # verdicts gossip both ways — a peer's quarantine benches the
        # worker here too, and this session's quarantines reach peers
        self.fleet = fleet
        # coordinator admission control (server/resource_groups.py,
        # docs/SERVING.md): when a ResourceGroupManager is attached,
        # every ClusterSession.sql queues/sheds against per-group
        # concurrency + memory budgets BEFORE planning — the cluster
        # analog of the protocol server's serving tier
        self.resource_groups = resource_groups
        # circuit breaker shared across this session's queries: trips on
        # consecutive failures, re-admits through probation (reference:
        # failureDetector/HeartbeatFailureDetector)
        self.health = R.HealthBoard(
            trip_after=int(self.session.properties.get(
                "cluster_health_trip_after", 3)),
            probation_s=float(self.session.properties.get(
                "cluster_health_probation_s", 5.0)))
        self._benched: List[str] = []  # quarantined, awaiting probation
        if fleet is not None:
            fleet.subscribe(on_health=self._on_peer_health)
        # fragment fusion: per-worker mesh declarations (/v1/info
        # meshDevices/meshId), fetched lazily once per worker; the
        # fused-fragment count + exchange counters of the last
        # successful attempt, folded into QueryStats by sql()
        self._worker_meta: Dict[str, dict] = {}
        self._fused_count = 0
        self._coord_counters: Dict[str, int] = {}
        # per-edge fusion economics of the last attempt
        # (plan/fusion_cost.decide_edges; folded into QueryStats.fusion_*)
        self._fusion_skips: Dict[str, int] = {}
        self._fusion_mispredicted = 0
        self._fusion_cost_ms = 0.0
        # fault tolerance (parallel/journal.py): `_resume` is set by
        # resume_sql so _sql_attempts runs an ADOPTED query against its
        # journaled durable dir at attempt+1 (completed tasks replay).
        # `_journal_keep` is the chaos hook: when True, a FAILED
        # journaled query leaves its journal entry + durable dir behind
        # — simulating a coordinator that died before cleanup, so
        # adoption is deterministically testable (precedent:
        # FleetMember.drop_broadcasts)
        self._resume = None
        self._journal_keep = False

    def _on_peer_health(self, worker_url: str, verdict: str) -> None:
        """Receive side of fleet health gossip: a peer coordinator's
        'open' verdict trips OUR breaker and benches the worker, so this
        coordinator stops scheduling onto a worker a peer already found
        dead instead of rediscovering the failure query by query.
        Probation re-admission (_refresh_pool) is unchanged — a wrong
        gossip costs one probation interval."""
        if verdict != "open":
            return  # recovery is probation's call, never gossip's
        self.health.force_open(worker_url)
        if worker_url in self.workers and worker_url not in self._benched:
            self.workers = [u for u in self.workers if u != worker_url]
            self._benched.append(worker_url)

    def _lease_for_post(self, url: str, ctx: R.RunContext) -> None:
        """Slot lease ahead of a task POST (fleet deployments only): the
        shared board (server/fleet.SlotLeaseBoard) blocks while the
        worker is saturated by OTHER coordinators; a timeout surfaces as
        a typed upstream failure instead of oversubscribing the worker."""
        if self.fleet is None:
            return
        import presto_tpu.server.fleet as FL

        rem = ctx.deadline.remaining()
        budget = FL.LEASE_TIMEOUT_S if rem == float("inf") \
            else max(min(FL.LEASE_TIMEOUT_S, rem), 0.0)
        if self.fleet.lease_slot(url, timeout_s=budget):
            ctx.count("slot_leases", url=url)
            return
        ctx.count("slot_lease_timeouts", url=url)
        raise UpstreamFailed(
            f"worker {url} slot lease timed out after {budget:.1f}s "
            f"(fleet saturated)")

    def _make_restarter(self, all_tasks, ctx):
        """Task-granular restart hook (the `ctx.task_restarter`
        contract in pull_pages): when ONE task dies mid-wave, re-run
        just that task's slot on a healthy survivor inside the SAME
        attempt — completed siblings' durable pages stay untouched and
        the fleet-wide `executed` delta equals the failed tasks, not
        the wave.  The hook repoints the mutable [url, task_id] slot in
        place (the hedge monitor's winner-swap mechanism) and returns
        True so the pull resumes at its current token: a restarted task
        re-publishes the identical page sequence (deterministic
        execution), so token dedup carries the consumer across.  Fused
        specs are excluded — their failure degrades the whole attempt
        to the cut path (_sql_attempts' fused-fallback contract)."""
        limit = int(self.session.properties.get(
            "cluster_task_restarts", 2))
        if limit <= 0 or len(self.workers) < 2:
            return None
        counts: Dict[str, int] = {}
        lock = threading.Lock()

        def _restart(slot) -> bool:
            url0, tid0 = slot[0], slot[1]
            spec, fid = self._task_specs.get(tid0, (None, None))
            if spec is None or spec.properties.get("fused_ndev"):
                return False
            base = tid0.split("_r", 1)[0]
            with lock:
                n = counts.get(base, 0) + 1
                if n > limit:
                    return False  # budget spent: whole-attempt retry
                counts[base] = n
            targets = [u for u in self.workers
                       if u != url0 and self.health.allow(u)]
            if not targets:
                return False
            # deterministic survivor pick (same form the hedge uses)
            target = targets[(fid + spec.windex + n) % len(targets)]
            rspec = dataclasses.replace(spec, task_id=f"{base}_r{n}",
                                        replay=False)
            if self.fleet is not None \
                    and not self.fleet.lease_slot(target, timeout_s=0.0):
                # never queue a restart behind a saturated survivor —
                # the whole-attempt path will remap with fresh leases
                return False
            try:
                _http_retry(f"{target}/v1/task",
                            plan_serde.dumps(rspec), method="POST",
                            ctx=ctx)
            except Exception:  # noqa: BLE001 — attempt-level retry next
                if self.fleet is not None:
                    self.fleet.release_slot(target)
                return False
            self._task_specs[rspec.task_id] = (rspec, fid)
            # all_tasks holds the ALIASED slot list, which is about to
            # point at the restarted task — snapshot the failed original
            # as a tuple first (the hedge's loser-cleanup idiom) so the
            # DELETE sweep reaps BOTH and the lease count stays balanced
            # (one lease per entry: the original POST's plus this one)
            all_tasks.append((url0, tid0))
            slot[0], slot[1] = target, rspec.task_id
            ctx.count("tasks_rerun", task=tid0, target=target)
            return True

        return _restart

    def _worker_info(self, url: str, ctx: R.RunContext) -> dict:
        """Cached /v1/info mesh declaration of one worker ({} when the
        worker can't answer — it simply isn't a fusion target)."""
        meta = self._worker_meta.get(url)
        if meta is None:
            try:
                info = json.loads(_http(f"{url}/v1/info",
                                        timeout=R.PROBE_TIMEOUT_S,
                                        ctx=ctx))
                meta = {"meshDevices": int(info.get("meshDevices") or 0),
                        "meshId": info.get("meshId") or url,
                        # multi-host fusion: jax.distributed membership
                        # this worker DECLARES (parallel/mesh.py)
                        "distCoordinator":
                            info.get("distCoordinator") or "",
                        "distProcessId":
                            int(info.get("distProcessId") or 0),
                        "distNumProcesses":
                            int(info.get("distNumProcesses") or 1),
                        "globalDevices":
                            int(info.get("globalDevices") or 0)}
            except R.DeadlineExceeded:
                raise
            except Exception:  # noqa: BLE001 — probe failure = no mesh
                meta = {"meshDevices": 0, "meshId": url,
                        "distCoordinator": "", "distProcessId": 0,
                        "distNumProcesses": 1, "globalDevices": 0}
            self._worker_meta[url] = meta
        return meta

    def _fusion_mesh(self, layout, ctx) \
            -> Tuple[Optional[List[str]], int, int]:
        """Placement-aware fusion target: (urls, ndev, nproc).

        Single-host: the worker declaring the largest exclusively-owned
        mesh of at least `fragment_fusion_min_devices` chips — urls is
        that one worker, nproc == 1.  Multi-host (`multihost_fusion`,
        default on): workers declaring jax.distributed membership form
        a GANG when every process id 0..n-1 of one distributed
        coordinator is present in the layout; the gang owns the GLOBAL
        mesh (globalDevices) and outbids any single host it beats on
        device count — urls is the gang in rank order, nproc == n.
        (None, 0, 1) = every exchange edge is cross-host and nothing
        fuses."""
        min_dev = int(self.session.properties.get(
            "fragment_fusion_min_devices", 2))
        best, best_n, best_np = None, 0, 1
        groups: Dict[str, Dict[int, tuple]] = {}
        for url in dict.fromkeys(layout):
            info = self._worker_info(url, ctx)
            n = info["meshDevices"]
            if info["distCoordinator"]:
                # a multi-controller member is NEVER a single-host
                # target: its jax.devices() are the GLOBAL set, and a
                # lone shard_map over them would hang waiting for peers
                groups.setdefault(info["distCoordinator"], {})[
                    info["distProcessId"]] = (url, info)
            elif n >= max(min_dev, 2) and n > best_n:
                best, best_n, best_np = [url], n, 1
        if bool(self.session.properties.get("multihost_fusion", True)):
            for members in groups.values():
                nproc = max(m[1]["distNumProcesses"]
                            for m in members.values())
                if nproc < 2 or set(members) != set(range(nproc)):
                    continue  # incomplete gang: a rank is missing
                gdev = members[0][1]["globalDevices"]
                if gdev >= max(min_dev, 2) and gdev > best_n:
                    best = [members[r][0] for r in range(nproc)]
                    best_n, best_np = gdev, nproc
        return best, best_n, best_np

    def _query_ctx(self, query_id: str = "") -> R.RunContext:
        """Per-query RunContext: ONE deadline budget every RPC timeout
        derives from (`cluster_query_deadline_s` session property, else
        PRESTO_TPU_QUERY_DEADLINE), the seeded retry policy, and this
        session's health board."""
        dl = self.session.properties.get("cluster_query_deadline_s")
        deadline = R.Deadline(float(dl)) if dl is not None else \
            R.Deadline(R.query_deadline_from_env())
        return R.RunContext(
            deadline=deadline, policy=R.RetryPolicy.from_env(),
            health=self.health,
            listeners=self.session.event_listeners, query_id=query_id)

    def _refresh_pool(self, ctx: R.RunContext) -> None:
        """Probation re-admission: a quarantined worker whose circuit
        allows a probe (probation elapsed) and answers it rejoins the
        pool — flapping workers come back instead of staying dropped."""
        for url in list(self._benched):
            if self.health.probe(url, lambda u: _probe(u, ctx)):
                self._benched.remove(url)
                self.workers.append(url)
                ctx.count("workers_readmitted", url=url)

    def sql(self, text: str):
        from presto_tpu.observe.stats import QueryMonitor

        mon = QueryMonitor.begin(self.session, text)
        mon.stats.execution_mode = "distributed"
        group = None
        if self.resource_groups is not None:
            # admission BEFORE planning: a queued query must not hold
            # planner/compile resources (reference: DispatchManager
            # admits via resource groups before query execution starts)
            t0a = time.monotonic()
            try:
                group = self.resource_groups.acquire(
                    self.session.user, self.session.source,
                    timeout=float(self.session.properties.get(
                        "admission_queue_timeout_s", 60.0)),
                    memory_bytes=int(self.session.properties.get(
                        "query_max_memory_bytes", 0)))
            except BaseException as e:
                mon.fail(e)
                raise
            mon.stats.admission_wait_ms = (time.monotonic() - t0a) * 1000.0
            mon.stats.resource_group = group.full_name
        t0q = time.monotonic()
        ctx = self._query_ctx(mon.stats.query_id)
        mon.stats.recovery = ctx.recovery  # live view, not a copy
        self._coord_df = {}
        self._fusion_skips = {}
        self._fusion_mispredicted = 0
        self._fusion_cost_ms = 0.0
        # tracer shared with the hedge monitor + the status-time span
        # collection; worker task spans merge into it before finish()
        self._tracer = mon.tracer
        self._frag_profile = {}
        try:
            with R.activate(ctx), CC.recording(mon.stats), \
                    TR.activate(mon.tracer):
                try:
                    result = self._sql_attempts(text, ctx, mon)
                except BaseException as e:
                    mon.fail(e)
                    raise
        finally:
            if group is not None:
                self.resource_groups.release(
                    group, cpu_s=time.monotonic() - t0q,
                    memory_bytes=int(self.session.properties.get(
                        "query_max_memory_bytes", 0)))
        from presto_tpu.exec.executor import _merge_sort_stats

        if self._coord_df:
            _merge_sort_stats(mon.stats, self._coord_df)
        # fragment fusion: the successful attempt's plan-time decision
        # (fragments spliced) + the exchange-economics counters the
        # coordinator observed / collected from fused task statuses,
        # plus the per-edge verdict economics (plan/fusion_cost.py):
        # edges fused/cut, memo-vs-model disagreements, decision wall,
        # and the per-reason skip counts (cost / kind / memo /
        # cross_host) that make a cost-cut edge distinguishable from a
        # kind-filtered or cross-host one
        mon.stats.fragments_fused = self._fused_count
        mon.stats.fusion_edges_fused = self._fused_count
        mon.stats.fusion_edges_cut = sum(self._fusion_skips.values())
        mon.stats.fusion_edges_mispredicted = self._fusion_mispredicted
        mon.stats.fusion_cost_ms = self._fusion_cost_ms
        for k, v in self._fusion_skips.items():
            mon.stats.fusion_skips[k] = \
                mon.stats.fusion_skips.get(k, 0) + int(v)
        for k in ("exchange_bytes_host", "exchange_bytes_collective",
                  "exchange_bytes_sketch", "exchange_bytes_dcn"):
            setattr(mon.stats, k, getattr(mon.stats, k, 0)
                    + int(self._coord_counters.get(k, 0)))
        # adaptive aggregation: per-task flip decisions + strategy
        # counts collected from worker task statuses and the
        # coordinator's own fragment executor (plan/agg_strategy.py)
        agg_counts = {k: v for k, v in self._coord_counters.items()
                      if k.startswith("agg_strategy::")
                      or k.startswith("partial_agg")}
        if agg_counts:
            _merge_sort_stats(mon.stats, agg_counts)
        # spill tiering on worker fragments: counters collected from
        # task statuses (_collect_spill_stats) + the coordinator's own
        # fragment executor fold in exactly like single-node spill
        spill_counts = {k: v for k, v in self._coord_counters.items()
                        if k.startswith("spill_")
                        or k == "degradation_tier"}
        if spill_counts:
            _merge_sort_stats(mon.stats, spill_counts)
        mon.finish(result.rows)
        if getattr(result, "stats", None) is None:
            result.stats = mon.stats  # race-free vs session.last_stats
        return result

    def _sql_attempts(self, text: str, ctx: R.RunContext, mon=None):
        import shutil

        from presto_tpu.exec.executor import plan_statement
        from presto_tpu.plan.distribute import Undistributable
        from presto_tpu.sql.parser import parse
        from presto_tpu.sql import ast as _ast

        self._refresh_pool(ctx)
        stmt = parse(text)
        if isinstance(stmt, _ast.Explain):
            if stmt.analyze and mon is not None:
                # cluster-profiled EXPLAIN ANALYZE: execute the inner
                # statement distributed with per-fragment profiling and
                # render fragments annotated with task wall + XLA cost
                return self._explain_analyze(stmt.statement, ctx, mon)
            self._fused_count = 0
            return self.session.sql(text)  # plain EXPLAIN: local render
        if mon is not None:
            with mon.phase("plan"):
                plan = plan_statement(self.session, stmt)
        else:
            plan = plan_statement(self.session, stmt)
        attempts = 1 + int(self.session.properties.get(
            "cluster_query_retries", 1))
        # durable exchange (P12): pages persist on (shared) disk for the
        # query's lifetime so a retry replays completed tasks instead of
        # re-executing them (reference: REMOTE_MATERIALIZED exchanges +
        # per-lifespan rescheduling, StageExecutionId.java:28-45).
        # `recoverable_grouped_execution` defaults to "auto": ON for
        # cluster queries whenever a spill/durable path is configured
        # (the durable store rides the spill tier's disk budget);
        # explicit true/false is respected either way.
        resume = getattr(self, "_resume", None)
        rge = self.session.properties.get(
            "recoverable_grouped_execution", False)
        rge_s = str(rge).strip().lower()
        spill_cfg = bool(self.session.properties.get(
            "spill_enabled", False)) or \
            bool(str(self.session.properties.get("spill_path", "") or ""))
        rge_on = rge is True or rge_s in ("true", "on", "1") or \
            (rge_s == "auto" and spill_cfg)
        ddir = None
        base_attempt = 0
        if resume is not None:
            # adoption resume (resume_sql): the SAME durable dir at the
            # journaled attempt + 1, so the durable store IS the
            # completed-task map — finished tasks replay from disk and
            # only the dead coordinator's lost work re-executes
            ddir = resume.get("ddir")
            base_attempt = int(resume.get("attempt", 0)) + 1
        elif rge_on:
            base = str(self.session.properties.get("spill_path", "")) or \
                os.path.join("/tmp", "presto_tpu_spill")
            ddir = os.path.join(base, "exchange", uuid.uuid4().hex[:16])
        # the query's task layout: slot i runs splits i of len(layout).
        # A retry keeps the LAYOUT (so bucket counts and splits stay
        # consistent with pages already durably produced) and remaps the
        # dead workers' slots onto survivors.
        layout = list(self.workers)
        # query journaling (parallel/journal.py): persist this query's
        # resumable state to the fleet-visible journal so a ring
        # successor can adopt it if THIS coordinator dies mid-flight
        jr, jqid, jentry = None, None, None
        coord = self.fleet.coord_id if self.fleet is not None else "solo"
        if ddir is not None and (resume is not None or J.enabled(
                self.session.properties, self.fleet is not None)):
            jr = J.QueryJournal(J.root_dir(self.session.properties),
                                coord_id=coord)
            jqid = (resume or {}).get("queryId") or \
                f"jq_{uuid.uuid4().hex[:12]}"
            jentry = J.entry_for(jqid, text, coord,
                                 self.session.properties, ddir=ddir,
                                 layout=list(layout),
                                 attempt=base_attempt)
            if jr.write(jentry):
                ctx.count("journal_writes")
                if self.fleet is not None:
                    self.fleet.replicate_journal(jentry)
        t0r = time.monotonic()
        # entered manually so attempt spans + worker RPCs land inside
        # the execute phase on this query's trace
        phase_cm = mon.phase("execute") if mon is not None else None
        if phase_cm is not None:
            phase_cm.__enter__()
        ok = False
        try:
            fuse_ok = True
            for attempt in range(base_attempt, base_attempt + attempts):
                try:
                    result = self._run_distributed(plan, layout, ddir,
                                                   attempt,
                                                   allow_fusion=fuse_ok)
                    ok = True
                    if resume is not None:
                        ctx.count("queries_adopted")
                        ctx.count("adoption_ms", n=max(int(
                            (time.monotonic() - t0r) * 1000.0), 1))
                    return result
                except (Undistributable, NotImplementedError):
                    # plan shape the cluster can't place — single-node
                    # fallback
                    self._fused_count = 0
                    ok = True
                    return self.session.sql(text)
                except R.DeadlineExceeded:
                    # the deadline is a query-level budget: never retry
                    # past it (_schedule already cancelled all tasks)
                    ctx.count("deadline_expired")
                    raise
                except (UpstreamFailed, RuntimeError, TimeoutError,
                        ConnectionError, OSError):
                    # worker failure mid-query: remap the dead slots and
                    # re-run; completed tasks replay from the durable
                    # store when enabled.  Survivorship is the circuit
                    # breaker's call, not a one-shot probe's.
                    was_fused = self._fused_count > 0
                    survivors = []
                    for url in self.workers:
                        if self.health.probe(url,
                                             lambda u: _probe(u, ctx)):
                            survivors.append(url)
                        elif url not in self._benched:
                            self._benched.append(url)
                            ctx.count("workers_quarantined", url=url)
                            if self.fleet is not None:
                                # tell peer coordinators before they
                                # rediscover the corpse query by query
                                self.fleet.gossip_health(url, "open")
                    if was_fused:
                        # ANY failure of a fused attempt (guard trip,
                        # fused-task fault, mesh-owner crash) degrades
                        # to the per-fragment HTTP path — a same-pool
                        # retry is NOT deterministic here because the
                        # execution model changes (the ISSUE's
                        # byte-identical fallback contract)
                        fuse_ok = False
                        ctx.count("fused_fallbacks")
                        if attempt == base_attempt + attempts - 1:
                            raise
                        if survivors:
                            layout = [u if u in survivors
                                      else survivors[i % len(survivors)]
                                      for i, u in enumerate(layout)]
                            self.workers = survivors
                        ctx.count("query_retries",
                                  survivors=len(survivors))
                        self._journal_retry(jr, jentry, ctx,
                                            attempt + 1, layout)
                        continue
                    if not survivors or attempt == base_attempt \
                            + attempts - 1 \
                            or set(survivors) >= set(layout):
                        # same pool => deterministic failure; re-running
                        # would fail identically
                        raise
                    layout = [u if u in survivors
                              else survivors[i % len(survivors)]
                              for i, u in enumerate(layout)]
                    self.workers = survivors
                    ctx.count("query_retries", survivors=len(survivors))
                    self._journal_retry(jr, jentry, ctx, attempt + 1,
                                        layout)
            raise RuntimeError("unreachable")
        finally:
            if phase_cm is not None:
                phase_cm.__exit__(None, None, None)
            # a coordinator ALIVE to observe the outcome cleans up —
            # journal entries and the durable dir outlive only a
            # coordinator that died (the `_journal_keep` chaos hook
            # simulates exactly that death-before-cleanup window)
            keep = (not ok) and bool(getattr(self, "_journal_keep",
                                             False))
            if jr is not None and not keep:
                jr.remove(jqid)
            if ddir is not None and not keep:
                shutil.rmtree(ddir, ignore_errors=True)

    def _journal_retry(self, jr, jentry, ctx, next_attempt,
                       layout) -> None:
        """Advance the journal entry before a whole-attempt retry so an
        adopter resumes past attempts this coordinator already
        burned (durable keys are attempt-scoped on the publish side)."""
        if jr is None:
            return
        jentry["attempt"] = int(next_attempt)
        jentry["layout"] = list(layout)
        if jr.write(jentry):
            ctx.count("journal_writes")
            if self.fleet is not None:
                self.fleet.replicate_journal(jentry)

    def resume_sql(self, text: str, ddir, attempt: int,
                   query_id: str = ""):
        """Adopter entry point: re-run a journaled statement against
        the SAME durable-exchange dir at the journaled attempt + 1, so
        every task whose durable output completed REPLAYS from disk and
        only the dead coordinator's lost work re-executes."""
        self._resume = {"ddir": ddir, "attempt": int(attempt),
                        "queryId": query_id}
        try:
            return self.sql(text)
        finally:
            self._resume = None

    def adopt_journaled(self, dead_coord_id: str):
        """Fleet adoption (discovery.watch_fleet -> ring successor):
        resume every in-flight journaled query the dead coordinator
        owned.  Corrupt/unreadable entries are SKIPPED (journal read
        faults surface as read_errors, never as wrong results).
        Returns [(query_id, result-or-exception)] in journal order."""
        import shutil

        jr = J.QueryJournal(J.root_dir(self.session.properties),
                            coord_id=self.fleet.coord_id
                            if self.fleet is not None else "solo")
        out = []
        for e in jr.entries(coord=dead_coord_id):
            qid = str(e.get("queryId", ""))
            try:
                res = self.resume_sql(str(e.get("sql", "")),
                                      e.get("ddir"),
                                      int(e.get("attempt", 0)),
                                      query_id=qid)
                out.append((qid, res))
            except Exception as exc:  # noqa: BLE001 — per-query isolation
                out.append((qid, exc))
            finally:
                jr.remove(qid)
                if e.get("ddir"):
                    shutil.rmtree(e["ddir"], ignore_errors=True)
        return out

    def _eval_subplan(self, sub, scalar_results) -> tuple:
        """Uncorrelated scalar subplan -> (value, valid), distributed the
        same way as the main plan so partial-sum merge order (and thus
        float totals compared against main-plan aggregates, e.g. TPC-H
        Q15) matches across both."""
        from presto_tpu.exec.executor import Executor, _single_value
        from presto_tpu.plan import nodes as P
        from presto_tpu.plan.distribute import Undistributable, distribute

        syms = [s for s, _ in sub.outputs()]
        try:
            splan = P.QueryPlan(P.Output(sub, syms, syms), {})
            dsub = distribute(splan, self.session, len(self.workers))
            res = self._schedule(cut_fragments(dsub.root), scalar_results)
            data, valid = res[syms[0]]
            if len(data) > 1:
                from presto_tpu.exec.executor import ExecutionError

                raise ExecutionError(
                    "scalar subquery returned more than one row")
            if len(data) == 0 or (valid is not None and not valid[0]):
                return (0, False)
            v = data[0]
            return (v.item() if hasattr(v, "item") else v, True)
        except (Undistributable, NotImplementedError):
            ex = Executor(self.session)
            ex.ctx.scalar_results.update(scalar_results)
            return _single_value(ex.exec_node(sub))

    def _run_distributed(self, plan, layout=None, ddir=None, attempt=0,
                         allow_fusion=True):
        from presto_tpu.plan import distribute as DIST
        from presto_tpu.plan import nodes as P
        from presto_tpu.plan.distribute import distribute
        from presto_tpu.session import QueryResult

        import copy

        layout = layout if layout is not None else list(self.workers)
        nw = len(layout)
        # per-attempt counter reset FIRST: an attempt that dies during
        # planning must not leak the previous attempt's fusion counters
        # into this query's stats
        self._fused_count = 0
        self._coord_counters = {}
        self._fusion_skips = {}
        self._fusion_mispredicted = 0
        self._fusion_cost_ms = 0.0
        self._last_fusion_decisions = None
        scalar_results: Dict[int, tuple] = {}
        for pid, sub in sorted(plan.subplans.items()):
            # deepcopy: distribute() rewrites nodes in place, and a
            # retry re-distributes the same logical plan
            scalar_results[pid] = self._eval_subplan(
                copy.deepcopy(sub), scalar_results)
        dplan = distribute(P.QueryPlan(copy.deepcopy(plan.root), {}),
                           self.session, nw)
        fragments = cut_fragments(dplan.root)
        # fragment fusion (plan/distribute.fuse_fragments + the
        # plan/fusion_cost.py economics): when a worker declares an
        # exclusively-owned mesh, every exchange edge between fragments
        # placed on that mesh is mesh-ELIGIBLE — the cost model then
        # prices each edge both ways (CUT = pack + host hop + unpack +
        # per-fragment dispatch vs FUSED = in-trace collective +
        # serialization penalty) and only net-win edges splice into a
        # traced shard_map program scheduled on the mesh owner.
        # `fragment_fusion=force` restores round 12's fuse-everything;
        # cross-host edges (no declared mesh) and kind-excluded edges
        # keep the per-fragment HTTP path either way, with the skip
        # reason counted per edge (QueryStats.fusion_skips).
        from presto_tpu.plan import fusion_cost as FC

        plan_fp = ""
        memo_on = FC.memo_enabled(self.session)
        if len(fragments) > 1 and memo_on \
                and not getattr(self, "_profile_fragments", False):
            # the decision memo records this shape's execute wall even
            # on forced/off legs — an A/B run teaches the auto mode
            plan_fp = FC.fingerprint(fragments)
        if allow_fusion and len(fragments) > 1 \
                and DIST.fusion_enabled(self.session):
            mode = DIST.fusion_mode(self.session)
            mesh_urls, mesh_ndev, mesh_nproc = self._fusion_mesh(
                layout, R.current())
            if mesh_urls is None:
                # no declared mesh: every edge is cross-host
                self._fusion_skips = {"cross_host": sum(
                    len(f.inputs) for f in fragments)}
            else:
                kinds = DIST.fusion_kinds(self.session)
                t0c = TR.wall_s()
                # nproc > 1 prices edges on the DCN lane (dcn_edge_ms /
                # dcn_ms_per_mb) — the cross_host_collective verdict
                verdict, skips, mispred, _fp, decisions = FC.decide_edges(
                    fragments, mesh_ndev, self.session, mode, kinds,
                    fp=plan_fp, nproc=mesh_nproc)
                self._fusion_cost_ms = (TR.wall_s() - t0c) * 1000.0
                self._fusion_skips = skips
                self._fusion_mispredicted = mispred
                self._last_fusion_decisions = decisions
                fused, nfused = DIST.fuse_fragments(
                    fragments,
                    lambda frag, inp: verdict.get(inp.eid, False))
                if nfused:
                    fused = _coordinator_passthrough(fused)
                    for f in fused:
                        if getattr(f, "fused", False):
                            f.fused_url = mesh_urls[0]
                            f.fused_ndev = mesh_ndev
                            # cross-host gang: one task per mesh member,
                            # rank order (scheduled by _schedule)
                            f.fused_gang = list(mesh_urls) \
                                if mesh_nproc > 1 else []
                    fragments = fused
                    self._fused_count = nfused
        self._last_fragments = fragments  # EXPLAIN ANALYZE rendering
        t0s = TR.wall_s()
        coordinator_result = self._schedule(fragments, scalar_results,
                                            layout, ddir, attempt)
        if plan_fp:
            # runtime feedback (plan/fusion_cost.DecisionMemo): record
            # the observed execute wall under the mode that ran, so a
            # mispredicted edge set flips on the NEXT execution of this
            # plan shape — hysteresis-guarded, never mid-query
            FC.MEMO.observe(
                plan_fp, "fused" if self._fused_count else "cut",
                (TR.wall_s() - t0s) * 1000.0)

        # shape the final columns like Session.sql
        out = dplan.root
        names = out.names
        types = [dict(out.outputs())[s] for s in out.symbols]
        rows_t = []
        for s, t in zip(out.symbols, types):
            data, valid = coordinator_result[s]
            vals = []
            for i in range(len(data)):
                if valid is not None and not valid[i]:
                    vals.append(None)
                    continue
                v = data[i]
                if t.is_decimal:
                    v = float(v) / (10 ** t.decimal_scale)
                vals.append(v.item() if hasattr(v, "item") else v)
            rows_t.append(vals)
        n = len(rows_t[0]) if rows_t else 0
        rows = [tuple(c[i] for c in rows_t) for i in range(n)]
        return QueryResult(list(zip(names, types)), rows)

    def _schedule(self, fragments: List[Fragment],
                  scalar_results: Dict[int, tuple], layout=None,
                  ddir=None, attempt=0):
        """Run fragments as BSP supersteps; returns the final fragment's
        unpacked columns (reference: SqlQueryScheduler's stage loop with
        an AllAtOnce-per-level policy)."""
        layout = layout if layout is not None else list(self.workers)
        nfr = len(fragments)
        # placement is a pure function of the fragment, so consumers'
        # bucket counts are known before producers run
        run_on_of: Dict[int, list] = {}
        for frag in fragments:
            if frag.fid == nfr - 1:
                run_on_of[frag.fid] = [None]  # coordinator-local output
            elif getattr(frag, "fused", False):
                gang = getattr(frag, "fused_gang", None) or []
                if len(gang) > 1:
                    # cross-host fused super-fragment: one GANG of tasks,
                    # one per mesh member in rank order, sharing a
                    # barrier epoch (multi-controller jax: every process
                    # must execute the same collectives)
                    run_on_of[frag.fid] = list(gang)
                else:
                    # fused super-fragment: ONE task on the declared-mesh
                    # owner; the shard_map supplies the parallelism the
                    # per-fragment path got from the worker fan-out
                    run_on_of[frag.fid] = [frag.fused_url]
            elif frag.on_workers:
                run_on_of[frag.fid] = list(layout)
            else:
                # single-node intermediate (e.g. the merge stage of a
                # distributed sort) runs on worker 0, which can serve its
                # buffers over HTTP — the coordinator cannot
                run_on_of[frag.fid] = [layout[0]]
        consumer_of = {inp.producer: frag.fid
                       for frag in fragments for inp in frag.inputs}

        placements: Dict[int, List[list]] = {}
        all_tasks: List[Tuple[str, str]] = []
        coordinator_result = None
        ctx = R.current()
        try:
            coordinator_result = self._run_fragments(
                fragments, scalar_results, run_on_of, consumer_of,
                placements, all_tasks, ddir=ddir, attempt=attempt)
        finally:
            ctx.task_restarter = None
            hedge = getattr(self, "_hedge", None)
            if hedge is not None:
                hedge.stop()
                self._hedge = None
            # free worker-side shuffle buffers; on abort / deadline
            # expiry this is also the cancellation path — every live
            # task observes DELETE so workers never run orphaned work
            # (reference: DELETE /v1/task/{id}, SqlTaskManager cancel)
            aborted = coordinator_result is None
            # cancellation must outlive the query deadline: DELETEs run
            # under a fresh never-expiring context so an aborted query
            # still reaps every worker task within ACK_TIMEOUT_S each
            reap_ctx = R.RunContext(deadline=R.Deadline.never(),
                                    policy=ctx.policy, health=ctx.health)
            for url, tid in all_tasks:
                try:
                    _http(f"{url}/v1/task/{tid}", method="DELETE",
                          timeout=R.ACK_TIMEOUT_S, ctx=reap_ctx)
                    if aborted:
                        ctx.count("task_cancels", url=url, task=tid)
                except Exception:
                    pass
                finally:
                    # one lease per all_tasks entry (task POSTs and
                    # hedge launches both record here): release even
                    # when the DELETE can't reach the worker — the
                    # lease guards COORDINATOR-side concurrency, and a
                    # dead worker's board entry vanishes on unregister
                    if self.fleet is not None:
                        self.fleet.release_slot(url)
        return coordinator_result

    def _run_fragments(self, fragments, scalar_results, run_on_of,
                       consumer_of, placements, all_tasks, ddir=None,
                       attempt=0):
        """Fragment scheduling.  Default: all-at-once with streaming
        pages (reference: AllAtOnceExecutionPolicy) — every task is
        submitted up front and workers stream pages between themselves.
        With the `phased_execution` session property (reference:
        PhasedExecutionSchedule): fragments are grouped into phases so
        that a join's BUILD-side producers complete before its
        PROBE-side producers start, bounding worker memory — probe
        pages never pile up behind an unfinished build."""
        nfr = len(fragments)
        ctx = R.current()
        # pre-assign every placement so consumers know their upstreams
        # at submission time (streaming needs no producer-finished
        # barrier; the page protocol carries readiness).  Slots are
        # MUTABLE [url, task_id] pairs shared with the hedge monitor:
        # when a hedge wins, the slot is repointed in place and every
        # coordinator-side pull follows it (pull_pages slot= contract).
        for frag in fragments:
            run_on = run_on_of[frag.fid]
            placements[frag.fid] = [
                [url, f"t_{uuid.uuid4().hex[:12]}"] for url in run_on]
        # dynamic-filtering routing (plan/runtime_filters.py): the
        # coordinator computes, AT SCHEDULE TIME, which fragment can
        # summarize each filter's build keys from an exchange input and
        # which fragments' scans consume that filter remotely — producer
        # tasks then POST completed summaries straight to the consumer
        # tasks (placements are pre-assigned, so the routing table is
        # known before anything runs).  Broadcast/gather build inputs
        # give every producer task the COMPLETE key set (nparts=1);
        # repartition inputs are per-bucket partials consumers union.
        df_push_of: Dict[int, dict] = {}
        df_expect_of: Dict[int, dict] = {}
        if DF.enabled(self.session):
            # fused super-fragments are excluded from the side channel:
            # a filter whose producer join lives inside the fused trace
            # is produced AND applied in-trace by the executor itself
            wiring = {f.fid: _rf_fragment_wiring(f) for f in fragments}
            for frag in fragments:
                if getattr(frag, "fused", False):
                    continue
                _produced, pushable, _consumed = wiring[frag.fid]
                for fid, cfg in pushable.items():
                    targets = []
                    remote_fids = []
                    for g in fragments:
                        if getattr(g, "fused", False):
                            continue
                        gp, _gpu, gc = wiring[g.fid]
                        if fid in gc and fid not in gp:
                            remote_fids.append(g.fid)
                            targets += [list(slot)
                                        for slot in placements[g.fid]
                                        if slot[0] is not None]
                    if not targets:
                        continue
                    if cfg["kind"] in ("broadcast", "gather"):
                        nparts, partial = 1, False
                    elif cfg["kind"] == "repartition":
                        nparts = len(placements[frag.fid])
                        partial = True
                    else:
                        continue  # scatter/range builds: not routed yet
                    df_push_of.setdefault(frag.fid, {})[fid] = {
                        "eid": cfg["eid"], "sym": cfg["sym"],
                        "partial": partial, "targets": targets}
                    for gfid in remote_fids:
                        df_expect_of.setdefault(gfid, {})[fid] = nparts
        coordinator_spec = None
        self._task_specs: Dict[str, tuple] = {}  # tid -> (spec, fid)
        phased = bool(self.session.properties.get(
            "phased_execution", False))
        phases = _fragment_phases(fragments) if phased else \
            {f.fid: 0 for f in fragments}
        self.schedule_trace = []  # [(fid, phase, submit_time)]
        prev_wave_tasks: List[Tuple[str, str]] = []
        for phase in sorted(set(phases.values())):
            if phased and prev_wave_tasks:
                # barrier: earlier phases (build sides) finish first
                self._wait(prev_wave_tasks)
                states = []
                for url, tid in prev_wave_tasks:
                    st = json.loads(_http(f"{url}/v1/task/{tid}/status"))
                    states.append(st.get("state"))
                self.schedule_trace.append(
                    ("barrier", phase, tuple(states)))
            prev_wave_tasks = []
            for frag in fragments:
                if phases[frag.fid] != phase:
                    continue
                out_symbols = [s for s, _ in frag.root.outputs()]
                from presto_tpu.plan import nodes as _P

                inputs = []
                for inp in frag.inputs:
                    prod = fragments[inp.producer]
                    inputs.append({
                        "eid": inp.eid, "kind": inp.kind,
                        "types": dict(prod.root.outputs()),
                        "upstreams": placements[inp.producer],
                        # pulls from the result-root producer are result
                        # delivery, not an inter-stage exchange — the
                        # exchange_bytes_host counter skips them
                        "result_root": isinstance(prod.root, _P.Output),
                    })
                run_on = run_on_of[frag.fid]
                cfid = consumer_of.get(frag.fid, -1)
                cfrag = fragments[cfid] if 0 <= cfid < nfr else None
                if cfrag is not None and \
                        len(getattr(cfrag, "fused_gang", None) or []) > 1:
                    # producer feeding a cross-host fused gang: write ONE
                    # gather-style bucket every rank reads in full — each
                    # gang member ingests the identical input and the
                    # fused program shards it over the global mesh itself
                    out_buckets = 1
                elif frag.out_kind in ("repartition", "scatter", "range"):
                    out_buckets = len(run_on_of.get(cfid, [None]))
                else:
                    out_buckets = 1
                payload_root = plan_serde.dumps(frag.root)
                tasks: List[list] = []
                rem = ctx.deadline.remaining()
                deadline_s = None if rem == float("inf") else max(rem, 0.0)
                fused = getattr(frag, "fused", False)
                gang = getattr(frag, "fused_gang", None) or []
                # one barrier epoch per gang per attempt: ranks of THIS
                # attempt rendezvous; a retry gets a fresh epoch so a
                # straggler from the dead attempt can never join it
                gang_epoch = f"g_{uuid.uuid4().hex[:12]}" \
                    if fused and len(gang) > 1 else None
                # content-addressed durable key: a fingerprint of the
                # fragment's serialized root + exchange shape, NOT its
                # fid.  Stable under the fused->unfused renumbering, so
                # FUSED tasks participate in replay too: a fused root's
                # serde bytes differ from every cut fragment's (keys
                # can't alias across execution models), while fragments
                # the fallback leaves untouched keep byte-identical
                # roots and REPLAY their completed durable pages.
                dkey_base = None
                if ddir is not None:
                    hh = hashlib.blake2b(payload_root, digest_size=8)
                    hh.update(repr((frag.out_kind, frag.out_keys,
                                    out_buckets, len(run_on))).encode())
                    dkey_base = f"x{hh.hexdigest()}"
                for w, (url, tid) in enumerate(placements[frag.fid]):
                    dkey = f"{dkey_base}_w{w}" \
                        if dkey_base is not None else None
                    # a completed durable output from a prior attempt means
                    # this slot REPLAYS from disk — only the victim's lost
                    # work re-executes (per-bucket retry, P12)
                    replay = False
                    if dkey is not None and attempt > 0:
                        kd = os.path.join(ddir, dkey)
                        if os.path.isdir(kd):
                            replay = any(
                                os.path.exists(os.path.join(kd, a, "_DONE"))
                                for a in os.listdir(kd))
                    spec = TaskSpec(
                        task_id=tid,
                        fragment=payload_root,
                        out_symbols=out_symbols,
                        nworkers=len(run_on), windex=w, inputs=inputs,
                        out_kind=frag.out_kind, out_keys=frag.out_keys,
                        out_buckets=out_buckets,
                        scalar_results=scalar_results,
                        properties={
                            "float32_compute": self.session.properties.get(
                                "float32_compute", False),
                            "time_zone": self.session.properties.get(
                                "time_zone", "UTC"),
                            # now()/current_date must be query-stable across
                            # the mesh (session_ctx contract)
                            "query_start_us": _sctx.query_start_us(),
                            # workers inherit the remaining query budget
                            "deadline_s": deadline_s,
                            # dynamic filtering: kill switch + side-channel
                            # wait budget travel with every task
                            "dynamic_filtering": self.session.properties
                            .get("dynamic_filtering", True),
                            "dynamic_filtering_wait_ms":
                            self.session.properties.get(
                                "dynamic_filtering_wait_ms", 0),
                            # tracing detail travels with the task so
                            # "full" turns on worker page-pull spans
                            "trace_detail": self.session.properties.get(
                                "trace_detail", "basic"),
                            # slot-lease provenance: the worker tags the
                            # task with the leasing coordinator so
                            # reap_expired can release a lease that
                            # coordinator died still holding
                            "lease_coord": self.fleet.coord_id
                            if self.fleet is not None else None,
                            # spill tiering (exec/spill_exec.py): the
                            # degradation knobs travel with every task so
                            # cluster fragment executors arm the same
                            # spill tiers the single-node engine does —
                            # a worker fragment past its memory budget
                            # degrades to hybrid spill instead of OOMing
                            **{k: self.session.properties.get(k)
                               for k in ("spill_enabled", "force_spill",
                                         "spill_threshold_bytes",
                                         "spill_trigger_rows",
                                         "spill_max_recursion_depth",
                                         "spill_path",
                                         "spill_verify_writes",
                                         "query_max_memory_bytes")}},
                        durable_dir=ddir, durable_key=dkey,
                        attempt=attempt, replay=replay,
                    )
                    if getattr(self, "_profile_fragments", False):
                        # EXPLAIN ANALYZE: workers attach XLA cost
                        # analysis to their task counters
                        spec.properties["profile_fragment"] = True
                    if fused:
                        # the worker routes this task through the fused
                        # mesh path (run_fused_fragment) at this ndev —
                        # GLOBAL device count for a cross-host gang
                        spec.properties["fused_ndev"] = frag.fused_ndev
                        spec.properties["fragments_fused"] = \
                            len(getattr(frag, "fused_fids", []))
                        if gang_epoch is not None:
                            spec.properties["gang_rank"] = w
                            spec.properties["gang_size"] = len(gang)
                            spec.properties["gang_epoch"] = gang_epoch
                            spec.properties["gang_home"] = gang[0]
                    pushcfg = df_push_of.get(frag.fid)
                    if pushcfg:
                        spec.properties["df_push"] = {
                            fid: {"eid": c["eid"], "sym": c["sym"],
                                  "part": (w if c["partial"] else 0),
                                  "targets": c["targets"]}
                            for fid, c in pushcfg.items()}
                    if frag.fid in df_expect_of:
                        spec.properties["df_expect"] = \
                            df_expect_of[frag.fid]
                    if url is None:  # final fragment: run on the coordinator
                        coordinator_spec = spec
                    else:
                        self._lease_for_post(url, ctx)
                        try:
                            _http_retry(f"{url}/v1/task",
                                        plan_serde.dumps(spec),
                                        method="POST")
                        except BaseException:
                            # failed POST holds no task: give the slot
                            # back now instead of waiting for reclaim
                            if self.fleet is not None:
                                self.fleet.release_slot(url)
                            raise
                        self._task_specs[tid] = (spec, frag.fid)
                        tasks.append(placements[frag.fid][w])
                self.schedule_trace.append(
                    (frag.fid, phases[frag.fid], TR.wall_s()))
                if tasks:
                    all_tasks.extend(tasks)
                    prev_wave_tasks.extend(tasks)
                if frag.out_kind == "range" and tasks:
                    self._coordinate_range(frag, tasks, out_buckets)
        # straggler hedging (reference: task-level speculative execution;
        # SURVEY.md hard-part: stragglers): watch the fragments whose
        # pages the COORDINATOR pulls (their upstream slots live in this
        # process, so a winner swap is visible mid-pull; worker-side
        # consumers hold serialized placements a swap can't reach) and
        # speculatively re-run late tasks on a healthy survivor — first
        # FINISHED wins, dedup by the page token sequence, which is
        # identical across attempts because execution is deterministic
        if bool(self.session.properties.get("cluster_hedging", True)) \
                and len(self.workers) > 1:
            hedged_fids = [
                f.fid for f in fragments
                if f.fid != nfr - 1 and f.out_kind != "range"
                and consumer_of.get(f.fid) == nfr - 1
                and len(placements[f.fid]) > 1
                # never hedge a gang member: a lone re-run of one rank
                # would wait out the barrier instead of helping — gang
                # failure is the was_fused fallback's job
                and not getattr(f, "fused", False)]
            watch = [(slot, placements_fid)
                     for placements_fid in hedged_fids
                     for slot in placements[placements_fid]]
            if watch:
                self._hedge = _HedgeMonitor(self, watch, all_tasks, ctx)
                self._hedge.start()
        # task-granular restart: arm the pull-side hook so one task's
        # mid-wave death re-runs ONLY that slot on a survivor inside
        # this same attempt (pull_pages consults ctx.task_restarter
        # before surfacing UpstreamFailed); disarmed in _schedule's
        # finally so cancellation never races a restart POST
        ctx.task_restarter = self._make_restarter(all_tasks, ctx)
        # the final fragment executes here, pulling pages (and thereby
        # blocking) until upstream production drains
        pages: Dict[int, List[bytes]] = {}
        cex = _ClusterExecutor(self.session, coordinator_spec,
                               publish=lambda b, p, enc=PAGE_ENC_PTPG:
                               pages.setdefault(b, []).append(p))
        cex.run()
        # coordinator-side filter activity folds into this query's stats
        # (worker-side activity aggregates on each worker's /v1/info)
        self._coord_df = dict(cex.df_counts)
        # exchange economics: coordinator-observed host bytes, plus the
        # fused tasks' counters (ICI byte estimate, external-input host
        # bytes) pulled from their status — only when fusion ran, so
        # the unfused path's RPC sequence stays byte-identical for the
        # deterministic fault plans
        for k, v in cex.counters.items():
            self._coord_counters[k] = \
                self._coord_counters.get(k, 0) + int(v)
        if self._fused_count:
            for frag in fragments:
                if not getattr(frag, "fused", False):
                    continue
                for slot in placements[frag.fid]:
                    try:
                        st = json.loads(_http(
                            f"{slot[0]}/v1/task/{slot[1]}/status",
                            ctx=ctx))
                        for k, v in (st.get("counters") or {}).items():
                            if k.startswith("exchange_bytes_"):
                                self._coord_counters[k] = \
                                    self._coord_counters.get(k, 0) \
                                    + int(v)
                    except Exception:  # noqa: BLE001 — telemetry only
                        pass
        self._collect_task_traces(fragments, placements, ctx)
        self._collect_agg_economics(fragments, placements, ctx)
        self._collect_spill_stats(fragments, placements, ctx)
        merged = [unpack_columns(p) for p in pages.get(0, [])]
        # single final page expected (gather output); concat defensively
        if len(merged) == 1:
            return merged[0]
        out: Dict[str, tuple] = {}
        for part in merged:
            for k, (d, v) in part.items():
                if k in out:
                    pd, pv = out[k]
                    d = np.concatenate([pd, d])
                    v = None if (pv is None and v is None) else \
                        np.concatenate([
                            pv if pv is not None
                            else np.ones(len(pd), bool),
                            v if v is not None
                            else np.ones(len(d) - len(pd), bool)])
                out[k] = (d, v)
        return out

    def _explain_analyze(self, stmt, ctx, mon):
        """Cluster-profiled EXPLAIN ANALYZE: run the statement through
        the real distributed path with per-fragment profiling enabled
        (workers attach XLA cost analysis to their task counters —
        fused tasks read it off the fused executable, cut tasks off a
        diagnostic static trace), then render every fragment annotated
        with measured task wall + FLOPs/HBM bytes + the roofline
        estimate.  One attempt; an undistributable plan falls back to
        the profiled single-node path."""
        from presto_tpu import types as T
        from presto_tpu.exec.executor import explain_analyze_text
        from presto_tpu.observe import profile as PR
        from presto_tpu.observe.stats import trace_summary_line
        from presto_tpu.plan import nodes as P
        from presto_tpu.plan.distribute import Undistributable
        from presto_tpu.exec.executor import plan_statement
        from presto_tpu.session import QueryResult

        self._profile_fragments = True
        try:
            with mon.phase("plan"):
                plan = plan_statement(self.session, stmt)
            try:
                phase_cm = mon.phase("execute")
                phase_cm.__enter__()
                try:
                    result = self._run_distributed(plan)
                finally:
                    phase_cm.__exit__(None, None, None)
            except (Undistributable, NotImplementedError):
                self._fused_count = 0
                text = explain_analyze_text(self.session, stmt, mon)
                return QueryResult([("Query Plan", T.VARCHAR)],
                                   [(text,)])
        finally:
            self._profile_fragments = False
        mon.stats.output_rows = len(result.rows)
        mon.rows_preset = True
        lines = []
        profile = getattr(self, "_frag_profile", {})
        fragments = getattr(self, "_last_fragments", [])
        nfr = len(fragments)
        for frag in fragments:
            p = profile.get(frag.fid) or {}
            fused = bool(getattr(frag, "fused", False))
            if frag.fid == nfr - 1:
                kind = "coordinator result delivery"
            elif fused:
                kind = (f"fused shard_map x{frag.fused_ndev} devices, "
                        f"absorbed {len(getattr(frag, 'fused_fids', []))}"
                        " fragments")
            else:
                kind = "cut, HTTP exchange"
            lines.append(f"Fragment {frag.fid} ({kind}, "
                         f"tasks={p.get('tasks', 0)}):")
            cost = {"flops": float(p.get("xla_flops", 0)),
                    "bytes_accessed":
                        float(p.get("xla_bytes_accessed", 0))} \
                if p.get("has_cost") else None
            note = "coordinator-local" if frag.fid == nfr - 1 \
                else "untraceable fragment"
            lines.append("   " + PR.cost_line(
                cost, p.get("wall_ms") or None, note))
            lines.append(P.plan_tree_str(frag.root, 1))
            lines.append("")
        # per-edge fuse-vs-cut verdicts (plan/fusion_cost.py) next to
        # the XLA cost attribution: what the model priced each exchange
        # edge at and why it fused or stayed an HTTP cut — the same
        # decisions QueryStats.fusion_skips aggregates
        decisions = getattr(self, "_last_fusion_decisions", None)
        if decisions:
            lines.append("Fusion edges (cut vs fused, "
                         "plan/fusion_cost.py):")
            for d in decisions:
                price = f"cut={d.cut_est_ms:.1f}ms"
                if d.fused_est_ms is not None:
                    price += f" fused={d.fused_est_ms:.1f}ms"
                verdict = "FUSE" if d.fuse else f"CUT ({d.reason})"
                lines.append(
                    f"   edge {d.eid} {d.kind} f{d.producer}->"
                    f"f{d.consumer} ~{d.est_bytes:,}B {price} "
                    f"-> {verdict}")
            lines.append("")
        lines.append(f"Query {mon.stats.query_id}: "
                     + ", ".join(f"{k}: {v / 1e6:.1f}ms"
                                 for k, v in mon.stats.phase_ns.items())
                     + f"; output rows: {mon.stats.output_rows}; "
                     f"fragments_fused: {self._fused_count}")
        lines.append(trace_summary_line(mon.stats))
        return QueryResult([("Query Plan", T.VARCHAR)],
                           [("\n".join(lines),)])

    def _collect_agg_economics(self, fragments, placements, ctx) -> None:
        """Post-success adaptive-agg counter collection: every worker
        task of a fragment carrying a PARTIAL aggregate made its OWN
        per-task flip decision (per-task ratio, plan/agg_strategy.py);
        the decision counters ride the task status and fold into this
        query's QueryStats here.  Best-effort and gated on the fragments
        actually containing partial aggregates, so plans without them
        keep their RPC sequence unchanged."""
        from presto_tpu.plan import agg_strategy as AGS
        from presto_tpu.plan import nodes as P

        if not AGS.enabled(self.session):
            return
        if getattr(ctx, "recovery", None):
            # degraded run (retries/hedges/worker deaths): a status GET
            # to a dead worker stalls the probe timeout per slot —
            # telemetry is not worth post-success stalls here, and the
            # deterministic chaos fault plans keep their RPC sequences
            return

        def has_partial(node) -> bool:
            if isinstance(node, P.Aggregate) and node.step == "PARTIAL":
                return True
            return any(has_partial(s)
                       for s in getattr(node, "sources", []))

        want = [f for f in fragments
                if getattr(f, "on_workers", True) and has_partial(f.root)]
        for frag in want:
            for slot in placements.get(frag.fid, []):
                if slot[0] is None:
                    continue  # the coordinator's own fragment
                try:
                    st = json.loads(_http(
                        f"{slot[0]}/v1/task/{slot[1]}/status",
                        timeout=R.PROBE_TIMEOUT_S, ctx=ctx))
                except Exception:  # noqa: BLE001 — telemetry only
                    continue
                for k, v in (st.get("counters") or {}).items():
                    if k.startswith("agg_strategy::") \
                            or k == "partial_aggs_bypassed" \
                            or k == "partial_aggs_reenabled":
                        self._coord_counters[k] = \
                            self._coord_counters.get(k, 0) + int(v)
                    elif k == "partial_agg_ratio" and v:
                        self._coord_counters[k] = float(v)

    def _collect_spill_stats(self, fragments, placements, ctx) -> None:
        """Post-success spill-degradation collection: worker fragment
        executors run the same spill tiers as the single-node engine
        (exec/spill_exec.py, knobs threaded via spec.properties); their
        spill_* counters and degradation tier ride the task status and
        fold into this query's QueryStats here.  Gated on the spill
        knobs actually being armed (SE.routing_enabled), so the default
        configuration keeps its RPC sequence byte-identical."""
        from presto_tpu.exec import spill_exec as SE

        if not SE.routing_enabled(self.session):
            return
        if getattr(ctx, "recovery", None):
            # degraded run: same no-post-success-stalls rule as the
            # adaptive-agg collection above
            return
        for frag in fragments:
            for slot in placements.get(frag.fid, []):
                if slot[0] is None:
                    continue  # the coordinator's own fragment
                try:
                    st = json.loads(_http(
                        f"{slot[0]}/v1/task/{slot[1]}/status",
                        timeout=R.PROBE_TIMEOUT_S, ctx=ctx))
                except Exception:  # noqa: BLE001 — telemetry only
                    continue
                for k, v in (st.get("counters") or {}).items():
                    if k == "degradation_tier":
                        self._coord_counters[k] = max(
                            int(self._coord_counters.get(k, 0)), int(v))
                    elif k.startswith("spill_") and v:
                        self._coord_counters[k] = \
                            self._coord_counters.get(k, 0) + int(v)

    def _collect_task_traces(self, fragments, placements, ctx) -> None:
        """Post-success trace merge: pull each worker task's recorded
        spans off its status payload and graft the ones carrying THIS
        query's trace id into the coordinator tracer — the coordinator
        and every worker then share ONE trace (hedge winners included:
        slots were repointed, so the winning attempt's spans are read).
        Also assembles the per-fragment profile (max task wall + the
        XLA cost counters the EXPLAIN ANALYZE path requested).  Runs
        only when tracing/profiling is on, so a trace_detail=off run's
        RPC sequence is byte-identical to the pre-tracing engine."""
        tracer = getattr(self, "_tracer", None)
        profiling = bool(getattr(self, "_profile_fragments", False))
        if tracer is None and not profiling:
            return
        self._frag_profile = {}
        for frag in fragments:
            prof = {"wall_ms": 0.0, "tasks": 0,
                    "fused": bool(getattr(frag, "fused", False)),
                    "xla_flops": 0, "xla_bytes_accessed": 0,
                    "has_cost": False}
            for slot in placements.get(frag.fid, []):
                if slot[0] is None:
                    continue  # the coordinator's own final fragment
                try:
                    st = json.loads(_http(
                        f"{slot[0]}/v1/task/{slot[1]}/status",
                        timeout=R.PROBE_TIMEOUT_S, ctx=ctx))
                except R.DeadlineExceeded:
                    raise
                except Exception:  # noqa: BLE001 — telemetry only
                    continue
                spans = st.get("spans") or []
                if tracer is not None:
                    tracer.add_spans(spans)
                prof["tasks"] += 1
                for d in spans:
                    if d.get("kind") == "task":
                        dur = (float(d.get("end_us", 0))
                               - float(d.get("start_us", 0))) / 1e3
                        prof["wall_ms"] = max(prof["wall_ms"], dur)
                counters = st.get("counters") or {}
                for k in ("xla_flops", "xla_bytes_accessed"):
                    if counters.get(k):
                        prof[k] += int(counters[k])
                        prof["has_cost"] = True
            self._frag_profile[frag.fid] = prof

    def _coordinate_range(self, frag, tasks, out_buckets):
        """Pull key samples from every range producer, compute global
        bucket boundaries, post them back (reference: the sampling stage
        of distributed sort, admin/dist-sort.rst)."""
        _sym, asc, _nf = frag.out_keys[0]
        samples = []
        for url, tid in tasks:
            # exactly one sample page per producer; the producer is
            # blocked awaiting boundaries, so never wait for "complete"
            for page in pull_pages(url, tid, out_buckets, max_pages=1):
                vals = plan_serde.loads(page)
                if len(vals):
                    samples.append(np.asarray(vals))
        if samples:
            allv = np.concatenate(samples)
            allv = np.sort(allv)
            k = out_buckets
            edges = [allv[int(len(allv) * i / k)]
                     for i in range(1, k)] if len(allv) else []
            boundaries = np.asarray(edges)
        else:
            boundaries = np.asarray([])
        payload = plan_serde.dumps(boundaries.tolist())
        for url, tid in tasks:
            _http_retry(f"{url}/v1/task/{tid}/range", payload,
                        method="POST")

    def _wait(self, tasks, timeout: Optional[float] = None,
              ctx: Optional[R.RunContext] = None):
        """Status-poll specific tasks to completion.  THE load-bearing
        phase barrier for phased_execution (_run_fragments waits here
        between waves); also used for range coordination and tests.
        `tasks` holds (url, tid) pairs or mutable slots — the target is
        re-read each poll, so a hedge winner satisfies the barrier."""
        ctx = ctx if ctx is not None else R.current()
        local = R.Deadline(R.WAIT_TIMEOUT_S if timeout is None else timeout)
        for slot in tasks:
            backoff = ctx.policy.backoff()
            while True:
                url, tid = slot[0], slot[1]
                st = json.loads(_http_retry(
                    f"{url}/v1/task/{tid}/status", ctx=ctx))
                if st["state"] == "FINISHED":
                    break
                if st["state"] == "FAILED":
                    raise RuntimeError(
                        f"task {tid} on {url} failed: {st['error']}")
                ctx.deadline.check(f"task {tid} on {url}")
                if local.expired():
                    raise TimeoutError(f"task {tid} on {url} timed out")
                backoff.sleep(local)

    def close(self):
        for url in self.workers + self._benched:
            try:
                _http(f"{url}/v1/shutdown", b"{}", method="POST",
                      timeout=R.ACK_TIMEOUT_S)
            except Exception:
                pass
        for p in getattr(self, "_procs", []):
            try:
                p.wait(timeout=R.SHUTDOWN_TIMEOUT_S)
            except Exception:
                p.kill()


def launch_local_cluster(session, catalog_spec: str, nworkers: int = 2,
                         timeout: Optional[float] = None,
                         multihost: bool = False,
                         local_devices: int = 0) -> "ClusterSession":
    """Spawn worker OS processes on this host and return a ClusterSession
    driving them (the in-process DistributedQueryRunner analog, but with
    REAL process isolation — each worker is its own interpreter + XLA
    client; reference: TestingPrestoServer boots real HTTP servers).

    multihost=True boots the workers as one N-process `jax.distributed`
    mesh (worker k = process k, gloo collectives over loopback — the CI
    stand-in for a real multi-host DCN fabric); `local_devices` forces
    that many virtual CPU devices per process so the GLOBAL mesh has
    nworkers x local_devices devices."""
    import subprocess
    import sys

    from presto_tpu.parallel.mesh import refuse_cpu_children

    refuse_cpu_children("launch_local_cluster")
    timeout = R.STARTUP_TIMEOUT_S if timeout is None else timeout
    if cluster_secret() is None:
        set_cluster_secret(_pysecrets.token_hex(32))
    env = dict(os.environ)
    env[_SECRET_ENV] = cluster_secret().decode()
    env["PRESTO_TPU_WORKER_PROC"] = "1"  # crash faults really _exit
    extra: List[str] = []
    if multihost:
        import socket

        with socket.socket() as s:  # free port for the jax coordinator
            s.bind(("127.0.0.1", 0))
            dist_port = s.getsockname()[1]
        extra = ["--distributed-coordinator", f"127.0.0.1:{dist_port}",
                 "--num-processes", str(nworkers)]
        env["JAX_PLATFORMS"] = "cpu"
    if local_devices:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count="
                            f"{local_devices}").strip()
    procs = []
    urls = []
    for k in range(nworkers):
        p = subprocess.Popen(
            [sys.executable, "-m", "presto_tpu.parallel.cluster",
             "--catalog", catalog_spec]
            + (extra + ["--process-id", str(k)] if multihost else []),
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, env=env)
        procs.append(p)
    import select

    deadline = TR.wall_s() + timeout
    try:
        for p in procs:
            while True:
                remaining = deadline - TR.wall_s()
                if remaining <= 0:
                    raise TimeoutError("cluster startup timed out")
                ready, _, _ = select.select([p.stdout], [], [],
                                            min(remaining, 1.0))
                if not ready:
                    if p.poll() is not None:
                        raise RuntimeError(
                            f"worker process exited rc={p.returncode} "
                            "during startup")
                    continue
                line = p.stdout.readline()
                if not line:
                    raise RuntimeError("worker process died during startup")
                urls.append(json.loads(line)["url"])
                break
    except BaseException:
        for q in procs:  # no orphaned workers on a failed launch
            q.kill()
        raise
    cs = ClusterSession(session, urls)
    cs._procs = procs
    return cs


# ---------------------------------------------------------------------------
# worker process entry point
# ---------------------------------------------------------------------------


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description="presto_tpu cluster worker")
    ap.add_argument("--catalog", required=True,
                    help="catalog spec, e.g. tpch:0.01:/tmp/cache")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--platform", default="cpu",
                    help="jax platform for this worker (default cpu: "
                         "worker processes must not contend for the TPU)")
    ap.add_argument("--mesh", type=int, default=None,
                    help="device-mesh size this worker EXCLUSIVELY owns "
                         "(fragment-fusion target; default env "
                         "PRESTO_TPU_WORKER_MESH, else 0 = no mesh)")
    ap.add_argument("--distributed-coordinator", default=None,
                    help="jax.distributed coordinator host:port — this "
                         "worker joins the GLOBAL multi-host mesh as one "
                         "process (cross-host collective fusion); also "
                         "settable via PRESTO_TPU_MULTIHOST="
                         "addr:port,nproc,pid")
    ap.add_argument("--num-processes", type=int, default=1,
                    help="total processes in the jax.distributed mesh")
    ap.add_argument("--process-id", type=int, default=0,
                    help="this worker's rank in the jax.distributed mesh")
    args = ap.parse_args(argv)
    os.environ["PRESTO_TPU_WORKER_PROC"] = "1"  # crash faults really exit
    if args.platform != "default":
        import jax

        jax.config.update("jax_platforms", args.platform)
        os.environ.setdefault("PRESTO_TPU_PLATFORM", args.platform)
    # multi-host membership initializes BEFORE any backend use — jax
    # devices() after distributed init returns the GLOBAL device set
    # (parallel/mesh.py is the single owner of jax.distributed)
    from presto_tpu.parallel import mesh as MH

    if args.distributed_coordinator:
        MH.init_multihost(args.distributed_coordinator,
                          args.num_processes, args.process_id)
    else:
        MH.init_multihost_from_env()
    w = WorkerServer(args.catalog, args.host, args.port,
                     mesh_devices=args.mesh)
    print(json.dumps({"url": w.url}), flush=True)
    w.serve_forever()


if __name__ == "__main__":
    main()


def _rf_fragment_wiring(frag: Fragment):
    """Dynamic-filter wiring of one fragment: (produced, pushable,
    consumed).  `produced` = filter ids whose producer join executes in
    this fragment (its local executor registers them); `pushable` maps
    the subset whose BUILD keys arrive via an exchange input — i.e. this
    fragment's task can summarize the build host-side right after the
    pull and POST the summary to remote consumers — to {"eid", "sym",
    "kind"}; `consumed` = filter ids this fragment's scans consume."""
    from presto_tpu.plan import ir
    from presto_tpu.plan import nodes as P

    kind_of = {i.eid: i.kind for i in frag.inputs}
    produced: set = set()
    pushable: Dict[str, dict] = {}
    consumed: set = set()

    def resolve_exch(node, sym):
        while True:
            if isinstance(node, P.TableScan):
                if node.table.startswith("__exch_") \
                        and sym in node.assignments:
                    return int(node.table[len("__exch_"):]), sym
                return None
            if isinstance(node, P.Filter):
                node = node.source
            elif isinstance(node, P.Project):
                e = node.assignments.get(sym)
                if not isinstance(e, ir.Ref):
                    return None
                sym = e.name
                node = node.source
            else:
                return None

    def walk(node):
        for s in getattr(node, "sources", []):
            walk(s)
        if isinstance(node, P.TableScan):
            for spec in getattr(node, "rf_consume", None) or []:
                consumed.add(spec["fid"])
            return
        if isinstance(node, P.Join) and node.join_type in ("INNER",
                                                           "SEMI"):
            for spec in getattr(node, "rf_produce", None) or []:
                produced.add(spec["fid"])
                hit = resolve_exch(node.right, spec["build_sym"])
                if hit is not None:
                    eid, sym = hit
                    pushable[spec["fid"]] = {
                        "eid": eid, "sym": sym,
                        "kind": kind_of.get(eid, "")}

    walk(frag.root)
    return produced, pushable, consumed


def _classify_exchange_inputs(root):
    """Walk a fragment plan: exchange-scan eids under any join's BUILD
    (right) subtree vs elsewhere (probe/pass-through)."""
    build: set = set()
    probe: set = set()

    def walk(node, under_build):
        from presto_tpu.plan import nodes as P

        if isinstance(node, P.TableScan) and \
                node.table.startswith("__exch_"):
            eid = int(node.table[len("__exch_"):])
            (build if under_build else probe).add(eid)
            return
        if isinstance(node, P.Join):
            walk(node.left, under_build)
            walk(node.right, True)
            return
        for s in getattr(node, "sources", []):
            walk(s, under_build)

    walk(root, False)
    return build, probe - build


def _fragment_phases(fragments) -> Dict[int, int]:
    """Phase numbers per fragment id (reference:
    PhasedExecutionSchedule.extractPhases): for every consumer, the
    producers feeding a join's build side get a STRICTLY earlier phase
    than those feeding its probe side; a consumer starts no earlier
    than its latest producer."""
    phase = {f.fid: 0 for f in fragments}
    strict = []  # (must-finish-first fid, later fid)
    for frag in fragments:
        build_eids, probe_eids = _classify_exchange_inputs(frag.root)
        prod = {inp.eid: inp.producer for inp in frag.inputs}
        for be in build_eids:
            if be not in prod:
                continue
            # build producers strictly precede probe-side producers...
            for pe in probe_eids:
                if pe in prod and prod[be] != prod[pe]:
                    strict.append((prod[be], prod[pe]))
            # ...and the consuming fragment itself when its probe side
            # is a local scan (the consumer IS the probe stage)
            strict.append((prod[be], frag.fid))
    for _ in range(len(fragments) + 1):
        changed = False
        for a, b in strict:
            if phase[b] < phase[a] + 1:
                phase[b] = phase[a] + 1
                changed = True
        for frag in fragments:
            for inp in frag.inputs:
                if phase[frag.fid] < phase[inp.producer]:
                    phase[frag.fid] = phase[inp.producer]
                    changed = True
        if not changed:
            break
    return phase
