"""Statistics: column ranges, cardinalities, uniqueness, fanout bounds.

Reference parity: the CBO stats layer (presto-main/.../cost/, 44 files:
StatsCalculator + per-node rules producing PlanNodeStatsEstimate).  Here
stats serve a second, TPU-specific master: they make shapes STATIC —
group-by capacities, key-pack layouts, and join expansion bounds become
compile-time constants so whole plans jit with zero host syncs (the
difference between a fused XLA program and per-op host syncs).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, FrozenSet, List, Optional, Tuple

from presto_tpu.plan import ir
from presto_tpu.plan import nodes as P


@dataclasses.dataclass
class ColStats:
    min: Optional[float] = None  # range of the PHYSICAL representation
    max: Optional[float] = None
    ndv: Optional[int] = None  # distinct values


@dataclasses.dataclass
class NodeStats:
    rows: int  # row-count UPPER BOUND (static shape sizing must trust it)
    cols: Dict[str, ColStats]
    unique: List[FrozenSet[str]]  # symbol sets known unique per row
    # max rows matching any single value of these key sets (join fanout bound)
    fanout: Dict[FrozenSet[str], int]
    # CBO cardinality ESTIMATE (selectivity-aware, may undershoot; used for
    # join ordering + distribution choice, never for static sizing).
    # None -> fall back to rows.  Reference: PlanNodeStatsEstimate
    # outputRowCount vs our additional static-shape contract.
    est: Optional[float] = None

    @property
    def est_rows(self) -> float:
        return self.rows if self.est is None else self.est


def derive(node: P.PlanNode, catalog, memo=None) -> NodeStats:
    """Bottom-up stats derivation (reference: ComposableStatsCalculator
    visiting per-node rules).  The memo stores (node, stats) and checks
    identity on lookup: entries hold a strong ref so a memo that
    outlives temporaries (e.g. the ReorderJoins DP deriving stats for
    rejected candidate trees) can never serve stale stats through a
    recycled id()."""
    if memo is None:
        memo = {}
    hit = memo.get(id(node))
    if hit is not None and hit[0] is node:
        return hit[1]
    s = _derive(node, catalog, memo)
    memo[id(node)] = (node, s)
    return s


def _derive(node, catalog, memo) -> NodeStats:
    d = lambda n: derive(n, catalog, memo)
    if isinstance(node, P.TableScan):
        t = catalog.get(node.table)
        rows = t.row_count()
        cols = {}
        for sym, col in node.assignments.items():
            cs = t.column_stats(col) if hasattr(t, "column_stats") else None
            cols[sym] = cs or ColStats()
        col_to_sym = {}
        for sym, col in node.assignments.items():
            col_to_sym.setdefault(col, sym)
        unique = []
        fanout = {}
        if hasattr(t, "unique_keys"):
            for keyset in t.unique_keys():
                if all(c in col_to_sym for c in keyset):
                    fs = frozenset(col_to_sym[c] for c in keyset)
                    unique.append(fs)
                    fanout[fs] = 1
        if hasattr(t, "max_rows_per_key"):
            for keyset, bound in t.max_rows_per_key().items():
                if all(c in col_to_sym for c in keyset):
                    fanout[frozenset(col_to_sym[c] for c in keyset)] = bound
        return NodeStats(rows, cols, unique, fanout)
    if isinstance(node, P.Values):
        return NodeStats(len(node.rows), {s: ColStats() for s in node.symbols},
                         [], {})
    if isinstance(node, P.Filter):
        s = d(node.source)
        sel, cols = filter_selectivity(s, node.predicate)
        src = node.source
        while isinstance(src, P.Project):
            src = src.source
        if isinstance(src, (P.Aggregate, P.GroupingSets)) \
                and src.group_keys \
                and _refs_agg_output(node.predicate, src):
            # HAVING-style comparison against an aggregate output:
            # range selectivity is unknowable from column stats, and
            # such filters are characteristically sharp (Q18's
            # sum(l_quantity) > 300 keeps ~0.4% of groups).  The
            # reference uses an unknown-filter coefficient here too
            # (FilterStatsCalculator.UNKNOWN_FILTER_COEFFICIENT);
            # downstream consumers of est guard against underestimates
            # (pre-aggregation compaction aborts to dynamic).
            sel = min(sel, 0.05)
        est = max(1.0, s.est_rows * sel)
        return NodeStats(s.rows, cols, s.unique, s.fanout, est)
    if isinstance(node, P.Project):
        s = d(node.source)
        cols = {}
        rename: Dict[str, str] = {}
        for sym, e in node.assignments.items():
            if isinstance(e, ir.Ref):
                cols[sym] = s.cols.get(e.name, ColStats())
                rename.setdefault(e.name, sym)
            else:
                cols[sym] = ColStats()
        unique = []
        for u in s.unique:
            if all(x in rename for x in u):
                unique.append(frozenset(rename[x] for x in u))
        fanout = {}
        for k, b in s.fanout.items():
            if all(x in rename for x in k):
                fanout[frozenset(rename[x] for x in k)] = b
        return NodeStats(s.rows, cols, unique, fanout, s.est)
    if isinstance(node, P.Aggregate):
        s = d(node.source)
        cap = capacity_for_groups(node, s)
        cols = {k: s.cols.get(k, ColStats()) for k in node.group_keys}
        for sym, a in node.aggs.items():
            cols[sym] = ColStats()
        keyset = frozenset(node.group_keys)
        return NodeStats(cap, cols, [keyset] if node.group_keys else [],
                         {keyset: 1} if node.group_keys else {},
                         min(float(cap), s.est_rows))
    if isinstance(node, P.GroupingSets):
        # the sets' Aggregates, concatenated; (keys, group id) is unique
        s = d(node.source)
        caps = [capacity_for_groups(node.set_aggregate(i), s)
                for i in range(len(node.sets))]
        cols = {k: s.cols.get(k, ColStats()) for k in node.group_keys}
        for sym in node.aggs:
            cols[sym] = ColStats()
        cols[node.group_id] = ColStats(0, len(caps) - 1, len(caps))
        keyset = frozenset(node.group_keys + [node.group_id])
        return NodeStats(sum(caps), cols, [keyset], {keyset: 1},
                         sum(min(float(c), s.est_rows) for c in caps))
    if isinstance(node, P.Join):
        ls, rs = d(node.left), d(node.right)
        if node.join_type in ("SEMI", "ANTI"):
            # matching fraction ~= |distinct build keys| / ndv(probe key)
            # (containment assumption, reference SemiJoinStatsCalculator);
            # 0.5 when ndv is unknown
            frac = 0.5
            if node.criteria:
                lk, rk = node.criteria[0]
                lcs = ls.cols.get(lk)
                rcs = rs.cols.get(rk)
                # DISTINCT build keys, not build rows (duplicates do not
                # admit more probe rows)
                build_keys = rs.est_rows
                if rcs and rcs.ndv:
                    build_keys = min(build_keys, float(rcs.ndv))
                if lcs and lcs.ndv:
                    frac = min(1.0, build_keys / max(float(lcs.ndv), 1.0))
            if node.join_type == "ANTI":
                frac = 1.0 - frac
            est = max(ls.est_rows * frac, 1.0)
            return NodeStats(ls.rows, ls.cols, ls.unique, ls.fanout, est)
        if node.join_type == "MARK":
            # every left row survives, one extra boolean column
            cols = dict(ls.cols)
            cols[node.mark] = ColStats(ndv=2)
            return NodeStats(ls.rows, cols, ls.unique, ls.fanout,
                             ls.est_rows)
        cols = {**ls.cols, **rs.cols}
        rkeys = frozenset(rk for _, rk in node.criteria)
        build_unique = any(u <= rkeys for u in rs.unique)
        if node.join_type == "CROSS":
            rows = ls.rows * rs.rows
            return NodeStats(rows, cols, [], {},
                             ls.est_rows * rs.est_rows)
        est = join_cardinality(ls, rs, node.criteria)
        bound = rs.fanout.get(_best_fanout_key(rs, rkeys), None)
        if build_unique:
            rows = ls.rows
            unique = list(ls.unique)
            fanout = dict(ls.fanout)
        else:
            if bound is None:
                # a plain small constant here UNDERSHOOTS (rows is a
                # bound the planner must be able to trust)
                bound = speculative_fanout_bound(rs, node.criteria)
            rows = ls.rows * (bound if bound is not None else 4)
            unique, fanout = [], {}
        if node.join_type in ("LEFT", "FULL"):
            est = max(est, ls.est_rows)  # outer side survives
        return NodeStats(rows, cols, unique, fanout, min(est, float(rows)))
    if isinstance(node, (P.Sort, P.Limit, P.TopN)):
        s = d(node.source)
        rows = s.rows
        est = s.est_rows
        if isinstance(node, (P.Limit, P.TopN)):
            rows = min(rows, node.count)
            est = min(est, float(node.count))
        return NodeStats(rows, s.cols, s.unique, s.fanout, est)
    if isinstance(node, P.Union):
        subs = [d(x) for x in node.sources_]
        rows = sum(x.rows for x in subs)
        cols = {sym: ColStats() for sym in node.symbols}
        return NodeStats(rows, cols, [], {}, sum(x.est_rows for x in subs))
    if isinstance(node, P.Window):
        s = d(node.source)
        cols = dict(s.cols)
        for sym in node.functions:
            cols[sym] = ColStats()
        return NodeStats(s.rows, cols, s.unique, s.fanout, s.est)
    if isinstance(node, P.Unnest):
        s = d(node.source)
        cols = dict(s.cols)
        cols[node.out_sym] = ColStats()
        # ragged fanout unknown; 3x is the planning guess (not a bound:
        # UNNEST is dynamic-mode only, so nothing sizes statically off it)
        return NodeStats(s.rows * 3, cols, [], {}, s.est_rows * 3)
    if isinstance(node, P.Exchange):
        # exchanges move rows, they don't change global cardinality
        return d(node.source)
    if isinstance(node, P.Output):
        s = d(node.source)
        return NodeStats(s.rows, s.cols, s.unique, s.fanout, s.est)
    raise TypeError(f"no stats rule for {type(node).__name__}")


# ---------------------------------------------------------------------------
# CBO estimation rules (reference: cost/FilterStatsCalculator.java,
# cost/JoinStatsRule.java)
# ---------------------------------------------------------------------------

UNKNOWN_FILTER_COEFFICIENT = 0.9   # reference: FilterStatsCalculator default
COMPARISON_UNKNOWN = 1.0 / 3.0     # range predicate with unknown bounds
EQ_UNKNOWN = 0.1
LIKE_COEFFICIENT = 0.25


def _lit_value(e) -> Optional[float]:
    if isinstance(e, ir.Lit) and isinstance(e.value, (int, float, bool)):
        return float(e.value)
    return None


def _refs_agg_output(pred, agg) -> bool:
    """Does the predicate reference any AGGREGATE symbol (vs group key)?"""
    agg_syms = set(agg.aggs)
    return bool(pred.refs() & agg_syms)


def filter_selectivity(src: NodeStats, pred: ir.RowExpr
                       ) -> Tuple[float, Dict[str, ColStats]]:
    """Estimated fraction of rows surviving `pred`, plus narrowed column
    stats for range predicates (containment assumption, like the
    reference's FilterStatsCalculator)."""
    cols = dict(src.cols)
    sel = 1.0
    for c in ir.conjuncts(pred):
        sel *= _conjunct_selectivity(c, cols)
    return max(min(sel, 1.0), 1e-9), cols


def _conjunct_selectivity(c: ir.RowExpr, cols: Dict[str, ColStats]) -> float:
    if not isinstance(c, ir.Call):
        return UNKNOWN_FILTER_COEFFICIENT
    fn = c.fn
    if fn == "and":
        return (_conjunct_selectivity(c.args[0], cols)
                * _conjunct_selectivity(c.args[1], cols))
    if fn == "or":
        a = _conjunct_selectivity(c.args[0], dict(cols))
        b = _conjunct_selectivity(c.args[1], dict(cols))
        return min(1.0, a + b - a * b)
    if fn == "not":
        return max(0.0, 1.0 - _conjunct_selectivity(c.args[0], dict(cols)))
    if fn == "is_null":
        return 0.1
    if fn == "like":
        return LIKE_COEFFICIENT
    if fn == "in":
        # lowered as OR of eq upstream; if present directly, treat as eq*k
        return min(1.0, EQ_UNKNOWN * max(1, len(c.args) - 1))
    if fn in ("eq", "ne", "lt", "le", "gt", "ge") and len(c.args) == 2:
        a, b = c.args
        if isinstance(b, ir.Ref) and not isinstance(a, ir.Ref):
            a, b = b, a
            fn = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}.get(fn, fn)
        if not isinstance(a, ir.Ref):
            return UNKNOWN_FILTER_COEFFICIENT
        if isinstance(b, ir.Ref):
            # column-to-column comparison (non-join residual)
            return 0.5 if fn != "eq" else EQ_UNKNOWN
        v = _lit_value(b)
        cs = cols.get(a.name)
        if fn == "eq":
            if cs is not None and cs.ndv:
                return 1.0 / cs.ndv
            return EQ_UNKNOWN
        if fn == "ne":
            if cs is not None and cs.ndv:
                return 1.0 - 1.0 / cs.ndv
            return 1.0 - EQ_UNKNOWN
        if v is None or cs is None or cs.min is None or cs.max is None \
                or cs.max <= cs.min:
            return COMPARISON_UNKNOWN
        span = cs.max - cs.min
        if fn in ("lt", "le"):
            frac = (v - cs.min) / span
            new = ColStats(cs.min, min(cs.max, v), cs.ndv)
        else:
            frac = (cs.max - v) / span
            new = ColStats(max(cs.min, v), cs.max, cs.ndv)
        frac = max(0.0, min(1.0, frac))
        if frac > 0:
            # narrow only the RANGE (a guaranteed bound on surviving
            # rows); ndv * frac is an estimate, not a bound, and these
            # ColStats feed static group-capacity sizing which must
            # never undershoot (join_cardinality caps ndv by est_rows
            # itself, so estimates still benefit)
            cols[a.name] = ColStats(new.min, new.max, cs.ndv)
        return frac
    return UNKNOWN_FILTER_COEFFICIENT


def join_cardinality(ls: NodeStats, rs: NodeStats, criteria) -> float:
    """|L join R| ~= |L|*|R| / prod(max(ndv_l, ndv_r)) over the equi-keys,
    ndv capped by the side's estimated rows (containment assumption) —
    reference: JoinStatsRule's formula."""
    est = ls.est_rows * rs.est_rows
    if not criteria:
        return est
    for lk, rk in criteria:
        lcs, rcs = ls.cols.get(lk), rs.cols.get(rk)
        ndv_l = min(lcs.ndv, max(ls.est_rows, 1)) if lcs and lcs.ndv else None
        ndv_r = min(rcs.ndv, max(rs.est_rows, 1)) if rcs and rcs.ndv else None
        if ndv_l and ndv_r:
            denom = max(ndv_l, ndv_r)
        elif ndv_l or ndv_r:
            denom = ndv_l or ndv_r
        else:
            denom = max(ls.est_rows, rs.est_rows, 1.0) * EQ_UNKNOWN
        est /= max(denom, 1.0)
    return max(est, 1.0)


def speculative_fanout_bound(rs: NodeStats, criteria) -> Optional[int]:
    """Build-side fanout bound from ndv when no connector bound exists:
    ~4x the average rows-per-key, min over every criterion key (a
    composite-key match is at most any single key's fanout).  The ONE
    definition shared by the stats join rule, annotate_static_hints and
    the ReorderJoins cost model — the executor guards the actual counts
    and re-runs dynamically on overflow, so 4x average is safe to
    speculate."""
    bound = None
    for _lk, rk in criteria:
        cs = rs.cols.get(rk)
        if cs is not None and cs.ndv:
            b = max(4, math.ceil(rs.rows / cs.ndv) * 4)
            bound = b if bound is None else min(bound, b)
    return bound


def _best_fanout_key(stats: NodeStats, keys: FrozenSet[str]):
    best = None
    for k in stats.fanout:
        if k <= keys and (best is None or stats.fanout[k] < stats.fanout[best]):
            best = k
    return best


def capacity_for_groups(node: P.Aggregate, src: NodeStats) -> int:
    """Static group capacity = product of key cardinalities, clamped to
    input rows; power-of-two padded."""
    cap = 1
    for k in node.group_keys:
        cs = src.cols.get(k)
        if cs is not None and cs.ndv:
            card = cs.ndv + 1
        elif cs is not None and cs.min is not None and cs.max is not None:
            card = int(cs.max - cs.min) + 2
        else:
            card = src.rows
        cap = min(cap * card, src.rows)
        if cap >= src.rows:
            return src.rows
    return max(int(2 ** math.ceil(math.log2(max(cap, 1)))), 1)
