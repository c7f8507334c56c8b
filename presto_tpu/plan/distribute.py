"""Distribution planning: insert Exchange nodes + split aggregations.

Reference parity: sql/planner/optimizations/AddExchanges.java (chooses
SINGLE/FIXED_HASH/FIXED_BROADCAST distributions and inserts remote
exchanges), DetermineJoinDistributionType (partitioned-vs-broadcast by
build-side size), and the partial->final aggregation split
(AddExchanges.java:239-265).  The output plan still executes single-pass —
a DistExecutor traces it inside ONE shard_map where each Exchange becomes
a collective (parallel/exchange.py).

Distribution lattice per node:
  any        — rows sharded arbitrarily over the mesh axis (SOURCE dist)
  hashed(K)  — sharded; all rows with equal values of K on one shard
  replicated — every shard holds every row (post-gather / broadcast)
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Dict, List, Optional, Tuple

from presto_tpu.plan import agg_strategy as AS
from presto_tpu.plan import ir
from presto_tpu.plan import nodes as P
from presto_tpu import types as T


@dataclasses.dataclass(frozen=True)
class Dist:
    kind: str  # 'any' | 'hashed' | 'replicated'
    keys: Tuple[str, ...] = ()


ANY = Dist("any")
REPLICATED = Dist("replicated")


class Undistributable(Exception):
    """Plan shape the distributed planner can't place; caller runs the
    single-device path instead."""


def distribute(plan: P.QueryPlan, session, ndev: int,
               bucketed=None) -> P.QueryPlan:
    """Rewrite an optimized single-device plan into a distributed one.
    Subplans (uncorrelated scalars) stay single-device — they are evaluated
    host-side before the superstep, like the reference's pre-requisite
    stages feeding a gather exchange.

    `bucketed` ({table: bucket column}) switches the planner into
    chunked/grouped-execution mode (reference: connector bucketing +
    grouped execution, BucketNodeMap + Lifespan): scans of bucketed
    tables are hashed on the bucket column (all rows of one bucket land
    in one chunk — range-bucketing colocates equi-joins the same way
    hash-bucketing does), every other scan is replicated (resident whole
    in HBM, visible to every chunk)."""
    d = Distributer(session, ndev, bucketed=bucketed)
    # subplans run in the SAME trace (not host-side) so float reduction
    # order — and therefore sums compared against the main plan, e.g.
    # TPC-H Q15's total_revenue = (select max(...)) — is bit-identical
    subplans = {}
    for pid, sub in sorted(plan.subplans.items()):
        snode, sdist = d.visit(sub)
        if sdist.kind != "replicated":
            snode = P.Exchange(snode, "gather")
        subplans[pid] = snode
    root, dist = d.visit(plan.root.source)
    if dist.kind != "replicated":
        root = P.Exchange(root, "gather")
    # post-exchange iterative rules (the reference runs e.g.
    # PushPartialAggregationThroughExchange AFTER AddExchanges,
    # PlanOptimizers.java:230-424)
    from presto_tpu.plan.iterative import (
        IterativeOptimizer, PushPartialAggregationThroughExchange)

    root = IterativeOptimizer(
        [PushPartialAggregationThroughExchange(session)]).optimize(root)
    out = P.Output(root, plan.root.names, plan.root.symbols)
    dplan = P.QueryPlan(out, subplans, plan.grouping_set_branches)
    # fragment-fusion economics (plan/fusion_cost.py): stamp every
    # Exchange node with stats-derived est_rows/est_bytes hints so the
    # coordinator's per-edge fuse-vs-cut pricing (and anything reading
    # the serde'd fragments) knows what each edge moves
    from presto_tpu.plan import fusion_cost as FC

    FC.annotate_exchange_bytes(dplan, session)
    return dplan


# aggregate fns that have a (partial fns -> final merge fn) decomposition
_MERGEABLE = {"count", "count_if", "sum", "min", "max", "avg",
              "bool_and", "every", "bool_or", "arbitrary", "any_value",
              "stddev", "stddev_samp", "stddev_pop",
              "variance", "var_samp", "var_pop",
              "min_by", "max_by", "checksum"}


def _sketch_mergeable(a: ir.AggCall) -> bool:
    """True when this sketch-family aggregate decomposes into a
    fixed-width device state (plan/agg_strategy.SKETCH_FNS).  The
    array-of-percentiles / weighted approx_percentile overloads have no
    fixed-shape state and keep the single-phase repartition route."""
    if a.distinct:
        return False
    if a.fn == "approx_percentile":
        return len(a.args) == 2 and a.type.name != "ARRAY"
    return a.fn in ("approx_distinct", "approx_count", "approx_sum")


class Distributer:
    def __init__(self, session, ndev: int = 1, bucketed=None):
        self.session = session
        self.ndev = ndev
        self.bucketed = bucketed or {}  # table -> bucket column (chunk mode)
        self.broadcast_rows = int(session.properties.get(
            "broadcast_join_threshold_rows", 1_000_000))
        if self.bucketed:
            # chunk mode: a "broadcast" build side is ONE resident
            # on-chip buffer shared by the sequential chunk loop, not a
            # per-shard copy — the economic threshold is HBM headroom,
            # not replication cost (q64's cs_ui at SF100 is ~1.8M rows
            # and must stay resident or the repartition path buffers
            # the 10x bigger store join output instead)
            self.broadcast_rows = int(session.properties.get(
                "chunk_broadcast_rows", 8_000_000))
        self.dist_sort_threshold = int(session.properties.get(
            "distributed_sort_threshold_rows", 100_000))
        self.partial_agg_groups = int(session.properties.get(
            "partial_aggregation_max_groups", 8192))
        self._ctr = 0
        # symbol equivalence classes from equi-join criteria and identity
        # projections (reference: AddExchanges' partitioning properties
        # carry symbol equivalences, so hashed(l_orderkey) satisfies a
        # requirement for hashed(o_orderkey) after l_orderkey=o_orderkey)
        self._equiv: dict = {}

    def fresh(self, base: str) -> str:
        self._ctr += 1
        return f"{base}$d{self._ctr}"

    def _find(self, s: str) -> str:
        root = s
        while self._equiv.get(root, root) != root:
            root = self._equiv[root]
        while self._equiv.get(s, s) != root:  # path compression
            self._equiv[s], s = root, self._equiv[s]
        return root

    def _union(self, a: str, b: str) -> None:
        ra, rb = self._find(a), self._find(b)
        if ra != rb:
            self._equiv[ra] = rb

    def _same_keys(self, keys_a, keys_b) -> bool:
        return [self._find(k) for k in keys_a] == \
            [self._find(k) for k in keys_b]

    def _keys_subset(self, keys, of) -> bool:
        reps = {self._find(k) for k in of}
        return all(self._find(k) in reps for k in keys)

    def _colocated(self, ldist, rdist, criteria) -> bool:
        """Both sides hashed on keys that some pairing of the equi-join
        criteria makes equal — regardless of criteria ORDER (hashed(K)
        colocates any join whose criteria CONTAIN K=K': q64 writes
        `ss_item_sk = sr_item_sk AND ss_ticket_number = sr_ticket_number`
        and both sides are bucketed on the ticket, the second
        criterion).  Reference: AddExchanges' partitioning-properties
        satisfaction is set-based the same way."""
        if not (ldist.kind == "hashed" and rdist.kind == "hashed"
                and len(ldist.keys) == len(rdist.keys)):
            return False
        pair = {}
        for lk, rk in criteria:
            pair.setdefault(self._find(lk), self._find(rk))
        want = [pair.get(self._find(lk)) for lk in ldist.keys]
        return (None not in want
                and want == [self._find(rk) for rk in rdist.keys])

    # ------------------------------------------------------------------
    def visit(self, node: P.PlanNode) -> Tuple[P.PlanNode, Dist]:
        m = getattr(self, f"_visit_{type(node).__name__.lower()}", None)
        if m is None:
            raise Undistributable(type(node).__name__)
        return m(node)

    def _visit_tablescan(self, node: P.TableScan):
        if self.bucketed:
            bcol = self.bucketed.get(node.table)
            if bcol is None:
                return node, REPLICATED  # resident table: whole per chunk
            syms = [s for s, c in node.assignments.items() if c == bcol]
            if syms:
                return node, Dist("hashed", (syms[0],))
            return node, ANY
        return node, ANY

    def _visit_values(self, node: P.Values):
        return node, REPLICATED

    def _visit_filter(self, node: P.Filter):
        src, dist = self.visit(node.source)
        node.source = src
        return node, dist

    def _visit_project(self, node: P.Project):
        src, dist = self.visit(node.source)
        node.source = src
        if dist.kind == "hashed":
            # hashed keys survive only through identity projections
            rename = {}
            for sym, e in node.assignments.items():
                if isinstance(e, ir.Ref):
                    rename.setdefault(e.name, sym)
            if all(k in rename for k in dist.keys):
                for old, new in rename.items():
                    self._union(old, new)  # identity: same values
                dist = Dist("hashed", tuple(rename[k] for k in dist.keys))
            else:
                dist = ANY
        return node, dist

    # ---- aggregation --------------------------------------------------
    def _visit_aggregate(self, node: P.Aggregate):
        src, dist = self.visit(node.source)
        node.source = src
        if dist.kind == "replicated":
            return node, REPLICATED
        if dist.kind == "hashed" and node.group_keys and \
                self._keys_subset(dist.keys, node.group_keys):
            # co-located: every group entirely on one shard
            return node, Dist("hashed", dist.keys)
        has_distinct = any(a.distinct for a in node.aggs.values())
        mergeable = all((a.fn in _MERGEABLE or _sketch_mergeable(a))
                        and not a.distinct
                        for a in node.aggs.values())
        # sketch aggregates (HLL registers / KLL summaries / seeded
        # samples): the partial state is fixed-width per group no matter
        # the input cardinality, so a hash repartition is NEVER cut for
        # them — the partial/final split's gather edge merges states
        # with one elementwise fold (lax.pmax on the fused mesh lane)
        has_sketch = any(_sketch_mergeable(a) for a in node.aggs.values())
        cap = getattr(node, "capacity_hint", None)
        small = cap is not None and cap <= self.partial_agg_groups
        # aggregation strategy (plan/agg_strategy.py): a final_only
        # aggregate routes rows to their group's shard and aggregates
        # ONCE — the global-table route, no partial stage planned at
        # all.  Chunked distribution (self.bucketed) is exempt: a
        # repartition exchange between chunk fragments buffers at input
        # scale, where the per-chunk partial state is tiny — there the
        # partial stays planned and the RUNTIME bypass adapts instead.
        strategy = getattr(node, "agg_strategy", None) \
            if AS.enabled(self.session) else None
        # final_only (repartition + single pass, the global-table route)
        # is consumed only where it can actually win:
        # - skew floor: with fewer distinct keys than ~4x the shard
        #   count, the hash repartition lands everything on a few
        #   shards (q1's four group combos over 8 devices overflow the
        #   in-trace all_to_all capacity);
        # - exchange-volume guard: the repartition moves EVERY input
        #   row, while two-phase exchanges ~ndev x groups partial rows —
        #   a strongly-reducing input (a 5-group GROUP BY over 15k rows)
        #   stays on the tiny-partial split; final_only wins exactly
        #   when the partial would NOT have reduced the exchange much.
        est = getattr(node, "input_est_hint", None)
        final_only = (node.group_keys and mergeable and not has_distinct
                      and not self.bucketed and strategy == AS.FINAL_ONLY
                      and cap is not None and cap >= 4 * self.ndev
                      and est is not None
                      and est <= cap * self.ndev * 4)
        # chunked (virtual-time-axis) distribution: a repartition
        # exchange between chunk fragments buffers at input scale
        # either way, so a high-estimated-NDV GROUP BY keeps the
        # partial/final split WITH THE RUNTIME BYPASS ARMED — the
        # partial probes its own reduction ratio and flips to
        # pass-through when it isn't paying (the adaptive plan is never
        # much worse than single-phase and wins whenever the estimate
        # was wrong the other way)
        adaptive_chunked = (self.bucketed and node.group_keys and mergeable
                            and not has_distinct
                            and strategy in (AS.TWO_PHASE, AS.ONE_PASS))
        if node.group_keys and (has_distinct or not mergeable
                                or (not small and not adaptive_chunked
                                    and not has_sketch)
                                or final_only):
            # repartition rows so each group lands wholly on one shard,
            # then aggregate locally in a single phase (handles DISTINCT
            # and non-decomposable aggregates for free; also the
            # final_only strategy's single global grouping pass)
            node.source = P.Exchange(src, "repartition", list(node.group_keys))
            return node, Dist("hashed", tuple(node.group_keys))
        if not mergeable:
            raise Undistributable(
                f"global aggregate with non-mergeable fns "
                f"{[a.fn for a in node.aggs.values()]}")
        return self._split_partial_final(node, src)

    def _visit_groupingsets(self, node: P.GroupingSets):
        """The node with PARTIAL calls on every shard (one source, every
        set) -> the sets' states moved -> one FINAL merge over (keys,
        group id): `_split_partial_final` for a node whose rows carry
        their set's index.  The states are gathered where all the sets
        together stay under `partial_aggregation_max_groups` (q36: 161
        groups) and where an aggregate's state is a sketch; else they
        are repartitioned by (keys, group id), so that a shard merges
        its share of the groups (q27: ~0.5 M).  (keys, group id) is
        unique in the node's output, and it is what the output is
        hashed on then."""
        src, dist = self.visit(node.source)
        node.source = src
        if dist.kind == "replicated":
            return node, REPLICATED
        if self.bucketed:
            # a chunk's states would have to be rolled up across chunks:
            # the chunk loop has no such stage for this node yet
            raise Undistributable("GroupingSets in the chunk loop")
        unsplit = sorted({("DISTINCT " if a.distinct else "") + a.fn
                          for a in node.aggs.values() if a.distinct
                          or not (a.fn in _MERGEABLE or _sketch_mergeable(a))})
        if unsplit:
            raise Undistributable(
                f"GroupingSets with aggregates that have no partial state "
                f"{unsplit}")
        partial_aggs, final_aggs = self._decompose_aggs(node.aggs)
        partial = P.GroupingSets(src, list(node.group_keys), node.sets,
                                 partial_aggs, node.group_id, node.hints)
        partial.step = "PARTIAL"
        merge_keys = list(node.group_keys) + [node.group_id]
        caps = [h.get("capacity_hint") for h in node.hints]
        cap = sum(caps) if caps and None not in caps else None
        sketch = any(_sketch_mergeable(a) for a in node.aggs.values())
        if sketch or (cap is not None and cap <= self.partial_agg_groups):
            moved, out = P.Exchange(partial, "gather"), REPLICATED
            if sketch:
                moved.sketch_only = True    # fixed-width states, as above
        else:
            moved = P.Exchange(partial, "repartition", merge_keys)
            out = Dist("hashed", tuple(merge_keys))
        # the merge is ONE Aggregate over (keys, group id): its hints are
        # the sets' taken together
        from presto_tpu.plan.stats import ColStats

        key_stats = {node.group_id: ColStats(0, len(node.sets) - 1,
                                             len(node.sets))}
        for h in node.hints:
            key_stats.update(h.get("key_stats") or {})
        final = P.GroupingSets(moved, list(node.group_keys), node.sets,
                               final_aggs, node.group_id)
        final.step = "FINAL"
        final.merge_hints = {"capacity_hint": cap, "key_stats": key_stats}
        ests = [h.get("input_est_hint") for h in node.hints]
        if moved.kind == "repartition" and None not in caps + ests:
            # the live states ONE chip receives: a shard's partial holds
            # at most min(its groups, its rows) a set, the repartition
            # spreads the sum over the chips (the compaction's own
            # doubling is the margin for hash skew)
            live = sum(min(c * self.ndev, e) for c, e in zip(caps, ests))
            final.merge_hints["input_est_hint"] = -(-live // self.ndev)
        return final, out

    def decompose_aggs(self, aggs):
        """(partial_aggs, final_aggs) for a mergeable aggregate map, or
        (None, None) when some aggregate has no partial/final
        decomposition (shared by _split_partial_final and the
        PushPartialAggregationThroughExchange rule)."""
        try:
            return self._decompose_aggs(aggs)
        except Undistributable:
            return None, None

    def _split_partial_final(self, node: P.Aggregate, src: P.PlanNode):
        """partial agg per shard -> gather -> final merge (the reference's
        PARTIAL/FINAL AggregationNode pair around a repartition,
        AddExchanges.java:239; here the combine is a gather because the
        partial output is tiny — <= partial_aggregation_max_groups rows)."""
        partial_aggs, final_aggs = self._decompose_aggs(node.aggs)
        partial = P.Aggregate(src, list(node.group_keys), partial_aggs, "PARTIAL")
        partial.capacity_hint = getattr(node, "capacity_hint", None)
        partial.key_stats = getattr(node, "key_stats", {})
        if AS.enabled(self.session):
            # the split plans two phases: the partial carries the
            # strategy (one_pass keeps the per-shard run-boundary
            # grouping; anything else is two_phase with the runtime
            # bypass armed) so executors count what actually ran and
            # the flip monitor knows its node.  Ordering hints move to
            # the partial with it — the partial's source IS the node's
            # source, so the claims (still guard-verified) transfer.
            s = getattr(node, "agg_strategy", None)
            partial.agg_strategy = s if s in (AS.ONE_PASS, AS.SKETCH) \
                else AS.TWO_PHASE
            for h in ("ordering_hint", "ordering_pack_order",
                      "ordering_hint_safe", "input_est_hint"):
                if hasattr(node, h):
                    setattr(partial, h, getattr(node, h))
        gathered = P.Exchange(partial, "gather")
        if any(_sketch_mergeable(a) for a in node.aggs.values()):
            # sketch-state edge: fixed-width mergeable rows.  Stamped so
            # fusion_cost prices it on the near-zero sketch lane and
            # cluster fragment cutting knows no repartition was needed.
            gathered.sketch_only = True
            if not node.group_keys and all(
                    a.fn == "$hll_partial" for a in partial_aggs.values()):
                # global HLL merge IS elementwise max over aligned
                # register rows: the fused mesh lane lowers this gather
                # to ONE lax.pmax collective (grouped states shard their
                # group slots data-dependently, so anything grouped —
                # and KLL's sort-merge — stays on all_gather + re-group)
                gathered.sketch_merge = "pmax"
        final = P.Aggregate(gathered, list(node.group_keys), final_aggs, "FINAL")
        final.capacity_hint = getattr(node, "capacity_hint", None)
        final.key_stats = getattr(node, "key_stats", {})
        return final, REPLICATED

    def _decompose_aggs(self, aggs):
        partial_aggs = {}
        final_aggs = {}
        for sym, a in aggs.items():
            fn = a.fn
            if fn in ("count", "count_if"):
                p = self.fresh(sym)
                partial_aggs[p] = a
                final_aggs[sym] = ir.AggCall("merge_count", (ir.Ref(p, T.BIGINT),),
                                             a.type)
            elif fn == "sum":
                p = self.fresh(sym)
                partial_aggs[p] = a
                final_aggs[sym] = ir.AggCall("sum", (ir.Ref(p, a.type),), a.type)
            elif fn in ("min", "max", "bool_and", "every", "bool_or",
                        "arbitrary", "any_value"):
                p = self.fresh(sym)
                partial_aggs[p] = a
                final_aggs[sym] = ir.AggCall(a.fn, (ir.Ref(p, a.type),), a.type)
            elif fn == "avg":
                ps = self.fresh(sym + "_s")
                pc = self.fresh(sym + "_c")
                partial_aggs[ps] = ir.AggCall("partial_sum_double", a.args,
                                              T.DOUBLE, False, a.filter)
                partial_aggs[pc] = ir.AggCall("count", a.args, T.BIGINT,
                                              False, a.filter)
                final_aggs[sym] = ir.AggCall(
                    "merge_avg", (ir.Ref(ps, T.DOUBLE), ir.Ref(pc, T.BIGINT)),
                    T.DOUBLE)
            elif fn in ("min_by", "max_by"):
                # partial keeps (winning value, winning key); final
                # re-runs the same argmin/argmax over the partials
                pv = self.fresh(sym + "_v")
                pk = self.fresh(sym + "_k")
                key_t = a.args[1].type if hasattr(a.args[1], "type") else a.type
                partial_aggs[pv] = a
                partial_aggs[pk] = ir.AggCall(
                    "min" if fn == "min_by" else "max", (a.args[1],),
                    key_t, False, a.filter)
                final_aggs[sym] = ir.AggCall(
                    fn, (ir.Ref(pv, a.type), ir.Ref(pk, key_t)), a.type)
            elif fn == "checksum":
                # wrapping sum is associative/commutative: sum the partials
                p = self.fresh(sym)
                partial_aggs[p] = a
                final_aggs[sym] = ir.AggCall("sum", (ir.Ref(p, T.BIGINT),),
                                             T.BIGINT)
            elif fn == "approx_distinct":
                # partial = (n_groups, m) HLL register rows; final folds
                # rows with elementwise max and estimates (exec/kernels
                # hll_partial / hll_merge_estimate) — estimates match
                # the single-pass kernel bit-for-bit at equal m
                from presto_tpu.exec.kernels import hll_m_for_error

                m = 1024
                if len(a.args) >= 2 and isinstance(a.args[1], ir.Lit) \
                        and a.args[1].value is not None:
                    m = hll_m_for_error(float(a.args[1].value))
                st = T.hll_state(m)
                p = self.fresh(sym)
                partial_aggs[p] = ir.AggCall("$hll_partial", (a.args[0],),
                                             st, False, a.filter)
                final_aggs[sym] = ir.AggCall("$hll_est",
                                             (ir.Ref(p, st),), T.BIGINT)
            elif fn == "approx_percentile" and _sketch_mergeable(a):
                # partial = (n_groups, 2K) quantile summary rows; the
                # percentile fraction literal rides the FINAL call.  K
                # sizes rank error ~1/K per merge level (session knob
                # approx_percentile_accuracy, default 0.01 -> K=200)
                acc = float(self.session.properties.get(
                    "approx_percentile_accuracy", 0.01))
                kk = max(16, int(math.ceil(2.0 / max(acc, 1e-6))))
                st = T.kll_state(2 * kk)
                p = self.fresh(sym)
                partial_aggs[p] = ir.AggCall("$kll_partial", (a.args[0],),
                                             st, False, a.filter)
                final_aggs[sym] = ir.AggCall(
                    "$kll_pct", (ir.Ref(p, st), a.args[1]), a.type)
            elif fn in ("approx_count", "approx_sum"):
                # the seeded sample is value-hash-determined, so the fn
                # is its own partial and the final just sums partials
                p = self.fresh(sym)
                partial_aggs[p] = a
                final_aggs[sym] = ir.AggCall(
                    "merge_count" if fn == "approx_count" else "sum",
                    (ir.Ref(p, a.type),), a.type)
            elif fn in ("approx_percentile", "geometric_mean", "corr",
                        "covar_samp", "covar_pop"):
                # array/weighted percentile forms and moment aggregates:
                # no fixed-shape partial state -> single-device
                # execution stays correct
                raise Undistributable(f"aggregate {fn}")
            elif fn in ("stddev", "stddev_samp", "stddev_pop", "variance",
                        "var_samp", "var_pop"):
                s1 = self.fresh(sym + "_s1")
                s2 = self.fresh(sym + "_s2")
                pc = self.fresh(sym + "_c")
                partial_aggs[s1] = ir.AggCall("partial_sum_double", a.args,
                                              T.DOUBLE, False, a.filter)
                partial_aggs[s2] = ir.AggCall("partial_sum_sq_double", a.args,
                                              T.DOUBLE, False, a.filter)
                partial_aggs[pc] = ir.AggCall("count", a.args, T.BIGINT,
                                              False, a.filter)
                final_aggs[sym] = ir.AggCall(
                    f"merge_{fn}",
                    (ir.Ref(s1, T.DOUBLE), ir.Ref(s2, T.DOUBLE),
                     ir.Ref(pc, T.BIGINT)), T.DOUBLE)
            else:
                raise Undistributable(f"aggregate {fn}")
        return partial_aggs, final_aggs

    # ---- joins --------------------------------------------------------
    def _visit_join(self, node: P.Join):
        left, ldist = self.visit(node.left)
        right, rdist = self.visit(node.right)
        node.left, node.right = left, right
        jt = node.join_type

        if ldist.kind == "replicated" and rdist.kind == "replicated":
            return node, REPLICATED

        if jt in ("RIGHT", "FULL") and node.criteria:
            # partitioned outer joins (reference: LookupOuterOperator +
            # AddExchanges): hash-repartition BOTH sides on the join keys
            # so matched pairs AND unmatched rows of either side are
            # decidable shard-locally.  Broadcast is never legal here —
            # a replicated side would emit its unmatched rows once per
            # shard.
            lkeys0 = [lk for lk, _ in node.criteria]
            rkeys0 = [rk for _, rk in node.criteria]
            if not self._colocated(ldist, rdist, node.criteria):
                # a replicated side must be scattered before the
                # repartition or every shard contributes a duplicate
                # copy of each row to the exchange (same rule as the
                # INNER repartition path below; exposed by q51's FULL
                # join over a gathered CTE)
                lsrc = P.Exchange(left, "scatter") \
                    if ldist.kind == "replicated" else left
                rsrc = P.Exchange(right, "scatter") \
                    if rdist.kind == "replicated" else right
                node.left = P.Exchange(lsrc, "repartition", lkeys0)
                node.right = P.Exchange(rsrc, "repartition", rkeys0)
            # output is NOT hashed on the keys: NULL-extended rows land
            # on shards by the OTHER side's hash, so the NULL key group
            # is scattered — downstream consumers must re-exchange
            return node, ANY
        if jt in ("RIGHT", "FULL"):
            node.left = self._to_replicated(left, ldist)
            node.right = self._to_replicated(right, rdist)
            return node, REPLICATED

        if jt == "CROSS":
            if rdist.kind != "replicated":
                node.right = P.Exchange(right, "broadcast")
            if ldist.kind == "replicated":
                return node, REPLICATED
            return node, ANY

        lkeys = [lk for lk, _ in node.criteria]
        rkeys = [rk for _, rk in node.criteria]

        if jt == "INNER":
            # equi-criteria make the key symbols equivalent in the output
            # (INNER only: outer joins NULL-extend one side)
            for lk, rk in node.criteria:
                self._union(lk, rk)

        # probe replicated + build sharded: each probe row would match on
        # every shard; make the build side whole instead (small by stats)
        if ldist.kind == "replicated":
            node.right = self._to_replicated(right, rdist)
            return node, REPLICATED

        build_rows = self._estimated_rows(node.right)
        broadcast_ok = (rdist.kind == "replicated"
                        or (build_rows is not None
                            and build_rows <= self.broadcast_rows))
        if self._colocated(ldist, rdist, node.criteria):
            out_dist = Dist("hashed", ldist.keys)
            return node, out_dist
        broadcast_ok = broadcast_ok or self._lookup_moves_less(node,
                                                               build_rows)
        if broadcast_ok and node.distribution != "PARTITIONED":
            if rdist.kind != "replicated":
                node.right = P.Exchange(right, "broadcast")
            if self._star_lookup(node, build_rows):
                node.star_lookup = True
            # probe side keeps its distribution
            return node, ldist
        # P1: repartition both sides on the join keys
        node.left = P.Exchange(left, "repartition", lkeys)
        node.right = P.Exchange(right, "repartition", rkeys)
        if rdist.kind == "replicated":
            # replicated build must be scattered first or every shard
            # contributes a duplicate copy of each row to the exchange
            node.right = P.Exchange(P.Exchange(right, "scatter"),
                                    "repartition", rkeys)
        return node, Dist("hashed", tuple(lkeys))

    def _dense_lookup_rows(self, node: P.Join, build_rows):
        """(build rows, probe rows), the planner's upper bounds, where
        the probe finds its build row by the build's dense key
        (`index_lookup`, identity layout: no sort of the build, so a
        whole copy on every shard costs its transfer and nothing else);
        else None.  Not in the chunk loop, whose broadcast threshold is
        HBM headroom."""
        il = getattr(node, "index_lookup", None)
        if il is None or self.bucketed or build_rows is None or \
                (il.get("block_keys", 1), il.get("block_rows", 1)) != (1, 1):
            return None
        probe_rows = self._estimated_rows(node.left)
        return None if probe_rows is None else (build_rows, probe_rows)

    def _lookup_moves_less(self, node: P.Join, build_rows) -> bool:
        """A star join whose dimension is past the row threshold (sf100:
        item 1.8 M rows, customer_demographics 1.92 M): the choice goes
        by bytes moved.  A dense lookup's build is broadcast if its
        columns times the shards are fewer bytes than the probe side's
        rows, which a repartition of both sides would move instead.
        `broadcast_join_threshold_rows` 0 still means no broadcast."""
        rows = self._dense_lookup_rows(node, build_rows)
        if rows is None or self.broadcast_rows <= 0:    # broadcasts are off
            return False
        from presto_tpu.plan.fusion_cost import _row_bytes

        return rows[0] * _row_bytes(node.right.outputs()) * self.ndev \
            <= rows[1] * _row_bytes(node.left.outputs())

    def _star_lookup(self, node: P.Join, build_rows) -> bool:
        """A dense lookup whose probe shard is at least
        `gather._SMALL_SOURCE_RATIO` times its replicated build (a fact
        table against a dimension): the mesh executor takes the index
        join for it, which a shard's scan slices otherwise forbid, and
        traces no runtime filter for it (a mask over the fact table's
        rows for a probe that is one gather).  A join of nearer sizes
        keeps the sort join and the filter that its program was compiled
        with (TPC-H Q3's customer join on the mesh; PERF.md section 7)."""
        rows = self._dense_lookup_rows(node, build_rows)
        if rows is None:
            return False
        from presto_tpu.exec.gather import _SMALL_SOURCE_RATIO

        return rows[1] >= _SMALL_SOURCE_RATIO * rows[0] * self.ndev

    def _to_replicated(self, node: P.PlanNode, dist: Dist) -> P.PlanNode:
        return node if dist.kind == "replicated" else P.Exchange(node, "gather")

    def _estimated_rows(self, node: P.PlanNode) -> Optional[int]:
        try:
            from presto_tpu.plan import stats as S

            return S.derive(node, self.session.catalog).rows
        except Exception:
            return None

    # ---- order/limit/misc --------------------------------------------
    def _visit_sort(self, node: P.Sort):
        src, dist = self.visit(node.source)
        rows = self._estimated_rows(src)
        small = rows is not None and rows <= self.dist_sort_threshold
        if dist.kind != "replicated" and not small:
            # P11 distributed sample-sort: range all_to_all on the primary
            # key, local full sort per shard, ordered gather — shard i's
            # rows all precede shard i+1's, so the concatenation IS the
            # merge (reference: partial sort + MergeOperator,
            # admin/dist-sort.rst)
            ex = P.Exchange(src, "range")
            ex.sort_keys = list(node.keys)
            local = P.Sort(ex, list(node.keys))
            return P.Exchange(local, "gather"), REPLICATED
        node.source = self._to_replicated(src, dist)
        return node, REPLICATED

    def _visit_topn(self, node: P.TopN):
        src, dist = self.visit(node.source)
        if dist.kind == "replicated":
            node.source = src
            return node, REPLICATED
        # local top-N per shard, then gather + final top-N: the
        # distributed-sort pattern (partial sort + MergeOperator,
        # SURVEY.md P11) with N small enough to replicate
        local = P.TopN(src, list(node.keys), node.count)
        node.source = P.Exchange(local, "gather")
        return node, REPLICATED

    def _visit_limit(self, node: P.Limit):
        src, dist = self.visit(node.source)
        if dist.kind == "replicated":
            node.source = src
            return node, REPLICATED
        local = P.Limit(src, node.count)
        node.source = P.Exchange(local, "gather")
        return node, REPLICATED

    def _visit_union(self, node: P.Union):
        new_sources = []
        for s in node.sources_:
            src, dist = self.visit(s)
            if dist.kind == "replicated":
                src = P.Exchange(src, "scatter")
            new_sources.append(src)
        node.sources_ = new_sources
        if node.distinct:
            raise Undistributable("UNION DISTINCT")  # planner lowers it to agg
        return node, ANY

    def _visit_unnest(self, node):
        # row-local expansion: each row explodes on its own shard, so the
        # source distribution passes through (hashed keys survive since
        # source columns are preserved in the output)
        src, dist = self.visit(node.source)
        node.source = src
        return node, dist

    def _visit_window(self, node: P.Window):
        src, dist = self.visit(node.source)
        if node.partition_by:
            # hash-partitioned window execution: all rows of a window
            # partition land on one shard, local sorted-scan windows per
            # shard (reference: WindowOperator + AddExchanges inserting a
            # partitioned exchange on the partition keys)
            if dist.kind == "replicated" or (
                    dist.kind == "hashed"
                    and self._keys_subset(dist.keys, node.partition_by)):
                node.source = src
                out = dist if dist.kind == "replicated" \
                    else Dist("hashed", dist.keys)
                return node, out
            node.source = P.Exchange(src, "repartition",
                                     list(node.partition_by))
            return node, Dist("hashed", tuple(node.partition_by))
        node.source = self._to_replicated(src, dist)
        return node, REPLICATED

    def _visit_exchange(self, node: P.Exchange):
        src, _ = self.visit(node.source)
        node.source = src
        return node, REPLICATED if node.kind in ("gather", "broadcast") else ANY


# ---------------------------------------------------------------------------
# fragment fusion (ROADMAP open item 1): splice mesh-local exchange edges
# back into ONE traced program
# ---------------------------------------------------------------------------
#
# The cluster path (parallel/cluster.py) cuts the distributed plan at its
# Exchange nodes and moves pages over HTTP between fragments.  When the
# producer and consumer of an exchange edge are placed on chips of the
# SAME ICI mesh, that host round-trip (pack -> POST -> poll -> GET ->
# unpack, per page) is pure overhead: the identical exchange lowers to a
# collective (`lax.all_to_all` for hash repartition, `all_gather` for
# broadcast/gather — parallel/exchange.py) inside the shard_map program
# the mesh executes anyway.  `fuse_fragments` contracts those edges: the
# consumer absorbs the producer's plan with the original Exchange node
# restored INLINE, so a scan -> repartition -> join -> aggregate pipeline
# compiles as one XLA program with zero host hops between stages.  The
# per-fragment HTTP path remains the fallback for cross-host edges,
# capacity-overflow guard trips, and fault recovery (any fused-attempt
# failure retries with fusion disabled — parallel/cluster.py).

#: exchange kinds the mesh collective kernels implement in-trace
#: (parallel/exchange.py + DistExecutor._exec_exchange) — all of them;
#: `fragment_fusion_kinds` can restrict for A/B runs
FUSIBLE_KINDS = frozenset(
    {"repartition", "broadcast", "gather", "scatter", "range"})


def fusion_mode(session) -> str:
    """Fragment-fusion policy: session property `fragment_fusion` —
    `auto` (default: the plan/fusion_cost.py per-edge cost model +
    decision memo pick fuse-vs-cut per exchange edge), `force` (round
    12's fuse-every-eligible-edge policy, byte-identical), `off` (the
    per-fragment HTTP path).  Legacy booleans map True -> force /
    False -> off so pre-round-18 callers keep their exact behavior.
    The PRESTO_TPU_FRAGMENT_FUSION env kill switch (off|0|false)
    disables process-wide."""
    env = os.environ.get("PRESTO_TPU_FRAGMENT_FUSION", "").lower()
    if env in ("off", "0", "false"):
        return "off"
    v = session.properties.get("fragment_fusion", "auto")
    if v is True:
        return "force"
    if v is False or v is None:
        return "off"
    v = str(v).strip().lower()
    if v in ("force", "on", "true", "1"):
        return "force"
    if v in ("off", "false", "0", ""):
        return "off"
    return "auto"


def fusion_enabled(session) -> bool:
    """Fragment-fusion master switch (any mode but `off`)."""
    return fusion_mode(session) != "off"


def fusion_kinds(session) -> frozenset:
    """Edge kinds eligible for fusion (session property
    `fragment_fusion_kinds`, csv)."""
    raw = session.properties.get("fragment_fusion_kinds", "")
    if not raw:
        return FUSIBLE_KINDS
    return frozenset(k.strip() for k in str(raw).split(",")
                     if k.strip()) & FUSIBLE_KINDS


def _rewrite_exch_scans(root, on_scan):
    """Generic rebuild of a fragment plan tree: `on_scan(eid, node)`
    returns a replacement for each `__exch_{eid}` scan (or the node
    itself).  Mirrors cut_fragments' rewrite, including the carry of
    optimizer instance attrs that are not dataclass fields."""

    def rewrite(n):
        if isinstance(n, P.TableScan):
            if n.table.startswith("__exch_"):
                return on_scan(int(n.table[len("__exch_"):]), n)
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
        fields = {f.name for f in dataclasses.fields(n)}
        for k, v in n.__dict__.items():
            if k not in fields and k not in nn.__dict__:
                setattr(nn, k, v)
        return nn

    return rewrite(root)


def fuse_fragments(fragments: list, verdict) -> Tuple[list, int]:
    """The fusion pass.  `fragments` is cut_fragments' output (duck-typed
    parallel/cluster.Fragment dataclasses, topological — producers
    first); `verdict(consumer_frag, exchange_input) -> bool` is the
    PER-EDGE fuse decision (the caller folds placement, kind filters,
    and the plan/fusion_cost.py cost model in: an edge only fuses when
    producer and consumer land on the same mesh AND the edge priced as
    a net win — or `fragment_fusion=force` said fuse everything).

    Every fused edge splices the producer fragment's plan into the
    consumer with the Exchange node restored inline, so the consumer
    becomes a SUPER-fragment whose inline exchanges lower to collectives
    (parallel/dist_executor.run_fused_fragment).  A producer's surviving
    (non-fused) inputs migrate to the consumer.  Non-fused repartition /
    range inputs that feed a super-fragment are wrapped in an in-trace
    re-exchange, restoring the hashed/range distribution contract the
    consumer plan was built against (the single fused task pulls ALL
    buckets of such an edge, so the wire partitioning is lost).

    Returns (new fragment list — renumbered, producers-first — and the
    number of fragments absorbed).  Fused fragments carry `fused=True`
    and `fused_fids` (the original fids they absorbed)."""
    if len(fragments) <= 1:
        return fragments, 0
    spliced: Dict[int, object] = {}    # old fid -> rewritten root
    ext_inputs: Dict[int, list] = {}   # old fid -> surviving inputs
    has_scan: Dict[int, bool] = {}
    absorbed_into: Dict[int, List[int]] = {}  # old fid -> absorbed fids
    absorbed: set = set()
    # range ExchangeInputs carry plain keys; the sort tuples live on the
    # producer fragment's out_keys — needed to rebuild the inline node
    okeys_of = {}
    for f in fragments:
        for inp in f.inputs:
            okeys_of[inp.eid] = fragments[inp.producer].out_keys

    for frag in fragments:
        by_eid = {i.eid: i for i in frag.inputs}
        kept: list = []
        taken: List[int] = []
        hscan = [frag.has_scan]

        def on_scan(eid, node):
            inp = by_eid.get(eid)
            if inp is None:  # an absorbed producer's migrated input
                return node
            if verdict(frag, inp):
                ex = P.Exchange(spliced[inp.producer], inp.kind,
                                list(inp.keys))
                if inp.kind == "range":
                    ex.sort_keys = list(okeys_of[eid])
                if getattr(inp, "sketch", False):
                    # restore the sketch-edge stamps cut_fragments
                    # carried: the inline gather keeps its pmax lowering
                    ex.sketch_only = True
                    if getattr(inp, "sketch_merge", ""):
                        ex.sketch_merge = inp.sketch_merge
                absorbed.add(inp.producer)
                taken.extend([inp.producer]
                             + absorbed_into.get(inp.producer, []))
                kept.extend(ext_inputs.pop(inp.producer, []))
                hscan[0] = hscan[0] or has_scan[inp.producer]
                return ex
            kept.append(inp)
            return node

        root = _rewrite_exch_scans(frag.root, on_scan)
        if taken:
            # super-fragment: restore the distribution contract of the
            # remaining EXTERNAL repartition/range inputs in-trace
            wrap_of = {i.eid: i for i in kept
                       if i.kind in ("repartition", "range")}

            def wrap(eid, node):
                inp = wrap_of.get(eid)
                if inp is None:
                    return node
                ex = P.Exchange(node, inp.kind, list(inp.keys))
                if inp.kind == "range":
                    ex.sort_keys = list(okeys_of[eid])
                return ex

            root = _rewrite_exch_scans(root, wrap)
        spliced[frag.fid] = root
        ext_inputs[frag.fid] = kept
        has_scan[frag.fid] = hscan[0]
        absorbed_into[frag.fid] = taken

    survivors = [f for f in fragments if f.fid not in absorbed]
    renum = {f.fid: i for i, f in enumerate(survivors)}
    out = []
    for f in survivors:
        inputs = [dataclasses.replace(i, producer=renum[i.producer])
                  for i in ext_inputs[f.fid]]
        nf = dataclasses.replace(f, fid=renum[f.fid],
                                 root=spliced[f.fid], inputs=inputs,
                                 has_scan=has_scan[f.fid])
        if absorbed_into[f.fid]:
            nf.fused = True
            nf.fused_fids = list(absorbed_into[f.fid])
        out.append(nf)
    return out, len(absorbed)


def fused_root_replicated(root, exch_kinds: Dict[int, str]) -> bool:
    """Is a fused super-fragment's output REPLICATED across the mesh
    (every shard holds the full result — emit one shard's copy) or
    per-shard (concatenate shards)?  Mirrors the coarse replicated/
    sharded projection of the Dist lattice distribute() used to build
    the plan; `exch_kinds` maps external `__exch_{eid}` inputs to their
    edge kind."""

    def walk(n) -> bool:
        if isinstance(n, P.Exchange):
            return n.kind in ("gather", "broadcast")
        if isinstance(n, P.TableScan):
            if n.table.startswith("__exch_"):
                eid = int(n.table[len("__exch_"):])
                return exch_kinds.get(eid) in ("gather", "broadcast")
            return False  # sharded_scan slices rows per shard
        if isinstance(n, P.Values):
            return True
        if isinstance(n, P.Union):
            return False  # distribute() scatters replicated sources
        srcs = n.sources
        if not srcs:
            return False
        if len(srcs) > 1:  # joins: replicated iff every side is
            return all(walk(s) for s in srcs)
        return walk(srcs[0])

    return walk(root)
