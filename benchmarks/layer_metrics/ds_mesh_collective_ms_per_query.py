"""Device time of the mesh's exchanges per query, mean over the classes:
self time of the operations under an `x:` scope (`x:all_gather`,
`x:repartition`, `x:range_partition`: the collective and the send layout
that feeds it) PLUS that of XLA's own collective operations that carry no
scope (`all-gather*`, `all-to-all*`, `all-reduce*`, `collective-permute*`).
On a v5e the compiler combines all-gathers and the combined operation keeps
no op_name, so `exchange_ms_per_query` reads 0.0 for them; broadcast builds
are most of this cell's exchange.
From the traced slice: span_reduce.py's own rules (events clipped to the
query spans, self times, a class's share by overlap, mean over the chips),
applied here because its tables keep unscoped time by label for the reader
only.  None without a trace or a query span; 0.0 where no such operation
ran."""

import re
import statistics

NAME = "ds_mesh_collective_ms_per_query"
UNIT = "ms"
LAYER = "mesh"
MOVES = "query_ms_geomean"

COLLECTIVE = re.compile(r"^(all-gather|all-to-all|all-reduce|collective-permute)")


def is_exchange(scope, short):
    if scope is not None:
        return scope.startswith("x:")
    return bool(COLLECTIVE.match(short))


def ms_per_query(S, ops, host, tables):
    """The reduction, over span_reduce.load's (ops, host)."""
    queries = [(n[len(S.QUERY):], s, e) for evs in host.values()
               for n, s, e in evs if n.startswith(S.QUERY)]
    if not queries:
        return None
    t0 = min(s for _, s, _ in queries)
    t1 = max(e for _, _, e in queries)
    by_class, ns_by_class, n_by_class = S.ByClass(queries), {}, {}
    for cls, _, _ in queries:
        n_by_class[cls] = n_by_class.get(cls, 0) + 1
    for plane in ops:
        clipped = [(ev, max(ev[1], t0), min(ev[2], t1)) for ev in ops[plane]
                   if ev[2] > t0 and ev[1] < t1]
        keyed = [((i, s, e), s, e) for i, (_, s, e) in enumerate(clipped)]
        for (i, s, e), ns in S.tr.self_times(keyed):
            ev = clipped[i][0]
            scope, _ = S.scopes_of(S.op_name_of(ev, tables))
            if is_exchange(scope, S.tr.short_name(ev[0])):
                by_class.add(ns_by_class, NAME, s, e, ns)
    planes = max(len(ops), 1)
    return statistics.fmean(
        ns_by_class.get(c, {}).get(NAME, 0.0) / planes / 1e6 / n
        for c, n in n_by_class.items())


def compute(run):
    if run.trace is None:
        return None
    S = run.sibling("idle_named_share").span_reduce()
    path = S.newest_xplane()
    if path is None:
        return None
    ops, host = S.load(path)
    return ms_per_query(S, ops, host, S.scope_tables())
