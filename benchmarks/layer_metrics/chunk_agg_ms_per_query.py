"""Device time of the plan node `Aggregate` per query in
`sf100_chunk_scan`, mean over the classes: each chunk's partial
aggregate and the final one, whatever implements them (Q1's
`k:fused_group_sums`, its `.operand` and the unscoped selects of its
group ids; Q6's masked sum under `k:segment`, which
`segment_ms_per_query` also reads).  `agg_ms_per_query` reads the same
node for Q1 alone and moves `query_ms_p95`, which this cell does not
report, hence a file of its own.
From the traced slice by span_reduce.py: device self time, per query of
the class; 0.0 where no such operation ran."""

NAME = "chunk_agg_ms_per_query"
UNIT = "ms"
LAYER = "kernels"
MOVES = "query_ms_geomean"


def covers(scope):
    return scope == "Aggregate"


def compute(run):
    return run.sibling("idle_named_share").per_query(
        run, "node_ns_by_class", covers)
