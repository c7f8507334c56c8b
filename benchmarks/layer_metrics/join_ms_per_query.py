"""Device time of the plan node `Join` per query, mean over the classes:
whatever implements the joins (build_probe, take_rows, the plain gather,
compaction of the survivors), as `engine_breakdown`'s `device_by_node` has
it for the slice.  Where a grouping-set expansion copies a star join into
each of its sub-queries (`QueryStats.grouping_set_branches`), every copy
that the compiler keeps counts.
From the traced slice by span_reduce.py: device self time of the operations
whose innermost plan-node scope is `Join`, per query of the class; 0.0
where no such operation ran."""

NAME = "join_ms_per_query"
UNIT = "ms"
LAYER = "kernels"
MOVES = "query_ms_geomean"
CLASS = None


def covers(scope):
    return scope == "Join"


def compute(run):
    return run.sibling("idle_named_share").per_query(
        run, "node_ns_by_class", covers, CLASS)
