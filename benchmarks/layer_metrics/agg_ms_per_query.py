"""Device time of the plan node `Aggregate` per Q1: whatever implements the
aggregate, its operand and its group ids (the plan-node scope).
From the traced slice by span_reduce.py: device self time, per query of
the class; 0.0 where no such operation ran."""

NAME = "agg_ms_per_query"
UNIT = "ms"
LAYER = "kernels"
MOVES = "query_ms_p95"
CLASS = "q1"


def covers(scope):
    return scope == "Aggregate"


def compute(run):
    return run.sibling("idle_named_share").per_query(
        run, "node_ns_by_class", covers, CLASS)
