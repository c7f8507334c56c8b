"""Device time under `k:fused_group_sums.operand` per Q1: the stack and the
pad that only the interface of fused_group_sums asks for.
From the traced slice by span_reduce.py: device self time, per query of
the class; 0.0 where no such operation ran."""

NAME = "agg_operand_ms_per_query"
UNIT = "ms"
LAYER = "kernels"
MOVES = "query_ms_p95"
CLASS = "q1"


def covers(scope):
    return scope == "k:fused_group_sums.operand"


def compute(run):
    return run.sibling("idle_named_share").per_query(
        run, "kernel_ns_by_class", covers, CLASS)
