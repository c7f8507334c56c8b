"""Device time under `k:segment` (segment_sum, segment_min, segment_max:
a per-aggregate segmented reduction, the route an aggregate takes when
the fused group-sum pass does not answer it) per query, mean over the
classes.
From the traced slice by span_reduce.py: device self time, per query of
the class; 0.0 where no such operation ran."""

NAME = "segment_ms_per_query"
UNIT = "ms"
LAYER = "kernels"
MOVES = "query_ms_geomean"
CLASS = None


def covers(scope):
    return scope == "k:segment"


def compute(run):
    return run.sibling("idle_named_share").per_query(
        run, "kernel_ns_by_class", covers, CLASS)
