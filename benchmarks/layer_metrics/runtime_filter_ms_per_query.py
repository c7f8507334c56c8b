"""Device time under `k:runtime_filter` (rf_build, rf_probe: a join's build
keys as a membership mask on the probe side's scan) per query, mean over
the classes.
From the traced slice by span_reduce.py: device self time, per query of
the class; 0.0 where no such operation ran."""

NAME = "runtime_filter_ms_per_query"
UNIT = "ms"
LAYER = "kernels"
MOVES = "query_ms_geomean"
CLASS = None


def covers(scope):
    return scope == "k:runtime_filter"


def compute(run):
    return run.sibling("idle_named_share").per_query(
        run, "kernel_ns_by_class", covers, CLASS)
