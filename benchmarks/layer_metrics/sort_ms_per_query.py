"""Device time under `k:sort` (sort_pair, sort_perm, sort_values) per query,
mean over the classes.
From the traced slice by span_reduce.py: device self time, per query of
the class; 0.0 where no such operation ran."""

NAME = "sort_ms_per_query"
UNIT = "ms"
LAYER = "kernels"
MOVES = "query_ms_geomean"
CLASS = None


def covers(scope):
    return scope == "k:sort"


def compute(run):
    return run.sibling("idle_named_share").per_query(
        run, "kernel_ns_by_class", covers, CLASS)
