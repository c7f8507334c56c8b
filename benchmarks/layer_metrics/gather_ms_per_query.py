"""Device time under `k:take_rows.staged` and `k:take_rows.flat` per query,
mean over the classes.
From the traced slice by span_reduce.py: device self time, per query of
the class; 0.0 where no such operation ran."""

NAME = "gather_ms_per_query"
UNIT = "ms"
LAYER = "kernels"
MOVES = "query_ms_geomean"
CLASS = None


def covers(scope):
    return scope.startswith("k:take_rows")


def compute(run):
    return run.sibling("idle_named_share").per_query(
        run, "kernel_ns_by_class", covers, CLASS)
