"""Device time under `k:window` (exec/window.execute_window: partition and
peer boundaries, frame bounds, the functions' scans, the gather into sorted
order) per query, mean over the classes.  The window's own sort is
`k:sort`'s, the innermost scope owning an operation; the plan node `Window`
in `engine_breakdown` holds both.
From the traced slice by span_reduce.py: device self time, per query of
the class; 0.0 where no such operation ran; nothing from a program whose
vocabulary lacks the scope (the parent of the PR that added it)."""

NAME = "window_ms_per_query"
UNIT = "ms"
LAYER = "kernels"
MOVES = "query_ms_geomean"
CLASS = None
SCOPE = "k:window"


def covers(scope):
    return scope == SCOPE


def compute(run):
    try:
        from presto_tpu.observe.names import KERNEL_SCOPES
    except ImportError:
        return None
    if SCOPE not in KERNEL_SCOPES:
        return None
    return run.sibling("idle_named_share").per_query(
        run, "kernel_ns_by_class", covers, CLASS)
