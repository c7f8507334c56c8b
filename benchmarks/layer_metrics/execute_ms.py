"""QueryStats phase_ns execute: dispatch, device time, fetch of the
result.  Mean over classes of the class median."""

NAME = "execute_ms"
UNIT = "ms"
LAYER = "executor"
MOVES = "query_ms_geomean"


def compute(run):
    return run.mean_of_class_medians(
        lambda q: q.stats.phase_ns.get("execute", 0) / 1e6)
