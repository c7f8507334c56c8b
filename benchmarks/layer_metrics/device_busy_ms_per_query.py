"""Union of the device-operation intervals in the traced slice over the
queries completed in it."""

NAME = "device_busy_ms_per_query"
UNIT = "ms"
LAYER = "device"
MOVES = "query_ms_geomean"


def compute(run):
    if run.trace is None or not run.trace["queries"]:
        return None
    return run.trace["busy_s"] * 1e3 / run.trace["queries"]
