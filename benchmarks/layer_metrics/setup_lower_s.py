"""Seconds of set-up spent tracing and lowering programs to StableHLO:
the server's counter of QueryStats.lower_ms (JAX's compile events, booked
where they run by exec/compile_cache.py) when the window opens, so all of
set-up.  Every new process pays it, even where the persistent cache holds
the executable.  None on a program without the counter."""

NAME = "setup_lower_s"
UNIT = "s"
LAYER = "executor"
MOVES = "setup_s"


def compute(run):
    ms = run.counters_before.get("presto_tpu_query_lower_ms_total")
    return None if ms is None else ms / 1e3
