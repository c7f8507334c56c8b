"""Seconds of set-up loading executables from the persistent compile
cache (the server's counter of QueryStats.cache_load_ms when the window
opens).  None on a program without the counter."""

NAME = "setup_cache_load_s"
UNIT = "s"
LAYER = "executor"
MOVES = "setup_s"


def compute(run):
    ms = run.counters_before.get("presto_tpu_query_cache_load_ms_total")
    return None if ms is None else ms / 1e3
