"""Seconds of set-up in XLA builds: backend compiles the persistent cache
did not serve (the server's counter of QueryStats.xla_build_ms when the
window opens).  A warm cache reads ~0; a program evicted from the cache
reads its build here.  None on a program without the counter."""

NAME = "setup_xla_build_s"
UNIT = "s"
LAYER = "executor"
MOVES = "setup_s"


def compute(run):
    ms = run.counters_before.get("presto_tpu_query_xla_build_ms_total")
    return None if ms is None else ms / 1e3
