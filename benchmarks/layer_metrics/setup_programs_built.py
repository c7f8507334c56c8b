"""Programs XLA built during set-up: backend compiles with no
persistent-cache hit on their thread (the server's counter of
QueryStats.programs_built when the window opens).  Programs below the
cache's size or time threshold count too.  None on a program without the
counter."""

NAME = "setup_programs_built"
UNIT = "count"
LAYER = "executor"
MOVES = "setup_s"


def compute(run):
    return run.counters_before.get("presto_tpu_query_programs_built_total")
