"""XLA programs built inside the window (difference of the process-wide
counter).  A sound run reads 0."""

NAME = "warm_compiles"
UNIT = "count"
LAYER = "executor"
MOVES = "qps"


def compute(run):
    return run.counter_delta("presto_tpu_query_compiles_total")
