"""Share of the window's finished queries that ran in mode `compiled`
(difference of the process-wide counter).  A sound run reads 1."""

NAME = "compiled_share"
UNIT = "share"
LAYER = "executor"
MOVES = "qps"


def compute(run):
    done = run.counter_delta("presto_tpu_queries_total{", 'state="FINISHED"')
    compiled = run.counter_delta("presto_tpu_queries_total{",
                                 'state="FINISHED"', 'mode="compiled"')
    return compiled / done if done else None
