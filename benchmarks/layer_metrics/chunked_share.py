"""Share of the window's finished queries that ran in mode `chunked`
(difference of the process-wide counter `presto_tpu_queries_total{mode}`)
in the cell `sf100_chunk_scan`.  It must read 1.0: the guard against a
silent fall from the chunk loop to the whole-table program, which a
600 M-row lineitem does not fit."""

NAME = "chunked_share"
UNIT = "share"
LAYER = "executor"
MOVES = "qps"


def compute(run):
    done = run.counter_delta("presto_tpu_queries_total{", 'state="FINISHED"')
    chunked = run.counter_delta("presto_tpu_queries_total{",
                                'state="FINISHED"', 'mode="chunked"')
    return chunked / done if done else None
