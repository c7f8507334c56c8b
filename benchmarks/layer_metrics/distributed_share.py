"""Share of the window's finished queries that ran in mode `distributed`
(difference of the process-wide counter): `compiled_share`'s twin for a
cell on the mesh.  A sound run reads 1; anything less means a query fell
back to one chip (`QueryStats.fallback_reason` says why) while the others
sat idle behind it."""

NAME = "distributed_share"
UNIT = "share"
LAYER = "mesh"
MOVES = "qps"


def compute(run):
    done = run.counter_delta("presto_tpu_queries_total{", 'state="FINISHED"')
    on_mesh = run.counter_delta("presto_tpu_queries_total{",
                                'state="FINISHED"', 'mode="distributed"')
    return on_mesh / done if done else None
