"""Device time of the plan node `GroupingSets` per query, mean over the
classes: on the mesh the node's PARTIAL step (every set's states on a
shard), the exchange of the states (`x:all_gather` or `x:repartition`,
lowered inside the node's scope) and the FINAL merge over (keys, group id)
all carry the node as their innermost plan-node scope
(`Executor._exec_groupingsets`).  On one chip the sets' aggregations read
as `Aggregate` and this reads the concatenation alone.
From the traced slice by span_reduce.py: device self time, mean over the
chips, per query of the class; 0.0 where no such operation ran."""

NAME = "ds_mesh_groupingsets_ms_per_query"
UNIT = "ms"
LAYER = "kernels"
MOVES = "query_ms_geomean"
CLASS = None


def covers(scope):
    return scope == "GroupingSets"


def compute(run):
    return run.sibling("idle_named_share").per_query(
        run, "node_ns_by_class", covers, CLASS)
