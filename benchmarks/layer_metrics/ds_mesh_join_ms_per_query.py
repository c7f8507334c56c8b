"""`join_ms_per_query` in the cell `ds100_mesh4_rollup`: device self time
of the operations whose innermost plan-node scope is `Join`, mean over the
chips, per query of the class, mean over the classes (the star joins of a
72 M-row shard to broadcast dimensions: the packed gathers, the compaction
of the survivors).  That metric's `workloads` list names its one cell, so
this file is its door here."""

NAME = "ds_mesh_join_ms_per_query"
UNIT = "ms"
LAYER = "kernels"
MOVES = "query_ms_geomean"


def compute(run):
    return run.sibling("join_ms_per_query").compute(run)
