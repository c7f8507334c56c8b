"""`exchange_bytes_per_query` in the cell `ds100_mesh4_rollup`: mean
`QueryStats.exchange_bytes_collective` of the window's queries, in MB (the
program's own count at trace time, buffers at capacity times the shards):
the broadcast dimensions, the grouping sets' states, the top-N.  That
metric's `workloads` list names its one cell, so this file is its door
here."""

NAME = "ds_mesh_exchange_bytes_per_query"
UNIT = "MB"
LAYER = "mesh"
MOVES = "query_ms_geomean"


def compute(run):
    return run.sibling("exchange_bytes_per_query").compute(run)
