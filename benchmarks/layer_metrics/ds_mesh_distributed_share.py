"""`distributed_share` in the cell `ds100_mesh4_rollup`: the share of the
window's finished queries that ran in mode `distributed` (difference of the
process-wide counter).  It must read 1.0: the guard against the silent fall
to one chip, where a 288 M-row fact table does not fit.  That metric's
`workloads` list names its one cell, so this file is its door here."""

NAME = "ds_mesh_distributed_share"
UNIT = "share"
LAYER = "mesh"
MOVES = "qps"


def compute(run):
    return run.sibling("distributed_share").compute(run)
