"""Memory-bound roofline share of the traced slice on ONE chip of the mesh:
the bytes a chip had to read for the slice's queries (a chip's share, one
over `chips`, of rows x resident width of the columns each class touches:
`reference_tpcds.bytes_read` over the workload's `columns_read`, reckoned
from the data and not from what the program happens to touch; the broadcast
dimensions, which every chip reads whole, are counted at their share too,
so the number errs low) over a chip's busy seconds x peak HBM bytes/s
(peaks.json).  `busy_s` is the mean over the chips.  Not a kernel's share:
the slice holds every program; it may never read over 1."""

NAME = "ds_mesh_hbm_share"
UNIT = "share"
LAYER = "kernels"
MOVES = "query_ms_geomean"


def compute(run):
    if run.trace is None or not run.trace["busy_s"]:
        return None
    by_class = run.trace["queries_by_class"]
    if any(not run.bytes_by_class.get(c) for c in by_class):
        return None     # a class that does not say what it reads
    need = sum(n * run.bytes_by_class[c] for c, n in by_class.items())
    return need / run.config["chips"] \
        / (run.trace["busy_s"] * run.peaks["hbm_gbps"] * 1e9)
