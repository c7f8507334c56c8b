"""Memory-bound roofline share of the traced slice in a TPC-DS cell: the
bytes its queries had to read (rows x resident width of the columns each
class touches, `reference_tpcds.bytes_read` over the workload's
`columns_read`, with TPC-DS row counts; each column once a query, however
many sub-queries a grouping-set expansion makes of it) over device busy
seconds x peak HBM bytes/s (peaks.json).  One client and no prepared
class: one program a query.  Not a kernel's share: the slice holds every
program; it is the roof under every later claim in the cell."""

NAME = "ds_hbm_share"
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
    return need / (run.trace["busy_s"] * run.peaks["hbm_gbps"] * 1e9)
