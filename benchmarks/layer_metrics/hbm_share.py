"""Memory-bound roofline share of the whole traced slice: bytes the
slice's queries had to read (rows x resident width of the columns each
class touches, reference.bytes_read; one read per program launched, so a
coalesced batch reads once) over device busy seconds x peak HBM bytes/s
(peaks.json).  Not a kernel's share: the slice holds every program."""

NAME = "hbm_share"
UNIT = "share"
LAYER = "kernels"
MOVES = "query_ms_geomean"


def compute(run):
    if run.trace is None or not run.trace["busy_s"]:
        return None
    if any(not run.bytes_by_class.get(c) for c in run.trace["queries_by_class"]):
        return None     # a class that does not say what it reads
    need = sum(n * run.bytes_by_class[c]
               for c, n in run.trace["queries_by_class"].items())
    launches = run.sibling("coalesced_batch_mean").launches_per_query(
        run, slice_only=True)
    return need * (launches or 1.0) / (
        run.trace["busy_s"] * run.peaks["hbm_gbps"] * 1e9)
