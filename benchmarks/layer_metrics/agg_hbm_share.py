"""Memory-bound roofline share of Q1's aggregate: the bytes Q1 has to read
(reference.bytes_read: rows x resident width of its columns — the work,
the same whatever kernel does it) over the device seconds under the plan
node `Aggregate` per Q1 x peak HBM bytes/s (peaks.json).  Expected ~0.05;
near 1 would mean the bytes are counted too high or the time too low."""

NAME = "agg_hbm_share"
UNIT = "share"
LAYER = "kernels"
MOVES = "query_ms_p95"
CLASS = "q1"


def compute(run):
    ms = run.sibling("agg_ms_per_query").compute(run)
    need = run.bytes_by_class.get(CLASS)
    if ms is None:
        return None
    if not ms or not need:
        return 0.0
    return need / (ms / 1e3 * run.peaks["hbm_gbps"] * 1e9)
