"""95th percentile of the latency of all the window's queries."""

import statistics

NAME = "query_ms_p95"
UNIT = "ms"


def compute(run):
    ms = [q.ms for q in run.good()]
    if len(ms) < 20:
        return None
    return statistics.quantiles(ms, n=20, method="inclusive")[18]
