"""What the client waits beyond the server's own account of the query:
client wall minus the QueryStats phase total and the admission wait.  HTTP,
JSON, thread hand-offs, polling.  Mean over classes of the class median."""

NAME = "serve_overhead_ms"
UNIT = "ms"
LAYER = "client and protocol"
MOVES = "query_ms_geomean"


def compute(run):
    return run.mean_of_class_medians(
        lambda q: q.ms - q.stats.total_ns / 1e6 - q.stats.admission_wait_ms)
