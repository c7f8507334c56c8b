"""QueryStats.admission_wait_ms: the wait for a slot of the resource
group.  Mean over classes of the class median."""

NAME = "admission_wait_ms"
UNIT = "ms"
LAYER = "serving tier"
MOVES = "query_ms_p95"


def compute(run):
    return run.mean_of_class_medians(lambda q: q.stats.admission_wait_ms)
