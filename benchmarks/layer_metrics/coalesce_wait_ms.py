"""The spans `coalesce.window` (the leader holds the micro-batch window
open) and `coalesce.ride` (a rider waits for the leader's launch).
Mean over classes of the class's total in the traced slice per query of
the class (a sum over three threads of a request, so no per-query median:
span_reduce.py); 0.0 where the span did not occur."""

NAME = "coalesce_wait_ms"
UNIT = "ms"
LAYER = "serving tier"
MOVES = "query_ms_p95"
SPANS = ("coalesce.window", "coalesce.ride")


def compute(run):
    return run.sibling("idle_named_share").per_query(
        run, "span_ns_by_class", SPANS)
