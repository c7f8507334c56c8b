"""The span `client.poll_sleep`: StatementClient.rows sleeping poll_interval
before it polls a query that the first response left unfinished.
Mean over classes of the class's total in the traced slice per query of
the class (a sum over three threads of a request, so no per-query median:
span_reduce.py); 0.0 where the span did not occur."""

NAME = "poll_sleep_ms"
UNIT = "ms"
LAYER = "client and protocol"
MOVES = "query_ms_geomean"
SPANS = ("client.poll_sleep",)


def compute(run):
    return run.sibling("idle_named_share").per_query(
        run, "span_ns_by_class", SPANS)
