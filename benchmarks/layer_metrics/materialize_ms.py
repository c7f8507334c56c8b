"""The spans `exec.materialize` (unpack_fetch, materialize_host) and
`result.rows` (the result as lists for the protocol pages).
Mean over classes of the class's total in the traced slice per query of
the class (a sum over three threads of a request, so no per-query median:
span_reduce.py); 0.0 where the span did not occur."""

NAME = "materialize_ms"
UNIT = "ms"
LAYER = "executor"
MOVES = "query_ms_geomean"
SPANS = ("exec.materialize", "result.rows")


def compute(run):
    return run.sibling("idle_named_share").per_query(
        run, "span_ns_by_class", SPANS)
