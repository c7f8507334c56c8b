"""The span `exec.dispatch`: scan batches, parameter stacking and the jitted
call until it returns (run_compiled, run_compiled_batched).
Mean over classes of the class's total in the traced slice per query of
the class (a sum over three threads of a request, so no per-query median:
span_reduce.py); 0.0 where the span did not occur."""

NAME = "dispatch_ms"
UNIT = "ms"
LAYER = "executor"
MOVES = "query_ms_geomean"
SPANS = ("exec.dispatch",)


def compute(run):
    return run.sibling("idle_named_share").per_query(
        run, "span_ns_by_class", SPANS)
