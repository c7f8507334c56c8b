"""Device time under the exchange scopes (`x:repartition`, `x:all_gather`,
`x:range_partition`: the collective and the bucket sort and scatter that
feed it) per query, mean over the classes.
From the traced slice by span_reduce.py: device self time, mean over the
chips, per query of the class; 0.0 where no such operation ran or the
program names none (a mesh program that was not compiled ahead of its
first call leaves no HLO text to name its operations by).  On a v5e the
compiler combines all-gathers and the combined operation keeps no scope,
so what is read there is `x:repartition` (PERF.md section 5)."""

NAME = "exchange_ms_per_query"
UNIT = "ms"
LAYER = "mesh"
MOVES = "query_ms_geomean"
CLASS = None


def covers(scope):
    return scope.startswith("x:")


def compute(run, cls=CLASS):
    return run.sibling("idle_named_share").per_query(
        run, "kernel_ns_by_class", covers, cls)
