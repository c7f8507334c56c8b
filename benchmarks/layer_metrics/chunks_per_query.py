"""Mean of `QueryStats.chunks_run` over the answered queries with stats:
the per-chunk programs a query launched, the calibrating chunk and every
redo of a loop included.  A sound run reads the grid's chunk count (75 at
SF100 and the engine's default chunk_orders); more means loops ran again
(`QueryStats.chunk_loop_redos`).  A count the profiler cannot slow, so
the traced slice's queries count too.  None where the program keeps no
such count."""

import statistics

NAME = "chunks_per_query"
UNIT = "count"
LAYER = "executor"
MOVES = "query_ms_geomean"


def compute(run):
    runs = [getattr(q.stats, "chunks_run", None) for q in run.good()
            if q.stats is not None]
    if not runs or None in runs:
        return None
    return statistics.fmean(runs)
