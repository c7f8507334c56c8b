"""Mean `QueryStats.exchange_bytes_collective` of the window's queries, in
MB: the program's own count of what its collectives move, taken at trace
time from the shapes (every shard's buffer at capacity, live rows or not,
times the shards), so the same for every run of a program.  0.0 from a
program that does not fill the counter on the mesh path."""

import statistics

NAME = "exchange_bytes_per_query"
UNIT = "MB"
LAYER = "mesh"
MOVES = "query_ms_geomean"


def compute(run):
    queries = run.with_stats()
    if not queries:
        return None
    return statistics.fmean(
        getattr(q.stats, "exchange_bytes_collective", 0) or 0
        for q in queries) / 1e6
