"""Table rows a second a chip over the traced slice's device busy time,
BASELINE.json's rate (SF100 rows/s/chip) in `sf100_chunk_scan`: the rows
of every table a class reads (the tables of its `columns_read`; all of
`lineitem`, 599,979,447 rows, for Q1 and for Q6) times the slice's
queries of the class, over busy seconds x the configuration's chips.
A streamed table is generated in the per-chunk program, so this is the
rate at which the chip generates, filters and aggregates rows, not a
read's bandwidth.  None without a trace, or where a class of the slice
says of no table that it reads it."""

NAME = "chunk_rows_per_s"
UNIT = "rows/s"
LAYER = "data on device"
MOVES = "query_ms_geomean"


def compute(run):
    if run.trace is None or not run.trace["busy_s"]:
        return None
    from presto_tpu.connectors import tpch as H

    tables = {c["name"]: c.get("columns_read", {})
              for c in run.workload["classes"]}
    if any(not tables.get(c) for c in run.trace["queries_by_class"]):
        return None
    rows = sum(n * sum(H.row_count(t, run.sf) for t in tables[c])
               for c, n in run.trace["queries_by_class"].items())
    return rows / (run.trace["busy_s"] * run.config["chips"])
