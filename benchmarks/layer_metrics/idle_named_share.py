"""Of the device's idle seconds inside queries, the share that the engine's
own spans name: span_reduce gives each gap to the `presto:` span open
nearest the device, and the catch-alls (`execute`, `http.post`, `http.get`,
`client.post`, `client.get`) and what no span covers do not count as named.
A sound run reads > 0.9.  Also the door to span_reduce.py for its
neighbours: `reduced(run)`, parsed once per process."""

import os
import sys

NAME = "idle_named_share"
UNIT = "share"
LAYER = "device"
MOVES = "query_ms_geomean"


def span_reduce():
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if bench not in sys.path:       # run.py's own directory, as a script
        sys.path.insert(0, bench)
    import span_reduce as module

    return module


def reduced(run):
    """span_reduce.reduce_events of the traced run, or None without one."""
    return span_reduce().reduced(run)


def per_query(run, table, keys, cls=None):
    """span_reduce.ms_per_query over the traced run: None without a trace
    to read, 0.0 where nothing of the kind occurred."""
    r = reduced(run)
    return None if r is None else span_reduce().ms_per_query(r, table, keys, cls)


def compute(run):
    r = reduced(run)
    if r is None:
        return None
    return r["idle_named_s"] / r["idle_in_query_s"] \
        if r["idle_in_query_s"] else 0.0
