"""The span `chunk.sync` per query, and only that span: the host blocked
on the device at the chunk loop's own syncs (chunk 0's calibrating
compaction, the partial aggregate's ratio window, the guards' and
overflows' fetch after the loop, every chunk of the syncing loop).  It
does not read the host's wait for a full dispatch queue, which falls in
`chunk.launch` (Q6 at SF100 on a v5e: ~6.4 s a query there, ~0.8 s
here), so a wait that moves between the two spans moves this number
without the loop's time changing; `execute_ms` holds both.  Mean over
the classes of the class's total in the traced slice per query of the
class (span_reduce.py); 0.0 where the span did not occur; nothing from a
program whose vocabulary lacks the span (the parent of the PR that added
it)."""

NAME = "chunk_sync_ms_per_query"
UNIT = "ms"
LAYER = "executor"
MOVES = "query_ms_geomean"
SPAN = "chunk.sync"


def compute(run):
    try:
        from presto_tpu.observe.names import SPANS
    except ImportError:
        return None
    if SPAN not in SPANS:
        return None
    return run.sibling("idle_named_share").per_query(
        run, "span_ns_by_class", (SPAN,))
