"""Device time under `k:chunk_scan` per query: a chunk's columns and
selection mask generated inside its per-chunk program, summed over the
query's chunks (what a resident table pays once, at its birth, a streamed
one pays in every query).
From the traced slice by span_reduce.py: device self time, mean over the
classes of the class's total per query of the class; 0.0 where no such
operation ran; nothing from a program whose vocabulary lacks the scope
(the parent of the PR that added it)."""

NAME = "chunk_scan_ms_per_query"
UNIT = "ms"
LAYER = "data on device"
MOVES = "query_ms_geomean"
SCOPE = "k:chunk_scan"


def compute(run):
    try:
        from presto_tpu.observe.names import KERNEL_SCOPES
    except ImportError:
        return None
    if SCOPE not in KERNEL_SCOPES:
        return None
    return run.sibling("idle_named_share").per_query(
        run, "kernel_ns_by_class", (SCOPE,))
