"""Device self seconds under any `k:`/`x:` scope of the engine's vocabulary
(presto_tpu/observe/names.py) over all device self seconds of the traced
slice: how much of the chip's time the vocabulary covers."""

NAME = "device_named_share"
UNIT = "share"
LAYER = "kernels"
MOVES = "query_ms_geomean"


def compute(run):
    r = run.sibling("idle_named_share").reduced(run)
    if r is None:
        return None
    return r["scoped_s"] / r["self_s"] if r["self_s"] else 0.0
