"""Share of setup_s the named layers account for: lowering, XLA builds,
cache loads and table birth over the set-up's seconds.  The rest is
import, device init, the warm-up's execution and the wait for the
reference.  Compile-ahead builds overlap the query thread, so the share
can pass 1.  None on a program without the counters."""

NAME = "setup_named_share"
UNIT = "share"
LAYER = "executor"
MOVES = "setup_s"

PARTS = ("setup_lower_s", "setup_xla_build_s", "setup_cache_load_s",
         "setup_data_s")


def compute(run):
    parts = [run.sibling(m).compute(run) for m in PARTS]
    if None in parts or not run.setup_seconds:
        return None
    return sum(parts) / run.setup_seconds
