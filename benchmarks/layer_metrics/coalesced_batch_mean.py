"""Queries finished per program launched (1 = every query ran solo), exact
over the window from the server's counters: a batch of B riders is one
launch, so launches = finished - riders coalesced + batches."""

NAME = "coalesced_batch_mean"
UNIT = "count"
LAYER = "serving tier"
MOVES = "qps"


def launches_per_query(run, slice_only=False):
    """Shared with hbm_share: launches over finished queries."""
    done = run.counter_delta("presto_tpu_queries_total{", 'state="FINISHED"',
                             slice_only=slice_only)
    riders = run.counter_delta("presto_tpu_coalesce_riders_coalesced",
                               slice_only=slice_only)
    batches = run.counter_delta("presto_tpu_coalesce_batches",
                                slice_only=slice_only)
    return (done - riders + batches) / done if done else None


def compute(run):
    ratio = launches_per_query(run)
    return 1.0 / ratio if ratio else None
