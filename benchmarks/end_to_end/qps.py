"""Queries completed and correct per second: the sum over the clients of
each client's count over its own window, from the opening to the end of its
last block (whole blocks only, so the mix does not depend on where the
seed's order is cut).  One chip, so also queries per chip-second."""

NAME = "qps"
UNIT = "queries/s"


def compute(run):
    by_client = {}
    for q in run.queries:
        by_client.setdefault(q.client, []).append(q)
    rate = sum(sum(q.ok for q in qs) / (max(q.t1 for q in qs) - run.t_open)
               for qs in by_client.values())
    return rate or None
