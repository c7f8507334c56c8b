"""Geometric mean, over the cell's query classes, of each class's median
latency in the window, POST to last row on the client's clock.  TPC-H's
own power metric is a geometric mean; a plain median over a two-class mix
jumps between the modes."""

import math

NAME = "query_ms_geomean"
UNIT = "ms"


def compute(run):
    med = run.class_medians(lambda q: q.ms)
    if set(med) != set(run.classes()):
        return None     # a class completed nothing: there is no mean to give
    return math.exp(sum(math.log(m) for m in med.values()) / len(med))
