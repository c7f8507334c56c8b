"""QueryStats phase_ns parse + plan.  Mean over classes of the class
median; a warm prepared bind skips both and reads 0."""

NAME = "plan_ms"
UNIT = "ms"
LAYER = "planner"
MOVES = "query_ms_geomean"


def compute(run):
    return run.mean_of_class_medians(
        lambda q: (q.stats.phase_ns.get("parse", 0)
                   + q.stats.phase_ns.get("plan", 0)) / 1e6)
