"""Process start to the window's opening: import, device tables, warm-up
of the cell's own classes (compile or cache load), expected answers."""

NAME = "setup_s"
UNIT = "s"


def compute(run):
    return run.setup_seconds
