"""Seconds of set-up in table birth: column sets generated on the device
or placed there, timed to ready (the server's counter of
QueryStats.data_load_ms when the window opens; a generator's build is in
the compile counters instead).  None on a program without the counter."""

NAME = "setup_data_s"
UNIT = "s"
LAYER = "data on device"
MOVES = "setup_s"


def compute(run):
    ms = run.counters_before.get("presto_tpu_query_data_load_ms_total")
    return None if ms is None else ms / 1e3
