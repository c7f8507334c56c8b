"""memory_stats()["peak_bytes_in_use"] of the fullest chip after the
window: resident tables plus the largest program's temporaries."""

NAME = "peak_hbm_gb"
UNIT = "GB"
LAYER = "data on device"
MOVES = "setup_s"


def compute(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
