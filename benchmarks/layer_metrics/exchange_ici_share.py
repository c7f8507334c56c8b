"""The exchange's share of its roof, for Q3: the seconds the interconnect
would need for the bytes a repartition has to move, over the device
seconds a Q3 spends under the `x:` scopes (`exchange_ms_per_query`).

The work is counted here and is the same whatever implements the exchange:
the bytes Q3 reads (`reference.bytes_read`: rows x resident width of its
columns) x (chips - 1) / chips — what a hash repartition of everything the
query reads puts on the wire, an upper envelope: filters come first and
the engine repartitions less — divided by the chips, since each sends its
part at once, over the per-chip ICI peak of peaks_ici.json.

Expect well under 0.1: `x:repartition` also holds the in-trace bucket sort
and the scatter into the send buffer, which are HBM and sort work, not
wire time.  Near 1 would mean the bytes are counted too high or the time
too low.  0.0 where no exchange time was read."""

import json
import os

NAME = "exchange_ici_share"
UNIT = "share"
LAYER = "mesh"
MOVES = "query_ms_geomean"
CLASS = "q3"

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks_ici.json")


def ici_bytes_per_s():
    """This device's row of peaks_ici.json.  An unknown TPU is an error; a
    CPU gets here only in a rehearsal, where any row lets the arithmetic
    run and the number means nothing."""
    import jax

    with open(PEAKS) as f:
        peaks = json.load(f)
    d0 = jax.devices()[0]
    if d0.device_kind not in peaks and d0.platform != "tpu":
        return next(iter(peaks.values()))["ici_gbps"] * 1e9
    return peaks[d0.device_kind]["ici_gbps"] * 1e9


def compute(run):
    ms = run.sibling("exchange_ms_per_query").compute(run, CLASS)
    need = run.bytes_by_class.get(CLASS)
    if ms is None:
        return None
    if not ms or not need:
        return 0.0
    chips = run.config["chips"]
    on_the_wire = need * (chips - 1) / chips / chips    # per chip
    return on_the_wire / (ms / 1e3 * ici_bytes_per_s())
