"""The plain reference of `tpch_sf100_chunked`: TPC-H Q1 and Q6 over the
HOST generator (`connectors/tpch.py`, the data's definition) at a scale
whose lineitem one pass of host numpy does not finish within a set-up.

Q1's and Q6's arithmetic is `reference.streamed`'s, line for line, over the
same order slices (ORDER_SLICE).  The slices are cut into contiguous runs,
one a worker process (this file run with `--worker`, on the CPU); a worker
returns each slice's partials and the parent adds them up in float64 in
slice order, as `reference.streamed` does in its one loop, so the same
slices give the same bits.  Shares no planner, executor, kernel or
device-generator code with the program.  Departures from
`reference.streamed`, none in the arithmetic:
- a lineitem slice's first row: the generator's `lineitem_offsets` sums
  the line counts of every order before the slice, at every call (at
  SF100 gigabytes and seconds a slice); the parent counts the rows before
  every run's first slice in one pass, a worker keeps the count running;
- of a slice's lineitem rows only the seven columns Q1 and Q6 read are
  generated, each by the generator's own expression for it
  (`_gen_lineitem_for_orders`).  The other nine, strings among them, are
  most of a slice's bytes, and a sandboxed host that takes freed memory
  back slowly counts every page a process maps anew: on a 40 GiB v5e host
  eight workers that made whole slices ran out of memory, as did
  short-lived ones that made these seven (hence also HEAP_ONLY);
- no `orders` rows are generated: they served Q3's and Q18's joins only;
- partials cross the process boundary as JSON, whose floats read back
  exactly.
Binds, point checks, comparison and the bytes a class reads are
`reference.py`'s.
"""

import hashlib
import json
import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import reference as base  # noqa: E402

ORDER_SLICE = base.ORDER_SLICE
MAX_WORKERS = 8             # worker processes, a contiguous run of slices each
OFFSET_BLOCK = 1 << 22      # orders summed at once for a run's first row
#: glibc's: every array of a slice (<= 16 MB at SF100) from the heap, and
#: the heap kept, so a worker's later slices reuse its first one's pages
HEAP_ONLY = {"MALLOC_MMAP_THRESHOLD_": str(32 << 20),
             "MALLOC_TRIM_THRESHOLD_": str(1 << 40)}

STREAMED = {"tpch_q1": 1, "tpch_q6": 6}
POINT = base.POINT
BINDS = base.BINDS
bind_values = base.bind_values
rows_equal = base.rows_equal
bytes_read = base.bytes_read
check = base.check


def default_workers():
    """One process a core this one may use, one core left to the engine's
    set-up beside it."""
    return max(1, min(MAX_WORKERS, len(os.sched_getaffinity(0)) - 1))


def streamed(sf, names, order_slice=ORDER_SLICE, workers=None):
    """{check name: expected rows} for the `names` of STREAMED, as
    `reference.streamed` gives them, from `workers` processes."""
    from presto_tpu.connectors import tpch as H

    starts = list(range(0, H.row_count("orders", sf), order_slice))
    workers = min(workers or default_workers(), len(starts))
    runs = [starts[len(starts) * k // workers:len(starts) * (k + 1) // workers]
            for k in range(workers)]
    jobs = [json.dumps({"sf": sf, "starts": run, "order_slice": order_slice,
                        "line0": line0})
            for run, line0 in zip(runs, lines_before(H, [r[0] for r in runs]))]
    with ThreadPoolExecutor(workers) as pool:
        partials = [part for out in pool.map(run_worker, jobs) for part in out]
    check(len(partials) == len(starts))
    q1, q6 = {}, 0.0
    for part in partials:
        for g, sums in part["q1"].items():
            q1[g] = q1.get(g, 0.0) + np.asarray(sums)
        q6 += part["q6"]
    out = {"tpch_q1": [(g[0], g[1], s[0], s[1], s[2], s[3], s[0] / s[5],
                        s[1] / s[5], s[4] / s[5], int(round(s[5])))
                       for g, s in sorted(q1.items())],
           "tpch_q6": [(q6,)]}
    return {n: [list(r) for r in out[n]] for n in names}


def run_worker(job):
    """This file run with `--worker job`, on the CPU: its slices' partials."""
    p = subprocess.run([sys.executable, os.path.abspath(__file__), "--worker", job],
                       stdout=subprocess.PIPE, cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu", **HEAP_ONLY))
    check(p.returncode == 0, f"a reference worker exited {p.returncode}")
    return json.loads(p.stdout)


def slice_partials(sf, starts, order_slice, line0):
    """[{"q1": {group: six sums}, "q6": sum}] of the contiguous slices that
    begin at `starts`, the first at lineitem row `line0`:
    `reference.streamed`'s Q1 and Q6 arithmetic."""
    from presto_tpu.connectors import tpch as H

    n_orders = H.row_count("orders", sf)
    d_q1 = H._days("1998-09-02")
    d_q6 = (H._days("1994-01-01"), H._days("1995-01-01"))
    out = []
    for r0 in starts:
        li, n = lineitem_columns(H, sf, r0, min(r0 + order_slice, n_orders),
                                 line0)
        line0 += n
        px, disc, qty = (li["l_extendedprice"], li["l_discount"],
                         li["l_quantity"])
        ship = li["l_shipdate"]
        m = ship <= d_q1
        rf = li["l_returnflag"].astype("U1")[m]
        ls = li["l_linestatus"].astype("U1")[m]
        groups, gid = np.unique(np.char.add(rf, ls), return_inverse=True)
        dp = px[m] * (1.0 - disc[m])
        cols = [qty[m], px[m], dp, dp * (1.0 + li["l_tax"][m]), disc[m],
                np.ones(m.sum())]
        sums = np.stack([np.bincount(gid, c, len(groups)) for c in cols])
        m = ((ship >= d_q6[0]) & (ship < d_q6[1]) & (disc >= 0.05)
             & (disc <= 0.07) & (qty < 24))
        out.append({"q1": {str(g): sums[:, j].tolist()
                           for j, g in enumerate(groups)},
                    "q6": float(np.sum(px[m] * disc[m]))})
    return out


def lineitem_columns(H, sf, o0, o1, row0):
    """({column: values}, rows) of the lineitem rows of orders [o0, o1),
    whose first is lineitem row `row0`: the seven columns Q1 and Q6 read,
    by `_gen_lineitem_for_orders`' own expressions."""
    t = "lineitem"
    counts = H._lines_per_order(np.arange(o0, o1, dtype=np.int64))
    n = int(np.sum(counts))
    odate = np.repeat(H._order_dates(o0, o1 - o0), counts).astype(np.int64)
    pk = H._u(t, "partkey", row0, n, 1, max(int(200_000 * sf), H.SUPP_PER_PART))
    qty = H._u(t, "quantity", row0, n, 1, 50).astype(np.float64)
    shipdate = (odate + H._u(t, "shipdelta", row0, n, 1, 121, np.int32)
                ).astype(np.int32)
    receiptdate = shipdate + H._u(t, "receiptdelta", row0, n, 1, 30, np.int32)
    return {
        "l_quantity": qty,
        "l_extendedprice": H._retailprice(pk) * qty,
        "l_discount": H._u(t, "discount", row0, n, 0, 10) / 100.0,
        "l_tax": H._u(t, "tax", row0, n, 0, 8) / 100.0,
        "l_returnflag": np.where(receiptdate <= H.CURRENT_DATE,
                                 H._pick(t, "returnflag", row0, n, ["R", "A"]),
                                 "N"),
        "l_linestatus": np.where(shipdate > H.CURRENT_DATE, "O", "F"),
        "l_shipdate": shipdate,
    }, n


def lines_before(H, order_rows):
    """Lineitem rows of the orders before each of the ascending
    `order_rows`: one pass in blocks."""
    out, total, b = [], 0, 0
    for r in order_rows:
        while b < r:
            e = min(b + OFFSET_BLOCK, r)
            total += int(np.sum(H._lines_per_order(np.arange(b, e, dtype=np.int64))))
            b = e
        out.append(total)
    return out


def source_hash():
    """Of this file: a cached answer is only as good as the code that made it."""
    with open(__file__, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:12]


def cached_streamed(cache_dir, config, sf, names):
    """`streamed` behind one JSON file per (config, scale, check, hash of
    this file) under `cache_dir`, as `reference.cached_streamed` keeps its
    own.  Returns ({name: rows}, computed?)."""
    digest = source_hash()
    paths = {n: os.path.join(cache_dir, f"ref_{config}_sf{sf:g}_{n}_{digest}.json")
             for n in names}
    out, missing = {}, []
    for n, p in paths.items():
        try:
            with open(p) as f:
                out[n] = json.load(f)
        except (OSError, ValueError):
            missing.append(n)
    if missing:
        os.makedirs(cache_dir, exist_ok=True)
        made = streamed(sf, missing)
        for n in missing:
            tmp = f"{paths[n]}.tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(made[n], f)
            os.replace(tmp, paths[n])
        out.update(made)
    return out, bool(missing)


def host_generator_alone():
    """Make `presto_tpu.connectors.tpch` importable without the package's
    __init__, which imports JAX and the engine: a worker needs numpy only,
    and each would otherwise load the device runtime beside the engine."""
    import types

    pkg = types.ModuleType("presto_tpu")
    pkg.__path__ = [os.path.join(ROOT, "presto_tpu")]
    sys.modules.setdefault("presto_tpu", pkg)


if __name__ == "__main__" and sys.argv[1:2] == ["--worker"]:
    host_generator_alone()
    job = json.loads(sys.argv[2])
    json.dump(slice_partials(job["sf"], job["starts"], job["order_slice"],
                             job["line0"]), sys.stdout)
