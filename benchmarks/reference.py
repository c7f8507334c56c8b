"""The benchmark's plain reference: expected answers, binds, comparison.

Straight-line numpy over the HOST generator (`connectors/tpch.py`, the
data's definition), streamed by order ranges so host memory stays
bounded.  Copied from chip_smoke.py (PR 22, proven on the chip at SF10);
shares no planner, executor, kernel or device-generator code with the
program.  A workload file names what it needs from here:

    "check": a key of STREAMED (one pass over the whole table, cached per
             checkout) or of POINT (all binds' answers in one call, cheap,
             made in every set-up)
    "binds": a key of BINDS (how a class's parameters are drawn from the seed)
"""

import hashlib
import json
import os

import numpy as np

ORDER_SLICE = 500_000       # orders per slice of the streamed reference


def check(cond, *what):
    """An assert that -O cannot remove."""
    if not cond:
        raise AssertionError(*what)


# ---------------------------------------------------------------------------
# streamed checks: TPC-H Q1, Q6, Q3, Q18 with their validation values
# ---------------------------------------------------------------------------

STREAMED = {"tpch_q1": 1, "tpch_q6": 6, "tpch_q3": 3, "tpch_q18": 18}


def streamed(sf, names, order_slice=ORDER_SLICE):
    """{check name: expected rows} for the `names` of STREAMED, in one
    pass: Q1/Q6 add up per slice, Q3/Q18 keep only per-order partials."""
    from presto_tpu.connectors import tpch as H

    queries = {STREAMED[n] for n in names}
    n_orders = H.row_count("orders", sf)
    d_q1 = H._days("1998-09-02")
    d_q3 = H._days("1995-03-15")
    d_q6 = (H._days("1994-01-01"), H._days("1995-01-01"))
    cu = H.generate("customer", sf)
    building = np.sort(
        cu["c_custkey"][cu["c_mktsegment"].astype("U10") == "BUILDING"])
    q1, q6, q3, q18 = {}, 0.0, [], []
    for r0 in range(0, n_orders, order_slice):
        li = H.generate("lineitem", sf, r0, r0 + order_slice)
        od = H.generate("orders", sf, r0, r0 + order_slice)
        o_key = od["o_orderkey"]
        check((o_key[1:] > o_key[:-1]).all())
        # every lineitem of these orders is in this slice
        l_pos = np.searchsorted(o_key, li["l_orderkey"])
        check((o_key[l_pos] == li["l_orderkey"]).all())
        px, disc, qty = (li["l_extendedprice"], li["l_discount"],
                         li["l_quantity"])
        ship = li["l_shipdate"]
        if 1 in queries:
            m = ship <= d_q1
            rf = li["l_returnflag"].astype("U1")[m]
            ls = li["l_linestatus"].astype("U1")[m]
            groups, gid = np.unique(np.char.add(rf, ls), return_inverse=True)
            dp = px[m] * (1.0 - disc[m])
            cols = [qty[m], px[m], dp, dp * (1.0 + li["l_tax"][m]), disc[m],
                    np.ones(m.sum())]
            sums = np.stack([np.bincount(gid, c, len(groups)) for c in cols])
            for j, g in enumerate(groups):
                q1[g] = q1.get(g, 0.0) + sums[:, j]
        if 6 in queries:
            m = ((ship >= d_q6[0]) & (ship < d_q6[1]) & (disc >= 0.05)
                 & (disc <= 0.07) & (qty < 24))
            q6 += float(np.sum(px[m] * disc[m]))
        if 3 in queries:
            o_ok = (od["o_orderdate"] < d_q3) & np.isin(od["o_custkey"],
                                                        building)
            m = (ship > d_q3) & o_ok[l_pos]
            rev = np.bincount(l_pos[m], px[m] * (1.0 - disc[m]), len(o_key))
            hit = np.flatnonzero(np.bincount(l_pos[m], minlength=len(o_key)))
            top = hit[np.lexsort((od["o_orderdate"][hit], -rev[hit]))[:10]]
            q3 += [(int(o_key[i]), float(rev[i]), int(od["o_orderdate"][i]),
                    int(od["o_shippriority"][i])) for i in top]
        if 18 in queries:
            qsum = np.bincount(l_pos, qty, len(o_key))
            for i in np.flatnonzero(qsum > 300.0):
                q18.append((int(od["o_custkey"][i]), int(o_key[i]),
                            int(od["o_orderdate"][i]),
                            float(od["o_totalprice"][i]), float(qsum[i])))
    out = {}
    if 1 in queries:
        out["tpch_q1"] = [(g[0], g[1], s[0], s[1], s[2], s[3], s[0] / s[5],
                           s[1] / s[5], s[4] / s[5], int(round(s[5])))
                          for g, s in sorted(q1.items())]
    if 6 in queries:
        out["tpch_q6"] = [(q6,)]
    if 3 in queries:
        q3.sort(key=lambda r: (-r[1], r[2]))
        out["tpch_q3"] = q3[:10]  # dates stay day numbers, as the client shows them
    if 18 in queries:
        q18.sort(key=lambda r: (-r[3], r[2]))
        c_key = cu["c_custkey"]
        check((c_key[1:] > c_key[:-1]).all())
        out["tpch_q18"] = [
            (str(cu["c_name"][np.searchsorted(c_key, ck)]), ck, ok, d, tp, q)
            for ck, ok, d, tp, q in q18[:100]]
    return {n: [list(r) for r in out[n]] for n in names}


def source_hash():
    """Of this file: a cached answer is only as good as the code that made it."""
    with open(__file__, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:12]


def cached_streamed(cache_dir, config, sf, names):
    """`streamed`, behind one JSON file per (config, scale, check, hash of
    this file) under `cache_dir`: the first run of a cell in a checkout
    computes, every later one reads.  Returns ({name: rows}, computed?)."""
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


# ---------------------------------------------------------------------------
# point checks and binds
# ---------------------------------------------------------------------------


def order_point(sf, binds):
    """[[count(*), sum(l_extendedprice)]] of one order per bind; a bind is
    (order row, its o_orderkey).  The host generator's cost of a slice grows
    with its first row, so consecutive rows are generated together."""
    from presto_tpu.connectors import tpch as H

    by_key, rows = {}, sorted(row for row, _ in binds)
    while rows:
        n = 1
        while n < len(rows) and rows[n] == rows[0] + n:
            n += 1
        li = H.generate("lineitem", sf, rows[0], rows[0] + n)
        keys, first = np.unique(li["l_orderkey"], return_index=True)
        for k, px in zip(keys, np.split(li["l_extendedprice"], first[1:])):
            by_key[int(k)] = [[len(px), float(np.sum(px))]]
        rows = rows[n:]
    return [by_key[key] for _, key in binds]


POINT = {"order_point": order_point}


def binds_none(sf, rng, spec):
    return [()]


def binds_order_key_pool(sf, rng, spec):
    """`pool` order rows as (order row, its o_orderkey), in `blocks` runs of
    consecutive orders whose places the seed draws uniformly; requests then
    draw from the pool.  Runs, because the reference above pays per run."""
    from presto_tpu.connectors import tpch as H

    per = int(spec["pool"]) // int(spec["blocks"])
    starts = rng.choice(H.row_count("orders", sf) // per,
                        size=int(spec["blocks"]), replace=False) * per
    out = []
    for s in sorted(int(s) for s in starts):
        keys = H.generate("orders", sf, s, s + per)["o_orderkey"]
        out += [(s + i, int(k)) for i, k in enumerate(keys)]
    return out


BINDS = {"none": binds_none, "order_key_pool": binds_order_key_pool}


def bind_values(bind):
    """What of a bind goes into the EXECUTE text: all but the order row."""
    return bind[1:]


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------


def rows_equal(got, want, rel):
    """Row count, row order, keys and counts exact; floats to `rel`."""
    if len(got) != len(want):
        return False
    for g, w in zip(got, want):
        if len(g) != len(w):
            return False
        for a, b in zip(g, w):
            if isinstance(b, float):
                if not (isinstance(a, (int, float)) and np.isfinite(a)
                        and abs(a - b) <= rel * max(abs(b), 1.0)):
                    return False
            elif a != b:
                return False
    return True


# ---------------------------------------------------------------------------
# bytes a class has to read (the numerator of a memory-bound roofline share)
# ---------------------------------------------------------------------------


def bytes_read(sf, columns_read):
    """Rows times resident width over the columns one execution touches.
    `columns_read` is {table: {column: bytes per value}}, from the
    workload file; rows are the specification's at this scale."""
    from presto_tpu.connectors import tpch as H

    return sum(H.row_count(table, sf) * sum(widths.values())
               for table, widths in columns_read.items())
