#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path starts on the chip.

One process, one TPU v5e chip: loads TPC-H at --sf (default 10: lineitem
60 M rows, resident on the device), serves Q1, Q6, Q3, Q18 and a prepared
point lookup through the entry points a user calls — presto_tpu.connect
-> PrestoTpuServer -> StatementClient — and checks every answer against
a plain numpy reference streamed over the HOST generator while the
engine scans the DEVICE generator.  Each phase prints one JSON line as
it ends; a failed phase raises and the exit code is non-zero.  The last
line is {"ok": true, "device": {...}} only on a TPU.

    python chip_smoke.py                   # one chip, SF10 (the driver's run)
    python chip_smoke.py --mesh            # four chips: SF1 Q1/Q3/Q18 on the
                                           # in-process mesh vs one chip
    JAX_PLATFORMS=cpu python chip_smoke.py --sf 0.01 --allow-cpu   # rehearsal

It never sets a platform.  Times it prints are set-up information, not
results.
"""

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SERVED = (1, 6, 3, 18)      # TPC-H queries of the serve phase
MESH = (1, 3, 18)           # ... of the --mesh phase
ORDER_SLICE = 500_000       # orders per slice of the streamed reference
REL = 1e-4                  # float sums under float32_compute
MESH_REL = 1e-6             # --mesh sets no float32_compute: this script's
                            # choice, so DOUBLE is f64 there (emulated on a
                            # TPU); with the property the mesh computes in f32

POINT_SQL = ("SELECT count(*) c, sum(l_extendedprice) s "
             "FROM lineitem WHERE l_orderkey = ?")


def emit(phase, **kw):
    print(json.dumps({"phase": phase, **kw}), flush=True)


def check(cond, *what):
    """An assert that -O cannot remove: a failed check fails the phase."""
    if not cond:
        raise AssertionError(*what)


def ms_since(t0):
    return round((time.perf_counter() - t0) * 1e3, 1)


# ---------------------------------------------------------------------------
# phase: device
# ---------------------------------------------------------------------------


def phase_device(args):
    from importlib import metadata

    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu" and not args.allow_cpu:
        raise SystemExit(f"chip_smoke: no TPU (jax found {d0.platform!r}); "
                         "--allow-cpu rehearses, and never says ok")
    if args.mesh and len(devs) < 4:
        raise SystemExit(f"chip_smoke --mesh needs 4 devices, "
                         f"jax found {len(devs)}")

    from presto_tpu import native
    from presto_tpu.exec import compile_cache as CC

    def version(pkg):
        try:
            return metadata.version(pkg)
        except metadata.PackageNotFoundError:
            return None

    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    emit("device", **device, jax=jax.__version__, jaxlib=version("jaxlib"),
         libtpu=version("libtpu"),
         compile_cache_dir=CC.resolve_cache_dir(),
         cache_dir_from_JAX_COMPILATION_CACHE_DIR=bool(
             os.environ.get(CC.JAX_CACHE_ENV)),
         native_library="built" if native.available() else "numpy path")
    return device


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------


def _compiled(fn, *args):
    """AOT-compile fn for args; returns (executable, ran_as_tpu_kernel)."""
    import jax

    c = jax.jit(fn).lower(*args).compile()
    return c, "tpu_custom_call" in c.as_text()


def phase_kernels(on_tpu):
    import jax
    import jax.numpy as jnp

    from presto_tpu import types as T
    from presto_tpu.batch import Column
    from presto_tpu.exec import gather as G
    from presto_tpu.exec import kernels as K

    # production shapes on the chip; a few blocks under --allow-cpu (an
    # interpreted grid of production size does not finish compiling).
    # Inputs come from the host and every check is ONE jitted program:
    # on the chip each eager op would be a compile of its own.
    rng = np.random.default_rng(0)
    n = 6_000_000 if on_tpu else 4 * 8192
    k = 8
    vals = jnp.asarray(rng.random((k, n), np.float32) * 1e3)
    for n_groups in (6, 4096):
        t0 = time.perf_counter()
        gid = jnp.asarray(rng.integers(0, n_groups, n, np.int32))
        c, is_kernel = _compiled(
            lambda v, g: K.fused_group_sums(v, g, n_groups), vals, gid)
        got = np.asarray(c(vals, gid))
        want = np.asarray(jax.jit(jax.vmap(
            lambda v, g: jax.ops.segment_sum(v.astype(jnp.float64), g,
                                             num_segments=n_groups),
            in_axes=(0, None)))(vals, gid))
        check(got.shape == (k, n_groups), got.shape)
        check(np.isfinite(got).all())
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)
        if on_tpu:
            check(is_kernel, "fused_group_sums ran without tpu_custom_call")
        emit("kernels", kernel="fused_group_sums", k=k, n=n, groups=n_groups,
             tpu_custom_call=is_kernel, ms=ms_since(t0))

    # staged gather at ascending indices, against src[idx] and against
    # the rows recomputed from their index (no gather in that reference)
    n, m = (6_000_000, 2_000_000) if on_tpu else (3 * 4096, 4096)
    idx = jnp.asarray(np.sort(rng.integers(0, n, m, np.int32)))
    for w in (2, 16):
        t0 = time.perf_counter()

        def rows_of(i):
            return i.astype(jnp.uint32)[:, None] * jnp.uint32(2654435761) \
                + jnp.arange(w, dtype=jnp.uint32)[None, :] * jnp.uint32(40503)

        src = jax.jit(lambda: rows_of(jnp.arange(n, dtype=jnp.int32)))()
        c, is_kernel = _compiled(G.staged_gather, src, idx)
        got = c(src, idx)
        check(got.shape == (m, w))
        same = jax.jit(lambda g, s, i: jnp.array_equal(g, s[i])
                       & jnp.array_equal(g, rows_of(i)))(got, src, idx)
        check(bool(same), "staged_gather != src[idx]")
        check(not is_kernel, "staged_gather is XLA's gather: no kernel")
        emit("kernels", kernel="staged_gather", n=n, m=m, w=w,
             tpu_custom_call=is_kernel, ms=ms_since(t0))

    # the route the engine takes by itself: a request-order gather of a
    # mixed-width row through take_rows (staged on the chip)
    t0 = time.perf_counter()
    idx = jnp.asarray(rng.integers(0, n, m, np.int32))

    def take_and_compare(i):
        rows = jnp.arange(n, dtype=jnp.int64)
        cols = [rows * 7_000_000_011, rows.astype(jnp.float32) * 0.5,
                rows % 3 == 0]
        return jnp.stack([jnp.array_equal(g, a[i])
                          for g, a in zip(K.take_rows(cols, i), cols)])

    check(bool(jax.jit(take_and_compare)(idx).all()), "take_rows != a[idx]")
    emit("kernels", kernel="take_rows", n=n, m=m,
         route=G.gather_route(n, m, 4), ms=ms_since(t0))

    # orderable keys against a host sort
    nk = 1_000_000 if on_tpu else 20_000
    special = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1e-300, -1e-300,
                        1e300, -1e300, 1.0, -1.0])
    v64 = np.concatenate([special, rng.standard_normal(nk) * 1e6,
                          rng.standard_normal(nk) * 1e-6])
    t0 = time.perf_counter()
    k64 = np.asarray(jax.jit(lambda d: K._orderable_int(
        Column(d, None, T.DOUBLE)))(jnp.asarray(v64)))
    _check_orderable(v64, k64, strict=not on_tpu)
    emit("kernels", kernel="f64_orderable_key", n=len(v64),
         branch="pair" if jax.default_backend() == "tpu" else "arith",
         ms=ms_since(t0))
    with np.errstate(over="ignore"):  # 1e300 -> inf is wanted
        v32 = v64.astype(np.float32)
    t0 = time.perf_counter()
    k32 = np.asarray(jax.jit(lambda d: K._sort_operand_native(
        Column(d, None, T.REAL)))(jnp.asarray(v32)))
    _check_orderable(v32, k32, strict=True)
    emit("kernels", kernel="f32_sort_key", n=len(v32), dtype=str(k32.dtype),
         ms=ms_since(t0))


def _check_orderable(vals, keys, strict):
    """keys order like a host sort of vals: monotone over the finite
    values (strictly where `strict`: the TPU's f64 pair key may merge
    doubles beyond 48 significant bits), -inf first, +inf above every
    finite value, NaN largest, +-0 equal."""
    finite = np.isfinite(vals)
    order = np.argsort(vals[finite], kind="stable")
    v, k = vals[finite][order], keys[finite][order]
    check((k[1:] >= k[:-1]).all(), "orderable key is not monotone")
    if strict:
        check(((k[1:] > k[:-1]) | (v[1:] == v[:-1])).all(),
              "orderable key merges distinct values")
    check(keys[np.isneginf(vals)].max() <= k.min())
    check(keys[np.isposinf(vals)].min() >= k.max())
    check(keys[np.isnan(vals)].min() > keys[np.isposinf(vals)].max())
    zeros = keys[vals == 0]
    check((zeros == zeros[0]).all())


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------


def _last_stats(session, sql):
    """The server's own account of the newest execution of `sql`."""
    for st in reversed(session.history_snapshot()):
        if st.sql == sql:
            return st
    raise AssertionError(f"no history row for {sql[:60]!r}")


def point_binds(sf):
    """Three order rows (first, middle, last) and their orderkeys."""
    from presto_tpu.connectors import tpch as H

    n_orders = H.row_count("orders", sf)
    rows = [0, n_orders // 2, n_orders - 1]
    keys = [int(H.generate("orders", sf, r, r + 1)["o_orderkey"][0])
            for r in rows]
    return rows, keys


def phase_serve(sf):
    import presto_tpu
    from presto_tpu.catalog import tpch_catalog
    from presto_tpu.client import StatementClient
    from presto_tpu.server import PrestoTpuServer
    from presto_tpu.server.serving import ServingTier
    from tests.tpch_queries import QUERIES

    session = presto_tpu.connect(tpch_catalog(sf, cache_dir=None))
    # DOUBLE math in f32 on the device, as the benchmark's cells run it:
    # puts the fused-aggregate kernel on the path
    session.set("float32_compute", True)
    # a second door over the same session whose tier has no result
    # cache, so a repeated text really executes (built first: the
    # session's back-reference stays with the default tier below)
    session.set("result_cache_enabled", False)
    uncached_tier = ServingTier(session)
    session.set("result_cache_enabled", True)
    check(uncached_tier.result_cache is None)
    srv = PrestoTpuServer(session).start()
    srv_uncached = PrestoTpuServer(session, serving=uncached_tier).start()
    answers = {}
    try:
        for qid in SERVED:
            sql = QUERIES[qid]
            t0 = time.perf_counter()
            rows = list(StatementClient(srv.uri, sql).rows())
            cold_ms = ms_since(t0)
            cold = _last_stats(session, sql)
            check(cold.execution_mode == "compiled",
                  (qid, cold.execution_mode, cold.fallback_reason))
            # the identical text again on the default tier: the result
            # cache answers — that is the served path working
            again = list(StatementClient(srv.uri, sql).rows())
            cached = _last_stats(session, sql)
            check(cached.execution_mode == "cached", cached.execution_mode)
            check(again == rows)
            t0 = time.perf_counter()
            warm_rows = list(StatementClient(srv_uncached.uri, sql).rows())
            warm_ms = ms_since(t0)
            warm = _last_stats(session, sql)
            check(warm.execution_mode == "compiled",
                  (qid, warm.execution_mode, warm.fallback_reason))
            check(warm.compiles == 0, (qid, warm.compiles))
            check(warm_rows == rows)
            answers[qid] = rows
            emit("serve", query=f"q{qid}", rows=len(rows), cold_ms=cold_ms,
                 warm_ms=warm_ms, execution_mode=warm.execution_mode,
                 cached_repeat=cached.execution_mode,
                 compiles=cold.compiles, warm_compiles=warm.compiles,
                 compile_cache_hits=cold.compile_cache_hits)

        list(StatementClient(srv.uri,
                             f"PREPARE smoke_point FROM {POINT_SQL}").rows())
        answers["point"] = []
        for key in point_binds(sf)[1]:
            sql = f"EXECUTE smoke_point USING {key}"
            t0 = time.perf_counter()
            rows = list(StatementClient(srv.uri, sql).rows())
            st = _last_stats(session, sql)
            answers["point"].append(rows)
            emit("serve", query="point", bind=key, rows=len(rows),
                 ms=ms_since(t0), execution_mode=st.execution_mode,
                 compiles=st.compiles,
                 compile_cache_hits=st.compile_cache_hits)
    finally:
        srv.stop()
        srv_uncached.stop()
    import jax

    emit("serve", peak_device_bytes=(jax.devices()[0].memory_stats() or {})
         .get("peak_bytes_in_use"))
    # no scanned table fell to host generation (executor reads the WHOLE
    # table on the host once one column is not device-generable)
    for name in ("lineitem", "orders", "customer"):
        check(not hasattr(session.catalog.get(name), "_data"),
              f"{name} was generated on the host")
    return answers


# ---------------------------------------------------------------------------
# phase: check — a plain numpy reference over the HOST generator
# ---------------------------------------------------------------------------


def reference(sf, queries, order_slice=ORDER_SLICE):
    """Q1/Q6/Q3/Q18 in straight-line numpy over host-generated arrays,
    streamed by order ranges so host memory stays bounded: Q1/Q6 add up
    per slice, Q3/Q18 keep only their per-order partials.  Shares no
    code with the planner, executor or kernels."""
    from presto_tpu.connectors import tpch as H

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
        out[1] = [(g[0], g[1], s[0], s[1], s[2], s[3], s[0] / s[5],
                   s[1] / s[5], s[4] / s[5], int(round(s[5])))
                  for g, s in sorted(q1.items())]
    if 6 in queries:
        out[6] = [(q6,)]
    if 3 in queries:
        q3.sort(key=lambda r: (-r[1], r[2]))
        out[3] = q3[:10]  # dates stay day numbers, as the client shows them
    if 18 in queries:
        q18.sort(key=lambda r: (-r[3], r[2]))
        c_key = cu["c_custkey"]
        check((c_key[1:] > c_key[:-1]).all())
        out[18] = [(str(cu["c_name"][np.searchsorted(c_key, ck)]), ck, ok,
                    d, tp, q) for ck, ok, d, tp, q in q18[:100]]
    return out


def reference_points(sf):
    from presto_tpu.connectors import tpch as H

    out = []
    for row, key in zip(*point_binds(sf)):
        li = H.generate("lineitem", sf, row, row + 1)
        check((li["l_orderkey"] == key).all())
        out.append([(len(li["l_orderkey"]),
                     float(np.sum(li["l_extendedprice"])))])
    return out


def assert_rows(label, got, want, rel=REL):
    """Row count, row order, keys and counts exact; floats to `rel`."""
    check(len(got) == len(want), (label, len(got), len(want)))
    for i, (g, w) in enumerate(zip(got, want)):
        check(len(g) == len(w), (label, i, g, w))
        for a, b in zip(g, w):
            if isinstance(b, float):
                check(isinstance(a, (int, float)) and np.isfinite(a)
                      and abs(a - b) <= rel * max(abs(b), 1.0),
                      (label, i, g, w))
            else:
                check(a == b, (label, i, g, w))


class Background:
    """fn(*args) on a daemon thread; result() joins and re-raises.  The
    reference is host numpy only (minutes at SF10), so it runs beside
    the device phases, which spend their time compiling."""

    def __init__(self, fn, *args):
        self._out = self._exc = None

        def run():
            t0 = time.perf_counter()
            try:
                self._out = fn(*args)
            except BaseException as e:  # noqa: BLE001 — result() re-raises
                self._exc = e
            self.ms = ms_since(t0)

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def result(self):
        self._thread.join()
        if self._exc is not None:
            raise self._exc
        return self._out


def phase_check(answers, ref):
    t0 = time.perf_counter()
    want, want_points = ref.result()
    waited = ms_since(t0)
    for qid in SERVED:
        assert_rows(f"q{qid}", answers[qid], want[qid])
        emit("check", query=f"q{qid}", rows=len(want[qid]), equal=True)
    for got, point in zip(answers["point"], want_points):
        assert_rows("point", got, point)
    emit("check", query="point", binds=len(answers["point"]), equal=True,
         reference_ms=ref.ms, waited_for_reference_ms=waited)


# ---------------------------------------------------------------------------
# --mesh: the in-process mesh on four chips against one chip
# ---------------------------------------------------------------------------


def phase_mesh(sf=1.0, ndev=4):
    import jax

    import presto_tpu
    from presto_tpu.catalog import tpch_catalog
    from tests.tpch_queries import QUERIES

    cat = tpch_catalog(sf, cache_dir=None)
    dist = presto_tpu.connect(cat)
    dist.set("distributed", True)
    dist.set("mesh_devices", ndev)
    one = presto_tpu.connect(cat)

    def run_all(session, mode):
        out = {}
        for qid in MESH:
            r = session.sql(QUERIES[qid])
            check(r.stats.execution_mode == mode,
                  (qid, r.stats.execution_mode, r.stats.fallback_reason))
            out[qid] = list(r.rows)
        return out

    # three lanes side by side — four-chip seconds are billed fourfold
    # and nearly all of them are compiles: the mesh programs on a
    # thread, the one-chip programs here, the numpy reference on another
    ref = Background(reference, sf, set(MESH))
    mesh_lane = Background(run_all, dist, "distributed")
    one_rows = run_all(one, "compiled")
    mesh_rows = mesh_lane.result()
    want = ref.result()
    for qid in MESH:
        text = " ".join(QUERIES[qid].split())
        entries = [v for k, v in dist._dist_cache.items() if k[0] == text]
        check(entries and all(e != "DYNAMIC" for e in entries),
              f"q{qid}: the mesh program was dropped")
        assert_rows(f"mesh q{qid} vs one chip", mesh_rows[qid], one_rows[qid],
                    rel=MESH_REL)
        assert_rows(f"mesh q{qid} vs reference", mesh_rows[qid], want[qid],
                    rel=MESH_REL)
        assert_rows(f"one-chip q{qid} vs reference", one_rows[qid], want[qid],
                    rel=MESH_REL)
        emit("mesh", query=f"q{qid}", rows=len(mesh_rows[qid]),
             execution_mode="distributed", one_chip_mode="compiled",
             equal_to_one_chip=True, equal_to_reference=True)
    # code that has only met virtual devices may put everything on the
    # first: a scanned column's shards sit on ndev distinct devices
    col = getattr(cat.get("lineitem"), f"_dist_cols_{ndev}")["l_quantity"]
    shard_devs = {s.device for s in col.data.addressable_shards}
    check(len(shard_devs) == ndev, shard_devs)
    platforms = {d.platform for d in shard_devs}
    check(platforms == {jax.devices()[0].platform}, platforms)
    emit("mesh", lineitem_shards=len(col.data.addressable_shards),
         devices=sorted(str(d) for d in shard_devs))


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=10.0,
                    help="TPC-H scale factor of the serve phase (default 10)")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on another backend than a TPU: tiny "
                         "kernel shapes, and the run never says ok")
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: only the device phase and the "
                         "in-process mesh at SF1 against one chip")
    args = ap.parse_args(argv)

    device = phase_device(args)
    on_tpu = device["platform"] == "tpu"
    if args.mesh:
        phase_mesh()
    else:
        ref = Background(lambda sf: (reference(sf, set(SERVED)),
                                     reference_points(sf)), args.sf)
        phase_kernels(on_tpu)
        answers = phase_serve(args.sf)
        phase_check(answers, ref)
    print(json.dumps({"ok": on_tpu, "device": device}), flush=True)
    return 0 if on_tpu else 1


if __name__ == "__main__":
    sys.exit(main())
