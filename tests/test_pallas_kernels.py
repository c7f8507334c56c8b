"""Pallas kernel + float-key tests on the CPU interpreter path.  The
chip's compiler sees the TPU bodies in tests/test_tpu_aot_compile.py,
and chip_smoke.py runs them on the device."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from presto_tpu.exec import gather as G
from presto_tpu.exec import kernels as K


def test_fused_group_sums_matches_segment_sum():
    rng = np.random.default_rng(0)
    n, k, G = 120_000, 6, 17
    vals = jnp.asarray(rng.random((k, n)) * 1e4)
    gid = jnp.asarray(rng.integers(0, G, n).astype(np.int32))
    out = K.fused_group_sums(vals, gid, G)
    ref = np.stack([jax.ops.segment_sum(vals[i], gid, num_segments=G)
                    for i in range(k)])
    assert np.allclose(np.asarray(out), ref, rtol=1e-9)


def test_fused_group_sums_f32_inputs():
    rng = np.random.default_rng(1)
    n, G = 100_000, 8
    vals = jnp.asarray(rng.random((2, n)), dtype=jnp.float32)
    gid = jnp.asarray(rng.integers(0, G, n).astype(np.int32))
    out = K.fused_group_sums(vals, gid, G)
    assert out.dtype == jnp.float64 or out.dtype == jnp.float32
    ref = np.stack([jax.ops.segment_sum(vals[i].astype(jnp.float64), gid,
                                        num_segments=G) for i in range(2)])
    assert np.allclose(np.asarray(out, dtype=np.float64), ref, rtol=1e-5)


@pytest.mark.parametrize("n_groups", [6, 1500, 4096])
def test_fused_group_sums_tpu_body_interpreted(monkeypatch, n_groups):
    """The body only a TPU runs (f32 block partials, one-hot tiled over
    the groups), driven on the CPU through Pallas' TPU interpret mode."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(K, "_pallas_interpret", lambda: False)
    rng = np.random.default_rng(2)
    n, k = 40_000, 3
    vals = rng.random((k, n)).astype(np.float32)
    gid = rng.integers(0, n_groups, n).astype(np.int32)
    with pltpu.force_tpu_interpret_mode():
        out = K.fused_group_sums(jnp.asarray(vals), jnp.asarray(gid),
                                 n_groups)
    ref = np.stack([np.bincount(gid, vals[i], n_groups) for i in range(k)])
    assert out.shape == (k, n_groups)
    assert np.allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-4)


def _check_orderable(fn, vals):
    r = np.asarray(jax.jit(fn)(jnp.asarray(vals)))
    finite = np.isfinite(vals)
    o = np.argsort(vals[finite], kind="stable")
    k = r[finite][o]
    assert (k[1:] >= k[:-1]).all(), "not monotone"  # diff would wrap int64
    i_nan = np.where(np.isnan(vals))[0]
    i_inf = np.where(np.isposinf(vals))[0]
    i_ninf = np.where(np.isneginf(vals))[0]
    if len(i_nan) and len(i_inf):
        assert r[i_nan[0]] > r[i_inf[0]] >= k.max()
    if len(i_ninf):
        assert r[i_ninf[0]] <= k.min()
    # +-0 equal
    z = np.asarray(jax.jit(fn)(jnp.asarray([0.0, -0.0])))
    assert z[0] == z[1] == 0
    return r


VALS = None


def _vals():
    global VALS
    if VALS is None:
        rng = np.random.default_rng(3)
        VALS = np.concatenate([
            rng.standard_normal(100_000) * 10.0 ** rng.integers(-300, 300, 100_000),
            np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1.0, 2.0, 0.5,
                      np.nextafter(1.0, 2.0), np.nextafter(1.0, 0.0)]),
            np.round(rng.random(50_000) * 1e7) / 100.0,
        ])
    return VALS


def test_orderable_top_binade():
    # 2^-1023 is subnormal: a naive one-step scale collapses the whole
    # top binade (review finding); sentinels must stay above DBL_MAX
    vals = np.array([8.98e307, 9e307, 1e308, 1.5e308,
                     1.7976931348623157e308, -1.7976931348623157e308,
                     2.0 ** 1022, 2.0 ** 1023, np.inf, -np.inf, np.nan])
    r = np.asarray(jax.jit(K._f64_orderable_arith)(jnp.asarray(vals)))
    finite = np.isfinite(vals)
    k = r[finite][np.argsort(vals[finite])]
    # compare, don't diff: int64 differences of near-full-range keys wrap
    assert (k[1:] > k[:-1]).all()
    imax = np.iinfo(np.int64).max
    assert k.max() < imax - 16  # below the inf sentinel and row mask
    assert r[8] == imax - 16 and r[10] == imax - 8 and r[9] == -(imax - 16)


def test_orderable_arith_exact():
    vals = _vals()
    r = _check_orderable(K._f64_orderable_arith, vals)
    # exact path: injective on normal-range values
    nz = np.isfinite(vals) & (np.abs(vals) >= 2.2250738585072014e-308)
    assert len(np.unique(vals[nz])) == len(np.unique(r[nz]))


def test_orderable_pair_monotone():
    vals = _vals()
    r = _check_orderable(K._f64_orderable_pair, vals)
    # pair path: injective at >= 48-bit granularity (money values)
    money = np.round(np.random.default_rng(4).random(50_000) * 1e7) / 100.0
    rm = np.asarray(jax.jit(K._f64_orderable_pair)(jnp.asarray(money)))
    assert len(np.unique(money)) == len(np.unique(rm))


def test_fused_agg_in_query(tpch_catalog_tiny):
    import presto_tpu

    s = presto_tpu.connect(tpch_catalog_tiny)
    on = s.sql("SELECT l_returnflag, count(*), sum(l_extendedprice), "
               "avg(l_quantity) FROM lineitem GROUP BY l_returnflag "
               "ORDER BY 1").rows
    s2 = presto_tpu.connect(tpch_catalog_tiny)
    s2.set("pallas_fused_agg", False)
    off = s2.sql("SELECT l_returnflag, count(*), sum(l_extendedprice), "
                 "avg(l_quantity) FROM lineitem GROUP BY l_returnflag "
                 "ORDER BY 1").rows
    assert len(on) == len(off)
    for a, b in zip(on, off):
        assert a[0] == b[0] and a[1] == b[1]
        assert abs(a[2] - b[2]) < 1e-6 * abs(b[2])
        assert abs(a[3] - b[3]) < 1e-9 * abs(b[3])


def _spy_fused(monkeypatch):
    """Record every operand K.fused_group_sums is called with."""
    operands = []
    orig = K.fused_group_sums

    def spy(vals, gid, n_groups):
        operands.append(vals)
        return orig(vals, gid, n_groups)

    monkeypatch.setattr(K, "fused_group_sums", spy)
    return operands


#: case -> (aggregates beside the group key, how many they are, rows the
#: fused operand holds).
#: count(x) reads only x's validity, so it rides the fused pass whatever
#: x's type; beside sum/avg of the same (x, filter) it IS their count row.
COUNT_X_CASES = {
    "double": ("count(l_quantity), count(*)", 2, 2),
    "bigint": ("count(l_orderkey), count(*)", 2, 2),
    "null_arg": ("count(CASE WHEN l_discount > 0.05 THEN l_quantity END), "
                 "count(*)", 2, 2),
    "filter": ("count(l_quantity) FILTER (WHERE l_shipdate > "
               "DATE '1995-01-01'), count(*)", 2, 2),
    # avg's rows, sum's rows; the count reuses avg's count row
    "beside_avg_sum": ("count(l_quantity), avg(l_quantity), "
                       "sum(l_quantity)", 3, 4),
    # the filter is part of the row's identity: no reuse across filters
    "other_filter": ("count(l_quantity) FILTER (WHERE l_tax > 0.02), "
                     "avg(l_quantity)", 2, 3),
}


@pytest.mark.parametrize("case", sorted(COUNT_X_CASES))
def test_count_of_argument_rides_the_fused_pass(tpch_catalog_tiny,
                                                monkeypatch, case):
    import presto_tpu

    aggs, n_aggs, operand_rows = COUNT_X_CASES[case]
    sql = (f"SELECT l_returnflag, {aggs} FROM lineitem "
           "GROUP BY l_returnflag ORDER BY 1")
    off = presto_tpu.connect(tpch_catalog_tiny)
    off.set("float32_compute", True)
    off.set("pallas_fused_agg", False)
    expected = off.sql(sql).rows
    assert off.history_snapshot()[-1].aggs_fused == 0
    on = presto_tpu.connect(tpch_catalog_tiny)
    on.set("float32_compute", True)
    operands = _spy_fused(monkeypatch)
    got = on.sql(sql).rows
    assert len(got) == len(expected) == 3
    for g, e in zip(got, expected):
        for x, y in zip(g, e):
            if isinstance(y, float):
                assert x == pytest.approx(y, rel=1e-9)
            else:
                assert x == y and type(x) is type(y)
    assert [o.shape[0] for o in operands] == [operand_rows]
    st = on.history_snapshot()[-1]
    assert (st.aggs_fused, st.aggs_unfused) == (n_aggs, 0)


def test_single_step_q1_operand_did_not_move(tpch_catalog_tiny, monkeypatch):
    """One chip: Q1 is a SINGLE aggregate, avg is fused whole and no
    count(x) exists — one fused call over (15, rows), as before
    count(x) became fusable."""
    import presto_tpu
    from presto_tpu.exec import compile_cache as CC
    from tests.tpch_queries import QUERIES

    s = presto_tpu.connect(tpch_catalog_tiny, execution_mode="compiled")
    s.set("float32_compute", True)
    CC.clear()  # traced here, not taken from the process-wide memo
    operands = _spy_fused(monkeypatch)
    s.sql(QUERIES[1])
    st = s.history_snapshot()[-1]
    assert st.execution_mode == "compiled"
    assert [o.shape[0] for o in operands] == [15]
    assert operands[0].shape[1] >= 32_768
    assert (st.aggs_fused, st.aggs_unfused) == (8, 0)


def test_operand_rows_without_count_of_argument_keep_their_order(
        tpch_catalog_tiny, monkeypatch):
    """Without a count(x) the operand is what it was: one row a
    count(*)/count_if, a value row and a count row a sum/avg, in the
    aggregates' order.  Each row's total names it."""
    import presto_tpu

    aggs = ("count(*), sum(l_extendedprice), count_if(l_discount > 0.05), "
            "avg(l_quantity), sum(l_tax) FILTER (WHERE l_quantity < 10)")
    s = presto_tpu.connect(tpch_catalog_tiny, execution_mode="dynamic")
    s.set("float32_compute", True)
    operands = _spy_fused(monkeypatch)
    s.sql(f"SELECT l_returnflag, {aggs} FROM lineitem GROUP BY l_returnflag")
    assert len(operands) == 1 and operands[0].shape[0] == 8
    totals = np.asarray(operands[0], dtype=np.float64).sum(axis=1)
    off = presto_tpu.connect(tpch_catalog_tiny)
    off.set("pallas_fused_agg", False)
    n, ext, n_disc, qty, tax, n_small = off.sql(
        "SELECT count(*), sum(l_extendedprice), count_if(l_discount > 0.05), "
        "sum(l_quantity), sum(l_tax) FILTER (WHERE l_quantity < 10), "
        "count(*) FILTER (WHERE l_quantity < 10) FROM lineitem").rows[0]
    np.testing.assert_allclose(
        totals, [n, ext, n, n_disc, qty, n, tax, n_small], rtol=1e-5)


# ---------------------------------------------------------------------------
# gather-aware tier (exec/gather.py): sort-order staging must be
# BYTE-IDENTICAL to the flat packed gather
# ---------------------------------------------------------------------------


@pytest.fixture
def tiny_gather(monkeypatch):
    """Shrink the routing constant so the staged tier engages at test
    sizes; 'force' opts in to staging off-TPU (auto mode is TPU-only)."""
    monkeypatch.setenv("PRESTO_TPU_GATHER", "force")
    monkeypatch.setattr(G, "_STAGED_MIN_INDICES", 1)
    yield


def _dtype_arrays(n, rng):
    """One array per engine dtype class take_rows packs differently."""
    return [
        jnp.asarray(rng.integers(-(1 << 30), 1 << 30, n).astype(np.int32)),
        jnp.asarray(rng.random(n).astype(np.float32)),
        jnp.asarray(rng.integers(-(1 << 60), 1 << 60, n)),      # i64 pair
        jnp.asarray(rng.random(n)),                             # f64 direct
        jnp.asarray(rng.integers(0, 2, n).astype(bool)),
        jnp.asarray(rng.integers(-100, 100, n).astype(np.int16)),
    ]


def _request_indices(dist, n, m, rng):
    """Request-order indices into n rows: spread evenly, piled on the
    two ends of the source, or hitting nearly every row."""
    if dist == "skewed":
        idx = np.where(rng.integers(0, 2, m) == 0, 0, n - 1)
    elif dist == "dense":
        idx = rng.permutation(np.arange(m) % n)
    else:
        idx = rng.integers(0, n, m)
    return jnp.asarray(idx.astype(np.int32))


@pytest.mark.parametrize("dist", ["uniform", "skewed", "dense"])
def test_staged_take_rows_matches_flat(tiny_gather, monkeypatch, dist):
    rng = np.random.default_rng(7)
    n, m = 5000, 4096
    arrays = _dtype_arrays(n, rng)
    idx = _request_indices(dist, n, m, rng)
    assert G.gather_route(n, m, 8) == "staged"
    staged = K.take_rows(arrays, idx)
    monkeypatch.setenv("PRESTO_TPU_GATHER", "flat")
    flat = K.take_rows(arrays, idx)
    for a, b in zip(flat, staged):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_staged_take_rows_presorted(tiny_gather, monkeypatch):
    rng = np.random.default_rng(8)
    n, m = 3000, 2048
    arrays = _dtype_arrays(n, rng)
    sidx = jnp.asarray(np.sort(rng.integers(0, n, m)).astype(np.int32))
    staged = K.take_rows(arrays, sidx, presorted=True)
    monkeypatch.setenv("PRESTO_TPU_GATHER", "flat")
    flat = K.take_rows(arrays, sidx)
    for a, b in zip(flat, staged):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_gather_batch_staged_oob_and_validity(tiny_gather, monkeypatch):
    """gather_batch clips out-of-range indices and ANDs idx_valid the
    same way on both routes, across validity masks."""
    from presto_tpu import types as T
    from presto_tpu.batch import Batch, Column

    rng = np.random.default_rng(11)
    n, m = 2000, 2048
    cols = {
        "a": Column(jnp.asarray(rng.integers(0, 99, n).astype(np.int32)),
                    jnp.asarray(rng.integers(0, 2, n).astype(bool)),
                    T.INTEGER, None),
        "b": Column(jnp.asarray(rng.random(n)), None, T.DOUBLE, None),
    }
    b = Batch(cols, jnp.asarray(rng.integers(0, 2, n).astype(bool)))
    idx = jnp.asarray(rng.integers(-50, n + 50, m).astype(np.int32))
    iv = jnp.asarray(rng.integers(0, 2, m).astype(bool))
    staged = K.gather_batch(b, idx, idx_valid=iv)
    monkeypatch.setenv("PRESTO_TPU_GATHER", "flat")
    flat = K.gather_batch(b, idx, idx_valid=iv)
    assert np.array_equal(np.asarray(staged.sel), np.asarray(flat.sel))
    for name in cols:
        sc, fc = staged.columns[name], flat.columns[name]
        assert np.array_equal(np.asarray(sc.data), np.asarray(fc.data))
        if fc.valid is not None:
            assert np.array_equal(np.asarray(sc.valid), np.asarray(fc.valid))


def test_staged_gather_empty_inputs(tiny_gather):
    src = jnp.zeros((0, 2), jnp.uint32)
    out = G.staged_gather(jnp.zeros((16, 2), jnp.uint32),
                          jnp.zeros((0,), jnp.int32))
    assert out.shape == (0, 2)
    # empty SOURCE goes through take_rows' zero-fill early return
    zero = K.take_rows([jnp.zeros((0,), jnp.int32)],
                       jnp.asarray([0, 0], dtype=jnp.int32))
    assert zero[0].shape == (2,)


def test_sort_order_plan_keeps_alignment():
    rng = np.random.default_rng(12)
    m = 5000
    idx = jnp.asarray(rng.integers(0, 1000, m).astype(np.int32))
    a = jnp.asarray(rng.integers(0, 7, m))
    flag = jnp.asarray(rng.integers(0, 2, m).astype(bool))
    sidx, (a2, f2) = K.sort_order_plan(idx, a, flag)
    assert (np.diff(np.asarray(sidx)) >= 0).all()
    assert f2.dtype == jnp.bool_
    before = sorted(zip(np.asarray(idx).tolist(), np.asarray(a).tolist(),
                        np.asarray(flag).tolist()))
    after = sorted(zip(np.asarray(sidx).tolist(), np.asarray(a2).tolist(),
                       np.asarray(f2).tolist()))
    assert before == after


# ---- routing heuristics (size/width crossover) ----------------------------


def test_gather_route_crossovers(monkeypatch):
    monkeypatch.setenv("PRESTO_TPU_GATHER", "force")
    M = G._STAGED_MIN_INDICES
    # large + wide: staged, both orders
    assert G.gather_route(1 << 23, M, 4) == "staged"
    assert G.gather_route(1 << 23, M, 4, presorted=True) == "staged"
    # below the index threshold: flat
    assert G.gather_route(1 << 23, M - 1, 8) == "flat"
    # narrow request-order gathers can't amortize the co-sort home...
    assert G.gather_route(1 << 23, M, 1) == "flat"
    # ...but presorted ones skip it, so width 1 still stages
    assert G.gather_route(1 << 23, M, 1, presorted=True) == "staged"
    # degenerate sources
    assert G.gather_route(0, M, 4) == "flat"
    assert G.gather_route(1 << 23, M, 0) == "flat"


def test_gather_route_env_off(monkeypatch):
    monkeypatch.setenv("PRESTO_TPU_GATHER", "flat")
    assert G.gather_route(1 << 23, 1 << 22, 8) == "flat"
    assert not G.sort_order_worthwhile(1 << 22, 4)


def test_gather_route_auto_is_tpu_only(monkeypatch):
    """Auto mode must NOT stage off-TPU: the routing constants are the
    TPU's."""
    monkeypatch.delenv("PRESTO_TPU_GATHER", raising=False)
    assert jax.default_backend() != "tpu"
    assert G.gather_route(1 << 23, 1 << 22, 8) == "flat"
    assert G.gather_route(1 << 23, 1 << 22, 8, presorted=True) == "flat"
    assert not G.sort_order_worthwhile(1 << 22, 4)


def test_sort_order_worthwhile_gate(monkeypatch):
    monkeypatch.setenv("PRESTO_TPU_GATHER", "force")
    M = G._STAGED_MIN_INDICES
    assert G.sort_order_worthwhile(M, 3)
    assert not G.sort_order_worthwhile(M - 1, 3)  # too small
    assert not G.sort_order_worthwhile(M, 0)      # build not wider
    assert not G.sort_order_worthwhile(M, -2)


def test_batch_word_width():
    from presto_tpu import types as T
    from presto_tpu.batch import Batch, Column

    n = 8
    b = Batch({
        "i": Column(jnp.zeros((n,), jnp.int32), None, T.INTEGER, None),
        "l": Column(jnp.zeros((n,), jnp.int64),
                    jnp.ones((n,), bool), T.BIGINT, None),
        "d": Column(jnp.zeros((n,), jnp.float64), None, T.DOUBLE, None),
    }, jnp.ones((n,), bool))
    # i32=1, i64+valid=3, f64=2
    assert K.batch_word_width(b) == 6


def test_expanding_join_sort_order_materialization(tiny_gather):
    """One-to-many join whose build side is WIDER than the probe, under
    an order-insensitive consumer: the executor pre-permutes the
    expansion into build-index order (sort_order_plan) and gathers the
    wide side presorted.  The output row SET must equal the flat
    path's; the row ORDER may differ — that is the point."""
    from presto_tpu import types as T
    from presto_tpu.batch import Batch, Column
    from presto_tpu.exec.executor import Executor
    from presto_tpu.plan import nodes as P

    rng = np.random.default_rng(13)
    nl, nr = 1500, 2000
    lkeys = rng.integers(0, 500, nl).astype(np.int64)
    rkeys = rng.integers(0, 500, nr).astype(np.int64)
    left = Batch({"x": Column(jnp.asarray(lkeys), None, T.BIGINT, None)},
                 jnp.ones((nl,), bool))
    right = Batch({
        "y": Column(jnp.asarray(rkeys), None, T.BIGINT, None),
        "p": Column(jnp.asarray(rng.random(nr)), None, T.DOUBLE, None),
        "q": Column(jnp.asarray(rng.integers(0, 9, nr)),
                    jnp.asarray(rng.integers(0, 2, nr).astype(bool)),
                    T.BIGINT, None),
        "r": Column(jnp.asarray(rng.integers(0, 7, nr).astype(np.int32)),
                    None, T.INTEGER, None),
    }, jnp.ones((nr,), bool))
    node = P.Join(P.Values(), P.Values(), "INNER", [("x", "y")])

    def run(mark):
        ex = Executor.__new__(Executor)
        ex.static = False
        ex.guards = []
        ex.monitor = None
        ex.mem = None
        # ordering-aware execution state (a bare harness Executor skips
        # __init__; mirror its round-8 fields)
        ex.session = type("S", (), {"properties": {}})()
        ex.sort_stats = {}
        ex._sort_memo = {}
        ex._perm_memo = {}
        ex._batch_order = {}
        from presto_tpu.exec.executor import EvalContext

        ex.ctx = EvalContext()
        if mark:
            ex._oi_ids = {id(node)}
        out = ex._join_batches(left, right, node)
        sel = np.asarray(out.sel)
        rows = []
        for i in np.flatnonzero(sel):
            row = []
            for name in ("x", "y", "p", "q", "r"):
                c = out.columns[name]
                v = None if (c.valid is not None
                             and not bool(np.asarray(c.valid)[i])) \
                    else np.asarray(c.data)[i].item()
                row.append(v)
            rows.append(tuple(row))
        return sorted(rows, key=repr)

    assert G.sort_order_worthwhile(1, K.batch_word_width(right)
                                   - K.batch_word_width(left))
    marked = run(mark=True)
    flat = run(mark=False)
    assert marked == flat and len(marked) > 0
