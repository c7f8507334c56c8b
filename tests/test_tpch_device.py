"""Device-side TPC-H generator must match the host generator
column-for-column (same splitmix64 counters; see
presto_tpu/connectors/tpch_device.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import presto_tpu
from presto_tpu.connectors import tpch as H
from presto_tpu.connectors import tpch_device as D

SF = 0.05


def _decode(col):
    data = np.asarray(col.data)
    if col.dictionary is not None:
        return np.asarray(col.dictionary.values[
            np.clip(data, 0, len(col.dictionary) - 1)])
    return data


@pytest.mark.parametrize("table", sorted(D.DEVICE_COLUMNS))
def test_device_matches_host(table):
    cols = sorted(D.DEVICE_COLUMNS[table])
    host = H.generate(table, SF)
    dev = D.generate_device(table, SF, cols)
    for c in cols:
        got = _decode(dev[c])
        want = np.asarray(host[c])
        assert got.shape == want.shape, (c, got.shape, want.shape)
        if want.dtype == object:
            assert (got == want).all(), (c, got[:5], want[:5])
        elif np.issubdtype(want.dtype, np.floating):
            np.testing.assert_allclose(got, want, rtol=0, atol=0,
                                       err_msg=c)
        else:
            assert (got == want).all(), (c, got[:5], want[:5])


def test_device_row_ranges_consistent():
    """A chunked read concatenates to the full read (split independence)."""
    cols = ["l_orderkey", "l_quantity", "l_shipdate"]
    full = D.generate_device("lineitem", SF, cols)
    n_orders = int(H._TABLE_ROWS["orders"] * SF)
    mid = n_orders // 3
    a = D.generate_device("lineitem", SF, cols, 0, mid)
    b = D.generate_device("lineitem", SF, cols, mid, n_orders)
    for c in cols:
        cat = np.concatenate([np.asarray(a[c].data), np.asarray(b[c].data)])
        assert (cat == np.asarray(full[c].data)).all(), c


def test_format_dictionary_renders():
    d = D.FormatDictionary("Customer#", 9, 1000)
    vals = d.values[np.array([1, 42, 999])]
    assert vals.tolist() == ["Customer#000000001", "Customer#000000042",
                             "Customer#000000999"]
    assert len(d) == 1000


# ---------------------------------------------------------------------------
# lines -> their orders (expand_orders), and the chunked scan that uses it
# ---------------------------------------------------------------------------

_RNG = np.random.default_rng(42)
#: order line counts, each case against np.repeat: every order one line;
#: every order seven; the order at lines 126..132 crossing the first
#: tile's end, then random counts over several coarse tiles
EXPAND_CASES = {
    "all_1": np.ones(40_000, np.int64),
    "all_7": np.full(6_000, 7, np.int64),
    "crosses_a_tile": np.concatenate([np.ones(D.WINDOW_LINES - 2, np.int64),
                                      [7], _RNG.integers(1, 8, 12_000)]),
}


@pytest.mark.parametrize("case", sorted(EXPAND_CASES))
def test_expand_orders_matches_repeat(case):
    counts = EXPAND_CASES[case]
    n = int(counts.sum())
    payload = _RNG.integers(0, 1 << 12, len(counts))
    want = np.repeat(np.arange(len(counts)), counts)
    starts = np.cumsum(counts) - counts
    # slots past the last line (a chunk's padding) hold garbage: not read
    order, line, got = (np.asarray(a)[:n] for a in D.expand_orders(
        jnp.asarray(counts), n + 1000, jnp.asarray(payload)))
    assert (order == want).all()
    assert (line == np.arange(n) - starts[want] + 1).all()
    assert (got == payload[want]).all()


CHUNK_ORDERS = 16_000   # SF's 75,000 orders: four chunks and a short fifth
WINDOW_COLS = sorted(D.WINDOW_COLUMNS)


@pytest.fixture(scope="module")
def host_lineitem():
    return H.generate("lineitem", SF)


@pytest.fixture(scope="module")
def grid():
    return D.TpchChunkGrid(SF, *H.chunk_grid(SF, CHUNK_ORDERS))


@pytest.mark.parametrize("case", ["first", "middle_traced", "last_short"])
def test_chunk_scan_matches_host(case, grid, host_lineitem):
    """A chunk's lineitem columns (the `pad` path: static capacities,
    starts that may be traced) are the host generator's rows."""
    i = {"first": 0, "middle_traced": grid.nchunks // 2,
         "last_short": grid.nchunks - 1}[case]
    scan = lambda a: grid.build_scan("lineitem", WINDOW_COLS, a, False)  # noqa: E731
    raw, sel = (jax.jit(scan) if case == "middle_traced" else scan)(
        grid.chunk_args(i))
    lo, hi = grid.line_offsets[i], grid.line_offsets[i + 1]
    n = hi - lo
    assert (case == "last_short") == (n < grid.cap_lines - 20_000)
    assert np.asarray(sel)[:n].all() and not np.asarray(sel)[n:].any()
    for c in WINDOW_COLS:
        got = _decode(raw[c])[:n]
        want = np.asarray(host_lineitem[c])[lo:hi]
        assert (got == want).all(), (c, got[:5], want[:5])


def test_chunk_scan_lowers_without_scatter(grid):
    """The expansion takes no scatter-add (jnp.repeat's) into the chunk's
    program."""
    text = jax.jit(lambda a: grid.build_scan(
        "lineitem", WINDOW_COLS, a, True)).lower(grid.chunk_args(1)).as_text()
    assert "scatter" not in text


@pytest.mark.parametrize("sql, windowed", [
    ("SELECT sum(l_extendedprice * l_discount) FROM lineitem "
     "WHERE l_shipdate >= DATE '1994-01-01'", True),
    ("SELECT sum(l_quantity) FROM lineitem", False),
])
def test_window_lines_count_a_chunk_a_launch(sql, windowed, grid):
    """QueryStats.lineitem_window_lines: a chunk's line capacity for every
    launch whose scan expands lines from their orders, else 0."""
    from presto_tpu.catalog import tpch_catalog

    s = presto_tpu.connect(tpch_catalog(SF, cache_dir=None))
    s.properties.update(chunked_rows_threshold=100_000,
                        chunk_orders=CHUNK_ORDERS)
    st = s.sql(sql).stats
    assert (st.execution_mode, st.chunks_run) == ("chunked", grid.nchunks)
    assert st.lineitem_window_lines == \
        (st.chunks_run * grid.cap_lines if windowed else 0)
