"""The copied reference against chip_smoke.py's at SF0.01, and its cache.

Run by hand: python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import reference as ref  # noqa: E402

SF = 0.01


@pytest.fixture(scope="module")
def smoke():
    import chip_smoke

    return chip_smoke


@pytest.fixture(scope="module")
def ours():
    return ref.streamed(SF, sorted(ref.STREAMED))


@pytest.mark.parametrize("name", sorted(ref.STREAMED))
def test_streamed_equals_chip_smoke(smoke, ours, name):
    qid = ref.STREAMED[name]
    want = smoke.reference(SF, {qid})[qid]
    assert ours[name] == [list(r) for r in want]
    assert ref.rows_equal(ours[name], want, rel=0.0)


def test_point_binds_equal_chip_smoke(smoke):
    rows, keys = smoke.point_binds(SF)
    want = smoke.reference_points(SF)
    assert len(want) == 3
    got = ref.order_point(SF, list(zip(rows, keys)))
    assert got == [[list(w[0])] for w in want]


def test_pool_is_seeded_and_keys_are_the_orders_own():
    spec = {"pool": 16, "blocks": 4}
    a = ref.binds_order_key_pool(SF, np.random.default_rng(2**31 + 11), spec)
    b = ref.binds_order_key_pool(SF, np.random.default_rng(2**31 + 11), spec)
    c = ref.binds_order_key_pool(SF, np.random.default_rng(5), spec)
    assert a == b and a != c and len(set(a)) == 16
    # four runs of four consecutive orders
    rows = [r for r, _ in a]
    assert sum(rows[i + 1] != rows[i] + 1 for i in range(15)) == 3
    assert ref.order_point(SF, a[5:7] + a[:1]) == [
        ref.order_point(SF, [b])[0] for b in a[5:7] + a[:1]]
    from presto_tpu.connectors import tpch as H

    for row, key in a:
        assert key == int(H.generate("orders", SF, row, row + 1)["o_orderkey"][0])
        assert ref.bind_values((row, key)) == (key,)


def test_rows_equal_is_exact_but_for_floats():
    want = [["A", 3, 100.0], ["B", 4, 0.5]]
    assert ref.rows_equal([("A", 3, 100.005), ("B", 4, 0.5)], want, 1e-4)
    assert not ref.rows_equal([("A", 3, 100.02), ("B", 4, 0.5)], want, 1e-4)
    assert not ref.rows_equal([("A", 4, 100.0), ("B", 4, 0.5)], want, 1e-4)
    assert not ref.rows_equal([("B", 4, 0.5), ("A", 3, 100.0)], want, 1e-4)
    assert not ref.rows_equal([("A", 3, 100.0)], want, 1e-4)
    assert not ref.rows_equal([("A", 3, float("nan")), ("B", 4, 0.5)], want, 1e-4)
    assert not ref.rows_equal([("A", 3, None), ("B", 4, 0.5)], want, 1e-4)


def test_cache_is_read_and_remade_when_the_source_changes(tmp_path, monkeypatch):
    calls = []
    real = ref.streamed
    monkeypatch.setattr(ref, "streamed",
                        lambda sf, names: calls.append(list(names)) or real(sf, names))
    first, computed = ref.cached_streamed(str(tmp_path), "cfg", SF, ["tpch_q6"])
    assert computed and calls == [["tpch_q6"]]
    again, computed = ref.cached_streamed(str(tmp_path), "cfg", SF, ["tpch_q6"])
    assert not computed and again == first and len(calls) == 1
    # only what is missing is computed
    both, computed = ref.cached_streamed(str(tmp_path), "cfg", SF,
                                         ["tpch_q1", "tpch_q6"])
    assert computed and calls[-1] == ["tpch_q1"] and both["tpch_q6"] == first["tpch_q6"]
    # a changed reference.py has another hash: its answers are made anew
    monkeypatch.setattr(ref, "source_hash", lambda: "0" * 12)
    _, computed = ref.cached_streamed(str(tmp_path), "cfg", SF, ["tpch_q6"])
    assert computed and calls[-1] == ["tpch_q6"]
    names = sorted(os.listdir(tmp_path))
    assert len(names) == 3 and sum("0" * 12 in n for n in names) == 1
    # a cache file that does not parse is made anew, not trusted
    bad = os.path.join(tmp_path, next(n for n in names if "0" * 12 in n))
    with open(bad, "w") as f:
        f.write("{")
    _, computed = ref.cached_streamed(str(tmp_path), "cfg", SF, ["tpch_q6"])
    assert computed
    with open(bad) as f:
        json.load(f)


def test_bytes_read():
    from presto_tpu.connectors import tpch as H

    n = H.row_count("lineitem", SF)
    assert ref.bytes_read(SF, {"lineitem": {"l_orderkey": 8, "l_tax": 4}}) == 12 * n
    assert ref.bytes_read(SF, {}) == 0
