"""The repartition's send layout (parallel/exchange._exchange_by_dest) on a
four-device virtual CPU mesh against a plain numpy reference written here:
every live row arrives exactly once at `dest`, in its input order within the
sender's bucket (range exchange: in `order_key` order), slot for slot; dead
and overflowed slots read the fill value and `sel` False; the guard trips
exactly when a bucket outgrows its capacity."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as PS

import presto_tpu
from presto_tpu import types as T
from presto_tpu.batch import Batch, Column, Dictionary
from presto_tpu.exec import kernels as K
from presto_tpu.parallel import dist_executor as DX
from presto_tpu.parallel import exchange as EX
from presto_tpu.parallel.mesh import AXIS, make_mesh
from tests.sqlite_oracle import assert_same_results, to_sqlite

NDEV = 4
ROWS = 96           # a shard's rows
SAMPLES = 8         # the range exchange's samples a shard
BIG = np.iinfo(np.int64).max
DICT = Dictionary(np.array(["ash", "birch", "cedar", "fir", "oak"]))
#: column -> (type, dictionary, has a validity mask)
COLUMNS = {
    "k64": (T.BIGINT, None, False),
    "f32": (T.REAL, None, True),
    "i32": (T.INTEGER, None, False),
    "name": (T.VARCHAR, DICT, True),
    "dec": (T.decimal(38, 2), None, True),      # two int64 limbs a row
}


def make_inputs(seed, dead):
    """{name: global array}: NDEV shards of ROWS rows, shard after shard."""
    rng = np.random.default_rng(seed)
    n = NDEV * ROWS
    data = {
        "k64": rng.integers(-(1 << 40), 1 << 40, n).astype(np.int64),
        "f32": rng.normal(size=n).astype(np.float32),
        "i32": rng.integers(-20, 20, n).astype(np.int32),   # ties: stability
        "name": rng.integers(0, len(DICT), n).astype(np.int32),
        "dec": rng.integers(-(1 << 50), 1 << 50, (n, 2)).astype(np.int64),
    }
    for name, (_t, _d, masked) in COLUMNS.items():
        if masked:
            data[name + "?"] = rng.random(n) < 0.7
    data["sel"] = rng.random(n) >= dead
    return data


def batch_of(arrays):
    cols = {name: Column(arrays[name], arrays.get(name + "?"), typ, dic)
            for name, (typ, dic, _m) in COLUMNS.items()}
    return Batch(cols, arrays["sel"])


def on_mesh(fn, arrays, extra=()):
    """fn(batch, *extra shards) -> (batch, guard) under shard_map over
    NDEV devices; -> ({name: global received array}, guard per shard)."""
    names = sorted(arrays)

    def inner(vals, more):
        out, overflow = fn(batch_of(dict(zip(names, vals))), *more)
        got = {"sel": out.sel}
        for name, c in out.columns.items():
            assert c.dictionary is COLUMNS[name][1]
            assert c.type == COLUMNS[name][0]
            got[name] = c.data
            if c.valid is not None:
                got[name + "?"] = c.valid
        return got, overflow[None]

    run = jax.jit(DX._shard_mapped(inner, make_mesh(NDEV),
                                   (PS(AXIS), PS(AXIS)), (PS(AXIS), PS(AXIS))))
    got, guard = run([arrays[k] for k in names], list(extra))
    return {k: np.asarray(v) for k, v in got.items()}, np.asarray(guard)


def reference(arrays, dest, cap, order_key=None):
    """The layout in numpy: sender s's bucket for d is its live rows with
    dest d, in input order (stably by order_key), cut at `cap`, padded with
    zeros; receiver r reads the senders' buckets for r one after another."""
    shard = lambda x, s: x[s * ROWS:(s + 1) * ROWS]  # noqa: E731
    want = {k: np.zeros((NDEV * NDEV * cap,) + v.shape[1:], v.dtype)
            for k, v in arrays.items()}
    guard = np.zeros(NDEV, bool)
    for s in range(NDEV):
        sel, dst = shard(arrays["sel"], s), shard(dest, s)
        for d in range(NDEV):
            rows = np.flatnonzero(sel & (dst == d))
            if order_key is not None:
                rows = rows[np.argsort(shard(order_key, s)[rows],
                                       kind="stable")]
            guard[s] |= len(rows) > cap
            rows = rows[:cap]
            at = (d * NDEV + s) * cap
            for k, v in arrays.items():
                want[k][at:at + len(rows)] = shard(v, s)[rows]
    return want, guard


def assert_layout(got, guard, want, want_guard):
    assert guard.tolist() == want_guard.tolist()
    assert sorted(got) == sorted(want)
    for k in want:
        assert got[k].dtype == want[k].dtype and got[k].shape == want[k].shape
        assert got[k].tobytes() == want[k].tobytes(), k


def hash_dest(arrays):
    key = Column(jax.numpy.asarray(arrays["k64"]), None, T.BIGINT, None)
    return np.asarray(EX.partition_hash([key]) % np.uint64(NDEV)).astype(np.int32)


def range_dest(arrays):
    """range_partition_batch's splitters, in numpy: each shard's sorted
    keys (dead rows as the largest value) sampled evenly, the samples of
    all shards sorted, cut into NDEV ranges."""
    key = arrays["i32"].astype(np.int64)
    pos = np.linspace(0, ROWS - 1, SAMPLES).astype(np.int32)
    samples = np.sort(np.concatenate([
        np.sort(np.where(arrays["sel"], key, BIG)[s * ROWS:(s + 1) * ROWS])[pos]
        for s in range(NDEV)]))
    cut = (np.arange(1, NDEV) * NDEV * SAMPLES) // NDEV
    dest = np.searchsorted(samples[cut], key, side="right")
    return np.clip(dest, 0, NDEV - 1).astype(np.int32), key


@pytest.mark.parametrize("dead", [0.0, 0.3, 1.0])
@pytest.mark.parametrize("kind", ["hash", "range", "hash_in_groups"])
def test_received_batch_is_the_reference_layout(kind, dead, monkeypatch):
    arrays = make_inputs(11, dead)
    slack = 2.0
    cap = int(np.ceil(slack * ROWS / NDEV))
    if kind == "range":
        dest, order_key = range_dest(arrays)
        got, guard = on_mesh(lambda b: EX.range_partition_batch(
            b, [("i32", True, None)], NDEV, AXIS, samples_per_shard=SAMPLES,
            slack=slack), arrays)
    else:
        if kind == "hash_in_groups":    # 11 operands, two a sort
            monkeypatch.setattr(K, "SORT_CARRY_WIDTH", 2)
        dest, order_key = hash_dest(arrays), None
        got, guard = on_mesh(lambda b: EX.repartition_batch(
            b, [b.columns["k64"]], NDEV, AXIS, slack=slack), arrays)
    want, want_guard = reference(arrays, dest, cap, order_key)
    assert_layout(got, guard, want, want_guard)
    live = arrays["sel"]
    assert got["sel"].sum() == live.sum() or want_guard.any()
    # every live row exactly once, at its destination
    recv = np.repeat(np.arange(NDEV), NDEV * cap)
    assert sorted(zip(recv[got["sel"]], got["k64"][got["sel"]])) == \
        sorted(zip(dest[live], arrays["k64"][live]))


@pytest.mark.parametrize("extra", [0, 1])
def test_guard_trips_one_row_past_capacity(extra):
    """Shard 1 sends `cap + extra` rows to destination 2: at `cap` the
    bucket is full and the guard is quiet; one more trips it on that
    shard alone, and the rows inside `cap` still arrive."""
    arrays = make_inputs(5, 0.0)
    cap = int(np.ceil(2.0 * ROWS / NDEV))
    dest = np.tile(np.array([0, 1, 3], np.int32), NDEV * ROWS // 3)
    dest[ROWS:ROWS + cap + extra] = 2
    got, guard = on_mesh(lambda b, d: EX._exchange_by_dest(
        b, d, NDEV, AXIS, 2.0), arrays, extra=[dest])
    want, want_guard = reference(arrays, dest, cap)
    assert want_guard.tolist() == [False, bool(extra), False, False]
    assert_layout(got, guard, want, want_guard)
    sent = got["sel"][(2 * NDEV + 1) * cap:(2 * NDEV + 2) * cap]
    assert sent.all()       # shard 1's bucket for 2: full either way


def test_a_tripped_guard_falls_back_and_answers_as_sqlite(
        tpch_catalog_tiny, tpch_sqlite_tiny):
    """Every order has o_shippriority 0: the single-phase aggregate's
    repartition sends all rows to one shard, the guard trips, and the
    query runs again off the mesh."""
    session = presto_tpu.connect(tpch_catalog_tiny)
    session.set("distributed", True)
    session.set("mesh_devices", NDEV)
    sql = ("select o_shippriority, count(distinct o_custkey) c, "
           "count(distinct o_clerk) d from orders group by o_shippriority")
    actual = session.sql(sql)
    stats = session.history_snapshot()[-1]
    assert "static assumption violated at runtime" in stats.fallback_reason
    assert stats.execution_mode != "distributed"
    assert "DYNAMIC" in session._dist_cache.values()
    expected = tpch_sqlite_tiny.execute(to_sqlite(sql)).fetchall()
    assert_same_results(actual.rows, expected, ordered=False)
