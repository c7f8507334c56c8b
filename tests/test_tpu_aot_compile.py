"""Ask the chip's compiler, without the chip: the TPU-only kernel bodies
and backend branches of the served path, compiled at production shapes
for a described v5e (on-chip-measurement guide, section 2).  Nothing
runs, so these say nothing about results or times — chip_smoke.py does.

The topology is described inside a module-scoped fixture and everything
compiles in the test's own process: only one process may hold libtpu,
and under xdist every worker imports this file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from presto_tpu import types as T
from presto_tpu.batch import Batch, Column
from presto_tpu.exec import gather as G
from presto_tpu.exec import kernels as K

N_ROWS = 6_000_000  # one SF1 lineitem


@pytest.fixture(scope="module")
def topology():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an AOT TPU executable is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topology):
    return SingleDeviceSharding(topology.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """jax.default_backend() still says cpu here: steer the engine's
    TPU branches from the test, not through an option of the program."""
    monkeypatch.setattr(K, "_pallas_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("n_groups", [6, 128, 4096])
def test_fused_group_sums_tpu_body(one_chip, as_tpu, n_groups):
    c = _compile(lambda v, g: K.fused_group_sums(v, g, n_groups), one_chip,
                 ((8, N_ROWS), jnp.float32), ((N_ROWS,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_staged_gather_runs_as_xla_on_tpu(one_chip, as_tpu):
    c = _compile(G.staged_gather, one_chip,
                 ((N_ROWS, 16), jnp.uint32), ((2_000_000,), jnp.int32))
    assert "tpu_custom_call" not in c.as_text()


def test_f64_orderable_pair(one_chip):
    _compile(K._f64_orderable_pair, one_chip, ((N_ROWS,), jnp.float64))


def test_orderable_int_takes_the_pair_on_tpu(one_chip, as_tpu):
    _compile(lambda d: K._orderable_int(Column(d, None, T.DOUBLE)),
             one_chip, ((N_ROWS,), jnp.float64))


def test_f32_sort_key(one_chip, as_tpu):
    def key_and_sort(d):
        k = K._sort_operand_native(Column(d, None, T.REAL))
        return jax.lax.sort((k, jnp.arange(d.shape[0], dtype=jnp.int32)),
                            num_keys=1)

    c = _compile(key_and_sort, one_chip, ((N_ROWS,), jnp.float32))
    assert "bitcast" in c.as_text()


def test_pack_fetch_12_columns(one_chip):
    n = 100
    dts = [jnp.int64, jnp.int32, jnp.float32, jnp.float64, jnp.bool_,
           jnp.int16] * 2
    typs = [T.BIGINT, T.INTEGER, T.REAL, T.DOUBLE, T.BOOLEAN,
            T.SMALLINT] * 2

    def pack(sel, guard, *cols):
        b = Batch({f"c{i}": Column(c, sel if i % 3 == 0 else None, typs[i])
                   for i, c in enumerate(cols)}, sel)
        return K.pack_fetch(b, guard)[0]

    _compile(pack, one_chip, ((n,), jnp.bool_), ((), jnp.int32),
             *[((n,), d) for d in dts])


SCOPED_ROWS = 8_192   # the names are the point here, not the sizes: the
# v5e's compiler takes minutes over 64-bit sorts of N_ROWS


def _scoped_kernels():
    """name -> (function, shapes, the scopes the v5e's HLO must name)."""
    def sort_then_probe(build, probe):
        with jax.named_scope("Join"):
            return K.build_probe(build, probe)

    def take(a, b, idx):
        with jax.named_scope("Join"):
            return K.take_rows([a, b], idx)

    def agg(v, g):
        with jax.named_scope("Aggregate"):
            return K.fused_group_sums(v, g, 6), K.segment_max(v[0], g, 6)

    return {
        "build_probe": (sort_then_probe,
                        (((SCOPED_ROWS // 4,), jnp.int32),
                         ((SCOPED_ROWS,), jnp.int32)),
                        ["Join/k:build_probe/", "k:build_probe/k:sort/"]),
        "take_rows": (take, (((N_ROWS,), jnp.int32), ((N_ROWS,), jnp.float32),
                             ((SCOPED_ROWS,), jnp.int32)),
                      ["Join/k:take_rows."]),
        "aggregate": (agg, (((8, N_ROWS + 5), jnp.float32),
                            ((N_ROWS + 5,), jnp.int32)),
                      ["Aggregate/k:fused_group_sums/",
                       "Aggregate/k:fused_group_sums/"
                       "k:fused_group_sums.operand/",
                       "Aggregate/k:segment/"]),
    }


@pytest.mark.parametrize("kernel", ["build_probe", "take_rows", "aggregate"])
def test_scoped_kernels_lower_and_keep_their_names(one_chip, as_tpu, kernel):
    """The kernel scopes (observe/names.py) are debug info only: the scoped
    kernels still compile for the v5e, and the chip's HLO — whose
    instruction lines are what a profile's device events are named by —
    holds the scope in `op_name`."""
    fn, shapes, scopes = _scoped_kernels()[kernel]
    text = _compile(fn, one_chip, *shapes).as_text()
    for scope in scopes:
        assert scope in text, scope


#: table, scale, columns: what the cell sf1_mesh4_join scans, at SF1 — but
#: lineitem's columns that repeat an order's value over its lines at
#: SF0.01: `jnp.repeat` over a shard's 1.5 M lines takes the v5e's compiler
#: ~110 s (5 s at SF0.01; on the chip it is part of a cold set-up)
SHARDED_SCANS = {
    "lineitem_sf1": ("lineitem", 1.0, ["l_quantity", "l_extendedprice",
                                       "l_discount", "l_tax"]),
    "lineitem_repeats": ("lineitem", 0.01, [
        "l_orderkey", "l_quantity", "l_extendedprice", "l_discount", "l_tax",
        "l_returnflag", "l_linestatus", "l_shipdate"]),
    "orders_sf1": ("orders", 1.0, ["o_orderkey", "o_custkey", "o_orderdate",
                                   "o_shippriority"]),
    "customer_sf1": ("customer", 1.0, ["c_custkey", "c_mktsegment"]),
    # the cell ds100_mesh4_rollup: TPC-DS's store_sales at sf100, 72 M rows
    # a chip, the columns q36 reads (the grid supplies the arguments)
    "store_sales_sf100": ("store_sales", 100.0, [
        "ss_sold_date_sk", "ss_item_sk", "ss_store_sk", "ss_ext_sales_price",
        "ss_net_profit"]),
}


@pytest.mark.parametrize("scan", sorted(SHARDED_SCANS))
def test_sharded_generation_program_compiles_for_four_chips(topology, scan):
    """The program in which every chip of the mesh generates its own
    range of a table (parallel/dist_executor.shard_generator): one
    shard_map over the described 2x2, the ranges as traced per-shard
    scalars, DOUBLE born f32, and no collective in it."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from presto_tpu.catalog import TpcdsShardedTable, TpchTable
    from presto_tpu.parallel import dist_executor as DX
    from presto_tpu.parallel.mesh import AXIS

    name, sf, cols = SHARDED_SCANS[scan]
    mesh = Mesh(np.asarray(topology.devices[:4]), (AXIS,))
    spec = NamedSharding(mesh, PartitionSpec(AXIS))
    table = (TpcdsShardedTable if name == "store_sales" else TpchTable)(name, sf)
    fn, args = DX.shard_generator(table, cols, mesh, 4, True)
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=spec)
              for a in args]
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "all-to-all" not in text and "all-gather" not in text
    out, sel = jax.eval_shape(fn, *shapes)
    assert sel.shape == (4 * table.shard_grid(4).capacity(name),)
    for c in cols:
        want = jnp.float32 if table.schema[c].name == "DOUBLE" \
            else out[c].data.dtype
        assert out[c].data.dtype == want and out[c].data.shape == sel.shape
