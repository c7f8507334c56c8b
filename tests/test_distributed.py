"""Distributed execution over the 8-device virtual CPU mesh vs the sqlite
oracle (reference analog: AbstractTestDistributedQueries on
DistributedQueryRunner — a fake multi-node cluster in one process,
presto-tests/.../DistributedQueryRunner.java:78)."""

import jax
import jax.numpy as jnp
import pytest

import presto_tpu
from tests.sqlite_oracle import assert_same_results, to_sqlite
from tests.tpch_queries import QUERIES

ORDERED = {1, 2, 3, 4, 5, 7, 8, 9, 10, 11, 12, 13, 15, 16, 18, 20, 21, 22}


@pytest.fixture(scope="module")
def dsession(tpch_catalog_tiny):
    s = presto_tpu.connect(tpch_catalog_tiny)
    s.set("distributed", True)
    return s


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8


# q21's mesh program alone costs ~40s of compile on the 1-core CI box;
# test_all_22_tpch_queries_distribute still covers it in tier 1
def test_make_mesh_refuses_more_devices_than_present():
    """Too few devices is an error, never a quiet move to another
    backend's (virtual) devices."""
    from presto_tpu.parallel.mesh import make_mesh

    have = len(jax.devices())
    assert make_mesh(have).devices.size == have
    with pytest.raises(RuntimeError, match=f"need {have + 1} devices"):
        make_mesh(have + 1)


def test_cpu_child_launchers_refuse_under_a_tpu_parent(monkeypatch):
    from presto_tpu.parallel import cluster as C
    from presto_tpu.parallel.mesh import refuse_cpu_children

    refuse_cpu_children("a CPU parent")  # fine on the CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU child processes"):
        C.launch_local_cluster(None, "tpch:0.01:", nworkers=2)


@pytest.mark.parametrize("qid", [
    pytest.param(q, marks=pytest.mark.slow) if q == 21 else q
    for q in sorted(QUERIES)])
def test_tpch_query_distributed(qid, dsession, tpch_sqlite_tiny):
    sql = QUERIES[qid]
    actual = dsession.sql(sql)
    expected = tpch_sqlite_tiny.execute(to_sqlite(sql)).fetchall()
    assert_same_results(actual.rows, expected, ordered=qid in ORDERED)


def test_distributed_actually_distributes(dsession):
    """The headline plans must run the collective path, not the fallback:
    check the distributed plan cache holds compiled entries for Q1/Q6
    (scan->partial agg->gather->final) and Q3 (repartition joins)."""
    for qid in (1, 3, 6):
        dsession.sql(QUERIES[qid])
    cache = getattr(dsession, "_dist_cache", {})
    compiled = [k for k, v in cache.items() if v != "DYNAMIC"]
    assert len(compiled) >= 2, (
        f"expected >=2 distributed plans compiled, cache={list(cache.values())!r}")


def test_repartition_group_by(dsession, tpch_sqlite_tiny):
    """Large-NDV group key forces the repartition (all_to_all) aggregate."""
    sql = ("select o_custkey, count(*) c, sum(o_totalprice) s from orders "
           "group by o_custkey order by s desc limit 10")
    actual = dsession.sql(sql)
    expected = tpch_sqlite_tiny.execute(to_sqlite(sql)).fetchall()
    assert_same_results(actual.rows, expected, ordered=True)


def test_distributed_minby_checksum(dsession, tpch_sqlite_tiny):
    """min_by/max_by/checksum decompose partial->final across shards
    (distribute.py _split_partial_final); results must match the
    single-device path."""
    sql = ("SELECT l_returnflag, max_by(l_shipmode, l_extendedprice), "
           "checksum(l_orderkey), min_by(l_partkey, l_extendedprice) "
           "FROM lineitem GROUP BY l_returnflag")
    dist = sorted(dsession.sql(sql).rows)
    import presto_tpu
    single = presto_tpu.connect(dsession.catalog)
    assert sorted(single.sql(sql).rows) == dist
    # global (no keys) goes through the same split
    g = "SELECT checksum(l_orderkey), max_by(l_shipmode, l_extendedprice) FROM lineitem"
    assert dsession.sql(g).rows == single.sql(g).rows


def test_distributed_sample_sort(tpch_catalog_tiny, tpch_sqlite_tiny):
    """P11: ORDER BY over sharded data goes through the range all_to_all +
    local sort + ordered gather path and matches the oracle exactly."""
    import presto_tpu
    from presto_tpu.plan import nodes as P

    s = presto_tpu.connect(tpch_catalog_tiny)
    s.set("distributed", True)
    s.set("distributed_sort_threshold_rows", 1000)
    sql = ("SELECT l_orderkey, l_linenumber, l_extendedprice FROM lineitem "
           "WHERE l_quantity < 30 ORDER BY l_extendedprice DESC, l_orderkey, "
           "l_linenumber")
    actual = s.sql(sql)
    expected = tpch_sqlite_tiny.execute(to_sqlite(sql)).fetchall()
    # the oracle's rows, in the order of the engine's own values: the mesh
    # generates l_extendedprice on its chips, where XLA turns the
    # generator's `cents / 100.0` into a multiplication (one ulp), so two
    # prices the host rounds apart can tie here and fall back on the keys
    assert_same_results(actual.rows, expected, ordered=False)
    order = [(-r[2], r[0], r[1]) for r in actual.rows]
    assert order == sorted(order)
    # the plan must contain a range exchange (not a gather-then-sort)
    entry = next(v for v in s._dist_cache.values() if v != "DYNAMIC")
    dplan = entry[0]
    kinds = []

    def walk(n):
        if isinstance(n, P.Exchange):
            kinds.append(n.kind)
        for src in n.sources:
            walk(src)

    walk(dplan.root)
    assert "range" in kinds, kinds


def test_distributed_sort_strings_and_nulls(tpch_catalog_tiny, tpch_sqlite_tiny):
    import presto_tpu

    s = presto_tpu.connect(tpch_catalog_tiny)
    s.set("distributed", True)
    s.set("distributed_sort_threshold_rows", 1000)
    sql = ("SELECT l_shipmode, l_orderkey, l_linenumber FROM lineitem "
           "ORDER BY l_shipmode, l_orderkey, l_linenumber LIMIT 5000")
    actual = s.sql(sql)
    expected = tpch_sqlite_tiny.execute(to_sqlite(sql)).fetchall()
    assert_same_results(actual.rows, expected, ordered=True)


def test_all_22_tpch_queries_distribute(dsession):
    """VERDICT r2 item 3: every TPC-H query must take the collective
    path — each run must add a compiled (non-DYNAMIC) _dist_cache entry.
    Windows hash-partition, approx_distinct merges HLL state,
    RIGHT/FULL joins repartition, UNNEST stays static."""
    import tests.tpch_queries as TQ

    for qid in sorted(TQ.QUERIES):
        dsession.sql(TQ.QUERIES[qid])
    # after running all 22, the memo must hold ONLY compiled entries —
    # any DYNAMIC value means some query fell off the collective path
    cache = dsession._dist_cache
    dynamic = [k for k, v in cache.items() if v == "DYNAMIC"]
    assert not dynamic, f"queries fell back to single-device: {dynamic}"
    assert len(cache) >= 22


def test_q1_partial_counts_ride_the_fused_pass(monkeypatch):
    """On the mesh avg(x) is partial_sum_double(x) + count(x): the three
    counts are the sums' own count rows of the one fused pass, and no
    integer segment_sum reads a shard's rows.  SF0.05 on four devices:
    a shard (75 k rows) is over the fused kernel's 32,768-row gate."""
    from presto_tpu.catalog import tpch_catalog
    from presto_tpu.exec import kernels as K

    cat = tpch_catalog(0.05, cache_dir=None)
    one = presto_tpu.connect(cat)
    one.set("float32_compute", True)
    expected = one.sql(QUERIES[1]).rows

    segment, fused = [], []
    seg, fus = K.segment_sum, K.fused_group_sums

    def segment_spy(x, gid, n_groups):
        segment.append((x.dtype, x.shape[0]))
        return seg(x, gid, n_groups)

    def fused_spy(vals, gid, n_groups):
        fused.append(vals.shape)
        return fus(vals, gid, n_groups)

    monkeypatch.setattr(K, "segment_sum", segment_spy)
    monkeypatch.setattr(K, "fused_group_sums", fused_spy)
    s = presto_tpu.connect(cat)
    s.set("float32_compute", True)
    s.set("distributed", True)
    s.set("mesh_devices", 4)
    for _ in range(2):  # traced, then replayed from the cached program
        got = s.sql(QUERIES[1]).rows
        st = s.history_snapshot()[-1]
        assert (st.execution_mode, st.fallback_reason) == ("distributed", "")
        # PARTIAL: sum x 4, partial_sum_double x 3, count(x) x 3, count(*),
        # all fused; what is unfused is the FINAL node's eight merges
        # over 4 x 16 gathered rows, under the kernel's gate
        assert (st.aggs_fused, st.aggs_unfused) == (11, 8)
        assert len(got) == len(expected) == 4
        for g, e in zip(got, expected):
            assert g[:2] == e[:2] and g[-1] == e[-1]
            assert g[2:-1] == pytest.approx(e[2:-1], rel=1e-6)
    assert len(fused) == 1 and fused[0][0] == 15
    shard_rows = fused[0][1]
    assert shard_rows >= 32_768
    assert [d for d, n in segment if n == shard_rows
            and jnp.issubdtype(d, jnp.integer)] == []
    assert any(n == shard_rows for _, n in segment)  # the `counts` row
