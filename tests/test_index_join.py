"""P10 index joins: dense and strided-block (invertible sparse) build
keys lower the probe to one gather.  Over a whole dense build the match
test is the packed gather of the build row, under the layout guard.

Reference: sql/planner/optimizations/IndexJoinOptimizer.java +
operator/index/IndexLoader; the TPU-native "index" is the closed-form
layout of the generator key — dense surrogates (customer, part) and
dbgen's sparse orderkey (8 keys per 32-key block, catalog.key_layout).
"""

import numpy as np
import pytest

import presto_tpu
from presto_tpu import types as T
from presto_tpu.catalog import Catalog, MemoryTable, tpch_catalog
from presto_tpu.exec.executor import plan_statement
from presto_tpu.plan import nodes as P
from presto_tpu.plan.stats import ColStats
from presto_tpu.sql.parser import parse

from tests.sqlite_oracle import assert_same_results, to_sqlite
from tests.tpch_queries import QUERIES


@pytest.fixture(scope="module")
def s():
    return presto_tpu.connect(
        tpch_catalog(0.01, "/tmp/presto_tpu_cache"))


def test_q3_index_annotations(s):
    # both joins carry the INDEX annotation (customer dense, orders
    # strided); the executor takes the strided gather only when the
    # probe is not much wider than the build (Q3's 4x probe runs the
    # compacted sort join — measured faster on chip)
    txt = s.sql("EXPLAIN " + QUERIES[3]).rows[0][0]
    assert txt.count("INDEX") == 2


def test_strided_orderkey_join_exact(s):
    # join through the sparse orderkey: totals must match the
    # two-sided aggregation (oracle-free invariant)
    r = s.sql("SELECT count(*), sum(o_totalprice) FROM lineitem, orders "
              "WHERE l_orderkey = o_orderkey").rows
    n_li = s.sql("SELECT count(*) FROM lineitem").rows[0][0]
    assert r[0][0] == n_li  # every lineitem has its order
    per_order = s.sql(
        "SELECT sum(o_totalprice * cnt) FROM orders, "
        "(SELECT l_orderkey AS k, count(*) AS cnt FROM lineitem "
        "GROUP BY l_orderkey) g WHERE o_orderkey = g.k").rows[0][0]
    assert r[0][1] == pytest.approx(per_order, rel=1e-9)


def test_probing_missing_keys_between_blocks(s):
    # keys in the 24-key gap of each 32-key block must MISS, not
    # alias onto a neighbor row (the in_slot check)
    r = s.sql("SELECT count(*) FROM (VALUES (9), (10), (31), (33)) "
              "AS p(k) LEFT JOIN orders ON k = o_orderkey "
              "WHERE o_orderkey IS NOT NULL").rows
    # dbgen block 0 holds keys 1..8; 9/10/31 are gaps, 33 exists
    assert r == [(1,)]


def test_left_join_null_extension_through_index(s):
    rows = s.sql("SELECT k, o_orderkey FROM (VALUES (1), (9)) AS p(k) "
                 "LEFT JOIN orders ON k = o_orderkey ORDER BY k").rows
    assert rows == [(1, 1), (9, None)]


# ---------------------------------------------------------------------------
# the match test of an index join over a whole build: the packed gather of
# the build row, below the star rule's sizes too (exec/gather.small_source)
# ---------------------------------------------------------------------------

#: probe keys around customer's 1..1500 at SF0.01: below the build's
#: minimum, its ends, past its end, repeated, NULL, and rows that
#: `c_acctbal > 0` masks out of the build (16, 19, 30)
PROBE = [-3, 0, 1, 2, 16, 19, 30, 750, 1499, 1500, 1501, 9999, None, 2, 1500, 16]

PACKED = {
    "inner": "SELECT k, c_custkey, c_acctbal FROM probe_keys "
             "JOIN customer ON k = c_custkey",
    "left_masked": "SELECT k, c_custkey, c_name FROM probe_keys LEFT JOIN "
                   "(SELECT c_custkey, c_name FROM customer WHERE c_acctbal > 0) c "
                   "ON k = c_custkey",
    "semi": "SELECT k FROM probe_keys WHERE k IN "
            "(SELECT c_custkey FROM customer WHERE c_acctbal > 0)",
    "anti": "SELECT k FROM probe_keys p WHERE NOT EXISTS (SELECT 1 FROM "
            "customer c WHERE c.c_custkey = p.k AND c.c_acctbal > 0)",
    "mark": "SELECT k FROM probe_keys WHERE k NOT IN "
            "(SELECT c_custkey FROM customer WHERE c_acctbal > 0)",
    "orders_inner": "SELECT o_orderkey, c_name, c_acctbal FROM orders, customer "
                    "WHERE o_custkey = c_custkey AND o_totalprice > 100000",
    "orders_left_masked": "SELECT o_orderkey, c_custkey, c_mktsegment FROM orders "
                          "LEFT JOIN (SELECT c_custkey, c_mktsegment FROM customer "
                          "WHERE c_mktsegment = 'BUILDING') c ON o_custkey = c_custkey",
}


def plan_joins(session, sql):
    def walk(node):
        yield node
        for s in node.sources:
            yield from walk(s)

    plan = plan_statement(session, parse(sql))
    roots = [plan.root] + list(plan.subplans.values())
    return [n for r in roots for n in walk(r) if isinstance(n, P.Join)]


@pytest.fixture(scope="module")
def probed(tpch_sqlite_tiny):
    cat = tpch_catalog(0.01, "/tmp/presto_tpu_cache")
    keys = np.ma.masked_array([0 if k is None else k for k in PROBE],
                              mask=[k is None for k in PROBE], dtype=np.int64)
    cat.register_memory("probe_keys", {"k": T.BIGINT}, {"k": keys})
    tpch_sqlite_tiny.execute("DROP TABLE IF EXISTS probe_keys")
    tpch_sqlite_tiny.execute("CREATE TABLE probe_keys (k INTEGER)")
    tpch_sqlite_tiny.executemany("INSERT INTO probe_keys VALUES (?)",
                                 [(k,) for k in PROBE])
    return cat, tpch_sqlite_tiny


@pytest.mark.parametrize("mode", ["compiled", "dynamic"])
@pytest.mark.parametrize("case", sorted(PACKED))
def test_index_join_takes_its_match_from_the_packed_gather(probed, case, mode):
    """Every join here is an index join over the whole customer table, far
    below the star rule's 2^23 probe rows: each takes its match from the
    build row's packed gather, none gathers the build key, and the answer
    is sqlite's."""
    cat, db = probed
    sql = PACKED[case]
    s = presto_tpu.connect(cat, execution_mode=mode)
    index = [j for j in plan_joins(s, sql) if j.index_lookup is not None]
    assert index and all(j.index_lookup["block_keys"] == 1 for j in index)
    want = db.execute(to_sqlite(sql)).fetchall()
    for _ in range(2):              # traced, then replayed from the cache
        got = s.sql(sql)
        assert got.stats.execution_mode == mode and not got.stats.fallback_reason
        assert_same_results(got.rows, want)
        assert (got.stats.index_joins_packed, got.stats.index_joins_keyed) \
            == (len(index), 0)


def test_a_strided_build_still_compares_its_keys(s):
    """orders' sparse key is laid out in blocks: its base comes from the
    data, so the join gathers the build key and compares it."""
    sql = ("SELECT k, o_orderkey FROM (VALUES (1), (9), (33)) AS p(k) "
           "LEFT JOIN orders ON k = o_orderkey")
    got = s.sql(sql)
    assert sorted(got.rows) == [(1, 1), (9, None), (33, 33)]
    assert (got.stats.index_joins_packed, got.stats.index_joins_keyed) == (0, 1)


class DenseDim(MemoryTable):
    """A memory table that declares `d_key` unique: its statistics then
    give the planner a dense-key hint whatever its rows' order."""

    def __init__(self, name, schema, data, stats=None):
        super().__init__(name, schema, data)
        self._stats = stats

    def unique_keys(self):
        return [("d_key",)]

    def column_stats(self, column):
        if column == "d_key" and self._stats is not None:
            return self._stats
        return super().column_stats(column)


def broken_layout(kind):
    """(catalog, d_key, f_key) of a dimension whose key breaks the dense
    layout its hint claims: rows out of order, or a gap under stale
    statistics; "dense" keeps it."""
    n = 500
    rng = np.random.default_rng(39)
    if kind == "dense":
        d_key, stats = np.arange(1, n + 1), None
    elif kind == "out_of_order":
        d_key, stats = rng.permutation(np.arange(1, n + 1)), None
    else:
        d_key = np.delete(np.arange(1, n + 2), 200)
        stats = ColStats(min=1.0, max=float(n), ndv=n)
    cat = Catalog()
    cat.register(DenseDim("dim", {"d_key": T.BIGINT, "d_val": T.BIGINT},
                          {"d_key": d_key, "d_val": d_key * 7 + 1}, stats))
    f_key = rng.integers(-5, n + 8, 4000)
    cat.register_memory("facts", {"f_id": T.BIGINT, "f_key": T.BIGINT},
                        {"f_id": np.arange(4000), "f_key": f_key})
    return cat, d_key, f_key


@pytest.mark.parametrize("mode", ["compiled", "dynamic"])
@pytest.mark.parametrize("kind", ["dense", "out_of_order", "gap"])
def test_a_broken_layout_never_takes_the_index_join(kind, mode):
    """The dense-key hint over a build that breaks it: compiled, the
    layout guard trips and the query re-runs without the program;
    dynamic, the host check sends it to the sort join.  Either way the
    answer is exact, and only the build that keeps its layout is
    answered by the index join alone."""
    cat, d_key, f_key = broken_layout(kind)
    s = presto_tpu.connect(cat, execution_mode=mode)
    sql = ("SELECT f_id, d_val FROM facts LEFT JOIN dim ON f_key = d_key")
    (join,) = plan_joins(s, sql)
    assert join.index_lookup == {"min": 1, "rows": 500, "block_keys": 1,
                                 "block_rows": 1}
    got = s.sql(sql)
    val = dict(zip(d_key.tolist(), (d_key * 7 + 1).tolist()))
    assert sorted(got.rows) == [(i, val.get(k)) for i, k in enumerate(f_key.tolist())]
    st = got.stats
    assert st.execution_mode == mode and st.index_joins_keyed == 0
    if kind == "dense":
        assert (st.index_joins_packed, st.sorts_taken) == (1, 0)
    else:       # the sort join answered; compiled, after a traced index join
        assert st.sorts_taken >= 2
        assert st.index_joins_packed == (mode == "compiled")
