"""GROUP BY ROLLUP / CUBE / GROUPING SETS as ONE plan node over ONE source
(ISSUE 35): `plan/nodes.GroupingSets`, lowered by
`Executor._exec_groupingsets`, against the sqlite oracle at SF0.01.  sqlite
has no grouping sets: each oracle text is the UNION ALL of the sets'
aggregations, which is what the planner used to make of the query itself.
Every case runs in dynamic and in compiled mode, and on a four-device mesh
(ISSUE 36) with the sets' states gathered and repartitioned.
"""

import pytest

import presto_tpu
from presto_tpu.exec.executor import plan_statement
from presto_tpu.plan import nodes as P
from presto_tpu.sql.parser import parse
from tests.sqlite_oracle import assert_same_results, to_sqlite

#: name -> (engine text, oracle text, rows compared in order, grouping sets)
CASES = {
    "rollup_two_keys": (
        "SELECT o_orderstatus, o_orderpriority, count(*), sum(o_totalprice) "
        "FROM orders GROUP BY ROLLUP (o_orderstatus, o_orderpriority)",
        "SELECT o_orderstatus, o_orderpriority, count(*), sum(o_totalprice) "
        "FROM orders GROUP BY o_orderstatus, o_orderpriority "
        "UNION ALL SELECT o_orderstatus, NULL, count(*), sum(o_totalprice) "
        "FROM orders GROUP BY o_orderstatus "
        "UNION ALL SELECT NULL, NULL, count(*), sum(o_totalprice) FROM orders",
        False, 3),
    "rollup_three_keys": (
        "SELECT l_returnflag, l_linestatus, l_shipmode, sum(l_quantity), "
        "avg(l_discount) FROM lineitem "
        "GROUP BY ROLLUP (l_returnflag, l_linestatus, l_shipmode)",
        "SELECT l_returnflag, l_linestatus, l_shipmode, sum(l_quantity), "
        "avg(l_discount) FROM lineitem GROUP BY 1, 2, 3 "
        "UNION ALL SELECT l_returnflag, l_linestatus, NULL, sum(l_quantity), "
        "avg(l_discount) FROM lineitem GROUP BY 1, 2 "
        "UNION ALL SELECT l_returnflag, NULL, NULL, sum(l_quantity), "
        "avg(l_discount) FROM lineitem GROUP BY 1 "
        "UNION ALL SELECT NULL, NULL, NULL, sum(l_quantity), avg(l_discount) "
        "FROM lineitem",
        False, 4),
    "cube": (
        "SELECT o_orderstatus, o_orderpriority, count(*) FROM orders "
        "GROUP BY CUBE (o_orderstatus, o_orderpriority)",
        "SELECT o_orderstatus, o_orderpriority, count(*) FROM orders "
        "GROUP BY 1, 2 "
        "UNION ALL SELECT o_orderstatus, NULL, count(*) FROM orders GROUP BY 1 "
        "UNION ALL SELECT NULL, o_orderpriority, count(*) FROM orders "
        "GROUP BY 2 "
        "UNION ALL SELECT NULL, NULL, count(*) FROM orders",
        False, 4),
    "finest_set_not_listed": (
        "SELECT o_orderstatus, o_orderpriority, min(o_totalprice), "
        "grouping(o_orderstatus, o_orderpriority) FROM orders "
        "GROUP BY GROUPING SETS ((o_orderstatus), (o_orderpriority))",
        "SELECT o_orderstatus, NULL, min(o_totalprice), 1 FROM orders "
        "GROUP BY 1 "
        "UNION ALL SELECT NULL, o_orderpriority, min(o_totalprice), 2 "
        "FROM orders GROUP BY 2",
        False, 2),
    "empty_set_over_empty_input": (
        "SELECT o_orderstatus, count(*), sum(o_totalprice) FROM orders "
        "WHERE o_orderkey < 0 GROUP BY ROLLUP (o_orderstatus)",
        "SELECT NULL, count(*), sum(o_totalprice) FROM orders "
        "WHERE o_orderkey < 0",
        False, 2),
    "grouping_in_case_and_order_by": (
        "SELECT CASE WHEN grouping(o_orderstatus) = 1 THEN 'all' "
        "ELSE o_orderstatus END AS s, o_orderpriority, "
        "grouping(o_orderstatus, o_orderpriority) AS g, count(*) FROM orders "
        "GROUP BY ROLLUP (o_orderstatus, o_orderpriority) "
        "ORDER BY grouping(o_orderstatus) + grouping(o_orderpriority) DESC, "
        "1, 2",
        "SELECT s, p, g, c FROM ("
        "SELECT o_orderstatus AS s, o_orderpriority AS p, 0 AS g, "
        "count(*) AS c, 0 AS lvl FROM orders GROUP BY 1, 2 "
        "UNION ALL SELECT o_orderstatus, NULL, 1, count(*), 1 FROM orders "
        "GROUP BY 1 "
        "UNION ALL SELECT 'all', NULL, 3, count(*), 2 FROM orders) "
        "ORDER BY lvl DESC, s, p",
        True, 3),
    "having_on_an_aggregate": (
        "SELECT o_orderstatus, o_orderpriority, count(*) FROM orders "
        "GROUP BY ROLLUP (o_orderstatus, o_orderpriority) "
        "HAVING count(*) > 2000",
        "SELECT * FROM ("
        "SELECT o_orderstatus, o_orderpriority, count(*) AS c FROM orders "
        "GROUP BY 1, 2 "
        "UNION ALL SELECT o_orderstatus, NULL, count(*) FROM orders GROUP BY 1 "
        "UNION ALL SELECT NULL, NULL, count(*) FROM orders) WHERE c > 2000",
        False, 3),
    "count_distinct_under_rollup": (
        "SELECT o_orderstatus, count(DISTINCT o_custkey), count(*) "
        "FROM orders GROUP BY ROLLUP (o_orderstatus)",
        "SELECT o_orderstatus, count(DISTINCT o_custkey), count(*) "
        "FROM orders GROUP BY 1 "
        "UNION ALL SELECT NULL, count(DISTINCT o_custkey), count(*) "
        "FROM orders",
        False, 2),
    # the case one window a sub-query got wrong: no PARTITION BY, so the
    # subtotal and total rows rank among the detail rows
    "rank_across_all_levels": (
        "SELECT o_orderstatus, o_orderpriority, sum(o_totalprice), "
        "rank() OVER (ORDER BY sum(o_totalprice)) FROM orders "
        "GROUP BY ROLLUP (o_orderstatus, o_orderpriority)",
        "SELECT s, p, t, rank() OVER (ORDER BY t) FROM ("
        "SELECT o_orderstatus AS s, o_orderpriority AS p, "
        "sum(o_totalprice) AS t FROM orders GROUP BY 1, 2 "
        "UNION ALL SELECT o_orderstatus, NULL, sum(o_totalprice) FROM orders "
        "GROUP BY 1 "
        "UNION ALL SELECT NULL, NULL, sum(o_totalprice) FROM orders)",
        False, 3),
    "sub_query_of_a_join": (
        "SELECT n_name, t.seg, t.c FROM nation JOIN ("
        "SELECT c_nationkey AS k, c_mktsegment AS seg, count(*) AS c "
        "FROM customer GROUP BY ROLLUP (c_nationkey, c_mktsegment)) t "
        "ON n_nationkey = t.k WHERE n_regionkey = 1",
        "SELECT n_name, t.seg, t.c FROM nation JOIN ("
        "SELECT c_nationkey AS k, c_mktsegment AS seg, count(*) AS c "
        "FROM customer GROUP BY 1, 2 "
        "UNION ALL SELECT c_nationkey, NULL, count(*) FROM customer "
        "GROUP BY 1) t ON n_nationkey = t.k WHERE n_regionkey = 1",
        False, 3),
}


def walk(node):
    yield node
    for s in node.sources:
        yield from walk(s)


@pytest.fixture(scope="module", params=["dynamic", "compiled"])
def session(request, tpch_catalog_tiny):
    return presto_tpu.connect(tpch_catalog_tiny, execution_mode=request.param)


@pytest.mark.parametrize("case", sorted(CASES))
def test_grouping_sets_equal_the_union_of_their_sets(session, tpch_sqlite_tiny,
                                                     case):
    text, oracle, ordered, n_sets = CASES[case]
    got = session.sql(text)
    want = tpch_sqlite_tiny.execute(to_sqlite(oracle)).fetchall()
    assert_same_results(got.rows, want, ordered=ordered, rel_tol=1e-6)
    mode = session.properties["execution_mode"]
    assert got.stats.execution_mode == mode, got.stats.fallback_reason
    # one node, one source: the FROM clause was planned and lowered once
    plan = plan_statement(session, parse(text))
    nodes = [n for n in walk(plan.root) if isinstance(n, P.GroupingSets)]
    assert len(nodes) == 1 and len(nodes[0].sets) == n_sets
    assert not any(isinstance(n, P.Union) for n in walk(plan.root))
    scanned = [n.table for n in walk(plan.root) if isinstance(n, P.TableScan)]
    assert len(scanned) == len(set(scanned))        # no table twice
    assert got.stats.grouping_set_branches == n_sets
    assert got.stats.grouping_set_sources == 1


def test_grouping_takes_keys_only(tpch_catalog_tiny):
    from presto_tpu.plan.planner import SemanticError

    s = presto_tpu.connect(tpch_catalog_tiny)
    with pytest.raises(SemanticError, match="grouping"):
        s.sql("SELECT grouping(o_custkey), count(*) FROM orders "
              "GROUP BY ROLLUP (o_orderstatus)")


def queries_total(mode):
    from presto_tpu.observe import metrics as M

    M.ensure_query_metrics()
    return M.REGISTRY.get("presto_tpu_queries_total").value(
        state="FINISHED", mode=mode)


#: partial_aggregation_max_groups -> how the sets' states move: gathered
#: where all the sets' capacities together stay under it (the default, at
#: this scale), repartitioned by (keys, group id) where they do not
STATES_MOVE = {"gather": 8192, "repartition": 1}


@pytest.fixture(scope="module", params=sorted(STATES_MOVE))
def mesh_session(request, tpch_catalog_tiny):
    s = presto_tpu.connect(tpch_catalog_tiny)
    s.set("distributed", True)
    s.set("mesh_devices", 4)
    s.set("partial_aggregation_max_groups", STATES_MOVE[request.param])
    s.states_move = request.param
    return s


@pytest.mark.parametrize("case", sorted(CASES))
def test_grouping_sets_on_a_mesh(mesh_session, tpch_sqlite_tiny, case):
    """`plan/distribute._visit_groupingsets` (ISSUE 36): PARTIAL sets a
    shard over ONE lowering of the source, the states moved, one FINAL
    merge over (keys, group id).  An aggregate without a partial state (a
    DISTINCT one) keeps the node off the mesh, and the reason says so."""
    from presto_tpu.plan.distribute import distribute

    text, oracle, ordered, n_sets = CASES[case]
    got = mesh_session.sql(text)
    want = tpch_sqlite_tiny.execute(to_sqlite(oracle)).fetchall()
    assert_same_results(got.rows, want, ordered=ordered, rel_tol=1e-6)
    assert got.stats.grouping_set_branches == n_sets
    assert got.stats.grouping_set_sources == 1
    if "DISTINCT" in text:
        assert got.stats.execution_mode == "compiled"
        assert ("distributed: Undistributable: GroupingSets with aggregates "
                "that have no partial state ['DISTINCT count']") \
            in got.stats.fallback_reason
        assert got.stats.grouping_set_state_rows == 0
        assert got.stats.grouping_set_merge_rows == 0
        return
    assert got.stats.execution_mode == "distributed", got.stats.fallback_reason
    assert not got.stats.fallback_reason
    plan = distribute(plan_statement(mesh_session, parse(text)), mesh_session, 4)
    steps = [(n.step, type(n.source).__name__,
              getattr(n.source, "kind", None))
             for n in walk(plan.root) if isinstance(n, P.GroupingSets)]
    assert steps == [("FINAL", "Exchange", mesh_session.states_move),
                     ("PARTIAL", steps[1][1], None)]
    partial = [n for n in walk(plan.root)
               if isinstance(n, P.GroupingSets) and n.step == "PARTIAL"][0]
    # the capacity of the states a chip sends: the sets' capacities, added
    assert got.stats.grouping_set_state_rows == sum(
        h["capacity_hint"] for h in partial.hints)
    # the merge runs over what a chip received (this scale is under the
    # compaction's floor): every chip's states gathered, or four buckets
    # of twice a chip's share (the repartition's slack)
    sent = got.stats.grouping_set_state_rows
    assert got.stats.grouping_set_merge_rows == (
        4 * sent if mesh_session.states_move == "gather"
        else 4 * -(-2 * sent // 4))
    assert got.stats.exchange_bytes_collective > 0


def test_mesh_session_answers_a_rollup_on_the_mesh(tpch_catalog_tiny,
                                                   tpch_sqlite_tiny):
    """The mesh planner places the node, so a `distributed=true` session
    answers a ROLLUP on the mesh, cold and from its program's memo, and
    the counter behind `distributed_share` says so."""
    s = presto_tpu.connect(tpch_catalog_tiny)
    s.set("distributed", True)
    s.set("mesh_devices", 4)
    text, oracle, ordered, n_sets = CASES["rollup_two_keys"]
    on_mesh, compiled = queries_total("distributed"), queries_total("compiled")
    counted = []
    for _ in range(2):      # traced, then replayed by the cached program
        got = s.sql(text)
        want = tpch_sqlite_tiny.execute(to_sqlite(oracle)).fetchall()
        assert_same_results(got.rows, want, ordered=ordered, rel_tol=1e-6)
        assert got.stats.execution_mode == "distributed"
        assert not got.stats.fallback_reason
        counted.append((got.stats.grouping_set_sources,
                        got.stats.grouping_set_branches,
                        got.stats.grouping_set_state_rows,
                        got.stats.exchange_bytes_collective))
    assert counted[0] == counted[1] and counted[0][:2] == (1, n_sets)
    assert got.stats.compiles == 0
    assert queries_total("distributed") == on_mesh + 2
    assert queries_total("compiled") == compiled
