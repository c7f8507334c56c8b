"""The deployment `tpcds_store_sf100_mesh4` at SF0.01 on four CPU devices
(ISSUE 36): TPC-DS's store channel on a mesh, `store_sales` born sharded.

(a) a born-sharded `store_sales` (2 and 4 shards) equals the host
    generator's rows column by column, range by range, dead rows under
    `sel`; the ranges are cut on ticket boundaries, a return lies with its
    sale, and TPC-H's grid and TPC-DS's feed one `shard_generator`;
(b) q27 and q36 (the benchmark's own texts, the configuration's catalog
    factory, session properties and server) through the served path equal
    `benchmarks/reference_tpcds.py` by its own `rows_equal`, in mode
    `distributed`, and no host copy of the fact table exists afterwards;
(c) the counters a mesh program replays, the planner's choices at sf100
    (dimensions past the row threshold broadcast by bytes moved, star
    lookups marked, the sets' states gathered or repartitioned), and what
    a star lookup changes in a shard's program;
(d) ISSUE 37: the FINAL merge of repartitioned states runs over the
    received buffer compacted to the planner's bound on a chip's live
    states, under the compaction's guard.
"""

import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from presto_tpu.catalog import (TpcdsShardedTable, TpchTable, tpcds_catalog,
                                tpcds_mesh_catalog, tpch_catalog)
from presto_tpu.connectors import tpcds as DS
from presto_tpu.exec.executor import plan_statement
from presto_tpu.parallel import dist_executor as DX
from presto_tpu.parallel.mesh import make_mesh
from presto_tpu.plan import nodes as P
from presto_tpu.plan.distribute import distribute
from presto_tpu.sql.parser import parse

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SF = 0.01

with open(os.path.join(BENCH, "configs", "tpcds_store_sf100_mesh4.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "workloads", "ds100_mesh4_rollup.json")) as f:
    CELL = json.load(f)
REL = CONFIG["guarantees"]["float_rel"]
CLASSES = {c["name"]: c for c in CELL["classes"]}
FACT_COLUMNS = sorted({col for c in CELL["classes"]
                       for col in c["columns_read"]["store_sales"]})


def text_of(cls):
    with open(os.path.join(BENCH, "queries", CLASSES[cls]["query"] + ".sql")) as f:
        return f.read().strip()


def walk(node):
    yield node
    for s in node.sources:
        yield from walk(s)


# ---------------------------------------------------------------------------
# (a) store_sales born sharded == the host generator, range by range
# ---------------------------------------------------------------------------


def generated(table, cols, ndev, f32=True):
    """`shard_generator`'s program, run: -> ({column: Column}, sel, grid)."""
    mesh = make_mesh(ndev)
    spec = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(DX.AXIS))
    fn, args = DX.shard_generator(table, cols, mesh, ndev, f32)
    out, sel = jax.jit(fn)(*(jax.device_put(a, spec) for a in args))
    return out, np.asarray(sel), table.shard_grid(ndev)


@pytest.fixture(scope="module")
def born():
    t = TpcdsShardedTable("store_sales", SF)
    return {ndev: generated(t, FACT_COLUMNS, ndev) for ndev in (2, 4)}


@pytest.mark.parametrize("ndev", [2, 4])
@pytest.mark.parametrize("column", FACT_COLUMNS)
def test_fact_column_born_sharded_equals_host(born, column, ndev):
    cols, sel, grid = born[ndev]
    host = DS.generate("store_sales", SF)[column]
    typ = DS.SCHEMAS["store_sales"][column]
    if typ.name == "DOUBLE":
        host = host.astype(np.float32)      # the cell's lane: float32_compute
    edges, cap = grid.row_edges("store_sales"), grid.capacity("store_sales")
    got = np.asarray(cols[column].data)
    assert cols[column].valid is None and cols[column].type == typ
    assert got.dtype == host.dtype and got.shape == sel.shape == (ndev * cap,)
    assert edges[0] == 0 and edges[-1] == len(host)
    for i, (a, b) in enumerate(zip(edges[:-1], edges[1:])):
        mine = slice(i * cap, i * cap + b - a)
        assert (got[mine] == host[a:b]).all()           # bit for bit
        assert sel[mine].all() and not sel[mine.stop:(i + 1) * cap].any()


@pytest.mark.parametrize("ndev", [2, 4, 8])
def test_shards_are_cut_on_ticket_boundaries(ndev):
    sales = TpcdsShardedTable("store_sales", SF)
    returns = TpcdsShardedTable("store_returns", SF)
    grid = sales.shard_grid(ndev)
    edges = grid.row_edges("store_sales")
    assert len(edges) == ndev + 1 and sorted(edges) == list(edges)
    assert edges[-1] == sales.row_count()
    assert all(e % DS.ITEMS_PER_TICKET == 0 for e in edges[:-1])
    # a return lies on the shard of its sale: return j's sale is row 10 j
    r = returns.shard_grid(ndev).row_edges("store_returns")
    assert r[0] == 0 and r[-1] == returns.row_count()
    for i in range(ndev):
        parents = np.arange(r[i], r[i + 1]) * DS.RETURN_EVERY
        assert ((edges[i] <= parents) & (parents < edges[i + 1])).all()


def test_returns_are_born_beside_their_sales():
    cols, sel, grid = generated(TpcdsShardedTable("store_returns", SF),
                                ["sr_ticket_number", "sr_item_sk"], 4)
    host = DS.generate("store_returns", SF)
    edges, cap = grid.row_edges("store_returns"), grid.capacity("store_returns")
    sales = grid.row_edges("store_sales")
    for i, (a, b) in enumerate(zip(edges[:-1], edges[1:])):
        mine = slice(i * cap, i * cap + b - a)
        for c in cols:
            assert (np.asarray(cols[c].data)[mine] == host[c][a:b]).all()
        tickets = host["sr_ticket_number"][a:b]     # ticket = row // 3 + 1
        assert ((tickets - 1) * DS.ITEMS_PER_TICKET >= sales[i]).all()
        assert ((tickets - 1) * DS.ITEMS_PER_TICKET < sales[i + 1]).all()
        assert int(sel[i * cap:(i + 1) * cap].sum()) == b - a


@pytest.mark.parametrize("table", [
    TpchTable("lineitem", SF), TpchTable("orders", SF),
    TpchTable("customer", SF), TpcdsShardedTable("store_sales", SF),
    TpcdsShardedTable("catalog_returns", SF)], ids=lambda t: t.name)
def test_a_grid_supplies_its_shards_arguments(table):
    """One `shard_generator` for both families: a shard is a chunk of
    the table's grid, and the program's arguments are every chunk's
    `chunk_args`, an array an argument."""
    grid = table.shard_grid(4)
    fn, args = DX.shard_generator(table, [next(iter(table.schema))],
                                  make_mesh(4), 4, True)
    assert len(args) == len(grid.chunk_args(0))
    assert all(a.shape == (4,) for a in args)
    for i in range(4):
        assert [int(a[i]) for a in args] == [int(x) for x in grid.chunk_args(i)]
        assert [a.dtype for a in args] == [np.asarray(x).dtype
                                           for x in grid.chunk_args(i)]


def test_only_the_mesh_catalog_bears_facts_sharded():
    from benchmarks.run import entry_point

    cat = entry_point(CONFIG["catalog_factory"])(SF, cache_dir=None)
    assert CONFIG["catalog_factory"].endswith(":tpcds_mesh_catalog")
    for table, rows in CONFIG["rows"].items():
        assert cat.get(table).sf == SF
        assert DS.row_count(table, CONFIG["scale_factor"]) == rows
    plain = tpcds_catalog(SF, cache_dir=None)
    for name in DS.SCHEMAS:
        fact = name in ("store_sales", "store_returns", "catalog_sales",
                        "catalog_returns")
        assert hasattr(cat.get(name), "shard_grid") is fact
        assert not hasattr(plain.get(name), "shard_grid")
    assert CONFIG["reduced"] == [] and CONFIG["chips"] == 4


# ---------------------------------------------------------------------------
# (b) the served path on the mesh == the plain reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_tpcds", os.path.join(BENCH, CELL["reference"]))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served():
    """{class: (rows, QueryStats, the cold request's QueryStats)} of a
    second, warm request through POST /v1/statement, and the session that
    answered."""
    from benchmarks import run

    session, srv = run.build_server(CONFIG, SF)
    try:
        out = {}
        for cls in CLASSES:
            run.send(srv.uri, text_of(cls))
            cold = run.last_stats(session, text_of(cls))
            rows = run.send(srv.uri, text_of(cls))
            out[cls] = (rows, run.last_stats(session, text_of(cls)), cold)
    finally:
        srv.stop()
    return out, session


@pytest.mark.parametrize("cls", sorted(CLASSES))
def test_served_mesh_equals_plain_reference(reference, served, cls):
    want = reference.streamed(SF, [CLASSES[cls]["check"]])[CLASSES[cls]["check"]]
    rows, stats, _ = served[0][cls]
    assert rows and len(rows) == len(want)
    assert stats.execution_mode == "distributed" and not stats.fallback_reason
    assert stats.compiles == 0      # the second request built nothing
    assert reference.rows_equal(rows, want, REL)


@pytest.mark.parametrize("cls", sorted(CLASSES))
def test_a_mesh_program_replays_its_counters(served, cls):
    _, stats, cold = served[0][cls]     # traced, then replayed
    assert cold.compiles > 0 and stats.compiles == 0
    for st in (cold, stats):
        assert (st.grouping_set_sources, st.grouping_set_branches) == (1, 3)
    # a chip sends at least a slot a set, and the final top-N's rows
    assert stats.grouping_set_state_rows == cold.grouping_set_state_rows >= 3
    assert stats.exchange_bytes_collective == cold.exchange_bytes_collective > 0
    assert (stats.aggs_fused, stats.aggs_unfused) == (cold.aggs_fused,
                                                      cold.aggs_unfused)
    # at this scale both classes gather their states: the merge runs over
    # every chip's, uncompacted
    assert stats.grouping_set_merge_rows == cold.grouping_set_merge_rows \
        == 4 * stats.grouping_set_state_rows
    plan = distribute(plan_statement(served[1], parse(text_of(cls))),
                      served[1], 4)
    # the star lookups' filters were declined, the others' traced
    joins = [n for n in walk(plan.root) if isinstance(n, P.Join)]
    star = [n for n in joins if getattr(n, "star_lookup", False)]
    assert 0 < len(star) < len(joins)
    assert stats.df_filters_declined == sum(
        len(getattr(n, "rf_produce", None) or ()) for n in star)
    # a star lookup is an index join over the whole broadcast dimension:
    # its match comes from the packed gather of the build row, traced once
    # and replayed by the warm request
    for st in (cold, stats):
        assert (st.index_joins_packed, st.index_joins_keyed) == (len(star), 0)


def test_fact_table_has_no_host_copy_on_the_mesh(served):
    cat = served[1].catalog
    fact = cat.get("store_sales")
    assert not hasattr(fact, "_data")               # never read on the host
    assert hasattr(cat.get("item"), "_data")        # a dimension was
    held = set(fact._dist_cols_4) | set(fact._dist_cols_4_f32)
    assert held == set(FACT_COLUMNS) | {"__sel__"}
    cap = fact.shard_grid(4).capacity("store_sales")
    assert all(c.data.shape == (4 * cap,) and len(c.data.sharding.device_set) == 4
               for c in fact._dist_cols_4_f32.values())


# ---------------------------------------------------------------------------
# (c) the planner's choices at sf100 (statistics alone: nothing generated)
# ---------------------------------------------------------------------------


def planned(catalog, text, ndev=4, **props):
    import presto_tpu

    s = presto_tpu.connect(catalog)
    for k, v in {**CONFIG["session_properties"], **props}.items():
        s.set(k, v)
    return distribute(plan_statement(s, parse(text)), s, ndev)


@pytest.mark.parametrize("cls,moved", [("q27", "repartition"), ("q36", "gather")])
def test_star_join_at_sf100_broadcasts_its_dimensions(cls, moved):
    """item (1.8 M rows) and customer_demographics (1.92 M) are past
    `broadcast_join_threshold_rows`; by rows the planner would repartition
    the 288 M-row fact table to meet them."""
    plan = planned(tpcds_mesh_catalog(100, cache_dir=None), text_of(cls))
    joins = [n for n in walk(plan.root) if isinstance(n, P.Join)]
    assert len(joins) == len(CLASSES[cls]["columns_read"]) - 1
    for j in joins:
        assert isinstance(j.right, P.Exchange) and j.right.kind == "broadcast"
        assert not isinstance(j.left, P.Exchange)
        assert j.star_lookup and j.index_lookup["block_keys"] == 1
    kinds = [n.kind for n in walk(plan.root) if isinstance(n, P.Exchange)]
    assert kinds.count("repartition") == (moved == "repartition")
    sets = [n for n in walk(plan.root) if isinstance(n, P.GroupingSets)]
    assert [(n.step, getattr(n.source, "kind", None)) for n in sets] == [
        ("FINAL", moved), ("PARTIAL", None)]
    if moved == "repartition":      # hashed on what is unique in the output
        assert sets[0].source.keys == sets[0].group_keys + [sets[0].group_id]


def test_by_rows_the_fact_table_would_be_repartitioned():
    """The same plan without the dense lookup's hint: the rule by bytes
    asks for one (no sort of the build), and falls back to the rows."""
    import presto_tpu
    from presto_tpu.plan.distribute import Distributer

    s = presto_tpu.connect(tpcds_mesh_catalog(100, cache_dir=None))
    plan = plan_statement(s, parse(text_of("q27")))
    for n in walk(plan.root):
        if isinstance(n, P.Join):
            n.index_lookup = None
    root, _ = Distributer(s, 4).visit(plan.root.source)
    moved = [n.source.table for n in walk(root) if isinstance(n, P.Exchange)
             and n.kind == "repartition" and isinstance(n.source, P.TableScan)]
    assert "store_sales" in moved


def test_tpch_q3_on_the_mesh_keeps_its_joins():
    """At SF1 no join of Q3 is a star lookup (a probe shard is 2.5 x the
    customer table): its program keeps the sort join and the runtime
    filter it was compiled with."""
    with open(os.path.join(BENCH, "queries", "tpch_q3.sql")) as f:
        plan = planned(tpch_catalog(1.0, cache_dir=None), f.read().strip())
    joins = [n for n in walk(plan.root) if isinstance(n, P.Join)]
    assert len(joins) == 2
    assert not any(hasattr(n, "star_lookup") for n in joins)
    assert not DX.DistExecutor.allow_index_join


def test_star_lookup_takes_the_index_join_on_a_shard(served, monkeypatch):
    """A marked join's build is whole on every shard and in the table's
    order: the shard probes it with one gather, under the layout guard."""
    taken = []
    real = DX.DistExecutor._index_build_whole

    def spy(self, node, il, right):
        out = real(self, node, il, right)
        taken.append((getattr(node, "star_lookup", False), out,
                      right.capacity >= il["rows"]))
        return out

    monkeypatch.setattr(DX.DistExecutor, "_index_build_whole", spy)
    import presto_tpu

    s = presto_tpu.connect(served[1].catalog)
    for k, v in CONFIG["session_properties"].items():
        s.set(k, v)
    s.set("partial_aggregation_max_groups", 4096)   # another program: traced
    assert s.sql(text_of("q36")).stats.execution_mode == "distributed"
    assert taken and all(mark == whole and fits for mark, whole, fits in taken)
    assert any(whole for _, whole, _ in taken)


@pytest.mark.parametrize("cls,scope", [("q27", "x:repartition"),
                                       ("q36", "x:all_gather")])
def test_states_exchange_lies_under_the_nodes_scope(served, lowered_texts,
                                                    cls, scope):
    """Partials, exchange and merge of a mesh `GroupingSets` carry the
    node as their innermost plan-node scope: the exchange is lowered
    inside the FINAL step and not through `exec_node`, and a set's
    aggregation opens no `Aggregate` scope as it does on one chip."""
    import re

    import presto_tpu
    from presto_tpu.exec import compile_cache as CC

    CC.clear()      # nothing in the process-wide memo: built, spied on
    s = presto_tpu.connect(served[1].catalog)
    for k, v in CONFIG["session_properties"].items():
        s.set(k, v)
    # q27's sets hold 2,305 groups at this scale (~0.5 M at sf100) and
    # q36's 273: under this bound the one repartitions as at sf100
    s.set("partial_aggregation_max_groups", 1024)
    assert s.sql(text_of(cls)).stats.execution_mode == "distributed"
    CC.clear()
    # inside the shard_map an op's name is its scopes' path from the root
    names = set(re.findall(r'loc\("(Output/[^"]*)"', "\n".join(lowered_texts)))
    under = [n for n in names if "/GroupingSets/" in n]

    def innermost_node(op_name):
        parts = [p for p in op_name.split("/") if re.match(r"^[A-Z][A-Za-z0-9]*$", p)]
        return parts[-1]

    moved = [n for n in under if f"/{scope}/" in n
             and innermost_node(n) == "GroupingSets"]
    assert moved
    # the sets' partial aggregations and the merge: kernels of the node
    kernels = [n for n in under if innermost_node(n) == "GroupingSets"
               and re.search(r"/k:(segment|group_ids|fused_group_sums|sort)/", n)]
    assert kernels
    assert not any("/GroupingSets/Aggregate/" in n for n in names)
    # every other exchange (the broadcast builds, the top-N) keeps the
    # Exchange node's scope
    other = [n for n in names if re.search(r"/x:\w+/", n) and n not in moved]
    assert other and all(innermost_node(n) == "Exchange" for n in other)


# ---------------------------------------------------------------------------
# what sf100 a chip forced: a staged gather's source in column groups
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("presorted", [True, False])
def test_staged_gather_in_word_groups_equals_the_flat_gather(monkeypatch,
                                                             presorted):
    """72 M slots x 21 words are a 6 GB source matrix: past
    `gather.STAGED_SOURCE_BYTES` the staged route stacks and gathers the
    words in groups, and takes the same rows."""
    import jax.numpy as jnp

    from presto_tpu.exec import gather as G
    from presto_tpu.exec import kernels as K

    assert G.staged_word_groups(72_010_101, 21) == [
        slice(0, 7), slice(7, 14), slice(14, 21)]
    # the largest source of the cells before: one matrix, as it was
    assert G.staged_word_groups(28_804_040, 21) == [slice(0, 21)]
    monkeypatch.setenv("PRESTO_TPU_GATHER", "force")
    monkeypatch.setattr(G, "_STAGED_MIN_INDICES", 1)
    monkeypatch.setattr(G, "STAGED_SOURCE_BYTES", 4 * 1000 * 4)   # 4 words
    rng = np.random.default_rng(36)
    n, m = 1000, 300
    arrays = [jnp.asarray(rng.integers(-2**62, 2**62, n)),            # 2 words
              jnp.asarray(rng.integers(0, 2**31, n).astype(np.int32)),
              jnp.asarray(rng.random(n).astype(np.float32)),
              jnp.asarray(rng.random(n) < 0.5),
              jnp.asarray(rng.random(n)),                             # direct
              jnp.asarray(rng.integers(-2**62, 2**62, n))]
    assert len(G.staged_word_groups(n, 7)) == 4
    idx = rng.integers(0, n, m).astype(np.int32)
    idx = jnp.asarray(np.sort(idx) if presorted else idx)
    assert G.gather_route(n, m, 7, presorted) == "staged"
    got = K.take_rows(arrays, idx, presorted=presorted)
    for g, a in zip(got, arrays):
        assert g.dtype == a.dtype
        assert (np.asarray(g) == np.asarray(a)[np.asarray(idx)]).all()


# ---------------------------------------------------------------------------
# (d) the FINAL merge aggregates the states it received (ISSUE 37)
# ---------------------------------------------------------------------------


def test_merge_estimate_at_sf100_is_a_chips_live_states():
    """q27's sets (statistics alone, sf100): a shard's partial holds at
    most min(the set's groups, its rows) live states, the repartition
    spreads their sum over the four chips.  The compaction doubles the
    estimate and rounds it up: 2^19 slots, where a chip receives
    2 x (2^20 + 2^20 + 1) = 4,194,309.  q36's gathered states get none."""
    cat = tpcds_mesh_catalog(100, cache_dir=None)
    plan = planned(cat, text_of("q27"))
    final, partial = [n for n in walk(plan.root) if isinstance(n, P.GroupingSets)]
    caps = [h["capacity_hint"] for h in partial.hints]
    ests = [h["input_est_hint"] for h in partial.hints]
    assert caps == [33_554_432, 2_097_152, 1] and ests == [385_415] * 3
    # (min(2^25 x 4, 385,415) + min(2^21 x 4, 385,415) + min(1 x 4, 385,415)) / 4
    assert -(-(385_415 + 385_415 + 4) // 4) == 192_709
    assert final.merge_hints["input_est_hint"] == 192_709
    assert final.merge_hints["capacity_hint"] == sum(caps)
    assert 1 << int(np.ceil(np.log2(2 * 192_709))) == 1 << 19
    plan = planned(cat, text_of("q36"))
    final = next(n for n in walk(plan.root) if isinstance(n, P.GroupingSets))
    assert final.source.kind == "gather"
    assert "input_est_hint" not in final.merge_hints


def merge_over(received, est):
    """The FINAL merge of `GROUP BY ROLLUP (k)`'s states in a compiled
    (static) executor: -> (live rows sorted, slots merged, guards)."""
    import presto_tpu
    from presto_tpu import types as T
    from presto_tpu.exec.executor import Executor
    from presto_tpu.plan import ir
    from presto_tpu.plan.stats import ColStats

    types = {"k": T.BIGINT, "s": T.BIGINT, "c": T.BIGINT, "g": T.INTEGER}
    scan = P.TableScan("states", {n: n for n in types}, types)
    node = P.GroupingSets(scan, ["k"], [["k"], []], {
        "total": ir.AggCall("sum", (ir.Ref("s", T.BIGINT),), T.BIGINT),
        "n": ir.AggCall("merge_count", (ir.Ref("c", T.BIGINT),), T.BIGINT)},
        "g")
    node.step = "FINAL"
    node.merge_hints = {"capacity_hint": received.capacity, "key_stats": {
        "g": ColStats(0, 1, 2), "k": ColStats(0, 9_999, 10_000)}}
    if est is not None:
        node.merge_hints["input_est_hint"] = est
    ex = Executor(presto_tpu.connect(tpch_catalog(SF, cache_dir=None)),
                  static=True, scan_inputs={id(scan): received})
    out = ex.exec_node(node)
    cols = {n: (np.asarray(c.data), None if c.valid is None
                else np.asarray(c.valid)) for n, c in out.columns.items()}
    rows = sorted(
        tuple(None if v is not None and not v[i] else int(d[i])
              for d, v in (cols[n] for n in ("g", "k", "total", "n")))
        for i in np.flatnonzero(np.asarray(out.sel)))
    return rows, out.capacity, ex.sort_stats["grouping_set_merge_rows"], \
        [bool(g) for g in ex.guards]


@pytest.fixture(scope="module")
def received():
    """2^19 + 5 slots of states, 40,000 of them live (a chip's share of
    the repartition: few live slots in a large buffer); dead slots hold
    garbage."""
    import jax.numpy as jnp

    from presto_tpu import types as T
    from presto_tpu.batch import Batch, Column

    rng = np.random.default_rng(37)
    n, live = (1 << 19) + 5, 40_000
    sel = np.zeros(n, bool)
    sel[rng.choice(n, live, replace=False)] = True
    g = np.where(sel, rng.integers(0, 2, n), rng.integers(-9, 9, n))
    k_valid = ~sel | (g == 0)                   # the total's key is NULL
    cols = {"k": Column(jnp.asarray(rng.integers(0, 10_000, n)),
                        jnp.asarray(k_valid), T.BIGINT),
            "s": Column(jnp.asarray(rng.integers(-10**6, 10**6, n)), None, T.BIGINT),
            "c": Column(jnp.asarray(rng.integers(1, 6, n)), None, T.BIGINT),
            "g": Column(jnp.asarray(g.astype(np.int32)), None, T.INTEGER)}
    return Batch(cols, jnp.asarray(sel))


def test_merge_compacts_the_received_states_and_answers_the_same(received):
    """An estimate under the live count (30,000 of 40,000, as q27's
    192,709 of ~210 k) still bounds them once doubled: the merge runs
    over 2^16 slots instead of 2^19 + 5, no guard trips, and every merged
    row equals the uncompacted merge's."""
    whole, whole_cap, whole_merged, _ = merge_over(received, None)
    rows, cap, merged, guards = merge_over(received, 30_000)
    assert (whole_cap, whole_merged) == (received.capacity, received.capacity)
    assert (cap, merged) == (1 << 16, 1 << 16)
    assert guards and not any(guards)
    assert rows == whole
    sel, g = np.asarray(received.sel), np.asarray(received.columns["g"].data)
    c = np.asarray(received.columns["c"].data)
    k = np.asarray(received.columns["k"].data)
    assert len(rows) == len(set(k[sel & (g == 0)])) + 1
    assert rows[-1] == (1, None, int(np.asarray(received.columns["s"].data)[
        sel & (g == 1)].sum()), int(c[sel & (g == 1)].sum()))


def test_merge_estimate_too_low_trips_the_guard(reference, served, monkeypatch):
    """q27's states repartitioned (a bound under its 2,305 groups) and
    the compaction's floors lowered to this scale: with the planner's
    estimate the merge compacts and answers on the mesh; forced to 1, the
    guard trips, the query re-runs off the mesh and still answers right."""
    import presto_tpu
    from presto_tpu.exec import compile_cache as CC
    from presto_tpu.exec.executor import Executor
    from presto_tpu.plan.distribute import Distributer

    monkeypatch.setattr(Executor, "COMPACT_MIN_CAPACITY", 1)
    monkeypatch.setattr(Executor, "COMPACT_MIN_BOUND_BITS", 0)
    want = reference.streamed(SF, ["tpcds_q27"])["tpcds_q27"]

    def q27():
        CC.clear()      # a program traced under the floors lowered
        s = presto_tpu.connect(served[1].catalog)
        for k, v in CONFIG["session_properties"].items():
            s.set(k, v)
        s.set("partial_aggregation_max_groups", 1024)
        got = s.sql(text_of("q27"))
        CC.clear()
        assert reference.rows_equal(got.rows, want, REL)
        return got.stats

    st = q27()
    assert st.execution_mode == "distributed" and not st.fallback_reason
    assert 0 < st.grouping_set_merge_rows < 2 * st.grouping_set_state_rows

    real = Distributer._visit_groupingsets

    def lying(self, node):
        final, out = real(self, node)
        assert final.merge_hints["input_est_hint"] > 1    # repartitioned
        final.merge_hints["input_est_hint"] = 1
        return final, out

    monkeypatch.setattr(Distributer, "_visit_groupingsets", lying)
    st = q27()
    assert st.execution_mode != "distributed"
    assert "static assumption violated at runtime" in st.fallback_reason
