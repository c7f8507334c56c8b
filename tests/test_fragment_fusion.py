"""Fragment fusion (ROADMAP open item 1): mesh-local exchange edges of a
cluster plan splice back into ONE traced shard_map program whose
Exchange nodes lower to ICI collectives (`plan/distribute.fuse_fragments`
+ `parallel/dist_executor.run_fused_fragment`), with the per-fragment
HTTP path as the byte-identical fallback for cross-host edges, kill
switches, and fault recovery."""

import json

import pytest

import presto_tpu
from presto_tpu.parallel import cluster as C
from tests.sqlite_oracle import assert_same_results, to_sqlite
from tests.tpch_queries import QUERIES


def norm(rows):
    return sorted(
        tuple(round(x, 4) if isinstance(x, float) else x for x in r)
        for r in rows)


def _counters(url):
    return json.loads(C._http(f"{url}/v1/info", timeout=10.0))["counters"]


# ---- fusion pass units ------------------------------------------------


def _fragments_for(session, sql, nw=1):
    from presto_tpu.exec.executor import plan_statement
    from presto_tpu.plan.distribute import distribute
    from presto_tpu.sql.parser import parse

    plan = plan_statement(session, parse(sql))
    dplan = distribute(plan, session, nw)
    return C.cut_fragments(dplan.root)


def test_fuse_fragments_full_splice(tpch_catalog_tiny):
    """Fusing every edge collapses the fragment DAG to ONE fragment
    whose root holds the original exchanges INLINE (no __exch_ scans),
    absorbing n-1 fragments."""
    from presto_tpu.plan import nodes as P
    from presto_tpu.plan.distribute import fuse_fragments

    s = presto_tpu.connect(tpch_catalog_tiny)
    frags = _fragments_for(
        s, "SELECT n_name, count(*) FROM customer, nation "
           "WHERE c_nationkey = n_nationkey GROUP BY n_name")
    assert len(frags) >= 2
    fused, n = fuse_fragments(frags, lambda f, i: True)
    assert n == len(frags) - 1
    assert len(fused) == 1 and getattr(fused[0], "fused", False)
    kinds, exch_scans = [], []

    def walk(node):
        if isinstance(node, P.Exchange):
            kinds.append(node.kind)
        if isinstance(node, P.TableScan) and node.table.startswith("__exch_"):
            exch_scans.append(node.table)
        for src in node.sources:
            walk(src)

    walk(fused[0].root)
    assert kinds and not exch_scans, (kinds, exch_scans)
    assert sorted(fused[0].fused_fids) == list(range(len(frags) - 1))


def test_fuse_fragments_partial_keeps_external_edge(tpch_catalog_tiny):
    """An excluded edge kind stays a cut: the super-fragment keeps an
    external __exch_ input (migrated producer inputs included) and the
    producer survives as its own fragment."""
    from presto_tpu.plan.distribute import fuse_fragments

    s = presto_tpu.connect(tpch_catalog_tiny)
    s.set("distributed_sort_threshold_rows", 100)
    frags = _fragments_for(
        s, "SELECT c_custkey, c_acctbal FROM customer "
           "ORDER BY c_acctbal DESC, c_custkey")
    assert any(i.kind == "range" for f in frags for i in f.inputs)
    fused, n = fuse_fragments(
        frags, lambda f, i: i.kind != "range")
    assert n >= 1 and len(fused) == len(frags) - n
    ext = [i for f in fused for i in f.inputs]
    assert [i.kind for i in ext] == ["range"]
    # producers renumbered consistently: every producer fid exists
    for f in fused:
        for i in f.inputs:
            assert 0 <= i.producer < f.fid


# ---- end-to-end over a declared-mesh worker ---------------------------


@pytest.fixture(scope="module")
def fusion_cluster(tpch_catalog_tiny):
    """In-process worker that DECLARES a 4-device mesh out of the
    8-device test process (the operator grant; workers never infer
    mesh ownership).  4 keeps the fused shard programs cheap on the
    1-core CI tier — the mechanism is ndev-independent."""
    session = presto_tpu.connect(tpch_catalog_tiny)
    w = C.WorkerServer("tpch:0.01:/tmp/presto_tpu_cache",
                       mesh_devices=4).start()
    cs = C.ClusterSession(session, [w.url])
    yield session, cs, w
    w.stop()


def test_worker_advertises_declared_mesh(fusion_cluster):
    _session, cs, w = fusion_cluster
    info = json.loads(C._http(f"{w.url}/v1/info", timeout=10.0))
    assert info["meshDevices"] == 4
    assert info["meshId"]
    # undeclared workers advertise no mesh (in-process default)
    w2 = C.WorkerServer("tpch:0.01:/tmp/presto_tpu_cache").start()
    try:
        assert json.loads(C._http(f"{w2.url}/v1/info",
                                  timeout=10.0))["meshDevices"] == 0
    finally:
        w2.stop()


@pytest.mark.parametrize("qid", [3,
                                 pytest.param(18, marks=pytest.mark.slow),
                                 pytest.param(21, marks=pytest.mark.slow)])
def test_fused_vs_cut_checksum_equivalence(qid, fusion_cluster,
                                           tpch_sqlite_tiny):
    """The acceptance gate: distributed q3(/q18/q21) executes as a
    single fused program on the mesh (fragments_fused > 0, zero
    exchange bytes through the host) with results identical to the
    fragment-cut path AND the sqlite oracle.  q18/q21's cut legs are
    tier-2 (the cut path's cold per-fragment execution costs tens of
    seconds on the 1-core CI tier); tier-1 covers q18 fused via
    test_q18_single_fused_program."""
    session, cs, w = fusion_cluster
    session.set("fragment_fusion", True)
    fused = cs.sql(QUERIES[qid])
    st = fused.stats
    assert st.fragments_fused > 0, "did not fuse"
    assert st.exchange_bytes_host == 0, st.exchange_bytes_host
    assert st.exchange_bytes_collective > 0
    session.set("fragment_fusion", False)
    try:
        cut = cs.sql(QUERIES[qid])
    finally:
        session.set("fragment_fusion", True)
    assert cut.stats.fragments_fused == 0
    assert norm(fused.rows) == norm(cut.rows)
    expected = tpch_sqlite_tiny.execute(to_sqlite(QUERIES[qid])).fetchall()
    assert_same_results(fused.rows, expected, ordered=True)


def test_q18_single_fused_program(fusion_cluster, tpch_sqlite_tiny):
    """q18 (the deep join+agg gate query) fuses into ONE program with
    zero host exchange bytes and matches the sqlite oracle; its full
    fused-vs-cut leg is tier-2;
    the round-18 AUTO leg (cost model picks cut here) lives in
    tests/test_fusion_cost.py."""
    session, cs, _w = fusion_cluster
    r = cs.sql(QUERIES[18])
    st = r.stats
    assert st.fragments_fused > 0
    assert st.exchange_bytes_host == 0
    assert st.exchange_bytes_collective > 0
    expected = tpch_sqlite_tiny.execute(to_sqlite(QUERIES[18])).fetchall()
    assert_same_results(r.rows, expected, ordered=True)


def test_fused_warm_run_reuses_executable(fusion_cluster):
    """One executable per fused pipeline (exec/compile_cache.fused_key):
    a warm re-run of a fused query compiles NOTHING on the worker."""
    session, cs, w = fusion_cluster
    cs.sql(QUERIES[3])  # ensure warm
    before = _counters(w.url)["compiles"]
    r = cs.sql(QUERIES[3])
    after = _counters(w.url)["compiles"]
    assert r.stats.fragments_fused > 0
    assert after == before, f"warm fused run recompiled ({after - before})"


def test_fused_worker_info_counters(fusion_cluster):
    """Satellite: worker /v1/info carries the fusion counters."""
    session, cs, w = fusion_cluster
    cs.sql(QUERIES[3])
    c = _counters(w.url)
    assert c["tasks_fused"] >= 1
    assert c["fragments_fused"] >= 1
    assert c["exchange_bytes_collective"] > 0


def test_partial_fusion_range_edge_stays_on_host(fusion_cluster,
                                                 tpch_sqlite_tiny):
    """fragment_fusion_kinds without `range`: the distributed sample
    sort's range edge stays an HTTP exchange between a scan fragment
    and the fused sort+output super-fragment — fragments still fuse,
    host exchange bytes are nonzero, order is exact."""
    session, cs, _w = fusion_cluster
    session.set("fragment_fusion_kinds",
                "repartition,broadcast,gather,scatter")
    session.set("distributed_sort_threshold_rows", 100)
    sql = ("SELECT c_custkey, c_acctbal FROM customer "
           "ORDER BY c_acctbal DESC, c_custkey")
    try:
        r = cs.sql(sql)
    finally:
        session.set("fragment_fusion_kinds", "")
        session.set("distributed_sort_threshold_rows", 100_000)
    st = r.stats
    assert st.fragments_fused > 0
    assert st.exchange_bytes_host > 0  # the unfused range edge
    expected = tpch_sqlite_tiny.execute(to_sqlite(sql)).fetchall()
    assert_same_results(r.rows, expected, ordered=True)


def test_cross_host_edges_do_not_fuse(fusion_cluster):
    """Forced cross-host topology: the worker's declared mesh falls
    below fragment_fusion_min_devices (a too-small mesh is no fusion
    target — same classifier verdict as an undeclared one), so every
    edge is cross-host: the per-fragment HTTP path runs, asserted via
    counters, with identical results."""
    session, cs, w = fusion_cluster
    fused_before = _counters(w.url)["tasks_fused"]
    session.set("fragment_fusion_min_devices", 99)
    q = ("SELECT n_name, count(*) c FROM customer, nation "
         "WHERE c_nationkey = n_nationkey GROUP BY n_name ORDER BY 1")
    try:
        r = cs.sql(q)
    finally:
        session.set("fragment_fusion_min_devices", 2)
    st = r.stats
    assert st.fragments_fused == 0
    assert st.exchange_bytes_host > 0  # pages crossed the host
    assert st.exchange_bytes_collective == 0
    assert norm(r.rows) == norm(session.sql(q).rows)
    assert _counters(w.url)["tasks_fused"] == fused_before


def test_fragment_fusion_kill_switches(fusion_cluster, monkeypatch):
    """Session property AND env kill switch each restore the old path
    exactly (fragments_fused == 0, host exchange bytes > 0, identical
    rows)."""
    session, cs, _w = fusion_cluster
    q = ("SELECT o_orderpriority, count(*) c FROM orders "
         "GROUP BY o_orderpriority ORDER BY 1")
    fused = cs.sql(q)
    assert fused.stats.fragments_fused > 0
    session.set("fragment_fusion", False)
    try:
        off = cs.sql(q)
    finally:
        session.set("fragment_fusion", True)
    assert off.stats.fragments_fused == 0
    assert off.stats.exchange_bytes_host > 0
    assert norm(off.rows) == norm(fused.rows)
    monkeypatch.setenv("PRESTO_TPU_FRAGMENT_FUSION", "off")
    env_off = cs.sql(q)
    assert env_off.stats.fragments_fused == 0
    assert norm(env_off.rows) == norm(fused.rows)
    monkeypatch.delenv("PRESTO_TPU_FRAGMENT_FUSION")


def test_fused_scalar_subquery_and_dynamic_filters(fusion_cluster):
    """Coordinator-evaluated scalar subqueries bake into the fused
    trace (and ride the executable-memo key); in-trace dynamic filters
    keep producing/applying inside the fused program."""
    session, cs, _w = fusion_cluster
    q = ("SELECT o_orderpriority, count(*) FROM orders "
         "WHERE o_totalprice > (SELECT avg(o_totalprice) FROM orders) "
         "GROUP BY o_orderpriority ORDER BY 1")
    r = cs.sql(q)
    assert r.stats.fragments_fused > 0
    assert norm(r.rows) == norm(session.sql(q).rows)


@pytest.mark.slow
def test_fused_all_22_tpch_queries_match_cut_path(fusion_cluster):
    """Tier-2 sweep: every TPC-H query agrees fused-vs-cut (shapes that
    cannot distribute fall back identically on both paths)."""
    session, cs, _w = fusion_cluster
    for qid in sorted(QUERIES):
        fused = cs.sql(QUERIES[qid])
        session.set("fragment_fusion", False)
        try:
            cut = cs.sql(QUERIES[qid])
        finally:
            session.set("fragment_fusion", True)
        assert norm(fused.rows) == norm(cut.rows), f"Q{qid}"
