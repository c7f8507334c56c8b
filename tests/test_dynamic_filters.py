"""Dynamic filtering (ISSUE 5): build-side runtime filters pushed into
probe scans.

Layers under test:
- exec/kernels.py rf_* family: CPU equivalence of the exact
  (searchsorted) and bloom membership probes against a numpy reference,
  across dtypes x masks x empty x all-pruned, plus the bloom sizing
  heuristic's false-positive rate and the host summary/union twins.
- plan/runtime_filters.py: producer/consumer annotation of q17-class
  plans, the kill switch, and domain merge (intersection) semantics.
- executor: dynamic mode counts pruned rows; a compiled program
  DECLINES the mask (df_filters_declined: fixed shapes, nothing would
  shrink) and traces nothing under k:runtime_filter; sharded executors
  keep it; results are IDENTICAL with filtering on/off.
- exec/chunked.py: whole chunks whose zone ranges miss the runtime
  domain are skipped (df_chunks_pruned) while the per-chunk programs
  decline the mask, results identical.
- parallel/cluster.py: in-fragment filters on broadcast-build joins and
  the coordinator-routed side channel for partitioned joins (partial
  summaries unioned per repartition bucket), observable via /v1/info.
"""

import json
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import presto_tpu
from presto_tpu import types as T
from presto_tpu.batch import Column
from presto_tpu.exec import kernels as K
from presto_tpu.plan import runtime_filters as RF
from presto_tpu.plan.domains import merge_domain_maps
from presto_tpu.storage.shard import Domain

from tpch_queries import QUERIES


def norm(rows):
    return [tuple(round(v, 2) if isinstance(v, float) else v for v in r)
            for r in rows]


# ---------------------------------------------------------------------------
# kernel units: exact + bloom membership vs numpy reference
# ---------------------------------------------------------------------------


def _ref_mask(build_vals, build_live, probe_vals, probe_valid):
    keep = set(np.asarray(build_vals)[np.asarray(build_live)].tolist())
    return np.asarray([bool(v) and (x in keep)
                       for x, v in zip(np.asarray(probe_vals).tolist(),
                                       np.asarray(probe_valid).tolist())])


@pytest.mark.parametrize("dtype", [np.int64, np.int32, np.int16])
@pytest.mark.parametrize("structure", ["exact", "bloom"])
@pytest.mark.parametrize("case", ["plain", "masked", "empty", "all_pruned"])
def test_rf_membership_equivalence(dtype, structure, case):
    rng = np.random.default_rng(7)
    nb, npr = 300, 2000
    if case == "empty":
        bvals = np.zeros((0,), dtype)
        blive = np.zeros((0,), bool)
    else:
        bvals = rng.integers(0, 500, nb).astype(dtype)
        blive = np.ones(nb, bool)
        if case == "masked":
            blive[::3] = False
    if case == "all_pruned":
        pvals = (rng.integers(600, 900, npr)).astype(dtype)  # disjoint
    else:
        pvals = rng.integers(0, 700, npr).astype(dtype)
    pvalid = np.ones(npr, bool)
    pvalid[::7] = False  # NULL probe keys: always prunable

    t = {np.int64: T.BIGINT, np.int32: T.INTEGER, np.int16: T.SMALLINT}[dtype]
    bcol = Column(jnp.asarray(bvals), None, t, None)
    pcol = Column(jnp.asarray(pvals), jnp.asarray(pvalid), t, None)
    summary = K.rf_build(bcol, jnp.asarray(blive), structure=structure)
    mask = np.asarray(K.rf_probe(summary, pcol))
    ref = _ref_mask(bvals, blive, pvals, pvalid)
    if structure == "exact":
        assert (mask == ref).all()
    else:
        # bloom contract: false positives allowed, false negatives never
        assert (mask | ~ref).all(), "bloom dropped a matching row"
        if case == "all_pruned":
            assert mask.mean() < 0.10  # and it does actually prune


def test_rf_bloom_auto_routing_and_fpr():
    """Builds over RF_EXACT_MAX route to bloom; the sizing heuristic
    (RF_BLOOM_BITS_PER_KEY bits/key, k=3) keeps the measured
    false-positive rate inside ~4x the analytic ~0.5%."""
    rng = np.random.default_rng(3)
    nb = 1 << 12
    bvals = np.unique(rng.integers(0, 1 << 40, nb)).astype(np.int64)
    bcol = Column(jnp.asarray(bvals), None, T.BIGINT, None)
    live = jnp.ones((bvals.size,), bool)
    auto = K.rf_build(bcol, live)
    assert auto["kind"] == "exact"  # small build: exact wins
    bloom = K.rf_build(bcol, live, structure="bloom")
    # 100k probes guaranteed OUTSIDE the build set: any hit is a FP
    pvals = rng.integers(1 << 41, 1 << 42, 100_000).astype(np.int64)
    pcol = Column(jnp.asarray(pvals), None, T.BIGINT, None)
    fpr = float(np.asarray(K.rf_probe(bloom, pcol)).mean())
    assert fpr < 0.02, fpr


def test_rf_host_summary_union_and_device_roundtrip():
    a = K.rf_summary_host(np.asarray([5, 1, 3, 3]))
    b = K.rf_summary_host(np.asarray([8, 2]))
    assert a == {"lo": 1, "hi": 5, "vals": [1, 3, 5]}
    u = K.rf_union_host([a, b])
    assert u == {"lo": 1, "hi": 8, "vals": [1, 2, 3, 5, 8]}
    # an inexact part degrades the union to a domain
    big = {"lo": 0, "hi": 100, "vals": None}
    assert K.rf_union_host([a, big])["vals"] is None
    # empty build -> impossible filter -> prunes every probe row
    empty = K.rf_host_to_device(K.rf_summary_host(np.asarray([])))
    pcol = Column(jnp.asarray(np.arange(16)), None, T.BIGINT, None)
    assert not np.asarray(K.rf_probe(empty, pcol)).any()
    dev = K.rf_host_to_device(u)
    got = np.asarray(K.rf_probe(dev, pcol))
    assert (got == np.isin(np.arange(16), [1, 2, 3, 5, 8])).all()
    dom = K.rf_host_to_device(big)
    assert dom["kind"] == "domain"
    assert np.asarray(K.rf_probe(dom, pcol)).all()


def test_merge_static_in_list_with_runtime_minmax():
    """ISSUE-5 satellite: runtime-derived domains INTERSECT statically
    extracted ones — an IN-list static domain combined with a runtime
    min/max on the same column keeps only the in-range list values."""
    static = {"l_partkey": Domain(values=[2, 40, 700]),
              "l_shipdate": Domain(10, 20)}
    runtime = {"l_partkey": Domain(30, 800), "l_orderkey": Domain(1, 5)}
    merged = merge_domain_maps(static, runtime)
    assert merged["l_partkey"].values == [40, 700]
    assert (merged["l_shipdate"].lo, merged["l_shipdate"].hi) == (10, 20)
    assert (merged["l_orderkey"].lo, merged["l_orderkey"].hi) == (1, 5)
    # intersection semantics drive pruning: a stripe overlapping the
    # static list but not the runtime range is now prunable
    assert not merged["l_partkey"].overlaps(0, 29)
    assert merged["l_partkey"].overlaps(30, 50)


# ---------------------------------------------------------------------------
# planner annotation
# ---------------------------------------------------------------------------


def test_planner_annotates_q17(tpch_catalog_tiny):
    from presto_tpu.exec.executor import plan_statement
    from presto_tpu.plan import nodes as P
    from presto_tpu.sql.parser import parse

    session = presto_tpu.connect(tpch_catalog_tiny)
    plan = plan_statement(session, parse(QUERIES[17]))
    produced, consumed = [], []

    def walk(n, seen):
        if id(n) in seen:
            return
        seen.add(id(n))
        produced.extend(getattr(n, "rf_produce", None) or [])
        if isinstance(n, P.TableScan):
            consumed.extend(getattr(n, "rf_consume", None) or [])
        for s in n.sources:
            walk(s, seen)

    seen = set()
    walk(plan.root, seen)
    for sub in plan.subplans.values():
        walk(sub, seen)
    assert produced, "q17's selective part join produced no filter"
    fids = {s["fid"] for s in produced}
    hit = [c for c in consumed if c["fid"] in fids]
    assert hit and hit[0]["column"] == "l_partkey", consumed


def test_planner_kill_switch(tpch_catalog_tiny):
    from presto_tpu.exec.executor import plan_statement
    from presto_tpu.sql.parser import parse

    session = presto_tpu.connect(tpch_catalog_tiny,
                                 dynamic_filtering=False)
    plan = plan_statement(session, parse(QUERIES[17]))

    def any_rf(n, seen):
        if id(n) in seen:
            return False
        seen.add(id(n))
        if getattr(n, "rf_produce", None) or getattr(n, "rf_consume", None):
            return True
        return any(any_rf(s, seen) for s in n.sources)

    assert not any_rf(plan.root, set())


def test_resolve_probe_refuses_shared_subtrees():
    from presto_tpu.plan import nodes as P

    scan = P.TableScan("t", {"a": "a"}, {"a": T.BIGINT})
    scan.shared_subtree = True
    assert RF.resolve_probe_scan(scan, "a") is None


# ---------------------------------------------------------------------------
# engine equivalence: q17-class on vs off
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def dyn_sessions(tpch_catalog_tiny):
    on = presto_tpu.connect(tpch_catalog_tiny, execution_mode="dynamic")
    off = presto_tpu.connect(tpch_catalog_tiny, execution_mode="dynamic",
                             dynamic_filtering=False)
    return on, off


def test_q17_dynamic_rows_pruned_and_identical(dyn_sessions):
    """Acceptance: with dynamic filtering on, q17 prunes probe rows
    BEFORE the join (df_rows_pruned > 0) and the result checksum is
    identical to dynamic_filtering=off."""
    on, off = dyn_sessions
    r_on = on.sql(QUERIES[17])
    r_off = off.sql(QUERIES[17])
    assert norm(r_on.rows) == norm(r_off.rows)
    assert r_on.stats.df_filters_produced >= 1
    assert r_on.stats.df_filters_applied >= 1
    assert r_on.stats.df_rows_pruned > 0
    assert r_off.stats.df_filters_applied == 0
    assert r_off.stats.df_rows_pruned == 0


@pytest.mark.slow
@pytest.mark.parametrize("qid", [8, 19])
def test_q8_q19_dynamic_identical(dyn_sessions, qid):
    on, off = dyn_sessions
    assert norm(on.sql(QUERIES[qid]).rows) == norm(off.sql(QUERIES[qid]).rows)


def test_q17_compiled_on_off_identical(tpch_catalog_tiny):
    """Compiled mode: shapes are fixed, so the mask would shrink nothing
    — the program declines it at trace time (df_filters_declined),
    produces and applies no filter, results identical on/off."""
    on = presto_tpu.connect(tpch_catalog_tiny, execution_mode="compiled")
    off = presto_tpu.connect(tpch_catalog_tiny, execution_mode="compiled",
                             dynamic_filtering=False)
    r_on = on.sql(QUERIES[17])
    r_off = off.sql(QUERIES[17])
    assert norm(r_on.rows) == norm(r_off.rows)
    assert r_on.stats.execution_mode == "compiled"
    assert r_on.stats.df_filters_declined >= 1
    assert r_on.stats.df_filters_applied == 0
    assert r_on.stats.df_filters_produced == 0
    assert r_off.stats.df_filters_declined == 0
    assert r_off.stats.df_filters_applied == 0


def _annotated_filters(session, sql):
    """How many filters the planner wires into sql's joins."""
    from presto_tpu.exec.executor import plan_statement
    from presto_tpu.sql.parser import parse

    plan = plan_statement(session, parse(sql))
    specs, seen = [], set()

    def walk(n):
        if id(n) in seen:
            return
        seen.add(id(n))
        specs.extend(getattr(n, "rf_produce", None) or [])
        for s in n.sources:
            walk(s)

    walk(plan.root)
    for sub in plan.subplans.values():
        walk(sub)
    return len(specs)


@pytest.mark.parametrize("query", [3, 18], ids=["tpch_q3", "tpch_q18"])
def test_compiled_program_traces_no_runtime_filter(
        tpch_catalog_tiny, lowered_texts, monkeypatch, query):
    """TPC-H's Q3 and Q18 (the join queries sf1_join serves): the
    compiled program holds NO operation under k:runtime_filter and
    counts every annotated filter as declined; dynamic mode still
    builds, probes and prunes."""
    from presto_tpu.exec import compile_cache as CC

    # the tiny catalog's probes sit under the planner's 50k-row gate
    monkeypatch.setenv("PRESTO_TPU_DF_MIN_PROBE", "1000")
    calls = []
    real_build, real_probe = K.rf_build, K.rf_probe
    monkeypatch.setattr(K, "rf_build", lambda *a, **k: (
        calls.append("build"), real_build(*a, **k))[1])
    monkeypatch.setattr(K, "rf_probe", lambda *a, **k: (
        calls.append("probe"), real_probe(*a, **k))[1])
    sql = QUERIES[query]

    compiled = presto_tpu.connect(tpch_catalog_tiny,
                                  execution_mode="compiled")
    filters = _annotated_filters(compiled, sql)
    assert filters >= 2
    CC.clear()      # nothing in the process-wide memo: built, spied on
    r_c = compiled.sql(sql)
    assert r_c.stats.execution_mode == "compiled"
    assert lowered_texts, "no program was built"
    assert "k:runtime_filter" not in "\n".join(lowered_texts)
    assert not calls
    assert r_c.stats.df_filters_declined == filters
    assert r_c.stats.df_filters_produced == 0
    assert r_c.stats.df_filters_applied == 0
    assert r_c.stats.df_rows_pruned == 0

    dynamic = presto_tpu.connect(tpch_catalog_tiny,
                                 execution_mode="dynamic")
    r_d = dynamic.sql(sql)
    assert norm(r_d.rows) == norm(r_c.rows)
    assert calls.count("build") == filters
    assert calls.count("probe") == filters
    assert r_d.stats.df_filters_declined == 0
    assert r_d.stats.df_filters_produced == filters
    assert r_d.stats.df_filters_applied == filters
    assert r_d.stats.df_rows_pruned > 0


def _probe_scan_and_batch():
    """A probe-side scan annotated as the consumer of filter df0, its
    batch (keys 0..15), and a device summary of the build keys."""
    from presto_tpu.batch import Batch
    from presto_tpu.plan import nodes as P

    scan = P.TableScan("t", {"a": "a"}, {"a": T.BIGINT})
    scan.rf_consume = [{"fid": "df0", "sym": "a", "column": "a"}]
    batch = Batch({"a": Column(jnp.arange(16), None, T.BIGINT, None)},
                  jnp.ones((16,), bool))
    build = Column(jnp.asarray([1, 2, 3, 5, 8]), None, T.BIGINT, None)
    return scan, batch, K.rf_build(build, jnp.ones((5,), bool))


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["compiled", "mesh_shard"])
def test_injected_summaries_apply_in_a_static_trace(tpch_catalog_tiny,
                                                    sharded):
    """_rf_apply is not gated by _rf_mask_pays: a summary that arrives
    from outside (rf_inject, the cluster's side channel) masks the scan
    in a static executor too.  Only a single-device compiled program
    declines to PRODUCE one; a mesh shard answers the predicate True."""
    from presto_tpu.exec.executor import Executor
    from presto_tpu.parallel.dist_executor import DistExecutor

    session = presto_tpu.connect(tpch_catalog_tiny)
    if sharded:
        ex = DistExecutor(session, 2, scan_inputs={})
    else:
        ex = Executor(session, static=True)
    assert ex.static
    assert ex._rf_mask_pays() is sharded
    assert Executor(session)._rf_mask_pays()    # dynamic mode: it pays
    scan, batch, summary = _probe_scan_and_batch()
    assert ex._rf_apply(scan, batch) is batch   # nothing registered
    ex.rf_inject({"df0": summary})
    out = ex._rf_apply(scan, batch)
    assert (np.asarray(out.sel)
            == np.isin(np.arange(16), [1, 2, 3, 5, 8])).all()
    assert ex.sort_stats["df_filters_applied"] == 1
    assert "df_filters_declined" not in ex.sort_stats


# ---------------------------------------------------------------------------
# chunked mode: chunk pruning + equivalence
# ---------------------------------------------------------------------------


def _chunked_session(cat, df=True):
    s = presto_tpu.connect(cat)
    s.properties["chunked_rows_threshold"] = 10_000
    s.properties["chunk_orders"] = 4_000  # ~4 chunks at SF0.01
    s.properties["dynamic_filtering"] = df
    return s


def test_chunked_runtime_domain_prunes_chunks(tpch_catalog_tiny):
    """Acceptance (chunked): a resident build joined to the chunked
    probe on the bucket column skips every chunk whose orderkey range
    misses the runtime domain — df_chunks_pruned > 0, results identical
    to filtering off AND to whole-table execution."""
    ddl = ("CREATE TABLE ok_list AS SELECT o_orderkey AS k FROM orders "
           "WHERE o_orderkey < 2000")
    q = ("SELECT count(*) c, sum(l_quantity) q FROM lineitem, ok_list "
         "WHERE l_orderkey = k")
    s_on = _chunked_session(tpch_catalog_tiny, True)
    s_off = _chunked_session(tpch_catalog_tiny, False)
    whole = presto_tpu.connect(tpch_catalog_tiny)
    whole.sql(ddl)  # the catalog is shared: create once
    r_on = s_on.sql(q)
    r_off = s_off.sql(q)
    r_whole = whole.sql(q)
    try:
        assert norm(r_on.rows) == norm(r_off.rows) == norm(r_whole.rows)
        assert r_on.stats.execution_mode == "chunked"
        assert r_on.stats.df_chunks_pruned > 0
        # the per-chunk programs are compiled: they decline the mask
        assert r_on.stats.df_filters_declined >= 1
        assert r_on.stats.df_filters_applied == 0
        assert r_off.stats.df_chunks_pruned == 0
        assert r_off.stats.df_filters_declined == 0
    finally:
        whole.sql("DROP TABLE ok_list")


def test_chunked_all_chunks_pruned_keeps_one_and_stays_empty(
        tpch_catalog_tiny):
    """A build whose keys miss EVERY chunk: the grid keeps one chunk so
    downstream shapes stay well-formed, and with the in-trace mask
    declined it is the join itself that drops that chunk's rows."""
    ddl = ("CREATE TABLE far_list AS SELECT o_orderkey + 100000000 AS k "
           "FROM orders WHERE o_orderkey < 2000")
    q = ("SELECT count(*) c, sum(l_quantity) q FROM lineitem, far_list "
         "WHERE l_orderkey = k")
    s_on = _chunked_session(tpch_catalog_tiny, True)
    s_off = _chunked_session(tpch_catalog_tiny, False)
    s_on.sql(ddl)  # the catalog is shared: create once
    try:
        r_on = s_on.sql(q)
        r_off = s_off.sql(q)
        assert norm(r_on.rows) == norm(r_off.rows)
        assert r_on.rows[0][0] == 0
        assert r_on.stats.execution_mode == "chunked"
        assert r_on.stats.df_chunks_pruned > 0
        assert r_on.stats.df_filters_applied == 0
    finally:
        s_on.sql("DROP TABLE far_list")


@pytest.mark.slow
def test_chunked_q17_on_off_identical(tpch_catalog_tiny):
    """q17 chunked: the per-chunk programs decline the in-trace filter
    (trace counter), results identical.  Chunk pruning is honestly 0
    here — l_partkey does not correlate with the orderkey-range chunk
    grid (docs/PERF.md r10)."""
    s_on = _chunked_session(tpch_catalog_tiny, True)
    s_off = _chunked_session(tpch_catalog_tiny, False)
    r_on = s_on.sql(QUERIES[17])
    r_off = s_off.sql(QUERIES[17])
    assert r_on.stats.execution_mode == "chunked"
    assert norm(r_on.rows) == norm(r_off.rows)
    assert r_on.stats.df_filters_declined >= 1
    assert r_on.stats.df_filters_applied == 0


# ---------------------------------------------------------------------------
# cluster mode: in-fragment filters + the coordinator-routed side channel
# ---------------------------------------------------------------------------


CLUSTER_Q = ("SELECT count(*) c, sum(l_extendedprice) s FROM lineitem, "
             "part WHERE p_partkey = l_partkey "
             "AND p_container = 'MED BOX'")


def _worker_counters(url):
    from presto_tpu.parallel import cluster as C

    req = C._signed_request("GET", f"{url}/v1/info")
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.loads(r.read())["counters"]


@pytest.fixture(scope="module")
def df_cluster(tpch_catalog_tiny):
    from presto_tpu.parallel import cluster as C

    session = presto_tpu.connect(tpch_catalog_tiny)
    workers = [C.WorkerServer("tpch:0.01:/tmp/presto_tpu_cache").start()
               for _ in range(2)]
    cs = C.ClusterSession(session, [w.url for w in workers])
    yield session, cs, workers
    for w in workers:
        if not w.crashed:
            w.stop()


def _df_delta(workers, before):
    keys = ("df_filters_produced", "df_filters_applied",
            "df_filters_declined", "df_rows_pruned")
    after = [_worker_counters(w.url) for w in workers]
    return {k: sum(a[k] - b[k] for a, b in zip(after, before))
            for k in keys}


@pytest.mark.slow
def test_cluster_broadcast_filters_in_fragment(df_cluster):
    """Default (broadcast build): the probe fragment holds both the
    producer join and the probe scan — workers apply the filter locally
    and report it via /v1/info; results match single-device."""
    session, cs, workers = df_cluster
    want = norm(session.sql(CLUSTER_Q).rows)
    before = [_worker_counters(w.url) for w in workers]
    got = cs.sql(CLUSTER_Q)
    assert norm(got.rows) == want
    d = _df_delta(workers, before)
    assert d["df_filters_applied"] >= 1, d
    assert d["df_rows_pruned"] > 0, d


def test_cluster_tasks_keep_their_filters(df_cluster, monkeypatch):
    """Tier-1 twin of the side-channel test below: a cluster task's
    FragmentExecutor answers _rf_mask_pays True (a pruned row is a row
    no exchange ships), so the partitioned join's summaries still
    travel, apply and prune on the workers, and nothing is declined."""
    from presto_tpu.exec.executor import Executor

    session, cs, workers = df_cluster
    pays = []
    real = Executor._exec_join

    def spy(self, node):
        if type(self).__name__ == "FragmentExecutor":
            pays.append(self._rf_mask_pays())
        return real(self, node)

    monkeypatch.setattr(Executor, "_exec_join", spy)
    want = norm(session.sql(CLUSTER_Q).rows)
    session.set("broadcast_join_threshold_rows", 0)
    session.set("dynamic_filtering_wait_ms", 8000)
    before = [_worker_counters(w.url) for w in workers]
    try:
        got = cs.sql(CLUSTER_Q)
    finally:
        session.set("broadcast_join_threshold_rows", 1_000_000)
        session.set("dynamic_filtering_wait_ms", 0)
    assert norm(got.rows) == want
    assert pays and all(pays)
    d = _df_delta(workers, before)
    assert d["df_filters_applied"] >= 1, d
    assert d["df_rows_pruned"] > 0, d
    assert d["df_filters_declined"] == 0, d


@pytest.mark.slow
def test_cluster_partitioned_side_channel(df_cluster):
    """Partitioned join (broadcast threshold 0): the probe leaf fragment
    is separate from the join fragment, so filters travel the side
    channel — each join task POSTs its repartition bucket's partial
    summary to the probe tasks, which wait (dynamic_filtering_wait_ms)
    and union the parts.  Probe rows prune on the workers; results
    identical."""
    session, cs, workers = df_cluster
    want = norm(session.sql(CLUSTER_Q).rows)
    session.set("broadcast_join_threshold_rows", 0)
    session.set("dynamic_filtering_wait_ms", 8000)
    before = [_worker_counters(w.url) for w in workers]
    try:
        got = cs.sql(CLUSTER_Q)
    finally:
        session.set("broadcast_join_threshold_rows", 1_000_000)
        session.set("dynamic_filtering_wait_ms", 0)
    assert norm(got.rows) == want
    d = _df_delta(workers, before)
    assert d["df_filters_applied"] >= 1, d
    assert d["df_rows_pruned"] > 0, d
    after = [_worker_counters(w.url) for w in workers]
    assert any(a["df_wait_ms"] > 0 for a in after)


@pytest.mark.slow
def test_cluster_kill_switch_no_activity(df_cluster):
    session, cs, workers = df_cluster
    want = norm(session.sql(CLUSTER_Q).rows)
    session.set("dynamic_filtering", False)
    before = [_worker_counters(w.url) for w in workers]
    try:
        got = cs.sql(CLUSTER_Q)
    finally:
        session.set("dynamic_filtering", True)
    assert norm(got.rows) == want
    d = _df_delta(workers, before)
    assert d == {"df_filters_produced": 0, "df_filters_applied": 0,
                 "df_filters_declined": 0, "df_rows_pruned": 0}, d
