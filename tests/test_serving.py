"""Serving-tier tests (ISSUE 6): prepared-statement lifecycle with
aval-abstracted plan/executable reuse, admission control under overload,
the result cache, and graceful-shutdown queue draining.

Reference analogs: TestQueuesDb / resource-group tests in presto-tests,
TestPreparedStatements over DistributedQueryRunner, plus the serving
acceptance criteria: warm EXECUTE records compiles == 0 with no
parse/plan work; an overloaded group queues in policy order with zero
failures; shed queries get a clean QUEUE_FULL error; identical
re-submitted queries serve from the result cache checksum-equal."""

import json
import threading
import urllib.request

import numpy as np
import pytest

import presto_tpu
from presto_tpu import types as T
from presto_tpu.catalog import MemoryTable
from presto_tpu.client import StatementClient, connect_http
from presto_tpu.client.statement import QueryError
from presto_tpu.server import PrestoTpuServer
from presto_tpu.server.resource_groups import (QueryRejected,
                                               ResourceGroupManager)
from presto_tpu.server.serving import ResultCache, ServingTier


def _session(**props):
    s = presto_tpu.connect(**props)
    s.catalog.register_memory(
        "t", {"k": T.BIGINT, "x": T.DOUBLE, "g": T.BIGINT, "s": T.VARCHAR},
        {"k": np.arange(200, dtype=np.int64),
         "x": np.arange(200, dtype=np.float64) * 1.5,
         "g": np.arange(200, dtype=np.int64) % 7,
         "s": np.array([f"val_{i:04d}" for i in range(200)], dtype=object)})
    return s


# ---------------------------------------------------------------------------
# prepared-statement lifecycle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["compiled", "dynamic"])
def test_prepared_lifecycle_zero_compile_warm(mode):
    """PREPARE -> EXECUTE (v1) -> EXECUTE (v2, differing values) ->
    re-EXECUTE: the warm binds record compiles == 0 AND no plan phase —
    parameter binding is a dict lookup plus device transfer."""
    s = _session(execution_mode=mode)
    s.sql("PREPARE pq FROM SELECT count(*) c, sum(x) v FROM t "
          "WHERE k < ? AND g = ?")
    r1 = s.sql("EXECUTE pq USING 120, 3")
    assert r1.rows == s.sql(
        "SELECT count(*) c, sum(x) v FROM t WHERE k < 120 AND g = 3").rows
    # warm: DIFFERENT parameter values, same type signature
    r2 = s.sql("EXECUTE pq USING 50, 5")
    assert r2.rows == s.sql(
        "SELECT count(*) c, sum(x) v FROM t WHERE k < 50 AND g = 5").rows
    assert r2.stats.compiles == 0
    assert r2.stats.prepared_binds == 1
    assert r2.stats.prepared_plan_hits == 1
    assert r2.stats.prepared_fallbacks == 0
    assert "plan" not in r2.stats.phase_ns  # no plan work on warm binds
    # re-EXECUTE previously seen values: still zero compiles
    r3 = s.sql("EXECUTE pq USING 120, 3")
    assert r3.stats.compiles == 0 and r3.stats.prepared_plan_hits == 1
    assert r3.rows == r1.rows
    # DEALLOCATE evicts; unknown names error cleanly
    s.sql("DEALLOCATE PREPARE pq")
    with pytest.raises(Exception, match="not found"):
        s.sql("EXECUTE pq USING 1, 1")
    with pytest.raises(Exception, match="not found"):
        s.sql("DEALLOCATE PREPARE pq")


def test_prepared_param_count_mismatch():
    s = _session()
    s.sql("PREPARE pq FROM SELECT count(*) FROM t WHERE k < ? AND g = ?")
    with pytest.raises(Exception, match="parameters"):
        s.sql("EXECUTE pq USING 1")
    with pytest.raises(Exception, match="parameters"):
        s.sql("EXECUTE pq USING 1, 2, 3")


def test_prepared_type_mismatch_errors_cleanly():
    s = _session()
    s.sql("PREPARE pq FROM SELECT count(*) FROM t WHERE x < ?")
    with pytest.raises(Exception):
        s.sql("EXECUTE pq USING 'not_a_number'")
    # the registry entry survives a failed bind
    assert s.sql("EXECUTE pq USING 3.0").rows[0][0] == 2


def test_prepared_varchar_params_fall_back_to_substitution():
    """String bindings cannot abstract to avals (device columns are
    dictionary-encoded); they take the substitution path, counted."""
    s = _session()
    s.sql("PREPARE pq FROM SELECT count(*) FROM t WHERE s = ?")
    r = s.sql("EXECUTE pq USING 'val_0007'")
    assert r.rows == [(1,)]
    assert r.stats.prepared_fallbacks == 1
    assert r.stats.prepared_binds == 0
    # quoting/escaping stays correct through the fallback
    assert s.sql("EXECUTE pq USING 'no''such'").rows == [(0,)]


def test_prepared_negative_and_date_params():
    s = _session()
    s.sql("PREPARE pq FROM SELECT count(*) FROM t WHERE k > ?")
    assert s.sql("EXECUTE pq USING -5").rows == [(200,)]
    cat = presto_tpu.connect()
    cat.catalog.register_memory(
        "d", {"dt": T.DATE},
        {"dt": np.array([0, 10_000, 20_000], dtype=np.int64)})
    cat.sql("PREPARE dq FROM SELECT count(*) FROM d WHERE dt < ?")
    r1 = cat.sql("EXECUTE dq USING DATE '1997-05-20'")  # day 10000 is 1997-05-19
    assert r1.rows == [(2,)]
    r2 = cat.sql("EXECUTE dq USING DATE '1970-01-02'")
    assert r2.rows == [(1,)] and r2.stats.compiles == 0


def test_prepared_limit_placeholder_uses_substitution():
    """`?` in a static grammar position (LIMIT) cannot stay symbolic:
    the registry marks the template subst-only and every EXECUTE
    substitutes text — correct results, value-keyed plans."""
    s = _session()
    s.sql("PREPARE pq FROM SELECT k FROM t ORDER BY k LIMIT ?")
    r = s.sql("EXECUTE pq USING 3")
    assert [x[0] for x in r.rows] == [0, 1, 2]
    assert r.stats.prepared_fallbacks == 1


def test_describe_input_infers_bound_types():
    s = _session()
    s.sql("PREPARE pq FROM SELECT k FROM t "
          "WHERE k > ? AND s LIKE ? AND x BETWEEN ? AND ?")
    rows = s.sql("DESCRIBE INPUT pq").rows
    assert rows == [(0, "bigint"), (1, "varchar"),
                    (2, "double"), (3, "double")]
    out = s.sql("DESCRIBE OUTPUT pq").rows
    assert out == [("k", "bigint")]


def test_execute_unknown_name():
    s = _session()
    with pytest.raises(Exception, match="not found"):
        s.sql("EXECUTE never_prepared USING 1")


def test_prepared_plan_value_free_across_catalog_write():
    """A catalog write bumps the version: the next EXECUTE replans
    (stale executables must not serve new data)."""
    s = _session(execution_mode="dynamic")
    s.sql("PREPARE pq FROM SELECT count(*) FROM t WHERE k < ?")
    assert s.sql("EXECUTE pq USING 100").rows == [(100,)]
    s.catalog.register_memory("u", {"a": T.BIGINT},
                              {"a": np.arange(3, dtype=np.int64)})
    r = s.sql("EXECUTE pq USING 100")  # version changed: fresh plan
    assert r.rows == [(100,)]


# ---------------------------------------------------------------------------
# result cache
# ---------------------------------------------------------------------------


def test_result_cache_unit():
    s = _session()
    rc = ResultCache(max_entries=4)
    cols = [{"name": "c", "type": "bigint"}]
    assert rc.get(s, "SELECT 1") is None
    assert rc.put(s, "SELECT 1", cols, [[1]])
    hit = rc.get(s, "SELECT 1")
    assert hit is not None and hit[1] == [[1]]
    # catalog version bump invalidates structurally (key miss)
    s.catalog.register_memory("v", {"a": T.BIGINT},
                              {"a": np.arange(2, dtype=np.int64)})
    assert rc.get(s, "SELECT 1") is None
    # volatile + non-SELECT statements never cache
    assert not rc.put(s, "SELECT now()", cols, [[1]])
    assert not rc.put(s, "INSERT INTO t VALUES (1)", cols, [[1]])
    rc.invalidate()
    assert rc.stats()["entries"] == 0


def test_result_cache_lru_and_bytes_bound():
    s = _session()
    rc = ResultCache(max_entries=2)
    cols = [{"name": "c", "type": "bigint"}]
    for i in range(4):
        rc.put(s, f"SELECT {i}", cols, [[i]])
    st = rc.stats()
    assert st["entries"] == 2 and st["evictions"] == 2
    # oversized results refuse the cache
    big = ResultCache(max_result_rows=2)
    assert not big.put(s, "SELECT 9", cols, [[1], [2], [3]])


def test_result_cache_table_scoped_invalidation():
    """Writes invalidate only the entries that reference the written
    table; everything else keeps serving (ISSUE 20 satellite)."""
    from presto_tpu.server.serving import referenced_tables, write_targets

    s = _session()
    s.catalog.register_memory("u", {"a": T.BIGINT},
                              {"a": np.arange(3, dtype=np.int64)})
    rc = ResultCache(max_entries=8)
    cols = [{"name": "c", "type": "bigint"}]
    assert rc.put(s, "SELECT count(*) FROM t", cols, [[200]])
    assert rc.put(s, "SELECT count(*) FROM u", cols, [[3]])
    assert rc.put(s, "SELECT 1", cols, [[1]])
    rc.invalidate(tables={"u"})
    assert rc.get(s, "SELECT count(*) FROM t") is not None
    assert rc.get(s, "SELECT count(*) FROM u") is None
    # provably table-free entries survive every scoped invalidation
    assert rc.get(s, "SELECT 1") is not None
    st = rc.stats()
    assert st["invalidationsScoped"] == 1
    assert st["invalidationsFull"] == 0
    rc.invalidate()  # no table set -> full clear
    assert rc.stats()["entries"] == 0
    assert rc.stats()["invalidationsFull"] == 1
    # the scoping helpers behind the cache
    assert "t" in referenced_tables("SELECT * FROM t JOIN u ON 1=1")
    assert "u" in referenced_tables("SELECT * FROM t JOIN u ON 1=1")
    assert write_targets("INSERT INTO u VALUES (1)") == frozenset({"u"})
    assert write_targets("REFRESH MATERIALIZED VIEW mv1") \
        == frozenset({"mv1"})
    assert write_targets("SELECT 1") is None


def test_result_cache_scoped_invalidation_through_server():
    """Protocol integration: a server write takes the SCOPED
    invalidation path (table set derived from the statement), not a
    full flush, and reads stay correct afterwards.  Locally the
    catalog-version cache key is the correctness backstop — the scoped
    drop is what rides the fleet broadcast so PEER coordinators (whose
    catalog version did not bump) keep serving unrelated entries."""
    s = _session()
    s.catalog.register_memory("u", {"a": T.BIGINT},
                              {"a": np.arange(3, dtype=np.int64)})
    srv = PrestoTpuServer(s).start()
    try:
        qt = "SELECT g, count(*) c FROM t GROUP BY g ORDER BY g"
        qu = "SELECT count(*) cu FROM u"
        first = connect_http(srv.uri).execute(qt).fetchall()
        connect_http(srv.uri).execute(qu).fetchall()
        connect_http(srv.uri).execute("INSERT INTO u VALUES (9)")
        info = json.loads(urllib.request.urlopen(
            f"{srv.uri}/v1/info").read())
        cache = info["serving"]["resultCache"]
        assert cache["invalidationsScoped"] >= 1
        assert cache["invalidationsFull"] == 0
        # correctness after the scoped drop: u recomputes fresh, t is
        # unchanged
        assert connect_http(srv.uri).execute(qu).fetchall() == [(4,)]
        assert connect_http(srv.uri).execute(qt).fetchall() == first
    finally:
        srv.stop()


def test_result_cache_serves_identical_query_checksum_equal():
    """Protocol integration: the identical re-submitted query serves
    from the cache with rows equal to the uncached execution."""
    s = _session()
    srv = PrestoTpuServer(s).start()
    try:
        q = "SELECT g, count(*) c, sum(x) v FROM t GROUP BY g ORDER BY g"
        first = connect_http(srv.uri).execute(q).fetchall()
        second = connect_http(srv.uri).execute(q).fetchall()
        assert first == second
        info = json.loads(urllib.request.urlopen(
            f"{srv.uri}/v1/info").read())
        assert info["serving"]["resultCache"]["hits"] >= 1
        # the cached execution shows up in history flagged as cached
        hist = json.loads(urllib.request.urlopen(
            f"{srv.uri}/v1/query").read())
        assert any(h["executionMode"] == "cached" for h in hist)
        # a write through the server invalidates explicitly
        connect_http(srv.uri).execute(
            "CREATE TABLE w AS SELECT k FROM t WHERE k < 3")
        info2 = json.loads(urllib.request.urlopen(
            f"{srv.uri}/v1/info").read())
        assert info2["serving"]["resultCache"]["invalidations"] >= 1
        third = connect_http(srv.uri).execute(q).fetchall()
        assert third == first
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_overload_queues_in_policy_order_zero_failures():
    """N sessions > the group's concurrency limit: every query
    completes, FIFO within the group, nothing fails."""
    s = _session()
    rgm = ResourceGroupManager()
    rgm.add_group("global.serve", hard_concurrency_limit=1,
                  max_queued=100)
    rgm.add_selector("global.serve")
    srv = PrestoTpuServer(s, resource_groups=rgm).start()
    results = {}
    order = []
    order_lock = threading.Lock()

    def run(i):
        cur = connect_http(srv.uri)
        cur.execute(f"SELECT count(*) FROM t WHERE k >= {i}")
        with order_lock:
            order.append(i)
        results[i] = cur.fetchall()

    try:
        threads = []
        for i in range(6):
            th = threading.Thread(target=run, args=(i,))
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=60)
        assert results == {i: [(200 - i,)] for i in range(6)}
        g = rgm._resolve("global.serve")
        assert g.total_admitted == 6 and g.total_rejected == 0
        assert g.running == 0 and g.queued == 0
    finally:
        srv.stop()


def test_shed_gets_clean_queue_full_error():
    s = _session()
    rgm = ResourceGroupManager()
    rgm.add_group("global.tiny", hard_concurrency_limit=1, max_queued=0)
    rgm.add_selector("global.tiny")
    srv = PrestoTpuServer(s, resource_groups=rgm).start()
    try:
        errors = []
        oks = []

        def run(i):
            try:
                cur = connect_http(srv.uri)
                cur.execute("SELECT count(*) FROM t, t t2 "
                            "WHERE t.k = t2.k")
                oks.append(i)
            except QueryError as e:
                errors.append(str(e))

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert oks  # at least the first one ran
        assert errors and all("Too many queued" in e for e in errors)
        info = json.loads(urllib.request.urlopen(
            f"{srv.uri}/v1/info").read())
        g = [x for x in info["resourceGroups"]
             if x["name"] == "global.tiny"][0]
        assert g["totalShed"] == len(errors)
        assert info["serving"]["shed"] == len(errors)
    finally:
        srv.stop()


def test_queue_full_error_code_in_payload():
    """The shed error carries the QUEUE_FULL code through the protocol
    payload (reference: QUERY_QUEUE_FULL error code in query JSON)."""
    s = _session()
    rgm = ResourceGroupManager()
    rgm.add_group("global.z", hard_concurrency_limit=1, max_queued=0)
    rgm.add_selector("global.z")
    srv = PrestoTpuServer(s, resource_groups=rgm).start()
    try:
        hold = rgm.acquire("u")  # saturate the group out-of-band
        job = srv.submit("SELECT 1")
        assert job.done.wait(timeout=30)
        payload = srv.results_payload(job, 0)
        assert payload["error"]["errorCode"] == "QUEUE_FULL"
        rgm.release(hold)
    finally:
        srv.stop()


def test_memory_budget_blocks_admission():
    rgm = ResourceGroupManager()
    rgm.add_group("global.m", hard_concurrency_limit=10,
                  soft_memory_limit_bytes=1 << 20)
    rgm.add_selector("global.m")
    g1 = rgm.acquire("u", memory_bytes=1 << 20)  # hits the limit
    with pytest.raises(QueryRejected):
        rgm.acquire("u", memory_bytes=1, timeout=0.1)
    rgm.release(g1, memory_bytes=1 << 20)
    g2 = rgm.acquire("u", memory_bytes=1)  # freed: admits again
    rgm.release(g2, memory_bytes=1)
    assert rgm._resolve("global.m").memory_reserved_bytes == 0


def test_admission_abort_drains_with_shutdown_code():
    rgm = ResourceGroupManager()
    rgm.add_group("global.a", hard_concurrency_limit=1, max_queued=10)
    rgm.add_selector("global.a")
    hold = rgm.acquire("u")
    flag = threading.Event()
    out = {}

    def waiter():
        try:
            rgm.acquire("u", timeout=30, abort=flag.is_set)
        except QueryRejected as e:
            out["code"] = e.code

    th = threading.Thread(target=waiter)
    th.start()
    while not rgm._resolve("global.a")._queue:
        pass
    flag.set()
    th.join(timeout=10)
    assert out.get("code") == "SERVER_SHUTTING_DOWN"
    rgm.release(hold)


# ---------------------------------------------------------------------------
# graceful shutdown drains the admission queue
# ---------------------------------------------------------------------------


class _SlowTable(MemoryTable):
    """MemoryTable whose reads block on an Event — deterministic
    long-running queries for drain tests."""

    def __init__(self, name, schema, data, gate):
        super().__init__(name, schema, data)
        self.gate = gate

    def read(self, columns=None, split=None):
        self.gate.wait(timeout=30)
        return super().read(columns, split)


def test_graceful_shutdown_cancels_queued_jobs_terminally():
    """Queued (admitted-but-not-started) jobs drain to a terminal
    CANCELED state their waiting clients can read; the running query
    completes (ISSUE 6 satellite: drain queued, not just running)."""
    gate = threading.Event()
    s = presto_tpu.connect(properties={"execution_mode": "dynamic"})
    s.catalog.register(_SlowTable(
        "slow", {"k": T.BIGINT},
        {"k": np.arange(10, dtype=np.int64)}, gate))
    rgm = ResourceGroupManager()
    rgm.add_group("global.one", hard_concurrency_limit=1, max_queued=10)
    rgm.add_selector("global.one")
    srv = PrestoTpuServer(s, resource_groups=rgm).start()
    try:
        running = StatementClient(srv.uri, "SELECT count(*) FROM slow")
        running.advance()
        run_job = srv.jobs[running.query_id]
        # wait until the first query holds the group slot
        deadline = threading.Event()
        for _ in range(200):
            if rgm._resolve("global.one").running == 1:
                break
            deadline.wait(timeout=0.02)
        queued = [StatementClient(srv.uri, f"SELECT count(*) + {i} "
                                  "FROM slow") for i in range(3)]
        for c in queued:
            c.advance()
        for _ in range(200):
            if rgm._resolve("global.one").queued == 3:
                break
            deadline.wait(timeout=0.02)
        assert rgm._resolve("global.one").queued == 3
        shut = threading.Thread(target=srv.graceful_shutdown,
                                kwargs={"timeout": 20}, daemon=True)
        shut.start()
        # queued jobs turn terminally CANCELED while the running one
        # still executes
        qjobs = [srv.jobs[c.query_id] for c in queued]
        for j in qjobs:
            assert j.done.wait(timeout=10)
            assert j.state == "CANCELED"
            assert "shutting down" in (j.error or "")
            assert j.error_code == "SERVER_SHUTTING_DOWN"
        assert run_job.state == "RUNNING"
        gate.set()  # release the running query; drain completes
        assert run_job.done.wait(timeout=20)
        assert run_job.state == "FINISHED"
        shut.join(timeout=20)
        assert not shut.is_alive()
    finally:
        gate.set()
        try:
            srv.stop()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# cluster coordinator admission
# ---------------------------------------------------------------------------


def test_cluster_coordinator_admission(monkeypatch):
    from presto_tpu.parallel.cluster import ClusterSession

    s = _session()
    rgm = ResourceGroupManager()
    rgm.add_group("global.c", hard_concurrency_limit=2)
    rgm.add_selector("global.c")
    cs = ClusterSession(s, [], resource_groups=rgm)

    class _R:
        rows = [(1,)]

    monkeypatch.setattr(ClusterSession, "_sql_attempts",
                        lambda self, text, ctx, mon=None: _R())
    cs.sql("SELECT 1")
    g = rgm._resolve("global.c")
    assert g.total_admitted == 1 and g.running == 0
    assert g.memory_reserved_bytes == 0
    st = s.last_stats
    assert st.resource_group == "global.c"
    assert st.admission_wait_ms >= 0.0


# ---------------------------------------------------------------------------
# query coalescing (ISSUE 12): vmap-batched prepared execution
# ---------------------------------------------------------------------------


def _coalesce_session(**props):
    """Session with int/double/date/decimal columns — the q6-shape
    parameter dtypes the coalescer must carry bit-identically."""
    s = presto_tpu.connect(**dict({"query_coalescing": "on",
                                   "coalesce_window_ms": 250.0}, **props))
    n = 300
    s.catalog.register_memory(
        "cq", {"k": T.BIGINT, "x": T.DOUBLE, "dt": T.DATE,
               "p": T.decimal(12, 2), "q": T.BIGINT},
        {"k": np.arange(n, dtype=np.int64),
         "x": (np.arange(n, dtype=np.float64) * 0.37) % 11.0,
         "dt": 9_000 + np.arange(n, dtype=np.int64) % 900,
         "p": (np.arange(n, dtype=np.int64) * 173) % 100_000,  # unscaled
         "q": np.arange(n, dtype=np.int64) % 50})
    return s


_COALESCE_TEMPLATE = (
    "PREPARE cq6 FROM SELECT count(*) c, sum(p * x) r, sum(q) s "
    "FROM cq WHERE dt >= ? AND x < ? AND p BETWEEN ? AND ? AND k < ?")


def _execute_concurrently(s, sqls, window_open=None):
    """Issue `sqls` from one thread each, released together through a
    barrier so they land inside one coalescing window.  Returns results
    in submission order; raises the first worker error."""
    barrier = threading.Barrier(len(sqls))
    out = [None] * len(sqls)
    errs = []

    def run(i, sql):
        try:
            barrier.wait(timeout=30)
            out[i] = s.sql(sql)
        except Exception as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    threads = [threading.Thread(target=run, args=(i, q))
               for i, q in enumerate(sqls)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    if errs:
        raise errs[0]
    return out


def test_coalesced_equivalence_across_dtypes():
    """Batched-vs-solo checksum equivalence with int, double, date, and
    decimal parameters (q6-shape): a 4-wide batch returns exactly what
    four solo executions return, every rider records the batch size,
    and the warm batch compiles nothing."""
    s = _coalesce_session()
    s.sql(_COALESCE_TEMPLATE)
    binds = [("DATE '1995-01-01'", 8.5, "10.00", "700.00", 250),
             ("DATE '1996-06-15'", 3.25, "0.05", "999.99", 300),
             ("DATE '1994-12-31'", 10.0, "250.50", "251.50", 120),
             ("DATE '1995-07-04'", 1.0, "0.01", "900.00", 77)]
    execs = [f"EXECUTE cq6 USING {d}, {x}, {lo}, {hi}, {k}"
             for d, x, lo, hi, k in binds]
    solo = []
    s.set("query_coalescing", "off")
    for e in execs:
        solo.append(s.sql(e).rows)
    s.set("query_coalescing", "on")
    batched = _execute_concurrently(s, execs)
    for r, expect in zip(batched, solo):
        assert r.rows == expect
        assert r.stats.coalesced_batch_size == 4
        assert r.stats.execution_mode == "compiled"
    # warm: a second 4-wide batch with fresh values compiles NOTHING —
    # the pow2 bucket's executable replays from the memo
    binds2 = [f"EXECUTE cq6 USING DATE '1995-03-0{i + 1}', "
              f"{2.0 + i}, 1.0{i}, 88{i}.00, {40 + i}" for i in range(4)]
    warm = _execute_concurrently(s, binds2)
    for r in warm:
        assert r.stats.compiles == 0
        assert r.stats.coalesced_batch_size == 4
    s.set("query_coalescing", "off")
    for r, e in zip(warm, binds2):
        assert r.rows == s.sql(e).rows


def test_coalesce_batch_sizes_and_pow2_padding():
    """Size 2 batches exactly; size 3 pads to the pow2 bucket (4) and a
    following size-4 batch REUSES that bucket's executable: compiles ==
    0 for every member."""
    s = _coalesce_session()
    s.sql("PREPARE pk FROM SELECT count(*) c, sum(x) v FROM cq "
          "WHERE k < ?")
    two = _execute_concurrently(
        s, ["EXECUTE pk USING 120", "EXECUTE pk USING 55"])
    assert [r.rows for r in two] == [[(120, pytest.approx(
        sum((i * 0.37) % 11.0 for i in range(120))))], [(55, pytest.approx(
            sum((i * 0.37) % 11.0 for i in range(55))))]]
    assert all(r.stats.coalesced_batch_size == 2 for r in two)
    three = _execute_concurrently(
        s, [f"EXECUTE pk USING {k}" for k in (10, 20, 30)])
    assert all(r.stats.coalesced_batch_size == 3 for r in three)
    assert [r.rows[0][0] for r in three] == [10, 20, 30]
    four = _execute_concurrently(
        s, [f"EXECUTE pk USING {k}" for k in (11, 22, 33, 44)])
    assert [r.rows[0][0] for r in four] == [11, 22, 33, 44]
    assert all(r.stats.coalesced_batch_size == 4 for r in four)
    # 3 padded to 4 built the bucket; the true 4 replays it
    assert all(r.stats.compiles == 0 for r in four)


def test_coalesce_window_timeout_runs_solo():
    """A lone EXECUTE under forced coalescing waits out the window and
    runs solo: correct rows, batch size 0, the window wait recorded."""
    s = _coalesce_session(coalesce_window_ms=40.0)
    s.sql("PREPARE pk FROM SELECT count(*) FROM cq WHERE k < ?")
    r = s.sql("EXECUTE pk USING 100")
    assert r.rows == [(100,)]
    assert r.stats.coalesced_batch_size == 0
    assert r.stats.coalesce_ms >= 30.0  # paid the (empty) window
    c = s._query_coalescer.stats()
    assert c["windowTimeouts"] >= 1 and c["batches"] == 0


def test_mixed_signatures_never_co_batch():
    """Two different prepared signatures submitted concurrently batch
    only within their own signature — the group key is the template x
    type-signature fingerprint, so cross-batching is structural."""
    s = _coalesce_session()
    s.sql("PREPARE pa FROM SELECT count(*) c FROM cq WHERE k < ?")
    s.sql("PREPARE pb FROM SELECT sum(x) v FROM cq WHERE x < ?")
    rs = _execute_concurrently(s, [
        "EXECUTE pa USING 100", "EXECUTE pb USING 5.5",
        "EXECUTE pa USING 200", "EXECUTE pb USING 2.5"])
    assert rs[0].rows == [(100,)] and rs[2].rows == [(200,)]
    exp_b = [sum(v for i in range(300)
                 if (v := (i * 0.37) % 11.0) < lim) for lim in (5.5, 2.5)]
    assert rs[1].rows[0][0] == pytest.approx(exp_b[0])
    assert rs[3].rows[0][0] == pytest.approx(exp_b[1])
    for r in rs:
        assert r.stats.coalesced_batch_size <= 2  # own signature only


def test_coalesce_leader_fault_riders_rerun_solo():
    """Chaos: an injected fault kills the batch leader's launch — every
    member re-runs solo with correct results, zero surfaced failures,
    and the fallback is counted."""
    from presto_tpu.parallel import faults as F

    s = _coalesce_session()
    s.sql("PREPARE pk FROM SELECT count(*) FROM cq WHERE k < ?")
    F.install(F.FaultPlan.parse("coalesce:BATCH:*:1:fail"))
    try:
        rs = _execute_concurrently(
            s, [f"EXECUTE pk USING {k}" for k in (60, 70, 80)])
    finally:
        F.install(None)
    assert [r.rows for r in rs] == [[(60,)], [(70,)], [(80,)]]
    assert sum(r.stats.coalesce_fallbacks for r in rs) == 3
    c = s._query_coalescer.stats()
    assert c["fallbacks"] >= 1 and c["batches"] == 0
    # the harness is gone: the next batch coalesces normally
    rs2 = _execute_concurrently(
        s, [f"EXECUTE pk USING {k}" for k in (61, 71, 81)])
    assert [r.rows for r in rs2] == [[(61,)], [(71,)], [(81,)]]
    assert all(r.stats.coalesced_batch_size == 3 for r in rs2)


def test_result_cache_hit_accounting_unchanged_under_coalescing():
    """A coalesced batch populates the result cache per-rider (keyed by
    the substituted template text), identical re-submitted EXECUTE
    values hit BEFORE joining any batch, and the hit accounting is the
    same whether coalescing is on or off."""
    s = _coalesce_session()
    tier = ServingTier(s)  # installs the result cache + backref
    s.sql("PREPARE pk FROM SELECT count(*) FROM cq WHERE k < ?")
    first = s.sql("EXECUTE pk USING 90")  # solo (window timeout), stores
    assert first.rows == [(90,)]
    assert tier.result_cache.stats()["stores"] == 1
    hit = s.sql("EXECUTE pk USING 90")
    assert hit.rows == [(90,)]
    assert hit.stats.result_cache_hit == 1
    assert hit.stats.execution_mode == "cached"
    assert tier.result_cache.stats()["hits"] == 1
    # a concurrent wave of the SAME value: every member serves from the
    # cache without forming a batch
    before = s._query_coalescer.stats()["batches"]
    rs = _execute_concurrently(s, ["EXECUTE pk USING 90"] * 3)
    assert all(r.rows == [(90,)] and r.stats.result_cache_hit == 1
               for r in rs)
    assert tier.result_cache.stats()["hits"] == 4
    assert s._query_coalescer.stats()["batches"] == before
    # a coalesced batch of DISTINCT values stores per-rider
    stores0 = tier.result_cache.stats()["stores"]
    rs = _execute_concurrently(
        s, [f"EXECUTE pk USING {k}" for k in (31, 42, 53)])
    assert [r.rows[0][0] for r in rs] == [31, 42, 53]
    assert tier.result_cache.stats()["stores"] == stores0 + 3
    # ... and each re-submission now hits without executing
    again = s.sql("EXECUTE pk USING 42")
    assert again.rows == [(42,)] and again.stats.result_cache_hit == 1
    # coalescing OFF (separate session — the cache keys on the property
    # map): the store-then-hit accounting is identical
    s2 = _coalesce_session(query_coalescing="off")
    tier2 = ServingTier(s2)
    s2.sql("PREPARE pk FROM SELECT count(*) FROM cq WHERE k < ?")
    s2.sql("EXECUTE pk USING 90")
    off = s2.sql("EXECUTE pk USING 90")
    assert off.rows == [(90,)] and off.stats.result_cache_hit == 1
    assert tier2.result_cache.stats()["stores"] == 1
    assert tier2.result_cache.stats()["hits"] == 1


def test_serving_tier_embedded_admission():
    """ServingTier.admit/release work embedded (no HTTP): the surface
    an embedded caller and the protocol server share."""
    s = _session()
    rgm = ResourceGroupManager()
    rgm.add_group("global.e", hard_concurrency_limit=1, max_queued=5)
    rgm.add_selector("global.e")
    tier = ServingTier(s, resource_groups=rgm)
    slot = tier.admit("u", "src")
    assert slot is not None and slot.group.full_name == "global.e"
    assert tier.queries_admitted == 1
    tier.release(slot, cpu_s=0.01)
    assert rgm._resolve("global.e").running == 0
    # no resource groups configured -> admission disabled, not an error
    assert ServingTier(s).admit("u") is None
