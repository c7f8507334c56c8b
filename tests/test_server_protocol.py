"""Server protocol + client + CLI + failure detection tests (reference
analogs: TestStatementResource / TestServer in presto-main,
TestGracefulShutdown and DistributedQueryRunner-based protocol tests in
presto-tests)."""

import json
import time
import urllib.error
import urllib.request

import pytest

import presto_tpu
from presto_tpu.client import StatementClient, connect_http
from presto_tpu.client.statement import QueryError
from presto_tpu.server import PrestoTpuServer
from presto_tpu.server.discovery import (ClusterSizeMonitor,
                                         HeartbeatFailureDetector)


@pytest.fixture(scope="module")
def server(tpch_catalog_tiny):
    s = presto_tpu.connect(tpch_catalog_tiny)
    srv = PrestoTpuServer(s).start()
    yield srv
    srv.stop()


def test_statement_roundtrip(server):
    client = StatementClient(server.uri, "SELECT count(*) FROM nation")
    rows = list(client.rows())
    assert rows == [(25,)]
    assert client.columns[0]["name"] == "count"
    assert client.stats["state"] == "FINISHED"


def test_multi_page_results(server, monkeypatch):
    import presto_tpu.server.protocol as proto

    monkeypatch.setattr(proto, "PAGE_ROWS", 100)
    client = StatementClient(
        server.uri, "SELECT c_custkey FROM customer ORDER BY c_custkey")
    rows = list(client.rows())
    assert len(rows) == 1500
    assert rows[0] == (1,) and rows[-1] == (1500,)


def test_error_propagation(server):
    client = StatementClient(server.uri, "SELECT nocol FROM nation")
    with pytest.raises(QueryError, match="nocol"):
        list(client.rows())


def test_cursor_api(server):
    cur = connect_http(server.uri)
    cur.execute("SELECT n_name FROM nation WHERE n_nationkey < 3 "
                "ORDER BY n_nationkey")
    assert cur.description[0][0] == "n_name"
    assert len(cur.fetchall()) == 3


def test_introspection_endpoints(server):
    connect_http(server.uri).execute("SELECT 1")
    with urllib.request.urlopen(f"{server.uri}/v1/query") as r:
        queries = json.loads(r.read())
    assert any(q["state"] == "FINISHED" for q in queries)
    with urllib.request.urlopen(f"{server.uri}/v1/info") as r:
        info = json.loads(r.read())
    assert info["state"] == "ACTIVE" and info["coordinator"]
    with urllib.request.urlopen(f"{server.uri}/v1/cluster") as r:
        cluster = json.loads(r.read())
    assert cluster["totalQueries"] >= 1


def test_page_refetch_is_idempotent(server, monkeypatch):
    """At-least-once delivery: re-fetching a token returns the same page."""
    import presto_tpu.server.protocol as proto

    monkeypatch.setattr(proto, "PAGE_ROWS", 10)
    client = StatementClient(server.uri,
                             "SELECT n_nationkey FROM nation ORDER BY 1")
    client.advance()  # POST
    qid = client.query_id
    assert server.jobs[qid].done.wait(timeout=30)  # page 1 needs FINISHED
    url = f"{server.uri}/v1/statement/{qid}/1"
    with urllib.request.urlopen(url) as r:
        page1 = json.loads(r.read())
    with urllib.request.urlopen(url) as r:
        page2 = json.loads(r.read())
    assert page1["data"] == page2["data"]
    # read to the end: a half-read result is kept for its client
    # (CLIENT_TIMEOUT_S) and would count among the shared server's jobs
    assert page1["nextUri"].endswith(f"{qid}/2")
    with urllib.request.urlopen(page1["nextUri"]) as r:
        assert "nextUri" not in json.loads(r.read())


def test_cancel(server):
    client = StatementClient(server.uri, "SELECT count(*) FROM lineitem")
    client.advance()
    client.cancel()
    # job either finished before the cancel landed or is canceled; the
    # protocol must respond coherently either way
    job = server.jobs[client.query_id]
    job.done.wait(timeout=30)
    assert job.state in ("FINISHED", "CANCELED")
    with urllib.request.urlopen(
            f"{server.uri}/v1/statement/{client.query_id}/0") as r:
        assert json.loads(r.read())["stats"]["state"] == job.state


def test_concurrent_queries(server):
    """Stats attach to the right job and history iteration never races
    (reference: concurrent query tests on DistributedQueryRunner)."""
    import threading

    results = {}

    def run(k):
        cur = connect_http(server.uri)
        cur.execute(f"SELECT n_nationkey + {k} FROM nation "
                    f"WHERE n_nationkey = 0")
        results[k] = cur.fetchall()

    threads = [threading.Thread(target=run, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == {k: [(k,)] for k in range(8)}


def test_done_jobs_bounded(server):
    for i in range(server.MAX_DONE_JOBS + 10):
        connect_http(server.uri).execute("SELECT 1")
    with server.jobs_lock:
        done = [j for j in server.jobs.values() if j.done.is_set()]
    assert len(done) <= server.MAX_DONE_JOBS + 1


# ---------------------------------------------------------------------------
# Retention of finished jobs (PrestoTpuServer._prune_done): a result stays
# until its client has read it, or CLIENT_TIMEOUT_S after it finished
# ---------------------------------------------------------------------------

KEYS_SQL = "SELECT n_nationkey FROM nation ORDER BY 1"  # 25 rows: 3 pages of 10


@pytest.fixture
def own_server(tpch_catalog_tiny, monkeypatch):
    """A server of the case's own (its `jobs` and `CLIENT_TIMEOUT_S` are the
    case's to set), one worker slot, ten rows a page."""
    import presto_tpu.server.protocol as proto

    monkeypatch.setattr(proto, "PAGE_ROWS", 10)
    srv = PrestoTpuServer(presto_tpu.connect(tpch_catalog_tiny),
                          max_concurrent=1).start()
    yield srv
    srv.stop()


def finish_unpolled(srv, sql):
    """A query that ran to its end and whose client has not asked yet."""
    job = srv.submit(sql)
    assert job.done.wait(timeout=30)
    return job.query_id


def get_page(srv, qid, token):
    """-> (HTTP status, payload) of one poll."""
    try:
        with urllib.request.urlopen(
                f"{srv.uri}/v1/statement/{qid}/{token}", timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, {}


def read_from(srv, qid, token):
    """The rows from page `token` to the last page."""
    rows = []
    while token is not None:
        status, page = get_page(srv, qid, token)
        assert status == 200, (qid, token, status)
        rows += [r[0] for r in page.get("data", [])]
        nxt = page.get("nextUri")
        token = int(nxt.rsplit("/", 1)[1]) if nxt else None
    return rows


def deliver(srv, n):
    """n later queries, each read to its last page."""
    for _ in range(n):
        connect_http(srv.uri).execute("SELECT 1")


def retention_unpolled(srv):
    qid = finish_unpolled(srv, KEYS_SQL)
    deliver(srv, srv.MAX_DONE_JOBS + 10)
    assert read_from(srv, qid, 0) == list(range(25))


def retention_half_read(srv):
    qid = finish_unpolled(srv, KEYS_SQL)
    status, page = get_page(srv, qid, 0)
    assert status == 200 and page["nextUri"].endswith(f"{qid}/1")
    deliver(srv, srv.MAX_DONE_JOBS + 10)
    assert read_from(srv, qid, 1) == list(range(10, 25))


def retention_abandoned(srv):
    srv.CLIENT_TIMEOUT_S = 0.0
    qid = finish_unpolled(srv, KEYS_SQL)
    deliver(srv, 1)  # the next last page served runs the rule
    assert get_page(srv, qid, 0)[0] == 404


def retention_refetch(srv):
    qid = finish_unpolled(srv, KEYS_SQL)
    assert read_from(srv, qid, 0) == list(range(25))
    deliver(srv, srv.MAX_DONE_JOBS - 1)  # still among the newest 64
    assert read_from(srv, qid, 2) == list(range(20, 25))
    deliver(srv, 1)
    assert get_page(srv, qid, 2)[0] == 404


def delivered_once_served(srv, qid, state):
    deliver(srv, srv.MAX_DONE_JOBS + 10)
    job = srv.jobs[qid]  # nobody asked: kept
    assert job.state == state and not job.delivered
    status, page = get_page(srv, qid, 0)
    assert status == 200 and page["stats"]["state"] == state
    assert job.delivered
    deliver(srv, srv.MAX_DONE_JOBS)  # now one of the delivered, and old
    assert get_page(srv, qid, 0)[0] == 404


def retention_failed(srv):
    qid = finish_unpolled(srv, "SELECT nocol FROM nation")
    delivered_once_served(srv, qid, "FAILED")


def retention_canceled(srv):
    assert srv._sema.acquire(timeout=30)  # the one slot: the job waits for it
    try:
        job = srv.submit(KEYS_SQL)
        req = urllib.request.Request(
            f"{srv.uri}/v1/statement/{job.query_id}/0", method="DELETE")
        with urllib.request.urlopen(req, timeout=30) as r:
            assert json.loads(r.read())["canceled"]
    finally:
        srv._sema.release()
    assert job.done.wait(timeout=30)
    delivered_once_served(srv, job.query_id, "CANCELED")


def retention_thousand(srv):
    deliver(srv, 1000)
    assert len(srv.jobs) <= srv.MAX_DONE_JOBS + 1


@pytest.mark.parametrize("case", [
    retention_unpolled, retention_half_read, retention_abandoned,
    retention_refetch, retention_failed, retention_canceled,
    retention_thousand], ids=lambda f: f.__name__[len("retention_"):])
def test_finished_job_retention(own_server, case):
    case(own_server)


def test_heartbeat_failure_detection(server):
    failures = []
    det = HeartbeatFailureDetector(interval=0.05,
                                   on_failure=failures.append)
    det.register(server.uri)
    det.register("http://127.0.0.1:1")  # nothing listens here
    for _ in range(30):
        det.ping_all()
    assert server.uri in det.alive_nodes()
    assert "http://127.0.0.1:1" in det.failed_nodes()
    assert "http://127.0.0.1:1" in failures
    mon = ClusterSizeMonitor(det, min_nodes=1)
    assert mon.wait_for_minimum_nodes(timeout=1.0)
    mon2 = ClusterSizeMonitor(det, min_nodes=2)
    assert not mon2.wait_for_minimum_nodes(timeout=0.2)


def test_graceful_shutdown(tpch_catalog_tiny):
    s = presto_tpu.connect(tpch_catalog_tiny)
    srv = PrestoTpuServer(s).start()
    connect_http(srv.uri).execute("SELECT 1")
    req = urllib.request.Request(f"{srv.uri}/v1/info/state",
                                 data=b'"SHUTTING_DOWN"', method="PUT")
    with urllib.request.urlopen(req) as r:
        assert json.loads(r.read())["state"] == "SHUTTING_DOWN"
    deadline = time.time() + 5
    refused = False
    while time.time() < deadline:
        try:
            connect_http(srv.uri).execute("SELECT 1")
            time.sleep(0.05)
        except Exception:
            refused = True
            break
    assert refused  # new queries refused / server stopped after drain


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_formatters():
    from presto_tpu.cli import (format_aligned, format_csv, format_json,
                                format_tsv)

    cols = ["a", "b"]
    rows = [(1, "x"), (None, "y")]
    aligned = format_aligned(cols, rows)
    assert "a" in aligned and "NULL" in aligned and "(2 rows)" in aligned
    assert format_csv(cols, rows).splitlines()[0] == "a,b"
    assert format_tsv(cols, rows).splitlines()[1] == "1\tx"
    assert json.loads(format_json(cols, rows))[0]["a"] == 1


def test_cli_execute_embedded(capsys):
    from presto_tpu.cli import main

    rc = main(["--sf", "0.01", "--execute",
               "SELECT count(*) FROM region", "--format", "CSV"])
    assert rc == 0
    out = capsys.readouterr().out
    assert out.splitlines()[0] == "count"
    assert out.splitlines()[1] == "5"


def test_cli_repl_remote(server):
    import io

    from presto_tpu.cli import RemoteBackend, repl

    out = io.StringIO()
    repl(RemoteBackend(server.uri), "CSV",
         stdin=io.StringIO("SELECT 41 + 1;\n\\q\n"), stdout=out)
    assert "42" in out.getvalue()


def test_web_ui_served(server):
    import urllib.request

    with urllib.request.urlopen(f"{server.uri}/ui") as r:
        assert r.headers["Content-Type"].startswith("text/html")
        html = r.read().decode()
    assert "presto_tpu" in html and "/v1/statement" in html


def test_query_detail_plan_and_timeline(server):
    """Round-4 UI depth (reference: webapp query.jsx/plan.jsx/
    timeline.jsx): /v1/query/{id} serves the plan pane, phase
    breakdown and wall-clock span; /v1/query rows carry the timeline
    fields."""
    import json
    import urllib.request

    client = StatementClient(
        server.uri, "SELECT n_name, count(*) c FROM customer, nation "
                    "WHERE c_nationkey = n_nationkey GROUP BY n_name "
                    "ORDER BY c DESC LIMIT 3")
    assert len(list(client.rows())) == 3
    hist = json.loads(urllib.request.urlopen(
        f"{server.uri}/v1/query").read())
    q = [x for x in hist if "n_nationkey" in (x.get("query") or "")][-1]
    assert q["createTime"] > 0 and q["endTime"] >= q["createTime"]
    detail = json.loads(urllib.request.urlopen(
        f"{server.uri}/v1/query/{q['queryId']}").read())
    assert detail["state"] == "FINISHED"
    assert "Join" in detail["planText"]  # the plan pane has a real plan
    assert "phaseMillis" in detail and detail["phaseMillis"]
    assert detail["executionMode"]
    # round-18 fusion economics block (plan/fusion_cost.py): always
    # present so the UI can render the per-edge verdict breakdown;
    # single-node runs report zeros and an empty skip map
    ff = detail["fragmentFusion"]
    assert set(ff) >= {"fragmentsFused", "edgesFused", "edgesCut",
                       "edgesMispredicted", "costMillis", "skips"}
    assert isinstance(ff["skips"], dict)
    # dynamic-filter economics, the trace-time decline count included
    assert set(detail["dynamicFilters"]) >= {
        "produced", "applied", "declined", "rowsPruned", "chunksPruned"}


def test_query_detail_node_stats_dynamic(server):
    """Per-node stats populate the detail view for dynamic runs
    (fused modes run as one XLA program by design)."""
    import json
    import urllib.request

    server.session.set("collect_node_stats", True)
    server.session.set("execution_mode", "dynamic")
    try:
        client = StatementClient(
            server.uri, "SELECT r_name, count(*) FROM region, nation "
                        "WHERE r_regionkey = n_regionkey GROUP BY r_name")
        assert len(list(client.rows())) == 5
        hist = json.loads(urllib.request.urlopen(
            f"{server.uri}/v1/query").read())
        q = [x for x in hist
             if "r_regionkey" in (x.get("query") or "")][-1]
        detail = json.loads(urllib.request.urlopen(
            f"{server.uri}/v1/query/{q['queryId']}").read())
        kinds = {n["kind"] for n in detail["nodes"]}
        assert "Join" in kinds and "Aggregate" in kinds
        assert all(n["wallMillis"] >= 0 for n in detail["nodes"])
    finally:
        server.session.set("collect_node_stats", False)
        server.session.set("execution_mode", "auto")
