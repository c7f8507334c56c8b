"""Server protocol + client + CLI + failure detection tests (reference
analogs: TestStatementResource / TestServer in presto-main,
TestGracefulShutdown and DistributedQueryRunner-based protocol tests in
presto-tests)."""

import json
import time
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


def test_cancel(server):
    client = StatementClient(server.uri, "SELECT count(*) FROM lineitem")
    client.advance()
    client.cancel()
    # job either finished before the cancel landed or is canceled; the
    # protocol must respond coherently either way
    job = server.jobs[client.query_id]
    job.done.wait(timeout=30)
    assert job.state in ("FINISHED", "CANCELED")


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
