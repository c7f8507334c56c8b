"""Observability (ISSUE 9): span tracing, the cluster-wide metrics
registry + /v1/metrics Prometheus scrape, and profiled EXPLAIN ANALYZE
with XLA cost-analysis attribution in compiled/chunked/cluster modes.

Reference analogs: QueryStats/OperatorStats + the query event pipeline
and web-UI timeline (execution/QueryStats.java, webapp timeline.jsx) —
reimagined as spans + compiler-sourced attribution because fused XLA
programs have no per-operator runtime boundary."""

import json
import re
import urllib.request

import pytest

import presto_tpu
from presto_tpu.observe import metrics as M
from presto_tpu.observe import trace as TR
from tests.tpch_queries import QUERIES


@pytest.fixture()
def session(tpch_catalog_tiny):
    return presto_tpu.connect(tpch_catalog_tiny)


def _get(url: str) -> bytes:
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.read()


# ---------------------------------------------------------------------------
# span recorder units
# ---------------------------------------------------------------------------


def test_span_ids_deterministic_and_clock_free():
    """Ids come from process counters — two tracers never collide, and
    no randomness/clock feeds them (seeded chaos runs must replay
    identical id sequences)."""
    a, b = TR.Tracer(), TR.Tracer()
    assert a.trace_id != b.trace_id
    s1, s2 = a.begin("x"), a.begin("y")
    assert s1.span_id != s2.span_id
    assert s1.span_id.startswith(a.trace_id + ".")


def test_span_nesting_follows_thread_stack():
    t = TR.Tracer()
    t.begin_root("query", kind="query")
    with t.span("phase", kind="phase"):
        with t.span("inner"):
            pass
        orphan = t.begin("sibling")  # parent = phase (stack top)
        t.end(orphan)
    by = {s.name: s for s in t.spans}
    assert by["inner"].parent_id == by["phase"].span_id
    assert by["sibling"].parent_id == by["phase"].span_id
    assert by["phase"].parent_id == by["query"].span_id
    assert by["query"].parent_id == ""


def test_chrome_export_is_valid_and_laned():
    t = TR.Tracer(lane="coordinator")
    t.begin_root("query", kind="query")
    with t.span("execute", kind="phase"):
        pass
    remote = TR.Tracer(trace_id=t.trace_id, lane="worker:1234",
                       root_parent=t.root.span_id)
    sp = remote.begin_root("task t_1", kind="task")
    remote.end(sp)
    assert t.add_spans(remote.snapshot()) == 1
    ch = t.to_chrome()
    json.dumps(ch)  # JSON-serializable
    evs = ch["traceEvents"]
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"
             and e["name"] == "process_name"}
    assert {"coordinator", "worker:1234"} <= names
    assert all(e["dur"] >= 0 for e in evs if e["ph"] == "X")
    assert ch["otherData"]["traceId"] == t.trace_id


def test_foreign_trace_spans_refused_and_counted():
    t, other = TR.Tracer(), TR.Tracer()
    other.end(other.begin("task", kind="task"))
    assert t.add_spans(other.snapshot()) == 0
    assert t.dropped == 1


def test_wire_context_roundtrip_and_kill_switch(monkeypatch):
    t = TR.Tracer()
    root = t.begin_root("query", kind="query")
    with TR.activate(t):
        hdr = TR.wire_context()
        assert TR.from_wire(hdr) == (t.trace_id, root.span_id)
        monkeypatch.setenv("PRESTO_TPU_TRACE_PROPAGATION", "off")
        assert TR.wire_context() is None
    assert TR.from_wire(None) == (None, "")
    assert TR.from_wire("garbage") == (None, "")


def test_trace_detail_off_disables_recorder(session):
    session.set("trace_detail", "off")
    r = session.sql("SELECT count(*) FROM nation")
    assert r.stats.trace_id == ""
    assert r.stats.trace_spans is None
    out = session.explain("SELECT 1", analyze=True)
    assert "Trace: disabled" in out


def test_query_records_trace_spans(session):
    r = session.sql("SELECT count(*) FROM region")
    st = r.stats
    assert st.trace_id and st.trace_spans
    kinds = {d["kind"] for d in st.trace_spans}
    assert "query" in kinds and "phase" in kinds
    assert {d["trace_id"] for d in st.trace_spans} == {st.trace_id}


# ---------------------------------------------------------------------------
# metrics registry units + Prometheus text validity
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? "
    r"(-?\d+(\.\d+)?([eE][+-]?\d+)?|\+Inf|NaN)$")


def assert_valid_prometheus(text: str):
    """Minimal text-exposition validator: every non-comment line is
    `name{labels} value`, every TYPE is a known kind."""
    assert text.endswith("\n")
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE"):
            assert line.split()[3] in ("counter", "gauge", "histogram",
                                       "summary", "untyped"), line
            continue
        if line.startswith("#"):
            continue
        assert _SAMPLE_RE.match(line), f"invalid sample line: {line!r}"


def test_counter_gauge_histogram_render():
    reg = M.Registry()
    c = reg.counter("t_total", "help", ("state",))
    c.inc(state="ok")
    c.inc(2, state="bad")
    reg.gauge("t_gauge", "g").set(1.5)
    h = reg.histogram("t_hist", "h", buckets=(1, 10))
    for v in (0.5, 5, 50):
        h.observe(v)
    text = reg.render()
    assert_valid_prometheus(text)
    assert 't_total{state="bad"} 2' in text
    assert "t_gauge 1.5" in text
    assert 't_hist_bucket{le="10"} 2' in text
    assert 't_hist_bucket{le="+Inf"} 3' in text
    assert "t_hist_count 3" in text


def test_label_escaping():
    reg = M.Registry()
    reg.counter("esc_total", "x", ("q",)).inc(q='say "hi"\nnl')
    text = reg.render()
    assert_valid_prometheus(text)
    assert '\\"hi\\"' in text and "\\n" in text


# ---------------------------------------------------------------------------
# the schema-drift contract: every numeric QueryStats counter is on the
# ops surface, forever
# ---------------------------------------------------------------------------


def test_querystats_counter_fields_enumeration():
    fields = M.querystats_counter_fields()
    # spot-check one counter per subsystem rolled up so far
    for expect in ("sorts_elided", "compiles", "df_rows_pruned",
                   "fragments_fused", "prepared_binds",
                   "trace_spans_dropped", "output_rows",
                   # set-up's layers (ISSUE 38)
                   "lower_ms", "xla_build_ms", "cache_load_ms",
                   "programs_built", "data_load_ms", "data_load_bytes"):
        assert expect in fields, fields
    for excluded in ("create_time", "end_time", "sql", "state",
                     "recovery", "phase_ns", "trace_spans"):
        assert excluded not in fields


def test_every_querystats_counter_exported_by_registry():
    M.ensure_query_metrics()
    text = M.REGISTRY.render()
    assert_valid_prometheus(text)
    for f in M.querystats_counter_fields():
        assert M.query_metric_name(f) in text, \
            f"QueryStats.{f} missing from the metrics registry"


def test_coordinator_scrape_covers_querystats_schema(session):
    from presto_tpu.server.protocol import PrestoTpuServer

    server = PrestoTpuServer(session).start()
    try:
        session.sql("SELECT count(*) FROM nation")
        text = _get(f"{server.uri}/v1/metrics").decode()
        assert_valid_prometheus(text)
        for f in M.querystats_counter_fields():
            assert M.query_metric_name(f) in text, f
        assert "presto_tpu_queries_total" in text
        assert "presto_tpu_query_phase_seconds_total" in text
        assert "presto_tpu_query_recovery_total" in text
        assert "presto_tpu_query_wall_ms_bucket" in text
    finally:
        server.stop()


def test_worker_scrape_covers_querystats_schema():
    from presto_tpu.parallel import cluster as C

    w = C.WorkerServer("tpch:0.01:/tmp/presto_tpu_cache").start()
    try:
        text = _get(f"{w.url}/v1/metrics").decode()
        assert_valid_prometheus(text)
        # workers never run whole queries, but the schema is still
        # pre-registered so dashboards see one uniform surface
        for f in M.querystats_counter_fields():
            assert M.query_metric_name(f) in text, f
        # task-accounting counters ride as worker gauges
        assert "presto_tpu_worker_executed" in text
        assert "presto_tpu_worker_exchange_bytes_host" in text
    finally:
        w.stop()


def test_metrics_accumulate_query_counters(session):
    M.ensure_query_metrics()
    before = M.REGISTRY.counter(M.query_metric_name("output_rows")).value()
    session.sql("SELECT n_name FROM nation")
    after = M.REGISTRY.counter(M.query_metric_name("output_rows")).value()
    assert after == before + 25


# ---------------------------------------------------------------------------
# protocol surfaces: /v1/query/{id}/trace
# ---------------------------------------------------------------------------


def test_trace_endpoint_serves_chrome_json(session):
    from presto_tpu.server.protocol import PrestoTpuServer

    server = PrestoTpuServer(session).start()
    try:
        r = session.sql("SELECT count(*) FROM region")
        qid = r.stats.query_id
        payload = json.loads(_get(f"{server.uri}/v1/query/{qid}/trace"))
        assert payload["otherData"]["traceId"] == r.stats.trace_id
        evs = payload["traceEvents"]
        assert any(e.get("ph") == "X" and e.get("cat") == "query"
                   for e in evs)
        detail = json.loads(_get(f"{server.uri}/v1/query/{qid}"))
        assert detail["traceId"] == r.stats.trace_id
        assert detail["spanCount"] == len(r.stats.trace_spans)
        assert detail["traceUri"].endswith(f"/v1/query/{qid}/trace")
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# satellite: listener failures are counted + debug-logged once per class
# ---------------------------------------------------------------------------


def test_listener_errors_counted_and_logged_once(session, monkeypatch,
                                                 caplog):
    from presto_tpu.observe import events as EV

    class Exploding(EV.EventListener):
        def query_completed(self, e):
            raise RuntimeError("listener bug")

    monkeypatch.setenv("PRESTO_TPU_DEBUG", "1")
    EV._logged_listener_classes.discard("Exploding")
    session.add_event_listener(Exploding())
    before = M.REGISTRY.counter(
        "presto_tpu_listener_errors_total", "", ("listener",)) \
        .value(listener="Exploding")
    import logging

    with caplog.at_level(logging.WARNING, logger="presto_tpu.observe"):
        session.sql("SELECT 1")
        session.sql("SELECT 2")  # second failure: counted, NOT re-logged
    after = M.REGISTRY.counter(
        "presto_tpu_listener_errors_total", "", ("listener",)) \
        .value(listener="Exploding")
    assert after == before + 2
    logged = [r for r in caplog.records if "Exploding" in r.getMessage()]
    assert len(logged) == 1
    assert "listener bug" in logged[0].getMessage()


# ---------------------------------------------------------------------------
# satellite: the audit log carries the full current QueryStats schema
# ---------------------------------------------------------------------------


def test_audit_log_covers_current_querystats_schema(session, tmp_path):
    from presto_tpu.observe.events import FileAuditLogListener

    path = tmp_path / "audit.jsonl"
    session.add_event_listener(FileAuditLogListener(str(path), user="u"))
    session.sql("SELECT count(*) FROM nation")
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    done = [l for l in lines if l["event"] == "query_completed"]
    assert done, lines
    rec = done[-1]
    # every numeric counter — compile/df/fusion/serving/recovery era
    # fields included — is present, enumerated from the dataclass
    for f in M.querystats_counter_fields():
        assert f in rec, f"audit record missing {f}"
    assert rec["recovery"] == {}
    assert rec["phase_ms"] and "parse" in rec["phase_ms"]
    assert rec["trace_id"]


# ---------------------------------------------------------------------------
# profiled EXPLAIN ANALYZE: compiled mode (q3 + q18); chunked and
# cluster modes live in test_observability_modes.py
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def compiled_session(tpch_catalog_tiny):
    s = presto_tpu.connect(tpch_catalog_tiny)
    s.set("execution_mode", "compiled")
    return s


@pytest.mark.parametrize("qid", [3, 18])
def test_explain_analyze_compiled_attaches_cost(compiled_session, qid):
    out = compiled_session.explain(QUERIES[qid], analyze=True)
    assert "Fragment 0 (compiled" in out
    assert "wall=" in out
    assert "xla_flops=" in out and "hbm_bytes=" in out \
        and "est_wall=" in out, out
    assert "Trace: tr-" in out


def test_explain_analyze_compiled_dynamic_fallback(tpch_catalog_tiny):
    """A query whose static trace falls back must say so instead of
    attributing a program that never ran."""
    s = presto_tpu.connect(tpch_catalog_tiny)
    s.set("execution_mode", "compiled")
    # volatile query: retraces per execution, still compiled — use a
    # long-decimal shape instead, which run_compiled routes DYNAMIC
    out = s.explain(
        "SELECT CAST(n_nationkey AS DECIMAL(25,2)) d FROM nation",
        analyze=True)
    assert "DYNAMIC fallback" in out or "Fragment 0 (compiled" in out
