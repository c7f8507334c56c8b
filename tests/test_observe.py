"""Observability tests: query stats, events, EXPLAIN ANALYZE (reference
analogs: TestQueryStats, TestEventListener, TestExplainAnalyze in
presto-main/src/test and presto-tests)."""

import pytest

import presto_tpu
from presto_tpu.observe import EventListener


@pytest.fixture()
def session(tpch_catalog_tiny):
    return presto_tpu.connect(tpch_catalog_tiny)


class Recorder(EventListener):
    def __init__(self):
        self.created = []
        self.completed = []

    def query_created(self, e):
        self.created.append(e)

    def query_completed(self, e):
        self.completed.append(e)


def test_query_events_and_stats(session):
    rec = Recorder()
    session.add_event_listener(rec)
    r = session.sql("SELECT count(*) FROM nation")
    assert len(r) == 1
    assert len(rec.created) == 1
    assert len(rec.completed) == 1
    ev = rec.completed[0]
    assert ev.state == "FINISHED"
    assert ev.query_id == rec.created[0].query_id
    st = session.last_stats
    assert st.state == "FINISHED"
    assert st.output_rows == 1
    assert st.total_ns > 0
    assert "parse" in st.phase_ns


def test_failed_query_event(session):
    rec = Recorder()
    session.add_event_listener(rec)
    with pytest.raises(Exception):
        session.sql("SELECT nosuchcol FROM nation")
    assert rec.completed[0].state == "FAILED"
    assert session.last_stats.state == "FAILED"
    assert "nosuchcol" in (session.last_stats.error or "")


def test_listener_failure_does_not_fail_query(session):
    class Bad(EventListener):
        def query_completed(self, e):
            raise RuntimeError("listener bug")

    session.add_event_listener(Bad())
    r = session.sql("SELECT count(*) FROM region")
    assert r.rows == [(5,)]


def test_explain_analyze_annotations(session):
    out = session.explain(
        "SELECT n_regionkey, count(*) c FROM nation GROUP BY n_regionkey",
        analyze=True)
    assert "rows=" in out and "time=" in out
    assert "Aggregate" in out and "TableScan" in out
    # TableScan emits all 25 nation rows; final output is 5 groups
    assert "rows=25" in out
    assert "output rows: 5" in out


def test_explain_analyze_via_sql(session):
    r = session.sql("EXPLAIN ANALYZE SELECT count(*) FROM supplier")
    text = r.rows[0][0]
    assert "rows=" in text and "Query" in text


def test_explain_analyze_records_sql_and_rows(session):
    session.explain("SELECT n_regionkey FROM nation", analyze=True)
    st = session.last_stats
    assert "SELECT n_regionkey FROM nation" in st.sql
    assert st.state == "FINISHED"
    assert st.output_rows == 25


def test_explain_analyze_failure_terminal_state(session):
    with pytest.raises(Exception):
        session.explain("SELECT nosuchcol FROM nation", analyze=True)
    assert session.last_stats.state == "FAILED"


def test_explain_analyze_sql_statement_keeps_analyzed_rowcount(session):
    session.sql("EXPLAIN ANALYZE SELECT n_regionkey FROM nation")
    assert session.last_stats.output_rows == 25


def test_explain_analyze_zero_row_query(session):
    session.sql("EXPLAIN ANALYZE SELECT n_name FROM nation WHERE n_nationkey < 0")
    assert session.last_stats.output_rows == 0


def test_history_tracks_queries(session):
    n0 = len(session.history)
    session.sql("SELECT 1")
    session.sql("SELECT 2")
    assert len(session.history) == n0 + 2
    assert session.history[-1].sql == "SELECT 2"


def test_roofline_peaks_come_from_the_device_kind(monkeypatch):
    """observe/profile: peaks are looked up by device_kind; a kind that
    is not in the table raises instead of borrowing another part's."""
    import types

    import jax

    from presto_tpu.observe import profile as OP

    monkeypatch.delenv("PRESTO_TPU_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("PRESTO_TPU_HBM_GBPS", raising=False)
    cost = {"flops": 1e9, "bytes_accessed": 1e9}
    assert OP.estimate_wall_ms(cost) == pytest.approx(50.0)  # cpu row
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    assert OP.device_peaks() == (197e12, 819e9)
    fake.device_kind = "TPU v99"
    with pytest.raises(LookupError, match="TPU v99"):
        OP.estimate_wall_ms(cost)
    monkeypatch.setenv("PRESTO_TPU_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("PRESTO_TPU_HBM_GBPS", "100")
    assert OP.estimate_wall_ms(cost) == pytest.approx(10.0)
