"""The deployment `tpch_sf100_chunked` at SF0.05 on the CPU: TPC-H Q1 and
Q6 through the served path (`PrestoTpuServer`, `StatementClient`) with
`lineitem` streamed through the chunk loop (`exec/chunked.py`).

(a) in each of the loop's three modes (pipelined; the bounded accumulator,
    forced by a small `chunk_buffer_max_rows`; syncing, `chunk_pipeline`
    false) the answers equal `benchmarks/reference_tpch_chunked.py` by its
    own `rows_equal`, and `QueryStats` says what ran: mode `chunked`, one
    launch a chunk, the loop's mode, no redo; a warm run builds nothing
    and reads the same counters;
(b) that reference equals `reference.streamed` over the same slices, bit
    for bit, whatever the number of its worker processes;
(c) the loop's spans are on the query's Tracer and the span helper raised
    nothing; `k:chunk_scan` opens in the chunk programs alone, whose names
    carry the chunk mark, and a resident or a mesh program's name does not;
    a compact bound that overflows runs the loop again and is counted.
"""

import importlib.util
import json
import os
import re

import pytest

import presto_tpu
from presto_tpu.catalog import tpch_catalog
from presto_tpu.client import StatementClient
from presto_tpu.connectors import tpch as H
from presto_tpu.exec import chunked as CH
from presto_tpu.exec import compile_cache as CC
from presto_tpu.observe import metrics as M
from presto_tpu.observe import names as NM
from presto_tpu.server import PrestoTpuServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SF = 0.05
CHUNK_ORDERS = 15_000       # 75,000 orders: five chunks
NCHUNKS = len(H.chunk_grid(SF, CHUNK_ORDERS)[0]) - 1
SLICE = 10_000              # the reference's order slices at this scale

with open(os.path.join(BENCH, "configs", "tpch_sf100_chunked.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "workloads", "sf100_chunk_scan.json")) as f:
    CELL = json.load(f)
REL = CONFIG["guarantees"]["float_rel"]
CLASSES = {c["name"]: c for c in CELL["classes"]}

#: what each mode forces on each class.  Q6's partial aggregate is one row
#: a chunk: its buffer of every chunk never outgrows even the accumulator's
#: small budget, so that loop stays pipelined.
MODES = {
    "pipelined": ({}, {"q1": "pipelined", "q6": "pipelined"}),
    "accumulate": ({"chunk_buffer_max_rows": 32},
                   {"q1": "accumulate", "q6": "pipelined"}),
    "syncing": ({"chunk_pipeline": False},
                {"q1": "syncing", "q6": "syncing"}),
}


def text_of(cls):
    with open(os.path.join(BENCH, "queries",
                           CLASSES[cls]["query"] + ".sql")) as f:
        return f.read().strip()


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_tpch_chunked", os.path.join(BENCH, CELL["reference"]))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def expected(reference):
    return reference.streamed(SF, sorted(reference.STREAMED),
                              order_slice=SLICE, workers=3)


def streaming_session(**props):
    """The configuration's session at SF0.05, lineitem past the streaming
    threshold, in chunks of CHUNK_ORDERS orders."""
    s = presto_tpu.connect(tpch_catalog(SF, cache_dir=None))
    for k, v in CONFIG["session_properties"].items():
        s.set(k, v)
    s.properties.update(chunked_rows_threshold=100_000,
                        chunk_orders=CHUNK_ORDERS, **props)
    return s


def serve(session, srv, sql):
    """(rows as lists, the server's QueryStats) of one request."""
    rows = [list(r) for r in StatementClient(srv.uri, sql).rows()]
    st = [h for h in session.history_snapshot() if h.sql.strip() == sql][-1]
    return rows, st


# ---------------------------------------------------------------------------
# (a) the served path in each mode of the loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", sorted(MODES))
def test_served_answers_equal_the_reference(mode, reference, expected):
    props, loop_mode = MODES[mode]
    session = streaming_session(**props)
    srv = PrestoTpuServer(session).start()
    try:
        for cls, spec in CLASSES.items():
            sql = text_of(cls)
            for attempt in ("cold", "warm"):
                rows, st = serve(session, srv, sql)
                assert reference.rows_equal(rows, expected[spec["check"]], REL), \
                    (cls, attempt, rows[:2], expected[spec["check"]][:2])
                assert (st.execution_mode, st.fallback_reason) == ("chunked", "")
                assert st.chunks_run == NCHUNKS, (cls, attempt)
                assert st.chunk_loop_mode == loop_mode[cls], (cls, attempt)
                assert st.chunk_loop_redos == 0 and st.df_chunks_pruned == 0
            assert st.compiles == 0     # the warm pass built nothing
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# (b) the blocked reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workers", [1, 4])
def test_blocked_reference_equals_the_streamed_one(reference, workers):
    base = reference.base
    names = sorted(reference.STREAMED)
    want = base.streamed(SF, names, order_slice=SLICE)
    got = reference.streamed(SF, names, order_slice=SLICE, workers=workers)
    assert got == want      # same slices, same order of additions: same bits
    assert reference.rows_equal(got["tpch_q1"], want["tpch_q1"], rel=0.0)


def test_a_workers_running_offsets_are_the_generators(reference):
    starts = [0, 7_000, 40_000, 41_000]
    assert reference.lines_before(H, starts) \
        == [H.lineitem_offsets(s, s + 1)[0] for s in starts]


@pytest.mark.parametrize("o0,o1", [(0, 10_000), (40_000, 75_000)])
def test_a_workers_columns_are_the_generators(reference, o0, o1):
    row0, row1 = H.lineitem_offsets(o0, o1)
    got, n = reference.lineitem_columns(H, SF, o0, o1, row0)
    want = H.generate("lineitem", SF, o0, o1)
    assert n == row1 - row0 == len(want["l_shipdate"])
    for col, values in got.items():
        assert values.tolist() == want[col].tolist(), col


# ---------------------------------------------------------------------------
# (c) spans, scope, program names, counters
# ---------------------------------------------------------------------------


def module_names(texts):
    return [m for t in texts for m in re.findall(r"module @(\S+)", t)[:1]]


def mark_of(module):
    """What a program's name holds behind `fn_s<v>_` and the plan's eight
    fingerprint characters: the marks of its late scopes."""
    m = re.match(rf"jit_\w+?_s{NM.SCOPE_VERSION}_[0-9a-f]{{8}}(\w*)$", module)
    return m.group(1) if m else None


def test_chunk_loop_spans_scope_and_mark(lowered_texts):
    M.ensure_query_metrics()
    errors = M.REGISTRY.get(M.TRACE_ERRORS).value()
    CC.clear()
    session = streaming_session()
    r = session.sql(text_of("q6"))
    names = [sp["name"] for sp in r.stats.trace_spans]
    assert names.count("chunk.launch") == NCHUNKS
    assert names.count("chunk.sync") >= 1 and names.count("chunk.concat") == 1
    assert M.REGISTRY.get(M.TRACE_ERRORS).value() == errors
    loops = [t for t in lowered_texts if "k:chunk_scan" in t]
    assert loops, "no program opened k:chunk_scan"
    assert {mark_of(m) for m in module_names(loops)} == {NM.CHUNK_SCAN_MARK}


def test_resident_and_mesh_programs_keep_their_names(lowered_texts):
    resident = presto_tpu.connect(tpch_catalog(0.01, cache_dir=None),
                                  execution_mode="compiled")
    resident.set("float32_compute", True)
    mesh = presto_tpu.connect(tpch_catalog(0.01, cache_dir=None),
                              distributed=True, mesh_devices=4)
    mesh.set("float32_compute", True)
    CC.clear()
    for session, mode in ((resident, "compiled"), (mesh, "distributed")):
        assert session.sql(text_of("q1")).stats.execution_mode == mode
    assert lowered_texts
    assert not any("k:chunk_scan" in t for t in lowered_texts)
    marks = {mark_of(m) for m in module_names(lowered_texts)} - {None}
    assert marks == {""}, marks


def test_an_overflowing_compact_bound_redoes_the_loop(monkeypatch):
    """A fragment bound below a chunk's live groups trips the overflow
    flag: the loop runs again at four times the bound, and counts it."""
    sql = "SELECT l_orderkey, sum(l_quantity) FROM lineitem GROUP BY l_orderkey"
    want = streaming_session(execution_mode="compiled").sql(sql).rows
    monkeypatch.setattr(CH._FragmentRunner, "_fragment_bound",
                        lambda self, frag, grid: 4096)    # 15,000 orders a chunk
    r = streaming_session().sql(sql)
    assert sorted(r.rows) == sorted(want)
    assert r.stats.execution_mode == "chunked"
    assert r.stats.chunk_loop_redos == 1
    assert r.stats.chunks_run == 2 * NCHUNKS
