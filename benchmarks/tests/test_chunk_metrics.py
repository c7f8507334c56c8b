"""The per-layer metrics of `sf100_chunk_scan` on a trace and a run made by
hand: `chunked_share`, `chunks_per_query`, `chunk_scan_ms_per_query`,
`chunk_sync_ms_per_query`, `chunk_rows_per_s`, `chunk_agg_ms_per_query`;
each reads nothing where its source is absent, and the entries of
BENCHMARK.json say what the files say.

Run by hand: python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import importlib.util
import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import span_reduce as S  # noqa: E402
from test_span_reduce import CLIENT, DEV, WORKER, tpu_op  # noqa: E402

CELL = "sf100_chunk_scan"
MINE = ("chunked_share", "chunks_per_query", "chunk_scan_ms_per_query",
        "chunk_sync_ms_per_query", "chunk_rows_per_s", "chunk_agg_ms_per_query")


def metric(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def chunked_slice():
    """Two Q1 and one Q6, 0..3000 ns; each query two chunks, a chunk's
    generation under k:chunk_scan, then its aggregate; the host syncs once
    at the end of each loop."""
    loop = "jit(fn_s2_0123abcdc1)"
    host = {CLIENT: [("query:q1", 0, 1000), ("query:q1", 1000, 2000),
                     ("query:q6", 2000, 3000)], WORKER: []}
    ops = []
    for t0, cls in ((0, "q1"), (1000, "q1"), (2000, "q6")):
        scan = 300 if cls == "q1" else 100
        for c in range(2):
            s = t0 + 400 * c
            ops += [tpu_op(f"gen.{t0}.{c}", f"{loop}/k:chunk_scan/add", s,
                           s + scan),
                    tpu_op(f"agg.{t0}.{c}", f"{loop}/Output/Aggregate/"
                           "k:segment/reduce", s + scan, s + 380)]
        host[WORKER] += [("presto:chunk.launch", t0 + 10, t0 + 20),
                         ("presto:chunk.launch", t0 + 20, t0 + 30),
                         ("presto:chunk.sync", t0 + 30, t0 + 790)]
    return S.reduce_events({DEV: ops}, host, {})


class Run:
    """What a metric file reads, over a reduction made by hand."""

    def __init__(self, r=None, **kw):
        self.r = r
        self.trace = None
        self.__dict__.update(kw)

    def sibling(self, name):
        if name == "idle_named_share":
            return SimpleNamespace(per_query=lambda run, table, keys, cls=None:
                                   None if self.r is None
                                   else S.ms_per_query(self.r, table, keys, cls))
        return metric(name)


def test_chunk_scan_and_sync_ms_per_query():
    r = chunked_slice()
    # q1: 2 x 300 ns a query under the scope, q6: 2 x 100; the span 760 each
    assert metric("chunk_scan_ms_per_query").compute(Run(r)) \
        == pytest.approx((600e-6 + 200e-6) / 2)
    assert metric("chunk_sync_ms_per_query").compute(Run(r)) \
        == pytest.approx(760e-6)
    for name in ("chunk_scan_ms_per_query", "chunk_sync_ms_per_query"):
        assert metric(name).compute(Run(None)) is None      # not traced


@pytest.mark.parametrize("name,table,key", [
    ("chunk_scan_ms_per_query", "KERNEL_SCOPES", "k:chunk_scan"),
    ("chunk_sync_ms_per_query", "SPANS", "chunk.sync")])
def test_nothing_from_a_program_without_the_name(monkeypatch, name, table,
                                                 key):
    from presto_tpu.observe import names as NM

    older = {k: v for k, v in getattr(NM, table).items() if k != key}
    monkeypatch.setattr(NM, table, older)
    assert metric(name).compute(Run(chunked_slice())) is None


def counters(**by_mode):
    c = {f'presto_tpu_queries_total{{state="FINISHED",mode="{m}"}}': float(n)
         for m, n in by_mode.items()}

    def delta(prefix, *having, slice_only=False):
        return sum(v for k, v in c.items()
                   if k.startswith(prefix) and all(h in k for h in having))
    return delta


def test_chunked_share_is_the_chunked_queries_share():
    m = metric("chunked_share")
    assert m.compute(Run(counter_delta=counters(chunked=8))) == 1.0
    assert m.compute(Run(counter_delta=counters(chunked=6, compiled=2))) \
        == pytest.approx(0.75)
    assert m.compute(Run(counter_delta=counters())) is None


def test_chunks_per_query_is_the_mean_launches():
    m = metric("chunks_per_query")
    stats = [SimpleNamespace(stats=SimpleNamespace(chunks_run=n))
             for n in (75, 75, 150)] + [SimpleNamespace(stats=None)]
    assert m.compute(Run(good=lambda: stats)) == pytest.approx(100.0)
    assert m.compute(Run(good=lambda: stats[-1:])) is None   # none paired
    older = [SimpleNamespace(stats=SimpleNamespace())]   # no such counter
    assert m.compute(Run(good=lambda: older)) is None


def test_chunk_rows_per_s_is_the_rows_over_busy_chip_seconds():
    from presto_tpu.connectors import tpch as H

    m = metric("chunk_rows_per_s")
    reads = {"lineitem": {"l_quantity": 4}}
    workload = {"classes": [{"name": "q1", "columns_read": reads},
                            {"name": "q6", "columns_read": reads}]}
    trace = {"busy_s": 2.0, "queries_by_class": {"q1": 2, "q6": 1}}
    run = Run(trace=trace, workload=workload, sf=100, config={"chips": 1})
    assert H.row_count("lineitem", 100) == 599_979_447
    assert m.compute(run) == pytest.approx(3 * 599_979_447 / 2.0)
    run.config = {"chips": 4}
    assert m.compute(run) == pytest.approx(3 * 599_979_447 / 8.0)
    assert m.compute(Run(workload=workload, sf=100)) is None    # not traced
    workload["classes"][1]["columns_read"] = {}     # a class that does not say
    assert m.compute(run) is None


def test_chunk_agg_ms_per_query_is_the_aggregate_node():
    r = chunked_slice()
    # each chunk's aggregate 380 - scan ns: q1 2 x 80 a query, q6 2 x 280
    assert metric("chunk_agg_ms_per_query").compute(Run(r)) \
        == pytest.approx((160e-6 + 560e-6) / 2)
    assert metric("chunk_agg_ms_per_query").compute(Run(None)) is None


def test_entries_say_what_the_files_say():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in MINE:
        mod, m = metric(name), entries[name]
        assert m["workloads"] == [CELL]
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            m["name"], m["unit"], m["layer"], m["moves"])
    assert CELL in entries["segment_ms_per_query"]["workloads"]
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["chips"] == 1
    assert cells[CELL]["config"] == "tpch_sf100_chunked"
    configs = {c["name"]: c for c in bench["configs"]}
    assert configs["tpch_sf100_chunked"]["reduced"] == []
