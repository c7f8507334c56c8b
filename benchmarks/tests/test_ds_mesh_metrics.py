"""The per-layer metrics of `ds100_mesh4_rollup` on a trace made by hand:
`ds_mesh_groupingsets_ms_per_query`, `ds_mesh_collective_ms_per_query`,
`ds_mesh_hbm_share`, and the doors to the accepted metrics whose lists name
one other cell.

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
from test_span_reduce import CLIENT, tpu_op  # noqa: E402

CELL = "ds100_mesh4_rollup"
CHIPS = ("/device:TPU:0", "/device:TPU:1")


def metric(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def mesh_slice():
    """Two q36 and one q27 on two chips, 0..3000 ns.  A q36 gathers its
    sets' states (scoped, under the node), a q27 repartitions them; both
    broadcast a dimension in a combined collective that kept no op_name,
    and the guard's all-reduce closes every program."""
    q36 = "jit(fn_s2_e8b43f43w1)/Output/TopN/Project/Window/Project/GroupingSets"
    q27 = "jit(fn_s2_948faf51)/Output/Project/TopN/Exchange/TopN/GroupingSets"
    host = {CLIENT: [("query:q36", 0, 1000), ("query:q36", 1000, 2000),
                     ("query:q27", 2000, 3000)]}
    ops = {}
    for chip in CHIPS:
        mine = ops[chip] = []
        for t0 in (0, 1000):
            mine += [
                tpu_op("all-reduce.7", None, t0 + 10, t0 + 40),         # broadcast
                tpu_op("gather.1", f"{q36}/GroupingSets/Project/Join/"
                       "k:take_rows.flat/gather", t0 + 40, t0 + 300),
                tpu_op("fusion.2", f"{q36}/GroupingSets/"
                       "k:fused_group_sums/pallas_call", t0 + 300, t0 + 400),
                tpu_op("fusion.3", f"{q36}/x:all_gather/all_gather",
                       t0 + 400, t0 + 420),
                tpu_op("fusion.4", f"{q36}/k:segment/scatter-add",
                       t0 + 420, t0 + 450),
                tpu_op("all-reduce.9", None, t0 + 450, t0 + 455)]       # guard
        mine += [
            tpu_op("all-reduce-start.1", None, 2010, 2050),
            tpu_op("gather.5", f"{q27}/GroupingSets/Project/Join/"
                   "k:take_rows.flat/gather", 2050, 2500),
            tpu_op("fusion.6", f"{q27}/GroupingSets/k:segment/scatter-add",
                   2500, 2700),
            tpu_op("fusion.7", f"{q27}/x:repartition/sort", 2700, 2760),
            tpu_op("all-to-all.2", f"{q27}/x:repartition/all_to_all",
                   2760, 2780),
            tpu_op("fusion.8", f"{q27}/k:segment/scatter-add", 2780, 2800),
            tpu_op("copy.3", None, 2800, 2810)]         # not a collective
    return ops, host


class Run:
    """What a metric file reads, over a reduction made by hand."""

    def __init__(self, r, **kw):
        self.r = r
        self.__dict__.update(kw)

    def sibling(self, name):
        if name == "idle_named_share":
            return SimpleNamespace(
                span_reduce=lambda: S,
                per_query=lambda run, table, keys, cls=None: None
                if self.r is None else S.ms_per_query(self.r, table, keys, cls))
        return metric(name)


def test_groupingsets_ms_is_partials_exchange_and_merge():
    ops, host = mesh_slice()
    r = S.reduce_events(ops, host, {})
    # q36: 100 + 20 + 30 ns a query; q27: 200 + 60 + 20 + 20; the joins
    # under the node's source are the Join's
    m = metric("ds_mesh_groupingsets_ms_per_query")
    assert m.compute(Run(r)) == pytest.approx((150e-6 + 300e-6) / 2)
    assert m.compute(Run(None)) is None
    assert metric("ds_mesh_join_ms_per_query").compute(Run(r)) \
        == pytest.approx((260e-6 + 450e-6) / 2)


def test_collective_ms_counts_scoped_and_unscoped_exchanges():
    ops, host = mesh_slice()
    m = metric("ds_mesh_collective_ms_per_query")
    # q36: 30 + 20 + 5 ns a query; q27: 40 + 60 + 20; mean over the chips
    assert m.ms_per_query(S, ops, host, {}) \
        == pytest.approx((55e-6 + 120e-6) / 2)
    # what the accepted exchange metric reads of the same slice: the scopes
    r = S.reduce_events(ops, host, {})
    assert metric("exchange_ms_per_query").compute(Run(r)) \
        == pytest.approx((20e-6 + 80e-6) / 2)
    assert m.ms_per_query(S, ops, {}, {}) is None       # no query span
    assert m.compute(Run(None, trace=None)) is None     # not traced
    assert m.is_exchange("x:all_gather", "fusion.3")
    assert not m.is_exchange("k:segment", "all-reduce.1")    # a kernel's own
    assert not m.is_exchange(None, "copy.3")


def test_hbm_share_is_a_chips_quarter():
    m = metric("ds_mesh_hbm_share")
    trace = {"busy_s": 2.0, "queries_by_class": {"q27": 2, "q36": 1}}
    run = Run(None, trace=trace, peaks={"hbm_gbps": 100.0},
              config={"chips": 4}, bytes_by_class={"q27": 40e9, "q36": 20e9})
    assert m.compute(run) == pytest.approx(100e9 / 4 / (2.0 * 100e9))
    assert 0 < m.compute(run) <= 1
    assert m.compute(Run(None, trace=None)) is None
    run.bytes_by_class = {"q27": 40e9, "q36": 0}    # a class that does not say
    assert m.compute(run) is None


def test_doors_read_what_the_accepted_metrics_read():
    counters = {
        'presto_tpu_queries_total{state="FINISHED",mode="distributed"}': 9.0,
        'presto_tpu_queries_total{state="FINISHED",mode="compiled"}': 1.0}

    def delta(prefix, *having, slice_only=False):
        return sum(v for k, v in counters.items()
                   if k.startswith(prefix) and all(h in k for h in having))

    run = Run(None, counter_delta=delta)
    assert metric("ds_mesh_distributed_share").compute(run) == pytest.approx(0.9)
    stats = [SimpleNamespace(stats=SimpleNamespace(exchange_bytes_collective=b))
             for b in (2e6, 4e6)]
    run = Run(None, with_stats=lambda: stats)
    assert metric("ds_mesh_exchange_bytes_per_query").compute(run) \
        == pytest.approx(3.0)


def test_entries_list_the_one_cell():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["name"].startswith("ds_mesh_")]
    assert len(mine) == 6 and bench["per_layer"][-6:] == mine
    for m in mine:
        mod = metric(m["name"])
        assert m["workloads"] == [CELL]
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            m["name"], m["unit"], m["layer"], m["moves"])
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["chips"] == 4
    assert bench["configs"][-1]["reduced"] == []
