"""The per-layer metrics of `ds_store_report` on a trace made by hand:
`window_ms_per_query`, `join_ms_per_query`, `ds_hbm_share`.

Run by hand: python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import importlib.util
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import span_reduce as S  # noqa: E402
from test_span_reduce import DEV, CLIENT, tpu_op  # noqa: E402


def metric(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def two_classes():
    """Two q36 and one q89, 0..3000 ns.  A q36 holds three copies of its
    join (one a grouping set) and a window whose sort is k:sort's."""
    jit = "jit(fn_s2_abcdef01w1)/Output/TopN/Union"
    host = {CLIENT: [("query:q36", 0, 1000), ("query:q36", 1000, 2000),
                     ("query:q89", 2000, 3000)]}
    ops = []
    for t0 in (0, 1000):
        for k in range(3):
            ops.append(tpu_op(f"gather.{k}", f"{jit}/Project/Window/Aggregate/"
                              "Join/gather", t0 + 100 * k, t0 + 100 * k + 80))
        ops += [tpu_op("sort.1", f"{jit}/Project/Window/k:window/k:sort/sort",
                       t0 + 400, t0 + 450),
                tpu_op("scan.1", f"{jit}/Project/Window/k:window/cumsum",
                       t0 + 450, t0 + 460)]
    ops += [tpu_op("probe.1", "jit(fn_s2_12345678w1)/Output/TopN/Filter/Window/"
                   "Aggregate/Join/k:take_rows.flat/gather", 2100, 2400),
            tpu_op("scan.2", "jit(fn_s2_12345678w1)/Output/TopN/Filter/Window/"
                   "k:window/reduce_window", 2500, 2530)]
    return S.reduce_events({DEV: ops}, host, {})


class Run:
    """What a metric file reads, over a reduction made by hand."""

    def __init__(self, r, **kw):
        self.r = r
        self.__dict__.update(kw)

    def sibling(self, name):
        assert name == "idle_named_share"
        return SimpleNamespace(per_query=lambda run, table, keys, cls=None:
                               None if self.r is None
                               else S.ms_per_query(self.r, table, keys, cls))


def test_window_ms_is_the_scope_without_its_sort():
    # q36: 10 ns a query, q89: 30 ns; the sort's 50 ns are k:sort's
    assert metric("window_ms_per_query").compute(Run(two_classes())) \
        == pytest.approx((10e-6 + 30e-6) / 2)
    assert metric("window_ms_per_query").compute(Run(None)) is None


def test_window_ms_reads_nothing_from_a_program_without_the_scope(monkeypatch):
    from presto_tpu.observe import names as NM

    scopes = {k: v for k, v in NM.KERNEL_SCOPES.items() if k != "k:window"}
    monkeypatch.setattr(NM, "KERNEL_SCOPES", scopes)
    assert metric("window_ms_per_query").compute(Run(two_classes())) is None


def test_join_ms_counts_every_copy_of_the_join():
    # q36: three copies x 80 ns a query, q89: 300 ns under Join
    assert metric("join_ms_per_query").compute(Run(two_classes())) \
        == pytest.approx((240e-6 + 300e-6) / 2)


def test_ds_hbm_share():
    m = metric("ds_hbm_share")
    trace = {"busy_s": 2.0, "queries_by_class": {"q36": 2, "q89": 1}}
    run = Run(None, trace=trace, peaks={"hbm_gbps": 100.0},
              bytes_by_class={"q36": 10e9, "q89": 20e9})
    assert m.compute(run) == pytest.approx(40e9 / (2.0 * 100e9))
    assert m.compute(Run(None, trace=None)) is None
    run.bytes_by_class = {"q36": 10e9, "q89": 0}    # a class that does not say
    assert m.compute(run) is None
