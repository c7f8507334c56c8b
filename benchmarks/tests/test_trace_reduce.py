"""trace_reduce.py against a trace reduced by hand and a small recorded one.

Run by hand: python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce as R  # noqa: E402

DATA = os.path.join(HERE, "data")


@pytest.fixture
def hand():
    with open(os.path.join(DATA, "hand_trace.json")) as f:
        d = json.load(f)
    return ({p: [tuple(e) for e in evs] for p, evs in d["ops"].items()},
            [tuple(s) for s in d["spans"]])


def test_by_hand(hand):
    ops, spans = hand
    r = R.reduce_events(ops, spans)
    # the slice is first span start .. last span end: 80 .. 1500
    assert r["window_s"] == pytest.approx(1420e-9)
    # copy ends before the slice; fusion.9 is cut at 1500; fusion.1 and
    # fusion.2 overlap: [100,400] + [600,950] + [1200,1300] + [1450,1500]
    assert r["busy_s"] == pytest.approx((300 + 350 + 100 + 50) * 1e-9)
    assert r["queries"] == 2 and r["queries_by_class"] == {"q1": 1, "q6": 1}
    assert r["device_ops"][0] == ["sort.3", pytest.approx(350e-9)]
    assert dict(map(tuple, r["device_ops"]))["fusion.1"] == pytest.approx(300e-9)
    assert "copy" not in dict(map(tuple, r["device_ops"]))
    # gaps: 80-100 and 400-600 in q1; 950-1200 is 50 of q1, 150 between,
    # 50 of q6; 1300-1450 in q6
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps["in_query:q1"] == pytest.approx(270e-9)
    assert gaps["in_query:q6"] == pytest.approx(200e-9)
    assert gaps["between_queries"] == pytest.approx(150e-9)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["longest_gaps"][0] == ["between_queries", pytest.approx(250e-9)]


def test_nested_operations_keep_self_time_and_short_names():
    ops = {"/device:TPU:0": [
        ("%while.2 = (u32[], f32[8]) while(%tuple), body=%body", 0, 100),
        ("%dynamic-slice.2 = f32[8] dynamic-slice(%x)", 0, 40),
        ("%fusion.7 = f32[8] fusion(%y), kind=kLoop", 40, 90),
        ("%fusion.7 = f32[8] fusion(%y), kind=kLoop", 120, 150)]}
    r = R.reduce_events(ops, [("q", 0, 200)])
    assert r["busy_s"] == pytest.approx(130e-9)
    assert dict(map(tuple, r["device_ops"])) == {
        "fusion.7": pytest.approx(80e-9), "dynamic-slice.2": pytest.approx(40e-9),
        "while.2": pytest.approx(10e-9)}


def test_two_planes_average(hand):
    ops, spans = hand
    ops["/device:TPU:1"] = [("fusion.1", 100, 300)]
    r = R.reduce_events(ops, spans)
    assert r["busy_s"] == pytest.approx((800 + 200) / 2 * 1e-9)


def test_nothing_to_read(hand):
    ops, spans = hand
    assert R.reduce_events({}, spans) is None
    assert R.reduce_events(ops, []) is None


def test_overlapping_spans_share_a_gap():
    # two clients' queries cover the same idle stretch: it is counted once
    r = R.reduce_events({"/device:TPU:0": [("op", 0, 10), ("op", 90, 100)]},
                        [("a", 0, 100), ("b", 0, 100)])
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps == {"in_query:a": pytest.approx(40e-9),
                    "in_query:b": pytest.approx(40e-9)}


def test_recorded_trace():
    """A CPU trace of three annotated calls (jax 0.9.0): the file format is
    read, the spans are found, the op line is told from the others."""
    ops, spans, layout = R.load(
        os.path.join(DATA, "cpu_three_queries.xplane.pb"),
        device_plane="/host:CPU", op_line="tf_XLAPjRtCpuClient")
    assert [c for c, _, _ in spans] == ["q1", "q1", "q1"]
    assert list(ops) == ["/host:CPU"]
    assert any(name.startswith("dot_general") for name, _, _ in ops["/host:CPU"])
    assert not any(name.startswith("query:") for name, _, _ in ops["/host:CPU"])
    r = R.reduce_events(ops, spans)
    assert r["queries"] == 3 and 0 < r["busy_s"] <= r["window_s"]
    # with the chip's names this CPU trace holds no device operation
    assert R.load(os.path.join(DATA, "cpu_three_queries.xplane.pb"))[0] == {}
