"""span_reduce.py's arithmetic on traces made by hand.

Run by hand: python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import span_reduce as S  # noqa: E402

DEV = "/device:TPU:0"
CLIENT, HANDLER, WORKER = ("/host:CPU", 0, "c"), ("/host:CPU", 1, "h"), \
    ("/host:CPU", 2, "w")


def tpu_op(instr, op_name, s, e):
    """A TPU's device event: its name is the whole HLO line."""
    meta = f', metadata={{op_name="{op_name}" stack_frame_id=3}}' \
        if op_name else ""
    return (f"%{instr} = f32[8]{{0}} fusion(%p), kind=kLoop{meta}", s, e,
            None, None)


def gaps(r):
    return dict(map(tuple, r["idle_by_span"]))


def one_request():
    """One Q1 as three threads see it, 0..1000 ns; the device runs
    100..400 and 600..650."""
    host = {
        CLIENT: [("query:q1", 0, 1000), ("presto:client.post", 10, 520),
                 ("presto:client.poll_sleep", 530, 900),
                 ("presto:client.get", 905, 990)],
        HANDLER: [("presto:http.post", 20, 510),
                  ("presto:http.grace_wait", 30, 500),
                  ("presto:http.encode", 500, 508)],
        WORKER: [("presto:admission.wait", 40, 50),
                 ("presto:parse", 50, 60),
                 ("presto:execute", 60, 700),
                 ("presto:exec.dispatch", 70, 110),
                 ("presto:exec.wait_fetch", 110, 660),
                 ("presto:exec.materialize", 660, 690),
                 ("presto:result.rows", 700, 720)],
    }
    ops = {DEV: [
        tpu_op("fusion.4", "jit(fn_s1_ab)/Output/Sort/Aggregate/"
               "k:fused_group_sums.operand/concatenate", 100, 250),
        tpu_op("Aggregate.1", "jit(fn_s1_ab)/Output/Sort/Aggregate/"
               "k:fused_group_sums/pallas_call", 250, 400),
        tpu_op("fusion.9", "jit(fn_s1_ab)/Output/Sort/Aggregate/"
               "k:group_ids/k:sort/sort", 600, 640),
        tpu_op("copy.2", None, 640, 650)]}
    return ops, host


def test_idle_goes_to_the_span_nearest_the_device():
    ops, host = one_request()
    r = S.reduce_events(ops, host, {}, top=20)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(350e-9)
    g = gaps(r)
    # 0..100: nothing 0..10, client.post 10..20, http.post 20..30,
    # http.grace_wait 30..40 (the handler's innermost), then the worker:
    # admission.wait 40..50, parse 50..60, execute 60..70 (its own, before
    # its child opens), exec.dispatch 70..100
    # (unnamed: 0..10, and at the end 900..905 and 990..1000)
    assert g["in_query:unnamed"] == pytest.approx((10 + 5 + 10) * 1e-9)
    assert g["client.post"] == pytest.approx(10e-9)
    assert g["http.post"] == pytest.approx(10e-9)
    assert g["http.grace_wait"] == pytest.approx(10e-9)
    assert g["admission.wait"] == pytest.approx(10e-9)
    assert g["parse"] == pytest.approx(10e-9)
    assert g["exec.dispatch"] == pytest.approx(30e-9)
    # 400..600 and 650..660: the worker waits for the device though the
    # handler waits its grace and the client polls: the worker wins
    assert g["exec.wait_fetch"] == pytest.approx((200 + 10) * 1e-9)
    assert g["exec.materialize"] == pytest.approx(30e-9)
    # 690..700 is execute's own again, 700..720 result.rows, then only
    # the client is left: its sleep to 900, 900..905 nothing, its GET
    assert g["execute"] == pytest.approx((10 + 10) * 1e-9)
    assert g["result.rows"] == pytest.approx(20e-9)
    assert g["client.poll_sleep"] == pytest.approx(180e-9)
    assert g["client.get"] == pytest.approx(85e-9)
    assert "between_queries" not in g
    assert sum(g.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # named: all but the catch-alls (execute, http.post, client.*) and
    # what no span covers
    catch_all = 20 + 10 + 10 + 85 + 25     # + unnamed 0..10, 900..905, 990..
    assert r["idle_in_query_s"] == pytest.approx(650e-9)
    assert r["idle_named_s"] == pytest.approx((650 - catch_all) * 1e-9)


def test_device_time_by_innermost_scope_and_plan_node():
    ops, host = one_request()
    r = S.reduce_events(ops, host, {})
    k = dict(map(tuple, r["device_by_kernel"]))
    assert k == {"k:fused_group_sums.operand": pytest.approx(150e-9),
                 "k:fused_group_sums": pytest.approx(150e-9),
                 "k:sort": pytest.approx(40e-9)}     # not k:group_ids
    assert dict(map(tuple, r["device_by_node"])) == {
        "Aggregate": pytest.approx(340e-9)}          # not Output, not Sort
    assert dict(map(tuple, r["device_unscoped"])) == {
        "copy.2": pytest.approx(10e-9)}
    assert r["scoped_s"] / r["self_s"] == pytest.approx(340 / 350)
    assert S.ms_per_query(r, "node_ns_by_class",
                          lambda s: s == "Aggregate", "q1") \
        == pytest.approx(340e-6)
    assert S.ms_per_query(r, "kernel_ns_by_class",
                          lambda s: s.startswith("k:take_rows")) == 0.0


def test_a_fusion_takes_its_roots_scope_from_the_programs_tables():
    """An event holds the module's and the instruction's names only (a
    CPU's as stats, a TPU's through the module event that runs meanwhile);
    the table made from the compiled HLO text has resolved a fusion
    without metadata to its root's op_name."""
    tables = {"jit_fn_s2_ab": {"wrapped_reduce": "jit(fn)/Output/Aggregate/"
                                                 "k:segment/reduce_sum"},
              "jit_fn_s2_cd": {"wrapped_reduce": "jit(fn)/Output/TopN/"
                                                 "k:sort/sort"}}
    ev = ("wrapped_reduce", 0, 10, "jit_fn_s2_cd", "wrapped_reduce")
    assert S.scopes_of(S.op_name_of(ev, tables)) == ("k:sort", "TopN")
    tpu = ("%wrapped_reduce = f32[] fusion(%p), kind=kLoop", 0, 10,
           "jit_fn_s2_ab", None)
    assert S.scopes_of(S.op_name_of(tpu, tables)) == ("k:segment", "Aggregate")
    # a module the engine did not build (jax's own eager programs): nothing
    assert S.op_name_of(("wrapped_reduce", 0, 10, "jit_convert", None),
                        tables) is None
    assert S.op_name_of(("wrapped_reduce", 0, 10, None, None), tables) is None


def test_spans_per_query_by_class():
    host = {
        CLIENT: [("query:q1", 0, 1000), ("presto:client.poll_sleep", 600, 900),
                 ("query:q6", 1100, 1200), ("query:q1", 1300, 2300),
                 ("presto:client.poll_sleep", 1900, 2100)],
        WORKER: [("presto:exec.dispatch", 10, 60),
                 ("presto:exec.dispatch", 1110, 1120),
                 ("presto:exec.dispatch", 1310, 1340)]}
    ops = {DEV: [tpu_op("fusion.1", None, 100, 200)]}
    r = S.reduce_events(ops, host, {})
    assert r["queries_by_class"] == {"q1": 2, "q6": 1}
    # q1: (300 + 200) / 2 queries; q6: 0; the mean over the two classes
    assert S.ms_per_query(r, "span_ns_by_class", ("client.poll_sleep",)) \
        == pytest.approx((250e-6 + 0.0) / 2)
    assert S.ms_per_query(r, "span_ns_by_class", ("exec.dispatch",)) \
        == pytest.approx(((50 + 30) / 2 * 1e-6 + 10e-6) / 2)
    assert gaps(r)["between_queries"] == pytest.approx(200e-9)


def test_a_program_without_the_vocabulary_reduces_to_zeros():
    """The parent of the PR that added the spans: only the harness's
    query annotation and XLA's own names."""
    host = {CLIENT: [("query:point", 0, 100), ("query:point", 50, 180)]}
    ops = {DEV: [("%fusion.4 = f32[8]{0} fusion(%p), kind=kLoop", 10, 60,
                  None, None)]}
    r = S.reduce_events(ops, host, {})
    assert r["idle_named_s"] == 0 and r["scoped_s"] == 0
    assert r["idle_in_query_s"] == pytest.approx(130e-9)
    assert gaps(r) == {"in_query:unnamed": pytest.approx(130e-9)}
    assert S.ms_per_query(r, "span_ns_by_class", ("exec.dispatch",)) == 0.0
    assert S.reduce_events(ops, {}, {}) is None     # no query span at all


def test_innermost_segments():
    segs = S.innermost_segments([("execute", 0, 100), ("exec.dispatch", 10, 30),
                                 ("xla_compile", 15, 20),
                                 ("exec.wait_fetch", 30, 90)])
    assert segs == [("execute", 0, 10), ("exec.dispatch", 10, 15),
                    ("xla_compile", 15, 20), ("exec.dispatch", 20, 30),
                    ("exec.wait_fetch", 30, 90), ("execute", 90, 100)]


def test_interval_arithmetic():
    assert S.intersect([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10), (20, 25)]
    assert S.subtract([(0, 10), (20, 30)], [(5, 25)]) == [(0, 5), (25, 30)]
    assert S.subtract([(0, 10)], [(2, 3), (4, 5)]) == [(0, 2), (3, 4), (5, 10)]
    assert S.rank("exec.dispatch") < S.rank("execute") < S.rank("http.encode") \
        < S.rank("client.poll_sleep") < S.rank("client.get")
    assert S.rank("coalesce.new_thing") == S.rank("coalesce.ride")


def test_a_tpus_operation_takes_the_module_that_runs_meanwhile():
    modules = [(0, 100, "jit_fn_s1_aa"), (150, 300, "jit_fn_s1_bbb2")]
    starts = [m[0] for m in modules]
    assert S.module_at(modules, starts, 0) == "jit_fn_s1_aa"
    assert S.module_at(modules, starts, 99) == "jit_fn_s1_aa"
    assert S.module_at(modules, starts, 120) is None
    assert S.module_at(modules, starts, 299) == "jit_fn_s1_bbb2"
    assert S.module_at(modules, starts, -5) is None


def test_load_reads_a_recorded_profile():
    """The CPU profile recorded for trace_reduce's test: operations with
    their module and instruction, the harness's query spans by thread."""
    ops, host = S.load(os.path.join(HERE, "data", "cpu_three_queries.xplane.pb"))
    assert list(ops) == ["/host:CPU"]
    name, s, e, module, hlo_op = ops["/host:CPU"][0]
    assert module == "jit__lambda" and hlo_op == name and e > s
    spans = [ev for evs in host.values() for ev in evs]
    assert len(spans) == 3
    assert {n for n, _, _ in spans} <= {"query:q1", "query:q6"}
    r = S.reduce_events(ops, host, {"jit__lambda": {
        "dot_general.1": "jit(f)/Aggregate/k:segment/dot_general"}})
    assert dict(map(tuple, r["device_by_kernel"])).get("k:segment", 0) > 0
