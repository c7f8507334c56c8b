"""The span helper under the dashboard's concurrency (ISSUE 26).

The shape of the benchmark's `sf10_dashboard` cell at SF0.01 on the CPU:
one server behind `global.serve` with four slots, eight client threads,
one prepared point signature, coalescing `auto`.  Every answer must equal
the solo answer, with the profiler off and inside `jax.profiler`'s trace,
and `presto_tpu_trace_errors_total` must not move.  A last case makes the
helper's own sinks raise: the query still answers and the counter moves.
"""

import sys
import threading
import time

import numpy as np
import pytest

import presto_tpu
from presto_tpu.client import StatementClient
from presto_tpu.observe import metrics as M
from presto_tpu.observe import trace as TR
from presto_tpu.server import PrestoTpuServer
from presto_tpu.server.resource_groups import ResourceGroupManager

CLIENTS = 8
LOOKUPS_PER_PHASE = 2600        # two phases: 5,200 lookups in all
POOL = 64
TIME_LIMIT_S = 240
POINT = ("SELECT count(*) c, sum(l_extendedprice) s FROM lineitem "
         "WHERE l_orderkey = ?")
GROUPS = {"groups": [{"name": "global.serve", "hardConcurrencyLimit": 4,
                      "maxQueued": 10000}],
          "selectors": [{"group": "global.serve"}]}


def trace_errors() -> float:
    M.ensure_query_metrics()
    return M.REGISTRY.get(M.TRACE_ERRORS).value()


@pytest.fixture(scope="module")
def served(tpch_catalog_tiny):
    session = presto_tpu.connect(tpch_catalog_tiny)
    session.set("float32_compute", True)
    session.set("result_cache_enabled", False)
    rgm = ResourceGroupManager()
    rgm.load_config(GROUPS)
    srv = PrestoTpuServer(session, resource_groups=rgm,
                          max_concurrent=4).start()
    try:
        list(StatementClient(srv.uri, f"PREPARE pt FROM {POINT}").rows())
        keys = [r[0] for r in session.sql(
            "SELECT o_orderkey FROM orders ORDER BY o_orderkey").rows]
        rng = np.random.default_rng(26)
        pool = [int(k) for k in rng.choice(keys, POOL, replace=False)]
        # the solo answers: one client, nothing to coalesce with
        solo = {k: [list(r) for r in StatementClient(
            srv.uri, f"EXECUTE pt USING {k}").rows()] for k in pool}
        assert all(len(v) == 1 and v[0][0] >= 1 for v in solo.values())
        yield session, srv, pool, solo
    finally:
        srv.stop()


def hammer(srv, pool, solo, n_total):
    """n_total lookups from CLIENTS closed loops -> (wrong, errors)."""
    per = n_total // CLIENTS
    wrong, errors = [], []
    deadline = time.monotonic() + TIME_LIMIT_S

    def loop(cid):
        rng = np.random.default_rng([26, cid])
        for _ in range(per):
            if time.monotonic() > deadline:
                errors.append(f"client {cid}: out of time")
                return
            k = pool[int(rng.integers(len(pool)))]
            try:
                got = [list(r) for r in StatementClient(
                    srv.uri, f"EXECUTE pt USING {k}").rows()]
            except Exception as e:  # noqa: BLE001 — counted, asserted on
                errors.append(f"{type(e).__name__}: {e}")
                continue
            if got != solo[k]:
                wrong.append((k, got, solo[k]))

    threads = [threading.Thread(target=loop, args=(i,), daemon=True)
               for i in range(CLIENTS)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)     # more interleavings per second
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=TIME_LIMIT_S + 30)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    return wrong, errors


@pytest.mark.parametrize("profiled", [False, True],
                         ids=["profiler_off", "profiler_on"])
def test_dashboard_shape_answers_as_solo(served, profiled, tmp_path):
    import jax

    session, srv, pool, solo = served
    before = trace_errors()
    coalesced0 = session._query_coalescer.riders_coalesced \
        if hasattr(session, "_query_coalescer") else 0
    if profiled:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        wrong, errors = hammer(srv, pool, solo, LOOKUPS_PER_PHASE)
    finally:
        if profiled:
            jax.profiler.stop_trace()
    assert errors == []
    assert wrong == []
    assert trace_errors() == before
    # the concurrency was real: riders did share launches
    assert session._query_coalescer.riders_coalesced > coalesced0
    if profiled:
        names = profile_span_names(tmp_path)
        for want in ("client.post", "http.post", "http.grace_wait",
                     "http.encode", "admission.wait", "prepared.bind",
                     "coalesce.window", "coalesce.ride", "execute",
                     "exec.dispatch", "exec.wait_fetch", "exec.materialize",
                     "result.rows"):
            assert "presto:" + want in names, want


def test_a_stalled_reader_keeps_its_answer(served, monkeypatch):
    """One of the eight clients sleeps before its GET (on the chip: a
    retransmitted SYN, PERF.md section 7) while the other seven keep the
    rate up, so more than MAX_DONE_JOBS lookups finish and are read in
    between: its rows are still there and equal the solo answer."""
    import presto_tpu.server.protocol as proto

    session, srv, pool, solo = served
    # no grace: every answer is fetched by a GET, as a lookup over the
    # grace is on the chip
    monkeypatch.setattr(proto, "FIRST_RESPONSE_GRACE_S", 0.0)
    stall_s, stalls_wanted = 0.5, 4
    read = [0]                  # lookups the seven have read to the end
    read_lock = threading.Lock()
    stalls, wrong, errors = [], [], []
    enough = threading.Event()
    deadline = time.monotonic() + TIME_LIMIT_S

    def lookup(k, stalled):
        c = StatementClient(srv.uri, f"EXECUTE pt USING {k}")
        c.advance()             # the POST
        got = [list(r) for r in c._current_data]
        if stalled:
            seen = read[0]
            time.sleep(stall_s)
            while (read[0] - seen <= srv.MAX_DONE_JOBS
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            stalls.append(read[0] - seen)
        got += [list(r) for r in c.rows()]
        if got != solo[k]:
            wrong.append((k, got, solo[k]))

    def loop(cid):
        rng = np.random.default_rng([32, cid])
        while not enough.is_set() and time.monotonic() < deadline:
            try:
                lookup(pool[int(rng.integers(len(pool)))], cid == 0)
            except Exception as e:  # noqa: BLE001 — counted, asserted on
                errors.append(f"client {cid}: {type(e).__name__}: {e}")
            if cid == 0:
                if len(stalls) >= stalls_wanted:
                    enough.set()
            else:
                with read_lock:
                    read[0] += 1

    threads = [threading.Thread(target=loop, args=(i,), daemon=True)
               for i in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=TIME_LIMIT_S + 30)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert wrong == []
    assert len(stalls) == stalls_wanted
    assert min(stalls) > srv.MAX_DONE_JOBS


def profile_span_names(trace_dir):
    import glob
    import os

    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert files
    names = set()
    for plane in ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("presto:"):
                    names.add(ev.name)
    return names


def test_a_failing_sink_cannot_fail_a_query(served, monkeypatch):
    session, srv, pool, solo = served

    class Boom:
        def __init__(self, *a, **kw):
            raise RuntimeError("annotation sink is broken")

    def boom(*a, **kw):
        raise RuntimeError("tracer sink is broken")

    before = trace_errors()
    with TR.span("warm"):    # the annotation class is imported on first use
        pass
    monkeypatch.setattr(TR, "_annotation", Boom)
    monkeypatch.setattr(TR.Tracer, "push", boom)
    k = pool[0]
    got = [list(r) for r in StatementClient(
        srv.uri, f"EXECUTE pt USING {k}").rows()]
    assert got == solo[k]
    # and through the embedded session, whose phases go through the helper
    r = session.sql(f"EXECUTE pt USING {k}")
    assert [list(x) for x in r.rows] == solo[k]
    assert r.stats.phase_ns.get("execute", 0) > 0
    assert trace_errors() > before
