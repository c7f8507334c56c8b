"""Compilation economics (exec/compile_cache.py): the persistent AOT
executable cache, the process-wide memo fronting every jit build, and
background compile-ahead.

Reference analog: PageFunctionCompiler's compiled-projection cache
(sql/gen/PageFunctionCompiler.java) — compile once, run many, across
queries and (via the disk cache) across processes."""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

import presto_tpu
from presto_tpu.exec import compile_cache as CC
from tests.tpch_queries import QUERIES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def norm(rows):
    return [tuple(round(v, 2) if isinstance(v, float) else v for v in r)
            for r in rows]


# ---------------------------------------------------------------------------
# same-process economics (acceptance: q3/q18 second run compiles == 0)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def compiled_session(tpch_catalog_tiny):
    return presto_tpu.connect(tpch_catalog_tiny,
                              execution_mode="compiled")


@pytest.mark.parametrize("qid", [3, 18])
def test_second_run_compiles_zero(qid, compiled_session):
    r1 = compiled_session.sql(QUERIES[qid])
    r2 = compiled_session.sql(QUERIES[qid])
    assert r2.stats.compiles == 0, \
        f"warm q{qid} rebuilt an executable: {r2.stats.compiles}"
    assert r2.stats.compile_ms == 0.0
    assert norm(r2.rows) == norm(r1.rows)


def test_q1_warm_path_stays_lean(compiled_session):
    """An old chip record once showed q1's warm time moving between
    two rounds; neither the gather-routing nor the ordering-aware
    change recompiles or re-materializes on q1's path — the current
    trace has ZERO warm compiles and (with ordering-aware grouping)
    ZERO sorts.  This test LOCKS the current lean shape: any future
    warm-path retrace or grouping sort on q1 fails tier-1."""
    compiled_session.sql(QUERIES[1])
    r = compiled_session.sql(QUERIES[1])
    assert r.stats.compiles == 0
    assert r.stats.sorts_taken == 0  # direct-gid grouping + elided sort


def test_cross_session_memo_hit(tpch_catalog_tiny, compiled_session):
    """A second session over the SAME catalog reuses the executable
    through the plan-fingerprint memo instead of retracing."""
    compiled_session.sql(QUERIES[6])  # ensure built
    s2 = presto_tpu.connect(tpch_catalog_tiny, execution_mode="compiled")
    r = s2.sql(QUERIES[6])
    assert r.stats.compiles == 0
    assert r.stats.compile_cache_hits >= 1


# ---------------------------------------------------------------------------
# memo mechanics: single-flight, ahead crediting, kill switches
# ---------------------------------------------------------------------------


def test_single_flight_builds_once():
    built = []
    done = threading.Barrier(8)

    def build():
        built.append(1)
        time.sleep(0.05)  # widen the race window
        return object()

    key = CC.fingerprint("test-single-flight", time.monotonic_ns())
    results = []

    def worker():
        done.wait()
        results.append(CC.get_or_build(key, build))

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(built) == 1, "single-flight compiled more than once"
    assert all(r is results[0] for r in results)


def test_failed_build_not_cached():
    key = CC.fingerprint("test-failed-build", time.monotonic_ns())
    calls = []

    def bad():
        calls.append(1)
        raise RuntimeError("trace failed")

    with pytest.raises(RuntimeError):
        CC.get_or_build(key, bad)
    with pytest.raises(RuntimeError):
        CC.get_or_build(key, bad)  # retried, not poisoned
    assert len(calls) == 2
    assert CC.get_or_build(key, lambda: "ok") == "ok"  # recoverable


def test_compile_ahead_hit_credited():
    key = CC.fingerprint("test-ahead-credit", time.monotonic_ns())
    assert CC.submit(lambda: CC.get_or_build(key, lambda: "v",
                                             ahead=True))
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if CC.stats()["memo_entries"] and key in CC._memo:
            break
        time.sleep(0.01)
    sink = CC.CompileStats()
    with CC.recording(sink):
        assert CC.get_or_build(key, lambda: "never") == "v"
        assert CC.get_or_build(key, lambda: "never") == "v"
    assert sink.compile_ahead_hits == 1  # credited exactly once
    assert sink.compile_cache_hits == 1  # later hits are plain hits


def test_compile_ahead_kill_switches(monkeypatch, tpch_catalog_tiny):
    s = presto_tpu.connect(tpch_catalog_tiny)
    monkeypatch.setenv("PRESTO_TPU_COMPILE_AHEAD", "on")
    assert CC.ahead_enabled(s)
    s.properties["compile_ahead"] = False  # property kills even forced-on
    assert not CC.ahead_enabled(s)
    s.properties["compile_ahead"] = True
    monkeypatch.setenv("PRESTO_TPU_COMPILE_AHEAD", "off")
    assert not CC.ahead_enabled(s)
    assert not CC.ahead_enabled(None)
    # unforced default scales with usable cores: off where a background
    # compile could only steal the query thread's core
    monkeypatch.delenv("PRESTO_TPU_COMPILE_AHEAD", raising=False)
    assert CC.ahead_enabled(s) == (CC._cores() > 1)


def test_pow2_bound_quantization():
    from presto_tpu.exec.chunked import _pow2

    assert _pow2(1) == 1
    assert _pow2(2) == 2
    assert _pow2(3) == 4
    assert _pow2(1000) == 1024
    assert _pow2(1024) == 1024
    assert _pow2(1025) == 2048
    # growth steps stay pow2: repeated misses reuse quantized shapes
    assert _pow2(_pow2(1000) * 4) == 4096


# ---------------------------------------------------------------------------
# compile-ahead never changes results (acceptance: on/off checksums)
# ---------------------------------------------------------------------------


def _chunked_session(catalog, **props):
    s = presto_tpu.connect(catalog)
    s.properties["chunked_rows_threshold"] = 10_000
    s.properties["chunk_orders"] = 5_000  # several chunks at SF0.01
    s.properties.update(props)
    return s


@pytest.mark.parametrize("qid", [
    3, pytest.param(18, marks=pytest.mark.slow)])
def test_compile_ahead_on_off_checksums_agree(qid, tpch_catalog_tiny,
                                              monkeypatch):
    monkeypatch.setenv("PRESTO_TPU_COMPILE_AHEAD", "on")  # force even 1-core
    on = _chunked_session(tpch_catalog_tiny, compile_ahead=True)
    r_on = on.sql(QUERIES[qid])
    assert r_on.stats.execution_mode == "chunked"
    monkeypatch.setenv("PRESTO_TPU_COMPILE_AHEAD", "off")  # env switch
    off = _chunked_session(tpch_catalog_tiny, compile_ahead=False)
    r_off = off.sql(QUERIES[qid])
    assert r_off.stats.execution_mode == "chunked"
    assert r_off.stats.compile_ahead_hits == 0
    assert norm(r_on.rows) == norm(r_off.rows)


@pytest.mark.slow
def test_concurrent_chunked_queries_with_compile_ahead(tpch_catalog_tiny,
                                                       monkeypatch):
    """Thread-safety hammer: two sessions run chunked queries
    concurrently while compile-ahead threads populate the shared memo —
    no crash, correct results, and the memo served both."""
    monkeypatch.setenv("PRESTO_TPU_COMPILE_AHEAD", "on")
    results = {}
    errors = []

    def run(name, qid):
        try:
            s = _chunked_session(tpch_catalog_tiny)
            results[name] = norm(s.sql(QUERIES[qid]).rows)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(f"{name}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=run, args=(f"t{i}_{qid}", qid))
               for i in range(2) for qid in (3, 18)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    ref = presto_tpu.connect(tpch_catalog_tiny)
    for name, rows in results.items():
        qid = int(name.split("_")[1])
        assert rows == norm(ref.sql(QUERIES[qid]).rows), name


# ---------------------------------------------------------------------------
# persistent cache across processes (acceptance: warmed-dir cold start)
# ---------------------------------------------------------------------------

_SUBPROC = r"""
import json, sys
sys.path.insert(0, {root!r})
import presto_tpu
from presto_tpu.catalog import tpch_catalog
from tests.tpch_queries import QUERIES

s = presto_tpu.connect(tpch_catalog(0.005, cache_dir=None),
                       execution_mode="compiled")
r = s.sql(QUERIES[3])
print(json.dumps({{"compiles": r.stats.compiles,
                  "cache_hits": r.stats.compile_cache_hits,
                  "rows": repr(r.rows)}}))
"""


def test_cache_dir_placed_from_outside_is_left_alone(monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself:
    configure() sets no directory in code and the session property and
    PRESTO_TPU_COMPILE_CACHE do not move it."""
    import jax

    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append(k), real_update(k, v)))
    monkeypatch.setattr(CC, "_configured_dir", "UNSET")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "out"))
    monkeypatch.setenv("PRESTO_TPU_COMPILE_CACHE", str(tmp_path / "ours"))
    s = presto_tpu.connect(None, compile_cache_dir=str(tmp_path / "prop"))
    assert CC.resolve_cache_dir(s) == str(tmp_path / "out")
    CC.configure(s)
    assert updates and "jax_compilation_cache_dir" not in updates
    # unset, the directory is set in code: property > env > .jax_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert CC.resolve_cache_dir(s) == str(tmp_path / "prop")
        CC.configure(s)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "prop")
        monkeypatch.delenv("PRESTO_TPU_COMPILE_CACHE")
        assert CC.resolve_cache_dir(None) == os.path.join(ROOT, ".jax_cache")
    finally:
        real_update("jax_compilation_cache_dir", before)


def test_persistent_cache_across_processes(tmp_path):
    """Two fresh subprocesses over one persistent cache dir: the first
    compiles cold into it; the second builds the same programs
    (`compiles` counts builds, loaded or not) with compile_cache_hits
    > 0 and answers the same rows — the compile bill is per MACHINE,
    not per process.  No wall time is compared: under load the second
    process can be the slower one."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               PRESTO_TPU_COMPILE_CACHE=str(tmp_path / "cc"),
               PRESTO_TPU_COMPILE_CACHE_MIN_S="0",
               PRESTO_TPU_COMPILE_AHEAD="off")
    # the child's cache is the tmp dir whatever the caller exports
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    script = _SUBPROC.format(root=ROOT)

    def run():
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, cwd=ROOT,
                             timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    r1 = run()
    r2 = run()
    assert r1["compiles"] > 0 and r1["cache_hits"] == 0
    assert r2["rows"] == r1["rows"] != "[]"
    assert r2["compiles"] == r1["compiles"]
    assert r2["cache_hits"] > 0, \
        f"warmed dir served no executables: {r2}"
