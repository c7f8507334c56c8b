"""Set-up's layers (ISSUE 38): JAX's compile stages booked where they run
(exec/compile_cache.py's jax.monitoring listener: lower_ms, xla_build_ms,
cache_load_ms, programs_built), the AOT stages' spans under xla_compile,
and table birth (compile_cache.data_load: data_load_ms, data_load_bytes)."""

import importlib.util
import json
import os
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import presto_tpu
from presto_tpu import types as T
from presto_tpu.catalog import tpch_catalog
from presto_tpu.exec import compile_cache as CC
from presto_tpu.observe import metrics as M

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGES = ("lower_ms", "xla_build_ms", "cache_load_ms")


@pytest.fixture()
def empty_cache(tmp_path, monkeypatch):
    """A persistent compile cache in an empty directory that keeps every
    program, restored afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("PRESTO_TPU_COMPILE_CACHE", str(tmp_path))
    monkeypatch.setenv("PRESTO_TPU_COMPILE_CACHE_MIN_S", "0")
    monkeypatch.setattr(CC, "_configured_dir", "UNSET")
    CC.configure()
    cc.reset_cache()
    try:
        yield tmp_path
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def counted(fn):
    st = CC.CompileStats()
    with CC.recording(st):
        fn()
    return st


@pytest.mark.parametrize("case", ["aot_cold", "aot_loaded", "first_call"])
def test_compile_stages_booked_where_they_run(case, empty_cache):
    """An AOT build into an empty cache is built by XLA; the same program
    after the memo is cleared is loaded; a build at its first call (no
    example) is counted with its seconds, where compile_ms has none."""
    scale = {"aot_cold": 3.0, "aot_loaded": 5.0, "first_call": 7.0}[case]

    def probe(x):
        return jnp.cumsum(jnp.sort(x) * scale)

    x = jnp.arange(64.0)
    jax.clear_caches()
    if case == "first_call":
        ex = CC.build_jit(probe)
        st = counted(lambda: ex(x).block_until_ready())
        assert st.compile_ms == 0.0
        assert st.lower_ms > 0 and st.xla_build_ms > 0
        assert st.programs_built >= 1 and st.cache_load_ms == 0
        return
    if case == "aot_loaded":
        CC.build_jit(probe, example=(x,))
        CC.clear()
        jax.clear_caches()
    st = counted(lambda: CC.build_jit(probe, example=(x,)))
    assert st.compiles == 1 and st.lower_ms > 0
    if case == "aot_cold":
        assert st.programs_built == 1 and st.xla_build_ms > 0
        assert st.cache_load_ms == 0 and st.compile_cache_hits == 0
    else:
        assert st.cache_load_ms > 0 and st.compile_cache_hits == 1
        assert st.programs_built == 0 and st.xla_build_ms == 0
    staged = sum(getattr(st, k) for k in STAGES)
    assert abs(staged - st.compile_ms) <= max(0.05 * st.compile_ms, 50.0)


def _served_columns(case):
    """-> (session, a query) for each way a table is born."""
    if case == "host_placed":
        s = presto_tpu.connect(None)
        s.catalog.register_memory(
            "birth", {"k": T.BIGINT, "v": T.DOUBLE},
            {"k": np.arange(1000), "v": np.arange(1000) * 0.5})
        return s, "SELECT count(*), sum(v) FROM birth WHERE k > 10"
    s = presto_tpu.connect(tpch_catalog(0.01, cache_dir=None))
    if case == "mesh":
        s.set("distributed", True)
        s.set("mesh_devices", 4)
    return s, "SELECT count(*), sum(l_quantity) FROM lineitem"


@pytest.mark.parametrize("case", ["device_generated", "host_placed", "mesh"])
def test_data_load_counted_at_birth_only(case):
    """A column set's first scan is table birth (timed to ready, its
    bytes); the second scan reads the resident columns and loads 0."""
    s, sql = _served_columns(case)
    first = s.sql(sql).stats
    second = s.sql(sql).stats
    if case == "mesh":
        assert first.execution_mode == second.execution_mode == \
            "distributed", first.fallback_reason
    assert first.data_load_ms > 0 and first.data_load_bytes > 0
    assert second.data_load_ms == 0 and second.data_load_bytes == 0
    assert "exec.data_load" in {sp["name"] for sp in first.trace_spans}


def _span_reduce():
    bench = os.path.join(ROOT, "benchmarks")
    sys.path.insert(0, bench)   # span_reduce imports trace_reduce
    try:
        spec = importlib.util.spec_from_file_location(
            "bench_span_reduce", os.path.join(bench, "span_reduce.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(bench)
    return mod


def test_stage_spans_nest_under_xla_compile(tpch_catalog_tiny):
    """exec.lower and exec.backend lie under xla_compile in
    /v1/query/{id}/trace, exec.backend names its program and its source;
    the profile's reduction puts both ahead of http.grace_wait; the
    listener raised nothing."""
    from presto_tpu.server.protocol import PrestoTpuServer

    M.ensure_query_metrics()
    errors = M.REGISTRY.get(M.TRACE_ERRORS).value()
    s = presto_tpu.connect(tpch_catalog_tiny, execution_mode="compiled")
    server = PrestoTpuServer(s).start()
    try:
        r = s.sql("SELECT n_regionkey, count(*) FROM nation "
                  "WHERE n_nationkey > 3 GROUP BY n_regionkey")
        url = f"{server.uri}/v1/query/{r.stats.query_id}/trace"
        with urllib.request.urlopen(url, timeout=10) as resp:
            payload = json.loads(resp.read())
    finally:
        server.stop()
    evs = [e for e in payload["traceEvents"] if e.get("ph") == "X"]
    by_id = {e["args"]["span_id"]: e for e in evs}
    compiles = [e for e in evs if e["name"] == "xla_compile"]
    assert compiles and r.stats.compiles >= 1
    for name in ("exec.lower", "exec.backend"):
        nested = [e for e in evs if e["name"] == name and by_id.get(
            e["args"].get("parent_id"), {}).get("name") == "xla_compile"]
        assert len(nested) == len(compiles), name
    backend = [e for e in evs if e["name"] == "exec.backend"
               and e["args"]["parent_id"] in by_id]
    assert all(e["args"]["source"] in ("built", "loaded") for e in backend)
    assert any(e["args"].get("program", "").startswith("jit_")
               for e in backend)
    sr = _span_reduce()
    for name in ("exec.lower", "exec.backend", "exec.data_load"):
        assert sr.rank(name) < sr.rank("http.grace_wait")
    assert M.REGISTRY.get(M.TRACE_ERRORS).value() == errors
