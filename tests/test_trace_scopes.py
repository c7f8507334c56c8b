"""The engine's trace vocabulary (observe/names.py): the kernel scopes are
in the programs the served queries lower to, under the plan-node scopes;
SCOPE_VERSION is in the persistent compile cache's key; the tables are what
docs/OBSERVABILITY.md quotes and what the instrumentation sites use."""

import os
import re

import pytest

import jax
import jax.numpy as jnp

import presto_tpu
from presto_tpu.exec import compile_cache as CC
from presto_tpu.observe import names as NM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
QUERIES = os.path.join(ROOT, "benchmarks", "queries")

#: query -> scopes its lowered text must hold, each under its plan node
EXPECTED = {
    "tpch_q1": ["Aggregate/k:fused_group_sums", "Aggregate/k:segment",
                "Aggregate/k:fused_group_sums.operand",
                "Filter/k:scan_filter"],
    "tpch_q3": ["Join/k:build_probe", "Join/k:take_rows.flat",
                "Aggregate/k:group_ids", "TopN/k:sort",
                "Filter/k:scan_filter"],
    "tpch_q18": ["Join/k:build_probe", "Aggregate/k:group_ids",
                 "Aggregate/k:segment", "TopN/k:sort"],
    "order_point": ["Aggregate/k:segment", "Filter/k:scan_filter"],
}


def read(name):
    with open(os.path.join(QUERIES, name + ".sql")) as f:
        return f.read().strip()


@pytest.mark.parametrize("query", sorted(EXPECTED))
def test_lowered_text_holds_the_scopes(tpch_catalog_tiny, lowered_texts,
                                       query):
    s = presto_tpu.connect(tpch_catalog_tiny, execution_mode="compiled")
    s.set("float32_compute", True)
    CC.clear()      # nothing in the process-wide memo: built, spied on
    if query == "order_point":
        s.sql(f"PREPARE pt FROM {read(query)}")
        r = s.sql("EXECUTE pt USING 1")
    else:
        r = s.sql(read(query))
    assert r.stats.execution_mode == "compiled"
    text = "\n".join(lowered_texts)
    assert f"_s{NM.SCOPE_VERSION}_" in text      # the versioned name
    for want in EXPECTED[query]:
        node, scope = want.split("/")
        assert re.search(rf"/{node}/(?:[^\"/]+/)*?{re.escape(scope)}[/\"]",
                         text), want
    for scope in set(re.findall(r"[/\"]([kx]:[\w.]+)", text)):
        assert scope in NM.KERNEL_SCOPES, scope


def test_scope_tables_resolve_a_fusions_root(tpch_catalog_tiny):
    s = presto_tpu.connect(tpch_catalog_tiny, execution_mode="compiled")
    s.set("float32_compute", True)
    s.sql(read("tpch_q1"))
    tables = CC.scope_tables()
    mine = [t for m, t in tables.items()
            if m.startswith(f"jit_fn_s{NM.SCOPE_VERSION}_")]
    assert mine
    assert any("k:fused_group_sums" in op for t in mine for op in t.values())


def test_hlo_op_names_takes_the_root_of_a_fusion_without_metadata():
    text = '''HloModule jit_fn_s1_ab, is_scheduled=true

%fused_computation (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %c = f32[] constant(2)
  ROOT %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(fn)/Output/Aggregate/k:segment/mul" stack_frame_id=2}
}

ENTRY %main.4 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %sort.0 = f32[8]{0} sort(%x.1), dimensions={0}, metadata={op_name="jit(fn)/Output/TopN/k:sort/sort" stack_frame_id=4}
  ROOT %wrapped = f32[8]{0} fusion(%sort.0), kind=kLoop, calls=%fused_computation
}
'''
    names = NM.hlo_op_names(text)
    assert names["sort.0"] == "jit(fn)/Output/TopN/k:sort/sort"
    assert names["wrapped"] == "jit(fn)/Output/Aggregate/k:segment/mul"
    assert names["mul.1"] == names["wrapped"]
    assert "p0" not in names and "c" not in names


def test_hlo_op_names_falls_back_to_what_most_of_a_fusion_carries():
    text = '''HloModule jit_fn_s2_ab

%fused_computation.3 (p0: f32[8], p1: pred[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = pred[8]{0} parameter(1)
  %mul.7 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(fn)/Output/Aggregate/Project/mul"}
  %sub.2 = f32[8]{0} subtract(%mul.7, %p0), metadata={op_name="jit(fn)/Output/Aggregate/Project/mul"}
  %neg.1 = f32[8]{0} negate(%sub.2), metadata={op_name="jit(fn)/Output/Aggregate/k:segment/neg"}
  ROOT %select.9 = f32[8]{0} select(%p1, %neg.1, %p0)
}

ENTRY %main (x: f32[8], m: pred[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %m = pred[8]{0} parameter(1)
  ROOT %multiply_select_fusion = f32[8]{0} fusion(%x, %m), kind=kLoop, calls=%fused_computation.3
}
'''
    names = NM.hlo_op_names(text)
    assert names["multiply_select_fusion"] \
        == "jit(fn)/Output/Aggregate/Project/mul"


def test_scope_version_is_a_cache_key(tmp_path, monkeypatch):
    """Two SCOPE_VERSIONs: two persistent-cache entries.  One version, two
    builds of the same program: one entry.  (The key strips debug info, so
    without the version in the function's name a program compiled under
    other scope names would be loaded for this one.)"""
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()

    def build(scope):
        def probe(x):
            with jax.named_scope(scope):   # debug info only: not in the key
                return jnp.cumsum(x * 3.0)
        jax.clear_caches()
        CC.build_jit(probe, example=(jnp.arange(64.0),))

    def entries():
        return sorted(f for f in os.listdir(tmp_path) if "probe" in f)

    try:
        build("k:sort")
        assert len(entries()) == 1
        build("k:segment")      # another vocabulary, the same version
        assert len(entries()) == 1
        monkeypatch.setattr(NM, "SCOPE_VERSION", NM.SCOPE_VERSION + 1)
        build("k:segment")
        assert len(entries()) == 2
        assert any(f"probe_s{NM.SCOPE_VERSION}-" in f for f in entries())
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


# ---------------------------------------------------------------------------
# one table each, used by the sites and quoted by the documentation
# ---------------------------------------------------------------------------


def sources():
    for base, _dirs, files in os.walk(os.path.join(ROOT, "presto_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(base, f)) as fh:
                    yield os.path.join(base, f), fh.read()


def test_every_site_uses_a_name_of_the_tables():
    spans, scopes = set(), set()
    for path, text in sources():
        if path.endswith(os.path.join("observe", "names.py")):
            continue
        spans |= set(re.findall(r'\bTR\.span\(\s*"([^"]+)"', text))
        scopes |= set(re.findall(
            r'\bNM\.(?:scoped|kernel_scope)\(\s*"([^"]+)"', text))
    phases = {"parse", "plan", "execute"}   # QueryMonitor.phase(<name>)
    assert spans | phases == set(NM.SPANS)
    assert scopes == set(NM.KERNEL_SCOPES)
    dynamic = set()
    for _path, text in sources():
        dynamic |= set(re.findall(r'\bTR\.span\(\s*f"([^"{]+)\{', text))
    assert dynamic == set(NM.DYNAMIC_SPANS)


def test_observability_doc_quotes_the_tables():
    with open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")) as f:
        doc = f.read()
    rows = dict(re.findall(r"^\| `([^`]+)` \| ([^|]+) \|", doc, re.M))
    for name, (layer, _site) in NM.SPANS.items():
        assert rows.get(name, "").strip() == layer, name
    for scope in NM.KERNEL_SCOPES:
        assert scope in rows, scope
    quoted = {k for k in rows if k.startswith(("k:", "x:"))}
    assert quoted == set(NM.KERNEL_SCOPES)
    assert f"`SCOPE_VERSION` = {NM.SCOPE_VERSION}" in doc
