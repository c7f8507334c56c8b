"""Static-analysis gate (round-4; reference: the error-prone +
checkstyle + modernizer stack in the root pom).  tools/lint.py is the
in-repo checker; the suite is red whenever it finds anything."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_lint_clean():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "lint.py"),
         os.path.join(ROOT, "presto_tpu"),
         os.path.join(ROOT, "tools")],
        capture_output=True, text=True)
    assert r.returncode == 0, f"lint findings:\n{r.stdout}"


def test_no_raw_device_sorts_outside_kernels():
    """Ordering-aware execution gate (ISSUE 3): every DEVICE sort must
    go through the routed entry points in exec/kernels.py (sort_pair /
    group_ids* / build_probe / sort_perm / argsort_stable / ...) or the
    staging sorts in exec/gather.py — those are the sites the
    executor's sort-permutation memo and the sorts_taken/sorts_elided
    accounting can see.  A raw jax.lax.sort / jnp.sort / jnp.argsort /
    jnp.lexsort anywhere else is an unrouted, unaccounted sort.  Host
    numpy sorts (np.sort over already-fetched data) are fine."""
    import ast

    ALLOWED = {os.path.join("exec", "kernels.py"),
               os.path.join("exec", "gather.py")}
    # device-array namespaces as imported across the engine
    DEVICE_NS = {"jnp", "lax"}
    FORBIDDEN_ATTRS = {"sort", "argsort", "lexsort", "sort_key_val"}
    pkg = os.path.join(ROOT, "presto_tpu")
    bad = []
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, pkg)
            if rel in ALLOWED:
                continue
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in FORBIDDEN_ATTRS):
                    continue
                base = node.func.value
                # jnp.sort(...) / lax.sort(...) / jax.lax.sort(...)
                name = base.id if isinstance(base, ast.Name) else (
                    base.attr if isinstance(base, ast.Attribute)
                    else None)
                if name in DEVICE_NS:
                    bad.append(f"{rel}:{node.lineno}: "
                               f"{name}.{node.func.attr}() — route "
                               "through exec/kernels.py")
    assert not bad, "\n".join(bad)


def test_no_raw_jax_jit_outside_compile_economics():
    """Compile-economics gate (ISSUE 4): every engine-level jax.jit
    must route through exec/compile_cache.py (build_jit)
    so XLA compiles are counted, memoized process-wide, and eligible
    for compile-ahead — the two executors (exec/chunked.py,
    exec/executor.py) are the only other modules allowed to spell
    jax.jit, for their own routed build sites.  A raw jax.jit anywhere
    else is an unaccounted compile the telemetry (QueryStats.compiles)
    and the persistent-cache economics cannot see.  Flags ANY reference
    to the attribute (calls AND partial(jax.jit, ...) uses) plus
    `from jax import jit` imports."""
    import ast

    ALLOWED = {os.path.join("exec", "chunked.py"),
               os.path.join("exec", "executor.py"),
               os.path.join("exec", "compile_cache.py")}
    pkg = os.path.join(ROOT, "presto_tpu")
    bad = []
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, pkg)
            if rel in ALLOWED:
                continue
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Attribute) \
                        and node.attr == "jit" \
                        and isinstance(node.value, ast.Name) \
                        and node.value.id == "jax":
                    bad.append(f"{rel}:{node.lineno}: jax.jit — route "
                               "through exec/compile_cache.build_jit")
                if isinstance(node, ast.ImportFrom) \
                        and node.module == "jax" \
                        and any(a.name == "jit" for a in node.names):
                    bad.append(f"{rel}:{node.lineno}: from jax import "
                               "jit — route through exec/compile_cache")
    assert not bad, "\n".join(bad)


def test_no_raw_membership_mixing_outside_kernels():
    """Dynamic-filtering gate (ISSUE 5): the runtime-filter membership
    primitives — device searchsorted probes and the splitmix64 mixing
    constants — must stay inside exec/kernels.py (rf_build / rf_probe /
    rf_summary_host and friends) on the engine's DATA PATH, so filter
    probing is routed, counted (df_filters_applied), and covered by the
    CPU-interpret equivalence tests.  Checked over the planner, storage,
    server, cluster, and executor layers; generator connectors and the
    exchange hash partitioner keep their own (pre-existing) mixing."""
    import ast

    SPLITMIX = {0x9E3779B97F4A7C15, 0xBF58476D1CE4E5B9,
                0x94D049BB133111EB}
    DEVICE_NS = {"jnp", "lax"}
    pkg = os.path.join(ROOT, "presto_tpu")
    checked = []
    for sub in ("plan", "storage", "server"):
        d = os.path.join(pkg, sub)
        checked += [os.path.join(d, f) for f in sorted(os.listdir(d))
                    if f.endswith(".py")]
    checked += [os.path.join(pkg, "parallel", f)
                for f in ("cluster.py", "faults.py", "retry.py",
                          "dist_executor.py")]
    checked += [os.path.join(pkg, "exec", f)
                for f in ("executor.py", "chunked.py", "compile_cache.py",
                          "gather.py")]
    bad = []
    for path in checked:
        rel = os.path.relpath(path, pkg)
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "searchsorted":
                base = node.func.value
                name = base.id if isinstance(base, ast.Name) else (
                    base.attr if isinstance(base, ast.Attribute) else None)
                if name in DEVICE_NS:
                    bad.append(f"{rel}:{node.lineno}: {name}.searchsorted"
                               " — route through exec/kernels.rf_probe")
            if isinstance(node, ast.Constant) \
                    and isinstance(node.value, int) \
                    and node.value in SPLITMIX:
                bad.append(f"{rel}:{node.lineno}: splitmix64 constant "
                           f"{hex(node.value)} — membership mixing "
                           "belongs in exec/kernels.py")
    assert not bad, "\n".join(bad)


def test_no_raw_vmap_outside_exec():
    """Query-coalescing gate (ISSUE 12): `jax.vmap` — the batched-
    execution primitive behind coalesced prepared EXECUTEs — is
    confined to `exec/` modules (run_compiled_batched in
    exec/executor.py is the routed entry), so every batched launch
    flows through the executable memo, the compile accounting, and the
    pow2 batch-size bucketing.  A raw vmap in the server/plan/parallel
    layers would mint unaccounted executables per batch size.  Flags
    attribute references (calls AND partial uses) plus `from jax
    import vmap` imports, same pattern as the jit rule."""
    import ast

    pkg = os.path.join(ROOT, "presto_tpu")
    bad = []
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, pkg)
            if rel.startswith("exec" + os.sep):
                continue
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Attribute) \
                        and node.attr == "vmap" \
                        and isinstance(node.value, ast.Name) \
                        and node.value.id == "jax":
                    bad.append(f"{rel}:{node.lineno}: jax.vmap — route "
                               "through exec/executor."
                               "run_compiled_batched")
                if isinstance(node, ast.ImportFrom) \
                        and node.module == "jax" \
                        and any(a.name == "vmap" for a in node.names):
                    bad.append(f"{rel}:{node.lineno}: from jax import "
                               "vmap — batched execution belongs in "
                               "exec/")
    assert not bad, "\n".join(bad)


def test_grouping_primitives_confined_to_agg_layer():
    """Adaptive-aggregation gate (ISSUE 13): the aggregation grouping
    primitives — raw `jax.ops.segment_*` scatters and the kernel-layer
    `segment_*` / `group_ids*` wrappers — are confined to the
    aggregation execution layer, so every grouping pass is routed
    (strategy-counted via agg_strategy, ratio-monitored by the partial
    bypass) and covered by the kernel equivalence tests.  Raw
    `jax.ops.segment_*` lives ONLY in exec/kernels.py; the K.* wrappers
    may be called from exec/kernels.py + exec/spill_exec.py and the
    executor-family modules that lower Aggregate/Window nodes
    (executor, dec128, window).  A grouping primitive appearing in
    plan/ server/ parallel/ storage/ would bypass the adaptive
    machinery entirely."""
    import ast

    pkg = os.path.join(ROOT, "presto_tpu")
    RAW_OK = {os.path.join("exec", "kernels.py")}
    WRAPPER_OK = RAW_OK | {
        os.path.join("exec", f) for f in
        ("spill_exec.py", "executor.py", "dec128.py", "window.py")}
    GROUPING = ("segment_", "group_ids")
    KERNEL_NS = {"K", "KK", "kernels"}
    bad = []
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, pkg)
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)):
                    continue
                attr = node.func.attr
                if not attr.startswith(GROUPING):
                    continue
                base = node.func.value
                # raw jax.ops.segment_* (ops is itself an attribute of
                # jax, or imported as a bare name)
                is_raw = (isinstance(base, ast.Attribute)
                          and base.attr == "ops") \
                    or (isinstance(base, ast.Name) and base.id == "ops")
                # kernel-layer wrapper through the conventional aliases
                is_wrapper = isinstance(base, ast.Name) \
                    and base.id in KERNEL_NS
                if is_raw and rel not in RAW_OK:
                    bad.append(f"{rel}:{node.lineno}: raw jax.ops.{attr}"
                               " — grouping scatters belong in "
                               "exec/kernels.py (use K.segment_*/"
                               "K.segment_any)")
                elif is_wrapper and rel not in WRAPPER_OK:
                    bad.append(f"{rel}:{node.lineno}: K.{attr} — "
                               "grouping belongs in the aggregation "
                               "execution layer (exec/kernels.py + "
                               "exec/spill_exec.py and the executor "
                               "family)")
    assert not bad, "\n".join(bad)


def test_no_raw_span_timing_outside_observe():
    """Observability gate (ISSUE 9): wall/span clock reads —
    `time.time()`, `time.perf_counter()`, `time.perf_counter_ns()` —
    are confined to `observe/` (trace.clock_ns / trace.wall_s are the
    routed entry points) across the engine's query-lifecycle layers,
    so every duration that can land in a span, a QueryStats field, or
    a metric flows through the same clocks the tracer uses.
    `time.monotonic()` stays allowed: the retry/deadline layer's
    budget arithmetic is not span timing.  Scope: the executors, the
    cluster/dist layers, and the server modules (PR-2's named-constant
    rule pattern); CLI/bench/verifier tooling keeps its own timers."""
    import ast

    CHECKED = [
        os.path.join("exec", f) for f in
        ("executor.py", "chunked.py", "compile_cache.py", "compiler.py",
         "gather.py", "kernels.py", "window.py", "writer.py")
    ] + [
        os.path.join("parallel", f) for f in
        ("cluster.py", "dist_executor.py", "exchange.py", "mesh.py")
    ] + [
        os.path.join("server", f) for f in
        ("protocol.py", "serving.py", "resource_groups.py",
         "discovery.py", "metastore.py")
    ]
    FORBIDDEN = {"time", "perf_counter", "perf_counter_ns"}
    pkg = os.path.join(ROOT, "presto_tpu")
    bad = []
    for rel in CHECKED:
        path = os.path.join(pkg, rel)
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) \
                    and node.attr in FORBIDDEN \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id == "time":
                bad.append(f"{rel}:{node.lineno}: time.{node.attr} — "
                           "route through observe/trace.clock_ns() / "
                           "wall_s()")
    assert not bad, "\n".join(bad)


def test_no_adhoc_write_io_outside_storage_layers():
    """Write-subsystem gate (ISSUE 10): file-creation / write I/O —
    `open(path, "w"/"wb"/"a"/"ab"/"x"/"xb")` — is confined to the
    layers that own persistence: `storage/` (encoders), `connectors/`
    (sinks + manifests), and `exec/writer.py` (the TableWriter
    orchestration).  An ad-hoc write in the plan/exec/server layers
    would bypass the PageSink staging/commit protocol (atomic manifest
    publishes, transactional snapshots) that makes engine writes safe.
    `server/metastore.py` is the metastore's OWN persistence layer and
    keeps its atomic tmp+replace writes; `memory/spill.py` is the spill
    subsystem's storage (pre-existing, cipher-wrapped)."""
    import ast

    WRITE_MODES = {"w", "wb", "a", "ab", "x", "xb", "w+", "wb+"}
    CHECKED_DIRS = ["plan", "exec", "server"]
    ALLOWED = {os.path.join("exec", "writer.py"),
               os.path.join("server", "metastore.py")}
    pkg = os.path.join(ROOT, "presto_tpu")
    bad = []
    for sub in CHECKED_DIRS:
        d = os.path.join(pkg, sub)
        for fn in sorted(os.listdir(d)):
            if not fn.endswith(".py"):
                continue
            rel = os.path.join(sub, fn)
            if rel in ALLOWED:
                continue
            with open(os.path.join(d, fn), encoding="utf-8") as f:
                tree = ast.parse(f.read(), rel)
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Name)
                        and node.func.id == "open"):
                    continue
                mode = None
                if len(node.args) > 1 and isinstance(node.args[1],
                                                     ast.Constant):
                    mode = node.args[1].value
                for kw in node.keywords:
                    if kw.arg == "mode" and isinstance(kw.value,
                                                       ast.Constant):
                        mode = kw.value.value
                if isinstance(mode, str) and mode in WRITE_MODES:
                    bad.append(
                        f"{rel}:{node.lineno}: open(..., {mode!r}) — "
                        "write I/O belongs in storage/, connectors/, or "
                        "exec/writer.py (PageSink staging/commit)")
    assert not bad, "\n".join(bad)


def test_spill_file_io_confined_to_spill_module():
    """Spill-subsystem gate (ISSUE 11, same pattern as the writer-I/O
    rule): every byte the spill tier puts on or takes off disk flows
    through `memory/spill.py` — the one module whose reads are
    checksum-verified (declared-encoding), whose writes are tracked by
    `SpillSpaceTracker`, and whose files the fault harness can damage
    deterministically.  `exec/spill_exec.py` (the degradation
    orchestrator) and the rest of `memory/` may not call `open()` at
    all, in ANY mode — an ad-hoc read there would bypass verification,
    an ad-hoc write the space accounting."""
    import ast

    CHECKED = [os.path.join("exec", "spill_exec.py"),
               os.path.join("memory", "context.py"),
               os.path.join("memory", "__init__.py")]
    pkg = os.path.join(ROOT, "presto_tpu")
    bad = []
    for rel in CHECKED:
        with open(os.path.join(pkg, rel), encoding="utf-8") as f:
            tree = ast.parse(f.read(), rel)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id == "open":
                bad.append(f"{rel}:{node.lineno}: open() — spill file "
                           "I/O belongs in memory/spill.py (checksum-"
                           "verified reads, tracked writes)")
    assert not bad, "\n".join(bad)


def test_journal_io_confined_to_journal_module():
    """Journal-subsystem gate (ISSUE 17, same pattern as the spill-I/O
    rule): every journal byte flows through `parallel/journal.py` — the
    one module whose writes are tmp+`os.replace` atomic, whose reads
    validate the entry schema, and whose ops the fault harness
    (`journal:WRITE` / `journal:READ`) can damage deterministically.
    Two checks: (a) the journal filename suffix `.qj` appears as a
    string constant ONLY in parallel/journal.py, so no other module can
    hand-roll an entry path; (b) the failover layers that CONSUME the
    journal — server/fleet.py, server/discovery.py,
    client/statement.py — may not call `open()` at all, in any mode."""
    import ast

    pkg = os.path.join(ROOT, "presto_tpu")
    bad = []
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            rel = os.path.relpath(os.path.join(dirpath, fn), pkg)
            if rel == os.path.join("parallel", "journal.py"):
                continue
            with open(os.path.join(dirpath, fn), encoding="utf-8") as f:
                tree = ast.parse(f.read(), rel)
            for node in ast.walk(tree):
                if isinstance(node, ast.Constant) \
                        and isinstance(node.value, str) \
                        and ".qj" in node.value:
                    bad.append(f"{rel}:{node.lineno}: journal suffix "
                               "'.qj' — journal paths belong to "
                               "parallel/journal.py")
    CHECKED = [os.path.join("server", "fleet.py"),
               os.path.join("server", "discovery.py"),
               os.path.join("client", "statement.py")]
    for rel in CHECKED:
        with open(os.path.join(pkg, rel), encoding="utf-8") as f:
            tree = ast.parse(f.read(), rel)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id == "open":
                bad.append(f"{rel}:{node.lineno}: open() — journal "
                           "file I/O belongs in parallel/journal.py "
                           "(atomic writes, schema-validated reads)")
    assert not bad, "\n".join(bad)


def test_no_sleeps_or_timeout_literals_in_spill_exec():
    """The degradation orchestrator is driven by memory pressure and
    deterministic knobs, never by wall-clock waits: no `time.sleep`, no
    hard-coded `timeout=` literals (the parallel-package rule, applied
    to the new module)."""
    import ast

    path = os.path.join(ROOT, "presto_tpu", "exec", "spill_exec.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    bad = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if isinstance(func, ast.Attribute) and func.attr == "sleep":
            bad.append(f"exec/spill_exec.py:{node.lineno}: sleep()")
        for kw in node.keywords:
            if kw.arg == "timeout" and isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, (int, float)):
                bad.append(f"exec/spill_exec.py:{kw.value.lineno}: "
                           f"hard-coded timeout={kw.value.value!r}")
    assert not bad, "\n".join(bad)


def test_fusion_cost_constants_confined_to_fusion_cost():
    """Fragment-fusion-economics gate (ISSUE 14): the calibrated
    exchange-roofline constants and profile reads live ONLY in
    plan/fusion_cost.py — distribute.py and cluster.py consume per-edge
    VERDICTS (decide_edges / fuse_fragments), never prices.  Forbidden
    elsewhere in the package: reads of the PRESTO_TPU_FUSION_PROFILE
    env var or the `fusion_profile` session property (session.py only
    REGISTERS the knob's default), and any reference to the pricing
    fields/methods (host_ms_per_mb, coll_ms_per_mb, serial_ms, cut_ms,
    fused_base_ms, ...) — a magic bandwidth number in the planner or
    the coordinator would fork the model."""
    import ast

    ALLOWED = {os.path.join("plan", "fusion_cost.py")}
    # session.py's defaults dict registers the knob name; that is not a
    # profile READ
    REGISTER_OK = {"session.py"}
    FORBIDDEN_STRINGS = {"PRESTO_TPU_FUSION_PROFILE", "fusion_profile"}
    FORBIDDEN_ATTRS = {"host_edge_ms", "host_ms_per_mb", "coll_edge_ms",
                       "coll_ms_per_mb", "serial_ms", "serial_free",
                       "cut_ms", "fused_base_ms", "serial_penalty_ms",
                       "dcn_edge_ms", "dcn_ms_per_mb",
                       "DEFAULT_PROFILES"}
    pkg = os.path.join(ROOT, "presto_tpu")
    bad = []
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, pkg)
            if rel in ALLOWED:
                continue
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Constant) \
                        and isinstance(node.value, str) \
                        and node.value in FORBIDDEN_STRINGS \
                        and rel not in REGISTER_OK:
                    bad.append(f"{rel}:{node.lineno}: {node.value!r} — "
                               "profile reads belong in "
                               "plan/fusion_cost.load_profile")
                if isinstance(node, ast.Attribute) \
                        and node.attr in FORBIDDEN_ATTRS:
                    bad.append(f"{rel}:{node.lineno}: .{node.attr} — "
                               "fusion pricing belongs in "
                               "plan/fusion_cost.py (consume "
                               "decide_edges verdicts instead)")
    assert not bad, "\n".join(bad)


def test_jax_distributed_confined_to_mesh_module():
    """Multi-host gate (ISSUE 18): `jax.distributed` — the multi-
    controller runtime behind cross-host collective fusion — is
    confined to parallel/mesh.py (init_multihost /
    init_multihost_from_env are the routed entries), so process-group
    initialisation happens exactly once, BEFORE any backend touch, and
    every other layer reasons about membership via the /v1/info
    declarations and mesh.multihost_spec().  A second initialize
    anywhere else would either crash (backend already live) or fork
    the process group.  Flags `jax.distributed` attribute chains and
    `from jax import distributed` imports."""
    import ast

    ALLOWED = {os.path.join("parallel", "mesh.py")}
    pkg = os.path.join(ROOT, "presto_tpu")
    bad = []
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, pkg)
            if rel in ALLOWED:
                continue
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Attribute) \
                        and node.attr == "distributed" \
                        and isinstance(node.value, ast.Name) \
                        and node.value.id == "jax":
                    bad.append(f"{rel}:{node.lineno}: jax.distributed "
                               "— multi-controller init belongs in "
                               "parallel/mesh.py")
                if isinstance(node, ast.ImportFrom) \
                        and node.module == "jax" \
                        and any(a.name == "distributed"
                                for a in node.names):
                    bad.append(f"{rel}:{node.lineno}: from jax import "
                               "distributed — route through "
                               "parallel/mesh.py")
    assert not bad, "\n".join(bad)


def test_no_raw_sleeps_or_timeouts_in_parallel():
    """Robustness gate (ISSUE 2, extended by ISSUE 6 to the serving
    modules): presto_tpu/parallel/retry.py is the ONLY module in the
    parallel package allowed to call `time.sleep` or hard-code a
    timeout; everything else routes waits through retry.Backoff /
    retry._sleep and derives per-call timeouts from the
    retry.*_TIMEOUT_S constants (each capped by the query Deadline), so
    one query-level budget governs every RPC.  The serving tier
    (server/serving.py, server/protocol.py, server/resource_groups.py)
    is held to the same rule: no time.sleep at all, and every wait's
    timeout is a NAMED module constant (ADMIT_POLL_S, LONG_POLL_S, ...)
    or a session-property-derived value — never an inline number.  This
    test forbids NEW call sites from creeping back in."""
    import ast

    pdir = os.path.join(ROOT, "presto_tpu", "parallel")
    checked = [(fn, os.path.join(pdir, fn))
               for fn in sorted(os.listdir(pdir))
               if fn.endswith(".py") and fn != "retry.py"]
    sdir = os.path.join(ROOT, "presto_tpu", "server")
    checked += [(f"server/{fn}", os.path.join(sdir, fn))
                for fn in ("serving.py", "protocol.py",
                           "resource_groups.py", "fleet.py")]
    bad = []
    for fn, path in checked:
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if isinstance(func, ast.Attribute) and func.attr == "sleep" \
                    and isinstance(func.value, ast.Name) \
                    and func.value.id == "time":
                bad.append(f"{fn}:{node.lineno}: bare time.sleep() — "
                           "use retry.Backoff / an Event wait on a "
                           "named-constant timeout")
            for kw in node.keywords:
                if kw.arg == "timeout" \
                        and isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, (int, float)):
                    bad.append(
                        f"{fn}:{kw.value.lineno}: hard-coded "
                        f"timeout={kw.value.value!r} — use a named "
                        "*_S / *_TIMEOUT_S constant")
    assert not bad, "\n".join(bad)


def test_fleet_ring_and_lease_arithmetic_confined_to_fleet():
    """Fleet-coordination gate (ISSUE 16): consistent-hash ring
    arithmetic and slot-lease accounting live ONLY in server/fleet.py —
    the protocol front door and the cluster scheduler consume VERDICTS
    (affinity_key / owns / owner_uri / lease_slot / release_slot),
    never ring points or ledger internals.  A second bisect over a
    private point list, or lease math inlined at a POST site, would
    fork the ownership model exactly the way a magic bandwidth number
    forks fusion pricing — so the same confinement discipline applies:
    the ring-hash helper, the ring's point list, the lease board's
    in-flight ledger and counters, and raw bisect ring lookups are
    forbidden everywhere else in the package."""
    import ast

    ALLOWED = {os.path.join("server", "fleet.py")}
    FORBIDDEN = {"_ring_hash", "_points", "_in_flight",
                 "leases_granted", "lease_waits", "leases_reclaimed",
                 "insort", "bisect_right", "bisect_left"}
    pkg = os.path.join(ROOT, "presto_tpu")
    bad = []
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, pkg)
            if rel in ALLOWED:
                continue
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Attribute) \
                        and node.attr in FORBIDDEN:
                    bad.append(f"{rel}:{node.lineno}: .{node.attr} — "
                               "ring/lease arithmetic belongs in "
                               "server/fleet.py (consume owns/"
                               "lease_slot verdicts instead)")
                if isinstance(node, ast.Name) and node.id == "_ring_hash":
                    bad.append(f"{rel}:{node.lineno}: _ring_hash — "
                               "ring hashing belongs in server/fleet.py")
    assert not bad, "\n".join(bad)


def test_sketch_bit_twiddling_confined_to_kernels():
    """Sketch-aggregate gate (ISSUE 19): the sketch state primitives —
    leading-zero rank extraction (`lax.clz`), the HLL estimator's
    bias-correction constants (0.7213 / 1.079), and the KLL compactor's
    stable multi-key prune sort (raw `jnp.lexsort`) — must stay inside
    exec/kernels.py (hll_partial / hll_merge / hll_estimate /
    kll_partial / kll_percentile), so every sketch state an executor
    folds or an exchange merges is a kernel-built state: traceable,
    mergeable across modes, and covered by the error-bound oracle
    tests.  A register scatter or compactor reimplemented in plan/
    parallel/ exec/ would fork the state layout and silently break
    cross-mode merge compatibility."""
    import ast

    HLL_CONSTANTS = {0.7213, 1.079}
    DEVICE_NS = {"jnp", "lax"}
    pkg = os.path.join(ROOT, "presto_tpu")
    checked = []
    for sub in ("plan", "storage", "server"):
        d = os.path.join(pkg, sub)
        checked += [os.path.join(d, f) for f in sorted(os.listdir(d))
                    if f.endswith(".py")]
    checked += [os.path.join(pkg, "parallel", f)
                for f in ("cluster.py", "dist_executor.py", "exchange.py",
                          "faults.py", "retry.py")]
    checked += [os.path.join(pkg, "exec", f)
                for f in ("executor.py", "chunked.py", "compiler.py",
                          "gather.py", "window.py", "spill_exec.py")]
    bad = []
    for path in checked:
        rel = os.path.relpath(path, pkg)
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("clz", "lexsort"):
                base = node.func.value
                name = base.id if isinstance(base, ast.Name) else (
                    base.attr if isinstance(base, ast.Attribute) else None)
                if name in DEVICE_NS:
                    bad.append(f"{rel}:{node.lineno}: {name}."
                               f"{node.func.attr} — sketch rho/compactor "
                               "primitives belong in exec/kernels.py")
            if isinstance(node, ast.Constant) \
                    and isinstance(node.value, float) \
                    and node.value in HLL_CONSTANTS:
                bad.append(f"{rel}:{node.lineno}: HLL bias constant "
                           f"{node.value} — the estimator belongs in "
                           "exec/kernels.hll_estimate")
    assert not bad, "\n".join(bad)


def test_manifest_generation_diffing_confined_to_connectors():
    """Manifest-delta gate (ISSUE 20): raw manifest generation state —
    the `"generation"` / `"retired"` manifest fields and the
    `_manifest` dict itself — may be read only under `connectors/`
    (where `connectors/delta.py` turns generations into DeltaVerdicts
    and `localfile.py` owns retirement/GC) and in `exec/writer.py`
    (which publishes commits).  Everything else — the MV refresh logic,
    the planner, the serving tier — consumes watermark captures and
    verdicts, never generations: a second diff implementation would
    fork the append-detection rules and silently disagree about what
    counts as a delta."""
    import ast

    pkg = os.path.join(ROOT, "presto_tpu")
    FIELDS = {"generation", "retired"}
    bad = []
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            rel = os.path.relpath(os.path.join(dirpath, fn), pkg)
            if rel.startswith("connectors" + os.sep) \
                    or rel == os.path.join("exec", "writer.py"):
                continue
            with open(os.path.join(dirpath, fn), encoding="utf-8") as f:
                tree = ast.parse(f.read(), rel)
            for node in ast.walk(tree):
                if isinstance(node, ast.Constant) \
                        and isinstance(node.value, str) \
                        and node.value in FIELDS:
                    bad.append(
                        f"{rel}:{node.lineno}: manifest field "
                        f"'{node.value}' — generation diffing belongs "
                        "in connectors/delta.py (capture/diff)")
                if isinstance(node, ast.Attribute) \
                        and node.attr == "_manifest":
                    bad.append(
                        f"{rel}:{node.lineno}: raw _manifest access — "
                        "manifest state belongs to connectors/ and "
                        "exec/writer.py")
    assert not bad, "\n".join(bad)
