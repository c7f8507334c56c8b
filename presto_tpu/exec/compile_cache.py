"""Compilation economics: one shared executable cache + compile-ahead.

Reference parity: the reference engine's "native" layer is
compile-once-run-many bytecode generation — PageFunctionCompiler memoizes
compiled projections/filters in a guava cache keyed by the row expression
(sql/gen/PageFunctionCompiler.java:105), and compiled classes are reused
across queries for the life of the JVM.  Our XLA analogue compiles a
whole fragment per (plan shape, chunk mult, mesh), which at SF100 runs
into MINUTES per program, so the compile bill must be paid once per
MACHINE, not once per process — and never serially in front of a
waiting query when it can overlap.

Three layers, all fronted by this module:

1. the JAX persistent compilation cache (disk, keyed by HLO hash).
   Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it and this
   module sets no directory in code; otherwise the directory is the
   `compile_cache_dir` session property, `PRESTO_TPU_COMPILE_CACHE`, or
   `<checkout>/.jax_cache` (a fixed path: the path is part of the
   cache key).  A cold process with a warmed cache dir loads
   executables instead of compiling them.
2. a process-wide executable memo keyed by engine-level fingerprints
   (plan serde bytes x chunk mult x mesh shape x dtype layout, see
   `fingerprint`/`plan_fingerprint`): the per-session `_jit` /
   `_chunked_cache` / `_compiled_cache` dicts are views over this —
   a second session (or a second runner) with an identical fragment
   reuses the executable without retracing.  Entries are built
   SINGLE-FLIGHT: a compile-ahead thread and the query thread asking for
   the same key compile it once, everyone else waits.
3. a bounded compile-ahead worker pool: chunked plans AOT-compile
   fragments 2..N while fragment 1 executes; miss-prone fragments
   pre-compile their next bound-growth mult so "bound miss -> grow +
   re-jit" re-runs against a ready executable; cluster workers warm
   their scan inputs at task-accept time instead of first-page time.
   `PRESTO_TPU_COMPILE_AHEAD=off` (or session property
   `compile_ahead=False`) kills all of it; compile-ahead never changes
   results, only WHEN the same executables get built.

Telemetry: every build routes through `build_jit`, so QueryStats gains
exact `compiles` / `compile_ms` / `compile_cache_hits` /
`compile_ahead_hits` per query.  Persistent-cache disk hits are
observed through jax.monitoring's `/jax/compilation_cache/cache_hits`
event.  The same listener books JAX's compile stages where they run,
AOT builds and first-call builds alike: `lower_ms` (jaxpr trace +
lowering to StableHLO), `xla_build_ms` + `programs_built` (backend
compiles XLA ran), `cache_load_ms` (backend stages the persistent cache
served).  `data_load` books table birth: `data_load_ms` /
`data_load_bytes`.
"""

from __future__ import annotations

import hashlib
import itertools
import os
import threading
import weakref
from contextlib import contextmanager
from typing import Any, Callable, Dict, Optional

import jax

from presto_tpu.observe import names as NM
from presto_tpu.observe import trace as TR

#: JAX's own variable: where it is set the cache is placed from outside
JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

#: QueryStats counter names this module maintains (observe/stats.py
#: declares the same fields)
COUNTERS = ("compiles", "compile_ms", "compile_cache_hits",
            "compile_ahead_hits", "lower_ms", "xla_build_ms",
            "cache_load_ms", "programs_built", "data_load_ms",
            "data_load_bytes")


class CompileStats:
    """Counter bag with the QueryStats compile-economics fields; used as
    the process-wide aggregate and for worker-side task accounting."""

    def __init__(self):
        self.compiles = 0
        self.compile_ms = 0.0
        self.compile_cache_hits = 0
        self.compile_ahead_hits = 0
        self.lower_ms = 0.0
        self.xla_build_ms = 0.0
        self.cache_load_ms = 0.0
        self.programs_built = 0
        self.data_load_ms = 0.0
        self.data_load_bytes = 0

    def snapshot(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in COUNTERS}


#: process totals (tools/roofline.py and tests read these)
GLOBAL = CompileStats()

_tls = threading.local()
_note_lock = threading.Lock()


def _sinks():
    sinks = [GLOBAL]
    extra = getattr(_tls, "sink", None)
    if extra is not None:
        sinks.append(extra)
    return sinks


def _note(field: str, amount=1) -> None:
    with _note_lock:
        for s in _sinks():
            setattr(s, field, getattr(s, field, 0) + amount)


@contextmanager
def recording(stats):
    """Route this thread's compile accounting into `stats` (a QueryStats
    or CompileStats).  Nests: inner recordings shadow outer ones, the
    GLOBAL aggregate always collects."""
    prev = getattr(_tls, "sink", None)
    _tls.sink = stats
    try:
        yield stats
    finally:
        _tls.sink = prev


# ---------------------------------------------------------------------------
# persistent-cache wiring
# ---------------------------------------------------------------------------

_conf_lock = threading.Lock()
_configured_dir: Optional[str] = "UNSET"
_listener_installed = False


def resolve_cache_dir(session=None) -> Optional[str]:
    """The persistent cache's directory.  JAX_COMPILATION_CACHE_DIR,
    where set, decides alone (JAX reads it; configure() then sets no
    directory).  Otherwise: `compile_cache_dir` session property >
    PRESTO_TPU_COMPILE_CACHE > <checkout>/.jax_cache, and '0' / 'off'
    disables (returns None)."""
    outside = os.environ.get(JAX_CACHE_ENV)
    if outside:
        return outside
    d = None
    if session is not None:
        d = session.properties.get("compile_cache_dir") or None
    if d is None:
        d = os.environ.get("PRESTO_TPU_COMPILE_CACHE") or DEFAULT_CACHE_DIR
    d = str(d)
    return None if d in ("0", "off") else d


# JAX's compile stages (jax/_src/dispatch.py), each reported on the
# compiling thread at its start (a scalar event holding the start time) and
# at its end (a duration, then the same as a time span).  Stages nest: the
# trace of a function holds the traces of the jitted functions it calls.
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_TO_MLIR = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_STAGES = (_TRACE, _TO_MLIR, _BACKEND)
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _stack() -> list:
    """This thread's open stages: the seconds their nested stages took."""
    st = getattr(_tls, "stages", None)
    if st is None:
        st = _tls.stages = []
    return st


def _staged_ms() -> float:
    """Stage milliseconds booked on this thread so far (a running sum)."""
    return getattr(_tls, "staged_ms", 0.0)


def _book(field: str, ms: float) -> None:
    _note(field, ms)
    _tls.staged_ms = _staged_ms() + ms


def _guarded(fn):
    """A listener runs inside JAX's compile: a fault of its own is counted
    in presto_tpu_trace_errors_total and never fails the build."""
    def listener(*args, **kw):
        try:
            fn(*args, **kw)
        except Exception:  # noqa: BLE001
            from presto_tpu.observe import metrics as M

            M.trace_error()
    return listener


def _on_event(event, **kw) -> None:
    if event == _CACHE_HIT:
        _note("compile_cache_hits")
        _tls.loaded = True      # the backend stage under way read the disk


def _on_stage_start(event, _start, **kw) -> None:
    if event in _STAGES:
        _stack().append(0.0)
        if event == _BACKEND:
            _tls.loaded = False


def _on_stage_end(event, secs, **kw) -> None:
    """Book a stage's own time (less its nested stages'): trace and
    lowering to `lower_ms`; a backend stage to `cache_load_ms` where the
    persistent cache served it (JAX times the retrieval inside it), else
    to `xla_build_ms` and one more `programs_built`.  JAX's cache_misses
    event is no test of a build: it fires only when an entry is written."""
    if event not in _STAGES:
        return
    stack = _stack()
    nested = stack.pop() if stack else 0.0
    if stack:
        stack[-1] += secs
    if event != _BACKEND:
        field = "lower_ms"
    elif getattr(_tls, "loaded", False):
        field = "cache_load_ms"
    else:
        field = "xla_build_ms"
        _note("programs_built")
    _book(field, max(secs - nested, 0.0) * 1e3)


def _on_stage_span(event, start, end, fun_name="", **kw) -> None:
    """A first-call build's outermost stages on the query's Tracer, at
    JAX's own start and end.  An AOT build opens its stages' spans itself
    (`Executable.aot_compile`)."""
    if event not in _STAGES or _stack() or getattr(_tls, "aot", False):
        return
    tracer = TR.current()
    if tracer is None:
        return
    args = {"program": fun_name}
    if event == _BACKEND:
        name = "exec.backend"
        args["source"] = "loaded" if getattr(_tls, "loaded", False) \
            else "built"
    else:
        name = "exec.lower"
        args["stage"] = "trace" if event == _TRACE else "to_mlir"
    sp = tracer.begin(name, kind="compile", at_ns=TR.ns_of_wall(start),
                      **args)
    tracer.end(sp, at_ns=TR.ns_of_wall(end))


def _listen() -> None:
    """Install the jax.monitoring listeners, once a process."""
    global _listener_installed
    if _listener_installed:
        return
    with _conf_lock:
        if _listener_installed:
            return
        jax.monitoring.register_event_listener(_guarded(_on_event))
        jax.monitoring.register_scalar_listener(_guarded(_on_stage_start))
        jax.monitoring.register_event_duration_secs_listener(
            _guarded(_on_stage_end))
        jax.monitoring.register_event_time_span_listener(
            _guarded(_on_stage_span))
        _listener_installed = True


def configure(session=None) -> None:
    """Idempotently point JAX's persistent compilation cache at the
    resolved dir — unless JAX_COMPILATION_CACHE_DIR placed it from
    outside, in which case no directory is set here — and install the
    listeners (`_listen`).  Safe to call per query: only reconfigures
    when the resolved dir changes."""
    global _configured_dir
    d = resolve_cache_dir(session)
    _listen()
    with _conf_lock:
        if d == _configured_dir:
            return
        _configured_dir = d
        if d is None:
            return
        if not os.environ.get(JAX_CACHE_ENV):
            jax.config.update("jax_compilation_cache_dir", d)
        # cache every compile that takes noticeable time (default 1s
        # would skip the many small per-fragment programs whose compiles
        # still add up across the 22-query suite); tests set the env to
        # 0 so CPU-sized compiles persist too
        min_s = float(os.environ.get("PRESTO_TPU_COMPILE_CACHE_MIN_S",
                                     "0.2"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_s)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------

_token_counter = itertools.count(1)


def catalog_token(catalog) -> str:
    """Process-unique identity token for a catalog instance.  id() is
    NOT usable in cache keys (a freed catalog's id can be recycled by a
    new one, aliasing stale executables onto fresh data); a token
    attribute assigned once per object cannot alias."""
    tok = getattr(catalog, "_compile_cache_token", None)
    if tok is None:
        tok = f"cat{next(_token_counter)}"
        try:
            catalog._compile_cache_token = tok
        except Exception:
            return f"id{id(catalog)}"  # slotted object: best effort
    return tok


def fingerprint(*parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(repr(p).encode())
        h.update(b"\x00")
    return h.hexdigest()


def plan_fingerprint(obj) -> Optional[str]:
    """Stable fingerprint of a plan (sub)tree via the cluster-wire serde
    (plan/serde.py) — the same bytes two sessions produce for identical
    plans.  None when the plan carries something unserializable; callers
    then skip the shared memo (the build is still counted)."""
    from presto_tpu.plan import serde

    try:
        return hashlib.sha256(serde.dumps(obj)).hexdigest()
    except Exception:
        return None


def avals_fingerprint(tree) -> str:
    """Shape/dtype fingerprint of a pytree of arrays (the dtype-layout
    component of executable keys: identical plans over different column
    layouts must not share executables)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple(
        (getattr(x, "shape", ()), str(getattr(x, "dtype", type(x).__name__)))
        for x in leaves)
    return fingerprint(str(treedef), shapes)


def fused_key(fragment_bytes: bytes, ndev: int, session,
              scalar_results, ext_inputs) -> Optional[str]:
    """Executable-memo key for a fused super-fragment (fragment fusion,
    plan/distribute.fuse_fragments): one executable per (fused pipeline
    fingerprint, mesh shape, catalog identity+version, property map),
    reused forever — the cluster analog of the chunked/compiled memo
    keys, compounding with the persistent disk cache.

    Host values baked into the trace must ride the key: coordinator-
    evaluated scalar-subquery results, and the dictionary VALUES of any
    string-typed external exchange input (partition_hash bakes a
    host-computed per-code hash LUT).  Oversized string externals
    return None — the build still runs, uncached.

    The MESH SHAPE rides the key too: the same fused fragment traced at
    the same ndev compiles a DIFFERENT program on a multi-process
    global mesh (per-process shard feeds, DCN collectives), so the
    process topology (count, index) is a key component alongside ndev —
    a single-host executable must never serve a gang member."""
    from presto_tpu.parallel import mesh as _MH

    h = hashlib.sha256(fragment_bytes)
    h.update(f"procs={_MH.process_count()}/{_MH.process_index()}"
             .encode())
    for _pid, val in sorted(scalar_results.items()):
        h.update(repr(val).encode())
        h.update(b"\x00")
    nvals = 0
    for eid in sorted(ext_inputs):
        for sym in sorted(ext_inputs[eid]["cols"]):
            data, _valid = ext_inputs[eid]["cols"][sym]
            import numpy as _np

            arr = _np.asarray(data)
            if arr.dtype == object or arr.dtype.kind in ("U", "S"):
                uniq = _np.unique(arr.astype(str))
                nvals += len(uniq)
                if nvals > 100_000:
                    return None  # hashing the dictionary costs too much
                for v in uniq.tolist():
                    h.update(str(v).encode("utf-8", "replace"))
                    h.update(b"\x01")
    return fingerprint("fused", h.hexdigest(), ndev,
                       session_fingerprint(session))


def session_fingerprint(session) -> tuple:
    """The session-dependent key components every executable bakes in at
    trace time: catalog identity+version and the full property map."""
    return (catalog_token(session.catalog),
            getattr(session.catalog, "version", 0),
            tuple(sorted((k, repr(v))
                         for k, v in session.properties.items())))


# ---------------------------------------------------------------------------
# counted jit builds (AOT when example args are available)
# ---------------------------------------------------------------------------


def _shape_struct(x):
    if getattr(x, "weak_type", False) or not hasattr(x, "dtype") \
            or not hasattr(x, "shape"):
        return x
    sharding = getattr(x, "sharding", None)  # mesh-sharded chunk args
    try:
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
    except TypeError:
        return jax.ShapeDtypeStruct(x.shape, x.dtype)


#: every live Executable, for scope_tables()
_executables: "weakref.WeakSet[Executable]" = weakref.WeakSet()


def _versioned(fn: Callable, tag: Optional[str]) -> Callable:
    """`fn` under the name every engine program is jitted by:
    <name>_s<SCOPE_VERSION>[_<tag>].  The name becomes the HLO module's
    (`jit_<name>`), which JAX's persistent-cache key hashes while it
    strips the debug info that holds the kernel scopes: with the version
    in the name a change of vocabulary is a change of key, and a program
    compiled before the scopes existed is never loaded for one that has
    them (observe/names.SCOPE_VERSION)."""
    suffix = f"_s{NM.SCOPE_VERSION}" + (f"_{tag}" if tag else "")
    name = getattr(fn, "__name__", "fn")
    if not name.endswith(suffix):   # a function built twice keeps one
        name += suffix
    try:
        fn.__name__ = name
        return fn
    except (AttributeError, TypeError):     # a partial, a callable object
        def named(*args, **kwargs):
            return fn(*args, **kwargs)

        named.__name__ = name
        return named


def scope_tables() -> Dict[str, Dict[str, str]]:
    """{HLO module name: {instruction name: op_name}} of every live
    AOT-compiled program: how a profile's device events, which carry the
    module's and the instruction's names, get the engine's scopes where the
    event itself does not hold the op_name (observe/names.hlo_op_names;
    read by benchmarks/span_reduce.py).  Parsed on demand from the
    executables' own HLO text: building and running a program pay nothing
    for it."""
    tables: Dict[str, Dict[str, str]] = {}
    for ex in list(_executables):
        compiled = ex._compiled
        if compiled is None:
            continue
        try:
            for mod in compiled.runtime_executable().hlo_modules():
                tables.setdefault(mod.name, {}).update(
                    NM.hlo_op_names(mod.to_string()))
        except Exception:  # noqa: BLE001 — a backend without HLO text
            continue
    return tables


def _module_name(lowered) -> str:
    """The HLO module's name (`jit_<fn>`), as a profile's events carry it."""
    try:
        return str(lowered._lowering.stablehlo().operation
                   .attributes["sym_name"].value)
    except Exception:  # noqa: BLE001 — a span argument, nothing more
        return ""


def _aot_stage(sp, run):
    """`run()` under the span `sp` (`exec.lower` or `exec.backend`), one
    stage of an AOT build.  JAX's events book the stage's parts as they
    run; what they leave of its wall (JAX's own work around them) is
    booked to the stage here, so that an AOT build's lower_ms +
    xla_build_ms + cache_load_ms is its compile_ms."""
    staged0, aot = _staged_ms(), getattr(_tls, "aot", False)
    backend = sp.name == "exec.backend"
    _tls.aot, _tls.loaded = True, False
    try:
        with sp as rec:
            out = run()
            loaded = backend and getattr(_tls, "loaded", False)
            if rec is not None and backend:
                rec.args["source"] = "loaded" if loaded else "built"
    finally:
        _tls.aot = aot
    field = "cache_load_ms" if loaded else \
        "xla_build_ms" if backend else "lower_ms"
    _book(field, max(sp.elapsed_ns / 1e6 - (_staged_ms() - staged0), 0.0))
    return out


class Executable:
    """A counted jax.jit product.  With example args it AOT-compiles
    immediately (lower+compile timed as compile_ms — execution excluded);
    calls dispatch to the AOT executable while argument avals match and
    fall back to the live jit wrapper (which retraces, counted) when
    they stop matching — e.g. an exchange-buffer capacity that changed
    between runs."""

    __slots__ = ("_jitted", "_compiled", "_fellback", "__weakref__")

    def __init__(self, fn, jit_kwargs, tag: Optional[str] = None):
        self._jitted = jax.jit(_versioned(fn, tag), **jit_kwargs)
        self._compiled = None
        self._fellback = False
        _executables.add(self)

    def aot_compile(self, example_args) -> None:
        t0 = TR.clock_ns()
        # lower against shape structs, not the concrete arrays: AOT must
        # not pin (or later donate) multi-GB example buffers.  Leaves
        # that aren't plain strong-typed arrays stay concrete — a
        # weak-typed scalar lowered strong would mismatch at call time.
        # The span puts the compile on the query's trace timeline —
        # compile-ahead builds appear on their own pool-thread lane.
        with TR.span("xla_compile", kind="compile"):
            shapes = jax.tree_util.tree_map(_shape_struct, example_args)
            lowered = _aot_stage(TR.span("exec.lower", kind="compile"),
                                 lambda: self._jitted.lower(*shapes))
            self._compiled = _aot_stage(
                TR.span("exec.backend", kind="compile",
                        program=_module_name(lowered)),
                lowered.compile)
        _note("compiles")
        _note("compile_ms", (TR.clock_ns() - t0) / 1e6)

    def lower(self, *args, **kw):
        return self._jitted.lower(*args, **kw)

    def __call__(self, *args):
        c = self._compiled
        if c is not None:
            try:
                return c(*args)
            except (TypeError, ValueError):
                # aval/sharding mismatch vs the AOT signature (e.g. an
                # exchange-buffer capacity that changed between runs, or
                # arrays that moved devices): retrace live
                self._compiled = None
        if not self._fellback and self._compiled is None \
                and c is not None:
            self._fellback = True
            _note("compiles")  # the retrace below compiles fresh
        return self._jitted(*args)


def build_jit(fn: Callable, *, example=None, tag: Optional[str] = None,
              **jit_kwargs) -> Executable:
    """THE routed constructor for engine-level jax.jit programs (the
    test_lint AST rule forbids raw jax.jit outside this module and the
    two executors).  `example`: concrete args to AOT-compile against —
    exact compile timing, and the executable is ready before first use.
    Without example the first call traces+compiles inside jit (counted
    as one compile; compile_ms grows by AOT builds only, and the first
    call's stages are booked by the listener: lower_ms, xla_build_ms or
    cache_load_ms).  `tag`: a fingerprint of
    the program that is the same in every process (the plan's): part of
    the module's name, so a profile tells one query's program from
    another's (`_versioned`)."""
    _listen()
    ex = Executable(fn, jit_kwargs, tag)
    if example is not None:
        try:
            ex.aot_compile(example)
        except ValueError as e:
            # mixed-device example (e.g. a mesh-sharded exchange buffer
            # next to host-created arrays): AOT pins explicit shardings
            # where the live jit would reshard implicitly — compile at
            # first call instead.  Anything else is a real trace error.
            if "incompatible devices" not in str(e):
                raise
            _note("compiles")
    else:
        _note("compiles")
    return ex


def data_load(make: Callable[[], Any]):
    """Table birth: `make()` -> a column set's device arrays (a pytree),
    timed to ready under the span `exec.data_load` into `data_load_ms`
    and `data_load_bytes`.  Called where a table and column set is born,
    once (never in a warm query), so its one block_until_ready costs a
    warm query nothing.  A build inside it stays in the compile counters,
    out of data_load_ms."""
    staged0 = _staged_ms()
    sp = TR.span("exec.data_load")
    with sp:
        out = make()
        jax.block_until_ready(out)
    _note("data_load_ms",
          max(sp.elapsed_ns / 1e6 - (_staged_ms() - staged0), 0.0))
    _note("data_load_bytes", sum(getattr(x, "nbytes", 0)
                                 for x in jax.tree_util.tree_leaves(out)))
    return out


# ---------------------------------------------------------------------------
# the process-wide executable memo (single-flight)
# ---------------------------------------------------------------------------


class _Entry:
    __slots__ = ("value", "built_ahead", "ahead_credited")

    def __init__(self, value, built_ahead: bool):
        self.value = value
        self.built_ahead = built_ahead
        self.ahead_credited = False


_memo: Dict[str, _Entry] = {}
_inflight: Dict[str, threading.Event] = {}
_memo_lock = threading.Lock()

#: fragment fingerprints that ever overflowed their compact bound in
#: this process: their next-growth executables are worth pre-compiling
_miss_prone: set = set()


def mark_miss_prone(fp: Optional[str]) -> None:
    if fp:
        with _memo_lock:
            _miss_prone.add(fp)


def is_miss_prone(fp: Optional[str]) -> bool:
    with _memo_lock:
        return fp in _miss_prone


def get_or_build(key: Optional[str], build: Callable[[], Any], *,
                 ahead: bool = False):
    """Memoized single-flight build.  `key` None => uncacheable, build
    directly.  Hits count as compile_cache_hits (or compile_ahead_hits
    the FIRST time a foreground caller collects a background build).
    A failed build caches nothing; concurrent waiters retry it
    themselves so the exception propagates to every caller."""
    if key is None:
        return build()
    while True:
        with _memo_lock:
            e = _memo.get(key)
            if e is not None:
                if not ahead:
                    if e.built_ahead and not e.ahead_credited:
                        e.ahead_credited = True
                        _note("compile_ahead_hits")
                    else:
                        _note("compile_cache_hits")
                return e.value
            ev = _inflight.get(key)
            if ev is None:
                ev = _inflight[key] = threading.Event()
                builder = True
            else:
                builder = False
        if builder:
            try:
                value = build()
                with _memo_lock:
                    _memo[key] = _Entry(value, ahead)
                return value
            finally:
                with _memo_lock:
                    _inflight.pop(key, None)
                ev.set()
        else:
            ev.wait()
            # loop: either the entry exists now, or the build failed and
            # this thread takes its turn


def clear() -> None:
    """Drop every memoized executable (test harness memory bounding —
    the tier-1 suite clears jax caches between modules; pinning
    executables here would defeat that)."""
    with _memo_lock:
        _memo.clear()
        _miss_prone.clear()


def stats() -> Dict[str, Any]:
    with _memo_lock:
        n = len(_memo)
    return dict(GLOBAL.snapshot(), memo_entries=n)


# ---------------------------------------------------------------------------
# compile-ahead pool
# ---------------------------------------------------------------------------

_pool = None
_pool_lock = threading.Lock()


def _cores() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return os.cpu_count() or 1


def ahead_enabled(session=None) -> bool:
    """Compile-ahead policy.  Kill switches: env
    PRESTO_TPU_COMPILE_AHEAD=off|0 (process-wide) or session property
    compile_ahead=False; env =on|1|force forces it on.  With neither
    forced, it is ON wherever a background compile can actually overlap
    the query thread (>1 usable core) and OFF on single-core hosts,
    where a "background" compile only steals cycles from the query it
    is supposed to hide behind (TPU hosts have dozens of cores; the
    1-core CI tier is the exception this guards)."""
    env = os.environ.get("PRESTO_TPU_COMPILE_AHEAD", "").lower()
    if env in ("off", "0", "false"):
        return False
    if session is not None and not bool(
            session.properties.get("compile_ahead", True)):
        return False
    if env in ("on", "1", "true", "force"):
        return True
    return _cores() > 1


def _get_pool():
    global _pool
    with _pool_lock:
        if _pool is None:
            from concurrent.futures import ThreadPoolExecutor

            n = int(os.environ.get("PRESTO_TPU_COMPILE_AHEAD_WORKERS",
                                   "2"))
            _pool = ThreadPoolExecutor(
                max_workers=max(n, 1),
                thread_name_prefix="presto-tpu-compile-ahead")
        return _pool


def current_sink():
    """The stats object this thread's compile accounting flows into
    (pass it to `submit` so background builds bill the initiating
    query), or None outside any recording."""
    return getattr(_tls, "sink", None)


def submit(job: Callable[[], Any], stats_sink=None) -> bool:
    """Queue a compile-ahead job on the bounded pool.  Jobs build
    through `get_or_build(..., ahead=True)`, so the single-flight memo
    makes them race-free against the query thread: whichever side
    starts first compiles, the other waits or hits.  Job failures are
    swallowed — the foreground will rebuild and surface the error
    properly."""

    # the submitting thread's trace context rides along, so background
    # builds appear on the query's trace under the pool thread's lane
    tracer = TR.current()

    def wrapped():
        try:
            with recording(stats_sink if stats_sink is not None
                           else CompileStats()), TR.activate(tracer):
                job()
        except BaseException:
            pass  # foreground retries and reports

    try:
        _get_pool().submit(wrapped)
    except RuntimeError:  # interpreter shutdown
        return False
    return True
