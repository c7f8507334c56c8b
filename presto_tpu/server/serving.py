"""Serving tier: admission control, prepared statements, result cache.

Reference parity: the dispatcy layer of the reference coordinator —
dispatcher/DispatchManager + execution/resourceGroups (admission),
QueryPreparer + ParameterRewriter (prepared statements), and the query
JSON's resourceGroupId/queuedTime surface — rebuilt around this engine's
compile economics.  The reference rewrites `?` parameters to constants
during analysis and replans per EXECUTE; we keep parameters SYMBOLIC
(ir.Param) so one plan and ONE XLA executable serve every parameter
value of a given type signature: a warm EXECUTE is a registry dict hit
plus a device transfer, never a parse, plan, or compile
(exec/compile_cache.py is the executable memo underneath).

Three pieces, composable and individually optional:

- `PreparedRegistry` (per session == per server: the protocol server
  multiplexes one session): PREPARE parses + validates the template
  once; EXECUTE binds parameter values to engine types, types a
  deep-copied template per type signature, and routes through
  `run_compiled(params=...)` (compiled/auto) or a memoized dynamic plan.
  Bindings the symbolic path cannot carry — strings (device columns are
  dictionary-encoded; a traced string scalar does not exist), NULLs,
  long decimals, parameters inside subqueries (their values bake into
  the compiled program via eager subplan evaluation), static positions
  like `LIMIT ?`, volatile templates, distributed/chunked sessions —
  fall back to the classic text-substitution path, counted as
  `prepared_fallbacks` (plans then key per VALUE, exactly the
  reference's semantics).
- `AdmissionController`: the resource-group tree
  (server/resource_groups.py) behind one `admit`/`release` surface with
  queue-depth gauges, shed counters, and a drain switch graceful
  shutdown uses to cancel queued-but-not-started queries.
- `QueryCoalescer`: the admission-side micro-batcher behind query
  coalescing — concurrent EXECUTEs of the SAME prepared signature that
  arrive within `coalesce_window_ms` of each other stack their bound
  parameters into a leading batch axis and ride ONE vmap-batched XLA
  launch (exec/executor.run_compiled_batched), so one device dispatch
  serves N users.  Default `auto`: a window only opens when another
  same-signature query is already in flight, so an idle EXECUTE never
  pays the window latency.  Anything that cannot batch (substitution
  fallbacks, volatile templates, long decimals, oversized results,
  tripped guards, a faulted leader) exits the batch and runs solo —
  never a wrong result, never a stall beyond the window.
- `ResultCache`: a bounded LRU serving IDENTICAL re-submitted SELECTs
  without execution, keyed by query text x catalog token+version x the
  session property map.  Any engine write bumps the catalog version, so
  staleness is structural, not temporal; `invalidate()` is the explicit
  hook and stale-version entries are swept on store.  Volatile queries
  (now()/random()), non-SELECT statements, open-transaction sessions,
  and oversized results are never cached.

`ServingTier` composes the three for the protocol server
(server/protocol.py).
"""

from __future__ import annotations

import copy
import dataclasses
import os
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional

from presto_tpu.observe import trace as TR
from presto_tpu.server.resource_groups import (QueryRejected,
                                               ResourceGroupManager)
from presto_tpu.sql import ast

#: hard bound on memoized dynamic plans / typed templates per registry —
#: a runaway generator of distinct type signatures must not grow memory
MAX_TYPED_ENTRIES = 256


# ---------------------------------------------------------------------------
# prepared statements
# ---------------------------------------------------------------------------


class PreparedStatement:
    """One PREPARE'd template (reference: Session.preparedStatements
    value, plus the analysis the reference redoes per EXECUTE)."""

    __slots__ = ("name", "text", "n_params", "template", "subst_only",
                 "subquery_params", "param_types", "typed")

    def __init__(self, name: str, text: str):
        self.name = name
        self.text = text
        self.n_params = 0
        self.template = None  # parsed AST with ast.Parameter nodes
        self.subst_only = False  # `?` in a static position (LIMIT ?)
        self.subquery_params = False  # `?` inside a subquery
        self.param_types: List[str] = []  # inferred, for DESCRIBE INPUT
        self.typed: Dict[tuple, object] = {}  # type sig -> typed AST


class PreparedRegistry:
    """Session-and-server-level prepared-statement registry (the
    protocol server embeds ONE session, so the session registry IS the
    server registry).  Thread-safe: the protocol server binds from
    concurrent worker threads."""

    def __init__(self):
        self._stmts: Dict[str, PreparedStatement] = {}
        self._lock = threading.Lock()

    def prepare(self, session, name: str, text: str) -> PreparedStatement:
        from presto_tpu.sql.parser import ParseError, parse

        entry = PreparedStatement(name, text)
        try:
            entry.template = parse(text)
            entry.n_params = _count_ast_params(entry.template)
        except ParseError:
            # `?` in a position the grammar types statically (LIMIT ?):
            # validate by substituting a literal that parses everywhere,
            # exactly the pre-serving behaviour; EXECUTE then always
            # substitutes text (plans key per value)
            parse(text.replace("?", "0"))
            entry.subst_only = True
            entry.n_params = _count_placeholders(text)
        if entry.template is not None:
            entry.subquery_params = _params_under_subquery(entry.template)
            entry.param_types = _infer_param_types(
                session, entry.template, entry.n_params)
        else:
            entry.param_types = ["unknown"] * entry.n_params
        with self._lock:
            self._stmts[name] = entry
        return entry

    def get(self, name: str) -> Optional[PreparedStatement]:
        with self._lock:
            return self._stmts.get(name)

    def deallocate(self, name: str) -> bool:
        with self._lock:
            return self._stmts.pop(name, None) is not None

    def names(self) -> list:
        with self._lock:
            return sorted(self._stmts)


def registry_for(session) -> PreparedRegistry:
    """The session's registry, created on first use.  Mirrors into
    `session.prepared_statements` ({name: text}, the pre-serving compat
    surface) — both views always agree."""
    reg = getattr(session, "prepared_registry", None)
    if reg is None:
        reg = session.prepared_registry = PreparedRegistry()
    if not hasattr(session, "prepared_statements"):
        session.prepared_statements = {}
    # adopt entries planted directly on the compat dict
    for name, text in list(session.prepared_statements.items()):
        if reg.get(name) is None:
            reg.prepare(session, name, text)
    return reg


def prepare(session, name: str, text: str):
    reg = registry_for(session)
    entry = reg.prepare(session, name, text)
    session.prepared_statements[name] = text
    return entry


def deallocate(session, name: str) -> None:
    from presto_tpu.exec.executor import ExecutionError

    reg = registry_for(session)
    if not reg.deallocate(name):
        raise ExecutionError(f"prepared statement '{name}' not found")
    session.prepared_statements.pop(name, None)


def describe_input(session, name: str) -> list:
    """(position, type) rows for DESCRIBE INPUT: parameter types
    inferred from the template's column comparisons (reference:
    DescribeInputRewrite reporting the analyzer's parameter types)."""
    from presto_tpu.exec.executor import ExecutionError

    entry = registry_for(session).get(name)
    if entry is None:
        raise ExecutionError(f"prepared statement '{name}' not found")
    return [(i, t) for i, t in enumerate(entry.param_types)]


def execute_prepared(session, stmt: ast.Execute, mon, dispatch):
    """EXECUTE dispatch: the typed aval-abstracted path when every
    binding supports it, else classic text substitution.  `dispatch` is
    executor._dispatch_statement (fallback re-entry)."""
    from presto_tpu import types as T
    from presto_tpu.exec import compile_cache as CC
    from presto_tpu.exec import executor as EX

    entry = registry_for(session).get(stmt.name)
    if entry is None:
        raise EX.ExecutionError(
            f"prepared statement '{stmt.name}' not found")

    def fallback():
        mon.stats.prepared_fallbacks += 1
        sql = EX._substitute_parameters(entry.text, stmt.parameters)
        from presto_tpu.sql.parser import parse
        return dispatch(session, sql, parse(sql), mon)

    if entry.subst_only or entry.subquery_params \
            or not bool(session.properties.get("prepared_typed_binding",
                                               True)) \
            or bool(session.properties.get("distributed", False)) \
            or session.properties.get("execution_mode") == "chunked" \
            or EX._VOLATILE_RE.search(entry.text) is not None:
        return fallback()

    with TR.span("prepared.bind"):
        # bind values: literal -> (host value, engine Type) via the SAME
        # lowering the substitution path's re-parse would apply, so the two
        # paths type identically
        lits = _fold_param_literals(stmt.parameters)
        if lits is None or len(lits) != entry.n_params:
            # non-literal parameters or a count mismatch: the substitution
            # path raises the canonical errors
            return fallback()
        bound = []
        for lit in lits:
            try:
                from presto_tpu.plan.planner import _literal_to_ir
                il = _literal_to_ir(lit)
            except Exception:
                return fallback()
            t = il.type
            if t == T.UNKNOWN or t.is_string \
                    or (t.is_decimal and t.is_long_decimal) \
                    or t.name in ("VARBINARY", "TIMESTAMP_TZ", "TIME_TZ"):
                return fallback()
            bound.append((il.value, t))
        sig = tuple(str(t) for _v, t in bound)

        # typed template per signature (deep copy: Parameter.type_ is bound
        # per signature and templates are shared across threads)
        typed = entry.typed.get(sig)
        if typed is None:
            typed = copy.deepcopy(entry.template)
            types_by_pos = {i: t for i, (_v, t) in enumerate(bound)}
            for p in _walk_params(typed):
                p.type_ = types_by_pos[p.position]
            if len(entry.typed) >= MAX_TYPED_ENTRIES:
                entry.typed.clear()
            entry.typed[sig] = typed
        mon.stats.prepared_binds += 1

        # the VALUE-free cache key: template text + type signature (+ the
        # session fingerprint inside run_compiled's own key)
        key_text = "$prepared$" + CC.fingerprint(entry.text, sig)

    # result cache, per rider and BEFORE any batching: the substituted
    # template text is the canonical cache identity (identical to what
    # a client submitting the rendered SELECT directly would key on),
    # so identical re-submitted EXECUTE values serve from the cache
    # without joining a batch, and hit accounting is independent of
    # whether the original execution was coalesced
    tier = getattr(session, "_serving_tier", None)
    cache_sql = None
    if tier is not None and tier.result_cache is not None:
        cache_sql = _prepared_cache_text(entry, stmt)
    if cache_sql is not None:
        hit = tier.result_lookup(cache_sql)
        if hit is not None:
            mon.stats.result_cache_hit = 1
            mon.stats.execution_mode = "cached"
            return _result_from_cache(hit)

    mode = session.properties.get("execution_mode", "auto")

    def run_typed_solo():
        compiled_cache = getattr(session, "_compiled_cache", {})
        marker = compiled_cache.get(
            (key_text, getattr(session.catalog, "version", 0),
             tuple(sorted((k, repr(v))
                          for k, v in session.properties.items())), 0))
        if mode in ("auto", "compiled") and marker != "DYNAMIC":
            import jax

            try:
                if marker is not None:
                    # warm bind: plan + executable replay from the
                    # session view over the process-wide memo — zero
                    # parse/plan work
                    mon.stats.prepared_plan_hits += 1
                with mon.phase("execute"):
                    mon.stats.execution_mode = "compiled"
                    return EX.run_compiled(session, key_text, typed,
                                           mon=mon, params=bound)
            except (EX.StaticFallback,
                    jax.errors.ConcretizationTypeError,
                    jax.errors.TracerArrayConversionError):
                if mode == "compiled":
                    raise
        # dynamic path: plan memoized per key (value-free — ir.Param
        # reads the binding at evaluation time)
        plans = session.__dict__.setdefault("_prepared_dyn_plans", {})
        dyn_key = (key_text, getattr(session.catalog, "version", 0),
                   tuple(sorted((k, repr(v))
                                for k, v in session.properties.items())))
        plan = plans.get(dyn_key)
        if plan is None:
            with mon.phase("plan"):
                plan = EX.plan_statement(session, typed)
            if len(plans) >= MAX_TYPED_ENTRIES:
                plans.clear()
            plans[dyn_key] = plan
        else:
            mon.stats.prepared_plan_hits += 1
        mon.stats.execution_mode = "dynamic"
        host_params = tuple((v, None) for v, _t in bound)
        with mon.phase("execute"):
            ex = EX.Executor(session, monitor=mon, params=host_params)
            return ex.run(plan)

    # coalescing needs ≥1 bound scalar to stack (a 0-param template has
    # no batch axis to map) and a compiled-capable mode
    if mode in ("auto", "compiled") and bound \
            and coalesce_mode(session) != "off":
        gk = (key_text,) + CC.session_fingerprint(session)

        def run_batched(riders, rider_mons):
            with mon.phase("execute"):
                return EX.run_compiled_batched(session, key_text, typed,
                                               riders, rider_mons)

        result = coalescer_for(session).submit(
            session, gk, bound, mon, run_batched, run_typed_solo)
    else:
        result = run_typed_solo()
    if cache_sql is not None and result is not None:
        cols = [{"name": n, "type": str(t).lower()}
                for n, t in result.columns]
        tier.result_store(cache_sql, cols, [list(r) for r in result.rows])
    return result


def _prepared_cache_text(entry, stmt) -> Optional[str]:
    """The canonical result-cache identity of a typed EXECUTE: the
    substituted template text — the SAME key an ad-hoc submission of the
    rendered SELECT produces, so prepared and ad-hoc reads of identical
    values share cache entries.  None when rendering fails (the
    execution path raises the canonical error instead)."""
    from presto_tpu.exec import executor as EX

    try:
        return EX._substitute_parameters(entry.text, stmt.parameters)
    except Exception:
        return None


def _result_from_cache(hit):
    """Result-cache entry -> QueryResult.  Entries store the protocol
    wire shape ({"name","type"} column dicts + list rows), shared with
    direct SELECT submissions through server/protocol.py."""
    from presto_tpu import types as T
    from presto_tpu.session import QueryResult

    columns, rows, _size = hit
    cols = []
    for c in columns:
        try:
            typ = T.parse_type(c["type"])
        except Exception:
            typ = T.VARCHAR
        cols.append((c["name"], typ))
    return QueryResult(cols, [tuple(r) for r in rows])


def _fold_param_literals(parameters) -> Optional[list]:
    """EXECUTE argument exprs -> ast.Literal list (folding unary minus),
    or None when any argument is not a literal."""
    out = []
    for p in parameters:
        neg = False
        while isinstance(p, ast.UnaryOp) and p.op == "-" \
                and isinstance(p.operand, ast.Literal) \
                and isinstance(p.operand.value, (int, float)):
            neg = not neg
            p = p.operand
        if not isinstance(p, ast.Literal):
            return None
        if neg:
            p = ast.Literal(-p.value, p.type_hint)
        out.append(p)
    return out


def _walk_params(node):
    if isinstance(node, ast.Parameter):
        yield node
    if isinstance(node, ast.Node):
        for c in node.children():
            yield from _walk_params(c)


def _count_ast_params(node) -> int:
    return sum(1 for _ in _walk_params(node))


def _count_placeholders(sql: str) -> int:
    n = 0
    in_str = False
    for ch in sql:
        if ch == "'":
            in_str = not in_str
        elif ch == "?" and not in_str:
            n += 1
    return n


def _params_under_subquery(node) -> bool:
    """True when any `?` sits inside a scalar/EXISTS/IN subquery: the
    compiled path evaluates subplans EAGERLY and bakes their values into
    the executable, which would pin the FIRST binding's values."""

    def walk(n, under):
        if isinstance(n, ast.Parameter) and under:
            return True
        u = under or isinstance(
            n, (ast.ScalarSubquery, ast.Exists, ast.InSubquery))
        if isinstance(n, ast.Node):
            return any(walk(c, u) for c in n.children())
        return False

    return walk(node, False)


def _infer_param_types(session, template, n_params: int) -> list:
    """Best-effort parameter types for DESCRIBE INPUT: a `?` compared
    (or combined arithmetically) with a column takes the column's type
    (reference: the analyzer's coercion assigns parameter types the
    same way).  Unresolvable positions report 'unknown'."""
    # column name -> type over every table the template references
    col_types: Dict[str, str] = {}
    for t in _walk_nodes(template, ast.Table):
        try:
            tab = session.catalog.get(t.name)
        except Exception:
            continue
        for c, ty in tab.schema.items():
            col_types.setdefault(c, str(ty).lower())
    out = ["unknown"] * n_params

    def note(param, other):
        if not isinstance(param, ast.Parameter):
            return
        if isinstance(other, ast.Identifier) \
                and other.name in col_types \
                and 0 <= param.position < n_params \
                and out[param.position] == "unknown":
            out[param.position] = col_types[other.name]

    for n in _walk_nodes(template, ast.BinaryOp):
        note(n.left, n.right)
        note(n.right, n.left)
    for n in _walk_nodes(template, ast.Between):
        note(n.low, n.value)
        note(n.high, n.value)
    for n in _walk_nodes(template, ast.InList):
        for item in n.items:
            note(item, n.value)
    for n in _walk_nodes(template, ast.Like):
        if isinstance(n.pattern, ast.Parameter) \
                and 0 <= n.pattern.position < n_params \
                and out[n.pattern.position] == "unknown":
            out[n.pattern.position] = "varchar"
    return out


def _walk_nodes(node, cls):
    if isinstance(node, cls):
        yield node
    if isinstance(node, ast.Node):
        for c in node.children():
            yield from _walk_nodes(c, cls)


# ---------------------------------------------------------------------------
# query coalescing
# ---------------------------------------------------------------------------

#: micro-batch window (ms) a leader holds open collecting riders; a few
#: ms is the point where one saved device dispatch repays the wait many
#: times over (tools/roofline.py --sweep coalesce measures the curve)
COALESCE_WINDOW_MS_DEFAULT = 2.0
#: batch-size ceiling (stacked parameters quantize to pow2 below this)
COALESCE_MAX_BATCH_DEFAULT = 16
#: rider backstop on the leader's batched launch: generous — the first
#: batch of a size bucket pays an XLA compile — and load-bearing only
#: if a leader thread dies without running its finally block (the
#: leader ALWAYS sets the group's done event; an expired rider re-runs
#: solo, same as any other batch fallback)
COALESCE_RIDER_WAIT_S = 300.0


def coalesce_mode(session) -> str:
    """'off' | 'on' | 'auto'.  Env PRESTO_TPU_QUERY_COALESCING=off is
    the process kill switch; session property `query_coalescing`
    accepts off/on/auto or a bool.  `auto` (the default) opens a batch
    window only when another query of the same prepared signature is
    already in flight — an idle EXECUTE never pays the window."""
    env = os.environ.get("PRESTO_TPU_QUERY_COALESCING", "").lower()
    if env in ("off", "0", "false"):
        return "off"
    v = session.properties.get("query_coalescing", "auto")
    if isinstance(v, str):
        lv = v.lower()
        if lv in ("off", "false", "0"):
            return "off"
        if lv in ("on", "true", "1", "force"):
            return "on"
        return "auto"
    return "on" if v else "off"


class _CoalesceGroup:
    """One micro-batch rendezvous: the leader (rider 0) holds the
    window open, closes the group, runs the batched launch, and
    distributes results; riders block on `done` and read their slot."""

    __slots__ = ("riders", "mons", "closed", "full", "done", "results",
                 "fallback")

    def __init__(self, bound, mon):
        self.riders = [bound]
        self.mons = [mon]
        self.closed = False
        self.full = threading.Event()
        self.done = threading.Event()
        self.results = None
        self.fallback = False


class QueryCoalescer:
    """Admission-side query coalescing (ROADMAP 3(a)): concurrent
    EXECUTEs of one prepared signature — same plan fingerprint x
    catalog token x property map, i.e. the same `gk` — that arrive
    within the micro-batch window are grouped, their bound parameters
    stacked into a leading axis, and dispatched as ONE vmap-batched
    executable (exec/executor.run_compiled_batched).  The first
    arrival leads: it waits out `coalesce_window_ms` (or until
    `coalesce_max_batch` riders joined), runs the batch, and hands each
    rider its slot.  ANY batch failure — Unbatchable shapes, tripped
    guards, an injected leader fault — flips the group to fallback and
    every member re-runs solo in its own thread: zero wrong results,
    zero surfaced failures, bounded added latency (the window).

    Per-session like the prepared registry (the protocol server
    multiplexes one session, so this is the server's coalescer)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._groups: Dict[tuple, _CoalesceGroup] = {}
        self._active: Dict[tuple, int] = {}  # gk -> in-flight count
        self.batches = 0
        self.riders_coalesced = 0
        self.fallbacks = 0
        self.window_timeouts = 0  # windows that closed with one member

    def submit(self, session, gk, bound, mon, run_batched, run_solo):
        """Coalescing entry point for one EXECUTE.  `bound`: the
        rider's (value, Type) parameter pairs.  `run_batched(riders,
        mons)` runs the stacked launch; `run_solo()` is the classic
        typed path.  Returns the rider's QueryResult either way."""
        window_s = max(float(session.properties.get(
            "coalesce_window_ms", COALESCE_WINDOW_MS_DEFAULT)), 0.0) / 1e3
        max_batch = max(int(session.properties.get(
            "coalesce_max_batch", COALESCE_MAX_BATCH_DEFAULT)), 1)
        mode = coalesce_mode(session)
        g = None
        idx = 0
        with self._lock:
            cur = self._groups.get(gk)
            if cur is not None and not cur.closed \
                    and len(cur.riders) < max_batch:
                g = cur
                idx = len(g.riders)
                g.riders.append(bound)
                g.mons.append(mon)
                if len(g.riders) >= max_batch:
                    g.full.set()
            elif max_batch > 1 and (
                    mode == "on"
                    or (mode == "auto" and self._active.get(gk, 0) > 0)):
                g = _CoalesceGroup(bound, mon)
                self._groups[gk] = g
            self._active[gk] = self._active.get(gk, 0) + 1
        try:
            if g is None:
                # no concurrency observed (auto mode): run solo, but the
                # _active mark lets the NEXT same-signature arrival open
                # a window while this one executes
                return run_solo()
            if idx > 0:
                return self._ride(g, idx, mon, run_solo)
            return self._lead(gk, g, mon, window_s, run_batched, run_solo)
        finally:
            with self._lock:
                n = self._active.get(gk, 0) - 1
                if n > 0:
                    self._active[gk] = n
                else:
                    self._active.pop(gk, None)

    # -- leader --------------------------------------------------------
    def _lead(self, gk, g, mon, window_s, run_batched, run_solo):
        t0 = time.monotonic()
        with TR.span("coalesce.window"):
            if window_s > 0:
                g.full.wait(timeout=window_s)
        with self._lock:
            g.closed = True  # late arrivals form their own group
            if self._groups.get(gk) is g:
                del self._groups[gk]
        mon.stats.coalesce_ms += (time.monotonic() - t0) * 1000.0
        if len(g.riders) == 1:
            # window expired with no riders: solo, nothing to unstack
            with self._lock:
                self.window_timeouts += 1
            try:
                return run_solo()
            finally:
                g.done.set()
        try:
            # deterministic chaos hook (parallel/faults.py):
            # coalesce:BATCH:<path>:nth:fail kills the leader's launch
            from presto_tpu.parallel import faults as F

            rule = F.client_plan().match("coalesce", "BATCH", str(gk[0]))
            if rule is not None and rule.action == "fail":
                raise RuntimeError("injected fault: coalesce batch leader")
            g.results = run_batched(list(g.riders), list(g.mons))
            mon.stats.coalesce_batches += 1
            with self._lock:
                self.batches += 1
                self.riders_coalesced += len(g.riders)
        except Exception:
            # Unbatchable shapes, tripped guards, injected faults: the
            # whole group degrades to solo re-runs — a genuine query
            # error resurfaces identically from run_solo below
            g.fallback = True
        finally:
            g.done.set()
        if g.fallback:
            mon.stats.coalesce_fallbacks += 1
            with self._lock:
                self.fallbacks += 1
            return run_solo()
        return g.results[0]

    # -- rider ---------------------------------------------------------
    def _ride(self, g, idx, mon, run_solo):
        with TR.span("coalesce.ride"):
            g.done.wait(timeout=COALESCE_RIDER_WAIT_S)
        if g.fallback or g.results is None:
            mon.stats.coalesce_fallbacks += 1
            with self._lock:
                self.fallbacks += 1
            return run_solo()  # the rider's own thread re-runs solo
        return g.results[idx]

    def stats(self) -> dict:
        with self._lock:
            return {
                "batches": self.batches,
                "ridersCoalesced": self.riders_coalesced,
                "fallbacks": self.fallbacks,
                "windowTimeouts": self.window_timeouts,
                "meanBatchSize": round(
                    self.riders_coalesced / self.batches, 2)
                if self.batches else 0.0,
            }


def coalescer_for(session) -> QueryCoalescer:
    """The session's coalescer, created on first use (same lifetime
    rule as the prepared registry)."""
    c = getattr(session, "_query_coalescer", None)
    if c is None:
        c = session._query_coalescer = QueryCoalescer()
    return c


# ---------------------------------------------------------------------------
# result cache
# ---------------------------------------------------------------------------

#: statement head keywords eligible for result caching: pure reads whose
#: results are functions of (text, catalog state, session properties)
_CACHEABLE_HEADS = ("SELECT", "WITH", "VALUES")


def _norm_table_names(names) -> frozenset:
    """Normalize table names for scoped-invalidation matching: both the
    full lowered name and its bare last component, so a write to
    'memory.default.t' still clears entries that read 't'."""
    out = set()
    for n in names:
        n = str(n).lower()
        out.add(n)
        out.add(n.split(".")[-1])
    return frozenset(out)


def referenced_tables(sql: str):
    """Tables a read statement touches (frozenset of normalized names),
    or None when the text cannot be analyzed — None-scoped entries fall
    on EVERY invalidation, so a parse failure degrades to the old
    clear-the-world behavior, never to a stale hit."""
    from presto_tpu.sql import ast
    from presto_tpu.sql.parser import parse

    try:
        stmt = parse(sql)
    except Exception:
        return None
    names = set()

    def walk(node):
        if isinstance(node, ast.Table):
            names.add(node.name)
        if dataclasses.is_dataclass(node):
            for f in dataclasses.fields(node):
                walk(getattr(node, f.name))
        elif isinstance(node, (list, tuple)):
            for x in node:
                walk(x)
        elif isinstance(node, dict):
            for x in node.values():
                walk(x)

    try:
        walk(stmt)
    except Exception:
        return None
    return _norm_table_names(names)


def write_targets(sql: str):
    """Tables a write/DDL statement mutates, or None when the statement
    shape is not recognized (None broadcasts a FULL invalidation)."""
    from presto_tpu.sql import ast
    from presto_tpu.sql.parser import parse

    try:
        stmt = parse(sql)
    except Exception:
        return None
    name = getattr(stmt, "name", None) or getattr(stmt, "table", None)
    if isinstance(stmt, (ast.CreateTableAs, ast.CreateTable,
                         ast.InsertInto, ast.DropTable, ast.Delete,
                         ast.CreateMaterializedView,
                         ast.RefreshMaterializedView,
                         ast.DropMaterializedView)) \
            and isinstance(name, str):
        return _norm_table_names([name])
    return None


class ResultCache:
    """Bounded LRU over materialized results (reference analog: none in
    the OSS reference — this is the hot-dashboard tier every production
    deployment bolts on).  Keys are (text, catalog token, catalog
    version, property fingerprint): an engine write bumps the catalog
    version, so a stale hit is structurally impossible; external
    mutation (e.g. the sqlite connector's backing file) is the
    documented exception, handled by `invalidate()`."""

    def __init__(self, max_entries: int = 256,
                 max_bytes: int = 64 << 20,
                 max_result_rows: int = 10_000):
        self.max_entries = max_entries
        self.max_bytes = max_bytes
        self.max_result_rows = max_result_rows
        self._lock = threading.Lock()
        self._entries: OrderedDict = OrderedDict()
        # parallel map key -> frozenset of referenced tables (or None
        # when the text resisted analysis); entries stay 3-tuples so
        # the protocol wire consumers are untouched
        self._entry_tables: Dict[tuple, Optional[frozenset]] = {}
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        self.stores = 0
        self.evictions = 0
        self.invalidations = 0
        self.invalidations_scoped = 0
        self.invalidations_full = 0

    # -- keying --------------------------------------------------------
    @staticmethod
    def cacheable(session, sql: str) -> bool:
        from presto_tpu.exec.executor import _VOLATILE_RE

        head = sql.lstrip().split(None, 1)
        if not head or head[0].upper() not in _CACHEABLE_HEADS:
            return False
        if _VOLATILE_RE.search(sql) is not None:
            return False
        if getattr(session.txn, "current", None) is not None:
            return False  # snapshot reads must not outlive their txn
        return True

    @staticmethod
    def key(session, sql: str) -> tuple:
        from presto_tpu.exec.compile_cache import catalog_token

        return (sql, catalog_token(session.catalog),
                getattr(session.catalog, "version", 0),
                tuple(sorted((k, repr(v))
                             for k, v in session.properties.items())))

    # -- operations ----------------------------------------------------
    def get(self, session, sql: str):
        if not self.cacheable(session, sql):
            return None
        k = self.key(session, sql)
        with self._lock:
            e = self._entries.get(k)
            if e is None:
                self.misses += 1
                return None
            self._entries.move_to_end(k)
            self.hits += 1
            return e

    def put(self, session, sql: str, columns, rows) -> bool:
        if not self.cacheable(session, sql):
            return False
        if len(rows) > self.max_result_rows:
            return False
        size = _result_bytes(rows)
        if size > self.max_bytes:
            return False
        k = self.key(session, sql)
        version = k[2]
        tables = referenced_tables(sql)
        with self._lock:
            if k in self._entries:
                return True
            self._entries[k] = (columns, rows, size)
            self._entry_tables[k] = tables
            self._bytes += size
            self.stores += 1
            # sweep entries from older catalog versions: they can never
            # hit again (the version is in the key) and would otherwise
            # squat the byte budget until LRU pressure finds them
            stale = [ok for ok in self._entries
                     if ok[1] == k[1] and ok[2] != version]
            for ok in stale:
                self._bytes -= self._entries.pop(ok)[2]
                self._entry_tables.pop(ok, None)
                self.evictions += 1
            while len(self._entries) > self.max_entries \
                    or self._bytes > self.max_bytes:
                ok, (_c, _r, sz) = self._entries.popitem(last=False)
                self._entry_tables.pop(ok, None)
                self._bytes -= sz
                self.evictions += 1
        return True

    def invalidate(self, tables=None) -> None:
        """Explicit invalidation (DDL/DML through the serving tier, or
        external catalog mutation the version cannot see).  With a
        `tables` set, only entries that REFERENCE one of those tables
        fall (plus entries whose reads resisted analysis); None keeps
        the old clear-the-world behavior."""
        with self._lock:
            self.invalidations += 1
            if tables is None:
                self.invalidations_full += 1
                self._entries.clear()
                self._entry_tables.clear()
                self._bytes = 0
                return
            self.invalidations_scoped += 1
            touched = _norm_table_names(tables)
            doomed = [k for k in self._entries
                      if self._entry_tables.get(k) is None
                      or (self._entry_tables[k] & touched)]
            for k in doomed:
                self._bytes -= self._entries.pop(k)[2]
                self._entry_tables.pop(k, None)

    def stats(self) -> dict:
        with self._lock:
            total = self.hits + self.misses
            return {"entries": len(self._entries), "bytes": self._bytes,
                    "hits": self.hits, "misses": self.misses,
                    "stores": self.stores, "evictions": self.evictions,
                    "invalidations": self.invalidations,
                    "invalidationsScoped": self.invalidations_scoped,
                    "invalidationsFull": self.invalidations_full,
                    "hitRate": round(self.hits / total, 4) if total else 0.0}


def _result_bytes(rows) -> int:
    """Cheap result-size estimate: sampled row cost x row count (exact
    accounting would walk every cell of every row on the store path)."""
    if not rows:
        return 64
    sample = rows[:32]
    per_row = 0
    for r in sample:
        per_row += 16
        for v in r:
            per_row += len(v) + 40 if isinstance(v, str) else 16
    return int(per_row / len(sample) * len(rows)) + 64


# ---------------------------------------------------------------------------
# admission + the composed tier
# ---------------------------------------------------------------------------


class AdmissionSlot:
    """One admitted query: the group plus the reservations release must
    return."""

    __slots__ = ("group", "memory_bytes", "wait_ms")

    def __init__(self, group, memory_bytes: int, wait_ms: float):
        self.group = group
        self.memory_bytes = memory_bytes
        self.wait_ms = wait_ms


class ServingTier:
    """Admission + prepared statements + result cache behind one
    surface, embedded by the protocol server and the QPS benchmark."""

    def __init__(self, session, resource_groups: Optional[
            ResourceGroupManager] = None, result_cache: Optional[
            ResultCache] = None):
        self.session = session
        self.resource_groups = resource_groups
        if result_cache is None and bool(
                session.properties.get("result_cache_enabled", True)):
            result_cache = ResultCache(
                max_entries=int(session.properties.get(
                    "result_cache_max_entries", 256)),
                max_bytes=int(session.properties.get(
                    "result_cache_max_bytes", 64 << 20)),
                max_result_rows=int(session.properties.get(
                    "result_cache_max_rows", 10_000)))
        self.result_cache = result_cache
        # engine-path writes (session.sql CTAS/INSERT through
        # exec/writer.py) invalidate through this back-reference — the
        # belt on top of the catalog-version keying, same rule as the
        # protocol path's textual detection
        session._serving_tier = self
        # coordinator fleet (server/fleet.FleetMember): when attached,
        # engine writes broadcast a version-stamped invalidation to peer
        # coordinators and peer broadcasts clear THIS tier's cache.
        # Best-effort both ways — the catalog token+version in every
        # cache key is the correctness backstop (a missed broadcast
        # degrades to a key miss, never a stale hit).
        self.fleet = None
        self.draining = threading.Event()
        self._lock = threading.Lock()
        self.queries_admitted = 0
        self.queries_shed = 0
        self.queries_drained = 0
        self.peak_queue_depth = 0

    # -- admission -----------------------------------------------------
    def admit(self, user: str = "", source: str = "",
              priority: int = 0, abort=None) -> Optional[AdmissionSlot]:
        """Admission BEFORE execution resources: may block (QUEUED),
        raises QueryRejected on shed/timeout/drain.  Returns None when
        no resource-group tree is configured (admission disabled)."""
        rgm = self.resource_groups
        if rgm is None:
            return None

        def aborted():
            if self.draining.is_set():
                return True
            return abort() if abort is not None else False

        mem = int(self.session.properties.get("query_max_memory_bytes", 0))
        timeout = float(self.session.properties.get(
            "admission_queue_timeout_s", 60.0))
        t0 = time.monotonic()
        try:
            group = rgm.acquire(user, source, priority=priority,
                                timeout=timeout, memory_bytes=mem,
                                abort=aborted)
        except QueryRejected as e:
            with self._lock:
                if e.code == "QUEUE_FULL":
                    self.queries_shed += 1
                elif e.code == "SERVER_SHUTTING_DOWN":
                    self.queries_drained += 1
            raise
        wait_ms = (time.monotonic() - t0) * 1000.0
        with self._lock:
            self.queries_admitted += 1
            depth = sum(i["queued"] for i in rgm.info()
                        if i["name"] == "global")
            self.peak_queue_depth = max(self.peak_queue_depth, depth)
        return AdmissionSlot(group, mem, wait_ms)

    def release(self, slot: Optional[AdmissionSlot],
                cpu_s: float = 0.0) -> None:
        if slot is not None and self.resource_groups is not None:
            self.resource_groups.release(slot.group, cpu_s=cpu_s,
                                         memory_bytes=slot.memory_bytes)

    def drain(self) -> None:
        """Graceful shutdown: queued admission waiters abort with
        SERVER_SHUTTING_DOWN instead of holding the drain open."""
        self.draining.set()

    # -- result cache --------------------------------------------------
    def result_lookup(self, sql: str):
        if self.result_cache is None:
            return None
        return self.result_cache.get(self.session, sql)

    def result_store(self, sql: str, columns, rows) -> None:
        if self.result_cache is not None:
            self.result_cache.put(self.session, sql, columns, rows)

    def on_write_statement(self, tables=None) -> None:
        """Explicit invalidation rule: any non-read statement through
        the tier invalidates the cache (belt) on top of the catalog-
        version keying (suspenders).  `tables` scopes the invalidation
        to entries referencing the written tables — a write to one hot
        table no longer evicts every OTHER dashboard's entries; None
        (unanalyzable statement) keeps the full clear.  With a fleet
        attached, the write also broadcasts a version-stamped
        invalidation carrying the same table set so PEER coordinators
        drop their pre-write entries promptly (fleet_invalidate knob;
        a dropped broadcast still misses on the bumped version key)."""
        if self.result_cache is not None:
            self.result_cache.invalidate(tables=tables)
        if self.fleet is not None and bool(
                self.session.properties.get("fleet_invalidate", True)):
            from presto_tpu.exec.compile_cache import catalog_token

            self.fleet.broadcast_invalidate(
                catalog_token(self.session.catalog),
                getattr(self.session.catalog, "version", 0),
                tables=tables)

    def attach_fleet(self, member) -> None:
        """Join this tier to a coordinator fleet: writes broadcast
        invalidations (see on_write_statement) and peer broadcasts clear
        this tier's result cache (scoped to the broadcast table set)."""
        self.fleet = member

        def on_invalidate(_token: str, _version: int,
                          tables=None) -> None:
            if self.result_cache is not None:
                self.result_cache.invalidate(tables=tables)

        member.subscribe(on_invalidate=on_invalidate)

    # -- introspection -------------------------------------------------
    def coalescer_stats(self) -> Optional[dict]:
        c = getattr(self.session, "_query_coalescer", None)
        return c.stats() if c is not None else None

    def stats(self) -> dict:
        out = {"admitted": self.queries_admitted,
               "shed": self.queries_shed,
               "drained": self.queries_drained,
               "peakQueueDepth": self.peak_queue_depth,
               "coalescing": self.coalescer_stats(),
               "resultCache": (self.result_cache.stats()
                               if self.result_cache is not None else None)}
        if self.resource_groups is not None:
            out["resourceGroups"] = self.resource_groups.info()
        return out
