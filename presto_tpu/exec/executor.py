"""Local query executor: logical plan -> device kernels -> host result.

Reference parity: the whole worker data plane — LocalExecutionPlanner
emitting DriverFactories + the Driver page-pump loop
(operator/Driver.java:347) — collapsed into a bottom-up plan walk where
each node materializes a whole-column Batch.  What the reference streams
page-at-a-time, XLA executes as fused whole-column programs; streaming
returns at the distributed layer as superstep chunking (parallel/).

Subquery plans (uncorrelated scalars) are evaluated first, like the
reference's gather exchanges from pre-requisite stages.
"""

from __future__ import annotations

import contextlib
import os
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu import types as T
from presto_tpu.batch import (Batch, Column, batch_from_numpy,
                              decode_host_column, to_numpy)
from presto_tpu.exec import compile_cache as CC
from presto_tpu.exec import gather as GA
from presto_tpu.exec import kernels as K
from presto_tpu.exec.compiler import EvalContext, eval_expr, eval_predicate, to_column
from presto_tpu.observe import names as NM
from presto_tpu.observe import trace as TR
from presto_tpu.plan import ir
from presto_tpu.plan import nodes as P
from presto_tpu.plan.optimizer import optimize
from presto_tpu.plan.planner import Planner, SemanticError
from presto_tpu.session import QueryResult
from presto_tpu.sql import ast
from presto_tpu.sql.parser import parse


import threading as _threading

_pool_init_lock = _threading.Lock()


class ExecutionError(Exception):
    pass


def _merge_sort_stats(stats, counts: dict) -> None:
    """Fold an executor's sort-economics + dynamic-filtering +
    spill-degradation + adaptive-aggregation counters into QueryStats."""
    for k in ("sorts_taken", "sorts_elided", "sort_memo_hits",
              "ordering_guard_trips",
              "df_filters_produced", "df_filters_applied",
              "df_filters_declined",
              "df_rows_pruned", "df_chunks_pruned", "df_splits_pruned",
              "chunks_run", "lineitem_window_lines", "chunk_loop_redos",
              "fragments_fused", "exchange_bytes_host",
              "exchange_bytes_collective", "exchange_bytes_sketch",
              "approx_rewrites",
              "spill_partitions", "spill_bytes", "spill_restores",
              "spill_recursions",
              "partial_aggs_bypassed", "partial_aggs_reenabled",
              "aggs_fused", "aggs_unfused",
              "index_joins_packed", "index_joins_keyed",
              "window_functions", "grouping_set_branches",
              "grouping_set_sources", "grouping_set_state_rows",
              "grouping_set_merge_rows"):
        setattr(stats, k, getattr(stats, k, 0) + int(counts.get(k, 0)))
    if counts.get("partial_agg_ratio"):
        # a gauge, not a sum: the last ratio a partial stage observed
        stats.partial_agg_ratio = float(counts["partial_agg_ratio"])
    for k, v in counts.items():
        # "agg_strategy::<name>" -> QueryStats.agg_strategy[name] (the
        # per-strategy execution counter, exported with labels)
        if k.startswith("agg_strategy::") and v:
            name = k.split("::", 1)[1]
            stats.agg_strategy[name] = \
                stats.agg_strategy.get(name, 0) + int(v)
    if counts.get("df_wait_ms"):
        stats.df_wait_ms = getattr(stats, "df_wait_ms", 0.0) \
            + float(counts["df_wait_ms"])
    # degradation_tier is a high-water mark, not a sum
    stats.degradation_tier = max(getattr(stats, "degradation_tier", 0),
                                 int(counts.get("degradation_tier", 0)))
    # legacy aliases (pre-round-15 dashboards + tests key on these)
    stats.spilled_partitions = getattr(stats, "spilled_partitions", 0) \
        + int(counts.get("spill_partitions", 0))
    stats.spilled_bytes = getattr(stats, "spilled_bytes", 0) \
        + int(counts.get("spill_bytes", 0))
    # spill-I/O recovery events ride the recovery dict (the
    # docs/ROBUSTNESS.md schema): enospc failures + transparent rewrites
    for k in ("spill_enospc", "spill_rewrites", "spill_df_resident"):
        if counts.get(k):
            rec = getattr(stats, "recovery", None)
            if rec is not None:
                rec[k] = rec.get(k, 0) + int(counts[k])


class StaticFallback(Exception):
    """Raised when a plan shape can't be made static (missing stats /
    unbounded join fanout); auto mode falls back to eager execution."""


def execute_query(session, text: str) -> QueryResult:
    """Query lifecycle wrapper: stats + events around the actual dispatch
    (reference: SqlQueryManager.createQuery + QueryStateMachine +
    QueryMonitor events, execution/SqlQueryManager.java:299)."""
    from presto_tpu.observe.stats import QueryMonitor

    mon = QueryMonitor.begin(session, text)
    from presto_tpu import session_ctx
    from presto_tpu.exec import compile_cache as CC
    from presto_tpu.observe import profile as PR
    from presto_tpu.observe import trace as TR

    session_ctx.activate(session)  # zone + query-stable now()
    CC.configure(session)  # honor a per-session compile_cache_dir
    try:
        # tracer activation makes nested instrumentation (compile
        # spans, cluster RPCs, chunked fragments) land on THIS query's
        # trace; maybe_profile wraps the query in jax.profiler capture
        # when profile_query / PRESTO_TPU_PROFILE asks for one
        with CC.recording(mon.stats), TR.activate(mon.tracer), \
                PR.maybe_profile(session):  # compile-economics counters
            with mon.phase("parse"):
                stmt = parse(text)
            result = _dispatch_statement(session, text, stmt, mon)
        mon.finish(result)
        result.stats = mon.stats  # this query's stats, race-free under
        return result             # concurrent sessions (vs last_stats)
    except BaseException as e:
        mon.fail(e)
        raise


def _dispatch_statement(session, text: str, stmt, mon) -> QueryResult:
    if isinstance(stmt, ast.Prepare):
        # serving-tier registry (server/serving.py): parses + validates
        # the template ONCE, infers parameter types for DESCRIBE INPUT,
        # and mirrors into session.prepared_statements (compat surface)
        from presto_tpu.server import serving as SV

        SV.prepare(session, stmt.name, stmt.statement_text)
        return QueryResult([("result", T.BOOLEAN)], [(True,)])
    if isinstance(stmt, ast.Execute):
        # typed aval-abstracted binding when possible (plan + executable
        # shared across parameter values), else text substitution
        from presto_tpu.server import serving as SV

        return SV.execute_prepared(session, stmt, mon, _dispatch_statement)
    if isinstance(stmt, ast.Deallocate):
        from presto_tpu.server import serving as SV

        SV.deallocate(session, stmt.name)  # unknown name is an error
        return QueryResult([("result", T.BOOLEAN)], [(True,)])
    if isinstance(stmt, ast.TransactionStatement):
        if stmt.action == "START":
            session.txn.begin(stmt.read_only)
        elif stmt.action == "COMMIT":
            session.txn.commit()
        else:
            session.txn.rollback()
        return QueryResult([("result", T.BOOLEAN)], [(True,)])
    if isinstance(stmt, ast.SetSession):
        session.access_control.check_can_set_session_property(
            session.user, stmt.name)
        session.set(stmt.name, stmt.value)
        return QueryResult([("result", T.BOOLEAN)], [(True,)])
    if isinstance(stmt, ast.ShowTables):
        from presto_tpu.exec.matview import MV_PREFIX

        # MV backing tables are engine-internal; SHOW MATERIALIZED VIEWS
        # lists the views themselves
        rows = sorted((t,) for t in session.catalog.tables
                      if not t.startswith(MV_PREFIX))
        return QueryResult([("Table", T.VARCHAR)], rows)
    if isinstance(stmt, ast.ShowColumns):
        t = session.catalog.get(stmt.table)
        rows = [(c, str(ty)) for c, ty in t.schema.items()]
        # recorded physical-layout properties surface as trailing
        # marker rows (tables without a recorded layout are unchanged)
        from presto_tpu.exec.writer import describe_extra_rows

        rows += describe_extra_rows(t)
        return QueryResult([("Column", T.VARCHAR), ("Type", T.VARCHAR)], rows)
    if isinstance(stmt, ast.ShowFunctions):
        from presto_tpu.functions import aggregate as _agg
        from presto_tpu.functions import scalar as _sc

        rows = sorted(
            [(n, "scalar") for n in _sc.REGISTRY
             if not n.startswith("$")]
            + [(n, "aggregate") for n in _agg.AGG_NAMES]
            + [(n, "window") for n in _agg.WINDOW_ONLY])
        return QueryResult(
            [("Function", T.VARCHAR), ("Type", T.VARCHAR)], rows)
    if isinstance(stmt, ast.ShowSession):
        rows = sorted((k, str(v)) for k, v in session.properties.items())
        return QueryResult(
            [("Name", T.VARCHAR), ("Value", T.VARCHAR)], rows)
    if isinstance(stmt, ast.ShowCatalogs):
        rows = sorted((q,) for q in session.catalog.known_qualifiers)
        return QueryResult([("Catalog", T.VARCHAR)], rows)
    if isinstance(stmt, ast.ShowSchemas):
        schemas = {"default"}
        for name in session.catalog.tables:
            parts = name.split(".")
            if len(parts) >= 2:
                schemas.add(parts[-2])
        return QueryResult([("Schema", T.VARCHAR)],
                           sorted((s,) for s in schemas))
    if isinstance(stmt, ast.ShowStats):
        # reference: ShowStatsRewrite — per-column connector statistics
        # plus the table row-count summary row
        t = session.catalog.get(stmt.table)
        rows = []
        for c in t.schema:
            st = t.column_stats(c)
            rows.append((c,
                         float(st.ndv) if st is not None
                         and st.ndv is not None else None,
                         st.min if st is not None else None,
                         st.max if st is not None else None,
                         None))
        rows.append((None, None, None, None, float(t.row_count())))
        return QueryResult(
            [("column_name", T.VARCHAR),
             ("distinct_values_count", T.DOUBLE),
             ("low_value", T.DOUBLE), ("high_value", T.DOUBLE),
             ("row_count", T.DOUBLE)], rows)
    if isinstance(stmt, ast.Explain):
        if stmt.analyze:
            text_plan = explain_analyze_text(session, stmt.statement, mon)
        elif stmt.type_ == "VALIDATE":
            # reference: ExplainType.VALIDATE — analysis only
            plan_statement(session, stmt.statement)
            return QueryResult([("Valid", T.BOOLEAN)], [(True,)])
        elif stmt.type_ == "DISTRIBUTED":
            text_plan = explain_distributed_text(session, stmt.statement)
        else:
            text_plan = explain_text(session, stmt.statement)
        return QueryResult([("Query Plan", T.VARCHAR)], [(text_plan,)])
    if isinstance(stmt, ast.DescribeInput):
        # reference: DescribeInputRewrite — parameter positions + types
        # inferred from the template's column comparisons (serving tier;
        # positions the inference cannot resolve report 'unknown')
        from presto_tpu.server import serving as SV

        rows = SV.describe_input(session, stmt.name)
        return QueryResult([("Position", T.BIGINT), ("Type", T.VARCHAR)],
                           rows)
    if isinstance(stmt, ast.DescribeOutput):
        # reference: DescribeOutputRewrite — plan with parameters bound
        # to NULL, report output names and types
        prepared = getattr(session, "prepared_statements", {}).get(stmt.name)
        if prepared is None:
            raise ExecutionError(f"prepared statement '{stmt.name}' not found")
        null_params = [ast.Literal(None)] * _count_placeholders(prepared)
        bound = _substitute_parameters(prepared, null_params)
        plan = plan_statement(session, parse(bound))
        types = dict(plan.root.source.outputs())
        rows = [(n, str(types.get(s, T.VARCHAR)).lower())
                for n, s in zip(plan.root.names, plan.root.symbols)]
        return QueryResult(
            [("Column Name", T.VARCHAR), ("Type", T.VARCHAR)], rows)
    if isinstance(stmt, ast.CreateTableAs):
        # PageSink write pipeline (exec/writer.py): TableWriter /
        # TableFinish plan, staged sinks, bucketed/sorted/partitioned
        # layout, atomic commit
        from presto_tpu.exec import writer as W

        return W.run_write(session, text, stmt, mon)
    if isinstance(stmt, ast.ShowCreateTable):
        from presto_tpu.exec import writer as W

        t = session.catalog.get(stmt.table)
        return QueryResult([("Create Table", T.VARCHAR)],
                           [(W.render_create_table(t),)])
    if isinstance(stmt, ast.CreateTable):
        session.access_control.check_can_create_table(session.user, stmt.name)
        if stmt.name in session.catalog:
            if stmt.if_not_exists:
                return QueryResult([("result", T.BOOLEAN)], [(True,)])
            raise ExecutionError(f"Table '{stmt.name}' already exists")
        schema = {c: T.parse_type(t) for c, t in stmt.columns}
        session.txn.record_create(stmt.name)
        _create_table(session, stmt.name, schema, stmt.properties, None)
        return QueryResult([("result", T.BOOLEAN)], [(True,)])
    if isinstance(stmt, ast.DropTable):
        session.access_control.check_can_drop_table(session.user, stmt.name)
        if stmt.name in session.catalog:
            t = session.catalog.get(stmt.name)
            session.txn.record_drop(t)
            if session.txn.current is None and hasattr(t, "drop_data"):
                t.drop_data()  # engine-managed storage goes with the table
        session.catalog.drop(stmt.name, stmt.if_exists)
        return QueryResult([("result", T.BOOLEAN)], [(True,)])
    if isinstance(stmt, ast.InsertInto):
        from presto_tpu.exec import writer as W

        return W.run_write(session, text, stmt, mon)
    if isinstance(stmt, ast.Delete):
        n = _delete_from(session, stmt)
        return QueryResult([("rows", T.BIGINT)], [(n,)])
    if isinstance(stmt, ast.CreateMaterializedView):
        from presto_tpu.exec import matview as MV

        return MV.create(session, stmt, mon)
    if isinstance(stmt, ast.RefreshMaterializedView):
        from presto_tpu.exec import matview as MV

        return MV.refresh(session, stmt, mon)
    if isinstance(stmt, ast.DropMaterializedView):
        from presto_tpu.exec import matview as MV

        return MV.drop(session, stmt, mon)
    if isinstance(stmt, ast.ShowMaterializedViews):
        from presto_tpu.exec import matview as MV

        return MV.show(session)

    if isinstance(stmt, ast.QueryStatement) \
            and getattr(session.catalog, "matviews", None):
        # MV-routed serving: a SELECT provably contained in a
        # materialized view reads the freshest snapshot instead of
        # executing (exec/matview.py try_route)
        from presto_tpu.exec import matview as MV

        routed = MV.try_route(session, stmt, mon)
        if routed is not None:
            return routed

    if session.properties.get("distributed", False):
        from presto_tpu.parallel.dist_executor import run_distributed
        from presto_tpu.plan.distribute import Undistributable

        try:
            with mon.phase("execute"):
                mon.stats.execution_mode = "distributed"
                return run_distributed(session, text, stmt, mon=mon)
        except (Undistributable, StaticFallback,
                jax.errors.ConcretizationTypeError,
                jax.errors.TracerArrayConversionError) as e:
            _note_fallback(mon, "distributed", e)  # single-device below
    mode = session.properties.get("execution_mode", "auto")
    if mode in ("auto", "compiled", "chunked"):
        # grouped/chunked execution when a scanned table exceeds the HBM
        # residency threshold (reference: grouped execution, Lifespan)
        from presto_tpu.exec import chunked as CH

        needs_chunks = False
        plan_probe = None
        warm_key = query_cache_key(session, text)
        if warm_key in getattr(session, "_chunked_cache", {}):
            needs_chunks = True  # memo hit: skip the planning probe
        elif mode == "chunked" or CH.catalog_may_need_chunks(session):
            try:
                plan_probe = plan_statement(session, stmt)
                needs_chunks = CH.chunk_plan_needed(session, plan_probe)
            except (SemanticError, ExecutionError, KeyError) as e:
                # the planner's own refusals: the path below plans again
                # and reports them to the user as the query's error
                _note_fallback(mon, "chunk probe", e)
        if needs_chunks or mode == "chunked":
            try:
                with mon.phase("execute"):
                    mon.stats.execution_mode = "chunked"
                    return CH.run_chunked(session, stmt, text, mon=mon)
            except (CH.Unchunkable, jax.errors.ConcretizationTypeError,
                    jax.errors.TracerArrayConversionError) as e:
                if mode == "chunked":
                    raise
                _note_fallback(mon, "chunked", e)
    if mode in ("auto", "compiled"):
        try:
            with mon.phase("execute"):
                mon.stats.execution_mode = "compiled"
                return run_compiled(session, text, stmt, mon=mon)
        except (StaticFallback, jax.errors.ConcretizationTypeError,
                jax.errors.TracerArrayConversionError) as e:
            if mode == "compiled":
                raise StaticFallback(str(e)) from e
            _note_fallback(mon, "compiled", e)
    mon.stats.execution_mode = "dynamic"
    with mon.phase("plan"):
        plan = plan_statement(session, stmt)
    with mon.phase("execute"):
        ex = Executor(session, monitor=mon)
        return ex.run(plan)


def _note_fallback(mon, dropped: str, e: Exception) -> None:
    """A drop to the next execution mode is how `auto` works, but it is
    never silent: QueryStats.fallback_reason names each mode that was
    tried and why it gave up, next to the execution_mode that ran."""
    why = f"{dropped}: {type(e).__name__}: {e}"[:300]
    prev = mon.stats.fallback_reason
    mon.stats.fallback_reason = f"{prev}; {why}" if prev else why


def _substitute_parameters(sql: str, params) -> str:
    """Replace `?` placeholders (outside string literals) with rendered
    literal parameters (reference: ParameterRewriter)."""
    rendered = []
    for p in params:
        neg = False
        while isinstance(p, ast.UnaryOp) and p.op == "-" \
                and isinstance(p.operand, ast.Literal) \
                and isinstance(p.operand.value, (int, float)):
            neg = not neg
            p = p.operand
        if not isinstance(p, ast.Literal):
            raise ExecutionError("EXECUTE parameters must be literals")
        v = p.value
        if v is None:
            rendered.append("NULL")
        elif isinstance(v, bool):
            rendered.append("TRUE" if v else "FALSE")
        elif isinstance(v, (int, float)):
            rendered.append(repr(-v if neg else v))
        elif getattr(p, "type_hint", None) == "date":
            rendered.append(f"DATE '{v}'")
        elif getattr(p, "type_hint", None) == "timestamp":
            rendered.append(f"TIMESTAMP '{v}'")
        else:
            rendered.append("'" + str(v).replace("'", "''") + "'")
    out = []
    i = n_used = 0
    in_str = False
    for ch in sql:
        if ch == "'":
            in_str = not in_str
        if ch == "?" and not in_str:
            if n_used >= len(rendered):
                raise ExecutionError(
                    f"{len(rendered)} parameters for more placeholders")
            out.append(rendered[n_used])
            n_used += 1
        else:
            out.append(ch)
        i += 1
    if n_used != len(rendered):
        raise ExecutionError(
            f"{len(rendered)} parameters but {n_used} placeholders")
    return "".join(out)


def _create_table(session, name, schema, properties, arrays):
    """Create + register an EMPTY table on the connector chosen by WITH
    properties (reference: StaticCatalogStore catalogs + per-connector
    metadata.createTable; default is the memory connector).  CTAS and
    INSERT route through exec/writer.py instead — `arrays` is kept for
    API compatibility and must be None.  Declared layout properties
    (sorted_by/bucketed_by/partitioned_by) record onto the empty table
    so later INSERTs apply and verify them."""
    assert arrays is None, "CTAS routes through exec/writer.run_write"
    from presto_tpu.exec import writer as W

    connector = W.target_connector(properties, session, name)
    if connector == "hive":
        from presto_tpu.connectors.hive import create_hive_table

        create_hive_table(session.catalog, name, schema, properties)
        return
    try:
        t, _ = W.build_target_table(session, name, schema, properties)
    except W.WriteError as e:
        raise ExecutionError(str(e)) from e
    try:
        wp = W.WriteProperties.parse(properties, schema, connector)
    except W.WriteError as e:
        raise ExecutionError(str(e)) from e
    if wp is not None and hasattr(t, "record_write_properties"):
        t.record_write_properties(wp.to_dict(), ordered=False)
    session.catalog.register(t)


def _delete_from(session, stmt: ast.Delete) -> int:
    """DELETE FROM t [WHERE pred]: evaluate the predicate over the whole
    table (a scan+project plan, preserving row order) and hand the keep
    mask to the connector (reference: MetadataDeleteOperator /
    DeleteOperator)."""
    session.access_control.check_can_delete(session.user, stmt.table)
    table = session.catalog.get(stmt.table)
    if not hasattr(table, "delete_where"):
        raise ExecutionError(f"table '{stmt.table}' does not support DELETE")
    session.txn.record_table_write(table)
    n = table.row_count()
    if stmt.where is None:
        keep = np.zeros(n, dtype=bool)
        return table.delete_where(keep)
    # SELECT <pred> FROM t  — project-only plan, row order == table order
    q = ast.Query(
        body=ast.QuerySpec(
            select=[ast.SelectItem(stmt.where, "__pred__")],
            from_=ast.Table(stmt.table)))
    arrays, _types = execute_plan_to_host(session, ast.QueryStatement(q))
    pred = next(iter(arrays.values()))
    if isinstance(pred, np.ma.MaskedArray):
        pred = pred.filled(False)
    keep = ~np.asarray(pred, dtype=bool)  # NULL predicate rows are kept
    return table.delete_where(keep)


def _collect_tablescans(node: P.PlanNode, out: list):
    if isinstance(node, P.TableScan):
        out.append(node)
    for s in node.sources:
        _collect_tablescans(s, out)


def _static_root_bound(node: P.PlanNode):
    """Row-count bound of the plan root when provable (TopN/Limit under
    Output/Project): lets the compiled program compact its output to k
    rows on device instead of shipping a scan-sized capacity to host."""
    while isinstance(node, (P.Output, P.Project)):
        node = node.source
    if isinstance(node, (P.TopN, P.Limit)) and node.count <= 1_000_000:
        return int(node.count)
    return None


@NM.scoped("k:compact")
def _compact_batch(out: Batch, bound: int) -> Batch:
    """Order-preserving on-device compaction to a fixed capacity.
    top_k over a positional score finds the first `bound` live rows —
    far cheaper on TPU than jnp.nonzero's cumsum+scatter lowering
    (~400ms -> ~10ms at 6M rows, measured via xplane)."""
    cap = out.sel.shape[0]
    pos = jnp.arange(cap, dtype=jnp.int32)
    score = jnp.where(out.sel, cap - pos, 0)  # earliest live = largest
    top = jax.lax.top_k(score, bound)[0]
    idx = jnp.clip(cap - top, 0, cap - 1)
    count = jnp.sum(out.sel)
    # idx is nondecreasing by construction (descending top_k scores →
    # ascending positions, dead-slot tail clips to cap-1), so the
    # materialization is one presorted packed gather — the staged tier
    # streams it through VMEM windows at chunk-compaction sizes
    raw, _ = K.take_columns(out.columns, idx, presorted=True)
    cols = {n: Column(data, valid, out.columns[n].type,
                      out.columns[n].dictionary)
            for n, (data, valid) in raw.items()}
    return Batch(cols, jnp.arange(bound) < count)


# results larger than this skip pack_fetch in favor of to_numpy's
# selective fetch (pull sel, gather survivors) — matches batch.py's
# _COMPACT_THRESHOLD reasoning
_PACK_FETCH_MAX = 262_144


def _plan_has_long_decimal(node) -> bool:
    import dataclasses as _dc

    for _s, t in node.outputs():
        if getattr(t, "is_decimal", False) and t.is_long_decimal:
            return True
    for f in _dc.fields(node):
        v = getattr(node, f.name)
        if isinstance(v, P.PlanNode) and _plan_has_long_decimal(v):
            return True
        if isinstance(v, list) and any(
                isinstance(x, P.PlanNode) and _plan_has_long_decimal(x)
                for x in v):
            return True
    return False


import re as _re

#: functions whose value must differ between executions of the SAME query
#: text (reference: FunctionMetadata deterministic=false / the session
#: start instant).  A cached compiled program bakes their values in at
#: trace time, so volatile queries key the program caches per query.
_VOLATILE_RE = _re.compile(
    r"\b(?:now|random|rand|uuid|shuffle)\s*\("
    r"|\bcurrent_(?:date|time|timestamp)\b|\blocaltime(?:stamp)?\b"
    r"|\btablesample\b",  # lowers to a random() filter
    _re.IGNORECASE)


def _volatile_nonce(text: str) -> int:
    """0 for deterministic queries (cache shared across executions);
    the per-query sequence number otherwise (every execution retraces,
    so now()/random() are fresh — matching per-query semantics)."""
    if _VOLATILE_RE.search(text) is None:
        return 0
    from presto_tpu import session_ctx

    return session_ctx.query_seq()


def query_cache_key(session, text: str) -> tuple:
    """The per-session program-cache key shared by the compiled and
    chunked executors (and EXPLAIN ANALYZE's profiled lookups): raw
    text (whitespace normalization would merge queries differing only
    inside string literals) x catalog version x the full property map x
    the volatile nonce."""
    return (text, getattr(session.catalog, "version", 0),
            tuple(sorted((k, repr(v))
                         for k, v in session.properties.items())),
            _volatile_nonce(text))


def _program_tag(plan_fp: Optional[str], plan: P.QueryPlan,
                 batch: int = 0) -> Optional[str]:
    """What tells this program's HLO module from another query's in a
    profile (compile_cache.build_jit): the head of the plan's fingerprint,
    the same in every process, the marks of the scopes younger than
    SCOPE_VERSION that the plan opens (observe/names.LATE_SCOPES), and
    the batch width of a coalesced one."""
    if plan_fp is None:
        return None
    marks = "".join(NM.late_scope_marks(n)
                    for n in [plan.root, *plan.subplans.values()])
    return plan_fp[:8] + marks + (f"b{batch}" if batch else "")


def bind_param_values(session, params):
    """Host (value, Type) pairs -> device 0-d scalars with the dtypes the
    traced program expects.  DOUBLE follows the session's
    float32_compute lane so a bound parameter never promotes an f32
    column expression back to f64 (serving tier, server/serving.py)."""
    f32 = bool(session.properties.get("float32_compute", False))
    out = []
    for v, t in params:
        dt = t.numpy_dtype()
        if f32 and t.name == "DOUBLE":
            dt = jnp.float32
        out.append(jnp.asarray(v, dtype=dt))
    return tuple(out)


def run_compiled(session, text: str, stmt, mon=None, params=None) -> QueryResult:
    """Compiled execution: the WHOLE plan traces into one jitted XLA
    program over the scan batches (the reference compiles expressions to
    bytecode per operator, sql/gen/; we compile the entire fragment DAG —
    XLA fuses scan->filter->project->agg->join chains end to end).

    Static shapes come from connector stats (plan/stats.py).  Runtime
    guards verify the static assumptions (group capacity, join fanout);
    a tripped guard re-runs the query in dynamic eager mode.

    `params`: prepared-statement bindings as (host_value, Type) pairs
    (server/serving.py).  They trace as 0-d device scalars, so the
    executable is VALUE-free: the memo keys on their avals and a new
    binding is a device transfer, not a retrace — the caller's `text`
    must then be the type-signature key, not the rendered SQL."""
    cache = getattr(session, "_compiled_cache", None)
    if cache is None:
        cache = session._compiled_cache = {}
    host_params = tuple((v, None) for v, _t in params) \
        if params is not None else None
    key = query_cache_key(session, text)
    entry = cache.get(key)
    if entry == "DYNAMIC":  # static assumptions known-violated for this query
        plan = plan_statement(session, stmt)
        return Executor(session, monitor=mon, params=host_params).run(plan)
    if entry is None:
        plan = plan_statement(session, stmt)
        if _plan_has_long_decimal(plan.root):
            # two-limb Int128 columns don't pack through the compiled
            # fetch plane yet; the dynamic executor carries them exactly
            cache[key] = "DYNAMIC"
            return Executor(session, monitor=mon, params=host_params).run(plan)
        # uncorrelated scalar subqueries: evaluate eagerly (tiny), bake in;
        # populate ctx as we go — later subplans may reference earlier ones
        # trace-time sort routing decisions, and what the planner counted
        sort_counts = {"grouping_set_branches": plan.grouping_set_branches}
        ex0 = Executor(session, sort_stats=sort_counts)
        scalar_results = ex0.ctx.scalar_results
        for pid, sub in sorted(plan.subplans.items()):
            scalar_results[pid] = _single_value(ex0.exec_node(sub))
        scan_nodes: list = []
        _collect_tablescans(plan.root, scan_nodes)

        bound = _static_root_bound(plan.root)
        f32 = bool(session.properties.get("float32_compute", False))
        batches = [scan_batch(session.catalog.get(n.table), n, f32)
                   for n in scan_nodes]
        pvals = bind_param_values(session, params) \
            if params is not None else None
        # process-wide executable memo (exec/compile_cache.py): keyed by
        # the plan's serde fingerprint + catalog identity + properties +
        # scan dtype layout, so a second session (or the same SQL under
        # a different text) reuses the executable instead of retracing.
        # Baked scalar-subquery values ride the key: same catalog+plan
        # => same values, anything else must not share.
        plan_fp = CC.plan_fingerprint(
            (plan.root, sorted(plan.subplans.items())))
        gkey = None if plan_fp is None else CC.fingerprint(
            "compiled", plan_fp, CC.session_fingerprint(session),
            _volatile_nonce(text), CC.avals_fingerprint(batches),
            CC.avals_fingerprint(pvals) if pvals is not None else "",
            sorted(scalar_results.items()))

        def build():
            meta_box: list = []  # static pack layout, set at trace time

            def trace(batches, pvals):
                ex = Executor(session, static=True,
                              scan_inputs={id(n): b for n, b
                                           in zip(scan_nodes, batches)},
                              sort_stats=sort_counts)
                ex.ctx.scalar_results = scalar_results
                if pvals is not None:
                    ex.ctx.params = tuple((pv, None) for pv in pvals)
                out = ex.exec_node(plan.root)
                if bound is not None and out.sel.shape[0] > 4 * bound:
                    out = _compact_batch(out, bound)
                if ex.guards:
                    guard = jnp.any(jnp.stack(
                        [jnp.asarray(g) for g in ex.guards]))
                else:
                    guard = jnp.asarray(False)
                meta_box.clear()
                if out.capacity > _PACK_FETCH_MAX or any(
                        getattr(c.data, "ndim", 1) > 1
                        for c in out.columns.values()):
                    # unbounded root over a scan-sized capacity — or a
                    # matrix-shaped column (sketch state, Int128 limbs)
                    # the u32 pack cannot flatten: keep the Batch so
                    # to_numpy's selective fetch (pull sel, gather
                    # survivors) can avoid shipping full columns
                    meta_box.append(None)
                    return out, guard
                # flat buffer -> ONE host fetch (see kernels.pack_fetch)
                buf, meta = K.pack_fetch(out, guard)
                meta_box.append(meta)
                return buf

            # AOT lower+compile: traces now (may raise StaticFallback),
            # counts compiles/compile_ms, and loads from the persistent
            # disk cache when this program was compiled before.  The
            # parameterless signature is kept distinct so existing
            # programs keep their persistent-cache identity.
            if params is None:
                def fn(batches):
                    return trace(batches, None)

                jitted = CC.build_jit(fn, example=(batches,),
                                       tag=_program_tag(plan_fp, plan))
            else:
                def fn(batches, pvals):
                    return trace(batches, pvals)

                jitted = CC.build_jit(fn, example=(batches, pvals),
                                       tag=_program_tag(plan_fp, plan))
            return (plan, jitted, scan_nodes, meta_box[0],
                    dict(sort_counts))

        # cache only after success; sort_counts are the program's
        # trace-time routing decisions, replayed into stats per run
        entry = CC.get_or_build(gkey, build)
        cache[key] = entry
        plan, jitted, scan_nodes, meta, sort_counts = entry
        with TR.span("exec.dispatch"):
            buf = jitted(batches) if params is None \
                else jitted(batches, pvals)
    else:
        plan, jitted, scan_nodes, meta, sort_counts = entry
        with TR.span("exec.dispatch"):
            f32 = bool(session.properties.get("float32_compute", False))
            batches = [scan_batch(session.catalog.get(n.table), n, f32)
                       for n in scan_nodes]
            if params is None:
                buf = jitted(batches)
            else:
                # warm prepared EXECUTE: binding is a device transfer into
                # the cached executable — no parse, no plan, no compile
                buf = jitted(batches, bind_param_values(session, params))
    if mon is not None:
        _merge_sort_stats(mon.stats, sort_counts)
    ex = Executor(session)
    if meta is None:  # sparse/unbounded result: selective to_numpy fetch
        out_batch, guard = buf
        with TR.span("exec.materialize"):  # its own fetches are inside
            result, guard_h = ex.materialize(plan, out_batch, extra=guard)
    else:
        # single device fetch: result columns + guard ride one buffer
        with TR.span("exec.wait_fetch"):
            fetched = jax.device_get(buf)
        with TR.span("exec.materialize"):
            datas, sel, guard_h = K.unpack_fetch(fetched, meta)
            result = ex.materialize_host(plan, meta, datas, sel)
    if bool(guard_h):
        # static assumption violated (incl. a tripped ordering-claim
        # monotonicity guard); data is static so it will trip again —
        # remember to go straight to dynamic next time (no retrace loop)
        cache[key] = "DYNAMIC"
        plan2 = plan_statement(session, stmt)
        return Executor(session, monitor=mon, params=host_params).run(plan2)
    return result


class Unbatchable(Exception):
    """Raised when a prepared program's shape cannot serve a coalesced
    batch (long decimals, unbounded pack-skipping roots, trace failures
    under vmap): the coalescer's riders re-run solo — never a wrong
    result, never a stall."""


def run_compiled_batched(session, text: str, stmt, params_list,
                         mons) -> list:
    """Query coalescing's device lane: serve N concurrent EXECUTEs of
    ONE prepared signature with ONE XLA launch (server/serving.py's
    QueryCoalescer is the admission-side batcher that collects them).

    The PR-6 symbolic-parameter channel makes the prepared trace
    value-free, so batching is a `jax.vmap` of that same trace over a
    LEADING parameter axis: each rider's bound scalars stack into
    shape-(B,) arrays, the scan batches broadcast (in_axes=None — the
    table is shared, only the parameters vary), and the packed result
    buffer comes back with a leading batch axis that unstacks into
    per-rider results.  Batch sizes quantize to the next power of two
    (the PR-4 `_pow2` discipline) with pad slots filled by replaying
    rider 0's values — a padded slot computes a real (discarded) result,
    so near-identical batch sizes share ONE executable instead of
    minting a fresh compile per arrival count.  The executable memoizes
    in exec/compile_cache.py keyed by (plan fingerprint x session
    fingerprint x scan avals x stacked-parameter avals), so a warm
    coalesced batch records compiles == 0.

    `params_list`: one (host_value, Type)-pair tuple per rider, all of
    the same type signature.  `mons`: the riders' QueryMonitors (batch
    facts + sort economics are recorded per rider).  Returns one
    QueryResult per rider, in order.  Raises Unbatchable when this
    program cannot batch; the caller re-runs every rider solo."""
    from presto_tpu.exec.chunked import _pow2

    cache = getattr(session, "_coalesced_cache", None)
    if cache is None:
        cache = session._coalesced_cache = {}
    nbatch = len(params_list)
    bpad = _pow2(nbatch)
    solo_key = query_cache_key(session, text)
    if getattr(session, "_compiled_cache", {}).get(solo_key) == "DYNAMIC":
        # static assumptions known-violated for this signature: the solo
        # path already degraded to dynamic — batching would re-trip
        raise Unbatchable("signature marked DYNAMIC")
    key = (solo_key, bpad)

    def stack_params():
        cols = []
        for j in range(len(params_list[0])):
            vals = [bind_param_values(session, (p[j],))[0]
                    for p in params_list]
            vals += [vals[0]] * (bpad - nbatch)  # pad: replay rider 0
            cols.append(jnp.stack(vals))
        return tuple(cols)

    entry = cache.get(key)
    if entry is None:
        plan = plan_statement(session, stmt)
        if _plan_has_long_decimal(plan.root):
            raise Unbatchable("long-decimal output")
        sort_counts = {}
        ex0 = Executor(session, sort_stats=sort_counts)
        scalar_results = ex0.ctx.scalar_results
        for pid, sub in sorted(plan.subplans.items()):
            scalar_results[pid] = _single_value(ex0.exec_node(sub))
        scan_nodes: list = []
        _collect_tablescans(plan.root, scan_nodes)
        bound = _static_root_bound(plan.root)
        f32 = bool(session.properties.get("float32_compute", False))
        batches = [scan_batch(session.catalog.get(n.table), n, f32)
                   for n in scan_nodes]
        stacked = stack_params()
        plan_fp = CC.plan_fingerprint(
            (plan.root, sorted(plan.subplans.items())))
        gkey = None if plan_fp is None else CC.fingerprint(
            "coalesced", plan_fp, CC.session_fingerprint(session),
            CC.avals_fingerprint(batches), CC.avals_fingerprint(stacked),
            sorted(scalar_results.items()))

        def build():
            meta_box: list = []

            def trace_one(batches, pvals):
                ex = Executor(session, static=True,
                              scan_inputs={id(n): b for n, b
                                           in zip(scan_nodes, batches)},
                              sort_stats=sort_counts)
                ex.ctx.scalar_results = scalar_results
                ex.ctx.params = tuple((pv, None) for pv in pvals)
                out = ex.exec_node(plan.root)
                if bound is not None and out.sel.shape[0] > 4 * bound:
                    out = _compact_batch(out, bound)
                if ex.guards:
                    guard = jnp.any(jnp.stack(
                        [jnp.asarray(g) for g in ex.guards]))
                else:
                    guard = jnp.asarray(False)
                if out.capacity > _PACK_FETCH_MAX:
                    # the solo path's selective-fetch lane doesn't have
                    # a batched twin: results this wide stay solo
                    raise Unbatchable("result capacity exceeds the "
                                      "packed-fetch plane")
                buf, meta = K.pack_fetch(out, guard)
                meta_box.clear()
                meta_box.append(meta)
                return buf

            def fn(batches, stacked):
                return jax.vmap(
                    lambda pv: trace_one(batches, pv),
                    in_axes=(0,))(stacked)

            try:
                jitted = CC.build_jit(fn, example=(batches, stacked),
                                       tag=_program_tag(plan_fp, plan, bpad))
            except Unbatchable:
                raise
            except (StaticFallback, jax.errors.ConcretizationTypeError,
                    jax.errors.TracerArrayConversionError) as e:
                raise Unbatchable(str(e)) from e
            return (plan, jitted, scan_nodes, meta_box[0],
                    dict(sort_counts))

        entry = CC.get_or_build(gkey, build)
        cache[key] = entry
        warm = False
    else:
        with TR.span("exec.dispatch"):
            stacked = stack_params()
        warm = True
    plan, jitted, scan_nodes, meta, sort_counts = entry
    with TR.span("exec.dispatch"):
        f32 = bool(session.properties.get("float32_compute", False))
        batches = [scan_batch(session.catalog.get(n.table), n, f32)
                   for n in scan_nodes]
        launched = jitted(batches, stacked)
    with TR.span("exec.wait_fetch"):
        buf, side = jax.device_get(launched)
    with TR.span("exec.materialize"):
        results = []
        any_guard = False
        for i in range(nbatch):
            datas, sel, guard_h = K.unpack_fetch(
                (buf[i], [s[i] for s in side]), meta)
            any_guard = any_guard or bool(guard_h)
            results.append(Executor(session).materialize_host(
                plan, meta, datas, sel))
    if any_guard:
        # a static assumption tripped for at least one binding; the data
        # is static so it would trip again — degrade the whole signature
        # to the dynamic path and re-run every rider solo
        scache = getattr(session, "_compiled_cache", None)
        if scache is None:
            scache = session._compiled_cache = {}
        scache[solo_key] = "DYNAMIC"
        cache.pop(key, None)
        raise Unbatchable("runtime guard tripped in batched program")
    for mon in mons:
        if mon is not None:
            _merge_sort_stats(mon.stats, sort_counts)
            mon.stats.coalesced_batch_size = nbatch
            mon.stats.execution_mode = "compiled"
            if warm:
                mon.stats.prepared_plan_hits += 1
    return results


def plan_statement(session, stmt) -> P.QueryPlan:
    """Plan + authorize: every table the plan scans is checked against
    the session's access control (reference: AccessControlManager
    .checkCanSelectFromColumns during analysis)."""
    if isinstance(stmt, (ast.CreateTableAs, ast.InsertInto)):
        # write statements plan as Output <- TableFinish <- TableWriter
        # over the (normally optimized) query plan (exec/writer.py)
        from presto_tpu.exec import writer as W

        return W.plan_write_statement(session, stmt)
    planner = Planner(session)
    plan = planner.plan_statement(stmt)
    if session.properties.get("optimizer_enabled", True):
        plan = optimize(plan, session)
    scans: list = []
    _collect_tablescans(plan.root, scans)
    for sub in plan.subplans.values():
        _collect_tablescans(sub, scans)
    for t in {n.table for n in scans}:
        session.access_control.check_can_select(session.user, t)
    return plan


def execute_plan_to_host(session, stmt):
    plan = plan_statement(session, stmt)
    ex = Executor(session)
    batch = ex.evaluate(plan)
    out = plan.root
    arrays, sel = to_numpy(batch)
    types = {}
    result = {}
    used = {}
    for name, sym in zip(out.names, out.symbols):
        n = name
        i = used.get(name, 0)
        used[name] = i + 1
        if i:
            n = f"{name}_{i}"
        a = arrays[sym]
        v = a[sel]
        # keep the mask — write sinks must see NULLs to reject/handle them
        result[n] = v if isinstance(v, np.ma.MaskedArray) else np.asarray(v)
        types[n] = dict(out.source.outputs())[sym] if sym in dict(out.source.outputs()) else T.VARCHAR
    return result, types


def explain_text(session, stmt) -> str:
    plan = plan_statement(session, stmt)
    from presto_tpu.plan import stats as S

    memo = {}

    def ann(node):
        try:
            st = S.derive(node, session.catalog, memo)
            return f"  {{rows: {st.est_rows:,.0f}}}"
        except Exception:
            return ""

    lines = [P.plan_tree_str(plan.root, annotate=ann)]
    for pid, sub in sorted(plan.subplans.items()):
        lines.append(f"\nSubplan {pid}:")
        lines.append(P.plan_tree_str(sub, 1, annotate=ann))
    return "\n".join(lines)


def _count_placeholders(sql: str) -> int:
    n = 0
    in_str = False
    for ch in sql:
        if ch == "'":
            in_str = not in_str
        elif ch == "?" and not in_str:
            n += 1
    return n


def explain_distributed_text(session, stmt) -> str:
    """EXPLAIN (TYPE DISTRIBUTED): fragment the optimized plan the way
    the cluster scheduler would and print each fragment (reference:
    PlanPrinter.textDistributedPlan over SubPlan fragments)."""
    from presto_tpu.parallel.cluster import cut_fragments
    from presto_tpu.plan.distribute import Undistributable, distribute

    plan = plan_statement(session, stmt)
    ndev = int(session.properties.get("explain_ndev", 8))
    try:
        dplan = distribute(plan, session, ndev)
    except Undistributable as e:
        return (f"single fragment (undistributable: {e})\n\n"
                + explain_text(session, stmt))
    lines = []
    for f in cut_fragments(dplan.root):
        lines.append(f"Fragment {f.fid}:")
        lines.append(P.plan_tree_str(f.root, 1))
        lines.append("")
    for pid, sub in sorted(dplan.subplans.items()):
        lines.append(f"Subplan {pid}:")
        lines.append(P.plan_tree_str(sub, 1))
        lines.append("")
    return "\n".join(lines).rstrip()


def explain_analyze_text(session, stmt, mon) -> str:
    """EXPLAIN ANALYZE, profiled per execution mode.

    dynamic/auto: execute eagerly with per-node stats and render the
    plan annotated with rows/time (reference: ExplainAnalyzeOperator +
    PlanPrinter stats rendering) — the richest attribution, one host
    sync per operator.

    compiled/chunked (execution_mode set accordingly): execute through
    the REAL compiled path, then attach per-fragment measured wall plus
    XLA cost analysis (FLOPs, HBM bytes, roofline-estimated wall) read
    off the fragment executables — the compiler-sourced attribution for
    programs that have no per-operator boundary at runtime.  Cluster
    mode has its own path (parallel/cluster.ClusterSession handles
    EXPLAIN ANALYZE with per-task attribution from worker spans)."""
    from presto_tpu.observe.stats import annotated_plan

    mode = str(session.properties.get("execution_mode", "auto"))
    if mode == "compiled":
        return _explain_analyze_compiled(session, stmt, mon)
    if mode == "chunked":
        return _explain_analyze_chunked(session, stmt, mon)
    mon.stats.execution_mode = "dynamic"
    mon.collect_node_stats = True  # ANALYZE implies per-node stats
    with mon.phase("plan"):
        plan = plan_statement(session, stmt)
    with mon.phase("execute"):
        ex = Executor(session, monitor=mon)
        result = ex.run(plan)
    mon.stats.output_rows = len(result)
    mon.rows_preset = True  # finish() must not overwrite with the 1-row plan text
    return annotated_plan(plan.root, plan.subplans, mon.stats)


def _phase_summary(stats) -> str:
    ph = ", ".join(f"{k}: {v / 1e6:.1f}ms"
                   for k, v in stats.phase_ns.items())
    return (f"Query {stats.query_id}: {ph}; output rows: "
            f"{stats.output_rows}")


def _explain_analyze_compiled(session, stmt, mon) -> str:
    """Profiled EXPLAIN ANALYZE through run_compiled: the whole plan is
    ONE fused XLA program (one 'fragment'); its cost analysis comes off
    the AOT executable the compiled cache holds."""
    from presto_tpu.observe import profile as PR
    from presto_tpu.observe.stats import trace_summary_line

    mon.stats.execution_mode = "compiled"
    text = mon.stats.sql  # a valid (distinct) program-cache key
    with mon.phase("execute"):
        result = run_compiled(session, text, stmt, mon=mon)
    mon.stats.output_rows = len(result)
    mon.rows_preset = True
    wall_ms = mon.stats.phase_ns.get("execute", 0) / 1e6
    entry = getattr(session, "_compiled_cache", {}).get(
        query_cache_key(session, text))
    lines = []
    if entry is None or entry == "DYNAMIC":
        # static assumptions were violated: the query really ran on the
        # dynamic path — say so instead of attributing a program that
        # never executed
        plan = plan_statement(session, stmt)
        lines.append(P.plan_tree_str(plan.root))
        lines.append("\nFragment 0 (compiled -> DYNAMIC fallback: "
                     "static assumptions violated):")
        lines.append(f"   {PR.cost_line(None, wall_ms, 'dynamic re-run')}")
    else:
        plan, jitted, _scan_nodes, _meta, _sort_counts = entry
        lines.append(P.plan_tree_str(plan.root))
        for pid, sub in sorted(plan.subplans.items()):
            lines.append(f"\nSubplan {pid} (evaluated eagerly, baked "
                         "into the trace):")
            lines.append(P.plan_tree_str(sub, 1))
        cost = PR.executable_cost(jitted)
        lines.append("\nFragment 0 (compiled, whole plan as one fused "
                     "XLA program):")
        lines.append(f"   {PR.cost_line(cost, wall_ms)}")
    lines.append("")
    lines.append(_phase_summary(mon.stats))
    lines.append(trace_summary_line(mon.stats))
    return "\n".join(lines)


def _explain_analyze_chunked(session, stmt, mon) -> str:
    """Profiled EXPLAIN ANALYZE through the chunked executor: one
    attribution block per fragment — measured wall from the per-run
    fragment timings, XLA cost analysis summed over the fragment's
    program family (chunk-loop + fold + compact executables)."""
    from presto_tpu.exec import chunked as CH
    from presto_tpu.observe import profile as PR
    from presto_tpu.observe.stats import trace_summary_line

    mon.stats.execution_mode = "chunked"
    text = mon.stats.sql
    with mon.phase("execute"):
        result = CH.run_chunked(session, stmt, text, mon=mon)
    mon.stats.output_rows = len(result)
    mon.rows_preset = True
    entry = getattr(session, "_chunked_cache", {}).get(
        query_cache_key(session, text))
    lines = []
    if entry is None:
        lines.append("(chunked prepared state unavailable)")
    else:
        _dplan, frags, runner, _table_family, _consumer_eid = entry
        def frag_key(key, fid):
            # runner._jit keys: (fid, mult) for the main program,
            # ("fold"|"compact"|"mesh", fid, ...) for the auxiliaries
            if not isinstance(key, tuple):
                return key == fid
            if key[0] in ("fold", "compact", "mesh"):
                return len(key) >= 2 and key[1] == fid
            return key[0] == fid

        for frag in frags:
            wall_ns = runner.frag_wall_ns.get(frag.fid, 0)
            cost = PR.merge_costs(
                PR.executable_cost(ex)
                for key, ex in runner._jit.items()
                if frag_key(key, frag.fid))
            note = "dynamic fragment" \
                if frag.fid in runner.dynamic_fids else ""
            lines.append(f"Fragment {frag.fid} (chunked"
                         + (", dynamic" if note else "") + "):")
            lines.append(f"   {PR.cost_line(cost, wall_ns / 1e6, note)}")
            lines.append(P.plan_tree_str(frag.root, 1))
            lines.append("")
    lines.append(_phase_summary(mon.stats))
    lines.append(trace_summary_line(mon.stats))
    return "\n".join(lines)


def explain_query(session, text: str, analyze: bool = False) -> str:
    stmt = parse(text)
    if isinstance(stmt, ast.Explain):
        analyze = analyze or stmt.analyze
        stmt = stmt.statement
    if analyze:
        from presto_tpu.observe import profile as PR
        from presto_tpu.observe import trace as TR
        from presto_tpu.observe.stats import QueryMonitor

        mon = QueryMonitor.begin(session, text)
        try:
            with TR.activate(mon.tracer), PR.maybe_profile(session):
                text_plan = explain_analyze_text(session, stmt, mon)
        except BaseException as e:
            mon.fail(e)
            raise
        mon.finish(None)
        return text_plan
    return explain_text(session, stmt)


class Executor:
    # index joins assume whole-table natural-order build batches; sharded
    # executors (DistExecutor, cluster FragmentExecutor) re-split scans
    # and must turn this off (the layout guard would catch it anyway, at
    # the cost of a spurious whole-query dynamic fallback)
    allow_index_join = True

    def __init__(self, session, static: bool = False, scan_inputs=None,
                 monitor=None, mem=None, sort_stats=None, params=None):
        self.session = session
        self.static = static  # compiled mode: no host syncs, static shapes
        self.scan_inputs = scan_inputs  # {node id: Batch} traced jit args
        self.guards = []  # traced bools: True => static assumption violated
        # ordering-aware execution state (plan/properties.py):
        # - sort economics counters (flow into QueryStats)
        # - the per-trace sort-permutation memo: key fingerprint ->
        #   (refs, (skey, order)) so a key sorted once in a fragment is
        #   never sorted again (refs hold the fingerprinted arrays
        #   alive, so a recycled id() can never alias a dead entry)
        # - the runtime CERTAIN-ordering channel: id(Batch) -> (batch,
        #   keys) for orderings this executor constructed itself
        #   (grouped output with an exact pack layout, sort output) —
        #   the only claims Sort/TopN elision may trust without a guard
        self.sort_stats = sort_stats if sort_stats is not None else {
            "sorts_taken": 0, "sorts_elided": 0, "sort_memo_hits": 0,
            "ordering_guard_trips": 0}
        self._sort_memo: Dict[tuple, tuple] = {}
        self._perm_memo: Dict[tuple, tuple] = {}
        # group-id mapping memo (round 17): key fingerprint ->
        # (refs, (gid, rep_rows, n_groups)) — a repeat grouping over
        # identical key arrays (AVG/STDDEV fold passes over a resident
        # build) replays the mapping instead of rebuilding the group
        # index; refs pin the fingerprinted arrays (id-reuse aliasing,
        # same discipline as _sort_memo)
        self._gid_memo: Dict[tuple, tuple] = {}
        self._batch_order: Dict[int, tuple] = {}
        # dynamic filtering (plan/runtime_filters.py): filter id ->
        # device summary (exec/kernels.rf_build), registered by producer
        # joins BEFORE their probe subtree executes; _rf_host carries the
        # host-side min/max Domain for stripe/zone-map pruning (dynamic
        # mode only — static mode must stay sync-free)
        self._rf: Dict[str, dict] = {}
        self._rf_host: Dict[str, object] = {}
        # static mode: expression-level overflow checks (decimal casts)
        # append to the SAME guard list, so a violation aborts the
        # compiled program to the dynamic path, which raises properly
        self.ctx = EvalContext(guards=self.guards if static else None)
        # prepared-statement parameters (server/serving.py): position ->
        # (value, valid) pairs ir.Param evaluation reads
        self.ctx.params = params
        self.monitor = monitor  # QueryMonitor collecting per-node stats
        # memory accounting: only for monitored (top-level) executions —
        # helper executors (subplan eval, CTAS materialization) must not
        # leave reservations behind, since only run() releases them
        if mem is None and not static and monitor is not None:
            from presto_tpu.memory import MemoryPool, QueryMemoryContext

            pool_cap = int(session.properties.get("memory_pool_bytes", 16 << 30))
            with _pool_init_lock:
                pool = getattr(session, "_memory_pool", None)
                if pool is None:
                    pool = session._memory_pool = MemoryPool(pool_cap)
            pool.capacity = pool_cap  # honor property changes mid-session
            mem = QueryMemoryContext(
                monitor.stats.query_id, pool,
                int(session.properties.get("query_max_memory_bytes", 4 << 30)))
        self.mem = mem

    # aggregates whose VALUE depends on input row order (beyond float
    # rounding): reordering their input would change results, not just
    # permute them
    _ORDER_SENSITIVE_AGGS = frozenset({
        "array_agg", "map_agg", "multimap_agg", "arbitrary", "any_value"})

    def mark_order_insensitive(self, root: P.PlanNode, root_flag: bool):
        """Precompute which plan nodes may emit their output in ANY row
        order — the hint behind sort-order materialization (gather.py):
        a join below an aggregation can leave its rows in sorted-gather
        order and skip the inverse permutation, because grouping sorts
        by key anyway and semi-join membership is a set question.

        `root_flag` says whether the ROOT's own output order is free
        (chunked partial fragments feeding a final aggregate/TopN: yes;
        a whole query's result rows: no).  The walk ANDs over every
        path to a node, so a shared DAG subtree feeding one
        order-sensitive consumer stays unmarked."""
        flags: Dict[int, bool] = {}

        def walk(node, flag):
            prev = flags.get(id(node))
            flags[id(node)] = flag if prev is None else (prev and flag)
            t = type(node).__name__
            if t in ("Aggregate", "GroupingSets"):
                # an ordering-exploiting aggregate (presorted grouping
                # hint) WANTS its input order: sort-order-materializing
                # joins below it would scramble the claimed ordering and
                # trade the elided grouping sort for a guard trip
                hints = node.hints if t == "GroupingSets" else [vars(node)]
                walk(node.source, not any(
                    a.fn in self._ORDER_SENSITIVE_AGGS
                    for a in node.aggs.values())
                    and all(h.get("ordering_hint") is None for h in hints))
            elif t in ("Filter", "Project", "Output"):
                # row-wise: input permutation = same output permutation
                walk(node.source, flag)
            elif t == "Join":
                walk(node.left, flag)
                # SEMI/ANTI/MARK consume the build side as a SET
                walk(node.right, True if node.join_type in
                     ("SEMI", "ANTI", "MARK") else flag)
            elif t == "Union":
                for s in node.sources_:
                    walk(s, flag)
            else:
                # Sort/TopN/Limit/Window/Unnest/...: input order shows
                # through (tie-breaking, first-n, frames) — conservative
                for s in getattr(node, "sources", []):
                    walk(s, False)

        walk(root, root_flag)
        self._oi_ids = {i for i, f in flags.items() if f}

    def _order_ok(self, node) -> bool:
        oi = getattr(self, "_oi_ids", None)
        return oi is not None and id(node) in oi

    # ---- ordering-aware execution plumbing ---------------------------
    def _count(self, key: str, n: int = 1) -> None:
        self.sort_stats[key] = self.sort_stats.get(key, 0) + n

    def _ordering_enabled(self) -> bool:
        return bool(self.session.properties.get(
            "ordering_aware_execution", True))

    # ---- dynamic filtering (plan/runtime_filters.py) -----------------
    def _df_enabled(self) -> bool:
        from presto_tpu.plan import runtime_filters as RF

        return RF.enabled(self.session)

    def rf_inject(self, summaries: Dict[str, dict]) -> None:
        """Register remotely produced filter summaries (the cluster side
        channel) so probe scans in this executor consume them."""
        self._rf.update(summaries)

    def _rf_build_complete(self, node) -> bool:
        """May this executor derive a filter from the join's build batch?
        True iff the batch it will see is the COMPLETE build key set.
        Single-device executors always see the whole build; sharded
        executors (DistExecutor, the cluster FragmentExecutor) override
        this — a shard/bucket/split-local build is a PARTIAL key set,
        and a membership filter over a partial set would prune probe
        rows that match on other shards."""
        return True

    def _rf_mask_pays(self, node=None) -> bool:
        """Does a consumer here do more with a filter's membership mask
        than AND it into sel?  Dynamic mode does: pruned rows are
        compacted away, counted, and turned into stripe/zone-map
        Domains.  A static trace has fixed shapes — a masked row costs
        what a live row costs in every operator up to the join, which
        then drops it itself (an INNER/SEMI join IS the filter, without
        false positives) — so compiled programs decline the mask
        (df_filters_declined).  A cluster task overrides this: it does
        not ship a pruned row.  The mesh executor overrides it too: it
        declines for a star join (`node.star_lookup`) and otherwise stays
        as on the parent, though its shapes are fixed like these: no
        cell has priced that mask yet (PERF.md section 7)."""
        return not self.static

    def _rf_register(self, specs, right: Batch) -> None:
        """Producer side: derive + register the build-key summaries of
        one join.  Skips keys the kernels can't summarize (dictionary
        codes, float storage, limb pairs) — the consumer then simply
        never finds the id and runs filter-free."""
        for spec in specs:
            col = right.columns.get(spec["build_sym"])
            if col is None or col.dictionary is not None \
                    or getattr(col.data, "ndim", 1) != 1 \
                    or jnp.issubdtype(col.data.dtype, jnp.floating):
                continue
            live = right.sel
            if col.valid is not None:
                live = live & col.valid
            self._rf[spec["fid"]] = K.rf_build(col, live)
            self._count("df_filters_produced")
            if not self.static:
                # LAZY host min/max domain for stripe/zone-map pruning:
                # the refs are stashed and only synced if a consumer
                # scan's table actually supports domain pushdown —
                # generator/device tables never pay the fetch
                self._rf_host[spec["fid"]] = (col, live)

    def _rf_host_domain(self, fid: str):
        entry = self._rf_host.get(fid)
        if entry is None:
            return None
        from presto_tpu.storage.shard import Domain

        if isinstance(entry, Domain):
            return entry
        col, live = entry
        lo, hi = (int(v) for v in jax.device_get(K.rf_domain(col, live)))
        dom = Domain(lo, hi) if lo <= hi else Domain(values=[])
        self._rf_host[fid] = dom
        return dom

    def _rf_scan_domains(self, node: P.TableScan):
        """{source column: Domain} of runtime filters consumable by this
        scan as zone-map constraints (dynamic mode only; the caller
        checks the table supports pushdown before we pay any sync)."""
        specs = getattr(node, "rf_consume", None)
        if not specs or self.static or not self._df_enabled():
            return None
        out = {}
        for spec in specs:
            dom = self._rf_host_domain(spec["fid"])
            col = spec.get("column")
            if dom is not None and col is not None:
                out[col] = dom
        return out or None

    def _rf_apply(self, node: P.TableScan, b: Batch) -> Batch:
        """Consumer side: AND every registered filter's membership mask
        into the scan's sel.  Unproduced ids are skipped — dynamic
        filtering is strictly best-effort and never changes results."""
        specs = getattr(node, "rf_consume", None)
        if not specs or not self._df_enabled():
            return b
        sel = b.sel
        applied = False
        for spec in specs:
            summary = self._rf.get(spec["fid"])
            if summary is None:
                continue
            col = b.columns.get(spec["sym"])
            if col is None or col.dictionary is not None \
                    or getattr(col.data, "ndim", 1) != 1 \
                    or jnp.issubdtype(col.data.dtype, jnp.floating):
                continue
            mask = K.rf_probe(summary, col)
            if self.static:
                sel = sel & mask  # counted at trace time only
            else:
                sel2 = sel & mask
                # ONE host fetch for both counts (dynamic mode only)
                before, after = jax.device_get((jnp.sum(sel),
                                                jnp.sum(sel2)))
                self._count("df_rows_pruned", int(before) - int(after))
                sel = sel2
            self._count("df_filters_applied")
            applied = True
        if not applied:
            return b
        out = b.with_sel(sel)
        # masking never moves rows; like Filter it punches interior holes
        self._copy_order(b, out, tail_ok=False)
        return out

    def _key_fp(self, cols, sel, layout):
        """(fingerprint, refs) identifying a packed key by the IDENTITY
        of its source arrays + pack layout — the sort-permutation memo
        key.  refs must be stored with the memo entry so the
        fingerprinted objects stay alive (id() reuse would otherwise
        alias entries).  None fp => not fingerprintable (2-D limbs)."""
        parts = []
        refs = [sel]
        for c in cols:
            d = c.data
            if getattr(d, "ndim", 1) != 1:
                return None, ()
            parts.append((id(d),
                          None if c.valid is None else id(c.valid)))
            refs.append(d)
            if c.valid is not None:
                refs.append(c.valid)
        lay = None if layout is None else tuple(tuple(x) for x in layout)
        return (tuple(parts), id(sel), lay), tuple(refs)

    def _memo_pair(self, key, fp, refs):
        """(skey, order) for a packed key, through the memo: the second
        and later group-bys/joins on the same key ride the cached
        permutation instead of re-sorting."""
        if not self._ordering_enabled():
            fp = None  # kill switch disables the memo too
        entry = self._sort_memo.get(fp) if fp is not None else None
        if entry is not None:
            self._count("sort_memo_hits")
            self._count("sorts_elided")
            return entry[1]
        self._count("sorts_taken")
        pair = K.sort_pair(key)
        if fp is not None:
            self._sort_memo[fp] = (refs, pair)
        return pair

    def _note_order(self, batch: Batch, keys, tail_ok: bool = True) -> None:
        """Record a CERTAIN output ordering this executor constructed
        (sorted over live rows on `keys`: tuple of (symbol, asc)).
        tail_ok: masked rows are confined to a suffix, so the FULL
        array (sentinels included) is nondecreasing once packed — what
        a presorted join build needs; live-row order alone (tail_ok
        False after a filter) still satisfies Sort/TopN elision."""
        if keys:
            self._batch_order[id(batch)] = (batch, tuple(keys), tail_ok)

    def _copy_order(self, src: Batch, dst: Batch, tail_ok=None) -> None:
        e = self._batch_order.get(id(src))
        if e is not None and e[0] is src:
            self._note_order(dst, e[1],
                             e[2] if tail_ok is None else (e[2] and tail_ok))

    def _order_satisfies(self, b: Batch, want) -> bool:
        """Does the runtime-certain ordering of `b` satisfy the
        requested sort keys?  `want`: list of (sym, asc, nulls_first).
        Requires the request to be a prefix of the known ordering and,
        because packed orderings place the NULL group first while SQL
        defaults differ, null-free key columns (valid is None)."""
        e = self._batch_order.get(id(b))
        if e is None or e[0] is not b:
            return False
        have = e[1]
        if len(want) > len(have):
            return False
        for (sym, asc, _nf), (hsym, hasc) in zip(want, have):
            if sym != hsym or bool(asc) != bool(hasc):
                return False
            col = b.columns.get(sym)
            if col is None or col.valid is not None:
                return False
        return True

    def _build_order_certain(self, node, right: Batch, rkeys) -> bool:
        """Runtime-certain presorted build: this executor constructed
        `right` sorted on the join key with masked rows in a suffix
        (e.g. a grouped output joined on its leading group key)."""
        if len(node.criteria) != 1 or rkeys[0].valid is not None:
            return False
        e = self._batch_order.get(id(right))
        if e is None or e[0] is not right or not e[2]:
            return False
        keys = e[1]
        rk = node.criteria[0][1]
        return bool(keys) and keys[0] == (rk, True)

    def _build_presorted(self, node, right: Batch, rkeys) -> bool:
        if len(node.criteria) != 1:
            return False
        return bool(getattr(node, "build_ordering_hint", False)) \
            or self._build_order_certain(node, right, rkeys)

    @staticmethod
    def _agg_pack_order(node, group_keys):
        """Key pack order: a presorted-input hint rotates the sorted
        key run to the front (most significant — kernels pack
        first-key-major), so the packed key is monotone whenever the
        claim + the remaining keys' functional dependence hold; the
        guard verifies both at once."""
        order = getattr(node, "ordering_pack_order", None) \
            if node is not None else None
        if order is not None and sorted(order) == sorted(group_keys):
            return list(order)
        hint = getattr(node, "ordering_hint", None) if node is not None \
            else None
        if hint is not None and hint in group_keys:
            return [hint] + [k for k in group_keys if k != hint]
        return list(group_keys)

    # ------------------------------------------------------------------
    def run(self, plan: P.QueryPlan) -> QueryResult:
        if self.monitor is not None:
            self.monitor.plan = plan  # rendered at finish (UI plan pane)
        self._count("grouping_set_branches", plan.grouping_set_branches)
        try:
            batch = self.evaluate(plan)
            return self.materialize(plan, batch)
        finally:
            if self.monitor is not None:
                _merge_sort_stats(self.monitor.stats, self.sort_stats)
            if self.mem is not None:
                if self.monitor is not None:
                    self.monitor.stats.peak_memory_bytes = self.mem.peak
                self.mem.release_all()

    def materialize(self, plan: P.QueryPlan, batch: Batch,
                    extra=None):
        """Batch -> QueryResult; `extra` (e.g. a guard scalar) rides the
        same device fetch, saving a device-to-host sync."""
        if extra is not None:
            arrays, sel, extra_h = to_numpy(batch, extra)
        else:
            arrays, sel = to_numpy(batch)
        result = self._format_result(plan, arrays, sel)
        return (result, extra_h) if extra is not None else result

    def materialize_host(self, plan: P.QueryPlan, meta: dict,
                         datas: Dict[str, tuple], sel) -> QueryResult:
        """Materialize from an unpack_fetch result (host numpy arrays):
        dictionary/decimal decode, then row formatting."""
        arrays = {}
        for name, _dtype_s, _words, _has_valid, typ, dic in meta["cols"]:
            data, valid = datas[name]
            arrays[name] = decode_host_column(data, valid, typ, dic)
        return self._format_result(plan, arrays, sel)

    def _format_result(self, plan: P.QueryPlan, arrays, sel) -> QueryResult:
        out = plan.root
        cols = []
        rows_data = []
        out_types = dict(out.source.outputs())
        for name, sym in zip(out.names, out.symbols):
            cols.append((name, out_types.get(sym, T.VARCHAR)))
            a = arrays[sym]
            vals = a[sel]
            rows_data.append(vals)
        rows = []
        n = len(rows_data[0]) if rows_data else 0
        for i in range(n):
            row = []
            for a in rows_data:
                v = a[i] if not np.ma.is_masked(a[i]) else None
                if isinstance(v, np.generic):
                    v = v.item()
                row.append(v)
            rows.append(tuple(row))
        return QueryResult(cols, rows)

    def evaluate(self, plan: P.QueryPlan) -> Batch:
        # evaluate scalar subplans first (dependency order is registration order)
        for pid, sub in sorted(plan.subplans.items()):
            b = self.exec_node(sub)
            val, valid = _single_value(b)
            self.ctx.scalar_results[pid] = (val, valid)
        return self.exec_node(plan.root)

    # ------------------------------------------------------------------
    def exec_node(self, node: P.PlanNode) -> Batch:
        if getattr(node, "shared_subtree", False):
            # plan DAGs (transitive semi-join inference shares the
            # filter subquery between both join sides): run once
            cache = getattr(self, "_shared_results", None)
            if cache is None:
                cache = self._shared_results = {}
            hit = cache.get(id(node))
            if hit is not None and hit[0] is node:
                return hit[1]
            b = self._exec_node_inner(node)
            cache[id(node)] = (node, b)
            return b
        return self._exec_node_inner(node)

    def _exec_node_inner(self, node: P.PlanNode) -> Batch:
        method = getattr(self, f"_exec_{type(node).__name__.lower()}", None)
        if method is None:
            raise ExecutionError(f"no executor for {type(node).__name__}")
        node_stats = self.monitor is not None and self.monitor.collect_node_stats
        if not node_stats and self.mem is None:
            # jax.named_scope at the operator-lowering site: inside a
            # static trace every op this node emits is scoped under the
            # plan-node name, so profiler timelines (PRESTO_TPU_PROFILE)
            # map back to plan nodes even though the compiled program is
            # one fused blob.  Trace-time only — a warm compiled run
            # never re-enters this path, so the hot loop pays nothing.
            with jax.named_scope(type(node).__name__):
                return method(node)
        # node stats collection (reference: OperationTimer around every
        # operator call, operator/Driver.java:380); the row count forces a
        # device sync, which is why it is opt-in / EXPLAIN ANALYZE only
        from presto_tpu.memory.context import batch_bytes
        from presto_tpu.observe import trace as _TR

        t0 = _TR.clock_ns()
        with jax.named_scope(type(node).__name__):
            b = method(node)
        if self.mem is not None:
            # live-set accounting: a node's output is resident until the
            # parent consumes it; child outputs die here (GC'd by Python,
            # mirroring operator page hand-off in Driver.processInternal)
            self.mem.set_bytes(id(node), batch_bytes(b))
            for child in node.sources:
                self.mem.set_bytes(id(child), 0)
        if node_stats:
            rows = int(b.row_count())
            self.monitor.record_node(node, rows, _TR.clock_ns() - t0)
        return b

    def _exec_window(self, node: P.Window) -> Batch:
        from presto_tpu.exec.window import execute_window

        return execute_window(self, node)

    # ---- leaves ------------------------------------------------------
    def _exec_tablescan(self, node: P.TableScan) -> Batch:
        if self.scan_inputs is not None:
            return self._rf_apply(node, self.scan_inputs[id(node)])
        table = self.session.catalog.get(node.table)
        rdoms = self._rf_scan_domains(node) \
            if getattr(table, "supports_domain_pushdown", False) else None
        if rdoms and hasattr(table, "pruned_stats"):
            # runtime domains intersected with the static scan_domains
            # prune EXTRA stripes — count only the delta the runtime
            # half removed (the static half prunes with filtering off)
            from presto_tpu.plan.domains import merge_domain_maps

            static = getattr(node, "scan_domains", None)
            kept_static, _tot = table.pruned_stats(static or None)
            kept_merged, _tot = table.pruned_stats(
                merge_domain_maps(static or {}, rdoms))
            self._count("df_splits_pruned",
                        max(kept_static - kept_merged, 0))
        b = scan_batch(
            table, node,
            bool(self.session.properties.get("float32_compute", False)),
            runtime_domains=rdoms)
        return self._rf_apply(node, b)

    def _exec_values(self, node: P.Values) -> Batch:
        arrays = {}
        valids = {}
        types = {}
        n = len(node.rows)
        collection_cols = {}
        for j, (sym, t) in enumerate(zip(node.symbols, node.types_)):
            vals = [r[j] for r in node.rows]
            if t.name in ("ARRAY", "MAP", "ROW"):
                # collection literals (folded ARRAY[..]/MAP(..) ctors):
                # dictionary-encode the tuple values like any column
                from presto_tpu.functions.scalar import _colval_from_pylist

                collection_cols[sym] = to_column(
                    _colval_from_pylist(vals, t), n)
                continue
            mask = np.asarray([v is not None for v in vals])
            if t.is_string:
                arr = np.asarray([v if v is not None else "" for v in vals], dtype=object)
            else:
                arr = np.asarray([v if v is not None else 0 for v in vals],
                                 dtype=t.numpy_dtype())
            arrays[sym] = arr
            types[sym] = t
            if not mask.all():
                valids[sym] = mask
        b = batch_from_numpy(arrays, types, valids or None) if arrays \
            else Batch({}, jnp.ones((n,), bool))
        if collection_cols:
            cols = dict(b.columns)
            cols.update(collection_cols)
            b = Batch(cols, b.sel)
        return b

    # ---- row-wise ----------------------------------------------------
    def _exec_filter(self, node: P.Filter) -> Batch:
        b = self.exec_node(node.source)
        with NM.kernel_scope("k:scan_filter"):
            mask = eval_predicate(node.predicate, b, self.ctx)
        out = b.with_sel(b.sel & mask)
        # masking never moves rows, but it punches interior holes
        self._copy_order(b, out, tail_ok=False)
        return out

    def _exec_project(self, node: P.Project) -> Batch:
        b = self.exec_node(node.source)
        cols = {}
        for sym, e in node.assignments.items():
            v = eval_expr(e, b, self.ctx)
            cols[sym] = to_column(v, b.capacity)
        out = Batch(cols, b.sel)
        src_order = self._batch_order.get(id(b))
        if src_order is not None and src_order[0] is b:
            # row-wise: certain orderings survive under identity (Ref)
            # renames up to the first non-Ref key
            renames = {}
            for sym, e in node.assignments.items():
                if isinstance(e, ir.Ref):
                    renames.setdefault(e.name, sym)
            mapped = []
            for sym, asc in src_order[1]:
                if sym not in renames:
                    break
                mapped.append((renames[sym], asc))
            self._note_order(out, tuple(mapped), tail_ok=src_order[2])
        return out

    # ---- aggregation -------------------------------------------------
    def _exec_aggregate(self, node: P.Aggregate) -> Batch:
        return self._aggregate_batch(node, self.exec_node(node.source))

    def _exec_groupingsets(self, node: P.GroupingSets) -> Batch:
        """The source ONCE; then each grouping set is the Aggregate of its
        keys over that one batch, with NULL columns for the keys the set
        leaves out and its index as the group id; the sets' rows are
        concatenated as a UNION ALL's are.  On a mesh the node comes in
        two steps, as an Aggregate does: PARTIAL is the same with the
        sets' states for aggregates, FINAL merges moved states
        (`_merge_groupingsets`)."""
        if node.step == "FINAL":
            return self._merge_groupingsets(node)
        b = self.exec_node(node.source)
        self._count("grouping_set_sources")
        if node.hints:
            # every set starts from the same input estimate: compact once
            b = self._maybe_compact_static(
                b, node.hints[0].get("input_est_hint"))
        outs = []
        for i, keys in enumerate(node.sets):
            # a SINGLE node's sets read as Aggregates in a profile; a mesh
            # node's partials, exchange and merge all read as the node
            scope = jax.named_scope("Aggregate") if node.step == "SINGLE" \
                else contextlib.nullcontext()
            with scope:
                out = self._aggregate_batch(node.set_aggregate(i), b)
            n = out.capacity
            cols = {}
            for k in node.group_keys:
                c = b.columns[k]    # left out: NULLs of the key's own kind
                cols[k] = out.columns[k] if k in keys else Column(
                    jnp.zeros((n,) + c.data.shape[1:], c.data.dtype),
                    jnp.zeros((n,), bool), c.type, c.dictionary)
            for s in node.aggs:
                cols[s] = out.columns[s]
            cols[node.group_id] = Column(jnp.full((n,), i, jnp.int32), None,
                                         T.INTEGER)
            outs.append(Batch(cols, out.sel))
        out = K.concat_batches(outs)
        if node.step == "PARTIAL":
            self._count("grouping_set_state_rows", out.capacity)
        return out

    def _merge_groupingsets(self, node: P.GroupingSets) -> Batch:
        """FINAL: the shards' states, moved by the Exchange under the
        node, merged by ONE Aggregate over (keys, group id).  A key that
        a set leaves out is NULL in all of the set's rows and the group
        id tells the sets apart, so the merged groups are the sets'.  The
        Exchange is lowered here and not through exec_node: its
        collective then lies under this node's scope in a profile, not
        under `Exchange` with the broadcast builds."""
        src = node.source
        b = self._exec_exchange(src) if isinstance(src, P.Exchange) \
            else self.exec_node(src)
        merge = P.Aggregate(src, node.group_keys + [node.group_id],
                            node.aggs, "FINAL")
        hints = dict(node.merge_hints)
        # repartitioned states carry the planner's bound on a chip's live
        # ones: the merge (and its output) runs over the received buffer
        # compacted to it, under the compaction's guard
        b = self._maybe_compact_static(b, hints.pop("input_est_hint", None))
        vars(merge).update(hints)
        self._count("grouping_set_merge_rows", b.capacity)
        out = self._aggregate_batch(merge, b)
        return Batch({s: out.columns[s] for s, _ in node.outputs()}, out.sel)

    def _aggregate_batch(self, node: P.Aggregate, b: Batch) -> Batch:
        """`node` over `b`, its source's rows."""
        from presto_tpu.memory.context import batch_bytes

        strat = getattr(node, "agg_strategy", None)
        if strat and node.group_keys and node.step != "FINAL":
            # planner strategy counter (plan/agg_strategy.py) — counted
            # where the aggregate EXECUTES (trace-time in static mode,
            # like the sort economics); FINAL merges are the other half
            # of an already-counted two-phase pair
            self._count("agg_strategy::" + strat)
        if any(a.distinct for a in node.aggs.values()):
            return self._exec_aggregate_with_distinct(node, b)
        # monitored chunked lane (exec/chunked.py): record the live row
        # count INTO the first PARTIAL stage as a traced scalar — the
        # runner's reduction-ratio monitor reads it per chunk
        if getattr(self, "capture_partial_agg_rows", False) \
                and node.step == "PARTIAL" and node.group_keys \
                and getattr(self, "captured_agg_rows", None) is None:
            self.captured_agg_rows = jnp.sum(b.sel, dtype=jnp.int32)
        # adaptive partial-aggregation bypass (plan/agg_strategy.py):
        # consulted BEFORE spill planning, so a bypassed partial never
        # builds grouped state or reserves revocable memory
        flip = self._pa_flip_state(node)
        if flip is not None and flip.bypassed and not flip.probe_due():
            flip.note_bypassed()
            self._count("partial_aggs_bypassed")
            return self._pa_passthrough(node, b)
        rows_in = None
        if flip is not None:
            # device scalar now (the spill path may free b); host-synced
            # only after the grouped pass ran
            rows_in = jnp.sum(b.sel, dtype=jnp.int64)
        if node.group_keys and not self.static:
            from presto_tpu.exec import spill_exec as SE

            # hash/agg state is ~2x its input in the worst case
            dec = SE.plan_degradation(
                self, node, SE.WORKING_SET_FACTOR * batch_bytes(b),
                b.capacity)
            if dec.degrade:
                holder = [b]
                del b  # holder owns the only reference; spill path frees it
                return SE.hybrid_aggregate(self, node, holder, dec)
            if dec.mem_key:
                try:
                    out = self._aggregate(b, node.group_keys, node.aggs,
                                          node)
                finally:
                    # converted revocable operator-state reservation
                    self.mem.set_bytes(dec.mem_key, 0)
                self._pa_observe(flip, rows_in, out)
                return out
        out = self._aggregate(b, node.group_keys, node.aggs, node)
        self._pa_observe(flip, rows_in, out)
        return out

    # ---- adaptive partial aggregation (plan/agg_strategy.py) ---------
    def _pa_flip_state(self, node):
        """The hysteresis flip state for a bypassable PARTIAL aggregate,
        or None (static traces make their flip decisions in the chunked
        runner, outside the program)."""
        if self.static or getattr(node, "step", "SINGLE") != "PARTIAL" \
                or not node.group_keys:
            return None
        from presto_tpu.plan import agg_strategy as AS

        if not AS.enabled(self.session):
            return None
        return AS.flip_state(self.session, node)

    def _pa_passthrough(self, node: P.Aggregate, b: Batch) -> Batch:
        """Serve a bypassed PARTIAL aggregate: every live row projected
        into the partial-output schema (count -> 0/1, sum -> x, ...) —
        no group build; the FINAL stage re-groups the raw stream."""
        from presto_tpu.plan import agg_strategy as AS

        proj = AS.passthrough_project(node)
        cols = {}
        for sym, e in proj.assignments.items():
            cols[sym] = to_column(eval_expr(e, b, self.ctx), b.capacity)
        return Batch(cols, b.sel)

    def _pa_observe(self, flip, rows_in, out: Batch) -> None:
        """Feed the grouped pass's reduction ratio into the flip state
        (one host fetch; dynamic mode only — callers pass flip=None in
        static traces).  The spill path skips observation: a degraded
        build's partition-local group counts are not the fragment
        ratio."""
        if flip is None or rows_in is None:
            return
        from presto_tpu.plan import agg_strategy as AS

        groups = int(out.capacity)  # dynamic grouping: sel == ones(n)
        rows = int(jax.device_get(rows_in))
        ratio = rows / max(groups, 1)
        self.sort_stats["partial_agg_ratio"] = ratio
        event = flip.observe(ratio, AS.min_reduction(self.session))
        if event == "flipped":
            self._count("partial_aggs_bypassed")
        elif event == "reenabled":
            self._count("partial_aggs_reenabled")

    # ---- spill / grouped execution (exec/spill_exec.py) --------------
    def _make_spiller(self):
        from presto_tpu.memory.spill import (FileSpiller, SpillCipher,
                                             SpillSpaceTracker,
                                             default_spill_dir)

        path = self.session.properties.get("spill_path") or default_spill_dir()
        tracker = getattr(self.session, "_spill_tracker", None)
        if tracker is None:
            tracker = self.session._spill_tracker = SpillSpaceTracker(
                int(self.session.properties.get("max_spill_bytes", 64 << 30)))
        tracker.max_bytes = int(
            self.session.properties.get("max_spill_bytes", 64 << 30))
        cipher = None
        if self.session.properties.get("spill_encryption", False):
            cipher = SpillCipher()  # ephemeral per-query key
        return FileSpiller(
            path, tracker, cipher,
            verify_writes=bool(self.session.properties.get(
                "spill_verify_writes", False)))

    def _grouped_recovery(self, nparts: int):
        """Per-bucket checkpoint hooks for recoverable grouped execution
        (reference: RECOVERABLE_GROUPED_EXECUTION lifespans re-scheduled
        after a node dies, StageExecutionDescriptor.java:26 — here a
        re-run resumes from completed buckets on disk).  Also carries
        the fault-injection hook used to test it.  Returns
        (load, store, bucket_done, finish)."""
        from presto_tpu.memory.spill import (default_spill_dir, load_batch,
                                             save_batch)

        # "auto" (the session default) means ON only for CLUSTER
        # durable-exchange recovery (parallel/cluster.py) — the
        # single-node checkpoint path here stays opt-in via an explicit
        # True/"on"
        rge = self.session.properties.get(
            "recoverable_grouped_execution", False)
        enabled = rge is True or str(rge).strip().lower() in (
            "true", "on", "1")
        # without a monitor there is no query text to fingerprint; sharing
        # a checkpoint key across unknown queries could serve query A's
        # buckets to query B, so recovery requires the monitored path
        if self.monitor is None or not self.monitor.stats.sql:
            enabled = False
        fail_after = int(self.session.properties.get(
            "fault_injection_fail_after_buckets", 0))
        seq = self._ckpt_seq = getattr(self, "_ckpt_seq", 0) + 1
        done_count = [0]
        if not enabled:
            def bucket_done():
                done_count[0] += 1
                if fail_after and done_count[0] >= fail_after:
                    raise ExecutionError("fault injection: worker died")
            return (lambda p: None), (lambda p, b: None), bucket_done, \
                (lambda: None)
        sql = self.monitor.stats.sql
        from presto_tpu import native

        fp = native.xxh64((" ".join(sql.split()) + f"|op{seq}").encode())
        d = os.path.join(
            self.session.properties.get("spill_path") or default_spill_dir(),
            f"ckpt_{fp:016x}_{nparts}")
        os.makedirs(d, exist_ok=True)

        def load(p):
            path = os.path.join(d, f"bucket_{p}.ptpg")
            if os.path.exists(path):
                if self.monitor is not None:
                    self.monitor.stats.recovered_buckets += 1
                return load_batch(path)
            return None

        def store(p, batch):
            save_batch(os.path.join(d, f"bucket_{p}.ptpg"), batch)

        def bucket_done():
            done_count[0] += 1
            if fail_after and done_count[0] >= fail_after:
                raise ExecutionError("fault injection: worker died")

        def finish():
            import shutil

            shutil.rmtree(d, ignore_errors=True)

        return load, store, bucket_done, finish

    def _exec_aggregate_with_distinct(self, node: P.Aggregate, b: Batch) -> Batch:
        """Rewrite: pre-group by (keys + distinct arg) then count non-null
        (reference: MultipleDistinctAggregationToMarkDistinct — single
        distinct column supported)."""
        distinct_aggs = {s: a for s, a in node.aggs.items() if a.distinct}
        plain_aggs = {s: a for s, a in node.aggs.items() if not a.distinct}
        if plain_aggs:
            # evaluate the two halves separately and merge: both group
            # passes enumerate the same key set in the same slot order
            # (sorted-unique dynamically; hash slots statically), so the
            # outputs align column-wise without a join (reference:
            # MarkDistinct keeps one pass; this is the two-pass analog)
            pb = self._aggregate(b, node.group_keys, plain_aggs)
            db = self._exec_aggregate_with_distinct(
                P.Aggregate(node.source, node.group_keys, distinct_aggs,
                            node.step), b)
            if pb.capacity != db.capacity:
                raise ExecutionError("distinct/plain group alignment failed")
            merged = dict(db.columns)
            for s in plain_aggs:
                merged[s] = pb.columns[s]
            # preserve the aggregate-declaration order for output mapping
            cols = {k: merged[k] for k in list(db.columns) if k not in node.aggs}
            for s in node.aggs:
                cols[s] = merged[s]
            return Batch(cols, db.sel)
        # one pre-group pass per distinct column; every pass enumerates
        # the same final key set in the same sorted-unique order, so the
        # outputs align column-wise (reference:
        # MultipleDistinctAggregationToMarkDistinct generalization)
        for a in distinct_aggs.values():
            if a.filter is not None:
                # the filter must apply BEFORE dedup, but the pre-group
                # output no longer carries the filter's columns; a clear
                # error beats a KeyError (or silently-wrong post-dedup
                # filtering)
                raise ExecutionError(
                    "DISTINCT aggregates with FILTER are not supported yet")
        by_col: Dict[str, Dict[str, ir.AggCall]] = {}
        for s, a in distinct_aggs.items():
            by_col.setdefault(a.args[0].name, {})[s] = a
        result = None
        for darg in sorted(by_col):
            pre = self._aggregate(b, node.group_keys + [darg], {})
            aggs2 = {}
            for s, a in by_col[darg].items():
                if a.fn in ("count", "approx_distinct"):
                    aggs2[s] = ir.AggCall("count", a.args, a.type, False,
                                          a.filter)
                elif a.fn in ("sum", "avg", "array_agg", "min", "max"):
                    # over the deduped pre-group these equal their
                    # DISTINCT forms
                    aggs2[s] = ir.AggCall(a.fn, a.args, a.type, False,
                                          a.filter)
                else:
                    raise ExecutionError(f"DISTINCT {a.fn} not supported")
            db = self._aggregate(pre, node.group_keys, aggs2)
            if result is None:
                result = db
            else:
                if result.capacity != db.capacity:
                    raise ExecutionError("distinct group alignment failed")
                cols = dict(result.columns)
                for s in aggs2:
                    cols[s] = db.columns[s]
                result = Batch(cols, result.sel)
        return result

    def _aggregate(self, b: Batch, group_keys: List[str],
                   aggs: Dict[str, ir.AggCall], node: Optional[P.Aggregate] = None) -> Batch:
        if not group_keys:
            return self._global_aggregate(b, aggs)
        key_cols = [b.columns[k] for k in group_keys]
        if self.static:
            return self._aggregate_static(b, group_keys, key_cols, aggs, node)
        pack_order = self._agg_pack_order(node, group_keys)
        pack_cols = [b.columns[k] for k in pack_order]
        key, layout = K.pack_keys(pack_cols, b.sel)
        gid = rep_rows = n_groups = None
        if layout is not None and self._ordering_enabled() \
                and getattr(node, "ordering_hint", None) == pack_order[0]:
            # presorted grouping: run-boundary scan, no sort, no
            # unpermute.  Dynamic mode host-checks the monotonicity
            # guard (one fetch shared with the group count) and falls
            # back to the sort path when the ordering claim lied.
            g2, newgrp, ng_t, guard = K.group_ids_presorted(key, b.sel)
            guard_h, ng = jax.device_get((guard, ng_t))
            if not bool(guard_h):
                n_groups = int(ng)
                gid = g2
                rep_rows = K.nonzero_i32(
                    newgrp, max(n_groups, 1), 0)[:n_groups] \
                    if n_groups else jnp.zeros((0,), jnp.int32)
                self._count("sorts_elided", 2)
            else:
                self._count("ordering_guard_trips")
        if gid is None:
            fp, refs = self._key_fp(pack_cols, b.sel, layout)
            hit = self._gid_memo.get(fp) if fp is not None \
                and self._ordering_enabled() else None
            if hit is not None:
                # group-id mapping memo: a second grouping over the SAME
                # key arrays (AVG/STDDEV fold passes over a resident
                # build, distinct pre-passes) reuses the whole
                # (gid, representatives, count) mapping — both the
                # grouping sort AND the unpermute co-sort elide
                gid, rep_rows, n_groups = hit[1]
                self._count("sort_memo_hits")
                self._count("sorts_elided", 2)
            else:
                pair = self._memo_pair(key, fp, refs)
                self._count("sorts_taken")  # the unpermute co-sort
                gid, rep_rows, n_groups = K.group_ids(key, b.sel,
                                                      sorted_pair=pair)
                if fp is not None:
                    self._gid_memo[fp] = (refs, (gid, rep_rows, n_groups))
        out_cols: Dict[str, Column] = {}
        raw, _ = K.take_columns({k: b.columns[k] for k in group_keys},
                                rep_rows)
        for k, (data, valid) in raw.items():
            c = b.columns[k]
            out_cols[k] = Column(data, valid, c.type, c.dictionary)
        out_cols.update(self._agg_columns(b, aggs, gid, n_groups))
        sel = jnp.ones((max(n_groups, 0),), dtype=bool)
        if n_groups == 0:
            out_cols = {k: Column(c.data[:0], None if c.valid is None else c.valid[:0],
                                  c.type, c.dictionary) for k, c in out_cols.items()}
        out = Batch(out_cols, sel)
        if layout is not None:
            # exact packing: group rows emitted ascending on the packed
            # key = lexicographic on pack_order (certain by construction
            # — both the sorted and the run-scan path number groups in
            # ascending key order)
            self._note_order(out, tuple((k, True) for k in pack_order))
        return out

    # layouts this small use the packed key AS the group id (no sort at
    # all); key columns are reconstructed from slot arithmetic
    _DIRECT_GID_BITS = 12

    def _aggregate_static(self, b: Batch, group_keys, key_cols, aggs, node) -> Batch:
        cap = getattr(node, "capacity_hint", None) if node is not None else None
        if cap is None:
            cap = b.capacity
        cap = min(cap, b.capacity) or 1
        # Guarded pre-aggregation compaction: after selective joins the
        # live set is often orders of magnitude below the mask-not-
        # compact capacity, and every grouping pass (sorts, segment
        # reductions, representative gathers) scales with CAPACITY.
        # Compact to an estimate-derived power-of-two bound (top_k path,
        # ~10ms) under a guard that aborts to dynamic if the estimate
        # lied.  Q3-class join->group queries drop ~3x wall-clock.
        est = getattr(node, "input_est_hint", None) if node is not None \
            else None
        b2 = self._maybe_compact_static(b, est)
        if b2 is not b:
            # order-preserving compaction (ascending top_k indices):
            # presorted-input claims survive it
            b = b2
            key_cols = [b.columns[k] for k in group_keys]
            cap = min(cap, b.capacity)
        key_stats = getattr(node, "key_stats", {}) if node is not None else {}
        pack_order = self._agg_pack_order(node, group_keys)
        pack_cols = [b.columns[k] for k in pack_order]
        layout = K.static_layout(pack_cols, [key_stats.get(k) for k in pack_order])
        key = K.pack_with_layout(pack_cols, b.sel, layout)  # None -> hash, sync-free
        if layout is not None:
            self.guards.append(K.layout_range_guard(pack_cols, b.sel, layout))
            total_bits = sum(w for _, _, w in layout)
            if total_bits <= self._DIRECT_GID_BITS and all(
                    not jnp.issubdtype(c.data.dtype, jnp.floating)
                    for c in pack_cols):
                return self._aggregate_direct(
                    b, pack_order, pack_cols, aggs, key, layout, total_bits)
        if layout is not None and self._ordering_enabled() \
                and getattr(node, "ordering_hint", None) == pack_order[0] \
                and getattr(node, "ordering_hint_safe", False):
            # presorted grouping, compiled mode: the traced monotonicity
            # guard rides the existing static-guard channel — a wrong
            # ordering claim re-runs the query on the dynamic path.
            # SAFE hints only (remaining keys provably constant within
            # leading runs): a static trip costs the whole program,
            # where the dynamic path's host check costs one fetch
            gid, rep_rows, exists, overflow, guard = \
                K.group_ids_presorted_static(key, cap)
            self.guards.append(guard)
            self._count("sorts_elided", 2)
        else:
            fp, refs = self._key_fp(pack_cols, b.sel, layout)
            pair = self._memo_pair(key, fp, refs)
            self._count("sorts_taken")  # the unpermute co-sort
            gid, rep_rows, exists, overflow = K.group_ids_static(
                key, cap, sorted_pair=pair)
        self.guards.append(overflow)
        out_cols: Dict[str, Column] = {}
        raw, _ = K.take_columns({k: b.columns[k] for k in group_keys},
                                rep_rows)
        for k, (data, valid) in raw.items():
            c = b.columns[k]
            out_cols[k] = Column(
                data, None if valid is None else (valid & exists),
                c.type, c.dictionary)
        out_cols.update(self._agg_columns(b, aggs, gid, cap))
        out = Batch(out_cols, exists)
        if layout is not None:
            # live prefix ascending on the packed key; dead slots carry
            # sentinels, so downstream full-array monotone guards hold
            self._note_order(out, tuple((k, True) for k in pack_order))
        return out

    def _aggregate_direct(self, b: Batch, group_keys, key_cols, aggs,
                          key, layout, total_bits: int) -> Batch:
        """Sort-free grouping for small static layouts: the packed key IS
        the group id (a dense slot in [0, 2^total_bits)), and the key
        columns come back from slot arithmetic instead of representative-
        row gathers.  TPC-H Q1's whole grouping collapses to one
        elementwise pass + the fused segmented reduction (reference
        analog: BigintGroupByHash's direct small-range fast path,
        operator/BigintGroupByHash.java)."""
        cap = 1 << total_bits
        # masked rows carry key_sentinel (huge) — clip sends them to the
        # dead slot `cap`, which every segment kernel already ignores
        gid = jnp.clip(key, 0, cap).astype(jnp.int32)
        counts = K.segment_sum(
            jnp.where(b.sel, 1.0, 0.0).astype(jnp.float32), gid, cap)
        exists = counts > 0.5
        slots = jnp.arange(cap, dtype=jnp.int64)
        out_cols: Dict[str, Column] = {}
        for k, c, (lo, stride, width) in zip(group_keys, key_cols, layout):
            code = (slots // stride) & ((1 << width) - 1)
            data = (code - 1 + lo).astype(c.data.dtype)
            valid = None if c.valid is None else ((code != 0) & exists)
            out_cols[k] = Column(data, valid, c.type, c.dictionary)
        out_cols.update(self._agg_columns(b, aggs, gid, cap))
        out = Batch(out_cols, exists)
        # slot order IS packed-key order (live slots ascending), but
        # EMPTY slots sit interspersed: not tail-masked
        self._note_order(out, tuple((k, True) for k in group_keys),
                         tail_ok=False)
        return out

    def _agg_columns(self, b: Batch, aggs: Dict[str, ir.AggCall],
                     gid, n_groups: int) -> Dict[str, Column]:
        """A grouped node's aggregate columns: the fused pass answers
        what it can, _agg_column the rest.  aggs_fused / aggs_unfused
        count each side (at trace time, like the sort economics), so a
        plan whose aggregates fall off the fused kernel says so in
        QueryStats."""
        fused = self._fused_sum_aggs(b, aggs, gid, n_groups)
        self._count("aggs_fused", len(fused))
        self._count("aggs_unfused", len(aggs) - len(fused))
        return {sym: fused.get(sym) or self._agg_column(b, a, gid, n_groups)
                for sym, a in aggs.items()}

    def _fused_sum_aggs(self, b: Batch, aggs: Dict[str, ir.AggCall],
                        gid, n_groups: int) -> Dict[str, Column]:
        """Prepass: compute all sum-shaped aggregates (count(*)/count(x)/
        count_if, sum/avg over DOUBLE) in ONE Pallas pass over the rows
        (kernels.fused_group_sums) instead of one scatter-add per
        aggregate.  Returns {} when not worthwhile; callers fall through
        to _agg_column per aggregate."""
        if not self.session.properties.get("pallas_fused_agg", True):
            return {}
        n = b.capacity
        if n < 32_768 or not (1 <= n_groups <= 4096) or len(aggs) < 1:
            return {}

        # pre-select fusable aggregates from METADATA ONLY, so a below-
        # threshold set bails out before any expression is evaluated
        # (otherwise _agg_column would redo each eval)
        def fusable(a):
            if a.fn == "count" and len(a.args) <= 1:
                return True
            if a.fn == "count_if":
                return True
            if a.fn in ("sum", "avg", "partial_sum_double") and a.args:
                t = getattr(a.args[0], "type", None)
                return t is not None and t.name in ("DOUBLE", "REAL")
            return False

        chosen = {sym: a for sym, a in aggs.items() if fusable(a)}
        f32_mode = bool(self.session.properties.get("float32_compute", False))
        if not f32_mode and not K._pallas_interpret():
            # the TPU kernel accumulates f32 block partials; without the
            # float32_compute opt-in the session promises full-precision
            # f64, so stay on the (slower) exact scatter-add path
            return {}
        # with f32 compute even a single aggregate is worth fusing (the
        # kernel's block-partial + f64 merge beats one long f32 reduce)
        if len(chosen) < (1 if f32_mode else 2):
            return {}

        rows: List[jnp.ndarray] = []
        plan: Dict[str, tuple] = {}
        any_f32 = False
        # count(x) waits for the sums: where sum/avg/partial_sum_double
        # of the same (argument, filter) stacks `valid` as its count row
        # (avg's PARTIAL decomposition, plan/distribute.py), the count
        # is that row and adds none
        count_rows: Dict[tuple, int] = {}
        for sym, a in sorted(chosen.items(), key=lambda kv: (
                kv[1].fn == "count" and bool(kv[1].args))):
            of_arg = (a.args[0], a.filter) if a.args else None
            if a.fn == "count" and of_arg in count_rows:
                plan[sym] = ("count", count_rows[of_arg])
                continue
            mask = b.sel
            if a.filter is not None:
                mask = mask & eval_predicate(a.filter, b, self.ctx)
            if a.fn == "count" and not a.args:
                plan[sym] = ("count", len(rows))
                rows.append(mask)
            elif a.fn == "count":
                # only x's validity is read: any argument type
                v = eval_expr(a.args[0], b, self.ctx)
                plan[sym] = ("count", len(rows))
                count_rows[of_arg] = len(rows)
                rows.append(mask if v.valid is None else (mask & v.valid))
            elif a.fn == "count_if":
                v = eval_expr(a.args[0], b, self.ctx)
                m = mask & jnp.asarray(v.data)
                if v.valid is not None:
                    m = m & v.valid
                plan[sym] = ("count", len(rows))
                rows.append(m)
            else:
                v = eval_expr(a.args[0], b, self.ctx)
                col = to_column(v, n)
                if col.data.dtype not in (jnp.float64, jnp.float32):
                    continue
                any_f32 = any_f32 or col.data.dtype == jnp.float32
                valid = mask if col.valid is None else (mask & col.valid)
                vi = len(rows)
                rows.append(jnp.where(valid, col.data,
                                      jnp.zeros((), col.data.dtype)))
                ci = len(rows)
                rows.append(valid)
                plan[sym] = (a.fn, vi, ci, a.type)
                count_rows.setdefault(of_arg, ci)
        if len(plan) < (1 if any_f32 else 2):
            return {}
        # on the TPU path the kernel uses f32 block partials with an f64
        # cross-block merge either way; the interpreter path accumulates
        # in acc_t across ALL blocks, so it must stay f64 (counts are
        # exact-integer semantics)
        acc_t = (jnp.float32 if any_f32 and not K._pallas_interpret()
                 else jnp.float64)
        with NM.kernel_scope("k:fused_group_sums.operand"):
            operand = jnp.stack([r.astype(acc_t) for r in rows])
        sums = K.fused_group_sums(
            operand,
            jnp.clip(gid, 0, n_groups - 1).astype(jnp.int32),
            n_groups)
        out: Dict[str, Column] = {}
        for sym, p in plan.items():
            if p[0] == "count":
                # float counts are exact below 2^53
                out[sym] = Column(jnp.round(sums[p[1]]).astype(jnp.int64),
                                  None, T.BIGINT)
                continue
            fn, vi, ci, out_t = p
            s = sums[vi]
            cnt = sums[ci]
            nonempty = cnt > 0.5
            if fn == "avg":
                out[sym] = Column(s / jnp.maximum(cnt, 1.0), nonempty, T.DOUBLE)
            else:
                out[sym] = Column(s, nonempty, out_t)
        return out

    def _agg_column(self, b: Batch, a: ir.AggCall, gid, n_groups) -> Column:
        mask = b.sel
        if a.filter is not None:
            mask = mask & eval_predicate(a.filter, b, self.ctx)
        if a.fn in ("count",) and not a.args:
            # i32 accumulate: an i64 scatter-add runs as u32-pair
            # emulation on TPU (~10x slower, measured); per-group row
            # counts within one batch always fit i32
            cnt = K.segment_sum(mask.astype(jnp.int32), gid, n_groups)
            return Column(cnt.astype(jnp.int64), None, T.BIGINT)
        if a.fn == "count_if":
            v = eval_expr(a.args[0], b, self.ctx)
            m = mask & jnp.asarray(v.data)
            if v.valid is not None:
                m = m & v.valid
            return Column(K.segment_sum(m.astype(jnp.int32), gid,
                                        n_groups).astype(jnp.int64),
                          None, T.BIGINT)
        if a.fn in ("merge_count", "merge_avg") or a.fn.startswith(
                ("merge_stddev", "merge_var")):
            return self._merge_agg_column(b, a, gid, n_groups, mask)
        v = eval_expr(a.args[0], b, self.ctx)
        col = to_column(v, b.capacity)
        valid = mask if col.valid is None else (mask & col.valid)
        cnt = K.segment_sum(valid.astype(jnp.int32), gid,
                            n_groups).astype(jnp.int64)  # i32: see count
        nonempty = cnt > 0
        if a.fn == "count":
            return Column(cnt, None, T.BIGINT)
        if a.fn == "approx_distinct":
            h = K.hll_hash64(col)  # value hash: matches distributed merge
            est = K.hll_registers_and_estimate(h, valid, gid, n_groups,
                                               m=_hll_m(a))
            return Column(est, None, T.BIGINT)
        if a.fn == "$hll_partial":
            # mergeable sketch partial: the state column IS the aggregate
            # output — (n_groups, m) uint8 registers, m from the TYPE
            h = K.hll_hash64(col)
            regs = K.hll_partial(h, valid, gid, n_groups,
                                 m=a.type.params[0])
            return Column(regs, None, a.type)
        if a.fn == "$hll_est":
            # final over partial states: fold register rows (elementwise
            # max) per group, then estimate; empty groups estimate 0,
            # matching the single-pass kernel (approx_distinct never
            # returns NULL)
            return Column(K.hll_merge_estimate(col.data, valid, gid,
                                               n_groups), None, T.BIGINT)
        if a.fn == "$hll_merge":
            # rollup merge: partial states in, folded state out (the
            # chunked loop's re-aggregation of partial pages)
            return Column(K.hll_merge(col.data, valid, gid, n_groups),
                          None, a.type)
        if a.fn == "$kll_partial":
            kk = a.type.params[0] // 2
            x = col.data.astype(jnp.float64) if col.data.dtype != \
                jnp.float64 else col.data
            return Column(K.kll_partial(x, valid, gid, n_groups, kk),
                          None, a.type)
        if a.fn == "$kll_pct":
            pv = eval_expr(a.args[1], b, self.ctx)
            p = pv.data if getattr(pv.data, "ndim", 0) == 0 else pv.data[0]
            kk = a.args[0].type.params[0] // 2
            vals, ok = K.kll_percentile(col.data, valid, gid, n_groups,
                                        p, kk)
            return Column(vals.astype(a.type.numpy_dtype()), ok, a.type)
        if a.fn in ("approx_count", "approx_sum"):
            # COUNT/SUM ... WITH ERROR: deterministic 1-in-8 value-hash
            # sample, scaled by exactly 8 — partition-independent, so
            # partials (the fn is its own partial) merge by plain sum
            keep = valid & K.sketch_sample_mask(K.hll_hash64(col))
            if a.fn == "approx_count":
                s = K.segment_sum(keep.astype(jnp.int32), gid, n_groups)
                return Column(s.astype(jnp.int64) * 8, None, T.BIGINT)
            x = jnp.where(keep, col.data, jnp.zeros_like(col.data))
            s = K.segment_sum(x, gid, n_groups)
            if a.type.is_integer:
                s = s.astype(jnp.int64)
            return Column(s.astype(a.type.numpy_dtype()) * 8, nonempty,
                          a.type)
        if a.fn == "checksum":
            # order-independent 64-bit checksum: wrapping sum of row
            # hashes (reference: ChecksumAggregationFunction, xor-based;
            # any commutative mix works for A/B verification)
            h = K._hash_keys([col], valid).astype(jnp.int64)
            s = K.segment_sum(jnp.where(valid, h, 0), gid, n_groups)
            return Column(s, nonempty, T.BIGINT)
        if a.fn == "approx_percentile":
            if a.type.name == "ARRAY" or len(a.args) >= 3:
                # array-of-percentiles / weighted forms: host-side
                # (reference: Approximate*PercentileArrayAggregations +
                # the weighted overloads)
                if self.static:
                    raise StaticFallback(
                        "array/weighted approx_percentile is "
                        "dynamic-mode only")
                return self._approx_percentile_host(b, a, gid, n_groups,
                                                    col, valid, nonempty)
            pv = eval_expr(a.args[1], b, self.ctx)
            p = pv.data if getattr(pv.data, "ndim", 0) == 0 else pv.data[0]
            x = col.data
            vals, ok = K.group_percentile(x, valid, gid, n_groups, p)
            return Column(vals.astype(col.data.dtype), ok, a.type,
                          col.dictionary)
        if a.fn in ("min_by", "max_by") and len(a.args) == 2:
            yv = to_column(eval_expr(a.args[1], b, self.ctx), b.capacity)
            # rank by KEY validity only: the winning row's value may be
            # NULL and must be returned as NULL (Presto MinMaxByNState)
            yvalid = mask if yv.valid is None else (mask & yv.valid)
            yi = K._orderable_int(yv)
            big = jnp.iinfo(jnp.int64).max
            ykey = jnp.where(yvalid, yi, big if a.fn == "min_by" else -big)
            extremum = (K.segment_min if a.fn == "min_by"
                        else K.segment_max)(ykey, gid, n_groups)
            hit = yvalid & (ykey == extremum[gid])
            idx = K.segment_max(
                jnp.where(hit, jnp.arange(b.capacity), -1), gid, n_groups)
            safe = jnp.clip(idx, 0, b.capacity - 1)
            ok = idx >= 0
            val_valid = ok if col.valid is None else (ok & col.valid[safe])
            return Column(col.data[safe], val_valid, a.type, col.dictionary)
        if a.fn == "array_agg":
            # ragged output: host-side build (reference: ArrayAggregation
            # over an ObjectBigArray); dynamic mode only
            if self.static:
                raise StaticFallback("array_agg is dynamic-mode only")
            gidh = np.asarray(gid)
            rows_live = np.asarray(mask)  # NULL inputs are kept as NULL
            vh = np.asarray(valid)        # elements (Presto array_agg)
            data = np.asarray(col.data)
            if col.dictionary is not None:
                data = col.dictionary.values[
                    np.clip(data, 0, len(col.dictionary) - 1)]
            groups = [[] for _ in range(n_groups)]
            for row in np.flatnonzero(rows_live):
                g = int(gidh[row])
                if 0 <= g < n_groups:
                    if not vh[row]:
                        groups[g].append(None)
                        continue
                    groups[g].append(data[row].item()
                                     if hasattr(data[row], "item")
                                     else data[row])
            tuples = np.empty(n_groups, dtype=object)
            tuples[:] = [tuple(g) for g in groups]
            return _tuples_to_dict_column(tuples, nonempty, a.type)
        if a.fn in ("approx_set", "merge", "qdigest_agg", "tdigest_agg"):
            # serializable sketch build/merge: host-side per group like
            # array_agg (reference: ApproximateSetAggregation /
            # MergeHyperLogLogAggregation / QuantileDigestAggregation);
            # the vectorized approx_distinct/approx_percentile kernels
            # remain the in-query fast path
            if self.static:
                raise StaticFallback(f"{a.fn} is dynamic-mode only")
            from presto_tpu.functions import sketches as SK

            gidh = np.asarray(gid)
            vh = np.asarray(valid)
            data = np.asarray(col.data)
            if col.dictionary is not None:
                data = col.dictionary.values[
                    np.clip(data, 0, len(col.dictionary) - 1)]
            elif col.type.is_decimal:
                data = data.astype(np.float64) / (10 ** col.type.decimal_scale)
            wdata = None
            if a.fn == "tdigest_agg" and len(a.args) >= 2:
                wcol = to_column(eval_expr(a.args[1], b, self.ctx),
                                 b.capacity)
                wdata = np.asarray(wcol.data, np.float64)
                if wdata.ndim == 0:
                    wdata = np.full(b.capacity, float(wdata))
            groups: list = [[] for _ in range(n_groups)]
            wgroups: list = [[] for _ in range(n_groups)]
            for row in np.flatnonzero(vh):
                g = int(gidh[row])
                if 0 <= g < n_groups:
                    v = data[row]
                    groups[g].append(v.item() if hasattr(v, "item") else v)
                    if wdata is not None:
                        wgroups[g].append(float(wdata[row]))
            blobs = np.empty(n_groups, dtype=object)
            if a.fn == "approx_set":
                blobs[:] = [SK.hll_from_values(g) for g in groups]
            elif a.fn == "qdigest_agg":
                blobs[:] = [SK.qdigest_from_values(g) for g in groups]
            elif a.fn == "tdigest_agg":
                from presto_tpu.functions import tdigest as TD

                compression = TD.DEFAULT_COMPRESSION
                if len(a.args) >= 3:  # constant compression argument
                    cv = np.asarray(eval_expr(a.args[2], b, self.ctx).data)
                    if cv.ndim > 0:
                        raise NotImplementedError(
                            "tdigest_agg compression must be a constant")
                    compression = float(cv)
                blobs[:] = [TD.tdigest_from_values(
                    g, weights=wg if wdata is not None else None,
                    compression=compression)
                    for g, wg in zip(groups, wgroups)]
            else:  # merge over serialized sketches
                if a.type.name in ("HLL", "P4HLL"):
                    blobs[:] = [SK.hll_merge(g) for g in groups]
                elif a.type.name == "TDIGEST":
                    from presto_tpu.functions import tdigest as TD

                    blobs[:] = [TD.tdigest_merge(g) for g in groups]
                else:
                    blobs[:] = [SK.qdigest_merge(g) for g in groups]
            return _tuples_to_dict_column(blobs, nonempty, a.type)
        if a.fn in ("map_agg", "multimap_agg"):
            # ragged output, host-side like array_agg (reference:
            # MapAggregationFunction / MultimapAggregationFunction over a
            # KeyValuePairsState); dynamic mode only
            if self.static:
                raise StaticFallback(f"{a.fn} is dynamic-mode only")
            vcol = to_column(eval_expr(a.args[1], b, self.ctx), b.capacity)
            kh = np.asarray(col.data)
            if col.dictionary is not None:
                kh = col.dictionary.values[
                    np.clip(kh, 0, len(col.dictionary) - 1)]
            vhd = np.asarray(vcol.data)
            if vcol.dictionary is not None:
                vhd = vcol.dictionary.values[
                    np.clip(vhd, 0, len(vcol.dictionary) - 1)]
            vval = np.asarray(valid)
            vok = np.ones(b.capacity, bool) if vcol.valid is None \
                else np.asarray(vcol.valid)
            gidh = np.asarray(gid)
            groups = [dict() for _ in range(n_groups)]
            for row in np.flatnonzero(vval):  # NULL keys are skipped
                g = int(gidh[row])
                if not (0 <= g < n_groups):
                    continue
                k = kh[row].item() if hasattr(kh[row], "item") else kh[row]
                if isinstance(k, np.str_):
                    k = str(k)
                val = None
                if vok[row]:
                    val = vhd[row].item() if hasattr(vhd[row], "item") \
                        else vhd[row]
                    if isinstance(val, np.str_):
                        val = str(val)
                if a.fn == "multimap_agg":
                    groups[g].setdefault(k, []).append(val)
                else:
                    groups[g].setdefault(k, val)  # first value wins
            tuples = np.empty(n_groups, dtype=object)
            if a.fn == "multimap_agg":
                tuples[:] = [tuple(sorted(((k, tuple(v)) for k, v
                                           in g.items()),
                                          key=lambda p: repr(p[0])))
                             for g in groups]
            else:
                tuples[:] = [tuple(sorted(g.items(),
                                          key=lambda p: repr(p[0])))
                             for g in groups]
            return _tuples_to_dict_column(tuples, nonempty, a.type)
        if a.fn.startswith("classification_"):
            if self.static:
                raise StaticFallback(f"{a.fn} is dynamic-mode only")
            return self._classification_host(b, a, gid, n_groups,
                                             nonempty)
        if a.fn in ("set_agg", "set_union", "map_union_sum",
                    "approx_most_frequent", "reduce_agg",
                    "evaluate_classifier_predictions") \
                or (a.fn in ("min_by", "max_by") and len(a.args) == 3):
            if self.static:
                raise StaticFallback(f"{a.fn} is dynamic-mode only")
            return self._agg_column_host(b, a, gid, n_groups, col, valid,
                                         nonempty)
        if a.fn == "geometric_mean":
            x = jnp.where(valid, col.data.astype(jnp.float64), 1.0)
            s = K.segment_sum(jnp.log(jnp.maximum(x, 1e-300)), gid, n_groups)
            return Column(jnp.exp(s / jnp.maximum(cnt, 1)), nonempty, T.DOUBLE)
        if a.fn == "sum":
            if a.type.is_decimal and a.type.is_long_decimal:
                # exact Int128 accumulation (reference:
                # DecimalSumAggregation over UnscaledDecimal128Arithmetic)
                from presto_tpu.exec import dec128 as D128

                limbs = jnp.asarray(col.data) \
                    if getattr(col.data, "ndim", 1) == 2 \
                    else D128.from_int64(jnp.asarray(col.data))
                s = D128.segment_sum128(limbs, valid, gid, n_groups)
                return Column(s, nonempty, a.type)
            x = jnp.where(valid, col.data, jnp.zeros_like(col.data))
            s = K.segment_sum(x, gid, n_groups)
            if a.type.is_integer:
                s = s.astype(jnp.int64)
            return Column(s.astype(a.type.numpy_dtype()), nonempty, a.type)
        if a.fn == "avg":
            if a.type.name.startswith("INTERVAL"):
                # interval average stays an interval: truncating integer
                # division of the micros/months sum (reference:
                # IntervalDayToSecondAverageAggregation)
                x = jnp.where(valid, col.data, jnp.zeros_like(col.data))
                s = K.segment_sum(x, gid, n_groups).astype(jnp.int64)
                d = jnp.maximum(cnt, 1)
                r = jnp.sign(s) * (jnp.abs(s) // d)
                return Column(r, nonempty, a.type)
            if getattr(col.data, "ndim", 1) == 2:  # long decimal limbs
                from presto_tpu.exec import dec128 as D128

                f = D128.to_float64(jnp.asarray(col.data)) \
                    / (10 ** col.type.decimal_scale)
                x = jnp.where(valid, f, 0.0)
                s = K.segment_sum(x, gid, n_groups)
                return Column(s / jnp.maximum(cnt, 1), nonempty, T.DOUBLE)
            x = jnp.where(valid, col.data.astype(jnp.float64), 0.0)
            if col.type.is_decimal:
                x = x / (10 ** col.type.decimal_scale)
            s = K.segment_sum(x, gid, n_groups)
            return Column(s / jnp.maximum(cnt, 1), nonempty, T.DOUBLE)
        if a.fn in ("min", "max"):
            if getattr(col.data, "ndim", 1) == 2:  # long decimal limbs
                from presto_tpu.exec import dec128 as D128

                r = D128.segment_minmax128(jnp.asarray(col.data), valid,
                                           gid, n_groups, a.fn == "min")
                return Column(r, nonempty, a.type)
            if jnp.issubdtype(col.data.dtype, jnp.floating):
                ext = jnp.inf if a.fn == "min" else -jnp.inf
            elif col.data.dtype == jnp.bool_:
                ext = a.fn == "min"
            else:
                info = jnp.iinfo(col.data.dtype)
                ext = info.max if a.fn == "min" else info.min
            x = jnp.where(valid, col.data, jnp.asarray(ext, col.data.dtype))
            f = K.segment_min if a.fn == "min" else K.segment_max
            r = f(x, gid, n_groups)
            return Column(r.astype(col.data.dtype), nonempty, a.type, col.dictionary)
        if a.fn in ("arbitrary", "any_value"):
            idx = K.segment_max(jnp.where(valid, jnp.arange(b.capacity), -1), gid, n_groups)
            safe = jnp.clip(idx, 0, b.capacity - 1)
            return Column(col.data[safe], nonempty & (idx >= 0), a.type, col.dictionary)
        if a.fn in ("stddev", "stddev_samp", "stddev_pop", "variance", "var_samp", "var_pop"):
            x = jnp.where(valid, col.data.astype(jnp.float64), 0.0)
            s1 = K.segment_sum(x, gid, n_groups)
            s2 = K.segment_sum(x * x, gid, n_groups)
            n = jnp.maximum(cnt, 1).astype(jnp.float64)
            var_pop = s2 / n - (s1 / n) ** 2
            var_pop = jnp.maximum(var_pop, 0.0)
            if a.fn in ("stddev", "stddev_samp", "variance", "var_samp"):
                denom = jnp.maximum(cnt - 1, 1).astype(jnp.float64)
                var = var_pop * n / denom
                ok = nonempty & (cnt > 1)
            else:
                var = var_pop
                ok = nonempty
            r = jnp.sqrt(var) if a.fn.startswith("stddev") else var
            return Column(r, ok, T.DOUBLE)
        if a.fn in ("bool_and", "every"):
            x = jnp.where(valid, jnp.asarray(col.data, bool), True)
            r = K.segment_min(x.astype(jnp.int32), gid, n_groups) > 0
            return Column(r, nonempty, T.BOOLEAN)
        if a.fn == "bool_or":
            x = jnp.where(valid, jnp.asarray(col.data, bool), False)
            r = K.segment_max(x.astype(jnp.int32), gid, n_groups) > 0
            return Column(r, nonempty, T.BOOLEAN)
        if a.fn in ("partial_sum_double", "partial_sum_sq_double"):
            # PARTIAL step of avg/stddev decomposition (plan/distribute.py):
            # the float64 running sums the reference's accumulators keep
            # (operator/aggregation/AverageAggregations, VarianceAggregation)
            x = col.data.astype(jnp.float64)
            if col.type.is_decimal:
                x = x / (10 ** col.type.decimal_scale)
            if a.fn.endswith("sq_double"):
                x = x * x
            s = K.segment_sum(jnp.where(valid, x, 0.0), gid, n_groups)
            return Column(s, nonempty, T.DOUBLE)
        if a.fn in ("corr", "covar_samp", "covar_pop", "regr_slope",
                    "regr_intercept"):
            # bivariate family from co-moment segment sums (reference:
            # operator/aggregation/{Corr,Covar,Regr}*Aggregation over
            # CovarianceState: n, meanX, meanY, c2 — same moments,
            # vectorized).  Presto argument order is (y, x).
            yv = to_column(eval_expr(a.args[1], b, self.ctx), b.capacity)
            both = valid if yv.valid is None else (valid & yv.valid)

            def f64(c):
                d = jnp.asarray(c.data).astype(jnp.float64)
                return d / (10 ** c.type.decimal_scale) \
                    if c.type.is_decimal else d

            y = jnp.where(both, f64(col), 0.0)
            x = jnp.where(both, f64(yv), 0.0)
            n = K.segment_sum(both.astype(jnp.int32), gid,
                              n_groups).astype(jnp.float64)
            sx = K.segment_sum(x, gid, n_groups)
            sy = K.segment_sum(y, gid, n_groups)
            sxy = K.segment_sum(x * y, gid, n_groups)
            sxx = K.segment_sum(x * x, gid, n_groups)
            syy = K.segment_sum(y * y, gid, n_groups)
            n1 = jnp.maximum(n, 1.0)
            covp = sxy / n1 - (sx / n1) * (sy / n1)
            varx = jnp.maximum(sxx / n1 - (sx / n1) ** 2, 0.0)
            vary = jnp.maximum(syy / n1 - (sy / n1) ** 2, 0.0)
            if a.fn == "covar_pop":
                return Column(covp, n > 0, T.DOUBLE)
            if a.fn == "covar_samp":
                r = covp * n / jnp.maximum(n - 1.0, 1.0)
                return Column(r, n > 1, T.DOUBLE)
            if a.fn == "corr":
                denom = jnp.sqrt(varx * vary)
                r = covp / jnp.maximum(denom, 1e-300)
                return Column(r, (n > 1) & (denom > 0), T.DOUBLE)
            slope = covp / jnp.maximum(varx, 1e-300)
            if a.fn == "regr_slope":
                return Column(slope, (n > 1) & (varx > 0), T.DOUBLE)
            icept = sy / n1 - slope * (sx / n1)
            return Column(icept, (n > 1) & (varx > 0), T.DOUBLE)
        if a.fn in ("skewness", "kurtosis"):
            # central moments from raw power sums (reference:
            # CentralMomentsAggregation over CentralMomentsState)
            x = jnp.where(valid, col.data.astype(jnp.float64), 0.0)
            n = jnp.maximum(cnt, 1).astype(jnp.float64)
            s1 = K.segment_sum(x, gid, n_groups)
            s2 = K.segment_sum(x * x, gid, n_groups)
            s3 = K.segment_sum(x ** 3, gid, n_groups)
            mu = s1 / n
            m2 = jnp.maximum(s2 - n * mu * mu, 0.0)
            if a.fn == "skewness":
                m3 = s3 - 3 * mu * s2 + 2 * n * mu ** 3
                sd2 = m2 / jnp.maximum(n - 1.0, 1.0)
                r = n / jnp.maximum((n - 1) * (n - 2), 1.0) \
                    * m3 / jnp.maximum(sd2 ** 1.5, 1e-300)
                return Column(r, (cnt > 2) & (m2 > 0), T.DOUBLE)
            s4 = K.segment_sum(x ** 4, gid, n_groups)
            m4 = s4 - 4 * mu * s3 + 6 * mu * mu * s2 - 3 * n * mu ** 4
            sd2 = m2 / jnp.maximum(n - 1.0, 1.0)
            d = jnp.maximum((n - 1) * (n - 2) * (n - 3), 1.0)
            r = n * (n + 1) / d * m4 / jnp.maximum(sd2 * sd2, 1e-300) \
                - 3.0 * (n - 1) ** 2 / jnp.maximum((n - 2) * (n - 3), 1.0)
            return Column(r, (cnt > 3) & (m2 > 0), T.DOUBLE)
        if a.fn == "entropy":
            # entropy of empirical distribution from count weights
            # (reference: EntropyAggregation): log2(S) - sum(c*log2 c)/S
            c = jnp.where(valid, col.data.astype(jnp.float64), 0.0)
            c = jnp.maximum(c, 0.0)
            s = K.segment_sum(c, gid, n_groups)
            clogc = K.segment_sum(
                jnp.where(c > 0, c * jnp.log2(jnp.maximum(c, 1e-300)), 0.0),
                gid, n_groups)
            r = jnp.where(s > 0,
                          jnp.log2(jnp.maximum(s, 1e-300)) - clogc
                          / jnp.maximum(s, 1e-300), 0.0)
            return Column(r, nonempty, T.DOUBLE)
        if a.fn in ("bitwise_and_agg", "bitwise_or_agg"):
            # per-bit segment min/max over an (n, 64) bit plane — ONE
            # segment op (reference: BitwiseAndAggregation/
            # BitwiseOrAggregation's running long)
            xi = jnp.asarray(col.data).astype(jnp.int64)
            shifts = jnp.arange(64, dtype=jnp.int64)
            bits = ((xi[:, None] >> shifts[None, :]) & 1).astype(jnp.int32)
            if a.fn == "bitwise_and_agg":
                bits = jnp.where(valid[:, None], bits, 1)
                red = K.segment_min(bits, gid, n_groups)
            else:
                bits = jnp.where(valid[:, None], bits, 0)
                red = K.segment_max(bits, gid, n_groups)
            r = jnp.sum(red.astype(jnp.int64) << shifts[None, :], axis=1)
            return Column(r, nonempty, T.BIGINT)
        if a.fn in ("learn_classifier", "learn_regressor"):
            # host-side training inside the aggregate (reference:
            # presto-ml LearnAggregations over libsvm; here numpy
            # logistic regression / ridge LSQ — see functions/ml.py)
            if self.static:
                raise StaticFallback(f"{a.fn} is dynamic-mode only")
            from presto_tpu.functions import ml as ML

            fv = eval_expr(a.args[1], b, self.ctx)
            feats = np.asarray(fv.data)
            labels = np.asarray(col.data)
            if col.dictionary is not None:
                labels = col.dictionary.values[
                    np.clip(labels, 0, len(col.dictionary) - 1)]
            elif col.type.is_decimal:
                labels = labels.astype(np.float64) \
                    / (10 ** col.type.decimal_scale)
            gidh = np.asarray(gid)
            vh = np.asarray(valid)
            if fv.valid is not None:  # rows with NULL features skip
                vh = vh & np.asarray(fv.valid)
            blobs = np.empty(n_groups, dtype=object)
            for g in range(n_groups):
                m = (gidh == g) & vh
                if not m.any():
                    blobs[g] = b""
                    continue
                if a.fn == "learn_classifier":
                    blobs[g] = ML.train_classifier(labels[m], feats[m])
                else:
                    blobs[g] = ML.train_regressor(
                        labels[m].astype(np.float64), feats[m])
            return _tuples_to_dict_column(blobs, nonempty, a.type)
        if a.fn in ("histogram", "numeric_histogram", "map_union"):
            # ragged MAP output, host-side like map_agg (reference:
            # Histogram / NumericHistogramAggregation / MapUnionAggregation)
            if self.static:
                raise StaticFallback(f"{a.fn} is dynamic-mode only")
            gidh = np.asarray(gid)
            vh = np.asarray(valid)
            data = np.asarray(col.data)
            if col.dictionary is not None:
                data = col.dictionary.values[
                    np.clip(data, 0, len(col.dictionary) - 1)]
            if a.fn == "numeric_histogram":
                nb_v = eval_expr(a.args[0], b, self.ctx)
                nb = int(nb_v.data if getattr(nb_v.data, "ndim", 0) == 0
                         else np.asarray(nb_v.data)[0])
                vcol = to_column(eval_expr(a.args[1], b, self.ctx),
                                 b.capacity)
                vvh = mask if vcol.valid is None else \
                    np.asarray(mask & vcol.valid)
                vdata = np.asarray(vcol.data).astype(np.float64)
                if vcol.type.is_decimal:
                    vdata = vdata / (10 ** vcol.type.decimal_scale)
                tuples = np.empty(n_groups, dtype=object)
                for g in range(n_groups):
                    vals = np.sort(vdata[(gidh == g) & vvh])
                    if not len(vals):
                        tuples[g] = ()
                        continue
                    bins = np.array_split(vals, max(min(nb, len(vals)), 1))
                    tuples[g] = tuple(sorted(
                        (float(np.mean(bin_)), float(len(bin_)))
                        for bin_ in bins if len(bin_)))
                return _tuples_to_dict_column(tuples, nonempty, a.type)
            groups = [dict() for _ in range(n_groups)]
            for row in np.flatnonzero(vh):
                g = int(gidh[row])
                if not (0 <= g < n_groups):
                    continue
                v = data[row]
                v = v.item() if hasattr(v, "item") else v
                if isinstance(v, np.str_):
                    v = str(v)
                if a.fn == "histogram":
                    groups[g][v] = groups[g].get(v, 0) + 1
                else:  # map_union: v is a map value (tuple of pairs)
                    for k, mv in v:
                        groups[g].setdefault(k, mv)
            tuples = np.empty(n_groups, dtype=object)
            tuples[:] = [tuple(sorted(g.items(), key=lambda p: repr(p[0])))
                         for g in groups]
            return _tuples_to_dict_column(tuples, nonempty, a.type)
        raise ExecutionError(f"aggregate {a.fn} not implemented")

    def _agg_column_host(self, b: Batch, a: ir.AggCall, gid, n_groups,
                         col, valid, nonempty) -> Column:
        """Host-side ragged aggregates added in round 5 (reference:
        SetAggregationFunction / SetUnionFunction / MapUnionSumAggregation
        / ApproximateMostFrequent / MinMaxByNAggregationFunction /
        ReduceAggregationFunction) — same dynamic-mode host-build shape
        as array_agg/map_agg above."""

        def decode(c):
            d = np.asarray(c.data)
            if c.dictionary is not None:
                d = c.dictionary.values[np.clip(d, 0, len(c.dictionary) - 1)]
            return d

        gidh = np.asarray(gid)
        vh = np.asarray(valid)
        data = decode(col)

        def host(v):
            v = v.item() if hasattr(v, "item") else v
            return str(v) if isinstance(v, np.str_) else v

        if a.fn == "set_agg":
            groups = [dict() for _ in range(n_groups)]  # ordered distinct
            for row in np.flatnonzero(vh):
                g = int(gidh[row])
                if 0 <= g < n_groups:
                    groups[g].setdefault(host(data[row]))
            tuples = np.empty(n_groups, dtype=object)
            tuples[:] = [tuple(g) for g in groups]
            return _tuples_to_dict_column(tuples, nonempty, a.type)
        if a.fn == "set_union":
            groups = [dict() for _ in range(n_groups)]
            for row in np.flatnonzero(vh):
                g = int(gidh[row])
                if 0 <= g < n_groups:
                    for e in data[row]:
                        groups[g].setdefault(e)
            tuples = np.empty(n_groups, dtype=object)
            tuples[:] = [tuple(g) for g in groups]
            return _tuples_to_dict_column(tuples, nonempty, a.type)
        if a.fn == "map_union_sum":
            groups = [dict() for _ in range(n_groups)]
            for row in np.flatnonzero(vh):
                g = int(gidh[row])
                if 0 <= g < n_groups:
                    for k, mv in data[row]:
                        if mv is None:
                            groups[g].setdefault(k, None)
                        else:
                            cur = groups[g].get(k)
                            groups[g][k] = mv if cur is None else cur + mv
            tuples = np.empty(n_groups, dtype=object)
            tuples[:] = [tuple(sorted(g.items(), key=lambda p: repr(p[0])))
                         for g in groups]
            return _tuples_to_dict_column(tuples, nonempty, a.type)
        if a.fn == "approx_most_frequent":
            # exact counting + top-K truncation: a superset of the
            # reference's stream-summary guarantee at this scale
            bk = np.asarray(eval_expr(a.args[0], b, self.ctx).data)
            buckets = int(bk if bk.ndim == 0 else bk.flat[0])
            vcol = to_column(eval_expr(a.args[1], b, self.ctx), b.capacity)
            vdata = decode(vcol)
            vvalid = np.asarray(b.sel if vcol.valid is None
                                else (b.sel & vcol.valid))
            counts = [dict() for _ in range(n_groups)]
            for row in np.flatnonzero(vvalid):
                g = int(gidh[row])
                if 0 <= g < n_groups:
                    k = host(vdata[row])
                    counts[g][k] = counts[g].get(k, 0) + 1
            tuples = np.empty(n_groups, dtype=object)
            tuples[:] = [
                tuple(sorted(
                    sorted(g.items(), key=lambda p: (-p[1], repr(p[0])))
                    [:buckets], key=lambda p: repr(p[0])))
                for g in counts]
            ok = jnp.asarray(
                np.asarray([len(g) > 0 for g in counts], bool))
            return _tuples_to_dict_column(tuples, ok, a.type)
        if a.fn in ("min_by", "max_by"):  # 3-arg: top-n by key
            ycol = to_column(eval_expr(a.args[1], b, self.ctx), b.capacity)
            ydata = decode(ycol)
            yvalid = vh if ycol.valid is None else (vh & np.asarray(
                ycol.valid))
            nv = np.asarray(eval_expr(a.args[2], b, self.ctx).data)
            topn = int(nv if nv.ndim == 0 else nv.flat[0])
            xvalid = np.ones(b.capacity, bool) if col.valid is None \
                else np.asarray(col.valid)
            rows_by_g = [[] for _ in range(n_groups)]
            for row in np.flatnonzero(np.asarray(b.sel) & yvalid):
                g = int(gidh[row])
                if 0 <= g < n_groups:
                    rows_by_g[g].append(row)
            tuples = np.empty(n_groups, dtype=object)
            out = []
            for g_rows in rows_by_g:
                g_rows.sort(key=lambda r: host(ydata[r]),
                            reverse=(a.fn == "max_by"))
                out.append(tuple(
                    host(data[r]) if xvalid[r] else None
                    for r in g_rows[:topn]))
            tuples[:] = out
            return _tuples_to_dict_column(tuples, nonempty, a.type)
        if a.fn == "evaluate_classifier_predictions":
            # accuracy + per-label precision/recall summary (reference:
            # presto-ml EvaluateClassifierPredictionsAggregation)
            pcol = to_column(eval_expr(a.args[1], b, self.ctx), b.capacity)
            pdata = decode(pcol)
            pvh = vh if pcol.valid is None else (vh & np.asarray(pcol.valid))
            texts = np.empty(n_groups, dtype=object)
            stats = [([], []) for _ in range(n_groups)]
            for row in np.flatnonzero(pvh):
                g = int(gidh[row])
                if 0 <= g < n_groups:
                    stats[g][0].append(host(data[row]))
                    stats[g][1].append(host(pdata[row]))
            for g, (truth, pred) in enumerate(stats):
                n = len(truth)
                if n == 0:
                    texts[g] = ""
                    continue
                correct = sum(1 for t, p in zip(truth, pred) if t == p)
                lines = [f"Accuracy: {correct}/{n} "
                         f"({100.0 * correct / n:.2f}%)"]
                for lab in sorted({*truth, *pred}, key=repr):
                    tp = sum(1 for t, p in zip(truth, pred)
                             if t == p == lab)
                    pp = sum(1 for p in pred if p == lab)
                    ap = sum(1 for t in truth if t == lab)
                    if pp:
                        lines.append(f"Precision({lab}): {tp}/{pp} "
                                     f"({100.0 * tp / pp:.2f}%)")
                    if ap:
                        lines.append(f"Recall({lab}): {tp}/{ap} "
                                     f"({100.0 * tp / ap:.2f}%)")
                texts[g] = "\n".join(lines)
            return _tuples_to_dict_column(texts, nonempty, a.type)
        # reduce_agg: vectorized input apply + per-level tree combine
        from presto_tpu.exec.colval import LambdaVal

        _value_ref, init_ref, in_lam, comb_lam = a.args
        in_l = LambdaVal(in_lam.params, in_lam.param_types, in_lam.body,
                         self.ctx, in_lam.type)
        comb_l = LambdaVal(comb_lam.params, comb_lam.param_types,
                           comb_lam.body, self.ctx, comb_lam.type)
        from presto_tpu.functions.scalar import (_colval_from_pylist,
                                                 _pylist_from_colval)

        init_v = eval_expr(init_ref, b, self.ctx)
        init_host = _pylist_from_colval(init_v, 1)[0]
        st = a.type
        rows = np.flatnonzero(vh)
        vals = [host(data[r]) for r in rows]
        if vals:
            states = _pylist_from_colval(
                in_l.apply({
                    in_lam.params[0]: _colval_from_pylist(
                        [init_host] * len(vals), st),
                    in_lam.params[1]: _colval_from_pylist(
                        vals, col.type)}), len(vals))
        else:
            states = []
        per_group: list = [[] for _ in range(n_groups)]
        for r, s in zip(rows, states):
            g = int(gidh[r])
            if 0 <= g < n_groups:
                per_group[g].append(s)
        # tree combine: one vectorized lambda apply per level
        while any(len(g) > 1 for g in per_group):
            lefts, rights, slots = [], [], []
            for gi, g in enumerate(per_group):
                nxt = []
                i = 0
                while i + 1 < len(g):
                    slots.append((gi, len(nxt)))
                    lefts.append(g[i])
                    rights.append(g[i + 1])
                    nxt.append(None)  # placeholder
                    i += 2
                if i < len(g):
                    nxt.append(g[i])
                per_group[gi] = nxt
            combined = _pylist_from_colval(
                comb_l.apply({
                    comb_lam.params[0]: _colval_from_pylist(lefts, st),
                    comb_lam.params[1]: _colval_from_pylist(rights, st)}),
                len(lefts))
            for (gi, si), val in zip(slots, combined):
                per_group[gi][si] = val
        results = [g[0] if g else None for g in per_group]
        return to_column(_colval_from_pylist(results, st), n_groups)

    def _approx_percentile_host(self, b: Batch, a: ir.AggCall, gid,
                                n_groups, col, valid, nonempty) -> Column:
        """Array-of-percentiles and weighted approx_percentile: exact
        host computation per group over (value, cumulative weight)
        (reference: Approximate*PercentileArrayAggregations and the
        weighted overloads; exact beats approximate at these sizes)."""
        has_weight = len(a.args) >= 3
        pv = eval_expr(a.args[2 if has_weight else 1], b, self.ctx)
        if pv.dictionary is not None:  # ARRAY of percentiles
            ps = list(pv.dictionary.values[int(np.asarray(pv.data).flat[0])])
            array_out = True
        else:
            p0 = np.asarray(pv.data)
            ps = [float(p0 if p0.ndim == 0 else p0.flat[0])]
            array_out = False
        data = np.asarray(col.data, np.float64)
        if col.type.is_decimal:
            data = data / (10 ** col.type.decimal_scale)
        wts = np.ones(b.capacity)
        if has_weight:
            wcol = to_column(eval_expr(a.args[1], b, self.ctx), b.capacity)
            wts = np.asarray(wcol.data, np.float64)
            if wts.ndim == 0:
                wts = np.full(b.capacity, float(wts))
        gidh = np.asarray(gid)
        vh = np.asarray(valid)
        outs = np.empty(n_groups, dtype=object)
        scalar_vals = np.zeros(n_groups)
        for g in range(n_groups):
            m = (gidh == g) & vh & (wts > 0)
            if not m.any():
                outs[g] = None
                continue
            v = data[m]
            w = wts[m]
            o = np.argsort(v, kind="stable")
            v, w = v[o], w[o]
            cw = np.cumsum(w)
            qs = []
            for p in ps:
                # first value whose cumulative weight reaches p * total
                i = int(np.searchsorted(cw, float(p) * cw[-1],
                                        side="left"))
                qs.append(float(v[min(i, len(v) - 1)]))
            outs[g] = tuple(qs)
            scalar_vals[g] = qs[0]
        if array_out:
            et = a.type.params[0]
            if et.is_integer:
                outs_t = np.empty(n_groups, dtype=object)
                outs_t[:] = [None if t is None
                             else tuple(int(x) for x in t) for t in outs]
                outs = outs_t
            ok = jnp.asarray(np.asarray(
                [t is not None for t in outs], bool)) & nonempty
            tuples = np.empty(n_groups, dtype=object)
            tuples[:] = [t if t is not None else () for t in outs]
            return _tuples_to_dict_column(tuples, ok, a.type)
        vals = scalar_vals
        if a.type.is_integer:
            vals = np.rint(vals)
        ok = jnp.asarray(np.asarray([t is not None for t in outs], bool))
        return Column(jnp.asarray(vals.astype(a.type.numpy_dtype())),
                      ok & nonempty, a.type)

    def _classification_host(self, b: Batch, a: ir.AggCall, gid,
                             n_groups, nonempty) -> Column:
        """classification_{miss_rate, fall_out, precision, recall,
        thresholds}(buckets, truth, prediction[, weight]) ->
        ARRAY(DOUBLE) at thresholds i/buckets (reference:
        PrecisionRecallAggregation family; prediction >= threshold
        counts as a positive call)."""
        bk = np.asarray(eval_expr(a.args[0], b, self.ctx).data)
        buckets = int(bk if bk.ndim == 0 else bk.flat[0])
        if buckets < 2:
            raise ExecutionError(f"{a.fn}: buckets must be >= 2")
        tcol = to_column(eval_expr(a.args[1], b, self.ctx), b.capacity)
        pcol = to_column(eval_expr(a.args[2], b, self.ctx), b.capacity)
        truth = np.asarray(tcol.data, bool)
        pred = np.asarray(pcol.data, np.float64)
        wts = np.ones(b.capacity)
        if len(a.args) > 3:
            wcol = to_column(eval_expr(a.args[3], b, self.ctx), b.capacity)
            wts = np.asarray(wcol.data, np.float64)
        vh = np.asarray(b.sel)
        for c in (tcol, pcol):
            if c.valid is not None:
                vh = vh & np.asarray(c.valid)
        if np.any(vh & ((pred < 0) | (pred > 1))):
            raise ExecutionError(
                f"{a.fn}: predictions must be in [0, 1]")
        gidh = np.asarray(gid)
        th = np.arange(buckets) / buckets
        tuples = np.empty(n_groups, dtype=object)
        for g in range(n_groups):
            m = (gidh == g) & vh
            if not m.any():
                tuples[g] = ()
                continue
            t, p, w = truth[m], pred[m], wts[m]
            pos = p[:, None] >= th[None, :]  # (rows, buckets)
            tp = (w[:, None] * (pos & t[:, None])).sum(0)
            fp = (w[:, None] * (pos & ~t[:, None])).sum(0)
            fn_ = (w[:, None] * (~pos & t[:, None])).sum(0)
            tn = (w[:, None] * (~pos & ~t[:, None])).sum(0)
            with np.errstate(invalid="ignore", divide="ignore"):
                if a.fn == "classification_thresholds":
                    out = th
                elif a.fn == "classification_precision":
                    out = tp / (tp + fp)
                elif a.fn == "classification_recall":
                    out = tp / (tp + fn_)
                elif a.fn == "classification_miss_rate":
                    out = fn_ / (tp + fn_)
                else:  # fall_out
                    out = fp / (fp + tn)
            tuples[g] = tuple(None if np.isnan(x) else float(x)
                              for x in np.broadcast_to(out, th.shape))
        return _tuples_to_dict_column(tuples, nonempty, a.type)

    def _merge_agg_column(self, b: Batch, a: ir.AggCall, gid, n_groups,
                          mask) -> Column:
        """FINAL-step merges over gathered partial states (reference:
        AggregationNode.Step.FINAL combining intermediate accumulator
        pages).  Args are Refs to partial-state columns."""

        def summed(e, zero=0.0):
            c = to_column(eval_expr(e, b, self.ctx), b.capacity)
            valid = mask if c.valid is None else (mask & c.valid)
            x = jnp.where(valid, c.data, jnp.asarray(zero, c.data.dtype))
            return K.segment_sum(x, gid, n_groups), K.segment_sum(
                valid.astype(jnp.int32), gid, n_groups).astype(jnp.int64)

        if a.fn == "merge_count":
            s, _ = summed(a.args[0], 0)
            return Column(s.astype(jnp.int64), None, T.BIGINT)
        if a.fn == "merge_avg":
            s, _ = summed(a.args[0])
            c, _ = summed(a.args[1], 0)
            c = c.astype(jnp.int64)
            return Column(s / jnp.maximum(c, 1), c > 0, T.DOUBLE)
        # merge_stddev*/merge_var*: args (sum, sum_sq, count)
        s1, _ = summed(a.args[0])
        s2, _ = summed(a.args[1])
        cnt, _ = summed(a.args[2], 0)
        cnt = cnt.astype(jnp.int64)
        n = jnp.maximum(cnt, 1).astype(jnp.float64)
        var_pop = jnp.maximum(s2 / n - (s1 / n) ** 2, 0.0)
        fn = a.fn[len("merge_"):]
        if fn in ("stddev", "stddev_samp", "variance", "var_samp"):
            denom = jnp.maximum(cnt - 1, 1).astype(jnp.float64)
            var = var_pop * n / denom
            ok = cnt > 1
        else:
            var = var_pop
            ok = cnt > 0
        r = jnp.sqrt(var) if fn.startswith("stddev") else var
        return Column(r, ok, T.DOUBLE)

    def _global_aggregate(self, b: Batch, aggs: Dict[str, ir.AggCall]) -> Batch:
        gid = jnp.zeros((b.capacity,), dtype=jnp.int64)
        out_cols = {}
        for sym, a in aggs.items():
            c = self._agg_column(b, a, gid, 1)
            out_cols[sym] = c
        return Batch(out_cols, jnp.ones((1,), bool))

    # ---- joins -------------------------------------------------------
    def _exec_spatialjoin(self, node) -> Batch:
        """Grid-indexed spatial inner join (reference:
        SpatialJoinOperator over PagesRTreeIndex; see P.SpatialJoin for
        the TPU-native redesign).  Dynamic-mode only: the match count is
        data-dependent."""
        if self.static:
            raise StaticFallback("spatial join is dynamic-mode only")
        from presto_tpu.functions import geospatial as GEO

        left = self.exec_node(node.left)
        right = self.exec_node(node.right)
        lrows = np.flatnonzero(np.asarray(left.sel))
        rrows = np.flatnonzero(np.asarray(right.sel))

        def coords(batch, rows, sym):
            c = batch.columns[sym]
            v = np.asarray(c.data, np.float64)[rows]
            if c.valid is not None:
                v = np.where(np.asarray(c.valid)[rows], v, np.nan)
            return v

        px = coords(left, lrows, node.probe_x)
        py = coords(left, lrows, node.probe_y)
        # NULL coordinates (NaN after masking) match nothing — drop them
        # BEFORE the grid, where a NaN would poison the cell math
        pkeep = np.isfinite(px) & np.isfinite(py)
        lrows, px, py = lrows[pkeep], px[pkeep], py[pkeep]
        if node.kind == "contains":
            gc = right.columns[node.build_geom]
            if gc.dictionary is None:
                raise ExecutionError("spatial join build side must be a "
                                     "geometry/varchar column")
            if gc.valid is not None:  # NULL geometry matches nothing
                rrows = rrows[np.asarray(gc.valid)[rrows]]
            codes = np.clip(np.asarray(gc.data)[rrows], 0,
                            len(gc.dictionary) - 1)
            entries = gc.dictionary.values
            # parse + index per DISTINCT referenced entry (a
            # low-cardinality geometry column must not replicate its
            # edge arrays per row, and unreferenced dictionary entries
            # must not poison the join)
            uniq, inv = np.unique(codes, return_inverse=True)
            geoms = []
            for c in uniq:
                g = entries[int(c)]
                g = g if isinstance(g, tuple) else GEO.parse_wkt(str(g))
                if g[0] not in ("polygon",):
                    raise ExecutionError(
                        f"spatial join build over {g[0]} geometries is "
                        "not supported (polygons only)")
                geoms.append(g)
            li, gi = GEO.grid_contains_join(px, py, geoms)
            # expand geometry matches back to build ROWS sharing the code
            order = np.argsort(inv, kind="stable")
            starts = np.searchsorted(inv[order], np.arange(len(uniq)))
            ends = np.searchsorted(inv[order], np.arange(len(uniq)),
                                   side="right")
            counts = ends[gi] - starts[gi]
            li = np.repeat(li, counts)
            flat = (np.arange(int(counts.sum()), dtype=np.int64)
                    - np.repeat(np.concatenate(
                        [[0], np.cumsum(counts)[:-1]]) if len(counts)
                        else np.empty(0, np.int64), counts)
                    + np.repeat(starts[gi], counts))
            ri = order[flat]
        else:
            bx = coords(right, rrows, node.build_x)
            by = coords(right, rrows, node.build_y)
            bkeep = np.isfinite(bx) & np.isfinite(by)
            rrows, bx, by = rrows[bkeep], bx[bkeep], by[bkeep]
            li, ri = GEO.grid_distance_join(px, py, bx, by, node.radius,
                                            node.strict)
        lgat = jnp.asarray(lrows[li]) if len(li) else jnp.zeros(0, jnp.int32)
        rgat = jnp.asarray(rrows[ri]) if len(ri) else jnp.zeros(0, jnp.int32)
        lb = K.gather_batch(left, lgat)
        rb = K.gather_batch(right, rgat)
        merged = dict(lb.columns)
        merged.update(rb.columns)
        out = Batch(merged, jnp.ones((len(li),), bool))
        if node.filter is not None:
            out = Batch(merged, eval_predicate(node.filter, out, self.ctx))
        return out

    #: a batch under this many slots is never compacted, and no bound is
    #: under 2 ** COMPACT_MIN_BOUND_BITS slots
    COMPACT_MIN_CAPACITY = 1 << 19
    COMPACT_MIN_BOUND_BITS = 14

    def _maybe_compact_static(self, b: Batch, est) -> Batch:
        """Guarded estimate-driven compaction (see _aggregate_static):
        dropping masked rows is always semantically safe; the guard
        covers the estimate being wrong."""
        if not self.static or est is None \
                or b.capacity < self.COMPACT_MIN_CAPACITY:
            return b
        bound = 1 << max(int(np.ceil(np.log2(max(est, 1) * 2))),
                         self.COMPACT_MIN_BOUND_BITS)
        # the top_k costs one sort of the capacity whatever the bound
        # (77.5 ms at 28.8 M slots for 2^20 and for 2^21: PERF.md section
        # 6, PR 34), the gather grows with the bound: up to 2^20 rows a
        # quarter of the capacity is worth it, beyond that an eighth
        if bound > min(b.capacity // 4, max(1 << 20, b.capacity // 8)):
            return b
        self.guards.append(jnp.sum(b.sel.astype(jnp.int32)) > bound)
        out = _compact_batch(b, bound)
        e = self._batch_order.get(id(b))
        if e is not None and e[0] is b:
            # compaction keeps live rows in order AND moves them to a
            # prefix: certainty upgrades to tail-masked
            self._note_order(out, e[1], tail_ok=True)
        return out

    def _exec_join(self, node: P.Join) -> Batch:
        from presto_tpu.memory.context import batch_bytes

        produce = getattr(node, "rf_produce", None)
        if not (produce and node.join_type in ("INNER", "SEMI")
                and self._df_enabled() and self._rf_build_complete(node)):
            produce = None
        elif not self._rf_mask_pays(node):
            # nothing is registered: the probe scans find no summary
            # and run filter-free, as under an unannotated join
            self._count("df_filters_declined", len(produce))
            produce = None
        if produce:
            # dynamic filtering: run the BUILD side first and register
            # its key summary, so the probe subtree's scans consume the
            # completed filter before they execute (the reference gates
            # probe-side scan startup on build completion the same way)
            right = self.exec_node(node.right)
            self._rf_register(produce, right)
            left = self.exec_node(node.left)
        else:
            left = self.exec_node(node.left)
            right = self.exec_node(node.right)
        left = self._maybe_compact_static(
            left, getattr(node, "left_est_hint", None))
        if getattr(node, "index_lookup", None) is None:
            # index joins need the build side's whole-table natural
            # order — never compact it
            right = self._maybe_compact_static(
                right, getattr(node, "right_est_hint", None))
        if node.join_type == "RIGHT":
            # RIGHT = mirrored LEFT with output order left-cols-first
            node = P.Join(node.right, node.left, "LEFT",
                          [(rk, lk) for lk, rk in node.criteria], node.filter)
            left, right = right, left
        # spill-tiered degradation (exec/spill_exec.py): correct for
        # INNER/LEFT/FULL equi-joins — every match pair lands in one
        # key-hash partition and unmatched rows surface exactly once.
        # SEMI/ANTI stay unspilled: their null-semantics couple
        # partitions.  The PR-5 dynamic filter above already pruned the
        # probe sel, and the live_est_fn re-probe lets a filter-shrunken
        # probe keep the join fully resident (compacted) — the
        # interaction the robust-HHJ paper highlights.
        if node.join_type in ("INNER", "LEFT", "FULL") and node.criteria \
                and not self.static:
            from presto_tpu.exec import spill_exec as SE

            def live_est():
                nl = int(jax.device_get(left.row_count()))
                nr = int(jax.device_get(right.row_count()))
                bl = batch_bytes(left) * nl / max(left.capacity, 1)
                br = batch_bytes(right) * nr / max(right.capacity, 1)
                return SE.WORKING_SET_FACTOR * (bl + br)

            dec = SE.plan_degradation(
                self, node,
                SE.WORKING_SET_FACTOR * (batch_bytes(left)
                                         + batch_bytes(right)),
                left.capacity + right.capacity, live_est_fn=live_est)
            if dec.degrade:
                holder = [left, right]
                del left, right  # holder owns the refs; spill path frees
                return SE.hybrid_join(self, holder, node, dec)
            if dec.mem_key:
                try:
                    if dec.budget == -1:
                        # filter-kept residency: shed the pruned rows so
                        # the live working set is what HBM actually holds
                        left = K.compact(left)
                        right = K.compact(right)
                    return self._join_batches(left, right, node)
                finally:
                    self.mem.set_bytes(dec.mem_key, 0)
        out = self._join_batches(left, right, node)
        if node.join_type in ("SEMI", "ANTI", "MARK"):
            # probe masked in place: row positions untouched
            self._copy_order(left, out)
        return out

    def _index_build_whole(self, node: P.Join, il: dict,
                           right: Batch) -> bool:
        """Is the build batch the index's WHOLE table in natural order
        (row i holds key min + i)?  Here a scan is the table."""
        return right.capacity == il["rows"]

    def _join_batches(self, left: Batch, right: Batch, node: P.Join) -> Batch:
        jt = node.join_type
        if jt == "CROSS":
            return self._cross_join(left, right, node)
        if jt == "FULL":
            return self._full_join(left, right, node)
        if right.capacity == 0 and jt in ("INNER", "LEFT", "SEMI",
                                          "ANTI", "MARK"):
            # zero-capacity build (e.g. an empty side an outer join must
            # preserve): no row matches, and gathers into zero-length
            # arrays are not representable — emit the no-match result
            # shape directly
            if jt == "SEMI":
                return left.with_sel(jnp.zeros_like(left.sel))
            if jt == "ANTI":
                return left
            merged = dict(left.columns)
            if jt == "MARK":
                # x IN (empty) is FALSE, never NULL, for every probe
                merged[node.mark] = Column(
                    jnp.zeros((left.capacity,), bool), None, T.BOOLEAN,
                    None)
                return Batch(merged, left.sel)
            never = jnp.zeros((left.capacity,), bool)
            for name, t in node.right.outputs():
                c = right.columns[name]
                shape = (left.capacity,) + tuple(c.data.shape[1:])
                merged[name] = Column(jnp.zeros(shape, c.data.dtype),
                                      never, t, c.dictionary)
            if jt == "INNER":
                return Batch(merged, never)
            return Batch(merged, left.sel)  # LEFT: all rows, NULL right
        lkeys = [left.columns[lk] for lk, _ in node.criteria]
        rkeys = [right.columns[rk] for _, rk in node.criteria]
        lkeys, rkeys = _unify_key_dictionaries(lkeys, rkeys)
        # SQL equi-join: NULL never matches NULL — exclude null-keyed rows
        # (pack_keys' null code is a GROUP BY semantic, not a join one)
        lsel = left.sel
        rsel = right.sel
        for c in lkeys:
            if c.valid is not None:
                lsel = lsel & c.valid
        for c in rkeys:
            if c.valid is not None:
                rsel = rsel & c.valid
        # P10 index join: dense unique build key -> the probe is ONE
        # gather at position key - key_min, no sorts at all (hint from
        # plan/optimizer._index_lookup_info).  The identity layout
        # (row i holds key min+i) only holds when the build batch is the
        # WHOLE table in natural order — sharded executors re-split
        # scans (allow_index_join=False there), and a build-side layout
        # verification catches everything else: a guard in static mode,
        # a host check (fall back to the sort join) in dynamic mode.
        il = getattr(node, "index_lookup", None)
        bk = il.get("block_keys", 1) if il else 1
        br = il.get("block_rows", 1) if il else 1
        strided = (bk, br) != (1, 1)
        full_build = il is not None and self._index_build_whole(
            node, il, right)
        use_index = (il is not None
                     and (self.allow_index_join or full_build)
                     and len(lkeys) == 1
                     # strided layouts also run over CHUNK-sized builds:
                     # bucket-aligned chunks are contiguous row ranges,
                     # so the layout holds with a chunk-local base taken
                     # from the build data itself (traced)
                     and (full_build or strided)
                     and lkeys[0].dictionary is None
                     and rkeys[0].dictionary is None
                     and getattr(lkeys[0].data, "ndim", 1) == 1)
        if use_index and strided:
            # strided builds: the index gather runs at PROBE capacity
            # and the output stays there, while the sort join's output
            # materializes at its est-driven bound — which wins big
            # whenever upstream filters/semi-joins leave the build
            # sparse (measured: SF1 Q3 6M/1.5M loses ~150ms; SF100 Q18
            # chunks with a highly selective semi-join upstream lose
            # ~7%).  Gate to probes not much wider than the build.
            use_index = lkeys[0].data.shape[0] <= 2 * right.capacity
        index_ridx = index_rows = None
        if il is not None and os.environ.get("PRESTO_TPU_DEBUG_INDEX"):
            import sys as _sys

            print(f"index-join debug: {node.criteria} use_index="
                  f"{use_index} full_build={full_build} strided={strided} "
                  f"rcap={right.capacity} lcap={lkeys[0].data.shape if hasattr(lkeys[0].data, 'shape') else '?'}",
                  file=_sys.stderr, flush=True)
        if use_index:
            nrows = right.capacity
            rk_arr = jnp.asarray(rkeys[0].data).astype(jnp.int64)
            ar = jnp.arange(nrows, dtype=jnp.int64)
            # row i holds key base + (i // br) * bk + i % br — dense
            # layouts are the bk == br == 1 case (identity)
            if full_build:
                base = jnp.asarray(il["min"], jnp.int64)
            else:
                # chunk-local base from the data; the verification
                # below proves the whole layout against it in-trace
                base = rk_arr[0]
            expect = base + (ar // br) * bk + ar % br \
                if strided else base + ar
            layout_ok = ~jnp.any(rsel & (rk_arr != expect))
            if self.static:
                self.guards.append(~layout_ok)
            elif not bool(layout_ok):
                use_index = False
        if use_index:
            lk = jnp.asarray(lkeys[0].data).astype(jnp.int64)
            off = lk - base
            if strided:
                pos_raw = (off // bk) * br + off % bk
                in_slot = (off % bk) < br  # keys between blocks miss
            else:
                pos_raw = off
                in_slot = jnp.ones_like(off, bool)
            pos = jnp.clip(pos_raw, 0, nrows - 1).astype(jnp.int32)
            in_range = (off >= 0) & (pos_raw < nrows) & in_slot
            if full_build and not strided and rkeys[0].valid is None:
                # the build is the whole table and the layout guard above
                # has every live build row hold key base + its position,
                # so a probe key in range lands on its match or on a dead
                # row: the match test is the build's sel at `pos`, which
                # comes in the one packed gather that brings the row's
                # columns (a semi join's, which brings none, is one word).
                # No gather of the build key: at 28.8 M probe rows three
                # one-word gathers and a staged one cost 1.2 s a join,
                # the packed one 0.2 (PERF.md section 6, PR 34)
                index_rows = K.gather_batch(
                    right if jt in ("INNER", "LEFT")
                    else Batch({}, right.sel), pos)
                found_idx = lsel & in_range & index_rows.sel
                self._count("index_joins_packed")
            else:
                # a strided build's base comes from its data, and a
                # nullable key's NULL rows are live but unguarded:
                # compare the keys
                rkd = jnp.asarray(rkeys[0].data)[pos].astype(jnp.int64)
                found_idx = lsel & in_range & rsel[pos] & (rkd == lk)
                self._count("index_joins_keyed")
            counts = found_idx.astype(jnp.int32)
            index_ridx = pos
        elif self.static:
            # compile-time layout from stats/dictionaries (shared ranges
            # across both sides); unknown ranges -> sync-free 64-bit hash
            key_stats = getattr(node, "key_stats", {})
            merged_stats = []
            for (lk, rk), lc, rc in zip(node.criteria, lkeys, rkeys):
                ls_, rs_ = key_stats.get(lk), key_stats.get(rk)
                merged_stats.append(_merge_range(ls_, rs_))
            layout = K.static_layout(rkeys, merged_stats)
            rkey = K.pack_with_layout(rkeys, rsel, layout)
            lkey = K.pack_with_layout(lkeys, lsel, layout)
            if layout is not None:
                self.guards.append(K.layout_range_guard(rkeys, rsel, layout))
                self.guards.append(K.layout_range_guard(lkeys, lsel, layout))
        else:
            rkey, layout = K.pack_keys(rkeys, rsel, extra_cols=lkeys)
            lkey = K.pack_with_layout(lkeys, lsel, layout)
        if index_ridx is None:
            build_order = None
            if layout is not None and self._ordering_enabled() \
                    and self._build_presorted(node, right, rkeys):
                # presorted build: the packed build key is fully
                # nondecreasing (sorted input, masked rows — sentinels —
                # confined to a suffix, e.g. a static aggregate's exists
                # tail), so the build argsort is the identity.  Certain
                # (runtime-channel) claims skip the dynamic host check;
                # static mode guards every claim — a reasoning bug
                # becomes a dynamic fallback, never wrong matches.
                certain = self._build_order_certain(node, right, rkeys)
                if self.static:
                    self.guards.append(K.monotone_guard(rkey))
                    build_order = jnp.arange(rkey.shape[0],
                                             dtype=jnp.int32)
                    self._count("sorts_elided")
                elif certain or not bool(K.monotone_guard(rkey)):
                    build_order = jnp.arange(rkey.shape[0],
                                             dtype=jnp.int32)
                    self._count("sorts_elided")
                else:
                    self._count("ordering_guard_trips")
            if build_order is None:
                # fingerprint over the COMPONENTS of rsel (base sel +
                # key validities, already in fp) so the two probes of a
                # shared build subtree hash alike
                fp, refs = self._key_fp(rkeys, right.sel, layout)
                build_order = self._memo_pair(rkey, fp, refs)[1]
            order, lb, ub = K.build_probe(rkey, lkey,
                                          build_order=build_order)
            self._count("sorts_taken", 2)  # composite sort + co-sort home
            counts = ub - lb

        if jt == "MARK":  # filter-free by construction (planner)
            # Presto semiJoinOutput NULL semantics: TRUE on match;
            # without a match the mark is NULL (not FALSE) when the
            # probe key is NULL or the build side contains any NULL —
            # `x NOT IN (sub)` must then filter the row, not keep it
            # (reference: SemiJoinNode / MarkDistinct null handling)
            merged = dict(left.columns)
            found = counts > 0
            lvalid = None
            for c in lkeys:
                if c.valid is not None:
                    v_ = jnp.asarray(c.valid)
                    lvalid = v_ if lvalid is None else (lvalid & v_)
            rnull = None
            for c in rkeys:
                if c.valid is not None:
                    has = jnp.any(right.sel & ~jnp.asarray(c.valid))
                    rnull = has if rnull is None else (rnull | has)
            if lvalid is None and rnull is None:
                mvalid = None  # keys can't be NULL: mark is 2-valued
            else:
                ok = jnp.ones_like(found) if lvalid is None else lvalid
                if rnull is not None:
                    ok = ok & ~rnull
                # An empty subquery makes the mark definitively FALSE no
                # matter what the probe key is: `NULL NOT IN (empty)` is
                # TRUE, so the mark must be valid-FALSE, not NULL.  Use
                # right.sel (all build rows), not rsel — a build of only
                # NULL keys is NOT empty and must keep the NULL mark.
                build_nonempty = jnp.any(right.sel)
                mvalid = found | ok | ~build_nonempty
            merged[node.mark] = Column(found, mvalid, T.BOOLEAN, None)
            return Batch(merged, left.sel)

        if jt in ("SEMI", "ANTI") and node.filter is None:
            found = counts > 0
            sel = left.sel & (found if jt == "SEMI" else ~found)
            return left.with_sel(sel)

        if index_ridx is not None:
            max_matches = 1  # dense unique build: at most one match,
            # no guard and (in dynamic mode) no max-count host sync
        elif self.static:
            if getattr(node, "build_unique", False):
                max_matches = 1
                if counts.shape[0]:
                    self.guards.append(jnp.max(counts) > 1)
            else:
                bound = getattr(node, "fanout_bound", None)
                if bound is None:
                    raise StaticFallback(
                        f"join fanout unbounded ({node.join_type} on {node.criteria})")
                if counts.shape[0]:
                    self.guards.append(jnp.max(counts) > bound)
                return self._expanding_join_static(left, right, node, order, lb,
                                                   counts, bound)
        else:
            max_matches = int(jnp.max(counts)) if counts.shape[0] else 0

        if max_matches <= 1 and jt in ("INNER", "LEFT", "SEMI", "ANTI"):
            found = counts > 0
            if index_ridx is not None:
                ridx = index_ridx
            else:
                match_pos = jnp.clip(lb, 0, max(order.shape[0] - 1, 0))
                ridx = order[match_pos]
            if index_rows is not None:
                # gathered with the probe: only the validity is left
                rbatch = Batch(
                    {n: Column(c.data, found if c.valid is None
                               else (c.valid & found), c.type, c.dictionary)
                     for n, c in index_rows.columns.items()},
                    index_rows.sel & found)
            else:
                rbatch = K.gather_batch(right, ridx, idx_valid=found)
            merged = dict(left.columns)
            merged.update(rbatch.columns)
            if node.filter is not None:
                fb = Batch(merged, left.sel)
                fmask = eval_predicate(node.filter, fb, self.ctx)
                found = found & fmask
                # data is independent of the match mask — only the
                # validity tightens, so refresh masks without re-gathering
                for name, c in rbatch.columns.items():
                    v = found if c.valid is None else (c.valid & found)
                    merged[name] = Column(c.data, v, c.type, c.dictionary)
            if jt == "SEMI":
                return left.with_sel(left.sel & found)
            if jt == "ANTI":
                return left.with_sel(left.sel & ~found)
            if jt == "INNER":
                return Batch(merged, left.sel & found)
            return Batch(merged, left.sel)  # LEFT

        # one-to-many: expand
        return self._expanding_join(left, right, node, order, lb, counts)

    def _expanding_join_static(self, left: Batch, right: Batch, node: P.Join,
                               order, lb, counts, bound: int) -> Batch:
        """One-to-many join with a STATIC per-probe-row slot layout: probe
        row i owns output slots [i*F, (i+1)*F), F = connector fanout bound
        (e.g. <=7 lineitems per order).  Unmatched slots are masked, not
        skipped — shape stays compile-time constant."""
        jt = node.join_type
        n = left.capacity
        total = n * bound
        if total > 100_000_000:
            raise StaticFallback(
                f"static expansion too large: {n} x fanout {bound}")
        with NM.kernel_scope("k:join_expand"):
            counts = jnp.where(left.sel, counts, 0)
            lidx = jnp.repeat(jnp.arange(n, dtype=jnp.int32), bound,
                              total_repeat_length=total)
            k = jnp.tile(jnp.arange(bound, dtype=jnp.int32), n)
            cnt_l, lb_l = K.take_rows(
                [jnp.minimum(counts, bound).astype(jnp.int32),
                 lb.astype(jnp.int32)], lidx, presorted=True)
            slot_live = k < cnt_l
            rpos = jnp.clip(lb_l + k, 0, max(order.shape[0] - 1, 0))
            ridx = order[rpos]
        if self._order_ok(node) and GA.sort_order_worthwhile(
                total, K.batch_word_width(right) - K.batch_word_width(left)):
            # sort-order materialization: every consumer up the tree is
            # order-insensitive, so the join output simply STAYS in
            # build-index order — the wide right side gathers
            # sequentially and nobody pays the way back.  The slot
            # arithmetic (k, slot_live) and the probe indices ride the
            # one planning sort.
            ridx, (lidx, k, slot_live) = K.sort_order_plan(
                ridx, lidx, k, slot_live)
            lbatch = K.gather_batch(left, lidx)
            rbatch = K.gather_batch(right, ridx, idx_valid=slot_live,
                                    presorted=True)
        else:
            # lidx is repeat(arange): nondecreasing by construction
            lbatch = K.gather_batch(left, lidx, presorted=True)
            rbatch = K.gather_batch(right, ridx, idx_valid=slot_live)
        merged = dict(lbatch.columns)
        merged.update(rbatch.columns)
        out = Batch(merged, lbatch.sel & slot_live)
        match_ok = out.sel
        if node.filter is not None:
            match_ok = match_ok & eval_predicate(node.filter, out, self.ctx)
        if jt == "INNER":
            return out.with_sel(match_ok)
        if jt in ("SEMI", "ANTI"):
            hit = K.segment_any(match_ok, lidx, n)
            want = hit if jt == "SEMI" else ~hit
            return left.with_sel(left.sel & want)
        if jt == "LEFT":
            any_ok = K.segment_any(match_ok, lidx, n)
            first_slot = k == 0
            keep = jnp.where(any_ok[lidx], match_ok, first_slot & left.sel[lidx])
            rvalid = match_ok
            for name in rbatch.columns:
                c = merged[name]
                v = rvalid if c.valid is None else (c.valid & rvalid)
                merged[name] = Column(c.data, v, c.type, c.dictionary)
            return Batch(merged, keep)
        raise StaticFallback(f"static join type {jt} not supported")

    def _expanding_join(self, left: Batch, right: Batch, node: P.Join,
                        order, lb, counts) -> Batch:
        jt = node.join_type
        counts = jnp.where(left.sel, counts, 0)
        eff_counts = counts
        if jt in ("LEFT", "FULL"):
            eff_counts = jnp.where(left.sel & (counts == 0), 1, counts)
        offsets = jnp.cumsum(eff_counts) - eff_counts
        total = int(jnp.sum(eff_counts))
        if total == 0:
            # empty result with merged schema
            merged = dict(left.columns)
            for name, c in right.columns.items():
                merged[name] = c
            empty = {n: Column(c.data[:0], None if c.valid is None else c.valid[:0],
                               c.type, c.dictionary) for n, c in merged.items()}
            return Batch(empty, jnp.zeros((0,), bool))
        with NM.kernel_scope("k:join_expand"):
            lidx = jnp.repeat(jnp.arange(left.capacity), eff_counts,
                              total_repeat_length=total)
            k = jnp.arange(total) - offsets[lidx]
            has_match = counts[lidx] > 0
            rpos = jnp.clip(lb[lidx] + k, 0, max(order.shape[0] - 1, 0))
            ridx = order[rpos]
        if self._order_ok(node) and GA.sort_order_worthwhile(
                total, K.batch_word_width(right) - K.batch_word_width(left)):
            # sort-order materialization (see _expanding_join_static)
            ridx, (lidx, k, has_match) = K.sort_order_plan(
                ridx, lidx, k, has_match)
            rbatch = K.gather_batch(right, ridx, idx_valid=has_match,
                                    presorted=True)
            lbatch = K.gather_batch(left, lidx)
        else:
            rbatch = K.gather_batch(right, ridx, idx_valid=has_match)
            # lidx is repeat(arange): nondecreasing by construction
            lbatch = K.gather_batch(left, lidx, presorted=True)
        merged = dict(lbatch.columns)
        merged.update(rbatch.columns)
        sel = lbatch.sel
        out = Batch(merged, sel)
        match_ok = has_match
        if node.filter is not None:
            fmask = eval_predicate(node.filter, out, self.ctx)
            match_ok = match_ok & fmask
        if jt == "INNER":
            return out.with_sel(sel & match_ok)
        if jt in ("SEMI", "ANTI"):
            # any passing match per left row?
            hit = K.segment_any(sel & match_ok, lidx, left.capacity)
            want = hit if jt == "SEMI" else ~hit
            return left.with_sel(left.sel & want)
        if jt == "LEFT":
            # keep one row for unmatched-left; for matched rows apply filter;
            # rows whose every match fails the filter must still appear once
            if node.filter is not None:
                any_ok = K.segment_any(sel & match_ok, lidx,
                                       left.capacity)
                first_of_row = k == 0
                keep = jnp.where(any_ok[lidx], match_ok, first_of_row)
                # null out right side where match failed
                rvalid = match_ok
                for name in rbatch.columns:
                    c = merged[name]
                    v = rvalid if c.valid is None else (c.valid & rvalid)
                    merged[name] = Column(c.data, v, c.type, c.dictionary)
                # dedupe unmatched duplicates: keep only first expansion row
                return Batch(merged, sel & keep)
            return out
        raise ExecutionError(f"join type {jt} not implemented")

    def _full_join(self, left: Batch, right: Batch, node: P.Join) -> Batch:
        """FULL = LEFT(l,r) ++ (rows of r with no match, left side typed
        NULL).  The anti pass mirrors probe/build (reference:
        LookupOuterOperator emitting unmatched build rows after probes
        finish).  Static-shape friendly: output capacity is the LEFT
        expansion plus right's capacity, no host syncs added."""
        lnode = P.Join(node.left, node.right, "LEFT", node.criteria,
                       node.filter)
        for attr in ("build_unique", "fanout_bound", "key_stats"):
            if hasattr(node, attr):
                setattr(lnode, attr, getattr(node, attr))
        left_part = self._join_batches(left, right, lnode)
        anode = P.Join(node.right, node.left, "ANTI",
                       [(rk, lk) for lk, rk in node.criteria], node.filter)
        right_anti = self._join_batches(right, left, anode)
        null_left = {}
        for name, c in left.columns.items():
            cap = right_anti.capacity
            null_left[name] = Column(
                jnp.zeros((cap,), c.data.dtype),
                jnp.zeros((cap,), bool), c.type, c.dictionary)
        # column order must match left_part's (left cols, then right cols)
        ro_cols = dict(null_left)
        ro_cols.update(right_anti.columns)
        right_only = Batch(ro_cols, right_anti.sel)
        return K.concat_batches([left_part, right_only])

    def _cross_join(self, left: Batch, right: Batch, node: P.Join) -> Batch:
        if not self.static:  # compaction needs a host sync
            left = K.compact(left)
            right = K.compact(right)
        nl, nr = left.capacity, right.capacity
        if nl * nr > 50_000_000:
            if self.static:
                # uncompacted capacities can be huge where the compacted
                # cross join is tiny — let the dynamic path try
                raise StaticFallback(f"static cross join too large: {nl} x {nr}")
            raise ExecutionError(f"cross join too large: {nl} x {nr}")
        lidx = jnp.repeat(jnp.arange(nl), nr, total_repeat_length=max(nl * nr, 1))
        ridx = jnp.tile(jnp.arange(nr), nl)[:max(nl * nr, 1)]
        if nl * nr == 0:
            lidx, ridx = lidx[:0], ridx[:0]
        lbatch = K.gather_batch(left, lidx)
        rbatch = K.gather_batch(right, ridx)
        merged = dict(lbatch.columns)
        merged.update(rbatch.columns)
        sel = lbatch.sel & rbatch.sel
        out = Batch(merged, sel)
        if node.filter is not None:
            out = out.with_sel(sel & eval_predicate(node.filter, out, self.ctx))
        return out

    # ---- sort / limit -------------------------------------------------
    def _sort_perm(self, b: Batch, key_spec) -> jnp.ndarray:
        """sort_perm through the permutation memo: an identical sort of
        the same batch (same key columns, same sel, same directions)
        replays the cached permutation."""
        keys = [(b.columns[s], asc, nf) for s, asc, nf in key_spec]
        fp, refs = self._key_fp([c for c, _, _ in keys], b.sel,
                                [("sort", s, bool(asc), nf)
                                 for s, asc, nf in key_spec])
        if not self._ordering_enabled():
            fp = None
        entry = self._perm_memo.get(fp) if fp is not None else None
        if entry is not None:
            self._count("sort_memo_hits")
            self._count("sorts_elided")
            return entry[1]
        self._count("sorts_taken")
        perm = K.sort_perm(b, keys)
        if fp is not None:
            self._perm_memo[fp] = (refs, perm)
        return perm

    def _exec_sort(self, node: P.Sort) -> Batch:
        b = self.exec_node(node.source)
        if self._ordering_enabled() and self._order_satisfies(b, node.keys):
            # input provably sorted (runtime-certain channel: grouped /
            # sorted output upstream): the Sort node is a no-op — live
            # rows already surface in order, masked rows stay hidden
            self._count("sorts_elided")
            return b
        perm = self._sort_perm(b, node.keys)
        out = K.gather_batch(b, perm)
        self._note_order(out, tuple((s, asc) for s, asc, _nf in node.keys))
        return out

    def _exec_topn(self, node: P.TopN) -> Batch:
        """TopN = key-only sort + k-row gather (reference: TopNOperator's
        bounded heap).  The previous full-sort-then-mask shape paid a
        full-capacity gather of EVERY output column to keep k rows —
        ~half of Q3's single-chip wall time at 6M capacity."""
        b = self.exec_node(node.source)
        if self._ordering_enabled() and self._order_satisfies(b, node.keys):
            # already ordered: TopN degenerates to LIMIT (rank mask)
            self._count("sorts_elided")
            out = self._limit(b, node.count)
            self._copy_order(b, out)
            return out
        k = min(int(node.count), b.capacity)
        perm = self._sort_perm(b, node.keys)  # masked rows sort last
        sorted_keys = tuple((s, asc) for s, asc, _nf in node.keys)
        if k == b.capacity:  # LIMIT >= capacity: plain sort
            out = K.gather_batch(b, perm)
            self._note_order(out, sorted_keys)
            return out
        idx = perm[:k]
        out = K.gather_batch(b, idx)
        live_total = jnp.sum(jnp.asarray(b.sel).astype(jnp.int32)) \
            if b.capacity else jnp.int32(0)
        sel = jnp.arange(k, dtype=jnp.int32) < live_total
        out = Batch(out.columns, out.sel & sel)
        self._note_order(out, sorted_keys)
        return out

    def _exec_limit(self, node: P.Limit) -> Batch:
        b = self.exec_node(node.source)
        out = self._limit(b, node.count)
        self._copy_order(b, out)  # rank mask: rows never move
        return out

    def _limit(self, b: Batch, n: int) -> Batch:
        # int32 rank: capacity < 2^31, and i64 cumsum runs emulated on TPU;
        # clamp the count host-side so a giant LIMIT cannot wrap int32
        n = min(int(n), b.capacity)
        rank = jnp.cumsum(b.sel.astype(jnp.int32))
        return b.with_sel(b.sel & (rank <= n))

    # ---- set ops ------------------------------------------------------
    def _exec_unnest(self, node: P.Unnest) -> Batch:
        """Lateral explode (reference: UnnestOperator)."""
        if self.static:
            return self._unnest_static(node)
        b = self.exec_node(node.source)
        v = eval_expr(node.array_expr, b, self.ctx)
        col = to_column(v, b.capacity)
        codes = np.asarray(col.data)
        sel = np.asarray(b.sel)
        live = sel if col.valid is None else (sel & np.asarray(col.valid))
        dvals = col.dictionary.values if col.dictionary is not None else []
        lens = np.asarray([len(t) for t in dvals], dtype=np.int64)
        counts = np.where(live, lens[np.clip(codes, 0, max(len(dvals) - 1, 0))]
                          if len(dvals) else 0, 0)
        total = int(counts.sum())
        idx = np.repeat(np.arange(b.capacity), counts)
        offs = np.concatenate([[0], np.cumsum(counts)])
        k = np.arange(total) - offs[idx]
        elems = []
        for row in np.flatnonzero(counts):
            elems.extend(dvals[codes[row]])
        from presto_tpu.batch import column_from_numpy

        if total == 0:
            elem_col = column_from_numpy(
                np.empty(0, dtype=object if node.elem_type.is_string
                         else node.elem_type.numpy_dtype()), node.elem_type)
            out = K.gather_batch(b, jnp.zeros((0,), jnp.int64))
        else:
            arr = np.asarray(elems, dtype=object) \
                if node.elem_type.is_string else \
                np.asarray(elems, dtype=node.elem_type.numpy_dtype())
            elem_col = column_from_numpy(arr, node.elem_type)
            out = K.gather_batch(b, jnp.asarray(idx))
        cols = dict(out.columns)
        cols[node.out_sym] = elem_col
        if node.ordinality_sym:
            cols[node.ordinality_sym] = Column(
                jnp.asarray(k + 1, jnp.int64), None, T.BIGINT)
        return Batch(cols, jnp.ones((max(total, 0),), bool) if total else
                     jnp.zeros((0,), bool))

    def _unnest_static(self, node: P.Unnest) -> Batch:
        """Static-shape UNNEST: ARRAY columns are int32 codes into a
        host tuple dictionary, which is a TRACE-TIME constant — so the
        ragged expansion precomputes, per dictionary entry, a padded
        (dict_size, maxlen) element matrix + lengths host-side, and the
        traced program is two gathers with a slot-liveness mask.  The
        fanout bound is maxlen (static, from the dictionary), the
        LazyBlock-style analog of UnnestOperator's per-page expansion."""
        b = self.exec_node(node.source)
        v = eval_expr(node.array_expr, b, self.ctx)
        col = to_column(v, b.capacity)
        if col.dictionary is None:
            raise StaticFallback("UNNEST over a non-dictionary array")
        dvals = col.dictionary.values
        lens_h = np.asarray([len(t) for t in dvals], dtype=np.int32)
        maxlen = int(lens_h.max()) if len(lens_h) else 0
        n = b.capacity
        total = n * max(maxlen, 1)
        if total > 50_000_000:
            raise StaticFallback(
                f"static UNNEST expansion too large: {n} x {maxlen}")
        elem_t = node.elem_type
        dsize = max(len(dvals), 1)
        mat_valid = np.zeros((dsize, max(maxlen, 1)), dtype=bool)
        if elem_t.is_string or elem_t.name in ("ARRAY", "MAP", "ROW",
                                               "JSON"):
            uniq = {e for t in dvals for e in t if e is not None}
            # string element dictionaries keep the lex==code-order
            # invariant; nested tuples use repr order (not compared)
            flat = sorted(uniq) if elem_t.is_string else sorted(uniq,
                                                                key=repr)
            edict_vals = np.empty(len(flat), dtype=object)
            edict_vals[:] = flat
            index = {e: i for i, e in enumerate(flat)}
            mat = np.zeros((dsize, max(maxlen, 1)), dtype=np.int32)
            for di, t in enumerate(dvals):
                for k_, e in enumerate(t):
                    if e is not None:
                        mat[di, k_] = index[e]
                        mat_valid[di, k_] = True
            from presto_tpu.batch import Dictionary as _Dict

            edict = _Dict(edict_vals)
        else:
            mat = np.zeros((dsize, max(maxlen, 1)), dtype=elem_t.numpy_dtype())
            for di, t in enumerate(dvals):
                for k_, e in enumerate(t):
                    if e is not None:
                        mat[di, k_] = e
                        mat_valid[di, k_] = True
            edict = None
        codes = jnp.clip(jnp.asarray(col.data), 0, dsize - 1)
        live = b.sel if col.valid is None else (b.sel & col.valid)
        if maxlen == 0:
            out = K.gather_batch(b, jnp.zeros((0,), jnp.int32))
            cols = dict(out.columns)
            cols[node.out_sym] = Column(
                jnp.zeros((0,), mat.dtype), None, elem_t, edict)
            if node.ordinality_sym:
                cols[node.ordinality_sym] = Column(
                    jnp.zeros((0,), jnp.int64), None, T.BIGINT)
            return Batch(cols, jnp.zeros((0,), bool))
        lidx = jnp.repeat(jnp.arange(n, dtype=jnp.int32), maxlen,
                          total_repeat_length=n * maxlen)
        k = jnp.tile(jnp.arange(maxlen, dtype=jnp.int32), n)
        code_l = codes[lidx]
        slot_live = live[lidx] & (k < jnp.asarray(lens_h)[code_l])
        elem_data = jnp.asarray(mat)[code_l, k]
        elem_valid = slot_live & jnp.asarray(mat_valid)[code_l, k]
        out = K.gather_batch(b, lidx, idx_valid=slot_live)
        cols = dict(out.columns)
        cols[node.out_sym] = Column(elem_data, elem_valid, elem_t, edict)
        if node.ordinality_sym:
            cols[node.ordinality_sym] = Column(
                (k + 1).astype(jnp.int64), None, T.BIGINT)
        return Batch(cols, out.sel)

    def _exec_union(self, node: P.Union) -> Batch:
        parts = []
        for src, mapping in zip(node.sources_, node.mappings):
            b = self.exec_node(src)
            cols = {}
            for out_sym in node.symbols:
                c = b.columns[mapping[out_sym]]
                cols[out_sym] = c
            parts.append(Batch(cols, b.sel))
        return K.concat_batches(parts)

    def _exec_output(self, node: P.Output) -> Batch:
        b = self.exec_node(node.source)
        return b.select([s for s in node.symbols])

    # ---- write pipeline (exec/writer.py; reference:
    # TableWriterOperator + TableFinishOperator) -----------------------
    def _exec_tablewriter(self, node) -> Batch:
        ctx = getattr(self, "write_ctx", None)
        if ctx is None:
            raise ExecutionError(
                "TableWriter requires a write context — write statements "
                "execute through exec/writer.run_write")
        from presto_tpu.exec import writer as W

        inner = node.source  # the query's Output node
        b = self.exec_node(inner)
        arrays, types = W._host_arrays(inner, b)
        try:
            n = ctx.write_page(arrays, types)
        except W.WriteError as e:
            raise ExecutionError(str(e)) from e
        return batch_from_numpy({node.rows_symbol:
                                 np.asarray([n], dtype=np.int64)},
                                {node.rows_symbol: T.BIGINT})

    def _exec_tablefinish(self, node) -> Batch:
        b = self.exec_node(node.source)
        ctx = getattr(self, "write_ctx", None)
        if ctx is not None:
            from presto_tpu.exec import writer as W

            try:
                ctx.finish()  # commit: staged files publish atomically
            except W.WriteError as e:
                raise ExecutionError(str(e)) from e
        return b


def _hll_m(a: ir.AggCall) -> int:
    """Register count for an approx_distinct call: the optional second
    argument is a max-standard-error LITERAL (reference:
    ApproximateCountDistinctAggregation's maxStandardError)."""
    if len(a.args) >= 2 and isinstance(a.args[1], ir.Lit) \
            and a.args[1].value is not None:
        return K.hll_m_for_error(float(a.args[1].value))
    return 1024


def _tuples_to_dict_column(tuples: np.ndarray, valid, typ) -> Column:
    """Canonicalize host object tuples into a sorted-unique dictionary
    column (shared by array_agg/map_agg/multimap_agg; the operator-side
    twin of functions.scalar._tuple_dict_normalize)."""
    from presto_tpu.batch import Dictionary as _Dict

    uniq = sorted(set(tuples.tolist()), key=repr)
    cmap = {t: i for i, t in enumerate(uniq)}
    codes = np.fromiter((cmap[t] for t in tuples.tolist()),
                        np.int32, len(tuples))
    u = np.empty(len(uniq), dtype=object)
    u[:] = uniq
    return Column(jnp.asarray(codes), valid, typ, _Dict(u))


def scan_batch(table, node: P.TableScan, f32: bool = False,
               runtime_domains=None) -> Batch:
    """Read + ingest a table's columns, with a per-table device-column
    cache (upload + dictionary-encode once per process; reference analog:
    a connector page source feeding a cache — here the 'page' is the whole
    column and lives in HBM).  f32=True stores DOUBLE columns as float32
    (see the float32_compute session property).  `runtime_domains`
    (dynamic filtering) intersect with the statically pushed-down
    scan_domains for zone-map stripe pruning — query-specific, so the
    read bypasses the device cache exactly like a static domain scan."""
    base = getattr(table, "_device_cols", None)
    if base is None:
        base = table._device_cols = {}
    f32cache = None
    if f32:
        # only DOUBLE columns differ in f32 mode; everything else shares
        # the base cache (no duplicate uploads / HBM residency)
        f32cache = getattr(table, "_device_cols_f32", None)
        if f32cache is None:
            f32cache = table._device_cols_f32 = {}

    def cache_for(colname):
        # virtual pushdown columns are not in the schema (BOOLEAN)
        t = table.schema.get(colname)
        if f32 and t is not None and t.name == "DOUBLE":
            return f32cache
        return base

    needed = list(dict.fromkeys(node.assignments.values()))
    domains = getattr(node, "scan_domains", None)
    if runtime_domains and getattr(table, "supports_domain_pushdown",
                                   False):
        from presto_tpu.plan.domains import merge_domain_maps

        domains = merge_domain_maps(domains or {}, runtime_domains)
    if domains and getattr(table, "supports_domain_pushdown", False):
        # selective scan: the reader prunes stripes/row groups on the
        # pushed-down domains, so the result is QUERY-specific — it
        # bypasses the per-table device cache entirely (all needed
        # columns in ONE read call keeps row alignment)
        from presto_tpu.batch import column_from_numpy

        data = table.read(needed, domains=domains)
        cols = {}
        n = 0
        for sym, src in node.assignments.items():
            t = node.types[sym]
            col = column_from_numpy(data[src], t)
            if f32 and t.name == "DOUBLE":
                col = Column(col.data.astype(jnp.float32), col.valid,
                             col.type, col.dictionary)
            cols[sym] = Column(col.data, col.valid, t, col.dictionary)
            n = col.data.shape[0]
        return Batch(cols, jnp.ones((n,), bool))
    missing = [c for c in needed if c not in cache_for(c)]
    if missing:
        dev = None
        if hasattr(table, "device_columns"):
            # generator connectors produce columns ON DEVICE (one jitted
            # program, no host materialization or H2D upload)
            dev = table.device_columns(missing, f32=f32)
        if dev is None:
            dev = CC.data_load(lambda: _placed(table, missing, f32))
        for c in missing:
            cache_for(c)[c] = dev[c]
    cols = {}
    n = None
    for sym, col in node.assignments.items():
        c = cache_for(col)[col]
        cols[sym] = Column(c.data, c.valid, node.types[sym], c.dictionary)
        n = c.data.shape[0]
    # ONE shared all-live sel per (table, capacity): scans of the same
    # table hand out identical (data, sel) array objects, which is what
    # lets the executor's sort-permutation memo fingerprint two scans of
    # the same key column as the same sort
    sel_key = ("__sel__", n or 0)
    sel = base.get(sel_key)
    if sel is None:
        sel = base[sel_key] = jnp.ones((n or 0,), bool)
    return Batch(cols, sel)


def _placed(table, columns, f32: bool) -> Dict[str, Column]:
    """A host-read column set put on the device (a table born on the
    host: TPC-DS's dimensions, memory tables, files)."""
    from presto_tpu.batch import column_from_numpy

    data = table.read(columns)
    out = {}
    for c in columns:
        t = table.schema.get(c, T.BOOLEAN)  # virtual: BOOLEAN
        col = column_from_numpy(data[c], t)
        if f32 and t.name == "DOUBLE":
            col = Column(col.data.astype(jnp.float32), col.valid,
                         col.type, col.dictionary)
        out[c] = col
    return out


def _merge_range(a, b):
    """Union of two ColStats ranges (None-safe) for shared join-key packing."""
    from presto_tpu.plan.stats import ColStats

    if a is None or b is None or a.min is None or b.min is None \
            or a.max is None or b.max is None:
        return None
    return ColStats(min=min(a.min, b.min), max=max(a.max, b.max))


def _unify_key_dictionaries(lkeys: List[Column], rkeys: List[Column]):
    """Join keys that are string columns with different dictionaries are
    re-encoded into a merged dictionary so code equality == string equality."""
    from presto_tpu.batch import Dictionary
    from presto_tpu.exec.colval import translate_codes

    lout, rout = [], []
    for lc, rc in zip(lkeys, rkeys):
        if not lc.type.is_string or lc.dictionary is rc.dictionary:
            lout.append(lc)
            rout.append(rc)
            continue
        merged = Dictionary(np.unique(np.concatenate(
            [lc.dictionary.values, rc.dictionary.values])))
        llut = jnp.asarray(translate_codes(lc.dictionary, merged))
        rlut = jnp.asarray(translate_codes(rc.dictionary, merged))
        lout.append(Column(llut[jnp.clip(lc.data, 0, len(lc.dictionary) - 1)],
                           lc.valid, lc.type, merged))
        rout.append(Column(rlut[jnp.clip(rc.data, 0, len(rc.dictionary) - 1)],
                           rc.valid, rc.type, merged))
    return lout, rout


def _single_value(b: Batch):
    arrays, sel = to_numpy(b)
    sym = next(iter(arrays))
    vals = arrays[sym][sel]
    if len(vals) == 0:
        return 0, False
    if len(vals) > 1:
        raise ExecutionError("scalar subquery returned more than one row")
    v = vals[0]
    if np.ma.is_masked(v):
        return 0, False
    if isinstance(v, np.generic):
        v = v.item()
    return v, True
