"""Distributed query execution: one shard_map superstep per query.

Reference parity: the coordinator/worker split (SqlQueryScheduler starting
HttpRemoteTasks per fragment, SURVEY.md §3.1-3.3) collapsed into the XLA
execution model: the DISTRIBUTED plan (plan/distribute.py) traces into a
single jitted shard_map program over the device mesh — every fragment of
the reference's stage DAG becomes a region of one fused XLA program, and
every remote exchange becomes a collective on the ICI axis.  There is no
task state machine because there are no tasks: scheduling, backpressure,
and page acks are XLA's problem now.

The worker-side guard discipline matches compiled single-chip mode:
static-shape assumptions (group capacity, join fanout, repartition bucket
capacity) are verified by traced guards psum'd across shards; a tripped
guard re-runs the query on the single-device dynamic path.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as PS

from presto_tpu.batch import Batch, Column
from presto_tpu.exec import compile_cache as CC
from presto_tpu.exec.executor import Executor
from presto_tpu.observe import trace as TR
from presto_tpu.parallel import exchange as EX
from presto_tpu.parallel import mesh as MH
from presto_tpu.parallel.mesh import AXIS, make_mesh
from presto_tpu.plan import nodes as P
from presto_tpu.plan.distribute import Undistributable, distribute


def _put(arr, spec):
    """device_put that also works on a multi-process global mesh.  A
    plain device_put cannot target non-addressable devices, so on a
    multihost mesh the feed goes through make_array_from_callback:
    every gang member holds an IDENTICAL full host copy (same catalog
    chunk, same pulled exchange pages, same padding) and materializes
    only its addressable shards of the global array."""
    if not MH.is_multihost():
        return jax.device_put(arr, spec)
    harr = np.asarray(arr)
    return jax.make_array_from_callback(harr.shape, spec,
                                        lambda idx: harr[idx])


def local_shard_rows(arr) -> np.ndarray:
    """Process-local rows of a row-sharded global array: addressable
    shards concatenated in mesh-index order.  The gang output contract
    reads through this — each rank publishes exactly these rows, and
    the coordinator's gather passthrough reassembles the global result
    rank by rank."""
    shards = sorted(arr.addressable_shards,
                    key=lambda s: (s.index[0].start or 0))
    return np.concatenate([np.asarray(s.data) for s in shards])


class FusedGuardTripped(Exception):
    """A fused super-fragment's traced guard fired at runtime (exchange
    capacity overflow / static-shape violation): the task reports
    FAILED and the coordinator retries on the per-fragment HTTP path."""


class DistExecutor(Executor):
    """Per-shard executor: inherits the whole static (compiled-mode)
    operator repertoire and adds Exchange lowering."""

    # per-shard scan slices break the index join's whole-table layout;
    # a star join's replicated build restores it (_index_build_whole)
    allow_index_join = False

    def __init__(self, session, ndev: int, scan_inputs, sort_stats=None):
        super().__init__(session, static=True, scan_inputs=scan_inputs,
                         sort_stats=sort_stats)
        self.ndev = ndev

    def _rf_build_complete(self, node) -> bool:
        """Inside the shard_map, a join's build batch is this SHARD's
        view: only a build that is replicated on every shard (gathered /
        broadcast, or Values) is the complete key set.  Repartition
        buckets and raw sharded scans are partial — filtering a
        pre-exchange probe scan with them would drop rows that match on
        other shards, so those joins produce no runtime filter here."""
        def complete(n):
            if isinstance(n, P.Exchange):
                return n.kind in ("gather", "broadcast")
            if isinstance(n, P.TableScan):
                return False  # sharded_scan slices rows per shard
            if isinstance(n, P.Values):
                return True  # replicated by construction
            srcs = n.sources
            return bool(srcs) and all(complete(s) for s in srcs)

        return complete(node.right)

    def _build_presorted(self, node, right: Batch, rkeys) -> bool:
        """The planner made its claim (a build sorted on the key, masked
        rows in a suffix) on the single-node plan.  An exchange under the
        build lays the shards' buffers end to end, each with its dead
        rows after its live ones, so the claim ends there
        (plan/properties.derive: an Exchange keeps no order); a build
        that stays on its shard keeps it."""
        return not isinstance(node.right, P.Exchange) \
            and super()._build_presorted(node, right, rkeys)

    def _index_build_whole(self, node, il, right: Batch) -> bool:
        """A star join's build on the mesh (`star_lookup`, the planner's
        mark: plan/distribute._star_lookup): a dimension that a gather or
        broadcast made whole on every shard.  `sharded_scan` cuts a table
        into contiguous ranges and pads only behind a range's live rows,
        and only the last range with rows is short, so the ranges laid
        end to end are the table's rows in order, then dead rows: the
        identity layout, which the join's layout guard verifies in the
        trace."""
        return getattr(node, "star_lookup", False) \
            and self._rf_build_complete(node) \
            and right.capacity >= il["rows"]

    def _rf_mask_pays(self, node=None) -> bool:
        # a star join's probe is one gather: no mask over the fact
        # table's rows for it.  Every other join keeps the filter as on
        # the parent until sf1_mesh4_join can price it, not because it
        # pays: a shard_map program's shapes are fixed too (PERF.md
        # section 7: goes if that cell shows what sf1_join did)
        return not getattr(node, "star_lookup", False)

    def _exchange_bytes(self, b: Batch) -> int:
        """Trace-time byte estimate of one collective exchange: every
        shard contributes its per-shard payload, so the mesh moves
        ~per-shard-bytes x ndev over ICI (never the host)."""
        total = int(b.sel.size)  # bool mask, 1 byte/row
        for c in b.columns.values():
            total += int(c.data.size) * c.data.dtype.itemsize
            if c.valid is not None:
                total += int(c.valid.size)
        return total * self.ndev

    def _exec_exchange(self, node: P.Exchange) -> Batch:
        b = self.exec_node(node.source)
        if node.kind == "gather" and \
                getattr(node, "sketch_merge", "") == "pmax":
            # sketch-state merge: HLL union is elementwise max over
            # aligned register rows, so this gather collapses to ONE
            # psum-shaped collective (lax.pmax) — the edge moves only
            # the fixed-width state, never repartitioned rows.  Only
            # stamped for global all-$hll_partial edges (grouped states
            # order their group slots data-dependently per shard; KLL
            # merges by sort, not max) — see plan/distribute.py.
            self._count("exchange_bytes_sketch", self._exchange_bytes(b))
            cols = {s: Column(jax.lax.pmax(c.data, AXIS), c.valid,
                              c.type, c.dictionary)
                    for s, c in b.columns.items()}
            return Batch(cols, b.sel)
        if node.kind != "scatter":  # scatter is a sel mask: no transfer
            # sketch-only edges (grouped HLL / KLL state gathers) still
            # lower to all_gather but carry fixed-width state, never
            # repartitioned input rows — ledgered on the sketch lane
            self._count("exchange_bytes_sketch"
                        if getattr(node, "sketch_only", False)
                        else "exchange_bytes_collective",
                        self._exchange_bytes(b))
        if node.kind in ("gather", "broadcast"):
            return EX.all_gather_batch(b, AXIS)
        if node.kind == "scatter":
            return EX.scatter_batch(b, AXIS)
        if node.kind == "repartition":
            key_cols = [b.columns[k] for k in node.keys]
            out, overflow = EX.repartition_batch(b, key_cols, self.ndev, AXIS)
            self.guards.append(overflow)
            return out
        if node.kind == "range":
            out, overflow = EX.range_partition_batch(
                b, node.sort_keys, self.ndev, AXIS)
            self.guards.append(overflow)
            return out
        raise Undistributable(f"exchange kind {node.kind}")


def _traced_single_value(b: Batch, guards: list):
    """Traced analog of executor._single_value: first live row of the
    single output column; >1 rows is a guarded runtime error (reference:
    EnforceSingleRowOperator)."""
    col = next(iter(b.columns.values()))
    guards.append(jnp.sum(b.sel) > 1)
    idx = jnp.argmax(b.sel)  # first live row (0 if none; valid=False then)
    val = col.data[idx]
    valid = b.sel[idx]
    if col.valid is not None:
        valid = valid & col.valid[idx]
    if col.type.is_decimal:
        val = val.astype(jnp.float64) / (10 ** col.type.decimal_scale)
    return val, valid


def _shard_mapped(fn, mesh, in_specs, out_specs):
    """The engine's one shard_map spelling (check_vma off: fragments
    mix replicated and per-shard values freely)."""
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------


def run_distributed(session, text: str, stmt, mon=None):
    """Plan, distribute, and execute a query over the mesh; results are
    gathered/replicated, so materialization reads shard 0's copy.

    Scans are fed by `sharded_scan` (span `mesh.feed`): born on their
    chips where the table generates on the device, in f32 under
    `float32_compute`.  The launch, the wait for the guard and the
    result's fetch lie under `exec.dispatch`, `exec.wait_fetch` and
    `exec.materialize`, as in run_compiled; `mon` (the query's
    QueryMonitor) gets the program's trace-time counters — exchange
    bytes, declined runtime filters — with every run, cold or warm.
    Compiles reach it through compile_cache.recording, like any
    program's."""
    from presto_tpu.exec import executor as X

    ndev = int(session.properties.get("mesh_devices", 0)) or len(jax.devices())
    if ndev <= 1:
        raise Undistributable("mesh has a single device")
    cache = getattr(session, "_dist_cache", None)
    if cache is None:
        cache = session._dist_cache = {}
    key = (" ".join(text.split()), ndev,
           getattr(session.catalog, "version", 0),
           tuple(sorted((k, repr(v)) for k, v in session.properties.items())))
    entry = cache.get(key)
    if entry == "DYNAMIC":
        raise Undistributable("static assumptions previously violated")

    if entry is None:
        try:
            entry = _build(session, stmt, ndev)
        except Exception as e:
            # memoize undistributable/untraceable shapes so re-executions
            # skip the failed plan+distribute+trace (the runtime-guard path
            # below already memoizes via "DYNAMIC")
            if isinstance(e, (Undistributable, X.StaticFallback,
                              jax.errors.ConcretizationTypeError)):
                cache[key] = "DYNAMIC"
            raise
        cache[key] = entry  # built (traced, compiled) without a failure
    dplan, jitted, scan_nodes, mesh, counters = entry
    with TR.span("exec.dispatch"):
        out_batch, guard = jitted(_feed(session, scan_nodes, mesh, ndev))
    if mon is not None:
        X._merge_sort_stats(mon.stats, counters)
    with TR.span("exec.wait_fetch"):
        tripped = bool(guard)  # the program's end: every shard's guards
    if tripped:
        cache[key] = "DYNAMIC"
        raise Undistributable("static assumption violated at runtime")
    with TR.span("exec.materialize"):  # its own fetches are inside
        return X.Executor(session).materialize(dplan, out_batch)


def _feed(session, scan_nodes, mesh, ndev):
    f32 = bool(session.properties.get("float32_compute", False))
    with TR.span("mesh.feed"):
        return [sharded_scan(session.catalog.get(n.table), n, mesh, ndev,
                             f32) for n in scan_nodes]


def _build(session, stmt, ndev):
    """-> (distributed plan, the whole-mesh program, its scans, the mesh,
    the program's trace-time counters)."""
    from presto_tpu.exec import executor as X

    mesh = make_mesh(ndev)
    plan = X.plan_statement(session, stmt)
    dplan = distribute(plan, session, ndev)
    for sub in dplan.subplans.values():
        t = next(iter(dict(sub.outputs()).values()))
        if t.is_string:
            raise Undistributable("string-valued scalar subquery")
    scan_nodes: List[P.TableScan] = []
    X._collect_tablescans(dplan.root, scan_nodes)
    for sub in sorted(dplan.subplans):
        X._collect_tablescans(dplan.subplans[sub], scan_nodes)
    counters: dict = {}

    def fn(batches):
        stats: dict = {}
        ex = DistExecutor(session, ndev,
                          {id(n): b for n, b in zip(scan_nodes, batches)},
                          sort_stats=stats)
        # scalar subqueries evaluated inside the same trace so float
        # reduction order matches the main plan bit-for-bit
        for pid in sorted(dplan.subplans):
            sb = ex.exec_node(dplan.subplans[pid])
            ex.ctx.scalar_results[pid] = _traced_single_value(sb, ex.guards)
        out = ex.exec_node(dplan.root)
        if ex.guards:
            g = jnp.any(jnp.stack([jnp.asarray(x) for x in ex.guards]))
        else:
            g = jnp.zeros((), bool)
        # any shard's violation aborts the whole query
        g = jax.lax.psum(g.astype(jnp.int32), AXIS) > 0
        # trace-time counters: filled anew by every (re)trace, replayed
        # into the query's stats by every run
        counters.clear()
        counters.update(stats)
        counters["grouping_set_branches"] = plan.grouping_set_branches
        return out, g

    sharded = _shard_mapped(fn, mesh, (PS(AXIS),), PS())
    # counted AOT build (exec/compile_cache.py): the whole-mesh program's
    # compile lands in this query's compile-economics counters, and its
    # HLO text is what lets a profile name its device time by the
    # engine's scopes (scope_tables); every scan is fed on the mesh under
    # one sharding, so the pinned input shardings are the feed's own.
    # The tag tells this query's module from another's in a profile.
    plan_fp = CC.plan_fingerprint(
        (dplan.root, sorted(dplan.subplans.items())))
    jitted = CC.build_jit(sharded,
                          example=(_feed(session, scan_nodes, mesh, ndev),),
                          tag=X._program_tag(plan_fp, dplan))
    return dplan, jitted, scan_nodes, mesh, counters


def _shard_rows(table, ndev: int):
    """-> (row edges [ndev+1], rows of the fullest shard): shard i holds
    the table's rows [edges[i], edges[i+1]), a contiguous primary-key
    range.  A table that generates on the device says where it cuts
    (`shard_grid`: order-row ranges for lineitem); any other is cut
    evenly."""
    if hasattr(table, "shard_grid"):
        grid = table.shard_grid(ndev)
        return grid.row_edges(table.name), grid.capacity(table.name)
    n = table.row_count()
    per = max(-(-n // ndev), 1)
    return [min(i * per, n) for i in range(ndev + 1)], per


def shard_generator(table, cols, mesh, ndev: int, f32: bool):
    """-> (fn, host args): `fn(*args)` is ONE shard_map program in which
    every chip generates its own range of `cols` — ({col: Column}, sel),
    row-sharded, a shard's live rows first and dead rows under `sel`
    after them up to the static per-shard length.  The generator is the
    one-chip one in its static-shape form (the chunk grid's
    `build_scan`), the ranges arrive as traced per-shard scalars."""
    grid = table.shard_grid(ndev)
    # a shard is a chunk of the grid (TPC-H's or TPC-DS's): every chunk's
    # `chunk_args` at once, an array an argument, a shard's own element in each
    args = tuple(np.stack([np.asarray(a) for a in arg]) for arg in
                 zip(*(grid.chunk_args(i) for i in range(ndev))))

    def shard(*mine):
        return grid.build_scan(table.name, cols,
                               tuple(a[0] for a in mine), f32)

    return _shard_mapped(shard, mesh, (PS(AXIS),) * len(args), PS(AXIS)), args


def sharded_scan(table, node: P.TableScan, mesh, ndev: int,
                 f32: bool = False) -> Batch:
    """A scan's columns as row-sharded device arrays over the mesh (P3
    source distribution: the split-assignment role of
    SourcePartitionedScheduler, done by sharding annotation instead of
    split queues).  A shard is a contiguous primary-key range
    (`_shard_rows`), padded to one static length with dead (sel=False)
    rows.

    Columns the table can generate on the device (`device_generable`)
    are born on the chip that holds them (`shard_generator`): no host
    copy exists.  The rest are read on the host, laid out by the same
    ranges and put on the mesh.  f32=True (the `float32_compute` session
    property) stores DOUBLE columns as float32, as scan_batch does on
    one chip.  Every column is cached on the table (`_dist_cols_<ndev>`,
    DOUBLE under f32 in `_dist_cols_<ndev>_f32`), so a warm query
    touches no generator and no host array."""
    base = vars(table).setdefault(f"_dist_cols_{ndev}", {})
    f32cache = vars(table).setdefault(f"_dist_cols_{ndev}_f32", {}) \
        if f32 else None

    def cache_for(colname):
        # virtual pushdown predicate columns are schema-less BOOLEANs
        t = table.schema.get(colname)
        return f32cache if f32 and t is not None and t.name == "DOUBLE" \
            else base

    spec = NamedSharding(mesh, PS(AXIS))
    needed = list(dict.fromkeys(node.assignments.values()))
    missing = [c for c in needed if c not in cache_for(c)]
    # born sharded needs the table's own cut (`shard_grid`); a table that
    # generates whole columns only (a TPC-DS fact table of a catalog other
    # than tpcds_mesh_catalog's) is host-read and laid out
    born = [c for c in missing if hasattr(table, "shard_grid")
            and table.device_generable(c)]
    sel_key = "__sel__"
    if born:
        fn, args = shard_generator(table, born, mesh, ndev, f32)
        args = tuple(_put(a, spec) for a in args)
        # AOT, counted: the generator's compile is part of a query's cold
        # cost, as TpchTable.device_columns' is on one chip; its run is
        # table birth
        prog = CC.build_jit(fn, example=args)
        cols, sel = CC.data_load(lambda: prog(*args))
        for c in born:
            cache_for(c)[c] = cols[c]
        base.setdefault(sel_key, sel)
    read = [c for c in missing if c not in born]
    if read or sel_key not in base:
        edges, per = _shard_rows(table, ndev)

        def laid_out(arr):
            out = np.zeros((ndev * per,) + arr.shape[1:], dtype=arr.dtype)
            for i, (a, b) in enumerate(zip(edges[:-1], edges[1:])):
                out[i * per:i * per + b - a] = arr[a:b]
            return _put(out, spec)

        def placed():
            from presto_tpu import types as T
            from presto_tpu.batch import column_from_numpy

            out = {}
            data = table.read(read) if read else {}
            for c in read:
                t = table.schema.get(c, T.BOOLEAN)
                col = column_from_numpy(data[c], t)
                arr = np.asarray(col.data)
                if f32 and t.name == "DOUBLE":
                    arr = arr.astype(np.float32)
                valid = None if col.valid is None \
                    else laid_out(np.asarray(col.valid))
                out[c] = Column(laid_out(arr), valid, col.type,
                                col.dictionary)
            if sel_key not in base:
                out[sel_key] = laid_out(np.ones((edges[-1],), bool))
            return out

        for c, col in CC.data_load(placed).items():
            (base if c == sel_key else cache_for(c))[c] = col
    cols = {}
    for sym, colname in node.assignments.items():
        c = cache_for(colname)[colname]
        cols[sym] = Column(c.data, c.valid, node.types[sym], c.dictionary)
    return Batch(cols, base[sel_key])


# ---------------------------------------------------------------------------
# fused super-fragments (fragment fusion, plan/distribute.fuse_fragments)
# ---------------------------------------------------------------------------


def _ext_shard_batch(host_cols, node: P.TableScan, mesh, ndev: int) -> Batch:
    """External (non-fused) exchange input -> row-sharded device Batch:
    rows padded to a multiple of ndev with dead (sel=False) rows, like
    sharded_scan.  The fused plan re-establishes hashed/range
    distribution in-trace via the wrap exchange the fusion pass spliced
    in; 'any'-distributed inputs (scatter) are correct as-is."""
    from presto_tpu.batch import column_from_numpy

    spec = NamedSharding(mesh, PS(AXIS))
    n = 0
    for _sym, (data, _valid) in host_cols.items():
        n = len(data)
        break
    npad = max(int(np.ceil(n / ndev)) * ndev, ndev)
    cols = {}
    for sym in node.assignments.values():
        data, valid = host_cols[sym]
        col = column_from_numpy(np.asarray(data), node.types[sym],
                                valid if valid is not None else None)
        arr = np.asarray(col.data)
        arr = np.concatenate(
            [arr, np.zeros((npad - n,) + arr.shape[1:], dtype=arr.dtype)])
        v = col.valid
        if v is not None:
            v = _put(np.concatenate(
                [np.asarray(v), np.zeros((npad - n,), bool)]), spec)
        cols[sym] = Column(_put(arr, spec), v, col.type,
                           col.dictionary)
    sel = _put(np.arange(npad) < n, spec)
    return Batch(cols, sel)


def _ext_repl_batch(host_cols, node: P.TableScan, mesh) -> Batch:
    """External gather/broadcast input -> replicated device Batch
    (every shard sees every row, matching the edge's semantics)."""
    from presto_tpu.batch import column_from_numpy

    spec = NamedSharding(mesh, PS())
    n = 0
    cols = {}
    for sym in node.assignments.values():
        data, valid = host_cols[sym]
        col = column_from_numpy(np.asarray(data), node.types[sym],
                                valid if valid is not None else None)
        v = None if col.valid is None else \
            _put(np.asarray(col.valid), spec)
        cols[sym] = Column(_put(np.asarray(col.data), spec), v,
                           col.type, col.dictionary)
        n = len(data)
    return Batch(cols, _put(np.ones((n,), bool), spec))


def run_fused_fragment(session, root, ndev: int, ext_inputs,
                       scalar_results, fragment_bytes: bytes,
                       profile: bool = False):
    """Execute a fused super-fragment — a plan root with INLINE Exchange
    nodes (plan/distribute.fuse_fragments) — as ONE shard_map program
    over this process's local mesh: base-table scans shard over the
    mesh, every inline exchange lowers to a collective, and the stages
    between them never touch the host.

    `ext_inputs`: {eid: {"kind", "cols" {sym: (data, valid)}}} — the
    already-pulled host columns of NON-fused exchange edges.  `scalar
    _results`: {pid: (value, valid)} host scalars baked into the trace
    (they ride the executable-memo key).

    Returns (out_batch, guard_host, counters): the device result (one
    replicated copy, or per-shard concatenation when the fused root is
    sharded), the host guard bool (True => the caller must degrade to
    the per-fragment path), and the trace-time exchange counters
    {exchange_bytes_collective, ...}.  The compiled program is memoized
    process-wide (exec/compile_cache.fused_key) — one executable per
    (fused pipeline, mesh), reused across queries and sessions."""
    from presto_tpu.exec import executor as X
    from presto_tpu.plan import distribute as D

    mesh = make_mesh(ndev)
    scan_nodes: List[P.TableScan] = []
    X._collect_tablescans(root, scan_nodes)
    real = [n for n in scan_nodes if not n.table.startswith("__exch_")]
    exch = [n for n in scan_nodes if n.table.startswith("__exch_")]
    kind_of = {eid: e["kind"] for eid, e in ext_inputs.items()}
    shard_nodes = [n for n in exch
                   if kind_of.get(int(n.table[len("__exch_"):]))
                   not in ("gather", "broadcast")]
    repl_nodes = [n for n in exch if n not in shard_nodes]
    replicated_out = D.fused_root_replicated(root, kind_of)

    counters: dict = {}

    def build():
        def fn(scan_b, shard_b, repl_b):
            nodes = real + shard_nodes + repl_nodes
            batches = list(scan_b) + list(shard_b) + list(repl_b)
            stats: dict = {}
            ex = DistExecutor(session, ndev,
                              {id(n): b for n, b in zip(nodes, batches)},
                              sort_stats=stats)
            for pid, val in sorted(scalar_results.items()):
                ex.ctx.scalar_results[pid] = val
            out = ex.exec_node(root)
            if ex.guards:
                g = jnp.any(jnp.stack([jnp.asarray(x) for x in ex.guards]))
            else:
                g = jnp.zeros((), bool)
            g = jax.lax.psum(g.astype(jnp.int32), AXIS) > 0
            # trace-time counters: re-filled on every (re)trace, replayed
            # from the memoized entry on executable reuse
            counters.clear()
            counters.update(stats)
            return out, g

        out_spec = PS() if replicated_out else PS(AXIS)
        sharded = _shard_mapped(fn, mesh, (PS(AXIS), PS(AXIS), PS()),
                                (out_spec, PS()))
        return CC.build_jit(sharded), counters

    key = CC.fused_key(fragment_bytes, ndev, session, scalar_results,
                       ext_inputs)
    jitted, counters = CC.get_or_build(key, build)
    scan_feed = [sharded_scan(session.catalog.get(n.table), n, mesh, ndev)
                 for n in real]
    shard_feed = [_ext_shard_batch(
        ext_inputs[int(n.table[len("__exch_"):])]["cols"], n, mesh, ndev)
        for n in shard_nodes]
    repl_feed = [_ext_repl_batch(
        ext_inputs[int(n.table[len("__exch_"):])]["cols"], n, mesh)
        for n in repl_nodes]
    out_batch, guard = jitted(scan_feed, shard_feed, repl_feed)
    out_counters = dict(counters)
    if profile:
        # EXPLAIN ANALYZE attribution: XLA cost analysis of the fused
        # program (the memoized executable is a live jit — lower
        # against the feeds; a diagnostic cost paid only when profiling)
        from presto_tpu.observe import profile as PR

        cost = PR.executable_cost(
            jitted, args=(scan_feed, shard_feed, repl_feed))
        if cost:
            out_counters["xla_flops"] = int(cost.get("flops", 0))
            out_counters["xla_bytes_accessed"] = int(
                cost.get("bytes_accessed", 0))
    return out_batch, bool(guard), out_counters
