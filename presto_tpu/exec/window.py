"""Window function execution — fully on-device (jnp), jit-compatible.

Reference parity: operator/WindowOperator.java + the 21 window function
implementations in operator/window/ (RowNumberFunction, RankFunction,
NthValueFunction, LagFunction, ...; framing in WindowPartition.java).
The reference sorts each partition with PagesIndex and walks frames row
by row; here the whole batch is sorted once by (partition, order) keys
and every function is computed as a vectorized prefix/segment scan over
the sorted columns — the TPU-friendly formulation (no per-row loop,
no host round trips), so windowed queries compile into the same XLA
program as the rest of the fragment and distribute by hash-partitioning
on the partition keys (sql/planner/optimizations/AddExchanges.java
inserts the same partitioned exchange for WindowNode).

Framing: ROWS/RANGE with UNBOUNDED/CURRENT/k-offset bounds.  Frame
SHAPE is decided at plan time (the spec is static), so the
prefix-vs-suffix-vs-sliding strategy never branches on data.  Sum-like
aggregates use prefix-sum differences over per-row [frame_start,
frame_end] index vectors; min/max use segmented Hillis-Steele scans or
a sparse-table (doubling) range query for bounded ROWS frames.

Masked (sel=False) rows sort last and form their own partition runs via
a leading liveness sort/partition key, so static mode needs no
compaction: dead rows produce garbage outputs that stay masked.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu import types as T
from presto_tpu.batch import Batch, Column
from presto_tpu.exec import kernels as K
from presto_tpu.observe import names as NM
from presto_tpu.plan import ir
from presto_tpu.plan import nodes as P


class WindowError(Exception):
    pass


def execute_window(ex, node: P.Window) -> Batch:
    b = ex.exec_node(node.source)
    ex._count("window_functions", len(node.functions))
    with NM.kernel_scope("k:window"):
        return _windowed(ex, node, b)


def _windowed(ex, node: P.Window, b: Batch) -> Batch:
    if not ex.static:
        b = K.compact(b)
    n = b.capacity
    live_col = Column(jnp.asarray(b.sel), None, T.BOOLEAN)
    # sort by (liveness, partition keys ASC, order keys as specified);
    # sort_perm already puts masked rows last, and the liveness flag as a
    # partition key fences them into their own (garbage, masked) runs
    keys = [(b.columns[s], True, None) for s in node.partition_by]
    keys += [(b.columns[s], asc, nf) for s, asc, nf in node.order_by]
    if keys or (ex.static and n):
        # static mode must sort even for OVER (): interleaved masked
        # rows would otherwise split the single partition into
        # per-liveness runs (sort_perm orders masked rows last)
        perm = K.sort_perm(b, keys)
        b = K.gather_batch(b, perm)
        live_col = Column(jnp.asarray(b.sel), None, T.BOOLEAN)
    cols = dict(b.columns)
    if n == 0:
        for sym, call in node.functions.items():
            dt = (np.dtype(np.int32) if call.type.is_string
                  else call.type.numpy_dtype())
            cols[sym] = Column(jnp.zeros(0, dt), None, call.type, None)
        return Batch(cols, b.sel)

    part_cols = [live_col] + [b.columns[s] for s in node.partition_by]
    order_cols = [b.columns[s] for s, _, _ in node.order_by]
    ctx = _FrameContext(n, part_cols, order_cols, bool(node.order_by),
                        node.frame)
    for sym, call in node.functions.items():
        cols[sym] = _compute(ctx, b, call)
    return Batch(cols, b.sel)


def _adjacent_change(cols: List[Column], n: int) -> jnp.ndarray:
    """new[i] = row i differs from row i-1 on any column (nulls equal)."""
    new = jnp.zeros(n, dtype=bool).at[0].set(True)
    for c in cols:
        d = jnp.asarray(c.data)
        diff = d[1:] != d[:-1]
        v = c.valid
        if v is not None:
            both_null = ~v[1:] & ~v[:-1]
            diff = jnp.where(both_null, False, diff | (v[1:] != v[:-1]))
        new = new.at[1:].set(new[1:] | diff)
    return new


class _FrameContext:
    """Per-window-spec row geometry: partition/peer boundaries and frame
    index vectors (reference: WindowPartition frame computation)."""

    def __init__(self, n, part_cols, order_cols, has_order, frame):
        self.n = n
        ar = jnp.arange(n)
        self.ar = ar
        self.part_new = _adjacent_change(part_cols, n)
        # no ORDER BY: every partition row is a peer of every other
        if order_cols:
            self.peer_new = self.part_new | _adjacent_change(order_cols, n)
        else:
            self.peer_new = self.part_new
        self.part_id = jnp.cumsum(self.part_new.astype(jnp.int32)) - 1
        self.part_start = jax.lax.cummax(
            jnp.where(self.part_new, ar, 0))
        nxt_part = jnp.concatenate(
            [self.part_new[1:], jnp.ones(1, bool)])
        self.part_end = jnp.flip(jax.lax.cummin(
            jnp.flip(jnp.where(nxt_part, ar, n))))
        self.part_size = self.part_end - self.part_start + 1
        self.peer_start = jax.lax.cummax(
            jnp.where(self.peer_new, ar, 0))
        nxt = jnp.concatenate([self.peer_new[1:], jnp.ones(1, bool)])
        self.peer_end = jnp.flip(jax.lax.cummin(
            jnp.flip(jnp.where(nxt, ar, n))))
        self.rn = ar - self.part_start + 1
        self.has_order = has_order
        self.frame = frame

    def frame_bounds(self):
        """(fs, fe, shape) — per-row inclusive bounds plus the STATIC
        frame shape tag: 'prefix' (fs==part_start), 'suffix'
        (fe==part_end), 'whole', 'single', 'sliding:<maxw>'."""
        if self.frame is None:
            if self.has_order:
                ftype, start, end = ("RANGE", "UNBOUNDED PRECEDING",
                                     "CURRENT ROW")
            else:
                ftype, start, end = ("ROWS", "UNBOUNDED PRECEDING",
                                     "UNBOUNDED FOLLOWING")
        else:
            ftype, start, end = self.frame
        fs, s_off = self._bound(ftype, start, is_start=True)
        fe, e_off = self._bound(ftype, end, is_start=False)
        fs = jnp.maximum(fs, self.part_start)
        fe = jnp.minimum(fe, self.part_end)
        if start == "UNBOUNDED PRECEDING" and end == "UNBOUNDED FOLLOWING":
            shape = "whole"
        elif start == "UNBOUNDED PRECEDING":
            shape = "prefix"
        elif end == "UNBOUNDED FOLLOWING":
            shape = "suffix"
        elif ftype == "ROWS" and start == end == "CURRENT ROW":
            shape = "single"
        elif ftype == "RANGE" and start == end == "CURRENT ROW":
            shape = "peer"  # the whole peer group (width is data-dependent)
        else:
            maxw = (s_off or 0) + (e_off or 0) + 1
            shape = f"sliding:{maxw}"
        return fs, fe, shape

    def _bound(self, ftype, spec, is_start):
        """Returns (index vector, static offset magnitude or None)."""
        ar = self.ar
        if spec == "UNBOUNDED PRECEDING":
            return self.part_start, None
        if spec == "UNBOUNDED FOLLOWING":
            return self.part_end, None
        if spec == "CURRENT ROW":
            if ftype == "ROWS":
                return ar, 0
            return (self.peer_start, None) if is_start \
                else (self.peer_end, None)
        k_str, direction = spec.split()
        k = int(k_str)
        if ftype != "ROWS":
            raise WindowError("RANGE with offset frame bounds not supported")
        return (ar - k if direction == "PRECEDING" else ar + k), k


# ---------------------------------------------------------------------------
# function dispatch
# ---------------------------------------------------------------------------

def _compute(ctx: _FrameContext, b: Batch, call: ir.AggCall) -> Column:
    fn = call.fn
    if fn == "row_number":
        return _int_col(ctx.rn, call.type)
    if fn == "rank":
        return _int_col(ctx.peer_start - ctx.part_start + 1, call.type)
    if fn == "dense_rank":
        dr = jnp.cumsum(ctx.peer_new.astype(jnp.int64))
        return _int_col(dr - dr[ctx.part_start] + 1, call.type)
    if fn == "percent_rank":
        rank = ctx.peer_start - ctx.part_start + 1
        denom = jnp.maximum(ctx.part_size - 1, 1)
        out = jnp.where(ctx.part_size > 1, (rank - 1) / denom, 0.0)
        return Column(out.astype(jnp.float64), None, call.type, None)
    if fn == "cume_dist":
        out = (ctx.peer_end - ctx.part_start + 1) / ctx.part_size
        return Column(out.astype(jnp.float64), None, call.type, None)
    if fn == "ntile":
        k = _lit_int(call.args[0], "ntile bucket count")
        if k < 1:
            raise WindowError("ntile bucket count must be positive")
        return _int_col(_ntile(ctx, k), call.type)
    if fn in ("lag", "lead"):
        return _lag_lead(ctx, b, call)
    if fn in ("first_value", "last_value", "nth_value"):
        return _value_fn(ctx, b, call)
    return _frame_aggregate(ctx, b, call)


def _int_col(a, t):
    return Column(a.astype(jnp.int64), None, t, None)


def _lit_int(e: ir.RowExpr, what: str) -> int:
    if isinstance(e, ir.Lit):
        return int(e.value)
    raise WindowError(f"{what} must be a literal")


def _ntile(ctx, k):
    rn0 = ctx.rn - 1
    size = ctx.part_size // k
    rem = ctx.part_size % k
    thresh = rem * (size + 1)
    big = jnp.where(size > 0, rn0 // jnp.maximum(size + 1, 1), rn0)
    small = rem + jnp.where(size > 0,
                            (rn0 - thresh) // jnp.maximum(size, 1), 0)
    return jnp.where(rn0 < thresh, big, small) + 1


def _arg_column(b: Batch, e: ir.RowExpr) -> Column:
    if isinstance(e, ir.Ref):
        return b.columns[e.name]
    if isinstance(e, ir.Lit):
        n = b.capacity
        if e.type.is_string:
            raise WindowError("string literal window argument")
        d = jnp.full(n, e.value if e.value is not None else 0,
                     dtype=e.type.numpy_dtype())
        v = None if e.value is not None else jnp.zeros(n, dtype=bool)
        return Column(d, v, e.type, None)
    raise WindowError("window argument must be a column or literal")


def _gather_col(c: Column, idx, in_frame) -> Column:
    d = jnp.asarray(c.data)
    safe = jnp.clip(idx, 0, d.shape[0] - 1)
    out = d[safe]
    valid = in_frame
    if c.valid is not None:
        valid = valid & c.valid[safe]
    if c.type.is_string and c.dictionary is None:
        raise WindowError("non-dictionary string window values")
    out = jnp.where(valid, out, jnp.zeros((), out.dtype))
    return Column(out, valid, c.type, c.dictionary)


def _nn_machinery(ctx, src):
    """(inclusive nn-count, exclusive nn-count) over the window-sorted
    rows — the vectorized basis for IGNORE NULLS: the m-th non-null's
    index is searchsorted(cnt, m) (reference: the value functions'
    nullTreatment in operator/window/)."""
    valid = src.valid if src.valid is not None \
        else jnp.ones(ctx.n, dtype=bool)
    cnt = jnp.cumsum(valid.astype(jnp.int32))
    return cnt, cnt - valid.astype(jnp.int32), valid


def _lag_lead(ctx, b, call):
    off = _lit_int(call.args[1], "offset") if len(call.args) > 1 else 1
    src = _arg_column(b, call.args[0])
    if getattr(call, "ignore_nulls", False):
        cnt, cnt0, _valid = _nn_machinery(ctx, src)
        if call.fn == "lag":
            # the off-th non-null strictly before this row
            m = cnt0 - off + 1
            in_part = m >= cnt0[ctx.part_start] + 1
        else:
            # the off-th non-null strictly after this row
            m = cnt + off
            in_part = m <= cnt[ctx.part_end]
        m = jnp.maximum(m, 1)
        idx = jnp.searchsorted(cnt, m).astype(jnp.int32)
    elif call.fn == "lag":
        idx = ctx.ar - off
        in_part = idx >= ctx.part_start
    else:
        idx = ctx.ar + off
        in_part = idx <= ctx.part_end
    out = _gather_col(src, idx, in_part)
    if len(call.args) > 2:  # default value fills out-of-partition slots
        dflt = _arg_column(b, call.args[2])
        same_dict = out.dictionary is dflt.dictionary
        if out.type.is_string and not same_dict:
            raise WindowError(
                "lag/lead string default requires matching encoding")
        use_d = ~in_part
        d = jnp.where(use_d, dflt.data, out.data)
        ones = jnp.ones(ctx.n, bool)
        valid = jnp.where(
            use_d,
            dflt.valid if dflt.valid is not None else ones,
            out.valid if out.valid is not None else ones)
        out = Column(d, valid, out.type, out.dictionary)
    return out


def _value_fn(ctx, b, call):
    src = _arg_column(b, call.args[0])
    fs, fe, _shape = ctx.frame_bounds()
    nonempty = fs <= fe
    if getattr(call, "ignore_nulls", False):
        cnt, cnt0, _valid = _nn_machinery(ctx, src)
        if call.fn == "first_value":
            m = cnt0[fs] + 1  # first non-null at/after frame start
        elif call.fn == "last_value":
            m = cnt[fe]  # last non-null at/before frame end
        else:
            k = _lit_int(call.args[1], "nth_value offset")
            if k < 1:
                raise WindowError("nth_value offset must be positive")
            m = cnt0[fs] + k
        nonempty = nonempty & (m >= cnt0[fs] + 1) & (m <= cnt[fe])
        idx = jnp.searchsorted(cnt, jnp.maximum(m, 1)).astype(jnp.int32)
        return _gather_col(src, idx, nonempty)
    if call.fn == "first_value":
        idx = fs
    elif call.fn == "last_value":
        idx = fe
    else:
        k = _lit_int(call.args[1], "nth_value offset")
        if k < 1:
            raise WindowError("nth_value offset must be positive")
        idx = fs + k - 1
        nonempty = nonempty & (idx <= fe)
    return _gather_col(src, idx, nonempty)


# ---------------------------------------------------------------------------
# aggregates over frames
# ---------------------------------------------------------------------------

def _prefix_at(csum, idx):
    """Sum of x[0..idx] using inclusive prefix csum; idx may be -1."""
    return jnp.where(idx >= 0,
                     csum[jnp.clip(idx, 0, csum.shape[0] - 1)], 0)


def _frame_aggregate(ctx, b, call):
    fn = call.fn
    fs, fe, shape = ctx.frame_bounds()
    nonempty = fs <= fe
    if fn == "count" and not call.args:
        cnt = jnp.where(nonempty, fe - fs + 1, 0)
        return _int_col(cnt, call.type)

    src = _arg_column(b, call.args[0]) if call.args else None
    d = jnp.asarray(src.data)
    notnull = src.valid if src.valid is not None \
        else jnp.ones(ctx.n, dtype=bool)
    cs = jnp.cumsum(notnull.astype(jnp.int64))
    cnt = _prefix_at(cs, fe) - _prefix_at(cs, fs - 1)
    cnt = jnp.where(nonempty, cnt, 0)
    if fn == "count":
        return _int_col(cnt, call.type)

    if fn in ("sum", "avg", "stddev", "stddev_samp", "stddev_pop",
              "variance", "var_samp", "var_pop"):
        if src.type.is_string:
            raise WindowError(f"{fn} over strings")
        acc = jnp.float32 if d.dtype == jnp.float32 else jnp.float64
        x = jnp.where(notnull, d, jnp.zeros((), d.dtype)).astype(acc)
        s = jnp.cumsum(x)
        tot = _prefix_at(s, fe) - _prefix_at(s, fs - 1)
        valid = nonempty & (cnt > 0)
        if fn == "sum":
            if call.type.is_integer or call.type.name == "DECIMAL":
                si = jnp.cumsum(jnp.where(
                    notnull, d, jnp.zeros((), d.dtype)).astype(jnp.int64))
                tot = _prefix_at(si, fe) - _prefix_at(si, fs - 1)
            return Column(tot, valid, call.type, None)
        mean = tot / jnp.maximum(cnt, 1)
        if fn == "avg":
            return Column(mean.astype(jnp.float64), valid, call.type, None)
        s2 = jnp.cumsum(x * x)
        tot2 = _prefix_at(s2, fe) - _prefix_at(s2, fs - 1)
        m2 = tot2 - tot * tot / jnp.maximum(cnt, 1)
        if fn in ("stddev", "stddev_samp", "variance", "var_samp"):
            denom = jnp.maximum(cnt - 1, 1)
            valid = valid & (cnt > 1)
        else:
            denom = jnp.maximum(cnt, 1)
        var = jnp.maximum(m2 / denom, 0.0)
        out = jnp.sqrt(var) if fn.startswith("stddev") else var
        return Column(out.astype(jnp.float64), valid, call.type, None)

    if fn in ("min", "max"):
        return _minmax(ctx, src, d, notnull, fs, fe, shape,
                       nonempty & (cnt > 0), call)
    raise WindowError(f"window aggregate {fn} not supported")


def _segmented_scan(vals, seg_new, op, identity):
    """Hillis-Steele segmented inclusive scan — log2(n) vectorized passes."""
    n = vals.shape[0]
    res = vals
    flag = seg_new
    shift = 1
    while shift < n:
        prev = jnp.concatenate([
            jnp.full(shift, identity, dtype=res.dtype), res[:-shift]])
        prev_flag = jnp.concatenate([
            jnp.ones(shift, dtype=bool), flag[:-shift]])
        res = jnp.where(flag, res, op(res, prev))
        flag = flag | prev_flag
        shift <<= 1
    return res


def _minmax(ctx, src, d, notnull, fs, fe, shape, valid, call):
    op = jnp.minimum if call.fn == "min" else jnp.maximum
    if src.type.is_string and src.dictionary is None:
        raise WindowError("min/max over non-dictionary strings")
    if src.dictionary is not None:
        # dictionary codes are sorted-unique -> order-preserving
        work = d.astype(jnp.int64)
        ident = (np.iinfo(np.int64).max if call.fn == "min"
                 else np.iinfo(np.int64).min)
    elif jnp.issubdtype(d.dtype, jnp.floating):
        work = d.astype(jnp.float64)
        ident = np.inf if call.fn == "min" else -np.inf
    else:
        work = d.astype(jnp.int64)
        ident = (np.iinfo(np.int64).max if call.fn == "min"
                 else np.iinfo(np.int64).min)
    work = jnp.where(notnull, work, ident)

    n = ctx.n
    # the frame SHAPE is static (from the spec), so strategy selection
    # never branches on data
    if shape == "prefix" or shape == "whole":
        run_fwd = _segmented_scan(work, ctx.part_new, op, ident)
        raw = run_fwd[jnp.clip(fe, 0, n - 1)]
    elif shape == "peer":
        # frame == the peer group: forward scan over PEER segments,
        # evaluated at each row's peer_end (== fe)
        run_fwd = _segmented_scan(work, ctx.peer_new, op, ident)
        raw = run_fwd[jnp.clip(fe, 0, n - 1)]
    elif shape == "suffix":
        nxt = jnp.concatenate([ctx.part_new[1:], jnp.ones(1, bool)])
        run_bwd = jnp.flip(_segmented_scan(
            jnp.flip(work), jnp.flip(nxt), op, ident))
        raw = run_bwd[jnp.clip(fs, 0, n - 1)]
    elif shape == "single":
        raw = work[jnp.clip(fs, 0, n - 1)]
    else:  # sliding:<maxw>
        maxw = int(shape.split(":")[1])
        raw = _minmax_sliding(work, fs, fe, op, ident, maxw)
    out = jnp.where(valid, raw, jnp.zeros((), raw.dtype))
    if src.dictionary is not None:
        out = out.astype(d.dtype)
    return Column(out, valid, call.type,
                  src.dictionary if src.dictionary is not None else None)


def _minmax_sliding(work, fs, fe, op, ident, max_w):
    """Bounded ROWS frames: sparse-table (doubling) range min/max —
    O(n log n) precompute, O(1) per row.  max_w is static (from the
    frame spec's offsets)."""
    n = work.shape[0]
    width = fe - fs + 1
    levels = [work]
    span = 1
    while span < max(max_w, 1):
        cur = levels[-1]
        nxt = op(cur, jnp.concatenate(
            [cur[span:], jnp.full(span, ident, cur.dtype)]))
        levels.append(nxt)
        span <<= 1
    k = jnp.maximum(width, 1)
    lev = jnp.floor(jnp.log2(k.astype(jnp.float64))).astype(jnp.int64)
    span_arr = 1 << lev
    out = jnp.full(n, ident, dtype=work.dtype)
    for li, table in enumerate(levels):
        m = lev == li
        a = table[jnp.clip(fs, 0, n - 1)]
        second = jnp.clip(fe - span_arr + 1, 0, n - 1)
        cand = op(a, table[second])
        out = jnp.where(m, cand, out)
    return out
