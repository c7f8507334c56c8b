"""Relational kernels over columnar device arrays.

Reference parity: the operator layer (presto-main/.../operator/, §2.4 of
SURVEY.md) re-expressed as whole-column array programs:

- HashAggregationOperator + GroupByHash (operator/MultiChannelGroupByHash.java)
  -> exact key packing + sort + segmented reductions.  TPUs have no
  scatter-friendly hash tables; sort-based grouping is contention-free and
  maps onto the sorting network + segmented-scan idioms XLA compiles well.
- HashBuilderOperator/LookupJoinOperator (PagesIndex + JoinProbe)
  -> sort build side + vectorized searchsorted probe; FK joins (unique
  build keys) are a pure gather; one-to-many expands via repeat with a
  computed total (the PositionLinks analog).
- OrderByOperator/TopNOperator -> multi-key lexicographic argsort / sort+cut.
- Masks replace selection: filters AND into `sel` (no compaction inside a
  fragment), the static-shape answer to data-dependent page sizes.

Eager-mode kernels pull capacities to host (dynamic result sizing); the
jitted fragment path reuses the same functions with static capacities.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.batch import Batch, Column, Dictionary
from presto_tpu.exec import gather as G
from presto_tpu.exec.colval import translate_codes
from presto_tpu.observe import names as NM

I64_MIN = np.iinfo(np.int64).min
I64_MAX = np.iinfo(np.int64).max
I32_MAX = np.iinfo(np.int32).max


def key_sentinel(key) -> int:
    """Masked-row sentinel for a packed key array: the dtype's max
    (narrow int32 keys avoid the TPU's emulated 64-bit integer ops —
    the hardware has no native int64, so every i64 compare/sort/gather
    runs as u32-pair fusions, measured ~8s of TPC-H Q18's runtime)."""
    return I32_MAX if key.dtype == jnp.int32 else I64_MAX


# ---------------------------------------------------------------------------
# key packing: N key columns -> one int64 (exact, using runtime ranges)
# ---------------------------------------------------------------------------


def pack_keys(cols: List[Column], sel, extra_cols: Optional[List[Column]] = None):
    """Pack key columns into a single integer key per row — int32 when
    the packed widths fit 30 bits (native on TPU), else int64.  Masked-out
    rows get the dtype's max as sentinel (sorts last, never matches; see
    key_sentinel). NULL in any key column gets its own code (SQL GROUP BY
    treats NULLs as one group).

    Returns (key: i32[n]|i64[n], layout) where layout allows packing another
    column set with the same strides (for join build/probe sides pass
    `extra_cols` so both sides share ranges).
    """
    def _minmax(col):
        d = _orderable_int(col)
        if d.shape[0] == 0:  # zero-capacity side (empty split/partition)
            return jnp.asarray(I64_MAX), jnp.asarray(I64_MIN)
        return (jnp.min(jnp.where(_valid_arr(col), d, I64_MAX)),
                jnp.max(jnp.where(_valid_arr(col), d, I64_MIN)))

    parts = []
    for i, c in enumerate(cols):
        lo, hi = _minmax(c)
        if extra_cols is not None:
            elo, ehi = _minmax(extra_cols[i])
            lo = jnp.minimum(lo, elo)
            hi = jnp.maximum(hi, ehi)
        lo_h = int(lo)
        hi_h = int(hi)
        if hi_h < lo_h:  # all null / empty
            lo_h, hi_h = 0, 0
        parts.append((lo_h, hi_h - lo_h + 2))  # +1 for range, +1 for null code

    total_bits = sum(int(np.ceil(np.log2(max(card, 2)))) for _, card in parts)
    if total_bits > 62:
        return _hash_keys(cols, sel), None

    key = _apply_layout(cols, (layout := _assign_strides(parts)))
    key = jnp.where(sel, key, key_sentinel(key))
    return key, layout


def _assign_strides(parts) -> list:
    """(lo, card) per column -> (lo, stride, width) with the FIRST column
    most significant: ascending packed-key order == lexicographic order
    of the columns as listed.  This is what makes grouped output sorted
    on its group keys (the ordering-properties framework's producer
    side) at zero cost — stride assignment order is free."""
    widths = [int(np.ceil(np.log2(max(card, 2)))) for _, card in parts]
    layout = []
    stride = 1
    for (lo_h, _card), width in zip(reversed(parts), reversed(widths)):
        layout.append((lo_h, stride, width))
        stride <<= width
    layout.reverse()
    return layout


def _apply_layout(cols: List[Column], layout) -> jnp.ndarray:
    total_bits = sum(w for _, _, w in layout)
    kt = jnp.int32 if total_bits <= 30 else jnp.int64  # native i32 wins
    key = None
    for c, (lo, stride, width) in zip(cols, layout):
        d = _orderable_int(c)
        code = jnp.where(_valid_arr(c), d - lo + 1, 0)  # 0 = null code
        contrib = code.astype(kt) * kt(stride)
        key = contrib if key is None else key + contrib
    return key


def pack_with_layout(cols: List[Column], sel, layout) -> jnp.ndarray:
    if layout is None:
        return _hash_keys(cols, sel)
    key = _apply_layout(cols, layout)
    return jnp.where(sel, key, key_sentinel(key))


_POW2 = None  # lazily-built exact power-of-two table (host constants)


def _f64_orderable_arith(d: jnp.ndarray) -> jnp.ndarray:
    """Order-preserving, injective f64 -> i64 WITHOUT any 64-bit bitcast
    (the TPU's X64 rewriter cannot lower f64 bitcasts).  Decomposes
    |x| = m * 2^e arithmetically: e from log2 with comparison fixups, m
    recovered by an EXACT power-of-two table multiply, so mant = m*2^52
    is the exact 53-bit significand.  Layout: subnormal magnitudes map to
    [1, 2^52), normals to [(e+1023)*2^52 + mant52] <= 2047*2^52 < 2^63;
    negatives mirror; +-0 both map to 0 (SQL-correct: they compare
    equal); +-inf and NaN get sentinels with NaN largest (Presto sort
    order).  Replaces the classic sign-flip bit trick, which is kept
    out because jax.lax.bitcast_convert_type(f64) does not compile
    on this TPU stack."""
    global _POW2
    if _POW2 is None:
        # host-side numpy so the table is a fresh constant per trace
        # (a traced global would leak tracers)
        _POW2 = np.asarray([2.0 ** i for i in range(-1099, 1024)],
                           dtype=np.float64)
    pow2 = jnp.asarray(_POW2)

    min_normal = 2.2250738585072014e-308
    ax = jnp.abs(d)
    e = jnp.floor(jnp.log2(jnp.maximum(ax, min_normal))).astype(jnp.int64)
    e = jnp.clip(e, -1022, 1023)
    # ax * 2^-e in two half-exponent steps: a single 2^-1023 constant is
    # subnormal and DAZ-flushed to zero (which would collapse the whole
    # top binade); both halves and both intermediates stay normal
    e1 = e // 2
    e2 = e - e1
    m = (ax * pow2[1099 - e1]) * pow2[1099 - e2]  # exact
    # log2 rounding can be off by one near power-of-two boundaries;
    # two fixup rounds restore m in [1, 2) exactly
    for _ in range(2):
        too_big = m >= 2.0
        e = jnp.where(too_big, e + 1, e)
        m = jnp.where(too_big, m * 0.5, m)
        too_small = m < 1.0
        e = jnp.where(too_small & (e > -1022), e - 1, e)
        m = jnp.where(too_small & (e >= -1022), m * 2.0, m)
    mant = (m * (2.0 ** 52)).astype(jnp.int64) - (1 << 52)
    # max key = 2047*2^52 - 1, safely below the +-inf/NaN sentinels and
    # the masked-row sentinel I64_MAX
    key_norm = (e + 1023) * (1 << 52) + mant
    # subnormals: XLA runs with FTZ/DAZ, so every arithmetic op in the
    # engine already sees them as zero — key 0 keeps grouping/joins
    # consistent with that arithmetic
    key_mag = jnp.where(ax < min_normal, 0, key_norm)
    key = jnp.where(d < 0, -key_mag, key_mag)
    key = jnp.where(jnp.isinf(d),
                    jnp.where(d > 0, jnp.int64(I64_MAX - 16),
                              jnp.int64(-(I64_MAX - 16))), key)
    return jnp.where(jnp.isnan(d), jnp.int64(I64_MAX - 8), key)


def _f64_orderable_pair(d: jnp.ndarray) -> jnp.ndarray:
    """TPU orderable key for f64: lexicographic (hi, lo) float32 pair
    packed into i64 via 32-bit bitcasts (the only bitcasts this TPU
    stack compiles).  Monotone for ALL doubles; injective down to
    48-bit significands — finer-grained values merge, which matches the
    hardware reality that this TPU's f64 is itself emulated (its
    floor/convert ops already round near bit 49, see
    _f64_orderable_arith for the exact CPU path)."""
    hi = jnp.clip(d.astype(jnp.float32), -3.4e38, 3.4e38)
    lo = (d - hi.astype(jnp.float64)).astype(jnp.float32)
    # finite values beyond f32 range merge near the top of the finite
    # band but stay strictly below +-inf
    lo = jnp.where(jnp.isfinite(d), jnp.clip(lo, -3.4e38, 3.4e38), lo)

    def o32(f):
        b = jax.lax.bitcast_convert_type(f, jnp.int32)
        return jnp.where(b < 0, (~b) + jnp.int32(-(1 << 31)), b)

    key = (o32(hi).astype(jnp.int64) * (1 << 32)
           + o32(lo).astype(jnp.int64) + (1 << 31))
    key = jnp.where(d == 0, 0, key)  # +-0 compare equal in SQL
    return jnp.where(jnp.isnan(d), jnp.int64(I64_MAX - 8), key)


def _orderable_int(c: Column) -> jnp.ndarray:
    d = c.data
    if d.dtype == jnp.bool_:
        return d.astype(jnp.int64)
    if jnp.issubdtype(d.dtype, jnp.floating):
        if jax.default_backend() == "tpu":
            return _f64_orderable_pair(d.astype(jnp.float64))
        return _f64_orderable_arith(d.astype(jnp.float64))
    return d.astype(jnp.int64)


def _valid_arr(c: Column) -> jnp.ndarray:
    if c.valid is None:
        return jnp.ones(c.data.shape, dtype=bool)
    return c.valid


def _hash_keys(cols: List[Column], sel) -> jnp.ndarray:
    """64-bit mix fallback when exact packing exceeds 62 bits.
    Collision probability for n rows ~ n^2/2^64 (documented engine limit;
    an exact verification pass can be layered later)."""
    h = jnp.zeros(cols[0].data.shape, dtype=jnp.uint64)
    for c in cols:
        d = _orderable_int(c).astype(jnp.uint64)
        d = jnp.where(_valid_arr(c), d, jnp.uint64(0x9E3779B97F4A7C15))
        h = h ^ (d + jnp.uint64(0x9E3779B97F4A7C15) + (h << jnp.uint64(6)) + (h >> jnp.uint64(2)))
        z = h
        z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
        h = z ^ (z >> jnp.uint64(31))
    key = (h >> jnp.uint64(1)).astype(jnp.int64)  # keep positive, below I64_MAX
    return jnp.where(sel, key, I64_MAX)


# ---------------------------------------------------------------------------
# group-by
# ---------------------------------------------------------------------------


def static_layout(cols: List[Column], stats_list) -> Optional[list]:
    """Compile-time pack layout from metadata: dictionary sizes for string
    codes, connector ColStats ranges for numerics.  Returns None when any
    column's range is unknown (callers fall back to 64-bit hashing, which
    needs no range and no host sync)."""
    parts = []
    for c, st in zip(cols, stats_list):
        if c.dictionary is not None:
            lo, hi = 0, max(len(c.dictionary) - 1, 0)
        elif c.data.dtype == jnp.bool_:
            lo, hi = 0, 1
        elif st is not None and st.min is not None and st.max is not None \
                and not jnp.issubdtype(c.data.dtype, jnp.floating):
            lo, hi = int(st.min), int(st.max)
        else:
            return None
        parts.append((lo, hi - lo + 2))
    total_bits = sum(int(np.ceil(np.log2(max(card, 2)))) for _, card in parts)
    if total_bits > 62:
        return None
    return _assign_strides(parts)


def layout_range_guard(cols: List[Column], sel, layout) -> jnp.ndarray:
    """True if any live value falls outside its static layout range —
    out-of-range values would bleed bits into adjacent packed fields and
    silently corrupt keys, so the compiled path re-runs dynamically."""
    bad = jnp.zeros((), bool)
    for c, (lo, _stride, width) in zip(cols, layout):
        d = _orderable_int(c)
        live = sel & _valid_arr(c)
        hi = lo + (1 << width) - 2  # code 0 reserved for NULL
        bad = bad | jnp.any(live & ((d < lo) | (d > hi)))
    return bad


def nonzero_i32(mask: jnp.ndarray, size: int, fill: int) -> jnp.ndarray:
    """jnp.nonzero(mask, size=, fill_value=)[0] in int32 throughout.
    Under jax x64 the stock nonzero computes its prefix sums in int64,
    which the TPU emulates as u32-pair fusions (~500ms per 6M rows,
    measured); an i32 cumsum + one i32 co-sort is ~3x cheaper."""
    n = mask.shape[0]
    fill = min(max(int(fill), 0), max(n - 1, 0))  # stock nonzero clips
    total = jnp.sum(mask.astype(jnp.int32)) if n else jnp.int32(0)
    if 0 < size <= (1 << 16) and n > 4 * size:
        # small k: top_k over a positional score (~10ms at 6M rows vs
        # ~170ms for the sort — same idiom as executor._compact_batch)
        pos = jnp.arange(n, dtype=jnp.int32)
        score = jnp.where(mask, n - pos, 0)
        top = jax.lax.top_k(score, size)[0]
        out = jnp.clip(n - top, 0, n - 1)
    else:
        ones = mask.astype(jnp.int32)
        cum = jnp.cumsum(ones)
        slot = jnp.where(mask, cum - ones, jnp.int32(n))  # excl. prefix
        _, sidx = jax.lax.sort((slot, jnp.arange(n, dtype=jnp.int32)),
                               num_keys=1)
        out = sidx[:size] if n >= size else jnp.concatenate(
            [sidx, jnp.full((size - n,), fill, jnp.int32)])
    return jnp.where(jnp.arange(size, dtype=jnp.int32) < total, out,
                     jnp.int32(fill))


def unpermute(order: jnp.ndarray, *payloads):
    """Carry payloads back to original row order: payload[i] moves to
    position order[i].  One co-sort keyed on the permutation replaces
    `payload[argsort(order)]` — on TPU an extra full-size GATHER costs
    ~43ms per 6M rows (measured, Q1 xplane) while sort payload operands
    ride along nearly free (8 payloads sort at 1-payload cost)."""
    out = jax.lax.sort((order,) + payloads, num_keys=1)[1:]
    return out[0] if len(out) == 1 else out


@NM.scoped("k:sort")
def sort_pair(key: jnp.ndarray):
    """(sorted key, permutation) — THE routed entry point for key sorts,
    so the executor's sort-permutation memo can cache and replay the
    permutation for every later grouping/join on the same key."""
    n = key.shape[0]
    return jax.lax.sort((key, jnp.arange(n, dtype=jnp.int32)), num_keys=1)


def monotone_guard(key: jnp.ndarray) -> jnp.ndarray:
    """True if `key` is NOT nondecreasing end to end (the traced
    ordering-claim verifier for presorted JOIN builds, where sentinels
    must already sit in a suffix — same pattern as layout_range_guard:
    a tripped guard sends the compiled program to the dynamic path)."""
    if key.shape[0] < 2:
        return jnp.zeros((), bool)
    return jnp.any(key[1:] < key[:-1])


def _live_runs(key: jnp.ndarray):
    """Run-boundary scan over a key whose LIVE subsequence is claimed
    nondecreasing (masked rows carry key_sentinel and may be anywhere).
    Returns (live, newgrp, guard): newgrp marks each live row starting a
    new key run; guard is True when the claim is violated.  The
    previous-live-key at row i is the running max of live keys before i
    — exact under the claim, and any violation (a live key below that
    max) trips the guard, so a wrong claim can never mis-group."""
    n = key.shape[0]
    live = key != key_sentinel(key)
    if n == 0:
        z = jnp.zeros((0,), bool)
        return z, z, jnp.zeros((), bool)
    # packed keys are nonnegative (codes >= 0 per field), so -1 is a
    # safe "no previous live row" floor
    floor = jnp.where(live, key, jnp.full((), -1, key.dtype))
    prev = jnp.concatenate([jnp.full((1,), -1, key.dtype),
                            jax.lax.cummax(floor)[:-1]])
    guard = jnp.any(live & (key < prev))
    newgrp = live & (key != prev)
    return live, newgrp, guard


@NM.scoped("k:group_ids")
def group_ids_presorted(key: jnp.ndarray, sel):
    """Sort-free grouping for a key already nondecreasing over its live
    rows (scan order from an ordering-declaring connector, or a
    prior grouped output): ONE run-boundary scan replaces the grouping
    sort AND the unpermute co-sort.  Returns (gid, newgrp, n_groups_t,
    guard) with gid semantics identical to group_ids — groups numbered
    in ascending key order; representatives are the first row of each
    run, recoverable as nonzero_i32(newgrp, ...) once the caller has
    host-synced n_groups_t (together with the guard, in ONE fetch).
    guard True => the ordering claim lied and the results are garbage;
    callers MUST fall back to group_ids."""
    live, newgrp, guard = _live_runs(key)
    n = key.shape[0]
    n_groups_t = jnp.sum(newgrp.astype(jnp.int32))
    gid = jnp.cumsum(newgrp.astype(jnp.int32)) - 1 if n else \
        jnp.zeros((0,), jnp.int32)
    gid = jnp.where(live, gid, n_groups_t)
    return gid, newgrp, n_groups_t, guard


@NM.scoped("k:group_ids")
def group_ids_presorted_static(key: jnp.ndarray, cap: int):
    """Static-capacity twin of group_ids_presorted: returns (gid,
    rep_rows[cap], exists[cap], overflow, guard) matching the
    group_ids_static contract, with guard riding the executor's existing
    static-guard channel (trip => whole-query dynamic fallback)."""
    live, newgrp, guard = _live_runs(key)
    n = key.shape[0]
    n_groups = jnp.sum(newgrp.astype(jnp.int32))
    if n == 0:
        gid = jnp.zeros((0,), jnp.int32)
        rep_rows = jnp.zeros((cap,), jnp.int32)
    else:
        gid = jnp.cumsum(newgrp.astype(jnp.int32)) - 1
        gid = jnp.where(live & (gid < cap), gid, cap)
        rep_rows = nonzero_i32(newgrp, cap, 0)
    exists = jnp.arange(cap) < n_groups
    return gid, rep_rows, exists, n_groups > cap, guard


@NM.scoped("k:group_ids")
def group_ids_static(key: jnp.ndarray, cap: int, sorted_pair=None):
    """Static-shape grouping: same sort-based scheme as group_ids but with
    a fixed group capacity.  Returns (gid, rep_rows[cap], exists[cap],
    overflow) — overflow True means cap was too small (caller re-runs in
    dynamic mode; the guard is checked once per query, not per op).
    `sorted_pair` replays a memoized (skey, order) for this exact key."""
    n = key.shape[0]
    skey, order = sorted_pair if sorted_pair is not None else sort_pair(key)
    newgrp = jnp.concatenate([jnp.ones((1,), bool), skey[1:] != skey[:-1]])
    live_sorted = skey != key_sentinel(key)
    newgrp = newgrp & live_sorted
    n_groups = jnp.sum(newgrp)
    gid_sorted = jnp.cumsum(newgrp.astype(jnp.int32)) - 1
    gid_sorted = jnp.where(live_sorted & (gid_sorted < cap), gid_sorted, cap)
    gid = unpermute(order, gid_sorted)
    rep_pos = nonzero_i32(newgrp, cap, 0)
    if n == 0:  # empty input (e.g. zero-row exchange buffer)
        rep_rows = jnp.zeros((cap,), jnp.int32)
    else:
        rep_rows = order[rep_pos]
    exists = jnp.arange(cap) < n_groups
    return gid, rep_rows, exists, n_groups > cap


@NM.scoped("k:group_ids")
def group_ids(key: jnp.ndarray, sel,
              sorted_pair=None) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Sort-based grouping. Returns (gid[n] in [0, n_groups) for live rows,
    representative row index per group [n_groups], n_groups).
    Masked rows get gid = n_groups (callers drop them via segment bounds).
    `sorted_pair` replays a memoized (skey, order) for this exact key."""
    n = key.shape[0]
    skey, order = sorted_pair if sorted_pair is not None \
        else sort_pair(key)  # masked rows sort last
    newgrp = jnp.concatenate([jnp.ones((1,), bool), skey[1:] != skey[:-1]])
    live_sorted = skey != key_sentinel(key)
    newgrp = newgrp & live_sorted
    gid_sorted = jnp.cumsum(newgrp.astype(jnp.int32)) - 1
    n_groups = int(jnp.sum(newgrp))
    gid_sorted = jnp.where(live_sorted, gid_sorted, n_groups)
    gid = unpermute(order, gid_sorted)
    # representative row per group = first sorted occurrence
    rep_sorted_pos = nonzero_i32(newgrp, max(n_groups, 1), 0)
    rep_rows = order[rep_sorted_pos][:n_groups] if n_groups else jnp.zeros((0,), order.dtype)
    return gid, rep_rows, n_groups


_MATMUL_GROUPS = 4096  # few-group segment sums go through the MXU instead
# (einsum against a fused one-hot costs ~7ms at 6M rows x 1024 groups,
# measured, vs ~48ms per column for the TPU scatter-add lowering)


@NM.scoped("k:segment")
def segment_sum(x, gid, n_groups):
    if n_groups == 1:
        # global aggregate: a plain reduction — segment scatter-add into
        # one bucket serializes on TPU (hundreds of memory passes)
        return jnp.sum(x)[None]
    if n_groups <= _MATMUL_GROUPS and x.ndim == 1 \
            and x.shape[0] >= 4 * n_groups:
        # few groups, many rows: one-hot matmul rides the MXU; the TPU
        # scatter-add lowering serializes per-bucket otherwise
        oh = jax.nn.one_hot(gid, n_groups, dtype=jnp.float32)
        if jnp.issubdtype(x.dtype, jnp.floating):
            acc = jnp.float64 if x.dtype == jnp.float64 else jnp.float32
            return jnp.einsum("r,rg->g", x.astype(acc),
                              oh.astype(acc)).astype(x.dtype)
        # exact int64 via three 22-bit limbs (each limb sum stays inside
        # the f64 integer range for any realistic row count); modular
        # reconstruction matches two's-complement int64 addition
        xi = x.astype(jnp.int64)
        ohf = oh.astype(jnp.float64)
        out = jnp.zeros((n_groups,), dtype=jnp.int64)
        for shift in (0, 22, 44):
            limb = ((xi >> shift) & 0x3FFFFF).astype(jnp.float64)
            s = jnp.einsum("r,rg->g", limb, ohf)
            out = out + (s.astype(jnp.int64) << shift)
        return out.astype(x.dtype if x.dtype != jnp.bool_ else jnp.int64)
    return jax.ops.segment_sum(x, gid, num_segments=n_groups + 1)[:n_groups]


def _reduce_identity(dtype, for_min: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf if for_min else -jnp.inf
    if dtype == jnp.bool_:
        return True if for_min else False
    info = jnp.iinfo(dtype)
    return info.max if for_min else info.min


@NM.scoped("k:segment")
def segment_min(x, gid, n_groups):
    if n_groups == 1:
        if x.shape[0] == 0:  # empty split/partition: the identity, like
            return jnp.full((1,), _reduce_identity(x.dtype, True), x.dtype)
        return jnp.min(x)[None]
    return jax.ops.segment_min(x, gid, num_segments=n_groups + 1)[:n_groups]


@NM.scoped("k:segment")
def segment_max(x, gid, n_groups):
    if n_groups == 1:
        if x.shape[0] == 0:
            return jnp.full((1,), _reduce_identity(x.dtype, False), x.dtype)
        return jnp.max(x)[None]
    return jax.ops.segment_max(x, gid, num_segments=n_groups + 1)[:n_groups]


@NM.scoped("k:segment")
def segment_any(mask, gid, n: int):
    """True where ANY row of the segment has `mask` set — the join
    layer's "any passing match per probe row" reduction.  Exact
    num_segments with no dead slot: gid here is a probe-row index,
    always in range (unlike the grouping kernels' sentinel slot)."""
    return jax.ops.segment_max(mask.astype(jnp.int32), gid,
                               num_segments=n) > 0


# ---------------------------------------------------------------------------
# join probe
# ---------------------------------------------------------------------------


def hll_hash64(col: Column) -> jnp.ndarray:
    """Process-independent 64-bit value hash for approx_distinct: string
    (dictionary) columns hash their VALUES via xxh64 host-side per
    dictionary entry (cached on the Dictionary), so shards/workers with
    different code assignments agree; numeric columns splitmix their
    orderable ints.  Single-device and distributed paths share this, so
    their HLL registers — and estimates — match exactly while both use
    m=1024 registers (hll_registers_and_estimate shrinks m above ~8k
    groups to bound the register matrix; past that point the two paths
    are independent — both valid — approximations)."""
    d = jnp.asarray(col.data)
    dic = col.dictionary
    if dic is not None and not hasattr(dic.values, "prefix"):
        hv = getattr(dic, "_value_hashes", None)
        if hv is None:
            from presto_tpu import native

            hv = np.asarray(
                [native.xxh64(str(v).encode("utf-8", "surrogatepass"))
                 for v in dic.values.tolist()], dtype=np.uint64)
            try:
                dic._value_hashes = hv
            except AttributeError:
                pass
        safe = jnp.clip(d, 0, max(len(dic) - 1, 0))
        return jnp.asarray(hv)[safe]
    # numeric / FormatDictionary (code<->value bijection): splitmix the value
    x = _orderable_int(col).astype(jnp.uint64)
    z = x + jnp.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return z ^ (z >> jnp.uint64(31))


def hll_registers_and_estimate(h: jnp.ndarray, valid: jnp.ndarray,
                               gid: jnp.ndarray, n_groups: int,
                               m: int = 1024) -> jnp.ndarray:
    """Vectorized HyperLogLog per group — the TPU-native
    approx_distinct (reference: ApproximateCountDistinctAggregation over
    airlift HLL sketches).  Instead of per-row sketch objects, all
    n_groups*m registers live in one array updated by a single
    segment_max; the bias-corrected estimate with small-range linear
    counting follows the standard HLL formula.  m=1024 registers gives
    ~3.25% standard error (1.04/sqrt(m)); for very large group counts m
    shrinks so the register matrix stays bounded (~64MB) instead of
    scaling to gigabytes with a static capacity hint."""
    max_registers = 1 << 23
    while m > 64 and n_groups * m > max_registers:
        m //= 2
    log2m = int(np.log2(m))
    reg = (h & jnp.uint64(m - 1)).astype(jnp.int64)
    w = ((h >> jnp.uint64(log2m)) & jnp.uint64(0xFFFFFFFF)).astype(jnp.float64)
    # rho = position of the leftmost 1-bit of the 32-bit w (1-based from
    # the top); w == 0 -> 33.  float64 log2 is exact for ints < 2^53.
    rho = jnp.where(w > 0, 32.0 - jnp.floor(jnp.log2(jnp.maximum(w, 1.0))),
                    33.0)
    seg = gid * m + reg
    seg = jnp.where(valid, seg, n_groups * m)  # dead rows -> overflow slot
    M = jax.ops.segment_max(
        jnp.where(valid, rho, 0.0), seg, num_segments=n_groups * m + 1,
    )[:-1].reshape(n_groups, m)
    M = jnp.maximum(M, 0.0)  # empty registers: segment_max identity is -inf
    return hll_estimate(M)


def hll_m_for_error(e: float) -> int:
    """Register count for a requested standard error e: the power of two
    with 1.04/sqrt(m) <= e, clamped to [64, 65536] (reference:
    HyperLogLog's indexBitLength from maxStandardError)."""
    m = 64
    while m < 65536 and 1.04 / np.sqrt(m) > e:
        m *= 2
    return m


def hll_estimate(M: jnp.ndarray) -> jnp.ndarray:
    """Bias-corrected HLL estimate with small-range linear counting from
    an (n_groups, m) register matrix (any integer/float register dtype)."""
    m = M.shape[1]
    Mf = M.astype(jnp.float64)
    alpha = 0.7213 / (1.0 + 1.079 / m)
    E = alpha * m * m / jnp.sum(2.0 ** (-Mf), axis=1)
    zeros = jnp.sum(Mf == 0.0, axis=1)
    linear = m * jnp.log(m / jnp.maximum(zeros, 1).astype(jnp.float64))
    est = jnp.where((E <= 2.5 * m) & (zeros > 0), linear, E)
    return jnp.round(est).astype(jnp.int64)


def hll_partial(h: jnp.ndarray, valid: jnp.ndarray, gid: jnp.ndarray,
                n_groups: int, m: int = 1024) -> jnp.ndarray:
    """Per-group HLL register ROWS as the mergeable partial state: one
    (n_groups, m) uint8 matrix built by a single segment_max.  Unlike
    hll_registers_and_estimate this never shrinks m — the state's shape
    is part of its TYPE (types.hll_state(m)) and must agree across
    chunks/shards so partials fold with elementwise max."""
    log2m = int(np.log2(m))
    reg = (h & jnp.uint64(m - 1)).astype(jnp.int64)
    w = ((h >> jnp.uint64(log2m)) & jnp.uint64(0xFFFFFFFF)).astype(jnp.float64)
    rho = jnp.where(w > 0, 32.0 - jnp.floor(jnp.log2(jnp.maximum(w, 1.0))),
                    33.0)
    seg = gid * m + reg
    seg = jnp.where(valid, seg, n_groups * m)  # dead rows -> overflow slot
    M = jax.ops.segment_max(
        jnp.where(valid, rho, 0.0), seg, num_segments=n_groups * m + 1,
    )[:-1].reshape(n_groups, m)
    return jnp.maximum(M, 0.0).astype(jnp.uint8)


def hll_merge(regs: jnp.ndarray, valid, gid: jnp.ndarray,
              n_groups: int) -> jnp.ndarray:
    """Fold partial register rows per group — HLL union IS elementwise
    max, so a 2-D segment_max over the row axis merges any number of
    partial sketches exactly (order- and partition-independent)."""
    g = gid if valid is None else jnp.where(valid, gid, n_groups)
    M = jax.ops.segment_max(regs.astype(jnp.int32), g,
                            num_segments=n_groups + 1)[:n_groups]
    return jnp.maximum(M, 0).astype(jnp.uint8)


def hll_merge_estimate(regs: jnp.ndarray, valid, gid: jnp.ndarray,
                       n_groups: int) -> jnp.ndarray:
    """Final aggregate over partial HLL states: merge rows per group,
    then estimate.  Estimates are bit-identical to the single-pass
    kernel at equal m because max is associative over the same rho set."""
    return hll_estimate(hll_merge(regs, valid, gid, n_groups))


def kll_partial(x: jnp.ndarray, valid: jnp.ndarray, gid: jnp.ndarray,
                n_groups: int, K: int) -> jnp.ndarray:
    """Fixed-shape per-group quantile summary (KLL-style single
    compactor level): K evenly-spaced order statistics + their integer
    weights, concatenated into a (n_groups, 2K) float64 state row.  One
    global (group, value) lexsort builds every group's summary; weight
    w_j = floor((j+1)*cnt/K) - floor(j*cnt/K) telescopes to exactly cnt,
    so merged rank queries stay within ~1/K of truth per merge level."""
    n = x.shape[0]
    if n == 0:
        return jnp.zeros((n_groups, 2 * K), jnp.float64)
    xf = jnp.where(valid, x.astype(jnp.float64), jnp.inf)
    g = jnp.where(valid, gid, n_groups)       # invalid rows: dead group
    order = jnp.lexsort((xf, g))
    cnt = jax.ops.segment_sum(valid.astype(jnp.int64), gid,
                              num_segments=n_groups + 1)[:n_groups]
    starts = jnp.cumsum(cnt) - cnt
    cf = cnt.astype(jnp.float64)[:, None]
    j = jnp.arange(K, dtype=jnp.float64)[None, :]
    # j-th summary value = the floor((j+0.5)*cnt/K)-th smallest of the
    # group (midpoint rule keeps both tails represented)
    r = jnp.floor((j + 0.5) * cf / K).astype(jnp.int64)
    r = jnp.clip(r, 0, jnp.maximum(cnt - 1, 0)[:, None])
    pos = jnp.clip(starts[:, None] + r, 0, n - 1)
    vals = xf[order][pos]
    wts = jnp.floor((j + 1.0) * cf / K) - jnp.floor(j * cf / K)
    vals = jnp.where(wts > 0, vals, 0.0)  # empty groups gather junk
    return jnp.concatenate([vals, wts], axis=1)


def kll_percentile(state: jnp.ndarray, valid, gid: jnp.ndarray,
                   n_groups: int, p: float, K: int) -> tuple:
    """Final aggregate over partial KLL states: flatten every state
    row's (value, weight) pairs, lexsort by (group, value), and read the
    first value whose within-group cumulative weight reaches the target
    rank floor(p*(N-1))+1.  Zero-weight entries can never win: their
    cumulative weight equals the previous positive entry's, which sits
    earlier in sort order.  Returns (values, nonempty)."""
    n = state.shape[0]
    if n == 0:
        return (jnp.zeros((n_groups,), jnp.float64),
                jnp.zeros((n_groups,), jnp.bool_))
    vals, wts = state[:, :K], state[:, K:]
    ok = jnp.ones((n,), jnp.bool_) if valid is None else valid
    g_flat = jnp.repeat(jnp.where(ok, gid, n_groups), K)
    v_flat = vals.reshape(-1)
    w_flat = jnp.where(ok[:, None], wts, 0.0).reshape(-1)
    order = jnp.lexsort((v_flat, g_flat))
    vs, ws, gs = v_flat[order], w_flat[order], g_flat[order]
    totw = jax.ops.segment_sum(ws, gs, num_segments=n_groups + 1)[:n_groups]
    offs = jnp.cumsum(totw) - totw            # weight of earlier groups
    cumw = jnp.cumsum(ws)                     # global prefix (dead group last)
    g_safe = jnp.minimum(gs, n_groups - 1)
    t = jnp.clip(jnp.floor(p * jnp.maximum(totw - 1, 0)) + 1.0, 1.0,
                 jnp.maximum(totw, 1.0))
    cand = (cumw - offs[g_safe] >= t[g_safe]) & (gs < n_groups)
    idx = jnp.where(cand, jnp.arange(vs.shape[0]), vs.shape[0])
    first = jax.ops.segment_min(idx, gs, num_segments=n_groups + 1)[:n_groups]
    out = vs[jnp.clip(first, 0, vs.shape[0] - 1)]
    return out, totw > 0


def sketch_sample_mask(h: jnp.ndarray) -> jnp.ndarray:
    """Deterministic 1-in-8 value sample for COUNT/SUM ... WITH ERROR:
    keep rows whose value hash lands in one of 8 residue classes.  The
    kept fraction is exactly 1/8 of DISTINCT hash space, so the x8
    scale-up is an exact power-of-two multiply and every execution mode
    (single, chunked, sharded) samples the SAME rows — estimates are
    bit-identical regardless of partitioning."""
    return (h & jnp.uint64(7)) == jnp.uint64(0)


def group_percentile(x: jnp.ndarray, valid: jnp.ndarray, gid: jnp.ndarray,
                     n_groups: int, p) -> tuple:
    """Per-group percentile by global sort — the TPU replacement for
    per-group quantile-digest accumulators (reference: approx_percentile
    over QuantileDigest): sort all rows by (group, value) once, then
    gather each group's p-th position.  Returns (values, nonempty)."""
    cnt = jax.ops.segment_sum(valid.astype(jnp.int64), gid,
                              num_segments=n_groups)
    xf = x.astype(jnp.float64)
    xf = jnp.where(valid, xf, jnp.inf)        # invalid rows sort last
    g = jnp.where(valid, gid, n_groups)       # ...and into a dead group
    order = jnp.lexsort((xf, g))
    starts = jnp.cumsum(cnt) - cnt
    k = jnp.clip(jnp.floor(p * jnp.maximum(cnt - 1, 0).astype(jnp.float64))
                 .astype(jnp.int64), 0, jnp.maximum(cnt - 1, 0))
    pos = jnp.clip(starts + k, 0, x.shape[0] - 1)
    vals = x[order[pos]]
    return vals, cnt > 0


@NM.scoped("k:build_probe")
def build_probe(build_key: jnp.ndarray, probe_key: jnp.ndarray,
                build_order=None):
    """Sort build side; position every probe key among the build keys.
    Returns (order, lb, ub): build_key[order] sorted; matches for probe row
    i are order[lb[i]:ub[i]].

    One composite lax.sort of (key, side-flag) + prefix scans replaces two
    searchsorted(method='sort') calls: each of those hides a full-size
    permutation SCATTER, which serializes on TPU (~600ms per 7M rows,
    measured) — the scan+gather formulation costs three sorts and no
    scatter, ~3x faster end-to-end on the join-heavy TPC-H queries.

    `build_order` elides the build argsort (1 of the 3 sorts): a
    memoized permutation of this exact key, or an identity arange when
    the build side is already fully nondecreasing (sentinels in a
    suffix — callers verify via monotone_guard; equal-key order within
    a run is free, matches are consumed as a set)."""
    nb = build_key.shape[0]
    npr = probe_key.shape[0]
    order = build_order if build_order is not None \
        else sort_pair(build_key)[1]
    n = nb + npr
    allk = jnp.concatenate([build_key, probe_key])
    flag = jnp.concatenate([jnp.zeros((nb,), jnp.int32),
                            jnp.ones((npr,), jnp.int32)])
    sk, sf, sidx = jax.lax.sort(
        (allk, flag, jnp.arange(n, dtype=jnp.int32)), num_keys=2)
    is_build = (sf == 0).astype(jnp.int32)
    before = jnp.cumsum(is_build) - is_build  # builds strictly before pos
    # first position of each equal-key run via a running maximum
    pos = jnp.arange(n, dtype=jnp.int32)
    newrun = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    run_start = jax.lax.cummax(jnp.where(newrun, pos, jnp.int32(-1)))
    # builds sort before probes within a run, so at a probe's position:
    #   lb = builds before its run (key <  probe key)
    #   ub = builds before itself  (key <= probe key)
    lb_at = before[jnp.clip(run_start, 0, n - 1)]
    # co-sort keyed on the permutation carries lb/ub home without the
    # two full-size inverse-perm gathers (see unpermute)
    lb_all, ub_all = unpermute(sidx, lb_at, before)
    lb = lb_all[nb:]
    ub = ub_all[nb:]
    # sentinel keys (masked build rows) must not match masked probe rows
    live = probe_key != key_sentinel(probe_key)
    lb = jnp.where(live, lb, 0)
    ub = jnp.where(live, ub, 0)
    return order, lb, ub


@NM.scoped("k:sort")
def sort_order_plan(idx: jnp.ndarray, *aligned):
    """Pre-permute a gather's request-aligned operands into ASCENDING
    index order — the sort-order materialization primitive (reference
    role: PagesIndex.getSortedPages).  Returns (sorted_idx,
    [aligned...]) permuted by ONE lax.sort; callers then gather with
    presorted=True and simply leave the batch in sorted order, skipping
    the inverse permutation entirely.  Only valid when every downstream
    consumer is order-insensitive (aggregation, semi-join membership) —
    the executor's order-insensitivity walk decides that."""
    ii = jnp.asarray(idx).astype(jnp.int32)
    ops = [ii]
    bools = []
    for a in aligned:
        a = jnp.asarray(a)
        bools.append(a.dtype == jnp.bool_)
        ops.append(a.astype(jnp.int32) if a.dtype == jnp.bool_ else a)
    out = jax.lax.sort(tuple(ops), num_keys=1)
    rest = [o.astype(jnp.bool_) if b else o
            for o, b in zip(out[1:], bools)]
    return out[0], rest


def batch_word_width(batch: Batch) -> int:
    """u32 words one gathered row of this batch costs (the take_rows
    pack width): sizes the sort-order-materialization side choice."""
    w = 0
    for c in batch.columns.values():
        w += 2 if c.data.dtype.itemsize == 8 else 1
        if c.valid is not None:
            w += 1
    return w


def take_rows(arrays: List[jnp.ndarray], idx: jnp.ndarray,
              presorted: bool = False) -> List[jnp.ndarray]:
    """Gather idx rows from every array, packing columns into one u32
    matrix so ONE gather moves them all.  TPU gathers pay a fixed
    per-index cost (~45ms per 6M f32 rows, measured) that amortizes
    across the row width: gathering a (6M,8) matrix costs ~1/7th of 8
    separate column gathers.  All 4-byte types bitcast to u32; bools
    widen; i64 splits into two u32 words; f64 stays separate (the TPU
    X64 rewriter cannot lower f64 bitcasts).

    Large gathers route through the gather-aware tier (exec/gather.py):
    indices are sorted, rows are gathered in ascending order, and
    results ride ONE co-sort back to request order.  `presorted=True`
    asserts idx is already nondecreasing (ascending expansions,
    sort_order_plan output): the staging then skips both the sort and
    the way home."""
    if arrays and arrays[0].shape[0] == 0 and idx.shape[0] > 0:
        # gathering from an EMPTY source (e.g. a zero-row exchange
        # buffer): every index is dead and the caller masks the result —
        # type-correct zeros avoid an out-of-range XLA gather
        return [jnp.zeros((idx.shape[0],) + a.shape[1:], a.dtype)
                for a in arrays]
    words: List[jnp.ndarray] = []    # u32 columns going into the pack
    spec: List = [None] * len(arrays)  # how to rebuild each output
    out: List = [None] * len(arrays)
    for i, a in enumerate(arrays):
        dt = a.dtype
        if a.ndim > 1:
            # matrix-shaped rows (sketch register states): whole-row
            # gather — the u32 pack is strictly rank-1 per word
            spec[i] = ("direct", None)
        elif dt == jnp.bool_:
            spec[i] = ("bool", len(words))
            words.append(a.astype(jnp.uint32))
        elif jnp.issubdtype(dt, jnp.floating) and dt.itemsize == 8:
            spec[i] = ("direct", None)
        elif dt.itemsize == 8:
            spec[i] = ("i64", len(words))
            m = jnp.asarray(0xFFFFFFFF, dt)  # dtype-matched (u64 vs i64)
            words.append((a & m).astype(jnp.uint32))
            words.append(((a >> 32) & m).astype(jnp.uint32))
        elif dt.itemsize == 4:
            spec[i] = ("cast", len(words))
            words.append(jax.lax.bitcast_convert_type(a, jnp.uint32))
        else:
            spec[i] = ("widen", len(words))
            words.append(jax.lax.bitcast_convert_type(
                a.astype(jnp.int32), jnp.uint32))
    n_src = arrays[0].shape[0] if arrays else 0
    route = G.gather_route(n_src, idx.shape[0], len(words), presorted)
    if route == "staged" and all(w.ndim == 1 for w in words) \
            and all(a.ndim == 1 for a in arrays):
        # 2-D words (Int128 limb columns) keep the flat path — the u32
        # matrix pack is rank-1-per-word on both routes
        return _take_rows_staged(arrays, idx, words, spec, presorted)
    # pack from TWO words up: the gather's per-index cost amortizes
    # across row width (measured: two separate 8M 1-col gathers 140ms
    # vs one (8M,2) packed gather 35-50ms on chip), so a single i64
    # column (= 2 u32 words) already wins
    with NM.kernel_scope("k:take_rows.flat"):
        if len(words) >= 2 and idx.shape[0] > 2 * G.PACKED_BLOCK:
            taken = _packed_gather_in_blocks(words, idx)
            col = lambda k: taken[k]
        elif len(words) >= 2 and idx.shape[0] >= 65536:
            packed = jnp.stack(words, axis=1)[idx]
            col = lambda k: packed[:, k]
        else:
            taken = [w[idx] for w in words]
            col = lambda k: taken[k]
        return _rebuild_taken(arrays, idx, spec, col, out)


def _packed_gather_in_blocks(words: List[jnp.ndarray],
                             idx: jnp.ndarray) -> List[jnp.ndarray]:
    """`jnp.stack(words, 1)[idx]`, column by column, G.PACKED_BLOCK
    indices at a time: the gathered (m, w) rows exist one block at a
    time (the TPU pads each to 128 lanes: exec/gather.py), the columns
    come out one-dimensional."""
    mat = jnp.stack(words, axis=1)
    m, block = idx.shape[0], G.PACKED_BLOCK
    nblocks = -(-m // block)
    blocks = jnp.pad(idx, (0, nblocks * block - m)).reshape(nblocks, block)
    cols = jax.lax.map(
        lambda b: tuple(mat[b][:, k] for k in range(len(words))), blocks)
    return [c.reshape(-1)[:m] for c in cols]


@NM.scoped("k:take_rows.staged")
def _take_rows_staged(arrays, idx, words, spec, presorted):
    """Sorted-index staging: the ascending gather (exec/gather.
    staged_gather), then (for request-order callers) ONE co-sort
    keyed on the saved positions carries every word — and the f64
    side columns — home together.  Payload operands ride a lax.sort
    nearly free; the inverse-permutation GATHER this replaces paid the
    full ~45ns/index random cost a second time."""
    out: List = [None] * len(arrays)
    ii = jnp.asarray(idx).astype(jnp.int32)
    if presorted:
        sidx, spos = ii, None
    else:
        n = ii.shape[0]
        sidx, spos = jax.lax.sort(
            (ii, jnp.arange(n, dtype=jnp.int32)), num_keys=1)
    cols = []
    for part in G.staged_word_groups(words[0].shape[0], len(words)):
        rows = G.staged_gather(jnp.stack(words[part], axis=1), sidx)
        cols += [rows[:, k] for k in range(rows.shape[1])]
    directs = {i: arrays[i][sidx] for i, a in enumerate(arrays)
               if spec[i][0] == "direct"}
    if spos is not None:
        home = unpermute(spos, *(cols + list(directs.values())))
        cols = list(home[:len(cols)])
        directs = dict(zip(directs, home[len(cols):]))
    col = lambda k: cols[k]
    for i, a in enumerate(arrays):
        if spec[i][0] == "direct":
            out[i] = directs[i]
    return _rebuild_taken(arrays, idx, spec, col, out, skip_direct=True)


def _rebuild_taken(arrays, idx, spec, col, out, skip_direct=False):
    for i, a in enumerate(arrays):
        kind, k = spec[i]
        dt = a.dtype
        if kind == "direct":
            if not skip_direct:
                out[i] = a[idx]
        elif kind == "bool":
            out[i] = col(k) != 0
        elif kind == "i64":
            lo = col(k).astype(jnp.int64)
            hi = jax.lax.bitcast_convert_type(col(k + 1),
                                              jnp.int32).astype(jnp.int64)
            out[i] = ((hi << 32) | lo).astype(dt)
        elif kind == "cast":
            out[i] = jax.lax.bitcast_convert_type(col(k), dt)
        else:  # widen
            out[i] = jax.lax.bitcast_convert_type(
                col(k), jnp.int32).astype(dt)
    return out


def take_columns(columns: Dict[str, Column], idx: jnp.ndarray,
                 extra: Optional[List[jnp.ndarray]] = None,
                 presorted: bool = False):
    """Gather idx rows of (data, valid) for every column in one packed
    take_rows pass.  Returns ({name: (data, valid)}, [extra results]).
    `extra` arrays ride the same pack."""
    arrays = list(extra or [])
    n_extra = len(arrays)
    for c in columns.values():
        arrays.append(c.data)
        if c.valid is not None:
            arrays.append(c.valid)
    taken = take_rows(arrays, idx, presorted=presorted)
    out = {}
    i = n_extra
    for name, c in columns.items():
        data = taken[i]
        i += 1
        valid = None
        if c.valid is not None:
            valid = taken[i]
            i += 1
        out[name] = (data, valid)
    return out, taken[:n_extra]


def _take_batch(batch: Batch, safe: jnp.ndarray, presorted: bool = False):
    """Gather rows of all of a batch's arrays (data+valid+sel) at safe
    (pre-clipped) indices with dtype-packed gathers."""
    raw, (sel,) = take_columns(batch.columns, safe, extra=[batch.sel],
                               presorted=presorted)
    cols = {name: (data, valid, batch.columns[name].type,
                   batch.columns[name].dictionary)
            for name, (data, valid) in raw.items()}
    return cols, sel


def gather_batch(batch: Batch, idx: jnp.ndarray, idx_valid=None,
                 presorted: bool = False) -> Batch:
    """Gather rows of all columns at idx (clipped); optionally mask.
    presorted=True asserts idx is nondecreasing (ascending expansions,
    sort_order_plan output) so large gathers stage sequentially without
    paying the way back to request order — idx_valid, if given, must
    already be in the same (sorted) order."""
    n = batch.capacity
    safe = jnp.clip(idx, 0, max(n - 1, 0))
    raw, sel = _take_batch(batch, safe, presorted=presorted)
    cols = {}
    for name, (data, valid, typ, dic) in raw.items():
        if idx_valid is not None:
            valid = idx_valid if valid is None else (valid & idx_valid)
        cols[name] = Column(data, valid, typ, dic)
    if idx_valid is not None:
        sel = sel & idx_valid
    return Batch(cols, sel)


def pack_fetch(batch: Batch, guard) -> Tuple[jnp.ndarray, dict]:
    """Flatten a result batch (+ guard scalar) into ONE uint32 buffer so
    the host pulls a single array: every array in a fetched pytree is
    its own device-to-host transfer, so a 12-column result fetched
    column-wise pays a dozen of them (cost on a directly attached chip:
    not measured on this tree).  Returns (buffer, meta); unpack_fetch
    inverts on host.
    Must be called under trace (jit) — meta is static."""
    n = batch.capacity
    parts = [jnp.asarray(batch.sel).astype(jnp.uint32)]
    side = []  # f64 columns ride as separate pytree leaves (one RPC still)
    cols_meta = []
    for name, c in batch.columns.items():
        d = c.data
        if jnp.issubdtype(d.dtype, jnp.floating) and d.dtype.itemsize == 8:
            # the TPU X64 rewriter cannot lower any f64 bitcast, so f64
            # can't enter the u32 buffer; it rides as a separate leaf
            # of the same fetch
            side.append(d)
            w, words = None, 0
        elif d.dtype == jnp.bool_:
            w, words = d.astype(jnp.uint32), 1
        elif d.dtype.itemsize == 8:
            # i64 -> 2x32 via shifts/masks (64->32 bitcast unsupported)
            lo = (d & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
            hi = ((d >> 32) & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
            w = jnp.stack([lo, hi], axis=1).reshape(-1)
            words = 2
        elif d.dtype.itemsize == 4:
            w, words = jax.lax.bitcast_convert_type(d, jnp.uint32), 1
        else:  # narrow ints: widen (host casts back)
            w = jax.lax.bitcast_convert_type(d.astype(jnp.int32), jnp.uint32)
            words = 1
        if w is not None:
            parts.append(w)
        if c.valid is not None:
            parts.append(c.valid.astype(jnp.uint32))
        cols_meta.append((name, str(d.dtype), words, c.valid is not None,
                          c.type, c.dictionary))
    parts.append(jnp.asarray(guard).astype(jnp.uint32).reshape(1))
    meta = {"n": n, "cols": cols_meta}
    return (jnp.concatenate(parts), side), meta


def unpack_fetch(fetched, meta: dict):
    """Host-side inverse of pack_fetch: returns ({name: (data, valid)},
    sel, guard) as numpy arrays."""
    buf, side = fetched
    n = meta["n"]
    buf = np.asarray(buf)
    side = [np.asarray(a) for a in side]
    si = 0
    sel = buf[:n] != 0
    off = n
    datas = {}
    for name, dtype_s, words, has_valid, _typ, _dic in meta["cols"]:
        dt = np.dtype(dtype_s)
        if words == 0:  # f64 side leaf
            data = side[si]
            si += 1
        else:
            raw = buf[off:off + n * words]
            off += n * words
            if dt == np.bool_:
                data = raw != 0
            elif words == 2:
                lo = raw.reshape(n, 2)[:, 0].astype(np.uint64)
                hi = raw.reshape(n, 2)[:, 1].astype(np.uint64)
                data = (lo | (hi << np.uint64(32))).view(np.int64) \
                    if dt == np.int64 else \
                    (lo | (hi << np.uint64(32))).astype(dt)
            elif dt.itemsize == 4:
                data = raw.view(dt)
            else:
                data = raw.view(np.int32).astype(dt)
        valid = None
        if has_valid:
            valid = buf[off:off + n] != 0
            off += n
        datas[name] = (data, valid)
    guard = bool(buf[off]) if off < len(buf) else False
    return datas, sel, guard


@NM.scoped("k:compact")
def compact(batch: Batch) -> Batch:
    """Drop masked rows (host-sync on the live count). Used at fragment
    boundaries (exchange points), not inside fragments."""
    n_live = int(jnp.sum(batch.sel))
    idx = nonzero_i32(batch.sel, max(n_live, 1), 0)
    if n_live == 0:
        idx = idx[:0]
    raw, _ = _take_batch(batch, idx)
    cols = {name: Column(data, valid, typ, dic)
            for name, (data, valid, typ, dic) in raw.items()}
    return Batch(cols, jnp.ones((n_live,), bool))


def concat_batches(batches: List[Batch]) -> Batch:
    """Concatenate same-schema batches (dictionary columns are merged)."""
    names = list(batches[0].columns)
    cols: Dict[str, Column] = {}
    for name in names:
        parts = [b.columns[name] for b in batches]
        dicts = [p.dictionary for p in parts]
        with_dict = [d for d in dicts if d is not None]
        if with_dict and (len({id(d) for d in with_dict}) > 1
                          or len(with_dict) < len(parts)):
            # branches without a dictionary are typed-NULL columns
            # (e.g. grouping-set padding): their codes are dead, any
            # in-range value serves
            all_vals = [v for d in with_dict for v in d.values.tolist()]
            if all(isinstance(v, str) for v in all_vals):
                # strings keep the np-sorted invariant (code order ==
                # lexicographic order, which comparisons rely on)
                merged = Dictionary(np.unique(np.concatenate(
                    [d.values for d in with_dict])))
                luts = {id(d): translate_codes(d, merged)
                        for d in with_dict}
            else:
                # tuple dictionaries (ARRAY columns, possibly holding
                # NULL elements): python-map merge, repr-keyed order
                # (array code order is not semantically compared)
                uniq = sorted(set(all_vals), key=repr)
                cmap = {v: i for i, v in enumerate(uniq)}
                u = np.empty(len(uniq), dtype=object)
                u[:] = uniq
                merged = Dictionary(u)
                luts = {id(d): np.asarray(
                    [cmap[v] for v in d.values.tolist()], dtype=np.int32)
                    for d in with_dict}
            datas = []
            for p in parts:
                if p.dictionary is None:
                    datas.append(jnp.zeros_like(jnp.asarray(p.data),
                                                dtype=jnp.int32))
                    continue
                lut = jnp.asarray(luts[id(p.dictionary)])
                datas.append(lut[jnp.clip(p.data, 0, len(p.dictionary) - 1)])
            data = jnp.concatenate(datas)
            dictionary = merged
        else:
            data = jnp.concatenate([p.data for p in parts])
            dictionary = dicts[0]
        if any(p.valid is not None for p in parts):
            valid = jnp.concatenate([
                p.valid if p.valid is not None else jnp.ones(p.data.shape, bool)
                for p in parts])
        else:
            valid = None
        cols[name] = Column(data, valid, parts[0].type, dictionary)
    sel = jnp.concatenate([b.sel for b in batches])
    return Batch(cols, sel)


# ---------------------------------------------------------------------------
# runtime filters (dynamic filtering)
#
# Build-side key summaries probed on the probe side BEFORE the join ever
# sees the rows (reference: DynamicFilterService + LocalDynamicFiltersCollector
# feeding TupleDomains into probe-side page sources).  Two membership
# structures, routed by build capacity:
#   exact  — the sorted build keys themselves + a searchsorted probe
#            (no false positives; masked rows ride as trailing sentinels)
#   bloom  — a blocked bloom bitset over splitmix64-mixed keys (bits set
#            within one 64-bit block per key; false positives possible,
#            false negatives never — the correctness contract)
# Everything is pure jnp so a filter built inside a compiled fragment
# stays inside the trace.  Host (numpy) twins serve the cluster side
# channel and chunk/zone-map pruning; this module is the ONLY home for
# the membership mixing (tests/test_lint.py enforces).
# ---------------------------------------------------------------------------


RF_EXACT_MAX = 1 << 17   # build capacities up to this probe exactly
RF_BLOOM_K = 3           # bits set/tested per key
RF_BLOOM_BITS_PER_KEY = 16  # target bitset density (m/n); FPR ~ 0.5%
RF_WIRE_MAX = 1 << 16    # largest exact key set shipped over the wire


def rf_bloom_bits(n_keys: int) -> int:
    """Bloom bitset size for n keys: ~RF_BLOOM_BITS_PER_KEY bits per
    key, power-of-two (block index = h % nblocks needs no division by a
    traced value), floor 1024.  FPR ~ (1 - e^(-k*n/m))^k ~ 0.5% at
    k=3, m/n=16 — tests/test_dynamic_filters.py pins the measured rate."""
    n = max(int(n_keys), 1)
    return 1 << max(int(np.ceil(np.log2(n * RF_BLOOM_BITS_PER_KEY))), 10)


def _rf_mix64(v: jnp.ndarray) -> jnp.ndarray:
    """splitmix64 finalizer over int64 key values (the same mixing
    family as _hash_keys / hll_hash64), uint64 out."""
    z = v.astype(jnp.uint64) + jnp.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return z ^ (z >> jnp.uint64(31))


def _rf_bloom_positions(h: jnp.ndarray, nbits: int):
    """RF_BLOOM_K bit positions per hash, all inside ONE 64-bit block
    (blocked bloom: the probe's k gathers hit one cache line)."""
    nblocks = max(nbits // 64, 1)
    block = (h % jnp.uint64(nblocks)).astype(jnp.int64) * 64
    return [block + ((h >> jnp.uint64(8 + 6 * j)) & jnp.uint64(63))
            .astype(jnp.int64) for j in range(RF_BLOOM_K)]


@NM.scoped("k:runtime_filter")
def rf_build(col: Column, live, structure: str = "auto") -> dict:
    """Build-side runtime-filter summary over the live rows of an
    integer-orderable key column.  Returns an all-jnp dict (trace-safe):
    {"kind": "exact", "keys": sorted i64 with dead rows as I64_MAX
    sentinels} or {"kind": "bloom", "bits": bool[nbits]}."""
    d = _orderable_int(col)
    live = live & _valid_arr(col)
    n = int(d.shape[0])
    kind = structure
    if kind == "auto":
        kind = "exact" if n <= RF_EXACT_MAX else "bloom"
    if kind == "exact":
        return {"kind": "exact",
                "keys": sort_values(jnp.where(live, d, I64_MAX))}
    nbits = rf_bloom_bits(n)
    h = _rf_mix64(d)
    # dead rows scatter into the overflow slot nbits (sliced off)
    idx = jnp.concatenate([jnp.where(live, p, nbits)
                           for p in _rf_bloom_positions(h, nbits)])
    bits = jnp.zeros((nbits + 1,), bool).at[idx].set(True)
    return {"kind": "bloom", "bits": bits[:nbits]}


@NM.scoped("k:runtime_filter")
def rf_probe(summary: dict, col: Column) -> jnp.ndarray:
    """Probe-side membership mask: True = the row MAY have a build match
    (exact/domain: iff; bloom: false positives possible, false negatives
    never).  NULL probe rows map False — an equi-join NULL never
    matches, so pruning them is always sound for INNER/SEMI consumers."""
    d = _orderable_int(col)
    valid = _valid_arr(col)
    kind = summary["kind"]
    if kind == "domain":
        return valid & (d >= summary["lo"]) & (d <= summary["hi"])
    if kind == "exact":
        keys = summary["keys"]
        nb = keys.shape[0]
        if nb == 0:
            return jnp.zeros(d.shape, bool)  # empty build: nothing matches
        pos = jnp.clip(jnp.searchsorted(keys, d), 0, nb - 1)
        # a probe value equal to the dead-row sentinel could only
        # "match" a masked build slot — keep it (false positive, safe)
        return valid & (keys[pos] == d)
    bits = summary["bits"]
    h = _rf_mix64(d)
    m = valid
    for p in _rf_bloom_positions(h, int(bits.shape[0])):
        m = m & bits[p]
    return m


@NM.scoped("k:runtime_filter")
def rf_domain(col: Column, live):
    """(lo, hi) traced min/max of the live key values — the runtime
    TupleDomain half of the filter.  Empty live set -> (I64_MAX,
    I64_MIN), which callers map to an impossible Domain."""
    d = _orderable_int(col)
    live = live & _valid_arr(col)
    if d.shape[0] == 0:
        return jnp.asarray(I64_MAX), jnp.asarray(I64_MIN)
    return (jnp.min(jnp.where(live, d, I64_MAX)),
            jnp.max(jnp.where(live, d, I64_MIN)))


def rf_summary_host(values: np.ndarray, max_exact: int = RF_WIRE_MAX) -> dict:
    """Host-side summary from live build key VALUES (integers): the wire
    form shipped over the cluster side channel and compared against
    shard zone maps / chunk grids.  {"lo", "hi", "vals": sorted-unique
    list, or None when the set is too large to ship exactly}."""
    v = np.asarray(values).astype(np.int64, copy=False)
    if v.size == 0:
        return {"lo": None, "hi": None, "vals": []}  # impossible domain
    uniq = np.unique(v)
    return {"lo": int(uniq[0]), "hi": int(uniq[-1]),
            "vals": [int(x) for x in uniq] if uniq.size <= max_exact
            else None}


def rf_union_host(parts: list) -> Optional[dict]:
    """Union partial host summaries (one per repartition bucket of the
    build side) into one complete summary — every build row lands in
    exactly one bucket, so the union over all buckets IS the build key
    set.  Any part without an exact value list degrades the union to a
    min/max domain; returns None for no parts."""
    if not parts:
        return None
    los = [p["lo"] for p in parts if p.get("lo") is not None]
    his = [p["hi"] for p in parts if p.get("hi") is not None]
    if not los:
        return {"lo": None, "hi": None, "vals": []}
    lo, hi = min(los), max(his)
    if any(p.get("vals") is None for p in parts):
        return {"lo": lo, "hi": hi, "vals": None}
    vals = sorted({v for p in parts for v in p["vals"]})
    if len(vals) > RF_WIRE_MAX:
        return {"lo": lo, "hi": hi, "vals": None}
    return {"lo": lo, "hi": hi, "vals": vals}


def rf_host_to_device(summary: dict) -> Optional[dict]:
    """Lift a wire/host summary into a probe-able device summary."""
    vals = summary.get("vals")
    if vals is not None:
        return {"kind": "exact",
                "keys": jnp.asarray(np.asarray(vals, dtype=np.int64))}
    if summary.get("lo") is None:
        return {"kind": "exact", "keys": jnp.zeros((0,), jnp.int64)}
    return {"kind": "domain", "lo": jnp.int64(summary["lo"]),
            "hi": jnp.int64(summary["hi"])}


# ---------------------------------------------------------------------------
# sort
# ---------------------------------------------------------------------------


@NM.scoped("k:sort")
def sort_perm(batch: Batch, keys: List[Tuple[Column, bool, Optional[bool]]]):
    """Lexicographic permutation; masked rows last.
    keys: (column, ascending, nulls_first). Default null order matches the
    reference (NULLS LAST for ASC, NULLS FIRST for DESC —
    presto-parser SortItem.NullOrdering defaults)."""
    n = batch.capacity
    # ONE multi-operand lexicographic lax.sort: masked-rows-last is the
    # primary key, then the sort keys in priority order, then a position
    # tiebreak for stability.  Extra sort-key operands are nearly free on
    # TPU, while the per-key argsort+gather chain this replaces paid a
    # full-size gather per key (~43ms per 6M rows each, measured).
    operands = [(~jnp.asarray(batch.sel)).astype(jnp.int32)]
    for col, asc, nulls_first in keys:
        valid = col.valid if col.valid is not None else \
            jnp.ones(col.data.shape[0], bool)  # 1-D even for limb pairs
        nf = (not asc) if nulls_first is None else nulls_first
        # a dedicated null-flag operand per key instead of in-band
        # sentinels: sentinel values can collide with real data at the
        # dtype extremes (int32 MIN under DESC negation), and extra
        # lexicographic operands are nearly free on TPU
        if col.valid is not None:
            operands.append(jnp.where(valid, jnp.int32(0 if not nf else 1),
                                      jnp.int32(1 if not nf else 0)))
        if getattr(col.data, "ndim", 1) == 2:
            # long decimal (Int128 limbs): two lexicographic operands
            # (reference: Int128ArrayBlock comparison is hi-then-lo)
            from presto_tpu.exec import dec128 as D128

            for d in D128.sort_operands(jnp.asarray(col.data)):
                if not asc:
                    # bitwise NOT is an exact order-reversing bijection
                    # on int64 (negation maps both I64_MIN and
                    # I64_MIN+1 to I64_MAX: low-limb ties would
                    # misorder DESC)
                    d = ~d
                operands.append(jnp.where(valid, d, 0))
            continue
        d = _sort_operand_native(col)
        if not asc:
            d = ~d  # order-reversing bijection; negation wraps the min
        operands.append(jnp.where(valid, d, jnp.zeros((), d.dtype)))
    operands.append(jnp.arange(n, dtype=jnp.int32))
    out = jax.lax.sort(tuple(operands), num_keys=len(operands))
    return out[-1]


def _sort_operand_native(col: Column) -> jnp.ndarray:
    """Orderable integer in the NARROWEST dtype that preserves order:
    int32 stays int32 and float32 maps onto int32 with ONE bitcast —
    i64 sort operands run u32-pair emulated on TPU (~1.5x), so keeping
    Q3-class sort keys (f32 revenue, i32 dates) in i32 roughly halves
    the multi-operand sort cost."""
    d = col.data
    if d.dtype == jnp.bool_:
        return d.astype(jnp.int32)
    if d.dtype == jnp.float32 and jax.default_backend() == "tpu":
        b = jax.lax.bitcast_convert_type(d, jnp.int32)
        key = jnp.where(b < 0, (~b) + jnp.int32(-(1 << 31)), b)
        key = jnp.where(d == 0, 0, key)  # +-0 compare equal in SQL
        # NaN sorts largest (Presto order) REGARDLESS of its sign bit —
        # a negative-bit NaN (0xFFC.., preserved verbatim from file
        # data) would otherwise land below -inf
        return jnp.where(jnp.isnan(d), jnp.int32((1 << 31) - 8), key)
    if jnp.issubdtype(d.dtype, jnp.floating):
        return _orderable_int(col)
    if d.dtype in (jnp.int32, jnp.int16, jnp.int8):
        return d.astype(jnp.int32)
    return d.astype(jnp.int64)


@NM.scoped("k:sort")
def argsort_stable(key: jnp.ndarray) -> jnp.ndarray:
    """Stable argsort (equal keys keep input order) — routed entry point
    for the write path's page order (write_sort_perm)."""
    return jnp.argsort(key, stable=True)


#: 1-D operands one carrying sort takes (a 64-bit column is two on the
#: TPU); a wider batch sorts in groups under the same keys.  The v5e
#: compiler's time grows faster than the width: 151 s with 8 operands,
#: 345 s with 16, over 3400 s with 40 (PERF.md section 7, PR 30)
SORT_CARRY_WIDTH = 16


def sort_carrying(keys, payloads):
    """Stable sort by `keys` (keys[0] primary; equal keys keep input
    order) that carries `payloads` along as operands: payload p, of any
    trailing shape, comes back as `p[order]` without a full-size gather
    per array — routed entry point for the exchange layer's send layout.
    More than SORT_CARRY_WIDTH operands sort in groups under the same
    keys (a stable sort by the same keys is the same permutation every
    time).  Returns (sorted keys, sorted payloads).  Like `unpermute` it
    opens no scope: its time is the caller's (`x:repartition`,
    `x:range_partition`)."""
    keys, nk = tuple(keys), len(keys)
    # 1-D sort operands: a long decimal's limbs ride one each
    flat = [p.reshape(p.shape[0], -1) for p in payloads]
    operands = [f[:, j] for f in flat for j in range(f.shape[1])]
    skeys, carried = None, []
    for i in range(0, max(len(operands), 1), SORT_CARRY_WIDTH):
        res = jax.lax.sort(keys + tuple(operands[i:i + SORT_CARRY_WIDTH]),
                           num_keys=nk, is_stable=True)
        skeys = res[:nk]
        carried.extend(res[nk:])
    carried = iter(carried)
    return skeys, [
        jnp.stack([next(carried) for _ in range(f.shape[1])],
                  axis=1).reshape(p.shape)
        for p, f in zip(payloads, flat)]


@NM.scoped("k:sort")
def sort_values(x: jnp.ndarray) -> jnp.ndarray:
    """Ascending value sort — routed entry point for splitter sampling
    in the range exchange."""
    return jnp.sort(x)


def spill_partition_ids(cols: List[Column], sel, nparts: int,
                        level: int = 0) -> np.ndarray:
    """Partition id per row for spill-tiered execution (exec/spill_exec.py)
    — the same splitmix64 mixing family as rf_* and write_bucket_ids, so a
    bucket-aligned dynamic filter, an engine-written layout, and a spill
    partition agree on which keys co-locate.  `level` salts the mix for
    recursive re-partitioning: rows of one level-N partition share a
    residue of the level-N mix, so an unsalted re-partition could never
    split them — a remix with a different salt decorrelates the levels.
    Host numpy out (the spill fan-out masks host-side); dead rows get an
    arbitrary id (they are dropped by the per-partition sel mask)."""
    key = _hash_keys(cols, sel)
    z = key.astype(jnp.uint64)
    if level:
        z = _rf_mix64(z + jnp.uint64(level))
    p = (z % jnp.uint64(max(int(nparts), 1))).astype(jnp.int32)
    return np.asarray(jax.device_get(p))


# ---------------------------------------------------------------------------
# write-path layout kernels (exec/writer.py): bucket assignment shares
# the splitmix64 mixing with the runtime-filter family above, so a
# bucket-aligned dynamic filter and an engine-written bucket layout
# agree on which keys co-locate; the sort permutation rides the same
# routed sort entry points the executor's accounting sees.
# ---------------------------------------------------------------------------


def write_bucket_ids(values, bucket_count: int) -> np.ndarray:
    """Hash-bucket assignment for a write's bucket column(s): splitmix64
    over each int64 key column, XOR-combined, modulo bucket_count
    (reference: HiveBucketing.getHiveBucket feeding HivePageSink's
    per-bucket writers).  Host numpy in, host numpy out — the writer
    partitions host pages; the mix itself runs through the device kernel
    so there is exactly ONE splitmix implementation, shared with the
    runtime-filter membership family above."""
    cols = values if isinstance(values, (list, tuple)) else [values]
    h = None
    for v in cols:
        m = _rf_mix64(jnp.asarray(
            np.ascontiguousarray(v, dtype=np.int64)))
        h = m if h is None else h ^ m
    b = (h % jnp.uint64(max(int(bucket_count), 1))).astype(jnp.int32)
    return np.asarray(jax.device_get(b))


def write_sort_perm(keys: List[np.ndarray],
                    ascending: Optional[List[bool]] = None) -> np.ndarray:
    """Lexicographic sort permutation for a write page: keys in priority
    order (keys[0] primary), each already an orderable host int/float
    array (string columns enter as sorted-dictionary codes, so code
    order == value order).  Successive stable sorts from minor to major
    key — the classic lexsort construction — with every device sort
    routed through argsort_stable."""
    n = len(keys[0]) if keys else 0
    perm = np.arange(n, dtype=np.int64)
    asc = ascending if ascending is not None else [True] * len(keys)
    for key, up in reversed(list(zip(keys, asc))):
        k = np.ascontiguousarray(np.asarray(key)[perm])
        if not up:
            if k.dtype.kind in ("i", "u"):
                k = ~k  # exact order-reversing bijection on ints
            else:
                k = -k
        o = np.asarray(jax.device_get(argsort_stable(jnp.asarray(k))))
        perm = perm[o]
    return perm


# ---------------------------------------------------------------------------
# Pallas TPU kernels (hot ops the XLA autovectorizer doesn't fuse:
# the multi-aggregate segmented reduction).  CPU test meshes run the
# same kernels under the Pallas interpreter.
# ---------------------------------------------------------------------------


def _pallas_interpret() -> bool:
    return jax.default_backend() != "tpu"


#: group columns per grid step of fused_group_sums' TPU body
_FUSED_GROUP_TILE = 512


@NM.scoped("k:fused_group_sums")
def fused_group_sums(vals: jnp.ndarray, gid: jnp.ndarray,
                     n_groups: int) -> jnp.ndarray:
    """ONE pass computing k segmented sums that share group ids.

    The reference engine pays one hash-table probe per aggregate per row
    (InMemoryHashAggregationBuilder); plain XLA pays one scatter-add
    pass per aggregate column.  This Pallas kernel streams each row
    block through VMEM once, expands gid to a one-hot (VPU compare
    against a lane iota), and accumulates ALL k aggregate columns into a
    VMEM-resident (k, G) table across the sequential TPU grid — the
    aggregation becomes bandwidth-bound on a single read of the data.

    vals: [k, n] float64 (dead rows must already be zeroed)
    gid:  [n] int32 in [0, n_groups)
    returns [k, n_groups] sums (float64).

    Mosaic has no 64-bit types, so the TPU path computes PER-BLOCK f32
    partial sums on the MXU (one [k,B]x[B,G] matmul per block, no
    cross-block carry in f32) and XLA reduces the per-block partials in
    f64 outside the kernel — block-local rounding only, never a long
    f32 accumulation chain.  The CPU interpreter path keeps f64 inside
    the kernel.
    """
    from jax.experimental import pallas as pl

    k, n = vals.shape
    G = max(int(np.ceil(n_groups / 128)) * 128, 128)
    BLOCK = 8192
    npad = int(np.ceil(n / BLOCK)) * BLOCK
    if npad != n:
        with NM.kernel_scope("k:fused_group_sums.operand"):
            vals = jnp.pad(vals, ((0, 0), (0, npad - n)))
            # padded rows carry zeros: harmless
            gid = jnp.pad(gid, (0, npad - n))
    steps = npad // BLOCK
    gid2 = gid.reshape(1, -1)

    if _pallas_interpret():
        def kernel(vals_ref, gid_ref, out_ref):
            @pl.when(pl.program_id(0) == 0)
            def _init():
                out_ref[:, :] = jnp.zeros_like(out_ref)

            g = gid_ref[0, :]  # [BLOCK]
            onehot = (g[:, None] == jax.lax.broadcasted_iota(
                jnp.int32, (BLOCK, G), 1)).astype(vals_ref.dtype)
            out_ref[:, :] += jax.lax.dot_general(
                vals_ref[:, :], onehot, (((1,), (0,)), ((), ())),
                preferred_element_type=vals_ref.dtype)

        out = pl.pallas_call(
            kernel,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((k, BLOCK), lambda i: (0, i)),
                pl.BlockSpec((1, BLOCK), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((k, G), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((k, G), vals.dtype),
            interpret=True,
        )(vals, gid2)
        return out[:, :n_groups]

    # the one-hot is tiled over G so it never outgrows fast memory: at
    # the top of the executor's gate (4096 groups) an untiled
    # (BLOCK, G) f32 one-hot is 128 MiB; a (BLOCK, GT) tile is 16 MiB
    # of vector values Mosaic streams through the MXU.  The row block
    # keeps its index across the inner grid axis, so it is fetched once.
    GT = min(G, _FUSED_GROUP_TILE)
    G = -(-G // GT) * GT

    def kernel32(vals_ref, gid_ref, out_ref):
        g = gid_ref[0, :] - pl.program_id(1) * GT
        onehot = (g[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (BLOCK, GT), 1)).astype(jnp.float32)
        out_ref[0, :, :] = jax.lax.dot_general(
            vals_ref[:, :], onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)

    vals32 = vals.astype(jnp.float32)
    # the engine runs with x64 on; Mosaic only takes 32-bit types, so the
    # kernel traces in an x64-off scope (operands are f32/i32 already)
    with jax.enable_x64(False):
        partials = pl.pallas_call(
            kernel32,
            grid=(steps, G // GT),
            in_specs=[
                pl.BlockSpec((k, BLOCK), lambda i, j: (0, i)),
                pl.BlockSpec((1, BLOCK), lambda i, j: (0, i)),
            ],
            out_specs=pl.BlockSpec((1, k, GT), lambda i, j: (i, 0, j)),
            out_shape=jax.ShapeDtypeStruct((steps, k, G), jnp.float32),
        )(vals32, gid2)
    return partials.astype(jnp.float64).sum(axis=0)[:, :n_groups]
