"""Gather-aware kernel family: blocked (windowed) gathers for the
random-index materialization passes that dominate the chunked path.

Round-5 op-level profiling (docs/PERF.md) showed 4-5 random-gather
passes per chunk ARE the SF100 chunk program: TPU random gathers run at
a fixed ~45ns/index against ~300GB/s sequential HBM, so at 8M indices a
single materialization pass costs ~360ms while the sorts around it cost
~25ms.  This mirrors the memory-access-bound finding of *Global Hash
Tables Strike Back!* (random access, not hashing, dominates parallel
GROUP BY): the win is restructuring data movement, not faster scalar
code.

The family (routing lives in kernels.take_rows):

1. **Sort-order staging** — sort the indices once (co-sorting the
   request positions), gather in ASCENDING index order, and carry the
   rows home through ONE co-sort keyed on the positions (kernels.
   unpermute: payload operands ride a lax.sort nearly free, while an
   inverse-permutation gather would pay the full random-index cost a
   second time).  Ascending indices alone already help the DMA engine;
   the Pallas kernel below makes the locality explicit.

2. **Pallas block-gather** — with the indices sorted, each block of
   `_IB` consecutive indices covers a narrow source range.  The kernel
   pulls one aligned `W`-row source window per grid step through VMEM
   (a SEQUENTIAL HBM read, double-buffered by the Pallas pipeline via a
   scalar-prefetched window table) and picks rows VMEM-locally.  A
   runtime coverage check guards the static window size: skewed index
   blocks whose span exceeds `W` fall back — inside the same compiled
   program, via lax.cond — to the plain ascending-order XLA gather,
   which is always correct.

3. **Sort-order materialization** (exec/chunked.py + executor join
   sites) — when every consumer of the gathered batch is
   order-insensitive (aggregation, semi-join membership), the caller
   pre-permutes ALL row-aligned operands with kernels.sort_order_plan
   and skips the inverse permutation entirely: the batch simply STAYS
   in sorted-gather order.  This is the TPU analog of the reference's
   PagesIndex sort-order materialization (operator/PagesIndex.java,
   getSortedPages): produce output in the order the machine likes, not
   the order the rows arrived in.

CPU test meshes run the kernel under the Pallas interpreter.  On the
TPU backend step 2 is switched off (_block_gather_enabled: Mosaic
refuses the kernel body) and staging runs steps 1 and 3 over the plain
ascending-order XLA gather.  The routing constants come from a model
(docs/PERF.md round 6); tools/roofline.py's gather sweep has not run on
a chip.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.exec import compile_cache

# ---------------------------------------------------------------------------
# routing constants (pinned by tools/roofline.py's gather sweep)
# ---------------------------------------------------------------------------

# below this index count the flat packed gather wins: two extra sorts
# (~25ms each at 6-8M rows, much less below) only amortize against the
# ~45ns/index random-gather constant once the index count is large
_STAGED_MIN_INDICES = 1 << 20

# staged request-order gathers pay one co-sort carrying all row words;
# payload operands are nearly free, so TWO u32 words (one i64 column)
# already clear the bar — same crossover the packed gather uses
_STAGED_MIN_WORDS = 2

# indices per Pallas grid step (one output block)
_IB = 1024

# the largest aligned source window one grid step may pull through VMEM
# (W * words * 4B; 8192 x 16 words = 512KB, comfortably inside VMEM
# next to the index and output blocks)
_MAX_WINDOW = 8192

# window sizing: expected span of _IB sorted indices is _IB * n/m rows;
# 2x headroom absorbs mild skew before the coverage cond bails
_WINDOW_SLACK = 2


def _env_mode() -> str:
    """PRESTO_TPU_GATHER: '' (auto: staged on TPU, flat elsewhere) |
    'flat' (disable staging) | 'sorted' (staging without the Pallas
    kernel — what the TPU backend always runs today, see
    _block_gather_enabled) | 'force' (staging even off-TPU: the CPU
    equivalence tests, which also shrink the routing constants)."""
    return os.environ.get("PRESTO_TPU_GATHER", "")


def _staging_enabled() -> bool:
    """Auto mode stages only on TPU: the blocked kernel runs in Pallas
    INTERPRET mode everywhere else, where a production-sized grid
    (1M+ indices / _IB) unrolls into an XLA CPU program that takes
    effectively forever to compile (observed: tpcds q37's static-bound
    join expansion hanging the CPU tier).  Tests opt in explicitly
    with PRESTO_TPU_GATHER=force after shrinking the constants."""
    mode = _env_mode()
    if mode == "flat":
        return False
    if mode in ("force", "sorted"):
        return True
    return jax.default_backend() == "tpu"


def gather_route(n: int, m: int, words: int,
                 presorted: bool = False) -> str:
    """Static routing for an m-index gather from an n-row, `words`-wide
    u32 source: 'flat' (XLA packed gather in request order) or 'staged'
    (ascending-order staging, Pallas-windowed when density allows).
    All inputs are trace-time constants — the route never host-syncs.

    presorted indices skip the sort AND the unpermute, so staging wins
    at any width; request-order gathers must clear _STAGED_MIN_WORDS to
    amortize the co-sort home."""
    if not _staging_enabled():
        return "flat"
    if m < _STAGED_MIN_INDICES or n <= 0 or words <= 0:
        return "flat"
    if not presorted and words < _STAGED_MIN_WORDS:
        return "flat"
    return "staged"


def sort_order_worthwhile(m: int, gain_words: int) -> bool:
    """Should a join pre-permute its expansion into build-index order
    (kernels.sort_order_plan)?  The permutation trades the wide side's
    random gather for a sequential one but turns the (previously
    ascending) probe-side expansion random, so it pays off only when
    the build rows are WIDER than the probe rows and the expansion is
    big enough to clear the staging threshold."""
    return (_staging_enabled() and m >= _STAGED_MIN_INDICES
            and gain_words > 0)


def window_rows(n: int, m: int) -> int | None:
    """Aligned VMEM window size (power of two) for a blocked gather, or
    None when the indices are too sparse for any window up to
    _MAX_WINDOW to cover a sorted block — staging then runs as the
    plain ascending-order gather (still the sort-order win, just
    without the explicit VMEM windows)."""
    if n <= 0 or m <= 0:
        return None
    span = _WINDOW_SLACK * _IB * n / m
    W = 1 << int(np.ceil(np.log2(max(span, _IB))))
    if W > _MAX_WINDOW:
        return None
    return int(W)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _block_gather_enabled() -> bool:
    """The Pallas block-gather is OFF on the TPU backend: the chip's
    compiler refuses its body at every shape (v5e, jax 0.9.0 / libtpu
    0.0.34, tests/test_tpu_aot_compile.py pins the message): the
    in-kernel `jnp.take` of IB rows from a (W, w) window fails with
    "Shape mismatch in input, indices and output", and the only gather
    Mosaic lowers, an equal-shape take_along_axis, stops at one vreg
    ("Not implemented: Multiple source vregs along gather dimension").
    A (n, 2..16)-wide u32 operand would also be lane-padded to 128 in
    HBM.  Staging on the chip therefore runs as the ascending-order XLA
    gather; the kernel stays reachable in interpret mode (the CPU
    equivalence tests) until it is rebuilt lane-major or deleted
    (ROADMAP Speed queue)."""
    return _interpret()


@partial(compile_cache.static_jit, static_argnames=("W", "IB"))
def _blocked_gather_call(blk, idx2, src, *, W: int, IB: int):
    """One Pallas launch: grid step i copies source window
    [blk[i]*W, blk[i]*W + W) into VMEM (sequential DMA, pipelined by
    the scalar-prefetched window table) and gathers its _IB indices
    VMEM-locally.  Caller guarantees coverage: every index in block i
    lies inside that window (checked by staged_gather's lax.cond)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m_pad = idx2.shape[1]
    w = src.shape[1]

    def kernel(blk_ref, idx_ref, src_ref, out_ref):
        i = pl.program_id(0)
        base = blk_ref[i] * np.int32(W)
        local = jnp.clip(idx_ref[0, :] - base, np.int32(0), np.int32(W - 1))
        # in-VMEM row pick: Mosaic lowers the dynamic take onto the VPU
        # (sublane gather); the HBM side of this step was the ONE
        # sequential window copy above
        out_ref[...] = jnp.take(src_ref[...], local, axis=0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m_pad // IB,),
        in_specs=[
            pl.BlockSpec((1, IB), lambda i, blk_ref: (0, i)),
            pl.BlockSpec((W, w), lambda i, blk_ref: (blk_ref[i], 0)),
        ],
        out_specs=pl.BlockSpec((IB, w), lambda i, blk_ref: (i, 0)),
    )
    # the engine runs with x64 on, but every operand and constant here
    # is explicitly 32-bit (u32/i32), so the kernel traces Mosaic-clean
    # without an x64-off scope (which would split the trace across two
    # promotion regimes — the interpreter rejects that)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, w), jnp.uint32),
        interpret=_interpret(),
    )(blk, idx2, src)


def staged_gather(src: jnp.ndarray, sidx: jnp.ndarray) -> jnp.ndarray:
    """Gather rows of a (n, w) u32 matrix at ASCENDING i32 indices.
    Routes through the Pallas block-gather when the density supports a
    VMEM window; a runtime coverage check falls back (lax.cond, no host
    sync) to the plain ascending-order XLA gather on skew.  Indices
    must be pre-clipped to [0, n)."""
    n, w = src.shape
    m = sidx.shape[0]
    W = window_rows(n, m)
    if W is None or m < _IB or _env_mode() == "sorted" \
            or not _block_gather_enabled():
        return src[sidx]
    m_pad = -(-m // _IB) * _IB
    if m_pad != m:
        # edge-pad keeps the tail ascending (coverage math stays valid)
        sidx = jnp.pad(sidx, (0, m_pad - m), mode="edge")
    n_pad = -(-n // W) * W
    src_p = jnp.pad(src, ((0, n_pad - n), (0, 0))) if n_pad != n else src
    blk = (sidx[::_IB] // W).astype(jnp.int32)
    ends = sidx[_IB - 1::_IB]
    covered = jnp.all(ends < (blk + 1) * W)
    idx2 = sidx.reshape(1, -1)
    out = jax.lax.cond(
        covered,
        lambda a: _blocked_gather_call(a[0], a[1], a[2], W=W, IB=_IB),
        lambda a: a[2][a[1][0, :]],
        (blk, idx2, src_p))
    return out[:m]
