"""Gather-aware kernel family: sort-order staging for the random-index
materialization passes that dominate the chunked path.

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
   second time).

2. **The ascending gather** (staged_gather) is XLA's own gather at the
   sorted indices, on every platform: ascending indices alone help the
   DMA engine.  There is no VMEM-window kernel behind it, because
   Mosaic lowers no in-kernel row gather wider than one vreg (v5e,
   jax 0.9.0 / libtpu 0.0.34, PR 22).

3. **Sort-order materialization** (exec/chunked.py + executor join
   sites) — when every consumer of the gathered batch is
   order-insensitive (aggregation, semi-join membership), the caller
   pre-permutes ALL row-aligned operands with kernels.sort_order_plan
   and skips the inverse permutation entirely: the batch simply STAYS
   in sorted-gather order.  This is the TPU analog of the reference's
   PagesIndex sort-order materialization (operator/PagesIndex.java,
   getSortedPages): produce output in the order the machine likes, not
   the order the rows arrived in.

The routing constants come from a model (docs/PERF.md round 6);
tools/roofline.py's gather sweep has not run on a chip.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

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


# A fact table's rows gathering from a dimension many times smaller (a
# star join's probe): sorting the indices buys nothing, the source being
# small wherever the index points, and past this many indices the packed
# result cannot even be laid out — a (m, w) u32 matrix takes m x 512
# bytes in the TPU's 128-lane tiles whatever w is (13.7 GB at 28.8 M rows:
# the compiler refused TPC-DS q36).  Measured on a v5e at 28.8 M indices
# into 180,000 x 5 words (PERF.md section 6, PR 34): staged 495 ms, the
# packed gather in blocks 198 ms, ascending indices 198 ms as well, five
# one-word gathers 1838 ms.  The rule routes gathers only: a gather of
# fewer indices, or from a larger source, takes the route it took before.
_SMALL_SOURCE_MIN_INDICES = 1 << 23
_SMALL_SOURCE_RATIO = 8
#: indices a block of the packed gather holds (2 GB of padded rows)
PACKED_BLOCK = 1 << 22


def small_source(n: int, m: int) -> bool:
    """An m-index gather from n rows is a star join's: many indices, a
    source at least _SMALL_SOURCE_RATIO times smaller."""
    return m > _SMALL_SOURCE_MIN_INDICES and m >= _SMALL_SOURCE_RATIO * n


def _staging_enabled() -> bool:
    """PRESTO_TPU_GATHER: '' (auto: staged on TPU, the platform the
    routing constants were modelled for, flat elsewhere) | 'flat'
    (disable staging) | 'force' (staging even off-TPU: the CPU
    equivalence tests, which also shrink the routing constants)."""
    mode = os.environ.get("PRESTO_TPU_GATHER", "")
    if mode == "flat":
        return False
    if mode == "force":
        return True
    return jax.default_backend() == "tpu"


def gather_route(n: int, m: int, words: int,
                 presorted: bool = False) -> str:
    """Static routing for an m-index gather from an n-row, `words`-wide
    u32 source: 'flat' (XLA packed gather in request order) or 'staged'
    (sort, gather in ascending order, co-sort home).
    All inputs are trace-time constants — the route never host-syncs.

    presorted indices skip the sort AND the unpermute, so staging wins
    at any width; request-order gathers must clear _STAGED_MIN_WORDS to
    amortize the co-sort home.  A small source under many indices
    (`small_source`) is gathered flat, in blocks."""
    if not _staging_enabled() or small_source(n, m):
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


# The staged route stacks a source's words into one (n, w) u32 matrix.
# Past this many bytes the matrix goes in column groups of at most half
# of it: a mesh shard of TPC-DS sf100's store_sales compacts 72 M slots
# x 21 words after its first join, a 6 GB matrix beside 3.5 GB of
# resident columns and its own copy in the gather (17.1 GB of
# temporaries: the v5e's compiler, PERF.md section 6, PR 36).  A gather
# costs by the index, not by the row's width (PR 34), so a group more is
# ~8 ns an index more.  No source of the cells before PR 36 is past it
# (the largest: 28.8 M x 21 words, 2.4 GB, TPC-DS q27 at sf10).
STAGED_SOURCE_BYTES = 1 << 32


def staged_word_groups(n: int, words: int):
    """The slices of a staged source's `words` u32 columns that are
    stacked and gathered together: all of them, unless n rows of them
    are past STAGED_SOURCE_BYTES."""
    if 4 * n * words <= STAGED_SOURCE_BYTES:
        return [slice(0, words)]
    per = max(STAGED_SOURCE_BYTES // 2 // (4 * n), 1)
    return [slice(k, min(k + per, words)) for k in range(0, words, per)]


def staged_gather(src: jnp.ndarray, sidx: jnp.ndarray) -> jnp.ndarray:
    """Gather rows of a (n, w) u32 matrix at ASCENDING i32 indices,
    pre-clipped to [0, n)."""
    return src[sidx]
