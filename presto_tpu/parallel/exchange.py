"""Collective exchange kernels: the data plane of distributed execution.

Reference parity: the HTTP shuffle (SURVEY.md §2.6 — PartitionedOutputOperator
-> PagesSerde -> OutputBuffer -> HttpPageBufferClient -> ExchangeClient)
re-based on XLA collectives over the ICI mesh.  Where the reference
serializes pages and pulls them over HTTP with ack tokens, here a whole
repartition is ONE `lax.all_to_all` (per array) inside the jitted
superstep: one stable sort by destination (the key's hash) carries every
column along, so each destination's rows lie contiguous; ndev slices of
length C out of the sorted arrays are the fixed (ndev, C) send layout,
exchanged and received as a fixed (ndev*C,) batch with a validity mask —
no gather and no scatter per column (random access costs 25-28 ns a row
on the v5e, a carried sort operand next to nothing).  Backpressure,
framing, compression, and retry disappear — XLA schedules the transfer and
overlap; capacity overflow is a traced guard that falls back to dynamic
execution (the analog of the reference's spill-on-buffer-full, but chosen
per-query instead of per-page).

All functions here run INSIDE shard_map (per-shard view, axis name bound).
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.batch import Batch, Column
from presto_tpu.exec import kernels as K
from presto_tpu.observe import names as NM


@NM.scoped("x:all_gather")
def all_gather_batch(b: Batch, axis: str) -> Batch:
    """P2/P5: replicate a sharded batch on every shard (broadcast build
    sides, gather-to-coordinator).  Dictionaries are host-side and already
    shared across shards (tracing happens once)."""
    cols = {}
    for name, c in b.columns.items():
        data = jax.lax.all_gather(c.data, axis, tiled=True)
        valid = None if c.valid is None else jax.lax.all_gather(c.valid, axis, tiled=True)
        cols[name] = Column(data, valid, c.type, c.dictionary)
    sel = jax.lax.all_gather(b.sel, axis, tiled=True)
    return Batch(cols, sel)


def scatter_batch(b: Batch, axis: str) -> Batch:
    """Replicated -> sharded: keep rows on shard 0 only, so a replicated
    input can feed a sharded union/concat without duplication."""
    idx = jax.lax.axis_index(axis)
    return b.with_sel(b.sel & (idx == 0))


def partition_hash(key_cols: List[Column]) -> jnp.ndarray:
    """Row -> uint32 bucket hash, STABLE across shards and across batches:
    string columns hash their dictionary *values* (via a host-computed
    per-code LUT) so two sides of a join agree even with different
    dictionaries.  (Reference: InterpretedHashGenerator feeding
    PartitionFunction, operator/repartition/PartitionedOutputOperator.java.)"""
    h = jnp.zeros(key_cols[0].data.shape, dtype=jnp.uint64)
    for c in key_cols:
        if c.dictionary is not None:
            lut = jnp.asarray(_dict_value_hashes(c.dictionary), dtype=jnp.uint64)
            d = lut[jnp.clip(c.data, 0, len(c.dictionary) - 1)]
        else:
            d = K._orderable_int(c).astype(jnp.uint64)
        d = jnp.where(K._valid_arr(c), d, jnp.uint64(0x9E3779B97F4A7C15))
        h = h ^ (d + jnp.uint64(0x9E3779B97F4A7C15)
                 + (h << jnp.uint64(6)) + (h >> jnp.uint64(2)))
        z = h
        z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
        h = z ^ (z >> jnp.uint64(31))
    return h


def _dict_value_hashes(dictionary) -> np.ndarray:
    """FNV-1a over utf-8 bytes of each dictionary value (host-side, once
    per trace; cached on the Dictionary, lifetime-bound to it)."""
    cached = getattr(dictionary, "_value_hashes", None)
    if cached is not None:
        return cached
    # by code, not by iteration: a functional dictionary (the device
    # generator's numbered names) renders values from codes and has no end
    values = np.asarray(dictionary.values[np.arange(len(dictionary))])
    raw = np.char.encode(values.astype(str), "utf-8")
    width = max(raw.dtype.itemsize, 1)
    octets = np.frombuffer(raw.astype(f"S{width}").tobytes(), np.uint8) \
        .reshape(len(raw), width).astype(np.uint64)
    lengths = np.char.str_len(raw)
    out = np.full(len(raw), 0xCBF29CE484222325, dtype=np.uint64)
    for j in range(width):  # one pass a byte position, all values at once
        out = np.where(j < lengths,
                       (out ^ octets[:, j]) * np.uint64(0x100000001B3), out)
    dictionary._value_hashes = out
    return out


def _exchange_by_dest(b: Batch, dest: jnp.ndarray, ndev: int, axis: str,
                      slack: float, order_key=None
                      ) -> Tuple[Batch, jnp.ndarray]:
    """Shared all_to_all machinery: move every live row to shard
    `dest[row]` (dest in [0, ndev); dead rows may carry any value).

    Static send layout: per-destination capacity C = ceil(slack * n/ndev).
    ONE stable sort keyed on (dest, order_key) — order_key gives the range
    exchange its within-destination order; without it rows keep their input
    order — carries every column's data and validity array as payload
    (kernels.sort_carrying), so the rows of destination d lie contiguous
    at first[d] .. first[d+1].  The (ndev*C,) send buffer is ndev
    contiguous slices of length C out of each sorted array, with the slots
    past a bucket's count zeroed: no gather and no scatter per column.
    Bucket overflow (count[d] > C: skew beyond `slack`) sets the returned
    guard and the rows beyond C are not sent — the caller falls back, the
    distributed analog of the reference's skew pathology (SURVEY.md §7
    hard-part 5).

    Returns (received batch with capacity ndev*C, overflow guard)."""
    n = b.capacity
    c_cap = max(int(np.ceil(slack * n / ndev)), 1)
    dest = jnp.where(b.sel, dest, ndev)  # dead rows sort last
    arrays = [x for c in b.columns.values() for x in (c.data, c.valid)
              if x is not None]
    keys = (dest,) if order_key is None else (dest, order_key)
    (sdest, *_), carried = K.sort_carrying(keys, arrays)
    first = jnp.searchsorted(sdest, jnp.arange(ndev + 1, dtype=sdest.dtype))
    count = first[1:] - first[:-1]  # live rows per destination
    overflow = jnp.any(count > c_cap)
    # a slot is sent iff its sender has a row for it, and a received slot
    # is live iff it was sent
    sent = (jnp.arange(c_cap)[None, :]
            < jnp.minimum(count, c_cap)[:, None]).reshape(-1)

    def all_to_all(send):
        return jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                                  tiled=True)

    def exchange(x):
        # padded by C so that no slice runs off the end (dynamic_slice
        # would move its start); past its bucket's count a slice holds the
        # next buckets' rows: zeroed, as a slot nothing was sent in reads
        trail = x.shape[1:]
        x = jnp.concatenate([x, jnp.zeros((c_cap,) + trail, x.dtype)])
        send = jnp.concatenate([
            jax.lax.dynamic_slice_in_dim(x, first[d], c_cap)
            for d in range(ndev)])
        keep = sent.reshape((-1,) + (1,) * len(trail))
        return all_to_all(jnp.where(keep, send, jnp.zeros((), x.dtype)))

    carried = iter(carried)
    cols = {}
    for name, c in b.columns.items():
        data = exchange(next(carried))
        valid = None if c.valid is None else exchange(next(carried))
        cols[name] = Column(data, valid, c.type, c.dictionary)
    return Batch(cols, all_to_all(sent)), overflow


@NM.scoped("x:repartition")
def repartition_batch(b: Batch, key_cols: List[Column], ndev: int, axis: str,
                      slack: float = 2.0) -> Tuple[Batch, jnp.ndarray]:
    """P1 hash repartition: every live row moves to shard
    hash(keys) % ndev via ONE all_to_all (see _exchange_by_dest)."""
    h = partition_hash(key_cols)
    dest = (h % jnp.uint64(ndev)).astype(jnp.int32)
    return _exchange_by_dest(b, dest, ndev, axis, slack)


def _sort_key_ints(col: Column, ascending: bool, nulls_first) -> jnp.ndarray:
    """Order-preserving int64 image of a sort column: flip for DESC, send
    NULLs to the requested end (defaults match ORDER BY: last for ASC,
    first for DESC)."""
    k = K._orderable_int(col).astype(jnp.int64)
    if not ascending:
        k = -k
    if nulls_first is None:
        nulls_first = not ascending
    if col.valid is not None:
        ext = jnp.iinfo(jnp.int64).min if nulls_first else jnp.iinfo(jnp.int64).max
        k = jnp.where(col.valid, k, ext)
    return k


@NM.scoped("x:range_partition")
def range_partition_batch(b: Batch, sort_keys, ndev: int, axis: str,
                         samples_per_shard: int = 64, slack: float = 2.0
                         ) -> Tuple[Batch, jnp.ndarray]:
    """P11 distributed sort, stage 1 — sample-sort range exchange: shard i
    receives all rows whose primary sort key falls in the i-th key range,
    with splitters chosen from a gathered sample (the TPU-native
    replacement for per-task partial sort + MergeOperator's n-way merge;
    reference: operator/MergeOperator.java + admin/dist-sort.rst).

    dest is a pure function of the primary key VALUE (searchsorted over
    shared splitters), so equal keys never split across shards and the
    secondary sort keys stay a per-shard problem.  After each shard sorts
    locally, an ordered all_gather concatenation is globally sorted."""
    sym, asc, nf = sort_keys[0]
    key = _sort_key_ints(b.columns[sym], asc, nf)
    n = b.capacity
    # evenly-spaced sample of the locally-sorted keys (dead rows last)
    big = jnp.iinfo(jnp.int64).max
    local_sorted = K.sort_values(jnp.where(b.sel, key, big))
    pos = jnp.linspace(0, n - 1, samples_per_shard).astype(jnp.int32)
    sample = local_sorted[pos]
    all_samples = K.sort_values(jax.lax.all_gather(sample, axis, tiled=True))
    total = ndev * samples_per_shard
    cut = (jnp.arange(1, ndev) * total) // ndev
    splitters = all_samples[cut]
    dest = jnp.searchsorted(splitters, key, side="right").astype(jnp.int32)
    # dead-row padding sampled as `big` skews splitters upward; real rows
    # overflowing a range trip the guard and fall back
    return _exchange_by_dest(b, jnp.clip(dest, 0, ndev - 1), ndev, axis,
                             slack, order_key=key)
