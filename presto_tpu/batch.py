"""Columnar batch representation — the TPU-native Page/Block.

Reference parity: presto-spi/.../spi/Page.java:34 (Page = positionCount +
Block[]) and the Block hierarchy in presto-spi/.../spi/block/.  Redesigned
for XLA's static-shape world:

- A `Batch` is a pytree of fixed-shape device arrays: one data array per
  column, an optional per-column validity mask (None == no nulls, like the
  reference's mayHaveNull fast path), and a row-selection mask `sel`.
- Filters AND into `sel` instead of compacting (no data-dependent shapes
  inside jit).  `row_count` is a traced scalar = popcount(sel).
- Strings are ALWAYS dictionary-encoded (the reference's DictionaryBlock,
  presto-spi/.../spi/block/DictionaryBlock.java, promoted from an
  optimization to the only representation): int32 codes on device, the
  dictionary itself is a host-side numpy array of strings shared by
  reference (`Dictionary`).  String functions evaluate over the (small)
  dictionary on host and the result is gathered through the codes on
  device — this is the dictionary-aware projection of
  operator/project/DictionaryAwarePageProjection.java, made mandatory.
- LazyBlock (late materialization) has no analog: XLA dead-code eliminates
  unused columns after tracing, which is strictly stronger.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.types import Type

_dict_ids = itertools.count()


class Dictionary:
    """Host-side string dictionary, identity-hashed so batches stay
    jit-cache-friendly (a new Dictionary object => new compilation key only
    when used as a static argument; codes arrays are ordinary operands)."""

    __slots__ = ("values", "_id", "_value_hashes")

    def __init__(self, values: np.ndarray):
        # values: 1-D object/str array; code i means values[i]. values[-1]
        # position is NOT reserved; null is carried by the validity mask.
        self.values = np.asarray(values)
        self._id = next(_dict_ids)

    def __len__(self) -> int:
        return len(self.values)

    def __hash__(self) -> int:
        return self._id

    def __eq__(self, other) -> bool:
        return self is other

    def __repr__(self) -> str:
        return f"Dictionary(#{self._id}, {len(self.values)} values)"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Column:
    """One column: data array + optional validity mask (True == non-null)."""

    data: jax.Array
    valid: Optional[jax.Array]  # None => all valid
    type: Type
    dictionary: Optional[Dictionary] = None

    def tree_flatten(self):
        return (self.data, self.valid), (self.type, self.dictionary)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, valid = children
        return cls(data, valid, aux[0], aux[1])

    @property
    def capacity(self) -> int:
        return self.data.shape[0]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Batch:
    """A set of equal-capacity columns + a row-selection mask."""

    columns: Dict[str, Column]
    sel: jax.Array  # bool[capacity]; True == row is live

    def tree_flatten(self):
        names = tuple(self.columns)
        return (tuple(self.columns[n] for n in names), self.sel), names

    @classmethod
    def tree_unflatten(cls, names, children):
        cols, sel = children
        return cls(dict(zip(names, cols)), sel)

    @property
    def capacity(self) -> int:
        return self.sel.shape[0]

    def row_count(self) -> jax.Array:
        return jnp.sum(self.sel)

    def column(self, name: str) -> Column:
        return self.columns[name]

    def with_columns(self, columns: Dict[str, Column]) -> "Batch":
        return Batch(columns, self.sel)

    def with_sel(self, sel: jax.Array) -> "Batch":
        return Batch(self.columns, sel)

    def select(self, names: Sequence[str]) -> "Batch":
        return Batch({n: self.columns[n] for n in names}, self.sel)


# ---------------------------------------------------------------------------
# Host-side ingestion
# ---------------------------------------------------------------------------


def encode_strings(values: np.ndarray) -> tuple[np.ndarray, Dictionary]:
    """Dictionary-encode a host string column -> (int32 codes, Dictionary).
    The dictionary is SORTED so that code order == lexicographic order,
    making ORDER BY / comparisons on strings pure integer ops on device.
    The O(n) hashing pass runs in the native C++ library when available
    (presto_tpu/native pt_dict_encode); numpy np.unique otherwise."""
    from presto_tpu import native

    arr = np.asarray(values, dtype=object).astype(str)
    if len(arr) >= 4096:
        encoded = native.dict_encode(arr)
        if encoded is not None:
            codes, uniq = encoded
            return codes, Dictionary(uniq)
    uniq, codes = np.unique(arr, return_inverse=True)
    return codes.astype(np.int32), Dictionary(uniq)


def column_from_numpy(data: np.ndarray, typ: Type, valid: Optional[np.ndarray] = None) -> Column:
    if isinstance(data, np.ma.MaskedArray):
        # connectors return masked arrays for nullable columns (the SPI's
        # null channel; reference: Block.isNull)
        mask = np.ma.getmaskarray(data)
        nv = ~mask
        valid = nv if valid is None else (np.asarray(valid) & nv)
        data = data.filled("" if typ.is_string else 0)
    dictionary = None
    if typ.is_string and data.dtype.kind in ("U", "S", "O"):
        data, dictionary = encode_strings(data)
    if typ.is_decimal and typ.is_long_decimal:
        from presto_tpu.exec import dec128 as D128

        if data.ndim == 2 and data.dtype.kind == "i":
            pass  # already limbs
        else:
            import decimal as _d

            s = typ.decimal_scale
            with _d.localcontext() as ctx:
                ctx.prec = 80  # default 28 can't hold 38-digit values
                ints = [int(_d.Decimal(str(v)).scaleb(s).quantize(
                    _d.Decimal(1), rounding=_d.ROUND_HALF_UP))
                    for v in data]
            data = D128.from_host_ints(ints)
        v = None if valid is None else jnp.asarray(valid, dtype=bool)
        return Column(jnp.asarray(data), v, typ, None)
    if typ.is_decimal and data.dtype.kind == "f":
        # host floats (e.g. a decoded decimal column re-ingested via
        # CTAS/INSERT) carry the unscaled value; rescale, don't truncate
        scaled = data * (10 ** typ.decimal_scale)
        from presto_tpu.types import check_decimal_overflow

        check_decimal_overflow(scaled, valid, "ingested value")
        data = np.round(scaled)
    data = np.ascontiguousarray(data, dtype=typ.numpy_dtype())
    v = None if valid is None else jnp.asarray(valid, dtype=bool)
    return Column(jnp.asarray(data), v, typ, dictionary)


def batch_from_numpy(
    arrays: Dict[str, np.ndarray],
    types: Dict[str, Type],
    valids: Optional[Dict[str, np.ndarray]] = None,
) -> Batch:
    cols = {}
    n = None
    for name, arr in arrays.items():
        v = (valids or {}).get(name)
        cols[name] = column_from_numpy(arr, types[name], v)
        n = len(arr) if n is None else n
        assert len(arr) == n, f"ragged column {name}"
    sel = jnp.ones((n or 0,), dtype=bool)
    return Batch(cols, sel)


_COMPACT_THRESHOLD = 262_144  # capacity above which selective fetch wins


def to_numpy(batch: Batch, extra=None):
    """Materialize to host: (column arrays with strings decoded, live-row
    mask[, extra pulled value]).  ONE device_get for the whole batch —
    per-column transfers each pay their own device-to-host sync.  Large
    mostly-dead batches (a TopN mask over a scan-sized
    capacity) are compacted on device first: pull the 1-byte/row sel,
    gather the survivors, pull only those — a 10-row result over a
    6M-row capacity then moves kilobytes, not the whole capacity (not
    measured on this tree)."""
    if batch.capacity > _COMPACT_THRESHOLD:
        sel_h, extra_h = jax.device_get((batch.sel, extra))
        sel_h = np.asarray(sel_h)
        live = np.flatnonzero(sel_h)
        if len(live) < batch.capacity // 4:
            idx = jnp.asarray(live)
            pulled = jax.device_get(
                {n: (c.data[idx],
                     None if c.valid is None else c.valid[idx])
                 for n, c in batch.columns.items()})
            out = _decode_pulled(batch, pulled)
            ones = np.ones(len(live), dtype=bool)
            return (out, ones) if extra is None else (out, ones, extra_h)
        # dense batch: fall through to the single full fetch below (sel
        # already pulled; extra too)
        pulled = jax.device_get(
            {n: (c.data, c.valid) for n, c in batch.columns.items()})
        out = _decode_pulled(batch, pulled)
        return (out, sel_h) if extra is None else (out, sel_h, extra_h)
    pulled = jax.device_get(
        (batch.sel,
         {n: (c.data, c.valid) for n, c in batch.columns.items()},
         extra))
    sel, datas, extra_h = pulled
    sel = np.asarray(sel)
    out = _decode_pulled(batch, datas)
    return (out, sel) if extra is None else (out, sel, extra_h)


def decode_host_column(data, valid, typ, dictionary) -> np.ndarray:
    """Decode one pulled column on host: dictionary lookup, decimal
    rescale, NULL masking.  Shared by every result-materialization path
    (to_numpy and the compiled packed fetch)."""
    data = np.asarray(data)
    if dictionary is not None:
        codes = np.clip(data, 0, len(dictionary) - 1)
        data = dictionary.values[codes]
    elif typ.is_decimal and typ.is_long_decimal and data.ndim == 2:
        # two-limb Int128: decode to exact python Decimals (reference:
        # Int128ArrayBlock -> SqlDecimal)
        from decimal import Decimal

        from presto_tpu.exec import dec128 as D128

        ints = D128.to_host_ints(data)  # signed (hi limb is signed)
        s = typ.decimal_scale
        out = np.empty(len(ints), dtype=object)
        import decimal as _d

        with _d.localcontext() as ctx:
            ctx.prec = 80  # scaleb ROUNDS to context precision (28!)
            for i, v in enumerate(ints):
                out[i] = Decimal(v).scaleb(-s)
        data = out
    elif typ.is_decimal:
        data = data.astype(np.float64) / (10 ** typ.decimal_scale)
    if valid is not None:
        data = np.ma.masked_array(data, mask=~np.asarray(valid))
    return data


def _decode_pulled(batch: Batch, datas) -> Dict[str, np.ndarray]:
    return {name: decode_host_column(datas[name][0], datas[name][1],
                                     col.type, col.dictionary)
            for name, col in batch.columns.items()}
