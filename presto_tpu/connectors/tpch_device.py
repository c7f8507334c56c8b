"""Device-side TPC-H generation: the TPU generates its own scan batches.

Reference parity: presto-tpch generates rows on the fly inside the scan
operator (TpchRecordSet) instead of reading storage.  TPU-native
adaptation: the generator is a counter-based hash (splitmix64 over
(table, column, row) counters, connectors/tpch.py), which is pure
integer math — so any row range of any column can be produced ON DEVICE
by the same XLA program that consumes it.  At SF100 the host generator
produces ~0.1M rows/s on one core; the device version produces the
needed columns at memory-bandwidth speed, which is what makes the
BASELINE SF10/SF100 configs runnable at all.

Exactness: every formula mirrors connectors/tpch.py bit-for-bit (same
splitmix64 counters, same f64 scaling), validated column-for-column
against the host generator in tests/test_tpch_device.py.

String columns come back as dictionary codes computed on device:
- enum picks (flags, segments, priorities, modes...) map through a tiny
  host-precomputed LUT onto the sorted-unique dictionary the engine
  expects (code order == lexicographic order);
- numbered names (Customer#000000001, Supplier#..., Clerk#...) use a
  FormatDictionary — a *functional* dictionary that renders values from
  codes at materialization time (the LazyBlock idea,
  presto-spi/.../spi/block/LazyBlock.java: decode only what the result
  actually touches);
- free-text columns (comments, p_name, addresses, phones) are NOT
  device-generable; reads of those fall back to the host generator.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from presto_tpu import types as T
from presto_tpu.batch import Column, Dictionary
from presto_tpu.connectors import tpch as H


# ---------------------------------------------------------------------------
# functional dictionary for numbered-name columns
# ---------------------------------------------------------------------------


class _FormatValues:
    """Vectorized `prefix#%0*d` renderer with ndarray-style indexing."""

    def __init__(self, prefix: str, width: int, n: int):
        self.prefix = prefix
        self.width = width
        self.n = n

    def __getitem__(self, codes):
        codes = np.asarray(codes)
        return np.char.add(
            self.prefix,
            np.char.zfill(codes.astype(np.int64).astype(str), self.width)
        ).astype(object)


class FormatDictionary(Dictionary):
    """Dictionary whose values are a formula, not an array: code k
    renders as `{prefix}{k:0{width}d}`.  Zero-filled numbering keeps
    code order == lexicographic order, the invariant dictionary
    comparisons rely on.  Codes are the entity keys themselves, so no
    giant value array ever materializes (15M customer names at SF100
    stay a single int column until the final rows are formatted)."""

    def __init__(self, prefix: str, width: int, n: int):
        # deliberately skip Dictionary.__init__'s np.asarray
        self.values = _FormatValues(prefix, width, n)
        self._id = next(type(self)._ids)
        self._n = n

    _ids = itertools.count(1 << 40)

    def __len__(self) -> int:
        return self._n

    def __repr__(self) -> str:
        return f"FormatDictionary({self.values.prefix!r}, n={self._n})"


# ---------------------------------------------------------------------------
# splitmix64 core on device (u64 emulated as u32 pairs by XLA)
# ---------------------------------------------------------------------------


def _mix(x: jnp.ndarray) -> jnp.ndarray:
    z = x + jnp.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return z ^ (z >> jnp.uint64(31))


def _raw(table: str, col: str, row0: int, n: int, draw: int = 0,
         k: int = 1) -> jnp.ndarray:
    """f64 uniforms in [0,1) for rows [row0, row0+n), draw index `draw`
    of `k` — matches H._raw(...)[:, draw] bit-for-bit."""
    rows = jnp.asarray(row0, jnp.uint64) + jnp.arange(n, dtype=jnp.uint64)
    ctr = (rows * jnp.uint64(k) + jnp.uint64(draw)
           + jnp.uint64(int(H._colkey(table, col)))
           * jnp.uint64(0x632BE59BD9B4E019))
    u = _mix(ctr)
    return (u >> jnp.uint64(11)).astype(jnp.float64) * (2.0 ** -53)


def _u(table, col, row0, n, lo, hi, dtype=jnp.int64):
    return (lo + jnp.floor(_raw(table, col, row0, n)
                           * (hi - lo + 1))).astype(dtype)


def _uf(table, col, row0, n, lo, hi):
    return lo + _raw(table, col, row0, n) * (hi - lo)


def _money(table, col, row0, n, lo_cents, hi_cents):
    return _u(table, col, row0, n, lo_cents, hi_cents) / 100.0


def _lines_per_order(oi: jnp.ndarray) -> jnp.ndarray:
    h = ((oi.astype(jnp.uint64) * jnp.uint64(0x9E3779B97F4A7C15))
         ^ jnp.uint64(0xBF58476D1CE4E5B9))
    return ((h >> jnp.uint64(33)) % jnp.uint64(7)
            + jnp.uint64(1)).astype(jnp.int64)


def _retailprice(pk: jnp.ndarray) -> jnp.ndarray:
    cents = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
    return cents / 100.0


def _orderkey(oi: jnp.ndarray) -> jnp.ndarray:
    return (oi // 8) * 32 + oi % 8 + 1


def _ps_suppkey(pk, slot, sf):
    s = max(int(10_000 * sf), 1)
    return (pk + slot * (s // H.SUPP_PER_PART + (pk - 1) // s)) % s + 1


def _order_dates(row0, n):
    return _u("orders", "orderdate", row0, n,
              H.START_DATE, H.END_DATE - 151, jnp.int32)


def _order_custkey(row0, n, sf):
    ncust = max(int(150_000 * sf), 3)
    ck = _u("orders", "custkey", row0, n, 1, ncust)
    ck = ck - (ck % 3 == 0)
    return jnp.maximum(ck, 1)


# ---------------------------------------------------------------------------
# line slots -> their orders, tile by tile
# ---------------------------------------------------------------------------

#: Order values are laid out in rows of _ROW orders, so that every slice
#: the expansion takes is whole rows: a gather XLA runs as one op.
_ROW = 128
#: Line slots of a tile (on a TPU v5e, an SF100 chunk's 8 M lines took
#: 5.5 ms at 128 and 5.9 ms at 256).  A tile's lines belong to the
#: WINDOW_LINES orders from the one that holds its first line (every
#: order has a line), which lie in the WINDOW_LINES // _ROW + 1 rows from
#: that order's row: each line finds its order among their starts by a
#: compare and a max over the window (`_window_max`).
WINDOW_LINES = 128
#: Line slots of a coarse tile.  A search of the rows' first starts finds
#: each coarse tile's first row; its tiles' first rows are counted among
#: the first starts of the 2 * _ROW rows from there.
COARSE_LINES = 128 * _ROW
_PAYLOAD_BITS = 12   # an order's payload: its date less START_DATE (< 2376)
_START_BIAS = 8      # a tile's first order starts at most 6 lines before it
_WINDOW_ROWS = WINDOW_LINES // _ROW + 1
_SLOT_BITS = (_WINDOW_ROWS * _ROW - 1).bit_length()
_START_SHIFT = _PAYLOAD_BITS + _SLOT_BITS
assert WINDOW_LINES % _ROW == 0 and COARSE_LINES % WINDOW_LINES == 0 \
    and COARSE_LINES <= _ROW * _ROW \
    and (WINDOW_LINES + _START_BIAS + 1) << _START_SHIFT < 2 ** 31
#: tiles a grid step of the window kernel holds, one a lane
_KERNEL_TILES = 128


def _window_max(key):
    """got[t, q] = the largest key[t, j] under (q + _START_BIAS + 1) <<
    _START_SHIFT, for the WINDOW_LINES line slots q of each tile t: a
    Pallas kernel that holds _KERNEL_TILES tiles' windows in VMEM, tiles
    along the lanes, and walks the window a row at a time.  The compare
    is never in memory: XLA's CPU backend writes it out whole, and on a
    TPU v5e XLA's fused reduction took 8.0 ms an SF100 chunk, the kernel
    5.5 ms."""
    from jax.experimental import pallas as pl

    from presto_tpu.exec import kernels as K

    nt, width = key.shape
    L, TB = WINDOW_LINES, _KERNEL_TILES
    key = jnp.pad(key, ((0, -nt % TB), (0, 0)))

    def kernel(k_ref, o_ref):
        below = (lax.broadcasted_iota(jnp.int32, (L, TB), 0)
                 + _START_BIAS + 1) << _START_SHIFT

        def step(j, acc):
            rows = k_ref[pl.ds(pl.multiple_of(j * 8, 8), 8), :]
            for r in range(8):
                row = rows[r:r + 1, :]
                acc = jnp.maximum(acc, jnp.where(row < below, row, 0))
            return acc

        o_ref[...] = lax.fori_loop(0, width // 8, step,
                                   jnp.zeros((L, TB), jnp.int32))

    # Mosaic takes 32-bit types only: trace the kernel with x64 off
    with jax.enable_x64(False):
        got = pl.pallas_call(
            kernel, grid=(key.shape[0] // TB,),
            in_specs=[pl.BlockSpec((width, TB), lambda i: (0, i))],
            out_specs=pl.BlockSpec((L, TB), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((L, key.shape[0]), jnp.int32),
            interpret=K._pallas_interpret(), name="line_orders")(key.T)
    return got.T[:nt]


def expand_orders(counts: jnp.ndarray, n: int, payload=None):
    """For `n` line slots of orders laid end to end, order i holding
    counts[i] >= 1 lines: each slot's order index, its line number in the
    order (from 1) and the order's `payload` (ints in [0, 2**12), or
    None), int32 (n,) each.  The order index is np.repeat(arange(len(
    counts)), counts), without the scatter-add, cumsum over the slots and
    gather of one index a slot that a repeat takes on the device: the
    starts are cumsummed over the orders, and each tile of WINDOW_LINES
    slots takes whole rows of them.  Slots past sum(counts) hold garbage
    for the caller to mask."""
    L, W, L1, span = WINDOW_LINES, _ROW, COARSE_LINES, _WINDOW_ROWS
    m = counts.shape[0]
    counts = counts.astype(jnp.int32)
    starts = jnp.cumsum(counts) - counts
    if payload is None:
        payload = jnp.zeros(m, jnp.int32)
    n_coarse = -(-n // L1)
    far = n_coarse * L1 + L1               # a start past every slot
    nrow = m // W + span + 1               # real rows, then rows of far starts

    def rows(a, fill, nr):
        return jnp.concatenate(
            [a, jnp.full(nr * W - a.shape[0], fill, jnp.int32)]).reshape(nr, W)

    start_rows = rows(starts, far, nrow)
    table = jnp.concatenate([start_rows, rows(payload.astype(jnp.int32), 0,
                                              nrow)], axis=1)
    heads = start_rows[:, 0]
    # coarse tiles: the row that holds each one's first line, by search
    t1 = jnp.arange(n_coarse, dtype=jnp.int32) * L1
    row1 = jnp.searchsorted(heads, t1, side="right").astype(jnp.int32) - 1
    # its tiles' rows: counted among the heads of 2 * W rows from the
    # row-of-rows that holds row1 (a coarse tile spans <= L1 / W + 1 rows)
    base1 = row1 // W * W
    hwin = jnp.take(rows(heads, far, nrow // W + 2),
                    row1[:, None] // W + jnp.arange(2), axis=0,
                    mode="clip").reshape(n_coarse, 2 * W)
    x = t1[:, None] + jnp.arange(0, L1, L, dtype=jnp.int32)
    first_row = (base1[:, None] - 1 + jnp.sum(
        hwin[:, :, None] <= x[:, None, :], axis=1,
        dtype=jnp.int32)).reshape(-1)
    # each tile's window: the starts and payloads of `span` whole rows
    win = jnp.take(table, first_row[:, None] + jnp.arange(span), axis=0,
                   mode="clip")
    nt = first_row.shape[0]
    t0 = jnp.arange(nt, dtype=jnp.int32) * L
    # key: the start (tile-relative, biased, clipped), the window slot, the
    # payload.  The starts increase along the window, so the largest key
    # of a start at or before a line is its order's.
    rel = jnp.clip(win[:, :, :W].reshape(nt, span * W) - t0[:, None]
                   + _START_BIAS, 0, L + _START_BIAS)
    slot = jnp.arange(span * W, dtype=jnp.int32)
    key = (rel << _START_SHIFT) | (slot << _PAYLOAD_BITS) \
        | win[:, :, W:].reshape(nt, span * W)
    got = _window_max(key)
    order = first_row[:, None] * W + ((got >> _PAYLOAD_BITS)
                                      & ((1 << _SLOT_BITS) - 1))
    line = (jnp.arange(L, dtype=jnp.int32) + _START_BIAS + 1
            - (got >> _START_SHIFT))
    flat = lambda a: a.reshape(-1)[:n]  # noqa: E731
    return (flat(order), flat(line),
            flat(got & ((1 << _PAYLOAD_BITS) - 1)))


# ---------------------------------------------------------------------------
# enum dictionaries: device code -> sorted-unique dictionary code LUTs
# ---------------------------------------------------------------------------


def _enum(choices: List[str]):
    """(Dictionary over sorted uniques, LUT: pick index -> dict code)."""
    values = np.unique(np.asarray(choices, dtype=object))
    lut = np.array([int(np.searchsorted(values, c)) for c in choices],
                   dtype=np.int32)
    return Dictionary(values), lut  # numpy: jit-safe host constant


def _enum2(c1: List[str], c2: List[str], sep=" "):
    combos = [a + sep + b for a in c1 for b in c2]
    values = np.unique(np.asarray(combos, dtype=object))
    lut = np.array([int(np.searchsorted(values, c)) for c in combos],
                   dtype=np.int32).reshape(len(c1), len(c2))
    return Dictionary(values), lut


def _enum3(c1, c2, c3):
    combos = [a + " " + b + " " + c for a in c1 for b in c2 for c in c3]
    values = np.unique(np.asarray(combos, dtype=object))
    lut = np.array([int(np.searchsorted(values, c)) for c in combos],
                   dtype=np.int32).reshape(len(c1), len(c2), len(c3))
    return Dictionary(values), lut


# built once per process (tiny)
_ENUMS: Dict[str, tuple] = {}


def _enums():
    if not _ENUMS:
        _ENUMS["returnflag"] = _enum(["A", "N", "R"])  # identity (sorted)
        _ENUMS["ra"] = _enum(["R", "A"])
        _ENUMS["linestatus"] = _enum(["F", "O"])
        _ENUMS["orderstatus"] = _enum(["F", "O", "P"])
        _ENUMS["segment"] = _enum(H.SEGMENTS)
        _ENUMS["priority"] = _enum(H.PRIORITIES)
        _ENUMS["instruct"] = _enum(H.INSTRUCTIONS)
        _ENUMS["mode"] = _enum(H.MODES)
        _ENUMS["container"] = _enum2(H.CONTAINER_S1, H.CONTAINER_S2)
        _ENUMS["type"] = _enum3(H.TYPE_S1, H.TYPE_S2, H.TYPE_S3)
        _ENUMS["mfgr"] = _enum([f"Manufacturer#{m}" for m in range(1, 6)])
        bvals = np.unique(np.asarray(
            [f"Brand#{m}{x}" for m in range(1, 6) for x in range(1, 6)],
            dtype=object))
        blut = np.array([[int(np.searchsorted(
            bvals, f"Brand#{m}{x}")) for x in range(1, 6)]
            for m in range(1, 6)], dtype=np.int32)
        _ENUMS["brand"] = (Dictionary(bvals), blut)
    return _ENUMS


# ---------------------------------------------------------------------------
# per-table device column generators
# generators return (data, dictionary) — dictionary None for plain types
# ---------------------------------------------------------------------------


def _gen_customer(sf, row0, n, cols):
    E = _enums()
    out = {}
    if "c_custkey" in cols:
        out["c_custkey"] = (row0 + 1 + jnp.arange(n, dtype=jnp.int64), None)
    if "c_nationkey" in cols:
        out["c_nationkey"] = (_u("customer", "nation", row0, n, 0, 24), None)
    if "c_acctbal" in cols:
        out["c_acctbal"] = (_money("customer", "acctbal", row0, n,
                                   -99999, 999999), None)
    if "c_mktsegment" in cols:
        d, lut = E["segment"]
        idx = _u("customer", "segment", row0, n, 0,
                 len(H.SEGMENTS) - 1, jnp.int32)
        out["c_mktsegment"] = (jnp.asarray(lut)[idx], d)
    if "c_name" in cols:
        ck = row0 + 1 + jnp.arange(n, dtype=jnp.int64)
        ncust = H.row_count("customer", sf)
        out["c_name"] = (ck.astype(jnp.int32),
                         FormatDictionary("Customer#", 9, ncust + 1))
    return out


def _gen_orders(sf, row0, n, cols):
    E = _enums()
    out = {}
    oi = jnp.arange(n, dtype=jnp.int64) + row0
    if "o_orderkey" in cols:
        out["o_orderkey"] = (_orderkey(oi), None)
    if "o_custkey" in cols:
        out["o_custkey"] = (_order_custkey(row0, n, sf), None)
    if "o_orderstatus" in cols:
        d, lut = E["orderstatus"]
        odate = _order_dates(row0, n)
        # F < O < P sorted: F=0, O=1, P=2
        code = jnp.where(odate + 121 < H.CURRENT_DATE, 0,
                         jnp.where(odate > H.CURRENT_DATE, 1, 2))
        out["o_orderstatus"] = (jnp.asarray(lut)[code], d)
    if "o_totalprice" in cols:
        out["o_totalprice"] = (_money("orders", "totalprice", row0, n,
                                      85000, 55000000), None)
    if "o_orderdate" in cols:
        out["o_orderdate"] = (_order_dates(row0, n), None)
    if "o_orderpriority" in cols:
        d, lut = E["priority"]
        idx = _u("orders", "priority", row0, n, 0,
                 len(H.PRIORITIES) - 1, jnp.int32)
        out["o_orderpriority"] = (jnp.asarray(lut)[idx], d)
    if "o_clerk" in cols:
        nclerk = max(int(1000 * sf), 1)
        ck = _u("orders", "clerk", row0, n, 1, nclerk, jnp.int32)
        out["o_clerk"] = (ck, FormatDictionary("Clerk#", 9, nclerk + 1))
    if "o_shippriority" in cols:
        out["o_shippriority"] = (jnp.zeros(n, jnp.int32), None)
    return out


#: lineitem columns made from their order's date
_ODATE_COLUMNS = {"l_shipdate", "l_commitdate", "l_receiptdate",
                  "l_returnflag", "l_linestatus"}
#: lineitem columns that take their order's values from expand_orders
WINDOW_COLUMNS = _ODATE_COLUMNS | {"l_orderkey", "l_linenumber"}


def _gen_lineitem(sf, order_row0, order_row1, cols,
                  n_orders=None, line_row0=None, pad=None):
    """row0/row1 index ORDERS rows, like the host generator.  Chunked
    callers pass static sizes (n_orders orders padded, pad lineitem
    rows) with possibly-traced starts (order_row0, line_row0); rows past
    the real chunk extent are garbage the caller masks via sel."""
    t = "lineitem"
    E = _enums()
    if n_orders is None:
        n_orders = order_row1 - order_row0
    counts = _lines_per_order(jnp.arange(n_orders, dtype=jnp.int64)
                              + order_row0)
    if pad is None:
        lo, hi = H.lineitem_offsets(order_row0, order_row1)
        n = hi - lo
        row0 = lo
    else:
        n = pad
        row0 = line_row0
    out = {}
    need_odate = bool(_ODATE_COLUMNS & cols)
    odate = None
    if WINDOW_COLUMNS & cols:
        line_order, linenumber, odate = expand_orders(
            counts, n, (_order_dates(order_row0, n_orders) - H.START_DATE)
            if need_odate else None)
        odate = odate.astype(jnp.int64) + H.START_DATE
    if "l_orderkey" in cols:
        out["l_orderkey"] = (_orderkey(line_order.astype(jnp.int64)
                                       + order_row0), None)
    pk = None
    if "l_partkey" in cols or "l_suppkey" in cols \
            or "l_extendedprice" in cols:
        npart = max(int(200_000 * sf), H.SUPP_PER_PART)
        pk = _u(t, "partkey", row0, n, 1, npart)
    if "l_partkey" in cols:
        out["l_partkey"] = (pk, None)
    if "l_suppkey" in cols:
        slot = _u(t, "suppslot", row0, n, 0, H.SUPP_PER_PART - 1)
        out["l_suppkey"] = (_ps_suppkey(pk, slot, sf), None)
    if "l_linenumber" in cols:
        out["l_linenumber"] = (linenumber, None)
    qty = None
    if "l_quantity" in cols or "l_extendedprice" in cols:
        qty = _u(t, "quantity", row0, n, 1, 50).astype(jnp.float64)
    if "l_quantity" in cols:
        out["l_quantity"] = (qty, None)
    if "l_extendedprice" in cols:
        out["l_extendedprice"] = (_retailprice(pk) * qty, None)
    if "l_discount" in cols:
        out["l_discount"] = (_u(t, "discount", row0, n, 0, 10) / 100.0, None)
    if "l_tax" in cols:
        out["l_tax"] = (_u(t, "tax", row0, n, 0, 8) / 100.0, None)
    shipdate = None
    if any(c in cols for c in ("l_shipdate", "l_receiptdate",
                               "l_returnflag", "l_linestatus")):
        shipdate = (odate + _u(t, "shipdelta", row0, n, 1, 121,
                               jnp.int32)).astype(jnp.int32)
    if "l_shipdate" in cols:
        out["l_shipdate"] = (shipdate, None)
    if "l_commitdate" in cols:
        out["l_commitdate"] = ((odate + _u(t, "commitdelta", row0, n, 30, 90,
                                           jnp.int32)).astype(jnp.int32),
                               None)
    receiptdate = None
    if "l_receiptdate" in cols or "l_returnflag" in cols:
        receiptdate = shipdate + _u(t, "receiptdelta", row0, n, 1, 30,
                                    jnp.int32)
    if "l_receiptdate" in cols:
        out["l_receiptdate"] = (receiptdate, None)
    if "l_returnflag" in cols:
        d, _ = E["returnflag"]  # sorted A,N,R
        ra = _u(t, "returnflag", row0, n, 0, 1, jnp.int32)  # 0=R 1=A
        code = jnp.where(receiptdate <= H.CURRENT_DATE,
                         jnp.where(ra == 0, 2, 0), 1)
        out["l_returnflag"] = (code.astype(jnp.int32), d)
    if "l_linestatus" in cols:
        d, _ = E["linestatus"]  # F=0 O=1
        out["l_linestatus"] = (
            (shipdate > H.CURRENT_DATE).astype(jnp.int32), d)
    if "l_shipinstruct" in cols:
        d, lut = E["instruct"]
        idx = _u(t, "instruct", row0, n, 0,
                 len(H.INSTRUCTIONS) - 1, jnp.int32)
        out["l_shipinstruct"] = (jnp.asarray(lut)[idx], d)
    if "l_shipmode" in cols:
        d, lut = E["mode"]
        idx = _u(t, "mode", row0, n, 0, len(H.MODES) - 1, jnp.int32)
        out["l_shipmode"] = (jnp.asarray(lut)[idx], d)
    return out


def _gen_part(sf, row0, n, cols):
    t = "part"
    E = _enums()
    out = {}
    pk = row0 + 1 + jnp.arange(n, dtype=jnp.int64)
    if "p_partkey" in cols:
        out["p_partkey"] = (pk, None)
    bm = bn = None
    if "p_mfgr" in cols or "p_brand" in cols:
        bm = _u(t, "brand_m", row0, n, 1, 5, jnp.int32)
        bn = _u(t, "brand_n", row0, n, 1, 5, jnp.int32)
    if "p_mfgr" in cols:
        d, lut = E["mfgr"]
        out["p_mfgr"] = (jnp.asarray(lut)[bm - 1], d)
    if "p_brand" in cols:
        d, lut = E["brand"]
        out["p_brand"] = (jnp.asarray(lut)[bm - 1, bn - 1], d)
    if "p_type" in cols:
        d, lut = E["type"]
        i1 = _u(t, "type1", row0, n, 0, len(H.TYPE_S1) - 1, jnp.int32)
        i2 = _u(t, "type2", row0, n, 0, len(H.TYPE_S2) - 1, jnp.int32)
        i3 = _u(t, "type3", row0, n, 0, len(H.TYPE_S3) - 1, jnp.int32)
        out["p_type"] = (jnp.asarray(lut)[i1, i2, i3], d)
    if "p_size" in cols:
        out["p_size"] = (_u(t, "size", row0, n, 1, 50, jnp.int32), None)
    if "p_container" in cols:
        d, lut = E["container"]
        i1 = _u(t, "cont1", row0, n, 0, len(H.CONTAINER_S1) - 1, jnp.int32)
        i2 = _u(t, "cont2", row0, n, 0, len(H.CONTAINER_S2) - 1, jnp.int32)
        out["p_container"] = (jnp.asarray(lut)[i1, i2], d)
    if "p_retailprice" in cols:
        out["p_retailprice"] = (_retailprice(pk), None)
    for c in cols:
        if c.startswith("p_name$contains$"):
            word = c.rsplit("$", 1)[1]
            target = H.COLORS.index(word)
            hit = jnp.zeros(n, bool)
            for j in range(5):
                idx = jnp.floor(_raw(t, "name", row0, n, draw=j, k=5)
                                * len(H.COLORS)).astype(jnp.int32)
                hit = hit | (idx == target)
            out[c] = (hit, None)
    return out


def _gen_supplier(sf, row0, n, cols):
    out = {}
    sk = row0 + 1 + jnp.arange(n, dtype=jnp.int64)
    if "s_suppkey" in cols:
        out["s_suppkey"] = (sk, None)
    if "s_nationkey" in cols:
        out["s_nationkey"] = (_u("supplier", "nation", row0, n, 0, 24), None)
    if "s_acctbal" in cols:
        out["s_acctbal"] = (_money("supplier", "acctbal", row0, n,
                                   -99999, 999999), None)
    if "s_name" in cols:
        nsupp = H.row_count("supplier", sf)
        out["s_name"] = (sk.astype(jnp.int32),
                         FormatDictionary("Supplier#", 9, nsupp + 1))
    return out


def _gen_partsupp(sf, row0, n, cols):
    t = "partsupp"
    out = {}
    r = jnp.arange(n, dtype=jnp.int64) + row0
    pk = r // H.SUPP_PER_PART + 1
    if "ps_partkey" in cols:
        out["ps_partkey"] = (pk, None)
    if "ps_suppkey" in cols:
        out["ps_suppkey"] = (_ps_suppkey(pk, r % H.SUPP_PER_PART, sf), None)
    if "ps_availqty" in cols:
        out["ps_availqty"] = (_u(t, "availqty", row0, n, 1, 9999,
                                 jnp.int32), None)
    if "ps_supplycost" in cols:
        out["ps_supplycost"] = (_money(t, "supplycost", row0, n,
                                       100, 100000), None)
    return out


_DEVICE_GENERATORS = {
    "customer": _gen_customer,
    "orders": _gen_orders,
    "lineitem": _gen_lineitem,
    "part": _gen_part,
    "supplier": _gen_supplier,
    "partsupp": _gen_partsupp,
}

# columns each table can produce on device
DEVICE_COLUMNS = {
    "customer": {"c_custkey", "c_nationkey", "c_acctbal", "c_mktsegment",
                 "c_name"},
    "orders": {"o_orderkey", "o_custkey", "o_orderstatus", "o_totalprice",
               "o_orderdate", "o_orderpriority", "o_clerk",
               "o_shippriority"},
    "lineitem": {"l_orderkey", "l_partkey", "l_suppkey", "l_linenumber",
                 "l_quantity", "l_extendedprice", "l_discount", "l_tax",
                 "l_returnflag", "l_linestatus", "l_shipdate",
                 "l_commitdate", "l_receiptdate", "l_shipinstruct",
                 "l_shipmode"},
    "part": {"p_partkey", "p_mfgr", "p_brand", "p_type", "p_size",
             "p_container", "p_retailprice"},  # + p_name$contains$<w>
             # virtual predicate columns (is_device_generable)
    "supplier": {"s_suppkey", "s_nationkey", "s_acctbal", "s_name"},
    "partsupp": {"ps_partkey", "ps_suppkey", "ps_availqty",
                 "ps_supplycost"},
}


def generate_device(table: str, sf: float, cols: List[str],
                    row0: int = 0, row1: Optional[int] = None,
                    f32: bool = False, pad: Optional[int] = None,
                    n_orders: Optional[int] = None,
                    line_row0=None) -> Dict[str, Column]:
    """Generate `cols` of `table` rows [row0,row1) on the default device
    (orders-row ranges for lineitem, like the host generator).  DOUBLE
    columns come back f32 when f32=True (saves HBM + emulated-f64 math
    for the float32_compute session mode).

    Chunked mode (pad is not None): shapes are STATIC (pad rows; for
    lineitem additionally n_orders padded orders) while the starts
    (row0, line_row0) may be traced scalars — one compiled program
    serves every chunk.  Rows past the real chunk extent are garbage
    the caller must mask via the batch sel."""
    schema = H.SCHEMAS[table]
    if pad is not None:
        if table == "lineitem":
            raw = _gen_lineitem(sf, row0, None, set(cols),
                                n_orders=n_orders, line_row0=line_row0,
                                pad=pad)
        else:
            raw = _DEVICE_GENERATORS[table](sf, row0, pad, set(cols))
        out = {}
        for c in cols:
            if c not in raw:
                raise KeyError(
                    f"column {c} of {table} is not device-generable")
            data, dic = raw[c]
            typ = schema.get(c, T.BOOLEAN)  # virtual predicate columns
            if f32 and typ.name == "DOUBLE":
                data = data.astype(jnp.float32)
            out[c] = Column(data, None, typ, dic)
        return out
    gen = _DEVICE_GENERATORS[table]
    if table == "lineitem":
        total = int(H._TABLE_ROWS["orders"] * sf)
    else:
        total = H.row_count(table, sf)
    row1 = total if row1 is None else min(row1, total)
    if table == "lineitem":
        raw = gen(sf, row0, row1, set(cols))
    else:
        raw = gen(sf, row0, row1 - row0, set(cols))
    out = {}
    for c in cols:
        if c not in raw:
            raise KeyError(f"column {c} of {table} is not device-generable")
        data, dic = raw[c]
        typ = schema.get(c, T.BOOLEAN)  # virtual predicate columns
        if f32 and typ.name == "DOUBLE":
            data = data.astype(jnp.float32)
        out[c] = Column(data, None, typ, dic)
    return out


def is_device_generable(table: str, col: str) -> bool:
    if col in DEVICE_COLUMNS.get(table, set()):
        return True
    return table == "part" and col.startswith("p_name$contains$")


# ---------------------------------------------------------------------------
# connector bucketing SPI (chunk family): how lineitem/orders stream
# chunk-wise through grouped execution.  Reference: connector bucketing
# (ConnectorNodePartitioningProvider, Connector.java:74, BucketNodeMap)
# + grouped execution (StageExecutionDescriptor.java:24-27,
# Lifespan.java:26-38).  TPU-native adaptation: a bucket is an
# order-row RANGE (range-bucketing colocates orderkey equi-joins the
# same way hash-bucketing does), and the "page source" for a bucket is
# device-side generation inside the consuming XLA program.
# ---------------------------------------------------------------------------


DEFAULT_CHUNK_ORDERS = 2_000_000


class TpchChunkGrid:
    """One chunk plan: order-row edges + lineitem offsets, static pad
    capacities, and the in-trace scan builder."""

    def __init__(self, sf: float, order_edges, line_offsets):
        self.sf = sf
        self.order_edges = order_edges
        self.line_offsets = line_offsets
        self.nchunks = len(order_edges) - 1
        self.cap_orders = max(b - a for a, b in zip(order_edges[:-1],
                                                    order_edges[1:]))
        self.cap_lines = max(b - a for a, b in zip(line_offsets[:-1],
                                                   line_offsets[1:]))

    def capacity(self, table: str) -> int:
        return self.cap_lines if table == "lineitem" else self.cap_orders

    def row_edges(self, table: str):
        """The table's own row range of every chunk, as edges."""
        return self.line_offsets if table == "lineitem" else self.order_edges

    def exchange_bound(self) -> int:
        """Default per-chunk compact bound for exchange outputs (chunk
        outputs are reductions of the chunk — aggregates on the bucket
        key, selective filters)."""
        return self.cap_orders

    def bucket_ndv(self) -> int:
        """Distinct bucket (orderkey) values in any one chunk — lets the
        chunked runner bound a per-chunk GROUP BY bucket_key output at
        order grain instead of lineitem grain."""
        return self.cap_orders

    def chunk_column_domain(self, table: str, col: str, i: int):
        """Zone map of `col` over chunk i, or None when unknowable —
        the dynamic-filtering chunk-pruning hook (exec/chunked.py):
        chunks whose range misses a runtime filter's domain are skipped
        before their program is ever dispatched.  Only the bucket
        column has a closed form: chunk i covers order rows
        [edges[i], edges[i+1]), and the sparse dbgen orderkey layout
        (8 keys per 32-key block) is monotone in the row index."""
        if table not in ("lineitem", "orders") or \
                col not in ("l_orderkey", "o_orderkey"):
            return None
        o0 = self.order_edges[i]
        o1 = self.order_edges[i + 1]
        if o1 <= o0:
            return None
        key = lambda oi: (oi // 8) * 32 + oi % 8 + 1  # noqa: E731
        return int(key(o0)), int(key(o1 - 1))

    def window_lines(self, table: str, cols) -> int:
        """Line slots a chunk's scan of `cols` expands from its orders
        (expand_orders): QueryStats.lineitem_window_lines a launch."""
        if table == "lineitem" and WINDOW_COLUMNS & set(cols):
            return self.cap_lines
        return 0

    def chunk_args(self, i: int):
        """Traced scalars for chunk i — a fixed pytree so ONE jitted
        program serves every chunk."""
        o0 = self.order_edges[i]
        o1 = self.order_edges[i + 1]
        return (jnp.asarray(o0, jnp.int64),
                jnp.asarray(self.line_offsets[i], jnp.int64),
                jnp.asarray(o1 - o0, jnp.int32),
                jnp.asarray(self.line_offsets[i + 1]
                            - self.line_offsets[i], jnp.int32))

    def build_scan(self, table: str, cols: List[str], args, f32: bool):
        """(raw {col: Column}, sel) for one chunk of `table`, inside the
        traced program."""
        o0, line0, n_ord, n_line = args
        if table == "lineitem":
            raw = generate_device(
                "lineitem", self.sf, cols, row0=o0, f32=f32,
                pad=self.cap_lines, n_orders=self.cap_orders,
                line_row0=line0)
            sel = jnp.arange(self.cap_lines) < n_line
        else:   # orders, or a table that shard_grid cut by its own rows
            raw = generate_device(table, self.sf, cols, row0=o0,
                                  f32=f32, pad=self.cap_orders)
            sel = jnp.arange(self.cap_orders) < n_ord
        return raw, sel


def shard_grid(table: str, sf: float, ndev: int) -> TpchChunkGrid:
    """The chunk grid cut for a mesh: `ndev` contiguous primary-key
    ranges of `table`, one a shard (parallel/dist_executor.sharded_scan
    generates each on the chip that holds it).  lineitem and orders are
    cut by the same order rows, so an order's lines lie on the shard
    that holds the order; any other table by its own rows, which then
    stand where the grid says orders.  Trailing ranges may be empty."""
    by_orders = table in TpchChunkFamily.BUCKET_COLUMNS
    total = int(H._TABLE_ROWS["orders"] * sf) if by_orders \
        else H.row_count(table, sf)
    per = max(-(-total // ndev), 1)
    edges = [min(i * per, total) for i in range(ndev + 1)]
    lines = edges
    if table == "lineitem":
        before = np.concatenate([[0], np.cumsum(H._lines_per_order(
            np.arange(total, dtype=np.int64)))])
        lines = [int(before[e]) for e in edges]
    return TpchChunkGrid(sf, edges, lines)


class TpchChunkFamily:
    """lineitem+orders co-bucketed on orderkey (reference:
    TpchNodePartitioningProvider buckets both on orderkey so the Q18
    join is colocated, presto-tpch/.../TpchNodePartitioningProvider)."""

    name = "tpch-orders"
    BUCKET_COLUMNS = {"lineitem": "l_orderkey", "orders": "o_orderkey"}

    def __init__(self, sf: float):
        self.sf = sf

    def tables(self):
        return set(self.BUCKET_COLUMNS)

    def bucket_column(self, table: str) -> str:
        return self.BUCKET_COLUMNS[table]

    def device_columns(self, table: str):
        return DEVICE_COLUMNS.get(table, set())

    def make_grid(self, session) -> TpchChunkGrid:
        chunk_orders = int(session.properties.get(
            "chunk_orders", DEFAULT_CHUNK_ORDERS))
        edges, line_offsets = H.chunk_grid(self.sf, chunk_orders)
        return TpchChunkGrid(self.sf, edges, line_offsets)


def chunk_family(table: str, sf: float):
    """Bucketing metadata for `table`, or None (the connector SPI hook
    TpchTable.bucketing delegates to)."""
    if table in TpchChunkFamily.BUCKET_COLUMNS:
        return TpchChunkFamily(sf)
    return None
