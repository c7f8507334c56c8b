"""Device-side TPC-DS fact-table generation + chunk families.

Reference parity: presto-tpcds generates rows inside the scan operator
(TpcdsRecordSet wrapping dsdgen); grouped execution streams bucketed
fact tables one bucket at a time (Lifespan.java:26-38,
StageExecutionDescriptor.java:24-27); connector bucketing colocates the
sales<->returns joins (ConnectorNodePartitioningProvider,
Connector.java:74).  TPU-native adaptation: the host generator
(connectors/tpcds.py) is a counter-based splitmix64 hash, pure integer
math — so any row range of any fact column is producible ON DEVICE by
the same XLA program that consumes it.  That is what makes TPC-DS
SF100 (store_sales ~288M rows) runnable on one chip: the scan never
exists anywhere, each chunk is generated, filtered and reduced inside
one compiled program.

The four big fact tables (store_sales, store_returns, catalog_sales,
catalog_returns) are fully numeric — every column is device-generable
(dates/customers/items are _sk ints) — so unlike TPC-H no dictionary
machinery is needed.

Chunk families (bucketing metadata the chunked runner consumes):
- store:   store_sales + store_returns co-bucketed on ticket_number.
  A chunk is a sales-row range aligned to ticket boundaries
  (ticket = row // 3 + 1); the returns rows for those sales are exactly
  j in [ceil(a/10), ceil(b/10)) because return j's parent sale is row
  j*10 — both stream with pure arithmetic offsets.
- catalog: catalog_sales + catalog_returns co-bucketed on order_number
  (order = row // 4 + 1), same construction.

Exactness: every formula mirrors connectors/tpcds.py bit-for-bit (same
splitmix64 counters, same f64 scaling/rounding), validated
column-for-column in tests/test_tpcds_device.py.
"""

from __future__ import annotations

from typing import Dict, List

import jax.numpy as jnp

from presto_tpu.batch import Column
from presto_tpu.connectors import tpcds as DS
from presto_tpu.connectors.tpch_device import _mix


# ---------------------------------------------------------------------------
# counter-based draws on device (bit-identical to tpcds.py's _raw_at)
# ---------------------------------------------------------------------------


def _key(table: str, col: str) -> int:
    """Host-precomputed (colkey * 0x632BE59BD9B4E019) mod 2^64 — numpy
    wraps the product; the device adds the wrapped constant."""
    return (int(DS._colkey("tpcds/" + table, col))
            * 0x632BE59BD9B4E019) % (1 << 64)


def _raw_at(table, col, rows, draw: int = 0, k: int = 1) -> jnp.ndarray:
    ctr = (rows.astype(jnp.uint64) * jnp.uint64(k) + jnp.uint64(draw)
           + jnp.uint64(_key(table, col)))
    u = _mix(ctr)
    return (u >> jnp.uint64(11)).astype(jnp.float64) * (2.0 ** -53)


def _u_at(table, col, rows, lo, hi, dtype=jnp.int64):
    return (lo + jnp.floor(_raw_at(table, col, rows)
                           * (hi - lo + 1))).astype(dtype)


def _money_at(table, col, rows, lo_cents, hi_cents):
    # * 0.01 (not / 100): must match the host generator's explicit
    # reciprocal-multiply, see tpcds._round
    return _u_at(table, col, rows, lo_cents, hi_cents) * 0.01


def _rint(x: jnp.ndarray) -> jnp.ndarray:
    """Exact round-half-to-even (np.rint semantics) built from floor.
    NOT lax.round: this environment's XLA CPU lowering of
    round_nearest_even is off-by-one near .5 boundaries for f64
    (lax.round(7582.499773998605) == 7581.0, lax.round(.49999999999999994)
    == -1.0), which would desync device generation from the host
    generator by whole cents."""
    f = jnp.floor(x)
    diff = x - f
    up = (diff > 0.5) | ((diff == 0.5) & (jnp.floor(f / 2) * 2 != f))
    r = f + up
    # beyond 2^52 every f64 is integral (and diff math loses meaning)
    return jnp.where(jnp.abs(x) >= 2.0 ** 52, x, r)


def _round2(x):
    """tpcds._round(x, 2) bit-for-bit: scale, rint, reciprocal-multiply
    (XLA's div-by-constant rewrite makes /100.0 a different operation
    under jit than on the host)."""
    return _rint(x * 100.0) * 0.01


# ---------------------------------------------------------------------------
# store channel
# ---------------------------------------------------------------------------


def _store_sales_cols(sf, rows, cols) -> Dict[str, jnp.ndarray]:
    """store_sales columns for explicit (possibly traced) row indices —
    mirrors tpcds._store_sales_cols formula-for-formula, computing only
    what `cols` needs."""
    t = "store_sales"
    need = set(cols)
    out = {}
    ticket = rows.astype(jnp.int64) // DS.ITEMS_PER_TICKET + 1
    if "ss_ticket_number" in need:
        out["ss_ticket_number"] = ticket
    # per-ticket attributes: drawn from the ticket counter, not the row
    if "ss_customer_sk" in need:
        out["ss_customer_sk"] = _u_at(t, "cust", ticket, 1,
                                      DS.row_count("customer", sf))
    if "ss_hdemo_sk" in need:
        out["ss_hdemo_sk"] = _u_at(
            t, "hdemo", ticket, 1,
            DS._FIXED_ROWS["household_demographics"])
    if "ss_addr_sk" in need:
        out["ss_addr_sk"] = _u_at(t, "addr", ticket, 1,
                                  DS.row_count("customer_address", sf))
    if "ss_store_sk" in need:
        out["ss_store_sk"] = _u_at(t, "store", ticket, 1,
                                   DS.row_count("store", sf))
    if "ss_sold_date_sk" in need:
        out["ss_sold_date_sk"] = _u_at(t, "date", ticket,
                                       DS.SALES_DATE_LO, DS.SALES_DATE_HI)
    # per-row attributes
    if "ss_sold_time_sk" in need:
        out["ss_sold_time_sk"] = _u_at(t, "time", rows, 28800, 75600)
    if "ss_item_sk" in need:
        out["ss_item_sk"] = _u_at(t, "item", rows, 1,
                                  DS.row_count("item", sf))
    if "ss_cdemo_sk" in need:
        out["ss_cdemo_sk"] = _u_at(
            t, "cdemo", rows, 1,
            DS.row_count("customer_demographics", sf))
    if "ss_promo_sk" in need:
        out["ss_promo_sk"] = _u_at(t, "promo", rows, 1,
                                   DS.row_count("promotion", sf))
    money = need & {"ss_quantity", "ss_wholesale_cost", "ss_list_price",
                    "ss_sales_price", "ss_ext_discount_amt",
                    "ss_ext_sales_price", "ss_ext_wholesale_cost",
                    "ss_ext_list_price", "ss_ext_tax", "ss_coupon_amt",
                    "ss_net_paid", "ss_net_paid_inc_tax", "ss_net_profit"}
    if money:
        qty = _u_at(t, "qty", rows, 1, 100, jnp.int32)
        wholesale = _money_at(t, "wholesale", rows, 100, 10_000)
        markup = _raw_at(t, "markup", rows) * 1.0
        discount = _raw_at(t, "discount", rows)
        list_price = _round2(wholesale * (1.0 + markup))
        sales_price = _round2(list_price * (1.0 - discount))
        qf = qty.astype(jnp.float64)
        ext_list = _round2(list_price * qf)
        ext_sales = _round2(sales_price * qf)
        ext_wholesale = _round2(wholesale * qf)
        coupon = _round2(ext_sales * (_raw_at(t, "coupon", rows) < 0.2)
                         * _raw_at(t, "coupamt", rows) * 0.5)
        net_paid = _round2(ext_sales - coupon)
        tax = _round2(net_paid * 0.08)
        vals = {
            "ss_quantity": qty,
            "ss_wholesale_cost": wholesale,
            "ss_list_price": list_price,
            "ss_sales_price": sales_price,
            "ss_ext_discount_amt": _round2(ext_list - ext_sales),
            "ss_ext_sales_price": ext_sales,
            "ss_ext_wholesale_cost": ext_wholesale,
            "ss_ext_list_price": ext_list,
            "ss_ext_tax": tax,
            "ss_coupon_amt": coupon,
            "ss_net_paid": net_paid,
            "ss_net_paid_inc_tax": _round2(net_paid + tax),
            "ss_net_profit": _round2(net_paid - ext_wholesale),
        }
        out.update({c: vals[c] for c in money})
    return out


def _store_returns_cols(sf, j, cols) -> Dict[str, jnp.ndarray]:
    """store_returns columns for return indices `j` — reads the parent
    sale's draws at row j*RETURN_EVERY like tpcds._gen_store_returns."""
    t = "store_returns"
    need = set(cols)
    parent = j.astype(jnp.int64) * DS.RETURN_EVERY
    parent_need = set()
    if need & {"sr_returned_date_sk"}:
        parent_need.add("ss_sold_date_sk")
    if "sr_item_sk" in need:
        parent_need.add("ss_item_sk")
    if "sr_customer_sk" in need:
        parent_need.add("ss_customer_sk")
    if "sr_cdemo_sk" in need:
        parent_need.add("ss_cdemo_sk")
    if "sr_hdemo_sk" in need:
        parent_need.add("ss_hdemo_sk")
    if "sr_addr_sk" in need:
        parent_need.add("ss_addr_sk")
    if "sr_store_sk" in need:
        parent_need.add("ss_store_sk")
    if "sr_ticket_number" in need:
        parent_need.add("ss_ticket_number")
    amount_cols = need & {"sr_return_quantity", "sr_return_amt",
                          "sr_return_tax", "sr_return_amt_inc_tax",
                          "sr_fee", "sr_return_ship_cost",
                          "sr_refunded_cash", "sr_reversed_charge",
                          "sr_store_credit", "sr_net_loss"}
    if amount_cols:
        parent_need |= {"ss_sales_price", "ss_quantity"}
    ss = _store_sales_cols(sf, parent, parent_need)
    out = {}
    if "sr_returned_date_sk" in need:
        out["sr_returned_date_sk"] = (ss["ss_sold_date_sk"]
                                      + _u_at(t, "lag", j, 1, 60))
    if "sr_return_time_sk" in need:
        out["sr_return_time_sk"] = _u_at(t, "time", j, 28800, 75600)
    for sr, sscol in (("sr_item_sk", "ss_item_sk"),
                      ("sr_customer_sk", "ss_customer_sk"),
                      ("sr_cdemo_sk", "ss_cdemo_sk"),
                      ("sr_hdemo_sk", "ss_hdemo_sk"),
                      ("sr_addr_sk", "ss_addr_sk"),
                      ("sr_store_sk", "ss_store_sk"),
                      ("sr_ticket_number", "ss_ticket_number")):
        if sr in need:
            out[sr] = ss[sscol]
    if "sr_reason_sk" in need:
        out["sr_reason_sk"] = _u_at(t, "reason", j, 1,
                                    DS._FIXED_ROWS["reason"])
    if amount_cols:
        ret_qty = jnp.minimum(_u_at(t, "qty", j, 1, 100, jnp.int32),
                              ss["ss_quantity"])
        amt = _round2(ss["ss_sales_price"] * ret_qty)
        tax = _round2(amt * 0.08)
        fee = _money_at(t, "fee", j, 50, 10_000)
        ship = _money_at(t, "ship", j, 0, 10_000)
        frac = _raw_at(t, "cashfrac", j)
        cash = _round2(amt * frac)
        charge = _round2((amt - cash) * _raw_at(t, "chargefrac", j))
        credit = _round2(amt - cash - charge)
        vals = {
            "sr_return_quantity": ret_qty,
            "sr_return_amt": amt,
            "sr_return_tax": tax,
            "sr_return_amt_inc_tax": _round2(amt + tax),
            "sr_fee": fee,
            "sr_return_ship_cost": ship,
            "sr_refunded_cash": cash,
            "sr_reversed_charge": charge,
            "sr_store_credit": credit,
            "sr_net_loss": _round2(fee + ship + tax),
        }
        out.update({c: vals[c] for c in amount_cols})
    return out


# ---------------------------------------------------------------------------
# catalog channel
# ---------------------------------------------------------------------------


def _sales_money_cols(t, rows, need) -> Dict[str, jnp.ndarray]:
    """Device mirror of tpcds._sales_money_cols (channel-shared pricing
    math), computing only the suffixes `need` asks for."""
    qty = _u_at(t, "qty", rows, 1, 100, jnp.int32)
    wholesale = _money_at(t, "wholesale", rows, 100, 10_000)
    markup = _raw_at(t, "markup", rows)
    discount = _raw_at(t, "discount", rows)
    list_price = _round2(wholesale * (1.0 + markup))
    sales_price = _round2(list_price * (1.0 - discount))
    qf = qty.astype(jnp.float64)
    ext_list = _round2(list_price * qf)
    ext_sales = _round2(sales_price * qf)
    ext_wholesale = _round2(wholesale * qf)
    coupon = _round2(ext_sales * (_raw_at(t, "coupon", rows) < 0.2)
                     * _raw_at(t, "coupamt", rows) * 0.5)
    ship_cost = _money_at(t, "shipc", rows, 0, 5_000) * qf
    net_paid = _round2(ext_sales - coupon)
    tax = _round2(net_paid * 0.08)
    vals = {
        "quantity": qty, "wholesale_cost": wholesale,
        "list_price": list_price, "sales_price": sales_price,
        "ext_discount_amt": _round2(ext_list - ext_sales),
        "ext_sales_price": ext_sales, "ext_wholesale_cost": ext_wholesale,
        "ext_list_price": ext_list, "ext_tax": tax, "coupon_amt": coupon,
        "ext_ship_cost": _round2(ship_cost), "net_paid": net_paid,
        "net_paid_inc_tax": _round2(net_paid + tax),
        "net_paid_inc_ship": _round2(net_paid + ship_cost),
        "net_paid_inc_ship_tax": _round2(net_paid + ship_cost + tax),
        "net_profit": _round2(net_paid - ext_wholesale),
    }
    return {k: v for k, v in vals.items() if k in need}


_CS_MONEY = {"quantity", "wholesale_cost", "list_price", "sales_price",
             "ext_discount_amt", "ext_sales_price", "ext_wholesale_cost",
             "ext_list_price", "ext_tax", "coupon_amt", "ext_ship_cost",
             "net_paid", "net_paid_inc_tax", "net_paid_inc_ship",
             "net_paid_inc_ship_tax", "net_profit"}


def _catalog_sales_cols(sf, rows, cols) -> Dict[str, jnp.ndarray]:
    t = "catalog_sales"
    need = set(cols)
    out = {}
    order = rows.astype(jnp.int64) // DS.ITEMS_PER_ORDER + 1
    if "cs_order_number" in need:
        out["cs_order_number"] = order
    n_cust = DS.row_count("customer", sf)
    n_cd = DS.row_count("customer_demographics", sf)
    n_hd = DS._FIXED_ROWS["household_demographics"]
    n_addr = DS.row_count("customer_address", sf)
    if "cs_bill_customer_sk" in need:
        out["cs_bill_customer_sk"] = _u_at(t, "bcust", order, 1, n_cust)
    if "cs_ship_customer_sk" in need:
        out["cs_ship_customer_sk"] = _u_at(t, "scust", order, 1, n_cust)
    sold = None
    if need & {"cs_sold_date_sk", "cs_ship_date_sk"}:
        sold = _u_at(t, "date", order, DS.SALES_DATE_LO, DS.SALES_DATE_HI)
    if "cs_sold_date_sk" in need:
        out["cs_sold_date_sk"] = sold
    if "cs_ship_date_sk" in need:
        out["cs_ship_date_sk"] = sold + _u_at(t, "shiplag", rows, 2, 90)
    if "cs_sold_time_sk" in need:
        out["cs_sold_time_sk"] = _u_at(t, "time", rows, 28800, 75600)
    if "cs_bill_cdemo_sk" in need:
        out["cs_bill_cdemo_sk"] = _u_at(t, "bcdemo", rows, 1, n_cd)
    if "cs_bill_hdemo_sk" in need:
        out["cs_bill_hdemo_sk"] = _u_at(t, "bhdemo", order, 1, n_hd)
    if "cs_bill_addr_sk" in need:
        out["cs_bill_addr_sk"] = _u_at(t, "baddr", order, 1, n_addr)
    if "cs_ship_cdemo_sk" in need:
        out["cs_ship_cdemo_sk"] = _u_at(t, "scdemo", rows, 1, n_cd)
    if "cs_ship_hdemo_sk" in need:
        out["cs_ship_hdemo_sk"] = _u_at(t, "shdemo", order, 1, n_hd)
    if "cs_ship_addr_sk" in need:
        out["cs_ship_addr_sk"] = _u_at(t, "saddr", order, 1, n_addr)
    if "cs_call_center_sk" in need:
        out["cs_call_center_sk"] = _u_at(t, "cc", rows, 1, 6)
    if "cs_catalog_page_sk" in need:
        out["cs_catalog_page_sk"] = _u_at(t, "cp", rows, 1, 11_718)
    if "cs_ship_mode_sk" in need:
        out["cs_ship_mode_sk"] = _u_at(t, "sm", rows, 1,
                                       DS._FIXED_ROWS["ship_mode"])
    if "cs_warehouse_sk" in need:
        out["cs_warehouse_sk"] = _u_at(t, "wh", rows, 1,
                                       DS.row_count("warehouse", sf))
    if "cs_item_sk" in need:
        out["cs_item_sk"] = _u_at(t, "item", rows, 1,
                                  DS.row_count("item", sf))
    if "cs_promo_sk" in need:
        out["cs_promo_sk"] = _u_at(t, "promo", rows, 1,
                                   DS.row_count("promotion", sf))
    money_need = {c[len("cs_"):] for c in need} & _CS_MONEY
    if money_need:
        m = _sales_money_cols(t, rows, money_need)
        out.update({"cs_" + k: v for k, v in m.items()})
    return out


def _catalog_returns_cols(sf, j, cols) -> Dict[str, jnp.ndarray]:
    t = "catalog_returns"
    need = set(cols)
    parent = j.astype(jnp.int64) * DS.RETURN_EVERY
    amount_cols = need & {"cr_return_quantity", "cr_return_amount",
                          "cr_return_tax", "cr_return_amt_inc_tax",
                          "cr_fee", "cr_return_ship_cost",
                          "cr_refunded_cash", "cr_reversed_charge",
                          "cr_store_credit", "cr_net_loss"}
    pairs = (("cr_item_sk", "cs_item_sk"),
             ("cr_refunded_customer_sk", "cs_bill_customer_sk"),
             ("cr_refunded_cdemo_sk", "cs_bill_cdemo_sk"),
             ("cr_refunded_hdemo_sk", "cs_bill_hdemo_sk"),
             ("cr_refunded_addr_sk", "cs_bill_addr_sk"),
             ("cr_returning_customer_sk", "cs_ship_customer_sk"),
             ("cr_returning_cdemo_sk", "cs_ship_cdemo_sk"),
             ("cr_returning_hdemo_sk", "cs_ship_hdemo_sk"),
             ("cr_returning_addr_sk", "cs_ship_addr_sk"),
             ("cr_call_center_sk", "cs_call_center_sk"),
             ("cr_catalog_page_sk", "cs_catalog_page_sk"),
             ("cr_ship_mode_sk", "cs_ship_mode_sk"),
             ("cr_warehouse_sk", "cs_warehouse_sk"),
             ("cr_order_number", "cs_order_number"))
    parent_need = {cs for cr, cs in pairs if cr in need}
    if "cr_returned_date_sk" in need:
        parent_need.add("cs_sold_date_sk")
    if amount_cols:
        parent_need |= {"cs_sales_price", "cs_quantity"}
    cs = _catalog_sales_cols(sf, parent, parent_need)
    out = {}
    if "cr_returned_date_sk" in need:
        out["cr_returned_date_sk"] = (cs["cs_sold_date_sk"]
                                      + _u_at(t, "lag", j, 1, 60))
    if "cr_returned_time_sk" in need:
        out["cr_returned_time_sk"] = _u_at(t, "time", j, 28800, 75600)
    for cr, cscol in pairs:
        if cr in need:
            out[cr] = cs[cscol]
    if "cr_reason_sk" in need:
        out["cr_reason_sk"] = _u_at(t, "reason", j, 1,
                                    DS._FIXED_ROWS["reason"])
    if amount_cols:
        ret_qty = jnp.minimum(_u_at(t, "qty", j, 1, 100, jnp.int32),
                              cs["cs_quantity"])
        amt = _round2(cs["cs_sales_price"] * ret_qty)
        tax = _round2(amt * 0.08)
        fee = _money_at(t, "fee", j, 50, 10_000)
        ship = _money_at(t, "ship", j, 0, 10_000)
        frac = _raw_at(t, "cashfrac", j)
        cash = _round2(amt * frac)
        charge = _round2((amt - cash) * _raw_at(t, "chargefrac", j))
        credit = _round2(amt - cash - charge)
        vals = {
            "cr_return_quantity": ret_qty,
            "cr_return_amount": amt,
            "cr_return_tax": tax,
            "cr_return_amt_inc_tax": _round2(amt + tax),
            "cr_fee": fee,
            "cr_return_ship_cost": ship,
            "cr_refunded_cash": cash,
            "cr_reversed_charge": charge,
            "cr_store_credit": credit,
            "cr_net_loss": _round2(fee + ship + tax),
        }
        out.update({c: vals[c] for c in amount_cols})
    return out


_GENERATORS = {
    "store_sales": _store_sales_cols,
    "store_returns": _store_returns_cols,
    "catalog_sales": _catalog_sales_cols,
    "catalog_returns": _catalog_returns_cols,
}

# every column of the four fact tables is numeric -> device-generable
DEVICE_COLUMNS = {t: set(DS.SCHEMAS[t]) for t in _GENERATORS}


def is_device_generable(table: str, col: str) -> bool:
    return col in DEVICE_COLUMNS.get(table, ())


def generate_device(table: str, sf: float, cols: List[str], row0,
                    pad: int, f32: bool = False) -> Dict[str, Column]:
    """Generate `cols` of `table` rows [row0, row0+pad) on device.
    Shapes are STATIC (pad rows) while row0 may be a traced scalar —
    one compiled program serves every chunk.  Rows past the real chunk
    extent are garbage the caller must mask via the batch sel."""
    rows = jnp.asarray(row0, jnp.int64) + jnp.arange(pad, dtype=jnp.int64)
    raw = _GENERATORS[table](sf, rows, set(cols))
    schema = DS.SCHEMAS[table]
    out = {}
    for c in cols:
        if c not in raw:
            raise KeyError(f"column {c} of {table} is not device-generable")
        data = raw[c]
        typ = schema[c]
        if f32 and typ.name == "DOUBLE":
            data = data.astype(jnp.float32)
        out[c] = Column(data, None, typ, None)
    return out


# ---------------------------------------------------------------------------
# chunk families (bucketing SPI, consumed by exec/chunked.py)
# ---------------------------------------------------------------------------


DEFAULT_CHUNK_FACT_ROWS = 12_000_000


class _SalesChunkGrid:
    """Chunk grid over a sales-row axis: the sales table streams in
    row ranges aligned to its per-unit stride (ticket/order), the
    returns table streams the exact matching parent ranges."""

    def __init__(self, sf, sales, returns, unit, edges, ret_edges):
        self.sf = sf
        self.sales = sales
        self.returns = returns
        self.unit = unit
        self.edges = edges
        self.ret_edges = ret_edges
        self.nchunks = len(edges) - 1
        self.cap_sales = max(b - a for a, b in zip(edges[:-1], edges[1:]))
        self.cap_returns = max(
            b - a for a, b in zip(ret_edges[:-1], ret_edges[1:]))

    def capacity(self, table: str) -> int:
        return self.cap_sales if table == self.sales else self.cap_returns

    def exchange_bound(self) -> int:
        # per-chunk exchange outputs are reductions of the chunk
        # (aggregates on the bucket key, selective filters, sales x
        # returns matches <= the chunk's return count x small fanout)
        return self.cap_sales // 2

    def bucket_ndv(self) -> int:
        # edges land on unit (ticket/order) boundaries, so a chunk
        # holds at most cap_sales/unit distinct bucket values
        return max(self.cap_sales // max(self.unit, 1), 1)

    def row_edges(self, table: str):
        """The table's own row range of every chunk, as edges."""
        return self.edges if table == self.sales else self.ret_edges

    def chunk_args(self, i: int):
        return (jnp.asarray(self.edges[i], jnp.int64),
                jnp.asarray(self.edges[i + 1] - self.edges[i], jnp.int32),
                jnp.asarray(self.ret_edges[i], jnp.int64),
                jnp.asarray(self.ret_edges[i + 1] - self.ret_edges[i],
                            jnp.int32))

    def build_scan(self, table: str, cols: List[str], args, f32: bool):
        s0, n_s, r0, n_r = args
        if table == self.sales:
            raw = generate_device(table, self.sf, cols, s0,
                                  self.cap_sales, f32)
            sel = jnp.arange(self.cap_sales) < n_s
        elif table == self.returns:
            raw = generate_device(table, self.sf, cols, r0,
                                  self.cap_returns, f32)
            sel = jnp.arange(self.cap_returns) < n_r
        else:
            raise KeyError(f"{table} is not in the {self.sales} family")
        return raw, sel


class _SalesChunkFamily:
    def __init__(self, name, sales, returns, bucket_cols, unit, sf):
        self.name = name
        self.sales = sales
        self.returns = returns
        self._bucket = bucket_cols  # table -> bucket column
        self.unit = unit
        self.sf = sf

    def tables(self):
        return {self.sales, self.returns}

    def bucket_column(self, table: str) -> str:
        return self._bucket[table]

    def device_columns(self, table: str):
        return DEVICE_COLUMNS[table]

    def make_grid(self, session) -> _SalesChunkGrid:
        chunk_rows = int(session.properties.get(
            "chunk_fact_rows", DEFAULT_CHUNK_FACT_ROWS))
        # interior edges on unit boundaries so every ticket/order's rows
        # land in exactly one chunk (the bucketing colocation property)
        chunk_rows = max(self.unit, chunk_rows - chunk_rows % self.unit)
        total = DS.row_count(self.sales, self.sf)
        edges = list(range(0, total, chunk_rows)) + [total]
        if len(edges) >= 2 and edges[-2] == edges[-1]:
            edges.pop()
        return self._grid(edges)

    def shard_grid(self, ndev: int) -> _SalesChunkGrid:
        """The chunk grid cut for a mesh: `ndev` contiguous ranges of
        the sales table's rows, interior edges on ticket / order
        boundaries, and the returns of the same tickets beside them
        (parallel/dist_executor.sharded_scan generates each range on the
        chip that holds it).  Trailing ranges may be empty."""
        total = DS.row_count(self.sales, self.sf)
        per = max(-(-total // ndev), 1)
        per += -per % self.unit
        return self._grid([min(i * per, total) for i in range(ndev + 1)])

    def _grid(self, edges) -> _SalesChunkGrid:
        total_ret = DS.row_count(self.returns, self.sf)
        # return j's parent sale is row j*RETURN_EVERY: parents in
        # [a, b) <=> j in [ceil(a/E), ceil(b/E)) — an exact partition
        E = DS.RETURN_EVERY
        ret_edges = [min(-(-a // E), total_ret) for a in edges]
        ret_edges[-1] = total_ret
        return _SalesChunkGrid(self.sf, self.sales, self.returns,
                               self.unit, edges, ret_edges)


def chunk_family(table: str, sf: float):
    """Bucketing metadata for `table`, or None (the connector SPI hook
    TpcdsTable.bucketing delegates to)."""
    if table in ("store_sales", "store_returns"):
        return _SalesChunkFamily(
            "tpcds-store", "store_sales", "store_returns",
            {"store_sales": "ss_ticket_number",
             "store_returns": "sr_ticket_number"},
            DS.ITEMS_PER_TICKET, sf)
    if table in ("catalog_sales", "catalog_returns"):
        return _SalesChunkFamily(
            "tpcds-catalog", "catalog_sales", "catalog_returns",
            {"catalog_sales": "cs_order_number",
             "catalog_returns": "cr_order_number"},
            DS.ITEMS_PER_ORDER, sf)
    return None
