"""The deployment `tpcds_store` at SF0.01 on the CPU (ISSUE 34): TPC-DS's
store channel through the normal path, `store_sales` born on the device.

(a) `TpcdsTable.device_columns` equals the host generator bit for bit for
    every `store_sales` column the benchmark's three texts read, in both
    lanes; a dimension falls back to `read()`; no host copy of the fact
    table exists after the queries ran;
(b) q27, q36, q89 (the benchmark's own texts, compiled mode, the
    configuration's session properties) equal `benchmarks/reference_tpcds.py`
    by its own `rows_equal`; that reference equals sqlite over the same
    data; the near-tie rule lets float32 turn a near-tie and nothing else;
(c) `QueryStats.window_functions` / `grouping_set_branches` /
    `grouping_set_sources` are what the plans hold (a ROLLUP's star join
    is planned and lowered once: ISSUE 35), `k:window` is in the vocabulary and in the program, the
    span helper raised nothing; the string statistics that size a star
    join's survivors never undershoot.
"""

import importlib.util
import json
import os
import re

import numpy as np
import pytest

import presto_tpu
from presto_tpu.catalog import TpcdsTable, tpcds_catalog
from presto_tpu.connectors import tpcds as DS
from presto_tpu.exec import compile_cache as CC
from presto_tpu.exec.executor import plan_statement
from presto_tpu.observe import metrics as M
from presto_tpu.observe import names as NM
from presto_tpu.plan import nodes as P
from presto_tpu.plan.stats import ColStats
from presto_tpu.sql.parser import parse
from tests.sqlite_oracle import build_sqlite, to_sqlite

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SF = 0.01

with open(os.path.join(BENCH, "configs", "tpcds_store.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "workloads", "ds_store_report.json")) as f:
    CELL = json.load(f)
REL = CONFIG["guarantees"]["float_rel"]
CLASSES = {c["name"]: c for c in CELL["classes"]}
FACT_COLUMNS = sorted({col for c in CELL["classes"]
                       for col in c["columns_read"]["store_sales"]})


def text_of(cls):
    with open(os.path.join(BENCH, "queries", CLASSES[cls]["query"] + ".sql")) as f:
        return f.read().strip()


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_tpcds", os.path.join(BENCH, CELL["reference"]))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def session():
    s = presto_tpu.connect(tpcds_catalog(SF, cache_dir=None),
                           execution_mode="compiled")
    for k, v in CONFIG["session_properties"].items():
        s.set(k, v)
    return s


@pytest.fixture(scope="module")
def answered(session):
    """{class: (rows as lists, QueryStats)} of a second, warm execution."""
    out = {}
    for cls in CLASSES:
        session.sql(text_of(cls))
        r = session.sql(text_of(cls))
        out[cls] = ([list(row) for row in r.rows], r.stats)
    return out


# ---------------------------------------------------------------------------
# (a) store_sales born on the device == the host generator
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def born():
    t = TpcdsTable("store_sales", SF)
    return {f32: t.device_columns(FACT_COLUMNS, f32=f32) for f32 in (False, True)}


@pytest.mark.parametrize("f32", [False, True], ids=["f64", "f32"])
@pytest.mark.parametrize("column", FACT_COLUMNS)
def test_fact_column_born_on_device_equals_host(born, column, f32):
    host = DS.generate("store_sales", SF)[column]
    typ = DS.SCHEMAS["store_sales"][column]
    if f32 and typ.name == "DOUBLE":
        host = host.astype(np.float32)
    col = born[f32][column]
    got = np.asarray(col.data)
    assert col.valid is None and col.type == typ
    assert got.dtype == host.dtype and got.shape == host.shape
    assert (got == host).all()      # bit for bit: same counters, same rounding


def test_the_configurations_factory_bears_facts_on_the_device():
    from benchmarks.run import entry_point

    cat = entry_point(CONFIG["catalog_factory"])(SF, cache_dir=None)
    for table, rows in CONFIG["rows"].items():
        assert cat.get(table).sf == SF and DS.row_count(table, 10) == rows
    assert all(cat.get("store_sales").device_generable(c) for c in FACT_COLUMNS)


def test_dimension_falls_back_to_read(session, answered):
    cat = session.catalog
    item = cat.get("item")
    assert not item.device_generable("i_category")
    assert item.device_columns(["i_item_sk", "i_category"]) is None
    assert cat.get("store_sales").device_columns(
        ["ss_item_sk", "no_such_column"]) is None
    # the queries ran: dimensions were read on the host, the fact table
    # never was (TpcdsTable._full_table keeps what it generates in _data)
    assert hasattr(item, "_data")
    assert not hasattr(cat.get("store_sales"), "_data")


def test_release_device_caches_drops_born_columns(session, answered):
    from presto_tpu.catalog import release_device_caches

    t = session.catalog.get("store_sales")
    assert "ss_item_sk" in t._device_cols and "ss_sales_price" in t._device_cols_f32
    release_device_caches()
    assert not hasattr(t, "_device_cols") and not hasattr(t, "_device_cols_f32")
    programs = dict(t._device_gen_jit)
    assert session.sql(text_of("q27")).rows
    assert "ss_cdemo_sk" in t._device_cols
    # generated again by the program that was kept, none built
    assert t._device_gen_jit == programs


# ---------------------------------------------------------------------------
# (b) engine == plain reference == sqlite; the near-tie rule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cls", sorted(CLASSES))
def test_engine_equals_plain_reference(reference, answered, cls):
    want = reference.streamed(SF, [CLASSES[cls]["check"]])[CLASSES[cls]["check"]]
    rows, stats = answered[cls]
    assert rows and len(rows) == len(want)
    assert stats.execution_mode == "compiled" and not stats.fallback_reason
    assert stats.compiles == 0      # the second execution built nothing
    assert reference.rows_equal(rows, want, REL)


class StoreChannel:
    """The five tables the texts read, as `build_sqlite` takes a generator."""

    __name__ = "tpcds_store_channel"
    SCHEMAS = {t: DS.SCHEMAS[t] for t in CONFIG["rows"]}
    generate = staticmethod(DS.generate)


def rollup_for_sqlite(text, levels):
    """A ROLLUP text as the UNION ALL of its grouping sets, sqlite having
    none: `levels` = [(select list, group by or None)], the FROM and WHERE
    are the text's own."""
    body = text[text.index("FROM store_sales"):text.index("GROUP BY ROLLUP")]
    return " UNION ALL ".join(
        f"SELECT * FROM (SELECT {sel} {body}" + (f" GROUP BY {by})" if by else ")")
        for sel, by in levels)


def sqlite_text(cls):
    text = text_of(cls)
    if cls == "q27":
        avgs = ("avg(ss_quantity), avg(ss_list_price), avg(ss_coupon_amt), "
                "avg(ss_sales_price)")
        return rollup_for_sqlite(text, [
            (f"i_item_id, s_state, 0, {avgs}", "i_item_id, s_state"),
            (f"i_item_id, NULL, 1, {avgs}", "i_item_id"),
            (f"NULL, NULL, 1, {avgs}", None)])
    if cls == "q36":
        ratio = "sum(ss_net_profit) / sum(ss_ext_sales_price)"
        return rollup_for_sqlite(text, [
            (f"{ratio}, i_category, i_class, 0, rank() OVER (PARTITION BY "
             f"i_category ORDER BY {ratio})", "i_category, i_class"),
            (f"{ratio}, i_category, NULL, 1, rank() OVER (ORDER BY {ratio})",
             "i_category"),
            (f"{ratio}, NULL, NULL, 2, 1", None)])
    return text[:text.rindex("ORDER BY")]      # the whole answer, unordered


@pytest.mark.parametrize("cls", sorted(CLASSES))
def test_plain_reference_equals_sqlite(reference, cls):
    """Something that shares nothing with the reference computes the whole
    answer; the reference's rows are those, in the text's order."""
    check = CLASSES[cls]["check"]
    conn = build_sqlite(SF, generator=StoreChannel)
    theirs = [list(r) for r in conn.execute(to_sqlite(sqlite_text(cls)))]
    whole = reference.answers(SF, [check])[check]
    assert whole and len(whole) == len(theirs)
    rule = reference.ORDERED_BY_FLOAT.get(check)
    key = rule["key"] if rule else (0, 1)
    by_key = {tuple(r[i] for i in key): r for r in theirs}
    assert len(by_key) == len(theirs)
    for row in whole:
        assert reference.same_row(by_key[tuple(row[i] for i in key)], row, 1e-9)
    # its order is the ORDER BY's: sqlite's rows, sorted here, in a LIMIT
    want = reference.Expected(check, whole)
    order = {"q27": lambda r: (reference.nulls_last(r[0]),
                               reference.nulls_last(r[1])),
             "q36": lambda r: (-r[3], reference.nulls_last(
                 r[1] if r[3] == 0 else None), r[4],
                 reference.nulls_last(r[1]), reference.nulls_last(r[2])),
             "q89": lambda r: (r[6] - r[7], r[3], r[6], r[0], r[1], r[2],
                               r[4], r[5])}[cls]
    assert reference.rows_equal(sorted(theirs, key=order)[:len(want)], want, 1e-9)


def q89_row(brand, total, avg):
    return ["Books", "mystery", brand, "able", "Unknown", 1, total, avg]


def q36_row(ratio, cls, rank):
    return [ratio, "Books", cls, 0, rank]


NEAR = 1.0 + 1e-6       # inside float_rel: float32 sums may turn it
FAR = 1.0 + 1e-3        # outside: a real difference


@pytest.mark.parametrize("case,check,want,got,equal", [
    ("as the reference", "tpcds_q89",
     [q89_row("a", 10.0, 50.0), q89_row("b", 20.0, 50.0)],
     [q89_row("a", 10.0, 50.0), q89_row("b", 20.0, 50.0)], True),
    ("a near-tie turned", "tpcds_q89",
     [q89_row("a", 10.0, 50.0), q89_row("b", 10.0 * NEAR, 50.0 * NEAR)],
     [q89_row("b", 10.0 * NEAR, 50.0 * NEAR), q89_row("a", 10.0, 50.0)], True),
    ("a real swap", "tpcds_q89",
     [q89_row("a", 10.0, 50.0), q89_row("b", 10.0, 50.0 / FAR)],
     [q89_row("b", 10.0, 50.0 / FAR), q89_row("a", 10.0, 50.0)], False),
    ("a value off", "tpcds_q89",
     [q89_row("a", 10.0, 50.0)], [q89_row("a", 10.0 * FAR, 50.0)], False),
    ("a row of its own", "tpcds_q89",
     [q89_row("a", 10.0, 50.0)], [q89_row("z", 10.0, 50.0)], False),
    ("ranks of a near-tie turned", "tpcds_q36",
     [q36_row(-0.4, "x", 1), q36_row(-0.4 + 1e-6, "y", 2), q36_row(-0.3, "z", 3)],
     [q36_row(-0.4 + 1e-6, "y", 1), q36_row(-0.4, "x", 2), q36_row(-0.3, "z", 3)],
     True),
    ("ranks of a near-tie shared", "tpcds_q36",
     [q36_row(-0.4, "x", 1), q36_row(-0.4 + 1e-6, "y", 2), q36_row(-0.3, "z", 3)],
     [q36_row(-0.4, "x", 1), q36_row(-0.4 + 1e-6, "y", 1), q36_row(-0.3, "z", 3)],
     True),
    ("ranks really swapped", "tpcds_q36",
     [q36_row(-0.4, "x", 1), q36_row(-0.3, "z", 2)],
     [q36_row(-0.3, "z", 1), q36_row(-0.4, "x", 2)], False),
    ("a rank off", "tpcds_q36",
     [q36_row(-0.4, "x", 1), q36_row(-0.3, "z", 2)],
     [q36_row(-0.4, "x", 1), q36_row(-0.3, "z", 3)], False),
    ("exact keys in another order", "tpcds_q27",
     [["A1", "AL", 0, 1.0, 1.0, 1.0, 1.0], ["A1", None, 1, 1.0, 1.0, 1.0, 1.0]],
     [["A1", None, 1, 1.0, 1.0, 1.0, 1.0], ["A1", "AL", 0, 1.0, 1.0, 1.0, 1.0]],
     False),
    ("a subtotal's grouping() off", "tpcds_q27",
     [["A1", None, 1, 1.0, 1.0, 1.0, 1.0]],
     [["A1", None, 0, 1.0, 1.0, 1.0, 1.0]], False),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) and " " in v else None)
def test_near_tie_rule(reference, case, check, want, got, equal):
    assert reference.rows_equal(got, reference.Expected(check, want), REL) is equal


def test_a_near_tie_may_cross_the_limit_and_nothing_else(reference, monkeypatch):
    monkeypatch.setattr(reference, "LIMIT", 1)
    near = reference.Expected("tpcds_q89", [q89_row("a", 10.0, 50.0),
                                            q89_row("b", 10.0 * NEAR, 50.0 * NEAR)])
    far = reference.Expected("tpcds_q89", [q89_row("a", 10.0, 50.0),
                                           q89_row("b", 10.0, 50.0 / FAR)])
    assert len(near) == 1 and len(near.beyond) == 1
    assert reference.rows_equal([near.beyond[0]], near, REL)
    assert not reference.rows_equal([far.beyond[0]], far, REL)


# ---------------------------------------------------------------------------
# what sf10 forced: a star join's gather, at this scale's sizes
# ---------------------------------------------------------------------------


@pytest.fixture
def star_sized(monkeypatch):
    """The shapes of sf10 at SF0.01: 28,804 fact rows count as many, a
    block of the packed gather holds 4096 of them."""
    from presto_tpu.exec import gather as G
    from presto_tpu.exec import kernels as K

    monkeypatch.setattr(G, "_SMALL_SOURCE_MIN_INDICES", 8192)
    monkeypatch.setattr(G, "PACKED_BLOCK", 4096)
    calls = []
    real = K._packed_gather_in_blocks
    monkeypatch.setattr(K, "_packed_gather_in_blocks",
                        lambda words, idx: calls.append(
                            (len(words), idx.shape[0])) or real(words, idx))
    CC.clear()
    yield calls
    CC.clear()


@pytest.mark.parametrize("mode", ["compiled", "dynamic"])
@pytest.mark.parametrize("cls", sorted(CLASSES))
def test_star_join_gathers_once_in_blocks(reference, session, star_sized,
                                          cls, mode):
    s = presto_tpu.connect(session.catalog, execution_mode=mode)
    for k, v in CONFIG["session_properties"].items():
        s.set(k, v)
    r = s.sql(text_of(cls))
    assert r.stats.execution_mode == mode and not r.stats.fallback_reason
    want = reference.streamed(SF, [CLASSES[cls]["check"]])[CLASSES[cls]["check"]]
    assert reference.rows_equal([list(x) for x in r.rows], want, REL)
    # the fact table's rows against item (180 rows) and store (12): star
    # joins, gathered in blocks where the dimension brings two words
    assert star_sized and all(m == 28804 for _, m in star_sized)


def test_star_join_keeps_unmatched_rows_of_a_left_join(session, star_sized):
    sql = ("SELECT count(*), count(i_item_sk), count(i_class), sum(i_item_sk) "
           "FROM store_sales LEFT JOIN (SELECT i_item_sk, i_class FROM item "
           "WHERE i_category = 'Books') ON ss_item_sk = i_item_sk")
    got = presto_tpu.connect(session.catalog, execution_mode="compiled").sql(sql)
    ss = DS.generate("store_sales", SF)["ss_item_sk"]
    item = DS.generate("item", SF)
    books = item["i_item_sk"][item["i_category"] == "Books"]
    hit = ss[np.isin(ss, books)]
    assert got.stats.execution_mode == "compiled"
    assert [tuple(r) for r in got.rows] == [
        (len(ss), len(hit), len(hit), int(hit.sum()))]
    assert star_sized


def test_packed_gather_in_blocks_equals_the_packed_gather(monkeypatch):
    import jax.numpy as jnp

    from presto_tpu.exec import gather as G
    from presto_tpu.exec import kernels as K

    monkeypatch.setattr(G, "PACKED_BLOCK", 1000)
    rng = np.random.default_rng(34)
    words = [jnp.asarray(rng.integers(0, 2**32, 77, dtype=np.uint32))
             for _ in range(3)]
    idx = jnp.asarray(rng.integers(0, 77, 2501).astype(np.int32))
    got = K._packed_gather_in_blocks(words, idx)
    assert [g.shape for g in got] == [(2501,)] * 3
    for g, w in zip(got, words):
        assert (np.asarray(g) == np.asarray(w)[np.asarray(idx)]).all()


def test_small_source_rule_changes_no_smaller_program():
    from presto_tpu.exec import gather as G

    assert G.small_source(180_000, 28_804_040)
    assert G.small_source(1_920_800, 28_804_040)
    assert not G.small_source(1_500_000, 6_001_215)     # TPC-H SF1
    assert not G.small_source(150_000, 1_500_000)
    assert not G.small_source(20_000_000, 28_804_040)   # a large source
    assert G.gather_route(180_000, 28_804_040, 5) == "flat"


# ---------------------------------------------------------------------------
# (c) counters, the scope, the statistics
# ---------------------------------------------------------------------------


def walk(node):
    yield node
    for s in node.sources:
        yield from walk(s)


@pytest.mark.parametrize("cls", sorted(CLASSES))
def test_stats_say_what_the_plan_holds(session, answered, cls):
    plan = plan_statement(session, parse(text_of(cls)))
    windows = [n for n in walk(plan.root) if isinstance(n, P.Window)]
    sets = [n for n in walk(plan.root) if isinstance(n, P.GroupingSets)]
    stats = answered[cls][1]        # a warm run: replayed, not re-traced
    assert stats.window_functions == sum(len(n.functions) for n in windows)
    assert stats.grouping_set_branches == sum(len(n.sets) for n in sets)
    assert stats.grouping_set_branches == plan.grouping_set_branches
    # ROLLUP (a, b) is three sets over ONE lowering of the star join
    rollup = "ROLLUP" in text_of(cls)
    assert stats.grouping_set_branches == (3 if rollup else 0)
    assert stats.grouping_set_sources == len(sets) == (1 if rollup else 0)
    assert not any(isinstance(n, P.Union) for n in walk(plan.root))
    assert sum(isinstance(n, P.TableScan) and n.table == "store_sales"
               for n in walk(plan.root)) == 1
    assert (stats.window_functions > 0) == ("OVER" in text_of(cls))
    assert NM.late_scope_marks(plan.root) == ("w1" if windows else "")


def test_window_scope_in_vocabulary_and_program(session, lowered_texts):
    assert "k:window" in NM.KERNEL_SCOPES
    CC.clear()      # nothing in the process-wide memo: built, spied on
    fresh = presto_tpu.connect(session.catalog, execution_mode="compiled")
    fresh.set("float32_compute", True)
    assert fresh.sql(text_of("q89")).stats.execution_mode == "compiled"
    text = "\n".join(lowered_texts)
    assert re.search(r"/Window/(?:[^\"/]+/)*?k:window[/\"]", text)
    assert re.search(rf"jit_fn_s{NM.SCOPE_VERSION}_[0-9a-f]{{8}}w1\b", text)
    # a program without a Window keeps the name, and so the cache key, it had
    lowered_texts.clear()
    CC.clear()
    fresh.sql(text_of("q27"))
    text = "\n".join(lowered_texts)
    assert re.search(rf"jit_fn_s{NM.SCOPE_VERSION}_[0-9a-f]{{8}}\b", text)
    assert "k:window" not in text and not re.search(r"_[0-9a-f]{8}w1\b", text)


def test_span_helper_raised_nothing(answered):
    counter = M.REGISTRY.get("presto_tpu_trace_errors_total")
    assert counter is None or counter.value() == 0.0


@pytest.mark.parametrize("table", sorted(StoreChannel.SCHEMAS))
def test_string_ndv_never_undershoots(table):
    """A dimension filter's selectivity is 1 / ndv: an enum read as 100,000
    values sized q27's first join at 15 rows for 411,486 and tripped its
    guard; an undershoot would trip a group capacity's."""
    data = DS.generate(table, SF)
    for column, typ in DS.SCHEMAS[table].items():
        if typ.name != "VARCHAR":
            continue
        ndv = DS.column_stats(table, column, SF, ColStats).ndv
        assert ndv is not None and len(set(data[column].tolist())) <= ndv, column
        if column in DS._VOCABULARY:
            assert ndv <= DS._VOCABULARY[column]
    assert DS.column_stats("item", "i_item_id", 10.0, ColStats).ndv == 180_000
