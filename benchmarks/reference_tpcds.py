"""The plain reference of configuration `tpcds_store`: TPC-DS q27, q36 and
q89 (store channel) in straight-line float64 numpy, the comparison that
holds the engine to the configuration's guarantees, the bytes a class reads.

Over the HOST generator (`connectors/tpcds.py`, the data's definition):
`store_sales` in row slices so that scale factor 10 fits the host,
surrogate keys as array lookups into the dimensions, groups by
`np.unique` / `bincount`, a window as a per-partition reduction, a rank as
a per-partition loop.  No planner, executor, kernel or device-generator
code.  The substitution values below are those of `queries/tpcds_q*.sql`
(configs/tpcds_store.json lists them under `assumed`).

The names `run.py` asks of a reference module (README.md): STREAMED, POINT,
BINDS, cached_streamed, bind_values, rows_equal, bytes_read.
"""

import hashlib
import json
import os

import numpy as np

ROW_SLICE = 2_000_000       # store_sales rows per slice of the reference
LIMIT = 100                 # every text's LIMIT
BEYOND = 100                # rows kept after the LIMIT for the near-tie rule

Q27 = {"gender": "M", "marital": "S", "education": "College", "year": 2000,
       "states": ("AL", "AZ", "AR", "CA", "CO", "CT")}
Q36 = {"year": 2000,
       "states": ("AL", "AK", "AZ", "AR", "CA", "CO", "CT", "FL")}
Q89 = {"year": 2000,
       "either": ((("Books", "Electronics", "Sports"),
                   ("mystery", "portable", "fishing")),
                  (("Men", "Jewelry", "Women"),
                   ("shirts", "earings", "dresses")))}

STREAMED = {"tpcds_q27": 27, "tpcds_q36": 36, "tpcds_q89": 89}
POINT = {}


def check(cond, *what):
    """An assert that -O cannot remove."""
    if not cond:
        raise AssertionError(*what)


# ---------------------------------------------------------------------------
# the three queries, one pass over store_sales
# ---------------------------------------------------------------------------


def dense(table, key, sf):
    """A dimension whose surrogate key is its row number + key[0]: a
    foreign key minus that is a row index."""
    from presto_tpu.connectors import tpcds as DS

    t = DS.generate(table, sf)
    k = t[key]
    check((np.diff(k) == 1).all(), table, "keys are not dense")
    return t, int(k[0])


def codes(values):
    """-> (sorted distinct values, each value's index among them): a
    string column as integers in the strings' own order."""
    return np.unique(np.asarray(values, dtype=object).astype(str),
                     return_inverse=True)


def nulls_last(v):
    return (v is None, "" if v is None else v)


def answers(sf, names, row_slice=ROW_SLICE, keep=None):
    """{check name: the answer's rows in ORDER BY order, before the LIMIT}
    (the first `keep` of them where given) for the `names` of STREAMED."""
    from presto_tpu.connectors import tpcds as DS

    want = {STREAMED[n] for n in names}
    item, item0 = dense("item", "i_item_sk", sf)
    store, store0 = dense("store", "s_store_sk", sf)
    date, date0 = dense("date_dim", "d_date_sk", sf)
    year, moy = date["d_year"], date["d_moy"]
    state = np.asarray(store["s_state"]).astype(str)
    cats, cat_of = codes(item["i_category"])
    classes, class_of = codes(item["i_class"])

    if 27 in want:
        cd, cd0 = dense("customer_demographics", "cd_demo_sk", sf)
        cd_ok = ((cd["cd_gender"] == Q27["gender"])
                 & (cd["cd_marital_status"] == Q27["marital"])
                 & (cd["cd_education_status"] == Q27["education"]))
        st27 = np.isin(state, Q27["states"])
        got27 = []
    if 36 in want:
        st36 = np.isin(state, Q36["states"])
        n36 = len(cats) * len(classes)
        profit, sales, count = np.zeros(n36), np.zeros(n36), np.zeros(n36)
    if 89 in want:
        cat_s = np.asarray(item["i_category"]).astype(str)
        class_s = np.asarray(item["i_class"]).astype(str)
        item_ok = np.zeros(len(cat_s), bool)
        for in_cat, in_class in Q89["either"]:
            item_ok |= np.isin(cat_s, in_cat) & np.isin(class_s, in_class)
        brands, brand_of = codes(item["i_brand"])
        names89, name_of = codes(store["s_store_name"])
        got89 = []

    n = DS.row_count("store_sales", sf)
    for r0 in range(0, n, row_slice):
        ss = DS.generate("store_sales", sf, r0, min(r0 + row_slice, n))
        i_item = ss["ss_item_sk"] - item0
        i_store = ss["ss_store_sk"] - store0
        i_date = ss["ss_sold_date_sk"] - date0
        if 27 in want:
            m = (cd_ok[ss["ss_cdemo_sk"] - cd0] & (year[i_date] == Q27["year"])
                 & st27[i_store])
            got27.append((i_item[m], i_store[m], ss["ss_quantity"][m],
                          ss["ss_list_price"][m], ss["ss_coupon_amt"][m],
                          ss["ss_sales_price"][m]))
        if 36 in want:
            m = (year[i_date] == Q36["year"]) & st36[i_store]
            g = cat_of[i_item[m]] * len(classes) + class_of[i_item[m]]
            profit += np.bincount(g, ss["ss_net_profit"][m], n36)
            sales += np.bincount(g, ss["ss_ext_sales_price"][m], n36)
            count += np.bincount(g, minlength=n36)
        if 89 in want:
            m = (year[i_date] == Q89["year"]) & item_ok[i_item]
            got89.append((i_item[m], i_store[m], moy[i_date[m]],
                          ss["ss_sales_price"][m]))

    out = {}
    if 27 in want:
        cols = [np.concatenate(c) for c in zip(*got27)]
        out["tpcds_q27"] = q27_rows(item["i_item_id"], state, *cols)
    if 36 in want:
        out["tpcds_q36"] = q36_rows(cats, classes, profit, sales, count)
    if 89 in want:
        i_item, i_store, month, price = (np.concatenate(c)
                                         for c in zip(*got89))
        check(len(set(store["s_company_name"])) == 1)
        out["tpcds_q89"] = q89_rows(
            (cats, cat_of[i_item]), (classes, class_of[i_item]),
            (brands, brand_of[i_item]), (names89, name_of[i_store]),
            str(store["s_company_name"][0]), month, price, keep)
    return {k: rows if keep is None else rows[:keep] for k, rows in out.items()}


def q27_rows(item_id, state, i_item, i_store, *values):
    """avg of four columns by ROLLUP (i_item_id, s_state), with
    grouping(s_state); ORDER BY i_item_id, s_state, NULLs last."""
    values = [np.asarray(v, np.float64) for v in values]
    rows = []

    def level(key, label):
        groups, gid = np.unique(key, return_inverse=True)
        n = np.bincount(gid)
        avgs = [np.bincount(gid, v) / n for v in values]
        for j, g in enumerate(groups):
            rows.append(label(int(g)) + [float(a[j]) for a in avgs])

    states, s_code = np.unique(state[i_store], return_inverse=True)
    level(i_item * len(states) + s_code,
          lambda g: [str(item_id[g // len(states)]),
                     str(states[g % len(states)]), 0])
    level(i_item, lambda g: [str(item_id[g]), None, 1])
    if len(i_item):
        level(np.zeros(len(i_item), np.int64), lambda g: [None, None, 1])
    rows.sort(key=lambda r: (nulls_last(r[0]), nulls_last(r[1])))
    return rows


def q36_rows(cats, classes, profit, sales, count):
    """sum(ss_net_profit) / sum(ss_ext_sales_price) by ROLLUP (i_category,
    i_class); rank() within the parent by that ratio, ascending."""
    nc = len(classes)
    levels = []     # (lochierarchy, parent, i_category, i_class, ratio)
    for g in np.flatnonzero(count):
        c = str(cats[g // nc])
        levels.append((0, c, c, str(classes[g % nc]), profit[g] / sales[g]))
    by_cat = count.reshape(-1, nc).sum(axis=1)
    for c in np.flatnonzero(by_cat):
        sl = slice(c * nc, (c + 1) * nc)
        levels.append((1, None, str(cats[c]), None,
                       profit[sl].sum() / sales[sl].sum()))
    if count.sum():
        levels.append((2, None, None, None, profit.sum() / sales.sum()))
    rows = []
    for loch, parent, cat, cls, ratio in levels:
        rank = 1 + sum(1 for o in levels
                       if o[:2] == (loch, parent) and o[4] < ratio)
        rows.append([float(ratio), cat, cls, loch, rank])
    rows.sort(key=lambda r: (-r[3], nulls_last(r[1] if r[3] == 0 else None),
                             r[4], nulls_last(r[1]), nulls_last(r[2])))
    return rows


def q89_rows(cat, cls, brand, name, company, month, price, keep):
    """sum(ss_sales_price) by six keys, its average over (i_category,
    i_brand, s_store_name, s_company_name), the rows that lie more than a
    tenth of that average from it, by sum - average."""
    dims = [cat, cls, brand, name]
    key = np.zeros(len(price), np.int64)
    for values, code in dims:
        key = key * len(values) + code
    groups, gid = np.unique(key * 12 + (month - 1), return_inverse=True)
    total = np.bincount(gid, np.asarray(price, np.float64))
    code, rest = {}, groups // 12
    for (values, _), k in zip(reversed(dims), "nbca"):   # name, brand, ...
        code[k] = rest % len(values)
        rest = rest // len(values)
    d_moy = groups % 12 + 1
    # the window: every group of one (category, brand, store name)
    part = (code["a"] * len(brand[0]) + code["b"]) * len(name[0]) + code["n"]
    _, pid = np.unique(part, return_inverse=True)
    avg = (np.bincount(pid, total) / np.bincount(pid))[pid]
    with np.errstate(divide="ignore", invalid="ignore"):
        far = np.where(avg != 0, np.abs(total - avg) / avg, 0.0) > 0.1
    idx = np.flatnonzero(far)
    # codes order as their strings do; the one company name orders nothing
    order = idx[np.lexsort((d_moy[idx], code["b"][idx], code["c"][idx],
                            code["a"][idx], total[idx], code["n"][idx],
                            (total - avg)[idx]))]
    return [[str(cat[0][code["a"][i]]), str(cls[0][code["c"][i]]),
             str(brand[0][code["b"][i]]), str(name[0][code["n"][i]]), company,
             int(d_moy[i]), float(total[i]), float(avg[i])]
            for i in (order if keep is None else order[:keep])]


# ---------------------------------------------------------------------------
# expected answers, cached per checkout
# ---------------------------------------------------------------------------


class Expected(list):
    """The rows a query must return (the first LIMIT of the reference's
    order), the reference's next rows (`beyond`: what may cross the LIMIT
    on a near-tie) and the check's name, by which `rows_equal` finds the
    rule of its ORDER BY."""

    def __init__(self, check_name, rows):
        super().__init__(rows[:LIMIT])
        self.check = check_name
        self.beyond = rows[LIMIT:]


def streamed(sf, names):
    made = answers(sf, names, keep=LIMIT + BEYOND)
    return {n: Expected(n, made[n]) for n in names}


def source_hash():
    """Of this file: a cached answer is only as good as the code that made it."""
    with open(__file__, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:12]


def cached_streamed(cache_dir, config, sf, names):
    """`streamed`, behind one JSON file per (config, scale, check, hash of
    this file) under `cache_dir`: the first run of a cell in a checkout
    computes, every later one reads.  Returns ({name: Expected}, computed?)."""
    digest = source_hash()
    paths = {n: os.path.join(cache_dir, f"ref_{config}_sf{sf:g}_{n}_{digest}.json")
             for n in names}
    out, missing = {}, []
    for n, p in paths.items():
        try:
            with open(p) as f:
                out[n] = Expected(n, json.load(f))
        except (OSError, ValueError):
            missing.append(n)
    if missing:
        os.makedirs(cache_dir, exist_ok=True)
        made = streamed(sf, missing)
        for n in missing:
            tmp = f"{paths[n]}.tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(list(made[n]) + made[n].beyond, f)
            os.replace(tmp, paths[n])
        out.update(made)
    return out, bool(missing)


def binds_none(sf, rng, spec):
    return [()]


BINDS = {"none": binds_none}


def bind_values(bind):
    return bind


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------

# An ORDER BY or a rank() over a float aggregate: the device sums in
# float32, the reference in float64, so two rows whose ordering values
# agree to `rel` may stand in either order (and take each other's rank,
# or one rank), and no other two may.  Per check: the columns that name a
# row, the exact keys that order before the float, the float, the rank's
# column.  A check without an entry orders by exact keys alone.
ORDERED_BY_FLOAT = {
    "tpcds_q36": {"key": (1, 2),
                  "before": lambda r: (-r[3], nulls_last(
                      r[1] if r[3] == 0 else None)),
                  "value": lambda r: r[0], "rank": 4},
    "tpcds_q89": {"key": (0, 1, 2, 3, 4, 5), "before": lambda r: (),
                  "value": lambda r: r[6] - r[7], "rank": None},
}


def close(a, b, rel):
    return abs(a - b) <= rel * max(abs(a), abs(b), 1.0)


def same_row(got, want, rel, skip=None):
    """Keys, counts and NULLs exact; floats to `rel`."""
    if len(got) != len(want):
        return False
    for j, (a, b) in enumerate(zip(got, want)):
        if j == skip:
            continue
        if isinstance(b, float):
            if not (isinstance(a, (int, float)) and not isinstance(a, bool)
                    and np.isfinite(a) and abs(a - b) <= rel * max(abs(b), 1.0)):
                return False
        elif a != b:
            return False
    return True


def rows_equal(got, want, rel):
    """Row count exact; rows, their order and their ranks exact but for
    ORDERED_BY_FLOAT's near-ties.  `want` is an `Expected`."""
    if len(got) != len(want):
        return False
    rule = ORDERED_BY_FLOAT.get(want.check)
    if rule is None:
        return all(same_row(g, w, rel) for g, w in zip(got, want))
    key, before, value, rank = (rule[k] for k in ("key", "before", "value", "rank"))
    pool = {tuple(r[i] for i in key): r for r in list(want) + want.beyond}
    mine = [pool.get(tuple(g[i] for i in key)) for g in got]
    if any(w is None or not same_row(g, w, rel, skip=rank)
           for g, w in zip(got, mine)):
        return False
    if len({id(w) for w in mine}) != len(mine):
        return False

    def surely_before(a, b):
        """By the reference's float64 values a precedes b, and not by a
        difference that float32 sums could turn."""
        if before(a) != before(b):
            return before(a) < before(b)
        return value(a) < value(b) and not close(value(a), value(b), rel)

    for j, later in enumerate(mine):
        if any(surely_before(later, earlier) for earlier in mine[:j]):
            return False
    inside = {id(w) for w in mine}
    for w in pool.values():     # a row left out that had to come first
        if id(w) not in inside and any(surely_before(w, m) for m in mine):
            return False
    if rank is not None:
        for g, w in zip(got, mine):
            others = [value(o) for o in pool.values()
                      if o is not w and before(o) == before(w)]
            v = value(w)
            lowest = 1 + sum(u < v and not close(u, v, rel) for u in others)
            highest = 1 + sum(u < v or close(u, v, rel) for u in others)
            if not lowest <= g[rank] <= highest:
                return False
    return True


# ---------------------------------------------------------------------------
# bytes a class has to read (the numerator of a memory-bound roofline share)
# ---------------------------------------------------------------------------


def bytes_read(sf, columns_read):
    """Rows times resident width over the columns one execution touches.
    `columns_read` is {table: {column: bytes per value}}, from the
    workload file; rows are `connectors/tpcds.row_count`'s at this scale."""
    from presto_tpu.connectors import tpcds as DS

    return sum(DS.row_count(table, sf) * sum(widths.values())
               for table, widths in columns_read.items())
