"""TPC-DS query template 67 (query67.tpl): a year of store sales joined to
three dimensions, GROUP BY ROLLUP over eight columns (five of them strings),
rank() over each category by the rolled-up sum, the first hundred ranks, an
eight-column ORDER BY with nulls last and LIMIT 100."""
from __future__ import annotations

import numpy as np

from _common import ARROW_WIDTH

TABLES = ("store_sales", "date_dim", "item", "store")
#: the rollup's columns, in the template's order
KEYS = ("i_category", "i_class", "i_brand", "i_product_name",
        "d_year", "d_qoy", "d_moy", "s_store_id")
COLUMNS = {
    "store_sales": {"ss_sold_date_sk": "int64", "ss_item_sk": "int64",
                    "ss_store_sk": "int64", "ss_sales_price": "double",
                    "ss_quantity": "int64"},
    "date_dim": {"d_date_sk": "int64", "d_month_seq": "int64", "d_year": "int64",
                 "d_qoy": "int64", "d_moy": "int64"},
    "item": {"i_item_sk": "int64", "i_category": "i_category", "i_class": "i_class",
             "i_brand": "i_brand", "i_product_name": "i_product_name"},
    "store": {"s_store_sk": "int64", "s_store_id": "s_store_id"},
}
#: Arrow widths as _common has them: a 4-byte offset plus the mean length of
#: the generator's values (ten categories of 5.9 letters; "<category>class<k>";
#: "brandbrand#" and one digit for a category's first class, four for the
#: others; "product" and the item's number, 4.4 digits at 18,000 items; an id
#: of 16 characters)
WIDTH = {**ARROW_WIDTH, "i_category": 4 + 5.9, "i_class": 4 + 11.9,
         "i_brand": 4 + 14.625, "i_product_name": 4 + 11.4, "s_store_id": 4 + 16}
_KIND = {name: kind for cols in COLUMNS.values() for name, kind in cols.items()}
KEY_WIDTH = [WIDTH[_KIND[k]] for k in KEYS]
#: a key the rollup has nulled: a number keeps its slot, a string its offset
NULL_WIDTH = [8 if _KIND[k] == "int64" else 4 for k in KEYS]
#: one row of the answer, and of the window's input: the eight keys and sumsales
ROW_BYTES = sum(KEY_WIDTH) + 8
RK_BYTES = 4
#: groups of each rollup level in the last reference(), for window_roofline
LEVEL_ROWS = None
#: the qualification substitution of the specification's appendix B
DEFAULT_PARAMS = {"dms": 1200}
RESULT_COLUMNS = KEYS + ("sumsales", "rk")


def dataframe(t, p):
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.functions import col
    from spark_rapids_tpu.window import Window

    dms = int(p["dms"])
    dates = t("date_dim").filter(
        (col("d_month_seq") >= dms) & (col("d_month_seq") <= dms + 11)
    )
    dw1 = (
        t("store_sales")
        .join(dates, on=[("ss_sold_date_sk", "d_date_sk")])
        .join(t("store"), on=[("ss_store_sk", "s_store_sk")])
        .join(t("item"), on=[("ss_item_sk", "i_item_sk")])
        .rollup(*KEYS)
        .agg(F.sum(F.coalesce(col("ss_sales_price") * col("ss_quantity"), F.lit(0.0)))
             .alias("sumsales"))
    )
    by_category = Window.partition_by("i_category").order_by(col("sumsales").desc())
    return (
        dw1.with_column("rk", F.rank().over(by_category))
        .filter(col("rk") <= 100)
        .order_by(*[col(k).asc_nulls_last() for k in KEYS], col("sumsales"), col("rk"))
        .limit(100)
    )


def sql(p) -> str:
    dms = int(p["dms"])
    keys = ", ".join(KEYS)
    return (
        f"select * from (select {keys}, sumsales, "
        "rank() over (partition by i_category order by sumsales desc) rk "
        f"from (select {keys}, "
        "sum(coalesce(ss_sales_price * ss_quantity, 0)) sumsales "
        "from store_sales, date_dim, store, item "
        "where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk "
        f"and ss_store_sk = s_store_sk and d_month_seq between {dms} and {dms} + 11 "
        f"group by rollup ({keys})) dw1) dw2 where rk <= 100 order by "
        + ", ".join(k + " nulls last" for k in KEYS)
        + ", sumsales, rk limit 100"
    )


def _lookup(keys, wanted):
    """Positions of ``wanted`` in the unique ``keys``, and which were found
    (a null foreign key reads NaN and is found nowhere)."""
    order = np.argsort(keys, kind="stable")
    at = np.searchsorted(keys, wanted, sorter=order)
    at = order[np.minimum(at, len(keys) - 1)]
    return at, keys[at] == wanted


def rollup_levels(read, p, dtype=np.float64):
    """The nine grouping levels of the rollup, each summed from the joined
    rows in the files' order: ``[(codes, sums)]`` where ``codes`` is
    (groups, 8) int64 with -1 for a rolled-up column and ``sums`` is in
    ``dtype``; and the sorted distinct values each column's codes index."""
    ss = read("store_sales", list(COLUMNS["store_sales"]))
    dd = read("date_dim", list(COLUMNS["date_dim"]))
    it = read("item", list(COLUMNS["item"]))
    st = read("store", list(COLUMNS["store"]))
    dms = int(p["dms"])
    d_at, d_ok = _lookup(dd["d_date_sk"], ss["ss_sold_date_sk"])
    i_at, i_ok = _lookup(it["i_item_sk"], ss["ss_item_sk"])
    s_at, s_ok = _lookup(st["s_store_sk"], ss["ss_store_sk"])
    seq = dd["d_month_seq"][d_at]
    keep = d_ok & i_ok & s_ok & (seq >= dms) & (seq <= dms + 11)
    d_at, i_at, s_at = d_at[keep], i_at[keep], s_at[keep]
    price = ss["ss_sales_price"][keep].astype(dtype)
    value = price * ss["ss_quantity"][keep].astype(dtype)
    value = np.where(np.isnan(value), dtype(0), value)  # coalesce(..., 0)
    domains, codes = [], []
    for k, (cols, at) in zip(KEYS, [(it, i_at)] * 4 + [(dd, d_at)] * 3 + [(st, s_at)]):
        values, inverse = np.unique(cols[k], return_inverse=True)
        domains.append(values)
        codes.append(inverse[at].astype(np.int64))
    levels = []
    for depth in range(len(KEYS) + 1):
        key = np.zeros(len(value), np.int64)
        for j in range(depth):
            key = key * len(domains[j]) + codes[j]
        order = np.argsort(key, kind="stable")
        _, starts = np.unique(key[order], return_index=True)
        sums = np.add.reduceat(value[order], starts) if len(value) else value[:0]
        group = np.full((len(starts), len(KEYS)), -1, np.int64)
        for j in range(depth):
            group[:, j] = codes[j][order][starts]
        levels.append((group, sums.astype(dtype)))
    return levels, domains


def reference(read, p, dtype=np.float64):
    """Rows the query must return, from the same files, in plain numpy:
    each rollup level summed from the joined rows, rank with gaps over ties,
    the template's order with nulls last, 100 rows. ``dtype`` is float64 as
    the configuration states; the control passes float32."""
    levels, domains = rollup_levels(read, p, dtype)
    _note_level_rows([len(g) for g, _ in levels])
    group = np.concatenate([g for g, _ in levels])
    sums = np.concatenate([s for _, s in levels])
    # rank() over (partition by i_category order by sumsales desc): one more
    # than the rows of the partition with a strictly greater sum
    order = np.lexsort((-sums, group[:, 0]))
    cat, val = group[order, 0], sums[order]
    pos = np.arange(len(order))
    first_of_cat = np.maximum.accumulate(np.where(np.r_[True, cat[1:] != cat[:-1]], pos, 0))
    first_of_peers = np.maximum.accumulate(
        np.where(np.r_[True, (cat[1:] != cat[:-1]) | (val[1:] != val[:-1])], pos, 0)
    )
    rk = np.empty(len(order), np.int64)
    rk[order] = first_of_peers - first_of_cat + 1
    top = np.flatnonzero(rk <= 100)
    # nulls last: a rolled-up column sorts after every value of its domain
    nulls_last = np.where(group[top] < 0, np.iinfo(np.int64).max, group[top])
    by = [rk[top], sums[top]] + [nulls_last[:, j] for j in reversed(range(len(KEYS)))]
    rows = []
    for i in top[np.lexsort(by)][:100]:
        keys = tuple(
            None if c < 0 else (int(d[c]) if d.dtype.kind in "iu" else str(d[c]))
            for c, d in zip(group[i], domains)
        )
        rows.append(keys + (float(sums[i]), int(rk[i])))
    return rows


def _note_level_rows(rows: list) -> None:
    """Kept on the copy of this module that ``import ds_q67`` gives: run.py
    loads the file under a name of its own, the metric readers import it."""
    import ds_q67

    ds_q67.LEVEL_ROWS = rows


def min_bytes(rows: dict, result_rows: int) -> int:
    read = sum(rows[t] * WIDTH[kind] for t, cols in COLUMNS.items() for kind in cols.values())
    return int(read + result_rows * (ROW_BYTES + RK_BYTES))


def window_min_bytes(level_rows) -> int:
    """Bytes the window must touch: the partition key, the order key and the
    carried columns in (one row of the rollup's answer), the same plus ``rk``
    out. ``level_rows[d]`` rows keep the first ``d`` keys; the others are
    null there."""
    total = 0
    for depth, rows in enumerate(level_rows):
        row = sum(KEY_WIDTH[:depth]) + sum(NULL_WIDTH[depth:]) + 8
        total += rows * (2 * row + RK_BYTES)
    return int(total)
