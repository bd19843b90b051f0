"""TPC-DS query template 95 (query95.tpl): web orders shipped from more than
one warehouse that were also returned, among two months of orders shipped to
one state through one company's sites. ``ws_wh`` joins web_sales to itself on
the order number where the warehouses differ (ten rows out for each row in),
is read twice, and feeds two ``IN (subquery)`` predicates; the answer is one
row: a ``count(distinct)`` and two sums."""
from __future__ import annotations

from datetime import date, timedelta

import numpy as np

from _common import ARROW_WIDTH, EPOCH

TABLES = ("web_sales", "web_returns", "date_dim", "customer_address", "web_site")
COLUMNS = {
    "web_sales": {"ws_order_number": "int64", "ws_warehouse_sk": "int64",
                  "ws_ship_date_sk": "int64", "ws_ship_addr_sk": "int64",
                  "ws_web_site_sk": "int64", "ws_ext_ship_cost": "double",
                  "ws_net_profit": "double"},
    "web_returns": {"wr_order_number": "int64"},
    "date_dim": {"d_date_sk": "int64", "d_date": "date32"},
    "customer_address": {"ca_address_sk": "int64", "ca_state": "ca_state"},
    "web_site": {"web_site_sk": "int64", "web_company_name": "web_company_name"},
}
#: Arrow widths as _common has them: a 4-byte offset plus the mean length of
#: the generator's values (a state's two letters; six company names of 3 to
#: 5 letters, each as often)
WIDTH = {**ARROW_WIDTH, "ca_state": 4 + 2, "web_company_name": 4 + 4.0}
#: the template's constant, and the days its window adds to the first
COMPANY, WINDOW_DAYS = "pri", 60
#: the qualification substitution of the specification's appendix B
DEFAULT_PARAMS = {"year": 1999, "month": 2, "state": "IL"}
RESULT_COLUMNS = ("order_count", "total_shipping_cost", "total_net_profit")
#: one row of the outer query as the semi joins pass it on (the order
#: number and the two money columns), and of ``ws_wh`` (order, wh1, wh2)
OUTER_ROW_BYTES, WS_WH_ROW_BYTES, KEY_BYTES = 24, 24, 8
#: what the last reference() counted, for join_min_bytes and the readers
COUNTS = None


def _window(p) -> tuple:
    first = date(int(p["year"]), int(p["month"]), 1)
    return first, first + timedelta(days=WINDOW_DAYS)


def _ws_wh(t):
    """web_sales joined to itself on the order number where the warehouses
    differ: (ws_order_number, wh1, wh2)."""
    from spark_rapids_tpu.functions import col

    ws1 = t("web_sales").select(col("ws_order_number"), col("ws_warehouse_sk").alias("wh1"))
    ws2 = t("web_sales").select(col("ws_order_number").alias("ws2_order_number"),
                                col("ws_warehouse_sk").alias("wh2"))
    return ws1.join(
        ws2, on=(col("ws_order_number") == col("ws2_order_number")) & (col("wh1") != col("wh2"))
    ).select("ws_order_number", "wh1", "wh2")


def dataframe(t, p):
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.functions import col

    lo, hi = _window(p)
    dates = t("date_dim").filter((col("d_date") >= lo) & (col("d_date") <= hi))
    returned = (
        t("web_returns")
        .join(_ws_wh(t), on=[("wr_order_number", "ws_order_number")])
        .select("wr_order_number")
    )
    return (
        t("web_sales")
        .join(dates, on=[("ws_ship_date_sk", "d_date_sk")])
        .join(t("customer_address").filter(col("ca_state") == p["state"]),
              on=[("ws_ship_addr_sk", "ca_address_sk")])
        .join(t("web_site").filter(col("web_company_name") == COMPANY),
              on=[("ws_web_site_sk", "web_site_sk")])
        .filter(col("ws_order_number").isin(_ws_wh(t).select("ws_order_number"))
                & col("ws_order_number").isin(returned))
        .agg(F.count_distinct(col("ws_order_number")).alias("order_count"),
             F.sum(col("ws_ext_ship_cost")).alias("total_shipping_cost"),
             F.sum(col("ws_net_profit")).alias("total_net_profit"))
        .order_by("order_count")
        .limit(100)
    )


def sql(p) -> str:
    lo, _ = _window(p)
    return (
        "with ws_wh as (select ws1.ws_order_number, ws1.ws_warehouse_sk wh1, "
        "ws2.ws_warehouse_sk wh2 from web_sales ws1, web_sales ws2 "
        "where ws1.ws_order_number = ws2.ws_order_number "
        "and ws1.ws_warehouse_sk <> ws2.ws_warehouse_sk) "
        "select count(distinct ws_order_number) as order_count, "
        "sum(ws_ext_ship_cost) as total_shipping_cost, "
        "sum(ws_net_profit) as total_net_profit "
        "from web_sales ws1, date_dim, customer_address, web_site "
        f"where d_date between date '{lo.isoformat()}' and "
        f"date '{lo.isoformat()}' + interval '{WINDOW_DAYS}' day "
        "and ws1.ws_ship_date_sk = d_date_sk and ws1.ws_ship_addr_sk = ca_address_sk "
        f"and ca_state = '{p['state']}' and ws1.ws_web_site_sk = web_site_sk "
        f"and web_company_name = '{COMPANY}' "
        "and ws1.ws_order_number in (select ws_order_number from ws_wh) "
        "and ws1.ws_order_number in (select wr_order_number from web_returns, ws_wh "
        "where wr_order_number = ws_wh.ws_order_number) "
        "order by count(distinct ws_order_number) limit 100"
    )


def _per_order(order: np.ndarray, weights=None, size: int = 0) -> np.ndarray:
    return np.bincount(order, weights=weights, minlength=size).astype(np.int64)


def reference(read, p, dtype=np.float64):
    """The one row the query must return, from the same files, in plain
    numpy. A null key reads NaN and equals nothing: a line with no warehouse
    pairs with none, a null order number is in no subquery's result, a null
    foreign key finds no dimension row; a null money value is left out of its
    sum. Sums run over the qualifying lines in the files' order, in
    ``dtype``: float64 as the configuration states; the control passes
    float32. Leaves what it counted on the way in ``COUNTS``."""
    ws = read("web_sales", list(COLUMNS["web_sales"]))
    wr = read("web_returns", list(COLUMNS["web_returns"]))
    dd = read("date_dim", list(COLUMNS["date_dim"]))
    ca = read("customer_address", list(COLUMNS["customer_address"]))
    site = read("web_site", list(COLUMNS["web_site"]))

    order = np.asarray(ws["ws_order_number"], np.float64)
    wh = np.asarray(ws["ws_warehouse_sk"], np.float64)
    size = int(np.nanmax(order, initial=0)) + 1
    has_order = ~np.isnan(order)
    o = np.where(has_order, order, 0).astype(np.int64)
    # ws_wh: a line pairs with the lines of its order in another warehouse
    lines = _per_order(o[has_order], size=size)  # key-matched: every line of the order
    placed = has_order & ~np.isnan(wh)
    in_wh = _per_order(o[placed], size=size)
    same = np.zeros(size, np.int64)
    for w in np.unique(wh[placed]):
        c = _per_order(o[placed & (wh == w)], size=size)
        same += c * c
    pairs = in_wh * in_wh - same  # rows of ws_wh, by order
    # the second subquery: a return joined to every row of ws_wh of its order
    r_order = np.asarray(wr["wr_order_number"], np.float64)
    r_order = r_order[~np.isnan(r_order) & (r_order < size)].astype(np.int64)
    returns = _per_order(r_order, size=size)
    in_first = pairs > 0
    in_second = in_first & (returns > 0)

    lo, hi = _window(p)
    day_lo, day_hi = (lo - EPOCH).days, (hi - EPOCH).days
    days = dd["d_date_sk"][(dd["d_date"] >= day_lo) & (dd["d_date"] <= day_hi)]
    addresses = ca["ca_address_sk"][ca["ca_state"] == p["state"]]
    sites = site["web_site_sk"][site["web_company_name"] == COMPANY]
    after_dates = np.isin(ws["ws_ship_date_sk"], days)
    after_addresses = after_dates & np.isin(ws["ws_ship_addr_sk"], addresses)
    outer = after_addresses & np.isin(ws["ws_web_site_sk"], sites)
    first = outer & has_order & in_first[o]
    keep = first & in_second[o]

    def total(column: str):
        values = ws[column][keep]
        values = values[~np.isnan(values)].astype(dtype)
        # SQL's sum over no value is null
        return float(np.cumsum(values, dtype=dtype)[-1]) if len(values) else None

    _note_counts({
        "web_sales": len(order), "web_returns": len(wr["wr_order_number"]),
        "date_dim": len(dd["d_date_sk"]), "customer_address": len(ca["ca_address_sk"]),
        "web_site": len(site["web_site_sk"]),
        "dates": len(days), "addresses": len(addresses), "sites": len(sites),
        "key_matched_pairs": int((lines * lines).sum()),
        "ws_wh_rows": int(pairs.sum()),
        "returns_join_rows": int((returns * pairs).sum()),
        "after_dates": int(after_dates.sum()), "after_addresses": int(after_addresses.sum()),
        "semi1_in": int(outer.sum()), "semi1_out": int(first.sum()),
        "semi2_out": int(keep.sum()),
        # rows of each subquery's result that a probe line of its semi join matches
        "semi1_pairs": int(pairs[o[outer & has_order]].sum()),
        "semi2_pairs": int((returns * pairs)[o[first]].sum()),
        "qualifying_orders": int(len(np.unique(o[keep]))),
    })
    return [(int(len(np.unique(o[keep]))), total("ws_ext_ship_cost"), total("ws_net_profit"))]


def _note_counts(counts: dict) -> None:
    """Kept on the copy of this module that ``import ds_q95`` gives: run.py
    loads the file under a name of its own, the metric readers import it."""
    import ds_q95

    ds_q95.COUNTS = counts


def key_matched_pairs(counts: dict) -> int:
    """Pairs the query's equi-joins match on their keys, before any residual
    condition: what the program's ``join.rowsOut`` counts a query. ``ws_wh``
    stands twice; the three dimension joins emit what they keep."""
    return (2 * counts["key_matched_pairs"] + counts["returns_join_rows"]
            + counts["after_dates"] + counts["after_addresses"] + counts["semi1_in"]
            + counts["semi1_pairs"] + counts["semi2_pairs"])


def min_bytes(rows: dict, result_rows: int) -> int:
    read = sum(rows[t] * WIDTH[kind] for t, cols in COLUMNS.items() for kind in cols.values())
    return int(read + result_rows * 3 * 8)


def join_min_bytes(counts: dict) -> int:
    """Bytes the query's joins must touch, whatever implements them: each
    join reads the key column of both its sides (and what its condition
    compares) and writes its rows, at Arrow widths, for the row counts the
    reference counted. ``ws_wh`` stands twice in the query and is counted
    twice: a self-join reads order number and warehouse of every line on
    both sides and writes (order, wh1, wh2) a pair."""
    ws, k = counts["web_sales"], KEY_BYTES
    ws_wh = 2 * ws * 2 * k + counts["ws_wh_rows"] * WS_WH_ROW_BYTES
    returns_join = (counts["web_returns"] + counts["ws_wh_rows"]) * k \
        + counts["returns_join_rows"] * k
    dimensions = (
        (ws + counts["date_dim"]) * k + counts["after_dates"] * (OUTER_ROW_BYTES + 2 * k)
        + (counts["after_dates"] + counts["customer_address"]) * k
        + counts["after_addresses"] * (OUTER_ROW_BYTES + k)
        + (counts["after_addresses"] + counts["web_site"]) * k
        + counts["semi1_in"] * OUTER_ROW_BYTES
    )
    semi1 = (counts["semi1_in"] + counts["ws_wh_rows"]) * k \
        + counts["semi1_out"] * OUTER_ROW_BYTES
    semi2 = (counts["semi1_out"] + counts["returns_join_rows"]) * k \
        + counts["semi2_out"] * OUTER_ROW_BYTES
    return int(2 * ws_wh + returns_join + dimensions + semi1 + semi2)
