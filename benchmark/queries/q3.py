"""TPC-H Q3 (clause 2.4.3), shipping priority: three scans, two joins, a
grouped sum and a top-10."""
from __future__ import annotations

import numpy as np

from _common import as_date, column_bytes, days

TABLES = ("customer", "orders", "lineitem")
COLUMNS = {
    "customer": {"c_custkey": "int64", "c_mktsegment": "c_mktsegment"},
    "orders": {"o_orderkey": "int64", "o_custkey": "int64",
               "o_orderdate": "date32", "o_shippriority": "int32"},
    "lineitem": {"l_orderkey": "int64", "l_extendedprice": "double",
                 "l_discount": "double", "l_shipdate": "date32"},
}
#: validation parameters of clause 2.4.3.3
DEFAULT_PARAMS = {"segment": "BUILDING", "date": "1995-03-15"}
RESULT_COLUMNS = ("l_orderkey", "revenue", "o_orderdate", "o_shippriority")


def dataframe(t, p):
    from datetime import date

    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.functions import col

    day = date.fromisoformat(p["date"])
    cust = t("customer").filter(col("c_mktsegment") == p["segment"]).select("c_custkey")
    orders = t("orders").filter(col("o_orderdate") < day).select(
        "o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"
    )
    li = t("lineitem").filter(col("l_shipdate") > day).select(
        "l_orderkey", "l_extendedprice", "l_discount"
    )
    return (
        cust.join(orders, on=[("c_custkey", "o_custkey")])
        .join(li, on=[("o_orderkey", "l_orderkey")])
        .group_by("l_orderkey", "o_orderdate", "o_shippriority")
        .agg(F.sum(col("l_extendedprice") * (1 - col("l_discount"))).alias("revenue"))
        .order_by(col("revenue").desc(), col("o_orderdate"))
        .limit(10)
    )


def sql(p) -> str:
    return (
        "select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue, "
        "o_orderdate, o_shippriority from customer, orders, lineitem "
        f"where c_mktsegment = '{p['segment']}' and c_custkey = o_custkey "
        f"and l_orderkey = o_orderkey and o_orderdate < date '{p['date']}' "
        f"and l_shipdate > date '{p['date']}' "
        "group by l_orderkey, o_orderdate, o_shippriority "
        "order by revenue desc, o_orderdate limit 10"
    )


def reference(read, p, dtype=np.float64):
    cu = read("customer", list(COLUMNS["customer"]))
    od = read("orders", list(COLUMNS["orders"]))
    li = read("lineitem", list(COLUMNS["lineitem"]))
    day = days(p["date"])
    building = cu["c_custkey"][cu["c_mktsegment"] == p["segment"]]
    o_keep = (od["o_orderdate"] < day) & np.isin(od["o_custkey"], building)
    o_key = od["o_orderkey"][o_keep]
    o_date, o_prio = od["o_orderdate"][o_keep], od["o_shippriority"][o_keep]
    l_keep = (li["l_shipdate"] > day) & np.isin(li["l_orderkey"], o_key)
    l_key = li["l_orderkey"][l_keep]
    value = li["l_extendedprice"][l_keep].astype(dtype) * (
        dtype(1) - li["l_discount"][l_keep].astype(dtype)
    )
    order = np.argsort(l_key, kind="stable")
    l_key, value = l_key[order], value[order]
    keys, starts = np.unique(l_key, return_index=True)
    revenue = np.add.reduceat(value, starts) if len(keys) else value[:0]
    at = np.searchsorted(o_key, keys)  # o_orderkey is ascending in the files
    rows = sorted(
        zip(keys.tolist(), revenue.astype(np.float64).tolist(),
            o_date[at].tolist(), o_prio[at].tolist()),
        key=lambda r: (-r[1], r[2]),
    )[:10]
    return [(k, float(v), as_date(d), int(s)) for k, v, d, s in rows]


def min_bytes(rows: dict, result_rows: int) -> int:
    return column_bytes(rows, COLUMNS) + result_rows * (8 + 8 + 4 + 4)
