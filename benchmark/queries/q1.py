"""TPC-H Q1 (clause 2.4.1), pricing summary report: one grouped aggregate
over nearly all of lineitem."""
from __future__ import annotations

import numpy as np

from _common import column_bytes, days

TABLES = ("lineitem",)
COLUMNS = {"lineitem": {
    "l_quantity": "double", "l_extendedprice": "double", "l_discount": "double",
    "l_tax": "double", "l_returnflag": "l_returnflag",
    "l_linestatus": "l_linestatus", "l_shipdate": "date32",
}}
#: validation parameter of clause 2.4.1.3: DELTA = 90 days before 1998-12-01
DEFAULT_PARAMS = {"delta_days": 90}
RESULT_COLUMNS = (
    "l_returnflag", "l_linestatus", "sum_qty", "sum_base_price",
    "sum_disc_price", "sum_charge", "avg_qty", "avg_price", "avg_disc",
    "count_order",
)


def _cutoff(p):
    from datetime import date, timedelta

    return date(1998, 12, 1) - timedelta(days=p["delta_days"])


def dataframe(t, p):
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.functions import col, count

    disc_price = col("l_extendedprice") * (1 - col("l_discount"))
    return (
        t("lineitem")
        .filter(col("l_shipdate") <= _cutoff(p))
        .group_by("l_returnflag", "l_linestatus")
        .agg(
            F.sum(col("l_quantity")).alias("sum_qty"),
            F.sum(col("l_extendedprice")).alias("sum_base_price"),
            F.sum(disc_price).alias("sum_disc_price"),
            F.sum(disc_price * (1 + col("l_tax"))).alias("sum_charge"),
            F.avg(col("l_quantity")).alias("avg_qty"),
            F.avg(col("l_extendedprice")).alias("avg_price"),
            F.avg(col("l_discount")).alias("avg_disc"),
            count("*").alias("count_order"),
        )
        .order_by("l_returnflag", "l_linestatus")
    )


def sql(p) -> str:
    return (
        "select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty, "
        "sum(l_extendedprice) as sum_base_price, "
        "sum(l_extendedprice * (1 - l_discount)) as sum_disc_price, "
        "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge, "
        "avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price, "
        "avg(l_discount) as avg_disc, count(*) as count_order from lineitem "
        f"where l_shipdate <= date '1998-12-01' - interval '{p['delta_days']}' day "
        "group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus"
    )


def reference(read, p, dtype=np.float64):
    li = read("lineitem", list(COLUMNS["lineitem"]))
    keep = li["l_shipdate"] <= days(_cutoff(p).isoformat())
    flag, status = li["l_returnflag"][keep], li["l_linestatus"][keep]
    qty, price, disc, tax = (
        np.asarray(li[c][keep], dtype=dtype)
        for c in ("l_quantity", "l_extendedprice", "l_discount", "l_tax")
    )
    one = dtype(1)
    disc_price = price * (one - disc)
    charge = disc_price * (one + tax)
    rows = []
    for f in "ANR":      # the domains of clause 4.2.3, in the query's order
        for s in "FO":
            g = (flag == f) & (status == s)
            n = int(g.sum())
            if not n:
                continue
            sums = [np.sum(x[g], dtype=dtype) for x in (qty, price, disc_price, charge)]
            avgs = [np.sum(x[g], dtype=dtype) / dtype(n) for x in (qty, price, disc)]
            rows.append((str(f), str(s), *map(float, sums), *map(float, avgs), n))
    return rows


def min_bytes(rows: dict, result_rows: int) -> int:
    return column_bytes(rows, COLUMNS) + result_rows * (2 * 5 + 8 * 8)
