"""TPC-H Q6 (clause 2.4.6), forecasting revenue change: one filter, one sum."""
from __future__ import annotations

import numpy as np

from _common import column_bytes, days

TABLES = ("lineitem",)
COLUMNS = {"lineitem": {
    "l_shipdate": "date32", "l_discount": "double", "l_quantity": "double",
    "l_extendedprice": "double",
}}
#: validation parameters of clause 2.4.6.3
DEFAULT_PARAMS = {"date": "1994-01-01", "discount": 0.06, "quantity": 24}
RESULT_COLUMNS = ("revenue",)


def dataframe(t, p):
    from datetime import date

    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.functions import col

    lo = date.fromisoformat(p["date"])
    hi = lo.replace(year=lo.year + 1)
    d = p["discount"]
    return (
        t("lineitem")
        .filter(
            (col("l_shipdate") >= lo) & (col("l_shipdate") < hi)
            & (col("l_discount") >= round(d - 0.01, 2))
            & (col("l_discount") <= round(d + 0.01, 2))
            & (col("l_quantity") < p["quantity"])
        )
        .agg(F.sum(col("l_extendedprice") * col("l_discount")).alias("revenue"))
    )


def sql(p) -> str:
    d = p["discount"]
    return (
        "select sum(l_extendedprice * l_discount) as revenue from lineitem "
        f"where l_shipdate >= date '{p['date']}' "
        f"and l_shipdate < date '{p['date']}' + interval '1' year "
        f"and l_discount between {round(d - 0.01, 2)} and {round(d + 0.01, 2)} "
        f"and l_quantity < {p['quantity']}"
    )


def reference(read, p, dtype=np.float64):
    """Rows the query must return, from the same files, in plain numpy.
    ``dtype`` is float64 as the configuration states; the control passes
    float32."""
    li = read("lineitem", list(COLUMNS["lineitem"]))
    lo = days(p["date"])
    hi = days(str(int(p["date"][:4]) + 1) + p["date"][4:])
    disc = li["l_discount"]
    keep = (
        (li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi)
        & (disc >= round(p["discount"] - 0.01, 2))
        & (disc <= round(p["discount"] + 0.01, 2))
        & (li["l_quantity"] < p["quantity"])
    )
    price = li["l_extendedprice"][keep].astype(dtype)
    revenue = np.sum(price * disc[keep].astype(dtype), dtype=dtype)
    return [(float(revenue),)]


def min_bytes(rows: dict, result_rows: int) -> int:
    return column_bytes(rows, COLUMNS) + result_rows * 8
