"""The benchmark's own TPC-DS generator: store_sales, date_dim, item and store
at a scale factor, from a seed, written as multi-file Parquet, every column of
the four tables.

Copied in shape from ``spark_rapids_tpu/tpcds/datagen.py`` (dsdgen-like
cardinalities and value domains, foreign keys with 2 % nulls, the per-line
money chain, money as float64), kept here so that no later change to the
program can change the data a cell reads; it imports nothing of the program.
Text columns are built with Arrow kernels instead of Python string loops, so
SF 1 takes seconds. One departure from the program's rig, on purpose:
``d_month_seq`` counts months from January 1900 as dsdgen's does, so that
January 2000 is 1200 and query template 67 keeps the specification's
``DMS = 1200`` (the rig counts from 1970 and its query text says 360).
Two more, where the specification fixes a shape that the rig does not keep:
business ids are char(16) (the rig's have 24 characters, and ``s_store_id``
is a rollup key whose padded width sets the sort's key words), and
``date_dim`` is the specification's 73,049 days from 1900-01-02 with the
julian day number as ``d_date_sk`` (the rig keeps seven years).

The marker of a finished write is this generator's own file: ``datagen.py``
keeps ``_COMPLETE.json`` under the same root (both configurations are SF 1),
and neither generator reads, rewrites or removes anything of the other's.
"""
from __future__ import annotations

import json
import os
import shutil
from concurrent.futures import ThreadPoolExecutor
from datetime import date, timedelta

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

EPOCH = date(1970, 1, 1)


def _d(y: int, m: int, d_: int) -> int:
    return (date(y, m, d_) - EPOCH).days


# date_dim is the specification's 73,049 days; every sale lies in 1998-2002
DATE_LO = _d(1900, 1, 2)
DATE_HI = _d(2100, 1, 1)
SALES_LO = _d(1998, 1, 1)
SALES_HI = _d(2002, 12, 31)  # exclusive, as the rig draws it
#: d_date_sk is the julian day number: 2415022 on 1900-01-02
SK_BASE = 2415022
#: item and store records are current since this day
REC_START = _d(1997, 1, 1)

CATEGORIES = [
    "Books", "Children", "Electronics", "Home", "Jewelry",
    "Men", "Music", "Shoes", "Sports", "Women",
]
CLASSES_PER_CAT = 8
BRANDS_PER_CLASS = 9
COLORS = [
    "white", "black", "red", "blue", "green", "yellow", "purple", "brown",
    "pink", "orange", "gray", "cream", "navy", "khaki", "salmon", "beige",
    "maroon", "olive", "turquoise", "azure", "chocolate", "coral", "ivory",
    "linen", "plum", "tan", "violet", "wheat", "snow", "misty", "powder",
    "honeydew", "floral", "deep", "light", "cornflower", "midnight", "cyan",
    "papaya", "frosted", "forest", "ghost", "pale", "peach", "metallic",
    "burnished", "spring", "sky", "steel", "seashell",
]
SIZES = ["small", "medium", "large", "extra large", "economy", "N/A", "petite"]
UNITS = [
    "Each", "Dozen", "Case", "Pallet", "Gross", "Box", "Bunch", "Carton",
    "Cup", "Dram", "Gram", "Lb", "Oz", "Ounce", "Pound", "Tbl", "Ton", "Tsp",
    "Unknown", "N/A",
]
ITEM_DESCS = [
    "carefully packed product", "bright popular gadget",
    "durable household staple", "imported seasonal special",
    "classic bestselling title", "quiet reliable tool",
    "colorful youth favorite", "premium branded accessory",
]
DAY_NAMES = ["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday", "Friday", "Saturday"]
STORE_NAMES = ["ought", "able", "pri", "ese", "anti", "cally", "ation", "eing", "bar"]
STORE_HOURS = ["8AM-4PM", "8AM-12AM", "8AM-8AM"]
FIRST_NAMES = [
    "James", "Mary", "John", "Patricia", "Robert", "Jennifer", "Michael",
    "Linda", "William", "Elizabeth", "David", "Barbara", "Richard", "Susan",
    "Joseph", "Jessica", "Thomas", "Sarah", "Charles", "Karen", "Daniel",
    "Nancy", "Matthew", "Lisa", "Anthony", "Betty", "Mark", "Margaret",
    "Donald", "Sandra", "Steven", "Ashley", "Paul", "Kimberly", "Andrew",
    "Emily", "Joshua", "Donna", "Kenneth", "Michelle",
]
LAST_NAMES = [
    "Smith", "Johnson", "Williams", "Brown", "Jones", "Garcia", "Miller",
    "Davis", "Rodriguez", "Martinez", "Hernandez", "Lopez", "Gonzalez",
    "Wilson", "Anderson", "Thomas", "Taylor", "Moore", "Jackson", "Martin",
    "Lee", "Perez", "Thompson", "White", "Harris", "Sanchez", "Clark",
    "Ramirez", "Lewis", "Robinson", "Walker", "Young", "Allen", "King",
    "Wright", "Scott", "Torres", "Nguyen", "Hill", "Flores",
]
MANAGERS = [f"{f} {l}" for f, l in zip(FIRST_NAMES[:20], LAST_NAMES[:20])]
MARKET_MANAGERS = [f"{f} {l}" for f, l in zip(FIRST_NAMES[20:], LAST_NAMES[20:])]
STREET_NAMES = [
    "Main", "Oak", "Park", "First", "Second", "Cedar", "Elm", "Maple",
    "Pine", "Lake", "Hill", "Washington", "Lincoln", "Jackson", "Church",
    "Spring", "River", "Sunset", "Highland", "Meadow",
]
STREET_TYPES = [
    "Street", "Avenue", "Boulevard", "Circle", "Court", "Drive", "Lane",
    "Parkway", "Road", "Way",
]
CITIES = ["Fairview", "Midway", "Oak Grove", "Five Points", "Centerville",
          "Pleasant Hill", "Riverside", "Salem"]
COUNTIES = ["Williamson County", "Ziebach County", "Walker County",
            "Daviess County", "Barrow County", "Franklin Parish"]
STATES = ["TN", "SD", "AL", "GA", "TX", "OH", "IL", "CA"]
ZIPS = [f"{z:05d}" for z in range(30001, 30101)]

#: row counts at SF 1; facts scale linearly, dimensions with sqrt(SF) like
#: dsdgen's stepped scaling, date_dim is fixed
SF1 = {"store_sales": 2_880_000, "item": 18_000, "store": 12, "customer": 100_000,
       "customer_address": 50_000, "promotion": 300}

#: tables this generator knows; a configuration's queries name a subset
TABLES = ("store_sales", "date_dim", "item", "store")
FILES_PER_TABLE = 8
MARKER = "_TPCDS_COMPLETE.json"
#: raised when the tables' contents change, so that an older write is made anew
CONTENTS = 2


def n_rows(name: str, sf: float) -> int:
    if name == "date_dim":
        return DATE_HI - DATE_LO + 1
    if name == "store_sales":
        return max(10, int(SF1[name] * sf))
    n = max(10, int(SF1[name] * (sf ** 0.5)))
    return max(2, n) if name == "store" else n


def _sk(days) -> np.ndarray:
    return (np.asarray(days) - DATE_LO + SK_BASE).astype(np.int64)


def _pick(vocab, idx) -> pa.Array:
    """vocab[idx] as an Arrow string array, without a Python loop."""
    return pc.take(pa.array(vocab), pa.array(idx))


def _joined(*parts) -> pa.Array:
    """Element-wise concatenation of strings, scalars and integer arrays."""
    cols = [
        pa.scalar(p) if isinstance(p, str)
        else p if isinstance(p, (pa.Array, pa.ChunkedArray))
        else pa.array(p).cast(pa.string())
        for p in parts
    ]
    return pc.binary_join_element_wise(*cols, "")


def _id_col(lo: int, hi: int) -> pa.Array:
    """Business ids of rows ``lo+1 .. hi``, char(16) as the specification
    has them: dsdgen's prefix and eight digits."""
    digits = pc.utf8_lpad(pa.array(np.arange(lo + 1, hi + 1)).cast(pa.string()), 8, "0")
    return _joined("AAAAAAAA", digits)


def _const(value, n: int, type_=None) -> pa.Array:
    return pa.array([value] * n, type=type_)


def _nullable(values: np.ndarray, null: np.ndarray) -> pa.Array:
    return pa.array(values.astype(np.int64), mask=null)


def _money(rng, lo, hi, n):
    return np.round(rng.uniform(lo, hi, n), 2)


# Each table is generated in two steps, as datagen.py does: every random draw
# and every number as numpy arrays over the whole table, then the Arrow
# columns of one file's rows at a time, so that text columns and the Parquet
# encoding of the files run side by side.

def draw_date_dim(sf: float, seed: int) -> dict:
    days = np.arange(DATE_LO, DATE_HI + 1, dtype=np.int64)
    dates = [EPOCH + timedelta(days=int(d)) for d in days]
    years = np.array([d.year for d in dates], np.int64)
    moy = np.array([d.month for d in dates], np.int64)
    qoy = (moy - 1) // 3 + 1
    return {
        "rows": len(days), "days": days, "year": years, "moy": moy,
        "dom": np.array([d.day for d in dates], np.int64),
        "dow": np.array([(d.weekday() + 1) % 7 for d in dates], np.int64),  # 0 = Sunday
        "qoy": qoy,
        # months, quarters and weeks are counted from January 1900
        "month_seq": (years - 1900) * 12 + moy - 1,
        "quarter_seq": (years - 1900) * 4 + qoy - 1,
        "week_seq": (days - _d(1900, 1, 1)) // 7 + 1,
        "first_dom": np.array([_d(d.year, d.month, 1) for d in dates], np.int64),
    }


def arrow_date_dim(d: dict, lo: int, hi: int) -> pa.Table:
    s = slice(lo, hi)
    n = hi - lo
    days, dow = d["days"][s], d["dow"][s]
    no = _const("N", n)
    return pa.table({
        "d_date_sk": _sk(days),
        "d_date_id": _id_col(lo, hi),
        "d_date": pa.array(days.astype(np.int32), type=pa.date32()),
        "d_month_seq": d["month_seq"][s],
        "d_week_seq": d["week_seq"][s],
        "d_quarter_seq": d["quarter_seq"][s],
        "d_year": d["year"][s],
        "d_dow": dow,
        "d_moy": d["moy"][s],
        "d_dom": d["dom"][s],
        "d_qoy": d["qoy"][s],
        "d_fy_year": d["year"][s],
        "d_fy_quarter_seq": d["quarter_seq"][s],
        "d_fy_week_seq": d["week_seq"][s],
        "d_day_name": _pick(DAY_NAMES, dow),
        "d_quarter_name": _joined(d["year"][s], "Q", d["qoy"][s]),
        "d_holiday": no,
        "d_weekend": _pick(["Y", "N", "N", "N", "N", "N", "Y"], dow),
        "d_following_holiday": no,
        "d_first_dom": _sk(d["first_dom"][s]),
        "d_last_dom": _sk(d["first_dom"][s] + 27),
        "d_same_day_ly": _sk(np.maximum(days - 365, DATE_LO)),
        "d_same_day_lq": _sk(np.maximum(days - 91, DATE_LO)),
        "d_current_day": no,
        "d_current_week": no,
        "d_current_month": no,
        "d_current_quarter": no,
        "d_current_year": no,
    })


def draw_item(sf: float, seed: int) -> dict:
    rng = np.random.default_rng([seed, 11])
    n = n_rows("item", sf)
    cat = rng.integers(0, len(CATEGORIES), n)
    cls = rng.integers(0, CLASSES_PER_CAT, n)
    brand = rng.integers(1, BRANDS_PER_CLASS + 1, n)
    price = _money(rng, 0.5, 300.0, n)
    return {
        "rows": n, "cat": cat, "cls": cls,
        "brand_id": ((cat + 1) * 1_000_000 + cls * 1000 + brand).astype(np.int64),
        "manufact": rng.integers(1, 1001, n).astype(np.int64),
        "price": price,
        "wholesale": np.round(price * rng.uniform(0.4, 0.8, n), 2),
        "desc": rng.integers(0, len(ITEM_DESCS), n),
        "size": rng.integers(0, len(SIZES), n),
        "formulation": rng.integers(0, len(COLORS), n),
        "color": rng.integers(0, len(COLORS), n),
        "units": rng.integers(0, len(UNITS), n),
        "manager": rng.integers(1, 101, n).astype(np.int64),
    }


def arrow_item(d: dict, lo: int, hi: int) -> pa.Table:
    s = slice(lo, hi)
    n = hi - lo
    cat, cls = d["cat"][s], d["cls"][s]
    return pa.table({
        "i_item_sk": np.arange(lo + 1, hi + 1, dtype=np.int64),
        "i_item_id": _id_col(lo, hi),
        "i_rec_start_date": pa.array(np.full(n, REC_START, np.int32), type=pa.date32()),
        "i_rec_end_date": _const(None, n, pa.date32()),
        "i_item_desc": _pick(ITEM_DESCS, d["desc"][s]),
        "i_current_price": d["price"][s],
        "i_wholesale_cost": d["wholesale"][s],
        "i_brand_id": d["brand_id"][s],
        "i_brand": _joined("brandbrand#", d["brand_id"][s] % 100000),
        "i_class_id": cls.astype(np.int64) + 1,
        "i_class": _joined(_pick([c.lower() for c in CATEGORIES], cat), "class", cls + 1),
        "i_category_id": cat.astype(np.int64) + 1,
        "i_category": _pick(CATEGORIES, cat),
        "i_manufact_id": d["manufact"][s],
        "i_manufact": _joined("manufact#", d["manufact"][s]),
        "i_size": _pick(SIZES, d["size"][s]),
        "i_formulation": _pick(COLORS, d["formulation"][s]),
        "i_color": _pick(COLORS, d["color"][s]),
        "i_units": _pick(UNITS, d["units"][s]),
        "i_container": _const("Unknown", n),
        "i_manager_id": d["manager"][s],
        "i_product_name": _joined("product", np.arange(lo + 1, hi + 1)),
    })


def draw_store(sf: float, seed: int) -> dict:
    rng = np.random.default_rng([seed, 13])
    n = n_rows("store", sf)
    return {
        "rows": n,
        "name": rng.integers(0, len(STORE_NAMES), n),
        "employees": rng.integers(200, 301, n).astype(np.int64),
        "floor_space": rng.integers(5_000_000, 10_000_001, n).astype(np.int64),
        "hours": rng.integers(0, len(STORE_HOURS), n),
        "manager": rng.integers(0, len(MANAGERS), n),
        "market_id": rng.integers(1, 11, n).astype(np.int64),
        "market_manager": rng.integers(0, len(MARKET_MANAGERS), n),
        "street_number": rng.integers(1, 1000, n),
        "street_name": rng.integers(0, len(STREET_NAMES), n),
        "street_type": rng.integers(0, len(STREET_TYPES), n),
        "suite": rng.integers(0, 500, n),
        "city": rng.integers(0, len(CITIES), n),
        "county": rng.integers(0, len(COUNTIES), n),
        "state": rng.integers(0, len(STATES), n),
        "zip": rng.integers(0, len(ZIPS), n),
        "gmt": rng.choice([-5.0, -6.0], n),
        "tax": np.round(rng.uniform(0.0, 0.11, n), 2),
    }


def arrow_store(d: dict, lo: int, hi: int) -> pa.Table:
    s = slice(lo, hi)
    n = hi - lo
    unknown = _const("Unknown", n)
    return pa.table({
        "s_store_sk": np.arange(lo + 1, hi + 1, dtype=np.int64),
        "s_store_id": _id_col(lo, hi),
        "s_rec_start_date": pa.array(np.full(n, REC_START, np.int32), type=pa.date32()),
        "s_rec_end_date": _const(None, n, pa.date32()),
        "s_closed_date_sk": _const(None, n, pa.int64()),
        "s_store_name": _pick(STORE_NAMES, d["name"][s]),
        "s_number_employees": d["employees"][s],
        "s_floor_space": d["floor_space"][s],
        "s_hours": _pick(STORE_HOURS, d["hours"][s]),
        "s_manager": _pick(MANAGERS, d["manager"][s]),
        "s_market_id": d["market_id"][s],
        "s_geography_class": unknown,
        "s_market_desc": _const("store market description", n),
        "s_market_manager": _pick(MARKET_MANAGERS, d["market_manager"][s]),
        "s_division_id": np.ones(n, np.int64),
        "s_division_name": unknown,
        "s_company_id": np.ones(n, np.int64),
        "s_company_name": unknown,
        "s_street_number": pa.array(d["street_number"][s]).cast(pa.string()),
        "s_street_name": _pick(STREET_NAMES, d["street_name"][s]),
        "s_street_type": _pick(STREET_TYPES, d["street_type"][s]),
        "s_suite_number": _joined("Suite ", d["suite"][s]),
        "s_city": _pick(CITIES, d["city"][s]),
        "s_county": _pick(COUNTIES, d["county"][s]),
        "s_state": _pick(STATES, d["state"][s]),
        "s_zip": _pick(ZIPS, d["zip"][s]),
        "s_country": _const("United States", n),
        "s_gmt_offset": d["gmt"][s],
        "s_tax_precentage": d["tax"][s],
    })


def draw_store_sales(sf: float, seed: int) -> dict:
    rng = np.random.default_rng([seed, 17])
    n = n_rows("store_sales", sf)

    def fk(hi: int, null_share: float) -> tuple:
        return rng.integers(1, hi + 1, n), rng.random(n) < null_share

    qty = rng.integers(1, 101, n)
    # the specification's per-line money chain: wholesale, list, sales, ext_*
    wholesale = _money(rng, 1.0, 100.0, n)
    list_price = np.round(wholesale * rng.uniform(1.0, 2.0, n), 2)
    sales_price = np.round(list_price * rng.uniform(0.0, 1.0, n), 2)
    ext_sales = np.round(sales_price * qty, 2)
    ext_wholesale = np.round(wholesale * qty, 2)
    tax = np.round(ext_sales * rng.uniform(0.0, 0.09, n), 2)
    coupon = np.where(rng.random(n) < 0.1,
                      np.round(ext_sales * rng.uniform(0.0, 0.5, n), 2), 0.0)
    net_paid = np.round(ext_sales - coupon, 2)
    return {
        "rows": n,
        "date": (_sk(rng.integers(SALES_LO, SALES_HI, n)), rng.random(n) < 0.02),
        "time": (rng.integers(0, 1440, n) * 60).astype(np.int64),
        "item": rng.integers(1, n_rows("item", sf) + 1, n).astype(np.int64),
        "customer": fk(n_rows("customer", sf), 0.02),
        "cdemo": fk(2 * 5 * 7 * 20, 0.02),
        "hdemo": fk(20 * 6 * 10 * 6, 0.02),
        "addr": fk(n_rows("customer_address", sf), 0.02),
        "store": fk(n_rows("store", sf), 0.02),
        "promo": fk(n_rows("promotion", sf), 0.1),
        "ticket": np.arange(n, dtype=np.int64) // 4 + 1,
        "qty": qty.astype(np.int64),
        "wholesale": wholesale, "list": list_price, "sales": sales_price,
        "ext_discount": np.round((list_price - sales_price) * qty, 2),
        "ext_sales": ext_sales, "ext_wholesale": ext_wholesale,
        "ext_list": np.round(list_price * qty, 2),
        "tax": tax, "coupon": coupon, "net_paid": net_paid,
        "net_paid_tax": np.round(net_paid + tax, 2),
        "net_profit": np.round(net_paid - ext_wholesale, 2),
    }


def arrow_store_sales(d: dict, lo: int, hi: int) -> pa.Table:
    s = slice(lo, hi)

    def fk(name: str) -> pa.Array:
        values, null = d[name]
        return _nullable(values[s], null[s])

    return pa.table({
        "ss_sold_date_sk": fk("date"),
        "ss_sold_time_sk": d["time"][s],
        "ss_item_sk": d["item"][s],
        "ss_customer_sk": fk("customer"),
        "ss_cdemo_sk": fk("cdemo"),
        "ss_hdemo_sk": fk("hdemo"),
        "ss_addr_sk": fk("addr"),
        "ss_store_sk": fk("store"),
        "ss_promo_sk": fk("promo"),
        "ss_ticket_number": d["ticket"][s],
        "ss_quantity": d["qty"][s],
        "ss_wholesale_cost": d["wholesale"][s],
        "ss_list_price": d["list"][s],
        "ss_sales_price": d["sales"][s],
        "ss_ext_discount_amt": d["ext_discount"][s],
        "ss_ext_sales_price": d["ext_sales"][s],
        "ss_ext_wholesale_cost": d["ext_wholesale"][s],
        "ss_ext_list_price": d["ext_list"][s],
        "ss_ext_tax": d["tax"][s],
        "ss_coupon_amt": d["coupon"][s],
        "ss_net_paid": d["net_paid"][s],
        "ss_net_paid_inc_tax": d["net_paid_tax"][s],
        "ss_net_profit": d["net_profit"][s],
    })


DRAW = {"store_sales": draw_store_sales, "date_dim": draw_date_dim,
        "item": draw_item, "store": draw_store}
ARROW = {"store_sales": arrow_store_sales, "date_dim": arrow_date_dim,
         "item": arrow_item, "store": arrow_store}


def _write_slice(name: str, draws: dict, lo: int, hi: int, path: str) -> None:
    pq.write_table(ARROW[name](draws, lo, hi), path)


def _submit_files(name: str, draws: dict, directory: str, files: int, pool) -> list:
    os.makedirs(directory, exist_ok=True)
    rows = draws["rows"]
    k = files if rows >= files * 64 else 1
    step = -(-rows // k)
    return [
        pool.submit(
            _write_slice, name, draws, i * step, min((i + 1) * step, rows),
            os.path.join(directory, f"part-{i:03d}.parquet"),
        )
        for i in range(k)
        if i * step < rows
    ]


def ensure_tables(root: str, sf: float, seed: int, tables,
                  files_per_table: int = FILES_PER_TABLE) -> dict:
    """``{table: directory}`` for ``tables`` under ``root``, generated where
    this generator's marker of a finished earlier write does not list them.
    ``_generated`` says whether anything was written this time. Only the
    directories of this generator's own tables are ever removed."""
    unknown = sorted(set(tables) - set(TABLES))
    if unknown:
        raise KeyError(f"the generator has no table {unknown}; it has {TABLES}")
    marker = os.path.join(root, MARKER)
    done = {}
    if os.path.exists(marker):
        with open(marker) as f:
            done = json.load(f)
    same = (done.get("sf"), done.get("seed"), done.get("contents")) == (sf, seed, CONTENTS)
    have = set(done.get("tables", [])) if same else set()
    missing = set(tables) - have
    if missing:
        if os.path.exists(marker):
            os.remove(marker)
        for name in missing:
            shutil.rmtree(os.path.join(root, name), ignore_errors=True)
        draws = {name: DRAW[name](sf, seed) for name in missing}
        with ThreadPoolExecutor(max_workers=files_per_table) as pool:
            futures = []
            # the largest table first, so that its files fill the pool
            for name in sorted(draws, key=lambda t: -draws[t]["rows"]):
                futures += _submit_files(
                    name, draws[name], os.path.join(root, name), files_per_table, pool
                )
            for fut in futures:
                fut.result()
        with open(marker, "w") as f:
            json.dump({"sf": sf, "seed": seed, "contents": CONTENTS,
                       "tables": sorted(have | missing)}, f)
    out = {name: os.path.join(root, name) for name in tables}
    out["_generated"] = bool(missing)
    return out
