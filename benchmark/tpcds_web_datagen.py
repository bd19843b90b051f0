"""The benchmark's generator of TPC-DS's web channel: web_sales, web_returns,
customer_address, web_site and date_dim at a scale factor, from a seed,
written as multi-file Parquet, every column of the five tables (34, 24, 13,
26 and 28).

A sibling of ``tpcds_datagen.py`` and built the same way: every random draw
as numpy arrays over the whole table, then the Arrow columns of one file's
rows at a time; it imports nothing of the program. ``date_dim`` is that
module's own (``draw_date_dim``, ``arrow_date_dim``), so both generators
write the same bytes for it, and where that generator's marker says it has
already written ``date_dim`` for the same scale and seed under the same root,
its files are used as they are.

What the specification fixes is kept: at SF 1 web_sales has 719,384 rows in
60,000 orders, web_returns 71,763, customer_address 50,000, web_site 30,
date_dim 73,049; ``ws_warehouse_sk`` ranges over 5 warehouses; sales lie in
1998-2002 and a line ships 1 to 120 days after its order was placed; a return
is of a line that was sold (its ``wr_order_number`` and ``wr_item_sk``, one
return a line at most); foreign keys are null in 2 % of rows. An order's
lines share what dsdgen's ``mk_master`` draws once an order (the day and time
sold, the billing and the shipping customer with their demographics and
addresses); ship date, item, page, site, ship mode, warehouse, promotion and
prices are drawn a line (``mk_detail``). Set here, and listed under
``assumed`` in the configuration: 8 to 16 lines an order, uniform; six
company names of equal weight; the value domains.

The marker of a finished write is this generator's own file, beside
``datagen.py``'s and ``tpcds_datagen.py``'s under a shared root; only the
directories of this generator's own tables are ever removed.
"""
from __future__ import annotations

import json
import os
import shutil
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

import tpcds_datagen as base
from tpcds_datagen import (  # noqa: F401 - date_dim is that module's own
    DATE_HI, DATE_LO, REC_START, SALES_HI, SALES_LO, arrow_date_dim, draw_date_dim,
)

#: row counts at SF 1; facts and orders scale linearly, dimensions with sqrt(SF)
SF1 = {"web_sales": 719_384, "orders": 60_000, "web_returns": 71_763,
       "customer_address": 50_000, "web_site": 30, "customer": 100_000,
       "item": 18_000, "web_page": 60, "promotion": 300}
LINES_PER_ORDER = (8, 16)
WAREHOUSES = 5
SHIP_MODES = 20
REASONS = 35
#: days from the order to a line's shipping
SHIP_LAG = (1, 120)
#: the fifty states and the District of Columbia
STATES = [
    "AK", "AL", "AR", "AZ", "CA", "CO", "CT", "DC", "DE", "FL", "GA", "HI", "IA",
    "ID", "IL", "IN", "KS", "KY", "LA", "MA", "MD", "ME", "MI", "MN", "MO", "MS",
    "MT", "NC", "ND", "NE", "NH", "NJ", "NM", "NV", "NY", "OH", "OK", "OR", "PA",
    "RI", "SC", "SD", "TN", "TX", "UT", "VA", "VT", "WA", "WI", "WV", "WY",
]
#: dsdgen's syllables; a site's company is one of the first six, each as often
COMPANY_NAMES = ["ought", "able", "pri", "ese", "anti", "cally"]
LOCATION_TYPES = ["apartment", "condo", "single family"]

TABLES = ("web_sales", "web_returns", "customer_address", "web_site", "date_dim")
FILES_PER_TABLE = 8
MARKER = "_TPCDS_WEB_COMPLETE.json"
#: raised when the tables' contents change, so that an older write is made anew
CONTENTS = 1


def n_rows(name: str, sf: float) -> int:
    if name == "date_dim":
        return DATE_HI - DATE_LO + 1
    if name in ("web_sales", "orders", "web_returns"):
        return max(16, int(SF1[name] * sf))
    n = int(SF1[name] * (sf ** 0.5))
    if name == "web_site":  # a multiple of the names, so that each is as often
        return max(1, n // len(COMPANY_NAMES)) * len(COMPANY_NAMES)
    return max(10, n)


def _address_columns(prefix: str, d: dict, s: slice) -> dict:
    """street number to country, which an address and a site both have."""
    n = len(d["street_number"][s])
    return {
        f"{prefix}_street_number": pa.array(d["street_number"][s]).cast(pa.string()),
        f"{prefix}_street_name": base._pick(base.STREET_NAMES, d["street_name"][s]),
        f"{prefix}_street_type": base._pick(base.STREET_TYPES, d["street_type"][s]),
        f"{prefix}_suite_number": base._joined("Suite ", d["suite"][s]),
        f"{prefix}_city": base._pick(base.CITIES, d["city"][s]),
        f"{prefix}_county": base._pick(base.COUNTIES, d["county"][s]),
        f"{prefix}_state": base._pick(STATES, d["state"][s]),
        f"{prefix}_zip": base._pick(base.ZIPS, d["zip"][s]),
        f"{prefix}_country": base._const("United States", n),
    }


def _draw_address(rng, n: int) -> dict:
    return {
        "street_number": rng.integers(1, 1000, n),
        "street_name": rng.integers(0, len(base.STREET_NAMES), n),
        "street_type": rng.integers(0, len(base.STREET_TYPES), n),
        "suite": rng.integers(0, 500, n),
        "city": rng.integers(0, len(base.CITIES), n),
        "county": rng.integers(0, len(base.COUNTIES), n),
        "state": rng.integers(0, len(STATES), n),
        "zip": rng.integers(0, len(base.ZIPS), n),
    }


def draw_customer_address(sf: float, seed: int) -> dict:
    rng = np.random.default_rng([seed, 23])
    n = n_rows("customer_address", sf)
    return {
        "rows": n, **_draw_address(rng, n),
        "gmt": rng.choice([-5.0, -6.0, -7.0, -8.0, -9.0, -10.0], n),
        "location": rng.integers(0, len(LOCATION_TYPES), n),
    }


def arrow_customer_address(d: dict, lo: int, hi: int) -> pa.Table:
    s = slice(lo, hi)
    return pa.table({
        "ca_address_sk": np.arange(lo + 1, hi + 1, dtype=np.int64),
        "ca_address_id": base._id_col(lo, hi),
        **_address_columns("ca", d, s),
        "ca_gmt_offset": d["gmt"][s],
        "ca_location_type": base._pick(LOCATION_TYPES, d["location"][s]),
    })


def draw_web_site(sf: float, seed: int) -> dict:
    rng = np.random.default_rng([seed, 29])
    n = n_rows("web_site", sf)
    return {
        "rows": n, **_draw_address(rng, n),
        # each name as often as the others, on sites the seed picks
        "company": rng.permutation(n) % len(COMPANY_NAMES),
        "manager": rng.integers(0, len(base.MANAGERS), n),
        "market_manager": rng.integers(0, len(base.MARKET_MANAGERS), n),
        "mkt_id": rng.integers(1, 7, n).astype(np.int64),
        "gmt": rng.choice([-5.0, -6.0, -7.0, -8.0], n),
        "tax": np.round(rng.uniform(0.0, 0.12, n), 2),
    }


def arrow_web_site(d: dict, lo: int, hi: int) -> pa.Table:
    s = slice(lo, hi)
    n = hi - lo
    unknown = base._const("Unknown", n)
    company = d["company"][s]
    return pa.table({
        "web_site_sk": np.arange(lo + 1, hi + 1, dtype=np.int64),
        "web_site_id": base._id_col(lo, hi),
        "web_rec_start_date": pa.array(np.full(n, REC_START, np.int32), type=pa.date32()),
        "web_rec_end_date": base._const(None, n, pa.date32()),
        "web_name": base._joined("site_", np.arange(lo, hi)),
        "web_open_date_sk": base._sk(np.full(n, REC_START, np.int64)),
        "web_close_date_sk": base._const(None, n, pa.int64()),
        "web_class": unknown,
        "web_manager": base._pick(base.MANAGERS, d["manager"][s]),
        "web_mkt_id": d["mkt_id"][s],
        "web_mkt_class": unknown,
        "web_mkt_desc": base._const("web market description", n),
        "web_market_manager": base._pick(base.MARKET_MANAGERS, d["market_manager"][s]),
        "web_company_id": company.astype(np.int64) + 1,
        "web_company_name": base._pick(COMPANY_NAMES, company),
        **_address_columns("web", d, s),
        "web_gmt_offset": d["gmt"][s],
        "web_tax_percentage": d["tax"][s],
    })


def _lines_per_order(rng, orders: int, rows: int) -> np.ndarray:
    """Lines of each order, uniform over LINES_PER_ORDER, then moved by one
    on orders the seed picks until they add up to the table's row count."""
    lo, hi = LINES_PER_ORDER
    rows = min(max(rows, orders * lo), orders * hi)
    lines = rng.integers(lo, hi + 1, orders)
    while (off := rows - int(lines.sum())) != 0:
        step = 1 if off > 0 else -1
        room = np.flatnonzero(lines < hi if step > 0 else lines > lo)
        lines[rng.choice(room, min(abs(off), len(room)), replace=False)] += step
    return lines


def draw_web_sales(sf: float, seed: int) -> dict:
    rng = np.random.default_rng([seed, 31])
    orders = n_rows("orders", sf)
    lines = _lines_per_order(rng, orders, n_rows("web_sales", sf))
    n = int(lines.sum())
    order = np.repeat(np.arange(orders, dtype=np.int64), lines)

    def fk(hi: int, rows: int = n) -> tuple:
        return rng.integers(1, hi + 1, rows), rng.random(rows) < 0.02

    def of_order(pair: tuple) -> tuple:
        return pair[0][order], pair[1][order]

    customers, addresses = n_rows("customer", sf), n_rows("customer_address", sf)
    sold = rng.integers(SALES_LO, SALES_HI, orders)
    bill = {k: fk(hi, orders) for k, hi in
            (("customer", customers), ("cdemo", 2 * 5 * 7 * 20),
             ("hdemo", 20 * 6 * 10 * 6), ("addr", addresses))}
    # most orders ship to the customer who placed them; a tenth are gifts
    gift = rng.random(orders) < 0.1
    ship = {k: (np.where(gift, fk(hi, orders)[0], bill[k][0]), bill[k][1])
            for k, hi in (("customer", customers), ("cdemo", 2 * 5 * 7 * 20),
                          ("hdemo", 20 * 6 * 10 * 6), ("addr", addresses))}
    qty = rng.integers(1, 101, n)
    # the specification's per-line money chain: wholesale, list, sales, ext_*
    wholesale = base._money(rng, 1.0, 100.0, n)
    list_price = np.round(wholesale * rng.uniform(1.0, 2.0, n), 2)
    sales_price = np.round(list_price * rng.uniform(0.0, 1.0, n), 2)
    ext_sales = np.round(sales_price * qty, 2)
    ext_wholesale = np.round(wholesale * qty, 2)
    tax = np.round(ext_sales * rng.uniform(0.0, 0.09, n), 2)
    coupon = np.where(rng.random(n) < 0.1,
                      np.round(ext_sales * rng.uniform(0.0, 0.5, n), 2), 0.0)
    net_paid = np.round(ext_sales - coupon, 2)
    ship_cost = np.round(np.round(list_price * rng.uniform(0.0, 0.5, n), 2) * qty, 2)
    return {
        "rows": n, "orders": orders,
        "order": order + 1,
        "sold": of_order((base._sk(sold), rng.random(orders) < 0.02)),
        "sold_day": sold[order],
        "time": (rng.integers(0, 86400, orders))[order].astype(np.int64),
        "ship_date": (base._sk(sold[order] + rng.integers(SHIP_LAG[0], SHIP_LAG[1] + 1, n)),
                      rng.random(n) < 0.02),
        "item": rng.integers(1, n_rows("item", sf) + 1, n).astype(np.int64),
        **{"bill_" + k: of_order(v) for k, v in bill.items()},
        **{"ship_" + k: of_order(v) for k, v in ship.items()},
        "page": fk(n_rows("web_page", sf)),
        "site": fk(n_rows("web_site", sf)),
        "ship_mode": fk(SHIP_MODES),
        "warehouse": fk(WAREHOUSES),
        "promo": fk(n_rows("promotion", sf)),
        "qty": qty.astype(np.int64),
        "wholesale": wholesale, "list": list_price, "sales": sales_price,
        "ext_discount": np.round((list_price - sales_price) * qty, 2),
        "ext_sales": ext_sales, "ext_wholesale": ext_wholesale,
        "ext_list": np.round(list_price * qty, 2),
        "tax": tax, "coupon": coupon, "ship_cost": ship_cost, "net_paid": net_paid,
        "net_paid_tax": np.round(net_paid + tax, 2),
        "net_paid_ship": np.round(net_paid + ship_cost, 2),
        "net_paid_ship_tax": np.round(net_paid + ship_cost + tax, 2),
        "net_profit": np.round(net_paid - ext_wholesale, 2),
    }


def arrow_web_sales(d: dict, lo: int, hi: int) -> pa.Table:
    s = slice(lo, hi)

    def fk(name: str) -> pa.Array:
        values, null = d[name]
        return base._nullable(values[s], null[s])

    return pa.table({
        "ws_sold_date_sk": fk("sold"),
        "ws_sold_time_sk": d["time"][s],
        "ws_ship_date_sk": fk("ship_date"),
        "ws_item_sk": d["item"][s],
        "ws_bill_customer_sk": fk("bill_customer"),
        "ws_bill_cdemo_sk": fk("bill_cdemo"),
        "ws_bill_hdemo_sk": fk("bill_hdemo"),
        "ws_bill_addr_sk": fk("bill_addr"),
        "ws_ship_customer_sk": fk("ship_customer"),
        "ws_ship_cdemo_sk": fk("ship_cdemo"),
        "ws_ship_hdemo_sk": fk("ship_hdemo"),
        "ws_ship_addr_sk": fk("ship_addr"),
        "ws_web_page_sk": fk("page"),
        "ws_web_site_sk": fk("site"),
        "ws_ship_mode_sk": fk("ship_mode"),
        "ws_warehouse_sk": fk("warehouse"),
        "ws_promo_sk": fk("promo"),
        "ws_order_number": d["order"][s],
        "ws_quantity": d["qty"][s],
        "ws_wholesale_cost": d["wholesale"][s],
        "ws_list_price": d["list"][s],
        "ws_sales_price": d["sales"][s],
        "ws_ext_discount_amt": d["ext_discount"][s],
        "ws_ext_sales_price": d["ext_sales"][s],
        "ws_ext_wholesale_cost": d["ext_wholesale"][s],
        "ws_ext_list_price": d["ext_list"][s],
        "ws_ext_tax": d["tax"][s],
        "ws_coupon_amt": d["coupon"][s],
        "ws_ext_ship_cost": d["ship_cost"][s],
        "ws_net_paid": d["net_paid"][s],
        "ws_net_paid_inc_tax": d["net_paid_tax"][s],
        "ws_net_paid_inc_ship": d["net_paid_ship"][s],
        "ws_net_paid_inc_ship_tax": d["net_paid_ship_tax"][s],
        "ws_net_profit": d["net_profit"][s],
    })


def draw_web_returns(sf: float, seed: int, sales: dict | None = None) -> dict:
    """Returns of lines that were sold: each points at a row of web_sales
    (``line``), a line returned once at most, in the order the lines were
    sold."""
    ws = sales if sales is not None else draw_web_sales(sf, seed)
    rng = np.random.default_rng([seed, 37])
    n = min(n_rows("web_returns", sf), ws["rows"])
    line = np.sort(rng.choice(ws["rows"], n, replace=False))
    ret_qty = np.maximum(1, (ws["qty"][line] * rng.uniform(0.1, 1.0, n)).astype(np.int64))
    amt = np.round(ws["sales"][line] * ret_qty, 2)
    tax = np.round(amt * rng.uniform(0.0, 0.09, n), 2)
    fee = base._money(rng, 0.5, 100.0, n)
    ship = np.round(base._money(rng, 0.0, 50.0, n), 2)
    refunded = np.round(amt * rng.uniform(0.3, 1.0, n), 2)
    reversed_ = np.round((amt - refunded) * rng.uniform(0.0, 1.0, n), 2)

    def null_again(name: str) -> tuple:
        values, null = ws[name]
        return values[line], null[line] | (rng.random(n) < 0.02)

    return {
        "rows": n, "line": line,
        "returned": (base._sk(ws["sold_day"][line] + rng.integers(1, 181, n)),
                     rng.random(n) < 0.02),
        "time": rng.integers(0, 86400, n).astype(np.int64),
        "item": ws["item"][line], "order": ws["order"][line],
        **{k: null_again(k) for k in
           ("bill_customer", "bill_cdemo", "bill_hdemo", "bill_addr",
            "ship_customer", "ship_cdemo", "ship_hdemo", "ship_addr", "page")},
        "reason": (rng.integers(1, REASONS + 1, n), rng.random(n) < 0.02),
        "qty": ret_qty, "amt": amt, "tax": tax, "fee": fee, "ship": ship,
        "refunded": refunded, "reversed": reversed_,
        "credit": np.round(amt - refunded - reversed_, 2),
        "net_loss": np.round(fee + ship + tax, 2),
    }


def arrow_web_returns(d: dict, lo: int, hi: int) -> pa.Table:
    s = slice(lo, hi)

    def fk(name: str) -> pa.Array:
        values, null = d[name]
        return base._nullable(values[s], null[s])

    return pa.table({
        "wr_returned_date_sk": fk("returned"),
        "wr_returned_time_sk": d["time"][s],
        "wr_item_sk": d["item"][s],
        "wr_refunded_customer_sk": fk("bill_customer"),
        "wr_refunded_cdemo_sk": fk("bill_cdemo"),
        "wr_refunded_hdemo_sk": fk("bill_hdemo"),
        "wr_refunded_addr_sk": fk("bill_addr"),
        "wr_returning_customer_sk": fk("ship_customer"),
        "wr_returning_cdemo_sk": fk("ship_cdemo"),
        "wr_returning_hdemo_sk": fk("ship_hdemo"),
        "wr_returning_addr_sk": fk("ship_addr"),
        "wr_web_page_sk": fk("page"),
        "wr_reason_sk": fk("reason"),
        "wr_order_number": d["order"][s],
        "wr_return_quantity": d["qty"][s],
        "wr_return_amt": d["amt"][s],
        "wr_return_tax": d["tax"][s],
        "wr_return_amt_inc_tax": np.round(d["amt"][s] + d["tax"][s], 2),
        "wr_fee": d["fee"][s],
        "wr_return_ship_cost": d["ship"][s],
        "wr_refunded_cash": d["refunded"][s],
        "wr_reversed_charge": d["reversed"][s],
        "wr_account_credit": d["credit"][s],
        "wr_net_loss": d["net_loss"][s],
    })


DRAW = {"web_sales": draw_web_sales, "web_returns": draw_web_returns,
        "customer_address": draw_customer_address, "web_site": draw_web_site,
        "date_dim": draw_date_dim}
ARROW = {"web_sales": arrow_web_sales, "web_returns": arrow_web_returns,
         "customer_address": arrow_customer_address, "web_site": arrow_web_site,
         "date_dim": arrow_date_dim}


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


def _marked(root: str, marker: str, sf: float, seed: int, contents: int) -> set:
    """Tables that ``marker`` under ``root`` says are written whole for this
    scale, seed and contents."""
    path = os.path.join(root, marker)
    if not os.path.exists(path):
        return set()
    with open(path) as f:
        done = json.load(f)
    same = (done.get("sf"), done.get("seed"), done.get("contents")) == (sf, seed, contents)
    return set(done.get("tables", [])) if same else set()


def ensure_tables(root: str, sf: float, seed: int, tables,
                  files_per_table: int = FILES_PER_TABLE) -> dict:
    """``{table: directory}`` for ``tables`` under ``root``, generated where
    this generator's marker of a finished earlier write does not list them.
    ``_generated`` says whether anything was written this time. Only the
    directories of this generator's own tables are ever removed; a
    ``date_dim`` that ``tpcds_datagen`` has finished there is its to keep."""
    unknown = sorted(set(tables) - set(TABLES))
    if unknown:
        raise KeyError(f"the generator has no table {unknown}; it has {TABLES}")
    have = _marked(root, MARKER, sf, seed, CONTENTS)
    theirs = {"date_dim"} & _marked(root, base.MARKER, sf, seed, base.CONTENTS)
    missing = set(tables) - have - theirs
    if missing:
        marker = os.path.join(root, MARKER)
        if os.path.exists(marker):
            os.remove(marker)
        for name in missing:
            shutil.rmtree(os.path.join(root, name), ignore_errors=True)
        draws = {}
        for name in sorted(missing, reverse=True):  # web_sales before web_returns
            draws[name] = (draw_web_returns(sf, seed, draws.get("web_sales"))
                           if name == "web_returns" else DRAW[name](sf, seed))
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
