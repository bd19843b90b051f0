"""The benchmark's own TPC-H generator: customer, orders and lineitem at a
scale factor, from a seed, written as multi-file Parquet.

Copied in shape from ``spark_rapids_tpu/tpch/datagen.py`` (dbgen-like
cardinalities and distributions, money as float64, o_totalprice and
o_orderstatus reduced exactly from the order's lineitems), kept here so that
no later change to the program can change the data a cell reads. Text columns
are built with Arrow kernels instead of Python string loops, and the files of
a table are written by a small thread pool, so SF 1 takes seconds.

Only the tables a cell names are generated and written; ``orders`` and
``lineitem`` always come together because the one is derived from the other.
"""
from __future__ import annotations

import json
import os
import shutil
from concurrent.futures import ThreadPoolExecutor
from datetime import date

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

EPOCH = date(1970, 1, 1)


def _d(y: int, m: int, d_: int) -> int:
    return (date(y, m, d_) - EPOCH).days


START_DATE = _d(1992, 1, 1)
END_DATE = _d(1998, 8, 2)
CURRENT_DATE = _d(1995, 6, 17)

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIP_MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
SHIP_INSTRUCT = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
FILLER = [
    "carefully", "final", "deposits", "accounts", "packages", "ideas",
    "quickly", "furiously", "slyly", "blithely", "pending", "express",
    "regular", "even", "silent", "bold", "unusual", "ironic", "special",
    "requests", "theodolites", "instructions", "foxes", "platelets",
    "dependencies", "excuses", "waters", "sauternes", "asymptotes",
]

#: tables this generator knows; a configuration names a subset
TABLES = ("customer", "orders", "lineitem")
FILES_PER_TABLE = 8
MARKER = "_COMPLETE.json"


def _pick(vocab, idx) -> pa.Array:
    """vocab[idx] as an Arrow string array, without a Python loop."""
    return pc.take(pa.array(vocab), pa.array(idx))


def _words(vocab, idx) -> pa.Array:
    """Rows of space-joined words: ``idx`` is (rows, words) into ``vocab``."""
    return pc.binary_join_element_wise(
        *[_pick(vocab, idx[:, j]) for j in range(idx.shape[1])], " "
    )


def _padded(prefix: str, keys, width: int) -> pa.Array:
    digits = pc.utf8_lpad(pa.array(keys).cast(pa.string()), width, "0")
    return pc.binary_join_element_wise(pa.scalar(prefix), digits, "")


def _phone(parts) -> pa.Array:
    return pc.binary_join_element_wise(
        *[pa.array(parts[:, j]).cast(pa.string()) for j in range(4)], "-"
    )


def n_customers(sf: float) -> int:
    return max(int(sf * 150_000), 30)


def n_orders(sf: float) -> int:
    return max(int(sf * 1_500_000), 150)


# Each table is generated in two steps: every random draw and every number
# as numpy arrays over the whole table (a few seconds at SF 1), then the Arrow
# columns of one file's rows at a time, so that the text columns and the
# Parquet encoding of the files run side by side.

def draw_customer(sf: float, seed: int) -> dict:
    rng = np.random.default_rng([seed, 3])
    n = n_customers(sf)
    nk = rng.integers(0, 25, n).astype(np.int64)
    return {
        "rows": n,
        "key": np.arange(1, n + 1, dtype=np.int64),
        "address": rng.integers(0, len(FILLER), (n, 3)).astype(np.int32),
        "nation": nk,
        "phone": np.column_stack([
            nk + 10, rng.integers(100, 1000, n), rng.integers(100, 1000, n),
            rng.integers(1000, 10000, n),
        ]),
        "acctbal": np.round(rng.uniform(-999.99, 9999.99, n), 2),
        "segment": rng.integers(0, 5, n).astype(np.int32),
        "comment": rng.integers(0, len(FILLER), (n, 8)).astype(np.int32),
    }


def arrow_customer(c: dict, lo: int, hi: int) -> pa.Table:
    s = slice(lo, hi)
    return pa.table({
        "c_custkey": c["key"][s],
        "c_name": _padded("Customer#", c["key"][s], 9),
        "c_address": _words(FILLER, c["address"][s]),
        "c_nationkey": c["nation"][s],
        "c_phone": _phone(c["phone"][s]),
        "c_acctbal": c["acctbal"][s],
        "c_mktsegment": _pick(SEGMENTS, c["segment"][s]),
        "c_comment": _words(FILLER, c["comment"][s]),
    })


def draw_orders_lineitem(sf: float, seed: int) -> tuple:
    """(orders, lineitem) draws: lineitem dates chain off o_orderdate, and
    o_orderstatus / o_totalprice are exact reductions of the order's items."""
    rng = np.random.default_rng([seed, 101])
    n_ord, n_cust = n_orders(sf), n_customers(sf)
    n_part = max(int(sf * 200_000), 50)
    n_supp = max(int(sf * 10_000), 25)

    okey = np.arange(1, n_ord + 1, dtype=np.int64)
    # customers whose key is a multiple of three place no orders (dbgen rule)
    ck = rng.integers(1, n_cust + 1, n_ord).astype(np.int64)
    for _ in range(2):
        ck = np.where(ck % 3 == 0, np.maximum((ck + 1) % (n_cust + 1), 1), ck)
    odate = rng.integers(START_DATE, END_DATE + 1, n_ord).astype(np.int32)

    n_li = rng.integers(1, 8, n_ord)
    starts = np.concatenate([[0], np.cumsum(n_li)[:-1]])
    total = int(n_li.sum())
    li_odate = np.repeat(odate, n_li)

    lk = rng.integers(1, n_part + 1, total).astype(np.int64)
    lsk = (lk + rng.integers(0, 4, total) * ((n_supp // 4) + 1)) % n_supp + 1
    qty = rng.integers(1, 51, total).astype(np.float64)
    retail = np.round((90000 + (lk % 200) * 100 + lk % 1000) / 100.0, 2)
    eprice = np.round(qty * retail, 2)
    disc = np.round(rng.integers(0, 11, total) / 100.0, 2)
    tax = np.round(rng.integers(0, 9, total) / 100.0, 2)
    sdate = (li_odate + rng.integers(1, 122, total)).astype(np.int32)
    cdate = (li_odate + rng.integers(30, 91, total)).astype(np.int32)
    rdate = (sdate + rng.integers(1, 31, total)).astype(np.int32)
    returned = rdate <= CURRENT_DATE
    shipped = sdate > CURRENT_DATE
    n_open = np.add.reduceat(shipped.astype(np.int64), starts)

    orders = {
        "rows": n_ord,
        "key": okey,
        "cust": ck,
        "status": np.where(n_open == 0, 0, np.where(n_open == n_li, 1, 2)).astype(np.int32),
        "totalprice": np.round(
            np.add.reduceat(eprice * (1.0 + tax) * (1.0 - disc), starts), 2
        ),
        "date": odate,
        "priority": rng.integers(0, 5, n_ord).astype(np.int32),
        "clerk": rng.integers(1, max(int(sf * 1000), 10) + 1, n_ord),
        "comment": rng.integers(0, len(FILLER), (n_ord, 6)).astype(np.int32),
        "special": rng.random(n_ord) < 0.01,
    }
    lineitem = {
        "rows": total,
        "order": np.repeat(okey, n_li),
        "part": lk,
        "supp": lsk.astype(np.int64),
        "number": (np.arange(total) - np.repeat(starts, n_li) + 1).astype(np.int32),
        "qty": qty, "eprice": eprice, "disc": disc, "tax": tax,
        "returnflag": np.where(
            returned, np.where(rng.random(total) < 0.5, 0, 1), 2
        ).astype(np.int32),
        "linestatus": shipped.astype(np.int32),
        "sdate": sdate, "cdate": cdate, "rdate": rdate,
        "instruct": rng.integers(0, 4, total).astype(np.int32),
        "mode": rng.integers(0, 7, total).astype(np.int32),
        "comment": rng.integers(0, len(FILLER), (total, 4)).astype(np.int32),
    }
    return orders, lineitem


def arrow_orders(o: dict, lo: int, hi: int) -> pa.Table:
    s = slice(lo, hi)
    comment = _words(FILLER, o["comment"][s])
    comment = pc.if_else(
        pa.array(o["special"][s]),
        pc.binary_join_element_wise(
            comment, pa.scalar("special packages requests"), " "
        ),
        comment,
    )
    return pa.table({
        "o_orderkey": o["key"][s],
        "o_custkey": o["cust"][s],
        "o_orderstatus": _pick(["F", "O", "P"], o["status"][s]),
        "o_totalprice": o["totalprice"][s],
        "o_orderdate": pa.array(o["date"][s], type=pa.date32()),
        "o_orderpriority": _pick(PRIORITIES, o["priority"][s]),
        "o_clerk": _padded("Clerk#", o["clerk"][s], 9),
        "o_shippriority": np.zeros(hi - lo, dtype=np.int32),
        "o_comment": comment,
    })


def arrow_lineitem(li: dict, lo: int, hi: int) -> pa.Table:
    s = slice(lo, hi)
    return pa.table({
        "l_orderkey": li["order"][s],
        "l_partkey": li["part"][s],
        "l_suppkey": li["supp"][s],
        "l_linenumber": li["number"][s],
        "l_quantity": li["qty"][s],
        "l_extendedprice": li["eprice"][s],
        "l_discount": li["disc"][s],
        "l_tax": li["tax"][s],
        "l_returnflag": _pick(["R", "A", "N"], li["returnflag"][s]),
        "l_linestatus": _pick(["F", "O"], li["linestatus"][s]),
        "l_shipdate": pa.array(li["sdate"][s], type=pa.date32()),
        "l_commitdate": pa.array(li["cdate"][s], type=pa.date32()),
        "l_receiptdate": pa.array(li["rdate"][s], type=pa.date32()),
        "l_shipinstruct": _pick(SHIP_INSTRUCT, li["instruct"][s]),
        "l_shipmode": _pick(SHIP_MODES, li["mode"][s]),
        "l_comment": _words(FILLER, li["comment"][s]),
    })


ARROW = {"customer": arrow_customer, "orders": arrow_orders, "lineitem": arrow_lineitem}


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
    the marker of a finished earlier write does not list them. ``_generated``
    says whether anything was written this time."""
    unknown = sorted(set(tables) - set(TABLES))
    if unknown:
        raise KeyError(f"the generator has no table {unknown}; it has {TABLES}")
    marker = os.path.join(root, MARKER)
    done = {}
    if os.path.exists(marker):
        with open(marker) as f:
            done = json.load(f)
    same = done.get("sf") == sf and done.get("seed") == seed
    have = set(done.get("tables", [])) if same else set()
    want = set(tables)
    if want & {"orders", "lineitem"}:
        want |= {"orders", "lineitem"}
    missing = want - have
    if missing:
        if os.path.exists(marker):
            os.remove(marker)
        for name in missing:
            shutil.rmtree(os.path.join(root, name), ignore_errors=True)
        draws = {}
        if "customer" in missing:
            draws["customer"] = draw_customer(sf, seed)
        if "lineitem" in missing:
            draws["orders"], draws["lineitem"] = draw_orders_lineitem(sf, seed)
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
            json.dump({"sf": sf, "seed": seed, "tables": sorted(have | missing)}, f)
    out = {name: os.path.join(root, name) for name in tables}
    out["_generated"] = bool(missing)
    return out
