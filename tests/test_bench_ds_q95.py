"""The benchmark's TPC-DS q95 at a small size on the CPU: the program through
``dataframe()`` and through ``sql()`` against the plain numpy reference under
the configuration's own limits; the plan (two ``IN (subquery)`` predicates as
left-semi joins, nothing of them on the host, scans and build sides pruned,
for q95 and for q94's ``EXISTS``); the float32 control and the faults the
comparison has to catch; the shapes the specification fixes; the metric
readers; the three generators side by side under one root.

Data comes from ``benchmark/tpcds_web_datagen.py`` at SF 0.05, seeded;
nothing here reads the program's own TPC-DS rig.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
for p in (os.path.join(BENCH, "queries"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import compare  # noqa: E402
import datagen  # noqa: E402
import ds_q95  # noqa: E402
import run as harness  # noqa: E402
import tpcds_datagen  # noqa: E402
import tpcds_web_datagen  # noqa: E402

# a seed past 32 signed bits, as the driver's are; at this one 11 lines reach
# the semi joins, 8 lines of 5 orders pass both
SF, SEED = 0.05, 2147485203
PARAMS = ds_q95.DEFAULT_PARAMS
CELL = "batch_ds_q95_semi_selfjoin"

Q94 = """
select count(distinct ws_order_number) as order_count,
       sum(ws_ext_ship_cost) as total_shipping_cost, sum(ws_net_profit) as total_net_profit
from web_sales ws1, date_dim, customer_address, web_site
where d_date between date '1999-02-01' and date '1999-02-01' + interval '60' day
  and ws1.ws_ship_date_sk = d_date_sk and ws1.ws_ship_addr_sk = ca_address_sk
  and ca_state = 'IL' and ws1.ws_web_site_sk = web_site_sk and web_company_name = 'pri'
  and exists (select * from web_sales ws2 where ws1.ws_order_number = ws2.ws_order_number
              and ws1.ws_warehouse_sk <> ws2.ws_warehouse_sk)
  and not exists (select * from web_returns wr1 where ws1.ws_order_number = wr1.wr_order_number)
order by count(distinct ws_order_number) limit 100
"""


@pytest.fixture(scope="module")
def config():
    return harness.load_config("tpcds_sf1_web_parquet")


@pytest.fixture(scope="module")
def paths(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("web") / f"sf{SF:g}-seed{SEED}")
    out = tpcds_web_datagen.ensure_tables(root, SF, SEED, list(ds_q95.TABLES), 8)
    assert out.pop("_generated")
    return out


@pytest.fixture(scope="module")
def read(paths):
    return harness.table_reader(paths)


@pytest.fixture(scope="module")
def want(read):
    return ds_q95.reference(read, PARAMS)


@pytest.fixture(scope="module")
def counts(want):
    return dict(ds_q95.COUNTS)


@pytest.fixture(scope="module")
def strict_session(config):
    from spark_rapids_tpu import TpuSession

    return TpuSession(dict(config["conf"]))


def _verdict(rows, want, config, names=ds_q95.RESULT_COLUMNS):
    numbers = compare.compare(
        [("q", list(names), rows)], {"q": (list(ds_q95.RESULT_COLUMNS), want)}
    )
    ok, _ = compare.verdict(numbers, config["limits"])
    return ok, numbers


def _program_df(entry, s, paths, text=None):
    if entry == "dataframe":
        return ds_q95.dataframe(lambda name: s.read.parquet(paths[name]), PARAMS)
    for name in ds_q95.TABLES:
        s.read.parquet(paths[name]).create_or_replace_temp_view(name)
    return s.sql(text or ds_q95.sql(PARAMS))


@pytest.mark.parametrize("entry", ["dataframe", "sql"])
def test_program_matches_reference(entry, strict_session, paths, want, counts, config):
    from spark_rapids_tpu.obs import metrics

    before = metrics.GLOBAL.snapshot()
    df = _program_df(entry, strict_session, paths)
    ok, numbers = _verdict(df.collect(), want, config, list(df.columns))
    assert ok, numbers
    assert numbers["rows_wrong"] == 0
    after = metrics.GLOBAL.snapshot()
    # nothing of a subquery's result came to the host
    assert after["subquery.hostValues"] == before["subquery.hostValues"]
    # join.rowsOut is the key-matched pairs of the query's joins, to the row
    assert after["join.rowsOut"] - before["join.rowsOut"] == ds_q95.key_matched_pairs(counts)
    assert after["join.calls"] > before["join.calls"]


def test_answer_is_not_vacuous(want, counts):
    (orders, shipping, profit), = want
    assert orders >= 2 and shipping > 0 and profit is not None
    # the semi joins do work: lines reach them that they drop, and an order
    # has more lines than one, so count(distinct) is not count(*)
    assert counts["semi1_in"] > counts["semi2_out"] > orders
    assert counts["ws_wh_rows"] > 8 * counts["web_sales"]  # the self-join emits more than it reads
    assert counts["key_matched_pairs"] > counts["ws_wh_rows"]


def _nodes(plan):
    yield plan
    for c in plan.children:
        yield from _nodes(c)


def _final_plan(entry, s, paths, text=None):
    plan, _ = s._prepare_plan(_program_df(entry, s, paths, text)._plan)
    return plan


@pytest.mark.parametrize("entry", ["dataframe", "sql"])
def test_in_subqueries_plan_as_semi_joins(entry, strict_session, paths):
    """The plan that runs holds a left-semi join for each IN (subquery) and
    no literal set; each semi join's build side is its key alone."""
    from spark_rapids_tpu.exec.tpu_join import TpuShuffledHashJoinExec
    from spark_rapids_tpu.obs import metrics

    semi_before = metrics.GLOBAL.snapshot()["subquery.semiJoins"]
    plan = _final_plan(entry, strict_session, paths)
    assert metrics.GLOBAL.snapshot()["subquery.semiJoins"] - semi_before == 2
    assert "INSET" not in plan.tree_string()
    semis = [n for n in _nodes(plan)
             if isinstance(n, TpuShuffledHashJoinExec) and n.join_type == "left_semi"]
    assert len(semis) == 2
    for j in semis:
        assert len(j.children[1].output.names) == 1
        assert j.residual is None


def _scanned(plan) -> dict:
    """{table: columns its scans read}, from the directory a scan reads."""
    from spark_rapids_tpu.io.files import CpuFileScanExec

    out = {}
    for n in _nodes(plan):
        if isinstance(n, CpuFileScanExec):
            table = os.path.basename(os.path.dirname(n.files[0]))
            out.setdefault(table, set()).update(n.output.names)
    return out


@pytest.mark.parametrize("entry", ["dataframe", "sql"])
def test_scans_read_only_what_q95_reads(entry, strict_session, paths):
    scanned = _scanned(_final_plan(entry, strict_session, paths))
    assert scanned == {t: set(cols) for t, cols in ds_q95.COLUMNS.items()}


def test_q94_exists_builds_are_pruned_to_keys_and_residual(strict_session, paths):
    """The EXISTS form: a semi join's build side carries its key and what
    its residual compares, an anti join's its key (34 and 24 columns of
    web_sales and web_returns before)."""
    from spark_rapids_tpu.exec.tpu_join import TpuShuffledHashJoinExec

    plan = _final_plan("sql", strict_session, paths, Q94)
    joins = {n.join_type: n for n in _nodes(plan)
             if isinstance(n, TpuShuffledHashJoinExec) and n.join_type.startswith("left_")}
    assert set(joins) == {"left_semi", "left_anti"}
    assert len(joins["left_semi"].children[1].output.names) == 2
    assert len(joins["left_anti"].children[1].output.names) == 1
    scanned = _scanned(plan)
    assert scanned["web_returns"] == {"wr_order_number"}
    assert scanned["web_sales"] == set(ds_q95.COLUMNS["web_sales"])


def _count_off_by_one(rows, counts):
    return [(rows[0][0] + 1,) + tuple(rows[0][1:])]


def _an_order_counted_twice(rows, counts):
    """count(*) where count(distinct) was asked: every line of an order."""
    return [(counts["semi2_out"],) + tuple(rows[0][1:])]


def _no_row(rows, counts):
    return []


@pytest.mark.parametrize(
    "fault", [_count_off_by_one, _an_order_counted_twice, _no_row],
    ids=["count_off_by_one", "order_counted_twice", "no_row"],
)
def test_fault_reads_rows_wrong(fault, want, counts, config):
    got = fault(want, counts)
    assert got != want
    ok, numbers = _verdict(got, want, config)
    assert not ok and numbers["rows_wrong"] == 1, numbers


def test_sum_over_the_unfiltered_rows_is_not_correct(read, want, config):
    """The sums of the lines that reach the semi joins, not of those that
    pass them: a float that differs reads as a gap, far over the limit."""
    ws = read("web_sales", list(ds_q95.COLUMNS["web_sales"]))
    dd = read("date_dim", list(ds_q95.COLUMNS["date_dim"]))
    ca = read("customer_address", list(ds_q95.COLUMNS["customer_address"]))
    site = read("web_site", list(ds_q95.COLUMNS["web_site"]))
    days = dd["d_date_sk"][(dd["d_date"] >= 10623) & (dd["d_date"] <= 10683)]
    outer = (np.isin(ws["ws_ship_date_sk"], days)
             & np.isin(ws["ws_ship_addr_sk"], ca["ca_address_sk"][ca["ca_state"] == "IL"])
             & np.isin(ws["ws_web_site_sk"], site["web_site_sk"][site["web_company_name"] == "pri"]))
    got = [(want[0][0], float(ws["ws_ext_ship_cost"][outer].sum()),
            float(ws["ws_net_profit"][outer].sum()))]
    ok, numbers = _verdict(got, want, config)
    assert not ok and numbers["float_rel_gap"] > 1e-3, numbers


def test_clean_answer_reads_correct(want, config):
    ok, numbers = _verdict([tuple(r) for r in want], want, config)
    assert ok and numbers == {"answers_compared": 1, "rows_wrong": 0, "float_rel_gap": 0.0}


def test_float32_control_is_not_correct(read, want, config):
    got = ds_q95.reference(read, PARAMS, dtype=np.float32)
    ok, numbers = _verdict(got, want, config)
    assert not ok, numbers
    assert numbers["rows_wrong"] == 0
    assert numbers["float_rel_gap"] > config["limits"]["float_rel_gap"]


def test_reference_treats_nulls_as_sql_does():
    """A line with no warehouse pairs with none, a null order number is in
    no subquery's result, a null money value is left out of its sum."""
    nan = float("nan")
    tables = {
        "web_sales": {
            # order 1: warehouses 1 and 2; order 2: one warehouse and a null;
            # order 3: two warehouses, never returned; a line with no order
            "ws_order_number": np.array([1, 1, 2, 2, 3, 3, nan]),
            "ws_warehouse_sk": np.array([1, 2, 1, nan, 1, 2, 2]),
            "ws_ship_date_sk": np.array([10.0] * 7),
            "ws_ship_addr_sk": np.array([5.0] * 7),
            "ws_web_site_sk": np.array([7.0] * 7),
            "ws_ext_ship_cost": np.array([1.0, nan, 4.0, 8.0, 16.0, 32.0, 64.0]),
            "ws_net_profit": np.array([-1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0]),
        },
        "web_returns": {"wr_order_number": np.array([1, 2, nan, 1])},
        "date_dim": {"d_date_sk": np.array([10]), "d_date": np.array([10630], np.int32)},
        "customer_address": {"ca_address_sk": np.array([5]), "ca_state": np.array(["IL"])},
        "web_site": {"web_site_sk": np.array([7]), "web_company_name": np.array(["pri"])},
    }
    got = ds_q95.reference(lambda t, cols: tables[t], PARAMS)
    assert got == [(1, 1.0, 1.0)]
    c = ds_q95.COUNTS
    assert (c["key_matched_pairs"], c["ws_wh_rows"], c["returns_join_rows"]) == (12, 4, 4)
    assert (c["semi1_in"], c["semi1_out"], c["semi2_out"]) == (7, 4, 2)
    assert (c["semi1_pairs"], c["semi2_pairs"]) == (8, 8)


def test_join_min_bytes_counts_keys_in_and_rows_out(counts):
    total = ds_q95.join_min_bytes(counts)
    # by hand, the parts that weigh: two self-joins read two columns of both
    # sides and write three a pair; the returns join and the two semi joins
    # read the subqueries' rows once each, a key wide
    ws, pairs, ret = counts["web_sales"], counts["ws_wh_rows"], counts["returns_join_rows"]
    heavy = 2 * (4 * ws * 8 + pairs * 24) + (pairs + ret) * 8 + pairs * 8 + ret * 8
    assert heavy < total < 1.1 * heavy
    more = dict(counts, ws_wh_rows=pairs + 1000)
    assert ds_q95.join_min_bytes(more) - total == 1000 * (2 * 24 + 8 + 8)
    rows = {t: counts[t] for t in ds_q95.TABLES}
    assert ds_q95.min_bytes(rows, 1) - ds_q95.min_bytes(rows, 0) == 24


def test_shapes_the_specification_fixes(read, paths):
    import pyarrow.parquet as pq

    g = tpcds_web_datagen
    assert [g.n_rows(t, 1.0) for t in g.TABLES] == [719_384, 71_763, 50_000, 30, 73_049]
    assert g.n_rows("orders", 1.0) == 60_000 and g.WAREHOUSES == 5
    columns = {t: pq.ParquetDataset(paths[t]).schema.names for t in g.TABLES}
    assert [len(columns[t]) for t in g.TABLES] == [34, 24, 13, 26, 28]
    ws = read("web_sales", ["ws_order_number", "ws_item_sk", "ws_warehouse_sk",
                            "ws_sold_date_sk", "ws_ship_date_sk", "ws_ship_addr_sk"])
    assert len(ws["ws_order_number"]) == int(719_384 * SF)
    orders, lines = np.unique(ws["ws_order_number"], return_counts=True)
    assert len(orders) == int(60_000 * SF) and lines.min() == 8 and lines.max() == 16
    wh = ws["ws_warehouse_sk"]
    assert set(wh[~np.isnan(wh)]) == {1, 2, 3, 4, 5}
    assert 0.01 < np.isnan(wh).mean() < 0.03  # 2 % null foreign keys
    both = ~np.isnan(ws["ws_sold_date_sk"]) & ~np.isnan(ws["ws_ship_date_sk"])
    lag = (ws["ws_ship_date_sk"] - ws["ws_sold_date_sk"])[both]
    assert lag.min() >= 1 and lag.max() <= 120
    dd = read("date_dim", ["d_date_sk", "d_year"])
    sold = ws["ws_sold_date_sk"][~np.isnan(ws["ws_sold_date_sk"])].astype(np.int64)
    assert set(dd["d_year"][sold - dd["d_date_sk"][0]]) == {1998, 1999, 2000, 2001, 2002}
    # an order's lines ship to one address (drawn an order), from warehouses drawn a line
    first = np.r_[True, ws["ws_order_number"][1:] != ws["ws_order_number"][:-1]]
    addr = np.nan_to_num(ws["ws_ship_addr_sk"], nan=-1.0)
    assert (addr == addr[np.flatnonzero(first)[np.cumsum(first) - 1]]).all()
    # a return is of a line that was sold, a line returned once at most
    wr = read("web_returns", ["wr_order_number", "wr_item_sk"])
    assert len(wr["wr_order_number"]) == int(71_763 * SF)
    sold_lines = set(zip(ws["ws_order_number"].tolist(), ws["ws_item_sk"].tolist()))
    assert set(zip(wr["wr_order_number"].tolist(), wr["wr_item_sk"].tolist())) <= sold_lines
    ca = read("customer_address", ["ca_state", "ca_address_id"])
    assert "IL" in set(ca["ca_state"]) and len(set(ca["ca_state"])) == 51
    assert {len(x) for x in ca["ca_address_id"]} == {16}
    names, each = np.unique(read("web_site", ["web_company_name"])["web_company_name"],
                            return_counts=True)
    assert len(names) == 6 and "pri" in names and len(set(each)) == 1


def test_date_dim_is_the_other_generators(tmp_path):
    """Both TPC-DS generators write the same bytes for date_dim."""
    import pyarrow.parquet as pq

    a = tpcds_web_datagen.ensure_tables(str(tmp_path / "a"), 0.01, 7, ["date_dim"], 2)
    b = tpcds_datagen.ensure_tables(str(tmp_path / "b"), 0.01, 7, ["date_dim"], 2)
    assert pq.read_table(a["date_dim"]).equals(pq.read_table(b["date_dim"]))
    assert sorted(os.listdir(a["date_dim"])) == sorted(os.listdir(b["date_dim"]))


def test_metric_readers(counts):
    """On a program without the counters and a trace without jit__join_*
    modules (the parent) every new reader says None; on made-up modules and
    counters they read what their docstrings say."""
    names = ("join_ms.batch", "join_roofline.batch", "subquery_host_values.batch",
             "join_rows_out.batch")
    run = harness.Run()
    run.trace = {"modules": [["jit_fn", 9.0], ["jit__aggregate", 1.0]], "busy_s": 10.0,
                 "window_s": 11.0}
    run.traced_requests = [(0.0, 1.0, True, "ds_q95:{}")]
    run.requests = list(run.traced_requests) * 2
    run.peaks = {"hbm_gbps": 819.0}
    got = {n: harness.load_module("metrics", n).read(run) for n in names}
    assert all(v is None for v in got.values()), got
    run.trace["modules"] = [["jit__join_pairs", 1.5], ["jit__join_bounds", 0.5], ["jit_fn", 9.0]]
    run.counters_before = {"subquery.hostValues": 10, "join.rowsOut": 100}
    run.counters_after = {"subquery.hostValues": 10, "join.rowsOut": 700}
    ds_q95.COUNTS = None
    assert harness.load_module("metrics", "join_roofline.batch").read(run) is None
    ds_q95.COUNTS = counts
    got = {n: harness.load_module("metrics", n).read(run) for n in names}
    assert got["join_ms.batch"] == 2000.0
    assert got["subquery_host_values.batch"] == 0.0 and got["join_rows_out.batch"] == 300.0
    share = 100.0 * ds_q95.join_min_bytes(counts) / 819e9 / 2.0
    assert abs(got["join_roofline.batch"] - share) < 1e-9 and 0 < share < 100
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    listed = {m["name"] for m in harness.metrics_for(bench, "per_layer", CELL)}
    assert set(names) <= listed and "query_roofline.batch" in listed
    assert {m["name"] for m in harness.metrics_for(bench, "end_to_end", CELL)} == {
        "query_s", "setup_s"}


def _listing(root):
    return sorted(
        (os.path.relpath(os.path.join(d, f), root), os.path.getmtime(os.path.join(d, f)))
        for d, _, files in os.walk(root) for f in files
    )


@pytest.mark.parametrize("first", ["tpch", "tpcds", "tpcds_web"])
def test_generators_leave_each_other_alone(first, tmp_path):
    """The three generators under one ``sf<sf>-seed<seed>`` root: each keeps
    a marker of its own and removes only its own tables, and a date_dim that
    ``tpcds_datagen`` has finished is left as it is."""
    root = str(tmp_path / "sf0.01-seed7")
    gens = {
        "tpch": (datagen, ["lineitem"], datagen.MARKER),
        "tpcds": (tpcds_datagen, ["item", "date_dim"], tpcds_datagen.MARKER),
        "tpcds_web": (tpcds_web_datagen, ["web_site", "web_returns"], tpcds_web_datagen.MARKER),
    }
    assert len({m for _, _, m in gens.values()}) == 3
    order = [first] + [k for k in gens if k != first]
    for name in order:
        gen, tables, _ = gens[name]
        assert gen.ensure_tables(root, 0.01, 7, tables, 2)["_generated"]
    before = _listing(root)
    for name in order + order:  # found again, nothing rewritten, by any
        gen, tables, marker = gens[name]
        assert not gen.ensure_tables(root, 0.01, 7, tables, 2)["_generated"]
        with open(os.path.join(root, marker)) as f:
            assert set(tables) <= set(json.load(f)["tables"])
    # the web generator finds the other's date_dim and writes none
    assert not tpcds_web_datagen.ensure_tables(root, 0.01, 7, ["date_dim"], 2)["_generated"]
    assert _listing(root) == before
    # nothing in a table's directory but Parquet files: run.py opens every entry as one
    for table in ("web_site", "web_returns", "date_dim"):
        assert all(f.endswith(".parquet") for f in os.listdir(os.path.join(root, table)))
    # a further table of this generator leaves the others' files as they were
    theirs = [e for e in before if e[0].split(os.sep)[0] in
              ("lineitem", "orders", "item", "date_dim", datagen.MARKER, tpcds_datagen.MARKER)]
    assert tpcds_web_datagen.ensure_tables(root, 0.01, 7, ["customer_address"], 2)["_generated"]
    assert [e for e in _listing(root) if e in theirs] == theirs


def test_generator_is_seeded(tmp_path):
    import pyarrow.parquet as pq

    tables = ["web_sales", "web_returns"]
    a = tpcds_web_datagen.ensure_tables(str(tmp_path / "a"), 0.01, SEED, tables, 2)
    b = tpcds_web_datagen.ensure_tables(str(tmp_path / "b"), 0.01, SEED, tables, 2)
    c = tpcds_web_datagen.ensure_tables(str(tmp_path / "c"), 0.01, SEED + 1, tables, 2)
    for t in tables:
        ta, tb, tc = (pq.read_table(x[t]) for x in (a, b, c))
        assert ta.equals(tb) and not ta.equals(tc)
    # web_returns alone draws the same sales to return from
    d = tpcds_web_datagen.ensure_tables(str(tmp_path / "d"), 0.01, SEED, ["web_returns"], 2)
    assert pq.read_table(d["web_returns"]).equals(pq.read_table(a["web_returns"]))
    assert pq.read_table(a["web_sales"]).num_rows == 7193
    # a write from before the tables' contents last changed is made anew
    with open(os.path.join(str(tmp_path / "a"), tpcds_web_datagen.MARKER), "w") as f:
        json.dump({"sf": 0.01, "seed": SEED, "tables": tables}, f)
    assert tpcds_web_datagen.ensure_tables(str(tmp_path / "a"), 0.01, SEED, tables, 2)["_generated"]
