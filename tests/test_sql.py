"""SQL front-end unit battery: parser + compiler shapes beyond TPC-H, each
checked against the DataFrame API or fixed expectations, plus a device
differential slice (the SQL layer emits the same logical plans, so device
coverage rides the existing operator battery — this proves the wiring).

Reference analogue: integration_tests/src/main/python/qa_nightly_sql.py
(Spark parses there; sql/ is the standalone replacement).
"""
from __future__ import annotations

import datetime as _dt

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.sql import parse
from spark_rapids_tpu.sql.parser import SqlError
from tests.harness import cpu_session, tpu_session, _normalize, _values_equal

N = 200
SEED = 11


def _tables():
    rng = np.random.default_rng(SEED)
    orders = pa.table(
        {
            "o_id": np.arange(N, dtype=np.int64),
            "c_id": rng.integers(0, 25, N).astype(np.int64),
            "amt": np.round(rng.uniform(0, 100, N), 2),
            "tag": pa.array([f"t{i % 7}" for i in range(N)]),
            "d": pa.array(
                [
                    _dt.date(2020, 1, 1) + _dt.timedelta(days=int(x))
                    for x in rng.integers(0, 400, N)
                ],
                type=pa.date32(),
            ),
        }
    )
    cust = pa.table(
        {
            "c_id": np.arange(25, dtype=np.int64),
            "name": pa.array([f"cust{i}" for i in range(25)]),
            "city": pa.array([f"city{i % 4}" for i in range(25)]),
        }
    )
    return orders, cust


@pytest.fixture(scope="module")
def cpu():
    s = cpu_session()
    orders, cust = _tables()
    s.create_dataframe(orders).create_or_replace_temp_view("orders")
    s.create_dataframe(cust).create_or_replace_temp_view("cust")
    return s


QUERIES = [
    # basic projection / filter / order / limit
    "select o_id, amt * 2 as dbl from orders where amt > 50 order by o_id limit 10",
    # aggregation with computed group key and ordinal group by
    "select tag, count(*) c, sum(amt) s, avg(amt) a from orders group by 1 order by tag",
    "select upper(tag) ut, min(amt) from orders group by upper(tag) order by ut",
    # having + alias in order by
    "select c_id, sum(amt) total from orders group by c_id having sum(amt) > 100 order by total desc, c_id",
    # joins: inner, left, USING, self
    "select o.o_id, c.name from orders o join cust c on o.c_id = c.c_id where o.amt > 90 order by o.o_id",
    "select c.name, count(o.o_id) n from cust c left join orders o on o.c_id = c.c_id group by c.name order by c.name",
    "select name from cust join orders using (c_id) where amt > 95 order by name",
    "select a.o_id x, b.o_id y from orders a join orders b on a.c_id = b.c_id and a.o_id + 1 = b.o_id order by x",
    # comma join + pushdown
    "select o_id from orders, cust where orders.c_id = cust.c_id and city = 'city1' and amt > 80 order by o_id",
    # subqueries
    "select o_id from orders where amt > (select avg(amt) from orders) and c_id in (select c_id from cust where city = 'city2') order by o_id",
    "select name from cust c where exists (select 1 from orders o where o.c_id = c.c_id and o.amt > 95) order by name",
    "select name from cust c where not exists (select 1 from orders o where o.c_id = c.c_id) order by name",
    "select o_id from orders o where amt > (select avg(amt) + 10 from orders o2 where o2.c_id = o.c_id) order by o_id",
    # or-of-exists (TPC-DS q10/q35 shape)
    "select name from cust c where exists (select 1 from orders o where o.c_id = c.c_id and o.amt > 99) or exists (select 1 from orders o2 where o2.c_id = c.c_id and o2.amt < 1) order by name",
    # set ops
    "select c_id from cust union select c_id from orders order by 1",
    "select c_id from cust union all select c_id from orders order by 1 limit 30",
    "select c_id from orders intersect select c_id from cust order by 1",
    "select c_id from cust except select c_id from orders order by 1",
    # CTEs (incl. reuse)
    "with big as (select * from orders where amt > 50) select tag, count(*) c from big group by tag order by tag",
    "with s as (select c_id, sum(amt) t from orders group by c_id) select a.c_id from s a join s b on a.c_id = b.c_id order by 1 limit 5",
    # windows
    "select o_id, row_number() over (partition by c_id order by amt desc, o_id) rn from orders order by o_id limit 20",
    "select o_id, sum(amt) over (partition by tag order by o_id rows between 2 preceding and current row) run from orders order by o_id limit 20",
    "select c_id, sum(amt) s, rank() over (order by sum(amt) desc) r from orders group by c_id order by r, c_id",
    # rollup / cube / grouping sets / grouping()
    "select city, count(*) c, grouping(city) g from cust group by rollup(city) order by city nulls last",
    "select city, name, count(*) c from cust group by cube(city, name) order by city nulls last, name nulls last limit 20",
    "select city, name, count(*) c from cust group by grouping sets ((city), (name), ()) order by city nulls last, name nulls last",
    # case / cast / between / like / in / is null / distinct
    "select distinct tag from orders where tag like 't%' and amt between 10 and 90 order by tag",
    "select o_id, case when amt >= 50 then 'hi' when amt >= 20 then 'mid' else 'lo' end band from orders order by o_id limit 15",
    "select cast(amt as int) ai, cast(o_id as double) od, cast(o_id as string) os from orders order by o_id limit 5",
    # date functions + interval arithmetic + extract
    "select o_id, year(d) y, month(d) m, extract(day from d) dd from orders order by o_id limit 8",
    "select o_id from orders where d between date '2020-03-01' and date '2020-03-01' + interval '60' day order by o_id limit 10",
    # scalar subquery in select list
    "select o_id, amt - (select avg(amt) from orders) diff from orders order by o_id limit 5",
    # nested subquery in FROM with alias columns
    "select t.b, count(*) from (select c_id a, tag b from orders where amt > 30) t group by t.b order by t.b",
    # concat operator and functions
    "select name || '-' || city nc, concat(city, name) cn from cust order by nc limit 6",
    # arithmetic precedence + neg
    "select o_id, -amt + 2 * 3 v from orders order by o_id limit 4",
]


@pytest.mark.parametrize("i", range(len(QUERIES)))
def test_sql_cpu_executes(cpu, i):
    rows = cpu.sql(QUERIES[i]).collect()
    assert isinstance(rows, list)


def _dataframe_twin(s):
    """A few SQL queries with DataFrame-API twins — results must match."""
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.functions import col

    o = s.table("orders")
    c = s.table("cust")
    return [
        (
            "select tag, sum(amt) s from orders where amt > 25 group by tag order by tag",
            o.filter(col("amt") > 25)
            .group_by("tag")
            .agg(F.sum(col("amt")).alias("s"))
            .order_by("tag"),
        ),
        (
            "select o.o_id, c.city from orders o join cust c on o.c_id = c.c_id order by o.o_id limit 12",
            o.join(c, on=[("c_id", "c_id")])
            .select("o_id", "city")
            .order_by("o_id")
            .limit(12),
        ),
        (
            "select c_id, count(distinct tag) dt from orders group by c_id order by c_id",
            o.group_by("c_id")
            .agg(F.count_distinct(col("tag")).alias("dt"))
            .order_by("c_id"),
        ),
    ]


def test_sql_matches_dataframe_api(cpu):
    for sql, df in _dataframe_twin(cpu):
        got = _normalize(cpu.sql(sql).collect(), True)
        want = _normalize(df.collect(), True)
        assert got == want, f"{sql}\nsql={got[:4]}\ndf={want[:4]}"


DEVICE_SLICE = [1, 4, 10, 12, 21, 23, 27]  # agg, join, subq, window, rollup


@pytest.mark.parametrize("i", DEVICE_SLICE)
def test_sql_device_differential(i):
    """The same SQL through the device engine and the CPU engine."""
    orders, cust = _tables()
    results = []
    for mk in (cpu_session, lambda: tpu_session({"spark.sql.shuffle.partitions": 2})):
        s = mk()
        s.create_dataframe(orders).create_or_replace_temp_view("orders")
        s.create_dataframe(cust).create_or_replace_temp_view("cust")
        results.append(_normalize(s.sql(QUERIES[i]).collect(), True))
    rows_c, rows_t = results
    assert len(rows_c) == len(rows_t)
    for rc, rt in zip(rows_c, rows_t):
        for vc, vt in zip(rc, rt):
            assert _values_equal(vc, vt, approx_float=True), f"{vc!r} vs {vt!r}"


def test_parse_errors_are_loud():
    for bad in [
        "select from orders",
        "select * from",
        "select o_id from orders extra_token)",  # trailing input
        "select * from orders where",
        "select * from orders group by",
    ]:
        with pytest.raises(SqlError):
            parse(bad)


def test_unknown_names_are_loud(cpu):
    with pytest.raises(SqlError):
        cpu.sql("select nope from orders")
    with pytest.raises(SqlError):
        cpu.sql("select * from nonexistent")
    with pytest.raises(SqlError):
        cpu.sql("select x.o_id from orders o")


def test_conjunct_repeated_in_every_or_branch_reaches_the_join(cpu):
    """``(k AND a) OR (k AND b)`` plans as ``k AND (a OR b)``: TPC-DS q13,
    q41 and q48 write their join and correlation keys inside the ORs, and a
    key left there made the comma join a product of whole tables."""
    written = cpu.sql(
        "select o_id from orders, cust where "
        "(orders.c_id = cust.c_id and city = 'city1' and amt > 80) or "
        "(orders.c_id = cust.c_id and city = 'city2' and amt < 20) or "
        "(orders.c_id = cust.c_id and city = 'city3') order by o_id"
    )
    factored = cpu.sql(
        "select o_id from orders, cust where orders.c_id = cust.c_id and "
        "((city = 'city1' and amt > 80) or (city = 'city2' and amt < 20) "
        "or city = 'city3') order by o_id"
    )
    assert str(written._plan) == str(factored._plan)
    assert "Join inner ['c_id='__c_id0]" in str(written._plan)
    rows = written.collect()
    assert rows and rows == factored.collect()
    # a branch that is only the repeated part absorbs the others
    absorbed = cpu.sql(
        "select o_id from orders where amt > 90 or (amt > 90 and tag = 't1') "
        "order by o_id"
    )
    assert absorbed.collect() == cpu.sql(
        "select o_id from orders where amt > 90 order by o_id"
    ).collect()
