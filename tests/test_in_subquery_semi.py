"""``IN (subquery)`` as a left-semi join (plan/subquery.py): a WHERE conjunct
keeps a row only where the predicate is TRUE, so the join is exact, with a
null probe, with nulls in the subquery's result, with an empty result and
with duplicates. ``NOT IN``, an ``IN`` under ``OR`` and an ``IN`` in a SELECT
list see NULL apart from FALSE and keep the literal-set route
(expr/subquery.py). Each case against fixed expectations (Spark's answers)
and against the CPU oracle session."""
from __future__ import annotations

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.functions import col
from spark_rapids_tpu.obs import metrics
from tests.harness import cpu_session, tpu_session

T = pa.table({"x": pa.array([1, 2, None, 9, 2], pa.int64()), "y": [1.0, 2.0, 3.0, 4.0, 5.0]})
SUBS = {
    "plain": pa.table({"v": pa.array([1, 2, 7], pa.int64())}),
    "with_null": pa.table({"v": pa.array([1, None, 2], pa.int64())}),
    "only_null": pa.table({"v": pa.array([None, None], pa.int64())}),
    "empty": pa.table({"v": pa.array([], pa.int64())}),
    "duplicates": pa.table({"v": pa.array([2, 2, 2, 1, 1], pa.int64())}),
    "narrow": pa.table({"v": pa.array([2, 9], pa.int32())}),
    "floats": pa.table({"v": pa.array([2.0, 9.5], pa.float64())}),
}


def _session(make):
    s = make()
    s.create_dataframe(T).create_or_replace_temp_view("t")
    for name, table in SUBS.items():
        s.create_dataframe(table).create_or_replace_temp_view(name)
    return s


@pytest.fixture(scope="module")
def tpu():
    return _session(tpu_session)


@pytest.fixture(scope="module")
def cpu():
    return _session(cpu_session)


def _counters():
    snap = metrics.GLOBAL.snapshot()
    return snap["subquery.semiJoins"], snap["subquery.hostValues"]


# (query, rows Spark returns, IN predicates planned as semi joins)
SEMI = [
    ("select y from t where x in (select v from plain) order by y", [1.0, 2.0, 5.0]),
    # a null probe is dropped; a null in the result makes misses NULL, dropped alike
    ("select y from t where x in (select v from with_null) order by y", [1.0, 2.0, 5.0]),
    ("select y from t where x in (select v from only_null) order by y", []),
    ("select y from t where x in (select v from empty) order by y", []),
    # duplicates in the result: each probe row once
    ("select y from t where x in (select v from duplicates) order by y", [1.0, 2.0, 5.0]),
    # beside another conjunct, and two IN conjuncts in one WHERE
    ("select y from t where y > 1.5 and x in (select v from plain) order by y", [2.0, 5.0]),
    ("select y from t where x in (select v from plain) and x in (select v from narrow) order by y",
     [2.0, 5.0]),
    # int64 probe against int32 and against double items: keys widened as Catalyst does
    ("select y from t where x in (select v from narrow) order by y", [2.0, 4.0, 5.0]),
    ("select y from t where x in (select v from floats) order by y", [2.0, 5.0]),
    # the subquery is a query of its own, with its own IN (subquery)
    ("select y from t where x in (select v from plain where v in (select v from narrow)) order by y",
     [2.0, 5.0]),
]


@pytest.mark.parametrize("query,want", SEMI, ids=[f"semi{i}" for i in range(len(SEMI))])
def test_where_conjunct_is_a_semi_join(tpu, cpu, query, want):
    semi, host = _counters()
    got = [r[0] for r in tpu.sql(query).collect()]
    plan = tpu._last_plan.tree_string()
    assert got == want
    assert "left_semi" in plan and "INSET" not in plan
    now = _counters()
    assert now[0] - semi == query.count(" in (") and now[1] == host
    assert [r[0] for r in cpu.sql(query).collect()] == want


# the shapes that keep the literal-set route: NULL is not FALSE there.
# (query, rows Spark returns, values of the subquery's result)
KEPT = [
    # NOT IN is null-aware: a null in the result empties the answer
    ("select y from t where x not in (select v from plain) order by y", [(4.0,)], 3),
    ("select y from t where x not in (select v from with_null) order by y", [], 3),
    ("select y from t where x is not null and x not in (select v from empty) order by y",
     [(1.0,), (2.0,), (4.0,), (5.0,)], 0),
    ("select y from t where not (x in (select v from with_null)) order by y", [], 3),
    # under OR the other branch can rescue a row whose IN is NULL or FALSE
    ("select y from t where x in (select v from plain) or y > 3.5 order by y",
     [(1.0,), (2.0,), (4.0,), (5.0,)], 3),
    # in a SELECT list the value itself is the answer
    ("select y, x in (select v from with_null) m from t order by y",
     [(1.0, True), (2.0, True), (3.0, None), (4.0, None), (5.0, True)], 3),
    ("select y, x in (select v from plain) m from t order by y",
     [(1.0, True), (2.0, True), (3.0, None), (4.0, False), (5.0, True)], 3),
    ("select y, case when x in (select v from plain) then 1 else 0 end m from t order by y",
     [(1.0, 1), (2.0, 1), (3.0, 0), (4.0, 0), (5.0, 1)], 3),
]


@pytest.mark.parametrize("query,want,values", KEPT, ids=[f"kept{i}" for i in range(len(KEPT))])
def test_null_observing_shapes_keep_their_answers(tpu, cpu, query, want, values):
    semi, host = _counters()
    got = tpu.sql(query).collect()
    assert got == want
    assert "left_semi" not in tpu._last_plan.tree_string()
    # the result's values came to the host, and no join was planned for them
    assert _counters() == (semi, host + values)
    assert cpu.sql(query).collect() == want


def test_dataframe_isin_in_filter_and_in_select(tpu):
    t, sub = tpu.table("t"), tpu.table("with_null")
    semi, host = _counters()
    got = t.filter(col("x").isin(sub) & (col("y") < 4.5)).order_by("y").collect()
    assert got == [(1, 1.0), (2, 2.0)]
    assert "left_semi" in tpu._last_plan.tree_string()
    assert _counters() == (semi + 1, host)
    got = t.select(col("x").isin(sub).alias("m")).collect()
    assert got == [(True,), (True,), (None,), (None,), (True,)]
    assert _counters() == (semi + 1, host + 3)


def test_items_of_another_kind_keep_the_literal_set(tpu):
    """A string probe against numeric items is no equi-join the planner
    coerces: the rewrite leaves it alone."""
    from spark_rapids_tpu.plan import logical as L
    from spark_rapids_tpu.plan.subquery import rewrite_in_subqueries

    strings = tpu.create_dataframe(pa.table({"s": ["1", "2"]}))
    df = strings.filter(col("s").isin(tpu.table("plain")))
    plan, n = rewrite_in_subqueries(df._plan)
    assert n == 0 and isinstance(plan, L.Filter)
    plan, n = rewrite_in_subqueries(tpu.table("t").filter(col("x").isin(tpu.table("plain")))._plan)
    assert n == 1 and isinstance(plan, L.Join) and plan.join_type == "left_semi"


def test_more_than_one_column_is_refused(tpu):
    two = tpu.create_dataframe(pa.table({"a": [1], "b": [2]}))
    with pytest.raises(ValueError, match="one column"):
        tpu.table("t").filter(col("x").isin(two)).collect()


def test_large_subquery_stays_on_the_device(tpu):
    """Thousands of values with duplicates and nulls: nothing comes to the
    host, and the answer is the set's."""
    rng = np.random.default_rng(5)
    probe = pa.table({"k": rng.integers(0, 5000, 4000)})
    items = rng.integers(0, 5000, 3000)
    sub = pa.table({"v": pa.array(items, mask=rng.random(3000) < 0.05)})
    semi, host = _counters()
    got = tpu.create_dataframe(probe, num_partitions=3).filter(
        col("k").isin(tpu.create_dataframe(sub, num_partitions=2))
    ).collect()
    valid = set(sub.column("v").drop_null().to_pylist())
    assert sorted(r[0] for r in got) == sorted(k for k in probe.column("k").to_pylist() if k in valid)
    assert _counters() == (semi + 1, host)
