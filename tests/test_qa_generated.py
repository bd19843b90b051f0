"""Generated QA battery: ~150 mixed-shape queries under strict fallback
mode, differential vs the CPU oracle.

The reference's long-tail interaction net is its ~756-SELECT nightly SQL
battery (integration_tests/src/main/python/qa_nightly_sql.py +
qa_nightly_select_test.py); this battery generates the same KIND of
coverage — cross products of aggregate shapes × joins × windows × filters ×
expression decorations over null-rich tables — deterministically from a
seed, so every run exercises identical queries. Strict mode
(spark.rapids.sql.test.enabled) fails any query that silently leaves the
device plan.
"""
from __future__ import annotations

import itertools
import random

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.functions import col
from spark_rapids_tpu.window import Window

from harness import assert_cpu_and_tpu_equal

N = 4_000
SEED = 1234


def _fact():
    rng = np.random.default_rng(SEED)
    k = rng.integers(0, 37, N)
    nulls = rng.random(N) < 0.08
    return pa.table(
        {
            "k": pa.array(k, type=pa.int64()),
            "g": pa.array(rng.integers(0, 7, N), type=pa.int32()),
            "x": pa.array(
                np.where(nulls, None, rng.integers(-999, 999, N)).tolist(),
                type=pa.int64(),
            ),
            "d": pa.array((rng.random(N) * 200 - 100).round(3)),
            "s": pa.array(
                [
                    None if i % 17 == 0 else f"row-{i % 23:02d}:{i % 5}"
                    for i in range(N)
                ]
            ),
            "dt": pa.array(
                rng.integers(10_000, 12_000, N).astype(np.int32),
                type=pa.date32(),
            ),
            "b": pa.array(rng.random(N) < 0.5),
        }
    )


def _dim():
    rng = np.random.default_rng(SEED + 1)
    n = 37
    return pa.table(
        {
            "dk": pa.array(np.arange(n), type=pa.int64()),
            "cat": pa.array([f"cat{i % 6}" for i in range(n)]),
            "w": pa.array((rng.random(n) * 10).round(2)),
        }
    )


FACT = _fact()
DIM = _dim()

FILTERS = [
    None,
    lambda: col("x") > 0,
    lambda: col("s").like("row-1%"),
    lambda: col("dt") >= __import__("datetime").date(1998, 10, 1),
    lambda: col("x").is_not_null() & (col("d") < 50.0),
    lambda: col("k").isin(1, 3, 5, 7, 11, 13) | col("b"),
]

PROJECTIONS = [
    None,
    lambda df: df.with_column("e1", col("d") * 2.0 + col("g")),
    lambda df: df.with_column(
        "e1", F.when(col("x") > 100, "hi").when(col("x") < -100, "lo").otherwise("mid")
    ),
    lambda df: df.with_column("e1", F.substring(col("s"), 5, 4)),
    lambda df: df.with_column("e1", F.year(col("dt")) + F.month(col("dt"))),
    lambda df: df.with_column("e1", F.coalesce(col("x"), col("k")) % 10),
]

AGGS = [
    [lambda: F.sum(col("x")).alias("a0"), lambda: F.count("*").alias("a1")],
    [lambda: F.avg(col("d")).alias("a0"), lambda: F.max(col("s")).alias("a1")],
    [
        lambda: F.count_distinct(col("g")).alias("a0"),
        lambda: F.min(col("dt")).alias("a1"),
    ],
    [
        lambda: F.stddev(col("d")).alias("a0"),
        lambda: F.sum(col("k") * 2).alias("a1"),
    ],
    [lambda: F.max(col("x")).alias("a0"), lambda: F.min(col("x")).alias("a1")],
]

GROUPINGS = ["none", "k", "multi", "rollup"]
JOINS = ["none", "inner", "left", "semi", "anti"]
WINDOWS = ["none", "rank", "runsum"]


def _build(case, s):
    (fi, pi, ai, grouping, join, window) = case
    df = s.create_dataframe(FACT, num_partitions=2)
    if FILTERS[fi] is not None:
        df = df.filter(FILTERS[fi]())
    if PROJECTIONS[pi] is not None:
        df = PROJECTIONS[pi](df)
    if join != "none":
        dim = s.create_dataframe(DIM)
        df = df.join(dim, on=[("k", "dk")], how=join)
    if window != "none":
        w = Window.partition_by("g").order_by("dt", "k")
        if window == "rank":
            df = df.with_column("wv", F.rank().over(w))
        else:
            df = df.with_column(
                "wv",
                F.sum(col("k")).over(
                    Window.partition_by("g").order_by("dt", "k").rows_between(
                        Window.unboundedPreceding, 0
                    )
                ),
            )
    aggs = [mk() for mk in AGGS[ai]]
    if grouping == "none":
        return df.agg(*aggs)
    if grouping == "k":
        return df.group_by("g").agg(*aggs)
    if grouping == "multi":
        return df.group_by("g", "b").agg(*aggs)
    return df.rollup("g", "b").agg(*aggs)


def _cases():
    """~150 deterministic samples of the cross-product."""
    rng = random.Random(SEED)
    full = list(
        itertools.product(
            range(len(FILTERS)),
            range(len(PROJECTIONS)),
            range(len(AGGS)),
            GROUPINGS,
            JOINS,
            WINDOWS,
        )
    )
    rng.shuffle(full)
    picked = full[:150]
    # windows over a semi/anti join of renamed columns etc. are fine; but
    # count_distinct inside rollup exercises the Expand path — keep them in
    return picked


CASES = _cases()


@pytest.mark.parametrize("idx", range(0, len(CASES), 1))
def test_qa_generated(idx):
    case = CASES[idx]
    assert_cpu_and_tpu_equal(
        lambda s: _build(case, s),
        approx_float=True,
        conf={"spark.sql.shuffle.partitions": 2},
    )
