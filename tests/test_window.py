"""Window function differential tests — WindowFunctionSuite /
window_function_test.py analogue (SURVEY.md §4)."""
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.functions import col
from spark_rapids_tpu.window import Window

from harness import assert_cpu_and_tpu_equal, tpu_session


def _table(n=300, groups=12, seed=21, with_ties=True):
    rng = np.random.default_rng(seed)
    ts = rng.integers(0, 40 if with_ties else 10_000_000, n).astype(np.int64)
    v = rng.integers(-100, 100, n).astype(np.int64)
    vmask = rng.random(n) < 0.1
    return pa.table(
        {
            "k": pa.array(rng.integers(0, groups, n).astype(np.int64)),
            "ts": pa.array(ts),
            "v": pa.array(v, mask=vmask),
            "f": pa.array(np.where(rng.random(n) < 0.05, np.nan, rng.random(n))),
            "s": pa.array([f"s{int(x)}" for x in rng.integers(0, 25, n)]),
        }
    )


def _w():
    return Window.partition_by("k").order_by("ts", "s")


def test_row_number():
    t = _table()
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=3).with_column(
            "rn", F.row_number().over(_w())
        )
    )


def test_rank_dense_rank_with_ties():
    t = _table(with_ties=True)
    w = Window.partition_by("k").order_by("ts")
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=3)
        .with_column("r", F.rank().over(w))
        .with_column("dr", F.dense_rank().over(w))
    )


def test_running_sum_default_frame_peers():
    # default frame with ORDER BY = RANGE UNBOUNDED..CURRENT: peers share
    t = _table(with_ties=True)
    w = Window.partition_by("k").order_by("ts")
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=2).with_column(
            "rs", F.sum(col("v")).over(w)
        )
    )


def test_partition_total_no_order():
    t = _table()
    w = Window.partition_by("k")
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=3)
        .with_column("tot", F.sum(col("v")).over(w))
        .with_column("cnt", F.count(col("v")).over(w))
        .with_column("mean", F.avg(col("v")).over(w))
    )


def test_lead_lag():
    t = _table(with_ties=False)
    w = _w()
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=2)
        .with_column("lg", F.lag(col("v"), 1).over(w))
        .with_column("ld", F.lead(col("v"), 2, -999).over(w))
        .with_column("sl", F.lag(col("s"), 1, "none").over(w))
    )


@pytest.mark.parametrize("lo,hi", [(-3, 0), (-2, 2), (0, 3), (-5, -1), (1, 4)])
def test_bounded_rows_sum_min_max(lo, hi):
    t = _table(with_ties=False)
    w = _w().rows_between(lo, hi)
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=2)
        .with_column("bs", F.sum(col("v")).over(w))
        .with_column("bmin", F.min(col("v")).over(w))
        .with_column("bmax", F.max(col("v")).over(w))
        .with_column("bc", F.count(col("v")).over(w)),
    )


def test_unbounded_prefix_suffix_min_max():
    t = _table(with_ties=False)
    w1 = _w().rows_between(Window.unbounded_preceding, Window.current_row)
    w2 = _w().rows_between(Window.current_row, Window.unbounded_following)
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=2)
        .with_column("pmin", F.min(col("v")).over(w1))
        .with_column("smax", F.max(col("v")).over(w2))
    )


def test_float_window_with_nans():
    t = _table()
    w = Window.partition_by("k")
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=2)
        .with_column("fmin", F.min(col("f")).over(w))
        .with_column("fmax", F.max(col("f")).over(w)),
        approx_float=True,
    )


def test_desc_order_window():
    t = _table(with_ties=False)
    w = Window.partition_by("k").order_by(col("ts").desc())
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=2).with_column(
            "rn", F.row_number().over(w)
        )
    )


def test_no_partition_window():
    t = _table(n=120)
    w = Window.order_by("ts", "s")
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=3).with_column(
            "rn", F.row_number().over(w)
        )
    )


def test_multiple_specs_one_select():
    t = _table(with_ties=False)
    w1 = Window.partition_by("k").order_by("ts", "s")
    w2 = Window.partition_by("s")
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=2)
        .with_column("rn", F.row_number().over(w1))
        .with_column("tot", F.count(col("v")).over(w2))
    )


def test_window_fallback_wide_minmax_frame():
    # frame wider than the unroll cap → CPU fallback, results still correct
    t = _table(n=100, with_ties=False)
    w = _w().rows_between(-300, 300)
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=2).with_column(
            "m", F.min(col("v")).over(w)
        ),
        allowed_non_tpu=[
            "CpuWindowExec",
            "CpuCoalescePartitionsExec",
            "CpuShuffleExchange",
        ],
    )


# ── numeric RANGE frames (device binary-search kernel vs CPU linear scan) ──


def _range_table(n=260, seed=33):
    rng = np.random.default_rng(seed)
    v = rng.integers(0, 60, n).astype(np.int64)  # heavy ties
    nulls = rng.random(n) < 0.08
    return pa.table(
        {
            "k": pa.array(rng.integers(0, 6, n).astype(np.int32)),
            "o": pa.array(
                [None if m else int(x) for x, m in zip(v, nulls)], type=pa.int64()
            ),
            "v": pa.array(rng.standard_normal(n)),
        }
    )


@pytest.mark.parametrize("lo,hi", [(-5, 0), (-3, 3), (0, 10), (-10, -2), (2, 8)])
def test_numeric_range_frames(lo, hi):
    t = _range_table()
    w = Window.partition_by("k").order_by("o").range_between(lo, hi)
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=2)
        .with_column("rs", F.sum(col("v")).over(w))
        .with_column("rmin", F.min(col("v")).over(w))
        .with_column("rmax", F.max(col("v")).over(w))
        .with_column("rc", F.count(col("v")).over(w)),
        approx_float=True,
    )


def test_numeric_range_desc_order():
    t = _range_table(seed=34)
    w = (
        Window.partition_by("k")
        .order_by(col("o").desc())
        .range_between(-4, 4)
    )
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=2)
        .with_column("rs", F.sum(col("v")).over(w))
        .with_column("rc", F.count(col("v")).over(w)),
        approx_float=True,
    )


def test_numeric_range_one_side_unbounded():
    t = _range_table(seed=35)
    w = Window.partition_by("k").order_by("o").range_between(
        Window.unboundedPreceding, 5
    )
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=2)
        .with_column("rs", F.sum(col("v")).over(w))
        .with_column("rmax", F.max(col("v")).over(w)),
        approx_float=True,
    )


def test_wide_bounded_rows_min_max_on_device():
    """Frames wider than the old unroll cap (256) now run on device via the
    sparse-table kernel."""
    t = _table(n=600, with_ties=False)
    w = _w().rows_between(-400, 400)
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=2)
        .with_column("bmin", F.min(col("v")).over(w))
        .with_column("bmax", F.max(col("v")).over(w)),
    )


# ── string min/max over windows (r2 gap: sparse-table lex ARG-pick over
# radix words — reference runs cudf string MIN/MAX windows) ────────────────
@pytest.mark.parametrize("frame", ["bounded", "unbounded", "growing"])
def test_string_min_max_over_window(frame):
    t = _table(n=400, seed=61)
    w = _w()
    if frame == "bounded":
        w = w.rows_between(-3, 2)
    elif frame == "growing":
        w = w.rows_between(Window.unbounded_preceding, Window.current_row)
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=2)
        .with_column("smin", F.min(col("s")).over(w))
        .with_column("smax", F.max(col("s")).over(w)),
    )


def test_string_min_max_window_with_nulls_and_empty():
    ss = ["b", None, "", "zz", None, "a", None, None]
    t = pa.table({"k": [1, 1, 1, 1, 2, 2, 3, 3], "o": list(range(8)), "s": ss})
    w = Window.partition_by("k").order_by("o").rows_between(-1, 0)
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t)
        .with_column("mn", F.min(col("s")).over(w))
        .with_column("mx", F.max(col("s")).over(w)),
    )


# ── decimal RANGE order keys (r2 gap: scale-adjusted frame bounds) ─────────
def test_decimal_range_frame():
    import decimal

    rng = np.random.default_rng(62)
    n = 300
    vals = [decimal.Decimal(f"{int(v)}.{int(v) % 100:02d}") for v in rng.integers(0, 60, n)]
    t = pa.table(
        {
            "k": pa.array(rng.integers(0, 6, n).astype(np.int64)),
            "d": pa.array(vals, type=pa.decimal128(10, 2)),
            "x": pa.array(rng.integers(0, 100, n).astype(np.int64)),
        }
    )
    w = Window.partition_by("k").order_by("d").range_between(-5, 5)
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=2)
        .with_column("sx", F.sum(col("x")).over(w))
        .with_column("cx", F.count(col("x")).over(w)),
    )
    # oracle spot check: the frame is ±5 in VALUE space, not unscaled space
    from harness import tpu_session

    t2 = pa.table(
        {
            "k": [1] * 3,
            "d": pa.array(
                [decimal.Decimal("1.00"), decimal.Decimal("4.00"), decimal.Decimal("9.00")],
                type=pa.decimal128(10, 2),
            ),
            "x": [10, 20, 40],
        }
    )
    rows = (
        tpu_session()
        .create_dataframe(t2)
        .with_column("sx", F.sum(col("x")).over(w))
        .collect()
    )
    got = {str(r[1]): r[3] for r in rows}
    assert got == {"1.00": 30, "4.00": 70, "9.00": 60}, got


def test_percent_rank_cume_dist_ntile():
    """percent_rank / cume_dist / ntile (Spark ranking family; device via
    the segment-scan kernel). Oracle check against hand-computed values,
    plus differential vs the CPU engine with ties."""
    t = pa.table(
        {
            "k": [1, 1, 1, 1, 2, 2, 2],
            "d": [10, 20, 20, 30, 5, 5, 7],
            "v": [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0],
        }
    )

    def q(s):
        w = Window.partition_by("k").order_by("d")
        return (
            s.create_dataframe(t)
            .with_column("pr", F.percent_rank().over(w))
            .with_column("cd", F.cume_dist().over(w))
            .with_column("nt", F.ntile(2).over(w))
        )

    assert_cpu_and_tpu_equal(q)
    s = tpu_session({})
    rows = {(r[0], r[1], r[2]): r[3:] for r in q(s).collect()}
    # k=1: d=[10,20,20,30] -> pr = [0, 1/3, 1/3, 1]; cd = [.25, .75, .75, 1]
    assert rows[(1, 10, 1.0)] == (0.0, 0.25, 1)
    assert rows[(1, 20, 2.0)][0] == pytest.approx(1 / 3)
    assert rows[(1, 20, 2.0)][1] == 0.75
    assert rows[(1, 30, 4.0)] == (1.0, 1.0, 2)
    # k=2: 3 rows, 2 buckets -> sizes [2, 1]
    assert [rows[(2, 5, 5.0)][2], rows[(2, 5, 6.0)][2], rows[(2, 7, 7.0)][2]] == [1, 1, 2]


def test_ntile_more_buckets_than_rows():
    t = pa.table({"k": [1, 1], "d": [1, 2]})

    def q(s):
        w = Window.partition_by("k").order_by("d")
        return s.create_dataframe(t).with_column("nt", F.ntile(5).over(w))

    assert_cpu_and_tpu_equal(q)
    s = tpu_session({})
    assert sorted(r[2] for r in q(s).collect()) == [1, 2]


# ── what a trace and the counters see of the window ────────────────────────


def _window_kernels():
    from spark_rapids_tpu import kernels as K

    return {k: fn for k, fn in K._KERNELS.items() if str(k[0]).startswith("window")}


def test_window_kernel_name_and_store_key():
    """The key's tag names the kernel (whether a stored executable is stale
    is cache/xla_store.py's to decide, from the source), and a device trace
    names the module after the jitted function."""
    t = _table(60)
    s = tpu_session()
    s.create_dataframe(t).with_column("r", F.rank().over(_w())).collect()
    mine = _window_kernels()
    assert mine and {k[0] for k in mine} == {"window"}
    assert {fn._fn.__name__ for fn in mine.values()} == {"_window"}


@pytest.mark.parametrize("parts", [1, 3])
def test_window_counters(parts, monkeypatch):
    """``window.*`` advance by the launches made, from values the host
    already holds (plain ints, no device value), and counting costs no
    ``block_until_ready``: a run with the counters taken out waits exactly
    as often."""
    import jax

    from spark_rapids_tpu.exec import tpu_window
    from spark_rapids_tpu.obs import metrics

    t = _table(200, groups=5)
    s = tpu_session()

    def query():
        return (
            s.create_dataframe(t, num_partitions=parts)
            .rollup(col("k"), col("s"))
            .agg(F.sum(col("v")).alias("sv"))
            .with_column("r", F.rank().over(Window.partition_by("k").order_by(col("sv").desc())))
        )

    waits = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready", lambda x: waits.append(1) or real(x))

    def counted_run():
        before, n = dict(metrics.GLOBAL.snapshot()), len(waits)
        rows = query().collect()
        after = dict(metrics.GLOBAL.snapshot())
        return rows, {k: after[k] - before.get(k, 0) for k in after}, len(waits) - n

    query().collect()  # compiled; the runs below launch and no more
    rows, delta, waits_counting = counted_run()
    calls = delta["window.calls"]
    assert calls >= 1
    # capacities are powers of two that hold every row the windows were given
    assert delta["window.rowsCapacity"] >= max(len(rows), calls)
    assert delta["window.rowsCapacity"] % 8 == 0
    assert len(rows) == len(t.group_by(["k", "s"]).aggregate([]).to_pylist()) + 5 + 1

    class Recorder:
        seen = []

        def add(self, v):
            assert type(v) is int, type(v)
            self.seen.append(v)

    for name in ("_M_CALLS", "_M_ROWS_CAPACITY"):
        monkeypatch.setattr(tpu_window, name, Recorder())
    _, delta, waits_plain = counted_run()
    assert len(Recorder.seen) == 2 * calls
    assert delta["window.calls"] == 0  # the real one stood still
    assert waits_plain == waits_counting


def test_sort_asc_nulls_last():
    """``asc_nulls_last`` in sort() and in a window's order: TPC-DS orders its
    rollups NULLS LAST."""
    t = _table(120)
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(t, num_partitions=2)
        .with_column(
            "r", F.rank().over(Window.partition_by("k").order_by(col("v").asc_nulls_last()))
        )
        .sort(col("v").asc_nulls_last(), col("k"), col("ts"), col("s"), col("f")),
        sort_result=False,
    )
    s = tpu_session()
    got = [r[0] for r in s.create_dataframe(t).sort(col("v").asc_nulls_last()).select("v").collect()]
    nulls = t.column("v").null_count
    assert nulls and got[-nulls:] == [None] * nulls and None not in got[:-nulls]
    assert got[:-nulls] == sorted(got[:-nulls])
