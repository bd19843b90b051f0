"""The join's bounds from one sort (ops/join.py ``join_bounds``): one stable
sort of [build ++ probe] by a packed key gives the build order and every
probe row's [lower, upper) range bit for bit as the three sorts over uint64
radix words did (and as plain numpy does over those words), and the joins
through a session answer as the CPU engine does. Small capacities
throughout."""
import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.device import DeviceColumn
from spark_rapids_tpu.exec import tpu_join
from spark_rapids_tpu.functions import col
from spark_rapids_tpu.obs import metrics as obs_metrics
from spark_rapids_tpu.ops import join as J
from spark_rapids_tpu.ops import sortkeys as sk
from spark_rapids_tpu.ops.aggregate import _normalize_float
from spark_rapids_tpu.types import (
    BOOLEAN,
    BYTE,
    DATE,
    DOUBLE,
    FLOAT,
    INT,
    LONG,
    SHORT,
    TIMESTAMP,
)

from harness import assert_cpu_and_tpu_equal
from test_sortkeys_packed import fixed_column, sort_permutation, string_column


# ── the three-sort form the one sort replaces ────────────────────────────
def _three_sort_key_words(cols, live, side_flag):
    words = []
    any_null = ~live
    for c in cols:
        c = _normalize_float(c)
        any_null = any_null | ~c.validity
        words.extend(sk.column_radix_words(c, value_only=True))
    sentinel = jnp.where(any_null, jnp.uint64(2 + side_flag), jnp.uint64(0))
    return [sentinel] + words


def three_sort_join_bounds(build_cols, build_live, probe_cols, probe_live):
    """``join_bounds`` as three ``sort_permutation`` calls over uint64 words:
    the build side alone, then [build ++ probe] with the probe first on ties
    (lower) and with the build first (upper)."""
    nb, npr = build_live.shape[0], probe_live.shape[0]
    bw = _three_sort_key_words(build_cols, build_live, 0)
    pw = _three_sort_key_words(probe_cols, probe_live, 1)
    build_order = sort_permutation(bw, None, live_first=False)

    def bound(probe_first):
        flag_b = jnp.full(nb, 1 if probe_first else 0, jnp.uint64)
        flag_p = jnp.full(npr, 0 if probe_first else 1, jnp.uint64)
        keys = [jnp.concatenate([b, p]) for b, p in zip(bw, pw)]
        src = jnp.concatenate([jnp.full(nb, -1, jnp.int32), jnp.arange(npr, dtype=jnp.int32)])
        perm = sort_permutation(keys + [jnp.concatenate([flag_b, flag_p])], None,
                                live_first=False)
        ssrc = src[perm]
        is_build = ssrc < 0
        nbefore = jnp.cumsum(is_build.astype(jnp.int32)) - is_build.astype(jnp.int32)
        tgt = jnp.where(is_build, npr, ssrc)
        return jnp.zeros(npr, jnp.int32).at[tgt].set(jnp.where(is_build, 0, nbefore),
                                                     mode="drop")

    return build_order, bound(True), bound(False)


def numpy_join_bounds(build_cols, build_live, probe_cols, probe_live):
    """The same three answers in plain numpy from the same uint64 words:
    ``np.lexsort`` (stable) for the build order, and each probe key's rank
    among all keys searched in the build side's sorted ranks for the bounds."""
    bw = np.stack([np.asarray(w) for w in _three_sort_key_words(build_cols, build_live, 0)], 1)
    pw = np.stack([np.asarray(w) for w in _three_sort_key_words(probe_cols, probe_live, 1)], 1)
    build_order = np.lexsort(bw.T[::-1])
    _, rank = np.unique(np.concatenate([bw, pw]), axis=0, return_inverse=True)
    rank = rank.ravel()
    build_ranks, probe_ranks = np.sort(rank[: len(bw)]), rank[len(bw):]
    lower = np.searchsorted(build_ranks, probe_ranks, side="left")
    upper = np.searchsorted(build_ranks, probe_ranks, side="right")
    return build_order, lower, upper


# ── inputs ───────────────────────────────────────────────────────────────
NULLS = {"none": 0.0, "some": 0.3, "all": 1.0}


def _pool(rng, spec, distinct):
    """``distinct`` non-null values of a key column (an int is a string plane width)."""
    if isinstance(spec, int):
        return string_column(rng, spec, distinct, alphabet=3, null_fraction=0.0)
    return fixed_column(rng, spec, distinct, null_fraction=0.0)


def _draw(rng, pool, cap, n, nulls, width=None, as_type=None):
    """``cap`` rows of ``pool``'s values, the first ``n`` live; the padding
    rows keep values and validity bits of their own, as a compacted batch's do."""
    pick = jnp.asarray(rng.integers(0, pool.data.shape[0], cap))
    valid = jnp.asarray(rng.random(cap) >= NULLS[nulls])
    data = pool.data[pick]
    if as_type is not None:
        data = data.astype(as_type.np_dtype)
    lengths = None if pool.lengths is None else pool.lengths[pick]
    if width is not None and width > data.shape[1]:
        data = jnp.pad(data, ((0, 0), (0, width - data.shape[1])))
    dt = pool.dtype if as_type is None else as_type
    return DeviceColumn(dt, data, valid, lengths), jnp.arange(cap) < n


def _sides(seed, specs, nb=48, npr=40, live_b=40, live_p=33, nulls=("some", "some"),
           distinct=6, shared=True, probe_types=None):
    rng = np.random.default_rng(seed)
    pools_b = [_pool(rng, s, distinct) for s in specs]
    pools_p = pools_b if shared else [_pool(rng, s, distinct) for s in specs]
    bcols, pcols = [], []
    for i, s in enumerate(specs):
        b, blive = _draw(rng, pools_b[i], nb, live_b, nulls[0])
        pt = None if probe_types is None else probe_types[i]
        p, plive = _draw(rng, pools_p[i], npr, live_p, nulls[1], as_type=pt)
        bcols.append(b)
        pcols.append(p)
    return bcols, blive, pcols, plive


def _unlike_string_widths(seed):
    """A key of string planes 5 and 12 bytes wide, padded to one width as
    the join's phase 1 does."""
    rng = np.random.default_rng(seed)
    pool = _pool(rng, 5, 7)
    b, blive = _draw(rng, pool, 48, 41, "some")
    p, plive = _draw(rng, pool, 40, 35, "some", width=12)
    w = max(b.data.shape[1], p.data.shape[1])
    return [J.pad_string_column(b, w)], blive, [J.pad_string_column(p, w)], plive


def _assert_same_bounds(bcols, blive, pcols, plive):
    got = jax.jit(J.join_bounds)(bcols, blive, pcols, plive)
    want = jax.jit(three_sort_join_bounds)(bcols, blive, pcols, plive)
    order, lower, upper = (np.asarray(a) for a in got)
    w_order, w_lower, w_upper = (np.asarray(a) for a in want)
    assert order.dtype == w_order.dtype and order.tobytes() == w_order.tobytes()
    assert lower.dtype == w_lower.dtype and lower.tobytes() == w_lower.tobytes()
    assert (upper - lower).tobytes() == (w_upper - w_lower).tobytes()
    assert upper.tobytes() == w_upper.tobytes()
    n_order, n_lower, n_upper = numpy_join_bounds(bcols, blive, pcols, plive)
    assert (order == n_order).all() and (lower == n_lower).all() and (upper == n_upper).all()
    return upper - lower


KEY_TYPES = [BYTE, SHORT, INT, LONG, DATE, TIMESTAMP, BOOLEAN, FLOAT, DOUBLE, 8]


@pytest.mark.parametrize("spec", KEY_TYPES, ids=lambda s: f"string{s}" if isinstance(s, int)
                         else s.simple_string)
def test_one_key_same_as_three_sorts(spec):
    counts = _assert_same_bounds(*_sides(1, [spec]))
    assert counts.sum() > 0


def test_float_keys_nan_and_negative_zero_match():
    """NaN matches NaN and -0.0 matches 0.0: the float pool holds both of each."""
    for spec in (FLOAT, DOUBLE):
        bcols, blive, pcols, plive = _sides(2, [spec], distinct=11, nulls=("none", "none"))
        counts = _assert_same_bounds(bcols, blive, pcols, plive)
        pk = np.asarray(pcols[0].data)
        assert np.isnan(pk).any() and (counts[np.isnan(pk) & np.asarray(plive)] > 0).all()


def test_strings_of_unlike_widths_same_as_three_sorts():
    counts = _assert_same_bounds(*_unlike_string_widths(3))
    assert counts.sum() > 0


@pytest.mark.parametrize("specs", [[LONG, INT], [8, DATE, DOUBLE], [BOOLEAN, 3, SHORT]],
                         ids=["long_int", "string_date_double", "bool_string_short"])
def test_multi_column_keys_same_as_three_sorts(specs):
    counts = _assert_same_bounds(*_sides(4, specs, distinct=4))
    assert counts.sum() > 0


def test_int_key_against_long_key_same_as_three_sorts():
    """An int32 build key against an int64 probe key at one position, and
    the other way round, beside a key of one dtype."""
    bcols, blive, pcols, plive = _sides(5, [INT, SHORT], probe_types=[LONG, None])
    counts = _assert_same_bounds(bcols, blive, pcols, plive)
    assert counts.sum() > 0
    counts = _assert_same_bounds(pcols, plive, bcols, blive)
    assert counts.sum() > 0


@pytest.mark.parametrize("build_nulls", list(NULLS))
@pytest.mark.parametrize("probe_nulls", list(NULLS))
def test_null_keys_same_as_three_sorts(build_nulls, probe_nulls):
    counts = _assert_same_bounds(*_sides(6, [LONG, 8], nulls=(build_nulls, probe_nulls),
                                         distinct=3))
    if "all" in (build_nulls, probe_nulls):
        assert counts.sum() == 0


@pytest.mark.parametrize("case", ["empty_build", "empty_probe", "both_empty", "all_padding_valid",
                                  "all_keys_equal", "no_key_shared", "one_row_each"])
def test_edge_cases_same_as_three_sorts(case):
    kw = {
        "empty_build": dict(live_b=0),
        "empty_probe": dict(live_p=0),
        "both_empty": dict(live_b=0, live_p=0),
        "all_padding_valid": dict(live_b=10, live_p=5, nulls=("none", "none")),
        "all_keys_equal": dict(distinct=1, nulls=("none", "none")),
        "no_key_shared": dict(shared=False, nulls=("none", "none")),
        "one_row_each": dict(nb=1, npr=1, live_b=1, live_p=1, distinct=1,
                             nulls=("none", "none")),
    }[case]
    counts = _assert_same_bounds(*_sides(7, [LONG], **kw))
    if case == "all_keys_equal":
        assert (counts == np.where(np.arange(40) < 33, 40, 0)).all()
    if case in ("empty_build", "empty_probe", "both_empty"):
        assert counts.sum() == 0


def test_duplicate_heavy_keys_same_as_three_sorts():
    """q95's shape: a few rows a key at a larger capacity."""
    _assert_same_bounds(*_sides(8, [LONG], nb=512, npr=384, live_b=500, live_p=380,
                                distinct=60))


def test_one_sort_and_its_passes():
    """Phase 1 runs one loop of packed passes: 3 for an int64 key where the
    three sorts over uint64 words ran 4 + 6 + 6."""
    bcols, blive, pcols, plive = _sides(9, [LONG])
    with sk.counting_passes() as count:  # a function of its own: traced afresh
        jax.eval_shape(lambda *a: J.join_bounds(*a), bcols, blive, pcols, plive)
    assert count == [3, 16]
    hlo = jax.jit(J.join_bounds).lower(bcols, blive, pcols, plive).as_text()
    assert hlo.count("stablehlo.while") == 1 and hlo.count("stablehlo.sort") == 1


# ── joins through a session, against the CPU engine ──────────────────────
JOIN_TYPES = ["inner", "left", "right", "full", "left_semi", "left_anti"]


def _join_tables(seed):
    rng = np.random.default_rng(seed)
    n, m = 240, 160
    left = pa.table({
        "k": pa.array(rng.integers(0, 30, n), mask=rng.random(n) < 0.1),
        "f": pa.array(np.where(rng.random(n) < 0.1, np.nan,
                               np.where(rng.random(n) < 0.1, -0.0, rng.integers(0, 5, n)))),
        "s": pa.array([f"v{int(x)}" for x in rng.integers(0, 9, n)]),
        "lv": pa.array(rng.integers(-50, 50, n)),
    })
    right = pa.table({
        "k2": pa.array(rng.integers(10, 40, m), mask=rng.random(m) < 0.1),
        "f2": pa.array(np.where(rng.random(m) < 0.1, np.nan,
                                np.where(rng.random(m) < 0.1, 0.0, rng.integers(0, 5, m)))),
        "s2": pa.array([f"v{int(x)}" for x in rng.integers(3, 12, m)]),
        "rv": pa.array(rng.integers(-50, 50, m)),
    })
    return left, right


@pytest.mark.parametrize("how", JOIN_TYPES)
@pytest.mark.parametrize("residual", [False, True], ids=["equi", "residual"])
def test_joins_same_as_cpu(how, residual):
    lt, rt = _join_tables(36)
    cond = (col("k") == col("k2")) & (col("s") == col("s2")) & (col("f") == col("f2"))
    if residual:
        cond = cond & (col("lv") < col("rv"))
    assert_cpu_and_tpu_equal(
        lambda s: s.create_dataframe(lt, num_partitions=3).join(
            s.create_dataframe(rt, num_partitions=2), on=cond, how=how),
        conf={"spark.sql.autoBroadcastJoinThreshold": "-1"},
    )


# ── the counters ─────────────────────────────────────────────────────────
def _pass_counters():
    snap = dict(obs_metrics.GLOBAL.snapshot())
    return snap.get("sort.keyPasses", 0), snap.get("sort.keyPassesUnpacked", 0)


def test_q3_shaped_join_counts_three_passes_a_launch(monkeypatch):
    """customer ⋈ orders ⋈ lineitem on int64 keys: each phase-1 launch adds
    3 to ``sort.keyPasses`` and 16 to ``sort.keyPassesUnpacked``."""
    from spark_rapids_tpu import TpuSession

    rng = np.random.default_rng(3)
    customer = pa.table({"c_custkey": pa.array(np.arange(40, dtype=np.int64)),
                         "c_mktsegment": pa.array(["BUILDING", "MACHINERY"] * 20)})
    orders = pa.table({"o_orderkey": pa.array(np.arange(120, dtype=np.int64)),
                       "o_custkey": pa.array(rng.integers(0, 40, 120))})
    lineitem = pa.table({"l_orderkey": pa.array(rng.integers(0, 120, 400)),
                         "l_price": pa.array(rng.random(400))})
    launches = []
    phase1 = tpu_join.TpuShuffledHashJoinExec._phase1

    def counting_phase1(self):
        kernel = phase1(self)

        def launch(*args, **kw):
            launches.append(1)
            return kernel(*args, **kw)
        return launch

    monkeypatch.setattr(tpu_join.TpuShuffledHashJoinExec, "_phase1", counting_phase1)
    s = TpuSession({"spark.rapids.sql.enabled": True, "spark.rapids.sql.test.enabled": True,
                    "spark.sql.autoBroadcastJoinThreshold": "-1"})
    before = _pass_counters()
    rows = (
        s.create_dataframe(customer).filter(col("c_mktsegment") == "BUILDING")
        .join(s.create_dataframe(orders, num_partitions=2), on=[("c_custkey", "o_custkey")])
        .join(s.create_dataframe(lineitem, num_partitions=2), on=[("o_orderkey", "l_orderkey")])
        .select("o_orderkey", "l_price").collect()
    )
    after = _pass_counters()
    assert len(rows) > 0 and len(launches) >= 2
    assert (after[0] - before[0], after[1] - before[1]) == (3 * len(launches),
                                                             16 * len(launches))
