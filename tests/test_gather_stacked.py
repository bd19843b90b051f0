"""Planes that share an index are stacked and gathered once (ops/gather.py
``gather_planes``): the same bits as a gather a plane, in as few gathers as the
planes make stacks. Small capacities throughout."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import kernels as K
from spark_rapids_tpu.columnar.device import DeviceBatch, DeviceColumn
from spark_rapids_tpu.exec import tpu_join
from spark_rapids_tpu.expr.base import BoundReference
from spark_rapids_tpu.expr.predicates import EqualTo, Not
from spark_rapids_tpu.obs import metrics as obs_metrics
from spark_rapids_tpu.ops import aggregate as agg
from spark_rapids_tpu.ops import gather as G
from spark_rapids_tpu.ops import join as J
from spark_rapids_tpu.types import (
    BOOLEAN,
    BYTE,
    DATE,
    DOUBLE,
    FLOAT,
    INT,
    LONG,
    SHORT,
    STRING,
    TIMESTAMP,
    ArrayType,
    DecimalType,
    Schema,
    StructField,
)

from test_sortkeys_packed import (
    Q1_KEYS,
    Q67_BENCH_KEYS,
    Q67_KEYS,
    _assert_trees_bit_equal,
    _batch,
    _grouped_rows,
    _key_columns,
    _valid,
    fixed_column,
    string_column,
)

CAP = 64


# ── the parent's gather, a plane at a time ───────────────────────────────
def per_plane_gather_column(col, idx, idx_valid=None):
    """``ops/gather.py::gather_column`` as it was before the stacking."""
    data = col.data[idx] if col.data is not None else None
    validity = col.validity[idx]
    if idx_valid is not None:
        validity = validity & idx_valid
    lengths = col.lengths[idx] if col.lengths is not None else None
    children = None
    if col.children is not None:
        children = tuple(per_plane_gather_column(c, idx) for c in col.children)
    return DeviceColumn(col.dtype, data, validity, lengths, children)


def per_plane_gather_planes(planes, idx):
    return [None if p is None else p[idx] for p in planes]


def _per_plane(monkeypatch):
    """Every gather site back to a gather a plane: what the parent ran."""
    for mod in (G, agg, J):
        monkeypatch.setattr(mod, "gather_planes", per_plane_gather_planes)


def _np_tree(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


def list_of_string_column(rng, cap=CAP, elems=4, width=16, nulls="some"):
    """An array<string> column: its planes are the children's."""
    lengths = rng.integers(0, width + 1, (cap, elems))
    data = rng.integers(1, 255, (cap, elems, width)).astype(np.uint8)
    data[np.arange(width)[None, None, :] >= lengths[:, :, None]] = 0
    counts = rng.integers(0, elems + 1, cap).astype(np.int32)
    elem_live = np.arange(elems)[None, :] < counts[:, None]
    elem = DeviceColumn(
        STRING,
        jnp.asarray(data),
        jnp.asarray(elem_live & (rng.random((cap, elems)) > 0.2)),
        jnp.asarray(lengths.astype(np.int32)),
    )
    return DeviceColumn(
        ArrayType(STRING), None, jnp.asarray(_nulls(rng, cap, nulls)),
        jnp.asarray(counts), (elem,),
    )


def _nulls(rng, cap, nulls):
    return {"none": np.ones(cap, bool), "all": np.zeros(cap, bool)}.get(
        nulls, rng.random(cap) >= 0.3
    )


def _with_nulls(col, valid):
    return DeviceColumn(col.dtype, col.data, jnp.asarray(valid), col.lengths, col.children)


COLUMN_TYPES = {
    "bool": BOOLEAN, "int8": BYTE, "int16": SHORT, "int32": INT, "int64": LONG,
    "float32": FLOAT, "float64": DOUBLE, "date": DATE, "timestamp": TIMESTAMP,
    "decimal": DecimalType(12, 2), "string1": 1, "string16": 16, "string32": 32,
    "list_of_string": "list",
}


def _column(rng, kind, nulls="some", cap=CAP):
    dt = COLUMN_TYPES[kind]
    if dt == "list":
        return list_of_string_column(rng, cap, nulls=nulls)
    col = string_column(rng, dt, cap) if isinstance(dt, int) else fixed_column(rng, dt, cap)
    return _with_nulls(col, _nulls(rng, cap, nulls))


def _indices(rng, cap=CAP):
    return {
        "permutation": rng.permutation(cap),
        "repeated": rng.integers(0, cap, cap),
        "shorter": rng.integers(0, cap, cap // 4),
        "longer": rng.integers(0, cap, 2 * cap),  # the join's pair capacity
        "identity": np.arange(cap),
    }


@pytest.mark.parametrize("nulls", ["none", "some", "all"])
@pytest.mark.parametrize("kind", sorted(COLUMN_TYPES))
def test_gather_column_same_bits_as_per_plane(kind, nulls):
    rng = np.random.default_rng(sorted(COLUMN_TYPES).index(kind))
    col = _column(rng, kind, nulls)
    for name, idx in _indices(rng).items():
        idx = jnp.asarray(idx.astype(np.int32))
        for idx_valid in (None, jnp.asarray(rng.random(idx.shape[0]) < 0.7)):
            got = jax.jit(G.gather_column)(col, idx, idx_valid)
            want = per_plane_gather_column(col, idx, idx_valid)
            _assert_trees_bit_equal(_np_tree(got), _np_tree(want))


def _every_type_batch(rng, nulls="some", cap=CAP):
    """Every column type in one batch: strings of widths 1, 16 and 32 and a
    list of strings among them."""
    cols = [_column(rng, kind, nulls, cap) for kind in sorted(COLUMN_TYPES)]
    return _batch(cols, cap - 5)


@pytest.mark.parametrize("index", ["permutation", "repeated", "shorter", "longer", "identity"])
@pytest.mark.parametrize("nulls", ["none", "some", "all"])
def test_gather_batch_same_bits_as_per_plane(nulls, index):
    rng = np.random.default_rng(7)
    batch = _every_type_batch(rng, nulls)
    idx = jnp.asarray(_indices(rng)[index].astype(np.int32))
    with G.counting_gathers() as count:
        got = jax.jit(lambda b, i: G.gather_batch(b, i, b.num_rows))(batch, idx)
    want = [per_plane_gather_column(c, idx) for c in batch.columns]
    _assert_trees_bit_equal(_np_tree(got.columns), _np_tree(want))
    planes = len(jax.tree_util.tree_leaves(batch.columns))
    # 14 columns, 34 planes: one stack of the 4-byte planes with the 64-bit
    # integers as halves and the bool planes as bits, one each of int8, int16
    # and float64, one of every byte plane (three strings' and the list's
    # elements') and one each of the list elements' validity and lengths
    assert count == [planes, 7] and planes == 34


def test_one_plane_is_gathered_as_it_is():
    """A stack of one plane is the plane: the program holds one gather of the
    plane itself and no concatenate."""
    x = jnp.arange(CAP, dtype=jnp.float32)
    idx = jnp.arange(CAP, dtype=jnp.int32)[::-1]
    text = jax.jit(lambda p, i: G.gather_planes([p], i)).lower(x, idx).as_text()
    assert _gathers(text) == 1 and "concatenate" not in text and "bitcast" not in text
    flag = x > 3
    text = jax.jit(lambda p, i: G.gather_planes([p], i)).lower(flag, idx).as_text()
    assert _gathers(text) == 1 and "concatenate" not in text and "shift" not in text


def test_none_and_repeats():
    x = jnp.arange(CAP, dtype=jnp.int32)
    flag = x % 3 == 0
    idx = jnp.asarray([5, 5, 0, 63], jnp.int32)
    with G.counting_gathers() as count:
        out = G.gather_planes([x, None, flag, x, flag], idx)
    assert out[1] is None and out[3] is out[0] and out[4] is out[2]
    np.testing.assert_array_equal(out[0], [5, 5, 0, 63])
    np.testing.assert_array_equal(out[2], [False, False, True, True])
    assert count == [2, 1]
    assert G.gather_planes([], idx) == [] and G.gather_planes([None], idx) == [None]


def test_many_validity_planes_take_more_than_one_word():
    rng = np.random.default_rng(40)
    flags = [jnp.asarray(rng.random(CAP) < 0.5) for _ in range(40)]
    idx = jnp.asarray(rng.integers(0, CAP, CAP).astype(np.int32))
    with G.counting_gathers() as count:
        got = jax.jit(G.gather_planes)(flags, idx)
    assert count == [40, 1]  # two words, one stack
    for g, f in zip(got, flags):
        assert g.dtype == jnp.bool_
        np.testing.assert_array_equal(g, np.asarray(f)[np.asarray(idx)])


def test_matrix_index():
    """``_group_collect`` gathers through an ``[groups, width]`` index."""
    rng = np.random.default_rng(5)
    col = string_column(rng, 8)
    idx = jnp.asarray(rng.integers(0, CAP, (CAP, 4)).astype(np.int32))
    got = jax.jit(G.gather_planes)([col.data, col.lengths, col.validity], idx)
    want = per_plane_gather_planes([col.data, col.lengths, col.validity], idx)
    _assert_trees_bit_equal(_np_tree(got), _np_tree(want))


def test_a_stack_is_bounded_in_bytes(monkeypatch):
    rng = np.random.default_rng(6)
    planes = [jnp.asarray(rng.integers(0, 99, CAP).astype(np.int32)) for _ in range(10)]
    strings = [string_column(rng, 16).data for _ in range(3)]
    idx = jnp.asarray(rng.permutation(CAP).astype(np.int32))
    monkeypatch.setattr(G, "_STACK_BYTES", 16 * CAP)  # four 4-byte planes, one string
    with G.counting_gathers() as count:
        got = G.gather_planes(planes + strings, idx)
    assert count == [13, 3 + 3]
    _assert_trees_bit_equal(_np_tree(got), _np_tree(per_plane_gather_planes(planes + strings, idx)))


def test_a_short_index_stacks_nothing():
    """Stacking copies every row: where the index is far shorter than the
    rows, every plane is gathered alone (validity still as bits)."""
    rows = 4 * G._ROWS_PER_INDEX
    x = [jnp.arange(rows, dtype=jnp.int32) * k for k in (1, 2, 3)]
    x.append(jnp.arange(rows, dtype=jnp.int64) << 33)
    flags = [x[0] % 2 == 0, x[0] % 3 == 0]
    for n, launches in ((3, 5), (4, 1)):
        idx = jnp.arange(n, dtype=jnp.int32) * 7
        with G.counting_gathers() as count:
            got = G.gather_planes(x + flags, idx)
        assert count == [6, launches]
        _assert_trees_bit_equal(_np_tree(got), _np_tree(per_plane_gather_planes(x + flags, idx)))


# ── the callers: the parent's outputs, bit for bit ───────────────────────
def _group_aggregate(batch, nkeys, agg_cols, ops, **kw):
    fn = jax.jit(lambda b, a: agg.group_aggregate(b, list(range(nkeys)), a, ops, **kw))
    return _np_tree(fn(batch, agg_cols))


def _q1_shaped(rng, cap=256, n=200):
    keys = _grouped_rows(rng, Q1_KEYS, cap, n, distinct=4)
    ops = ["sum", "sum", "sum", "sum", "count", "min", "max", "first", "last",
           "sum", "count"]
    aggs = [DeviceColumn(DOUBLE, jnp.asarray(rng.standard_normal(cap) * 1e3),
                         jnp.asarray(_valid(rng, cap, 0.1))) for _ in ops[:-2]]
    aggs[5].data.at[:3].set(jnp.nan)
    aggs += [aggs[0], aggs[0]]  # avg: one column feeds two aggregates
    return _batch(keys, n), aggs, ops


@pytest.mark.parametrize("shape", ["q1", "q67", "strings"])
def test_group_aggregate_same_as_per_plane(monkeypatch, shape):
    rng = np.random.default_rng(33)
    kw = {}
    if shape == "q1":
        batch, aggs, ops = _q1_shaped(rng)
        kw = dict(live_mask=jnp.asarray(rng.random(256) >= 0.2) & batch.row_mask())
    elif shape == "q67":
        keys = _grouped_rows(rng, Q67_KEYS, 256, 230, distinct=40)
        batch, ops = _batch(keys, 230), ["sum"]
        aggs = [DeviceColumn(DOUBLE, jnp.asarray(rng.standard_normal(256) * 1e4),
                             jnp.asarray(_valid(rng, 256, 0.1)))]
    else:  # string min/max/first and a float32 min with NaNs
        keys = _grouped_rows(rng, [8, INT], 128, 100, distinct=7)
        batch = _batch(keys, 100)
        s = string_column(rng, 16, 128)
        f = fixed_column(rng, FLOAT, 128)
        aggs, ops = [s, s, s, f, f, f], ["min", "max", "first", "min", "max", "count"]
    got = _group_aggregate(batch, len(batch.columns), aggs, ops, **kw)
    _per_plane(monkeypatch)
    want = _group_aggregate(batch, len(batch.columns), aggs, ops, **kw)
    _assert_trees_bit_equal(got, want)
    assert int(got[2]) >= 2


@pytest.mark.parametrize("grouped", [True, False], ids=["grouped", "ungrouped"])
@pytest.mark.parametrize("op", ["collect_set", "collect_list"])
@pytest.mark.parametrize("strings", [True, False], ids=["strings", "ints"])
def test_collect_same_as_per_plane(monkeypatch, op, grouped, strings):
    rng = np.random.default_rng(11)
    cap, n = 64, 50
    keys = _grouped_rows(rng, [8, INT], cap, n, distinct=5) if grouped else []
    vals = string_column(rng, 8, cap, alphabet=2) if strings else DeviceColumn(
        INT, jnp.asarray(rng.integers(0, 4, cap).astype(np.int32)),
        jnp.asarray(_valid(rng, cap, 0.2)))
    batch = _batch(keys or [vals], n)
    nkeys = len(keys)
    got = _group_aggregate(batch, nkeys, [vals], [op], collect_width=16)
    _per_plane(monkeypatch)
    want = _group_aggregate(batch, nkeys, [vals], [op], collect_width=16)
    _assert_trees_bit_equal(got, want)


def test_compact_and_partition_slices_same_as_per_plane(monkeypatch):
    rng = np.random.default_rng(9)
    batch = _every_type_batch(rng)
    keep = jnp.asarray(rng.random(CAP) < 0.6)
    pids = jnp.asarray(rng.integers(0, 3, CAP).astype(np.int32))

    def run():
        return _np_tree((
            jax.jit(G.compact)(batch, keep),
            jax.jit(lambda b, p, k: G.partition_slices(b, p, 3, k))(batch, pids, keep),
        ))

    got = run()
    _per_plane(monkeypatch)
    _assert_trees_bit_equal(got, run())


def _pair_sides(rng, nb=64, npr=32):
    """q95's shape: four int64 columns a side, keyed on the first, a few rows a key."""
    def side(cap, n):
        cols = [DeviceColumn(LONG, jnp.asarray(rng.integers(0, 6, cap)),
                             jnp.asarray(_valid(rng, cap, 0.1))) for _ in range(4)]
        return _batch(cols, n)
    return side(nb, nb - 4), side(npr, npr - 3)


@pytest.mark.parametrize("jt", ["inner", "left_semi", "left_anti"])
@pytest.mark.parametrize("residual", [False, True], ids=["equi", "residual"])
def test_join_pairs_same_as_per_plane(monkeypatch, residual, jt):
    rng = np.random.default_rng(95)
    build, probe = _pair_sides(rng)
    nl = len(probe.columns)
    # ws1.warehouse <> ws2.warehouse, over the pair schema (probe, then build)
    res = Not(EqualTo(BoundReference(1, LONG), BoundReference(nl + 1, LONG))) if residual else None
    right_ords = (1, 3)
    fields = list(probe.schema.fields) if jt != "inner" else (
        list(probe.schema.fields)
        + [StructField(f"r{i}", LONG, True) for i in right_ords])
    phase1 = jax.jit(tpu_join._make_phase1((BoundReference(0, LONG),), (BoundReference(0, LONG),)))

    def run():
        order, lower, counts = phase1(build, probe)
        out_cap = jnp.zeros(2 * 256, jnp.int8)  # pair slots: twice the matches' bucket
        phase2 = jax.jit(tpu_join._make_phase2(Schema(fields), right_ords, jt, res))
        return _np_tree(phase2(build, probe, order, lower, counts, out_cap))

    got = run()
    assert int(got[0].num_rows) > 0
    _per_plane(monkeypatch)
    _assert_trees_bit_equal(got, run())


def test_join_bounds_same_as_with_the_flags_gathered():
    """``join_bounds`` tells the sides apart by the gathered source row alone."""
    rng = np.random.default_rng(4)
    build, probe = _pair_sides(rng)
    order, lower, upper = J.join_bounds(
        [build.columns[0]], build.row_mask(), [probe.columns[0]], probe.row_mask())
    bk = np.where(np.asarray(build.columns[0].validity & build.row_mask()),
                  np.asarray(build.columns[0].data), -1)
    pk = np.asarray(probe.columns[0].data)
    ok = np.asarray(probe.columns[0].validity & probe.row_mask())
    want = np.array([(bk == k).sum() if v else 0 for k, v in zip(pk, ok)])
    np.testing.assert_array_equal(np.asarray(upper - lower), want)
    assert sorted(np.asarray(order)) == list(range(build.capacity))


@pytest.mark.parametrize("what", ["window", "join", "join_residual", "cross_semi", "explode"])
def test_queries_same_as_per_plane(monkeypatch, what):
    """``_window``, the joins and generate through a session: the rows of the
    stacked kernels are the rows of the per-plane ones."""
    from spark_rapids_tpu import TpuSession
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.functions import col
    from spark_rapids_tpu.window import Window

    rng = np.random.default_rng(21)
    n = 200
    t = pa.table({
        "k": pa.array(rng.integers(0, 9, n).astype(np.int64)),
        "ts": pa.array(rng.integers(0, 30, n).astype(np.int64)),
        "v": pa.array(rng.integers(-50, 50, n).astype(np.int64), mask=rng.random(n) < 0.1),
        "f": pa.array(np.where(rng.random(n) < 0.05, np.nan, rng.random(n))),
        "s": pa.array([f"s{int(x)}" for x in rng.integers(0, 25, n)]),
        "a": pa.array([[f"e{j}" for j in range(int(c))] for c in rng.integers(0, 4, n)]),
    })
    u = pa.table({"k2": pa.array(np.arange(12).astype(np.int64) % 10),
                  "w": pa.array(rng.integers(0, 30, 12).astype(np.int64)),
                  "name": pa.array([f"n{i}" for i in range(12)])})

    def query(s):
        df, du = s.create_dataframe(t, num_partitions=2), s.create_dataframe(u)
        if what == "window":
            w = Window.partition_by("k").order_by("ts", "s")
            return df.drop("a").with_column("r", F.rank().over(w)).with_column(
                "rs", F.sum(col("v")).over(w))
        if what == "join":
            return df.drop("a").join(du, on=[("k", "k2")], how="left")
        if what == "join_residual":
            return df.drop("a").join(du, (col("k") == col("k2")) & (col("ts") != col("w")), "inner")
        if what == "cross_semi":
            return df.drop("a").join(du, col("ts") < col("w"), "left_semi")
        return df.select("k", "s", F.explode(col("a")).alias("e"))

    def rows():
        K.clear()
        s = TpuSession({"spark.rapids.sql.enabled": True, "spark.rapids.sql.test.enabled": True})
        return sorted(map(repr, query(s).collect()))

    got = rows()
    _per_plane(monkeypatch)
    want = rows()
    K.clear()
    assert got == want and len(got) > 20


# ── how many gathers a program holds ─────────────────────────────────────
def _gathers(text: str) -> int:
    return len(re.findall(r"#stablehlo\.gather<", text))  # one attribute an op


def _zero_batch(spec, cap):
    cols = _key_columns(spec, cap)
    return _batch(cols, cap)


def _q1_aggregate():
    """q1's partial aggregate: two one-character keys, seven sums of doubles
    (three of them an avg's, whose counts follow) and count(*)."""
    cap = 1 << 10
    keys = _key_columns(Q1_KEYS, cap)
    d = [DeviceColumn(DOUBLE, jnp.zeros(cap), jnp.zeros(cap, bool)) for _ in range(6)]
    aggs = [d[0], d[1], d[2], d[3], d[0], d[0], d[1], d[1], d[4], d[4], d[5]]
    ops = ["sum", "sum", "sum", "sum", "sum", "count", "sum", "count", "sum", "count", "count"]
    fn = lambda b, a: agg.group_aggregate(b, [0, 1], a, ops)
    return fn, (_batch(keys, cap), aggs)


def _q1_sort():
    """q1's sort: the aggregate's ten output columns by the two keys."""
    from spark_rapids_tpu.exec.tpu import device_sort_fn
    from spark_rapids_tpu.plan.logical import SortOrder

    cap = 1 << 10
    batch = _zero_batch(Q1_KEYS + [DOUBLE] * 7 + [LONG], cap)
    order = [SortOrder(BoundReference(i, STRING), True) for i in (0, 1)]
    K.clear()  # a kernel built before holds the program it traced then
    return device_sort_fn(order)._fn, (batch,)


def _q67_aggregate():
    cap = 1 << 10
    keys = _key_columns(Q67_BENCH_KEYS, cap)
    sales = DeviceColumn(DOUBLE, jnp.zeros(cap), jnp.zeros(cap, bool))
    fn = lambda b, a: agg.group_aggregate(b, list(range(len(keys))), a, ["sum"])
    return fn, (_batch(keys, cap), [sales])


def _q95_join_pairs():
    """q95's self-join: four int64 columns a side, ws1.warehouse <> ws2.warehouse,
    two of the build side's columns out."""
    cap = 1 << 10
    side = _zero_batch([LONG] * 4, cap)
    res = Not(EqualTo(BoundReference(1, LONG), BoundReference(5, LONG)))
    out = Schema(list(side.schema.fields) + [StructField(f"r{i}", LONG, True) for i in (0, 1)])
    fn = tpu_join._make_phase2(out, (0, 1), "inner", res)
    i32 = jnp.zeros(cap, jnp.int32)
    return fn, (side, side, i32, i32, i32, jnp.zeros(2 * cap, jnp.int8))


#: gathers in the lowered program: as the parent (eba9adf) lowers it, and the
#: ceiling held here (what it lowers to now)
GATHER_CEILINGS = {
    "q1_aggregate": (_q1_aggregate, 45, 9),
    "q1_sort": (_q1_sort, 23, 4),
    "q67_aggregate": (_q67_aggregate, 30, 9),
    "q95_join_pairs": (_q95_join_pairs, 27, 5),
}


@pytest.mark.parametrize("name", sorted(GATHER_CEILINGS))
def test_gathers_in_the_lowered_program(monkeypatch, name):
    make, parent, ceiling = GATHER_CEILINGS[name]
    fn, args = make()
    stacked = _gathers(jax.jit(fn).lower(*args).as_text())
    assert stacked <= ceiling < parent
    _per_plane(monkeypatch)
    fn, args = make()
    assert _gathers(jax.jit(fn).lower(*args).as_text()) >= 2 * stacked


# ── the counters ─────────────────────────────────────────────────────────
def _gather_counters():
    snap = dict(obs_metrics.GLOBAL.snapshot())
    return snap.get("gather.planes", 0), snap.get("gather.launches", 0)


def test_gathers_counted_per_launch():
    rng = np.random.default_rng(3)
    batch = _every_type_batch(rng)
    idx = jnp.asarray(rng.permutation(CAP).astype(np.int32))
    kernel = K.counted_kernel(
        ("test_gathers_counted_per_launch",),
        lambda: lambda b, i: G.gather_batch(b, i, b.num_rows),
    )
    before = _gather_counters()
    kernel(batch, idx)
    kernel(batch, idx)
    after = _gather_counters()
    assert (after[0] - before[0], after[1] - before[1]) == (68, 14)
    # traced abstractly once, then a lookup and the adds a launch
    assert list(kernel._on_launch._gathers.values()) == [(34, 7)]


def test_gathers_counted_in_q1():
    from spark_rapids_tpu import TpuSession
    from spark_rapids_tpu.functions import avg, col, count, sum as sum_

    rng = np.random.default_rng(1)
    n = 500
    t = pa.table({
        "l_returnflag": pa.array(rng.choice(["A", "N", "R"], n)),
        "l_linestatus": pa.array(rng.choice(["F", "O"], n)),
        "l_quantity": rng.integers(1, 50, n).astype(np.float64),
        "l_extendedprice": rng.random(n) * 1e4,
        "l_discount": rng.integers(0, 10, n) / 100.0,
        "l_tax": rng.integers(0, 8, n) / 100.0,
    })
    s = TpuSession({"spark.rapids.sql.enabled": True, "spark.rapids.sql.test.enabled": True})
    disc = col("l_extendedprice") * (1 - col("l_discount"))
    before = _gather_counters()
    rows = (
        s.create_dataframe(t).group_by("l_returnflag", "l_linestatus").agg(
            sum_(col("l_quantity")).alias("sum_qty"),
            sum_(col("l_extendedprice")).alias("sum_base_price"),
            sum_(disc).alias("sum_disc_price"),
            sum_(disc * (1 + col("l_tax"))).alias("sum_charge"),
            avg(col("l_quantity")).alias("avg_qty"),
            avg(col("l_extendedprice")).alias("avg_price"),
            avg(col("l_discount")).alias("avg_disc"),
            count(col("l_quantity")).alias("count_order"),
        ).sort("l_returnflag", "l_linestatus").collect()
    )
    after = _gather_counters()
    assert len(rows) == 6
    planes, launches = after[0] - before[0], after[1] - before[1]
    # the stacking engages: several planes a gather in q1's aggregate and sort
    assert launches > 0 and planes >= 4 * launches
