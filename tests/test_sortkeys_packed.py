"""The sort/group key as one packed bit string (ops/sortkeys.py
``column_key_fields`` / ``packed_key`` / ``packed_sort``): the same
permutation, bit for bit, as two passes a uint64 radix word, in as few passes
as the key has 32-bit words of information. Small capacities throughout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu import kernels as K
from spark_rapids_tpu.columnar.device import DeviceBatch, DeviceColumn
from spark_rapids_tpu.obs import metrics as obs_metrics
from spark_rapids_tpu.ops import aggregate as agg
from spark_rapids_tpu.ops import sortkeys as sk
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
    DecimalType,
    Schema,
    StructField,
)

CAP = 64
DIRECTIONS = [(True, True), (True, False), (False, True), (False, False)]


def _valid(rng, cap, null_fraction=0.25):
    return rng.random(cap) >= null_fraction


def string_column(rng, width, cap=CAP, alphabet=3, null_fraction=0.25):
    """Few distinct bytes (0 among them: interior NULs), every length from 0
    to the plane width, zero beyond the length — and residual bytes under
    some NULLs, which the encoders have to blank."""
    lengths = rng.integers(0, width + 1, cap)
    data = rng.integers(0, alphabet, (cap, width)).astype(np.uint8)
    valid = _valid(rng, cap, null_fraction)
    data[(np.arange(width)[None, :] >= lengths[:, None]) & valid[:, None]] = 0
    lengths = np.where(valid | (rng.random(cap) < 0.5), lengths, 0)
    return DeviceColumn(
        STRING, jnp.asarray(data), jnp.asarray(valid), jnp.asarray(lengths.astype(np.int32))
    )


def strings_column(values, width):
    """A column of the given byte strings (``None`` is NULL)."""
    data = np.zeros((len(values), width), np.uint8)
    lengths = np.zeros(len(values), np.int32)
    for i, v in enumerate(values):
        if v is not None:
            data[i, : len(v)] = np.frombuffer(v, np.uint8)
            lengths[i] = len(v)
    valid = np.array([v is not None for v in values])
    return DeviceColumn(STRING, jnp.asarray(data), jnp.asarray(valid), jnp.asarray(lengths))


def _plant(data, special):
    """The special values at the head of ``data``, then some of them again (ties)."""
    special = special[: len(data)]
    data[: len(special)] = special
    ties = data[len(special): len(special) + 8]
    ties[:] = data[: len(ties)]


def fixed_column(rng, dt, cap=CAP, null_fraction=0.25):
    npdt = dt.np_dtype
    if npdt == np.bool_:
        data = rng.random(cap) < 0.5
    elif npdt.kind == "f":
        data = rng.standard_normal(cap).astype(npdt) * 1e3
        special = [0.0, -0.0, np.nan, -np.nan, np.inf, -np.inf, 1.0, -1.0,
                   np.finfo(npdt).max, np.finfo(npdt).min, np.finfo(npdt).tiny]
        _plant(data, np.array(special, npdt))
    else:
        info = np.iinfo(npdt)
        data = rng.integers(info.min, info.max, cap, endpoint=True).astype(npdt)
        special = [info.min, info.max, 0, -1, 1, info.min + 1, info.max - 1]
        _plant(data, np.array(special, npdt))
    perm = rng.permutation(cap)
    return DeviceColumn(dt, jnp.asarray(data[perm]), jnp.asarray(_valid(rng, cap, null_fraction)))


def radix_words(cols, ascs=None, nfs=None):
    """Every column's uint64 radix words, as the parent's sorts took them."""
    return [
        w
        for i, c in enumerate(cols)
        for w in sk.column_radix_words(
            c, True if ascs is None else ascs[i], True if nfs is None else nfs[i]
        )
    ]


def sort_permutation(words, row_mask, live_first=True):
    """The stable sort by uint64 radix words that the packed key replaced:
    padding rows last (``live_first``), then two 32-bit passes a word."""
    halves = []  # most-significant first
    if live_first:
        halves.append(jnp.where(row_mask, jnp.uint32(0), jnp.uint32(1)))
    for w in words:
        w = w.astype(jnp.uint64)
        halves += [(w >> jnp.uint64(32)).astype(jnp.uint32), w.astype(jnp.uint32)]
    return sk._radix_passes(jnp.stack(halves))


def unpacked_permutation(cols, mask, ascs=None, nfs=None):
    return sort_permutation(radix_words(cols, ascs, nfs), mask)


def assert_same_permutation(cols, mask, ascs=None, nfs=None):
    key = sk.packed_key(cols, mask, ascs, nfs)
    assert key.words.dtype == jnp.uint32
    got = np.asarray(sk.packed_sort(key))
    want = np.asarray(unpacked_permutation(cols, mask, ascs, nfs))
    np.testing.assert_array_equal(got, want)
    return key


KEY_TYPES = {
    "bool": BOOLEAN, "int8": BYTE, "int16": SHORT, "int32": INT, "date": DATE,
    "int64": LONG, "timestamp": TIMESTAMP, "decimal": DecimalType(12, 2),
    "float32": FLOAT, "float64": DOUBLE, "string8": 8, "string16": 16,
}


@pytest.mark.parametrize("asc,nulls_first", DIRECTIONS)
@pytest.mark.parametrize("name", list(KEY_TYPES))
def test_one_column_same_permutation(name, asc, nulls_first):
    rng = np.random.default_rng(sum(map(ord, name)))
    dt = KEY_TYPES[name]
    col = string_column(rng, dt) if isinstance(dt, int) else fixed_column(rng, dt)
    mask = jnp.asarray(rng.random(CAP) >= 0.15)  # padding rows anywhere
    key = assert_same_permutation([col], mask, [asc], [nulls_first])
    # the live flag, the validity bit and the value's bits, and no more
    value_bits = {
        "bool": 1, "int8": 8, "int16": 16, "int32": 32, "date": 32, "int64": 64,
        "timestamp": 64, "decimal": 64, "float32": 32, "float64": 64,
        "string8": 64 + 4, "string16": 128 + 5,
    }[name]
    assert key.column_end_bits == (2 + value_bits,)
    assert key.words.shape[0] == -(-(2 + value_bits) // 32)
    assert key.unpacked_passes == 1 + 2 * len(sk.column_radix_words(col))


@pytest.mark.parametrize("seed", range(6))
def test_mixed_columns_same_permutation(seed):
    rng = np.random.default_rng(100 + seed)
    makers = [
        lambda: string_column(rng, 8, alphabet=2),
        lambda: string_column(rng, 16, alphabet=2),
        lambda: string_column(rng, 32, alphabet=2),
        lambda: fixed_column(rng, BOOLEAN),
        lambda: fixed_column(rng, BYTE),
        lambda: fixed_column(rng, SHORT),
        lambda: fixed_column(rng, INT),
        lambda: fixed_column(rng, LONG),
        lambda: fixed_column(rng, FLOAT),
        lambda: fixed_column(rng, DOUBLE),
    ]
    # low-cardinality leading columns, so that later ones decide ties
    lead = [
        DeviceColumn(BYTE, jnp.asarray(rng.integers(0, 2, CAP).astype(np.int8)),
                     jnp.asarray(_valid(rng, CAP))),
        string_column(rng, 8, alphabet=2, null_fraction=0.1),
    ]
    picks = rng.choice(len(makers), size=4, replace=False)
    cols = lead + [makers[i]() for i in picks]
    ascs = [bool(b) for b in rng.integers(0, 2, len(cols))]
    nfs = [bool(b) for b in rng.integers(0, 2, len(cols))]
    mask = jnp.asarray(rng.random(CAP) >= 0.1)
    assert_same_permutation(cols, mask, ascs, nfs)


@pytest.mark.parametrize("asc,nulls_first", DIRECTIONS)
def test_string_ties_and_nuls(asc, nulls_first):
    values = [
        b"ab", b"ab\x00", b"a\x00b", b"a", b"", None, b"ab\x00\x00", b"\x00",
        b"\x00\x00", b"b", b"ab", None, b"abcdefgh", b"abcdefg", b"abcdefg\x00", b"",
    ]
    col = strings_column(values, 8)
    # residual bytes and a length under the NULLs: they must still tie, and
    # sort apart from the valid empty strings
    data = np.asarray(col.data).copy()
    lengths = np.asarray(col.lengths).copy()
    data[5, :3], lengths[5] = (9, 9, 9), 3
    col = DeviceColumn(STRING, jnp.asarray(data), col.validity, jnp.asarray(lengths))
    mask = jnp.ones(len(values), bool)
    assert_same_permutation([col], mask, [asc], [nulls_first])
    perm = np.asarray(sk.packed_sort(sk.packed_key([col], mask, [asc], [nulls_first])))
    ordered = [values[i] for i in perm]
    nulls = [v for v in ordered if v is None]
    rest = sorted((v for v in values if v is not None), reverse=not asc)
    assert ordered == (nulls + rest if nulls_first else rest + nulls)
    # stability: equal keys keep their input order
    for a, b in zip(perm[:-1], perm[1:]):
        if values[a] == values[b]:
            assert a < b


@pytest.mark.parametrize("dt", [FLOAT, DOUBLE], ids=str)
def test_float_order_is_sparks(dt):
    nan = float("nan")
    vals = np.array([1.5, nan, -0.0, 0.0, np.inf, -np.inf, -nan, -2.0, 0.0, nan], dt.np_dtype)
    col = DeviceColumn(dt, jnp.asarray(vals), jnp.ones(len(vals), bool))
    mask = jnp.ones(len(vals), bool)
    assert_same_permutation([col], mask)
    perm = np.asarray(sk.packed_sort(sk.packed_key([col], mask)))
    # -inf, -2, the three zeros in input order, 1.5, inf, the three NaNs in input order
    np.testing.assert_array_equal(perm, [5, 7, 2, 3, 8, 0, 4, 1, 6, 9])
    words = list(np.asarray(sk.packed_key([col], mask).words))
    for a, b in [(2, 3), (3, 8), (1, 6), (6, 9)]:  # -0 == 0, NaN == NaN: equal bits
        assert all(w[a] == w[b] for w in words)


def test_padding_rows_sort_last_in_input_order():
    rng = np.random.default_rng(7)
    col = fixed_column(rng, INT, null_fraction=0.0)
    mask = np.asarray(rng.random(CAP) >= 0.4)
    # as the kernels hand it over: a padding row is a NULL
    col = DeviceColumn(INT, col.data, jnp.asarray(mask))
    perm = np.asarray(sk.packed_sort(sk.packed_key([col], jnp.asarray(mask))))
    n = int(mask.sum())
    assert mask[perm[:n]].all() and not mask[perm[n:]].any()
    np.testing.assert_array_equal(perm[n:], np.flatnonzero(~mask))
    assert_same_permutation([col], jnp.asarray(mask))


def test_segment_starts_on_packed_words():
    rng = np.random.default_rng(8)
    cols = [string_column(rng, 8, alphabet=2), fixed_column(rng, BOOLEAN),
            string_column(rng, 16, alphabet=2, null_fraction=0.5)]
    cols[2] = DeviceColumn(STRING, cols[2].data[:, :16] * 0 + cols[2].data[:, :1],
                           cols[2].validity, jnp.minimum(cols[2].lengths, 1))
    mask = jnp.arange(CAP) < 50
    cols = [DeviceColumn(c.dtype, c.data, c.validity & mask, c.lengths) for c in cols]
    words = radix_words(cols)
    perm = sort_permutation(words, mask)
    key = sk.packed_key(cols, mask)
    live = jnp.arange(CAP) < 50
    want = np.asarray(sk.segment_starts([w[perm] for w in words], live))
    s_words = key.sorted_words(perm)
    np.testing.assert_array_equal(np.asarray(sk.segment_starts(s_words, live)), want)
    assert 1 < want.sum() < 50  # some groups hold several rows
    # a prefix of the columns: the groups of the first two alone
    two = radix_words(cols[:2])
    want2 = np.asarray(sk.segment_starts([w[perm] for w in two], live))
    got2 = np.asarray(sk.segment_starts(key.prefix(s_words, 2), live))
    np.testing.assert_array_equal(got2, want2)
    assert want2.sum() < want.sum()


def test_pack_fields_splits_across_words():
    a = (jnp.asarray([0x1, 0x0], jnp.uint32), 1)
    b = (jnp.asarray([0xABCDE, 0x12345], jnp.uint32), 20)
    c = (jnp.asarray([0xFFFFFFFF, 0x80000001], jnp.uint32), 32)
    d = (jnp.asarray([0x5, 0x2], jnp.uint32), 3)
    words = [np.asarray(w) for w in sk.pack_fields([a, b, c, d])]
    assert len(words) == 2  # 56 bits
    for row, vals in enumerate([(1, 0xABCDE, 0xFFFFFFFF, 5), (0, 0x12345, 0x80000001, 2)]):
        bits = (vals[0] << 55) | (vals[1] << 35) | (vals[2] << 3) | vals[3]
        bits <<= 8  # the spare low bits of the last word are zero
        assert (int(words[0][row]), int(words[1][row])) == (bits >> 32, bits & 0xFFFFFFFF)


def _key_columns(spec, cap=8):
    """Empty columns of a key schema: string plane widths and fixed dtypes."""
    out = []
    for s in spec:
        if isinstance(s, int):
            out.append(DeviceColumn(STRING, jnp.zeros((cap, s), jnp.uint8),
                                    jnp.zeros(cap, bool), jnp.zeros(cap, jnp.int32)))
        else:
            out.append(DeviceColumn(s, jnp.zeros(cap, s.np_dtype), jnp.zeros(cap, bool)))
    return out


#: q1 groups by l_returnflag, l_linestatus (one character each: planes of 8);
#: q67's rollup by i_category, i_class, i_brand, i_product_name (planes 16, 16,
#: 16, 32), d_year, d_qoy, d_moy, s_store_id (16) and the grouping id
Q1_KEYS = [8, 8]
Q67_KEYS = [16, 16, 16, 32, INT, INT, INT, 16, INT]
#: as the benchmark's generator writes date_dim: d_year, d_qoy, d_moy are int64
Q67_BENCH_KEYS = [16, 32, 16, 16, LONG, LONG, LONG, 16, INT]


@pytest.mark.parametrize(
    "spec,bits,passes,unpacked",
    [(Q1_KEYS, 139, 5, 13), (Q67_KEYS, 932, 30, 53), (Q67_BENCH_KEYS, 1028, 33, 59)],
    ids=["q1", "q67", "q67_int64_dates"],
)
def test_static_pass_counts(spec, bits, passes, unpacked):
    cols = _key_columns(spec)
    fields = [f for c in cols for f in sk.column_key_fields(c)]
    assert 1 + sum(b for _, b in fields) == bits
    key = sk.packed_key(cols, jnp.zeros(8, bool))
    assert key.words.shape[0] == passes == -(-bits // 32)
    assert key.unpacked_passes == unpacked == 1 + 2 * len(radix_words(cols))
    with sk.counting_passes() as count:
        jax.eval_shape(lambda ws: sk.packed_sort(key._replace(words=ws)), key.words)
    assert count == [passes, unpacked]


# ── group_aggregate: the same answers as over the unpacked key ───────────
def _unpacked_key(columns, row_mask, ascendings=None, nulls_firsts=None):
    """The parent's key in ``packed_key``'s clothes: the live flag and both
    halves of every uint64 radix word, one pass each."""
    halves = [jnp.where(row_mask, jnp.uint32(0), jnp.uint32(1))]
    ends = []
    for i, c in enumerate(columns):
        nf = True if nulls_firsts is None else nulls_firsts[i]
        for w in sk.column_radix_words(c, True, nf):
            halves += [(w >> jnp.uint64(32)).astype(jnp.uint32), w.astype(jnp.uint32)]
        ends.append(32 * len(halves))
    return sk.PackedKey(jnp.stack(halves), tuple(ends), len(halves))


def _batch(cols, n):
    schema = Schema([StructField(f"c{i}", c.dtype, True) for i, c in enumerate(cols)])
    return DeviceBatch(schema, list(cols), jnp.asarray(n, jnp.int32))


def _run_group_aggregate(monkeypatch, unpacked, batch, nkeys, agg_cols, ops, **kw):
    if unpacked:
        monkeypatch.setattr(agg, "packed_key", _unpacked_key)
    fn = jax.jit(lambda b, a: agg.group_aggregate(b, list(range(nkeys)), a, ops, **kw))
    out = fn(batch, agg_cols)
    monkeypatch.undo()
    return jax.tree_util.tree_map(np.asarray, out)


def _assert_trees_bit_equal(got, want):
    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


def _grouped_rows(rng, spec, cap, n, distinct):
    """Key columns over ``distinct`` combinations, live rows first."""
    pool = [string_column(rng, s, distinct, alphabet=4, null_fraction=0.1)
            if isinstance(s, int) else fixed_column(rng, s, distinct, null_fraction=0.1)
            for s in spec]
    pick = jnp.asarray(rng.integers(0, distinct, cap))
    live = jnp.arange(cap) < n
    cols = []
    for c in pool:
        lengths = None if c.lengths is None else c.lengths[pick]
        cols.append(DeviceColumn(c.dtype, c.data[pick], c.validity[pick] & live, lengths))
    return cols


def test_group_aggregate_q67_shaped(monkeypatch):
    rng = np.random.default_rng(67)
    cap, n = 256, 230
    keys = _grouped_rows(rng, Q67_KEYS, cap, n, distinct=40)
    sales = DeviceColumn(DOUBLE, jnp.asarray(rng.standard_normal(cap) * 1e4),
                         jnp.asarray(_valid(rng, cap, 0.1)))
    batch = _batch(keys, n)
    got = _run_group_aggregate(monkeypatch, False, batch, len(keys), [sales], ["sum"])
    want = _run_group_aggregate(monkeypatch, True, batch, len(keys), [sales], ["sum"])
    _assert_trees_bit_equal(got, want)
    assert 20 <= int(got[2]) <= 40


def test_group_aggregate_q1_shaped(monkeypatch):
    rng = np.random.default_rng(1)
    cap, n = 256, 200
    keys = _grouped_rows(rng, Q1_KEYS, cap, n, distinct=4)
    ops = ["sum", "sum", "sum", "sum", "count", "min", "max", "first"]
    aggs = [DeviceColumn(DOUBLE, jnp.asarray(rng.standard_normal(cap) * 1e3),
                         jnp.asarray(_valid(rng, cap, 0.1))) for _ in ops]
    batch = _batch(keys, n)
    live = jnp.asarray(rng.random(cap) >= 0.2) & batch.row_mask()  # a fused filter
    kw = dict(live_mask=live)
    got = _run_group_aggregate(monkeypatch, False, batch, 2, aggs, ops, **kw)
    want = _run_group_aggregate(monkeypatch, True, batch, 2, aggs, ops, **kw)
    _assert_trees_bit_equal(got, want)
    assert 1 <= int(got[2]) <= 4
    # and against plain numpy: the groups' counts
    ng = int(got[2])
    counted = {}
    kd = [(np.asarray(k.data), np.asarray(k.validity), np.asarray(k.lengths)) for k in keys]
    for i in np.flatnonzero(np.asarray(live)):
        ident = tuple(
            (bytes(d[i, : l[i]]) if v[i] else None) for d, v, l in kd
        )
        counted[ident] = counted.get(ident, 0) + int(np.asarray(aggs[4].validity)[i])
    out_keys, out_aggs = got[0], got[1]
    seen = {}
    for g in range(ng):
        ident = tuple(
            (bytes(k.data[g, : k.lengths[g]]) if k.validity[g] else None) for k in out_keys
        )
        seen[ident] = int(out_aggs[4].data[g])
    assert seen == counted


@pytest.mark.parametrize("op", ["collect_set", "collect_list"])
def test_group_collect_same_as_unpacked(monkeypatch, op):
    rng = np.random.default_rng(11)
    cap, n = 64, 50
    keys = _grouped_rows(rng, [8, INT], cap, n, distinct=5)
    vals = DeviceColumn(INT, jnp.asarray(rng.integers(0, 4, cap).astype(np.int32)),
                        jnp.asarray(_valid(rng, cap, 0.2)))
    batch = _batch(keys, n)
    kw = dict(collect_width=16)
    got = _run_group_aggregate(monkeypatch, False, batch, 2, [vals], [op], **kw)
    want = _run_group_aggregate(monkeypatch, True, batch, 2, [vals], [op], **kw)
    _assert_trees_bit_equal(got, want)


def test_group_max_size_same_as_unpacked(monkeypatch):
    rng = np.random.default_rng(12)
    keys = _grouped_rows(rng, [8, SHORT], 64, 60, distinct=6)
    batch = _batch(keys, 60)
    got = int(agg.group_max_size(batch, [0, 1]))
    monkeypatch.setattr(agg, "packed_key", _unpacked_key)
    assert got == int(agg.group_max_size(batch, [0, 1])) >= 10


# ── the counters ─────────────────────────────────────────────────────────
def _pass_counters():
    snap = dict(obs_metrics.GLOBAL.snapshot())
    return snap.get("sort.keyPasses", 0), snap.get("sort.keyPassesUnpacked", 0)


def test_key_passes_counted_per_launch():
    rng = np.random.default_rng(3)
    keys = _grouped_rows(rng, Q1_KEYS, 32, 30, distinct=3)
    vals = DeviceColumn(DOUBLE, jnp.asarray(rng.standard_normal(32)), jnp.ones(32, bool))
    batch = _batch(keys + [vals], 30)

    def make():
        def _aggregate(b):
            return agg.group_aggregate(_batch(b.columns[:2], b.num_rows), [0, 1],
                                       [b.columns[2]], ["sum"])
        return _aggregate

    kernel = K.counted_kernel(("test_key_passes_counted_per_launch",), make)
    before = _pass_counters()
    kernel(batch)
    kernel(batch)
    after = _pass_counters()
    assert (after[0] - before[0], after[1] - before[1]) == (10, 26)
    # traced abstractly once, then a lookup and two adds a launch
    assert list(kernel._on_launch._passes.values()) == [(5, 13)]


def test_key_passes_counted_in_a_query():
    import pyarrow as pa

    from spark_rapids_tpu import TpuSession
    from spark_rapids_tpu.functions import col, sum as sum_

    t = pa.table({
        "flag": ["A", "N", "R", "N", "A", "R", "N", "N"],
        "status": ["F", "O", "F", "F", "F", "O", "O", "O"],
        "qty": [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0],
    })
    s = TpuSession({"spark.rapids.sql.enabled": True, "spark.rapids.sql.test.enabled": True})
    before = _pass_counters()
    rows = (
        s.create_dataframe(t).group_by("flag", "status").agg(sum_(col("qty")).alias("q"))
        .sort("flag", "status").collect()
    )
    after = _pass_counters()
    assert [tuple(r) for r in rows] == [
        ("A", "F", 6.0), ("N", "F", 4.0), ("N", "O", 17.0), ("R", "F", 3.0), ("R", "O", 6.0)
    ]
    passes, unpacked = after[0] - before[0], after[1] - before[1]
    # every aggregate and sort kernel of the plan keys on two one-character
    # strings: 5 packed passes where two a radix word would be 13
    assert passes > 0 and passes % 5 == 0
    assert unpacked * 5 == passes * 13
