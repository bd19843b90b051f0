"""The pad phase of ``host_to_device`` (columnar/device.py) writes each host
plane once, into the capacity-sized array that ships. Its planes must be the
planes of the pad it replaced, bit for bit: that pad (PR 30's tree) is kept
below as the reference."""
import decimal

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import device as D
from spark_rapids_tpu.columnar.host import fixed_np, np_from_arrow
from spark_rapids_tpu.obs.metrics import GLOBAL


# ── the reference: the pad as it was, with its [rows, width] int64 index ────


def ref_string_to_padded(arr, width, max_str_bytes=None):
    arr = arr.cast(pa.string())
    n = len(arr)
    valid = ~np.asarray(arr.is_null())
    buf_offsets = np.frombuffer(arr.buffers()[1], dtype=np.int32)[
        arr.offset : arr.offset + n + 1
    ]
    lengths = (buf_offsets[1:] - buf_offsets[:-1]).astype(np.int32)
    lengths = np.where(valid, lengths, 0).astype(np.int32)
    maxlen = int(lengths.max()) if n else 0
    if width is None:
        if max_str_bytes is not None and maxlen > max_str_bytes:
            raise ValueError(
                f"string length {maxlen} exceeds "
                f"spark.rapids.tpu.string.maxBytes={max_str_bytes}"
            )
        width = D.bucket_width(max(maxlen, 1))
    if maxlen > width:
        raise ValueError(f"string length {maxlen} exceeds device width {width}")
    out = np.zeros((n, width), dtype=np.uint8)
    values = (
        np.frombuffer(arr.buffers()[2], dtype=np.uint8)
        if arr.buffers()[2]
        else np.zeros(0, np.uint8)
    )
    starts = buf_offsets[:-1]
    cols = np.arange(width, dtype=np.int64)[None, :]
    idx = starts.astype(np.int64)[:, None] + cols
    take_mask = cols < lengths[:, None]
    idx = np.where(take_mask, idx, 0)
    if values.size:
        gathered = values[np.clip(idx, 0, values.size - 1)]
        out = np.where(take_mask, gathered, 0).astype(np.uint8)
    return out, lengths, valid, width


def ref_np_from_arrow_fixed(arr, dt):
    """host.np_from_arrow's fixed-width part as it was (it was the pad's too)."""
    valid = ~np.asarray(arr.is_null())
    n = len(arr)
    if isinstance(dt, T.DecimalType):
        buf = arr.buffers()[1]
        if buf is None:
            return np.zeros(n, dtype=np.int64), valid
        pairs = np.frombuffer(buf, dtype=np.int64, count=(arr.offset + n) * 2)
        data = pairs.reshape(-1, 2)[arr.offset :, 0]
        return np.where(valid, data, 0), valid
    if pa.types.is_date32(arr.type):
        arr = arr.cast(pa.int32())
    elif pa.types.is_timestamp(arr.type):
        arr = arr.cast(pa.int64())
    data = fixed_np(arr, dt.np_dtype)
    if not valid.all():
        data = np.where(valid, data, np.zeros((), dtype=dt.np_dtype))
    return np.ascontiguousarray(data), valid


def ref_np_col_from_arrow(arr, dt, cap, width=None, max_str_bytes=None):
    """Flat (string or fixed-width) columns only: nesting is unchanged code."""
    n = len(arr)
    if isinstance(dt, T.StringType):
        data, lengths, valid, w = ref_string_to_padded(arr, width, max_str_bytes)
        pdata = np.zeros((cap, w), dtype=np.uint8)
        pdata[:n] = data
        plen = np.zeros(cap, dtype=np.int32)
        plen[:n] = lengths
        pval = np.zeros(cap, dtype=bool)
        pval[:n] = valid
        return D.DeviceColumn(dt, pdata, pval, plen)
    data, valid = ref_np_from_arrow_fixed(arr, dt)
    pdata = np.zeros(cap, dtype=dt.np_dtype)
    pdata[:n] = data
    pval = np.zeros(cap, dtype=bool)
    pval[:n] = valid
    return D.DeviceColumn(dt, pdata, pval)


def assert_same_planes(got: D.DeviceColumn, want: D.DeviceColumn):
    assert got.dtype == want.dtype
    for name in ("data", "validity", "lengths"):
        g, w = getattr(got, name), getattr(want, name)
        if w is None:
            assert g is None, name
            continue
        assert isinstance(g, np.ndarray), name
        assert g.dtype == w.dtype, (name, g.dtype, w.dtype)
        assert g.shape == w.shape, (name, g.shape, w.shape)
        assert g.flags.c_contiguous, name
        # the bits, not the values: -0.0 and NaN payloads must survive
        assert np.array_equal(g.view(np.uint8), w.view(np.uint8)), name


# ── strings ─────────────────────────────────────────────────────────────────


def _strings(lengths: str, n: int, seed: int) -> list:
    """Values of every byte but NUL-free UTF-8 would hide: multi-byte
    characters straddle byte positions, and "\\x00" inside a value must stay."""
    rng = np.random.default_rng(seed)
    alphabet = list("abcXYZ 09_é漢\x00")
    lo, hi = {"all1": (1, 1), "all16": (16, 16), "r0-10": (0, 10), "r0-100": (0, 100)}[lengths]
    out = []
    for _ in range(n):
        k = int(rng.integers(lo, hi + 1))
        if lo == hi:  # fixed BYTE length: ASCII only
            out.append("".join(rng.choice(list("ANRFO0123456789abcdef"), k)))
        else:
            s = "".join(rng.choice(alphabet, k))
            while len(s.encode()) > hi:
                s = s[:-1]
            out.append(s)
    return out


def _with_nulls(values: list, nulls: str, seed: int) -> list:
    if nulls == "none":
        return values
    if nulls == "all":
        return [None] * len(values)
    rng = np.random.default_rng(seed + 1)
    return [None if rng.random() < 0.3 else v for v in values]


@pytest.mark.parametrize("nulls", ["none", "some", "all"])
@pytest.mark.parametrize("lengths", ["all1", "all16", "r0-10", "r0-100"])
def test_string_planes_equal_the_old_pad(lengths, nulls):
    vals = _with_nulls(_strings(lengths, 777, 1), nulls, 1)
    arr = pa.array(vals, type=pa.string())
    cap = D.bucket_capacity(len(arr))
    assert_same_planes(
        D._np_col_from_arrow(arr, T.StringType(), cap),
        ref_np_col_from_arrow(arr, T.StringType(), cap),
    )


@pytest.mark.parametrize(
    "make",
    [
        pytest.param(lambda a: a.slice(100, 500).slice(7, 300), id="slice_of_slice"),
        pytest.param(lambda a: a.slice(0, 0), id="empty_slice"),
        pytest.param(lambda a: a.dictionary_encode(), id="dictionary"),
        pytest.param(lambda a: a.dictionary_encode().slice(33, 400), id="dictionary_sliced"),
        pytest.param(lambda a: a.cast(pa.large_string()), id="large_string"),
        pytest.param(lambda a: a.cast(pa.large_string()).slice(5, 99), id="large_string_sliced"),
    ],
)
@pytest.mark.parametrize("lengths,nulls", [("all16", "none"), ("r0-10", "some")])
def test_string_planes_of_sliced_and_encoded_inputs(make, lengths, nulls):
    arr = make(pa.array(_with_nulls(_strings(lengths, 777, 2), nulls, 2), type=pa.string()))
    cap = D.bucket_capacity(max(len(arr), 1))
    assert_same_planes(
        D._np_col_from_arrow(arr, T.StringType(), cap),
        ref_np_col_from_arrow(arr, T.StringType(), cap),
    )


def test_empty_string_array():
    arr = pa.array([], type=pa.string())
    got = D._np_col_from_arrow(arr, T.StringType(), 8)
    assert_same_planes(got, ref_np_col_from_arrow(arr, T.StringType(), 8))
    assert got.data.shape == (8, D.MIN_STR_WIDTH)


@pytest.mark.parametrize("under", [3, 37], ids=["short", "wider_than_the_plane"])
def test_null_slots_with_bytes_under_them_are_zeroed(under):
    """Arrow lets a null slot span value bytes, more of them than the plane
    is wide too; the plane must not show them."""
    offsets = np.array([0, 3, 3 + under, 6 + under], np.int32)
    arr = pa.StringArray.from_buffers(
        3,
        pa.py_buffer(offsets.tobytes()),
        pa.py_buffer(b"abc" + b"Z" * under + b"ghi"),
        pa.py_buffer(bytes([0b101])),
    )
    assert arr.to_pylist() == ["abc", None, "ghi"]
    got = D._np_col_from_arrow(arr, T.StringType(), 8)
    assert_same_planes(got, ref_np_col_from_arrow(arr, T.StringType(), 8))
    assert got.data.shape == (8, 8)
    assert not got.data[1].any() and got.lengths[1] == 0


@pytest.mark.parametrize("lengths,nulls", [("all1", "none"), ("r0-10", "none"), ("r0-10", "some")])
def test_width_hint_wider_than_needed(lengths, nulls):
    arr = pa.array(_with_nulls(_strings(lengths, 300, 3), nulls, 3), type=pa.string())
    got = D._np_col_from_arrow(arr, T.StringType(), 512, width=64)
    assert_same_planes(got, ref_np_col_from_arrow(arr, T.StringType(), 512, width=64))
    assert got.data.shape == (512, 64)


@pytest.mark.parametrize("lengths", ["all16", "r0-100"])
def test_ragged_fill_in_chunks(monkeypatch, lengths):
    """A plane of more rows than one pyarrow call pads: the chunks must tile it."""
    arr = pa.array(_with_nulls(_strings(lengths, 1000, 4), "some", 4), type=pa.string())
    monkeypatch.setattr(D, "_PAD_CHUNK_BYTES", 128 * 37)
    assert_same_planes(
        D._np_col_from_arrow(arr, T.StringType(), 1024),
        ref_np_col_from_arrow(arr, T.StringType(), 1024),
    )


@pytest.mark.parametrize(
    "kw,text",
    [
        (dict(max_str_bytes=16), "string length 20 exceeds spark.rapids.tpu.string.maxBytes=16"),
        (dict(width=16), "string length 20 exceeds device width 16"),
        # a width hint wins over the ceiling, as it always did
        (dict(width=16, max_str_bytes=8), "string length 20 exceeds device width 16"),
    ],
)
def test_too_long_a_string_raises_as_before(kw, text):
    arr = pa.array(["ab", "x" * 20, None])
    with pytest.raises(ValueError) as new:
        D._np_col_from_arrow(arr, T.StringType(), 8, **kw)
    with pytest.raises(ValueError) as old:
        ref_np_col_from_arrow(arr, T.StringType(), 8, **kw)
    assert str(new.value) == str(old.value) == text


def test_no_wide_temporary_in_the_string_pad(monkeypatch):
    """The old pad held a [rows, width] int64 index; the new one may not build
    any [rows, width] array of a type wider than the bytes it moves."""
    seen = []
    for fn in ("where", "clip", "arange", "zeros", "empty"):
        real = getattr(np, fn)

        def spy(*a, _real=real, **k):
            out = _real(*a, **k)
            seen.append(out)
            return out

        monkeypatch.setattr(np, fn, spy)
    arr = pa.array(_strings("r0-100", 400, 5), type=pa.string())
    D._np_col_from_arrow(arr, T.StringType(), 512)
    wide = [a.shape for a in seen if a.ndim == 2 and a.dtype.itemsize > 1]
    assert not wide, wide


# ── fixed-width planes ──────────────────────────────────────────────────────


def _fixed_cases():
    rng = np.random.default_rng(6)
    n = 333
    f64 = rng.standard_normal(n)
    f64[:4] = [-0.0, np.nan, np.inf, -np.inf]
    dec = [decimal.Decimal(int(v)).scaleb(-2) for v in rng.integers(-10**9, 10**9, n)]
    return {
        "int32": (pa.array(rng.integers(-(2**31), 2**31, n), pa.int32()), T.IntegerType()),
        "int64": (pa.array(rng.integers(-(2**62), 2**62, n), pa.int64()), T.LongType()),
        "float64": (pa.array(f64, pa.float64()), T.DoubleType()),
        "date32": (pa.array(rng.integers(0, 20000, n).astype(np.int32), pa.int32()).cast(pa.date32()), T.DateType()),
        "timestamp": (pa.array(rng.integers(0, 2**50, n), pa.int64()).cast(pa.timestamp("us")), T.TimestampType()),
        "bool": (pa.array(rng.random(n) < 0.5), T.BooleanType()),
        "decimal": (pa.array(dec, pa.decimal128(12, 2)), T.DecimalType(12, 2)),
    }


@pytest.mark.parametrize("shape", ["whole", "slice_of_slice", "empty"])
@pytest.mark.parametrize("nulls", ["none", "some", "all"])
@pytest.mark.parametrize(
    "kind", ["int32", "int64", "float64", "date32", "timestamp", "bool", "decimal"]
)
def test_fixed_width_planes_equal_the_old_pad(kind, nulls, shape):
    arr, dt = _fixed_cases()[kind]
    if nulls != "none":
        mask = np.random.default_rng(7).random(len(arr)) < (0.3 if nulls == "some" else 2)
        arr = pa.array(arr.to_pylist(), type=arr.type, mask=mask)
    if shape == "slice_of_slice":
        arr = arr.slice(20, 300).slice(9, 200)
    elif shape == "empty":
        arr = arr.slice(5, 0)
    cap = D.bucket_capacity(max(len(arr), 1))
    assert_same_planes(
        D._np_col_from_arrow(arr, dt, cap), ref_np_col_from_arrow(arr, dt, cap)
    )
    # the CPU engine's route shares the buffer view: its results stay too
    for got, want in zip(np_from_arrow(arr, dt), ref_np_from_arrow_fixed(arr, dt)):
        assert got.dtype == want.dtype and got.flags.c_contiguous
        assert np.array_equal(got.view(np.uint8), want.view(np.uint8))


# ── nested: the element planes index the flat planes this code returns ──────


@pytest.mark.parametrize("nulls", ["none", "some"])
def test_list_of_string_planes(nulls):
    rng = np.random.default_rng(8)
    words = _with_nulls(_strings("r0-10", 64, 8), nulls, 8)
    rows = [
        None if nulls == "some" and rng.random() < 0.2
        else [words[int(i)] for i in rng.integers(0, 64, int(rng.integers(0, 6)))]
        for _ in range(50)
    ]
    arr = pa.array(rows, type=pa.list_(pa.string())).slice(3, 40)
    dt = T.ArrayType(T.StringType())
    cap = D.bucket_capacity(len(arr))
    got = D._np_list_from_arrow(arr, dt, cap)
    # its flat plane, built by the old pad, gives the same element planes
    import unittest.mock as mock

    with mock.patch.object(D, "_np_col_from_arrow", ref_np_col_from_arrow):
        want = D._np_list_from_arrow(arr, dt, cap)
    assert np.array_equal(got.validity, want.validity)
    assert np.array_equal(got.lengths, want.lengths)
    assert_same_planes(got.children[0], want.children[0])
    back = D._arrow_from_np_col(got, dt, len(arr))
    assert back.to_pylist() == arr.to_pylist()


def test_host_to_device_round_trip_and_shapes():
    vals = _with_nulls(_strings("r0-100", 100, 9), "some", 9)
    rb = pa.record_batch(
        {"s": pa.array(vals), "c": pa.array(["R"] * 100), "x": pa.array(np.arange(100.0))}
    )
    db = D.host_to_device(rb)
    want = D.abstract_batch(db.schema, db.capacity, {0: 128, 1: 8})
    for got_c, want_c in zip(db.columns, want.columns):
        assert got_c.data.shape == want_c.data.shape
        assert got_c.data.dtype == want_c.data.dtype
    assert D.device_to_host(db).to_pydict() == rb.to_pydict()


# ── the counters ────────────────────────────────────────────────────────────


def test_counters_say_which_fill_ran():
    """q1's two string columns are char(1) flags: both planes take the
    one-copy fill; a ragged column beside them does not."""
    planes, fixed = GLOBAL.counter("batch.padStringPlanes"), GLOBAL.counter(
        "batch.padStringPlanesFixedLen"
    )
    rng = np.random.default_rng(10)
    rb = pa.record_batch(
        {
            "l_returnflag": pa.array(rng.choice(["R", "A", "N"], 1000)),
            "l_linestatus": pa.array(rng.choice(["O", "F"], 1000)),
            "l_quantity": pa.array(rng.random(1000)),
        }
    )
    p0, f0 = planes.value, fixed.value
    D.host_to_device(rb)
    assert (planes.value - p0, fixed.value - f0) == (2, 2)
    p0, f0 = planes.value, fixed.value
    D.host_to_device(pa.record_batch({"seg": pa.array(["BUILDING", "AUTOMOBILE"])}))
    D.host_to_device(pa.record_batch({"flag": pa.array(["R", None])}))
    assert (planes.value - p0, fixed.value - f0) == (2, 0)
