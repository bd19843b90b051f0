"""ops/concat.py against a numpy reference built from the inputs' live rows.

Every leaf of the result (data, validity, lengths, down through nested
children) is compared bit for bit: live rows of each input in input order,
then zeros — dead rows of the output hold zeroed data and false validity,
whatever the inputs' dead rows held.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.device import (
    DeviceBatch,
    bucket_capacity,
    host_to_device,
)
from spark_rapids_tpu.ops import concat as C


def _fixed(n, seed):
    rng = np.random.default_rng(seed)
    return {
        "f": pa.array(rng.standard_normal(n), mask=rng.random(n) < 0.3),
        "i": pa.array(rng.integers(-(2**31), 2**31, n).astype(np.int32)),
        "b": pa.array(rng.random(n) < 0.5, mask=rng.random(n) < 0.2),
    }


def _strings(n, seed, longest):
    rng = np.random.default_rng(seed)
    vals = [
        None if rng.random() < 0.2 else "x" * int(rng.integers(0, longest + 1))
        for _ in range(n)
    ]
    if n:
        vals[0] = "y" * longest  # the padded width follows the longest value
    return {"s": pa.array(vals, pa.string()), "k": pa.array(np.arange(n))}


def _nested(n, seed, longest):
    rng = np.random.default_rng(seed)
    lists = [
        None
        if rng.random() < 0.2
        else [None if rng.random() < 0.2 else int(v) for v in rng.integers(0, 99, int(rng.integers(0, longest + 1)))]
        for _ in range(n)
    ]
    if n:
        lists[-1] = list(range(longest))
    structs = [
        None if rng.random() < 0.2 else {"a": int(rng.integers(0, 99)), "t": "q" * int(rng.integers(0, 5))}
        for _ in range(n)
    ]
    return {
        "l": pa.array(lists, pa.list_(pa.int64())),
        "st": pa.array(structs, pa.struct([("a", pa.int64()), ("t", pa.string())])),
    }


def _batch(cols, capacity=None):
    return host_to_device(pa.record_batch(cols), capacity=capacity)


def _with_garbage(batch: DeviceBatch) -> DeviceBatch:
    """The same batch with every bit of every dead row set."""
    n = int(batch.num_rows)

    def fill(x):
        x = np.array(x)
        if x.ndim:
            x[n:] = True if x.dtype == np.bool_ else np.array(-1).astype(x.dtype)
        return jnp.asarray(x)

    cols = jax.tree.map(fill, batch.columns)
    return DeviceBatch(batch.schema, cols, batch.num_rows)


def _cases():
    def fixed():
        return [_batch(_fixed(n, n)) for n in (5, 11)]

    def string_widths():  # padded widths 8 and 64
        return [_batch(_strings(9, 1, 3)), _batch(_strings(7, 2, 40))]

    def nested():  # list planes of widths that differ, a struct of two fields
        return [_batch(_nested(6, 1, 2)), _batch(_nested(10, 2, 9))]

    def empty_input():
        return [_batch(_fixed(4, 1)), _batch(_fixed(0, 2)), _batch(_fixed(3, 3))]

    def full_input():  # num_rows == capacity, first and last
        return [
            _batch(_fixed(16, 1), capacity=16),
            _batch(_fixed(3, 2)),
            _batch(_fixed(32, 3), capacity=32),
        ]

    def unequal_capacities():
        caps = (1024, 8, 2048, 64)
        return [_batch(_strings(c // 2 + 1, c, 5) | _fixed(c // 2 + 1, c), capacity=c) for c in caps]

    def eight_inputs():
        return [_batch(_fixed(n, n) | _strings(n, n, n)) for n in (1, 30, 0, 7, 64, 2, 19, 5)]

    def garbage_dead_rows():
        return [
            _with_garbage(_batch(_fixed(n, n) | _strings(n, n, 6) | _nested(n, n, 3)))
            for n in (3, 0, 12)
        ]

    return [
        fixed, string_widths, nested, empty_input, full_input,
        unequal_capacities, eight_inputs, garbage_dead_rows,
    ]


def _leaves(col, path):
    """(path, array) of every plane of a column, children included."""
    for name in ("data", "validity", "lengths"):
        x = getattr(col, name)
        if x is not None:
            yield f"{path}.{name}", np.asarray(x)
    for k, kid in enumerate(col.children or ()):
        yield from _leaves(kid, f"{path}[{k}]")


def _reference(batches, cap):
    """Leaf by leaf in numpy: the live rows of each input, trailing axes
    zero-padded to the widest input, stacked in input order over zeros."""
    ns = [int(b.num_rows) for b in batches]
    out = {}
    for i in range(len(batches[0].columns)):
        per_input = [dict(_leaves(b.columns[i], f"c{i}")) for b in batches]
        for path in per_input[0]:
            planes = [p[path] for p in per_input]
            trail = tuple(max(p.shape[ax] for p in planes) for ax in range(1, planes[0].ndim))
            want = np.zeros((cap,) + trail, planes[0].dtype)
            at = 0
            for p, n in zip(planes, ns):
                want[(slice(at, at + n),) + tuple(slice(0, w) for w in p.shape[1:])] = p[:n]
                at += n
            out[path] = want
    return out, sum(ns)


@pytest.mark.parametrize("make", _cases(), ids=lambda f: f.__name__)
def test_concat_matches_numpy_reference(make):
    batches = make()
    cap = bucket_capacity(sum(b.capacity for b in batches))
    want, total = _reference(batches, cap)
    got = C.concat_device(batches)
    assert got.schema == batches[0].schema
    assert got.capacity == cap
    assert int(got.num_rows) == total
    got_leaves = {}
    for i, col in enumerate(got.columns):
        got_leaves.update(_leaves(col, f"c{i}"))
    assert got_leaves.keys() == want.keys()
    for path, w in want.items():
        g = got_leaves[path]
        assert g.dtype == w.dtype and g.shape == w.shape, path
        assert g.tobytes() == w.tobytes(), path


def test_garbage_case_holds_garbage():
    """The dead rows the garbage case hands in are really non-zero."""
    b = _with_garbage(_batch(_fixed(3, 3)))
    assert np.asarray(b.columns[0].validity)[3:].all()
    assert np.asarray(b.columns[1].data)[3:].all()


def test_concat_lowers_to_block_copies_not_scatters():
    batches = [
        _batch(_fixed(3, c) | _strings(3, c, 4), capacity=c) for c in (1024, 8, 2048, 64)
    ]
    cap = bucket_capacity(sum(b.capacity for b in batches))
    text = jax.jit(lambda bs: C._concat_impl(list(bs), cap)).lower(tuple(batches)).as_text()
    assert "scatter" not in text
    # 4 inputs of (3 fixed + string + int64) columns: 11 planes each
    assert text.count("dynamic_update_slice") == 44


def test_one_batch_is_returned_as_it_is():
    b = _batch(_fixed(5, 5))
    assert C.concat_device([b]) is b


def test_capacity_is_not_a_parameter():
    """The output capacity is always the bucketed sum of the inputs', which
    is what keeps every block copy's start where the offset puts it (a
    smaller capacity would let dynamic_update_slice move a start back over
    live rows). No caller ever passed one; the parameter is gone."""
    batches = [_batch(_fixed(2, 1)), _batch(_fixed(2, 2))]
    with pytest.raises(TypeError):
        C.concat_device(batches, capacity=batches[0].capacity)
    with pytest.raises(TypeError):
        C.concat_device(batches, batches[0].capacity)


def test_store_key_and_kernel_name():
    """The key's tag names the kernel (whether a stored executable is stale
    is cache/xla_store.py's to decide, from the source), and a device trace
    names the module after the jitted function."""
    from spark_rapids_tpu import kernels as K

    batches = [_batch(_fixed(2, 1)), _batch(_fixed(2, 2))]
    C.concat_device(batches)
    mine = {k: fn for k, fn in K._KERNELS.items() if str(k[0]).startswith("concat")}
    assert {k[0] for k in mine} == {"concat"}
    assert {fn._fn.__name__ for fn in mine.values()} == {"_concat"}
