"""Host-side columnar helpers — the ``RapidsHostColumnVector`` analogue.

The host currency everywhere (spill, shuffle, CPU fallback operators, IO) is
``pyarrow.RecordBatch``. The CPU execution engine computes over numpy views
with explicit validity masks so Spark semantics (Java integer wraparound,
null propagation, NaN ordering) are implemented exactly rather than inherited
from pyarrow.compute.
"""
from __future__ import annotations

import decimal as _dec
from typing import Optional

import numpy as np
import pyarrow as pa

from ..types import DataType, DecimalType, NullType, Schema, StringType, is_complex


def fixed_np(arr: pa.Array, np_dtype: np.dtype) -> np.ndarray:
    """Zero-copy-ish view of a fixed-width arrow array's data buffer.

    Avoids ``to_numpy``'s nullable-int→float64 promotion, which silently
    loses precision on int64 values beyond 2^53 (null slots hold garbage —
    callers mask them)."""
    n = len(arr)
    buf = arr.buffers()[1]
    if buf is None:
        return np.zeros(n, dtype=np_dtype)
    if np_dtype == np.bool_:
        bits = np.frombuffer(buf, dtype=np.uint8)
        idx = np.arange(arr.offset, arr.offset + n)
        return ((bits[idx // 8] >> (idx % 8)) & 1).astype(bool)
    data = np.frombuffer(buf, dtype=np_dtype, count=arr.offset + n)[arr.offset :]
    return data


def fixed_view(arr: pa.Array, dt: DataType) -> np.ndarray:
    """A fixed-width arrow array's values as ``dt.np_dtype``, viewed in place
    where the buffer's layout allows (decimals are a strided view, bools are
    unpacked). Null slots hold whatever the buffer holds — callers mask them."""
    n = len(arr)
    if isinstance(dt, DecimalType):
        # decimal128 storage is 128-bit little-endian; DECIMAL64 gating means
        # the value always fits the low 64 bits (two's complement)
        buf = arr.buffers()[1]
        if buf is None:
            return np.zeros(n, dtype=np.int64)
        pairs = np.frombuffer(buf, dtype=np.int64, count=(arr.offset + n) * 2)
        return pairs.reshape(-1, 2)[arr.offset :, 0]
    if pa.types.is_date32(arr.type):
        arr = arr.cast(pa.int32())
    elif pa.types.is_timestamp(arr.type):
        arr = arr.cast(pa.int64())
    return fixed_np(arr, dt.np_dtype)


def np_from_arrow(arr: pa.Array, dt: DataType) -> tuple[np.ndarray, np.ndarray]:
    """Arrow array → (data, validity). For strings, data is an object ndarray
    of python str (None for null). Null slots in fixed-width data are zeroed."""
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    valid = ~np.asarray(arr.is_null())
    n = len(arr)
    if isinstance(dt, StringType):
        data = np.empty(n, dtype=object)
        data[:] = arr.cast(pa.string()).to_pylist()
        return data, valid
    if is_complex(dt):
        # CPU oracle representation: object ndarray of python values
        # (lists / dicts-as-lists-of-pairs / structs-as-dicts)
        data = np.empty(n, dtype=object)
        data[:] = arr.to_pylist()
        return data, valid
    if isinstance(dt, NullType):
        return np.zeros(n, dtype=np.int8), np.zeros(n, dtype=bool)
    data = fixed_view(arr, dt)
    if isinstance(dt, DecimalType):
        return np.where(valid, data, 0), valid
    if not valid.all():
        data = np.where(valid, data, np.zeros((), dtype=dt.np_dtype))
    return np.ascontiguousarray(data), valid


def arrow_from_np(data: np.ndarray, valid: np.ndarray, dt: DataType) -> pa.Array:
    n = len(data)
    if isinstance(dt, NullType):
        return pa.nulls(n)
    if isinstance(dt, StringType):
        py = [data[i] if valid[i] else None for i in range(n)]
        return pa.array(py, type=pa.string())
    if isinstance(dt, DecimalType):
        py = [
            _dec.Decimal(int(data[i])).scaleb(-dt.scale) if valid[i] else None
            for i in range(n)
        ]
        return pa.array(py, type=pa.decimal128(dt.precision, dt.scale))
    if is_complex(dt):
        py = [data[i] if valid[i] else None for i in range(n)]
        return pa.array(py, type=dt.to_arrow())
    mask = None if valid.all() else ~valid
    return pa.array(data, type=dt.to_arrow(), mask=mask)


def batch_from_columns(
    schema: Schema, cols: list[tuple[np.ndarray, np.ndarray]]
) -> pa.RecordBatch:
    arrays = [
        arrow_from_np(d, v, f.data_type) for (d, v), f in zip(cols, schema)
    ]
    return pa.RecordBatch.from_arrays(arrays, schema=schema.to_arrow())


def concat_batches(schema: Schema, batches: list[pa.RecordBatch]) -> pa.RecordBatch:
    if not batches:
        return pa.RecordBatch.from_arrays(
            [pa.array([], type=f.data_type.to_arrow()) for f in schema],
            schema=schema.to_arrow(),
        )
    table = pa.Table.from_batches(batches)
    return table.combine_chunks().to_batches()[0] if table.num_rows else batches[0].slice(0, 0)
