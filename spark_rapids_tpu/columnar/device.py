"""Device-resident columnar data — the ``GpuColumnVector``/``ColumnarBatch``
layer re-designed for TPU/XLA.

Reference analogue: sql-plugin GpuColumnVector.java (cudf ColumnVector wrapper,
Table<->batch converters :550-582, type map :476) and the batch currency that
every GpuExec operator streams. Here a column is a pytree of JAX arrays in
Arrow layout:

* fixed-width types: ``data``: ``dtype[capacity]``, ``validity``: ``bool[capacity]``
* strings: ``data``: ``uint8[capacity, width]`` (padded bytes), ``lengths``:
  ``int32[capacity]``, ``validity`` — a fixed-width design chosen for the MXU/
  VPU's static-shape world instead of cudf's offsets+chars, with ``width``
  bucketed to a power of two to bound recompilation.

Key TPU-first departures from the reference:

* **Static shapes**: every batch has a power-of-two ``capacity``; live rows are
  prefix-compacted ``[0, num_rows)`` and ``num_rows`` is a *device* scalar so
  pipelines (filter -> project -> partial agg) run with zero host syncs.
  ``DeviceBatch.row_count()`` syncs on demand at operator boundaries only.
* **jit caching**: kernels are plain jitted functions of these pytrees; the
  (tree structure, shapes, dtypes) tuple is the compile cache key — the
  analogue of cudf's pre-compiled kernel library.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from ..types import (
    DataType,
    DecimalType,
    NullType,
    Schema,
    StringType,
    StructField,
    from_arrow,
)

MIN_CAPACITY = 8
MIN_STR_WIDTH = 8


def bucket_capacity(n: int) -> int:
    """Round a row count up to the shape-bucket lattice: the next power of
    two at or above ``kernels.shape_bucket_floor()`` (>= MIN_CAPACITY), so
    the number of distinct compiled shapes per schema is logarithmic AND
    every batch below the floor shares ONE geometry — one cached executable
    serves them all (spark.rapids.tpu.shapeBuckets.*). Padding rows above
    ``num_rows`` are masked inert by the batch invariant."""
    from .. import kernels as K

    cap = K.shape_bucket_floor()
    if cap < MIN_CAPACITY:
        cap = MIN_CAPACITY
    while cap < n:
        cap <<= 1
    return cap


def tight_capacity(n: int) -> int:
    """Round a row count up to the next power of two >= MIN_CAPACITY,
    ignoring the shape-bucket lattice floor. The shrink-to-fit path
    (ops/gather.shrink_one) exists to CUT device footprint before
    non-splittable merges and D2H packing; re-bucketing it to the lattice
    floor would pin tiny batches (13-group partial-aggregate outputs) at
    the ingest geometry and re-inflate exactly the buffers it is meant to
    shrink."""
    cap = MIN_CAPACITY
    while cap < n:
        cap <<= 1
    return cap


def bucket_width(n: int) -> int:
    w = MIN_STR_WIDTH
    while w < n:
        w <<= 1
    return w


def pad_scalar_bytes(raw: bytes) -> tuple[np.ndarray, int]:
    """Encode one byte string into the padded scalar-string device layout:
    (uint8[bucket_width], true length). Shared by string literals and the
    TaskVals file-name channel."""
    w = bucket_width(max(len(raw), 1))
    buf = np.zeros(w, dtype=np.uint8)
    buf[: len(raw)] = np.frombuffer(raw, dtype=np.uint8)
    return buf, len(raw)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceColumn:
    """One column of a device batch. ``dtype`` is static pytree metadata.

    Layouts by type:
    * fixed-width: ``data``: dtype[cap]; ``validity``: bool[cap]
    * string: ``data``: uint8[cap, w]; ``lengths``: int32[cap]
    * array<e>: ``data`` None; ``lengths``: int32[cap] (list sizes);
      ``children`` = (element column,) whose planes carry a second padded
      axis: element data [cap, W(, w)], element validity [cap, W]
    * struct: ``data`` None; ``children`` = per-field columns [cap]
    * map<k,v>: like array with ``children`` = (keys, values) planes
    """

    dtype: DataType
    data: Optional[jax.Array]
    validity: jax.Array  # bool[cap]
    lengths: Optional[jax.Array] = None  # string/array/map: int32[cap]
    children: Optional[tuple] = None  # nested columns (array/struct/map)

    def tree_flatten(self):
        return (self.data, self.validity, self.lengths, self.children), self.dtype

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, validity, lengths, kids = children
        if kids is not None:
            kids = tuple(kids)
        return cls(aux, data, validity, lengths, kids)

    @property
    def capacity(self) -> int:
        if self.data is not None:
            return int(self.data.shape[0])
        return int(self.validity.shape[0])

    @property
    def is_string(self) -> bool:
        return isinstance(self.dtype, StringType)

    @property
    def str_width(self) -> int:
        assert self.is_string
        return int(self.data.shape[1])

    @property
    def list_width(self) -> int:
        """Padded element count per row (array/map columns)."""
        return int(self.children[0].data.shape[1])


def dc_replace(col: DeviceColumn, **kw) -> DeviceColumn:
    """dataclasses.replace for DeviceColumn — the way to rebuild a column
    with a changed field WITHOUT dropping nested children planes."""
    return dataclasses.replace(col, **kw)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceBatch:
    """A batch of columns with a device-resident live-row count.

    Rows ``[0, num_rows)`` are live; padding rows have ``validity == False``
    and zeroed data. ``schema`` is static pytree metadata.
    """

    schema: Schema
    columns: list[DeviceColumn]
    num_rows: jax.Array  # int32 scalar (device)

    def tree_flatten(self):
        return (self.columns, self.num_rows), self.schema

    @classmethod
    def tree_unflatten(cls, aux, children):
        columns, num_rows = children
        return cls(aux, list(columns), num_rows)

    @property
    def capacity(self) -> int:
        if self.columns:
            return self.columns[0].capacity
        return 0

    def row_count(self) -> int:
        """Host-sync the live-row count. Use only at operator boundaries."""
        return int(self.num_rows)

    def row_mask(self) -> jax.Array:
        """bool[capacity] — True for live rows."""
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.num_rows

    def column(self, i: int) -> DeviceColumn:
        return self.columns[i]

    def by_name(self, name: str) -> DeviceColumn:
        """Column lookup for the ``to_jax()`` export path."""
        return self.columns[self.schema.index_of(name)]

    def with_columns(self, schema: Schema, columns: list[DeviceColumn]) -> "DeviceBatch":
        return DeviceBatch(schema, columns, self.num_rows)

    def size_bytes(self) -> int:
        """Approximate device footprint (for batching goals / spill accounting)."""

        def col_bytes(c) -> int:
            total = 0
            if c.data is not None:
                total += c.data.size * c.data.dtype.itemsize
            total += c.validity.size
            if c.lengths is not None:
                total += c.lengths.size * 4
            if c.children is not None:
                total += sum(col_bytes(k) for k in c.children)
            return total

        return sum(col_bytes(c) for c in self.columns)


# ── Host <-> device transfer (the H2D/D2H seam; reference: GpuColumnVector
#    from(Table)/from(ColumnarBatch) + RapidsHostColumnVector) ───────────────


def _padded_validity(arr: pa.Array, cap: int) -> tuple[np.ndarray, Optional[np.ndarray]]:
    """Validity plane of ``arr`` at capacity (padding rows False), and the
    live rows that are null as a mask — None where the array has none, so
    the planes of a column without nulls are written with no mask pass."""
    n = len(arr)
    pval = np.empty(cap, dtype=bool)
    pval[n:] = False
    if arr.null_count == 0:
        pval[:n] = True
        return pval, None
    pval[:n] = np.asarray(arr.is_valid())
    return pval, ~pval[:n]


def _fixed_to_padded(arr: pa.Array, dt: DataType, cap: int) -> tuple[np.ndarray, np.ndarray]:
    """Arrow fixed-width array → (data[cap], validity[cap]), null slots and
    padding rows zero: the buffer view host.np_from_arrow (the CPU engine's
    route) masks and returns is here copied once, into the plane that ships."""
    from .host import fixed_view

    n = len(arr)
    pval, nulls = _padded_validity(arr, cap)
    pdata = np.empty(cap, dtype=dt.np_dtype)
    pdata[:n] = fixed_view(arr, dt)
    pdata[n:] = 0
    if nulls is not None:
        np.putmask(pdata[:n], nulls, 0)
    return pdata, pval


# rows of a ragged string plane padded per pyarrow call: bounds the padded
# temporary (and keeps rows * width inside a string array's int32 offsets)
_PAD_CHUNK_BYTES = 1 << 22


def _string_to_padded(
    arr: pa.Array,
    cap: int,
    width: Optional[int] = None,
    max_str_bytes: Optional[int] = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Arrow string array → (bytes[cap, width], lengths[cap], validity[cap]),
    each plane allocated at capacity and written once: bytes past a value's
    length, null slots and padding rows are zero. ``max_str_bytes``
    (spark.rapids.tpu.string.maxBytes) caps the inferred width — longer
    values raise, surfacing the configured ceiling.

    The fill follows what the array shows. Where every value has one length
    L and none is null (char(n) columns), the value buffer IS a [rows, L]
    matrix and one strided copy places it. Otherwise pyarrow right-pads the
    values to ``width`` with NUL bytes, a chunk of rows at a time, which
    makes the same matrix of each chunk."""
    from ..obs.metrics import GLOBAL as _M

    arr = arr.cast(pa.string())
    n = len(arr)
    pval, nulls = _padded_validity(arr, cap)
    # Offsets/values buffers give us lengths without python-object round trips.
    offsets = np.frombuffer(arr.buffers()[1], dtype=np.int32)[
        arr.offset : arr.offset + n + 1
    ]
    plen = np.empty(cap, dtype=np.int32)
    plen[n:] = 0
    np.subtract(offsets[1:], offsets[:-1], out=plen[:n])
    if nulls is not None:
        np.putmask(plen[:n], nulls, 0)
    maxlen = int(plen[:n].max()) if n else 0
    if width is None:
        if max_str_bytes is not None and maxlen > max_str_bytes:
            raise ValueError(
                f"string length {maxlen} exceeds "
                f"spark.rapids.tpu.string.maxBytes={max_str_bytes}"
            )
        width = bucket_width(max(maxlen, 1))
    if maxlen > width:
        raise ValueError(f"string length {maxlen} exceeds device width {width}")
    _M.counter("batch.padStringPlanes").add(1)
    pdata = np.empty((cap, width), dtype=np.uint8)
    pdata[n:] = 0
    if maxlen == 0:  # empty, all null or all "": the values buffer may be absent
        pdata[:n] = 0
        return pdata, plen, pval
    values = arr.buffers()[2]
    first = int(offsets[0])
    if nulls is None and int(offsets[-1]) - first == n * maxlen:
        _M.counter("batch.padStringPlanesFixedLen").add(1)
        flat = np.frombuffer(values, dtype=np.uint8)[first : first + n * maxlen]
        pdata[:n, :maxlen] = flat.reshape(n, maxlen)
        pdata[:n, maxlen:] = 0
        return pdata, plen, pval
    # a null pads as "": Arrow lets a null slot span value bytes, of any length
    dense = arr.fill_null("") if nulls is not None else arr
    step = max(1, _PAD_CHUNK_BYTES // width)
    for lo in range(0, n, step):
        rows = min(step, n - lo)
        padded = pc.ascii_rpad(dense.slice(lo, rows), width=width, padding="\0")
        start = int(np.frombuffer(padded.buffers()[1], dtype=np.int32)[padded.offset])
        pdata[lo : lo + rows] = np.frombuffer(padded.buffers()[2], dtype=np.uint8)[
            start : start + rows * width
        ].reshape(rows, width)
    return pdata, plen, pval


def _padded_to_string(data: np.ndarray, lengths: np.ndarray, valid: np.ndarray, n: int) -> pa.Array:
    data, lengths, valid = data[:n], lengths[:n], valid[:n]
    lengths = np.where(valid, lengths, 0).astype(np.int64)
    offsets = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(lengths, out=offsets[1:])
    width = data.shape[1] if data.ndim == 2 else 0
    take = np.arange(width)[None, :] < lengths[:, None]
    values = data[take].astype(np.uint8).tobytes() if n and width else b""
    null_mask = None
    if not valid.all():
        null_mask = pa.array(valid.astype(bool)).buffers()[1]
    return pa.StringArray.from_buffers(
        n, pa.py_buffer(offsets.tobytes()), pa.py_buffer(values), null_mask
    )


def _np_col_from_arrow(
    arr: pa.Array,
    dt: DataType,
    cap: int,
    width: Optional[int] = None,
    max_str_bytes: Optional[int] = None,
) -> DeviceColumn:
    """Arrow array → host-side DeviceColumn (numpy leaves), padded to cap.
    Recursive over array/struct/map nesting."""
    from ..types import ArrayType, MapType, StructType

    n = len(arr)
    if isinstance(dt, StringType):
        pdata, plen, pval = _string_to_padded(arr, cap, width, max_str_bytes)
        return DeviceColumn(dt, pdata, pval, plen)
    if isinstance(dt, NullType):
        return DeviceColumn(dt, np.zeros(cap, np.int8), np.zeros(cap, bool))
    if isinstance(dt, StructType):
        arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
        pval = np.zeros(cap, dtype=bool)
        pval[:n] = ~np.asarray(arr.is_null())
        kids = tuple(
            _np_col_from_arrow(arr.field(i), f.data_type, cap)
            for i, f in enumerate(dt.fields)
        )
        return DeviceColumn(dt, None, pval, None, kids)
    if isinstance(dt, (ArrayType, MapType)):
        return _np_list_from_arrow(arr, dt, cap)
    pdata, pval = _fixed_to_padded(arr, dt, cap)
    return DeviceColumn(dt, pdata, pval)


def _list_offsets(arr) -> np.ndarray:
    off_buf = arr.buffers()[1]
    off_dt = np.int64 if pa.types.is_large_list(arr.type) else np.int32
    return np.frombuffer(off_buf, dtype=off_dt)[arr.offset : arr.offset + len(arr) + 1]


def _np_list_from_arrow(arr, dt, cap: int) -> DeviceColumn:
    """List/Map arrow array → padded element-plane layout. The element plane
    is built by converting the (flat) child values, then gathering them into
    [cap, W] rows — the strings recipe generalized."""
    from ..types import ArrayType, MapType

    arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    n = len(arr)
    offsets = _list_offsets(arr)
    valid = np.zeros(cap, dtype=bool)
    valid[:n] = ~np.asarray(arr.is_null())
    lengths = np.zeros(cap, dtype=np.int32)
    lengths[:n] = np.where(valid[:n], offsets[1:] - offsets[:-1], 0)
    W = bucket_width(max(int(lengths.max()) if n else 0, 1))

    def plane(values: pa.Array, vdt) -> DeviceColumn:
        # child values carry the parent's slice offset via `offsets`
        vcap = bucket_capacity(max(len(values), 1))
        flat = _np_col_from_arrow(values, vdt, vcap)
        starts = offsets[:-1].astype(np.int64)
        cols_ix = np.arange(W, dtype=np.int64)[None, :]
        idx = np.zeros((cap, W), dtype=np.int64)
        idx[:n] = starts[:, None] + cols_ix
        mask = np.arange(W)[None, :] < lengths[:, None]
        idx = np.where(mask, np.clip(idx, 0, max(len(values) - 1, 0)), 0)
        d = flat.data[idx]  # [cap, W(, w)]
        if d.ndim == 3:
            d = np.where(mask[:, :, None], d, 0)
        else:
            d = np.where(mask, d, 0)
        v = np.where(mask, flat.validity[idx], False)
        ln = None
        if flat.lengths is not None:
            ln = np.where(mask, flat.lengths[idx], 0).astype(np.int32)
        return DeviceColumn(vdt, d, v, ln)

    if isinstance(dt, MapType):
        kids = (plane(arr.keys, dt.key_type), plane(arr.items, dt.value_type))
    else:
        kids = (plane(arr.values, dt.element_type),)
    return DeviceColumn(dt, None, valid, lengths, kids)


def host_to_device(
    rb: pa.RecordBatch,
    capacity: Optional[int] = None,
    str_widths: Optional[dict[int, int]] = None,
    max_str_bytes: Optional[int] = None,
) -> DeviceBatch:
    """Arrow RecordBatch (host currency) → DeviceBatch, padded to a bucketed
    capacity. Every buffer ships in ONE batched ``jax.device_put`` call —
    PJRT coalesces the transfers, so a slow link pays one round trip per
    batch instead of one per buffer. ``max_str_bytes``
    (spark.rapids.tpu.string.maxBytes) caps the padded string width the
    fixed-width layout will materialize."""
    import time as _time

    from ..obs import ledger as _ledger
    from ..obs import metrics as _metrics

    n = rb.num_rows
    cap = capacity or bucket_capacity(max(n, 1))
    schema = Schema.from_arrow(rb.schema)
    host_cols = []
    # padding to the bucketed capacity is host work worth attributing: the
    # shape-bucket lattice trades it for compile reuse, and the ledger's
    # exclusive `pad` phase (carved out of the enclosing h2d scope) is how
    # the trade stays measurable per query
    t0 = _time.perf_counter_ns()
    with _ledger.phase("pad"):
        for i, field in enumerate(schema):
            arr = rb.column(i)
            if isinstance(arr, pa.ChunkedArray):  # pragma: no cover - RecordBatch cols are flat
                arr = arr.combine_chunks()
            host_cols.append(
                _np_col_from_arrow(
                    arr,
                    field.data_type,
                    cap,
                    (str_widths or {}).get(i),
                    max_str_bytes,
                )
            )
    _metrics.GLOBAL.timer("batch.padTimeNs").add(
        _time.perf_counter_ns() - t0
    )
    num_rows, cols = jax.device_put((np.asarray(n, np.int32), host_cols))
    return DeviceBatch(schema, list(cols), num_rows)


def abstract_batch(
    schema: Schema, capacity: int, str_widths: Optional[dict] = None
) -> Optional[DeviceBatch]:
    """DeviceBatch pytree with ``jax.ShapeDtypeStruct`` leaves — the
    abstract input the kernel pre-compilation pass (plan/planner.py
    precompile_plan) lowers kernels against via ``GuardedJit.warm``. The
    treedef and leaf shapes match what ``host_to_device`` produces for the
    same geometry, so the warmed binary is the one the real batch hits.

    Returns None when the schema cannot be shaped statically: nested types
    (their element-plane widths are data-dependent) or a string column
    without a width hint in ``str_widths`` (column index → padded width).
    """
    from ..types import ArrayType, MapType, StructType

    S = jax.ShapeDtypeStruct
    cols = []
    for i, f in enumerate(schema):
        dt = f.data_type
        if isinstance(dt, (ArrayType, MapType, StructType)):
            return None
        if isinstance(dt, StringType):
            w = (str_widths or {}).get(i)
            if not w:
                return None
            cols.append(
                DeviceColumn(
                    dt,
                    S((capacity, int(w)), np.uint8),
                    S((capacity,), np.bool_),
                    S((capacity,), np.int32),
                )
            )
            continue
        if isinstance(dt, NullType):
            cols.append(
                DeviceColumn(dt, S((capacity,), np.int8), S((capacity,), np.bool_))
            )
            continue
        cols.append(
            DeviceColumn(
                dt, S((capacity,), dt.np_dtype), S((capacity,), np.bool_)
            )
        )
    return DeviceBatch(schema, cols, S((), np.int32))


def _pad8(nbytes: int) -> int:
    return (nbytes + 7) & ~7


def _pack_kernel(schema: Schema, cap: int, widths: tuple):
    """Cached device kernel: flatten a whole batch (row count + every data/
    validity/lengths buffer, each 8-byte aligned) into ONE uint8 vector —
    the contiguous-buffer D2H currency (reference: JCudfSerialization /
    GpuColumnVectorFromBuffer; here it buys one PJRT transfer per batch).

    float64 data buffers ride as separate raw leaves beside the flat vector:
    the TPU X64 emulation cannot bitcast 64-bit floats and recovering their
    bits arithmetically would canonicalize values the emulation flushes —
    a raw PJRT transfer is exact for whatever the device holds."""
    from .. import kernels as K

    return K.kernel(
        ("pack_d2h", schema, cap, widths), lambda: K.GuardedJit(_pack_pure)
    )


def _pack_to_bytes(flat):
    """1-D array → little-endian uint8 bytes. 64-bit ints split into
    (lo, hi) uint32 halves arithmetically (ops/bits.py): the TPU X64
    emulation can't width-change bitcast 64-bit types."""
    from ..ops.bits import i64_bytes_le

    if flat.dtype == jnp.bool_:
        return flat.astype(jnp.uint8)
    if flat.dtype in (jnp.dtype(jnp.int64), jnp.dtype(jnp.uint64)):
        return i64_bytes_le(flat)
    if flat.dtype != jnp.uint8:
        return jax.lax.bitcast_convert_type(flat, jnp.uint8).reshape(-1)
    return flat


def _pack_pure(batch: DeviceBatch):
    """The traceable pack body (shape-generic; callers cache per shape)."""
    parts = [_pack_to_bytes(batch.num_rows.astype(jnp.int64).reshape(1))]
    side: list[jax.Array] = []

    def add(arr):
        flat = _pack_to_bytes(arr.reshape(-1))
        pad = _pad8(flat.shape[0]) - flat.shape[0]
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros(pad, jnp.uint8)])
        parts.append(flat)

    for f, col in zip(batch.schema, batch.columns):
        # decode derives the layout from the SCHEMA; a drifted device dtype
        # would silently shift every later offset — fail at trace time
        assert col.data.dtype == _decode_np_dtype(f.data_type), (
            f.name,
            col.data.dtype,
            f.data_type,
        )
        assert (col.lengths is not None) == _has_lengths(f.data_type), f.name
        if col.data.dtype == jnp.dtype(jnp.float64):
            side.append(col.data.reshape(-1))
        else:
            add(col.data)
        add(col.validity.astype(jnp.uint8))
        if col.lengths is not None:
            add(col.lengths)
    # ONE f64 side leaf: each device_get leaf is its own transfer and
    # sync, so 8 float columns as 8 leaves stall dispatch 8 times
    side_cat = jnp.concatenate(side) if side else jnp.zeros(0, jnp.float64)
    return jnp.concatenate(parts), side_cat


SPEC_PULL_PREFIX = 8192


def device_to_host_speculative(batch: DeviceBatch):
    """ONE-transfer fetch for small results: pull (true row count, pack of
    the first SPEC_PULL_PREFIX rows) together; when the batch's live rows
    fit the prefix, that single round trip IS the result — the usual
    shrink-then-pull path pays two. Aggregate/TopN outputs (a handful of
    rows in a capacity-sized batch) are exactly this shape, and every
    round trip is a sync that stalls dispatch. Returns (record_batch, None)
    on success; (None, true_row_count) when the result does not fit so the
    caller can shrink WITHOUT re-paying the row-count sync; (None, None)
    for nested/small batches it does not handle."""
    cap = batch.capacity
    if cap <= SPEC_PULL_PREFIX or not batch.columns:
        return None, None
    if any(c.children is not None for c in batch.columns):
        return None, None
    from .. import kernels as K
    from ..ops.gather import gather_columns

    widths = tuple(
        c.data.shape[1] if c.data.ndim == 2 else None for c in batch.columns
    )

    def make():
        def run(b: DeviceBatch):
            idx = jnp.arange(SPEC_PULL_PREFIX, dtype=jnp.int32)
            cols = gather_columns(b.columns, idx)
            nb = DeviceBatch(
                b.schema, cols, jnp.minimum(b.num_rows, SPEC_PULL_PREFIX)
            )
            flat, side = _pack_pure(nb)
            # the TRUE row count rides as an extra 8-byte header word in the
            # SAME flat buffer — a separate leaf would be its own transfer,
            # defeating the one-transfer point
            true_hdr = _pack_to_bytes(b.num_rows.astype(jnp.int64).reshape(1))
            return jnp.concatenate([true_hdr, flat]), side

        return K.GuardedJit(run)

    kernel = K.kernel(("d2h_spec", batch.schema, cap, widths), make)
    flat, side = jax.device_get(kernel(batch))
    flat = np.asarray(flat)
    n_true = int(flat[:8].view(np.int64)[0])
    if n_true > SPEC_PULL_PREFIX:
        return None, n_true
    rb = _decode_packed(
        batch.schema,
        widths,
        SPEC_PULL_PREFIX,
        flat[8:],
        np.asarray(side),
    )
    return rb, None


def device_to_host(batch: DeviceBatch, shrink: bool = True) -> pa.RecordBatch:
    """DeviceBatch → Arrow RecordBatch sliced to live rows.

    The whole batch is packed on device into one flat buffer and fetched
    with a single transfer — one host sync, not one per buffer (per-column
    ``np.asarray`` syncs once per column). Pass ``shrink=False`` when the
    caller already re-bucketed the batch (DeviceToHostExec bulk-shrinks a
    window of batches with one row-count sync — the per-batch sync here
    would stall dispatch a second time)."""
    cap = batch.capacity
    if cap == 0:
        return pa.RecordBatch.from_arrays(
            [pa.array([], type=f.data_type.to_arrow()) for f in batch.schema],
            schema=batch.schema.to_arrow(),
        )
    if shrink and cap > MIN_CAPACITY:
        # never ship padding over a slow link: re-bucket to the live rows
        # first (one row-count round trip buys skipping up to cap-n rows
        # of every buffer)
        from ..ops.gather import shrink_one

        batch = shrink_one(batch, batch.row_count())
        cap = batch.capacity
    if any(c.children is not None for c in batch.columns):
        # nested columns: fetch the whole pytree in one device_get and
        # rebuild arrow recursively (the flat pack layout is for the common
        # primitive/string case)
        num_rows, host_cols = jax.device_get((batch.num_rows, batch.columns))
        n = int(num_rows)
        arrays = [
            _arrow_from_np_col(c, f.data_type, n)
            for f, c in zip(batch.schema, host_cols)
        ]
        return pa.RecordBatch.from_arrays(arrays, schema=batch.schema.to_arrow())
    widths = tuple(
        c.data.shape[1] if c.data.ndim == 2 else None for c in batch.columns
    )
    flat, side = jax.device_get(_pack_kernel(batch.schema, cap, widths)(batch))
    return _decode_packed(
        batch.schema, widths, cap, np.asarray(flat), np.asarray(side)
    )


def _decode_np_dtype(dt: DataType) -> "np.dtype":
    """Device storage dtype of a flat column (strings ride as uint8 byte
    matrices; everything else stores its np_dtype)."""
    if isinstance(dt, StringType):
        return np.dtype(np.uint8)
    return np.dtype(dt.np_dtype)


def _has_lengths(dt: DataType) -> bool:
    return isinstance(dt, StringType)


def _decode_packed(
    schema: Schema, widths: tuple, cap: int, flat: "np.ndarray", side: "np.ndarray"
) -> pa.RecordBatch:
    """Host-side decode of _pack_pure's flat layout → Arrow RecordBatch."""
    n = int(flat[:8].view(np.int64)[0])
    off = 8
    side_off = 0
    host_cols: list[DeviceColumn] = []
    for f, w in zip(schema, widths):
        np_dt = _decode_np_dtype(f.data_type)
        if np_dt == np.dtype(np.float64):
            count = cap * (w or 1)
            data = side[side_off : side_off + count]
            if w:
                data = data.reshape(cap, w)
            side_off += count
        else:
            itemsize = np_dt.itemsize
            count = cap * (w or 1)
            nbytes = count * itemsize
            data = flat[off : off + nbytes].view(np_dt)
            data = data.reshape(cap, w) if w else data
            off += _pad8(nbytes)
        validity = flat[off : off + cap].view(np.bool_)
        off += _pad8(cap)
        lengths = None
        if _has_lengths(f.data_type):
            lengths = flat[off : off + cap * 4].view(np.int32)
            off += _pad8(cap * 4)
        host_cols.append(DeviceColumn(f.data_type, data, validity, lengths))
    arrays: list[pa.Array] = []
    fields: list[pa.Field] = []
    for f, col in zip(schema, host_cols):
        dt = f.data_type
        valid = np.asarray(col.validity)[: max(n, 0)].astype(bool)
        if isinstance(dt, StringType):
            data = np.asarray(col.data)
            lengths = np.asarray(col.lengths)
            arr = _padded_to_string(data, lengths, np.asarray(col.validity), n)
        elif isinstance(dt, NullType):
            arr = pa.nulls(n)
        else:
            data = np.asarray(col.data)[:n]
            if isinstance(dt, DecimalType):
                # data holds unscaled int64; rebuild decimals by value.
                import decimal as _dec

                scale = dt.scale
                py = [
                    None if not v else _dec.Decimal(int(x)).scaleb(-scale)
                    for x, v in zip(data.tolist(), valid.tolist())
                ]
                arr = pa.array(py, type=pa.decimal128(dt.precision, dt.scale))
            else:
                mask = None if valid.all() else ~valid
                arr = pa.array(data, type=dt.to_arrow(), from_pandas=False, mask=mask)
        arrays.append(arr)
        fields.append(pa.field(f.name, dt.to_arrow(), f.nullable))
    return pa.RecordBatch.from_arrays(arrays, schema=pa.schema(fields))


def _arrow_from_np_col(col: DeviceColumn, dt: DataType, n: int) -> pa.Array:
    """Host-side (numpy-leaf) DeviceColumn → arrow array of n rows.
    Recursive inverse of _np_col_from_arrow."""
    from ..types import ArrayType, MapType, StructType

    valid = np.asarray(col.validity)[:n].astype(bool)
    null_mask = None if valid.all() else ~valid
    if isinstance(dt, StringType):
        return _padded_to_string(
            np.asarray(col.data), np.asarray(col.lengths), np.asarray(col.validity), n
        )
    if isinstance(dt, NullType):
        return pa.nulls(n)
    if isinstance(dt, StructType):
        kids = [
            _arrow_from_np_col(c, f.data_type, n)
            for c, f in zip(col.children, dt.fields)
        ]
        return pa.StructArray.from_arrays(
            kids,
            fields=[pa.field(f.name, f.data_type.to_arrow(), f.nullable) for f in dt.fields],
            mask=pa.array(~valid) if null_mask is not None else None,
        )
    if isinstance(dt, (ArrayType, MapType)):
        lengths = np.where(valid, np.asarray(col.lengths)[:n], 0).astype(np.int64)
        offsets = np.zeros(n + 1, dtype=np.int32)
        offsets[1:] = np.cumsum(lengths)
        W = col.children[0].data.shape[1] if col.children[0].data is not None else 0
        take = np.arange(W)[None, :] < lengths[:, None]

        def flatten_plane(plane: DeviceColumn, vdt) -> pa.Array:
            total = int(lengths.sum())
            d = np.asarray(plane.data)[:n]
            v = np.asarray(plane.validity)[:n]
            fdata = d[take]  # [total(, w)]
            fvalid = v[take]
            flen = (
                np.asarray(plane.lengths)[:n][take]
                if plane.lengths is not None
                else None
            )
            fcol = DeviceColumn(vdt, fdata, fvalid, flen)
            return _arrow_from_np_col(fcol, vdt, total)

        # a null offset marks a null list (arrow from_arrays convention)
        offs = pa.array(
            offsets,
            type=pa.int32(),
            mask=np.append(~valid, False) if null_mask is not None else None,
        )
        if isinstance(dt, MapType):
            keys = flatten_plane(col.children[0], dt.key_type)
            items = flatten_plane(col.children[1], dt.value_type)
            return pa.MapArray.from_arrays(offs, keys, items)
        values = flatten_plane(col.children[0], dt.element_type)
        return pa.ListArray.from_arrays(offs, values)
    data = np.asarray(col.data)[:n]
    if isinstance(dt, DecimalType):
        import decimal as _dec

        scale = dt.scale
        py = [
            None if not v else _dec.Decimal(int(x)).scaleb(-scale)
            for x, v in zip(data.tolist(), valid.tolist())
        ]
        return pa.array(py, type=pa.decimal128(dt.precision, dt.scale))
    return pa.array(data, type=dt.to_arrow(), from_pandas=False, mask=null_mask)


def _empty_col(dt: DataType, capacity: int, plane_w: Optional[int] = None) -> DeviceColumn:
    from ..types import ArrayType, MapType, StructType

    shape = (capacity,) if plane_w is None else (capacity, plane_w)
    valid = jnp.zeros(shape, dtype=bool)
    if isinstance(dt, StringType):
        return DeviceColumn(
            dt,
            jnp.zeros(shape + (MIN_STR_WIDTH,), dtype=jnp.uint8),
            valid,
            jnp.zeros(shape, dtype=jnp.int32),
        )
    if isinstance(dt, StructType):
        kids = tuple(_empty_col(f.data_type, capacity, plane_w) for f in dt.fields)
        return DeviceColumn(dt, None, valid, None, kids)
    if isinstance(dt, ArrayType):
        kid = _empty_col(dt.element_type, capacity, 1)
        return DeviceColumn(dt, None, valid, jnp.zeros(shape, jnp.int32), (kid,))
    if isinstance(dt, MapType):
        kids = (
            _empty_col(dt.key_type, capacity, 1),
            _empty_col(dt.value_type, capacity, 1),
        )
        return DeviceColumn(dt, None, valid, jnp.zeros(shape, jnp.int32), kids)
    return DeviceColumn(dt, jnp.zeros(shape, dtype=dt.np_dtype), valid)


def empty_batch(schema: Schema, capacity: int = MIN_CAPACITY) -> DeviceBatch:
    cols = [_empty_col(f.data_type, capacity) for f in schema]
    return DeviceBatch(schema, cols, jnp.asarray(0, dtype=jnp.int32))
