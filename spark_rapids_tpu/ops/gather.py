"""Row gather/compaction primitives over DeviceBatch — the analogues of
cudf's gather / Table.filter (reference: basicPhysicalOperators.scala
GpuFilterExec; Table.filter applies a boolean-mask gather).

All static shapes: compaction permutes kept rows to the front of the same
capacity and updates the device-resident ``num_rows``; downstream kernels
mask by ``row_mask()``.

Planes that are gathered through one index are stacked and gathered once
(``gather_planes``): a v5e charges a gather by the index, 8.7 ns each, not by
the byte — eight uint32 planes of 2^23 rows take 931 ms as eight gathers and
124 ms as one gather of their stack (PERF.md section 6, PR 28 and PR 33).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp

from ..columnar.device import DeviceBatch, DeviceColumn, dc_replace
from .scan import first_k_positions

#: a stack holds at most this many bytes, of its planes or of what it gathers,
#: so that a wide batch's temporaries stay a fraction of HBM (PERF.md section
#: 6, PR 33: q67's 2^23-row batches)
_STACK_BYTES = 1 << 28
#: stacking copies every row of its planes and saves a gather's cost an
#: index: below one index in this many rows the copy costs more than it saves
_ROWS_PER_INDEX = 256

_COUNT = threading.local()


@contextlib.contextmanager
def counting_gathers():
    """Collect ``[planes handed over, gathers issued]`` of every
    ``gather_planes`` traced on this thread inside the block (the launch
    counters ``gather.planes`` and ``gather.launches`` read it once per
    kernel and input signature, under ``jax.eval_shape``)."""
    _COUNT.count = count = [0, 0]
    try:
        yield count
    finally:
        _COUNT.count = None


def _pack_bits(flags: Sequence[jax.Array]) -> jax.Array:
    """Up to 32 bool planes as the bits of one uint32 plane."""
    word = flags[0].astype(jnp.uint32)
    for k, f in enumerate(flags[1:], 1):
        word = word | (f.astype(jnp.uint32) << k)
    return word


def _view(plane: jax.Array, dtype) -> jax.Array:
    return plane if plane.dtype == dtype else jax.lax.bitcast_convert_type(plane, dtype)


def _halves(plane: jax.Array) -> list[jax.Array]:
    """A 64-bit integer plane as its high and low uint32 planes: what the
    chip holds it as, so the split and ``_whole`` cost nothing there."""
    return [(plane >> 32).astype(jnp.uint32), plane.astype(jnp.uint32)]


def _whole(high: jax.Array, low: jax.Array, dtype) -> jax.Array:
    both = (high.astype(jnp.uint64) << 32) | low.astype(jnp.uint64)
    return both.astype(dtype)


def _gather_rows(rows: list[jax.Array], idx: jax.Array, most: int, dtype) -> list[jax.Array]:
    """``[r[idx] for r in rows]`` for 1-D planes of one width, ``most`` to a
    ``[k, rows]`` stack of ``dtype`` and a gather a stack. Either way of
    stacking compiles to the same thing on the chip: rows minor, tiles of
    ``(k, 128)``."""
    out: list[jax.Array] = []
    for lo in range(0, len(rows), most):
        part = rows[lo: lo + most]
        _count_launch()
        if len(part) == 1:
            out.append(part[0][idx])
        else:
            g = jnp.stack([_view(p, dtype) for p in part])[:, idx]
            out.extend(_view(g[k], p.dtype) for k, p in enumerate(part))
    return out


def _gather_wide(planes: list[jax.Array], idx: jax.Array, most_bytes: int) -> list[jax.Array]:
    """``[p[idx] for p in planes]`` for planes of one dtype with further axes
    (a string's bytes, a list's elements): side by side along one flattened
    trailing axis, a gather a stack of at most ``most_bytes`` a row."""
    out: list[jax.Array] = []
    part: list[jax.Array] = []

    def flush():
        _count_launch()
        if len(part) == 1:
            out.append(part[0][idx])
        else:
            flat = [p.reshape(p.shape[0], -1) for p in part]
            g = jnp.concatenate(flat, axis=1)[idx]
            at = 0
            for p, f in zip(part, flat):
                w = f.shape[1]
                out.append(g[..., at: at + w].reshape(idx.shape + p.shape[1:]))
                at += w
        part.clear()

    row_bytes = 0
    for p in planes:
        b = (p.size // max(p.shape[0], 1)) * p.dtype.itemsize
        if part and row_bytes + b > most_bytes:
            flush()
            row_bytes = 0
        part.append(p)
        row_bytes += b
    if part:
        flush()
    return out


def _count_launch():
    count = getattr(_COUNT, "count", None)
    if count is not None:
        count[1] += 1


def gather_planes(planes: Sequence[Optional[jax.Array]], idx: jax.Array) -> list:
    """``[p[idx] for p in planes]``, bit for bit, in as few gathers as the
    planes make stacks. Planes share their row axis (axis 0); ``None`` stays
    ``None`` and an array handed over twice is gathered once.

    What shares a stack is decided by what the planes are: ``[rows]`` planes
    of one dtype stack as ``[k, rows]``. The 4-byte dtypes (int32, uint32,
    float32) share one uint32 stack through a bitcast; a 64-bit integer
    plane rides in it as its two halves, and bool planes (validity) as the
    bits of uint32 planes, 32 to a plane. float64 keeps a stack of its own
    (the chip holds it as two float32 arrays and cannot bitcast it: two
    gathers a stack). Planes with further axes (string bytes ``[rows, w]``,
    list elements) lie side by side along them, by dtype. A stack of one
    plane is the plane: no copy is made. A stack is bounded in bytes, and
    where the index is far shorter than the rows every plane is a stack of
    its own: copying the rows would cost more than it saves.
    """
    planes = list(planes)
    idx = jnp.asarray(idx)
    out: list = [None] * len(planes)
    seen: dict[int, int] = {}  # id(plane) -> where it first stands
    again: list[tuple[int, int]] = []
    flags: list[int] = []  # positions of the bool [rows] planes
    words: list[int] = []  # of the 4-byte [rows] planes
    longs: list[int] = []  # of the 64-bit integer [rows] planes
    flat: dict = {}  # dtype -> positions of the other [rows] planes
    wide: dict = {}  # dtype -> positions of planes with further axes
    for pos, p in enumerate(planes):
        if p is None:
            continue
        if id(p) in seen:
            again.append((pos, seen[id(p)]))
            continue
        seen[id(p)] = pos
        if p.ndim > 1:
            wide.setdefault(p.dtype, []).append(pos)
        elif p.dtype == jnp.bool_:
            flags.append(pos)
        elif p.dtype.itemsize == 4:
            words.append(pos)
        elif p.dtype in (jnp.int64, jnp.uint64):
            longs.append(pos)
        else:
            flat.setdefault(p.dtype, []).append(pos)
    count = getattr(_COUNT, "count", None)
    if count is not None:
        count[0] += len(seen)
    if not seen:
        return out

    rows = planes[next(iter(seen.values()))].shape[0]
    if idx.size * _ROWS_PER_INDEX < rows:
        stack_bytes = 0  # every plane alone
    else:
        stack_bytes = _STACK_BYTES // max(rows, idx.size, 1)  # a row of a stack

    # a plane with nothing to ride with is gathered as it is
    stacking = stack_bytes >= 8
    if not stacking or (len(longs) == 1 and not (words or flags)):
        for pos in longs:
            flat.setdefault(planes[pos].dtype, []).append(pos)
        longs = []
    if len(flags) == 1 and not (stacking and (words or longs)):
        flat[planes[flags[0]].dtype] = flags
        flags = []
    if words or longs or flags:
        rows32 = [planes[pos] for pos in words]
        for pos in longs:
            rows32 += _halves(planes[pos])
        rows32 += [
            _pack_bits([planes[pos] for pos in flags[lo: lo + 32]])
            for lo in range(0, len(flags), 32)
        ]
        g = _gather_rows(rows32, idx, max(stack_bytes // 4, 1), jnp.uint32)
        for pos, r in zip(words, g):
            out[pos] = r
        at = len(words)
        for pos in longs:
            out[pos] = _whole(g[at], g[at + 1], planes[pos].dtype)
            at += 2
        for k, pos in enumerate(flags):
            out[pos] = ((g[at + k // 32] >> (k % 32)) & 1).astype(jnp.bool_)
    for dt, poss in flat.items():
        # the chip gathers a 64-bit stack as two 32-bit ones, one after the other
        most = max(stack_bytes // min(dt.itemsize, 4), 1)
        for pos, r in zip(poss, _gather_rows([planes[pos] for pos in poss], idx, most, dt)):
            out[pos] = r
    for dt, poss in wide.items():
        g = _gather_wide([planes[pos] for pos in poss], idx, stack_bytes)
        for pos, r in zip(poss, g):
            out[pos] = r
    for pos, src in again:
        out[pos] = out[src]
    return out


def _planes_of(col: DeviceColumn, into: list) -> None:
    into += [col.data, col.validity, col.lengths]
    for child in col.children or ():  # nested planes share the row axis
        _planes_of(child, into)


def _column_of(col: DeviceColumn, planes: Iterator) -> DeviceColumn:
    data, validity, lengths = next(planes), next(planes), next(planes)
    children = None
    if col.children is not None:
        children = tuple(_column_of(c, planes) for c in col.children)
    return DeviceColumn(col.dtype, data, validity, lengths, children)


def gather_columns(
    cols: Sequence[DeviceColumn], idx: jax.Array, idx_valid=None
) -> list[DeviceColumn]:
    """Every plane of every column (data, validity, lengths, children) through
    ``idx`` in as few gathers as they make stacks (``gather_planes``)."""
    planes: list = []
    for c in cols:
        _planes_of(c, planes)
    gathered = iter(gather_planes(planes, idx))
    out = [_column_of(c, gathered) for c in cols]
    if idx_valid is not None:
        out = [dc_replace(c, validity=c.validity & idx_valid) for c in out]
    return out


def gather_column(col: DeviceColumn, idx: jax.Array, idx_valid=None) -> DeviceColumn:
    return gather_columns([col], idx, idx_valid)[0]


def gather_batch(batch: DeviceBatch, idx: jax.Array, new_num_rows) -> DeviceBatch:
    cols = gather_columns(batch.columns, idx)
    return DeviceBatch(batch.schema, cols, jnp.asarray(new_num_rows, jnp.int32))


def shrink_one(batch: DeviceBatch, n: int, tight: bool = True) -> DeviceBatch:
    """Re-bucket a batch to the capacity its ``n`` live rows need (no-op when
    already tight). Cached fused kernel per (schema, in-cap, out-cap).

    ``tight=True`` (default) uses the raw pow-2 capacity, ignoring the
    shape-bucket lattice: footprint-critical sites (pre-merge concat, OOM
    split/retry, exchange slicing) need tiny batches to actually BE tiny —
    a 1024-row lattice floor would make shrinking a no-op for exactly the
    13-group partial-aggregate outputs it exists for. ``tight=False``
    quantizes to the lattice instead: the local D2H pack window uses it so
    collect-tail pack kernels keep ONE stable geometry per bucket (still
    cutting a 512k-capacity sparse batch to the floor) instead of
    compiling per live-row count."""
    from ..columnar.device import bucket_capacity, tight_capacity
    from .. import kernels as K

    cap2 = (tight_capacity if tight else bucket_capacity)(max(n, 1))
    if cap2 >= batch.capacity:
        return batch

    def make():
        def _shrink(b):  # a device trace names the module after it
            return gather_batch(b, jnp.arange(cap2, dtype=jnp.int32), b.num_rows)

        return _shrink

    fn = K.counted_kernel(("shrink", batch.schema, batch.capacity, cap2), make)
    return fn(batch)


def bulk_shrink(
    batches: list[DeviceBatch], tight: bool = True
) -> list[DeviceBatch]:
    """Re-bucket batches whose live prefix is much smaller than capacity
    (partial-aggregate outputs, selective filters). ONE bulk row-count fetch
    for the whole list — the work feeding every batch is already dispatched
    asynchronously, so the wait overlaps all of it instead of serializing
    per batch. Downstream kernels (exchange slicing, concat, merge sort,
    D2H packing) then compile and run at the small capacities. ``tight``
    forwards to ``shrink_one`` (lattice-quantized vs raw pow-2 targets)."""
    import numpy as np

    if not batches:
        return batches
    try:
        same_dev = (
            len({next(iter(b.num_rows.devices())) for b in batches}) <= 1
        )
    except Exception:
        same_dev = True
    if same_dev:
        # stack the device scalars so the host fetch is ONE array transfer
        counts = np.asarray(jnp.stack([b.num_rows for b in batches]))
    else:
        # mesh mode gathers batches from several chips: device_get pipelines
        # the per-device pulls (copy_to_host_async per leaf)
        counts = np.asarray(jax.device_get([b.num_rows for b in batches]))
    return [shrink_one(b, int(n), tight) for b, n in zip(batches, counts)]


def partition_slices(batch: DeviceBatch, pids: jax.Array, nparts: int,
                     live=None) -> list[DeviceBatch]:
    """Slice a batch into per-partition batches with ONE stable sort by
    partition id instead of ``nparts`` compaction sorts (the exchange's
    hot path; a fused filter predicate rides in as ``live``). Sorted rows
    for partition p occupy [bounds[p], bounds[p+1]); each slice gathers
    its shifted window at full capacity (static shapes)."""
    cap = batch.capacity
    if live is None:
        live = batch.row_mask()
    else:
        live = live & batch.row_mask()
    key = jnp.where(live, pids.astype(jnp.int32), nparts).astype(jnp.uint32)
    iota = jnp.arange(cap, dtype=jnp.int32)
    _, order = jax.lax.sort((key, iota), num_keys=1, is_stable=True)
    skey = key[order].astype(jnp.int32)
    bounds = jnp.searchsorted(
        skey, jnp.arange(nparts + 1, dtype=jnp.int32)
    ).astype(jnp.int32)
    outs = []
    for p in range(nparts):
        start = bounds[p]
        cnt = bounds[p + 1] - start
        # compose through the cheap int32 permutation: ONE wide gather per
        # slice straight from the input, no intermediate sorted copy
        row_idx = order[jnp.clip(start + iota, 0, cap - 1)]
        sb = gather_batch(batch, row_idx, cnt)
        live_p = iota < cnt
        cols = [
            dc_replace(c, validity=c.validity & live_p) for c in sb.columns
        ]
        outs.append(DeviceBatch(sb.schema, cols, cnt))
    return outs


def compact_permutation(keep: jax.Array) -> jax.Array:
    """Stable compaction permutation: position k holds the row index of the
    k-th kept row. One single-key stable sort — measured 3.3x FASTER than
    the cumsum+searchsorted formulation on TPU (XLA's searchsorted
    lowering loses to the sorting network at 2M rows: 406ms vs 122ms).
    Sorted with an int32 iota: ``jnp.argsort`` under x64 carries an int64
    one, which doubles what the TPU compiler spends on the sort."""
    return first_k_positions(keep)


def compact(batch: DeviceBatch, keep: jax.Array) -> DeviceBatch:
    """Stable-compact rows where ``keep`` (bool[cap]) into the prefix."""
    keep = keep & batch.row_mask()
    perm = compact_permutation(keep)
    n = keep.sum().astype(jnp.int32)
    out = gather_batch(batch, perm, n)
    # zero validity in the tail so padding rows are inert and deterministic
    live = jnp.arange(batch.capacity, dtype=jnp.int32) < n
    cols = [
        dc_replace(c, validity=c.validity & live)
        for c in out.columns
    ]
    return DeviceBatch(out.schema, cols, n)
