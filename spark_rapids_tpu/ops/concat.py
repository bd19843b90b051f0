"""Device batch concatenation — the Table.concatenate analogue used by the
aggregate merge loop, sort, and shuffle coalesce (reference:
GpuCoalesceBatches.scala:133-455, aggregate.scala:451).

Static shapes: the output capacity is the bucketed sum of input capacities
(a trace-time constant). Live rows are a prefix of every input, so each
input lands as one contiguous block: its dead rows are zeroed and the whole
plane is copied (``lax.dynamic_update_slice``) to an offset carried as a
device scalar, the running sum of ``num_rows`` — no host syncs and no index
scatter, which this chip runs as a serial loop per element (ops/scan.py).
Input k+1 starts over input k's zeroed tail, so the copies run in input
order. Nested columns (arrays/structs/maps) concatenate recursively along
the row axis with padded-plane width alignment.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..columnar.device import DeviceBatch, DeviceColumn, bucket_capacity
from .. import kernels as K


def _pad_axes(data: jax.Array, shape: tuple) -> jax.Array:
    """Zero-pad trailing axes of ``data`` (beyond axis 0) up to ``shape``."""
    pads = [(0, 0)]
    for have, want in zip(data.shape[1:], shape):
        pads.append((0, want - have))
    if any(p[1] for p in pads):
        return jnp.pad(data, pads)
    return data


def _plane_shape(cols: list[jax.Array]) -> tuple:
    """Max trailing-axes shape across inputs (W / string width alignment)."""
    ndim = cols[0].ndim
    return tuple(
        max(c.shape[ax] for c in cols) for ax in range(1, ndim)
    )


def _copy_rows(dst: jax.Array, src: jax.Array, offset) -> jax.Array:
    """Copy all of src's rows into dst as one block starting at row
    ``offset`` (traced). dynamic_update_slice would move a start back so
    that the block fits; none is ever moved, because ``offset`` is at most
    the capacities of the inputs before this one and dst holds the sum of
    all of them (concat_device)."""
    # under x64 a bare 0 is int64 and mixed index types are refused
    start = (offset,) + (jnp.zeros((), offset.dtype),) * (src.ndim - 1)
    return jax.lax.dynamic_update_slice(dst, src, start)


def _concat_plane(planes: list[jax.Array], lives: list[jax.Array], offsets, cap):
    """Concat one leaf plane (data/validity/lengths, any trailing shape)."""
    trail = _plane_shape(planes)
    dst = jnp.zeros((cap,) + trail, dtype=planes[0].dtype)
    for p, live, off in zip(planes, lives, offsets):
        p = _pad_axes(p, trail)
        mask = live.reshape((-1,) + (1,) * (p.ndim - 1))
        p = jnp.where(mask, p, jnp.zeros_like(p))
        dst = _copy_rows(dst, p, off)
    return dst


def _concat_col(cols: list[DeviceColumn], lives, offsets, cap) -> DeviceColumn:
    dt = cols[0].dtype
    data = (
        _concat_plane([c.data for c in cols], lives, offsets, cap)
        if cols[0].data is not None
        else None
    )
    validity = _concat_plane([c.validity for c in cols], lives, offsets, cap)
    lengths = (
        _concat_plane([c.lengths for c in cols], lives, offsets, cap)
        if cols[0].lengths is not None
        else None
    )
    children = None
    if cols[0].children is not None:
        children = tuple(
            _concat_col([c.children[k] for c in cols], lives, offsets, cap)
            for k in range(len(cols[0].children))
        )
    return DeviceColumn(dt, data, validity, lengths, children)


def _col_shape_sig(c: DeviceColumn):
    return (
        None if c.data is None else c.data.shape,
        None if c.lengths is None else True,
        None if c.children is None else tuple(_col_shape_sig(k) for k in c.children),
    )


def concat_device(batches: list[DeviceBatch]) -> DeviceBatch:
    """Concatenate device batches (same schema) into one batch whose
    capacity is the bucketed sum of theirs: the live rows of each input, in
    input order, then zeroed dead rows. ONE fused jitted program per
    (schema, input shapes), cached module-wide; eager per-column copies
    would dispatch hundreds of tiny ops per call."""
    assert batches, "concat of zero batches"
    if len(batches) == 1:
        return batches[0]
    batches = _colocate(batches)
    schema = batches[0].schema
    # at least the sum of the capacities: _copy_rows relies on it
    cap = bucket_capacity(sum(b.capacity for b in batches))
    shapes = tuple(tuple(_col_shape_sig(c) for c in b.columns) for b in batches)

    def build():
        def _concat(bs):  # a device trace names the module after it
            return _concat_impl(list(bs), cap)

        return K.GuardedJit(_concat)

    fn = K.kernel(("concat", schema, shapes, cap), build)
    return fn(tuple(batches))


def _colocate(batches: list[DeviceBatch]) -> list[DeviceBatch]:
    """Mesh mode gathers batches produced on different chips (coalesce /
    sort merge / broadcast build); XLA requires one device per program, so
    stragglers move to the first batch's device. Single-device mode: no-op
    (metadata check only, no transfer)."""

    def dev_of(b):
        if not b.columns:
            return None
        data = b.columns[0].data
        devices = getattr(data, "devices", None)
        if devices is None:
            return None  # tracer / non-committed value
        try:
            return next(iter(devices()))
        except Exception:
            return None
    devs = [dev_of(b) for b in batches]
    real = [d for d in devs if d is not None]
    if len(set(real)) <= 1:
        return batches
    target = real[0]
    return [
        b if d is None or d == target else jax.device_put(b, target)
        for b, d in zip(batches, devs)
    ]


def _concat_impl(batches: list[DeviceBatch], cap: int) -> DeviceBatch:
    schema = batches[0].schema
    lives = [
        jnp.arange(b.capacity, dtype=jnp.int32) < b.num_rows for b in batches
    ]
    offsets = []
    off = jnp.asarray(0, jnp.int32)
    for b in batches:
        offsets.append(off)
        off = off + b.num_rows
    out_cols = [
        _concat_col([b.columns[i] for b in batches], lives, offsets, cap)
        for i in range(len(schema))
    ]
    total = jnp.asarray(0, jnp.int32)
    for b in batches:
        total = total + b.num_rows
    return DeviceBatch(schema, out_cols, total)
