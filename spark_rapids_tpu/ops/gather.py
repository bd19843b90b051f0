"""Row gather/compaction primitives over DeviceBatch — the analogues of
cudf's gather / Table.filter (reference: basicPhysicalOperators.scala
GpuFilterExec; Table.filter applies a boolean-mask gather).

All static shapes: compaction permutes kept rows to the front of the same
capacity and updates the device-resident ``num_rows``; downstream kernels
mask by ``row_mask()``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..columnar.device import DeviceBatch, DeviceColumn, dc_replace
from .scan import first_k_positions


def gather_column(col: DeviceColumn, idx: jax.Array, idx_valid=None) -> DeviceColumn:
    data = col.data[idx] if col.data is not None else None
    validity = col.validity[idx]
    if idx_valid is not None:
        validity = validity & idx_valid
    lengths = col.lengths[idx] if col.lengths is not None else None
    children = None
    if col.children is not None:  # nested planes share the row axis
        children = tuple(gather_column(c, idx) for c in col.children)
    return DeviceColumn(col.dtype, data, validity, lengths, children)


def gather_batch(batch: DeviceBatch, idx: jax.Array, new_num_rows) -> DeviceBatch:
    cols = [gather_column(c, idx) for c in batch.columns]
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

    def build():
        def _shrink(b):  # a device trace names the module after it
            return gather_batch(b, jnp.arange(cap2, dtype=jnp.int32), b.num_rows)

        return K.GuardedJit(_shrink)

    fn = K.kernel(("shrink", batch.schema, batch.capacity, cap2), build)
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
