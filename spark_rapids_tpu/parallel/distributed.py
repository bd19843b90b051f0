"""Multi-chip distributed execution over a JAX device mesh.

The reference scales with one GPU per Spark executor and moves shuffle
partitions over UCX (SURVEY.md §2.7). The TPU-native equivalent keeps the
same logical dataflow — partial aggregate → hash-partition exchange → final
aggregate — but maps it onto a ``jax.sharding.Mesh``: rows are data-parallel
across chips, the exchange is a single fused ``lax.all_to_all`` over ICI
(replacing the UCX tag-matched sends + bounce buffers), and the whole
partial→exchange→final step compiles to ONE XLA program. This is the
dataflow TPC-H/DS group-bys execute on a pod.

Everything is static-shape: each chip sends a fixed-capacity bucket to every
other chip; live counts ride as per-bucket scalars.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..columnar.device import DeviceBatch, DeviceColumn
from ..ops.aggregate import group_aggregate
from ..ops.hash import murmur3_rows, partition_ids
from ..types import Schema


def make_mesh(n_devices: int, axis: str = "dp") -> Mesh:
    devs = np.array(jax.devices()[:n_devices])
    return Mesh(devs.reshape(n_devices), (axis,))


def distributed_group_sum_step(mesh: Mesh, axis: str = "dp") -> Callable:
    """Build a jitted distributed step: per-chip partial group-sum →
    all_to_all hash exchange over ICI → per-chip final merge.

    Input (sharded along rows over ``axis``):
      keys   int[N]    group keys
      valid  bool[N]   key validity
      vals   val[N]    values to sum
      vvalid bool[N]
      num_rows int32[n_chips]  live rows per shard

    Output (sharded): per-chip final (keys, sums, counts, num_groups).
    """
    n = mesh.devices.size

    def per_chip(keys, kvalid, vals, vvalid, num_rows):
        # shard_map passes per-chip row slices; num_rows is [1] per chip
        nrows = num_rows[0]
        cap = keys.shape[0]
        from ..types import LONG

        kcol = DeviceColumn(LONG, keys.astype(jnp.int64), kvalid)
        vcol = DeviceColumn(LONG, vals.astype(jnp.int64), vvalid)
        ccol = DeviceColumn(LONG, jnp.ones(cap, jnp.int64), jnp.ones(cap, bool))
        out_keys, out_aggs, num_groups = group_aggregate(
            _mini_batch([kcol], nrows), [0], [vcol, ccol], ["sum", "sum"]
        )
        gk, gs, gc = out_keys[0], out_aggs[0], out_aggs[1]
        glive = jnp.arange(cap, dtype=jnp.int32) < num_groups

        # ── exchange: bucket groups by murmur3(key) % n over ICI ─────────
        h = murmur3_rows(jnp, [(LONG, gk.data, gk.validity, None)], cap)
        pid = partition_ids(jnp, h, n)
        pid = jnp.where(glive, pid, n)  # dead groups → no bucket
        bucket_cap = cap  # safe upper bound
        # slot within destination bucket: stable sort by pid, rank inside
        order = jnp.argsort(pid, stable=True)
        sorted_pid = pid[order]
        start = jnp.searchsorted(sorted_pid, jnp.arange(n + 1))
        rank_sorted = jnp.arange(cap) - start[jnp.clip(sorted_pid, 0, n)]
        slot = jnp.zeros(cap, jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))

        def scatter(vals_, fill):
            # dead groups carry pid == n (out of bounds) → mode="drop"
            # discards them instead of clobbering a live slot
            buf = jnp.full((n, bucket_cap), fill, dtype=vals_.dtype)
            return buf.at[pid, slot].set(vals_, mode="drop")

        sk = scatter(gk.data, jnp.int64(0))
        skv = scatter(gk.validity & glive, False)
        sv = scatter(jnp.where(gs.validity, gs.data, 0), jnp.int64(0))
        svv = scatter(gs.validity & glive, False)
        sc = scatter(jnp.where(gc.validity, gc.data, 0), jnp.int64(0))
        slive = scatter(glive, False)

        # single fused all-to-all per buffer (the ICI shuffle): row block i
        # of the [n, bucket_cap] send buffer goes to chip i
        rk = jax.lax.all_to_all(sk, axis, 0, 0, tiled=True)
        rkv = jax.lax.all_to_all(skv, axis, 0, 0, tiled=True)
        rv = jax.lax.all_to_all(sv, axis, 0, 0, tiled=True)
        rvv = jax.lax.all_to_all(svv, axis, 0, 0, tiled=True)
        rc = jax.lax.all_to_all(sc, axis, 0, 0, tiled=True)
        rlive = jax.lax.all_to_all(slive, axis, 0, 0, tiled=True)

        # flatten received buckets, compact live rows, final merge aggregate
        fk, fkv = rk.reshape(-1), rkv.reshape(-1)
        fv, fvv = rv.reshape(-1), rvv.reshape(-1)
        fc = rc.reshape(-1)
        flive = rlive.reshape(-1)
        perm = jnp.argsort(~flive, stable=True)
        nlive = flive.sum().astype(jnp.int32)
        fkcol = DeviceColumn(LONG, fk[perm], fkv[perm] & (jnp.arange(fk.shape[0]) < nlive))
        fvcol = DeviceColumn(LONG, fv[perm], fvv[perm])
        fccol = DeviceColumn(LONG, fc[perm], flive[perm])
        okeys, oaggs, on_groups = group_aggregate(
            _mini_batch([fkcol], nlive), [0], [fvcol, fccol], ["sum", "sum"]
        )
        return (
            okeys[0].data,
            okeys[0].validity,
            oaggs[0].data,
            oaggs[1].data,
            on_groups[None],
        )

    mapped = shard_map(
        per_chip,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
    )
    from .. import kernels as K

    return K.GuardedJit(mapped)


def _mini_batch(cols, num_rows) -> DeviceBatch:
    from ..types import Schema, StructField

    schema = Schema([StructField(f"c{i}", c.dtype, True) for i, c in enumerate(cols)])
    return DeviceBatch(schema, list(cols), jnp.asarray(num_rows, jnp.int32))
