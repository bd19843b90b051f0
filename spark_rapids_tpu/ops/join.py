"""Device equi-join kernels — the Table.innerJoin/leftJoin/... analogue
(reference: GpuHashJoin.scala:165-362, cudf hash joins).

TPU-first design: no hash table. Both sides' keys are packed into uint32
words (ops/sortkeys' fields: a 2-bit sentinel, the key's value bits, a side
bit) and matched with a **merge-join via one concatenated sort**: a stable
sort of [build ++ probe] puts the build rows before the probe rows of equal
key, so counting the build rows before each probe row gives its upper bound
(build keys less or equal), the count at the start of its run of equal keys
its lower bound (build keys strictly less), and the build rows in the order
are the key-sorted build side — exact lexicographic multi-word matching,
no collisions, static shapes.

Join semantics (Spark): NULL keys never match (side-specific sentinels
make them unequal to everything); NaN keys match each other and -0.0 == 0.0
(float keys are normalized before encoding).

Output size is data-dependent: phase 1 returns per-probe match counts (the
one host sync per join batch — cudf's join does the same); phase 2 gathers
pairs into a bucketed static capacity.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..columnar.device import DeviceBatch, DeviceColumn, dc_replace
from ..ops.aggregate import _normalize_float
from ..ops.gather import gather_planes
from ..ops.sortkeys import (
    PackedKey,
    column_key_fields,
    pack_fields,
    packed_sort,
    segment_starts,
    unpacked_key_words,
)
from ..types import StringType


def join_output_schema(
    join_type: str,
    left_fields,
    right_fields,
    drop_right: list[str] | None = None,
):
    """Join output schema shared by every join exec (CPU and TPU): semi/anti
    keep only the left side; outer sides become nullable."""
    import dataclasses as _dc

    from ..types import Schema

    lt = list(left_fields)
    rt = [f for f in right_fields if f.name not in (drop_right or [])]
    if join_type in ("left_semi", "left_anti"):
        return Schema(lt)
    if join_type in ("left", "full"):
        rt = [_dc.replace(f, nullable=True) for f in rt]
    if join_type in ("right", "full"):
        lt = [_dc.replace(f, nullable=True) for f in lt]
    return Schema(lt + rt)


def pad_string_column(col: DeviceColumn, width: int) -> DeviceColumn:
    if not isinstance(col.dtype, StringType) or col.data.shape[1] >= width:
        return col
    data = jnp.pad(col.data, ((0, 0), (0, width - col.data.shape[1])))
    return DeviceColumn(col.dtype, data, col.validity, col.lengths)


def _widened(col: DeviceColumn, other: DeviceColumn) -> DeviceColumn:
    """``col`` at ``other``'s dtype where that is the wider (an int32 key
    against an int64 one), so both sides lay the key out alike."""
    if isinstance(col.dtype, StringType):
        return col
    if col.dtype.np_dtype.itemsize >= other.dtype.np_dtype.itemsize:
        return col
    return dc_replace(col, dtype=other.dtype, data=col.data.astype(other.dtype.np_dtype))


def _side_fields(cols, others, live: jax.Array, side: int):
    """One side's join key as ``(uint32 array, bits)`` fields, most
    significant first, and where each key column's fields end: a 2-bit
    sentinel, each column's value fields, and the side bit (build 0, probe 1).

    The sentinel is 0 for a live row whose keys are all non-null, 2 for an
    excluded build row and 3 for an excluded probe row (a padding row, or a
    null key), so an excluded row equals nothing on the other side. A
    column's null slots hold zero value bits. Where those tie a null with
    the least value (bool, int8/16/32, date: their codes start at zero) the
    column keeps ``column_key_fields``' validity bit, so that the excluded
    rows keep the order the uint64 radix words gave them, where a null sorts
    below those types' values and level with the least int64 and the empty
    string; otherwise the sentinel carries the validity. A column narrower
    than the other side's at its position is widened to it first."""
    any_null = ~live
    fields: list = []
    ends, at = [], 2
    for c, o in zip(cols, others):
        c = _widened(_normalize_float(c), o)
        any_null = any_null | ~c.validity
        narrow = not isinstance(c.dtype, StringType) and c.dtype.np_dtype.itemsize <= 4
        mine = column_key_fields(c)[0 if narrow else 1:]
        fields += mine
        at += sum(bits for _, bits in mine)
        ends.append(at)
    sentinel = jnp.where(any_null, jnp.uint32(2 + side), jnp.uint32(0))
    flag = jnp.full(live.shape, side, jnp.uint32)
    return [(sentinel, 2)] + fields + [(flag, 1)], tuple(ends)


def join_key(
    build_cols: list[DeviceColumn],
    build_live: jax.Array,
    probe_cols: list[DeviceColumn],
    probe_live: jax.Array,
) -> PackedKey:
    """The packed key of ``[build ++ probe]``: ``_side_fields`` of each side
    in the fewest uint32 words, so ``prefix(words, len(build_cols))`` is the
    key without the side bit. ``unpacked_passes`` is what the three sorts
    over uint64 radix words that this key's one sort replaces ran: the build
    side by the sentinel and the key words, then ``[build ++ probe]`` twice
    by those and a side-flag word, two passes a word."""
    fb, ends = _side_fields(build_cols, probe_cols, build_live, 0)
    fp, _ = _side_fields(probe_cols, build_cols, probe_live, 1)
    words = jnp.concatenate(
        [jnp.stack(pack_fields(fb)), jnp.stack(pack_fields(fp))], axis=1
    )
    nwords = sum(unpacked_key_words(c, value_only=True) for c in build_cols)
    unpacked = 2 * (1 + nwords) + 2 * 2 * (2 + nwords)
    return PackedKey(words, ends, unpacked)


def join_bounds(
    build_cols: list[DeviceColumn],
    build_live: jax.Array,
    probe_cols: list[DeviceColumn],
    probe_live: jax.Array,
):
    """Per-probe-row [lower, upper) ranges into the key-sorted build order.

    Returns (build_order, lower, upper) where ``build_order`` maps sorted
    positions to original build row indices.

    One stable sort of ``[build ++ probe]`` by ``join_key`` (sentinel, key,
    side bit) holds all three. With ``nbefore`` the build rows before a
    sorted position: a probe row's ``upper`` is its ``nbefore``, since on
    equal keys the build rows sort first; its ``lower`` is the ``nbefore``
    at the first row of its run of equal keys (every word but the side
    bit), a running max of ``nbefore`` at the run starts, as ``nbefore``
    never falls; and the ``k``-th build row in the order is
    ``build_order[k]``, which by stability is the order a sort of the build
    side alone gives. A scatter a result puts each in place.
    """
    nb, npr = build_live.shape[0], probe_live.shape[0]
    key = join_key(build_cols, build_live, probe_cols, probe_live)
    perm = packed_sort(key)
    # the rows' keys in sorted order, side bit masked off, in one gather
    keys = key.prefix(key.sorted_words(perm), len(build_cols))
    is_build = perm < nb
    nbefore = jnp.cumsum(is_build.astype(jnp.int32)) - is_build.astype(jnp.int32)
    starts = segment_starts(keys, jnp.bool_(True))
    lower = jax.lax.cummax(jnp.where(starts, nbefore, 0))
    # a build row to its rank among the build rows, a probe row to its own
    # index: 1-D scatters into each side's rows (one scatter of a [2, rows]
    # stack costs a v5e about four times what these do: PERF.md section 6)
    to_build = jnp.where(is_build, nbefore, nb)
    to_probe = jnp.where(is_build, npr, perm - nb)
    build_order = jnp.zeros(nb, jnp.int32).at[to_build].set(perm, mode="drop")
    lower = jnp.zeros(npr, jnp.int32).at[to_probe].set(lower, mode="drop")
    upper = jnp.zeros(npr, jnp.int32).at[to_probe].set(nbefore, mode="drop")
    return build_order, lower, upper


def gather_pairs(
    build_order: jax.Array,
    lower: jax.Array,
    counts: jax.Array,
    probe_live: jax.Array,
    out_cap: int,
):
    """Expand per-probe match ranges into (probe_idx, build_idx) pair arrays
    of static length ``out_cap`` with a live-pair mask and total count."""
    ends = jnp.cumsum(counts)  # one past probe i's last pair
    offsets = ends - counts  # start of probe i's pairs
    total = counts.sum()
    j = jnp.arange(out_cap, dtype=jnp.int32)
    # probe index for output slot j: the probes whose pairs end at or before
    # j, counted by one scatter of the ends and a running sum over the slots.
    # (A binary search of ``ends`` for every slot is a loop of log2(probes)
    # gathers at the OUTPUT capacity: 10.9 s of TPC-DS q95's 33 s, whose
    # self-join emits 9 M pairs from 0.7 M rows.)
    ended = jnp.zeros(out_cap, jnp.int32).at[ends].add(1, mode="drop")
    probe_idx = jnp.cumsum(ended)
    probe_idx = jnp.clip(probe_idx, 0, lower.shape[0] - 1)
    first_slot, first_match = gather_planes([offsets, lower], probe_idx)
    sorted_pos = first_match + (j - first_slot)
    sorted_pos = jnp.clip(sorted_pos, 0, build_order.shape[0] - 1)
    build_idx = build_order[sorted_pos]
    pair_live = j < total
    return probe_idx, build_idx, pair_live, total
