"""Device equi-join kernels — the Table.innerJoin/leftJoin/... analogue
(reference: GpuHashJoin.scala:165-362, cudf hash joins).

TPU-first design: no hash table. Both sides' keys are radix-encoded
(ops/sortkeys) and matched with a **merge-join via concatenated sort**:
sorting [build ++ probe] keys with a side-flag tiebreak yields, for every
probe row, the count of build keys strictly-less (lower bound) or
less-or-equal (upper bound) — exact lexicographic multi-word matching with
two stable sorts, no collisions, static shapes.

Join semantics (Spark): NULL keys never match (side-specific sentinel words
make them unequal to everything); NaN keys match each other and -0.0 == 0.0
(float keys are normalized before encoding).

Output size is data-dependent: phase 1 returns per-probe match counts (the
one host sync per join batch — cudf's join does the same); phase 2 gathers
pairs into a bucketed static capacity.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..columnar.device import DeviceBatch, DeviceColumn
from ..ops.aggregate import _normalize_float
from ..ops.gather import gather_planes
from ..ops.sortkeys import column_radix_words, sort_permutation
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


def _key_words(cols: list[DeviceColumn], live: jax.Array, side_flag: int):
    """Radix words for join keys + leading null-exclusion word.

    Rows with any NULL key (or padding rows) get a side-specific sentinel in
    the leading word so they can never equal anything on the other side."""
    words: list[jax.Array] = []
    any_null = ~live
    for c in cols:
        c = _normalize_float(c)
        any_null = any_null | ~c.validity
        # no standalone validity word (nulls handled by the exclusion
        # sentinel; packed sub-64-bit words keep their folded bit, which is
        # constant across valid rows so equality is unaffected)
        words.extend(column_radix_words(c, value_only=True))
    sentinel = jnp.where(any_null, jnp.uint64(2 + side_flag), jnp.uint64(0))
    return [sentinel] + words, any_null


def join_bounds(
    build_cols: list[DeviceColumn],
    build_live: jax.Array,
    probe_cols: list[DeviceColumn],
    probe_live: jax.Array,
):
    """Per-probe-row [lower, upper) ranges into the key-sorted build order.

    Returns (build_order, lower, upper) where ``build_order`` maps sorted
    positions to original build row indices.
    """
    nb = build_live.shape[0]
    npr = probe_live.shape[0]
    bw, _ = _key_words(build_cols, build_live, 0)
    pw, _ = _key_words(probe_cols, probe_live, 1)

    # build sort order (for the gather phase). Every sort here goes through
    # sort_permutation's single-key passes: one variadic lax.sort over the
    # key words costs the TPU compiler minutes at these sizes
    build_order = sort_permutation(bw, None, live_first=False)

    def bound(probe_first: bool):
        # concatenated sort: side flag breaks ties; count build rows before
        # each probe row
        flag_b = jnp.full(nb, 0 if not probe_first else 1, dtype=jnp.uint8)
        flag_p = jnp.full(npr, 1 if not probe_first else 0, dtype=jnp.uint8)
        keys = [jnp.concatenate([b, p]) for b, p in zip(bw, pw)]
        flags = jnp.concatenate([flag_b, flag_p])
        src = jnp.concatenate(
            [jnp.full(nb, -1, jnp.int32), jnp.arange(npr, dtype=jnp.int32)]
        )
        perm = sort_permutation(
            keys + [flags.astype(jnp.uint64)], None, live_first=False
        )
        # a build row's source is -1: one gather tells the sides apart
        ssrc = src[perm]
        is_build = ssrc < 0
        nbefore = jnp.cumsum(is_build.astype(jnp.int32)) - is_build.astype(jnp.int32)
        # scatter each probe row's build-count back to its original position
        is_probe = ~is_build
        tgt = jnp.where(is_probe, ssrc, npr)
        res = jnp.zeros(npr, dtype=jnp.int32).at[tgt].set(
            jnp.where(is_probe, nbefore, 0), mode="drop"
        )
        return res

    lower = bound(probe_first=True)  # count of build keys < probe key
    upper = bound(probe_first=False)  # count of build keys <= probe key
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
