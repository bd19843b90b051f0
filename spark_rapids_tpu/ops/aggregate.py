"""Sort-based group-by aggregation kernel — the device engine under
TpuHashAggregateExec.

Reference: aggregate.scala's ``Table.groupBy(...).aggregate`` hot loop
(:345-520). cudf hash-aggregates; the TPU-first equivalent is ONE fused XLA
program per (schema, capacity): radix-encode keys → LSD radix ``lax.sort`` →
segment boundaries by adjacent-difference → **segmented scans** over the
sorted runs, with group outputs gathered at segment boundaries through a
compaction permutation. Everything is static-shape (output capacity == input
capacity; live groups prefix-compacted with a device-resident count), so the
whole update/merge pipeline stays on device with no host syncs.

No scatters anywhere: ``jax.ops.segment_*`` lowers to a serial per-element
scatter loop on TPU (~µs/row — seconds/batch); scans + gathers are log-depth
and vectorized. Ungrouped reductions skip the sort entirely and lower to
plain masked ``jnp.sum``/``min``/``max``.

Spark semantics: NULL keys form a group; float keys are normalized
(-0.0 → 0.0, canonical NaN) as Spark's NormalizeFloatingNumbers does; sums
wrap for longs; min/max/first/last are NULL on all-null groups; float
min/max treat NaN as the greatest value.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..columnar.device import DeviceBatch, DeviceColumn, dc_replace
from ..types import LONG, DoubleType, FloatType, StringType
from .gather import compact_permutation, gather_column, gather_columns, gather_planes
from .scan import first_k_positions, seg_end_flags, segscan
from .sortkeys import (
    column_radix_words,
    packed_key,
    packed_sort,
    segment_starts,
)

_BIG = jnp.int32(2**31 - 1)


def _normalize_float(col: DeviceColumn, has_nans: bool = True) -> DeviceColumn:
    if isinstance(col.dtype, (FloatType, DoubleType)):
        x = col.data
        x = jnp.where(x == 0, jnp.zeros_like(x), x)
        if has_nans:  # spark.rapids.sql.hasNans=false skips canonicalization
            x = jnp.where(jnp.isnan(x), jnp.full_like(x, jnp.nan), x)
        return DeviceColumn(col.dtype, x, col.validity, col.lengths)
    return col


def _minmax_fill(op: str, dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf if op == "min" else -jnp.inf, dtype=dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if op == "min" else info.min, dtype=dtype)


def _scan_reduce(op: str, data, valid, starts, idx, cap):
    """Per-row inclusive segmented reduction over sorted rows. Returns
    (scan_vals, scan_valid, pick) where values at each segment's END row are
    the segment totals; ``pick`` (per-row running pick index) is set for
    first/last ops."""
    if op == "sum":
        vals = jnp.where(valid, data, jnp.zeros_like(data))
        return segscan(vals, starts, jnp.add), segscan(
            valid.astype(jnp.int32), starts, jnp.add
        ) > 0, None
    if op == "count":
        out = segscan(valid.astype(jnp.int64), starts, jnp.add)
        return out, jnp.ones(cap, dtype=bool), None
    if op in ("min", "max"):
        fill = _minmax_fill(op, data.dtype)
        masked = jnp.where(valid, data, fill)
        is_float = jnp.issubdtype(data.dtype, jnp.floating)
        if is_float:
            # Spark NaN ordering: NaN is the greatest value. +inf sentinel so
            # the scan never propagates NaN; restored by the caller.
            masked = jnp.where(jnp.isnan(masked), jnp.inf, masked)
        fn = jnp.minimum if op == "min" else jnp.maximum
        out = segscan(masked, starts, fn)
        any_valid = segscan(valid.astype(jnp.int32), starts, jnp.add) > 0
        return out, any_valid, None
    # first/last family: running pick of a row index per segment
    if op == "first":
        pick = segscan(idx, starts, jnp.minimum)
    elif op == "last":
        pick = segscan(idx, starts, jnp.maximum)
    elif op == "first_ignore_nulls":
        pick = segscan(jnp.where(valid, idx, _BIG), starts, jnp.minimum)
    elif op == "last_ignore_nulls":
        pick = segscan(jnp.where(valid, idx, jnp.int32(-1)), starts, jnp.maximum)
    else:  # pragma: no cover
        raise ValueError(f"unknown reduce op {op}")
    return pick, None, pick


def _had_nan_scan(data, valid, starts):
    """Per-row 'segment saw a valid NaN' flag (Spark: NaN greatest)."""
    return segscan((valid & jnp.isnan(data)).astype(jnp.int32), starts, jnp.add) > 0


def _string_base_words(col: DeviceColumn):
    """Ascending sortable uint64 value words of a string column (computed
    once per column even when both min AND max aggregate it)."""
    return column_radix_words(
        col, ascending=True, nulls_first=True, value_only=True
    )


def _string_value_words(base_words: list, valid, want_min: bool):
    """Words for the lex-min scan with invalid rows losing STRICTLY: the
    prepended validity word (valid→0, invalid→all-ones) breaks ties so a
    NULL row carrying residual branch bytes can never beat a valid empty
    string. ``want_min=False`` inverts the value words so one lex-MIN scan
    serves both directions."""
    lose = jnp.uint64(0xFFFFFFFFFFFFFFFF)
    out = [jnp.where(valid, jnp.uint64(0), lose)]
    for w in base_words:
        w = w if want_min else ~w
        out.append(jnp.where(valid, w, lose))
    return out


def _seg_arglexmin(words: list, starts, idx):
    """Per-row running index of the lexicographically smallest word tuple in
    the segment (ties keep the earlier row — stable, like the CPU oracle).
    The (flag, words…, idx) combine is the standard segmented-scan form."""

    def comb(a, b):
        af, bf = a[0], b[0]
        a_ws, b_ws = a[1:-1], b[1:-1]
        lt = jnp.zeros(a_ws[0].shape, dtype=bool)
        eq = jnp.ones(a_ws[0].shape, dtype=bool)
        for aw, bw in zip(a_ws, b_ws):
            lt = lt | (eq & (bw < aw))
            eq = eq & (bw == aw)
        take_b = bf | lt  # segment restart at b, or b strictly smaller
        out_ws = tuple(
            jnp.where(take_b, bw, aw) for aw, bw in zip(a_ws, b_ws)
        )
        out_i = jnp.where(take_b, b[-1], a[-1])
        return (af | bf, *out_ws, out_i)

    carry = (starts, *words, idx)
    out = jax.lax.associative_scan(comb, carry)
    return out[-1]


def _whole_arglexmin(words: list, valid, cap):
    """Index of the lex-smallest valid word tuple over the whole column
    (returns _BIG when no row is valid)."""
    cand = valid
    for w in words:
        masked = jnp.where(cand, w, jnp.uint64(0xFFFFFFFFFFFFFFFF))
        m = masked.min()
        cand = cand & (masked == m) & valid
    idx = jnp.arange(cap, dtype=jnp.int32)
    return jnp.where(cand, idx, _BIG).min()


def group_aggregate(
    batch: DeviceBatch,
    key_ordinals: list[int],
    agg_columns: list[DeviceColumn],
    ops: list[str],
    min_groups: int = 0,
    live_mask=None,
    has_nans: bool = True,
    collect_width: int = 0,
) -> tuple[list[DeviceColumn], list[DeviceColumn], jax.Array]:
    """Group ``batch`` rows by key columns; reduce ``agg_columns[i]`` with
    ``ops[i]``. Returns (key cols, agg cols, num_groups) — all [capacity]
    with live groups in the prefix. ``min_groups=1`` gives ungrouped
    reductions their one output row even on empty input (Spark: global
    count() over nothing is 0, not no-rows).

    ``live_mask`` (bool[cap]) restricts which rows participate — the fused
    pre-filter path: a filter feeding an aggregate contributes a mask here
    instead of compacting its output (saving a full gather of every column).
    """
    cap = batch.capacity
    if not batch.columns and agg_columns:
        cap = agg_columns[0].capacity  # ungrouped: key-less work batch
    keys = [_normalize_float(batch.columns[i], has_nans) for i in key_ordinals]
    if not keys:
        return _ungrouped_aggregate(
            batch, agg_columns, ops, cap, live_mask,
            collect_width=collect_width, has_nans=has_nans,
        )

    row_mask = batch.row_mask() if live_mask is None else live_mask
    n_live = (
        batch.num_rows if live_mask is None else live_mask.sum().astype(jnp.int32)
    )
    # a row's key is read once, as its packed words; every full-capacity
    # gather below is ~3x the sort pass it would feed (PERF.md section 5)
    key = packed_key(keys, row_mask)
    perm = packed_sort(key)
    # live rows sort first, so the sorted live mask is a prefix of n_live
    live = jnp.arange(cap, dtype=jnp.int32) < n_live
    starts = segment_starts(key.sorted_words(perm), live)
    num_groups = jnp.maximum(starts.sum().astype(jnp.int32), min_groups)
    group_live = jnp.arange(cap, dtype=jnp.int32) < num_groups
    idx = jnp.arange(cap, dtype=jnp.int32)
    # the first padding row "starts a segment" so the LAST live segment's
    # end lands on row n_live-1, not cap-1
    ends = seg_end_flags(starts | (idx == n_live)) & live

    # group-ordered positions of segment starts/ends (no scatters: one
    # single-key compaction sort each)
    start_pos = first_k_positions(starts)
    end_pos = first_k_positions(ends)

    # representative keys: the first sorted row of each segment, gathered
    # once from the unsorted columns (not at perm and again at start_pos),
    # every key's planes in one call
    first_row = perm[start_pos]
    out_keys = [
        DeviceColumn(
            k.dtype,
            _mask_data(gk.data, group_live),
            gk.validity & group_live,
            None if gk.lengths is None else jnp.where(group_live, gk.lengths, 0),
        )
        for k, gk in zip(keys, gather_columns(keys, first_row, group_live))
    ]

    # Every aggregate scans first; the planes they read at their segments'
    # ends go through end_pos in ONE call (stacked: ops/gather.py), and then
    # each aggregate is finished from its share of what came back.
    at_end: list[jax.Array] = []
    finish: list = []  # (function of an aggregate's gathered planes, their slice)

    def defer(fn, *planes):
        finish.append((fn, len(at_end), len(at_end) + len(planes)))
        at_end.extend(planes)

    str_words_cache: dict = {}  # id(col) → ascending base words (min+max share)
    # a column that feeds several aggregates is gathered once; a stack is
    # gathered whole, so a count hands over the validity it reads and no more
    read = [
        DeviceColumn(c.dtype, None, c.validity) if op == "count" else c
        for c, op in zip(agg_columns, ops)
    ]
    for col, sc, op in zip(agg_columns, gather_columns(read, perm), ops):
        v = sc.validity & live
        is_str = isinstance(col.dtype, StringType)
        if op in ("collect_list", "collect_set"):
            collected = _group_collect(
                op,
                col,
                sc,
                keys,
                row_mask,
                n_live,
                live,
                starts,
                end_pos,
                group_live,
                collect_width,
                cap,
                has_nans,
            )
            defer(lambda collected=collected: collected)
            continue
        if is_str and op in ("min", "max"):
            # string min/max: lexicographic arg-scan over the sortable word
            # encoding, then an index-pick like first/last (UTF8String
            # byte order — the re-sort-free strategy the r1 verdict asked for)
            base = str_words_cache.get(id(col))
            if base is None:
                base = _string_base_words(sc)
                str_words_cache[id(col)] = base
            vwords = _string_value_words(base, v, op == "min")
            pickrow = _seg_arglexmin(vwords, starts, idx)
            any_seen = segscan(v.astype(jnp.int32), starts, jnp.add) > 0

            def picked_string(gpick, any_v, sc=sc, col=col):
                ok = any_v & group_live
                data, lengths = gather_planes(
                    [sc.data, sc.lengths], jnp.clip(gpick, 0, cap - 1)
                )
                data = jnp.where(ok[:, None], data, 0).astype(jnp.uint8)
                lengths = jnp.where(ok, lengths, 0).astype(jnp.int32)
                return DeviceColumn(col.dtype, data, ok, lengths)

            defer(picked_string, pickrow, any_seen)
            continue
        scan_vals, scan_valid, pick = _scan_reduce(op, sc.data, v, starts, idx, cap)
        if pick is not None:
            # first/last: gather the picked row's value per group

            def picked(gpick, sc=sc, col=col):  # the pick at each segment's end
                ok = (gpick != _BIG) & (gpick >= 0) & group_live
                data, valid_out, lengths = gather_planes(
                    [sc.data, sc.validity, sc.lengths], jnp.clip(gpick, 0, cap - 1)
                )
                if data.ndim == 2:
                    data = jnp.where(ok[:, None], data, 0)
                else:
                    data = jnp.where(ok, data, jnp.zeros_like(data))
                return DeviceColumn(col.dtype, data, valid_out & ok, lengths)

            defer(picked, scan_vals)
            continue
        # count only reads validity, so string inputs are fine there
        assert not (is_str and op != "count"), (
            f"string op {op} requires an index-pick"
        )
        nan_flags = []
        if (
            op in ("min", "max")
            and jnp.issubdtype(sc.data.dtype, jnp.floating)
            and has_nans
        ):
            nan_flags.append(_had_nan_scan(sc.data, v, starts))
            if op == "min":
                # min is NaN only when EVERY valid value was NaN — a real
                # +inf minimum alongside a NaN must stay +inf (NaN greatest)
                nan_flags.append(
                    segscan(
                        (v & ~jnp.isnan(sc.data)).astype(jnp.int32), starts, jnp.add
                    )
                    > 0
                )

        def reduced(data, valid_out, *nans, op=op, col=col):
            valid_out = valid_out & group_live
            if len(nans) == 1:
                data = jnp.where(nans[0], jnp.nan, data)
            elif nans:
                had_nan, has_nonnan = nans
                data = jnp.where(had_nan & ~has_nonnan, jnp.nan, data)
            if op == "count":
                valid_out = group_live  # count is never null
            data = _mask_data(data, group_live)
            # count's output is a LONG regardless of the input column's type
            out_dtype = LONG if op == "count" else col.dtype
            return DeviceColumn(out_dtype, data, valid_out, None)

        defer(reduced, scan_vals, scan_valid, *nan_flags)
    gathered = gather_planes(at_end, end_pos)
    out_aggs = [fn(*gathered[lo:hi]) for fn, lo, hi in finish]
    return out_keys, out_aggs, num_groups


def group_max_size(batch: DeviceBatch, key_ordinals: list[int], live_mask=None,
                   has_nans: bool = True) -> jax.Array:
    """Largest group's row count — the collect family's width pre-pass
    (upper bound on any collect plane width; ONE host sync in the exec)."""
    cap = batch.capacity
    keys = [_normalize_float(batch.columns[i], has_nans) for i in key_ordinals]
    row_mask = batch.row_mask() if live_mask is None else live_mask
    n_live = (
        batch.num_rows if live_mask is None
        else live_mask.sum().astype(jnp.int32)
    )
    if not keys:
        return n_live.astype(jnp.int32)
    key = packed_key(keys, row_mask)
    perm = packed_sort(key)
    live = jnp.arange(cap, dtype=jnp.int32) < n_live
    starts = segment_starts(key.sorted_words(perm), live)
    run = segscan(jnp.ones(cap, jnp.int32), starts, jnp.add)
    return jnp.where(live, run, 0).max().astype(jnp.int32)


def _group_collect(
    op: str,
    col: DeviceColumn,
    sc: DeviceColumn,
    keys: list,
    row_mask,
    n_live,
    live,
    starts,
    end_pos,
    group_live,
    W: int,
    cap: int,
    has_nans: bool,
) -> DeviceColumn:
    """collect_list / collect_set as an array-plane build — the device list
    accumulator (reference GpuCollectList/GpuCollectSet,
    AggregateFunctions.scala:644). No scatters: kept rows compact to the
    front with ONE stable argsort, group planes gather through an
    offset+rank index matrix. ``W`` (static plane width) is the
    bucket-capacity of the largest group, measured by the exec's width
    kernel in a prior pass (the one host sync this aggregate family needs).

    collect_list keeps input row order (the key sort is stable); collect_set
    re-sorts by value and dedupes adjacent equal values, so its output is
    value-ascending — deterministic, and mirrored by the CPU engine (Spark
    itself guarantees no order)."""
    from ..types import ArrayType

    idx = jnp.arange(cap, dtype=jnp.int32)
    if op == "collect_set":
        vcol = _normalize_float(col, has_nans)
        # one key: the group's columns, then the value (nulls last)
        key2 = packed_key(
            keys + [vcol], row_mask, nulls_firsts=[True] * len(keys) + [False]
        )
        perm2 = packed_sort(key2)
        s_words2 = key2.sorted_words(perm2)
        starts2 = segment_starts(key2.prefix(s_words2, len(keys)), live)
        sc2 = gather_column(vcol, perm2)
        v2 = sc2.validity & live
        # a kept row differs from the one before it in group or in value
        keep = v2 & segment_starts(s_words2, live)
        ends2 = seg_end_flags(starts2 | (idx == n_live)) & live
        end_pos2 = first_k_positions(ends2)
        use_sc, use_starts, use_end_pos = sc2, starts2, end_pos2
    else:
        use_sc, use_starts, use_end_pos = sc, starts, end_pos
        keep = sc.validity & live

    kc = segscan(keep.astype(jnp.int32), use_starts, jnp.add)[use_end_pos]
    kc = jnp.where(group_live, kc, 0).astype(jnp.int32)
    # kept rows to the front, (group, order) sequence preserved
    kept = gather_column(use_sc, compact_permutation(keep))
    offs = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(kc)[:-1].astype(jnp.int32)]
    )
    j = jnp.arange(max(W, 1), dtype=jnp.int32)[None, :]
    gidx = offs[:, None] + j  # [cap, W]
    elem_live = (j < kc[:, None]) & group_live[:, None]
    safe = jnp.clip(gidx, 0, cap - 1)
    if isinstance(col.dtype, StringType):
        edata, elengths = gather_planes([kept.data, kept.lengths], safe)
        edata = jnp.where(elem_live[:, :, None], edata, 0).astype(jnp.uint8)
        elengths = jnp.where(elem_live, elengths, 0).astype(jnp.int32)
        elem = DeviceColumn(col.dtype, edata, elem_live, elengths)
    else:
        edata = jnp.where(elem_live, kept.data[safe], jnp.zeros((), kept.data.dtype))
        elem = DeviceColumn(col.dtype, edata, elem_live, None)
    # collect is never null: empty array for all-null/empty groups
    return DeviceColumn(
        ArrayType(col.dtype, contains_null=False),
        None,
        group_live,
        kc,
        (elem,),
    )


def _mask_data(data, group_live):
    if data.ndim == 2:
        return jnp.where(group_live[:, None], data, 0)
    return jnp.where(group_live, data, jnp.zeros_like(data))


def _ungrouped_aggregate(
    batch, agg_columns, ops, cap, live_mask=None, collect_width: int = 0,
    has_nans: bool = True,
):
    """No keys: one output group; plain masked whole-array reductions."""
    if live_mask is not None:
        live = live_mask
    else:
        live = jnp.arange(cap, dtype=jnp.int32) < batch.num_rows
    idx = jnp.arange(cap, dtype=jnp.int32)
    out_aggs: list[DeviceColumn] = []
    one_live = jnp.arange(cap, dtype=jnp.int32) < 1
    for col, op in zip(agg_columns, ops):
        data, valid = col.data, col.validity & live
        is_str = isinstance(col.dtype, StringType)

        def place(scalar, ok, lengths_scalar=None, out_dtype=None):
            """Put the scalar into row 0 of a [cap] column."""
            if getattr(scalar, "ndim", 0) == 1:  # string bytes [w]
                out = jnp.zeros((cap, scalar.shape[0]), dtype=scalar.dtype)
                out = jnp.where(one_live[:, None], scalar[None, :], out)
            else:
                out = jnp.where(one_live, scalar, jnp.zeros(cap, dtype=scalar.dtype))
            vout = one_live & ok
            lout = None
            if lengths_scalar is not None:
                lout = jnp.where(one_live, lengths_scalar, 0).astype(jnp.int32)
            return DeviceColumn(out_dtype or col.dtype, out, vout, lout)

        any_valid = valid.any()
        if op == "sum":
            total = jnp.where(valid, data, jnp.zeros_like(data)).sum()
            out_aggs.append(place(total, any_valid))
        elif op == "count":
            out_aggs.append(
                place(valid.sum().astype(jnp.int64), jnp.bool_(True), out_dtype=LONG)
            )
        elif op in ("collect_list", "collect_set"):
            from ..types import ArrayType

            W = max(collect_width, 1)
            if op == "collect_set":
                vcol = _normalize_float(col, has_nans)
                key2 = packed_key([vcol], valid, nulls_firsts=[False])
                perm2 = packed_sort(key2)
            else:
                vcol = col
                perm2 = compact_permutation(valid)
            # the live validity rides with the column's planes
            svals = gather_column(dc_replace(vcol, validity=valid), perm2)
            keep = svals.validity
            if op == "collect_set":
                # valid rows sort first: among them a new value starts a run
                keep = segment_starts(key2.sorted_words(perm2), keep)
            kept = gather_column(svals, compact_permutation(keep))
            kcount = keep.sum().astype(jnp.int32)
            jW = jnp.arange(W, dtype=jnp.int32)
            elem_live0 = jW < kcount  # [W]
            safeW = jnp.clip(jW, 0, cap - 1)
            if is_str:
                row0, len0 = gather_planes([kept.data, kept.lengths], safeW)
                row0 = jnp.where(elem_live0[:, None], row0, 0).astype(jnp.uint8)
                edata = jnp.where(one_live[:, None, None], row0[None], 0)
                elengths = jnp.where(
                    one_live[:, None],
                    jnp.where(elem_live0, len0, 0)[None, :],
                    0,
                ).astype(jnp.int32)
                elem = DeviceColumn(
                    col.dtype, edata, one_live[:, None] & elem_live0[None, :],
                    elengths,
                )
            else:
                row0 = jnp.where(
                    elem_live0, kept.data[safeW],
                    jnp.zeros((), kept.data.dtype),
                )
                edata = jnp.where(one_live[:, None], row0[None], jnp.zeros((), row0.dtype))
                elem = DeviceColumn(
                    col.dtype, edata, one_live[:, None] & elem_live0[None, :],
                    None,
                )
            out_aggs.append(
                DeviceColumn(
                    ArrayType(col.dtype, contains_null=False),
                    None,
                    one_live,
                    jnp.where(one_live, kcount, 0).astype(jnp.int32),
                    (elem,),
                )
            )
        elif op in ("min", "max") and is_str:
            vwords = _string_value_words(_string_base_words(col), valid, op == "min")
            pick = _whole_arglexmin(vwords, valid, cap)
            ok = pick != _BIG
            safe = jnp.clip(pick, 0, cap - 1)
            out_aggs.append(
                place(col.data[safe], col.validity[safe] & ok, col.lengths[safe])
            )
        elif op in ("min", "max"):
            fill = _minmax_fill(op, data.dtype)
            masked = jnp.where(valid, data, fill)
            is_float = jnp.issubdtype(data.dtype, jnp.floating)
            if is_float:
                masked = jnp.where(jnp.isnan(masked), jnp.inf, masked)
            total = masked.min() if op == "min" else masked.max()
            if is_float:
                had_nan = (valid & jnp.isnan(data)).any()
                if op == "max":
                    total = jnp.where(had_nan, jnp.nan, total)
                else:
                    # NaN only when every valid value was NaN (NaN greatest)
                    has_nonnan = (valid & ~jnp.isnan(data)).any()
                    total = jnp.where(had_nan & ~has_nonnan, jnp.nan, total)
            out_aggs.append(place(total, any_valid))
        else:  # first/last family
            if op == "first":
                pick = jnp.where(live, idx, _BIG).min()
            elif op == "last":
                pick = jnp.where(live, idx, jnp.int32(-1)).max()
            elif op == "first_ignore_nulls":
                pick = jnp.where(valid, idx, _BIG).min()
            elif op == "last_ignore_nulls":
                pick = jnp.where(valid, idx, jnp.int32(-1)).max()
            else:  # pragma: no cover
                raise ValueError(f"unknown reduce op {op}")
            ok = (pick != _BIG) & (pick >= 0)
            safe = jnp.clip(pick, 0, cap - 1)
            out_aggs.append(
                place(
                    data[safe],
                    col.validity[safe] & ok,
                    None if col.lengths is None else col.lengths[safe],
                )
            )
    num_groups = jnp.int32(1)
    return [], out_aggs, num_groups