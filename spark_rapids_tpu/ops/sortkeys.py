"""Order-preserving radix key encoding — the foundation of device sort,
sort-based group-by, and sort-merge machinery.

The reference leans on cudf's type-aware comparators (Table.orderBy,
groupBy). The TPU-first design instead maps every SQL value to one or more
**uint64 radix words whose unsigned order equals Spark's sort order**, then
uses a single variadic ``jax.lax.sort`` over all words (XLA sorts
lexicographically by the first ``num_keys`` operands) — one fused kernel, no
custom comparators, static shapes.

Orderings implemented to Spark's spec:
* NULLs first/last via a leading validity word
* floats: IEEE total-order bit trick with Spark's NaN semantics (all NaNs
  collapse to one greatest value) and -0.0 == 0.0 normalization
* strings: padded UTF-8 bytes packed big-endian 8-per-word, ties broken by
  length (exact lexicographic byte order, incl. interior NULs)
* descending via bitwise complement of the value words
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..columnar.device import DeviceColumn
from ..types import (
    BooleanType,
    DataType,
    DoubleType,
    FloatType,
    StringType,
)

_SIGN64 = jnp.uint64(1 << 63)


def _float_bits_ordered(data: jax.Array, dt: DataType) -> jax.Array:
    """Map float to uint64 preserving Spark order (NaN greatest, -0==0)."""
    if isinstance(dt, FloatType):
        x = data.astype(jnp.float32)
        x = jnp.where(x == 0.0, jnp.float32(0.0), x)  # -0.0 -> +0.0
        x = jnp.where(jnp.isnan(x), jnp.float32(jnp.nan), x)  # canonical NaN
        b = jax.lax.bitcast_convert_type(x, jnp.int32).astype(jnp.int64)
        flipped = jnp.where(b < 0, ~b, b | jnp.int64(1 << 31))
        return flipped.astype(jnp.uint64)
    from .bits import f64_bits

    x = data.astype(jnp.float64)
    x = jnp.where(x == 0.0, jnp.float64(0.0), x)
    x = jnp.where(jnp.isnan(x), jnp.float64(jnp.nan), x)
    u = f64_bits(x)  # no 64-bit bitcast on TPU (ops/bits.py)
    b = u.astype(jnp.int64)
    flipped = jnp.where(b < 0, ~u, u | _SIGN64)
    return flipped


def column_radix_words(
    col: DeviceColumn,
    ascending: bool = True,
    nulls_first: bool = True,
    value_only: bool = False,
) -> list[jax.Array]:
    """Encode one column into uint64 words; unsigned lexicographic order over
    the word list == the requested Spark ordering.

    ``value_only`` omits the standalone validity word for callers that
    handle nulls themselves AND keeps the classic widened-to-64-bit
    encoding: the join compares words across columns of DIFFERENT integer
    widths, which only works when every width shares one encoding. Default
    (sort) callers get the packed layout for sub-64-bit types — validity
    folded into bit 63 of the single value word — so callers must never
    assume word[0] is a validity word; use this flag instead of slicing."""
    dt = col.dtype
    valid = col.validity
    # validity word: order nulls relative to values
    vw = jnp.where(valid, jnp.uint64(1), jnp.uint64(0))
    if not nulls_first:
        vw = jnp.where(valid, jnp.uint64(0), jnp.uint64(1))
    words: list[jax.Array] = []
    if isinstance(dt, StringType):
        data, lengths = col.data, col.lengths
        cap, w = data.shape
        nwords = (w + 7) // 8
        padded = jnp.pad(data, ((0, 0), (0, nwords * 8 - w)))
        d64 = padded.astype(jnp.uint64).reshape(cap, nwords, 8)
        shifts = jnp.arange(7, -1, -1, dtype=jnp.uint64) * 8
        packed = (d64 << shifts[None, None, :]).sum(axis=-1, dtype=jnp.uint64)
        for k in range(nwords):
            words.append(packed[:, k])
        words.append(lengths.astype(jnp.uint64))
    elif not value_only and (
        isinstance(dt, BooleanType)
        or (
            getattr(dt, "np_dtype", None) is not None
            and dt.np_dtype.itemsize <= 4
        )
    ):
        # value encoding fits 32 bits: fold the validity bit into bit 63 of
        # the SAME word — one LSD pass instead of two for int8/16/32, date,
        # float32, bool keys (each pass is ~15ms at 2M rows, and sorts are
        # the engine's hottest primitive)
        if isinstance(dt, FloatType):
            enc = _float_bits_ordered(col.data, dt) & jnp.uint64(0xFFFFFFFF)
        elif isinstance(dt, BooleanType):
            enc = col.data.astype(jnp.uint64)
        else:
            enc = (
                col.data.astype(jnp.int64) + jnp.int64(1 << 31)
            ).astype(jnp.uint64)
        packed = (vw << jnp.uint64(63)) | jnp.where(
            valid, enc, jnp.uint64(0)
        )
        if not ascending:
            # invert the VALUE bits only — null placement is nulls_first's
            # job (the unpacked layout never inverts its validity word)
            packed = packed ^ jnp.uint64(0x7FFFFFFFFFFFFFFF)
        return [packed]
    elif isinstance(dt, (FloatType, DoubleType)):
        words.append(_float_bits_ordered(col.data, dt))
    else:  # integral / date / timestamp / decimal(int64)
        words.append(
            (col.data.astype(jnp.int64).astype(jnp.uint64)) ^ _SIGN64
        )
    # null slots: zero value words so padding/nulls compare equal
    words = [jnp.where(valid, wd, jnp.uint64(0)) for wd in words]
    if not ascending:
        words = [~wd for wd in words]
    if value_only:
        return words
    return [vw] + words


def batch_radix_words(
    columns: list[DeviceColumn],
    ascendings: list[bool] | None = None,
    nulls_firsts: list[bool] | None = None,
) -> list[jax.Array]:
    out: list[jax.Array] = []
    for i, c in enumerate(columns):
        asc = True if ascendings is None else ascendings[i]
        nf = True if nulls_firsts is None else nulls_firsts[i]
        out.extend(column_radix_words(c, asc, nf))
    return out


def sort_permutation(
    words: list[jax.Array],
    row_mask: jax.Array,
    live_first: bool = True,
) -> jax.Array:
    """Stable sort permutation over radix words; padding rows sort last.

    Implemented as an LSD radix sort: a ``lax.scan`` of stable SINGLE-key
    ``lax.sort`` passes from the least- to the most-significant word. XLA's
    TPU sort lowering compiles a full sorting network whose compile time
    grows sharply with both array size and operand count — a variadic
    ``lax.sort`` over k words compiled in O(minutes) at 2^16+ rows, while
    this form embeds exactly ONE two-operand sort in the program regardless
    of key count (the scan reuses it per word), with identical ordering
    semantics (stable passes ⇒ lexicographic). Each 64-bit word goes as two
    32-bit passes: 64-bit types are emulated on the TPU, and the one
    embedded sort compiles about three times faster on a uint32 key.
    """
    cap = words[0].shape[0]
    halves = []  # most-significant first
    if live_first:
        halves.append(jnp.where(row_mask, jnp.uint32(0), jnp.uint32(1)))
    for w in words:
        w = w.astype(jnp.uint64)
        halves.append((w >> jnp.uint64(32)).astype(jnp.uint32))
        halves.append(w.astype(jnp.uint32))
    stacked = jnp.stack(halves[::-1])  # least-significant half first
    # inherit the data's varying-axis type so the scan carry matches inside
    # shard_map (a plain iota is replicated; the sorted perm is varying)
    iota = jnp.arange(cap, dtype=jnp.int32) + (stacked[0] * jnp.uint32(0)).astype(
        jnp.int32
    )

    def one_pass(perm, w):
        _, perm = jax.lax.sort((w[perm], perm), num_keys=1, is_stable=True)
        return perm, None

    perm, _ = jax.lax.scan(one_pass, iota, stacked)
    return perm


def _lex_less(words_a: list[jax.Array], words_b: list[jax.Array], or_equal: bool):
    """Elementwise lexicographic a < b (or a <= b) over aligned word lists."""
    lt = jnp.zeros(words_a[0].shape, dtype=bool)
    eq = jnp.ones(words_a[0].shape, dtype=bool)
    for wa, wb in zip(words_a, words_b):
        lt = lt | (eq & (wa < wb))
        eq = eq & (wa == wb)
    return (lt | eq) if or_equal else lt


def merge_permutation(
    words: list[jax.Array], na, nb
) -> jax.Array:
    """Permutation that merges two sorted live segments of one batch:
    rows ``[0, na)`` and ``[na, na+nb)`` are each sorted by ``words``'s
    unsigned lexicographic order; the returned perm gathers the stable
    merge (A wins ties). Each row binary-searches the OTHER segment for its
    merged position — O(n log n) gathers per level instead of the re-sort's
    full sorting network (reference: GpuSortExec.scala:212-510)."""
    cap = words[0].shape[0]
    idx = jnp.arange(cap, dtype=jnp.int32)
    is_a = idx < na
    # A rows search the B segment (side=left: A precedes equal B rows);
    # B rows search the A segment (side=right)
    pos_in_b = _binary_search(words, na, nb, words, right=False)
    pos_in_a = _binary_search(words, jnp.asarray(0, jnp.int32), na, words, right=True)
    pos = jnp.where(is_a, idx + pos_in_b, (idx - na) + pos_in_a)
    pos = jnp.where(idx < na + nb, pos, cap)  # drop padding rows
    perm = jnp.zeros(cap, dtype=jnp.int32).at[pos].set(idx, mode="drop")
    return perm


def _binary_search(
    words: list[jax.Array], base, m, queries: list[jax.Array], right: bool
) -> jax.Array:
    cap = words[0].shape[0]
    n = queries[0].shape[0]
    lo = jnp.zeros(n, dtype=jnp.int32)
    hi = jnp.broadcast_to(jnp.asarray(m, jnp.int32), (n,)).astype(jnp.int32)
    base = jnp.asarray(base, jnp.int32)
    steps = max(1, cap.bit_length())
    for _ in range(steps):
        active = lo < hi
        mid = (lo + hi) >> 1
        at = jnp.clip(base + mid, 0, cap - 1)
        seg = [w[at] for w in words]
        # side=left: descend right while seg[mid] <  q  (first idx with seg >= q)
        # side=right: descend right while seg[mid] <= q (first idx with seg >  q)
        go_right = _lex_less(seg, queries, or_equal=right) & active
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
    return lo


def np_column_radix_words(
    dt: DataType,
    data,
    valid,
    lengths=None,
    ascending: bool = True,
    nulls_first: bool = True,
):
    """Numpy twin of :func:`column_radix_words` for the CPU engine's range
    partitioner. NOT the same word layout anymore: the device version packs
    validity into the value word for sub-64-bit types; this twin keeps the
    classic [validity, value64] pair. The engines never mix word spaces —
    do not compare words across the two functions."""
    import numpy as np

    valid = np.asarray(valid).astype(bool)
    one, zero, sign = np.uint64(1), np.uint64(0), np.uint64(1 << 63)
    vw = np.where(valid, one, zero) if nulls_first else np.where(valid, zero, one)
    words: list = []
    if isinstance(dt, StringType):
        if getattr(data, "ndim", 1) != 2 or lengths is None:
            from .hash import np_strings_to_padded

            data, lengths = np_strings_to_padded(data, valid)
        n, w = data.shape
        nwords = (w + 7) // 8
        padded = np.zeros((n, nwords * 8), dtype=np.uint8)
        padded[:, :w] = data
        d64 = padded.astype(np.uint64).reshape(n, nwords, 8)
        shifts = np.arange(7, -1, -1, dtype=np.uint64) * np.uint64(8)
        packed = (d64 << shifts[None, None, :]).sum(axis=-1, dtype=np.uint64)
        words = [packed[:, k] for k in range(nwords)]
        words.append(np.asarray(lengths).astype(np.uint64))
    elif isinstance(dt, BooleanType):
        words.append(np.asarray(data).astype(np.uint64))
    elif isinstance(dt, (FloatType, DoubleType)):
        from ..exec.cpu_kernels import normalized_float_bits

        b = normalized_float_bits(np.asarray(data))
        words.append(np.where(b < 0, ~b.view(np.uint64), b.view(np.uint64) | sign))
    else:  # integral / date / timestamp / decimal(int64)
        words.append((np.asarray(data).astype(np.int64).view(np.uint64)) ^ sign)
    words = [np.where(valid, wd, zero) for wd in words]
    if not ascending:
        words = [~wd for wd in words]
    return [vw] + words


def segment_starts(words: list[jax.Array], row_mask: jax.Array) -> jax.Array:
    """bool[cap]: row i starts a new group (equal radix words ⇔ equal keys).
    Assumes rows already sorted by ``words`` with live rows first."""
    cap = words[0].shape[0]
    diff = jnp.zeros(cap, dtype=bool)
    for w in words:
        prev = jnp.concatenate([w[:1], w[:-1]])
        diff = diff | (w != prev)
    first = jnp.arange(cap) == 0
    return (diff | first) & row_mask
