"""Order-preserving radix key encoding — the foundation of device sort,
sort-based group-by, and sort-merge machinery.

The reference leans on cudf's type-aware comparators (Table.orderBy,
groupBy). The TPU-first design instead maps every SQL value to bits whose
unsigned order equals Spark's sort order and sorts them with an LSD radix
sort of stable single-key ``jax.lax.sort`` passes — one fused kernel, no
custom comparators, static shapes. Two encodings of the same order:

* **fields** (``column_key_fields`` → ``packed_key``): every value as uint32
  fields of static bit widths (a validity bit, an int8 in 8 bits, a string's
  length in ``plane_width.bit_length()``), concatenated into the fewest
  uint32 words. What the sort, the group-by and the window run: a sort pass
  and two full-capacity gathers cost per WORD, so the key carries no bit
  that is known to be zero.
* **uint64 radix words** (``column_radix_words``): one or more whole words a
  column, comparable across columns of different integer widths. What the
  join, the out-of-core merge, the range bounds and the string min/max
  arg-scan compare.

Orderings implemented to Spark's spec:
* NULLs first/last via a leading validity word
* floats: IEEE total-order bit trick with Spark's NaN semantics (all NaNs
  collapse to one greatest value) and -0.0 == 0.0 normalization
* strings: padded UTF-8 bytes packed big-endian 8-per-word, ties broken by
  length (exact lexicographic byte order, incl. interior NULs)
* descending via bitwise complement of the value words
"""
from __future__ import annotations

import contextlib
import threading
from typing import NamedTuple

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


def _float32_bits_ordered(data: jax.Array) -> jax.Array:
    """Map float32 to uint32 preserving Spark order (NaN greatest, -0==0)."""
    x = data.astype(jnp.float32)
    x = jnp.where(x == 0.0, jnp.float32(0.0), x)  # -0.0 -> +0.0
    x = jnp.where(jnp.isnan(x), jnp.float32(jnp.nan), x)  # canonical NaN
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> jnp.uint32(31) == jnp.uint32(1), ~u, u | jnp.uint32(1 << 31))


def _float_bits_ordered(data: jax.Array, dt: DataType) -> jax.Array:
    """Map float to uint64 preserving Spark order (NaN greatest, -0==0)."""
    if isinstance(dt, FloatType):
        return _float32_bits_ordered(data).astype(jnp.uint64)
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


def sort_permutation(
    words: list[jax.Array],
    row_mask: jax.Array,
    live_first: bool = True,
) -> jax.Array:
    """Stable sort permutation over uint64 radix words; padding rows sort
    last. Each 64-bit word goes as two 32-bit passes of ``_radix_passes``:
    64-bit types are emulated on the TPU, and the one embedded sort compiles
    about three times faster on a uint32 key. Callers whose key is theirs
    alone (sort, group-by, window) run ``packed_key`` instead."""
    halves = []  # most-significant first
    if live_first:
        halves.append(jnp.where(row_mask, jnp.uint32(0), jnp.uint32(1)))
    for w in words:
        w = w.astype(jnp.uint64)
        halves.append((w >> jnp.uint64(32)).astype(jnp.uint32))
        halves.append(w.astype(jnp.uint32))
    return _radix_passes(jnp.stack(halves))


def _radix_passes(keys: jax.Array) -> jax.Array:
    """Stable sort permutation by ``keys``, uint32[nkeys, cap] with the most
    significant key first.

    An LSD radix sort: a ``lax.scan`` of stable SINGLE-key ``lax.sort``
    passes from the least- to the most-significant key. XLA's TPU sort
    lowering compiles a full sorting network whose compile time grows
    sharply with both array size and operand count — a variadic
    ``lax.sort`` over k words compiled in O(minutes) at 2^16+ rows, while
    this form embeds exactly ONE two-operand sort in the program regardless
    of key count (the scan reuses it per key), with identical ordering
    semantics (stable passes ⇒ lexicographic). A pass is the gather
    ``w[perm]`` and the sort; on a v5e the gather is the larger part
    (PERF.md section 5), so what a sort costs is its number of keys."""
    cap = keys.shape[1]
    # inherit the data's varying-axis type so the scan carry matches inside
    # shard_map (a plain iota is replicated; the sorted perm is varying)
    iota = jnp.arange(cap, dtype=jnp.int32) + (keys[0] * jnp.uint32(0)).astype(
        jnp.int32
    )

    def one_pass(perm, w):
        _, perm = jax.lax.sort((w[perm], perm), num_keys=1, is_stable=True)
        return perm, None

    # least significant key first: the same array, read from its end
    perm, _ = jax.lax.scan(one_pass, iota, keys, reverse=True)
    return perm


# ── the key as one packed bit string ─────────────────────────────────────
def column_key_fields(
    col: DeviceColumn, ascending: bool = True, nulls_first: bool = True
) -> list[tuple[jax.Array, int]]:
    """Encode one column as ``(uint32 array, bits)`` fields, most significant
    first: every array holds values below ``2**bits``, and the unsigned
    order of the concatenated bits is the requested Spark ordering — the
    same order, ties included, as ``column_radix_words`` gives.

    A validity bit leads (nulls first/last); bool is 1 bit; int8/16/32, date
    and float32 their 8/16/32-bit order-preserving code; 64-bit types two
    32-bit fields; a string its plane bytes big-endian, 4 a field, then its
    length in ``plane_width.bit_length()`` bits (ties by length stay exact,
    interior NULs included). Descending complements the value bits within
    their width, never the validity bit; null slots hold zero value bits so
    that equal keys are equal bits. Built in uint32 throughout: 64-bit
    integers are emulated on the TPU."""
    dt, valid = col.dtype, col.validity
    null_bit = jnp.uint32(0 if nulls_first else 1)
    fields = [(jnp.where(valid, jnp.uint32(1) - null_bit, null_bit), 1)]

    def value(enc: jax.Array, bits: int):
        if not ascending:
            enc = enc ^ jnp.uint32((1 << bits) - 1)
        return (jnp.where(valid, enc, jnp.uint32(0)), bits)

    if isinstance(dt, StringType):
        data = col.data
        cap, w = data.shape
        for k in range(0, w, 4):
            n = min(4, w - k)
            enc = data[:, k].astype(jnp.uint32)
            for j in range(1, n):
                enc = (enc << jnp.uint32(8)) | data[:, k + j].astype(jnp.uint32)
            fields.append(value(enc, 8 * n))
        fields.append(value(col.lengths.astype(jnp.uint32), int(w).bit_length()))
    elif isinstance(dt, BooleanType):
        fields.append(value(col.data.astype(jnp.bool_).astype(jnp.uint32), 1))
    elif isinstance(dt, FloatType):
        fields.append(value(_float32_bits_ordered(col.data), 32))
    elif isinstance(dt, DoubleType):
        u = _float_bits_ordered(col.data, dt)  # 64-bit by nature of the data
        hi = (u >> jnp.uint64(32)).astype(jnp.uint32)
        fields += [value(hi, 32), value(u.astype(jnp.uint32), 32)]
    elif dt.np_dtype.itemsize <= 4:  # int8/16/32, date (and NullType's plane)
        bits = 8 * dt.np_dtype.itemsize
        enc = jax.lax.bitcast_convert_type(col.data.astype(jnp.int32), jnp.uint32)
        enc = (enc + jnp.uint32(1 << (bits - 1))) & jnp.uint32((1 << bits) - 1)
        fields.append(value(enc, bits))
    else:  # int64 / timestamp / decimal(int64)
        x = col.data.astype(jnp.int64)
        hi = (x >> jnp.int64(32)).astype(jnp.uint32) ^ jnp.uint32(1 << 31)
        fields += [value(hi, 32), value(x.astype(jnp.uint32), 32)]
    return fields


def unpacked_key_words(col: DeviceColumn) -> int:
    """How many uint64 words ``column_radix_words`` makes of this column:
    two sort passes each. Static: the dtype and the plane width."""
    if isinstance(col.dtype, StringType):
        return 1 + (col.data.shape[1] + 7) // 8 + 1
    return 1 if col.dtype.np_dtype.itemsize <= 4 else 2


def pack_fields(fields: list[tuple[jax.Array, int]]) -> list[jax.Array]:
    """Concatenate fields, most significant first, into the fewest uint32
    words; a field is split across a word boundary where it falls, and the
    last word's spare low bits are zero."""
    nwords = -(-sum(bits for _, bits in fields) // 32)
    words: list = [None] * nwords

    def put(i, part):
        words[i] = part if words[i] is None else words[i] | part

    pos = 0
    for arr, bits in fields:
        i, end = pos // 32, pos % 32 + bits
        if end <= 32:
            put(i, arr << jnp.uint32(32 - end) if end < 32 else arr)
        else:  # the low ``end - 32`` bits open the next word
            put(i, arr >> jnp.uint32(end - 32))
            put(i + 1, arr << jnp.uint32(64 - end))
        pos += bits
    return words


class PackedKey(NamedTuple):
    """A sort/group key as one bit string in uint32 words."""

    words: jax.Array  # uint32[nwords, cap], most significant word first
    column_end_bits: tuple  # where each column's fields end in the string
    unpacked_passes: int  # what two passes a uint64 radix word would run

    def sorted_words(self, perm: jax.Array) -> jax.Array:
        """Every word through ``perm`` in ONE gather. On a v5e a gather costs
        by the index, not by the byte: eight words of the stack move in
        1.7 times what one alone does, where a gather a word is 8 times
        (PERF.md section 6, PR 28)."""
        return self.words[:, perm]

    def prefix(self, words: jax.Array, ncols: int) -> jax.Array:
        """Of ``words`` (these, or these gathered), what holds the first
        ``ncols`` columns: whole words, and the last one masked."""
        full, rem = divmod(self.column_end_bits[ncols - 1], 32)
        if not rem:
            return words[:full]
        last = words[full] & jnp.uint32(((1 << rem) - 1) << (32 - rem))
        return jnp.concatenate([words[:full], last[None]])


def packed_key(
    columns: list[DeviceColumn],
    row_mask: jax.Array,
    ascendings: list[bool] | None = None,
    nulls_firsts: list[bool] | None = None,
) -> PackedKey:
    """The key over ``columns`` with a leading live flag (padding rows sort
    last): the order of ``sort_permutation`` over every column's
    ``column_radix_words``, in as few words as its bits need."""
    fields = [(jnp.where(row_mask, jnp.uint32(0), jnp.uint32(1)), 1)]
    ends, unpacked = [], 1
    for i, c in enumerate(columns):
        asc = True if ascendings is None else ascendings[i]
        nf = True if nulls_firsts is None else nulls_firsts[i]
        fields.extend(column_key_fields(c, asc, nf))
        ends.append(sum(bits for _, bits in fields))
        unpacked += 2 * unpacked_key_words(c)
    return PackedKey(jnp.stack(pack_fields(fields)), tuple(ends), unpacked)


_PASSES = threading.local()


@contextlib.contextmanager
def counting_passes():
    """Collect ``[passes run, passes unpacked]`` of every ``packed_sort``
    traced on this thread inside the block (``exec/``'s launch counters
    ``sort.keyPasses`` and ``sort.keyPassesUnpacked`` read it once per
    kernel and input signature, under ``jax.eval_shape``)."""
    _PASSES.count = count = [0, 0]
    try:
        yield count
    finally:
        _PASSES.count = None


def packed_sort(key: PackedKey) -> jax.Array:
    """Stable sort permutation by a packed key: one pass a word. By
    stability the permutation is that of the unpacked form, bit for bit."""
    count = getattr(_PASSES, "count", None)
    if count is not None:
        count[0] += key.words.shape[0]
        count[1] += key.unpacked_passes
    return _radix_passes(key.words)


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


def segment_starts(words, row_mask: jax.Array) -> jax.Array:
    """bool[cap]: row i starts a new group (equal words ⇔ equal keys).
    ``words`` is ``[nwords, cap]`` (or a list of ``[cap]`` words), the rows
    already sorted by them with live rows first."""
    w = words if isinstance(words, jax.Array) else jnp.stack(words)
    diff = (w[:, 1:] != w[:, :-1]).any(axis=0)
    return jnp.concatenate([jnp.ones(1, dtype=bool), diff]) & row_mask
