"""Relational kernels: group-by, join, sort/TopN, limit — XLA-native, static shapes.

Reference blueprint (SURVEY.md §2.5, §3.2 "hot loops"): FlatHash.putIfAbsent
(operator/FlatHash.java:251), PagesHash/JoinProbe (operator/join/), TopNOperator.
Trino's hot structures are open-addressing hash tables built row-at-a-time; on TPU
scatter-heavy hashing is hostile to the memory model, so every kernel here is
*sort-based* (SURVEY.md §7 "sort-based fallback" promoted to the primary strategy):

- group-by: lexsort keys -> boundary detection -> segment reductions. O(n log n)
  but fully vectorized on the VPU, no data-dependent shapes.
- join: argsort build keys -> searchsorted probes -> rank-space expansion. The
  expansion trick (searchsorted over match-offset prefix sums) produces arbitrary
  1:N matches into a *static* output capacity.
- TopN/sort: lexsort with direction/null-order encoded as extra key columns.

All kernels are mask-oblivious: inactive rows ride along with sentinel keys and are
dropped by the output ``active`` mask. Everything traces under jit/shard_map.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

INT64_MAX = np.iinfo(np.int64).max
INT64_MIN = np.iinfo(np.int64).min


def float_order_key(data: jnp.ndarray) -> jnp.ndarray:
    """IEEE doubles -> order-preserving signed int64 (sign-magnitude unfold:
    positives keep their bits, negatives map to ~bits with the sign bit set)."""
    bits = data.astype(jnp.float64).view(jnp.int64)
    return jnp.where(bits < 0, jnp.bitwise_xor(~bits, jnp.int64(INT64_MIN)), bits)


def order_key(data: jnp.ndarray) -> jnp.ndarray:
    if jnp.issubdtype(data.dtype, jnp.floating):
        return float_order_key(data)
    if data.dtype == jnp.bool_:
        return data.astype(jnp.int64)
    return data.astype(jnp.int64)


def encode_sort_column(
    data: jnp.ndarray, valid: jnp.ndarray, ascending: bool = True, nulls_first: bool = False
) -> jnp.ndarray:
    k = order_key(data)
    if not ascending:
        # avoid overflow on INT64_MIN: bitwise not (== -x-1) is order-reversing
        k = ~k
    sentinel = jnp.int64(INT64_MIN) if nulls_first else jnp.int64(INT64_MAX)
    return jnp.where(valid, k, sentinel)


def encode_sort_columns(
    data: jnp.ndarray, valid: jnp.ndarray, ascending: bool = True, nulls_first: bool = False
) -> List[jnp.ndarray]:
    """Sort keys for one column, most-significant first — usually one key;
    Int128 limb columns (ndim 2) contribute TWO (hi, then unsigned lo), the
    pad-and-mask long-decimal ordering (ref spi/type/Int128.java compareTo)."""
    if data.ndim == 2:
        from . import int128 as i128

        h, l = i128.order_key_pair(data)
        if not ascending:
            h, l = ~h, ~l
        sentinel = jnp.int64(INT64_MIN) if nulls_first else jnp.int64(INT64_MAX)
        return [jnp.where(valid, h, sentinel), jnp.where(valid, l, sentinel)]
    return [encode_sort_column(data, valid, ascending, nulls_first)]


def splitmix64(x: jnp.ndarray) -> jnp.ndarray:
    """SplitMix64 finalizer: int64 -> well-mixed int64 (wrapping arithmetic)."""
    x = x.astype(jnp.int64) + jnp.int64(-7046029254386353131)  # 0x9E3779B97F4A7C15
    x = (x ^ jax.lax.shift_right_logical(x, jnp.int64(30))) * jnp.int64(
        -4658895280553007687  # 0xBF58476D1CE4E5B9
    )
    x = (x ^ jax.lax.shift_right_logical(x, jnp.int64(27))) * jnp.int64(
        -7723592293110705685  # 0x94D049BB133111EB
    )
    return x ^ jax.lax.shift_right_logical(x, jnp.int64(31))


HLL_BITS = 11  # 2048 registers -> standard error 1.04/sqrt(2048) ~= 2.3%,
# matching the reference's default (spi/block -> airlift HyperLogLog,
# operator/aggregation/ApproximateCountDistinctAggregations default 0.023).


def hll_registers(
    vals: jnp.ndarray,
    weight: jnp.ndarray,
    gid: jnp.ndarray,
    num_groups: int,
    bits: int = HLL_BITS,
) -> jnp.ndarray:
    """Per-group HyperLogLog registers [num_groups, 2**bits] (int32).

    Each row hashes its value (SplitMix64 over the order key), takes the top
    ``bits`` bits as the bucket and the leading-zero count of the rest (+1) as
    rho; registers are the per-(group, bucket) max of rho via one scatter-max.
    This replaces the exact path's full cosort with a single scatter and a
    bounded [G, m] state — the property that matters at SF100 cardinalities.
    """
    m = 1 << bits
    h = splitmix64(order_key(vals))
    bucket = jax.lax.shift_right_logical(h, jnp.int64(64 - bits))
    rest = jax.lax.shift_left(h, jnp.int64(bits))
    rho = jnp.where(rest == 0, jnp.int64(64 - bits + 1), jax.lax.clz(rest) + 1)
    ids = jnp.where(weight, gid.astype(jnp.int64) * m + bucket, num_groups * m)
    regs = jax.ops.segment_max(
        rho.astype(jnp.int32), ids.astype(jnp.int32), num_segments=num_groups * m + 1
    )[: num_groups * m].reshape(num_groups, m)
    return jnp.maximum(regs, 0)  # empty slots come back as int32 min


def hll_estimate(regs: jnp.ndarray) -> jnp.ndarray:
    """Bias-corrected HLL estimate per group from [G, m] registers -> int64[G].

    Standard estimator with the linear-counting small-range correction; the
    64-bit hash makes the large-range correction unnecessary."""
    m = regs.shape[1]
    z = jnp.sum(jnp.exp2(-regs.astype(jnp.float32)), axis=1)
    alpha = 0.7213 / (1.0 + 1.079 / m)
    e = alpha * m * m / z
    v = jnp.sum((regs == 0).astype(jnp.int32), axis=1)
    small = (e <= 2.5 * m) & (v > 0)
    linear = m * jnp.log(m / jnp.maximum(v, 1).astype(jnp.float32))
    return jnp.round(jnp.where(small, linear, e)).astype(jnp.int64)


def cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """1-D inclusive cumsum that scales on TPU.

    XLA lowers big 1-D cumsums to a reduce-window whose scoped VMEM blows past
    the 16MB limit around a few million elements (observed at SF1). Two-level
    blocked scan: row-wise cumsum of (n/K, K) + exclusive prefix of row totals —
    every window stays K elements."""
    n = x.shape[0]
    K = 2048
    if n <= K * 4:
        return jnp.cumsum(x)
    pad = (-n) % K
    xp = jnp.pad(x, (0, pad)) if pad else x
    rows = xp.reshape(-1, K)
    within = jnp.cumsum(rows, axis=1)
    row_totals = within[:, -1]
    prefix = jnp.cumsum(row_totals) - row_totals
    out = (within + prefix[:, None]).reshape(-1)
    return out[:n] if pad else out


# --------------------------------------------------------------------------- #
# the sort family: order fields -> 32-bit words -> one two-key sort in a loop,
# payloads gathered by the permutation as one matrix
# --------------------------------------------------------------------------- #
#
# What the TPU's compiler charges for a sort grows with its operands, not
# with its rows: on a v5e's host a 2-operand 32-bit sort compiles in 7 to 16
# s (262,144 to 18.9M rows), every further 32-bit operand adds 6 to 14 s, a
# 64-bit key costs as much as three words, `is_stable` another operand (PERF.md
# section 6, PR 34). A page's columns riding a sort made single programs of 200
# to 500 s. So every sort here has two or three 32-bit operands whatever the
# page holds: a key word, the row's position as the second key (which makes the
# sort stable without `is_stable`), and the permutation so far. Run time, same
# chip, 18.9M rows: such a sort 52 to 72 ms; a 1-D gather of 4 / 8 bytes 163 /
# 326 ms (8.6 / 17 ns an element, ascending or random alike); eight 32-bit
# words gathered as one [8, n] matrix 278 ms (1.8 ns a word), which is why
# `gather_rows` packs what it moves.

_SIGN32 = np.uint32(1 << 31)


def order_field(data: jnp.ndarray, bits: Optional[int] = None) -> Tuple[jnp.ndarray, int]:
    """(unsigned value, its width in bits) whose unsigned order is the order of
    ``data``: a boolean takes 1 bit, a signed integer of w bits w (its sign
    bit flipped), a double 64 (``float_order_key``). ``bits``, where given,
    says that ``data`` holds codes in [0, 2**bits) (dictionary codes)."""
    if bits is not None:
        return data.astype(jnp.uint64) & jnp.uint64((1 << bits) - 1), bits
    if data.dtype == jnp.bool_:
        return data.astype(jnp.uint64), 1
    if jnp.issubdtype(data.dtype, jnp.floating):
        data = float_order_key(data)
    width = data.dtype.itemsize * 8
    if jnp.issubdtype(data.dtype, jnp.unsignedinteger):
        return data.astype(jnp.uint64), width
    if width == 64:
        return data.view(jnp.uint64) ^ jnp.uint64(1 << 63), 64
    return (data.astype(jnp.int64) + (1 << (width - 1))).astype(jnp.uint64), width


def sort_words(fields: Sequence[Tuple[jnp.ndarray, int]]) -> List[jnp.ndarray]:
    """The bit string ``fields`` spell (most significant first, each an
    ``order_field``) cut into int32 words, LEAST significant first: sorting by
    the words in that order, each pass stable, sorts by the fields. Fields
    are packed: seven dictionary-coded keys and their validity bits are two
    words, not fourteen passes."""
    words: List[jnp.ndarray] = []
    cur, cur_bits = None, 0
    for value, bits in reversed(list(fields)):
        pieces = [(value, bits)]
        if bits > 32:
            pieces = [(value & jnp.uint64(0xFFFFFFFF), 32), (value >> jnp.uint64(32), bits - 32)]
        for v, b in pieces:
            cur = v if cur is None else cur | (v << jnp.uint64(cur_bits))
            cur_bits += b
            if cur_bits >= 32:
                words.append((cur & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32))
                cur, cur_bits = cur >> jnp.uint64(32), cur_bits - 32
                if cur_bits == 0:
                    cur = None
    if cur_bits:
        words.append(cur.astype(jnp.uint32))
    # unsigned order as signed words: flip the top bit, keep the bits
    return [jax.lax.bitcast_convert_type(w ^ _SIGN32, jnp.int32) for w in words]


def sort_perm(fields: Sequence[Tuple[jnp.ndarray, int]]) -> jnp.ndarray:
    """The stable ascending permutation (int32) of rows ordered by ``fields``
    (most significant first). One sort instance whatever the number of keys:
    the words of ``sort_words`` are stacked and a loop runs one pass a word,
    least significant first; a pass sorts (word, position, permutation) on
    its first two operands. A word that is the same in every row orders
    nothing and its pass is skipped at run time (the upper half of a bigint
    key that stays under 2**32, validity bits of columns without nulls)."""
    words = sort_words(fields)
    n = words[0].shape[0]
    if len(words) == 1:
        iota = jnp.arange(n, dtype=jnp.int32)
        return jax.lax.sort((words[0], iota), num_keys=2, is_stable=False)[1]
    stack = jnp.stack(words)
    # zero, but typed as the words are: under shard_map the loop's state has
    # to vary over the mesh as any of them does (XLA folds x ^ x)
    zero = stack[0] ^ stack[0]
    iota = jnp.arange(n, dtype=jnp.int32) + zero
    orders = jnp.min(stack, axis=1) != jnp.max(stack, axis=1)

    def one_pass(p, state):
        def run(state):
            perm, moved = state
            # the first pass that runs reads its word in place: perm is iota
            word = jax.lax.cond(moved, lambda: stack[p][perm], lambda: stack[p])
            perm = jax.lax.sort((word, iota, perm), num_keys=2, is_stable=False)[2]
            return perm, zero[0] == 0

        return jax.lax.cond(orders[p], run, lambda state: state, state)

    perm, _ = jax.lax.fori_loop(0, len(words), one_pass, (iota, zero[0] != 0))
    return perm


# What the two forms of `gather_rows` cost on a v5e, read by
# tools/gather_probe.py (chiprun_out/pr35_probe/probe.json, PR 35): q14's
# eight arrays (three 64-bit, one 32-bit, four masks: 11 gathers of their own,
# or 8 words of one matrix) moved by m ascending indices out of n rows, ms:
#
#   n = 18,874,368   m = 1,024  16,384  65,536  262,144  1,048,576
#   plain                 4.08    6.03   12.87    42.62     202.64
#   packed                7.99    8.13    8.75    11.50      22.39
#   n = 1,048,576    plain 1.31   2.84    7.39    29.52     155.01
#                    packed 1.42  1.49    1.59     2.37       5.26
#
# A gather of one 32-bit array costs 12 to 18 ns a slot wherever the slots
# lie (a 64-bit array is two such gathers, a mask one): 11 gathers of 2.3 to
# 4.1 ms at q14's shape, ascending or shuffled. Packed, a slot of up to eight
# words is one (8, 128) tile fetched, 13.5 ns (the gather: 3.3 ms), after a
# pass that writes the matrix (`concatenate` 2.5 ms: XLA does not fuse it into
# the gather) and the upper words: 3.9 ms over what plain pays to read 64-bit
# arrays at all, 0.026 ns a word of a row. The forms cross near one row in
# 600 moved (n = 16,777,216: plain 5.82 and packed 7.32 ms at one in 1,024,
# 12.08 and 8.10 at one in 256), not at one in 8. Held to one, two, three
# and sixteen arrays (pr35_probe2/widths.json): right wherever the forms
# differ by more than a factor of two, a millisecond lost at three shapes
# nearer the crossover. A third form (each array as [n/128, 128], the row
# gathered and the lane selected: no pass) reads 24.9 ms at q14's shape and
# within a millisecond of the cheaper of these two everywhere below it: not
# kept.
_PLAIN_SLOT_NS = 12.7    # a slot of one 32-bit array, gathered by itself
_PACKED_SLOT_NS = 13.5   # a slot of the [words, n] matrix, for each eight words
_PACK_WORD_NS = 0.026    # a word of a row, written into the matrix


def gather_form(n: int, m: int, gathers: int, words: int) -> str:
    """How ``gather_rows`` moves ``m`` rows of ``n``: ``packed`` or ``plain``,
    whichever costs less on a v5e by the constants above. ``gathers`` is the
    32-bit gathers the plain form makes and ``words`` the 32-bit words the
    packed form stacks (``gather_shape``). Static shapes only: the choice is
    made while the program is traced, and ``executor._compact`` states it
    on its span by the same call."""
    plain = gathers * m * _PLAIN_SLOT_NS
    packed = words * n * _PACK_WORD_NS + -(-words // 8) * m * _PACKED_SLOT_NS
    return "packed" if packed < plain else "plain"


def _packable(a) -> bool:
    # a double is gathered as it is: the TPU emulates it, and its compiler
    # cannot bitcast it into the two words a 64-bit integer makes
    return a.ndim == 1 and a.dtype.itemsize <= 8 and a.dtype != jnp.float64


def gather_shape(arrays: Sequence) -> Tuple[int, int]:
    """(gathers, words) of what ``gather_rows`` may pack of ``arrays`` (arrays
    or their shapes): a 64-bit array is two gathers and two words, up to 32
    masks share a word. Arrays of more dimensions count for neither."""
    flat = [a for a in arrays if _packable(a)]
    flags = sum(a.dtype == jnp.bool_ for a in flat)
    gathers = len(flat) + sum(a.dtype.itemsize == 8 for a in flat)
    return gathers, gathers - flags + -(-flags // 32)


def gather_rows(arrays: Sequence[jnp.ndarray], idx: jnp.ndarray) -> List[jnp.ndarray]:
    """``[a[idx] for a in arrays]``, in the form ``gather_form`` finds cheaper
    for these shapes. Packed, arrays of one dimension travel together: they
    are cut into 32-bit words (a 64-bit value is two, up to 32 booleans share
    one), stacked as one [words, n] matrix and gathered in one gather. Arrays
    of more dimensions (Int128 limbs, vectors, array lanes) are rows already
    and are gathered as they are."""
    arrays = list(arrays)
    n = arrays[0].shape[0] if arrays else 0
    if gather_form(n, idx.shape[0], *gather_shape(arrays)) == "plain":
        return [a[idx] for a in arrays]
    return _gather_packed(arrays, idx)


def _gather_packed(arrays: List[jnp.ndarray], idx: jnp.ndarray) -> List[jnp.ndarray]:
    """``gather_rows``' packed form: the arrays that can be packed as 32-bit
    words of one [words, n] matrix, one gather, and the words put back
    together; the others gathered as they are."""
    words, plan = _pack_words(arrays)
    moved = jnp.stack(words)[:, idx]
    return [_unpack_words(moved, plan[i], a) if i in plan else a[idx] for i, a in enumerate(arrays)]


def _pack_words(arrays: List[jnp.ndarray]):
    """The arrays of one dimension cut into int32 words (a 64-bit value is
    two, up to 32 booleans share one, an 8- or 16-bit value is widened), and
    where each such array's words are: {index: (first word, kind)}, kind 2, 1
    or 0 by the width and -1 - bit for a flag."""
    n = arrays[0].shape[0]
    words: List[jnp.ndarray] = []
    plan = {}
    flags: List[int] = []
    for i, a in enumerate(arrays):
        if not _packable(a):
            continue
        if a.dtype == jnp.bool_:
            flags.append(i)
        elif a.dtype.itemsize == 8:
            v = a.view(jnp.uint64)
            plan[i] = (len(words), 2)
            words.append(jax.lax.bitcast_convert_type(v.astype(jnp.uint32), jnp.int32))
            words.append(jax.lax.bitcast_convert_type((v >> jnp.uint64(32)).astype(jnp.uint32), jnp.int32))
        elif a.dtype.itemsize == 4:
            plan[i] = (len(words), 1)
            words.append(a if a.dtype == jnp.int32 else jax.lax.bitcast_convert_type(a, jnp.int32))
        else:
            plan[i] = (len(words), 0)
            words.append(a.astype(jnp.int32))
    for at in range(0, len(flags), 32):
        word = jnp.zeros((n,), dtype=jnp.uint32)
        for bit, i in enumerate(flags[at:at + 32]):
            word = word | (arrays[i].astype(jnp.uint32) << jnp.uint32(bit))
            plan[i] = (len(words), -1 - bit)
        words.append(jax.lax.bitcast_convert_type(word, jnp.int32))
    return words, plan


def _unpack_words(moved: jnp.ndarray, where: Tuple[int, int], like: jnp.ndarray) -> jnp.ndarray:
    """The array ``like`` was, from the gathered [words, m] matrix."""
    at, kind = where
    if kind < 0:
        return ((moved[at] >> jnp.int32(-1 - kind)) & 1).astype(jnp.bool_)
    if kind == 2:
        lo = jax.lax.bitcast_convert_type(moved[at], jnp.uint32).astype(jnp.uint64)
        hi = jax.lax.bitcast_convert_type(moved[at + 1], jnp.uint32).astype(jnp.uint64)
        return ((hi << jnp.uint64(32)) | lo).view(like.dtype)
    if kind == 1:
        return moved[at] if like.dtype == jnp.int32 else jax.lax.bitcast_convert_type(moved[at], like.dtype)
    return moved[at].astype(like.dtype)


def cummax(x: jnp.ndarray) -> jnp.ndarray:
    """1-D inclusive running maximum, blocked as ``cumsum`` is: the TPU's
    compiler takes 15 s over a flat ``lax.cummax`` of 524,288 elements and
    under a second over rows of 2,048 and the rows' maxima (PR 34)."""
    n = x.shape[0]
    K = 2048
    if n <= K * 4:
        return jax.lax.cummax(x)
    least = jnp.iinfo(x.dtype).min if jnp.issubdtype(x.dtype, jnp.integer) else -jnp.inf
    pad = (-n) % K
    xp = jnp.pad(x, (0, pad), constant_values=least) if pad else x
    within = jax.lax.cummax(xp.reshape(-1, K), axis=1)
    upto = cummax(within[:, -1])  # rows' maxima, inclusive
    before = jnp.concatenate([jnp.full((1,), least, x.dtype), upto[:-1]])
    out = jnp.maximum(within, before[:, None]).reshape(-1)
    return out[:n] if pad else out


def segment_running(values: jnp.ndarray, new_segment: jnp.ndarray, kind: str) -> jnp.ndarray:
    """1-D inclusive running minimum or maximum (``kind``) that starts anew at
    every row ``new_segment`` flags: row i holds the extreme of its segment's
    rows up to i, so a segment's last row holds the segment's.

    No scatter and no scan of fixed depth: every row knows how far it is from
    its segment's first row (one ``cummax`` of the flagged positions), and a
    loop doubles a shift s = 1, 2, 4, ...: a row at least s rows into its
    segment takes in the row s before it, which by then covers the s rows up
    to itself. The loop ends once s passes the longest segment, so it makes
    log2 of the longest segment passes over the array (three where a group
    is an order's one to seven lines) and each pass is one shifted read and
    an elementwise op. A scatter-min of 18.9M rows into 4.5M groups took 2.07 s
    on a v5e (PR 36: TPC-H Q21's min and max of l_suppkey by l_orderkey)."""
    op = jnp.minimum if kind == "min" else jnp.maximum
    n = values.shape[0]
    at = jnp.arange(n, dtype=jnp.int32)
    into = at - cummax(jnp.where(new_segment, at, 0))  # rows since the segment's first
    longest = jnp.max(into)

    def widen(state):
        shift, running = state
        return shift * 2, jnp.where(into >= shift, op(running, jnp.roll(running, shift)), running)

    return jax.lax.while_loop(lambda state: state[0] <= longest, widen, (jnp.int32(1), values))[1]


def lexsort_perm(keys: Sequence[jnp.ndarray], active: jnp.ndarray) -> jnp.ndarray:
    """Permutation sorting by keys (first = most significant); inactive rows
    last; stable. ``sort_perm`` over the keys' order fields."""
    fields = [order_field(~active)] + [order_field(k) for k in keys]
    return sort_perm(fields)


def cosort(pass_keys: Sequence[jnp.ndarray], payloads: Sequence[jnp.ndarray]):
    """Stable sort by ``pass_keys`` (least significant first: the last is
    primary) that brings ``payloads`` along. Returns (sorted_pass_keys,
    sorted_payloads). The permutation comes from ``sort_perm``; keys and
    payloads follow it in one ``gather_rows``, so the program holds one sort
    of three operands whatever it carries."""
    pass_keys, payloads = list(pass_keys), list(payloads)
    perm = sort_perm([order_field(k) for k in reversed(pass_keys)])
    moved = gather_rows(pass_keys + payloads, perm)
    return moved[: len(pass_keys)], moved[len(pass_keys):]


def last_active_prev(vals: jnp.ndarray, active: jnp.ndarray):
    """For each row i, the value at the most recent ACTIVE row strictly before
    i (and whether one exists): lets presorted grouping skip sorts even when
    inactive (filtered) rows are interleaved. Read only at active rows.

    Every row knows how far back its last active row lies (one blocked
    ``cummax`` of the active positions), and a loop brings the value over by
    the bits of that distance, least first: at shift s a row whose distance
    has bit s set takes the row s before it, which by then holds the value the
    lower bits reach from there. The loop runs to the longest distance an
    active row's predecessor needs, so a dense page makes no pass and a page
    with runs of up to seven filtered rows three; a flat
    ``lax.associative_scan`` over 18.9M rows did not compile for a v5e
    inside 900 s (PR 36)."""
    n = vals.shape[0]
    at = jnp.arange(n, dtype=jnp.int32)
    last = cummax(jnp.where(active, at, -1))  # the last active row at or before i, -1 if none
    back = at - last
    # the rows read are the predecessors of active rows
    read = jnp.concatenate([active[1:], jnp.zeros((1,), active.dtype)]) & (last >= 0)
    longest = jnp.max(jnp.where(read, back, 0))

    def reach(state):
        shift, held = state
        return shift * 2, jnp.where((back & shift) != 0, jnp.roll(held, shift), held)

    held = jax.lax.while_loop(
        lambda state: state[0] <= longest, reach, (jnp.int32(1), jnp.where(active, vals, 0))
    )[1]
    # exclusive: shift right by one
    prev_vals = jnp.roll(held, 1).at[0].set(0)
    prev_has = jnp.roll(last >= 0, 1).at[0].set(False)
    return prev_vals, prev_has


# live_indices cuts the mask into rows of this many entries: the most whose
# counts (0..255 live entries before a lane) fit a byte, so that a slot
# gathers 256 bytes.
_LIVE_ROW = 256
# Keeping more than one row in this many, a single sort of the positions is
# cheaper than 26 ns a slot (v5e, capacity 37.7M: the sort takes 117 ms at any
# share kept; PERF.md section 6). `executor._compact_path` turns on it too.
LIVE_INDEX_SHARE = 16


def live_indices(active: jnp.ndarray, new_cap: int) -> jnp.ndarray:
    """Positions (int32) of the first ``new_cap`` True entries of ``active``
    in row order; slots past the live count hold n = len(active).

    No sort of the page and no scatter over it: the mask is cut into rows of
    256, one pass counts the live entries before each lane of a row, the rows'
    totals place every output slot in its row (``expand_probe_slots``: one
    scatter of the row starts, n/256 updates, and a cummax over the slots),
    and the lane is the number of the row's counts that do not exceed the
    slot's ordinal within the row: one gather of a 256-byte row per slot.
    Cost: one pass over the mask plus work per SLOT, whatever the density
    and however the live entries cluster. When most of the page is kept the
    per-slot work loses to a single-operand sort of the positions, which
    costs the same at any ``new_cap``; the choice is on the static shapes."""
    n = active.shape[0]
    if new_cap * LIVE_INDEX_SHARE > n:
        pos = jnp.where(active, jnp.arange(n, dtype=jnp.int32), jnp.int32(n))
        # a sort of one operand takes the TPU's compiler 23 s at 18.9M rows, the
        # same sort with a second operand that nothing reads 4 s (PR 34)
        idx = jax.lax.sort((pos, jnp.zeros((n,), jnp.int8)), num_keys=1, is_stable=False)[0][:new_cap]
        if n < new_cap:
            idx = jnp.pad(idx, (0, new_cap - n), constant_values=n)
        return idx
    pad = (-n) % _LIVE_ROW
    rows = (jnp.pad(active, (0, pad)) if pad else active).reshape(-1, _LIVE_ROW)
    ones = rows.astype(jnp.int32)
    before = jnp.cumsum(ones, axis=1, dtype=jnp.int32) - ones  # live left of each lane
    per_row = before[:, -1] + ones[:, -1]
    row_of, ordinal, in_range, _ = expand_probe_slots(per_row, new_cap)
    # `before` is nondecreasing along a row and reaches `ordinal` at the lane
    # sought, so that lane is (how many counts are <= ordinal) - 1
    at_most = before.astype(jnp.uint8)[row_of] <= ordinal.astype(jnp.uint8)[:, None]
    lane = jnp.sum(at_most, axis=1, dtype=jnp.int32) - 1
    idx = row_of.astype(jnp.int32) * _LIVE_ROW + lane
    return jnp.where(in_range, idx, jnp.int32(n))


# --------------------------------------------------------------------------- #
# group-by
# --------------------------------------------------------------------------- #


def group_ids(
    key_cols: Sequence[Tuple[jnp.ndarray, jnp.ndarray]],
    active: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sort-based grouping (the FlatGroupByHash analogue).

    Returns (perm, gid_sorted, new_group_sorted, num_groups):
    - perm: sort permutation placing equal keys adjacent, inactive rows last
    - gid_sorted[i]: dense group id of sorted row i (valid where active)
    - new_group_sorted[i]: True at each group's first sorted row
    - num_groups: scalar count of groups
    """
    cap = active.shape[0]
    norm_keys = []
    for data, valid in key_cols:
        if data.ndim == 2:  # Int128 limbs: two grouping keys
            from . import int128 as i128

            h, l = i128.order_key_pair(data)
            norm_keys.append(jnp.where(valid, h, jnp.int64(INT64_MAX)))
            norm_keys.append(jnp.where(valid, l, jnp.int64(INT64_MAX)))
        else:
            k = order_key(data)
            k = jnp.where(valid, k, jnp.int64(INT64_MAX))  # nulls group last
            norm_keys.append(k)
        v = valid.astype(jnp.int8)  # distinguishes null from a real INT64_MAX
        norm_keys.append(v)
    if not norm_keys:
        # global aggregation: single group of active rows
        perm = jnp.arange(cap)
        gid = jnp.zeros(cap, dtype=jnp.int32)
        new_group = jnp.zeros(cap, dtype=bool).at[0].set(True)
        return perm, gid, new_group, jnp.int32(1)
    perm = lexsort_perm(norm_keys, active)
    active_s = active[perm]
    sorted_keys = [k[perm] for k in norm_keys]
    diff = jnp.zeros(cap, dtype=bool)
    for k in sorted_keys:
        diff = diff | (k != jnp.roll(k, 1))
    first = jnp.zeros(cap, dtype=bool).at[0].set(True)
    prev_active = jnp.roll(active_s, 1).at[0].set(False)
    new_group = active_s & (first | diff | ~prev_active)
    gid = (cumsum(new_group.astype(jnp.int32)) - 1).astype(jnp.int32)
    num_groups = jnp.sum(new_group.astype(jnp.int32))
    return perm, gid, new_group, num_groups


# bitwise aggregate reduces (BitwiseAndAggregation/BitwiseOrAggregation —
# xor_agg added in newer reference versions): op + identity
_BIT_OPS = {
    "band": (lambda a, b: a & b, -1),
    "bor": (lambda a, b: a | b, 0),
    "bxor": (lambda a, b: a ^ b, 0),
}


def reads_at_ends(values: jnp.ndarray, kind: str) -> bool:
    """Whether ``segment_reduce_at_ends`` computes this reduction: counts,
    exact (integer) sums, and the extremes of a 1-D column. A floating sum
    keeps ``segment_reduce``'s own form (it adds the segment's first value
    back and reads two places)."""
    if values.ndim != 1:
        return False
    return kind in ("count", "min", "max") or (
        kind == "sum" and jnp.issubdtype(values.dtype, jnp.integer)
    )


def segment_scan(values: jnp.ndarray, weight: jnp.ndarray, kind: str, new_segment: jnp.ndarray):
    """The per-row array whose reads at the segments' last rows give the
    reduction ``kind`` of each segment of group-sorted rows: for ``min`` and
    ``max`` the running extreme that starts anew at every segment
    (``segment_running``), for ``sum`` and ``count`` the running sum over the
    whole page (a segment's sum is the difference of two neighbouring reads).
    Rows outside ``weight`` take no part."""
    if kind == "count":
        # a count never passes the page's rows: one 32-bit word to read, not two
        n = weight.shape[0]
        return cumsum(weight.astype(jnp.int32 if n < 2**31 else jnp.int64))
    if kind == "sum":
        return cumsum(jnp.where(weight, values, jnp.zeros_like(values)))
    vals = jnp.where(weight, values, _reduce_identity(values.dtype, kind))
    return segment_running(vals, new_segment, kind)


def segment_reduce_at_ends(requests, new_segment: jnp.ndarray, ends: jnp.ndarray):
    """The reductions ``[(values, weight, kind), ...]`` (``reads_at_ends``
    holds for each) over group-sorted rows whose segments end at rows
    ``ends``, one slot a segment: every request's ``segment_scan`` read at
    ``ends`` in ONE ``gather_rows``. On a v5e a slot of one 32-bit array
    gathered by itself costs what a slot of eight words gathered together does
    (PR 35), and TPC-H Q21's min, max and count of l_suppkey over 4.5M orders
    were seven such gathers of 5.2M slots, 0.5 of the aggregation's 0.57 s
    (PR 36).

    A segment without a participant reads the identity (0 for a sum or a
    count), and a slot past the segments what the last segment's end holds
    (``ends`` is padded with the page's last row): callers mask both by the
    participant count."""
    n = new_segment.shape[0]
    at = jnp.clip(ends, 0, n - 1)
    moved = gather_rows([segment_scan(v, w, kind, new_segment) for v, w, kind in requests], at)
    out = []
    for (values, _, kind), read in zip(requests, moved):
        if kind in ("sum", "count"):
            # rows of no weight lie before the first segment and between
            # segments, so the running sum at the segment before's end is the
            # one before this segment's first row
            read = read - jnp.concatenate([jnp.zeros((1,), read.dtype), read[:-1]])
            read = read.astype(jnp.int64 if kind == "count" else values.dtype)
        out.append(read)
    return out


def segment_reduce(
    values_sorted: jnp.ndarray,
    weight_sorted: jnp.ndarray,  # bool: row participates
    gid_sorted: jnp.ndarray,
    capacity: int,
    kind: str,
    new_group_sorted: Optional[jnp.ndarray] = None,
    bounds: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
):
    """Masked segment reduction into ``capacity`` output slots.

    For sum/count with segment boundaries available (``new_group_sorted``), uses
    the cumsum-at-boundaries formulation instead of scatter-add: rows are sorted
    by group, so segment g's sum is csum[end_g] - csum[start_g] + v[start_g].
    TPU scatters serialize; cumsum + two small gathers vectorize fully. With
    ``bounds`` too, counts, exact sums and min/max are
    ``segment_reduce_at_ends`` of the one request: one gather.
    """
    if capacity == 1:
        # global aggregation: plain masked reduction
        if kind == "sum":
            vals = jnp.where(weight_sorted, values_sorted, jnp.zeros_like(values_sorted))
            return jnp.sum(vals, keepdims=True)
        if kind == "count":
            return jnp.sum(weight_sorted.astype(jnp.int64), keepdims=True)
        if kind == "min":
            return jnp.min(values_sorted, keepdims=True)
        if kind == "max":
            return jnp.max(values_sorted, keepdims=True)
        if kind in _BIT_OPS:
            op, ident = _BIT_OPS[kind]
            vals = jnp.where(
                weight_sorted, values_sorted.astype(jnp.int64), jnp.int64(ident)
            )
            return jax.lax.reduce(vals, jnp.int64(ident), op, (0,))[None]
        raise ValueError(kind)
    if new_group_sorted is not None and bounds is not None and reads_at_ends(values_sorted, kind):
        # rows are group-sorted and the segments' bounds known: exact sums,
        # counts and extremes are read at the segments' last rows
        return segment_reduce_at_ends(
            [(values_sorted, weight_sorted, kind)], new_group_sorted, bounds[1]
        )[0]
    if kind in ("sum", "count") and new_group_sorted is not None:
        vals = (
            weight_sorted.astype(jnp.int64)
            if kind == "count"
            else jnp.where(weight_sorted, values_sorted, jnp.zeros_like(values_sorted))
        )
        csum = cumsum(vals)
        n = values_sorted.shape[0]
        if bounds is not None:
            start, end = bounds
        else:
            idx = jnp.arange(n)
            # start[g] = first sorted row of group g; slots with no group default
            # to n so that end[g] = start[g+1] - 1 is n-1 for the last real group
            ids = jnp.where(new_group_sorted, gid_sorted, capacity).astype(jnp.int32)
            start = jnp.full((capacity + 1,), n).at[ids].set(idx, mode="drop")[:capacity]
            end = jnp.concatenate([start[1:], jnp.array([n])]) - 1
        end = jnp.clip(end, 0, n - 1)
        start = jnp.clip(start, 0, n - 1)
        return csum[end] - csum[start] + vals[start]
    if kind in _BIT_OPS:
        # segmented associative scan (rows are group-sorted): carry =
        # (segment-start flag, accumulated value); combining across a
        # boundary restarts the accumulator — the classic segmented-scan
        # trick, which TPU/XLA lowers to a log-depth scan instead of the
        # serialized scatter a segment_or would need
        op, ident = _BIT_OPS[kind]
        n = values_sorted.shape[0]
        vals = jnp.where(
            weight_sorted, values_sorted.astype(jnp.int64), jnp.int64(ident)
        )
        # rows of a group are CONTIGUOUS (group-sorted) but group ids are not
        # monotone along the array, and padding rows carry junk ids — so the
        # read point per group is the scatter-max row index over its
        # PARTICIPATING rows, not a start[g+1]-1 walk
        boundary = (
            new_group_sorted
            if new_group_sorted is not None
            else jnp.concatenate(
                [jnp.ones((1,), bool), gid_sorted[1:] != gid_sorted[:-1]]
            )
        )

        def combine(a, b):
            af, av = a
            bf, bv = b
            return af | bf, jnp.where(bf, bv, op(av, bv))

        _, scanned = jax.lax.associative_scan(combine, (boundary, vals))
        idx = jnp.arange(n, dtype=jnp.int32)
        ids = jnp.where(weight_sorted, gid_sorted, capacity).astype(jnp.int32)
        ends = (
            jnp.zeros((capacity + 1,), dtype=jnp.int32)
            .at[ids].max(idx, mode="drop")[:capacity]
        )
        # groups with zero participants read scanned[0] — callers mask their
        # validity by the participant count
        return scanned[ends]
    ids = jnp.where(weight_sorted, gid_sorted, capacity).astype(jnp.int32)
    if kind == "sum":
        vals = jnp.where(weight_sorted, values_sorted, jnp.zeros_like(values_sorted))
        out = jax.ops.segment_sum(vals, ids, num_segments=capacity + 1)
    elif kind == "count":
        out = jax.ops.segment_sum(
            weight_sorted.astype(jnp.int64), ids, num_segments=capacity + 1
        )
    elif kind == "min":
        out = jax.ops.segment_min(values_sorted, ids, num_segments=capacity + 1)
    elif kind == "max":
        out = jax.ops.segment_max(values_sorted, ids, num_segments=capacity + 1)
    else:
        raise ValueError(kind)
    return out[:capacity]


def direct_group_reduce(
    values: jnp.ndarray,
    weight: jnp.ndarray,  # bool: row participates
    gid: jnp.ndarray,
    num_groups: int,
    kind: str,
) -> jnp.ndarray:
    """Grouped reduction for SMALL static group counts — no sort, no scatter.

    out[g] = reduce(values[i] for rows with gid[i]==g and weight[i]). The
    [G, n] broadcast-mask formulation: XLA fuses the compare/select producers
    into one row-wise reduction pass over the data, so a whole Q1-style
    aggregation is bandwidth-bound instead of sort-bound. Use only when the
    group-key domain is statically known and small (dictionary-coded keys);
    for large/unknown G the sort path (group_ids + segment_reduce) wins.
    (ref: BigintGroupByHash's small-domain fast path, GroupByHash.java:82)
    """
    if jax.default_backend() == "cpu":
        # XLA:CPU materializes the [G, n] mask per reduction (measured 181 ms
        # per reduce at n=6M vs 18 ms for segment_sum); its scatter-add is
        # fine. On TPU the opposite holds — scatter serializes, the masked
        # form streams at HBM rate — so this branch is backend-keyed at
        # trace time (programs are compiled per backend anyway).
        import jax.ops as jops

        if kind == "sum":
            vals = jnp.where(weight, values, jnp.zeros((), dtype=values.dtype))
            return jops.segment_sum(vals, gid, num_segments=num_groups)
        if kind == "count":
            return jops.segment_sum(
                weight.astype(jnp.int64), gid, num_segments=num_groups
            )
        if kind in ("min", "max"):
            ident = _reduce_identity(values.dtype, kind)
            vals = jnp.where(weight, values, ident)
            seg = jops.segment_min if kind == "min" else jops.segment_max
            out = seg(vals, gid, num_segments=num_groups)
            # segment_min/max yield dtype-extreme for EMPTY groups already
            # (identity fill) — matches the masked formulation
            return out
    onehot = gid[None, :] == jnp.arange(num_groups, dtype=gid.dtype)[:, None]
    w = onehot & weight[None, :]
    if kind == "sum":
        vals = jnp.where(w, values[None, :], jnp.zeros((), dtype=values.dtype))
        return jnp.sum(vals, axis=1)
    if kind == "count":
        return jnp.sum(w.astype(jnp.int64), axis=1)
    if kind in ("min", "max"):
        ident = _reduce_identity(values.dtype, kind)
        masked = jnp.where(w, values[None, :], ident)
        return (jnp.min if kind == "min" else jnp.max)(masked, axis=1)
    if kind in _BIT_OPS:
        op, ident = _BIT_OPS[kind]
        masked = jnp.where(w, values[None, :].astype(jnp.int64), jnp.int64(ident))
        return jax.lax.reduce(masked, jnp.int64(ident), op, (1,))
    raise ValueError(kind)


def _reduce_identity(dtype, kind: str):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf if kind == "min" else -jnp.inf, dtype=dtype)
    if dtype == jnp.bool_:
        return jnp.array(kind == "min", dtype=jnp.bool_)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if kind == "min" else info.min, dtype=dtype)


def direct_group_first(
    values: jnp.ndarray, weight: jnp.ndarray, gid: jnp.ndarray, num_groups: int
) -> jnp.ndarray:
    """out[g] = value of some participating row of group g (num_groups gathers)."""
    n = values.shape[0]
    onehot = (gid[None, :] == jnp.arange(num_groups, dtype=gid.dtype)[:, None]) & weight[None, :]
    idx = jnp.max(jnp.where(onehot, jnp.arange(n)[None, :], -1), axis=1)
    return values[jnp.clip(idx, 0, n - 1)]


def scatter_first(
    values_sorted: jnp.ndarray,
    new_group_sorted: jnp.ndarray,
    gid_sorted: jnp.ndarray,
    capacity: int,
) -> jnp.ndarray:
    """out[gid] = value at the group's first sorted row (for group keys)."""
    ids = jnp.where(new_group_sorted, gid_sorted, capacity).astype(jnp.int32)
    zero = jnp.zeros((capacity + 1,) + values_sorted.shape[1:], dtype=values_sorted.dtype)
    return zero.at[ids].set(values_sorted, mode="drop")[:capacity]


# --------------------------------------------------------------------------- #
# join
# --------------------------------------------------------------------------- #


def join_keys(
    probe_cols: Sequence[Tuple[jnp.ndarray, jnp.ndarray]],
    build_cols: Sequence[Tuple[jnp.ndarray, jnp.ndarray]],
):
    """Multi-column join keys as ``join_match`` takes them: (probe key
    columns, probe_valid, build key columns, build_valid), a row valid when
    every one of its key columns is. The columns stay apart: the match sorts
    by all of their words at once, so a key of several columns needs no
    renumbering pass and cannot collide (ref: JoinCompiler hashes then
    CONFIRMS equality, operator/join/PagesHash.java; here the comparison is
    on the values themselves)."""
    p_valid = probe_cols[0][1]
    for _, v in probe_cols[1:]:
        p_valid = p_valid & v
    b_valid = build_cols[0][1]
    for _, v in build_cols[1:]:
        b_valid = b_valid & v
    return [d for d, _ in probe_cols], p_valid, [d for d, _ in build_cols], b_valid


# A row's class in the merged order, where the tag carries one
# (``_merge_match`` given ``probe_live``): nothing (an inactive build, a probe
# row no longer live), an active build, a probe row whose key can match, a live
# probe row whose key cannot (null, or outside the narrowed range: a LEFT join
# still emits one null-padded row for it).
BUILD, PROBE_KEYED, PROBE_KEYLESS = 1, 2, 3


def _merge_match(build_keys, build_active, probe_keys, probe_active, key_bits, probe_live=None):
    """The one merge of a match: every build row and every probe row once,
    n + m rows. Returns, in merged order, (position, class, lo, count): the
    row's place in [builds, probes], 1 where it is an active build, and, for
    a probe's row, how many active builds hold a smaller key and how many hold
    its own. Where ``probe_live`` (the probe's live rows, whatever their key)
    is given, the tag carries two bits of the row's class (``BUILD``,
    ``PROBE_KEYED``, ``PROBE_KEYLESS``; 0 for the rest) in place of one bit of
    is_build, so that the expansion knows each probe row's part without a
    gather over the n + m rows."""
    if not isinstance(build_keys, (list, tuple)):
        build_keys, probe_keys = [build_keys], [probe_keys]
    n = probe_active.shape[0]
    m = build_active.shape[0]
    total = n + m
    tag_bits = 1 if probe_live is None else 2
    if total >= 1 << (31 - tag_bits):
        raise ValueError(
            f"join of {n} probe and {m} build rows: the tag needs n + m < 2**{31 - tag_bits}"
        )
    fields = []
    for i, (bk, pk) in enumerate(zip(build_keys, probe_keys)):
        bits = key_bits[i] if key_bits is not None else None
        if bits is not None:
            bk = jnp.where(build_active, bk, 0)
            fields.append(order_field(jnp.concatenate([bk, pk]), bits=bits))
            continue
        # integer keys of one width keep it (a date or an integer is one word,
        # a bigint two); anything else meets as an int64 order key
        if pk.dtype != bk.dtype or not jnp.issubdtype(pk.dtype, jnp.signedinteger):
            pk, bk = order_key(pk), order_key(bk)
        # an inactive build's key orders nothing: zero it
        bk = jnp.where(build_active, bk, jnp.zeros((), bk.dtype))
        fields.append(order_field(jnp.concatenate([bk, pk])))
    pos = jnp.arange(total, dtype=jnp.int32)
    builds = build_active.astype(jnp.int32)
    if probe_live is None:
        probes = jnp.zeros(n, jnp.int32)
    else:
        probes = jnp.where(probe_active, PROBE_KEYED, jnp.where(probe_live, PROBE_KEYLESS, 0)).astype(jnp.int32)
    classes = jnp.concatenate([builds, probes])
    words = sort_words(fields)[::-1]  # most significant first, as lax.sort compares
    *s_words, s_tag = jax.lax.sort(
        (*words, pos * (1 << tag_bits) + classes), num_keys=len(words) + 1, is_stable=False
    )
    if probe_live is None:
        s_class = s_is_build = s_tag & 1
    else:
        s_class = s_tag & 3
        s_is_build = (s_class == BUILD).astype(jnp.int32)
    # the builds stand before the probes, so a probe stands behind every build
    # of its key: the builds before it are those at or below its key (hi)
    builds_before = cumsum(s_is_build) - s_is_build  # exclusive
    # the sort hands the key's words back in order: a run of equal keys begins
    # where a word differs from the row before, and the count at a run's first
    # row (the builds strictly below the key) reaches the run's other rows by
    # a running maximum, the counts never falling
    differs = s_words[0][1:] != s_words[0][:-1]
    for w in s_words[1:]:
        differs = differs | (w[1:] != w[:-1])
    run_starts = jnp.concatenate([jnp.ones((1,), jnp.bool_), differs])
    lo = cummax(jnp.where(run_starts, builds_before, 0))
    return s_tag >> tag_bits, s_class, lo, builds_before - lo


def _ranks_back(s_pos, m: int, n: int, payloads):
    """``payloads`` of the merged order's probe rows, in the probe's order."""
    return _by_probe_row(jnp.where(s_pos >= m, s_pos - m, n), n, payloads)


def _by_probe_row(qid, n: int, payloads):
    """``payloads`` of the merged order's rows whose probe row number ``qid``
    is below ``n``, in the probe's order: one sort by that number, which is
    distinct; the builds (``qid`` = n) sort behind the probes. A sort and not
    a scatter: the TPU's scatter sorts its indices itself, with more
    operands."""
    return [p[:n] for p in jax.lax.sort((qid, *payloads), num_keys=1, is_stable=False)[1:]]


def rank_words(m: int) -> int:
    """Payload words a join's ranks travel back in (``join_match``): ``lo``
    and ``count`` lie in [0, m], and where two such fit 32 bits they share a
    word."""
    return 1 if 2 * m.bit_length() <= 32 else 2


def _pack_ranks(lo, count, m: int) -> list:
    """``lo`` and ``count`` as the payload words they travel in (``rank_words``)."""
    if rank_words(m) == 1:
        shift = m.bit_length()  # unsigned: at m = 65,535 the word is full
        return [(lo.astype(jnp.uint32) << shift) | count.astype(jnp.uint32)]
    return [lo, count]


def _unpack_ranks(words, m: int):
    """(lo, count) from the words ``_pack_ranks`` made."""
    if rank_words(m) == 1:
        shift = m.bit_length()
        (packed,) = words
        return (packed >> shift).astype(jnp.int32), (packed & jnp.uint32((1 << shift) - 1)).astype(jnp.int32)
    return tuple(words)


def join_match(build_keys, build_active, probe_keys, probe_active, key_bits=None):
    """Sorted-build matching: returns (perm_b, lo, hi, count) where sorted build
    rows [lo, hi) match each probe row. (PagesHash/JoinProbe analogue.)
    ``build_keys`` / ``probe_keys``: one int64 key column or a sequence of
    them (a multi-column key, most significant first). ``key_bits``, where
    given, holds for each column None or the bits its values fit (they are
    then in [0, 2**bits) on every active row of both sides): the columns'
    fields are packed, so narrow keys make a one-word sort.

    Probe ranks come from ONE merge sort, not searchsorted: binary search is
    ~20 dependent gather rounds over the probe (measured 2.5s for 6M probes
    into 1M build on v5e) while a sort of the concatenated keys streams.
    The merge holds every row once, n + m rows. Concat order IS the
    tie-break: [builds, probes], and the row's position is the sort's last
    key, so a probe stands behind its equal builds and the active builds
    before it count the keys <= its own (hi); the count where its run of
    equal keys begins, carried along the run, counts the keys strictly below
    (lo). The sort's operands are the key's 32-bit words and one tag,
    position * 2 + is_build: nothing else rides it (what a sort costs the
    TPU's compiler grows with its operands). Only ACTIVE builds carry
    is_build, so an inactive build row is never counted whatever its key
    holds (zeroed, it joins key 0's run and counts for nothing): no sentinel
    key, and a genuine INT64_MAX or INT64_MIN matches like any other. The
    way back to the probe's order is a second sort of n + m rows by the
    probe's row number that carries ``lo`` and ``count``: two payload words,
    or one where the build side is small enough for both (``rank_words``).
    ``perm_b`` lists the active build rows in key order, ties in row order;
    the slots after them hold row 0 and are never matched."""
    n = probe_active.shape[0]
    m = build_active.shape[0]
    s_pos, s_is_build, s_lo, s_count = _merge_match(
        build_keys, build_active, probe_keys, probe_active, key_bits
    )
    lo, count = _unpack_ranks(_ranks_back(s_pos, m, n, _pack_ranks(s_lo, s_count, m)), m)
    count = jnp.where(probe_active, count, 0)
    return _builds_in_order(s_pos, s_is_build, n, m), lo, lo + count, count


def _builds_in_order(s_pos, s_is_build, n: int, m: int):
    """``perm_b``: the builds in sorted order ARE it, so where the r-th active
    build stands in the merged order is read off the mask (``live_indices``: a
    walk over it where the builds are few among the probes, a sort of the
    positions where they are not). Slots past the active builds hold row 0:
    nothing matches there."""
    total = n + m
    at = live_indices(s_is_build == 1, m)
    return jnp.where(at < total, s_pos[jnp.minimum(at, total - 1)], 0)


def join_merge(build_keys, build_active, probe_keys, probe_active, probe_live, key_bits=None):
    """``join_match`` that stops at the merge: the ranks stay in the merged
    order, and the way back to the probe's order is the expansion's
    (``emitting_ranks`` or ``merged_ranks``, by ``ranks_form``). Returns
    (perm_b, qid, lo, count, live), the last four over the n + m merged rows:
    the probe row number (n on a build row), ``lo`` and ``count`` as
    ``join_match`` gives them (``count`` 0 where the probe row is not
    active), and whether the row is a live probe row (``probe_live``; a LEFT
    join emits for each). One sort, the merge, and ``perm_b``'s walk."""
    n = probe_active.shape[0]
    m = build_active.shape[0]
    s_pos, s_class, s_lo, s_count = _merge_match(
        build_keys, build_active, probe_keys, probe_active, key_bits, probe_live
    )
    qid = jnp.where(s_pos >= m, s_pos - m, n)
    count = jnp.where(s_class == PROBE_KEYED, s_count, 0)
    perm_b = _builds_in_order(s_pos, (s_class == BUILD).astype(jnp.int32), n, m)
    return perm_b, qid, s_lo, count, s_class >= PROBE_KEYED


# What the two ways back from the merged order cost on a v5e, read by
# tools/ranks_probe.py (its part `ways`) on merged orders of the join
# cells' shapes, ms:
#
#   merged_ranks      n + m = 17,301,504 (two rank words) 56.70; 22,020,096 (two) 66.55;
#                     18,882,560 (one) 47.45
#   emitting_ranks    n + m = 17,301,504, slots 32,768 / 262,144 / 1,048,576 (the walk)
#                     5.90 / 15.75 / 42.75; slots 2,097,152 / 4,194,304 (the sort) 55.96 / 96.12
#   expand_probe_slots  over a probe of 5,242,880 rows 47.44; over 262,144 listed rows 3.07
#
# A merged row costs 2.5 ns sorted with one rank word and 3.0 to 3.75 with two
# (the whole expansion's `%sort` at Q3's shape: 64.9 ms). Listing
# the emitting rows costs 0.27 ns a merged row and 17 ns a slot where
# `live_indices` walks, 0.92 ns a merged row where it sorts the positions (a
# top-k of `slots`), and then 19 ns a listed row (the gather, the sort of the
# list). The general expansion's scatter costs 9 ns an update. The whole
# `_jit_join_expand` (one bigint a side; the probe's part `programs`),
# emitting against merged: Q3's shape 22.0 against 76.8 ms with
# 131,072 rows emitting, 92.6 against 123.3 with 1,048,576; Q13's LEFT join
# (524,288 x 5,242,880, every customer emits) 290.3 against 363.0; Q5's
# (18,874,368 x 8,192, 3.6M emit) 218.2 against 197.5. The rule below picks
# the cheaper form at all six.
_MERGED_ROW_NS = {1: 2.5, 2: 3.4}   # a merged row, sorted by the probe row number with its rank words
_MASK_ROW_NS = 0.27                 # a row of live_indices' mask, where it walks
_WALK_SLOT_NS = 17.0                # a slot of live_indices' walk
_POSITIONS_ROW_NS = 0.92            # a row of live_indices' mask, where it sorts the positions
_LISTED_SLOT_NS = 19.0              # a listed row: gathered, then sorted by the probe row number
_SCATTER_UPDATE_NS = 9.0            # an update of expand_probe_slots' scatter


def _finding_ns(rows: int, slots: int) -> float:
    """What ``live_indices`` costs to find ``slots`` entries in a mask of ``rows``."""
    if slots * LIVE_INDEX_SHARE > rows:
        return rows * _POSITIONS_ROW_NS
    return rows * _MASK_ROW_NS + slots * _WALK_SLOT_NS


def ranks_form(merged_rows: int, probe_rows: int, slots: int, words: int, unique: bool) -> str:
    """How a join's ranks reach its expansion from the merged order:
    ``emitting`` (``emitting_ranks``: the probe rows that emit, found in the
    merged order and sorted alone, ``slots`` of them) or ``merged``
    (``merged_ranks``: a sort of every merged row by the probe's row number),
    whichever costs less on a v5e by the constants above. ``words`` is
    ``rank_words``. After the merged way the unique expansion finds its
    slots among the probe's rows (``unique_slots``) and the general one
    scatters one update a probe row; after the emitting way the slots are the
    list, and the general expansion scatters one update a listed row. Static
    shapes only, as ``gather_form``."""
    merged = merged_rows * _MERGED_ROW_NS[words]
    emitting = _finding_ns(merged_rows, slots) + slots * _LISTED_SLOT_NS
    if unique:
        merged += _finding_ns(probe_rows, slots)
    else:
        merged += probe_rows * _SCATTER_UPDATE_NS
        emitting += slots * _SCATTER_UPDATE_NS
    return "emitting" if emitting < merged else "merged"


def emitting_ranks(qid, lo, count, emit, m: int, slots: int):
    """The probe rows that emit, in the probe's order, from the merged order
    (``join_merge``): their merged positions by ``live_indices`` over
    ``emit > 0`` (a walk where they are few), (qid, lo, count) gathered there
    in one ``gather_rows``, and a sort of those ``slots`` entries alone by
    the probe row number, ``lo`` and ``count`` in one word where
    ``rank_words`` allows. The probe's last row is listed whether it emits or
    not: a slot past the rows emitted takes it, as ``expand_probe_slots``'
    do. Returns (qid, lo, count) of the ``slots`` entries; past the listed
    rows ``qid`` is n."""
    total = qid.shape[0]
    n = total - m
    at = live_indices((emit > 0) | (qid == n - 1), slots)
    listed = at < total
    e_qid, e_lo, e_count = gather_rows([qid, lo, count], jnp.minimum(at, total - 1))
    e_qid = jnp.where(listed, e_qid, n)
    e_qid, *words = jax.lax.sort((e_qid, *_pack_ranks(e_lo, e_count, m)), num_keys=1, is_stable=False)
    return (e_qid, *_unpack_ranks(words, m))


def merged_ranks(qid, lo, count, m: int):
    """(lo, count) of every probe row, in the probe's order, from the merged
    order (``join_merge``): one sort of the n + m rows by the probe row
    number that carries them (``join_match``'s way back)."""
    n = qid.shape[0] - m
    return _unpack_ranks(_by_probe_row(qid, n, _pack_ranks(lo, count, m)), m)


def expand_listed(e_qid, e_lo, e_count, n: int, last_live, perm_b, out_capacity: int, *,
                  unique: bool, left_outer: bool):
    """``expand_matches`` over the list ``emitting_ranks`` gives, for a probe
    of ``n`` rows: the same (probe_idx, build_pos, matched, out_active,
    total), slot for slot, with no work over the probe's rows. ``last_live``:
    whether the probe's last row is live (a LEFT join emits for it). A slot
    past the rows emitted is the general form's: the probe's last row holds
    it, with that row's ``lo`` and ``count`` and ``d`` running on from that
    row's start."""
    is_last = e_qid == n - 1
    if left_outer:  # every listed row is live, but for the last row perhaps
        e_emit = jnp.where((e_qid < n) & (last_live | ~is_last), jnp.maximum(e_count, 1), 0)
    else:
        e_emit = jnp.where(e_qid < n, e_count, 0)
    last_lo, last_count, last_emit = (jnp.sum(jnp.where(is_last, a, 0)) for a in (e_lo, e_count, e_emit))
    if unique:
        if e_qid.shape[0] < out_capacity:
            raise ValueError(f"{e_qid.shape[0]} listed rows for {out_capacity} slots")
        total = jnp.sum(e_emit)
        out_active = jnp.arange(out_capacity) < total
        probe_idx = jnp.where(out_active, e_qid[:out_capacity], n - 1)
        lo_at = jnp.where(out_active, e_lo[:out_capacity], last_lo)
        count_at = jnp.where(out_active, e_count[:out_capacity], last_count)
        build_pos, matched = unique_build_rows(lo_at, count_at, perm_b, out_active)
        return probe_idx, build_pos, matched, out_active, total
    entry, d, out_active, total = expand_probe_slots(e_emit, out_capacity)
    q_at, lo_at, count_at = gather_rows([e_qid, e_lo, e_count], entry)
    probe_idx = jnp.where(out_active, q_at, n - 1)
    lo_at = jnp.where(out_active, lo_at, last_lo)
    count_at = jnp.where(out_active, count_at, last_count)
    d = jnp.where(out_active, d, jnp.arange(out_capacity) - total + last_emit)
    matched = d < count_at
    build_pos = perm_b[jnp.clip(lo_at + d, 0, perm_b.shape[0] - 1)]
    return probe_idx, build_pos, matched, out_active, total


def expand_probe_slots(emit: jnp.ndarray, out_capacity: int):
    """Slot-assignment half of rank-space match expansion, shared between the
    sort-based join (expand_matches) and the hash-probe megakernel
    (ops/megakernels.py) — both paths MUST place probe row i's output rows at
    the same slots for the fused/serial bit-identity contract to hold.

    Returns (probe_idx, d, out_active, total):
    - probe_idx[p]: probe row for output slot p (last i with start[i] <= p)
    - d[p]: ordinal of slot p within its probe row's emission
    - out_active[p]: slot p holds a real output row (p < total)
    - total: number of output rows (traced scalar)
    """
    start = cumsum(emit) - emit  # exclusive prefix sum
    total = jnp.sum(emit)
    p = jnp.arange(out_capacity)
    # probe_idx[p] = last i with start[i] <= p, via scatter-max + cummax
    # (searchsorted is ~20 dependent gather rounds; this is one scatter at
    # probe size + one scan at output size). Ties on start (zero-emit rows)
    # resolve to the max i — the searchsorted('right')-1 behavior.
    # start never decreases, and the scatter is told so: left to find that
    # out, the TPU's scatter sorts its indices first (13 s of compiling and a
    # sort's time at 18.9M rows, PR 34)
    marks = (
        jnp.zeros(out_capacity, dtype=jnp.int32)
        .at[start]
        .max(jnp.arange(start.shape[0], dtype=jnp.int32), mode="drop", indices_are_sorted=True)
    )
    probe_idx = cummax(marks)
    probe_idx = jnp.clip(probe_idx, 0, start.shape[0] - 1)
    d = p - start[probe_idx]
    out_active = p < total
    return probe_idx, d, out_active, total


def unique_slots(emit: jnp.ndarray, out_capacity: int):
    """``expand_probe_slots`` where no probe row emits more than one row: the
    output is the emitting rows in row order, so slot p holds the p-th of
    them (``live_indices``: a walk over the mask where the slots are few, one
    sort of the positions where they are not). No prefix sum of ``emit`` and
    no scatter over the probe rows, whose cost grows with the probe's
    capacity and not with the rows emitted. Returns (probe_idx, out_active);
    a slot past the rows emitted holds the last probe row."""
    n = emit.shape[0]
    idx = live_indices(emit > 0, out_capacity)
    return jnp.minimum(idx, n - 1), idx < n


def unique_build_rows(lo_at, count_at, perm_b, out_active):
    """(build_pos, matched) of a unique expansion's slots from ``lo`` and
    ``match_count`` read at each slot's probe row: the slot is that row's one
    emission, its first match (d = 0) or, where the row matched nothing (a
    LEFT join's null-padded row), no match."""
    build_pos = perm_b[jnp.clip(lo_at, 0, perm_b.shape[0] - 1)]
    return build_pos, out_active & (count_at > 0)


def expand_matches(
    emit: jnp.ndarray,
    match_count: jnp.ndarray,
    lo: jnp.ndarray,
    perm_b: jnp.ndarray,
    out_capacity: int,
    *,
    unique: bool = False,
):
    """Rank-space expansion of 1:N matches into a static output.

    ``emit[i]``: output slots probe row i produces (0 for inactive rows; for a
    left outer join, 1 for active-but-unmatched rows). ``match_count[i]``: how
    many of those slots are real matches (the rest are null-padded).
    ``unique`` (static): the caller knows that no ``emit[i]`` exceeds 1, and
    the slots are found by ``unique_slots``; every active slot gets what the
    general form gives it.

    Returns (probe_idx, build_pos, matched, out_active, total):
    - probe_idx[p]: probe row for output slot p
    - build_pos[p]: build row (original index) for output slot p
    - matched[p]: False for null-padded (outer) slots
    - out_active[p]: slot p holds a real output row
    - total: number of output rows (traced scalar)

    Selection invariant: slot p maps to the last probe row i with start[i] <= p;
    zero-emit rows share their successor's start and are never selected within
    [0, total).
    """
    if unique:
        probe_idx, out_active = unique_slots(emit, out_capacity)
        lo_at, count_at = gather_rows([lo, match_count], probe_idx)
        build_pos, matched = unique_build_rows(lo_at, count_at, perm_b, out_active)
        return probe_idx, build_pos, matched, out_active, jnp.sum(emit)
    probe_idx, d, out_active, total = expand_probe_slots(emit, out_capacity)
    matched = d < match_count[probe_idx]
    build_sorted_pos = jnp.clip(lo[probe_idx] + d, 0, perm_b.shape[0] - 1)
    build_pos = perm_b[build_sorted_pos]
    return probe_idx, build_pos, matched, out_active, total


def semijoin_mask(
    build_key: jnp.ndarray,
    build_active: jnp.ndarray,
    probe_key: jnp.ndarray,
    probe_active: jnp.ndarray,
) -> jnp.ndarray:
    """matched[i] for each probe row (HashSemiJoinOperator/SetBuilderOperator):
    ``join_match``'s merge, one word back (the count), and no ``perm_b``."""
    s_pos, _, _, s_count = _merge_match(build_key, build_active, probe_key, probe_active, None)
    (count,) = _ranks_back(s_pos, build_active.shape[0], probe_active.shape[0], [s_count])
    return probe_active & (count > 0)


# --------------------------------------------------------------------------- #
# sort / topn / limit
# --------------------------------------------------------------------------- #


def topn_perm(
    sort_keys: Sequence[jnp.ndarray],  # already encoded (encode_sort_column)
    active: jnp.ndarray,
    count: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sort permutation + output active mask (first min(count, n) rows)."""
    perm = lexsort_perm(list(sort_keys), active)
    n_active = jnp.sum(active.astype(jnp.int32))
    cap = active.shape[0]
    idx = jnp.arange(cap)
    limit = n_active if count is None else jnp.minimum(n_active, count)
    out_active = idx < limit
    return perm, out_active


def limit_mask(active: jnp.ndarray, count: int, offset: int = 0) -> jnp.ndarray:
    """Keep active rows with ordinal in [offset, offset+count) (LimitOperator)."""
    ordinal = cumsum(active.astype(jnp.int64)) - 1
    keep = active & (ordinal >= offset)
    if count >= 0:
        keep = keep & (ordinal < offset + count)
    return keep
