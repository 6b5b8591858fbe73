"""Device BSI (bit-sliced index) arithmetic.

TPU-native port of the reference's per-fragment BSI loops
(/root/reference/fragment.go:1111-1538: sum, minUnsigned/maxUnsigned,
rangeEQ/NEQ/LT/GT/Between ladders). Values are stored sign+magnitude
(fragment.go:936-1041 positionsForValue): plane layout follows
fragment.go:88-96 — row 0 = exists (not-null), row 1 = sign, rows 2.. =
magnitude bit planes (handled by the fragment layer; functions here receive
the plane stack directly).

Layout here: `planes: uint32[bit_depth, W]` (plane i = bit i of magnitude),
`exists/sign/filter: uint32[W]` dense word rows. The sequential Go ladders
become unrolled elementwise XLA programs: `bit_depth` is static (compile-time
unrolled, one fused kernel), the *predicate* is traced, so one compiled
program serves every query at a given depth. Branches on predicate bits
become `jnp.where` selects — both sides are cheap elementwise ops, and XLA
fuses the whole ladder into a single pass over HBM.

Counts return as per-plane uint32 partials; hosts combine with exact Python
ints (see the count convention in ops/bitmap.py).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

_pc = jax.lax.population_count


def _count(words):
    """uint32 popcount over the trailing axis (a single row's words)."""
    return jnp.sum(_pc(words), dtype=jnp.uint32)


@partial(jax.jit, static_argnames=("bit_depth",))
def sum_counts(planes, exists, sign, filter_words, bit_depth: int):
    """Per-plane intersection counts for BSI sum (fragment.go:1111).

    Returns (count, pos_counts[bit_depth], neg_counts[bit_depth]); the host
    computes sum = Σ 2^i * (pos[i] - neg[i]) in exact Python ints.
    filter_words of all-ones means "no filter".
    """
    consider = jnp.bitwise_and(exists, filter_words)
    nrow = jnp.bitwise_and(sign, consider)
    prow = jnp.bitwise_and(consider, jnp.bitwise_not(sign))
    count = _count(consider)
    pos_counts = jnp.stack([_count(jnp.bitwise_and(planes[i], prow)) for i in range(bit_depth)])
    neg_counts = jnp.stack([_count(jnp.bitwise_and(planes[i], nrow)) for i in range(bit_depth)])
    return count, pos_counts, neg_counts


@partial(jax.jit, static_argnames=("bit_depth",))
def sum_counts_stacked(planes, exists, sign, filter_words, bit_depth: int):
    """sum_counts over stacked operands: planes uint32[D, S, W], the rest
    uint32[S, W]. Counts reduce over the word axis only, returning per-shard
    partials the host sums in exact Python ints — per-shard partials can
    never overflow uint32 (a shard holds at most 2^20 bits), while a
    whole-stack uint32 sum could at >4B columns.

    Returns ONE fused uint32[1 + 2*D, S] array — row 0 the considered
    count, rows 1..D the positive-branch plane counts, rows D+1..2D the
    negative branch — so the host pays a single device read (three
    separate outputs would be three blocking reads)."""
    consider = jnp.bitwise_and(exists, filter_words)
    nrow = jnp.bitwise_and(sign, consider)
    prow = jnp.bitwise_and(consider, jnp.bitwise_not(sign))
    count = jnp.sum(_pc(consider), axis=-1, dtype=jnp.uint32)
    rows = [count[None]]
    for branch in (prow, nrow):
        for i in range(bit_depth):
            rows.append(
                jnp.sum(
                    _pc(jnp.bitwise_and(planes[i], branch)),
                    axis=-1,
                    dtype=jnp.uint32,
                )[None]
            )
    return jnp.concatenate(rows, axis=0)


@partial(jax.jit, static_argnames=("bit_depth",))
def min_unsigned(planes, filter_words, bit_depth: int):
    """Lowest magnitude among filter columns (fragment.go:1173 minUnsigned).

    Returns (min_value uint32, final_filter_words). The count of columns
    attaining the min is popcount(final_filter) — computed by the caller.
    Shape-generic: works on single rows [W] or stacked rows [S, W] (the
    narrowing test is a global any, not a count, so it cannot overflow).
    """
    filt = filter_words
    mval = jnp.uint32(0)
    for i in reversed(range(bit_depth)):
        row = jnp.bitwise_and(filt, jnp.bitwise_not(planes[i]))
        nonzero = jnp.any(row != 0)
        filt = jnp.where(nonzero, row, filt)
        mval = mval + jnp.where(nonzero, jnp.uint32(0), jnp.uint32(1) << i)
    return mval, filt


@partial(jax.jit, static_argnames=("bit_depth",))
def max_unsigned(planes, filter_words, bit_depth: int):
    """Highest magnitude among filter columns (fragment.go:1215 maxUnsigned)."""
    filt = filter_words
    mval = jnp.uint32(0)
    for i in reversed(range(bit_depth)):
        row = jnp.bitwise_and(planes[i], filt)
        nonzero = jnp.any(row != 0)
        filt = jnp.where(nonzero, row, filt)
        mval = mval + jnp.where(nonzero, jnp.uint32(1) << i, jnp.uint32(0))
    return mval, filt


@partial(jax.jit, static_argnames=("bit_depth", "is_min"))
def min_max_signed(planes, exists, sign, filter_words, bit_depth: int, is_min: bool):
    """Global signed min/max in ONE dispatch (the fused form of
    Fragment.min/max's sign decomposition, fragment.go:1146/1191), shape-
    generic over [W] or stacked [S, W] operands.

    Returns ONE fused uint32 1-D array [magnitude, negative, any,
    counts...] — the unsigned min/max magnitude (exact for any bit_depth
    <= 32; the sign is the separate `negative` 0/1 flag so no signed cast
    can truncate), `any` 0/1 for whether any column is considered, then
    the per-shard attain-counts flattened — a single device read instead
    of three round trips. Both sign-branch ladders are evaluated and
    selected with `where` — cheap elementwise passes XLA fuses into one
    HBM sweep."""
    consider = jnp.bitwise_and(exists, filter_words)
    negatives = jnp.bitwise_and(consider, sign)
    positives = jnp.bitwise_and(consider, jnp.bitwise_not(sign))
    any_ = jnp.any(consider != 0)
    if is_min:
        # negatives present -> most-negative = -max magnitude among negatives
        branch = jnp.any(negatives != 0)
        bval, bfilt = max_unsigned(planes, negatives, bit_depth)
        oval, ofilt = min_unsigned(planes, consider, bit_depth)
        negative = branch
    else:
        # positives present -> max among positives; else -min magnitude
        branch = jnp.any(positives != 0)
        bval, bfilt = max_unsigned(planes, positives, bit_depth)
        oval, ofilt = min_unsigned(planes, consider, bit_depth)
        negative = jnp.logical_not(branch)
    mag = jnp.where(branch, bval, oval)
    final = jnp.where(branch, bfilt, ofilt)
    counts = jnp.sum(_pc(final), axis=-1, dtype=jnp.uint32)
    return jnp.concatenate(
        [
            mag.astype(jnp.uint32)[None],
            negative.astype(jnp.uint32)[None],
            any_.astype(jnp.uint32)[None],
            counts.ravel(),
        ]
    )


# ---------------------------------------------------------------------------
# Range ladders. All predicates are traced uint32 magnitudes; sign split is
# done by the caller (fragment layer) exactly as in rangeLT/rangeGT/rangeEQ.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("bit_depth",))
def range_eq_unsigned(base, planes, upredicate, bit_depth: int):
    """Columns whose magnitude == upredicate, within base (fragment.go:1288)."""
    b = base
    for i in reversed(range(bit_depth)):
        bit = (upredicate >> jnp.uint32(i)) & jnp.uint32(1)
        row = planes[i]
        b = jnp.where(bit == 1, jnp.bitwise_and(b, row), jnp.bitwise_and(b, jnp.bitwise_not(row)))
    return b


@partial(jax.jit, static_argnames=("bit_depth", "allow_equality"))
def range_lt_unsigned(filter_words, planes, upredicate, bit_depth: int, allow_equality: bool):
    """Columns with magnitude < (or <=) upredicate (fragment.go:1358
    rangeLTUnsigned). Fully traced port of the keep/leading-zeros ladder."""
    filt = filter_words
    keep = jnp.zeros_like(filter_words)
    leading_zeros = jnp.bool_(True)
    for i in reversed(range(bit_depth)):
        row = planes[i]
        bit = (upredicate >> jnp.uint32(i)) & jnp.uint32(1)
        bit_is_zero = bit == 0

        # leading-zeros phase: predicate bit 0 -> drop columns with this bit set.
        in_lz_skip = jnp.logical_and(leading_zeros, bit_is_zero)
        filt_lz = jnp.bitwise_and(filt, jnp.bitwise_not(row))
        leading_zeros = jnp.logical_and(leading_zeros, bit_is_zero)

        if i == 0 and not allow_equality:
            # If bit is zero: only already-kept columns. If one: remove
            # exact-match columns (row minus keep). Note: when the predicate is
            # 0 this returns empty (strict `< 0` has no unsigned solutions);
            # the reference's ladder would return the 0-valued columns here
            # (fragment.go:1358 leading-zeros `continue` at i==0) — an edge
            # quirk we deliberately correct.
            return jnp.where(
                bit_is_zero,
                keep,
                jnp.bitwise_and(
                    filt, jnp.bitwise_not(jnp.bitwise_and(row, jnp.bitwise_not(keep)))
                ),
            )

        # bit == 0: filter = filter - (row - keep)
        drop = jnp.bitwise_and(
            filt, jnp.bitwise_not(jnp.bitwise_and(row, jnp.bitwise_not(keep)))
        )
        # bit == 1: keep |= filter - row (not on final iteration)
        keep_next = (
            jnp.bitwise_or(keep, jnp.bitwise_and(filt, jnp.bitwise_not(row))) if i > 0 else keep
        )

        filt = jnp.where(in_lz_skip, filt_lz, jnp.where(bit_is_zero, drop, filt))
        keep = jnp.where(jnp.logical_or(in_lz_skip, bit_is_zero), keep, keep_next)
    return filt


@partial(jax.jit, static_argnames=("bit_depth", "allow_equality"))
def range_gt_unsigned(filter_words, planes, upredicate, bit_depth: int, allow_equality: bool):
    """Columns with magnitude > (or >=) upredicate (fragment.go:1425
    rangeGTUnsigned)."""
    filt = filter_words
    keep = jnp.zeros_like(filter_words)
    for i in reversed(range(bit_depth)):
        row = planes[i]
        bit = (upredicate >> jnp.uint32(i)) & jnp.uint32(1)
        bit_is_one = bit == 1

        if i == 0 and not allow_equality:
            # bit one -> only kept columns; bit zero -> remove columns that are
            # exactly equal: filter - ((filter - row) - keep)
            eq_removed = jnp.bitwise_and(
                filt,
                jnp.bitwise_not(
                    jnp.bitwise_and(
                        jnp.bitwise_and(filt, jnp.bitwise_not(row)), jnp.bitwise_not(keep)
                    )
                ),
            )
            return jnp.where(bit_is_one, keep, eq_removed)

        # bit == 1: filter = filter - ((filter - row) - keep)
        narrowed = jnp.bitwise_and(
            filt,
            jnp.bitwise_not(
                jnp.bitwise_and(
                    jnp.bitwise_and(filt, jnp.bitwise_not(row)), jnp.bitwise_not(keep)
                )
            ),
        )
        # bit == 0: keep |= filter & row (not on final iteration)
        keep_next = jnp.bitwise_or(keep, jnp.bitwise_and(filt, row)) if i > 0 else keep

        filt = jnp.where(bit_is_one, narrowed, filt)
        keep = jnp.where(bit_is_one, keep, keep_next)
    return filt


@partial(jax.jit, static_argnames=("bit_depth",))
def range_between_unsigned(filter_words, planes, umin, umax, bit_depth: int):
    """Columns with umin <= magnitude <= umax (fragment.go:1506
    rangeBetweenUnsigned): the GTE and LTE ladders run in one pass."""
    filt = filter_words
    keep1 = jnp.zeros_like(filter_words)  # GTE side
    keep2 = jnp.zeros_like(filter_words)  # LTE side
    for i in reversed(range(bit_depth)):
        row = planes[i]
        bit1 = (umin >> jnp.uint32(i)) & jnp.uint32(1)
        bit2 = (umax >> jnp.uint32(i)) & jnp.uint32(1)

        # GTE umin
        narrowed = jnp.bitwise_and(
            filt,
            jnp.bitwise_not(
                jnp.bitwise_and(
                    jnp.bitwise_and(filt, jnp.bitwise_not(row)), jnp.bitwise_not(keep1)
                )
            ),
        )
        keep1_next = jnp.bitwise_or(keep1, jnp.bitwise_and(filt, row)) if i > 0 else keep1
        filt = jnp.where(bit1 == 1, narrowed, filt)
        keep1 = jnp.where(bit1 == 1, keep1, keep1_next)

        # LTE umax
        dropped = jnp.bitwise_and(
            filt, jnp.bitwise_not(jnp.bitwise_and(row, jnp.bitwise_not(keep2)))
        )
        keep2_next = (
            jnp.bitwise_or(keep2, jnp.bitwise_and(filt, jnp.bitwise_not(row)))
            if i > 0
            else keep2
        )
        filt = jnp.where(bit2 == 0, dropped, filt)
        keep2 = jnp.where(bit2 == 0, keep2, keep2_next)
    return filt


# ---------------------------------------------------------------------------
# Plane-streamed fused aggregate kernels (the BSI roofline rework).
#
# The kernels above answer a whole-field aggregate by reading the plane
# stack several times: `sum_counts_stacked` walks planes once per sign
# branch, `min_max_signed` evaluates BOTH sign-branch ladders with a
# global `any` reduction per plane (which breaks elementwise fusion into
# one full [S, W] sweep per plane per ladder), and both read [1 + 2D, S]
# per-shard partials back to the host: several times the bytes a Count
# over the same stack reads.
#
# The streamed kernels are WORD-LOCAL: every decision that the global
# ladders made with a cross-word `any` is made per 32-column word in
# registers, so the whole aggregate fuses into ONE streaming pass that
# reads each plane word exactly once, and the cross-word combine is a
# plain reduction that finishes IN PROGRAM to a scalar-sized result —
# under a mesh NamedSharding the SPMD partitioner emits that reduction
# as the cross-device collective (psum), so a mesh-group BSI aggregate
# is one dispatch + one scalar host read regardless of group size
# (exactly the plan.py "total" contract for Count).
#
# PARTS, not concatenation: operands arrive as TUPLES of shard-axis
# slices — exactly the extents hbm/residency keeps resident — and every
# kernel reduces across the parts inside the one compiled program. At
# 954 shards the old path's device-side concat of 4 extents into one
# [D, S, W] operand re-copied ~2 GB per query before the kernel even
# ran; parts reach the same single dispatch with zero assembly traffic.
# A monolithic operand (mesh placement, small stacks) is simply the
# 1-tuple.
#
# Exactness bounds (everything stays uint32; no x64 dependency):
# - per-word packed sums: <= 8 planes per pack group, so a group partial
#   is < 2^13 per 16-bit half (32 bits/word x sum(2^i, i<8));
# - per-shard halves are < 2^28 (2^13 x 2^15 words/shard at the default
#   shard width), reduced exactly;
# - shard-axis totals concatenate the tiny per-shard vectors across
#   parts and use the (lo, hi) halfword-pair split of plan._root_out,
#   exact while the total shard axis is <= 65536.
#
# Min/Max: signed min/max collapses to a SINGLE branch-free max-ladder
# over D+1 virtual planes via a sign-transformed key space — for Min the
# key is [sign, p_i ^ ~sign]: any negative key (2^D + mag) outranks any
# positive key (2^D - 1 - mag), larger negative magnitudes rank higher,
# smaller positive magnitudes rank higher, so max(key) IS the signed
# minimum. Both the reference's sign branches (fragment.go:1146/1191)
# fall out of one ladder with no lax.cond and no wasted second ladder.
# ---------------------------------------------------------------------------

# planes per packed accumulator group: sum partials stay under 2^13 per
# 16-bit half (see exactness bounds above)
_SUM_PACK = 8


def _total_pair(per_shard: jax.Array) -> jax.Array:
    """Exact shard-axis total of a uint32[S] vector as a (lo, hi)
    halfword pair (the plan._root_out split): per-shard values must be
    < 2^28 and the shard axis <= 65536."""
    lo = jnp.sum(jnp.bitwise_and(per_shard, jnp.uint32(0xFFFF)), dtype=jnp.uint32)
    hi = jnp.sum(jnp.right_shift(per_shard, jnp.uint32(16)), dtype=jnp.uint32)
    return jnp.stack([lo, hi])


def _cat_total_pair(per_shard_parts) -> jax.Array:
    """_total_pair over per-part per-shard vectors (concatenating the
    TINY [s_i] vectors, never the word data)."""
    v = (
        per_shard_parts[0]
        if len(per_shard_parts) == 1
        else jnp.concatenate(list(per_shard_parts))
    )
    return _total_pair(v)


def pair_value(arr, off: int = 0) -> int:
    """Host decode of one (lo, hi) halfword pair at `arr[off:off+2]`."""
    return int(arr[off]) + (int(arr[off + 1]) << 16)


def _count_pair_parts(parts) -> jax.Array:
    """Exact total popcount of a row given as [s_i, W] parts, as a
    halfword pair: per-shard counts are < 2^20 (one row within a
    shard), so the split is exact for total shard axes up to 65536."""
    return _cat_total_pair(
        [jnp.sum(_pc(p), axis=-1, dtype=jnp.uint32) for p in parts]
    )


def _part(x, i: int):
    """Part i of an optional parts tuple (None stays None)."""
    return None if x is None else x[i]


@partial(jax.jit, static_argnames=("signed_", "with_count"))
def sum_stream_slab(planes, consider, sign, signed_: bool, with_count: bool):
    """One plane SLAB's contribution to a BSI Sum, reduced in program.

    planes is a tuple of uint32[d, s_i, W] shard-axis parts of one slab
    of consecutive magnitude planes; `consider` (exists & filter) and
    `sign` are matching [s_i, W] part tuples. Per word, per pack group
    of <= 8 planes, the 2^i-weighted popcounts accumulate into one
    uint32 per branch — a word's group partial is at most 32 x 255 =
    8160, under 2^13, so the accumulator never nears overflow and one
    halfword-pair reduction per group (inside _cat_total_pair) keeps
    the shard totals exact. Output layout: [cnt_lo, cnt_hi]? + per
    group ([pos pair] + [neg pair]?) — scalar-sized however many shards
    the parts span. The host weights group totals by
    2^(slab_base + 8*g) in exact Python ints (decode_sum_slab), so the
    compiled program is slab-offset-blind and one executable serves
    every slab of a deep field."""
    d = planes[0].shape[0]
    out = []
    if with_count:
        out.append(_count_pair_parts(consider))
    for g0 in range(0, d, _SUM_PACK):
        gplanes = range(g0, min(g0 + _SUM_PACK, d))
        per_shard_p, per_shard_n = [], []
        for i, cons in enumerate(consider):
            p_i = planes[i]
            if signed_:
                sg = sign[i]
                prow = jnp.bitwise_and(cons, jnp.bitwise_not(sg))
                nrow = jnp.bitwise_and(cons, sg)
            else:
                prow, nrow = cons, None
            acc_p = jnp.zeros_like(cons)
            acc_n = jnp.zeros_like(cons) if signed_ else None
            for k in gplanes:
                w = jnp.uint32(k - g0)
                acc_p = acc_p + (_pc(jnp.bitwise_and(p_i[k], prow)) << w)
                if signed_:
                    acc_n = acc_n + (_pc(jnp.bitwise_and(p_i[k], nrow)) << w)
            # per-shard group partials: <= 8160 x words/shard < 2^30,
            # within _cat_total_pair's exactness bound
            per_shard_p.append(jnp.sum(acc_p, axis=-1, dtype=jnp.uint32))
            if signed_:
                per_shard_n.append(
                    jnp.sum(acc_n, axis=-1, dtype=jnp.uint32)
                )
        out.append(_cat_total_pair(per_shard_p))
        if signed_:
            out.append(_cat_total_pair(per_shard_n))
    return jnp.concatenate(out)


def decode_sum_slab(host, signed_: bool, with_count: bool, base: int,
                    d: int) -> Tuple[int, int]:
    """Host combine of one sum_stream_slab read: (count, signed partial
    sum weighted by 2^base). `count` is 0 unless with_count."""
    off = 0
    count = 0
    if with_count:
        count = pair_value(host, 0)
        off = 2
    total = 0
    weight = 1 << base
    for g0 in range(0, d, _SUM_PACK):
        pos = pair_value(host, off)
        off += 2
        neg = 0
        if signed_:
            neg = pair_value(host, off)
            off += 2
        total += weight * (pos - neg)
        weight <<= _SUM_PACK
    return count, total


# -- min/max: the word-local virtual-key ladder -----------------------------


def _vkey_ladder(planes, sign, fa, va, is_min: bool, signed_: bool):
    """Advance the word-local max-ladder over one plane slab PART
    (MSB-first within the slab). fa narrows to each word's best-key
    survivors; va accumulates the key bits. Pure elementwise — fuses
    into one pass."""
    d = planes.shape[0]
    if signed_:
        # per-column transform into the virtual key space: for Min,
        # negative columns keep p_i (bigger magnitude ranks higher) and
        # positive columns flip (smaller magnitude ranks higher); Max is
        # the mirror image
        tx = jnp.bitwise_not(sign) if is_min else sign
    for k in reversed(range(d)):
        p = planes[k]
        if signed_:
            t = jnp.bitwise_xor(p, tx)
        else:
            t = jnp.bitwise_not(p) if is_min else p
        ra = jnp.bitwise_and(fa, t)
        nz = ra != 0
        fa = jnp.where(nz, ra, fa)
        va = jnp.bitwise_or(va << jnp.uint32(1), nz.astype(jnp.uint32))
    return fa, va


def _vkey_init(exists, sign, filt, is_min: bool, signed_: bool):
    """Mask + ladder state after the virtual sign plane (the key MSB),
    for one part."""
    mask = exists if filt is None else jnp.bitwise_and(exists, filt)
    fa = mask
    va = jnp.zeros_like(mask)
    if signed_:
        top = jnp.bitwise_and(mask, sign if is_min else jnp.bitwise_not(sign))
        nz = top != 0
        fa = jnp.where(nz, top, fa)
        va = nz.astype(jnp.uint32)
    return mask, fa, va


def _vkey_reduce(masks, fas, vas, key_bits: int):
    """Finish the ladder across all parts: global best key + exact
    attain count, in program. When the key leaves >= 6 spare bits the
    per-word count packs into the key word so the value and count
    phases share one materialized array per part; deeper keys pay a
    two-phase where() scan."""
    packed = key_bits + 6 <= 32
    if packed:
        kws = [
            jnp.where(
                mask != 0,
                jnp.bitwise_or(va << jnp.uint32(6), _pc(fa)),
                jnp.uint32(0),
            )
            for mask, fa, va in zip(masks, fas, vas)
        ]
        best = kws[0].max() if len(kws) == 1 else jnp.max(
            jnp.stack([kw.max() for kw in kws])
        )
        vbest = best >> jnp.uint32(6)
        cnt = jnp.uint32(0)
        for kw in kws:
            cnt = cnt + jnp.sum(
                jnp.where(
                    (kw >> jnp.uint32(6)) == vbest,
                    jnp.bitwise_and(kw, jnp.uint32(63)), 0,
                ).astype(jnp.uint32),
                dtype=jnp.uint32,
            )
    else:
        vms = [
            jnp.where(mask != 0, va, jnp.uint32(0))
            for mask, va in zip(masks, vas)
        ]
        vbest = vms[0].max() if len(vms) == 1 else jnp.max(
            jnp.stack([vm.max() for vm in vms])
        )
        cnt = jnp.uint32(0)
        for mask, fa, va in zip(masks, fas, vas):
            cnt = cnt + jnp.sum(
                jnp.where(
                    jnp.logical_and(mask != 0, va == vbest), _pc(fa), 0
                ).astype(jnp.uint32),
                dtype=jnp.uint32,
            )
    any_ = jnp.any(
        jnp.stack([jnp.any(mask != 0) for mask in masks])
    )
    return jnp.stack([
        vbest,
        any_.astype(jnp.uint32),
        jnp.bitwise_and(cnt, jnp.uint32(0xFFFF)),
        cnt >> jnp.uint32(16),
    ])


@partial(jax.jit, static_argnames=("is_min", "signed_"))
def min_max_stream(planes, exists, sign, filt, is_min: bool, signed_: bool):
    """Whole signed Min/Max as ONE fused streaming dispatch (bit_depth
    <= slab) over part tuples: init + virtual-key ladder + in-program
    reduce. Returns uint32[4] = [best_key, any, cnt_lo, cnt_hi];
    decode_min_max turns the key back into (value, negative)."""
    d = planes[0].shape[0]
    masks, fas, vas = [], [], []
    for i, p in enumerate(planes):
        sg = _part(sign, i)
        mask, fa, va = _vkey_init(
            exists[i], sg, _part(filt, i), is_min, signed_
        )
        fa, va = _vkey_ladder(p, sg, fa, va, is_min, signed_)
        masks.append(mask)
        fas.append(fa)
        vas.append(va)
    return _vkey_reduce(masks, fas, vas, d + (1 if signed_ else 0))


def _min_max_stream_step(planes, exists, sign, filt, fa, va,
                         is_min: bool, signed_: bool, first: bool):
    out_fa, out_va = [], []
    for i, p in enumerate(planes):
        sg = _part(sign, i)
        if first:
            _, fa_i, va_i = _vkey_init(
                exists[i], sg, _part(filt, i), is_min, signed_
            )
        else:
            fa_i, va_i = fa[i], va[i]
        fa_i, va_i = _vkey_ladder(p, sg, fa_i, va_i, is_min, signed_)
        out_fa.append(fa_i)
        out_va.append(va_i)
    return tuple(out_fa), tuple(out_va)


# Lazy jit cache for the carried-state step kernels: on accelerators the
# state buffers are DONATED (the whole point of slab streaming is that
# peak residency stays slab + state sized — without donation every step
# would hold both the old and new state generations); the CPU backend
# ignores donation with a warning, so it compiles a plain variant there.
_STEP_JIT: dict = {}


def _donate_steps() -> bool:
    return jax.default_backend() != "cpu"


def _step_jit(name, impl, static, donate_argnums):
    donate = _donate_steps()
    fn = _STEP_JIT.get((name, donate))
    if fn is None:
        kw = {"static_argnames": static}
        if donate:
            kw["donate_argnums"] = donate_argnums
        fn = _STEP_JIT[(name, donate)] = partial(jax.jit, **kw)(impl)
    return fn


def min_max_stream_step(planes, exists, sign, filt, fa, va,
                        is_min: bool, signed_: bool, first: bool):
    """One plane slab of a multi-slab Min/Max over part tuples: carries
    the word-local ladder state (fa, va part tuples) between dispatches
    so peak plane residency is slab-sized. Slabs arrive MSB-first;
    state buffers donate on accelerators."""
    fn = _step_jit(
        "mm_step", _min_max_stream_step,
        ("is_min", "signed_", "first"), (4, 5),
    )
    return fn(planes, exists, sign, filt, fa, va, is_min, signed_, first)


@partial(jax.jit, static_argnames=("key_bits",))
def min_max_stream_finish(exists, sign, filt, fa, va, key_bits: int):
    """Reduce a multi-slab ladder's final state to the scalar [4] out."""
    del sign
    masks = [
        e if filt is None else jnp.bitwise_and(e, filt[i])
        for i, e in enumerate(exists)
    ]
    return _vkey_reduce(masks, list(fa), list(va), key_bits)


def decode_min_max(host, bit_depth: int, is_min: bool,
                   signed_: bool) -> Tuple[int, int, bool]:
    """Host decode of a min/max stream read: (value, count, any)."""
    if not host[1]:
        return 0, 0, False
    key = int(host[0])
    cnt = int(host[2]) | (int(host[3]) << 16)
    low_mask = (1 << bit_depth) - 1
    if not signed_:
        mag = ((low_mask - key) & low_mask) if is_min else key
        return mag, cnt, True
    top = (key >> bit_depth) & 1
    low = key & low_mask
    if is_min:
        negative = bool(top)
        mag = low if negative else (low_mask - low)
    else:
        negative = not top
        mag = (low_mask - low) if negative else low
    return (-mag if negative else mag), cnt, True


# -- streamed Range/Between predicate ladders --------------------------------
#
# The same keep/leading-zeros ladders as range_lt/gt/between_unsigned
# above, restructured so each plane slab advances carried word state
# instead of requiring the whole [D, S, W] stack in one program. Job
# descriptors are static (kind, mask selector, allow_eq); predicates are
# traced uint32 scalars, so one compiled program serves every threshold
# at a given (slab shape, job set). States and operands are part tuples
# (ladders are shard-local, so parts advance independently).

# job kinds and their carried word-state widths (per part)
_JOB_STATE = {"lt": 3, "gt": 2, "between": 3, "eq": 1}


def _job_mask(sel: str, exists, sign, filt):
    consider = exists if filt is None else jnp.bitwise_and(exists, filt)
    if sel == "consider":
        return consider
    if sel == "pos":
        return jnp.bitwise_and(consider, jnp.bitwise_not(sign))
    if sel == "neg":
        return jnp.bitwise_and(consider, sign)
    raise AssertionError(sel)


def _job_init(job, exists, sign, filt):
    kind, sel, _ = job
    mask = _job_mask(sel, exists, sign, filt)
    zero = jnp.zeros_like(mask)
    if kind == "lt":
        # state: (filt, keep, leading_zeros flag as a scalar array)
        return (mask, zero, jnp.uint32(1))
    if kind == "gt":
        return (mask, zero)
    if kind == "between":
        return (mask, zero, zero)
    return (mask,)  # eq


def _job_step(job, state, planes, preds, lo: int):
    """Advance one job's ladder over one PART of a plane slab (absolute
    plane index of planes[k] is lo + k; slabs arrive MSB-first, planes
    walked high to low). Mirrors range_*_unsigned exactly, including the
    i == 0 strict-inequality finals."""
    kind, _, allow_eq = job
    d = planes.shape[0]
    if kind == "eq":
        (b,) = state
        upred = preds[0]
        for k in reversed(range(d)):
            i = lo + k
            row = planes[k]
            bit = (upred >> jnp.uint32(i)) & jnp.uint32(1)
            b = jnp.where(
                bit == 1, jnp.bitwise_and(b, row),
                jnp.bitwise_and(b, jnp.bitwise_not(row)),
            )
        return (b,)
    if kind == "lt":
        filt, keep, lz = state
        upred = preds[0]
        for k in reversed(range(d)):
            i = lo + k
            row = planes[k]
            bit = (upred >> jnp.uint32(i)) & jnp.uint32(1)
            bit_is_zero = bit == 0
            leading_zeros = lz != 0
            in_lz_skip = jnp.logical_and(leading_zeros, bit_is_zero)
            filt_lz = jnp.bitwise_and(filt, jnp.bitwise_not(row))
            lz = jnp.logical_and(leading_zeros, bit_is_zero).astype(jnp.uint32)
            if i == 0 and not allow_eq:
                res = jnp.where(
                    bit_is_zero,
                    keep,
                    jnp.bitwise_and(
                        filt,
                        jnp.bitwise_not(
                            jnp.bitwise_and(row, jnp.bitwise_not(keep))
                        ),
                    ),
                )
                return (res, keep, lz)
            drop = jnp.bitwise_and(
                filt, jnp.bitwise_not(jnp.bitwise_and(row, jnp.bitwise_not(keep)))
            )
            keep_next = (
                jnp.bitwise_or(keep, jnp.bitwise_and(filt, jnp.bitwise_not(row)))
                if i > 0
                else keep
            )
            filt = jnp.where(in_lz_skip, filt_lz, jnp.where(bit_is_zero, drop, filt))
            keep = jnp.where(jnp.logical_or(in_lz_skip, bit_is_zero), keep, keep_next)
        return (filt, keep, lz)
    if kind == "gt":
        filt, keep = state
        upred = preds[0]
        for k in reversed(range(d)):
            i = lo + k
            row = planes[k]
            bit = (upred >> jnp.uint32(i)) & jnp.uint32(1)
            bit_is_one = bit == 1
            if i == 0 and not allow_eq:
                eq_removed = jnp.bitwise_and(
                    filt,
                    jnp.bitwise_not(
                        jnp.bitwise_and(
                            jnp.bitwise_and(filt, jnp.bitwise_not(row)),
                            jnp.bitwise_not(keep),
                        )
                    ),
                )
                return (jnp.where(bit_is_one, keep, eq_removed), keep)
            narrowed = jnp.bitwise_and(
                filt,
                jnp.bitwise_not(
                    jnp.bitwise_and(
                        jnp.bitwise_and(filt, jnp.bitwise_not(row)),
                        jnp.bitwise_not(keep),
                    )
                ),
            )
            keep_next = jnp.bitwise_or(keep, jnp.bitwise_and(filt, row)) if i > 0 else keep
            filt = jnp.where(bit_is_one, narrowed, filt)
            keep = jnp.where(bit_is_one, keep, keep_next)
        return (filt, keep)
    if kind == "between":
        filt, keep1, keep2 = state
        umin, umax = preds[0], preds[1]
        for k in reversed(range(d)):
            i = lo + k
            row = planes[k]
            bit1 = (umin >> jnp.uint32(i)) & jnp.uint32(1)
            bit2 = (umax >> jnp.uint32(i)) & jnp.uint32(1)
            narrowed = jnp.bitwise_and(
                filt,
                jnp.bitwise_not(
                    jnp.bitwise_and(
                        jnp.bitwise_and(filt, jnp.bitwise_not(row)),
                        jnp.bitwise_not(keep1),
                    )
                ),
            )
            keep1_next = (
                jnp.bitwise_or(keep1, jnp.bitwise_and(filt, row)) if i > 0 else keep1
            )
            filt = jnp.where(bit1 == 1, narrowed, filt)
            keep1 = jnp.where(bit1 == 1, keep1, keep1_next)
            dropped = jnp.bitwise_and(
                filt, jnp.bitwise_not(jnp.bitwise_and(row, jnp.bitwise_not(keep2)))
            )
            keep2_next = (
                jnp.bitwise_or(keep2, jnp.bitwise_and(filt, jnp.bitwise_not(row)))
                if i > 0
                else keep2
            )
            filt = jnp.where(bit2 == 0, dropped, filt)
            keep2 = jnp.where(bit2 == 0, keep2, keep2_next)
        return (filt, keep1, keep2)
    raise AssertionError(kind)


def _range_terms(jobs, states, extras, exists, sign, filt):
    """Final count terms, one halfword pair each: every job's surviving
    words (summed across parts) plus every extra plain mask. The host
    combines the pairs with its own +/- weights in exact ints."""
    out = []
    for _job, part_states in zip(jobs, states):
        out.append(
            _count_pair_parts([st[0] for st in part_states])
        )
    for sel in extras:
        out.append(
            _count_pair_parts([
                _job_mask(sel, e, _part(sign, i), _part(filt, i))
                for i, e in enumerate(exists)
            ])
        )
    return jnp.concatenate(out) if out else jnp.zeros(0, jnp.uint32)


def _npred(job) -> int:
    return 2 if job[0] == "between" else 1


@partial(jax.jit, static_argnames=("jobs", "extras"))
def range_stream_single(planes, exists, sign, filt, preds,
                        jobs, extras):
    """A whole streamed Range/Between count as ONE fused dispatch (depth
    <= slab) over part tuples: init every job per part, run all ladders
    over the one slab (planes read once, shared by all jobs), and
    reduce each term to a halfword pair in program."""
    states = []
    for job in jobs:
        states.append([
            _job_init(job, e, _part(sign, i), _part(filt, i))
            for i, e in enumerate(exists)
        ])
    off = 0
    for n, job in enumerate(jobs):
        np_ = _npred(job)
        states[n] = [
            _job_step(job, st, planes[i], preds[off:off + np_], 0)
            for i, st in enumerate(states[n])
        ]
        off += np_
    return _range_terms(jobs, states, extras, exists, sign, filt)


def _range_stream_step(planes, exists, sign, filt, flat_state, preds,
                       jobs, lo: int, first: bool):
    n_parts = len(planes)
    states = []
    if first:
        for job in jobs:
            states.append([
                _job_init(job, e, _part(sign, i), _part(filt, i))
                for i, e in enumerate(exists)
            ])
    else:
        i = 0
        for job in jobs:
            n = _JOB_STATE[job[0]]
            part_states = []
            for _p in range(n_parts):
                part_states.append(tuple(flat_state[i:i + n]))
                i += n
            states.append(part_states)
    off = 0
    out = []
    for n, job in enumerate(jobs):
        np_ = _npred(job)
        for i in range(n_parts):
            st = _job_step(
                job, states[n][i], planes[i], preds[off:off + np_], lo
            )
            out.extend(st)
        off += np_
    return tuple(out)


def range_stream_step(planes, exists, sign, filt, flat_state, preds,
                      jobs, lo: int, first: bool):
    """One plane slab of a multi-slab streamed range over part tuples:
    advances every job's carried word state (donated on accelerators).
    `flat_state` is the tuple of state arrays for all (job, part)
    combinations in job-major order; pass () on the first slab — init
    builds the real states."""
    fn = _step_jit(
        "range_step", _range_stream_step, ("jobs", "lo", "first"), (4,),
    )
    return fn(planes, exists, sign, filt, flat_state, preds, jobs, lo, first)


@partial(jax.jit, static_argnames=("jobs", "extras"))
def range_stream_finish(exists, sign, filt, flat_state, jobs, extras):
    """Reduce a multi-slab streamed range's final state to its count
    term pairs."""
    n_parts = len(exists)
    states = []
    i = 0
    for job in jobs:
        n = _JOB_STATE[job[0]]
        part_states = []
        for _p in range(n_parts):
            part_states.append(tuple(flat_state[i:i + n]))
            i += n
        states.append(part_states)
    return _range_terms(jobs, states, extras, exists, sign, filt)


@partial(jax.jit, static_argnames=("sel",))
def mask_count_pair(exists, sign, filt, sel: str = "consider"):
    """Popcount of one plain mask (part tuples) as a halfword pair (the
    no-ladder degenerate range counts: != null, strict < 0, saturated
    predicates)."""
    return _count_pair_parts([
        _job_mask(sel, e, _part(sign, i), _part(filt, i))
        for i, e in enumerate(exists)
    ])
