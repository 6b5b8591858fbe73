"""Device bitmap engine — dense uint32 bit-block algebra.

This is the TPU-native replacement for the reference's roaring container op
matrix (reference: roaring/roaring.go:3121-5196 — intersect/union/difference/
xor/shift/flip/intersectionCount specialized per container type-pair, and
popcount at roaring.go:5291).

Design: instead of three polymorphic container encodings (array/bitmap/run)
with a 9-way op dispatch, a row's bits within one shard are a *dense*
little-endian uint32 vector of WORDS_PER_ROW words living in HBM. All set
algebra is elementwise bitwise ops + `lax.population_count`, which XLA fuses
and tiles onto the VPU. Compression exists only at the storage/interchange
boundary (core/roaring_io.py), never on the compute path.

Conventions:
- bit b of word w  <=>  in-shard column position 32*w + b  (little-endian).
- All ops broadcast over arbitrary leading axes, so [W], [rows, W] and
  [shards, rows, W] stacks share one code path (and one compiled kernel).
- Counts are returned as uint32/int32 device scalars; callers `int()` them
  at the host boundary.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_ROW

# ---------------------------------------------------------------------------
# Host-side packing (storage boundary only — never on the query path)
# ---------------------------------------------------------------------------


def pack_positions(positions, n_bits: int = SHARD_WIDTH) -> np.ndarray:
    """Pack sorted/unsorted in-shard positions into a dense uint32 word vector."""
    words = np.zeros(n_bits // 32, dtype=np.uint32)
    if len(positions):
        p = np.asarray(positions, dtype=np.uint64)
        if p.size and (p.max() >= n_bits):
            raise ValueError(f"position {p.max()} out of range for {n_bits} bits")
        np.bitwise_or.at(
            words,
            (p >> 5).astype(np.int64),
            np.uint32(1) << (p & np.uint64(31)).astype(np.uint32),
        )
    return words


def unpack_positions(words: np.ndarray) -> np.ndarray:
    """Inverse of pack_positions: dense words -> sorted uint64 positions."""
    w = np.ascontiguousarray(np.asarray(words, dtype=np.uint32))
    bits = np.unpackbits(w.view(np.uint8), bitorder="little")
    return np.nonzero(bits)[0].astype(np.uint64)


# ---------------------------------------------------------------------------
# Device algebra — jitted, shape-polymorphic over leading axes
# ---------------------------------------------------------------------------


@jax.jit
def b_and(a, b):
    return jnp.bitwise_and(a, b)


@jax.jit
def b_or(a, b):
    return jnp.bitwise_or(a, b)


@jax.jit
def b_xor(a, b):
    return jnp.bitwise_xor(a, b)


@jax.jit
def b_andnot(a, b):
    """a AND NOT b (reference: roaring difference, roaring.go:4119)."""
    return jnp.bitwise_and(a, jnp.bitwise_not(b))


@jax.jit
def b_not(a, exists):
    """NOT a, bounded by the existence row (reference: executor.go:1734
    executeNot via the `_exists` field — complement is always relative to
    actually-present columns, never the full 2^64 space)."""
    return jnp.bitwise_and(jnp.bitwise_not(a), exists)


# Count convention: one (row, shard) holds at most SHARD_WIDTH <= 2^30 bits
# (shardwidth.py caps the exponent), so a per-row popcount always fits
# uint32. Cross-row / cross-shard totals can
# exceed 2^32; the *_rows variants below are therefore the query-path API — the
# executor reduces the per-row partials host-side in exact Python ints
# (mirroring the reference's reduceFn merges, executor.go:2489), and the mesh
# path reduces them with collectives before a final host combine. The scalar
# conveniences (popcount/count_and/...) sum over ALL axes in uint32 and are
# only safe when the true total is < 2^32.


@jax.jit
def popcount(words) -> jnp.ndarray:
    """Total set bits over ALL axes (uint32 scalar; wraps above 2^32 — use
    popcount_rows + host reduce for large stacks)."""
    return jnp.sum(lax_popcount_u32(words), dtype=jnp.uint32)


@jax.jit
def popcount_rows(words) -> jnp.ndarray:
    """Set bits per row: sums over the trailing word axis only."""
    return jnp.sum(lax_popcount_u32(words), axis=-1, dtype=jnp.uint32)


def lax_popcount_u32(words):
    return jax.lax.population_count(words.astype(jnp.uint32))


@jax.jit
def count_and(a, b) -> jnp.ndarray:
    """Fused popcount(a & b) — Count(Intersect(...)) without materializing
    the intersection (reference: intersectionCount, roaring.go:3121).
    All-axes uint32 sum; see count convention above."""
    return jnp.sum(jax.lax.population_count(jnp.bitwise_and(a, b)), dtype=jnp.uint32)


@jax.jit
def count_and_rows(a, b) -> jnp.ndarray:
    """Fused per-row intersection count (trailing axis reduced only)."""
    return jnp.sum(
        jax.lax.population_count(jnp.bitwise_and(a, b)), axis=-1, dtype=jnp.uint32
    )


@jax.jit
def gather_tally_sorted(src, idx, mask, starts, ends) -> jnp.ndarray:
    """Segment sums of popcount(src.flat[idx] & mask), segments given as
    sorted half-open [starts, ends) ranges over the entry axis ->
    uint32[n_seg].

    The sparse half of the TopN filtered tally: each entry is one live
    word of a sparse candidate row, so the filter stack is gathered at
    just those words instead of streaming full zero-padded candidate
    planes from HBM (the reference recounts candidate rows per shard on
    the CPU instead, fragment.go:1570-1743). Segment reduction is
    cumsum + two boundary gathers — NOT scatter-add (segment_sum), which
    serializes on TPU. uint32 cumsum is exact while the entry count stays
    under 2^27 (each entry contributes <= 32); the caller enforces that
    bound when building entries."""
    vals = jax.lax.population_count(jnp.bitwise_and(src.reshape(-1)[idx], mask))
    # (a two-level blocked scan was tried here and measured at parity:
    # the scattered gather dominates and overlaps the scan)
    cum = jnp.concatenate(
        [jnp.zeros(1, jnp.uint32), jnp.cumsum(vals, dtype=jnp.uint32)]
    )
    return cum[ends] - cum[starts]


@jax.jit
def count_andnot(a, b) -> jnp.ndarray:
    return jnp.sum(
        jax.lax.population_count(jnp.bitwise_and(a, jnp.bitwise_not(b))), dtype=jnp.uint32
    )


@jax.jit
def union_reduce(stack):
    """Bitwise-or reduce over axis 0: n-way union (reference: unionInPlace
    bulk n-way union, roaring.go:739-890)."""
    return jax.lax.reduce(
        stack, jnp.uint32(0), jnp.bitwise_or, dimensions=(0,)
    )


@jax.jit
def intersect_reduce(stack):
    ones = jnp.uint32(0xFFFFFFFF)
    return jax.lax.reduce(stack, ones, jnp.bitwise_and, dimensions=(0,))


@jax.jit
def xor_reduce(stack):
    return jax.lax.reduce(stack, jnp.uint32(0), jnp.bitwise_xor, dimensions=(0,))


@partial(jax.jit, static_argnames=("n_bits",))
def range_mask_words(start, stop, n_bits: int = SHARD_WIDTH):
    """Dense mask with bits [start, stop) set — for CountRange / flip windows.

    start/stop are traced (arbitrary user-supplied ranges must not retrace;
    only the shape argument n_bits is static)."""
    n_words = n_bits // 32
    base = jnp.arange(n_words, dtype=jnp.int32) * 32
    start = jnp.asarray(start, dtype=jnp.int32)
    stop = jnp.asarray(stop, dtype=jnp.int32)
    # bits set in word w: max(0, min(stop, base+32) - max(start, base)) contiguous
    lo = jnp.clip(start - base, 0, 32)
    hi = jnp.clip(stop - base, 0, 32)
    nset = jnp.maximum(hi - lo, 0)
    # mask = ((1<<nset)-1) << lo, with nset==32 handled via full-ones select
    ones = jnp.uint32(0xFFFFFFFF)
    body = jnp.where(
        nset >= 32,
        ones,
        ((jnp.uint32(1) << nset.astype(jnp.uint32)) - jnp.uint32(1)),
    )
    return jnp.where(nset > 0, body << lo.astype(jnp.uint32), jnp.uint32(0))


@jax.jit
def count_range(words, start, stop) -> jnp.ndarray:
    """popcount of bits in [start, stop) (reference: CountRange, roaring.go:~390).
    start/stop are traced; one compiled kernel serves all ranges."""
    mask = range_mask_words(start, stop, words.shape[-1] * 32)
    return jnp.sum(jax.lax.population_count(jnp.bitwise_and(words, mask)), dtype=jnp.uint32)


@partial(jax.jit, static_argnames=("n",))
def shift_bits(words, n: int = 1):
    """Shift the whole bit-vector towards higher positions by n (static).

    Returns (shifted, overflow) where `overflow` is the n high bits that fell
    off the end, rebased to positions [0, n) — the executor carries them into
    the next shard (reference: roaring shift, roaring.go:4579; Row.Shift,
    row.go). Operates on the last axis.
    """
    if n == 0:
        return words, jnp.zeros_like(words)
    n_words = words.shape[-1]
    if not 0 <= n <= n_words * 32:
        raise ValueError(
            f"shift amount {n} out of range [0, {n_words * 32}]: overflow may only "
            "carry into the immediately following shard"
        )
    q, r = divmod(n, 32)

    def word_shift(x, k):
        if k == 0:
            return x
        pad = jnp.zeros(x.shape[:-1] + (k,), dtype=x.dtype)
        return jnp.concatenate([pad, x[..., : n_words - k]], axis=-1)

    shifted = word_shift(words, q)
    if r:
        lo = jnp.left_shift(shifted, jnp.uint32(r))
        prev = jnp.concatenate(
            [jnp.zeros(shifted.shape[:-1] + (1,), dtype=shifted.dtype), shifted[..., :-1]],
            axis=-1,
        )
        shifted = jnp.bitwise_or(lo, jnp.right_shift(prev, jnp.uint32(32 - r)))

    # Overflow: original bits in [n_bits - n, n_bits) rebased to [0, n).
    # Compute by shifting the original DOWN by (n_bits - n).
    m = n_words * 32 - n
    qd, rd = divmod(m, 32)
    down = jnp.concatenate(
        [words[..., qd:], jnp.zeros(words.shape[:-1] + (qd,), dtype=words.dtype)], axis=-1
    )
    if rd:
        nxt = jnp.concatenate(
            [down[..., 1:], jnp.zeros(down.shape[:-1] + (1,), dtype=down.dtype)], axis=-1
        )
        down = jnp.bitwise_or(
            jnp.right_shift(down, jnp.uint32(rd)), jnp.left_shift(nxt, jnp.uint32(32 - rd))
        )
    overflow_mask = range_mask_words(0, n, n_words * 32)
    overflow = jnp.bitwise_and(down, overflow_mask)
    return shifted, overflow


@jax.jit
def any_set(words) -> jnp.ndarray:
    """True if any bit is set (bool scalar)."""
    return jnp.any(words != 0)


def empty_row(n_words: int = WORDS_PER_ROW) -> np.ndarray:
    return np.zeros(n_words, dtype=np.uint32)
