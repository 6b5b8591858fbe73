"""Device-side deferred-delta merge: sort/dedup/word-OR of staged
position keys as ONE compiled program.

The host sorted-array merge that `Fragment._sync_locked` pays per
fragment at every read barrier became the ingest ceiling once the
staged write path made everything else cheap. The staged architecture
batches naturally: the
pending position buffers of EVERY staged fragment a read is about to
touch are stacked into one key array (segment id packed into the high
bits, core/merge.py) and this module sorts + dedups them in one XLA
dispatch.

Kernel shape (mirrors the TopN gather-tally style — segmentation by
cumsum, no scatter):

- x64 stays off (TPU-native dtypes are 32-bit), so a uint64 key sorts
  as its (hi, lo) uint32 halves via `lax.sort` with two sort keys —
  one stable multi-operand sort, lexicographic by (hi, lo). Every
  backend runs this one formulation, so the CPU tests certify the
  kernel the chip runs.
- dedup is a neighbor-compare mask over the sorted keys; padding
  (all-ones sentinel, unreachable because core/merge.py bounds the
  packed keyspace below 2^63) sorts to the tail and masks out.
- the word-OR rides a uint32 cumsum of per-key single-bit
  contributions: after dedup each (word, bit) pair appears once, so
  OR == sum within a word, and uint32 wraparound keeps per-word
  cumsum differences exact (each word's sum <= 0xFFFFFFFF).

Input sizes pad to power-of-two buckets so the jit cache stays bounded
(log2 of the largest burst, not one executable per burst size).

The compiled dispatch rides exec/plan.py's `_DISPATCH_MU` (one compiled
program in flight at a time — the same rule every stacked query plan
follows); the device->host readback happens OUTSIDE the lock, which a
single-device program permits (no collective rendezvous to deadlock).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Launch accounting: the cross-fragment barrier's "one program launch
# per burst" contract is counter-asserted against this in tests.
MERGE_STATS = {"device_launches": 0, "host_merges": 0}


def reset_stats() -> None:
    MERGE_STATS["device_launches"] = 0
    MERGE_STATS["host_merges"] = 0


_SENTINEL64 = np.uint64(0xFFFFFFFFFFFFFFFF)
_PAD_MIN = 1024


@jax.jit
def _merge_sorted_u32(hi, lo):
    """Sort uint64 keys given as (hi, lo) uint32 halves, mark the first
    occurrence of each distinct key, and cumsum the deduped single-bit
    word contributions. Returns (hi_sorted, lo_sorted, keep, cum)."""
    hi_s, lo_s = jax.lax.sort((hi, lo), num_keys=2)
    changed = (hi_s[1:] != hi_s[:-1]) | (lo_s[1:] != lo_s[:-1])
    first = jnp.concatenate([jnp.ones(1, bool), changed])
    pad = (hi_s == jnp.uint32(0xFFFFFFFF)) & (lo_s == jnp.uint32(0xFFFFFFFF))
    keep = first & ~pad
    # word-OR by cumsum segmentation: each KEPT key contributes its bit
    # (1 << (pos & 31)); duplicate/padding lanes contribute 0 so the
    # inclusive cumsum's per-word differences are the word OR values
    bit = jnp.where(
        keep,
        jnp.left_shift(jnp.uint32(1), jnp.bitwise_and(lo_s, jnp.uint32(31))),
        jnp.uint32(0),
    )
    cum = jnp.cumsum(bit, dtype=jnp.uint32)
    return hi_s, lo_s, keep, cum


def _pad_pow2(keys: np.ndarray) -> np.ndarray:
    n = len(keys)
    cap = _PAD_MIN
    while cap < n:
        cap <<= 1
    if cap == n:
        return keys
    buf = np.full(cap, _SENTINEL64, dtype=np.uint64)
    buf[:n] = keys
    return buf


def merge_keys_device(keys: np.ndarray):
    """Sorted unique keys of a uint64 burst, merged on device as one
    program launch. Returns (merged_keys uint64[], cum uint32[]) where
    `cum` is the inclusive cumsum of each kept key's single-bit word
    contribution, aligned with merged_keys (the word-OR values fall out
    as in-word differences — see module docstring). Keys must stay
    below the all-ones sentinel (core/merge.py guards the packing)."""
    from pilosa_tpu.exec.plan import dispatch_mutex

    buf = _pad_pow2(np.ascontiguousarray(keys, dtype=np.uint64))
    hi = (buf >> np.uint64(32)).astype(np.uint32)
    lo = buf.astype(np.uint32)  # truncates to the low 32 bits
    hi_d = jax.device_put(hi)
    lo_d = jax.device_put(lo)
    with dispatch_mutex():
        out = _merge_sorted_u32(hi_d, lo_d)
    MERGE_STATS["device_launches"] += 1
    hi_s, lo_s, keep, cum = (np.asarray(x) for x in out)
    merged = (hi_s[keep].astype(np.uint64) << np.uint64(32)) | lo_s[
        keep
    ].astype(np.uint64)
    return merged, cum[keep]


def merge_keys_host(keys: np.ndarray):
    """The vectorized host path (one pass for the whole burst — still
    cross-fragment batched, just without a device dispatch): np.unique
    sort/dedup plus the same inclusive bit cumsum contract as the
    device kernel. Tiny deltas stay here behind the
    `merge-device-threshold` crossover — a 200-position burst must not
    pay a program dispatch."""
    MERGE_STATS["host_merges"] += 1
    merged = np.unique(np.asarray(keys, dtype=np.uint64))
    bits = np.uint32(1) << (merged & np.uint64(31)).astype(np.uint32)
    cum = np.cumsum(bits, dtype=np.uint32)
    return merged, cum


def word_or_from_sorted(pos: np.ndarray, cum: np.ndarray):
    """(word_idx uint32[], word_val uint32[]) for a slice of sorted
    unique in-row positions and its aligned inclusive bit cumsum — the
    dense-word delta form the in-place extent patcher uploads. Within a
    word OR == sum (deduped bits are distinct powers of two) and uint32
    wraparound keeps the cumsum differences exact per word."""
    if not len(pos):
        return np.empty(0, np.int64), np.empty(0, np.uint32)
    widx = (pos >> np.uint64(5)).astype(np.int64)
    last = np.concatenate(
        [np.flatnonzero(widx[1:] != widx[:-1]), [len(widx) - 1]]
    ).astype(np.int64)
    ends = cum[last].astype(np.uint32, copy=False)
    # exact Python ints then wrap: numpy SCALAR unsigned overflow warns,
    # array wraparound (ends - starts below) does not
    base = np.uint32(
        (int(cum[0]) - (1 << (int(pos[0]) & 31))) & 0xFFFFFFFF
    )
    starts = np.empty(len(ends), np.uint32)
    starts[0] = base
    starts[1:] = ends[:-1]
    vals = ends - starts  # uint32 wraparound: exact per-word sums
    return widx[last], vals
