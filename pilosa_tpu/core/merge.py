"""Cross-fragment deferred-delta merge barrier.

`Fragment._sync_locked` merges each fragment's staged ingest delta
independently at its own read barrier — correct, but a 954-fragment
ingest burst then pays 954 per-fragment host passes (each a handful of
small-numpy calls plus a lock, with per-row rewrite work on top) the
first time a query reads the view. This module is the view/field-level
collector: it gathers the pending position buffers of every staged
fragment a read is about to touch, packs them into ONE uint64 key
array (segment id in the high bits, position in the low bits),
sort/dedups the whole burst in one pass — on device (ops/merge.py, one
program launch) at or above the `merge-device-threshold` crossover, as
one vectorized host pass below it — and hands each fragment its merged
slice back as a parked DELTA LAYER (pending-part format). The barrier
is O(burst): the row-store materialization rides each fragment's next
HOST read (`_sync_locked` folds layers into the vectorized merge it
already runs), while the device stays exact immediately — resident
extents are patched in place with the same merged word deltas
(core/view.py), so warm device-served queries never wait on a host
row rewrite at all.

Concurrency handshake (no fragment lock is ever held across another's,
and none is held during the merge itself):

- snapshot phase: under each fragment's lock, the barrier records a
  REFERENCE to the current pending parts list, its length, the
  fragment's `_pending_gen` and `_staged_base_version`. Nothing is
  popped — a concurrent reader hitting `_sync_locked` mid-merge still
  sees (and merges) everything, staying exact.
- apply phase: under each fragment's lock again,
  `Fragment.apply_merged_delta` re-checks the generation. If a
  concurrent `_sync_locked` already merged the captured parts the
  apply is skipped (the work was done exactly once by the other
  path); otherwise the merged delta layer parks, the captured parts
  are trimmed, and the generation bumps.

The per-fragment outcome (`FragMerge`) carries what the view needs for
in-place extent patching (hbm/residency.py): which rows changed, their
word-level deltas, and the version window [base, base + n_parts] the
patch is valid for — a patch is only taken when the fragment saw no
other mutation in between (`clean`), since anything else either merged
the delta itself or invalidated the covering extents already.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional

import numpy as np

from pilosa_tpu.utils.locks import TrackedLock
from pilosa_tpu.ops import merge as ops_merge
from pilosa_tpu.shardwidth import SHARD_WIDTH, SHARD_WIDTH_EXPONENT

# Crossover between the batched host merge and the device program:
# bursts with at least this many total pending positions dispatch the
# sort/dedup kernel; smaller deltas stay on the vectorized host path
# (a 200-position delta must not pay a program dispatch). < 0 disables
# the device path outright; 0 forces it (tests use both extremes).
# None = AUTO: 65536 on an accelerator, device-off on the CPU backend —
# there the "device" is the same silicon reached through XLA's two-key
# sort comparator (ops/merge.py), which np.unique beats at every burst
# size, so the dispatch can never pay for itself.
_ACCEL_DEVICE_THRESHOLD = 65536


def _env_threshold() -> Optional[int]:
    raw = os.environ.get("PILOSA_TPU_MERGE_DEVICE_THRESHOLD")
    try:
        return int(raw) if raw not in (None, "") else None
    except ValueError:
        return None


_device_threshold: Optional[int] = _env_threshold()
_auto_threshold: List[int] = []  # backend probe cache (lazy: jax init)

_stats_mu = TrackedLock("merge.stats_mu")
_counters: Dict[str, float] = {
    "barrier_ms": 0.0,  # cumulative wall ms spent in merge barriers
    "barriers": 0,  # barrier invocations that merged at least one fragment
    "batches": 0,  # staged pending buffers merged (barrier + per-fragment)
    "device": 0,  # barriers that dispatched the device merge program
    "positions": 0,  # raw staged positions merged through barriers
}


_UNSET = object()


def configure(device_threshold=_UNSET) -> None:
    """Install the server's [ingest] knobs (cli/config.py ->
    server/node.py). None selects the backend-adaptive AUTO crossover.
    Process-global, like the [hbm] knobs: all in-process nodes share
    one device."""
    global _device_threshold
    if device_threshold is not _UNSET:
        _device_threshold = (
            None if device_threshold is None else int(device_threshold)
        )


def device_threshold() -> int:
    """The resolved crossover (AUTO probes the backend once, lazily —
    importing this module must not initialize jax)."""
    if _device_threshold is not None:
        return _device_threshold
    if not _auto_threshold:
        import jax

        # a backend that fails to initialise raises here: substituting
        # "cpu" would silently turn the device merge off on the chip
        _auto_threshold.append(
            -1 if jax.default_backend() == "cpu" else _ACCEL_DEVICE_THRESHOLD
        )
    return _auto_threshold[0]


def reset_stats() -> None:
    with _stats_mu:
        for k in _counters:
            _counters[k] = 0 if k != "barrier_ms" else 0.0


def note_host_sync(n_batches: int) -> None:
    """Book a per-fragment `_sync_locked` merge (the non-barrier path)
    so `ingest.merge_batches` counts every staged buffer exactly once
    however it got merged."""
    with _stats_mu:
        _counters["batches"] += n_batches


def stats_snapshot() -> Dict[str, float]:
    """ingest.merge_* gauge values (NodeServer.publish_cache_gauges)."""
    with _stats_mu:
        return dict(_counters)


class FragMerge:
    """One fragment's barrier outcome, consumed by the view's extent
    reconciliation. `rows` is the fragment's touched row ids (ascending
    python ints); `starts`/`ends` index into the barrier's GLOBAL merged
    column/cumsum arrays (`cols`/`cum`, shared across all FragMerges of
    one barrier — slicing is lazy, only for rows a patch actually
    needs). Each row's slice is its sorted-unique staged DELTA, so the
    word-OR handed to the extent patcher is exactly the bits the burst
    set. `clean` means the fragment moved from `base_version` to
    `new_version` by EXACTLY the captured staged batches (structurally
    true whenever the apply landed: pending parts are a contiguous
    version range, since any non-stage mutation drains pending first
    under the fragment lock), so a resident extent keyed at
    `base_version` can be patched in place to `new_version` instead of
    re-staged — even mid-burst, with later batches still pending and
    re-keying the extent forward at their own barrier."""

    __slots__ = (
        "frag",
        "shard",
        "applied",
        "clean",
        "base_version",
        "new_version",
        "rows",
        "cols",
        "cum",
        "starts",
        "ends",
        "old_words",
    )

    def __init__(self, frag, rows, cols, cum, starts, ends):
        self.frag = frag
        self.shard = frag.shard
        self.applied = False
        self.clean = False
        self.base_version = -1
        self.new_version = -1
        self.rows = rows  # python list of touched row ids, ascending
        self.cols = cols
        self.cum = cum
        self.starts = starts
        self.ends = ends
        # row id -> host words at base_version, captured BEFORE the
        # delta layer parked — only for rows the result cache registered
        # interest in (core/resultcache.py count repair)
        self.old_words: Dict[int, np.ndarray] = {}

    def word_delta(self, row_id: int):
        """(word_idx, word_val) arrays of this row's merged delta, for
        the device-side extent patch."""
        i = self.rows.index(row_id)
        s, e = self.starts[i], self.ends[i]
        return ops_merge.word_or_from_sorted(self.cols[s:e], self.cum[s:e])


def _repair_interest(frag) -> set:
    """Rows of this fragment's (index, field, view) that repairable
    cached Counts are watching (core/resultcache.py). Lazy import: the
    cache module is light, but core/merge must stay importable without
    it mid-bootstrap; the common path is one dict lookup returning
    empty."""
    from pilosa_tpu.core.resultcache import RESULT_CACHE

    return RESULT_CACHE.interest_rows(frag.index, frag.field, frag.view)


def merge_barrier(frags) -> List[FragMerge]:
    """Merge the pending deltas of every staged fragment in `frags` as
    one batched pass. Returns a FragMerge per fragment that had a
    delta captured (applied or not). Mutex fragments never stage, so
    they are skipped by construction.

    The barrier's cost is O(burst), independent of fragment count and
    of accumulated fragment content: pack, sort/dedup (device program
    or np.unique) and per-row boundary math all run GLOBALLY over the
    staged positions, and each fragment's apply just trims its pending
    batches and parks its merged slice as a delta layer (the row-store
    materialization rides the fragment's next HOST read barrier — the
    device is kept exact directly, via in-place extent patches built
    from the FragMerge word deltas). The per-fragment host path pays
    ~a dozen small-numpy calls per fragment plus per-row rewrite work;
    at bench geometry (954 fragments x ~30 rows) that overhead IS the
    merge cost."""
    staged = [f for f in frags if f is not None and f._pending_n]
    if not staged:
        return []
    t0 = time.perf_counter()
    caps = []
    for f in staged:
        snap = f.pending_snapshot()
        if snap is not None:
            caps.append((f,) + snap)
    if not caps:
        return []

    # pack (segment, position) into one uint64 keyspace: ROW_SPAN is
    # the per-fragment span, rounded up to a SHARD_WIDTH multiple so
    # key >> SHARD_WIDTH_EXPONENT stays (segment, row)-unique and the
    # low 5 bits stay the in-word bit (the kernel's word-OR relies on
    # both). Pathological row ids that would overflow the packing
    # (2^63 guard) fall back to per-fragment host merges.
    parts_flat: List[np.ndarray] = []
    part_seg: List[int] = []
    for i, cap in enumerate(caps):
        for part in cap[1]:
            parts_flat.append(part)
            part_seg.append(i)
    combined = (
        parts_flat[0] if len(parts_flat) == 1 else np.concatenate(parts_flat)
    )
    max_pos = int(combined.max())
    row_span = ((max_pos >> SHARD_WIDTH_EXPONENT) + 1) << SHARD_WIDTH_EXPONENT
    if len(caps) * row_span >= 1 << 63:
        for cap in caps:
            cap[0].sync_pending_now()
        return []
    if len(caps) > 1 or part_seg[0]:
        seg_off = np.repeat(
            np.array(part_seg, np.uint64) * np.uint64(row_span),
            [len(p) for p in parts_flat],
        )
        combined = combined + seg_off
    rows_per_seg = row_span >> SHARD_WIDTH_EXPONENT

    thr = device_threshold()
    use_device = thr >= 0 and len(combined) >= thr
    if use_device:
        merged, cum = ops_merge.merge_keys_device(combined)
    else:
        merged, cum = ops_merge.merge_keys_host(combined)

    # per-row boundaries over the whole burst, then plain-list slices
    # per fragment (the apply must not touch numpy per row); `local`
    # de-offsets the keyspace once so each fragment can park its slice
    # as a delta layer in pending-part format
    seg_edges = np.searchsorted(
        merged, np.arange(len(caps) + 1, dtype=np.uint64) * np.uint64(row_span)
    )
    local = merged - np.repeat(
        np.arange(len(caps), dtype=np.uint64) * np.uint64(row_span),
        np.diff(seg_edges),
    )
    cols_g = (merged & np.uint64(SHARD_WIDTH - 1)).astype(np.uint32)
    rowkeys = merged >> np.uint64(SHARD_WIDTH_EXPONENT)
    bounds = np.flatnonzero(rowkeys[1:] != rowkeys[:-1]) + 1
    starts_g = np.empty(len(bounds) + 1, np.int64)
    starts_g[0] = 0
    starts_g[1:] = bounds
    ends_g = np.empty_like(starts_g)
    ends_g[:-1] = bounds
    ends_g[-1] = len(merged)
    rk_start = rowkeys[starts_g]
    row_of = (rk_start % np.uint64(rows_per_seg)).astype(np.int64).tolist()
    starts_l = starts_g.tolist()
    ends_l = ends_g.tolist()
    frag_edges = np.searchsorted(
        rk_start,
        np.arange(len(caps) + 1, dtype=np.uint64) * np.uint64(rows_per_seg),
    ).tolist()

    seg_edges_l = seg_edges.tolist()
    out: List[FragMerge] = []
    n_batches = 0
    for i, (f, parts, n_parts, gen, base_version) in enumerate(caps):
        rlo, rhi = frag_edges[i], frag_edges[i + 1]
        if rlo == rhi:
            continue
        rows_i = row_of[rlo:rhi]
        fm = FragMerge(
            f, rows_i, cols_g, cum, starts_l[rlo:rhi], ends_l[rlo:rhi]
        )
        fm.base_version = base_version
        # count-repair old-words capture: for rows a cached Count is
        # watching, read the row's host words at base_version NOW —
        # after the apply below the fragment's content has moved past
        # the base and popcount(delta & ~old) is no longer computable.
        # EVERY interest row is captured, not just the burst's: a
        # repair-spec tree patch (core/resultcache.py) needs the
        # UNTOUCHED leaves' words from the same consistent base
        # snapshot to evaluate op(old)/op(new) — an untouched row's
        # capture equals its merged content, so it serves both sides.
        # A concurrent _sync_locked between this read and the apply
        # bumps the generation, the apply returns None, and the capture
        # is discarded with the failed FragMerge — never applied stale.
        want = _repair_interest(f)
        for rid in want:
            fm.old_words[rid] = f.premerge_row_words(rid)
        # the layer is COPIED out of the shared burst buffer: a view
        # would pin the whole round's merged array until the last
        # fragment's host read materializes it
        res = f.apply_merged_delta(
            local[seg_edges_l[i] : seg_edges_l[i + 1]].copy(),
            n_parts, sum(map(len, parts)), gen,
        )
        if res is not None:
            fm.applied = True
            # the captured delta moves content EXACTLY base ->
            # base+n_parts: pending parts are always a contiguous
            # version range (any non-stage mutation drains pending
            # first, under the fragment lock), so batches staged AFTER
            # the snapshot stay pending and re-key the extent forward
            # at THEIR barrier — the patch chain never breaks under
            # continuous ingest
            fm.new_version = base_version + n_parts
            fm.clean = True
            n_batches += n_parts
        out.append(fm)

    dt_ms = (time.perf_counter() - t0) * 1000.0
    with _stats_mu:
        _counters["barrier_ms"] += dt_ms
        _counters["barriers"] += 1
        _counters["batches"] += n_batches
        _counters["positions"] += len(combined)
        if use_device:
            _counters["device"] += 1
    return out
