"""Extent-granular operand residency.

A stacked query operand is `uint32[S, W]` (one row across S shards) or
`uint32[D, S, W]` (D BSI planes x S shards). Staged monolithically, an HBM
budget below one query's working set churns the WHOLE operand set per
query. Here the shard axis is split into EXTENTS — fixed-size shard-major
slices of `hbm-extent-rows` row-planes — that are individually LRU-tracked
in the device cache (core/devcache.py), so under pressure only the evicted
slices re-upload and the operand is reassembled with one device-side
concat (HBM bandwidth, not PCIe).

Anti-thrash protocol (the reason extents beat plain LRU's cyclic-scan
pathology): staging an operand first PINS its already-resident extents,
then builds the missing ones — so staging extent k can never evict extent
k-1 of the same operand, and a budget one slice short of the working set
costs one slice of re-upload per query, not the whole working set. The
pins are handed to the plan's ExtentTable and held through the compiled
dispatch (exec/plan.py releases them in its dispatch `finally`), so an
in-flight operand's extents are never evicted mid-query; with no table
(ad-hoc callers) they release when assembly returns.

Mesh note: under an active device mesh (parallel/mesh.py) operands carry
NamedSharding placement and XLA owns their layout across chips — extent
slicing would fight the SPMD partitioner, so mesh-placed stacks stage
monolithically (still budget-tracked). Extent paging targets the
single-chip serving path, where the measured eviction cliff lives.
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from contextlib import contextmanager
from typing import Callable, Dict, Iterator, List, Optional, Set, Tuple

import numpy as np

from pilosa_tpu.core.devcache import DEVICE_CACHE
from pilosa_tpu.shardwidth import WORDS_PER_ROW
from pilosa_tpu.utils import tracing
from pilosa_tpu.utils.locks import TrackedLock

_DEFAULT_EXTENT_ROWS = 256


def _env_extent_rows() -> int:
    raw = os.environ.get("PILOSA_TPU_HBM_EXTENT_ROWS")
    try:
        return int(raw) if raw else _DEFAULT_EXTENT_ROWS
    except ValueError:
        return _DEFAULT_EXTENT_ROWS


_extent_rows = _env_extent_rows()

_stats_mu = TrackedLock("hbm.stats_mu")
_counters: Dict[str, int] = {
    "restage_bytes": 0,  # host->device upload bytes through this layer
    "prefetch_hits": 0,  # query staging hit an extent the prefetcher warmed
    "prefetch_staged": 0,  # extents the prefetcher uploaded
    # resident extents rewritten in place (old words | merged staged
    # delta, on device) instead of invalidated + re-staged over PCIe —
    # the merge barrier's reconciliation books these (core/view.py)
    "extent_patches": 0,
    # batched patch scatters issued (one gather|OR|scatter per patched
    # entry per 256 dirty delta blocks — the memory-bounded batch
    # size): a smeared burst's cascade is O(entries) device ops, not
    # O(dirty shards) — compare against extent_patches to read the
    # coalescing ratio
    "extent_patch_batches": 0,
}
# per-owner-index restage attribution ("-" collects staging not bound to
# an index); dropped by drop_index() when the index is deleted so a
# churning tenant set cannot leak counter entries
_restage_by_index: Dict[str, int] = {}
_prefetched_keys: Set[Tuple] = set()

_tls = threading.local()


def configure(
    extent_rows: Optional[int] = None, pin_timeout: Optional[float] = None
) -> None:
    """Install the server's [hbm] knobs (cli/config.py -> server/node.py).
    extent_rows <= 0 disables extent slicing (monolithic staging);
    pin_timeout is the stale-pin safety valve on the shared device cache."""
    global _extent_rows
    if extent_rows is not None:
        _extent_rows = int(extent_rows)
    if pin_timeout is not None:
        DEVICE_CACHE.pin_timeout = float(pin_timeout)


def extent_rows() -> int:
    return _extent_rows


def _bump(key: str, value: int = 1) -> None:
    with _stats_mu:
        _counters[key] += value


def reset_stats() -> None:
    with _stats_mu:
        for k in _counters:
            _counters[k] = 0
        _restage_by_index.clear()
        _prefetched_keys.clear()


def drop_index(index: str) -> None:
    """Label GC hook (NodeServer.drop_index_telemetry): forget a deleted
    index's restage attribution so per-index counter entries cannot
    accumulate across tenant churn. Also re-buckets the device cache's
    residency attribution (zombie bytes pinned by an in-flight dispatch
    would otherwise resurrect the dropped gauge series on the next
    sampler tick)."""
    with _stats_mu:
        _restage_by_index.pop(index, None)
    DEVICE_CACHE.drop_index_attribution(index)


def stats_snapshot() -> Dict[str, int]:
    """hbm.* gauge values (NodeServer.publish_cache_gauges): residency
    comes from the shared device-cache ledger, traffic counters from this
    module. `restage_by_index` splits the cumulative restage bytes by
    owner index (values sum to `restage_bytes`)."""
    snap = DEVICE_CACHE.stats_snapshot()
    with _stats_mu:
        return {
            "resident_extents": snap["resident_extents"],
            "pinned_bytes": snap["pinned_bytes"],
            "restage_bytes": _counters["restage_bytes"],
            "restage_by_index": dict(_restage_by_index),
            "prefetch_hits": _counters["prefetch_hits"],
            "prefetch_staged": _counters["prefetch_staged"],
            "extent_patches": _counters["extent_patches"],
            "extent_patch_batches": _counters["extent_patch_batches"],
            "evicted_extent_bytes": snap["evicted_extent_bytes"],
        }


def eviction_pressure() -> int:
    """Cumulative extent-eviction bytes the device cache has shed — the
    tier plane's demotion-pressure signal (tier/manager.py demote_tick):
    growth between ticks means the working set exceeds the device
    budget, so idle cold-placement fragments demote at half their idle
    threshold instead of waiting out the full clock."""
    return int(DEVICE_CACHE.stats_snapshot().get("evicted_extent_bytes", 0))


def note_extent_patch(batches: int = 0) -> None:
    """Book one in-place device-side extent patch (core/view.py
    _patch_entry): a write that kept its covering extent resident.
    `batches` counts the batched gather|OR|scatter device ops the patch
    issued (one per 256 dirty delta blocks, never one per shard)."""
    with _stats_mu:
        _counters["extent_patches"] += 1
        _counters["extent_patch_batches"] += batches


@contextmanager
def prefetching() -> Iterator[None]:
    """Mark this thread as the prefetch worker: extents it stages are
    remembered, and a later query hit on one counts as a prefetch hit."""
    _tls.active = True
    try:
        yield
    finally:
        _tls.active = False


def _in_prefetch() -> bool:
    return getattr(_tls, "active", False)


class ExtentTable:
    """The extents one lowered plan's operands are pinned on. Ownership of
    one pin per key transfers here from staging; exec/plan.py releases in
    its dispatch `finally`. Release is idempotent — double release (e.g.
    an error path AND the plan finally) never over-decrements."""

    __slots__ = ("_keys", "_released")

    def __init__(self) -> None:
        self._keys: List[Tuple] = []
        self._released = False

    def add(self, keys: List[Tuple]) -> None:
        if self._released:
            # staging after release (a plan re-lowered late): hold nothing
            DEVICE_CACHE.unpin_all(keys)
            return
        self._keys.extend(keys)

    def release(self) -> None:
        if self._released:
            return
        self._released = True
        DEVICE_CACHE.unpin_all(self._keys)

    def __len__(self) -> int:
        return len(self._keys)

    @property
    def keys(self) -> List[Tuple]:
        return list(self._keys)


# ---------------------------------------------------------------------------
# host staging buffers
# ---------------------------------------------------------------------------

# idle buffers kept for the next build, in bytes: two of a four-chip host's
# 78 MB row stacks fit, a taxi plane extent (19 x 256 rows = 637 MB) does
# not and is allocated per build
_POOL_KEEP_BYTES = 256 << 20


class StagingPool:
    """The host buffers a miss builds its row or plane stack into, by
    shape. A build used to allocate one 128 KiB row per fragment and
    `np.stack` them into a fresh stack: 150 allocations of glibc's mmap
    threshold and up, every page faulted in and handed back once per
    miss (PERF.md section 6, PR 34). `take` hands out a buffer, `give`
    takes it back together with the device array that was put from it;
    the buffer is lent again only once that array is ready
    (`jax.device_put` returns before the copy has read the host memory)
    and never when the array aliases it (the CPU backend adopts an
    aligned host buffer instead of copying). A buffer that is never
    given back is simply garbage; `give` ignores what `take` did not
    hand out."""

    def __init__(self, keep_bytes: int = _POOL_KEEP_BYTES) -> None:
        self.keep_bytes = keep_bytes
        self._mu = TrackedLock("hbm.staging_mu")
        self._lent = weakref.WeakValueDictionary()  # id(buf) -> buf
        self._free: Dict[Tuple[int, ...], List[np.ndarray]] = {}
        self._free_bytes = 0
        # given back, the upload may still be reading: (buf, weakref(arr))
        self._in_flight: List[Tuple[np.ndarray, object]] = []
        self.reused = 0  # takes served by a pooled buffer

    def take(self, shape: Tuple[int, ...]) -> np.ndarray:
        """An uninitialised uint32 buffer of `shape`; the caller writes
        every word."""
        shape = tuple(int(n) for n in shape)
        if 0 in shape:
            return np.empty(shape, np.uint32)  # nothing to pool
        with self._mu:
            self._reap_locked()
            bufs = self._free.get(shape)
            if bufs:
                buf = bufs.pop()
                self._free_bytes -= buf.nbytes
                self.reused += 1
            else:
                buf = np.empty(shape, np.uint32)
            self._lent[id(buf)] = buf
        return buf

    def give(self, buf, arr=None) -> None:
        """`buf` back after `arr = device_put(buf)` (or with no upload
        at all: `arr` None makes it free at once)."""
        with self._mu:
            if self._lent.pop(id(buf), None) is not buf:
                return  # the caller's own array
            self._in_flight.append(
                (buf, None if arr is None else weakref.ref(arr))
            )
            self._reap_locked()

    def _reap_locked(self) -> None:  # guarded-by: _mu
        waiting = []
        for buf, ref in self._in_flight:
            if ref is not None:
                arr = ref()
                if arr is None or arr.is_deleted():
                    continue  # the upload's end cannot be seen: drop it
                if not arr.is_ready():
                    waiting.append((buf, ref))
                    continue
                if _aliases(buf, arr):
                    continue  # the array owns this memory now
            if self._free_bytes + buf.nbytes <= self.keep_bytes:
                self._free.setdefault(buf.shape, []).append(buf)
                self._free_bytes += buf.nbytes
        self._in_flight = waiting

    def clear(self) -> None:
        with self._mu:
            self._lent.clear()
            self._free.clear()
            self._free_bytes = 0
            self._in_flight = []
            self.reused = 0


def _aliases(buf: np.ndarray, arr) -> bool:
    """Whether a device array's memory lies inside `buf` (a zero-copy
    `device_put` on the CPU backend); an accelerator's never does."""
    lo = buf.ctypes.data
    for shard in arr.addressable_shards:
        if shard.device.platform != "cpu":
            return False
        if lo <= shard.data.unsafe_buffer_pointer() < lo + buf.nbytes:
            return True
    return False


STAGING = StagingPool()


def _fill_rows(out: np.ndarray, frags, row_id: int) -> None:
    for row, frag in zip(out, frags):
        if frag is not None:
            frag.fill_row_words(row_id, row)
        else:
            row.fill(0)


def build_row_slice(frags, row_id: int) -> np.ndarray:
    """uint32[len(frags), W] host stack of one row over the fragments
    `frags` (None = no fragment: zeros), built into a staging buffer.
    With `build_plane_slice` THE build of a stack's slice, for `View`
    and the mesh group's `GroupView`, on one device and on a mesh."""
    out = STAGING.take((len(frags), WORDS_PER_ROW))
    _fill_rows(out, frags, row_id)
    return out


def build_plane_slice(frags, row_ids) -> np.ndarray:
    """uint32[D, len(frags), W]: the rows `row_ids` (BSI planes) over
    `frags`, built into one staging buffer."""
    out = STAGING.take((len(row_ids), len(frags), WORDS_PER_ROW))
    for plane, row_id in zip(out, row_ids):
        _fill_rows(plane, frags, row_id)
    return out


# ---------------------------------------------------------------------------
# staging
# ---------------------------------------------------------------------------


def _note_upload(
    nbytes: int, key: Tuple, built: bool, index: Optional[str] = None
) -> None:
    """Book one extent acquisition: uploads count restage bytes; hits on
    prefetcher-staged extents count prefetch hits. Query-thread work also
    feeds the per-thread flight-recorder staging account (flushed into an
    exec.stage span by the dispatch that consumes the operands)."""
    if built:
        _bump("restage_bytes", nbytes)
        label = index if index is not None else "-"
        with _stats_mu:
            _restage_by_index[label] = (
                _restage_by_index.get(label, 0) + nbytes
            )
        if _in_prefetch():
            _bump("prefetch_staged")
            with _stats_mu:
                _prefetched_keys.add(key)
        else:
            tracing.note_stage(nbytes=nbytes)
        return
    if not _in_prefetch():
        with _stats_mu:
            if key in _prefetched_keys:
                _prefetched_keys.discard(key)
                _counters["prefetch_hits"] += 1
                credit = True
            else:
                credit = False
        if credit:
            tracing.note_stage(prefetch_hits=1)


def _stage(
    key_base: Tuple,
    n_shards: int,
    build_slice: Callable[[int, int], object],
    shard_axis: int,
    table: Optional[ExtentTable],
    versions: Optional[Tuple[int, ...]] = None,
    shards: Optional[Tuple[int, ...]] = None,
    index: Optional[str] = None,
    parts: bool = False,
) -> object:
    """Assemble one device operand from per-extent cache entries.

    build_slice(lo, hi) -> host ndarray covering shard positions [lo, hi)
    of the stack. Returns the assembled device array — or, with
    `parts=True`, the TUPLE of per-extent device arrays in shard order
    with no assembly at all: the plane-streamed BSI kernels
    (exec/bsistream.py) reduce across the parts inside their one compiled
    program, and the GroupBy cross tally (exec/groupby.py,
    ops/pallas_kernels.py) launches once per part inside its one program;
    a device-side concat of a ~GB operand would re-copy it on every
    staging (2 and 4 GB per GroupBy at 954 shards, ledger PR 31). Every
    extent ends pinned exactly once — ownership goes to `table`
    (released after the plan's dispatch) or is released here when no
    table is given.

    `versions` (one entry per shard position) rides INSIDE each extent's
    cache key as that extent's own span slice: a write to one shard
    re-keys only the covering extent, so a warm stack re-stages exactly
    its dirty slices after a write burst. `shards` (the shard ids by
    position) is registered with the device cache as each entry's
    coverage, which is what invalidate_owner_shard matches against."""
    t_stage0 = time.perf_counter()
    try:
        return _stage_inner(
            key_base, n_shards, build_slice, shard_axis, table,
            versions=versions, shards=shards, index=index, parts=parts,
        )
    finally:
        # staging wall time feeds the flight recorder's per-thread
        # account (prefetch-worker staging is its own concern, not a
        # query's milliseconds)
        if not _in_prefetch():
            tracing.note_stage(seconds=time.perf_counter() - t_stage0)


def _build_and_put(
    build_slice: Callable[[int, int], object], lo: int, hi: int
) -> object:
    """One miss: the host stack of shard positions [lo, hi) built
    (`build_slice`, into a staging buffer when it is `View`'s) and handed
    to the device with the active mesh's placement. The buffer goes back
    to the pool with the array that reads it. The two halves feed the
    query thread's staging account apart (`stage.build_ms`,
    `stage.put_ms`; the put returns before the copy is done, the
    dispatch waits for that)."""
    from pilosa_tpu.parallel import mesh as pmesh

    t0 = time.perf_counter()
    host = build_slice(lo, hi)
    t1 = time.perf_counter()
    arr = pmesh.put_stack(host)
    STAGING.give(host, arr)
    if not _in_prefetch():
        tracing.note_stage(
            build_seconds=t1 - t0,
            put_seconds=time.perf_counter() - t1,
            rows=np.size(host) // WORDS_PER_ROW,
        )
    return arr


def _stage_inner(
    key_base: Tuple,
    n_shards: int,
    build_slice: Callable[[int, int], object],
    shard_axis: int,
    table: Optional[ExtentTable],
    versions: Optional[Tuple[int, ...]] = None,
    shards: Optional[Tuple[int, ...]] = None,
    index: Optional[str] = None,
    parts: bool = False,
) -> object:
    import jax

    from pilosa_tpu.parallel import mesh as pmesh

    rows = _extent_rows
    if pmesh.active_mesh() is not None or rows <= 0 or n_shards <= rows:
        # monolithic: mesh-placed stacks (XLA owns cross-chip layout) and
        # stacks no bigger than one extent. One cache entry covering every
        # shard; still budget-tracked and pin-protected.
        built: List[bool] = []
        key = key_base if versions is None else key_base + ("mono", versions)

        def build_all() -> object:
            built.append(True)
            return _build_and_put(build_slice, 0, n_shards)

        arr = DEVICE_CACHE.get_or_build(
            key, build_all, extent=True, pin=True, shards=shards,
            index=index,
        )
        try:
            _note_upload(
                int(getattr(arr, "nbytes", 0)), key, bool(built), index=index
            )
        except BaseException:
            # accounting must not leak the pin: an unpinned failure
            # leaves the entry evictable instead of wedged forever
            DEVICE_CACHE.unpin(key)
            raise
        if table is not None:
            # transfer: pin moves to the caller's ExtentTable.release()
            table.add([key])
        else:
            DEVICE_CACHE.unpin(key)
        return (arr,) if parts else arr

    spans = [(lo, min(lo + rows, n_shards)) for lo in range(0, n_shards, rows)]
    keys = [
        key_base
        + ("ext", rows, i)
        + (() if versions is None else (versions[lo:hi],))
        for i, (lo, hi) in enumerate(spans)
    ]
    # pass 1: pin every already-resident extent of this operand BEFORE
    # building any missing one — otherwise staging slice k evicts slice
    # k-1 and a cyclic scan re-uploads the whole stack (LRU's classic
    # sequential-scan pathology, i.e. the monolithic cliff all over again)
    resident = [DEVICE_CACHE.pin_if_present(k) for k in keys]
    # `held` tracks EVERY pin this staging owns from the start (incl.
    # pass-1 pins on extents the loop has not reached yet): a build
    # failure mid-loop must release all of them, not just the visited ones
    held: List[Tuple] = [k for k, r in zip(keys, resident) if r]
    out_parts: List[object] = []
    try:
        for (lo, hi), key, was_resident in zip(spans, keys, resident):
            arr = None
            if was_resident:
                arr = DEVICE_CACHE.get(key)
                if arr is None:
                    # invalidated between pin and get (write landed): the
                    # pin now guards a zombie — drop it and rebuild fresh
                    DEVICE_CACHE.unpin(key)
                    held.remove(key)
                    was_resident = False
                else:
                    _note_upload(
                        int(getattr(arr, "nbytes", 0)), key, built=False
                    )
            if arr is None:
                freshly_built: List[bool] = []

                def build(
                    lo: int = lo,
                    hi: int = hi,
                    built: List[bool] = freshly_built,
                ) -> object:
                    built.append(True)
                    return _build_and_put(build_slice, lo, hi)

                arr = DEVICE_CACHE.get_or_build(
                    key, build, extent=True, pin=True,
                    shards=None if shards is None else shards[lo:hi],
                    index=index,
                )
                held.append(key)
                _note_upload(
                    int(getattr(arr, "nbytes", 0)), key, bool(freshly_built),
                    index=index,
                )
            out_parts.append(arr)
    except BaseException:
        DEVICE_CACHE.unpin_all(held)
        raise
    if table is not None:
        # transfer: pins move to the caller's ExtentTable.release()
        table.add(held)
        held = []
    try:
        if parts:
            assembled = tuple(out_parts)
        else:
            assembled = (
                out_parts[0]
                if len(out_parts) == 1
                else jax.numpy.concatenate(out_parts, axis=shard_axis)
            )
    finally:
        # tableless callers keep their pins only for the assembly
        # itself — released even when concatenate raises (an OOM here
        # used to strand every staged extent pinned)
        DEVICE_CACHE.unpin_all(held)
    return assembled


def stage_row_stack(
    key_base: Tuple,
    n_shards: int,
    build_slice: Callable[[int, int], object],
    table: Optional[ExtentTable] = None,
    versions: Optional[Tuple[int, ...]] = None,
    shards: Optional[Tuple[int, ...]] = None,
    index: Optional[str] = None,
    parts: bool = False,
) -> object:
    """uint32[S, W] operand: extents slice axis 0 (the shard axis).
    `index` attributes the staged bytes to their owning index for the
    per-tenant residency/restage telemetry; `parts` skips assembly and
    returns the per-extent arrays (plane-streamed aggregate path)."""
    return _stage(
        key_base, n_shards, build_slice, 0, table,
        versions=versions, shards=shards, index=index, parts=parts,
    )


def stage_plane_stack(
    key_base: Tuple,
    n_shards: int,
    build_slice: Callable[[int, int], object],
    table: Optional[ExtentTable] = None,
    versions: Optional[Tuple[int, ...]] = None,
    shards: Optional[Tuple[int, ...]] = None,
    index: Optional[str] = None,
    parts: bool = False,
) -> object:
    """uint32[D, S, W] operand: extents slice axis 1; every extent carries
    all D planes for its shard range (one slice pages the whole magnitude
    ladder for those shards together — they are always used together).
    `parts` skips assembly and returns the per-extent arrays."""
    return _stage(
        key_base, n_shards, build_slice, 1, table,
        versions=versions, shards=shards, index=index, parts=parts,
    )
