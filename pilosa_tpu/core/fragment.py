"""Fragment: one (index, field, view, shard) slab of bits.

Reference: /root/reference/fragment.go — the unit of storage, locking,
snapshotting and placement ("Fragment=intersection of field & shard",
NOTES:25). This rebuild keeps the same unit but splits responsibilities
TPU-style:

- host side: sparse-or-dense RowBits per row (core/rowstore.py), WAL +
  snapshot persistence (core/wal.py), mutex vector for mutex fields
  (fragment.go:670), op counting with MaxOpN snapshot triggering
  (fragment.go:84,2296).
- device side: per-row dense uint32 blocks cached in HBM; all query math
  (row algebra, BSI ladders, counts) happens there via ops/bitmap.py and
  ops/bsi.py. Host bitmap math never serves a query — the host store is the
  mutable/durable representation only.

Position convention matches fragment.go:3090:
    pos = row_id * SHARD_WIDTH + (col % SHARD_WIDTH).
"""

from __future__ import annotations

import contextlib
import os
import time
from itertools import repeat
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import numpy as np

from pilosa_tpu.utils import resources
from pilosa_tpu.utils.locks import TrackedRLock
from pilosa_tpu.utils.race import race_checked
from pilosa_tpu.core import cache as cachemod
from pilosa_tpu.core import wal as walmod
from pilosa_tpu.core.devcache import DEVICE_CACHE, new_owner_token
from pilosa_tpu.core import merge as merge_mod
from pilosa_tpu.core import rowstore as rowstore_mod
from pilosa_tpu.core.rowstore import RowBits
from pilosa_tpu.utils.arrays import group_slices
from pilosa_tpu.ops import bitmap as ob
from pilosa_tpu.ops import bsi as obsi
from pilosa_tpu.shardwidth import SHARD_WIDTH, SHARD_WIDTH_EXPONENT

# Reference: fragment.go:84 — ops between snapshots.
DEFAULT_MAX_OP_N = 10_000

# BSI plane rows (reference: fragment.go:88-96).
BSI_EXISTS_BIT = 0
BSI_SIGN_BIT = 1
BSI_OFFSET_BIT = 2

# Live-transfer write capture (streaming resize): a capture that grows past
# this many positions is dropped and marked LOST — the destination refetches
# the full snapshot instead of this node buffering an unbounded delta for a
# transfer whose driver may have died.
CAPTURE_MAX_POSITIONS = 1 << 22  # ~32 MB of uint64 positions


class TransferCaptureLost(Exception):
    """The write capture backing an in-flight fragment transfer is gone
    (overflowed, replaced wholesale, or never started): the destination
    must restart from a fresh full snapshot (HTTP 410 on the delta
    endpoint), not treat the delta stream as complete."""


class TransferCutover(Exception):
    """This fragment is inside its resize-cutover write barrier: the
    coordinator quiesced it so the final capture drain is provably
    complete before the topology install. Writes are rejected with a
    retryable error (HTTP 503 + Retry-After) for the barrier's bounded
    window — the internode retry plane re-maps and lands them on the
    post-cutover owner."""


# Lazy host snapshot tier: fragments open by indexing the snapshot headers
# only, materializing RowBits from seek-reads on first access — holder
# open is O(rows), untouched rows stay on disk in the page cache (the
# host analog of the reference's zero-copy mmap storage, fragment.go:311
# + syswrap). PILOSA_TPU_LAZY_SNAPSHOTS=0 forces eager loads.
_LAZY_SNAPSHOTS = os.environ.get("PILOSA_TPU_LAZY_SNAPSHOTS", "1") in ("1", "true")


class _LazyRows:
    """MutableMapping-shaped row store over an on-disk snapshot.

    Materialized rows (mutated or read) live in `_mat` and take precedence;
    everything else is served by seeking into the snapshot file on demand
    (open-per-access: no fd is held between reads, so thousands of lazy
    fragments cost zero resident fds — the page cache keeps repeat reads
    cheap). After snapshot() rewrites the file, rebase() re-indexes against
    the new file while keeping materialized rows (they are the
    authoritative, identical state that was just written)."""

    __slots__ = ("n_bits", "path", "_mat", "_index", "_bulk_f")

    def __init__(self, path: str, expect_n_bits: int):
        _, n_bits, index = walmod.read_snapshot_index(path)
        if n_bits != expect_n_bits:
            raise ValueError(
                f"{path}: snapshot width {n_bits} != configured "
                f"SHARD_WIDTH {expect_n_bits}"
            )
        self.n_bits = n_bits
        self.path = path
        self._mat: Dict[int, RowBits] = {}
        self._index = index
        self._bulk_f = None  # shared fd during bulk() scans

    @contextlib.contextmanager
    def bulk(self):
        """Context manager holding ONE fd across a bulk scan (snapshot
        writes, cache rebuilds): per-row open/close would cost ~4 syscalls
        per row under the fragment lock."""
        if self._bulk_f is not None:  # nested: reuse
            yield
            return
        with open(self.path, "rb") as f:
            self._bulk_f = f
            try:
                yield
            finally:
                self._bulk_f = None

    def _read_payload(self, off: int, n: int) -> np.ndarray:
        f = self._bulk_f
        if f is not None:
            f.seek(off)
            data = f.read(n * 4)
        else:
            with open(self.path, "rb") as f2:
                f2.seek(off)
                data = f2.read(n * 4)
        if len(data) != n * 4:
            raise ValueError(f"{self.path}: truncated payload at {off}")
        return np.frombuffer(data, dtype="<u4")

    # -- mapping protocol --------------------------------------------------

    def __getitem__(self, row_id: int) -> RowBits:
        rb = self._mat.get(row_id)
        if rb is None:
            meta = self._index.get(row_id)
            if meta is None:
                raise KeyError(row_id)
            rep, off, n = meta
            payload = self._read_payload(off, n)
            rb = self._mat[row_id] = RowBits.from_payload(self.n_bits, rep, payload)
        return rb

    def get(self, row_id: int, default=None):
        if row_id in self._mat or row_id in self._index:
            return self[row_id]
        return default

    def __setitem__(self, row_id: int, rb: RowBits) -> None:
        self._mat[row_id] = rb

    def __delitem__(self, row_id: int) -> None:
        found = self._mat.pop(row_id, None) is not None
        found = self._index.pop(row_id, None) is not None or found
        if not found:
            raise KeyError(row_id)

    def __contains__(self, row_id) -> bool:
        return row_id in self._mat or row_id in self._index

    def __iter__(self):
        return iter(self._mat.keys() | self._index.keys())

    def __len__(self) -> int:
        return len(self._mat.keys() | self._index.keys())

    def __bool__(self) -> bool:
        return bool(self._mat) or bool(self._index)

    def items(self):
        for row_id in self:
            yield row_id, self[row_id]

    def values(self):
        for row_id in self:
            yield self[row_id]

    def keys(self):
        return self._mat.keys() | self._index.keys()

    # -- lazy-aware accessors ----------------------------------------------

    def count_of(self, row_id: int) -> int:
        """Row cardinality WITHOUT materializing: array reps know it from
        the header; dense reps popcount the mapped payload (page cache,
        no resident RowBits)."""
        rb = self._mat.get(row_id)
        if rb is not None:
            return rb.count()
        meta = self._index.get(row_id)
        if meta is None:
            return 0
        rep, off, n = meta
        if rep == rowstore_mod.ARRAY_REP:
            return n
        return rowstore_mod._popcount_words(self._read_payload(off, n))

    def rep_payload(self, row_id: int) -> Tuple[int, np.ndarray]:
        """(rep, payload) for snapshot writing, without materializing."""
        rb = self._mat.get(row_id)
        if rb is not None:
            return rb.rep(), rb.payload()
        rep, off, n = self._index[row_id]
        return rep, self._read_payload(off, n)

    def rebase(self, path: str) -> None:
        """Point unmaterialized rows at a freshly written snapshot file.
        Materialized rows may appear in both _mat and _index afterwards —
        that is fine: iteration/len use the key-set union and __getitem__
        prefers _mat, whose content is identical to what was written."""
        self.path = path
        _, _, self._index = walmod.read_snapshot_index(path)


@race_checked(exclude=(
    # version is read lock-free by design across the codebase: extent/
    # stack cache keys are version-salted, and a torn read only yields a
    # stale key that the next barrier invalidates (monotonic int, GIL-
    # atomic). on_mutate is installed once by the owning View at
    # registration, before concurrent writers exist for that view.
    "version",
    "on_mutate",
    # staged-delta counters are SNAPSHOT-read lock-free by design: the
    # merge barrier's phase-1 peek (core/merge.py merge_barrier), the
    # admission cost estimator's staged surcharge (sched/cost.py) and
    # holder.staged_position_count() all read these GIL-atomic ints
    # without the fragment lock — every consumer that ACTS on them
    # revalidates under the lock via the pending_snapshot/_pending_gen
    # handshake, so a stale peek costs one wasted pass, never a wrong
    # answer. Writes stay under _mu (LOCK004 enforces that statically).
    "_pending_n",
    "_premerged_n",
))
class Fragment:
    """One shard of one view of one field.

    Thread-safety: a single re-entrant lock guards host structures (the
    reference uses fragment.mu the same way, fragment.go:100-159).
    """

    def __init__(
        self,
        path: Optional[str],
        index: str,
        field: str,
        view: str,
        shard: int,
        *,
        mutex: bool = False,
        max_op_n: int = DEFAULT_MAX_OP_N,
        cache_type: str = cachemod.CACHE_TYPE_RANKED,
        cache_size: int = cachemod.DEFAULT_CACHE_SIZE,
    ):
        self.path = path  # None => purely in-memory (test harness)
        self.index = index
        self.field = field
        self.view = view
        self.shard = shard
        self.mutex = mutex
        self.max_op_n = max_op_n
        # row-rank cache for TopN (reference: fragment.go:131 f.cache)
        self.cache = cachemod.make_cache(cache_type, cache_size)
        self._cache_top_arrays = None  # memoized (top, rids, cnts)
        self._cache_id_arrays = None  # memoized id-sorted (top, rids, cnts)

        self._mu = TrackedRLock("fragment.mu")
        self._rows: Dict[int, RowBits] = {}
        # Bulk-ingest fast path (stage_positions): SET positions appended
        # here are already WAL-framed and device-invalidated but not yet
        # merged into _rows; every read barrier merges them first
        # (_sync_locked) in one vectorized pass. len bookkeeping lives in
        # _pending_n so the hot check is one int compare.
        self._pending: List[np.ndarray] = []
        self._pending_n = 0
        # Cross-fragment merge handshake (core/merge.py): `_pending_gen`
        # bumps whenever pending parts are consumed (per-fragment
        # _sync_locked, batched apply_merged_delta, from_bytes reset) so
        # a barrier that snapshotted parts can tell whether a concurrent
        # path already merged them; `_staged_base_version` is the
        # mutation version just BEFORE the first un-merged staged batch
        # (each staged batch bumps version by exactly one), which is the
        # version a resident extent must be keyed at for the in-place
        # patch to be exact.
        self._pending_gen = 0
        self._staged_base_version = 0
        # Pre-merged delta layers (core/merge.py barrier outcome): each
        # is the fragment's slice of one burst's globally sorted+deduped
        # staged positions, NOT yet materialized into RowBits. The
        # barrier pays O(burst) only — the device stays exact via
        # in-place extent patches built from the same merged delta —
        # and the host row store catches up at the next HOST read:
        # every host read funnels through _sync_locked, which folds the
        # layers into the one vectorized merge pass it already runs for
        # raw pending parts (layers and pending share the row-major
        # uint64 key format). Bounded by _LAYER_CAP.
        self._premerged: List[np.ndarray] = []
        self._premerged_n = 0
        # Device residency goes through the process-global budgeted LRU
        # (core/devcache.py): per-row arrays under _token, multi-row stacks
        # under _stack_token (stacks are invalidated wholesale on mutation).
        self._token = new_owner_token()
        self._stack_token = new_owner_token()
        # Monotonic mutation counter; cross-fragment caches (view row stacks)
        # validate against it.
        self.version = 0
        self._wal: Optional[walmod.WalWriter] = None
        self._op_n = 0
        # mutex fields: col -> owning row (reference keeps a mutex vector,
        # fragment.go:670 handleMutex)
        self._mutex_map: Optional[Dict[int, int]] = {} if mutex else None
        # optional owner hook fired after any mutation (the View registers
        # one to drop its cross-shard stacks covering this fragment)
        self.on_mutate = None
        # Live-transfer write captures (streaming resize): while transfers
        # are in flight, every mutation funnel appends its records to each
        # armed capture (the same (op, positions) shape the WAL frames) so
        # destinations can replay exactly the writes that landed after
        # their snapshots. NAMED per transfer tag: at replica_n > 1 two
        # destinations stream the same source fragment concurrently, and
        # each must see the full delta — a shared buffer would let one
        # drain steal records the other never gets.
        self._captures: Dict[str, List[Tuple[int, np.ndarray]]] = {}
        self._capture_ns: Dict[str, int] = {}
        self._captures_lost: set = set()
        # resize-cutover write barrier: monotonic deadline; 0 = open. The
        # deadline (not a bool) makes the barrier self-expiring, so a lost
        # resize-release can never block a fragment's writes forever.
        self._write_block_until = 0.0
        self._open = False

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    @property
    def snap_path(self) -> Optional[str]:
        return None if self.path is None else self.path + ".snap"

    @property
    def wal_path(self) -> Optional[str]:
        return None if self.path is None else self.path + ".wal"

    @property
    def cache_path(self) -> Optional[str]:
        return None if self.path is None else self.path + ".cache"

    def open(self) -> "Fragment":
        with self._mu:
            if self._open:
                return self
            replayed = 0
            if self.path is not None:
                os.makedirs(os.path.dirname(self.path), exist_ok=True)
                if os.path.exists(self.snap_path):
                    # mutex fields load eagerly: rebuilding the col->row
                    # mutex vector needs every bit anyway, so laziness
                    # would only add indexing overhead
                    if _LAZY_SNAPSHOTS and self._mutex_map is None:
                        self._rows = _LazyRows(self.snap_path, SHARD_WIDTH)
                    else:
                        _, n_bits, rows = walmod.read_snapshot(self.snap_path)
                        if n_bits != SHARD_WIDTH:
                            raise ValueError(
                                f"{self.snap_path}: snapshot width {n_bits} != "
                                f"configured SHARD_WIDTH {SHARD_WIDTH}"
                            )
                        self._rows = rows
                for op, positions in walmod.replay_wal(self.wal_path):
                    if op == walmod.OP_ROW_WORDS:
                        # commutes with staged SETs (both only set bits):
                        # no flush needed before the word union
                        self._apply_row_words(
                            int(positions[0]),
                            np.ascontiguousarray(positions[1:]).view(np.uint32),
                        )
                    elif op == walmod.OP_SET and self._mutex_map is None:
                        # replay fast path: staged OP_SET frames are
                        # already durable (they ARE the WAL), so they
                        # re-stage straight into the pending buffer and
                        # land via ONE deferred merge at the first read
                        # barrier instead of one exact apply per frame
                        if not self._pending:
                            self._staged_base_version = self.version
                        self._pending.append(
                            positions.astype(np.uint64, copy=False)
                        )
                        self._pending_n += len(positions)
                        self.version += 1
                    else:
                        # clears do not commute with staged sets: merge
                        # the pending prefix first so replay order holds
                        self._sync_locked()
                        self._apply_positions(
                            positions if op == walmod.OP_SET else np.empty(0, np.uint64),
                            positions if op == walmod.OP_CLEAR else np.empty(0, np.uint64),
                        )
                    self._op_n += len(positions)
                    replayed += 1
                if self._pending:
                    # land the whole staged replay suffix as ONE deferred
                    # merge (the fast path's contract: N staged frames,
                    # one vectorized pass) so open() returns a fully
                    # merged fragment — the rank-cache rebuild below
                    # reads _rows directly
                    self._sync_locked()
                self._wal = walmod.WalWriter(self.wal_path)
            if self._mutex_map is not None:
                self._rebuild_mutex_map()
            if self.cache.cache_type != cachemod.CACHE_TYPE_NONE:
                # The .cache sidecar is only trusted when no WAL ops were
                # replayed: snapshot() and close() flush it, so replayed
                # records mean mutations landed after the last flush and
                # the sidecar is stale. Counts are exact host metadata, so
                # the rebuild is always available.
                loaded = (
                    replayed == 0
                    and self.cache_path is not None
                    and cachemod.read_cache(self.cache_path, self.cache)
                )
                if not loaded and self._rows:
                    self.recalculate_cache()
            self._open = True
            return self

    def close(self) -> None:
        with self._mu:
            if self._wal is not None:
                self._wal.close()
                self._wal = None
            self.flush_cache()
            DEVICE_CACHE.invalidate_owner(self._token)
            DEVICE_CACHE.invalidate_owner(self._stack_token)
            self._open = False

    def flush_cache(self) -> None:
        """Persist the rank cache sidecar (reference: holder.go:506
        monitorCacheFlush ticker / cache.go:291 WriteTo)."""
        with self._mu:
            self._sync_locked()
            if (
                self.cache_path is not None
                and self.cache.cache_type != cachemod.CACHE_TYPE_NONE
            ):
                cachemod.write_cache(self.cache_path, self.cache)

    def recalculate_cache(self) -> None:
        """Rebuild the cache from exact per-row counts
        (reference: api.go RecalculateCaches). Lazy stores count from the
        header index / mapped payloads without materializing rows."""
        with self._mu:
            self._sync_locked()
            self.cache.clear()
            count_of = getattr(self._rows, "count_of", None)
            if count_of is not None:
                bulk = getattr(self._rows, "bulk", None)
                with bulk() if bulk is not None else contextlib.nullcontext():
                    self.cache.bulk_add(
                        (rid, count_of(rid)) for rid in self._rows
                    )
            else:
                self.cache.bulk_add(
                    (row_id, rb.count()) for row_id, rb in self._rows.items()
                )

    def _rebuild_mutex_map(self) -> None:  # guarded-by: _mu
        self._mutex_map = {}
        for row_id, rb in self._rows.items():
            for p in rb.to_positions():
                self._mutex_map[int(p)] = row_id

    # ------------------------------------------------------------------
    # reads (host metadata; bit math lives on device)
    # ------------------------------------------------------------------

    def row_ids(self) -> List[int]:
        with self._mu:
            self._sync_locked()
            return sorted(self._rows)

    def has_row(self, row_id: int) -> bool:
        with self._mu:
            self._sync_locked()
            return row_id in self._rows

    def max_row_id(self) -> Optional[int]:
        with self._mu:
            self._sync_locked()
            return max(self._rows) if self._rows else None

    def min_row_id(self) -> Optional[int]:
        with self._mu:
            self._sync_locked()
            return min(self._rows) if self._rows else None

    def row_words(self, row_id: int) -> np.ndarray:
        """Host dense words for one row (zeros if absent)."""
        with self._mu:
            self._sync_locked()
            rb = self._rows.get(row_id)
            return rb.to_words() if rb is not None else ob.empty_row()

    def fill_row_words(self, row_id: int, out: np.ndarray) -> None:
        """`row_words` into the caller's uint32[W] row (zeros if absent):
        what a stack build calls once per shard of its staging buffer."""
        with self._mu:
            self._sync_locked()
            rb = self._rows.get(row_id)
            if rb is not None:
                rb.write_words(out)
            else:
                out.fill(0)

    def row_positions(self, row_id: int) -> np.ndarray:
        with self._mu:
            self._sync_locked()
            rb = self._rows.get(row_id)
            return rb.to_positions() if rb is not None else np.empty(0, np.uint32)

    def premerge_row_words(self, row_id: int) -> np.ndarray:
        """Host words of one row at the STAGED-BASE version: the raw row
        store plus parked pre-merged layers, with pending parts excluded
        (no read barrier runs — this is NOT a host read). The merge
        barrier calls it just before parking a burst's delta layer so
        the result cache's count repair has `old_words` for
        count(new) = count(old) + popcount(delta & ~old_words), which is
        only exact against content at the burst's base version."""
        with self._mu:
            rb = self._rows.get(row_id)
            words = np.array(
                rb.to_words() if rb is not None else ob.empty_row(),
                dtype=np.uint32,
                copy=True,
            )
            lo = np.uint64(row_id) * np.uint64(SHARD_WIDTH)
            for layer in self._premerged:
                s, e = np.searchsorted(
                    layer, (lo, lo + np.uint64(SHARD_WIDTH))
                )
                if e > s:
                    cols = (layer[s:e] - lo).astype(np.uint32)
                    np.bitwise_or.at(
                        words,
                        cols >> np.uint32(5),
                        np.left_shift(np.uint32(1), cols & np.uint32(31)),
                    )
            return words

    def rows_sparse_concat(self, row_ids) -> Tuple[np.ndarray, np.ndarray]:
        """One-lock bulk sparse read for the TopN tally: concatenated
        sorted bit positions of the listed rows plus per-row lengths;
        length -1 marks a dense-rep row (the caller routes those through
        the plane path instead of gathering individual words)."""
        with self._mu:
            self._sync_locked()
            rows = self._rows
            parts = []
            lens = np.empty(len(row_ids), np.int64)
            for i, rid in enumerate(row_ids):
                rb = rows.get(rid)
                if rb is None:
                    lens[i] = 0
                elif rb.dense is not None:
                    lens[i] = -1
                else:
                    p = rb.positions
                    lens[i] = len(p)
                    if len(p):
                        parts.append(p)
            cat = np.concatenate(parts) if parts else np.empty(0, np.uint32)
            return cat, lens

    def row_device(self, row_id: int) -> jax.Array:
        """Device-resident dense row; cached (budgeted LRU) until the row
        mutates."""
        with self._mu:
            return DEVICE_CACHE.get_or_build(
                (self._token, row_id),
                lambda: jax.device_put(self.row_words(row_id)),
                index=self.index,
            )

    def rows_device(self, row_ids: Iterable[int]) -> jax.Array:
        """Stacked [k, W] device matrix for the given rows; the stack is
        cached as one budgeted entry (one transfer, not k)."""
        ids = tuple(row_ids)
        with self._mu:
            return DEVICE_CACHE.get_or_build(
                (self._stack_token, ids),
                lambda: jax.device_put(
                    np.stack([self.row_words(r) for r in ids])
                    if ids
                    else np.empty((0, SHARD_WIDTH // 32), np.uint32)
                ),
                index=self.index,
            )

    def contains(self, row_id: int, col: int) -> bool:
        with self._mu:
            self._sync_locked()
            rb = self._rows.get(row_id)
            return rb is not None and rb.contains(col % SHARD_WIDTH)

    def row_count(self, row_id: int) -> int:
        """Cardinality of one row (host metadata; used by caches/imports).
        Lazy stores answer from header metadata without materializing."""
        with self._mu:
            self._sync_locked()
            count_of = getattr(self._rows, "count_of", None)
            if count_of is not None:
                return count_of(row_id)
            rb = self._rows.get(row_id)
            return rb.count() if rb is not None else 0

    def cache_top(self):
        """Rank-cache snapshot taken under the fragment lock, so a concurrent
        writer mutating the cache in _apply_positions can't tear the read."""
        with self._mu:
            self._sync_locked()
            return self.cache.top()

    def cache_top_arrays(self):
        """(row_ids uint64[], counts uint64[]) of the rank cache in rank
        order, memoized against the cache's own top() snapshot — the
        vectorized TopN paths read these instead of building 10^4s of
        Python tuples per query."""
        with self._mu:
            self._sync_locked()
            t = self.cache.top()
            memo = self._cache_top_arrays
            if memo is None or memo[0] is not t:
                n = len(t)
                rids = np.fromiter((p[0] for p in t), np.uint64, n)
                cnts = np.fromiter((p[1] for p in t), np.uint64, n)
                memo = self._cache_top_arrays = (t, rids, cnts)
            return memo[1], memo[2]

    def cache_counts_exact(self, row_ids: np.ndarray) -> Optional[np.ndarray]:
        """uint64 cardinalities for row_ids straight from the rank cache,
        or None unless the cache is provably complete (never pruned for
        capacity): every write path maintains cache.add with the exact
        count and open rebuilds from exact counts, so an unpruned cache
        IS the full row->count map. Saves TopN pass-2's O(rows x shards)
        count() walk; pruned caches fall back to row_counts_host."""
        with self._mu:
            self._sync_locked()
            cache = self.cache
            t = cache.top() if hasattr(cache, "top") else []
            if getattr(cache, "pruned", True):
                return None  # checked AFTER top(): recalculate may prune
            memo = self._cache_id_arrays
            if memo is None or memo[0] is not t:
                # reuse the rank-order memo (pass 1 builds it) instead of
                # re-iterating the tuple list
                rids, cnts = self.cache_top_arrays()
                o = np.argsort(rids)
                memo = self._cache_id_arrays = (t, rids[o], cnts[o])
            _, rs, cs = memo
            ids = np.asarray(row_ids, np.uint64)
            if not len(rs):
                return np.zeros(len(ids), np.uint64)
            pos = np.searchsorted(rs, ids)
            posc = np.minimum(pos, len(rs) - 1)
            found = (pos < len(rs)) & (rs[posc] == ids)
            return np.where(found, cs[posc], 0).astype(np.uint64)

    def row_summary(self) -> Tuple[int, np.ndarray, np.ndarray, bool]:
        """(version, row ids, cardinalities, exact) of every non-empty row,
        count descending with ties by lowest id, all read under one lock
        acquisition so the cells belong to exactly that version — the
        view's row summary (core/rowsummary.py) keeps them until the
        version moves. `exact` is cache_counts_exact's condition for a
        ranked cache: never pruned, so the cells ARE the rank cache in
        rank order and TopN may select over them; otherwise the cells
        come from the row store and serve Rows only."""
        with self._mu:
            self._sync_locked()
            if self.cache.cache_type == cachemod.CACHE_TYPE_RANKED:
                rids, cnts = self.cache_top_arrays()
                if not self.cache.pruned:  # checked AFTER top(): it may prune
                    return self.version, rids, cnts, True
            ids = list(self._rows)
            rids = np.asarray(ids, np.uint64)
            cnts = self.row_counts_host(ids)
            order = np.lexsort((rids, -cnts.astype(np.int64)))
            order = order[cnts[order] > 0]
            return self.version, rids[order], cnts[order], False

    def row_counts_host(self, row_ids) -> np.ndarray:
        """Cardinalities of the listed rows as one uint64 vector under one
        lock acquisition (TopN pass-2 reads n_shards x n_candidates counts;
        per-call locking would dominate)."""
        with self._mu:
            self._sync_locked()
            rows = self._rows
            count_of = getattr(rows, "count_of", None)
            if count_of is not None:
                return np.fromiter(
                    (count_of(r) for r in row_ids), np.uint64, len(row_ids)
                )
            return np.fromiter(
                (rb.count() if (rb := rows.get(r)) is not None else 0 for r in row_ids),
                np.uint64,
                len(row_ids),
            )

    # ------------------------------------------------------------------
    # writes — everything funnels through import_positions
    # ------------------------------------------------------------------

    def set_bit(self, row_id: int, col: int) -> bool:
        """Set one bit; col is the in-shard position OR an absolute column
        belonging to this shard. Returns True if it changed.
        (reference: fragment.go:647 setBit)"""
        pos = self._pos(row_id, col)
        if self._mutex_map is not None:
            return self._set_bit_mutex(row_id, col % SHARD_WIDTH)
        changed, _ = self.import_positions(np.array([pos], np.uint64), None)
        return changed > 0

    def clear_bit(self, row_id: int, col: int) -> bool:
        pos = self._pos(row_id, col)
        _, cleared = self.import_positions(None, np.array([pos], np.uint64))
        return cleared > 0

    def _set_bit_mutex(self, row_id: int, in_shard: int) -> bool:
        # the barrier defers import_positions' group-commit wait past the
        # `with self._mu` below: a strict-mode fsync round must never run
        # WITH the fragment lock held (it would serialize every reader
        # and writer of this fragment behind disk latency and defeat the
        # cross-caller coalescing)
        with walmod.GROUP_COMMIT.barrier():
            with self._mu:
                existing = self._mutex_map.get(in_shard)
                if existing == row_id:
                    return False
                to_clear = None
                if existing is not None:
                    to_clear = np.array(
                        [existing * SHARD_WIDTH + in_shard], np.uint64
                    )
                to_set = np.array([row_id * SHARD_WIDTH + in_shard], np.uint64)
                changed, _ = self.import_positions(to_set, to_clear)
                self._mutex_map[in_shard] = row_id
        return changed > 0

    def import_positions(
        self, to_set: Optional[np.ndarray], to_clear: Optional[np.ndarray]
    ) -> Tuple[int, int]:
        """Batched bit mutation by fragment position; the single EXACT
        write path (reference: fragment.go:2053 importPositions) — the
        pending ingest delta is merged first so the returned
        (n_set_changed, n_clear_changed) counts are exact. WAL framing is
        one append per import call: set+clear land as one write+flush
        instead of interleaving two syscall round-trips with the apply.
        Durability is a GROUP COMMIT: the fsync wait happens after the
        fragment lock is released, so concurrent importers coalesce into
        one commit round instead of serializing fsyncs behind each
        other's locks (strict mode; `wal-sync-interval` > 0 acks on the
        buffered write and defers the fsync to the background cadence)."""
        tok = None
        with self._mu:
            self._check_write_block_locked()
            self._sync_locked()
            records = []
            if to_set is not None and len(to_set):
                records.append((walmod.OP_SET, to_set))
            if to_clear is not None and len(to_clear):
                records.append((walmod.OP_CLEAR, to_clear))
            if records and self._wal is not None:
                tok = self._wal.append_many(records)
            for op, positions in records:
                self._capture_record(op, positions)
            n_set, n_clear = self._apply_positions(
                to_set if to_set is not None else np.empty(0, np.uint64),
                to_clear if to_clear is not None else np.empty(0, np.uint64),
            )
            self._op_n += n_set + n_clear
            if self._op_n > self.max_op_n:
                self.snapshot()
                tok = None  # snapshot fsynced + truncated: already durable
        if tok is not None:
            walmod.GROUP_COMMIT.wait_durable(tok)
        return n_set, n_clear

    def stage_positions(self, positions: np.ndarray, *, notify: bool = True) -> int:
        """Bulk-ingest fast path: append SET positions to the fragment's
        pending delta buffer WITHOUT merging them into the row store —
        the merge (one vectorized pass + a single rank-cache
        reconciliation, however many batches accumulated) is deferred to
        the next read barrier (_sync_locked). Durability is NOT deferred:
        the batch is WAL-framed here, so a crash before the merge replays
        it on open. Returns the number of staged positions (an upper
        bound on changed bits; exact change counts exist only at merge
        time — callers needing them use import_positions).

        notify=False skips the per-fragment device-cache invalidation and
        the on_mutate hook (the version still bumps): the field-level
        bulk router batches those into one device-cache pass for ALL
        fragments it touched, instead of two global-lock hits per shard.

        Mutex fields cannot take this path (last-write-wins needs the
        mutex vector consulted at apply time)."""
        positions = np.asarray(positions, dtype=np.uint64)
        n = len(positions)
        with self._mu:
            # mutex-ness never changes after construction, but the map
            # itself is guarded state: check under the lock (LOCK005) —
            # and BEFORE the empty-batch return, so misrouting a mutex
            # field through the staging path raises on every call, not
            # only on non-empty batches
            if self._mutex_map is not None:
                raise ValueError(
                    "stage_positions is not supported on mutex fields"
                )
            if not n:
                return 0
            self._check_write_block_locked()
            tok = self._wal_append(walmod.OP_SET, positions)
            self._capture_record(walmod.OP_SET, positions)
            if not self._pending:
                self._staged_base_version = self.version
            self._pending.append(positions)
            self._pending_n += n
            self._op_n += n
            self.version += 1
            if notify:
                DEVICE_CACHE.invalidate_owner(self._token)
                DEVICE_CACHE.invalidate_owner(self._stack_token)
                if self.on_mutate is not None:
                    self.on_mutate()
            if self._op_n > self.max_op_n:
                self.snapshot()  # merges pending first (snapshot reads rows)
                tok = None  # snapshot fsynced + truncated: already durable
        if tok is not None:
            # group-commit durability wait OUTSIDE the fragment lock:
            # View.stage_bulk wraps its whole per-shard loop in a
            # GROUP_COMMIT.barrier(), so a bulk import pays ONE commit
            # round however many fragments it staged
            walmod.GROUP_COMMIT.wait_durable(tok)
        return n

    def _sync_locked(self) -> None:
        """Merge the pending ingest delta into the row store. Called (under
        self._mu) at the top of every host read; device rebuild paths all
        funnel through row_words, so a staged-then-queried fragment is
        merged exactly once, not per row. Device invalidation and version
        bumps already happened at stage time — this only moves bits and
        reconciles the rank cache. Pre-merged barrier layers fold into
        the same single pass (they are already sorted/deduped row-major
        keys, the exact format of a raw pending part)."""
        if not self._pending_n and not self._premerged:
            return
        if self._pending:
            # parked layers were already booked at their barrier
            merge_mod.note_host_sync(len(self._pending))
        parts = self._premerged + self._pending
        self._premerged = []
        self._premerged_n = 0
        self._pending = []
        self._pending_n = 0
        self._pending_gen += 1  # a barrier's snapshot of `parts` is stale now
        inc = parts[0] if len(parts) == 1 else np.concatenate(parts)
        touched: set = set()
        self._bulk_set_sparse(inc, touched)
        rows_store = self._rows
        self.cache.add_many(
            (rid, rb.count() if (rb := rows_store.get(rid)) is not None else 0)
            for rid in touched
        )
        if rowstore_mod.PARANOIA:
            self._paranoia_check(touched)

    # -- cross-fragment merge barrier handshake (core/merge.py) --------

    def sync_pending_now(self) -> None:
        """Force the per-fragment merge (the barrier's fallback when key
        packing would overflow, and the bench's per-fragment baseline)."""
        with self._mu:
            self._sync_locked()

    def pending_snapshot(self):
        """Barrier phase 1: (parts, n_parts, gen, base_version) of the
        CURRENT pending delta, or None when there is nothing staged.
        `parts` is a copy of the list (the arrays are shared — staged
        buffers are append-only); nothing is popped, so a concurrent
        per-fragment read barrier stays exact."""
        with self._mu:
            if not self._pending:
                return None
            return (
                list(self._pending),
                len(self._pending),
                self._pending_gen,
                self._staged_base_version,
            )

    # Parked pre-merged layers above this many total keys fold into the
    # row store inline at the barrier instead of lazily at the next
    # host read: the layers pin the barriers' shared merged buffers,
    # and a fragment nobody host-reads must not accumulate them
    # without bound.
    _LAYER_CAP = 1 << 20

    def apply_merged_delta(
        self,
        keys_local: np.ndarray,
        n_parts: int,
        captured_n: int,
        gen: int,
    ) -> Optional[int]:
        """Barrier phase 2: accept the burst's merged delta —
        `keys_local` is this fragment's slice of the globally
        sorted+deduped staged positions (row-major uint64 keys, the
        same format as a raw pending part) covering exactly the first
        `n_parts` pending batches — trim those batches and PARK the
        layer. Returns the fragment's current version, or None when
        `gen` is stale (a concurrent `_sync_locked` already merged the
        captured parts, so applying again would only redo finished
        work).

        Materialization into RowBits is DEFERRED to the fragment's
        next HOST read: `_sync_locked` folds parked layers into the
        one vectorized merge pass it already runs — the contract that
        already ordered staged deltas before row reads. The device
        path needs no host rows at all (resident extents are patched
        in place with this same merged delta), so a barrier under
        sustained device-served load pays O(burst), never a row-store
        rewrite. WAL durability is untouched — the staged frames stay
        on disk until a snapshot, and a crash replays them into
        pending as before."""
        with self._mu:
            if gen != self._pending_gen:
                return None
            # crash-matrix injection point: a kill here leaves every
            # staged WAL frame on disk (merges never truncate), so
            # restart replay rebuilds the exact pre-install state
            walmod.fault_point("merge.install", self.path or "")
            del self._pending[:n_parts]
            self._pending_n -= captured_n
            self._pending_gen += 1
            self._staged_base_version += n_parts
            self._premerged.append(keys_local)
            self._premerged_n += len(keys_local)
            if self._premerged_n > self._LAYER_CAP:
                self._sync_locked()  # bound the parked-layer debt
            return self.version

    def _apply_positions(  # guarded-by: _mu (every mutation funnel holds it)
        self, to_set: np.ndarray, to_clear: np.ndarray
    ) -> Tuple[int, int]:
        # The single EXACT mutation funnel: every write path (including WAL
        # replay, clears from Store/ClearRow, bulk clear imports) flows
        # through here or through _sync_locked, so the mutex vector and the
        # rank cache are maintained here and nowhere else. Per-row Python
        # work is limited to the row-store handoff: set/clear merges are one
        # sort + group_slices pass each, the mutex vector updates at
        # C speed (dict.update over a zip), and the rank-cache/device-cache
        # reconciliation is a single deferred pass per batch instead of two
        # pokes per touched row.
        n_set = n_clear = 0
        touched: set = set()

        if len(to_set):
            if self._mutex_map is None:
                n_set += self._bulk_set_sparse(to_set, touched)
            else:
                rows = (to_set // SHARD_WIDTH).astype(np.int64)
                cols = (to_set % SHARD_WIDTH).astype(np.uint32)
                for row_id, sl in group_slices(rows):
                    row_id = int(row_id)
                    rb = self._rows.get(row_id)
                    if rb is None:
                        rb = self._rows[row_id] = RowBits(SHARD_WIDTH)
                    row_cols = cols[sl]
                    n_set += rb.add(row_cols)
                    touched.add(row_id)
                    self._mutex_map.update(
                        zip(row_cols.tolist(), repeat(row_id))
                    )
        if len(to_clear):
            n_clear += self._bulk_clear_sparse(to_clear, touched)
            if self._mutex_map is not None:
                mm = self._mutex_map
                rows = (to_clear // SHARD_WIDTH).astype(np.int64)
                cols = (to_clear % SHARD_WIDTH).astype(np.uint32)
                for row_id, sl in group_slices(rows):
                    row_id = int(row_id)
                    for c in cols[sl].tolist():
                        if mm.get(c) == row_id:
                            del mm[c]
        if touched:
            rows_store = self._rows
            self.cache.add_many(
                (
                    rid,
                    rb.count() if (rb := rows_store.get(rid)) is not None else 0,
                )
                for rid in touched
            )
            DEVICE_CACHE.invalidate_many(
                (self._token, rid) for rid in touched
            )
        if rowstore_mod.PARANOIA:
            self._paranoia_check(touched)
        if touched:
            # multi-row stacks may contain any touched row; drop them all
            DEVICE_CACHE.invalidate_owner(self._stack_token)
            self.version += 1
            if self.on_mutate is not None:
                self.on_mutate()
        return n_set, n_clear

    def _bulk_set_sparse(self, to_set: np.ndarray, touched: set) -> int:  # guarded-by: _mu
        """Set a batch of keyed positions (row*SHARD_WIDTH + col) with ONE
        merge for all sparse-rep rows: their stored position arrays and
        the incoming batch are re-keyed into the same row-major space, so
        one np.unique over the concatenation replaces a union1d per
        (row, shard) — the per-call numpy overhead used to dominate
        scattered bulk imports ~3:1. Dense-rep rows keep the per-row word
        path (their bits are cheap to OR in place)."""
        rows_arr = to_set // SHARD_WIDTH
        uniq_rows = np.unique(rows_arr).astype(np.uint64)
        dense_rows = [
            int(r)
            for r in uniq_rows
            if (rb := self._rows.get(int(r))) is not None and rb.dense is not None
        ]
        n = 0
        if dense_rows:
            m = np.isin(rows_arr, np.array(dense_rows, np.uint64))
            cols = (to_set[m] % SHARD_WIDTH).astype(np.uint32)
            for row_id, sl in group_slices(rows_arr[m].astype(np.int64)):
                rb = self._rows[int(row_id)]
                n += rb.add(cols[sl])
                touched.add(int(row_id))
            if len(dense_rows) == len(uniq_rows):
                return n
            incoming = to_set[~m]
        else:
            incoming = to_set
        dense_set = set(dense_rows)
        sparse_rows = [int(r) for r in uniq_rows if int(r) not in dense_set]
        parts = [incoming.astype(np.uint64)]
        before = 0
        for rid in sparse_rows:
            rb = self._rows.get(rid)
            if rb is not None and len(rb.positions):
                before += len(rb.positions)
                parts.append(
                    rb.positions.astype(np.uint64) + np.uint64(rid) * np.uint64(SHARD_WIDTH)
                )
        merged = np.unique(np.concatenate(parts))
        # split the sorted row-major keyspace back into per-row arrays;
        # the %/cast runs ONCE for the whole fragment, then each row takes
        # a COPY of its slice — a shared view would pin the entire merge
        # buffer for as long as any one straggler row kept its slice
        # (rows densify/rewrite independently)
        all_pos = (merged % np.uint64(SHARD_WIDTH)).astype(np.uint32)
        edges = np.searchsorted(
            merged,
            np.array(
                [r * SHARD_WIDTH for r in sparse_rows]
                + [(sparse_rows[-1] + 1) * SHARD_WIDTH],
                np.uint64,
            ),
        )
        for i, rid in enumerate(sparse_rows):
            rb = self._rows.get(rid)
            if rb is None:
                rb = self._rows[rid] = RowBits(SHARD_WIDTH)
            rb.positions = all_pos[edges[i] : edges[i + 1]].copy()
            rb._maybe_densify()
            touched.add(rid)
        n += len(merged) - before
        return n

    def _bulk_clear_sparse(self, to_clear: np.ndarray, touched: set) -> int:  # guarded-by: _mu
        """Clear a batch of keyed positions with ONE merged membership test
        for all sparse-rep rows (the clear-side mirror of _bulk_set_sparse):
        stored position arrays and the incoming batch are re-keyed into the
        same row-major space, a single searchsorted pass marks the cleared
        keys, and each shrunken row takes a copy of its surviving slice.
        Dense-rep rows keep the per-row word path (bitwise_and.at inside
        RowBits.discard). Returns how many bits were actually cleared."""
        rows_arr = to_clear // SHARD_WIDTH
        uniq_rows = np.unique(rows_arr).astype(np.uint64)
        dense_rows: List[int] = []
        sparse_rows: List[int] = []
        for r in uniq_rows:
            rb = self._rows.get(int(r))
            if rb is None:
                continue
            (dense_rows if rb.dense is not None else sparse_rows).append(int(r))
        n = 0
        if dense_rows:
            m = np.isin(rows_arr, np.array(dense_rows, np.uint64))
            cols = (to_clear[m] % SHARD_WIDTH).astype(np.uint32)
            for row_id, sl in group_slices(rows_arr[m].astype(np.int64)):
                rb = self._rows[int(row_id)]
                n += rb.discard(cols[sl])
                touched.add(int(row_id))
        if not sparse_rows:
            return n
        inc_mask = np.isin(rows_arr, np.array(sparse_rows, np.uint64))
        inc = np.unique(to_clear[inc_mask].astype(np.uint64))
        parts = []
        for rid in sparse_rows:
            p = self._rows.get(rid).positions
            if len(p):
                parts.append(
                    p.astype(np.uint64) + np.uint64(rid) * np.uint64(SHARD_WIDTH)
                )
        if not parts:
            return n
        stored = np.concatenate(parts)
        idx = np.searchsorted(inc, stored)
        idxc = np.minimum(idx, len(inc) - 1)
        hit = (idx < len(inc)) & (inc[idxc] == stored)
        kept = stored[~hit]
        n += len(stored) - len(kept)
        all_pos = (kept % np.uint64(SHARD_WIDTH)).astype(np.uint32)
        edges = np.searchsorted(
            kept,
            np.array(
                [r * SHARD_WIDTH for r in sparse_rows]
                + [(sparse_rows[-1] + 1) * SHARD_WIDTH],
                np.uint64,
            ),
        )
        for i, rid in enumerate(sparse_rows):
            rb = self._rows.get(rid)
            sl = all_pos[edges[i] : edges[i + 1]]
            if len(sl) != rb.count():
                rb.positions = sl.copy()
            touched.add(rid)
        return n

    def import_row_words(self, row_id: int, words: np.ndarray) -> int:
        """Word-level bulk union into one row — the device-native analog of
        the reference's zero-parse roaring import (fragment.go:2255
        ImportRoaringBits unioning a shipped bitmap in place): callers ship
        the row's dense uint32[W] words and they are OR'd into the store in
        one vector op. Returns how many bits were newly set."""
        words = np.ascontiguousarray(words, dtype=np.uint32)
        if words.shape != (SHARD_WIDTH // 32,):
            raise ValueError(
                f"import_row_words: want shape ({SHARD_WIDTH // 32},), got {words.shape}"
            )
        tok = None
        with self._mu:
            # see stage_positions: the mutex vector is guarded state
            if self._mutex_map is not None:
                raise ValueError(
                    "word-level import is not supported on mutex fields"
                )
            self._check_write_block_locked()
            self._sync_locked()
            if self._wal is not None or self._captures:
                payload = np.empty(1 + words.nbytes // 8, np.uint64)
                payload[0] = row_id
                payload[1:] = words.view(np.uint64)
                if self._wal is not None:
                    tok = self._wal.append(walmod.OP_ROW_WORDS, payload)
                self._capture_record(walmod.OP_ROW_WORDS, payload)
            added = self._apply_row_words(row_id, words)
            self._op_n += added
            if self._op_n > self.max_op_n:
                self.snapshot()
                tok = None  # snapshot fsynced + truncated: already durable
        if tok is not None:
            walmod.GROUP_COMMIT.wait_durable(tok)
        return added

    def _apply_row_words(self, row_id: int, words: np.ndarray) -> int:  # guarded-by: _mu
        rb = self._rows.get(row_id)
        if rb is None:
            rb = self._rows[row_id] = RowBits(SHARD_WIDTH)
        added = rb.union_words(words)
        if added:
            self.cache.add(row_id, rb.count())
            DEVICE_CACHE.invalidate((self._token, row_id))
            DEVICE_CACHE.invalidate_owner(self._stack_token)
            self.version += 1
            if self.on_mutate is not None:
                self.on_mutate()
        if rowstore_mod.PARANOIA:
            self._paranoia_check({row_id})
        return added

    def _paranoia_check(self, touched) -> None:  # guarded-by: _mu
        """Opt-in invariant pass after every mutation (the reference's
        roaringparanoia tag, roaring/roaring_paranoia.go:15): rowstore
        structural checks plus cache/rowstore count coherence for the
        touched rows. Called under self._mu."""
        for row_id in touched:
            rb = self._rows.get(row_id)
            if rb is None:
                continue
            rb.check()
            if self.cache.cache_type != cachemod.CACHE_TYPE_NONE:
                cached = self.cache.get(row_id)
                if cached and cached != rb.count():
                    raise AssertionError(
                        f"row {row_id}: cache count {cached} != "
                        f"rowstore count {rb.count()}"
                    )
            if self._mutex_map is not None and self._open and rb.count():
                # mutex invariant: every set bit's column maps back to
                # this row in the mutex vector (bounded spot check without
                # materializing the row). Skipped during open()'s WAL
                # replay: the vector is only rebuilt after replay, so
                # snapshot-loaded columns are not in it yet.
                for col in rb.first_positions(64):
                    if self._mutex_map.get(int(col)) != row_id:
                        raise AssertionError(
                            f"mutex vector disagrees at col {int(col)}"
                        )

    def _wal_append(self, op: int, positions: np.ndarray) -> Optional[int]:
        if self._wal is not None:
            return self._wal.append(op, positions)
        return None

    def _pos(self, row_id: int, col: int) -> int:
        if col >= SHARD_WIDTH:
            min_col = self.shard * SHARD_WIDTH
            if not min_col <= col < min_col + SHARD_WIDTH:
                raise ValueError(f"column {col} out of bounds for shard {self.shard}")
        return row_id * SHARD_WIDTH + (col % SHARD_WIDTH)

    def bulk_import(self, row_ids: np.ndarray, cols: np.ndarray, clear: bool = False) -> int:
        """Batched standard import (reference: fragment.go:1997 bulkImport /
        :2011 bulkImportStandard). cols may be absolute or in-shard."""
        row_ids = np.asarray(row_ids, dtype=np.uint64)
        cols = np.asarray(cols, dtype=np.uint64) % SHARD_WIDTH
        positions = row_ids * SHARD_WIDTH + cols
        if self._mutex_map is not None and not clear:
            return self._bulk_import_mutex(row_ids, cols)
        if clear:
            _, n = self.import_positions(None, positions)
        else:
            n, _ = self.import_positions(positions, None)
        return n

    def _bulk_import_mutex(self, row_ids: np.ndarray, cols: np.ndarray) -> int:
        """Mutex import: last write per column wins
        (reference: fragment.go:2106 bulkImportMutex). The barrier
        defers the group-commit wait until the fragment lock below is
        released (see _set_bit_mutex)."""
        with walmod.GROUP_COMMIT.barrier(), self._mu:
            # keep last occurrence per column
            _, last_idx = np.unique(cols[::-1], return_index=True)
            idx = len(cols) - 1 - last_idx
            to_set = []
            to_clear = []
            updates = {}
            for i in idx:
                col, row = int(cols[i]), int(row_ids[i])
                existing = self._mutex_map.get(col)
                if existing == row:
                    continue
                if existing is not None:
                    to_clear.append(existing * SHARD_WIDTH + col)
                to_set.append(row * SHARD_WIDTH + col)
                updates[col] = row
            n, _ = self.import_positions(
                np.array(to_set, np.uint64) if to_set else None,
                np.array(to_clear, np.uint64) if to_clear else None,
            )
            # map update only after the bits landed: import_positions can
            # raise TransferCutover (resize write barrier) and the caller
            # retries the whole batch — a pre-updated map would make the
            # retry a no-op (existing == row) and silently drop the write
            self._mutex_map.update(updates)
            return n

    # ------------------------------------------------------------------
    # BSI (int fields) — reference: fragment.go:932-1110, ladders in ops/bsi
    # ------------------------------------------------------------------

    def set_value(self, col: int, bit_depth: int, value: int, clear: bool = False) -> bool:
        """Sign+magnitude write (reference: fragment.go:936 positionsForValue)."""
        in_shard = col % SHARD_WIDTH
        uvalue = abs(value)
        to_set: List[int] = []
        to_clear: List[int] = []
        (to_clear if clear else to_set).append(BSI_EXISTS_BIT * SHARD_WIDTH + in_shard)
        (to_clear if (value >= 0 or clear) else to_set).append(
            BSI_SIGN_BIT * SHARD_WIDTH + in_shard
        )
        for i in range(bit_depth):
            p = (BSI_OFFSET_BIT + i) * SHARD_WIDTH + in_shard
            (to_set if (uvalue >> i) & 1 and not clear else to_clear).append(p)
        n_set, n_clear = self.import_positions(
            np.array(to_set, np.uint64), np.array(to_clear, np.uint64)
        )
        return (n_set + n_clear) > 0

    def import_values(self, cols: np.ndarray, values: np.ndarray, bit_depth: int) -> None:
        """Columnar BSI import: transpose columns×values into per-plane row
        sets (reference: fragment.go:2205 importValue)."""
        cols = np.asarray(cols, dtype=np.uint64) % SHARD_WIDTH
        values = np.asarray(values, dtype=np.int64)
        # last write per column wins
        _, last_idx = np.unique(cols[::-1], return_index=True)
        idx = len(cols) - 1 - last_idx
        cols, values = cols[idx], values[idx]
        mags = np.abs(values).astype(np.uint64)
        to_set = [BSI_EXISTS_BIT * SHARD_WIDTH + cols]
        to_clear = []
        neg = values < 0
        to_set.append(BSI_SIGN_BIT * SHARD_WIDTH + cols[neg])
        to_clear.append(BSI_SIGN_BIT * SHARD_WIDTH + cols[~neg])
        for i in range(bit_depth):
            has = (mags >> np.uint64(i)) & np.uint64(1) != 0
            base = (BSI_OFFSET_BIT + i) * SHARD_WIDTH
            to_set.append(base + cols[has])
            to_clear.append(base + cols[~has])
        self.import_positions(np.concatenate(to_set), np.concatenate(to_clear))

    def value(self, col: int, bit_depth: int) -> Tuple[int, bool]:
        """Read one column's BSI value (host point-read;
        reference: fragment.go:896)."""
        with self._mu:
            in_shard = col % SHARD_WIDTH
            if not self.contains(BSI_EXISTS_BIT, in_shard):
                return 0, False
            v = 0
            for i in range(bit_depth):
                if self.contains(BSI_OFFSET_BIT + i, in_shard):
                    v |= 1 << i
            if self.contains(BSI_SIGN_BIT, in_shard):
                v = -v
            return v, True

    def _bsi_stack(self, bit_depth: int):
        planes = self.rows_device(range(BSI_OFFSET_BIT, BSI_OFFSET_BIT + bit_depth))
        exists = self.row_device(BSI_EXISTS_BIT)
        sign = self.row_device(BSI_SIGN_BIT)
        return planes, exists, sign

    _FULL_FILTER = None

    @classmethod
    def _full_filter(cls) -> jax.Array:
        if cls._FULL_FILTER is None or cls._FULL_FILTER.shape != (SHARD_WIDTH // 32,):
            cls._FULL_FILTER = jax.device_put(
                np.full(SHARD_WIDTH // 32, 0xFFFFFFFF, dtype=np.uint32)
            )
        return cls._FULL_FILTER

    def sum(self, filter_words, bit_depth: int) -> Tuple[int, int]:
        """(sum of stored base-values, count) — device per-plane counts,
        exact host combine (reference: fragment.go:1111)."""
        planes, exists, sign = self._bsi_stack(bit_depth)
        filt = filter_words if filter_words is not None else self._full_filter()
        count, pos_counts, neg_counts = obsi.sum_counts(planes, exists, sign, filt, bit_depth)
        pos_counts = np.asarray(pos_counts)
        neg_counts = np.asarray(neg_counts)
        total = sum(
            (1 << i) * (int(pos_counts[i]) - int(neg_counts[i])) for i in range(bit_depth)
        )
        return total, int(count)

    def min(self, filter_words, bit_depth: int) -> Tuple[int, int]:
        """(min stored value, count attaining it) — reference: fragment.go:1146."""
        import jax.numpy as jnp

        planes, exists, sign = self._bsi_stack(bit_depth)
        filt = filter_words if filter_words is not None else self._full_filter()
        consider = ob.b_and(exists, filt)
        if int(ob.popcount(consider)) == 0:
            return 0, 0
        negatives = ob.b_and(consider, sign)
        if int(ob.popcount(negatives)) > 0:
            mval, final = obsi.max_unsigned(planes, negatives, bit_depth)
            return -int(mval), int(ob.popcount(final))
        mval, final = obsi.min_unsigned(planes, consider, bit_depth)
        return int(mval), int(ob.popcount(final))

    def max(self, filter_words, bit_depth: int) -> Tuple[int, int]:
        """(max stored value, count attaining it) — reference: fragment.go:1191."""
        planes, exists, sign = self._bsi_stack(bit_depth)
        filt = filter_words if filter_words is not None else self._full_filter()
        consider = ob.b_and(exists, filt)
        if int(ob.popcount(consider)) == 0:
            return 0, 0
        positives = ob.b_andnot(consider, sign)
        if int(ob.popcount(positives)) == 0:
            mval, final = obsi.min_unsigned(planes, consider, bit_depth)
            return -int(mval), int(ob.popcount(final))
        mval, final = obsi.max_unsigned(planes, positives, bit_depth)
        return int(mval), int(ob.popcount(final))

    def range_op(self, op: str, bit_depth: int, predicate: int) -> jax.Array:
        """Device words of columns whose stored value satisfies `op predicate`
        (reference: fragment.go:1273 rangeOp). op in {eq,neq,lt,lte,gt,gte}."""
        planes, exists, sign = self._bsi_stack(bit_depth)
        upred = np.uint32(abs(predicate))
        if op == "eq" or op == "neq":
            base = (
                ob.b_and(exists, sign) if predicate < 0 else ob.b_andnot(exists, sign)
            )
            eq = obsi.range_eq_unsigned(base, planes, upred, bit_depth)
            if op == "eq":
                return eq
            return ob.b_andnot(exists, eq)
        # Sign decomposition. Note: the reference folds predicate -1/0 strict
        # cases into the positive-side ladder (fragment.go:1332,1405
        # `predicate >= -1 && !allowEquality`), which mis-handles e.g.
        # `> -1` (drops 0 and 1) and `< -1` (includes 0 and -1). We use the
        # exact decomposition instead:
        #   v <  p, p <= 0: negatives with mag > |p|   (strict/eq via allow_eq)
        #   v <  p, p  > 0: positives with mag < p, plus all negatives
        #   v >  p, p >= 0: positives with mag > p
        #   v >  p, p  < 0: negatives with mag < |p|, plus all positives
        positives = ob.b_andnot(exists, sign)
        negatives = ob.b_and(exists, sign)
        if op in ("lt", "lte"):
            allow_eq = op == "lte"
            if predicate > 0 or (predicate == 0 and allow_eq):
                pos = obsi.range_lt_unsigned(positives, planes, upred, bit_depth, allow_eq)
                return ob.b_or(negatives, pos)
            if predicate == 0:  # strict < 0
                return negatives
            return obsi.range_gt_unsigned(negatives, planes, upred, bit_depth, allow_eq)
        if op in ("gt", "gte"):
            allow_eq = op == "gte"
            if predicate > 0 or (predicate == 0 and allow_eq):
                return obsi.range_gt_unsigned(positives, planes, upred, bit_depth, allow_eq)
            if predicate == 0:  # strict > 0
                return obsi.range_gt_unsigned(positives, planes, upred, bit_depth, False)
            neg = obsi.range_lt_unsigned(negatives, planes, upred, bit_depth, allow_eq)
            return ob.b_or(positives, neg)
        raise ValueError(f"invalid range op {op!r}")

    def range_between(self, bit_depth: int, pmin: int, pmax: int) -> jax.Array:
        """Columns with pmin <= value <= pmax (reference: fragment.go:1463)."""
        planes, exists, sign = self._bsi_stack(bit_depth)
        umin, umax = np.uint32(abs(pmin)), np.uint32(abs(pmax))
        positives = ob.b_andnot(exists, sign)
        negatives = ob.b_and(exists, sign)
        if pmin >= 0:
            return obsi.range_between_unsigned(positives, planes, umin, umax, bit_depth)
        if pmax < 0:
            return obsi.range_between_unsigned(negatives, planes, umax, umin, bit_depth)
        pos = obsi.range_lt_unsigned(positives, planes, umax, bit_depth, True)
        neg = obsi.range_lt_unsigned(negatives, planes, umin, bit_depth, True)
        return ob.b_or(pos, neg)

    def not_null(self) -> jax.Array:
        return self.row_device(BSI_EXISTS_BIT)

    # ------------------------------------------------------------------
    # TopN support: batched row cardinalities on device
    # ------------------------------------------------------------------

    def row_counts(
        self, row_ids: List[int], filter_words=None, chunk: int = 256
    ) -> np.ndarray:
        """Cardinality of each listed row (optionally intersected with a
        filter), computed on device in chunks (reference: fragment.go:1570
        top; rank cache comes later at the field layer)."""
        import jax.numpy as jnp

        out = np.empty(len(row_ids), dtype=np.uint64)
        for i in range(0, len(row_ids), chunk):
            ids = row_ids[i : i + chunk]
            stack = self.rows_device(ids)
            if filter_words is not None:
                counts = ob.count_and_rows(stack, filter_words)
            else:
                counts = ob.popcount_rows(stack)
            out[i : i + len(ids)] = np.asarray(counts, dtype=np.uint64)
        return out

    # ------------------------------------------------------------------
    # anti-entropy + streaming (reference: fragment.go:1762-1874 Blocks,
    # :2436-2606 WriteTo/ReadFrom)
    # ------------------------------------------------------------------

    def pairs(
        self, row_lo: Optional[int] = None, row_hi: Optional[int] = None
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Bits as (row_ids, in-shard cols) arrays, row-major sorted,
        optionally restricted to rows in [row_lo, row_hi)."""
        with self._mu:
            self._sync_locked()
            rows_out = []
            cols_out = []
            for row_id in sorted(self._rows):
                if row_lo is not None and row_id < row_lo:
                    continue
                if row_hi is not None and row_id >= row_hi:
                    continue
                pos = self._rows[row_id].to_positions()
                if len(pos):
                    rows_out.append(np.full(len(pos), row_id, dtype=np.uint64))
                    cols_out.append(pos.astype(np.uint64))
            if not rows_out:
                return np.empty(0, np.uint64), np.empty(0, np.uint64)
            return np.concatenate(rows_out), np.concatenate(cols_out)

    def block_checksums(self) -> Dict[int, bytes]:
        """Per-100-row-block digests for replica sync
        (reference: fragment.go:2814-2838 blockHasher)."""
        from pilosa_tpu.core.blocks import block_checksums as _bc

        return _bc(self.pairs())

    def block_pairs(self, block_id: int) -> Tuple[np.ndarray, np.ndarray]:
        """(rows, cols) bits within one checksum block."""
        from pilosa_tpu.core.blocks import HASH_BLOCK_SIZE

        return self.pairs(block_id * HASH_BLOCK_SIZE, (block_id + 1) * HASH_BLOCK_SIZE)

    def apply_deltas(
        self, sets: Tuple[np.ndarray, np.ndarray], clears: Tuple[np.ndarray, np.ndarray]
    ) -> Tuple[int, int]:
        """Apply (rows, cols) set/clear deltas from an anti-entropy merge."""
        sr, sc = sets
        cr, cc = clears
        to_set = (
            np.asarray(sr, np.uint64) * SHARD_WIDTH + np.asarray(sc, np.uint64)
            if len(sr)
            else None
        )
        to_clear = (
            np.asarray(cr, np.uint64) * SHARD_WIDTH + np.asarray(cc, np.uint64)
            if len(cr)
            else None
        )
        return self.import_positions(to_set, to_clear)

    def to_bytes(self) -> bytes:
        """Full-fragment serialization for resize streaming / backup
        (reference: fragment.go:2436 WriteTo — streams storage as tar)."""
        import io

        with self._mu:
            self._sync_locked()
            buf = io.BytesIO()
            walmod.write_snapshot_stream(buf, self.shard, SHARD_WIDTH, self._rows)
            return buf.getvalue()

    # -- live-transfer write capture (streaming resize) ----------------

    def begin_streaming(self, tag: str = "default") -> bytes:
        """Phase 1 of a live fragment transfer: serialize the full row
        store AND, atomically under the same lock hold, arm the `tag`
        capture for every subsequent mutation — so the snapshot plus the
        captured delta is exactly this fragment's state at any later
        drain point. The fragment keeps serving reads and accepting
        writes throughout. Captures are independent per tag (one per
        destination transfer leg); re-beginning a tag replaces that
        tag's capture only (idempotent refetch)."""
        import io

        with self._mu:
            self._sync_locked()
            buf = io.BytesIO()
            walmod.write_snapshot_stream(buf, self.shard, SHARD_WIDTH, self._rows)
            if tag not in self._captures:
                resources.acquire("fragment.capture", (id(self), tag))
            self._captures[tag] = []
            self._capture_ns[tag] = 0
            self._captures_lost.discard(tag)
            return buf.getvalue()

    def begin_capture_if_version(self, tag: str, version: int) -> bool:
        """Arm a `tag` write capture WITHOUT serializing, iff the
        fragment is still at `version` — the tier's snapshot-bootstrap
        offer path: the destination fetches the already-uploaded
        snapshot object (taken at `version`) from the object store, so
        object + capture is exact only if nothing mutated since the
        currency check. The version re-check and the arming share one
        lock hold, which is what closes that race; on False the caller
        falls back to classic peer streaming."""
        with self._mu:
            if self.version != version:
                return False
            self._sync_locked()
            if self.version != version:
                return False  # the sync itself merged a staged delta
            if tag not in self._captures:
                resources.acquire("fragment.capture", (id(self), tag))
            self._captures[tag] = []
            self._capture_ns[tag] = 0
            self._captures_lost.discard(tag)
            return True

    def drain_capture(self, tag: str = "default") -> bytes:
        """Phase 2: pop one tag's captured write records as one WAL-framed
        byte stream (the read barrier — concurrent writers to THIS
        fragment block only for the pop). The capture stays armed, so
        repeated drains stream catch-up rounds until the delta runs dry.
        Raises TransferCaptureLost when there is nothing to resume from."""
        with self._mu:
            records = self._captures.get(tag)
            if records is None:
                raise TransferCaptureLost(
                    f"{self.index}/{self.field}/{self.view}/{self.shard}: "
                    + ("write capture overflowed"
                       if tag in self._captures_lost
                       else "no active write capture")
                )
            self._captures[tag] = []
            self._capture_ns[tag] = 0
            return walmod.encode_records(records)

    def end_capture(self, tag: Optional[str] = None) -> None:
        """Stop capturing for `tag` (cutover complete, or transfer
        abandoned); None ends every capture. Once the last capture is
        gone the cutover write barrier (if any) lifts with it — no
        transfer can still depend on a frozen delta."""
        with self._mu:
            if tag is None:
                for t in self._captures:
                    resources.release("fragment.capture", (id(self), t))
                self._captures.clear()
                self._capture_ns.clear()
                self._captures_lost.clear()
            else:
                if tag in self._captures:
                    resources.release("fragment.capture", (id(self), tag))
                self._captures.pop(tag, None)
                self._capture_ns.pop(tag, None)
                self._captures_lost.discard(tag)
            if not self._captures:
                self._write_block_until = 0.0

    def block_writes(self, ttl: float) -> None:
        """Arm the cutover write barrier for `ttl` seconds: every mutation
        funnel raises TransferCutover until the barrier lifts (release,
        end of captures, or deadline expiry). Reads keep serving."""
        with self._mu:
            self._write_block_until = time.monotonic() + max(ttl, 0.0)

    def unblock_writes(self) -> None:
        with self._mu:
            self._write_block_until = 0.0

    def _check_write_block_locked(self) -> None:
        # called under self._mu at the top of every mutation funnel
        if not self._write_block_until:
            return
        if time.monotonic() >= self._write_block_until:
            self._write_block_until = 0.0  # lost release; self-heal
            return
        raise TransferCutover(
            f"{self.index}/{self.field}/{self.view}/{self.shard}: "
            "resize cutover in progress, retry"
        )

    def _capture_record(self, op: int, positions: np.ndarray) -> None:  # guarded-by: _mu
        # called under self._mu by every mutation funnel
        if not self._captures:
            return
        for tag in list(self._captures):
            self._captures[tag].append((op, positions))
            n = self._capture_ns[tag] + len(positions)
            if n > CAPTURE_MAX_POSITIONS:
                # unbounded buffering is worse than a refetch: drop this
                # tag's capture and make its next drain signal "restart
                # from a fresh snapshot"
                del self._captures[tag]
                del self._capture_ns[tag]
                self._captures_lost.add(tag)
                resources.release("fragment.capture", (id(self), tag))
            else:
                self._capture_ns[tag] = n

    def apply_transfer_records(self, data: bytes) -> int:
        """Destination-side delta replay: apply a drain_capture() byte
        stream through the normal exact write funnels (WAL-framed and
        device-invalidated like any other write). The whole stream is
        decoded BEFORE the first record applies: decode_records is strict,
        and materializing up front is what actually honors its torn-wire
        contract — a ValueError mid-iteration after a partial apply would
        leave this fragment holding an un-resumable prefix. Returns
        positions applied."""
        records = list(walmod.decode_records(data))
        n = 0
        # one group-commit round for the whole delta, not one per record
        with walmod.GROUP_COMMIT.barrier():
            for op, positions in records:
                if op == walmod.OP_ROW_WORDS:
                    words = np.ascontiguousarray(positions[1:]).view(np.uint32)
                    self.import_row_words(int(positions[0]), words)
                    # count set BITS, not payload words: `n` feeds
                    # resize.delta_positions and the job's deltas counter,
                    # documented as write positions — a whole-row union
                    # record would otherwise add 1 + words_per_row
                    # regardless of how many bits the row carries
                    n += int(np.unpackbits(words.view(np.uint8)).sum())
                else:
                    if op == walmod.OP_SET:
                        self.import_positions(positions, None)
                    else:
                        self.import_positions(None, positions)
                    n += len(positions)
        return n

    def merge_from_bytes(self, data: bytes) -> int:
        """Union a snapshot stream INTO this fragment instead of replacing
        it — the post-commit resize sweep uses this when the destination
        fragment already exists (post-cutover writes created it), where
        from_bytes' wholesale replace would erase those acknowledged
        writes. Rides import_row_words, so every merged row is WAL-framed
        and device-invalidated like any other write. Returns bits newly
        set."""
        import io

        shard, n_bits, rows = walmod.read_snapshot_stream(io.BytesIO(data))
        if shard != self.shard:
            raise ValueError(
                f"fragment stream is for shard {shard}, not {self.shard}"
            )
        if n_bits != SHARD_WIDTH:
            raise ValueError(
                f"fragment stream shard width {n_bits} != local {SHARD_WIDTH}"
            )
        added = 0
        # one group-commit round for the whole merged stream, not one
        # fsync wait per row
        with walmod.GROUP_COMMIT.barrier():
            for row_id, rb in rows.items():
                words = np.array(rb.to_words(), dtype=np.uint32)
                if words.any():
                    added += self.import_row_words(row_id, words)
        return added

    def from_bytes(self, data: bytes) -> None:
        """Replace this fragment's contents from to_bytes() output
        (reference: fragment.go:2527 ReadFrom)."""
        import io

        shard, n_bits, rows = walmod.read_snapshot_stream(io.BytesIO(data))
        if shard != self.shard:
            raise ValueError(
                f"fragment stream is for shard {shard}, not {self.shard}"
            )
        if n_bits != SHARD_WIDTH:
            raise ValueError(
                f"fragment stream shard width {n_bits} != local {SHARD_WIDTH}"
            )
        with self._mu:
            # pending deltas describe the REPLACED contents; the forced
            # snapshot below truncates their WAL records with everything
            # else, so they must not merge into the new rows. The gen
            # bump invalidates any in-flight barrier snapshot of them.
            self._pending = []
            self._pending_n = 0
            self._pending_gen += 1
            self._premerged = []  # replaced contents: parked layers are void
            self._premerged_n = 0
            if self._captures:
                # a wholesale replace invalidates every in-flight
                # transfer's snapshot+delta contract: force peers to
                # refetch
                self._captures_lost.update(self._captures)
                self._captures.clear()
                self._capture_ns.clear()
            self._rows = rows
            DEVICE_CACHE.invalidate_owner(self._token)
            DEVICE_CACHE.invalidate_owner(self._stack_token)
            self.version += 1
            if self.on_mutate is not None:
                self.on_mutate()
            if self._mutex_map is not None:
                self._rebuild_mutex_map()
            # the rank cache reflects the replaced contents, and snapshot()
            # below persists the sidecar — rebuild before it goes to disk
            self.recalculate_cache()
            self._op_n = self.max_op_n + 1  # force snapshot on next write
            if self.path is not None:
                self.snapshot()

    # ------------------------------------------------------------------
    # persistence
    # ------------------------------------------------------------------

    def snapshot(self) -> None:
        """Write full snapshot and reset the WAL
        (reference: fragment.go:2337-2395)."""
        with self._mu:
            # the pending delta MUST merge before the snapshot is written:
            # truncate() below discards its WAL records, so unmerged bits
            # would otherwise be lost
            self._sync_locked()
            if self.path is None:
                self._op_n = 0
                return
            walmod.write_snapshot(self.snap_path, self.shard, SHARD_WIDTH, self._rows)
            if isinstance(self._rows, _LazyRows):
                # offsets moved with the rewrite: re-index unmaterialized
                # rows against the new file (materialized rows unaffected)
                self._rows.rebase(self.snap_path)
            # flush the sidecar BEFORE truncating the WAL: open() trusts
            # the sidecar only when the WAL replayed nothing, so a crash
            # in between leaves a non-empty WAL -> replay -> recalculate,
            # never a stale sidecar served as "provably complete" exact
            # counts (code-review r5 crash-window finding)
            self.flush_cache()
            # crash-matrix injection point: snapshot durable (written,
            # fsynced, dir-synced), WAL not yet truncated — a kill here
            # must replay the full WAL over the fresh snapshot without
            # double-applying (all ops are idempotent re-unions/clears)
            walmod.fault_point("snapshot.pre_truncate", self.snap_path or "")
            if self._wal is not None:
                self._wal.truncate()
            self._op_n = 0
