"""Budgeted LRU cache for device-resident (HBM) arrays — the extent store.

The reference bounds storage residency with mmap + explicit resource caps
(/root/reference/roaring.go:1437 RemapRoaringStorage, syswrap/mmap.go map
count caps): hot data lives in the page cache, cold data is a page fault
away. On TPU the analog is HBM residency: every row/stack a query touches
is device_put into HBM and should stay there while hot — but HBM is a fixed
budget, so residency must be *bounded* and cold entries must fall back to
the host store (a rebuild away, as a page fault is in the reference).

One process-global DeviceCache instance backs:
- Fragment per-row device arrays (core/fragment.py row_device),
- View-level multi-shard row stacks (core/view.py row_stack), and
- Operand EXTENTS (pilosa_tpu/hbm/residency.py): shard-major slices of a
  stacked operand, individually tracked so an HBM budget below one query's
  working set evicts and re-stages *slices*, not whole stacks,
so the budget is enforced jointly across all fragments, stacks and extents.

Keys are (owner, *rest) tuples where `owner` is a per-object token from
`new_owner_token()`; `invalidate_owner` drops everything an object cached
(fragment close / replace-from-stream).

Three properties the hbm/ residency layer leans on:

- get_or_build is SINGLE-FLIGHT: concurrent callers of the same key run
  exactly one build; the rest wait and share the result (a thundering herd
  of identical device_puts would overshoot the byte ledger and waste PCIe).
- Entries can be PINNED (refcounted): a pinned entry is never evicted —
  eviction is deferred until unpin — so an extent in use by an in-flight
  compiled dispatch cannot be dropped mid-query. Explicit invalidation of
  a pinned entry removes it from lookup immediately (new queries rebuild
  under the new version key) but its bytes stay on the ledger until the
  last unpin, because the device memory genuinely is still held by the
  in-flight operand ("zombie" bytes).
- `pin_timeout` is a leak safety valve: a pin held longer than the timeout
  (default: forever disabled here; the server wires hbm-pin-timeout) is
  forcibly released by the evictor, so a leaked pin degrades to an
  eviction, never to a permanently wedged budget.
"""

from __future__ import annotations

import os
import time
from collections import OrderedDict
from contextlib import contextmanager
from typing import (
    Callable,
    Dict,
    Hashable,
    Iterable,
    Iterator,
    List,
    Optional,
    Set,
    Tuple,
)

from pilosa_tpu.utils import resources
from pilosa_tpu.utils.locks import TrackedCondition, TrackedLock
from pilosa_tpu.utils.race import race_checked

_DEFAULT_BUDGET_MB = 4096


def _env_budget_bytes() -> int:
    raw = os.environ.get("PILOSA_TPU_HBM_BUDGET_MB")
    try:
        mb = int(raw) if raw else _DEFAULT_BUDGET_MB
    except ValueError:
        mb = _DEFAULT_BUDGET_MB
    return mb * 1024 * 1024


_token_lock = TrackedLock("devcache.token_lock")
_token_next = 0


def new_owner_token() -> int:
    """Process-unique owner id (object identity is not reuse-safe)."""
    global _token_next
    with _token_lock:
        _token_next += 1
        return _token_next


def _nbytes(arr: object) -> int:
    nb = getattr(arr, "nbytes", None)
    if nb is not None:
        return int(nb)
    import numpy as np

    return int(np.asarray(arr).nbytes)


@race_checked(exclude=(
    # budget_bytes / pin_timeout are operator knobs written by
    # set_budget()/NodeServer configuration and read inside _mu holds;
    # a torn read is impossible (int/float) and a stale one only delays
    # an eviction by one pass. The stats counters are read lock-free by
    # gauge snapshots on purpose (monotonic, GIL-atomic int adds).
    "budget_bytes",
    "pin_timeout",
    "hits",
    "misses",
    "evictions",
    "evicted_extent_bytes",
    "stale_pin_reclaims",
    "quota_evictions",
))
class DeviceCache:
    """LRU key -> device array map with a byte budget.

    A single entry larger than the whole budget is still admitted (the query
    needs it to run) but is evicted as soon as anything else is inserted —
    the budget bounds *steady-state* residency. Likewise, when every entry
    is pinned the cache may sit over budget transiently; eviction resumes
    as pins release.
    """

    def __init__(
        self,
        budget_bytes: int | None = None,
        pin_timeout: float = 0.0,  # seconds; 0 = stale-pin reclaim off
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        self._mu = TrackedLock("devcache.mu")
        # single-flight get_or_build: waiters park here while a peer builds
        self._build_cv = TrackedCondition(self._mu, name="devcache.build_cv")
        self._building: Set[Tuple] = set()
        self._entries: "OrderedDict[Tuple, object]" = OrderedDict()
        self._sizes: Dict[Tuple, int] = {}
        self._by_owner: Dict[Hashable, Set[Tuple]] = {}
        # live (lookup-visible) bytes per owner: moves wherever _by_owner
        # moves, so owner_resident_bytes — read by every admission
        # (sched/cost.py) — never walks, or re-hashes, the owner's keys
        self._owner_bytes: Dict[Hashable, int] = {}
        self._bytes = 0
        # pin refcounts + first-pin time (for the stale-pin safety valve)
        self._pins: Dict[Tuple, int] = {}
        self._pin_t0: Dict[Tuple, float] = {}
        # invalidated-while-pinned entries: gone from lookup, bytes still
        # on the ledger until the last unpin releases the device memory
        self._zombies: Dict[Tuple, int] = {}
        # operand extents (hbm/residency.py) are flagged at insert so the
        # hbm.* gauges can report them separately from per-row entries
        self._extent_keys: Set[Tuple] = set()
        # shard coverage per key (hbm staging registers the shard span an
        # extent covers): invalidate_owner_shard drops only the entries
        # whose coverage contains the dirty shard — entries with no
        # recorded coverage are dropped conservatively
        self._cover: Dict[Tuple, frozenset] = {}
        # per-index attribution: insert sites tag each entry with the
        # index that owns it (fragment rows, view stacks, hbm extents all
        # know their index name); entries staged outside any index fall
        # into the "-" bucket so index_resident_bytes() always sums to
        # the global ledger byte-for-byte. The map lives and dies with
        # the entry (zombie bytes keep theirs until the last unpin), so
        # index churn cannot leak attribution state.
        self._key_index: Dict[Tuple, str] = {}
        # eviction-deferral sessions (deferred_eviction): while a query's
        # lowering stages its operand set, evicting to make room for
        # operand K must not take operand K+1's resident extents — LRU's
        # cyclic-scan cascade would re-upload the whole working set every
        # query, the exact cliff extents exist to remove. Residency may
        # transiently exceed the budget up to the query's working set
        # (the same overshoot the oversized-entry rule already allows);
        # the ledger settles back under budget when the session ends.
        self._defer_evict = 0
        # per-index (tenant) residency quotas: 0 / absent = unlimited.
        # Enforced by _evict_locked — eviction pressure lands on the
        # over-quota owner FIRST (its own LRU order), and an index stays
        # within its quota even when the global budget has room, so
        # tenant A's warm extents survive tenant B's flood. Configured
        # by NodeServer from the [tenants] section (configure_quotas).
        self._index_quota_default = 0
        self._index_quota: Dict[str, int] = {}
        self._quota_evictions_index: Dict[str, int] = {}
        self.pin_timeout = pin_timeout
        self._clock = clock
        self.budget_bytes = (
            budget_bytes if budget_bytes is not None else _env_budget_bytes()
        )
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.evicted_extent_bytes = 0  # cumulative; paging tests diff this
        self.stale_pin_reclaims = 0
        self.quota_evictions = 0  # subset of evictions: tenant-quota passes

    # -- core --------------------------------------------------------------

    def get(self, key: Tuple) -> Optional[object]:
        with self._mu:
            arr = self._entries.get(key)
            if arr is not None:
                self._entries.move_to_end(key)
                self.hits += 1
            else:
                self.misses += 1
            return arr

    def put(
        self,
        key: Tuple,
        arr: object,
        *,
        extent: bool = False,
        shards: Optional[Iterable[int]] = None,
        index: Optional[str] = None,
    ) -> None:
        nb = _nbytes(arr)
        with self._mu:
            self._put_locked(
                key, arr, nb, extent=extent, shards=shards, index=index
            )

    def _put_locked(
        self,
        key: Tuple,
        arr: object,
        nb: int,
        *,
        extent: bool,
        shards: Optional[Iterable[int]] = None,
        index: Optional[str] = None,
    ) -> None:
        if key in self._entries:
            # replace: the old bytes leave the ledger even if pinned (the
            # pins transfer to the new array — stage-level code only pins
            # entries it just fetched/built, so a same-key replace means
            # the pin holder is being handed the new array anyway)
            self._drop_locked(key, replacing=True)
        self._entries[key] = arr
        self._sizes[key] = nb
        self._by_owner.setdefault(key[0], set()).add(key)
        self._owner_bytes[key[0]] = self._owner_bytes.get(key[0], 0) + nb
        if extent:
            self._extent_keys.add(key)
        if shards is not None:
            self._cover[key] = frozenset(shards)
        if index is not None:
            self._key_index[key] = index
        self._bytes += nb
        self._evict_locked(keep=key)

    def get_or_build(
        self,
        key: Tuple,
        build: Callable[[], object],
        *,
        extent: bool = False,
        pin: bool = False,
        shards: Optional[Iterable[int]] = None,
        index: Optional[str] = None,
    ) -> object:
        """Return the cached array for `key`, building it at most once
        process-wide even under concurrent callers (single-flight). With
        pin=True the returned entry is pinned under the same lock hold
        that found/inserted it — no eviction window in between."""
        with self._mu:
            while True:
                arr = self._entries.get(key)
                if arr is not None:
                    self._entries.move_to_end(key)
                    self.hits += 1
                    if pin:
                        self._pin_locked(key)
                    return arr
                if key not in self._building:
                    self._building.add(key)
                    self.misses += 1
                    break
                # a peer is building this key: wait for its insert instead
                # of double-building (and double-charging the byte ledger)
                self._build_cv.wait()
        import time as _t

        t_build0 = _t.perf_counter()
        try:
            arr = build()
        except BaseException:
            with self._mu:
                self._building.discard(key)
                self._build_cv.notify_all()
            raise
        nb = _nbytes(arr)
        if not extent:
            # flight-recorder staging attribution for NON-extent entries
            # (TopN tally bundles etc.) — extent staging is accounted by
            # hbm/residency, which wraps the whole assembly
            from pilosa_tpu.utils import tracing as _tracing

            _tracing.note_stage(
                nbytes=nb, seconds=_t.perf_counter() - t_build0
            )
        with self._mu:
            self._building.discard(key)
            self._put_locked(
                key, arr, nb, extent=extent, shards=shards, index=index
            )
            if pin:
                self._pin_locked(key)
            self._build_cv.notify_all()
        return arr

    def invalidate(self, key: Tuple) -> None:
        with self._mu:
            if key in self._entries:
                self._drop_locked(key)

    def invalidate_many(self, keys: Iterable[Tuple]) -> None:
        """Drop a batch of keys under ONE lock hold (bulk ingest
        reconciles a whole batch's touched rows in one pass instead of
        one lock acquisition per row)."""
        with self._mu:
            for key in keys:
                if key in self._entries:
                    self._drop_locked(key)

    def invalidate_owner(self, owner: Hashable) -> None:
        with self._mu:
            for key in list(self._by_owner.get(owner, ())):
                self._drop_locked(key)

    def invalidate_owners(self, owners: Iterable[Hashable]) -> None:
        """invalidate_owner for a batch of owner tokens under one lock
        hold (the ingest fast path drops many fragments' row entries per
        import call)."""
        with self._mu:
            for owner in owners:
                for key in list(self._by_owner.get(owner, ())):
                    self._drop_locked(key)

    def invalidate_owner_shard(self, owner: Hashable, shard: int) -> None:
        """Dirty-extent invalidation: drop only this owner's entries
        whose registered shard coverage contains `shard` (entries without
        coverage are dropped conservatively). A single-shard write then
        frees just the covering extent(s), not the owner's whole stack
        set — the read side re-stages only those slices."""
        with self._mu:
            for key in list(self._by_owner.get(owner, ())):
                cov = self._cover.get(key)
                if cov is None or shard in cov:
                    self._drop_locked(key)

    def invalidate_owner_uncovered(self, owner: Hashable) -> None:
        """Drop this owner's entries with NO registered shard coverage
        (ad-hoc builds like the TopN tally bundles, which are not
        version-keyed). The staged write path invalidates these eagerly
        while coverage-registered extents — version-keyed, hence never
        served stale — defer to the merge barrier's patch-or-invalidate
        reconciliation (core/view.py sync_pending)."""
        with self._mu:
            for key in list(self._by_owner.get(owner, ())):
                if self._cover.get(key) is None:
                    self._drop_locked(key)

    def owner_entries(
        self, owner: Hashable
    ) -> List[Tuple[Tuple, Optional[frozenset], bool]]:
        """Snapshot of one owner's live entries as
        [(key, coverage_or_None, is_extent)] under one lock hold — the
        merge barrier's extent reconciliation walks this to decide
        patch vs invalidate per entry."""
        with self._mu:
            return [
                (k, self._cover.get(k), k in self._extent_keys)
                for k in self._by_owner.get(owner, ())
            ]

    def clear(self) -> None:
        with self._mu:
            self._entries.clear()
            self._sizes.clear()
            self._by_owner.clear()
            self._owner_bytes.clear()
            self._extent_keys.clear()
            self._cover.clear()
            self._key_index.clear()
            for key, n in self._pins.items():
                for _ in range(n):
                    resources.release("hbm.pin", key)
            self._pins.clear()
            self._pin_t0.clear()
            self._zombies.clear()
            self._bytes = 0

    @contextmanager
    def deferred_eviction(self) -> Iterator[None]:
        """Suspend budget eviction for the duration (nestable; settles —
        evicts down to budget — when the outermost session exits). Used
        by the stacked lowering around operand staging; see _defer_evict."""
        with self._mu:
            self._defer_evict += 1
        try:
            yield
        finally:
            with self._mu:
                self._defer_evict -= 1
                if self._defer_evict == 0:
                    self._evict_locked(keep=None)

    # -- pinning -----------------------------------------------------------

    def pin_if_present(self, key: Tuple) -> bool:
        """Pin `key` iff it is resident; True when the pin was taken."""
        with self._mu:
            if key not in self._entries:
                return False
            self._entries.move_to_end(key)
            self._pin_locked(key)
            return True

    def _pin_locked(self, key: Tuple) -> None:
        n = self._pins.get(key, 0)
        self._pins[key] = n + 1
        if n == 0:
            self._pin_t0[key] = self._clock()
        resources.acquire("hbm.pin", key)

    def unpin(self, key: Tuple) -> None:
        """Release one pin. Unpinning an unknown key is a no-op (the pin
        may have been force-released by the stale-pin safety valve)."""
        with self._mu:
            n = self._pins.get(key, 0)
            if n >= 1:
                resources.release("hbm.pin", key)
            if n <= 1:
                self._pins.pop(key, None)
                self._pin_t0.pop(key, None)
                zb = self._zombies.pop(key, None)
                if zb is not None:
                    # last pin on an invalidated entry: the in-flight
                    # operand is done with it — bytes leave the ledger now
                    self._bytes -= zb
                    if key not in self._entries:
                        self._key_index.pop(key, None)
                if n == 1:
                    # unpinned entries become evictable: settle any debt
                    # deferred while the dispatch was in flight
                    self._evict_locked(keep=None)
            else:
                self._pins[key] = n - 1

    def unpin_all(self, keys: Iterable[Tuple]) -> None:
        for key in keys:
            self.unpin(key)

    def _pinned_locked(self, key: Tuple) -> bool:
        if key not in self._pins:
            return False
        if (
            self.pin_timeout > 0
            and self._clock() - self._pin_t0.get(key, 0.0) > self.pin_timeout
        ):
            # leak safety valve: a pin this old is a bug, not a dispatch;
            # force-release it so the budget cannot wedge permanently
            for _ in range(self._pins.pop(key, 0)):
                resources.release("hbm.pin", key)
            self._pin_t0.pop(key, None)
            self.stale_pin_reclaims += 1
            return False
        return True

    @property
    def pinned_bytes(self) -> int:
        with self._mu:
            return self._pinned_bytes_locked()

    def _pinned_bytes_locked(self) -> int:
        total = 0
        for key in self._pins:
            total += self._sizes.get(key) or self._zombies.get(key, 0)
        return total

    # -- internals ---------------------------------------------------------

    def _drop_locked(self, key: Tuple, replacing: bool = False) -> None:
        self._entries.pop(key, None)
        nb = self._sizes.pop(key, 0)
        if not replacing and key in self._pins:
            # invalidated while an in-flight dispatch holds it: the array
            # lives until the last unpin, so its bytes stay accounted —
            # and stay ATTRIBUTED (the index tag is released with the
            # zombie bytes, not here, so per-index sums keep reconciling
            # with the ledger while the operand is in flight)
            self._zombies[key] = self._zombies.get(key, 0) + nb
        else:
            self._bytes -= nb
            if key not in self._zombies:
                self._key_index.pop(key, None)
        self._extent_keys.discard(key)
        self._cover.pop(key, None)
        owner = key[0]
        owner_keys = self._by_owner.get(owner)
        if owner_keys is not None:
            owner_keys.discard(key)
            if owner_keys:
                self._owner_bytes[owner] -= nb
            else:
                del self._by_owner[owner]
                del self._owner_bytes[owner]

    def _evict_locked(self, keep: Optional[Tuple]) -> None:
        if self._defer_evict > 0:
            return
        if self._index_quota or self._index_quota_default > 0:
            # tenant quotas first: pressure lands on over-quota owners
            # before any in-quota entry is touched, and an index is held
            # to its own quota even with global budget to spare
            self._evict_over_quota_locked(keep)
        if self._bytes <= self.budget_bytes:
            return
        for key in list(self._entries):
            if self._bytes <= self.budget_bytes or len(self._entries) <= 1:
                break
            if key == keep:
                # the just-inserted entry is the only way to finish the
                # current query; evict around it
                continue
            if self._pinned_locked(key):
                # pinned by an in-flight dispatch: eviction is DEFERRED —
                # the budget may be transiently exceeded; unpin() retries
                continue
            if key in self._extent_keys:
                self.evicted_extent_bytes += self._sizes.get(key, 0)
            self._drop_locked(key)
            self.evictions += 1

    def _quota_for_locked(self, index: str) -> int:
        q = self._index_quota.get(index)
        return q if q is not None else self._index_quota_default

    def _evict_over_quota_locked(self, keep: Optional[Tuple]) -> None:
        """Per-index quota pass (LRU order within each owner). Counts
        ZOMBIE bytes against the owner — invalidated-while-pinned device
        memory is genuinely held on that tenant's behalf — but can only
        evict live unpinned entries, so a tenant whose quota is consumed
        by in-flight pins overshoots transiently, exactly like the
        global budget does."""
        by_idx = self._index_bytes_locked()
        for key in list(self._entries):
            if len(self._entries) <= 1:
                break
            if key == keep:
                continue
            idx = self._key_index.get(key, "-")
            if idx == "-":
                continue  # unattributed system entries are not a tenant
            quota = self._quota_for_locked(idx)
            if quota <= 0:
                continue
            held = by_idx.get(idx, 0)
            if held <= quota:
                continue
            if self._pinned_locked(key):
                continue
            nb = self._sizes.get(key, 0)
            if nb >= held and nb > quota:
                # a single entry larger than the whole quota is still
                # admitted when it is ALL the index holds (the query
                # needs it to run) — same oversized-entry rule as the
                # global budget; it goes once the index holds more
                continue
            if key in self._extent_keys:
                self.evicted_extent_bytes += nb
            self._drop_locked(key)
            by_idx[idx] = held - nb
            self.evictions += 1
            self.quota_evictions += 1
            self._quota_evictions_index[idx] = (
                self._quota_evictions_index.get(idx, 0) + 1
            )

    # -- introspection -----------------------------------------------------

    @property
    def bytes_used(self) -> int:
        # under the ledger lock: the bare read was the race detector's
        # first true positive (a torn view during a replace/evict pass
        # could report bytes that never existed); one uncontended
        # acquire per gauge scrape is free
        with self._mu:
            return self._bytes

    def index_resident_bytes(self) -> Dict[str, int]:
        """Resident device bytes grouped by owning INDEX (the per-tenant
        attribution the telemetry plane publishes as `hbm.resident_bytes`
        with an `index:` label). Entries inserted without an index tag
        land in "-"; zombie bytes (invalidated-while-pinned) keep their
        attribution until the last unpin releases them. Invariant —
        regression-tested under eviction pressure: the sum over every
        bucket equals `bytes_used` byte-for-byte, because both are
        computed from the same _sizes/_zombies ledgers under one lock
        hold."""
        with self._mu:
            return self._index_bytes_locked()

    def _index_bytes_locked(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for key, nb in self._sizes.items():
            idx = self._key_index.get(key, "-")
            out[idx] = out.get(idx, 0) + nb
        for key, nb in self._zombies.items():
            idx = self._key_index.get(key, "-")
            out[idx] = out.get(idx, 0) + nb
        return out

    def configure_quotas(
        self,
        default_bytes: int = 0,
        overrides: Optional[Dict[str, int]] = None,
    ) -> None:
        """Install per-index residency quotas ([tenants] section; 0 =
        unlimited) and settle immediately: an index already over its new
        quota sheds its own LRU entries now, not at its next insert."""
        with self._mu:
            self._index_quota_default = max(0, int(default_bytes))
            self._index_quota = {
                k: max(0, int(v)) for k, v in (overrides or {}).items()
            }
            self._evict_locked(keep=None)

    def quota_evictions_by_index(self) -> Dict[str, int]:
        """Cumulative tenant-quota evictions per index (published as
        `tenant.quota_evictions{cache=hbm}` gauges)."""
        with self._mu:
            return dict(self._quota_evictions_index)

    def drop_index_attribution(self, index: str) -> None:
        """Label GC for a deleted index: re-bucket any surviving
        attribution — zombie bytes still held by an in-flight dispatch's
        pins — into "-". Without this, the tick after
        drop_index_telemetry would re-create the dropped per-index gauge
        series from the zombie entry and the label would live at 0
        forever. The per-index sum still equals the global ledger; the
        orphaned bytes just report as unattributed until the last unpin
        releases them."""
        with self._mu:
            for key in [
                k for k, v in self._key_index.items() if v == index
            ]:
                del self._key_index[key]
            # tenant ledger GC rides along: the per-index eviction
            # counter must not outlive the index (its gauge series was
            # just dropped). The quota OVERRIDE stays — it is operator
            # config, bounded by config size, and must re-apply if the
            # index is recreated.
            self._quota_evictions_index.pop(index, None)

    def owner_resident_bytes(self, owner: Hashable) -> int:
        """Resident bytes cached under one owner token (the admission
        cost estimator discounts queries whose operands are already on
        device, sched/cost.py): the owner's live entries, pinned ones
        included, zombies not. A running total, one dict read whatever
        the owner holds."""
        with self._mu:
            return self._owner_bytes.get(owner, 0)

    def __len__(self) -> int:
        return len(self._entries)

    def stats_snapshot(self) -> Dict[str, int]:
        """One consistent view of the residency counters (exported as
        gauges on /metrics and /debug/vars by NodeServer)."""
        with self._mu:
            return {
                "resident_bytes": self._bytes,
                "entries": len(self._entries),
                "evictions": self.evictions,
                "hits": self.hits,
                "misses": self.misses,
                "budget_bytes": self.budget_bytes,
                "resident_extents": len(self._extent_keys),
                "pinned_bytes": self._pinned_bytes_locked(),
                "evicted_extent_bytes": self.evicted_extent_bytes,
                "stale_pin_reclaims": self.stale_pin_reclaims,
                "quota_evictions": self.quota_evictions,
            }


# Process-global instance shared by fragments, views and the hbm extent
# layer. Tests may swap the budget (set_budget) or replace the instance
# outright.
DEVICE_CACHE = DeviceCache()


def set_budget(budget_bytes: int) -> None:
    DEVICE_CACHE.budget_bytes = budget_bytes


def _pin_probe() -> List[str]:
    """Conftest leak probe (utils/resources.py): every pin staging takes
    must be released by the plan's dispatch finally or an executor error
    path. A leaked pin makes its bytes permanently unevictable — the
    budget wedges a little tighter on every leak. Clears the cache on
    failure so one leak doesn't cascade into later tests."""
    snap = DEVICE_CACHE.stats_snapshot()
    if snap["pinned_bytes"]:
        DEVICE_CACHE.clear()
        return [
            f"device-cache extent pins leaked: {snap['pinned_bytes']} "
            "bytes still pinned after the test"
        ]
    return []


resources.register_probe("hbm.pin", _pin_probe)
