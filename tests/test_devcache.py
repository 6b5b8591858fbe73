"""Device cache budget tests (VERDICT round-1 task 3: bound HBM residency).

The reference bounds storage residency via mmap + syswrap caps
(/root/reference/syswrap/mmap.go, roaring.go:1437 RemapRoaringStorage);
here the analog is the byte-budgeted LRU over device arrays — now the
extent store for the HBM residency manager (pilosa_tpu/hbm/): builds are
single-flight, entries can be pinned (eviction deferred), and invalidation
of a pinned entry keeps its bytes on the ledger until the last unpin.
"""

import threading
import time

import numpy as np
import pytest

from pilosa_tpu.core.devcache import DEVICE_CACHE, DeviceCache, new_owner_token
from pilosa_tpu.core.fragment import Fragment
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_ROW


class TestDeviceCacheUnit:
    def test_lru_eviction_under_budget(self):
        c = DeviceCache(budget_bytes=1000)
        t = new_owner_token()
        for i in range(10):
            c.put((t, i), np.zeros(64, np.uint32))  # 256 B each
        assert c.bytes_used <= 1000
        # oldest entries evicted, newest kept
        assert c.get((t, 9)) is not None
        assert c.get((t, 0)) is None
        assert c.evictions > 0

    def test_get_refreshes_recency(self):
        c = DeviceCache(budget_bytes=600)
        t = new_owner_token()
        c.put((t, 0), np.zeros(64, np.uint32))
        c.put((t, 1), np.zeros(64, np.uint32))
        c.get((t, 0))  # refresh 0
        c.put((t, 2), np.zeros(64, np.uint32))  # evicts 1, not 0
        assert c.get((t, 0)) is not None
        assert c.get((t, 1)) is None

    def test_oversized_entry_admitted(self):
        c = DeviceCache(budget_bytes=100)
        t = new_owner_token()
        big = np.zeros(1000, np.uint32)
        c.put((t, "big"), big)
        assert c.get((t, "big")) is not None  # admitted to serve the query
        c.put((t, "next"), np.zeros(8, np.uint32))
        assert c.bytes_used <= 4032 + 100  # big evicted once anything lands

    def test_owner_invalidation(self):
        c = DeviceCache(budget_bytes=10_000)
        t1, t2 = new_owner_token(), new_owner_token()
        c.put((t1, 0), np.zeros(8, np.uint32))
        c.put((t1, 1), np.zeros(8, np.uint32))
        c.put((t2, 0), np.zeros(8, np.uint32))
        c.invalidate_owner(t1)
        assert c.get((t1, 0)) is None and c.get((t1, 1)) is None
        assert c.get((t2, 0)) is not None

    def test_replacement_accounting(self):
        c = DeviceCache(budget_bytes=10_000)
        t = new_owner_token()
        c.put((t, 0), np.zeros(100, np.uint32))
        c.put((t, 0), np.zeros(50, np.uint32))
        assert c.bytes_used == 200


class TestSingleFlightBuilds:
    def test_concurrent_get_or_build_runs_one_build(self):
        """Satellite acceptance: two threads get_or_build the same key ->
        exactly one build runs and the byte ledger never overshoots."""
        c = DeviceCache(budget_bytes=1 << 20)
        t = new_owner_token()
        builds = []
        entered = threading.Event()
        release = threading.Event()

        def build():
            builds.append(threading.current_thread().name)
            entered.set()
            release.wait(5)  # hold the build open so peers must wait
            return np.zeros(64, np.uint32)  # 256 B

        results = {}

        def worker(name):
            results[name] = c.get_or_build((t, "k"), build)

        threads = [
            threading.Thread(target=worker, args=(f"w{i}",), name=f"w{i}")
            for i in range(4)
        ]
        threads[0].start()
        assert entered.wait(5)
        for th in threads[1:]:
            th.start()
        time.sleep(0.05)  # let the waiters park on the build condition
        release.set()
        for th in threads:
            th.join(5)
        assert len(builds) == 1  # exactly one build process-wide
        assert c.bytes_used == 256  # no double-charge on the ledger
        arrs = list(results.values())
        assert all(a is arrs[0] for a in arrs)  # everyone shares the result

    def test_failed_build_releases_the_flight(self):
        c = DeviceCache(budget_bytes=1 << 20)
        t = new_owner_token()

        def boom():
            raise RuntimeError("build failed")

        with pytest.raises(RuntimeError):
            c.get_or_build((t, "k"), boom)
        # the key is not wedged: a later build succeeds
        arr = c.get_or_build((t, "k"), lambda: np.zeros(8, np.uint32))
        assert arr is not None
        assert c.bytes_used == 32


class TestPinning:
    def test_pinned_entry_survives_eviction_pressure(self):
        """Satellite acceptance: eviction during a pinned dispatch is
        deferred — the pinned entry is never dropped mid-flight."""
        c = DeviceCache(budget_bytes=1000)
        t = new_owner_token()
        c.put((t, 0), np.zeros(64, np.uint32))  # 256 B
        assert c.pin_if_present((t, 0))
        for i in range(1, 12):
            c.put((t, i), np.zeros(64, np.uint32))
        assert c.get((t, 0)) is not None  # pinned: deferred, not evicted
        assert c.stats_snapshot()["pinned_bytes"] == 256
        c.unpin((t, 0))
        # unpin settles the deferred debt: back under budget
        assert c.bytes_used <= 1000

    def test_pin_refcounts_nest(self):
        c = DeviceCache(budget_bytes=1000)
        t = new_owner_token()
        c.put((t, 0), np.zeros(64, np.uint32))
        assert c.pin_if_present((t, 0))
        assert c.pin_if_present((t, 0))
        c.unpin((t, 0))
        # still pinned once: pressure must not evict it
        for i in range(1, 12):
            c.put((t, i), np.zeros(64, np.uint32))
        assert c.get((t, 0)) is not None
        c.unpin((t, 0))

    def test_invalidate_while_pinned_keeps_bytes_until_unpin(self):
        """An in-flight operand's memory is genuinely held even after a
        write invalidates its entry: lookup misses immediately, the byte
        ledger releases only at the last unpin (zombie accounting)."""
        c = DeviceCache(budget_bytes=10_000)
        t = new_owner_token()
        c.put((t, 0), np.zeros(64, np.uint32))
        assert c.pin_if_present((t, 0))
        c.invalidate_owner(t)
        assert c.get((t, 0)) is None  # new queries rebuild
        assert c.bytes_used == 256  # bytes still accounted (in flight)
        assert c.stats_snapshot()["pinned_bytes"] == 256
        c.unpin((t, 0))
        assert c.bytes_used == 0
        assert c.stats_snapshot()["pinned_bytes"] == 0

    def test_stale_pin_safety_valve(self):
        """A leaked pin older than pin_timeout is forcibly released by
        the evictor instead of wedging the budget forever."""
        clock = [0.0]
        c = DeviceCache(
            budget_bytes=1000, pin_timeout=5.0, clock=lambda: clock[0]
        )
        t = new_owner_token()
        c.put((t, 0), np.zeros(64, np.uint32))
        assert c.pin_if_present((t, 0))  # never unpinned: the "leak"
        clock[0] = 10.0  # past the timeout
        for i in range(1, 12):
            c.put((t, i), np.zeros(64, np.uint32))
        assert c.get((t, 0)) is None  # reclaimed and evicted
        assert c.stats_snapshot()["stale_pin_reclaims"] == 1
        assert c.bytes_used <= 1000

    def test_deferred_eviction_session(self):
        """deferred_eviction() suspends budget settling until the session
        exits (the lowering's whole-operand-set staging window)."""
        c = DeviceCache(budget_bytes=1000)
        t = new_owner_token()
        with c.deferred_eviction():
            for i in range(12):
                c.put((t, i), np.zeros(64, np.uint32))
            assert c.bytes_used == 12 * 256  # transiently over budget
            assert len(c) == 12
        assert c.bytes_used <= 1000  # settled on exit
        assert c.get((t, 11)) is not None  # LRU tail kept, head dropped
        assert c.get((t, 0)) is None


class TestFragmentUnderBudget:
    def test_topn_row_counts_stay_under_budget(self):
        """Open a many-row fragment, run batched row counts (the TopN pass-2
        shape), and assert device residency never exceeds the budget."""
        old_budget = DEVICE_CACHE.budget_bytes
        row_bytes = WORDS_PER_ROW * 4
        n_rows = 512
        budget = 32 * row_bytes  # fits 32 of 512 rows
        DEVICE_CACHE.budget_bytes = budget
        try:
            f = Fragment(None, "i", "f", "standard", 0)
            f.open()
            rng = np.random.default_rng(0)
            rows = rng.integers(0, n_rows, 20_000).astype(np.uint64)
            cols = rng.integers(0, SHARD_WIDTH, 20_000).astype(np.uint64)
            f.bulk_import(rows, cols)
            ids = f.row_ids()
            assert len(ids) == n_rows
            counts = f.row_counts(ids, chunk=16)
            assert DEVICE_CACHE.bytes_used <= budget + 16 * row_bytes
            # correctness unaffected by eviction
            want = np.array([f.row_count(r) for r in ids], np.uint64)
            np.testing.assert_array_equal(counts, want)
        finally:
            DEVICE_CACHE.budget_bytes = old_budget

    def test_mutation_invalidates_then_rebuilds(self):
        f = Fragment(None, "i", "f", "standard", 0)
        f.open()
        f.set_bit(3, 100)
        before = int(np.asarray(f.row_device(3)).sum())
        f.set_bit(3, 200)
        arr = np.asarray(f.row_device(3))
        from pilosa_tpu.ops.bitmap import unpack_positions

        assert set(unpack_positions(arr).tolist()) == {100, 200}
        assert before != 0


def _owner_sums(c: DeviceCache, owner) -> int:
    """What owner_resident_bytes computed before it kept a running
    total: the sizes of the owner's live keys, summed. The oracle."""
    return sum(c._sizes[k] for k in c._by_owner.get(owner, ()))


class _CountingKey(tuple):
    """A cache key that counts how often it is hashed (CPython keeps no
    hash in a tuple: a real key re-hashes its 2 x S shard ints)."""

    hashes = 0

    def __hash__(self):
        _CountingKey.hashes += 1
        return tuple.__hash__(self)


def _put_counting_stacks(c: DeviceCache, owner, n: int = 512) -> None:
    """n entries under `owner`, keyed as hbm/residency.py keys a
    monolithic row stack of 149 shards (shards and versions per shard)."""
    shards = tuple(range(149))
    for r in range(n):
        c.put(
            _CountingKey((owner, "row", r, shards, 0, "mono", shards)),
            np.zeros(8, np.uint32),
        )


class TestOwnerResidentBytes:
    """owner_resident_bytes is a running total per owner; every admission
    reads it (sched/cost.py resident_bytes)."""

    @pytest.mark.parametrize("seed", range(8))
    def test_running_total_equals_sum_over_owner_keys(self, seed):
        rng = np.random.default_rng(seed)
        c = DeviceCache(budget_bytes=2000)
        owners = [new_owner_token() for _ in range(3)]
        pinned = []  # one entry per pin taken and not yet released

        def key():
            return (owners[rng.integers(3)], "row", int(rng.integers(12)))

        def arr():
            return np.zeros(int(rng.integers(1, 120)), np.uint32)

        def insert():
            k = key()
            cover = None if rng.integers(3) == 0 else [int(rng.integers(4))]
            c.put(k, arr(), extent=bool(rng.integers(2)), shards=cover)

        def build_pinned():
            k = key()
            c.get_or_build(k, arr, pin=True, shards=[int(rng.integers(4))])
            pinned.append(k)

        def replace():
            live = list(c._entries)
            if live:
                c.put(live[rng.integers(len(live))], arr())

        def pin():
            k = key()
            if c.pin_if_present(k):
                pinned.append(k)

        def unpin():
            if pinned:
                c.unpin(pinned.pop(rng.integers(len(pinned))))

        def drop_pinned():  # leaves a zombie until the last unpin
            if pinned:
                c.invalidate(pinned[rng.integers(len(pinned))])

        def deferred_burst():  # over budget inside, evicts on exit
            with c.deferred_eviction():
                for _ in range(6):
                    insert()
                    check()

        def clear():
            c.clear()
            pinned.clear()

        ops = [
            (insert, 8),
            (build_pinned, 2),
            (replace, 3),
            (pin, 3),
            (unpin, 4),
            (drop_pinned, 2),
            (lambda: c.invalidate(key()), 2),
            (lambda: c.invalidate_many([key(), key()]), 1),
            (lambda: c.invalidate_owner(owners[rng.integers(3)]), 1),
            (lambda: c.invalidate_owners(owners[:2]), 1),
            (
                lambda: c.invalidate_owner_shard(
                    owners[rng.integers(3)], int(rng.integers(4))
                ),
                2,
            ),
            (
                lambda: c.invalidate_owner_uncovered(owners[rng.integers(3)]),
                2,
            ),
            (deferred_burst, 1),
            (clear, 1),
        ]
        weights = np.array([w for _, w in ops], float)
        weights /= weights.sum()

        def check():
            assert set(c._owner_bytes) == set(c._by_owner)
            for o in owners:
                assert c.owner_resident_bytes(o) == _owner_sums(c, o)

        zombies_seen = 0
        for _ in range(600):
            ops[rng.choice(len(ops), p=weights)][0]()
            zombies_seen += bool(c._zombies)
            check()
        # the walk reached the states the total has to survive
        assert c.evictions
        assert zombies_seen
        c.clear()
        check()
        assert c._owner_bytes == {}

    def test_reading_the_total_hashes_no_entry_key(self):
        c = DeviceCache(budget_bytes=1 << 30)
        t = new_owner_token()
        _put_counting_stacks(c, t)
        before = _CountingKey.hashes
        assert c.owner_resident_bytes(t) == 512 * 32
        assert _CountingKey.hashes == before

    def test_estimate_hashes_no_entry_key(self):
        """512 stacks resident under the view a Count names: pricing the
        query for admission reads one total, not 512 keys."""
        from pilosa_tpu.core.field import FieldOptions
        from pilosa_tpu.core.holder import Holder
        from pilosa_tpu.pql import parse
        from pilosa_tpu.sched.cost import estimate

        h = Holder().open()
        idx = h.create_index("ownerbytes")
        f = idx.create_field("f", FieldOptions())
        f.set_bit(1, 7)
        (view,) = f.views.values()
        t = view._stack_token
        try:
            _put_counting_stacks(DEVICE_CACHE, t)
            q = parse("Count(Intersect(Row(f=1), Row(f=2)))")
            before = _CountingKey.hashes
            cost = estimate(idx, q, shards=[0])
            assert _CountingKey.hashes == before
            # two row stacks of one shard, less what the view holds
            assert cost.device_bytes == 2 * WORDS_PER_ROW * 4 - 512 * 32
        finally:
            DEVICE_CACHE.invalidate_owner(t)
            h.close()
