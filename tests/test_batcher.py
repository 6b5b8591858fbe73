"""Cross-request group-commit Count batching (exec/batcher.py).

VERDICT r4 #3: concurrent single-Count clients must share dispatches —
per-query system latency approaches RTT/N + device time instead of each
client paying the full round trip (the reference gives concurrent
requests no cross-request amortization; its worker pool only bounds
fan-out, executor.go:2559-2613)."""

import threading
import time

import numpy as np
import pytest

from pilosa_tpu.exec import batcher as batchmod
from pilosa_tpu.exec.batcher import CountBatcher
from pilosa_tpu.pql import parse
from pilosa_tpu.server.node import NodeServer
from pilosa_tpu.shardwidth import SHARD_WIDTH


def _reset_stats():
    for k in batchmod.STATS:
        batchmod.STATS[k] = 0


class TestBatchable:
    def test_pure_counts(self):
        assert batchmod.batchable(parse("Count(Row(f=1))"))
        assert batchmod.batchable(
            parse("Count(Row(f=1))Count(Intersect(Row(f=1), Row(f=2)))")
        )

    def test_rejects_non_counts(self):
        assert not batchmod.batchable(parse("Row(f=1)"))
        assert not batchmod.batchable(parse("Count(Row(f=1))Row(f=2)"))
        assert not batchmod.batchable(parse("Set(1, f=1)"))
        assert not batchmod.batchable(parse("TopN(f, n=3)"))


def _await_queued(b, n, index="i"):
    """Wait until n waiters sit in the batcher's queue for `index`."""
    for _ in range(1000):
        with b._mu:
            if len(b._queue.get(index, [])) >= n:
                return
        time.sleep(0.005)
    raise AssertionError("waiters never queued")


class TestGroupCommit:
    def test_leader_runs_alone_immediately(self):
        b = CountBatcher()
        calls = []
        out = b.run("i", parse("Count(Row(f=1))"), lambda q: calls.append(q) or [7])
        assert out == [7]
        assert len(calls) == 1 and len(calls[0].calls) == 1

    def test_waiters_merge_into_one_execution(self):
        b = CountBatcher()
        entered = threading.Event()
        release = threading.Event()
        execs = []

        def execute(q):
            execs.append(len(q.calls))
            if len(execs) == 1:
                entered.set()
                release.wait(5)  # hold the leader so followers queue
            return list(range(len(q.calls)))

        results = {}

        def client(name):
            results[name] = b.run("i", parse("Count(Row(f=1))"), execute)

        leader = threading.Thread(target=client, args=("leader",))
        leader.start()
        assert entered.wait(5)  # leader is now inside execute()
        followers = [
            threading.Thread(target=client, args=(f"f{i}",)) for i in range(4)
        ]
        for i, t in enumerate(followers):
            t.start()
            _await_queued(b, i + 1)  # f{i} enqueued behind the busy leader
        release.set()
        leader.join(5)
        for t in followers:
            t.join(5)
        # leader ran alone; all 4 followers merged into ONE execution
        assert execs == [1, 4]
        assert results["leader"] == [0]
        for i in range(4):
            assert results[f"f{i}"] == [i]  # sliced back in queue order

    def test_promoted_leader_merges_own_query(self):
        """Arrivals during a batch round get served by a PROMOTED leader
        that merges its own query into the next round — under sustained
        load every round is a full batch, not leader-solo alternation."""
        b = CountBatcher()
        entered = [threading.Event(), threading.Event()]
        gates = [threading.Event(), threading.Event()]
        execs = []

        def execute(q):
            i = len(execs)
            execs.append(len(q.calls))
            if i < len(gates):
                entered[i].set()
                gates[i].wait(5)
            return list(range(len(q.calls)))

        results = {}

        def client(name):
            results[name] = b.run("i", parse("Count(Row(f=1))"), execute)

        leader = threading.Thread(target=client, args=("L",))
        leader.start()
        assert entered[0].wait(5)  # leader inside exec 0
        ab = [threading.Thread(target=client, args=(n,)) for n in ("A", "B")]
        for t in ab:
            t.start()
        _await_queued(b, 2)  # A, B queued
        gates[0].set()  # leader finishes; round [A, B] starts (exec 1)
        assert entered[1].wait(5)
        cd = [threading.Thread(target=client, args=(n,)) for n in ("C", "D")]
        for t in cd:
            t.start()
        _await_queued(b, 2)  # C, D queued behind the running round
        gates[1].set()  # round [A, B] finishes -> C promoted
        for t in [leader] + ab + cd:
            t.join(5)
        # exec 2 must carry BOTH C and D (merged), not C solo then D
        assert execs == [1, 2, 2], execs
        assert results["C"] == [0] and results["D"] == [1]

    def test_error_isolation(self):
        b = CountBatcher()
        entered = threading.Event()
        release = threading.Event()
        state = {"n": 0}

        def execute(q):
            state["n"] += 1
            if state["n"] == 1:
                entered.set()
                release.wait(5)
                return [1]
            if any("boom" in c.children[0].args for c in q.calls):
                raise ValueError("boom")
            return [len(q.calls)] * len(q.calls)

        results, errors = {}, {}

        def client(name, pql):
            try:
                results[name] = b.run("i", parse(pql), execute)
            except ValueError as e:
                errors[name] = str(e)

        leader = threading.Thread(target=client, args=("L", "Count(Row(f=1))"))
        leader.start()
        assert entered.wait(5)
        good = threading.Thread(target=client, args=("good", "Count(Row(f=1))"))
        bad = threading.Thread(target=client, args=("bad", "Count(Row(boom=1))"))
        good.start()
        bad.start()
        _await_queued(b, 2)
        release.set()
        for t in (leader, good, bad):
            t.join(5)
        # merged exec raised -> split: the good query still answers, only
        # the bad one errors
        assert results["good"] == [1]
        assert errors["bad"] == "boom"

    def test_batch_size_cap(self):
        b = CountBatcher()
        entered = threading.Event()
        release = threading.Event()
        execs = []

        def execute(q):
            execs.append(len(q.calls))
            if len(execs) == 1:
                entered.set()
                release.wait(5)
            return [0] * len(q.calls)

        threads = [
            threading.Thread(
                target=lambda: b.run("i", parse("Count(Row(f=1))"), execute)
            )
            for _ in range(batchmod.MAX_BATCH_CALLS + 10)
        ]
        threads[0].start()
        assert entered.wait(5)
        for t in threads[1:]:
            t.start()
        _await_queued(b, len(threads) - 1)
        release.set()
        for t in threads:
            t.join(5)
        assert execs[0] == 1
        assert max(execs) <= batchmod.MAX_BATCH_CALLS
        # padding rounds batches up to pow2, so total calls executed can
        # exceed the real query count but never by more than 2x
        assert batchmod.MAX_BATCH_CALLS + 10 <= sum(execs) <= 2 * (
            batchmod.MAX_BATCH_CALLS + 10
        )

    def test_pow2_padding_uses_noop_lanes(self):
        """Satellite: pad lanes are zero-row no-ops (Count(Difference())
        -> PZero), NOT repeats of the last real call — repeating a heavy
        call wasted up to ~2x device work on odd batch sizes. Pads are
        masked out: every waiter gets exactly its own results."""
        b = CountBatcher()
        entered = threading.Event()
        release = threading.Event()
        merged_queries = []

        def execute(q):
            merged_queries.append(q)
            if len(merged_queries) == 1:
                entered.set()
                release.wait(5)
            return list(range(len(q.calls)))

        threads = [
            threading.Thread(
                target=lambda: b.run("i", parse("Count(Row(f=1))"), execute)
            )
        ]
        threads[0].start()
        assert entered.wait(5)  # it took leadership and blocks in execute
        outs = []
        for _ in range(3):  # 3 waiters -> merged round of 3, padded to 4
            th = threading.Thread(
                target=lambda: outs.append(
                    b.run("i", parse("Count(Row(f=1))"), execute)
                )
            )
            th.start()
            threads.append(th)
        _await_queued(b, 3)
        release.set()
        for th in threads:
            th.join(5)
        merged = next(q for q in merged_queries if len(q.calls) == 4)
        real, pad = merged.calls[:3], merged.calls[3]
        assert all(c.name == "Count" for c in merged.calls)
        assert all(c.children[0].name == "Row" for c in real)
        # the pad lane is the zero-row no-op, not a repeat of a real call
        assert pad.children[0].name == "Difference"
        assert not pad.children[0].children
        # pads masked out: each waiter saw exactly one (its own) result
        assert sorted(len(o) for o in outs) == [1, 1, 1]

    def test_noop_pad_call_counts_zero_end_to_end(self):
        """The pad lane must execute as a true no-op on the real
        executor: Count(Difference()) == 0 whatever data exists."""
        from pilosa_tpu.core.field import FieldOptions
        from pilosa_tpu.core.holder import Holder
        from pilosa_tpu.exec import Executor
        from pilosa_tpu.pql import Query

        h = Holder().open()
        idx = h.create_index("padx")
        f = idx.create_field("f", FieldOptions())
        f.set_bit(1, 7)
        ex = Executor(h)
        pad = batchmod._noop_pad_call()
        assert ex.execute("padx", Query(calls=[pad])) == [0]
        # and merged next to a real call, results stay position-correct
        got = ex.execute(
            "padx", Query(calls=[parse("Count(Row(f=1))").calls[0], pad])
        )
        assert got == [1, 0]

    def test_indexes_batch_independently(self):
        b = CountBatcher()
        entered = threading.Event()
        release = threading.Event()
        execs = []

        def execute(q):
            execs.append(len(q.calls))
            if len(execs) == 1:
                entered.set()
                release.wait(5)
            return [0] * len(q.calls)

        t1 = threading.Thread(target=lambda: b.run("a", parse("Count(Row(f=1))"), execute))
        t1.start()
        assert entered.wait(5)
        # different index: must NOT queue behind index a's leader
        out = b.run("b", parse("Count(Row(f=1))"), lambda q: [42])
        assert out == [42]
        release.set()
        t1.join(5)


class TestEndToEnd:
    @pytest.fixture()
    def server(self):
        srv = NodeServer(None, "batch-test")
        srv.start()
        yield srv
        srv.stop()

    def test_concurrent_clients_share_dispatches(self, server, monkeypatch):
        api = server.api
        api.create_index("bi")
        api.create_field("bi", "f")
        idx = server.holder.index("bi")
        f = idx.field("f")
        rng = np.random.default_rng(5)
        for row in (1, 2):
            cols = rng.integers(0, 4 * SHARD_WIDTH, 5000).astype(np.uint64)
            f.import_bits(np.full(len(cols), row, np.uint64), cols)
        q = "Count(Intersect(Row(f=1), Row(f=2)))"
        (expect,) = api.query("bi", q)  # warm + truth
        # a result-cache hit is tens of microseconds of pure Python, far
        # under the interpreter's 5 ms switch interval, so on their own
        # the clients run one after the other and never meet in the
        # batcher. Each round's first leader therefore holds its
        # execution until a second client has queued behind it (the
        # Event of the unit tests above, end to end)
        real_execute = server.executor.execute_response
        held = []

        def execute(*a, **k):
            if not held:
                held.append(True)
                deadline = time.monotonic() + 5
                while (
                    not server.count_batcher._queue.get("bi")
                    and time.monotonic() < deadline
                ):
                    time.sleep(0.001)
            return real_execute(*a, **k)

        monkeypatch.setattr(server.executor, "execute_response", execute)
        # overlap is timing-dependent, so retry the round until at least
        # one batch forms (locked STATS make the totals exact per round);
        # a round is milliseconds, and at 5 rounds none overlapped in about
        # one run in six on an 8-core host
        for _ in range(40):
            _reset_stats()
            del held[:]
            results = []
            errs = []

            def client():
                try:
                    for _ in range(3):
                        results.append(api.query("bi", q)[0])
                except Exception as e:  # noqa: BLE001
                    errs.append(e)

            threads = [threading.Thread(target=client) for _ in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(10)
            assert not errs
            assert results == [expect] * 24
            s = batchmod.STATS
            assert s["leader"] + s["batched"] == 24
            assert s["fallback_splits"] == 0
            if s["batched"] >= 1:
                break
        assert s["leader"] >= 1
        assert s["batched"] >= 1  # some clients coalesced

    def test_batched_counts_survive_node_failover(self):
        """Concurrent batched Counts against a replicated cluster keep
        answering correctly while a node dies mid-stream: the merged
        executions fan out through the distributed executor, which
        re-maps dead owners to live replicas; a failing merged exec
        splits per-query rather than poisoning batchmates."""
        from pilosa_tpu.testing import ClusterHarness

        with ClusterHarness(3, replica_n=2, in_memory=True) as cluster:
            api = cluster[0].api
            api.create_index("fi")
            api.create_field("fi", "f")
            rng = np.random.default_rng(12)
            cols = rng.integers(0, 6 * SHARD_WIDTH, 2500).astype(np.uint64)
            q = "".join(f"Set({int(c)}, f=1)" for c in cols[:400])
            api.query("fi", q)
            expect = len({int(c) for c in cols[:400]})
            qc = "Count(Row(f=1))"
            assert api.query("fi", qc)[0] == expect  # warm
            stop_at = threading.Event()
            errs, got = [], []

            def client():
                try:
                    for i in range(6):
                        got.append(api.query("fi", qc)[0])
                        if i == 1:
                            stop_at.set()
                except Exception as e:  # noqa: BLE001
                    errs.append(e)

            threads = [threading.Thread(target=client) for _ in range(6)]
            for t in threads:
                t.start()
            assert stop_at.wait(10)
            cluster.stop_node(2)  # mid-stream kill; replicas hold the data
            for t in threads:
                t.join(30)
            assert not errs, errs[:1]
            assert got == [expect] * 36

    def test_non_count_queries_bypass(self, server):
        api = server.api
        api.create_index("bj")
        api.create_field("bj", "f")
        api.query("bj", "Set(1, f=1)Set(9, f=1)")
        _reset_stats()
        (row,) = api.query("bj", "Row(f=1)")
        assert sorted(int(c) for c in row.columns()) == [1, 9]
        assert batchmod.STATS["leader"] == 0  # never entered the batcher


class TestBatchSizeStat:
    def test_solo_round_records_one(self):
        from pilosa_tpu.utils.stats import StatsClient

        b = CountBatcher()
        st = StatsClient()
        b.stats = st
        b.run("i", parse("Count(Row(f=1))"), lambda q: [1])
        hist = st.registry.snapshot().get("batcher.batch_size")
        assert hist is not None and hist["count"] == 1 and hist["max"] == 1

    def test_merged_round_records_total_calls(self):
        from pilosa_tpu.utils.stats import StatsClient

        b = CountBatcher()
        st = StatsClient()
        b.stats = st
        release = threading.Event()
        started = threading.Event()

        def execute(q):
            started.set()
            if not release.is_set():
                release.wait(5)
            return list(range(len(q.calls)))

        results = {}

        def follower(i):
            results[i] = b.run("i", parse("Count(Row(f=2))"), execute)

        leader = threading.Thread(
            target=lambda: b.run("i", parse("Count(Row(f=1))"), execute),
            daemon=True,
        )
        leader.start()
        started.wait(5)
        followers = [
            threading.Thread(target=follower, args=(i,), daemon=True)
            for i in range(3)
        ]
        for th in followers:
            th.start()
        _await_queued(b, 3)  # all three queued behind the leader
        release.set()
        leader.join(5)
        for th in followers:
            th.join(5)
        hist = st.registry.snapshot()["batcher.batch_size"]
        assert hist["max"] >= 3  # the merged follower round
        assert all(len(r) == 1 for r in results.values())

    def test_run_builds_its_queue_as_a_deque(self):
        """The waiter queue created by run() itself must be a deque —
        the list-as-queue pop(0) was O(n) per dequeue (satellite fix)."""
        from collections import deque

        b = CountBatcher()
        release = threading.Event()
        started = threading.Event()

        def execute(q):
            started.set()
            release.wait(5)
            return list(range(len(q.calls)))

        leader = threading.Thread(
            target=lambda: b.run("i", parse("Count(Row(f=1))"), execute),
            daemon=True,
        )
        leader.start()
        started.wait(5)
        follower = threading.Thread(
            target=lambda: b.run("i", parse("Count(Row(f=2))"), execute),
            daemon=True,
        )
        follower.start()
        _await_queued(b, 1)
        with b._mu:
            queue_obj = b._queue.get("i")
        assert isinstance(queue_obj, deque), type(queue_obj)
        release.set()
        leader.join(5)
        follower.join(5)
