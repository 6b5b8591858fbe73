"""Query admission control & QoS scheduling (pilosa_tpu/sched/).

Unit tests drive the AdmissionController on an injectable clock (no real
sleeps for deadline logic); the saturation tests boot a real node and
assert the acceptance contract: in-flight executions never exceed
max-concurrent-queries, excess queries get 429 + Retry-After instead of
unbounded queueing, interactive dequeues ahead of batch, shed queries
leave no queue residue (the conftest leak guard re-checks), and the
scheduler's load feed pushes CountBatcher rounds to >= 4 calls."""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from pilosa_tpu.core.devcache import DEVICE_CACHE
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.core.resultcache import RESULT_CACHE
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec import batcher as batchmod
from pilosa_tpu.exec.batcher import CountBatcher
from pilosa_tpu.parallel import mesh as pmesh
from pilosa_tpu.pql import parse
from pilosa_tpu.sched.admission import AdmissionController, ShedError
from pilosa_tpu.sched.cost import QueryCost, estimate
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_ROW
from pilosa_tpu.testing import ClusterHarness
from pilosa_tpu.utils.stats import StatsClient


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _wait_until(pred, timeout=5.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.002)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------
# cost estimation
# ---------------------------------------------------------------------------

_STACK = WORDS_PER_ROW * 4  # one row stack of one shard, bytes


@pytest.fixture
def two_warm_fields():
    """An index on one device whose field f has one row stack resident
    and whose field g has two, over four shards; the result cache off so
    the estimate reaches the residency discount."""
    old_mesh = pmesh.active_mesh()
    pmesh.set_active_mesh(None)
    old_budget = DEVICE_CACHE.budget_bytes
    old_result_budget = RESULT_CACHE.budget_bytes
    DEVICE_CACHE.clear()
    DEVICE_CACHE.budget_bytes = 1 << 30
    RESULT_CACHE.configure(budget_bytes=0)
    h = Holder().open()
    idx = h.create_index("twofields")
    shards = [0, 1, 2, 3]
    for name in ("f", "g"):
        fld = idx.create_field(name, FieldOptions())
        for row in range(3):
            for s in shards:
                fld.set_bit(row, s * SHARD_WIDTH + row)
    ex = Executor(h)
    ex.execute("twofields", "Count(Row(f=0))")
    ex.execute("twofields", "Count(Intersect(Row(g=0), Row(g=1)))")
    yield idx, shards
    h.close()
    DEVICE_CACHE.clear()
    DEVICE_CACHE.budget_bytes = old_budget
    RESULT_CACHE.configure(budget_bytes=old_result_budget)
    pmesh.set_active_mesh(old_mesh)


class TestCost:
    def test_bsi_heavier_than_plain_row(self):
        plain = estimate(None, parse("Count(Row(f=1))"), shards=[0])
        bsi = estimate(None, parse("Count(Row(v > 7))"), shards=[0])
        assert plain.device_bytes > 0
        assert bsi.device_bytes > plain.device_bytes

    def test_writes_carry_no_device_weight(self):
        w = estimate(None, parse("Set(1, f=1)"), shards=[0])
        assert w.write
        assert w.device_bytes == 0

    def test_more_shards_cost_more(self):
        one = estimate(None, parse("Count(Row(f=1))"), shards=[0])
        four = estimate(None, parse("Count(Row(f=1))"), shards=[0, 1, 2, 3])
        assert four.device_bytes == 4 * one.device_bytes

    def test_raw_text_and_garbage_never_raise(self):
        assert estimate(None, "Count(Row(f=1))").sweeps >= 1
        assert estimate(None, "This(Is(Not PQL").device_bytes == 0

    @pytest.mark.parametrize(
        "pql, shards, want",
        [
            ("Count(Row(f=1))", [0], QueryCost(_STACK, 1)),
            ("Count(Row(f=1))", [0, 1, 2, 3], QueryCost(4 * _STACK, 1)),
            ("Count(Intersect(Row(f=1), Row(f=2)))", [0], QueryCost(2 * _STACK, 1)),
            ("TopN(f, n=3)", [0, 1], QueryCost(32 * _STACK, 1)),
            ("Set(1, f=1)", [0], QueryCost(0, 0, write=True)),
            (
                "Set(1, f=1)Count(Not(Row(f=1)))",
                [0],
                QueryCost(2 * _STACK, 1, write=True),
            ),
        ],
    )
    def test_exact_cost_without_an_index(self, pql, shards, want):
        assert estimate(None, parse(pql), shards=shards) == want

    def test_discount_is_the_touched_fields_resident_bytes(self, two_warm_fields):
        """Two fields resident: a query is discounted by exactly what the
        cache holds for the views of the field it names (summed here key
        by key, as the estimate itself did before the cache kept a total
        per owner), and by nothing of the other field's."""
        idx, shards = two_warm_fields

        def held(field):
            return sum(
                DEVICE_CACHE._sizes[k]
                for v in idx.field(field).views.values()
                for k in DEVICE_CACHE._by_owner.get(v._stack_token, ())
            )

        stack = len(shards) * _STACK
        assert held("f") == stack and held("g") == 2 * stack
        q3 = "Count(Union(Row({0}=0), Row({0}=1), Row({0}=2)))"
        for field in ("f", "g"):
            got = estimate(idx, parse(q3.format(field)), shards)
            assert got == QueryCost(3 * stack - held(field), 1)
        q4 = "Count(Union(Row(f=0), Row(f=1), Row(f=2), Row(g=0)))"
        both = estimate(idx, parse(q4), shards)
        assert both == QueryCost(4 * stack - held("f") - held("g"), 1)
        # a field with nothing resident keeps its whole weight
        idx.create_field("cold", FieldOptions()).set_bit(1, 7)
        assert estimate(idx, parse("Count(Row(cold=1))"), shards) == QueryCost(stack, 1)


# ---------------------------------------------------------------------------
# AdmissionController units (injectable clock, no server)
# ---------------------------------------------------------------------------


class TestAdmission:
    def test_immediate_admit_and_release(self):
        ctl = AdmissionController(max_concurrent=2, clock=FakeClock())
        t1 = ctl.admit()
        t2 = ctl.admit(cls="batch")
        assert ctl.pending() == (0, 2)
        t1.release()
        t2.release()
        t2.release()  # idempotent
        assert ctl.pending() == (0, 0)

    def test_unknown_class_falls_back_to_default(self):
        ctl = AdmissionController(default_class="batch")
        t = ctl.admit(cls="platinum")
        assert t.cls == "batch"
        t.release()

    def test_queued_grant_on_release(self):
        ctl = AdmissionController(max_concurrent=1)
        t1 = ctl.admit()
        got = []
        th = threading.Thread(
            target=lambda: got.append(ctl.admit()), daemon=True
        )
        th.start()
        _wait_until(lambda: ctl.queue_depth() == 1, what="waiter queued")
        assert ctl.pending() == (1, 1)
        t1.release()
        th.join(5)
        assert got and got[0].waited >= 0.0
        got[0].release()
        assert ctl.pending() == (0, 0)

    def test_shed_when_queue_full_carries_retry_after(self):
        ctl = AdmissionController(
            max_concurrent=1, queue_depth=0, retry_after=3.5
        )
        t1 = ctl.admit()
        with pytest.raises(ShedError) as ei:
            ctl.admit()
        assert ei.value.retry_after == 3.5
        assert ei.value.status == 429
        t1.release()
        assert ctl.pending() == (0, 0)

    def test_deadline_exhausted_on_arrival_sheds(self):
        ctl = AdmissionController(clock=FakeClock())
        with pytest.raises(ShedError):
            ctl.admit(deadline=0.0)
        assert ctl.pending() == (0, 0)

    def test_deadline_expiring_in_queue_sheds_without_residue(self):
        clock = FakeClock()
        ctl = AdmissionController(max_concurrent=1, clock=clock)
        t1 = ctl.admit()
        sheds = []
        def waiter():
            try:
                ctl.admit(deadline=1.0)
            except ShedError as e:
                sheds.append(e)
        th = threading.Thread(target=waiter, daemon=True)
        th.start()
        _wait_until(lambda: ctl.queue_depth() == 1, what="waiter queued")
        clock.advance(2.0)  # its deadline is now in the past
        t1.release()  # pump purges the expired head and wakes it
        th.join(5)
        assert sheds, "expired waiter must shed, not run"
        assert ctl.pending() == (0, 0)

    def test_weighted_fair_interactive_dequeues_ahead_of_batch(self):
        ctl = AdmissionController(max_concurrent=1)
        filler = ctl.admit(cls="batch")
        order = []
        olock = threading.Lock()

        def worker(cls):
            t = ctl.admit(cls=cls)
            with olock:
                order.append(cls)
            t.release()

        threads = []
        # enqueue batch FIRST: arrival order must not beat class weight
        for i, cls in enumerate(
            ["batch", "batch", "batch", "interactive", "interactive",
             "interactive"]
        ):
            th = threading.Thread(target=worker, args=(cls,), daemon=True)
            th.start()
            threads.append(th)
            _wait_until(
                lambda n=i: ctl.queue_depth() == n + 1, what="enqueue"
            )
        filler.release()
        for th in threads:
            th.join(5)
        assert order == ["interactive"] * 3 + ["batch"] * 3
        assert ctl.pending() == (0, 0)

    def test_byte_budget_gates_inflight(self):
        ctl = AdmissionController(max_concurrent=8, byte_budget=100)
        t1 = ctl.admit(cost=QueryCost(device_bytes=60))
        granted = []
        th = threading.Thread(
            target=lambda: granted.append(
                ctl.admit(cost=QueryCost(device_bytes=60))
            ),
            daemon=True,
        )
        th.start()
        _wait_until(lambda: ctl.queue_depth() == 1, what="byte-gated waiter")
        assert not granted  # 60 + 60 > 100: must wait despite free slots
        t1.release()
        th.join(5)
        assert granted
        granted[0].release()
        assert ctl.pending() == (0, 0)

    def test_oversized_query_still_runs_alone(self):
        ctl = AdmissionController(max_concurrent=8, byte_budget=100)
        t = ctl.admit(cost=QueryCost(device_bytes=10_000))
        assert ctl.pending() == (0, 1)
        t.release()

    def test_stats_emitted(self):
        st = StatsClient()
        ctl = AdmissionController(
            max_concurrent=1, queue_depth=0, stats=st
        )
        t = ctl.admit()
        with pytest.raises(ShedError):
            ctl.admit(cls="batch")
        t.release()
        snap = st.registry.snapshot()
        # admit/shed carry class AND index labels ("-" = no index bound);
        # shed additionally carries the reason tag
        assert snap.get("sched.admit;class:interactive,index:-") == 1
        assert snap.get("sched.shed;class:batch,index:-,reason:queue") == 1
        assert "sched.queue_depth" in snap
        assert "sched.inflight" in snap


# ---------------------------------------------------------------------------
# adaptive batching: scheduler load feeds CountBatcher
# ---------------------------------------------------------------------------


def test_adaptive_batching_reaches_queue_depth(monkeypatch):
    """With the scheduler reporting load >= 4, a CountBatcher leader
    holds until 4 calls line up and runs them as ONE merged round —
    observable via the batcher.batch_size stat (acceptance criterion)."""
    for k in batchmod.STATS:
        batchmod.STATS[k] = 0
    ctl = AdmissionController(max_concurrent=8)
    st = StatsClient()
    b = CountBatcher()
    b.stats = st
    b.load_hint = ctl.load  # the NodeServer wiring, minus the server
    b.hold_timeout = 2.0  # generous: determinism over latency in tests
    # 4 batchable (pure-Count) queries in flight on index "i"
    tickets = [ctl.admit(batchable=True, index="i") for _ in range(4)]
    results = {}

    def client(i):
        results[i] = b.run(
            "i",
            parse("Count(Row(f=1))"),
            lambda q: list(range(len(q.calls))),
        )

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(4)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join(10)
    for t in tickets:
        t.release()
    assert all(len(r) == 1 for r in results.values())
    assert batchmod.STATS["merged_execs"] == 1  # ONE merged dispatch
    hist = st.registry.snapshot().get("batcher.batch_size")
    assert hist is not None and hist["max"] >= 4


# ---------------------------------------------------------------------------
# saturation over a real node (HTTP)
# ---------------------------------------------------------------------------


def _post_query(uri, index, pql, headers=None):
    req = urllib.request.Request(
        f"{uri}/index/{index}/query",
        data=json.dumps({"query": pql}).encode(),
        method="POST",
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    with urllib.request.urlopen(req, timeout=15) as resp:
        return resp.status, json.loads(resp.read())


def _gated_executor(srv):
    """Wrap the node's executor so executions block on a gate while the
    test builds up saturation; records peak concurrency + order."""
    orig = srv.executor.execute_response
    state = {"cur": 0, "max": 0, "order": []}
    lock = threading.Lock()
    gate = threading.Event()

    def gated(index, query, shards=None, opt=None, **kw):
        with lock:
            state["cur"] += 1
            state["max"] = max(state["max"], state["cur"])
            state["order"].append(str(query))
        try:
            gate.wait(15)
            return orig(index, query, shards=shards, opt=opt, **kw)
        finally:
            with lock:
                state["cur"] -= 1

    srv.executor.execute_response = gated
    return gate, state


def test_saturation_sheds_429_and_bounds_inflight():
    with ClusterHarness(
        1,
        in_memory=True,
        max_concurrent_queries=2,
        admission_queue_depth=2,
        shed_retry_after=7.5,
    ) as c:
        srv = c[0]
        uri = srv.node.uri
        srv.api.create_index("sat")
        srv.api.create_field("sat", "f", {"type": "set"})
        srv.api.query("sat", "Set(1, f=1)")
        gate, state = _gated_executor(srv)
        outcomes = []
        olock = threading.Lock()

        def client():
            try:
                status, _ = _post_query(uri, "sat", "Row(f=1)")
                with olock:
                    outcomes.append((status, None))
            except urllib.error.HTTPError as e:
                with olock:
                    outcomes.append(
                        (
                            e.code,
                            (
                                e.headers.get("Retry-After"),
                                e.headers.get("X-Pilosa-Retry-After"),
                            ),
                        )
                    )
                e.close()

        threads = [
            threading.Thread(target=client, daemon=True) for _ in range(8)
        ]
        for th in threads:
            th.start()
        # 2 executing + 2 queued + 4 shed, all before the gate opens
        _wait_until(
            lambda: len(outcomes) == 4
            and state["cur"] == 2
            and srv.scheduler.queue_depth() == 2,
            what="saturation to settle (4 sheds, 2 executing, 2 queued)",
        )
        # shed queries carry 429 + the configured Retry-After: RFC
        # delta-seconds (integer) on the standard header, the precise
        # value on the vendor header
        assert all(code == 429 for code, _ in outcomes)
        assert all(ra == ("8", "7.5") for _, ra in outcomes)
        gate.set()
        for th in threads:
            th.join(15)
        assert len(outcomes) == 8
        assert sorted(code for code, _ in outcomes) == [200] * 4 + [429] * 4
        # admitted in-flight executions never exceeded the cap
        assert state["max"] <= 2
        # no shed query left queue residue
        assert srv.scheduler.pending() == (0, 0)
        # acceptance: sched stats visible on /metrics
        with urllib.request.urlopen(f"{uri}/metrics", timeout=10) as resp:
            text = resp.read().decode()
        assert "pilosa_tpu_sched_queue_depth" in text
        assert "pilosa_tpu_sched_shed" in text
        assert "pilosa_tpu_sched_wait_ms_count" in text
        assert "pilosa_tpu_sched_admit" in text


def test_priority_header_orders_dequeue_over_http():
    with ClusterHarness(
        1,
        in_memory=True,
        max_concurrent_queries=1,
        admission_queue_depth=8,
    ) as c:
        srv = c[0]
        uri = srv.node.uri
        srv.api.create_index("pri")
        srv.api.create_field("pri", "f", {"type": "set"})
        srv.api.query("pri", "Set(1, f=1) Set(1, f=2) Set(1, f=3)")
        gate, state = _gated_executor(srv)
        threads = []

        def client(pql, cls):
            def run():
                try:
                    _post_query(
                        uri, "pri", pql, headers={"X-Pilosa-Priority": cls}
                    )
                except urllib.error.HTTPError as e:
                    e.close()

            th = threading.Thread(target=run, daemon=True)
            th.start()
            threads.append(th)

        client("Row(f=1)", "batch")  # filler: occupies the single slot
        _wait_until(lambda: state["cur"] == 1, what="filler executing")
        # batch legs enqueue FIRST; interactive must still dequeue ahead
        for pql, cls in [
            ("Row(f=11)", "batch"),
            ("Row(f=12)", "batch"),
            ("Row(f=21)", "interactive"),
            ("Row(f=22)", "interactive"),
        ]:
            n_before = srv.scheduler.queue_depth()
            client(pql, cls)
            _wait_until(
                lambda n=n_before: srv.scheduler.queue_depth() == n + 1,
                what="leg queued",
            )
        gate.set()
        for th in threads:
            th.join(15)
        order = [q for q in state["order"] if "f=1)" not in q]
        interactive_pos = [
            i for i, q in enumerate(order) if "f=2" in q
        ]
        batch_pos = [i for i, q in enumerate(order) if "f=1" in q]
        assert max(interactive_pos) < min(batch_pos), order
        assert srv.scheduler.pending() == (0, 0)


def test_exhausted_internode_deadline_sheds_early():
    """A leg arriving with an already-spent X-Pilosa-Deadline budget is
    shed immediately (429, retryable) instead of timing out late."""
    with ClusterHarness(1, in_memory=True) as c:
        srv = c[0]
        uri = srv.node.uri
        srv.api.create_index("dl")
        srv.api.create_field("dl", "f", {"type": "set"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post_query(
                uri, "dl", "Row(f=1)", headers={"X-Pilosa-Deadline": "0"}
            )
        assert ei.value.code == 429
        assert ei.value.headers.get("Retry-After") is not None
        ei.value.close()
        assert srv.scheduler.pending() == (0, 0)


# ---------------------------------------------------------------------------
# review-hardening regressions
# ---------------------------------------------------------------------------


def test_uncontended_grants_bank_no_wfq_credit():
    """Fast-path (uncontended) grants must not advance WFQ virtual time:
    a long interactive-only warmup would otherwise bank enough lag that
    batch dequeues FIRST when contention starts — priority inversion."""
    ctl = AdmissionController(max_concurrent=1)
    for _ in range(50):
        ctl.admit().release()  # interactive warmup, all uncontended
    filler = ctl.admit()
    order = []
    olock = threading.Lock()

    def worker(cls):
        t = ctl.admit(cls=cls)
        with olock:
            order.append(cls)
        t.release()

    threads = []
    for i, cls in enumerate(["batch", "interactive"]):
        th = threading.Thread(target=worker, args=(cls,), daemon=True)
        th.start()
        threads.append(th)
        _wait_until(lambda n=i: ctl.queue_depth() == n + 1, what="enqueue")
    filler.release()
    for th in threads:
        th.join(5)
    assert order == ["interactive", "batch"]
    assert ctl.pending() == (0, 0)


def test_expired_head_unblocks_queue_without_a_release():
    """A byte-gated head expiring in the queue must pump: entries behind
    it that now fit run immediately, not at the next ticket release."""
    clock = FakeClock()
    ctl = AdmissionController(max_concurrent=4, byte_budget=100, clock=clock)
    t1 = ctl.admit(cost=QueryCost(device_bytes=60))
    sheds, grants = [], []

    def fat():
        try:
            ctl.admit(cost=QueryCost(device_bytes=60), deadline=1.0)
        except ShedError as e:
            sheds.append(e)

    def cheap():
        grants.append(ctl.admit(cost=QueryCost(device_bytes=10)))

    tf = threading.Thread(target=fat, daemon=True)
    tf.start()
    _wait_until(lambda: ctl.queue_depth() == 1, what="fat queued")
    tc = threading.Thread(target=cheap, daemon=True)
    tc.start()
    _wait_until(lambda: ctl.queue_depth() == 2, what="cheap queued")
    clock.advance(2.0)  # fat's deadline passes; nothing releases
    tf.join(10)
    tc.join(10)
    assert sheds, "fat head must shed on its deadline"
    assert grants, "cheap entry must be granted by the shed's pump alone"
    grants[0].release()
    t1.release()
    assert ctl.pending() == (0, 0)


def test_load_hint_capped_at_concurrency_limit():
    """load() feeds the batcher's hold target; queued queries hold no
    ticket, so the hint must never exceed what can actually line up."""
    ctl = AdmissionController(max_concurrent=2, queue_depth=8)
    t1, t2 = ctl.admit(batchable=True), ctl.admit(batchable=True)
    threads = []
    for i in range(3):
        th = threading.Thread(
            target=lambda: ctl.admit(batchable=True).release(), daemon=True
        )
        th.start()
        threads.append(th)
        _wait_until(lambda n=i: ctl.queue_depth() == n + 1, what="queued")
    assert ctl.load() == 2  # min(2 inflight + 3 queued, cap 2)
    t1.release()
    t2.release()
    for th in threads:
        th.join(5)
    assert ctl.pending() == (0, 0)


def test_internode_429_is_breaker_neutral_and_honors_retry_after():
    """A loaded peer is not a dead peer: a 429 shed must not open the
    sender's circuit breaker, and the retry loop must honor the peer's
    Retry-After instead of the policy's (smaller) base backoff."""
    from pilosa_tpu.server import faults as fmod
    from pilosa_tpu.server.client import ClientError, InternalClient

    with ClusterHarness(
        1,
        in_memory=True,
        max_concurrent_queries=1,
        admission_queue_depth=0,
        shed_retry_after=0.01,
    ) as c:
        srv = c[0]
        uri = srv.node.uri
        srv.api.create_index("br")
        srv.api.create_field("br", "f", {"type": "set"})
        gate, state = _gated_executor(srv)
        th = threading.Thread(
            target=lambda: _post_query(uri, "br", "Row(f=1)"), daemon=True
        )
        th.start()
        _wait_until(lambda: state["cur"] == 1, what="slot occupied")
        sleeps = []
        reg = fmod.BreakerRegistry(threshold=1)
        policy = fmod.RetryPolicy(
            max_attempts=2, base_backoff=0.0001, sleep=sleeps.append
        )
        client = InternalClient(breakers=reg, retry_policy=policy)
        with pytest.raises(ClientError) as ei:
            client.query_node(uri, "br", "Count(Row(f=1))")
        assert ei.value.status == 429
        assert ei.value.retryable  # fan-out can fail over to a replica
        assert ei.value.retry_after == 0.01
        # both attempts shed, yet the breaker must stay closed
        assert reg.state(uri) == fmod.CLOSED
        assert sleeps and sleeps[-1] >= 0.01  # honored Retry-After
        gate.set()
        th.join(10)
        assert srv.scheduler.pending() == (0, 0)


def test_only_same_index_batchable_load_feeds_the_batcher_hint():
    """Row/TopN/remote traffic — and other indexes' Counts — can never
    join this index's count batch: a solo Count under mixed load must
    see load(index) <= 1 and pay no adaptive-hold window."""
    ctl = AdmissionController(max_concurrent=8)
    rows = [ctl.admit() for _ in range(3)]  # non-batchable in flight
    other = ctl.admit(batchable=True, index="other")  # different index
    assert ctl.load("i") == 0
    count = ctl.admit(batchable=True, index="i")
    assert ctl.load("i") == 1
    assert ctl.load("other") == 1
    count.release()
    other.release()
    for t in rows:
        t.release()
    assert ctl.pending() == (0, 0)


def test_class_debt_bounded_after_solo_saturation_epoch():
    """WFQ debt banked by a class that saturated alone must not starve
    it when mixed contention resumes later: re-activating classes are
    lifted to the global virtual clock, bounding the residual handicap
    to ~one service quantum (weight x a handful of grants, not the whole
    epoch)."""
    ctl = AdmissionController(max_concurrent=1, queue_depth=64)
    # batch-only saturated epoch: 3 CONTENDED batch grants bank debt
    filler = ctl.admit(cls="batch")
    for _ in range(3):
        nxt = []
        th = threading.Thread(
            target=lambda: nxt.append(ctl.admit(cls="batch")), daemon=True
        )
        th.start()
        _wait_until(lambda: ctl.queue_depth() == 1, what="epoch waiter")
        filler.release()
        th.join(5)
        filler = nxt[0]
    filler.release()  # idle: queues drained, nothing in flight
    assert ctl.pending() == (0, 0)
    # mixed contention resumes, interactive enqueued FIRST
    filler = ctl.admit()
    order = []
    olock = threading.Lock()

    def worker(cls):
        t = ctl.admit(cls=cls)
        with olock:
            order.append(cls)
        t.release()

    legs = ["interactive"] * 20 + ["batch"]
    threads = []
    for i, cls in enumerate(legs):
        th = threading.Thread(target=worker, args=(cls,), daemon=True)
        th.start()
        threads.append(th)
        _wait_until(lambda n=i: ctl.queue_depth() == n + 1, what="enqueue")
    filler.release()
    for th in threads:
        th.join(5)
    # batch re-enters with ~1 quantum of residual debt -> granted after
    # at most ~2 quanta of interactive (weight 8 each), NOT dead last
    assert "batch" in order
    assert order.index("batch") <= 17, order
    assert ctl.pending() == (0, 0)


def test_byte_gated_head_reserves_bytes_but_not_slots():
    """A byte-gated head blocks only its own class's FIFO and EARMARKS
    its bytes: zero-byte work (writes) from other classes still flows
    (work-conserving), but byte-weighted entries must not eat the
    earmark — a steady cheap stream could otherwise refill the budget
    forever and starve the gated head."""
    ctl = AdmissionController(max_concurrent=8, byte_budget=100)
    t1 = ctl.admit(cost=QueryCost(device_bytes=60))
    t2 = ctl.admit(cost=QueryCost(device_bytes=30))
    fat_grants, write_grants, cheap_grants = [], [], []
    tf = threading.Thread(
        target=lambda: fat_grants.append(
            ctl.admit(cost=QueryCost(device_bytes=60))
        ),
        daemon=True,
    )
    tf.start()
    _wait_until(lambda: ctl.queue_depth() == 1, what="fat queued")
    # zero-byte write in another class: granted around the gate
    tw = threading.Thread(
        target=lambda: write_grants.append(
            ctl.admit(cls="batch", cost=QueryCost(device_bytes=0))
        ),
        daemon=True,
    )
    tw.start()
    tw.join(5)
    assert write_grants, "zero-byte work must flow around a byte gate"
    # byte-weighted entry in another class: must NOT eat the earmark
    tc = threading.Thread(
        target=lambda: cheap_grants.append(
            ctl.admit(cls="internal", cost=QueryCost(device_bytes=20))
        ),
        daemon=True,
    )
    tc.start()
    _wait_until(lambda: ctl.queue_depth() == 2, what="cheap queued")
    t2.release()  # 60 in flight: fat still gated; cheap must stay queued
    time.sleep(0.05)
    assert not fat_grants and not cheap_grants
    assert ctl.queue_depth() == 2
    t1.release()  # earmark satisfied: fat runs first, then cheap fits
    tf.join(5)
    tc.join(5)
    assert fat_grants and cheap_grants
    write_grants[0].release()
    fat_grants[0].release()
    cheap_grants[0].release()
    assert ctl.pending() == (0, 0)


def test_ticket_released_even_when_span_construction_fails():
    """A failure anywhere after admission — even building the tracing
    span — must release the slot, or the node bleeds capacity into
    permanent 429s."""
    with ClusterHarness(1, in_memory=True, max_concurrent_queries=1) as c:
        srv = c[0]
        uri = srv.node.uri
        srv.api.create_index("tl")
        srv.api.create_field("tl", "f", {"type": "set"})
        srv.api.query("tl", "Set(1, f=1)")

        orig = srv.tracer

        class BoomTracer:
            """Fails where api.query is built, which is after admission;
            the handler's http.request root, opened before the body is
            read, is the real tracer's."""

            def start_span(self, name, *a, **k):
                if name == "api.query":
                    raise RuntimeError("boom")
                return orig.start_span(name, *a, **k)

            def start_span_from_headers(self, name, *a, **k):
                if name == "api.query":
                    raise RuntimeError("boom")
                return orig.start_span_from_headers(name, *a, **k)

            def __getattr__(self, attr):
                return getattr(orig, attr)

        # the failure has to land on a request that holds a ticket, or
        # this test shows nothing: keep what admission granted
        granted = []
        real_admit = srv.scheduler.admit

        def admit(*a, **k):
            granted.append(real_admit(*a, **k))
            return granted[-1]

        srv.scheduler.admit = admit
        srv.tracer = BoomTracer()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post_query(uri, "tl", "Row(f=1)")
            assert ei.value.code == 500
            ei.value.close()
        finally:
            srv.tracer = orig
            srv.scheduler.admit = real_admit
        assert len(granted) == 1 and granted[0]._released
        assert srv.scheduler.pending() == (0, 0)
        # the single slot was NOT leaked: the next query runs
        status, body = _post_query(uri, "tl", "Row(f=1)")
        assert status == 200 and body["results"][0]["columns"] == [1]


def test_learned_service_time_sheds_unmeetable_deadline_early():
    """Early shedding: once the controller has learned the service rate,
    a deadline that cannot be met from the back of the queue is rejected
    IMMEDIATELY (sender still has budget to re-map), not when it
    expires. Deadlines that fit still queue."""
    clock = FakeClock()
    ctl = AdmissionController(max_concurrent=1, clock=clock)
    t = ctl.admit()
    clock.advance(1.0)
    t.release()  # learned service time: ~1.0s per query
    filler = ctl.admit()
    with pytest.raises(ShedError) as ei:
        ctl.admit(deadline=0.5)  # est. wait ~1.0s > 0.5s budget
    assert "back of the queue" in str(ei.value)
    ok = []
    th = threading.Thread(
        target=lambda: ok.append(ctl.admit(deadline=10.0)), daemon=True
    )
    th.start()
    _wait_until(lambda: ctl.queue_depth() == 1, what="feasible leg queued")
    filler.release()
    th.join(5)
    assert ok, "a meetable deadline must queue, not shed"
    ok[0].release()
    assert ctl.pending() == (0, 0)


def test_attr_variant_counts_do_not_feed_batchable_hint():
    """Counts carrying columnAttrs/exclude* opts bypass the batcher, so
    they must not inflate the adaptive-batching load hint either."""
    from pilosa_tpu.exec.executor import ExecOptions

    with ClusterHarness(1, in_memory=True) as c:
        srv = c[0]
        srv.api.create_index("ba")
        srv.api.create_field("ba", "f", {"type": "set"})
        q = parse("Count(Row(f=1))")
        t = srv.api._admit(
            "ba", q, None, False, None, ExecOptions(column_attrs=True)
        )
        assert t is not None and not t.batchable
        assert srv.scheduler.load("ba") == 0
        t.release()
        t2 = srv.api._admit("ba", q, None, False, None, ExecOptions())
        assert t2.batchable and t2.index == "ba"
        assert srv.scheduler.load("ba") == 1
        t2.release()
        assert srv.scheduler.pending() == (0, 0)


def test_malformed_pql_still_counts_in_query_metrics():
    """Parsing moved ahead of the span/stat machinery (admission needs
    the call tree); a malformed-PQL flood must still register on query
    dashboards instead of looking like an idle node."""
    with ClusterHarness(1, in_memory=True) as c:
        srv = c[0]
        uri = srv.node.uri
        srv.api.create_index("mm")
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post_query(uri, "mm", "Nope(((")
        assert ei.value.code == 400
        ei.value.close()
        snap = srv.stats.registry.snapshot()
        assert snap.get("query_n;index:mm") == 1


def test_internal_legs_ride_a_separate_lane():
    """Fan-out legs must not compete for coordinator slots — sharing
    them allows a distributed hold-and-wait (each node's coordinator
    holds its slot while its leg queues behind the peer's coordinator)."""
    ctl = AdmissionController(max_concurrent=1)
    coordinator = ctl.admit()  # the node's only coordinator slot
    leg = ctl.admit(cls="internal", leg=True)  # must NOT block
    assert leg.leg
    assert ctl.pending() == (0, 2)
    leg.release()
    coordinator.release()
    assert ctl.pending() == (0, 0)


def test_leg_lane_is_bounded_and_deadline_aware():
    ctl = AdmissionController(max_concurrent=1, queue_depth=0)
    l1 = ctl.admit(leg=True)
    with pytest.raises(ShedError):  # lane full, waiting bound 0
        ctl.admit(leg=True)
    with pytest.raises(ShedError):  # exhausted deadline sheds on arrival
        ctl.admit(leg=True, deadline=0.0)
    l1.release()
    l2 = ctl.admit(leg=True)  # released slot is reusable
    l2.release()
    assert ctl.pending() == (0, 0)


def test_concurrent_distributed_queries_with_single_slot_nodes():
    """Acceptance for the hold-and-wait fix: two nodes each coordinate a
    distributed query at the same time with max-concurrent-queries=1;
    both must complete well inside the deadline instead of deadlocking
    until it expires."""
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    with ClusterHarness(
        2,
        in_memory=True,
        max_concurrent_queries=1,
        query_deadline=20.0,
    ) as c:
        c[0].api.create_index("dd")
        c[0].api.create_field("dd", "f", {"type": "set"})
        # bits on several shards so both nodes own some of the fan-out
        cols = [s * SHARD_WIDTH + 1 for s in range(8)]
        c[0].api.import_bits("dd", "f", [0] * len(cols), cols)
        results = {}
        errors = []

        def coordinate(i):
            try:
                results[i] = c[i].api.query("dd", "Count(Row(f=0))")[0]
            except Exception as e:  # noqa: BLE001 - surfaced in assert
                errors.append(e)

        threads = [
            threading.Thread(target=coordinate, args=(i,), daemon=True)
            for i in (0, 1)
        ]
        t0 = time.monotonic()
        for th in threads:
            th.start()
        for th in threads:
            th.join(15)
        elapsed = time.monotonic() - t0
        assert not errors, errors
        assert results == {0: 8, 1: 8}
        assert elapsed < 10, f"queries took {elapsed:.1f}s — hold-and-wait?"
        for srv in c.nodes:
            assert srv.scheduler.pending() == (0, 0)


def test_arrival_pump_grants_around_byte_gated_head():
    """Work-conserving on arrival: zero-byte work arriving behind a
    byte-gated fat head (slots free) must be granted immediately by the
    enqueue-time pump, not wait for the next release."""
    ctl = AdmissionController(max_concurrent=4, byte_budget=100)
    t1 = ctl.admit(cost=QueryCost(device_bytes=60))
    fat_grants = []
    tf = threading.Thread(
        target=lambda: fat_grants.append(
            ctl.admit(cost=QueryCost(device_bytes=60))
        ),
        daemon=True,
    )
    tf.start()
    _wait_until(lambda: ctl.queue_depth() == 1, what="fat queued")
    writes = []
    tc = threading.Thread(
        target=lambda: writes.append(
            ctl.admit(cls="batch", cost=QueryCost(device_bytes=0))
        ),
        daemon=True,
    )
    tc.start()
    tc.join(5)  # NO release happened: the arrival pump must grant it
    assert writes, "zero-byte arrival must be granted with slots free"
    writes[0].release()
    t1.release()
    tf.join(5)
    assert fat_grants
    fat_grants[0].release()
    assert ctl.pending() == (0, 0)


def test_done_batching_drops_hint_before_release():
    """After its batcher round, a Count still holds its slot (result
    serialization) but must stop counting as a potential batch mate."""
    ctl = AdmissionController(max_concurrent=8)
    t = ctl.admit(batchable=True, index="i")
    assert ctl.load("i") == 1
    t.done_batching()
    assert ctl.load("i") == 0
    t.release()  # must not double-decrement
    assert ctl.load("i") == 0
    assert ctl.pending() == (0, 0)
    t2 = ctl.admit(batchable=True, index="i")
    t2.release()  # release without done_batching still decrements once
    assert ctl.load("i") == 0
    assert ctl.pending() == (0, 0)


def test_waiting_legs_are_not_barged_by_new_arrivals():
    ctl = AdmissionController(max_concurrent=1, queue_depth=4)
    l0 = ctl.admit(leg=True)
    done = []

    def leg_worker():
        t = ctl.admit(leg=True)
        done.append(t)
        t.release()

    threads = []
    for i in range(2):
        th = threading.Thread(target=leg_worker, daemon=True)
        th.start()
        threads.append(th)
        _wait_until(
            lambda n=i: ctl.pending()[0] == n + 1, what="leg waiting"
        )
    l0.release()
    for th in threads:
        th.join(5)
    assert len(done) == 2
    assert ctl.pending() == (0, 0)


def test_retry_restamps_shrunken_deadline_header():
    """A retried fan-out leg must advertise its SHRUNKEN remaining
    budget to the peer, not the original stamp — a stale header makes
    the peer queue the leg for time the sender no longer has."""
    from pilosa_tpu.server import faults as fmod
    from pilosa_tpu.server.client import InternalClient

    with ClusterHarness(
        1,
        in_memory=True,
        max_concurrent_queries=1,
        admission_queue_depth=0,
        shed_retry_after=0.4,
    ) as c:
        srv = c[0]
        uri = srv.node.uri
        srv.api.create_index("rd")
        srv.api.create_field("rd", "f", {"type": "set"})
        srv.api.query("rd", "Set(1, f=1)")
        seen = []
        orig_qr = srv.api.query_response

        def spy(index, query, **kw):
            h = kw.get("headers")
            raw = h.get("X-Pilosa-Deadline") if h is not None else None
            if raw:
                seen.append(float(raw))
            return orig_qr(index, query, **kw)

        srv.api.query_response = spy
        # fill the LEG lane so the first internal attempt is shed 429
        blocker = srv.scheduler.admit(leg=True)
        client = InternalClient(
            retry_policy=fmod.RetryPolicy(max_attempts=2, base_backoff=0.01)
        )
        results = []
        th = threading.Thread(
            target=lambda: results.append(
                client.query_node(
                    uri, "rd", "Count(Row(f=1))", remote=True,
                    timeout=5.0, deadline=5.0,
                )
            ),
            daemon=True,
        )
        th.start()
        _wait_until(
            lambda: srv.stats.registry.snapshot().get(
                "sched.shed;class:internal,index:rd,reason:queue", 0
            )
            >= 1,
            what="first attempt shed",
        )
        blocker.release()  # retry (after Retry-After 0.4s) will succeed
        th.join(10)
        assert results and results[0] == [1]
        assert len(seen) == 2, seen
        assert seen[0] > seen[1], seen
        assert seen[1] <= seen[0] - 0.3, seen  # shrunk by >= the backoff
        assert srv.scheduler.pending() == (0, 0)


def test_invalid_default_class_rejected_at_startup():
    """A typo'd admission-default-class must fail fast, not silently
    promote all headerless traffic to interactive."""
    with pytest.raises(ValueError, match="bach"):
        AdmissionController(default_class="bach")


def test_oversized_head_drains_bytes_and_runs():
    """An over-budget query must not starve under a sustained stream of
    byte-weighted traffic: once queued, its reservation stops further
    byte grants, the account drains, and it runs."""
    ctl = AdmissionController(max_concurrent=4, byte_budget=100)
    t1 = ctl.admit(cost=QueryCost(device_bytes=30))
    big, cheap = [], []
    tb = threading.Thread(
        target=lambda: big.append(
            ctl.admit(cost=QueryCost(device_bytes=500))
        ),
        daemon=True,
    )
    tb.start()
    _wait_until(lambda: ctl.queue_depth() == 1, what="oversize queued")
    tc = threading.Thread(
        target=lambda: cheap.append(
            ctl.admit(cls="batch", cost=QueryCost(device_bytes=10))
        ),
        daemon=True,
    )
    tc.start()
    _wait_until(lambda: ctl.queue_depth() == 2, what="cheap queued")
    assert not big and not cheap  # both byte-held behind the reservation
    t1.release()  # account drains to zero: the oversize head runs FIRST
    tb.join(5)
    assert big, "oversize head must run once bytes drain"
    assert not cheap  # 500 in flight: cheap is gated behind it
    big[0].release()
    tc.join(5)
    assert cheap
    cheap[0].release()
    assert ctl.pending() == (0, 0)


def test_leg_bytes_count_against_public_budget():
    """Fan-out legs account their device bytes (public admission must
    see the real HBM pressure) without ever byte-GATING — and a leg's
    release pumps the public lane it may have been blocking."""
    ctl = AdmissionController(max_concurrent=4, byte_budget=100)
    leg = ctl.admit(leg=True, cost=QueryCost(device_bytes=80))
    assert ctl.snapshot()["inflightBytes"] == 80
    blocked = []
    th = threading.Thread(
        target=lambda: blocked.append(
            ctl.admit(cost=QueryCost(device_bytes=50))
        ),
        daemon=True,
    )
    th.start()
    _wait_until(lambda: ctl.queue_depth() == 1, what="public byte-gated")
    assert not blocked  # 80 + 50 > 100: leg bytes push back on public
    leg.release()  # frees the bytes AND pumps the public lane
    th.join(5)
    assert blocked
    blocked[0].release()
    assert ctl.pending() == (0, 0)


def test_leg_lane_sheds_unmeetable_deadline_early():
    """The leg lane — the path X-Pilosa-Deadline actually arrives on —
    must early-shed once it has learned its service rate."""
    clock = FakeClock()
    ctl = AdmissionController(max_concurrent=1, clock=clock)
    warm = ctl.admit(leg=True)
    clock.advance(1.0)
    warm.release()  # learned leg service time: ~1.0s
    filler = ctl.admit(leg=True)
    with pytest.raises(ShedError) as ei:
        ctl.admit(leg=True, deadline=0.5)  # est. wait ~1.0s > 0.5s
    assert "back of the queue" in str(ei.value)
    filler.release()
    assert ctl.pending() == (0, 0)


def test_gauges_include_leg_lane():
    """A node saturated with fan-out legs must not look idle on
    /metrics: sched.inflight/queue_depth cover both lanes."""
    st = StatsClient()
    ctl = AdmissionController(max_concurrent=2, stats=st)
    leg = ctl.admit(leg=True)
    assert st.registry.snapshot()["sched.inflight"] == 1
    leg.release()
    assert st.registry.snapshot()["sched.inflight"] == 0
    assert ctl.pending() == (0, 0)
