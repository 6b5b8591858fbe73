"""Cross-request count batching: group-commit coalescing of concurrent
Count queries into one multi-root device dispatch.

The executor already folds adjacent Count calls *within* one PQL request
into a single MultiCountPlan dispatch (exec/plan.py). This module extends
that amortization *across requests*: concurrent clients each issuing a
single Count pay ~one dispatch+read between all of them instead of one
each: N blocking host reads (N synchronisations) become one.

Group-commit (not a timer window): the first arriving query executes
immediately as the leader — an idle server adds ZERO latency. Queries
arriving while the leader's dispatch is in flight queue up; when the
leader finishes, the whole queue executes as one merged multi-Count
request, slicing results back per caller. Batch size adapts to load
(arrival rate x dispatch time), the way group commit batches WAL writers.
The reference instead bounds per-request fan-out with a worker pool
(reference: executor.go:2559-2613 mapReduce + shard worker pool) and
gives concurrent requests no cross-request amortization at all.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional

from pilosa_tpu.utils import tracing
from pilosa_tpu.utils.locks import TrackedCondition, TrackedLock
from pilosa_tpu.utils.race import race_checked
from pilosa_tpu.pql import Call, Query


def _noop_pad_call() -> Call:
    """Zero-row no-op lane for pow2 padding: `Count(Difference())` lowers
    to a PZero root — an all-zero stack that adds no operand reads and no
    meaningful device work — unlike repeating the batch's last call, which
    re-ran real (possibly heavy) device work for every pad lane (up to
    ~2x waste on odd batch sizes). Pad results are masked out of the
    per-waiter slices by construction (slicing stops at the real calls)."""
    return Call(name="Count", children=[Call(name="Difference")])

# Bound on calls merged into one execution: keeps lowered plan shapes in a
# small family (compile cache) and bounds result-slicing latency for the
# earliest waiter under pathological fan-in.
MAX_BATCH_CALLS = 64

STATS = {"leader": 0, "batched": 0, "merged_execs": 0, "fallback_splits": 0}
_STATS_MU = TrackedLock("batcher.stats_mu")


def _bump(key: str) -> None:
    # '+=' from concurrent request threads loses increments across GIL
    # preemption; tests assert exact totals
    with _STATS_MU:
        STATS[key] += 1


def batchable(query: Query) -> bool:
    """Only plain read Counts merge: every call `Count(<one child>)`."""
    return bool(query.calls) and all(
        c.name == "Count" and len(c.children) == 1 for c in query.calls
    )


def batch_eligible(query, shards, opt) -> bool:
    """Will this request be ROUTED through the batcher? The single
    source of truth shared by api._query_batched (routing) and
    api._admit (the adaptive-batching load hint) — two copies of this
    condition would silently diverge and mis-size the hint."""
    return (
        shards is None
        and not opt.remote
        and not opt.column_attrs
        and not opt.exclude_row_attrs
        and not opt.exclude_columns
        and isinstance(query, Query)
        and batchable(query)
    )


class _Waiter:
    __slots__ = ("query", "event", "results", "error", "promoted", "cls")

    def __init__(self, query: Query, cls=None):
        self.query = query
        self.event = threading.Event()
        self.results = None
        self.error = None
        self.promoted = False  # woken to take over leadership
        # lowering class (CountBatcher.classify): queries of different
        # classes must not merge into one multi-root plan — a mesh-group
        # Count's sharded operands and an extent-path Count's local
        # stacks have incompatible placements
        self.cls = cls


@race_checked(exclude=(
    # wired once by NodeServer between construction and serving (init-
    # before-publish handoff); hold_timeout is a test/operator knob
    "load_hint",
    "hold_timeout",
    "stats",
    "classify",
))
class CountBatcher:
    """Per-index group-commit batcher. `execute` is called with a merged
    Query and must return one result per call (the api layer binds it to
    executor.execute_response).

    Leadership is bounded and handed off: a leader executes its own query,
    serves ONE snapshot of the waiters that queued behind it, then — if
    new waiters arrived meanwhile — promotes the first of them to leader
    instead of looping. Under sustained load every client therefore waits
    at most ~two service rounds; the first arriver is never stuck serving
    everyone else's queries forever."""

    def __init__(self):
        self._mu = TrackedLock("batcher.mu")
        # signalled whenever a waiter enqueues; the adaptive leader hold
        # (see run()) sleeps on it instead of polling
        self._arrived = TrackedCondition(self._mu, name="batcher.arrived")
        self._busy: Dict[str, bool] = {}
        self._queue: Dict[str, Deque[_Waiter]] = {}
        # -- adaptive batching (sched/ admission feeds this) --------------
        # load_hint(index) returns the number of BATCHABLE queries for
        # `index` currently admitted or queued by the admission
        # controller — i.e. actual potential batch mates. When it
        # reports load, a fresh leader HOLDS its dispatch briefly
        # (hold_timeout) until that many calls have accumulated, so batch
        # size tracks queue depth (the fixed per-sweep cost amortizes
        # over the batch) instead of relying on dispatch-overlap luck.
        self.load_hint: Optional[Callable[[str], int]] = None
        self.hold_timeout: float = 0.005  # seconds; bounds added latency
        # stats client (NodeServer wires its own); emits one
        # `batcher.batch_size` observation per executed round
        self.stats = None
        # lowering-class hook: classify(index, query) -> hashable key.
        # Rounds are executed per class — a merged multi-root plan must
        # never mix mesh-group and extent-path Counts (incompatible
        # operand placements). None = one class for everything (the
        # single-node default). Must never raise for a valid query; a
        # failure degrades to the shared default class.
        self.classify: Optional[Callable[[str, Query], object]] = None

    def _class_of(self, index: str, query: Query):
        if self.classify is None:
            return None
        try:
            return self.classify(index, query)
        except Exception:  # noqa: BLE001 - classification is advisory
            return None

    def run(self, index: str, query: Query, execute: Callable[[Query], list]):
        cls = self._class_of(index, query)
        with self._mu:
            if self._busy.get(index):
                w = _Waiter(query, cls)
                self._queue.setdefault(index, deque()).append(w)
                self._arrived.notify_all()
            else:
                self._busy[index] = True
                w = None
        if w is not None:
            t_wait0 = time.monotonic()
            w.event.wait()
            if w.promoted:
                # took over leadership: this thread executes the next
                # round MERGED WITH ITS OWN QUERY (a solo promoted leader
                # would make every other round a batch of one under
                # sustained load), then hands off again
                _bump("leader")
                self._serve_round(index, execute, first=w)
            else:
                _bump("batched")
            # flight record: this query rode along in someone else's
            # round — the wait (and, when promoted, the round it then
            # led) is where its milliseconds went
            tracing.record_span(
                "exec.batch",
                time.monotonic() - t_wait0,
                tags={
                    "batcher.role": "promoted" if w.promoted else "batched",
                },
            )
            if w.error is not None:
                raise w.error
            return w.results
        # leadership taken: only NOW consult the scheduler's load hint —
        # followers and promoted leaders never read it, so the hot path
        # pays the (locked) hint lookup once per round, not per call
        target = 0
        if self.load_hint is not None:
            try:
                target = min(int(self.load_hint(index)), MAX_BATCH_CALLS)
            except Exception:  # noqa: BLE001 - a hint must never fail a query
                target = 0
        if target >= 2:
            # adaptive hold: the admission controller reports `target`
            # queries in flight/queued — wait (bounded) for them to line
            # up behind us, then run the whole set as ONE merged dispatch
            lead = _Waiter(query, cls)
            deadline = time.monotonic() + self.hold_timeout
            with self._mu:
                # target counts QUERIES (the admission hint's unit), so
                # the lined-up side counts queries too — comparing calls
                # against a query target would end the hold early for
                # any multi-call leader
                while 1 + len(self._queue.get(index, ())) < target:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._arrived.wait(remaining)
            _bump("leader")
            self._serve_round(index, execute, first=lead)
            if lead.error is not None:
                raise lead.error
            return lead.results
        return self._lead(index, query, execute)

    # -- internals ---------------------------------------------------------

    def _lead(self, index: str, query: Query, execute):
        _bump("leader")
        self._record_round(len(query.calls))
        try:
            with tracing.start_span("exec.batch") as sp:
                sp.set_tag("batcher.role", "leader")
                sp.set_tag("batcher.calls", len(query.calls))
                return execute(query)
        finally:
            self._serve_round(index, execute)

    def _serve_round(self, index: str, execute, first: "_Waiter" = None) -> None:
        """Serve the waiters present right now (in MAX_BATCH_CALLS-sized
        merges, `first` prepended when a promoted leader brings its own
        query), then hand leadership to the first later arrival — or
        release the slot when the queue is empty.

        Merges are split BY LOWERING CLASS (self.classify): a round mixing
        mesh-group and fan-out/extent Counts executes as one sub-batch per
        class in arrival order — one merged multi-root plan must never mix
        operand placements."""
        with self._mu:
            round_ = self._queue.get(index) or deque()
            self._queue[index] = deque()
        if first is not None:
            round_.appendleft(first)
        # partition by class, preserving arrival order within each
        by_cls: Dict[object, Deque[_Waiter]] = {}
        order: List[object] = []
        for wtr in round_:
            if wtr.cls not in by_cls:
                by_cls[wtr.cls] = deque()
                order.append(wtr.cls)
            by_cls[wtr.cls].append(wtr)
        for cls in order:
            bucket = by_cls[cls]
            while bucket:
                batch: List[_Waiter] = []
                n = 0
                while bucket and n + len(bucket[0].query.calls) <= MAX_BATCH_CALLS:
                    wtr = bucket.popleft()
                    batch.append(wtr)
                    n += len(wtr.query.calls)
                if not batch:  # single oversized query: run it alone
                    batch = [bucket.popleft()]
                self._run_batch(batch, execute)
        with self._mu:
            queued = self._queue.get(index)
            if queued:
                nxt = queued.popleft()
                nxt.promoted = True
                nxt.event.set()  # takes over; _busy stays held
            else:
                self._queue.pop(index, None)
                self._busy.pop(index, None)

    def _record_round(self, n_calls: int) -> None:
        """One executed round's size — the observable the scheduler's
        adaptive hook is judged by (it should grow under load)."""
        if self.stats is not None:
            self.stats.histogram("batcher.batch_size", float(n_calls))

    def _run_batch(self, batch: List[_Waiter], execute) -> None:
        if len(batch) == 1:
            w = batch[0]
            self._record_round(len(w.query.calls))
            try:
                w.results = execute(w.query)
            except Exception as e:  # noqa: BLE001 - delivered to the waiter
                w.error = e
            w.event.set()
            return
        calls = [c for w in batch for c in w.query.calls]
        self._record_round(len(calls))
        # pad to a pow2 call count with zero-row no-op lanes (masked out
        # of results by the per-waiter slicing below): the multi-root plan
        # compiles once per size family instead of once per distinct
        # batch size, and the pad lanes cost ~no device work
        n_real = len(calls)
        target = 1 << max(n_real - 1, 0).bit_length()
        calls = calls + [_noop_pad_call() for _ in range(target - n_real)]
        merged = Query(calls=calls)
        try:
            _bump("merged_execs")
            with tracing.start_span("exec.batch") as sp:
                sp.set_tag("batcher.role", "merged-leader")
                sp.set_tag("batcher.calls", n_real)
                res = execute(merged)
            k = 0
            for w in batch:
                n = len(w.query.calls)
                w.results = res[k : k + n]
                k += n
                w.event.set()
        except Exception:
            # error isolation: one bad query must not fail its batchmates
            _bump("fallback_splits")
            for w in batch:
                try:
                    w.results = execute(w.query)
                except Exception as e:  # noqa: BLE001
                    w.error = e
                w.event.set()
