"""Tracing: spans, cross-node context propagation, trace assembly.

Reference: tracing/tracing.go:23-72 — a global tracer with a nop default,
spans started manually at executor/API/fragment entry points
(executor.go:113, api.go:921), and HTTP header propagation between nodes
(tracing/opentracing/opentracing.go:60 InjectHTTPHeaders, used by
http/client.go).

This module is the flight-recorder substrate:

* every span name the package starts is declared in SPAN_NAMES (the
  api-invariants AST pass rejects undeclared literals and flags stale
  entries — the same contract STAT_NAMES has for metrics);
* durations are measured on the MONOTONIC clock (an NTP step mid-query
  must not corrupt a latency number); the epoch `start` is kept for
  display and cross-node ordering only;
* the ring is a deque(maxlen=keep) — O(1) eviction under tracing.mu;
* spans completed on a remote node ride back to the coordinator on the
  internal query response (`Tracer.ingest`), so one assembled tree
  covers the whole cluster;
* `assemble` builds that tree, clamping children into their parent's
  window (cross-node clock skew must not make a child appear to start
  before its parent — the raw window is kept alongside) and computing
  per-span self-time, which feeds the slow-query flight record.

Cross-node context rides the `X-Pilosa-Trace-Id` / `X-Pilosa-Span-Id`
headers.

The profiler's clock: while a sampled span is entered (`with span:`) it
also holds a `jax.profiler.TraceAnnotation(name, trace_id=...)`, so a
profiler session finds the program's spans in its `/host:CPU` plane on
the clock the device planes use (under a microsecond per span while no
session is open). Synthetic spans (`record_span`: `sched.admit`,
`exec.stage`) describe work that has already happened and cannot be
annotated after the fact: they exist in the ring and the assembled tree
only. Exported spans carry `startMonoNs`, the span's start on
`time.monotonic()` in nanoseconds — on Linux the clock of
`time.perf_counter()`, so a client on the same host can place a span
between its own send and receive without a handshake.

Process-wide stops: two things stop a request thread that no span of
its own covers, and both are on the profiler's clock too, as
annotations that are not spans (no ring, no tree): a pass of CPython's
collector (`runtime.gc`, a `gc.callbacks` entry installed once at
import) and one pass of a node's background loop (`node.tick`, the
`tick` helper server/node.py wraps each pass in). Both add to plain
module integers, taking no lock — a collection can start while its
thread holds any lock, the registries' own included — and every node's
publish_cache_gauges copies the totals to `runtime.gc_*` and
`runtime.ticks` / `runtime.tick_ms` at scrape time.
"""

from __future__ import annotations

import contextvars
import gc
import random
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional

from jax.profiler import TraceAnnotation

from pilosa_tpu.utils.locks import TrackedLock
from pilosa_tpu.utils.race import race_checked

# ---------------------------------------------------------------------------
# Span-name registry. Every span name the package starts MUST be declared
# here (the api-invariants AST pass rejects start_span / record_span calls
# with undeclared literal names, and flags declared-but-never-started
# names as stale). This is the single place to look up which stages the
# flight recorder can attribute — dashboards and the assembly tests key
# on these exact names.
# ---------------------------------------------------------------------------

SPAN_NAMES = frozenset(
    {
        # one served HTTP request, from before the body is read to after
        # the reply's last write (server/handler.py: the query route and
        # the two import routes); tags: http.route / http.bytes_in /
        # http.bytes_out / http.read_ms / http.encode_ms, and
        # http.write_ms, which is known only after the request's own
        # ?profile=1 tree has left (ring and /debug/traces have it)
        "http.request",
        # PQL text -> call tree (server/api.py); tags: pql.family, the
        # top-level call names ("Count", "TopN,Sum")
        "api.parse",
        # cost estimate, prefetch hand-off and scheduler.admit
        # (server/api.py _admit); tags: sched.class / sched.wait_ms
        "api.admit",
        # execution after admission (server/api.py); the root when the
        # API is called without the HTTP handler
        "api.query",
        "api.import",
        # admission queue wait, recorded retroactively once the ticket is
        # granted, and only when above 0 (server/api.py): a child of
        # api.admit, or of api.query where no api.admit span is open (the
        # wait then precedes its parent, so assembly clamps it and keeps
        # the raw window)
        "sched.admit",
        # one top-level call (exec/executor.py execute_response): the
        # host work that is in no child — rank-cache walk, per-shard
        # loops, result assembly; tags: pql.family
        "exec.call",
        # result cache (exec/executor.py _cache_lookup / _cache_store):
        # key, lookup, revalidation, store; tags: cache.op / cache.hit
        "exec.cache",
        # a call becoming device operands (exec/executor.py _lower_roots,
        # _group_by_stacked; exec/bsistream.py staging helpers): lowering
        # and residency lookups, exec.stage is its child; tags:
        # plan.family (stacked / bsi / groupby)
        "exec.lower",
        # cross-request count batching rounds (exec/batcher.py):
        # leader-executed merges and ride-along waits
        "exec.batch",
        # operand staging through the HBM residency layer (exec/plan.py
        # flushes the per-thread accumulator fed by hbm/residency.py +
        # core/devcache.py); tags: stage.bytes / stage.rows uploaded,
        # stage.build_ms (row stacks built on the host) and stage.put_ms
        # (handed to the device) within the span, stage.prefetch_hits
        "exec.stage",
        # one compiled dispatch under plan._DISPATCH_MU: lock wait vs
        # device eval vs blocking host read (exec/plan.py); tags:
        # plan.family / plan.program (the jitted program as the
        # profiler's "XLA Modules" line names it) / dispatch.compiled /
        # mesh.devices (devices the program's operands span, 1 on a
        # single device) and, above 1, mesh.axes ("shards=2,cols=2"); on
        # a GroupBy's (exec/executor.py _tag_group_by) groupby.levels /
        # live_groups / planes (0 without aggregate=) / group_block
        # (aggregate only: groups tallied per loaded plane tile) /
        # tally_words (aggregate only: the group tally's tile in words,
        # 0 for the XLA program) /
        # cross_tile (the cross kernel's word-loop tile of each body it
        # ran, "<prefixes>x<rows>x<cols>") / tallies (launches) / fold_ms
        # (host time from the last read to the finished groups) /
        # shard_chunks (chunks of the shard axis tallied; 1 unchunked) /
        # prune_ms and prune_rows (aggregate under a filter: host time of
        # the tallies of each dimension against the filter, with their
        # reads, and the rows so tallied)
        "exec.dispatch",
        # a whole distributed fan-out incl. re-map rounds
        # (exec/distributed.py)
        "exec.fanout",
        # one mesh-group dispatch: the ICI-domain-local share of a
        # fan-out answered as ONE compiled sharded program with the
        # reduction in program (exec/distributed.py + exec/meshgroup.py);
        # tags: mesh.group_size / mesh.local_shards / mesh.collective_bytes
        # (the multi-node fold; a single node's sharded program is an
        # exec.dispatch with mesh.devices > 1)
        "exec.mesh_dispatch",
        # one per-peer fan-out leg, with retry/breaker outcome tags
        # (exec/distributed.py; server/client.py tags rpc.retries)
        "rpc.leg",
        # streaming resize (server/node.py): one fragment transfer leg
        # (snapshot fetch or ledger-resumed catch-up) on the destination
        "resize.transfer",
        # the coordinator's atomic topology cutover: schema refresh to
        # joiners + the required-ack install broadcast
        "resize.cutover",
        # tiered storage (pilosa_tpu/tier/manager.py): one fragment
        # demotion — snapshot upload, capture drain, local eviction;
        # tags: index / shard / bytes / reason (idle, budget, manual)
        "tier.demote",
        # one single-flight cold-fragment hydration — object fetch,
        # checksum verify, adopt; tags: index / shard / bytes
        "tier.hydrate",
        # cache coherence plane (pilosa_tpu/coherence/manager.py): one
        # batched version-vector publish flush to lease holders; tags:
        # grants / errors
        "coherence.publish",
        # one subscription update delivery attempt — incremental repair
        # or batch-class recompute, then long-poll wakeup; tags:
        # index / sub / pushed / shed / error
        "sub.push",
        # profiler-clock-only names: annotations with no span in the ring
        # or the tree (module docstring, "Process-wide stops"). One pass
        # of CPython's collector (_on_gc); arg: generation
        "runtime.gc",
        # one pass of a node's background loop (tick); arg: ticker
        "node.tick",
    }
)

# current span for the executing task/thread; entered spans install
# themselves so nested spans and the internode client pick up the context
_current: contextvars.ContextVar = contextvars.ContextVar("pilosa_span", default=None)


def current_span():
    return _current.get()


# ids: 64 random bits as 16 hex digits, from a generator of this module's
# own (seeded from the OS; a test that seeds the global one must not make
# ids repeat). Telling spans apart needs no urandom call per id, and
# uuid4 was half of a span's cost
_getrandbits = random.Random().getrandbits

TRACE_HEADER = "X-Pilosa-Trace-Id"
SPAN_HEADER = "X-Pilosa-Span-Id"

_RING = 1024


def new_trace_id() -> str:
    """Fresh trace id (also used to stamp shed queries so a 429 is
    diagnosable from the client side without any span existing)."""
    return "%016x" % _getrandbits(64)


class Span:
    __slots__ = ("tracer", "name", "trace_id", "span_id", "parent_id", "tags",
                 "start", "start_mono", "duration", "sampled", "node", "_token",
                 "_annotation")

    def __init__(self, tracer, name, trace_id=None, parent_id=None,
                 sampled=True, node=""):
        self.tracer = tracer
        self.name = name
        self.trace_id = trace_id or new_trace_id()
        self.span_id = "%016x" % _getrandbits(64)
        self.parent_id = parent_id
        self.tags: Dict[str, object] = {}
        # epoch start is DISPLAY/ordering only; duration is measured on
        # the monotonic clock so an NTP step mid-span cannot corrupt it
        self.start = time.time()
        self.start_mono = time.monotonic()
        self.duration: Optional[float] = None
        self.sampled = sampled
        self.node = node
        self._token = None
        self._annotation = None

    def set_tag(self, key: str, value) -> "Span":
        self.tags[key] = value
        return self

    def finish(self) -> None:
        if self.duration is None:
            self.duration = time.monotonic() - self.start_mono
            if self.sampled:
                self.tracer._record(self)

    def __enter__(self) -> "Span":
        self._token = _current.set(self)
        if self.sampled:
            # the same span on the profiler's clock (module docstring)
            self._annotation = TraceAnnotation(
                self.name, trace_id=self.trace_id
            )
            self._annotation.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        if self._token is not None:
            _current.reset(self._token)
            self._token = None
        self.finish()

    def to_json(self) -> dict:
        return {
            "name": self.name,
            "traceId": self.trace_id,
            "spanId": self.span_id,
            "parentId": self.parent_id,
            "node": self.node,
            "start": self.start,
            "startMonoNs": int(self.start_mono * 1e9),
            "durationMs": None if self.duration is None else self.duration * 1000,
            "tags": dict(self.tags),
        }

    @classmethod
    def from_json(cls, tracer, d: dict, node: str = "") -> "Span":
        """Rehydrate a remote span (internal-response piggyback)."""
        s = cls.__new__(cls)
        s.tracer = tracer
        s.name = d.get("name", "")
        s.trace_id = d.get("traceId", "")
        s.span_id = d.get("spanId", "")
        s.parent_id = d.get("parentId")
        s.tags = dict(d.get("tags") or {})
        s.start = float(d.get("start") or 0.0)
        s.start_mono = 0.0  # foreign monotonic base is meaningless here
        # (to_json then says startMonoNs 0, which assemble leaves out)
        dur = d.get("durationMs")
        s.duration = None if dur is None else float(dur) / 1000.0
        s.sampled = True
        s.node = d.get("node") or node
        s._token = None
        s._annotation = None
        return s


@race_checked(exclude=(
    # sample_rate/keep/node are set at construction (or by tests before
    # traffic); the rng is only touched for ROOT sampling decisions and
    # python's Random is internally locked
    "sample_rate",
    "keep",
    "node",
))
class Tracer:
    """In-memory ring-buffer tracer (the default).

    `sample_rate` applies to ROOT spans only: a span continuing a trace
    (child of a local parent, or carrying an incoming trace header) is
    always recorded — the node that started the trace made the sampling
    decision for the whole cluster. `force=True` (the `profile=true`
    query option) records regardless of the rate."""

    def __init__(self, keep: int = _RING, sample_rate: float = 1.0,
                 node: str = ""):
        self.keep = max(1, int(keep))
        self.sample_rate = float(sample_rate)
        self.node = node
        self._mu = TrackedLock("tracing.mu")
        # deque(maxlen=...): O(1) ring maintenance — the list slice-delete
        # this replaced was O(n) under tracing.mu on every span past the
        # watermark (same shape as the PR-3 batcher fix). _ids mirrors the
        # ring's span ids so ingest dedup is O(batch), not an O(ring) set
        # rebuild per internal response.
        self._spans: Deque[Span] = deque(maxlen=self.keep)
        self._ids: set = set()
        self._rng = random.Random()

    def _sample_root(self, force: bool) -> bool:
        if force or self.sample_rate >= 1.0:
            return True
        if self.sample_rate <= 0.0:
            return False
        return self._rng.random() < self.sample_rate

    def start_span(self, name: str, parent: Optional[Span] = None,
                   trace_id: Optional[str] = None, force: bool = False) -> Span:
        if parent is None:
            parent = current_span()
        if parent is not None and getattr(parent, "trace_id", ""):
            return Span(
                self, name, trace_id=parent.trace_id,
                parent_id=parent.span_id,
                sampled=bool(getattr(parent, "sampled", True)) or force,
                node=self.node,
            )
        return Span(
            self, name, trace_id=trace_id,
            sampled=self._sample_root(force), node=self.node,
        )

    def start_span_from_headers(self, name: str, headers,
                                force: bool = False) -> Span:
        trace_id = headers.get(TRACE_HEADER) if headers else None
        parent_id = headers.get(SPAN_HEADER) if headers else None
        if trace_id:
            # continuing a trace the sender already sampled
            return Span(self, name, trace_id=trace_id,
                        parent_id=parent_id or None, sampled=True,
                        node=self.node)
        return Span(self, name, sampled=self._sample_root(force),
                    node=self.node)

    def record_span(self, name: str, duration: float,
                    tags: Optional[dict] = None,
                    parent: Optional[Span] = None) -> Optional[Span]:
        """Record a synthetic span for work that already happened (e.g.
        the admission wait, which completes before the root span opens,
        or staging accumulated by the residency layer). The window is
        [now - duration, now]; assembly clamps it into the parent."""
        if parent is None:
            parent = current_span()
        if parent is None or not getattr(parent, "sampled", False):
            return None
        s = Span(self, name, trace_id=parent.trace_id,
                 parent_id=parent.span_id, node=self.node)
        s.start -= duration
        s.start_mono -= duration
        if tags:
            s.tags.update(tags)
        s.duration = duration
        self._record(s)
        return s

    def _record(self, span: Span) -> None:
        with self._mu:
            self._append_locked(span)

    def _append_locked(self, span: Span) -> None:
        if len(self._spans) == self._spans.maxlen:
            self._ids.discard(self._spans[0].span_id)  # about to evict
        self._spans.append(span)
        self._ids.add(span.span_id)

    def ingest(self, span_dicts: List[dict]) -> int:
        """Record spans completed on a remote node (piggybacked on the
        internal query response). Dedupes by span id so a multi-round
        fan-out re-sending a peer's earlier spans records them once."""
        if not span_dicts:
            return 0
        n = 0
        with self._mu:
            for d in span_dicts:
                sid = d.get("spanId")
                if not sid or sid in self._ids:
                    continue
                self._append_locked(Span.from_json(self, d))
                n += 1
        return n

    def spans(self) -> List[Span]:
        with self._mu:
            return list(self._spans)

    def spans_for(self, trace_id: str) -> List[dict]:
        with self._mu:
            return [
                s.to_json() for s in self._spans if s.trace_id == trace_id
            ]

    def to_json(self) -> List[dict]:
        return [s.to_json() for s in self.spans()]


class NopSpan:
    trace_id = ""
    span_id = ""
    sampled = False
    tags: Dict[str, object] = {}

    def set_tag(self, key, value):
        return self

    def finish(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


class NopTracer:
    node = ""

    def start_span(self, name, parent=None, trace_id=None, force=False):
        return NopSpan()

    def start_span_from_headers(self, name, headers, force=False):
        return NopSpan()

    def record_span(self, name, duration, tags=None, parent=None):
        return None

    def ingest(self, span_dicts):
        return 0

    def spans(self):
        return []

    def spans_for(self, trace_id):
        return []

    def to_json(self):
        return []


def inject_http_headers(span, headers: dict) -> dict:
    """Attach span context to an outgoing request's headers
    (reference: opentracing.go:60)."""
    if getattr(span, "trace_id", ""):
        headers[TRACE_HEADER] = span.trace_id
        headers[SPAN_HEADER] = span.span_id
    return headers


# ---------------------------------------------------------------------------
# module helpers: child spans / synthetic records routed to the tracer
# that owns the active trace (each NodeServer has its own ring, so a span
# started deep in exec/ must land in the ring of the node serving the
# request, not a process-global one)
# ---------------------------------------------------------------------------


def active_span() -> Optional[Span]:
    """The current span when it is a real, sampled span — None otherwise
    (the cheap guard instrumentation sites use to skip span work)."""
    s = _current.get()
    if s is None or not getattr(s, "sampled", False):
        return None
    return s


def start_span(name: str, parent: Optional[Span] = None):
    """Start a child of `parent` (default: the current span) in the
    parent's own tracer. Returns a NopSpan when there is no sampled
    active span — instrumentation is free while nothing is tracing."""
    if parent is None:
        parent = active_span()
    elif not getattr(parent, "sampled", False):
        parent = None
    if parent is None:
        return NopSpan()
    tracer = getattr(parent, "tracer", None)
    if tracer is None:
        return NopSpan()
    return tracer.start_span(name, parent=parent)


def record_span(name: str, duration: float, tags: Optional[dict] = None,
                parent: Optional[Span] = None) -> None:
    """Synthetic-span counterpart of start_span (same routing rules)."""
    if parent is None:
        parent = active_span()
    elif not getattr(parent, "sampled", False):
        parent = None
    if parent is None:
        return
    tracer = getattr(parent, "tracer", None)
    if tracer is not None:
        tracer.record_span(name, duration, tags=tags, parent=parent)


def ingest_spans(span_dicts: List[dict]) -> int:
    """Ingest remote piggybacked spans into the active trace's tracer
    (server/client.py calls this when an internal response carries
    spans). No active sampled span -> dropped."""
    s = active_span()
    if s is None:
        return 0
    tracer = getattr(s, "tracer", None)
    if tracer is None:
        return 0
    return tracer.ingest(span_dicts)


# ---------------------------------------------------------------------------
# per-thread staging accounting (hbm/residency.py + core/devcache.py feed
# it; exec/plan.py flushes it into an exec.stage span just before the
# dispatch that consumes the staged operands)
# ---------------------------------------------------------------------------

_stage_tls = threading.local()


class StageAccount:
    """One thread's staging since the last take: host->device upload
    `nbytes` and `rows` (row-planes of W words), wall `seconds` of the
    whole staging (residency lookups included), of which `build_seconds`
    went into building row stacks on the host and `put_seconds` into
    handing them to the device, and `hits`, extents the prefetcher had
    warmed."""

    __slots__ = ("nbytes", "seconds", "hits", "build_seconds",
                 "put_seconds", "rows")

    def __init__(self) -> None:
        self.nbytes = 0
        self.seconds = 0.0
        self.hits = 0
        self.build_seconds = 0.0
        self.put_seconds = 0.0
        self.rows = 0


def note_stage(nbytes: int = 0, seconds: float = 0.0,
               prefetch_hits: int = 0, build_seconds: float = 0.0,
               put_seconds: float = 0.0, rows: int = 0) -> None:
    """Accumulate staging work done on this thread (the fields of
    StageAccount). Cheap (six adds); flushed by take_stage_account."""
    acc = getattr(_stage_tls, "account", None)
    if acc is None:
        acc = _stage_tls.account = StageAccount()
    acc.nbytes += int(nbytes)
    acc.seconds += float(seconds)
    acc.hits += int(prefetch_hits)
    acc.build_seconds += float(build_seconds)
    acc.put_seconds += float(put_seconds)
    acc.rows += int(rows)


def take_stage_account() -> StageAccount:
    """What this thread accumulated since the last take; resets the
    accumulator."""
    acc = getattr(_stage_tls, "account", None)
    _stage_tls.account = None
    return acc if acc is not None else StageAccount()


# ---------------------------------------------------------------------------
# process-wide stops (module docstring): collector passes and background
# ticks, counted and timed in plain integers. No lock: CPython runs one
# collection at a time, and under the GIL an add can only be lost to a
# tick of the same name in another in-process node
# ---------------------------------------------------------------------------

# passes and pause nanoseconds by collector generation
_gc_passes = [0, 0, 0]
_gc_pause_ns = [0, 0, 0]
_gc_t0 = 0  # the open pass's start, 0 between passes
_gc_annotation = None
_annotating = TraceAnnotation.is_enabled  # a profiler session is open


def _on_gc(phase: str, info: dict) -> None:
    # about 1 us a pass with no profiler session open: the annotation is
    # built only while one is (is_enabled), which halves the callback
    global _gc_t0, _gc_annotation
    try:
        if phase == "start":
            _gc_t0 = time.perf_counter_ns()
            if _annotating():
                _gc_annotation = TraceAnnotation(
                    "runtime.gc", generation=info["generation"]
                )
                _gc_annotation.__enter__()
        elif _gc_t0:
            pause = time.perf_counter_ns() - _gc_t0
            _gc_t0 = 0
            if _gc_annotation is not None:
                _gc_annotation.__exit__(None, None, None)
                _gc_annotation = None
            gen = info["generation"]
            _gc_passes[gen] += 1
            _gc_pause_ns[gen] += pause
    except BaseException:  # noqa: BLE001 - a collector callback never raises
        pass


gc.callbacks.append(_on_gc)


def gc_totals() -> dict:
    """Collector passes and pause milliseconds since the process began:
    over every generation, and of generation 2 (full passes) alone."""
    return {
        "passes": sum(_gc_passes),
        "pause_ms": sum(_gc_pause_ns) / 1e6,
        "full_passes": _gc_passes[2],
        "full_pause_ms": _gc_pause_ns[2] / 1e6,
    }


# the background loops of server/node.py, by the name their errors and
# their runtime.ticks label carry
TICKERS = (
    "coherence", "tier-demote", "telemetry", "runtime-poll",
    "cache-flush", "liveness-probe", "anti-entropy",
)
_ticks = {name: [0, 0] for name in TICKERS}  # [passes, wall ns]


class tick:
    """`with tick(<ticker>):` around one pass of a background loop: the
    pass on the profiler's clock as `node.tick` and in the ticker's
    totals (tick_totals)."""

    __slots__ = ("ticker", "_t0", "_annotation")

    def __init__(self, ticker: str) -> None:
        self.ticker = ticker

    def __enter__(self) -> "tick":
        self._annotation = TraceAnnotation("node.tick", ticker=self.ticker)
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        wall = time.perf_counter_ns() - self._t0
        self._annotation.__exit__(None, None, None)
        row = _ticks[self.ticker]
        row[0] += 1
        row[1] += wall


def tick_totals() -> dict:
    """{ticker: (passes, wall ms)} since the process began, every ticker
    of TICKERS listed from the start."""
    return {name: (n, ns / 1e6) for name, (n, ns) in _ticks.items()}


# ---------------------------------------------------------------------------
# trace assembly
# ---------------------------------------------------------------------------


def assemble(span_dicts: List[dict], trace_id: str) -> dict:
    """Assemble one trace's spans (local + ingested remote) into a tree.

    Children are CLAMPED into their parent's [start, end] window: epoch
    clocks across nodes skew, and synthetic spans (sched.admit) complete
    before their parent opens — a child must never appear to start
    before its parent. When clamping changes a window the raw one is
    kept under "raw" so skew stays diagnosable. `selfMs` is the span's
    clamped duration minus its children's clamped durations (floored at
    0 — parallel children like fan-out legs legitimately overlap)."""
    spans: List[dict] = []
    seen: set = set()
    for d in span_dicts:
        if d.get("traceId") != trace_id:
            continue
        sid = d.get("spanId")
        if not sid or sid in seen:
            continue
        seen.add(sid)
        spans.append(d)
    by_parent: Dict[Optional[str], List[dict]] = {}
    ids = {d["spanId"] for d in spans}
    for d in spans:
        pid = d.get("parentId")
        key = pid if pid in ids else None
        by_parent.setdefault(key, []).append(d)

    t0 = min((d.get("start") or 0.0) for d in spans) if spans else 0.0

    def build(d: dict, pstart: float, pend: float) -> dict:
        raw_start = float(d.get("start") or 0.0)
        raw_dur = float(d.get("durationMs") or 0.0) / 1000.0
        start = min(max(raw_start, pstart), pend)
        end = min(max(raw_start + raw_dur, start), pend)
        node = {
            "name": d.get("name", ""),
            "spanId": d["spanId"],
            "node": d.get("node", ""),
            "startMs": round((start - t0) * 1000.0, 3),
            "durationMs": round((end - start) * 1000.0, 3),
            "tags": dict(d.get("tags") or {}),
            "children": [],
        }
        if d.get("startMonoNs"):  # absent on a span ingested from a peer
            node["startMonoNs"] = d["startMonoNs"]
        if (start, end) != (raw_start, raw_start + raw_dur):
            node["raw"] = {
                "startMs": round((raw_start - t0) * 1000.0, 3),
                "durationMs": round(raw_dur * 1000.0, 3),
            }
        child_ms = 0.0
        for c in sorted(
            by_parent.get(d["spanId"], ()), key=lambda c: c.get("start") or 0.0
        ):
            cn = build(c, start, end)
            node["children"].append(cn)
            child_ms += cn["durationMs"]
        node["selfMs"] = round(max(0.0, node["durationMs"] - child_ms), 3)
        return node

    roots = [
        build(d, float("-inf"), float("inf"))
        for d in sorted(by_parent.get(None, ()), key=lambda d: d.get("start") or 0.0)
    ]
    return {"traceId": trace_id, "spanCount": len(spans), "roots": roots}


def assemble_open(span: Span, span_dicts: List[dict]) -> dict:
    """The tree of `span`'s trace while `span` itself is still open (the
    HTTP handler attaches the tree to the reply that its root span
    covers): the open span enters with its duration so far."""
    d = span.to_json()
    d["durationMs"] = (time.monotonic() - span.start_mono) * 1000.0
    return assemble(span_dicts + [d], span.trace_id)


def _walk(node: dict):
    yield node
    for c in node.get("children", ()):
        yield from _walk(c)


def top_stages(span_dicts: List[dict], trace_id: str, n: int = 5) -> List[dict]:
    """The n stages of one trace with the most self-time (the slow-query
    flight record: where a query's milliseconds actually went)."""
    tree = assemble(span_dicts, trace_id)
    stages: List[dict] = []
    for root in tree["roots"]:
        for nd in _walk(root):
            stages.append(
                {
                    "name": nd["name"],
                    "node": nd["node"],
                    # a leg span lives on the COORDINATOR, so its node
                    # label alone can't say which peer it went to
                    "peer": nd["tags"].get("peer"),
                    "selfMs": nd["selfMs"],
                    "durationMs": nd["durationMs"],
                }
            )
    stages.sort(key=lambda s: -s["selfMs"])
    return stages[:n]


_global: Any = Tracer()
_global_lock = TrackedLock("tracing.global_lock")


def global_tracer():
    return _global


def set_global_tracer(tracer) -> None:
    global _global
    with _global_lock:
        _global = tracer
