"""API: every cluster operation as a validated method.

Reference: /root/reference/api.go — API.Query (:135), CreateIndex/Field,
Import (:920) with shard->owner routing, ImportValue (:1031), ExportCSV
(:500), cluster-state gating (:101-126, apiMethod enum :1340-1393),
ClusterMessage receive (server.go:569 receiveMessage dispatch).

The API belongs to one node (NodeServer); multi-node behavior goes through
the node's DistributedExecutor and InternalClient."""

from __future__ import annotations

import io
import json
import re
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from pilosa_tpu.cluster.topology import (
    STATE_DEGRADED,
    STATE_NORMAL,
    STATE_RESIZING,
)
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core import timeq
from pilosa_tpu.exec.executor import ExecError, ExecOptions, NotFoundError
from pilosa_tpu.shardwidth import SHARD_WIDTH, SHARD_WIDTH_EXPONENT


class ApiError(Exception):
    pass


def _group_by_shard(shards: np.ndarray, timestamps):
    """(shard, index_array, ts_slice) groups from ONE sort
    (utils/arrays.group_slices) — the O(shards x bits) boolean-mask
    rescan (and its per-shard full-batch timestamp regather) this import
    path used to run is gone. Timestamps gather per group from the same
    index arrays, so each group's ts list aligns with its rows/cols by
    construction."""
    from pilosa_tpu.utils.arrays import group_slices

    return [
        (
            int(shard),
            sl,
            [timestamps[i] for i in sl.tolist()]
            if timestamps is not None
            else None,
        )
        for shard, sl in group_slices(shards)
    ]


_VIEW_NAME_RE = re.compile(r"[a-z][a-z0-9_]{0,63}")


def _pql_family(query) -> str:
    """The top-level call names of a parsed query, each once, in order
    ("Count", "TopN,Sum"): the `pql.family` tag of the request's spans."""
    return ",".join(dict.fromkeys(c.name for c in query.calls))


def _tag_admit(estimate_s: float, grant_s: float) -> None:
    """api.admit part by part, as tags and not child spans, so its self
    time stays what it was: the whole cost estimate (of which
    sched/cost.py tagged the fragment walk, admit.merge_walk_ms) and the
    grant less its queue wait (that is the sched.admit child). What is
    left of api.admit's self time is _admit's own glue."""
    from pilosa_tpu.utils import tracing

    span = tracing.active_span()
    if span is None:
        return
    span.set_tag("admit.estimate_ms", round(estimate_s * 1000.0, 3))
    span.tags.setdefault("admit.merge_walk_ms", 0.0)
    span.set_tag("admit.grant_ms", round(max(0.0, grant_s) * 1000.0, 3))


def _validate_view_name(view: str) -> None:
    """View names become path components (view.go naming: standard,
    standard_YYYYMMDDHH, bsig_<field>); anything else is rejected so
    caller-supplied names can't traverse out of the data directory."""
    if not _VIEW_NAME_RE.fullmatch(view):
        raise ApiError(f"invalid view name: {view!r}")


class DisabledError(ApiError):
    """Operation not allowed in the current cluster state
    (reference: ErrClusterDoesNotOwnShard / apiMethodNotAllowedError)."""


# Cluster-state gating (api.go:101-105,1379-1393): DEGRADED allows the
# full NORMAL method set (writes to a down replica are best-effort and
# repaired by anti-entropy when it returns); RESIZING allows only
# non-write queries and internal/status traffic.


class API:
    def __init__(self, server: "NodeServer"):  # noqa: F821
        self.server = server

    # -- helpers -----------------------------------------------------------

    @property
    def holder(self):
        return self.server.holder

    @property
    def cluster(self):
        return self.server.cluster

    def _check_write_count(self, n: int) -> None:
        """Reject an import larger than max-writes-per-request (-> HTTP
        400, reference http/handler.go maxWritesPerRequest): one huge
        request would hold the import pool and the WAL group-commit
        window hostage; clients are expected to batch."""
        limit = getattr(self.server, "max_writes_per_request", 0)
        if limit and n > limit:
            raise ApiError(
                f"import of {n} writes exceeds max-writes-per-request "
                f"({limit}); split the request into smaller batches"
            )

    def _validate(self, method: str, write: bool = False) -> None:
        state = self.server.state
        if state == STATE_NORMAL:
            return
        if state == STATE_DEGRADED:
            # same method set as NORMAL (api.go:104) — the cluster keeps
            # serving writes while < replicaN nodes are down — EXCEPT
            # schema deletes: the rejoin repair channel (probe-pass schema
            # push + apply_schema) is additive-only, so a delete the down
            # node misses would diverge it forever. Deliberate deviation
            # from the reference, which has the same unrepaired-delete hole.
            if method in ("delete_index", "delete_field", "delete_view"):
                raise DisabledError(
                    f"api method {method!r} not allowed in state {state}: "
                    "a down node would never learn the delete"
                )
            return
        if state == STATE_RESIZING and method in ("query",) and not write:
            return
        raise DisabledError(f"api method {method!r} not allowed in state {state}")

    def _broadcast(self, message: dict) -> None:
        """Send a cluster message to every peer (reference:
        server.go:666-705 SendSync; delivery here is per-node HTTP)."""
        for n in self.cluster.nodes:
            if n.id == self.server.node.id:
                continue
            try:
                self.server.client.send_message(n.uri, message)
            except Exception:
                self.server.logger(
                    f"broadcast {message.get('type')} to {n.id} failed"
                )

    # -- query (api.go:135) ------------------------------------------------

    def query(
        self,
        index: str,
        query: str,
        shards: Optional[Sequence[int]] = None,
        remote: bool = False,
        headers: Optional[dict] = None,
    ) -> List[Any]:
        """Execute PQL and return the per-call results list."""
        return self.query_response(
            index, query, shards=shards, remote=remote, headers=headers
        ).results

    def query_response(
        self,
        index: str,
        query: str,
        shards: Optional[Sequence[int]] = None,
        remote: bool = False,
        headers: Optional[dict] = None,
        column_attrs: bool = False,
        exclude_row_attrs: bool = False,
        exclude_columns: bool = False,
        profile: bool = False,
    ):
        """Execute PQL, with admission control (pilosa_tpu/sched/), a
        trace span, per-query stats and slow-query logging; returns the
        full QueryResponse incl. column attr sets (reference: api.go:135
        Query + executor spans executor.go:113-115, LongQueryTime
        api.go:1157).

        Admission happens BEFORE the span/stat machinery: a shed query
        (ShedError -> HTTP 429 + Retry-After) never counts as executed —
        but it DOES carry the trace id the query would have flown under,
        so a 429 is diagnosable from the client side. The priority class
        comes from the X-Pilosa-Priority header (internal fan-out legs
        default to the `internal` class) and the remaining deadline from
        X-Pilosa-Deadline, stamped by the distributed executor so remote
        nodes shed early instead of timing out late.

        `profile=True` (the `profile` query option) forces the trace to
        be sampled and attaches the assembled cross-node trace tree to
        the response (`QueryResponse.profile`)."""
        import time as _time

        from pilosa_tpu.sched.admission import ShedError
        from pilosa_tpu.utils import tracing

        self._validate("query")
        # the span this call runs under, if any: the HTTP handler's
        # http.request. api.parse and api.admit are its children (no
        # span of their own without one: they come before api.query
        # opens and would be roots beside it), and the handler, whose
        # span is still open, assembles the profile tree
        enclosing = tracing.current_span()
        pql_text = query if isinstance(query, str) else str(query)
        if isinstance(query, str):
            from pilosa_tpu.pql import parse
            from pilosa_tpu.pql.parser import ParseError

            try:
                with tracing.start_span("api.parse") as parse_span:
                    query = parse(query)
                    family = _pql_family(query)
                    parse_span.set_tag("pql.family", family)
            except ParseError:
                # parsing now happens before the span/stat machinery (the
                # admission cost estimate needs the call tree), but a
                # malformed-PQL flood must still show on query dashboards
                # — count it before the 400 surfaces
                stats = self.server.stats.with_tags(f"index:{index}")
                stats.count("query_n")
                stats.timing("query_ms", 0.0)
                raise
        else:
            family = _pql_family(query)
        opt = ExecOptions(
            remote=remote,
            column_attrs=column_attrs,
            exclude_row_attrs=exclude_row_attrs,
            exclude_columns=exclude_columns,
        )
        # trace context is resolved BEFORE admission: a shed query never
        # executes, but its 429 must still name the flight record it
        # would have flown under (satellite: diagnosable sheds)
        incoming_trace = headers.get(tracing.TRACE_HEADER) if headers else None
        trace_id = (
            getattr(enclosing, "trace_id", "")
            or incoming_trace
            or tracing.new_trace_id()
        )
        # everything from admission on runs under the ticket's
        # try/finally — even a failure closing or building a span must
        # release the slot, or the node would bleed concurrency capacity
        # until restart
        ticket = None
        try:
            with tracing.start_span("api.admit") as admit_span:
                try:
                    ticket = self._admit(
                        index, query, shards, remote, headers, opt
                    )
                except ShedError as e:
                    if not e.trace_id:
                        e.trace_id = trace_id
                    raise
                if ticket is not None:
                    admit_span.set_tag("sched.class", ticket.cls)
                    admit_span.set_tag(
                        "sched.wait_ms", round(ticket.waited * 1000.0, 3)
                    )
                    # the queue wait as a stage of its own, inside
                    # api.admit's window. Fast-path grants (waited 0) record
                    # nothing — a zero-length span per query would evict real
                    # stages from the ring, and the sched.wait_ms tag carries
                    # the value
                    if ticket.waited > 0:
                        tracing.record_span(
                            "sched.admit",
                            ticket.waited,
                            tags={"sched.class": ticket.cls},
                            parent=admit_span,
                        )
            if enclosing is not None:
                span = self.server.tracer.start_span(
                    "api.query", parent=enclosing, force=profile
                )
            elif incoming_trace:
                span = self.server.tracer.start_span_from_headers(
                    "api.query", headers, force=profile
                )
            else:
                span = self.server.tracer.start_span(
                    "api.query", trace_id=trace_id, force=profile
                )
            t0 = _time.perf_counter()
            resp = None
            with span:
                span.set_tag("index", index)
                span.set_tag("remote", remote)
                span.set_tag("pql.family", family)
                if ticket is not None:
                    span.set_tag("sched.class", ticket.cls)
                    span.set_tag(
                        "sched.wait_ms", round(ticket.waited * 1000.0, 3)
                    )
                    # without an api.admit span the wait is recorded here:
                    # it completed before this span opened, so assembly
                    # clamps it and keeps the raw window
                    if ticket.waited > 0 and enclosing is None:
                        tracing.record_span(
                            "sched.admit",
                            ticket.waited,
                            tags={"sched.class": ticket.cls},
                        )
                try:
                    # per-query profiling hook: a real cProfile context
                    # only while a /debug/pprof window is open (one
                    # attribute read otherwise, server/profiling.py)
                    with self.server.profiler.maybe_profile():
                        batched, parsed = self._query_batched(
                            index, query, shards, opt
                        )
                        if ticket is not None:
                            # past the batcher: this query can no longer
                            # be anyone's batch mate — drop it from the
                            # adaptive-batching hint before serialization
                            ticket.done_batching()
                        if batched is not None:
                            resp = batched
                        else:
                            resp = self.server.executor.execute_response(
                                index, parsed if parsed is not None else query,
                                shards=shards, opt=opt,
                            )
                finally:
                    dt = _time.perf_counter() - t0
                    span.set_tag("query_ms", round(dt * 1000.0, 3))
                    stats = self.server.stats.with_tags(f"index:{index}")
                    stats.count("query_n")
                    stats.timing("query_ms", dt)
                    lqt = self.server.long_query_time
                    if lqt > 0 and dt > lqt:
                        self._log_slow_query(index, pql_text, dt, lqt, span)
            # the root span is finished and recorded here; the remote
            # legs' spans were ingested during execution, so the ring now
            # holds the whole trace (under an enclosing span the root is
            # the caller's, and so is the assembly)
            if profile and resp is not None and enclosing is None:
                resp.profile = self._assemble_trace(span.trace_id or trace_id)
            return resp
        finally:
            if ticket is not None:
                ticket.release()

    def _assemble_trace(self, trace_id: str) -> Optional[dict]:
        """Assembled cross-node trace tree for `trace_id` from this
        node's ring (best-effort: a swapped-in tracer without spans_for
        simply yields no profile)."""
        from pilosa_tpu.utils import tracing

        spans_for = getattr(self.server.tracer, "spans_for", None)
        if spans_for is None or not trace_id:
            return None
        return tracing.assemble(spans_for(trace_id), trace_id)

    def _log_slow_query(
        self, index: str, pql_text: str, dt: float, lqt: float, span
    ) -> None:
        """Slow-query flight record: one line with the trace id and the
        top stages by self-time — where the milliseconds actually went —
        instead of the bare PQL echo (reference: LongQueryTime,
        api.go:1157)."""
        from pilosa_tpu.utils import tracing

        trace_id = getattr(span, "trace_id", "")
        stages = ""
        spans_for = getattr(self.server.tracer, "spans_for", None)
        if trace_id and spans_for is not None:
            tops = tracing.top_stages(spans_for(trace_id), trace_id, 5)
            if tops:
                stages = "; top stages by self-time: " + ", ".join(
                    f"{t['name']}"
                    + (f"({t['peer']})" if t.get("peer") else "")
                    + (f"@{t['node']}" if t["node"] else "")
                    + f"={t['selfMs']:.1f}ms"
                    for t in tops
                )
        self.server.logger(
            f"slow query ({dt:.3f}s > {lqt:.3f}s) on {index!r} "
            f"trace={trace_id or '-'}: {pql_text[:200]}{stages}"
        )

    def _admit(self, index, query, shards, remote, headers, opt):
        """Admission gate: estimate the query's device cost and block
        until the scheduler grants a slot (or raise ShedError -> 429).
        Returns the Ticket to release after execution, or None when the
        scheduler is disabled (max-concurrent-queries = 0)."""
        scheduler = getattr(self.server, "scheduler", None)
        if scheduler is None:
            return None
        import time as _time

        from pilosa_tpu.sched import admission as admod
        from pilosa_tpu.sched import cost as costmod

        cls = None
        deadline = None
        if headers is not None:
            cls = headers.get(admod.PRIORITY_HEADER)
            raw_deadline = headers.get(admod.DEADLINE_HEADER)
            if raw_deadline:
                try:
                    deadline = float(raw_deadline)
                except ValueError:
                    deadline = None
        if remote and not cls:
            cls = admod.CLASS_INTERNAL
        idx = self.holder.index(index)
        shard_count = None
        if shards is None and idx is not None:
            # multi-node coordinator: this node's device only holds its
            # expected LOCAL share of the fan-out (peers charge their
            # legs' shards themselves); charging the full cluster-wide
            # shard axis would over-throttle the coordinator
            nodes = max(1, len(self.cluster.nodes))
            if nodes > 1:
                try:
                    total = max(1, len(idx.available_shards()))
                except Exception:  # noqa: BLE001 - estimation best-effort
                    total = 1
                import math as _math

                share = min(1.0, self.cluster.replica_n / nodes)
                shard_count = max(1, _math.ceil(total * share))
        # transport terms (collective-cost accounting): how much of this
        # query folds into the mesh-group collective vs rides cross-group
        # legs — remote legs are somebody else's fan-out and price nothing
        transport = None
        if not remote and idx is not None and len(self.cluster.nodes) > 1:
            profile_fn = getattr(
                self.server.executor, "transport_profile", None
            )
            if profile_fn is not None:
                transport = profile_fn(idx, shards)
            # a mesh-group dispatch stages the WHOLE group's operands on
            # this node's device while the members admit no leg: charge
            # the full device shard axis, not the coordinator's 1/N
            # heuristic share (admission's byte budget must see the real
            # residency the fold creates)
            if transport and transport.get("device_shards", 0) > 0:
                shard_count = max(
                    shard_count or 1, transport["device_shards"]
                )
        t_estimate = _time.perf_counter()
        qcost = costmod.estimate(
            idx, query, shards, shard_count=shard_count, transport=transport
        )
        t_estimated = _time.perf_counter()
        from pilosa_tpu.exec import batcher as batchmod

        # only batcher-eligible traffic feeds the adaptive-batching hint
        # — same predicate the routing in _query_batched uses, so the
        # hint can never count a query the batcher would divert
        batchable = batchmod.batch_eligible(query, shards, opt)
        # HBM prefetch feed (hbm/prefetch.py): if this query is about to
        # wait, stage its operand extents in the background while the
        # current dispatch holds the device. Local reads only: a remote
        # leg's shards are warmed by its own node, and a multi-node
        # coordinator's local device holds just its share (warming the
        # whole cluster-wide shard axis here would churn local HBM).
        if (
            not remote
            and not qcost.write
            and len(self.cluster.nodes) <= 1
        ):
            warm_q = query
            # index rides along so a rate-throttled tenant cannot keep
            # warming HBM through the prefetch side door
            scheduler.maybe_prefetch(
                lambda: self.server.executor.warm(index, warm_q, shards),
                index=index,
            )
        t_grant = _time.perf_counter()
        ticket = scheduler.admit(
            cls=cls,
            cost=qcost,
            deadline=deadline,
            batchable=batchable,
            index=index,
            # remote legs ride the scheduler's separate internal lane: a
            # coordinator blocks on its legs WHILE holding its own slot,
            # so legs competing for coordinator slots across nodes could
            # hold-and-wait until every deadline expired
            leg=remote,
        )
        try:
            _tag_admit(
                t_estimated - t_estimate,
                _time.perf_counter() - t_grant - ticket.waited,
            )
        except BaseException:  # a granted ticket is released on every path
            ticket.release()
            raise
        return ticket

    def _query_batched(self, index, query, shards, opt):
        """Route pure-Count requests through the group-commit batcher
        (exec/batcher.py): concurrent single-Count clients share one
        multi-root dispatch. `query` is already parsed (query_response
        parses once, up front, for admission cost estimation). Returns
        (response, query); response is None when the request is not
        batchable."""
        import dataclasses

        from pilosa_tpu.exec import batcher as batchmod
        from pilosa_tpu.exec.executor import QueryResponse

        q = query
        if not batchmod.batch_eligible(q, shards, opt):
            return None, q
        results = self.server.count_batcher.run(
            index,
            q,
            lambda merged: self.server.executor.execute_response(
                index, merged, shards=None, opt=dataclasses.replace(opt)
            ).results,
        )
        return QueryResponse(results=results), q

    # -- query subscriptions (pilosa_tpu/coherence/) -----------------------

    def subscribe(self, index: str, query: str) -> dict:
        """Register a standing PQL program against `index`: the
        coherence manager executes it once, pins its result-cache
        entries, and pushes updates on invalidation (long-polled by the
        handler). Raises NotFoundError when subscriptions are disabled
        or the index does not exist; ShedError over the cap."""
        self._validate("subscribe")
        mgr = self.server.coherence
        if mgr is None or not mgr.subs_enabled:
            raise NotFoundError("subscriptions disabled")
        if self.holder.index(index) is None:
            raise NotFoundError(f"index not found: {index}")
        return mgr.subscribe(index, query)

    # -- schema DDL (api.go:206-368) ---------------------------------------

    def create_index(
        self,
        name: str,
        keys: bool = False,
        track_existence: bool = True,
        broadcast: bool = True,
    ):
        self._validate("create_index", write=True)
        idx = self.holder.create_index_if_not_exists(
            name, keys=keys, track_existence=track_existence
        )
        self.server.wire_translation()
        if broadcast:
            self._broadcast(
                {
                    "type": "create-index",
                    "index": name,
                    "keys": keys,
                    "trackExistence": track_existence,
                }
            )
        return idx

    def delete_index(self, name: str, broadcast: bool = True) -> None:
        self._validate("delete_index", write=True)
        try:
            self.holder.delete_index(name)
        except KeyError:
            pass
        # label GC: the deleted index's per-index metric series must not
        # outlive it (a churning tenant set would leak gauge families)
        self.server.drop_index_telemetry(name)
        if broadcast:
            self._broadcast({"type": "delete-index", "index": name})

    def create_field(
        self,
        index: str,
        name: str,
        options: Optional[dict] = None,
        broadcast: bool = True,
    ):
        self._validate("create_field", write=True)
        idx = self.holder.index(index)
        if idx is None:
            raise NotFoundError(f"index not found: {index}")
        opts = FieldOptions(**(options or {}))
        f = idx.create_field_if_not_exists(name, opts)
        self.server.wire_translation()
        if broadcast:
            self._broadcast(
                {
                    "type": "create-field",
                    "index": index,
                    "field": name,
                    "options": options or {},
                }
            )
        return f

    def delete_field(self, index: str, name: str, broadcast: bool = True) -> None:
        self._validate("delete_field", write=True)
        idx = self.holder.index(index)
        if idx is None:
            raise NotFoundError(f"index not found: {index}")
        try:
            idx.delete_field(name)
        except KeyError:
            pass
        # mesh-group adapters cache this index's Field/View objects; a
        # delete (+ possible recreate) must not leave the mesh path
        # reading the dead objects — drop the whole index's adapters
        # (coarse but exact; they rebuild lazily on the next fold)
        from pilosa_tpu.exec import meshgroup

        meshgroup.drop_index(index)
        if broadcast:
            self._broadcast({"type": "delete-field", "index": index, "field": name})

    def schema(self) -> List[dict]:
        return self.holder.schema()

    def apply_schema(self, schema: List[dict]) -> None:
        """Apply a full schema dump (reference: api.ApplySchema / resize
        applySchema, holder.go:327)."""
        self._validate("apply_schema", write=True)
        for ix in schema:
            idx = self.holder.create_index_if_not_exists(
                ix["name"],
                keys=ix.get("options", {}).get("keys", False),
                track_existence=ix.get("options", {}).get("trackExistence", True),
            )
            for fd in ix.get("fields", []):
                opts = _field_options_from_json(fd.get("options", {}))
                idx.create_field_if_not_exists(fd["name"], opts)
        self.server.wire_translation()

    # -- imports (api.go:920 Import, :1031 ImportValue) --------------------

    def import_bits(
        self,
        index: str,
        field: str,
        rows: Sequence,
        cols: Sequence,
        clear: bool = False,
        timestamps: Optional[Sequence] = None,
        local_only: bool = False,
    ) -> dict:
        """Bulk set-bit import; translates keys, groups bits by shard with
        ONE argsort (timestamps ride the same permutation — no per-shard
        batch rescans) and ships the shard batches to their owner nodes
        BATCHED PER NODE on the bounded import pool (api.go:963-996): the
        grouping/slicing/encoding all run on pool threads, and each peer
        receives one frame carrying every shard it owns from this call
        (fewer, larger RPCs over the retry/breaker plane). The local
        share applies as ONE batched field import while the node frames
        are in flight. Returns an application summary {"applied",
        "expected", "errors"} so callers can detect reduced durability
        when a replica was down (r2 advisor: partial application must be
        visible, not silent)."""
        import time as _time

        self._validate("import_bits", write=True)
        if not local_only:  # replica frames are slices of a capped request
            self._check_write_count(len(cols))
        idx, f = self._index_field(index, field)
        rows, cols = self._translate_import(idx, f, rows, cols)
        stats = self.server.stats.with_tags(f"index:{index}")
        span = self.server.tracer.start_span("api.import")
        with span:
            span.set_tag("index", index)
            span.set_tag("field", field)
            span.set_tag("ingest.bits", int(len(cols)))
            shards = cols >> np.uint64(SHARD_WIDTH_EXPONENT)
            summary = {"applied": 0, "expected": 0, "errors": []}
            t0 = _time.perf_counter()
            if local_only or len(self.cluster.nodes) == 1:
                shard_list = [int(s) for s in np.unique(shards)]
                ts = (
                    [
                        timeq.parse_time(t) if t is not None else None
                        for t in timestamps
                    ]
                    if timestamps is not None
                    else None
                )
                f.import_bits(rows, cols, timestamps=ts, clear=clear)
                idx.track_columns(cols)
                summary["applied"] = summary["expected"] = len(shard_list)
                apply_s = _time.perf_counter() - t0
                route_s = 0.0
                failed = []
            else:
                def local_apply(sel, groups):
                    lts = None
                    if timestamps is not None:
                        lts = [
                            timeq.parse_time(t) if t is not None else None
                            for g in groups
                            for t in g[2]
                        ]
                    f.import_bits(
                        rows[sel], cols[sel], timestamps=lts, clear=clear
                    )
                    idx.track_columns(cols[sel])

                def ship_node(n, gs):
                    # ONE frame per node, sliced + encoded on the pool
                    # thread: cols are absolute, so the receiver's
                    # local-only apply re-groups the multi-shard frame
                    # itself
                    sel = (
                        gs[0][1]
                        if len(gs) == 1
                        else np.concatenate([g[1] for g in gs])
                    )
                    ts = (
                        [t for g in gs for t in g[2]]
                        if timestamps is not None
                        else None
                    )
                    self.server.client.import_bits(
                        n.uri, idx.name, f.name, gs[0][0],
                        rows[sel], cols[sel], clear, timestamps=ts,
                    )

                shard_list, failed, apply_s, route_s = self._import_routed(
                    idx, shards, timestamps, local_apply, ship_node,
                    "import", summary,
                )
            stats.count("ingest.bits", int(len(cols)))
            stats.count("ingest.batches", len(shard_list))
            stats.timing("ingest.apply_ms", apply_s)
            stats.timing("ingest.route_ms", route_s)
            span.set_tag("ingest.batches", len(shard_list))
            # applied shards announce BEFORE a fully-failed shard raises:
            # bits that did land must become query-visible even when a
            # sibling shard in the same call had no reachable owner
            if not local_only and shard_list:
                self._announce_shards(idx.name, f.name, shard_list)
            if failed:
                shard, errs = failed[0]
                raise ApiError(
                    f"import shard {shard}: no owner reachable: {errs}"
                )
            return summary

    def _import_routed(
        self, idx, shards, timestamps, local_apply, ship_node, kind,
        summary,
    ):
        """Multi-node shard routing shared by import_bits and
        import_values — the free-threaded ingest path (ISSUE 12): the
        one-sort shard grouping (argsort + split; numpy releases the
        GIL for the sort) runs on the bounded import pool instead of
        the serving thread, and replica legs are BATCHED PER NODE —
        every shard group bound for one peer ships as ONE frame over
        the PR 1 retry/breaker plane (`ship_node`, executed on the
        pool, does its own slicing and wire encoding there too). A
        replica hiccup therefore costs one bounded retry cycle per
        node instead of one per shard, and degrades to per-shard
        pending-repair debt rather than stalling the leader's commit
        group. The local share applies as ONE batch (`local_apply`)
        while the node frames fly. Fills `summary` with the
        partial-application accounting — a down replica is an error
        entry per shard plus pending-repair debt; a shard with NO live
        owner lands in `failed` for the caller to raise AFTER
        announcing what did apply. Returns (applied_shard_list,
        failed[(shard, errors)], apply_s, route_s)."""
        import time as _time

        from pilosa_tpu.server.client import ClientError

        pool = self.server.import_pool
        t_route0 = _time.perf_counter()
        # the grouping rides its own small pool: import_pool's workers
        # can all be parked in a flapping replica's retry cycle, and the
        # argsort queued behind them would stall healthy local ingest
        groups = self.server.route_pool.submit(
            _group_by_shard, shards, timestamps
        ).result()
        applied = {g[0]: 0 for g in groups}
        shard_errors = {g[0]: [] for g in groups}
        local_groups = []
        by_node = {}
        for g in groups:
            owners = self.cluster.shard_nodes(idx.name, g[0])
            summary["expected"] += len(owners)
            for n in owners:
                if n.id == self.server.node.id:
                    local_groups.append(g)
                else:
                    by_node.setdefault(n.id, (n, []))[1].append(g)
        futures = [
            (n, gs, pool.submit(ship_node, n, gs))
            for n, gs in by_node.values()
        ]
        t0 = _time.perf_counter()
        if local_groups:
            local_apply(np.concatenate([g[1] for g in local_groups]), local_groups)
            for g in local_groups:
                applied[g[0]] += 1
        apply_s = _time.perf_counter() - t0
        for n, gs, fut in futures:
            try:
                fut.result()
                for g in gs:
                    applied[g[0]] += 1
            except ClientError as e:
                # replica fan-out is best-effort per owner: a down replica
                # is repaired by anti-entropy after it returns (the
                # reference likewise keeps accepting writes in DEGRADED,
                # api.go:104). Ledger entries only at replica_n>1: with no
                # second copy AE has nothing to repair from, so an entry
                # could never drain (the summary carries the error). One
                # failed node frame books debt for EVERY shard it carried.
                for g in gs:
                    shard_errors[g[0]].append(f"{n.id}: {e}")
                    if self.cluster.replica_n > 1:
                        self.holder.record_pending_repair(idx.name, g[0], n.id)
                        self.server.stats.count("write_replica_dropped", 1)
                self.server.logger(
                    f"{kind} shards {sorted(g[0] for g in gs)} to replica "
                    f"{n.id} failed (anti-entropy will repair): {e}"
                )
        route_s = _time.perf_counter() - t_route0
        failed = []
        for g in groups:
            if not applied[g[0]]:
                failed.append((g[0], shard_errors[g[0]]))
                continue
            summary["applied"] += applied[g[0]]
            summary["errors"] += shard_errors[g[0]]
        shard_list = [g[0] for g in groups if applied[g[0]]]
        return shard_list, failed, apply_s, route_s

    def import_values(
        self,
        index: str,
        field: str,
        cols: Sequence,
        values: Sequence[int],
        local_only: bool = False,
    ) -> dict:
        import time as _time

        self._validate("import_values", write=True)
        if not local_only:  # replica frames are slices of a capped request
            self._check_write_count(len(cols))
        idx, f = self._index_field(index, field)
        _, cols = self._translate_import(idx, f, None, cols)
        values = np.asarray(values, dtype=np.int64)
        stats = self.server.stats.with_tags(f"index:{index}")
        span = self.server.tracer.start_span("api.import")
        with span:
            span.set_tag("index", index)
            span.set_tag("field", field)
            span.set_tag("ingest.bits", int(len(cols)))
            shards = cols >> np.uint64(SHARD_WIDTH_EXPONENT)
            summary = {"applied": 0, "expected": 0, "errors": []}
            t0 = _time.perf_counter()
            if local_only or len(self.cluster.nodes) == 1:
                shard_list = [int(s) for s in np.unique(shards)]
                f.import_values(cols, values)
                idx.track_columns(cols)
                summary["applied"] = summary["expected"] = len(shard_list)
                apply_s = _time.perf_counter() - t0
                route_s = 0.0
                failed = []
            else:
                def local_apply(sel, groups):
                    f.import_values(cols[sel], values[sel])
                    idx.track_columns(cols[sel])

                def ship_node(n, gs):
                    sel = (
                        gs[0][1]
                        if len(gs) == 1
                        else np.concatenate([g[1] for g in gs])
                    )
                    self.server.client.import_values(
                        n.uri, index, field, gs[0][0], cols[sel], values[sel]
                    )

                shard_list, failed, apply_s, route_s = self._import_routed(
                    idx, shards, None, local_apply, ship_node,
                    "import-value", summary,
                )
            stats.count("ingest.bits", int(len(cols)))
            stats.count("ingest.batches", len(shard_list))
            stats.timing("ingest.apply_ms", apply_s)
            stats.timing("ingest.route_ms", route_s)
            span.set_tag("ingest.batches", len(shard_list))
            if not local_only and shard_list:
                self._announce_shards(idx.name, f.name, shard_list)
            if failed:
                shard, errs = failed[0]
                raise ApiError(
                    f"import-value shard {shard}: no owner reachable: {errs}"
                )
            return summary

    def _index_field(self, index: str, field: str):
        idx = self.holder.index(index)
        if idx is None:
            raise NotFoundError(f"index not found: {index}")
        f = idx.field(field)
        if f is None:
            raise NotFoundError(f"field not found: {field}")
        return idx, f

    def _translate_import(self, idx, f, rows, cols):
        if rows is not None:
            if len(rows) and isinstance(rows[0], str):
                if not f.options.keys:
                    raise ApiError("row keys on an unkeyed field")
                rows = f.translate_store.translate_keys(list(rows))
            rows = np.asarray(rows, dtype=np.uint64)
        if len(cols) and isinstance(cols[0], str):
            if not idx.keys:
                raise ApiError("column keys on an unkeyed index")
            cols = idx.translate_store.translate_keys(list(cols))
        cols = np.asarray(cols, dtype=np.uint64)
        return rows, cols

    def import_roaring(
        self,
        index: str,
        field: str,
        shard: int,
        data: bytes,
        clear: bool = False,
        view: Optional[str] = None,
        local_only: bool = False,
    ) -> int:
        """Zero-parse bulk ingest: a serialized roaring bitmap (pilosa
        dialect or official spec, core/roaring_io.py) whose bit positions are
        fragment positions row*SHARD_WIDTH + col%SHARD_WIDTH, unioned (or
        cleared) in one batch and fanned out to every shard owner
        (reference: api.go:368 ImportRoaring, fragment.go:2255).
        Returns the max changed-bit count across the owners reached."""
        from pilosa_tpu import native
        from pilosa_tpu.core.field import (
            FIELD_TYPE_SET,
            FIELD_TYPE_TIME,
            VIEW_STANDARD,
        )

        self._validate("import_roaring", write=True)
        idx, f = self._index_field(index, field)
        if f.options.type not in (FIELD_TYPE_SET, FIELD_TYPE_TIME):
            # the mutex one-row-per-column invariant and the BSI bit-plane
            # layout both need the parsing import paths (api.go:386 applies
            # the same restriction)
            raise ApiError(
                f"cannot import roaring into {f.options.type} field {field!r}"
            )
        view = view or VIEW_STANDARD
        _validate_view_name(view)
        changed = 0
        owners = self.cluster.shard_nodes(idx.name, shard)
        for n in [self.server.node] if local_only else owners:
            if n.id == self.server.node.id:
                positions = native.roaring_decode(data)
                frag = f._view_create(view).fragment(shard)
                if clear:
                    _, local_changed = frag.import_positions(None, positions)
                else:
                    local_changed, _ = frag.import_positions(positions, None)
                changed = max(changed, local_changed)
                if len(positions) and not clear:
                    cols = np.unique(positions % SHARD_WIDTH) + np.uint64(
                        shard * SHARD_WIDTH
                    )
                    idx.track_columns(cols)
            else:
                changed = max(
                    changed,
                    self.server.client.import_roaring(
                        n.uri, index, field, shard, data, clear=clear, view=view
                    ),
                )
        if not local_only:
            self._announce_shard(index, field, shard)
        return changed

    def export_roaring(
        self, index: str, field: str, shard: int, view: Optional[str] = None
    ) -> bytes:
        """Serialize one fragment as a pilosa-dialect roaring file (the
        interchange inverse of import_roaring)."""
        from pilosa_tpu import native
        from pilosa_tpu.core.field import VIEW_STANDARD

        self._validate("export_roaring")
        idx, f = self._index_field(index, field)
        if view is not None:
            _validate_view_name(view)
        v = f.view(view or VIEW_STANDARD)
        frag = v.fragment_if_exists(shard) if v is not None else None
        if frag is None:
            return native.roaring_encode(np.empty(0, dtype=np.uint64))
        rows, cols = frag.pairs()
        return native.roaring_encode(rows * np.uint64(SHARD_WIDTH) + cols)

    def _announce_shard(self, index: str, field: str, shard: int) -> None:
        """Tell every node the shard now exists so query fan-out covers it
        (reference: field.AddRemoteAvailableShards broadcast)."""
        self._announce_shards(index, field, [shard])

    def _announce_shards(self, index: str, field: str, shards: List[int]) -> None:
        """One availability broadcast for a whole import's shard set — a
        bulk import covering hundreds of shards announces once, not once
        per shard."""
        msg = {
            "type": "available-shards",
            "index": index,
            "field": field,
            "shards": list(shards),
        }
        self.receive_message(msg)
        self._broadcast(msg)

    # -- export (api.go:500 ExportCSV) -------------------------------------

    def export_csv(self, index: str, field: str, shard: Optional[int] = None) -> str:
        self._validate("export_csv")
        idx, f = self._index_field(index, field)
        from pilosa_tpu.core.view import VIEW_STANDARD

        v = f.view(VIEW_STANDARD)
        out = io.StringIO()
        if v is None:
            return ""
        shards = [shard] if shard is not None else sorted(v.fragments)
        for s in shards:
            frag = v.fragment_if_exists(s)
            if frag is None:
                continue
            rows, cols = frag.pairs()
            base = s * SHARD_WIDTH
            for r, c in zip(rows.tolist(), cols.tolist()):
                rk = (
                    f.translate_store.key_for_id(int(r))
                    if f.options.keys
                    else None
                )
                ck = (
                    idx.translate_store.key_for_id(int(base + c))
                    if idx.keys
                    else None
                )
                out.write(
                    f"{rk if rk is not None else int(r)},"
                    f"{ck if ck is not None else int(base + c)}\n"
                )
        return out.getvalue()

    def recalculate_caches(self) -> None:
        """Rebuild all rank caches cluster-wide
        (reference: api.go:1307 RecalculateCaches + its broadcast)."""
        self._validate("recalculate_caches")
        self.holder.recalculate_caches()
        self._broadcast({"type": "recalculate-caches"})

    # -- cluster lifecycle (cluster.go:1141-1561, api.go:1226-1250) --------

    def cluster_join(self, node: dict) -> dict:
        """Admit a node: coordinator drives a resize job adding it to the
        membership (reference: nodeJoin -> listenForJoins -> resize job,
        cluster.go:1796,1141). Returns the job record (poll resize_job)."""
        self._validate("cluster_join", write=True)
        from pilosa_tpu.cluster.topology import Node

        joiner = Node.from_json(node)
        if not joiner.id or not joiner.uri:
            raise ApiError("join requires node id and uri")
        # a fresh node self-reports as its own coordinator; it joins as a
        # plain member (one coordinator per cluster)
        joiner.is_coordinator = False
        cur = self.server.cluster.nodes
        if any(n.id == joiner.id for n in cur):
            # idempotent re-join of a known member: nothing to move
            return {"state": "DONE", "action": "noop", "nodes": [n.to_json() for n in cur]}
        from pilosa_tpu.server.client import ClientError

        try:
            return self.server.start_resize(list(cur) + [joiner], "add-node")
        except ClientError as e:
            raise ApiError(str(e))

    def remove_node(self, node_id: str) -> dict:
        """Reference: api.go:1226 RemoveNode -> nodeLeave resize."""
        self._validate("remove_node", write=True)
        from pilosa_tpu.cluster.topology import Node

        cur = self.server.cluster.nodes
        if not any(n.id == node_id for n in cur):
            raise NotFoundError(f"node not in cluster: {node_id}")
        remaining = [
            Node(
                id=n.id, uri=n.uri, is_coordinator=n.is_coordinator,
                mesh_group=n.mesh_group,
            )
            for n in cur
            if n.id != node_id
        ]
        if not remaining:
            raise ApiError("cannot remove the last node")
        # removing the coordinator transfers coordinatorship (the role of
        # the reference's set-coordinator message, cluster.go:311)
        if not any(n.is_coordinator for n in remaining):
            remaining[0].is_coordinator = True
        from pilosa_tpu.server.client import ClientError

        try:
            return self.server.start_resize(remaining, "remove-node")
        except ClientError as e:
            raise ApiError(str(e))

    def resize_abort(self) -> dict:
        return self.server.abort_resize()

    def resize_job(self) -> dict:
        return self.server.resize_job or {"state": "NONE"}

    # -- cluster info ------------------------------------------------------

    def status(self) -> dict:
        breakers = getattr(self.server.client, "breakers", None)
        return {
            "state": self.server.state,
            "localID": self.server.node.id,
            "clusterID": self.server.cluster_name,
            "nodes": [n.to_json() for n in self.cluster.nodes],
            # replica writes dropped on this node's fan-outs, awaiting
            # anti-entropy repair (visible drift, ISSUE satellite #2)
            "pendingRepairs": self.holder.pending_repair_count(),
            # WAL-staged write positions awaiting a read-barrier merge
            # (bulk-ingest fast path); /cluster/health sums this across
            # members as staging debt
            "walStagedPositions": self.holder.staged_position_count(),
            # peer URI -> circuit state, so operators see shunned peers
            "breakers": breakers.snapshot() if breakers is not None else {},
            # the structured cluster verdict lives one endpoint over
            "health": "/cluster/health",
        }

    def hosts(self) -> List[dict]:
        return [n.to_json() for n in self.cluster.nodes]

    def version(self) -> str:
        from pilosa_tpu import __version__

        return __version__

    def info(self) -> dict:
        """Host info (reference: api.Info — shard width + CPU counts).
        Here the host's hardware is the accelerator, so the devices this
        process holds and the HBM budget in force are reported too."""
        import os as _os

        from pilosa_tpu.core.devcache import DEVICE_CACHE
        from pilosa_tpu.parallel.mesh import device_report

        logical = _os.cpu_count() or 1
        physical = logical
        try:
            pairs = set()
            with open("/proc/cpuinfo") as f:
                phys = core = None
                for line in f:
                    if line.startswith("physical id"):
                        phys = line.split(":")[1].strip()
                    elif line.startswith("core id"):
                        core = line.split(":")[1].strip()
                    elif not line.strip() and phys is not None:
                        pairs.add((phys, core))
                        phys = core = None
            if pairs:
                physical = len(pairs)
        except OSError:
            pass
        return {
            "shardWidth": SHARD_WIDTH,
            "cpuPhysicalCores": physical,
            "cpuLogicalCores": logical,
            "devices": device_report(),
            "hbmBudgetBytes": DEVICE_CACHE.budget_bytes,
        }

    def index_info(self, name: str) -> dict:
        idx = self.holder.index(name)
        if idx is None:
            raise NotFoundError(f"index not found: {name}")
        return {
            "name": idx.name,
            "options": {"keys": idx.keys, "trackExistence": idx.track_existence},
            "shardWidth": SHARD_WIDTH,
            "fields": [f.name for f in idx.fields()],
        }

    def set_coordinator(self, node_id: str) -> dict:
        """Transfer coordinatorship (reference: api.go SetCoordinator ->
        cluster.go:311 setCoordinator): rebuild the membership with the new
        coordinator flag and broadcast the status to every member."""
        self._validate("set_coordinator", write=True)
        from pilosa_tpu.cluster.topology import Node

        cur = self.cluster.nodes
        if not any(n.id == node_id for n in cur):
            raise NotFoundError(f"node not in cluster: {node_id}")
        # preserve liveness marks (a DOWN node must stay DOWN)
        members = [
            Node(
                id=n.id, uri=n.uri,
                is_coordinator=(n.id == node_id), state=n.state,
                mesh_group=n.mesh_group,
            )
            for n in cur
        ]
        old = [
            Node(
                id=n.id, uri=n.uri,
                is_coordinator=n.is_coordinator, state=n.state,
                mesh_group=n.mesh_group,
            )
            for n in cur
        ]
        from pilosa_tpu.server.client import ClientError

        # every member must acknowledge: split coordinatorship would give
        # two nodes the key-translation writer role. On partial delivery,
        # roll the old coordinator back everywhere before failing.
        try:
            self.server._send_status(
                members, members, self.cluster.replica_n, self.server.state,
                require=True,
            )
        except ClientError as e:
            self.server._send_status(
                old, old, self.cluster.replica_n, self.server.state, retries=10
            )
            raise ApiError(f"set-coordinator rolled back: {e}")
        return {"coordinator": node_id}

    def delete_remote_available_shard(self, index: str, field: str, shard: int) -> None:
        """Forget a cluster-known shard (reference:
        handleDeleteRemoteAvailableShard — operational repair for stale
        availability entries)."""
        idx, f = self._index_field(index, field)
        f.remove_remote_available(shard)

    def shard_nodes(self, index: str, shard: int) -> List[dict]:
        return [n.to_json() for n in self.cluster.shard_nodes(index, shard)]

    def max_shards(self) -> Dict[str, int]:
        out = {}
        for idx in self.holder.indexes():
            av = idx.available_shards()
            out[idx.name] = (max(av) + 1) if av else 0
        return out

    # -- message dispatch (server.go:569 receiveMessage) -------------------

    def receive_message(self, msg: dict) -> dict:
        t = msg.get("type")
        if t == "create-index":
            self.holder.create_index_if_not_exists(
                msg["index"],
                keys=msg.get("keys", False),
                track_existence=msg.get("trackExistence", True),
            )
            self.server.wire_translation()
        elif t == "delete-index":
            try:
                self.holder.delete_index(msg["index"])
            except KeyError:
                pass
            self.server.drop_index_telemetry(msg["index"])
        elif t == "create-field":
            idx = self.holder.index(msg["index"])
            if idx is not None:
                idx.create_field_if_not_exists(
                    msg["field"], FieldOptions(**msg.get("options", {}))
                )
            self.server.wire_translation()
        elif t == "delete-field":
            idx = self.holder.index(msg["index"])
            if idx is not None:
                try:
                    idx.delete_field(msg["field"])
                except KeyError:
                    pass
        elif t == "available-shards":
            idx = self.holder.index(msg["index"])
            if idx is not None:
                f = idx.field(msg["field"])
                if f is not None:
                    f.add_remote_available(msg["shards"])
        elif t == "cluster-status":
            self.server.apply_cluster_status(msg)
        elif t == "node-state":
            self.server.set_node_state(msg["node"], msg["state"])
        elif t == "recalculate-caches":
            self.holder.recalculate_caches()
        elif t == "clean-holder":
            # post-resize GC (holder.go:1126 CleanHolder): drop fragments
            # the current topology no longer assigns to this node
            self.server.clean_holder()
        elif t == "resize-quiesce":
            # cutover write barrier: sources stop accepting writes to
            # fragments with armed captures for this job (503 retryable),
            # so the coordinator's final drain provably runs dry before
            # the topology install. Required-ack: a ClientError on this
            # send aborts the job pre-commit.
            self.server.quiesce_job_captures(
                msg.get("job", ""), float(msg.get("ttl", 30.0))
            )
        elif t == "resize-release":
            # streaming-resize normal completion: end this job's write
            # captures and drop the transfer ledger (fragments stay — the
            # cutover committed them)
            self.server.release_job_captures(msg.get("job"))
        elif t == "resize-cleanup":
            # streaming-resize abort: delete fragments this job's
            # transfers created here and release captures — pre-resize
            # topology, debt, and device residency are fully restored
            self.server.resize_cleanup(msg.get("job", ""), aborting=True)
        else:
            raise ApiError(f"unknown cluster message type {t!r}")
        return {"ok": True}


def _field_options_from_json(o: dict) -> FieldOptions:
    return FieldOptions(
        type=o.get("type", "set"),
        cache_type=o.get("cacheType", o.get("cache_type", "ranked")),
        cache_size=o.get("cacheSize", o.get("cache_size", 50000)),
        min=o.get("min", 0),
        max=o.get("max", 0),
        time_quantum=o.get("timeQuantum", o.get("time_quantum", "")),
        keys=o.get("keys", False),
        no_standard_view=o.get("noStandardView", o.get("no_standard_view", False)),
    )
