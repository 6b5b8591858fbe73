"""Distributed executor: cluster fan-out + per-call reduce + failover.

Reference: /root/reference/executor.go:2460-2613 — mapReduce groups shards
by owner node, runs the local subset on the worker pool and ships remote
subsets as Remote=true queries (executor.go:2419 remoteExec); the reduce
loop merges partial results as they arrive and, when a node errors, re-maps
its shards onto surviving replicas (executor.go:2489-2518).

Structure here: DistributedExecutor subclasses the single-node Executor and
intercepts exactly the per-call entry points. A "partial" is the result of
one call restricted to one node's shard subset, executed with remote
semantics (no translation, untrimmed TopN candidates); `_fan_out` computes
partials (local subset via super(), remote via InternalClient) and
`_reduce` folds them per result type — the same shape the reference's
reduceFn table has. TopN keeps its exact two-pass protocol because pass 1/
pass 2 each go through the overridden `_topn_shards` fan-out.

Write calls route by ownership: single-column writes go to every replica
owner of the column's shard (executor.go:2142-2172 fan-out to owners);
row-wide writes (ClearRow/Store) run on every node over its owned shards;
attr writes replicate to all nodes.

Mesh-group execution (exec/meshgroup.py): read fan-outs first fold every
owner node sharing this node's ICI domain (topology mesh_group + the
process-local registry, parallel/mesh.py) into ONE compiled sharded
program with the reduction in program — one dispatch + one blocking host
read for the whole group instead of one HTTP leg per member. HTTP/DCN
legs remain the transport only for nodes OUTSIDE the group; any
mesh-ineligible shape falls back to legs transparently."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Sequence

from pilosa_tpu.utils import tracing
from pilosa_tpu.utils.locks import TrackedLock
from pilosa_tpu.cluster.topology import Cluster
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.core.index import Index
from pilosa_tpu.core.row import Row
from pilosa_tpu.exec.executor import (
    ExecError,
    ExecOptions,
    Executor,
    GroupCount,
    Pair,
    ValCount,
)
from pilosa_tpu.pql.ast import Call
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils.stats import NopStatsClient


def _faults():
    # lazy: pilosa_tpu.server.__init__ imports node -> this module, so a
    # top-level "from pilosa_tpu.server import faults" would be circular
    # when exec.distributed is imported before the server package
    from pilosa_tpu.server import faults

    return faults

DEFAULT_QUERY_DEADLINE = 30.0


class RemoteError(ExecError):
    """A remote node failed to execute its shard subset."""


class DistributedExecutor(Executor):
    def __init__(
        self,
        holder: Holder,
        cluster_fn: Callable[[], Cluster],
        client,
        local_id: str,
        stats=None,
        query_deadline: float = DEFAULT_QUERY_DEADLINE,
        mesh_min_nodes: int = 2,
    ):
        super().__init__(holder)
        self.cluster_fn = cluster_fn
        self.client = client
        self.local_id = local_id
        self.stats = stats if stats is not None else NopStatsClient()
        # overall wall-clock bound on one distributed call's fan-out,
        # covering every re-map round and backoff (config: query-deadline)
        self.query_deadline = query_deadline
        # mesh-group execution ([mesh] min-nodes knob): group-local owner
        # nodes below this count keep their HTTP legs (folding a single
        # node buys nothing); 0 disables the mesh path entirely
        self.mesh_min_nodes = mesh_min_nodes
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pool_mu = TrackedLock("distributed.pool_mu")
        # coherence plane (pilosa_tpu/coherence/): set by NodeServer when
        # [coherence] is enabled. A live lease mirror answers remote
        # version vectors with zero wire round-trips; None = every remote
        # repeat pays the /internal/versions RPC as before.
        self.coherence = None

    def _fanout_pool(self) -> ThreadPoolExecutor:
        """Lazy shared pool for concurrent per-node requests (the role of
        the reference's one-mapper-goroutine-per-node, executor.go:2522).
        Lock-guarded: concurrent first queries must not leak duplicate
        pools (HTTP handler threads share this executor)."""
        with self._pool_mu:
            if self._pool is None:
                # owns: released by close() from NodeServer.stop()
                self._pool = ThreadPoolExecutor(
                    max_workers=16, thread_name_prefix=f"fanout-{self.local_id}"
                )
            return self._pool

    def close(self) -> None:
        """Release the lazy fan-out pool. NodeServer.stop() calls this;
        before it did, every server start/stop cycle stranded up to 16
        idle fanout-* threads for the life of the process."""
        with self._pool_mu:
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=False)

    # ------------------------------------------------------------------
    # fan-out plumbing
    # ------------------------------------------------------------------

    def _cluster(self) -> Cluster:
        return self.cluster_fn()

    def _is_single_node(self) -> bool:
        return len(self._cluster().nodes) <= 1

    def _uri_of(self, node_id: str) -> str:
        n = self._cluster().node_by_id(node_id)
        if n is None:
            raise RemoteError(f"unknown node {node_id}")
        return n.uri

    def _breaker_open(self, uri: str) -> bool:
        faults = _faults()
        breakers = getattr(self.client, "breakers", None) or faults.global_breakers()
        return breakers is not None and breakers.state(uri) == faults.OPEN

    def _fan_out(
        self, idx: Index, c: Call, shards: Optional[Sequence[int]], write: bool = False
    ) -> List[Any]:
        """Run call `c` over the cluster's shards; returns the list of
        partial results (local partial included). Reads go to the first
        live owner per shard with failover re-mapping (executor.go:2497);
        writes go to EVERY live replica owner (executor.go:2142).

        The whole fan-out — every re-map round and backoff included — is
        bounded by `query_deadline`; re-map rounds back off with the
        client's retry policy, and owner selection prefers replicas whose
        circuit breaker is not open (a known-dead peer only gets picked
        when every replica looks dead)."""
        cluster = self._cluster()
        all_shards = self._shards_for(idx, shards, c)
        if write:
            remaining = dict(cluster.shards_by_all_owners(idx.name, all_shards))
        else:
            remaining = dict(cluster.shards_by_node(idx.name, all_shards))
        policy = getattr(self.client, "retry_policy", None) or _faults().RetryPolicy()
        deadline = policy.budget(self.query_deadline)
        partials: List[Any] = []
        failed: set = set()
        attempts = 0
        # flight recorder: one exec.fanout span covers the whole fan-out
        # (all re-map rounds); each per-node request runs inside its own
        # rpc.leg child, ENTERED ON THE POOL THREAD so the internode
        # client sees it as the current span — that is what propagates
        # the trace headers to the peer and hosts the rpc.retries /
        # breaker tags (the pool thread has no inherited contextvars)
        fspan = tracing.start_span("exec.fanout")
        fspan.set_tag("fanout.call", c.name)
        fspan.set_tag("fanout.shards", len(all_shards))
        if write:
            fspan.set_tag("fanout.write", True)
        with fspan:
            # mesh-group fold: owner nodes sharing this node's ICI domain
            # answer as ONE compiled sharded program (exec/meshgroup.py)
            # instead of one HTTP leg each; ineligible shapes fall back to
            # legs below, transparently
            if not write and remaining:
                mesh_nodes = self._mesh_group_nodes(remaining)
                if mesh_nodes and self._mesh_eligible(c):
                    from pilosa_tpu.exec import meshgroup

                    try:
                        partials.append(
                            self._mesh_group_partial(idx, c, mesh_nodes, fspan)
                        )
                    except meshgroup.MeshUnsupported as e:
                        meshgroup.note_fallback()
                        # reason-tagged fallback counter: a silent drop
                        # to HTTP legs is a 5-9x latency regression that
                        # must be visible on dashboards
                        self.stats.with_tags(
                            f"reason:{getattr(e, 'reason', 'unsupported')}"
                        ).count("mesh.fallback")
                    else:
                        for nid in mesh_nodes:
                            remaining.pop(nid, None)
                        fspan.set_tag("fanout.mesh_nodes", len(mesh_nodes))
            while remaining:
                attempts += 1
                if attempts > len(cluster.nodes) + 1:
                    raise RemoteError("shards could not be placed on any live node")
                if deadline.expired():
                    raise RemoteError(
                        f"query deadline ({self.query_deadline}s) exceeded with "
                        f"shards unplaced on nodes {sorted(remaining)}"
                    )
                if attempts > 1:
                    # breathe between re-map rounds: a replica refusing
                    # connections during a restart needs milliseconds, not an
                    # instant second hammering (bounded by the deadline)
                    delay = min(policy.backoff(attempts - 1), deadline.remaining())
                    if delay > 0:
                        policy.sleep(delay)
                # one concurrent request per node (executor.go:2522 mapper
                # goroutines): a slow node no longer serializes the others.
                # RemoteErrors come back as values so failover re-mapping
                # inspects every node's outcome; other exceptions propagate.
                items = list(remaining.items())

                def attempt(t):
                    node_id, node_shards = t
                    with tracing.start_span("rpc.leg", parent=fspan) as leg:
                        leg.set_tag("peer", node_id)
                        leg.set_tag(
                            "leg.local", node_id == self.local_id
                        )
                        leg.set_tag("leg.shards", len(node_shards))
                        try:
                            # each RPC is bounded by the query deadline's
                            # REMAINING time, so a hung (connected-but-
                            # silent) peer cannot stall the fan-out past
                            # the deadline
                            return self._node_partial(
                                idx,
                                c,
                                node_id,
                                node_shards,
                                write=write,
                                timeout=max(0.05, deadline.remaining()),
                                # the peer's admission controller sheds
                                # this leg (429, retryable) when OUR
                                # remaining budget can no longer be met
                                # in its queue
                                deadline=max(0.05, deadline.remaining()),
                            )
                        except RemoteError as e:
                            leg.set_tag("leg.error", str(e)[:200])
                            return e

                if len(items) == 1:
                    outcomes = [attempt(items[0])]
                else:
                    outcomes = list(self._fanout_pool().map(attempt, items))
                retry: Dict[str, List[int]] = {}
                for (node_id, node_shards), res in zip(items, outcomes):
                    if not isinstance(res, RemoteError):
                        partials.append(res)
                        continue
                    failed.add(node_id)
                    if write:
                        # replicas already targeted; drift repairs via
                        # anti-entropy — but the debt must be VISIBLE: record
                        # each dropped (index, shard, replica) for /status and
                        # bump the drop counter (ISSUE satellite #2). Ledger
                        # entries only exist at replica_n>1: with no second
                        # copy there is nothing for AE to repair FROM, so an
                        # entry could never drain (the error surfaces through
                        # the call's own result/logs instead).
                        if cluster.replica_n > 1:
                            for s in node_shards:
                                self.holder.record_pending_repair(
                                    idx.name, s, node_id
                                )
                            self.stats.count(
                                "write_replica_dropped", len(node_shards)
                            )
                        continue
                    # re-map this node's shards to the next live replica,
                    # preferring replicas whose breaker is closed
                    for s in node_shards:
                        owners = [
                            n
                            for n in cluster.shard_nodes(idx.name, s)
                            if n.id not in failed and n.state != "DOWN"
                        ]
                        if not owners:
                            raise RemoteError(
                                f"shard {s} unavailable: all replicas down"
                            )
                        owners.sort(
                            key=lambda n: n.id != self.local_id
                            and self._breaker_open(n.uri)
                        )
                        retry.setdefault(owners[0].id, []).append(s)
                remaining = retry
            fspan.set_tag("fanout.rounds", attempts)
            if failed:
                fspan.set_tag("fanout.failed_peers", sorted(failed))
        return partials

    # ------------------------------------------------------------------
    # mesh-group execution (exec/meshgroup.py)
    # ------------------------------------------------------------------

    def _mesh_group(self) -> str:
        """This node's ICI-domain id per the installed topology ([mesh]
        group knob, carried on every topology install)."""
        return self._cluster().mesh_group_of(self.local_id)

    def _mesh_members(self) -> Dict[str, Any]:
        """node_id -> holder for every group member reachable in-process
        (the registry, parallel/mesh.py) — the local node always is."""
        from pilosa_tpu.parallel import mesh as pmesh

        group = self._mesh_group()
        if not group or self.mesh_min_nodes <= 0:
            return {}
        members = pmesh.group_members(group)
        members[self.local_id] = self.holder
        return members

    def _mesh_group_nodes(
        self, remaining: Dict[str, List[int]]
    ) -> Dict[str, List[int]]:
        """The subset of a read fan-out's owner grouping answerable as one
        mesh-group dispatch: nodes declaring this node's mesh group in the
        topology AND registered in the process-local registry (sharing an
        ICI domain means sharing this process's device mesh). Below the
        min-nodes knob the fold buys nothing over plain legs — {}."""
        members = self._mesh_members()
        if not members:
            return {}
        cluster = self._cluster()
        group = self._mesh_group()
        out = {
            nid: shards
            for nid, shards in remaining.items()
            if nid in members and cluster.mesh_group_of(nid) == group
        }
        # the knob is honored as documented: min-nodes=1 folds even a
        # single group-local owner (saving its HTTP leg when it is a
        # peer); the default of 2 skips the adapter overhead when only
        # this node's own shards are in play
        if len(out) < max(1, self.mesh_min_nodes):
            return {}
        return out

    def _mesh_eligible(self, c: Call) -> bool:
        from pilosa_tpu.exec import meshgroup

        return meshgroup.eligible(c)

    def _mesh_group_index(self, idx: Index, mesh_nodes: Dict[str, List[int]]):
        from pilosa_tpu.exec import meshgroup

        return meshgroup.group_index(idx, self._mesh_members(), mesh_nodes)

    def _mesh_group_partial(
        self, idx: Index, c: Call, mesh_nodes: Dict[str, List[int]], fspan
    ) -> Any:
        """One partial for the WHOLE mesh group: the unchanged single-node
        execution over a group-spanning index adapter, so the result is
        bit-identical to merging the members' per-leg partials (the merge
        is associative) while the device work is one compiled program.
        Count ends in the in-program reduction (plan "total" mode) — one
        dispatch + one scalar-sized blocking read regardless of group
        shard count."""
        from pilosa_tpu.exec import meshgroup

        gidx = self._mesh_group_index(idx, mesh_nodes)
        shard_list = sorted(s for lst in mesh_nodes.values() for s in lst)
        span = tracing.start_span("exec.mesh_dispatch", parent=fspan)
        with span:
            span.set_tag("mesh.group_size", len(mesh_nodes))
            span.set_tag("mesh.local_shards", len(shard_list))
            span.set_tag("mesh.call", c.name)
            if c.name == "Count":
                result, cbytes = meshgroup.mesh_count(self, gidx, c, shard_list)
            else:
                # TopN tallies and bitmap trees ride the unchanged local
                # execution paths over the group adapter (remote
                # semantics: untrimmed candidates, no attr/translate tail)
                result = Executor._execute_call(
                    self, gidx, c, shard_list, ExecOptions(remote=True)
                )
                from pilosa_tpu.shardwidth import WORDS_PER_ROW

                # a row-shaped result gathers its [S, W] stack; tallies
                # and counts read shard-count-bound vectors
                cbytes = (
                    len(shard_list) * WORDS_PER_ROW * 4
                    if isinstance(result, Row)
                    else len(shard_list) * 8
                )
            span.set_tag("mesh.collective_bytes", cbytes)
            meshgroup.note_dispatch(len(mesh_nodes), len(shard_list), cbytes)
        return result

    def _execute_count_batch(
        self, idx: Index, calls: List[Call], shards, opt: Optional[ExecOptions] = None
    ):
        """Coordinator-side multi-Count batching: legal only when EVERY
        call's owners fold into one mesh-group dispatch (operands of the
        mesh and extent paths have incompatible placements — the batcher
        splits its rounds by lowering class for exactly this reason).
        Remote legs and single-node execution keep the local lowering."""
        if (opt is not None and opt.remote) or self._is_single_node():
            return super()._execute_count_batch(idx, calls, shards, opt)
        from pilosa_tpu.exec import meshgroup

        cluster = self._cluster()
        lists = [self._shards_for(idx, shards, c) for c in calls]
        if any(lst != lists[0] for lst in lists[1:]):
            return None
        if not all(self._mesh_eligible(c) for c in calls):
            return None
        remaining = dict(cluster.shards_by_node(idx.name, lists[0]))
        mesh_nodes = self._mesh_group_nodes(remaining)
        if set(mesh_nodes) != set(remaining):
            return None  # cross-group legs present: per-call fan-out
        gidx = self._mesh_group_index(idx, mesh_nodes)
        shard_list = sorted(s for lst in mesh_nodes.values() for s in lst)
        span = tracing.start_span("exec.mesh_dispatch")
        try:
            with span:
                span.set_tag("mesh.group_size", len(mesh_nodes))
                span.set_tag("mesh.local_shards", len(shard_list))
                span.set_tag("mesh.call", f"Count[{len(calls)}]")
                totals, cbytes = meshgroup.mesh_count_batch(
                    self, gidx, calls, shard_list
                )
                span.set_tag("mesh.collective_bytes", cbytes)
                meshgroup.note_dispatch(len(mesh_nodes), len(shard_list), cbytes)
                return totals
        except meshgroup.MeshUnsupported as e:
            meshgroup.note_fallback()
            self.stats.with_tags(
                f"reason:{getattr(e, 'reason', 'unsupported')}"
            ).count("mesh.fallback")
            return None

    # ------------------------------------------------------------------
    # versioned result cache: assembled version vectors (core/resultcache)
    # ------------------------------------------------------------------

    def version_vector(self, idx: Index, ctx, opt: ExecOptions, expect=None):
        """The fan-out's assembled version vector: per owner node, the
        versions of the fragments its partial would read — local and
        in-process mesh members by direct (lock-free) reads, remote
        peers over one parallel /internal/versions round. Per-node shard
        lists are Shift-extended exactly like the legs' execution, so
        the vector covers every fragment a leg actually touches. None =
        uncacheable this round (unreachable peer, first sighting of an
        RPC-vector key, topology lookup failure). `expect` (the
        store-path guard's pre-execution vector): when the CHEAP
        in-process parts already diverge from it — continuous local
        ingest racing the query — bail before paying the remote RPC
        round for a store that cannot succeed."""
        if opt.remote or self._is_single_node():
            return super().version_vector(idx, ctx, opt)
        from pilosa_tpu.core import resultcache as rcache

        cluster = self._cluster()
        try:
            remaining = dict(
                cluster.shards_by_node(idx.name, list(ctx.shard_list))
            )
        except Exception:  # noqa: BLE001 - assembly is best-effort
            return None
        members = self._mesh_members()
        parts: List[Any] = []
        rpc: List[tuple] = []
        for nid in sorted(remaining):
            node_shards = tuple(
                Executor._shards_for(
                    self, idx, sorted(remaining[nid]), ctx.call
                )
            )
            if nid == self.local_id:
                parts.append(
                    self.local_version_vector(
                        idx, ctx.views, node_shards, node=nid
                    )
                )
            elif nid in members:
                idx2 = members[nid].index(idx.name)
                if idx2 is None:
                    return None
                parts.append(
                    self.local_version_vector(
                        idx2, ctx.views, node_shards, node=nid
                    )
                )
            else:
                rpc.append((nid, node_shards))
                parts.append(None)
        if rpc:
            if expect is not None and not self._parts_match_expect(
                parts, expect, len(ctx.views)
            ):
                return None
            mgr = self.coherence
            if mgr is not None and mgr.leases_enabled:
                fetched = self._leased_remote_versions(idx, ctx, rpc, mgr)
            else:
                # remote versions cost one RTT per peer: only repeat keys
                # pay it (a one-off query would be taxed for nothing)
                if not rcache.RESULT_CACHE.note_candidate(ctx.key):
                    return None
                fetched = self._fetch_remote_versions(idx, ctx, rpc)
            if fetched is None:
                return None
            it = iter(fetched)
            parts = [next(it) if p is None else p for p in parts]
        out: List[tuple] = []
        for elems in parts:
            out.extend(elems)
        return tuple(out)

    def clock_vector(self, idx: Index, ctx, opt: ExecOptions):
        """The O(#views) clock fast path applies only where every clock
        is readable in-process (single node, remote legs): coordinator
        entries span peers whose clocks live behind the same RPC the
        exact vector rides, so the fast path would save nothing."""
        if opt.remote or self._is_single_node():
            return super().clock_vector(idx, ctx, opt)
        return None

    @staticmethod
    def _parts_match_expect(parts, expect, views_per_node) -> bool:
        """Whether every already-collected (in-process) per-node part
        equals its positional slice of `expect` — each node contributes
        exactly one element per referenced view, so slices align unless
        the assignment itself changed (then the mismatch is the right
        answer too)."""
        o = 0
        for p in parts:
            if p is not None and tuple(expect[o:o + views_per_node]) != p:
                return False
            o += views_per_node
        return True

    def _leased_remote_versions(self, idx: Index, ctx, rpc, mgr):
        """Lease-plane replacement for the per-peer version round: a
        live mirror answers a peer's element slice with ZERO wire RTTs;
        uncovered peers try one lease acquire (which replaces this
        round's version RPC and every later one — the mirror then
        serves ALL keys over this (peer, index)) before degrading to
        the plain fetch. Deliberately NO note_candidate gate: the lease
        is per-(peer, index) and amortizes across every key, so even a
        first-sighted key rides it — and because mirror elements are
        bit-identical to /internal/versions elements, a fresh grant
        retro-covers entries stored from earlier RPC vectors (the
        second hit after lease grant is already RTT-free, not the
        third). coherence.version_rtts counts only the rounds that
        still paid a wire fetch."""
        need: List[tuple] = []
        slots: Dict[int, tuple] = {}
        for pos, (nid, node_shards) in enumerate(rpc):
            # the peer extends the shard list it receives by the call's
            # Shift count before reading versions (versions_payload);
            # mirror reads must cover the same extended axis to stay
            # element-identical with fetched vectors
            ext = tuple(
                Executor._shards_for(self, idx, sorted(node_shards), ctx.call)
            )
            elems = mgr.mirror_elements(nid, idx.name, ctx.views, ext)
            if elems is None and mgr.acquire(
                nid, self._uri_of(nid), idx.name
            ):
                elems = mgr.mirror_elements(nid, idx.name, ctx.views, ext)
            if elems is None:
                need.append((nid, node_shards))
            else:
                slots[pos] = elems
        if need:
            mgr.count_version_rtt(len(need))
            fetched = self._fetch_remote_versions(idx, ctx, need)
            if fetched is None:
                return None
            it = iter(fetched)
            for pos in range(len(rpc)):
                if pos not in slots:
                    slots[pos] = next(it)
        return [slots[pos] for pos in range(len(rpc))]

    def _fetch_remote_versions(self, idx: Index, ctx, rpc):
        """One parallel /internal/versions round; None when any peer is
        unreachable or reports the call ineligible on its side."""
        def fetch(t):
            nid, node_shards = t
            try:
                resp = self.client.fragment_versions(
                    self._uri_of(nid), idx.name, ctx.text, list(node_shards)
                )
            except Exception:  # noqa: BLE001 - degrade to uncacheable
                return None
            if not isinstance(resp, dict) or resp.get("views") is None:
                return None
            boot = str(resp.get("boot", ""))
            try:
                shards = tuple(int(s) for s in resp.get("shards", node_shards))
                elems = []
                for item in resp["views"]:
                    if item[0] == "m":
                        elems.append(("m", nid, item[1], item[2]))
                    else:
                        elems.append(
                            ("v", nid, item[1], item[2],
                             (boot, int(item[3])), shards,
                             tuple(int(x) for x in item[4]))
                        )
                return tuple(elems)
            except Exception:  # noqa: BLE001 - malformed peer payload
                return None

        if len(rpc) == 1:
            fetched = [fetch(rpc[0])]
        else:
            fetched = list(self._fanout_pool().map(fetch, rpc))
        if any(f is None for f in fetched):
            return None
        return fetched

    def versions_payload(self, index_name: str, pql: str, shards):
        """Serve /internal/versions (server/handler.py): this node's
        version vector for one call over `shards`, Shift-extended the
        way a leg's execution would extend them. Returns (shard_list,
        elements) or None when the call is cache-ineligible here."""
        idx = self.holder.index(index_name)
        if idx is None:
            return None
        from pilosa_tpu.pql import parse
        from pilosa_tpu.pql.parser import ParseError

        try:
            q = parse(pql)
        except ParseError:
            return None
        if len(q.calls) != 1:
            return None
        c = q.calls[0]
        ctx = self._cache_spec(
            idx, c, list(shards), ExecOptions(remote=True)
        )
        if ctx is None:
            return None
        shard_list = tuple(
            Executor._shards_for(self, idx, sorted(int(s) for s in shards), c)
        )
        out = []
        for elem in self.local_version_vector(idx, ctx.views, shard_list):
            if elem[0] == "m":
                out.append(["m", elem[2], elem[3]])
            else:
                out.append(["v", elem[2], elem[3], elem[4], list(elem[6])])
        return list(shard_list), out

    def count_lowering_class(self, index_name: str, query) -> str:
        """Which lowering a pure-Count query's batch round would ride:
        "mesh" when every call folds into one mesh-group dispatch,
        "fanout" when any call needs HTTP legs, "local" on a single node.
        The CountBatcher splits its group-commit rounds by this key —
        merging a mesh-path Count with a fan-out Count into one multi-root
        plan would hand XLA operands with incompatible placements.
        Classification must never fail a query: errors degrade to
        "fanout" (per-call execution is always correct)."""
        try:
            if self._is_single_node():
                return "local"
            idx = self.holder.index(index_name)
            if idx is None:
                return "fanout"
            cluster = self._cluster()
            for c in query.calls:
                if not self._mesh_eligible(c):
                    return "fanout"
                shard_list = self._shards_for(idx, None, c)
                remaining = dict(cluster.shards_by_node(idx.name, shard_list))
                mesh_nodes = self._mesh_group_nodes(remaining)
                if set(mesh_nodes) != set(remaining):
                    return "fanout"
            return "mesh"
        except Exception:  # noqa: BLE001 - classification is advisory
            return "fanout"

    def transport_profile(self, idx: Index, shards=None) -> Optional[Dict[str, int]]:
        """Admission-time transport split for sched/cost.py's collective
        terms: how many of the query's shards fold into the mesh-group
        collective vs ride cross-group HTTP legs. `device_shards` is the
        shard axis THIS node's device actually materializes — the whole
        group's shards when the fold engages (the one compiled program
        stages every member's operands here, while the members admit no
        leg) plus the local-only share — which the api layer feeds to the
        cost estimator so a mesh dispatch is byte-charged in full, not at
        the coordinator's 1/N share. Metadata walk only; failures degrade
        to None (the caller keeps its local-share heuristic)."""
        try:
            if self._is_single_node():
                return {
                    "mesh_shards": 0, "legs": 0, "leg_shards": 0,
                    "device_shards": 0,
                }
            all_shards = self._shards_for(idx, shards, None)
            remaining = dict(
                self._cluster().shards_by_node(idx.name, all_shards)
            )
            mesh_nodes = self._mesh_group_nodes(remaining)
            mesh_shards = sum(len(v) for v in mesh_nodes.values())
            # the local node's own share crosses no link: it is neither a
            # DCN leg nor (unless folded with peers) a collective
            legs = [
                n
                for n in remaining
                if n not in mesh_nodes and n != self.local_id
            ]
            leg_shards = sum(len(remaining[n]) for n in legs)
            local_only = (
                0
                if self.local_id in mesh_nodes
                else len(remaining.get(self.local_id, []))
            )
            return {
                "mesh_shards": mesh_shards,
                "legs": len(legs),
                "leg_shards": leg_shards,
                "device_shards": mesh_shards + local_only,
            }
        except Exception:  # noqa: BLE001 - estimation must never fail
            return None

    def _node_partial(
        self,
        idx: Index,
        c: Call,
        node_id: str,
        node_shards: List[int],
        write: bool = False,
        timeout: Optional[float] = None,
        deadline: Optional[float] = None,
    ) -> Any:
        if node_id == self.local_id:
            opt = ExecOptions(remote=True)
            return super()._execute_call(idx, c, node_shards, opt)
        try:
            results = self.client.query_node(
                self._uri_of(node_id),
                idx.name,
                str(c),
                shards=node_shards,
                remote=True,
                timeout=timeout,
                deadline=deadline,
            )
        except Exception as e:
            # reads: node-down shaped failures fail over to a replica; a
            # non-retryable ClientError (4xx / remote payload error) means
            # the peer is alive and rejected the request — replaying the
            # same bad request on a replica cannot succeed (satellite #1).
            # writes: EVERY failure stays RemoteError-shaped so the write
            # path records pending-repair debt for this replica and keeps
            # going instead of aborting the fan-out mid-flight with other
            # replicas already written.
            if write or getattr(e, "retryable", True):
                raise RemoteError(f"node {node_id}: {e}") from e
            raise ExecError(f"node {node_id}: {e}") from e
        return results[0]

    # ------------------------------------------------------------------
    # reduce table
    # ------------------------------------------------------------------

    @staticmethod
    def _reduce_rows(partials: List[Any]) -> Row:
        out = Row()
        for p in partials:
            if isinstance(p, Row):
                out = out.union(p)
        return out

    def _reduce(self, name: str, c: Call, partials: List[Any]) -> Any:
        partials = [p for p in partials if p is not None]
        if name in (
            "Row", "Union", "Intersect", "Difference", "Xor", "Not",
            "Shift", "Range", "All",
        ):
            return self._reduce_rows(partials)
        if name == "Count":
            return sum(int(p) for p in partials)
        if name in ("Clear", "ClearRow", "Store"):
            return any(bool(p) for p in partials)
        if name == "Sum":
            vc = ValCount(0, 0)
            for p in partials:
                vc = ValCount(vc.value + p.value, vc.count + p.count)
            return vc
        if name in ("Min", "Max"):
            best: Optional[ValCount] = None
            for p in partials:
                if p.count == 0:
                    continue
                if best is None:
                    best = ValCount(p.value, p.count)
                elif (p.value < best.value) == (name == "Min") and p.value != best.value:
                    best = ValCount(p.value, p.count)
                elif p.value == best.value:
                    best = ValCount(best.value, best.count + p.count)
            return best or ValCount(0, 0)
        if name in ("MinRow", "MaxRow"):
            best = None
            for p in partials:
                if not p or p.get("count", 0) == 0:
                    continue
                if best is None:
                    best = dict(p)
                elif p["id"] == best["id"]:
                    best["count"] += p["count"]
                elif (p["id"] < best["id"]) == (name == "MinRow"):
                    best = dict(p)
            return best or {"id": 0, "count": 0}
        if name == "Rows":
            merged = set()
            for p in partials:
                merged.update(p)
            out = sorted(merged)
            limit = c.uint_arg("limit")
            prev = c.uint_arg("previous")
            if prev is not None:
                out = [r for r in out if r > prev]
            if limit is not None:
                out = out[:limit]
            return out
        if name == "GroupBy":
            merged: Dict[tuple, GroupCount] = {}
            for p in partials:
                for gc in p:
                    key = tuple((fr.field, fr.row_id) for fr in gc.group)
                    if key in merged:
                        merged[key].count += gc.count
                        if gc.sum is not None:  # aggregate=Sum: exact ints
                            merged[key].sum = (merged[key].sum or 0) + gc.sum
                    else:
                        merged[key] = GroupCount(
                            group=list(gc.group), count=gc.count, sum=gc.sum
                        )
            out = sorted(merged.values(), key=lambda g: g.compare_key())
            offset = c.uint_arg("offset")
            limit = c.uint_arg("limit")
            if offset:
                out = out[offset:]
            if limit is not None:
                out = out[:limit]
            return out
        raise ExecError(f"no distributed reduce for call {name!r}")

    # ------------------------------------------------------------------
    # call interception
    # ------------------------------------------------------------------

    _FANOUT_CALLS = {
        "Row", "Union", "Intersect", "Difference", "Xor", "Not", "Shift",
        "Range", "All", "Count", "Sum", "Min", "Max", "MinRow", "MaxRow",
        "Rows", "GroupBy", "ClearRow", "Store",
    }

    def _counts_batchable(self, opt: ExecOptions) -> bool:
        # batching evaluates locally over the given shard list, which is
        # only this node's responsibility under remote/single-node
        # execution. Coordinator-side batches are legal exactly when the
        # mesh-group path can fold EVERY call into one sharded dispatch —
        # _execute_count_batch checks per batch and returns None (per-call
        # fan-out) otherwise.
        return opt.remote or self._is_single_node() or self.mesh_min_nodes > 0

    def _execute_call(self, idx: Index, c: Call, shards, opt: ExecOptions):
        if opt.remote or self._is_single_node():
            return super()._execute_call(idx, c, shards, opt)
        name = c.name
        if name in ("Set", "Clear"):
            return self._execute_write_by_column(idx, c)
        if name in ("SetRowAttrs", "SetColumnAttrs"):
            # attrs replicate to every node (reference broadcasts attr writes)
            super()._execute_call(idx, c, shards, ExecOptions(remote=True))
            self._broadcast_call(idx, c)
            return None
        if name == "Options":
            return super()._execute_call(idx, c, shards, opt)
        if name == "TopN":
            return self._execute_topn_distributed(idx, c, shards, opt)
        if name in self._FANOUT_CALLS:
            partials = self._fan_out(
                idx, c, shards, write=name in ("ClearRow", "Store")
            )
            out = self._reduce(name, c, partials)
            if isinstance(out, Row):
                # attrs/exclusions attach on the coordinator only
                # (reference: executeBitmapCall runs the tail on the
                # non-remote node, executor.go:595-647)
                out = self._finish_bitmap_row(idx, c, out, opt)
            return out
        return super()._execute_call(idx, c, shards, opt)

    def _execute_write_by_column(self, idx: Index, c: Call) -> bool:
        """Route a single-column write to every replica owner of its shard
        (executor.go:2142-2172 executeSetBitField)."""
        col = c.args.get("_col")
        if not isinstance(col, int) or isinstance(col, bool):
            raise ExecError(f"{c.name}() column argument required")
        shard = col // SHARD_WIDTH
        cluster = self._cluster()
        owners = cluster.shard_nodes(idx.name, shard)
        changed = False
        errs = []
        failed_nodes = []
        for n in owners:
            try:
                if n.id == self.local_id:
                    r = super()._execute_call(
                        idx, c, [shard], ExecOptions(remote=True)
                    )
                else:
                    r = self.client.query_node(
                        n.uri, idx.name, str(c), shards=[shard], remote=True,
                        # bound the peer-side admission wait: without a
                        # deadline a saturated peer parks this leg's
                        # handler thread indefinitely — long after we
                        # timed out and recorded pending-repair debt
                        timeout=self.query_deadline,
                        deadline=self.query_deadline,
                    )[0]
                changed = changed or bool(r)
            except Exception as e:
                errs.append(f"{n.id}: {e}")
                failed_nodes.append(n)
        if errs and len(errs) == len(owners):
            raise RemoteError("; ".join(errs))
        # partial application: some replica missed this write — visible
        # pending-repair debt instead of silent drift (satellite #2).
        # Only REMOTE replicas at replica_n>1 are recorded: a local-apply
        # failure is not replica drift (the primary's normal AE pushes to
        # us), a self-keyed entry could never be resolved by any sync
        # path, and at replica_n<=1 there is no second copy to repair
        # from so the entry could never drain.
        dropped = [n for n in failed_nodes if n.id != self.local_id]
        if cluster.replica_n > 1:
            for n in dropped:
                self.holder.record_pending_repair(idx.name, shard, n.id)
            if dropped:
                self.stats.count("write_replica_dropped", len(dropped))
        if c.name == "Set":
            self._announce_written_shard(idx, c, shard)
        return changed

    def _announce_written_shard(self, idx: Index, c: Call, shard: int) -> None:
        """Make a newly-created shard visible to cluster-wide fan-out
        (reference: field.AddRemoteAvailableShards broadcast on write)."""
        try:
            field_name = self._field_arg_name(c)
        except ExecError:
            return
        f = idx.field(field_name)
        if f is None:
            return
        # remote_available_shards doubles as "already announced cluster-wide"
        if shard in f.remote_available_shards:
            return
        f.add_remote_available([shard])
        msg = {
            "type": "available-shards",
            "index": idx.name,
            "field": field_name,
            "shards": [shard],
        }

        def send(n):
            try:
                self.client.send_message(n.uri, msg)
            except Exception:
                pass  # peers discover via the next import/announce

        self._to_peers(send)

    def _broadcast_call(self, idx: Index, c: Call) -> None:
        pql = str(c)

        def send(n):
            try:
                self.client.query_node(
                    n.uri, idx.name, pql, shards=None, remote=True,
                    # deadline-bounded so a saturated peer sheds the
                    # broadcast early instead of parking it forever
                    # (drift repairs via anti-entropy either way)
                    timeout=self.query_deadline,
                    deadline=self.query_deadline,
                )
            except Exception:
                pass  # attr drift repairs via anti-entropy

        self._to_peers(send)

    def _to_peers(self, fn) -> None:
        """Run fn(node) for every live peer concurrently — a slow peer must
        not stall a write path (VERDICT r2 weak #3)."""
        peers = [
            n
            for n in self._cluster().nodes
            if n.id != self.local_id and n.state != "DOWN"
        ]
        if not peers:
            return
        if len(peers) == 1:
            fn(peers[0])
            return
        list(self._fanout_pool().map(fn, peers))

    def _topn_fan_out(self, idx: Index, c: Call, shards) -> List[Pair]:
        """One TopN pass across the cluster: partials are untrimmed
        per-node candidate lists with exact per-node counts."""
        partials = self._fan_out(idx, c, shards)
        merged: Dict[int, int] = {}
        for p in partials:
            for pair in p or []:
                merged[pair.id] = merged.get(pair.id, 0) + pair.count
        pairs = [Pair(id=i, count=cnt) for i, cnt in merged.items()]
        pairs.sort(key=lambda p: (-p.count, p.id))
        return pairs

    def _execute_topn_distributed(
        self, idx: Index, c: Call, shards, opt: ExecOptions
    ) -> List[Pair]:
        """Coordinator-level two-pass TopN (executor.go:860-999): pass 1
        collects per-node candidates; pass 2 re-counts the merged candidate
        ids exactly on every node."""
        pairs = self._topn_fan_out(idx, c, shards)
        n = c.uint_arg("n")
        if not pairs or c.args.get("ids"):
            return pairs
        other = Call(c.name, dict(c.args), list(c.children))
        other.args["ids"] = sorted(p.id for p in pairs)
        trimmed = self._topn_fan_out(idx, other, shards)
        if n and len(trimmed) > n:
            trimmed = trimmed[:n]
        return trimmed

    def _shards_for(self, idx: Index, shards, call: Optional[Call] = None) -> List[int]:
        """Cluster-wide shard list: the union of available shards known
        locally plus remote-available bitmaps (field.go:88)."""
        if shards is not None:
            return super()._shards_for(idx, shards, call)
        s = set(idx.available_shards())
        for f in idx.fields(include_hidden=True):
            s.update(f.remote_available_shards)
        base = sorted(s) or [0]
        return super()._shards_for(idx, base, call)
