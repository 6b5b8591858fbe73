"""NodeServer: composition root for one cluster node.

Reference: /root/reference/server.go — Server owns holder + cluster +
executor + background loops (anti-entropy :514, runtime metrics :813) and
dispatches received broadcast messages (:569). Bootstrap is the
server/server.go SetupServer path.

TPU-native membership: the mesh is STATIC configuration (a list of node
ids/URIs), the JAX-distributed-runtime model, instead of SWIM gossip —
liveness is detected by HTTP /status probes (the reference also
belt-and-suspenders probes over HTTP, cluster.go:1724-1752). Elasticity is
STREAMING resharding under live traffic (the reference's resizeJob +
ResizeInstruction flow, cluster.go:1141-1561): each moving fragment ships
as a full snapshot plus a live write capture replayed at read barriers
(core/fragment.py begin_streaming/drain_capture), and ownership cuts over
atomically in the coordinator's job FSM via a required-ack topology
install — writes are never globally frozen, only a per-fragment drain
window. The older checkpoint path (`resize_to` under a RESIZING freeze)
remains as the manual/bootstrap fallback."""

from __future__ import annotations

import os
import threading
import time
import traceback
from typing import Dict, List, Optional, Sequence

from pilosa_tpu.utils import tracing
from pilosa_tpu.utils.locks import TrackedLock

from pilosa_tpu.cluster.topology import (
    STATE_NORMAL,
    STATE_RESIZING,
    Cluster,
    JumpHasher,
    Node,
)
from pilosa_tpu.cluster import antientropy
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec.distributed import DistributedExecutor
from pilosa_tpu.server import faults
from pilosa_tpu.server.client import ClientError, InternalClient


class _ResizeAborted(Exception):
    pass


# Source-side write captures self-expire after this many seconds without a
# drain: a coordinator (or destination) that died mid-transfer must not
# leave sources buffering deltas forever. Each capture-plane request
# refreshes its own lease and sweeps expired ones.
CAPTURE_LEASE = 600.0

# Catch-up rounds per stream step: the loop exits early when a round
# drains zero positions; this only bounds pathological write storms (the
# cutover-timeout knob bounds the wall clock of the same loop).
_MAX_CATCHUP_ROUNDS = 8


class NodeServer:
    def __init__(
        self,
        data_dir: Optional[str],
        node_id: str,
        *,
        bind: str = "localhost:0",
        replica_n: int = 1,
        hasher=None,
        cluster_name: str = "cluster0",
        anti_entropy_interval: float = 0.0,  # 0 = manual sync only
        cache_flush_interval: float = 60.0,  # 0 = flush on close only
        probe_interval: float = 0.0,  # 0 = no background liveness loop
        stats_service: str = "expvar",  # expvar|prometheus|statsd|none
        stats_host: str = "localhost:8125",  # statsd daemon (service="statsd")
        metric_poll_interval: float = 0.0,  # 0 = no runtime poller
        long_query_time: float = 0.0,  # seconds; 0 = disabled
        logger=None,
        tls_cert: str = "",  # PEM chain; with tls_key, serve HTTPS
        tls_key: str = "",
        tls_skip_verify: bool = False,  # internode client: trust any cert
        tls_ca_cert: str = "",  # internode client: pin this CA instead
        retry_max_attempts: int = 3,  # internode RPC attempts per budget
        retry_base_backoff: float = 0.05,  # first-retry backoff, seconds
        breaker_threshold: int = 5,  # consecutive failures before open
        breaker_cooldown: float = 2.0,  # seconds open before half-open
        query_deadline: float = 30.0,  # distributed fan-out wall bound
        max_concurrent_queries: int = 16,  # admission cap (0 disables sched)
        admission_queue_depth: int = 128,  # bounded admission queue
        admission_byte_budget: int = 0,  # in-flight bytes; 0 = devcache budget
        admission_default_class: str = "interactive",  # headerless queries
        shed_retry_after: float = 1.0,  # Retry-After seconds on 429 (floor)
        tenant_default_qps: float = 0.0,  # per-index query rate; 0 = unlimited
        tenant_default_bytes_per_s: float = 0.0,  # per-index device-byte rate
        tenant_default_inflight_bytes: int = 0,  # per-index in-flight byte cap
        tenant_default_hbm_bytes: int = 0,  # per-index devcache residency quota
        tenant_default_cache_bytes: int = 0,  # per-index result-cache quota
        tenant_overrides: Sequence[str] = (),  # "idx:qps=5;hbm-bytes=65536"
        hbm_extent_rows: int = 256,  # shards per operand extent; 0 = monolithic
        hbm_prefetch_depth: int = 0,  # warm-queue bound; 0 disables prefetch
        hbm_pin_timeout: float = 60.0,  # stale-pin safety valve, seconds
        bsi_slab_planes: int = 16,  # BSI planes per streamed dispatch; <=0 default
        merge_device_threshold: Optional[int] = None,  # None = backend AUTO
        wal_sync_interval: float = 0.0,  # 0 strict; >0 bounded-loss cadence, s
        mesh_group: str = "",  # ICI domain id; "" = no mesh-local execution
        mesh_min_nodes: int = 2,  # group-local owners before the fold engages; 0 off
        mesh_ici_gbps: float = 100.0,  # intra-group collective link (cost model)
        mesh_dcn_gbps: float = 3.0,  # cross-group HTTP/DCN link (cost model)
        cache_result_mb: int = 64,  # result-cache LRU budget, MB; 0 disables
        cache_count_repair: bool = True,  # in-place Count repair on bursts
        import_concurrency: int = 8,  # parallel replica-import RPCs per call
        max_writes_per_request: int = 5000,  # bits/values per import; 0 = no cap
        resize_transfer_concurrency: int = 4,  # parallel fragment fetches
        resize_cutover_timeout: float = 30.0,  # catch-up barrier bound, s
        resize_resume_policy: str = "resume",  # resume|abort on failed leg
        tracing_enabled: bool = True,  # sample root spans at all
        trace_sample_rate: float = 1.0,  # fraction of root queries traced
        trace_ring: int = 1024,  # spans kept in the per-node ring
        telemetry_sample_interval: float = 5.0,  # timeline tick, s; 0=off
        telemetry_ring: int = 720,  # utilization samples kept per node
        tier_store_path: str = "",  # object-store dir; "" disables the tier
        tier_store=None,  # injected ObjectStore (tests/harness); wins over path
        tier_placement: str = "hot",  # default per-index placement
        tier_overrides: Sequence[str] = (),  # "idx:placement=cold"
        tier_demote_after: float = 300.0,  # idle seconds before demotion; 0 off
        tier_host_budget_bytes: int = 0,  # local snap+wal byte cap; 0 = no cap
        tier_fetch_concurrency: int = 4,  # parallel object-store transfers
        coherence_lease_duration: float = 0.0,  # s; 0 disables version leases
        coherence_publish_batch_ms: float = 20.0,  # bump batch/flush tick, ms
        coherence_max_subscriptions: int = 64,  # per-node cap; 0 disables subs
        coherence_sub_poll_interval: float = 5.0,  # unleased refresh floor, s
    ):
        self.data_dir = data_dir
        # durable node identity: a data dir that already carries a .id keeps
        # it across restarts regardless of flags (reference:
        # holder.go:599-621 loadNodeID) — placement is keyed by id, so an id
        # change would orphan every fragment the node holds
        node_id = self._load_or_create_id(node_id)
        # a fresh node is its own coordinator until a topology install says
        # otherwise (set_topology syncs identity from the membership list)
        self.mesh_group_name = mesh_group
        self.node = Node(
            id=node_id, uri="", is_coordinator=True, mesh_group=mesh_group
        )
        self.bind = bind
        self.cluster = Cluster(
            nodes=[self.node], replica_n=replica_n, hasher=hasher or JumpHasher()
        )
        self.cluster_name = cluster_name
        self.state = STATE_NORMAL
        self.holder = Holder(data_dir)
        # TLS plane (reference: server/config.go:151-157 applied in
        # server.go:222-295): one cert/key pair serves both the client API
        # and the internode plane; the internode client carries the trust
        # config so replication/AE/resize all ride the same channel.
        self.tls_cert = tls_cert
        self.tls_key = tls_key
        if bool(tls_cert) != bool(tls_key):
            raise ValueError("tls_cert and tls_key must be set together")
        from pilosa_tpu.utils import stats as statsmod

        self.stats = statsmod.new_stats_client(stats_service, host=stats_host)
        self.logger = logger or (lambda msg: None)
        # fault-tolerance plane (server/faults.py): one retry policy and
        # one per-peer breaker registry shared by EVERY internode path —
        # queries, probes, broadcasts, anti-entropy, and resize all ride
        # the same policy instead of ad-hoc timeouts
        self.retry_policy = faults.RetryPolicy(
            max_attempts=retry_max_attempts, base_backoff=retry_base_backoff
        )
        self.breakers = faults.BreakerRegistry(
            threshold=breaker_threshold,
            cooldown=breaker_cooldown,
            stats=self.stats,
            logger=self.logger,
        )
        self.client = InternalClient(
            tls_skip_verify=tls_skip_verify,
            tls_ca_cert=tls_ca_cert,
            retry_policy=self.retry_policy,
            breakers=self.breakers,
            stats=self.stats,
        )
        self.executor = DistributedExecutor(
            self.holder,
            lambda: self.cluster,
            self.client,
            node_id,
            stats=self.stats,
            query_deadline=query_deadline,
            mesh_min_nodes=mesh_min_nodes,
        )
        # mesh collective-cost link classes (sched/cost.py): process-global
        # like the [hbm]/[ingest] knobs — all in-process nodes share one
        # device mesh, so the last-constructed server's values win
        from pilosa_tpu.sched import cost as costmod

        costmod.configure_links(ici_gbps=mesh_ici_gbps, dcn_gbps=mesh_dcn_gbps)
        # cross-request group-commit Count batching (exec/batcher.py)
        from pilosa_tpu.exec.batcher import CountBatcher

        self.count_batcher = CountBatcher()
        self.count_batcher.stats = self.stats
        # group-commit rounds split by lowering class: a merged multi-root
        # plan must not mix mesh-group and fan-out/extent Counts
        self.count_batcher.classify = self.executor.count_lowering_class
        # query admission control & QoS (pilosa_tpu/sched/): every query
        # is admitted before it may dispatch — bounded concurrency, a
        # bounded priority queue, 429 load shedding — and the observed
        # load feeds the count batcher so batch size grows under load
        # multi-tenant QoS policy (sched/tenants.py): per-index token
        # buckets and byte quotas. One policy object is shared by the
        # scheduler (admission-time rate limits + inflight quota), the
        # prefetcher gate, and both caches (residency quotas) so a single
        # [tenants] section governs every enforcement point.
        from pilosa_tpu.sched.tenants import TenantPolicy

        self.tenant_policy = TenantPolicy(
            default_qps=tenant_default_qps,
            default_bytes_per_s=tenant_default_bytes_per_s,
            default_inflight_bytes=tenant_default_inflight_bytes,
            default_hbm_bytes=tenant_default_hbm_bytes,
            default_cache_bytes=tenant_default_cache_bytes,
            overrides=tenant_overrides,
        )
        self.scheduler = None
        if max_concurrent_queries > 0:
            from pilosa_tpu.sched.admission import AdmissionController

            self.scheduler = AdmissionController(
                max_concurrent=max_concurrent_queries,
                queue_depth=admission_queue_depth,
                byte_budget=admission_byte_budget,
                default_class=admission_default_class,
                retry_after=shed_retry_after,
                stats=self.stats,
                tenants=self.tenant_policy,
            )
            self.count_batcher.load_hint = self.scheduler.load
        # HBM residency manager (pilosa_tpu/hbm/): extent-granular paging
        # over the shared device cache, plus the optional background
        # prefetcher fed by the scheduler's admitted-queue peek. The
        # [hbm] knobs are PROCESS-global (like PILOSA_TPU_HBM_BUDGET_MB):
        # all in-process nodes share one device and one extent store, so
        # the last-constructed server's values win — multi-node-in-one-
        # process harnesses must configure them consistently.
        from pilosa_tpu import hbm as hbmmod

        hbmmod.configure(
            extent_rows=hbm_extent_rows, pin_timeout=hbm_pin_timeout
        )
        # plane-streamed BSI aggregate slab bound (exec/bsistream.py):
        # process-global for the same reason as the [hbm] knobs — all
        # in-process nodes share one device
        from pilosa_tpu.exec import bsistream as bsistream_mod

        bsistream_mod.configure(slab_planes=bsi_slab_planes)
        # cross-fragment deferred-delta merge crossover (core/merge.py):
        # process-global for the same reason as the [hbm] knobs — all
        # in-process nodes share the one device the merge dispatches to
        from pilosa_tpu.core import merge as merge_mod

        merge_mod.configure(device_threshold=merge_device_threshold)
        # durable write path (core/wal.py): group-commit fsync cadence.
        # Process-global for the same reason — WAL files belong to the
        # process, and all in-process nodes share ONE commit loop (so
        # concurrent imports coalesce across them); the last-constructed
        # server's knob and stats sink win.
        from pilosa_tpu.core import wal as wal_mod

        wal_mod.GROUP_COMMIT.configure(sync_interval=wal_sync_interval)
        wal_mod.GROUP_COMMIT.stats = self.stats
        # versioned result cache (core/resultcache.py): process-global
        # like the [hbm] knobs (entries stay node-scoped through the
        # index/view tokens in their keys) — the last-constructed
        # server's budget wins. boot_id salts the version vectors this
        # node reports to coordinators: a restart replays versions from
        # 0, so without it a coordinator's cached entry could alias a
        # rebuilt-but-different fragment at the same version count.
        import uuid

        from pilosa_tpu.core.resultcache import RESULT_CACHE

        self.boot_id = uuid.uuid4().hex
        cache_default, cache_over = self.tenant_policy.cache_quota_map()
        RESULT_CACHE.configure(
            budget_bytes=max(0, int(cache_result_mb)) << 20,
            repair=cache_count_repair,
            tenant_default_bytes=cache_default,
            tenant_overrides=cache_over,
        )
        # per-index HBM residency quotas (process-global like the [hbm]
        # knobs — one shared device cache): eviction pressure lands on
        # over-quota owners before the global LRU pass
        from pilosa_tpu.core.devcache import DEVICE_CACHE

        hbm_default, hbm_over = self.tenant_policy.hbm_quota_map()
        DEVICE_CACHE.configure_quotas(
            default_bytes=hbm_default, overrides=hbm_over
        )
        # cache coherence plane (pilosa_tpu/coherence/): push invalidation
        # + version leases + query subscriptions. Per-NODE manager (like
        # the tracer): in-process harness nodes each publish their own
        # views and hold their own mirrors. None = both planes disabled —
        # the hub's empty-registry fast path keeps mutation cost at zero.
        self.coherence = None
        self.coherence_tick_interval = 0.0
        if coherence_lease_duration > 0 or coherence_max_subscriptions > 0:
            from pilosa_tpu.coherence.manager import CoherenceManager

            self.coherence = CoherenceManager(
                node_id=node_id,
                boot_id=self.boot_id,
                holder=self.holder,
                client=self.client,
                logger=self.logger,
                lease_duration=coherence_lease_duration,
                publish_batch_ms=coherence_publish_batch_ms,
                max_subscriptions=coherence_max_subscriptions,
                sub_poll_interval=coherence_sub_poll_interval,
            )
            self.coherence_tick_interval = max(
                0.005, float(coherence_publish_batch_ms) / 1000.0
            )
        # the executor consults the mirror plane before paying remote
        # version RPCs (exec/distributed.py _leased_remote_versions)
        self.executor.coherence = self.coherence
        self._coherence_thread = None
        self.prefetcher = None
        if hbm_prefetch_depth > 0 and self.scheduler is not None:
            self.prefetcher = hbmmod.Prefetcher(
                depth=hbm_prefetch_depth, logger=self.logger
            ).start()
            self.scheduler.prefetcher = self.prefetcher
        # bulk-import replica fan-out (server/api.py): shard batches ship
        # to their owner nodes on this bounded pool concurrently instead
        # of one serial HTTP round-trip per shard. Threads spawn lazily,
        # so an idle pool costs nothing.
        self.import_concurrency = max(1, int(import_concurrency))
        self.max_writes_per_request = max(0, int(max_writes_per_request))
        self._import_pool = None
        self._import_pool_mu = TrackedLock("node.import_pool_mu")
        # separate SMALL pool for the routing step (argsort/split): the
        # import pool's workers can all be parked in replica-ship retry
        # cycles when a peer is flapping, and grouping queued behind
        # them would stall healthy LOCAL ingest behind a sick replica
        self._route_pool = None
        # streaming-resize plane: source-side write captures (keyed by
        # (job, index, field, view, shard), leased) and the destination-
        # side per-job transfer ledger used for crash resume and abort
        # cleanup — see "streaming resize" section below
        if resize_resume_policy not in ("resume", "abort"):
            raise ValueError(
                f"resize_resume_policy must be 'resume' or 'abort', "
                f"got {resize_resume_policy!r}"
            )
        self.resize_transfer_concurrency = max(
            1, int(resize_transfer_concurrency)
        )
        self.resize_cutover_timeout = float(resize_cutover_timeout)
        self.resize_resume_policy = resize_resume_policy
        self._transfer_mu = TrackedLock("node.transfer_mu")
        self._transfer_captures: Dict[tuple, dict] = {}
        self._resize_ledger: Dict[str, dict] = {}
        # test hook: called with each resize-job phase label on the job
        # thread — the deterministic chaos matrix uses it to kill/abort
        # at exact FSM points instead of racing wall-clock sleeps
        self.resize_phase_hook = None
        self.anti_entropy_interval = anti_entropy_interval
        self.cache_flush_interval = cache_flush_interval
        self.probe_interval = probe_interval
        # True once start() restored membership from the on-disk .topology;
        # the boot layer must then NOT override membership with static
        # flags (flags seed the first multi-node boot and still heal peer
        # URIs on later boots; membership itself comes from disk)
        self.topology_restored = False
        self.long_query_time = long_query_time
        self.metric_poll_interval = metric_poll_interval
        # per-NODE tracer ring (not the process global): in-process
        # multi-node harnesses must exercise REAL cross-node propagation
        # and piggyback assembly, which a shared ring would fake. With
        # tracing disabled, root spans never sample — but an incoming
        # trace header (the sender sampled) and profile=true still record,
        # so the flight recorder works on demand even at sample-rate 0.
        self.tracer = tracing.Tracer(
            keep=trace_ring,
            sample_rate=trace_sample_rate if tracing_enabled else 0.0,
            node=node_id,
        )
        # on-demand query profiling window (GET /debug/pprof?seconds=N)
        from pilosa_tpu.server.profiling import QueryProfiler

        self.profiler = QueryProfiler()
        # cluster telemetry plane (server/telemetry.py): the always-on
        # utilization timeline sampler plus the /cluster/* federation
        # (metrics rollup, overview, health, merged timeline)
        from pilosa_tpu.server.telemetry import Telemetry

        self.telemetry_sample_interval = float(telemetry_sample_interval)
        self.telemetry = Telemetry(
            self, telemetry_sample_interval, telemetry_ring
        )
        self._telemetry_thread = None
        self._httpd = None
        self._http_thread = None
        self._ae_thread = None
        self._cache_thread = None
        self._runtime_thread = None
        self._probe_thread = None
        self._closing = threading.Event()
        self._down_ids: set = set()
        # coordinator-driven resize job (cluster.go:1447-1561 resizeJob):
        # at most one at a time; RUNNING -> DONE | ABORTED
        self.resize_job: Optional[dict] = None
        # last-synced fragment versions: AE prioritizes fragments mutated
        # since their last pass (fresh drift repairs first under load)
        self._ae_versions: Dict[tuple, int] = {}
        self._resize_mu = TrackedLock("node.resize_mu")
        # single-flight anti-entropy: the AE ticker, the operator's POST
        # /internal/sync, and a peer's debt nudge must not stack passes —
        # and single-flight breaks the A-nudges-B-nudges-A recursion
        self._sync_once = TrackedLock("node.sync_once")
        # single-flight for the nudge itself: it runs OUTSIDE _sync_once
        # (a slow primary must not stall our own next pass), so it needs
        # its own guard against mutual-debt nudge recursion
        self._nudge_once = TrackedLock("node.nudge_once")
        # serializes cluster-status emission: the probe ticker's stale
        # NORMAL must never land after a resize's RESIZING freeze
        self._status_mu = TrackedLock("node.status_mu")
        self._resize_abort = threading.Event()
        self._resize_thread: Optional[threading.Thread] = None

        # tiered storage (pilosa_tpu/tier/): per-node manager over a
        # (possibly shared) object store. The STORE may be shared across
        # nodes — snapshot bootstrap depends on it — but the manager is
        # strictly per node: in-process harness nodes share index names,
        # and a global cold set would alias them.
        self.tier = None
        self._tier_thread = None
        self.tier_demote_interval = 0.0
        store = tier_store
        if store is None and tier_store_path:
            from pilosa_tpu.tier.store import LocalDirStore

            store = LocalDirStore(tier_store_path)
        if store is not None:
            from pilosa_tpu.tier import TierManager, TierPolicy

            self.tier = TierManager(
                store,
                TierPolicy(tier_placement, tier_overrides),
                self.holder,
                demote_after=tier_demote_after,
                host_budget_bytes=tier_host_budget_bytes,
                fetch_concurrency=tier_fetch_concurrency,
                scheduler=self.scheduler,
                tracer=self.tracer,
            )
            if tier_demote_after > 0 or tier_host_budget_bytes > 0:
                # tick a few times per idle window so demotion lands
                # within ~demote-after of true idleness without a
                # dedicated knob; clamped so tests stay responsive and
                # production stays cheap
                base = tier_demote_after / 4 if tier_demote_after > 0 else 5.0
                self.tier_demote_interval = min(30.0, max(0.5, base))

        from pilosa_tpu.server.api import API

        self.api = API(self)

    # -- durable identity + membership -------------------------------------
    # Reference: holder.go:599-621 (.id) and cluster.go:1657-1692
    # (.topology): a resized cluster must reboot into its post-resize
    # membership from disk, not the stale static flags.

    def _load_or_create_id(self, node_id: str) -> str:
        if not self.data_dir:
            return node_id
        path = os.path.join(os.path.expanduser(self.data_dir), ".id")
        try:
            with open(path) as f:
                disk_id = f.read().strip()
        except FileNotFoundError:
            pass
        except OSError as e:
            # an existing-but-unreadable .id must never be clobbered with a
            # fresh identity: that would orphan every fragment placement
            # keyed to the old id — the exact failure durable ids prevent
            raise RuntimeError(f"cannot read node id at {path}: {e}") from e
        else:
            if disk_id:
                return disk_id
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(node_id)
        os.replace(tmp, path)
        return node_id

    @property
    def _topology_path(self) -> Optional[str]:
        if not self.data_dir:
            return None
        return os.path.join(os.path.expanduser(self.data_dir), ".topology")

    def _save_topology(self) -> None:
        """Persist multi-node membership; a reset to a standalone cluster
        removes the file so static flags seed the next boot again."""
        path = self._topology_path
        if path is None:
            return
        import json

        try:
            in_cluster = any(n.id == self.node.id for n in self.cluster.nodes)
            if len(self.cluster.nodes) <= 1 or not in_cluster:
                # standalone again, or removed from membership: forget the
                # old cluster so flags seed the next boot
                if os.path.exists(path):
                    os.remove(path)
                return
            doc = {
                "clusterName": self.cluster_name,
                "replicaN": self.cluster.replica_n,
                "partitionN": self.cluster.partition_n,
                "nodes": [
                    {
                        "id": n.id,
                        "uri": n.uri,
                        "isCoordinator": n.is_coordinator,
                        "meshGroup": n.mesh_group,
                        # liveness is probed fresh each boot, never trusted
                        # from disk
                    }
                    for n in self.cluster.nodes
                ],
            }
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
        except OSError as e:
            self.logger(f"persist .topology: {e}")

    def _restore_topology(self) -> None:
        """Reinstall persisted membership on boot (called from start() once
        the node's own URI is known, so the self entry heals a changed
        bind)."""
        path = self._topology_path
        if path is None or not os.path.exists(path):
            return
        import json

        try:
            with open(path) as f:
                doc = json.load(f)
            nodes = [
                Node(
                    id=n["id"],
                    uri=n.get("uri", ""),
                    is_coordinator=n.get("isCoordinator", False),
                    mesh_group=n.get("meshGroup", ""),
                )
                for n in doc.get("nodes", [])
            ]
        except (OSError, ValueError, KeyError) as e:
            self.logger(f"restore .topology: {e} (ignored; flags will seed)")
            return
        if len(nodes) <= 1 or not any(n.id == self.node.id for n in nodes):
            return
        self.set_topology(
            nodes,
            replica_n=doc.get("replicaN"),
            partition_n=doc.get("partitionN"),
        )
        self.topology_restored = True
        self.logger(
            f"restored {len(nodes)}-node topology from disk "
            f"(replicaN={self.cluster.replica_n})"
        )

    def heal_peer_uris(self, hosts) -> List[str]:
        """Update peer addresses from (id, uri) pairs without touching the
        restored membership — the static-flag analog of the reference
        re-learning a moved node's address through gossip. Returns the ids
        whose URI changed."""
        by_id = dict(hosts)
        healed = []
        for n in self.cluster.nodes:
            if n.id == self.node.id:
                continue
            new_uri = by_id.get(n.id)
            if new_uri and new_uri != n.uri:
                n.uri = new_uri
                healed.append(n.id)
        if healed:
            self.wire_translation()
            self._save_topology()
        return healed

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "NodeServer":
        # Warm the native codec off the request path: the first call may
        # compile the C++ extension (seconds), which must not land on an
        # import-roaring request.
        from pilosa_tpu import native

        native.available()
        # Multi-device hosts serve the compiled query path over a device
        # mesh: stacked plan operands get NamedSharding placement and XLA
        # inserts the ICI collectives (parallel/mesh.py). Single-device
        # hosts (and the CPU test harness before force_cpu(n>1)) no-op.
        from pilosa_tpu.parallel.mesh import (
            activate_default_mesh,
            register_group_member,
        )

        activate_default_mesh()
        # mesh-group membership ([mesh] group knob): announce this node's
        # shards as in-process-reachable for mesh-local sharded execution
        # (exec/meshgroup.py) — peers in the same ICI domain fold our
        # shards into their compiled dispatch instead of sending a leg
        if self.mesh_group_name:
            register_group_member(
                self.mesh_group_name, self.node.id, self.holder
            )
        self.holder.open()
        if self.tier is not None:
            # rebuild the cold set from the store (self-describing: a
            # manifest whose fragment has no local copy is cold — covers
            # every demote/hydrate crash window) and attach the resolver
            # to the views that need it
            n_cold = self.tier.load_cold_set()
            if n_cold:
                self.logger(f"tier: {n_cold} cold fragments from store")
        from pilosa_tpu.server.handler import make_http_server

        host, port = self.bind.rsplit(":", 1)
        self._httpd = make_http_server(self, host, int(port))
        scheme = "http"
        if self.tls_cert:
            import ssl

            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(self.tls_cert, self.tls_key)
            self._httpd.socket = ctx.wrap_socket(
                self._httpd.socket, server_side=True
            )
            scheme = "https"
        actual_port = self._httpd.server_address[1]
        self.node.uri = f"{scheme}://{host}:{actual_port}"
        # Restore persisted membership BEFORE serving: a request landing in
        # between would see a standalone NORMAL coordinator with wrong shard
        # placement. The socket is already bound, so early connections just
        # queue in the listen backlog until serve_forever picks them up.
        self._restore_topology()
        self._http_thread = threading.Thread(
            target=self._httpd.serve_forever, name=f"http-{self.node.id}", daemon=True
        )
        self._http_thread.start()
        if self.probe_interval > 0:
            self._probe_thread = threading.Thread(
                target=self._probe_loop, name=f"probe-{self.node.id}", daemon=True
            )
            self._probe_thread.start()
        if self.anti_entropy_interval > 0:
            self._ae_thread = threading.Thread(
                target=self._anti_entropy_loop, daemon=True
            )
            self._ae_thread.start()
        if self.cache_flush_interval > 0 and self.data_dir is not None:
            self._cache_thread = threading.Thread(
                target=self._cache_flush_loop, daemon=True
            )
            self._cache_thread.start()
        if self.metric_poll_interval > 0:
            self._runtime_thread = threading.Thread(
                target=self._runtime_poll_loop, daemon=True
            )
            self._runtime_thread.start()
        if self.telemetry_sample_interval > 0:
            self._telemetry_thread = threading.Thread(
                target=self._telemetry_loop,
                name=f"telemetry-{self.node.id}",
                daemon=True,
            )
            self._telemetry_thread.start()
        if self.tier is not None and self.tier_demote_interval > 0:
            self._tier_thread = threading.Thread(
                target=self._tier_demote_loop,
                name=f"tier-{self.node.id}",
                daemon=True,
            )
            self._tier_thread.start()
        if self.coherence is not None:
            from pilosa_tpu.coherence import hub as coherence_hub

            self.coherence.start(
                exec_fn=self._coherence_exec,
                uri_fn=lambda: self.node.uri,
                tracer=self.tracer,
            )
            # registered AFTER start: the hub funnels mutation notes in
            # under fragment locks, and the manager must be fully wired
            # before the first note arrives
            coherence_hub.register(self.coherence)
            self._coherence_thread = threading.Thread(
                target=self._coherence_loop,
                name=f"coherence-{self.node.id}",
                daemon=True,
            )
            self._coherence_thread.start()
        return self

    def _coherence_loop(self) -> None:
        """Coherence flush ticker: batch dirty-view bumps into pushed
        publishes (one wire payload per grant per tick), expire dead
        mirrors, and wake subscription refreshes."""
        while not self._closing.wait(self.coherence_tick_interval):
            with tracing.tick("coherence"):
                try:
                    self.coherence.tick()
                except Exception as e:  # noqa: BLE001 - keep the ticker alive
                    self._ticker_error("coherence", e)

    def _coherence_exec(self, index: str, query: str):
        """Subscription (re)compute: through normal admission in the
        batch WFQ class — a standing query is background work charged to
        its tenant's buckets, never allowed to starve interactive
        traffic. Returns the PUBLIC wire encoding so pushed results are
        bit-identical to what a poller of POST /index/{i}/query sees."""
        from pilosa_tpu.sched import admission as _admission

        resp = self.api.query_response(
            index, query,
            headers={_admission.PRIORITY_HEADER: _admission.CLASS_BATCH},
        )
        from pilosa_tpu.server import wire

        return [wire.result_to_public_json(r) for r in resp.results]

    def _tier_demote_loop(self) -> None:
        """Tier demotion ticker: idle cold-placement fragments demote to
        the object store, warm fragments shed device residency, and
        budget pressure demotes LRU until local bytes fit."""
        while not self._closing.wait(self.tier_demote_interval):
            with tracing.tick("tier-demote"):
                try:
                    self.tier.demote_tick()
                except Exception as e:  # noqa: BLE001 - keep the ticker alive
                    self._ticker_error("tier-demote", e)

    def _telemetry_loop(self) -> None:
        """Always-on utilization timeline ticker: refresh residency
        gauges (statsd backends see them without an HTTP scrape) and
        append one sample to the /debug/timeline ring per interval."""
        while not self._closing.wait(self.telemetry_sample_interval):
            with tracing.tick("telemetry"):
                try:
                    self.telemetry.sampler.sample_once()
                except Exception as e:  # noqa: BLE001 - keep the ticker alive
                    self._ticker_error("telemetry", e)

    def publish_cache_gauges(self) -> None:
        """Refresh device-cache residency gauges at scrape time (the
        /metrics and /debug/vars handlers call this just before
        rendering): HBM residency is the TPU analog of the reference's
        mmap/page-cache pressure, so operators need it on dashboards."""
        from pilosa_tpu.core.devcache import DEVICE_CACHE

        snap = DEVICE_CACHE.stats_snapshot()
        self.stats.gauge("devcache.resident_bytes", snap["resident_bytes"])
        self.stats.gauge("devcache.entries", snap["entries"])
        self.stats.gauge("devcache.evictions", snap["evictions"])
        self.stats.gauge("devcache.hits", snap["hits"])
        self.stats.gauge("devcache.misses", snap["misses"])
        # HBM residency manager gauges (pilosa_tpu/hbm/): extent paging,
        # pin pressure and prefetch effectiveness
        from pilosa_tpu import hbm as hbmmod

        hsnap = hbmmod.stats_snapshot()
        self.stats.gauge("hbm.resident_extents", hsnap["resident_extents"])
        self.stats.gauge("hbm.pinned_bytes", hsnap["pinned_bytes"])
        self.stats.gauge("hbm.prefetch_hits", hsnap["prefetch_hits"])
        self.stats.gauge("hbm.extent_patches", hsnap["extent_patches"])
        self.stats.gauge(
            "hbm.extent_patch_batches", hsnap["extent_patch_batches"]
        )
        # plane-streamed BSI aggregates (exec/bsistream.py): slabs
        # staged, cumulative slab operand bytes, compiled dispatches —
        # the one-dispatch-per-slab contract made observable
        from pilosa_tpu.exec import bsistream as bsistream_mod

        bsnap = bsistream_mod.stats_snapshot()
        self.stats.gauge("bsi.slabs", bsnap["slabs"])
        self.stats.gauge("bsi.slab_bytes", bsnap["slab_bytes"])
        self.stats.gauge("bsi.plane_dispatches", bsnap["plane_dispatches"])
        # cross-fragment deferred-delta merge barrier (core/merge.py):
        # cumulative barrier wall ms, staged buffers merged through any
        # path, and barriers that dispatched the device program
        from pilosa_tpu.core import merge as merge_mod

        msnap = merge_mod.stats_snapshot()
        self.stats.gauge("ingest.merge_ms", msnap["barrier_ms"])
        self.stats.gauge("ingest.merge_batches", msnap["batches"])
        self.stats.gauge("ingest.merge_device", msnap["device"])
        # durable write path (core/wal.py group commit): cumulative
        # commit rounds and file fsyncs — the coalescing ratio operators
        # watch is fsyncs vs import calls (wal.group_size holds the
        # per-round histogram, emitted by the commit loop itself)
        from pilosa_tpu.core import wal as wal_mod

        wsnap = wal_mod.stats_snapshot()
        self.stats.gauge("wal.commit_groups", wsnap["commit_groups"])
        self.stats.gauge("wal.fsyncs", wsnap["fsyncs"])
        self.stats.gauge("wal.sync_failures", wsnap["sync_failures"])
        # mesh-group execution (exec/meshgroup.py): live registered group
        # size plus cumulative shards served mesh-locally and bytes moved
        # by in-program collectives (the observability contract of the
        # mesh dispatch — docs/observability.md)
        from pilosa_tpu.exec import meshgroup
        from pilosa_tpu.parallel import mesh as pmesh_mod

        gsnap = meshgroup.stats_snapshot()
        group_size = (
            len(pmesh_mod.group_members(self.mesh_group_name))
            if self.mesh_group_name
            else 0
        )
        self.stats.gauge("mesh.group_size", group_size)
        self.stats.gauge("mesh.local_shards", gsnap["local_shards"])
        self.stats.gauge("mesh.collective_bytes", gsnap["collective_bytes"])
        # the placement itself: devices of the active mesh every operand
        # stack is sharded over (0: none, one device holds everything)
        active = pmesh_mod.active_mesh()
        self.stats.gauge(
            "mesh.devices", 0 if active is None else active.devices.size
        )
        # compiled dispatches (exec/plan.py) and the batcher's rounds
        # (exec/batcher.py): the counts those modules keep, and the
        # compile counts jax.monitoring feeds the process registry —
        # exec.compiles that moves in a steady state is a program
        # compiled per request shape
        from pilosa_tpu.exec import batcher as batcher_mod
        from pilosa_tpu.exec import groupby as groupby_mod
        from pilosa_tpu.exec import plan as plan_mod
        from pilosa_tpu.utils.stats import PROCESS

        self.stats.gauge("exec.dispatches", plan_mod.STATS["evals"])
        self.stats.gauge("exec.host_reads", plan_mod.STATS["host_reads"])
        self.stats.gauge("exec.compiles", PROCESS.total_counter("exec.compiles"))
        self.stats.gauge(
            "exec.compile_ms", PROCESS.total_counter("exec.compile_ms")
        )
        self.stats.gauge(
            "exec.compile_cache_hits",
            PROCESS.total_counter("exec.compile_cache_hits"),
        )
        # process-wide stops (utils/tracing.py): collector passes and
        # their pauses, all generations and full (generation 2) alone,
        # and each background loop's passes and wall time, every ticker
        # listed from process start
        gct = tracing.gc_totals()
        self.stats.gauge("runtime.gc_passes", gct["passes"])
        self.stats.gauge("runtime.gc_pause_ms", gct["pause_ms"])
        self.stats.gauge("runtime.gc_full_passes", gct["full_passes"])
        self.stats.gauge("runtime.gc_full_pause_ms", gct["full_pause_ms"])
        for ticker, (n, ms) in tracing.tick_totals().items():
            self.stats.with_tags(f"ticker:{ticker}").gauge("runtime.ticks", n)
            self.stats.with_tags(f"ticker:{ticker}").gauge(
                "runtime.tick_ms", ms
            )
        # which program tallied the GroupBy / filtered-TopN crosses
        # (exec/groupby.py cross_tally): the VMEM kernel or the XLA loop;
        # and whether a view of several extents was read where it lies
        # (inplace_tallies) or written again as one stack first
        # (assembled_stacks, one per operand)
        self.stats.gauge(
            "groupby.kernel_tallies", groupby_mod.STATS["kernel_tallies"]
        )
        self.stats.gauge(
            "groupby.xla_tallies", groupby_mod.STATS["xla_tallies"]
        )
        self.stats.gauge(
            "groupby.inplace_tallies", groupby_mod.STATS["inplace_tallies"]
        )
        self.stats.gauge(
            "groupby.assembled_stacks", groupby_mod.STATS["assembled_stacks"]
        )
        # what those concatenations wrote, and the aggregate GroupBy's
        # work: queries, and group x plane pairs counted
        self.stats.gauge(
            "groupby.assembled_bytes", groupby_mod.STATS["assembled_bytes"]
        )
        self.stats.gauge(
            "groupby.aggregate_queries",
            groupby_mod.STATS["aggregate_queries"],
        )
        self.stats.gauge(
            "groupby.plane_tallies", groupby_mod.STATS["plane_tallies"]
        )
        # of the kernel's tallies, those run under shard_map on the mesh
        self.stats.gauge(
            "groupby.mesh_tallies", groupby_mod.STATS["mesh_tallies"]
        )
        # of the kernel's group tallies, those run on the rows they name
        self.stats.gauge(
            "groupby.compacted_tallies",
            groupby_mod.STATS["compacted_tallies"],
        )
        # the views' row summaries (core/view.py row_summary): hits over
        # hits + bypassed is the share of Rows / GroupBy-prefetch /
        # unfiltered-TopN reads the table served
        self.stats.gauge(
            "rowsummary.hits", PROCESS.total_counter("rowsummary.hits")
        )
        self.stats.gauge(
            "rowsummary.rebuilds", PROCESS.total_counter("rowsummary.rebuilds")
        )
        self.stats.gauge(
            "rowsummary.refreshed_shards",
            PROCESS.total_counter("rowsummary.refreshed_shards"),
        )
        self.stats.gauge(
            "rowsummary.bypassed", PROCESS.total_counter("rowsummary.bypassed")
        )
        self.stats.gauge("batcher.leader", batcher_mod.STATS["leader"])
        self.stats.gauge("batcher.batched", batcher_mod.STATS["batched"])
        self.stats.gauge(
            "batcher.merged_execs", batcher_mod.STATS["merged_execs"]
        )
        self.stats.gauge(
            "batcher.fallback_splits", batcher_mod.STATS["fallback_splits"]
        )
        # per-index attribution (the telemetry-plane families): who owns
        # the resident bytes, and who has been paying the restage bill.
        # hbm.resident_bytes sums over labels to the global devcache
        # ledger byte-for-byte ("-" = entries owned by no index);
        # hbm.restage_bytes likewise splits the cumulative upload bytes.
        by_index = DEVICE_CACHE.index_resident_bytes()
        # an index whose residency drained to zero must PUBLISH the zero
        # (a gauge frozen at its last nonzero value would break the
        # per-index == global-ledger reconciliation); once zeroed the
        # label leaves the working set (index deletion GCs the series)
        stale = getattr(self, "_hbm_idx_published", set()) - set(by_index)
        self._hbm_idx_published = set(by_index)
        for idx, nb in by_index.items():
            self.stats.with_tags(f"index:{idx}").gauge(
                "hbm.resident_bytes", nb
            )
        for idx in stale:
            self.stats.with_tags(f"index:{idx}").gauge(
                "hbm.resident_bytes", 0
            )
        for idx, nb in hsnap["restage_by_index"].items():
            self.stats.with_tags(f"index:{idx}").gauge(
                "hbm.restage_bytes", nb
            )
        if self.scheduler is not None:
            for idx, nb in self.scheduler.inflight_bytes_by_index().items():
                self.stats.with_tags(f"index:{idx}").gauge(
                    "sched.index_inflight_bytes", nb
                )
        # versioned result cache (core/resultcache.py): hit/miss/repair
        # counters plus per-index resident bytes (the sum over labels is
        # the cache's whole footprint; an index that drained publishes a
        # final 0 then leaves the working set, like hbm.resident_bytes)
        from pilosa_tpu.core.resultcache import RESULT_CACHE

        csnap = RESULT_CACHE.stats_snapshot()
        self.stats.gauge("cache.hits", csnap["hits"])
        self.stats.gauge("cache.misses", csnap["misses"])
        self.stats.gauge("cache.revalidations", csnap["revalidations"])
        self.stats.gauge("cache.repairs", csnap["repairs"])
        self.stats.gauge("cache.evictions", csnap["evictions"])
        self.stats.gauge("cache.entries", csnap["entries"])
        cache_by_index = csnap["by_index"]
        cstale = getattr(self, "_cache_idx_published", set()) - set(
            cache_by_index
        )
        self._cache_idx_published = set(cache_by_index)
        for idx, nb in cache_by_index.items():
            self.stats.with_tags(f"index:{idx}").gauge(
                "cache.resident_bytes", nb
            )
        for idx in cstale:
            self.stats.with_tags(f"index:{idx}").gauge(
                "cache.resident_bytes", 0
            )
        # multi-tenant quota plane (sched/tenants.py): effective per-index
        # quota values (defaults merged with overrides) plus cumulative
        # quota-first eviction counts from both caches. Published only
        # when SOME [tenants] limit is configured — a quota-free node
        # keeps its metrics surface unchanged.
        pol = getattr(self, "tenant_policy", None)
        if pol is not None and pol.any_limits():
            live = sorted(
                {i.name for i in self.holder.indexes()}
                | set(by_index)
                | set(cache_by_index)
            )
            for idx in live:
                if idx == "-":
                    continue
                lim = pol.limits(idx)
                self.stats.with_tags(f"index:{idx}").gauge(
                    "tenant.hbm_quota_bytes", lim.hbm_bytes
                )
                self.stats.with_tags(f"index:{idx}").gauge(
                    "tenant.cache_quota_bytes", lim.cache_bytes
                )
                self.stats.with_tags(f"index:{idx}").gauge(
                    "tenant.inflight_quota_bytes", lim.inflight_bytes
                )
            for idx, n in DEVICE_CACHE.quota_evictions_by_index().items():
                self.stats.with_tags("cache:hbm", f"index:{idx}").gauge(
                    "tenant.quota_evictions", n
                )
            for idx, n in csnap["quota_evictions_by_index"].items():
                self.stats.with_tags("cache:result", f"index:{idx}").gauge(
                    "tenant.quota_evictions", n
                )
        # tiered storage (pilosa_tpu/tier/): cumulative demote/hydrate/
        # bootstrap/sync counters plus per-index cold-set gauges. An
        # index whose cold set drained publishes a final zero then
        # leaves the working set, like hbm.resident_bytes above.
        if self.tier is not None:
            tc = self.tier.counters()
            self.stats.gauge("tier.demotions", tc["demotions"])
            self.stats.gauge("tier.demote_bytes", tc["demote_bytes"])
            self.stats.gauge("tier.demote_aborts", tc["demote_aborts"])
            self.stats.gauge("tier.hydrations", tc["hydrations"])
            self.stats.gauge("tier.fetches", tc["fetches"])
            self.stats.gauge("tier.fetch_bytes", tc["fetch_bytes"])
            self.stats.gauge("tier.bootstrap_objects",
                             tc["bootstrap_objects"])
            self.stats.gauge("tier.bootstrap_bytes", tc["bootstrap_bytes"])
            self.stats.gauge("tier.ae_repairs", tc["ae_repairs"])
            self.stats.gauge("tier.sync_uploads", tc["sync_uploads"])
            tsum = self.tier.index_summary()
            tstale = getattr(self, "_tier_idx_published", set()) - set(tsum)
            self._tier_idx_published = set(tsum)
            for idx, row in tsum.items():
                self.stats.with_tags(f"index:{idx}").gauge(
                    "tier.cold_fragments", row["cold_fragments"]
                )
                self.stats.with_tags(f"index:{idx}").gauge(
                    "tier.local_bytes", row["local_bytes"]
                )
            for idx in tstale:
                self.stats.with_tags(f"index:{idx}").gauge(
                    "tier.cold_fragments", 0
                )
                self.stats.with_tags(f"index:{idx}").gauge(
                    "tier.local_bytes", 0
                )
        # monotone-tree repair / structural re-key counters ride the
        # cache.* family (they are result-cache behavior and exist with
        # coherence disabled — PR 13's repair generalized)
        self.stats.gauge("cache.tree_repairs", csnap["tree_repairs"])
        self.stats.gauge("cache.rekeys", csnap["rekeys"])
        # cache coherence plane (pilosa_tpu/coherence/): lease/publish/
        # subscription counters and gauges, plus the per-index
        # subscription gauge with the same stale-zero pattern as
        # hbm.resident_bytes. Gated on active(): a node that never
        # leased, granted, or subscribed renders NO coherence.* series
        # (the unleased-harness contract in tools/metrics_smoke.py).
        mgr = self.coherence
        if mgr is not None and mgr.active():
            ccnt = mgr.counters_snapshot()
            self.stats.gauge("coherence.version_rtts", ccnt["version_rtts"])
            self.stats.gauge("coherence.lease_hits", ccnt["lease_hits"])
            self.stats.gauge("coherence.grants_issued", ccnt["grants_issued"])
            self.stats.gauge("coherence.publishes", ccnt["publishes"])
            self.stats.gauge("coherence.publish_errors",
                             ccnt["publish_errors"])
            self.stats.gauge("coherence.invalidations", ccnt["invalidations"])
            self.stats.gauge("coherence.sub_pushes", ccnt["sub_pushes"])
            cg = mgr.gauges()
            self.stats.gauge("coherence.leases", cg["leases"])
            self.stats.gauge("coherence.grants", cg["grants"])
            subs = mgr.subscriptions_by_index()
            sstale = getattr(self, "_coh_idx_published", set()) - set(subs)
            self._coh_idx_published = set(subs)
            for idx, n in subs.items():
                self.stats.with_tags(f"index:{idx}").gauge(
                    "coherence.subscriptions", n
                )
            for idx in sstale:
                self.stats.with_tags(f"index:{idx}").gauge(
                    "coherence.subscriptions", 0
                )

    def drop_index_telemetry(self, index: str) -> None:
        """Label GC for a deleted index: remove every per-index metric
        series and attribution entry so a churning tenant set cannot
        leak gauge families (regression-tested: create/delete 100
        indexes returns the registry's family count to baseline)."""
        reg = getattr(self.stats, "registry", None)
        if reg is not None:
            reg.drop_label("index", index)
        from pilosa_tpu import hbm as hbmmod

        hbmmod.drop_index(index)
        # mesh-group adapters hold device-cache owner tokens per index;
        # a deleted index's group stacks must leave the ledger with it
        from pilosa_tpu.exec import meshgroup

        meshgroup.drop_index(index)
        # coherence GC: the index's subscriptions close (unpinning their
        # cache entries and releasing blocked long-polls), its grants
        # and lease mirrors drop, and the coherence.subscriptions series
        # must not be resurrected by a stale-zero publish
        if self.coherence is not None:
            self.coherence.drop_index(index)
        coh_published = getattr(self, "_coh_idx_published", None)
        if coh_published is not None:
            coh_published.discard(index)
        # result-cache entries and their per-index byte attribution must
        # not outlive the index (cache.resident_bytes{index} label GC)
        from pilosa_tpu.core.resultcache import RESULT_CACHE

        RESULT_CACHE.drop_index(index)
        if self.scheduler is not None:
            # the scheduler GCs its queues AND the shared tenant policy's
            # runtime ledgers (token buckets) for the index
            self.scheduler.drop_index(index)
        elif getattr(self, "tenant_policy", None) is not None:
            self.tenant_policy.drop_index(index)
        published = getattr(self, "_hbm_idx_published", None)
        if published is not None:
            published.discard(index)
        cache_published = getattr(self, "_cache_idx_published", None)
        if cache_published is not None:
            cache_published.discard(index)
        # tier GC: cold-set entries, the placement override, AND the
        # stored snapshot objects (snap/<index>/...) all die with the
        # index — a deleted tenant's data must not linger in the store
        if self.tier is not None:
            removed = self.tier.drop_index(index)
            if removed:
                self.logger(
                    f"tier: removed {removed} stored objects for {index!r}"
                )
            published = getattr(self, "_tier_idx_published", None)
            if published is not None:
                published.discard(index)

    def _ticker_error(self, ticker: str, exc: BaseException) -> None:
        """Background tickers must survive any failure, but never silently:
        the full traceback goes to the log and `ticker.error` counts it so
        a quietly-failing loop shows up on dashboards instead of being
        discovered as stale caches / undetected dead peers much later."""
        self.stats.count("ticker.error")
        self.logger(
            f"{ticker} ticker error: {exc!r}\n{traceback.format_exc()}"
        )

    def _runtime_poll_loop(self) -> None:
        """Sample process runtime gauges (reference: server.go:813
        monitorRuntime — goroutines/heap/open-files). No count of live
        objects: gc.get_objects() walks the whole heap under the GIL (a
        process-wide stop that grows with the index) and its list holds a
        reference to every tracked object, half-built tuples included —
        a request thread preempted inside tuple(<generator>) then fails
        with SystemError (tupleobject.c: refcount != 1 at the resize)."""
        import resource

        while not self._closing.wait(self.metric_poll_interval):
            with tracing.tick("runtime-poll"):
                try:
                    usage = resource.getrusage(resource.RUSAGE_SELF)
                    self.stats.gauge("runtime.max_rss_kb", usage.ru_maxrss)
                    self.stats.gauge("runtime.threads", threading.active_count())
                    try:
                        self.stats.gauge("runtime.open_files", len(os.listdir("/proc/self/fd")))
                    except OSError:
                        pass
                except Exception as e:  # noqa: BLE001 - keep the ticker alive
                    self._ticker_error("runtime-poll", e)

    def _cache_flush_loop(self) -> None:
        """Persist rank caches periodically (reference: holder.go:506
        monitorCacheFlush, 1-minute ticker)."""
        while not self._closing.wait(self.cache_flush_interval):
            with tracing.tick("cache-flush"):
                try:
                    self.holder.flush_caches()
                except Exception as e:  # noqa: BLE001 - keep the ticker alive
                    self._ticker_error("cache-flush", e)

    @property
    def import_pool(self):
        """Lazily created bounded thread pool for replica import fan-out
        (created under a lock on the first multi-node import — two
        concurrent first imports must not each build a pool and leak one;
        single-node imports never touch it)."""
        with self._import_pool_mu:
            if self._import_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                # owns: stop() swaps the pool out and shuts it down
                self._import_pool = ThreadPoolExecutor(
                    max_workers=self.import_concurrency,
                    thread_name_prefix="pilosa-tpu-import",
                )
            return self._import_pool

    @property
    def route_pool(self):
        """Lazily created pool for the import ROUTING step (the argsort/
        split that moved off the serving thread, ISSUE 12). Deliberately
        separate from import_pool: routing must never queue behind
        replica-ship frames stuck in a sick peer's retry cycle."""
        with self._import_pool_mu:
            if self._route_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                # owns: stop() swaps the pool out and shuts it down
                self._route_pool = ThreadPoolExecutor(
                    max_workers=min(4, self.import_concurrency),
                    thread_name_prefix="pilosa-tpu-route",
                )
            return self._route_pool

    def stop(self) -> None:
        self._closing.set()
        # sync any buffered WAL tail (bounded-loss mode) before teardown:
        # a clean stop must not leave the loss window open
        try:
            from pilosa_tpu.core import wal as wal_mod

            wal_mod.GROUP_COMMIT.flush()
        except OSError as e:
            self.logger(f"wal flush on stop failed: {e}")
        if self.mesh_group_name:
            from pilosa_tpu.parallel.mesh import unregister_group_member

            unregister_group_member(self.mesh_group_name, self.node.id)
        self.profiler.close()  # unblock any open /debug/pprof window
        if self.coherence is not None:
            from pilosa_tpu.coherence import hub as coherence_hub

            # unregister BEFORE stop: notes must not land on a manager
            # that is tearing down; stop() then closes every
            # subscription (releasing blocked long-polls) and joins the
            # push worker
            coherence_hub.unregister(self.coherence)
            self.coherence.stop()
        if self._coherence_thread is not None:
            self._coherence_thread.join(timeout=5.0)
            self._coherence_thread = None
        with self._import_pool_mu:
            pool, self._import_pool = self._import_pool, None
            rpool, self._route_pool = self._route_pool, None
        if pool is not None:
            pool.shutdown(wait=False)
        if rpool is not None:
            rpool.shutdown(wait=False)
        self.executor.close()  # lazy fan-out pool (see DistributedExecutor)
        if self.prefetcher is not None:
            self.prefetcher.stop()  # joins the warm worker before teardown
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        if self._tier_thread is not None:
            self._tier_thread.join(timeout=5.0)
            self._tier_thread = None
        self.holder.close()
        self.stats.close()  # statsd clients own a UDP socket

    # -- topology ----------------------------------------------------------

    def set_topology(
        self,
        nodes: List[Node],
        replica_n: Optional[int] = None,
        partition_n: Optional[int] = None,
    ) -> None:
        """Install the static cluster membership (all nodes must agree; the
        test/bootstrap harness calls this after every node has bound)."""
        self.cluster = Cluster(
            nodes=[
                # preserve liveness marks: a node the sender saw DOWN must
                # stay DOWN here too (placement skips DOWN nodes) until a
                # probe says otherwise
                Node(
                    id=n.id, uri=n.uri,
                    is_coordinator=n.is_coordinator, state=n.state,
                    mesh_group=n.mesh_group,
                )
                for n in nodes
            ],
            replica_n=replica_n if replica_n is not None else self.cluster.replica_n,
            partition_n=partition_n if partition_n is not None else self.cluster.partition_n,
            hasher=self.cluster.hasher,
            state=STATE_NORMAL,
        )
        # keep self.node identity in sync with the membership entry; we are
        # definitionally alive, whatever a peer's stale view says — and OUR
        # mesh group comes from OUR config, not a peer's (possibly stale or
        # group-unaware) membership broadcast
        mine = self.cluster.node_by_id(self.node.id)
        if mine is not None:
            mine.uri = self.node.uri
            mine.state = "READY"
            mine.mesh_group = self.mesh_group_name
            self.node = mine
        # in-process peers that registered a mesh group but were seeded
        # into this topology without one (e.g. a static-flag or harness
        # install that predates their group config) are enriched from the
        # process-local registry — topology stays the source of truth for
        # cross-process deployments (join payloads and .topology carry it)
        from pilosa_tpu.parallel import mesh as pmesh

        for n in self.cluster.nodes:
            if not n.mesh_group and n.id != self.node.id:
                n.mesh_group = pmesh.registered_group_of(n.id)
        self.wire_translation()
        self._save_topology()
        # a departed node's drift debt is moot (it owns nothing anymore);
        # without this prune its ledger entries could never resolve —
        # `reached` sets are built from CURRENT owners — and would pin
        # /status pendingRepairs nonzero forever
        member_ids = {n.id for n in self.cluster.nodes}
        for iname, shard, debtor in self.holder.pending_repairs():
            if debtor not in member_ids:
                self.holder.discard_pending_repair(iname, shard, debtor)

    def wire_translation(self) -> None:
        """Install single-writer key translation: the coordinator's stores
        stay writable; every other node's stores forward allocations to the
        coordinator and catch up from its append log (reference:
        boltdb/translate.go single-writer + holder.go:785-880 follower)."""
        coord = self.cluster.coordinator() or (
            self.cluster.nodes[0] if self.cluster.nodes else None
        )
        if coord is None:
            return
        is_primary = coord.id == self.node.id
        for idx in self.holder.indexes():
            if idx.keys:
                self._wire_store(idx.translate_store, coord, is_primary, idx.name, None)
            for f in idx.fields(include_hidden=True):
                if f.options.keys:
                    self._wire_store(
                        f.translate_store, coord, is_primary, idx.name, f.name
                    )

    def _wire_store(self, store, coord, is_primary: bool, index: str, field) -> None:
        if is_primary:
            store.read_only = False
            store.forward_fn = None
            store.catchup_fn = None
            return
        if not hasattr(store, "_repl_offset"):
            store._repl_offset = 0
        store.read_only = True
        store.forward_fn = lambda keys: self.client.translate_keys_remote(
            coord.uri, index, field, keys
        )

        def catchup():
            entries, off = self.client.translate_entries(
                coord.uri, index, field, store._repl_offset
            )
            store.apply_entries(entries)
            store._repl_offset = off

        store.catchup_fn = catchup

    def apply_cluster_status(self, msg: dict) -> None:
        self.set_topology(
            [Node.from_json(n) for n in msg["nodes"]],
            replica_n=msg.get("replicaN"),
        )
        self.state = msg.get("state", self.state)

    def set_node_state(self, node_id: str, state: str) -> None:
        # _status_mu makes the RESIZING check-then-set atomic against a
        # concurrent freeze broadcast (_send_status holds the same lock
        # while applying it locally): without it a probe tick could
        # evaluate the check pre-freeze and write NORMAL post-freeze,
        # unfreezing the coordinator while fragments move
        with self._status_mu:
            n = self.cluster.node_by_id(node_id)
            if n is not None:
                n.state = state
            if state == "DOWN":
                self._down_ids.add(node_id)
            else:
                self._down_ids.discard(node_id)
            # RESIZING is owned by the resize job's status flow: a liveness
            # probe that resolves mid-freeze must not clobber it back to
            # NORMAL (the job's final/rollback broadcast restores the state)
            if self.state != STATE_RESIZING:
                self.state = self.cluster.determine_state(self._down_ids)

    def probe_peers(self, timeout: float = 2.0) -> Dict[str, bool]:
        """One failure-detection pass: /status every peer CONCURRENTLY, so
        a resize (or liveness tick) over a cluster with several dead nodes
        pays one probe timeout, not one per corpse (reference:
        confirmNodeDown, cluster.go:1724)."""
        from concurrent.futures import ThreadPoolExecutor

        peers = list(self.cluster.nodes)

        def probe(n: Node) -> bool:
            if n.id == self.node.id:
                return True
            try:
                # probe=True bypasses the breaker: probes are how an open
                # breaker learns a peer recovered (success closes it)
                self.client.status(n.uri, timeout=timeout, probe=True)
                return True
            except ClientError:
                return False

        if len(peers) > 1:
            with ThreadPoolExecutor(max_workers=min(16, len(peers))) as pool:
                results = list(pool.map(probe, peers))
        else:
            results = [probe(n) for n in peers]
        alive = {}
        for n, ok in zip(peers, results):
            alive[n.id] = ok
            if n.id != self.node.id:
                self.set_node_state(n.id, "READY" if ok else "DOWN")
        return alive

    # -- background liveness (the gossip/SWIM role) ------------------------

    def _probe_loop(self) -> None:
        """Continuous failure detection: the coordinator probes every member
        on a ticker and broadcasts membership/state changes, so a node that
        dies while the cluster idles flips the cluster NORMAL⇄DEGRADED
        without waiting for a query to fail over (the reference gets this
        from memberlist's SWIM loop, gossip/gossip.go:364-443; here it is
        an explicit probe ticker on the coordinator)."""
        while not self._closing.wait(self.probe_interval):
            with tracing.tick("liveness-probe"):
                try:
                    self.run_probe_pass()
                except Exception as e:  # noqa: BLE001 - keep the ticker alive
                    self._ticker_error("liveness-probe", e)

    def run_probe_pass(self, timeout: float = 2.0) -> bool:
        """One coordinator liveness tick. Returns True when a state change
        was detected and broadcast. Non-coordinators learn liveness from the
        resulting cluster-status broadcast, not by probing themselves."""
        if not self.node.is_coordinator or len(self.cluster.nodes) <= 1:
            return False
        if self.state == STATE_RESIZING:
            return False  # the resize job owns the status flow
        before = {n.id: n.state for n in self.cluster.nodes}
        before_state = self.state
        self.probe_peers(timeout=timeout)
        with self._status_mu:
            # a resize may have started while we were probing (probe_peers
            # can block up to `timeout` on a dead peer): its freeze
            # broadcast must not be followed by our now-stale status. The
            # re-check holds _status_mu — the same lock _send_status takes —
            # so the freeze cannot interleave between this check and the
            # broadcast below.
            if self.state == STATE_RESIZING or (
                self.resize_job is not None
                and self.resize_job.get("state") == "RUNNING"
            ):
                return False
            after = {n.id: n.state for n in self.cluster.nodes}
            if before == after and before_state == self.state:
                return False
            changed = sorted(k for k in after if after[k] != before.get(k))
            self.logger(
                f"liveness: node state changes {changed}, cluster {self.state}"
            )
            msg = {
                "type": "cluster-status",
                "nodes": [m.to_json() for m in self.cluster.nodes],
                "replicaN": self.cluster.replica_n,
                "state": self.state,
            }
            for n in self.cluster.nodes:
                if n.id == self.node.id or n.state == "DOWN":
                    continue
                try:
                    # bounded: one hung (but probe-alive) peer must not pin
                    # _status_mu for the client's 30s default and stall a
                    # pending resize freeze behind it
                    self.client.send_message(n.uri, msg, timeout=5.0)
                except ClientError as e:
                    self.logger(f"liveness broadcast to {n.id}: {e}")
        # a node that recovered missed every DDL broadcast while it was
        # DOWN; push the full schema so its holder catches up (the
        # reference replays schema through gossip NodeStatus on rejoin,
        # gossip.go:295-362 — fragment/attr contents then converge via AE)
        recovered = [
            nid
            for nid, st in after.items()
            if st != "DOWN" and before.get(nid) == "DOWN"
        ]
        if recovered:
            schema = self.api.schema()
            for nid in recovered:
                n = self.cluster.node_by_id(nid)
                if n is None or n.id == self.node.id:
                    continue
                try:
                    self.client.post_schema(n.uri, schema)
                except ClientError as e:
                    self.logger(f"schema push to recovered {nid}: {e}")
        return True

    # -- anti-entropy (holder.go:911 SyncHolder) ---------------------------

    def _anti_entropy_loop(self) -> None:
        while not self._closing.wait(self.anti_entropy_interval):
            with tracing.tick("anti-entropy"):
                try:
                    # non-waiting variant: the tick must not stall behind
                    # remote passes triggered by the debt nudge
                    self.try_sync_holder()
                except Exception as e:  # noqa: BLE001 - keep the ticker alive
                    self._ticker_error("anti-entropy", e)
                if self.tier is not None:
                    try:
                        # anti-entropy extended to snapshot objects: the
                        # shallow pass uploads missing/stale manifests so
                        # the store keeps mirroring local state (deep
                        # verify is on demand via POST
                        # /internal/tier/sync?deep=true)
                        self.tier.sync_snapshots(deep=False)
                    except Exception as e:  # noqa: BLE001
                        self._ticker_error("tier-sync", e)

    def sync_holder(self) -> int:
        """One full anti-entropy pass: for every local fragment whose shard
        this node PRIMARY-owns, reconcile all replicas via block checksums
        + majority-vote merge (fragment.go:2861 syncFragment). Returns the
        number of fragments that needed repair. Single-flight: a pass
        requested while one runs returns 0 immediately.

        Fragment syncs run on a thread pool (one slow replica no longer
        serializes the whole walk — the reference runs one goroutine per
        mapper the same way, executor.go:2522)."""
        res = self.try_sync_holder(wait_nudge=True)
        return 0 if res is None else res[0]

    def try_sync_holder(self, wait_nudge: bool = False):
        """One pass, or None when another pass is already running —
        callers like the debt nudge must be able to tell "a pass ran"
        from "nothing happened". Returns (repaired_count, reached) where
        `reached` is the set of confirmed (index, shard, node_id)
        reconciliations — returned (not stored on the instance) so a
        concurrently starting pass cannot clobber it before the
        /internal/sync handler builds its reply. The debt nudge runs on a
        background thread: the handler must reply as soon as the LOCAL
        pass is done, or mutual-debt clusters would chain blocking passes
        (A waits on B's pass which waits on C's…) with a 300s timeout per
        hop. `wait_nudge` restores the blocking behavior for the
        operator/test-facing sync_holder()."""
        if not self._sync_once.acquire(blocking=False):
            return None
        try:
            n = self._sync_holder_pass()
        finally:
            self._sync_once.release()
        if self.holder.pending_repair_count() == 0:
            return n  # nothing to nudge; skip the thread spawn
        t = threading.Thread(
            target=self._nudge_debt_primaries,
            name=f"nudge-{self.node.id}",
            daemon=True,
        )
        t.start()
        if wait_nudge:
            t.join()
        return n

    def _sync_holder_pass(self):
        """Returns (repaired_count, confirmed_reached_triples)."""
        from concurrent.futures import ThreadPoolExecutor

        if len(self.cluster.nodes) <= 1:
            return 0, set()
        # merge peers' availability first: a node restarted after missing
        # shard announcements must re-learn which shards exist cluster-wide
        # (the reference's gossip NodeStatus state merge, gossip.go:295-362).
        # This runs even at replica_n=1 — availability is about query
        # fan-out correctness, not replica repair.
        peers = [
            n
            for n in self.cluster.nodes
            if n.id != self.node.id and n.state != "DOWN"
        ]

        def merge_avail(args) -> None:
            idx, peer = args
            try:
                for fname, shards in self.client.available_shards(
                    peer.uri, idx.name
                ).items():
                    f = idx.field(fname)
                    if f is not None:
                        f.add_remote_available(shards)
            except ClientError:
                pass

        tasks = [(idx, p) for idx in self.holder.indexes() for p in peers]
        if tasks:
            with ThreadPoolExecutor(max_workers=min(8, len(tasks))) as pool:
                list(pool.map(merge_avail, tasks))
        # attrs replicate to every node (not sharded), so their repair runs
        # even at replica_n=1 (reference: holder.go:975-1019 syncIndex)
        self._sync_attrs(peers)
        if self.cluster.replica_n <= 1:
            return 0, set()
        sync_tasks = self._ae_tasks()
        if not sync_tasks:
            return 0, set()

        def run_sync(t):
            idx, f, vname, shard, replicas = t
            attempted = [n.id for n in replicas]
            try:
                repaired, reached = self._sync_fragment(
                    idx, f, vname, shard, replicas
                )
            except Exception as e:  # noqa: BLE001 - one bad fragment must
                # not abort the rest of the pass
                self.logger(f"anti-entropy {idx.name}/{f.name}/{shard}: {e}")
                return False, (idx.name, shard, attempted, [])
            frag = f.views[vname].fragment_if_exists(shard)
            if frag is not None:
                self._ae_versions[(idx.name, f.name, vname, shard)] = frag.version
            return repaired, (idx.name, shard, attempted, reached)

        with ThreadPoolExecutor(max_workers=min(8, len(sync_tasks))) as pool:
            results = list(pool.map(run_sync, sync_tasks))
        # a (index, shard, replica) reconciliation is confirmed only when
        # EVERY fragment task of that shard (each field/view is its own
        # sync) reached the replica — one failed fragment means the
        # shard's debt is NOT repaid. Clearing on partial success would
        # recreate the silent drift the ledger exists to prevent.
        confirmed: Dict[tuple, bool] = {}
        for _, (iname, shard, attempted, reached) in results:
            for nid in attempted:
                key = (iname, shard, nid)
                confirmed[key] = confirmed.get(key, True) and nid in reached
        reached_triples = {k for k, ok in confirmed.items() if ok}
        # when EVERY fragment of a shard reached EVERY attempted replica,
        # this node's own copy merged everything live — report the shard
        # reconciled for THIS node too, so a peer whose debtor is the
        # PRIMARY (we never appear in our own replica lists) can resolve
        # its ledger entry instead of carrying it forever. (If the only
        # holder of a dropped write is DOWN, its return triggers a later
        # pass; the ledger tracks repair debt, not unreachable history.)
        shard_all_ok: Dict[tuple, bool] = {}
        for _, (iname, shard, attempted, reached) in results:
            ok = all(nid in reached for nid in attempted)
            shard_all_ok[(iname, shard)] = (
                shard_all_ok.get((iname, shard), True) and ok
            )
        for (iname, shard), ok in shard_all_ok.items():
            if ok:
                reached_triples.add((iname, shard, self.node.id))
        for iname, shard, nid in reached_triples:
            self.holder.discard_pending_repair(iname, shard, nid)
        # /internal/sync replies with this set, so a nudging peer resolves
        # exactly these confirmed repairs
        return sum(r for r, _ in results), reached_triples

    def _nudge_debt_primaries(self) -> None:
        """Pending-repair debt on shards this node does NOT own cannot be
        repaired locally (we hold no copy): ask each such shard's primary
        to run an anti-entropy pass now — the coordinator's drop ledger
        must drain even when the repair work happens elsewhere. An entry
        is resolved ONLY when the primary's reply lists that exact
        (index, shard, debtor) reconciliation in `reached`; a pass that
        ran but could not reach the debtor keeps the debt visible.
        Single-flight (and skipped while another nudge runs) so
        mutual-debt clusters cannot recurse A-nudges-B-nudges-A."""
        if not self._nudge_once.acquire(blocking=False):
            return
        try:
            foreign: Dict[str, set] = {}
            for iname, shard, debtor in self.holder.pending_repairs():
                owners = self.cluster.shard_nodes(iname, shard)
                if not owners or any(n.id == self.node.id for n in owners):
                    continue  # our own debt-driven sync task covers it
                if owners[0].state != "DOWN":
                    foreign.setdefault(owners[0].id, set()).add(
                        (iname, shard, debtor)
                    )
            for nid, entries in foreign.items():
                n = self.cluster.node_by_id(nid)
                if n is None:
                    continue
                try:
                    resp = self.client.trigger_sync(n.uri)
                except ClientError as e:
                    self.logger(f"debt sync nudge to {nid}: {e}")
                    continue
                if not resp.get("ran"):
                    continue  # the primary was mid-pass; retry next AE tick
                reached = {
                    (i, int(s), d) for i, s, d in resp.get("reached", [])
                }
                for entry in entries & reached:
                    self.holder.discard_pending_repair(*entry)
        finally:
            self._nudge_once.release()

    def _ae_tasks(self) -> list:
        """Fragment sync work list for one AE pass, locally-mutated-since-
        last-pass fragments first (the reference walks in fixed order,
        holder.go:911 — under sustained writes that starves fresh drift
        behind a long tail of clean fragments)."""
        sync_tasks = []
        for idx in self.holder.indexes():
            for f in idx.fields(include_hidden=True):
                for vname, v in list(f.views.items()):
                    # include shards known cluster-wide but absent locally:
                    # a replica may hold a fragment the primary missed (e.g.
                    # a write that partially failed) — the primary must pull
                    # it, not skip it
                    shards = set(v.fragments) | set(f.remote_available_shards)
                    for shard in sorted(shards):
                        owners = self.cluster.shard_nodes(idx.name, shard)
                        if not owners or owners[0].id != self.node.id:
                            continue  # only the primary drives the sync
                        replicas = [n for n in owners[1:] if n.state != "DOWN"]
                        if not replicas:
                            continue
                        sync_tasks.append((idx, f, vname, shard, replicas))

        # debt-driven tasks: a shard with a pending-repair entry gets
        # reconciled NOW even when this node is only a replica — the
        # primary may be the very node that missed the write, and the
        # coordinator that observed the drop is the one holding the debt
        pending: Dict[str, set] = {}
        for iname, shard, _nid in self.holder.pending_repairs():
            pending.setdefault(iname, set()).add(shard)
        seen = {
            (idx.name, f.name, vname, shard)
            for idx, f, vname, shard, _ in sync_tasks
        }
        for idx in self.holder.indexes():
            debt_shards = pending.get(idx.name)
            if not debt_shards:
                continue
            for f in idx.fields(include_hidden=True):
                for vname, v in list(f.views.items()):
                    for shard in sorted(set(v.fragments) & debt_shards):
                        if (idx.name, f.name, vname, shard) in seen:
                            continue
                        owners = self.cluster.shard_nodes(idx.name, shard)
                        if not any(n.id == self.node.id for n in owners):
                            continue  # not our copy; the primary nudge covers it
                        replicas = [
                            n
                            for n in owners
                            if n.id != self.node.id and n.state != "DOWN"
                        ]
                        if not replicas:
                            continue
                        sync_tasks.append((idx, f, vname, shard, replicas))

        # prune recorded versions for fragments no longer in the walk
        # (deleted/recreated indexes must not inherit stale "clean" marks,
        # and the map must not grow forever under index churn)
        live_keys = {
            (idx.name, f.name, vname, shard)
            for idx, f, vname, shard, _ in sync_tasks
        }
        for key in list(self._ae_versions):
            if key not in live_keys:
                # pop, not del: a concurrent pass (AE loop + operator's
                # POST /internal/sync) may have pruned the key already
                self._ae_versions.pop(key, None)

        def prio(t):
            idx, f, vname, shard, _ = t
            frag = f.views[vname].fragment_if_exists(shard)
            key = (idx.name, f.name, vname, shard)
            changed = frag is None or self._ae_versions.get(key) != frag.version
            return 0 if changed else 1

        sync_tasks.sort(key=prio)
        return sync_tasks

    def _sync_attrs(self, peers) -> None:
        """Pull-merge attribute stores from peers via block-checksum diffs
        (reference: holder.go:975-1019 syncIndex — column attrs per index,
        row attrs per field; attr.go:90 AttrBlock.Diff). Pull-only and
        ADD-ONLY, matching the reference's BulkSetAttrs merge: a delete
        that a peer missed can be resurrected by drift repair (the
        reference has the same property; deletes normally propagate via
        the SetRowAttrs/SetColumnAttrs broadcast, not via AE). Peer block
        lists are fetched concurrently; local checksums are computed once
        per store and refreshed only after a merge."""
        from concurrent.futures import ThreadPoolExecutor

        if not peers:
            return
        stores = []
        for idx in self.holder.indexes():
            stores.append((idx.name, None, idx.column_attr_store))
            for f in idx.fields():
                stores.append((idx.name, f.name, f.row_attr_store))
        if not stores:
            return

        def fetch(args):
            iname, fname, peer = args
            try:
                return self.client.attr_blocks(peer.uri, iname, fname)
            except ClientError:
                return None

        # ONE pool over the full (store x peer) cross product — wall time
        # is bounded by the slowest peer, not stores x peers round trips
        jobs = [(iname, fname, p) for iname, fname, _ in stores for p in peers]
        with ThreadPoolExecutor(max_workers=min(16, len(jobs))) as pool:
            remotes = list(pool.map(fetch, jobs))
        by_store: Dict[tuple, list] = {}
        for (iname, fname, peer), remote in zip(jobs, remotes):
            by_store.setdefault((iname, fname), []).append((peer, remote))
        for iname, fname, store in stores:
            results = by_store.get((iname, fname), [])
            if not any(r for _, r in results):
                continue
            local = {b["id"]: b["checksum"] for b in store.blocks()}
            for peer, remote in results:
                for b in remote or []:
                    bid = int(b["id"])
                    if local.get(bid) == b["checksum"]:
                        continue
                    try:
                        data = self.client.attr_block_data(
                            peer.uri, iname, fname, bid
                        )
                    except ClientError:
                        continue
                    if data:
                        store.set_bulk_attrs(
                            {int(k): v for k, v in data.items()}
                        )
                        # refresh only the merged block's checksum
                        local[bid] = store.block_checksum(bid)

    def _sync_fragment(self, idx, f, view: str, shard: int, replicas):
        """Returns (repaired, reached_node_ids): reached lists the
        replicas that actually participated in the reconciliation, so the
        pending-repair ledger only resolves confirmed repairs."""
        # materialize the local fragment if only replicas hold it
        frag = f.views[view].fragment(shard)
        local_sums = frag.block_checksums()
        peer_sums = []
        live = []
        for n in replicas:
            try:
                peer_sums.append(
                    {
                        int(k): bytes.fromhex(hx)
                        for k, hx in self.client.fragment_blocks(
                            n.uri, idx.name, f.name, view, shard
                        ).items()
                    }
                )
                live.append(n)
            except ClientError:
                continue
        if not live:
            return False, []
        reached = [n.id for n in live]
        diff: set = set()
        for ps in peer_sums:
            diff.update(antientropy.diff_blocks(local_sums, ps))
        if not diff:
            return False, reached
        for bid in sorted(diff):
            blocks = [frag.block_pairs(bid)]
            for n in live:
                blocks.append(
                    self.client.block_data(n.uri, idx.name, f.name, view, shard, bid)
                )
            sets, clears = antientropy.merge_block(bid, blocks)
            frag.apply_deltas(sets[0], clears[0])
            for i, n in enumerate(live, start=1):
                if len(sets[i][0]) or len(clears[i][0]):
                    self.client.send_block_deltas(
                        n.uri, idx.name, f.name, view, shard, sets[i], clears[i]
                    )
        return True, reached

    # -- resize (checkpoint-based resharding; cluster.go:1447 analog) ------

    def _resize_source_legs(
        self,
        new_nodes: List[Node],
        replica_n: Optional[int] = None,
        old_nodes: Optional[List[Node]] = None,
        old_replica_n: Optional[int] = None,
    ):
        """(old_cluster, new_cluster, legs): the fragment transfers THIS
        node must run for the old->new placement diff — legs are
        ((index, field, view, shard), ResizeSource) pairs. ONE copy of
        the placement-critical walk, shared by the legacy checkpoint path
        (resize_to) and the streaming path (resize_stream). The old
        cluster is built with `old_replica_n` (the coordinator passes the
        PRE-resize replication so a resize that also changes replica_n
        does not mis-compute who already holds what; the replica_n
        fallback keeps the legacy manual-call shape). Old nodes marked
        DOWN (the coordinator's probe pass rides in on `old_nodes`) are
        skipped during inventory so a corpse costs nothing."""
        from pilosa_tpu.cluster.topology import Frag

        old = self.cluster
        if old_nodes is not None:
            if old_replica_n is None:
                old_replica_n = (
                    replica_n if replica_n is not None else old.replica_n
                )
            old = Cluster(
                nodes=old_nodes,
                replica_n=old_replica_n,
                partition_n=old.partition_n,
                hasher=old.hasher,
            )
        new = Cluster(
            nodes=new_nodes,
            replica_n=replica_n if replica_n is not None else old.replica_n,
            partition_n=old.partition_n,
            hasher=old.hasher,
            state=STATE_NORMAL,
        )
        legs = []
        for idx in self.holder.indexes():
            # cluster-wide fragment inventory: union of every old-cluster
            # node's local fragments (a joining node has none of its own)
            inventory = set()
            for n in old.nodes:
                if n.id == self.node.id:
                    for f in idx.fields(include_hidden=True):
                        for vname, v in f.views.items():
                            inventory.update(
                                (f.name, vname, s) for s in v.fragments
                            )
                    continue
                if n.state == "DOWN":
                    continue
                try:
                    inventory.update(
                        self.client.fragment_inventory(n.uri, idx.name)
                    )
                except ClientError:
                    continue
            if not inventory:
                continue
            # make every inventoried shard visible to future query fan-out
            for fl, vw, sh in inventory:
                f = idx.field(fl)
                if f is not None:
                    f.add_remote_available([sh])
            frags = [Frag(fl, vw, sh) for fl, vw, sh in sorted(inventory)]
            sources = old.frag_sources(new, idx.name, frags)
            for src in sources.get(self.node.id, []):
                if idx.field(src.field) is None:
                    continue
                legs.append(
                    ((idx.name, src.field, src.view, src.shard), src)
                )
        return old, new, legs

    def resize_to(
        self,
        new_nodes: List[Node],
        replica_n: Optional[int] = None,
        old_nodes: Optional[List[Node]] = None,
        old_replica_n: Optional[int] = None,
    ) -> int:
        """Checkpoint-based resize (the manual/bootstrap fallback): diff
        fragment placement old->new, fetch fragments this node must
        acquire, then install the new topology locally. Each node runs
        this against the same `new_nodes` list (the bootstrap/ops layer
        coordinates the order); a JOINING node passes `old_nodes` (the
        membership it is joining) since its own cluster view is just
        itself. Returns fragments fetched."""
        _, new, legs = self._resize_source_legs(
            new_nodes, replica_n, old_nodes, old_replica_n
        )
        fetched = 0
        for (iname, fname, vname, shard), src in legs:
            try:
                blob = self.client.retrieve_fragment(
                    src.node.uri, iname, fname, vname, shard
                )
            except ClientError as e:
                self.logger(f"resize fetch {iname}/{fname}: {e}")
                continue
            idx = self.holder.index(iname)
            f = idx.field(fname) if idx is not None else None
            if f is None:
                # concurrent DDL deleted the field since the inventory
                # walk — the fragment has no post-resize owner to miss
                continue
            f._view_create(vname).fragment(shard).from_bytes(blob)
            fetched += 1
        self.set_topology(new_nodes, replica_n=new.replica_n)
        return fetched

    def clean_holder(self) -> int:
        """Remove fragments the current topology no longer assigns to this
        node (reference: holderCleaner.CleanHolder, holder.go:1126) —
        without this every resize leaks disk and devcache residency.
        Returns the number of fragments removed."""
        if len(self.cluster.nodes) <= 1:
            return 0
        removed = 0
        for idx in self.holder.indexes():
            for f in idx.fields(include_hidden=True):
                for v in list(f.views.values()):
                    for shard in list(v.fragments):
                        owners = self.cluster.shard_nodes(idx.name, shard)
                        if any(n.id == self.node.id for n in owners):
                            continue
                        v.delete_fragment(shard)
                        removed += 1
        if removed:
            self.logger(f"holder cleaner removed {removed} fragments")
        return removed

    # -- streaming resize: source-side write captures ----------------------
    # A moving fragment ships in two phases (cluster.go:1297
    # followResizeInstruction, made live): (1) the destination GETs
    # /internal/fragment/data?capture=<job>, which snapshots the fragment
    # AND arms a write capture atomically; (2) it drains the capture
    # (/internal/fragment/delta) in catch-up rounds until dry, and once
    # more after the topology cutover. Captures are leased: a dead
    # driver's capture self-expires instead of buffering forever.

    def begin_fragment_capture(self, tag: str, key: tuple, frag) -> bytes:
        """Snapshot + arm the write capture for one fragment transfer
        leg; `key` is (index, field, view, shard) and `tag` is the
        destination's opaque transfer tag (`<job>:<dest node id>` — each
        destination gets its OWN capture, so two replicas streaming the
        same source fragment never steal each other's records). Returns
        the snapshot blob."""
        blob = frag.begin_streaming(tag)
        try:
            now = time.monotonic()
            with self._transfer_mu:
                self._sweep_captures_locked(now)
                # transfer: lease table owns it (sweep expires, drain ends)
                self._transfer_captures[(tag,) + tuple(key)] = {
                    "frag": frag,
                    "expires": now + CAPTURE_LEASE,
                }
        except BaseException:
            # a capture armed but never registered has no lease — nothing
            # would ever drain or expire it, and it buffers every write
            # to the fragment until overflow; disarm before propagating
            frag.end_capture(tag)
            raise
        return blob

    def tier_offer(self, iname: str, fname: str, vname: str, shard: int, tag: str) -> dict:
        """Source-side snapshot-bootstrap offer for one transfer leg.
        Instead of streaming the fragment's bytes peer-to-peer, the
        destination asks whether a current snapshot object already sits
        in the shared store. Three answers:

        - "cold": the fragment is demoted — the stored object IS its
          exact contents (a cold fragment has provably taken zero
          writes). A None-frag lease entry plus a hydration watch keep
          the delta plane exact: drains return an empty delta while
          cold, and if the fragment hydrates mid-transfer the watch
          arms a capture BEFORE the fragment publishes, so no write can
          slip between the object the joiner fetched and the capture.
        - "snapshot": the fragment is live but its manifest still
          matches its contents; `begin_capture_if_version` re-verifies
          currency and arms the capture atomically — any interleaved
          write flunks the version check and falls back to streaming.
        - "stream": no current object; use the classic byte-streaming
          path."""
        key = (iname, fname, vname, shard)
        if self.tier is None:
            return {"mode": "stream"}
        mode, meta, live_version = self.tier.offer(*key)
        if mode == "stream" or meta is None:
            return {"mode": "stream"}
        now = time.monotonic()
        if mode == "snapshot":
            idx = self.holder.index(iname)
            f = idx.field(fname) if idx is not None else None
            v = f.views.get(vname) if f is not None else None
            frag = v.fragments.get(shard) if v is not None else None
            if frag is None or not frag.begin_capture_if_version(tag, live_version):
                return {"mode": "stream"}
            with self._transfer_mu:
                self._sweep_captures_locked(now)
                self._transfer_captures[(tag,) + key] = {
                    "frag": frag,
                    "expires": now + CAPTURE_LEASE,
                }
            return {"mode": "snapshot", "meta": meta}
        with self._transfer_mu:
            self._sweep_captures_locked(now)
            self._transfer_captures[(tag,) + key] = {
                "frag": None,
                "expires": now + CAPTURE_LEASE,
            }
        armed = self.tier.watch_hydration(
            key, tag, lambda frag: self._arm_watched_capture(tag, key, frag)
        )
        if not armed:
            # raced a hydration: the key is no longer cold and no watch
            # will ever fire — retract the lease and stream classically
            with self._transfer_mu:
                self._transfer_captures.pop((tag,) + key, None)
            return {"mode": "stream"}
        return {"mode": "cold", "meta": meta}

    def _arm_watched_capture(self, tag: str, key: tuple, frag) -> None:
        """Hydration-watch callback for a cold-mode bootstrap offer.
        Runs pre-publish (adopt_fragment's on_ready), so the capture is
        armed before any write can reach the fragment — the joiner's
        fetched object plus this capture's delta is exact. An expired
        lease means the joiner is gone; leave the fragment untouched."""
        now = time.monotonic()
        with self._transfer_mu:
            ent = self._transfer_captures.get((tag,) + tuple(key))
            if ent is None or now >= ent["expires"]:
                return
            if frag.begin_capture_if_version(tag, frag.version):
                ent["frag"] = frag
            else:
                # cannot happen on an unpublished fragment, but if it
                # ever did, a dropped lease turns the next drain into a
                # 410 -> full snapshot refetch, which is always safe
                self._transfer_captures.pop((tag,) + tuple(key), None)

    def drain_fragment_capture(self, tag: str, key: tuple) -> bytes:
        """Pop one transfer leg's captured writes (WAL-framed bytes).
        Raises TransferCaptureLost (-> HTTP 410) when the capture is gone
        — expired lease, overflow, or a source restart — telling the
        destination to refetch the full snapshot."""
        from pilosa_tpu.core.fragment import TransferCaptureLost

        now = time.monotonic()
        with self._transfer_mu:
            self._sweep_captures_locked(now)
            ent = self._transfer_captures.get((tag,) + tuple(key))
            if ent is not None:
                ent["expires"] = now + CAPTURE_LEASE
        if ent is None:
            raise TransferCaptureLost(f"no active capture for {key} ({tag})")
        if ent["frag"] is None:
            # cold-mode bootstrap watch (tier_offer): the fragment is
            # still demoted, so it has provably taken zero writes — an
            # empty delta is exact, not a fallback
            from pilosa_tpu.core import wal as wal_mod

            return wal_mod.encode_records([])
        return ent["frag"].drain_capture(tag)

    def _sweep_captures_locked(self, now: float) -> None:
        for key, ent in list(self._transfer_captures.items()):
            if now >= ent["expires"]:
                del self._transfer_captures[key]
                if ent["frag"] is not None:
                    ent["frag"].end_capture(key[0])
                elif self.tier is not None:
                    self.tier.unwatch(key[0])

    def _transfer_tag(self, job: str) -> str:
        """This node's capture tag for one job's transfer legs."""
        return f"{job}:{self.node.id}"

    def quiesce_job_captures(self, job: str, ttl: float) -> int:
        """Arm the per-fragment cutover write barrier on every fragment
        with an armed capture for `job` (`resize-quiesce` broadcast, sent
        required-ack by the coordinator right before the final drain):
        writes to moving fragments 503 retryably for the barrier window,
        so the drain that follows provably empties every capture BEFORE
        the topology installs — the stale-replay inversion (an old
        captured record replayed over a newer post-cutover write) is
        structurally impossible. The barrier lifts on resize-release /
        resize-cleanup (end_capture) or self-expires at `ttl`."""
        with self._transfer_mu:
            frags = [
                ent["frag"]
                for k, ent in self._transfer_captures.items()
                if (k[0] == job or k[0].startswith(job + ":"))
                and ent["frag"] is not None
            ]
        for f in frags:
            f.block_writes(ttl)
        return len(frags)

    def release_job_captures(self, job: Optional[str] = None) -> int:
        """End this job's captures (all jobs when None) and drop the
        destination-side ledger — the normal-completion teardown (the
        coordinator broadcasts `resize-release` after the final drain).
        Matches both the bare job id and every per-destination
        `<job>:<dest>` tag. Fetched fragments are KEPT: the cutover
        committed them."""
        with self._transfer_mu:
            keys = [
                k
                for k in self._transfer_captures
                if job is None or k[0] == job or k[0].startswith(job + ":")
            ]
            ents = [(k, self._transfer_captures.pop(k)) for k in keys]
            if job is None:
                self._resize_ledger.clear()
            else:
                self._resize_ledger.pop(job, None)
        for k, ent in ents:
            if ent["frag"] is not None:
                ent["frag"].end_capture(k[0])
            elif self.tier is not None:
                self.tier.unwatch(k[0])
        return len(ents)

    def resize_cleanup(self, job: str, aborting: bool = False) -> int:
        """Abort-path teardown (`resize-cleanup` broadcast) and
        stale-ledger sweep: delete the fragments this job's transfers
        CREATED here (restoring disk and device-cache residency to the
        pre-resize state), then release captures and the ledger.
        Fragments that already existed before the job are untouched —
        their contents only ever gained replayed writes through the
        normal exact funnels. `aborting` deletes created fragments
        unconditionally: a rolled-back job's fetches must leave no trace
        even when the restored topology happens to claim the shard — in
        particular a joiner reset to a solo cluster owns EVERY shard, so
        the stale-ledger ownership guard below would keep all of them."""
        with self._transfer_mu:
            ledger = self._resize_ledger.get(job)
            created = list(ledger["created"]) if ledger else []
        removed = 0
        for iname, fname, vname, shard in created:
            if not aborting and self.cluster.owns_shard(self.node.id, iname, shard):
                # the CURRENT topology assigns this shard here: the
                # ledger is stale because a resize-release got lost after
                # a COMMITTED job, not because this job rolled back —
                # deleting would drop live, owned data.
                continue
            idx = self.holder.index(iname)
            f = idx.field(fname) if idx is not None else None
            v = f.views.get(vname) if f is not None else None
            if v is not None and v.delete_fragment(shard):
                removed += 1
        self.release_job_captures(job)
        if removed:
            self.logger(f"resize cleanup ({job}): removed {removed} fragments")
        return removed

    # -- streaming resize: destination-side transfer steps -----------------

    def resize_stream(
        self,
        job: str,
        new_nodes: List[Node],
        replica_n: Optional[int] = None,
        old_nodes: Optional[List[Node]] = None,
        old_replica_n: Optional[int] = None,
        post_commit: bool = False,
    ) -> dict:
        """One node's phase-1 step of a STREAMING resize: fetch every
        fragment the new placement assigns to this node (full snapshot +
        armed write capture on the source), then drain delta rounds until
        the source runs dry — all WITHOUT touching the installed topology,
        so this node keeps serving reads and writes against the OLD
        placement the whole time. Crash-resumable: fragments already in
        this job's ledger skip the refetch and just catch up (a lost
        source capture forces that leg back to a full snapshot). Returns
        {"fetched", "deltas", "shards"} — `shards` feeds the
        coordinator's post-cutover repair-debt pass."""
        from concurrent.futures import ThreadPoolExecutor

        with self._transfer_mu:
            stale = [j for j in self._resize_ledger if j != job]
            ledger = self._resize_ledger.get(job)
            if ledger is None:
                ledger = self._resize_ledger[job] = {
                    "fetched": {},  # (index, field, view, shard) -> src uri
                    "created": set(),  # keys whose fragment we created
                }
        for j in stale:
            # a superseded job's leftovers (its coordinator died before
            # broadcasting cleanup) must not shadow this one
            self.resize_cleanup(j)
        _, _, legs = self._resize_source_legs(
            new_nodes, replica_n, old_nodes, old_replica_n
        )
        if post_commit:
            # the final sweep only hunts fragments CREATED after this
            # node's first inventory walk. Legs already in the ledger were
            # drained dry under the cutover write barrier — complete by
            # construction — and re-draining them now would 410 (captures
            # released) into a snapshot refetch that clobbers post-cutover
            # writes.
            with self._transfer_mu:
                done = set(ledger["fetched"])
            legs = [(k, s) for k, s in legs if k not in done]
        fetched = 0
        deltas = 0
        if legs:
            workers = min(self.resize_transfer_concurrency, len(legs))
            with ThreadPoolExecutor(
                max_workers=workers, thread_name_prefix="resize-xfer"
            ) as pool:
                results = list(
                    pool.map(
                        lambda leg: self._transfer_leg(
                            job, ledger, *leg, post_commit=post_commit
                        ),
                        legs,
                    )
                )
            fetched = sum(f for f, _ in results)
            deltas += sum(d for _, d in results)
        if not post_commit:
            # catch-up rounds: drain every source until a round comes back
            # empty (bounded by rounds and by the cutover-timeout wall clock)
            deadline = time.monotonic() + max(self.resize_cutover_timeout, 0.5)
            for _ in range(_MAX_CATCHUP_ROUNDS):
                applied = self._catchup_round(job)
                self.stats.count("resize.catchup_rounds", 1)
                deltas += applied
                if applied == 0 or time.monotonic() >= deadline:
                    break
        shards: Dict[str, List[int]] = {}
        with self._transfer_mu:
            for iname, _f, _v, shard in ledger["fetched"]:
                if shard not in shards.setdefault(iname, []):
                    shards[iname].append(shard)
        return {"fetched": fetched, "deltas": deltas, "shards": shards}

    def _transfer_leg(
        self, job: str, ledger: dict, key: tuple, src, post_commit: bool = False
    ) -> tuple:
        """Stream one fragment from its source (or just catch it up when
        the ledger says the snapshot already landed in a prior attempt).
        Post-commit (the coordinator's final sweep), the leg is a late
        arrival the first inventory walk missed: fetch it WITHOUT arming a
        capture (the install already routed its writes to this node) and
        MERGE into any existing contents — a wholesale replace would erase
        post-cutover writes already acknowledged here.
        Returns (fetched 0|1, delta_positions)."""
        iname, fname, vname, shard = key
        span = self.tracer.start_span("resize.transfer")
        with span:
            span.set_tag("index", iname)
            span.set_tag("field", fname)
            span.set_tag("shard", shard)
            span.set_tag("peer", src.node.uri)
            if post_commit:
                blob_len = self._fetch_leg(
                    job, ledger, key, src.node.uri,
                    capture=False, merge_existing=True,
                )
            else:
                with self._transfer_mu:
                    resumed = key in ledger["fetched"]
                if resumed:
                    applied = self._drain_or_refetch(
                        job, ledger, key, src.node.uri
                    )
                    span.set_tag("resize.resumed", True)
                    return 0, applied
                blob_len = self._fetch_leg(job, ledger, key, src.node.uri)
            if blob_len is None:
                span.set_tag("resize.skipped", True)
                return 0, 0
            span.set_tag("resize.bytes", blob_len)
            return 1, 0

    def _fetch_leg(
        self,
        job: str,
        ledger: dict,
        key: tuple,
        src_uri: str,
        capture: bool = True,
        merge_existing: bool = False,
    ) -> Optional[int]:
        """Fetch one leg's full snapshot (arming the source's write
        capture atomically unless `capture=False`) and record it in the
        job ledger. Returns the blob size, or None when the leg is moot
        (its field was deleted since the inventory walk) or could not be
        merged — skipped, never an AttributeError 500."""
        iname, fname, vname, shard = key
        idx = self.holder.index(iname)
        f = idx.field(fname) if idx is not None else None
        if f is None:
            # concurrent DDL: the field is gone, so there is nothing to
            # own post-cutover — skip the leg instead of failing the job
            self.logger(f"resize fetch {iname}/{fname}: field gone, skipping")
            return None
        blob = None
        via_tier = False
        if capture and not merge_existing and self.tier is not None:
            blob = self._tier_fetch_leg(job, key, src_uri)
            via_tier = blob is not None
        if blob is None:
            blob = self.client.retrieve_fragment(
                src_uri, iname, fname, vname, shard,
                capture=self._transfer_tag(job) if capture else None,
            )
        v = f._view_create(vname)
        existing = v.fragment_if_exists(shard)
        created = existing is None
        if merge_existing and not created:
            try:
                existing.merge_from_bytes(blob)
            except ValueError as e:
                # mutex fragments cannot word-merge; the newer local
                # contents stand and the repair-debt backstop reconciles
                self.logger(f"resize sweep merge {key}: {e}")
                return None
        else:
            v.fragment(shard).from_bytes(blob)
        with self._transfer_mu:
            ledger["fetched"][key] = src_uri
            if created:
                ledger["created"].add(key)
        if not via_tier:
            # tier-path legs count tier.bootstrap_* (in bootstrap_fetch)
            # instead — the snapshot-bootstrap acceptance criterion
            # compares the two byte counters
            self.stats.count("resize.fragments_streamed", 1)
            self.stats.count("resize.bytes_streamed", len(blob))
        return len(blob)

    def _tier_fetch_leg(self, job: str, key: tuple, src_uri: str) -> Optional[bytes]:
        """Try the snapshot-bootstrap path for one transfer leg: ask the
        source to offer the fragment as a stored object (arming its
        capture or hydration watch on the way out), then fetch the
        object from the shared store instead of streaming the bytes
        from the peer. Returns the verified blob, or None to fall back
        to classic streaming (source untiered, offer said stream, or
        the store fetch failed — in which case the classic retrieve
        re-arms the same tag and the transfer stays exact)."""
        from pilosa_tpu.tier.store import StoreError

        iname, fname, vname, shard = key
        try:
            offer = self.client.tier_offer(
                src_uri, iname, fname, vname, shard, self._transfer_tag(job)
            )
        except ClientError as e:
            if e.status != 404:
                self.logger(f"tier offer {key}: {e}; streaming")
            return None
        meta = offer.get("meta")
        if offer.get("mode") not in ("cold", "snapshot") or not meta:
            return None
        try:
            return self.tier.bootstrap_fetch(meta)
        except StoreError as e:
            self.logger(f"tier bootstrap fetch {key}: {e}; streaming")
            return None

    def _drain_or_refetch(self, job: str, ledger: dict, key: tuple, src_uri: str) -> int:
        """Drain one leg's capture. ANY drain failure recovers by
        refetching the full snapshot and draining the fresh capture once:
        the source-side pop is destructive and the drain RPC deliberately
        single-attempt, so a failed drain is ambiguous (a lost response
        may have taken popped records with it) or lost outright (410) —
        and the snapshot is always a superset of whatever the delta would
        have carried. ValueError covers a torn/corrupt wire delta: the
        strict decode applied NOTHING, and the popped records live only in
        the garbled bytes, so only a fresh snapshot can recover them. The
        refetch itself rides the normal retry plane; if it fails too, the
        error propagates to the caller's resume/abort policy.

        EXCEPTION: a 429 admission shed is NOT ambiguous — the handler
        sheds before `drain_fragment_capture` runs, so no records were
        popped and the drain is safe to retry. Escalating a shed to a
        full snapshot refetch would amplify the very load that caused it
        (and inside the cutover barrier would turn a near-empty delta
        pop into a whole-fragment transfer)."""
        err: Exception
        for _ in range(4):
            try:
                return self._drain_leg(job, key, src_uri)
            except ClientError as e:
                err = e
                if e.status == 429:
                    time.sleep(min(e.retry_after or 0.05, 1.0))
                    continue
                break
            except ValueError as e:
                err = e
                break
        self.logger(f"resize drain {key}: {err}; refetching snapshot")
        if self._fetch_leg(job, ledger, key, src_uri) is None:
            return 0
        try:
            return self._drain_leg(job, key, src_uri)
        except (ClientError, ValueError) as e:
            # the refetched snapshot already carries everything up to its
            # arm point; whatever landed since stays in the fresh capture
            # for the next catch-up round (or the repair-debt backstop)
            self.logger(f"resize drain {key} after refetch: {e}")
            return 0

    def _drain_leg(self, job: str, key: tuple, src_uri: str) -> int:
        iname, fname, vname, shard = key
        data = self.client.fragment_delta(
            src_uri, iname, fname, vname, shard, self._transfer_tag(job)
        )
        if not data:
            return 0
        idx = self.holder.index(iname)
        f = idx.field(fname) if idx is not None else None
        v = f.views.get(vname) if f is not None else None
        frag = v.fragment(shard) if v is not None else None
        if frag is None:
            return 0
        applied = frag.apply_transfer_records(data)
        if applied:
            self.stats.count("resize.delta_positions", applied)
        return applied

    def _catchup_round(self, job: str) -> int:
        """One drain round over every transfer leg in this job's ledger
        (lost captures recover via snapshot refetch), legs drained in
        parallel on the same `resize_transfer_concurrency` bound as the
        stream phase — the cutover's write-barrier window is one of these
        rounds, so a sequential drain would scale that window with
        legs x RTT instead of legs/concurrency. Per-leg work is
        independent (distinct destination fragments, per-leg captures;
        ledger access under _transfer_mu), exactly as in the concurrent
        stream phase. Returns total positions applied; raises ClientError
        when a source is unreachable (the caller decides resume vs
        abort)."""
        from concurrent.futures import ThreadPoolExecutor

        with self._transfer_mu:
            ledger = self._resize_ledger.get(job)
            legs = list(ledger["fetched"].items()) if ledger else []
        if not legs:
            return 0
        workers = min(self.resize_transfer_concurrency, len(legs))
        if workers <= 1:
            return sum(
                self._drain_or_refetch(job, ledger, key, src_uri)
                for key, src_uri in legs
            )
        with ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="resize-drain"
        ) as pool:
            return sum(
                pool.map(
                    lambda leg: self._drain_or_refetch(job, ledger, *leg),
                    legs,
                )
            )

    def resize_catchup(self, job: str) -> int:
        """The cutover's final drain (the coordinator orders one on every
        destination after quiescing the sources, BEFORE the topology
        install): with the write barrier armed, this round provably
        empties every capture, so nothing is left to replay over writes
        the new topology will route."""
        return self._catchup_round(job)

    # -- coordinator-driven resize jobs (cluster.go:1141-1561) -------------

    def start_resize(
        self,
        new_nodes: List[Node],
        action: str,
        replica_n: Optional[int] = None,
    ) -> dict:
        """Start a coordinator-driven resize job: order every node through
        resize_to under a RUNNING/DONE/ABORTED job record, with rollback of
        the old topology on failure or abort (the role of the reference's
        listenForJoins -> generateResizeJob -> resizeJob.run,
        cluster.go:1141,1196,1504 — checkpoint-streaming instead of live
        ResizeInstructions, per the TPU-native static-mesh design).
        Returns the job record immediately; poll `resize_job` for state."""
        if not self.node.is_coordinator:
            raise ClientError("node is not the coordinator")
        with self._resize_mu:
            if self.resize_job is not None and self.resize_job["state"] == "RUNNING":
                raise ClientError("a resize job is already running")
            job = {
                "id": f"{self.node.id}-{int(time.time() * 1000)}",
                "action": action,
                "state": "RUNNING",
                "phase": "starting",
                "committed": False,
                "nodes": [n.to_json() for n in new_nodes],
                "transfers": {},
                "moved": [],
                "error": None,
            }
            self.resize_job = job
            self._resize_abort.clear()
            self._resize_thread = threading.Thread(
                target=self._run_resize,
                args=(job, list(new_nodes), replica_n),
                name=f"resize-{self.node.id}",
                daemon=True,
            )
            self._resize_thread.start()
        return job

    def abort_resize(self) -> dict:
        """Abort path (reference: api.go:1250 ResizeAbort). The running job
        notices at its next phase boundary and rolls back the old
        topology. Once the cutover install has been ACKNOWLEDGED (the job
        is "committed"), abort is a no-op: the cluster already agreed on
        the new topology, and un-installing it could race the NORMAL
        broadcast into a split placement view — the job rolls forward to
        DONE instead."""
        with self._resize_mu:
            job = self.resize_job
            if (
                job is not None
                and job["state"] == "RUNNING"
                and not job.get("committed")
            ):
                self._resize_abort.set()
        return self.resize_job or {"state": "NONE"}

    def _run_resize(self, job: dict, new_nodes: List[Node], replica_n) -> None:
        """Streaming resize job FSM. Phases:

        probe -> stream (per-node snapshot+capture transfer legs, catch-up
        rounds, old topology still serving everything) -> cutover
        (quiesce sources behind the per-fragment write barrier, final
        drain to provably-empty captures, then required-ack install of
        the new topology — the ATOMIC commit point) -> sweep (fetch-only
        fragments created after the first inventory walks) -> gc. Writes
        are never globally frozen — only a bounded per-fragment barrier
        window at cutover — and queries admit through the whole job. Any
        failure or abort BEFORE the cutover ack rolls back to the old
        topology with every transferred fragment deleted and every
        capture released — no half-owned shards. After the cutover ack
        the job only rolls FORWARD: residual drift is recorded as repair
        debt and drained by anti-entropy."""
        old_members = list(self.cluster.nodes)
        old_replica = self.cluster.replica_n
        old_ids = {n.id for n in old_members}
        new_ids = {n.id for n in new_nodes}
        joiners = [n for n in new_nodes if n.id not in old_ids]
        removed = [n for n in old_members if n.id not in new_ids]
        job_id = job["id"]

        def phase(name: str) -> None:
            job["phase"] = name
            hook = self.resize_phase_hook
            if hook is not None:
                hook(name)
            if self._resize_abort.is_set():
                raise _ResizeAborted()

        def rollback() -> None:
            # restore the old membership on the old members; any joiner
            # that already installed the new topology is reset to a
            # standalone single-node cluster (it never became a member).
            # Then every participant tears down its transfer state: the
            # resize-cleanup broadcast deletes destination-side fetched
            # fragments and releases source-side captures, so the stream
            # phase leaves NO trace — topology, repair debt, and device
            # residency all read as pre-resize. Delivery is best-effort
            # with retries; a node that misses cleanup self-heals via the
            # capture lease and the next job's stale-ledger sweep.
            self.stats.count("resize.aborts", 1)
            self._send_status(
                old_members, old_members, old_replica, STATE_NORMAL, retries=10
            )
            for n in joiners:
                solo = Node(id=n.id, uri=n.uri, is_coordinator=True)
                self._send_status([solo], [solo], 1, STATE_NORMAL)
            self._broadcast_transfer_msg(
                list(new_nodes) + old_members,
                {"type": "resize-cleanup", "job": job_id},
            )

        try:
            # refresh liveness first so dead members are excluded from
            # inventory walks and source picks (the reference confirms
            # down via /status probes before honoring it, cluster.go:1724)
            phase("probe")
            self.probe_peers()
            # joiners are not members yet, so probe_peers never reaches
            # them: probe directly (probe=True also heals an open breaker
            # left by an earlier failed attempt) and abort fast when a
            # joiner is dead instead of discovering it mid-stream
            for n in joiners:
                self.client.status(n.uri, timeout=2.0, probe=True)
            # the old membership WITH fresh liveness marks rides along to
            # every destination, so their inventory/fetch skips corpses
            old_json = [m.to_json() for m in old_members]
            # existing members first (they fetch from current owners while
            # everyone still holds their old fragments), joiners last
            order = [n for n in new_nodes if n.id in old_ids] + joiners
            phase("stream")
            for n in order:
                phase(f"stream:{n.id}")
                self._stream_step(
                    job, n, new_nodes, old_json, replica_n, old_replica,
                    joining=n.id not in old_ids,
                )
            new_replica = replica_n if replica_n is not None else old_replica
            phase("cutover")
            t0 = time.perf_counter()
            span = self.tracer.start_span("resize.cutover")
            with span:
                span.set_tag("job", job_id)
                # late DDL: re-push the schema to joiners so fields created
                # while they streamed exist before they start serving
                for n in joiners:
                    try:
                        self.client.post_schema(n.uri, self.api.schema())
                    except ClientError as e:
                        self.logger(f"schema refresh to joiner {n.id}: {e}")
                # quiesce the sources: arm the per-fragment cutover write
                # barrier on every armed capture, REQUIRED-ack — a source
                # that keeps accepting writes would keep growing captures
                # whose post-install replay could clobber newer writes
                # routed through the new topology (last-write-wins
                # inversion). A failure here aborts pre-commit: clean
                # rollback, and resize-cleanup lifts any barrier already
                # armed. The deadline-based barrier self-expires, so even
                # a lost release cannot freeze a fragment forever.
                quiesce_ttl = max(self.resize_cutover_timeout, 5.0) * 2
                for n in old_members:
                    if n.state == "DOWN":
                        continue
                    if n.id == self.node.id:
                        self.quiesce_job_captures(job_id, quiesce_ttl)
                    else:
                        self.client.send_message(
                            n.uri,
                            {
                                "type": "resize-quiesce",
                                "job": job_id,
                                "ttl": quiesce_ttl,
                            },
                        )
                # final drain to dry: with writes barred, one round per
                # destination pops everything its sources captured — after
                # this the captures are provably empty and stay empty, so
                # the install below cuts over with nothing left to replay
                for n in new_nodes:
                    if n.id == self.node.id:
                        self.resize_catchup(job_id)
                    else:
                        self.client.resize_catchup(n.uri, job_id)
                # THE commit point: every new member must acknowledge the
                # new topology or the job aborts and rolls back — a
                # partial install would split the cluster's placement view
                self._send_status(
                    new_nodes, new_nodes, new_replica, STATE_NORMAL,
                    require=True,
                )
            self.stats.timing("resize.cutover_ms", time.perf_counter() - t0)
        except _ResizeAborted:
            rollback()
            job["state"] = "ABORTED"
            job["error"] = "aborted"
            return
        except Exception as e:  # noqa: BLE001 - job record carries the error
            rollback()
            job["state"] = "ABORTED"
            job["error"] = str(e)
            self.logger(f"resize job {job_id} aborted: {e}")
            return
        # ---- committed. From here the job only rolls FORWARD: an abort
        # request is a no-op (honoring it would have to un-acknowledge an
        # installed topology) and per-node failures degrade to logged
        # repair debt, never to a rollback racing the NORMAL broadcast.
        job["committed"] = True
        job["phase"] = "drain"
        if self.resize_phase_hook is not None:
            self.resize_phase_hook("committed")
        # removed nodes get the final status too (best-effort): they learn
        # they are no longer members and reset to standalone
        if removed:
            self._send_status(removed, new_nodes, new_replica, STATE_NORMAL)
        # final sweep: re-issue every node's stream step in POST-COMMIT
        # mode, which only hunts fragments a write CREATED after that
        # node's first inventory walk — without the sweep, such a
        # fragment's only old-placement copy would be GC'd below with its
        # new owner never having fetched it. Sources still hold everything
        # (GC has not run). Ledger legs are deliberately NOT re-touched:
        # they drained dry under the cutover write barrier, and a
        # post-install re-drain or refetch could replay stale state over
        # writes the new topology already acknowledged. Best-effort
        # post-commit: failures degrade to logged repair debt, never a
        # rollback.
        for n in new_nodes:
            try:
                self._stream_step(
                    job, n, new_nodes, old_json, replica_n, old_replica,
                    joining=n.id not in old_ids, post_commit=True,
                )
            except (_ResizeAborted, ClientError) as e:
                self.logger(
                    f"post-cutover sweep on {n.id}: {e} "
                    "(anti-entropy will repair)"
                )
        # repair-debt backstop: every moved fragment gets a pending-repair
        # entry for its new owner, so the anti-entropy plane re-verifies
        # block checksums even if an in-flight write slipped both drains.
        # Only meaningful with replicas to reconcile against (same rule as
        # the import fan-out's dropped-replica ledger).
        if new_replica > 1:
            for iname, shard, dest in job.get("moved", []):
                self.holder.record_pending_repair(iname, int(shard), dest)
        # drop captures and ledgers everywhere (sources include removed
        # nodes — they streamed their fragments out)
        self._broadcast_transfer_msg(
            list(new_nodes) + old_members,
            {"type": "resize-release", "job": job_id},
        )
        # post-resize GC: members drop fragments the new topology no longer
        # assigns to them (holder.go:1126 CleanHolder). Runs AFTER the
        # cluster committed to the new topology — sources keep their data
        # until every node has fetched its set, and a GC failure must never
        # roll the resize back. DONE is reported only once GC finished, so
        # observers of DONE see the cleaned state.
        job["phase"] = "gc"
        for n in new_nodes:
            try:
                if n.id == self.node.id:
                    self.clean_holder()
                else:
                    self.client.send_message(n.uri, {"type": "clean-holder"})
            except Exception as e:  # noqa: BLE001 - GC is best-effort
                self.logger(f"clean-holder on {n.id}: {e}")
        job["state"] = "DONE"
        if job.get("moved") and new_replica > 1:
            # drain the just-recorded transfer repair debt NOW instead of
            # leaving it standing in /status until the next anti-entropy
            # tick (the interval defaults to manual). Runs after DONE so
            # pollers never wait on it; the AE ticker + debt nudges
            # remain the backstop if this pass cannot reach a peer.
            try:
                self.try_sync_holder(wait_nudge=True)
            except Exception as e:  # noqa: BLE001 - drain is best-effort
                self.logger(f"post-resize repair drain: {e}")

    def _stream_step(
        self,
        job: dict,
        n: Node,
        new_nodes: List[Node],
        old_json: List[dict],
        replica_n,
        old_replica_n,
        joining: bool,
        post_commit: bool = False,
    ) -> None:
        """Order one node through its stream phase, honoring the
        resume-vs-abort policy: under "resume" a failed step gets one
        retry after a liveness refresh — the destination's transfer
        ledger skips already-landed snapshots, so the retry only moves
        what the first attempt missed. Under "abort" the first failure
        aborts the job."""
        attempts = 2 if self.resize_resume_policy == "resume" else 1
        last: Optional[ClientError] = None
        for attempt in range(attempts):
            try:
                if n.id == self.node.id:
                    res = self.resize_stream(
                        job["id"],
                        new_nodes,
                        replica_n=replica_n,
                        old_nodes=[Node.from_json(m) for m in old_json],
                        old_replica_n=old_replica_n,
                        post_commit=post_commit,
                    )
                else:
                    res = self.client.resize_stream(
                        n.uri,
                        job["id"],
                        [m.to_json() for m in new_nodes],
                        old_nodes=old_json,
                        replica_n=replica_n,
                        old_replica_n=old_replica_n,
                        schema=self.api.schema() if joining else None,
                        post_commit=post_commit,
                    )
                # accumulate across sweeps: the post-install drain re-runs
                # this step with every leg resumed (fetched=0), and an
                # overwrite would erase the first sweep's counts from the
                # operator-facing job record
                ent = job.setdefault("transfers", {}).setdefault(
                    n.id, {"fetched": 0, "deltas": 0}
                )
                ent["fetched"] += int(res.get("fetched", 0))
                ent["deltas"] += int(res.get("deltas", 0))
                moved = job.setdefault("moved", [])
                for iname, shards in (res.get("shards") or {}).items():
                    for s in shards:
                        ent = [iname, int(s), n.id]
                        if ent not in moved:  # sweep re-reports the same legs
                            moved.append(ent)
                return
            except ClientError as e:
                last = e
                self.logger(
                    f"resize stream step on {n.id} failed "
                    f"(attempt {attempt + 1}/{attempts}): {e}"
                )
                if attempt + 1 < attempts:
                    self.probe_peers()
                    try:
                        # direct probe: closes the node's breaker if it is
                        # actually healthy (probe_peers only covers
                        # members, and the failed step may have opened it)
                        self.client.status(n.uri, timeout=2.0, probe=True)
                    except ClientError:
                        pass
                    if self._resize_abort.is_set():
                        raise _ResizeAborted()
        raise last

    def _broadcast_transfer_msg(self, nodes: List[Node], msg: dict) -> None:
        """Best-effort delivery of a transfer-plane teardown message to a
        node set (self handled locally); duplicates are deduped by id."""
        seen: set = set()
        for n in nodes:
            if n.id in seen:
                continue
            seen.add(n.id)
            if n.id == self.node.id:
                try:
                    self.api.receive_message(dict(msg))
                except Exception as e:  # noqa: BLE001 - teardown best-effort
                    self.logger(f"{msg.get('type')} locally: {e}")
                continue
            try:
                self.client.send_message(n.uri, msg, timeout=10.0)
            except ClientError as e:
                self.logger(f"{msg.get('type')} to {n.id}: {e}")

    def _send_status(
        self,
        to_nodes: List[Node],
        member_nodes: List[Node],
        replica_n: int,
        state: str,
        require: bool = False,
        retries: int = 3,
    ) -> List[str]:
        """Deliver a cluster-status to a node set (the RESIZING/NORMAL
        broadcasts of resizeJob.run), retrying and VERIFYING each member
        applied the state via /status (r2 advisor: a member that misses
        the RESIZING freeze keeps accepting writes while fragments move; a
        member that misses the NORMAL restore stays frozen forever).
        Returns the ids that never acknowledged; raises instead when
        `require` is set, so the resize job aborts and rolls back."""
        msg = {
            "type": "cluster-status",
            "nodes": [m.to_json() for m in member_nodes],
            "replicaN": replica_n,
            "state": state,
        }
        with self._status_mu:
            return self._send_status_locked(msg, to_nodes, require, retries)

    def _send_status_locked(
        self, msg: dict, to_nodes: List[Node], require: bool, retries: int
    ) -> List[str]:
        state = msg["state"]
        failed: List[str] = []
        for n in to_nodes:
            if n.id == self.node.id:
                self.apply_cluster_status(msg)
                continue
            ok = False
            last: Optional[Exception] = None
            for attempt in range(max(retries, 1)):
                try:
                    self.client.send_message(n.uri, msg, timeout=10.0)
                    st = self.client.status(n.uri, timeout=5.0)
                    if st.get("state") == state:
                        ok = True
                        break
                    last = ClientError(
                        f"applied state {st.get('state')!r}, want {state!r}"
                    )
                except ClientError as e:
                    last = e
                if attempt + 1 < max(retries, 1):
                    # shared policy's jittered backoff instead of the old
                    # ad-hoc 0.1*(attempt+1) ladder; no sleep after the
                    # final attempt — _status_mu is held here
                    time.sleep(self.retry_policy.backoff(attempt + 1))
            if not ok:
                failed.append(n.id)
                self.logger(
                    f"cluster-status {state} to {n.id} not acknowledged: {last}"
                )
        if require and failed:
            raise ClientError(
                f"cluster-status {state} not acknowledged by: {failed}"
            )
        return failed
