"""CLI: server / import / export / inspect / check / config subcommands.

Reference: /root/reference/cmd/ (cobra tree: root.go:28, server.go:60) and
ctl/ (ImportCommand csv pipeline ctl/import.go:82-392, ExportCommand
ctl/export.go:53, CheckCommand offline integrity ctl/check.go:47-133,
InspectCommand ctl/inspect.go:49, GenerateConfigCommand
ctl/generate_config.go:41). argparse instead of cobra/viper; same surface.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import urllib.request
from typing import List, Optional

from pilosa_tpu.cli.config import Config, parse_hosts


def _bool_flag(v: str) -> bool:
    """Explicit true/false flag value (for default-True knobs, where
    store_true could never express an override back to False). Anything
    unrecognized is a usage error — silently coercing a typo like
    'ture' to False would disable the knob with no diagnostic."""
    s = v.strip().lower()
    if s in ("1", "true", "yes", "on"):
        return True
    if s in ("0", "false", "no", "off"):
        return False
    raise argparse.ArgumentTypeError(
        f"expected true/false, got {v!r}"
    )


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="pilosa-tpu", description="TPU-native distributed bitmap index"
    )
    p.add_argument("--config", "-c", help="path to TOML config file")
    sub = p.add_subparsers(dest="command")

    sp = sub.add_parser("server", help="run a cluster node")
    sp.add_argument("--data-dir", "-d")
    sp.add_argument("--bind", "-b")
    sp.add_argument("--node-id")
    sp.add_argument("--log-path", help="append server log here (default stderr)")
    sp.add_argument(
        "--long-query-time", type=float,
        help="log queries slower than this many seconds (0 disables)",
    )
    sp.add_argument(
        "--max-writes-per-request", type=int,
        help="reject write batches larger than this",
    )
    sp.add_argument("--cluster-hosts", help="comma-separated id@uri entries")
    sp.add_argument("--replicas", type=int)
    sp.add_argument(
        "--coordinator", action="store_true", default=None,
        help="force this node to act as cluster coordinator",
    )
    sp.add_argument(
        "--probe-interval", type=float,
        help="coordinator liveness-probe ticker seconds (0 disables)",
    )
    sp.add_argument("--anti-entropy-interval", type=float)
    sp.add_argument(
        "--metric-service",
        help="metrics backend: none | expvar | prometheus | statsd",
    )
    sp.add_argument("--metric-host", help="statsd daemon host:port")
    sp.add_argument(
        "--metric-poll-interval", type=float,
        help="runtime-gauge sampling ticker seconds (0 disables)",
    )
    sp.add_argument(
        "--tracing-enabled", action="store_true", default=None,
        help="record spans for incoming queries",
    )
    sp.add_argument(
        "--tracing-sample-rate", type=float,
        help="fraction of queries traced when tracing is enabled",
    )
    sp.add_argument(
        "--tracing-ring", type=int,
        help="spans kept in the per-node flight-recorder ring "
        "(/debug/traces)",
    )
    sp.add_argument(
        "--telemetry-sample-interval", type=float,
        help="utilization-timeline sampler tick seconds (each tick also "
        "refreshes the residency gauges; 0 disables the sampler)",
    )
    sp.add_argument(
        "--telemetry-ring", type=int,
        help="utilization samples kept in the per-node /debug/timeline "
        "ring",
    )
    sp.add_argument(
        "--retry-max-attempts", type=int,
        help="internode RPC attempts within one deadline budget",
    )
    sp.add_argument(
        "--retry-base-backoff", type=float,
        help="seconds before the first internode retry (doubles per retry)",
    )
    sp.add_argument(
        "--breaker-threshold", type=int,
        help="consecutive failures before a peer's circuit opens",
    )
    sp.add_argument(
        "--breaker-cooldown", type=float,
        help="seconds a circuit stays open before a half-open probe",
    )
    sp.add_argument(
        "--query-deadline", type=float,
        help="wall-clock bound on one distributed query fan-out, seconds",
    )
    sp.add_argument(
        "--max-concurrent-queries", type=int,
        help="queries executing at once; extra queries queue (0 disables "
        "admission control)",
    )
    sp.add_argument(
        "--admission-queue-depth", type=int,
        help="waiting queries before load shedding replies 429",
    )
    sp.add_argument(
        "--admission-byte-budget", type=int,
        help="in-flight estimated device bytes before queries queue "
        "(0 = follow the HBM devcache budget)",
    )
    sp.add_argument(
        "--admission-default-class",
        choices=["interactive", "batch", "internal"],
        help="priority class for queries without an X-Pilosa-Priority "
        "header",
    )
    sp.add_argument(
        "--shed-retry-after", type=float,
        help="Retry-After seconds sent with 429 load-shed responses",
    )
    sp.add_argument(
        "--tenants-default-qps", type=float,
        help="per-index query-rate limit, queries/second (token bucket "
        "with a one-second burst; 0 disables)",
    )
    sp.add_argument(
        "--tenants-default-bytes-per-s", type=float,
        help="per-index device-byte rate limit priced by the admission "
        "cost estimator, bytes/second (0 disables)",
    )
    sp.add_argument(
        "--tenants-default-inflight-bytes", type=int,
        help="per-index cap on estimated device bytes in flight at once "
        "(0 disables)",
    )
    sp.add_argument(
        "--tenants-default-hbm-bytes", type=int,
        help="per-index HBM devcache residency quota; eviction pressure "
        "lands on over-quota indexes first (0 disables)",
    )
    sp.add_argument(
        "--tenants-default-cache-bytes", type=int,
        help="per-index result-cache byte quota (0 disables)",
    )
    sp.add_argument(
        "--tenants-overrides", nargs="*",
        help="per-index limit overrides, one entry per index: "
        "'idx:qps=5;bytes-per-s=1e6;hbm-bytes=65536' (semicolon-joined "
        "key=value pairs; keys: qps, bytes-per-s, inflight-bytes, "
        "hbm-bytes, cache-bytes)",
    )
    sp.add_argument(
        "--hbm-extent-rows", type=int,
        help="shards per HBM operand extent — the paging granularity "
        "under memory pressure (0 stages whole stacks monolithically)",
    )
    sp.add_argument(
        "--hbm-prefetch-depth", type=int,
        help="queued warm tasks the background extent prefetcher holds "
        "(0 disables prefetching)",
    )
    sp.add_argument(
        "--hbm-pin-timeout", type=float,
        help="seconds before a leaked extent pin is forcibly released "
        "(safety valve; 0 disables)",
    )
    sp.add_argument(
        "--bsi-slab-planes", type=int,
        help="magnitude planes per compiled dispatch for plane-streamed "
        "BSI aggregates (Sum/Min/Max/Range counts): peak plane "
        "residency stays slab-sized however deep the field "
        "(<= 0 restores the default)",
    )
    sp.add_argument(
        "--import-concurrency", type=int,
        help="parallel replica-import RPCs per bulk import call (shard "
        "batches ship to their owner nodes on a pool this wide)",
    )
    sp.add_argument(
        "--merge-device-threshold", type=int,
        help="staged positions per read-barrier burst at which the "
        "cross-fragment deferred-delta merge dispatches the device "
        "program instead of the vectorized host pass (<0 never, "
        "0 always; unset = backend auto — 65536 on an accelerator, "
        "never on the CPU backend)",
    )
    sp.add_argument(
        "--wal-sync-interval", type=float,
        help="WAL group-commit fsync cadence, seconds: 0 = strict (every "
        "commit group fsyncs before any caller returns), > 0 = bounded-"
        "loss mode (callers return after the buffered write; a "
        "background syncer fsyncs on this interval — the crash loss "
        "window)",
    )
    sp.add_argument(
        "--mesh-group",
        help="ICI domain id of this node: nodes sharing a non-empty group "
        "execute mesh-local queries as one compiled sharded program "
        "instead of per-node HTTP legs (empty disables)",
    )
    sp.add_argument(
        "--cache-result-mb", type=int,
        help="versioned result cache LRU byte budget in MB — repeat "
        "Count/TopN/GroupBy queries revalidate against fragment "
        "versions and serve from host memory with zero dispatches "
        "(0 disables)",
    )
    sp.add_argument(
        "--cache-count-repair", type=_bool_flag,
        help="patch cached Counts in place from the merge barrier's "
        "word deltas after set-only staged bursts instead of "
        "recomputing (true/false)",
    )
    sp.add_argument(
        "--mesh-min-nodes", type=int,
        help="group-local owner nodes a fan-out must span before the "
        "mesh-group fold engages (0 disables mesh-local execution)",
    )
    sp.add_argument(
        "--mesh-ici-gbps", type=float,
        help="assumed intra-group (ICI) collective bandwidth, GB/s, for "
        "admission's collective-cost terms",
    )
    sp.add_argument(
        "--mesh-dcn-gbps", type=float,
        help="assumed cross-group (HTTP/DCN) bandwidth, GB/s, for "
        "admission's collective-cost terms",
    )
    sp.add_argument(
        "--resize-transfer-concurrency", type=int,
        help="parallel fragment transfer legs per node during a "
        "streaming resize",
    )
    sp.add_argument(
        "--resize-cutover-timeout", type=float,
        help="wall-clock bound on a resize step's delta catch-up barrier, "
        "seconds",
    )
    sp.add_argument(
        "--resize-resume-policy", choices=["resume", "abort"],
        help="on a failed resize transfer leg: 'resume' retries once from "
        "the per-fragment transfer ledger, 'abort' rolls the job back "
        "immediately",
    )
    sp.add_argument(
        "--tier-store-path",
        help="shared object-store directory for tiered storage — idle "
        "fragments demote to immutable snapshot objects there and "
        "hydrate on demand (empty disables the tier plane)",
    )
    sp.add_argument(
        "--tier-placement", choices=["hot", "warm", "cold"],
        help="default fragment placement: hot (host + device), warm "
        "(host only, device residency shed when idle), cold (demoted "
        "to the object store when idle)",
    )
    sp.add_argument(
        "--tier-overrides", nargs="*",
        help="per-index placement overrides, one entry per index: "
        "'idx:placement=cold'",
    )
    sp.add_argument(
        "--tier-demote-after", type=float,
        help="idle seconds before a cold-placement fragment demotes to "
        "the object store",
    )
    sp.add_argument(
        "--tier-host-budget-bytes", type=int,
        help="local snapshot+WAL byte budget; beyond it the tier ticker "
        "demotes least-recently-used fragments regardless of idle time "
        "(0 = unlimited)",
    )
    sp.add_argument(
        "--tier-fetch-concurrency", type=int,
        help="concurrent object-store transfers per node (demote "
        "uploads + hydration fetches share the bound)",
    )
    sp.add_argument(
        "--coherence-lease-duration", type=float,
        help="coherence lease bound, seconds: peers holding a lease serve "
        "fan-out warm hits from pushed version mirrors with zero "
        "version RTTs; on publisher death/partition staleness is "
        "bounded by this window before falling back to revalidation "
        "(0 disables leases)",
    )
    sp.add_argument(
        "--coherence-publish-batch-ms", type=float,
        help="invalidation publish batching window, milliseconds — "
        "version-vector bumps funnel through merge-barrier/stage-bulk "
        "and ship to lease holders at this cadence",
    )
    sp.add_argument(
        "--coherence-max-subscriptions", type=int,
        help="live query subscriptions per node; registration beyond the "
        "cap sheds with 429 (0 disables subscriptions)",
    )
    sp.add_argument(
        "--coherence-sub-poll-interval", type=float,
        help="fallback re-check cadence, seconds, for subscription "
        "results whose queries fall outside push invalidation coverage",
    )
    sp.add_argument(
        "--join",
        help="coordinator URI to join on boot (self-registers and waits for "
        "the resize job; the listenForJoins role, cluster.go:1141)",
    )
    sp.add_argument("--verbose", action="store_true", default=None)
    sp.add_argument("--tls-certificate", help="PEM cert chain; serve HTTPS")
    sp.add_argument("--tls-key", help="PEM private key for --tls-certificate")
    sp.add_argument(
        "--tls-skip-verify",
        action="store_true",
        default=None,
        help="internode client trusts any peer certificate (self-signed)",
    )
    sp.add_argument(
        "--tls-ca-certificate",
        help="internode client verifies peers against this CA bundle",
    )

    ip = sub.add_parser("import", help="bulk-import CSV rows (row,col[,ts])")
    ip.add_argument("--host", default="http://localhost:10101")
    ip.add_argument("--index", "-i", required=True)
    ip.add_argument("--field", "-f", required=True)
    ip.add_argument("--batch-size", type=int, default=100_000)
    ip.add_argument("--clear", action="store_true")
    ip.add_argument("--create", action="store_true", help="create index/field")
    ip.add_argument("--field-type", default="set")
    ip.add_argument("--field-keys", action="store_true")
    ip.add_argument("--index-keys", action="store_true")
    ip.add_argument("paths", nargs="*", help="CSV files ('-' or empty = stdin)")

    ep = sub.add_parser("export", help="export a field as CSV")
    ep.add_argument("--host", default="http://localhost:10101")
    ep.add_argument("--index", "-i", required=True)
    ep.add_argument("--field", "-f", required=True)
    ep.add_argument("--output", "-o", help="output path (default stdout)")

    np_ = sub.add_parser("inspect", help="dump fragment info from a data dir")
    np_.add_argument("data_dir")
    np_.add_argument("--index")
    np_.add_argument("--field")

    cp = sub.add_parser("check", help="offline integrity check of data files")
    cp.add_argument("paths", nargs="+", help=".snap / .wal files or data dirs")

    sub.add_parser("config", help="print the effective configuration")
    sub.add_parser("generate-config", help="print default configuration")
    return p


# argparse dest -> (section, knob) for every server flag that overrides a
# Config field; None section means a flat Config field. The api-invariants
# pass checks this stays in sync with cli/config.py's dataclasses.
_FLAG_KNOBS = {
    "data_dir": (None, "data_dir"),
    "bind": (None, "bind"),
    "node_id": (None, "node_id"),
    "log_path": (None, "log_path"),
    "verbose": (None, "verbose"),
    "long_query_time": (None, "long_query_time"),
    "max_writes_per_request": (None, "max_writes_per_request"),
    "import_concurrency": (None, "import_concurrency"),
    "cluster_hosts": ("cluster", "hosts"),
    "replicas": ("cluster", "replicas"),
    "coordinator": ("cluster", "coordinator"),
    "probe_interval": ("cluster", "probe_interval"),
    "retry_max_attempts": ("cluster", "retry_max_attempts"),
    "retry_base_backoff": ("cluster", "retry_base_backoff"),
    "breaker_threshold": ("cluster", "breaker_threshold"),
    "breaker_cooldown": ("cluster", "breaker_cooldown"),
    "query_deadline": ("cluster", "query_deadline"),
    "max_concurrent_queries": ("sched", "max_concurrent_queries"),
    "admission_queue_depth": ("sched", "admission_queue_depth"),
    "admission_byte_budget": ("sched", "admission_byte_budget"),
    "admission_default_class": ("sched", "admission_default_class"),
    "shed_retry_after": ("sched", "shed_retry_after"),
    "tenants_default_qps": ("tenants", "default_qps"),
    "tenants_default_bytes_per_s": ("tenants", "default_bytes_per_s"),
    "tenants_default_inflight_bytes": ("tenants", "default_inflight_bytes"),
    "tenants_default_hbm_bytes": ("tenants", "default_hbm_bytes"),
    "tenants_default_cache_bytes": ("tenants", "default_cache_bytes"),
    "tenants_overrides": ("tenants", "overrides"),
    "hbm_extent_rows": ("hbm", "extent_rows"),
    "hbm_prefetch_depth": ("hbm", "prefetch_depth"),
    "hbm_pin_timeout": ("hbm", "pin_timeout"),
    "bsi_slab_planes": ("bsi", "slab_planes"),
    "merge_device_threshold": ("ingest", "merge_device_threshold"),
    "wal_sync_interval": ("wal", "sync_interval"),
    "mesh_group": ("mesh", "group"),
    "mesh_min_nodes": ("mesh", "min_nodes"),
    "cache_result_mb": ("cache", "result_mb"),
    "cache_count_repair": ("cache", "count_repair"),
    "mesh_ici_gbps": ("mesh", "ici_gbps"),
    "mesh_dcn_gbps": ("mesh", "dcn_gbps"),
    "resize_transfer_concurrency": ("resize", "transfer_concurrency"),
    "resize_cutover_timeout": ("resize", "cutover_timeout"),
    "resize_resume_policy": ("resize", "resume_policy"),
    "tier_store_path": ("tier", "store_path"),
    "tier_placement": ("tier", "placement"),
    "tier_overrides": ("tier", "overrides"),
    "tier_demote_after": ("tier", "demote_after"),
    "tier_host_budget_bytes": ("tier", "host_budget_bytes"),
    "tier_fetch_concurrency": ("tier", "fetch_concurrency"),
    "coherence_lease_duration": ("coherence", "lease_duration"),
    "coherence_publish_batch_ms": ("coherence", "publish_batch_ms"),
    "coherence_max_subscriptions": ("coherence", "max_subscriptions"),
    "coherence_sub_poll_interval": ("coherence", "sub_poll_interval"),
    "anti_entropy_interval": ("anti_entropy", "interval"),
    "metric_service": ("metric", "service"),
    "metric_host": ("metric", "host"),
    "metric_poll_interval": ("metric", "poll_interval"),
    "tracing_enabled": ("tracing", "enabled"),
    "tracing_sample_rate": ("tracing", "sample_rate"),
    "tracing_ring": ("tracing", "ring"),
    "telemetry_sample_interval": ("telemetry", "sample_interval"),
    "telemetry_ring": ("telemetry", "ring"),
    "tls_certificate": ("tls", "certificate"),
    "tls_key": ("tls", "key"),
    "tls_skip_verify": ("tls", "skip_verify"),
    "tls_ca_certificate": ("tls", "ca_certificate"),
}


def _load_config(args) -> Config:
    overrides: dict = {}
    for dest, (section, knob) in _FLAG_KNOBS.items():
        v = getattr(args, dest, None)
        if v is None:
            continue
        if section is None:
            overrides[knob] = v
        else:
            overrides.setdefault(section, {})[knob] = v
    return Config.load(path=args.config, overrides=overrides)


# ---------------------------------------------------------------------------
# subcommands
# ---------------------------------------------------------------------------


def _scheme(cfg: Config) -> str:
    """URI scheme this node serves on (TLS flips the whole plane to https,
    including the id derivation from --cluster-hosts entries)."""
    return "https" if cfg.tls.certificate else "http"


def _join_on_boot(
    srv,
    coordinator_uri: str,
    timeout: float = 180.0,
    clock=None,
    wake=None,
) -> None:
    """Self-register with the coordinator and wait until this node is an
    active member (reference: gossip join -> listenForJoins -> resize job,
    cluster.go:1141,1796). Retries while the coordinator is busy with
    another resize — concurrent joins serialize on the coordinator's
    one-job-at-a-time rule.

    `clock` (monotonic-seconds callable) and `wake` (Event-like; `.wait(t)`
    bounds each poll step and an external `.set()` wakes the loop
    immediately) are injectable so tests drive the loop on a virtual clock
    instead of racing wall-time sleeps."""
    import threading
    import time

    from pilosa_tpu.server.client import ClientError

    if clock is None:
        clock = time.monotonic
    if wake is None:
        wake = threading.Event()
    payload = {
        "id": srv.node.id,
        "uri": srv.node.uri,
        # the joiner's ICI-domain declaration rides the join so the
        # post-resize topology carries its mesh-group membership
        "meshGroup": srv.mesh_group_name,
    }
    deadline = clock() + timeout
    registered_at: Optional[float] = None
    while clock() < deadline:
        if registered_at is None:
            try:
                srv.client.join_cluster(coordinator_uri, payload)
                registered_at = clock()
            except ClientError as e:
                # coordinator busy (a resize job is already running) or not
                # up yet: back off and retry
                print(f"join: waiting for coordinator: {e}", file=sys.stderr)
                wake.wait(1.0)
                continue
        elif len(srv.cluster.nodes) <= 1 and clock() - registered_at > 10.0:
            # the join resize aborted and rolled us back to a solo
            # cluster: re-register rather than idling out the deadline
            print("join: resize rolled back; re-registering", file=sys.stderr)
            registered_at = None
            continue
        if (
            len(srv.cluster.nodes) > 1
            and any(n.id == srv.node.id for n in srv.cluster.nodes)
            and srv.state == "NORMAL"
        ):
            print(
                f"joined cluster of {len(srv.cluster.nodes)} nodes via "
                f"{coordinator_uri}",
                file=sys.stderr,
            )
            return
        wake.wait(0.2)
    raise SystemExit(f"join via {coordinator_uri} did not complete in {timeout}s")


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.
    Where JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and no
    directory is set here; otherwise the cache lives at the fixed
    `<checkout>/.jax_cache` beside the package (the path is part of the
    cache key, so it must not move between runs). A served path compiles
    one plan per query shape, most in well under JAX's default one-second
    floor for persisting an entry, so the floor is dropped."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import pilosa_tpu

        checkout = os.path.dirname(
            os.path.dirname(os.path.abspath(pilosa_tpu.__file__))
        )
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(checkout, ".jax_cache")
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def cmd_server(cfg: Config, wait: bool = True, join: Optional[str] = None):
    from pilosa_tpu import native
    from pilosa_tpu.cluster.topology import Node
    from pilosa_tpu.parallel.mesh import device_report
    from pilosa_tpu.server.node import NodeServer

    data_dir = os.path.expanduser(cfg.data_dir) if cfg.data_dir else None
    hosts = parse_hosts(cfg.cluster.hosts, default_scheme=_scheme(cfg))
    node_id = cfg.node_id
    if not node_id:
        # derive the same id parse_hosts would give this bind address, so a
        # '--cluster-hosts host:port,...' entry naming us matches our id
        my_uri = cfg.bind if cfg.bind.startswith("http") else f"{_scheme(cfg)}://{cfg.bind}"
        matched = [nid for nid, uri in hosts if uri == my_uri]
        node_id = matched[0] if matched else cfg.bind.replace(":", "-")
    from pilosa_tpu.utils.logger import new_logger

    log_stream = open(cfg.log_path, "a") if cfg.log_path else None
    srv = NodeServer(
        data_dir,
        node_id,
        bind=cfg.bind,
        replica_n=cfg.cluster.replicas,
        anti_entropy_interval=cfg.anti_entropy.interval,
        probe_interval=cfg.cluster.probe_interval,
        retry_max_attempts=cfg.cluster.retry_max_attempts,
        retry_base_backoff=cfg.cluster.retry_base_backoff,
        breaker_threshold=cfg.cluster.breaker_threshold,
        breaker_cooldown=cfg.cluster.breaker_cooldown,
        query_deadline=cfg.cluster.query_deadline,
        max_concurrent_queries=cfg.sched.max_concurrent_queries,
        admission_queue_depth=cfg.sched.admission_queue_depth,
        admission_byte_budget=cfg.sched.admission_byte_budget,
        admission_default_class=cfg.sched.admission_default_class,
        shed_retry_after=cfg.sched.shed_retry_after,
        tenant_default_qps=cfg.tenants.default_qps,
        tenant_default_bytes_per_s=cfg.tenants.default_bytes_per_s,
        tenant_default_inflight_bytes=cfg.tenants.default_inflight_bytes,
        tenant_default_hbm_bytes=cfg.tenants.default_hbm_bytes,
        tenant_default_cache_bytes=cfg.tenants.default_cache_bytes,
        tenant_overrides=cfg.tenants.overrides,
        hbm_extent_rows=cfg.hbm.extent_rows,
        hbm_prefetch_depth=cfg.hbm.prefetch_depth,
        hbm_pin_timeout=cfg.hbm.pin_timeout,
        bsi_slab_planes=cfg.bsi.slab_planes,
        merge_device_threshold=cfg.ingest.merge_device_threshold,
        wal_sync_interval=cfg.wal.sync_interval,
        mesh_group=cfg.mesh.group,
        mesh_min_nodes=cfg.mesh.min_nodes,
        mesh_ici_gbps=cfg.mesh.ici_gbps,
        mesh_dcn_gbps=cfg.mesh.dcn_gbps,
        cache_result_mb=cfg.cache.result_mb,
        cache_count_repair=cfg.cache.count_repair,
        import_concurrency=cfg.import_concurrency,
        max_writes_per_request=cfg.max_writes_per_request,
        resize_transfer_concurrency=cfg.resize.transfer_concurrency,
        resize_cutover_timeout=cfg.resize.cutover_timeout,
        resize_resume_policy=cfg.resize.resume_policy,
        tier_store_path=os.path.expanduser(cfg.tier.store_path) if cfg.tier.store_path else "",
        tier_placement=cfg.tier.placement,
        tier_overrides=cfg.tier.overrides,
        tier_demote_after=cfg.tier.demote_after,
        tier_host_budget_bytes=cfg.tier.host_budget_bytes,
        tier_fetch_concurrency=cfg.tier.fetch_concurrency,
        coherence_lease_duration=cfg.coherence.lease_duration,
        coherence_publish_batch_ms=cfg.coherence.publish_batch_ms,
        coherence_max_subscriptions=cfg.coherence.max_subscriptions,
        coherence_sub_poll_interval=cfg.coherence.sub_poll_interval,
        stats_service=cfg.metric.service,
        stats_host=cfg.metric.host,
        metric_poll_interval=cfg.metric.poll_interval,
        tracing_enabled=cfg.tracing.enabled,
        trace_sample_rate=cfg.tracing.sample_rate,
        trace_ring=cfg.tracing.ring,
        telemetry_sample_interval=cfg.telemetry.sample_interval,
        telemetry_ring=cfg.telemetry.ring,
        long_query_time=cfg.long_query_time,
        logger=new_logger(verbose=cfg.verbose, stream=log_stream),
        tls_cert=os.path.expanduser(cfg.tls.certificate) if cfg.tls.certificate else "",
        tls_key=os.path.expanduser(cfg.tls.key) if cfg.tls.key else "",
        tls_skip_verify=cfg.tls.skip_verify,
        tls_ca_cert=os.path.expanduser(cfg.tls.ca_certificate) if cfg.tls.ca_certificate else "",
    )
    cache_dir = configure_compile_cache()
    srv.start()
    # static --cluster-hosts flags SEED a cluster; once membership is on
    # disk (.topology, written whenever a multi-node topology installs),
    # disk wins on reboot (cluster.go:1657-1692) — otherwise a restart
    # would silently revert a resized cluster to its stale launch config
    # and strand the re-placed fragments. Flags still HEAL peer URIs: the
    # membership (ids/coordinator/replicaN) comes from disk, but an
    # operator who moved a peer to a new address updates it via flags
    # (the reference re-learns URIs through gossip; static flags are our
    # address channel).
    if srv.topology_restored:
        if hosts:
            healed = srv.heal_peer_uris(hosts)
            print(
                "cluster-hosts: membership restored from .topology"
                + (f"; healed URIs for {healed}" if healed else ""),
                file=sys.stderr,
            )
    elif hosts:
        my_uri = cfg.bind if cfg.bind.startswith("http") else f"{_scheme(cfg)}://{cfg.bind}"
        members = []
        for nid, uri in hosts:
            if uri == my_uri and nid != srv.node.id:
                # the entry naming THIS address keeps the durable .id —
                # two members with one URI would give placement a phantom
                # owner no server identifies as
                print(
                    f"cluster-hosts id {nid!r} for this address overridden "
                    f"by on-disk .id {srv.node.id!r}",
                    file=sys.stderr,
                )
                nid = srv.node.id
            members.append(Node(id=nid, uri=uri))
        if not any(m.id == srv.node.id for m in members):
            members.append(Node(id=srv.node.id, uri=srv.node.uri))
        members[0].is_coordinator = True
        srv.set_topology(members, replica_n=cfg.cluster.replicas)
    if join:
        if srv.topology_restored:
            print(
                f"--join {join} ignored: membership restored from .topology "
                f"(remove {srv._topology_path} to join a different cluster)",
                file=sys.stderr,
            )
        else:
            _join_on_boot(srv, join)
    devices = device_report()
    print(
        f"pilosa-tpu node {srv.node.id} listening on {srv.node.uri}"
        f" platform={devices[0]['platform']}"
        f" device_kind={devices[0]['deviceKind']!r}"
        f" devices={len(devices)}"
        f" native={'on' if native.available() else 'off'}"
        f" compile_cache={cache_dir}",
        file=sys.stderr,
    )
    if wait:
        stop = []
        signal.signal(signal.SIGINT, lambda *a: stop.append(1))
        signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
        try:
            while not stop:
                signal.pause()
        finally:
            srv.stop()
    return srv


def _iter_csv_rows(paths: List[str]):
    files = paths or ["-"]
    for path in files:
        fh = sys.stdin if path == "-" else open(path)
        try:
            for line in fh:
                line = line.strip()
                if not line or line.startswith("#"):
                    continue
                parts = line.split(",")
                if len(parts) < 2:
                    raise ValueError(f"bad csv line: {line!r}")
                yield parts[0], parts[1], (parts[2] if len(parts) > 2 else None)
        finally:
            if path != "-":
                fh.close()


def _post_json(url: str, body: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        raw = resp.read()
    return json.loads(raw) if raw else {}


def cmd_import(args) -> int:
    def maybe_int(s):
        try:
            return int(s)
        except ValueError:
            return s  # string key

    if args.create:
        _post_json(
            f"{args.host}/index/{args.index}",
            {"options": {"keys": args.index_keys}},
        )
        _post_json(
            f"{args.host}/index/{args.index}/field/{args.field}",
            {"options": {"type": args.field_type, "keys": args.field_keys}},
        )
    batch_rows, batch_cols, batch_ts, n = [], [], [], 0
    is_value = args.field_type == "int"

    def flush():
        nonlocal batch_rows, batch_cols, batch_ts
        if not batch_cols:
            return
        if is_value:
            _post_json(
                f"{args.host}/index/{args.index}/field/{args.field}/import-value",
                {"cols": batch_cols, "values": [int(r) for r in batch_rows]},
            )
        else:
            body = {"rows": batch_rows, "cols": batch_cols}
            if any(t is not None for t in batch_ts):
                body["timestamps"] = batch_ts
            if args.clear:
                body["clear"] = True
            _post_json(
                f"{args.host}/index/{args.index}/field/{args.field}/import", body
            )
        batch_rows, batch_cols, batch_ts = [], [], []

    for row, col, ts in _iter_csv_rows(args.paths):
        batch_rows.append(maybe_int(row))
        batch_cols.append(maybe_int(col))
        batch_ts.append(ts)
        n += 1
        if len(batch_cols) >= args.batch_size:
            flush()
    flush()
    print(f"imported {n} records", file=sys.stderr)
    return 0


def cmd_export(args) -> int:
    url = f"{args.host}/export?index={args.index}&field={args.field}"
    with urllib.request.urlopen(url, timeout=120) as resp:
        data = resp.read()
    if args.output:
        with open(args.output, "wb") as f:
            f.write(data)
    else:
        sys.stdout.write(data.decode())
    return 0


def cmd_inspect(args) -> int:
    from pilosa_tpu.core.holder import Holder

    h = Holder(args.data_dir).open()
    try:
        for idx in h.indexes():
            if args.index and idx.name != args.index:
                continue
            for f in idx.fields(include_hidden=True):
                if args.field and f.name != args.field:
                    continue
                for vname, v in f.views.items():
                    for shard in sorted(v.fragments):
                        frag = v.fragments[shard]
                        rows, _ = frag.pairs()
                        n_rows = len(frag.row_ids())
                        print(
                            f"{idx.name}/{f.name}/{vname}/shard={shard}: "
                            f"rows={n_rows} bits={len(rows)} op_n={frag._op_n}"
                        )
    finally:
        h.close()
    return 0


def cmd_check(paths: List[str]) -> int:
    """Offline integrity check (reference: ctl/check.go:47-133)."""
    from pilosa_tpu.core import wal as walmod

    failed = 0
    todo: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, _, files in os.walk(p):
                todo.extend(
                    os.path.join(root, fn)
                    for fn in files
                    if fn.endswith((".snap", ".wal", ".bitmap", ".roaring"))
                )
        else:
            todo.append(p)
    for p in todo:
        try:
            if p.endswith(".snap"):
                shard, n_bits, rows = walmod.read_snapshot(p)
                total = sum(rb.count() for rb in rows.values())
                print(f"{p}: ok shard={shard} rows={len(rows)} bits={total}")
            elif p.endswith(".wal"):
                n_ops, status, detail = walmod.check_wal(p)
                if status == "corrupt":
                    raise ValueError(f"{detail} (after {n_ops} valid ops)")
                note = f" ({detail}, discarded on replay)" if status == "torn" else ""
                print(f"{p}: ok ops={n_ops}{note}")
            elif p.endswith((".bitmap", ".roaring")):
                # reference-format roaring files (ctl/check.go checks .bitmap)
                from pilosa_tpu.core import roaring_io

                with open(p, "rb") as fh:
                    info = roaring_io.inspect(fh.read())
                print(
                    f"{p}: ok dialect={info['dialect']} bits={info['bit_count']} "
                    f"max={info['max_position']}"
                )
            else:
                print(f"{p}: skipped (unknown extension)")
        except Exception as e:
            print(f"{p}: CORRUPT: {e}")
            failed += 1
    return 1 if failed else 0


def main(argv: Optional[List[str]] = None) -> int:
    parser = _build_parser()
    args = parser.parse_args(argv)
    if not args.command:
        parser.print_help()
        return 2
    if args.command == "server":
        cmd_server(_load_config(args), join=getattr(args, "join", None))
        return 0
    if args.command == "import":
        return cmd_import(args)
    if args.command == "export":
        return cmd_export(args)
    if args.command == "inspect":
        return cmd_inspect(args)
    if args.command == "check":
        return cmd_check(args.paths)
    if args.command == "config":
        sys.stdout.write(_load_config(args).to_toml())
        return 0
    if args.command == "generate-config":
        sys.stdout.write(Config().to_toml())
        return 0
    parser.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
