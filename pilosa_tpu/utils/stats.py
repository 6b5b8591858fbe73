"""Metrics: StatsClient interface + registry with expvar/prometheus views
and a real statsd (DogStatsD) UDP push client.

Reference: stats/stats.go:31-64 StatsClient (Count/Gauge/Histogram/Set/
Timing, WithTags child clients), chosen by config `metric.service`:
expvar (default), prometheus (served at /metrics, prometheus/prometheus.go),
statsd (DataDog, statsd/statsd.go:48), none. Tagged per-index/field
children are used throughout the hot paths (fragment.go stats,
executor.go:295).

Here one thread-safe Registry backs the scrape views: /debug/vars renders
it as expvar-style JSON, /metrics renders prometheus text. `statsd`
additionally pushes DogStatsD datagrams over UDP to metric.host
(fire-and-forget, best-effort — a down daemon never blocks a query),
while still feeding the registry so the scrape endpoints keep working.
`none` selects the no-op client.
"""

from __future__ import annotations

import bisect
import socket
import time
from collections import defaultdict, deque
from typing import Dict, Iterable, List, Optional, Tuple

from pilosa_tpu.utils.locks import TrackedLock

# ---------------------------------------------------------------------------
# Metric-name registry. Every stat name the package emits MUST be declared
# here (the api-invariants AST pass in pilosa_tpu/analysis/ rejects
# emissions of undeclared literals, and flags declared-but-never-emitted
# names as stale). This is the single place to look up what the server can
# report, and it keeps dashboards/alerts from silently referencing metrics
# that a refactor renamed away.
# ---------------------------------------------------------------------------

STAT_NAMES = frozenset(
    {
        # query path (server/api.py)
        "query_n",
        "query_ms",
        # distributed writes (exec/distributed.py, server/api.py)
        "write_replica_dropped",
        # bulk ingest (server/api.py import endpoints): bits and shard
        # batches accepted, local apply vs replica routing latency
        "ingest.bits",
        "ingest.batches",
        "ingest.apply_ms",
        "ingest.route_ms",
        # internode fault tolerance (server/client.py)
        "internode.retry",
        "internode.breaker_fastfail",
        # background tickers (server/node.py)
        "ticker.error",
        # runtime gauges (server/node.py monitorRuntime analog)
        "runtime.max_rss_kb",
        "runtime.threads",
        "runtime.open_files",
        # query admission control & QoS (sched/admission.py); admit/shed/
        # wait series carry "class:<interactive|batch|internal>" and
        # "index:<name>" tags (index "-" when the request is not bound to
        # one, e.g. resize transfer serving)
        "sched.queue_depth",
        "sched.inflight",
        "sched.inflight_bytes",
        "sched.index_inflight_bytes",
        "sched.admit",
        "sched.shed",
        "sched.wait_ms",
        # cross-request count batching (exec/batcher.py): calls merged
        # into each executed round; then the batcher's own counts of
        # rounds led, requests that rode another's round, merged
        # executions and merges split after an error (batcher.STATS,
        # published at scrape time by publish_cache_gauges)
        "batcher.batch_size",
        "batcher.leader",
        "batcher.batched",
        "batcher.merged_execs",
        "batcher.fallback_splits",
        # compiled dispatches (exec/plan.py, published at scrape time,
        # process-global like the hbm.* gauges): dispatches and blocking
        # device->host reads (plan.STATS), and what jax.monitoring tells
        # the listeners plan.py registers — compile requests that reached
        # the backend (compiles; compile_ms their wall time) and how many
        # of those the persistent cache answered (compile_cache_hits;
        # compiles - compile_cache_hits were compiled anew). A steady
        # state compiles nothing: exec.compiles that rises with traffic
        # is a program compiled per request shape
        "exec.dispatches",
        "exec.host_reads",
        "exec.compiles",
        "exec.compile_ms",
        "exec.compile_cache_hits",
        # cross tallies of GroupBy and filtered TopN (exec/groupby.py
        # cross_tally, published at scrape time): how many ran as the VMEM
        # kernel (stacks on one TPU) and how many as the XLA program (any
        # other backend, mesh-sharded stacks)
        "groupby.kernel_tallies",
        "groupby.xla_tallies",
        # of the kernel's tallies, those that read operands staged as more
        # than one extent in place; and operands whose extents were
        # concatenated into one stack for a tally (the XLA program, the
        # pruned descent, more than three levels)
        "groupby.inplace_tallies",
        "groupby.assembled_stacks",
        # bytes those concatenations wrote (it moves wherever
        # assembled_stacks does); GroupBys that carried aggregate=Sum and
        # took the device path, and the group x plane pairs their tallies
        # counted (exec/groupby.py group_by_aggregate)
        "groupby.assembled_bytes",
        "groupby.aggregate_queries",
        "groupby.plane_tallies",
        # per-view row summary (core/view.py row_summary, counted in the
        # process registry and published at scrape time): readers that
        # found the table under the view's current mutation clock, tables
        # built from nothing, shards read again after a clock mismatch,
        # and Rows / unfiltered TopN calls that kept the per-fragment walk
        # (exec/executor.py)
        "rowsummary.hits",
        "rowsummary.rebuilds",
        "rowsummary.refreshed_shards",
        "rowsummary.bypassed",
        # device-cache residency (core/devcache.py, refreshed at scrape
        # time by server/node.py publish_cache_gauges)
        "devcache.resident_bytes",
        "devcache.entries",
        "devcache.evictions",
        "devcache.hits",
        "devcache.misses",
        # HBM residency manager (pilosa_tpu/hbm/): extent-granular paging,
        # pinning and prefetch gauges, refreshed at scrape time alongside
        # the devcache gauges. resident/restage bytes are attributed per
        # owner index ("index:" label; "-" collects entries staged outside
        # any index); the sum over labels equals the global ledger.
        "hbm.resident_extents",
        "hbm.pinned_bytes",
        "hbm.resident_bytes",
        "hbm.restage_bytes",
        "hbm.prefetch_hits",
        # in-place device-side extent patches (core/view.py merge-barrier
        # reconciliation): writes that kept their covering extent resident
        # instead of forcing an invalidate + PCIe re-stage.
        # extent_patch_batches counts the batched gather|OR|scatter ops
        # issued — one per patched entry per 256 dirty delta blocks,
        # never one per shard (a smeared burst's cascade is O(entries)
        # device ops, not O(dirty shards))
        "hbm.extent_patches",
        "hbm.extent_patch_batches",
        # plane-streamed BSI aggregates (exec/bsistream.py, refreshed at
        # scrape/sampler time): plane slabs staged, cumulative slab
        # operand bytes, and compiled dispatches issued by the streamed
        # path — a depth <= slab field answers one dispatch per query
        # chunk, so dispatches tracking slabs ~1:1 is the healthy shape
        "bsi.slabs",
        "bsi.slab_bytes",
        "bsi.plane_dispatches",
        # cross-fragment deferred-delta merge barrier (core/merge.py,
        # refreshed at scrape time): cumulative barrier wall ms, staged
        # buffers merged (any path), and barriers that dispatched the
        # device merge program. Process-global like the hbm.* gauges —
        # the merge rides the one shared device.
        "ingest.merge_ms",
        "ingest.merge_batches",
        "ingest.merge_device",
        # durable write path (core/wal.py group-commit WAL): commit
        # rounds, file fsyncs (commit_groups/fsyncs are cumulative
        # counters published as gauges at scrape/sampler time), appends
        # coalesced per round (histogram), and — bounded-loss mode —
        # how long buffered appends waited for their background fsync.
        # Process-global like the hbm.* gauges: one commit loop per
        # process.
        "wal.commit_groups",
        "wal.fsyncs",
        "wal.group_size",
        "wal.sync_lag_ms",
        "wal.sync_failures",
        # mesh-group execution (exec/meshgroup.py, refreshed at scrape/
        # sampler time): live registered members of this node's ICI
        # domain, cumulative shards answered mesh-locally (no HTTP leg),
        # and cumulative bytes moved by in-program collectives. Process-
        # global counters like the hbm.* gauges — all in-process nodes
        # share one device mesh.
        "mesh.group_size",
        "mesh.local_shards",
        "mesh.collective_bytes",
        # devices of the process's active mesh (parallel/mesh.py), 0 with
        # none: where every operand stack is placed, whether or not the
        # node is in a mesh group
        "mesh.devices",
        # mesh-group fallbacks (exec/distributed.py): eligible fan-outs
        # that bailed to HTTP legs at lowering time, tagged by reason
        # ("budget" / "no_stacked_form" / "unsupported") so a fallback-
        # rate regression — a 5-9x latency cliff — is visible instead of
        # silent
        "mesh.fallback",
        # versioned result cache (core/resultcache.py, refreshed at
        # scrape/sampler time by publish_cache_gauges): revalidated and
        # repaired hits serve with zero compiled dispatches; resident
        # bytes are attributed per index (label GC on index delete)
        "cache.hits",
        "cache.misses",
        "cache.revalidations",
        "cache.repairs",
        "cache.evictions",
        "cache.entries",
        "cache.resident_bytes",
        # multi-tenant QoS enforcement (sched/tenants.py policy; gauges
        # refreshed at scrape/sampler time by publish_cache_gauges when
        # any [tenants] limit is configured): the per-index EFFECTIVE
        # quotas — defaults merged with overrides, so dashboards can
        # plot usage/quota without parsing config — and the cumulative
        # per-index tenant-quota evictions in each cache
        # ("cache:<hbm|result>" tag)
        "tenant.hbm_quota_bytes",
        "tenant.cache_quota_bytes",
        "tenant.inflight_quota_bytes",
        "tenant.quota_evictions",
        # live elastic resize (server/node.py streaming resharding):
        # per-fragment transfer legs, delta catch-up volume, cutover
        # latency and aborted jobs
        "resize.fragments_streamed",
        "resize.bytes_streamed",
        "resize.delta_positions",
        "resize.catchup_rounds",
        "resize.cutover_ms",
        "resize.cutover_rejects",
        "resize.aborts",
        # tiered storage (pilosa_tpu/tier/): demotion to the object
        # store, on-demand hydration (fetches counts STORE round trips —
        # the single-flight assertion reads it), snapshot-based joiner
        # bootstrap (compared against resize.bytes_streamed), and the
        # anti-entropy snapshot sync; plus per-index cold-set gauges
        "tier.demotions",
        "tier.demote_bytes",
        "tier.demote_aborts",
        "tier.hydrations",
        "tier.fetches",
        "tier.fetch_bytes",
        "tier.bootstrap_objects",
        "tier.bootstrap_bytes",
        "tier.ae_repairs",
        "tier.sync_uploads",
        "tier.cold_fragments",
        "tier.local_bytes",
        # result-cache monotone-tree maintenance (core/resultcache.py
        # counters surfaced by publish_cache_gauges): in-place tree
        # patches from merge word-deltas and structural re-keys of
        # entries whose burst provably touched no depended-on row
        "cache.tree_repairs",
        "cache.rekeys",
        # cache coherence plane (pilosa_tpu/coherence/): push
        # invalidation + version leases + live query subscriptions.
        # version_rtts counts peers that still paid a wire
        # /internal/versions fetch during fan-out revalidation (a
        # leased warm hit leaves it flat); lease_hits counts mirrors
        # served without that RTT; publishes/publish_errors/
        # invalidations track the batched push path; sub_pushes counts
        # delivered subscription updates
        "coherence.version_rtts",
        "coherence.lease_hits",
        "coherence.leases",
        "coherence.grants",
        "coherence.grants_issued",
        "coherence.publishes",
        "coherence.publish_errors",
        "coherence.invalidations",
        "coherence.sub_pushes",
        "coherence.subscriptions",
    }
)

# Prefixes for families whose full names are built dynamically (e.g.
# breaker state-transition counters "breaker.open"/"breaker.closed"/
# "breaker.half_open" in server/faults.py) or that are synthesized
# outside the StatsClient emission path: "cluster." families are written
# into the merged registry by the federated rollup
# (server/telemetry.py), and "stats." covers the metrics plane's own
# self-reporting ("stats.dropped_preboot" from the statsd transport).
# Dynamic emissions must start with a declared prefix.
STAT_PREFIXES = frozenset({"breaker.", "cluster.", "stats."})

# Labeled metric families: family name -> the EXACT set of label keys
# every series of that family must carry (enforced end-to-end by
# tools/prom_lint.py against the rendered /metrics and /cluster/metrics
# text — a family here may neither drop a label nor mix labeled and
# unlabeled series; families NOT listed must render unlabeled). "-" is
# the conventional placeholder value when a label is structurally
# unknowable (e.g. admission of a request bound to no index).
STAT_LABELS: Dict[str, Tuple[str, ...]] = {
    "query_n": ("index",),
    "query_ms": ("index",),
    "ingest.bits": ("index",),
    "ingest.batches": ("index",),
    "ingest.apply_ms": ("index",),
    "ingest.route_ms": ("index",),
    "sched.admit": ("class", "index"),
    # shed additionally carries the reason tag — rate (tenant qps
    # bucket), bytes (tenant bytes/s bucket or in-flight byte quota),
    # queue (admission/leg queue full), deadline (all deadline sheds) —
    # so overload and abuse are distinguishable from /metrics alone
    "sched.shed": ("class", "index", "reason"),
    "sched.wait_ms": ("class", "index"),
    "sched.index_inflight_bytes": ("index",),
    "hbm.resident_bytes": ("index",),
    "hbm.restage_bytes": ("index",),
    "cache.resident_bytes": ("index",),
    "tenant.hbm_quota_bytes": ("index",),
    "tenant.cache_quota_bytes": ("index",),
    "tenant.inflight_quota_bytes": ("index",),
    "tenant.quota_evictions": ("cache", "index"),
    "tier.cold_fragments": ("index",),
    "tier.local_bytes": ("index",),
    "coherence.subscriptions": ("index",),
    "mesh.fallback": ("reason",),
    # federation meta-gauges (server/telemetry.py writes these into the
    # merged registry directly; the "cluster." prefix covers the names)
    "cluster.peer_stale": ("node",),
    "cluster.snapshot_age_s": ("node",),
}


def is_declared_stat(name: str) -> bool:
    """True when `name` is a declared metric or under a declared dynamic
    prefix (used by the static gate; cheap enough for runtime asserts)."""
    return name in STAT_NAMES or any(
        name.startswith(p) for p in STAT_PREFIXES
    )


def _key(name: str, tags: Tuple[str, ...]) -> Tuple[str, Tuple[str, ...]]:
    return (name, tuple(sorted(tags)))


# ---------------------------------------------------------------------------
# Histograms. Fixed log-spaced buckets (1 / 2.5 / 5 per decade) replace the
# old 512-sample ring: bounded memory per series, exact counts/sums forever
# (a ring forgets everything older than 512 samples — its "p50" was a
# recency artifact, not a distribution), and a real Prometheus
# `_bucket`/`_sum`/`_count` exposition whose quantiles any backend can
# aggregate. The bounds cover sub-ms timings through minutes-long scans
# and double as sane buckets for sizes (batch size, bytes are observed in
# the same family).
# ---------------------------------------------------------------------------

HIST_BOUNDS: Tuple[float, ...] = tuple(
    m * (10.0 ** e) for e in range(-3, 5) for m in (1.0, 2.5, 5.0)
)


class Histogram:
    """Fixed log-bucket histogram: counts per bucket plus exact count /
    sum / min / max. Quantiles interpolate linearly inside the owning
    bucket and clamp to the observed [min, max], so a constant stream
    reports that constant, not a bucket edge."""

    __slots__ = ("buckets", "count", "total", "vmin", "vmax")

    def __init__(self) -> None:
        self.buckets = [0] * (len(HIST_BOUNDS) + 1)  # +1: the +Inf bucket
        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")

    def observe(self, value: float) -> None:
        value = float(value)
        self.buckets[bisect.bisect_left(HIST_BOUNDS, value)] += 1
        self.count += 1
        self.total += value
        if value < self.vmin:
            self.vmin = value
        if value > self.vmax:
            self.vmax = value

    def quantile(self, q: float) -> float:
        """Estimated q-quantile (q in [0, 1]); 0.0 when empty."""
        if self.count == 0:
            return 0.0
        rank = q * self.count
        cum = 0
        for i, n in enumerate(self.buckets):
            if n == 0:
                continue
            if cum + n >= rank:
                lo = HIST_BOUNDS[i - 1] if i > 0 else 0.0
                hi = HIST_BOUNDS[i] if i < len(HIST_BOUNDS) else self.vmax
                frac = (rank - cum) / n
                est = lo + (hi - lo) * max(0.0, min(1.0, frac))
                return max(self.vmin, min(self.vmax, est))
            cum += n
        return self.vmax

    def cumulative(self) -> List[Tuple[float, int]]:
        """[(upper_bound, cumulative_count)] incl. the +Inf bucket —
        exactly the Prometheus `_bucket{le=...}` series."""
        out: List[Tuple[float, int]] = []
        cum = 0
        for bound, n in zip(HIST_BOUNDS, self.buckets):
            cum += n
            out.append((bound, cum))
        out.append((float("inf"), cum + self.buckets[-1]))
        return out

    def snapshot(self) -> dict:
        if self.count == 0:
            return {"count": 0}
        return {
            "count": self.count,
            "sum": self.total,
            "mean": self.total / self.count,
            "min": self.vmin,
            "p50": self.quantile(0.50),
            "p95": self.quantile(0.95),
            "p99": self.quantile(0.99),
            "max": self.vmax,
        }

    def export_dict(self) -> dict:
        """JSON-safe full state: the raw per-bucket counts plus exact
        count/sum/min/max — everything merge_dict needs to reconstruct
        this histogram on another node. Because every node shares the
        fixed HIST_BOUNDS, a bucket-wise merge of N exported histograms
        is EXACTLY the histogram of the union of their samples."""
        return {
            "buckets": list(self.buckets),
            "count": self.count,
            "sum": self.total,
            "min": self.vmin if self.count else 0.0,
            "max": self.vmax if self.count else 0.0,
        }

    def merge_dict(self, d: dict) -> bool:
        """Fold one exported histogram into this one (bucket-wise sums,
        exact count/sum, min/max of extremes). Returns False — merging
        nothing — when the export's bucket layout does not match this
        build's HIST_BOUNDS (mixed-version cluster) or any field fails
        to parse (half-written snapshot): a malformed payload must
        degrade to missing data, not raise out of a /cluster/* merge.
        Every field is coerced BEFORE the first mutation so a bad entry
        can't leave the accumulator partially updated."""
        buckets = d.get("buckets")
        try:
            count = int(d.get("count", 0))
            if (
                not isinstance(buckets, list)
                or len(buckets) != len(self.buckets)
                or count <= 0
            ):
                return False
            adds = [int(n) for n in buckets]
            total = float(d.get("sum", 0.0))
            vmin = float(d.get("min", float("inf")))
            vmax = float(d.get("max", float("-inf")))
        except (TypeError, ValueError):
            return False
        for i, n in enumerate(adds):
            self.buckets[i] += n
        self.count += count
        self.total += total
        self.vmin = min(self.vmin, vmin)
        self.vmax = max(self.vmax, vmax)
        return True


class Registry:
    """Tagged counters / gauges / histograms / sets, shared by all views."""

    def __init__(self):
        self._mu = TrackedLock("stats.registry_mu")
        self._counters: Dict[Tuple[str, Tuple[str, ...]], float] = defaultdict(float)
        self._gauges: Dict[Tuple[str, Tuple[str, ...]], float] = {}
        self._hists: Dict[Tuple[str, Tuple[str, ...]], Histogram] = {}
        self._sets: Dict[Tuple[str, Tuple[str, ...]], set] = defaultdict(set)

    def count(self, name, value, tags):
        with self._mu:
            self._counters[_key(name, tags)] += value

    def gauge(self, name, value, tags):
        with self._mu:
            self._gauges[_key(name, tags)] = value

    def observe(self, name, value, tags):
        with self._mu:
            k = _key(name, tags)
            h = self._hists.get(k)
            if h is None:
                h = self._hists[k] = Histogram()
            h.observe(value)

    def add_to_set(self, name, value, tags):
        with self._mu:
            self._sets[_key(name, tags)].add(value)

    def quantile(self, name: str, q: float, tags: Iterable[str] = ()) -> float:
        """Estimated quantile of one histogram series (0.0 when the
        series has never been observed) — the principled tail estimate
        consumers like the admission controller read."""
        with self._mu:
            h = self._hists.get(_key(name, tuple(tags)))
            return h.quantile(q) if h is not None else 0.0

    def total_counter(self, name: str) -> float:
        """Sum of one counter family across every tagged series (the
        telemetry sampler reads cumulative ingest/query totals this way)."""
        with self._mu:
            return sum(
                v for (n, _), v in self._counters.items() if n == name
            )

    def drop_label(self, key: str, value: str) -> int:
        """Label GC: remove every series (counter/gauge/histogram/set)
        carrying the `key:value` tag — called when an index is deleted so
        a churning tenant set cannot leak per-index gauge families
        forever. Returns the number of series removed."""
        tag = f"{key}:{value}"
        removed = 0
        with self._mu:
            for store in (
                self._counters, self._gauges, self._hists, self._sets,
            ):
                for k in [k for k in store if tag in k[1]]:
                    del store[k]
                    removed += 1
        return removed

    # -- federation (server/telemetry.py cluster rollup) -------------------

    def export_state(self) -> dict:
        """One JSON-safe, MERGEABLE snapshot of every series. Unlike
        snapshot() (which renders histograms as summary quantiles) this
        carries raw bucket counts, so a peer can fold it into its own
        registry with merge_state and compute REAL cluster quantiles
        from the merged buckets instead of averaging per-node averages."""
        with self._mu:
            return {
                "histBuckets": len(HIST_BOUNDS) + 1,
                "counters": [
                    [n, list(t), v] for (n, t), v in self._counters.items()
                ],
                "gauges": [
                    [n, list(t), v] for (n, t), v in self._gauges.items()
                ],
                "hists": [
                    [n, list(t), h.export_dict()]
                    for (n, t), h in self._hists.items()
                    if h.count
                ],
                "sets": [
                    [n, list(t), len(m)] for (n, t), m in self._sets.items()
                ],
            }

    def merge_state(self, state: dict) -> None:
        """Fold one export_state() payload into this registry: counters
        and gauges merge by SUM (the byte ledgers and throughput counters
        are extensive quantities — the cluster total is the sum of node
        totals), set series merge by summed cardinality (rendered as
        gauges either way), histograms bucket-wise (exact, shared
        bounds). Malformed entries are skipped, never raised — a peer's
        half-written snapshot must degrade, not 500 the rollup."""
        with self._mu:
            for entry in state.get("counters", ()):
                try:
                    n, t, v = entry
                    k, v = _key(n, tuple(t)), float(v)
                except (TypeError, ValueError):
                    # coerce BEFORE touching the store: the defaultdict
                    # would otherwise materialize a phantom zero series
                    # for an entry whose value fails to parse
                    continue
                self._counters[k] += v
            for entry in list(state.get("gauges", ())) + list(
                state.get("sets", ())
            ):
                try:
                    n, t, v = entry
                    k = _key(n, tuple(t))
                    self._gauges[k] = self._gauges.get(k, 0.0) + float(v)
                except (TypeError, ValueError):
                    continue
            for entry in state.get("hists", ()):
                try:
                    n, t, d = entry
                    k = _key(n, tuple(t))
                except (TypeError, ValueError):
                    continue
                if not isinstance(d, dict):
                    continue
                h = self._hists.get(k)
                if h is None:
                    # register the series only if the payload merges: a
                    # malformed entry must not materialize a phantom
                    # empty histogram
                    h = Histogram()
                    if h.merge_dict(d):
                        self._hists[k] = h
                else:
                    h.merge_dict(d)

    # -- views -------------------------------------------------------------

    def snapshot(self) -> dict:
        """expvar-style JSON object (served at /debug/vars). Histogram
        series render as {count, sum, mean, min, p50, p95, p99, max}."""

        def fmt(k):
            name, tags = k
            return name if not tags else f"{name};{','.join(tags)}"

        with self._mu:
            out: dict = {}
            for k, v in sorted(self._counters.items()):
                out[fmt(k)] = v
            for k, v in sorted(self._gauges.items()):
                out[fmt(k)] = v
            for k, h in sorted(self._hists.items()):
                if h.count:
                    out[fmt(k)] = h.snapshot()
            for k, members in sorted(self._sets.items()):
                out[fmt(k)] = len(members)
            return out

    def prometheus_text(self, prefix: str = "pilosa_tpu_") -> str:
        """Prometheus exposition format (served at /metrics).

        Families are grouped so each metric name carries exactly ONE
        `# TYPE` line before all of its series (the spec forbids
        repeating it per tagged series — tools/prom_lint.py enforces
        this on the rendered text). Histogram series export real
        `_bucket{le=...}`/`_sum`/`_count` triplets with cumulative,
        monotone bucket counts."""

        def sanitize(name):
            return prefix + "".join(c if c.isalnum() else "_" for c in name)

        def esc(v):
            # label-value escaping per the exposition format spec
            return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")

        def labels(tags, extra: str = ""):
            pairs = []
            for t in tags:
                k, _, v = t.partition(":")
                pairs.append(f'{k or "tag"}="{esc(v or k)}"')
            if extra:
                pairs.append(extra)
            if not pairs:
                return ""
            return "{" + ",".join(pairs) + "}"

        def fmt_le(bound: float) -> str:
            if bound == float("inf"):
                return "+Inf"
            return f"{bound:g}"

        # family name -> (type, [series lines]); insertion-ordered so the
        # output stays stable for tests and diffing
        families: Dict[str, Tuple[str, List[str]]] = {}

        def family(name: str, mtype: str) -> List[str]:
            m = sanitize(name)
            got = families.get(m)
            if got is None:
                got = families[m] = (mtype, [])
            return got[1]

        with self._mu:
            for (name, tags), v in sorted(self._counters.items()):
                m = sanitize(name)
                family(name, "counter").append(f"{m}{labels(tags)} {v}")
            for (name, tags), v in sorted(self._gauges.items()):
                m = sanitize(name)
                family(name, "gauge").append(f"{m}{labels(tags)} {v}")
            for (name, tags), h in sorted(self._hists.items()):
                if not h.count:
                    continue
                m = sanitize(name)
                lines = family(name, "histogram")
                for bound, cum in h.cumulative():
                    le = f'le="{fmt_le(bound)}"'
                    lines.append(f"{m}_bucket{labels(tags, le)} {cum}")
                lines.append(f"{m}_sum{labels(tags)} {h.total}")
                lines.append(f"{m}_count{labels(tags)} {h.count}")
            for (name, tags), members in sorted(self._sets.items()):
                m = sanitize(name)
                family(name, "gauge").append(f"{m}{labels(tags)} {len(members)}")
        out: List[str] = []
        for m, (mtype, lines) in families.items():
            out.append(f"# TYPE {m} {mtype}")
            out.extend(lines)
        return "\n".join(out) + "\n"


# Counters of work that belongs to the PROCESS and not to a node: the
# compile listeners of exec/plan.py count here, where no NodeServer is in
# reach, and every node's publish_cache_gauges copies the totals into its
# own registry (in-process harness nodes share one jax, as they share
# one device).
PROCESS = Registry()


class StatsClient:
    """Registry-backed client (reference iface: stats/stats.go:31-64)."""

    def __init__(self, registry: Optional[Registry] = None, tags: Iterable[str] = ()):
        self.registry = registry or Registry()
        self.tags: Tuple[str, ...] = tuple(tags)

    def with_tags(self, *tags: str) -> "StatsClient":
        return StatsClient(self.registry, self.tags + tags)

    def count(self, name: str, value: float = 1, rate: float = 1.0) -> None:
        self.registry.count(name, value, self.tags)

    def gauge(self, name: str, value: float) -> None:
        self.registry.gauge(name, value, self.tags)

    def histogram(self, name: str, value: float) -> None:
        self.registry.observe(name, value, self.tags)

    def set_value(self, name: str, value: str) -> None:
        self.registry.add_to_set(name, value, self.tags)

    def timing(self, name: str, seconds: float) -> None:
        self.registry.observe(name, seconds * 1000.0, self.tags)

    def timer(self, name: str):
        """Context manager recording elapsed ms into a timing series."""
        return _Timer(self, name)

    def close(self) -> None:
        pass  # registry client holds no OS resources


class _Timer:
    def __init__(self, client: StatsClient, name: str):
        self.client = client
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.client.timing(self.name, time.perf_counter() - self.t0)


class NopStatsClient:
    """metric.service = none."""

    registry = None
    tags: Tuple[str, ...] = ()

    def with_tags(self, *tags: str) -> "NopStatsClient":
        return self

    def count(self, name, value=1, rate=1.0):
        pass

    def gauge(self, name, value):
        pass

    def histogram(self, name, value):
        pass

    def set_value(self, name, value):
        pass

    def timing(self, name, seconds):
        pass

    def timer(self, name):
        return _NopTimer()

    def close(self):
        pass


class _NopTimer:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


def _split_hostport(host: str) -> Tuple[str, int]:
    """'host', 'host:port', '[v6]:port', or bare 'v6' -> (host, port).
    Raises a config-shaped ValueError on SYNTAX problems only — name
    resolution is the transport's (retryable) concern, not parsing's."""
    h, p = host, 8125
    if host.startswith("["):  # [v6]:port
        end = host.find("]")
        if end < 0:
            raise ValueError(f"metric.host {host!r}: unclosed '[' in address")
        h = host[1:end]
        rest = host[end + 1 :]
        if rest.startswith(":"):
            p = rest[1:]
    elif host.count(":") == 1:  # host:port
        h, _, p = host.partition(":")
    # else: bare hostname or bare IPv6 literal, default port
    try:
        p = int(p)
    except ValueError:
        raise ValueError(
            f"metric.host {host!r}: port {p!r} is not an integer"
        ) from None
    return h or "localhost", p


class _StatsdTransport:
    """Shared UDP push channel for one StatsdClient family (with_tags
    children share their parent's transport, hence one socket and one
    buffer). Name resolution is LAZY with bounded retry: a daemon whose
    DNS entry appears after boot (the common sidecar race) no longer
    fails the server, and datagrams recorded before resolution succeeds
    are buffered (bounded, drop-oldest) and flushed on the first
    successful resolve instead of vanishing — the early-boot latency
    histograms dashboards kept missing. Every datagram that IS lost
    (buffer overflow, or still unflushed at close) is counted in the
    registry as `stats.dropped_preboot`, so the loss is visible on the
    very scrape endpoints that kept working."""

    BUFFER_MAX = 2048
    RESOLVE_RETRY = 1.0  # seconds between resolution attempts

    def __init__(
        self,
        host: str,
        registry: Optional[Registry],
        sock: Optional[socket.socket] = None,
    ):
        self.host = host
        self.registry = registry
        self._hostport = _split_hostport(host)  # syntax errors raise NOW
        self._mu = TrackedLock("stats.statsd_mu")
        self._sock = sock
        self._addr = None
        self._resolving = False
        self._next_resolve = 0.0
        self._buffer: "deque[bytes]" = deque()
        self._closed = False
        # one boot-time attempt (keeps the common resolvable-at-boot
        # case on the fast path from the very first datagram)
        with self._mu:
            attempt = self._mark_resolving_locked()
        if attempt:
            self._finish_resolve()

    def _mark_resolving_locked(self) -> bool:
        """Claim the (single) resolution slot if a retry is due. The DNS
        lookup itself runs in _finish_resolve with the mutex RELEASED:
        a slow resolver (missing DNS entry, multi-second timeout) must
        never park every metric-emitting thread behind the transport
        lock — at most one emitter per retry interval pays the lookup,
        everyone else buffers and moves on."""
        if self._addr is not None or self._resolving or self._closed:
            return False
        now = time.monotonic()
        if now < self._next_resolve:
            return False
        self._resolving = True
        self._next_resolve = now + self.RESOLVE_RETRY
        return True

    def _finish_resolve(self) -> None:
        h, p = self._hostport
        try:
            info = socket.getaddrinfo(h, p, type=socket.SOCK_DGRAM)[0]
        except (OSError, UnicodeError):
            # gaierror IS an OSError; UnicodeError covers an overlong
            # IDNA label. Either way: stay unresolved, retry next
            # interval, and — critically — fall through so _resolving
            # resets (a wedged True would disable resolution forever)
            info = None
        with self._mu:
            self._resolving = False
            if info is None or self._closed or self._addr is not None:
                return
            if self._sock is None:
                try:
                    self._sock = socket.socket(info[0], socket.SOCK_DGRAM)
                except OSError:
                    # fd exhaustion: _addr stays unset (a half-resolved
                    # transport with no socket would crash every later
                    # emission); retry the whole resolve next interval
                    return
            self._addr = info[4]
            while self._buffer:
                self._sendto_locked(self._buffer.popleft())

    def send(self, datagram: bytes) -> None:
        dropped = 0
        attempt = False
        with self._mu:
            if self._closed:
                return
            if self._addr is None:
                if len(self._buffer) >= self.BUFFER_MAX:
                    self._buffer.popleft()
                    dropped = 1
                self._buffer.append(datagram)
                attempt = self._mark_resolving_locked()
            else:
                while self._buffer:
                    self._sendto_locked(self._buffer.popleft())
                self._sendto_locked(datagram)
        if attempt:
            self._finish_resolve()
        if dropped and self.registry is not None:
            self.registry.count("stats.dropped_preboot", dropped, ())

    def _sendto_locked(self, datagram: bytes) -> None:
        try:
            self._sock.sendto(datagram, self._addr)
        except OSError:
            pass  # best-effort: never block or fail the caller

    def close(self) -> None:
        with self._mu:
            if self._closed:
                return
            self._closed = True
            unflushed = len(self._buffer)
            self._buffer.clear()
            if self._sock is not None:
                try:
                    self._sock.close()
                except OSError:
                    pass
        if unflushed and self.registry is not None:
            self.registry.count("stats.dropped_preboot", unflushed, ())


class StatsdClient(StatsClient):
    """DogStatsD UDP push client (reference: statsd/statsd.go:48 uses the
    DataDog client). Every metric still lands in the shared Registry (so
    /metrics and /debug/vars work), and is ALSO pushed as a datagram:
    `name:value|type|#tag1,tag2`. UDP is fire-and-forget; serialization
    errors and unreachable daemons are swallowed — metrics must never
    take down a query. Pre-resolution pushes buffer in the shared
    transport (see _StatsdTransport) instead of silently disappearing."""

    def __init__(
        self,
        host: str = "localhost:8125",
        registry: Optional[Registry] = None,
        tags: Iterable[str] = (),
        prefix: str = "pilosa_tpu.",
        sock: Optional[socket.socket] = None,
        transport: Optional[_StatsdTransport] = None,
    ):
        super().__init__(registry, tags)
        self.host = host
        self.prefix = prefix
        self._transport = transport or _StatsdTransport(
            host, self.registry, sock=sock
        )

    def close(self) -> None:
        """Release the UDP socket (NodeServer.stop calls this; with_tags
        children share the parent's transport, so close only the root)."""
        self._transport.close()

    def with_tags(self, *tags: str) -> "StatsdClient":
        return StatsdClient(
            self.host,
            self.registry,
            self.tags + tags,
            self.prefix,
            transport=self._transport,  # children share socket + buffer
        )

    def _push(self, name: str, value, mtype: str) -> None:
        datagram = f"{self.prefix}{name}:{value}|{mtype}"
        if self.tags:
            datagram += "|#" + ",".join(self.tags)
        self._transport.send(datagram.encode())

    def count(self, name: str, value: float = 1, rate: float = 1.0) -> None:
        super().count(name, value, rate)
        self._push(name, value, "c")

    def gauge(self, name: str, value: float) -> None:
        super().gauge(name, value)
        self._push(name, value, "g")

    def histogram(self, name: str, value: float) -> None:
        super().histogram(name, value)
        self._push(name, value, "h")

    def set_value(self, name: str, value: str) -> None:
        super().set_value(name, value)
        self._push(name, value, "s")

    def timing(self, name: str, seconds: float) -> None:
        super().timing(name, seconds)
        self._push(name, round(seconds * 1000.0, 3), "ms")


def new_stats_client(service: str = "expvar", host: str = "localhost:8125"):
    """reference: server/server.go:419 newStatsClient."""
    if service in ("expvar", "prometheus", ""):
        return StatsClient()
    if service == "statsd":
        return StatsdClient(host=host)
    if service in ("none", "nostats"):
        return NopStatsClient()
    raise ValueError(f"unknown metric service {service!r}")
