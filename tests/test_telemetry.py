"""Cluster telemetry plane tests (ISSUE 8): per-index resource
attribution, federated metrics rollup, utilization timeline.

Layers: histogram bucket-wise merge property tests (merge of N node
histograms is IDENTICAL to one histogram fed the union of samples);
registry export/merge units; label GC (create/delete 100 indexes
returns the series count to baseline); per-index HBM attribution
reconciling byte-for-byte with the global devcache ledger under
eviction pressure; the statsd preboot buffer; prom-lint labeled-family
rules on seeded violations; and the 3-node acceptance scenario —
exact per-index counter merge, a seeded slow node pulling the cluster
p99 up, and a killed peer degrading /cluster/overview to stale-marked
data instead of a 500."""

import json
import math
import socket
import urllib.error
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.core.devcache import DEVICE_CACHE
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import Executor
from pilosa_tpu.hbm import residency as hbm_res
from pilosa_tpu.parallel import mesh as pmesh
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_ROW
from pilosa_tpu.testing import ClusterHarness
from pilosa_tpu.utils import stats as statsmod
from pilosa_tpu.utils.stats import (
    HIST_BOUNDS,
    Histogram,
    Registry,
    _StatsdTransport,
)

from tools.prom_lint import lint


def http_json(method, url, body=None, headers=None, timeout=30):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    if data is not None:
        req.add_header("Content-Type", "application/json")
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        raw = resp.read()
    return json.loads(raw) if raw else {}


def _seed(api, index, n_shards=3, rows=2):
    api.create_index(index)
    api.create_field(index, "f", {"type": "set"})
    rws, cols = [], []
    for s in range(n_shards):
        for r in range(rows):
            for k in range(20):
                rws.append(r)
                cols.append(s * SHARD_WIDTH + 17 * k + r)
    api.import_bits(index, "f", rws, cols)


# ---------------------------------------------------------------------------
# histogram merge: the property the whole federation rests on
# ---------------------------------------------------------------------------


class TestHistogramMerge:
    def test_merge_identical_to_union(self):
        """Bucket-wise merge of N per-node histograms must be EXACTLY
        the histogram of the union of their samples — same buckets,
        count, min/max; sum within float addition reordering."""
        rng = np.random.default_rng(7)
        per_node = [
            list(rng.lognormal(mean, 1.2, size=n))
            for mean, n in ((0.0, 400), (2.0, 150), (4.5, 37))
        ]
        nodes = []
        union = Histogram()
        for samples in per_node:
            h = Histogram()
            for v in samples:
                h.observe(v)
                union.observe(v)
            nodes.append(h)
        merged = Histogram()
        for h in nodes:
            assert merged.merge_dict(h.export_dict())
        assert merged.buckets == union.buckets
        assert merged.count == union.count == sum(len(s) for s in per_node)
        assert merged.total == pytest.approx(union.total, rel=1e-12)
        assert merged.vmin == union.vmin
        assert merged.vmax == union.vmax
        # the Prometheus exposition series are therefore identical too
        assert merged.cumulative() == union.cumulative()

    def test_merged_quantiles_within_interpolation_tolerance(self):
        """Quantiles of the merged histogram track the true sample
        quantiles to within one log-bucket (bounds step at most 2.5x)."""
        rng = np.random.default_rng(11)
        per_node = [list(rng.lognormal(1.0, 1.0, size=300)) for _ in range(4)]
        merged = Histogram()
        for samples in per_node:
            h = Histogram()
            for v in samples:
                h.observe(v)
            merged.merge_dict(h.export_dict())
        flat = np.sort(np.concatenate(per_node))
        for q in (0.5, 0.95, 0.99):
            est = merged.quantile(q)
            true = float(np.quantile(flat, q))
            assert true / 2.6 <= est <= true * 2.6, (q, est, true)

    def test_mismatched_bucket_layout_rejected(self):
        """A mixed-version peer with different bounds must be skipped,
        never mis-merged."""
        h = Histogram()
        h.observe(3.0)
        before = list(h.buckets)
        assert not h.merge_dict({"buckets": [1] * 4, "count": 1, "sum": 9.0})
        assert not h.merge_dict({"buckets": "nope", "count": 5})
        assert not h.merge_dict({"count": 0, "buckets": [0] * len(h.buckets)})
        assert h.buckets == before and h.count == 1

    def test_one_slow_node_pulls_merged_p99_up(self):
        """The seeded-skew property: two fast nodes with tight
        distributions plus one slow node — the merged p99 must land in
        the slow regime even though 2/3 of nodes report fast p99s."""
        fast_a, fast_b, slow = Histogram(), Histogram(), Histogram()
        for _ in range(500):
            fast_a.observe(2.0)
            fast_b.observe(3.0)
        for _ in range(40):  # >1% of the merged population
            slow.observe(4000.0)
        merged = Histogram()
        for h in (fast_a, fast_b, slow):
            merged.merge_dict(h.export_dict())
        assert fast_a.quantile(0.99) < 10
        assert fast_b.quantile(0.99) < 10
        assert merged.quantile(0.99) > 1000


class TestRegistryFederation:
    def test_export_merge_sums_counters_and_gauges(self):
        a, b, merged = Registry(), Registry(), Registry()
        a.count("query_n", 3, ("index:t1",))
        b.count("query_n", 4, ("index:t1",))
        b.count("query_n", 9, ("index:t2",))
        a.gauge("sched.inflight_bytes", 100, ())
        b.gauge("sched.inflight_bytes", 50, ())
        a.add_to_set("uniq", "x", ())
        b.add_to_set("uniq", "y", ())
        merged.merge_state(a.export_state())
        merged.merge_state(b.export_state())
        snap = merged.snapshot()
        assert snap["query_n;index:t1"] == 7
        assert snap["query_n;index:t2"] == 9
        assert snap["sched.inflight_bytes"] == 150
        assert snap["uniq"] == 2  # set series merge by cardinality

    def test_merge_state_skips_malformed_entries(self):
        merged = Registry()
        merged.merge_state(
            {
                "counters": [["ok", [], 1], ["bad"], ["bad2", [], "x"]],
                "gauges": [[1, 2]],
                "hists": [["h", [], "not-a-dict"], "junk"],
            }
        )
        assert merged.snapshot() == {"ok": 1.0}

    def test_merge_state_skips_garbled_histogram_payloads(self):
        """A half-written snapshot (non-numeric bucket or count) must be
        skipped whole — no raise out of the /cluster/* merge, no
        partially-updated accumulator, no phantom empty series."""
        h = Histogram()
        h.observe(3.0)
        good = h.export_dict()
        bad_bucket = dict(good, buckets=[*good["buckets"]])
        bad_bucket["buckets"][0] = "x"
        merged = Registry()
        merged.merge_state(
            {
                "hists": [
                    ["h", [], bad_bucket],
                    ["h", [], dict(good, count="nope")],
                    ["h", [], good],
                ]
            }
        )
        # only the clean payload landed, and it landed exactly once
        assert merged.quantile("h", 0.5, ()) > 0
        snap = merged.snapshot()
        assert snap["h"]["count"] == 1
        # the garbled-only series never materialized
        merged2 = Registry()
        merged2.merge_state({"hists": [["solo", [], bad_bucket]]})
        assert merged2.snapshot() == {}

    def test_drop_label_removes_every_series_kind(self):
        reg = Registry()
        reg.count("query_n", 1, ("index:gone",))
        reg.gauge("hbm.resident_bytes", 5, ("index:gone",))
        reg.observe("query_ms", 1.0, ("index:gone",))
        reg.add_to_set("uniq", "x", ("index:gone",))
        reg.count("query_n", 1, ("index:kept",))
        assert reg.drop_label("index", "gone") == 4
        snap = reg.snapshot()
        assert snap == {"query_n;index:kept": 1.0}


# ---------------------------------------------------------------------------
# prom-lint labeled-family rules (STAT_LABELS)
# ---------------------------------------------------------------------------


class TestPromLintLabels:
    LABELS = {"query_ms": ("index",), "sched.admit": ("class", "index")}

    def _lint(self, text):
        return lint(
            text,
            declared={"query_ms", "sched.admit", "plain"},
            declared_prefixes=set(),
            labels=self.LABELS,
        )

    def test_clean_labeled_exposition(self):
        text = (
            "# TYPE pilosa_tpu_sched_admit counter\n"
            'pilosa_tpu_sched_admit{class="interactive",index="a"} 3\n'
            'pilosa_tpu_sched_admit{class="batch",index="-"} 1\n'
            "# TYPE pilosa_tpu_plain gauge\n"
            "pilosa_tpu_plain 5\n"
        )
        assert self._lint(text) == []

    def test_dropped_label_key_flagged(self):
        text = (
            "# TYPE pilosa_tpu_sched_admit counter\n"
            'pilosa_tpu_sched_admit{class="interactive"} 3\n'
        )
        errs = self._lint(text)
        assert any("missing ['index']" in e for e in errs)

    def test_unlabeled_series_mixed_into_labeled_family_flagged(self):
        text = (
            "# TYPE pilosa_tpu_query_ms histogram\n"
            'pilosa_tpu_query_ms_bucket{index="a",le="+Inf"} 2\n'
            'pilosa_tpu_query_ms_sum{index="a"} 3.0\n'
            'pilosa_tpu_query_ms_count{index="a"} 2\n'
            'pilosa_tpu_query_ms_bucket{le="+Inf"} 1\n'
            "pilosa_tpu_query_ms_sum 1.0\n"
            "pilosa_tpu_query_ms_count 1\n"
        )
        errs = self._lint(text)
        assert any("violates its STAT_LABELS key set" in e for e in errs)

    def test_le_is_not_a_label(self):
        text = (
            "# TYPE pilosa_tpu_query_ms histogram\n"
            'pilosa_tpu_query_ms_bucket{index="a",le="1"} 2\n'
            'pilosa_tpu_query_ms_bucket{index="a",le="+Inf"} 2\n'
            'pilosa_tpu_query_ms_sum{index="a"} 1.2\n'
            'pilosa_tpu_query_ms_count{index="a"} 2\n'
        )
        assert self._lint(text) == []

    def test_unlisted_family_with_labels_flagged(self):
        text = (
            "# TYPE pilosa_tpu_plain gauge\n"
            'pilosa_tpu_plain{index="a"} 5\n'
        )
        errs = self._lint(text)
        assert any("not declared in STAT_LABELS" in e for e in errs)

    def test_undeclared_extra_label_flagged(self):
        text = (
            "# TYPE pilosa_tpu_sched_admit counter\n"
            'pilosa_tpu_sched_admit{class="batch",index="a",shard="0"} 3\n'
        )
        errs = self._lint(text)
        assert any("undeclared ['shard']" in e for e in errs)


def test_stat_labels_documented_in_observability_doc():
    """Doc-side half of the labeled-family contract: every STAT_LABELS
    family and each of its label keys appears in docs/observability.md."""
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs",
        "observability.md",
    )
    with open(path, encoding="utf-8") as fh:
        text = fh.read()
    for family, keys in statsmod.STAT_LABELS.items():
        assert family in text, f"STAT_LABELS family {family!r} undocumented"
        for k in keys:
            assert k in text


# ---------------------------------------------------------------------------
# statsd preboot buffering (satellite: early-boot observations must not
# silently vanish before the backend's DNS resolves)
# ---------------------------------------------------------------------------


class _FakeSock:
    def __init__(self):
        self.sent = []

    def sendto(self, datagram, addr):
        self.sent.append(datagram)

    def close(self):
        pass


class TestStatsdPreboot:
    def test_buffers_until_resolution_then_flushes_in_order(
        self, monkeypatch
    ):
        reg = Registry()
        fails = {"n": 2}
        real_getaddrinfo = socket.getaddrinfo

        def flaky(host, port, **kw):
            if fails["n"] > 0:
                fails["n"] -= 1
                raise socket.gaierror("not yet")
            return real_getaddrinfo("127.0.0.1", port, **kw)

        monkeypatch.setattr(socket, "getaddrinfo", flaky)
        monkeypatch.setattr(_StatsdTransport, "RESOLVE_RETRY", 0.0)
        sock = _FakeSock()
        # construction burns failed resolve #1
        t = _StatsdTransport("statsd.sidecar:8125", reg, sock=sock)
        t.send(b"a:1|c")  # second failed resolve -> buffered
        assert sock.sent == []
        t.send(b"b:1|c")  # resolves: buffer flushes first, in order
        t.send(b"c:1|c")
        assert sock.sent == [b"a:1|c", b"b:1|c", b"c:1|c"]
        assert reg.snapshot() == {}  # nothing was dropped

    def test_overflow_and_close_count_dropped_preboot(self, monkeypatch):
        def never(host, port, **kw):
            raise socket.gaierror("no such host")

        monkeypatch.setattr(socket, "getaddrinfo", never)
        reg = Registry()
        t = _StatsdTransport("statsd.sidecar:8125", reg, sock=_FakeSock())
        monkeypatch.setattr(t, "BUFFER_MAX", 8)
        for i in range(11):  # 3 over the buffer bound: drop-oldest
            t.send(b"x:%d|c" % i)
        assert reg.snapshot()["stats.dropped_preboot"] == 3
        t.close()  # 8 still-buffered datagrams are lost too
        assert reg.snapshot()["stats.dropped_preboot"] == 11
        t.send(b"late:1|c")  # after close: ignored, not counted
        assert reg.snapshot()["stats.dropped_preboot"] == 11


# ---------------------------------------------------------------------------
# per-index HBM attribution reconciles with the global ledger
# ---------------------------------------------------------------------------


@pytest.fixture
def paging_env():
    old_mesh = pmesh.active_mesh()
    pmesh.set_active_mesh(None)
    old_budget = DEVICE_CACHE.budget_bytes
    old_rows = hbm_res.extent_rows()
    DEVICE_CACHE.clear()
    hbm_res.reset_stats()
    yield
    hbm_res.configure(extent_rows=old_rows)
    DEVICE_CACHE.budget_bytes = old_budget
    DEVICE_CACHE.clear()
    hbm_res.reset_stats()
    pmesh.set_active_mesh(old_mesh)


class TestHbmAttribution:
    def _two_tenant_holder(self, n_rows, n_shards):
        h = Holder().open()
        rng = np.random.default_rng(5)
        for name in ("ten_a", "ten_b"):
            idx = h.create_index(name)
            f = idx.create_field("f", FieldOptions())
            for r in range(n_rows):
                for s in range(n_shards):
                    f.import_row_words(
                        r,
                        s,
                        rng.integers(0, 2**32, WORDS_PER_ROW).astype(
                            np.uint32
                        ),
                    )
        return Executor(h), h

    def test_per_index_bytes_reconcile_under_eviction_pressure(
        self, paging_env
    ):
        """Acceptance: sum of per-index resident bytes == the global
        devcache ledger byte-for-byte while two tenants fight over a
        budget below their combined working set (evictions churning the
        attribution map must never desync it)."""
        row_bytes = WORDS_PER_ROW * 4
        S, EXT_ROWS, N_ROWS = 8, 2, 6
        hbm_res.configure(extent_rows=EXT_ROWS)
        stack_bytes = S * row_bytes
        ws_one = N_ROWS * stack_bytes  # one tenant's working set
        DEVICE_CACHE.budget_bytes = int(1.5 * ws_one)  # < 2 tenants
        ex, _h = self._two_tenant_holder(N_ROWS, S)
        q = (
            "Count(Union("
            + ", ".join(f"Row(f={r})" for r in range(N_ROWS))
            + "))"
        )

        def reconcile():
            by_index = DEVICE_CACHE.index_resident_bytes()
            assert sum(by_index.values()) == DEVICE_CACHE.bytes_used
            return by_index

        for idx in ("ten_a", "ten_b", "ten_a", "ten_b", "ten_a"):
            ex.execute(idx, q)
            by_index = reconcile()
            # the tenant that just ran is resident
            assert by_index.get(idx, 0) > 0
        snap = hbm_res.stats_snapshot()
        # eviction pressure actually happened (budget < combined ws)
        assert snap["evicted_extent_bytes"] > 0
        # restage attribution splits the cumulative bill across tenants
        per_idx = snap["restage_by_index"]
        assert set(per_idx) >= {"ten_a", "ten_b"}
        assert sum(per_idx.values()) == snap["restage_bytes"]

    def test_gauge_path_reconciles_on_a_live_node(self, paging_env):
        """Through the server funnel: publish_cache_gauges' per-index
        hbm.resident_bytes series sum to devcache.resident_bytes."""
        with ClusterHarness(1, in_memory=True) as c:
            srv = c[0]
            _seed(srv.api, "ga", n_shards=2)
            _seed(srv.api, "gb", n_shards=2)
            for idx in ("ga", "gb", "ga"):
                srv.api.query(idx, "Count(Row(f=0))")
            srv.publish_cache_gauges()
            snap = srv.stats.registry.snapshot()
            per_index = {
                k: v
                for k, v in snap.items()
                if k.startswith("hbm.resident_bytes;")
            }
            assert per_index, snap.keys()
            assert sum(per_index.values()) == snap["devcache.resident_bytes"]
            assert DEVICE_CACHE.bytes_used == snap["devcache.resident_bytes"]

    def test_deleted_index_leaves_the_device_ledger(self, paging_env):
        """View-level stacks (row stacks, tally bundles) are owned by
        the view token: index deletion must drop them from the device
        cache so the dead tenant's label cannot resurrect."""
        with ClusterHarness(1, in_memory=True) as c:
            srv = c[0]
            _seed(srv.api, "gonner", n_shards=2)
            srv.api.query("gonner", "Count(Row(f=0))")
            assert DEVICE_CACHE.index_resident_bytes().get("gonner", 0) > 0
            srv.api.delete_index("gonner")
            assert DEVICE_CACHE.index_resident_bytes().get("gonner", 0) == 0

    def test_zombie_pins_cannot_resurrect_a_dropped_label(self, paging_env):
        """Delete an index while a dispatch still pins its extents: the
        invalidated-while-pinned (zombie) bytes stay on the ledger by
        design, but drop_index must re-bucket their attribution to "-"
        so the next gauge publish cannot re-create the dropped per-index
        series — while the per-index sum keeps equaling the ledger."""
        arr = np.zeros(64, np.uint32)
        key = ("zomb", 0)
        DEVICE_CACHE.put(key, arr, index="ztenant")
        assert DEVICE_CACHE.pin_if_present(key)
        DEVICE_CACHE.invalidate(key)  # in-flight: bytes become zombie
        assert DEVICE_CACHE.index_resident_bytes()["ztenant"] == arr.nbytes
        hbm_res.drop_index("ztenant")  # the delete-index GC hook
        by_index = DEVICE_CACHE.index_resident_bytes()
        assert "ztenant" not in by_index
        # sum invariant survives: the zombie bytes report unattributed
        assert by_index.get("-", 0) == arr.nbytes
        assert sum(by_index.values()) == DEVICE_CACHE.bytes_used
        DEVICE_CACHE.unpin(key)  # last unpin releases the zombie bytes
        assert DEVICE_CACHE.bytes_used == 0
        assert "ztenant" not in DEVICE_CACHE.index_resident_bytes()


# ---------------------------------------------------------------------------
# label GC: a churning tenant set cannot leak metric series
# ---------------------------------------------------------------------------


class TestLabelGC:
    def test_create_delete_100_indexes_returns_to_baseline(self):
        # generous tenant limits: the quota machinery runs (per-index
        # gauges, rate buckets, quota ledgers) without ever shedding,
        # so the churn also proves the tenant series and bucket state GC
        with ClusterHarness(
            1,
            in_memory=True,
            tenant_default_qps=1e9,
            tenant_default_hbm_bytes=1 << 30,
            tenant_default_cache_bytes=1 << 30,
        ) as c:
            srv = c[0]

            from pilosa_tpu.core.resultcache import RESULT_CACHE

            def churn(idx):
                _seed(srv.api, idx, n_shards=1, rows=1)
                # query TWICE: the repeat stores+serves a result-cache
                # entry, so the churn also exercises cache.* per-index
                # attribution and its cache.resident_bytes{index} series
                srv.api.query(idx, "Count(Row(f=0))")
                srv.api.query(idx, "Count(Row(f=0))")
                # a live subscription per tenant: the delete must close
                # it and GC its coherence.subscriptions{index} series
                sub = srv.api.subscribe(idx, "Count(Row(f=0))")
                srv.publish_cache_gauges()
                assert RESULT_CACHE.stats_snapshot()["by_index"].get(idx, 0) > 0
                srv.api.delete_index(idx)
                assert srv.coherence.poll(sub["id"], -1, 0.0) is None
                srv.publish_cache_gauges()

            # warm-up round creates every GLOBAL series (sched gauges,
            # devcache gauges, class:interactive,index:- lanes, ...)
            churn("warm0")
            baseline = set(srv.stats.registry.snapshot())
            cache_base = RESULT_CACHE.stats_snapshot()["resident_bytes"]
            for i in range(100):
                churn(f"tenant_{i}")
            final = set(srv.stats.registry.snapshot())
            leaked = {k for k in final - baseline if "tenant_" in k}
            assert leaked == set(), sorted(leaked)[:10]
            assert len(final) == len(baseline), (
                sorted(final - baseline)[:10],
                sorted(baseline - final)[:10],
            )
            # cache bytes return to baseline with no tenant attribution
            csnap = RESULT_CACHE.stats_snapshot()
            assert csnap["resident_bytes"] == cache_base
            assert not any(k.startswith("tenant_") for k in csnap["by_index"])
            # the tenant policy's lazy bucket map is GC'd with the index
            assert srv.tenant_policy.bucket_count() == 0
            assert not any(
                k.startswith("tenant_")
                for k in csnap["quota_evictions_by_index"]
            )
            # every churned subscription is gone from the coherence plane
            assert srv.coherence.list_subscriptions() == []
            assert srv.coherence.gauges() == {"leases": 0, "grants": 0}

    def test_release_after_drop_cannot_resurrect_the_series(self):
        """Delete an index while its query is in flight: the release's
        byte decrement lands after drop_index popped the attribution
        key. Re-inserting it (even at 0) would re-emit the gauge and
        re-create the registry series the label GC just removed."""
        from pilosa_tpu.sched.admission import AdmissionController
        from pilosa_tpu.sched.cost import QueryCost
        from pilosa_tpu.utils.stats import StatsClient

        st = StatsClient()
        ctl = AdmissionController(max_concurrent=2, stats=st)
        t = ctl.admit(cost=QueryCost(device_bytes=64), index="gone")
        assert ctl.inflight_bytes_by_index() == {"gone": 64}
        ctl.drop_index("gone")
        st.registry.drop_label("index", "gone")  # the GC hook's other half
        t.release()
        assert ctl.inflight_bytes_by_index() == {}
        held = [
            k for k in st.registry.snapshot() if "index:gone" in k
        ]
        assert held == [], held

    def test_delete_broadcast_gcs_labels_on_peers(self):
        """The delete-index broadcast must GC per-index series on every
        member, not just the coordinator — including the coherence
        plane: leases revoked, grants dropped, subscriptions closed."""
        with ClusterHarness(
            3, replica_n=1, in_memory=True, coherence_lease_duration=30.0
        ) as c:
            _seed(c[0].api, "bye", n_shards=6)
            for _ in range(2):
                c[0].api.query("bye", "Count(Row(f=0))")
            sub = c[0].api.subscribe("bye", "Count(Row(f=0))")
            # the leased fan-out armed mirrors/grants across the cluster
            assert c[0].coherence.gauges()["leases"] >= 1
            assert any(
                s.coherence.gauges()["grants"] >= 1 for s in c.nodes
            )
            # fan-out legs created per-index series on the peers
            assert any(
                "index:bye" in k
                for s in c.nodes
                for k in s.stats.registry.snapshot()
            )
            c[0].api.delete_index("bye")
            assert c[0].coherence.poll(sub["id"], -1, 0.0) is None
            for s in c.nodes:
                assert s.coherence.gauges() == {"leases": 0, "grants": 0}
                assert s.coherence.list_subscriptions() == []
                s.publish_cache_gauges()
                held = [
                    k
                    for k in s.stats.registry.snapshot()
                    if "index:bye" in k
                ]
                assert held == [], (s.node.id, held)


# ---------------------------------------------------------------------------
# utilization timeline
# ---------------------------------------------------------------------------


class TestTimeline:
    def test_sampler_ring_and_rates(self):
        with ClusterHarness(
            1, in_memory=True, telemetry_ring=3,
            telemetry_sample_interval=0.0,  # tick manually
        ) as c:
            srv = c[0]
            _seed(srv.api, "tl", n_shards=1)
            sampler = srv.telemetry.sampler
            first = sampler.sample_once()
            for key in (
                "hbmResidentBytes",
                "hbmPinnedBytes",
                "queueDepth",
                "inflightBytes",
                "inflightBytesByIndex",
                "ingestBits",
                "ingestBitsPerS",
                "queries",
                "queriesPerS",
                "resizePhase",
                "walStagedPositions",
            ):
                assert key in first, key
            assert first["ingestBits"] > 0  # _seed imported bits
            srv.api.query("tl", "Count(Row(f=0))")
            second = sampler.sample_once()
            assert second["queries"] == first["queries"] + 1
            assert second["queriesPerS"] > 0
            for _ in range(4):
                sampler.sample_once()
            snap = sampler.snapshot()
            assert len(snap["samples"]) == 3  # ring bound holds
            assert snap["node"] == srv.node.id

    def test_background_ticker_fills_the_ring(self):
        """The real [telemetry] sampler thread: samples accumulate with
        no scrape and no manual tick."""
        import time

        with ClusterHarness(
            1, in_memory=True, telemetry_sample_interval=0.02,
        ) as c:
            srv = c[0]
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if len(srv.telemetry.sampler.snapshot()["samples"]) >= 2:
                    break
                time.sleep(0.02)
            assert len(srv.telemetry.sampler.snapshot()["samples"]) >= 2
            # the tick refreshed the gauges scrape-free
            assert "devcache.resident_bytes" in srv.stats.registry.snapshot()

    def test_debug_timeline_http_and_sample_param(self):
        with ClusterHarness(
            1, in_memory=True, telemetry_sample_interval=0.0
        ) as c:
            srv = c[0]
            tl = http_json("GET", f"{srv.node.uri}/debug/timeline")
            assert tl["samples"] == []
            tl = http_json(
                "GET", f"{srv.node.uri}/debug/timeline?sample=1"
            )
            assert len(tl["samples"]) == 1

    def test_sampler_refreshes_gauges_without_scrape(self):
        """Satellite fix: the residency gauges must reach the registry
        (hence any statsd backend) from the sampler tick alone — no
        /metrics scrape anywhere."""
        with ClusterHarness(
            1, in_memory=True, telemetry_sample_interval=0.0
        ) as c:
            srv = c[0]
            _seed(srv.api, "gv", n_shards=1)
            srv.api.query("gv", "Count(Row(f=0))")
            assert "devcache.resident_bytes" not in srv.stats.registry.snapshot()
            srv.telemetry.sampler.sample_once()
            snap = srv.stats.registry.snapshot()
            assert snap["devcache.resident_bytes"] >= 0
            assert "hbm.resident_extents" in snap

    def test_cluster_timeline_groups_by_node(self):
        with ClusterHarness(
            3, replica_n=1, in_memory=True,
            telemetry_sample_interval=0.0,
        ) as c:
            for s in c.nodes:
                s.telemetry.sampler.sample_once()
            merged = http_json(
                "GET", f"{c[0].node.uri}/cluster/timeline"
            )
            assert set(merged["nodes"]) == {"node0", "node1", "node2"}
            for nid, row in merged["nodes"].items():
                assert row["stale"] is False
                assert len(row["samples"]) == 1


# ---------------------------------------------------------------------------
# /cluster/health
# ---------------------------------------------------------------------------


class TestClusterHealth:
    def test_healthy_cluster_reports_ok(self):
        with ClusterHarness(3, replica_n=1, in_memory=True) as c:
            h = http_json("GET", f"{c[0].node.uri}/cluster/health")
            assert h["status"] == "ok"
            assert h["reasons"] == []
            assert len(h["nodes"]) == 3
            assert all(n["reachable"] for n in h["nodes"])
            # /status links the verdict
            st = http_json("GET", f"{c[0].node.uri}/status")
            assert st["health"] == "/cluster/health"
            assert "walStagedPositions" in st

    def test_down_replica_degrades(self):
        with ClusterHarness(3, replica_n=2, in_memory=True) as c:
            c.stop_node(2)
            h = http_json("GET", f"{c[0].node.uri}/cluster/health")
            assert h["status"] == "degraded"
            assert any("node2 unreachable" in r for r in h["reasons"])
            row = [n for n in h["nodes"] if n["id"] == "node2"][0]
            assert row["reachable"] is False

    def test_unreachable_at_replica_n_is_critical(self):
        with ClusterHarness(3, replica_n=1, in_memory=True) as c:
            c.stop_node(1)
            h = http_json("GET", f"{c[0].node.uri}/cluster/health")
            assert h["status"] == "critical"
            assert any("no live owner" in r for r in h["reasons"])

    def test_pending_repairs_surface(self):
        with ClusterHarness(1, in_memory=True) as c:
            srv = c[0]
            srv.holder.record_pending_repair("idx", 0, "ghost")
            h = http_json("GET", f"{srv.node.uri}/cluster/health")
            assert h["status"] == "degraded"
            assert h["pendingRepairs"] == 1
            assert any("pending replica repair" in r for r in h["reasons"])


# ---------------------------------------------------------------------------
# acceptance: 3-node federated rollup
# ---------------------------------------------------------------------------


def _hist_for(state, name, index):
    """One node's exported query_ms histogram dict for an index tag."""
    for n, t, d in state.get("hists", ()):
        if n == name and f"index:{index}" in t:
            return d
    return None


def _cluster_bucket_counts(text, index):
    """[(le, cum)] + count for query_ms{index=...} from exposition."""
    buckets, count = [], None
    for line in text.splitlines():
        if line.startswith("pilosa_tpu_query_ms_bucket") and (
            f'index="{index}"' in line
        ):
            le = line.split('le="', 1)[1].split('"', 1)[0]
            bound = float("inf") if le == "+Inf" else float(le)
            buckets.append((bound, float(line.rsplit(" ", 1)[1])))
        elif line.startswith("pilosa_tpu_query_ms_count") and (
            f'index="{index}"' in line
        ):
            count = float(line.rsplit(" ", 1)[1])
    return buckets, count


def test_three_node_rollup_acceptance():
    """ISSUE 8 acceptance: (a) /cluster/metrics per-index query_ms
    counts equal the sum of the three per-node counts exactly; (b) the
    cluster p99 derives from merged buckets — one seeded-slow node
    pulls it up even though the other two nodes' p99s are fast; (c)
    killing one node degrades /cluster/overview to stale-marked data
    for that peer without failing the endpoint."""
    with ClusterHarness(3, replica_n=1, in_memory=True) as c:
        uri = c[0].node.uri
        _seed(c[0].api, "ten_a", n_shards=6)
        _seed(c[0].api, "ten_b", n_shards=6)
        for _ in range(4):
            http_json(
                "POST", f"{uri}/index/ten_a/query",
                {"query": "Count(Row(f=0))"},
            )
        for _ in range(2):
            http_json(
                "POST", f"{uri}/index/ten_b/query",
                {"query": "Count(Row(f=1))"},
            )
        # seeded skew: node2 observed slow ten_a queries (5 s each);
        # enough of them that the true cluster p99 sits in the slow
        # regime while node0/node1 report fast p99s
        for _ in range(3):
            c[2].stats.with_tags("index:ten_a").timing("query_ms", 5.0)

        # (a) exact per-index counter merge: cluster == sum of nodes
        node_states = [
            http_json("GET", f"{s.node.uri}/internal/stats")["stats"]
            for s in c.nodes
        ]
        per_node = [
            _hist_for(st, "query_ms", "ten_a") for st in node_states
        ]
        want_count = sum(int(d["count"]) for d in per_node if d)
        want_sum = sum(float(d["sum"]) for d in per_node if d)
        assert want_count >= 4 + 3  # coordinator + seeded observations

        with urllib.request.urlopen(
            f"{uri}/cluster/metrics", timeout=30
        ) as r:
            text = r.read().decode()
        buckets, count = _cluster_bucket_counts(text, "ten_a")
        assert count == want_count  # EXACT, not approximate
        assert buckets[-1][0] == float("inf")
        assert buckets[-1][1] == count
        m = [
            ln
            for ln in text.splitlines()
            if ln.startswith("pilosa_tpu_query_ms_sum")
            and 'index="ten_a"' in ln
        ]
        assert float(m[0].rsplit(" ", 1)[1]) == pytest.approx(
            want_sum, rel=1e-9
        )
        # ten_b series exist and are disjoint from ten_a's
        _, count_b = _cluster_bucket_counts(text, "ten_b")
        assert count_b and count_b < count

        # (b) merged-bucket p99: the slow node dominates the tail
        overview = http_json("GET", f"{uri}/cluster/overview")
        ten_a = overview["indexes"]["ten_a"]
        assert ten_a["queryMsP99"] > 1000  # seeded 5 s observations
        assert ten_a["queryMsP50"] < ten_a["queryMsP99"]
        # the two fast nodes' own p99s do NOT show the tail
        for s in (c[0], c[1]):
            fast = s.stats.registry.quantile(
                "query_ms", 0.99, ("index:ten_a",)
            )
            assert fast < 1000, (s.node.id, fast)
        assert overview["totals"]["queries"] > 0
        assert {n["id"] for n in overview["nodes"]} == {
            "node0", "node1", "node2",
        }
        assert not any(n["stale"] for n in overview["nodes"])

        # (c) kill node2: the rollup degrades, never 500s
        c.stop_node(2)
        degraded = http_json("GET", f"{uri}/cluster/overview")
        rows = {n["id"]: n for n in degraded["nodes"]}
        assert rows["node2"]["stale"] is True
        assert rows["node2"]["ageS"] is not None
        assert rows["node0"]["stale"] is False
        # the cached snapshot keeps contributing: ten_a's seeded tail
        # survives in the merged quantile
        assert degraded["indexes"]["ten_a"]["queryMsP99"] > 1000
        with urllib.request.urlopen(
            f"{uri}/cluster/metrics", timeout=30
        ) as r:
            text2 = r.read().decode()
        assert 'pilosa_tpu_cluster_peer_stale{node="node2"} 1' in text2
        assert 'pilosa_tpu_cluster_peer_stale{node="node0"} 0' in text2
        # health sees it too (replica_n=1 -> critical)
        h = http_json("GET", f"{uri}/cluster/health")
        assert h["status"] == "critical"


def test_malformed_peer_body_degrades_stale_not_500(monkeypatch):
    """A peer answering 200 with a non-JSON body (mid-restart, error
    page from a proxy in front of it) must degrade exactly like a dead
    peer — the rollup endpoints promise staleness markers, never a
    500."""
    import json as _json

    with ClusterHarness(2, replica_n=1, in_memory=True) as c:
        srv = c[0]

        def garbled(uri, timeout=5.0):
            raise _json.JSONDecodeError("Expecting value", "<html>", 0)

        monkeypatch.setattr(srv.client, "node_stats", garbled)
        monkeypatch.setattr(srv.client, "node_timeline", garbled)
        ov = http_json("GET", f"{srv.node.uri}/cluster/overview")
        rows = {n["id"]: n for n in ov["nodes"]}
        assert rows["node1"]["stale"] is True
        assert rows["node0"]["stale"] is False
        tl = http_json("GET", f"{srv.node.uri}/cluster/timeline")
        assert tl["nodes"]["node1"]["stale"] is True

        # valid JSON of the WRONG SHAPE (proxy maintenance page) must
        # degrade the same way, not AttributeError into a 500
        def listy(uri, timeout=5.0):
            return ["maintenance"]

        monkeypatch.setattr(srv.client, "node_stats", listy)
        monkeypatch.setattr(srv.client, "node_timeline", listy)
        ov = http_json("GET", f"{srv.node.uri}/cluster/overview")
        assert {n["id"]: n["stale"] for n in ov["nodes"]}["node1"] is True
        tl = http_json("GET", f"{srv.node.uri}/cluster/timeline")
        assert tl["nodes"]["node1"]["stale"] is True


def test_internal_stats_export_is_mergeable_shape():
    with ClusterHarness(1, in_memory=True) as c:
        srv = c[0]
        _seed(srv.api, "ms", n_shards=1)
        srv.api.query("ms", "Count(Row(f=0))")
        payload = http_json("GET", f"{srv.node.uri}/internal/stats")
        assert payload["node"] == srv.node.id
        st = payload["stats"]
        assert st["histBuckets"] == len(HIST_BOUNDS) + 1
        merged = Registry()
        merged.merge_state(st)
        assert merged.quantile("query_ms", 0.5, ("index:ms",)) >= 0
        assert math.isfinite(payload["collectedAt"])


def test_runtime_poller_publishes_without_walking_the_heap():
    """The poller's gauges appear, and no module of the package asks the
    collector for every live object: `gc.get_objects()` holds a reference
    to each — a `tuple(<generator>)` half built on a request thread then
    fails its resize with SystemError (seen as a 500 on a Sum in the taxi
    cell) — and stops every thread while it walks a large heap."""
    import ast
    import pathlib
    import time

    import pilosa_tpu

    with ClusterHarness(1, in_memory=True, metric_poll_interval=0.02) as c:
        deadline = time.monotonic() + 5.0
        snap = {}
        while "runtime.threads" not in snap and time.monotonic() < deadline:
            time.sleep(0.02)
            snap = c[0].stats.registry.snapshot()
        assert {"runtime.threads", "runtime.max_rss_kb"} <= set(snap)
        assert "ticker.error" not in snap
    root = pathlib.Path(pilosa_tpu.__file__).parent
    walkers = [
        f"{path.relative_to(root)}:{node.lineno}"
        for path in sorted(root.rglob("*.py"))
        for node in ast.walk(ast.parse(path.read_text()))
        if isinstance(node, ast.Attribute) and node.attr == "get_objects"
    ]
    assert not walkers, walkers
