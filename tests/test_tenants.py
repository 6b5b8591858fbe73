"""Multi-tenant QoS enforcement (pilosa_tpu/sched/tenants.py and its
enforcement points): token-bucket units on an injected clock, override
parsing, admission-time rate/quota shedding on both lanes with derived
Retry-After (the shed-retry-after knob as a floor), second-level
per-index SFQ dequeue order inside a WFQ class, quota-first eviction in
the device cache (including zombie-pinned attribution) and the result
cache, prefetcher gating, X-Pilosa-Quota-* response headers, and the
@slow two-tenant overload soak: the abusive index sheds 429 while
well-behaved tenants keep their latency and their cache residency.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.core.devcache import DeviceCache, new_owner_token
from pilosa_tpu.core.resultcache import ResultCache
from pilosa_tpu.sched.admission import AdmissionController, ShedError
from pilosa_tpu.sched.cost import QueryCost
from pilosa_tpu.sched.tenants import (
    TenantPolicy,
    TokenBucket,
    parse_overrides,
)
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
# token bucket (injected clock, no sleeps)
# ---------------------------------------------------------------------------


class TestTokenBucket:
    def test_starts_full_and_denies_with_refill_seconds(self):
        b = TokenBucket(rate=2.0, burst=2.0, now=0.0)
        assert b.take(1.0, 0.0) == 0.0
        assert b.take(1.0, 0.0) == 0.0
        # empty: one token refills in 1/rate seconds
        assert b.take(1.0, 0.0) == pytest.approx(0.5)

    def test_refills_at_rate_and_caps_at_burst(self):
        b = TokenBucket(rate=2.0, burst=2.0, now=0.0)
        assert b.take(2.0, 0.0) == 0.0
        assert b.take(1.0, 0.25) > 0.0  # only 0.5 tokens back
        assert b.take(1.0, 0.5) == 0.0
        # idling far past the burst window banks nothing extra
        assert b.take(2.0, 100.0) == 0.0
        assert b.take(0.5, 100.0) > 0.0

    def test_refund_clamps_to_burst(self):
        b = TokenBucket(rate=1.0, burst=1.0, now=0.0)
        b.refund(5.0)
        assert b.tokens == 1.0

    def test_peek_consumes_nothing(self):
        b = TokenBucket(rate=1.0, burst=1.0, now=0.0)
        assert b.peek(1.0, 0.0)
        assert b.peek(1.0, 0.0)  # still there
        assert b.take(1.0, 0.0) == 0.0
        assert not b.peek(1.0, 0.0)


# ---------------------------------------------------------------------------
# override parsing (operator config: malformed entries must raise)
# ---------------------------------------------------------------------------


class TestParseOverrides:
    def test_parses_multi_knob_entries(self):
        got = parse_overrides(
            ["a:qps=5;bytes-per-s=1e6", "b:hbm-bytes=65536"]
        )
        assert got == {
            "a": {"qps": 5.0, "bytes-per-s": 1e6},
            "b": {"hbm-bytes": 65536.0},
        }

    def test_blank_entries_skipped(self):
        assert parse_overrides(["", "  "]) == {}

    def test_malformed_entries_raise(self):
        for bad in (
            "no-colon-here",
            ":qps=1",
            "a:frobs=1",
            "a:qps=fast",
            "a:qps",
        ):
            with pytest.raises(ValueError):
                parse_overrides([bad])


# ---------------------------------------------------------------------------
# TenantPolicy units
# ---------------------------------------------------------------------------


class TestTenantPolicy:
    def test_limits_merge_overrides_over_defaults(self):
        pol = TenantPolicy(
            default_qps=10.0,
            default_hbm_bytes=1000,
            overrides=["a:qps=2;cache-bytes=64"],
        )
        a = pol.limits("a")
        assert a.qps == 2.0
        assert a.hbm_bytes == 1000  # default fills the unlisted knob
        assert a.cache_bytes == 64
        b = pol.limits("b")
        assert b.qps == 10.0 and b.cache_bytes == 0

    def test_any_limits(self):
        assert not TenantPolicy().any_limits()
        assert TenantPolicy(default_cache_bytes=1).any_limits()
        assert TenantPolicy(overrides=["a:qps=1"]).any_limits()

    def test_quota_maps(self):
        pol = TenantPolicy(
            default_hbm_bytes=100,
            default_cache_bytes=200,
            overrides=["a:hbm-bytes=7", "b:cache-bytes=9"],
        )
        assert pol.hbm_quota_map() == (100, {"a": 7})
        assert pol.cache_quota_map() == (200, {"b": 9})

    def test_qps_denial_and_refill(self):
        clk = FakeClock()
        pol = TenantPolicy(default_qps=1.0, clock=clk)
        assert pol.acquire("a", 0) is None  # burst token
        denial = pol.acquire("a", 0)
        assert denial is not None
        assert denial.reason == "rate" and denial.limit == "qps"
        assert denial.retry_after == pytest.approx(1.0)
        clk.advance(1.0)
        assert pol.acquire("a", 0) is None

    def test_byte_denial_refunds_the_qps_token(self):
        clk = FakeClock()
        pol = TenantPolicy(
            default_qps=2.0, default_bytes_per_s=100.0, clock=clk
        )
        assert pol.acquire("a", 60) is None
        # second query's bytes don't fit (40 tokens left) — the shed
        # must consume NEITHER budget, so the qps token comes back
        denial = pol.acquire("a", 60)
        assert denial is not None
        assert denial.reason == "bytes" and denial.limit == "bytes-per-s"
        assert denial.retry_after == pytest.approx(0.2)
        # qps burst was 2: one spent on the grant; without the refund
        # this zero-byte acquire would be a rate denial
        assert pol.acquire("a", 0) is None

    def test_oversized_byte_estimate_charged_at_burst(self):
        clk = FakeClock()
        pol = TenantPolicy(default_bytes_per_s=100.0, clock=clk)
        # heavier than the whole bucket: charged the burst, not denied
        # forever (single-oversized rule)
        assert pol.acquire("a", 10_000) is None
        denial = pol.acquire("a", 1)
        assert denial is not None and denial.reason == "bytes"

    def test_throttled_peek_consumes_nothing(self):
        clk = FakeClock()
        pol = TenantPolicy(default_qps=1.0, clock=clk)
        assert not pol.throttled("a")
        assert pol.acquire("a", 0) is None
        assert pol.throttled("a")
        assert pol.throttled("a")  # still just a peek
        clk.advance(1.0)
        assert not pol.throttled("a")
        assert pol.throttled(None) is False

    def test_unlimited_and_indexless_create_no_buckets(self):
        pol = TenantPolicy(default_qps=1.0)
        assert pol.acquire(None, 50) is None
        assert pol.bucket_count() == 0
        unlim = TenantPolicy()
        assert unlim.acquire("a", 50) is None
        assert unlim.bucket_count() == 0

    def test_drop_index_gcs_bucket_state(self):
        pol = TenantPolicy(default_qps=1.0)
        pol.acquire("a", 0)
        pol.acquire("b", 0)
        assert pol.bucket_count() == 2
        pol.drop_index("a")
        assert pol.bucket_count() == 1


# ---------------------------------------------------------------------------
# admission enforcement (both lanes, injected clock)
# ---------------------------------------------------------------------------


def _controller(clk, policy, **kw):
    kw.setdefault("max_concurrent", 2)
    kw.setdefault("stats", StatsClient())
    return AdmissionController(clock=clk, tenants=policy, **kw)


class TestAdmissionEnforcement:
    def test_rate_shed_carries_reason_quota_and_derived_retry_after(self):
        clk = FakeClock()
        ctl = _controller(
            clk, TenantPolicy(default_qps=1.0, clock=clk), retry_after=0.25
        )
        t = ctl.admit(index="t")
        with pytest.raises(ShedError) as ei:
            ctl.admit(index="t")
        e = ei.value
        assert e.reason == "rate"
        assert e.quota_limit == "qps" and e.quota_value == 1.0
        # derived refill (1s) dominates the 0.25 floor
        assert e.retry_after == pytest.approx(1.0)
        snap = ctl.stats.registry.snapshot()
        assert (
            snap.get("sched.shed;class:interactive,index:t,reason:rate")
            == 1
        )
        t.release()
        clk.advance(1.0)
        ctl.admit(index="t").release()
        assert ctl.pending() == (0, 0)

    def test_retry_after_knob_floors_the_derived_value(self):
        clk = FakeClock()
        ctl = _controller(
            clk, TenantPolicy(default_qps=1.0, clock=clk), retry_after=5.0
        )
        t = ctl.admit(index="t")
        with pytest.raises(ShedError) as ei:
            ctl.admit(index="t")
        assert ei.value.retry_after == pytest.approx(5.0)
        t.release()

    def test_rate_buckets_charge_the_leg_lane_too(self):
        clk = FakeClock()
        ctl = _controller(clk, TenantPolicy(default_qps=1.0, clock=clk))
        t = ctl.admit(index="t", leg=True)
        with pytest.raises(ShedError) as ei:
            ctl.admit(index="t", leg=True)
        assert ei.value.reason == "rate"
        t.release()
        assert ctl.pending() == (0, 0)

    def test_untenanted_requests_are_never_rate_limited(self):
        clk = FakeClock()
        ctl = _controller(clk, TenantPolicy(default_qps=1.0, clock=clk))
        for _ in range(5):
            ctl.admit(index=None).release()

    def test_inflight_byte_quota_both_lanes(self):
        clk = FakeClock()
        pol = TenantPolicy(default_inflight_bytes=100, clock=clk)
        ctl = _controller(clk, pol, max_concurrent=4)
        t1 = ctl.admit(index="t", cost=QueryCost(device_bytes=80))
        with pytest.raises(ShedError) as ei:
            ctl.admit(index="t", cost=QueryCost(device_bytes=40))
        e = ei.value
        assert e.reason == "bytes" and e.quota_limit == "inflight-bytes"
        assert e.quota_usage == 80.0 and e.quota_value == 100.0
        # the leg lane polices the same quota on fan-out peers
        with pytest.raises(ShedError) as ei:
            ctl.admit(index="t", cost=QueryCost(device_bytes=40), leg=True)
        assert ei.value.quota_limit == "inflight-bytes"
        # another tenant is unaffected
        ctl.admit(index="u", cost=QueryCost(device_bytes=40)).release()
        t1.release()
        ctl.admit(index="t", cost=QueryCost(device_bytes=40)).release()
        assert ctl.pending() == (0, 0)

    def test_single_query_over_whole_quota_runs_alone(self):
        clk = FakeClock()
        pol = TenantPolicy(default_inflight_bytes=100, clock=clk)
        ctl = _controller(clk, pol, max_concurrent=4)
        big = ctl.admit(index="t", cost=QueryCost(device_bytes=500))
        with pytest.raises(ShedError):
            ctl.admit(index="t", cost=QueryCost(device_bytes=1))
        big.release()
        assert ctl.pending() == (0, 0)

    def test_second_level_sfq_interleaves_same_class_tenants(self):
        """Three queued queries from index a and one from b (same class)
        must NOT drain FIFO: b dequeues right after a's first grant."""
        ctl = AdmissionController(max_concurrent=1, stats=StatsClient())
        filler = ctl.admit(cls="batch", index="filler")
        order = []
        olock = threading.Lock()
        threads = []

        def run(tag, index):
            def go():
                t = ctl.admit(cls="batch", index=index)
                with olock:
                    order.append(tag)
                time.sleep(0.01)
                t.release()

            th = threading.Thread(target=go, daemon=True)
            th.start()
            threads.append(th)

        # enqueue one at a time so arrival order is deterministic
        for tag, index in [
            ("a1", "a"), ("a2", "a"), ("a3", "a"), ("b1", "b")
        ]:
            n = ctl.queue_depth()
            run(tag, index)
            _wait_until(
                lambda n=n: ctl.queue_depth() == n + 1, what="enqueue"
            )
        filler.release()
        for th in threads:
            th.join(10)
        # SFQ: a1 (lowest virtual time, arrived first), then b1 at equal
        # footing beats a2/a3 whose index already banked service
        assert order == ["a1", "b1", "a2", "a3"], order
        assert ctl.pending() == (0, 0)

    def test_throttled_tenant_is_not_prefetch_warmed(self):
        clk = FakeClock()
        pol = TenantPolicy(default_qps=1.0, clock=clk)
        ctl = _controller(clk, pol, max_concurrent=1)
        offers = []

        class FakePrefetcher:
            def offer(self, warm):
                offers.append(warm)
                return True

        ctl.prefetcher = FakePrefetcher()
        # saturate so any arrival would wait (the offer precondition)
        slot = ctl.admit(index="other")
        assert ctl.maybe_prefetch(lambda: None, index="cold") is True
        # spend cold's burst: now throttled -> never offered
        pol.acquire("cold", 0)
        assert ctl.maybe_prefetch(lambda: None, index="cold") is False
        assert len(offers) == 1
        slot.release()

    def test_drop_index_gcs_policy_buckets(self):
        clk = FakeClock()
        pol = TenantPolicy(default_qps=100.0, clock=clk)
        ctl = _controller(clk, pol)
        ctl.admit(index="gone").release()
        assert pol.bucket_count() == 1
        ctl.drop_index("gone")
        assert pol.bucket_count() == 0


# ---------------------------------------------------------------------------
# device-cache residency quotas (quota-first eviction)
# ---------------------------------------------------------------------------


def _arr(words):
    return np.zeros(words, np.uint32)  # 4 bytes each


class TestDevcacheQuota:
    def test_over_quota_owner_pays_before_in_quota_tenants(self):
        c = DeviceCache(budget_bytes=100_000)
        c.configure_quotas(overrides={"a": 600})
        t = new_owner_token()
        c.put((t, "b0"), _arr(64), index="b")  # 256 B, no quota
        c.put((t, "a0"), _arr(64), index="a")
        c.put((t, "a1"), _arr(64), index="a")
        # third insert pushes a to 768 B > 600: its own LRU head goes,
        # b's entry untouched, global budget never under pressure
        c.put((t, "a2"), _arr(64), index="a")
        assert c.get((t, "a0")) is None
        assert c.get((t, "a1")) is not None
        assert c.get((t, "b0")) is not None
        assert c.quota_evictions == 1
        assert c.quota_evictions_by_index() == {"a": 1}
        assert c.stats_snapshot()["quota_evictions"] == 1

    def test_default_quota_applies_to_every_index(self):
        c = DeviceCache(budget_bytes=100_000)
        c.configure_quotas(default_bytes=300)
        t = new_owner_token()
        for idx in ("a", "b"):
            c.put((t, idx, 0), _arr(64), index=idx)
            c.put((t, idx, 1), _arr(64), index=idx)
        # each index independently held to 300 B
        for idx in ("a", "b"):
            assert c.get((t, idx, 0)) is None, idx
            assert c.get((t, idx, 1)) is not None, idx
        assert c.quota_evictions_by_index() == {"a": 1, "b": 1}

    def test_unattributed_entries_are_not_a_tenant(self):
        c = DeviceCache(budget_bytes=100_000)
        c.configure_quotas(default_bytes=100)
        t = new_owner_token()
        c.put((t, 0), _arr(64))  # "-" bucket
        c.put((t, 1), _arr(64))
        assert len(c) == 2 and c.quota_evictions == 0

    def test_oversized_single_entry_kept_while_alone(self):
        c = DeviceCache(budget_bytes=100_000)
        c.configure_quotas(overrides={"a": 100})
        t = new_owner_token()
        c.put((t, "big"), _arr(64), index="a")  # 256 B > quota
        assert c.get((t, "big")) is not None  # all the index holds
        c.put((t, "next"), _arr(8), index="a")
        # more arrived: the oversized entry goes (LRU first)
        assert c.get((t, "big")) is None
        assert c.get((t, "next")) is not None

    def test_configure_quotas_settles_immediately(self):
        c = DeviceCache(budget_bytes=100_000)
        t = new_owner_token()
        c.put((t, 0), _arr(64), index="a")
        c.put((t, 1), _arr(64), index="a")
        c.configure_quotas(overrides={"a": 300})
        assert c.get((t, 0)) is None
        assert c.get((t, 1)) is not None

    def test_pinned_entries_survive_quota_pressure(self):
        c = DeviceCache(budget_bytes=100_000)
        c.configure_quotas(overrides={"a": 300})
        t = new_owner_token()
        c.put((t, 0), _arr(64), index="a")
        assert c.pin_if_present((t, 0))
        c.put((t, 1), _arr(64), index="a")
        # the pinned entry is skipped; the fresh one is `keep`; the
        # quota overshoots transiently like the global budget does
        assert c.get((t, 0)) is not None
        assert c.get((t, 1)) is not None
        c.unpin((t, 0))
        c.put((t, 2), _arr(8), index="a")
        # pins released: pressure settles on the owner's LRU order
        assert c.get((t, 0)) is None
        c.unpin_all([])

    def test_zombie_pinned_bytes_count_against_the_owner(self):
        """Invalidated-while-pinned device memory is still held on the
        tenant's behalf: its bytes weigh in the quota pass until the
        last unpin."""
        c = DeviceCache(budget_bytes=100_000)
        c.configure_quotas(overrides={"a": 300})
        t = new_owner_token()
        c.put((t, 0), _arr(64), index="a")  # 256 B
        assert c.pin_if_present((t, 0))
        c.invalidate((t, 0))  # zombie: gone from lookup, bytes held
        assert c.index_resident_bytes()["a"] == 256
        c.put((t, 1), _arr(32), index="a")  # live 128 + zombie 256 > 300
        c.put((t, 2), _arr(8), index="a")
        # the zombie pushed the owner over: its LIVE lru entry paid
        assert c.get((t, 1)) is None
        assert c.quota_evictions_by_index()["a"] >= 1
        c.unpin((t, 0))
        assert "a" not in c.index_resident_bytes() or (
            c.index_resident_bytes()["a"] < 256
        )

    def test_drop_index_attribution_gcs_ledger_keeps_override(self):
        c = DeviceCache(budget_bytes=100_000)
        c.configure_quotas(overrides={"a": 300})
        t = new_owner_token()
        for i in range(3):
            c.put((t, i), _arr(64), index="a")
        assert c.quota_evictions_by_index() == {"a": 2}
        c.invalidate_owner(t)
        c.drop_index_attribution("a")
        assert c.quota_evictions_by_index() == {}
        # the OVERRIDE is operator config: a recreated index is still
        # held to it
        t2 = new_owner_token()
        for i in range(3):
            c.put((t2, i), _arr(64), index="a")
        assert c.quota_evictions_by_index() == {"a": 2}


# ---------------------------------------------------------------------------
# result-cache tenant quotas
# ---------------------------------------------------------------------------


def _vec(token, shards=(0,), versions=(0,)):
    return (("v", "", "f", "standard", token, tuple(shards), tuple(versions)),)


class TestResultCacheQuota:
    def _cache(self, **kw):
        rc = ResultCache()
        rc.configure(budget_bytes=1 << 20, **kw)
        return rc

    def test_quota_first_eviction_spares_other_tenants(self):
        rc = self._cache()
        rc.put(("b", "q", (0,), False), "count", "idx_b", "q", 1, _vec(1))
        quota = rc.stats_snapshot()["by_index"]["idx_b"] * 2
        rc.configure(tenant_overrides={"idx_a": quota})
        for i in range(4):
            rc.put(
                (i, f"q{i}", (0,), False), "count", "idx_a", f"q{i}", i,
                _vec(i),
            )
        snap = rc.stats_snapshot()
        assert snap["by_index"]["idx_a"] <= quota
        assert snap["by_index"]["idx_b"] > 0  # untouched
        assert snap["quota_evictions"] >= 1
        assert snap["quota_evictions_by_index"]["idx_a"] >= 1
        # the last-stored entries survived (LRU within the owner)
        assert rc.get((3, "q3", (0,), False), _vec(3))[0]
        assert rc.get((0, "q0", (0,), False), _vec(0), recount=False)[0] is False

    def test_entry_bigger_than_quota_never_stored(self):
        rc = self._cache(tenant_default_bytes=8)
        rc.put(("k", "q", (0,), False), "count", "i", "q", 5, _vec(1))
        assert rc.stats_snapshot()["entries"] == 0

    def test_reset_clears_tenant_quotas(self):
        rc = self._cache(tenant_default_bytes=8)
        rc.reset()
        rc.configure(budget_bytes=1 << 20)
        rc.put(("k", "q", (0,), False), "count", "i", "q", 5, _vec(1))
        assert rc.stats_snapshot()["entries"] == 1

    def test_drop_index_gcs_quota_eviction_ledger(self):
        rc = self._cache(tenant_overrides={"idx_a": 1})
        # quota 1 byte: every put rejected, so force the ledger via a
        # default small enough to store then shrink
        rc.configure(tenant_overrides={})
        rc.put(("a", "q", (0,), False), "count", "idx_a", "q", 1, _vec(1))
        nb = rc.stats_snapshot()["by_index"]["idx_a"]
        rc.put(("a2", "q2", (0,), False), "count", "idx_a", "q2", 2, _vec(2))
        rc.configure(tenant_overrides={"idx_a": nb})  # now over: evicts
        assert rc.stats_snapshot()["quota_evictions_by_index"].get(
            "idx_a", 0
        ) >= 1
        rc.drop_index("idx_a")
        assert rc.stats_snapshot()["quota_evictions_by_index"] == {}


# ---------------------------------------------------------------------------
# server integration: 429 detail headers, tenant gauges, overview
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


def _seed(api, index, rows=(1,), n=50):
    api.create_index(index)
    api.create_field(index, "f", {"type": "set"})
    for r in rows:
        api.import_bits(
            index, "f",
            np.full(n, r, np.uint64),
            np.arange(n, dtype=np.uint64),
        )


def test_quota_shed_carries_429_detail_headers():
    with ClusterHarness(
        1,
        in_memory=True,
        telemetry_sample_interval=0.0,
        shed_retry_after=0.5,
        tenant_overrides=["abuser:qps=1"],
    ) as c:
        srv = c[0]
        uri = srv.node.uri
        _seed(srv.api, "abuser")
        _seed(srv.api, "good")
        status, _ = _post_query(uri, "abuser", "Count(Row(f=1))")
        assert status == 200  # the one-second burst token
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post_query(uri, "abuser", "Count(Row(f=1))")
        e = ei.value
        assert e.code == 429
        assert e.headers.get("X-Pilosa-Quota-Limit") == "qps"
        assert float(e.headers.get("X-Pilosa-Quota-Value")) == 1.0
        # derived bucket refill (~1s) dominates the 0.5 floor
        assert float(e.headers.get("X-Pilosa-Retry-After")) >= 0.5
        assert int(e.headers.get("Retry-After")) >= 1
        e.close()
        # the unlimited tenant is untouched by its neighbor's limit
        status, body = _post_query(uri, "good", "Count(Row(f=1))")
        assert status == 200 and body["results"] == [50]
        # node-saturation sheds keep the reason tag but carry NO quota
        # headers (nothing tenant-specific tripped)
        snap = srv.stats.registry.snapshot()
        assert any(
            "sched.shed" in k and "reason:rate" in k
            and "index:abuser" in k
            for k in snap
        ), sorted(k for k in snap if "shed" in k)


def test_tenant_gauges_publish_only_when_configured():
    with ClusterHarness(
        1, in_memory=True, telemetry_sample_interval=0.0
    ) as c:
        srv = c[0]
        _seed(srv.api, "quiet")
        srv.publish_cache_gauges()
        assert not any(
            k.startswith("tenant.")
            for k in srv.stats.registry.snapshot()
        )
    with ClusterHarness(
        1,
        in_memory=True,
        telemetry_sample_interval=0.0,
        tenant_default_hbm_bytes=1 << 30,
        tenant_overrides=["t0:cache-bytes=4096"],
    ) as c:
        srv = c[0]
        _seed(srv.api, "t0")
        srv.publish_cache_gauges()
        snap = srv.stats.registry.snapshot()
        assert snap.get("tenant.hbm_quota_bytes;index:t0") == 1 << 30
        assert snap.get("tenant.cache_quota_bytes;index:t0") == 4096
        assert snap.get("tenant.inflight_quota_bytes;index:t0") == 0
        # overview rows carry the quota column
        overview = srv.telemetry.cluster_overview()
        row = overview["indexes"]["t0"]
        assert row["quotaBytes"] == 1 << 30
        assert row["quotaEvictions"] >= 0


# ---------------------------------------------------------------------------
# overload soak (@slow): one abusive tenant among well-behaved ones
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_two_tenant_overload_soak():
    """N tenants, one abusive (tight query loop, no backoff) with a qps
    and an HBM quota; the rest issue modest repeat Counts. Acceptance:
    the abusive index sheds 429 + informed Retry-After + quota headers;
    the well-behaved tenants see NO sheds, bounded latency, and keep
    their result-cache residency; quota-first eviction pressure lands
    only on the abusive index's devcache attribution."""
    from pilosa_tpu.core.devcache import DEVICE_CACHE
    from pilosa_tpu.core.resultcache import RESULT_CACHE

    good = [f"soak_t{i}" for i in range(4)]
    with ClusterHarness(
        1,
        in_memory=True,
        telemetry_sample_interval=0.0,
        max_concurrent_queries=4,
        admission_queue_depth=16,
        shed_retry_after=0.1,
        tenant_overrides=["soak_abuser:qps=5;hbm-bytes=65536"],
    ) as c:
        srv = c[0]
        uri = srv.node.uri
        for idx in good:
            _seed(srv.api, idx)
        _seed(srv.api, "soak_abuser", rows=(1, 2, 3))
        stop = time.monotonic() + 3.0
        results = {idx: {"ok": 0, "shed": 0, "lat": []} for idx in good}
        results["soak_abuser"] = {"ok": 0, "shed": 0, "lat": []}
        headers_seen = []
        hlock = threading.Lock()

        def tenant_loop(idx, pqls, pause):
            i = 0
            while time.monotonic() < stop:
                t0 = time.monotonic()
                try:
                    status, _ = _post_query(uri, idx, pqls[i % len(pqls)])
                    results[idx]["ok"] += 1
                    results[idx]["lat"].append(time.monotonic() - t0)
                except urllib.error.HTTPError as e:
                    results[idx]["shed"] += 1
                    if e.code == 429:
                        with hlock:
                            headers_seen.append(
                                (
                                    idx,
                                    e.headers.get("X-Pilosa-Quota-Limit"),
                                    e.headers.get("Retry-After"),
                                )
                            )
                    e.close()
                i += 1
                if pause:
                    time.sleep(pause)

        threads = [
            threading.Thread(
                target=tenant_loop,
                args=(idx, ["Count(Row(f=1))"], 0.03),
                daemon=True,
            )
            for idx in good
        ] + [
            threading.Thread(
                target=tenant_loop,
                args=(
                    "soak_abuser",
                    ["Row(f=1)", "Row(f=2)", "Row(f=3)"],
                    0.0,
                ),
                daemon=True,
            )
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(30)

        # the abusive tenant was actually shed, with informed detail
        ab = results["soak_abuser"]
        assert ab["shed"] > 0, results
        assert ab["ok"] <= 5 * 3.0 + 6  # rate-limited to ~qps * wall
        quota_sheds = [h for h in headers_seen if h[0] == "soak_abuser"]
        assert quota_sheds and all(
            lim == "qps" and int(ra) >= 1 for _, lim, ra in quota_sheds
        ), quota_sheds[:5]
        # well-behaved tenants: zero sheds, every query answered, tail
        # latency bounded (generous: CI boxes are noisy)
        for idx in good:
            r = results[idx]
            assert r["shed"] == 0, (idx, r)
            assert r["ok"] > 0, (idx, r)
            lat = sorted(r["lat"])
            p99 = lat[min(len(lat) - 1, int(0.99 * len(lat)))]
            assert p99 < 5.0, (idx, p99)
        # quota-first eviction pressure landed ONLY on the abuser: its
        # three distinct row operands cannot fit a 64 KiB quota
        qev = DEVICE_CACHE.quota_evictions_by_index()
        assert qev.get("soak_abuser", 0) > 0, qev
        assert set(qev) <= {"soak_abuser"}, qev
        # the good tenants' cached repeats survived the abuse
        by_index = RESULT_CACHE.stats_snapshot()["by_index"]
        for idx in good:
            assert by_index.get(idx, 0) > 0, by_index
        # shed reasons on /metrics: the abuser's rate sheds are tagged
        snap = srv.stats.registry.snapshot()
        assert any(
            "sched.shed" in k
            and "index:soak_abuser" in k
            and "reason:rate" in k
            for k in snap
        )
