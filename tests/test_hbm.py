"""HBM residency manager tests (ISSUE 4 tentpole): extent-granular
paging, pinning, prefetch, gauges, and the /debug/pprof satellite.

The acceptance property: with an HBM budget BELOW a query's working set,
the second run of the same query re-uploads only the evicted extents'
bytes — never the whole stack set (whole-set re-staging per query is
the cliff this layer exists to remove).
"""

import threading
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.core.devcache import DEVICE_CACHE
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import Executor
from pilosa_tpu.hbm import residency as hbm_res
from pilosa_tpu.hbm.prefetch import Prefetcher
from pilosa_tpu.parallel import mesh as pmesh
from pilosa_tpu.pql import parse
from pilosa_tpu.sched.admission import AdmissionController
from pilosa_tpu.server.node import NodeServer
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_ROW


@pytest.fixture
def paging_env():
    """Single-device staging (no mesh), clean extent stats, restored
    budget/extent-rows — the deterministic environment the paging
    assertions need."""
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


def _populated_executor(n_rows: int, n_shards: int, index: str = "hbmx"):
    h = Holder().open()
    idx = h.create_index(index)
    f = idx.create_field("f", FieldOptions())
    rng = np.random.default_rng(5)
    for r in range(n_rows):
        for s in range(n_shards):
            f.import_row_words(
                r, s, rng.integers(0, 2**32, WORDS_PER_ROW).astype(np.uint32)
            )
    return Executor(h), h


class TestExtentPaging:
    def test_partial_restage_under_budget_pressure(self, paging_env):
        """THE acceptance test: budget one-quarter short of the working
        set -> run 2 re-uploads exactly the deficit, not the full set."""
        row_bytes = WORDS_PER_ROW * 4
        S, EXT_ROWS, N_ROWS = 8, 2, 8
        hbm_res.configure(extent_rows=EXT_ROWS)
        ext_bytes = EXT_ROWS * row_bytes
        stack_bytes = S * row_bytes  # 4 extents per row stack
        ws = N_ROWS * stack_bytes  # 32 extents
        budget = 24 * ext_bytes  # holds 24 of 32 extents
        # the executor's _stack_guard chunks stacks over budget/4; the
        # geometry must keep one stack under that so lowering stays whole
        assert stack_bytes <= budget // 4
        DEVICE_CACHE.budget_bytes = budget

        ex, _h = _populated_executor(N_ROWS, S)
        q = (
            "Count(Union("
            + ", ".join(f"Row(f={r})" for r in range(N_ROWS))
            + "))"
        )
        # evicted_extent_bytes / restage_bytes are CUMULATIVE process
        # counters: assert on deltas, not absolutes
        snap0 = hbm_res.stats_snapshot()
        got1 = ex.execute("hbmx", q)[0]
        snap1 = hbm_res.stats_snapshot()
        deficit = ws - budget
        # cold run staged the whole working set ...
        assert snap1["restage_bytes"] - snap0["restage_bytes"] == ws
        # ... and settling back under budget evicted exactly the deficit
        evicted1 = (
            snap1["evicted_extent_bytes"] - snap0["evicted_extent_bytes"]
        )
        assert evicted1 == deficit
        assert DEVICE_CACHE.bytes_used <= budget
        # no pins survive the dispatch
        assert snap1["pinned_bytes"] == 0

        from pilosa_tpu.core.resultcache import RESULT_CACHE

        RESULT_CACHE.reset()  # run 2 must exercise extent re-staging
        got2 = ex.execute("hbmx", q)[0]
        assert got2 == got1
        snap2 = hbm_res.stats_snapshot()
        restage2 = snap2["restage_bytes"] - snap1["restage_bytes"]
        # the acceptance inequality: re-staged bytes on run 2 are bounded
        # by the evicted extents' bytes — and equal the deficit exactly
        assert restage2 <= evicted1
        assert restage2 == deficit
        assert restage2 < ws // 2  # nowhere near whole-set churn

    def test_resident_budget_means_zero_restage(self, paging_env):
        """Budget >= working set: the second run uploads nothing."""
        hbm_res.configure(extent_rows=2)
        DEVICE_CACHE.budget_bytes = 1 << 30
        ex, _h = _populated_executor(4, 8)
        q = "Count(Union(Row(f=0), Row(f=1), Row(f=2), Row(f=3)))"
        ex.execute("hbmx", q)
        snap1 = hbm_res.stats_snapshot()
        ex.execute("hbmx", q)
        snap2 = hbm_res.stats_snapshot()
        assert snap2["restage_bytes"] == snap1["restage_bytes"]

    def test_extent_and_monolithic_results_agree(self, paging_env):
        """Extent-assembled operands must be bit-identical to monolithic
        staging — same counts whatever the paging granularity."""
        DEVICE_CACHE.budget_bytes = 1 << 30
        ex, _h = _populated_executor(3, 7)
        q = "Count(Intersect(Row(f=0), Row(f=1)))Count(Xor(Row(f=1), Row(f=2)))"
        hbm_res.configure(extent_rows=0)  # monolithic
        DEVICE_CACHE.clear()
        want = ex.execute("hbmx", q)
        for rows in (1, 2, 3, 16):
            hbm_res.configure(extent_rows=rows)
            DEVICE_CACHE.clear()
            assert ex.execute("hbmx", q) == want, f"extent_rows={rows}"

    def test_write_invalidates_extents(self, paging_env):
        """A write to a covered fragment must invalidate the row's extent
        set — the next query sees the new bits, not a stale slice."""
        hbm_res.configure(extent_rows=2)
        DEVICE_CACHE.budget_bytes = 1 << 30
        ex, h = _populated_executor(1, 8)
        f = h.index("hbmx").field("f")
        f.set_bit(5, 0)
        assert ex.execute("hbmx", "Count(Row(f=5))")[0] == 1
        # second write lands in a DIFFERENT shard: only stale extents may
        # be served if invalidation missed — the count would stay 1
        f.set_bit(5, 2 * SHARD_WIDTH + 7)
        assert ex.execute("hbmx", "Count(Row(f=5))")[0] == 2

    def test_dirty_extent_single_shard_write(self, paging_env):
        """ISSUE 5 acceptance: warm an 8-extent stack, write ONE bit into
        one shard, re-run the count — the restage delta is exactly the
        covering extent's bytes (not the whole stack), and the result
        matches a cold full re-stage."""
        hbm_res.configure(extent_rows=1)  # 8 shards -> 8 extents
        DEVICE_CACHE.budget_bytes = 1 << 30
        S = 8
        ex, h = _populated_executor(1, S)
        q = "Count(Row(f=0))"
        got1 = ex.execute("hbmx", q)[0]
        snap1 = hbm_res.stats_snapshot()
        # warm repeat: fully resident, zero restage
        assert ex.execute("hbmx", q)[0] == got1
        snap2 = hbm_res.stats_snapshot()
        assert snap2["restage_bytes"] == snap1["restage_bytes"]

        f = h.index("hbmx").field("f")
        changed = f.set_bit(0, 3 * SHARD_WIDTH + 11)  # one bit, shard 3
        got2 = ex.execute("hbmx", q)[0]
        assert got2 == got1 + (1 if changed else 0)  # results stay exact
        snap3 = hbm_res.stats_snapshot()
        delta = snap3["restage_bytes"] - snap2["restage_bytes"]
        ext_bytes = 1 * WORDS_PER_ROW * 4
        stack_bytes = S * WORDS_PER_ROW * 4
        # the acceptance equality: ONLY the covering extent re-staged
        assert delta == ext_bytes
        assert delta < stack_bytes
        # equality vs a cold run: full re-stage computes the same count
        DEVICE_CACHE.clear()
        assert ex.execute("hbmx", q)[0] == got2

    def test_dirty_extent_bulk_ingest_other_row(self, paging_env):
        """A staged bulk import into OTHER rows of two shards no longer
        re-stages even the covering extents: the merge barrier's
        reconciliation (ISSUE 9) patches the resident extents in place
        to the post-merge version keys — the written row is not part of
        the operand, so the patch is a pure re-key with ZERO PCIe bytes
        (the invalidate+restage baseline paid one full extent per
        touched shard)."""
        import numpy as np

        hbm_res.configure(extent_rows=1)
        DEVICE_CACHE.budget_bytes = 1 << 30
        S = 8
        ex, h = _populated_executor(1, S)
        q = "Count(Row(f=0))"
        got1 = ex.execute("hbmx", q)[0]
        snap1 = hbm_res.stats_snapshot()
        f = h.index("hbmx").field("f")
        # staged fast path: bits for row 9 into shards 2 and 5
        f.import_bits(
            np.array([9, 9], np.uint64),
            np.array([2 * SHARD_WIDTH + 1, 5 * SHARD_WIDTH + 1], np.uint64),
        )
        assert ex.execute("hbmx", q)[0] == got1  # row 0 unchanged
        snap2 = hbm_res.stats_snapshot()
        delta = snap2["restage_bytes"] - snap1["restage_bytes"]
        baseline = 2 * WORDS_PER_ROW * 4  # invalidate+restage: two extents
        assert delta == 0, delta  # patched in place: nothing re-shipped
        assert delta < baseline
        assert (
            snap2["extent_patches"] - snap1["extent_patches"] == 2
        )  # one per covering extent
        # equality vs a cold full re-stage
        DEVICE_CACHE.clear()
        assert ex.execute("hbmx", q)[0] == got1

    def test_extent_patch_same_row_content(self, paging_env):
        """ISSUE 9 acceptance: a staged write INTO the warm operand's own
        row is patched into the resident extent ON DEVICE (old words |
        merged delta, re-keyed to the post-merge version) — the query
        sees the new bits with ZERO restage bytes, where the
        invalidate+restage baseline re-shipped the covering extent."""
        import numpy as np

        hbm_res.configure(extent_rows=4)  # 8 shards -> 2 extents
        DEVICE_CACHE.budget_bytes = 1 << 30
        S = 8
        ex, h = _populated_executor(1, S)
        q = "Count(Row(f=0))"
        got1 = ex.execute("hbmx", q)[0]
        snap1 = hbm_res.stats_snapshot()
        f = h.index("hbmx").field("f")
        # two fresh bits in row 0, shard 3: word 0 and a mid-row word
        frag3 = f.view("standard").fragments[3]
        w = frag3.row_words(0).copy()
        free = [
            int(i) * 32 + int(np.flatnonzero((w[i] & (1 << np.arange(32))) == 0)[0])
            for i in np.flatnonzero(w != 0xFFFFFFFF)[:2]
        ]
        f.import_bits(
            np.zeros(len(free), np.uint64),
            np.array([3 * SHARD_WIDTH + c for c in free], np.uint64),
        )
        got2 = ex.execute("hbmx", q)[0]
        assert got2 == got1 + len(free)  # the patched words carry the bits
        snap2 = hbm_res.stats_snapshot()
        assert snap2["restage_bytes"] == snap1["restage_bytes"]  # no PCIe re-stage
        assert snap2["extent_patches"] - snap1["extent_patches"] == 1
        # equality vs a cold full re-stage of the patched stack
        DEVICE_CACHE.clear()
        assert ex.execute("hbmx", q)[0] == got2

    def test_subset_barrier_preserves_other_shards_patchability(
        self, paging_env
    ):
        """A barrier over a SUBSET of shards must not invalidate (or
        forget) still-patchable extents covering OTHER dirty shards: a
        query population reading shards 0-3 under sustained ingest into
        shards 0-7 would otherwise silently defeat in-place patching
        for the 4-7 population (code-review finding on ISSUE 9)."""
        import numpy as np

        hbm_res.configure(extent_rows=4)  # 8 shards -> 2 extents
        DEVICE_CACHE.budget_bytes = 1 << 30
        S = 8
        ex, h = _populated_executor(1, S)
        q = "Count(Row(f=0))"
        got1 = ex.execute("hbmx", q)[0]  # both extents resident
        snap1 = hbm_res.stats_snapshot()
        f = h.index("hbmx").field("f")
        v = f.view("standard")
        # stage row-9 bits into BOTH extents' shards
        f.import_bits(
            np.array([9, 9], np.uint64),
            np.array([1 * SHARD_WIDTH + 1, 5 * SHARD_WIDTH + 1], np.uint64),
        )
        # subset barrier: only shard 1's fragment (extent 0)
        v.sync_pending(frags=[v.fragments[1]])
        assert 5 in v._dirty_staged  # shard 5 stays remembered
        # shard 5's own barrier still patches its extent in place
        v.sync_pending(frags=[v.fragments[5]])
        assert ex.execute("hbmx", q)[0] == got1
        snap2 = hbm_res.stats_snapshot()
        assert snap2["restage_bytes"] == snap1["restage_bytes"], (
            "subset barrier forced an extent re-stage"
        )
        assert snap2["extent_patches"] - snap1["extent_patches"] == 2
        DEVICE_CACHE.clear()
        assert ex.execute("hbmx", q)[0] == got1

    def test_cost_discount_scoped_to_referenced_fields(self, paging_env):
        """Field f's warm residency discounts f-queries only — a cold
        query on field g keeps its full admission byte weight."""
        from pilosa_tpu.core.field import FieldOptions
        from pilosa_tpu.sched import cost as costmod

        hbm_res.configure(extent_rows=2)
        DEVICE_CACHE.budget_bytes = 1 << 30
        ex, h = _populated_executor(2, 8)  # field "f"
        idx = h.index("hbmx")
        g = idx.create_field("g", FieldOptions())
        g.set_bit(1, 7)
        shards = list(range(8))
        cold_g = costmod.estimate(idx, parse("Count(Row(g=1))"), shards)
        cold_f = costmod.estimate(idx, parse("Count(Row(f=0))"), shards)
        assert cold_g.device_bytes > 0
        ex.execute("hbmx", "Count(Row(f=0))")  # f's stack now resident
        warm_f = costmod.estimate(idx, parse("Count(Row(f=0))"), shards)
        cold_g2 = costmod.estimate(idx, parse("Count(Row(g=1))"), shards)
        assert warm_f.device_bytes < cold_f.device_bytes  # f discounted
        assert cold_g2.device_bytes == cold_g.device_bytes  # g untouched

    def test_prefetch_warm_then_hit(self, paging_env):
        """A warm pass staged under prefetching() marks its extents;
        the real query's staging then counts prefetch hits."""
        hbm_res.configure(extent_rows=2)
        DEVICE_CACHE.budget_bytes = 1 << 30
        ex, _h = _populated_executor(2, 8)
        q = "Count(Intersect(Row(f=0), Row(f=1)))"
        with hbm_res.prefetching():
            warmed = ex.warm("hbmx", parse(q))
        assert warmed == 1
        snap = hbm_res.stats_snapshot()
        assert snap["prefetch_staged"] >= 8  # 2 stacks x 4 extents
        assert snap["prefetch_hits"] == 0
        ex.execute("hbmx", q)
        snap2 = hbm_res.stats_snapshot()
        assert snap2["prefetch_hits"] >= 8
        # warm staged it all: the query itself uploaded nothing new
        assert snap2["restage_bytes"] == snap["restage_bytes"]


class TestPrefetcher:
    def test_runs_offered_tasks(self):
        p = Prefetcher(depth=4).start()
        try:
            done = threading.Event()
            p.offer(done.set)
            assert done.wait(5)
        finally:
            p.stop()

    def test_bounded_queue_drops_oldest(self):
        p = Prefetcher(depth=1).start()
        try:
            gate = threading.Event()
            first_running = threading.Event()
            ran: list = []

            def blocker():
                first_running.set()
                gate.wait(5)

            p.offer(blocker)
            assert first_running.wait(5)
            # worker busy: these contend for the single queue slot
            p.offer(lambda: ran.append("a"))
            p.offer(lambda: ran.append("b"))
            gate.set()
            deadline = time.monotonic() + 5
            while not p.idle() and time.monotonic() < deadline:
                time.sleep(0.01)
            time.sleep(0.05)  # let the last popped task finish
            assert p.dropped == 1
            assert ran == ["b"]  # oldest queued offer was shed
        finally:
            p.stop()

    def test_task_errors_are_swallowed(self):
        msgs: list = []
        p = Prefetcher(depth=2, logger=msgs.append).start()
        try:
            done = threading.Event()
            p.offer(lambda: (_ for _ in ()).throw(RuntimeError("boom")))
            p.offer(done.set)
            assert done.wait(5)
            assert any("boom" in m for m in msgs)
        finally:
            p.stop()

    def test_admission_queue_peek_feeds_prefetcher(self):
        """maybe_prefetch offers ONLY when a new arrival would wait."""

        class FakePrefetcher:
            def __init__(self):
                self.offers = []

            def offer(self, warm):
                self.offers.append(warm)
                return True

        ctl = AdmissionController(max_concurrent=1, queue_depth=4)
        fake = ctl.prefetcher = FakePrefetcher()
        assert not ctl.maybe_prefetch(lambda: None)  # idle: no offer
        t = ctl.admit()
        try:
            assert ctl.maybe_prefetch(lambda: None)  # saturated: offered
            assert len(fake.offers) == 1
            assert not ctl.maybe_prefetch(None)  # no warm closure
        finally:
            t.release()
        assert not ctl.maybe_prefetch(lambda: None)  # idle again


class TestServerIntegration:
    @pytest.fixture()
    def server(self):
        srv = NodeServer(None, "hbm-srv", hbm_prefetch_depth=4)
        srv.start()
        yield srv
        srv.stop()

    def test_hbm_gauges_exported_on_metrics(self, server):
        api = server.api
        api.create_index("hg")
        api.create_field("hg", "f")
        f = server.holder.index("hg").field("f")
        rng = np.random.default_rng(1)
        for s in range(4):
            f.import_row_words(
                1, s, rng.integers(0, 2**32, WORDS_PER_ROW).astype(np.uint32)
            )
        assert api.query("hg", "Count(Row(f=1))")[0] > 0
        with urllib.request.urlopen(
            f"{server.node.uri}/metrics", timeout=10
        ) as resp:
            text = resp.read().decode()
        for gauge in (
            "pilosa_tpu_hbm_resident_extents",
            "pilosa_tpu_hbm_pinned_bytes",
            "pilosa_tpu_hbm_restage_bytes",
            "pilosa_tpu_hbm_prefetch_hits",
        ):
            assert gauge in text, gauge
        # a query ran: at least one extent-marked operand is resident
        line = next(
            ln
            for ln in text.splitlines()
            if ln.startswith("pilosa_tpu_hbm_resident_extents ")
        )
        assert float(line.split()[-1]) >= 1

    def test_debug_pprof_profiles_live_queries(self, server):
        api = server.api
        api.create_index("pi")
        api.create_field("pi", "f")
        f = server.holder.index("pi").field("f")
        rng = np.random.default_rng(2)
        for s in range(2):
            f.import_row_words(
                1, s, rng.integers(0, 2**32, WORDS_PER_ROW).astype(np.uint32)
            )
        api.query("pi", "Count(Row(f=1))")  # warm compile
        out = {}

        def capture():
            with urllib.request.urlopen(
                f"{server.node.uri}/debug/pprof?seconds=1", timeout=30
            ) as resp:
                out["text"] = resp.read().decode()

        t = threading.Thread(target=capture)
        t.start()
        # keep queries flowing through the whole capture window
        while t.is_alive():
            api.query("pi", "Count(Row(f=1))")
        t.join(10)
        text = out["text"]
        assert "cProfile capture" in text
        assert "(no queries executed" not in text
        # pstats table header + a function from the query path
        assert "cumulative" in text
        assert "query_response" in text or "execute_response" in text

    def test_debug_pprof_rejects_bad_seconds(self, server):
        import urllib.error

        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"{server.node.uri}/debug/pprof?seconds=abc", timeout=10
            )
        assert ei.value.code == 400
