"""Headline benchmark: BASELINE configs on a 1-billion-column index.

Reports BOTH of VERDICT round-1's requested numbers:
- device: the raw compiled kernel for Count(Intersect(Row,Row)) over the
  954-shard [S, W] stacks, batch-256 salted dispatches so the blocking
  host read (one synchronisation) amortizes to noise; this is the
  HBM-roofline number (achieved GB/s reported in extras).
- system: the same query as a PQL string through api.query -> Executor ->
  compiled stacked plan (BASELINE config #1's query path), timed end to
  end. Each query is one device dispatch + one blocking host read;
  extras report the measured dispatch+sync round trip (rtt_ms)
  alongside. The cross-request amortization story is
  system_concurrent8_ms: 8 client threads sharing dispatches through
  the group-commit batcher (exec/batcher.py) — per-query latency
  approaches rtt/8 + device.

Also recorded (extras):
- config #2: TopN(f, n=100) over all 954 shards (zero-dispatch host
  metadata path) and filtered TopN (r5: ONE device read per query —
  one-pass select + sparse gather tally, exec/executor.py).
- config #3: BSI Sum over the full index (one stacked dispatch).
- config #4: GroupBy over 3 fields x 64 shards (192 groups), system ms.
- config #5: mesh_scaling — Count/Union/Xor multi-query dispatch on a
  virtual 1/2/4/8-device CPU mesh (the same NamedSharding program the
  multichip dryrun compiles; a trend stand-in until real multi-chip).
- hbm_evict_count_ms: the count query with the HBM budget forced below
  the working set — the eviction path must stay correct and the cliff is
  recorded (VERDICT r4 weak #5).

The reference publishes no absolute numbers (BASELINE.md "published: {}"),
so vs_baseline is measured on the spot: the same popcount(a & b) with
vectorized numpy on the host CPU — the reference's execution model
(per-shard CPU bitmap math) minus its Python/HTTP overheads, i.e. a
generous stand-in for the Go engine. vs_baseline = CPU / TPU-device.

Prints ONE json line: {"metric", "value", "unit", "vs_baseline", "extras"}.
"""

import json
import os
import subprocess
import sys
import threading
import time

BATCH = int(os.environ.get("PILOSA_TPU_BENCH_BATCH", "256"))
WINDOWS = 4
N_COLS = int(os.environ.get("PILOSA_TPU_BENCH_COLS", "1000000000"))
BSI_DEPTH = 8
GB_SHARDS = 64  # config 4 geometry
MIXED_SECONDS = float(os.environ.get("PILOSA_TPU_BENCH_MIXED_S", "3.0"))
MIXED_SHARDS = 64  # sustained mixed read/write geometry
TQ_SHARDS = 8  # time-quantum range-query geometry


def _median_ms(fn, reps):
    import numpy as np

    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        out.append((time.perf_counter() - t0) * 1000)
    return float(np.median(out))


def mesh_scaling_main():
    """Config 5 stand-in (runs in a CPU subprocess): the multi-Count
    stacked-plan dispatch on a virtual 1/2/4/8-device mesh. Prints one
    JSON list of {devices, mq4_ms} rows."""
    from pilosa_tpu.utils.cpuonly import force_cpu

    force_cpu(8)

    import jax
    import numpy as np

    from pilosa_tpu.core.devcache import DEVICE_CACHE
    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.parallel import mesh as pmesh
    from pilosa_tpu.shardwidth import WORDS_PER_ROW

    from pilosa_tpu.core.resultcache import RESULT_CACHE

    # scaling numbers measure the compiled dispatch, not the result
    # cache's revalidation fast path (which would serve every repeat)
    RESULT_CACHE.configure(budget_bytes=0)

    n_shards = 64
    rng = np.random.default_rng(3)
    h = Holder().open()
    idx = h.create_index("ms")
    f = idx.create_field("f", FieldOptions())
    for s in range(n_shards):
        f.import_row_words(1, s, rng.integers(0, 2**32, WORDS_PER_ROW, np.uint32))
        f.import_row_words(2, s, rng.integers(0, 2**32, WORDS_PER_ROW, np.uint32))
    ex = Executor(h)
    q = (
        "Count(Intersect(Row(f=1), Row(f=2)))"
        "Count(Union(Row(f=1), Row(f=2)))"
        "Count(Xor(Row(f=1), Row(f=2)))"
        "Count(Difference(Row(f=1), Row(f=2)))"
    )
    rows = []
    truth = None
    for n in (1, 2, 4, 8):
        # pure shard-axis mesh: config 5 is about scaling the shard
        # (data-parallel) dimension; the default 2D factoring puts a
        # cols split at n=4 that adds collective overhead without adding
        # shard parallelism (the multichip dryrun certifies the 2D mesh)
        pmesh.set_active_mesh(
            pmesh.make_mesh(jax.devices()[:n], shards_axis=n) if n > 1 else None
        )
        DEVICE_CACHE.clear()  # rebuild stacks under the new sharding
        got = ex.execute("ms", q)  # warm: compile + stack build
        if truth is None:
            truth = got
        assert got == truth, (n, got, truth)
        # min-of-medians: the shared host's CPU load swings individual
        # medians by 2x; the min is the contention-free estimate
        ms = min(_median_ms(lambda: ex.execute("ms", q), 7) for _ in range(3))
        rows.append({"devices": n, "mq4_ms": round(ms, 3)})
    base = rows[0]["mq4_ms"]
    for r in rows:
        r["speedup"] = round(base / r["mq4_ms"], 2)
    print(json.dumps(rows))


def replicated_bench(seconds=None, writers=8, sync_interval=0.0):
    """Replicated mixed read/write — the benched configuration (ISSUE 12):
    two NodeServers with REAL data dirs (WAL + fsync on the bench host's
    filesystem) and real HTTP between them, replica_n=2, `writers`
    concurrent import threads driving api.import_bits under the strict
    group-commit WAL while a Count stream runs against the same node.
    Reports aggregate logical ingest bits/s (each bit also lands on the
    replica — physical write volume is 2x), the fsyncs-per-import
    coalescing ratio and mean commit-group size from the group-commit
    counters, and query p99 under replicated ingest from the PR 6
    flight-recorder histograms."""
    import shutil
    import tempfile

    import numpy as np

    from pilosa_tpu.core import wal as walmod
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.testing import ClusterHarness

    if seconds is None:
        seconds = float(os.environ.get("PILOSA_TPU_BENCH_REPL_S", "3.0"))
    n_shards = 16
    base = tempfile.mkdtemp(prefix="pilosa-benchrepl-")
    try:
        with ClusterHarness(
            2, replica_n=2, base_dir=base, wal_sync_interval=sync_interval
        ) as c:
            api = c[0].api
            api.create_index("rx")
            api.create_field("rx", "f", {"type": "set"})
            rng = np.random.default_rng(5)
            cols0 = rng.integers(0, n_shards * SHARD_WIDTH, 20_000).astype(
                np.uint64
            )
            api.import_bits("rx", "f", np.ones(len(cols0), np.uint64), cols0)
            api.query("rx", "Count(Row(f=1))")  # warm: stage + compile
            # drop warm-up observations: the histogram must hold ONLY
            # queries issued under replicated ingest pressure
            c[0].stats.registry.drop_label("index", "rx")
            w0 = walmod.stats_snapshot()
            stop = threading.Event()
            wrote = [0] * writers
            calls = [0] * writers
            errs = []

            def writer(t):
                try:
                    wrng = np.random.default_rng(200 + t)
                    batch = 20_000
                    while not stop.is_set():
                        r = wrng.integers(1, 9, batch).astype(np.uint64)
                        cl = wrng.integers(
                            0, n_shards * SHARD_WIDTH, batch
                        ).astype(np.uint64)
                        api.import_bits("rx", "f", r, cl)
                        wrote[t] += batch
                        calls[t] += 1
                except BaseException as e:  # noqa: BLE001 - fail the bench
                    errs.append(e)

            threads = [
                threading.Thread(target=writer, args=(t,))
                for t in range(writers)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            queries = 0
            try:
                while time.perf_counter() - t0 < seconds:
                    api.query("rx", "Count(Row(f=1))")
                    queries += 1
            finally:
                stop.set()
                for t in threads:
                    t.join()
            elapsed = time.perf_counter() - t0
            if errs:  # a dead writer fakes the numbers
                raise errs[0]
            w1 = walmod.stats_snapshot()
            reg = c[0].stats.registry
            n_calls = sum(calls) or 1
            groups = max(w1["commit_groups"] - w0["commit_groups"], 1)
            return {
                "ingest_replicated_bits_mps": round(
                    sum(wrote) / elapsed / 1e6, 2
                ),
                "query_p99_under_replicated_ingest_ms": round(
                    reg.quantile("query_ms", 0.99, tags=("index:rx",)), 3
                ),
                "replicated_queries": queries,
                "replicated_imports": n_calls,
                "wal_fsyncs_per_import": round(
                    (w1["fsyncs"] - w0["fsyncs"]) / n_calls, 3
                ),
                # per WAL APPEND (one per fragment touched per node):
                # the group commit's real coalescing ratio when a call
                # fans across many fragment files
                "wal_fsyncs_per_append": round(
                    (w1["fsyncs"] - w0["fsyncs"])
                    / max(w1["commits"] - w0["commits"], 1),
                    3,
                ),
                "wal_commit_group_mean": round(
                    (w1["commits"] - w0["commits"]) / groups, 2
                ),
            }
    finally:
        shutil.rmtree(base, ignore_errors=True)


def tier_bench():
    """Tiered-storage families (ISSUE 18): demote throughput, cold
    first-query hydration latency, and a beyond-RAM run — corpus bigger
    than the configured host budget, hot working set served from local
    fragments at unchanged latency while the cold rest lives in the
    store. The store is a LocalDirStore behind a SlowStoreWrapper (5 ms
    per op), modeling a same-region object store's round trip rather
    than pretending local-disk numbers are remote numbers."""
    import shutil
    import tempfile

    import numpy as np

    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.shardwidth import WORDS_PER_ROW
    from pilosa_tpu.tier import TierManager, TierPolicy
    from pilosa_tpu.tier.store import LocalDirStore, SlowStoreWrapper

    n_shards = 16
    n_rows = 4
    hot = list(range(4))  # the working set the budget must keep local
    rng = np.random.default_rng(21)
    base = tempfile.mkdtemp(prefix="pilosa-benchtier-")
    try:
        h = Holder(os.path.join(base, "data")).open()
        idx = h.create_index_if_not_exists("tb")
        f = idx.create_field_if_not_exists("f", FieldOptions())
        for s in range(n_shards):
            for row in range(n_rows):
                f.import_row_words(
                    row, s,
                    rng.integers(0, 2**32, WORDS_PER_ROW, dtype=np.uint32),
                )
        v = f.views["standard"]
        for fr in v.fragments.values():
            fr.snapshot()
        store = SlowStoreWrapper(
            LocalDirStore(os.path.join(base, "store")), 0.005
        )
        tier = TierManager(store, TierPolicy("cold"), h,
                           fetch_concurrency=4)

        def hot_read():
            for s in hot:
                v.fragments[s].row_positions(1)

        hot_ms_baseline = _median_ms(hot_read, 5)

        # demote throughput: serialize + upload (2 slow puts each) +
        # capture-drain check + local delete, all 16 fragments
        frags = [v.fragments[s] for s in sorted(v.fragments)]
        t0 = time.perf_counter()
        for fr in frags:
            assert tier.demote_fragment(v, fr)
        demote_s = time.perf_counter() - t0
        demote_bytes = tier.counters()["demote_bytes"]
        local_total = demote_bytes  # snapshots mirror local bytes here

        # cold first-query latency: each shard's FIRST read pays one
        # verified store fetch + adopt (single-flight); median per shard
        lat = []
        for s in range(n_shards):
            t0 = time.perf_counter()
            tier.hydrate(v, s)
            lat.append((time.perf_counter() - t0) * 1000)
        lat.sort()

        # beyond-RAM: budget ~1/3 of the corpus; the hot subset is
        # touched last so LRU budget pressure demotes the cold rest
        for s in range(n_shards):
            if s not in hot:
                tier.touch_many(v, (s,))
        tier.touch_many(v, hot)
        tier.host_budget_bytes = local_total // 3
        cold_n = tier.demote_tick()
        assert cold_n >= n_shards // 2, cold_n  # corpus really > budget
        for s in hot:
            assert s in v.fragments, s  # working set stayed local
        hot_ms_under_budget = _median_ms(hot_read, 5)
        h.close()
        return {
            "tier_demote_mbps": round(demote_bytes / demote_s / 1e6, 1),
            "tier_hydrate_cold_query_ms": round(lat[len(lat) // 2], 3),
            "tier_hydrate_cold_query_p95_ms": round(
                lat[int(len(lat) * 0.95)], 3
            ),
            "tier_corpus_bytes": int(local_total),
            "tier_beyond_budget_cold_fragments": int(cold_n),
            "tier_hot_query_ms_baseline": round(hot_ms_baseline, 3),
            "tier_hot_query_ms_under_budget": round(hot_ms_under_budget, 3),
        }
    finally:
        shutil.rmtree(base, ignore_errors=True)


def coherence_bench():
    """Coherence-plane families (ISSUE 19): the leased fan-out warm hit
    against the wire-revalidate baseline (version-RTT counter deltas
    reported for both — the leased number is asserted ZERO), the
    write-to-delivery latency of subscription pushes, and the in-place
    monotone tree repair of a cached Intersect — each result asserted
    equal to a from-scratch recompute."""
    import numpy as np

    from pilosa_tpu.core.resultcache import RESULT_CACHE
    from pilosa_tpu.exec import plan as planmod_x
    from pilosa_tpu.server import wire
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.testing import ClusterHarness

    n_shards = 8
    reps = 30
    q = "Count(Row(f=1))"

    def seed(api):
        api.create_index("cx")
        api.create_field("cx", "f", {"type": "set"})
        rng = np.random.default_rng(17)
        for r in (1, 2):
            cols = rng.integers(0, n_shards * SHARD_WIDTH, 50_000).astype(
                np.uint64
            )
            api.import_bits(
                "cx", "f", np.full(len(cols), r, np.uint64), cols
            )

    out = {}
    # revalidate baseline: leases off, every warm fan-out hit pays the
    # /internal/versions round (one wire revalidation per hit)
    RESULT_CACHE.reset()
    with ClusterHarness(
        2, in_memory=True, telemetry_sample_interval=0.0,
        max_writes_per_request=0,
    ) as c:
        api = c[0].api
        seed(api)
        for _ in range(3):  # past the candidate gate: stored + hit
            base = api.query("cx", q)[0]
        rv0 = RESULT_CACHE.stats_snapshot()["revalidations"]
        out["fanout_warm_hit_revalidate_ms"] = round(
            _median_ms(lambda: api.query("cx", q), reps), 3
        )
        out["fanout_revalidate_wire_rounds"] = (
            RESULT_CACHE.stats_snapshot()["revalidations"] - rv0
        )

    # leased: the mirror assembles the version vector host-side
    RESULT_CACHE.reset()
    with ClusterHarness(
        2,
        in_memory=True,
        telemetry_sample_interval=0.0,
        coherence_lease_duration=30.0,
        coherence_publish_batch_ms=5.0,
        coherence_sub_poll_interval=0.2,
        max_writes_per_request=0,
    ) as c:
        api = c[0].api
        seed(api)
        got = api.query("cx", q)[0]
        assert got == base, (got, base)
        api.query("cx", q)  # mirror armed
        mgr = c[0].coherence
        rtt0 = mgr.counters_snapshot()["version_rtts"]
        out["fanout_warm_hit_leased_ms"] = round(
            _median_ms(lambda: api.query("cx", q), reps), 3
        )
        snap = mgr.counters_snapshot()
        assert snap["version_rtts"] == rtt0, "leased warm hit paid an RTT"
        out["fanout_leased_version_rtts"] = snap["version_rtts"] - rtt0
        assert snap["lease_hits"] > 0

        # subscription push: a remote-node write to a fresh column of a
        # dedicated row; latency is write-issue -> long-poll delivery,
        # every pushed result checked against the wire recompute
        qs = "Count(Row(f=3))"
        sub = api.subscribe("cx", qs)
        seq = sub["seq"]
        lat = []
        for i in range(20):
            t0 = time.perf_counter()
            c[1].api.import_bits(
                "cx", "f",
                np.array([3], np.uint64), np.array([i], np.uint64),
            )
            snap_s = mgr.poll(sub["id"], after=seq, wait_s=30.0)
            lat.append((time.perf_counter() - t0) * 1000)
            assert snap_s is not None and snap_s["seq"] > seq, snap_s
            seq = snap_s["seq"]
            want = [
                wire.result_to_public_json(r)
                for r in api.query_response("cx", qs).results
            ]
            assert snap_s["result"] == want, (snap_s["result"], want)
        lat.sort()
        out["subscription_push_p50_ms"] = round(lat[len(lat) // 2], 3)
        out["subscription_push_p95_ms"] = round(
            lat[int(len(lat) * 0.95)], 3
        )

    # monotone tree repair: set-only bursts into a cached Intersect are
    # patched host-side from the merge barrier's word deltas — zero
    # compiled dispatches, asserted equal to a cache-dropped recompute
    RESULT_CACHE.reset()
    with ClusterHarness(
        1, in_memory=True, telemetry_sample_interval=0.0,
        max_writes_per_request=0,
    ) as c:
        api = c[0].api
        api.create_index("rx")
        api.create_field("rx", "f", {"type": "set"})
        for r, step in ((1, 2), (2, 3)):
            cols = np.arange(0, 300_000, step, dtype=np.uint64)
            api.import_bits(
                "rx", "f", np.full(len(cols), r, np.uint64), cols
            )
        qr = "Count(Intersect(Row(f=1), Row(f=2)))"
        api.query("rx", qr)
        api.query("rx", qr)  # stored
        # keep the bursts STAGED: the op-count snapshot trigger would
        # merge them inside the import call, leaving the read barrier
        # nothing to repair from (same idiom as the merge rooflines)
        fobj = c[0].holder.index("rx").field("f")
        for fr in fobj.view("standard").fragments.values():
            fr.max_op_n = max(fr.max_op_n, 1 << 22)
        tr0 = RESULT_CACHE.stats_snapshot()["tree_repairs"]
        ev0 = planmod_x.STATS["evals"]
        lat = []
        got = None
        for i in range(10):
            cols = np.arange(
                500_000 + i * 2_000, 500_000 + (i + 1) * 2_000,
                dtype=np.uint64,
            )
            api.import_bits(
                "rx", "f", np.full(len(cols), 1, np.uint64), cols
            )
            t0 = time.perf_counter()
            got = api.query("rx", qr)[0]
            lat.append((time.perf_counter() - t0) * 1000)
        assert RESULT_CACHE.stats_snapshot()["tree_repairs"] >= tr0 + 10
        assert planmod_x.STATS["evals"] == ev0, "tree repair dispatched"
        RESULT_CACHE.reset()
        fresh = api.query("rx", qr)[0]
        assert got == fresh, (got, fresh)
        lat.sort()
        out["monotone_repair_ms"] = round(lat[len(lat) // 2], 3)
    return out


def main():
    os.environ.setdefault("PILOSA_TPU_HBM_BUDGET_MB", "16384")
    # bigger tally tiles at bench scale: fewer filtered-TopN chunk dispatches
    os.environ.setdefault("PILOSA_TPU_GROUPBY_TILE_MB", "1024")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pilosa_tpu.core.devcache import DEVICE_CACHE
    from pilosa_tpu.core.fragment import BSI_EXISTS_BIT, BSI_OFFSET_BIT
    from pilosa_tpu.server.node import NodeServer
    from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_ROW

    n_shards = (N_COLS + SHARD_WIDTH - 1) // SHARD_WIDTH
    shape = (n_shards, WORDS_PER_ROW)
    rng = np.random.default_rng(7)

    # ~25% bit density: dense-ish rows (worst case for the compute path;
    # sparse shards would be skipped by the executor's shard index).
    def dense(density_and=True):
        x = rng.integers(0, 2**32, shape, np.uint32)
        return (x & rng.integers(0, 2**32, shape, np.uint32)) if density_and else x

    a_h = dense()
    b_h = dense()

    # ---- the system under test: a real node (in-memory), PQL via api ----
    # cache_result_mb=0: every repeated-query median below measures the
    # EXECUTION cost (dispatches, staging, reads); the result cache gets
    # its own section, which enables it explicitly and measures the
    # revalidation/repair fast path against these numbers
    srv = NodeServer(None, "bench", cache_result_mb=0)
    srv.start()
    try:
        api = srv.api
        api.create_index("bx")
        api.create_field("bx", "f")
        idx = srv.holder.index("bx")
        f = idx.field("f")
        for s in range(n_shards):
            f.import_row_words(1, s, a_h[s])
            f.import_row_words(2, s, b_h[s])
        # TopN corpus: 30 extra sparse rows so the rank-cache merge is real
        # (timed: this is the position-wise ingest path, the analog of the
        # reference's fragment import benchmarks, fragment_internal_test.go)
        n_bits = 200_000
        rows = rng.integers(3, 33, n_bits).astype(np.uint64)
        cols = rng.integers(0, n_shards * SHARD_WIDTH, n_bits).astype(np.uint64)
        t0 = time.perf_counter()
        f.import_bits(rows, cols)
        ingest_bits_mps = n_bits / (time.perf_counter() - t0) / 1e6
        # steady-state rate: the first call pays fragment creation; the
        # staged fast path's sustained number is what mixed-load serving
        # sees (both are reported)
        rows2 = rng.integers(3, 33, n_bits).astype(np.uint64)
        cols2 = rng.integers(0, n_shards * SHARD_WIDTH, n_bits).astype(np.uint64)
        t0 = time.perf_counter()
        f.import_bits(rows2, cols2)
        ingest_bits_mps_warm = n_bits / (time.perf_counter() - t0) / 1e6
        # BSI field: 8 planes ingested word-level straight into the bsig
        # view (synthetic planes ⊆ exists; value = Σ 2^d · plane_d bits)
        api.create_field(
            "bx", "v", {"type": "int", "min": 0, "max": (1 << BSI_DEPTH) - 1}
        )
        v = idx.field("v")
        bsiv = v._view_create(v.bsi_view_name())
        exists_h = dense(density_and=False)  # ~50%
        plane_sum = 0
        for s in range(n_shards):
            bsiv.fragment(s).import_row_words(BSI_EXISTS_BIT, exists_h[s])
        # the word-level (roaring-analog) ingest path, timed: dense rows
        # union straight into the store with no position parsing — the
        # MB/s here is the zero-parse bulk-load roofline
        planes_h = []
        for d in range(BSI_DEPTH):
            plane = (
                rng.integers(0, 2**32, shape, np.uint32) & exists_h
            ).astype(np.uint32)
            plane_sum += (1 << d) * int(
                np.bitwise_count(plane).sum()
                if hasattr(np, "bitwise_count")
                else np.unpackbits(plane.view(np.uint8)).sum()
            )
            planes_h.append(plane)
        t0 = time.perf_counter()
        for d, plane in enumerate(planes_h):
            for s in range(n_shards):
                bsiv.fragment(s).import_row_words(BSI_OFFSET_BIT + d, plane[s])
        ingest_roaring_mbps = (
            BSI_DEPTH * n_shards * WORDS_PER_ROW * 4
            / (time.perf_counter() - t0)
            / 1e6
        )
        # config 4 corpus: 3 fields over 64 shards (8 x 6 x 4 = 192 groups)
        api.create_index("gbx")
        gb_shape = (GB_SHARDS, WORDS_PER_ROW)
        gidx = srv.holder.index("gbx")
        for fname, nrows in (("ga", 8), ("gb", 6), ("gc", 4)):
            api.create_field("gbx", fname)
            gf = gidx.field(fname)
            for r in range(nrows):
                words = (
                    rng.integers(0, 2**32, gb_shape, np.uint32)
                    & rng.integers(0, 2**32, gb_shape, np.uint32)
                )
                for s in range(GB_SHARDS):
                    gf.import_row_words(r, s, words[s])

        # ---- device kernel (the r1 methodology, batch 256) ----
        a = jax.device_put(a_h)
        b = jax.device_put(b_h)

        @jax.jit
        def count_and_salted(a, b, salt):
            x = jnp.bitwise_and(jnp.bitwise_xor(a, salt), b)
            return jnp.sum(jax.lax.population_count(x), dtype=jnp.uint32)

        expect = int(count_and_salted(a, b, np.uint32(0)))  # warm + truth
        salt_i = 1
        window_ms = []
        for _ in range(WINDOWS):
            t0 = time.perf_counter()
            outs = []
            for _ in range(BATCH):
                outs.append(count_and_salted(a, b, np.uint32(salt_i)))
                salt_i += 1
            _ = int(outs[-1])  # host read syncs the stream
            window_ms.append((time.perf_counter() - t0) * 1000 / BATCH)
        device_ms = float(np.median(window_ms))
        bytes_per_q = 2 * n_shards * WORDS_PER_ROW * 4
        device_gbps = bytes_per_q / (device_ms / 1000) / 1e9

        # device-resident burst: BATCH salted queries inside ONE dispatch
        # (lax.fori_loop) — the per-dispatch-overhead-free HBM number
        @jax.jit
        def burst(a, b, k0):
            def body(i, acc):
                x = jnp.bitwise_and(jnp.bitwise_xor(a, i.astype(jnp.uint32)), b)
                return acc + jnp.sum(jax.lax.population_count(x), dtype=jnp.uint32)
            return jax.lax.fori_loop(k0, k0 + BATCH, body, jnp.uint32(0))

        _ = int(burst(a, b, jnp.uint32(0)))  # warm
        burst_ms = float(
            np.min(
                [
                    _median_ms(lambda: int(burst(a, b, jnp.uint32(1))), 1) / BATCH
                    for _ in range(5)
                ]
            )
        )
        burst_gbps = bytes_per_q / (burst_ms / 1000) / 1e9

        # multi-query burst: 4 salted queries per sweep — the fixed
        # per-iteration cost amortizes and per-query time ~halves (the
        # regime the executor's multi-Count batching exploits)
        MQ = 4

        @jax.jit
        def burst_mq(a, b, k0):
            def body(i, acc):
                salts = k0 + i * MQ + jnp.arange(MQ, dtype=jnp.uint32)
                x = jnp.bitwise_and(
                    jnp.bitwise_xor(a[None], salts[:, None, None]), b[None]
                )
                return acc + jnp.sum(jax.lax.population_count(x), dtype=jnp.uint32)
            return jax.lax.fori_loop(
                jnp.uint32(0), jnp.uint32(BATCH // MQ), body, jnp.uint32(0)
            )

        _ = int(burst_mq(a, b, jnp.uint32(0)))  # warm
        mq_ms = float(
            np.min(
                [
                    _median_ms(lambda: int(burst_mq(a, b, jnp.uint32(1))), 1) / BATCH
                    for _ in range(5)
                ]
            )
        )
        mq_gbps_effective = bytes_per_q / (mq_ms / 1000) / 1e9

        # ---- filtered-TopN device work, RTT-amortized ----
        # The exact shapes the one-pass tally dispatches at bench scale:
        # dense-candidate cross tally [1,S,W]x[2,S,W], sparse gather of
        # ~200k live words + sorted-segment cumsum, fused [32,S] concat.
        # Batched back-to-back with ONE final sync, same methodology as
        # the count device number — this is the colocated-hardware cost
        # of a filtered TopN query (the system number is RTT-bound).
        from pilosa_tpu.exec import groupby as gbm
        from pilosa_tpu.ops import bitmap as obm

        planes2 = jax.device_put(np.stack([a_h, b_h]))  # dense candidates
        k_ent = 1 << 18
        g_idx = jax.device_put(
            rng.integers(0, n_shards * WORDS_PER_ROW, k_ent).astype(np.int32)
        )
        g_mask = jax.device_put(rng.integers(0, 2**32, k_ent, np.uint32))
        segs = np.sort(rng.integers(0, k_ent, 32 * n_shards)).astype(np.int32)
        g_starts = jax.device_put(segs)
        g_ends = jax.device_put(np.minimum(segs + 8, k_ent).astype(np.int32))

        @jax.jit
        def topn_tally_once(b, planes2, g_idx, g_mask, g_starts, g_ends, salt):
            # operands as arguments, not closure: closed-over device
            # arrays would embed as compile-time constants
            src = jnp.bitwise_xor(b, salt)
            dense_c = gbm._counts_cross(src[None], planes2)[0]
            sparse_c = obm.gather_tally_sorted(
                src, g_idx, g_mask, g_starts, g_ends
            ).reshape(32, n_shards)
            return jnp.concatenate([dense_c, sparse_c], axis=0)

        args_t = (b, planes2, g_idx, g_mask, g_starts, g_ends)
        _ = np.asarray(topn_tally_once(*args_t, np.uint32(0)))  # warm
        TB = 32
        t0 = time.perf_counter()
        outs = [topn_tally_once(*args_t, np.uint32(i + 1)) for i in range(TB)]
        _ = np.asarray(outs[-1])  # one sync for the whole batch
        topn_filtered_device_ms = (time.perf_counter() - t0) * 1000 / TB

        # ---- BSI Sum device work, RTT-amortized (config 3) ----
        # The exact shape Sum dispatches at bench scale: per-plane
        # popcounts of planes[D,S,W] & exists[S,W] in one fused [D]
        # reduction (the executor's fused aggregate read; the 2^d
        # weighting is an exact host combine). Salted back-to-back with
        # ONE final sync — without this the config-3 number sits on the
        # blocking-read floor (VERDICT weak #2).
        planes_dev = jax.device_put(
            np.stack(
                [
                    (
                        rng.integers(0, 2**32, shape, np.uint32) & exists_h
                    ).astype(np.uint32)
                    for _ in range(BSI_DEPTH)
                ]
            )
        )
        exists_dev = jax.device_put(exists_h)

        @jax.jit
        def bsi_sum_once(exists, planes, salt):
            src = jnp.bitwise_xor(exists, salt)
            return jnp.sum(
                jax.lax.population_count(jnp.bitwise_and(planes, src[None])),
                axis=(1, 2),
                dtype=jnp.uint32,
            )

        _ = np.asarray(bsi_sum_once(exists_dev, planes_dev, np.uint32(0)))
        t0 = time.perf_counter()
        outs = [
            bsi_sum_once(exists_dev, planes_dev, np.uint32(i + 1))
            for i in range(TB)
        ]
        _ = np.asarray(outs[-1])  # one sync for the whole batch
        bsi_sum_device_ms = (time.perf_counter() - t0) * 1000 / TB
        del planes_dev, exists_dev

        # ---- GroupBy device work, RTT-amortized (config 4) ----
        # The tally kernel at config-4 geometry: ga's 8 rows crossed with
        # the 24 (gb x gc) pair rows over 64 shards -> [8, 24, S] counts,
        # the same _counts_cross the executor's group_by_device runs.
        from pilosa_tpu.exec import groupby as gbm_dev

        gb_shape3 = (GB_SHARDS, WORDS_PER_ROW)
        ga_dev = jax.device_put(
            np.stack(
                [
                    rng.integers(0, 2**32, gb_shape3, np.uint32)
                    & rng.integers(0, 2**32, gb_shape3, np.uint32)
                    for _ in range(8)
                ]
            )
        )
        gbc_dev = jax.device_put(
            np.stack(
                [
                    rng.integers(0, 2**32, gb_shape3, np.uint32)
                    & rng.integers(0, 2**32, gb_shape3, np.uint32)
                    for _ in range(24)
                ]
            )
        )

        @jax.jit
        def groupby_tally_once(ga, gbc, salt):
            return gbm_dev._counts_cross(jnp.bitwise_xor(ga, salt), gbc)

        _ = np.asarray(groupby_tally_once(ga_dev, gbc_dev, np.uint32(0)))
        t0 = time.perf_counter()
        outs = [
            groupby_tally_once(ga_dev, gbc_dev, np.uint32(i + 1))
            for i in range(TB)
        ]
        _ = np.asarray(outs[-1])  # one sync for the whole batch
        groupby_device_ms = (time.perf_counter() - t0) * 1000 / TB
        del ga_dev, gbc_dev

        # ---- round trip (dispatch + sync of a trivial op) ----
        tiny = jax.device_put(np.uint32(1))
        add1 = jax.jit(lambda x: x + 1)
        _ = int(add1(tiny))
        rtt_ms = _median_ms(lambda: int(add1(tiny)), 5)

        # ---- system numbers through api.query ----
        q_count = "Count(Intersect(Row(f=1), Row(f=2)))"
        got = api.query("bx", q_count)[0]  # warm: compile + stack build
        assert got == expect, (got, expect)
        system_ms = _median_ms(lambda: api.query("bx", q_count), 12)

        # multi-Count batching: 4 counts in one PQL request = ONE dispatch
        # + one host read — per-query system cost ~RTT/4
        q_multi = (
            "Count(Intersect(Row(f=1), Row(f=2)))"
            "Count(Union(Row(f=1), Row(f=2)))"
            "Count(Xor(Row(f=1), Row(f=2)))"
            "Count(Difference(Row(f=1), Row(f=2)))"
        )
        multi_got = api.query("bx", q_multi)  # warm
        assert multi_got[0] == expect, multi_got
        system_mq4_ms = _median_ms(lambda: api.query("bx", q_multi), 8) / 4

        # cross-request amortization: 8 concurrent single-Count clients
        # share dispatches through the group-commit batcher; per-query
        # latency approaches RTT/8 + device (VERDICT r4 #3)
        def concurrent_ms(query, n_threads=8, reps=4):
            def run_round():
                def client(errbox):
                    try:
                        for _ in range(reps):
                            api.query("bx", query)
                    except Exception as e:  # noqa: BLE001
                        errbox.append(e)

                errs: list = []
                threads = [
                    threading.Thread(target=client, args=(errs,))
                    for _ in range(n_threads)
                ]
                t0 = time.perf_counter()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                assert not errs, errs[:1]
                return (time.perf_counter() - t0) * 1000 / (n_threads * reps)

            run_round()  # warm: first round compiles the merged plan shapes
            return run_round()

        system_concurrent8_ms = concurrent_ms(q_count)

        (topn,) = api.query("bx", "TopN(f, n=100)")  # warm
        assert topn and topn[0].id in (1, 2), topn[:3]
        topn_ms = _median_ms(lambda: api.query("bx", "TopN(f, n=100)"), 5)

        q_topn_f = "TopN(f, Row(f=2), n=100)"
        (topn_f,) = api.query("bx", q_topn_f)  # warm: gather-bundle build
        assert topn_f and topn_f[0].id == 2, topn_f[:3]
        topn_filtered_ms = _median_ms(lambda: api.query("bx", q_topn_f), 5)
        from pilosa_tpu.exec.executor import TOPN_STATS

        for k in TOPN_STATS:
            TOPN_STATS[k] = 0
        api.query("bx", q_topn_f)
        assert TOPN_STATS["one_pass"] == 1, TOPN_STATS
        assert TOPN_STATS["tally_evals"] <= 2, TOPN_STATS

        (sum_vc,) = api.query("bx", "Sum(field=v)")  # warm (stack build)
        assert sum_vc.value == plane_sum, (sum_vc.value, plane_sum)
        sum_ms = _median_ms(lambda: api.query("bx", "Sum(field=v)"), 5)

        # config 4: GroupBy over 3 fields, 64 shards, 192 groups
        q_gb = "GroupBy(Rows(ga), Rows(gb), Rows(gc))"
        (groups,) = api.query("gbx", q_gb)  # warm
        assert len(groups) == 8 * 6 * 4, len(groups)
        groupby_ms = _median_ms(lambda: api.query("gbx", q_gb), 5)

        # ---- bench-coverage gap families (ROADMAP item 4) ----
        # Xor/Not/Shift plus BSI Min/Max/Range at the same 1B-column
        # config as the existing intersect/sum numbers — these shapes
        # had no baselines, so regressions in their lowering were
        # invisible. Asserted against host truth like everything else.
        def _popc(words) -> int:
            return int(
                np.bitwise_count(words).sum()
                if hasattr(np, "bitwise_count")
                else np.unpackbits(
                    np.ascontiguousarray(words).view(np.uint8)
                ).sum()
            )

        q_xor = "Count(Xor(Row(f=1), Row(f=2)))"
        expect_xor = _popc(a_h ^ b_h)
        got = api.query("bx", q_xor)[0]  # warm
        assert got == expect_xor, (got, expect_xor)
        xor_ms = _median_ms(lambda: api.query("bx", q_xor), 5)

        # existence for Not: row words imported directly (track_columns
        # over 1B columns would be a second full position-wise ingest)
        ef = idx.existence_field()
        for s in range(n_shards):
            ef.import_row_words(0, s, a_h[s] | b_h[s])
        q_not = "Count(Not(Row(f=1)))"
        expect_not = _popc((a_h | b_h) & ~a_h)
        got = api.query("bx", q_not)[0]  # warm
        assert got == expect_not, (got, expect_not)
        not_ms = _median_ms(lambda: api.query("bx", q_not), 5)

        q_shift = "Count(Shift(Row(f=1), n=1))"
        got = api.query("bx", q_shift)[0]  # warm
        # the carry out of the last shard lands in its (materialized)
        # successor, so no bit is lost and the count is exactly row 1's
        assert got == _popc(a_h), (got, _popc(a_h))
        shift_ms = _median_ms(lambda: api.query("bx", q_shift), 5)

        # BSI aggregates ride the plane-streamed lowering (ISSUE 15):
        # counter-asserted dispatch shape — ONE compiled dispatch + ONE
        # scalar-sized host read per warm aggregate at this depth-8 /
        # 954-shard config (exactly one budget chunk, exactly one slab)
        from pilosa_tpu.exec import plan as planmod_b

        def _one_dispatch(q):
            ev0 = planmod_b.STATS["evals"]
            rd0 = planmod_b.STATS["host_reads"]
            (res,) = api.query("bx", q)
            assert planmod_b.STATS["evals"] - ev0 == 1, (
                q, planmod_b.STATS["evals"] - ev0,
            )
            assert planmod_b.STATS["host_reads"] - rd0 == 1, (
                q, planmod_b.STATS["host_reads"] - rd0,
            )
            return res

        (min_vc,) = api.query("bx", "Min(field=v)")  # warm
        assert min_vc.count > 0, min_vc
        assert _one_dispatch("Min(field=v)").value == min_vc.value
        bsi_min_ms = _median_ms(lambda: api.query("bx", "Min(field=v)"), 5)
        (max_vc,) = api.query("bx", "Max(field=v)")  # warm
        assert max_vc.count > 0 and max_vc.value >= min_vc.value, (
            min_vc, max_vc,
        )
        assert _one_dispatch("Max(field=v)").value == max_vc.value
        bsi_max_ms = _median_ms(lambda: api.query("bx", "Max(field=v)"), 5)
        assert _one_dispatch("Sum(field=v)").value == plane_sum
        q_bsi_range = f"Count(Row(v > {(1 << BSI_DEPTH) // 2}))"
        api.query("bx", q_bsi_range)  # warm
        _one_dispatch(q_bsi_range)
        bsi_range_ms = _median_ms(lambda: api.query("bx", q_bsi_range), 5)

        # HBM-pressure eviction: budget below the ~250 MB count working
        # set; results must stay correct while operands re-stage per query.
        # With extent-granular paging (pilosa_tpu/hbm/) only the evicted
        # slices re-upload — hbm_restage_mb_per_query records the actual
        # PCIe traffic per query under pressure (monolithic staging
        # re-shipped the full working set every time: the 30-40x cliff).
        from pilosa_tpu.hbm import residency as hbm_res

        old_budget = DEVICE_CACHE.budget_bytes
        DEVICE_CACHE.budget_bytes = 128 << 20
        DEVICE_CACHE.clear()
        got = api.query("bx", q_count)[0]
        assert got == expect, (got, expect)
        restage0 = hbm_res.stats_snapshot()["restage_bytes"]
        evict_reps = 5
        hbm_evict_count_ms = _median_ms(
            lambda: api.query("bx", q_count), evict_reps
        )
        hbm_restage_mb_per_query = (
            hbm_res.stats_snapshot()["restage_bytes"] - restage0
        ) / evict_reps / (1 << 20)
        DEVICE_CACHE.budget_bytes = old_budget
        DEVICE_CACHE.clear()
        got = api.query("bx", q_count)[0]  # restore + re-verify
        assert got == expect, (got, expect)

        # dirty-extent restage (ISSUE 5): a single-shard write into a warm
        # working set, then the same count — only the covering extent(s)
        # re-stage, not the ~250 MB stack set (monolithic invalidation
        # re-shipped everything from the write side)
        restage0 = hbm_res.stats_snapshot()["restage_bytes"]
        f.set_bit(1, 7)  # shard 0 of a count operand
        api.query("bx", q_count)
        ingest_dirty_restage_mb = (
            hbm_res.stats_snapshot()["restage_bytes"] - restage0
        ) / (1 << 20)

        # ---- versioned result cache: the warm path (ISSUE 14) ----
        # the bench server runs with the cache disabled so every number
        # above is an execution cost; this section enables it and
        # measures the canonical dashboard steady state — the SAME
        # Count/TopN re-issued while a writer stages continuous ingest
        # into another field — plus the in-place Count repair after a
        # set-only burst into the cached row itself. Counter-asserted:
        # revalidated hits issue zero compiled dispatches, zero blocking
        # device reads, and zero host->device upload bytes.
        from pilosa_tpu.core.resultcache import RESULT_CACHE
        from pilosa_tpu.exec import plan as planmod_c

        api.create_field("bx", "cache_tgt")
        RESULT_CACHE.configure(budget_bytes=64 << 20, repair=True)
        try:
            q_cached = [q_count, "TopN(f, n=100)"]
            for q in q_cached:
                api.query("bx", q)
                api.query("bx", q)  # repeat stores + first hit
            stop_w = threading.Event()
            werrs: list = []

            def cache_writer():
                wrng = np.random.default_rng(23)
                try:
                    while not stop_w.is_set():
                        cc = wrng.integers(
                            0, n_shards * SHARD_WIDTH, 20_000
                        ).astype(np.uint64)
                        api.import_bits(
                            "bx", "cache_tgt",
                            np.full(len(cc), 1, np.uint64), cc,
                        )
                except Exception as e:  # noqa: BLE001 - surfaced below
                    werrs.append(e)

            wt = threading.Thread(target=cache_writer)
            wt.start()
            time.sleep(0.2)
            ev0 = planmod_c.STATS["evals"]
            rd0 = planmod_c.STATS["host_reads"]
            up0 = hbm_res.stats_snapshot()["restage_bytes"]
            hit0 = RESULT_CACHE.stats_snapshot()["hits"]
            lat = []
            reps_c = 300
            for i in range(reps_c):
                t0 = time.perf_counter()
                api.query("bx", q_cached[i % 2])
                lat.append((time.perf_counter() - t0) * 1000)
            stop_w.set()
            wt.join(60)
            assert not werrs, werrs[:1]
            lat.sort()
            cached_query_p50_ms = lat[len(lat) // 2]
            cached_query_p99_ms = lat[int(len(lat) * 0.99)]
            assert (
                RESULT_CACHE.stats_snapshot()["hits"] - hit0 == reps_c
            ), "a repeat under disjoint-field ingest failed to revalidate"
            assert planmod_c.STATS["evals"] == ev0, "cached hit dispatched"
            assert planmod_c.STATS["host_reads"] == rd0, "cached hit read"
            assert (
                hbm_res.stats_snapshot()["restage_bytes"] == up0
            ), "cached hit uploaded operand bytes"
            assert cached_query_p50_ms < 1.0, cached_query_p50_ms

            # in-place Count repair: a set-only staged burst into the
            # cached row is patched from the merge barrier's word delta —
            # no operand re-read, no re-staging, no dispatch
            q_repair = "Count(Row(f=3))"
            base_rep = api.query("bx", q_repair)[0]
            assert api.query("bx", q_repair)[0] == base_rep
            # shard-local burst (the canonical ingest locality): a burst
            # smeared over all 954 shards instead measures the merge
            # barrier's per-shard extent-patch cascade, which dwarfs the
            # repair itself (the repair's marginal cost is the counter-
            # asserted zero below either way). Keep the burst STAGED:
            # the op-count snapshot trigger would merge it inside the
            # import call, leaving the barrier nothing to repair from —
            # a closed repair window, not a wrong answer (same idiom as
            # the merge-roofline section below)
            for fr in f.view("standard").fragments.values():
                fr.max_op_n = max(fr.max_op_n, 1 << 22)
            rc_cols = rng.integers(
                0, min(4, n_shards) * SHARD_WIDTH, 50_000
            ).astype(np.uint64)
            f.import_bits(np.full(len(rc_cols), 3, np.uint64), rc_cols)
            ev0 = planmod_c.STATS["evals"]
            rd0 = planmod_c.STATS["host_reads"]
            up0 = hbm_res.stats_snapshot()["restage_bytes"]
            rp0 = RESULT_CACHE.stats_snapshot()["repairs"]
            t0 = time.perf_counter()
            repaired = api.query("bx", q_repair)[0]
            count_repair_ms = (time.perf_counter() - t0) * 1000
            assert RESULT_CACHE.stats_snapshot()["repairs"] > rp0
            assert planmod_c.STATS["evals"] == ev0, "repair dispatched"
            assert planmod_c.STATS["host_reads"] == rd0, "repair read device"
            assert (
                hbm_res.stats_snapshot()["restage_bytes"] == up0
            ), "repair re-staged operand bytes"
            RESULT_CACHE.reset()
            fresh = api.query("bx", q_repair)[0]
            assert repaired == fresh, (repaired, fresh)
        finally:
            RESULT_CACHE.reset()
            RESULT_CACHE.configure(budget_bytes=0)

        # ---- deferred-delta merge barrier roofline (ISSUE 9) ----
        # the read barrier a staged burst pays: per-fragment host merges
        # (the pre-ISSUE-9 path, ~a dozen small-numpy calls + a lock per
        # staged fragment) vs the cross-fragment barrier (ONE batched
        # sort/dedup pass for the whole burst, core/merge.py). The burst
        # shape is the classic low-cardinality ingest: a handful of hot
        # rows spread across every shard — per-FRAGMENT overhead is
        # exactly what the barrier amortizes. merge_barrier_ms rides the
        # shipped AUTO crossover (host pass on a CPU dev host, device
        # program on an accelerator); the forced-device run below pins
        # the one-launch contract on the compiled program itself.
        from pilosa_tpu.core import merge as merge_mod

        std = f.view("standard")
        burst_bits = 200_000
        # keep the roofline bursts STAGED: the op-count snapshot trigger
        # would otherwise merge them eagerly mid-section (in-memory
        # snapshots are cheap resets, but they'd empty the barrier)
        for fr in std.fragments.values():
            fr.max_op_n = max(fr.max_op_n, 1 << 22)

        def stage_burst():
            r = rng.integers(3, 8, burst_bits).astype(np.uint64)
            c = rng.integers(0, n_shards * SHARD_WIDTH, burst_bits).astype(
                np.uint64
            )
            f.import_bits(r, c)

        stage_burst()  # warm: touched rows get stored sparse content
        std.sync_pending()
        for fr in std.fragments.values():
            fr.sync_pending_now()  # materialize overlays: clean baseline
        stage_burst()
        frags = [fr for fr in std.fragments.values() if fr._pending_n]
        t0 = time.perf_counter()
        for fr in frags:
            fr.sync_pending_now()
        merge_perfrag_host_ms = (time.perf_counter() - t0) * 1000
        stage_burst()
        merge_mod.reset_stats()
        t0 = time.perf_counter()
        std.sync_pending()
        merge_barrier_ms = (time.perf_counter() - t0) * 1000
        msnap = merge_mod.stats_snapshot()
        assert msnap["barriers"] == 1, msnap
        # the deferred row-store materialization the barrier parked
        # (installed at each fragment's next HOST read; the device path
        # reads patched extents and never pays it) — reported so the
        # barrier number is honest about what moved off the write path
        t0 = time.perf_counter()
        for fr in std.fragments.values():
            fr.sync_pending_now()
        merge_install_ms = (time.perf_counter() - t0) * 1000
        # forced-device: the 954-fragment burst pays ONE program launch
        merge_mod.configure(device_threshold=0)
        stage_burst()  # warm: compiles the merge program's pow2 bucket
        std.sync_pending()
        stage_burst()
        merge_mod.reset_stats()
        t0 = time.perf_counter()
        std.sync_pending()
        merge_barrier_device_ms = (time.perf_counter() - t0) * 1000
        msnap = merge_mod.stats_snapshot()
        assert msnap["barriers"] == 1 and msnap["device"] == 1, msnap
        merge_mod.configure(device_threshold=None)  # back to AUTO

        # ---- smeared-burst extent-patch cascade (ISSUE 15 satellite) ----
        # round-10's named caveat: a 50k-position burst smeared over all
        # 954 shards paid one `.at[].set` FULL-EXTENT copy per dirty
        # shard in the merge barrier's patch cascade (~11.6 s measured).
        # The cascade is now batched per extent — one gather|OR|scatter
        # per resident entry — so the barrier is O(extents) device ops.
        api.query("bx", q_count)  # re-warm operand extents at live versions
        psnap0 = hbm_res.stats_snapshot()
        smear_cols = rng.integers(
            0, n_shards * SHARD_WIDTH, 50_000
        ).astype(np.uint64)
        f.import_bits(np.full(len(smear_cols), 1, np.uint64), smear_cols)
        t0 = time.perf_counter()
        std.sync_pending()
        mixed_patch_cascade_ms = (time.perf_counter() - t0) * 1000
        psnap1 = hbm_res.stats_snapshot()
        patch_cascade_patches = (
            psnap1["extent_patches"] - psnap0["extent_patches"]
        )
        patch_cascade_batches = (
            psnap1["extent_patch_batches"] - psnap0["extent_patch_batches"]
        )
        # O(extents) contract, asserted for real: the batching engaged
        # (at least one scatter-bearing patch), the cascade issued FAR
        # fewer device scatters than the ~954 dirty shards (the old
        # path's .at[].set count), and the wall time is at least 10x
        # under the measured 11.6 s per-shard baseline (ISSUE 15
        # acceptance; measured ~0.24 s on this host)
        smear_dirty = len({int(c) // SHARD_WIDTH for c in smear_cols})
        assert 0 < patch_cascade_batches < smear_dirty // 4, (
            patch_cascade_batches, smear_dirty,
        )
        assert mixed_patch_cascade_ms < 11_600 / 10, mixed_patch_cascade_ms
        got_after_smear = api.query("bx", q_count)[0]
        DEVICE_CACHE.clear()  # exactness vs a cold full re-stage
        got_cold = api.query("bx", q_count)[0]
        assert got_after_smear == got_cold, (got_after_smear, got_cold)

        # ---- sustained mixed read/write (the production workload) ----
        # continuous staged ingest against one index while Count/TopN
        # queries stream in: every query's read barrier merges whatever
        # the writer staged since the last one. Throughput and query
        # tail latency are read from the PR 6 flight-recorder histograms
        # (per-index query_ms series).
        api.create_index("mx")
        api.create_field("mx", "f")
        mf = srv.holder.index("mx").field("f")
        m_shape = (MIXED_SHARDS, WORDS_PER_ROW)
        mw = rng.integers(0, 2**32, m_shape, np.uint32)
        for s in range(MIXED_SHARDS):
            mf.import_row_words(1, s, mw[s] & (mw[s] >> np.uint32(1)))
            mf.import_row_words(2, s, mw[s] & (mw[s] << np.uint32(1)))
        q_mix_count = "Count(Row(f=1))"
        q_mix_topn = "TopN(f, n=50)"
        api.query("mx", q_mix_count)  # warm: stage + compile
        api.query("mx", q_mix_topn)
        # drop the warm-up observations so the histogram holds ONLY
        # queries issued under ingest pressure
        srv.stats.registry.drop_label("index", "mx")
        stop = threading.Event()
        wrote = [0]
        writer_errs = []

        def mixed_writer():
            try:
                wrng = np.random.default_rng(99)
                batch = 20_000
                while not stop.is_set():
                    r = wrng.integers(3, 33, batch).astype(np.uint64)
                    c = wrng.integers(
                        0, MIXED_SHARDS * SHARD_WIDTH, batch
                    ).astype(np.uint64)
                    mf.import_bits(r, c)
                    wrote[0] += batch
            except BaseException as e:  # noqa: BLE001 - fail the bench
                writer_errs.append(e)

        mb0 = merge_mod.stats_snapshot()
        patches0 = hbm_res.stats_snapshot()["extent_patches"]
        wt = threading.Thread(target=mixed_writer)
        t0 = time.perf_counter()
        wt.start()
        try:
            mixed_queries = 0
            while time.perf_counter() - t0 < MIXED_SECONDS:
                api.query("mx", q_mix_count)
                api.query("mx", q_mix_topn)
                mixed_queries += 2
        finally:
            stop.set()
            wt.join()
        assert not writer_errs, writer_errs  # a dead writer fakes the numbers
        mixed_elapsed = time.perf_counter() - t0
        ingest_mixed_bits_mps = wrote[0] / mixed_elapsed / 1e6
        reg = srv.stats.registry
        query_p50_under_ingest_ms = reg.quantile(
            "query_ms", 0.5, tags=("index:mx",)
        )
        query_p99_under_ingest_ms = reg.quantile(
            "query_ms", 0.99, tags=("index:mx",)
        )
        mb1 = merge_mod.stats_snapshot()
        mixed_merge_barriers = mb1["barriers"] - mb0["barriers"]
        mixed_merge_barrier_ms_mean = (
            (mb1["barrier_ms"] - mb0["barrier_ms"]) / mixed_merge_barriers
            if mixed_merge_barriers
            else 0.0
        )
        mixed_extent_patches = (
            hbm_res.stats_snapshot()["extent_patches"] - patches0
        )

        # ---- time-quantum range path (ROADMAP item 5 baseline) ----
        from datetime import datetime, timedelta

        from pilosa_tpu.core.field import FIELD_TYPE_TIME, FieldOptions

        api.create_index("tqx")
        tf = srv.holder.index("tqx").create_field(
            "e", FieldOptions(type=FIELD_TYPE_TIME, time_quantum="YMD")
        )
        tq_bits = 50_000
        t_base = datetime(2019, 1, 1)
        tq_rows = rng.integers(1, 5, tq_bits).astype(np.uint64)
        tq_cols = rng.integers(0, TQ_SHARDS * SHARD_WIDTH, tq_bits).astype(
            np.uint64
        )
        tq_days = rng.integers(0, 45, tq_bits)
        tf.import_bits(
            tq_rows,
            tq_cols,
            timestamps=[t_base + timedelta(days=int(d)) for d in tq_days],
        )
        q_tq = "Count(Range(e=1, 2019-01-05T00:00, 2019-01-20T00:00))"
        (tq_count,) = api.query("tqx", q_tq)  # warm
        assert int(tq_count) > 0, tq_count
        timeq_range_ms = _median_ms(lambda: api.query("tqx", q_tq), 5)
    finally:
        srv.stop()

    # replicated mixed read/write — the production write configuration
    # (ISSUE 12): replica_n=2 over two real-data-dir HTTP nodes with the
    # strict group-commit WAL on; its own harness, so it runs after the
    # in-memory node is down
    try:
        replicated = replicated_bench()
    except Exception as e:  # noqa: BLE001 - bench must still print its line
        replicated = {"replicated_error": f"{type(e).__name__}: {e}"[:200]}

    # tiered storage (ISSUE 18): demote throughput, cold-query hydration
    # latency, beyond-budget serving — against a slow-wrapped local store
    try:
        tier_metrics = tier_bench()
    except Exception as e:  # noqa: BLE001 - bench must still print its line
        tier_metrics = {"tier_error": f"{type(e).__name__}: {e}"[:200]}

    # cache coherence (ISSUE 19): leased vs revalidate warm fan-out hits,
    # subscription push latency, monotone tree repair — its own harnesses
    try:
        coherence_metrics = coherence_bench()
    except Exception as e:  # noqa: BLE001 - bench must still print its line
        coherence_metrics = {
            "coherence_error": f"{type(e).__name__}: {e}"[:200]
        }

    # config 5 stand-in: virtual-mesh scaling curve in a CPU subprocess
    # (pinned to CPU; same env recipe as tests/conftest.py)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--mesh-scaling"],
            capture_output=True,
            text=True,
            timeout=900,
            env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        mesh_scaling = json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001 - bench must still print its line
        mesh_scaling = [{"error": f"{type(e).__name__}: {e}"[:200]}]

    # mesh-group certification (ISSUE 10): 16- and 32-virtual-device
    # clusters, one ICI domain, Count folded into ONE compiled dispatch
    # + ONE blocking host read (counter-asserted in the child) and
    # bit-identical to the HTTP fan-out — the numbers the north-star
    # arithmetic now rests on (tools/mesh_cert.py; the cert env clears
    # XLA_FLAGS itself, one subprocess per device count)
    mesh_group: dict = {}
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        out = subprocess.run(
            [sys.executable, os.path.join(here, "tools", "mesh_cert.py")],
            capture_output=True, text=True, timeout=1800, env=env, cwd=here,
        )
        cert = json.loads(out.stdout.strip())
        for rnd in cert.get("rounds", []):
            n = rnd.get("n_devices")
            mesh_group[f"mesh{n}_count_ms"] = rnd.get("mesh_count_ms")
            mesh_group[f"mesh{n}_http_count_ms"] = rnd.get("http_count_ms")
            mesh_group[f"mesh{n}_dispatches"] = rnd.get("dispatches")
            mesh_group[f"mesh{n}_host_reads"] = rnd.get("host_reads")
        mesh_group["ok"] = cert.get("ok", False)
    except Exception as e:  # noqa: BLE001 - bench must still print its line
        mesh_group = {"error": f"{type(e).__name__}: {e}"[:200]}

    # ---- CPU comparator: vectorized numpy popcount, same data ----
    if hasattr(np, "bitwise_count"):
        def cpu_count():
            return int(np.bitwise_count(a_h & b_h).sum())
    else:
        lut = np.array([bin(i).count("1") for i in range(1 << 16)], np.uint16)
        def cpu_count():
            return int(lut[(a_h & b_h).view(np.uint16)].sum(dtype=np.int64))

    got = cpu_count()
    assert got == expect, (got, expect)
    cpu_ms = _median_ms(cpu_count, 3)

    print(
        json.dumps(
            {
                "metric": "count_intersect_1b_cols_per_query_ms",
                "value": round(device_ms, 3),
                "unit": "ms",
                "vs_baseline": round(cpu_ms / device_ms, 2),
                "extras": {
                    "system_ms": round(system_ms, 3),
                    "system_concurrent8_ms": round(system_concurrent8_ms, 3),
                    "rtt_ms": round(rtt_ms, 3),
                    "device_gbps": round(device_gbps, 1),
                    "device_burst_ms": round(burst_ms, 4),
                    "device_burst_gbps": round(burst_gbps, 1),
                    "device_mq4_ms": round(mq_ms, 4),
                    "device_mq4_gbps_effective": round(mq_gbps_effective, 1),
                    "system_mq4_ms": round(system_mq4_ms, 3),
                    "cpu_baseline_ms": round(cpu_ms, 3),
                    "ingest_bits_mps": round(ingest_bits_mps, 2),
                    "ingest_bits_mps_warm": round(ingest_bits_mps_warm, 2),
                    "ingest_roaring_mbps": round(ingest_roaring_mbps, 1),
                    "ingest_dirty_restage_mb": round(
                        ingest_dirty_restage_mb, 2
                    ),
                    "merge_barrier_ms": round(merge_barrier_ms, 3),
                    "merge_perfrag_host_ms": round(
                        merge_perfrag_host_ms, 3
                    ),
                    "merge_barrier_device_ms": round(
                        merge_barrier_device_ms, 3
                    ),
                    "merge_install_ms": round(merge_install_ms, 3),
                    "ingest_mixed_bits_mps": round(
                        ingest_mixed_bits_mps, 2
                    ),
                    "query_p50_under_ingest_ms": round(
                        query_p50_under_ingest_ms, 3
                    ),
                    "query_p99_under_ingest_ms": round(
                        query_p99_under_ingest_ms, 3
                    ),
                    "mixed_queries": mixed_queries,
                    "mixed_merge_barriers": mixed_merge_barriers,
                    "mixed_merge_barrier_ms_mean": round(
                        mixed_merge_barrier_ms_mean, 3
                    ),
                    "mixed_extent_patches": mixed_extent_patches,
                    "mixed_patch_cascade_ms": round(
                        mixed_patch_cascade_ms, 3
                    ),
                    "patch_cascade_patches": patch_cascade_patches,
                    "patch_cascade_batches": patch_cascade_batches,
                    **replicated,
                    **tier_metrics,
                    **coherence_metrics,
                    "timeq_range_ms": round(timeq_range_ms, 3),
                    "topn_n100_954shards_ms": round(topn_ms, 3),
                    "topn_filtered_n100_ms": round(topn_filtered_ms, 3),
                    "topn_filtered_device_ms": round(topn_filtered_device_ms, 3),
                    "xor_ms": round(xor_ms, 3),
                    "not_ms": round(not_ms, 3),
                    "shift_ms": round(shift_ms, 3),
                    "bsi_min_ms": round(bsi_min_ms, 3),
                    "bsi_max_ms": round(bsi_max_ms, 3),
                    "bsi_range_ms": round(bsi_range_ms, 3),
                    "cached_query_p50_ms": round(cached_query_p50_ms, 4),
                    "cached_query_p99_ms": round(cached_query_p99_ms, 4),
                    "count_repair_ms": round(count_repair_ms, 3),
                    "bsi_sum_1b_cols_ms": round(sum_ms, 3),
                    "bsi_sum_device_ms": round(bsi_sum_device_ms, 3),
                    "groupby_3f_64shards_ms": round(groupby_ms, 3),
                    "groupby_device_ms": round(groupby_device_ms, 3),
                    "hbm_evict_count_ms": round(hbm_evict_count_ms, 3),
                    "hbm_restage_mb_per_query": round(
                        hbm_restage_mb_per_query, 2
                    ),
                    "mesh_scaling": mesh_scaling,
                    "mesh_group": mesh_group,
                    "batch": BATCH,
                    "n_shards": n_shards,
                },
            }
        )
    )


if __name__ == "__main__":
    if "--mesh-scaling" in sys.argv:
        sys.exit(mesh_scaling_main())
    if "--replicated" in sys.argv:
        # the replicated write-path section alone (quick durability runs)
        print(json.dumps(replicated_bench()))
        sys.exit(0)
    if "--coherence" in sys.argv:
        # the coherence-plane section alone (quick lease/push runs)
        print(json.dumps(coherence_bench()))
        sys.exit(0)
    sys.exit(main())
