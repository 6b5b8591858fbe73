"""Batched TopN: differential vs the per-shard path + dispatch accounting.

VERDICT r2 #1 done-criteria: results identical to the per-shard host path on
randomized and adversarial-skew corpora, and a dispatch-count assertion that
the batched path issues O(1) device tallies per pass — never one per shard
(reference: fragment.go:1570-1743 top, executor.go:860-999 two-pass TopN).
"""

import numpy as np
import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec import executor as exmod
from pilosa_tpu.exec import plan as planmod
from pilosa_tpu.shardwidth import SHARD_WIDTH


def _mk(bits, cache_size=50_000, src_bits=None, attrs=None):
    """bits: iterable of (row, col) for field f; src_bits likewise for g."""
    h = Holder().open()
    idx = h.create_index("i")
    f = idx.create_field("f", FieldOptions(cache_size=cache_size))
    if bits:
        rows = np.array([r for r, _ in bits], np.uint64)
        cols = np.array([c for _, c in bits], np.uint64)
        f.import_bits(rows, cols)
    if src_bits is not None:
        g = idx.create_field("g")
        rows = np.array([r for r, _ in src_bits], np.uint64)
        cols = np.array([c for _, c in src_bits], np.uint64)
        g.import_bits(rows, cols)
    if attrs:
        for rid, kv in attrs.items():
            f.row_attr_store.set_attrs(rid, kv)
    return h, Executor(h)


def _pairs(res):
    return [(p.id, p.count) for p in res]


def _both_paths(h, ex, pql, monkeypatch):
    """Run a query on the default (one-pass where eligible) path, the
    classic batched two-pass, and the forced per-shard path; assert the
    first two agree and return (default, serial) for the caller's check —
    a three-way differential over every TopN execution strategy."""
    fast = ex.execute("i", pql)
    with monkeypatch.context() as m:
        m.setattr(
            Executor, "_topn_local_full", lambda self, idx, c, shards: None
        )
        batched = ex.execute("i", pql)
        assert _pairs(fast[0]) == _pairs(batched[0]), pql
        m.setattr(
            Executor, "_topn_merged_batched", lambda self, idx, spec, shards: None
        )
        serial = ex.execute("i", pql)
    return fast, serial


QUERIES = [
    "TopN(f)",
    "TopN(f, n=1)",
    "TopN(f, n=3)",
    "TopN(f, n=100)",
    "TopN(f, threshold=3)",
    "TopN(f, n=2, threshold=5)",
    "TopN(f, ids=[0, 1, 2, 7])",
    "TopN(f, Row(g=0))",
    "TopN(f, Row(g=0), n=2)",
    "TopN(f, Row(g=0), threshold=3)",
    "TopN(f, Row(g=0), n=4, threshold=2)",
    "TopN(f, Row(g=0), n=3, tanimotoThreshold=30)",
    "TopN(f, Row(g=0), n=3, tanimotoThreshold=80)",
    "TopN(f, Row(g=0), ids=[1, 2, 3])",
]


class TestDifferential:
    def test_randomized(self, monkeypatch, rng):
        """Random corpus over 6 shards, zipf-ish row sizes."""
        n_shards = 6
        bits = []
        for row in range(18):
            n = int(rng.integers(1, 400) // (row + 1)) + 1
            cols = rng.integers(0, n_shards * SHARD_WIDTH, n)
            bits += [(row, int(c)) for c in cols]
        src = [(0, int(c)) for c in rng.integers(0, n_shards * SHARD_WIDTH, 500)]
        h, ex = _mk(bits, src_bits=src)
        for pql in QUERIES:
            b, s = _both_paths(h, ex, pql, monkeypatch)
            assert _pairs(b[0]) == _pairs(s[0]), pql

    def test_adversarial_skew(self, monkeypatch, rng):
        """One dominating row in one shard, heavy ties, rows in disjoint
        shard subsets, empty shard gaps."""
        bits = []
        # row 0 dominates shard 4 only
        bits += [(0, 4 * SHARD_WIDTH + i) for i in range(2000)]
        # rows 1..6 tie exactly (count 7 each), spread over shards 0..2
        for row in range(1, 7):
            bits += [(row, (i % 3) * SHARD_WIDTH + row * 50 + i) for i in range(7)]
        # rows 7..10 live only in shard 7 (gap at shards 3,5,6)
        for row in range(7, 11):
            bits += [(row, 7 * SHARD_WIDTH + row * 11 + i) for i in range(row)]
        src = [(0, 4 * SHARD_WIDTH + i) for i in range(0, 2000, 2)]
        src += [(0, i * 50) for i in range(60)]
        h, ex = _mk(bits, src_bits=src)
        for pql in QUERIES:
            b, s = _both_paths(h, ex, pql, monkeypatch)
            assert _pairs(b[0]) == _pairs(s[0]), pql

    def test_cache_eviction_approximation(self, monkeypatch, rng):
        """With a tiny rank cache, evicted rows are not candidates — the
        documented approximation must be IDENTICAL on both paths."""
        n_shards = 3
        bits = []
        for row in range(20):
            n = 21 - row
            cols = rng.integers(0, n_shards * SHARD_WIDTH, n * 3)
            bits += [(row, int(c)) for c in cols]
        h, ex = _mk(bits, cache_size=4)
        for pql in ["TopN(f)", "TopN(f, n=3)", "TopN(f, ids=[0, 15, 19])"]:
            b, s = _both_paths(h, ex, pql, monkeypatch)
            assert _pairs(b[0]) == _pairs(s[0]), pql

    def test_attr_filters(self, monkeypatch):
        bits = []
        for row in range(8):
            bits += [(row, row * 3 + i) for i in range(row + 1)]
        attrs = {r: {"cat": "a" if r % 2 else "b"} for r in range(8)}
        h, ex = _mk(bits, attrs=attrs)
        pql = 'TopN(f, n=4, attrName="cat", attrValues=["a"])'
        b, s = _both_paths(h, ex, pql, monkeypatch)
        assert _pairs(b[0]) == _pairs(s[0])
        assert all(p[0] % 2 == 1 for p in _pairs(b[0]))

    def test_attr_filters_with_src(self, monkeypatch):
        """Attr filter + filter bitmap together exercise the one-pass
        vectorized attr prune against both fallbacks."""
        bits = []
        for row in range(8):
            bits += [(row, row * 3 + i) for i in range(row + 1)]
        src = [(0, c) for c in range(0, 30)]
        attrs = {r: {"cat": "a" if r % 2 else "b"} for r in range(8)}
        h, ex = _mk(bits, src_bits=src, attrs=attrs)
        pql = 'TopN(f, Row(g=0), n=4, attrName="cat", attrValues=["a"])'
        b, s = _both_paths(h, ex, pql, monkeypatch)
        assert _pairs(b[0]) == _pairs(s[0])
        assert all(p[0] % 2 == 1 for p in _pairs(b[0]))


class TestDispatchCounts:
    def test_plain_topn_is_pure_host(self):
        """Unfiltered TopN reads only exact host metadata: ZERO device
        dispatches (the r2 bench's 273.9 ms was all host merge)."""
        bits = [(r, r * 7 + i) for r in range(10) for i in range(r + 1)]
        bits += [(r, SHARD_WIDTH + r) for r in range(10)]
        h, ex = _mk(bits)
        ex.execute("i", "TopN(f, n=5)")  # warm
        from pilosa_tpu.core.resultcache import RESULT_CACHE

        RESULT_CACHE.reset()  # the probe asserts the tally path, not the cache
        planmod.reset_stats()
        for k in exmod.TOPN_STATS:
            exmod.TOPN_STATS[k] = 0
        ex.execute("i", "TopN(f, n=5)")
        assert planmod.STATS["evals"] == 0
        assert exmod.TOPN_STATS["tally_evals"] == 0
        assert exmod.TOPN_STATS["batched"] == 2  # both passes batched
        assert exmod.TOPN_STATS["fallback"] == 0

    def test_filtered_topn_bounded_dispatches(self):
        """Filtered TopN runs as ONE pass: one stacked src eval + one
        batched tally covering both the pass-1 select and the pass-2 exact
        recount, independent of shard count (r5: the [R, S] ic matrix is
        reused host-side for pass 2 — a second dispatch+read would double
        the blocking reads per query)."""
        n_shards = 40
        bits = []
        for row in range(12):
            bits += [
                (row, s * SHARD_WIDTH + row * 13 + i)
                for s in range(n_shards)
                for i in range(3)
            ]
        src = [(0, s * SHARD_WIDTH + i) for s in range(n_shards) for i in range(200)]
        h, ex = _mk(bits, src_bits=src)
        ex.execute("i", "TopN(f, Row(g=0), n=5)")  # warm
        from pilosa_tpu.core.resultcache import RESULT_CACHE

        RESULT_CACHE.reset()  # the probe asserts the tally path, not the cache
        planmod.reset_stats()
        for k in exmod.TOPN_STATS:
            exmod.TOPN_STATS[k] = 0
        ex.execute("i", "TopN(f, Row(g=0), n=5)")
        assert exmod.TOPN_STATS["fallback"] == 0
        assert exmod.TOPN_STATS["one_pass"] == 1
        # ONE src plan eval for the whole query (no pass-2 re-eval)
        assert planmod.STATS["evals"] == 1
        # tallies bounded by candidate chunks (dense planes + sparse
        # gather), NOT by the 40 shards, and issued once, not per pass
        assert exmod.TOPN_STATS["tally_evals"] <= 2

    def test_cache_counts_exact(self):
        """The pass-2 cardinality fast path: an unpruned rank cache is a
        complete exact row->count map; once pruned it must return None
        (callers fall back to row_counts_host)."""
        bits = [(r, r * 5 + i) for r in range(6) for i in range(r + 1)]
        h, ex = _mk(bits)
        frag = (
            h.index("i").field("f").view("standard").fragment_if_exists(0)
        )
        ids = np.array([0, 3, 5, 99], np.uint64)
        got = frag.cache_counts_exact(ids)
        assert got is not None
        want = frag.row_counts_host([0, 3, 5, 99])
        assert (got == want).all(), (got, want)
        # pruned cache -> None
        h2, ex2 = _mk(bits, cache_size=3)
        frag2 = (
            h2.index("i").field("f").view("standard").fragment_if_exists(0)
        )
        assert frag2.cache_counts_exact(ids) is None

    def test_pruned_flag_survives_sidecar_reload(self, tmp_path):
        """A pruned cache flushed to the .cache sidecar and reloaded must
        NOT reload as 'provably complete' — cache_counts_exact would
        return 0 for the pruned rows and TopN pass-2 would silently
        undercount after a restart (code-review r5 finding)."""
        from pilosa_tpu.core import cache as cachemod

        cache = cachemod.RankCache(max_size=3)
        for r in range(6):
            cache.add(r, 10 + r)
        cache.recalculate()
        assert cache.pruned
        path = str(tmp_path / "frag.cache")
        cachemod.write_cache(path, cache)
        fresh = cachemod.RankCache(max_size=3)
        assert cachemod.read_cache(path, fresh)
        assert fresh.pruned  # the flag rode the sidecar
        # and an unpruned cache round-trips as unpruned
        ok = cachemod.RankCache(max_size=50)
        ok.add(1, 7)
        path2 = str(tmp_path / "ok.cache")
        cachemod.write_cache(path2, ok)
        fresh2 = cachemod.RankCache(max_size=50)
        assert cachemod.read_cache(path2, fresh2)
        assert not fresh2.pruned

    def test_cache_counts_exact_none_after_restart_when_pruned(self, tmp_path):
        """End-to-end: fragment with more rows than cache_size, snapshot +
        close + reopen — the fast path must refuse (None), not undercount."""
        from pilosa_tpu.core.field import FieldOptions
        from pilosa_tpu.core.holder import Holder

        d = str(tmp_path / "h")
        h = Holder(d).open()
        idx = h.create_index("i")
        f = idx.create_field("f", FieldOptions(cache_size=4))
        bits = [(r, r * 3 + i) for r in range(10) for i in range(r + 1)]
        rows = np.array([r for r, _ in bits], np.uint64)
        cols = np.array([c for _, c in bits], np.uint64)
        f.import_bits(rows, cols)
        frag = f.view("standard").fragment_if_exists(0)
        frag.snapshot()  # WAL truncated: sidecar will be trusted on reopen
        h.close()
        h2 = Holder(d).open()
        frag2 = (
            h2.index("i").field("f").view("standard").fragment_if_exists(0)
        )
        ids = np.arange(10, dtype=np.uint64)
        assert frag2.cache_counts_exact(ids) is None
        # authoritative counts still exact
        want = np.array([r + 1 for r in range(10)], np.uint64)
        assert (frag2.row_counts_host(list(range(10))) == want).all()
        h2.close()

    def test_snapshot_flushes_sidecar_before_wal_truncate(self, tmp_path, monkeypatch):
        """Crash-window ordering: the cache sidecar must hit disk BEFORE
        the WAL truncates — open() only trusts the sidecar when the WAL
        replays nothing, so a crash in between must leave a non-empty WAL
        (recalculate path), never a stale 'complete' sidecar serving
        wrong exact counts (code-review r5 finding)."""
        from pilosa_tpu.core import fragment as fragmod
        from pilosa_tpu.core import wal as walmod
        from pilosa_tpu.core.holder import Holder

        h = Holder(str(tmp_path / "h")).open()
        idx = h.create_index("i")
        f = idx.create_field("f")
        f.import_bits(np.array([1, 2], np.uint64), np.array([5, 9], np.uint64))
        frag = f.view("standard").fragment_if_exists(0)
        order = []
        orig_flush = fragmod.Fragment.flush_cache
        orig_trunc = walmod.WalWriter.truncate
        monkeypatch.setattr(
            fragmod.Fragment, "flush_cache",
            lambda self: (order.append("flush"), orig_flush(self))[1],
        )
        monkeypatch.setattr(
            walmod.WalWriter, "truncate",
            lambda self: (order.append("truncate"), orig_trunc(self))[1],
        )
        frag.snapshot()
        assert order.index("flush") < order.index("truncate"), order
        h.close()

    def test_row_count_is_o1(self):
        """RowBits cardinality must be maintained, not recomputed (plain
        TopN pass 2 does n_shards x n_candidates count() calls)."""
        from pilosa_tpu.core.rowstore import RowBits

        rb = RowBits(SHARD_WIDTH)
        rng = np.random.default_rng(3)
        ref = set()
        for _ in range(8):
            new = rng.integers(0, SHARD_WIDTH, 40_000).astype(np.uint32)
            rb.add(new)
            ref |= set(int(x) for x in new)
            assert rb.count() == len(ref)
            gone = rng.integers(0, SHARD_WIDTH, 10_000).astype(np.uint32)
            rb.discard(gone)
            ref -= set(int(x) for x in gone)
            assert rb.count() == len(ref)
        words = np.zeros(SHARD_WIDTH // 32, np.uint32)
        words[:100] = 0xFFFFFFFF
        rb.union_words(words)
        ref |= set(range(3200))
        assert rb.count() == len(ref)


class TestMinMaxRowBatched:
    def test_differential_and_dispatch_count(self, monkeypatch, rng):
        """Filtered MinRow/MaxRow matches the per-shard path on a random
        corpus and issues O(1) tallies, not one dispatch per shard."""
        n_shards = 30
        bits = []
        for row in (2, 5, 9, 14, 30):
            cols = rng.integers(0, n_shards * SHARD_WIDTH, 300)
            bits += [(row, int(c)) for c in cols]
        src = [(0, int(c)) for c in rng.integers(0, n_shards * SHARD_WIDTH, 4000)]
        h, ex = _mk(bits, src_bits=src)
        for pql in ("MinRow(Row(g=0), field=f)", "MaxRow(Row(g=0), field=f)"):
            got = ex.execute("i", pql)
            with monkeypatch.context() as m:
                m.setattr(
                    Executor,
                    "_min_max_row_batched",
                    lambda self, idx, v, fc, sl, is_min: None,
                )
                want = ex.execute("i", pql)
            assert got == want, pql
        exmod.TOPN_STATS["tally_evals"] = 0
        ex.execute("i", "MinRow(Row(g=0), field=f)")
        assert 0 < exmod.TOPN_STATS["tally_evals"] <= 2

    def test_filter_matches_nothing(self, rng):
        bits = [(r, r * 11 + i) for r in (3, 7) for i in range(5)]
        src = [(0, SHARD_WIDTH * 2 + 1)]  # disjoint from all rows
        h, ex = _mk(bits, src_bits=src)
        assert ex.execute("i", "MinRow(Row(g=0), field=f)") == [
            {"id": 0, "count": 0}
        ]

    def test_unfiltered_still_host(self, rng):
        bits = [(r, r * 11 + i) for r in (3, 7, 12) for i in range(4)]
        h, ex = _mk(bits)
        assert ex.execute("i", "MinRow(field=f)")[0]["id"] == 3
        assert ex.execute("i", "MaxRow(field=f)")[0]["id"] == 12
