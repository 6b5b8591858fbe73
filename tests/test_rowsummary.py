"""View row summary (core/rowsummary.py, View.row_summary): Rows, the
GroupBy prefetch and the unfiltered TopN answered from one clock-checked
table of per-(row, shard) counts.

Differential: every query through the table against the forced
per-fragment path (row_summary -> None), on random and skewed corpora over
48 shards, with full and partial shard lists, and again right after each
kind of write — the first read after an acknowledged write must see it.
Counting: a warm repeat makes no per-fragment metadata call at all.
"""

import numpy as np
import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.fragment import Fragment
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.core.resultcache import RESULT_CACHE
from pilosa_tpu.core.view import View
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec import executor as exmod
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils.stats import PROCESS

N_SHARDS = 48
ROWS = {"a": 5, "b": 4, "c": 3}

COUNTERS = ("hits", "rebuilds", "refreshed_shards", "bypassed")


def _counters():
    return {k: PROCESS.total_counter(f"rowsummary.{k}") for k in COUNTERS}


def _delta(before):
    now = _counters()
    return {k: int(now[k] - before[k]) for k in COUNTERS}


@pytest.fixture(autouse=True)
def _no_result_cache():
    """The table must carry the reads by itself: no stored answers."""
    budget = RESULT_CACHE.budget_bytes
    RESULT_CACHE.configure(budget_bytes=0)
    yield
    RESULT_CACHE.configure(budget_bytes=budget)


def _corpus(kind, rng, n_shards=N_SHARDS, per_shard=24):
    """{field: (rows, cols)} over one set of columns, so the fields'
    rows cross: `random` spreads every row over every shard; `skewed`
    gives row r a 2^-r share, leaves whole shards without some rows and
    shard 7 without field b."""
    cols = (
        np.repeat(np.arange(n_shards, dtype=np.uint64), per_shard)
        * np.uint64(SHARD_WIDTH)
        + rng.integers(0, SHARD_WIDTH, n_shards * per_shard).astype(np.uint64)
    )
    out = {}
    for name, n_rows in ROWS.items():
        if kind == "random":
            rows = rng.integers(0, n_rows, len(cols))
        else:
            p = 0.5 ** np.arange(n_rows)
            rows = rng.choice(n_rows, len(cols), p=p / p.sum())
        keep = np.ones(len(cols), bool)
        if kind == "skewed" and name == "b":
            keep = cols // np.uint64(SHARD_WIDTH) != 7
        out[name] = (rows[keep].astype(np.uint64), cols[keep])
    return out


def _mk(kind, rng, **kw):
    h = Holder().open()
    idx = h.create_index("i")
    corpus = _corpus(kind, rng, **kw)
    for name, (rows, cols) in corpus.items():
        idx.create_field(name).import_bits(rows, cols)
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=1000))
    cols = np.unique(corpus["a"][1])
    v.import_values(cols, (cols % 997).astype(np.int64))
    for rid in range(ROWS["a"]):
        idx.field("a").row_attr_store.set_attrs(
            rid, {"kind": "odd" if rid % 2 else "even"}
        )
    return h, idx, Executor(h)


QUERIES = [
    "Rows(a)",
    "Rows(b, limit=2)",
    "Rows(a, previous=1)",
    "Rows(v)",
    "Options(Rows(a), shards=[0, 3, 60])",
    "Options(TopN(a, n=2), shards=[1, 2, 7])",
    "GroupBy(Rows(a), Rows(b))",
    "GroupBy(Rows(a), Rows(b), Rows(c))",
    "GroupBy(Rows(a), Rows(b), filter=Row(v > 500))",
    "TopN(a)",
    "TopN(a, n=2)",
    "TopN(b, n=1, threshold=5)",
    "TopN(a, threshold=7)",
    "TopN(a, ids=[0, 3, 4, 9])",
    "TopN(a, ids=[1, 1, 2], threshold=3)",
    'TopN(a, n=3, attrName="kind", attrValues=["odd"])',
    'TopN(a, ids=[0, 1, 2, 3], attrName="kind", attrValues=["even"])',
]

SHARD_LISTS = {
    "all": None,
    "partial": [0, 3, 7, 8, 21, 40, 47],
    "with-absent": [5, 7, 46, 47, 60, 61],
}


def _plain(res):
    out = []
    for r in res:
        if isinstance(r, list) and r and hasattr(r[0], "to_json"):
            r = [x.to_json() for x in r]
        out.append(r)
    return out


def _differential(ex, monkeypatch, queries=QUERIES, shard_lists=SHARD_LISTS):
    """Each query through the table and through the forced per-fragment
    path; the table's run comes first, so it is the first read after
    whatever write preceded the call."""
    for sname, shards in shard_lists.items():
        for pql in queries:
            got = _plain(ex.execute("i", pql, shards=shards))
            with monkeypatch.context() as m:
                m.setattr(View, "row_summary", lambda self: None)
                want = _plain(ex.execute("i", pql, shards=shards))
            assert got == want, (pql, sname)


@pytest.mark.parametrize("kind", ["random", "skewed"])
def test_differential_static(kind, monkeypatch, rng):
    h, idx, ex = _mk(kind, rng)
    before = _counters()
    _differential(ex, monkeypatch)
    d = _delta(before)
    assert d["hits"] > 0 and d["rebuilds"] == len(ROWS), d
    # served, not bypassed: only the forced runs count there
    assert ex.execute("i", "Rows(a)")[0] == list(range(ROWS["a"]))
    h.close()


def _w_set_new_row(idx, ex):
    assert ex.execute("i", f"Set({11 * SHARD_WIDTH + 5}, a=9)") == [True]


def _w_clear_last_bit(idx, ex):
    f = idx.field("c")
    shard, rid = 0, 2
    frag = f.view().fragment(shard)
    for p in frag.row_positions(rid)[1:]:
        frag.clear_bit(rid, int(p))
    # one bit of row 2 left in shard 0, and none anywhere else
    for s in range(1, N_SHARDS):
        other = f.view().fragment_if_exists(s)
        for p in other.row_positions(rid):
            other.clear_bit(rid, int(p))
    (last,) = frag.row_positions(rid)
    assert ex.execute("i", "Rows(c)")[0] == [0, 1, 2]
    assert ex.execute("i", f"Clear({int(last)}, c=2)") == [True]


def _w_import_bits_exact(idx, ex):
    # clear=True takes the exact per-fragment bulk_import path
    rows, cols = np.array([0, 1, 0], np.uint64), np.array(
        [3, 3 + 9 * SHARD_WIDTH, 3 + 40 * SHARD_WIDTH], np.uint64
    )
    idx.field("a").import_bits(rows, cols)
    ex.execute("i", "Rows(a)")
    idx.field("a").import_bits(rows[:2], cols[:2], clear=True)


def _w_staged_burst(idx, ex):
    # set imports stage per fragment; nothing merges until a read barrier
    rng = np.random.default_rng(7)
    cols = rng.integers(0, N_SHARDS * SHARD_WIDTH, 600).astype(np.uint64)
    rows = rng.integers(0, 8, 600).astype(np.uint64)  # rows 5..7 are new
    idx.field("a").import_bits(rows, cols)
    assert any(
        fr._pending_n for fr in idx.field("a").view().fragments.values()
    )


def _w_import_value(idx, ex):
    # 900 on the lowest populated column of each of the first 30 shards
    frags = idx.field("a").view().fragments
    cols = np.array(
        [
            s * SHARD_WIDTH
            + min(int(frags[s].row_positions(r)[0]) for r in frags[s].row_ids())
            for s in range(30)
        ],
        np.uint64,
    )
    idx.field("v").import_values(cols, np.full(len(cols), 900, np.int64))


def _w_delete_fragment(idx, ex):
    assert idx.field("a").view().delete_fragment(3)
    assert idx.field("b").view().delete_fragment(21)


def _w_fragment_created_empty(idx, ex):
    idx.field("a").view().fragment(60)
    idx.field("b").view().fragment(61)


WRITES = {
    "set_new_row": _w_set_new_row,
    "clear_last_bit": _w_clear_last_bit,
    "import_bits_exact": _w_import_bits_exact,
    "staged_burst": _w_staged_burst,
    "import_value": _w_import_value,
    "delete_fragment": _w_delete_fragment,
    "fragment_created_empty": _w_fragment_created_empty,
}


@pytest.mark.parametrize("kind", ["random", "skewed"])
@pytest.mark.parametrize("write", sorted(WRITES))
def test_differential_after_write(write, kind, monkeypatch, rng):
    h, idx, ex = _mk(kind, rng)
    for pql in QUERIES:  # warm every table the queries read
        ex.execute("i", pql)
    WRITES[write](idx, ex)
    _differential(ex, monkeypatch)
    h.close()


@pytest.mark.parametrize("write", sorted(WRITES))
def test_write_visible_to_next_read(write, rng):
    """The read that follows an acknowledged write at once, checked
    against what the write must have done (not against another path)."""
    h, idx, ex = _mk("random", rng)
    rows_a = ex.execute("i", "Rows(a)")[0]
    top_a = {p.id: p.count for p in ex.execute("i", "TopN(a)")[0]}
    rows_c = ex.execute("i", "Rows(c)")[0]
    gb = ex.execute("i", "GroupBy(Rows(a), Rows(b))")[0]
    WRITES[write](idx, ex)
    if write == "set_new_row":
        assert ex.execute("i", "Rows(a)")[0] == rows_a + [9]
        assert {p.id: p.count for p in ex.execute("i", "TopN(a)")[0]} == {
            **top_a, 9: 1,
        }
    elif write == "clear_last_bit":
        assert ex.execute("i", "Rows(c)")[0] == [0, 1]
        assert rows_c == [0, 1, 2]
    elif write == "import_bits_exact":
        # col 3 of shard 40 stays set for row 0; the two cleared go
        got = {p.id: p.count for p in ex.execute("i", "TopN(a, ids=[0, 1])")[0]}
        frag = idx.field("a").view().fragment(40)
        assert frag.contains(0, 3)
        want = {
            r: sum(
                fr.row_count(r)
                for fr in idx.field("a").view().fragments.values()
            )
            for r in (0, 1)
        }
        assert got == want
    elif write == "staged_burst":
        assert ex.execute("i", "Rows(a)")[0] == list(range(8))
        total = sum(p.count for p in ex.execute("i", "TopN(a)")[0])
        assert total > sum(top_a.values())
    elif write == "import_value":
        got = ex.execute("i", "GroupBy(Rows(a), Rows(b), filter=Row(v == 900))")
        assert sum(g.count for g in got[0]) == 30  # one (a, b) pair a column
        assert ex.execute("i", "Rows(a)")[0] == rows_a
    elif write == "delete_fragment":
        frag_sum = sum(
            fr.row_count(0) for fr in idx.field("a").view().fragments.values()
        )
        (p,) = ex.execute("i", "TopN(a, ids=[0])")[0]
        assert p.count == frag_sum < top_a[0]
        after = ex.execute("i", "GroupBy(Rows(a), Rows(b))")[0]
        assert sum(g.count for g in after) < sum(g.count for g in gb)
    elif write == "fragment_created_empty":
        assert ex.execute("i", "Rows(a)")[0] == rows_a
        assert {p.id: p.count for p in ex.execute("i", "TopN(a)")[0]} == top_a
        after = ex.execute("i", "GroupBy(Rows(a), Rows(b))")[0]
        assert [g.to_json() for g in after] == [g.to_json() for g in gb]
    h.close()


# -- counting ---------------------------------------------------------------

N_WIDE = 208


@pytest.fixture
def wide(rng):
    """Two 3-row fields over 208 shards, two bits a row a shard."""
    h = Holder().open()
    idx = h.create_index("i")
    for name in ("a", "b"):
        cols = (
            np.repeat(np.arange(N_WIDE, dtype=np.uint64), 6)
            * np.uint64(SHARD_WIDTH)
            + np.tile(np.arange(6, dtype=np.uint64), N_WIDE) * np.uint64(17)
        )
        rows = np.tile(np.array([0, 0, 1, 1, 2, 2], np.uint64), N_WIDE)
        idx.create_field(name).import_bits(rows, cols)
    yield h, idx, Executor(h)
    h.close()


class _Calls:
    """Counts calls to the per-fragment metadata readers."""

    NAMES = ("row_count", "cache_top_arrays", "cache_counts_exact")

    def __init__(self, monkeypatch):
        self.n = dict.fromkeys(self.NAMES, 0)
        for name in self.NAMES:
            monkeypatch.setattr(Fragment, name, self._wrap(name))

    def _wrap(self, name):
        real = getattr(Fragment, name)

        def counted(frag, *a, **kw):
            self.n[name] += 1
            return real(frag, *a, **kw)

        return counted

    def total(self):
        return sum(self.n.values())


@pytest.mark.parametrize(
    "pql", ["GroupBy(Rows(a), Rows(b))", "TopN(a, n=3)", "Rows(b)"]
)
def test_warm_repeat_touches_no_fragment(pql, wide, monkeypatch):
    h, idx, ex = wide
    first = _plain(ex.execute("i", pql))
    calls = _Calls(monkeypatch)
    before = _counters()
    assert _plain(ex.execute("i", pql)) == first
    d = _delta(before)
    assert calls.total() == 0, calls.n
    assert d["hits"] > 0, d
    assert (d["rebuilds"], d["refreshed_shards"], d["bypassed"]) == (0, 0, 0), d


@pytest.mark.parametrize("pql", ["GroupBy(Rows(a), Rows(b))", "TopN(a, n=3)"])
def test_one_set_rereads_one_shard(pql, wide, monkeypatch):
    h, idx, ex = wide
    ex.execute("i", pql)
    ex.execute("i", f"Set({100 * SHARD_WIDTH + 999}, a=1)")
    calls = _Calls(monkeypatch)
    before = _counters()
    got = ex.execute("i", pql)[0]
    d = _delta(before)
    assert d["refreshed_shards"] == 1 and d["rebuilds"] == 0, d
    # the one moved fragment is read once (its rank-cache arrays); no other
    assert calls.n["row_count"] == 0 and calls.n["cache_counts_exact"] == 0
    assert calls.n["cache_top_arrays"] == 1, calls.n
    if pql.startswith("TopN"):
        assert {p.id: p.count for p in got} == {
            0: 2 * N_WIDE, 1: 2 * N_WIDE + 1, 2: 2 * N_WIDE,
        }
    else:
        assert sum(g.count for g in got) == 6 * N_WIDE


def test_no_answer_is_stored(wide):
    """Result cache off: two identical TopN requests both run the
    selection over the counts (both passes each)."""
    h, idx, ex = wide
    before = exmod.TOPN_STATS["batched"]
    a = ex.execute("i", "TopN(a, n=3)")[0]
    assert exmod.TOPN_STATS["batched"] == before + 2
    b = ex.execute("i", "TopN(a, n=3)")[0]
    assert exmod.TOPN_STATS["batched"] == before + 4
    assert [(p.id, p.count) for p in a] == [(p.id, p.count) for p in b]


def _mk_bypass(case, rng):
    h = Holder().open()
    idx = h.create_index("i")
    n_shards = 12
    cols = (
        np.repeat(np.arange(n_shards, dtype=np.uint64), 40)
        * np.uint64(SHARD_WIDTH)
        + rng.integers(0, SHARD_WIDTH, n_shards * 40).astype(np.uint64)
    )
    rows = rng.integers(0, 10, len(cols)).astype(np.uint64)
    if case == "column":
        idx.create_field("f").import_bits(rows, cols)
        pql = f"Rows(f, column={int(cols[0])})"
    elif case == "time_range":
        from datetime import datetime

        f = idx.create_field("f", FieldOptions(type="time", time_quantum="YMD"))
        stamps = [datetime(2019, 1 + int(r) % 3, 2) for r in rows]
        f.import_bits(rows, cols, timestamps=stamps)
        pql = 'Rows(f, from="2019-02-01T00:00", to="2019-03-01T00:00")'
    elif case == "lru_cache":
        idx.create_field("f", FieldOptions(cache_type="lru")).import_bits(rows, cols)
        pql = "TopN(f, n=4)"
    elif case == "pruned_rank_cache":
        idx.create_field("f", FieldOptions(cache_size=4)).import_bits(rows, cols)
        pql = "TopN(f, n=2)"
    return h, Executor(h), pql


@pytest.mark.parametrize(
    "case", ["column", "time_range", "lru_cache", "pruned_rank_cache"]
)
def test_bypass_counts_and_answers_as_before(case, monkeypatch, rng):
    h, ex, pql = _mk_bypass(case, rng)
    ex.execute("i", pql)
    before = _counters()
    got = _plain(ex.execute("i", pql))
    d = _delta(before)
    assert d["bypassed"] >= 1, d
    with monkeypatch.context() as m:
        m.setattr(View, "row_summary", lambda self: None)
        want = _plain(ex.execute("i", pql))
    assert got == want and got[0], pql
    if case in ("lru_cache", "pruned_rank_cache"):
        # the table itself is still exact for Rows over the same field
        assert ex.execute("i", "Rows(f)")[0] == list(range(10))
    h.close()


def test_cold_shards_keep_the_fragment_path(rng):
    """A view whose tier resolver reports cold shards has no table: the
    per-fragment path hydrates through fragment_if_exists."""
    h, idx, ex = _mk("random", rng)
    want = ex.execute("i", "Rows(a)")[0]
    v = idx.field("a").view()

    class Resolver:
        def cold_shards(self, view):
            return {999}

        def resolve(self, view, shard):
            return None

        def touch_many(self, view, shards):
            pass

    v.cold_resolver = Resolver()
    assert v.row_summary() is None
    before = _counters()
    assert ex.execute("i", "Rows(a)")[0] == want
    assert _delta(before)["bypassed"] == 1
    v.cold_resolver = None
    assert v.row_summary() is not None
    h.close()


def test_readers_race_a_writer(monkeypatch, rng):
    """Reads overlapping writes never fail and never go backwards past
    an acknowledged write; once the writer stops, the table's answers
    equal the per-fragment path's."""
    import sys
    import threading

    h, idx, ex = _mk("random", rng)
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # preempt inside the table's build too
    stop = threading.Event()
    acked = []  # row ids whose Set has returned
    errors = []

    def writer():
        try:
            for k in range(60):
                rid = 100 + k
                ex.execute("i", f"Set({(k % N_SHARDS) * SHARD_WIDTH + 77}, a={rid})")
                acked.append(rid)
        except Exception as e:  # noqa: BLE001
            errors.append(e)
        finally:
            stop.set()

    t = threading.Thread(target=writer)
    t.start()
    try:
        while not stop.is_set():
            seen = list(acked)
            rows = set(ex.execute("i", "Rows(a)")[0])
            assert rows.issuperset(seen)
            top = {p.id for p in ex.execute("i", "TopN(a)")[0]}
            assert top.issuperset(seen)
    finally:
        t.join(timeout=120)
        sys.setswitchinterval(switch)
    assert not t.is_alive() and len(acked) == 60
    assert not errors, errors
    _differential(
        ex, monkeypatch,
        queries=["Rows(a)", "TopN(a)", "TopN(a, n=4)", "GroupBy(Rows(a), Rows(b))"],
        shard_lists={"all": None},
    )
    h.close()
