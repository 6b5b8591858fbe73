"""Stacked (compiled mesh) query path tests.

VERDICT round-1 task 1 acceptance: Count(Intersect(Row,Row)) over >=64
shards issues exactly ONE compiled device dispatch (asserted via the plan
dispatch counter), the same code path runs unchanged on the 8-device CPU
mesh, and results match the per-shard path / naive oracle exactly.

Reference parity: replaces the role of the per-shard mapReduce worker pool
(/root/reference/executor.go:2460-2613).
"""

import numpy as np
import pytest

import jax

from pilosa_tpu.core.field import FIELD_TYPE_INT, FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import plan as planmod
from pilosa_tpu.exec.executor import ExecError, Executor
from pilosa_tpu.parallel import mesh as pmesh
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_ROW


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "holder")).open()
    yield h
    h.close()


def _populate(idx, field, pairs):
    """pairs: iterable of (row, col)."""
    f = idx.field(field) or idx.create_field(field)
    rows = np.array([p[0] for p in pairs], np.uint64)
    cols = np.array([p[1] for p in pairs], np.uint64)
    f.import_bits(rows, cols)
    idx.track_columns(cols)
    return f


def _mk_index(holder, n_shards=4, seed=3):
    idx = holder.create_index("stk", track_existence=True)
    rng = np.random.default_rng(seed)
    pairs_a = [(1, int(c)) for c in rng.integers(0, n_shards * SHARD_WIDTH, 500)]
    pairs_b = [(2, int(c)) for c in rng.integers(0, n_shards * SHARD_WIDTH, 500)]
    _populate(idx, "f", pairs_a + pairs_b)
    return idx


def _expected_counts(idx):
    f = idx.field("f")
    a = set()
    b = set()
    from pilosa_tpu.core.view import VIEW_STANDARD

    v = f.view(VIEW_STANDARD)
    for shard, frag in v.fragments.items():
        base = shard * SHARD_WIDTH
        a.update(base + int(p) for p in frag.row_positions(1))
        b.update(base + int(p) for p in frag.row_positions(2))
    return a, b


class TestStackedCorrectness:
    def test_count_matches_serial(self, holder):
        idx = _mk_index(holder)
        ex = Executor(holder)
        a, b = _expected_counts(idx)
        q = "Count(Intersect(Row(f=1), Row(f=2)))"
        got = ex.execute("stk", q)[0]
        assert got == len(a & b)
        # serial fallback agrees
        import pilosa_tpu.exec.executor as exmod

        old = exmod._STACKED_ENABLED
        exmod._STACKED_ENABLED = False
        try:
            assert ex.execute("stk", q)[0] == got
        finally:
            exmod._STACKED_ENABLED = old

    def test_bitmap_algebra_matches_oracle(self, holder):
        idx = _mk_index(holder)
        ex = Executor(holder)
        a, b = _expected_counts(idx)
        cases = {
            "Union(Row(f=1), Row(f=2))": a | b,
            "Intersect(Row(f=1), Row(f=2))": a & b,
            "Difference(Row(f=1), Row(f=2))": a - b,
            "Xor(Row(f=1), Row(f=2))": a ^ b,
            "Not(Row(f=1))": (a | b) - a,
        }
        for q, want in cases.items():
            row = ex.execute("stk", q)[0]
            assert set(row.columns().tolist()) == want, q

    def test_count_missing_row_is_zero(self, holder):
        idx = _mk_index(holder)
        ex = Executor(holder)
        assert ex.execute("stk", "Count(Row(f=99))")[0] == 0
        assert ex.execute("stk", "Count(Intersect(Row(f=1), Row(f=99)))")[0] == 0
        assert (
            ex.execute("stk", "Count(Union(Row(f=1), Row(f=99)))")[0]
            == ex.execute("stk", "Count(Row(f=1))")[0]
        )

    def test_shift_carries_across_shards(self, holder):
        idx = holder.create_index("shift_idx")
        f = idx.create_field("f")
        # last column of shard 0 -> shifts into shard 1
        f.set_bit(1, SHARD_WIDTH - 1)
        f.set_bit(1, 10)
        idx.track_columns(np.array([SHARD_WIDTH - 1, 10], np.uint64))
        ex = Executor(holder)
        row = ex.execute("shift_idx", "Shift(Row(f=1), n=1)")[0]
        assert set(row.columns().tolist()) == {11, SHARD_WIDTH}

    def test_shift_carry_with_explicit_shard_subset(self, holder):
        """A query restricted to shard 1 must still receive the carry from
        shard 0's last column (serial path reads shard-1 regardless of the
        subset; the stacked plan appends predecessor shards to the stack)."""
        idx = holder.create_index("sub")
        f = idx.create_field("f")
        f.set_bit(1, SHARD_WIDTH - 1)  # shard 0, last col
        f.set_bit(1, SHARD_WIDTH + 5)  # shard 1
        idx.track_columns(np.array([SHARD_WIDTH - 1, SHARD_WIDTH + 5], np.uint64))
        ex = Executor(holder)
        row = ex.execute("sub", "Shift(Row(f=1), n=1)", shards=[1])[0]
        got = set(row.columns().tolist())
        assert got == {SHARD_WIDTH, SHARD_WIDTH + 6}
        # serial fallback agrees
        import pilosa_tpu.exec.executor as exmod

        old = exmod._STACKED_ENABLED
        exmod._STACKED_ENABLED = False
        try:
            row2 = ex.execute("sub", "Shift(Row(f=1), n=1)", shards=[1])[0]
            assert set(row2.columns().tolist()) == got
        finally:
            exmod._STACKED_ENABLED = old

    def test_bsi_conditions_stacked(self, holder):
        idx = holder.create_index("bsi_idx")
        f = idx.create_field("v", FieldOptions(type=FIELD_TYPE_INT, min=-100, max=100))
        vals = {}
        rng = np.random.default_rng(5)
        for col in rng.integers(0, 3 * SHARD_WIDTH, 200):
            vals[int(col)] = int(rng.integers(-100, 101))
        cols = np.array(list(vals), np.uint64)
        f.import_values(cols, np.array(list(vals.values()), np.int64))
        idx.track_columns(cols)
        ex = Executor(holder)
        for q, pred in [
            ("Row(v > 10)", lambda x: x > 10),
            ("Row(v >= 10)", lambda x: x >= 10),
            ("Row(v < -5)", lambda x: x < -5),
            ("Row(v <= 0)", lambda x: x <= 0),
            ("Row(v == 7)", lambda x: x == 7),
            ("Row(v != 7)", lambda x: x != 7),
            ("Row(-20 < v < 30)", lambda x: -20 < x < 30),
        ]:
            got = set(ex.execute("bsi_idx", q)[0].columns().tolist())
            want = {c for c, x in vals.items() if pred(x)}
            assert got == want, q


class TestOneDispatch:
    def test_count_is_one_dispatch_64_shards(self, holder):
        idx = holder.create_index("wide", track_existence=True)
        rng = np.random.default_rng(11)
        n_shards = 64
        pairs = [(1, int(c)) for c in rng.integers(0, n_shards * SHARD_WIDTH, 2000)]
        pairs += [(2, int(c)) for c in rng.integers(0, n_shards * SHARD_WIDTH, 2000)]
        _populate(idx, "f", pairs)
        # make every shard exist so the fan-out really covers 64 shards
        f = idx.field("f")
        for s in range(n_shards):
            f.set_bit(1, s * SHARD_WIDTH)
        ex = Executor(holder)
        assert len(idx.available_shards()) == n_shards

        # warm the stacks, then assert: one plan eval, zero serial lowering
        ex.execute("wide", "Count(Intersect(Row(f=1), Row(f=2)))")
        planmod.reset_stats()
        from pilosa_tpu.core.resultcache import RESULT_CACHE

        RESULT_CACHE.reset()  # the probe asserts the dispatch, not the cache
        import pilosa_tpu.exec.executor as exmod

        def boom(*a, **k):  # the serial per-shard path must never run
            raise AssertionError("per-shard path used on stacked query")

        old = exmod.Executor._bitmap_call_shard
        exmod.Executor._bitmap_call_shard = boom
        try:
            got = ex.execute("wide", "Count(Intersect(Row(f=1), Row(f=2)))")[0]
        finally:
            exmod.Executor._bitmap_call_shard = old
        assert planmod.STATS["evals"] == 1
        assert got >= 0


class TestStackedOnMesh:
    """The same executor path, unchanged, over the 8-device CPU mesh."""

    @pytest.fixture(autouse=True)
    def mesh(self):
        m = pmesh.make_mesh(jax.devices())
        pmesh.set_active_mesh(m)
        yield m
        pmesh.set_active_mesh(None)

    def test_count_on_mesh_matches(self, holder):
        idx = _mk_index(holder, n_shards=6)  # not divisible by mesh: padding
        ex = Executor(holder)
        a, b = _expected_counts(idx)
        got = ex.execute("stk", "Count(Intersect(Row(f=1), Row(f=2)))")[0]
        assert got == len(a & b)
        got_u = ex.execute("stk", "Count(Union(Row(f=1), Row(f=2)))")[0]
        assert got_u == len(a | b)

    def test_bitmap_and_shift_on_mesh(self, holder):
        idx = _mk_index(holder, n_shards=5)
        ex = Executor(holder)
        a, b = _expected_counts(idx)
        row = ex.execute("stk", "Difference(Row(f=1), Row(f=2))")[0]
        assert set(row.columns().tolist()) == a - b
        # shift across the sharded axis = cross-device carry
        idx2 = holder.create_index("mshift")
        f = idx2.create_field("f")
        f.set_bit(1, SHARD_WIDTH - 1)
        f.set_bit(1, 3 * SHARD_WIDTH - 2)
        idx2.track_columns(
            np.array([SHARD_WIDTH - 1, 3 * SHARD_WIDTH - 2], np.uint64)
        )
        row = ex.execute("mshift", "Shift(Row(f=1), n=2)")[0]
        assert set(row.columns().tolist()) == {SHARD_WIDTH + 1, 3 * SHARD_WIDTH}

    def test_bsi_on_mesh(self, holder):
        idx = holder.create_index("mbsi")
        f = idx.create_field("v", FieldOptions(type=FIELD_TYPE_INT, min=0, max=1000))
        cols = np.arange(0, 3 * SHARD_WIDTH, SHARD_WIDTH // 3, dtype=np.uint64)
        vals = (cols % 997).astype(np.int64)
        f.import_values(cols, vals)
        idx.track_columns(cols)
        ex = Executor(holder)
        got = set(ex.execute("mbsi", "Row(v > 500)")[0].columns().tolist())
        want = {int(c) for c, v in zip(cols, vals) if v > 500}
        assert got == want


class TestStackCacheInvalidation:
    def test_write_invalidates_stack(self, holder):
        idx = _mk_index(holder, n_shards=3)
        ex = Executor(holder)
        before = ex.execute("stk", "Count(Row(f=1))")[0]
        f = idx.field("f")
        f.set_bit(1, 2 * SHARD_WIDTH + 12345)
        after = ex.execute("stk", "Count(Row(f=1))")[0]
        assert after == before + 1


class TestStackedBSIAggregates:
    """Stacked Sum/Min/Max: one dispatch over all shards, exact host
    combine; results must match the per-shard path and a naive model."""

    def _mk_bsi(self, holder, n_shards=5, seed=11, lo=-300, hi=300):
        idx = holder.create_index("agg", track_existence=True)
        rng = np.random.default_rng(seed)
        cols = np.unique(
            rng.integers(0, n_shards * SHARD_WIDTH, 3000).astype(np.uint64)
        )
        vals = rng.integers(lo, hi + 1, len(cols)).astype(np.int64)
        v = idx.create_field("v", FieldOptions(type=FIELD_TYPE_INT, min=lo, max=hi))
        v.import_values(cols, vals)
        idx.track_columns(cols)
        # a filter row hitting ~half the columns
        fcols = cols[rng.random(len(cols)) < 0.5]
        f = idx.create_field("f")
        f.import_bits(np.full(len(fcols), 1, np.uint64), fcols)
        return idx, dict(zip(cols.tolist(), vals.tolist())), set(fcols.tolist())

    def test_sum_min_max_match_naive_and_serial(self, holder, monkeypatch):
        import pilosa_tpu.exec.executor as exmod

        idx, model, filt = self._mk_bsi(holder)
        ex = Executor(holder)
        queries = ["Sum(field=v)", "Min(field=v)", "Max(field=v)",
                   "Sum(Row(f=1), field=v)", "Min(Row(f=1), field=v)",
                   "Max(Row(f=1), field=v)"]

        vals_all = list(model.values())
        vals_f = [v for c, v in model.items() if c in filt]
        want = [
            (sum(vals_all), len(vals_all)),
            (min(vals_all), vals_all.count(min(vals_all))),
            (max(vals_all), vals_all.count(max(vals_all))),
            (sum(vals_f), len(vals_f)),
            (min(vals_f), vals_f.count(min(vals_f))),
            (max(vals_f), vals_f.count(max(vals_f))),
        ]
        planmod.reset_stats()
        got = [ex.execute("agg", q)[0] for q in queries]
        for q, g, w in zip(queries, got, want):
            assert (g.value, g.count) == w, (q, (g.value, g.count), w)
        # plane-streamed accounting (round 11): every aggregate is ONE
        # counted dispatch (run_counted); filtered ones additionally
        # evaluate the filter plan once each
        assert planmod.STATS["evals"] == 9, planmod.STATS

        # serial path agrees
        monkeypatch.setattr(exmod, "_STACKED_ENABLED", False)
        got_serial = [ex.execute("agg", q)[0] for q in queries]
        for q, g, s in zip(queries, got_serial, got):
            assert (g.value, g.count) == (s.value, s.count), q

    def test_sum_empty_field(self, holder):
        idx = holder.create_index("agg2", track_existence=True)
        idx.create_field("v", FieldOptions(type=FIELD_TYPE_INT, min=0, max=10))
        ex = Executor(holder)
        for q in ("Sum(field=v)", "Min(field=v)", "Max(field=v)"):
            r = ex.execute("agg2", q)[0]
            assert (r.value, r.count) == (0, 0), q

    def test_sum_on_mesh(self, holder):
        idx, model, filt = self._mk_bsi(holder, n_shards=7, seed=23)
        mesh = pmesh.make_mesh(jax.devices())
        pmesh.set_active_mesh(mesh)
        try:
            ex = Executor(holder)
            g = ex.execute("agg", "Sum(Row(f=1), field=v)")[0]
            vals_f = [v for c, v in model.items() if c in filt]
            assert (g.value, g.count) == (sum(vals_f), len(vals_f))
            m = ex.execute("agg", "Min(field=v)")[0]
            assert m.value == min(model.values())
        finally:
            pmesh.set_active_mesh(None)


class TestStackedGroupBy:
    """Device GroupBy (exec/groupby.py): the whole cross-product tallied in
    O(depth) batched dispatches, matching the per-shard recursive walk
    (reference: executor.go:3063 groupByIterator)."""

    def _mk_gb(self, holder, n_shards=4, seed=5, rows_a=6, rows_b=5, rows_c=3):
        idx = holder.create_index("gb", track_existence=True)
        rng = np.random.default_rng(seed)
        # shared column pool spanning all shards, so row intersections
        # across fields are dense enough to produce real groups
        pool = np.unique(
            rng.integers(0, n_shards * SHARD_WIDTH, 800).astype(np.uint64)
        )
        for name, n_rows, n_bits in (
            ("a", rows_a, 2500), ("b", rows_b, 2500), ("c", rows_c, 1500)
        ):
            rows = rng.integers(0, n_rows, n_bits).astype(np.uint64)
            cols = rng.choice(pool, n_bits)
            f = idx.create_field(name)
            f.import_bits(rows, cols)
            idx.track_columns(cols)
        return idx

    def _serial(self, ex, monkeypatch, query):
        import pilosa_tpu.exec.executor as exmod

        with monkeypatch.context() as m:
            m.setattr(exmod, "_STACKED_ENABLED", False)
            return ex.execute("gb", query)[0]

    @staticmethod
    def _as_t(gs):
        return [
            (tuple((fr.field, fr.row_id) for fr in g.group), g.count) for g in gs
        ]

    @pytest.mark.parametrize(
        "query",
        [
            "GroupBy(Rows(a))",
            "GroupBy(Rows(a), Rows(b))",
            "GroupBy(Rows(a), Rows(b), Rows(c))",
            "GroupBy(Rows(a), Rows(b), filter=Row(c=1))",
            "GroupBy(Rows(a), Rows(b), filter=Intersect(Row(c=0), Row(c=1)))",
            "GroupBy(Rows(a), Rows(b), limit=3)",
            "GroupBy(Rows(a), Rows(b), previous=[2, 1])",
            "GroupBy(Rows(a, previous=1), Rows(b, previous=2), limit=4)",
            "GroupBy(Rows(a), Rows(b, previous=3), filter=Row(c=1))",
        ],
    )
    def test_matches_serial(self, holder, monkeypatch, query):
        idx = self._mk_gb(holder)
        ex = Executor(holder)
        got = ex.execute("gb", query)[0]
        want = self._serial(ex, monkeypatch, query)
        assert self._as_t(got) == self._as_t(want), query
        assert got, query  # non-trivial corpus

    def test_dispatch_count_is_o_depth(self, holder):
        from pilosa_tpu.exec import groupby as qgb

        idx = self._mk_gb(holder)
        ex = Executor(holder)
        qgb.reset_stats()
        groups = ex.execute("gb", "GroupBy(Rows(a), Rows(b))")[0]
        assert len(groups) >= 20  # the walk would pay >= 1 dispatch/group
        # r5 one-shot path (small cross-product): depth-2 no-filter =
        # ONE cross-tally dispatch and crucially ONE host read
        assert qgb.STATS["evals"] == 1, qgb.STATS

    def test_group_by_on_mesh(self, holder, monkeypatch):
        idx = self._mk_gb(holder, n_shards=6, seed=9)
        mesh = pmesh.make_mesh(jax.devices())
        pmesh.set_active_mesh(mesh)
        try:
            ex = Executor(holder)
            got = ex.execute("gb", "GroupBy(Rows(a), Rows(b), filter=Row(c=2))")[0]
        finally:
            pmesh.set_active_mesh(None)
        want = self._serial(ex, monkeypatch, "GroupBy(Rows(a), Rows(b), filter=Row(c=2))")
        assert self._as_t(got) == self._as_t(want)
        assert got

    @staticmethod
    def _engage_kernel(monkeypatch, qgb, gmax=2):
        """What a one-chip TPU presents, steered from the test: the tally
        takes the VMEM kernel (interpreted here) and the prefix tile holds
        `gmax` rows, as 256 MB does at 954 shards."""
        monkeypatch.setattr(qgb, "_kernel_covers", lambda *stacks: True)
        monkeypatch.setattr(qgb, "_gmax", lambda s, w: gmax)

    @pytest.mark.parametrize(
        "query,launches",
        [
            ("GroupBy(Rows(a), Rows(b))", 1),
            ("GroupBy(Rows(a), Rows(b), Rows(c))", 1),
            ("GroupBy(Rows(a), Rows(b), Rows(c), filter=Row(a=1))", 1),
            ("GroupBy(Rows(a), Rows(b), filter=Row(c=1))", 1),
            # four levels: the cross of the first two is written out (one
            # expand, within the tile), the last two ride in the kernel
            ("GroupBy(Rows(c), Rows(c), Rows(a), Rows(b))", None),
        ],
    )
    def test_kernel_tallies_up_to_three_levels_in_one_launch(
        self, holder, monkeypatch, query, launches
    ):
        """With the kernel a 2- or 3-field GroupBy is ONE launch and one
        read at a prefix tile of two rows, where the XLA program has to
        chunk its prefixes by two and descend."""
        from jax.experimental.pallas import tpu as pltpu

        from pilosa_tpu.core.resultcache import RESULT_CACHE
        from pilosa_tpu.exec import groupby as qgb

        self._mk_gb(holder)
        ex = Executor(holder)
        want = self._serial(ex, monkeypatch, query)
        assert want
        with monkeypatch.context() as m:
            m.setattr(qgb, "_gmax", lambda s, w: 2)
            RESULT_CACHE.reset()  # each run below must execute
            qgb.reset_stats()
            assert self._as_t(ex.execute("gb", query)[0]) == self._as_t(want)
            chunked = dict(qgb.STATS)
        assert chunked["evals"] > 3 and chunked["kernel_tallies"] == 0
        if launches is None:  # 3 x 3 prefixes do not fit a tile of two
            self._engage_kernel(monkeypatch, qgb, gmax=9)
            launches = 2
        else:
            self._engage_kernel(monkeypatch, qgb)
        RESULT_CACHE.reset()
        qgb.reset_stats()
        with pltpu.force_tpu_interpret_mode():
            got = ex.execute("gb", query)[0]
        assert self._as_t(got) == self._as_t(want), query
        assert qgb.STATS == {
            "evals": launches, "kernel_tallies": 1, "xla_tallies": 0,
            "inplace_tallies": 0, "assembled_stacks": 0,  # one extent
            "assembled_bytes": 0, "aggregate_queries": 0, "plane_tallies": 0,
        }

    def test_kernel_replaces_the_xla_tally_inside_the_descent(
        self, holder, monkeypatch
    ):
        """A cross-product whose count read is too large to take whole is
        still pruned level by level; every tally of the descent is then
        the two-operand kernel."""
        from jax.experimental.pallas import tpu as pltpu

        from pilosa_tpu.core.resultcache import RESULT_CACHE
        from pilosa_tpu.exec import groupby as qgb

        self._mk_gb(holder)
        ex = Executor(holder)
        query = "GroupBy(Rows(a), Rows(b), Rows(c), filter=Row(c=0))"
        want = self._serial(ex, monkeypatch, query)
        self._engage_kernel(monkeypatch, qgb)
        monkeypatch.setattr(qgb, "_ONESHOT_READ_BYTES", 64)
        RESULT_CACHE.reset()
        qgb.reset_stats()
        with pltpu.force_tpu_interpret_mode():
            got = ex.execute("gb", query)[0]
        assert self._as_t(got) == self._as_t(want)
        assert qgb.STATS["xla_tallies"] == 0
        assert qgb.STATS["kernel_tallies"] > 3  # depth 0, then per chunk

    @pytest.fixture
    def three_extents(self, holder):
        """5 shards staged as extents of 2 + 2 + 1 on one device, as 954
        are as 256 + 256 + 256 + 186 on a chip."""
        from pilosa_tpu.core.devcache import DEVICE_CACHE
        from pilosa_tpu.core.resultcache import RESULT_CACHE
        from pilosa_tpu.hbm import residency as hbm_res

        old_rows = hbm_res.extent_rows()
        DEVICE_CACHE.clear()
        hbm_res.configure(extent_rows=2)
        RESULT_CACHE.reset()
        self._mk_gb(holder, n_shards=5, seed=11)
        yield Executor(holder)
        hbm_res.configure(extent_rows=old_rows)
        DEVICE_CACHE.clear()

    _EXTENT_QUERIES = [
        "GroupBy(Rows(a), Rows(b))",
        "GroupBy(Rows(a), Rows(b), Rows(c))",
        "GroupBy(Rows(a), Rows(b), filter=Row(c=1))",
    ]

    @pytest.mark.parametrize("query", _EXTENT_QUERIES)
    def test_kernel_reads_resident_extents_in_place(
        self, three_extents, monkeypatch, query
    ):
        """A view of three extents is tallied where it lies: one kernel
        tally, in place, and no operand concatenated."""
        from jax.experimental.pallas import tpu as pltpu

        from pilosa_tpu.core.resultcache import RESULT_CACHE
        from pilosa_tpu.exec import groupby as qgb

        ex = three_extents
        want = self._serial(ex, monkeypatch, query)
        assert want
        self._engage_kernel(monkeypatch, qgb)
        RESULT_CACHE.reset()  # the run below must execute
        qgb.reset_stats()
        with pltpu.force_tpu_interpret_mode():
            got = ex.execute("gb", query)[0]
        assert self._as_t(got) == self._as_t(want), query
        assert qgb.STATS == {
            "evals": 1, "kernel_tallies": 1, "xla_tallies": 0,
            "inplace_tallies": 1, "assembled_stacks": 0,
            "assembled_bytes": 0, "aggregate_queries": 0, "plane_tallies": 0,
        }

    @pytest.mark.parametrize(
        "path,query",
        [
            ("xla", _EXTENT_QUERIES[0]),
            ("xla", _EXTENT_QUERIES[1]),
            ("descent", _EXTENT_QUERIES[0]),
            ("descent", _EXTENT_QUERIES[1]),
            ("four_levels", _EXTENT_QUERIES[0]),
        ],
    )
    def test_paths_that_need_whole_stacks_assemble_the_extents(
        self, three_extents, monkeypatch, query, path
    ):
        """The XLA program, the pruned descent and a cross deeper than the
        kernel's three levels concatenate each operand once, and answer
        as the per-shard walk does."""
        from jax.experimental.pallas import tpu as pltpu

        from pilosa_tpu.core.resultcache import RESULT_CACHE
        from pilosa_tpu.exec import groupby as qgb

        ex = three_extents
        if path == "four_levels":
            query = query.replace("GroupBy(", "GroupBy(Rows(c), Rows(c), ")
        fields = query.count("Rows(")
        want = self._serial(ex, monkeypatch, query)
        assert want
        if path != "xla":
            self._engage_kernel(monkeypatch, qgb, gmax=9)
        if path == "descent":
            monkeypatch.setattr(qgb, "_ONESHOT_READ_BYTES", 64)
        RESULT_CACHE.reset()
        qgb.reset_stats()
        with pltpu.force_tpu_interpret_mode():
            got = ex.execute("gb", query)[0]
        assert self._as_t(got) == self._as_t(want), query
        assert qgb.STATS["assembled_stacks"] == fields
        assert qgb.STATS["inplace_tallies"] == 0
        assert bool(qgb.STATS["xla_tallies"]) == (path == "xla")

    def test_write_restages_one_extent_under_an_in_place_tally(
        self, three_extents, monkeypatch
    ):
        """The parts are the version-keyed extents themselves: a write to
        one shard re-keys the extent that covers it, the next GroupBy
        stages that one again and its answer holds the write."""
        from jax.experimental.pallas import tpu as pltpu

        from pilosa_tpu.exec import groupby as qgb
        from pilosa_tpu.hbm import residency as hbm_res

        ex = three_extents
        query = "GroupBy(Rows(a), Rows(b))"
        self._engage_kernel(monkeypatch, qgb)
        with pltpu.force_tpu_interpret_mode():
            first = self._as_t(ex.execute("gb", query)[0])
            before = hbm_res.stats_snapshot()
            # a column of shard 3 (extent 1 of 3) that row a=0 lacks
            col = 3 * SHARD_WIDTH + 77
            assert ex.execute("gb", f"Set({col}, a=0)")[0] is True
            assert ex.execute("gb", f"Set({col}, b=0)")[0] is True
            qgb.reset_stats()
            second = self._as_t(ex.execute("gb", query)[0])
        after = hbm_res.stats_snapshot()
        key = ((("a", 0), ("b", 0)))
        assert dict(second)[key] == dict(first).get(key, 0) + 1
        assert qgb.STATS["inplace_tallies"] == 1
        assert qgb.STATS["assembled_stacks"] == 0
        # one extent of each written field went up again (or was patched
        # on the device), never the other two
        extent_bytes = lambda rows: rows * 2 * WORDS_PER_ROW * 4  # noqa: E731
        restaged = after["restage_bytes"] - before["restage_bytes"]
        patched = after["extent_patches"] - before["extent_patches"]
        assert restaged <= extent_bytes(6) + extent_bytes(5)
        assert restaged or patched
        want = self._serial(ex, monkeypatch, query)
        assert second == self._as_t(want)

    def test_kernel_covers_only_stacks_on_one_tpu(self, holder):
        """The choice of program is read from the operands: host arrays,
        another backend and mesh-sharded stacks are the XLA program's."""
        from pilosa_tpu.exec import groupby as qgb

        host = np.zeros((2, 4, 256), np.uint32)
        dev = jax.numpy.asarray(host)
        mesh = pmesh.make_mesh(jax.devices())
        pmesh.set_active_mesh(mesh)
        try:
            sharded = pmesh.put_stack(np.zeros((2, 8, 256), np.uint32))
        finally:
            pmesh.set_active_mesh(None)
        assert len(sharded.devices()) > 1
        for stack in (host, dev, sharded):
            assert not qgb._kernel_covers(stack)
            assert qgb.tally_program([stack]) == "jit__counts_cross"
        qgb.reset_stats()
        out = qgb.cross_tally(dev, dev, filt=dev[0])
        assert out.shape == (2, 2, 4)
        assert qgb.STATS["xla_tallies"] == 1 and not qgb.STATS["kernel_tallies"]

    def test_tiny_tile_chunking(self, holder, monkeypatch):
        """Force one-prefix chunks: results identical, memory bounded."""
        from pilosa_tpu.exec import groupby as qgb

        monkeypatch.setattr(qgb, "_tile_bytes", lambda: 1)  # gmax == 1
        idx = self._mk_gb(holder)
        ex = Executor(holder)
        got = ex.execute("gb", "GroupBy(Rows(a), Rows(b), Rows(c))")[0]
        want = self._serial(ex, monkeypatch, "GroupBy(Rows(a), Rows(b), Rows(c))")
        assert self._as_t(got) == self._as_t(want)
