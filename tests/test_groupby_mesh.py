"""The aggregate GroupBy where one chip's budget does not hold it: the
group tally as the kernel under `shard_map` on a 2 x 2 mesh (four of the
CPU's virtual devices, the kernel interpreted), and a GroupBy whose stacks
are over the dispatch cap staged and tallied over chunks of the shard
axis — each against the XLA oracle, the per-shard walk and numpy."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from pilosa_tpu.core.devcache import DEVICE_CACHE
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec import executor as exmod
from pilosa_tpu.exec import groupby as qgb
from pilosa_tpu.exec import plan as planmod
from pilosa_tpu.ops import pallas_kernels as pk
from pilosa_tpu.parallel import mesh as pmesh
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_ROW


@pytest.fixture
def mesh_2x2():
    """The ("shards", "cols") mesh a four-chip host forms, over four of
    the CPU's virtual devices, active for the test."""
    from pilosa_tpu.core.resultcache import RESULT_CACHE

    old = pmesh.active_mesh()
    mesh = pmesh.make_mesh(jax.devices()[:4])
    assert dict(mesh.shape) == {"shards": 2, "cols": 2}
    pmesh.set_active_mesh(mesh)
    RESULT_CACHE.reset()
    yield mesh
    pmesh.set_active_mesh(old)
    DEVICE_CACHE.clear()


# -- the kernel under shard_map against its oracle and numpy ----------------


def _numpy_counts(dims, idx, planes, filt, mask_row):
    """[G, 1 + P] of the listed groups, from the bits themselves."""
    out = []
    for g in range(idx.shape[1]):
        t = dims[0][idx[0, g]]
        for d, i in zip(dims[1:], idx[1:, g]):
            t = t & d[i]
        if filt is not None:
            t = t & filt
        row = [np.unpackbits(t.view(np.uint8)).sum()]
        if planes is not None:
            v = t if mask_row is None else t & planes[mask_row]
            row += [np.unpackbits((v & p).view(np.uint8)).sum() for p in planes]
        out.append(row)
    return np.array(out, np.uint64)


def named_table(rng, rows, named, g: int, live: int) -> np.ndarray:
    """int32[n_dims, g]: the first `live` groups name exactly named[d] rows
    of dimension d (drawn from its rows[d], each at least once, repeated
    and shuffled); the rest is the table's padding."""
    idx = np.zeros((len(rows), g), np.int32)
    for d, (r, n) in enumerate(zip(rows, named)):
        pick = rng.choice(r, n, replace=False)
        idx[d, :live] = rng.permutation(pick[np.arange(live) % n])
    return idx


# (rows of each dimension, planes, mask row, filtered, shards[, rows the
# table names of each dimension: the tally is cut to them])
_MESH_CASES = {
    # a prune: one dimension's every row against the filter
    "prune_5": ((7,), 0, None, True, 5),
    "prune_7": ((25,), 0, None, True, 7),
    # the listed groups with the value planes, under the exists mask and
    # under the sign mask (a signed field's second pass)
    "planes_exists_5": ((5, 7, 3), 6, 0, True, 5),
    "planes_sign_7": ((5, 7, 3), 6, 1, True, 7),
    "planes_unfiltered_7": ((6, 4), 5, 0, False, 7),
    # a wide dimension of which a few rows are named (cut to 8 rows, the
    # last named row repeated as padding), beside two no cut would shrink
    "cut_wide_few_5": ((40, 5, 3), 6, 0, True, 5, (3, 5, 2)),
    # 11 groups over 2 x 1 rows: every row repeated in the table
    "cut_repeats_sign_7": ((40, 6), 5, 1, True, 7, (2, 1)),
    # more than 8 rows named: cut to 16 and to 8, no filter
    "cut_sixteen_unfiltered_5": ((40, 12), 3, None, False, 5, (9, 4)),
}


@pytest.mark.parametrize("case", sorted(_MESH_CASES))
def test_mesh_group_tally_equals_the_oracle_and_numpy(
    mesh_2x2, monkeypatch, case
):
    """Each chip tallies its [R, S / 2, W / 2] block and sums it over its
    shards, the "cols" halves are added on the device: one count a mesh
    row of shards, each the oracle's per-shard counts of that row added,
    the padded shards (5 -> 6, 7 -> 8) among them and counting 0. Where
    the table names few rows of a dimension and the tile buffer is too
    small for every resident row at the full width, `group_tally` cuts
    each chip's dimensions to the named rows and the tile widens."""
    from jax.experimental.pallas import tpu as pltpu

    rows, n_planes, mask_row, filtered, shards, *named = _MESH_CASES[case]
    rng = np.random.default_rng(11)
    s_pad, w = pmesh.padded_shards(shards), 1024 if named else 256

    def stack(n):
        x = rng.integers(0, 2**32, (n, s_pad, w), dtype=np.uint32)
        x[:, shards:] = 0  # the padding put_stack adds is all zero
        return x

    dims = [stack(r) for r in rows]
    filt = stack(1)[0] if filtered else None
    planes = stack(n_planes) if n_planes else None
    g, live = 16, 11
    if len(rows) == 1:  # a prune lists every row of its dimension
        g = 1 << (rows[0] - 1).bit_length()
        live = rows[0]
        idx = np.zeros((1, g), np.int32)
        idx[0, :live] = np.arange(live)
    elif named:
        idx = named_table(rng, rows, named[0], g, live)
    else:
        idx = np.stack(
            [rng.integers(0, r, g) for r in rows]).astype(np.int32)
    row_spec = NamedSharding(mesh_2x2, P("shards", "cols"))
    stack_spec = NamedSharding(mesh_2x2, P(None, "shards", "cols"))
    on_mesh = (
        tuple(jax.device_put(d, stack_spec) for d in dims),
        None if planes is None else jax.device_put(planes, stack_spec),
        None if filt is None else jax.device_put(filt, row_spec),
    )
    with pltpu.force_tpu_interpret_mode():
        if not named:
            got = pk.group_counts_mesh(
                mesh_2x2, on_mesh[0], idx, np.array([live], np.int32),
                *on_mesh[1:], mask_row,
            )
        else:
            # a buffer that holds one tile of the cut rows at each chip's
            # full width, and of the resident rows at less
            other = n_planes + filtered
            cut = sum(min(r, pk.compact_rows(n))
                      for r, n in zip(rows, named[0])) + other
            monkeypatch.setattr(pk, "_GROUP_ROWS_BYTES", cut * 8 * 4 * w // 2)
            assert pk.group_words(sum(rows) + other, w // 2) < w // 2
            monkeypatch.setattr(qgb, "_on_tpu", lambda devices: True)
            qgb.reset_stats()
            info = {}
            (got,) = qgb.group_tally(
                on_mesh[0], idx[:, :live], on_mesh[1], on_mesh[2], mask_row,
                info,
            )
            assert qgb.STATS["compacted_tallies"] == 1
            assert qgb.STATS["mesh_tallies"] == 1
            assert info["tally_words"] == w // 2
    assert got.sharding.is_equivalent_to(
        NamedSharding(mesh_2x2, P(None, None, "shards")), 3)
    got = np.asarray(got)
    want = np.asarray(qgb._counts_groups(
        tuple(jnp.asarray(d) for d in dims), idx,
        None if planes is None else jnp.asarray(planes),
        None if filt is None else jnp.asarray(filt), mask_row,
    ))
    assert want.shape == (g, 1 + n_planes, s_pad)
    assert got.shape == (g, 1 + n_planes, 2)  # the mesh's two shard rows
    rows = want.reshape(g, 1 + n_planes, 2, s_pad // 2).sum(axis=-1)
    assert (got[:live] == rows[:live]).all()
    assert not got[live:].any()  # the padding of the table is not tallied
    assert not want[:live, :, shards:].any()
    summed = qgb._read_groups([got], live)
    assert (summed == _numpy_counts(dims, idx[:, :live], planes, filt,
                                    mask_row)).all()


# (resident rows of each dimension, rows its table names, plane and filter
# rows, words a chip's row holds, the resident tile, the cut tile or 0)
_CUT_RULE = {
    # SSB Q3.2 at city grain on a chip of the 2 x 2 mesh: two 250-row
    # cities and the years, 26 planes and the filter
    "ssb_x4_q32": ((250, 250, 7), (10, 10, 6), 27, 16384, 512, 4096),
    "ssb_x4_q33": ((250, 250, 7), (2, 2, 6), 27, 16384, 512, 4096),
    "ssb_x4_q34": ((250, 250, 7), (2, 1, 1), 27, 16384, 512, 4096),
    # SSB Q3.1 on one chip: 84 rows already allow 4 096 words
    "ssb_q31": ((25, 25, 7), (5, 5, 6), 27, 32768, 4096, 0),
    # a prune names every row of its dimension
    "ssb_x4_city_prune": ((250,), (250,), 1, 16384, 1024, 0),
    "ssb_x4_year_prune": ((7,), (7,), 1, 16384, 16384, 0),
}


@pytest.mark.parametrize("case", sorted(_CUT_RULE))
def test_the_tally_is_cut_to_named_rows_where_the_tile_is_narrow(case):
    """The group tally cuts its dimensions to the rows its table names
    only where it names fewer than some dimension holds and the resident
    rows leave a tile under `GROUP_FULL_WORDS`; then the tile is sized
    from the cut rows."""
    resident, named, other, w, whole, cut = _CUT_RULE[case]
    assert pk.group_words(sum(resident) + other, w) == whole
    assert pk.compacted_words(resident, named, other, w) == cut
    assert cut == 0 or whole < pk.GROUP_FULL_WORDS <= cut


def test_mesh_selection_reads_the_operands(mesh_2x2, monkeypatch):
    """The mesh program where every operand is sharded over the active
    mesh as the stacked path places it and every device is a TPU; a
    stack on one device, or a CPU mesh, is the XLA program's."""
    stack_spec = NamedSharding(mesh_2x2, P(None, "shards", "cols"))
    dims = [jax.device_put(np.zeros((3, 4, 256), np.uint32), stack_spec)]
    filt = jax.device_put(np.zeros((4, 256), np.uint32),
                          NamedSharding(mesh_2x2, P("shards", "cols")))
    assert qgb.group_program(dims, None, filt) == qgb.GROUP_XLA_PROGRAM
    monkeypatch.setattr(qgb, "_on_tpu", lambda devices: True)
    assert qgb.group_program(dims, None, filt) == qgb.GROUP_MESH_PROGRAM
    assert qgb.group_block(dims, None, filt) == pk.group_block(1, 0)[0]
    one_device = [jnp.zeros((3, 4, 256), jnp.uint32)]
    assert qgb.group_program(one_device, None, None) != qgb.GROUP_MESH_PROGRAM
    replicated = jax.device_put(np.zeros((4, 256), np.uint32),
                                NamedSharding(mesh_2x2, P()))
    assert qgb.group_program(dims, None, replicated) == qgb.GROUP_XLA_PROGRAM


# -- a GroupBy over the dispatch cap, chunked over shards -------------------

N_SHARDS = 40  # halves of 20: above the 16 below which a chunk is walked
SETS = {"a": 6, "b": 5, "c": 3}
V_MIN, V_MAX = -500, 1000


class Data:
    def __init__(self, seed: int):
        rng = np.random.default_rng(seed)
        self.cols = np.unique(
            rng.integers(0, N_SHARDS * SHARD_WIDTH, 4000).astype(np.uint64))
        n = len(self.cols)
        self.rows = {f: rng.integers(0, r, n) for f, r in SETS.items()}
        self.has = rng.random(n) < 0.8
        self.values = rng.integers(V_MIN, V_MAX + 1, n)

    def holder(self, path) -> Holder:
        h = Holder(str(path)).open()
        idx = h.create_index("i", track_existence=True)
        for f in SETS:
            idx.create_field(f).import_bits(
                self.rows[f].astype(np.uint64), self.cols)
        keep = self.has
        idx.create_field("v", FieldOptions(type="int", min=V_MIN, max=V_MAX)) \
            .import_values(self.cols[keep], self.values[keep])
        idx.track_columns(self.cols)
        return h

    def group_by(self, fields, mask, aggregate: bool) -> list:
        keys = np.stack([self.rows[f][mask] for f in fields], axis=1)
        has, vals = self.has[mask], self.values[mask]
        out = []
        for key in sorted({tuple(k) for k in keys.tolist()}):
            m = (keys == np.array(key)).all(axis=1)
            out.append((key, int(m.sum()),
                        int(vals[m & has].sum()) if aggregate else None))
        return out


@pytest.fixture(scope="module")
def data():
    return Data(23)


@pytest.fixture
def ex(data, tmp_path):
    from pilosa_tpu.core.resultcache import RESULT_CACHE

    pmesh.set_active_mesh(None)  # an earlier test's node formed one
    DEVICE_CACHE.clear()
    budget = RESULT_CACHE.budget_bytes
    RESULT_CACHE.configure(budget_bytes=0)  # every GroupBy is executed
    h = data.holder(tmp_path / "h")
    yield Executor(h)
    h.close()
    RESULT_CACHE.configure(budget_bytes=budget)
    RESULT_CACHE.reset()
    DEVICE_CACHE.clear()


def _cap_at(monkeypatch, shards: int, mult: int) -> None:
    """A device budget whose quarter holds `shards` shards of a stack of
    `mult` rows: one of N_SHARDS is over the cap, half of them is not."""
    monkeypatch.setattr(
        DEVICE_CACHE, "budget_bytes", 4 * shards * WORDS_PER_ROW * 4 * mult)


def _value_rows(ex) -> int:
    """Rows of v's plane stack: exists, sign and the magnitude planes."""
    return ex.holder.index("i").field("v").options.bit_depth + 2


def _served(ex, pql: str) -> list:
    return [
        (tuple(m.row_id for m in g.group), g.count, g.sum)
        for g in ex.execute("i", pql)[0]
    ]


def _walked(ex, monkeypatch, pql: str) -> list:
    with monkeypatch.context() as m:
        m.setattr(exmod, "_STACKED_ENABLED", False)
        return _served(ex, pql)


def _taps(monkeypatch) -> list:
    """The facts each GroupBy dispatch tags its span with."""
    seen = []
    tag = exmod._tag_group_by

    def record(launches, info):
        seen.append(dict(info, tallies=launches))
        tag(launches, info)

    monkeypatch.setattr(exmod, "_tag_group_by", record)
    return seen


FILTER = "Union(Row(c=0), Row(c=2))"
_CHUNK_CASES = {
    # (query, fields, filtered, aggregate, rows of the stack over the cap)
    "count_only": ("GroupBy(Rows(a), Rows(b))", ("a", "b"), False, False, 6),
    "count_only_filtered": (f"GroupBy(Rows(a), Rows(b), filter={FILTER})",
                            ("a", "b"), True, False, 6),
    # the value field's planes are the stack over the cap
    "aggregate_signed": ("GroupBy(Rows(a), Rows(b), aggregate=Sum(field=v))",
                         ("a", "b"), False, True, None),
    "aggregate_signed_filtered": (
        f"GroupBy(Rows(a), Rows(b), Rows(c), filter={FILTER}, "
        "aggregate=Sum(field=v))", ("a", "b", "c"), True, True, None),
}


@pytest.mark.parametrize("case", sorted(_CHUNK_CASES))
def test_chunked_group_by_equals_the_walk_and_numpy(
    ex, data, monkeypatch, case
):
    pql, fields, filtered, aggregate, mult = _CHUNK_CASES[case]
    mask = np.ones(len(data.cols), bool)
    if filtered:
        mask = (data.rows["c"] == 0) | (data.rows["c"] == 2)
    want = data.group_by(fields, mask, aggregate)
    walked = _walked(ex, monkeypatch, pql)
    assert walked == want
    whole = _taps(monkeypatch)
    assert _served(ex, pql) == want
    assert whole[-1]["shard_chunks"] == 1
    if mult is None:
        mult = _value_rows(ex)
    _cap_at(monkeypatch, N_SHARDS * 3 // 4, mult)
    seen = _taps(monkeypatch)
    assert _served(ex, pql) == want
    assert seen[-1]["shard_chunks"] == 2
    if aggregate:
        assert seen[-1]["planes"] == mult
        assert (seen[-1]["prune_rows"] > 0) == filtered
        assert (seen[-1]["prune_ms"] > 0) == filtered


def test_limit_offset_and_previous_apply_after_the_chunks_merge(
    ex, data, monkeypatch
):
    pql = ("GroupBy(Rows(a), Rows(b), previous=[1, 2], offset=1, limit=5, "
           "aggregate=Sum(field=v))")
    want = _walked(ex, monkeypatch, pql)
    assert len(want) == 5
    _cap_at(monkeypatch, N_SHARDS * 3 // 4, _value_rows(ex))
    seen = _taps(monkeypatch)
    assert _served(ex, pql) == want
    assert seen[-1]["shard_chunks"] == 2


def test_a_chunk_below_16_shards_is_walked(ex, data, monkeypatch):
    """A cap no half of 16 or more shards fits: the per-shard walk answers,
    as before chunking."""
    pql = "GroupBy(Rows(a), Rows(b), aggregate=Sum(field=v))"
    want = data.group_by(("a", "b"), np.ones(len(data.cols), bool), True)
    _cap_at(monkeypatch, 4, 6)
    qgb.reset_stats()
    assert _served(ex, pql) == want
    assert qgb.STATS["aggregate_queries"] == 0  # the device path stayed out


def test_chunked_aggregate_runs_the_mesh_tally(
    data, tmp_path, mesh_2x2, monkeypatch
):
    """Over the 2 x 2 mesh (its devices taken for TPUs, the kernel
    interpreted) a filtered aggregate GroupBy over the cap is two chunks,
    every tally the kernel under shard_map and none XLA's, one dispatch
    named `jit__group_counts_mesh`, and the answer numpy's."""
    from jax.experimental import pallas as pl

    monkeypatch.setattr(qgb, "_on_tpu", lambda devices: True)
    monkeypatch.setattr(
        pl, "pallas_call", functools.partial(pl.pallas_call, interpret=True))
    h = data.holder(tmp_path / "h")
    try:
        ex = Executor(h)
        pql = (f"GroupBy(Rows(a), Rows(b), Rows(c), filter={FILTER}, "
               "aggregate=Sum(field=v))")
        mask = (data.rows["c"] == 0) | (data.rows["c"] == 2)
        want = data.group_by(("a", "b", "c"), mask, True)
        _cap_at(monkeypatch, N_SHARDS * 3 // 4, _value_rows(ex))
        seen = _taps(monkeypatch)
        programs = []
        run_counted = planmod.run_counted

        def named(fn, **kw):
            programs.append(kw.get("program"))
            return run_counted(fn, **kw)

        monkeypatch.setattr(planmod, "run_counted", named)
        qgb.reset_stats()
        assert _served(ex, pql) == want
        assert seen[-1]["shard_chunks"] == 2
        # per chunk: a prune of each of three dimensions, the groups
        assert qgb.STATS["mesh_tallies"] == 2 * (3 + 2)
        assert qgb.STATS["xla_tallies"] == 0
        assert qgb.STATS["kernel_tallies"] == qgb.STATS["mesh_tallies"]
        assert programs == [qgb.GROUP_MESH_PROGRAM]
    finally:
        h.close()


def test_a_served_groupby_tags_its_chunks_and_prunes(data):
    """On a served node (a CPU mesh: the XLA program) a filtered aggregate
    GroupBy's `exec.dispatch` carries `groupby.shard_chunks`,
    `groupby.prune_ms` and `groupby.prune_rows`, a count-only one its
    chunks alone, and `/debug/vars` publishes `groupby.mesh_tallies`."""
    import json
    import urllib.request

    from pilosa_tpu.core.resultcache import RESULT_CACHE
    from pilosa_tpu.testing import ClusterHarness

    def dispatch_tags(uri, pql):
        req = urllib.request.Request(
            f"{uri}/index/i/query?profile=1", data=pql.encode(), method="POST")
        with urllib.request.urlopen(req, timeout=120) as resp:
            todo = list(json.loads(resp.read())["profile"]["roots"])
        while todo:
            span = todo.pop()
            todo.extend(span.get("children", []))
            if span["name"] == "exec.dispatch" and "groupby.levels" in span["tags"]:
                return span["tags"]

    def gauges(uri):
        with urllib.request.urlopen(f"{uri}/debug/vars", timeout=60) as resp:
            return json.loads(resp.read())

    DEVICE_CACHE.clear()
    RESULT_CACHE.reset()
    with ClusterHarness(1, in_memory=True) as c:
        api, uri = c[0].api, c[0].node.uri
        before = gauges(uri)
        api.create_index("i")
        for f in SETS:
            api.create_field("i", f)
            api.import_bits("i", f, data.rows[f].tolist(), data.cols.tolist())
        api.create_field("i", "v", {"type": "int", "min": V_MIN, "max": V_MAX})
        api.import_values("i", "v", data.cols[data.has].tolist(),
                          data.values[data.has].tolist())
        tags = dispatch_tags(uri, f"GroupBy(Rows(a), Rows(b), filter={FILTER}, "
                                  "aggregate=Sum(field=v))")
        assert tags["groupby.shard_chunks"] == 1
        assert tags["groupby.prune_rows"] == SETS["a"] + SETS["b"]
        assert tags["groupby.prune_ms"] > 0
        plain = dispatch_tags(uri, f"GroupBy(Rows(a), Rows(b), filter={FILTER})")
        assert plain["groupby.shard_chunks"] == 1
        assert "groupby.prune_ms" not in plain
        after = gauges(uri)
        # a CPU mesh: no kernel, the XLA program
        assert after["groupby.mesh_tallies"] == before["groupby.mesh_tallies"]
        assert after["groupby.xla_tallies"] > before["groupby.xla_tallies"]
    pmesh.set_active_mesh(None)  # the node formed one over every device
    DEVICE_CACHE.clear()
