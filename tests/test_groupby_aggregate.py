"""`GroupBy(..., aggregate=Sum(field=))` against a plain numpy reference
written here: seeded random data at a small size, served through
`POST /index/<i>/query`, every path that can answer it (the stacked
tally as the XLA program and as the VMEM kernel in interpret mode, the
per-shard walk, a two-node cluster's merge, the result cache), and the
counts of work the tally is held to."""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.core.fragment import SHARD_WIDTH
from pilosa_tpu.exec import groupby as qgb
from pilosa_tpu.testing import ClusterHarness

N_SHARDS = 5
SETS = {"a": 6, "b": 5, "c": 3, "d": 4}
# min, max, share of the columns that hold a value
INTS = {
    "v": (-500, 1000, 0.8),  # negative values, columns without a value
    "deep": (0, (1 << 20) + 7, 1.0),  # 21 planes: deeper than a 16-plane slab
    "off": (1000, 2000, 0.9),  # base 1000: stored values are offsets
}


class Star:
    """The data, as flat arrays over its columns, and the reference."""

    def __init__(self, seed: int):
        rng = np.random.default_rng(seed)
        self.cols = np.unique(
            rng.integers(0, N_SHARDS * SHARD_WIDTH, 1500).astype(np.uint64)
        )
        n = len(self.cols)
        # a set field's row per column (one row a column keeps the
        # reference a table; -1: the column is in no row of the field)
        self.rows = {
            f: np.where(rng.random(n) < 0.9, rng.integers(0, r, n), -1)
            for f, r in SETS.items()
        }
        self.has = {f: rng.random(n) < s for f, (_, _, s) in INTS.items()}
        self.values = {
            f: rng.integers(lo, hi + 1, n) for f, (lo, hi, _) in INTS.items()
        }

    def load(self, api, index: str = "star") -> None:
        api.create_index(index)
        for f in SETS:
            api.create_field(index, f)
            keep = self.rows[f] >= 0
            api.import_bits(index, f, self.rows[f][keep].tolist(),
                            self.cols[keep].tolist())
        for f, (lo, hi, _) in INTS.items():
            api.create_field(index, f, {"type": "int", "min": lo, "max": hi})
            keep = self.has[f]
            api.import_values(index, f, self.cols[keep].tolist(),
                              self.values[f][keep].tolist())

    def load_holder(self, holder, index: str = "star"):
        """The same data into a bare holder (no server, no mesh): what the
        kernel tests run an `Executor` over."""
        from pilosa_tpu.core.field import FieldOptions

        idx = holder.create_index(index, track_existence=True)
        for f in SETS:
            keep = self.rows[f] >= 0
            idx.create_field(f).import_bits(
                self.rows[f][keep].astype(np.uint64), self.cols[keep])
        for f, (lo, hi, _) in INTS.items():
            keep = self.has[f]
            idx.create_field(f, FieldOptions(type="int", min=lo, max=hi)) \
                .import_values(self.cols[keep], self.values[f][keep])
        idx.track_columns(self.cols)
        return idx

    def group_by(self, fields, value, mask=None) -> list:
        """[(row ids, count, sum)] of every group that holds a column of
        `mask`, in row-id order: `sum` over the group's columns that hold
        a value of `value`."""
        out = []
        sel = np.ones(len(self.cols), bool) if mask is None else mask
        for f in fields:
            sel = sel & (self.rows[f] >= 0)
        keys = np.stack([self.rows[f][sel] for f in fields], axis=1)
        has, vals = self.has[value][sel], self.values[value][sel]
        for key in sorted({tuple(k) for k in keys.tolist()}):
            m = (keys == np.array(key)).all(axis=1)
            out.append((key, int(m.sum()), int(vals[m & has].sum())))
        return out

    def row(self, field: str, rid: int) -> np.ndarray:
        return self.rows[field] == rid


def served(uri: str, pql: str, index: str = "star"):
    req = urllib.request.Request(
        f"{uri}/index/{index}/query", data=pql.encode(), method="POST"
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())["results"][0]


def as_tuples(result) -> list:
    return [
        (tuple(m["rowID"] for m in g["group"]), g["count"], g["sum"])
        for g in result
    ]


@pytest.fixture(scope="module")
def star():
    return Star(37)


@pytest.fixture
def node(star):
    from pilosa_tpu.core.devcache import DEVICE_CACHE
    from pilosa_tpu.core.resultcache import RESULT_CACHE

    DEVICE_CACHE.clear()
    RESULT_CACHE.reset()
    with ClusterHarness(1, in_memory=True) as c:
        star.load(c[0].api)
        yield c[0]
    DEVICE_CACHE.clear()


def _rows(fields) -> str:
    return ", ".join(f"Rows({f})" for f in fields)


@pytest.mark.parametrize("fields", [
    ("a",), ("a", "b"), ("a", "b", "c"), ("a", "b", "c", "d"),
])
@pytest.mark.parametrize("filtered", [False, True])
def test_served_answer_equals_the_reference(node, star, fields, filtered):
    uri = node.node.uri
    filt, mask = "", None
    if filtered:
        filt = ", filter=Union(Row(d=1), Row(d=2))"
        mask = star.row("d", 1) | star.row("d", 2)
    for value in INTS:
        got = served(
            uri, f"GroupBy({_rows(fields)}{filt}, aggregate=Sum(field={value}))"
        )
        want = star.group_by(fields, value, mask)
        assert want and as_tuples(got) == want, (fields, value)
    # without the argument the answer has no `sum` key at all
    plain = served(uri, f"GroupBy({_rows(fields)}{filt})")
    assert [set(g) for g in plain] == [{"group", "count"}] * len(want)
    assert [g["count"] for g in plain] == [c for _, c, _ in want]


def test_negative_values_and_columns_without_a_value(node, star):
    got = as_tuples(served(
        node.node.uri, "GroupBy(Rows(a), aggregate=Sum(field=v))"))
    want = star.group_by(("a",), "v")
    assert got == want
    assert any(s < 0 for _, _, s in want) or min(s for _, _, s in want) < \
        max(s for _, _, s in want)
    # `count` holds the columns without a value too; `sum` does not see them
    group0 = star.row("a", 0)
    assert got[0][1] == int(group0.sum())
    assert int((group0 & star.has["v"]).sum()) < got[0][1]


@pytest.mark.parametrize("args", [
    "limit=4", "offset=3, limit=5", "previous=[2, 1]", "offset=2",
    "previous=[1, 3], limit=2",
])
def test_limit_offset_and_previous_act_on_groups(node, star, args):
    """As without the argument: the same groups in the same order, each
    with its sum."""
    uri = node.node.uri
    whole = star.group_by(("a", "b"), "v")
    got = as_tuples(served(
        uri, f"GroupBy(Rows(a), Rows(b), {args}, aggregate=Sum(field=v))"))
    plain = served(uri, f"GroupBy(Rows(a), Rows(b), {args})")
    assert [(k, c) for k, c, _ in got] == [
        (tuple(m["rowID"] for m in g["group"]), g["count"]) for g in plain]
    assert got and set(got) <= set(whole)
    want = {
        "limit=4": whole[:4], "offset=3, limit=5": whole[3:8],
        "previous=[2, 1]": [g for g in whole if g[0] > (2, 1)],
        "offset=2": whole[2:],
        "previous=[1, 3], limit=2": [g for g in whole if g[0] > (1, 3)][:2],
    }[args]
    assert got == want


def test_a_childs_previous_acts_as_without_the_argument(node, star):
    uri = node.node.uri
    q = "GroupBy(Rows(a, previous=1), Rows(b, previous=2), limit=3{})"
    got = as_tuples(served(uri, q.format(", aggregate=Sum(field=off)")))
    plain = served(uri, q.format(""))
    assert [(k, c) for k, c, _ in got] == [
        (tuple(m["rowID"] for m in g["group"]), g["count"]) for g in plain]
    assert len(got) == 3 and set(got) <= set(star.group_by(("a", "b"), "off"))


@pytest.fixture
def bare(star, tmp_path):
    """An `Executor` over a bare holder whose five shards are staged as
    extents of 2 + 2 + 1 on one device, as 573 are as 256 + 256 + 61 on a
    chip (a served node on this CPU forms a mesh, which is the XLA
    program's)."""
    from pilosa_tpu.core.devcache import DEVICE_CACHE
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.core.resultcache import RESULT_CACHE
    from pilosa_tpu.exec.executor import Executor
    from pilosa_tpu.hbm import residency as hbm_res

    from pilosa_tpu.parallel import mesh as pmesh

    old = hbm_res.extent_rows()
    pmesh.set_active_mesh(None)  # an earlier test's node formed one
    DEVICE_CACHE.clear()
    RESULT_CACHE.reset()
    hbm_res.configure(extent_rows=2)
    holder = Holder(str(tmp_path / "holder")).open()
    star.load_holder(holder)
    yield Executor(holder)
    holder.close()
    hbm_res.configure(extent_rows=old)
    DEVICE_CACHE.clear()


@pytest.fixture
def interpreted(monkeypatch):
    """The group tally's `pallas_call` run by Pallas's own interpreter (the
    kernel as plain XLA on this CPU; the TPU interpret mode calls back into
    Python for every load, minutes at this row width)."""
    import functools

    from jax.experimental import pallas as pl

    monkeypatch.setattr(
        pl, "pallas_call", functools.partial(pl.pallas_call, interpret=True))


def executed(ex, pql: str) -> list:
    return as_tuples([g.to_json() for g in ex.execute("star", pql)[0]])


Q31 = ("GroupBy(Rows(a), Rows(b), Rows(c), filter=Intersect(Row(d=1), "
       "Union(Row(c=0), Row(c=1))), aggregate=Sum(field=deep))")


def _q31_want(star):
    mask = star.row("d", 1) & (star.row("c", 0) | star.row("c", 1))
    return star.group_by(("a", "b", "c"), "deep", mask)


def test_kernel_tallies_live_groups_by_planes_in_place(
    bare, star, monkeypatch, interpreted
):
    """(b), (c), (d) of the tally on a Q3.1-shaped query over three extents
    of shards: work = candidate groups x planes and not the cross x planes,
    nothing concatenated, launches and reads O(levels)."""
    want = _q31_want(star)
    monkeypatch.setattr(qgb, "_kernel_covers", lambda *stacks: True)
    qgb.reset_stats()
    got = executed(bare, Q31)
    assert got == want
    planes = 2 + 21
    # the filter leaves every row of a and b, and two of c's three: the
    # candidates are 6 x 5 x 2, each with every plane, and no more
    assert len(want) <= 60 < 6 * 5 * 3
    assert qgb.STATS == {
        "aggregate_queries": 1, "plane_tallies": 60 * planes,
        # one launch a dimension against the filter, one for the groups
        "kernel_tallies": 3 + 1, "inplace_tallies": 3 + 1, "evals": 3 + 1,
        "xla_tallies": 0, "assembled_stacks": 0, "assembled_bytes": 0,
        "mesh_tallies": 0, "compacted_tallies": 0,
    }
    # a signed field costs one more launch, over the same groups
    qgb.reset_stats()
    got = executed(bare, Q31.replace("deep", "v"))
    assert [g[:2] for g in got] == [g[:2] for g in want]
    assert qgb.STATS["kernel_tallies"] == 3 + 2
    assert qgb.STATS["assembled_stacks"] == 0


def test_xla_program_over_extents_equals_the_kernel(bare, star):
    """The XLA program (every backend but one TPU) needs whole stacks and
    says so in `assembled_stacks`; its answer is the kernel's."""
    qgb.reset_stats()
    assert executed(bare, Q31) == _q31_want(star)
    assert qgb.STATS["xla_tallies"] >= 4 and qgb.STATS["kernel_tallies"] == 0
    assert qgb.STATS["assembled_stacks"] > 0
    assert qgb.STATS["assembled_bytes"] > 0


def test_more_groups_than_one_launch_lists(node, star, monkeypatch):
    """Where the candidates outnumber a launch's table the count-only
    tally finds the live groups first and they alone meet the planes."""
    from pilosa_tpu.ops import pallas_kernels

    monkeypatch.setattr(pallas_kernels, "GROUP_MAX_GROUPS", 8)
    qgb.reset_stats()
    got = as_tuples(served(
        node.node.uri, "GroupBy(Rows(a), Rows(b), aggregate=Sum(field=off))"))
    want = star.group_by(("a", "b"), "off")
    assert got == want and len(want) > 8
    assert qgb.STATS["plane_tallies"] == len(want) * (2 + 10)


def test_per_shard_fallback_carries_the_sum(node, star, monkeypatch):
    import pilosa_tpu.exec.executor as exmod

    monkeypatch.setattr(exmod, "_STACKED_ENABLED", False)
    qgb.reset_stats()
    for value in INTS:
        got = as_tuples(served(
            node.node.uri,
            f"GroupBy(Rows(a), Rows(c), filter=Row(d=2), "
            f"aggregate=Sum(field={value}))"))
        assert got == star.group_by(("a", "c"), value, star.row("d", 2))
    assert qgb.STATS["aggregate_queries"] == 0  # the device path stayed out


def test_two_node_cluster_merges_counts_and_sums(star):
    from pilosa_tpu.core.devcache import DEVICE_CACHE

    DEVICE_CACHE.clear()
    with ClusterHarness(2, in_memory=True) as c:
        star.load(c[0].api)
        owners = {
            n.node.id for n in c.nodes
            if any(n.holder.index("star").field("a").view("standard")
                   .fragment_if_exists(s) is not None for s in range(N_SHARDS))
        }
        assert len(owners) == 2  # both nodes hold shards of the index
        for uri in (c[0].node.uri, c[1].node.uri):
            got = as_tuples(served(
                uri, "GroupBy(Rows(a), Rows(b), filter=Row(d=1), limit=7, "
                "aggregate=Sum(field=v))"))
            assert got == star.group_by(("a", "b"), "v", star.row("d", 1))[:7]
    DEVICE_CACHE.clear()


def test_result_cache_keeps_the_two_forms_apart(node, star):
    """Aggregate, count-only, aggregate of the same GroupBy with the cache
    on: three right answers. The count-only form is kept and served; the
    aggregate is executed every time and sees a new value at once."""
    from pilosa_tpu.core.resultcache import RESULT_CACHE

    old_budget = RESULT_CACHE.budget_bytes
    RESULT_CACHE.configure(budget_bytes=8 << 20)
    try:
        uri = node.node.uri
        want = star.group_by(("a", "b"), "v")
        agg = "GroupBy(Rows(a), Rows(b), aggregate=Sum(field=v))"
        first = served(uri, agg)
        plain = served(uri, "GroupBy(Rows(a), Rows(b))")
        hits = RESULT_CACHE.stats_snapshot()["hits"]
        again = served(uri, agg)
        assert as_tuples(first) == want and again == first
        assert [set(g) for g in plain] == [{"group", "count"}] * len(want)
        assert [g["count"] for g in plain] == [c for _, c, _ in want]
        assert served(uri, "GroupBy(Rows(a), Rows(b))") == plain
        assert RESULT_CACHE.stats_snapshot()["hits"] == hits + 1  # the plain one
        qgb.reset_stats()
        n = next(i for i in range(len(star.cols)) if star.has["v"][i]
                 and star.rows["a"][i] >= 0 and star.rows["b"][i] >= 0)
        served(uri, f"Set({int(star.cols[n])}, v={int(star.values['v'][n]) + 5})")
        after = {k: s for k, _, s in as_tuples(served(uri, agg))}
        key = (int(star.rows["a"][n]), int(star.rows["b"][n]))
        assert after[key] == {k: s for k, _, s in want}[key] + 5
        assert qgb.STATS["aggregate_queries"] == 1
    finally:
        RESULT_CACHE.configure(budget_bytes=old_budget)
        RESULT_CACHE.reset()


@pytest.mark.parametrize("pql, message", [
    ("GroupBy(Rows(a), aggregate=Count(Row(b=1)))", "aggregate 'Count' is not"),
    ("GroupBy(Rows(a), aggregate=Sum())", "no field="),
    ("GroupBy(Rows(a), aggregate=Sum(Row(b=1), field=v))", "a child query"),
    ("GroupBy(Rows(a), aggregate=Sum(field=v, limit=2))", "argument 'limit'"),
    ("GroupBy(Rows(a), aggregate=Sum(field=b))", "field b is not an int field"),
    ("GroupBy(Rows(a), aggregate=Sum(field=nope))", "nope"),  # 404: no field
    ("GroupBy(Rows(a), aggregate=7)", "aggregate must be a call"),
    ("GroupBy(Rows(a), having=3)", "does not take the argument 'having'"),
])
def test_a_malformed_aggregate_is_refused_by_name(node, pql, message):
    with pytest.raises(urllib.error.HTTPError) as e:
        served(node.node.uri, pql)
    assert e.value.code == (404 if "nope" in pql else 400)
    assert message in e.value.read().decode()


# the oracle's cases: (rows of each dimension, planes, mask row, filtered,
# live groups; "gb" the kernel's block for these dimensions and planes)
_ORACLE_CASES = {
    # PR 37's three cases
    "p11_mask0": ((5, 7, 3), 11, 0, True, 13),
    "p40_mask1": ((5, 7, 3), 40, 1, True, 16),
    "count_only": ((5, 7, 3), 0, None, True, 9),
    # a prune launch: one dimension against the filter, a block of groups
    **{f"prune_{n}": ((7,), 0, None, True, n)
       for n in ("1", "gb-1", "gb", "gb+1", "full")},
    "count_only_3dims_gb-1": ((5, 7, 3), 0, None, False, "gb-1"),
    "count_only_3dims_gb+1": ((5, 7, 3), 0, None, False, "gb+1"),
    **{f"p1_{n}": ((7,), 1, 0, False, n)
       for n in ("1", "gb-1", "gb", "gb+1", "full")},
    # the SSB cell's tallies: cost (19 planes) and revenue (26)
    "p19_gb+1": ((7, 5), 19, 0, True, "gb+1"),
    "p19_full": ((7, 5), 19, None, True, "full"),
    "p26_1": ((5, 7, 3), 26, 0, True, "1"),
    "p26_gb+1": ((5, 7, 3), 26, 1, False, "gb+1"),
    # deeper than one slab of partials
    "p34_gb": ((5,), 34, 0, True, "gb"),
    "p34_full": ((5,), 34, None, False, "full"),
    # through `group_tally`, with a tile buffer that holds every resident
    # row at less than the full width: each extent's wide dimensions cut to
    # the rows the table names (the last named row repeated up to 8 or 16
    # rows), repeated rows, dimensions no cut would shrink read whole
    **{"cut_" + "_".join(map(str, n)): ((40, 5, 3), 6, 0, True, 11, n)
       for n in ((3, 5, 2), (1, 1, 1))},
    "cut_16_sign": ((40, 12), 26, 1, False, 11, (9, 4)),
}


@pytest.mark.parametrize("case", sorted(_ORACLE_CASES))
def test_group_counts_kernel_equals_its_xla_oracle(
    interpreted, monkeypatch, case
):
    """The VMEM group tally (interpreted here) against `_counts_groups` on
    random words: parts of unequal spans read in place, a table longer
    than its live groups, ragged and whole blocks of groups, a mask row,
    more planes than one slab of partials, several rounds of the word
    loop, dimensions cut to the rows the table names. Groups past `live`
    read 0."""
    import jax.numpy as jnp

    from pilosa_tpu.ops import pallas_kernels as pk

    rows, n_planes, mask_row, filtered, live, *named = _ORACLE_CASES[case]
    gb = pk.group_block(len(rows), n_planes)[0]
    g = max(16, 1 << gb.bit_length())  # the table: a power of two > gb
    live = {"1": 1, "gb-1": max(1, gb - 1), "gb": gb, "gb+1": gb + 1,
            "full": g}.get(live, live)
    rng = np.random.default_rng(5)
    spans, w = (8, 8, 5), 2048

    def stack(n):
        return rng.integers(0, 2**32, (n, sum(spans), w), dtype=np.uint32)

    def parts(x):
        cuts = np.cumsum(spans)[:-1]
        return tuple(jnp.asarray(p) for p in np.split(x, cuts, axis=1))

    dims = [stack(r) for r in rows]
    filt = stack(1)[0] if filtered else None
    planes = stack(n_planes) if n_planes else None
    idx = np.stack([rng.integers(0, len(d), g) for d in dims]).astype(np.int32)
    if not named:
        got = np.asarray(pk.group_counts(
            [parts(d) for d in dims], idx, np.array([live], np.int32),
            None if planes is None else parts(planes),
            None if filt is None else jnp.asarray(filt), mask_row,
        ))
    else:
        from test_groupby_mesh import named_table

        idx = named_table(rng, rows, named[0], g, live)
        other = n_planes + filtered
        cut = sum(min(r, pk.compact_rows(n))
                  for r, n in zip(rows, named[0])) + other
        monkeypatch.setattr(pk, "_GROUP_ROWS_BYTES", cut * 8 * 4 * w)
        assert pk.group_words(sum(rows) + other, w) < w
        monkeypatch.setattr(qgb, "_kernel_covers", lambda *stacks: True)
        qgb.reset_stats()
        info = {}
        (got,) = qgb.group_tally(
            [parts(d) for d in dims], idx[:, :live],
            None if planes is None else parts(planes),
            None if filt is None else jnp.asarray(filt), mask_row, info,
        )
        got = np.asarray(got)
        assert qgb.STATS["compacted_tallies"] == 1
        assert qgb.STATS["inplace_tallies"] == 1
        assert info["tally_words"] == w
    want = np.asarray(qgb._counts_groups(
        tuple(jnp.asarray(d) for d in dims), idx,
        None if planes is None else jnp.asarray(planes),
        None if filt is None else jnp.asarray(filt), mask_row,
    ))
    assert got.shape == want.shape == (g, 1 + n_planes, sum(spans))
    assert (got[:live] == want[:live]).all()
    assert not got[live:].any()  # the padding is not tallied


def _groupby_dispatch_tags(uri: str, pql: str) -> list:
    """The tags of every GroupBy `exec.dispatch` span of one served query."""
    req = urllib.request.Request(
        f"{uri}/index/star/query?profile=1", data=pql.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=120) as resp:
        profile = json.loads(resp.read())["profile"]
    found, todo = [], list(profile["roots"])
    while todo:
        span = todo.pop()
        todo.extend(span.get("children", []))
        if span["name"] == "exec.dispatch" and "groupby.levels" in span["tags"]:
            found.append(span["tags"])
    return found


@pytest.mark.parametrize("aggregate", [True, False])
def test_an_aggregate_groupbys_dispatch_tags_its_group_block(node, aggregate):
    """`groupby.group_block` (groups tallied per loaded plane tile; 1 for
    the XLA program a CPU node runs) and `groupby.tally_words` (the
    kernel's tile; 0 for the XLA program) ride on an aggregate GroupBy's
    `exec.dispatch` and on no count-only one."""
    pql = "GroupBy(Rows(a), Rows(b), filter=Row(d=1)"
    pql += ", aggregate=Sum(field=v))" if aggregate else ")"
    (tags,) = _groupby_dispatch_tags(node.node.uri, pql)
    if aggregate:
        assert tags["groupby.group_block"] >= 1
        assert tags["groupby.planes"] > 0
        assert tags["groupby.tally_words"] == 0
    else:
        assert tags.get("groupby.group_block", 0) == 0
        assert "groupby.tally_words" not in tags


def test_group_block_is_the_kernels_block_where_it_covers(monkeypatch):
    import jax.numpy as jnp

    from pilosa_tpu.ops import pallas_kernels as pk

    dims = [jnp.zeros((7, 8, 256), jnp.uint32)]
    planes = jnp.zeros((2, 8, 256), jnp.uint32)
    assert qgb.group_block(dims, planes) == 1  # the XLA program on a CPU
    monkeypatch.setattr(qgb, "_kernel_covers", lambda *stacks: True)
    for p in (None, planes):
        want = pk.group_block(1, 0 if p is None else 2)[0]
        assert qgb.group_block(dims, p) == want > 1
