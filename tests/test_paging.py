"""The paged path (ISSUE 34): an index with more rows than its device
budget holds, served over HTTP and held to the benchmark's numpy
reference, and the staging-buffer build of a stack's slice held bit for
bit to the build it replaced (`np.stack` of `Fragment.row_words`)."""

import copy
import json
import os
import sys

import numpy as np
import pytest

from pilosa_tpu.core.devcache import DEVICE_CACHE
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.hbm import residency as hbm_res
from pilosa_tpu.parallel import mesh as pmesh
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_ROW

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from run import counters  # noqa: E402
from lib.data import Data, Http, create_schema, load  # noqa: E402
from lib.reference import Reference  # noqa: E402
from lib.traffic import Mix  # noqa: E402

ROWS, SHARDS, BUDGET_ROWS = 16, 3, 5
ROW_BYTES = SHARDS * WORDS_PER_ROW * 4


@pytest.fixture
def paging_env():
    """One device, no mesh, clean caches and pool; budget, extent rows
    and mesh restored."""
    from pilosa_tpu.core.resultcache import RESULT_CACHE

    old_mesh = pmesh.active_mesh()
    pmesh.set_active_mesh(None)
    old_budget = DEVICE_CACHE.budget_bytes
    old_rows = hbm_res.extent_rows()
    DEVICE_CACHE.clear()
    RESULT_CACHE.reset()
    hbm_res.reset_stats()
    hbm_res.STAGING.clear()
    yield
    hbm_res.configure(extent_rows=old_rows)
    DEVICE_CACHE.budget_bytes = old_budget
    DEVICE_CACHE.clear()
    hbm_res.reset_stats()
    hbm_res.STAGING.clear()
    pmesh.set_active_mesh(old_mesh)


def paged_config():
    with open(os.path.join(
            ROOT, "benchmarks", "configs", "segment-10b-paged.json")) as f:
        config = copy.deepcopy(json.load(f))
    config["shards"] = SHARDS
    config["fields"][0]["rows"] = ROWS
    return config


def test_paged_index_answers_exactly_and_reads_its_writes(paging_env):
    """16 rows behind a budget of 5: 200 seeded Counts of the cell's three
    templates equal the reference's, paging shows in the two counters the
    benchmark reads, and an acknowledged `Set` and an acknowledged
    `/import` on a row that is not resident are in the next `Count`."""
    from pilosa_tpu.testing import ClusterHarness

    config = paged_config()
    with open(os.path.join(
            ROOT, "benchmarks", "traffic", "count-zipf.json")) as f:
        mix_spec = json.load(f)
    with ClusterHarness(1, in_memory=True) as c:
        uri = c[0].node.uri
        pmesh.set_active_mesh(None)  # the node formed one over the 8 CPUs
        DEVICE_CACHE.clear()
        DEVICE_CACHE.budget_bytes = BUDGET_ROWS * ROW_BYTES
        http_ = Http(uri)
        info = http_.call("GET", "/info")
        seed = 2**31 + 34
        data = Data(config, seed, info["shardWidth"])
        ref = Reference(data)
        create_schema(http_, config)
        load(uri, data)
        before = counters(http_)
        qpath = f"/index/{data.index}/query"
        mix = Mix(mix_spec, data.n_rows, seed)
        stream = mix.stream(0)
        seen = set()
        for _ in range(200):
            template, text = next(stream)
            seen.add(template)
            got = http_.call("POST", qpath, text)["results"][0]
            assert got == ref.answer(text), text
        assert seen == {"intersect", "union3", "difference"}
        after = counters(http_)
        assert after["hbm.restage_bytes"] - before.get("hbm.restage_bytes", 0) \
            > ROWS * ROW_BYTES  # more than one staging of every row
        assert after["devcache.evictions"] > before.get("devcache.evictions", 0)
        assert DEVICE_CACHE.bytes_used <= BUDGET_ROWS * ROW_BYTES
        assert hbm_res.STAGING.reused > 0

        def push_out(row):
            """Name BUDGET_ROWS + 1 other rows: `row` cannot be resident."""
            others = [r for r in range(ROWS) if r != row][: BUDGET_ROWS + 1]
            for r in others:
                http_.call("POST", qpath, f"Count(Row(seg={r}))")

        field = config["guarantees"]["read_your_writes"]["field"]
        set_row, import_row = 7, 11
        col = int(data.unused_columns([0])[0])
        push_out(set_row)
        out = http_.call("POST", qpath, f"Set({col}, {field}={set_row})")
        assert out["results"] == [True]
        ref.add_columns(field, set_row, [col])
        cols = data.unused_columns([1])[:64].tolist()
        push_out(import_row)
        http_.call("POST", f"/index/{data.index}/field/{field}/import",
                   {"rows": [import_row] * len(cols), "cols": cols})
        ref.add_columns(field, import_row, cols)
        for rid in (set_row, import_row):
            push_out(rid)
            staged = counters(http_)["hbm.restage_bytes"]
            text = f"Count(Row({field}={rid}))"
            assert http_.call("POST", qpath, text)["results"][0] \
                == ref.answer(text)
            # it was built for this Count: the row was not resident
            assert counters(http_)["hbm.restage_bytes"] >= staged + ROW_BYTES
        http_.close()


# ---------------------------------------------------------------------------
# the build into a staging buffer against the build it replaced
# ---------------------------------------------------------------------------

N_SHARDS = 7


def old_row_slice(frags, row_id):
    return np.stack([
        f.row_words(row_id) if f is not None
        else np.zeros(WORDS_PER_ROW, np.uint32)
        for f in frags
    ])


def old_plane_slice(frags, row_ids):
    if not row_ids:
        return np.zeros((0, len(frags), WORDS_PER_ROW), np.uint32)
    return np.stack([old_row_slice(frags, r) for r in row_ids])


@pytest.fixture
def view():
    """Rows 0-3 over 7 shards: shard 2 has no fragment at all, row 1 is
    absent from shards 0 and 4, row 2 is dense everywhere, row 3 sparse
    (a few positions: the array representation)."""
    h = Holder().open()
    f = h.create_index("pg").create_field("f", FieldOptions())
    rng = np.random.default_rng(34)
    for s in range(N_SHARDS):
        if s == 2:
            continue
        for r in (0, 2):
            f.import_row_words(
                r, s, rng.integers(0, 2**32, WORDS_PER_ROW).astype(np.uint32))
        if s not in (0, 4):
            f.import_row_words(
                1, s, rng.integers(0, 2**32, WORDS_PER_ROW).astype(np.uint32))
        for p in rng.integers(0, SHARD_WIDTH, 5):
            f.set_bit(3, s * SHARD_WIDTH + int(p))
    v = f.view("standard")
    v.sync_pending()
    hbm_res.STAGING.clear()
    yield v
    hbm_res.STAGING.clear()


BUILD_CASES = {
    # name: (row ids (one int = a row stack), lo, hi)
    "row_all_present_but_one_fragment": (0, 0, N_SHARDS),
    "row_absent_in_two_shards": (1, 0, N_SHARDS),
    "row_absent_everywhere": (9, 0, N_SHARDS),
    "row_sparse_positions": (3, 0, N_SHARDS),
    "row_first_extent": (2, 0, 4),
    "row_partial_last_extent": (2, 4, N_SHARDS),
    "row_only_the_absent_fragment": (0, 2, 3),
    "planes": ((0, 1, 2, 3), 0, N_SHARDS),
    "planes_partial_last_extent": ((3, 9, 1), 4, N_SHARDS),
    "planes_none": ((), 0, N_SHARDS),
}


def build_both(view, rows, lo, hi):
    frags = view._frags_for(tuple(range(N_SHARDS)))[lo:hi]
    if isinstance(rows, tuple):
        return (hbm_res.build_plane_slice(frags, rows),
                old_plane_slice(frags, rows))
    return hbm_res.build_row_slice(frags, rows), old_row_slice(frags, rows)


@pytest.mark.parametrize("case", sorted(BUILD_CASES))
def test_pooled_build_equals_the_stacked_build(view, case):
    new, old = build_both(view, *BUILD_CASES[case])
    assert new.dtype == old.dtype == np.uint32 and new.shape == old.shape
    assert np.array_equal(new, old)


@pytest.mark.parametrize("case", sorted(BUILD_CASES))
def test_a_reused_buffer_holds_only_the_new_stack(view, case):
    """The buffer comes back from the pool full of another stack's words
    (all ones here) and the build must leave none of them."""
    rows, lo, hi = BUILD_CASES[case]
    first, _ = build_both(view, rows, lo, hi)
    first.fill(0xFFFFFFFF)
    hbm_res.STAGING.give(first)
    new, old = build_both(view, rows, lo, hi)
    if new.size:
        assert new is first and hbm_res.STAGING.reused == 1
    assert np.array_equal(new, old)


def test_the_pool_lends_a_buffer_again_only_when_its_upload_is_done(view):
    """`give(buf, arr)`: not while `arr` aliases the buffer (the CPU
    backend adopts an aligned buffer), not a buffer `take` never handed
    out, and within `keep_bytes`."""
    import jax

    pool = hbm_res.StagingPool(keep_bytes=3 * WORDS_PER_ROW * 4)
    shape = (2, WORDS_PER_ROW)
    buf = pool.take(shape)
    buf.fill(7)
    arr = jax.device_put(buf)
    arr.block_until_ready()
    pool.give(buf, arr)
    again = pool.take(shape)
    aliased = arr.unsafe_buffer_pointer() == buf.ctypes.data
    assert (again is buf) != aliased
    again.fill(9)
    assert (np.asarray(arr) == 7).all()  # either way the array is intact
    # a stranger's array is ignored
    mine = np.zeros(shape, np.uint32)
    pool.give(mine)
    assert pool.take(shape) is not mine
    # twice the same buffer: once
    b = pool.take(shape)
    pool.give(b)
    pool.give(b)
    assert pool.take(shape) is b and pool.take(shape) is not b
    # over keep_bytes: dropped
    big = pool.take((4, WORDS_PER_ROW))
    pool.give(big)
    assert pool.take((4, WORDS_PER_ROW)) is not big


@pytest.mark.parametrize("extent_rows", [0, 4])
def test_staged_stack_equals_the_host_build(view, paging_env, extent_rows):
    """Through residency and the device, monolithic and in extents (the
    last one partial), twice, so that the second staging runs on pooled
    buffers: the device words are the host build's."""
    hbm_res.configure(extent_rows=extent_rows)
    DEVICE_CACHE.budget_bytes = 1 << 30
    shards = tuple(range(N_SHARDS))
    frags = view._frags_for(shards)
    for _ in range(2):
        DEVICE_CACHE.clear()
        for r in (0, 1, 2, 3):
            got = np.asarray(view.row_stack(r, shards))
            assert np.array_equal(got, old_row_slice(frags, r)), r
        got = np.asarray(view.plane_stack((0, 1, 2), shards))
        assert np.array_equal(got, old_plane_slice(frags, (0, 1, 2)))
