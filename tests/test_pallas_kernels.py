"""The GroupBy cross tally kernel vs its XLA reference (differential,
interpreted).

Mirrors the reference's differential testing discipline (roaring vs naive
model, roaring/fuzzer.go): `ops/pallas_kernels.cross_counts` must agree
bit-for-bit with `exec/groupby._counts_cross` and with numpy.
"""

import jax
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

import pilosa_tpu.ops.pallas_kernels as pk


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def interpret_mode():
    """The kernels compile for the TPU only; this CPU suite asks for the
    Pallas TPU interpreter explicitly around every test."""
    with pltpu.force_tpu_interpret_mode():
        yield


def test_no_silent_interpreter(rng):
    """Outside an explicit interpret request a backend that cannot
    compile the kernel raises — it never quietly interprets."""
    acc = rand_words(rng, 1, 8, _CROSS_W)
    planes = rand_words(rng, 2, 8, _CROSS_W)
    with pltpu.force_tpu_interpret_mode(None):
        with pytest.raises(ValueError, match="interpret"):
            pk.cross_counts(acc, planes, shard_major=False)


def rand_words(rng, *shape):
    return rng.integers(0, 2**32, size=shape, dtype=np.uint32)


# -- GroupBy cross tally (pk.cross_counts vs exec/groupby._counts_cross) -----

_CROSS_W = 256  # two lane tiles: the word loop runs more than once


def _np_cross(acc, planes, mid=None, filt=None):
    bc = lambda x: np.bitwise_count(x).astype(np.uint64).sum(-1)  # noqa: E731
    a = acc if filt is None else acc & filt[None]
    if mid is None:
        return bc(a[:, None] & planes[None])
    return bc(a[:, None, None] & mid[None, :, None] & planes[None, None])


def _xla_cross(acc, planes, mid=None, filt=None):
    """The differential oracle: the XLA program over prefixes written out."""
    from pilosa_tpu.exec import groupby as gb

    a = acc if filt is None else acc & filt[None]
    if mid is None:
        return np.asarray(gb._counts_cross(a, planes))
    out = gb._counts_cross(gb._cross_expand(a, mid), planes)
    return np.asarray(out).reshape(len(acc), len(mid), len(planes), -1)


def _pow2_padded(rng, live, s):
    """A chunk as the descent selects it: `live` rows, index-padded to the
    next power of two with copies of row 0 (gb._pad_pow2)."""
    from pilosa_tpu.exec import groupby as gb

    stack = rand_words(rng, live + 2, s, _CROSS_W)
    idx = gb._pad_pow2(np.arange(1, live + 1))
    assert len(idx) > live
    return stack[idx]


def _extreme_rows(rng, n, s):
    rows = rand_words(rng, n, s, _CROSS_W)
    rows[0] = 0
    rows[-1] = 0xFFFFFFFF
    return rows


_CROSS_CASES = {
    # name: (acc, planes, mid, filt) builders over (rng)
    "topn_g1_r2": lambda r: (rand_words(r, 1, 5, _CROSS_W),
                             rand_words(r, 2, 5, _CROSS_W), None, None),
    "g1_filtered": lambda r: (rand_words(r, 1, 3, _CROSS_W),
                              rand_words(r, 4, 3, _CROSS_W), None,
                              rand_words(r, 3, _CROSS_W)),
    "pow2_padded": lambda r: (_pow2_padded(r, 3, 4), _pow2_padded(r, 5, 4),
                              None, None),
    "shards_11": lambda r: (rand_words(r, 3, 11, _CROSS_W),
                            rand_words(r, 5, 11, _CROSS_W), None, None),
    "shards_11_filtered": lambda r: (rand_words(r, 3, 11, _CROSS_W),
                                     rand_words(r, 5, 11, _CROSS_W), None,
                                     rand_words(r, 11, _CROSS_W)),
    "zero_and_one_rows": lambda r: (_extreme_rows(r, 3, 9),
                                    _extreme_rows(r, 4, 9), None, None),
    "row_tiles_ragged": lambda r: (rand_words(r, 9, 3, 128),
                                   rand_words(r, 17, 3, 128), None, None),
    "fused": lambda r: (rand_words(r, 2, 9, _CROSS_W),
                        rand_words(r, 5, 9, _CROSS_W),
                        rand_words(r, 3, 9, _CROSS_W), None),
    "fused_filtered": lambda r: (rand_words(r, 2, 9, _CROSS_W),
                                 rand_words(r, 5, 9, _CROSS_W),
                                 rand_words(r, 3, 9, _CROSS_W),
                                 rand_words(r, 9, _CROSS_W)),
    "fused_pow2_padded": lambda r: (_pow2_padded(r, 3, 5),
                                    _pow2_padded(r, 3, 5),
                                    _pow2_padded(r, 5, 5), None),
    "fused_zero_and_one_rows": lambda r: (_extreme_rows(r, 2, 3),
                                          _extreme_rows(r, 3, 3),
                                          _extreme_rows(r, 2, 3),
                                          _extreme_rows(r, 3, 3)[:, 0]),
    "fused_q4_shape": lambda r: (rand_words(r, 8, 2, 128),
                                 rand_words(r, 16, 2, 128),
                                 rand_words(r, 8, 2, 128), None),
    "fused_row_tiles_ragged": lambda r: (rand_words(r, 9, 2, 128),
                                         rand_words(r, 17, 2, 128),
                                         rand_words(r, 10, 2, 128), None),
    # 24 prefixes x 6 rows = 144 pairs in groups of 5 x 6: the row-major
    # body's fifth group writes lanes 120-149, across two lane chunks, and
    # its last group is ragged (4 of 5 prefixes)
    "fused_group_across_lane_chunks": lambda r: (rand_words(r, 8, 3, 128),
                                                 rand_words(r, 6, 3, 128),
                                                 rand_words(r, 3, 3, 128),
                                                 rand_words(r, 3, 128)),
}


# The parts form: every stack operand is handed over as the tuple of its
# per-extent parts (split along the shard axis at these sizes), as
# hbm/residency.py stages a view of more than one extent. name: (case to
# build, part sizes, whether the kernel body alternates from part to part).
_PARTS_CASES = {
    "parts_8_8_3": ("shards_19", (8, 8, 3), False),
    "parts_body_per_part": ("shards_19", (8, 8, 3), True),
    "parts_fused": ("fused_shards_19", (8, 8, 3), True),
    "parts_filtered": ("shards_11_filtered", (4, 4, 3), False),
    "parts_fused_filtered": ("fused_filtered", (3, 3, 3), True),
    "parts_row_tiles_ragged": ("fused_row_tiles_ragged", (1, 1), True),
    "parts_one": ("fused_filtered", (9,), False),
}
_CROSS_CASES.update({
    "shards_19": lambda r: (rand_words(r, 3, 19, _CROSS_W),
                            rand_words(r, 5, 19, _CROSS_W), None, None),
    "fused_shards_19": lambda r: (rand_words(r, 2, 19, _CROSS_W),
                                  rand_words(r, 5, 19, _CROSS_W),
                                  rand_words(r, 3, 19, _CROSS_W), None),
})


def _split(x, sizes):
    """A stack [rows, S, W] as the tuple of its parts along the shard axis."""
    if x is None:
        return None
    assert x.shape[1] == sum(sizes)
    return tuple(np.split(x, np.cumsum(sizes)[:-1], axis=1))


@pytest.mark.parametrize("shard_major", [True, False],
                         ids=["shard_major", "row_major"])
@pytest.mark.parametrize("case", sorted(_CROSS_CASES) + sorted(_PARTS_CASES))
def test_cross_counts_matches_xla_and_numpy(rng, case, shard_major):
    build, sizes, alternate = _PARTS_CASES.get(case, (case, None, False))
    acc, planes, mid, filt = _CROSS_CASES[build](rng)
    if sizes is None:
        got = pk.cross_counts(acc, planes, mid, filt, shard_major=shard_major)
    else:
        bodies = tuple(
            shard_major ^ (alternate and i % 2 == 1) for i in range(len(sizes))
        )
        got = pk.cross_counts(
            _split(acc, sizes), _split(planes, sizes), _split(mid, sizes),
            filt, shard_major=bodies,
        )
        # the parts read in place answer as the one assembled stack does
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(
                pk.cross_counts(acc, planes, mid, filt, shard_major=shard_major)
            ),
        )
    got = np.asarray(got)
    assert got.dtype == np.uint32
    want = _np_cross(acc, planes, mid, filt)
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, _xla_cross(acc, planes, mid, filt))


def test_one_part_is_the_single_stack_program(rng):
    """A monolithic stack is a tuple of one: the bare array and the tuple
    run the same compiled program, so a view of one extent compiles and
    runs what it did before there were parts."""
    acc = rand_words(rng, 2, 5, 128)
    planes = rand_words(rng, 3, 5, 128)
    whole = np.asarray(pk.cross_counts(acc, planes, shard_major=False))
    programs = pk._cross_counts_vmem._cache_size()
    one = pk.cross_counts((acc,), (planes,), shard_major=(False,))
    assert pk._cross_counts_vmem._cache_size() == programs
    np.testing.assert_array_equal(np.asarray(one), whole)


def test_cross_counts_follows_the_layout_of_its_candidate_rows(rng):
    """Left to itself the wrapper reads the layout from the array: a host
    array or a row-major device array is the row-major body's."""
    planes = rand_words(rng, 4, 3, 128)
    assert not pk._shard_major(planes)
    assert not pk._shard_major(jax.numpy.asarray(planes))
    acc = rand_words(rng, 2, 3, 128)
    np.testing.assert_array_equal(
        np.asarray(pk.cross_counts(acc, planes)), _np_cross(acc, planes)
    )
    # parts: one body per part, each from its own part's layout
    halves = lambda x: (x[:, :2], x[:, 2:])  # noqa: E731
    np.testing.assert_array_equal(
        np.asarray(pk.cross_counts(halves(acc), halves(planes))),
        _np_cross(acc, planes),
    )
