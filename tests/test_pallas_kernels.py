"""Pallas kernels vs the jnp reference paths (differential, interpreted).

Mirrors the reference's differential testing discipline (roaring vs naive
model, roaring/fuzzer.go): every kernel must agree bit-for-bit with the
ops/bitmap.py / ops/bsi.py implementations it can replace.
"""

import jax
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

import pilosa_tpu.ops.bitmap as ob
import pilosa_tpu.ops.bsi as bsi
import pilosa_tpu.ops.pallas_kernels as pk
from pilosa_tpu.shardwidth import WORDS_PER_ROW


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
    a = rand_words(rng, 1024)
    with pltpu.force_tpu_interpret_mode(None):
        with pytest.raises(ValueError, match="interpret"):
            pk.popcount(a)


def rand_words(rng, *shape):
    return rng.integers(0, 2**32, size=shape, dtype=np.uint32)


@pytest.mark.parametrize("shape", [(WORDS_PER_ROW,), (3, 1024), (2, 5, 256)])
def test_count2_ops(rng, shape):
    a = rand_words(rng, *shape)
    b = rand_words(rng, *shape)
    assert int(pk.count_and(a, b)) == int(ob.count_and(a, b))
    assert int(pk.count_andnot(a, b)) == int(ob.count_andnot(a, b))
    assert int(pk.count_or(a, b)) == int(ob.popcount(np.bitwise_or(a, b)))
    assert int(pk.count_xor(a, b)) == int(ob.popcount(np.bitwise_xor(a, b)))
    assert int(pk.popcount(a)) == int(ob.popcount(a))


def test_count2_unaligned_tail(rng):
    # shapes that don't divide the tile: zero-padding must not change counts
    a = rand_words(rng, 7, 131)  # 917 words
    b = rand_words(rng, 7, 131)
    assert int(pk.count_and(a, b)) == int(ob.count_and(a, b))
    assert int(pk.popcount(a)) == int(ob.popcount(a))


def test_rows_counts(rng):
    stack = rand_words(rng, 13, 1024)  # 13 rows: exercises row padding
    filt = rand_words(rng, 1024)
    np.testing.assert_array_equal(
        np.asarray(pk.popcount_rows(stack)), np.asarray(ob.popcount_rows(stack))
    )
    np.testing.assert_array_equal(
        np.asarray(pk.count_and_rows(stack, filt)),
        np.asarray(ob.count_and_rows(stack, filt)),
    )


def test_bsi_sum_counts(rng):
    depth = 9
    w = 3000  # not a multiple of the BSI tile: exercises lane padding
    planes = rand_words(rng, depth, w)
    exists = rand_words(rng, w)
    sign = rand_words(rng, w)
    filt = rand_words(rng, w)
    c0, p0, n0 = bsi.sum_counts(planes, exists, sign, filt, depth)
    c1, p1, n1 = pk.sum_counts(planes, exists, sign, filt, depth)
    assert int(c0) == int(c1)
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
    np.testing.assert_array_equal(np.asarray(n0), np.asarray(n1))


def test_bsi_sum_no_filter(rng):
    depth = 4
    w = pk._BSI_TILE  # exactly one tile
    planes = rand_words(rng, depth, w)
    exists = rand_words(rng, w)
    sign = np.zeros(w, dtype=np.uint32)
    filt = np.full(w, 0xFFFFFFFF, dtype=np.uint32)
    c0, p0, n0 = bsi.sum_counts(planes, exists, sign, filt, depth)
    c1, p1, n1 = pk.sum_counts(planes, exists, sign, filt, depth)
    assert int(c0) == int(c1)
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
    assert int(np.asarray(n1).sum()) == 0


def test_bitmap_dispatch_flag(monkeypatch, rng):
    """PILOSA_TPU_PALLAS=1 routes ops.bitmap's counting ops through pallas."""
    import pilosa_tpu.ops.bitmap as bitmap

    a = rand_words(rng, 4, 256)
    b = rand_words(rng, 4, 256)
    want = int(bitmap.count_and(a, b))
    monkeypatch.setattr(bitmap, "_USE_PALLAS", True)
    assert int(bitmap.count_and(a, b)) == want
    assert int(bitmap.count_andnot(a, b)) == int(pk.count_andnot(a, b))
    np.testing.assert_array_equal(
        np.asarray(bitmap.popcount_rows(a)), np.asarray(pk.popcount_rows(a))
    )
    filt = rand_words(rng, 256)
    np.testing.assert_array_equal(
        np.asarray(bitmap.count_and_rows(a, filt)),
        np.asarray(pk.count_and_rows(a, filt)),
    )


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
}


@pytest.mark.parametrize("shard_major", [True, False],
                         ids=["shard_major", "row_major"])
@pytest.mark.parametrize("case", sorted(_CROSS_CASES))
def test_cross_counts_matches_xla_and_numpy(rng, case, shard_major):
    acc, planes, mid, filt = _CROSS_CASES[case](rng)
    got = np.asarray(
        pk.cross_counts(acc, planes, mid, filt, shard_major=shard_major)
    )
    assert got.dtype == np.uint32
    want = _np_cross(acc, planes, mid, filt)
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, _xla_cross(acc, planes, mid, filt))


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
