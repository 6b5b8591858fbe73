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
