"""Pallas kernels vs the jnp reference paths (differential, interpreted).

Mirrors the reference's differential testing discipline (roaring vs naive
model, roaring/fuzzer.go): every kernel must agree bit-for-bit with the
ops/bitmap.py / ops/bsi.py implementations it can replace.
"""

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
