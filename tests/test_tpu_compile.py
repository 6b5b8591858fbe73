"""The kernels of the served path, compiled for a TPU v5e that is
described, not attached: Mosaic refuses here what it would refuse on the
chip (tiling, VMEM), at the sizes the benchmark runs, and the compiled
module shows whether XLA had to copy a stack to feed the kernel. Nothing
runs, so no answer and no time is checked — `chip_smoke.py` does that.

The topology is described inside a fixture and only in this file: one
process at a time may hold the TPU library, and every xdist worker
imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from pilosa_tpu.ops import pallas_kernels as pk

SHARDS, WORDS = 954, 32768  # taxi-1b at the shipped shard width


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    described = {
        "TPU_ACCELERATOR_TYPE": "v5litepod-4",
        "TPU_WORKER_HOSTNAMES": "localhost",
        "TPU_SKIP_MDS_QUERY": "1",
        "TPU_LOG_DIR": "disabled",
    }
    with pytest.MonkeyPatch.context() as m:
        for k, v in described.items():
            if k not in os.environ:
                m.setenv(k, v)
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _stack(sharding, rows, shards=SHARDS):
    shape = (rows, shards, WORDS) if rows else (shards, WORDS)
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


EXTENTS = (256, 256, 256, 186)  # 954 shards as hbm/residency.py keeps them

# name: (G, R, M, filter, shards of each part, kernel body of each part,
#        bytes XLA may copy)
_CASES = {
    # the benchmark's GroupBys, read where the view's four extents lie: a
    # [8k, 256, W] extent is row-major (8 divides 256), the [8k, 186, W]
    # tail shard-major, and each is read in place by the body of its layout
    "taxi_q3": (8, 8, 0, False, EXTENTS, (False,) * 3 + (True,), 0),
    "taxi_q4": (8, 16, 8, False, EXTENTS, (False,) * 3 + (True,), 0),
    # a filter [S, W] is sliced per extent, and the slice of a shard-major
    # extent re-tiled to one row per shard: at most the filter's own 125 MB
    "taxi_q4_filtered": (8, 16, 8, True, EXTENTS, (False,) * 3 + (True,),
                         SHARDS * WORDS * 4),
    # the same GroupBys over one assembled [8k, 954, W] stack, which the
    # device keeps shard-major (what every tally read before PR 32)
    "taxi_q3_assembled": (8, 8, 0, False, (SHARDS,), (True,), 0),
    "taxi_q4_assembled": (8, 16, 8, False, (SHARDS,), (True,), 0),
    "taxi_q4_assembled_filtered": (8, 16, 8, True, (SHARDS,), (True,),
                                   SHARDS * WORDS * 4),
    # the filtered TopN's dense chunk and a descent chunk at 954 shards
    "topn_chunk": (1, 2, 0, False, (SHARDS,), (True,), 0),
    "descent_chunk": (2, 16, 0, False, (SHARDS,), (True,), 0),
    # stacks the device keeps row-major: odd row counts, or 8 | S
    "odd_rows": (3, 5, 6, True, (SHARDS,), (False,), 0),
    "shards_960": (8, 16, 8, False, (960,), (False,), 0),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_cross_counts_compiles_for_v5e_without_copying_its_stacks(
    one_chip, case
):
    g, r, m, filtered, parts, bodies, copied = _CASES[case]

    def stack(rows):
        return tuple(_stack(one_chip, rows, s) for s in parts)

    compiled = pk._cross_counts_vmem.lower(
        stack(g),
        stack(r),
        stack(m) if m else None,
        _stack(one_chip, 0, sum(parts)) if filtered else None,
        shard_major=bodies,
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= len(parts)
    assert compiled.memory_analysis().temp_size_in_bytes <= copied * 1.01


SSB_EXTENTS = (256, 256, 61)  # 573 shards: ssb-sf100

# name: (rows of each dimension, table length, planes, mask row)
_GROUP_CASES = {
    # ssb-sf100.flights-q3-q4: each dimension against the filter, then the
    # groups the surviving rows make with the value field's planes
    "ssb_prune_25": ((25,), 32, 0, None),
    "ssb_prune_7": ((7,), 8, 0, None),
    "ssb_q31": ((25, 25, 7), 256, 26, 0),
    "ssb_q41_cost": ((7, 25), 64, 19, 0),
    "ssb_q42": ((7, 25, 25), 128, 26, 0),
    # a signed field's second pass, and the deepest field there is
    "signed": ((25, 25, 7), 256, 26, 1),
    "deep_34": ((25,), 32, 34, 0),
}


@pytest.mark.parametrize("case", sorted(_GROUP_CASES))
def test_group_counts_compiles_for_v5e_without_copying_its_stacks(
    one_chip, case
):
    """The aggregate GroupBy's tally at the SSB cell's shapes: every
    dimension, the planes and the filter read where the three extents lie
    (row-major: none of these row counts is 1, 2, 4 or 8k)."""
    dims, g, planes, mask_row = _GROUP_CASES[case]

    def stack(rows):
        return tuple(_stack(one_chip, rows, s) for s in SSB_EXTENTS)

    compiled = pk._group_counts_vmem.lower(
        tuple(stack(r) for r in dims),
        jax.ShapeDtypeStruct((len(dims), g), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip),
        stack(planes) if planes else None,
        _stack(one_chip, 0, sum(SSB_EXTENTS)),
        mask_row=mask_row,
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= len(SSB_EXTENTS)
    assert compiled.memory_analysis().temp_size_in_bytes <= 1 << 20
