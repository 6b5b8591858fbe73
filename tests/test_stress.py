"""Concurrency stress + shard-width matrix (VERDICT r2 #7a/#7c).

The reference runs its whole suite under -race and re-runs CI at
SHARD_WIDTH=22 (SURVEY §4). Python has no race detector, so the stress
test drives the lock discipline (fragment._mu, devcache._mu, resize/_
topology swaps) under real contention — concurrent imports + queries +
anti-entropy against one live cluster — and asserts invariants at the end;
the width matrix re-runs core suites in subprocesses at exponents 16/22.
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.testing import ClusterHarness


@pytest.mark.slow
def test_concurrent_imports_queries_ae():
    """Writers, readers and anti-entropy hammer one 3-node cluster
    concurrently; nothing may raise, and the final state must equal the
    union of everything written on every node."""
    with ClusterHarness(3, replica_n=2, in_memory=True) as c:
        api = c[0].api
        api.create_index("st")
        api.create_field("st", "f", {"type": "set"})
        api.create_field(
            "st", "v", {"type": "int", "min": 0, "max": 1_000_000}
        )
        stop = threading.Event()
        errors: list = []
        written_cols: list = [set() for _ in range(3)]

        def writer(wid: int):
            rng = np.random.default_rng(100 + wid)
            try:
                while not stop.is_set():
                    cols = rng.integers(0, 8 * SHARD_WIDTH, 200).astype(np.uint64)
                    # rotate the entry node: writes land via different
                    # coordinators and replica fan-outs
                    node = c[wid % 3]
                    node.api.import_bits(
                        "st", "f", np.full(len(cols), wid, np.uint64), cols
                    )
                    written_cols[wid] |= {int(x) for x in cols}
                    node.api.import_values(
                        "st", "v", cols[:50], rng.integers(0, 1_000_000, 50)
                    )
            except Exception as e:  # noqa: BLE001 - collected for assert
                errors.append(("writer", wid, repr(e)))

        def reader(rid: int):
            try:
                while not stop.is_set():
                    node = c[rid % 3]
                    node.api.query("st", f"Count(Row(f={rid % 3}))")
                    node.api.query("st", "TopN(f, n=3)")
                    node.api.query("st", "Sum(field=v)")
            except Exception as e:  # noqa: BLE001
                errors.append(("reader", rid, repr(e)))

        def ae():
            try:
                while not stop.is_set():
                    for node in c.nodes:
                        node.sync_holder()
                    time.sleep(0.05)
            except Exception as e:  # noqa: BLE001
                errors.append(("ae", 0, repr(e)))

        threads = (
            [threading.Thread(target=writer, args=(i,)) for i in range(3)]
            + [threading.Thread(target=reader, args=(i,)) for i in range(3)]
            + [threading.Thread(target=ae)]
        )
        for t in threads:
            t.start()
        time.sleep(6.0)
        stop.set()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive(), "stress thread wedged"
        assert not errors, errors[:5]
        # settle: one final AE pass from every node, then every node must
        # agree with the exact union of what the writers recorded
        for node in c.nodes:
            node.sync_holder()
        for wid in range(3):
            expect = len(written_cols[wid])
            for node in c.nodes:
                (cnt,) = node.api.query("st", f"Count(Row(f={wid}))")
                assert cnt == expect, (node.node.id, wid, cnt, expect)
        # devcache bookkeeping survived the churn
        from pilosa_tpu.core.devcache import DEVICE_CACHE

        assert DEVICE_CACHE.bytes_used >= 0
        assert DEVICE_CACHE.bytes_used <= DEVICE_CACHE.budget_bytes * 2


# ---------------------------------------------------------------------------
# shard-width matrix (CI re-run at SHARD_WIDTH=22; SURVEY §4)
# ---------------------------------------------------------------------------

_CORE_SUITES = [
    "tests/test_storage.py",
    "tests/test_executor.py",
    "tests/test_roaring_io.py",
    "tests/test_topn_batched.py",  # r5 gather-tally bit packing
    "tests/test_merge.py",  # ISSUE 9 cross-fragment merge equivalence
    "tests/test_meshexec.py",  # ISSUE 10 mesh-group differential equivalence
    "tests/test_bsistream.py",  # ISSUE 15 plane-streamed BSI differential
]


@pytest.mark.slow
@pytest.mark.parametrize("exponent", ["16", "22"])
def test_shard_width_matrix(exponent):
    """Core suites must pass at non-default shard widths — catching any
    width-hardcoding (the reference's SHARD_WIDTH=22 CI job)."""
    env = dict(os.environ)
    env["PILOSA_TPU_SHARD_WIDTH_EXPONENT"] = exponent
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q"] + _CORE_SUITES,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True,
        text=True,
        timeout=900,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]


@pytest.mark.slow
def test_paranoia_suite():
    """Storage + executor suites under PILOSA_TPU_PARANOIA=1: the invariant
    guards must hold on every mutation path (roaringparanoia CI analog)."""
    env = dict(os.environ)
    env["PILOSA_TPU_PARANOIA"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         "tests/test_storage.py", "tests/test_executor.py"],
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True,
        text=True,
        timeout=900,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
