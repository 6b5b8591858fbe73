"""Durable write path: deterministic crash-kill matrix + group-commit
WAL properties.

Kill matrix (ISSUE 12): a real writer process (tests/crash_worker.py)
drives the staged import path with a FaultInjector "kill" rule armed at
one exact durability point — inside the group-commit round (pre-fsync,
post-fsync-pre-ack), during a replica ship, at the merge-barrier
install, between snapshot and WAL truncate — and SIGKILLs itself there.
The parent then audits the survivor state against the killed process's
fsynced ack log: every acked batch must replay bit-identically (rows
AND rank-cache order), and replay must be deterministic (two
independent opens agree). The full matrix (bounded-loss mode, replica
ship, soak) runs @slow in CI's mesh job.

Property layer: the torn-tail test truncates a group-committed WAL at
EVERY byte boundary and asserts replay recovers exactly the longest
valid CRC-framed prefix; the coalescing test drives >= 8 concurrent
importers and asserts fsyncs-per-import < 0.5 (the group commit
measurably coalesces); the solo-writer test pins the no-hold-window
contract (one fsync per import, latency within 2x of a bare
write+fsync)."""

import importlib.util
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from pilosa_tpu.core import wal as walmod
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.server import faults
from pilosa_tpu.shardwidth import SHARD_WIDTH

_HERE = os.path.dirname(os.path.abspath(__file__))
_WORKER = os.path.join(_HERE, "crash_worker.py")

_spec = importlib.util.spec_from_file_location("crash_worker", _WORKER)
crash_worker = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(crash_worker)


@pytest.fixture(autouse=True)
def _strict_commit_mode():
    """Every test leaves the process-global committer in strict mode
    with no background syncer cadence armed."""
    yield
    walmod.GROUP_COMMIT.configure(sync_interval=0.0)


# ---------------------------------------------------------------------------
# kill-matrix driver
# ---------------------------------------------------------------------------


def _run_worker(tmp_path, point, sync_interval=0.0, kill_after=2,
                batches=30, n_shards=4, max_op_n=0, expect_kill=True,
                require_incomplete=True):
    data_dir = os.path.join(str(tmp_path), "data")
    ack_log = os.path.join(str(tmp_path), "acks.log")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    args = [
        sys.executable, _WORKER,
        "--point", point,
        "--data-dir", data_dir,
        "--ack-log", ack_log,
        "--sync-interval", str(sync_interval),
        "--batches", str(batches),
        "--kill-after", str(kill_after),
        "--n-shards", str(n_shards),
        "--max-op-n", str(max_op_n),
    ]
    proc = subprocess.run(
        args, env=env, capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(_HERE),
    )
    if expect_kill:
        # the injector must have SIGKILLed the worker mid-write — a
        # clean exit means the kill point never fired and the test
        # would be vacuous
        assert proc.returncode == -signal.SIGKILL, (
            point, proc.returncode, proc.stdout[-2000:], proc.stderr[-2000:],
        )
        if require_incomplete:
            assert "COMPLETED" not in proc.stdout, proc.stdout
    acked = []
    if os.path.exists(ack_log):
        with open(ack_log) as fh:
            acked = [int(x) for x in fh.read().split()]
    return data_dir, acked


def _expected_positions(batch_ids, n_shards):
    want = set()
    for i in batch_ids:
        rows, cols = crash_worker.batch_bits(i, n_shards)
        shards = cols // SHARD_WIDTH
        in_shard = cols % SHARD_WIDTH
        want.update(
            zip(shards.tolist(), rows.tolist(), in_shard.tolist())
        )
    return want


def _state_of(data_dir, index="ck"):
    """(positions, cache_tops): the full replayed bit set as
    (shard, row, col) tuples plus each fragment's rank-cache top list."""
    h = Holder(data_dir).open()
    try:
        idx = h.index(index)
        assert idx is not None, f"index {index!r} missing after replay"
        f = idx.field("f")
        std = f.view("standard")
        got = set()
        tops = {}
        for shard, frag in sorted(std.fragments.items()):
            rows, cols = frag.pairs()
            got.update(
                (shard, int(r), int(c)) for r, c in zip(rows.tolist(), cols.tolist())
            )
            tops[shard] = list(frag.cache_top())
        return got, tops
    finally:
        h.close()


def _verify_replay(data_dir, acked, batches, n_shards, *, index="ck",
                   acked_must_survive=True):
    got1, tops1 = _state_of(data_dir, index)
    got2, tops2 = _state_of(data_dir, index)
    # replay is deterministic: two independent opens are bit-identical,
    # including the rank-cache (TopN) order
    assert got1 == got2
    assert tops1 == tops2
    sent = _expected_positions(range(batches), n_shards)
    assert got1 <= sent, "replay invented bits that were never written"
    if acked_must_survive:
        want = _expected_positions(acked, n_shards)
        missing = want - got1
        assert not missing, (
            f"{len(missing)} acked bits lost after crash replay "
            f"(acked batches {acked[:5]}..{acked[-1] if acked else None})"
        )
    return got1


# The tier-1 deterministic subset: one strict-mode kill at each
# single-process point. The full matrix (bounded-loss mode, replica
# ship) rides @slow below.
@pytest.mark.parametrize(
    "point,max_op_n",
    [
        ("commit.pre_fsync", 0),
        ("commit.post_fsync", 0),
        ("snapshot.pre_truncate", 400),
        ("merge.install", 0),
    ],
)
def test_kill_matrix_strict(tmp_path, point, max_op_n):
    data_dir, acked = _run_worker(
        tmp_path, point, sync_interval=0.0, kill_after=2, max_op_n=max_op_n
    )
    # the kill fired mid-batch: not every batch can have been acked
    assert len(acked) < 30, "worker finished all batches before the kill"
    _verify_replay(data_dir, acked, 30, 4)


@pytest.mark.slow
@pytest.mark.parametrize(
    "point,max_op_n",
    [
        ("commit.pre_fsync", 0),
        ("commit.post_fsync", 0),
        ("snapshot.pre_truncate", 400),
        ("merge.install", 0),
    ],
)
def test_kill_matrix_bounded_loss(tmp_path, point, max_op_n):
    """sync-interval > 0: acks outpace fsyncs by design. A process kill
    still loses nothing (the buffered bytes live in the OS page cache,
    which survives the process) — the loss window only opens on a
    machine crash, which is exactly what the torn-tail property test
    models at the byte level. Replay must stay deterministic and a
    subset of what was sent."""
    # require_incomplete=False: in bounded-loss mode the kill rides the
    # background syncer's cadence, so it may land only after the last
    # (already acked) batch — that is the mode's contract, not a miss
    data_dir, acked = _run_worker(
        tmp_path, point, sync_interval=0.05, kill_after=0,
        max_op_n=max_op_n, require_incomplete=False,
    )
    _verify_replay(data_dir, acked, 30, 4)


@pytest.mark.slow
def test_kill_during_replica_ship(tmp_path):
    """Kill the importing node while a pool thread is shipping a replica
    frame (2 real in-process nodes over HTTP). Both data dirs must
    replay deterministically; every ACKED batch survives on the
    coordinator (acks wait for local apply + ship resolution), and the
    replica holds a subset of what was sent."""
    data_dir, acked = _run_worker(
        tmp_path, "replica.ship", kill_after=3, batches=20,
    )
    got_a = _verify_replay(os.path.join(data_dir, "a"), acked, 20, 4)
    got_b = _verify_replay(
        os.path.join(data_dir, "b"), acked, 20, 4, acked_must_survive=False
    )
    # acked writes reached the coordinator; the replica may trail by
    # the in-flight frame only (anti-entropy repairs the rest, as the
    # pending-repair ledger records)
    assert got_b <= _expected_positions(range(20), 4)
    assert len(got_a) >= len(got_b)


# ---------------------------------------------------------------------------
# torn-tail property: replay recovers exactly the longest valid prefix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sync_interval", [0.0, 0.05])
def test_torn_tail_every_byte_boundary(tmp_path, sync_interval):
    walmod.GROUP_COMMIT.configure(sync_interval=sync_interval)
    rng = np.random.default_rng(7)
    records = [
        (walmod.OP_SET, rng.integers(0, 1 << 40, 37).astype(np.uint64)),
        (walmod.OP_CLEAR, rng.integers(0, 1 << 40, 11).astype(np.uint64)),
        # an OP_ROW_WORDS frame: payload[0] = row id, rest = row words
        (walmod.OP_ROW_WORDS, rng.integers(0, 1 << 60, 33).astype(np.uint64)),
        (walmod.OP_SET, rng.integers(0, 1 << 40, 23).astype(np.uint64)),
    ]
    p = str(tmp_path / "torn.wal")
    w = walmod.WalWriter(p)
    for op, positions in records:
        w.append(op, positions)
    walmod.GROUP_COMMIT.wait_durable()
    w.close()
    data = open(p, "rb").read()
    # record byte spans: header (13 bytes) + 8 bytes per position
    spans = []
    off = 0
    for op, positions in records:
        off += walmod._REC_HDR.size + 8 * len(positions)
        spans.append(off)
    assert spans[-1] == len(data)
    trunc = str(tmp_path / "trunc.wal")
    for cut in range(len(data) + 1):
        with open(trunc, "wb") as fh:
            fh.write(data[:cut])
        replayed = list(walmod.replay_wal(trunc))
        # the longest valid prefix: every record whose bytes fit in the cut
        n_want = sum(1 for s in spans if s <= cut)
        assert len(replayed) == n_want, (cut, n_want, len(replayed))
        for (op_w, pos_w), (op_g, pos_g) in zip(records, replayed):
            assert op_w == op_g
            np.testing.assert_array_equal(pos_w, pos_g)
        n_ops, status, _ = walmod.check_wal(trunc)
        assert n_ops == n_want
        assert status == ("ok" if cut in (0, *spans) else "torn")


def test_append_skips_empty_records(tmp_path):
    p = str(tmp_path / "empty.wal")
    w = walmod.WalWriter(p)
    assert w.append(walmod.OP_SET, np.empty(0, np.uint64)) is None
    assert w.append_many([(walmod.OP_SET, np.empty(0, np.uint64))]) is None
    assert os.path.getsize(p) == 0
    # a mixed batch frames only the non-empty record
    tok = w.append_many(
        [
            (walmod.OP_SET, np.empty(0, np.uint64)),
            (walmod.OP_CLEAR, np.array([5, 9], np.uint64)),
        ]
    )
    assert tok is not None
    walmod.GROUP_COMMIT.wait_durable(tok)
    w.close()
    replayed = list(walmod.replay_wal(p))
    assert len(replayed) == 1
    assert replayed[0][0] == walmod.OP_CLEAR


def test_truncate_is_fsynced_and_dir_synced(tmp_path):
    # behavioural floor: a truncated WAL stays empty across reopen and
    # a fresh writer's file is immediately visible/replayable (the
    # fsync/dir-fsync calls themselves can only be proven on a real
    # power cut; this pins the code path end to end)
    p = str(tmp_path / "t.wal")
    w = walmod.WalWriter(p)
    tok = w.append(walmod.OP_SET, np.array([1, 2, 3], np.uint64))
    walmod.GROUP_COMMIT.wait_durable(tok)
    w.truncate()
    assert os.path.getsize(p) == 0
    assert list(walmod.replay_wal(p)) == []
    w.close()


# ---------------------------------------------------------------------------
# group-commit coalescing + solo-writer contract
# ---------------------------------------------------------------------------


def test_group_commit_coalesces_concurrent_imports(tmp_path):
    """Acceptance: >= 8 concurrent import threads, fsyncs-per-import
    < 0.5. An injected 3 ms fsync makes the rounds overlap the way a
    real disk does (on tmpfs an fsync is near-free and nothing would
    queue), so followers pile up behind the leader and each round
    releases several imports with ONE fsync."""
    inj = faults.FaultInjector(seed=0).add_wal_rule(
        "slow", point="wal.fsync", delay=0.003
    )
    faults.install_injector(inj)
    h = Holder(str(tmp_path)).open()
    try:
        idx = h.create_index("gc")
        f = idx.create_field("f", FieldOptions())
        # warm: create the fragment outside the measured window
        f.import_bits(np.array([0], np.uint64), np.array([0], np.uint64))
        walmod.GROUP_COMMIT.flush()
        s0 = walmod.stats_snapshot()
        per_thread = 15
        n_threads = 8
        errs = []

        def writer(t):
            try:
                rng = np.random.default_rng(t)
                for _ in range(per_thread):
                    rows = rng.integers(0, 4, 200).astype(np.uint64)
                    cols = rng.integers(0, SHARD_WIDTH, 200).astype(np.uint64)
                    f.import_bits(rows, cols)
            except Exception as e:  # noqa: BLE001 - fail the test
                errs.append(e)

        threads = [
            threading.Thread(target=writer, args=(t,)) for t in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs[:1]
        s1 = walmod.stats_snapshot()
        imports = n_threads * per_thread
        fsyncs = s1["fsyncs"] - s0["fsyncs"]
        groups = s1["commit_groups"] - s0["commit_groups"]
        assert fsyncs / imports < 0.5, (fsyncs, imports)
        assert groups <= fsyncs  # every round fsynced at least one file
    finally:
        faults.uninstall_injector()
        h.close()


@pytest.mark.skipif(
    os.environ.get("PILOSA_TPU_RACE_CHECK") == "1",
    reason="latency-budget assertion: the race checker's attribute "
    "instrumentation adds per-access overhead that blows the 2x-bare-"
    "fsync bound by design; the functional fsync-count assertions are "
    "covered by the rest of the matrix under the checker",
)
def test_solo_writer_strict_no_hold_window(tmp_path):
    """A solo strict-mode writer pays exactly one fsync round per import
    (the leader fires immediately — group commit adds no hold window)
    and its latency stays within 2x of a bare write+fsync."""
    h = Holder(str(tmp_path)).open()
    try:
        idx = h.create_index("solo")
        f = idx.create_field("f", FieldOptions())
        f.import_bits(np.array([0], np.uint64), np.array([0], np.uint64))
        walmod.GROUP_COMMIT.flush()
        s0 = walmod.stats_snapshot()
        n = 30
        rng = np.random.default_rng(3)
        gc_times = []
        for _ in range(n):
            rows = rng.integers(0, 4, 64).astype(np.uint64)
            cols = rng.integers(0, SHARD_WIDTH, 64).astype(np.uint64)
            t0 = time.perf_counter()
            f.import_bits(rows, cols)
            gc_times.append(time.perf_counter() - t0)
        s1 = walmod.stats_snapshot()
        # one commit round, one fsync per import — never more
        assert s1["fsyncs"] - s0["fsyncs"] <= n
        assert s1["commit_groups"] - s0["commit_groups"] <= n
        # bare write+fsync baseline on the same filesystem
        raw_path = str(tmp_path / "baseline.bin")
        data = walmod.encode_records(
            [(walmod.OP_SET, rng.integers(0, 1 << 40, 64).astype(np.uint64))]
        )
        naive_times = []
        with open(raw_path, "ab") as raw:
            for _ in range(n):
                t0 = time.perf_counter()
                raw.write(data)
                raw.flush()
                os.fsync(raw.fileno())
                naive_times.append(time.perf_counter() - t0)
        med_gc = sorted(gc_times)[n // 2]
        med_naive = sorted(naive_times)[n // 2]
        # 2x the bare fsync plus 2 ms absolute slack: the import also
        # stages positions and runs numpy, which a bare write does not
        assert med_gc <= 2 * med_naive + 0.002, (med_gc, med_naive)
    finally:
        h.close()


# ---------------------------------------------------------------------------
# replicated-ingest soak (@slow; the benched configuration's test twin)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_replicated_ingest_soak(tmp_path):
    """replica_n=2, two real HTTP nodes, 4 concurrent writers + a query
    stream: all writes converge on BOTH replicas, queries stay correct
    under ingest, and the group commit coalesces across the whole
    process (fsyncs-per-import < 2 with multi-shard batches)."""
    from pilosa_tpu.testing import ClusterHarness

    n_shards = 4
    with ClusterHarness(2, replica_n=2, base_dir=str(tmp_path)) as c:
        api = c[0].api
        api.create_index("soak")
        api.create_field("soak", "f", {"type": "set"})
        s0 = walmod.stats_snapshot()
        stop = threading.Event()
        sent = [set() for _ in range(4)]
        errs = []
        n_imports = [0]

        def writer(t):
            try:
                rng = np.random.default_rng(100 + t)
                for _ in range(12):
                    rows = np.zeros(500, np.uint64)
                    cols = rng.integers(
                        0, n_shards * SHARD_WIDTH, 500
                    ).astype(np.uint64)
                    api.import_bits("soak", "f", rows, cols)
                    sent[t].update(cols.tolist())
                    n_imports[0] += 1
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        def reader():
            try:
                while not stop.is_set():
                    (cnt,) = c[1].api.query("soak", "Count(Row(f=0))")
                    assert cnt >= 0
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=writer, args=(t,)) for t in range(4)]
        rt = threading.Thread(target=reader)
        for t in threads:
            t.start()
        rt.start()
        for t in threads:
            t.join()
        stop.set()
        rt.join()
        assert not errs, errs[:1]
        expect = len(set().union(*sent))
        for node in c.nodes:
            (cnt,) = node.api.query("soak", "Count(Row(f=0))")
            assert cnt == expect, node.node.id
        s1 = walmod.stats_snapshot()
        fsyncs = s1["fsyncs"] - s0["fsyncs"]
        appends = s1["commits"] - s0["commits"]
        # every append (data fragments AND the index's column-existence
        # tracking, on both replicas) is covered by strictly fewer
        # fsyncs: concurrent writers share commit rounds, so same-file
        # appends from different calls resolve under one fsync
        assert fsyncs < appends, (fsyncs, appends, n_imports[0])
