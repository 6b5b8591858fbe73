"""Cluster lifecycle: coordinator-driven join/remove resize jobs and a
subprocess-level fault-injection E2E (SIGKILL mid-import, WAL replay,
anti-entropy convergence).

Reference parity: cluster.go:1141-1561 (listenForJoins -> resizeJob with
RUNNING/DONE/ABORTED states + abort), api.go:1226-1250 (RemoveNode /
ResizeAbort), internal/clustertests/cluster_test.go:28-79 (containerized
kill-a-node-mid-import E2E — here OS processes instead of containers)."""

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.server.node import NodeServer
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.testing import ClusterHarness


def http_json(method, url, body=None, timeout=30):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    if data is not None:
        req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        raw = resp.read()
    return json.loads(raw) if raw else {}


def wait_job(uri, want="DONE", timeout=30.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        job = http_json("GET", f"{uri}/cluster/resize/job")
        if job["state"] != "RUNNING":
            assert job["state"] == want, job
            return job
        time.sleep(0.05)
    raise AssertionError("resize job did not finish")


# ---------------------------------------------------------------------------
# in-process join / remove / abort
# ---------------------------------------------------------------------------


def test_join_via_coordinator():
    """POST /cluster/join on the coordinator moves data to the new node and
    installs the grown topology everywhere."""
    with ClusterHarness(2, in_memory=True) as c:
        api = c[0].api
        api.create_index("j")
        api.create_field("j", "f", {"type": "set"})
        cols = [(i % 16) * SHARD_WIDTH + i for i in range(160)]
        api.import_bits("j", "f", [0] * len(cols), cols)

        joiner = NodeServer(None, "joiner").start()
        try:
            uri = c[0].node.uri
            job = http_json(
                "POST", f"{uri}/cluster/join",
                {"id": joiner.node.id, "uri": joiner.node.uri},
            )
            assert job["state"] in ("RUNNING", "DONE")
            wait_job(uri)
            # every node (incl. joiner) has the 3-node topology + NORMAL
            for s in [c[0], c[1], joiner]:
                assert len(s.cluster.nodes) == 3, s.node.id
                assert s.state == "NORMAL"
                (cnt,) = s.api.query("j", "Count(Row(f=0))")
                assert cnt == 160, s.node.id
            # joiner actually owns (and serves) some fragments
            assert any(
                s == joiner.node.id
                for sh in range(16)
                for s in [n.id for n in c[0].cluster.shard_nodes("j", sh)]
            )
        finally:
            joiner.stop()


def test_join_idempotent_and_gated():
    with ClusterHarness(2, in_memory=True) as c:
        uri = c[0].node.uri
        # re-join of an existing member is a no-op
        job = http_json(
            "POST", f"{uri}/cluster/join",
            {"id": c[1].node.id, "uri": c[1].node.uri},
        )
        assert job["action"] == "noop"
        # non-coordinator refuses
        with pytest.raises(urllib.error.HTTPError):
            http_json(
                "POST", f"{c[1].node.uri}/cluster/join",
                {"id": "x", "uri": "http://localhost:1"},
            )


def test_remove_node_rebalances():
    with ClusterHarness(3, replica_n=2, in_memory=True) as c:
        api = c[0].api
        api.create_index("rm")
        api.create_field("rm", "f", {"type": "set"})
        cols = [(i % 8) * SHARD_WIDTH + i for i in range(80)]
        api.import_bits("rm", "f", [0] * len(cols), cols)
        uri = c[0].node.uri
        http_json(
            "POST", f"{uri}/cluster/resize/remove-node", {"id": c[2].node.id}
        )
        wait_job(uri)
        for s in [c[0], c[1]]:
            assert len(s.cluster.nodes) == 2
            (cnt,) = s.api.query("rm", "Count(Row(f=0))")
            assert cnt == 80, s.node.id
        # the removed node unfroze (got the final status) and knows it is
        # no longer a member
        assert c[2].state == "NORMAL"
        assert all(n.id != c[2].node.id for n in c[2].cluster.nodes)


def test_remove_coordinator_transfers_role():
    with ClusterHarness(3, in_memory=True) as c:
        uri = c[0].node.uri
        http_json(
            "POST", f"{uri}/cluster/resize/remove-node", {"id": c[0].node.id}
        )
        wait_job(uri)
        # a surviving node holds coordinatorship; lifecycle ops still work
        coords = [n for n in c[1].cluster.nodes if n.is_coordinator]
        assert len(coords) == 1
        new_coord = next(s for s in [c[1], c[2]] if s.node.id == coords[0].id)
        assert new_coord.node.is_coordinator
        job = new_coord.api.resize_job()
        assert job["state"] in ("NONE", "DONE")


def test_joiner_does_not_become_coordinator():
    with ClusterHarness(2, in_memory=True) as c:
        joiner = NodeServer(None, "aaa-joiner").start()  # id sorts first
        try:
            uri = c[0].node.uri
            http_json(
                "POST", f"{uri}/cluster/join",
                {"id": joiner.node.id, "uri": joiner.node.uri,
                 "isCoordinator": True},  # self-reported flag is ignored
            )
            wait_job(uri)
            coords = [n for n in c[0].cluster.nodes if n.is_coordinator]
            assert [n.id for n in coords] == [c[0].node.id]
            assert not joiner.node.is_coordinator
        finally:
            joiner.stop()


def test_join_unreachable_member_aborts_and_rolls_back():
    """A resize step failing (member down) ABORTs the job and restores the
    old topology on the surviving members."""
    with ClusterHarness(3, in_memory=True) as c:
        uri = c[0].node.uri
        old_ids = {n.id for n in c[0].cluster.nodes}
        c[2].stop()  # kill a member; its resize step will fail
        joiner = NodeServer(None, "joiner2").start()
        try:
            http_json(
                "POST", f"{uri}/cluster/join",
                {"id": joiner.node.id, "uri": joiner.node.uri},
            )
            job = wait_job(uri, want="ABORTED")
            assert job["error"]
            for s in [c[0], c[1]]:
                assert {n.id for n in s.cluster.nodes} == old_ids, s.node.id
                assert s.state == "NORMAL"
            # the joiner is reset to a standalone cluster, not left with a
            # divergent membership view
            assert [n.id for n in joiner.cluster.nodes] == [joiner.node.id]
            assert joiner.state == "NORMAL"
        finally:
            joiner.stop()


def test_abort_with_no_job():
    with ClusterHarness(1, in_memory=True) as c:
        uri = c[0].node.uri
        assert http_json("POST", f"{uri}/cluster/resize/abort")["state"] in (
            "NONE", "DONE", "ABORTED",
        )
        assert http_json("GET", f"{uri}/cluster/resize/job")["state"] == "NONE"


# ---------------------------------------------------------------------------
# subprocess E2E: SIGKILL mid-import -> restart -> WAL replay + AE converge
# ---------------------------------------------------------------------------


def _free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_node(base, name, port, hosts, replicas=2):
    """Boot `pilosa-tpu server` as a real OS process (CPU-only env)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    args = [
        sys.executable, "-m", "pilosa_tpu.cli", "server",
        "--data-dir", os.path.join(base, name),
        "--bind", f"localhost:{port}",
        "--node-id", name,
        "--cluster-hosts", hosts,
        "--replicas", str(replicas),
        "--anti-entropy-interval", "0",
    ]
    return subprocess.Popen(
        args, env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL
    )


def _wait_up(uri, timeout=60.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            return http_json("GET", f"{uri}/status", timeout=2)
        except Exception:
            time.sleep(0.2)
    raise AssertionError(f"node at {uri} did not come up")


@pytest.mark.slow
def test_sigkill_mid_import_wal_replay_and_ae():
    """Boot 3 server processes, import across shards, SIGKILL one
    mid-import, restart it, and assert WAL replay + anti-entropy converge
    every node to the correct counts (clustertests cluster_test.go:28-79,
    with SIGKILL in place of pumba pause)."""
    base = tempfile.mkdtemp(prefix="pilosa-e2e-")
    ports = [_free_port() for _ in range(3)]
    names = ["p0", "p1", "p2"]
    hosts = ",".join(
        f"{n}@http://localhost:{p}" for n, p in zip(names, ports)
    )
    uris = [f"http://localhost:{p}" for p in ports]
    procs = [_spawn_node(base, n, p, hosts) for n, p in zip(names, ports)]
    try:
        for u in uris:
            _wait_up(u)
        http_json("POST", f"{uris[0]}/index/e2e", {"options": {}})
        http_json(
            "POST", f"{uris[0]}/index/e2e/field/f", {"options": {"type": "set"}}
        )
        rng = np.random.default_rng(11)
        all_cols = sorted(
            {int(c) for c in rng.integers(0, 8 * SHARD_WIDTH, 1000)}
        )
        half = len(all_cols) // 2
        # first half of the import lands while all nodes are alive
        http_json(
            "POST", f"{uris[0]}/index/e2e/field/f/import",
            {"rows": [0] * half, "cols": all_cols[:half]},
            timeout=120,
        )
        # attrs written pre-kill: the append-log (r5) must survive the
        # SIGKILL (no clean close -> no compaction, torn tail possible)
        http_json(
            "POST", f"{uris[0]}/index/e2e/query",
            {"query": 'SetRowAttrs(f, 0, label="alpha", rank=7)'},
            timeout=120,
        )
        # SIGKILL a replica mid-stream (no clean shutdown: open WALs)
        procs[2].send_signal(signal.SIGKILL)
        procs[2].wait(timeout=30)
        # the rest of the import goes to the survivors (write fan-out to a
        # dead replica is best-effort; AE repairs it after restart)
        http_json(
            "POST", f"{uris[0]}/index/e2e/field/f/import",
            {"rows": [0] * (len(all_cols) - half), "cols": all_cols[half:]},
            timeout=120,
        )
        (survivor_count,) = (
            http_json(
                "POST", f"{uris[0]}/index/e2e/query",
                {"query": "Count(Row(f=0))"}, timeout=120,
            )["results"]
        )
        assert survivor_count == len(all_cols)
        # restart the killed node: its fragments reopen via snapshot + WAL
        # replay (torn tail tolerated), then AE pulls what it missed
        procs[2] = _spawn_node(base, names[2], ports[2], hosts)
        _wait_up(uris[2])
        # every node runs an AE pass: each primary pushes repairs to its
        # replicas (the ticker would do this on anti-entropy.interval)
        for u in uris:
            http_json("POST", f"{u}/internal/sync", timeout=300)
        for u in uris:
            r = http_json(
                "POST", f"{u}/index/e2e/query",
                {"query": "Count(Row(f=0))"}, timeout=120,
            )
            assert r["results"][0] == len(all_cols), u
        # attrs survived the SIGKILL + restart (append-log replay) and
        # AE propagated them with the row data — assert on EVERY node,
        # including the restarted one (its store was repaired by attr AE)
        for u in uris:
            r = http_json(
                "POST", f"{u}/index/e2e/query",
                {"query": "Row(f=0)"}, timeout=120,
            )
            assert r["results"][0].get("attrs") == {
                "label": "alpha", "rank": 7,
            }, u
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()


@pytest.mark.slow
def test_join_on_boot_subprocess():
    """A fresh `pilosa-tpu server --join <coordinator>` process
    self-registers, triggers the resize job and serves its shard subset —
    zero manual topology calls (reference: gossip join -> listenForJoins,
    cluster.go:1141,1796; VERDICT r2 #6 done-criterion)."""
    base = tempfile.mkdtemp(prefix="pilosa-join-")
    p0_port, p1_port = _free_port(), _free_port()
    uri0 = f"http://localhost:{p0_port}"
    uri1 = f"http://localhost:{p1_port}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"

    def spawn(name, port, extra):
        args = [
            sys.executable, "-m", "pilosa_tpu.cli", "server",
            "--data-dir", os.path.join(base, name),
            "--bind", f"localhost:{port}",
            "--node-id", name,
        ] + extra
        return subprocess.Popen(
            args, env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL
        )

    procs = [spawn("c0", p0_port, [])]
    try:
        _wait_up(uri0)
        http_json("POST", f"{uri0}/index/jb", {"options": {}})
        http_json(
            "POST", f"{uri0}/index/jb/field/f", {"options": {"type": "set"}}
        )
        cols = [s * SHARD_WIDTH + 2 for s in range(16)]
        http_json(
            "POST", f"{uri0}/index/jb/field/f/import",
            {"rows": [0] * len(cols), "cols": cols}, timeout=120,
        )
        procs.append(spawn("j1", p1_port, ["--join", uri0]))
        _wait_up(uri1)
        # both processes converge to the 2-node NORMAL membership
        deadline = time.time() + 120
        while time.time() < deadline:
            s0 = http_json("GET", f"{uri0}/status", timeout=5)
            s1 = http_json("GET", f"{uri1}/status", timeout=5)
            if (
                len(s0["nodes"]) == 2
                and len(s1["nodes"]) == 2
                and s0["state"] == "NORMAL"
                and s1["state"] == "NORMAL"
            ):
                break
            time.sleep(0.3)
        else:
            raise AssertionError((s0, s1))
        # the joiner serves queries over the full index (owning some shards)
        r = http_json(
            "POST", f"{uri1}/index/jb/query",
            {"query": "Count(Row(f=0))"}, timeout=120,
        )
        assert r["results"][0] == len(cols)
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()
