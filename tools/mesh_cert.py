#!/usr/bin/env python
"""Mesh-group certification on large virtual meshes (ISSUE 10).

Parent mode spawns one hermetic child per device count (16 and 32 by
default — bigger than the 8-device tier-1 mesh) with
`--xla_force_host_platform_device_count` forced before JAX initializes.
Each child boots a REAL 4-node in-process cluster sharing one ICI domain
(`[mesh] group`), drives PQL through the coordinator's HTTP-facing api
layer, and certifies:

- a mesh-local `Count(Intersect(Row, Row))` executes with EXACTLY one
  compiled dispatch and one blocking host read (plan.STATS counters),
  with exactly one mesh-group dispatch and zero HTTP fallbacks;
- every certified query shape is bit-identical across the mesh-group
  path, the HTTP fan-out path (mesh disabled per node), and a host-side
  truth model (python sets over the imported positions);
- warm per-query wall time for the mesh path vs the HTTP fan-out path
  (`meshN_count_ms` / `httpN_count_ms`: virtual CPU devices, so counts
  of nothing anyone deploys).

The parent writes MULTICHIP_r06.json; CI uploads it as an artifact.
Run locally: `python tools/mesh_cert.py --out MULTICHIP_r06.json`.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def child(n_devices: int) -> dict:
    from pilosa_tpu.utils.cpuonly import force_cpu

    force_cpu(n_devices)

    import numpy as np

    from pilosa_tpu.exec import meshgroup
    from pilosa_tpu.exec import plan as planmod
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.testing import ClusterHarness

    rng = np.random.default_rng(10)
    n_shards = n_devices * 2 + 1  # deliberately unpadded
    out: dict = {"n_devices": n_devices, "n_shards": n_shards, "nodes": 4}

    # cache_result_mb=0: the cert counter-asserts the DISPATCH shape of
    # repeat queries; a result-cache hit (the intended fast path) would
    # serve them with zero dispatches and certify nothing
    with ClusterHarness(
        4, in_memory=True, mesh_group="cert-ici",
        telemetry_sample_interval=0.0, cache_result_mb=0,
    ) as cluster:
        api = cluster[0].api
        api.create_index("cert")
        api.create_field("cert", "f")
        api.create_field(
            "cert", "v", options={"type": "int", "min": -500, "max": 500}
        )
        cols = {}
        # rows 1/2 drawn from a 4-shard window (dense enough that the
        # certified intersection is nonzero — a trivially-empty result
        # would certify nothing), row 3 over the full column space so
        # every node owns live shards. Volumes stay modest on purpose:
        # the virtual-device collectives schedule 32 participants onto
        # ~2 CI cores, so the cert certifies correctness + counters, not
        # throughput.
        window = min(4, n_shards) * SHARD_WIDTH
        for r, hi in ((1, window), (2, window), (3, n_shards * SHARD_WIDTH)):
            c = rng.integers(0, hi, 4000).astype(np.uint64)
            api.import_bits("cert", "f", np.full(len(c), r, np.uint64), c)
            cols[r] = set(c.tolist())
        vcols = np.unique(
            rng.integers(0, n_shards * SHARD_WIDTH, 2000).astype(np.uint64)
        )
        vvals = rng.integers(-500, 501, len(vcols)).astype(np.int64)
        api.import_values("cert", "v", vcols, vvals)

        def set_mesh(on: bool) -> None:
            for node in cluster.nodes:
                node.executor.mesh_min_nodes = 2 if on else 0

        # --- acceptance counters: 1 dispatch + 1 blocking read ----------
        set_mesh(True)
        api.query("cert", "Count(Intersect(Row(f=1), Row(f=2)))")  # warm
        planmod.reset_stats()
        meshgroup.reset_stats()
        (got_i,) = api.query("cert", "Count(Intersect(Row(f=1), Row(f=2)))")
        snap = meshgroup.stats_snapshot()
        out["count_intersect"] = int(got_i)
        out["dispatches"] = planmod.STATS["evals"]
        out["host_reads"] = planmod.STATS["host_reads"]
        out["mesh_dispatches"] = snap["dispatches"]
        out["mesh_local_shards"] = snap["local_shards"]
        out["mesh_fallbacks"] = snap["fallbacks"]
        assert planmod.STATS["evals"] == 1, planmod.STATS
        assert planmod.STATS["host_reads"] == 1, planmod.STATS
        assert snap["dispatches"] == 1 and snap["fallbacks"] == 0, snap
        assert got_i == len(cols[1] & cols[2]), (got_i, len(cols[1] & cols[2]))

        # --- differential equivalence: mesh vs HTTP vs host truth -------
        want_gt = sum(1 for x in vvals if x > 100)
        shapes = [
            ("Count(Intersect(Row(f=1), Row(f=2)))", len(cols[1] & cols[2])),
            ("Count(Union(Row(f=1), Row(f=2)))", len(cols[1] | cols[2])),
            ("Count(Difference(Row(f=1), Row(f=3)))", len(cols[1] - cols[3])),
            ("Count(Xor(Row(f=2), Row(f=3)))", len(cols[2] ^ cols[3])),
            ("Count(Row(v > 100))", want_gt),
        ]
        for pql, truth in shapes:
            set_mesh(True)
            (mesh_r,) = api.query("cert", pql)
            set_mesh(False)
            (http_r,) = api.query("cert", pql)
            assert mesh_r == http_r == truth, (pql, mesh_r, http_r, truth)
        for pql in ("TopN(f, n=3)", "TopN(f, Row(f=2), n=3)"):
            set_mesh(True)
            (mesh_p,) = api.query("cert", pql)
            set_mesh(False)
            (http_p,) = api.query("cert", pql)
            assert [(p.id, p.count) for p in mesh_p] == [
                (p.id, p.count) for p in http_p
            ], (pql, mesh_p, http_p)
        # BSI aggregate shapes (round 11, plane-streamed lowering): mesh
        # == HTTP == host truth, and each warm mesh aggregate is exactly
        # ONE compiled dispatch + ONE scalar-sized blocking host read
        # however many devices the group spans
        want_min = int(min(vvals))
        want_max = int(max(vvals))
        bsi_shapes = [
            ("Sum(field=v)", (int(vvals.sum()), len(vvals))),
            ("Min(field=v)", (want_min, int((vvals == want_min).sum()))),
            ("Max(field=v)", (want_max, int((vvals == want_max).sum()))),
        ]
        for pql, (want_v, want_c) in bsi_shapes:
            set_mesh(True)
            api.query("cert", pql)  # warm: stage + compile
            planmod.reset_stats()
            meshgroup.reset_stats()
            (mesh_vc,) = api.query("cert", pql)
            snap = meshgroup.stats_snapshot()
            assert planmod.STATS["evals"] == 1, (pql, planmod.STATS)
            assert planmod.STATS["host_reads"] == 1, (pql, planmod.STATS)
            assert snap["dispatches"] == 1 and snap["fallbacks"] == 0, (
                pql, snap,
            )
            set_mesh(False)
            (http_vc,) = api.query("cert", pql)
            assert (mesh_vc.value, mesh_vc.count) == (want_v, want_c), (
                pql, mesh_vc, want_v, want_c,
            )
            assert (http_vc.value, http_vc.count) == (want_v, want_c), (
                pql, http_vc,
            )
        # streamed Range count: the traced-predicate program, 1 dispatch
        set_mesh(True)
        api.query("cert", "Count(Row(v > 99))")  # warm the program shape
        planmod.reset_stats()
        (got_r,) = api.query("cert", "Count(Row(v > 100))")
        assert got_r == want_gt, (got_r, want_gt)
        assert planmod.STATS["evals"] == 1, planmod.STATS
        assert planmod.STATS["host_reads"] == 1, planmod.STATS
        out["bsi_shapes"] = len(bsi_shapes) + 1
        out["equivalence_shapes"] = len(shapes) + 2 + len(bsi_shapes) + 1

        # --- warm latency: mesh fold vs HTTP fan-out --------------------
        def median_ms(fn, n: int = 5) -> float:
            ts = []
            for _ in range(n):
                t0 = time.perf_counter()
                fn()
                ts.append((time.perf_counter() - t0) * 1e3)
            ts.sort()
            return ts[len(ts) // 2]

        pql = "Count(Intersect(Row(f=1), Row(f=2)))"
        set_mesh(True)
        api.query("cert", pql)  # warm stacks + compile under this mode
        out["mesh_count_ms"] = round(
            median_ms(lambda: api.query("cert", pql)), 3
        )
        set_mesh(False)
        api.query("cert", pql)
        out["http_count_ms"] = round(
            median_ms(lambda: api.query("cert", pql)), 3
        )
    out["ok"] = True
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", type=int, help="internal: run one device count")
    ap.add_argument(
        "--devices", type=int, nargs="*", default=[16, 32],
        help="virtual device counts to certify (parent mode)",
    )
    ap.add_argument("--out", default=None, help="write the JSON report here")
    args = ap.parse_args()

    if args.child:
        print(json.dumps(child(args.child)))
        return 0

    report: dict = {"rounds": []}
    ok = True
    for n in args.devices:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--child", str(n)],
                capture_output=True, text=True, timeout=2400, env=env,
                cwd=REPO_ROOT,
            )
            if proc.returncode != 0:
                ok = False
                report["rounds"].append({
                    "n_devices": n, "ok": False,
                    "tail": (proc.stderr or proc.stdout)[-2000:],
                })
            else:
                report["rounds"].append(
                    json.loads(proc.stdout.strip().splitlines()[-1])
                )
        except Exception as e:  # noqa: BLE001 - report, don't crash CI silently
            ok = False
            report["rounds"].append(
                {"n_devices": n, "ok": False, "tail": f"{type(e).__name__}: {e}"}
            )
    report["ok"] = ok
    text = json.dumps(report, indent=2)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
