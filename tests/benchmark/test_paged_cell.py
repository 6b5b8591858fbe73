"""What ISSUE 34 adds to the benchmark, small, on the CPU: the paged
configuration, the four-caller taxi mix and the three staging metrics load
and say what BENCHMARK.json says of them; and a run of
`segment-10b-paged.count-zipf` cut to 12 rows x 3 shards behind a budget of
4 rows pages, answers exactly, and reads all three metrics above 0 from the
tags and the counter the program emits. (Both new cells also run as cases
of `test_benchmark_harness.py`, which is parametrised over `workloads`.)
A test that reads BENCHMARK.json's lists takes `bench_root`
(`conftest.py`): the repo's tree and copies with entries appended."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import run as harness  # noqa: E402
from lib import readers, trace as tracelib  # noqa: E402

import test_benchmark_harness as small  # noqa: E402

BENCH = harness.read_json(ROOT, "BENCHMARK.json")
PAGED = "segment-10b-paged.count-zipf"
TAXI_C4 = "taxi-1b.q1-q4-c4"
NEW_METRICS = ("stage_build_ms", "stage_put_ms", "evictions_per_query")
ROW_BYTES = 131072


def entry(group, name, bench=BENCH):
    return next(e for e in bench[group] if e["name"] == name)


def test_the_paged_configuration_states_its_deployment(bench_root):
    root = bench_root
    bench = harness.read_json(root, "BENCHMARK.json")
    cell = harness.Cell(root, PAGED)
    config, listed = cell.config, entry("configs", "segment-10b-paged", bench)
    sibling = harness.Cell(root, "segment-10b-share.count-zipf").config
    assert config["source"] == listed["source"] and len(listed["source"]) <= 200
    assert sorted(config["reduced"]) == sorted(listed["reduced"])
    # the sibling's shapes, mix and guarantees; only the scale differs
    for key in ("index", "shards", "shard_width_exponent", "columns_per_shard",
                "guarantees", "published"):
        assert config[key] == sibling[key], key
    assert cell.mix == harness.Cell(root, "segment-10b-share.count-zipf").mix
    field, = config["fields"]
    assert field == dict(sibling["fields"][0], rows=1024)
    # 20.0 GB of dense device rows behind a budget that holds 659 of them
    stack = config["shards"] * ROW_BYTES
    assert field["rows"] * stack == 19_998_441_472
    budget = int(config["server"]["env"]["PILOSA_TPU_HBM_BUDGET_MB"]) << 20
    assert budget // stack == 659 and config["server"]["toml"] == {}
    # everything the server runs under is accounted for, one line each
    assert set(config["server"]["env"]) <= set(config["assumed"])
    assert all(
        k.startswith(("PILOSA_TPU_", "MALLOC_")) for k in config["server"]["env"]
    )
    assert cell.entry["chips"] == 1 and len(cell.entry["why"]) <= 200


def test_the_four_caller_mix_is_the_taxi_mix_with_four_clients(bench_root):
    root = bench_root
    one = harness.read_json(root, "benchmarks", "traffic", "q1-q4.json")
    four = harness.read_json(root, "benchmarks", "traffic", "q1-q4-c4.json")
    assert four["clients"] == 4 and four["why"] != one["why"]
    assert dict(four, clients=1, why="") == dict(one, why="")
    cell = harness.Cell(root, TAXI_C4)
    assert cell.entry["config"] == "taxi-1b" and cell.entry["chips"] == 1
    assert cell.config == harness.Cell(root, "taxi-1b.q1-q4").config
    # no p50 in a cell of four templates, as in its sibling
    assert small.known(cell, "end_to_end") == {
        "qps", "latency_p95_ms", "setup_s"}


@pytest.mark.parametrize("new, sibling", [
    (PAGED, "segment-10b-share.count-zipf"), (TAXI_C4, "taxi-1b.q1-q4")])
def test_a_new_cell_reports_what_its_sibling_reports(bench_root, new, sibling):
    """Everything its sibling reports, and the paged cell the three staging
    metrics; one appended since for the sibling alone is the appending
    change's to judge."""
    cell, other = (harness.Cell(bench_root, c) for c in (new, sibling))
    ours = {m["name"] for g in ("end_to_end", "per_layer")
            for m in cell.metrics(g)}
    theirs = {m for g in ("end_to_end", "per_layer")
              for m in small.known(other, g)}
    assert theirs | (set(NEW_METRICS) if new == PAGED else set()) <= ours


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_staging_metric_file_says_what_its_entry_says(bench_root, name):
    """The paged cell is listed first; a cell appended since that pages
    too may follow it."""
    bench = harness.read_json(bench_root, "BENCHMARK.json")
    spec = harness.read_json(
        bench_root, "benchmarks", "metrics", name + ".json")
    listed = entry("per_layer", name, bench)
    assert (spec["name"], spec["layer"], spec["unit"], spec["moves"]) == (
        name, listed["layer"], listed["unit"], listed["moves"])
    assert listed["workloads"][:1] == [PAGED] and listed["better"] == "lower"
    assert listed["layer"] == entry(
        "per_layer", "restage_mb_per_query", bench)["layer"]
    kind = spec["source"]["kind"]
    assert listed["source"] == {"span": "program_span",
                                "counter": "program_counter"}[kind]
    assert "module" not in spec["source"]  # a reducer `lib/readers.py` has


def test_a_program_without_the_tags_reads_nothing_and_raises_nothing():
    """The parent's `exec.stage` has no `stage.build_ms`: the reader gives
    0.0 for it, and None where no request came back at all."""
    spec = harness.read_json(
        ROOT, "benchmarks", "metrics", "stage_build_ms.json")

    class Ctx:
        requests = [{"roots": [{"name": "exec.stage", "tags": {
            "stage.bytes": 7}, "children": []}]}]

    assert readers.read(spec, Ctx) == 0.0
    Ctx.requests = []
    assert readers.read(spec, Ctx) is None


def test_a_run_that_pages_reads_the_three_metrics(tmp_path, monkeypatch):
    from pilosa_tpu.core.devcache import DEVICE_CACHE
    from pilosa_tpu.hbm import residency as hbm_res
    from pilosa_tpu.parallel import mesh as pmesh

    import jax

    from pilosa_tpu.testing import ClusterHarness

    seen = {}

    class Keeping(readers.Context):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            seen["ctx"] = self

    monkeypatch.setattr(readers, "Context", Keeping)
    monkeypatch.setattr(tracelib, "extract", lambda d, w: small.HAND_TRACE)
    old_mesh, old_budget = pmesh.active_mesh(), DEVICE_CACHE.budget_bytes
    with ClusterHarness(1, in_memory=True) as c:
        try:
            pmesh.set_active_mesh(None)  # one device, as in the cell
            DEVICE_CACHE.clear()
            DEVICE_CACHE.budget_bytes = 4 * 3 * ROW_BYTES  # 4 of 12 rows
            cell = small.small_cell(PAGED, len(jax.devices()))
            out = harness.run_cell(
                cell, seed=2**31 + 34, seconds=1.5, trace=True,
                server=small.ServedNode(c[0].node.uri), work=str(tmp_path),
                require_tpu=False,
            )
        finally:
            DEVICE_CACHE.budget_bytes = old_budget
            DEVICE_CACHE.clear()
            hbm_res.STAGING.clear()
            pmesh.set_active_mesh(old_mesh)
    assert out["correct"] and out["failed"] == 0, out["checks"]
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    for name in NEW_METRICS + ("restage_mb_per_query",):
        assert metrics[name] > 0, (name, metrics)
    assert metrics["compiles_per_query"] == 0
    # a miss stages 3 shards x 128 KiB; the tags lie inside their span
    stages = [s for r in seen["ctx"].requests
              for s in readers.spans(r["roots"], "exec.stage")]
    built = [s for s in stages if s["tags"]["stage.bytes"]]
    assert built and len(built) < len(seen["ctx"].requests)
    for s in built:
        tags = s["tags"]
        assert tags["stage.bytes"] == tags["stage.rows"] * ROW_BYTES
        assert tags["stage.rows"] % 3 == 0
        assert tags["stage.build_ms"] > 0 and tags["stage.put_ms"] > 0
        assert tags["stage.build_ms"] + tags["stage.put_ms"] \
            <= s["durationMs"] + 0.002
    per_request = sum(
        s["tags"]["stage.build_ms"] for s in stages
    ) / len(seen["ctx"].requests)
    assert metrics["stage_build_ms"] == pytest.approx(per_request)
