"""`segment-10b-x4.count-zipf` at a small size on the CPU: the configuration
cut to a few shards x 12 rows, served over HTTP by a node whose stacks lie
on a 4-device 2 x 2 mesh (what a four-chip host forms), agrees with the
numpy reference on every template of `count-zipf` and on both read-backs,
and every dispatch says where it ran; the same index with no mesh gives the
same answers from one device (the tie between the one-chip and the
four-chip share); and `lib/meshwork.py`'s arithmetic on hand-built traces.
"""

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as harness  # noqa: E402
from lib import meshwork, readers, work  # noqa: E402
from lib.data import Data, Http, create_schema, load  # noqa: E402
from lib.reference import Reference  # noqa: E402
from lib.traffic import Mix  # noqa: E402

import test_benchmark_harness as small  # noqa: E402

CELL = "segment-10b-x4.count-zipf"
SIBLING = "segment-10b-share.count-zipf"
SEED = 2**31 + 28
PER_TEMPLATE = 4  # requests of each template that reached the device


def cut_config(shards: int) -> dict:
    config = copy.deepcopy(harness.Cell(ROOT, CELL).config)
    config["shards"] = shards
    config["fields"][0]["rows"] = 12
    return config


def serve(shards: int, mesh_devices: int):
    """Serve the cut configuration from one node whose active mesh spans
    `mesh_devices` devices (0: none), send `count-zipf` with `?profile=1`
    until every template has dispatched `PER_TEMPLATE` times, then the two
    read-backs. Returns ([(template, text, answer, reference's answer,
    [tags of its exec.dispatch spans])], read-backs wrong, `mesh.devices`
    gauge)."""
    import jax

    from pilosa_tpu.parallel import mesh as pmesh
    from pilosa_tpu.testing import ClusterHarness

    config = cut_config(shards)
    mix_spec = harness.Cell(ROOT, CELL).mix
    old_mesh = pmesh.active_mesh()
    with ClusterHarness(1, in_memory=True) as c:
        # after the start: activate_default_mesh would take all 8 devices
        pmesh.set_active_mesh(
            pmesh.make_mesh(jax.devices()[:mesh_devices]) if mesh_devices
            else None
        )
        try:
            uri = c[0].node.uri
            http_ = Http(uri)
            info = http_.call("GET", "/info")
            data = Data(config, SEED, info["shardWidth"])
            ref = Reference(data)
            create_schema(http_, config)
            load(uri, data)
            stream = Mix(mix_spec, data.n_rows, SEED).stream(0)
            path = f"/index/{data.index}/query?profile=1"
            out, dispatched = [], {t["name"]: 0 for t in mix_spec["templates"]}
            for _ in range(400):
                if min(dispatched.values()) >= PER_TEMPLATE:
                    break
                template, text = next(stream)
                body = http_.call("POST", path, text)
                tags = [
                    s["tags"] for s in
                    readers.spans(body["profile"]["roots"], "exec.dispatch")
                ]
                dispatched[template] += bool(tags)
                out.append((template, text, body["results"][0],
                            ref.answer(text), tags))
            assert min(dispatched.values()) >= PER_TEMPLATE, dispatched
            wrong = harness.write_then_read(http_, data, ref)
            gauge = harness.counters(http_)["mesh.devices"]
            http_.close()
        finally:
            pmesh.set_active_mesh(old_mesh)
    return out, wrong, gauge


def test_four_device_mesh_and_one_device_give_the_reference_answers():
    on_mesh, wrong_mesh, gauge_mesh = serve(6, 4)
    on_one, wrong_one, gauge_one = serve(6, 0)
    assert wrong_mesh == 0 and wrong_one == 0
    assert (gauge_mesh, gauge_one) == (4, 0)
    for template, text, got, want, tags in on_mesh:
        assert got == want, (template, text)
        for t in tags:
            assert t["mesh.devices"] == 4, t
            assert t["mesh.axes"] == "shards=2,cols=2", t
            assert "dispatch.read_ms" in t and "dispatch.eval_ms" in t
    for template, text, got, want, tags in on_one:
        assert got == want, (template, text)
        for t in tags:
            assert t["mesh.devices"] == 1 and "mesh.axes" not in t, t
    # the same seed sends the same requests: answer for answer the same
    shared = min(len(on_mesh), len(on_one))
    assert [r[:3] for r in on_mesh[:shared]] == [r[:3] for r in on_one[:shared]]


@pytest.mark.parametrize("shards", [5, 7])
def test_shard_counts_the_mesh_does_not_divide_answer_exactly(shards):
    """596 is even; a share that is not is zero-padded to the "shards"
    axis (`parallel/mesh.put_stack`) and the pad counts nothing."""
    answers, wrong, _ = serve(shards, 4)
    assert wrong == 0
    for template, text, got, want, tags in answers:
        assert got == want, (template, text)
        assert all(t["mesh.devices"] == 4 for t in tags)


# -- lib/meshwork.py on hand-built traces ------------------------------------


def plane(i: int, ops: list) -> dict:
    """A device plane with `ops` = [(instruction, start_ns, duration_ns)]."""
    return {
        "name": f"/device:TPU:{i}",
        "lines": [
            {"name": "XLA Modules", "events": [["jit__eval_jit(1)", 0, 10_000_000]]},
            {"name": "XLA Ops", "events": [list(op) for op in ops]},
        ],
    }


def context(config: dict, planes: list, devices=(4, 4, 4)) -> readers.Context:
    """Three intersects answered inside a 10 ms slice, each with one
    `exec.dispatch` that spanned `devices[i]` devices."""
    text = "Count(Intersect(Row(seg=3),Row(seg=9)))"
    answered = [
        (harness.Record("intersect", text, 0.001 * i, 0.001 * i + 0.0005, 200, b""),
         {"profile": {"roots": [{
             "name": "exec.dispatch", "durationMs": 1.0, "children": [],
             "tags": {"mesh.devices": n, "dispatch.read_ms": 0.25},
         }]}})
        for i, n in enumerate(devices)
    ]
    return readers.Context(
        config=config, answered=answered, before={}, after={},
        kind="TPU v5 lite", require_peak=True, slice_=(0.0, 0.010),
        planes=planes,
    )


def test_per_chip_roofline_is_a_quarter_of_the_one_chip_reading():
    config = harness.Cell(ROOT, CELL).config
    busy = [("%fusion.1", 0, 1_000_000)]
    ctx = context(config, [plane(i, busy) for i in range(4)])
    one_chip = readers.read(
        harness.read_json(ROOT, "benchmarks", "metrics", "query_kernels_roofline.json"),
        ctx,
    )
    per_chip = readers.read(
        harness.read_json(ROOT, "benchmarks", "metrics", "mesh_kernels_roofline.json"),
        ctx,
    )
    # three requests x 2 rows x 596 shards x 128 KiB in 1 ms of mean busy
    assert one_chip == pytest.approx(
        100.0 * 3 * 2 * 596 * 131072 / 819e9 / 0.001)
    assert per_chip == pytest.approx(one_chip / 4)
    # a CPU server has no peak of its own: the deployment's chip stands in
    ctx.peak = None
    assert meshwork.per_chip_roofline_pct(ctx, 4) == pytest.approx(per_chip)
    assert meshwork.read({"reduce": "per_chip_roofline_pct"}, ctx) == pytest.approx(per_chip)


def test_collective_share_reads_the_stated_length_and_zero_without_one():
    config = harness.Cell(ROOT, CELL).config
    spec = harness.read_json(ROOT, "benchmarks", "metrics", "collective_share_pct.json")
    with_one = [("%fusion.1", 0, 3_000_000), ("%all-reduce.1", 3_000_000, 1_000_000)]
    ctx = context(config, [plane(i, with_one) for i in range(4)])
    assert readers.read(spec, ctx) == pytest.approx(25.0)
    split = [("%fusion.1", 0, 2_000_000),
             ("%all-reduce-start", 2_000_000, 500_000),
             ("%all-reduce-done", 2_500_000, 500_000),
             ("%all-gather.2", 5_000_000, 1_000_000)]
    ctx = context(config, [plane(i, split) for i in range(4)])
    assert readers.read(spec, ctx) == pytest.approx(50.0)
    none = [("%fusion.1", 0, 3_000_000), ("%reduce.7", 3_000_000, 1_000_000)]
    ctx = context(config, [plane(i, none) for i in range(4)])
    assert readers.read(spec, ctx) == 0.0
    assert not meshwork.is_collective("jit_f/%fusion.all-reduce")


def test_the_psum_of_a_shard_map_is_a_collective():
    """`jax.shard_map`'s all-reduce keeps the name `psum` in the trace (the
    group tally's sum over "cols"); an ordinary fusion is none."""
    assert meshwork.is_collective("jit__group_counts_mesh/%psum.3")
    assert meshwork.is_collective("jit__group_counts_mesh/psum")
    assert not meshwork.is_collective("jit__group_counts_mesh/%fusion.3")
    assert not meshwork.is_collective("jit__group_counts_mesh/%group_counts.1")
    config = harness.Cell(ROOT, CELL).config
    spec = harness.read_json(ROOT, "benchmarks", "metrics", "collective_share_pct.json")
    ops = [("%group_counts.1", 0, 3_000_000), ("%psum.3", 3_000_000, 1_000_000)]
    ctx = context(config, [plane(i, ops) for i in range(4)])
    assert readers.read(spec, ctx) == pytest.approx(25.0)


def test_sharded_dispatch_share_and_what_reads_nothing():
    config = harness.Cell(ROOT, CELL).config
    spec = harness.read_json(ROOT, "benchmarks", "metrics", "sharded_dispatch_pct.json")
    busy = [plane(0, [("%fusion.1", 0, 1_000_000)])]
    assert readers.read(spec, context(config, busy)) == 100.0
    assert readers.read(spec, context(config, busy, devices=(4, 1, 4))) == pytest.approx(200 / 3)
    # a program from before the tag: nothing to read, and no error
    ctx = context(config, busy)
    for r in ctx.requests:
        for s in r["roots"]:
            del s["tags"]["mesh.devices"]
    assert readers.read(spec, ctx) is None
    # a configuration that states no chips (the one-chip cells)
    sibling = harness.Cell(ROOT, SIBLING).config
    assert readers.read(spec, context(sibling, busy)) is None
    read_ms = harness.read_json(ROOT, "benchmarks", "metrics", "dispatch_read_ms.json")
    assert readers.read(read_ms, context(config, busy)) == pytest.approx(0.25)


def test_the_configuration_fills_four_chips_as_the_sibling_fills_one(bench_root):
    cell = harness.Cell(bench_root, CELL)
    sibling = harness.Cell(bench_root, SIBLING)
    config = cell.config
    assert config["chips"] == cell.entry["chips"] == 4
    assert config["mesh"]["shards"] * config["mesh"]["cols"] == config["chips"]
    assert config["shards"] == 4 * sibling.config["shards"]
    assert cell.entry["traffic"] == sibling.entry["traffic"]
    with open(os.path.join(bench_root, "benchmarks", "lib", "peaks.json")) as f:
        peak = json.load(f)[config["chip_kind"]]
    per_chip = (
        work.field_rows(config, "seg") * work.row_bytes(config) / config["chips"]
    )
    assert 0.50 <= per_chip / peak["hbm_bytes"] <= 0.75
    # the budget is stated for the four chips together (global bytes)
    budget = int(config["server"]["env"]["PILOSA_TPU_HBM_BUDGET_MB"]) << 20
    assert budget == 4 * (
        int(sibling.config["server"]["env"]["PILOSA_TPU_HBM_BUDGET_MB"]) << 20)
    assert budget > per_chip * config["chips"]
    # every per-layer metric of the sibling but the one-chip roofline, and
    # the mesh's three; one appended for either cell alone is the
    # appending change's to judge
    mine = {m["name"] for m in cell.metrics("per_layer")}
    assert small.known(sibling, "per_layer") - mine == {"query_kernels_roofline"}
    assert mine >= {
        "mesh_kernels_roofline", "collective_share_pct", "sharded_dispatch_pct"}
