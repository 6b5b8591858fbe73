"""The benchmark harness's own parts, small, on CPU (2-3 shards, a served
`ClusterHarness` node in the program's place): cells resolve to their
files, generator + loader + reference agree with a served index, a broken
timed path and the control make `correct` false, a CPU server is refused,
and the trace and work arithmetic give the stated numbers."""

import copy
import glob
import json
import os
import re
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import control  # noqa: E402
import run as harness  # noqa: E402
from lib import trace as tracelib, work  # noqa: E402
from lib.traffic import Mix  # noqa: E402

BENCH = harness.read_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
# the entries of each metric list that this suite's checks know: where a
# check compares what two cells report, it compares these, and leaves an
# entry appended since, for one cell or for some, to the change that
# appends it
KNOWN = {"end_to_end": 4, "per_layer": 33}


def known(cell, group: str) -> set:
    """The names of `cell`'s metrics among the first KNOWN[group] of
    `group` (`end_to_end` or `per_layer`)."""
    first = {m["name"] for m in cell.bench[group][:KNOWN[group]]}
    return {m["name"] for m in cell.metrics(group)} & first

# what the profiler's device plane looks like, cut to the essentials: two
# operations that overlap for 1 ms, a gap of 6 ms, a third operation
HAND_TRACE = [{
    "name": "/device:TPU:0",
    "lines": [
        {"name": "XLA Modules", "events": [["jit_f", 0, 10_000_000]]},
        {"name": "XLA Ops", "events": [
            ["fusion.1", 0, 2_000_000],
            ["fusion.2", 1_000_000, 2_000_000],
            ["fusion.1", 9_000_000, 1_000_000],
        ]},
    ],
}]


class ServedNode:
    """What `run_cell` needs of a server, over an in-process node."""

    def __init__(self, uri):
        self.uri, self.t0 = uri, time.perf_counter()

    def control(self, command):
        return "[]" if command == "mem" else "ok"

    def cache_entries(self):
        return 0

    def stop_clean(self):
        pass


def small_cell(name, n_devices):
    cell = harness.Cell(ROOT, name)
    cell.entry = dict(cell.entry, chips=n_devices)
    cell.config = copy.deepcopy(cell.config)
    cell.config["shards"] = 3
    for f in cell.config["fields"]:
        if "rows" in f:
            f["rows"] = 12
    cell.mix = copy.deepcopy(cell.mix)
    cell.mix["warmup"]["mix_seconds"] = 0.3
    cell.mix["trace_slice_s"] = 0.3
    return cell


def served_run(name, tmp_path, trace=False, seconds=1.0):
    import jax

    from pilosa_tpu.testing import ClusterHarness

    with ClusterHarness(1, in_memory=True) as c:
        cell = small_cell(name, len(jax.devices()))
        return harness.run_cell(
            cell, seed=2**31 + 7, seconds=seconds, trace=trace,
            server=ServedNode(c[0].node.uri), work=str(tmp_path),
            require_tpu=False,
        )


def test_every_cell_resolves_and_every_name_is_legal(bench_root):
    root = bench_root
    bench = harness.read_json(root, "BENCHMARK.json")
    names = []
    for cell_name in [w["name"] for w in bench["workloads"]]:
        cell = harness.Cell(root, cell_name)
        assert cell.config["fields"] and cell.mix["templates"]
        Mix(cell.mix, lambda f, c=cell: work.field_rows(c.config, f), 1)
        for m in cell.metrics("per_layer"):
            spec = harness.read_json(root, "benchmarks", "metrics", m["name"] + ".json")
            assert (spec["layer"], spec["unit"], spec["moves"]) == (
                m["layer"], m["unit"], m["moves"])
        assert {m["name"] for m in cell.metrics("end_to_end")} >= {"setup_s", "qps"}
        names += [cell_name, cell.entry["config"], cell.entry["traffic"]]
    metrics = bench["end_to_end"] + bench["per_layer"]
    names += [m["name"] for m in metrics]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert all(m["moves"] in e2e for m in bench["per_layer"])
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("name", CELLS)
def test_cell_agrees_with_a_served_index(name, tmp_path, monkeypatch):
    """Generator, loader, warm-up, clients, read-your-writes and the numpy
    reference against a real node, then the same with `?profile=1` and
    the per-layer readers over a hand-built device trace."""
    out = served_run(name, tmp_path)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    cell = harness.Cell(ROOT, name)
    assert set(out["metrics"]) == {m["name"] for m in cell.metrics("end_to_end")}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"

    monkeypatch.setattr(tracelib, "extract", lambda d, w: HAND_TRACE)
    out = served_run(name, tmp_path, trace=True)
    assert out["correct"], out["checks"]
    listed = {m["name"] for m in cell.metrics("per_layer")}
    # no peak is known for a CPU: the roofline reader has nothing to read
    assert set(out["metrics"]) == listed - {"query_kernels_roofline"}
    assert out["device"]["busy_s"] == pytest.approx(0.004)
    assert out["metrics"]["dispatches_per_query"]["value"] > 0
    assert len(out["breakdown"]["device_ops"]) == 2


@pytest.mark.parametrize("name", CELLS)
def test_an_altered_answer_makes_correct_false(name, tmp_path, monkeypatch):
    """The timed path broken underneath: every fifth response of the
    executor carries a count that is off by one where it is produced."""
    from pilosa_tpu.exec.executor import Executor

    real = Executor.execute_response
    calls = []

    def altered(self, *args, **kwargs):
        resp = real(self, *args, **kwargs)
        calls.append(1)
        if len(calls) % 5 == 0:
            resp.results = [_off_by_one(r) for r in resp.results]
        return resp

    monkeypatch.setattr(Executor, "execute_response", altered)
    out = served_run(name, tmp_path)
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0
    assert out["failed"] >= out["checks"]["wrong_answers"]["value"]


def _off_by_one(result):
    if isinstance(result, bool):
        return result
    if isinstance(result, int):
        return result + 1
    if isinstance(result, list) and result:
        return result[:-1]
    if hasattr(result, "value"):
        result.value += 1
    elif isinstance(result, dict) and "value" in result:
        result = dict(result, value=result["value"] + 1)
    return result


@pytest.mark.parametrize("name", CELLS)
def test_the_control_comes_out_not_correct(name):
    """The reference in the program's place with a guarantee broken
    (answers over all shards but one; acknowledged writes not applied) is
    refused by the same comparison, on three seeds."""
    cell = small_cell(name, 1)
    for seed in (1, 2**31 + 5, 77):
        checks = control.control_run(cell, seed, 200, 1 << 20)
        assert checks["wrong_answers"]["value"] > 0
        assert checks["readback_wrong"]["value"] == 2
        assert checks["failed_requests"]["value"] == 0


def test_a_cpu_server_is_refused_and_a_bare_checkout_measures_nothing(tmp_path):
    cpu = {"devices": [{"id": 0, "platform": "cpu", "deviceKind": "cpu"}]}
    with pytest.raises(RuntimeError, match="not on a TPU"):
        harness.check_device(cpu)
    with pytest.raises(RuntimeError, match="not on a TPU"):
        harness.check_device({"devices": []})
    tpu = {"devices": [{"id": 0, "platform": "tpu", "deviceKind": "TPU v5 lite"}]}
    assert harness.check_device(tpu) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    with open(os.path.join(ROOT, "benchmarks", "lib", "peaks.json")) as f:
        assert "TPU v5 lite" in json.load(f)


# numpy + stdlib: the process that measures never holds the chip, and the
# yardstick shares nothing with the program (`serve.py`, the child, and
# `lib/xplane.py`, which reads the profiler's file, import jax by design)
YARDSTICK = ["run.py", "control.py"] + [
    os.path.join("lib", f) for f in (
        "pql.py", "reference.py", "work.py", "data.py", "traffic.py")
] + sorted(
    os.path.relpath(p, os.path.join(ROOT, "benchmarks")) for p in glob.glob(
        os.path.join(ROOT, "benchmarks", "lib", "dialects", "*.py"))
)


@pytest.mark.parametrize("path", YARDSTICK)
def test_the_yardstick_imports_neither_jax_nor_the_program(path):
    source = open(os.path.join(ROOT, "benchmarks", path)).read()
    assert not re.search(r"^\s*(import|from)\s+(jax|pilosa_tpu)", source, re.M)


def test_trace_reduction_on_a_hand_built_trace():
    out = tracelib.reduce(HAND_TRACE, window_s=0.010)
    assert out["busy_s"] == pytest.approx(0.004)  # 0-3 ms and 9-10 ms
    assert out["breakdown"]["device_ops"] == [
        ["jit_f/fusion.1", pytest.approx(0.003)], ["jit_f/fusion.2", pytest.approx(0.002)]]
    assert out["breakdown"]["idle_gaps"] == [["unattributed", pytest.approx(0.006)]]
    # a plane with no operation gives nothing to read, never a 0
    assert tracelib.reduce([{"name": "/device:TPU:0", "lines": []}], 1.0) is None


def test_work_gives_the_stated_bytes_for_each_template():
    seg = harness.Cell(ROOT, "segment-10b-share.count-zipf").config
    taxi = harness.Cell(ROOT, "taxi-1b.q1-q4").config
    row = 131072
    assert work.row_bytes(seg) == 149 * row and work.row_bytes(taxi) == 954 * row
    assert work.request_bytes(
        seg, "Count(Intersect(Row(seg=3),Row(seg=9)))") == 2 * 149 * row
    assert work.request_bytes(
        seg, "Count(Union(Row(seg=3),Row(seg=9),Row(seg=3)))") == 2 * 149 * row
    # a filtered Sum: its filter row, 17 planes, exists and sign
    assert work.request_rows(
        taxi, "Sum(Row(passenger_count=2), field=total_amount)") == 1 + 17 + 2
    assert work.request_rows(taxi, "TopN(cab_type, n=3)") == 3
    assert work.request_rows(
        taxi, "GroupBy(Rows(passenger_count), Rows(pickup_year), Rows(dist_miles))"
    ) == 8 + 8 + 16
    # the configuration's device rows: 3 + 8 + 8 + 16 + 19 (+ _exists)
    assert sum(work.field_rows(taxi, f["name"]) for f in taxi["fields"]) == 54
