"""What ISSUE 37 adds to the benchmark, small, on the CPU: the `ssb-sf100`
configuration, its dialect (`lib/dialects/ssb.py`: the field kind
`set/rollup` and the query form `GroupBy(aggregate=)`), the mix
`flights-q3-q4` and the two metric files say what BENCHMARK.json says of
them; a run of `ssb-sf100.flights-q3-q4` cut to a few shards over an
in-process node comes out correct, with one `sum` altered underneath not
correct, and its control fails as it must. (The cell also runs as a case
of `test_benchmark_harness.py`, which is parametrised over `workloads`.)
A test that reads BENCHMARK.json's lists takes `bench_root`
(`conftest.py`): the repo's tree and copies with entries appended."""

import copy
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import control  # noqa: E402
import run as harness  # noqa: E402
from lib import readers, work  # noqa: E402
from lib.data import Data  # noqa: E402
from lib.reference import Reference  # noqa: E402
from lib.traffic import Mix  # noqa: E402

import test_benchmark_harness as small  # noqa: E402

BENCH = harness.read_json(ROOT, "BENCHMARK.json")
CELL = "ssb-sf100.flights-q3-q4"
NEW_METRICS = ("groupby_fold_ms", "assembled_mb_per_query")
ROW_BYTES = 131072
SHARDS = 4  # of a served run; the reference alone takes REF_SHARDS
REF_SHARDS = 64  # enough columns that every group of Q4.2 holds some


def entry(group, name, bench=BENCH):
    return next(e for e in bench[group] if e["name"] == name)


def small_ssb(n_devices):
    """The cell over a few shards: every shape of the configuration kept
    (fields, rows, hierarchy, value ranges, the five texts)."""
    cell = harness.Cell(ROOT, CELL)
    cell.entry = dict(cell.entry, chips=n_devices)
    cell.config = dict(copy.deepcopy(cell.config), shards=SHARDS)
    cell.mix = copy.deepcopy(cell.mix)
    cell.mix["warmup"]["mix_seconds"] = 0.3
    cell.mix["trace_slice_s"] = 0.3
    return cell


@pytest.fixture(scope="module")
def ssb():
    cell = small_ssb(1)
    cell.config["shards"] = REF_SHARDS
    data = Data(cell.config, 2**31 + 37, 1 << 20, cell.dialect)
    return cell, data, Reference(data)


def test_the_configuration_states_its_deployment(bench_root):
    root = bench_root
    bench = harness.read_json(root, "BENCHMARK.json")
    cell = harness.Cell(root, CELL)
    config, listed = cell.config, entry("configs", "ssb-sf100", bench)
    assert config["source"] == listed["source"] and len(listed["source"]) <= 200
    assert sorted(config["reduced"]) == sorted(listed["reduced"])
    assert config["dialect"] == "ssb" and config["architecture"] is None
    assert cell.dialect.forms == ("GroupBy(aggregate=)",)
    assert cell.dialect.kinds == ("set/rollup",)
    # SF 100: 600 037 902 lineorder rows in shards of 2^20
    assert config["shards"] == -(-config["published"]["lineorder_rows"] // 2**20)
    # 143 dense device rows with _exists: 10.74 GB, 63 % of the chip's limit
    rows = {f["name"]: work.field_rows(config, f["name"], cell.dialect)
            for f in config["fields"]}
    assert rows == {
        "d_year": 7, "c_nation": 25, "c_region": 5, "s_nation": 25,
        "s_region": 5, "p_category": 25, "p_mfgr": 5, "lo_revenue": 26,
        "lo_supplycost": 19}
    assert (sum(rows.values()) + 1) * work.row_bytes(config) == 10_739_908_608
    assert work.row_bytes(config) == 573 * ROW_BYTES
    # the result cache is off, and everything the server runs under is
    # accounted for
    assert config["server"]["toml"] == {"cache": {"result-mb": 0}}
    assert set(config["server"]["env"]) <= set(config["assumed"])
    # read-your-writes on a field no rollup derives from
    ryw = config["guarantees"]["read_your_writes"]["field"]
    assert ryw not in {f.get("of") for f in config["fields"]}
    assert cell.entry["chips"] == 1 and len(cell.entry["why"]) <= 200
    # no p50 in a cell of five fixed templates, as in taxi's
    assert small.known(cell, "end_to_end") == {
        "qps", "latency_p95_ms", "setup_s"}


def test_the_cell_reports_what_taxis_cell_reports_and_its_two_metrics(
        bench_root):
    """Taxi's metrics and its own two; one listed for it since (as
    `groupby_prune_ms`), or for taxi's cell alone, is the appending
    change's to judge."""
    ours = {m["name"] for m in
            harness.Cell(bench_root, CELL).metrics("per_layer")}
    taxi = small.known(harness.Cell(bench_root, "taxi-1b.q1-q4"), "per_layer")
    assert set(NEW_METRICS) <= ours - taxi and taxi <= ours


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_file_says_what_its_entry_says(bench_root, name):
    """The SSB cell is listed first; a cell appended since that reports the
    metric too may follow it."""
    bench = harness.read_json(bench_root, "BENCHMARK.json")
    spec = harness.read_json(
        bench_root, "benchmarks", "metrics", name + ".json")
    listed = entry("per_layer", name, bench)
    assert (spec["name"], spec["layer"], spec["unit"], spec["moves"]) == (
        name, listed["layer"], listed["unit"], listed["moves"])
    assert listed["workloads"][:1] == [CELL] and listed["better"] == "lower"
    sibling = {"groupby_fold_ms": "query_kernels_roofline",
               "assembled_mb_per_query": "restage_mb_per_query"}[name]
    assert listed["layer"] == entry("per_layer", sibling, bench)["layer"]
    assert listed["source"] == {"span": "program_span", "counter":
                                "program_counter"}[spec["source"]["kind"]]
    assert "module" not in spec["source"]  # a reducer `lib/readers.py` has


def test_a_program_without_the_tag_or_the_counter_raises_nothing():
    """The parent has neither: the span reader gives 0.0 (no tag on its
    exec.dispatch), the counter reader nothing at all."""
    fold = harness.read_json(ROOT, "benchmarks", "metrics", "groupby_fold_ms.json")
    asm = harness.read_json(
        ROOT, "benchmarks", "metrics", "assembled_mb_per_query.json")

    class Ctx:
        requests = [{"roots": [{"name": "exec.dispatch", "tags": {
            "plan.family": "groupby"}, "children": []}]}]
        before, after = {"exec.compiles": 1}, {"exec.compiles": 1}

    assert readers.read(fold, Ctx) == 0.0
    assert readers.read(asm, Ctx) is None
    Ctx.after = {"groupby.assembled_bytes": 3_000_000}
    assert readers.read(asm, Ctx) == pytest.approx(3.0)


def test_a_rollup_row_is_the_union_of_its_five_finer_rows(ssb):
    cell, data, _ = ssb
    for coarse, fine in (("c_region", "c_nation"), ("s_region", "s_nation"),
                         ("p_mfgr", "p_category")):
        assert data.n_rows(coarse) == 5 and data.n_rows(fine) == 25
        for r in range(5):
            union = np.logical_or.reduce(
                [data.row_mask(fine, 5 * r + k) for k in range(5)])
            assert (data.row_mask(coarse, r) == union).all() and union.any()
    # and a seed's stream is what it was without the rollups: they draw
    # nothing from it
    plain = dict(cell.config, fields=[
        f for f in cell.config["fields"] if f.get("membership") != "rollup"])
    other = Data(plain, 2**31 + 37, 1 << 20, cell.dialect)
    for name in ("d_year", "c_nation", "p_category"):
        assert (other.fields[name]["labels"] == data.fields[name]["labels"]).all()
    assert (other.fields["lo_supplycost"]["values"]
            == data.fields["lo_supplycost"]["values"]).all()


def test_the_five_filters_leave_exactly_their_groups(ssb):
    cell, data, ref = ssb
    texts = {t["name"]: t["pql"] for t in cell.mix["templates"]}
    assert list(texts) == ["q31", "q41r", "q41c", "q42r", "q42c"]
    groups = {name: ref.answer(text) for name, text in texts.items()}
    assert {n: len(g) for n, g in groups.items()} == {
        "q31": 150, "q41r": 35, "q41c": 35, "q42r": 100, "q42c": 100}
    # Q3.1: ASIA's five nations both ways, 1992-1997
    assert set(groups["q31"]) == {
        (c, s, y) for c in range(10, 15) for s in range(10, 15) for y in range(6)}
    assert set(groups["q42r"]) == {
        (y, s, p) for y in (5, 6) for s in range(5, 10) for p in range(10)}
    # a profit is two requests over the same groups, count for count
    for r, c in (("q41r", "q41c"), ("q42r", "q42c")):
        assert {k: v[0] for k, v in groups[r].items()} == {
            k: v[0] for k, v in groups[c].items()}
        assert all(groups[r][k][1] > groups[c][k][1] for k in groups[r])
    # one group by hand: its columns, and the sum of their revenues
    key = (12, 10, 3)
    m = (data.row_mask("c_nation", 12) & data.row_mask("s_nation", 10)
         & data.row_mask("d_year", 3))
    revenue = data.fields["lo_revenue"]
    assert groups["q31"][key] == (
        int(m.sum()), int(revenue["values"][m & revenue["has"]].sum()))
    assert 0 <= revenue["values"].min() and revenue["values"].max() <= 10_494_950


def test_request_rows_of_the_five_texts_equal_a_hand_count(ssb):
    cell, _, _ = ssb
    rows = {t["name"]: work.request_rows(cell.config, t["pql"], cell.dialect)
            for t in cell.mix["templates"]}
    assert rows == {
        # Rows: 25 + 25 + 7; Row: 2 regions + 6 years; planes: 24 + 2
        "q31": 57 + 8 + 26,
        # Rows: 7 + 25; Row: 2 regions + 2 manufacturers; planes 26 / 17 + 2
        "q41r": 32 + 4 + 26, "q41c": 32 + 4 + 19,
        # Rows: 7 + 25 + 25; Row: 2 regions + 2 years + 2 manufacturers
        "q42r": 57 + 6 + 26, "q42c": 57 + 6 + 19}
    assert work.request_bytes(
        cell.config, cell.mix["templates"][0]["pql"], cell.dialect
    ) == 91 * REF_SHARDS * ROW_BYTES
    # the same call without the argument is the base rule's
    plain = cell.mix["templates"][1]["pql"].replace(
        ", aggregate=Sum(field=lo_revenue)", "")
    assert work.request_rows(cell.config, plain, cell.dialect) == 36


def test_the_warm_up_sends_each_text_twice_and_the_forms_round_trip(ssb):
    cell, data, ref = ssb
    mix = Mix(cell.mix, data.n_rows, 7)
    texts = [t["pql"] for t in cell.mix["templates"]]
    assert cell.dialect.warmup_requests(mix) == texts * 2
    assert mix.warmup_requests() == texts  # the base rule: once
    for text in texts:
        want = ref.answer(text)
        served = ref.served_form(text, want)
        assert all(set(g) == {"group", "count", "sum"} for g in served)
        assert ref.normalise(text, served) == want
        # a program that reads past aggregate= answers counts alone: that
        # compares unequal and raises nothing
        counts_only = [{k: v for k, v in g.items() if k != "sum"} for g in served]
        got = ref.normalise(text, counts_only)
        assert got != want and set(got) == set(want)


def served_run(tmp_path, trace=False, one_device=False):
    """`one_device`: the node's stacks on one device, as on the cell's one
    chip, where a CPU node otherwise forms a mesh of all it holds."""
    import jax

    from pilosa_tpu.parallel import mesh as pmesh
    from pilosa_tpu.testing import ClusterHarness

    old_mesh = pmesh.active_mesh()
    with ClusterHarness(1, in_memory=True) as c:
        try:
            if one_device:
                pmesh.set_active_mesh(None)
            return harness.run_cell(
                small_ssb(len(jax.devices())), seed=2**31 + 37, seconds=1.5,
                trace=trace, server=small.ServedNode(c[0].node.uri),
                work=str(tmp_path), require_tpu=False,
            )
        finally:
            pmesh.set_active_mesh(old_mesh)


def test_the_small_cell_runs_correct_and_reads_its_two_metrics(
    tmp_path, monkeypatch
):
    from lib import trace as tracelib

    out = served_run(tmp_path)
    assert out["correct"] and out["failed"] == 0, out["checks"]
    assert out["attempted"] >= 5
    assert set(out["metrics"]) == {"qps", "latency_p95_ms", "setup_s"}
    monkeypatch.setattr(tracelib, "extract", lambda d, w: small.HAND_TRACE)
    out = served_run(tmp_path, trace=True)
    assert out["correct"], out["checks"]
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert metrics["groupby_fold_ms"] > 0 and metrics["groupby_prune_ms"] > 0
    assert metrics["assembled_mb_per_query"] >= 0  # a CPU node's mesh: none
    # the filter's plan and the tallies: two spans a request
    assert metrics["dispatches_per_query"] == 2.0
    assert metrics["compiles_per_query"] == 0


def test_the_one_device_aggregate_path_tags_its_prunes(tmp_path, monkeypatch):
    """With no mesh, as on the cell's one chip, every text's GroupBy prunes
    each dimension against its filter and its exec.dispatch says how long
    that took: `groupby_prune_ms` has something to read in this cell too."""
    from lib import trace as tracelib

    monkeypatch.setattr(tracelib, "extract", lambda d, w: small.HAND_TRACE)
    out = served_run(tmp_path, trace=True, one_device=True)
    assert out["correct"], out["checks"]
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert metrics["groupby_prune_ms"] > 0
    assert "mesh_tally_pct" not in metrics  # not listed for this cell


def test_one_sum_altered_underneath_makes_correct_false(tmp_path, monkeypatch):
    from pilosa_tpu.exec.executor import Executor

    real, calls = Executor.execute_response, []

    def altered(self, *args, **kwargs):
        resp = real(self, *args, **kwargs)
        calls.append(1)
        groups = resp.results[0] if resp.results else None
        if len(calls) % 4 == 0 and isinstance(groups, list) and groups \
                and getattr(groups[0], "sum", None) is not None:
            groups[len(groups) // 2].sum += 1  # counts and groups untouched
        return resp

    monkeypatch.setattr(Executor, "execute_response", altered)
    out = served_run(tmp_path)
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0
    assert out["checks"]["failed_requests"]["value"] == 0


def test_the_control_comes_out_not_correct():
    cell = small_ssb(1)
    for seed in (1, 2**31 + 5, 77):
        checks = control.control_run(cell, seed, 100, 1 << 20)
        assert checks["wrong_answers"]["value"] >= 99  # nearly every answer
        assert checks["readback_wrong"]["value"] == 2
        assert checks["failed_requests"]["value"] == 0
