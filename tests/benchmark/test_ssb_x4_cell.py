"""The cell `ssb-sf100-x4.q32-q34`, small, on the CPU: its configuration
loads through the `ssb` dialect and states a four-chip deployment, the
rollups of the city fields and of the month agree, the three templates'
reference answers equal a numpy group-by written here, the cell is listed
where a mesh cell reports, and a run cut to a few shards over an
in-process node comes out correct. (The cell also runs as a case
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

import run as harness  # noqa: E402
from lib import readers, work  # noqa: E402
from lib.data import Data  # noqa: E402
from lib.reference import Reference  # noqa: E402
from lib.traffic import Mix  # noqa: E402

import test_benchmark_harness as small  # noqa: E402

BENCH = harness.read_json(ROOT, "BENCHMARK.json")
CELL = "ssb-sf100-x4.q32-q34"
ROW_BYTES = 131072
SHARDS = 4  # of a served run
# of the reference alone: SF 100's own, the least at which Q3.4 (one
# month, two cities each way: 8e-7 of the columns) finds groups
REF_SHARDS = 573
SEED = 2**31 + 41
SSB = "ssb-sf100.flights-q3-q4"
# the group tally's two metrics of this cell: what each file reads, and the
# cells listed first for it
TALLY_METRICS = {
    "mesh_tally_pct": ({"module": "meshwork", "reduce": "mesh_tally_pct"},
                       [CELL]),
    "groupby_prune_ms": ({"kind": "span", "span": "exec.dispatch",
                          "tag": "groupby.prune_ms",
                          "reduce": "mean_tag_per_request"}, [SSB, CELL]),
}


def entry(group, name, bench=BENCH):
    return next(e for e in bench[group] if e["name"] == name)


def small_cell(n_devices, shards=SHARDS):
    """The cell over a few shards: every shape of the configuration kept
    (fields, rows, hierarchy, value range, the three texts)."""
    cell = harness.Cell(ROOT, CELL)
    cell.entry = dict(cell.entry, chips=n_devices)
    # the devices the node holds stand for the four chips
    cell.config = dict(copy.deepcopy(cell.config), shards=shards,
                       chips=n_devices)
    cell.mix = copy.deepcopy(cell.mix)
    cell.mix["warmup"]["mix_seconds"] = 0.3
    cell.mix["trace_slice_s"] = 0.3
    return cell


@pytest.fixture(scope="module")
def ssb():
    cell = small_cell(1, REF_SHARDS)
    data = Data(cell.config, SEED, 1 << 20, cell.dialect)
    return cell, data, Reference(data)


def test_the_configuration_states_a_four_chip_deployment(bench_root):
    root = bench_root
    bench = harness.read_json(root, "BENCHMARK.json")
    cell = harness.Cell(root, CELL)
    config, listed = cell.config, entry("configs", "ssb-sf100-x4", bench)
    assert config["source"] == listed["source"] and len(listed["source"]) <= 200
    assert config["source"] != entry("configs", "ssb-sf100", bench)["source"]
    assert sorted(config["reduced"]) == sorted(listed["reduced"])
    assert config["dialect"] == "ssb" and config["architecture"] is None
    assert cell.dialect.forms == ("GroupBy(aggregate=)",)
    # SF 100, as ssb-sf100: nothing of the scale is cut
    sibling = harness.Cell(root, "ssb-sf100.flights-q3-q4").config
    for key in ("shards", "shard_width_exponent", "columns_per_shard"):
        assert config[key] == sibling[key]
    assert config["published"]["lineorder_rows"] == 600_037_902
    # one host of four chips as the 2 x 2 mesh, the budget stated globally
    x4 = harness.Cell(root, "segment-10b-x4.count-zipf").config
    assert (config["chips"], config["chip_kind"], config["mesh"]) == (
        x4["chips"], x4["chip_kind"], x4["mesh"])
    assert config["server"]["env"] == x4["server"]["env"]
    assert config["server"]["toml"] == {"cache": {"result-mb": 0}}
    assert set(config["server"]["env"]) <= set(config["assumed"])
    assert cell.entry["chips"] == 4 and len(cell.entry["why"]) <= 200
    assert small.known(cell, "end_to_end") == {
        "qps", "latency_p95_ms", "setup_s"}
    rows = {f["name"]: work.field_rows(config, f["name"], cell.dialect)
            for f in config["fields"]}
    assert rows == {
        "d_yearmonth": 84, "d_year": 7, "c_city": 250, "c_nation": 25,
        "c_region": 5, "s_city": 250, "s_nation": 25, "s_region": 5,
        "lo_revenue": 26}
    assert work.row_bytes(config) == 573 * ROW_BYTES


def test_a_city_stack_is_over_the_dispatch_cap_and_half_of_it_is_not():
    """A quarter of the stated budget (the executor's `_stack_guard`) holds
    neither city field over 573 shards, and each shard half of it."""
    config = harness.Cell(ROOT, CELL).config
    cap = int(config["server"]["env"]["PILOSA_TPU_HBM_BUDGET_MB"]) * 2**20 // 4
    assert 573 * ROW_BYTES * 250 > cap >= 287 * ROW_BYTES * 250
    # and the whole of what the three texts stage fits the budget: both
    # cities and the years in two chunks, 26 planes, 13 filter rows
    staged = (2 * 250 + 7 + 26 + 13) * 574 * ROW_BYTES
    assert staged < 4 * cap


def test_the_rollups_agree_with_their_finer_fields(ssb):
    cell, data, _ = ssb
    for side in ("c", "s"):
        city = data.fields[f"{side}_city"]["labels"]
        for coarse, divide in ((f"{side}_nation", 10), (f"{side}_region", 50)):
            for r in range(data.n_rows(coarse)):
                assert (data.row_mask(coarse, r) == (city // divide == r)).all()
        # a region is also its nations' union: city // 50 = (city // 10) // 5
        for r in range(5):
            union = np.logical_or.reduce(
                [data.row_mask(f"{side}_nation", 5 * r + k) for k in range(5)])
            assert (data.row_mask(f"{side}_region", r) == union).all()
    months = data.fields["d_yearmonth"]["labels"]
    for y in range(7):
        assert (data.row_mask("d_year", y) == (months // 12 == y)).all()
    # 1998 ends in July: its last five months hold nothing
    assert not np.isin(months, np.arange(79, 84)).any()


def _numpy_group_by(data, text_name: str) -> dict:
    """Q3.2-Q3.4 written again from the arrays: the filter, then one
    group per distinct (c_city, s_city, d_year) with its count and sum."""
    c = data.fields["c_city"]["labels"].astype(np.int64)
    s = data.fields["s_city"]["labels"].astype(np.int64)
    ym = data.fields["d_yearmonth"]["labels"].astype(np.int64)
    year = ym // 12
    kings = np.isin(c, (191, 195)) & np.isin(s, (191, 195))
    keep = {
        "q32": (c // 10 == 9) & (s // 10 == 9) & (year <= 5),
        "q33": kings & (year <= 5),
        "q34": kings & (ym == 71),
    }[text_name]
    rev = data.fields["lo_revenue"]
    out = {}
    for i in np.nonzero(keep)[0]:
        key = (int(c[i]), int(s[i]), int(year[i]))
        n, total = out.get(key, (0, 0))
        out[key] = (n + 1, total + (int(rev["values"][i]) if rev["has"][i] else 0))
    return dict(sorted(out.items()))


def test_the_three_templates_equal_a_numpy_group_by(ssb):
    cell, data, ref = ssb
    texts = {t["name"]: t["pql"] for t in cell.mix["templates"]}
    assert list(texts) == ["q32", "q33", "q34"]
    for name, text in texts.items():
        want = _numpy_group_by(data, name)
        assert ref.answer(text) == want and want, name
    # Q3.2 is US x US cities, Q3.3 / Q3.4 the two UNITED KINGDOM ones
    assert {k[:2] for k in ref.answer(texts["q32"])} <= {
        (a, b) for a in range(90, 100) for b in range(90, 100)}
    assert {k[2] for k in ref.answer(texts["q34"])} == {5}


def test_request_rows_of_the_three_texts_equal_a_hand_count(ssb):
    cell, _, _ = ssb
    rows = {t["name"]: work.request_rows(cell.config, t["pql"], cell.dialect)
            for t in cell.mix["templates"]}
    # Rows: 250 + 250 + 7; planes 24 + 2; Row: 2 nations + 6 years, or
    # 4 cities + 6 years, or 4 cities + 1 month
    assert rows == {"q32": 507 + 26 + 8, "q33": 507 + 26 + 10,
                    "q34": 507 + 26 + 5}


def test_the_warm_up_sends_each_text_twice(ssb):
    cell, data, _ = ssb
    mix = Mix(cell.mix, data.n_rows, 7)
    texts = [t["pql"] for t in cell.mix["templates"]]
    assert cell.dialect.warmup_requests(mix) == texts * 2
    assert [t["weight"] * cell.mix["block"] for t in cell.mix["templates"]] \
        == [1.0, 1.0, 1.0]


# the cells that stood before this one, and where it was appended
OLDER = ["segment-10b-share.count-zipf", "taxi-1b.q1-q4",
         "segment-10b-x4.count-zipf", "taxi-1b.q1-q4-c4",
         "segment-10b-paged.count-zipf", "ssb-sf100.flights-q3-q4"]


def test_the_new_cell_is_listed_where_a_mesh_cell_reports(bench_root):
    """Every metric this suite knows whose list names all six older cells,
    and the mesh's three, list this one; the one-chip roofline (whose
    reader would read 4 x the truth here), the cache metrics (the cell runs
    with the cache off), and the staging and eviction metrics (nothing
    pages under a mesh) do not. The cell stands where it was appended, after the
    six, whatever is appended after it; and the four-chip cells stay within
    the rule for them: at most half of all cells, rounded down, or one."""
    bench = harness.read_json(bench_root, "BENCHMARK.json")
    names = [w["name"] for w in bench["workloads"]]
    assert names[:6] == OLDER and names[6] == CELL
    for m in bench["per_layer"][:small.KNOWN["per_layer"]]:
        listed = m.get("workloads")
        if listed is not None and set(OLDER) <= set(listed):
            assert CELL in listed, m["name"]
    for name in ("mesh_kernels_roofline", "collective_share_pct",
                 "sharded_dispatch_pct", *TALLY_METRICS):
        assert CELL in entry("per_layer", name, bench)["workloads"]
    for name in ("query_kernels_roofline", "cache_hit_pct", "cache_lookup_ms",
                 "stage_build_ms", "stage_put_ms", "evictions_per_query"):
        assert CELL not in entry("per_layer", name, bench)["workloads"], name
    assert entry("workloads", CELL, bench)["chips"] == 4
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(len(names) // 2, 1)


@pytest.mark.parametrize("name", TALLY_METRICS)
def test_a_tally_metric_file_says_what_its_entry_says(bench_root, name):
    """Layer and `moves` as `groupby_fold_ms`; the cells named here come
    first in its list, and a cell appended since may follow them."""
    bench = harness.read_json(bench_root, "BENCHMARK.json")
    spec = harness.read_json(
        bench_root, "benchmarks", "metrics", name + ".json")
    listed, fold = entry("per_layer", name, bench), entry(
        "per_layer", "groupby_fold_ms", bench)
    source, first = TALLY_METRICS[name]
    assert (spec["name"], spec["layer"], spec["unit"], spec["moves"]) == (
        name, listed["layer"], listed["unit"], listed["moves"])
    assert (listed["layer"], listed["moves"]) == (fold["layer"], fold["moves"])
    assert listed["workloads"][:len(first)] == first
    assert spec["source"] == source  # a reducer `lib/` has
    assert listed["source"] == (
        "program_span" if source.get("kind") == "span" else "program_counter")
    assert (listed["unit"], listed["better"]) in (
        ("%", "higher"), ("ms", "lower"))


def test_the_tally_readers_give_the_mesh_share_and_the_prune_time():
    """The mesh's share is of all the GroupBy tallies, the kernels' and
    XLA's: a mesh tally is counted among the kernel's too, so a tally that
    fell back to the one-device kernel lowers it. A program without the
    counter reads nothing, one without the tag 0.0."""
    mesh = harness.read_json(ROOT, "benchmarks", "metrics", "mesh_tally_pct.json")
    prune = harness.read_json(
        ROOT, "benchmarks", "metrics", "groupby_prune_ms.json")

    class Ctx:
        config = {"chips": 4}
        before = {"groupby.mesh_tallies": 10, "groupby.xla_tallies": 3,
                  "groupby.kernel_tallies": 10}
        after = {"groupby.mesh_tallies": 22, "groupby.xla_tallies": 3,
                 "groupby.kernel_tallies": 22}
        # the filter's plan, then the tally; the second prunes nothing
        requests = [{"roots": [{"name": "http.request", "tags": {},
                                "children": [
            {"name": "exec.dispatch", "tags": {"plan.family": "stacked"},
             "children": []},
            {"name": "exec.dispatch", "tags": {"plan.family": "groupby",
                                               "groupby.prune_ms": p},
             "children": []}]}]} for p in (6.0, 0.0)]

    assert readers.read(mesh, Ctx) == 100.0
    assert readers.read(prune, Ctx) == pytest.approx(3.0)
    Ctx.after = dict(Ctx.after, **{"groupby.xla_tallies": 7})
    assert readers.read(mesh, Ctx) == pytest.approx(75.0)
    # four tallies fell back to the one-device kernel: mesh / (mesh + XLA)
    # would still read 100
    Ctx.after = {"groupby.mesh_tallies": 22, "groupby.xla_tallies": 3,
                 "groupby.kernel_tallies": 26}
    assert readers.read(mesh, Ctx) == pytest.approx(75.0)
    Ctx.after = {"groupby.mesh_tallies": 10, "groupby.xla_tallies": 3,
                 "groupby.kernel_tallies": 10}  # no tally in the window
    assert readers.read(mesh, Ctx) is None
    Ctx.after = {"groupby.xla_tallies": 7,  # a program without the counter
                 "groupby.kernel_tallies": 10}
    assert readers.read(mesh, Ctx) is None
    Ctx.requests = [{"roots": [{"name": "exec.dispatch", "tags": {},
                                "children": []}]}]
    assert readers.read(prune, Ctx) == 0.0


def served_run(tmp_path, trace=False):
    import jax

    from pilosa_tpu.testing import ClusterHarness

    with ClusterHarness(1, in_memory=True) as c:
        return harness.run_cell(
            small_cell(len(jax.devices())), seed=SEED, seconds=1.5,
            trace=trace, server=small.ServedNode(c[0].node.uri),
            work=str(tmp_path), require_tpu=False,
        )


def test_the_small_cell_runs_correct_on_every_device(
    tmp_path, monkeypatch
):
    from lib import trace as tracelib

    out = served_run(tmp_path)
    assert out["correct"] and out["failed"] == 0, out["checks"]
    assert out["attempted"] >= 3
    monkeypatch.setattr(tracelib, "extract", lambda d, w: small.HAND_TRACE)
    out = served_run(tmp_path, trace=True)
    assert out["correct"], out["checks"]
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    # the filter's plan and the tallies: two spans a request, all of them
    # over every device the node holds
    assert metrics["dispatches_per_query"] == 2.0
    assert metrics["sharded_dispatch_pct"] == 100.0
    # every text prunes under its filter; a CPU mesh tallies with XLA
    assert metrics["groupby_prune_ms"] > 0
    assert metrics["mesh_tally_pct"] == 0.0
