"""What ISSUE 39 adds to the benchmark: six per-layer metric files, each
read by a reducer `lib/readers.py` already has, listed for all six
accepted cells. The process-runtime three read counters the parent does
not publish (nothing, and no error, there); the admission three read tags
the parent's api.admit span does not carry (0.0 there). A test that
reads BENCHMARK.json's lists takes `bench_root` (`conftest.py`): the
repo's tree and copies with entries appended."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as harness  # noqa: E402
from lib import readers  # noqa: E402

import test_benchmark_harness as small  # noqa: E402

BENCH = harness.read_json(ROOT, "BENCHMARK.json")
COUNTERS = {"gc_pause_ms": "runtime.gc_pause_ms",
            "gc_full_pause_ms": "runtime.gc_full_pause_ms",
            "ticker_ms": "runtime.tick_ms"}
TAGS = {"admit_estimate_ms": "admit.estimate_ms",
        "admit_merge_walk_ms": "admit.merge_walk_ms",
        "admit_grant_ms": "admit.grant_ms"}
RUNTIME_LAYER = "process runtime (CPython collector, server/node.py tickers)"
# the six in the order they were appended, and how many per_layer entries
# stood before them then
SIX = list(COUNTERS) + list(TAGS)
FOLLOWED = 25


def entry(name, bench=BENCH):
    return next(e for e in bench["per_layer"] if e["name"] == name)


def spec(name, root=ROOT):
    return harness.read_json(root, "benchmarks", "metrics", name + ".json")


@pytest.mark.parametrize("name", sorted(COUNTERS) + sorted(TAGS))
def test_a_new_metric_file_says_what_its_entry_says(bench_root, name):
    bench = harness.read_json(bench_root, "BENCHMARK.json")
    s, listed = spec(name, bench_root), entry(name, bench)
    assert (s["name"], s["layer"], s["unit"], s["moves"]) == (
        name, listed["layer"], listed["unit"], listed["moves"])
    assert listed["moves"] == "qps" and listed["better"] == "lower"
    # every cell, those appended since included: a cell appends its name
    assert listed["workloads"] == [w["name"] for w in bench["workloads"]]
    assert "module" not in s["source"]  # a reducer `lib/readers.py` has
    if name in COUNTERS:
        assert s["source"] == {"kind": "counter", "stat": COUNTERS[name],
                               "reduce": "delta_per_request"}
        assert (listed["source"], listed["layer"]) == (
            "program_counter", RUNTIME_LAYER)
    else:
        assert s["source"] == {"kind": "span", "span": "api.admit",
                               "tag": TAGS[name],
                               "reduce": "mean_tag_per_request"}
        assert listed["source"] == "program_span"
        assert listed["layer"] == entry("admit_ms", bench)["layer"]


def test_the_new_entries_come_last_and_every_cell_reports_them(bench_root):
    """The six are one run, in their order, where they were appended:
    whatever is appended after them leaves them there."""
    bench = harness.read_json(bench_root, "BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]]
    assert names[FOLLOWED:FOLLOWED + 6] == SIX
    for cell in bench["workloads"]:
        got = {m["name"] for m in
               harness.Cell(bench_root, cell["name"]).metrics("per_layer")}
        assert set(SIX) <= got


class _Ctx:
    """Two requests, as a program with or without this PR gives them."""

    def __init__(self, after, admit_tags):
        self.before = {"exec.compiles": 4, "runtime.gc_pause_ms": 10.0}
        self.after = dict(after, **{"exec.compiles": 4})
        self.requests = [
            {"roots": [{"name": "http.request", "tags": {}, "children": [
                {"name": "api.admit", "tags": dict(admit_tags),
                 "children": []}]}]},
            {"roots": [{"name": "http.request", "tags": {}, "children": [
                {"name": "api.admit", "tags": dict(admit_tags),
                 "children": []}]}]},
        ]


def test_a_parent_without_the_counters_or_tags_raises_nothing():
    parent = _Ctx({}, {"sched.class": "interactive", "sched.wait_ms": 0.0})
    for name in COUNTERS:
        assert readers.read(spec(name), parent) is None
    for name in TAGS:
        assert readers.read(spec(name), parent) == 0.0


def test_the_readers_give_per_request_deltas_and_tag_means():
    ctx = _Ctx(
        {"runtime.gc_pause_ms": 14.0, "runtime.gc_full_pause_ms": 3.0,
         "runtime.tick_ms": 1.0},  # run.py sums tick_ms over its labels
        {"admit.estimate_ms": 0.5, "admit.merge_walk_ms": 0.25,
         "admit.grant_ms": 0.125})
    got = {n: readers.read(spec(n), ctx) for n in list(COUNTERS) + list(TAGS)}
    assert got == pytest.approx({
        "gc_pause_ms": 2.0, "gc_full_pause_ms": 1.5, "ticker_ms": 0.5,
        "admit_estimate_ms": 0.5, "admit_merge_walk_ms": 0.25,
        "admit_grant_ms": 0.125})


def test_a_small_traced_run_reads_all_six(tmp_path, monkeypatch):
    from lib import trace as tracelib

    monkeypatch.setattr(tracelib, "extract", lambda d, w: small.HAND_TRACE)
    out = small.served_run("segment-10b-share.count-zipf", tmp_path,
                           trace=True)
    assert out["correct"], out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    for name in list(COUNTERS) + list(TAGS):
        assert m[name] >= 0, name
    assert m["gc_full_pause_ms"] <= m["gc_pause_ms"]
    assert m["admit_merge_walk_ms"] <= m["admit_estimate_ms"]
    assert m["admit_estimate_ms"] + m["admit_grant_ms"] <= m["admit_ms"] + 0.003
