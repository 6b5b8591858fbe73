"""The benchmark takes an appended cell and metric without an edit. Every
test of `tests/benchmark/` that reads BENCHMARK.json's lists takes the
`bench_root` fixture (`conftest.py`), and so runs on the repo's tree and
on each copy to which cells and metrics were appended the way a later PR
appends them. What is checked here is the copies themselves: that each
changed nothing but appends, and that what it appended is listed where it
should be."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import run as harness  # noqa: E402

BENCH = harness.read_json(ROOT, "BENCHMARK.json")


def _appended_to(now: dict, was: dict) -> bool:
    """`now` is `was` with names appended to its `workloads` at most."""
    rest = {k: v for k, v in now.items() if k != "workloads"}
    listed = was.get("workloads")
    return rest == {k: v for k, v in was.items() if k != "workloads"} and (
        listed is None and "workloads" not in now
        or now["workloads"][:len(listed)] == listed)


def test_the_copy_only_appends(appended):
    """What the copy changed is what a later PR may change: entries at the
    end of the lists, names at the end of a metric's `workloads`, and new
    files. Every list that named all the accepted cells names the cells
    appended too, and each pair of cell and metric that the copy adds is
    one that `Cell.metrics` finds."""
    root, bench, reported = appended
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        was = BENCH[group]
        assert all(map(_appended_to, bench[group][:len(was)], was)), group
    assert reported
    accepted = [w["name"] for w in BENCH["workloads"]]
    names = [w["name"] for w in bench["workloads"]]
    assert names[:len(accepted)] == accepted
    for m in bench["end_to_end"] + bench["per_layer"]:
        if set(accepted) <= set(m.get("workloads", [])):
            assert m["workloads"][len(accepted):] == names[len(accepted):]
    for cell, metric in reported:
        assert metric in {m["name"] for g in ("end_to_end", "per_layer")
                          for m in harness.Cell(root, cell).metrics(g)}
