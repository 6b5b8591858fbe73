"""`bench_root`: the root of a tree for a test that reads BENCHMARK.json's
lists. Besides the repo's own, it is each of a few copies to which entries
were appended the way a later PR that adds a cell or a metric appends them:
in files of their own, at the end of the lists, and names at the end of a
metric's `workloads`; nothing already there moves or changes. A test that
takes `bench_root` holds on every copy, so a check that pins what comes
last, or refuses an appended entry, fails here.

The copies (`BENCHMARK.json` and `benchmarks/`, in a directory each):

- `cells.<cell>`, one for each accepted one-chip cell: that cell and the
  four-chip `ssb-sf100-x4.q32-q34`, each again under a new name with a
  configuration file of the same content, listed in every `workloads`
  list that names the cell it copies (so in every list that names all
  the accepted cells); and one per-layer metric with its file, listed for
  the two new cells alone;
- `metrics.each-cell`: for each accepted cell, one per-layer metric with
  its file and one end-to-end metric, each listed for that cell alone;
- `in-metrics`: accepted cells appended to the `workloads` of
  per-layer metrics that list others: the four-chip SSB cell to the
  one-chip SSB cell's fold and assembly metrics (the mesh path tags its
  fold too).

`appended` gives a copy with what it added: the pairs of cell and metric
that it lists and the accepted tree does not."""

import copy
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import run as harness  # noqa: E402

BENCH = harness.read_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
ONE_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]
FOUR_CHIP = "ssb-sf100-x4.q32-q34"
IN_METRICS = {FOUR_CHIP: ["groupby_fold_ms", "assembled_mb_per_query"]}


def _entry(bench, group, name):
    return next(e for e in bench[group] if e["name"] == name)


def _metric(root, bench, name, like, cells):
    """A per-layer metric `name`, with its file: a copy of `like`'s,
    listed for `cells` alone."""
    spec = harness.read_json(ROOT, "benchmarks", "metrics", like + ".json")
    with open(os.path.join(root, "benchmarks", "metrics", name + ".json"),
              "w") as f:
        json.dump(dict(spec, name=name), f, indent=2)
    bench["per_layer"].append(dict(
        _entry(bench, "per_layer", like), name=name, workloads=list(cells)))


def _cells(root, bench, copied):
    new = {}
    for source in copied:
        cell = _entry(bench, "workloads", source)
        config = _entry(bench, "configs", cell["config"])
        name = "appended-" + config["name"]
        path = f"benchmarks/configs/{name}.json"
        shutil.copyfile(os.path.join(ROOT, config["file"]),
                        os.path.join(root, path))
        bench["configs"].append(dict(config, name=name, file=path))
        new[source] = f"{name}.{cell['traffic']}"
        bench["workloads"].append(dict(cell, name=new[source], config=name))
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads")
        if listed is not None:
            listed += [new[s] for s in copied if s in listed]
    _metric(root, bench, "appended_dispatches_per_query",
            "dispatches_per_query", new.values())


def _each_cell(root, bench):
    for i, cell in enumerate(CELLS):
        _metric(root, bench, f"only_dispatches_per_query.{i}",
                "dispatches_per_query", [cell])
        bench["end_to_end"].append(dict(
            _entry(bench, "end_to_end", "latency_p95_ms"),
            name=f"only_latency_p99_ms.{i}", workloads=[cell]))


def _in_metrics(root, bench):
    for cell, names in IN_METRICS.items():
        for name in names:
            listed = _entry(bench, "per_layer", name)["workloads"]
            if cell not in listed:
                listed.append(cell)


def _reported(bench, root):
    """(cell, metric) pairs listed in `bench` and not in the accepted
    tree."""
    def pairs(b, r):
        return {(w["name"], m["name"])
                for w in b["workloads"]
                for g in ("end_to_end", "per_layer")
                for m in harness.Cell(r, w["name"]).metrics(g)}
    return pairs(bench, root) - pairs(BENCH, ROOT)


COPIES = {
    **{f"cells.{c}": lambda root, bench, c=c: _cells(root, bench, [c, FOUR_CHIP])
       for c in ONE_CHIP},
    "metrics.each-cell": _each_cell,
    "in-metrics": _in_metrics,
}
_MADE = {}


def _copy(name, tmp_path_factory):
    if name not in _MADE:
        root = str(tmp_path_factory.mktemp(name))
        shutil.copytree(os.path.join(ROOT, "benchmarks"),
                        os.path.join(root, "benchmarks"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        bench = copy.deepcopy(BENCH)
        COPIES[name](root, bench)
        with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
            json.dump(bench, f, indent=2)
        _MADE[name] = root, bench, _reported(bench, root)
    return _MADE[name]


@pytest.fixture(scope="session", params=["repo", *COPIES])
def bench_root(request, tmp_path_factory):
    if request.param == "repo":
        return ROOT
    return _copy(request.param, tmp_path_factory)[0]


@pytest.fixture(scope="session", params=list(COPIES))
def appended(request, tmp_path_factory):
    return _copy(request.param, tmp_path_factory)
