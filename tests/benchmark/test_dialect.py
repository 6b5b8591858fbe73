"""A deployment the base language cannot say, brought as files alone
(ISSUE 36): in `tmp_path`, a configuration that names a dialect, the
dialect (one field kind the base does not draw: `set/sparse`, a few bits a
row kept as sorted column indexes; one query form the base does not answer:
`GroupBy(..., previous=[...])`), a mix with templates that draw from two
fields, and the `BENCHMARK.json` entries. `run_cell` over an in-process
node comes out correct; with an answer altered underneath, not correct;
its control fails as it must. Nothing under `benchmarks/` is written, and
a dialect that reaches for what the base answers is refused."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import control  # noqa: E402
import run as harness  # noqa: E402
from lib import dialects, pql, work  # noqa: E402
from lib.data import Data  # noqa: E402
from lib.reference import Reference  # noqa: E402
from lib.traffic import Mix  # noqa: E402

import test_benchmark_harness as small  # noqa: E402

DIALECT = '''
"""The shop's own: tags are sparse (a few columns a row), and the catalogue
page is a GroupBy resumed after the last group the caller saw."""

import numpy as np

from lib.data import encode_roaring

FORMS = ("GroupBy(previous=)",)
KINDS = ("set/sparse",)


# -- the field kind --------------------------------------------------------

def draw(data, spec, rng):
    held = [np.sort(rng.choice(data.n, size=spec["bits"], replace=False))
            for _ in range(spec["rows"])]
    return {"spec": spec, "rows": spec["rows"], "held": held}


def row_mask(data, f, rid):
    m = np.zeros(data.n, dtype=bool)
    m[f["held"][rid]] = True
    return m


def field_options(spec):
    return {"type": "set", "cacheType": "ranked", "cacheSize": 1000}


def field_rows(spec):
    return spec["rows"]


def importer(data, name, f):
    def one(http_, s):
        lo, hi = s * data.per_shard, (s + 1) * data.per_shard
        frag_pos = np.sort(np.concatenate([
            rid * data.shard_width + data.pos[s, idx[(idx >= lo) & (idx < hi)] - lo]
            for rid, idx in enumerate(f["held"])
        ]))
        if len(frag_pos):
            out = http_.call(
                "POST", f"/index/{data.index}/field/{name}/import-roaring/{s}",
                encode_roaring(frag_pos))
            assert out["changed"] == len(frag_pos), out

    return one


# -- the query form --------------------------------------------------------

def _fields(call):
    return [rows.children[0] for rows in call.children]


def answer(ref, call):
    previous = call.args["previous"]
    after = tuple(previous[:-1]) + (previous[-1] + 1,)
    filt = call.args.get("filter")
    mask = ref.mask(filt) if filt is not None else ref.visible
    return {key: int(np.count_nonzero(m))
            for key, m in ref.groups(_fields(call), mask) if key >= after}


def normalise(call, result):
    return {tuple(m["rowID"] for m in g["group"]): g["count"] for g in result}


def served_form(call, answer):
    return [{"group": [{"field": f, "rowID": r}
                       for f, r in zip(_fields(call), key)], "count": n}
            for key, n in answer.items()]


def request_rows(config, call, base_rows):
    return base_rows(call._replace(
        args={k: v for k, v in call.args.items() if k != "previous"}))


# -- warm-up: the base rule, and the first page of the catalogue ------------

def warmup_requests(mix):
    return mix.warmup_requests() + ["GroupBy(Rows(colour), Rows(size), previous=[0, 0])"]
'''

CONFIG = {
    "source": "tests/benchmark/test_dialect.py: a shop's catalogue, made up",
    "dialect": "shop",
    "index": "shop",
    "shards": 3,
    "shard_width_exponent": 20,
    "columns_per_shard": 2048,
    "fields": [
        {"name": "colour", "type": "set", "membership": "one_of",
         "shares": [0.4, 0.3, 0.2, 0.1]},
        {"name": "size", "type": "set", "membership": "one_of",
         "shares": [0.5, 0.3, 0.2]},
        {"name": "tag", "type": "set", "membership": "sparse", "rows": 24,
         "bits": 300},
        {"name": "price", "type": "int", "min": 0, "max": 5000, "share": 0.9},
    ],
    "server": {"toml": {}, "env": {}},
    "guarantees": {"read_your_writes": {
        "field": "colour", "set_row": 1, "import_row": 0, "import_columns": 64}},
}

MIX = {
    "loop": "closed", "clients": 1, "block": 4, "order": "fixed",
    "templates": [
        {"name": "tagged", "weight": 0.25,
         "pql": "Count(Intersect(Row(tag=$t), Row(colour=$c)))"},
        {"name": "two_tags", "weight": 0.25,
         "pql": "Count(Union(Row(tag=$t), Row(tag=$u), Row(size=$s)))"},
        {"name": "page", "weight": 0.25,
         "pql": "GroupBy(Rows(colour), Rows(size), previous=[$c, $s])"},
        {"name": "cheap", "weight": 0.25,
         "pql": "GroupBy(Rows(colour), filter=Row(price < 1000), previous=[$c])"},
    ],
    "variables": {
        "t": {"field": "tag", "draw": "zipf", "s": 1.0},
        "u": {"field": "tag", "draw": "zipf", "s": 1.0},
        "c": {"field": "colour", "draw": "uniform"},
        "s": {"field": "size", "draw": "uniform"},
    },
    "warmup": {"mix_seconds": 0.3},
    "trace_slice_s": 0.3,
}


def deployment(tmp_path, n_devices, dialect_source=DIALECT) -> harness.Cell:
    """The shop's files under `tmp_path`, and its cell."""
    bench = harness.read_json(ROOT, "BENCHMARK.json")
    for sub in ("configs", "traffic", os.path.join("lib", "dialects")):
        os.makedirs(tmp_path / "benchmarks" / sub)
    (tmp_path / "benchmarks" / "configs" / "shop.json").write_text(json.dumps(CONFIG))
    (tmp_path / "benchmarks" / "traffic" / "browse.json").write_text(json.dumps(MIX))
    (tmp_path / "benchmarks" / "lib" / "dialects" / "shop.py").write_text(
        dialect_source)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(dict(
        bench,
        configs=[{"name": "shop", "source": CONFIG["source"], "reduced": [],
                  "file": "benchmarks/configs/shop.json", "why": "a test"}],
        workloads=[{"name": "shop.browse", "config": "shop", "traffic": "browse",
                    "chips": n_devices, "why": "a test"}],
        per_layer=[m for m in bench["per_layer"] if "workloads" not in m],
    )))
    return harness.Cell(str(tmp_path), "shop.browse")


def served_run(tmp_path):
    import jax

    from pilosa_tpu.testing import ClusterHarness

    before = sorted(os.listdir(os.path.join(ROOT, "benchmarks", "lib", "dialects")))
    with ClusterHarness(1, in_memory=True) as c:
        cell = deployment(tmp_path, len(jax.devices()))
        out = harness.run_cell(
            cell, seed=2**31 + 36, seconds=1.0, trace=False,
            server=small.ServedNode(c[0].node.uri), work=str(tmp_path),
            require_tpu=False,
        )
    assert before == sorted(
        os.listdir(os.path.join(ROOT, "benchmarks", "lib", "dialects")))
    return out


def test_the_deployment_runs_correct_from_its_own_files(tmp_path):
    out = served_run(tmp_path)
    assert out["correct"] and out["failed"] == 0, out["checks"]
    assert out["attempted"] >= 8  # every template, more than once
    assert set(out["metrics"]) >= {"qps", "latency_p95_ms", "setup_s"}


def test_an_altered_answer_under_the_dialect_makes_correct_false(tmp_path, monkeypatch):
    from pilosa_tpu.exec.executor import Executor

    real, calls = Executor.execute_response, []

    def altered(self, *args, **kwargs):
        resp = real(self, *args, **kwargs)
        calls.append(1)
        if len(calls) % 3 == 0:
            resp.results = [small._off_by_one(r) for r in resp.results]
        return resp

    monkeypatch.setattr(Executor, "execute_response", altered)
    out = served_run(tmp_path)
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0


def test_the_deployments_control_comes_out_not_correct(tmp_path):
    cell = deployment(tmp_path, 1)
    for seed in (1, 2**31 + 5, 77):
        checks = control.control_run(cell, seed, 200, 1 << 20)
        assert checks["wrong_answers"]["value"] > 0
        assert checks["readback_wrong"]["value"] == 2
        assert checks["failed_requests"]["value"] == 0


def test_the_dialects_forms_kinds_and_warm_up_are_what_the_harness_uses(tmp_path):
    cell = deployment(tmp_path, 1)
    assert cell.dialect.forms == ("GroupBy(previous=)",)
    data = Data(cell.config, 7, 1 << 20, cell.dialect)
    ref = Reference(data)
    assert data.n_rows("tag") == 24
    assert all(data.row_mask("tag", r).sum() == 300 for r in range(24))
    # the page after (1, 0): every group of the plain GroupBy from (1, 1) on
    whole = ref.answer("GroupBy(Rows(colour), Rows(size))")
    page = ref.answer("GroupBy(Rows(colour), Rows(size), previous=[1, 0])")
    assert page == {k: n for k, n in whole.items() if k >= (1, 1)} and page
    served = ref.served_form("GroupBy(Rows(colour), previous=[0])",
                             ref.answer("GroupBy(Rows(colour), previous=[0])"))
    assert [g["group"][0]["rowID"] for g in served] == [1, 2, 3]
    # work: the dialect's rule for its form, its kind's rows under the base's
    assert work.request_rows(
        cell.config, "GroupBy(Rows(colour), filter=Row(price < 9), previous=[1])",
        cell.dialect) == 4 + 13 + 2
    assert work.request_rows(
        cell.config, "TopN(tag, Row(colour=1), n=5)", cell.dialect) == 24 + 1
    with pytest.raises(dialects.Unknown, match="no dialect is loaded"):
        work.request_rows(cell.config, "TopN(tag, n=5)")
    # warm-up: the dialect's list, which holds the base rule's
    mix = Mix(cell.mix, data.n_rows, 7)
    warm = cell.dialect.warmup_requests(mix)
    assert warm[:-1] == mix.warmup_requests() and "previous=[0, 0]" in warm[-1]
    assert dialects.NONE.warmup_requests(mix) == mix.warmup_requests()
    declines = dialects.Dialect("d", types.SimpleNamespace(
        warmup_requests=lambda mix: None))
    assert declines.warmup_requests(mix) == mix.warmup_requests()


def test_warm_up_walks_every_field_a_template_draws_from(tmp_path):
    cell = deployment(tmp_path, 1)
    mix = Mix(cell.mix, lambda f: work.field_rows(cell.config, f, cell.dialect), 3)
    warm = mix.warmup_requests()
    named = set()
    for text in warm:
        named |= pql.row_refs(pql.parse(text))
    assert named >= {("tag", r) for r in range(24)} \
        | {("colour", r) for r in range(4)} | {("size", r) for r in range(3)}
    # two_tags walks `tag` two rows a step (12 steps) and `size` round and
    # round beside it; tagged, the first to draw from `colour`, walks its 4
    # rows; the others are sent once
    by_template = {t["name"]: 0 for t in MIX["templates"]}
    for text in warm:
        call = pql.parse(text)
        key = ("tagged" if "Intersect" in text else "two_tags" if "Union" in text
               else "cheap" if "filter" in call.args else "page")
        by_template[key] += 1
    assert by_template == {"tagged": 4, "two_tags": 12, "page": 1, "cheap": 1}
    assert warm[1] == "Count(Union(Row(tag=0), Row(tag=1), Row(size=0)))"
    assert warm[-1] == "Count(Union(Row(tag=22), Row(tag=23), Row(size=2)))"


@pytest.mark.parametrize("declares, message", [
    ('FORMS = ("Count",)', "declares Count, which the base language answers"),
    ('FORMS = ("GroupBy(filter=)",)', r"declares GroupBy\(filter=\)"),
    ('FORMS = ("Row(colour=)",)', r"declares Row\(colour=\)"),
    ('FORMS = ("Group By",)', "is no form"),
    ('KINDS = ("int",)', "declares the field kind 'int'"),
    ("import jax", "imports jax or pilosa_tpu"),
    ("from pilosa_tpu.exec import executor", "imports jax or pilosa_tpu"),
])
def test_a_dialect_that_reaches_for_the_base_is_refused(tmp_path, declares, message):
    with pytest.raises(ValueError, match=message):
        deployment(tmp_path, 1, dialect_source=declares + "\n")


def test_a_form_nobody_declares_raises_by_name(tmp_path):
    cell = deployment(tmp_path, 1)
    ref = Reference(Data(cell.config, 7, 1 << 20, cell.dialect))
    with pytest.raises(dialects.Unknown, match=r"GroupBy\(aggregate=\).*'shop' does not declare"):
        ref.answer("GroupBy(Rows(colour), aggregate=Sum(field=price))")
    with pytest.raises(dialects.Unknown, match=r"Rows\(limit=\)"):
        ref.answer("GroupBy(Rows(colour, limit=2))")
    with pytest.raises(dialects.Unknown, match="declares .* but has no `mask`"):
        dialects.Dialect("d", types.SimpleNamespace(FORMS=("Shift",))).hook(
            "mask", "Shift")
