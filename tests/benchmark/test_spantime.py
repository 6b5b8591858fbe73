"""`benchmarks/lib/spantime.py` on hand-built span trees: the per-layer
times add up to the client's wall time, a request with none of a metric's
spans counts 0.0, a program without the `http.request` root reads nothing,
and the metric files that name the reader are well-formed."""

import glob
import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from lib import readers, spantime  # noqa: E402

METRICS = {}
for path in glob.glob(os.path.join(ROOT, "benchmarks", "metrics", "*.json")):
    with open(path) as f:
        spec = json.load(f)
    if spec["source"].get("module") == "spantime":
        METRICS[spec["name"]] = spec


def span(name, duration, *children, **tags):
    return {
        "name": name, "durationMs": duration, "tags": tags,
        "selfMs": round(duration - sum(c["durationMs"] for c in children), 3),
        "children": list(children),
    }


def miss():
    """A Count that missed the result cache: 3.0 ms in the handler, 3.5 ms
    at the client."""
    return [span(
        "http.request", 3.0,
        span("api.parse", 0.1),
        span("api.admit", 0.5, span("sched.admit", 0.3)),
        span("api.query", 2.0, span(
            "exec.batch", 1.8,
            span("exec.cache", 0.2),
            span("exec.call", 1.3,
                 span("exec.lower", 0.5, span("exec.stage", 0.1)),
                 span("exec.dispatch", 0.6)),
            span("exec.cache", 0.1),
        )),
    )]


def hit():
    """The same from the cache: no call, no lowering, no dispatch."""
    return [span(
        "http.request", 1.0,
        span("api.parse", 0.1),
        span("api.admit", 0.2),
        span("api.query", 0.4, span("exec.batch", 0.3, span("exec.cache", 0.2))),
    )]


def context(*requests):
    return SimpleNamespace(requests=[
        {"text": "", "wall_ms": wall, "received": 0.0, "roots": roots}
        for wall, roots in requests
    ])


EXPECTED = {  # mean of (miss, hit)
    "handler_ms": (0.4 + 0.3) / 2,
    "unspanned_ms": (0.5 + 0.6) / 2,
    "parse_ms": 0.1,
    "admit_ms": (0.2 + 0.2) / 2,
    "exec_glue_ms": ((0.2 + 0.2 + 0.2) + (0.1 + 0.1)) / 2,
    "cache_lookup_ms": (0.3 + 0.2) / 2,
    "lower_ms": (0.4 + 0.0) / 2,
    "dispatch_host_ms": (0.6 + 0.0) / 2,
}


def test_the_metric_files_are_the_expected_eight():
    assert set(METRICS) == set(EXPECTED)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_metric_reads_its_share_of_a_hand_built_tree(name):
    ctx = context((3.5, miss()), (1.6, hit()))
    value = readers.read(METRICS[name], ctx)
    assert value == pytest.approx(EXPECTED[name])


def test_the_metrics_and_the_synthetic_spans_add_up_to_the_wall_time():
    ctx = context((3.5, miss()), (1.6, hit()))
    total = sum(readers.read(spec, ctx) for spec in METRICS.values())
    stage = spantime.read(
        {"spans": ["exec.stage"], "field": "selfMs"}, ctx)
    wait = spantime.read(
        {"spans": ["sched.admit"], "field": "selfMs"}, ctx)
    assert (stage, wait) == (pytest.approx(0.05), pytest.approx(0.15))
    assert total + stage + wait == pytest.approx((3.5 + 1.6) / 2)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_request_with_none_of_the_spans_counts_zero_and_not_none(name):
    bare = [span("http.request", 0.7)]
    value = readers.read(METRICS[name], context((1.0, bare)))
    want = {"handler_ms": 0.7, "unspanned_ms": 0.3}.get(name, 0.0)
    assert value is not None and value == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_program_without_the_root_span_gives_nothing_to_read(name, capsys):
    """The parent commit's tree: api.query is the root. Nothing is read
    and nothing raised, but stderr says which root the trees had."""
    old = [span("api.query", 1.4, span("exec.batch", 1.2, span("exec.dispatch", 0.6)))]
    assert readers.read(METRICS[name], context((3.5, old))) is None
    said = capsys.readouterr().err
    assert "http.request" in said and "roots: api.query" in said
    assert readers.read(METRICS[name], context()) is None
    assert capsys.readouterr().err == ""  # no trees at all: an untraced run


def test_an_unknown_field_is_an_error_and_not_a_zero():
    with pytest.raises(ValueError):
        spantime.read({"spans": ["api.parse"], "field": "startMs"},
                      context((1.0, hit())))


def test_compiles_per_query_is_a_counter_delta_over_the_requests():
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           "compiles_per_query.json")) as f:
        spec = json.load(f)
    ctx = context((1.0, hit()), (1.0, hit()), (1.0, hit()), (1.0, hit()))
    ctx.before, ctx.after = {"exec.compiles": 40.0}, {"exec.compiles": 42.0}
    assert readers.read(spec, ctx) == pytest.approx(0.5)
    ctx.after = {"exec.compiles": 40.0}
    assert readers.read(spec, ctx) == 0.0
    ctx.before, ctx.after = {}, {}  # the parent commit has no such counter
    assert readers.read(spec, ctx) is None
