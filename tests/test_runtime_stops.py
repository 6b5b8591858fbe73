"""Process-wide stops on the program's own clock (ISSUE 39): CPython
collector passes and background-loop ticks counted, timed and annotated
(`utils/tracing.py` `_on_gc` / `tick`, published at scrape time by
`publish_cache_gauges`), and `api.admit` measured part by part."""

import gc
import glob
import json
import os
import threading
import time
import urllib.request

import pytest

from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.testing import ClusterHarness
from pilosa_tpu.utils import stats as statsmod
from pilosa_tpu.utils import tracing

GC_STATS = ("runtime.gc_passes", "runtime.gc_pause_ms",
            "runtime.gc_full_passes", "runtime.gc_full_pause_ms")


def _vars(srv):
    with urllib.request.urlopen(f"{srv.node.uri}/debug/vars", timeout=30) as r:
        return json.loads(r.read())


def _metrics(srv):
    with urllib.request.urlopen(f"{srv.node.uri}/metrics", timeout=30) as r:
        return r.read().decode()


@pytest.fixture(scope="module")
def served():
    with ClusterHarness(1, in_memory=True) as c:
        api = c[0].api
        api.create_index("rs")
        api.create_field("rs", "f", {"type": "set"})
        api.import_bits("rs", "f", [0, 1, 1, 2],
                        [1, 2, SHARD_WIDTH + 3, 2 * SHARD_WIDTH + 4])
        yield c[0]


def test_every_stop_stat_is_published_from_the_first_scrape(served):
    got = _vars(served)
    for name in GC_STATS:
        assert got[name] >= 0
    for ticker in tracing.TICKERS:
        assert got[f"runtime.ticks;ticker:{ticker}"] >= 0
        assert got[f"runtime.tick_ms;ticker:{ticker}"] >= 0
    text = _metrics(served)
    assert "pilosa_tpu_runtime_gc_full_pause_ms " in text
    assert 'pilosa_tpu_runtime_ticks{ticker="cache-flush"}' in text


def test_a_forced_full_collection_raises_the_full_pass_counters(served):
    before = _vars(served)
    gc.collect(2)
    after = _vars(served)
    assert after["runtime.gc_full_passes"] >= before["runtime.gc_full_passes"] + 1
    assert after["runtime.gc_full_pause_ms"] > before["runtime.gc_full_pause_ms"]
    assert after["runtime.gc_passes"] >= after["runtime.gc_full_passes"]
    assert after["runtime.gc_pause_ms"] >= after["runtime.gc_full_pause_ms"]


def test_the_callback_is_installed_once_however_many_nodes_start():
    with ClusterHarness(2, in_memory=True):
        with ClusterHarness(1, in_memory=True):
            assert gc.callbacks.count(tracing._on_gc) == 1
            assert [cb for cb in gc.callbacks
                    if getattr(cb, "__name__", "") == "_on_gc"] == [tracing._on_gc]


def test_a_collection_completes_while_the_process_registry_lock_is_held():
    """The callback takes no lock: a pass forced on another thread while
    this one holds PROCESS's lock finishes (a deadlock fails here, under
    a timeout of the test's own, instead of hanging the suite)."""
    before = tracing.gc_totals()["full_passes"]
    worker = threading.Thread(target=gc.collect, args=(2,), daemon=True)
    with statsmod.PROCESS._mu:
        worker.start()
        worker.join(timeout=60)
        finished = not worker.is_alive()
    worker.join(timeout=60)
    assert finished, "a collector pass blocked on the registry's lock"
    assert tracing.gc_totals()["full_passes"] >= before + 1


def test_one_tick_of_a_loop_raises_its_labelled_count():
    with ClusterHarness(1, in_memory=True, metric_poll_interval=0.05) as c:
        key = "runtime.ticks;ticker:runtime-poll"
        first = _vars(c[0])[key]
        deadline = time.monotonic() + 30
        while _vars(c[0])[key] <= first and time.monotonic() < deadline:
            time.sleep(0.05)
        got = _vars(c[0])
        assert got[key] >= first + 1
        assert got["runtime.tick_ms;ticker:runtime-poll"] > 0


def test_tick_totals_name_every_loop_and_count_a_pass():
    n, ms = tracing.tick_totals()["anti-entropy"]
    with tracing.tick("anti-entropy"):
        time.sleep(0.002)
    n2, ms2 = tracing.tick_totals()["anti-entropy"]
    assert n2 == n + 1 and ms2 - ms >= 2.0
    assert set(tracing.tick_totals()) == set(tracing.TICKERS)


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_a_profiler_session_holds_a_collector_pass_and_a_tick(tmp_path):
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # as benchmarks/serve.py asks
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        gc.collect(2)
        with tracing.tick("telemetry"):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("runtime.gc", "node.tick"):
                    found.setdefault(ev.name, []).append(dict(ev.stats))
    assert {"generation": 2} in found["runtime.gc"]
    assert {"ticker": "telemetry"} in found["node.tick"]


def test_a_profiled_query_carries_admission_part_by_part(served):
    req = urllib.request.Request(
        f"{served.node.uri}/index/rs/query?profile=1",
        data=b"Count(Union(Row(f=1),Row(f=2)))", method="POST")
    with urllib.request.urlopen(req, timeout=60) as resp:
        out = json.loads(resp.read())
    assert out["results"] == [3]

    def walk(node):
        yield node
        for c in node["children"]:
            yield from walk(c)

    (admit,) = [n for r in out["profile"]["roots"] for n in walk(r)
                if n["name"] == "api.admit"]
    tags = admit["tags"]
    parts = ("admit.estimate_ms", "admit.merge_walk_ms", "admit.grant_ms")
    assert all(tags[p] >= 0 for p in parts), tags
    assert tags["admit.estimate_ms"] >= tags["admit.merge_walk_ms"]
    # tags, not children: the self time still holds both parts
    assert not [c for c in admit["children"] if c["name"] != "sched.admit"]
    assert tags["admit.estimate_ms"] + tags["admit.grant_ms"] <= (
        admit["selfMs"] + 0.003)


def test_admission_tags_nothing_without_a_sampled_span():
    from pilosa_tpu.server import api as apimod

    assert tracing.active_span() is None
    apimod._tag_admit(0.001, 0.002)  # no span open: a no-op, no error


def test_annotations_are_built_by_a_span_the_collector_and_a_tick_alone():
    import ast
    import pathlib

    pkg = pathlib.Path(tracing.__file__).resolve().parents[1]
    sites = []
    for path in sorted(pkg.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text(encoding="utf-8"))):
            fn = getattr(node, "func", None)
            name = getattr(fn, "id", None) or getattr(fn, "attr", None)
            if isinstance(node, ast.Call) and name == "TraceAnnotation":
                sites.append((path.name, ast.unparse(node.args[0])))
    assert sorted(sites) == [("tracing.py", "'node.tick'"),
                             ("tracing.py", "'runtime.gc'"),
                             ("tracing.py", "self.name")]
