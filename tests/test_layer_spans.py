"""Spans at every layer boundary of a served query (ISSUE 26): the
`?profile=1` tree from the first byte the handler reads, the same spans
on the profiler's clock, and the compile and dispatch counters on
`/debug/vars`."""

import glob
import json
import os
import threading
import time
import urllib.request

import pytest

from pilosa_tpu.exec import plan as planmod
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.testing import ClusterHarness
from pilosa_tpu.utils import stats as statsmod
from pilosa_tpu.utils import tracing

COUNT = "Count(Intersect(Row(f=0),Row(f=1)))"
GROUP_BY = "GroupBy(Rows(f),Rows(g))"
# spans that are recorded after the fact and so hold no annotation
SYNTHETIC = {"sched.admit", "exec.stage"}
COUNTERS = (
    "exec.compiles", "exec.compile_ms", "exec.compile_cache_hits",
    "exec.dispatches", "exec.host_reads", "batcher.leader",
    "batcher.batched", "batcher.merged_execs", "batcher.fallback_splits",
)


def _seed(api, n_shards=3):
    api.create_index("ls")
    for field in ("f", "g"):
        api.create_field("ls", field, {"type": "set"})
        rows, cols = [], []
        for s in range(n_shards):
            for r in range(3):
                for k in range(40):
                    rows.append(r)
                    cols.append(s * SHARD_WIDTH + 13 * k + r)
        api.import_bits("ls", field, rows, cols)


def _post(srv, text, profile=True):
    """(parsed reply, perf_counter_ns at the send, and at the last byte)."""
    url = f"{srv.node.uri}/index/ls/query" + ("?profile=1" if profile else "")
    req = urllib.request.Request(url, data=text.encode(), method="POST")
    sent = time.perf_counter_ns()
    with urllib.request.urlopen(req, timeout=60) as resp:
        raw = resp.read()
    return json.loads(raw), sent, time.perf_counter_ns()


def _get(srv, path):
    with urllib.request.urlopen(f"{srv.node.uri}{path}", timeout=30) as resp:
        return json.loads(resp.read())


def _walk(node, parent=None):
    yield node, parent
    for child in node["children"]:
        yield from _walk(child, node)


def _by_name(root):
    out = {}
    for node, _ in _walk(root):
        out.setdefault(node["name"], []).append(node)
    return out


def _ring_root(srv, trace_id, timeout=5.0):
    """The finished http.request span of a trace, from the ring: the
    handler closes it after the reply's last write, so a client that has
    its answer may be ahead of it."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for s in srv.tracer.spans_for(trace_id):
            if s["name"] == "http.request":
                return s
        time.sleep(0.005)
    raise AssertionError(f"no finished http.request for trace {trace_id}")


@pytest.fixture(scope="module")
def served():
    # the result cache is on (and process-global): a test that needs a
    # dispatch asks for something no other test of the module has asked
    with ClusterHarness(1, in_memory=True) as c:
        _seed(c[0].api)
        yield c[0]


# -- (b) the tree of a served request ----------------------------------------


def test_tree_of_a_served_count_has_every_layer_with_its_tags(served):
    srv = served
    out, sent, received = _post(srv, "Count(Union(Row(f=0),Row(f=2)))")
    assert out["results"] == [240]
    (root,) = out["profile"]["roots"]
    assert root["name"] == "http.request"
    spans = _by_name(root)
    want_tags = {
        "http.request": {"http.route", "http.bytes_in", "http.read_ms",
                         "http.encode_ms"},
        "api.parse": {"pql.family"},
        "api.admit": {"sched.class", "sched.wait_ms"},
        "api.query": {"pql.family", "sched.wait_ms", "query_ms"},
        "exec.batch": {"batcher.role"},
        "exec.call": {"pql.family"},
        "exec.cache": {"cache.op"},
        "exec.lower": {"plan.family"},
        "exec.dispatch": {"plan.family", "plan.program", "dispatch.compiled",
                          "dispatch.lock_wait_ms", "dispatch.eval_ms"},
    }
    for name, tags in want_tags.items():
        assert name in spans, (name, sorted(spans))
        assert tags <= set(spans[name][0]["tags"]), (name, spans[name][0]["tags"])
    assert root["tags"]["http.route"] == "query"
    assert root["tags"]["http.bytes_in"] == len("Count(Union(Row(f=0),Row(f=2)))")
    assert spans["api.parse"][0]["tags"]["pql.family"] == "Count"
    assert spans["api.query"][0]["tags"]["pql.family"] == "Count"
    assert spans["exec.call"][0]["tags"]["pql.family"] == "Count"
    lookup = [s for s in spans["exec.cache"] if s["tags"]["cache.op"] == "lookup"]
    assert lookup and lookup[0]["tags"]["cache.hit"] is False
    assert {s["tags"]["cache.op"] for s in spans["exec.cache"]} == {"lookup", "store"}
    assert spans["exec.lower"][0]["tags"]["plan.family"] == "stacked"
    dispatch = spans["exec.dispatch"][0]["tags"]
    assert dispatch["plan.family"] == "stacked"
    assert dispatch["plan.program"] == "jit__eval_jit"
    # who holds whom
    parents = {n["name"]: (p or {}).get("name") for n, p in _walk(root)}
    assert parents["api.parse"] == parents["api.admit"] == "http.request"
    assert parents["api.query"] == "http.request"
    assert parents["exec.call"] == "exec.batch"
    assert parents["exec.lower"] == "exec.call"
    # every real span starts between the client's send and its last byte
    for node, _ in _walk(root):
        if node["name"] not in SYNTHETIC:
            assert sent <= node["startMonoNs"] <= received, node["name"]
    # self times add up to the root's duration (rounded to the µs each)
    total_self = sum(n["selfMs"] for n, _ in _walk(root))
    assert total_self == pytest.approx(root["durationMs"], abs=0.02)
    assert root["durationMs"] <= (received - sent) / 1e6

    # the repeat is the result cache's: no call, no lowering, no dispatch
    out, _, _ = _post(srv, "Count(Union(Row(f=0),Row(f=2)))")
    spans = _by_name(out["profile"]["roots"][0])
    assert spans["exec.cache"][0]["tags"]["cache.hit"] is True
    assert not {"exec.call", "exec.lower", "exec.dispatch"} & set(spans)


def test_write_time_reaches_the_ring_and_not_the_requests_own_tree(served):
    out, _, _ = _post(served, "Count(Difference(Row(f=1),Row(g=0)))")
    (root,) = out["profile"]["roots"]
    assert "http.write_ms" not in root["tags"]
    _ring_root(served, out["profile"]["traceId"])
    tree = _get(served, f"/debug/traces?trace={out['profile']['traceId']}")
    (ring_root,) = tree["roots"]
    assert ring_root["name"] == "http.request"
    assert ring_root["tags"]["http.write_ms"] >= 0.0
    assert ring_root["tags"]["http.bytes_out"] > len('{"results": [80]}')
    # the finished span is longer than the tree's still-open one
    assert ring_root["durationMs"] >= root["durationMs"]


@pytest.mark.parametrize("text,family,plan_family,program", [
    ("GroupBy(Rows(f),Rows(g),limit=7)", "GroupBy", "groupby", "jit__counts_cross"),
    ("Sum(field=v)", "Sum", "bsi", "jit_sum_stream_slab"),
])
def test_groupby_and_bsi_paths_lower_and_dispatch_under_their_family(
        served, text, family, plan_family, program):
    if family == "Sum" and served.holder.index("ls").field("v") is None:
        served.api.create_field("ls", "v", {"type": "int", "min": 0, "max": 500})
        served.api.import_values("ls", "v", list(range(0, 400, 4)), list(range(100)))
    out, _, _ = _post(served, text)
    spans = _by_name(out["profile"]["roots"][0])
    assert spans["api.query"][0]["tags"]["pql.family"] == family
    assert spans["exec.call"][0]["tags"]["pql.family"] == family
    assert plan_family in {s["tags"]["plan.family"] for s in spans["exec.lower"]}
    tags = [s["tags"] for s in spans["exec.dispatch"]]
    assert (plan_family, program) in {
        (t["plan.family"], t["plan.program"]) for t in tags}


def test_queue_wait_is_a_child_of_api_admit_and_not_in_its_self_time():
    with ClusterHarness(1, in_memory=True, max_concurrent_queries=1,
                        admission_queue_depth=4) as c:
        srv = c[0]
        _seed(srv.api, n_shards=2)
        held = srv.scheduler.admit()  # the only slot
        threading.Timer(0.08, held.release).start()
        out, _, _ = _post(srv, COUNT)
    (root,) = out["profile"]["roots"]
    spans = _by_name(root)
    (admit,) = spans["api.admit"]
    (wait,) = spans["sched.admit"]
    assert wait in admit["children"]
    assert wait["durationMs"] >= 40.0
    assert admit["tags"]["sched.wait_ms"] >= 40.0
    assert spans["api.query"][0]["tags"]["sched.wait_ms"] == admit["tags"]["sched.wait_ms"]
    assert admit["selfMs"] < wait["durationMs"] / 2
    assert sum(n["selfMs"] for n, _ in _walk(root)) == pytest.approx(
        root["durationMs"], abs=0.02)


def test_a_direct_api_call_keeps_api_query_as_its_only_root(served):
    resp = served.api.query_response(
        "ls", "Count(Xor(Row(f=1),Row(g=2)))", profile=True)
    (root,) = resp.profile["roots"]
    assert root["name"] == "api.query"
    names = {n["name"] for n, _ in _walk(root)}
    assert not {"http.request", "api.parse", "api.admit"} & names
    assert {"exec.call", "exec.lower", "exec.dispatch"} <= names


def test_import_routes_root_their_api_import_in_http_request(served):
    body = json.dumps({"rows": [2], "cols": [2]}).encode()  # a bit that is set
    req = urllib.request.Request(
        f"{served.node.uri}/index/ls/field/f/import", data=body, method="POST")
    with urllib.request.urlopen(req, timeout=30) as resp:
        resp.read()
    deadline = time.monotonic() + 5.0
    roots = []
    while not roots and time.monotonic() < deadline:
        roots = [s for s in served.tracer.to_json()
                 if s["name"] == "http.request"
                 and s["tags"]["http.route"] == "import"]
    root = roots[-1]
    assert root["tags"]["http.bytes_in"] == len(body)
    assert {"http.read_ms", "http.encode_ms", "http.write_ms", "http.bytes_out"} <= set(root["tags"])
    children = [s for s in served.tracer.spans_for(root["traceId"])
                if s["parentId"] == root["spanId"]]
    assert [s["name"] for s in children] == ["api.import"]


# -- (a) the same spans on the profiler's clock ------------------------------


def _host_events(trace_dir):
    """{trace id: [(name, start_ns, end_ns)]} of the annotated events of
    the newest trace's /host:CPU plane."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                tid = dict(ev.stats).get("trace_id")
                if tid is not None:
                    out.setdefault(tid, []).append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_spans_are_on_the_profilers_clock_with_the_requests_trace_id(
        served, tmp_path):
    import jax

    for text in (COUNT, GROUP_BY):
        _post(served, text)  # compile outside the session
    # the same programs over other rows: compiled, and not yet cached
    texts = ("Count(Intersect(Row(g=2),Row(f=1)))", "GroupBy(Rows(g),Rows(f))")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # as benchmarks/serve.py asks
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        replies = [_post(served, text)[0] for text in texts]
        for reply in replies:  # the roots close after their replies left
            _ring_root(served, reply["profile"]["traceId"])
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    for reply in replies:
        profile = reply["profile"]
        (root,) = profile["roots"]
        mine = events[profile["traceId"]]
        tree_names = sorted(
            n["name"] for n, _ in _walk(root) if n["name"] not in SYNTHETIC)
        assert sorted(name for name, _, _ in mine) == tree_names
        assert {"http.request", "api.query", "exec.dispatch"} <= set(tree_names)
        # nesting as in the tree: each span's event lies inside the event
        # of its parent (by name and order of start, as the tree has them)
        by_name = {}
        for name, start, end in sorted(mine, key=lambda e: e[1]):
            by_name.setdefault(name, []).append((start, end))
        seen = {}
        for node, parent in _walk(root):
            if node["name"] in SYNTHETIC:
                continue
            i = seen.get(node["name"], 0)
            seen[node["name"]] = i + 1
            node["_event"] = by_name[node["name"]][i]
            if parent is not None:
                (ps, pe), (s, e) = parent["_event"], node["_event"]
                assert ps <= s and e <= pe, (parent["name"], node["name"])
        # and the durations agree with the tree's (two clocks, one span)
        (s, e) = root["_event"]
        api_query = next(n for n, _ in _walk(root) if n["name"] == "api.query")
        qs, qe = api_query["_event"]
        assert (qe - qs) / 1e6 == pytest.approx(api_query["durationMs"], abs=0.5)


# -- (c) counters where the work happens -------------------------------------


def test_every_counter_is_on_debug_vars_from_start_up():
    with ClusterHarness(1, in_memory=True) as c:
        snapshot = _get(c[0], "/debug/vars")
    for name in COUNTERS:
        assert isinstance(snapshot[name], (int, float)), name
        assert name in statsmod.STAT_NAMES


def test_compiles_rise_on_a_new_plan_structure_and_not_on_its_repeat(served):
    # a tree no other test lowers, over this module's 3 shards
    text = ("Count(Xor(Difference(Union(Row(f=0),Row(g=1)),Row(f=2)),"
            "Intersect(Row(g=0),Row(f=2)),Union(Row(g=2),Row(f=1),Row(g=1))))")

    def one(text):
        before = _get(served, "/debug/vars")
        out, _, _ = _post(served, text)
        after = _get(served, "/debug/vars")
        (dispatch,) = _by_name(out["profile"]["roots"][0])["exec.dispatch"]
        return out["results"], dispatch["tags"]["dispatch.compiled"], {
            k: after[k] - before[k] for k in COUNTERS}

    first, compiled, delta = one(text)
    assert compiled is True
    assert delta["exec.compiles"] >= 1 and delta["exec.compile_ms"] > 0
    assert delta["exec.dispatches"] == 1 and delta["exec.host_reads"] == 1
    assert delta["batcher.leader"] == 1 and delta["batcher.batched"] == 0
    # the same structure over the other field's rows (the result cache
    # would answer the same text): the operands change, the program not
    swapped = text.replace("f=", "h=").replace("g=", "f=").replace("h=", "g=")
    again, compiled, delta = one(swapped)
    assert again == first  # both fields hold the same bits
    assert compiled is False
    assert delta["exec.compiles"] == 0 and delta["exec.compile_ms"] == 0
    assert delta["exec.dispatches"] == 1


def test_the_listeners_hear_the_events_the_installed_jax_records():
    from jax._src import dispatch

    assert planmod._BACKEND_COMPILE_EVENT == dispatch.BACKEND_COMPILE_EVENT
    import inspect

    from jax._src import compiler

    assert repr(planmod._CACHE_HIT_EVENT) in inspect.getsource(
        compiler.compile_or_get_cached)
    before = statsmod.PROCESS.total_counter("exec.compile_cache_hits")
    planmod._on_event(planmod._CACHE_HIT_EVENT)
    planmod._on_event("/jax/some/other/event")
    assert statsmod.PROCESS.total_counter("exec.compile_cache_hits") == before + 1


# -- the span itself ----------------------------------------------------------


def test_ids_are_sixteen_hex_digits_and_spans_export_their_monotonic_start():
    tr = tracing.Tracer()
    t0 = time.monotonic_ns()
    with tr.start_span("root") as root:
        with tracing.start_span("child") as child:
            pass
    t1 = time.monotonic_ns()
    for span in (root, child):
        d = span.to_json()
        assert len(d["spanId"]) == 16 and int(d["spanId"], 16) >= 0
        assert len(d["traceId"]) == 16
        assert t0 - 1000 <= d["startMonoNs"] <= t1
    assert child.trace_id == root.trace_id and child.parent_id == root.span_id
    assert len({tracing.new_trace_id() for _ in range(1000)}) == 1000
    # a span ingested from a peer has no monotonic start of this host's
    tree = tracing.assemble(
        [dict(root.to_json(), startMonoNs=0)], root.trace_id)
    assert "startMonoNs" not in tree["roots"][0]


def test_an_open_root_enters_its_tree_at_its_duration_so_far():
    tr = tracing.Tracer()
    with tr.start_span("root") as root:
        with tracing.start_span("child"):
            time.sleep(0.002)
        tree = tracing.assemble_open(root, tr.spans_for(root.trace_id))
        assert root.duration is None  # still open
    (node,) = tree["roots"]
    assert node["name"] == "root" and node["durationMs"] >= 2.0
    assert [c["name"] for c in node["children"]] == ["child"]
    assert node["selfMs"] == pytest.approx(
        node["durationMs"] - node["children"][0]["durationMs"], abs=0.002)
