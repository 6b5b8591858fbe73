"""HTTP handler: public REST routes + internal internode routes.

Reference: /root/reference/http/handler.go:276-318 route table —
public:   /status /schema /index/{i} /index/{i}/query
          /index/{i}/field/{f} /index/{i}/field/{f}/import /export
internal: /internal/index/{i}/query /internal/cluster/message
          /internal/fragment/{blocks,block/data,data}
          /internal/translate/data /internal/shards/max

stdlib ThreadingHTTPServer; JSON request/response bodies (PQL queries may
also arrive as raw text, matching the reference's text/plain handling)."""

from __future__ import annotations

import json
import re
import time
import traceback
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from pilosa_tpu.core.fragment import TransferCutover
from pilosa_tpu.exec.executor import ExecError, NotFoundError
from pilosa_tpu.pql.parser import ParseError
from pilosa_tpu.sched.admission import ShedError
from pilosa_tpu.server import wire
from pilosa_tpu.server.api import ApiError, DisabledError
from pilosa_tpu.utils import tracing

_ROUTES: List[Tuple[str, re.Pattern, str]] = []

_REQUIRED = object()


class BadParam(ValueError):
    """Malformed/missing query parameter -> 400 with a JSON error body
    (instead of a bare int() traceback surfacing as an opaque message)."""


def route(method: str, pattern: str):
    rx = re.compile("^" + pattern + "$")

    def deco(fn):
        _ROUTES.append((method, rx, fn.__name__))
        return fn

    return deco


def _ms_since(t: float) -> float:
    return round((time.perf_counter() - t) * 1000.0, 3)


class Handler(BaseHTTPRequestHandler):
    server_version = "pilosa-tpu/0.1"
    protocol_version = "HTTP/1.1"

    # quiet default request logging; NodeServer.logger gets errors only
    def log_message(self, fmt, *args):
        pass

    @property
    def node(self):
        return self.server.node_server

    @property
    def api(self):
        return self.server.node_server.api

    # -- plumbing ----------------------------------------------------------

    def _body(self, span=None) -> bytes:
        """The request's body; under a request span, timed into its
        http.read_ms / http.bytes_in tags."""
        t = time.perf_counter()
        n = int(self.headers.get("Content-Length") or 0)
        data = self.rfile.read(n) if n else b""
        if span is not None:
            span.set_tag("http.read_ms", _ms_since(t))
            span.set_tag("http.bytes_in", len(data))
        return data

    def _json_body(self, span=None) -> Any:
        data = self._body(span)
        return json.loads(data) if data else {}

    def _reply(self, obj: Any, code: int = 200, raw: Optional[bytes] = None,
               content_type: str = "application/json",
               extra_headers: Optional[Dict[str, str]] = None) -> None:
        body = raw if raw is not None else json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        if extra_headers:
            for k, v in extra_headers.items():
                self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _error(self, msg: str, code: int = 400) -> None:
        self._reply({"error": msg}, code=code)

    # -- the request's root span (query and import routes) ------------------
    # http.request covers the route from before the body is read to after
    # the reply's last write; _body and _write_reply time its parts into
    # tags. An error reply is written by _dispatch, after the span closed.

    def _request_span(self, route_name: str, force: bool = False):
        span = self.node.tracer.start_span_from_headers(
            "http.request", self.headers, force=force
        )
        return span.set_tag("http.route", route_name)

    def _write_reply(self, span, obj: Any = None,
                     raw: Optional[bytes] = None) -> None:
        """Reply with `raw`, or with `obj` encoded here (http.encode_ms)."""
        if raw is None:
            t = time.perf_counter()
            raw = json.dumps(obj).encode()
            span.set_tag("http.encode_ms", _ms_since(t))
        span.set_tag("http.bytes_out", len(raw))
        t = time.perf_counter()
        self._reply(None, raw=raw)
        span.set_tag("http.write_ms", _ms_since(t))

    def _int_param(self, name: str, default: Any = _REQUIRED) -> Optional[int]:
        """Validated integer query parameter: absent -> `default` (or 400
        when required), non-numeric -> 400 with a JSON error body naming
        the parameter (satellite: `?shard=abc` must be a client error,
        never an opaque coercion failure)."""
        raw = self.query.get(name)
        if raw is None:
            if default is _REQUIRED:
                raise BadParam(f"missing required query parameter {name!r}")
            return default
        try:
            return int(raw)
        except ValueError:
            raise BadParam(
                f"query parameter {name!r} must be an integer, got {raw!r}"
            ) from None

    def _str_param(self, name: str) -> str:
        raw = self.query.get(name)
        if not raw:
            raise BadParam(f"missing required query parameter {name!r}")
        return raw

    def _bool_param(self, name: str, default: bool = False) -> bool:
        """Validated boolean query parameter: absent -> default; anything
        other than 1/0/true/false -> 400 naming the parameter (a typo'd
        `?clear=ture` must be a client error, never a silent False)."""
        raw = self.query.get(name)
        if raw is None:
            return default
        if raw in ("1", "true"):
            return True
        if raw in ("0", "false", ""):
            return False
        raise BadParam(
            f"query parameter {name!r} must be a boolean "
            f"(1/0/true/false), got {raw!r}"
        )

    def _int_path(self, name: str, raw: str) -> int:
        """Validated integer path component -> 400 naming the component
        (`/import-roaring/abc` must be a client error, not an opaque
        404/500)."""
        try:
            return int(raw)
        except ValueError:
            raise BadParam(
                f"path parameter {name!r} must be an integer, got {raw!r}"
            ) from None

    def _json_body_dict(self) -> dict:
        """Validated JSON object body -> 400 naming the problem (the
        resize control surface takes structured bodies; `[]` or a bare
        string must be a client error, never an AttributeError 500)."""
        try:
            d = self._json_body()
        except ValueError:
            raise BadParam("request body must be valid JSON") from None
        if d is None:
            return {}
        if not isinstance(d, dict):
            raise BadParam(
                f"request body must be a JSON object, got {type(d).__name__}"
            )
        return d

    def _body_str(self, d: dict, name: str) -> str:
        raw = d.get(name)
        if not isinstance(raw, str) or not raw:
            raise BadParam(
                f"body field {name!r} must be a non-empty string, got {raw!r}"
            )
        return raw

    def _body_int(self, d: dict, name: str) -> Optional[int]:
        raw = d.get(name)
        if raw is None:
            return None
        if isinstance(raw, bool) or not isinstance(raw, int):
            raise BadParam(
                f"body field {name!r} must be an integer, got {raw!r}"
            )
        return raw

    def _body_nodes(self, d: dict, name: str, required: bool = True):
        """Validated membership list -> topology Nodes; 400 names the
        field and element on malformed input."""
        from pilosa_tpu.cluster.topology import Node as TNode

        raw = d.get(name)
        if raw is None:
            if required:
                raise BadParam(f"missing required body field {name!r}")
            return None
        if not isinstance(raw, list):
            raise BadParam(
                f"body field {name!r} must be a list of node objects, "
                f"got {type(raw).__name__}"
            )
        nodes = []
        for i, n in enumerate(raw):
            if not isinstance(n, dict) or not isinstance(n.get("id"), str) or not n["id"]:
                raise BadParam(
                    f"body field {name!r}[{i}] must be a node object "
                    "with a non-empty string 'id'"
                )
            nodes.append(TNode.from_json(n))
        return nodes

    def _admit_transfer(self):
        """Resize transfer serving rides the `batch` admission class:
        streaming a reshard is bulk work that must never starve
        interactive queries (WFQ weight 1 vs 8), but it still occupies a
        real slot so concurrent transfer legs cannot monopolize the node
        either. Returns the ticket to release (None when admission is
        disabled); saturation sheds 429, which the internode retry plane
        absorbs with backoff."""
        sched = self.node.scheduler
        if sched is None:
            return None
        from pilosa_tpu.sched.admission import CLASS_BATCH

        return sched.admit(cls=CLASS_BATCH)

    def _int_list_param(self, name: str) -> List[int]:
        raw = self.query.get(name, "")
        try:
            # no empty-segment filtering: "1,,2" is a client typo that
            # must 400, not silently become [1, 2]
            return [int(s) for s in raw.split(",")]
        except ValueError:
            raise BadParam(
                f"query parameter {name!r} must be comma-separated "
                f"integers, got {raw!r}"
            ) from None

    def _dispatch(self, method: str) -> None:
        parsed = urllib.parse.urlparse(self.path)
        self.query = {
            k: v[0] for k, v in urllib.parse.parse_qs(parsed.query).items()
        }
        for m, rx, fn_name in _ROUTES:
            if m != method:
                continue
            match = rx.match(parsed.path)
            if match:
                try:
                    getattr(self, fn_name)(**match.groupdict())
                except (NotFoundError,) as e:
                    self._error(str(e), 404)
                except ShedError as e:
                    # admission-control load shed: 429 is retryable per
                    # server/faults.py, so internode callers fail over /
                    # back off instead of treating this as a hard error.
                    # Retry-After must be RFC 9110 delta-seconds (an
                    # integer) or standard client stacks ignore it; the
                    # precise value rides a vendor header for the
                    # internode client's sub-second backoff. The trace id
                    # the query would have flown under rides both the
                    # body and the standard trace header so a shed query
                    # is diagnosable from the client side.
                    import math

                    trace_id = getattr(e, "trace_id", "")
                    hdrs = {
                        "Retry-After": str(max(1, math.ceil(e.retry_after))),
                        "X-Pilosa-Retry-After": f"{e.retry_after:g}",
                    }
                    if getattr(e, "quota_limit", ""):
                        # tenant-quota sheds name the limit that tripped
                        # so a client can tell "slow down" (rate) from
                        # "shrink your working set" (byte quota)
                        hdrs["X-Pilosa-Quota-Limit"] = e.quota_limit
                        hdrs["X-Pilosa-Quota-Usage"] = f"{e.quota_usage:g}"
                        hdrs["X-Pilosa-Quota-Value"] = f"{e.quota_value:g}"
                    body = {"error": str(e)}
                    if trace_id:
                        hdrs[tracing.TRACE_HEADER] = trace_id
                        body["traceId"] = trace_id
                    self._reply(body, code=429, extra_headers=hdrs)
                except DisabledError as e:
                    self._error(str(e), 503)
                except TransferCutover as e:
                    # resize-cutover write barrier: 503 is retryable for
                    # the internode plane, and Retry-After covers direct
                    # clients — the barrier window is sub-second in the
                    # normal case (quiesce -> final drain -> install)
                    self.node.stats.count("resize.cutover_rejects", 1)
                    self._reply(
                        {"error": str(e)},
                        code=503,
                        extra_headers={"Retry-After": "1"},
                    )
                except (ExecError, ApiError, ParseError, ValueError, KeyError) as e:
                    self._error(str(e), 400)
                except BrokenPipeError:
                    pass
                except Exception as e:
                    self.node.logger(traceback.format_exc())
                    self._error(f"internal error: {e}", 500)
                return
        self._error(f"no route for {method} {parsed.path}", 404)

    def do_GET(self):
        self._dispatch("GET")

    def do_POST(self):
        self._dispatch("POST")

    def do_DELETE(self):
        self._dispatch("DELETE")

    # -- public routes -----------------------------------------------------

    @route("GET", "/status")
    def get_status(self):
        self._reply(self.api.status())

    @route("GET", "/")
    def get_home(self):
        """Reference: handleHome — a pointer at the docs/endpoints."""
        self._reply(
            {
                "name": "pilosa-tpu",
                "version": self.api.version(),
                "see": ["/status", "/schema", "/index/{index}/query"],
            }
        )

    @route("GET", "/version")
    def get_version(self):
        self._reply({"version": self.api.version()})

    @route("GET", "/info")
    def get_info(self):
        """Host info (reference: handleGetInfo — shard width + CPU info)."""
        self._reply(self.api.info())

    @route("GET", "/index/(?P<index>[^/]+)")
    def get_index(self, index: str):
        self._reply(self.api.index_info(index))

    @route("GET", "/index")
    def get_indexes(self):
        self._reply(self.api.schema())

    @route("POST", "/cluster/resize/set-coordinator")
    def post_set_coordinator(self):
        self._reply(self.api.set_coordinator(self._json_body().get("id", "")))

    @route("GET", "/internal/nodes")
    def get_internal_nodes(self):
        self._reply(self.api.hosts())

    @route("GET", "/internal/fragment/nodes")
    def get_fragment_nodes(self):
        """Owner nodes of one shard (reference: handleGetFragmentNodes)."""
        index = self.query.get("index", "")
        shard = self._int_param("shard", 0)
        self._reply(self.api.shard_nodes(index, shard))

    @route(
        "DELETE",
        "/internal/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)"
        "/remote-available-shards/(?P<shard>[0-9]+)",
    )
    def delete_remote_available_shard(self, index: str, field: str, shard: str):
        self.api.delete_remote_available_shard(index, field, int(shard))
        self._reply({})

    @route("GET", "/metrics")
    def get_metrics(self):
        """Prometheus exposition (reference: http/handler.go:282).
        Device-cache residency gauges are refreshed at scrape time — they
        are cheap reads of counters the cache already keeps."""
        self.node.publish_cache_gauges()
        reg = getattr(self.node.stats, "registry", None)
        text = reg.prometheus_text() if reg is not None else ""
        self._reply(None, raw=text.encode(), content_type="text/plain; version=0.0.4")

    @route("GET", "/debug/vars")
    def get_debug_vars(self):
        """expvar-style dump (reference: http/handler.go:281)."""
        self.node.publish_cache_gauges()
        reg = getattr(self.node.stats, "registry", None)
        self._reply(reg.snapshot() if reg is not None else {})

    @route("GET", "/debug/timeline")
    def get_debug_timeline(self):
        """This node's utilization timeline ring (server/telemetry.py
        TimelineSampler): periodic snapshots of HBM residency, queue
        depth, in-flight bytes, ingest/query rates, and resize phase.
        `?sample=1` forces a fresh sample first (deterministic tests and
        point-in-time reads; the background ticker appends the rest)."""
        if self._bool_param("sample"):
            self.node.telemetry.sampler.sample_once()
        self._reply(self.node.telemetry.sampler.snapshot())

    @route("GET", "/internal/stats")
    def get_internal_stats(self):
        """Mergeable registry export for the federated rollup (raw
        histogram buckets included, so /cluster/metrics merges them
        bucket-wise into true cluster quantiles)."""
        self._reply(self.node.telemetry.local_stats_export())

    @route("GET", "/cluster/metrics")
    def get_cluster_metrics(self):
        """Prometheus exposition of the CLUSTER-merged registry: every
        member's counters/gauges summed, histograms merged bucket-wise
        (exact — shared bounds), down peers degraded to their last
        snapshot with `cluster.peer_stale{node=...} 1` markers."""
        text = self.node.telemetry.cluster_metrics_text()
        self._reply(
            None, raw=text.encode(),
            content_type="text/plain; version=0.0.4",
        )

    @route("GET", "/cluster/overview")
    def get_cluster_overview(self):
        """Per-node and per-index rollup JSON (queries, real merged
        p50/p99, ingest bits, HBM residency, in-flight bytes) with
        staleness markers for unreachable peers."""
        self._reply(self.node.telemetry.cluster_overview())

    @route("GET", "/cluster/timeline")
    def get_cluster_timeline(self):
        """Every member's /debug/timeline ring grouped by node (dead
        peers degrade to their cached ring, stale-marked)."""
        self._reply(self.node.telemetry.cluster_timeline())

    @route("GET", "/cluster/health")
    def get_cluster_health(self):
        """Structured health rollup: ok | degraded | critical with the
        reasons (peer reachability, breakers, repair debt, resize phase,
        WAL staging depth)."""
        self._reply(self.node.telemetry.cluster_health())

    @route("GET", "/debug/traces")
    def get_debug_traces(self):
        """Flat span ring by default; `?trace=<id>` assembles that
        trace's spans (local + ingested remote) into ONE tree with
        clamped windows and per-span self-times — the flight record."""
        trace_id = self.query.get("trace")
        if trace_id:
            self._reply(
                tracing.assemble(
                    self.node.tracer.spans_for(trace_id), trace_id
                )
            )
            return
        self._reply(self.node.tracer.to_json())

    @route("GET", "/debug/pprof")
    def get_debug_pprof(self):
        """On-demand CPU profile of a live node (reference:
        http/handler.go:281 net/http/pprof). Blocks for ?seconds=N
        (default 2, capped) while every query that executes runs under
        cProfile; replies with the aggregated pstats text."""
        from pilosa_tpu.server.profiling import ProfileWindowBusy

        seconds = self._int_param("seconds", 2)
        try:
            text = self.node.profiler.capture(seconds)
        except ProfileWindowBusy as e:
            self._error(str(e), 409)
            return
        self._reply(None, raw=text.encode(), content_type="text/plain")

    @route("GET", "/schema")
    def get_schema(self):
        self._reply({"indexes": self.api.schema()})

    @route("POST", "/schema")
    def post_schema(self):
        self.api.apply_schema(self._json_body().get("indexes", []))
        self._reply({})

    @route("GET", "/hosts")
    def get_hosts(self):
        self._reply(self.api.hosts())

    @route("POST", "/index/(?P<index>[^/]+)")
    def post_index(self, index: str):
        opts = self._json_body().get("options", {})
        self.api.create_index(
            index,
            keys=opts.get("keys", False),
            track_existence=opts.get("trackExistence", True),
        )
        self._reply({"success": True})

    @route("DELETE", "/index/(?P<index>[^/]+)")
    def delete_index(self, index: str):
        self.api.delete_index(index)
        self._reply({"success": True})

    @route("POST", "/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)")
    def post_field(self, index: str, field: str):
        opts = self._json_body().get("options", {})
        # accept the reference's camelCase public option names
        from pilosa_tpu.server.api import _field_options_from_json
        from dataclasses import asdict

        self.api.create_field(index, field, options=asdict(_field_options_from_json(opts)))
        self._reply({"success": True})

    @route("DELETE", "/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)")
    def delete_field(self, index: str, field: str):
        self.api.delete_field(index, field)
        self._reply({"success": True})

    @route("POST", "/index/(?P<index>[^/]+)/query")
    def post_query(self, index: str):
        with self._request_span(
            "query", force=self.query.get("profile", "") in ("1", "true")
        ) as span:
            self._post_query(index, span)

    def _post_query(self, index: str, span) -> None:
        body = self._body(span)
        ctype = (self.headers.get("Content-Type") or "").split(";")[0].strip()
        shards = None
        if ctype == "application/json":
            d = json.loads(body) if body else {}
            pql = d.get("query", "")
            shards = d.get("shards")
        else:
            pql = body.decode("utf-8")
            if "shards" in self.query:
                shards = self._int_list_param("shards")

        def flag(name: str, d: Optional[dict] = None) -> bool:
            if d is not None and name in d:
                return bool(d[name])
            return self.query.get(name, "") in ("1", "true")

        opts = d if ctype == "application/json" else None
        profile = flag("profile", opts)
        if profile:
            span.sampled = True  # asked for in the JSON body: known only now
        resp = self.api.query_response(
            index,
            pql,
            shards=shards,
            headers=self.headers,
            column_attrs=flag("columnAttrs", opts),
            exclude_row_attrs=flag("excludeRowAttrs", opts),
            exclude_columns=flag("excludeColumns", opts),
            profile=profile,
        )
        t = time.perf_counter()
        out = {"results": [wire.result_to_public_json(r) for r in resp.results]}
        if resp.column_attr_sets is not None:
            out["columnAttrs"] = [s.to_json() for s in resp.column_attr_sets]
        raw = json.dumps(out).encode()
        span.set_tag("http.encode_ms", _ms_since(t))
        if profile:
            # the tree, with this still-open root at its duration so far:
            # assembly, the tree's own encoding and the write lie after
            # it, so the tree of a request never holds them (the ring's
            # copy of the finished span does)
            tree = tracing.assemble_open(
                span, self.node.tracer.spans_for(span.trace_id)
            )
            raw = b'%s, "profile": %s}' % (raw[:-1], json.dumps(tree).encode())
        self._write_reply(span, raw=raw)

    @route("POST", "/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/import")
    def post_import(self, index: str, field: str):
        with self._request_span("import") as span:
            d = self._json_body(span)
            rows = d.get("rowKeys") or d.get("rows") or []
            cols = d.get("colKeys") or d.get("cols") or []
            summary = self.api.import_bits(
                index, field, rows, cols,
                clear=d.get("clear", False),
                timestamps=d.get("timestamps"),
            )
            self._write_reply(span, summary or {})

    @route("POST", "/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/import-value")
    def post_import_value(self, index: str, field: str):
        with self._request_span("import-value") as span:
            d = self._json_body(span)
            cols = d.get("colKeys") or d.get("cols") or []
            summary = self.api.import_values(
                index, field, cols, d.get("values", [])
            )
            self._write_reply(span, summary or {})

    @route(
        "POST",
        "/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/import-roaring/(?P<shard>[^/]+)",
    )
    def post_import_roaring(self, index: str, field: str, shard: str):
        """Zero-parse roaring ingest; body is a serialized roaring bitmap
        (reference route: http/handler.go import-roaring). shard and the
        boolean flags are coerced with the validating helpers: garbage
        -> 400 JSON naming the parameter, never a 500."""
        changed = self.api.import_roaring(
            index,
            field,
            self._int_path("shard", shard),
            self._body(),
            clear=self._bool_param("clear"),
            view=self.query.get("view"),
            local_only=self._bool_param("remote"),
        )
        self._reply({"changed": changed})

    @route(
        "GET",
        "/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/export-roaring/(?P<shard>[^/]+)",
    )
    def get_export_roaring(self, index: str, field: str, shard: str):
        data = self.api.export_roaring(
            index, field, self._int_path("shard", shard),
            view=self.query.get("view"),
        )
        self._reply(None, raw=data, content_type="application/octet-stream")

    @route("POST", "/recalculate-caches")
    def post_recalculate_caches(self):
        self.api.recalculate_caches()
        self._reply({})

    @route("GET", "/export")
    def get_export(self):
        index = self._str_param("index")
        field = self._str_param("field")
        shard = self._int_param("shard", None)
        csv = self.api.export_csv(index, field, shard)
        self._reply(None, raw=csv.encode(), content_type="text/csv")

    @route("GET", "/internal/shards/max")
    def get_max_shards(self):
        self._reply({"standard": self.api.max_shards()})

    @route("GET", "/index/(?P<index>[^/]+)/shard-nodes")
    def get_shard_nodes(self, index: str):
        self._reply(self.api.shard_nodes(index, self._int_param("shard")))

    # -- internal routes ---------------------------------------------------

    @route("POST", "/internal/index/(?P<index>[^/]+)/query")
    def post_internal_query(self, index: str):
        d = self._json_body()
        trace_id = self.headers.get(tracing.TRACE_HEADER)
        try:
            results = self.api.query(
                index,
                d.get("query", ""),
                shards=d.get("shards"),
                remote=d.get("remote", True),
                headers=self.headers,
            )
        except (ExecError, ApiError) as e:
            self._reply({"error": str(e)})
            return
        out = {"results": [wire.encode_result(r) for r in results]}
        if trace_id:
            # cross-node trace assembly: piggyback the spans this node
            # completed for the sender's trace so the coordinator can
            # assemble ONE tree (the sender dedupes by span id; cap the
            # payload so a hot trace cannot bloat every leg response)
            spans = self.node.tracer.spans_for(trace_id)
            if spans:
                out["spans"] = spans[-128:]
        self._reply(out)

    @route("POST", "/internal/versions")
    def post_internal_versions(self):
        """Result-cache revalidation (core/resultcache.py): the
        coordinator asks for this node's fragment-version vector for
        one call over a shard list — a cheap metadata read instead of a
        full leg execution. `views: null` = the call is cache-ineligible
        here (the coordinator then executes normally)."""
        d = self._json_body_dict()
        index = self._body_str(d, "index")
        pql = self._body_str(d, "query")
        shards = d.get("shards")
        if not isinstance(shards, list) or not all(
            isinstance(s, int) and not isinstance(s, bool) for s in shards
        ):
            raise BadParam("shards must be a list of integers")
        payload = self.node.executor.versions_payload(index, pql, shards)
        if payload is None:
            self._reply({"views": None})
            return
        shard_list, views = payload
        self._reply(
            {"boot": self.node.boot_id, "shards": shard_list, "views": views}
        )

    # -- cache coherence plane (pilosa_tpu/coherence/) ---------------------

    @route("POST", "/internal/coherence/lease")
    def post_coherence_lease(self):
        """Grant a coherence lease: the reply is a whole-index version
        snapshot the caller mirrors; pushed bumps keep it current. 404
        when leases are disabled here — the caller backs off to the
        plain /internal/versions revalidate path."""
        d = self._json_body_dict()
        mgr = self.node.coherence
        if mgr is None or not mgr.leases_enabled:
            raise NotFoundError("coherence leases disabled")
        g = mgr.grant(
            self._body_str(d, "node"),
            self._body_str(d, "node_uri"),
            self._body_str(d, "index"),
        )
        if g is None:
            raise NotFoundError(f"index not found: {d.get('index')}")
        self._reply(g)

    @route("POST", "/internal/coherence/publish")
    def post_coherence_publish(self):
        """Apply one batched version-bump payload to this node's lease
        mirror. `ok: false` (seq gap, boot mismatch, unknown grant)
        tells the publisher to drop the grant — the next query here
        re-leases from a fresh snapshot."""
        mgr = self.node.coherence
        if mgr is None:
            raise NotFoundError("coherence disabled")
        self._reply(mgr.apply_publish(self._json_body_dict()))

    @route("POST", "/subscriptions")
    def post_subscription(self):
        """Register a standing PQL program: the node pins its result
        entries and pushes updates on invalidation (long-polled via GET
        /subscriptions/<id>). Over-cap registration sheds 429 through
        the standard admission mapping."""
        d = self._json_body_dict()
        self._reply(
            self.api.subscribe(
                self._body_str(d, "index"), self._body_str(d, "query")
            )
        )

    @route("GET", "/subscriptions")
    def get_subscriptions(self):
        mgr = self.node.coherence
        if mgr is None or not mgr.subs_enabled:
            raise NotFoundError("subscriptions disabled")
        self._reply({"subscriptions": mgr.list_subscriptions()})

    @route("GET", "/subscriptions/(?P<sub_id>[^/]+)")
    def get_subscription(self, sub_id: str):
        """Long-poll one subscription: blocks until seq > `after`, the
        subscription closes, or `wait` seconds pass (capped server-side;
        a timeout returns the current seq with no result payload)."""
        mgr = self.node.coherence
        if mgr is None or not mgr.subs_enabled:
            raise NotFoundError("subscriptions disabled")
        after = self._int_param("after", -1)
        raw_wait = self.query.get("wait", "0")
        try:
            wait = float(raw_wait or 0)
        except ValueError:
            raise BadParam(
                f"query parameter 'wait' must be a number, got {raw_wait!r}"
            ) from None
        snap = mgr.poll(sub_id, after, wait)
        if snap is None:
            raise NotFoundError(f"subscription not found: {sub_id}")
        self._reply(snap)

    @route("DELETE", "/subscriptions/(?P<sub_id>[^/]+)")
    def delete_subscription(self, sub_id: str):
        mgr = self.node.coherence
        if mgr is None or not mgr.subs_enabled:
            raise NotFoundError("subscriptions disabled")
        if not mgr.unsubscribe(sub_id):
            raise NotFoundError(f"subscription not found: {sub_id}")
        self._reply({"success": True})

    @route("POST", "/internal/cluster/message")
    def post_cluster_message(self):
        self._reply(self.api.receive_message(self._json_body()))

    # -- cluster lifecycle (cluster.go:1141-1561; api.go:1226-1250) --------

    @route("POST", "/cluster/join")
    def post_cluster_join(self):
        d = self._json_body_dict()
        self._body_str(d, "id")
        self._body_str(d, "uri")
        self._reply(self.api.cluster_join(d))

    @route("POST", "/cluster/resize/remove-node")
    def post_remove_node(self):
        self._reply(self.api.remove_node(self._body_str(self._json_body_dict(), "id")))

    @route("POST", "/cluster/resize/abort")
    def post_resize_abort(self):
        self._reply(self.api.resize_abort())

    @route("GET", "/cluster/resize/job")
    def get_resize_job(self):
        self._reply(self.api.resize_job())

    @route("GET", "/internal/index/(?P<index>[^/]+)/available-shards")
    def get_available_shards(self, index: str):
        """Per-field cluster-known shards (the NodeStatus availableShards
        exchange of the reference's gossip state merge, gossip.go:295-362;
        here pulled over HTTP at anti-entropy time)."""
        idx = self.node.holder.index(index)
        if idx is None:
            raise NotFoundError(f"index not found: {index}")
        self._reply(
            {
                "fields": {
                    f.name: sorted(f.available_shards())
                    for f in idx.fields(include_hidden=True)
                }
            }
        )

    @route("GET", "/internal/index/(?P<index>[^/]+)/attrs/blocks")
    def get_attr_blocks(self, index: str):
        """Attr-store block checksums for anti-entropy diffing
        (reference: attr.go:90 AttrBlock, holder.go:975 syncIndex).
        ?field= selects a row attr store; absent = column attrs."""
        store = self._attr_store(index, self.query.get("field"))
        self._reply({"blocks": store.blocks()})

    @route("GET", "/internal/index/(?P<index>[^/]+)/attrs/block/(?P<block>[0-9]+)")
    def get_attr_block_data(self, index: str, block: str):
        store = self._attr_store(index, self.query.get("field"))
        self._reply({"attrs": {str(k): v for k, v in store.block_data(int(block)).items()}})

    def _attr_store(self, index: str, field):
        idx = self.node.holder.index(index)
        if idx is None:
            raise NotFoundError(f"index not found: {index}")
        if not field:
            return idx.column_attr_store
        f = idx.field(field)
        if f is None:
            raise NotFoundError(f"field not found: {field}")
        return f.row_attr_store

    @route("POST", "/internal/sync")
    def post_internal_sync(self):
        """Trigger one anti-entropy pass now (operational hook; the loop
        runs on anti-entropy.interval — server.go:514 monitorAntiEntropy).
        `ran` is false when a pass was already in flight (single-flight);
        `reached` lists the (index, shard, node) reconciliations the pass
        confirmed — the debt-nudge caller resolves exactly those."""
        res = self.node.try_sync_holder()
        if res is None:
            self._reply({"synced": 0, "ran": False})
            return
        synced, reached = res
        self._reply(
            {
                "synced": synced,
                "ran": True,
                "reached": [[i, s, d] for i, s, d in sorted(reached)],
            }
        )

    @route("POST", "/internal/resize")
    def post_internal_resize(self):
        """One node's step of a CHECKPOINT resize (the manual/bootstrap
        fallback): apply schema if supplied (joining nodes), then reshard
        to the new membership (cluster.go:1297 followResizeInstruction).
        The coordinator's job FSM uses /internal/resize/stream instead."""
        d = self._json_body_dict()
        nodes = self._body_nodes(d, "nodes")
        old_nodes = self._body_nodes(d, "oldNodes", required=False)
        replica_n = self._body_int(d, "replicaN")
        if d.get("schema"):
            self.api.apply_schema(d["schema"])
        fetched = self.node.resize_to(
            nodes, replica_n=replica_n, old_nodes=old_nodes,
            old_replica_n=self._body_int(d, "oldReplicaN"),
        )
        self._reply({"fetched": fetched})

    @route("POST", "/internal/resize/stream")
    def post_internal_resize_stream(self):
        """One node's STREAMING resize step: fetch every fragment the new
        placement assigns here (snapshot + live write capture on the
        source) and drain catch-up rounds — without touching the
        installed topology, so this node serves reads AND writes against
        the old placement throughout. Malformed bodies -> 400 JSON naming
        the field (import/export coercion convention)."""
        d = self._json_body_dict()
        job = self._body_str(d, "job")
        nodes = self._body_nodes(d, "nodes")
        old_nodes = self._body_nodes(d, "oldNodes", required=False)
        replica_n = self._body_int(d, "replicaN")
        old_replica_n = self._body_int(d, "oldReplicaN")
        post_commit = d.get("postCommit", False)
        if not isinstance(post_commit, bool):
            raise BadParam(
                f"body field 'postCommit' must be a boolean, got {post_commit!r}"
            )
        if d.get("schema"):
            self.api.apply_schema(d["schema"])
        self._reply(
            self.node.resize_stream(
                job, nodes, replica_n=replica_n, old_nodes=old_nodes,
                old_replica_n=old_replica_n, post_commit=post_commit,
            )
        )

    @route("POST", "/internal/resize/catchup")
    def post_internal_resize_catchup(self):
        """Cutover drain round: with the sources quiesced this empties
        every capture for this node's transferred fragments before the
        coordinator installs the new topology."""
        d = self._json_body_dict()
        job = self._body_str(d, "job")
        self._reply({"applied": self.node.resize_catchup(job)})

    @route("POST", "/internal/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/import")
    def post_internal_import(self, index: str, field: str):
        """Replica-side bulk import. Body is either the binary array
        stream (rows, cols; clear via ?clear=1) or JSON — timestamped
        (time-field) imports stay JSON (http/client.go:319 protobuf body
        analog)."""
        ctype = (self.headers.get("Content-Type") or "").split(";")[0].strip()
        if ctype == wire.ARRAYS_CTYPE:
            rows, cols = wire.decode_arrays(self._body(), 2)
            self.api.import_bits(
                index, field, rows, cols,
                clear=self._bool_param("clear"),
                local_only=True,
            )
        else:
            d = self._json_body()
            self.api.import_bits(
                index, field, d.get("rows", []), d.get("cols", []),
                clear=d.get("clear", False),
                timestamps=d.get("timestamps"),
                local_only=True,
            )
        self._reply({})

    @route("POST", "/internal/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/import-value")
    def post_internal_import_value(self, index: str, field: str):
        ctype = (self.headers.get("Content-Type") or "").split(";")[0].strip()
        if ctype == wire.ARRAYS_CTYPE:
            cols, vals_u64 = wire.decode_arrays(self._body(), 2)
            # values travel as uint64 two's-complement (BSI values are signed)
            self.api.import_values(
                index, field, cols, vals_u64.view(np.int64), local_only=True
            )
        else:
            d = self._json_body()
            self.api.import_values(
                index, field, d.get("cols", []), d.get("values", []), local_only=True
            )
        self._reply({})

    def _fragment(self):
        index = self._str_param("index")
        idx = self.node.holder.index(index)
        if idx is None:
            raise NotFoundError(f"index not found: {index}")
        field = self._str_param("field")
        f = idx.field(field)
        if f is None:
            raise NotFoundError(f"field not found: {field}")
        v = f.views.get(self.query.get("view", "standard"))
        if v is None:
            return None
        return v.fragment_if_exists(self._int_param("shard"))

    @route("GET", "/internal/fragment/blocks")
    def get_fragment_blocks(self):
        frag = self._fragment()
        sums = frag.block_checksums() if frag is not None else {}
        self._reply({"blocks": {str(k): v.hex() for k, v in sums.items()}})

    @route("GET", "/internal/fragment/block/data")
    def get_block_data(self):
        binary = wire.ARRAYS_CTYPE in (self.headers.get("Accept") or "")
        block = self._int_param("block")  # validate even for absent frags
        frag = self._fragment()
        if frag is None:
            rows = cols = np.zeros(0, np.uint64)
        else:
            rows, cols = frag.block_pairs(block)
        if binary:
            self._reply(
                None,
                raw=wire.encode_arrays(rows, cols),
                content_type=wire.ARRAYS_CTYPE,
            )
        else:
            self._reply({"rows": rows.tolist(), "cols": cols.tolist()})

    @route("POST", "/internal/fragment/block/deltas")
    def post_block_deltas(self):
        ctype = (self.headers.get("Content-Type") or "").split(";")[0].strip()
        if ctype == wire.ARRAYS_CTYPE:
            d = dict(self.query)
            sr, sc, cr, cc = wire.decode_arrays(self._body(), 4)
            sets, clears = (sr, sc), (cr, cc)
        else:
            d = self._json_body()
            sets = (
                np.array(d["sets"]["rows"], np.uint64),
                np.array(d["sets"]["cols"], np.uint64),
            )
            clears = (
                np.array(d["clears"]["rows"], np.uint64),
                np.array(d["clears"]["cols"], np.uint64),
            )
        idx = self.node.holder.index(d["index"])
        if idx is None:
            raise NotFoundError(f"index not found: {d['index']}")
        f = idx.field(d["field"])
        if f is None:
            raise NotFoundError(f"field not found: {d['field']}")
        v = f._view_create(d.get("view", "standard"))
        frag = v.fragment(int(d["shard"]))
        frag.apply_deltas(sets, clears)
        self._reply({})

    @route("GET", "/internal/fragment/data")
    def get_fragment_data(self):
        """Full-fragment snapshot. With `?capture=<job>` (streaming
        resize phase 1) the snapshot and a live write capture arm
        atomically, and the serving rides the batch admission lane so a
        rebalance cannot starve interactive queries."""
        capture = self.query.get("capture")
        ticket = self._admit_transfer() if capture else None
        try:
            frag = self._fragment()
            if frag is None:
                self._error("fragment not found", 404)
                return
            if capture:
                key = (
                    self.query["index"],
                    self.query["field"],
                    self.query.get("view", "standard"),
                    self._int_param("shard"),
                )
                blob = self.node.begin_fragment_capture(capture, key, frag)
            else:
                blob = frag.to_bytes()
            self._reply(None, raw=blob, content_type="application/octet-stream")
        finally:
            if ticket is not None:
                ticket.release()

    @route("GET", "/internal/fragment/delta")
    def get_fragment_delta(self):
        """Drain one transfer leg's captured writes (WAL-framed bytes;
        streaming resize phase 2). 410 Gone when the capture is lost
        (lease expiry, overflow, source restart) — the destination must
        refetch the full snapshot."""
        from pilosa_tpu.core.fragment import TransferCaptureLost

        job = self._str_param("job")
        key = (
            self._str_param("index"),
            self._str_param("field"),
            self.query.get("view", "standard"),
            self._int_param("shard"),
        )
        ticket = self._admit_transfer()
        try:
            try:
                data = self.node.drain_fragment_capture(job, key)
            except TransferCaptureLost as e:
                self._error(str(e), 410)
                return
            self._reply(
                None, raw=data, content_type="application/octet-stream"
            )
        finally:
            if ticket is not None:
                ticket.release()

    # -- tiered storage (object-store cold fragments) ----------------------

    def _tier(self):
        tier = self.node.tier
        if tier is None:
            raise NotFoundError("tiered storage is not enabled on this node")
        return tier

    def _tier_view(self):
        """Resolve the (view, shard) a tier control call names; 400 on
        malformed params (naming the parameter), 404 on unknown
        index/field/view."""
        iname = self._str_param("index")
        fname = self._str_param("field")
        vname = self.query.get("view", "standard")
        shard = self._int_param("shard")
        idx = self.node.holder.index(iname)
        if idx is None:
            raise NotFoundError(f"index not found: {iname}")
        f = idx.field(fname)
        if f is None:
            raise NotFoundError(f"field not found: {fname}")
        v = f.views.get(vname)
        if v is None:
            raise NotFoundError(f"view not found: {vname}")
        return v, shard

    @route("GET", "/internal/tier/status")
    def get_tier_status(self):
        self._reply(self._tier().status())

    @route("GET", "/internal/tier/offer")
    def get_tier_offer(self):
        """Snapshot-bootstrap offer for one transfer leg (see
        NodeServer.tier_offer). Deliberately NOT 404 on untiered nodes:
        a mixed cluster answers {"mode": "stream"} so the joiner falls
        back without special-casing."""
        iname = self._str_param("index")
        fname = self._str_param("field")
        vname = self.query.get("view", "standard")
        shard = self._int_param("shard")
        tag = self._str_param("tag")
        self._reply(self.node.tier_offer(iname, fname, vname, shard, tag))

    @route("POST", "/internal/tier/demote")
    def post_tier_demote(self):
        """Manually demote one fragment to the object store. 200 with
        demoted=false when the demote was skipped or aborted (already
        cold, already in flight, or a write raced the upload)."""
        tier = self._tier()
        v, shard = self._tier_view()
        frag = v.fragments.get(shard)
        if frag is None:
            already = tier.is_cold(v, shard)
            self._reply({"demoted": False, "cold": already})
            return
        ok = tier.demote_fragment(v, frag, reason="manual")
        self._reply({"demoted": bool(ok), "cold": tier.is_cold(v, shard)})

    @route("POST", "/internal/tier/hydrate")
    def post_tier_hydrate(self):
        """Manually hydrate one cold fragment (prewarm). Rides the same
        single-flight path as a cold query."""
        tier = self._tier()
        v, shard = self._tier_view()
        frag = tier.hydrate(v, shard)
        self._reply({"hydrated": frag is not None,
                     "cold": tier.is_cold(v, shard)})

    @route("POST", "/internal/tier/placement")
    def post_tier_placement(self):
        """Set (or clear, with placement="") one index's placement
        override; 400 names the malformed field."""
        tier = self._tier()
        d = self._json_body_dict()
        index = self._body_str(d, "index")
        placement = d.get("placement")
        if not isinstance(placement, str):
            raise BadParam(
                f"body field 'placement' must be a string, got {placement!r}"
            )
        if placement == "":
            tier.policy.drop_index(index)
        else:
            try:
                tier.policy.set_override(index, placement)
            except ValueError as e:
                raise BadParam(str(e)) from None
        self._reply({"index": index,
                     "placement": tier.policy.placement(index)})

    @route("POST", "/internal/tier/sync")
    def post_tier_sync(self):
        """Run one snapshot-sync pass (anti-entropy over stored
        objects); ?deep=true verifies stored bytes by checksum and
        re-uploads corrupt/torn objects."""
        tier = self._tier()
        deep = self._bool_param("deep", False)
        self._reply(tier.sync_snapshots(deep=deep))

    @route("POST", "/internal/translate/keys")
    def post_translate_keys(self):
        d = self._json_body()
        idx = self.node.holder.index(d["index"])
        if idx is None:
            raise NotFoundError(f"index not found: {d['index']}")
        store = idx.translate_store
        if d.get("field"):
            f = idx.field(d["field"])
            if f is None:
                raise NotFoundError(f"field not found: {d['field']}")
            store = f.translate_store
        coord = self.node.cluster.coordinator()
        if coord is not None and coord.id != self.node.node.id:
            self._reply({"error": "not the translation primary"})
            return
        self._reply({"ids": store.translate_keys(d.get("keys", []))})

    @route("GET", "/internal/index/(?P<index>[^/]+)/fragments")
    def get_fragment_inventory(self, index: str):
        idx = self.node.holder.index(index)
        if idx is None:
            raise NotFoundError(f"index not found: {index}")
        frags = []
        for f in idx.fields(include_hidden=True):
            for vname, v in f.views.items():
                for shard in sorted(v.fragments):
                    frags.append([f.name, vname, shard])
        self._reply({"frags": frags})

    @route("GET", "/internal/translate/data")
    def get_translate_data(self):
        index = self._str_param("index")
        idx = self.node.holder.index(index)
        if idx is None:
            raise NotFoundError(f"index not found: {index}")
        store = idx.translate_store
        if "field" in self.query:
            f = idx.field(self.query["field"])
            if f is None:
                raise NotFoundError(f"field not found: {self.query['field']}")
            store = f.translate_store
        entries, offset = store.entries_since(self._int_param("offset", 0))
        self._reply({"entries": entries, "offset": offset})


class NodeHTTPServer(ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True


def make_http_server(node_server, host: str, port: int) -> NodeHTTPServer:
    srv = NodeHTTPServer((host, port), Handler)
    srv.node_server = node_server
    return srv
