"""The per-layer metrics' readers. A metric is a file `metrics/<name>.json`
whose `source` names one of the reducers below and its parameters; a metric
that needs a new reducer brings a new module (`"module"` in its source,
found beside this one, with a `read(source, ctx)` of its own). A reader
that finds nothing to read returns None and the metric is left out."""

from __future__ import annotations

import importlib
import json
import os
import statistics

from . import trace as tracelib
from . import work
from .dialects import NONE


class Context:
    """What a traced run gives the readers: the answered requests with
    their span trees (`?profile=1`), counter readings around the window,
    and the device trace of the slice."""

    def __init__(self, config, answered, before, after, kind, require_peak,
                 slice_, planes, dialect=NONE):
        self.config, self.dialect = config, dialect
        self.requests = [
            {"text": r.text, "wall_ms": (r.received - r.sent) * 1000.0,
             "received": r.received,
             "roots": (body.get("profile") or {}).get("roots", [])}
            for r, body in answered
        ]
        self.before, self.after = before, after
        self.slice = slice_
        reduced = tracelib.reduce(planes, slice_[1] - slice_[0])
        if reduced is None:
            raise RuntimeError("no operation ran on the device in the trace")
        self.trace = reduced
        with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
            peaks = json.load(f)
        if kind not in peaks and require_peak:
            raise RuntimeError(f"no peaks known for device kind {kind!r}")
        self.peak = peaks.get(kind)


def spans(roots: list, name: str):
    for s in roots:
        if s["name"] == name:
            yield s
        yield from spans(s.get("children", ()), name)


def did_device_work(roots: list) -> bool:
    """A dispatch of its own, or a ride in another request's merged
    dispatch (the batcher's `batched` role); a result-cache or rank-cache
    answer shows neither."""
    if next(spans(roots, "exec.dispatch"), None) is not None:
        return True
    return any(
        s["tags"].get("batcher.role") == "batched" and not s["tags"].get("cache.hit")
        for s in spans(roots, "exec.batch")
    )


def _span(source: dict, ctx: Context):
    name, reduce = source["span"], source["reduce"]
    per_request = []
    for r in ctx.requests:
        found = list(spans(r["roots"], name))
        if reduce == "count_per_request":
            per_request.append(len(found))
        elif reduce == "mean_tag_per_request":
            per_request.append(
                sum(s["tags"].get(source["tag"], 0.0) for s in found)
            )
        else:
            raise ValueError(f"unknown span reducer {reduce!r}")
    if not per_request:
        return None
    return statistics.fmean(per_request)


def _counter(source: dict, ctx: Context):
    def delta(stat):
        if stat not in ctx.after:
            return None
        return ctx.after[stat] - ctx.before.get(stat, 0)

    reduce = source["reduce"]
    if reduce == "share_pct":  # stat / (stat + others), as a percentage
        top = delta(source["stat"])
        rest = [delta(s) for s in source["others"]]
        if top is None or None in rest or top + sum(rest) == 0:
            return None
        return 100.0 * top / (top + sum(rest))
    if reduce == "delta_per_request":
        d = delta(source["stat"])
        if d is None or not ctx.requests:
            return None
        return d * source.get("scale", 1.0) / len(ctx.requests)
    raise ValueError(f"unknown counter reducer {reduce!r}")


def _trace(source: dict, ctx: Context):
    reduce = source["reduce"]
    busy, window = ctx.trace["busy_s"], ctx.trace["window_s"]
    if reduce == "idle_pct":
        return 100.0 * (1.0 - busy / window)
    if reduce == "hbm_roofline_pct":
        if ctx.peak is None or busy <= 0:
            return None
        t0, t1 = ctx.slice
        logical = sum(
            work.request_bytes(ctx.config, r["text"], ctx.dialect)
            for r in ctx.requests
            if t0 <= r["received"] <= t1 and did_device_work(r["roots"])
        )
        if not logical:
            return None
        return 100.0 * logical / ctx.peak["hbm_bytes_per_s"] / busy
    raise ValueError(f"unknown trace reducer {reduce!r}")


KINDS = {"span": _span, "counter": _counter, "trace": _trace}


def read(spec: dict, ctx: Context):
    source = spec["source"]
    if "module" in source:
        mod = importlib.import_module(f"{__package__}.{source['module']}")
        return mod.read(source, ctx)
    return KINDS[source["kind"]](source, ctx)
