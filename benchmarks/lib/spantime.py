"""Where a request's host time goes, from its `?profile=1` span tree.

The reader of the metrics whose source says `"module": "spantime"`:

    {"module": "spantime", "spans": ["api.query", "exec.batch", "exec.call"],
     "field": "selfMs"}
    {"module": "spantime", "spans": ["http.request"], "field": "durationMs",
     "from_wall": true}

The value is the mean over the answered requests of the summed `field`
(`selfMs`, a span's duration less its children's, or `durationMs`) of
every span of the request's tree whose name is listed; a request that has
none of them counts 0.0, so that metrics over disjoint sets of names add
up: over all the names a tree holds, the `selfMs` metrics sum to the mean
duration of the root. With `from_wall` the sum is taken from the
request's wall time at the client instead (what lies outside the spans:
socket, and whatever the server does before the root opens and after the
tree was assembled).

The program roots a served request's tree in an `http.request` span. A
program from before that span has nothing here to read: where no answered
request holds one, `read` returns None for every metric and the line
leaves them out. That may not be an error: the driver lays these files
over the parent commit of the PR that brought them and asks for no more
than silence there. But a root renamed or lost later must not make eight
metrics vanish unseen, so trees that lack the root are named on stderr.
"""

from __future__ import annotations

import statistics
import sys

from .readers import spans

ROOT = "http.request"


def request_ms(roots: list, names, field: str) -> float:
    """Summed `field` of the spans named, over one request's tree."""
    return sum(s[field] for name in names for s in spans(roots, name))


def read(source: dict, ctx):
    names, field = sorted(set(source["spans"])), source["field"]
    if field not in ("selfMs", "durationMs"):
        raise ValueError(f"unknown span field {field!r}")
    requests = ctx.requests
    if not any(s["name"] == ROOT for r in requests for s in r["roots"]):
        rooted = sorted({s["name"] for r in requests for s in r["roots"]})
        if rooted:
            print(
                f"spantime: {'+'.join(names)} not read: no request's tree is "
                f"rooted in {ROOT} (roots: {', '.join(rooted)})",
                file=sys.stderr,
            )
        return None
    per_request = [request_ms(r["roots"], names, field) for r in requests]
    if source.get("from_wall"):
        per_request = [
            r["wall_ms"] - ms for r, ms in zip(requests, per_request)
        ]
    return statistics.fmean(per_request)
