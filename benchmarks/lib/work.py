"""Logical bytes a request has to read, from its text and the
configuration's sizes alone: every distinct row the request names is read
once, dense, over every shard — rows x shards x (shard width / 8) bytes.
It is the same number whatever implements the query, so a roofline share
built on it compares implementations. Every kernel behind these requests
(AND / OR / ANDNOT / popcount, plane walks) is bound by memory bandwidth,
not by arithmetic.

The rules of the base language: a `Row(f=r)` anywhere in the request is one
row; `TopN` and each `Rows` of a `GroupBy` read every row of their field;
`Sum` / `Min` / `Max` read their int field's planes, and so does a
condition (`Row(v > 5)`), once for the request however many conditions and
aggregates name the field. A request that holds a form of the
configuration's dialect is counted by the dialect's `request_rows`, a field
of a dialect's kind by its `field_rows`; anything else raises by name."""

from __future__ import annotations

from . import pql
from .dialects import BASE_KINDS, NONE, Unknown, foreign, kind


def _field(config: dict, name: str) -> dict:
    for spec in config["fields"]:
        if spec["name"] == name:
            return spec
    raise KeyError(f"no field {name!r} in the configuration")


def field_rows(config: dict, name: str, dialect=NONE) -> int:
    """Device rows one field holds: a set field's rows; an int field's
    magnitude planes plus its exists and sign rows."""
    spec = _field(config, name)
    if kind(spec) not in BASE_KINDS:
        return dialect.hook("field_rows", kind(spec))(spec)
    if spec["type"] == "int":
        return max(abs(spec["min"]), abs(spec["max"])).bit_length() + 2
    return len(spec["shares"]) if "shares" in spec else spec["rows"]


def row_bytes(config: dict) -> int:
    """One row over every shard, dense."""
    return config["shards"] * (1 << config["shard_width_exponent"]) // 8


def whole_fields(call: pql.Call) -> set:
    """The fields a request of the base language reads whole."""
    fields = pql.cond_fields(call)
    if call.name in ("Sum", "Min", "Max"):
        fields.add(call.args["field"])
    elif call.name == "TopN":
        fields.add(call.children[0])
    elif call.name == "GroupBy":
        fields.update(r.children[0] for r in call.children)
    elif call.name != "Count":
        raise Unknown(f"no work rule for a top-level {call.name}")
    return fields


def request_rows(config: dict, text: str, dialect=NONE) -> int:
    def base_rows(call: pql.Call) -> int:
        return len(pql.row_refs(call)) + sum(
            field_rows(config, f, dialect) for f in whole_fields(call)
        )

    call = pql.parse(text)
    what = [w for c in pql.calls(call) for w in foreign(c)]
    if what:
        return dialect.hook("request_rows", *dict.fromkeys(what))(
            config, call, base_rows)
    return base_rows(call)


def request_bytes(config: dict, text: str, dialect=NONE) -> int:
    return request_rows(config, text, dialect) * row_bytes(config)
