"""Logical bytes a request has to read, from its text and the
configuration's sizes alone: every distinct row the request names is read
once, dense, over every shard — rows x shards x (shard width / 8) bytes.
It is the same number whatever implements the query, so a roofline share
built on it compares implementations. Every kernel behind these requests
(AND / OR / ANDNOT / popcount, plane walks) is bound by memory bandwidth,
not by arithmetic."""

from __future__ import annotations

from . import pql


def _field(config: dict, name: str) -> dict:
    for spec in config["fields"]:
        if spec["name"] == name:
            return spec
    raise KeyError(f"no field {name!r} in the configuration")


def field_rows(config: dict, name: str) -> int:
    """Device rows one field holds: a set field's rows; an int field's
    magnitude planes plus its exists and sign rows."""
    spec = _field(config, name)
    if spec["type"] == "int":
        return max(abs(spec["min"]), abs(spec["max"])).bit_length() + 2
    return len(spec["shares"]) if "shares" in spec else spec["rows"]


def row_bytes(config: dict) -> int:
    """One row over every shard, dense."""
    return config["shards"] * (1 << config["shard_width_exponent"]) // 8


def request_rows(config: dict, text: str) -> int:
    call = pql.parse(text)
    rows = len(pql.row_refs(call))
    if call.name == "Sum":
        rows += field_rows(config, call.args["field"])
    elif call.name == "TopN":
        rows += field_rows(config, call.children[0])
    elif call.name == "GroupBy":
        rows += sum(field_rows(config, r.children[0]) for r in call.children)
    elif call.name != "Count":
        raise ValueError(f"no work rule for {call.name}")
    return rows


def request_bytes(config: dict, text: str) -> int:
    return request_rows(config, text) * row_bytes(config)
