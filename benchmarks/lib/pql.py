"""A reader for the subset of PQL the traffic mixes use: nested calls with
positional children and `key=value` arguments, e.g.
`Sum(Row(passenger_count=3), field=total_amount)`. The reference and
`work.py` walk the same tree the request's text gives."""

from __future__ import annotations

import re
from typing import NamedTuple

_TOKEN = re.compile(r"\s*([A-Za-z_][A-Za-z0-9_]*|-?\d+|[(),=])")


class Call(NamedTuple):
    name: str
    children: tuple  # of Call, or str for a bare word (TopN's field)
    args: dict  # key -> int | str


def parse(text: str) -> Call:
    """One call (a request of the benchmark holds exactly one)."""
    tokens = []
    pos = 0
    while pos < len(text):
        m = _TOKEN.match(text, pos)
        if m is None:
            if text[pos:].strip():
                raise ValueError(f"cannot read PQL at {text[pos:]!r}")
            break
        tokens.append(m.group(1))
        pos = m.end()
    call, rest = _call(tokens, 0)
    if rest != len(tokens):
        raise ValueError(f"trailing PQL after one call: {text!r}")
    return call


def _value(tok: str):
    return int(tok) if re.fullmatch(r"-?\d+", tok) else tok


def _call(tokens: list, i: int):
    name = tokens[i]
    if tokens[i + 1] != "(":
        raise ValueError(f"expected '(' after {name}")
    i += 2
    children, args = [], {}
    while tokens[i] != ")":
        if tokens[i] == ",":
            i += 1
            continue
        if tokens[i + 1] == "(":
            child, i = _call(tokens, i)
            children.append(child)
        elif tokens[i + 1] == "=":
            args[tokens[i]] = _value(tokens[i + 2])
            i += 3
        else:
            children.append(_value(tokens[i]))
            i += 1
    return Call(name, tuple(children), args), i + 1


def row_refs(call: Call) -> set:
    """Every distinct (field, row id) a call names through `Row(f=r)`."""
    out = set()
    if call.name == "Row":
        out.update(call.args.items())
    for c in call.children:
        if isinstance(c, Call):
            out |= row_refs(c)
    return out
