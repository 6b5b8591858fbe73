"""A reader of one PQL call, for every form a read can take (the grammar
`pilosa_tpu/pql/parser.py` accepts, read again here so that the yardstick
shares nothing with the program): nested calls with positional children,
`key=value` arguments whose value is a number, a word, a quoted string, a
timestamp, `true` / `false` / `null`, a list or a call (`filter=Row(a=1)`,
`aggregate=Sum(field=v)`), and conditions on an int field
(`Row(v > 5)`, `Row(3 <= v < 9)`).

    Sum(Row(passenger_count=3), field=total_amount)
      -> Call("Sum", (Call("Row", (), {"passenger_count": 3}),),
              {"field": "total_amount"})
    Row(3 <= v < 9) -> Call("Row", (), {"v": Cond("><", [3, 8])})

The reference, `work.py` and a dialect walk the same tree the request's
text gives; which of it the base knows is `lib/dialects/__init__.py`'s."""

from __future__ import annotations

import re
from typing import NamedTuple

_TOKEN = re.compile(r"""\s*(?:
    (?P<time>\d{4}-[01]\d-[0-3]\dT\d\d:\d\d)(?![A-Za-z0-9:_-])
  | (?P<num>-?(?:\d+(?:\.\d*)?|\.\d+))(?![A-Za-z0-9:_-])
  | (?P<word>[A-Za-z0-9_][A-Za-z0-9:_-]*)
  | "(?P<dq>(?:[^"\\]|\\.)*)"
  | '(?P<sq>(?:[^'\\]|\\.)*)'
  | (?P<op>><|<=|>=|==|!=|<|>)
  | (?P<punct>[(),=\[\]])
)""", re.X)
_KEYWORDS = {"true": True, "false": False, "null": None}
_NAME = re.compile(r"[A-Za-z_][A-Za-z0-9_-]*\Z")


class Call(NamedTuple):
    name: str
    children: tuple  # of Call, or a bare value (TopN's and Rows' field)
    args: dict  # key -> int | float | str | bool | None | list | Call | Cond


class Cond(NamedTuple):
    """A condition on an int field, the value of `args[field]` in a `Row`.
    `op` is one of > >= < <= == != and "><" (between, both ends included:
    `lo < f <= hi` is stored as "><" [lo + 1, hi], as the program reads
    it)."""

    op: str
    value: object  # int, None (`!= null`), or [lo, hi] for "><"


class _Tok(NamedTuple):
    kind: str  # num, word, str, time, op, or the punctuation itself
    value: object


def _tokens(text: str) -> list:
    out, pos = [], 0
    while pos < len(text):
        m = _TOKEN.match(text, pos)
        if m is None:
            if text[pos:].strip():
                raise ValueError(f"cannot read PQL at {text[pos:].lstrip()!r}")
            break
        pos = m.end()
        kind = m.lastgroup
        raw = m.group(kind)
        if kind == "num":
            out.append(_Tok("num", float(raw) if "." in raw else int(raw)))
        elif kind in ("dq", "sq"):
            out.append(_Tok("str", re.sub(r"\\(.)", r"\1", raw)))
        elif kind == "punct":
            out.append(_Tok(raw, raw))
        else:
            out.append(_Tok(kind, raw))
    out.append(_Tok("end", None))
    return out


def parse(text: str) -> Call:
    """One call (a request of the benchmark holds exactly one)."""
    tokens = _tokens(text)
    call, rest = _call(tokens, 0, text)
    if tokens[rest].kind != "end":
        raise ValueError(f"trailing PQL after one call: {text!r}")
    return call


def _expect(tokens: list, i: int, kind: str, text: str) -> int:
    if tokens[i].kind != kind:
        raise ValueError(
            f"expected {kind!r}, found {tokens[i].value!r} in {text!r}")
    return i + 1


def _call(tokens: list, i: int, text: str):
    name = tokens[i].value
    if tokens[i].kind != "word" or not _NAME.match(name):
        raise ValueError(f"expected a call, found {name!r} in {text!r}")
    i = _expect(tokens, i + 1, "(", text)
    children, args = [], {}

    def put(key, value):
        if key in args:
            raise ValueError(f"duplicate argument {key!r} in {text!r}")
        args[key] = value

    while tokens[i].kind != ")":
        if tokens[i].kind == "end":
            raise ValueError(f"unbalanced call: no ')' closes {name}( in {text!r}")
        tok, after = tokens[i], tokens[i + 1]
        if tok.kind == "word" and after.kind == "(":
            child, i = _call(tokens, i, text)
            children.append(child)
        elif tok.kind == "word" and after.kind == "=":
            value, i = _value(tokens, i + 2, text)
            put(tok.value, value)
        elif tok.kind == "word" and after.kind == "op":
            value, i = _value(tokens, i + 2, text)
            put(tok.value, Cond(after.value, value))
        elif tok.kind == "num" and after.kind == "op":
            i = _between(tokens, i, put, text)
        elif tok.kind in ("word", "num", "str", "time"):
            children.append(_scalar(tok))
            i += 1
        else:
            raise ValueError(f"cannot read {tok.value!r} in {text!r}")
        if tokens[i].kind == ",":
            i += 1
        elif tokens[i].kind not in (")", "end"):
            raise ValueError(
                f"expected ',' or ')', found {tokens[i].value!r} in {text!r}")
    return Call(name, tuple(children), args), i + 1


def _scalar(tok: _Tok):
    if tok.kind == "word" and tok.value in _KEYWORDS:
        return _KEYWORDS[tok.value]
    return tok.value


def _value(tokens: list, i: int, text: str):
    """The value of an argument or a condition, and the index after it."""
    tok = tokens[i]
    if tok.kind == "[":
        items, i = [], i + 1
        while tokens[i].kind != "]":
            item, i = _value(tokens, i, text)
            items.append(item)
            if tokens[i].kind == ",":
                i += 1
            elif tokens[i].kind != "]":
                raise ValueError(f"unbalanced list in {text!r}")
        return items, i + 1
    if tok.kind == "word" and tokens[i + 1].kind == "(":
        return _call(tokens, i, text)
    if tok.kind in ("word", "num", "str", "time"):
        return _scalar(tok), i + 1
    raise ValueError(f"expected a value, found {tok.value!r} in {text!r}")


def _between(tokens: list, i: int, put, text: str) -> int:
    """`lo < f <= hi`: strict ends move inward to an inclusive pair."""
    lo, op1, field, op2, hi = (t.value for t in tokens[i:i + 5])
    kinds = [t.kind for t in tokens[i:i + 5]]
    if kinds != ["num", "op", "word", "op", "num"] or not (
        {op1, op2} <= {"<", "<="}
        and isinstance(lo, int) and isinstance(hi, int)
    ):
        raise ValueError(f"expected `lo < field < hi` in {text!r}")
    put(field, Cond("><", [lo + (op1 == "<"), hi - (op2 == "<")]))
    return i + 5


# ---------------------------------------------------------------------------
# walking the tree
# ---------------------------------------------------------------------------

ROW_TIME_ARGS = ("from", "to")  # in a `Row`, every other key names a field


def calls(call: Call):
    """This call and every call under it: children, and the values of
    arguments (`filter=`, `aggregate=`)."""
    yield call
    for c in list(call.children) + list(call.args.values()):
        if isinstance(c, Call):
            yield from calls(c)


def row_refs(call: Call) -> set:
    """Every distinct (field, row) the tree names through `Row(f=r)`;
    a condition (`Row(v > 5)`) names no row: see `cond_fields`."""
    return {
        (k, v) for c in calls(call) if c.name == "Row"
        for k, v in c.args.items()
        if k not in ROW_TIME_ARGS and not isinstance(v, (Cond, Call, list))
    }


def cond_fields(call: Call) -> set:
    """Every int field a condition of the tree reads."""
    return {
        k for c in calls(call) if c.name == "Row"
        for k, v in c.args.items() if isinstance(v, Cond)
    }
