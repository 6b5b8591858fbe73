"""The plain reference: the same operations on the same seeded data with
numpy boolean algebra over the populated columns (the semantics of the
smoke's `Reference`, which worked over sorted column-id arrays; a mask per
row is the same set and lets every answer of a window be checked in less
time than the window took). Shares no code with `pilosa_tpu/`.

What it reads is the base language (`lib/dialects/__init__.py` `BASE`):
`Count` of a bitmap call, `TopN(field[, filter], n=)`, `Sum` / `Min` /
`Max([filter,] field=)`, `GroupBy(Rows(f), ..., filter=, limit=)`; a bitmap
call is `Row(f=r)`, `Row(<int field> <condition>)`, `Intersect`, `Union`,
`Difference`, `Xor`, `Not`. Any other call or argument is the
configuration's dialect's, asked only for what the base refuses, or an
`Unknown` that names it: no argument is ever read past."""

from __future__ import annotations

import numpy as np

from . import pql
from .data import Data
from .dialects import Unknown, foreign

_COMPARE = {
    ">": np.greater, ">=": np.greater_equal, "<": np.less,
    "<=": np.less_equal, "==": np.equal, "!=": np.not_equal,
}


def _foreign_to_answer(call: pql.Call) -> list:
    """What of a request's outer call the base does not answer: the call,
    its arguments, and the arguments of a GroupBy's `Rows`."""
    what = foreign(call)
    if call.name == "GroupBy":
        for c in call.children:
            if isinstance(c, pql.Call) and c.name == "Rows":
                what += foreign(c)
    return what


class Reference:
    def __init__(self, data: Data, visible=None):
        """`visible` (bool[n], or None for all) is the set of columns the
        answers are computed over: all of them, but for the control, which
        leaves some out (`control.py`)."""
        self.data = data
        self.dialect = data.dialect
        self.visible = visible
        # columns written after the load (the read-your-writes check):
        # field -> row -> set of new column ids
        self.extra = {}
        self._memo = {}
        self._parsed = {}

    def parse(self, text: str) -> pql.Call:
        if text not in self._parsed:
            self._parsed[text] = pql.parse(text)
        return self._parsed[text]

    # -- set algebra -------------------------------------------------------

    def universe(self) -> np.ndarray:
        """Every column the answers are computed over (all of them exist)."""
        if self.visible is not None:
            return self.visible
        return np.ones(self.data.n, dtype=bool)

    def mask(self, call: pql.Call) -> np.ndarray:
        """bool[n]: the visible populated columns a bitmap call selects."""
        what = foreign(call)
        if what:
            return self.dialect.hook("mask", *what)(self, call)
        if call.name == "Row":
            return self._row(call)
        kids = [self.mask(c) for c in call.children]
        if call.name == "Intersect":
            return np.logical_and.reduce(kids)
        if call.name == "Union":
            return np.logical_or.reduce(kids)
        if call.name == "Difference":
            return kids[0] & ~np.logical_or.reduce(kids[1:])
        if call.name == "Xor":
            return np.logical_xor.reduce(kids)
        if call.name == "Not":
            return self.universe() & ~kids[0]
        raise Unknown(f"{call.name} is no bitmap call of the base language")

    def _row(self, call: pql.Call) -> np.ndarray:
        if len(call.args) != 1 or call.children:
            raise ValueError(f"Row takes one field: {call}")
        ((field, value),) = call.args.items()
        if isinstance(value, pql.Cond):
            m = self._range(field, value)
        else:
            m = self.data.row_mask(field, value)
        return m if self.visible is None else m & self.visible

    def _int_field(self, name: str) -> dict:
        f = self.data.fields[name]
        if "values" not in f:
            raise ValueError(f"field {name} is not an int field")
        return f

    def _range(self, field: str, cond: pql.Cond) -> np.ndarray:
        """Columns that hold a value, and whose value meets the condition."""
        f = self._int_field(field)
        has, values = f["has"], f["values"]
        if cond.op == "!=" and cond.value is None:
            return has
        if cond.op == "><":
            lo, hi = cond.value
            return has & (values >= lo) & (values <= hi)
        if cond.op not in _COMPARE or type(cond.value) is not int:
            raise ValueError(f"no condition {field} {cond.op} {cond.value!r}")
        return has & _COMPARE[cond.op](values, cond.value)

    # -- answers -----------------------------------------------------------

    def answer(self, text: str):
        """The answer the server owes for one request, in the normal form
        of `normalise`."""
        if text not in self._memo:
            self._memo[text] = self._answer(self.parse(text))
        return self._memo[text]

    def _answer(self, call: pql.Call):
        what = _foreign_to_answer(call)
        if what:
            return self.dialect.hook("answer", *what)(self, call)
        if call.name not in _ANSWERS:
            raise Unknown(f"the base reference answers no top-level {call.name}")
        return _ANSWERS[call.name](self, call)

    def _count(self, call: pql.Call) -> int:
        (child,) = call.children
        n = int(np.count_nonzero(self.mask(child)))
        if child.name == "Row" and not foreign(child):
            ((field, rid),) = child.args.items()
            if not isinstance(rid, pql.Cond):
                n += len(self.extra.get(field, {}).get(rid, ()))
        return n

    def _filter(self, children) -> np.ndarray | None:
        """The one optional bitmap child of TopN / Sum / Min / Max: its
        mask, or the visible columns (None for all)."""
        if len(children) > 1:
            raise ValueError(f"one filter at most, found {len(children)}")
        return self.mask(children[0]) if children else self.visible

    def _topn(self, call: pql.Call) -> list:
        field, filt = call.children[0], self._filter(call.children[1:])
        counts = []
        for rid in range(self.data.n_rows(field)):
            m = self.data.row_mask(field, rid)
            counts.append((rid, int(np.count_nonzero(m if filt is None else m & filt))))
        if len({c for _, c in counts}) != len(counts):
            raise ValueError("tied TopN counts: order is not defined")
        counts.sort(key=lambda rc: -rc[1])
        return [{"id": rid, "count": c} for rid, c in counts[:call.args.get("n")] if c]

    def _selected(self, call: pql.Call) -> np.ndarray:
        """The values a Sum / Min / Max goes over."""
        f = self._int_field(call.args["field"])
        filt = self._filter(call.children)
        sel = f["has"] if filt is None else f["has"] & filt
        return f["values"][sel]

    def _sum(self, call: pql.Call) -> dict:
        values = self._selected(call)
        return {"value": int(values.sum()), "count": len(values)}

    def _extreme(self, call: pql.Call) -> dict:
        """Min / Max: the value, and how many columns hold it."""
        values = self._selected(call)
        if not len(values):
            return {"value": 0, "count": 0}
        best = values.min() if call.name == "Min" else values.max()
        return {"value": int(best), "count": int(np.count_nonzero(values == best))}

    def _group_by(self, call: pql.Call) -> dict:
        fields = []
        for rows in call.children:
            if not isinstance(rows, pql.Call) or rows.name != "Rows" \
                    or len(rows.children) != 1:
                raise ValueError(f"GroupBy takes Rows(field) children: {call}")
            fields.append(rows.children[0])
        filt = call.args.get("filter")
        if filt is None:
            mask = self.visible
        elif isinstance(filt, pql.Call):
            mask = self.mask(filt)
        else:
            raise ValueError(f"GroupBy filter= takes a bitmap call: {call}")
        out = {}
        for key, m in self.groups(fields, mask):
            if len(out) == call.args.get("limit", -1):
                break
            out[key] = int(np.count_nonzero(m))
        return out

    def groups(self, fields: list, mask=None, prefix: tuple = ()):
        """(row ids, bool[n]) of every group of `fields` that holds a
        column of `mask` (None for all), in the order the server sorts them."""
        for rid in range(self.data.n_rows(fields[0])):
            m = self.data.row_mask(fields[0], rid)
            if mask is not None:
                m = m & mask
            if not m.any():
                continue
            if len(fields) == 1:
                yield prefix + (rid,), m
            else:
                yield from self.groups(fields[1:], m, prefix + (rid,))

    # -- the served form ---------------------------------------------------

    def normalise(self, text: str, result):
        """A served result in the reference's form: a GroupBy as a dict of
        row-id tuples, everything else as it came."""
        call = self.parse(text)
        what = _foreign_to_answer(call)
        if what:
            return self.dialect.hook("normalise", *what)(call, result)
        if call.name == "GroupBy":
            return {
                tuple(m["rowID"] for m in g["group"]): g["count"] for g in result
            }
        return result

    def served_form(self, text: str, answer):
        """A reference answer as the server's JSON would carry it."""
        call = self.parse(text)
        what = _foreign_to_answer(call)
        if what:
            return self.dialect.hook("served_form", *what)(call, answer)
        if call.name == "GroupBy":
            fields = [r.children[0] for r in call.children]
            return [
                {"group": [{"field": f, "rowID": r} for f, r in zip(fields, key)],
                 "count": n}
                for key, n in answer.items()
            ]
        return answer

    # -- writes ------------------------------------------------------------

    def add_columns(self, field: str, rid: int, cols) -> None:
        """New columns (never among the loaded ones) written to one row."""
        self.extra.setdefault(field, {}).setdefault(rid, set()).update(
            int(c) for c in cols
        )
        self._memo.clear()


_ANSWERS = {
    "Count": Reference._count, "TopN": Reference._topn, "Sum": Reference._sum,
    "Min": Reference._extreme, "Max": Reference._extreme,
    "GroupBy": Reference._group_by,
}
