"""The plain reference: the same operations on the same seeded data with
numpy boolean algebra over the populated columns (the semantics of the
smoke's `Reference`, which worked over sorted column-id arrays; a mask per
row is the same set and lets every answer of a window be checked in less
time than the window took). Shares no code with `pilosa_tpu/`."""

from __future__ import annotations

import numpy as np

from . import pql
from .data import Data


class Reference:
    def __init__(self, data: Data, visible=None):
        """`visible` (bool[n], or None for all) is the set of columns the
        answers are computed over: all of them, but for the control, which
        leaves some out (`control.py`)."""
        self.data = data
        self.visible = visible
        # columns written after the load (the read-your-writes check):
        # field -> row -> set of new column ids
        self.extra = {}
        self._memo = {}

    # -- set algebra -------------------------------------------------------

    def _mask(self, call: pql.Call) -> np.ndarray:
        kids = [self._mask(c) for c in call.children]
        if call.name == "Row":
            ((field, rid),) = call.args.items()
            m = self.data.row_mask(field, rid)
            return m if self.visible is None else m & self.visible
        if call.name == "Intersect":
            return np.logical_and.reduce(kids)
        if call.name == "Union":
            return np.logical_or.reduce(kids)
        if call.name == "Difference":
            return kids[0] & ~np.logical_or.reduce(kids[1:])
        if call.name == "Xor":
            return np.logical_xor.reduce(kids)
        if call.name == "Not":  # every loaded column exists
            return ~kids[0]
        raise ValueError(f"the reference has no bitmap call {call.name}")

    # -- answers -----------------------------------------------------------

    def answer(self, text: str):
        """The answer the server owes for one request, in the normal form
        of `normalise`."""
        if text not in self._memo:
            self._memo[text] = self._answer(pql.parse(text))
        return self._memo[text]

    def _answer(self, call: pql.Call):
        if call.name == "Count":
            (child,) = call.children
            n = int(np.count_nonzero(self._mask(child)))
            if child.name == "Row":
                ((field, rid),) = child.args.items()
                n += len(self.extra.get(field, {}).get(rid, ()))
            return n
        if call.name == "TopN":
            field = call.children[0]
            filt = [c for c in call.children[1:] if isinstance(c, pql.Call)]
            return self._topn(field, call.args["n"],
                              self._mask(filt[0]) if filt else self.visible)
        if call.name == "Sum":
            f = self.data.fields[call.args["field"]]
            sel = f["has"]
            if call.children:
                sel = sel & self._mask(call.children[0])
            elif self.visible is not None:
                sel = sel & self.visible
            return {"value": int(f["values"][sel].sum()),
                    "count": int(np.count_nonzero(sel))}
        if call.name == "GroupBy":
            fields = [rows.children[0] for rows in call.children]
            out = {}
            self._group(fields, (), self.visible, out)
            return out
        raise ValueError(f"the reference has no call {call.name}")

    def _topn(self, field: str, n: int, filt) -> list:
        counts = []
        for rid in range(self.data.n_rows(field)):
            m = self.data.row_mask(field, rid)
            counts.append((rid, int(np.count_nonzero(m if filt is None else m & filt))))
        if len({c for _, c in counts}) != len(counts):
            raise ValueError("tied TopN counts: order is not defined")
        counts.sort(key=lambda rc: -rc[1])
        return [{"id": rid, "count": c} for rid, c in counts[:n] if c]

    def _group(self, fields: list, prefix: tuple, mask, out: dict) -> None:
        for rid in range(self.data.n_rows(fields[0])):
            m = self.data.row_mask(fields[0], rid)
            if mask is not None:
                m = m & mask
            if len(fields) == 1:
                n = int(np.count_nonzero(m))
                if n:
                    out[prefix + (rid,)] = n
            elif m.any():
                self._group(fields[1:], prefix + (rid,), m, out)

    # -- writes ------------------------------------------------------------

    def add_columns(self, field: str, rid: int, cols) -> None:
        """New columns (never among the loaded ones) written to one row."""
        self.extra.setdefault(field, {}).setdefault(rid, set()).update(
            int(c) for c in cols
        )
        self._memo.clear()


def normalise(result):
    """A served result in the reference's form: GroupBy as a dict of
    row-id tuples, everything else as it came."""
    if isinstance(result, list) and result and isinstance(result[0], dict) \
            and "group" in result[0]:
        return {
            tuple(m["rowID"] for m in g["group"]): g["count"] for g in result
        }
    return result
