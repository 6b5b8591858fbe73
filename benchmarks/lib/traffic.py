"""The one traffic generator: reads a mix file (`traffic/<mix>.json`) and
gives every client its own endless stream of requests.

What the seed changes is the data and which row stands behind each rank;
the sequence of templates and ranks a client sends is the same for every
seed (drawn from a fixed stream per client), so every seed is the same
work: the same repeats for the result cache, the same shapes in the same
order. A run-to-run difference is then the machine's, not the draw's.

A mix states: `loop` ("closed": a client sends its next request when the
last one is answered), `clients`, `templates` (PQL with `$var`
placeholders and a weight), `variables` (how each `$var` draws a row id of
a field: "zipf" with exponent `s` over a seeded rank -> row permutation,
or "uniform"), `block` (requests per block: every block holds each
template exactly weight x block times, so every seed sends the same
composition; in shuffled order, or with `"order": "fixed"` in the order the
templates are listed, which keeps the count of answers at the window's
close steady where one template takes a hundred times another's time),
`warmup` and `trace_slice_s`.
"""

from __future__ import annotations

import re
from string import Template

import numpy as np


SEQUENCE_STREAM = 20240924  # the one stream every seed's sequence comes from


class Mix:
    def __init__(self, mix: dict, n_rows, seed: int):
        """`n_rows(field)` gives a field's row count."""
        self.mix = mix
        self.clients = mix["clients"]
        if mix["loop"] != "closed":
            raise ValueError(f"loop kind {mix['loop']!r} is not implemented")
        self.templates = mix["templates"]
        self._vars = [
            re.findall(r"\$(\w+)", t["pql"]) for t in self.templates
        ]
        counts = [t["weight"] * mix["block"] for t in self.templates]
        if any(abs(c - round(c)) > 1e-9 for c in counts):
            raise ValueError("weight x block must be whole for every template")
        self._block = np.repeat(
            np.arange(len(self.templates)), np.rint(counts).astype(int)
        )
        shared = np.random.default_rng([seed, 0])
        self._draw = {}
        for name, var in mix.get("variables", {}).items():
            n = n_rows(var["field"])
            if var["draw"] == "zipf":
                p = 1.0 / np.arange(1, n + 1) ** var["s"]
                ids = shared.permutation(n)  # rank -> row id
            elif var["draw"] == "uniform":
                p, ids = np.ones(n), np.arange(n)
            else:
                raise ValueError(f"unknown draw {var['draw']!r}")
            self._draw[name] = (var["field"], np.cumsum(p / p.sum()), ids)

    def _instance(self, t: int, rng) -> tuple:
        taken, values = {}, {}
        for name in self._vars[t]:
            field, cdf, ids = self._draw[name]
            while True:
                rid = int(ids[min(np.searchsorted(cdf, rng.random()), len(ids) - 1)])
                if rid not in taken.setdefault(field, set()):
                    break  # rows of one request are distinct
            taken[field].add(rid)
            values[name] = rid
        tpl = self.templates[t]
        return tpl["name"], Template(tpl["pql"]).substitute(values)

    def stream(self, client: int):
        """Endless (template name, PQL text) for one client."""
        rng = np.random.default_rng([SEQUENCE_STREAM, client])
        fixed = self.mix.get("order") == "fixed"
        while True:
            for t in self._block if fixed else rng.permutation(self._block):
                yield self._instance(int(t), rng)

    def warmup_requests(self) -> list:
        """Requests that touch every row this mix can name, in the shapes
        the mix sends, each template at least once: what has to be staged
        and compiled before the window opens."""
        out = []
        for t, names in enumerate(self._vars):
            if not names:
                out.append(self.templates[t]["pql"])
        widest = max(range(len(self._vars)), key=lambda t: len(self._vars[t]))
        names = self._vars[widest]
        if names:
            field, _, ids = self._draw[names[0]]
            if any(self._draw[v][0] != field for v in names):
                raise ValueError("warm-up expects one field per template")
            rows, k = len(ids), len(names)
            for lo in range(0, rows, k):
                ids = [(lo + j) % rows for j in range(k)]
                for t, tnames in enumerate(self._vars):
                    if tnames and (t == widest or lo == 0):
                        out.append(Template(self.templates[t]["pql"]).substitute(
                            dict(zip(tnames, ids))
                        ))
        return out
