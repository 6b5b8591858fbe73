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
        and compiled before the window opens. First the templates without
        a variable; then every other template once with the first rows of
        its fields, and again, walking on, for as long as it is the one
        that walks a field: for each field that is the first of the
        templates with the most variables drawing from it. In a step every
        variable of such a template moves on through its own field's rows
        (the k variables of one field take k new rows a step, and go round
        again where another field has more rows), until every row of every
        field has been sent once."""
        out = [
            self.templates[t]["pql"]
            for t, names in enumerate(self._vars) if not names
        ]
        n_rows = {field: len(ids) for field, _, ids in self._draw.values()}
        by_field = []  # per template: field -> its variables, in order
        for names in self._vars:
            groups = {}
            for v in dict.fromkeys(names):
                groups.setdefault(self._draw[v][0], []).append(v)
            by_field.append(groups)
        steps = [1 if groups else 0 for groups in by_field]
        for field in n_rows:
            walker = max(
                range(len(by_field)),
                key=lambda t: len(by_field[t].get(field, ())),
            )
            k = len(by_field[walker].get(field, ()))
            if k:
                steps[walker] = max(steps[walker], -(-n_rows[field] // k))
        for step in range(max(steps, default=0)):
            for t, groups in enumerate(by_field):
                if step >= steps[t]:
                    continue
                values = {
                    v: (step * len(names) + j) % n_rows[field]
                    for field, names in groups.items()
                    for j, v in enumerate(names)
                }
                out.append(Template(self.templates[t]["pql"]).substitute(values))
        return out
