"""Where the base language ends, and how a deployment adds to it.

The base is what `lib/reference.py`, `lib/work.py`, `lib/data.py` and
`lib/traffic.py` know: the calls and arguments of `BASE`, the field kinds
of `BASE_KINDS`, and one warm-up rule. A deployment that asks or stores
something else brings a dialect: a file `lib/dialects/<name>.py`, named by
`"dialect": "<name>"` in its configuration file and found by that name, as
a metric's `"module"` reader is. Nothing that is here is edited for it.

A dialect is numpy + stdlib (it may import `lib.pql`, `lib.work`,
`lib.data`); it imports neither `jax` nor `pilosa_tpu`, and `load` refuses
one that does. It declares what it adds and supplies the hooks for it,
every one optional:

    FORMS = ("GroupBy(aggregate=)", "Percentile")  # calls, or arguments of
    KINDS = ("set/sparse",)       # base calls; `<type>/<membership>` kinds

  query forms (asked only for a call that holds a declared form)
    answer(ref, call)          the answer, in the normal form compared;
                               `ref` is the base `Reference`: `ref.data`,
                               `ref.mask(call)`, `ref.groups(fields, mask)`,
                               `ref.universe()`, `ref.visible`, `ref.extra`
    mask(ref, call)            bool[n] of a bitmap call the base has not
    normalise(call, result)    a served result in that normal form
    served_form(call, answer)  and back, as the server's JSON carries it
                               (`control.py` puts the reference in the
                               program's place)
    request_rows(config, call, base_rows)
                               device rows the request reads (`lib/work.py`);
                               `base_rows(call)` is the base rule's count for
                               a call the base knows, say this one without
                               the dialect's argument
  field kinds (asked only for a field of a declared kind)
    draw(data, spec, rng)      the field's state from the seed's stream: a
                               dict with "spec", and "rows" where it has rows
    row_mask(data, f, rid)     bool[n]: the populated columns row `rid` holds
    field_options(spec)        the options `create_schema` posts
    importer(data, name, f)    `one(http, shard)`, through a public import route
    field_rows(spec)           device rows the field holds (`lib/work.py`)
  warm-up
    warmup_requests(mix)       the requests that stage and compile what the
                               mix can name; None leaves it to the base rule

What it may not do: answer for the base. The base is asked first and a
dialect only for what the base refuses by name, so a later PR cannot soften
an accepted cell's check through one; a dialect that declares a form or a
kind the base knows is refused when it is loaded.
"""

from __future__ import annotations

import importlib.util
import os
import re

from .. import pql

# every call the base reference and the base work rules read, with the
# arguments they read; a `Row`'s keys are field names, but for from= / to=
BASE = {
    "Count": (), "TopN": ("n",), "Sum": ("field",), "Min": ("field",),
    "Max": ("field",), "GroupBy": ("filter", "limit"), "Rows": (),
    "Row": (), "Intersect": (), "Union": (), "Difference": (), "Xor": (),
    "Not": (),
}
BASE_KINDS = ("int", "set/one_of", "set/independent")  # `lib/data.py` draws these
_FORM = re.compile(r"([A-Za-z_][A-Za-z0-9_-]*)(?:\(([A-Za-z_][A-Za-z0-9_-]*)=\))?\Z")
_FORBIDDEN_IMPORT = re.compile(r"^\s*(import|from)\s+(jax|pilosa_tpu)\b", re.M)


class Unknown(ValueError):
    """A call, argument or field kind that nothing loaded knows, by name."""


def kind(spec: dict) -> str:
    """A field's kind, as `KINDS` names it: `int`, `set/one_of`."""
    if "membership" in spec:
        return f"{spec['type']}/{spec['membership']}"
    return spec["type"]


def base_knows(name: str, arg: str = None) -> bool:
    if name not in BASE or arg is None:
        return name in BASE
    if name == "Row":
        return arg not in pql.ROW_TIME_ARGS
    return arg in BASE[name]


def foreign(call: pql.Call) -> list:
    """What of this one call (not of its children) the base does not know,
    as `FORMS` names it: `Percentile`, `GroupBy(aggregate=)`."""
    if not base_knows(call.name):
        return [call.name]
    return [f"{call.name}({k}=)" for k in call.args
            if not base_knows(call.name, k)]


class Dialect:
    """One loaded dialect, or with no module the empty one (`NONE`): what
    every configuration without a `"dialect"` key runs under."""

    def __init__(self, name: str = None, module=None):
        self.name, self.module = name, module
        self.forms = tuple(getattr(module, "FORMS", ()))
        self.kinds = tuple(getattr(module, "KINDS", ()))
        for f in self.forms:
            m = _FORM.match(f)
            if m is None:
                raise ValueError(f"dialect {name!r}: {f!r} is no form; write "
                                 "`Call` or `Call(argument=)`")
            if base_knows(*m.groups()):
                raise ValueError(
                    f"dialect {name!r} declares {f}, which the base language "
                    "answers: a dialect adds forms, it overrides none")
        for k in self.kinds:
            if k in BASE_KINDS:
                raise ValueError(
                    f"dialect {name!r} declares the field kind {k!r}, which "
                    "lib/data.py draws: a dialect adds kinds, it overrides none")

    def hook(self, hook: str, *what: str):
        """The dialect's function `hook` for the forms or kinds `what`, all
        of which it has to declare; else `Unknown`, naming them."""
        missing = [w for w in what if w not in self.forms + self.kinds]
        if self.module is None or missing:
            who = (f"dialect {self.name!r} does not declare it"
                   if self.module is not None else "no dialect is loaded")
            raise Unknown(f"the base knows no {', '.join(missing or what)}, "
                          f"and {who}")
        fn = getattr(self.module, hook, None)
        if fn is None:
            raise Unknown(f"dialect {self.name!r} declares {', '.join(what)} "
                          f"but has no `{hook}`")
        return fn

    def warmup_requests(self, mix) -> list:
        fn = getattr(self.module, "warmup_requests", None)
        out = fn(mix) if fn is not None else None
        return mix.warmup_requests() if out is None else out


NONE = Dialect()


def load(bench_dir: str, name) -> Dialect:
    """`<bench_dir>/lib/dialects/<name>.py`, or `NONE` for no name."""
    if name is None:
        return NONE
    if not re.fullmatch(r"[A-Za-z0-9_]+", name):
        raise ValueError(f"dialect name {name!r}: letters, digits and _ only")
    path = os.path.join(bench_dir, "lib", "dialects", name + ".py")
    with open(path) as f:
        if _FORBIDDEN_IMPORT.search(f.read()):
            raise ValueError(f"{path} imports jax or pilosa_tpu: a dialect is "
                             "numpy + stdlib, as run.py is")
    spec = importlib.util.spec_from_file_location(f"{__name__}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return Dialect(name, module)
