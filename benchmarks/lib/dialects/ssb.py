"""The Star Schema Benchmark's own (configuration `ssb-sf100`): a star
schema held as one denormalised fact index, and its drill-down queries.

One field kind. A dimension hierarchy is a shape of the source and is kept:
a nation lies in one region, a category in one manufacturer. The base kinds
draw every field by itself, so `set/rollup` derives a coarser attribute from
a finer `set/one_of` field of the same configuration:

    {"name": "c_region", "type": "set", "membership": "rollup",
     "of": "c_nation", "divide": 5, "rows": 5}

row r holds the columns whose `of` row is in r * divide .. r * divide +
divide - 1. It draws nothing from the seed's stream.

One query form, `GroupBy(Rows(f)..., [filter=], [limit=],
aggregate=Sum(field=<int field>))`: the base GroupBy's groups, each with
the sum of the int field over the group's columns that hold a value. The
normal form is {row ids: (count, sum)}; a served group without a `sum` (a
program that reads past `aggregate=`) is (count, None), which equals no
reference answer and raises nothing. Work: the rows of the same call
without `aggregate=`, plus the value field's planes, once a request,
whatever implements the tally.

numpy + stdlib; imports neither `jax` nor `pilosa_tpu`."""

from __future__ import annotations

import numpy as np

from .. import pql, work
from ..data import encode_roaring

FORMS = ("GroupBy(aggregate=)",)
KINDS = ("set/rollup",)


# -- the field kind: a coarser attribute of a finer field -------------------


def draw(data, spec, rng) -> dict:
    return {"spec": spec, "rows": field_rows(spec)}


def field_rows(spec) -> int:
    return spec["rows"]


def field_options(spec) -> dict:
    return {"type": "set"}


def _labels(data, f) -> np.ndarray:
    """The rollup's row of every populated column."""
    finer = data.fields[f["spec"]["of"]]
    return finer["labels"] // f["spec"]["divide"]


def row_mask(data, f, rid) -> np.ndarray:
    return _labels(data, f) == rid


def importer(data, name, f):
    labels = _labels(data, f)

    def one(http_, s):
        lo, hi = s * data.per_shard, (s + 1) * data.per_shard
        idx = np.argsort(labels[lo:hi], kind="stable")
        rows = labels[lo:hi][idx].astype(np.int64)
        frag_pos = rows * data.shard_width + data.pos[s, idx]
        out = http_.call(
            "POST", f"/index/{data.index}/field/{name}/import-roaring/{s}",
            encode_roaring(frag_pos),
        )
        if out["changed"] != len(frag_pos):
            raise RuntimeError(f"{name}/{s}: import changed {out}")

    return one


# -- the query form: GroupBy(..., aggregate=Sum(field=)) ---------------------


def _fields(call: pql.Call) -> list:
    return [rows.children[0] for rows in call.children]


def _value_field(call: pql.Call) -> str:
    agg = call.args["aggregate"]
    if not isinstance(agg, pql.Call) or agg.name != "Sum" \
            or set(agg.args) != {"field"} or agg.children:
        raise ValueError(f"aggregate= takes Sum(field=<int field>): {call}")
    return agg.args["field"]


def _without_aggregate(call: pql.Call) -> pql.Call:
    return call._replace(
        args={k: v for k, v in call.args.items() if k != "aggregate"})


def answer(ref, call) -> dict:
    f = ref.data.fields[_value_field(call)]
    if "values" not in f:
        raise ValueError(f"{_value_field(call)} is not an int field")
    filt = call.args.get("filter")
    mask = ref.mask(filt) if filt is not None else ref.visible
    out = {}
    for key, m in ref.groups(_fields(call), mask):
        if len(out) == call.args.get("limit", -1):
            break
        out[key] = (int(np.count_nonzero(m)),
                    int(f["values"][m & f["has"]].sum()))
    return out


def normalise(call, result) -> dict:
    return {
        tuple(m["rowID"] for m in g["group"]): (g["count"], g.get("sum"))
        for g in result
    }


def served_form(call, answer) -> list:
    fields = _fields(call)
    return [
        {"group": [{"field": f, "rowID": r} for f, r in zip(fields, key)],
         "count": n, "sum": total}
        for key, (n, total) in answer.items()
    ]


def request_rows(config, call, base_rows) -> int:
    return base_rows(_without_aggregate(call)) + work.field_rows(
        config, _value_field(call))


# -- warm-up ------------------------------------------------------------------


def warmup_requests(mix) -> list:
    """Every text twice. The mix has no variable for the base rule to walk,
    and the first GroupBy of a process traces its kernels inside the server:
    the second round runs every shape compiled and every row resident."""
    return [t["pql"] for t in mix.templates] * 2
