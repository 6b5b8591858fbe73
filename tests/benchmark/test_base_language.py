"""What ISSUE 36 adds to the base language, each form against a served
index (a shrunk `taxi-1b` on an in-process node: reference = served) and
with its stated row count under `lib/work.py`; and `lib/pql.py` on every
token it now reads and every error it names."""

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import run as harness  # noqa: E402
from lib import dialects, pql, work  # noqa: E402
from lib.data import Data, Http, create_schema, load  # noqa: E402
from lib.pql import Call, Cond  # noqa: E402
from lib.reference import Reference  # noqa: E402

# taxi-1b's device rows: passenger_count 8, pickup_year 8, dist_miles 16,
# cab_type 3, total_amount 17 planes + exists + sign = 19
FORMS = [
    ("GroupBy(Rows(passenger_count), Rows(pickup_year), filter=Row(cab_type=0))",
     8 + 8 + 1),
    ("GroupBy(Rows(passenger_count), Rows(pickup_year), limit=5)", 8 + 8),
    ("GroupBy(Rows(dist_miles), filter=Row(total_amount > 50000), limit=3)", 16 + 19),
    ("GroupBy(Rows(cab_type), filter=Intersect(Row(passenger_count=1), "
     "Row(pickup_year=2)))", 3 + 2),
    ("GroupBy(Rows(cab_type), filter=Row(total_amount > 100000))", 3 + 19),
    ("Count(Row(total_amount > 60000))", 19),
    ("Count(Row(total_amount >= 60000))", 19),
    ("Count(Row(total_amount < 250))", 19),
    ("Count(Row(total_amount <= 250))", 19),
    ("Count(Row(total_amount != 250))", 19),
    ("Count(Row(total_amount != null))", 19),
    ("Count(Row(total_amount >< [1000, 2000]))", 19),
    ("Count(Intersect(Row(cab_type=1), Row(20000 < total_amount < 70000)))", 1 + 19),
    ("Count(Intersect(Row(cab_type=1), Row(20000 <= total_amount <= 70000)))", 1 + 19),
    # two conditions on one field read its planes once
    ("Count(Union(Row(total_amount < 100), Row(total_amount > 99900)))", 19),
    ("Count(Not(Row(total_amount > 100)))", 19),
    # the filter's planes are the aggregate's
    ("Sum(Row(total_amount >= 90000), field=total_amount)", 19),
    ("Min(field=total_amount)", 19),
    ("Max(field=total_amount)", 19),
    ("Min(Row(passenger_count=2), field=total_amount)", 1 + 19),
    ("Max(Row(total_amount < 777), field=total_amount)", 19),
    ("Max(Row(total_amount < 0), field=total_amount)", 19),
    ("TopN(dist_miles, Row(total_amount > 50000), n=4)", 16 + 19),
]


@pytest.fixture(scope="module")
def taxi():
    """(config, query function, reference) over a served shrunk taxi-1b,
    total_amount on 70 % of the rides so that `exists` matters."""
    from pilosa_tpu.testing import ClusterHarness

    config = copy.deepcopy(harness.Cell(ROOT, "taxi-1b.q1-q4").config)
    config["shards"] = 3
    next(f for f in config["fields"] if f["name"] == "total_amount")["share"] = 0.7
    with ClusterHarness(1, in_memory=True) as c:
        http_ = Http(c[0].node.uri)
        info = http_.call("GET", "/info")
        data = Data(config, 2**31 + 36, info["shardWidth"])
        create_schema(http_, config)
        load(c[0].node.uri, data)
        path = f"/index/{data.index}/query"
        yield config, lambda text: http_.call("POST", path, text)["results"][0], \
            Reference(data)
        http_.close()


@pytest.mark.parametrize("text, rows", FORMS)
def test_a_form_of_the_grown_base_answers_as_the_served_index(taxi, text, rows):
    config, query, ref = taxi
    want = ref.answer(text)
    assert ref.normalise(text, query(text)) == want
    assert work.request_rows(config, text) == rows
    assert work.request_bytes(config, text) == rows * 3 * 131072
    # the reference in the program's place reads back as itself
    assert ref.normalise(text, ref.served_form(text, want)) == want
    if "total_amount < 0" not in text and "> 100000" not in text:
        assert want not in (0, {}, [], {"value": 0, "count": 0}), "an empty case"


def test_the_reference_answers_are_the_stated_ones(taxi):
    _, _, ref = taxi
    f = ref.data.fields["total_amount"]
    held = f["values"][f["has"]]
    assert 0.65 < f["has"].mean() < 0.75
    assert ref.answer("Count(Row(total_amount != null))") == len(held)
    assert ref.answer("Count(Row(total_amount > 60000))") == (held > 60000).sum()
    assert ref.answer("Count(Row(20000 < total_amount < 70000))") \
        == ((held > 20000) & (held < 70000)).sum()
    assert ref.answer("Min(field=total_amount)")["value"] == held.min()
    assert ref.answer("Max(field=total_amount)") == {
        "value": held.max(), "count": (held == held.max()).sum()}
    assert ref.answer("Max(Row(total_amount < 0), field=total_amount)") \
        == {"value": 0, "count": 0}
    whole = ref.answer("GroupBy(Rows(passenger_count), Rows(pickup_year))")
    assert ref.answer("GroupBy(Rows(passenger_count), Rows(pickup_year), limit=5)") \
        == dict(sorted(whole.items())[:5])
    assert ref.answer(
        "Count(Not(Row(total_amount > 100)))") == ref.data.n - (held > 100).sum()


@pytest.mark.parametrize("bad", [7, [1, 2], [{"count": 3}], {"value": 1}, None])
def test_an_answer_of_the_wrong_shape_is_a_wrong_answer(taxi, bad):
    _, _, ref = taxi
    record = harness.Record("q", "GroupBy(Rows(cab_type))", 0.0, 0.0, 200,
                            json.dumps({"results": [bad]}).encode())
    assert harness.judge([record], ref)[:2] == (1, 0)


@pytest.mark.parametrize("text, names", [
    ("GroupBy(Rows(cab_type), aggregate=Sum(field=total_amount))", r"GroupBy\(aggregate=\)"),
    ("GroupBy(Rows(cab_type), previous=[1])", r"GroupBy\(previous=\)"),
    ("GroupBy(Rows(cab_type, limit=2))", r"Rows\(limit=\)"),
    ("Percentile(field=total_amount, nth=99)", "Percentile"),
    ("Count(Shift(Row(cab_type=1), n=1))", "Shift"),
    ("Count(Row(cab_type=1, from=2019-01-01T00:00, to=2020-01-01T00:00))", r"Row\(from=\)"),
    ("TopN(cab_type, n=2, ids=[0, 1])", r"TopN\(ids=\)"),
    ("Sum(field=total_amount, filter=Row(cab_type=1))", r"Sum\(filter=\)"),
])
def test_what_the_base_does_not_know_raises_by_name(text, names):
    """Never a silently ignored argument: both the reference and the work
    rule refuse, naming the form."""
    config = harness.Cell(ROOT, "taxi-1b.q1-q4").config

    class NoData:
        dialect, fields, n = dialects.NONE, {}, 0

    with pytest.raises(dialects.Unknown, match=names):
        Reference(NoData).answer(text)
    with pytest.raises(dialects.Unknown, match=names):
        work.request_rows(config, text)


def test_a_known_call_in_the_wrong_place_is_refused():
    class NoData:
        dialect, fields, n = dialects.NONE, {"f": {"labels": None}}, 0

    ref = Reference(NoData)
    for text, message in [
        ("Row(f=1)", "no top-level Row"),
        ("Count(Sum(field=f))", "Sum is no bitmap call"),
        ("Count(Row(f > 3))", "not an int field"),
        ("Count(Row(f=1, g=2))", "Row takes one field"),
        ("Sum(Row(f=1), Row(f=2), field=f)", "not an int field|one filter"),
        ("GroupBy(Row(f=1))", "Rows"),
        ("GroupBy(Rows(f), filter=3)", "filter= takes a bitmap call"),
    ]:
        with pytest.raises(ValueError, match=message):
            ref.answer(text)
    with pytest.raises(dialects.Unknown, match="no work rule"):
        work.request_rows({"fields": []}, "Row(f=1)")


row = lambda **args: Call("Row", (), args)  # noqa: E731


@pytest.mark.parametrize("text, tree", [
    ("Count(Row(v > 5))", Call("Count", (row(v=Cond(">", 5)),), {})),
    ("Count(Row(v>=-5))", Call("Count", (row(v=Cond(">=", -5)),), {})),
    ("Row(v < 5)", row(v=Cond("<", 5))),
    ("Row(v <= 5)", row(v=Cond("<=", 5))),
    ("Row(v == 5)", row(v=Cond("==", 5))),
    ("Row(v != 5)", row(v=Cond("!=", 5))),
    ("Row(v != null)", row(v=Cond("!=", None))),
    ("Row(v >< [3, 9])", row(v=Cond("><", [3, 9]))),
    ("Count(Row(3 <= v < 9))", Call("Count", (row(v=Cond("><", [3, 8])),), {})),
    ("Row(3 < v <= 9)", row(v=Cond("><", [4, 9]))),
    ('Row(f="k")', row(f="k")),
    ("Row(f='it\\'s')", row(f="it's")),
    ('Row(f="a \\"b\\" (c), d=1")', row(f='a "b" (c), d=1')),
    ("Row(f=k-1_x)", row(f="k-1_x")),
    ("Row(my-field=3)", Call("Row", (), {"my-field": 3})),
    ("Row(f=1.5)", row(f=1.5)),
    ("Row(f=true, g=false, h=null)", row(f=True, g=False, h=None)),
    ("Row(f=3, from=2019-01-01T00:00, to='2020-06-30T23:59')",
     Call("Row", (), {"f": 3, "from": "2019-01-01T00:00", "to": "2020-06-30T23:59"})),
    ("GroupBy(Rows(a), previous=[1], limit=10)",
     Call("GroupBy", (Call("Rows", ("a",), {}),), {"previous": [1], "limit": 10})),
    ("TopN(f, n=2, ids=[1, 2, 3], names=[\"a\", b])",
     Call("TopN", ("f",), {"n": 2, "ids": [1, 2, 3], "names": ["a", "b"]})),
    ("GroupBy(Rows(a), Rows(b), filter=Intersect(Row(c=1), Row(d=2)), "
     "aggregate=Sum(field=v))",
     Call("GroupBy", (Call("Rows", ("a",), {}), Call("Rows", ("b",), {})), {
         "filter": Call("Intersect", (row(c=1), row(d=2)), {}),
         "aggregate": Call("Sum", (), {"field": "v"})})),
    (" Sum( Row( a = 1 ) ,\n field = v , ) ",
     Call("Sum", (row(a=1),), {"field": "v"})),
    ("Count(All())", Call("Count", (Call("All", (), {}),), {})),
])
def test_the_reader_reads_every_form_of_a_read(text, tree):
    assert pql.parse(text) == tree


def test_what_the_tree_names():
    call = pql.parse(
        "GroupBy(Rows(a), filter=Intersect(Row(c=1), Row(v > 5), Row(3 < w < 9), "
        "Row(k=\"x\", from=2019-01-01T00:00, to=2019-02-01T00:00)), "
        "aggregate=Sum(Row(c=2), field=v))")
    # a condition is no row reference, and neither is a time bound
    assert pql.row_refs(call) == {("c", 1), ("c", 2), ("k", "x")}
    assert pql.cond_fields(call) == {"v", "w"}
    assert [c.name for c in pql.calls(call)] == [
        "GroupBy", "Rows", "Intersect", "Row", "Row", "Row", "Row", "Sum", "Row"]
    assert dialects.foreign(call) == ["GroupBy(aggregate=)"]
    assert dialects.foreign(call.args["filter"].children[3]) == [
        "Row(from=)", "Row(to=)"]


@pytest.mark.parametrize("text, message", [
    ("Count(Row(a=1)", "unbalanced call: no '\\)' closes Count"),
    ("Count(Row(a=1),", "unbalanced call"),
    ("Count(Row(a=[1, 2))", "unbalanced list"),
    ("Count(Row(a=1)) x", "trailing PQL"),
    ("Count(Row(a=1)))", "trailing PQL"),
    ("Count(Row(a=1)) Count(Row(a=2))", "trailing PQL"),
    ("Count(Row(a=))", "expected a value"),
    ("Count(Row(a=1 b=2))", "expected ',' or '\\)'"),
    ("Count(Row(3 < v > 9))", "expected `lo < field < hi`"),
    ("Count(Row(1.5 < v < 9))", "expected `lo < field < hi`"),
    ("Count(Row(a=1, a=2))", "duplicate argument 'a'"),
    ("5(Row(a=1))", "expected a call"),
    ("Count Row(a=1)", "expected '\\('"),
    ("Count(Row(a ~ 1))", "cannot read PQL at '~"),
    ("Count(Row(a=\"open))", "cannot read PQL at '\"open"),
    ("", "expected a call"),
])
def test_the_reader_names_what_it_cannot_read(text, message):
    with pytest.raises(ValueError, match=message):
        pql.parse(text)
