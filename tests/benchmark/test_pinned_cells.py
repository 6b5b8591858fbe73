"""What the accepted cells read, pinned: the request streams, the warm-up
list, the parse tree of every text, the logical bytes of every text and the
reference's answers, each as a digest (`pinned_cells.json`, written by
running this file: `python tests/benchmark/test_pinned_cells.py >
tests/benchmark/pinned_cells.json`). A change to `lib/pql.py`,
`lib/traffic.py`, `lib/work.py`, `lib/data.py`, `lib/reference.py` or a
dialect (`lib/dialects/`) that moves one of them has changed what an
accepted cell sends, or what it is compared with, and fails here.

Each is taken as `run.py` takes it: through the cell's dialect where its
configuration names one (the SSB cells: `lib/dialects/ssb.py`), which draws
its field kinds, counts its rows, answers its forms and lists its warm-up.
Streams and warm-up are taken at the cells' own row counts (the generator
needs the configuration's sizes only) and at `small_cell`'s; answers at
`small_cell`'s, where the data fits a test. A dialect cell's answers are
also taken at its configuration's own sizes, on the first seed
(`answers.full`): `small_cell` cuts every field to 12 rows, which leaves
out the rows its texts name (SSB's UNITED STATES is nation 9, UNITED
KINGDOM's cities 191 and 195), so its small answers find no groups; at its
own sizes every text finds some. A cell appended after the pins were last
written has no pin and is not a case here."""

import hashlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as harness  # noqa: E402
from lib import pql, work  # noqa: E402
from lib.data import Data  # noqa: E402
from lib.reference import Reference  # noqa: E402
from lib.traffic import Mix  # noqa: E402

import test_benchmark_harness as small  # noqa: E402

PINS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "pinned_cells.json")
SEEDS = (1, 77, 2**31 + 5)
PER_CLIENT = 200
KINDS = ("stream.full", "warmup.full", "stream.small", "warmup.small",
         "request_bytes", "parse", "answers")


def _digest(obj) -> str:
    return hashlib.sha256(
        json.dumps(obj, sort_keys=True, separators=(",", ":")).encode()
    ).hexdigest()


def _sent(mix: Mix, dialect) -> tuple:
    streams = []
    for c in range(mix.clients):
        stream = mix.stream(c)
        streams.append([list(next(stream)) for _ in range(PER_CLIENT)])
    return streams, dialect.warmup_requests(mix)


def _texts(streams, warm) -> set:
    return {text for s in streams for _, text in s} | set(warm)


def _plain(answer):
    """An answer as JSON can carry it: a GroupBy's dict as sorted pairs."""
    if isinstance(answer, dict) and answer and isinstance(next(iter(answer)), tuple):
        return sorted([list(k), v] for k, v in answer.items())
    return answer


def digests(cell_name: str) -> dict:
    full = harness.Cell(ROOT, cell_name)
    cut = small.small_cell(cell_name, 1)
    dialect = full.dialect
    out = {k: [] for k in KINDS}
    full_texts, cut_texts = set(), set()
    for seed in SEEDS:
        streams, warm = _sent(Mix(
            full.mix, lambda f: work.field_rows(full.config, f, dialect),
            seed), dialect)
        out["stream.full"].append(streams)
        out["warmup.full"].append(warm)
        full_texts |= _texts(streams, warm)
        data = Data(cut.config, seed, 1 << cut.config["shard_width_exponent"],
                    dialect)
        streams, warm = _sent(Mix(cut.mix, data.n_rows, seed), dialect)
        out["stream.small"].append(streams)
        out["warmup.small"].append(warm)
        texts = _texts(streams, warm)
        cut_texts |= texts
        ref = Reference(data)
        out["answers"].append(
            [[t, _plain(ref.answer(t))] for t in sorted(texts)])
    out["request_bytes"] = [
        [t, work.request_bytes(full.config, t, dialect)]
        for t in sorted(full_texts)]
    out["parse"] = [
        [t, repr(pql.parse(t))] for t in sorted(full_texts | cut_texts)]
    if full.config.get("dialect"):
        ref = Reference(Data(full.config, SEEDS[0],
                             1 << full.config["shard_width_exponent"], dialect))
        out["answers.full"] = [
            [t, _plain(ref.answer(t))] for t in sorted(full_texts)]
        assert all(a for _, a in out["answers.full"]), cell_name
    return {
        k: {"sha256": _digest(v), "items": sum(map(len, v)) if k not in (
            "request_bytes", "parse", "answers.full") else len(v)}
        for k, v in out.items()
    }


def _pins() -> dict:
    with open(PINS) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def computed():
    memo = {}

    def get(cell_name):
        if cell_name not in memo:
            memo[cell_name] = digests(cell_name)
        return memo[cell_name]

    return get


@pytest.mark.parametrize("cell_name, kind", [
    (c, k) for c, pinned in sorted(_pins().items()) for k in pinned
] if os.path.exists(PINS) else [])
def test_a_pinned_cell_reads_what_it_read_at_the_parent(cell_name, kind, computed):
    assert computed(cell_name)[kind] == _pins()[cell_name][kind]


if __name__ == "__main__":
    bench = harness.read_json(ROOT, "BENCHMARK.json")
    print(json.dumps(
        {w["name"]: digests(w["name"]) for w in bench["workloads"]}, indent=1))
