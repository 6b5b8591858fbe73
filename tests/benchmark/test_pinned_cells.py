"""What the five cells of ISSUE 36's parent read, pinned: the request
streams, the warm-up list, the parse tree of every text, the logical bytes
of every text and the reference's answers, each as a digest taken on the
parent commit (`pinned_cells.json`, written by running this file there:
`python tests/benchmark/test_pinned_cells.py > tests/benchmark/pinned_cells.json`).
A change to `lib/pql.py`, `lib/traffic.py`, `lib/work.py`, `lib/data.py` or
`lib/reference.py` that moves one of them has changed what an accepted cell
sends, or what it is compared with, and fails here.

Streams and warm-up are taken at the cells' own row counts (the generator
needs the configuration's sizes only) and at `small_cell`'s; answers at
`small_cell`'s, where the data fits a test. A cell a later PR adds has no
pin and is not a case here."""

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


def _sent(mix: Mix) -> tuple:
    streams = []
    for c in range(mix.clients):
        stream = mix.stream(c)
        streams.append([list(next(stream)) for _ in range(PER_CLIENT)])
    return streams, mix.warmup_requests()


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
    out = {k: [] for k in KINDS}
    full_texts, cut_texts = set(), set()
    for seed in SEEDS:
        streams, warm = _sent(Mix(
            full.mix, lambda f: work.field_rows(full.config, f), seed))
        out["stream.full"].append(streams)
        out["warmup.full"].append(warm)
        full_texts |= _texts(streams, warm)
        data = Data(cut.config, seed, 1 << cut.config["shard_width_exponent"])
        streams, warm = _sent(Mix(cut.mix, data.n_rows, seed))
        out["stream.small"].append(streams)
        out["warmup.small"].append(warm)
        texts = _texts(streams, warm)
        cut_texts |= texts
        ref = Reference(data)
        out["answers"].append(
            [[t, _plain(ref.answer(t))] for t in sorted(texts)])
    out["request_bytes"] = [
        [t, work.request_bytes(full.config, t)] for t in sorted(full_texts)]
    out["parse"] = [
        [t, repr(pql.parse(t))] for t in sorted(full_texts | cut_texts)]
    return {
        k: {"sha256": _digest(v), "items": sum(map(len, v)) if k not in (
            "request_bytes", "parse") else len(v)}
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


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("cell_name", sorted(_pins()) if os.path.exists(PINS) else [])
def test_a_pinned_cell_reads_what_it_read_at_the_parent(cell_name, kind, computed):
    assert computed(cell_name)[kind] == _pins()[cell_name][kind]


if __name__ == "__main__":
    bench = harness.read_json(ROOT, "BENCHMARK.json")
    print(json.dumps(
        {w["name"]: digests(w["name"]) for w in bench["workloads"]}, indent=1))
