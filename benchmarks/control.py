#!/usr/bin/env python3
"""The control of `correct`: the reference put in the program's place with
one stated guarantee broken, judged by the harness's own comparison. It has
to come out as not correct.

The system states no numeric precision; its configurations state "every
answer exact" and "an acknowledged write is visible to the next Count". The
control breaks them the way a tempting optimisation would:

  approximate  every answer is computed over all shards but the last (a
               sampled or pruned scan) - wrong_answers has to read above 0;
  stale        acknowledged writes are not applied before the read-back
               (a late or rare flush) - readback_wrong has to read above 0.

    python benchmarks/control.py --workload <cell> --seed <n> [--requests N]

No server and no chip: the cell's own data size and its own request
streams, answered by numpy. Prints each number beside its limit and exits 0
only if both controls came out not correct.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as harness  # noqa: E402
from lib.data import Data  # noqa: E402
from lib.reference import Reference  # noqa: E402
from lib.traffic import Mix  # noqa: E402


def all_but_last_shard(data: Data) -> np.ndarray:
    keep = np.ones(data.n, dtype=bool)
    keep[-data.per_shard:] = False
    return keep


def control_run(cell, seed: int, n_requests: int, shard_width: int) -> dict:
    data = Data(cell.config, seed, shard_width, cell.dialect)
    ref = Reference(data)
    approx = Reference(data, visible=all_but_last_shard(data))
    mix = Mix(cell.mix, data.n_rows, seed)
    records = []
    for c in range(mix.clients):
        stream = mix.stream(c)
        for _ in range(n_requests // mix.clients):
            template, text = next(stream)
            raw = json.dumps(
                {"results": [approx.served_form(text, approx.answer(text))]}
            ).encode()
            records.append(harness.Record(template, text, 0.0, 0.0, 200, raw))
    wrong, failed, _ = harness.judge(records, ref)
    # stale: the writes are acknowledged, the read-back answers without them
    g = cell.config["guarantees"]["read_your_writes"]
    stale = Reference(data)
    before = [stale.answer(f"Count(Row({g['field']}={r}))")
              for r in (g["set_row"], g["import_row"])]
    ref.add_columns(g["field"], g["set_row"], data.unused_columns([0])[:1])
    ref.add_columns(g["field"], g["import_row"],
                    data.unused_columns([1])[: g["import_columns"]])
    after = [ref.answer(f"Count(Row({g['field']}={r}))")
             for r in (g["set_row"], g["import_row"])]
    return {
        "wrong_answers": {"value": wrong, "limit": 0, "of": len(records)},
        "failed_requests": {"value": failed, "limit": 0},
        "readback_wrong": {
            "value": sum(a != b for a, b in zip(before, after)), "limit": 0},
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=2000)
    args = ap.parse_args()
    cell = harness.Cell(harness.ROOT, args.workload)
    checks = control_run(cell, args.seed, args.requests,
                         1 << cell.config["shard_width_exponent"])
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "control": checks}))
    failed_as_it_must = (checks["wrong_answers"]["value"] > 0
                         and checks["readback_wrong"]["value"] > 0)
    return 0 if failed_as_it_must else 1


if __name__ == "__main__":
    sys.exit(main())
