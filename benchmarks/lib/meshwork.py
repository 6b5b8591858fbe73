"""Readers for what a mesh adds: a cell whose one server process drives
several chips as one sharded program. The metrics whose source says
`"module": "meshwork"`:

    {"module": "meshwork", "reduce": "per_chip_roofline_pct"}
    {"module": "meshwork", "reduce": "collective_share_pct"}
    {"module": "meshwork", "reduce": "sharded_dispatch_pct"}

All three take the number of chips from the configuration's file (`chips`;
`run.py` has already held the server's `/info` to the cell's). A
configuration without it, or a program whose `exec.dispatch` spans carry no
`mesh.devices` tag (one from before the tag), gives nothing to read: None,
and the line leaves the metric out.
"""

from __future__ import annotations

import json
import os

from . import work
from .readers import did_device_work, spans

# by instruction name; the asynchronous forms (`all-reduce-start`,
# `all-reduce-done`) and numbered copies (`all-reduce.1`) hold the same words
COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
)


def _peak(ctx):
    """The peak of the server's device kind; where `peaks.json` has none
    for it (which `run.py` allows only with `require_peak=False`: the CPU
    tests), that of the deployment's chip as the configuration names it."""
    if ctx.peak is not None:
        return ctx.peak
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        return json.load(f).get(ctx.config.get("chip_kind"))


def per_chip_roofline_pct(ctx, chips: int):
    """Each chip's share of its own HBM roofline: logical bytes
    (`lib/work.py`) of the slice's requests that did device work, over
    `chips` x the chip's bytes/s, over the mean device-busy seconds of the
    planes. Every chip reads a `1 / chips` part of every row, so it cannot
    pass 100 unless the bytes are counted wrongly."""
    peak, busy = _peak(ctx), ctx.trace["busy_s"]
    if peak is None or busy <= 0:
        return None
    t0, t1 = ctx.slice
    logical = sum(
        work.request_bytes(ctx.config, r["text"], ctx.dialect)
        for r in ctx.requests
        if t0 <= r["received"] <= t1 and did_device_work(r["roots"])
    )
    if not logical:
        return None
    return 100.0 * logical / (chips * peak["hbm_bytes_per_s"]) / busy


def is_collective(op_name: str) -> bool:
    """`<program>/<instruction>` as `lib/trace.py` names an operation."""
    instruction = op_name.rsplit("/", 1)[-1].lstrip("%")
    return instruction.startswith(COLLECTIVES)


def collective_share_pct(ctx, chips: int):
    """Seconds of collective operations among `breakdown.device_ops` (the
    ten longest of the slice, per-plane means) over busy seconds; 0.0
    where none ran."""
    busy = ctx.trace["busy_s"]
    if busy <= 0:
        return None
    seconds = sum(
        s for name, s in ctx.trace["breakdown"]["device_ops"]
        if is_collective(name)
    )
    return 100.0 * seconds / busy


def sharded_dispatch_pct(ctx, chips: int):
    """Share of the window's `exec.dispatch` spans whose program spanned
    all the configuration's chips (`mesh.devices` tag)."""
    placed = [
        s["tags"]["mesh.devices"]
        for r in ctx.requests
        for s in spans(r["roots"], "exec.dispatch")
        if "mesh.devices" in s["tags"]
    ]
    if not placed:
        return None
    return 100.0 * sum(n == chips for n in placed) / len(placed)


REDUCERS = {
    "per_chip_roofline_pct": per_chip_roofline_pct,
    "collective_share_pct": collective_share_pct,
    "sharded_dispatch_pct": sharded_dispatch_pct,
}


def read(source: dict, ctx):
    chips = ctx.config.get("chips")
    if not chips:
        return None
    return REDUCERS[source["reduce"]](ctx, chips)
