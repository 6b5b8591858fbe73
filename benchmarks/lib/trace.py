"""From the profiler's device planes to busy seconds, the operations that
took most time and the longest idle gaps.

Busy is the union of the intervals in which an operation ran on a device
(its "XLA Ops" line), averaged over the device planes; the window is the
traced slice as the harness timed it around the profiler's start and stop.
The program puts no span on the profiler's clock yet, so every idle gap is
"unattributed" (PERF.md, for the tracing issue)."""

from __future__ import annotations

import bisect
import json
import os
import subprocess
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def extract(trace_dir: str, work: str) -> list:
    """Device planes of the trace under `trace_dir`, read by `xplane.py`
    in a process of its own held to the CPU backend."""
    out = os.path.join(work, "planes.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "xplane.py"),
         trace_dir, out],
        env=env, check=True, timeout=240,
    )
    with open(out) as f:
        return json.load(f)["planes"]


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def op_events(plane: dict) -> list:
    """[name, start_ns, duration_ns] of every device operation of a plane,
    named `<jitted program>/<instruction>`: an instruction's name alone
    (`%while`, `%fusion.3`) repeats from program to program. A loop's own
    event spans its body's, so a `%while` total holds its body's ops'."""
    modules = sorted((s, s + d, n) for n, s, d in _line(plane, MODULES_LINE))
    starts = [m[0] for m in modules]
    out = []
    for name, start, dur in _line(plane, OPS_LINE):
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < modules[i][1]:
            name = f"{modules[i][2].split('(')[0]}/{name}"
        out.append([name, start, dur])
    return out


def busy_and_gaps(events: list) -> tuple:
    """(busy ns, idle gaps ns between operations) of one device."""
    spans = sorted((s, s + d) for _, s, d in events)
    busy, gaps, end = 0, [], None
    for s, e in spans:
        if end is None:
            busy, end = e - s, e
        elif s > end:
            gaps.append(s - end)
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    return busy, gaps


def reduce(planes: list, window_s: float) -> dict | None:
    """None where no operation ran on any device plane."""
    per_plane = [op_events(p) for p in planes]
    per_plane = [ev for ev in per_plane if ev]
    if not per_plane:
        return None
    busy, gaps, by_name = [], [], {}
    for events in per_plane:
        b, g = busy_and_gaps(events)
        busy.append(b)
        gaps.extend(g)
        for name, _, d in events:
            by_name[name] = by_name.get(name, 0) + d
    n = len(per_plane)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": window_s,
        "breakdown": {
            "device_ops": [[name, d / n / 1e9] for name, d in ops],
            "idle_gaps": [
                ["unattributed", g / 1e9]
                for g in sorted(gaps, reverse=True)[:TOP]
            ],
        },
    }
