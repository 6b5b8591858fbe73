#!/usr/bin/env python3
"""`.xplane.pb` -> the device events the trace reduction reads, as JSON.

Run as a process of its own after the server has exited (jax's reader is
the only parser of the format the container has; this process is held to
the CPU backend and never touches the chip):

    python xplane.py <trace dir> <out.json>

Output: {"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, duration_ns], ...]}]}]} for every device plane
("/device:TPU:n"), plus "host_planes": their names only.
"""

from __future__ import annotations

import glob
import json
import os
import sys


def device_planes(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes, host = [], []
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            host.append(plane.name)
            continue
        lines = []
        for line in plane.lines:
            # an XLA op's name is its whole HLO line: keep what stands
            # before " = ", the instruction's own name
            events = [
                [ev.name.split(" = ")[0], int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
            ]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "host_planes": host}


def main() -> int:
    trace_dir, out = sys.argv[1], sys.argv[2]
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        print(f"no .xplane.pb under {trace_dir}", file=sys.stderr)
        return 1
    with open(out, "w") as f:
        json.dump(device_planes(found[-1]), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
