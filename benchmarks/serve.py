#!/usr/bin/env python3
"""The benchmark's server child: the one process that holds the chip.

Starts the program's normal server (`cli.main.cmd_server`: NodeServer +
server/handler.py, what `python -m pilosa_tpu.cli server` runs) with the
settings of a configuration file, and adds one thing only a process that
holds the chip can do: on a line from the parent it starts or stops
`jax.profiler`, or reports the devices' peak memory.

  trace_start <dir>   -> "ok"
  trace_stop          -> "ok"
  mem                 -> one JSON line: memory_stats() of every device
  (end of input, or SIGTERM) -> stop the server, exit 0
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))  # the checkout: `pilosa_tpu`


class _Stop(Exception):
    pass


def _raise_stop(*_):
    raise _Stop()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--data-dir", required=True)
    args = ap.parse_args()

    import jax

    from pilosa_tpu.cli.config import Config
    from pilosa_tpu.cli.main import cmd_server

    with open(args.config) as f:
        settings = json.load(f)["server"]["toml"]
    cfg = Config.load(overrides={
        **settings, "data-dir": args.data_dir, "bind": "localhost:0",
    })
    srv = cmd_server(cfg, wait=False)  # prints the "listening on" line
    signal.signal(signal.SIGTERM, _raise_stop)
    signal.signal(signal.SIGINT, _raise_stop)
    tracing = False
    try:
        for line in sys.stdin:
            words = line.split()
            if not words:
                continue
            if words[0] == "trace_start":
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0  # device planes are what is read
                opts.host_tracer_level = 1
                jax.profiler.start_trace(words[1], profiler_options=opts)
                tracing = True
                reply = "ok"
            elif words[0] == "trace_stop":
                jax.profiler.stop_trace()
                tracing = False
                reply = "ok"
            elif words[0] == "mem":
                reply = json.dumps(
                    [d.memory_stats() or {} for d in jax.devices()]
                )
            else:
                reply = f"error unknown command {words[0]}"
            print(reply, flush=True)
    except _Stop:
        pass
    finally:
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        if tracing:
            jax.profiler.stop_trace()
        srv.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
