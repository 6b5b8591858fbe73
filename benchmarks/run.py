#!/usr/bin/env python3
"""The benchmark's one command:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

numpy + stdlib only: this process never imports jax or `pilosa_tpu`, so
the one child it starts (`serve.py`, the program's normal server) is the
only process that holds the chip. Everything about a cell is data:
`BENCHMARK.json` names the cell's configuration and traffic mix, and the
files `configs/<config>.json`, `traffic/<mix>.json` and
`metrics/<metric>.json` are found by those names; a configuration that
asks or stores what the base language has not names its dialect,
`lib/dialects/<name>.py` (the contract is that package's docstring).

A run: start the child, read the device from `/info` (a TPU, or fail),
create the schema, load through the public import routes, warm up this
cell's shapes (all of that is `setup_s`), drive the closed-loop clients for
`--seconds`, read counters and peak memory, check read-your-writes, stop
the child (exit 0 required), compare every answer of the window with the
numpy reference, and print one JSON object as the last line of stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import NamedTuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from lib import dialects, readers, trace as tracelib  # noqa: E402
from lib.data import Data, Http, HttpError, create_schema, load  # noqa: E402
from lib.reference import Reference  # noqa: E402
from lib.traffic import Mix  # noqa: E402

START_TIMEOUT_S = 300
# the server's start-up line (a traceback quotes the source that prints it)
LISTENING = re.compile(r"listening on (https?://\S+)")
ANSWER_WAIT_S = 60  # an answer due in the window may come this late


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def read_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """One entry of BENCHMARK.json's `workloads`, with its files, all
    found under `root` (the checkout; a test's own tree)."""

    def __init__(self, root: str, name: str):
        self.dir = os.path.join(root, os.path.basename(HERE))
        self.bench = read_json(root, "BENCHMARK.json")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.entry = cells[name]
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config_file = os.path.join(
            root, configs[self.entry["config"]]["file"]
        )
        self.config = read_json(self.config_file)
        self.mix = read_json(self.dir, "traffic", self.entry["traffic"] + ".json")
        self.dialect = dialects.load(self.dir, self.config.get("dialect"))

    def metrics(self, group: str) -> list:
        """This cell's metrics of `end_to_end` or `per_layer`: those with
        no `workloads` key, or with this cell in it."""
        return [
            m for m in self.bench[group]
            if self.name in m.get("workloads", [self.name])
        ]


# ---------------------------------------------------------------------------
# the server child
# ---------------------------------------------------------------------------


class Server:
    """`serve.py` as a child on JAX_PLATFORMS=tpu whatever this process
    inherited, with no PILOSA_TPU_* variable but the configuration's."""

    def __init__(self, cell: Cell, work: str):
        env = {
            k: v for k, v in os.environ.items()
            if not k.startswith("PILOSA_TPU_")
        }
        env["JAX_PLATFORMS"] = "tpu"
        env["PYTHONHASHSEED"] = "0"  # one source of run-to-run difference less
        env.update(cell.config["server"]["env"])
        self.log_path = os.path.join(work, "server.log")
        self._log = open(self.log_path, "w")
        self.t0 = time.perf_counter()
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "serve.py"),
             "--config", cell.config_file,
             "--data-dir", os.path.join(work, "data")],
            cwd=ROOT, env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._log, text=True,
        )
        line = self._await_listening()
        self.uri = LISTENING.search(line).group(1)
        self.cache_dir = re.search(r"compile_cache=(\S+)", line).group(1)
        log(f"server: {line}")

    def _log_text(self) -> str:
        with open(self.log_path) as f:
            return f.read()

    def log_tail(self) -> str:
        return f"--- {self.log_path} (tail)\n{self._log_text()[-4000:]}"

    def _await_listening(self) -> str:
        deadline = time.monotonic() + START_TIMEOUT_S
        while time.monotonic() < deadline:
            for line in self._log_text().splitlines():
                if LISTENING.search(line):
                    return line
            if self.proc.poll() is not None:
                break
            time.sleep(0.1)
        self.stop()
        raise RuntimeError(
            f"server did not start (exit {self.proc.returncode})\n"
            + self.log_tail()
        )

    def control(self, command: str) -> str:
        """One line to the child, one line back."""
        self.proc.stdin.write(command + "\n")
        self.proc.stdin.flush()
        reply = self.proc.stdout.readline().strip()
        if not reply or reply.startswith("error"):
            raise RuntimeError(f"server child on {command!r}: {reply!r}\n"
                               + self.log_tail())
        return reply

    def cache_entries(self) -> int:
        try:
            return len(os.listdir(self.cache_dir))
        except FileNotFoundError:
            return 0

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self.proc.stdin.close()
        self.proc.stdout.close()
        self._log.close()

    def stop_clean(self) -> None:
        self.stop()
        if self.proc.returncode != 0:
            raise RuntimeError(
                f"server exited {self.proc.returncode} on SIGTERM\n"
                + self.log_tail()
            )


def check_device(info: dict, require_tpu: bool = True) -> dict:
    """The device the SERVER holds, from its `/info`; anything but a TPU is
    a failure (a server that fell back to the CPU answers every query).
    The tests, which serve from a CPU node, pass `require_tpu=False`."""
    devices = info.get("devices") or []
    if require_tpu and (
        not devices or any(d["platform"] != "tpu" for d in devices)
    ):
        raise RuntimeError(f"server is not on a TPU: /info devices = {devices}")
    return {
        "platform": devices[0]["platform"],
        "kind": devices[0]["deviceKind"],
        "count": len(devices),
    }


# ---------------------------------------------------------------------------
# clients
# ---------------------------------------------------------------------------


class Record(NamedTuple):
    template: str
    text: str
    sent: float  # perf_counter at the send
    received: float  # and when the last byte was in
    status: int  # HTTP status; -1 for a broken connection
    raw: bytes


def client_loop(uri, path, stream, until, out, stop_flag) -> None:
    """One closed-loop client: the next request goes out when the last
    is answered, until the clock passes `until`."""
    http_ = Http(uri, timeout=ANSWER_WAIT_S + 300)
    try:
        while not stop_flag.is_set():
            sent = time.perf_counter()
            if sent >= until:
                break
            template, text = next(stream)
            status, raw = 200, b""
            try:
                raw = http_.call_raw("POST", path, text)
            except HttpError as e:
                status, raw = e.status, str(e).encode()
            except OSError as e:
                status, raw = -1, repr(e).encode()
                http_.close()
                http_ = Http(uri, timeout=ANSWER_WAIT_S + 300)
            out.append(Record(template, text, sent, time.perf_counter(),
                              status, raw))
    finally:
        http_.close()


def drive(uri, path, streams, seconds, during=None) -> tuple:
    """All clients for `seconds`; returns (records, the window's end).
    `during` runs on this thread while they work (the traced slice)."""
    outs = [[] for _ in streams]
    stop_flag = threading.Event()
    start = time.perf_counter()
    until = start + seconds
    threads = [
        threading.Thread(
            target=client_loop,
            args=(uri, path, s, until, o, stop_flag), daemon=True,
        )
        for s, o in zip(streams, outs)
    ]
    for t in threads:
        t.start()
    try:
        if during is not None:
            during()
        for t in threads:
            t.join(timeout=seconds + ANSWER_WAIT_S + 300)
    finally:
        stop_flag.set()
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a client never got its answer")
    return [r for o in outs for r in o], until


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def counters(http_: Http) -> dict:
    """`/debug/vars` counters summed over their tag sets, by stat name."""
    out = {}
    for key, v in http_.call("GET", "/debug/vars").items():
        if isinstance(v, (int, float)):
            name = key.split(";")[0]
            out[name] = out.get(name, 0) + v
    return out


def write_then_read(http_: Http, data: Data, ref: Reference) -> int:
    """The configuration's read-your-writes guarantee: an acknowledged
    `Set` and an acknowledged `/import` request are visible to the next
    `Count`. Returns how many of the two read-backs were wrong."""
    g = data.config["guarantees"]["read_your_writes"]
    field, wrong = g["field"], 0
    qpath = f"/index/{data.index}/query"
    col = int(data.unused_columns([0])[0])
    out = http_.call("POST", qpath, f"Set({col}, {field}={g['set_row']})")
    if out["results"] != [True]:
        raise RuntimeError(f"Set -> {out}")
    ref.add_columns(field, g["set_row"], [col])
    cols = data.unused_columns([1])[: g["import_columns"]].tolist()
    http_.call("POST", f"/index/{data.index}/field/{field}/import",
               {"rows": [g["import_row"]] * len(cols), "cols": cols})
    ref.add_columns(field, g["import_row"], cols)
    for rid in (g["set_row"], g["import_row"]):
        text = f"Count(Row({field}={rid}))"
        got = http_.call("POST", qpath, text)["results"][0]
        wrong += got != ref.answer(text)
    return wrong


def judge(records: list, ref: Reference) -> tuple:
    """Every answer of the window, in the reference's normal form, against
    the reference's. Returns (wrong, failed, [(record, parsed body)] of the
    right ones): a request that failed or was shed never got its answer;
    one that was answered wrongly, or not in the shape of an answer to its
    call, says the wrong thing."""
    wrong, failed, good = 0, 0, []
    for r in records:
        if r.status != 200:
            failed += 1
            if failed <= 3:
                log(f"FAILED {r.text}: {r.status} {r.raw[:600]!r}")
            continue
        body = json.loads(r.raw)
        want = ref.answer(r.text)
        try:
            got = ref.normalise(r.text, body["results"][0])
        except (KeyError, TypeError, IndexError):
            got = None  # no answer is None
        if got != want:
            wrong += 1
            if wrong <= 3:
                log(f"WRONG {r.text}: {str(body['results'][0])[:200]} != "
                    f"{str(want)[:200]}")
            continue
        good.append((r, body))
    return wrong, failed, good


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, server,
             work: str, require_tpu: bool = True) -> dict:
    """Everything of a run but starting the child. `server` gives `uri`,
    `t0`, `control()`, `cache_entries()` and `stop_clean()`."""
    config, mix_spec = cell.config, cell.mix
    http_ = Http(server.uri)
    info = http_.call("GET", "/info")
    device = check_device(info, require_tpu)
    if device["count"] != cell.entry["chips"]:
        raise RuntimeError(
            f"the cell asks for {cell.entry['chips']} chips, the server "
            f"holds {device['count']}"
        )
    if info["shardWidth"] != 1 << config["shard_width_exponent"]:
        raise RuntimeError(f"shard width {info['shardWidth']} is not the "
                           "configuration's")
    log(f"device: {device}  hbm budget in force {info.get('hbmBudgetBytes')}")

    # -- set-up: data, schema, load, warm-up ------------------------------
    t = time.perf_counter()
    data = Data(config, seed, info["shardWidth"], cell.dialect)
    ref = Reference(data)
    t_gen = time.perf_counter() - t
    create_schema(http_, config, cell.dialect)
    t = time.perf_counter()
    load(server.uri, data)
    t_load = time.perf_counter() - t

    mix = Mix(mix_spec, data.n_rows, seed)
    path = f"/index/{data.index}/query" + ("?profile=1" if trace else "")
    t = time.perf_counter()
    warm = cell.dialect.warmup_requests(mix)
    for text in warm:
        http_.call_raw("POST", path, text)
    t_stage = time.perf_counter() - t
    streams = [mix.stream(c) for c in range(mix.clients)]
    t = time.perf_counter()
    warm_records, _ = drive(
        server.uri, path, streams, mix_spec["warmup"]["mix_seconds"]
    )
    t_mix = time.perf_counter() - t
    setup_s = time.perf_counter() - server.t0
    log(f"set-up {setup_s:.1f} s: generate {t_gen:.1f}, load {t_load:.1f}, "
        f"{len(warm)} warm-up requests {t_stage:.1f}, warm-up mix "
        f"{t_mix:.1f} ({len(warm_records)} requests); the rest is the "
        "server's start")

    # -- the window --------------------------------------------------------
    before = counters(http_)
    cache_before = server.cache_entries()
    slice_ = {}

    def traced_slice():
        server.control(f"trace_start {os.path.join(work, 'trace')}")
        slice_["t0"] = time.perf_counter()
        time.sleep(min(mix_spec["trace_slice_s"], seconds))
        slice_["t1"] = time.perf_counter()
        server.control("trace_stop")

    records, end = drive(
        server.uri, path, streams, seconds, traced_slice if trace else None
    )
    after = counters(http_)
    cache_after = server.cache_entries()
    mem = json.loads(server.control("mem"))
    peak = max((m.get("peak_bytes_in_use", 0) for m in mem), default=0)
    in_use = [d["bytesInUse"] for d in http_.call("GET", "/info")["devices"]]
    log(f"compile-cache entries before / after the window: {cache_before} / "
        f"{cache_after}; device bytes in use {in_use}, peak {peak}")

    # -- after the window: guarantees, stop, compare ----------------------
    readback_wrong = write_then_read(http_, data, ref)
    http_.close()
    server.stop_clean()

    t = time.perf_counter()
    wrong, failed, answered = judge(records, ref)
    log(f"reference compared {len(records)} answers in "
        f"{time.perf_counter() - t:.1f} s")
    lat = [(r.received - r.sent) * 1000.0 for r, _ in answered]
    if not lat:
        raise RuntimeError("no request of the window was answered")
    in_window = sum(r.received <= end for r, _ in answered)
    per_template = {}
    for (r, _), ms in zip(answered, lat):
        per_template.setdefault(r.template, []).append(ms)
    for name, ms in sorted(per_template.items()):
        log(f"  {name:12s} n={len(ms):6d}  median {np.median(ms):9.3f} ms  "
            f"max {max(ms):9.3f} ms")
    slowest = max(answered, key=lambda a: a[0].received - a[0].sent)[0]
    log(f"  slowest: {slowest.template}, sent {slowest.sent - (end - seconds):.2f}"
        " s into the window")

    checks = {
        "wrong_answers": {"value": wrong, "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
        "readback_wrong": {"value": readback_wrong, "limit": 0},
    }
    if trace:
        t = time.perf_counter()
        ctx = readers.Context(
            config=config, dialect=cell.dialect, answered=answered,
            before=before, after=after,
            kind=device["kind"], require_peak=require_tpu,
            slice_=(slice_["t0"], slice_["t1"]),
            planes=tracelib.extract(os.path.join(work, "trace"), work),
        )
        log(f"trace read and reduced in {time.perf_counter() - t:.1f} s")
        metrics = {}
        for m in cell.metrics("per_layer"):
            spec = read_json(cell.dir, "metrics", m["name"] + ".json")
            value = readers.read(spec, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=ctx.trace["busy_s"], window_s=ctx.trace["window_s"])
        breakdown = ctx.trace["breakdown"]
    else:
        values = {
            "qps": in_window / seconds,
            "latency_p50_ms": float(np.percentile(lat, 50)),
            "latency_p95_ms": float(np.percentile(lat, 95)),
            "setup_s": setup_s,
        }
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.metrics("end_to_end")
        }
        breakdown = None
    device["memory_peak_bytes"] = peak
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(records),
        "failed": failed + wrong,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "pilosa_tpu")):
        log("no pilosa_tpu/ beside benchmarks/: nothing to measure")
        return 2
    cell = Cell(ROOT, args.workload)
    work = tempfile.mkdtemp(prefix="pilosa_bench_")  # under TMPDIR
    server = None
    try:
        server = Server(cell, work)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          server, work)
    except BaseException:
        if server is not None:
            log(server.log_tail())
        raise
    finally:
        if server is not None:
            server.stop()
        shutil.rmtree(work, ignore_errors=True)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
