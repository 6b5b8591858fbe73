#!/usr/bin/env python3
"""Chip smoke: serve a 1 B-column index from the accelerator, end to end.

Starts `python -m pilosa_tpu.cli server` as a child with JAX_PLATFORMS=tpu
and no PILOSA_TPU_* variable, and talks to it over HTTP only, so exactly
one process holds the chip and this parent never initialises a JAX backend
(numpy + stdlib here; nothing of `pilosa_tpu` is imported). It

1. reads the device from `/info` and fails unless the platform is `tpu`;
2. loads the reference's scale anchor (1 B columns = 954 shards of 2^20)
   from --seed through the public import routes, every shard present in
   every field: a set field `f`, two low-cardinality set fields `g`/`h`,
   and an int field `v` deeper than one 16-plane BSI slab;
3. asks a few requests of every query family the executor has a device
   program for, with `?profile=1`, and compares every answer with a plain
   numpy set-algebra reference computed here from the same seeded arrays;
   each cold answer must carry at least one `exec.dispatch` span
   (unfiltered TopN is served from the rank cache: the one exception),
   and the GroupBy's cross tally must have run as the VMEM kernel on one
   chip (`groupby.kernel_tallies` on `/debug/vars`) over the view's four
   resident extents as they lie (`groupby.assembled_stacks` flat), as the
   XLA program on several, and so must the group tally of the filtered
   `GroupBy(..., aggregate=Sum(field=v))` on one chip, which on several
   runs the kernel under shard_map (`groupby.mesh_tallies`) and no XLA
   program; on a TPU the aggregate over a 250-row field of which the
   filter leaves 10 rows must have cut its tally to the rows it names
   (`groupby.compacted_tallies`, a 4 096-word tile in the span's
   `groupby.tally_words`), and the one over 30 resident rows must not;
   every cold dispatch must have run as one program over all
   the devices the server holds (`mesh.devices` on its span: 4 on a
   four-chip host, 1 on one chip);
4. writes (a PQL `Set`, then an `/import` burst large enough to cross the
   device-merge threshold), reads the writes back, restarts the server on
   the same data dir and reads them back again;
5. after the last server has exited, runs the GroupBy cross tally kernel
   (`ops/pallas_kernels.cross_counts`) directly at the real stack width
   in a child of its own (`cross_counts_child`).

Anything a phase raises ends the run non-zero; nothing is folded into the
output. Times printed are smoke timings on a cold process, not metrics.
The last line of stdout is `{"ok": true, "device": {...}}`.
"""

from __future__ import annotations

import argparse
import http.client
import importlib.metadata
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from urllib.parse import urlparse

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
INDEX = "smoke"
SET_FIELDS = ("f", "g", "h", "k")
SHARDS = 954  # 1 B columns at the shipped shard width (BASELINE.md)
PER_SHARD = 4096  # populated columns per shard
F_DENSITY = (0.60, 0.52, 0.45, 0.38, 0.31, 0.24, 0.17, 0.10)  # per f row
G_ROWS, H_ROWS = 3, 4
# a wide dimension, as a city is in a star schema: the group tally over 10
# of its rows, f's 8 and g's 3 is cut to the rows it names (the tile of
# every resident row would be 1 024 words)
K_ROWS, K_NAMED, K_SHARDS = 250, 10, 16
CUT_WORDS = 4096  # the group tally's tile once cut (GROUP_FULL_WORDS)
V_MAX = 1_000_000  # needs 20 planes: more than one 16-plane slab
V_SHARE = 0.75  # of populated columns carrying a value
V_THRESHOLD = 500_000
BURST_PER_SHARD = 74  # x 954 shards = 70 596 >= the 65 536 merge threshold
MAX_WRITES_PER_REQUEST = 5000  # the server's shipped request cap
START_TIMEOUT_S = 300
LOAD_WORKERS = 8


# ---------------------------------------------------------------------------
# data and the plain reference
# ---------------------------------------------------------------------------


class Data:
    """Seeded index contents as flat arrays over the populated columns.
    Column order is shard-major and ascending, so every boolean selection
    of `cols` is a sorted unique array of column ids."""

    def __init__(self, seed: int, shards: int, shard_width: int):
        rng = np.random.default_rng(seed)
        self.shards = shards
        self.shard_width = shard_width
        self.stride = shard_width // PER_SHARD
        # one populated column per stride-wide stratum: distinct, sorted,
        # and spread evenly over the row
        offs = rng.integers(0, self.stride, size=(shards, PER_SHARD))
        self.pos = np.arange(PER_SHARD) * self.stride + offs  # in-shard
        self.cols = (
            self.pos + np.arange(shards)[:, None] * shard_width
        ).astype(np.uint64)
        dens = np.asarray(F_DENSITY)[:, None, None]
        self.f = rng.random((len(F_DENSITY), shards, PER_SHARD)) < dens
        self.g = rng.integers(0, G_ROWS, size=(shards, PER_SHARD))
        self.h = rng.integers(0, H_ROWS, size=(shards, PER_SHARD))
        self.has_v = rng.random((shards, PER_SHARD)) < V_SHARE
        self.v = rng.integers(0, V_MAX + 1, size=(shards, PER_SHARD))
        self.k = rng.integers(0, K_ROWS, size=(shards, PER_SHARD))
        if not (self.f.any(axis=2).all() and self.has_v.any(axis=1).all()):
            raise AssertionError("a (row, shard) came out empty")

    def set_rows(self, field: str) -> list:
        """Boolean [shards, PER_SHARD] membership of each row of `field`."""
        if field == "f":
            return list(self.f)
        vals, n = {"g": (self.g, G_ROWS), "h": (self.h, H_ROWS),
                   "k": (self.k, K_ROWS)}[field]
        return [vals == r for r in range(n)]

    def unused_columns(self, strata) -> np.ndarray:
        """For every shard, one column in each of `strata` that the load
        never populates: uint64[shards * len(strata)], ascending."""
        strata = np.asarray(strata)
        lo = strata * self.stride
        p = lo + (self.pos[:, strata] - lo + 1) % self.stride
        return (
            p + np.arange(self.shards)[:, None] * self.shard_width
        ).astype(np.uint64).ravel()


class Reference:
    """The same operations on the same data with numpy set algebra over
    sorted column-id arrays (the semantics of `core/naive.py`)."""

    def __init__(self, data: Data):
        flat = data.cols.ravel()
        self.rows = {
            name: [flat[m.ravel()] for m in data.set_rows(name)]
            for name in SET_FIELDS
        }
        self.shards, self.width = data.shards, data.shard_width
        hv = data.has_v.ravel()
        self.v_cols = flat[hv]
        self.v_vals = data.v.ravel()[hv].astype(np.int64)
        self.exists = flat.copy()

    def row(self, field: str, rid: int) -> np.ndarray:
        return self.rows[field][rid]

    def add_bits(self, field: str, rid: int, cols) -> None:
        cols = np.asarray(cols, dtype=np.uint64)
        self.rows[field][rid] = np.union1d(self.rows[field][rid], cols)
        self.exists = np.union1d(self.exists, cols)

    def count_not(self, cols: np.ndarray) -> int:
        return len(np.setdiff1d(self.exists, cols, assume_unique=True))

    def topn(self, field: str, n: int, filt=None) -> list:
        counts = [
            (rid, len(r if filt is None else np.intersect1d(r, filt, True)))
            for rid, r in enumerate(self.rows[field])
        ]
        if len({c for _, c in counts}) != len(counts):
            raise AssertionError("tied TopN counts: order is not defined")
        counts.sort(key=lambda rc: -rc[1])
        return [{"id": rid, "count": c} for rid, c in counts[:n] if c]

    def topn_tanimoto(self, field: str, n: int, filt, threshold: int) -> list:
        """Filtered TopN under a Tanimoto threshold. The reference applies
        the threshold per fragment (fragment.top); this checks that every
        (row, shard) clears it, so the answer is the filtered TopN."""

        def per_shard(cols):
            return np.bincount(
                (cols // np.uint64(self.width)).astype(np.int64),
                minlength=self.shards,
            )

        src = per_shard(filt)
        for r in self.rows[field]:
            cnt, both = per_shard(r), per_shard(np.intersect1d(r, filt, True))
            clears = (
                (100 * both > threshold * (cnt + src - both))
                & (src * threshold < 100 * cnt)
                & (cnt * threshold < 100 * src)
            )
            if not clears.all():
                raise AssertionError("a (row, shard) is under the threshold")
        return self.topn(field, n, filt)

    def extreme_row(self, field: str, filt, is_min: bool) -> dict:
        """MinRow/MaxRow under a filter: the first/last row with a column in
        the filter, and how many."""
        rows = list(enumerate(self.rows[field]))
        for rid, r in rows if is_min else reversed(rows):
            n = len(np.intersect1d(r, filt, True))
            if n:
                return {"id": rid, "count": n}
        return {"id": 0, "count": 0}

    def _values(self, filt=None) -> np.ndarray:
        if filt is None:
            return self.v_vals
        return self.v_vals[np.isin(self.v_cols, filt, assume_unique=True)]

    def sum(self, filt=None) -> dict:
        vals = self._values(filt)
        return {"value": int(vals.sum()), "count": len(vals)}

    def extreme(self, fn) -> dict:
        m = int(fn(self.v_vals))
        return {"value": m, "count": int((self.v_vals == m).sum())}

    def count_gt(self, k: int) -> int:
        return int((self.v_vals > k).sum())

    def group_by(self, a: str, b: str) -> dict:
        out = {}
        for i, ra in enumerate(self.rows[a]):
            for j, rb in enumerate(self.rows[b]):
                n = len(np.intersect1d(ra, rb, True))
                if n:
                    out[(i, j)] = n
        return out


    def group_by_sum(self, fields, filt: np.ndarray) -> dict:
        """{(row of each field): (columns under the filter, sum of v over
        those of them that hold a value)}."""
        out = {}

        def walk(key, cols):
            if len(key) == len(fields):
                out[key] = (len(cols), int(self._values(cols).sum()))
                return
            for r, row in enumerate(self.rows[fields[len(key)]]):
                sub = np.intersect1d(cols, row, True)
                if len(sub):
                    walk(key + (r,), sub)

        walk((), filt)
        return out


def read_queries(ref: Reference) -> list:
    """(family, pql, expected answer, fewest `exec.dispatch` spans a cold
    run may show)."""
    f = [ref.row("f", r) for r in range(len(F_DENSITY))]
    agg_shards = min(ref.shards, 300)
    cut_shards = min(ref.shards, K_SHARDS)
    named_k = np.unique(np.concatenate(
        [ref.row("k", r) for r in range(K_NAMED)]))
    cut_filt = np.intersect1d(f[1], named_k, True)
    k_rows = ", ".join(f"Row(k={r})" for r in range(K_NAMED))
    return [
        ("count_intersect", "Count(Intersect(Row(f=0), Row(f=1)))",
         len(np.intersect1d(f[0], f[1], True)), 1),
        ("count_union", "Count(Union(Row(f=2), Row(f=3)))",
         len(np.union1d(f[2], f[3])), 1),
        ("count_difference", "Count(Difference(Row(f=4), Row(f=5)))",
         len(np.setdiff1d(f[4], f[5], True)), 1),
        ("count_xor", "Count(Xor(Row(f=6), Row(f=7)))",
         len(np.setxor1d(f[6], f[7], True)), 1),
        ("count_not", "Count(Not(Row(f=2)))", ref.count_not(f[2]), 1),
        # served from the rank cache (host metadata): no dispatch expected
        ("topn", "TopN(f, n=10)", ref.topn("f", 10), 0),
        ("topn_filtered", "TopN(f, Row(g=1), n=10)",
         ref.topn("f", 10, ref.row("g", 1)), 1),
        ("sum", "Sum(field=v)", ref.sum(), 1),
        ("sum_filtered", "Sum(Row(f=1), field=v)", ref.sum(f[1]), 1),
        # v is deeper than one 16-plane slab: at least two carried-state
        # slab steps (donated on the chip) and the finish
        ("min", "Min(field=v)", ref.extreme(np.min), 3),
        ("max", "Max(field=v)", ref.extreme(np.max), 3),
        ("count_range", f"Count(Row(v > {V_THRESHOLD}))",
         ref.count_gt(V_THRESHOLD), 1),
        ("group_by", "GroupBy(Rows(g), Rows(h))", ref.group_by("g", "h"), 1),
        # the filter's plan, then the group tally: each dimension against
        # the filter, and the groups the surviving rows make with v's planes
        # (on a four-chip host the kernel under shard_map on every chip).
        # Over the first 300 shards (two extents): v's 19 planes over all
        # 954 are more than a quarter of the shipped 4 GB budget, which
        # the executor tallies in four shard chunks
        ("group_by_sum",
         f"Options(GroupBy(Rows(g), Rows(h), filter=Row(f=1), "
         f"aggregate=Sum(field=v)), shards={list(range(agg_shards))})",
         ref.group_by_sum(("g", "h"), f[1][f[1] < agg_shards * ref.width]),
         2),
        # over a 250-row field of which the filter leaves 10 rows, f's 8
        # and g's 3, on the first 16 shards (8 a chip on each of the mesh's
        # rows of shards): on a TPU the tally is cut to the rows its table
        # names, 16 + 8 + 3 with v's 22 planes and the filter (SSB Q3.4's
        # 50 rows at city grain)
        ("group_by_sum_cut",
         f"Options(GroupBy(Rows(k), Rows(f), Rows(g), filter=Intersect("
         f"Row(f=1), Union({k_rows})), aggregate=Sum(field=v)), "
         f"shards={list(range(cut_shards))})",
         ref.group_by_sum(
             ("k", "f", "g"), cut_filt[cut_filt < cut_shards * ref.width]),
         2),
        # a filtered MinRow/MaxRow counts the [S, W] filter stack
        # (`ops/bitmap.popcount`), a Tanimoto TopN counts it per shard
        # (`popcount_rows`): the served path's only uses of the two
        ("minrow_filtered", "MinRow(Row(g=2), field=f)",
         ref.extreme_row("f", ref.row("g", 2), True), 1),
        ("maxrow_filtered", "MaxRow(Row(g=2), field=f)",
         ref.extreme_row("f", ref.row("g", 2), False), 1),
        ("topn_tanimoto", "TopN(f, Row(h=1), n=10, tanimotoThreshold=1)",
         ref.topn_tanimoto("f", 10, ref.row("h", 1), 1), 1),
    ]


# ---------------------------------------------------------------------------
# HTTP
# ---------------------------------------------------------------------------


class Http:
    """One keep-alive connection to the server; any non-200 raises."""

    def __init__(self, uri: str):
        u = urlparse(uri)
        self.conn = http.client.HTTPConnection(u.hostname, u.port, timeout=600)
        self.conn.connect()
        # headers and body go out as two sends: without this every request
        # waits out a delayed ACK
        self.conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def call(self, method: str, path: str, body=None):
        headers = {}
        if isinstance(body, (dict, list)):
            body = json.dumps(body).encode()
            headers["Content-Type"] = "application/json"
        elif isinstance(body, str):
            body = body.encode()
            headers["Content-Type"] = "text/plain"
        elif body is not None:
            headers["Content-Type"] = "application/octet-stream"
        self.conn.request(method, path, body=body, headers=headers)
        resp = self.conn.getresponse()
        raw = resp.read()
        if resp.status != 200:
            raise RuntimeError(f"{method} {path} -> {resp.status}: {raw[:500]!r}")
        return json.loads(raw)

    def close(self) -> None:
        self.conn.close()


def encode_roaring(positions: np.ndarray) -> bytes:
    """Sorted unique fragment positions -> a pilosa-dialect roaring file of
    array containers (the format header of `core/roaring_io.py`)."""
    keys, starts, counts = np.unique(
        positions >> 16, return_index=True, return_counts=True
    )
    if counts.max() > 4096:
        raise AssertionError("container too dense for an array container")
    n = len(keys)
    head = np.zeros(n, dtype=[("key", "<u8"), ("type", "<u2"), ("card", "<u2")])
    head["key"], head["type"], head["card"] = keys, 1, counts - 1
    data_at = 8 + 12 * n + 4 * n
    offsets = (data_at + 2 * starts).astype("<u4")
    return b"".join([
        np.array([12348, n], dtype="<u4").tobytes(),
        head.tobytes(),
        offsets.tobytes(),
        (positions & 0xFFFF).astype("<u2").tobytes(),
    ])


def _fan_out(uri: str, n_items: int, one) -> None:
    """Run `one(http, i)` for every i on a few keep-alive connections."""

    def work(chunk):
        http_ = Http(uri)
        try:
            for i in chunk.tolist():
                one(http_, i)
        finally:
            http_.close()

    chunks = np.array_split(np.arange(n_items), LOAD_WORKERS)
    with ThreadPoolExecutor(LOAD_WORKERS) as pool:
        for fut in [pool.submit(work, c) for c in chunks if len(c)]:
            fut.result()


def create_schema(http_: Http) -> None:
    http_.call("POST", f"/index/{INDEX}", {"options": {}})
    for name in SET_FIELDS:
        http_.call("POST", f"/index/{INDEX}/field/{name}",
                   {"options": {"type": "set"}})
    http_.call("POST", f"/index/{INDEX}/field/v",
               {"options": {"type": "int", "min": 0, "max": V_MAX}})


def load(uri: str, data: Data) -> dict:
    """Every shard of every field through the public import routes; returns
    the wall seconds of each field's load (smoke timings)."""
    took = {}
    for name in SET_FIELDS:
        rows = data.set_rows(name)

        def one(http_, s, rows=rows, name=name):
            frag_pos = np.concatenate([
                r * data.shard_width + data.pos[s, m[s]]
                for r, m in enumerate(rows)
            ])
            out = http_.call(
                "POST",
                f"/index/{INDEX}/field/{name}/import-roaring/{s}",
                encode_roaring(frag_pos),
            )
            if out["changed"] != len(frag_pos):
                raise AssertionError(f"{name}/{s}: changed {out}")

        t0 = time.perf_counter()
        _fan_out(uri, data.shards, one)
        took[name] = time.perf_counter() - t0

    def one_v(http_, s):
        m = data.has_v[s]
        if m.sum() > MAX_WRITES_PER_REQUEST:
            raise AssertionError("value batch above the request cap")
        http_.call("POST", f"/index/{INDEX}/field/v/import-value",
                   {"cols": data.cols[s, m].tolist(),
                    "values": data.v[s, m].tolist()})

    t0 = time.perf_counter()
    _fan_out(uri, data.shards, one_v)
    took["v"] = time.perf_counter() - t0
    return took


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------


def check_device(info: dict) -> dict:
    """The device the SERVER holds, from its `/info`; anything but a TPU is
    a failure (a server that fell back to the CPU answers every query)."""
    devices = info.get("devices") or []
    if not devices or any(d["platform"] != "tpu" for d in devices):
        raise RuntimeError(f"server is not on a TPU: /info devices = {devices}")
    return {
        "platform": devices[0]["platform"],
        "kind": devices[0]["deviceKind"],
        "count": len(devices),
    }


def _spans(span: dict, name: str):
    if span["name"] == name:
        yield span
    for c in span.get("children", ()):
        yield from _spans(c, name)


def _normalise(result):
    if isinstance(result, list) and result and "group" in result[0]:
        return {
            tuple(m["rowID"] for m in g["group"]):
                (g["count"], g["sum"]) if "sum" in g else g["count"]
            for g in result
        }
    return result


def ask(http_: Http, pql: str) -> tuple:
    """(answer, the tags of each exec.dispatch span, wall seconds) of one
    profiled query."""
    t0 = time.perf_counter()
    out = http_.call("POST", f"/index/{INDEX}/query?profile=1", pql)
    took = time.perf_counter() - t0
    dispatched = [
        s["tags"]
        for r in out["profile"]["roots"] for s in _spans(r, "exec.dispatch")
    ]
    return _normalise(out["results"][0]), dispatched, took


TALLY_GAUGES = ("kernel_tallies", "xla_tallies", "inplace_tallies",
                "assembled_stacks", "mesh_tallies", "compacted_tallies")


def tally_counts(http_: Http) -> tuple:
    """(kernel, xla, in-place, assembled, mesh, compacted): the tallies so
    far by program, those that read a view's extents where they lie, the
    operands concatenated for a tally, the kernel's tallies that ran
    under shard_map over the mesh, and its group tallies cut to the rows
    their table names (exec/groupby.py)."""
    vars_ = http_.call("GET", "/debug/vars")
    return tuple(int(vars_.get(f"groupby.{g}", 0)) for g in TALLY_GAUGES)


def check_tally_program(family: str, kernel: int, xla: int, device: dict,
                        assembled: int = 0, mesh: int = 0) -> None:
    """A cold GroupBy or filtered TopN tallies its cross on the device.
    On one TPU chip that is the VMEM kernel and never the XLA loop, and it
    reads the view's resident extents in place: no operand is written
    again as one stack (`assembled`). On several TPU chips the aggregate
    GroupBy's group tally is the same kernel under shard_map on every
    chip (`mesh`, each also a kernel tally), and never the XLA program;
    the count-only cross over sharded stacks, and anything on another
    backend, is the XLA program's. A JAX upgrade that breaks the kernel
    fails here."""
    tpu = device["platform"] == "tpu"
    one_chip = tpu and device["count"] == 1
    on_mesh = tpu and device["count"] > 1 and family.startswith("group_by_sum")
    ran, other = (kernel, xla) if one_chip or on_mesh else (xla, kernel)
    # a filtered TopN whose candidates are all sparse rows tallies no stack
    if other or not (ran or not family.startswith("group_by")) or (
        on_mesh and mesh != kernel
    ) or (not on_mesh and mesh):
        raise AssertionError(
            f"{family}: {kernel} kernel and {xla} XLA cross tallies on "
            f"{device['count']} x {device['platform']}, {mesh} kernel "
            f"tallies on the mesh"
        )
    if one_chip and assembled:
        raise AssertionError(
            f"{family}: {assembled} operand(s) concatenated for a tally on "
            f"one chip, where the kernel reads the extents in place"
        )


def check_cut(family: str, compacted: int, words: int, device: dict) -> None:
    """On a TPU, one chip or the mesh, the aggregate over `k` (250 resident
    rows, 10 named) is tallied on the rows its table names, at the
    4 096-word tile (`groupby.tally_words` of its dispatch), and the one
    over `g` and `h` (30 resident rows: a tile of 8 192 words or more
    already) on the rows as they are resident. Elsewhere the XLA program
    cuts nothing."""
    cut = device["platform"] == "tpu" and family == "group_by_sum_cut"
    if (compacted > 0) != cut or (cut and words != CUT_WORDS):
        raise AssertionError(
            f"{family}: {compacted} group tallies cut to their named rows, "
            f"a tile of {words} words, on {device['count']} x "
            f"{device['platform']}"
        )


def check_placement(family: str, placed: list, device: dict) -> None:
    """Every compiled dispatch of a cold query ran as one program over
    all the devices the server holds: 4 on a four-chip host, whose stacks
    lie on the 2 x 2 mesh, 1 on one chip. A stack placed on device 0
    alone, or a server whose mesh did not form, fails here."""
    if any(n != device["count"] for n in placed):
        raise AssertionError(
            f"{family}: exec.dispatch spans with mesh.devices {placed} on "
            f"{device['count']} x {device['platform']}"
        )


TALLY_FAMILIES = ("group_by", "group_by_sum", "group_by_sum_cut",
                  "topn_filtered")


def run_queries(http_: Http, queries: list, cold: bool,
                device: dict = None) -> None:
    for family, pql, want, min_dispatches in queries:
        tallied = cold and device is not None and family in TALLY_FAMILIES
        before = tally_counts(http_) if tallied else None
        got, dispatched, took = ask(http_, pql)
        n_dispatch = len(dispatched)
        if cold and device is not None:
            check_placement(
                family, [t.get("mesh.devices") for t in dispatched], device)
        if tallied:
            kernel, xla, inplace, assembled, mesh, compacted = (
                a - b for a, b in zip(tally_counts(http_), before))
            check_tally_program(family, kernel, xla, device, assembled, mesh)
            words = max(t.get("groupby.tally_words", 0) for t in dispatched)
            check_cut(family, compacted, words, device)
            print(f"  {family:22s} tallies: kernel={kernel} (mesh={mesh}) "
                  f"xla={xla} in place={inplace} assembled stacks={assembled} "
                  f"cut={compacted} tile={words}")
        if got != want:
            raise AssertionError(f"{family}: {pql} -> {got!r}, want {want!r}")
        if cold and n_dispatch < min_dispatches:
            raise AssertionError(
                f"{family}: cold answer with {n_dispatch} exec.dispatch "
                f"spans, expected at least {min_dispatches}"
            )
        print(f"  {family:22s} ok  dispatches={n_dispatch}  "
              f"smoke timing {'cold' if cold else 'repeat'} {took:.3f} s")


def merge_device_count(http_: Http) -> int:
    vars_ = http_.call("GET", "/debug/vars")
    return int(vars_.get("ingest.merge_device", 0))


def write_then_read(http_: Http, data: Data, ref: Reference) -> list:
    """A PQL Set, then an /import burst of new columns into f row 0 over
    every shard; each must be visible to the next Count. Returns the
    (pql, count) pairs a restarted server has to answer the same."""
    col = int(data.unused_columns([0])[0])  # shard 0, stratum 0
    out = http_.call("POST", f"/index/{INDEX}/query", f"Set({col}, f=1)")
    if out["results"] != [True]:
        raise AssertionError(f"Set -> {out}")
    ref.add_bits("f", 1, [col])
    readback = [("Count(Row(f=1))", len(ref.row("f", 1)))]
    run_queries(http_, [("set_then_count", *readback[0], 0)], cold=False)

    readback.append(import_burst(http_, data, ref, 100))
    return readback


def import_burst(http_: Http, data: Data, ref: Reference,
                 first_stratum: int) -> tuple:
    """An /import burst of one new column per shard in each of
    BURST_PER_SHARD strata from `first_stratum`, into f row 0, read back
    by the next Count. Returns that (pql, count)."""
    cols = data.unused_columns(
        np.arange(first_stratum, first_stratum + BURST_PER_SHARD))
    for lo in range(0, len(cols), MAX_WRITES_PER_REQUEST):
        part = cols[lo:lo + MAX_WRITES_PER_REQUEST].tolist()
        http_.call("POST", f"/index/{INDEX}/field/f/import",
                   {"rows": [0] * len(part), "cols": part})
    ref.add_bits("f", 0, cols)
    readback = ("Count(Row(f=0))", len(ref.row("f", 0)))
    run_queries(http_, [("import_then_count", *readback, 0)], cold=False)
    return readback


# ---------------------------------------------------------------------------
# the server process
# ---------------------------------------------------------------------------


class Server:
    """`python -m pilosa_tpu.cli server` as a child on JAX_PLATFORMS=tpu,
    whatever this process inherited, with no PILOSA_TPU_* variable."""

    def __init__(self, data_dir: str, log_path: str):
        env = {
            k: v for k, v in os.environ.items()
            if not k.startswith("PILOSA_TPU_")
        }
        env["JAX_PLATFORMS"] = "tpu"
        self.log_path = log_path
        self._log = open(log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu.cli", "server",
             "--data-dir", data_dir, "--bind", "localhost:0"],
            cwd=HERE, env=env, stdout=self._log, stderr=self._log,
        )
        self.line = self._await_listening()
        self.uri = re.search(r"listening on (\S+)", self.line).group(1)
        self.cache_dir = re.search(r"compile_cache=(\S+)", self.line).group(1)

    def _log_text(self) -> str:
        with open(self.log_path) as f:
            return f.read()

    def log_tail(self) -> str:
        return f"--- {self.log_path} (tail)\n{self._log_text()[-4000:]}"

    def _await_listening(self) -> str:
        deadline = time.monotonic() + START_TIMEOUT_S
        while time.monotonic() < deadline:
            for line in self._log_text().splitlines():
                if "listening on" in line:
                    return line
            if self.proc.poll() is not None:
                break
            time.sleep(0.2)
        self.stop()
        raise RuntimeError(
            f"server did not start (exit {self.proc.returncode})\n"
            + self.log_tail()
        )

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()

    def stop_clean(self) -> None:
        """SIGTERM, require the clean exit, and report the compile cache."""
        self.stop()
        if self.proc.returncode != 0:
            raise RuntimeError(
                f"server exited {self.proc.returncode} on SIGTERM\n"
                + self.log_tail()
            )
        entries = len(os.listdir(self.cache_dir))
        print(f"compile cache {self.cache_dir}: {entries} entries")


# ---------------------------------------------------------------------------
# the GroupBy cross tally kernel, driven directly (child process)
# ---------------------------------------------------------------------------


def cross_counts_child(seed: int) -> None:
    """Runs in a child of its own after the last server has exited:
    `ops/pallas_kernels.cross_counts` at the real stack width against
    numpy, at the filtered TopN's shape (one filter row against a chunk
    of two dense candidate rows — the smoke's own rows are sparse, so its
    served TopN tallies no stack) and fused over three stacks with a
    filter."""
    import jax

    if jax.devices()[0].platform != "tpu":
        raise RuntimeError(f"not on a TPU: {jax.devices()}")
    from pilosa_tpu.ops import pallas_kernels as pk
    from pilosa_tpu.shardwidth import WORDS_PER_ROW

    rng = np.random.default_rng(seed)
    shape = (SHARDS, WORDS_PER_ROW)
    a = rng.integers(0, 2**32, size=shape, dtype=np.uint32)
    b = rng.integers(0, 2**32, size=shape, dtype=np.uint32)
    pair = np.stack([b, a ^ b])
    ad, bd, pair_d = (jax.device_put(x) for x in (a, b, pair))

    def per_shard(x):
        return np.bitwise_count(x).astype(np.uint64).sum(axis=-1).tolist()

    checks = [
        ("g=1",
         np.asarray(pk.cross_counts(ad[None], pair_d)).tolist(),
         per_shard(a[None, None] & pair[None])),
        ("fused",
         np.asarray(pk.cross_counts(ad[None], pair_d, pair_d, bd)).tolist(),
         per_shard((a & b)[None, None, None] & pair[None, :, None]
                   & pair[None, None])),
    ]
    for name, have, want in checks:
        if have != want:
            raise AssertionError(f"cross_counts {name}: {have!r} != {want!r}")
        print(f"  cross_counts {name:6s} compiled, equal to numpy")


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cross-counts-kernel", action="store_true",
                    help=argparse.SUPPRESS)  # the smoke's own child mode
    args = ap.parse_args()
    if args.cross_counts_kernel:
        cross_counts_child(args.seed)
        return

    sys.stdout.reconfigure(line_buffering=True)
    print("installed: " + ", ".join(
        f"{pkg} {importlib.metadata.version(pkg)}"
        for pkg in ("jax", "jaxlib", "libtpu", "numpy")
    ))
    t_all = time.perf_counter()
    work = tempfile.mkdtemp(prefix="pilosa_smoke_")
    data_dir = os.path.join(work, "data")
    srv = None
    try:
        t0 = time.perf_counter()
        srv = Server(data_dir, os.path.join(work, "server1.log"))
        print(f"server: {srv.line}")
        print(f"smoke timing start {time.perf_counter() - t0:.1f} s")
        http_ = Http(srv.uri)
        info = http_.call("GET", "/info")
        device = check_device(info)
        print(f"device: platform={device['platform']} "
              f"device_kind={device['kind']!r} count={device['count']}")
        for d in info["devices"]:
            print(f"  device {d['id']}: bytes_limit={d['bytesLimit']} "
                  f"(hbm budget in force {info['hbmBudgetBytes']})")

        t0 = time.perf_counter()
        data = Data(args.seed, SHARDS, info["shardWidth"])
        ref = Reference(data)
        queries = read_queries(ref)
        print(f"reference: {SHARDS} shards x {PER_SHARD} columns, seed "
              f"{args.seed}, built in {time.perf_counter() - t0:.1f} s")

        create_schema(http_)
        for name, took in load(srv.uri, data).items():
            print(f"loaded field {name}: {SHARDS} shards over HTTP, "
                  f"smoke timing {took:.1f} s")

        print("cold queries:")
        run_queries(http_, queries, cold=True, device=device)
        info = http_.call("GET", "/info")
        for d in info["devices"]:
            print(f"  device {d['id']}: bytes_in_use={d['bytesInUse']}")
        if not all(d["bytesInUse"] for d in info["devices"]):
            raise AssertionError("a device holds no operand bytes after the load")
        print("repeat queries:")
        run_queries(http_, queries, cold=False)

        print("write then read:")
        before = merge_device_count(http_)
        readback = write_then_read(http_, data, ref)
        bursts = 1
        while merge_device_count(http_) == before:
            # the server's once-a-minute rank-cache flush
            # (holder.flush_caches) merges each fragment's staged delta on
            # the host as it passes; a burst staged while it runs reaches
            # the read barrier in pieces under the device threshold. A
            # warm compile cache puts this step about a minute after the
            # server's start, so it happens: burst again on new columns
            if bursts == 3:
                raise AssertionError(
                    "three import bursts did not run the device merge")
            print("  the burst was merged on the host in pieces; once more")
            readback[1] = import_burst(
                http_, data, ref, 100 + bursts * BURST_PER_SHARD)
            bursts += 1
        print(f"  ingest.merge_device moved after {bursts} burst(s)")
        http_.close()
        srv.stop_clean()

        t0 = time.perf_counter()
        srv = Server(data_dir, os.path.join(work, "server2.log"))
        print(f"restarted: {srv.line}")
        print(f"smoke timing restart {time.perf_counter() - t0:.1f} s")
        http_ = Http(srv.uri)
        check_device(http_.call("GET", "/info"))
        run_queries(http_, [("readback", q, n, 1) for q, n in readback],
                    cold=True)
        http_.close()
        srv.stop_clean()
        srv = None

        subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--cross-counts-kernel", "--seed", str(args.seed)],
            cwd=HERE, env=dict(os.environ, JAX_PLATFORMS="tpu"), check=True,
            timeout=600,
        )
    except BaseException:
        if srv is not None:  # what the server said, before its log goes
            print(srv.log_tail(), file=sys.stderr)
        raise
    finally:
        if srv is not None:
            srv.stop()
        shutil.rmtree(work, ignore_errors=True)
    print(f"smoke timing total {time.perf_counter() - t_all:.1f} s")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
