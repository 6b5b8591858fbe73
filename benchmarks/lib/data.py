"""Seeded index contents for one configuration file, and the loader that
puts them into a server through the public import routes. Extended from
`chip_smoke.py` (`Data`, `encode_roaring`, `load`), which passed on the
chip in PR 21: there the schema was fixed in code, here it is the
configuration's `fields` list.

A field is one of
  set / "independent": every row an independent Bernoulli draw over the
      populated columns, with the row densities a fixed geometric ladder
      from `lo` to `hi`, permuted by the seed (every seed holds the same
      set of densities, so the load is the same work whatever the seed);
  set / "one_of": every populated column carries exactly one row, drawn
      with the stated `shares`;
  int: a value uniform in `min`..`max` on a `share` of the columns;
or of a kind the configuration's dialect brings (`lib/dialects/`): its draw,
its rows, its schema options and its loader are the dialect's.
"""

from __future__ import annotations

import http.client
import json
import socket
from concurrent.futures import ThreadPoolExecutor
from urllib.parse import urlparse

import numpy as np

from .dialects import BASE_KINDS, NONE, kind

MAX_WRITES_PER_REQUEST = 5000  # the server's shipped request cap
LOAD_WORKERS = 8
ROW_CHUNK = 32  # rows drawn at a time: bounds the float scratch


class Data:
    """Contents as flat arrays over the populated columns. Column order is
    shard-major and ascending, so a boolean selection of `cols` is a
    sorted unique array of column ids."""

    def __init__(self, config: dict, seed: int, shard_width: int,
                 dialect=NONE):
        rng = np.random.default_rng(seed)
        self.config = config
        self.dialect = dialect
        self.index = config["index"]
        self.shards = shards = config["shards"]
        self.per_shard = per = config["columns_per_shard"]
        self.shard_width = shard_width
        self.stride = shard_width // per
        # one populated column per stride-wide stratum: distinct, sorted,
        # spread evenly over the row
        offs = rng.integers(0, self.stride, size=(shards, per))
        self.pos = np.arange(per) * self.stride + offs  # in-shard position
        self.cols = (
            self.pos + np.arange(shards)[:, None] * shard_width
        ).astype(np.uint64)
        self.n = shards * per
        self.fields = {}
        for spec in config["fields"]:
            self.fields[spec["name"]] = self._draw(spec, rng)

    def _draw(self, spec: dict, rng) -> dict:
        n = self.n
        k = kind(spec)
        if k not in BASE_KINDS:
            return self.dialect.hook("draw", k)(self, spec, rng)
        if k == "int":
            has = rng.random(n) < spec["share"]
            vals = rng.integers(spec["min"], spec["max"] + 1, size=n)
            return {"spec": spec, "has": has, "values": vals.astype(np.int64)}
        if spec["membership"] == "one_of":
            shares = np.asarray(spec["shares"], dtype=np.float64)
            labels = rng.choice(len(shares), size=n, p=shares / shares.sum())
            return {"spec": spec, "rows": len(shares),
                    "labels": labels.astype(np.int16)}
        rows = spec["rows"]
        d = spec["density"]
        dens = rng.permutation(np.geomspace(d["lo"], d["hi"], rows))
        member = np.empty((rows, n), dtype=bool)
        for lo in range(0, rows, ROW_CHUNK):
            hi = min(rows, lo + ROW_CHUNK)
            draw = rng.random((hi - lo, n), dtype=np.float32)
            member[lo:hi] = draw < dens[lo:hi, None].astype(np.float32)
        return {"spec": spec, "rows": rows, "member": member}

    def n_rows(self, field: str) -> int:
        return self.fields[field]["rows"]

    def row_mask(self, field: str, rid: int) -> np.ndarray:
        """bool[n]: which populated columns row `rid` of `field` holds."""
        f = self.fields[field]
        if "member" in f:
            return f["member"][rid]
        if "labels" in f:
            return f["labels"] == rid
        return self.dialect.hook("row_mask", kind(f["spec"]))(self, f, rid)

    def shard_positions(self, field: str, s: int) -> np.ndarray:
        """Sorted fragment positions (row * width + in-shard position) of
        every set bit of a set field in shard `s`."""
        f = self.fields[field]
        lo, hi = s * self.per_shard, (s + 1) * self.per_shard
        if "member" in f:
            rows, idx = np.nonzero(f["member"][:, lo:hi])
        else:
            labels = f["labels"][lo:hi]
            idx = np.argsort(labels, kind="stable")
            rows = labels[idx].astype(np.int64)
        return rows * self.shard_width + self.pos[s, idx]

    def unused_columns(self, strata) -> np.ndarray:
        """For every shard, one column in each of `strata` that the load
        never populates: uint64[shards * len(strata)], ascending."""
        strata = np.asarray(strata)
        lo = strata * self.stride
        p = lo + (self.pos[:, strata] - lo + 1) % self.stride
        return (
            p + np.arange(self.shards)[:, None] * self.shard_width
        ).astype(np.uint64).ravel()


# ---------------------------------------------------------------------------
# HTTP
# ---------------------------------------------------------------------------


class HttpError(RuntimeError):
    def __init__(self, status: int, text: str):
        super().__init__(text)
        self.status = status


class Http:
    """One keep-alive connection to the server; any non-200 raises."""

    def __init__(self, uri: str, timeout: float = 600.0):
        u = urlparse(uri)
        self.conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout)
        self.conn.connect()
        # headers and body go out as two sends: without this every request
        # waits out a delayed ACK (PR 21: 44 ms in the sandbox)
        self.conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def call_raw(self, method: str, path: str, body=None) -> bytes:
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
            raise HttpError(
                resp.status, f"{method} {path} -> {resp.status}: {raw[:500]!r}"
            )
        return raw

    def call(self, method: str, path: str, body=None):
        return json.loads(self.call_raw(method, path, body))

    def close(self) -> None:
        self.conn.close()


def encode_roaring(positions: np.ndarray) -> bytes:
    """Sorted unique fragment positions -> a pilosa-dialect roaring file of
    array containers (the format header of `core/roaring_io.py`)."""
    keys, starts, counts = np.unique(
        positions >> 16, return_index=True, return_counts=True
    )
    if counts.max() > 4096:
        raise ValueError("container too dense for an array container")
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


def create_schema(http_: Http, config: dict, dialect=NONE) -> None:
    index = config["index"]
    http_.call("POST", f"/index/{index}", {"options": {}})
    for spec in config["fields"]:
        k = kind(spec)
        if k not in BASE_KINDS:
            options = dialect.hook("field_options", k)(spec)
        else:
            options = {"type": spec["type"]}
            if spec["type"] == "int":
                options.update(min=spec["min"], max=spec["max"])
        http_.call("POST", f"/index/{index}/field/{spec['name']}",
                   {"options": options})


def load(uri: str, data: Data) -> None:
    """Every shard of every field through the public import routes: set
    fields as one roaring file per shard, int fields as `import-value`
    requests under the server's request cap, a dialect's kind through
    the dialect's importer."""
    for name, f in data.fields.items():
        k = kind(f["spec"])
        if k not in BASE_KINDS:
            one = data.dialect.hook("importer", k)(data, name, f)
        elif k == "int":
            one = _value_importer(data, name, f)
        else:
            one = _roaring_importer(data, name)
        _fan_out(uri, data.shards, one)


def _roaring_importer(data: Data, name: str):
    def one(http_, s):
        frag_pos = data.shard_positions(name, s)
        out = http_.call(
            "POST",
            f"/index/{data.index}/field/{name}/import-roaring/{s}",
            encode_roaring(frag_pos),
        )
        if out["changed"] != len(frag_pos):
            raise RuntimeError(f"{name}/{s}: import changed {out}")

    return one


def _value_importer(data: Data, name: str, f: dict):
    def one(http_, s):
        lo, hi = s * data.per_shard, (s + 1) * data.per_shard
        m = f["has"][lo:hi]
        cols = data.cols[s, m]
        vals = f["values"][lo:hi][m]
        for at in range(0, len(cols), MAX_WRITES_PER_REQUEST):
            http_.call(
                "POST", f"/index/{data.index}/field/{name}/import-value",
                {"cols": cols[at:at + MAX_WRITES_PER_REQUEST].tolist(),
                 "values": vals[at:at + MAX_WRITES_PER_REQUEST].tolist()},
            )

    return one
