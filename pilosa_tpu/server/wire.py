"""Wire encoding of query results (internode reduce + public JSON).

Reference: /root/reference/encoding/proto/proto.go — every QueryResult
variant (Row, Pairs, ValCount, uint64, bool, RowIdentifiers, GroupCounts)
has a tagged wire form so the coordinating node can merge per-node partial
results (executor.go:2489-2518 reduce loop).

Here the internode form is tagged JSON; Row segments travel as
base64(uint32 positions) per shard so a remote node's partial Row merges
exactly (segment-aligned) into the coordinator's reduce, not as a lossy
column list."""

from __future__ import annotations

import base64
import struct
from typing import Any, Dict, List

import numpy as np

from pilosa_tpu.core.row import Row
from pilosa_tpu.exec.executor import FieldRow, GroupCount, Pair, ValCount
from pilosa_tpu.ops import bitmap as ob

# -- binary array streams (bulk data plane) ---------------------------------
#
# Raw little-endian uint64 arrays with a magic + length-prefixed framing,
# replacing JSON number lists for the bulk internode paths (imports, block
# deltas/data) — the role of the reference's protobuf bodies
# (encoding/proto/proto.go; http/client.go:319-669). JSON stays on the
# control plane; these are ~8 bytes/value instead of ~8-20 chars + parse.

ARRAYS_MAGIC = b"PTA1"
ARRAYS_CTYPE = "application/octet-stream"
_MAX_ARRAY_BYTES = 1 << 31  # 2 GiB bound: reject absurd length prefixes


def encode_arrays(*arrays) -> bytes:
    """magic | u32 n_arrays | per array: u32 length | raw <u8 bytes.

    Enforces the same _MAX_ARRAY_BYTES bound as decode_arrays: a sender
    must never produce a payload the receiver is guaranteed to reject
    (r2 advisor) — callers chunk oversized transfers instead."""
    parts = [ARRAYS_MAGIC, struct.pack("<I", len(arrays))]
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a, dtype=np.uint64))
        if a.nbytes > _MAX_ARRAY_BYTES:
            raise ValueError(
                f"array of {a.nbytes} bytes exceeds the {_MAX_ARRAY_BYTES}-byte "
                "wire frame bound; chunk the transfer"
            )
        parts.append(struct.pack("<I", a.size))
        parts.append(a.astype("<u8", copy=False).tobytes())
    return b"".join(parts)


def decode_arrays(data: bytes, expect: int) -> List[np.ndarray]:
    """Strictly validated inverse of encode_arrays (untrusted input)."""
    if len(data) < 8 or data[:4] != ARRAYS_MAGIC:
        raise ValueError("bad array-stream magic")
    (n,) = struct.unpack_from("<I", data, 4)
    if n != expect:
        raise ValueError(f"array-stream has {n} arrays, expected {expect}")
    off = 8
    out: List[np.ndarray] = []
    for _ in range(n):
        if off + 4 > len(data):
            raise ValueError("truncated array-stream header")
        (ln,) = struct.unpack_from("<I", data, off)
        off += 4
        nbytes = ln * 8
        if nbytes > _MAX_ARRAY_BYTES or off + nbytes > len(data):
            raise ValueError("truncated array-stream payload")
        out.append(np.frombuffer(data, dtype="<u8", count=ln, offset=off).copy())
        off += nbytes
    if off != len(data):
        raise ValueError("trailing bytes in array-stream")
    return out


def _b64_positions(words) -> str:
    pos = ob.unpack_positions(np.asarray(words)).astype(np.uint32)
    return base64.b64encode(pos.tobytes()).decode("ascii")


def _positions_from_b64(s: str) -> np.ndarray:
    return np.frombuffer(base64.b64decode(s), dtype=np.uint32)


def encode_result(r: Any) -> Dict[str, Any]:
    """Tagged internode encoding of one call result."""
    if isinstance(r, Row):
        return {
            "type": "row",
            "segments": {str(s): _b64_positions(w) for s, w in r.segments.items()},
            "attrs": r.attrs,
            "keys": r.keys,
        }
    if isinstance(r, bool):
        return {"type": "bool", "value": r}
    if isinstance(r, int):
        return {"type": "uint64", "value": r}
    if isinstance(r, ValCount):
        return {"type": "valcount", "value": r.value, "count": r.count}
    if isinstance(r, Pair):
        return {"type": "pair", "id": r.id, "count": r.count, "key": r.key}
    if isinstance(r, list):
        if all(isinstance(p, Pair) for p in r):
            return {
                "type": "pairs",
                "pairs": [{"id": p.id, "count": p.count, "key": p.key} for p in r],
            }
        if all(isinstance(g, GroupCount) for g in r):
            return {
                "type": "groupcounts",
                "groups": [
                    {
                        "group": [
                            {
                                "field": fr.field,
                                "rowID": fr.row_id,
                                "rowKey": fr.row_key,
                            }
                            for fr in g.group
                        ],
                        "count": g.count,
                        # present only under aggregate=Sum (exact int)
                        **({} if g.sum is None else {"sum": g.sum}),
                    }
                    for g in r
                ],
            }
        if all(isinstance(x, str) for x in r):
            return {"type": "rowkeys", "keys": r}
        if all(isinstance(x, int) for x in r):
            return {"type": "rowids", "rows": r}
    if r is None:
        return {"type": "none"}
    raise TypeError(f"cannot encode result of type {type(r)!r}")


def decode_result(d: Dict[str, Any]) -> Any:
    t = d.get("type")
    if t == "row":
        segments = {}
        for s, b in d.get("segments", {}).items():
            pos = _positions_from_b64(b)
            segments[int(s)] = ob.pack_positions(pos)
        row = Row(segments)
        row.attrs = d.get("attrs")
        row.keys = d.get("keys")
        return row
    if t == "bool":
        return bool(d["value"])
    if t == "uint64":
        return int(d["value"])
    if t == "valcount":
        return ValCount(value=int(d["value"]), count=int(d["count"]))
    if t == "pair":
        return Pair(id=int(d["id"]), count=int(d["count"]), key=d.get("key"))
    if t == "pairs":
        return [
            Pair(id=int(p["id"]), count=int(p["count"]), key=p.get("key"))
            for p in d["pairs"]
        ]
    if t == "groupcounts":
        return [
            GroupCount(
                group=[
                    FieldRow(
                        field=fr["field"],
                        row_id=int(fr.get("rowID") or 0),
                        row_key=fr.get("rowKey"),
                    )
                    for fr in g["group"]
                ],
                count=int(g["count"]),
                sum=None if g.get("sum") is None else int(g["sum"]),
            )
            for g in d["groups"]
        ]
    if t == "rowkeys":
        return list(d["keys"])
    if t == "rowids":
        return [int(x) for x in d["rows"]]
    if t == "none":
        return None
    raise TypeError(f"cannot decode result type {t!r}")


def result_to_public_json(r: Any) -> Any:
    """Public /index/{i}/query response form (reference: http/handler.go
    handlePostQuery JSON branch)."""
    if isinstance(r, Row):
        out: Dict[str, Any] = {"attrs": r.attrs or {}}
        out["columns"] = [int(c) for c in r.columns().tolist()]
        if r.keys is not None:
            out["keys"] = r.keys
        return out
    if isinstance(r, (bool, int)):
        return r
    if isinstance(r, (ValCount, Pair)):
        return r.to_json()
    if isinstance(r, list):
        return [x.to_json() if hasattr(x, "to_json") else x for x in r]
    if r is None:
        return None
    return r
