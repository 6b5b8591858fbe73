"""Tier object store: S3-shaped `ObjectStore` interface + local-directory
implementation.

The tier plane (pilosa_tpu/tier/) keeps immutable fragment SNAPSHOT
OBJECTS — `Fragment.to_bytes()` output taken at the WAL-truncation
consistency point — in a store addressed by flat slash-separated keys:

    snap/<index>/<field>/<view>/<shard>/<version>-<checksum>   (immutable)
    snap/<index>/<field>/<view>/<shard>/LATEST                 (manifest)

The object name embeds version and content checksum, so a fetched object
is self-verifying; LATEST is a tiny JSON manifest pointing at the current
object (rewritten atomically, never patched). The interface is the subset
of S3 semantics the tier needs — durable whole-object put, get, head,
prefix list/delete — so a real bucket client can drop in behind the same
calls. Stores are INJECTABLE (TierManager takes any ObjectStore) and
fault-wrappable: a module-level fault hook mirrors core/wal.py's
set_fault_hook, letting server/faults.py inject error / slow /
torn-object / missing-object / kill behavior point-prefix matched like
the WAL rules.

LocalDirStore persists puts with the WAL's tmp + fsync + os.replace +
dir-fsync idiom (core/wal.py write_snapshot): after put() returns, the
object survives a crash — which is what lets demotion order "snapshot
uploaded" strictly before "local copy deleted".
"""

from __future__ import annotations

import os
from typing import Callable, Dict, List, Optional


class StoreError(Exception):
    """Object-store operation failed (injected or real I/O error)."""


class ObjectMissing(StoreError):
    """The requested object does not exist."""


class ObjectCorrupt(StoreError):
    """Fetched object bytes do not match the checksum in its name."""


# -- fault hook (server/faults.py installs the injector's on_store) --------
#
# hook(point, key) may raise StoreError (error kind), sleep internally
# (slow kind), SIGKILL the process (kill kind), or return a directive the
# store honors: "torn" (persist/return truncated bytes — simulating a
# non-atomic backend or a corrupted object) or "missing" (pretend the
# object is gone). None = no fault.

_fault_hook: Optional[Callable[[str, str], Optional[str]]] = None


def set_fault_hook(fn: Optional[Callable[[str, str], Optional[str]]]) -> None:
    global _fault_hook
    _fault_hook = fn


def fault_point(point: str, key: str) -> Optional[str]:
    """Consult the installed fault hook (no-op when none). Kept public:
    the TierManager marks its own protocol windows (demote pre-delete,
    hydrate pre-apply) through the same hook so the kill matrix can
    place a SIGKILL between upload and local truncate."""
    hook = _fault_hook
    if hook is None:
        return None
    return hook(point, key)


def _validate_key(key: str) -> List[str]:
    parts = key.split("/")
    if not key or key.startswith("/") or any(
        p in ("", ".", "..") for p in parts
    ):
        raise StoreError(f"invalid object key {key!r}")
    return parts


class ObjectStore:
    """S3-shaped store interface. `put` must be DURABLE before returning
    (the demote ordering contract depends on it); `get` returns the whole
    object; `head` returns {"bytes": n} or None; `list` returns every key
    under a prefix; `delete` is idempotent."""

    def put(self, key: str, data: bytes) -> None:
        raise NotImplementedError

    def get(self, key: str) -> bytes:
        raise NotImplementedError

    def head(self, key: str) -> Optional[Dict[str, int]]:
        raise NotImplementedError

    def list(self, prefix: str = "") -> List[str]:
        raise NotImplementedError

    def delete(self, key: str) -> None:
        raise NotImplementedError

    def delete_prefix(self, prefix: str) -> int:
        """Remove every object under `prefix`; returns objects removed
        (index-delete GC). Default rides list+delete like S3 does."""
        n = 0
        for key in self.list(prefix):
            self.delete(key)
            n += 1
        return n


class LocalDirStore(ObjectStore):
    """Objects as files under a root directory (the store an operator
    points at a shared mount; also the test double for the S3-shaped
    API). Keys map to relative paths; puts are atomic and durable."""

    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)

    def _path(self, key: str) -> str:
        return os.path.join(self.root, *_validate_key(key))

    def put(self, key: str, data: bytes) -> None:
        directive = fault_point("store.put", key)
        if directive == "torn":
            # simulate a non-atomic backend persisting a partial object
            data = data[: len(data) // 2]
        path = self._path(key)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        # fsync the directory so the rename itself survives a crash
        # (same idiom as core/wal.py write_snapshot)
        dfd = os.open(os.path.dirname(path), os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)

    def get(self, key: str) -> bytes:
        directive = fault_point("store.get", key)
        if directive == "missing":
            raise ObjectMissing(key)
        path = self._path(key)
        try:
            with open(path, "rb") as f:
                data = f.read()
        except FileNotFoundError:
            raise ObjectMissing(key) from None
        if directive == "torn":
            data = data[: len(data) // 2]
        return data

    def head(self, key: str) -> Optional[Dict[str, int]]:
        directive = fault_point("store.head", key)
        if directive == "missing":
            return None
        try:
            st = os.stat(self._path(key))
        except FileNotFoundError:
            return None
        return {"bytes": int(st.st_size)}

    def list(self, prefix: str = "") -> List[str]:
        fault_point("store.list", prefix)
        out: List[str] = []
        for dirpath, _dirnames, filenames in os.walk(self.root):
            rel = os.path.relpath(dirpath, self.root)
            rel = "" if rel == "." else rel.replace(os.sep, "/") + "/"
            for fn in filenames:
                if fn.endswith(".tmp"):
                    continue  # torn put leftovers are not objects
                key = rel + fn
                if key.startswith(prefix):
                    out.append(key)
        return sorted(out)

    def delete(self, key: str) -> None:
        fault_point("store.delete", key)
        try:
            os.remove(self._path(key))
        except FileNotFoundError:
            pass


class MemoryStore(ObjectStore):
    """In-process dict-backed store (in-memory harness nodes, unit
    tests). Same fault-hook surface as LocalDirStore so fault tests can
    run without a filesystem."""

    def __init__(self):
        self._objects: Dict[str, bytes] = {}

    def put(self, key: str, data: bytes) -> None:
        directive = fault_point("store.put", key)
        _validate_key(key)
        if directive == "torn":
            data = data[: len(data) // 2]
        self._objects[key] = bytes(data)

    def get(self, key: str) -> bytes:
        directive = fault_point("store.get", key)
        if directive == "missing":
            raise ObjectMissing(key)
        data = self._objects.get(key)
        if data is None:
            raise ObjectMissing(key)
        if directive == "torn":
            data = data[: len(data) // 2]
        return data

    def head(self, key: str) -> Optional[Dict[str, int]]:
        directive = fault_point("store.head", key)
        if directive == "missing":
            return None
        data = self._objects.get(key)
        return None if data is None else {"bytes": len(data)}

    def list(self, prefix: str = "") -> List[str]:
        fault_point("store.list", prefix)
        return sorted(k for k in self._objects if k.startswith(prefix))

    def delete(self, key: str) -> None:
        fault_point("store.delete", key)
        self._objects.pop(key, None)
