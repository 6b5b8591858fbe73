"""Mesh parallelism: the distributed query/ingest step.

This replaces the reference's per-shard mapReduce + HTTP fan-out
(/root/reference/executor.go:2460-2613 mapperLocal/worker pool, and the
cluster broadcast plane cluster.go/broadcast.go) with a compiled SPMD
program over a `jax.sharding.Mesh`:

- mesh axis "shards": the shard (column-block) axis — the reference's
  data-parallel unit (`shard = col / ShardWidth`). Each device owns a
  contiguous stripe of shards, exactly like nodes own shard partitions.
- mesh axis "cols": the word axis *within* a shard — sequence-parallel
  splitting of the column space, the analog of the reference's
  2^16-bit containers within a shard (fragment.go:55-63).

Reductions (Count, TopN tallies, BSI plane counts) become `lax.psum` over
both axes — they ride ICI instead of HTTP+protobuf. Union/Intersect are
elementwise and need no communication at all. Ingest is a bitwise-or merge
with buffer donation, the device-side analog of fragment.bulkImport
(fragment.go:1997).

Data layout: `data: uint32[S, R, W]` — S shards × R rows × W words,
sharded P("shards", None, "cols"). Rows are replicated across the mesh so
any row pair intersects locally (rows are the small axis; shards/cols are
the 2^64-column scale-out axes).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pilosa_tpu.ops import bitmap as ob
from pilosa_tpu.utils.locks import TrackedLock
from pilosa_tpu.utils.race import race_checked

_pc = jax.lax.population_count


def make_mesh(
    devices: Optional[Sequence] = None, shards_axis: Optional[int] = None
) -> Mesh:
    """Build a 2D ("shards", "cols") mesh over the given devices.

    The factorization favors the shard axis (the reference's scaling axis);
    "cols" gets a factor of 2 when the device count allows, exercising the
    sequence-parallel dimension."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if shards_axis is None:
        cols_axis = 2 if n % 2 == 0 and n >= 4 else 1
        shards_axis = n // cols_axis
    else:
        cols_axis = n // shards_axis
    if shards_axis * cols_axis != n:
        raise ValueError(f"cannot factor {n} devices into ({shards_axis}, {cols_axis})")
    arr = np.array(devices).reshape(shards_axis, cols_axis)
    return Mesh(arr, ("shards", "cols"))


DATA_SPEC = P("shards", None, "cols")


def shard_stack(mesh: Mesh, data: np.ndarray) -> jax.Array:
    """Place a [S, R, W] stack onto the mesh with the canonical sharding."""
    return jax.device_put(data, NamedSharding(mesh, DATA_SPEC))


# ---------------------------------------------------------------------------
# Active mesh: the executor's stacked query path places its [S, W] operand
# stacks with a NamedSharding over this mesh; jit's SPMD partitioner then
# splits the compiled plan across devices and inserts the collectives
# (replacing the reference's node fan-out, executor.go:2460-2613). With no
# active mesh the same code runs single-device.
# ---------------------------------------------------------------------------

_ACTIVE_MESH: Optional[Mesh] = None
_MESH_EPOCH = 0  # bumps on every set; cache keys include it


def set_active_mesh(mesh: Optional[Mesh]) -> None:
    global _ACTIVE_MESH, _MESH_EPOCH
    if mesh is _ACTIVE_MESH:
        return
    _ACTIVE_MESH = mesh
    _MESH_EPOCH += 1
    # placement changed: everything cached under the old placement is
    # unreachable (epoch-keyed) — free it now rather than waiting on LRU
    from pilosa_tpu.core.devcache import DEVICE_CACHE

    DEVICE_CACHE.clear()


def mesh_epoch() -> int:
    return _MESH_EPOCH


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH


def activate_default_mesh() -> Optional[Mesh]:
    """Activate a mesh over all local devices when there is more than one
    (server boot calls this; harmless single-device no-op). Idempotent:
    a second caller in the same process (e.g. every node of the in-process
    cluster harness) reuses the active mesh."""
    devices = jax.devices()
    if len(devices) > 1:
        if _ACTIVE_MESH is None or set(_ACTIVE_MESH.devices.flat) != set(devices):
            set_active_mesh(make_mesh(devices))
    return _ACTIVE_MESH


def device_report() -> list:
    """The devices this process holds, as JAX reports them: one dict per
    device with its platform, kind, id and `memory_stats()` bytes in use
    and limit (None where the backend reports no memory stats, as the
    CPU backend does). `/info` and the server's start-up line carry
    this, so a server that came up on the wrong backend says so."""
    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append(
            {
                "id": d.id,
                "platform": d.platform,
                "deviceKind": d.device_kind,
                "bytesInUse": stats.get("bytes_in_use"),
                "bytesLimit": stats.get("bytes_limit"),
            }
        )
    return out


# ---------------------------------------------------------------------------
# Mesh-group runtime: which cluster nodes share THIS process's ICI domain.
#
# A mesh group (cluster/topology.py Node.mesh_group, the [mesh] config knob)
# is the set of nodes whose chips sit in one ICI domain: their shards can be
# answered by ONE compiled sharded program with in-program collectives
# instead of per-node HTTP legs. Sharing an ICI domain means sharing the
# process's device mesh, so reachability is a process-local registry: each
# NodeServer registers its (group, node id, holder) on boot, and the
# distributed executor folds exactly the registered peers of its own group
# into the mesh dispatch (exec/meshgroup.py builds the group-spanning
# operand stacks from the registered holders). Unregistered peers — other
# processes, other ICI domains — keep riding HTTP/DCN.
# ---------------------------------------------------------------------------

@race_checked
class MeshGroupRegistry:
    """Process-local mesh-group membership: group -> node_id -> holder,
    plus a generation counter caches key on. One instance per process
    (module-global, like DEVICE_CACHE); every access goes through
    `self._mu` — the registry is read on the query hot path by every
    fan-out and written by NodeServer start/stop and topology installs,
    concurrently, so it is one of the race detector's designated
    shared objects."""

    def __init__(self) -> None:
        self._mu = TrackedLock("mesh.group_mu")
        self._members: dict = {}  # group -> node_id -> holder
        self._gen = 0  # bumps on every (un)register

    def register(self, group: str, node_id: str, holder) -> None:
        if not group:
            return
        with self._mu:
            self._members.setdefault(group, {})[node_id] = holder
            self._gen += 1

    def unregister(self, group: str, node_id: str) -> None:
        if not group:
            return
        with self._mu:
            members = self._members.get(group)
            if members is not None and members.pop(node_id, None) is not None:
                self._gen += 1
                if not members:
                    del self._members[group]

    def members(self, group: str) -> dict:
        if not group:
            return {}
        with self._mu:
            return dict(self._members.get(group, {}))

    def group_of(self, node_id: str) -> str:
        with self._mu:
            for group, members in self._members.items():
                if node_id in members:
                    return group
        return ""

    def generation(self) -> int:
        with self._mu:
            return self._gen


_GROUP_REGISTRY = MeshGroupRegistry()


def register_group_member(group: str, node_id: str, holder) -> None:
    """Announce that `node_id`'s shards are reachable in-process through
    `holder` for mesh-group execution (NodeServer.start)."""
    _GROUP_REGISTRY.register(group, node_id, holder)


def unregister_group_member(group: str, node_id: str) -> None:
    _GROUP_REGISTRY.unregister(group, node_id)


def group_members(group: str) -> dict:
    """node_id -> holder for every registered member of `group` (copy)."""
    return _GROUP_REGISTRY.members(group)


def registered_group_of(node_id: str) -> str:
    """The group `node_id` registered under in THIS process, or "" — used
    to enrich topology installs that predate a member's group config
    (server/node.py set_topology)."""
    return _GROUP_REGISTRY.group_of(node_id)


def group_generation() -> int:
    """Bumps whenever group membership changes; mesh-group operand caches
    (exec/meshgroup.py) key on it so a restarted member's stale holder is
    never read through a cached adapter."""
    return _GROUP_REGISTRY.generation()


def stack_sharding(ndim: int) -> Optional[NamedSharding]:
    """Sharding for a query-operand stack whose axis 0 is the shard axis and
    whose LAST axis is the word (column) axis: [S, W] row stacks get
    P("shards", "cols"); [D, S, W] BSI plane stacks replicate the plane axis
    and shard the trailing two. Returns None when no mesh is active."""
    mesh = _ACTIVE_MESH
    if mesh is None:
        return None
    if ndim == 2:
        spec = P("shards", "cols")
    elif ndim == 3:
        spec = P(None, "shards", "cols")
    else:
        spec = P("shards")
    return NamedSharding(mesh, spec)


def padded_shards(n_shards: int) -> int:
    """Shard-axis length after padding to the active mesh's "shards" axis
    (device_put requires dimension divisibility; zero-padded shards are
    semantically inert — absent rows are all-zero words)."""
    mesh = _ACTIVE_MESH
    if mesh is None:
        return n_shards
    m = mesh.shape["shards"]
    return ((n_shards + m - 1) // m) * m


def put_stack(data: np.ndarray) -> jax.Array:
    """device_put a host operand stack with the active mesh's sharding (or
    default placement when no mesh is active), zero-padding the shard axis
    to the mesh factor.

    BSI plane stacks are [D, S, W] with S on axis 1; everything else carries
    the shard axis first and words last."""
    sh = stack_sharding(np.ndim(data))
    if sh is None:
        return jax.device_put(data)
    shard_axis = 1 if np.ndim(data) == 3 else 0
    s = data.shape[shard_axis]
    target = padded_shards(s)
    if target != s:
        pad = [(0, 0)] * data.ndim
        pad[shard_axis] = (0, target - s)
        data = np.pad(data, pad)
    return jax.device_put(data, sh)


def _query_math(data, row_a: int, row_b: int):
    """The shared single-program query math over a local [S, R, W] block.

    Returns (intersect_count, union_count, row_counts[R], bsi_plane_counts)
    as LOCAL partial sums — callers psum them (mesh path) or use them
    directly (single device).
    """
    a = data[:, row_a, :]
    b = data[:, row_b, :]
    intersect_count = jnp.sum(_pc(jnp.bitwise_and(a, b)), dtype=jnp.uint32)
    union_count = jnp.sum(_pc(jnp.bitwise_or(a, b)), dtype=jnp.uint32)
    # per-row tallies: the TopN candidate counts AND the BSI per-plane counts
    # (planes are rows 2.. in a BSI fragment) in one reduction.
    row_counts = jnp.sum(_pc(data), axis=(0, 2), dtype=jnp.uint32)
    return intersect_count, union_count, row_counts


def make_query_step(mesh: Mesh, row_a: int = 0, row_b: int = 1):
    """Compiled distributed ingest+query step.

    One call = the full Pilosa serving loop for a query batch: merge a delta
    of new bits (ingest), then answer Count(Intersect), Count(Union) and the
    per-row tallies, with psum reductions over ICI. `data` is donated — the
    store updates in place in HBM.
    """

    def local_step(data, delta):
        data = jnp.bitwise_or(data, delta)
        inter, uni, rows = _query_math(data, row_a, row_b)
        inter = jax.lax.psum(inter, ("shards", "cols"))
        uni = jax.lax.psum(uni, ("shards", "cols"))
        rows = jax.lax.psum(rows, ("shards", "cols"))
        return data, inter, uni, rows

    sharded = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(DATA_SPEC, DATA_SPEC),
        out_specs=(DATA_SPEC, P(), P(), P()),
    )
    return jax.jit(sharded, donate_argnums=(0,))


def make_single_device_step(row_a: int = 0, row_b: int = 1):
    """Single-chip version of the query step (same math, no collectives)."""

    @partial(jax.jit, donate_argnums=(0,))
    def step(data, delta):
        data = jnp.bitwise_or(data, delta)
        inter, uni, rows = _query_math(data, row_a, row_b)
        return data, inter, uni, rows

    return step


# ---------------------------------------------------------------------------
# Sharded executor bridge: stack fragment rows across shards and answer
# multi-shard counts in one compiled call (used by bench + the server's
# fast path for large indexes).
# ---------------------------------------------------------------------------


@jax.jit
def count_and_stacked(a, b):
    """Total intersection count over stacked [S, W] rows. When a/b carry a
    NamedSharding, XLA partitions the reduction and inserts the all-reduce."""
    return jnp.sum(_pc(jnp.bitwise_and(a, b)), dtype=jnp.uint32)


@jax.jit
def count_stacked(a):
    return jnp.sum(_pc(a), dtype=jnp.uint32)


def stack_field_row(field, row_id: int, shards: Sequence[int]) -> np.ndarray:
    """Materialize one row across shards as a [S, W] host stack."""
    from pilosa_tpu.core.view import VIEW_STANDARD

    v = field.view(VIEW_STANDARD)
    rows = []
    for s in shards:
        frag = v.fragment_if_exists(s) if v is not None else None
        rows.append(frag.row_words(row_id) if frag is not None else ob.empty_row())
    return np.stack(rows)
