"""Mesh-group lowering: one compiled sharded program over an ICI domain.

The distributed executor (exec/distributed.py) answers a multi-node query
with one HTTP leg per owner node plus a host-side reduce, and every leg
ends in its own blocking host read (a synchronisation each). Nodes
that share an ICI domain (cluster/topology.py Node.mesh_group, the [mesh]
knob set) don't need the transport at all: their chips sit on one device
mesh, so their shards can be staged as ONE NamedSharding-placed operand
stack and the whole call tree evaluated as ONE compiled program whose
reduction ends in the collective (exec/plan.py "total" mode) — exactly one
dispatch and one blocking host read regardless of how many nodes or shards
the group spans. HTTP/DCN remains the transport only ACROSS groups,
mirroring the reference's cluster-over-mapReduce split at L2/L3.

Mechanics: a mesh group's members register their holders in the process-
local registry (parallel/mesh.py register_group_member — sharing an ICI
domain means sharing the process's device mesh). This module wraps the
registered holders in Group* adapters that present the group's UNION of
shards as one index to the UNCHANGED single-node lowering
(executor._StackedLowering): GroupView stages a row across the group as
one [S, W] stack (shard -> owning member resolved through the fan-out's
assignment), so Count/Intersect/Union/Difference/Xor/Not trees, BSI
condition rows and the TopN tally all lower exactly as they do on one
node — the mesh IS the executor, now spanning the group.

Staging coexists with the extent path: group stacks ride the same
hbm/residency staging (monolithic under an active mesh — XLA owns
cross-chip layout — extent-paged otherwise) with fragment versions baked
into the cache keys, so a member's write re-keys the covering entry and
the next query re-stages exactly the dirty slice; entries are owned by
per-group tokens and never served stale.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

from pilosa_tpu.core.devcache import DEVICE_CACHE, new_owner_token
from pilosa_tpu.parallel import mesh as pmesh
from pilosa_tpu.pql.ast import Call
from pilosa_tpu.utils.locks import TrackedLock


class MeshUnsupported(Exception):
    """The call (or its operands) has no mesh-group form; the caller falls
    back to per-node HTTP legs — never an error surface. `reason` is a
    LOW-CARDINALITY tag (budget / no_stacked_form / unsupported) for the
    `mesh.fallback` counter, so fallback-rate regressions are visible on
    dashboards instead of silent."""

    def __init__(self, msg: str = "", reason: str = "unsupported"):
        super().__init__(msg)
        self.reason = reason


# Calls the mesh-group path may fold into one sharded program. Shift is
# excluded: its cross-shard carry reads predecessor shards that may live
# OUTSIDE the group (per-node execution composes carries locally, which the
# group-spanning stack cannot reproduce for foreign predecessors). Time
# ranges (from/to args) are excluded because time-view discovery walks the
# COORDINATOR's view list, which need not cover views materialized only on
# a peer. Sum/Min/Max fold via the plane-streamed aggregates
# (exec/bsistream.py): the group adapter's plane stacks stage under the
# mesh sharding and the kernels' in-program reductions partition into the
# cross-device psum, so a mesh-group BSI aggregate is one dispatch + one
# scalar host read regardless of group size — the Count "total" contract
# extended to the whole BSI family.
_ELIGIBLE = frozenset(
    {"Count", "Row", "Union", "Intersect", "Difference", "Xor", "Not", "All",
     "TopN", "Sum", "Min", "Max"}
)


def eligible(c: Call) -> bool:
    """True when the whole call tree is foldable into a mesh-group
    dispatch (structure check only — operand shapes may still bail to
    MeshUnsupported at lowering time)."""
    if c.name not in _ELIGIBLE:
        return False
    if "from" in c.args or "to" in c.args:
        return False
    for child in c.children:
        if not eligible(child):
            return False
    for v in c.args.values():
        if isinstance(v, Call) and not eligible(v):
            return False
    return True


# ---------------------------------------------------------------------------
# dispatch accounting (satellite: observability contract). Cumulative
# counters; NodeServer.publish_cache_gauges publishes them as the mesh.*
# gauge families at every scrape/sampler tick.
# ---------------------------------------------------------------------------

_stats_mu = TrackedLock("meshgroup.stats_mu")
_counters: Dict[str, int] = {
    "dispatches": 0,  # mesh-group partials computed
    "local_shards": 0,  # shards served mesh-locally (no HTTP leg, cumulative)
    "collective_bytes": 0,  # bytes moved by in-program collectives (cumulative)
    "fallbacks": 0,  # eligible fan-outs that bailed back to HTTP legs
}


def note_dispatch(group_size: int, n_shards: int, collective_bytes: int) -> None:
    with _stats_mu:
        _counters["dispatches"] += 1
        _counters["local_shards"] += n_shards
        _counters["collective_bytes"] += collective_bytes
    del group_size  # tagged on the span; the gauge reads the live registry


def note_fallback() -> None:
    with _stats_mu:
        _counters["fallbacks"] += 1


def stats_snapshot() -> Dict[str, int]:
    with _stats_mu:
        return dict(_counters)


def reset_stats() -> None:
    with _stats_mu:
        for k in _counters:
            _counters[k] = 0


# ---------------------------------------------------------------------------
# Group adapters: present the group's union of shards as ONE index/field/
# view to the unchanged single-node stacked lowering.
# ---------------------------------------------------------------------------


class GroupView:
    """One (field, view) across the group: the shape _StackedLowering and
    the TopN tally expect of a View, with shard -> owning member resolved
    through the fan-out's assignment. Operand stacks are staged through
    hbm/residency under this view's own owner token, version-keyed per
    shard position exactly like View.row_stack — a member's write re-keys
    the covering entry, so group stacks are never served stale."""

    def __init__(self, gidx: "GroupIndex", view_name: str,
                 member_field: Callable[[object], Optional[object]]):
        self.index = gidx.name
        self.name = view_name
        self._gidx = gidx
        self._member_field = member_field
        self._stack_token = new_owner_token()
        self._view_memo: Dict[str, Optional[object]] = {}

    # -- member resolution --------------------------------------------------

    def _view_of(self, node_id: Optional[str]):
        if node_id is None:
            return None
        v = self._view_memo.get(node_id)
        if v is None:
            # memoize only RESOLVED views: views materialize lazily on a
            # member's first write, and this adapter is cached across
            # queries — a memoized miss would pin the view invisible (and
            # its rows at zero) long after data landed. Re-resolving a
            # miss is three dict lookups; a member-side field recreate
            # also heals through the same re-resolution.
            holder = self._gidx.members.get(node_id)
            idx = holder.index(self._gidx.name) if holder is not None else None
            f = self._member_field(idx) if idx is not None else None
            v = f.view(self.name) if f is not None else None
            if v is not None:
                self._view_memo[node_id] = v
        return v

    def _owner_view(self, shard: int):
        return self._view_of(self._gidx.assignment.get(shard))

    # -- the View surface the lowering and tally paths use ------------------

    def fragment_if_exists(self, shard: int):
        v = self._owner_view(shard)
        return v.fragment_if_exists(shard) if v is not None else None

    def row_summary(self):
        """No group-wide table (View.row_summary): each member view keeps
        its own, over its own shards and under its own clock, so readers
        of the group's union walk the fragments."""
        return None

    def _frags_for(self, shards: Tuple[int, ...]):
        """(frags by position, member view -> its frags) for one stack."""
        frags = []
        by_view: Dict[int, Tuple[object, List[object]]] = {}
        for s in shards:
            v = self._owner_view(s)
            frag = v.fragment_if_exists(s) if v is not None else None
            frags.append(frag)
            if v is not None and frag is not None:
                by_view.setdefault(id(v), (v, []))[1].append(frag)
        return frags, by_view

    def sync_pending(self, shards=None, frags=None) -> None:
        """Read barrier across the group: each member view merges its own
        staged burst (core/merge.py batches per member — no fragment lock
        is ever held across another member's)."""
        if frags is None:
            if shards is None:
                return
            frags = [self.fragment_if_exists(s) for s in shards]
        by_view: Dict[int, Tuple[object, List[object]]] = {}
        for frag in frags:
            if frag is None:
                continue
            v = self._owner_view(frag.shard)
            if v is not None:
                by_view.setdefault(id(v), (v, []))[1].append(frag)
        for v, fl in by_view.values():
            v.sync_pending(frags=fl)

    def _base_key(self, kind: str, ident, shards: tuple) -> tuple:
        # same shape as View._stack_key so downstream key handling (extent
        # spans, version slices) parses identically; staging appends the
        # per-extent version slices itself
        return (self._stack_token, kind, ident, shards, pmesh.mesh_epoch())

    def _stack_key(self, kind: str, ident, shards: tuple) -> tuple:
        """Version-salted key for EXTERNAL cachers (the TopN tally
        bundle). Nothing eagerly invalidates group-token entries — a
        member fragment's on_mutate only fires on its OWN view's token —
        so correctness rests entirely on the versions baked in here: a
        member write re-keys the entry and the stale one ages out via
        LRU, exactly like the staged stacks' version slices."""
        shards = tuple(shards)
        frags, _ = self._frags_for(shards)
        versions = tuple(f.version if f is not None else -1 for f in frags)
        return self._base_key(kind, ident, shards) + (versions,)

    def row_stack(self, row_id: int, shards, extents=None,
                  parts: bool = False):
        """uint32[S, W] device stack of one row over the GROUP's shards
        (None when wholly absent) — the group-spanning analog of
        View.row_stack, staged under this adapter's owner token."""
        from pilosa_tpu.hbm import residency as hbm_res

        shards = tuple(shards)
        frags, by_view = self._frags_for(shards)
        if all(f is None for f in frags):
            return None
        for v, fl in by_view.values():
            v.sync_pending(frags=fl)
        versions = tuple(f.version if f is not None else -1 for f in frags)
        key = self._base_key("row", row_id, shards)

        def build_slice(lo: int, hi: int):
            return hbm_res.build_row_slice(frags[lo:hi], row_id)

        return hbm_res.stage_row_stack(
            key, len(shards), build_slice, table=extents,
            versions=versions, shards=shards, index=self.index,
            parts=parts,
        )

    def plane_stack(self, row_ids, shards, extents=None,
                    parts: bool = False):
        """uint32[D, S, W] BSI plane stack over the group's shards."""
        from pilosa_tpu.hbm import residency as hbm_res

        row_ids = tuple(row_ids)
        shards = tuple(shards)
        frags, by_view = self._frags_for(shards)
        if all(f is None for f in frags):
            return None
        for v, fl in by_view.values():
            v.sync_pending(frags=fl)
        versions = tuple(f.version if f is not None else -1 for f in frags)
        key = self._base_key("planes", row_ids, shards)

        def build_slice(lo: int, hi: int):
            return hbm_res.build_plane_slice(frags[lo:hi], row_ids)

        return hbm_res.stage_plane_stack(
            key, len(shards), build_slice, table=extents,
            versions=versions, shards=shards, index=self.index,
            parts=parts,
        )

    def close(self) -> None:
        DEVICE_CACHE.invalidate_owner(self._stack_token)


class GroupField:
    """Field adapter: schema/metadata (options, BSI base math, row attrs —
    all replicated cluster-wide) comes from the coordinator's field; DATA
    access goes through GroupViews spanning the members."""

    def __init__(self, gidx: "GroupIndex", coord_field,
                 member_field: Callable[[object], Optional[object]]):
        self._gidx = gidx
        self._f = coord_field
        self._member_field = member_field
        self.name = coord_field.name
        self._views: Dict[str, GroupView] = {}

    @property
    def options(self):
        return self._f.options

    @property
    def row_attr_store(self):
        return self._f.row_attr_store

    @property
    def views(self):
        # metadata-only surface (time-view discovery); time ranges are
        # gated out of the mesh path, so the coordinator's list suffices
        return self._f.views

    def bsi_view_name(self) -> str:
        return self._f.bsi_view_name()

    def base_value(self, *a, **kw):
        return self._f.base_value(*a, **kw)

    def base_value_between(self, *a, **kw):
        return self._f.base_value_between(*a, **kw)

    def view(self, name: str) -> Optional[GroupView]:
        gv = self._views.get(name)
        if gv is None:
            # a view absent EVERYWHERE lowers to PZero via the adapter's
            # empty fragment map, matching the serial path's None view;
            # constructing it lazily is still cheap (no fragment access)
            gv = self._views[name] = GroupView(
                self._gidx, name, self._member_field
            )
        return gv

    def close(self) -> None:
        for gv in self._views.values():
            gv.close()


class GroupIndex:
    """Index adapter handed to the unchanged single-node lowering: schema
    from the coordinator's index, shard data resolved across the group's
    registered holders by the fan-out's shard -> node assignment."""

    def __init__(self, coord_index, members: Dict[str, object],
                 assignment: Dict[int, str]):
        self.name = coord_index.name
        self._idx = coord_index
        self.members = members
        self.assignment = assignment
        self._fields: Dict[str, GroupField] = {}

    @property
    def keys(self):
        return self._idx.keys

    @property
    def track_existence(self):
        return self._idx.track_existence

    def field(self, name: str) -> Optional[GroupField]:
        gf = self._fields.get(name)
        if gf is None:
            f = self._idx.field(name)
            if f is None:
                return None
            gf = self._fields[name] = GroupField(
                self, f, lambda idx, n=name: idx.field(n)
            )
        return gf

    def existence_field(self) -> Optional[GroupField]:
        ef = self._idx.existence_field()
        if ef is None:
            return None
        gf = self._fields.get(ef.name)
        if gf is None:
            gf = self._fields[ef.name] = GroupField(
                self, ef, lambda idx: idx.existence_field()
            )
        return gf

    def available_shards(self) -> List[int]:
        return sorted(self.assignment)

    def close(self) -> None:
        for gf in self._fields.values():
            gf.close()


# ---------------------------------------------------------------------------
# GroupIndex cache: device-cache reuse across queries requires stable owner
# tokens, so adapters persist per (coordinator index, assignment,
# membership generation). Bounded LRU; evicted adapters invalidate their
# tokens' device entries (version-keyed — never stale — but dead weight).
# ---------------------------------------------------------------------------

_CACHE_MAX = 8
_cache_mu = TrackedLock("meshgroup.cache_mu")
_cache: "OrderedDict[tuple, GroupIndex]" = OrderedDict()


def group_index(coord_index, members: Dict[str, object],
                assignment_by_node: Dict[str, List[int]]) -> GroupIndex:
    """Get-or-build the adapter for one (index, shard assignment,
    membership) combination. The registry generation in the key makes a
    restarted member's stale holder unreachable through a cached adapter."""
    assignment: Dict[int, str] = {}
    for nid, shards in assignment_by_node.items():
        for s in shards:
            assignment[s] = nid
    key = (
        coord_index.name,
        id(coord_index),
        tuple(sorted((nid, tuple(sorted(sh)))
                     for nid, sh in assignment_by_node.items())),
        pmesh.group_generation(),
    )
    with _cache_mu:
        gi = _cache.get(key)
        if gi is not None:
            _cache.move_to_end(key)
            return gi
    gi = GroupIndex(coord_index, dict(members), assignment)
    evicted = []
    with _cache_mu:
        cur = _cache.get(key)
        if cur is not None:
            gi = cur
        else:
            _cache[key] = gi
            while len(_cache) > _CACHE_MAX:
                evicted.append(_cache.popitem(last=False)[1])
    for old in evicted:
        old.close()
    return gi


def drop_index(index_name: str) -> None:
    """GC hook (NodeServer.drop_index_telemetry): a deleted index's group
    adapters — and their device-cache entries — must not outlive it."""
    dead = []
    with _cache_mu:
        for key in [k for k in _cache if k[0] == index_name]:
            dead.append(_cache.pop(key))
    for gi in dead:
        gi.close()


def clear_cache() -> None:
    with _cache_mu:
        dead = list(_cache.values())
        _cache.clear()
    for gi in dead:
        gi.close()


# ---------------------------------------------------------------------------
# mesh-group dispatch helpers (called by exec/distributed.py)
# ---------------------------------------------------------------------------


def mesh_count(ex, gidx: GroupIndex, c: Call, shard_list: List[int]) -> Tuple[int, int]:
    """Count(<bitmap tree>) over the group as ONE compiled program ending
    in the in-program reduction (plan "total" mode): one dispatch + one
    scalar-sized blocking read however many shards the group holds.
    Returns (total, collective_bytes). Raises MeshUnsupported when the
    child has no stacked form or the operands exceed the device budget
    (per-node legs chunk within their own budgets instead)."""
    from pilosa_tpu.exec.plan import BudgetExceeded, StackedPlan

    if len(c.children) != 1:
        from pilosa_tpu.exec.executor import ExecError

        raise ExecError("Count() only accepts a single bitmap input")
    child = c.children[0]
    if child.name in ("Row", "Range") and child.has_conditions():
        # single-BSI-condition counts ride the plane-streamed ladders
        # over the group adapter (exec/bsistream.py): the in-program
        # halfword-pair reductions partition into the mesh psum, so the
        # group answers in one dispatch per slab with a scalar read
        from pilosa_tpu.exec import bsistream

        streamed = bsistream.count_range(ex, gidx, child, shard_list)
        if streamed is not None:
            return streamed, 4 * 4  # two halfword pairs replicated
    try:
        lowered = ex._lower_roots(gidx, [child], shard_list, empty_ok=True)
    except BudgetExceeded as e:
        raise MeshUnsupported(str(e), reason="budget") from e
    if lowered is None:
        raise MeshUnsupported("no stacked form", reason="no_stacked_form")
    if lowered == ex._EMPTY_LOWER:
        return 0, 0
    roots, low, n_out, out_shards = lowered
    sp = StackedPlan(
        roots[0], low.operands, low.scalars, n_out, out_shards,
        extents=low.extents,
    )
    # collective payload: the [S]-per-shard partial counts folded across
    # devices plus the replicated (lo, hi) result — shard-count-bound,
    # NOT operand-bound (operands never leave their chips)
    return sp.total(), (n_out + 2) * 4


def mesh_count_batch(ex, gidx: GroupIndex, calls: List[Call],
                     shard_list: List[int]) -> Tuple[List[int], int]:
    """N Counts over the group as ONE multi-root compiled program with
    in-program totals (the batcher's mesh lowering class rides this).
    Returns (totals, collective_bytes); MeshUnsupported falls back to
    per-call fan-out."""
    from pilosa_tpu.exec.executor import ExecError
    from pilosa_tpu.exec.plan import BudgetExceeded, MultiCountPlan

    children = []
    for c in calls:
        if len(c.children) != 1:
            raise ExecError("Count() only accepts a single bitmap input")
        children.append(c.children[0])
    try:
        lowered = ex._lower_roots(gidx, children, shard_list, empty_ok=True)
    except BudgetExceeded as e:
        raise MeshUnsupported(str(e), reason="budget") from e
    if lowered is None:
        raise MeshUnsupported("no stacked form", reason="no_stacked_form")
    if lowered == ex._EMPTY_LOWER:
        return [0] * len(calls), 0
    roots, low, n_out, out_shards = lowered
    mp = MultiCountPlan(
        roots, low.operands, low.scalars, n_out, out_shards,
        extents=low.extents,
    )
    return mp.totals(), (n_out + 2) * 4 * len(calls)
