"""Per-query cost estimation for admission control.

Admission must be weighted by real HBM pressure, not query count: a
`Count(Row(f=1))` touches one `uint32[S, W]` row stack while a BSI
`Row(v > 7)` drags `bit_depth + 2` plane stacks onto the device. The
estimator walks the parsed PQL call tree — the same structure
exec/executor.py lowers to a plan — and prices it with exactly the
accounting `_stack_guard` uses for `BudgetExceeded`: one row stack is
`n_shards * WORDS_PER_ROW * 4` bytes, and no single dispatch may hold
more than a quarter of the devcache budget (larger queries are chunked
by the executor, so the *peak* per-dispatch residency is capped at that
quarter while the *sweep count* grows instead).

The estimate is intentionally cheap (no lowering, no fragment access)
and intentionally conservative-but-bounded: admission weighting, not
billing. Estimation must never fail a query — any error degrades to
ZERO_COST and the query is admitted on the concurrency cap alone.

Residency discount: bytes already resident on device don't need to be
staged again, so the in-flight byte account reads TRUE residency — the
estimate subtracts what the HBM extent store (core/devcache.py via
pilosa_tpu/hbm/) currently holds for the views of the fields THIS query
references (summed by the views' owner tokens, so there is no
cross-index or cross-field aliasing). A warm repeat query therefore
admits nearly byte-free instead of double-charging HBM the budget
already accounts for.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Set

from pilosa_tpu.pql import Call, Query

# Row-stack equivalents charged for rank/tally calls (TopN, GroupBy):
# they tile over the field's rows in bounded chunks rather than stacking
# everything at once (executor tally bundles), so a flat charge models
# the working set without reading fragment row counts at admission time.
_TALLY_ROW_EQUIV = 16

# Plane count assumed for a BSI reference whose field can't be resolved
# at admission time (index/field not created yet — the executor will
# reject it later; admission just needs a finite weight).
_DEFAULT_BSI_PLANES = 18

_WRITE_CALLS = frozenset(
    {"Set", "Clear", "Store", "ClearRow", "SetRowAttrs", "SetColumnAttrs"}
)

# ---------------------------------------------------------------------------
# Collective-cost link classes (mesh-group execution). A mesh dispatch's
# in-program reduction rides ICI; a cross-group HTTP leg ships its partial
# result over DCN and pays a per-leg round trip. Admission prices both so
# a mesh dispatch is weighed honestly against the legs it replaced:
# transport_ms shrinks a query's effective deadline in the feasibility
# check (sched/admission.py). Process-global knobs ([mesh] ici-gbps /
# dcn-gbps) — in-process nodes share one device mesh.
# ---------------------------------------------------------------------------

_ICI_GBPS = 100.0  # intra-group collective link
_DCN_GBPS = 3.0  # cross-group HTTP/DCN link
_DCN_LEG_MS = 0.5  # fixed per-leg round-trip floor (serialization + HTTP)


def configure_links(
    ici_gbps: Optional[float] = None, dcn_gbps: Optional[float] = None
) -> None:
    """Install the server's [mesh] link-class knobs (cli/config.py ->
    server/node.py). Values <= 0 keep the current setting."""
    global _ICI_GBPS, _DCN_GBPS
    if ici_gbps is not None and ici_gbps > 0:
        _ICI_GBPS = float(ici_gbps)
    if dcn_gbps is not None and dcn_gbps > 0:
        _DCN_GBPS = float(dcn_gbps)


def link_gbps(link: str) -> float:
    return _ICI_GBPS if link == "ici" else _DCN_GBPS


def collective_ms(nbytes: int, link: str = "ici") -> float:
    """Milliseconds to move `nbytes` over one link class (bytes x
    link-class term — the per-collective accounting unit)."""
    if nbytes <= 0:
        return 0.0
    return nbytes / (link_gbps(link) * 1e9) * 1e3


def transport_ms(
    mesh_collective_bytes: int, leg_bytes: int, legs: int
) -> float:
    """One query's estimated transport bill: the mesh dispatch's ICI
    collective plus every cross-group leg's DCN result shipping and
    round-trip floor. Legs run concurrently (the fan-out pool), so the
    per-leg floor is paid once, not per leg; the byte terms sum because
    they funnel into one coordinator NIC."""
    ms = collective_ms(mesh_collective_bytes, "ici")
    ms += collective_ms(leg_bytes, "dcn")
    if legs > 0:
        ms += _DCN_LEG_MS
    return ms


@dataclass(frozen=True)
class QueryCost:
    """What one query costs to run.

    device_bytes — estimated PEAK per-dispatch operand residency (bytes);
    sweeps — estimated jitted dispatches (chunking inflates this, never
    the peak); write — mutates data (writes skip stacked lowering, so
    they carry no device weight, but they still hold a concurrency slot);
    transport_ms — estimated collective + cross-group transport latency
    (mesh ICI reduction and DCN legs priced by link class), which the
    admission feasibility check subtracts from the query's deadline.
    """

    device_bytes: int = 0
    sweeps: int = 0
    write: bool = False
    transport_ms: float = 0.0


ZERO_COST = QueryCost()


def hydrate_cost(nbytes: int) -> QueryCost:
    """Admission cost of one tier hydration (pilosa_tpu/tier/): the
    object fetch is a DCN-class transfer of the snapshot object, not a
    device staging — no device bytes, one 'sweep' to weigh it in the
    batch lane, and the transport bill priced like a cross-group leg so
    deadline feasibility accounts for the fetch latency."""
    return QueryCost(
        device_bytes=0,
        sweeps=1,
        transport_ms=collective_ms(max(0, int(nbytes)), "dcn"),
    )


def _bsi_planes(idx: Any, field_name: Optional[str]) -> int:
    """Row-stack equivalents a BSI reference to `field_name` holds at
    PEAK: the plane-streamed lowering (exec/bsistream.py) stages and
    reduces planes in `bsi-slab-planes`-bounded slabs with carried word
    state, so peak residency is min(bit_depth, slab) planes + the
    exists/sign/state rows — NOT the whole bit_depth+2 stack the old
    estimator charged. Pricing the full stack over-charged admission
    for warm deep-field repeats by up to ~2x (sweep count still grows
    with depth via the slab dispatches)."""
    from pilosa_tpu.exec import bsistream

    slab = bsistream.slab_planes()
    if idx is not None and field_name:
        f = idx.field(field_name)
        o = getattr(f, "options", None)
        depth = getattr(o, "bit_depth", 0) if f else 0
        if depth:
            signed_ = getattr(o, "min", 0) < getattr(o, "base", 0)
            if signed_ and depth > 31:
                # the streamed path declines this shape (its virtual
                # key needs depth+sign bits in uint32) and the kept
                # legacy lowering stages the WHOLE stack — price that,
                # not the slab peak
                return depth + 2
            return min(depth, slab) + 3
    return min(_DEFAULT_BSI_PLANES, slab + 3)


def _call_rows(idx: Any, c: Call) -> float:
    """Row-stack equivalents the call's operand set occupies."""
    if c.name in _WRITE_CALLS:
        return 0.0
    rows = 0.0
    if c.name == "Row":
        conds = c.condition_args()
        if conds:
            for fname in conds:
                rows += _bsi_planes(idx, fname)
        else:
            rows += 1.0
    elif c.name in ("Sum", "Min", "Max"):
        fname = c.args.get("field") or c.args.get("_field")
        fname = fname if isinstance(fname, str) else None
        rows += _bsi_planes(idx, fname)
    elif c.name in ("TopN", "GroupBy", "Rows"):
        rows += _TALLY_ROW_EQUIV
        agg = c.args.get("aggregate")
        if c.name == "GroupBy" and isinstance(agg, Call):
            # aggregate=Sum(field=): the value field's exists, sign and
            # magnitude planes are staged as ONE stack beside the
            # dimensions (exec/executor.py _group_by_stacked), not in slabs
            fname = agg.args.get("field")
            f = idx.field(fname) if idx is not None and isinstance(fname, str) else None
            depth = getattr(getattr(f, "options", None), "bit_depth", 0)
            rows += (depth or _DEFAULT_BSI_PLANES) + 2
    elif c.name == "Not":
        rows += 1.0  # the existence stack
    for child in c.children:
        rows += _call_rows(idx, child)
    for k, v in c.args.items():
        if isinstance(v, Call) and (c.name, k) != ("GroupBy", "aggregate"):
            rows += _call_rows(idx, v)
    return rows


def _referenced_fields(c: Call, out: Set[str]) -> None:
    """Field names a call tree touches (same extraction rules as the
    executor's _field_arg_name / condition args), for scoping the
    residency discount to views this query can actually reuse."""
    for k in c.args:
        if not k.startswith("_") and k not in ("from", "to"):
            out.add(k)
    fname = c.args.get("field") or c.args.get("_field")
    if isinstance(fname, str):
        out.add(fname)
    for child in c.children:
        _referenced_fields(child, out)
    for v in c.args.values():
        if isinstance(v, Call):
            _referenced_fields(v, out)


def resident_bytes(idx: Any, field_names: Optional[Set[str]] = None) -> int:
    """Device bytes currently cached for `idx`'s views (row stacks, BSI
    plane extents, per-row arrays), summed by owner token — restricted
    to `field_names` when given, so a query is only discounted for views
    IT touches (field A's warm gigabytes must not zero out field B's
    cold admission weight). Metadata walk only — no fragment or device
    access."""
    from pilosa_tpu.core.devcache import DEVICE_CACHE

    total = 0
    try:
        fields = getattr(idx, "_fields", None) or {}
        for name, f in fields.items():
            if field_names is not None and name not in field_names:
                continue
            for v in getattr(f, "views", {}).values():
                token = getattr(v, "_stack_token", None)
                if token is not None:
                    total += DEVICE_CACHE.owner_resident_bytes(token)
    except Exception:  # noqa: BLE001 - estimation must never fail
        return 0
    return total


def staged_merge_bytes(idx: Any, field_names: Optional[Set[str]] = None) -> int:
    """Bytes of staged-but-unmaterialized ingest delta the next read
    barrier of this query's fields may have to merge (8-byte position
    keys, the merge working set — core/merge.py): raw pending buffers
    plus barrier-merged layers still parked for a host read. A query
    arriving mid-burst pays that bill before its first dispatch (a
    warm query over patched extents skips it, so this is the
    conservative side). Metadata walk only: plain int reads per
    fragment, no locks taken."""
    total = 0
    try:
        fields = getattr(idx, "_fields", None) or {}
        for name, f in fields.items():
            if field_names is not None and name not in field_names:
                continue
            for v in getattr(f, "views", {}).values():
                for frag in getattr(v, "fragments", {}).values():
                    total += (
                        int(getattr(frag, "_pending_n", 0))
                        + int(getattr(frag, "_premerged_n", 0))
                    ) * 8
    except Exception:  # noqa: BLE001 - estimation must never fail
        return 0
    return total


def _probe_text(idx: Any, c: Call) -> Optional[str]:
    """Canonical POST-translation text for the result-cache probe:
    admission runs before the executor translates row keys to ids, but
    cache entries are keyed on translated text, so a probe with raw key
    strings would never match on a keyed field. Resolution here is
    READ-ONLY (`find_key` — never creating ids the way execution's
    translation may); an unresolvable key means no entry can exist, so
    None (no discount)."""
    s = str(c)
    if '"' not in s:
        return s  # no string args anywhere: already canonical
    import copy as _copy

    cc = _copy.deepcopy(c)
    if not _probe_translate(idx, cc):
        return None
    return str(cc)


def _probe_translate(idx: Any, c: Call) -> bool:
    """Replace string row-key args with their ids in place, keyed-field
    rows only (the shapes the cache deems eligible carry no other
    translatable strings); False when any key cannot resolve."""
    for k, v in list(c.args.items()):
        if isinstance(v, Call):
            if not _probe_translate(idx, v):
                return False
        elif (
            isinstance(v, str)
            and not k.startswith("_")
            and k not in ("from", "to")
        ):
            f = idx.field(k) if idx is not None else None
            if f is None or not getattr(f.options, "keys", False):
                return False
            rid = f.translate_store.find_key(v)
            if rid is None:
                return False
            c.args[k] = rid
    for child in c.children:
        if not _probe_translate(idx, child):
            return False
    return True


def _shard_count(idx: Any, shards: Optional[Sequence[int]]) -> int:
    if shards is not None:
        return max(1, len(shards))
    if idx is not None:
        try:
            return max(1, len(idx.available_shards()))
        except Exception:  # noqa: BLE001 - estimation must never fail
            return 1
    return 1


_ROW_RESULT_CALLS = frozenset(
    {"Row", "Union", "Intersect", "Difference", "Xor", "Not", "Shift",
     "Range", "All"}
)


def _transport_estimate(calls: Sequence[Call], transport: Dict[str, Any]) -> float:
    """Price a query's transport from the executor's fan-out split
    (exec/distributed.py transport_profile): mesh-local shards fold into
    an ICI collective, cross-group legs ship partials over DCN. A
    row-returning root gathers its [S, W] result stack; everything else
    (counts, tallies, aggregates) reads shard-count-bound vectors."""
    from pilosa_tpu.shardwidth import WORDS_PER_ROW

    mesh_shards = int(transport.get("mesh_shards", 0))
    legs = int(transport.get("legs", 0))
    leg_shards = int(transport.get("leg_shards", 0))
    if mesh_shards <= 0 and legs <= 0:
        return 0.0
    total = 0.0
    read_calls = 0
    for c in calls:
        if c.name in _WRITE_CALLS:
            continue
        read_calls += 1
        per_shard = WORDS_PER_ROW * 4 if c.name in _ROW_RESULT_CALLS else 8
        # byte terms per call (each call's results ship); the fixed
        # round-trip floor is added ONCE below — legs run concurrently
        # and adjacent calls share dispatches, so charging it per call
        # would shed batched queries whose wall time pays it once
        total += transport_ms(mesh_shards * per_shard, leg_shards * per_shard, 0)
    if legs > 0 and read_calls > 0:
        total += transport_ms(0, 0, legs)  # the round-trip floor, once
    return total


def estimate(
    idx: Any,
    query: Any,
    shards: Optional[Sequence[int]] = None,
    shard_count: Optional[int] = None,
    transport: Optional[Dict[str, Any]] = None,
) -> QueryCost:
    """Estimate `query` (a parsed Query/Call, or raw PQL text) against
    index object `idx` (may be None — e.g. not created yet).
    `shard_count` overrides the shard-axis size — the api layer passes
    this node's expected LOCAL share in a multi-node cluster, since a
    coordinator's own device only materializes the shards it owns (the
    rest are charged by the peers admitting the fan-out legs).
    `transport` (exec/distributed.py transport_profile) adds the
    mesh-collective / cross-group-leg latency terms."""
    from pilosa_tpu.core.devcache import DEVICE_CACHE
    from pilosa_tpu.shardwidth import WORDS_PER_ROW

    try:
        if isinstance(query, str):
            from pilosa_tpu.pql import parse

            query = parse(query)
        calls = query.calls if isinstance(query, Query) else [query]
        n_shards = (
            max(1, shard_count)
            if shard_count is not None
            else _shard_count(idx, shards)
        )
        stack_bytes = n_shards * WORDS_PER_ROW * 4
        # the executor's _stack_guard chunks any dispatch whose stacks
        # would exceed a quarter of the devcache budget
        dispatch_cap = max(1, DEVICE_CACHE.budget_bytes // 4)
        peak = 0
        sweeps = 0
        write = False
        for c in calls:
            if c.name in _WRITE_CALLS:
                write = True
                continue
            raw = int(_call_rows(idx, c) * stack_bytes)
            if raw <= 0:
                continue
            peak = max(peak, min(raw, dispatch_cap))
            sweeps += max(1, math.ceil(raw / dispatch_cap))
        if peak and idx is not None:
            # result-cache discount FIRST: when every read call has a
            # LIVE cached entry (key presence — the version check would
            # cost what it saves), the query is cache-hit-likely and
            # will serve from host memory with zero dispatches —
            # charging it full device bytes would queue microsecond
            # answers behind byte-budget waits, and the per-fragment
            # residency/staged walks below would cost more than the
            # whole cached answer
            from pilosa_tpu.core.resultcache import RESULT_CACHE

            scope = getattr(idx, "_cache_scope", None)
            read_calls = [c for c in calls if c.name not in _WRITE_CALLS]
            if scope is not None and read_calls:
                texts = [_probe_text(idx, c) for c in read_calls]
                if all(
                    t is not None and RESULT_CACHE.has_text(scope, t)
                    for t in texts
                ):
                    peak = 0
                elif all(
                    t is not None
                    and (
                        RESULT_CACHE.has_text(scope, t)
                        or RESULT_CACHE.repair_likely(scope, t)
                    )
                    for t in texts
                ):
                    # middle tier: every read call is either hit-likely
                    # or maybe-stale-but-repairable (monotone-tree patch
                    # / re-key from merge word deltas) — the repeat
                    # costs host microseconds, so charge one row-stack
                    # as a floor instead of the full device walk; the
                    # floor keeps a recompute from riding byte-free if
                    # the repair window closes unluckily
                    peak = min(peak, stack_bytes)
        if peak and idx is not None:
            # cached-resident discount: operands already in HBM stage for
            # free, so don't charge the byte account for them twice —
            # scoped to the fields THIS query references
            touched: Set[str] = set()
            for c in calls:
                _referenced_fields(c, touched)
            if touched:
                peak = max(0, peak - resident_bytes(idx, touched))
                # staged-delta surcharge: this query's read barrier will
                # merge the fields' pending ingest delta (device keys at
                # 8 bytes/position) before it can dispatch
                peak += staged_merge_bytes(idx, touched)
        t_ms = _transport_estimate(calls, transport) if transport else 0.0
        return QueryCost(
            device_bytes=peak, sweeps=sweeps, write=write, transport_ms=t_ms
        )
    except Exception:  # noqa: BLE001 - never fail admission on estimation
        return ZERO_COST
