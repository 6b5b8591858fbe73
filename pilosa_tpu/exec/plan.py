"""Compiled stacked query plans: a whole PQL bitmap tree as ONE jitted call.

This is the mesh-parallel replacement for the reference's per-shard
mapReduce (/root/reference/executor.go:2460-2613): instead of mapping a
shard loop over a worker pool and reducing host-side, the executor lowers a
bitmap call tree to a *plan* — a small static expression tree over stacked
operands `uint32[S, W]` (one row across all S shards) — and evaluates it in
one jitted dispatch. Under an active device mesh (parallel/mesh.py) the
operand stacks carry a NamedSharding over the "shards"/"cols" axes, so
XLA's SPMD partitioner splits the same compiled program across devices and
inserts the ICI collectives that replace the reference's HTTP fan-out.

Plan nodes are frozen (hashable) dataclasses: the plan itself is a static
jit argument, so structurally identical queries share one compiled
executable regardless of which rows/fields they touch (operands are traced
arguments; BSI predicates are traced scalars — changing a threshold never
recompiles).

Count convention: the "count" output mode returns per-shard uint32 counts
[S] (a single row within a shard can never exceed uint32); the executor
sums them in exact Python ints — one device->host read per query.
"""

from __future__ import annotations

import threading
import time as _time
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pilosa_tpu.utils import tracing
from pilosa_tpu.utils.locks import TrackedLock
from pilosa_tpu.utils.stats import PROCESS
from pilosa_tpu.ops import bsi as obsi
from pilosa_tpu.ops.bitmap import shift_bits

# Dispatch accounting: evals counts jitted plan executions; host_reads
# counts blocking device->host result reads (the "one dispatch + one
# blocking host read" contracts are asserted against these in tests — the
# mesh-group path's acceptance depends on both staying at exactly 1 per
# query regardless of group shard count).
STATS = {"evals": 0, "host_reads": 0}

# One in-flight compiled mesh dispatch at a time. Concurrent entry into a
# multi-device program from several HTTP handler threads can DEADLOCK the
# XLA CPU client when virtual devices outnumber physical cores (each
# program parks in its collective rendezvous waiting for device threads
# another program holds — observed as cluster tests hanging inside
# pjit __call__ on 2-core CI hosts). A single program occupying the whole
# mesh is the execution model anyway; the lock makes it explicit. It is
# held through the device->host read so no async execution escapes it.
_DISPATCH_MU = TrackedLock("plan.dispatch_mu")


# Compile accounting, from jax.monitoring (names as jax 0.9.0 records
# them: jax/_src/dispatch.py BACKEND_COMPILE_EVENT, which times every
# compile request that reaches the backend, persistent-cache hits
# included, and jax/_src/compiler.py, which records the hit). The counts
# live in the process registry (utils/stats.py PROCESS) and reach
# /debug/vars as exec.compiles / exec.compile_ms / exec.compile_cache_hits.
# jit compiles on the calling thread, so a per-thread count tells a
# dispatch whether it compiled (the span's dispatch.compiled tag).
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_compile_tls = threading.local()


def _on_event_duration(event: str, duration_secs: float, **_kw) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        _compile_tls.n = getattr(_compile_tls, "n", 0) + 1
        PROCESS.count("exec.compiles", 1, ())
        PROCESS.count("exec.compile_ms", duration_secs * 1000.0, ())


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        PROCESS.count("exec.compile_cache_hits", 1, ())


jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
jax.monitoring.register_event_listener(_on_event)


def _thread_compiles() -> int:
    return getattr(_compile_tls, "n", 0)


def reset_stats() -> None:
    STATS["evals"] = 0
    STATS["host_reads"] = 0


def _note_host_read() -> None:
    """Book one blocking device->host result read. Counted at the read
    site, not the dispatch site: a dispatch whose eval raised never
    reached its read."""
    STATS["host_reads"] += 1


def dispatch_mutex() -> TrackedLock:
    """The one-compiled-program-at-a-time mutex. Non-plan compiled
    dispatches (the cross-fragment deferred-delta merge, ops/merge.py)
    ride the same lock so the execution model stays one program on the
    device at a time; single-device callers release it BEFORE their
    blocking host read (no collective rendezvous to deadlock)."""
    return _DISPATCH_MU


def run_counted(fn, read: bool = True, family: str = "", program: str = "",
                arrays=None):
    """run_serialized plus dispatch accounting and the exec.dispatch
    attribution probe: STATS["evals"] books the compiled dispatch and —
    when `read` — STATS["host_reads"] books the blocking result read the
    caller is about to take. The plane-streamed BSI aggregates ride this
    so their "one dispatch per budget chunk / one scalar read" contracts
    are counter-asserted exactly like StackedPlan's. `family` and
    `program` name what `fn` runs, for the span's plan.* tags. `fn`
    closes over its operands: the span's mesh.devices is read from
    `arrays` (the operands, from a caller whose `fn` reads its results
    to the host itself) or else from what `fn` returns (an SPMD
    program's results lie on its devices)."""
    t_lock = _pre_dispatch()
    with _DISPATCH_MU:
        probe = _DispatchProbe(t_lock, family, program, arrays=arrays)
        try:
            import jax

            out = jax.block_until_ready(fn())
            probe.evaled()
            if arrays is None:
                probe.placed(out)
            if read:
                _note_host_read()
            return out
        finally:
            probe.finish()


def run_serialized(fn):
    """Run one non-plan compiled dispatch under the one-program-at-a-time
    mutex, holding it through completion, and return fn()'s result fully
    materialized. The executor's tally/aggregate dispatches (TopN
    intersection counts, BSI fused aggregates, the GroupBy cross-tally)
    consume mesh-sharded operand stacks, so their compiled programs carry
    collectives exactly like plan dispatches — concurrent entry from
    fan-out legs of several in-process nodes can park the XLA-CPU
    collective rendezvous when virtual devices outnumber cores (the PR-1
    deadlock, observed again on the 16-virtual-device mesh-group
    certification). Dispatch AND the blocking wait stay under the lock:
    releasing before completion would let a second program interleave
    into the same rendezvous. Callers stage operands BEFORE entering
    (staging is transfers, which don't rendezvous — it may overlap)."""
    import jax

    with _DISPATCH_MU:
        return jax.block_until_ready(fn())


class Unsupported(Exception):
    """Raised during lowering when a call shape has no stacked form; the
    executor falls back to the per-shard path."""


class BudgetExceeded(Unsupported):
    """The stacks for this shard list would exceed the device budget.
    Recoverable: the executor splits the shard axis and evaluates chunked
    plans (a handful of dispatches) instead of falling back to the
    dispatch-per-shard loop."""


class SparseView(Unsupported):
    """A view is materialized in too few of the requested shards for a
    dense stack to be economical. Unlike other Unsupported shapes, the
    executor recovers by re-lowering over a compacted shard list (only
    present shards + Shift relay successors) instead of falling back to
    the per-shard loop — sparse shards stay free, as in the reference
    (/root/reference/field.go:263-296 available-shards)."""


# ---------------------------------------------------------------------------
# Plan nodes
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PNode:
    pass


@dataclass(frozen=True)
class PLeaf(PNode):
    """Operand reference: operands[slot] is a uint32[S, W] row stack."""

    slot: int


@dataclass(frozen=True)
class PNary(PNode):
    """n-ary set algebra; op in {and, or, xor, andnot}. andnot folds left:
    c0 &~ c1 &~ c2 ... (reference: roaring difference, roaring.go:4119)."""

    op: str
    children: Tuple[PNode, ...]


@dataclass(frozen=True)
class PShift(PNode):
    """Shift bits up by n within each shard, carrying overflow into the
    *following* shard. prev_idx[i] is the stack index holding shard_id-1
    for stack position i, or -1 when that shard is absent from the stack
    (then no carry arrives). Matches the executor's per-shard carry
    composition (reference: roaring.go:4579 shift; row.go Shift)."""

    child: PNode
    n: int
    prev_idx: Tuple[int, ...]


@dataclass(frozen=True)
class PRangeEQ(PNode):
    """BSI magnitude == scalars[pred] within base (fragment.go:1288)."""

    base: PNode
    planes: int  # operand slot holding uint32[D, S, W]
    pred: int  # scalar slot


@dataclass(frozen=True)
class PRangeCmp(PNode):
    """BSI magnitude </>(=) scalars[pred] within filt (fragment.go:1358,
    1425). kind in {lt, gt}; allow_eq is static (distinct ladders)."""

    kind: str
    filt: PNode
    planes: int
    pred: int
    allow_eq: bool


@dataclass(frozen=True)
class PRangeBetween(PNode):
    """BSI scalars[lo] <= magnitude <= scalars[hi] within filt
    (fragment.go:1506)."""

    filt: PNode
    planes: int
    lo: int
    hi: int


@dataclass(frozen=True)
class PZero(PNode):
    """All-zero stack (absent rows); shape follows the query's stacks."""


# ---------------------------------------------------------------------------
# Evaluation (traced under jit; plan + out_mode are static)
# ---------------------------------------------------------------------------


def _eval_node(  # dispatch-ok: trace-time helper; inlines into _eval_jit's one program
    node: PNode, operands, scalars, shape, memo
) -> jax.Array:
    hit = memo.get(id(node))
    if hit is not None:
        return hit
    if isinstance(node, PLeaf):
        val = operands[node.slot]
    elif isinstance(node, PZero):
        val = jnp.zeros(shape, jnp.uint32)
    elif isinstance(node, PNary):
        vals = [_eval_node(c, operands, scalars, shape, memo) for c in node.children]
        val = vals[0]
        if node.op == "and":
            for v in vals[1:]:
                val = jnp.bitwise_and(val, v)
        elif node.op == "or":
            for v in vals[1:]:
                val = jnp.bitwise_or(val, v)
        elif node.op == "xor":
            for v in vals[1:]:
                val = jnp.bitwise_xor(val, v)
        elif node.op == "andnot":
            for v in vals[1:]:
                val = jnp.bitwise_and(val, jnp.bitwise_not(v))
        else:
            raise AssertionError(node.op)
    elif isinstance(node, PShift):
        child = _eval_node(node.child, operands, scalars, shape, memo)
        shifted, overflow = shift_bits(child, node.n)
        prev = np.asarray(node.prev_idx, np.int32)
        has_prev = prev >= 0
        if has_prev.any():
            take = np.where(has_prev, prev, 0)
            carried = jnp.where(
                jnp.asarray(has_prev)[: shifted.shape[0], None],
                overflow[jnp.asarray(take)],
                jnp.uint32(0),
            )
            shifted = jnp.bitwise_or(shifted, carried)
        val = shifted
    elif isinstance(node, PRangeEQ):
        base = _eval_node(node.base, operands, scalars, shape, memo)
        planes = operands[node.planes]
        val = obsi.range_eq_unsigned(
            base, planes, scalars[node.pred], planes.shape[0]
        )
    elif isinstance(node, PRangeCmp):
        filt = _eval_node(node.filt, operands, scalars, shape, memo)
        planes = operands[node.planes]
        fn = (
            obsi.range_lt_unsigned if node.kind == "lt" else obsi.range_gt_unsigned
        )
        val = fn(filt, planes, scalars[node.pred], planes.shape[0], node.allow_eq)
    elif isinstance(node, PRangeBetween):
        filt = _eval_node(node.filt, operands, scalars, shape, memo)
        planes = operands[node.planes]
        val = obsi.range_between_unsigned(
            filt, planes, scalars[node.lo], scalars[node.hi], planes.shape[0]
        )
    else:
        raise AssertionError(type(node))
    memo[id(node)] = val
    return val


# Shard-axis bound for the exact (lo, hi) uint32 split of "total" mode:
# per-shard counts are < 2^20 (one row within a shard), so the low-halfword
# sum stays under 2^32 while the shard axis is at most this wide. Wider
# stacks fall back to the [S] per-shard read.
_TOTAL_MAX_SHARDS = 65536


def _root_out(res, out_mode: str):
    """Finish one evaluated root for the requested output mode. "count"
    keeps the per-shard [S] vector (the executor sums host-side); "total"
    folds the shard axis IN PROGRAM — under a mesh NamedSharding the SPMD
    partitioner emits this reduction as the cross-device collective
    (psum), which is what lets a mesh-group dispatch return a scalar-sized
    result instead of a gathered [S] vector. The grand total is returned
    as an exact (lo, hi) uint32 halfword pair: uint64 accumulation needs
    x64 mode, and callers bound the shard axis by _TOTAL_MAX_SHARDS."""
    if out_mode == "row":
        return res
    counts = jnp.sum(jax.lax.population_count(res), axis=-1, dtype=jnp.uint32)
    if out_mode == "count":
        return counts
    lo = jnp.sum(jnp.bitwise_and(counts, jnp.uint32(0xFFFF)), dtype=jnp.uint32)
    hi = jnp.sum(jnp.right_shift(counts, 16), dtype=jnp.uint32)
    return jnp.stack([lo, hi])


@partial(jax.jit, static_argnums=(0, 1))
def _eval_multi_jit(roots: Tuple[PNode, ...], out_mode: str, operands: Tuple, scalars: Tuple):
    """Evaluate several plan roots in ONE compiled program: the shared memo
    means operands referenced by more than one root are read from HBM once
    per dispatch, and the per-dispatch fixed cost amortizes over all roots
    (measured ~2x per-query at 4 counts/dispatch on v5e — see bench notes).
    Returns stacked [n_roots, ...] results."""
    shape = None
    for op in operands:
        if op.ndim == 2:
            shape = op.shape
            break
    if shape is None:
        for op in operands:
            if op.ndim == 3:
                shape = op.shape[1:]
                break
    memo: dict = {}
    outs = []
    for r in roots:
        res = _eval_node(r, operands, scalars, shape, memo)
        outs.append(_root_out(res, out_mode))
    return jnp.stack(outs)


@partial(jax.jit, static_argnums=(0, 1))
def _eval_jit(plan: PNode, out_mode: str, operands: Tuple, scalars: Tuple):
    # operand stacks: row stacks are [S, W]; plane stacks are [D, S, W].
    shape = None
    for op in operands:
        if op.ndim == 2:
            shape = op.shape
            break
    if shape is None:
        for op in operands:
            if op.ndim == 3:
                shape = op.shape[1:]
                break
    res = _eval_node(plan, operands, scalars, shape, {})
    return _root_out(res, out_mode)


def _flush_stage_span() -> None:
    """Flush this thread's staging account (hbm/residency uploads, device
    cache build waits, prefetch credit) into an exec.stage span: at the
    end of the exec.lower span that staged (lower_span), and, for what
    staged outside one, just before the dispatch that consumes the
    operands. Always drains the accumulator — staging by an unsampled
    query must not leak into the next sampled one on the same thread."""
    acc = tracing.take_stage_account()
    if tracing.active_span() is None:
        return
    if acc.nbytes == 0 and acc.seconds < 1e-6 and acc.hits == 0:
        return
    tracing.record_span(
        "exec.stage",
        acc.seconds,
        tags={
            "stage.bytes": acc.nbytes,
            "stage.rows": acc.rows,
            "stage.build_ms": round(acc.build_seconds * 1000.0, 3),
            "stage.put_ms": round(acc.put_seconds * 1000.0, 3),
            "stage.prefetch_hits": acc.hits,
        },
    )


class lower_span:
    """`with lower_span(family):` — the exec.lower span around a call
    becoming device operands (lowering, residency lookups, and on a miss
    the uploads). The staging done inside is flushed into its exec.stage
    child on the way out, so the child's window lies in the parent's and
    the two self times add up."""

    __slots__ = ("_span",)

    def __init__(self, family: str):
        self._span = tracing.start_span("exec.lower")
        self._span.set_tag("plan.family", family)

    def __enter__(self):
        return self._span.__enter__()

    def __exit__(self, *exc) -> None:
        _flush_stage_span()
        self._span.__exit__(*exc)


def _pre_dispatch() -> float:
    """Shared dispatch preamble: count the eval, flush staging
    attribution, and start the lock-wait clock. Returns the timestamp to
    hand _DispatchProbe once the mutex is acquired."""
    STATS["evals"] += 1
    _flush_stage_span()
    return _time.perf_counter()


def _placement(arrays) -> Tuple[int, str]:
    """(devices spanned, mesh axes) of a compiled dispatch, read from the
    sharding of the first device array among `arrays` (a pytree of its
    operands, or of its results where the operands are out of reach):
    (1, "") on a single device, (4, "shards=2,cols=2") for a stack placed
    over the 2 x 2 mesh a four-chip host forms."""
    for a in jax.tree_util.tree_leaves(arrays):
        sharding = getattr(a, "sharding", None)
        if sharding is None:
            continue
        n = len(sharding.device_set)
        mesh = getattr(sharding, "mesh", None)
        if n > 1 and mesh is not None:
            return n, ",".join(f"{k}={v}" for k, v in mesh.shape.items())
        return n, ""
    return 1, ""


class _DispatchProbe:
    """Attribution for ONE compiled dispatch. Construct immediately
    after acquiring _DISPATCH_MU (with the pre-lock timestamp from
    _pre_dispatch), call evaled() between the jitted call and the host
    read, finish() in the dispatch `finally`. Tags: lock wait vs device
    eval vs blocking device->host read; eval/read are omitted when the
    eval raised before evaled(). `family` is the plan family (stacked /
    bsi / groupby), `program` the jitted program as the profiler's "XLA
    Modules" line names it, which joins this span to its device ops;
    dispatch.compiled says whether this dispatch had to compile;
    mesh.devices how many devices the program's `arrays` span (its
    operands; placed() takes them later where only the results are at
    hand) and, above one, mesh.axes the mesh they are sharded over. The
    span is entered and left by hand, as a `with` would, so that it is
    on the profiler's clock too (utils/tracing.py)."""

    __slots__ = ("_span", "_t_lock", "_t0", "_t1", "_compiles")

    def __init__(self, t_lock: float, family: str = "stacked",
                 program: str = "jit__eval_jit", arrays=None):
        self._span = sp = tracing.start_span("exec.dispatch")
        sp.__enter__()
        sp.set_tag("plan.family", family)
        sp.set_tag("plan.program", program)
        if arrays is not None:
            self.placed(arrays)
        self._compiles = _thread_compiles()
        self._t_lock = t_lock
        self._t0 = _time.perf_counter()
        self._t1: Optional[float] = None

    def tag(self, key: str, value) -> None:
        self._span.set_tag(key, value)

    def placed(self, arrays) -> None:
        n, axes = _placement(arrays)
        self._span.set_tag("mesh.devices", n)
        if n > 1:
            self._span.set_tag("mesh.axes", axes)

    def evaled(self) -> None:
        self._t1 = _time.perf_counter()

    def finish(self) -> None:
        end = _time.perf_counter()
        sp = self._span
        sp.set_tag(
            "dispatch.lock_wait_ms",
            round((self._t0 - self._t_lock) * 1000.0, 3),
        )
        if self._t1 is not None:
            sp.set_tag(
                "dispatch.eval_ms", round((self._t1 - self._t0) * 1000.0, 3)
            )
            sp.set_tag(
                "dispatch.read_ms", round((end - self._t1) * 1000.0, 3)
            )
        sp.set_tag("dispatch.compiled", _thread_compiles() > self._compiles)
        sp.__exit__(None, None, None)


class StackedPlan:
    """A lowered plan plus its operand stacks, ready to evaluate.

    `out_shards` maps output stack positions 0..n_shards-1 back to shard
    ids: under compacted lowering (SparseView recovery) the stack covers
    only present shards, so consumers must not assume position == the
    requested shard list.

    `extents` (hbm.ExtentTable, optional) holds the pins staging took on
    this plan's operand extents: they stay pinned — unevictable — from
    lowering THROUGH the compiled dispatch, and are released in the
    dispatch `finally` (under the same _DISPATCH_MU hold, so release
    ordering matches the one-program-at-a-time execution model). Release
    is idempotent; re-dispatching a released plan runs unpinned, which is
    safe — the assembled operand arrays hold their own device buffers."""

    __slots__ = ("root", "operands", "scalars", "n_shards", "out_shards", "extents")

    def __init__(
        self,
        root: PNode,
        operands: List,
        scalars: List[int],
        n_shards: int,
        out_shards: Optional[List[int]] = None,
        extents=None,
    ):
        self.root = root
        self.operands = operands
        self.scalars = scalars
        self.n_shards = n_shards
        self.out_shards = out_shards
        self.extents = extents

    def _scalar_args(self) -> Tuple:
        return tuple(jnp.uint32(s) for s in self.scalars)

    def release_extents(self) -> None:
        """Unpin this plan's operand extents (idempotent). Called by the
        dispatch methods' finally; executor error paths also call it so a
        lowered-but-never-dispatched plan cannot leak pins."""
        if self.extents is not None:
            self.extents.release()

    def count(self) -> int:
        """Total count: ONE jitted dispatch + one [S] host read, summed in
        exact Python ints (replaces the per-shard int() sync loop)."""
        t_lock = _pre_dispatch()
        with _DISPATCH_MU:
            probe = _DispatchProbe(t_lock, arrays=self.operands)
            try:
                counts = _eval_jit(
                    self.root, "count", tuple(self.operands), self._scalar_args()
                )
                probe.evaled()
                _note_host_read()
                host = np.asarray(counts[: self.n_shards], dtype=np.uint64)
            finally:
                probe.finish()
                self.release_extents()
        return int(host.sum())

    def total(self) -> int:
        """Grand-total count with the shard reduction folded IN PROGRAM:
        the compiled program ends in the collective (psum under a mesh
        NamedSharding), so the blocking host read is a single (lo, hi)
        halfword pair — one dispatch + one scalar-sized read regardless
        of the stack's shard count. This is the mesh-group dispatch shape
        (exec/meshgroup.py); stacks too wide for the exact halfword split
        fall back to the [S] read."""
        from pilosa_tpu.parallel.mesh import padded_shards

        if padded_shards(self.n_shards) > _TOTAL_MAX_SHARDS:
            return self.count()
        t_lock = _pre_dispatch()
        with _DISPATCH_MU:
            probe = _DispatchProbe(t_lock, arrays=self.operands)
            probe.tag("dispatch.mode", "total")
            try:
                out = _eval_jit(
                    self.root, "total", tuple(self.operands), self._scalar_args()
                )
                probe.evaled()
                _note_host_read()
                host = np.asarray(out, dtype=np.uint64)
            finally:
                probe.finish()
                self.release_extents()
        return int(host[0]) + (int(host[1]) << 16)

    def shard_counts(self) -> np.ndarray:
        t_lock = _pre_dispatch()
        with _DISPATCH_MU:
            probe = _DispatchProbe(t_lock, arrays=self.operands)
            try:
                counts = _eval_jit(
                    self.root, "count", tuple(self.operands), self._scalar_args()
                )
                probe.evaled()
                _note_host_read()
                return np.asarray(counts)[: self.n_shards]
            finally:
                probe.finish()
                self.release_extents()

    def rows(self) -> jax.Array:
        """Materialized [S, W] result stack (padded shards trimmed)."""
        t_lock = _pre_dispatch()
        with _DISPATCH_MU:
            probe = _DispatchProbe(t_lock, arrays=self.operands)
            try:
                out = _eval_jit(
                    self.root, "row", tuple(self.operands), self._scalar_args()
                )
                probe.evaled()
                _note_host_read()
                return out[: self.n_shards].block_until_ready()
            finally:
                probe.finish()
                self.release_extents()

    def rows_full(self) -> jax.Array:
        """Materialized result stack INCLUDING mesh-padded shards (all-zero
        rows), for composing with other padded [S, W] stacks on device."""
        t_lock = _pre_dispatch()
        with _DISPATCH_MU:
            probe = _DispatchProbe(t_lock, arrays=self.operands)
            try:
                out = _eval_jit(
                    self.root, "row", tuple(self.operands), self._scalar_args()
                )
                probe.evaled()
                _note_host_read()
                return out.block_until_ready()
            finally:
                probe.finish()
                self.release_extents()


class MultiCountPlan:
    """Several lowered roots over one shared operand set: a whole
    multi-Count PQL query as ONE jitted dispatch + one [N, S] host read
    (the per-dispatch overhead and any shared operand reads amortize over
    the batch — the reference answers each call separately,
    executor.go:231 execute loop). Extent pins release after the dispatch,
    as in StackedPlan."""

    __slots__ = ("roots", "operands", "scalars", "n_shards", "out_shards", "extents")

    def __init__(self, roots, operands, scalars, n_shards, out_shards=None,
                 extents=None):
        self.roots = list(roots)
        self.operands = operands
        self.scalars = scalars
        self.n_shards = n_shards
        self.out_shards = out_shards
        self.extents = extents

    def release_extents(self) -> None:
        if self.extents is not None:
            self.extents.release()

    def counts(self) -> List[int]:
        t_lock = _pre_dispatch()
        with _DISPATCH_MU:
            probe = _DispatchProbe(
                t_lock, program="jit__eval_multi_jit", arrays=self.operands
            )
            probe.tag("dispatch.roots", len(self.roots))
            try:
                out = _eval_multi_jit(
                    tuple(self.roots),
                    "count",
                    tuple(self.operands),
                    tuple(jnp.uint32(s) for s in self.scalars),
                )
                probe.evaled()
                _note_host_read()
                h = np.asarray(out, dtype=np.uint64)[:, : self.n_shards]
            finally:
                probe.finish()
                self.release_extents()
        return [int(x) for x in h.sum(axis=1)]

    def totals(self) -> List[int]:
        """All roots' grand totals with the shard reduction in program
        (see StackedPlan.total): ONE dispatch + one [N, 2] halfword-pair
        read however many roots and shards the batch spans — the
        mesh-group shape of the multi-Count batch."""
        from pilosa_tpu.parallel.mesh import padded_shards

        if padded_shards(self.n_shards) > _TOTAL_MAX_SHARDS:
            return self.counts()
        t_lock = _pre_dispatch()
        with _DISPATCH_MU:
            probe = _DispatchProbe(
                t_lock, program="jit__eval_multi_jit", arrays=self.operands
            )
            probe.tag("dispatch.roots", len(self.roots))
            probe.tag("dispatch.mode", "total")
            try:
                out = _eval_multi_jit(
                    tuple(self.roots),
                    "total",
                    tuple(self.operands),
                    tuple(jnp.uint32(s) for s in self.scalars),
                )
                probe.evaled()
                _note_host_read()
                h = np.asarray(out, dtype=np.uint64)
            finally:
                probe.finish()
                self.release_extents()
        return [int(lo) + (int(hi) << 16) for lo, hi in h]
