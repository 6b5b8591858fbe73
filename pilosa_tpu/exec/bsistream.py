"""Plane-streamed BSI aggregate execution (the BSI roofline rework).

The pre-existing lowering (`executor._stacked_bsi`) materialized the full
`[bit_depth, S, W]` plane stack before dispatching, so `_chunk_by_budget`
halved the SHARD axis until a depth-wide operand fit the quarter-budget —
a deep int field paid many sequential staged dispatches where Count pays
one — and `sum_counts_stacked`/`min_max_signed` read `[1 + 2D, S]`
partials back for a Python host combine, with kernels that swept the
word rows once per plane where a Count sweeps them once.

This module rebuilds the lowering as plane-streamed:

- planes stage and reduce in bounded SLABS of at most `bsi-slab-planes`
  planes (the `[bsi]` knob): each slab is one compiled dispatch whose
  word-local kernels (ops/bsi.py) read every plane word exactly once,
  carrying ladder state between slabs with donated buffers so peak
  plane residency is slab-sized — the shard axis is only chunked when a
  single slab over every shard exceeds the quarter-budget;
- Sum/Min/Max and the single-condition Range/Between counts finish IN
  PROGRAM to scalar-sized halfword-pair results (the plan.py "total"
  contract): under a mesh NamedSharding the final reduction partitions
  into the cross-device collective (psum), so a mesh-group BSI
  aggregate stays exactly 1 dispatch + 1 scalar host read per group;
- dispatches ride `plan.run_counted` so the one-dispatch-per-budget-
  chunk contract is counter-asserted exactly like StackedPlan's.

Fields whose value range cannot store negatives (`options.min >=
options.base` — the bsi_base construction guarantees stored magnitudes
are then non-negative) compile UNSIGNED kernel variants that skip the
sign row entirely: no sign staging, no second popcount branch.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from pilosa_tpu.utils.locks import TrackedLock

_DEFAULT_SLAB_PLANES = 16


def _env_slab_planes() -> int:
    raw = os.environ.get("PILOSA_TPU_BSI_SLAB_PLANES")
    try:
        v = int(raw) if raw else _DEFAULT_SLAB_PLANES
    except ValueError:
        return _DEFAULT_SLAB_PLANES
    # same contract as configure(): <= 0 restores the default (a
    # negative slab would make every plane range empty and the
    # aggregates silently zero)
    return v if v > 0 else _DEFAULT_SLAB_PLANES


_slab_planes = _env_slab_planes()

_stats_mu = TrackedLock("bsistream.stats_mu")
_counters: Dict[str, int] = {
    # plane slabs staged+consumed by streamed aggregates (a depth-8
    # field at the default knob is exactly 1 slab per query chunk)
    "slabs": 0,
    # cumulative bytes of plane-slab operands consumed (resident or
    # freshly staged; hbm.restage_bytes books actual uploads)
    "slab_bytes": 0,
    # compiled dispatches issued by the plane-streamed path (slab steps
    # + finishers + degenerate mask counts)
    "plane_dispatches": 0,
}


def configure(slab_planes: Optional[int] = None) -> None:
    """Install the server's [bsi] knobs (cli/config.py -> server/node.py).
    Process-global like the [hbm] knobs — all in-process nodes share one
    device. slab_planes <= 0 restores the default."""
    global _slab_planes
    if slab_planes is not None:
        _slab_planes = int(slab_planes) if slab_planes > 0 else _DEFAULT_SLAB_PLANES


def slab_planes() -> int:
    return _slab_planes


def _bump(key: str, value: int = 1) -> None:
    with _stats_mu:
        _counters[key] += value


def stats_snapshot() -> Dict[str, int]:
    with _stats_mu:
        return dict(_counters)


def reset_stats() -> None:
    with _stats_mu:
        for k in _counters:
            _counters[k] = 0


# ---------------------------------------------------------------------------
# shared staging helpers
# ---------------------------------------------------------------------------


def _quarter_budget() -> int:
    from pilosa_tpu.core.devcache import DEVICE_CACHE

    return max(1, DEVICE_CACHE.budget_bytes // 4)


def _slab_guard(n_shards: int, depth: int) -> None:
    """The slab-peak budget guard: one slab of planes plus the word rows
    (exists, sign, filter) and one generation of carried ladder state
    must fit the quarter-budget; otherwise BudgetExceeded and the caller
    halves the SHARD axis (exec.executor._chunk_by_budget) — the plane
    axis is already slab-bounded, so this fires far later than the old
    bit_depth+3 whole-stack guard."""
    from pilosa_tpu.exec.plan import BudgetExceeded
    from pilosa_tpu.shardwidth import WORDS_PER_ROW

    # exactness bound, independent of the byte budget: the min/max
    # attain count accumulates in uint32 IN PROGRAM, so one chunk may
    # span at most 2048 shards (2^31 columns) — huge-budget configs
    # chunk rather than risk a wrapped count
    if n_shards > 2048:
        raise BudgetExceeded("BSI chunk exceeds the exact-count bound")
    mult = min(max(depth, 1), _slab_planes) + 3
    if n_shards * WORDS_PER_ROW * 4 * mult > _quarter_budget():
        raise BudgetExceeded("BSI slab exceeds device budget")


def _run(program: str, fn, read: bool = True):
    """One streamed dispatch; `program` is the jitted kernel `fn` runs,
    as the profiler names it (the exec.dispatch span's plan.program)."""
    from pilosa_tpu.exec import plan as planmod

    _bump("plane_dispatches")
    return planmod.run_counted(fn, read=read, family="bsi", program=program)


def _lower_span():
    """exec.lower for the streamed path's staging: the residency lookups
    (and, on a miss, uploads) that turn a field into device operands."""
    from pilosa_tpu.exec import plan as planmod

    return planmod.lower_span("bsi")


def _stage_slab(bsiv, lo: int, d: int, shards) -> Any:
    """Stage one plane slab (absolute planes [lo, lo+d)) via the view's
    version-keyed residency path, as the TUPLE of per-extent [d, s_i, W]
    parts — the kernels reduce across parts in program, so the slab is
    never concatenated (a device-side concat would re-copy the whole
    slab on every staging)."""
    from pilosa_tpu.core.fragment import BSI_OFFSET_BIT

    with _lower_span():
        planes = bsiv.plane_stack(
            range(BSI_OFFSET_BIT + lo, BSI_OFFSET_BIT + lo + d), shards,
            parts=True,
        )
    _bump("slabs")
    if planes is not None:
        _bump(
            "slab_bytes",
            sum(int(getattr(p, "nbytes", 0)) for p in planes),
        )
    return planes


def _signed_field(f) -> bool:
    """Whether the field can store negative base-values: bsi_base makes
    stored = value - base, and every write is range-checked against
    [min, max], so min >= base implies an empty sign row forever."""
    return f.options.min < f.options.base


def _field_rows(bsiv, shards, signed_: bool):
    """(exists, sign) word-row PART tuples for one shard chunk; sign is
    None for unsigned fields (the kernels compile sign-free variants).
    Parts align with _stage_slab's: same shard list, same extent rows."""
    from pilosa_tpu.core.fragment import BSI_EXISTS_BIT, BSI_SIGN_BIT

    with _lower_span():
        exists = bsiv.row_stack(BSI_EXISTS_BIT, shards, parts=True)
        if exists is None:
            return None, None
        sign = (
            bsiv.row_stack(BSI_SIGN_BIT, shards, parts=True)
            if signed_
            else None
        )
        return exists, sign


_EMPTY = "empty"  # chunk sentinel: no data -> zero contribution


def _filter_stack(ex, idx, filter_call, shards):
    """Lower an aggregate's filter bitmap to a [S, W] device stack over
    `shards` (mirrors executor._stacked_bsi's filter handling). Returns
    the stack, _EMPTY when the filter matches nothing, or None when the
    filter has no stacked form (caller falls back)."""
    from pilosa_tpu.core.devcache import DEVICE_CACHE
    from pilosa_tpu.exec.executor import _StackedLowering
    from pilosa_tpu.exec.plan import PZero, StackedPlan, Unsupported

    low = _StackedLowering(ex, idx, list(shards), no_sparse_guard=True)
    try:
        with _lower_span(), DEVICE_CACHE.deferred_eviction():
            root = low.lower(filter_call)
            if isinstance(root, PZero):
                return _EMPTY
            if not low.operands:
                return None
            sp = StackedPlan(root, low.operands, low.scalars, len(shards))
            return sp.rows_full()
    except Unsupported:
        return None
    finally:
        # pins protect the staging window only; the assembled stack and
        # the aggregate's own operands hold their own device buffers
        low.extents.release()


def _filter_parts(filt, exists_parts):
    """Slice an assembled [S_pad, W] filter stack into parts aligned
    with the staged operand parts (one bounded device slice per part —
    the filter is plan output, so it arrives assembled by nature)."""
    if filt is None:
        return None
    out = []
    off = 0
    for e in exists_parts:
        n = e.shape[0]
        out.append(filt[off:off + n])
        off += n
    return tuple(out)




# ---------------------------------------------------------------------------
# Sum / Min / Max
# ---------------------------------------------------------------------------


def aggregate(ex, idx, c, f, shard_list: Sequence[int], kind: str):
    """Whole-field BSI aggregate (kind in sum|min|max) via the streamed
    lowering. Returns a ValCount, or None to fall back to the legacy
    stacked/per-shard paths (no stacked form for the filter, stream-
    ineligible depth). Raises ExecError for semantic errors exactly like
    the legacy path would."""
    from pilosa_tpu.exec import executor as exmod

    depth = f.options.bit_depth
    signed_ = _signed_field(f)
    if depth <= 0 or depth > 32 or (signed_ and depth > 31):
        # the virtual-key ladder needs depth(+sign) key bits in uint32
        return None
    if not exmod._STACKED_ENABLED or not shard_list:
        return None
    bsiv = f.view(f.bsi_view_name())
    if bsiv is None:
        return exmod.ValCount(0, 0)
    filter_call = None
    if len(c.children) == 1:
        filter_call = c.children[0]
    else:
        fa = c.args.get("filter")
        if fa is not None:
            if not isinstance(fa, exmod.Call):
                return None
            filter_call = fa
    if filter_call is not None and ex._count_shifts(filter_call):
        return None  # Shift needs predecessor-shard augmentation
    bsi_shards = [
        s for s in shard_list if bsiv.fragment_if_exists(s) is not None
    ]
    if not bsi_shards:
        return exmod.ValCount(0, 0)

    def one(chunk):
        # guard BEFORE any staging; a BudgetExceeded from here (or from
        # the filter lowering inside the chunk) halves the shard axis
        _slab_guard(len(chunk), depth)
        part = _aggregate_chunk(
            ex, idx, bsiv, f, filter_call, chunk, kind, depth, signed_
        )
        return None if part is None else [part]

    parts = ex._chunk_by_budget(list(bsi_shards), one)
    if parts is None:
        return None
    count = 0
    total = 0
    best: Optional[Tuple[int, int]] = None  # (value, count) for min/max
    for part in parts:
        if part == _EMPTY:
            continue
        if kind == "sum":
            count += part[0]
            total += part[1]
        else:
            val, cnt, any_ = part
            if not any_ or cnt == 0:
                continue
            if best is None or (
                (val < best[0]) if kind == "min" else (val > best[0])
            ):
                best = (val, cnt)
            elif val == best[0]:
                best = (val, best[1] + cnt)
    if kind == "sum":
        return exmod.ValCount(value=total + count * f.options.base, count=count)
    if best is None:
        return exmod.ValCount(0, 0)
    return exmod.ValCount(value=best[0] + f.options.base, count=best[1])


def _aggregate_chunk(ex, idx, bsiv, f, filter_call, chunk, kind: str,
                     depth: int, signed_: bool):
    """One shard chunk's streamed aggregate: stage word rows + filter
    once, then walk plane slabs. Returns (count, weighted_total) for
    sum, (value, count, any) for min/max, _EMPTY, or None (fallback)."""
    from pilosa_tpu.core.devcache import DEVICE_CACHE
    from pilosa_tpu.exec import plan as planmod
    from pilosa_tpu.ops import bsi as obsi

    with DEVICE_CACHE.deferred_eviction():
        exists, sign = _field_rows(bsiv, chunk, signed_)
        if exists is None:
            return _EMPTY
        filt = None
        if filter_call is not None:
            filt = _filter_stack(ex, idx, filter_call, chunk)
            if filt is None:
                return None
            if filt == _EMPTY:
                return _EMPTY
            filt = _filter_parts(filt, exists)
        slab = _slab_planes
        if kind == "sum":
            # consider computed ONCE per chunk and shared by every slab
            consider = exists
            if filt is not None:
                import jax.numpy as jnp

                consider = planmod.run_serialized(
                    lambda: tuple(
                        jnp.bitwise_and(e, filt[i])
                        for i, e in enumerate(exists)
                    )
                )
            count = 0
            total = 0
            for lo in range(0, depth, slab):
                d = min(slab, depth - lo)
                planes = _stage_slab(bsiv, lo, d, chunk)
                host = np.asarray(
                    _run(
                        "jit_sum_stream_slab",
                        lambda planes=planes, lo=lo:
                        obsi.sum_stream_slab(
                            planes, consider, sign, signed_, lo == 0
                        )
                    ),
                    dtype=np.uint64,
                )
                cnt, part = obsi.decode_sum_slab(
                    host, signed_, lo == 0, lo, d
                )
                count += cnt
                total += part
            return count, total
        # min/max
        is_min = kind == "min"
        if depth <= slab:
            planes = _stage_slab(bsiv, 0, depth, chunk)
            host = np.asarray(
                _run(
                    "jit_min_max_stream",
                    lambda: obsi.min_max_stream(
                        planes, exists, sign, filt, is_min, signed_
                    )
                ),
                dtype=np.uint64,
            )
        else:
            # EMPTY state on the first step — the kernel inits in
            # program. Never pass live arrays as placeholders: the step
            # jit DONATES the state argnums on accelerators, and a
            # donated placeholder that aliases a cached operand (the
            # exists parts) would be deleted under the cache's feet.
            fa: tuple = ()
            va: tuple = ()
            los = list(range(0, depth, slab))
            for n, lo in enumerate(reversed(los)):
                d = min(slab, depth - lo)
                planes = _stage_slab(bsiv, lo, d, chunk)
                fa, va = _run(
                    "jit__min_max_stream_step",
                    lambda planes=planes, fa=fa, va=va, n=n:
                    obsi.min_max_stream_step(
                        planes, exists, sign, filt, fa, va,
                        is_min, signed_, n == 0
                    ),
                    read=False,
                )
            host = np.asarray(
                _run(
                    "jit_min_max_stream_finish",
                    lambda: obsi.min_max_stream_finish(
                        exists, sign, filt, fa, va,
                        depth + (1 if signed_ else 0),
                    )
                ),
                dtype=np.uint64,
            )
    val, cnt, any_ = obsi.decode_min_max(host, depth, is_min, signed_)
    if not any_:
        return _EMPTY
    return val, cnt, any_


# ---------------------------------------------------------------------------
# single-condition Range/Between counts
# ---------------------------------------------------------------------------


def count_range(ex, idx, c, shard_list: Sequence[int]) -> Optional[int]:
    """Count(Row(<single BSI condition>)) via the streamed ladders:
    slab-bounded plane residency, one dispatch per slab (one total at
    depth <= slab), scalar halfword-pair reads. Returns None for shapes
    this path does not own — the caller's plan/per-shard lowering then
    applies its own (identical) semantic checks."""
    from pilosa_tpu.core.field import FIELD_TYPE_INT
    from pilosa_tpu.exec import executor as exmod

    if not exmod._STACKED_ENABLED or not shard_list:
        return None
    conds = c.condition_args()
    if len(c.args) != 1 or len(conds) != 1 or c.children:
        return None
    field_name, cond = next(iter(conds.items()))
    f = idx.field(field_name)
    if f is None or f.options.type != FIELD_TYPE_INT:
        return None  # the legacy path raises the canonical ExecError
    depth = f.options.bit_depth
    if depth <= 0 or depth > 32:
        return None
    signed_ = _signed_field(f)
    bsiv = f.view(f.bsi_view_name())
    if bsiv is None:
        return 0
    dec = _decompose(f, cond, signed_)
    if dec is None:
        return None
    if dec == _ZERO:
        return 0
    jobs, preds, job_weights, extras = dec
    bsi_shards = [
        s for s in shard_list if bsiv.fragment_if_exists(s) is not None
    ]
    if not bsi_shards:
        return 0

    def one(chunk):
        # degenerate NEQ(None)/saturated shapes carry no ladder jobs:
        # they still stream (one mask-count dispatch per chunk), so
        # plane depth only prices the guard when planes are read
        _slab_guard(len(chunk), depth if jobs else 1)
        return [
            _count_chunk(
                bsiv, chunk, depth, signed_, jobs, preds, job_weights,
                extras,
            )
        ]

    parts = ex._chunk_by_budget(list(bsi_shards), one)
    if parts is None:
        return None
    return sum(parts)


# decomposition sentinel: the predicate provably matches nothing
_ZERO = ((), (), (), ())


def _decompose(f, cond, signed_: bool):
    """Mirror of executor._lower_row_bsi's sign/saturation decomposition
    (itself mirroring fragment.range_op/range_between), producing static
    ladder-job descriptors: (jobs, preds, job_weights, extras) where
    jobs = ((kind, mask_sel, allow_eq), ...), preds are uint32
    magnitudes aligned with the jobs (two for between), job_weights and
    extras carry the +/-1 host-combine weights ((sel, weight), ...).
    For unsigned fields the pos/neg selectors collapse: "pos" becomes
    "consider" and "neg" terms drop (the sign row is provably empty).
    Returns None for shapes the streamed path does not own."""
    from pilosa_tpu.pql.ast import BETWEEN, EQ, GT, GTE, LT, LTE, NEQ

    o = f.options

    def final(jobs, preds, weights, extras):
        if signed_:
            return tuple(jobs), tuple(preds), tuple(weights), tuple(extras)
        jobs2, preds2, weights2 = [], [], []
        off = 0
        for job, w in zip(jobs, weights):
            npred = 2 if job[0] == "between" else 1
            if job[1] == "neg":
                off += npred
                continue  # empty mask: zero contribution
            sel = "consider" if job[1] == "pos" else job[1]
            jobs2.append((job[0], sel, job[2]))
            preds2.extend(preds[off:off + npred])
            weights2.append(w)
            off += npred
        extras2 = []
        for sel, w in extras:
            if sel == "neg":
                continue
            extras2.append(("consider" if sel == "pos" else sel, w))
        return tuple(jobs2), tuple(preds2), tuple(weights2), tuple(extras2)

    consider_only = final([], [], [], [("consider", 1)])

    if cond.op == NEQ and cond.value is None:  # != null
        return consider_only
    if cond.op == BETWEEN:
        lo, hi = cond.int_pair()
        blo, bhi, out_of_range = f.base_value_between(lo, hi)
        if out_of_range:
            return _ZERO
        if lo <= o.min and hi >= o.max:
            return consider_only
        if blo >= 0:
            return final(
                [("between", "pos", False)], [abs(blo), abs(bhi)], [1], []
            )
        if bhi < 0:
            return final(
                [("between", "neg", False)], [abs(bhi), abs(blo)], [1], []
            )
        return final(
            [("lt", "pos", True), ("lt", "neg", True)],
            [abs(bhi), abs(blo)], [1, 1], [],
        )

    if not isinstance(cond.value, int) or isinstance(cond.value, bool):
        return None  # the legacy path raises the canonical ExecError
    value = cond.value
    op = {EQ: "eq", NEQ: "neq", LT: "lt", LTE: "lte", GT: "gt", GTE: "gte"}[
        cond.op
    ]
    base_value, out_of_range = f.base_value(op, value)
    if out_of_range and cond.op != NEQ:
        return _ZERO
    if (
        (cond.op == LT and value > o.max)
        or (cond.op == LTE and value >= o.max)
        or (cond.op == GT and value < o.min)
        or (cond.op == GTE and value <= o.min)
    ):
        return consider_only
    if out_of_range and cond.op == NEQ:
        return consider_only
    upred = abs(base_value)
    if op in ("eq", "neq"):
        sel = "neg" if base_value < 0 else "pos"
        if op == "eq":
            return final([("eq", sel, False)], [upred], [1], [])
        return final([("eq", sel, False)], [upred], [-1], [("consider", 1)])
    if op in ("lt", "lte"):
        allow_eq = op == "lte"
        if base_value > 0 or (base_value == 0 and allow_eq):
            return final(
                [("lt", "pos", allow_eq)], [upred], [1], [("neg", 1)]
            )
        if base_value == 0:  # strict < 0
            return final([], [], [], [("neg", 1)])
        return final([("gt", "neg", allow_eq)], [upred], [1], [])
    if op in ("gt", "gte"):
        allow_eq = op == "gte"
        if base_value > 0 or (base_value == 0 and allow_eq):
            return final([("gt", "pos", allow_eq)], [upred], [1], [])
        if base_value == 0:  # strict > 0
            return final([("gt", "pos", False)], [upred], [1], [])
        return final(
            [("lt", "neg", allow_eq)], [upred], [1], [("pos", 1)]
        )
    return None


def _count_chunk(bsiv, chunk, depth: int, signed_: bool, jobs, preds,
                 job_weights, extras) -> int:
    """One shard chunk's streamed range count; exact host combine of the
    per-term halfword pairs with the decomposition's +/- weights."""
    from pilosa_tpu.core.devcache import DEVICE_CACHE
    from pilosa_tpu.ops import bsi as obsi

    import jax.numpy as jnp

    if not jobs and not extras:
        return 0
    with DEVICE_CACHE.deferred_eviction():
        exists, sign = _field_rows(bsiv, chunk, signed_)
        if exists is None:
            return 0
        filt = None  # Count(Row(cond)) carries no separate filter
        upreds = tuple(jnp.uint32(p) for p in preds)
        extra_sels = tuple(sel for sel, _ in extras)
        if not jobs:
            # pure mask count: != null, strict < 0, saturated predicates
            host = np.asarray(
                _run(
                    "jit_mask_count_pair",
                    lambda: obsi.mask_count_pair(
                        exists, sign, filt, extra_sels[0]
                    )
                ),
                dtype=np.uint64,
            )
            return extras[0][1] * obsi.pair_value(host)
        slab = _slab_planes
        if depth <= slab:
            planes = _stage_slab(bsiv, 0, depth, chunk)
            host = np.asarray(
                _run(
                    "jit_range_stream_single",
                    lambda: obsi.range_stream_single(
                        planes, exists, sign, filt, upreds, jobs, extra_sels
                    )
                ),
                dtype=np.uint64,
            )
        else:
            state: tuple = ()
            los = list(range(0, depth, slab))
            for n, lo in enumerate(reversed(los)):
                d = min(slab, depth - lo)
                planes = _stage_slab(bsiv, lo, d, chunk)
                state = _run(
                    "jit__range_stream_step",
                    lambda planes=planes, state=state, lo=lo, n=n:
                    obsi.range_stream_step(
                        planes, exists, sign, filt, state, upreds,
                        jobs, lo, n == 0
                    ),
                    read=False,
                )
            host = np.asarray(
                _run(
                    "jit_range_stream_finish",
                    lambda: obsi.range_stream_finish(
                        exists, sign, filt, state, jobs, extra_sels
                    )
                ),
                dtype=np.uint64,
            )
    total = 0
    off = 0
    for w in job_weights:
        total += w * obsi.pair_value(host, off)
        off += 2
    for _, w in extras:
        total += w * obsi.pair_value(host, off)
        off += 2
    return total
