"""Device GroupBy: batched cross-product tally over stacked row operands.

TPU-native replacement for the reference's groupByIterator
(/root/reference/executor.go:3063), which walks the rows cross-product one
group element at a time — in the round-1 rebuild that meant one device
dispatch + host sync per (group-prefix, depth).

Shapes: `planes` stacks are uint32[R, S, W] (candidate rows x shards x
words; shard-axis-sharded under an active mesh); the accumulator `acc` is
uint32[G, S, W] for the G live prefixes. An operand arrives as one array
or as the TUPLE of its resident per-extent parts `[R, S_e, W]`, as
`View.plane_stack(parts=True)` stages them (hbm/residency.py): a view of
more than one extent of shards is never written again as one stack for a
GroupBy the kernel tallies in one shot.
Counts are reduced over W on device in uint32 (one shard holds at most
2^20 bits, so a per-shard count can never wrap) and over the shard axis
on the host in exact uint64 — the same overflow discipline as
StackedPlan.count (exec/plan.py).

Two programs tally a cross (`cross_tally` picks by what its operands are):

- `ops/pallas_kernels.cross_counts`, a VMEM kernel, for stacks that live
  on ONE TPU: every operand row is read from HBM once per tally, the
  whole G x R cross of AND + popcount is formed on tiles in VMEM, and
  with a third stack the last prefix level (acc[g] & mid[m]) is formed
  there too and never written to HBM. A 2- or 3-level GroupBy whose
  [groups, S] count read fits `_ONESHOT_READ_BYTES` is therefore ONE
  launch and ONE host read whatever the number of shards, and reads its
  operands' parts in place (one `pallas_call` per extent inside the one
  program; `inplace_tallies`).
- `_counts_cross`, the XLA program (a lax.map over the candidate rows
  that re-reads `acc` per row), for every other backend and for stacks
  sharded over a mesh; it is also the kernel's differential oracle. Its
  [G, S, W] intermediate is why prefixes are chunked for it.

Everything but the kernel's one-shot path works on whole stacks — the XLA
program, the pruned descent's row selections, a cross of more than three
levels — and concatenates an operand given as parts once, where it is
first needed (`assembled_stacks`).

A GroupBy that carries an aggregate (`group_by_aggregate`) owes every live
group one count per BSI plane of the value field besides its own. Its
work follows the groups, not the cross: under a filter each dimension is
first tallied against the filter alone (the rows the filter leaves are
the only ones a group can hold), and the groups those rows make — or,
where they are too many to list, the live groups of the count-only tally
above — are handed to `group_tally` as a table of row indices, which
counts each listed group and its planes in one pass over the rows where
they lie (`ops/pallas_kernels.group_counts`; `_counts_groups` is the XLA
program for every other backend and the kernel's oracle). Per-shard
counts are uint32 and are folded on the host in exact integers.

Cross-products too deep or too large for one read are tallied level-wise:
at depth d one tally counts every live prefix against every candidate
row, and one host read prunes zero groups before descending. Dispatch
count is O(depth x chunks), independent of the number of groups. Prefixes
materialised in HBM are processed depth-first in chunks of at most
`_gmax()` rows (PILOSA_TPU_GROUPBY_TILE_MB, default 256 MB per tile), so
live device memory is <= depth * tile regardless of group fan-out. Chunk
index vectors are padded to powers of two to bound recompilation.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Dispatch accounting (tests assert O(depth), not O(groups), dispatches);
# the tally counts are published as groupby.* gauges (server/node.py):
# tallies by program, tallies that read more than one extent in place, and
# operands concatenated into one stack for a tally.
# `assembled_bytes` is what those concatenations wrote; `aggregate_queries`
# and `plane_tallies` (group x plane pairs counted) are the aggregate's.
STATS = {
    "evals": 0, "kernel_tallies": 0, "xla_tallies": 0,
    "inplace_tallies": 0, "assembled_stacks": 0, "assembled_bytes": 0,
    "aggregate_queries": 0, "plane_tallies": 0,
}

KERNEL_PROGRAM = "jit__cross_counts_vmem"
XLA_PROGRAM = "jit__counts_cross"


def reset_stats() -> None:
    for k in STATS:
        STATS[k] = 0


def _tile_bytes() -> int:
    mb = int(os.environ.get("PILOSA_TPU_GROUPBY_TILE_MB", "256"))
    return max(1, mb) << 20


def _gmax(s: int, w: int) -> int:
    return max(1, _tile_bytes() // (s * w * 4))


def _pad_pow2(idx: np.ndarray) -> np.ndarray:
    n = len(idx)
    target = 1 << max(n - 1, 0).bit_length()
    if target == n:
        return idx
    return np.concatenate([idx, np.zeros(target - n, idx.dtype)])


@jax.jit
def _counts_planes(planes):
    """uint32[R, S, W] -> per-shard counts uint32[R, S]."""
    return jnp.sum(jax.lax.population_count(planes), axis=-1, dtype=jnp.uint32)


@jax.jit
def _counts_cross(acc, planes):
    """acc uint32[G, S, W] x planes uint32[R, S, W] -> per-shard counts
    uint32[G, R, S].

    lax.map over the candidate-row axis keeps the live intermediate at
    [G, S, W] instead of materializing the full [G, R, S, W] cross."""

    def per_row(p):
        return jnp.sum(
            jax.lax.population_count(jnp.bitwise_and(acc, p[None])),
            axis=-1,
            dtype=jnp.uint32,
        )

    out = jax.lax.map(per_row, planes)  # [R, G, S]
    return jnp.transpose(out, (1, 0, 2))


def _parts(x) -> tuple:
    """An operand as the tuple of its per-extent parts (one array: one)."""
    return x if isinstance(x, tuple) else (x,)


def _shape(x) -> Tuple[int, int, int]:
    """(rows, shards, words) of a stack given whole or as parts."""
    parts = _parts(x)
    rows, _, w = parts[0].shape
    return int(rows), sum(int(p.shape[1]) for p in parts), int(w)


def _assembled(x):
    """The whole [R, S, W] stack of an operand (None stays None). Parts
    are concatenated along the shard axis, which writes the operand
    again: counted."""
    if not isinstance(x, tuple):
        return x
    if len(x) == 1:
        return x[0]
    STATS["assembled_stacks"] += 1
    STATS["assembled_bytes"] += sum(int(p.nbytes) for p in x)
    return jnp.concatenate(x, axis=1)


def _spans_align(*stacks) -> bool:
    """Whether the operands' parts cover the same shard spans, part for
    part (the same shard list staged at one extent size always does)."""
    spans = {tuple(int(p.shape[1]) for p in _parts(x)) for x in stacks}
    return len(spans) == 1


def _kernel_covers(*stacks) -> bool:
    """Whether the VMEM kernel tallies these operands: all of them (every
    part of them) on one TPU, rows a whole number of lanes. Read from the
    arrays themselves — a mesh-sharded stack, another backend or a host
    array is the XLA program's."""
    for part in (p for x in stacks if x is not None for p in _parts(x)):
        devices = getattr(part, "devices", None)
        if devices is None or part.shape[-1] % 128:
            return False
        devices = devices()
        if len(devices) != 1 or next(iter(devices)).platform != "tpu":
            return False
    return True


def tally_program(planes_list: Sequence, filt=None) -> str:
    """The jitted program that will tally this GroupBy, as the profiler's
    "XLA Modules" line names it (the exec.dispatch span's plan.program)."""
    return KERNEL_PROGRAM if _kernel_covers(*planes_list, filt) else XLA_PROGRAM


def cross_tally(acc, planes, mid=None, filt=None):  # dispatch-ok: caller holds dispatch_mutex
    """Per-shard counts uint32[G, R, S] of popcount(acc[g] & planes[r]) —
    with `mid`, uint32[G, M, R, S] of acc[g] & mid[m] & planes[r]; `filt`
    uint32[S, W] masks acc. The kernel where it covers the operands, else
    the XLA program over prefixes materialised here (callers keep G x M
    within `_gmax` for it). acc, planes and mid are each one array or a
    tuple of parts: the kernel reads parts that line up in place, anything
    else is assembled first."""
    stacks = [x for x in (acc, planes, mid) if x is not None]
    kernel = _kernel_covers(*stacks, filt)
    if not (kernel and _spans_align(*stacks)):
        acc, planes, mid = (_assembled(x) for x in (acc, planes, mid))
    if kernel:
        from pilosa_tpu.ops import pallas_kernels

        STATS["kernel_tallies"] += 1
        if len(_parts(planes)) > 1:
            STATS["inplace_tallies"] += 1
        return pallas_kernels.cross_counts(acc, planes, mid, filt)
    STATS["xla_tallies"] += 1
    if filt is not None:
        acc = jnp.bitwise_and(acc, filt[None])
    if mid is None:
        return _counts_cross(acc, planes)
    out = _counts_cross(_cross_expand(acc, mid), planes)
    return out.reshape(acc.shape[0], mid.shape[0], *out.shape[1:])


def _host_sum(counts) -> np.ndarray:
    """Sum per-shard uint32 counts over the shard axis in exact uint64."""
    return np.asarray(counts).astype(np.uint64).sum(axis=-1)


@jax.jit
def _select_rows(planes, r_idx):
    return planes[r_idx]


@jax.jit
def _select_rows_filtered(planes, r_idx, filt):
    return jnp.bitwise_and(planes[r_idx], filt[None])


@jax.jit
def _select_pairs(acc, planes, g_idx, r_idx):
    return jnp.bitwise_and(acc[g_idx], planes[r_idx])


@jax.jit
def _cross_expand(acc, planes):
    """uint32[G, S, W] x uint32[R, S, W] -> uint32[G*R, S, W], row-major
    (group g, row r) -> g*R + r."""
    out = jnp.bitwise_and(acc[:, None], planes[None])
    return out.reshape(-1, acc.shape[1], acc.shape[2])


# Cap on the fused [G, R_last, S] count read of the one-shot path.
_ONESHOT_READ_BYTES = 64 << 20


# dispatch-ok escapes below: the CALLER holds the mutex —
# executor._group_by_stacked wraps the whole cross-tally pipeline in
# plan.dispatch_mutex() (operands staged before entry)
def group_by_device(  # dispatch-ok: caller holds dispatch_mutex
    planes_list: Sequence,
    row_lists: Sequence[Sequence[int]],
    filt: Optional[jax.Array] = None,
) -> Dict[Tuple[int, ...], int]:
    """Tally the full GroupBy cross-product on device.

    planes_list[k] is the uint32[R_k, S, W] stack of child k's candidate
    rows, whole or as the tuple of its per-extent parts; row_lists[k] the
    matching row ids; filt an optional uint32[S, W] filter stack (same
    shard padding, always whole). Returns {(row0, row1, ...): count}
    with zero-count groups pruned — the same contract as the per-shard
    groupByIterator walk, summed over all shards."""
    merged: Dict[Tuple[int, ...], int] = {}
    rows = [_shape(p)[0] for p in planes_list]
    if not rows or not all(rows):
        return merged
    depth_n = len(planes_list)
    _, s, w = _shape(planes_list[0])
    gmax = _gmax(s, w)
    kernel = _kernel_covers(*planes_list, filt)

    # One-shot path for small cross-products: NO intermediate host reads,
    # tally the last level, read ONCE. The pruned descent below costs one
    # blocking read per depth — a synchronisation each, pure latency — and
    # pruning only pays when the count read is too big to take whole.
    # What bounds it besides the read is the prefix rows it must hold in
    # HBM at once: all of them for the XLA program (its tally holds a
    # [G, S, W] intermediate even when G is an operand as staged); for the
    # kernel, which forms the last prefix level in VMEM, only the cross of
    # the levels before the last two — nothing up to three levels.
    g_pre = int(np.prod(rows[:-1], dtype=np.int64))
    read_cells = g_pre * rows[-1] * s * 4
    held = rows[:-1] if not kernel else rows[:-2] if depth_n > 3 else []
    g_held = int(np.prod(held, dtype=np.int64))
    if g_held <= gmax and read_cells <= _ONESHOT_READ_BYTES:
        return _group_by_oneshot(planes_list, row_lists, filt, kernel)

    # The descent selects rows out of whole stacks.
    planes_list = [_assembled(p) for p in planes_list]
    # Depth 0: counts for every candidate row of the first child.
    if filt is not None:
        h = _host_sum(cross_tally(filt[None], planes_list[0])[0])
    else:
        h = _host_sum(_counts_planes(planes_list[0]))
    STATS["evals"] += 1
    live = np.nonzero(h)[0]
    if depth_n == 1:
        for i in live:
            merged[(int(row_lists[0][i]),)] = int(h[i])
        return merged

    for start in range(0, len(live), gmax):
        idx = live[start : start + gmax]
        idx_p = _pad_pow2(idx)
        if filt is not None:
            acc = _select_rows_filtered(planes_list[0], idx_p, filt)
        else:
            acc = _select_rows(planes_list[0], idx_p)
        STATS["evals"] += 1
        prefixes = [(int(row_lists[0][i]),) for i in idx]
        _descend(1, acc, prefixes, planes_list, row_lists, merged, gmax)
    return merged


def _group_by_oneshot(  # dispatch-ok: caller holds dispatch_mutex
    planes_list: Sequence,
    row_lists: Sequence[Sequence[int]],
    filt: Optional[jax.Array],
    kernel: bool,
) -> Dict[Tuple[int, ...], int]:
    """Whole cross-product in one fused device pipeline + ONE host read.
    Zero-count groups are pruned at merge (same contract as the descent).
    All dispatches are async; only the final np.asarray blocks. With the
    kernel the filter and the last prefix level ride inside the tally, so
    two or three levels are a single launch over the operands as they
    are staged, parts included; any other shape runs a program that needs
    whole stacks before the tally."""
    merged: Dict[Tuple[int, ...], int] = {}
    n = len(planes_list)
    if not (kernel and n in (2, 3)):
        planes_list = [_assembled(p) for p in planes_list]
    acc = planes_list[0]
    if filt is not None and (n == 1 or not kernel):
        acc = _select_rows_filtered(acc, np.arange(acc.shape[0]), filt)
        STATS["evals"] += 1
        filt = None
    keys: List[Tuple[int, ...]] = [(int(r),) for r in row_lists[0]]
    if n == 1:
        h = _host_sum(_counts_planes(acc))
        STATS["evals"] += 1
        for i, cnt in enumerate(h):
            if cnt:
                merged[keys[i]] = int(cnt)
        return merged
    mid = None
    for d in range(1, n - 1):
        if kernel and d == n - 2:
            mid = planes_list[d]  # the last prefix level: formed in the tally
        else:
            acc = _cross_expand(acc, planes_list[d])
            STATS["evals"] += 1
        keys = [k + (int(r),) for k in keys for r in row_lists[d]]
    last_rows = row_lists[-1]
    h = _host_sum(cross_tally(acc, planes_list[-1], mid, filt))
    h = h.reshape(len(keys), len(last_rows))  # [G(, M), R_last] -> [G, R_last]
    STATS["evals"] += 1
    gs, rs = np.nonzero(h)
    for g, r in zip(gs, rs):
        merged[keys[g] + (int(last_rows[r]),)] = int(h[g, r])
    return merged


def _descend(  # dispatch-ok: caller holds dispatch_mutex
    depth: int,
    acc: jax.Array,
    prefixes: List[Tuple[int, ...]],
    planes_list: Sequence[jax.Array],
    row_lists: Sequence[Sequence[int]],
    merged: Dict[Tuple[int, ...], int],
    gmax: int,
) -> None:
    h = _host_sum(cross_tally(acc, planes_list[depth]))[: len(prefixes)]
    STATS["evals"] += 1
    gs, rs = np.nonzero(h)
    if depth == len(planes_list) - 1:
        for g, r in zip(gs, rs):
            key = prefixes[g] + (int(row_lists[depth][r]),)
            merged[key] = merged.get(key, 0) + int(h[g, r])
        return
    for start in range(0, len(gs), gmax):
        gi = gs[start : start + gmax]
        ri = rs[start : start + gmax]
        acc2 = _select_pairs(
            acc, planes_list[depth], _pad_pow2(gi), _pad_pow2(ri)
        )
        STATS["evals"] += 1
        pfx = [
            prefixes[g] + (int(row_lists[depth][r]),) for g, r in zip(gi, ri)
        ]
        _descend(depth + 1, acc2, pfx, planes_list, row_lists, merged, gmax)


# ---------------------------------------------------------------------------
# GroupBy with an aggregate: counts of listed groups and of their planes
# ---------------------------------------------------------------------------

GROUP_KERNEL_PROGRAM = "jit__group_counts_vmem"
GROUP_XLA_PROGRAM = "jit__counts_groups"
# rows of the value field's plane stack, as its fragments number them
from pilosa_tpu.core.fragment import (  # noqa: E402
    BSI_EXISTS_BIT as _EXISTS_ROW,
    BSI_OFFSET_BIT as _OFFSET_ROW,
    BSI_SIGN_BIT as _SIGN_ROW,
)


@functools.partial(jax.jit, static_argnames=("mask_row",))
def _counts_groups(dims, idx, planes, filt, mask_row):
    """dims a tuple of uint32[R_d, S, W], idx int32[n_dims, G], planes
    uint32[P, S, W] or None, filt uint32[S, W] or None -> per-shard counts
    uint32[G, 1 + P, S]: [g, 0] of t = AND_d dims[d][idx[d, g]] (& filt),
    [g, 1 + p] of t & planes[mask_row] & planes[p]. The listed prefixes are
    materialised ([G, S, W]: callers keep G within `_gmax`)."""
    t = dims[0][idx[0]]
    for d, i in zip(dims[1:], idx[1:]):
        t = jnp.bitwise_and(t, d[i])
    if filt is not None:
        t = jnp.bitwise_and(t, filt[None])
    own = _counts_planes(t)[:, None]
    if planes is None:
        return own
    if mask_row is not None:
        t = jnp.bitwise_and(t, planes[mask_row][None])
    return jnp.concatenate([own, _counts_cross(t, planes)], axis=1)


def _group_kernel_covers(dims, planes, filt) -> bool:
    """Whether the VMEM group tally counts these operands: what
    `_kernel_covers` asks, parts that line up, and one tile of every row
    within the kernel's buffer."""
    from pilosa_tpu.ops import pallas_kernels

    stacks = list(dims) + ([planes] if planes is not None else [])
    if not (_kernel_covers(*stacks, filt) and _spans_align(*stacks)):
        return False
    rows = sum(_shape(x)[0] for x in stacks) + (filt is not None)
    return bool(pallas_kernels.group_words(rows, _shape(dims[0])[2]))


def group_program(dims, planes=None, filt=None) -> str:
    """The jitted program that tallies an aggregate GroupBy's groups."""
    if _group_kernel_covers(dims, planes, filt):
        return GROUP_KERNEL_PROGRAM
    return GROUP_XLA_PROGRAM


def group_tally(dims, idx: np.ndarray, planes=None, filt=None, mask_row=None):  # dispatch-ok: caller holds dispatch_mutex
    """Per-shard counts of the groups `idx` int[n_dims, G] lists (row
    idx[d, g] of dimension d makes group g), and of each with every plane
    of `planes`: a list of device arrays uint32[<= G padded, 1 + P, S], one
    a launch, for `_read_groups`. The kernel where it covers the operands
    (parts read in place, the table padded to a power of two and only the
    listed groups tallied), else the XLA program over whole stacks in
    chunks of `_gmax` groups. Launches are asynchronous."""
    from pilosa_tpu.ops import pallas_kernels

    g_n = idx.shape[1]
    STATS["plane_tallies"] += g_n * (0 if planes is None else _shape(planes)[0])
    kernel = _group_kernel_covers(dims, planes, filt)
    if kernel:
        step = pallas_kernels.GROUP_MAX_GROUPS
        in_place = len(_parts(dims[0])) > 1
    else:
        dims = tuple(_assembled(d) for d in dims)
        planes = _assembled(planes)
        _, s, w = _shape(dims[0])
        step = 1 << (_gmax(s, w).bit_length() - 1)
    outs = []
    for lo in range(0, g_n, step):
        chunk = idx[:, lo : lo + step]
        table = np.stack([_pad_pow2(r) for r in chunk]).astype(np.int32)
        STATS["evals"] += 1
        if kernel:
            STATS["kernel_tallies"] += 1
            STATS["inplace_tallies"] += in_place
            outs.append(pallas_kernels.group_counts(
                dims, table, np.array([chunk.shape[1]], np.int32), planes,
                filt, mask_row,
            ))
        else:
            STATS["xla_tallies"] += 1
            outs.append(_counts_groups(dims, table, planes, filt, mask_row))
    return outs


def _read_groups(outs, g_n: int) -> np.ndarray:
    """The launches of one `group_tally` read and summed over the shard
    axis in exact uint64: [G, 1 + P]."""
    return np.concatenate([_host_sum(o) for o in outs], axis=0)[:g_n]


def group_by_aggregate(  # dispatch-ok: caller holds dispatch_mutex
    planes_list: Sequence,
    row_lists: Sequence[Sequence[int]],
    filt: Optional[jax.Array],
    value,
    depth: int,
    signed: bool,
    info: dict,
) -> Dict[Tuple[int, ...], Tuple[int, int, int]]:
    """GroupBy with `aggregate=Sum(field=)` on device.

    planes_list, row_lists and filt as for `group_by_device`; `value` the
    value field's uint32[2 + depth, S, W] stack (exists, sign, then the
    magnitude planes, least significant first), whole or as parts that
    line up with the dimensions'; `signed` whether a stored value can be
    negative. Returns {(row0, row1, ...): (count, stored sum, values)}
    with zero-count groups pruned: `count` the group's columns (under the
    filter), `values` those of them that hold a value and `stored sum` the
    exact integer sum of the stored (base-relative) values over them.
    `info` gains levels / live_groups / planes / fold_ms.

    Launches and blocking reads are O(levels): under a filter one tally
    of each dimension against the filter; then the groups the surviving
    rows make are counted with their planes in one launch — or, where
    they are more than one launch lists, `group_by_device` finds the live
    groups first and they alone are tallied with the planes. Work follows
    the listed groups x planes, never the unpruned cross."""
    from pilosa_tpu.ops import pallas_kernels

    STATS["aggregate_queries"] += 1
    info.update(levels=len(planes_list), planes=_shape(value)[0],
                live_groups=0, fold_ms=0.0)
    rows = [_shape(p)[0] for p in planes_list]
    if not rows or not all(rows):
        return {}
    # the rows of each dimension a group can hold: those the filter leaves
    live = [np.arange(r) for r in rows]
    if filt is not None:
        outs = [
            group_tally([p], np.arange(r)[None], filt=filt)
            for p, r in zip(planes_list, rows)
        ]
        live = [
            np.nonzero(_read_groups(o, r)[:, 0])[0] for o, r in zip(outs, rows)
        ]
        if not all(len(x) for x in live):
            return {}
    n_cand = int(np.prod([len(x) for x in live], dtype=np.int64))
    if n_cand <= pallas_kernels.GROUP_MAX_GROUPS:
        grid = np.meshgrid(*live, indexing="ij")
        idx = np.stack([g.ravel() for g in grid])
    else:
        counted = group_by_device(planes_list, row_lists, filt)
        if not counted:
            return {}
        at = [{int(r): i for i, r in enumerate(rl)} for rl in row_lists]
        idx = np.array(
            [[at[d][k[d]] for k in sorted(counted)] for d in range(len(rows))]
        )
    g_n = idx.shape[1]
    pos = group_tally(planes_list, idx, value, filt, _EXISTS_ROW)
    neg = None
    if signed:
        neg = group_tally(planes_list, idx, value, filt, _SIGN_ROW)
    pos = _read_groups(pos, g_n)
    neg = None if neg is None else _read_groups(neg, g_n)

    t0 = time.perf_counter()
    keep = np.nonzero(pos[:, 0])[0]
    weights = np.array([1 << p for p in range(depth)], dtype=object)
    mags = pos[keep, 1 + _OFFSET_ROW : 1 + _OFFSET_ROW + depth].astype(object)
    if neg is not None:
        mags = mags - 2 * neg[
            keep, 1 + _OFFSET_ROW : 1 + _OFFSET_ROW + depth
        ].astype(object)
    sums = (mags * weights).sum(axis=1) if depth else np.zeros(len(keep), object)
    merged = {}
    for n, g in enumerate(keep):
        key = tuple(int(row_lists[d][idx[d, g]]) for d in range(len(rows)))
        merged[key] = (
            int(pos[g, 0]), int(sums[n]), int(pos[g, 1 + _EXISTS_ROW])
        )
    info.update(live_groups=len(merged),
                fold_ms=round((time.perf_counter() - t0) * 1000.0, 3))
    return merged
