"""The hand-written TPU kernels, both GroupBy's: the cross tally
(`cross_counts`) and, for a GroupBy that carries an aggregate, the tally
of listed groups and their value planes (`group_counts`, at the end of
this file).

`cross_counts` (reference: the GroupBy tally, executor.go:3063) forms a
G (x M) x R cross of AND + popcount over multi-GB `[rows, S, W]` stacks on
tiles in VMEM, so every operand row is read from HBM once and only the
per-shard counts are written. An operand is one array or the tuple of the
per-extent arrays `[rows, S_e, W]` a view of many shards is resident as
(hbm/residency.py): the one jitted entry runs the kernel once per extent,
each with the body of that extent's own layout, and concatenates the
counts, so the extents are read where they lie and never written again
as one stack. `exec/groupby.py` `cross_tally` selects it
from what its operands are (every stack on one TPU, lane-aligned) and
otherwise runs XLA's `_counts_cross`, which is also the kernel's reference
in the tests. XLA ran that cross as a per-row slice-and-copy loop at 0.40 %
of the HBM roofline where the kernel reads 12.9 % (ledger PR 27,
`taxi-1b.q1-q4`). Every other counting op is one pass over its operands,
which XLA fuses and tiles by itself (Count: 88.6 % of the roofline on one
chip, 85.0 % on the mesh; ledger PR 30), and has no kernel here.

Both kernels
- operate on uint32 word arrays (bit b of word w = position 32w+b),
- accumulate in int32 (wrap-compatible with the uint32 count convention
  in ops/bitmap.py),
- compile for the TPU only: nothing here chooses interpret mode. A test
  that wants the interpreter asks for it around the call
  (`pltpu.force_tpu_interpret_mode()`); anywhere else a backend that
  cannot compile the kernel raises.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The tally never reshapes or copies a stack: it reads each [rows, S, W]
# operand in the layout the device already keeps it in. The TPU runtime
# picks that layout from the shape. "Shard-major" (S outermost, a tile =
# 8 rows x 128 words of one shard) is what a stack of 1, 2, 4 or 8k rows
# gets when S is not a multiple of 8 (the 186-shard tail extent of 954
# shards, and a whole [8k, 954, W] stack); otherwise the row-major default
# (a tile = 8 shards x 128 words of one row: a full 256-shard extent). One
# kernel body per layout, chosen per extent; an operand in the other layout
# is still answered exactly, after a relayout copy XLA inserts.
_CROSS_ROWS = (8, 8, 16)  # acc / mid / planes rows per grid step
_CROSS_PARTS = 32  # partial-count vregs carried through the word loop
# Words of a row per grid step. The row-major body ends every grid step
# with a lane reduction per pair, so it wants long steps: 16384 words is
# what its largest blocks (8 + 8 + 16 rows x 8 shards, double-buffered =
# 32 MB) leave room for under the VMEM limit (one [8|16, 256, W] extent
# of Q4, unrolled body: 12.6 ms at 4096, 11.8 at 8192, 11.3 at 16384; my
# chip runs, PR 32).
_CROSS_WORDS = {True: 32768, False: 16384}
_CROSS_VMEM_BYTES = 40 << 20
_SUBLANES = 8
_LANES = 128


def _cross_refs(mt: int, has_filt: bool, refs):
    refs = list(refs)
    acc_ref = refs.pop(0)
    filt_ref = refs.pop(0) if has_filt else None
    mid_ref = refs.pop(0) if mt else None
    planes_ref, out_ref = refs

    @pl.when(pl.program_id(4) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    return acc_ref, filt_ref, mid_ref, planes_ref, out_ref


def _lane_slice(j):
    return pl.ds(pl.multiple_of(j * _LANES, _LANES), _LANES)


def _word_loop(steps: int, body, init):
    """The loop over a block's 128-word columns, unrolled while the
    partials of the unrolled bodies still fit the vreg budget (a body of
    one or two pairs is otherwise mostly loop overhead)."""
    unroll = max(1, min(8, _CROSS_PARTS // len(init)))
    while steps % unroll:
        unroll -= 1

    def unrolled(i, parts):
        for k in range(unroll):
            parts = body(i * unroll + k, parts)
        return parts

    return jax.lax.fori_loop(0, steps // unroll, unrolled, init)


def _tally_step(parts, pre, row, acc_ref, filt, mid_ref, ps):
    """parts += popcount(acc[g] (& filt) (& mid[m]) & p) for every (g, m)
    of `pre` and candidate tile p of `ps`, prefix-major; `row(ref, i)`
    loads row i's tile, each operand row once (a row index is a Python
    int or a traced scalar; the same index object is the same row)."""

    def key(i):
        return i if isinstance(i, int) else id(i)

    def rows(ref, idx):
        return {k: row(ref, i) for k, i in {key(i): i for i in idx}.items()}

    accs = rows(acc_ref, [g for g, _ in pre])
    if filt is not None:
        accs = {g: a & filt for g, a in accs.items()}
    if mid_ref is None:
        ts = [accs[key(g)] for g, _ in pre]
    else:
        mids = rows(mid_ref, [m for _, m in pre])
        ts = [accs[key(g)] & mids[key(m)] for g, m in pre]
    pcs = [
        jax.lax.population_count(jnp.bitwise_and(t, p).astype(jnp.int32))
        for t in ts
        for p in ps
    ]
    return tuple(a + b for a, b in zip(parts, pcs))


def _cross_kernel_shard_major(gt, mt, rt, has_filt, *refs):
    """One grid step on [rows, wt words] blocks of ONE shard: a vreg holds
    128 words of 8 candidate rows, each prefix row is broadcast over the
    sublanes. Partials [8 rows, 128 lanes] per (prefix, 8-row slab) are
    carried through the word loop and reduced across lanes once, into lane
    `prefix` of the resident out block [rt, 128]. mt == 0: no mid level."""
    acc_ref, filt_ref, mid_ref, planes_ref, out_ref = _cross_refs(
        mt, has_filt, refs
    )
    steps = planes_ref.shape[-1] // _LANES
    m_n = max(mt, 1)
    prefixes = [(g, m) for g in range(gt) for m in range(m_n)]
    slabs = [
        (r0, min(r0 + _SUBLANES, rt)) for r0 in range(0, rt, _SUBLANES)
    ]
    sub_p = max(1, _CROSS_PARTS // len(slabs))
    for p0 in range(0, len(prefixes), sub_p):
        pre = prefixes[p0 : p0 + sub_p]

        def body(j, parts, pre=pre):
            sl = _lane_slice(j)
            return _tally_step(
                parts, pre, lambda ref, i: ref[i : i + 1, sl], acc_ref,
                filt_ref[:, sl] if has_filt else None, mid_ref,
                [planes_ref[lo:hi, sl] for lo, hi in slabs],
            )

        zeros = tuple(
            jnp.zeros((hi - lo, _LANES), jnp.int32) for lo, hi in slabs
        )
        parts = _word_loop(steps, body, zeros * len(pre))
        for v, (lo, hi) in enumerate(slabs):
            lane = jax.lax.broadcasted_iota(jnp.int32, (hi - lo, _LANES), 1)
            tile = zeros[v]
            for k in range(len(pre)):
                col = jnp.sum(parts[k * len(slabs) + v], axis=1, keepdims=True)
                tile = jnp.where(lane == p0 + k, col, tile)
            out_ref[lo:hi, :] += tile


def _cross_kernel_row_major(gt, mt, rt, has_filt, *refs):
    """One grid step on [rows, 8 shards, wt words] blocks: a vreg holds the
    same 128 words of 8 shards of one row, so a pair's partial
    [8 shards, 128 lanes] reduces across lanes to 8 per-shard counts, put
    in lane `pair` of the resident out block [8, pairs]. The prefix
    groups that fit the carried partials are a LOOP over one group's code,
    not unrolled (64 prefixes x 16 rows are 16 groups x 2 slabs of rows):
    unrolled, one Q4 program of four extents took 6 s to trace and lower
    and 22 s inside a server whose heap holds an index; so 1 s and 5 s, for
    a 4 % slower tally (40.5 -> 42.1 ms; my chip runs, PR 32). Candidate
    rows keep static indices: with those dynamic too it is 48 ms. A ragged
    last group tallies its last prefix again and does not write it."""
    acc_ref, filt_ref, mid_ref, planes_ref, out_ref = _cross_refs(
        mt, has_filt, refs
    )
    steps = planes_ref.shape[-1] // _LANES
    m_n = max(mt, 1)
    n_pre = gt * m_n
    sub_r = min(rt, _SUBLANES)
    sub_p = max(1, min(n_pre, _CROSS_PARTS // sub_r))
    window = sub_p * rt  # lanes of the out block one group writes: <= 64

    def group(i, carry):
        p0 = i * sub_p
        ps = [jnp.minimum(p0 + k, n_pre - 1) for k in range(sub_p)]
        if m_n % sub_p == 0:  # one acc row per group: loaded once
            g = p0 // m_n
            pre = [(g, p % m_n) for p in ps]
        else:
            pre = [(p // m_n, p % m_n) for p in ps]
        for r0 in range(0, rt, sub_r):
            rows = range(r0, min(r0 + sub_r, rt))

            def body(j, parts, rows=rows):
                sl = _lane_slice(j)
                return _tally_step(
                    parts, pre, lambda ref, i: ref[i, :, sl], acc_ref,
                    filt_ref[:, sl] if has_filt else None, mid_ref,
                    [planes_ref[r, :, sl] for r in rows],
                )

            zero = jnp.zeros((_SUBLANES, _LANES), jnp.int32)
            parts = _word_loop(steps, body, (zero,) * (sub_p * len(rows)))
            # the group's pairs are lanes [p0 * rt, (p0 + sub_p) * rt) of
            # the out block: gathered in registers (a store per pair to a
            # lane chunk found at run time is a chain of dependent
            # read-modify-writes), then added to the one or two 128-lane
            # chunks the window touches
            lane = jax.lax.broadcasted_iota(jnp.int32, zero.shape, 1)
            first = p0 * rt // _LANES
            tiles = [zero] * (1 if _LANES % window == 0 else 2)
            for k in range(sub_p):
                for n, r in enumerate(rows):
                    col = jnp.sum(
                        parts[k * len(rows) + n], axis=1, keepdims=True
                    )
                    to = jnp.where(
                        p0 + k < n_pre, p0 * rt % _LANES + k * rt + r, -1
                    )
                    tiles = [
                        jnp.where(lane == to - c * _LANES, col, t)
                        for c, t in enumerate(tiles)
                    ]
            last = out_ref.shape[-1] // _LANES - 1
            for c, t in enumerate(tiles):
                at = jnp.minimum(first + c, last) * _LANES
                out_ref[:, pl.ds(pl.multiple_of(at, _LANES), _LANES)] += t
        return carry

    jax.lax.fori_loop(0, pl.cdiv(n_pre, sub_p), group, 0)


@functools.partial(jax.jit, static_argnames=("shard_major",))
def _cross_counts_extent(acc, planes, mid, filt, shard_major: bool):
    """One `pallas_call` over one extent of every operand: acc [G, S_e, W],
    planes [R, S_e, W], mid [M, S_e, W] or None, filt [S_e, W] or None ->
    uint32[G(, M), R, S_e]. Jitted so that the extents of one shape (three
    of 954 shards' four) are traced and lowered once inside the entry."""
    g_n, s_n, w = acc.shape
    r_n = planes.shape[0]
    m_n = 1 if mid is None else mid.shape[0]
    assert w % _LANES == 0, f"row width {w} not a lane multiple"
    wt = math.gcd(w, _CROSS_WORDS[shard_major])
    gt, rt = min(g_n, _CROSS_ROWS[0]), min(r_n, _CROSS_ROWS[2])
    mt = 0 if mid is None else min(m_n, _CROSS_ROWS[1])
    pre = gt * max(mt, 1)  # prefixes per grid step
    n_g, n_m, n_r = pl.cdiv(g_n, gt), pl.cdiv(m_n, max(mt, 1)), pl.cdiv(r_n, rt)
    pairs = pre * rt
    if shard_major:
        # [rows, S, W] seen as [S, rows, W]: no data moves for an operand
        # the device keeps shard-major
        def view(x):
            return x.transpose(1, 0, 2)

        def spec(t, axis):
            return pl.BlockSpec((None, t, wt), lambda *i: (i[0], i[axis], i[4]))

        filt_view = None if filt is None else filt[:, None, :]
        filt_spec = pl.BlockSpec((None, 1, wt), lambda s, g, m, r, j: (s, 0, j))
        kernel, n_s = _cross_kernel_shard_major, s_n
        out_block, lanes = (None, None, rt, _LANES), _LANES
        assert pre <= _LANES
    else:
        def view(x):
            return x

        def spec(t, axis):
            return pl.BlockSpec(
                (t, _SUBLANES, wt), lambda *i: (i[axis], i[0], i[4])
            )

        filt_view = filt
        filt_spec = pl.BlockSpec((_SUBLANES, wt), lambda s, g, m, r, j: (s, j))
        kernel, n_s = _cross_kernel_row_major, pl.cdiv(s_n, _SUBLANES)
        lanes = pl.cdiv(pairs, _LANES) * _LANES
        out_block = (None, None, _SUBLANES, lanes)
    in_specs, args = [spec(gt, 1)], [view(acc)]
    if filt is not None:
        in_specs.append(filt_spec)
        args.append(filt_view)
    if mt:
        in_specs.append(spec(mt, 2))
        args.append(view(mid))
    in_specs.append(spec(rt, 3))
    args.append(view(planes))
    out = pl.pallas_call(
        functools.partial(kernel, gt, mt, rt, filt is not None),
        out_shape=jax.ShapeDtypeStruct(
            (n_g * n_m * n_r, n_s) + out_block[2:], jnp.int32
        ),
        grid=(n_s, n_g, n_m, n_r, w // wt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            out_block, lambda s, g, m, r, j: ((g * n_m + m) * n_r + r, s, 0, 0)
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 4 + ("arbitrary",),
            vmem_limit_bytes=_CROSS_VMEM_BYTES,
        ),
        name="cross_counts",
    )(*[a.astype(jnp.uint32) for a in args])
    # per-tile counts -> [G, M, R, S]: a few MB at most
    if shard_major:  # [tile, S, rt, prefix]
        out = out[..., :pre].transpose(0, 3, 2, 1)
    else:  # [tile, S/8, 8, pair]
        out = out.reshape(-1, n_s * _SUBLANES, lanes)[:, :s_n, :pairs]
        out = out.reshape(-1, s_n, pre, rt).transpose(0, 2, 3, 1)
    out = out.reshape(n_g, n_m, n_r, gt, pre // gt, rt, s_n)
    out = out.transpose(0, 3, 1, 4, 2, 5, 6).reshape(
        n_g * gt, n_m * (pre // gt), n_r * rt, s_n
    )[:g_n, :m_n, :r_n].astype(jnp.uint32)
    return out if mt else out[:, 0]


@functools.partial(jax.jit, static_argnames=("shard_major",))
def _cross_counts_vmem(acc, planes, mid, filt, shard_major):
    """The one jitted entry. acc, planes and mid (or None) are TUPLES of
    per-extent parts that line up span for span along the shard axis,
    `shard_major` the kernel body of each extent; filt [S, W] is whole and
    sliced per extent here. One `pallas_call` per extent inside the one
    program; only the per-shard counts are concatenated."""
    outs, lo = [], 0
    for e, (a, p) in enumerate(zip(acc, planes)):
        hi = lo + a.shape[1]
        outs.append(
            _cross_counts_extent(
                a, p, None if mid is None else mid[e],
                None if filt is None else filt[lo:hi], shard_major[e],
            )
        )
        lo = hi
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)


def _shard_major(x) -> bool:
    """Whether the device keeps stack x [rows, S, W] with the shard axis
    outermost (read from the array; host arrays and tracers: no)."""
    layout = getattr(getattr(x, "format", None), "layout", None)
    order = getattr(layout, "major_to_minor", None)
    return order is not None and tuple(order)[0] == 1


def _parts(x) -> Optional[tuple]:
    return x if x is None or isinstance(x, tuple) else (x,)


def cross_counts(  # dispatch-ok: wrapper; callers serialize (run_serialized)
    acc, planes, mid=None, filt=None, shard_major=None
) -> jnp.ndarray:
    """GroupBy cross tally in one pass over its rows.

    acc uint32[G, S, W] x planes uint32[R, S, W] -> per-shard counts
    uint32[G, R, S] of popcount(acc[g] & planes[r]); with mid uint32[M, S, W]
    the last prefix level is formed in VMEM, never in HBM, and the result
    is uint32[G, M, R, S] of popcount(acc[g] & mid[m] & planes[r]). filt
    uint32[S, W] is one more AND on the acc tiles. Each grid step brings
    its tile of every operand row into VMEM once and forms the whole cross
    there; only the counts are written.

    acc, planes and mid are each one array or the tuple of its resident
    per-extent parts `[rows, S_e, W]` (hbm/residency.py, `parts=True`),
    the same spans in each: the parts are read where they are, one launch
    per extent inside one program, and no `[rows, S, W]` stack is written.
    `shard_major` names the kernel body — one bool for every extent or one
    per extent; left None each extent follows the layout of its own
    candidate rows."""
    acc, planes, mid = _parts(acc), _parts(planes), _parts(mid)
    if shard_major is None:
        shard_major = tuple(_shard_major(p) for p in planes)
    elif isinstance(shard_major, bool):
        shard_major = (shard_major,) * len(planes)
    return _cross_counts_vmem(
        acc, planes, mid, filt, shard_major=tuple(shard_major)
    )


# ---------------------------------------------------------------------------
# The group tally: counts of LISTED groups, and of their value planes
# ---------------------------------------------------------------------------
# `cross_counts` tallies a whole cross. A GroupBy that carries an aggregate
# knows its live groups (or, under a filter, the few rows of each dimension
# that the filter leaves) and owes each of them one count per BSI plane of
# the value field: work that follows the listed groups, not the cross. Here
# every dimension's rows, the filter and the planes come into VMEM once per
# tile, all rows of a dimension as they are resident (no row is selected or
# written in HBM), and a table of row indices in SMEM says which rows make
# each group.
_GROUP_ROWS_BYTES = 12 << 20  # one buffer of row tiles; the pipeline keeps two
_GROUP_PARTS = 31  # plane partials carried beside the group's own count
GROUP_MAX_GROUPS = 2048  # groups per launch: the index table lives in SMEM


def group_words(rows_total: int, w: int) -> int:
    """Words of a row per grid step: the most (a power of two dividing w,
    at most 16384: every step ends with a lane reduction per count) that
    lets one 8-shard tile of every row the tally reads fit its buffer; 0
    when not even one lane column fits (too many rows for the kernel)."""
    wt = math.gcd(w, 16384)
    while wt >= _LANES and rows_total * _SUBLANES * wt * 4 > _GROUP_ROWS_BYTES:
        wt //= 2
    return wt if wt >= _LANES and w % wt == 0 else 0


def _group_kernel(n_dims, p_n, q_pad, g_pad, has_filt, mask_row,
                  idx_ref, live_ref, *refs):
    """One grid step on [rows, 8 shards, wt words] blocks. For each listed
    group g (a loop of `live` rounds, not unrolled): t = the AND of row
    idx[d, g] of every dimension d (and the filter); lane g * q_pad of the
    out block gains popcount(t), lane g * q_pad + 1 + p popcount(t & v &
    planes[p]) with v = planes[mask_row] (no mask: v is left out). A vreg
    holds the same 128 words of 8 shards of one row, as in the row-major
    cross body."""
    refs = list(refs)
    dims = [refs.pop(0) for _ in range(n_dims)]
    filt_ref = refs.pop(0) if has_filt else None
    planes_ref = refs.pop(0) if p_n else None
    (out_ref,) = refs

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    steps = dims[0].shape[-1] // _LANES
    zero = jnp.zeros((_SUBLANES, _LANES), jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, zero.shape, 1)
    # counts of one group in slabs the carried partials hold: the group's
    # own count rides in the first
    qs = list(range(1 + p_n))
    slabs = [qs[i : i + 1 + _GROUP_PARTS] for i in range(0, len(qs), 1 + _GROUP_PARTS)]

    def group(g, carry):
        rows = [idx_ref[d * g_pad + g] for d in range(n_dims)]
        at = g * q_pad
        for slab in slabs:

            def body(j, parts, slab=slab):
                sl = _lane_slice(j)
                t = dims[0][rows[0], :, sl]
                for ref, i in zip(dims[1:], rows[1:]):
                    t = t & ref[i, :, sl]
                if has_filt:
                    t = t & filt_ref[:, sl]
                tv = t if mask_row is None else t & planes_ref[mask_row, :, sl]
                out = []
                for k, q in enumerate(slab):
                    x = t if q == 0 else tv & planes_ref[q - 1, :, sl]
                    out.append(
                        parts[k]
                        + jax.lax.population_count(x.astype(jnp.int32))
                    )
                return tuple(out)

            parts = _word_loop(steps, body, (zero,) * len(slab))
            tile = zero
            for k, q in enumerate(slab):
                col = jnp.sum(parts[k], axis=1, keepdims=True)
                tile = jnp.where(lane == at % _LANES + q, col, tile)
            chunk = pl.multiple_of(at // _LANES * _LANES, _LANES)
            out_ref[:, pl.ds(chunk, _LANES)] += tile
        return carry

    jax.lax.fori_loop(0, live_ref[0], group, 0)


@functools.partial(jax.jit, static_argnames=("mask_row",))
def _group_counts_extent(dims, idx, live, planes, filt, mask_row):
    """One `pallas_call` over one extent: dims a tuple of [R_d, S_e, W],
    idx int32[n_dims, G] (G a power of two), live int32[1] (groups listed:
    the rest of the table is padding and is not tallied), planes [P, S_e,
    W] or None, filt [S_e, W] or None -> uint32[G, 1 + P, S_e]."""
    n_dims, g_pad = idx.shape
    _, s_n, w = dims[0].shape
    p_n = 0 if planes is None else planes.shape[0]
    rows_total = sum(d.shape[0] for d in dims) + p_n + (filt is not None)
    wt = group_words(rows_total, w)
    assert wt, f"{rows_total} rows of {w} words do not fit the group tally"
    q_pad = 1 << p_n.bit_length()  # > p_n: the count and the planes
    assert q_pad <= _LANES and g_pad <= GROUP_MAX_GROUPS
    lanes = max(g_pad * q_pad, _LANES)
    n_s = pl.cdiv(s_n, _SUBLANES)

    def rows_spec(x):
        return pl.BlockSpec(
            (x.shape[0], _SUBLANES, wt), lambda s, j, *_: (0, s, j)
        )

    in_specs, args = [rows_spec(d) for d in dims], list(dims)
    if filt is not None:
        in_specs.append(pl.BlockSpec((_SUBLANES, wt), lambda s, j, *_: (s, j)))
        args.append(filt)
    if p_n:
        in_specs.append(rows_spec(planes))
        args.append(planes)
    out = pl.pallas_call(
        functools.partial(
            _group_kernel, n_dims, p_n, q_pad, g_pad, filt is not None,
            mask_row,
        ),
        out_shape=jax.ShapeDtypeStruct((n_s, _SUBLANES, lanes), jnp.int32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_s, w // wt),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (None, _SUBLANES, lanes), lambda s, j, *_: (s, 0, 0)
            ),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_CROSS_VMEM_BYTES,
        ),
        name="group_counts",
    )(idx.reshape(-1), live, *[a.astype(jnp.uint32) for a in args])
    # [S/8, 8, G * q_pad] -> [G, 1 + P, S]
    out = out.reshape(n_s * _SUBLANES, lanes)[:s_n, : g_pad * q_pad]
    out = out.reshape(s_n, g_pad, q_pad)[:, :, : 1 + p_n]
    return out.transpose(1, 2, 0).astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("mask_row",))
def _group_counts_vmem(dims, idx, live, planes, filt, mask_row):
    """The jitted entry: every dimension (and planes) a TUPLE of per-extent
    parts that line up span for span, filt [S, W] whole and sliced per
    extent here; one `pallas_call` per extent inside the one program, the
    per-shard counts concatenated."""
    outs, lo = [], 0
    for e in range(len(dims[0])):
        hi = lo + dims[0][e].shape[1]
        outs.append(
            _group_counts_extent(
                tuple(d[e] for d in dims), idx, live,
                None if planes is None else planes[e],
                None if filt is None else filt[lo:hi], mask_row,
            )
        )
        lo = hi
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)


def group_counts(  # dispatch-ok: wrapper; callers serialize (run_serialized)
    dims, idx, live, planes=None, filt=None, mask_row=None
) -> jnp.ndarray:
    """Counts of listed groups in one pass over the rows they name.

    dims[d] uint32[R_d, S, W], one array or the tuple of its resident
    per-extent parts; idx int32[n_dims, G] the row of each dimension that
    makes group g, of which the first `live` (int32[1]) are tallied;
    planes uint32[P, S, W] (or parts) the value field's planes; filt
    uint32[S, W]. Returns uint32[G, 1 + P, S]: [g, 0] the per-shard count
    of AND_d dims[d][idx[d, g]] (& filt), [g, 1 + p] that of the same &
    planes[mask_row] & planes[p] (mask_row None: no mask). Every row is
    read from HBM once per tally where it lies; nothing but the counts is
    written."""
    dims = tuple(_parts(d) for d in dims)
    return _group_counts_vmem(
        dims, idx, live, _parts(planes), filt, mask_row=mask_row
    )
