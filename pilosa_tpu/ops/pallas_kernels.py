"""The hand-written TPU kernels, both GroupBy's: the cross tally
(`cross_counts`) and, for a GroupBy that carries an aggregate, the tally
of listed groups and their value planes (`group_counts`, at the end of
this file; `group_counts_mesh` runs it under shard_map on every chip of a
mesh the stacks are sharded over).

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
from jax.sharding import PartitionSpec as P

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
# Words of a row per grid step. The row-major body ends every grid step
# with a lane reduction per pair, so it wants long steps: 16384 words is
# what its largest blocks (8 + 8 + 16 rows x 8 shards, double-buffered =
# 32 MB) leave room for under the VMEM limit (one [8|16, 256, W] extent
# of Q4, unrolled body: 12.6 ms at 4096, 11.8 at 8192, 11.3 at 16384; my
# chip runs, PR 32). Since PR 40 the reductions of a pass of 16 pairs
# take 174 bundles against 1 728 in its word loop (v5e bundle dump).
_CROSS_WORDS = {True: 32768, False: 16384}
_CROSS_VMEM_BYTES = 40 << 20
_SUBLANES = 8
_LANES = 128
# The word loop's live set, in the 64 vregs of a v5e core: one partial
# vreg per pair a pass carries, the tiles one side of its pairs holds
# through a round (row-major: the prefixes; shard-major: mid & candidate
# slab), one tile of the other side, and the temporaries of the schedule.
# Until PR 40 a pass carried 32 partials through rounds of one 128-word
# column: at taxi Q4's shapes 27-28 spill stores and 1.72 (row-major) /
# 1.53 (shard-major) bundles a pair-vreg, where AND, popcount and add
# need 0.75 (v5e bundle dump). 16 partials, and partials + tiles within
# 32 vregs, leave both loops without a spill at every served shape
# (0.84 / 0.98 at Q4's; 40 vregs spilled 5-14 stores an iteration).
_CROSS_PARTS = 16
_CROSS_LIVE = 32
_CROSS_COLS = 8  # most 128-word columns of one tile load
# Columns of one loop iteration, in rounds of a tile each, so that the
# next round's loads are scheduled under this one's VALU work (Pallas
# lowers no partial unroll of a loop): 0.93 -> 0.84 bundles a pair-vreg
# row-major; 32 columns read 0.80 for twice the code to trace.
_CROSS_SPAN = 16


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


def cross_tile(gt: int, m_n: int, rt: int, steps: int,
               shard_major: bool) -> tuple:
    """(sub, cols): what a pass of the cross body's word loop carries, and
    the 128-word columns of the tile every operand row is loaded as (its
    address worked out once a tile, not once a vreg). Row-major: a pass
    is `sub` prefixes x all rt candidate rows, the prefix tiles held
    through a round, the candidate rows streamed. Shard-major: a pass is
    all gt acc rows x `sub` mid rows x the 8-row candidate slabs, the
    tiles mid & slab held, the acc rows streamed. The most pairs a pass
    (`_CROSS_PARTS`), then the widest tile, that keep partials + held
    tiles + one streamed tile within `_CROSS_LIVE` vregs (a pass grows
    while tiles of half the widest width still fit)."""
    if shard_major:
        held = pl.cdiv(rt, _SUBLANES)
        pairs, most = gt * held, m_n
    else:
        held, pairs, most = 1, rt, gt * m_n

    def live(sub, cols):
        return sub * (pairs + held * cols) + cols

    cols = min(steps, _CROSS_COLS)
    sub = 1
    while (sub < most and (sub + 1) * pairs <= _CROSS_PARTS
           and live(sub + 1, min(cols, _CROSS_COLS // 2)) <= _CROSS_LIVE):
        sub += 1
    while cols > 1 and live(sub, cols) > _CROSS_LIVE:
        cols //= 2
    return sub, cols


def _word_loop(steps: int, cols: int, body, init):
    """The loop over a block's tiles of `cols` columns, `_CROSS_SPAN`
    columns an iteration where the block has them."""
    rounds = steps // cols
    u = min(rounds, max(1, _CROSS_SPAN // cols))

    def rounds_of(i, parts):
        for k in range(u):
            parts = body(i * u + k, parts)
        return parts

    return jax.lax.fori_loop(0, rounds // u, rounds_of, init)


def _prefixes(pre, row, acc_ref, filt, mid_ref):
    """The prefix tiles acc[g] (& filt) (& mid[m]) of every (g, m) of
    `pre`; `row(ref, i)` loads row i's tile, each operand row once (a row
    index is a Python int or a traced scalar; the same index object is the
    same row)."""

    def key(i):
        return i if isinstance(i, int) else id(i)

    def rows(ref, idx):
        return {k: row(ref, i) for k, i in {key(i): i for i in idx}.items()}

    accs = rows(acc_ref, [g for g, _ in pre])
    if filt is not None:
        accs = {g: a & filt for g, a in accs.items()}
    if mid_ref is None:
        return [accs[key(g)] for g, _ in pre]
    mids = rows(mid_ref, [m for _, m in pre])
    return [accs[key(g)] & mids[key(m)] for g, m in pre]


def _count(out, i, x, cols):
    """out[i] += popcount(x), the tile's `cols` 128-word columns added into
    the one partial vreg."""
    c = jax.lax.population_count(x.astype(jnp.int32))
    for u in range(cols):
        out[i] = out[i] + c[:, u * _LANES : (u + 1) * _LANES]


def _lane_sum(part):
    """A partial's counts summed across its lanes, float32 [n, 1]: one
    reduction where an int32 sum is two (of 16-bit halves), and exact: a
    lane holds at most 32 x 256 and the sum at most 2**20."""
    return jnp.sum(part.astype(jnp.float32), axis=1, keepdims=True)


def _cross_kernel_shard_major(gt, mt, rt, has_filt, *refs):
    """One grid step on [rows, wt words] blocks of ONE shard: a vreg holds
    128 words of 8 candidate rows, and an acc or mid row is broadcast over
    the sublanes. A pass (`cross_tile`; a loop over the mid rows, not
    unrolled) holds the tiles mid[m] (& filt) & slab for its mid rows and
    every 8-row candidate slab, each mid row broadcast once, and streams
    every acc row through them, so a broadcast serves all the pairs of its
    row. Partials [8 rows, 128 lanes] per (g, m, slab) are carried through
    the word loop and reduced across lanes once, into lane g * M + m of
    the resident out block [rt, 128]. mt == 0: no mid level. A ragged last
    pass tallies its last mid row again and does not write it. At taxi
    Q4's tail (8 x 8 x 16 rows) a pass is 8 acc rows x 1 mid row x 2 slabs
    in tiles of 4 columns: 250 bundles an iteration of four rounds, 256
    pair-vregs (0.98), no spill (v5e bundle dump); a row's broadcast is a
    sublane permute per column, 0.56 of a pair-vreg's 3.8 VALU ops."""
    acc_ref, filt_ref, mid_ref, planes_ref, out_ref = _cross_refs(
        mt, has_filt, refs
    )
    steps = planes_ref.shape[-1] // _LANES
    m_n = max(mt, 1)
    sub, cols = cross_tile(gt, m_n, rt, steps, True)
    width = cols * _LANES
    slabs = [(lo, min(lo + _SUBLANES, rt)) for lo in range(0, rt, _SUBLANES)]
    zeros = tuple(jnp.zeros((hi - lo, _LANES), jnp.int32) for lo, hi in slabs)
    held_n = sub * len(slabs)

    def tally(i, carry):
        m0 = i * sub
        ms = [jnp.minimum(m0 + k, m_n - 1) for k in range(sub)]

        def body(j, parts):
            sl = pl.ds(pl.multiple_of(j * width, width), width)
            f = filt_ref[:, sl] if has_filt else None
            ps = [planes_ref[lo:hi, sl] for lo, hi in slabs]
            held = []
            for m in ms:
                h = f
                if mt:
                    row = mid_ref[pl.ds(m, 1), sl]
                    h = row if h is None else h & row
                held += [p if h is None else h & p for p in ps]
            out = list(parts)
            for g in range(gt):
                row = acc_ref[g : g + 1, sl]
                for k, h in enumerate(held):
                    _count(out, g * held_n + k, row & h, cols)
            return tuple(out)

        parts = _word_loop(steps, cols, body, zeros * (gt * held_n))
        for v, (lo, hi) in enumerate(slabs):
            lane = jax.lax.broadcasted_iota(jnp.int32, (hi - lo, _LANES), 1)
            tile = jnp.zeros((hi - lo, _LANES), jnp.float32)
            for g in range(gt):
                for k, m in enumerate(ms):
                    col = _lane_sum(parts[g * held_n + k * len(slabs) + v])
                    to = jnp.where(m0 + k < m_n, g * m_n + m, -1)
                    tile = jnp.where(lane == to, col, tile)
            out_ref[lo:hi, :] += tile.astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, pl.cdiv(m_n, sub), tally, 0)


def _cross_kernel_row_major(gt, mt, rt, has_filt, *refs):
    """One grid step on [rows, 8 shards, wt words] blocks: a vreg holds the
    same 128 words of 8 shards of one row, so a pair's partial
    [8 shards, 128 lanes] reduces across lanes to 8 per-shard counts, put
    in lane `pair` of the resident out block [8, pairs]. A pass
    (`cross_tile`) forms the tiles of its prefixes once a round and
    streams every candidate row through them. The passes are a LOOP over
    one pass's code, not unrolled: unrolled, one Q4 program of four
    extents took 6 s to trace and lower and 22 s inside a server whose
    heap holds an index; so 1 s and 5 s, for a 4 % slower tally (40.5 ->
    42.1 ms; my chip runs, PR 32). Candidate rows keep static indices: with
    those dynamic too it is 48 ms. A ragged last pass tallies its last
    prefix again and does not write it. At taxi Q4's shapes a pass is 1
    prefix x 16 rows in tiles of 8 columns: 216 bundles an iteration of
    two rounds, 256 pair-vregs (0.84), no spill (v5e bundle dump)."""
    acc_ref, filt_ref, mid_ref, planes_ref, out_ref = _cross_refs(
        mt, has_filt, refs
    )
    steps = planes_ref.shape[-1] // _LANES
    m_n = max(mt, 1)
    n_pre = gt * m_n
    sub_p, cols = cross_tile(gt, m_n, rt, steps, False)
    width = cols * _LANES
    window = sub_p * rt  # lanes of the out block one pass writes: <= 16

    def group(i, carry):
        p0 = i * sub_p
        ps = [jnp.minimum(p0 + k, n_pre - 1) for k in range(sub_p)]
        if m_n % sub_p == 0:  # one acc row per group: loaded once
            g = p0 // m_n
            pre = [(g, p % m_n) for p in ps]
        else:
            pre = [(p // m_n, p % m_n) for p in ps]

        def body(j, parts):
            sl = pl.ds(pl.multiple_of(j * width, width), width)
            ts = _prefixes(
                pre, lambda ref, i: ref[i, :, sl], acc_ref,
                filt_ref[:, sl] if has_filt else None, mid_ref,
            )
            out = list(parts)
            for r in range(rt):
                p = planes_ref[r, :, sl]
                for k, t in enumerate(ts):
                    _count(out, k * rt + r, t & p, cols)
            return tuple(out)

        zero = jnp.zeros((_SUBLANES, _LANES), jnp.int32)
        parts = _word_loop(steps, cols, body, (zero,) * (sub_p * rt))
        # the group's pairs are lanes [p0 * rt, (p0 + sub_p) * rt) of the
        # out block: gathered in registers (a store per pair to a lane
        # chunk found at run time is a chain of dependent
        # read-modify-writes), then added to the one or two 128-lane chunks
        # the window touches
        lane = jax.lax.broadcasted_iota(jnp.int32, zero.shape, 1)
        first = p0 * rt // _LANES
        tiles = [jnp.zeros(zero.shape, jnp.float32)] * (
            1 if _LANES % window == 0 else 2
        )
        for k in range(sub_p):
            for r in range(rt):
                col = _lane_sum(parts[k * rt + r])
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
            out_ref[:, pl.ds(pl.multiple_of(at, _LANES), _LANES)] += t.astype(
                jnp.int32
            )
        return carry

    jax.lax.fori_loop(0, pl.cdiv(n_pre, sub_p), group, 0)


def _cross_block(acc, planes, mid, shard_major: bool) -> tuple:
    """(gt, mt, rt, wt): the acc, mid (0: none) and candidate rows one
    grid step of an extent holds, and the words of a row it reads."""
    gt = min(acc.shape[0], _CROSS_ROWS[0])
    mt = 0 if mid is None else min(mid.shape[0], _CROSS_ROWS[1])
    rt = min(planes.shape[0], _CROSS_ROWS[2])
    return gt, mt, rt, math.gcd(acc.shape[-1], _CROSS_WORDS[shard_major])


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
    gt, mt, rt, wt = _cross_block(acc, planes, mid, shard_major)
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
    return _cross_counts_vmem(
        acc, planes, mid, filt, shard_major=_bodies(planes, shard_major)
    )


def _bodies(planes: tuple, shard_major) -> tuple:
    """The kernel body of each extent, as `cross_counts` takes it."""
    if shard_major is None:
        return tuple(_shard_major(p) for p in planes)
    if isinstance(shard_major, bool):
        return (shard_major,) * len(planes)
    return tuple(shard_major)


def cross_tiles(acc, planes, mid=None, shard_major=None) -> str:
    """The word loop's tile of each kernel body `cross_counts` runs over
    these operands (given as it takes them), as "<prefixes>x<rows>x<cols>"
    — the prefixes and candidate rows a pass tallies (`cross_tile`) and
    the tile's 128-word columns — in extent order, each once, joined by
    ",". Taxi Q4's four extents: "1x16x8,8x16x4"."""
    acc, planes, mid = _parts(acc), _parts(planes), _parts(mid)
    tags = []
    for e, body in enumerate(_bodies(planes, shard_major)):
        gt, mt, rt, wt = _cross_block(
            acc[e], planes[e], None if mid is None else mid[e], body
        )
        sub, cols = cross_tile(gt, max(mt, 1), rt, wt // _LANES, body)
        tag = f"{gt * sub if body else sub}x{rt}x{cols}"
        if tag not in tags:
            tags.append(tag)
    return ",".join(tags)


# ---------------------------------------------------------------------------
# The group tally: counts of LISTED groups, and of their value planes
# ---------------------------------------------------------------------------
# `cross_counts` tallies a whole cross. A GroupBy that carries an aggregate
# knows its live groups (or, under a filter, the few rows of each dimension
# that the filter leaves) and owes each of them one count per BSI plane of
# the value field: work that follows the listed groups, not the cross. Here
# every dimension's rows, the filter and the planes come into VMEM once per
# tile, and a table of row indices in SMEM says which rows make each group.
# A dimension comes as it is resident, or, where its resident rows would
# narrow the tile below `GROUP_FULL_WORDS`, cut first to the rows the table
# names (`compacted_words`: a copy of those rows, written once a tally).
_GROUP_ROWS_BYTES = 12 << 20  # one buffer of row tiles; the pipeline keeps two
GROUP_MAX_GROUPS = 2048  # groups per launch: the index table lives in SMEM
# A block's row indices are held in scalar registers through its word loop
# (32 of them, the loop's own addresses among them).
_GROUP_ROW_SCALARS = 16
# 128-word columns a round of the word loop covers: its unrolled bodies,
# each row loaded as ONE tile of that width, so that the address of a load
# is worked out once for 8 vregs. Loaded one vreg at a time, each load
# spent three scalar ops on its address and two scalar slots a bundle bound
# the loop (2.0 bundles a pair where its VALU work needs 1.2; v5e bundle
# dump, PR 38); a tile of 8 leaves it VALU-bound (0.88 a pair).
_GROUP_COLS = 8
_GROUP_PARTS = 32  # partial vregs a block carries through its word loop
# The narrowest tile at which the tally is bound by its word loop's VALU
# work with the row loads hidden behind it (0.88 bundles a pair-vreg at
# 4 096 words, v5e bundle dump and chip). Every grid step ends with one lane
# reduction per count, ~9 bundles against ~3.5 of loop work a pair at 512
# words: a table whose resident rows leave a narrower tile has its
# dimensions cut to the rows it names (`compacted_words`).
GROUP_FULL_WORDS = 4096


def group_words(rows_total: int, w: int) -> int:
    """Words of a row per grid step: the most (a power of two dividing w,
    at most 16384: every step ends with a lane reduction per count) that
    lets one 8-shard tile of every row the tally reads fit its buffer; 0
    when not even one lane column fits (too many rows for the kernel)."""
    wt = math.gcd(w, 16384)
    while wt >= _LANES and rows_total * _SUBLANES * wt * 4 > _GROUP_ROWS_BYTES:
        wt //= 2
    return wt if wt >= _LANES and w % wt == 0 else 0


def compact_rows(named: int) -> int:
    """Rows of a dimension cut to the `named` rows its table names: a power
    of two of at least 8 (the last named row repeated), so that tables of
    1 to 8 rows compile one shape."""
    return max(_SUBLANES, 1 << (named - 1).bit_length())


def compacted_words(resident, named, other: int, w: int) -> int:
    """The tile of a group tally whose dimensions are cut to the rows its
    table names — dimension d, of which the table names named[d] of its
    resident[d] rows, to `compact_rows(named[d])` rows where those are
    fewer — beside `other` rows read whole (planes, filter), at w words a
    row; 0 where the tally reads the resident rows: no dimension would be
    cut, the resident rows allow a `GROUP_FULL_WORDS` tile already, or the
    cut would not widen it."""
    whole = group_words(sum(resident) + other, w)
    kept = [min(r, compact_rows(n)) for r, n in zip(resident, named)]
    if whole >= GROUP_FULL_WORDS or kept == list(resident):
        return 0
    cut = group_words(sum(kept) + other, w)
    return cut if cut > whole else 0


def group_block(n_dims: int, p_n: int) -> tuple:
    """(gb, pb): the group tally takes its groups gb at a time, each plane
    tile loaded once for the gb of them, and carries pb counts of each
    group (its own and up to pb - 1 planes) through a word loop: gb x pb
    partials within `_GROUP_PARTS`, the block's row indices
    within `_GROUP_ROW_SCALARS`, its counts within one 128-lane chunk of
    the out block. The (1 + p_n) counts are cut into slabs of equal size.
    A deep field's tally keeps one group a block: with 8-column tiles its
    loop is bound by VALU work (AND, popcount, add a pair), which a block
    does not share, and 2 or 4 groups of 27 counts in slabs of 14 or 7
    were 3-24 % slower than one (my chip run, PR 38)."""
    q = 1 + p_n
    q_pad = 1 << p_n.bit_length()
    gb = 1
    while (2 * gb * q <= _GROUP_PARTS and 2 * gb * n_dims <= _GROUP_ROW_SCALARS
           and 2 * gb * q_pad <= _LANES):
        gb *= 2
    slabs = pl.cdiv(q, max(1, _GROUP_PARTS // gb))
    return gb, pl.cdiv(q, slabs)


def _group_kernel(n_dims, p_n, q_pad, g_pad, has_filt, mask_row,
                  idx_ref, live_ref, *refs):
    """One grid step on [rows, 8 shards, wt words] blocks. The listed
    groups are taken gb at a time (a loop of cdiv(live, gb) rounds, not
    unrolled; `group_block`). For group g, t = the AND of row idx[d, g] of
    every dimension d (and the filter); lane g * q_pad of the out block
    gains popcount(t), lane g * q_pad + 1 + p popcount(t & v & planes[p])
    with v = planes[mask_row] (no mask: v is left out). Within a block the
    filter, the mask and every plane tile are loaded once for its gb
    groups. A vreg holds the same 128 words of 8 shards of one row, as in
    the row-major cross body. A ragged last block tallies its last group
    again and does not write it."""
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
    gb, pb = group_block(n_dims, p_n)
    qs = list(range(1 + p_n))
    slabs = [qs[i : i + pb] for i in range(0, len(qs), pb)]
    cols = min(steps, _GROUP_COLS)  # 128-word columns of one load
    width = cols * _LANES
    live = live_ref[0]

    def block(b, carry):
        g0 = b * gb
        rows = [
            [idx_ref[d * g_pad + jnp.minimum(g0 + n, live - 1)]
             for d in range(n_dims)]
            for n in range(gb)
        ]
        at = g0 * q_pad  # the block's counts: lanes [at, at + gb * q_pad)
        for slab in slabs:

            def body(j, parts, slab=slab):
                sl = pl.ds(pl.multiple_of(j * width, width), width)
                f = filt_ref[:, sl] if has_filt else None
                v = None
                if mask_row is not None and slab[-1]:
                    v = planes_ref[mask_row, :, sl]
                out = list(parts)

                def add(n, k, x):
                    c = jax.lax.population_count(x.astype(jnp.int32))
                    for u in range(cols):
                        out[n * len(slab) + k] += c[:, u * _LANES : (u + 1) * _LANES]

                tvs = []
                for n, r in enumerate(rows):
                    t = dims[0][r[0], :, sl]
                    for ref, i in zip(dims[1:], r[1:]):
                        t = t & ref[i, :, sl]
                    if f is not None:
                        t = t & f
                    if slab[0] == 0:
                        add(n, 0, t)
                    if slab[-1]:
                        tvs.append(t if v is None else t & v)
                for k, q in enumerate(slab):
                    if q:
                        p = planes_ref[q - 1, :, sl]
                        for n, tv in enumerate(tvs):
                            add(n, k, tv & p)
                return tuple(out)

            parts = jax.lax.fori_loop(
                0, steps // cols, body, (zero,) * (gb * len(slab)))
            tile = zero
            for n in range(gb):
                for k, q in enumerate(slab):
                    col = jnp.sum(parts[n * len(slab) + k], axis=1, keepdims=True)
                    to = jnp.where(g0 + n < live, at % _LANES + n * q_pad + q, -1)
                    tile = jnp.where(lane == to, col, tile)
            chunk = pl.multiple_of(at // _LANES * _LANES, _LANES)
            out_ref[:, pl.ds(chunk, _LANES)] += tile
        return carry

    jax.lax.fori_loop(0, pl.cdiv(live, gb), block, 0)


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


def _cut(dims, rows):
    """Each dimension [R_d, S, W] cut to its rows rows[d] int32[L_d] ->
    [L_d, S, W], a copy written row by row (the table indexes those rows);
    a dimension whose rows[d] is None, or all of them where rows is None,
    as it is."""
    if rows is None:
        return dims
    return tuple(
        d if r is None else jnp.stack(
            [jax.lax.dynamic_index_in_dim(d, r[i], keepdims=False)
             for i in range(r.shape[0])])
        for d, r in zip(dims, rows)
    )


@functools.partial(jax.jit, static_argnames=("mask_row",))
def _group_counts_vmem(dims, idx, live, planes, filt, mask_row, rows=None):
    """The jitted entry: every dimension (and planes) a TUPLE of per-extent
    parts that line up span for span, filt [S, W] whole and sliced per
    extent here, rows None or a tuple of int32[L_d] or None a dimension
    (each extent's dimensions cut to them, `_cut`); one `pallas_call` per
    extent inside the one program, the per-shard counts concatenated."""
    outs, lo = [], 0
    for e in range(len(dims[0])):
        hi = lo + dims[0][e].shape[1]
        outs.append(
            _group_counts_extent(
                _cut(tuple(d[e] for d in dims), rows), idx, live,
                None if planes is None else planes[e],
                None if filt is None else filt[lo:hi], mask_row,
            )
        )
        lo = hi
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)


def group_counts(  # dispatch-ok: wrapper; callers serialize (run_serialized)
    dims, idx, live, planes=None, filt=None, mask_row=None, rows=None
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
    written. With rows (a tuple of int32[L_d], or None for a dimension
    read as it is) a dimension is first cut to its rows rows[d], written
    once as a copy, and idx indexes those."""
    dims = tuple(_parts(d) for d in dims)
    return _group_counts_vmem(
        dims, idx, live, _parts(planes), filt, mask_row=mask_row, rows=rows
    )


@functools.partial(jax.jit, static_argnames=("mask_row", "mesh"))
def _group_counts_mesh(dims, idx, live, planes, filt, mask_row, mesh,
                       rows=None):
    """The group tally of stacks sharded over `mesh`: dims [R_d, S, W] and
    planes [P, S, W] as P(None, "shards", "cols"), filt [S, W] as
    P("shards", "cols"), the table, live count and rows (None, or each
    dimension's rows to cut it to) on every device. Each chip cuts its
    dimensions' blocks to those rows (the row axis is not sharded: no
    collective), runs the one-extent kernel on its [R, S / shards,
    W / cols] block, sums its per-shard counts on the device, and the
    "cols" halves are added by a psum -> uint32[G, 1 + P, k] sharded over
    "shards", k the mesh's "shards" rows, each the counts of its shards.
    Exact in uint32 while a chip row's shards hold fewer than 2^32 bits
    (4 095 shards of 2^20); a wider block keeps its per-shard counts
    (k = S), which the host sums in uint64. Either way `_read_groups` sums the last axis; the
    per-shard form of 1 024 listed groups x 27 counts over 288 shards is
    32 MB, whose uint64 sum alone took 79 ms on a v5e host."""
    stack, row = P(None, "shards", "cols"), P("shards", "cols")
    _, s, w = dims[0].shape
    per_chip = s // mesh.shape["shards"] * w * 32 < 1 << 32
    args = [dims, idx, live]
    specs = [(stack,) * len(dims), P(), P()]
    cut = None if rows is None else tuple(
        None if r is None else P() for r in rows)
    for x, spec in ((rows, cut), (planes, stack), (filt, row)):
        if x is not None:
            args.append(x)
            specs.append(spec)

    def body(dims, idx, live, *rest):
        rest = list(rest)
        r = rest.pop(0) if rows is not None else None
        p = rest.pop(0) if planes is not None else None
        f = rest.pop(0) if filt is not None else None
        out = _group_counts_extent(_cut(dims, r), idx, live, p, f, mask_row)
        if per_chip:
            out = jnp.sum(out, axis=-1, keepdims=True, dtype=jnp.uint32)
        return jax.lax.psum(out, "cols")

    return jax.shard_map(
        body, mesh=mesh, in_specs=tuple(specs),
        out_specs=P(None, None, "shards"), check_vma=False,
    )(*args)


def group_counts_mesh(  # dispatch-ok: wrapper; callers serialize (run_serialized)
    mesh, dims, idx, live, planes=None, filt=None, mask_row=None, rows=None
) -> jnp.ndarray:
    """`group_counts` for operands sharded over `mesh` (each one array, as
    the stacked path places them there): one program, the kernel on every
    chip's block (its dimensions first cut to `rows`, as there), its
    counts summed over the chip's shards and the "cols" halves added on
    the device -> uint32[G, 1 + P, k], k the mesh's "shards" rows
    (`_group_counts_mesh`). The profiler names it
    `jit__group_counts_mesh`."""
    return _group_counts_mesh(
        tuple(_parts(d)[0] for d in dims), idx, live,
        None if planes is None else _parts(planes)[0], filt,
        mask_row=mask_row, mesh=mesh, rows=rows,
    )
