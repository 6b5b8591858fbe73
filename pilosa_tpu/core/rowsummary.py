"""Per-view row summary: the exact cardinality of every non-empty
(row, shard) cell of a view's fragments as one host-side table.

`Rows`, the GroupBy prefetch and the unfiltered `TopN` need, per query,
only what the fragments' row stores and rank caches already know — which
rows are non-empty where, and how large — and that changes only when
somebody writes. Asking each fragment again (lock, read barrier, a Python
call per row) costs 10^4 calls per query at 10^3 shards; the table answers
the same questions with a few vector passes.

Layout: CSR over the view's fragments in shard order. Shard `shards[i]`
owns cells `offsets[i]:offsets[i+1]` of `rids` / `counts`, in the rank
cache's order (count descending, ties by lowest id — core/cache.py
`RankCache.top`). Only non-zero cells are stored, so memory follows the
data (16 B a cell), not rows x shards.

The table is immutable once built. `View.row_summary` owns validity (the
mutation clock and the fragment-set epoch it was built under); a refresh
builds a new table that shares the unchanged shards' cells.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

Cells = Tuple[np.ndarray, np.ndarray, np.ndarray]  # rids, counts, offsets


def cells_of(parts: Sequence[Tuple[np.ndarray, np.ndarray]]) -> Cells:
    """Concatenate per-shard (rids, counts) pairs into one CSR triple."""
    offsets = np.zeros(len(parts) + 1, np.int64)
    if not parts:
        return np.empty(0, np.uint64), np.empty(0, np.uint64), offsets
    np.cumsum([len(p[0]) for p in parts], out=offsets[1:])
    return (
        np.concatenate([p[0] for p in parts]),
        np.concatenate([p[1] for p in parts]),
        offsets,
    )


def head_per_segment(keep: np.ndarray, offsets: np.ndarray, n: int) -> np.ndarray:
    """`keep` with all but the first `n` kept cells of each segment
    cleared (the per-shard n-cut of a rank-ordered pool)."""
    seen = np.cumsum(keep)
    before = np.concatenate(([0], seen))[offsets[:-1]]
    rank = seen - np.repeat(before, np.diff(offsets))  # 1-based among kept
    return keep & (rank <= n)


class RowSummary:
    __slots__ = (
        "epoch", "clock", "frags", "shards", "versions", "exact",
        "rids", "counts", "offsets", "reads",
    )

    def __init__(self, epoch: int, clock: int, frags: list,
                 old: Optional["RowSummary"] = None):
        """Read `frags` (shard order) into a table stamped (epoch, clock).
        With `old` — a table over the SAME fragment objects — only the
        fragments whose version moved are read again."""
        self.epoch = epoch
        self.clock = clock
        self.frags = frags
        if old is None or not frags:
            self.shards = np.fromiter(
                (f.shard for f in frags), np.int64, len(frags)
            )
            parts: list = [None] * len(frags)
            versions = [-1] * len(frags)
            exact = np.zeros(len(frags), bool)
            stale = range(len(frags))
        else:
            self.shards = old.shards
            parts = list(zip(
                np.split(old.rids, old.offsets[1:-1]),
                np.split(old.counts, old.offsets[1:-1]),
            ))
            versions = list(old.versions)
            exact = old.exact.copy()
            stale = [
                i for i, f in enumerate(frags) if f.version != versions[i]
            ]
        for i in stale:
            versions[i], rids, counts, exact[i] = frags[i].row_summary()
            parts[i] = (rids, counts)
        self.reads = len(stale)  # fragments this build read
        self.versions = versions
        self.exact = exact
        self.rids, self.counts, self.offsets = cells_of(parts)

    def positions(self, shard_list) -> np.ndarray:
        """Indices (into `shards`) of the listed shards that have a
        fragment, in the list's order."""
        want = np.asarray(shard_list, np.int64)
        if len(want) == len(self.shards) and np.array_equal(want, self.shards):
            return np.arange(len(want))
        if not len(self.shards):
            return np.empty(0, np.int64)
        pos = np.minimum(np.searchsorted(self.shards, want), len(self.shards) - 1)
        return pos[self.shards[pos] == want]

    def present(self, shard_list) -> List[int]:
        """The listed shards that have a fragment in this view."""
        return self.shards[self.positions(shard_list)].tolist()

    def cells(self, pos: np.ndarray) -> Cells:
        """CSR triple of the shards at `pos` (from `positions`)."""
        if len(pos) == len(self.shards) and np.array_equal(
            pos, np.arange(len(pos))
        ):
            return self.rids, self.counts, self.offsets
        starts = self.offsets[pos]
        lens = self.offsets[pos + 1] - starts
        offsets = np.zeros(len(pos) + 1, np.int64)
        np.cumsum(lens, out=offsets[1:])
        take = np.arange(offsets[-1]) + np.repeat(starts - offsets[:-1], lens)
        return self.rids[take], self.counts[take], offsets
