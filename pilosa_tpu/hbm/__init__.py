"""HBM residency management: extent-granular paging, pinning & prefetch.

Layering: `hbm` sits BETWEEN core and exec. core/devcache.py is the byte
ledger (LRU + pins); this package decides *what* the ledger holds for the
stacked query path: operand stacks are split into shard-major EXTENTS that
page in and out individually, so an HBM budget below one query's working
set re-stages only the evicted slices instead of re-shipping whole ~100 MB
stacks over PCIe per query. exec/plan.py pins a plan's extents for the
duration of its compiled dispatch; sched/ reads residency for admission
cost discounts and feeds the optional prefetcher from its queue peek.

This is the KV-cache-shaped residency layer every serving stack grows:
page (extents), pin (in-use can't evict), prefetch (warm the next query's
operands while the current dispatch runs).
"""

from pilosa_tpu.hbm.residency import (
    ExtentTable,
    configure,
    drop_index,
    extent_rows,
    prefetching,
    stage_row_stack,
    stage_plane_stack,
    stats_snapshot,
)
from pilosa_tpu.hbm.prefetch import Prefetcher

__all__ = [
    "ExtentTable",
    "Prefetcher",
    "configure",
    "drop_index",
    "extent_rows",
    "prefetching",
    "stage_row_stack",
    "stage_plane_stack",
    "stats_snapshot",
]
