"""Tiered storage: object-store cold fragments, snapshot bootstrap,
beyond-RAM capacity (ROADMAP item 3).

store   — S3-shaped ObjectStore (LocalDirStore / MemoryStore), durable
          puts, fault-hook surface for server/faults.py.
policy  — per-index hot/warm/cold placement (defaults + overrides,
          [tier] config section).
manager — TierManager: demote/hydrate protocol, single-flight cold
          fetches, LRU demotion ticker, snapshot bootstrap offers,
          anti-entropy over snapshot objects.
"""

from pilosa_tpu.tier.manager import TierManager  # noqa: F401
from pilosa_tpu.tier.policy import (  # noqa: F401
    PLACEMENT_COLD,
    PLACEMENT_HOT,
    PLACEMENT_WARM,
    PLACEMENTS,
    TierPolicy,
    parse_overrides,
    validate_placement,
)
from pilosa_tpu.tier.store import (  # noqa: F401
    LocalDirStore,
    MemoryStore,
    ObjectCorrupt,
    ObjectMissing,
    ObjectStore,
    StoreError,
)
