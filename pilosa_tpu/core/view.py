"""View: groups fragments by shard for one "view" of a field.

Reference: /root/reference/view.go — view names are `standard`, time-quantum
views (`standard_2019`, `standard_201907`, ...), and `bsig_<field>` for BSI
groups (view.go:37-41)."""

from __future__ import annotations

import os
from typing import Dict, List, Optional

import numpy as np

from pilosa_tpu.utils.locks import TrackedLock, TrackedRLock
from pilosa_tpu.coherence import hub as coherence_hub
from pilosa_tpu.core import wal as walmod
from pilosa_tpu.core.devcache import DEVICE_CACHE, new_owner_token
from pilosa_tpu.core.fragment import Fragment
from pilosa_tpu.core.resultcache import RESULT_CACHE
from pilosa_tpu.core.rowsummary import RowSummary
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_ROW
from pilosa_tpu.utils.stats import PROCESS

VIEW_STANDARD = "standard"
VIEW_BSI_PREFIX = "bsig_"


class View:
    def __init__(
        self,
        name: str,
        index: str,
        field: str,
        path: Optional[str],
        *,
        mutex: bool = False,
        max_op_n: int = 10_000,
        cache_type: str = "ranked",
        cache_size: int = 50_000,
    ):
        self.name = name
        self.index = index
        self.field = field
        self.path = path  # directory holding fragments/; None => in-memory
        self.mutex = mutex
        self.max_op_n = max_op_n
        self.cache_type = cache_type
        self.cache_size = cache_size
        self._mu = TrackedRLock("view.mu")
        self.fragments: Dict[int, Fragment] = {}
        # owner token for cross-shard row stacks in the global device cache
        self._stack_token = new_owner_token()
        # view-level mutation clock (result cache fast path): bumped on
        # EVERY mutation event that bumps a fragment version — the
        # on_mutate funnel and the bulk stage router — so clock-equal
        # implies every fragment version in this view is unchanged. The
        # cache revalidates warm repeats against this one integer per
        # view instead of walking the whole shard axis; a clock mismatch
        # falls back to the exact per-shard vector (a write to a
        # DISJOINT shard subset must not kill covering entries).
        # ORDERING CONTRACT: the clock bumps AFTER the version bump(s),
        # before the mutation call returns. A reader overlapping an
        # IN-FLIGHT write may therefore still fast-path the pre-write
        # result — the same partial-visibility window any query racing
        # a bulk import already has — but once the write returns, every
        # later lookup sees the new clock. Trailing (not leading) is
        # load-bearing: it guarantees a clock read always corresponds
        # to a state no NEWER than any vector read after it, which is
        # what makes arming entries with (clock, vector) pairs sound —
        # a leading bump could arm a pre-write vector under the
        # post-write clock and serve stale forever.
        # Dedicated leaf lock: bumps happen under fragment locks, and
        # view._mu is taken BEFORE fragment locks elsewhere (fragment
        # creation) — a lost += under concurrency could freeze the clock
        # across a real mutation, which revalidation soundness forbids.
        self._clock_mu = TrackedLock("view.clock_mu")
        self.mutation_clock = 0
        # shards with staged writes whose covering stack extents were NOT
        # invalidated at stage time (they are version-keyed, so they can
        # never be served stale): the merge barrier's reconciliation
        # either patches them in place to the merged version or drops
        # them (sync_pending -> _reconcile_extents)
        self._dirty_staged: set = set()
        # tiered storage (pilosa_tpu/tier/): when set, shards missing
        # from `fragments` may be COLD — demoted to the object store —
        # and every lookup that would treat absence as emptiness must
        # consult the resolver first (resolve() hydrates on demand,
        # single-flight). None = tier disabled, zero overhead.
        self.cold_resolver = None
        # row summary (core/rowsummary.py): one table of every non-empty
        # (row, shard) cardinality, built on first use and validated per
        # reader by row_summary() against the two integers it was built
        # under. mutation_clock covers content; _frag_epoch covers the
        # fragment SET, which changes without a clock bump (creation,
        # deletion, tier demotion / hydration) — it bumps under
        # _mu together with the dict update, so a table whose epoch still
        # matches was read from exactly today's fragments.
        self._frag_epoch = 0  # lock-free: monotonic int written under _mu; GIL-atomic reads
        self._summary: Optional[RowSummary] = None  # lock-free: immutable table, swapped whole

    def open(self) -> "View":
        """Load existing fragments from disk (view.go:120 openFragments)."""
        if self.path is not None:
            frag_dir = os.path.join(self.path, "fragments")
            if os.path.isdir(frag_dir):
                for fn in sorted(os.listdir(frag_dir)):
                    if fn.endswith(".snap") or fn.endswith(".wal"):
                        shard_s = fn.rsplit(".", 1)[0]
                        if shard_s.isdigit():
                            self.fragment(int(shard_s))
        # coherence plane: register for deferred tree-repair operand reads
        # (core/resultcache.py resolves tokens back to live views through
        # this weak registry; a no-op when repair never defers)
        RESULT_CACHE.register_view(self)
        return self

    def close(self) -> None:
        with self._mu:
            for frag in self.fragments.values():
                frag.close()
            # drop the view-level device stacks (row/plane stacks, TopN
            # tally bundles — all keyed under _stack_token): a deleted
            # index's arrays must leave the device ledger, and their
            # per-index attribution must not resurrect the label after
            # telemetry GC
            DEVICE_CACHE.invalidate_owner(self._stack_token)
            RESULT_CACHE.drop_view(self._stack_token)
            self._dirty_staged.clear()
            self._summary = None
        # outside the view lock: publishers ship drop tombstones so leased
        # mirrors forget this view instead of holding its last versions
        # forever (monotone merge would otherwise mask the deletion)
        coherence_hub.note_view_drop(self)

    def _fragment_path(self, shard: int) -> Optional[str]:
        if self.path is None:
            return None
        return os.path.join(self.path, "fragments", str(shard))

    def fragment(self, shard: int) -> Fragment:
        """Get-or-create the fragment for a shard (view.go:263
        CreateFragmentIfNotExists)."""
        with self._mu:
            frag = self.fragments.get(shard)
        if frag is not None:
            return frag
        res = self.cold_resolver
        if res is not None:
            # the shard may be demoted: creating a fresh empty fragment
            # here would SHADOW the stored snapshot and lose it on the
            # next hydrate — resolve (and possibly fetch) outside the
            # view lock, since hydration blocks on store I/O
            frag = res.resolve(self, shard)
            if frag is not None:
                return frag
        with self._mu:
            frag = self.fragments.get(shard)
            if frag is None:
                frag = Fragment(
                    self._fragment_path(shard),
                    self.index,
                    self.field,
                    self.name,
                    shard,
                    mutex=self.mutex,
                    max_op_n=self.max_op_n,
                    cache_type=self.cache_type,
                    cache_size=self.cache_size,
                ).open()
                # dirty-extent invalidation: a write reports WHICH shard
                # changed, and only the stack entries whose extent span
                # covers it are dropped (stale version keys would miss
                # anyway; this frees exactly the stale HBM immediately
                # instead of churning the whole owner or waiting on LRU)
                frag.on_mutate = lambda s=shard: self._on_fragment_mutate(s)
                self.fragments[shard] = frag
                self._frag_epoch += 1
            return frag

    def _on_fragment_mutate(self, shard: int) -> None:
        """The per-mutation funnel (Fragment.on_mutate): dirty-extent
        device invalidation plus the result-cache notification — cached
        results covering the mutated (view, shard) drop eagerly unless
        they are Count entries awaiting the merge barrier's in-place
        repair (core/resultcache.py)."""
        with self._clock_mu:
            self.mutation_clock += 1
        DEVICE_CACHE.invalidate_owner_shard(self._stack_token, shard)
        RESULT_CACHE.note_mutation(self._stack_token, shard)
        coherence_hub.note_view_mutation(self, (shard,))
        res = self.cold_resolver
        if res is not None:
            # writes count as activity for the tier's LRU demote clock —
            # a write-hot fragment must never look idle to the ticker
            res.touch_many(self, (shard,))

    def fragment_if_exists(self, shard: int) -> Optional[Fragment]:
        frag = self.fragments.get(shard)
        if frag is not None:
            return frag
        res = self.cold_resolver
        if res is not None:
            # "exists" includes cold: a demoted fragment still HAS the
            # data (in the object store) — hydrate rather than report
            # absence, which reads as zeros to every caller
            return res.resolve(self, shard)
        return None

    def delete_fragment(self, shard: int) -> bool:
        """Drop one shard's fragment: close it, delete its on-disk files
        and free its device-cache residency (the post-resize holder
        cleaner's unit of work, reference holder.go:1126)."""
        with self._mu:
            frag = self.fragments.pop(shard, None)
            if frag is None:
                return False
            self._frag_epoch += 1
            frag.close()  # also frees the fragment's device-cache residency
            for p in (frag.snap_path, frag.wal_path, frag.cache_path):
                if p is not None:
                    try:
                        os.remove(p)
                    except OSError:
                        pass
            DEVICE_CACHE.invalidate_owner(self._stack_token)
            RESULT_CACHE.drop_view(self._stack_token)
        # fragment gone: the publisher's flush finds no fragment for this
        # shard and demotes the bump to a drop tombstone, so leased mirrors
        # never pin the deleted shard's last version as live
        coherence_hub.note_view_mutation(self, (shard,))
        return True

    def available_shards(self) -> List[int]:
        with self._mu:
            shards = set(self.fragments)
        res = self.cold_resolver
        if res is not None:
            # cold shards are still AVAILABLE — they hydrate on access;
            # omitting them would silently shrink every query's shard
            # span the moment a fragment demotes
            shards |= res.cold_shards(self)
        return sorted(shards)

    def evict_fragment(self, shard: int, end_capture_tag=None) -> bool:
        """Tier demotion eviction: detach + close + delete the local
        files of a shard whose snapshot object is already DURABLE in the
        tier store. Unlike delete_fragment the data still exists (cold),
        so only this shard's device entries drop — version-keyed stack
        extents and cached results covering OTHER shards stay exact, and
        the result cache is untouched (content is unchanged, so serving
        a covering cached result remains correct)."""
        with self._mu:
            frag = self.fragments.pop(shard, None)
            if frag is not None:
                self._frag_epoch += 1
        if frag is None:
            return False
        if end_capture_tag is not None:
            # ends the demote capture AFTER detach: the lifted write
            # barrier exposes nothing — new lookups resolve through the
            # cold set, and stragglers holding this ref get 503 retries
            # until the barrier TTL, whose retry hydrates
            frag.end_capture(end_capture_tag)
        frag.close()  # frees the fragment's own device-cache residency
        # deletion order is load-bearing: the .snap goes LAST so a crash
        # mid-eviction leaves either a complete local fragment or
        # nothing — never a bare artifact that would reopen as an empty
        # shadow of the stored object
        for p in (frag.wal_path, frag.cache_path, frag.snap_path):
            if p is not None:
                try:
                    os.remove(p)
                except OSError:
                    pass
        DEVICE_CACHE.invalidate_owner_shard(self._stack_token, shard)
        return True

    def adopt_fragment(self, shard: int, blob: bytes,
                       on_ready=None) -> Fragment:
        """Tier hydration target: materialize a demoted fragment from
        its snapshot object (`to_bytes` output). Any retained WAL tail —
        a crash between a hydration's local snapshot and its WAL
        truncate can leave one — replays AFTER the snapshot applies (its
        records postdate the upload by construction), so it is collected
        up front; left in place, open() would replay it UNDER the
        from_bytes replacement and lose it.

        The fragment is PUBLISHED (inserted into `fragments`) only after
        its contents are complete and `on_ready` ran — callers hold no
        other reference, so `on_ready` (the tier's bootstrap-watch
        capture arming) observes a state no write can have moved yet."""
        path = self._fragment_path(shard)
        tail: list = []
        if path is not None and os.path.exists(path + ".wal"):
            tail = list(walmod.replay_wal(path + ".wal"))
            os.remove(path + ".wal")
        frag = Fragment(
            path,
            self.index,
            self.field,
            self.name,
            shard,
            mutex=self.mutex,
            max_op_n=self.max_op_n,
            cache_type=self.cache_type,
            cache_size=self.cache_size,
        ).open()
        frag.from_bytes(blob)
        if tail:
            frag.apply_transfer_records(walmod.encode_records(tail))
        if on_ready is not None:
            on_ready(frag)
        with self._mu:
            existing = self.fragments.get(shard)
            if existing is not None:
                # lost a (single-flight-guarded, so unexpected) race:
                # the published fragment wins; ours was never visible
                frag.end_capture(None)
                frag.close()
                return existing
            frag.on_mutate = lambda s=shard: self._on_fragment_mutate(s)
            self.fragments[shard] = frag
            self._frag_epoch += 1
        return frag

    # -- row summary (core/rowsummary.py) ----------------------------------

    def row_summary(self) -> Optional[RowSummary]:
        """The view's table of per-(row, shard) cardinalities, current as
        of this call, or None when the view cannot keep one (the tier
        reports cold shards: they hydrate through fragment_if_exists, so
        those readers keep the per-fragment path).

        The clock is read FIRST (the ordering contract in __init__: a
        clock read is never newer than content read after it), so a table
        stamped with it can be stale-by-clock, never fresh-by-clock over
        old data. Clock-equal is a hit. Otherwise the staged burst merges
        as one batched barrier and only the fragments whose version moved
        are read again; a moved epoch rebuilds from nothing. No lock is
        held across the build: concurrent readers may each build, every
        result is exact under its own stamp, and the last one stored is
        revalidated like any other."""
        clock = self.mutation_clock
        old = self._summary
        if old is not None and old.epoch == self._frag_epoch:
            epoch, frags = old.epoch, old.frags
        else:
            old = None
            with self._mu:
                epoch = self._frag_epoch
                frags = [self.fragments[s] for s in sorted(self.fragments)]
        res = self.cold_resolver
        if res is not None and res.cold_shards(self):
            # asked AFTER the fragment set was read: demotion registers a
            # shard cold before it detaches the fragment (tier/manager.py
            # _demote), so a set read earlier that lacks it is caught here
            return None
        if old is not None and old.clock == clock:
            PROCESS.count("rowsummary.hits", 1, ())
            return old
        self.sync_pending(frags=frags)
        new = RowSummary(epoch, clock, frags, old)
        if old is None:
            PROCESS.count("rowsummary.rebuilds", 1, ())
        else:
            PROCESS.count("rowsummary.refreshed_shards", new.reads, ())
        self._summary = new
        return new

    # -- stacked operands for the compiled query path ----------------------
    #
    # A "stack" is one row materialized across a shard list as a dense
    # uint32[S, W] device array (shard-axis sharded under an active mesh).
    # Staging goes through the HBM residency layer (pilosa_tpu/hbm/):
    # big stacks are split into shard-major EXTENTS that page in/out of
    # the budgeted device cache individually, keyed by the fragments'
    # mutation versions — a write to any covered fragment makes the keys
    # miss and the affected slices rebuild lazily. Callers on the compiled
    # query path pass their lowering's ExtentTable so the staged extents
    # stay pinned through the plan's dispatch.

    def _frags_for(self, shards: tuple) -> list:
        """Fragment list for a shard span, hydrating any COLD member
        through the tier resolver (single-flight; a missing shard with
        no cold copy stays None and reads as zeros, as before). Also
        feeds the tier's LRU touch clock so hot working sets never look
        idle to the demote ticker."""
        with self._mu:
            frags = [self.fragments.get(s) for s in shards]
        res = self.cold_resolver
        if res is not None:
            if any(f is None for f in frags):
                cold = res.cold_shards(self)
                for i, s in enumerate(shards):
                    if frags[i] is None and s in cold:
                        frags[i] = res.resolve(self, s)
            res.touch_many(
                self, [s for s, f in zip(shards, frags) if f is not None]
            )
        return frags

    def _stack_key(self, kind: str, ident, shards: tuple) -> tuple:
        # fragment versions are NOT part of the base key: staging appends
        # each extent's OWN shard-span version slice, so a write to one
        # shard re-keys only the covering extent instead of the whole
        # stack (the dirty-extent property the invalidation relies on)
        from pilosa_tpu.parallel import mesh as pmesh

        return (self._stack_token, kind, ident, shards, pmesh.mesh_epoch())

    @staticmethod
    def _frag_versions(frags) -> tuple:
        return tuple(f.version if f is not None else -1 for f in frags)

    # -- cross-fragment merge barrier (core/merge.py) ----------------------

    def sync_pending(self, shards=None, frags=None) -> None:
        """Read barrier over many fragments at once: gather every listed
        (default: every) fragment's staged pending delta and merge the
        whole burst in ONE batched pass — device program or vectorized
        host pass by the `merge-device-threshold` crossover — instead of
        one `_sync_locked` host pass per fragment. Afterwards, resident
        stack extents covering the written shards are patched in place
        on device (or dropped when unpatchable) so sustained mixed load
        does not oscillate between invalidate and ~32 MB re-stages. No
        fragment lock is held across another's, and none during the
        merge itself."""
        from pilosa_tpu.core import merge as merge_mod

        if frags is None:
            with self._mu:
                if shards is None:
                    frags = list(self.fragments.values())
                else:
                    frags = [self.fragments.get(s) for s in shards]
        merges = merge_mod.merge_barrier(frags)
        if merges:
            # result-cache repair/re-key: the SAME merged word deltas
            # that patch resident device extents below also patch cached
            # Count scalars in place (count += popcount(delta & ~old)),
            # so a repeat Count after a set-only burst serves from host
            # memory without re-reading a single operand word
            RESULT_CACHE.note_merges(self._stack_token, merges)
        # reconcile ONLY the shards this barrier covered: a query over a
        # disjoint shard span must not invalidate (and forget) other
        # shards' still-patchable extents — they stay dirty until their
        # own barrier merges them
        synced = {f.shard for f in frags if f is not None}
        with self._mu:
            dirty = self._dirty_staged & synced
        if merges or dirty:
            self._reconcile_extents(merges, dirty)

    def _reconcile_extents(self, merges, dirty: set) -> None:
        """Patch-or-invalidate every stack entry covering a shard whose
        staged delta just merged (or merged earlier via a per-fragment
        host barrier — `dirty` remembers those). An entry is patched
        only when every affected shard's fragment was `clean` (moved
        base -> base+n_parts by exactly the captured staged batches;
        batches staged mid-barrier stay pending and re-key the entry
        forward at their own barrier) AND the entry is keyed at exactly
        the pre-burst version; anything else drops it — the version
        keys already made it unservable."""
        patches = {m.shard: m for m in merges if m.clean}
        affected = dirty | {m.shard for m in merges}
        stale = affected - set(patches)
        if not affected:
            return
        from pilosa_tpu.parallel import mesh as pmesh

        patchable = pmesh.active_mesh() is None  # never touch sharded arrays
        for key, cover, is_extent in DEVICE_CACHE.owner_entries(
            self._stack_token
        ):
            if cover is None:
                # no registered coverage => not version-keyed: drop
                # conservatively (same rule as invalidate_owner_shard)
                DEVICE_CACHE.invalidate(key)
                continue
            hit = cover & affected
            if not hit:
                continue
            if (
                not patchable
                or (hit & stale)
                or not self._patch_entry(key, hit, patches, is_extent)
            ) and not self._entry_current(key, hit):
                # keep-if-current guards the races this reconcile can't
                # see: a concurrent barrier may have ALREADY patched the
                # entry to the fragments' live versions (this thread's
                # stale apply lost the generation race), or a dirty
                # marker may describe a write another barrier fully
                # reconciled — an entry keyed at the current versions
                # is exact by construction and must not be dropped
                DEVICE_CACHE.invalidate(key)
        with self._mu:
            self._dirty_staged -= affected

    def _entry_current(self, key, hit: set) -> bool:
        """True when the entry's version key matches every hit shard's
        fragment CURRENT version — i.e. the entry is exact right now
        and any 'stale' verdict about it is outdated. Lock-free version
        reads: a racing mutation makes the entry stale-by-key anyway
        (a wrong keep leaks one unservable entry until eviction, never
        a wrong answer), and the mutation re-marks the shard dirty so a
        later reconcile retries."""
        if key[0] != self._stack_token or len(key) < 6:
            return False
        tail = key[5:]
        if tail[0] == "ext" and len(tail) == 4:
            versions = tail[3]
            lo = tail[2] * tail[1]
        elif tail[0] == "mono" and len(tail) == 2:
            versions = tail[1]
            lo = 0
        else:
            return False
        span = key[3][lo : lo + len(versions)]
        for p, s in enumerate(span):
            if s in hit:
                frag = self.fragments.get(s)
                if frag is None or versions[p] != frag.version:
                    return False
        return True

    def _patch_entry(self, key, hit: set, patches, is_extent: bool) -> bool:
        """Rebuild one resident stack entry as (old contents | merged
        delta) ON DEVICE and re-insert it under the post-merge version
        key. True = reconciled (patched, or provably gone); False = the
        caller must invalidate. Exactness: the entry must be keyed at
        each patched fragment's pre-burst `base_version`, and the
        fragment must have been `clean` — content(base) | delta ==
        content(new) holds only when nothing else mutated in between."""
        import jax

        from pilosa_tpu.parallel import mesh as pmesh

        if key[0] != self._stack_token or len(key) < 6:
            return False
        if key[4] != pmesh.mesh_epoch():
            return False  # pre-mesh-change entry: a patched key is dead
        kind, ident, shards_t = key[1], key[2], key[3]
        tail = key[5:]
        if tail[0] == "ext" and len(tail) == 4:
            rows_per, ei, versions = tail[1], tail[2], tail[3]
            lo = ei * rows_per
        elif tail[0] == "mono" and len(tail) == 2:
            versions = tail[1]
            lo = 0
        else:
            return False
        span = shards_t[lo : lo + len(versions)]
        if kind == "row":
            row_ids = [ident]
        elif kind == "planes":
            row_ids = list(ident)
        else:
            return False
        upd = list(versions)
        deltas = []
        for p, s in enumerate(span):
            if s not in hit:
                continue
            m = patches.get(s)
            if m is None or versions[p] != m.base_version:
                return False
            upd[p] = m.new_version
            deltas.append((p, m))
        if not deltas:
            return False
        arr = DEVICE_CACHE.get(key)
        if arr is None:
            return True  # evicted meanwhile: nothing resident to go stale
        # batch the patch per ENTRY: every dirty (plane, shard-position)
        # delta lands through ONE gather | OR | scatter with stacked
        # index arrays, so a burst smeared over S shards costs one
        # whole-extent copy instead of S of them — the old per-position
        # `.at[p].set` cascade paid a full-extent copy per dirty shard
        idx_p: List[int] = []
        idx_d: List[int] = []
        blocks: List[np.ndarray] = []
        for p, m in deltas:
            for d, rid in enumerate(row_ids):
                if rid not in m.rows:
                    continue  # row untouched by the delta: re-key only
                widx, wvals = m.word_delta(rid)
                if not len(widx):
                    continue
                delta = np.zeros(WORDS_PER_ROW, np.uint32)
                delta[widx] = wvals
                blocks.append(delta)
                idx_p.append(p)
                idx_d.append(d)
        new_arr = arr
        n_batches = 0
        # bounded scatter batches: stacking EVERY delta block at once
        # would spike host+device memory by (dirty positions x touched
        # rows x row bytes) — a whole-index smear into a monolithic
        # deep-field entry could transiently allocate gigabytes. 256
        # blocks (~32 MB at the default shard width) keeps the spike
        # bounded while the cascade stays O(entries + deltas/256)
        # device ops, never O(dirty shards).
        CHUNK = 256
        for c0 in range(0, len(blocks), CHUNK):
            ddev = jax.device_put(np.stack(blocks[c0:c0 + CHUNK]))
            if kind == "row":
                pi = np.asarray(idx_p[c0:c0 + CHUNK])
                new_arr = new_arr.at[pi].set(new_arr[pi] | ddev)
            else:
                di = np.asarray(idx_d[c0:c0 + CHUNK])
                pi = np.asarray(idx_p[c0:c0 + CHUNK])
                new_arr = new_arr.at[di, pi].set(new_arr[di, pi] | ddev)
            n_batches += 1
        new_key = key[:5] + (
            ("ext", rows_per, ei, tuple(upd))
            if tail[0] == "ext"
            else ("mono", tuple(upd))
        )
        DEVICE_CACHE.put(
            new_key, new_arr, extent=is_extent, shards=span, index=self.index
        )
        DEVICE_CACHE.invalidate(key)
        from pilosa_tpu.hbm import residency as hbm_res

        hbm_res.note_extent_patch(batches=n_batches)
        return True

    def row_stack(self, row_id: int, shards, extents=None,
                  parts: bool = False) -> Optional[object]:
        """uint32[S, W] device stack of one row over `shards`, or None when
        no listed shard has a fragment (the row is wholly absent).
        `extents` (hbm.ExtentTable) receives the pinned extent keys;
        `parts` returns the per-extent arrays unassembled (the
        plane-streamed aggregate path reduces across them in program
        instead of paying a device-side concat per staging)."""
        from pilosa_tpu.hbm import residency as hbm_res

        shards = tuple(shards)
        frags = self._frags_for(shards)
        if all(f is None for f in frags):
            return None
        # merge the staged burst (all touched fragments, one pass) and
        # patch/drop covering extents BEFORE versions are read below, so
        # the staged keys reflect the merged state
        self.sync_pending(frags=frags)
        key = self._stack_key("row", row_id, shards)

        def build_slice(lo: int, hi: int):
            return hbm_res.build_row_slice(frags[lo:hi], row_id)

        return hbm_res.stage_row_stack(
            key, len(shards), build_slice, table=extents,
            versions=self._frag_versions(frags), shards=shards,
            index=self.index, parts=parts,
        )

    def stage_bulk(self, shards: np.ndarray, positions: np.ndarray) -> None:
        """Bulk-ingest router (the write-side hot path): ONE argsort over
        the whole batch splits the fragment positions into per-shard
        views; per-fragment cost is then a WAL frame + a pending-buffer
        append (Fragment.stage_positions with notify=False). The
        device-cache work every write owes — dropping the touched
        fragments' row entries and the dirty shards' covering extents —
        runs as two batched passes at the end instead of two global-lock
        hits per shard."""
        if not len(shards):
            return
        # hand-rolled grouping instead of utils/arrays.group_slices: this
        # is THE write hot path, and group_slices' stable argsort costs
        # ~4x quicksort on uint64 keys while its per-group index arrays
        # force a fancy-gather per shard — np.split on the pre-permuted
        # positions hands out views. Stability is not needed: set bits
        # commute.
        order = np.argsort(shards)
        sh = shards[order]
        pos = positions[order]
        bounds = np.flatnonzero(sh[1:] != sh[:-1]) + 1
        starts = np.concatenate(([0], bounds)).astype(np.int64)
        uniq = sh[starts]
        chunks = np.split(pos, bounds)
        tokens = []
        dirty = []
        # one group-commit fsync round for the WHOLE batch at barrier
        # exit: each stage_positions defers its durability wait, so a
        # 100-shard import pays one commit round, not 100 — and
        # concurrent import calls coalesce into each other's rounds
        with walmod.GROUP_COMMIT.barrier():
            for shard, chunk in zip(uniq.tolist(), chunks):
                frag = self.fragment(int(shard))
                frag.stage_positions(chunk, notify=False)
                tokens.append(frag._token)
                tokens.append(frag._stack_token)
                dirty.append(int(shard))
        DEVICE_CACHE.invalidate_owners(tokens)
        # view-level stack entries: ad-hoc (uncovered) builds like the
        # TopN tally bundles are not version-keyed, so they drop NOW;
        # coverage-registered extents ARE version-keyed (never served
        # stale) and defer to the merge barrier, which patches resident
        # ones in place with the merged delta instead of forcing a
        # ~extent-sized PCIe re-stage per touched extent
        with self._clock_mu:
            self.mutation_clock += 1
        DEVICE_CACHE.invalidate_owner_uncovered(self._stack_token)
        # result-cache dirty reporting, batched like the device pass:
        # stale non-repairable results drop now, repairable Counts wait
        # for the barrier's repair (stage_positions ran notify=False, so
        # the per-fragment on_mutate funnel did not fire)
        RESULT_CACHE.note_mutations(self._stack_token, dirty)
        coherence_hub.note_view_mutation(self, dirty)
        with self._mu:
            self._dirty_staged.update(dirty)

    def plane_stack(self, row_ids, shards, extents=None,
                    parts: bool = False) -> Optional[object]:
        """uint32[D, S, W] device stack (BSI planes × shards), or None when
        no listed shard has a fragment. Extents slice the shard axis: one
        slice pages all D planes for its shard range together. `parts`
        returns the per-extent arrays unassembled."""
        from pilosa_tpu.hbm import residency as hbm_res

        row_ids = tuple(row_ids)
        shards = tuple(shards)
        frags = self._frags_for(shards)
        if all(f is None for f in frags):
            return None
        self.sync_pending(frags=frags)
        key = self._stack_key("planes", row_ids, shards)

        def build_slice(lo: int, hi: int):
            return hbm_res.build_plane_slice(frags[lo:hi], row_ids)

        return hbm_res.stage_plane_stack(
            key, len(shards), build_slice, table=extents,
            versions=self._frag_versions(frags), shards=shards,
            index=self.index, parts=parts,
        )

    # -- fan-down helpers (view.go:367-474) --------------------------------

    def set_bit(self, row_id: int, col: int) -> bool:
        return self.fragment(col // SHARD_WIDTH).set_bit(row_id, col)

    def clear_bit(self, row_id: int, col: int) -> bool:
        frag = self.fragment_if_exists(col // SHARD_WIDTH)
        return frag.clear_bit(row_id, col) if frag is not None else False

    def set_value(self, col: int, bit_depth: int, value: int, clear: bool = False) -> bool:
        return self.fragment(col // SHARD_WIDTH).set_value(col, bit_depth, value, clear)

    def value(self, col: int, bit_depth: int):
        frag = self.fragment_if_exists(col // SHARD_WIDTH)
        if frag is None:
            return 0, False
        return frag.value(col, bit_depth)

    def row_positions(self, row_id: int) -> np.ndarray:
        """Absolute columns of a row across all shards (host; for exports)."""
        cols = []
        for shard in self.available_shards():
            frag = self.fragment_if_exists(shard)  # hydrates cold shards
            if frag is None:
                continue
            p = frag.row_positions(row_id)
            if len(p):
                cols.append(p.astype(np.uint64) + np.uint64(shard) * np.uint64(SHARD_WIDTH))
        return np.concatenate(cols) if cols else np.empty(0, np.uint64)
