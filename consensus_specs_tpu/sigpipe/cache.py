"""Pubkey caches for the signature pipeline.

Two layers, both bounded (FIFO eviction, same discipline as the spec's
shuffle-permutation LRU):

* `PubkeyCache` — compressed 48-byte pubkey -> validated decompressed G1
  Point.  Decompression + subgroup check is the per-key host cost of every
  verification; real clients cache it across blocks, so do we.
* `AggregatePubkeyCache` — participant-set digest -> aggregated G1 Point.
  The committee/sync-aggregate G1 sums are O(committee) point adds per set;
  re-verifying the same participant set (oracle cross-checks, repeated
  dispatch of one block, fork-choice replays) hits the cache instead.
  Entries carry a human-readable hint like ``("att", epoch,
  committee_index)`` for debugging, but the KEY is a content digest of the
  participant pubkeys — a label collision can therefore never return the
  wrong aggregate.

COLD sums run on the accelerator, not the host: every compute path
(`aggregate`, `aggregate_many`, `warm_many`) funnels into
`_sum_batch`, which fuses all cold sets of a call into ONE
`ops/g1_sweep.g1_add_sweep` ragged-segment reduction behind the
`ops.g1_aggregate` resilience dispatch seam — the scheduler's flush and
the gossip prewarm therefore cost one batched dispatch each instead of
O(sets x committee) Python point adds.  The supervised fallback is the
byte-identical per-set host loop, and every add it performs lands in
the `host_point_adds` counter (the number the device offload exists to
drive to ~0); `g1_aggregate_dispatches` counts the batched calls.

Hit/miss counters land in sigpipe.metrics.METRICS.

Both caches are thread-safe (one lock each around lookup/insert/evict):
the supervisor's watchdog runs dispatches on worker threads, and the
gossip-path follow-up (ROADMAP) will share these caches across
verification threads.  Point decompression runs OUTSIDE the lock — it is
the expensive part and needs no cache state.
"""
from __future__ import annotations

import hashlib

from ..crypto import curve as cv
from ..crypto.bls12_381 import _load_pubkey
from ..crypto.curve import DecodeError
from ..utils.locks import named_rlock
from . import pipeline_async
from .metrics import METRICS


class PubkeyCache:
    def __init__(self, max_size: int = 1 << 16, metrics=METRICS):
        self._cache: dict = {}
        self._max = max_size
        self._metrics = metrics
        self._lock = named_rlock("sigpipe.pubkey_cache")

    def get(self, pubkey) -> cv.Point:
        """Decompressed, validated G1 point for compressed bytes; raises
        DecodeError/ValueError exactly like the scalar `_load_pubkey`."""
        key = bytes(pubkey)
        with self._lock:
            point = self._cache.get(key)
        if point is not None:
            self._metrics.inc("pubkey_cache_hits")
            return point
        self._metrics.inc("pubkey_cache_misses")
        point = _load_pubkey(key)   # DecodeError / ValueError propagate
        if not pipeline_async.writes_allowed():
            return point    # abandoned in-flight flush: leave no trace
        with self._lock:
            if len(self._cache) >= self._max:
                self._cache.pop(next(iter(self._cache)))
            self._cache[key] = point
        return point

    def prime(self, items) -> None:
        """Insert already-validated (compressed pubkey, G1 Point) pairs:
        the registry's keys a node validated when they entered it, as
        after a restart from its own snapshot.  Bounded like `get`."""
        with self._lock:
            for pubkey, point in items:
                if len(self._cache) >= self._max:
                    self._cache.pop(next(iter(self._cache)))
                self._cache[bytes(pubkey)] = point

    def clear(self) -> None:
        with self._lock:
            self._cache.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._cache)


class AggregatePubkeyCache:
    def __init__(self, pubkeys: PubkeyCache, max_size: int = 1 << 12,
                 metrics=METRICS):
        self._pubkeys = pubkeys
        self._cache: dict = {}
        self._max = max_size
        self._metrics = metrics
        self._lock = named_rlock("sigpipe.aggregate_cache")
        self._track_stack: list = []    # open insert-tracking scopes

    # -- insert tracking (txn/ rollback invalidation) -------------------
    # A transaction that rolls back must be able to evict exactly the
    # aggregates it inserted (prewarms and verification-miss inserts):
    # a rolled-back block's participant sets would otherwise linger in
    # the cache as warm state the store never accepted.  Content
    # addressing keeps them CORRECT, but crash-only discipline says a
    # rolled-back operation leaves no trace.

    def begin_track(self) -> set:
        """Start recording digests inserted from now on; returns the
        live set (hand it to `evict` on rollback, `end_track` always)."""
        tracked: set = set()
        with self._lock:
            self._track_stack.append(tracked)
        return tracked

    def end_track(self, tracked: set) -> None:
        with self._lock:
            self._track_stack = [t for t in self._track_stack
                                 if t is not tracked]

    def evict(self, digests) -> int:
        """Drop the given digests; returns how many were present."""
        with self._lock:
            evicted = sum(1 for d in digests
                          if self._cache.pop(d, None) is not None)
        if evicted:
            self._metrics.inc("aggregate_cache_evictions", evicted)
        return evicted

    @staticmethod
    def _digest(pubkey_bytes_list) -> bytes:
        return hashlib.sha256(
            b"".join(bytes(pk) for pk in pubkey_bytes_list)).digest()

    def aggregate(self, pubkey_bytes_list, hint=None) -> cv.Point:
        """Sum of the (decompressed) pubkeys; cached by content digest."""
        digest = self._digest(pubkey_bytes_list)
        with self._lock:
            entry = self._cache.get(digest)
        if entry is not None:
            self._metrics.inc("aggregate_cache_hits")
            return entry[0]
        self._metrics.inc("aggregate_cache_misses")
        agg = self._compute_and_insert(digest, pubkey_bytes_list, hint)
        return agg

    def _collect_cold(self, jobs, hit_counter, miss_counter):
        """Shared cold-collection for the batch entry points: digest
        each (pubkey_bytes_list, hint) job, count cache hits/misses
        under the given metric names (None skips the count), decode the
        cold sets — a job whose pubkeys fail decode is dropped, the
        per-job stand-in for the scalar path's DecodeError/ValueError —
        and dedup by content digest within the call.  Returns
        (hits: job index -> cached Point,
         cold: digest -> (decompressed points, hint),
         slots: digest -> job indices awaiting that cold sum)."""
        hits: dict = {}
        cold: dict = {}
        slots: dict = {}
        for k, (pks, hint) in enumerate(jobs):
            digest = self._digest(pks)
            with self._lock:
                entry = self._cache.get(digest)
            if entry is not None:
                if hit_counter:
                    self._metrics.inc(hit_counter)
                hits[k] = entry[0]
                continue
            if digest in cold:
                # an intra-call duplicate reads as a HIT, matching the
                # sequential scalar path (first call misses and
                # computes, the second hits the fresh entry)
                if hit_counter:
                    self._metrics.inc(hit_counter)
                slots[digest].append(k)
                continue
            if miss_counter:
                self._metrics.inc(miss_counter)
            try:
                pts = [self._pubkeys.get(pk) for pk in pks]
            except (DecodeError, ValueError):
                continue
            cold[digest] = (pts, hint)
            slots[digest] = [k]
        return hits, cold, slots

    def _sum_and_insert(self, cold) -> list:
        """ONE batched `_sum_batch` dispatch over every cold set, each
        sum inserted under its digest; returns the sums in `cold`
        iteration order."""
        digests = list(cold)
        sums = self._sum_batch([cold[d][0] for d in digests])
        for digest, agg in zip(digests, sums):
            self._insert(digest, agg, cold[digest][1])
        return sums

    def aggregate_many(self, jobs) -> list:
        """Batch form of `aggregate` for a whole scheduler flush: `jobs`
        is a list of (pubkey_bytes_list, hint) pairs; returns one
        aggregated Point per job, or None where a pubkey failed
        decode/validation (the per-job stand-in for the scalar path's
        DecodeError/ValueError).  Hits come straight from the cache; ALL
        cold jobs' committee sums fuse into one `_sum_batch` device
        dispatch, deduplicated by content digest within the call."""
        hits, cold, slots = self._collect_cold(
            jobs, "aggregate_cache_hits", "aggregate_cache_misses")
        results = [None] * len(jobs)
        for k, agg in hits.items():
            results[k] = agg
        if cold:
            for digest, agg in zip(cold, self._sum_and_insert(cold)):
                for k in slots[digest]:
                    results[k] = agg
        return results

    def warm_many(self, jobs) -> int:
        """Pre-compute aggregates OUTSIDE a verification (the on_block
        prewarm sweep, gossip/prewarm.py): inserts every cold
        participant set of `jobs` via one `_sum_batch` dispatch,
        counting `aggregate_cache_prewarms` instead of hits/misses so
        warm-up work never distorts the hit rate the dashboards track;
        returns how many sets were actually cold.  Best-effort like the
        prewarm path itself — a set whose pubkeys fail decode is
        skipped, never an error."""
        _hits, cold, _slots = self._collect_cold(jobs, None, None)
        if not cold:
            return 0
        self._metrics.inc("aggregate_cache_prewarms", len(cold))
        self._sum_and_insert(cold)
        return len(cold)

    def _compute_and_insert(self, digest, pubkey_bytes_list,
                            hint) -> cv.Point:
        # decompression (the expensive per-key host step, cached in
        # PubkeyCache) raises DecodeError/ValueError exactly like the
        # scalar path; the sum itself rides the batched dispatch seam
        pts = [self._pubkeys.get(pk) for pk in pubkey_bytes_list]
        agg = self._sum_batch([pts])[0]
        self._insert(digest, agg, hint)
        return agg

    def _sum_batch(self, point_lists) -> list:
        """THE cold-sum path: one `ops.g1_aggregate` dispatch for every
        cold participant set of a call (ops/g1_sweep.py padded
        ragged-segment reduction); the supervised fallback is the
        byte-identical per-set host loop, its adds counted."""
        from ..resilience.supervisor import dispatch
        self._metrics.inc("g1_aggregate_dispatches")

        def device():
            from ..ops.g1_sweep import g1_add_sweep
            return g1_add_sweep(point_lists)

        return dispatch("ops.g1_aggregate", device,
                        lambda: [self._host_sum(pts)
                                 for pts in point_lists])

    def _host_sum(self, pts) -> cv.Point:
        agg = cv.g1_infinity()
        for p in pts:
            agg = agg + p
        if pts:
            self._metrics.inc("host_point_adds", len(pts))
        return agg

    def _insert(self, digest, agg, hint) -> None:
        if not pipeline_async.writes_allowed():
            # a flush the caller abandoned past its watchdog deadline
            # keeps computing on the engine worker but may no longer
            # warm shared state: same purity pin as the abandoned
            # merkle sweep (values would be content-correct, but
            # crash-only discipline says a zombie leaves no trace)
            return
        with self._lock:
            if len(self._cache) >= self._max:
                self._cache.pop(next(iter(self._cache)))
            self._cache[digest] = (agg, hint)
            for tracked in self._track_stack:
                tracked.add(digest)

    def clear(self) -> None:
        with self._lock:
            self._cache.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._cache)


# speclint: disable=global-mutable-state -- content-addressed cache:
# pubkey bytes -> decompressed Point, identical whichever node computes
# it, so fleet-wide sharing is sound (and what makes SimNode fleets cheap)
PUBKEYS = PubkeyCache()
# speclint: disable=global-mutable-state -- keyed by participant-set
# digest, values node-independent; txn rollback evicts only entries the
# aborted transaction itself inserted (begin_track/end_track)
AGGREGATES = AggregatePubkeyCache(PUBKEYS)


def clear() -> None:
    PUBKEYS.clear()
    AGGREGATES.clear()
