"""Lightweight pipeline metrics: counters, observations, histograms.

One module-global `METRICS` registry is shared by the collector, scheduler,
bisection, caches and the resilience supervisor, so a single `snapshot()`
describes a whole verification run (batch sizes, dispatch count, bisection
depth, cache hit rate, breaker trips, fallback reasons) — dumpable as JSON
for `bench.py` and asserted on by tests/test_sigpipe.py.

`METRICS` is a *router*, not a bare registry: every call consults the
node-context stack (utils/nodectx.py) and lands in the active node's
own `Metrics` instance when the scenario harness installed one, or in
the process-global default otherwise.  Single-node callers never see
the difference; the multi-node driver gets per-node books (each tagged
with its `node_id`, which `snapshot()` carries) from the exact same
call sites.

Thread-safe: a single re-entrant lock guards every mutation and snapshot.
The gossip-path follow-up (ROADMAP) and the supervisor's watchdog thread
both touch the registry off the main thread; per-counter races would make
degradation counters lie exactly when they matter.

Labeled counters (`inc_labeled`) keep one counter per (name, label) pair —
the `scalar_fallbacks` counter is labeled by degradation reason
(`collector_miss`, `breaker_open`, `dispatch_failed`, `guard_mismatch`,
`disabled`) so a metrics snapshot says not just that the pipeline
degraded but why.

The device-G1-sweep offload (PR 5) is observable through three plain
counters: `g1_aggregate_dispatches` (batched committee-sum calls at the
`ops.g1_aggregate` seam) and `msm_dispatches` (coefficient-weighted
sweep calls at `ops.msm`) count the per-flush device work — exactly one
of each per fused flush — while `host_point_adds` counts every
point add/double the per-set HOST fallback loops perform (cache sums,
weighting ladders, the G2 fold's fallback sum, bisection's oracle
re-derivation): ~0 whenever the device path is healthy, which is what
`make msm-bench` and the sweep tests pin.  All three ride the ordinary
counter path and land in the JSON dump.

The folded pairing product (sigpipe/fold.py) adds the COUNTED perf
invariant the fold bench and tier-1 assert without wall-clock timing:
`miller_loops_per_flush` (an observation — per fused flush, the number
of pairing legs assembled: N+1 folded vs 2N unfolded for an N-set
flush), the labeled `fold_enabled` counter (one `on`/`off` tick per
fused flush, so a snapshot says which assembly every flush used), and
`fold_dispatches` (one `ops.pairing_fold` dispatch per folded flush).
The `scalar_fallbacks` reason vocabulary gains `fold_mismatch`: a
differential-guard trip on the folded path, distinguishable from a
legacy `guard_mismatch` in incident streams.

Incremental merkleization (ssz/incremental.py) reports here too, so one
snapshot covers the whole per-block device story: `merkle_sweep_dispatches`
(one `ssz.merkle_sweep` dispatch per re-rooted tracked view),
`merkle_sweep_levels` (ragged batched level-calls inside those sweeps —
bounded by the state tree height), `merkle_chunks_hashed` (2-to-1 hashes
the sweeps performed — O(diff · log state), the number the merkle bench
asserts scales with diff size), `merkle_dirty_nodes` (dirty leaf chunks
swept) with the power-of-two `merkle_dirty_occupancy` histogram,
`merkle_flat_roots` (roots of flat sequence elements — registry
records, eth1 votes — planned in sweeps from their field values, as
such elements keep no cache of their own),
`merkle_cache_builds` (first full builds of a tracked view),
`merkle_full_rebuilds` (legacy full re-roots taken as the sweep-site
fallback), `merkle_cached_roots` (re-roots answered from cache with no
hashing), and `merkle_guard_samples` / `merkle_guard_mismatches` for the
differential guard.

The async flush engine (pipeline_async.py) reports here:
`async_flushes` / `inline_flushes` (engine-worker vs caller-inline
submits), `abandoned_flushes`, the power-of-two
`flush_inflight_depth` histogram, and
`merkle_device_round_trips` (host<->device transfers per merkle sweep:
1 on the fused device-resident path, one per bulk level otherwise)
with its sibling counters `merkle_sibling_uploads` (literal chunks a
fused sweep actually uploaded) and `merkle_sibling_uploads_skipped`
(clean-sibling level buffers found already device-resident in the
literal pool — the re-uploads the pool exists to skip).

The fused epoch sweep (specs/epoch_fast.py) pins its one-dispatch
contract here: `epoch_sweep_dispatches` counts `ops.epoch_sweep`
seam dispatches — exactly one per `process_epoch` when the device
path is live, which the fork-matrix tests and `make epoch-bench`
assert — while the labeled `epoch_sweep_fallbacks` counter says why
any epoch instead ran the counted numpy twin (`unsupervised`,
`disabled`, `quarantined`, `breaker_open`, `dispatch_failed`).
`epoch_writeback_elems` totals the leaf elements the batched
`bulk_set_basic` writeback pushed into tracked SSZ views (O(1)
Python-level calls per epoch regardless of validator count), and
`epoch_guard_samples` / `epoch_guard_mismatches` record the sampled
lane-level differential guard that quarantines a corrupting device
program before its outputs reach the state.

Every entry counts events or observes sizes: none is read from a
clock, so a (scenario, seed) replay snapshots bit-identically.  Where
time goes is the profiler's business: the program's spans
(utils/spans.py) mark the flush join, each device dispatch, the epoch
phases, the merkle re-roots and the state copy.

Histograms (`observe_hist`) bucket integer observations by
power-of-two: the gossip admission layer records batch occupancy per
flush here (`batch_occupancy`: how many signature sets each dispatch
actually fused — the number that decides whether batching pays), and
the window-flush reason rides a labeled counter (`gossip_window_flushes`:
`deadline` vs `size` vs `drain`).  Buckets instead of raw samples keep
the registry O(log max) per series while still answering "mostly
singletons or mostly full windows?".
"""
from __future__ import annotations

import json

from ..utils import nodectx
from ..utils.locks import named_rlock


class Metrics:
    def __init__(self, node_id: str | None = None):
        self._lock = named_rlock("sigpipe.metrics")
        self.node_id = node_id
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.counters: dict = {}
            self.labeled: dict = {}
            self.observations: dict = {}
            self.histograms: dict = {}

    # -- counters ------------------------------------------------------
    def inc(self, name: str, by: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + by

    def count(self, name: str) -> int:
        with self._lock:
            return self.counters.get(name, 0)

    # -- labeled counters (one counter per (name, label) pair) ---------
    def inc_labeled(self, name: str, label: str, by: int = 1) -> None:
        with self._lock:
            series = self.labeled.setdefault(name, {})
            series[label] = series.get(label, 0) + by

    def count_labeled(self, name: str, label: str | None = None) -> int:
        """Count for one label, or the sum across all labels of `name`."""
        with self._lock:
            series = self.labeled.get(name, {})
            if label is not None:
                return series.get(label, 0)
            return sum(series.values())

    # -- observations (count/total/min/max, no per-sample storage) -----
    def observe(self, name: str, value) -> None:
        with self._lock:
            o = self.observations.get(name)
            if o is None:
                self.observations[name] = {"count": 1, "total": value,
                                           "min": value, "max": value}
            else:
                o["count"] += 1
                o["total"] += value
                o["min"] = min(o["min"], value)
                o["max"] = max(o["max"], value)

    # -- histograms (power-of-two buckets over non-negative ints) ------
    @staticmethod
    def _bucket(value: int) -> str:
        if value <= 0:
            return "0"
        return str(1 << (int(value) - 1).bit_length())

    def observe_hist(self, name: str, value: int) -> None:
        """Count `value` into its power-of-two bucket (1,2,4,8,...):
        bucket "8" holds observations in (4, 8]."""
        bucket = self._bucket(value)
        with self._lock:
            series = self.histograms.setdefault(name, {})
            series[bucket] = series.get(bucket, 0) + 1

    def hist_counts(self, name: str) -> dict:
        with self._lock:
            return dict(self.histograms.get(name, {}))

    # -- export --------------------------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            out = dict(self.counters)
            if self.node_id is not None:
                out["node_id"] = self.node_id
            for name, series in self.labeled.items():
                out[name] = dict(series)
            for name, o in self.observations.items():
                out[name] = dict(o)
                if o["count"]:
                    out[name]["mean"] = o["total"] / o["count"]
            for name, series in self.histograms.items():
                # numeric bucket order so the JSON reads as a histogram
                out[f"{name}_hist"] = {
                    b: series[b]
                    for b in sorted(series, key=lambda s: int(s))}
            # derived rates the dashboards care about
            hits = self.counters.get("pubkey_cache_hits", 0)
            misses = self.counters.get("pubkey_cache_misses", 0)
            if hits + misses:
                out["pubkey_cache_hit_rate"] = round(
                    hits / (hits + misses), 4)
            dedup_hits = self.counters.get("gossip_dedup_hits", 0)
            dedup_misses = self.counters.get("gossip_dedup_misses", 0)
            if dedup_hits + dedup_misses:
                out["gossip_dedup_hit_rate"] = round(
                    dedup_hits / (dedup_hits + dedup_misses), 4)
            return out

    def to_json(self) -> str:
        return json.dumps(self.snapshot(), sort_keys=True)


METRICS = nodectx.Router(Metrics(), "metrics")
