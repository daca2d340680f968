"""Graceful-degradation supervisor for accelerator dispatches.

`dispatch(site, device_fn, fallback_fn)` is the single seam every
accelerator entry point routes through (utils/bls.py batch APIs and
pairing check, sigpipe's hash-to-G2 sweep, ssz/merkle device hashing,
kzg's device MSM).  With no supervisor enabled it is a two-attribute
read and a profiler span around the call — behavior byte-identical to
the unwrapped code, including exception propagation.

With a supervisor enabled, each site gets a circuit breaker:

    CLOSED ──failures ≥ threshold──▶ OPEN ──probe_after fallbacks──▶
    HALF_OPEN ──probe ok──▶ CLOSED   (probe fails ─▶ OPEN again)

* Transient faults are absorbed in place: up to `max_retries` in-call
  retries with exponential backoff, never visible to the caller.
* Persistent faults trip the breaker; every dispatch at that site then
  takes the native fallback — same values, same exceptions at the same
  operation boundary, because the fallback IS the scalar-oracle code
  path — until a half-open probe answers correctly again.
* A watchdog deadline (optional) runs the dispatch on a daemon worker
  thread and abandons it on expiry: an XLA dispatch cannot be cancelled,
  but the block-processing thread must not hang with it.  The abandoned
  thread parks on the dead dispatch and is never joined — the same
  discipline production clients use for a wedged device runtime.
* `quarantine()` (the differential guard's verdict-corruption response)
  is an OPEN state that never half-opens: silent corruption means the
  device cannot be trusted to self-report recovery, so only an explicit
  operator `reset()` re-arms the accelerator path.

Degradation is observable, not silent: every retry/trip/probe/restore
lands in the incident log, and every fallback increments the
reason-labeled `scalar_fallbacks` counter (`dispatch_failed` for a
failed call below the trip threshold, `breaker_open` once tripped,
`guard_mismatch` / the quarantine reason, `disabled` for the forced
kill switch) — the reason always agrees with the breaker-state map.
"""
from __future__ import annotations

import queue
import threading
from dataclasses import dataclass, field

from ..sigpipe.metrics import METRICS
from ..utils import nodectx, spans
from ..utils.clock import MONOTONIC
from ..utils.locks import named_lock, named_rlock
from . import faults
from .incidents import INCIDENTS

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"
QUARANTINED = "quarantined"


class DispatchTimeout(RuntimeError):
    """Watchdog deadline expired before the dispatch answered."""


@dataclass
class SupervisorConfig:
    max_retries: int = 2          # in-call retries before a failure counts
    backoff_base_s: float = 0.0   # first retry delay; doubles per retry
    breaker_threshold: int = 3    # consecutive failed calls until trip
    probe_after: int = 4          # fallback calls in OPEN before a probe
    cooldown_s: float = 0.0       # min wall-clock in OPEN before a probe
    deadline_s: float | None = None   # watchdog; None = no watchdog
    # decision clock (utils/clock.py): breaker cooldown reads and retry
    # backoff sleeps go through it so chaos schedules replay
    # deterministically under a ManualClock.  The watchdog deadline
    # stays on real thread waits — it times an actual worker thread,
    # which no virtual clock can advance.
    clock: object = field(default_factory=lambda: MONOTONIC)


class _Breaker:
    __slots__ = ("state", "consecutive_failures", "fallbacks_since_trip",
                 "tripped_at", "trips", "restores", "quarantine_reason")

    def __init__(self):
        self.state = CLOSED
        self.consecutive_failures = 0
        self.fallbacks_since_trip = 0
        self.tripped_at = 0.0
        self.trips = 0
        self.restores = 0
        self.quarantine_reason = None


class _SiteWorker:
    """One long-lived daemon worker per dispatch site for watchdog'd
    calls: the healthy path pays a queue hand-off, not a thread spawn.
    On deadline expiry the worker is abandoned (it parks on the hung
    dispatch, finishes it whenever the runtime lets go, then exits) and
    the site gets a fresh worker on the next call."""

    def __init__(self, site: str):
        self._jobs: queue.Queue = queue.Queue()
        self.abandoned = False
        self._thread = threading.Thread(
            target=self._loop, name=f"dispatch-{site}", daemon=True)
        self._thread.start()

    def _loop(self):
        while True:
            fn, box, done = self._jobs.get()
            if fn is not None:
                try:
                    box.append((True, fn()))
                except BaseException as e:   # shipped across the boundary
                    box.append((False, e))
                done.set()
            if self.abandoned:
                return

    def call(self, fn, deadline: float):
        """Run fn on the worker; returns (ok, value-or-exception), or
        None if the deadline expired (worker now abandoned)."""
        box: list = []
        done = threading.Event()
        self._jobs.put((fn, box, done))
        if not done.wait(deadline):
            self.abandoned = True
            # wake the worker if the job actually finished just now, so
            # a non-hung abandoned worker exits instead of parking on an
            # empty queue forever
            self._jobs.put((None, None, None))
            return None
        return box[0]


class Supervisor:
    def __init__(self, config: SupervisorConfig | None = None, **overrides):
        self.config = config or SupervisorConfig(**overrides)
        self._clock = self.config.clock
        self._breakers: dict = {}
        self._workers: dict = {}
        self._worker_locks: dict = {}
        self._lock = named_rlock("resilience.supervisor")
        self._forced_scalar = False

    @property
    def forced_scalar(self) -> bool:
        """True while the force_scalar() kill switch is held on."""
        return self._forced_scalar

    # -- administrative controls --------------------------------------
    def force_scalar(self, on: bool = True) -> None:
        """Administratively disable the accelerator path (every dispatch
        takes the fallback, reason `disabled`) — the bench degraded tier
        and operator kill switches use this."""
        self._forced_scalar = bool(on)

    def quarantine(self, site: str, reason: str = "guard_mismatch") -> None:
        """Permanently open `site` (no half-open probes) until reset().
        `reason` labels both the incident and every subsequent fallback
        the quarantine forces."""
        with self._lock:
            br = self._breaker(site)
            if br.state != QUARANTINED:
                br.state = QUARANTINED
                br.quarantine_reason = reason
                br.tripped_at = self._clock.now()
                br.trips += 1
                METRICS.inc("breaker_trips")
                METRICS.inc("quarantines")
                INCIDENTS.record(site, "quarantine", reason=reason)

    def reset(self, site: str | None = None) -> None:
        """Re-arm one site's breaker, or all of them."""
        with self._lock:
            sites = [site] if site is not None else list(self._breakers)
            for s in sites:
                br = self._breakers.get(s)
                if br is not None and br.state != CLOSED:
                    INCIDENTS.record(s, "reset", previous=br.state)
                self._breakers.pop(s, None)

    def breaker_state(self, site: str) -> str:
        with self._lock:
            br = self._breakers.get(site)
            return br.state if br is not None else CLOSED

    def breaker_states(self) -> dict:
        with self._lock:
            return {site: br.state for site, br in self._breakers.items()}

    # -- the seam ------------------------------------------------------
    def run(self, site: str, device_fn, fallback_fn):
        if self._forced_scalar:
            return self._fallback(site, fallback_fn, "disabled")
        with self._lock:
            br = self._breaker(site)
            state = br.state
            if state == OPEN:
                br.fallbacks_since_trip += 1
                if (br.fallbacks_since_trip >= self.config.probe_after
                        and (self._clock.now() - br.tripped_at
                             >= self.config.cooldown_s)):
                    br.state = state = HALF_OPEN
                    INCIDENTS.record(site, "probe")
                    METRICS.inc("breaker_probes")
        if state == QUARANTINED:
            return self._fallback(site, fallback_fn,
                                  br.quarantine_reason or "guard_mismatch")
        if state == OPEN:
            return self._fallback(site, fallback_fn, "breaker_open")
        # CLOSED or HALF_OPEN: attempt the device path, with in-call
        # retries for transient faults
        attempt = 0
        while True:
            try:
                result = self._call(site, device_fn)
            except Exception as e:
                attempt += 1
                kind = ("timeout" if isinstance(e, DispatchTimeout)
                        else "dispatch_error")
                INCIDENTS.record(site, kind, attempt=attempt,
                                 error=f"{type(e).__name__}: {e}")
                if state != HALF_OPEN and attempt <= self.config.max_retries:
                    METRICS.inc("dispatch_retries")
                    backoff = self.config.backoff_base_s * (
                        2 ** (attempt - 1))
                    if backoff > 0:
                        self._clock.sleep(backoff)
                    continue
                self._on_failure(site, br, state)
                # label by what the breaker actually did: below the trip
                # threshold this call failed but the site is still live
                reason = ("breaker_open"
                          if br.state in (OPEN, QUARANTINED)
                          else "dispatch_failed")
                return self._fallback(site, fallback_fn, reason)
            else:
                self._on_success(site, br, state, recovered=attempt > 0)
                return result

    # -- internals -----------------------------------------------------
    def _breaker(self, site: str) -> _Breaker:
        br = self._breakers.get(site)
        if br is None:
            br = self._breakers[site] = _Breaker()
        return br

    def _call(self, site: str, fn):
        deadline = self.config.deadline_s
        if deadline is None:
            return fn()
        # serialize watchdog'd calls per site: a job is only handed to
        # the worker when it is idle, so the deadline clocks the
        # dispatch itself — a caller queued behind a slow-but-healthy
        # dispatch waits on the site lock (uncounted), never inherits
        # the previous job's elapsed time as its own timeout
        with self._lock:
            site_lock = self._worker_locks.get(site)
            if site_lock is None:
                site_lock = self._worker_locks[site] = named_lock(
                    "resilience.site_worker")
        with site_lock:
            with self._lock:
                worker = self._workers.get(site)
                if worker is None or worker.abandoned:
                    worker = self._workers[site] = _SiteWorker(site)
            # carry the async flush engine's in-flight ticket across
            # the thread hop: the abandoned-flush cache-write
            # suppression (pipeline_async.writes_allowed) is
            # thread-local and must follow the dispatch onto this
            # site's worker
            from ..sigpipe.pipeline_async import bind_current_ticket
            outcome = worker.call(bind_current_ticket(fn), deadline)
        if outcome is None:
            # abandoned: the worker parks on the hung dispatch; the next
            # call gets a fresh one
            METRICS.inc("watchdog_timeouts")
            raise DispatchTimeout(
                f"dispatch at {site} exceeded {deadline}s watchdog")
        ok, value = outcome
        if not ok:
            raise value
        return value

    def _on_failure(self, site: str, br: _Breaker, state: str) -> None:
        with self._lock:
            br.consecutive_failures += 1
            if state == HALF_OPEN:
                # failed probe: back to OPEN, wait a full window again
                br.state = OPEN
                br.fallbacks_since_trip = 0
                br.tripped_at = self._clock.now()
                INCIDENTS.record(site, "probe_failed")
                METRICS.inc("breaker_probe_failures")
            elif (br.state == CLOSED and br.consecutive_failures
                    >= self.config.breaker_threshold):
                br.state = OPEN
                br.fallbacks_since_trip = 0
                br.tripped_at = self._clock.now()
                br.trips += 1
                INCIDENTS.record(
                    site, "trip", failures=br.consecutive_failures)
                METRICS.inc("breaker_trips")

    def _on_success(self, site: str, br: _Breaker, state: str,
                    recovered: bool) -> None:
        with self._lock:
            br.consecutive_failures = 0
            if state == HALF_OPEN:
                br.state = CLOSED
                br.restores += 1
                INCIDENTS.record(site, "restore")
                METRICS.inc("breaker_restores")
            elif recovered:
                INCIDENTS.record(site, "retry_recovered")

    def _fallback(self, site: str, fallback_fn, reason: str):
        METRICS.inc_labeled("scalar_fallbacks", reason)
        return fallback_fn()


# The active supervisor is a per-node-context ROUTER (the
# INCIDENTS/METRICS discipline): a SimNode that owns a `supervisor`
# Slot gets its own breaker table — a trip, quarantine, or
# force_scalar on node 3 leaves nodes 0-2 on the device path — while
# callers with no node context installed land on the process-global
# default cell exactly as before.
_ACTIVE = nodectx.StateRouter("supervisor")


def enable(config: SupervisorConfig | None = None, **overrides) -> Supervisor:
    """Install a supervisor at every dispatch seam (for the active node
    context's slot when one is installed, else process-global);
    returns it."""
    sup = Supervisor(config, **overrides)
    _ACTIVE.set(sup)
    return sup


def disable() -> None:
    _ACTIVE.set(None)


def enabled() -> bool:
    return _ACTIVE.get() is not None


def active() -> Supervisor | None:
    return _ACTIVE.get()


def dispatch(site: str, device_fn, fallback_fn):
    """THE accelerator dispatch seam.

    `device_fn` runs the accelerated path (whatever backend is selected);
    `fallback_fn` is the native-scalar oracle path with byte-identical
    semantics.  Fault injection (faults.py) wraps `device_fn` only — the
    fallback is the trusted path, which is exactly what makes
    trip-to-scalar a *recovery* and not a different failure mode.

    The call, supervised or not, runs inside the profiler span
    `dispatch:<site>` (utils/spans.py): host wall time over the device
    call with its transfers and readback, or over the fallback taken
    in its place.
    """
    plan = faults.active_plan()
    fn = plan.wrap(site, device_fn) if plan is not None else device_fn
    sup = _ACTIVE.get()
    with spans.span(spans.DISPATCH + site):
        if sup is None:
            return fn()
        return sup.run(site, fn, fallback_fn)
