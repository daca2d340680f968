"""Async pipelined flush engine: overlapped verify dispatches and
double-buffered flush state.

A flush used to run strictly back-to-back on the calling thread:
collect -> G1 sweep -> hash-to-G2 -> MSM -> pairing product -> merkle
re-root, each stage waiting on the previous and the caller idle while
the device worked.  This module supplies the two overlap mechanisms the
scheduler and the gossip pipeline now ride:

* **flush double-buffering** (`submit` / :class:`FlushTicket`) — the
  whole batch-verify of flush N runs on ONE long-lived engine worker
  while the submitting thread goes on to host-side work: the gossip
  drainer collects and stages window N+1 (hash_tree_root digests,
  committee prediction, Fiat-Shamir transcripts) and delivers window
  N-1's handlers while N's device dispatches are in flight.  The ticket
  is the explicit join handle — `ticket.result()` is the ONLY way a
  verdict leaves the engine, so the join barrier is a visible call
  site, not an accident of data flow.
* **intra-flush legs** (`launch_leg` / :class:`Leg`) — the one verify
  dispatch with no data dependency on the G1 chain (the hash-to-G2
  cofactor sweep: it needs only the signing roots) launches on a leg
  worker concurrently with prepare + G1 aggregation + Fiat-Shamir
  derivation, and joins at the pairing-product assembly — the verdict
  join barrier (sigpipe/scheduler.py `_verify_fused`).

DRAIN SEMANTICS.  The engine adds NO new failure modes: every device
dispatch inside a submitted flush still crosses its own
`resilience.dispatch` seam, so a breaker trip, watchdog abandon, or
bisection probe inside an in-flight flush degrades on the worker
exactly as it would inline — the ticket then simply delivers the
byte-identical scalar-fallback verdicts.  A ticket the CALLER abandons
(`ticket.abandon()`, or a `result(timeout)` that expires) keeps running
on the worker but its outcome is discarded at the join and, from the
abandonment on, the flush may no longer write shared caches
(`writes_allowed` — sigpipe/cache.py consults it before every insert)
— the same purity discipline as the abandoned merkle sweep
(ssz/incremental.py `_commit`, pinned by test_merkle_inc.py).

SCOPE.  The engine is process-global and deliberately SYNCHRONOUS in
two situations: `ASYNC_FLUSH=0` (the escape hatch — every submit runs
inline on the caller, byte-identical by construction since the worker
would execute the very same closure), and whenever a TRANSIENT node
context is installed (utils/nodectx.py): the context stack is
process-global, so overlapping two simulated nodes' flushes would
interleave push/pop and mis-attribute exactly the incidents the
scenario tier asserts on — fleet simulations therefore run inline.  A
RESIDENT context (`nodectx.pin`, the real node process's one-process/
one-node wiring) is exempt: it sits at the base of the stack for the
process's whole lifetime, every worker thread resolves to the same
context with no push/pop to interleave, so the node process's device
verifies genuinely pipeline (the mesh PR lifted the old blanket
restriction; tests/test_node.py pins async-on/off byte parity of the
served roots).

Observability: the sigpipe counters `async_flushes` / `inline_flushes`
and `abandoned_flushes`, and the power-of-two `flush_inflight_depth`
histogram (tickets in flight at each submit).  How long a caller waits
is a profiler span (utils/spans.py): `sigpipe.join` around the wait in
`FlushTicket.result`, `sigpipe.leg_join` around `Leg.get`.
"""
from __future__ import annotations

import os
import queue
import threading
import time

from ..utils import nodectx
from ..utils.locks import named_condition, named_lock
from ..utils.spans import span
from .metrics import METRICS

# states a ticket moves through (monotonic)
PENDING = "pending"
RUNNING = "running"
DONE = "done"
FAILED = "failed"
ABANDONED = "abandoned"

_FORCED: bool | None = None     # enable()/disable() override; None = env


def enabled() -> bool:
    """Whether flushes are submitted to the engine worker at all.
    `ASYNC_FLUSH=0` (or `off`) is the escape hatch; `enable()` /
    `disable()` override the environment for tests and benches."""
    if _FORCED is not None:
        return _FORCED
    return os.environ.get("ASYNC_FLUSH", "") not in ("0", "off")


def enable() -> None:
    global _FORCED
    _FORCED = True


def disable() -> None:
    global _FORCED
    _FORCED = False


def reset() -> None:
    """Back to the environment default (test teardown)."""
    global _FORCED
    _FORCED = None


def overlap_live() -> bool:
    """True when a submit would actually overlap: async on AND either
    no node context installed or the active context is process-RESIDENT
    (`nodectx.pin` — the real node process).  A transient context (a
    scenario SimNode's `use()` push) still forces inline: the stack is
    process-global, and overlapping two simulated nodes' flushes would
    interleave its push/pop and mis-attribute their records."""
    if not enabled():
        return False
    ctx = nodectx.current()
    return ctx is None or getattr(ctx, "resident", False)


class FlushTicket:
    """Join handle for one in-flight flush.  `result()` blocks for the
    outcome and re-raises nothing: a flush that failed (or that this
    caller abandoned) answers None, which every consumer already treats
    as "no batch verdicts — deliver scalar" (the degradation ladder).
    """

    __slots__ = ("label", "_done", "_state", "_value", "_error", "_lock")

    def __init__(self, label: str):
        self.label = label
        self._done = threading.Event()
        self._state = PENDING
        self._value = None
        self._error = None
        self._lock = named_lock("sigpipe.ticket")

    # -- worker side ---------------------------------------------------
    def _start(self) -> None:
        with self._lock:
            if self._state == PENDING:
                self._state = RUNNING

    def _finish(self, value, error) -> None:
        with self._lock:
            if self._state == ABANDONED:
                # late completion of an abandoned flush: the outcome is
                # dropped on the floor — never installed, never cached
                return
            self._value = value
            self._error = error
            self._state = FAILED if error is not None else DONE
        self._done.set()

    # -- caller side ---------------------------------------------------
    def done(self) -> bool:
        return self._done.is_set()

    def state(self) -> str:
        with self._lock:
            return self._state

    def abandoned(self) -> bool:
        with self._lock:
            return self._state == ABANDONED

    def abandon(self) -> None:
        """Give up on this flush: the worker keeps running it (an XLA
        dispatch cannot be cancelled) but its result is discarded and
        its remaining cache writes are suppressed (`writes_allowed`)."""
        with self._lock:
            if self._state in (DONE, FAILED):
                return
            self._state = ABANDONED
        METRICS.inc("abandoned_flushes")
        self._done.set()    # wake any joiner: the answer is None now

    def result(self, timeout: float | None = None):
        """THE join barrier.  Returns the flush's value, or None when
        the flush failed, was abandoned, or `timeout` expired (the
        ticket is then abandoned — late completion is discarded)."""
        with span("sigpipe.join"):
            finished = self._done.wait(timeout)
        if not finished:
            self.abandon()
            return None
        with self._lock:
            if self._state != DONE:
                if self._error is not None:
                    METRICS.inc("pipeline_errors")
                return None
            return self._value


# speclint: disable=global-mutable-state -- thread-local slot carrying
# the worker's OWN in-flight ticket; by construction never shared
# between threads, so fleet isolation cannot be breached through it
_TL = threading.local()         # .ticket — set on engine/leg workers


def current_ticket() -> FlushTicket | None:
    """The ticket the CURRENT thread is executing (engine/leg workers
    only; None on ordinary threads)."""
    return getattr(_TL, "ticket", None)


def writes_allowed() -> bool:
    """Whether flush-side shared-cache writes may proceed: False only
    on a worker whose ticket the caller has abandoned — from the
    watchdog deadline on, a zombie flush must leave no trace
    (sigpipe/cache.py consults this before every insert)."""
    t = current_ticket()
    return t is None or not t.abandoned()


def bind_current_ticket(fn):
    """Wrap `fn` to execute under the CALLING thread's in-flight ticket
    (identity when there is none).  The resilience supervisor's
    watchdog runs dispatches on per-site worker threads
    (supervisor._SiteWorker) — a plain thread-local would lose the
    flush identity across that hop and an abandoned flush could write
    caches again from the site worker, so the supervisor binds every
    watchdog'd device fn through this before the hand-off."""
    ticket = current_ticket()
    if ticket is None:
        return fn

    def bound():
        prev = getattr(_TL, "ticket", None)
        _TL.ticket = ticket
        try:
            return fn()
        finally:
            _TL.ticket = prev
    return bound


class _Worker:
    """One long-lived daemon worker draining a FIFO queue of (ticket,
    fn) jobs.  FIFO is the determinism contract: tickets complete in
    submit order, so a seeded run's flushes verify in the same order
    the sync path would have."""

    def __init__(self, name: str):
        self._jobs: queue.Queue = queue.Queue()
        self._pending = 0               # queued + running jobs
        self._cv = named_condition("sigpipe.worker_cv")
        self._thread = threading.Thread(
            target=self._loop, name=name, daemon=True)
        self._thread.start()

    def depth(self) -> int:
        with self._cv:
            return self._pending

    def put(self, ticket: FlushTicket, fn) -> None:
        with self._cv:
            self._pending += 1
        self._jobs.put((ticket, fn))

    def join_idle(self, timeout: float) -> bool:
        with self._cv:
            return self._cv.wait_for(lambda: self._pending == 0, timeout)

    def _loop(self) -> None:
        while True:
            ticket, fn = self._jobs.get()
            ticket._start()
            _TL.ticket = ticket
            try:
                ticket._finish(fn(), None)
            except Exception as e:          # shipped across the join
                ticket._finish(None, e)
            except BaseException as e:      # KeyboardInterrupt/SystemExit:
                # finish the ticket so joiners never hang, then let the
                # interrupt kill this thread (never silently convert it
                # into a scalar-fallback window); _worker() respawns
                ticket._finish(None, e)
                raise
            finally:
                _TL.ticket = None
                with self._cv:
                    self._pending -= 1
                    self._cv.notify_all()


# the flush worker (double-buffering) and the leg worker (intra-flush
# dispatch overlap) are separate on purpose: a flush RUNNING on the
# flush worker launches its hash leg on the leg worker, so one thread
# for both would deadlock the leg behind its own flush
_ENGINE_LOCK = named_lock("sigpipe.engine")
_FLUSH_WORKER: _Worker | None = None
_LEG_WORKER: _Worker | None = None


def _worker(which: str) -> _Worker:
    global _FLUSH_WORKER, _LEG_WORKER
    with _ENGINE_LOCK:
        if which == "flush":
            if _FLUSH_WORKER is None or \
                    not _FLUSH_WORKER._thread.is_alive():
                _FLUSH_WORKER = _Worker("sigpipe-flush-engine")
            return _FLUSH_WORKER
        if _LEG_WORKER is None or not _LEG_WORKER._thread.is_alive():
            _LEG_WORKER = _Worker("sigpipe-flush-leg")
        return _LEG_WORKER


def submit(fn, label: str = "flush") -> FlushTicket:
    """Submit one flush's batch-verify closure; returns its ticket.
    Inline (executed on the caller before returning, ticket already
    done) when overlap is off — byte-identical by construction: the
    worker would run the exact same closure."""
    ticket = FlushTicket(label)
    if not overlap_live():
        METRICS.inc("inline_flushes")
        ticket._start()
        try:
            ticket._finish(fn(), None)
        except Exception as e:
            # Exception only: a Ctrl-C mid-flush must propagate exactly
            # as the pre-engine direct call would have, not degrade the
            # window to scalar delivery
            ticket._finish(None, e)
        return ticket
    worker = _worker("flush")
    METRICS.inc("async_flushes")
    METRICS.observe_hist("flush_inflight_depth", worker.depth() + 1)
    worker.put(ticket, fn)
    return ticket


class Leg:
    """Join handle for one intra-flush dispatch leg.  Unlike a ticket,
    `get()` RE-RAISES the leg's exception: a leg stands in for an
    inline call (the scheduler's hash-to-G2 dispatch), so its errors
    must surface at the join with the same types the inline call would
    have raised there."""

    __slots__ = ("_ticket",)

    def __init__(self, ticket: FlushTicket):
        self._ticket = ticket

    def get(self):
        with span("sigpipe.leg_join"):
            self._ticket._done.wait()
        with self._ticket._lock:
            if self._ticket._error is not None:
                raise self._ticket._error
            return self._ticket._value


def launch_leg(fn, label: str) -> Leg:
    """Run `fn` on the leg worker concurrently with the caller's own
    dispatch chain; join with `Leg.get()` at the verdict barrier.
    Inline when overlap is off."""
    ticket = FlushTicket(label)
    if not overlap_live():
        ticket._start()
        try:
            ticket._finish(fn(), None)
        except Exception as e:      # Leg.get() re-raises at the join
            ticket._finish(None, e)
        return Leg(ticket)
    _worker("leg").put(ticket, fn)
    return Leg(ticket)


def drain(timeout: float = 30.0) -> bool:
    """Block until every submitted flush and leg has completed (the
    breaker-trip / shutdown discipline: nothing may still be in flight
    when the caller re-reads shared state).  Returns False on timeout.
    """
    deadline = time.perf_counter() + timeout
    with _ENGINE_LOCK:
        # snapshot under the engine lock: _worker() may be respawning a
        # dead worker concurrently, and a torn read here would join an
        # orphaned instance while jobs land on its replacement
        workers = (_FLUSH_WORKER, _LEG_WORKER)
    for w in workers:
        if w is None:
            continue
        if not w.join_idle(max(deadline - time.perf_counter(), 0.0)):
            return False
    return True
