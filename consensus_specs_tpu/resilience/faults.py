"""Deterministic fault injection at the accelerator dispatch seams.

A `FaultPlan` holds `FaultSpec`s — one per targeted dispatch site — and a
seeded RNG; `inject(plan)` installs it so every `resilience.dispatch()`
call consults the plan before running the device function.  Four fault
kinds model the ways a real accelerator dispatch goes wrong:

* ``raise``   — the dispatch dies with a `DeviceFault` (XLA runtime error,
                lost device, OOM): loud, immediate.
* ``timeout`` — the dispatch hangs: the injected function sleeps past the
                supervisor's watchdog deadline before answering.  Without
                a supervisor it is merely slow — exactly like a real hang.
* ``corrupt`` — the dispatch *answers wrong*: a verdict bool (or one
                element of a verdict list) is silently flipped.  No
                exception, no signal — only the differential guard can
                catch this one.
* ``shard_dead`` — one seeded device of the verify mesh dies under a
                SHARDED dispatch (registry `sharded=True` sites): the
                runtime surfaces a dead mesh member as a failed launch,
                so the seam sees a raised `ShardDead` (a `DeviceFault`)
                and the incident log records which shard died.  Same
                breaker → scalar-fallback → half-open contract as
                ``raise`` — "one shard of the mesh died" is just
                another fault.

Transient vs persistent: a transient spec fires on a seeded coin-flip per
call (bounded by `max_fires`); a persistent spec fires on every call once
triggered — the model of a wedged device that will not heal until the
breaker quarantines it.

Every fired fault is recorded in the incident log (event ``injected``)
and counted in METRICS *by the injector itself*, so the chaos tier can
assert "every injected fault is visible" without trusting the component
under test to have noticed.

Determinism: decisions come from `random.Random(seed)` in call order, so
a single-threaded replay with the same plan injects the same faults at
the same dispatches.
"""
from __future__ import annotations

import random
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

from ..sigpipe.metrics import METRICS
from ..utils import nodectx
from ..utils.locks import named_rlock
from . import sites
from .incidents import INCIDENTS

KINDS = ("raise", "timeout", "corrupt", "shard_dead")


class DeviceFault(RuntimeError):
    """Injected stand-in for a raised device/runtime error."""


class ShardDead(DeviceFault):
    """One device of the verify mesh died mid-dispatch — it raised, or
    returned garbage the collective's checksum rejected.  Either way
    the XLA runtime surfaces a dead mesh member as a FAILED launch, so
    at the dispatch seam "one shard died" is just another raised
    fault: same retry → breaker-trip → scalar-fallback → half-open
    contract (parallel/shard_verify.py owns the sharded entry points;
    its `poison_shard` hook models the returns-garbage flavor with
    real data in the kernel tier)."""

    def __init__(self, site: str, shard: int, fire: int):
        super().__init__(
            f"injected dead mesh shard {shard} at {site} (fire {fire})")
        self.shard = shard


def _mesh_width() -> int:
    """Shards a seeded shard_dead fault can kill: the live verify-mesh
    width, 1 when the mesh (or jax itself) is unavailable — the fault
    still fires, modeling the last chip of a 1-wide mesh."""
    try:
        from ..parallel.shard_verify import mesh_devices
        return max(mesh_devices(), 1)
    except Exception:
        return 1


@dataclass
class FaultSpec:
    site: str                    # dispatch site name (exact match)
    kind: str                    # "raise" | "timeout" | "corrupt"
    rate: float = 1.0            # per-call fire probability (seeded)
    persistent: bool = False     # once fired, fire on every later call
    max_fires: int | None = None  # cap for transient specs (None: no cap)
    sleep_s: float = 0.05        # hang duration for kind="timeout"
    fires: int = field(default=0, compare=False)
    _triggered: bool = field(default=False, compare=False)

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}")


def _is_bool(v) -> bool:
    return isinstance(v, bool) or type(v).__name__ == "bool_"  # np.bool_


# sites whose dispatch result is a root digest guarded by a
# differential oracle check — ONLY these get bytes corruption (a bytes
# result at an unguarded site, e.g. ops.sha256.hash_level, has no
# quarantine path, so corrupting it would just break the byte-identical
# invariant instead of modeling a catchable silent fault).  Derived from
# the canonical site registry (corrupt="digest" entries).
_DIGEST_GUARDED_SITES = sites.digest_guarded_sites()

# sites whose dispatch result is a tuple of numpy lane arrays guarded by
# a differential oracle (corrupt="lanes" entries): corruption damages
# one element of one array — the silent-lane fault only the sampled
# guard comparison can catch.
_LANES_GUARDED_SITES = sites.lanes_guarded_sites()


def _flip_verdict(result, rng: random.Random, site: str | None = None):
    """Corrupt a verdict-shaped result: flip a bool, one element of a
    list of bools, at digest-guarded sites one bit of a bytes root, or
    at lanes-guarded sites one element of one numpy lane array (the
    silent corruption only the differential guard can catch).  Other
    payloads pass through unchanged (a corrupted point batch surfaces
    as a False product, which the `raise` path already covers)."""
    if _is_bool(result):
        return not bool(result)
    if isinstance(result, list) and result and all(
            _is_bool(v) for v in result):
        out = [bool(v) for v in result]
        j = rng.randrange(len(out))
        out[j] = not out[j]
        return out
    if (site in _DIGEST_GUARDED_SITES
            and isinstance(result, (bytes, bytearray)) and result):
        out = bytearray(result)
        j = rng.randrange(len(out))
        out[j] ^= 1 << rng.randrange(8)
        return bytes(out)
    if (site in _LANES_GUARDED_SITES and isinstance(result, tuple)
            and result and all(hasattr(a, "dtype") for a in result)):
        lanes = [a.copy() for a in result]
        k = rng.randrange(len(lanes))
        arr = lanes[k]
        if arr.size:
            j = rng.randrange(arr.size)
            flat = arr.reshape(-1)
            if flat.dtype.kind == "b":
                flat[j] = not bool(flat[j])
            else:
                flat[j] = flat[j] ^ 1
        return tuple(lanes)
    return result


class FaultPlan:
    """Seeded schedule of faults over named dispatch sites."""

    def __init__(self, specs, seed: int = 0):
        self.specs = list(specs)
        self.seed = seed
        self._rng = random.Random(seed)
        self._lock = named_rlock("resilience.faults")
        by_site: dict = {}
        for s in self.specs:
            by_site.setdefault(s.site, []).append(s)
        self._by_site = by_site

    def _should_fire(self, spec: FaultSpec) -> bool:
        if spec.persistent and spec._triggered:
            return True
        if spec.max_fires is not None and spec.fires >= spec.max_fires:
            return False
        if self._rng.random() >= spec.rate:
            return False
        spec._triggered = True
        return True

    def decide(self, site: str) -> FaultSpec | None:
        """The spec firing at this call to `site`, if any (first match
        wins; records the injection)."""
        with self._lock:
            for spec in self._by_site.get(site, ()):
                if self._should_fire(spec):
                    spec.fires += 1
                    METRICS.inc("faults_injected")
                    METRICS.inc_labeled("faults_injected_by_kind",
                                        spec.kind)
                    INCIDENTS.record(site, "injected", kind=spec.kind,
                                     persistent=spec.persistent,
                                     fire=spec.fires)
                    return spec
            return None

    def wrap(self, site: str, fn):
        """Device function -> possibly-faulting device function.  The
        decision is made per CALL (at invocation time), so retries of the
        same dispatch re-roll the schedule — a transient fault heals, a
        persistent one keeps firing."""
        if site not in self._by_site:
            return fn

        def faulty():
            spec = self.decide(site)
            if spec is None:
                return fn()
            if spec.kind == "raise":
                raise DeviceFault(f"injected fault at {site} "
                                  f"(fire {spec.fires})")
            if spec.kind == "shard_dead":
                # a seeded mesh member dies; the launch fails loud
                # (ShardDead is a DeviceFault: the breaker contract is
                # identical, the incident records WHICH shard).  The
                # shard draw rides the plan lock like every other draw:
                # concurrent dispatches racing the seeded stream would
                # otherwise de-determinize the schedule
                with self._lock:
                    shard = self._rng.randrange(_mesh_width())
                INCIDENTS.record(site, "shard_dead", shard=shard,
                                 fire=spec.fires)
                raise ShardDead(site, shard, spec.fires)
            if spec.kind == "timeout":
                time.sleep(spec.sleep_s)
                return fn()
            # corrupt: silently flip the verdict.  The dispatch itself
            # runs OUTSIDE the plan lock (holding it across a device
            # call would serialize every site behind one flush); only
            # the flip's draws are serialized
            result = fn()
            with self._lock:
                return _flip_verdict(result, self._rng, site)
        return faulty

    def total_fires(self) -> int:
        with self._lock:
            return sum(s.fires for s in self.specs)


# The active plan is a per-node-context ROUTER: a SimNode that owns a
# `fault_plan` Slot has its own seeded schedule (possibly empty — a
# Slot holding None is "no faults for THIS node", never a fall-through
# to a globally injected plan), so the scenario generator can kill one
# node's device while the rest of the fleet stays healthy.  Callers
# with no node context land on the process-global default cell.
_ACTIVE = nodectx.StateRouter("fault_plan")


def active_plan() -> FaultPlan | None:
    return _ACTIVE.get()


def fire(site: str) -> None:
    """Consult the active plan at a *barrier* site — a named point in a
    control path that produces no value to corrupt (a store mutation, a
    commit boundary, a journal write; the txn/ subsystem's kill points).
    A ``raise`` spec dies here with a `DeviceFault` (the simulated
    crash), a ``timeout`` spec stalls, and a ``corrupt`` spec is a no-op
    beyond being recorded — there is no verdict at a barrier to flip.
    With no plan installed this is one routed read."""
    plan = _ACTIVE.get()
    if plan is None:
        return
    spec = plan.decide(site)
    if spec is None:
        return
    if spec.kind == "raise":
        raise DeviceFault(f"injected crash at {site} (fire {spec.fires})")
    if spec.kind == "timeout":
        time.sleep(spec.sleep_s)


@contextmanager
def inject(plan: FaultPlan):
    """Install `plan` at every dispatch seam for the duration — into
    the active node context's plan slot when one is installed (and
    still installed at exit: enter and exit must see the same
    context), else process-global."""
    previous = _ACTIVE.get()
    _ACTIVE.set(plan)
    try:
        yield plan
    finally:
        _ACTIVE.set(previous)
