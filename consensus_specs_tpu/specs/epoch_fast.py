"""Fused epoch processing behind the `ops.epoch_sweep` dispatch seam.

The reference's epoch passes are per-validator Python loops over O(n)
validators with O(n) helpers inside (e.g. `get_base_reward` recomputing
the total active balance), which is quadratic at mainnet scale
(reference: specs/phase0/beacon-chain.md:1553-1589, altair:385-421).
This engine extracts a structure-of-arrays snapshot of the validator
registry ONCE per epoch (`StateArrays`), precomputes the
committee-dependent masks and global scalars on host, and hands every
hot pass — attestation / participation-flag delta sets, inactivity
scores, slashings, effective-balance hysteresis, registry-eligibility
masks — to ONE registered device dispatch::

    resilience.dispatch("ops.epoch_sweep", device_fn, numpy_fallback)

`ops/epoch_sweep.py` holds the fused jitted program (the only module
allowed to import it is this one — speclint `epoch-scalar-bypass`);
`numpy_sweep` here is the counted, byte-identical fallback AND the
differential-guard oracle.  Writeback is batched through
`ssz.incremental.bulk_set_basic` — one Python-level call per mutated
column (balances, inactivity scores), marking the dirty merkle cone in
one pass — so a mainnet everyone's-balance-changed epoch no longer pays
1M `__setitem__` round trips and the re-root stays the O(dirty) fused
device sweep.  The rare per-validator mutations (registry churn,
effective-balance hysteresis hits) stay scalar spec calls.

Escape hatches: `scalar_epoch()` restores the reference-shaped scalar
pass list (differential testing, the bench scalar leg);
`supervisor.force_scalar()` keeps the fused shape but pins the numpy
fallback (counted, reason `disabled`).  `set_guard(rate, seed)` arms
sampled lane-for-lane comparison of device output against the numpy
oracle — a mismatch quarantines the site and returns the oracle lanes.

Public surface (everything else is engine-internal — speclint
`epoch-scalar-bypass` flags outside access): ENABLED, SWEEP_SITE,
scalar_epoch, numpy_epoch, fused_epoch, set_guard.
"""
from __future__ import annotations

import contextlib
import random
from math import isqrt

import numpy as np

from ..utils.spans import span

ENABLED = True
_ON_DEVICE = True

SWEEP_SITE = "ops.epoch_sweep"

_I64MAX = np.iinfo(np.int64).max
_ORDER_BITS = 24          # attestations per epoch < 2**24; delay keys above

_GUARD_RATE = 0.0
_GUARD_RNG = random.Random(0)


@contextlib.contextmanager
def scalar_epoch():
    """Temporarily disable the fused engine (differential testing)."""
    global ENABLED
    prev, ENABLED = ENABLED, False
    try:
        yield
    finally:
        ENABLED = prev


@contextlib.contextmanager
def numpy_epoch():
    """Temporarily run the fused engine's lanes on the numpy twin
    instead of the device program — the host side of a device/host
    differential run (no dispatch is made or counted)."""
    global _ON_DEVICE
    prev, _ON_DEVICE = _ON_DEVICE, False
    try:
        yield
    finally:
        _ON_DEVICE = prev


def set_guard(rate: float, seed: int = 0) -> None:
    """Differential-guard sampling probability for the fused sweep
    (production: low single-digit percent; the chaos tier runs 1.0).
    A sampled epoch recomputes every lane through `numpy_sweep` and
    compares; a mismatch quarantines `ops.epoch_sweep` and the oracle
    lanes are the ones written back."""
    global _GUARD_RATE, _GUARD_RNG
    if not 0.0 <= rate <= 1.0:
        raise ValueError(f"guard rate {rate} outside [0, 1]")
    _GUARD_RATE = rate
    _GUARD_RNG = random.Random(seed)


# ---------------------------------------------------------------------------
# structure-of-arrays extraction
# ---------------------------------------------------------------------------

class StateArrays:
    """Validator-axis columns of the BeaconState (read-only snapshot)."""

    def __init__(self, state):
        vs = state.validators
        n = len(vs)
        self.n = n
        self.eff = np.fromiter(
            (int(v.effective_balance) for v in vs), np.int64, n)
        self.slashed = np.fromiter((bool(v.slashed) for v in vs), bool, n)
        self.activation_eligibility = np.fromiter(
            (int(v.activation_eligibility_epoch) for v in vs), np.uint64, n)
        self.activation = np.fromiter(
            (int(v.activation_epoch) for v in vs), np.uint64, n)
        self.exit = np.fromiter(
            (int(v.exit_epoch) for v in vs), np.uint64, n)
        self.withdrawable = np.fromiter(
            (int(v.withdrawable_epoch) for v in vs), np.uint64, n)
        self.balances = np.fromiter(
            (int(b) for b in state.balances), np.int64, n)

    def active(self, epoch) -> np.ndarray:
        e = np.uint64(int(epoch))
        return (self.activation <= e) & (e < self.exit)

    def eligible(self, previous_epoch) -> np.ndarray:
        """Reference get_eligible_validator_indices semantics."""
        prev = int(previous_epoch)
        return self.active(prev) | (
            self.slashed & (np.uint64(prev + 1) < self.withdrawable))

    def total_active_balance(self, epoch, increment) -> int:
        return max(int(increment), int(self.eff[self.active(epoch)].sum()))


# ---------------------------------------------------------------------------
# phase0: attestation participation masks
# ---------------------------------------------------------------------------

def phase0_attestation_masks(spec, state, epoch, targets_only=False):
    """source/target/head attester masks for `epoch`'s pending attestations
    plus, per source attester, the minimal-inclusion-delay key and its
    proposer (reference beacon-chain.md:1497-1551 matching helpers).

    `targets_only` skips the head/inclusion-delay bookkeeping — the
    justification pass needs only the target mask."""
    n = len(state.validators)
    src = np.zeros(n, bool)
    tgt = np.zeros(n, bool)
    head = np.zeros(n, bool)
    best_key = np.full(n, _I64MAX, np.int64)
    best_prop = np.zeros(n, np.int64)
    atts = spec.get_matching_source_attestations(state, epoch)
    if not atts:
        return src, tgt, head, best_key, best_prop
    target_root = spec.get_block_root(state, epoch)
    for order, a in enumerate(atts):
        committee = spec.get_beacon_committee(
            state, a.data.slot, a.data.index)
        m = len(committee)
        comm = np.fromiter((int(c) for c in committee), np.int64, m)
        bits = np.fromiter(
            (bool(b) for b in a.aggregation_bits), bool, m)
        att = comm[bits]
        src[att] = True
        if a.data.target.root == target_root:
            tgt[att] = True
            if not targets_only and a.data.beacon_block_root == \
                    spec.get_block_root_at_slot(state, int(a.data.slot)):
                head[att] = True
        if targets_only:
            continue
        key = (int(a.inclusion_delay) << _ORDER_BITS) | order
        upd = key < best_key[att]
        best_key[att] = np.where(upd, key, best_key[att])
        best_prop[att] = np.where(upd, int(a.proposer_index), best_prop[att])
    return src, tgt, head, best_key, best_prop


def _participation(state, which: str, n: int) -> np.ndarray:
    col = (state.previous_epoch_participation if which == "previous"
           else state.current_epoch_participation)
    return np.fromiter((int(x) for x in col), np.int64, n)


# ---------------------------------------------------------------------------
# sweep inputs: everything the fused program needs, host-extracted once
# ---------------------------------------------------------------------------

class SweepInputs:
    """Immutable-by-convention bundle crossing the dispatch seam.

    `family` is "phase0" or "altair"; `cols` maps the family's column
    names (ops.epoch_sweep.{PHASE0,ALTAIR}_COLS) to length-n numpy
    arrays; `scalars` maps the family's scalar names to numpy 0-d
    values; `statics` is a sorted tuple of (name, value) pairs baked
    into the compiled program (the compile-cache key)."""

    __slots__ = ("family", "n", "cols", "scalars", "statics")

    def __init__(self, family, n, cols, scalars, statics):
        self.family = family
        self.n = n
        self.cols = cols
        self.scalars = scalars
        self.statics = statics


def _collect(spec, state, arr, part_prev, masks_prev, do_rewards, leak,
             tb, cur, prev):
    """Build SweepInputs from the post-justification state.  Every value
    here is read ONCE; the sweep (device or numpy) is a pure function of
    this bundle."""
    n = arr.n
    incr = int(spec.EFFECTIVE_BALANCE_INCREMENT)
    altair_family = bool(spec.is_post("altair"))
    electra = bool(spec.is_post("electra"))
    finalized = int(state.finalized_checkpoint.epoch)
    adj = min(sum(int(x) for x in state.slashings)
              * int(spec.proportional_slashing_multiplier()), tb)
    slash_epoch = cur + int(spec.EPOCHS_PER_SLASHINGS_VECTOR) // 2
    if electra:
        prefix = np.fromiter(
            (v.withdrawal_credentials[0] for v in state.validators),
            np.uint8, n)
        comp = prefix == int.from_bytes(
            bytes(spec.COMPOUNDING_WITHDRAWAL_PREFIX), "big")
        max_eff = np.where(comp, int(spec.MAX_EFFECTIVE_BALANCE_ELECTRA),
                           int(spec.MIN_ACTIVATION_BALANCE)).astype(np.int64)
    else:
        max_eff = np.full(n, int(spec.MAX_EFFECTIVE_BALANCE), np.int64)
    cols = {
        "eff": arr.eff, "slashed": arr.slashed,
        "activation": arr.activation, "exit_epoch": arr.exit,
        "act_elig": arr.activation_eligibility,
        "withdrawable": arr.withdrawable,
        "balances": arr.balances, "max_eff": max_eff,
    }
    scalars = {
        "cur": np.uint64(cur), "prev": np.uint64(prev),
        "finalized": np.uint64(finalized),
        "slash_epoch": np.uint64(slash_epoch),
        "tb": np.int64(tb), "adj": np.int64(adj),
    }
    statics = {
        "do_rewards": bool(do_rewards), "leak": bool(leak), "incr": incr,
        "max_eb": int(spec.MAX_EFFECTIVE_BALANCE),
        "ejection": int(spec.config.EJECTION_BALANCE),
        "hyst_q": int(spec.HYSTERESIS_QUOTIENT),
        "hyst_down": int(spec.HYSTERESIS_DOWNWARD_MULTIPLIER),
        "hyst_up": int(spec.HYSTERESIS_UPWARD_MULTIPLIER),
    }
    if altair_family:
        family = "altair"
        cols["part_prev"] = part_prev
        cols["scores"] = np.fromiter(
            (int(s) for s in state.inactivity_scores), np.int64, n)
        scalars["base_per_incr"] = np.int64(
            incr * int(spec.BASE_REWARD_FACTOR) // isqrt(tb))
        scalars["bias"] = np.int64(int(spec.config.INACTIVITY_SCORE_BIAS))
        scalars["recovery"] = np.int64(
            int(spec.config.INACTIVITY_SCORE_RECOVERY_RATE))
        scalars["inact_denom"] = np.int64(
            int(spec.config.INACTIVITY_SCORE_BIAS)
            * int(spec.inactivity_penalty_quotient()))
        statics["electra"] = electra
        statics["wd"] = int(spec.WEIGHT_DENOMINATOR)
        statics["target_flag"] = int(spec.TIMELY_TARGET_FLAG_INDEX)
        statics["flags"] = tuple(
            (i, int(w), i == int(spec.TIMELY_HEAD_FLAG_INDEX))
            for i, w in enumerate(spec.PARTICIPATION_FLAG_WEIGHTS))
    else:
        family = "phase0"
        if masks_prev is None:
            src = np.zeros(n, bool)
            tgt = np.zeros(n, bool)
            head = np.zeros(n, bool)
            best_key = np.full(n, _I64MAX, np.int64)
            best_prop = np.zeros(n, np.int64)
        else:
            src, tgt, head, best_key, best_prop = masks_prev
        cols.update(src=src, tgt=tgt, head=head,
                    best_key=best_key, best_prop=best_prop)
        scalars["sqrt_tb"] = np.int64(isqrt(tb))
        scalars["finality_delay"] = np.int64(
            int(spec.get_finality_delay(state)) if do_rewards else 1)
        statics["brf"] = int(spec.BASE_REWARD_FACTOR)
        statics["brpe"] = int(spec.BASE_REWARDS_PER_EPOCH)
        statics["prop_q"] = int(spec.PROPOSER_REWARD_QUOTIENT)
        statics["inact_q"] = int(spec.INACTIVITY_PENALTY_QUOTIENT)
    return SweepInputs(family, n, cols, scalars,
                       tuple(sorted(statics.items())))


# ---------------------------------------------------------------------------
# the numpy twin: counted fallback AND differential-guard oracle
# ---------------------------------------------------------------------------

def numpy_sweep(inp: SweepInputs):
    """Exact lane math of the `ops.epoch_sweep` device program, in host
    numpy, from the same SweepInputs — byte-identical by construction
    (the fork-matrix differential tests pin device == numpy ==
    `scalar_epoch()` post-state roots).  All integer math is int64 with
    non-negative operands and non-zero divisors, so `//` agrees with
    the device's floor division exactly."""
    st = dict(inp.statics)
    c = inp.cols
    incr = st["incr"]
    n = inp.n
    cur = np.uint64(inp.scalars["cur"])
    prev = np.uint64(inp.scalars["prev"])
    finalized = np.uint64(inp.scalars["finalized"])
    slash_epoch = np.uint64(inp.scalars["slash_epoch"])
    tb = int(inp.scalars["tb"])
    adj = int(inp.scalars["adj"])
    eff = c["eff"]
    slashed = c["slashed"]
    activation = c["activation"]
    exit_epoch = c["exit_epoch"]
    far = np.uint64((1 << 64) - 1)

    active_prev = (activation <= prev) & (prev < exit_epoch)
    active_cur = (activation <= cur) & (cur < exit_epoch)
    eligible = active_prev | (
        slashed & (np.uint64(int(prev) + 1) < c["withdrawable"]))
    unsl = ~slashed
    bal = c["balances"]
    new_scores = None

    if inp.family == "phase0":
        if st["do_rewards"]:
            base = eff * st["brf"] // int(inp.scalars["sqrt_tb"]) \
                // st["brpe"]
            prop_reward = base // st["prop_q"]
            rewards = np.zeros(n, np.int64)
            penalties = np.zeros(n, np.int64)
            for mask in (c["src"], c["tgt"], c["head"]):
                m = mask & unsl
                if st["leak"]:
                    comp = base
                else:
                    att_bal = max(incr, int(eff[m].sum()))
                    comp = base * (att_bal // incr) // (tb // incr)
                rewards = rewards + np.where(eligible & m, comp, 0)
                penalties = penalties + np.where(eligible & ~m, base, 0)
            unsl_src = c["src"] & unsl
            delays = c["best_key"] >> _ORDER_BITS
            rewards = rewards + np.where(
                unsl_src, (base - prop_reward) // delays, 0)
            prop_gain = np.zeros(n, np.int64)
            np.add.at(prop_gain, c["best_prop"],
                      np.where(unsl_src, prop_reward, 0))
            rewards = rewards + prop_gain
            if st["leak"]:
                unsl_tgt = c["tgt"] & unsl
                penalties = penalties + np.where(
                    eligible, st["brpe"] * base - prop_reward, 0)
                penalties = penalties + np.where(
                    eligible & ~unsl_tgt,
                    eff * int(inp.scalars["finality_delay"])
                    // st["inact_q"], 0)
            bal = np.maximum(bal + rewards - penalties, 0)
    else:
        new_scores = c["scores"]
        if st["do_rewards"]:
            part_prev = c["part_prev"]
            tflag = st["target_flag"]
            tgt_unsl = (active_prev & (((part_prev >> tflag) & 1) == 1)
                        & unsl)
            bias = int(inp.scalars["bias"])
            new_scores = np.where(
                eligible & tgt_unsl,
                new_scores - np.minimum(1, new_scores), new_scores)
            new_scores = np.where(
                eligible & ~tgt_unsl, new_scores + bias, new_scores)
            if not st["leak"]:
                rec = int(inp.scalars["recovery"])
                new_scores = np.where(
                    eligible, new_scores - np.minimum(rec, new_scores),
                    new_scores)
            active_incr = tb // incr
            base = (eff // incr) * int(inp.scalars["base_per_incr"])
            for flag_idx, weight, is_head in st["flags"]:
                funsl = (active_prev
                         & (((part_prev >> flag_idx) & 1) == 1) & unsl)
                if st["leak"]:
                    r = 0
                else:
                    part_incr = max(incr, int(eff[funsl].sum())) // incr
                    r = np.where(
                        eligible & funsl,
                        base * weight * part_incr
                        // (active_incr * st["wd"]), 0)
                if is_head:
                    p = 0
                else:
                    p = np.where(eligible & ~funsl,
                                 base * weight // st["wd"], 0)
                bal = np.maximum(bal + r - p, 0)
            pen = eff * new_scores // int(inp.scalars["inact_denom"])
            bal = np.maximum(
                bal - np.where(eligible & ~tgt_unsl, pen, 0), 0)

    # slashings
    eff_incr = eff // incr
    if st.get("electra"):
        pen = eff_incr * (adj // (tb // incr))
    else:
        pen = eff_incr * adj // tb * incr
    slash_mask = slashed & (c["withdrawable"] == slash_epoch)
    bal = np.maximum(bal - np.where(slash_mask, pen, 0), 0)

    # effective-balance hysteresis
    h = incr // st["hyst_q"]
    cond = ((bal + h * st["hyst_down"] < eff)
            | (eff + h * st["hyst_up"] < bal))
    new_eff = np.where(
        cond, np.minimum(bal - bal % incr, c["max_eff"]), eff)

    # registry-update eligibility masks
    elig_q = (c["act_elig"] == far) & (eff == st["max_eb"])
    eject = active_cur & (eff <= st["ejection"])
    ready = (c["act_elig"] <= finalized) & (activation == far)

    if new_scores is None:
        return bal, new_eff, elig_q, eject, ready
    return bal, new_scores, new_eff, elig_q, eject, ready


# ---------------------------------------------------------------------------
# writeback + registry application (the rare scalar mutations)
# ---------------------------------------------------------------------------

def _bulk_write(view, old: np.ndarray, new: np.ndarray) -> int:
    """ONE Python-level writeback call for a whole mutated column: the
    changed-index vector + packed values go through
    `incremental.bulk_set_basic`, which marks the dirty merkle cone in
    one pass.  Returns the element count (epoch_writeback_elems)."""
    changed = np.nonzero(new != old)[0]
    if changed.size:
        from ..ssz import incremental
        incremental.bulk_set_basic(view, changed, new[changed])
    return int(changed.size)


def _apply_registry(spec, state, cur, arr, elig_q, eject, ready) -> None:
    """Pre-electra process_registry_updates from the sweep's masks
    (beacon-chain.md:1590): only the (rare) mutating indices run scalar
    spec calls so churn bookkeeping stays identical."""
    for i in np.nonzero(elig_q)[0].tolist():
        state.validators[i].activation_eligibility_epoch = cur + 1
    for i in np.nonzero(eject)[0].tolist():
        spec.initiate_validator_exit(state, i)
    # activation queue: the sweep's `ready` mask is computed from the
    # PRE-update eligibility epochs, which is exact — newly eligible
    # validators get epoch cur+1 > finalized and can never be ready in
    # the same epoch
    idx = np.nonzero(ready)[0]
    order = np.lexsort((idx, arr.activation_eligibility[idx]))
    churn = int(spec.get_validator_churn_limit(state))
    target_epoch = int(spec.compute_activation_exit_epoch(cur))
    for i in idx[order][:churn].tolist():
        state.validators[i].activation_epoch = target_epoch


def _fallback_reason() -> str:
    from ..resilience import supervisor
    sup = supervisor.active()
    if sup is None:
        return "unsupervised"
    if sup.forced_scalar:
        return "disabled"
    state = sup.breaker_state(SWEEP_SITE)
    if state == supervisor.QUARANTINED:
        return "quarantined"
    if state == supervisor.OPEN:
        return "breaker_open"
    return "dispatch_failed"


# ---------------------------------------------------------------------------
# the orchestrator: ONE dispatch per process_epoch
# ---------------------------------------------------------------------------

def fused_epoch(spec, state) -> bool:
    """Run the fused head of `process_epoch` — justification through the
    effective-balance update (electra: including the scalar registry +
    pending-deposit/consolidation queues at their reference positions) —
    with exactly ONE `ops.epoch_sweep` dispatch.  Returns True when it
    handled that prefix (the caller then runs only the tail resets);
    returns False — before mutating anything — when the engine is
    disabled, so the caller falls through to the reference-shaped scalar
    pass list."""
    if not ENABLED:
        return False
    n = len(state.validators)
    if n == 0:
        return False
    from ..sigpipe.metrics import METRICS

    with span("epoch.collect"):
        altair_family = bool(spec.is_post("altair"))
        electra = bool(spec.is_post("electra"))
        cur = int(spec.get_current_epoch(state))
        prev = int(spec.get_previous_epoch(state))
        genesis = int(spec.GENESIS_EPOCH)
        do_rewards = cur != genesis
        incr = int(spec.EFFECTIVE_BALANCE_INCREMENT)
        arr = StateArrays(state)
        tb = arr.total_active_balance(cur, incr)

        # -- host prefix: justification (checkpoint/bit mutations only) ----
        part_prev = None
        masks_prev = None
        if altair_family:
            part_prev = _participation(state, "previous", n)
        elif do_rewards:
            masks_prev = phase0_attestation_masks(spec, state, prev)
        if cur > genesis + 1:
            if altair_family:
                tflag = int(spec.TIMELY_TARGET_FLAG_INDEX)
                prev_m = (arr.active(prev)
                          & (((part_prev >> tflag) & 1) == 1) & ~arr.slashed)
                part_cur = _participation(state, "current", n)
                cur_m = (arr.active(cur)
                         & (((part_cur >> tflag) & 1) == 1) & ~arr.slashed)
            else:
                prev_m = masks_prev[1] & ~arr.slashed
                cur_m = phase0_attestation_masks(
                    spec, state, cur, targets_only=True)[1] & ~arr.slashed
            spec.weigh_justification_and_finalization(
                state, tb,
                max(incr, int(arr.eff[prev_m].sum())),
                max(incr, int(arr.eff[cur_m].sum())))

        # leak/finality/finalized all read the POST-justification state
        leak = bool(spec.is_in_inactivity_leak(state)) if do_rewards else False
        inp = _collect(spec, state, arr, part_prev, masks_prev,
                       do_rewards, leak, tb, cur, prev)

    # -- the ONE dispatch ----------------------------------------------
    from ..resilience import supervisor

    used_fallback = False

    def _device():
        from ..ops import epoch_sweep
        return epoch_sweep.run_sweep(inp)

    def _numpy_fallback():
        nonlocal used_fallback
        used_fallback = True
        METRICS.inc_labeled("epoch_sweep_fallbacks", _fallback_reason())
        return numpy_sweep(inp)

    if _ON_DEVICE:
        METRICS.inc("epoch_sweep_dispatches")
        out = supervisor.dispatch(SWEEP_SITE, _device, _numpy_fallback)
    else:
        used_fallback = True        # host lanes: nothing to guard
        out = numpy_sweep(inp)

    # -- differential guard: sampled, device output only, pre-writeback
    if not used_fallback and _GUARD_RNG.random() < _GUARD_RATE:
        METRICS.inc("epoch_guard_samples")
        oracle = numpy_sweep(inp)
        if not all(np.array_equal(a, b) for a, b in zip(out, oracle)):
            METRICS.inc("epoch_guard_mismatches")
            from ..resilience.incidents import INCIDENTS
            INCIDENTS.record(SWEEP_SITE, "guard_mismatch",
                             detail="sweep lanes != numpy oracle")
            sup = supervisor.active()
            if sup is not None:
                sup.quarantine(SWEEP_SITE, "guard_mismatch")
            out = oracle

    if altair_family:
        new_bal, new_scores, new_eff, elig_q, eject, ready = out
    else:
        new_bal, new_eff, elig_q, eject, ready = out
        new_scores = None

    with span("epoch.writeback"):
        # -- batched writeback + the rare scalar mutations ------------------
        wb = 0
        if new_scores is not None:
            wb += _bulk_write(state.inactivity_scores,
                              inp.cols["scores"], new_scores)
        wb += _bulk_write(state.balances, arr.balances, new_bal)

        if not electra:
            _apply_registry(spec, state, cur, arr, elig_q, eject, ready)
            changed = np.nonzero(new_eff != arr.eff)[0]
            for i in changed.tolist():
                state.validators[i].effective_balance = int(new_eff[i])
            wb += int(changed.size)
        else:
            # electra's single-pass registry and its deposit/consolidation
            # queues stay scalar spec calls at their reference positions;
            # they read effective balances (untouched so far) and may move
            # balances or append validators — the sweep's hysteresis lanes
            # stay valid exactly for the untouched validators
            spec.process_registry_updates(state)
            spec.process_pending_deposits(state)
            spec.process_pending_consolidations(state)
            n2 = len(state.validators)
            bal_after = np.fromiter(
                (int(b) for b in state.balances), np.int64, n2)
            moved = np.ones(n2, bool)
            moved[:n] = bal_after[:n] != new_bal
            untouched = np.nonzero(~moved[:n] & (new_eff != arr.eff))[0]
            for i in untouched.tolist():
                state.validators[i].effective_balance = int(new_eff[i])
            wb += int(untouched.size)
            h = incr // int(spec.HYSTERESIS_QUOTIENT)
            down = h * int(spec.HYSTERESIS_DOWNWARD_MULTIPLIER)
            up = h * int(spec.HYSTERESIS_UPWARD_MULTIPLIER)
            comp_prefix = int.from_bytes(
                bytes(spec.COMPOUNDING_WITHDRAWAL_PREFIX), "big")
            for i in np.nonzero(moved)[0].tolist():
                v = state.validators[i]
                bal_i = int(bal_after[i])
                eff_i = int(v.effective_balance)
                if bal_i + down < eff_i or eff_i + up < bal_i:
                    max_eb = (int(spec.MAX_EFFECTIVE_BALANCE_ELECTRA)
                              if v.withdrawal_credentials[0] == comp_prefix
                              else int(spec.MIN_ACTIVATION_BALANCE))
                    v.effective_balance = min(bal_i - bal_i % incr, max_eb)

        METRICS.inc("epoch_writeback_elems", wb)
    return True
