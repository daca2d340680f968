"""ONE fused device program per epoch: the `ops.epoch_sweep` seam body.

Every hot per-validator pass of epoch processing — attestation /
participation-flag delta sets, inactivity-score updates, the slashings
pass, effective-balance hysteresis, and the registry-update eligibility
masks — compiles into a single jitted XLA program over the validator
axis (int64 lanes, masks + global reductions, one scatter for phase0's
proposer micro-rewards).  The host (specs/epoch_fast.py, the only
module allowed to import this one — speclint `epoch-scalar-bypass`)
extracts the StateArrays columns, precomputes the committee-dependent
masks and the global scalars, and dispatches here exactly once per
`process_epoch` through ``resilience.dispatch("ops.epoch_sweep", ...)``
with the numpy twin as the counted byte-identical fallback.

Two program families share one compile cache keyed by (family,
statics): ``phase0`` (pending-attestation masks, inclusion-delay
rewards with proposer scatter) and ``altair`` (participation-flag
deltas + inactivity scores; the ``electra`` static only switches the
slashings form).  All integer math is exact int64/uint64 — identical
results to the numpy lanes on any backend — and division operands are
non-negative with non-zero divisors by construction, so jnp floor
division matches numpy exactly.

Mesh scaling rides :func:`parallel.shard_verify.shard_jobs`: the
validator axis is padded to a mesh multiple with neutral lanes (False
masks, zero balances — they contribute nothing to reductions or the
scatter) and placed with a ``NamedSharding``; GSPMD partitions the
same program over the devices.  This is what retired the ad-hoc
``epoch_fast.MESH_ENGINE`` flag/slashings hooks.

``run_sweep`` performs the ONE host-sync download per epoch; it is
registered in ``resilience.sites.HOST_SYNC_BARRIERS``.
"""
from __future__ import annotations

import numpy as np

SITE = "ops.epoch_sweep"

# inclusion-delay keys pack (delay << ORDER_BITS) | attestation order;
# must match specs/epoch_fast.py's _ORDER_BITS
ORDER_BITS = 24
FAR = (1 << 64) - 1

# column orders are part of the program signature (epoch_fast builds
# SweepInputs.cols with exactly these keys)
PHASE0_COLS = ("eff", "slashed", "activation", "exit_epoch", "act_elig",
               "withdrawable", "balances", "max_eff",
               "src", "tgt", "head", "best_key", "best_prop")
ALTAIR_COLS = ("eff", "slashed", "activation", "exit_epoch", "act_elig",
               "withdrawable", "balances", "max_eff",
               "part_prev", "scores")
PHASE0_SCALARS = ("cur", "prev", "finalized", "slash_epoch",
                  "tb", "sqrt_tb", "adj", "finality_delay")
ALTAIR_SCALARS = ("cur", "prev", "finalized", "slash_epoch",
                  "tb", "adj", "base_per_incr", "bias", "recovery",
                  "inact_denom")

# neutral padding lanes for the mesh multiple: never active, never
# eligible, zero balance — invisible to reductions and the scatter
_PAD = {"eff": 0, "slashed": False, "activation": FAR, "exit_epoch": FAR,
        "act_elig": FAR, "withdrawable": 0, "balances": 0, "max_eff": 0,
        "src": False, "tgt": False, "head": False,
        "best_key": 1 << 62, "best_prop": 0, "part_prev": 0, "scores": 0}

_PROGRAMS: dict = {}


def reset() -> None:
    """Drop the compiled-program cache (device/mesh reconfiguration)."""
    _PROGRAMS.clear()


def _build(family: str, st: dict):
    import jax
    import jax.numpy as jnp

    incr = st["incr"]
    leak = st["leak"]
    do_rewards = st["do_rewards"]
    far = jnp.uint64(FAR)
    one = jnp.uint64(1)

    def masks(cur, prev, activation, exit_epoch, slashed, withdrawable):
        active_prev = (activation <= prev) & (prev < exit_epoch)
        active_cur = (activation <= cur) & (cur < exit_epoch)
        eligible = active_prev | (slashed & ((prev + one) < withdrawable))
        return active_prev, active_cur, eligible

    def tail(bal, eff, slashed, withdrawable, act_elig, activation,
             max_eff, active_cur, slash_epoch, finalized, tb, adj):
        # slashings: correlation penalty at the halfway-window epoch
        eff_incr = eff // incr
        if st.get("electra"):
            pen = eff_incr * (adj // (tb // incr))
        else:
            pen = eff_incr * adj // tb * incr
        slash_mask = slashed & (withdrawable == slash_epoch)
        bal = jnp.maximum(bal - jnp.where(slash_mask, pen, 0), 0)
        # effective-balance hysteresis (reads the post-deltas balance)
        h = incr // st["hyst_q"]
        cond = ((bal + h * st["hyst_down"] < eff)
                | (eff + h * st["hyst_up"] < bal))
        new_eff = jnp.where(
            cond, jnp.minimum(bal - bal % incr, max_eff), eff)
        # registry-update eligibility masks (host applies the rare
        # mutations scalar-sequentially; electra ignores these — its
        # single-pass registry stays a scalar host pass)
        elig_q = (act_elig == far) & (eff == st["max_eb"])
        eject = active_cur & (eff <= st["ejection"])
        ready = (act_elig <= finalized) & (activation == far)
        return bal, new_eff, elig_q, eject, ready

    if family == "phase0":
        def prog(eff, slashed, activation, exit_epoch, act_elig,
                 withdrawable, balances, max_eff, src, tgt, head,
                 best_key, best_prop,
                 cur, prev, finalized, slash_epoch, tb, sqrt_tb, adj,
                 finality_delay):
            active_prev, active_cur, eligible = masks(
                cur, prev, activation, exit_epoch, slashed, withdrawable)
            bal = balances
            if do_rewards:
                unsl = ~slashed
                base = eff * st["brf"] // sqrt_tb // st["brpe"]
                prop_reward = base // st["prop_q"]
                rewards = jnp.zeros_like(eff)
                penalties = jnp.zeros_like(eff)
                for mask in (src, tgt, head):
                    m = mask & unsl
                    if leak:
                        comp = base
                    else:
                        att_bal = jnp.maximum(
                            incr, jnp.sum(jnp.where(m, eff, 0)))
                        comp = base * (att_bal // incr) // (tb // incr)
                    rewards = rewards + jnp.where(eligible & m, comp, 0)
                    penalties = penalties + jnp.where(
                        eligible & ~m, base, 0)
                # inclusion-delay rewards (no eligibility filter) + the
                # proposer scatter
                unsl_src = src & unsl
                delays = best_key >> ORDER_BITS
                rewards = rewards + jnp.where(
                    unsl_src, (base - prop_reward) // delays, 0)
                rewards = rewards + jnp.zeros_like(eff).at[best_prop].add(
                    jnp.where(unsl_src, prop_reward, 0))
                if leak:
                    unsl_tgt = tgt & unsl
                    penalties = penalties + jnp.where(
                        eligible, st["brpe"] * base - prop_reward, 0)
                    penalties = penalties + jnp.where(
                        eligible & ~unsl_tgt,
                        eff * finality_delay // st["inact_q"], 0)
                bal = jnp.maximum(bal + rewards - penalties, 0)
            return tail(bal, eff, slashed, withdrawable, act_elig,
                        activation, max_eff, active_cur, slash_epoch,
                        finalized, tb, adj)
    else:
        tflag = st["target_flag"]

        def prog(eff, slashed, activation, exit_epoch, act_elig,
                 withdrawable, balances, max_eff, part_prev, scores,
                 cur, prev, finalized, slash_epoch, tb, adj,
                 base_per_incr, bias, recovery, inact_denom):
            active_prev, active_cur, eligible = masks(
                cur, prev, activation, exit_epoch, slashed, withdrawable)
            bal = balances
            new_scores = scores
            if do_rewards:
                unsl = ~slashed
                tgt_unsl = (active_prev & (((part_prev >> tflag) & 1) == 1)
                            & unsl)
                # inactivity scores FIRST: the penalty set below reads
                # the updated scores (scalar ordering: inactivity
                # updates precede rewards)
                new_scores = jnp.where(
                    eligible & tgt_unsl,
                    new_scores - jnp.minimum(1, new_scores), new_scores)
                new_scores = jnp.where(
                    eligible & ~tgt_unsl, new_scores + bias, new_scores)
                if not leak:
                    new_scores = jnp.where(
                        eligible,
                        new_scores - jnp.minimum(recovery, new_scores),
                        new_scores)
                # per-flag delta sets, applied sequentially with the
                # spec's zero-floor decrease semantics
                active_incr = tb // incr
                base = (eff // incr) * base_per_incr
                for flag_idx, weight, is_head in st["flags"]:
                    funsl = (active_prev
                             & (((part_prev >> flag_idx) & 1) == 1)
                             & unsl)
                    if leak:
                        r = 0
                    else:
                        part_incr = jnp.maximum(
                            incr,
                            jnp.sum(jnp.where(funsl, eff, 0))) // incr
                        r = jnp.where(
                            eligible & funsl,
                            base * weight * part_incr
                            // (active_incr * st["wd"]), 0)
                    if is_head:
                        p = 0
                    else:
                        p = jnp.where(eligible & ~funsl,
                                      base * weight // st["wd"], 0)
                    bal = jnp.maximum(bal + r - p, 0)
                # inactivity-penalty set (uses the NEW scores)
                pen = eff * new_scores // inact_denom
                bal = jnp.maximum(
                    bal - jnp.where(eligible & ~tgt_unsl, pen, 0), 0)
            out = tail(bal, eff, slashed, withdrawable, act_elig,
                       activation, max_eff, active_cur, slash_epoch,
                       finalized, tb, adj)
            return (out[0], new_scores) + out[1:]

    return jax.jit(prog)


def _program(family: str, statics: tuple):
    key = (family, statics)
    fn = _PROGRAMS.get(key)
    if fn is None:
        fn = _build(family, dict(statics))
        _PROGRAMS[key] = fn
    return fn


def run_sweep(inp):
    """The fused epoch program: upload (mesh-sharded when a verify mesh
    is live), ONE compiled dispatch, ONE host-sync download.

    `inp` is a `specs.epoch_fast.SweepInputs`; returns numpy lanes
    sliced back to the true validator count:
    phase0 → (balances, new_eff, elig_q, eject, ready),
    altair → (balances, scores, new_eff, elig_q, eject, ready)."""
    import jax

    from ..parallel.shard_verify import mesh_devices, shard_jobs

    phase0 = inp.family == "phase0"
    col_order = PHASE0_COLS if phase0 else ALTAIR_COLS
    scalar_order = PHASE0_SCALARS if phase0 else ALTAIR_SCALARS
    n = inp.n
    n_dev = mesh_devices()
    n_pad = n + (-n) % n_dev if n_dev > 1 else n
    arrays = []
    for name in col_order:
        a = inp.cols[name]
        if n_pad != n:
            a = np.concatenate(
                [a, np.full(n_pad - n, _PAD[name], dtype=a.dtype)])
        arrays.append(a)
    scalars = [inp.scalars[k] for k in scalar_order]
    # build/trace under x64 too: the program closes over uint64
    # constants (FAR epochs) that overflow 32-bit lanes
    with jax.enable_x64(True):
        fn = _program(inp.family, inp.statics)
        arrays = shard_jobs(tuple(arrays), SITE)
        out = jax.device_get(fn(*arrays, *scalars))
    return tuple(np.asarray(o)[:n] for o in out)
