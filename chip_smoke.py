"""Chip smoke: the mainnet-preset signed-block `state_transition` on a TPU.

One process drives the executable spec's main path end to end with the
device engines armed (BLS pairing/MSM/sweeps, incremental device
merkleization, the one-dispatch `ops.epoch_sweep`) and checks it
against the host engines:

1. build an altair mainnet-preset state of 262,144 validators from
   `--seed`, one slot before an epoch boundary, and give real seeded
   BLS keys to every validator the block's signatures read;
2. sign one block: 128 attestations (MAX_ATTESTATIONS: two slots x 64
   committees x 128 members), a full 512-member sync aggregate, a
   proposer signature and a RANDAO reveal;
3. warm the device path on a throwaway copy, then time the transition
   on a fresh copy, reject a tampered copy of the block, and compare
   the post-state root and two attestation verdicts with the host.

Earlier stdout lines are the report; the last line is the one JSON
object `{"ok": true, "device": {...}}`.  Any mismatch, an accepted
tampered block, a failed phase, a counted fallback, or a platform
other than `tpu` exits non-zero without that line.

`--chips 4` runs only the mesh comparison: the same block through the
4-device verify mesh and mesh-sharded epoch sweep, against this
process's own 1-device run.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import sys
import time

SEED = 21
N_VALIDATORS = 1 << 18
MAINNET_REGISTRY = 1_050_000        # ~mainnet registry (SURVEY.md §5)
REQUIRED_PLATFORM = "tpu"
DEVICE_MODES = {"msm": "pippenger", "g1_sweep": "jax"}
TAMPERED = 5                        # the attestation the bad block breaks


def say(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# compile accounting: every backend compile, by program name
# ---------------------------------------------------------------------------

class CompileLog:
    """Backend compile seconds per jitted program, from JAX's own
    monitoring events (a persistent-cache hit records no compile)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import threading

        import jax
        self.by_name: dict = {}
        self._lock = threading.Lock()       # compiles run concurrently
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == self.EVENT:
            name = kw.get("fun_name", "?")
            with self._lock:
                n, s = self.by_name.get(name, (0, 0.0))
                self.by_name[name] = (n + 1, s + secs)

    def total(self) -> float:
        return sum(s for _n, s in self.by_name.values())


# ---------------------------------------------------------------------------
# state, keys, block
# ---------------------------------------------------------------------------

def build_state(spec, n: int, seed: int):
    """Altair-family state of `n` active validators at the last slot of
    epoch 2 (past the genesis-epoch guards, not a sync-committee period
    boundary), full participation in both epochs, RANDAO mixes from
    `seed`.  Pubkeys are synthetic placeholders until `assign_keys`."""
    from consensus_specs_tpu.ssz import uint64

    mix = hashlib.sha256(b"chip-smoke-mix" + seed.to_bytes(8, "little"))
    state = spec.BeaconState(
        genesis_time=spec.config.MIN_GENESIS_TIME,
        randao_mixes=[mix.digest()] * spec.EPOCHS_PER_HISTORICAL_VECTOR)
    max_eb = int(spec.MAX_EFFECTIVE_BALANCE)
    state.validators = [
        spec.Validator(
            pubkey=i.to_bytes(8, "little") + b"\x5b" * 40,
            withdrawal_credentials=b"\x01" + b"\x00" * 31,
            effective_balance=max_eb,
            activation_epoch=0,
            activation_eligibility_epoch=0,
            exit_epoch=spec.FAR_FUTURE_EPOCH,
            withdrawable_epoch=spec.FAR_FUTURE_EPOCH)
        for i in range(n)]
    state.balances = [max_eb] * n
    state.slot = uint64(3 * spec.SLOTS_PER_EPOCH - 1)
    full = (1 << len(spec.PARTICIPATION_FLAG_WEIGHTS)) - 1
    state.previous_epoch_participation = [full] * n
    state.current_epoch_participation = [full] * n
    state.inactivity_scores = [0] * n
    return state


def attestation_duties(spec, state, n_attestations: int):
    """(slot, committee index) of the block's attestations: every
    committee of the slots before `state.slot`, newest first, until
    `n_attestations` (all in the current epoch, so they all target
    the block's previous epoch)."""
    duties = []
    slot = int(state.slot) - 1
    epoch_start = int(spec.compute_start_slot_at_epoch(
        spec.get_current_epoch(state)))
    per_slot = int(spec.get_committee_count_per_slot(
        state, spec.get_current_epoch(state)))
    while len(duties) < n_attestations and slot >= epoch_start:
        for index in range(per_slot):
            if len(duties) < n_attestations:
                duties.append((slot, index))
        slot -= 1
    return duties


class Keys:
    """Seeded real BLS keys for the validators the block reads:
    sk(v) = base + v (mod r), so pk(v) = pk(v_prev) + (v - v_prev) * G
    costs one point add per key (the small multiples are memoized)."""

    def __init__(self, seed: int, indices):
        from consensus_specs_tpu.crypto import curve as cv
        from consensus_specs_tpu.crypto.fields import R

        self.R = R
        self.base = int.from_bytes(hashlib.sha256(
            b"chip-smoke-keys" + seed.to_bytes(8, "little")).digest(),
            "big") % R
        g = cv.g1_generator()
        steps = {}
        self.point = {}
        prev, pt = None, None
        for v in sorted(set(indices)):
            if prev is None:
                pt = g * ((self.base + v) % R)
            else:
                d = v - prev
                if d not in steps:
                    steps[d] = g * d
                pt = pt + steps[d]
            self.point[v] = pt
            prev = v
        self.pubkey = {v: cv.g1_to_bytes(p) for v, p in self.point.items()}

    def sk(self, v: int) -> int:
        return (self.base + v) % self.R

    def agg_sk(self, validators) -> int:
        return sum(self.sk(int(v)) for v in validators) % self.R


def assign_keys(spec, state, n_attestations: int, seed: int):
    """Give real keys to every validator the block's signatures read:
    its attesting committees, the sync committee and the proposer.
    Committees, sync-committee indices and the proposer depend on the
    registry and the RANDAO mixes, never on pubkeys, so they are
    computed first.  Returns (keys, duties, sync indices, lookahead
    state at the block's slot, proposer index)."""
    from consensus_specs_tpu.crypto import curve as cv
    from consensus_specs_tpu.ssz import uint64

    duties = attestation_duties(spec, state, n_attestations)
    attesters = set()
    for slot, index in duties:
        attesters.update(int(v) for v in spec.get_beacon_committee(
            state, uint64(slot), uint64(index)))
    sync_indices = [int(v) for v in spec.get_next_sync_committee_indices(
        state)]
    # the block slot's proposer, read ahead of the epoch transition:
    # the seed's RANDAO mix and the effective balances it samples are
    # the same on both sides of it (asserted on the lookahead below)
    slot = uint64(int(state.slot) + 1)
    epoch = spec.compute_epoch_at_slot(slot)
    proposer = int(spec.compute_proposer_index(
        state, spec.get_active_validator_indices(state, epoch),
        spec.hash(spec.get_seed(state, epoch, spec.DOMAIN_BEACON_PROPOSER)
                  + spec.uint_to_bytes(slot))))

    keys = Keys(seed, attesters | set(sync_indices) | {proposer})
    for v, pk in keys.pubkey.items():
        state.validators[v].pubkey = pk
    agg = cv.g1_infinity()
    for v in sync_indices:
        agg = agg + keys.point[v]
    state.current_sync_committee = spec.SyncCommittee(
        pubkeys=[keys.pubkey[v] for v in sync_indices],
        aggregate_pubkey=cv.g1_to_bytes(agg))
    look = state.copy()
    spec.process_slots(look, slot)
    check(int(spec.get_beacon_proposer_index(look)) == proposer,
          "proposer read ahead of the epoch transition moved")
    return keys, duties, sync_indices, look, proposer


def sign_block(spec, state, look, keys, duties, sync_indices, proposer):
    """The block at `state.slot + 1`, unsigned and without state root.
    Aggregates use the sum-of-secret-keys identity: every member signs
    the same root, so each aggregate costs one hash-to-curve and one G2
    multiplication."""
    from consensus_specs_tpu.crypto import bls12_381 as native_bls
    from consensus_specs_tpu.ssz import hash_tree_root, uint64
    from consensus_specs_tpu.test_infra.attestations import (
        build_attestation_data)

    block = spec.BeaconBlock(
        slot=look.slot, proposer_index=proposer,
        parent_root=hash_tree_root(look.latest_block_header))
    block.body.eth1_data.deposit_count = look.eth1_deposit_index
    block.body.randao_reveal = spec.get_epoch_signature(
        look, block, keys.sk(proposer))
    for slot, index in duties:
        data = build_attestation_data(spec, state, uint64(slot), index)
        committee = spec.get_beacon_committee(
            state, uint64(slot), uint64(index))
        domain = spec.get_domain(state, spec.DOMAIN_BEACON_ATTESTER,
                                 data.target.epoch)
        att = spec.Attestation(
            aggregation_bits=[True] * len(committee), data=data,
            signature=native_bls.Sign(
                keys.agg_sk(committee),
                spec.compute_signing_root(data, domain)))
        block.body.attestations.append(att)
    previous_slot = uint64(int(look.slot) - 1)
    domain = spec.get_domain(look, spec.DOMAIN_SYNC_COMMITTEE,
                             spec.compute_epoch_at_slot(previous_slot))
    sync_root = spec.compute_signing_root(
        spec.get_block_root_at_slot(look, previous_slot), domain)
    block.body.sync_aggregate = spec.SyncAggregate(
        sync_committee_bits=[True] * len(sync_indices),
        sync_committee_signature=native_bls.Sign(
            keys.agg_sk(sync_indices), sync_root))
    return block


def seal(spec, look, keys, block):
    """Sign `block` as its proposer."""
    from consensus_specs_tpu.crypto import bls12_381 as native_bls
    domain = spec.get_domain(look, spec.DOMAIN_BEACON_PROPOSER,
                             spec.compute_epoch_at_slot(block.slot))
    return spec.SignedBeaconBlock(
        message=block, signature=native_bls.Sign(
            keys.sk(int(block.proposer_index)),
            spec.compute_signing_root(block, domain)))


def tamper(spec, state, look, keys, signed, j: int):
    """A copy of `signed` whose attestation `j` carries a valid signature
    by its own committee over a different root.  Its state root is that
    of the tampered body (BLS off, on the engines armed at the call),
    and the proposer re-seals it, so only that signature is wrong."""
    from consensus_specs_tpu.crypto import bls12_381 as native_bls
    from consensus_specs_tpu.ssz import hash_tree_root
    from consensus_specs_tpu.test_infra import disable_bls

    block = signed.message.copy()
    att = block.body.attestations[j]
    committee = spec.get_beacon_committee(state, att.data.slot,
                                          att.data.index)
    other = hashlib.sha256(b"not-the-attested-root").digest()
    att.signature = native_bls.Sign(keys.agg_sk(committee), other)
    post = state.copy()
    with disable_bls():
        spec.process_slots(post, block.slot)
        spec.process_block(post, block)
    block.state_root = hash_tree_root(post)
    return seal(spec, look, keys, block)


# ---------------------------------------------------------------------------
# engines
# ---------------------------------------------------------------------------

def arm_device_engines() -> None:
    """The device path, armed as the vector factory arms it: the tpu BLS
    backend, the fused sigpipe flush, and the batched SHA-256 kernel
    under the incremental merkle sweeps.  The epoch engine
    (`ops.epoch_sweep`) is on by default.  No resilience supervisor: a
    device fault raises."""
    from consensus_specs_tpu import sigpipe
    from consensus_specs_tpu.ssz import merkle
    from consensus_specs_tpu.utils import bls

    bls.use_tpu()
    sigpipe.enable(mode="fused")
    merkle.use_tpu_hashing()


def disarm_device_engines() -> None:
    from consensus_specs_tpu import sigpipe
    from consensus_specs_tpu.ssz import merkle
    from consensus_specs_tpu.utils import bls

    bls.use_native()
    sigpipe.disable()
    merkle.use_host_hashing()


def host_post_state(spec, state, block):
    """The same transition on the host engines — hashlib merkleization,
    the numpy twin of the epoch sweep, and BLS off, since signatures do
    not change the state.  Returns (post-state, its root); the root is
    the incremental cache's, checked against a full rebuild."""
    from consensus_specs_tpu.specs import epoch_fast
    from consensus_specs_tpu.ssz import hash_tree_root, incremental
    from consensus_specs_tpu.test_infra import disable_bls

    post = state.copy()
    with epoch_fast.numpy_epoch(), disable_bls():
        spec.process_slots(post, block.slot)
        spec.process_block(post, block)
    root = bytes(hash_tree_root(post))
    check(bytes(incremental.oracle_root(post)) == root,
          "incremental host root != full rebuild")
    return post, root


def warm_device_stages(spec, state, look, signed) -> float:
    """Compile the block's device programs concurrently (XLA compiles
    release the GIL), each stage through its own entry point at the
    block's shapes: the epoch transition on a throwaway copy and, on
    the tpu BLS backend, hash-to-G2 of the block's signing roots, the
    committee sums, the Fiat-Shamir weighting ladder, the signature
    fold, and the pairing stages at the flush's bucket (plus the mesh
    product's when a mesh is live).  Returns the wall seconds."""
    from concurrent.futures import ThreadPoolExecutor

    from consensus_specs_tpu import sigpipe
    from consensus_specs_tpu.crypto import curve as cv
    from consensus_specs_tpu.ops import bls_tpu, msm, pairing_jax
    from consensus_specs_tpu.parallel import shard_verify
    from consensus_specs_tpu.sigpipe import fold
    from consensus_specs_tpu.sigpipe.cache import AGGREGATES
    from consensus_specs_tpu.utils import bls

    sets = sigpipe.collect_block_sets(spec, look, signed)
    n = len(sets)
    g1, g2 = cv.g1_generator(), cv.g2_generator()
    coeffs = [1 + (i * 0x9E3779B97F4A7C15) % (2**64 - 1) for i in range(n)]

    def pairing():
        if shard_verify.pairing_live():
            shard_verify.pairing_product([(g1, g2)] * (n + 1))
        if pairing_jax._resolve_mode() == "staged":
            pairing_jax.warmup(k=bls_tpu._min_pairs(), rows=1)

    jobs = [lambda: spec.process_slots(state.copy(), look.slot)]
    if bls.current_backend() == "tpu":
        jobs += [
            lambda: bls_tpu.hash_to_g2_batch(
                [s.signing_root for s in sets]),
            lambda: AGGREGATES.aggregate_many(
                [(s.pubkeys, s.hint) for s in sets]),
            lambda: msm.g1_weighted_sweep([g1] * n, coeffs),
            lambda: fold.fold_signatures([g2] * n, coeffs),
            pairing,
        ]
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(jobs)) as pool:
        for job in [pool.submit(j) for j in jobs]:
            job.result()
    AGGREGATES.clear()
    return time.perf_counter() - t0


def resolved_modes() -> dict:
    from consensus_specs_tpu.ops import g1_sweep, msm, pairing_jax
    from consensus_specs_tpu.sigpipe import fold
    return {"pairing": pairing_jax._resolve_mode(),
            "msm": msm._resolve_mode(),
            "g1_sweep": g1_sweep._resolve_mode(),
            "one_launch_fold": fold.one_launch_live()}


def _tampered_index(signed) -> int:
    return min(TAMPERED, len(signed.message.body.attestations) - 1)


def _rejects(spec, state, signed) -> bool:
    try:
        spec.state_transition(state.copy(), signed)
    except AssertionError:
        return True
    return False


# ---------------------------------------------------------------------------
# the phases
# ---------------------------------------------------------------------------

def prepare(spec, n_validators: int, n_attestations: int, seed: int,
            report: dict):
    """State, keys and the signed block, all on the host engines; the
    block's state root comes from the host transition.  The state is
    tracked by the incremental merkle cache (hashlib levels until the
    device path is armed), so its copies share one chunk tree.  Returns
    (state, look, keys, signed, host_root)."""
    from consensus_specs_tpu.sigpipe.cache import PUBKEYS
    from consensus_specs_tpu.specs import epoch_fast
    from consensus_specs_tpu.ssz import hash_tree_root, incremental

    t0 = time.perf_counter()
    state = build_state(spec, n_validators, seed)
    incremental.enable()
    incremental.track(state)
    hash_tree_root(state)
    with epoch_fast.numpy_epoch():
        keys, duties, sync_indices, look, proposer = assign_keys(
            spec, state, n_attestations, seed)
    # the pubkey cache holds the keyed validators' points, as a running
    # node's holds the keys it validated when they joined the registry
    # (cold, 16.9k host decompressions + subgroup checks ~ 4.5 min)
    PUBKEYS.prime((keys.pubkey[v], keys.point[v]) for v in keys.point)
    report["state_build_s"] = time.perf_counter() - t0
    report["keyed_validators"] = len(keys.pubkey)
    say(f"state: {n_validators} validators, slot {int(state.slot)}, "
        f"{len(keys.pubkey)} keyed "
        f"({report['state_build_s']:.1f} s)")

    t0 = time.perf_counter()
    block = sign_block(spec, state, look, keys, duties, sync_indices,
                       proposer)
    report["signing_s"] = time.perf_counter() - t0
    report["attestations"] = len(block.body.attestations)
    report["sync_participants"] = sum(
        bool(b) for b in block.body.sync_aggregate.sync_committee_bits)
    say(f"block: slot {int(block.slot)}, {report['attestations']} "
        f"attestations, {report['sync_participants']}-member sync "
        f"aggregate ({report['signing_s']:.1f} s)")

    t0 = time.perf_counter()
    _post, host_root = host_post_state(spec, state, block)
    report["host_transition_s"] = time.perf_counter() - t0
    report["host_root"] = host_root.hex()
    say(f"host transition (hashlib merkle, numpy epoch twin, BLS off): "
        f"root {host_root.hex()} ({report['host_transition_s']:.1f} s)")
    block.state_root = host_root
    return state, look, keys, seal(spec, look, keys, block), host_root


def run_main_path(spec, n_validators: int, n_attestations: int,
                  seed: int = SEED) -> dict:
    """Requests 1-5 on the device engines; returns the report."""
    from consensus_specs_tpu import sigpipe
    from consensus_specs_tpu.crypto import bls12_381 as native_bls
    from consensus_specs_tpu.sigpipe import METRICS
    from consensus_specs_tpu.sigpipe.cache import AGGREGATES
    from consensus_specs_tpu.ssz import hash_tree_root, incremental

    report: dict = {}
    state, look, keys, signed, host_root = prepare(
        spec, n_validators, n_attestations, seed, report)
    arm_device_engines()
    try:
        report["modes"] = resolved_modes()
        # 1. warm: the block's programs compile concurrently, then a
        # throwaway transition compiles whatever is left
        report["stage_warm_s"] = warm_device_stages(spec, state, look,
                                                    signed)
        say(f"device stages compiled concurrently: "
            f"{report['stage_warm_s']:.1f} s")
        t0 = time.perf_counter()
        spec.state_transition(state.copy(), signed)
        report["warm_s"] = time.perf_counter() - t0
        say(f"warm transition: {report['warm_s']:.1f} s")

        # 2. the timed transition.  It ends on the host: every device
        # result it depends on (verdicts, epoch lanes, merkle levels)
        # is read back inside it, so nothing is left in flight.
        # the committee sums are per block, so the timed run starts
        # with a cold aggregate cache (validated pubkeys stay cached)
        post = state.copy()
        AGGREGATES.clear()
        METRICS.reset()
        t0 = time.perf_counter()
        spec.state_transition(post, signed)
        report["transition_s"] = time.perf_counter() - t0
        snap = METRICS.snapshot()
        report["epoch_sweep_dispatches"] = snap.get(
            "epoch_sweep_dispatches", 0)
        report["epoch_sweep_fallbacks"] = METRICS.count_labeled(
            "epoch_sweep_fallbacks")
        report["scalar_fallbacks"] = METRICS.count_labeled(
            "scalar_fallbacks")
        report["miller_loops_per_flush"] = snap.get(
            "miller_loops_per_flush", {}).get("max")
        report["fold_dispatches"] = snap.get("fold_dispatches", 0)
        report["device_root"] = bytes(hash_tree_root(post)).hex()
        say(f"timed transition: {report['transition_s']:.3f} s, root "
            f"{report['device_root']}")

        bad = tamper(spec, state, look, keys, signed, _tampered_index(signed))

        # 3. the tampered block must be rejected
        t0 = time.perf_counter()
        report["tampered_rejected"] = _rejects(spec, state, bad)
        report["tampered_s"] = time.perf_counter() - t0
        say(f"tampered block rejected: {report['tampered_rejected']} "
            f"({report['tampered_s']:.1f} s)")

        # 5. device verdicts of one good and the tampered attestation
        _vm, sets, verdicts = sigpipe.compute_verdicts(spec, look, bad)
        by_sig = {s.signature: v for s, v in zip(sets, verdicts)}
        atts = bad.message.body.attestations
        j_bad = _tampered_index(bad)
        j_good = 0 if j_bad else 1
        report["verdicts"] = {}
        for j in (j_good, j_bad):
            att = atts[j]
            committee = spec.get_beacon_committee(look, att.data.slot,
                                                  att.data.index)
            domain = spec.get_domain(look, spec.DOMAIN_BEACON_ATTESTER,
                                     att.data.target.epoch)
            root = spec.compute_signing_root(att.data, domain)
            oracle = native_bls.FastAggregateVerify(
                [bytes(look.validators[int(v)].pubkey) for v in committee],
                bytes(root), bytes(att.signature))
            report["verdicts"][j] = (by_sig.get(bytes(att.signature)),
                                     oracle)
        say(f"attestation verdicts (device, host oracle): "
            f"{report['verdicts']}")
    finally:
        disarm_device_engines()
        incremental.disable()
    report["host_root"] = host_root.hex()
    return report


def verify_report(report: dict) -> None:
    """The acceptance checks of the main path (platform-independent)."""
    check(report["device_root"] == report["host_root"],
          f"device root {report['device_root']} != host root "
          f"{report['host_root']}")
    check(report["tampered_rejected"], "tampered block was accepted")
    check(report["epoch_sweep_dispatches"] == 1,
          f"epoch_sweep_dispatches = {report['epoch_sweep_dispatches']}")
    check(report["epoch_sweep_fallbacks"] == 0,
          f"epoch_sweep_fallbacks = {report['epoch_sweep_fallbacks']}")
    check(report["scalar_fallbacks"] == 0,
          f"scalar_fallbacks = {report['scalar_fallbacks']}")
    for j, (device, oracle) in report["verdicts"].items():
        check(device == oracle,
              f"attestation {j}: device verdict {device} != oracle {oracle}")
    good = [o for _d, o in report["verdicts"].values()]
    check(good == [True, False], f"oracle verdicts {good}")


def run_mesh_compare(spec, n_validators: int, n_attestations: int,
                     seed: int, n_devices: int) -> dict:
    """The same block at verify-mesh widths 1 and `n_devices`: the
    post-state root and the tampered block's per-set device verdicts
    must agree (its bad attestation the only invalid set)."""
    from consensus_specs_tpu import sigpipe
    from consensus_specs_tpu.parallel import shard_verify
    from consensus_specs_tpu.sigpipe import METRICS
    from consensus_specs_tpu.ssz import hash_tree_root, incremental

    report: dict = {}
    state, look, keys, signed, host_root = prepare(
        spec, n_validators, n_attestations, seed, report)
    arm_device_engines()
    try:
        shard_verify.configure(max_devices=1)
        bad = tamper(spec, state, look, keys, signed, _tampered_index(signed))
        for width in (1, n_devices):
            shard_verify.configure(max_devices=width)
            t0 = time.perf_counter()
            warm_device_stages(spec, state, look, signed)
            METRICS.reset()
            post = state.copy()
            spec.state_transition(post, signed)
            root = bytes(hash_tree_root(post)).hex()
            _vm, _sets, verdicts = sigpipe.compute_verdicts(spec, look, bad)
            secs = time.perf_counter() - t0
            sharded = METRICS.snapshot().get("sharded_dispatches", {})
            report[width] = {"root": root, "verdicts": verdicts,
                             "mesh": shard_verify.mesh_devices(),
                             "sharded_dispatches": sharded,
                             "seconds": secs}
            invalid = [i for i, v in enumerate(verdicts) if not v]
            say(f"mesh width {shard_verify.mesh_devices()}: root {root}; "
                f"tampered block: {len(verdicts)} sets, invalid {invalid}; "
                f"sharded dispatches {sharded} ({secs:.1f} s, cold)")
    finally:
        shard_verify.configure(None)
        disarm_device_engines()
        incremental.disable()
    report["host_root"] = host_root.hex()
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=SEED)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the 1-device vs 4-device mesh compare")
    args = ap.parse_args(argv)

    import jax
    from consensus_specs_tpu.utils import compile_cache
    cache_dir = compile_cache.configure()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != REQUIRED_PLATFORM:
        print(f"chip_smoke: JAX found platform {platform!r}, need "
              f"{REQUIRED_PLATFORM!r}; not running on anything else",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    say(f"device: {device}; compile cache {cache_dir}")

    from consensus_specs_tpu.specs import get_spec
    spec = get_spec("altair", "mainnet")
    n_att = int(spec.MAX_ATTESTATIONS)
    say(f"registry: {N_VALIDATORS} validators, a quarter of mainnet's "
        f"~{MAINNET_REGISTRY} (cut for host-side state-construction "
        f"time); the block is not cut")
    compile_log = CompileLog()
    t_start = time.perf_counter()
    try:
        if args.chips > 1:
            report = run_mesh_compare(spec, N_VALIDATORS, n_att, args.seed,
                                      args.chips)
            one, many = report[1], report[args.chips]
            check(many["mesh"] == args.chips,
                  f"mesh width {many['mesh']} != {args.chips}")
            check(one["root"] == many["root"] == report["host_root"],
                  "roots differ across mesh widths or from the host")
            check(one["verdicts"] == many["verdicts"],
                  "tampered-block verdicts differ across mesh widths")
            check(one["verdicts"].count(False) == 1,
                  "tampered block: not exactly one invalid set")
        else:
            report = run_main_path(spec, N_VALIDATORS, n_att, args.seed)
            modes = report["modes"]
            say(f"modes: {modes}")
            for name, want in DEVICE_MODES.items():
                check(modes[name] == want,
                      f"{name} resolved {modes[name]!r}, want {want!r}")
            verify_report(report)
            say(f"counters: epoch_sweep_dispatches="
                f"{report['epoch_sweep_dispatches']} "
                f"epoch_sweep_fallbacks={report['epoch_sweep_fallbacks']} "
                f"miller_loops_per_flush={report['miller_loops_per_flush']}"
                f" fold_dispatches={report['fold_dispatches']}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        for name, (n, secs) in sorted(compile_log.by_name.items(),
                                      key=lambda kv: -kv[1][1]):
            if secs >= 0.5:
                say(f"compile: {name} x{n} {secs:.1f} s")
        say(f"compile total: {compile_log.total():.1f} s in "
            f"{sum(n for n, _s in compile_log.by_name.values())} programs")
        say(f"wall: {time.perf_counter() - t_start:.1f} s")
    stats = devices[0].memory_stats() or {}
    say(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
