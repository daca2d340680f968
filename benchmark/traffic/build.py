"""The one traffic generator: a cell's pre-state, keys and signed blocks
from its configuration file, its traffic file and `--seed`.

Copied from `chip_smoke.py`'s builders (state, keys, signing, tamper)
and generalised over the traffic file's parameters and the electra
block.  Committees, the proposer, the sync committee, every signing
root and every state root come from the plain reference
(`benchmark/reference`), never from the program's spec; the program
gives only its SSZ types (the blocks it is fed) and its host BLS
signer, since the benchmark carries no BLS of its own.
"""
from __future__ import annotations

import copy
import hashlib
import json
import os
from dataclasses import dataclass, field

import numpy as np

from benchmark.reference import ssz as rssz
from benchmark.reference.beacon import (FAR_FUTURE_EPOCH, FLAG_WEIGHTS,
                                        PRESETS, Transition, shuffle_rounds)

DOMAIN_RANDAO = b"\x02\x00\x00\x00"
DOMAIN_ATTESTER = b"\x01\x00\x00\x00"
DOMAIN_PROPOSER = b"\x00\x00\x00\x00"
DOMAIN_SYNC = b"\x07\x00\x00\x00"
UNSET_DEPOSIT_REQUESTS_START_INDEX = 2**64 - 1


@dataclass
class Traffic:
    spec: object
    ref: Transition
    state: object                 # the program's pre-state
    signed: object                # the cell's signed block
    tampered: object              # the same block, one attestation bad
    bad_signature: bytes          # the tampered attestation's signature
    post_root: bytes              # reference post-state roots
    tampered_root: bytes
    keys: "Keys"
    sets: int                     # signature sets in the block
    attesting: int                # attesting validators in the block
    timings: dict = field(default_factory=dict)


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
        steps, self.point = {}, {}
        prev, pt = None, None
        for v in sorted(set(int(i) for i in indices)):
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
        return (self.base + int(v)) % self.R

    def agg_sk(self, validators) -> int:
        return sum(self.sk(v) for v in validators) % self.R


def sign(sk: int, root: bytes) -> bytes:
    from consensus_specs_tpu.crypto import bls12_381 as native_bls
    return bytes(native_bls.Sign(sk, root))


VECTORS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "reference", "bls_vectors.json")


def check_signer() -> None:
    """The host signer the traffic is signed with, against the known
    answers of the consensus-specs BLS suite: the verdicts the traffic
    is built to have rest on it, and it shares hash-to-curve and the
    point encodings with the verifier under test."""
    from consensus_specs_tpu.crypto import bls12_381 as native_bls
    with open(VECTORS) as f:
        vectors = json.load(f)
    for sk, pk in vectors["pubkeys"].items():
        if bytes(native_bls.SkToPk(int(sk, 16))).hex() != pk:
            raise RuntimeError(f"host signer: wrong public key for {sk[:8]}")
    for v in vectors["sign"]:
        got = sign(int(v["privkey"], 16), bytes.fromhex(v["message"]))
        if got.hex() != v["signature"]:
            raise RuntimeError(f"host signer: wrong signature for "
                               f"{v['privkey'][:8]} over {v['message'][:8]}")


def check_decoded(got: dict, want: dict, what: str) -> None:
    """Each dotted path of `want` holds the same values in `got`, the
    reference's decoding of the program's serialization: the program's
    SSZ encoder writes what both sides are fed, so the generator checks
    it against the values it meant."""
    for path, value in want.items():
        if isinstance(value, dict):
            check_decoded(got, {f"{path}.{k}": x for k, x in value.items()},
                          what)
            continue
        v = got
        for key in path.split("."):
            v = v[int(key)] if key.isdigit() else v[key]
        if not np.array_equal(np.asarray(v), np.asarray(value)):
            raise RuntimeError(f"{what}: {path} decodes to other values "
                               f"than the generator set")


def _pubkey_rows(n: int, pubkey: dict) -> np.ndarray:
    """[n, 48] pubkeys as the state holds them: the keyed validators'
    keys, `_placeholder(i)` for the rest."""
    rows = np.full((n, 48), 0x5B, np.uint8)
    rows[:, :8] = np.arange(n, dtype="<u8").view(np.uint8).reshape(n, 8)
    for v, pk in pubkey.items():
        rows[v] = np.frombuffer(pk, np.uint8)
    return rows


def signing_root(object_root: bytes, domain_type: bytes, s: dict,
                 epoch: int) -> bytes:
    fork = s["fork"]
    version = fork["previous_version"] if epoch < fork["epoch"] \
        else fork["current_version"]
    fork_data_root = rssz.sha256(version.ljust(32, b"\x00")
                                 + s["genesis_validators_root"])
    return rssz.sha256(object_root + domain_type + fork_data_root[:28])


def next_sync_committee(ref: Transition, s: dict) -> list:
    """`get_next_sync_committee_indices`, on the shuffled order."""
    P = ref.P
    epoch = ref.current_epoch(s) + 1
    active = np.nonzero(ref.active(s, epoch))[0]
    seed = ref.seed(s, epoch, DOMAIN_SYNC)
    order = active[shuffle_rounds(len(active), seed, P["SHUFFLE_ROUND_COUNT"])]
    eff = s["validators"]["effective_balance"]
    out, i = [], 0
    while len(out) < P["SYNC_COMMITTEE_SIZE"]:
        cand = int(order[i % len(order)])
        if ref.electra:
            rnd = rssz.sha256(seed + (i // 16).to_bytes(8, "little"))
            value = int.from_bytes(rnd[i % 16 * 2:i % 16 * 2 + 2], "little")
            ok = int(eff[cand]) * 0xFFFF >= \
                P["MAX_EFFECTIVE_BALANCE_ELECTRA"] * value
        else:
            value = rssz.sha256(seed + (i // 32).to_bytes(8, "little"))[i % 32]
            ok = int(eff[cand]) * 255 >= P["MAX_EFFECTIVE_BALANCE"] * value
        if ok:
            out.append(cand)
        i += 1
    return out


def attestation_duties(ref: Transition, s: dict, mix: dict) -> list:
    """[(slot, committee indices, head vote)] of the block's aggregates,
    newest slot first: the `slots` slots before the pre-state's, each
    split into `aggregates_per_slot` aggregates by committee index
    modulo that count (one committee each before electra).  With
    `head_votes: split`, every aggregate after a slot's first votes a
    head other than the canonical one."""
    per_slot = ref.committee_count(s, ref.current_epoch(s))
    groups = mix["aggregates_per_slot"]
    cap = ref.P["MAX_ATTESTATIONS_ELECTRA" if ref.electra
                else "MAX_ATTESTATIONS"]
    duties = []
    for slot in range(s["slot"] - 1, s["slot"] - 1 - mix["slots"], -1):
        if ref.electra:
            parts = [[c for c in range(per_slot) if c % groups == g]
                     for g in range(groups)]
        else:
            parts = [[c] for c in range(per_slot)]
        for g, part in enumerate(parts):
            split = mix["head_votes"] == "split" and g > 0
            duties.append((slot, part, split))
    if len(duties) > cap:
        duties = duties[:cap]
    return duties


def _placeholder(i: int) -> bytes:
    return i.to_bytes(8, "little") + b"\x5b" * 40


def build(spec, config: dict, mix: dict, seed: int) -> Traffic:
    """Everything one run feeds the program, from the seed."""
    import time

    from consensus_specs_tpu.crypto import curve as cv
    from consensus_specs_tpu.ssz import serialize

    timings = {}
    t0 = time.perf_counter()
    check_signer()
    ref = Transition(config["fork"], config["preset"])
    P = PRESETS[config["preset"]]
    spe, n = P["SLOTS_PER_EPOCH"], config["validators"]
    block_slot = mix["block_epoch"] * spe + mix["block_slot_in_epoch"]
    pre_slot = block_slot - 1
    mix_root = hashlib.sha256(b"chip-smoke-mix"
                              + seed.to_bytes(8, "little")).digest()
    balance = config["balance_gwei"]
    # the registry as the reference reads it, for the block's duties
    registry = rssz.Columns(n, {
        "effective_balance": np.full(n, balance, np.uint64),
        "activation_epoch": np.zeros(n, np.uint64),
        "exit_epoch": np.full(n, FAR_FUTURE_EPOCH, np.uint64)})
    duty = {"slot": pre_slot, "validators": registry,
            "randao_mixes": np.tile(np.frombuffer(mix_root, np.uint8),
                                    (P["EPOCHS_PER_HISTORICAL_VECTOR"], 1))}
    duties = attestation_duties(ref, duty, mix)
    members = [np.concatenate([ref.committee(duty, slot, c) for c in part])
               for slot, part, _split in duties]
    sync = next_sync_committee(ref, duty)
    duty["slot"] = block_slot
    proposer = ref.proposer_index(duty)
    attesting = set(int(v) for m in members for v in m)
    keys = Keys(seed, attesting | set(sync) | {proposer})

    # the program's pre-state
    state = spec.BeaconState(
        genesis_time=spec.config.MIN_GENESIS_TIME,
        randao_mixes=[mix_root] * P["EPOCHS_PER_HISTORICAL_VECTOR"])
    creds = bytes([config["withdrawal_prefix"]]) + b"\x00" * 31
    state.validators = [
        spec.Validator(
            pubkey=keys.pubkey.get(i) or _placeholder(i),
            withdrawal_credentials=creds, effective_balance=balance,
            activation_epoch=0, activation_eligibility_epoch=0,
            exit_epoch=spec.FAR_FUTURE_EPOCH,
            withdrawable_epoch=spec.FAR_FUTURE_EPOCH)
        for i in range(n)]
    state.balances = [balance] * n
    state.slot = pre_slot
    full = (1 << len(spec.PARTICIPATION_FLAG_WEIGHTS)) - 1
    state.previous_epoch_participation = [full] * n
    unset_flags = np.zeros(n, bool)
    if mix["current_participation"] == "unset_for_attesters":
        unset_flags[sorted(attesting)] = True
    state.current_epoch_participation = [
        0 if unset else full for unset in unset_flags]
    state.inactivity_scores = [0] * n
    agg = cv.g1_infinity()
    for v in sync:
        agg = agg + keys.point[v]
    state.current_sync_committee = spec.SyncCommittee(
        pubkeys=[keys.pubkey[v] for v in sync],
        aggregate_pubkey=cv.g1_to_bytes(agg))
    if ref.electra:
        state.latest_execution_payload_header = spec.ExecutionPayloadHeader(
            block_hash=hashlib.sha256(b"el-head" + seed.to_bytes(
                8, "little")).digest(),
            block_number=pre_slot, gas_limit=30_000_000,
            base_fee_per_gas=7, timestamp=int(state.genesis_time)
            + pre_slot * P["SECONDS_PER_SLOT"])
        state.deposit_requests_start_index = \
            UNSET_DEPOSIT_REQUESTS_START_INDEX
    timings["state_build_s"] = time.perf_counter() - t0

    # the reference's view of the pre-state, advanced to the block slot
    t0 = time.perf_counter()
    pre = ref.decode_state(serialize(state))
    flags = (1 << len(FLAG_WEIGHTS)) - 1
    want = {
        "slot": pre_slot,
        "randao_mixes": duty["randao_mixes"],
        "validators.pubkey": _pubkey_rows(n, keys.pubkey),
        "validators.withdrawal_credentials": np.tile(
            np.frombuffer(creds, np.uint8), (n, 1)),
        "validators.effective_balance": registry["effective_balance"],
        "validators.slashed": np.zeros(n, bool),
        "validators.activation_eligibility_epoch": np.zeros(n, np.uint64),
        "validators.activation_epoch": registry["activation_epoch"],
        "validators.exit_epoch": registry["exit_epoch"],
        "validators.withdrawable_epoch": registry["exit_epoch"],
        "balances": np.full(n, balance, np.uint64),
        "previous_epoch_participation": np.full(n, flags, np.uint8),
        "current_epoch_participation": np.array(
            [0 if unset else flags for unset in unset_flags], np.uint8),
        "inactivity_scores": np.zeros(n, np.uint64),
        "current_sync_committee.pubkeys": np.array(
            [np.frombuffer(keys.pubkey[v], np.uint8) for v in sync]),
        "block_roots": np.zeros((P["SLOTS_PER_HISTORICAL_ROOT"], 32),
                                np.uint8),
        "slashings": np.zeros(P["EPOCHS_PER_SLASHINGS_VECTOR"], np.uint64),
        "justification_bits": np.zeros(4, bool),
    }
    if ref.electra:
        want["deposit_requests_start_index"] = \
            UNSET_DEPOSIT_REQUESTS_START_INDEX
        want["latest_execution_payload_header.block_number"] = pre_slot
    check_decoded(pre, want, "pre-state")
    look = copy.deepcopy(pre)
    ref.process_slots(look, block_slot)
    if ref.proposer_index(look) != proposer:
        raise RuntimeError("proposer read ahead of the epoch transition moved")
    timings["reference_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    epoch = ref.current_epoch(look)
    block = spec.BeaconBlock(
        slot=block_slot, proposer_index=proposer,
        parent_root=ref.T["Header"].root(look["latest_block_header"]))
    block.body.eth1_data.deposit_count = look["eth1_deposit_index"]
    block.body.randao_reveal = sign(keys.sk(proposer), signing_root(
        epoch.to_bytes(8, "little").ljust(32, b"\x00"), DOMAIN_RANDAO,
        look, epoch))
    signed_atts = {}
    for (slot, part, split), who in zip(duties, members):
        target = slot // spe
        justified = pre["current_justified_checkpoint"] \
            if target == ref.current_epoch(pre) \
            else pre["previous_justified_checkpoint"]
        data = {"slot": slot, "index": 0 if ref.electra else part[0],
                "beacon_block_root": ref.block_root_at_slot(pre, slot),
                "source": justified,
                "target": {"epoch": target,
                           "root": ref.block_root(pre, target)}}
        if split:
            data["beacon_block_root"] = hashlib.sha256(
                b"split-head" + slot.to_bytes(8, "little")).digest()
        root = signing_root(ref.T["AttestationData"].root(data),
                            DOMAIN_ATTESTER, pre, target)
        att = spec.Attestation(
            aggregation_bits=[True] * len(who),
            data=spec.AttestationData(
                slot=slot, index=data["index"],
                beacon_block_root=data["beacon_block_root"],
                source=spec.Checkpoint(**justified),
                target=spec.Checkpoint(**data["target"])),
            signature=sign(keys.agg_sk(who), root))
        if ref.electra:
            att.committee_bits = [c in part for c in
                                  range(P["MAX_COMMITTEES_PER_SLOT"])]
        i = len(block.body.attestations)
        signed_atts.update({
            f"body.attestations.{i}.data": data,
            f"body.attestations.{i}.aggregation_bits": np.ones(len(who), bool),
            f"body.attestations.{i}.signature": bytes(att.signature)})
        block.body.attestations.append(att)
    prev_slot = block_slot - 1
    sync_root = signing_root(ref.block_root_at_slot(look, prev_slot),
                             DOMAIN_SYNC, look, prev_slot // spe)
    block.body.sync_aggregate = spec.SyncAggregate(
        sync_committee_bits=[True] * len(sync),
        sync_committee_signature=sign(keys.agg_sk(sync), sync_root))
    if ref.electra:
        block.body.execution_payload = _payload(spec, ref, look)
    check_decoded(ref.T["Block"].decode(serialize(block)), {
        "slot": block_slot, "proposer_index": proposer,
        "body.randao_reveal": bytes(block.body.randao_reveal),
        "body.sync_aggregate.sync_committee_bits": np.ones(len(sync), bool),
        **signed_atts}, "block")
    timings["signing_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    post_root = _seal_root(ref, look, block)
    signed = _seal(spec, ref, look, keys, block)
    j = min(mix["tampered_attestation"], len(duties) - 1)
    bad = block.copy()
    bad_signature = sign(keys.agg_sk(members[j]), hashlib.sha256(
        b"not-the-attested-root").digest())
    bad.body.attestations[j].signature = bad_signature
    tampered_root = _seal_root(ref, look, bad)
    tampered = _seal(spec, ref, look, keys, bad)
    timings["reference_s"] += time.perf_counter() - t0
    return Traffic(spec=spec, ref=ref, state=state, signed=signed,
                   tampered=tampered, bad_signature=bad_signature,
                   post_root=post_root, tampered_root=tampered_root,
                   keys=keys, sets=len(duties) + 3,
                   attesting=len(attesting), timings=timings)


def _payload(spec, ref: Transition, look: dict):
    """An empty execution payload consistent with `look`: its expected
    withdrawals, no transactions, no blobs."""
    from consensus_specs_tpu.ssz import serialize

    head = look["latest_execution_payload_header"]
    payload = spec.ExecutionPayload(
        parent_hash=head["block_hash"], state_root=head["state_root"],
        prev_randao=ref.randao_mix(look, ref.current_epoch(look)),
        block_number=head["block_number"] + 1, gas_limit=head["gas_limit"],
        timestamp=look["genesis_time"] + look["slot"]
        * ref.P["SECONDS_PER_SLOT"],
        base_fee_per_gas=head["base_fee_per_gas"])
    payload.withdrawals = [
        spec.Withdrawal(index=i, validator_index=v, address=a, amount=m)
        for i, v, a, m in ref.expected_withdrawals(look)]
    T = ref.T["Payload"]
    payload.block_hash = rssz.sha256(
        T.root(T.decode(serialize(payload))) + b"FAKE RLP HASH")
    return payload


def _seal_root(ref: Transition, look: dict, block) -> bytes:
    """The reference post-state root of `block`; sets it on the block."""
    from consensus_specs_tpu.ssz import serialize

    s = copy.deepcopy(look)
    ref.process_block(s, ref.T["Block"].decode(serialize(block)))
    block.state_root = ref.root(s)
    return bytes(block.state_root)


def _seal(spec, ref: Transition, look: dict, keys: Keys, block):
    from consensus_specs_tpu.ssz import serialize

    root = ref.T["Block"].root(ref.T["Block"].decode(serialize(block)))
    epoch = int(block.slot) // ref.P["SLOTS_PER_EPOCH"]
    return spec.SignedBeaconBlock(message=block, signature=sign(
        keys.sk(int(block.proposer_index)),
        signing_root(root, DOMAIN_PROPOSER, look, epoch)))
