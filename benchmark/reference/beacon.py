"""Plain reference of the beacon-chain state transition, for the cells'
blocks: altair (epoch processing included) and electra (mid-epoch
blocks only).

Written from the consensus-specs markdown (phase0 through electra
`beacon-chain.md`), on numpy and hashlib, importing nothing of the
program.  Its input is the pre-state and the signed block as SSZ bytes,
as a second client receives them; its output is the post-state root.
Signatures are not verified here: they leave the state unchanged, and
the harness compares the program's per-set verdicts with the ones the
traffic was built to have.  What the cells never carry (slashings,
deposits, exits, execution requests, electra epoch processing, a
sync-committee period boundary) raises `NotImplementedError`, so a
block that needs it is refused rather than mis-modelled.
"""
from __future__ import annotations

import json
import math
import os

import numpy as np

from . import ssz
from .ssz import (Bitlist, Bitvector, Boolean, ByteList, ByteVector,
                  Container, List, Uint, Unused, Vector)

FAR_FUTURE_EPOCH = 2**64 - 1
FLAG_WEIGHTS = (14, 26, 14)          # timely source, target, head
TIMELY_TARGET, TIMELY_HEAD = 1, 2
WEIGHT_DENOMINATOR, PROPOSER_WEIGHT, SYNC_REWARD_WEIGHT = 64, 8, 2
DOMAIN_BEACON_PROPOSER = b"\x00\x00\x00\x00"
DOMAIN_BEACON_ATTESTER = b"\x01\x00\x00\x00"
FORKS = ("altair", "bellatrix", "capella", "deneb", "electra")

with open(os.path.join(os.path.dirname(__file__), "presets.json")) as _f:
    PRESETS = json.load(_f)


class Invalid(Exception):
    """The block breaks a rule of the transition."""


def need(cond, what: str) -> None:
    if not cond:
        raise Invalid(what)


def schemas(P: dict, fork: str) -> dict:
    """The SSZ containers of `fork` with the preset `P`."""
    at = FORKS.index(fork)
    U64, B32, B48, B96 = Uint(8), ByteVector(32), ByteVector(48), \
        ByteVector(96)
    Checkpoint = Container("Checkpoint", [("epoch", U64), ("root", B32)])
    Fork = Container("Fork", [("previous_version", ByteVector(4)),
                              ("current_version", ByteVector(4)),
                              ("epoch", U64)])
    Header = Container("BeaconBlockHeader", [
        ("slot", U64), ("proposer_index", U64), ("parent_root", B32),
        ("state_root", B32), ("body_root", B32)])
    Eth1Data = Container("Eth1Data", [("deposit_root", B32),
                                      ("deposit_count", U64),
                                      ("block_hash", B32)])
    Validator = Container("Validator", [
        ("pubkey", B48), ("withdrawal_credentials", B32),
        ("effective_balance", U64), ("slashed", Boolean()),
        ("activation_eligibility_epoch", U64), ("activation_epoch", U64),
        ("exit_epoch", U64), ("withdrawable_epoch", U64)])
    AttestationData = Container("AttestationData", [
        ("slot", U64), ("index", U64), ("beacon_block_root", B32),
        ("source", Checkpoint), ("target", Checkpoint)])
    SyncCommittee = Container("SyncCommittee", [
        ("pubkeys", Vector(B48, P["SYNC_COMMITTEE_SIZE"])),
        ("aggregate_pubkey", B48)])
    SyncAggregate = Container("SyncAggregate", [
        ("sync_committee_bits", Bitvector(P["SYNC_COMMITTEE_SIZE"])),
        ("sync_committee_signature", B96)])
    electra = at >= FORKS.index("electra")
    if electra:
        Attestation = Container("Attestation", [
            ("aggregation_bits", Bitlist(P["MAX_VALIDATORS_PER_COMMITTEE"]
                                         * P["MAX_COMMITTEES_PER_SLOT"])),
            ("data", AttestationData), ("signature", B96),
            ("committee_bits", Bitvector(P["MAX_COMMITTEES_PER_SLOT"]))])
        n_att, n_aslash = P["MAX_ATTESTATIONS_ELECTRA"], \
            P["MAX_ATTESTER_SLASHINGS_ELECTRA"]
    else:
        Attestation = Container("Attestation", [
            ("aggregation_bits", Bitlist(P["MAX_VALIDATORS_PER_COMMITTEE"])),
            ("data", AttestationData), ("signature", B96)])
        n_att, n_aslash = P["MAX_ATTESTATIONS"], P["MAX_ATTESTER_SLASHINGS"]
    body = [
        ("randao_reveal", B96), ("eth1_data", Eth1Data), ("graffiti", B32),
        ("proposer_slashings", Unused(P["MAX_PROPOSER_SLASHINGS"],
                                      "proposer slashings")),
        ("attester_slashings", Unused(n_aslash, "attester slashings")),
        ("attestations", List(Attestation, n_att)),
        ("deposits", Unused(P["MAX_DEPOSITS"], "deposits")),
        ("voluntary_exits", Unused(P["MAX_VOLUNTARY_EXITS"], "exits")),
        ("sync_aggregate", SyncAggregate)]
    state = [
        ("genesis_time", U64), ("genesis_validators_root", B32),
        ("slot", U64), ("fork", Fork), ("latest_block_header", Header),
        ("block_roots", Vector(B32, P["SLOTS_PER_HISTORICAL_ROOT"])),
        ("state_roots", Vector(B32, P["SLOTS_PER_HISTORICAL_ROOT"])),
        ("historical_roots", List(B32, P["HISTORICAL_ROOTS_LIMIT"])),
        ("eth1_data", Eth1Data),
        ("eth1_data_votes", List(Eth1Data, P["EPOCHS_PER_ETH1_VOTING_PERIOD"]
                                 * P["SLOTS_PER_EPOCH"])),
        ("eth1_deposit_index", U64),
        ("validators", List(Validator, P["VALIDATOR_REGISTRY_LIMIT"])),
        ("balances", List(U64, P["VALIDATOR_REGISTRY_LIMIT"])),
        ("randao_mixes", Vector(B32, P["EPOCHS_PER_HISTORICAL_VECTOR"])),
        ("slashings", Vector(U64, P["EPOCHS_PER_SLASHINGS_VECTOR"])),
        ("previous_epoch_participation",
         List(Uint(1), P["VALIDATOR_REGISTRY_LIMIT"])),
        ("current_epoch_participation",
         List(Uint(1), P["VALIDATOR_REGISTRY_LIMIT"])),
        ("justification_bits", Bitvector(4)),
        ("previous_justified_checkpoint", Checkpoint),
        ("current_justified_checkpoint", Checkpoint),
        ("finalized_checkpoint", Checkpoint),
        ("inactivity_scores", List(U64, P["VALIDATOR_REGISTRY_LIMIT"])),
        ("current_sync_committee", SyncCommittee),
        ("next_sync_committee", SyncCommittee)]
    out = {"Header": Header, "Eth1Data": Eth1Data}
    if at >= FORKS.index("bellatrix"):
        if fork != "electra":
            raise NotImplementedError(f"reference: fork {fork}")
        Withdrawal = Container("Withdrawal", [
            ("index", U64), ("validator_index", U64),
            ("address", ByteVector(20)), ("amount", U64)])
        out["Withdrawals"] = List(Withdrawal, P["MAX_WITHDRAWALS_PER_PAYLOAD"])
        out["Transactions"] = List(ByteList(P["MAX_BYTES_PER_TRANSACTION"]),
                                   P["MAX_TRANSACTIONS_PER_PAYLOAD"])
        head = [("parent_hash", B32), ("fee_recipient", ByteVector(20)),
                ("state_root", B32), ("receipts_root", B32),
                ("logs_bloom", ByteVector(P["BYTES_PER_LOGS_BLOOM"])),
                ("prev_randao", B32), ("block_number", U64),
                ("gas_limit", U64), ("gas_used", U64), ("timestamp", U64),
                ("extra_data", ByteList(P["MAX_EXTRA_DATA_BYTES"])),
                ("base_fee_per_gas", Uint(32)), ("block_hash", B32)]
        tail = [("blob_gas_used", U64), ("excess_blob_gas", U64)]
        Payload = Container("ExecutionPayload", head + [
            ("transactions", out["Transactions"]),
            ("withdrawals", out["Withdrawals"])] + tail)
        out["Payload"] = Payload
        PayloadHeader = Container("ExecutionPayloadHeader", head + [
            ("transactions_root", B32), ("withdrawals_root", B32)] + tail)
        body += [
            ("execution_payload", Payload),
            ("bls_to_execution_changes", Unused(
                P["MAX_BLS_TO_EXECUTION_CHANGES"], "credential changes")),
            ("blob_kzg_commitments",
             List(B48, P["MAX_BLOB_COMMITMENTS_PER_BLOCK"])),
            ("execution_requests", Container("ExecutionRequests", [
                ("deposits", Unused(P["MAX_DEPOSIT_REQUESTS_PER_PAYLOAD"],
                                    "deposit requests")),
                ("withdrawals", Unused(
                    P["MAX_WITHDRAWAL_REQUESTS_PER_PAYLOAD"],
                    "withdrawal requests")),
                ("consolidations", Unused(
                    P["MAX_CONSOLIDATION_REQUESTS_PER_PAYLOAD"],
                    "consolidation requests"))]))]
        state += [
            ("latest_execution_payload_header", PayloadHeader),
            ("next_withdrawal_index", U64),
            ("next_withdrawal_validator_index", U64),
            ("historical_summaries", List(Container("HistoricalSummary", [
                ("block_summary_root", B32), ("state_summary_root", B32)]),
                P["HISTORICAL_ROOTS_LIMIT"])),
            ("deposit_requests_start_index", U64),
            ("deposit_balance_to_consume", U64),
            ("exit_balance_to_consume", U64), ("earliest_exit_epoch", U64),
            ("consolidation_balance_to_consume", U64),
            ("earliest_consolidation_epoch", U64),
            ("pending_deposits", List(Container("PendingDeposit", [
                ("pubkey", B48), ("withdrawal_credentials", B32),
                ("amount", U64), ("signature", B96), ("slot", U64)]),
                P["PENDING_DEPOSITS_LIMIT"])),
            ("pending_partial_withdrawals", List(Container(
                "PendingPartialWithdrawal", [
                    ("validator_index", U64), ("amount", U64),
                    ("withdrawable_epoch", U64)]),
                P["PENDING_PARTIAL_WITHDRAWALS_LIMIT"])),
            ("pending_consolidations", List(Container(
                "PendingConsolidation", [("source_index", U64),
                                         ("target_index", U64)]),
                P["PENDING_CONSOLIDATIONS_LIMIT"]))]
    Body = Container("BeaconBlockBody", body)
    out["Body"] = Body
    out["State"] = Container("BeaconState", state)
    out["Block"] = Container("BeaconBlock", [
        ("slot", U64), ("proposer_index", U64), ("parent_root", B32),
        ("state_root", B32), ("body", Body)])
    out["AttestationData"] = AttestationData
    out["SignedBlock"] = Container("SignedBeaconBlock", [
        ("message", out["Block"]), ("signature", B96)])
    return out


def shuffle_rounds(n: int, seed: bytes, rounds: int) -> np.ndarray:
    """`compute_shuffled_index(i, n, seed)` for every i in range(n): the
    swap-or-not rounds, vectorised."""
    idx = np.arange(n, dtype=np.int64)
    n_src = (n + 255) // 256
    for r in range(rounds):
        rb = bytes([r])
        pivot = int.from_bytes(ssz.sha256(seed + rb)[:8], "little") % n
        flip = (pivot + n - idx) % n
        pos = np.maximum(idx, flip)
        src = b"".join(ssz.sha256(seed + rb + i.to_bytes(4, "little"))
                       for i in range(n_src))
        bits = np.unpackbits(np.frombuffer(src, np.uint8), bitorder="little")
        idx = np.where(bits[pos] == 1, flip, idx)
    return idx


def shuffled_index(index: int, n: int, seed: bytes, rounds: int) -> int:
    """`compute_shuffled_index` for one index."""
    for r in range(rounds):
        rb = bytes([r])
        pivot = int.from_bytes(ssz.sha256(seed + rb)[:8], "little") % n
        flip = (pivot + n - index) % n
        pos = max(index, flip)
        src = ssz.sha256(seed + rb + (pos // 256).to_bytes(4, "little"))
        if (src[(pos % 256) // 8] >> (pos % 8)) & 1:
            index = flip
    return index


class Transition:
    """One fork and preset: decode, transition, root."""

    def __init__(self, fork: str, preset: str):
        self.fork = fork
        self.P = PRESETS[preset]
        self.T = schemas(self.P, fork)
        self.electra = fork == "electra"
        self._perm = {}

    # -- plumbing ---------------------------------------------------------
    def decode_state(self, data: bytes) -> dict:
        return self.T["State"].decode(data)

    def decode_block(self, data: bytes) -> dict:
        return self.T["SignedBlock"].decode(data)

    def root(self, state: dict) -> bytes:
        return self.T["State"].root(state)

    def post_root(self, pre: bytes, signed_block: bytes) -> bytes:
        state = self.decode_state(pre)
        self.apply(state, self.decode_block(signed_block)["message"])
        return self.root(state)

    def apply(self, s: dict, block: dict) -> None:
        """process_slots to the block's slot, then process_block."""
        need(s["slot"] < block["slot"], "block slot not after state slot")
        self.process_slots(s, block["slot"])
        self.process_block(s, block)

    # -- accessors --------------------------------------------------------
    def epoch(self, slot: int) -> int:
        return slot // self.P["SLOTS_PER_EPOCH"]

    def current_epoch(self, s) -> int:
        return self.epoch(s["slot"])

    def previous_epoch(self, s) -> int:
        return max(self.current_epoch(s) - 1, 0)

    def active(self, s, epoch: int) -> np.ndarray:
        v = s["validators"]
        return (v["activation_epoch"] <= np.uint64(epoch)) & \
            (np.uint64(epoch) < v["exit_epoch"])

    def randao_mix(self, s, epoch: int) -> bytes:
        return s["randao_mixes"][
            epoch % self.P["EPOCHS_PER_HISTORICAL_VECTOR"]].tobytes()

    def seed(self, s, epoch: int, domain: bytes) -> bytes:
        P = self.P
        mix = self.randao_mix(s, epoch + P["EPOCHS_PER_HISTORICAL_VECTOR"]
                              - P["MIN_SEED_LOOKAHEAD"] - 1)
        return ssz.sha256(domain + epoch.to_bytes(8, "little") + mix)

    def block_root_at_slot(self, s, slot: int) -> bytes:
        need(slot < s["slot"] <= slot + self.P["SLOTS_PER_HISTORICAL_ROOT"],
             "block root out of range")
        return s["block_roots"][
            slot % self.P["SLOTS_PER_HISTORICAL_ROOT"]].tobytes()

    def block_root(self, s, epoch: int) -> bytes:
        return self.block_root_at_slot(s, epoch * self.P["SLOTS_PER_EPOCH"])

    def total_balance(self, s, mask) -> int:
        return max(self.P["EFFECTIVE_BALANCE_INCREMENT"],
                   int(s["validators"]["effective_balance"][mask].sum()))

    def total_active_balance(self, s) -> int:
        return self.total_balance(s, self.active(s, self.current_epoch(s)))

    def base_reward_per_increment(self, s) -> int:
        return (self.P["EFFECTIVE_BALANCE_INCREMENT"]
                * self.P["BASE_REWARD_FACTOR"]
                // math.isqrt(self.total_active_balance(s)))

    def base_rewards(self, s) -> np.ndarray:
        eff = s["validators"]["effective_balance"]
        return (eff // np.uint64(self.P["EFFECTIVE_BALANCE_INCREMENT"])) * \
            np.uint64(self.base_reward_per_increment(s))

    def committee_count(self, s, epoch: int) -> int:
        P = self.P
        n = int(self.active(s, epoch).sum())
        return max(1, min(P["MAX_COMMITTEES_PER_SLOT"],
                          n // P["SLOTS_PER_EPOCH"]
                          // P["TARGET_COMMITTEE_SIZE"]))

    def committee(self, s, slot: int, index: int) -> np.ndarray:
        P = self.P
        epoch = self.epoch(slot)
        count = self.committee_count(s, epoch) * P["SLOTS_PER_EPOCH"]
        k = (slot % P["SLOTS_PER_EPOCH"]) * (count // P["SLOTS_PER_EPOCH"]) \
            + index
        indices = np.nonzero(self.active(s, epoch))[0]
        seed = self.seed(s, epoch, DOMAIN_BEACON_ATTESTER)
        key = (seed, indices.tobytes())
        if key not in self._perm:
            self._perm = {key: shuffle_rounds(len(indices), seed,
                                              P["SHUFFLE_ROUND_COUNT"])}
        n = len(indices)
        perm = self._perm[key]
        return indices[perm[n * k // count:n * (k + 1) // count]]

    def proposer_index(self, s) -> int:
        epoch = self.current_epoch(s)
        seed = ssz.sha256(self.seed(s, epoch, DOMAIN_BEACON_PROPOSER)
                          + s["slot"].to_bytes(8, "little"))
        indices = np.nonzero(self.active(s, epoch))[0]
        eff = s["validators"]["effective_balance"]
        total, i = len(indices), 0
        while True:
            cand = int(indices[shuffled_index(
                i % total, total, seed, self.P["SHUFFLE_ROUND_COUNT"])])
            if self.electra:
                rnd = ssz.sha256(seed + (i // 16).to_bytes(8, "little"))
                value = int.from_bytes(rnd[i % 16 * 2:i % 16 * 2 + 2],
                                       "little")
                if int(eff[cand]) * 0xFFFF >= \
                        self.P["MAX_EFFECTIVE_BALANCE_ELECTRA"] * value:
                    return cand
            else:
                value = ssz.sha256(seed + (i // 32).to_bytes(8, "little"))[
                    i % 32]
                if int(eff[cand]) * 255 >= \
                        self.P["MAX_EFFECTIVE_BALANCE"] * value:
                    return cand
            i += 1

    def participating(self, s, flag: int, epoch: int) -> np.ndarray:
        need(epoch in (self.previous_epoch(s), self.current_epoch(s)),
             "participation epoch")
        part = s["current_epoch_participation"] \
            if epoch == self.current_epoch(s) \
            else s["previous_epoch_participation"]
        return self.active(s, epoch) & ((part >> flag) & 1 == 1) & \
            ~s["validators"]["slashed"]

    def eligible(self, s) -> np.ndarray:
        v, prev = s["validators"], self.previous_epoch(s)
        return self.active(s, prev) | (
            v["slashed"] & (np.uint64(prev + 1) < v["withdrawable_epoch"]))

    def in_leak(self, s) -> bool:
        return self.previous_epoch(s) - s["finalized_checkpoint"]["epoch"] \
            > self.P["MIN_EPOCHS_TO_INACTIVITY_PENALTY"]

    @staticmethod
    def increase(s, index: int, amount: int) -> None:
        s["balances"][index] = int(s["balances"][index]) + amount

    @staticmethod
    def decrease(s, index: int, amount: int) -> None:
        b = int(s["balances"][index])
        s["balances"][index] = 0 if amount > b else b - amount

    @staticmethod
    def apply_deltas(s, rewards, penalties) -> None:
        bal = s["balances"] + rewards
        s["balances"] = np.where(penalties > bal, np.uint64(0),
                                 bal - penalties)

    # -- slots and epochs -------------------------------------------------
    def process_slots(self, s, slot: int) -> None:
        P = self.P
        while s["slot"] < slot:
            root = self.root(s)
            i = s["slot"] % P["SLOTS_PER_HISTORICAL_ROOT"]
            s["state_roots"][i] = np.frombuffer(root, np.uint8)
            if s["latest_block_header"]["state_root"] == b"\x00" * 32:
                s["latest_block_header"]["state_root"] = root
            s["block_roots"][i] = np.frombuffer(
                self.T["Header"].root(s["latest_block_header"]), np.uint8)
            if (s["slot"] + 1) % P["SLOTS_PER_EPOCH"] == 0:
                self.process_epoch(s)
            s["slot"] += 1

    def process_epoch(self, s) -> None:
        if self.fork != "altair":
            raise NotImplementedError(f"reference: {self.fork} epoch")
        self.justification_and_finalization(s)
        self.inactivity_updates(s)
        self.rewards_and_penalties(s)
        self.registry_updates(s)
        self.slashings(s)
        P, cur = self.P, self.current_epoch(s)
        nxt = cur + 1
        if nxt % P["EPOCHS_PER_ETH1_VOTING_PERIOD"] == 0:
            s["eth1_data_votes"] = dict(self.T["State"].fields)[
                "eth1_data_votes"].decode(b"")
        self.effective_balance_updates(s)
        s["slashings"][nxt % P["EPOCHS_PER_SLASHINGS_VECTOR"]] = 0
        s["randao_mixes"][nxt % P["EPOCHS_PER_HISTORICAL_VECTOR"]] = \
            s["randao_mixes"][cur % P["EPOCHS_PER_HISTORICAL_VECTOR"]]
        if nxt % (P["SLOTS_PER_HISTORICAL_ROOT"]
                  // P["SLOTS_PER_EPOCH"]) == 0:
            B32 = ByteVector(32)
            vec = Vector(B32, P["SLOTS_PER_HISTORICAL_ROOT"])
            batch = ssz.merkleize(np.frombuffer(
                vec.root(s["block_roots"]) + vec.root(s["state_roots"]),
                np.uint8).reshape(2, 32))
            s["historical_roots"] = np.concatenate([
                s["historical_roots"],
                np.frombuffer(batch, np.uint8).reshape(1, 32)])
        s["previous_epoch_participation"] = s["current_epoch_participation"]
        s["current_epoch_participation"] = np.zeros_like(
            s["previous_epoch_participation"])
        if nxt % P["EPOCHS_PER_SYNC_COMMITTEE_PERIOD"] == 0:
            raise NotImplementedError("reference: sync committee period")

    def justification_and_finalization(self, s) -> None:
        cur, prev = self.current_epoch(s), self.previous_epoch(s)
        if cur <= 1:
            return
        total = self.total_active_balance(s)
        prev_bal = self.total_balance(s, self.participating(
            s, TIMELY_TARGET, prev))
        cur_bal = self.total_balance(s, self.participating(
            s, TIMELY_TARGET, cur))
        old_prev = dict(s["previous_justified_checkpoint"])
        old_cur = dict(s["current_justified_checkpoint"])
        s["previous_justified_checkpoint"] = dict(old_cur)
        bits = np.zeros(4, bool)
        bits[1:] = s["justification_bits"][:3]
        if prev_bal * 3 >= total * 2:
            s["current_justified_checkpoint"] = {
                "epoch": prev, "root": self.block_root(s, prev)}
            bits[1] = True
        if cur_bal * 3 >= total * 2:
            s["current_justified_checkpoint"] = {
                "epoch": cur, "root": self.block_root(s, cur)}
            bits[0] = True
        s["justification_bits"] = bits
        if bits[1:4].all() and old_prev["epoch"] + 3 == cur:
            s["finalized_checkpoint"] = old_prev
        if bits[1:3].all() and old_prev["epoch"] + 2 == cur:
            s["finalized_checkpoint"] = old_prev
        if bits[0:3].all() and old_cur["epoch"] + 2 == cur:
            s["finalized_checkpoint"] = old_cur
        if bits[0:2].all() and old_cur["epoch"] + 1 == cur:
            s["finalized_checkpoint"] = old_cur

    def inactivity_updates(self, s) -> None:
        if self.current_epoch(s) == 0:
            return
        P, scores = self.P, s["inactivity_scores"]
        elig = self.eligible(s)
        hit = self.participating(s, TIMELY_TARGET, self.previous_epoch(s))
        on, off = elig & hit, elig & ~hit
        scores[on] -= np.minimum(np.uint64(1), scores[on])
        scores[off] += np.uint64(P["INACTIVITY_SCORE_BIAS"])
        if not self.in_leak(s):
            scores[elig] -= np.minimum(
                np.uint64(P["INACTIVITY_SCORE_RECOVERY_RATE"]), scores[elig])

    def rewards_and_penalties(self, s) -> None:
        if self.current_epoch(s) == 0:
            return
        P, prev = self.P, self.previous_epoch(s)
        inc = P["EFFECTIVE_BALANCE_INCREMENT"]
        active_increments = self.total_active_balance(s) // inc
        base, elig, leak = self.base_rewards(s), self.eligible(s), \
            self.in_leak(s)
        n = len(base)
        deltas = []
        for flag, weight in enumerate(FLAG_WEIGHTS):
            part = self.participating(s, flag, prev)
            part_inc = self.total_balance(s, part) // inc
            rewards = np.zeros(n, np.uint64)
            penalties = np.zeros(n, np.uint64)
            m = elig & part
            if not leak:
                rewards[m] = base[m] * np.uint64(weight * part_inc) // \
                    np.uint64(active_increments * WEIGHT_DENOMINATOR)
            if flag != TIMELY_HEAD:
                m = elig & ~part
                penalties[m] = base[m] * np.uint64(weight) // \
                    np.uint64(WEIGHT_DENOMINATOR)
            deltas.append((rewards, penalties))
        m = elig & ~self.participating(s, TIMELY_TARGET, prev)
        penalties = np.zeros(n, np.uint64)
        penalties[m] = s["validators"]["effective_balance"][m] * \
            s["inactivity_scores"][m] // np.uint64(
                P["INACTIVITY_SCORE_BIAS"]
                * P["INACTIVITY_PENALTY_QUOTIENT_ALTAIR"])
        deltas.append((np.zeros(n, np.uint64), penalties))
        for rewards, penalties in deltas:
            self.apply_deltas(s, rewards, penalties)

    def registry_updates(self, s) -> None:
        P, v, cur = self.P, s["validators"], self.current_epoch(s)
        queue = (v["activation_eligibility_epoch"] ==
                 np.uint64(FAR_FUTURE_EPOCH)) & \
            (v["effective_balance"] == np.uint64(P["MAX_EFFECTIVE_BALANCE"]))
        v["activation_eligibility_epoch"][queue] = cur + 1
        if (self.active(s, cur) & (v["effective_balance"] <=
                                   np.uint64(P["EJECTION_BALANCE"]))).any():
            raise NotImplementedError("reference: ejections")
        ready = np.nonzero((v["activation_eligibility_epoch"] <= np.uint64(
            s["finalized_checkpoint"]["epoch"])) & (
            v["activation_epoch"] == np.uint64(FAR_FUTURE_EPOCH)))[0]
        ready = ready[np.lexsort((ready,
                                  v["activation_eligibility_epoch"][ready]))]
        churn = max(P["MIN_PER_EPOCH_CHURN_LIMIT"],
                    int(self.active(s, cur).sum()) // P["CHURN_LIMIT_QUOTIENT"])
        v["activation_epoch"][ready[:churn]] = cur + 1 + P["MAX_SEED_LOOKAHEAD"]

    def slashings(self, s) -> None:
        P, v, cur = self.P, s["validators"], self.current_epoch(s)
        total = self.total_active_balance(s)
        adjusted = min(int(s["slashings"].sum())
                       * P["PROPORTIONAL_SLASHING_MULTIPLIER_ALTAIR"], total)
        inc = P["EFFECTIVE_BALANCE_INCREMENT"]
        hit = v["slashed"] & (np.uint64(
            cur + P["EPOCHS_PER_SLASHINGS_VECTOR"] // 2)
            == v["withdrawable_epoch"])
        for i in np.nonzero(hit)[0]:
            num = int(v["effective_balance"][i]) // inc * adjusted
            self.decrease(s, int(i), num // total * inc)

    def effective_balance_updates(self, s) -> None:
        P = self.P
        inc = P["EFFECTIVE_BALANCE_INCREMENT"]
        hyst = inc // P["HYSTERESIS_QUOTIENT"]
        down = np.uint64(hyst * P["HYSTERESIS_DOWNWARD_MULTIPLIER"])
        up = np.uint64(hyst * P["HYSTERESIS_UPWARD_MULTIPLIER"])
        bal, eff = s["balances"], s["validators"]["effective_balance"]
        m = (bal + down < eff) | (eff + up < bal)
        eff[m] = np.minimum(bal[m] - bal[m] % np.uint64(inc),
                            np.uint64(P["MAX_EFFECTIVE_BALANCE"]))

    # -- block ------------------------------------------------------------
    def process_block(self, s, block: dict) -> None:
        body = block["body"]
        need(block["slot"] == s["slot"], "block slot")
        need(block["slot"] > s["latest_block_header"]["slot"],
             "block not newer than header")
        proposer = self.proposer_index(s)
        need(block["proposer_index"] == proposer, "proposer index")
        need(block["parent_root"] ==
             self.T["Header"].root(s["latest_block_header"]), "parent root")
        s["latest_block_header"] = {
            "slot": block["slot"], "proposer_index": proposer,
            "parent_root": block["parent_root"], "state_root": b"\x00" * 32,
            "body_root": self.T["Body"].root(body)}
        need(not s["validators"]["slashed"][proposer], "proposer slashed")
        if self.electra:
            self.process_withdrawals(s, body["execution_payload"])
            self.process_execution_payload(s, body)
        epoch = self.current_epoch(s)
        mix = np.frombuffer(self.randao_mix(s, epoch), np.uint8) ^ \
            np.frombuffer(ssz.sha256(body["randao_reveal"]), np.uint8)
        s["randao_mixes"][epoch % self.P["EPOCHS_PER_HISTORICAL_VECTOR"]] = mix
        self.process_eth1_data(s, body["eth1_data"])
        self.process_operations(s, body, proposer)
        self.process_sync_aggregate(s, body["sync_aggregate"], proposer)

    def process_eth1_data(self, s, vote: dict) -> None:
        votes = s["eth1_data_votes"]
        cols = {k: np.concatenate([votes[k], np.asarray(
            [np.frombuffer(vote[k], np.uint8)] if isinstance(vote[k], bytes)
            else [vote[k]], votes[k].dtype)]) for k in votes}
        s["eth1_data_votes"] = ssz.Columns(len(votes) + 1, cols)
        same = np.ones(len(votes) + 1, bool)
        for k, col in cols.items():
            want = np.frombuffer(vote[k], np.uint8) \
                if isinstance(vote[k], bytes) else vote[k]
            same &= (col == want).reshape(len(same), -1).all(axis=1)
        if int(same.sum()) * 2 > self.P["EPOCHS_PER_ETH1_VOTING_PERIOD"] * \
                self.P["SLOTS_PER_EPOCH"]:
            s["eth1_data"] = dict(vote)

    def process_operations(self, s, body: dict, proposer: int) -> None:
        limit = s["eth1_data"]["deposit_count"]
        if self.electra:
            limit = min(limit, s["deposit_requests_start_index"])
        pending = max(limit - s["eth1_deposit_index"], 0)
        need(len(body["deposits"]) == min(self.P["MAX_DEPOSITS"], pending),
             "deposit count")
        for att in body["attestations"]:
            self.process_attestation(s, att, proposer)

    def flag_indices(self, s, data: dict, delay: int) -> list:
        cur = self.current_epoch(s)
        target = data["target"]["epoch"]
        justified = s["current_justified_checkpoint"] if target == cur \
            else s["previous_justified_checkpoint"]
        source = data["source"]["epoch"] == justified["epoch"] and \
            data["source"]["root"] == justified["root"]
        need(source, "attestation source")
        is_target = data["target"]["root"] == self.block_root(s, target)
        is_head = is_target and data["beacon_block_root"] == \
            self.block_root_at_slot(s, data["slot"])
        flags = []
        if delay <= math.isqrt(self.P["SLOTS_PER_EPOCH"]):
            flags.append(0)
        if is_target and (self.electra or delay <= self.P["SLOTS_PER_EPOCH"]):
            flags.append(1)
        if is_head and delay == self.P["MIN_ATTESTATION_INCLUSION_DELAY"]:
            flags.append(2)
        return flags

    def attesters(self, s, att: dict) -> np.ndarray:
        data, bits = att["data"], att["aggregation_bits"]
        target = data["target"]["epoch"]
        if not self.electra:
            need(data["index"] < self.committee_count(s, target),
                 "committee index")
            committee = self.committee(s, data["slot"], data["index"])
            need(len(bits) == len(committee), "aggregation bits length")
            return np.unique(committee[bits])
        need(data["index"] == 0, "electra attestation data index")
        parts, offset = [], 0
        for ci in np.nonzero(att["committee_bits"])[0]:
            need(ci < self.committee_count(s, target), "committee index")
            committee = self.committee(s, data["slot"], int(ci))
            need(offset + len(committee) <= len(bits),
                 "aggregation bits short")
            members = committee[bits[offset:offset + len(committee)]]
            need(len(members) > 0, "empty committee aggregate")
            parts.append(members)
            offset += len(committee)
        need(len(bits) == offset, "aggregation bits length")
        return np.unique(np.concatenate(parts)) if parts else \
            np.zeros(0, np.int64)

    def process_attestation(self, s, att: dict, proposer: int) -> None:
        P, data = self.P, att["data"]
        cur, prev = self.current_epoch(s), self.previous_epoch(s)
        target = data["target"]["epoch"]
        need(target in (prev, cur), "target epoch")
        need(target == self.epoch(data["slot"]), "target epoch of slot")
        need(data["slot"] + P["MIN_ATTESTATION_INCLUSION_DELAY"]
             <= s["slot"], "inclusion delay")
        if not self.electra:
            need(s["slot"] <= data["slot"] + P["SLOTS_PER_EPOCH"],
                 "attestation too old")
        idx = self.attesters(s, att)
        flags = self.flag_indices(s, data, s["slot"] - data["slot"])
        need(len(idx) > 0, "no attesters")
        part = s["current_epoch_participation"] if target == cur \
            else s["previous_epoch_participation"]
        base = self.base_rewards(s)
        numerator = 0
        for flag in flags:
            bit = np.uint8(1 << flag)
            fresh = idx[(part[idx] & bit) == 0]
            part[fresh] |= bit
            numerator += int(base[fresh].sum()) * FLAG_WEIGHTS[flag]
        denominator = (WEIGHT_DENOMINATOR - PROPOSER_WEIGHT) * \
            WEIGHT_DENOMINATOR // PROPOSER_WEIGHT
        self.increase(s, proposer, numerator // denominator)

    def process_sync_aggregate(self, s, agg: dict, proposer: int) -> None:
        P = self.P
        total_increments = self.total_active_balance(s) // \
            P["EFFECTIVE_BALANCE_INCREMENT"]
        total_base = self.base_reward_per_increment(s) * total_increments
        max_participant = total_base * SYNC_REWARD_WEIGHT // \
            WEIGHT_DENOMINATOR // P["SLOTS_PER_EPOCH"]
        reward = max_participant // P["SYNC_COMMITTEE_SIZE"]
        proposer_reward = reward * PROPOSER_WEIGHT // \
            (WEIGHT_DENOMINATOR - PROPOSER_WEIGHT)
        keys = s["validators"]["pubkey"].tobytes()
        where = {}
        for i in range(len(keys) // 48 - 1, -1, -1):
            where[keys[48 * i:48 * i + 48]] = i
        members = s["current_sync_committee"]["pubkeys"]
        for row, bit in zip(members, agg["sync_committee_bits"]):
            i = where.get(row.tobytes())
            need(i is not None, "sync committee pubkey not in registry")
            if bit:
                self.increase(s, i, reward)
                self.increase(s, proposer, proposer_reward)
            else:
                self.decrease(s, i, reward)

    def expected_withdrawals(self, s) -> list:
        P, v = self.P, s["validators"]
        if len(s["pending_partial_withdrawals"]):
            raise NotImplementedError("reference: pending partial withdrawals")
        n, epoch = len(v), self.current_epoch(s)
        order = (s["next_withdrawal_validator_index"] + np.arange(
            min(n, P["MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP"]))) % n
        prefix = v["withdrawal_credentials"][order, 0]
        has_exec = (prefix == 1) | (prefix == 2)
        bal = s["balances"][order]
        full = has_exec & (v["withdrawable_epoch"][order] <= np.uint64(epoch)) \
            & (bal > 0)
        max_eb = np.where(prefix == 2,
                          np.uint64(P["MAX_EFFECTIVE_BALANCE_ELECTRA"]),
                          np.uint64(P["MIN_ACTIVATION_BALANCE"]))
        partial = has_exec & (v["effective_balance"][order] == max_eb) & \
            (bal > max_eb)
        out = []
        for k, h in enumerate(np.nonzero(full | partial)[0]
                              [:P["MAX_WITHDRAWALS_PER_PAYLOAD"]]):
            vi = int(order[h])
            amount = int(bal[h]) if full[h] else int(bal[h] - max_eb[h])
            out.append((s["next_withdrawal_index"] + k, vi,
                        v["withdrawal_credentials"][vi, 12:].tobytes(),
                        amount))
        return out

    def process_withdrawals(self, s, payload: dict) -> None:
        P = self.P
        expected = self.expected_withdrawals(s)
        got = payload["withdrawals"]
        need(len(got) == len(expected), "withdrawal count")
        for k, (index, vi, address, amount) in enumerate(expected):
            need((int(got["index"][k]), int(got["validator_index"][k]),
                  got["address"][k].tobytes(), int(got["amount"][k]))
                 == (index, vi, address, amount), "withdrawal")
            self.decrease(s, vi, amount)
        if expected:
            s["next_withdrawal_index"] = expected[-1][0] + 1
        n = len(s["validators"])
        if len(expected) == P["MAX_WITHDRAWALS_PER_PAYLOAD"]:
            s["next_withdrawal_validator_index"] = (expected[-1][1] + 1) % n
        else:
            s["next_withdrawal_validator_index"] = (
                s["next_withdrawal_validator_index"]
                + P["MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP"]) % n

    def process_execution_payload(self, s, body: dict) -> None:
        p = body["execution_payload"]
        head = s["latest_execution_payload_header"]
        need(p["parent_hash"] == head["block_hash"], "payload parent hash")
        need(p["prev_randao"] == self.randao_mix(s, self.current_epoch(s)),
             "payload prev_randao")
        need(p["timestamp"] == s["genesis_time"] + s["slot"]
             * self.P["SECONDS_PER_SLOT"], "payload timestamp")
        need(len(body["blob_kzg_commitments"])
             <= self.P["MAX_BLOBS_PER_BLOCK_ELECTRA"], "blob count")
        new = {k: p[k] for k in head if k in p}
        new["transactions_root"] = self.T["Transactions"].root(
            p["transactions"])
        new["withdrawals_root"] = self.T["Withdrawals"].root(p["withdrawals"])
        s["latest_execution_payload_header"] = new
