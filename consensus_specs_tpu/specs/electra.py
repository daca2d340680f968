"""Electra spec: maxEB (EIP-7251), execution-layer requests (EIP-7002,
EIP-6110), committee-bit attestations (EIP-7549), blob throughput (EIP-7691).

From-scratch implementation of /root/reference/specs/electra/
{beacon-chain.md,fork.md} as a DenebSpec subclass.  Docstring citations are
to the reference markdown (file:line) for parity checking.

NOTE: SSZ Container fields are live class annotations (no PEP 563 here).
"""
from dataclasses import dataclass

from ..ssz import (
    uint64, Bitlist, Bitvector, Vector, List, Container,
    Bytes4, Bytes20, Bytes32, Bytes48, Bytes96,
    hash_tree_root, serialize,
)
from ..utils import bls, spans
from .deneb import DenebSpec
from .phase0 import bytes_to_uint64


@dataclass
class NewPayloadRequest:
    """electra/beacon-chain.md:1012 — adds execution_requests."""
    execution_payload: object
    versioned_hashes: list
    parent_beacon_block_root: bytes
    execution_requests: object


class ElectraSpec(DenebSpec):
    fork = "electra"

    # ------------------------------------------------------------------
    # constants (electra/beacon-chain.md:127-151)
    # ------------------------------------------------------------------
    def _build_constants(self) -> None:
        super()._build_constants()
        self.UNSET_DEPOSIT_REQUESTS_START_INDEX = uint64(2**64 - 1)
        self.FULL_EXIT_REQUEST_AMOUNT = uint64(0)
        self.COMPOUNDING_WITHDRAWAL_PREFIX = b"\x02"
        self.DEPOSIT_REQUEST_TYPE = b"\x00"
        self.WITHDRAWAL_REQUEST_TYPE = b"\x01"
        self.CONSOLIDATION_REQUEST_TYPE = b"\x02"

    # ------------------------------------------------------------------
    # containers (electra/beacon-chain.md:218-422)
    # ------------------------------------------------------------------
    def _build_types(self) -> None:
        super()._build_types()
        p = self

        class PendingDeposit(Container):
            pubkey: Bytes48
            withdrawal_credentials: Bytes32
            amount: uint64
            signature: Bytes96
            slot: uint64

        class PendingPartialWithdrawal(Container):
            validator_index: uint64
            amount: uint64
            withdrawable_epoch: uint64

        class PendingConsolidation(Container):
            source_index: uint64
            target_index: uint64

        class DepositRequest(Container):
            pubkey: Bytes48
            withdrawal_credentials: Bytes32
            amount: uint64
            signature: Bytes96
            index: uint64

        class WithdrawalRequest(Container):
            source_address: Bytes20
            validator_pubkey: Bytes48
            amount: uint64

        class ConsolidationRequest(Container):
            source_address: Bytes20
            source_pubkey: Bytes48
            target_pubkey: Bytes48

        class ExecutionRequests(Container):
            deposits: List[DepositRequest, p.MAX_DEPOSIT_REQUESTS_PER_PAYLOAD]
            withdrawals: List[WithdrawalRequest, p.MAX_WITHDRAWAL_REQUESTS_PER_PAYLOAD]
            consolidations: List[ConsolidationRequest, p.MAX_CONSOLIDATION_REQUESTS_PER_PAYLOAD]

        # [Modified in Electra:EIP7549] aggregation across a slot's committees
        class Attestation(Container):
            aggregation_bits: Bitlist[p.MAX_VALIDATORS_PER_COMMITTEE * p.MAX_COMMITTEES_PER_SLOT]
            data: p.AttestationData
            signature: Bytes96
            committee_bits: Bitvector[p.MAX_COMMITTEES_PER_SLOT]

        class IndexedAttestation(Container):
            attesting_indices: List[uint64, p.MAX_VALIDATORS_PER_COMMITTEE * p.MAX_COMMITTEES_PER_SLOT]
            data: p.AttestationData
            signature: Bytes96

        class AttesterSlashing(Container):
            attestation_1: IndexedAttestation
            attestation_2: IndexedAttestation

        class SingleAttestation(Container):
            committee_index: uint64
            attester_index: uint64
            data: p.AttestationData
            signature: Bytes96

        # [Modified in Electra] rebuilt over the EIP-7549 Attestation
        # (electra/validator.md AggregateAndProof/SignedAggregateAndProof)
        class AggregateAndProof(Container):
            aggregator_index: uint64
            aggregate: Attestation
            selection_proof: Bytes96

        class SignedAggregateAndProof(Container):
            message: AggregateAndProof
            signature: Bytes96

        class BeaconBlockBody(Container):
            randao_reveal: Bytes96
            eth1_data: p.Eth1Data
            graffiti: Bytes32
            proposer_slashings: List[p.ProposerSlashing, p.MAX_PROPOSER_SLASHINGS]
            attester_slashings: List[AttesterSlashing, p.MAX_ATTESTER_SLASHINGS_ELECTRA]
            attestations: List[Attestation, p.MAX_ATTESTATIONS_ELECTRA]
            deposits: List[p.Deposit, p.MAX_DEPOSITS]
            voluntary_exits: List[p.SignedVoluntaryExit, p.MAX_VOLUNTARY_EXITS]
            sync_aggregate: p.SyncAggregate
            execution_payload: p.ExecutionPayload
            bls_to_execution_changes: List[p.SignedBLSToExecutionChange, p.MAX_BLS_TO_EXECUTION_CHANGES]
            blob_kzg_commitments: List[Bytes48, p.MAX_BLOB_COMMITMENTS_PER_BLOCK]
            execution_requests: ExecutionRequests

        class BeaconBlock(Container):
            slot: uint64
            proposer_index: uint64
            parent_root: Bytes32
            state_root: Bytes32
            body: BeaconBlockBody

        class SignedBeaconBlock(Container):
            message: BeaconBlock
            signature: Bytes96

        class BeaconState(Container):
            genesis_time: uint64
            genesis_validators_root: Bytes32
            slot: uint64
            fork: p.Fork
            latest_block_header: p.BeaconBlockHeader
            block_roots: Vector[Bytes32, p.SLOTS_PER_HISTORICAL_ROOT]
            state_roots: Vector[Bytes32, p.SLOTS_PER_HISTORICAL_ROOT]
            historical_roots: List[Bytes32, p.HISTORICAL_ROOTS_LIMIT]
            eth1_data: p.Eth1Data
            eth1_data_votes: List[p.Eth1Data, p.EPOCHS_PER_ETH1_VOTING_PERIOD * p.SLOTS_PER_EPOCH]
            eth1_deposit_index: uint64
            validators: List[p.Validator, p.VALIDATOR_REGISTRY_LIMIT]
            balances: List[uint64, p.VALIDATOR_REGISTRY_LIMIT]
            randao_mixes: Vector[Bytes32, p.EPOCHS_PER_HISTORICAL_VECTOR]
            slashings: Vector[uint64, p.EPOCHS_PER_SLASHINGS_VECTOR]
            previous_epoch_participation: List[p.ParticipationFlags, p.VALIDATOR_REGISTRY_LIMIT]
            current_epoch_participation: List[p.ParticipationFlags, p.VALIDATOR_REGISTRY_LIMIT]
            justification_bits: Bitvector[p.JUSTIFICATION_BITS_LENGTH]
            previous_justified_checkpoint: p.Checkpoint
            current_justified_checkpoint: p.Checkpoint
            finalized_checkpoint: p.Checkpoint
            inactivity_scores: List[uint64, p.VALIDATOR_REGISTRY_LIMIT]
            current_sync_committee: p.SyncCommittee
            next_sync_committee: p.SyncCommittee
            latest_execution_payload_header: p.ExecutionPayloadHeader
            next_withdrawal_index: uint64
            next_withdrawal_validator_index: uint64
            historical_summaries: List[p.HistoricalSummary, p.HISTORICAL_ROOTS_LIMIT]
            deposit_requests_start_index: uint64
            deposit_balance_to_consume: uint64
            exit_balance_to_consume: uint64
            earliest_exit_epoch: uint64
            consolidation_balance_to_consume: uint64
            earliest_consolidation_epoch: uint64
            pending_deposits: List[PendingDeposit, p.PENDING_DEPOSITS_LIMIT]
            pending_partial_withdrawals: List[PendingPartialWithdrawal, p.PENDING_PARTIAL_WITHDRAWALS_LIMIT]
            pending_consolidations: List[PendingConsolidation, p.PENDING_CONSOLIDATIONS_LIMIT]

        for name, cls in list(locals().items()):
            if isinstance(cls, type) and issubclass(cls, Container):
                setattr(self, name, cls)

    # ------------------------------------------------------------------
    # predicates (electra/beacon-chain.md:426-529)
    # ------------------------------------------------------------------
    def compute_proposer_index(self, state, indices, seed):
        """16-bit random value filter against MAX_EFFECTIVE_BALANCE_ELECTRA
        (electra/beacon-chain.md:433)."""
        assert len(indices) > 0
        MAX_RANDOM_VALUE = 2**16 - 1
        i = 0
        total = len(indices)
        perm = self._shuffle_permutation(seed, total)
        while True:
            candidate_index = indices[int(perm[i % total])]
            random_bytes = self.hash(
                bytes(seed) + self.uint_to_bytes(uint64(i // 16)))
            offset = i % 16 * 2
            random_value = bytes_to_uint64(random_bytes[offset:offset + 2])
            effective_balance = \
                state.validators[candidate_index].effective_balance
            if (effective_balance * MAX_RANDOM_VALUE
                    >= self.MAX_EFFECTIVE_BALANCE_ELECTRA * random_value):
                return uint64(candidate_index)
            i += 1

    def is_eligible_for_activation_queue(self, validator) -> bool:
        # [Modified in Electra:EIP7251] >= MIN_ACTIVATION_BALANCE
        return (validator.activation_eligibility_epoch == self.FAR_FUTURE_EPOCH
                and validator.effective_balance >= self.MIN_ACTIVATION_BALANCE)

    def is_compounding_withdrawal_credential(self,
                                             withdrawal_credentials) -> bool:
        return bytes(withdrawal_credentials)[:1] \
            == self.COMPOUNDING_WITHDRAWAL_PREFIX

    def has_compounding_withdrawal_credential(self, validator) -> bool:
        return self.is_compounding_withdrawal_credential(
            validator.withdrawal_credentials)

    def has_execution_withdrawal_credential(self, validator) -> bool:
        return (self.has_compounding_withdrawal_credential(validator)
                or self.has_eth1_withdrawal_credential(validator))

    def is_fully_withdrawable_validator(self, validator, balance,
                                        epoch) -> bool:
        return (self.has_execution_withdrawal_credential(validator)
                and validator.withdrawable_epoch <= epoch
                and balance > 0)

    def is_partially_withdrawable_validator(self, validator,
                                            balance) -> bool:
        max_effective_balance = self.get_max_effective_balance(validator)
        has_max_effective_balance = (
            validator.effective_balance == max_effective_balance)
        has_excess_balance = balance > max_effective_balance
        return (self.has_execution_withdrawal_credential(validator)
                and has_max_effective_balance and has_excess_balance)

    # ------------------------------------------------------------------
    # misc + accessors (electra/beacon-chain.md:531-651)
    # ------------------------------------------------------------------
    def get_committee_indices(self, committee_bits):
        return [uint64(index) for index, bit in enumerate(committee_bits)
                if bit]

    def get_max_effective_balance(self, validator):
        if self.has_compounding_withdrawal_credential(validator):
            return self.MAX_EFFECTIVE_BALANCE_ELECTRA
        return self.MIN_ACTIVATION_BALANCE

    def max_effective_balance_for_validator(self, validator):
        # hook used by process_effective_balance_updates (phase0.py)
        return self.get_max_effective_balance(validator)

    def get_balance_churn_limit(self, state):
        churn = max(
            self.config.MIN_PER_EPOCH_CHURN_LIMIT_ELECTRA,
            self.get_total_active_balance(state)
            // self.config.CHURN_LIMIT_QUOTIENT)
        return uint64(churn - churn % self.EFFECTIVE_BALANCE_INCREMENT)

    def get_activation_exit_churn_limit(self, state):
        return uint64(min(
            self.config.MAX_PER_EPOCH_ACTIVATION_EXIT_CHURN_LIMIT,
            self.get_balance_churn_limit(state)))

    def get_consolidation_churn_limit(self, state):
        return uint64(self.get_balance_churn_limit(state)
                      - self.get_activation_exit_churn_limit(state))

    def get_pending_balance_to_withdraw(self, state, validator_index):
        return uint64(sum(
            int(withdrawal.amount)
            for withdrawal in state.pending_partial_withdrawals
            if withdrawal.validator_index == validator_index))

    def get_attesting_indices(self, state, attestation):
        """Across the slot's committees via committee_bits
        (electra/beacon-chain.md:601)."""
        output = set()
        committee_indices = self.get_committee_indices(
            attestation.committee_bits)
        committee_offset = 0
        for committee_index in committee_indices:
            committee = self.get_beacon_committee(
                state, attestation.data.slot, committee_index)
            committee_attesters = set(
                attester_index for i, attester_index in enumerate(committee)
                if attestation.aggregation_bits[committee_offset + i])
            output = output.union(committee_attesters)
            committee_offset += len(committee)
        return output

    def compute_on_chain_aggregate(self, network_aggregates):
        """Densely pack same-data aggregates from distinct committees
        into one on-chain Attestation (electra/validator.md:118)."""
        from ..utils import bls
        aggregates = sorted(
            network_aggregates,
            key=lambda a: self.get_committee_indices(a.committee_bits)[0])
        data = aggregates[0].data
        aggregation_bits = []
        for a in aggregates:
            aggregation_bits.extend(a.aggregation_bits)
        signature = bls.Aggregate([bytes(a.signature) for a in aggregates])
        committee_indices = [
            self.get_committee_indices(a.committee_bits)[0]
            for a in aggregates]
        committee_flags = [(index in committee_indices)
                           for index in range(self.MAX_COMMITTEES_PER_SLOT)]
        return self.Attestation(
            aggregation_bits=aggregation_bits,
            data=data,
            committee_bits=committee_flags,
            signature=signature)

    def get_next_sync_committee_indices(self, state):
        """16-bit random filter (electra/beacon-chain.md:626)."""
        epoch = uint64(self.get_current_epoch(state) + 1)
        MAX_RANDOM_VALUE = 2**16 - 1
        active_validator_indices = self.get_active_validator_indices(
            state, epoch)
        active_validator_count = len(active_validator_indices)
        seed = self.get_seed(state, epoch, self.DOMAIN_SYNC_COMMITTEE)
        i = 0
        sync_committee_indices = []
        while len(sync_committee_indices) < self.SYNC_COMMITTEE_SIZE:
            shuffled_index = self.compute_shuffled_index(
                i % active_validator_count, active_validator_count, seed)
            candidate_index = active_validator_indices[shuffled_index]
            random_bytes = self.hash(
                bytes(seed) + self.uint_to_bytes(uint64(i // 16)))
            offset = i % 16 * 2
            random_value = bytes_to_uint64(random_bytes[offset:offset + 2])
            effective_balance = \
                state.validators[candidate_index].effective_balance
            if (effective_balance * MAX_RANDOM_VALUE
                    >= self.MAX_EFFECTIVE_BALANCE_ELECTRA * random_value):
                sync_committee_indices.append(candidate_index)
            i += 1
        return sync_committee_indices

    # ------------------------------------------------------------------
    # mutators (electra/beacon-chain.md:653-789)
    # ------------------------------------------------------------------
    def initiate_validator_exit(self, state, index) -> None:
        validator = state.validators[index]
        if validator.exit_epoch != self.FAR_FUTURE_EPOCH:
            return
        exit_queue_epoch = self.compute_exit_epoch_and_update_churn(
            state, validator.effective_balance)
        validator.exit_epoch = exit_queue_epoch
        validator.withdrawable_epoch = uint64(
            validator.exit_epoch
            + self.config.MIN_VALIDATOR_WITHDRAWABILITY_DELAY)

    def switch_to_compounding_validator(self, state, index) -> None:
        validator = state.validators[index]
        validator.withdrawal_credentials = (
            self.COMPOUNDING_WITHDRAWAL_PREFIX
            + bytes(validator.withdrawal_credentials)[1:])
        self.queue_excess_active_balance(state, index)

    def queue_excess_active_balance(self, state, index) -> None:
        balance = state.balances[index]
        if balance > self.MIN_ACTIVATION_BALANCE:
            excess_balance = uint64(balance - self.MIN_ACTIVATION_BALANCE)
            state.balances[index] = uint64(self.MIN_ACTIVATION_BALANCE)
            validator = state.validators[index]
            # G2 point at infinity as signature placeholder; GENESIS_SLOT
            # distinguishes from a pending deposit request
            state.pending_deposits.append(self.PendingDeposit(
                pubkey=validator.pubkey,
                withdrawal_credentials=validator.withdrawal_credentials,
                amount=excess_balance,
                signature=self.G2_POINT_AT_INFINITY,
                slot=self.GENESIS_SLOT))

    def compute_exit_epoch_and_update_churn(self, state, exit_balance):
        earliest_exit_epoch = max(
            int(state.earliest_exit_epoch),
            int(self.compute_activation_exit_epoch(
                self.get_current_epoch(state))))
        per_epoch_churn = self.get_activation_exit_churn_limit(state)
        if state.earliest_exit_epoch < earliest_exit_epoch:
            exit_balance_to_consume = int(per_epoch_churn)
        else:
            exit_balance_to_consume = int(state.exit_balance_to_consume)

        if exit_balance > exit_balance_to_consume:
            balance_to_process = int(exit_balance) - exit_balance_to_consume
            additional_epochs = (balance_to_process - 1) \
                // int(per_epoch_churn) + 1
            earliest_exit_epoch += additional_epochs
            exit_balance_to_consume += additional_epochs * int(per_epoch_churn)

        state.exit_balance_to_consume = uint64(
            exit_balance_to_consume - int(exit_balance))
        state.earliest_exit_epoch = uint64(earliest_exit_epoch)
        return state.earliest_exit_epoch

    def compute_consolidation_epoch_and_update_churn(self, state,
                                                     consolidation_balance):
        earliest_consolidation_epoch = max(
            int(state.earliest_consolidation_epoch),
            int(self.compute_activation_exit_epoch(
                self.get_current_epoch(state))))
        per_epoch_consolidation_churn = \
            self.get_consolidation_churn_limit(state)
        if state.earliest_consolidation_epoch < earliest_consolidation_epoch:
            consolidation_balance_to_consume = \
                int(per_epoch_consolidation_churn)
        else:
            consolidation_balance_to_consume = \
                int(state.consolidation_balance_to_consume)

        if consolidation_balance > consolidation_balance_to_consume:
            balance_to_process = (int(consolidation_balance)
                                  - consolidation_balance_to_consume)
            additional_epochs = (balance_to_process - 1) \
                // int(per_epoch_consolidation_churn) + 1
            earliest_consolidation_epoch += additional_epochs
            consolidation_balance_to_consume += \
                additional_epochs * int(per_epoch_consolidation_churn)

        state.consolidation_balance_to_consume = uint64(
            consolidation_balance_to_consume - int(consolidation_balance))
        state.earliest_consolidation_epoch = \
            uint64(earliest_consolidation_epoch)
        return state.earliest_consolidation_epoch

    def min_slashing_penalty_quotient(self) -> int:
        return self.MIN_SLASHING_PENALTY_QUOTIENT_ELECTRA

    def whistleblower_reward_quotient(self) -> int:
        return self.WHISTLEBLOWER_REWARD_QUOTIENT_ELECTRA

    # ------------------------------------------------------------------
    # epoch processing (electra/beacon-chain.md:793-1003)
    # ------------------------------------------------------------------
    def process_epoch(self, state) -> None:
        from . import epoch_fast
        if epoch_fast.fused_epoch(self, state):
            # fused_epoch ran the scalar registry + pending-deposit /
            # consolidation queues at their reference positions itself
            with spans.span("epoch.tail"):
                self.process_eth1_data_reset(state)
                self.process_slashings_reset(state)
                self.process_randao_mixes_reset(state)
                self.process_historical_summaries_update(state)
                self.process_participation_flag_updates(state)
                self.process_sync_committee_updates(state)
            return
        self.process_justification_and_finalization(state)
        self.process_inactivity_updates(state)
        self.process_rewards_and_penalties(state)
        self.process_registry_updates(state)
        self.process_slashings(state)
        self.process_eth1_data_reset(state)
        self.process_pending_deposits(state)
        self.process_pending_consolidations(state)
        self.process_effective_balance_updates(state)
        self.process_slashings_reset(state)
        self.process_randao_mixes_reset(state)
        self.process_historical_summaries_update(state)
        self.process_participation_flag_updates(state)
        self.process_sync_committee_updates(state)

    def process_registry_updates(self, state) -> None:
        """Single-pass eligibility/ejection/activation, activations no
        longer churn-limited (electra/beacon-chain.md:825)."""
        current_epoch = self.get_current_epoch(state)
        activation_epoch = self.compute_activation_exit_epoch(current_epoch)
        for index, validator in enumerate(state.validators):
            if self.is_eligible_for_activation_queue(validator):
                validator.activation_eligibility_epoch = uint64(
                    current_epoch + 1)
            if (self.is_active_validator(validator, current_epoch)
                    and validator.effective_balance
                    <= self.config.EJECTION_BALANCE):
                self.initiate_validator_exit(state, index)
            if self.is_eligible_for_activation(state, validator):
                validator.activation_epoch = activation_epoch

    def process_slashings(self, state) -> None:
        """Increment-factored correlation penalty
        (electra/beacon-chain.md:846)."""
        epoch = self.get_current_epoch(state)
        total_balance = self.get_total_active_balance(state)
        adjusted_total_slashing_balance = min(
            sum(int(x) for x in state.slashings)
            * self.proportional_slashing_multiplier(),
            int(total_balance))
        increment = self.EFFECTIVE_BALANCE_INCREMENT
        penalty_per_effective_balance_increment = \
            adjusted_total_slashing_balance // (int(total_balance) // increment)
        for index, validator in enumerate(state.validators):
            if (validator.slashed
                    and epoch + self.EPOCHS_PER_SLASHINGS_VECTOR // 2
                    == validator.withdrawable_epoch):
                effective_balance_increments = \
                    validator.effective_balance // increment
                penalty = (penalty_per_effective_balance_increment
                           * effective_balance_increments)
                self.decrease_balance(state, index, uint64(penalty))

    def apply_pending_deposit(self, state, deposit) -> None:
        validator_pubkeys = [v.pubkey for v in state.validators]
        if deposit.pubkey not in validator_pubkeys:
            if self.is_valid_deposit_signature(
                    deposit.pubkey, deposit.withdrawal_credentials,
                    deposit.amount, deposit.signature):
                self.add_validator_to_registry(
                    state, deposit.pubkey, deposit.withdrawal_credentials,
                    deposit.amount)
        else:
            validator_index = validator_pubkeys.index(deposit.pubkey)
            self.increase_balance(state, validator_index, deposit.amount)

    def process_pending_deposits(self, state) -> None:
        """Finalization/churn-bounded pending-deposit application
        (electra/beacon-chain.md:894).  With sigpipe enabled, the
        epoch's deposit signature checks are batch-verified up front
        (valid-or-skip, like block deposits) and consumed at the
        `is_valid_deposit_signature` seam inside the loop."""
        from ..sigpipe import verify as sigpipe_verify
        with sigpipe_verify.pending_deposit_scope(self, state):
            self._process_pending_deposits_inline(state)

    def _process_pending_deposits_inline(self, state) -> None:
        next_epoch = uint64(self.get_current_epoch(state) + 1)
        available_for_processing = (
            int(state.deposit_balance_to_consume)
            + int(self.get_activation_exit_churn_limit(state)))
        processed_amount = 0
        next_deposit_index = 0
        deposits_to_postpone = []
        is_churn_limit_reached = False
        finalized_slot = self.compute_start_slot_at_epoch(
            state.finalized_checkpoint.epoch)

        for deposit in state.pending_deposits:
            # deposit requests wait until eth1-bridge deposits are drained
            if (deposit.slot > self.GENESIS_SLOT
                    and state.eth1_deposit_index
                    < state.deposit_requests_start_index):
                break
            if deposit.slot > finalized_slot:
                break
            if next_deposit_index >= self.MAX_PENDING_DEPOSITS_PER_EPOCH:
                break

            is_validator_exited = False
            is_validator_withdrawn = False
            validator_pubkeys = [v.pubkey for v in state.validators]
            if deposit.pubkey in validator_pubkeys:
                validator = state.validators[
                    validator_pubkeys.index(deposit.pubkey)]
                is_validator_exited = \
                    validator.exit_epoch < self.FAR_FUTURE_EPOCH
                is_validator_withdrawn = \
                    validator.withdrawable_epoch < next_epoch

            if is_validator_withdrawn:
                # balance will never become active: apply without churn
                self.apply_pending_deposit(state, deposit)
            elif is_validator_exited:
                deposits_to_postpone.append(deposit)
            else:
                is_churn_limit_reached = (
                    processed_amount + int(deposit.amount)
                    > available_for_processing)
                if is_churn_limit_reached:
                    break
                processed_amount += int(deposit.amount)
                self.apply_pending_deposit(state, deposit)

            next_deposit_index += 1

        state.pending_deposits = type(state.pending_deposits)(
            list(state.pending_deposits)[next_deposit_index:]
            + deposits_to_postpone)

        if is_churn_limit_reached:
            state.deposit_balance_to_consume = uint64(
                available_for_processing - processed_amount)
        else:
            state.deposit_balance_to_consume = uint64(0)

    def process_pending_consolidations(self, state) -> None:
        next_epoch = uint64(self.get_current_epoch(state) + 1)
        next_pending_consolidation = 0
        for pending_consolidation in state.pending_consolidations:
            source_validator = \
                state.validators[pending_consolidation.source_index]
            if source_validator.slashed:
                next_pending_consolidation += 1
                continue
            if source_validator.withdrawable_epoch > next_epoch:
                break
            source_effective_balance = min(
                int(state.balances[pending_consolidation.source_index]),
                int(source_validator.effective_balance))
            self.decrease_balance(state, pending_consolidation.source_index,
                                  uint64(source_effective_balance))
            self.increase_balance(state, pending_consolidation.target_index,
                                  uint64(source_effective_balance))
            next_pending_consolidation += 1

        state.pending_consolidations = type(state.pending_consolidations)(
            list(state.pending_consolidations)[next_pending_consolidation:])

    # ------------------------------------------------------------------
    # block processing (electra/beacon-chain.md:1092-1311)
    # ------------------------------------------------------------------
    def max_blobs_per_block(self) -> int:
        # [Modified in Electra:EIP7691]
        return self.config.MAX_BLOBS_PER_BLOCK_ELECTRA

    def get_expected_withdrawals(self, state):
        """Returns (withdrawals, processed_partial_withdrawals_count)
        (electra/beacon-chain.md:1112)."""
        epoch = self.get_current_epoch(state)
        withdrawal_index = int(state.next_withdrawal_index)
        validator_index = int(state.next_withdrawal_validator_index)
        withdrawals = []
        processed_partial_withdrawals_count = 0

        # [New in Electra:EIP7251] consume pending partial withdrawals
        for withdrawal in state.pending_partial_withdrawals:
            if (withdrawal.withdrawable_epoch > epoch
                    or len(withdrawals)
                    == self.MAX_PENDING_PARTIALS_PER_WITHDRAWALS_SWEEP):
                break
            validator = state.validators[withdrawal.validator_index]
            has_sufficient_effective_balance = (
                validator.effective_balance >= self.MIN_ACTIVATION_BALANCE)
            has_excess_balance = (
                state.balances[withdrawal.validator_index]
                > self.MIN_ACTIVATION_BALANCE)
            if (validator.exit_epoch == self.FAR_FUTURE_EPOCH
                    and has_sufficient_effective_balance
                    and has_excess_balance):
                withdrawable_balance = min(
                    int(state.balances[withdrawal.validator_index])
                    - int(self.MIN_ACTIVATION_BALANCE),
                    int(withdrawal.amount))
                withdrawals.append(self.Withdrawal(
                    index=withdrawal_index,
                    validator_index=withdrawal.validator_index,
                    address=Bytes20(
                        bytes(validator.withdrawal_credentials)[12:]),
                    amount=withdrawable_balance))
                withdrawal_index += 1
            processed_partial_withdrawals_count += 1

        # sweep for remaining
        bound = min(len(state.validators),
                    self.MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP)
        for _ in range(bound):
            validator = state.validators[validator_index]
            partially_withdrawn_balance = sum(
                int(withdrawal.amount) for withdrawal in withdrawals
                if withdrawal.validator_index == validator_index)
            balance = uint64(int(state.balances[validator_index])
                             - partially_withdrawn_balance)
            address = Bytes20(bytes(validator.withdrawal_credentials)[12:])
            if self.is_fully_withdrawable_validator(validator, balance,
                                                    epoch):
                withdrawals.append(self.Withdrawal(
                    index=withdrawal_index,
                    validator_index=validator_index,
                    address=address,
                    amount=balance))
                withdrawal_index += 1
            elif self.is_partially_withdrawable_validator(validator,
                                                          balance):
                withdrawals.append(self.Withdrawal(
                    index=withdrawal_index,
                    validator_index=validator_index,
                    address=address,
                    amount=uint64(
                        int(balance)
                        - int(self.get_max_effective_balance(validator)))))
                withdrawal_index += 1
            if len(withdrawals) == self.MAX_WITHDRAWALS_PER_PAYLOAD:
                break
            validator_index = (validator_index + 1) % len(state.validators)
        return withdrawals, processed_partial_withdrawals_count

    def process_withdrawals(self, state, payload) -> None:
        expected_withdrawals, processed_partial_withdrawals_count = \
            self.get_expected_withdrawals(state)

        assert len(payload.withdrawals) == len(expected_withdrawals)
        for expected, actual in zip(expected_withdrawals,
                                    payload.withdrawals):
            assert actual == expected

        for withdrawal in expected_withdrawals:
            self.decrease_balance(state, withdrawal.validator_index,
                                  withdrawal.amount)

        # [New in Electra:EIP7251] drop consumed pending partials
        state.pending_partial_withdrawals = \
            type(state.pending_partial_withdrawals)(
                list(state.pending_partial_withdrawals)[
                    processed_partial_withdrawals_count:])

        if len(expected_withdrawals) != 0:
            state.next_withdrawal_index = uint64(
                expected_withdrawals[-1].index + 1)
        if len(expected_withdrawals) == self.MAX_WITHDRAWALS_PER_PAYLOAD:
            next_validator_index = uint64(
                (expected_withdrawals[-1].validator_index + 1)
                % len(state.validators))
        else:
            next_index = (int(state.next_withdrawal_validator_index)
                          + self.MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP)
            next_validator_index = uint64(
                next_index % len(state.validators))
        state.next_withdrawal_validator_index = next_validator_index

    def get_execution_requests_list(self, execution_requests):
        """EIP-7685 encoding (electra/beacon-chain.md:1212)."""
        requests = [
            (self.DEPOSIT_REQUEST_TYPE, execution_requests.deposits),
            (self.WITHDRAWAL_REQUEST_TYPE, execution_requests.withdrawals),
            (self.CONSOLIDATION_REQUEST_TYPE,
             execution_requests.consolidations),
        ]
        return [request_type + serialize(request_data)
                for request_type, request_data in requests
                if len(request_data) != 0]

    def get_execution_requests(self, execution_requests_list):
        """EIP-7685 decoding (electra/validator.md:198): typed request
        chunks in strictly ascending type order, no empties."""
        from ..ssz import List
        deposits: list = []
        withdrawals: list = []
        consolidations: list = []
        request_types = [self.DEPOSIT_REQUEST_TYPE,
                         self.WITHDRAWAL_REQUEST_TYPE,
                         self.CONSOLIDATION_REQUEST_TYPE]
        prev_request_type = None
        for request in execution_requests_list:
            request_type, request_data = \
                bytes(request[0:1]), bytes(request[1:])
            assert request_type in request_types
            assert len(request_data) != 0
            # strictly ascending, no duplicates
            assert prev_request_type is None \
                or prev_request_type < request_type
            prev_request_type = request_type
            if request_type == self.DEPOSIT_REQUEST_TYPE:
                deposits = List[
                    self.DepositRequest,
                    self.MAX_DEPOSIT_REQUESTS_PER_PAYLOAD
                ].deserialize(request_data)
            elif request_type == self.WITHDRAWAL_REQUEST_TYPE:
                withdrawals = List[
                    self.WithdrawalRequest,
                    self.MAX_WITHDRAWAL_REQUESTS_PER_PAYLOAD
                ].deserialize(request_data)
            elif request_type == self.CONSOLIDATION_REQUEST_TYPE:
                consolidations = List[
                    self.ConsolidationRequest,
                    self.MAX_CONSOLIDATION_REQUESTS_PER_PAYLOAD
                ].deserialize(request_data)
        return self.ExecutionRequests(
            deposits=deposits, withdrawals=withdrawals,
            consolidations=consolidations)

    def process_execution_payload(self, state, body,
                                  execution_engine) -> None:
        payload = body.execution_payload
        assert payload.parent_hash == \
            state.latest_execution_payload_header.block_hash
        assert payload.prev_randao == self.get_randao_mix(
            state, self.get_current_epoch(state))
        assert payload.timestamp == self.compute_timestamp_at_slot(
            state, state.slot)
        assert len(body.blob_kzg_commitments) <= self.max_blobs_per_block()
        versioned_hashes = [
            self.kzg_commitment_to_versioned_hash(commitment)
            for commitment in body.blob_kzg_commitments]
        assert execution_engine.verify_and_notify_new_payload(
            NewPayloadRequest(
                execution_payload=payload,
                versioned_hashes=versioned_hashes,
                parent_beacon_block_root=state.latest_block_header.parent_root,
                execution_requests=body.execution_requests))
        state.latest_execution_payload_header = \
            self.build_execution_payload_header(payload)

    def process_operations(self, state, body) -> None:
        """[Modified in Electra:EIP6110] legacy deposit phase-out + new
        execution-request ops (electra/beacon-chain.md:1281)."""
        eth1_deposit_index_limit = min(
            int(state.eth1_data.deposit_count),
            int(state.deposit_requests_start_index))
        if state.eth1_deposit_index < eth1_deposit_index_limit:
            assert len(body.deposits) == min(
                self.MAX_DEPOSITS,
                eth1_deposit_index_limit - int(state.eth1_deposit_index))
        else:
            assert len(body.deposits) == 0

        for operation in body.proposer_slashings:
            self.process_proposer_slashing(state, operation)
        for operation in body.attester_slashings:
            self.process_attester_slashing(state, operation)
        for operation in body.attestations:
            self.process_attestation(state, operation)
        for operation in body.deposits:
            self.process_deposit(state, operation)
        for operation in body.voluntary_exits:
            self.process_voluntary_exit(state, operation)
        for operation in body.bls_to_execution_changes:
            self.process_bls_to_execution_change(state, operation)
        for operation in body.execution_requests.deposits:
            self.process_deposit_request(state, operation)
        for operation in body.execution_requests.withdrawals:
            self.process_withdrawal_request(state, operation)
        for operation in body.execution_requests.consolidations:
            self.process_consolidation_request(state, operation)

    def process_attestation(self, state, attestation) -> None:
        """[Modified in Electra:EIP7549] committee_bits validation
        (electra/beacon-chain.md:1312)."""
        data = attestation.data
        assert data.target.epoch in (self.get_previous_epoch(state),
                                     self.get_current_epoch(state))
        assert data.target.epoch == self.compute_epoch_at_slot(data.slot)
        assert data.slot + self.MIN_ATTESTATION_INCLUSION_DELAY <= state.slot

        assert data.index == 0
        committee_indices = self.get_committee_indices(
            attestation.committee_bits)
        committee_offset = 0
        for committee_index in committee_indices:
            assert committee_index < self.get_committee_count_per_slot(
                state, data.target.epoch)
            committee = self.get_beacon_committee(
                state, data.slot, committee_index)
            committee_attesters = set(
                attester_index for i, attester_index in enumerate(committee)
                if attestation.aggregation_bits[committee_offset + i])
            assert len(committee_attesters) > 0
            committee_offset += len(committee)
        assert len(attestation.aggregation_bits) == committee_offset

        participation_flag_indices = \
            self.get_attestation_participation_flag_indices(
                state, data, uint64(state.slot - data.slot))

        assert self.is_valid_indexed_attestation(
            state, self.get_indexed_attestation(state, attestation))

        if data.target.epoch == self.get_current_epoch(state):
            epoch_participation = state.current_epoch_participation
        else:
            epoch_participation = state.previous_epoch_participation

        proposer_reward_numerator = 0
        for index in self.get_attesting_indices(state, attestation):
            for flag_index, weight in enumerate(
                    self.PARTICIPATION_FLAG_WEIGHTS):
                if (flag_index in participation_flag_indices
                        and not self.has_flag(epoch_participation[index],
                                              flag_index)):
                    epoch_participation[index] = self.add_flag(
                        epoch_participation[index], flag_index)
                    proposer_reward_numerator += int(
                        self.get_base_reward(state, index) * weight)

        proposer_reward_denominator = (
            (self.WEIGHT_DENOMINATOR - self.PROPOSER_WEIGHT)
            * self.WEIGHT_DENOMINATOR // self.PROPOSER_WEIGHT)
        proposer_reward = uint64(
            proposer_reward_numerator // proposer_reward_denominator)
        self.increase_balance(
            state, self.get_beacon_proposer_index(state), proposer_reward)

    def get_validator_from_deposit(self, pubkey, withdrawal_credentials,
                                   amount):
        """[Modified in Electra:EIP7251] credential-dependent cap
        (electra/beacon-chain.md:1367)."""
        validator = self.Validator(
            pubkey=pubkey,
            withdrawal_credentials=withdrawal_credentials,
            effective_balance=uint64(0),
            slashed=False,
            activation_eligibility_epoch=self.FAR_FUTURE_EPOCH,
            activation_epoch=self.FAR_FUTURE_EPOCH,
            exit_epoch=self.FAR_FUTURE_EPOCH,
            withdrawable_epoch=self.FAR_FUTURE_EPOCH)
        max_effective_balance = self.get_max_effective_balance(validator)
        validator.effective_balance = uint64(min(
            int(amount) - int(amount) % self.EFFECTIVE_BALANCE_INCREMENT,
            int(max_effective_balance)))
        return validator

    def is_valid_deposit_signature(self, pubkey, withdrawal_credentials,
                                   amount, signature) -> bool:
        deposit_message = self.DepositMessage(
            pubkey=pubkey,
            withdrawal_credentials=withdrawal_credentials,
            amount=amount)
        domain = self.compute_domain(self.DOMAIN_DEPOSIT)
        signing_root = self.compute_signing_root(deposit_message, domain)
        return self.bls_verify(pubkey, signing_root, signature)

    def apply_deposit(self, state, pubkey, withdrawal_credentials, amount,
                      signature) -> None:
        """[Modified in Electra:EIP7251] deposits are queued, not applied
        (electra/beacon-chain.md:1409)."""
        validator_pubkeys = [v.pubkey for v in state.validators]
        if pubkey not in validator_pubkeys:
            if self.is_valid_deposit_signature(
                    pubkey, withdrawal_credentials, amount, signature):
                self.add_validator_to_registry(
                    state, pubkey, withdrawal_credentials, uint64(0))
            else:
                return
        state.pending_deposits.append(self.PendingDeposit(
            pubkey=pubkey,
            withdrawal_credentials=withdrawal_credentials,
            amount=amount,
            signature=signature,
            slot=self.GENESIS_SLOT))

    def process_voluntary_exit(self, state, signed_voluntary_exit) -> None:
        voluntary_exit = signed_voluntary_exit.message
        validator = state.validators[voluntary_exit.validator_index]
        assert self.is_active_validator(validator,
                                        self.get_current_epoch(state))
        assert validator.exit_epoch == self.FAR_FUTURE_EPOCH
        assert self.get_current_epoch(state) >= voluntary_exit.epoch
        assert (self.get_current_epoch(state) >= validator.activation_epoch
                + self.config.SHARD_COMMITTEE_PERIOD)
        # [New in Electra:EIP7251] no pending withdrawals in the queue
        assert self.get_pending_balance_to_withdraw(
            state, voluntary_exit.validator_index) == 0
        domain = self.voluntary_exit_domain(state, voluntary_exit)
        signing_root = self.compute_signing_root(voluntary_exit, domain)
        assert self.bls_verify(validator.pubkey, signing_root,
                               signed_voluntary_exit.signature)
        self.initiate_validator_exit(state, voluntary_exit.validator_index)

    def process_withdrawal_request(self, state, withdrawal_request) -> None:
        """EIP-7002/EIP-7251 EL-triggered (partial) withdrawals
        (electra/beacon-chain.md:1511)."""
        amount = withdrawal_request.amount
        is_full_exit_request = amount == self.FULL_EXIT_REQUEST_AMOUNT

        if (len(state.pending_partial_withdrawals)
                == self.PENDING_PARTIAL_WITHDRAWALS_LIMIT
                and not is_full_exit_request):
            return

        validator_pubkeys = [v.pubkey for v in state.validators]
        request_pubkey = withdrawal_request.validator_pubkey
        if request_pubkey not in validator_pubkeys:
            return
        index = validator_pubkeys.index(request_pubkey)
        validator = state.validators[index]

        has_correct_credential = \
            self.has_execution_withdrawal_credential(validator)
        is_correct_source_address = (
            bytes(validator.withdrawal_credentials)[12:]
            == bytes(withdrawal_request.source_address))
        if not (has_correct_credential and is_correct_source_address):
            return
        if not self.is_active_validator(validator,
                                        self.get_current_epoch(state)):
            return
        if validator.exit_epoch != self.FAR_FUTURE_EPOCH:
            return
        if (self.get_current_epoch(state) < validator.activation_epoch
                + self.config.SHARD_COMMITTEE_PERIOD):
            return

        pending_balance_to_withdraw = \
            self.get_pending_balance_to_withdraw(state, index)

        if is_full_exit_request:
            if pending_balance_to_withdraw == 0:
                self.initiate_validator_exit(state, index)
            return

        has_sufficient_effective_balance = (
            validator.effective_balance >= self.MIN_ACTIVATION_BALANCE)
        has_excess_balance = (
            state.balances[index] > self.MIN_ACTIVATION_BALANCE
            + pending_balance_to_withdraw)

        if (self.has_compounding_withdrawal_credential(validator)
                and has_sufficient_effective_balance
                and has_excess_balance):
            to_withdraw = min(
                int(state.balances[index])
                - int(self.MIN_ACTIVATION_BALANCE)
                - int(pending_balance_to_withdraw),
                int(amount))
            exit_queue_epoch = self.compute_exit_epoch_and_update_churn(
                state, uint64(to_withdraw))
            withdrawable_epoch = uint64(
                exit_queue_epoch
                + self.config.MIN_VALIDATOR_WITHDRAWABILITY_DELAY)
            state.pending_partial_withdrawals.append(
                self.PendingPartialWithdrawal(
                    validator_index=index,
                    amount=to_withdraw,
                    withdrawable_epoch=withdrawable_epoch))

    def process_deposit_request(self, state, deposit_request) -> None:
        """EIP-6110 EL deposits (electra/beacon-chain.md:1578)."""
        if (state.deposit_requests_start_index
                == self.UNSET_DEPOSIT_REQUESTS_START_INDEX):
            state.deposit_requests_start_index = deposit_request.index
        state.pending_deposits.append(self.PendingDeposit(
            pubkey=deposit_request.pubkey,
            withdrawal_credentials=deposit_request.withdrawal_credentials,
            amount=deposit_request.amount,
            signature=deposit_request.signature,
            slot=state.slot))

    def is_valid_switch_to_compounding_request(
            self, state, consolidation_request) -> bool:
        if (consolidation_request.source_pubkey
                != consolidation_request.target_pubkey):
            return False
        source_pubkey = consolidation_request.source_pubkey
        validator_pubkeys = [v.pubkey for v in state.validators]
        if source_pubkey not in validator_pubkeys:
            return False
        source_validator = state.validators[
            validator_pubkeys.index(source_pubkey)]
        if (bytes(source_validator.withdrawal_credentials)[12:]
                != bytes(consolidation_request.source_address)):
            return False
        if not self.has_eth1_withdrawal_credential(source_validator):
            return False
        current_epoch = self.get_current_epoch(state)
        if not self.is_active_validator(source_validator, current_epoch):
            return False
        if source_validator.exit_epoch != self.FAR_FUTURE_EPOCH:
            return False
        return True

    def process_consolidation_request(
            self, state, consolidation_request) -> None:
        """EIP-7251 consolidations (electra/beacon-chain.md:1654)."""
        if self.is_valid_switch_to_compounding_request(
                state, consolidation_request):
            validator_pubkeys = [v.pubkey for v in state.validators]
            source_index = validator_pubkeys.index(
                consolidation_request.source_pubkey)
            self.switch_to_compounding_validator(state, source_index)
            return

        # a consolidation cannot double as an exit
        if (consolidation_request.source_pubkey
                == consolidation_request.target_pubkey):
            return
        if (len(state.pending_consolidations)
                == self.PENDING_CONSOLIDATIONS_LIMIT):
            return
        if (self.get_consolidation_churn_limit(state)
                <= self.MIN_ACTIVATION_BALANCE):
            return

        validator_pubkeys = [v.pubkey for v in state.validators]
        request_source_pubkey = consolidation_request.source_pubkey
        request_target_pubkey = consolidation_request.target_pubkey
        if request_source_pubkey not in validator_pubkeys:
            return
        if request_target_pubkey not in validator_pubkeys:
            return
        source_index = validator_pubkeys.index(request_source_pubkey)
        target_index = validator_pubkeys.index(request_target_pubkey)
        source_validator = state.validators[source_index]
        target_validator = state.validators[target_index]

        has_correct_credential = \
            self.has_execution_withdrawal_credential(source_validator)
        is_correct_source_address = (
            bytes(source_validator.withdrawal_credentials)[12:]
            == bytes(consolidation_request.source_address))
        if not (has_correct_credential and is_correct_source_address):
            return
        if not self.has_compounding_withdrawal_credential(target_validator):
            return

        current_epoch = self.get_current_epoch(state)
        if not self.is_active_validator(source_validator, current_epoch):
            return
        if not self.is_active_validator(target_validator, current_epoch):
            return
        if source_validator.exit_epoch != self.FAR_FUTURE_EPOCH:
            return
        if target_validator.exit_epoch != self.FAR_FUTURE_EPOCH:
            return
        if (current_epoch < source_validator.activation_epoch
                + self.config.SHARD_COMMITTEE_PERIOD):
            return
        if self.get_pending_balance_to_withdraw(state, source_index) > 0:
            return

        source_validator.exit_epoch = \
            self.compute_consolidation_epoch_and_update_churn(
                state, source_validator.effective_balance)
        source_validator.withdrawable_epoch = uint64(
            source_validator.exit_epoch
            + self.config.MIN_VALIDATOR_WITHDRAWABILITY_DELAY)
        state.pending_consolidations.append(self.PendingConsolidation(
            source_index=source_index, target_index=target_index))

    # ------------------------------------------------------------------
    # fork upgrade (electra/fork.md:77)
    # ------------------------------------------------------------------
    def genesis_fork_versions(self):
        return (Bytes4(self.config.DENEB_FORK_VERSION),
                Bytes4(self.config.ELECTRA_FORK_VERSION))

    def upgrade_from(self, pre):
        epoch = self.get_current_epoch(pre)

        earliest_exit_epoch = int(self.compute_activation_exit_epoch(epoch))
        for validator in pre.validators:
            if validator.exit_epoch != self.FAR_FUTURE_EPOCH:
                if validator.exit_epoch > earliest_exit_epoch:
                    earliest_exit_epoch = int(validator.exit_epoch)
        earliest_exit_epoch += 1

        post = self.BeaconState(
            genesis_time=pre.genesis_time,
            genesis_validators_root=pre.genesis_validators_root,
            slot=pre.slot,
            fork=self.Fork(
                previous_version=pre.fork.current_version,
                current_version=Bytes4(self.config.ELECTRA_FORK_VERSION),
                epoch=epoch),
            latest_block_header=pre.latest_block_header,
            block_roots=list(pre.block_roots),
            state_roots=list(pre.state_roots),
            historical_roots=list(pre.historical_roots),
            eth1_data=pre.eth1_data,
            eth1_data_votes=list(pre.eth1_data_votes),
            eth1_deposit_index=pre.eth1_deposit_index,
            validators=list(pre.validators),
            balances=list(pre.balances),
            randao_mixes=list(pre.randao_mixes),
            slashings=list(pre.slashings),
            previous_epoch_participation=list(
                pre.previous_epoch_participation),
            current_epoch_participation=list(
                pre.current_epoch_participation),
            justification_bits=list(pre.justification_bits),
            previous_justified_checkpoint=pre.previous_justified_checkpoint,
            current_justified_checkpoint=pre.current_justified_checkpoint,
            finalized_checkpoint=pre.finalized_checkpoint,
            inactivity_scores=list(pre.inactivity_scores),
            current_sync_committee=pre.current_sync_committee,
            next_sync_committee=pre.next_sync_committee,
            latest_execution_payload_header=(
                pre.latest_execution_payload_header),
            next_withdrawal_index=pre.next_withdrawal_index,
            next_withdrawal_validator_index=(
                pre.next_withdrawal_validator_index),
            historical_summaries=list(pre.historical_summaries),
            deposit_requests_start_index=(
                self.UNSET_DEPOSIT_REQUESTS_START_INDEX),
            deposit_balance_to_consume=0,
            exit_balance_to_consume=0,
            earliest_exit_epoch=earliest_exit_epoch,
            consolidation_balance_to_consume=0,
            earliest_consolidation_epoch=(
                self.compute_activation_exit_epoch(epoch)))

        post.exit_balance_to_consume = \
            self.get_activation_exit_churn_limit(post)
        post.consolidation_balance_to_consume = \
            self.get_consolidation_churn_limit(post)

        # add validators that are not yet active to the pending-deposit queue
        pre_activation = sorted(
            [index for index, validator in enumerate(post.validators)
             if validator.activation_epoch == self.FAR_FUTURE_EPOCH],
            key=lambda index: (
                int(post.validators[index].activation_eligibility_epoch),
                index))
        for index in pre_activation:
            balance = post.balances[index]
            post.balances[index] = uint64(0)
            validator = post.validators[index]
            validator.effective_balance = uint64(0)
            validator.activation_eligibility_epoch = self.FAR_FUTURE_EPOCH
            post.pending_deposits.append(self.PendingDeposit(
                pubkey=validator.pubkey,
                withdrawal_credentials=validator.withdrawal_credentials,
                amount=balance,
                signature=self.G2_POINT_AT_INFINITY,
                slot=self.GENESIS_SLOT))

        # early adopters of compounding credentials go through the churn
        for index, validator in enumerate(post.validators):
            if self.has_compounding_withdrawal_credential(validator):
                self.queue_excess_active_balance(post, index)

        return post
