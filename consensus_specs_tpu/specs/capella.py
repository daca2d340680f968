"""Capella spec: withdrawals, BLS-to-execution changes, historical summaries.

From-scratch implementation of /root/reference/specs/capella/beacon-chain.md
as a BellatrixSpec subclass.
"""
from ..ssz import (
    uint64, uint256, Bitvector, Vector, List, Container, ByteList,
    ByteVector, Bytes4, Bytes20, Bytes32, Bytes48, Bytes96,
    hash_tree_root,
)
from ..utils import spans
from .bellatrix import BellatrixSpec


class CapellaSpec(BellatrixSpec):
    fork = "capella"

    def _build_constants(self) -> None:
        super()._build_constants()
        self.DOMAIN_BLS_TO_EXECUTION_CHANGE = Bytes4("0x0A000000")
        self.WithdrawalIndex = uint64

    def _build_types(self) -> None:
        super()._build_types()
        p = self

        class Withdrawal(Container):
            index: uint64
            validator_index: uint64
            address: Bytes20
            amount: uint64

        class BLSToExecutionChange(Container):
            validator_index: uint64
            from_bls_pubkey: Bytes48
            to_execution_address: Bytes20

        class SignedBLSToExecutionChange(Container):
            message: BLSToExecutionChange
            signature: Bytes96

        class HistoricalSummary(Container):
            block_summary_root: Bytes32
            state_summary_root: Bytes32

        class ExecutionPayload(Container):
            parent_hash: Bytes32
            fee_recipient: Bytes20
            state_root: Bytes32
            receipts_root: Bytes32
            logs_bloom: ByteVector[p.BYTES_PER_LOGS_BLOOM]
            prev_randao: Bytes32
            block_number: uint64
            gas_limit: uint64
            gas_used: uint64
            timestamp: uint64
            extra_data: ByteList[p.MAX_EXTRA_DATA_BYTES]
            base_fee_per_gas: uint256
            block_hash: Bytes32
            transactions: List[p.Transaction, p.MAX_TRANSACTIONS_PER_PAYLOAD]
            withdrawals: List[Withdrawal, p.MAX_WITHDRAWALS_PER_PAYLOAD]

        class ExecutionPayloadHeader(Container):
            parent_hash: Bytes32
            fee_recipient: Bytes20
            state_root: Bytes32
            receipts_root: Bytes32
            logs_bloom: ByteVector[p.BYTES_PER_LOGS_BLOOM]
            prev_randao: Bytes32
            block_number: uint64
            gas_limit: uint64
            gas_used: uint64
            timestamp: uint64
            extra_data: ByteList[p.MAX_EXTRA_DATA_BYTES]
            base_fee_per_gas: uint256
            block_hash: Bytes32
            transactions_root: Bytes32
            withdrawals_root: Bytes32

        class BeaconBlockBody(Container):
            randao_reveal: Bytes96
            eth1_data: p.Eth1Data
            graffiti: Bytes32
            proposer_slashings: List[p.ProposerSlashing, p.MAX_PROPOSER_SLASHINGS]
            attester_slashings: List[p.AttesterSlashing, p.MAX_ATTESTER_SLASHINGS]
            attestations: List[p.Attestation, p.MAX_ATTESTATIONS]
            deposits: List[p.Deposit, p.MAX_DEPOSITS]
            voluntary_exits: List[p.SignedVoluntaryExit, p.MAX_VOLUNTARY_EXITS]
            sync_aggregate: p.SyncAggregate
            execution_payload: ExecutionPayload
            bls_to_execution_changes: List[SignedBLSToExecutionChange, p.MAX_BLS_TO_EXECUTION_CHANGES]

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
            latest_execution_payload_header: ExecutionPayloadHeader
            next_withdrawal_index: uint64
            next_withdrawal_validator_index: uint64
            historical_summaries: List[HistoricalSummary, p.HISTORICAL_ROOTS_LIMIT]

        for name, cls in list(locals().items()):
            if isinstance(cls, type) and issubclass(cls, Container):
                setattr(self, name, cls)

    # ------------------------------------------------------------------
    # withdrawal predicates & sweep
    # ------------------------------------------------------------------
    def has_eth1_withdrawal_credential(self, validator) -> bool:
        return bytes(validator.withdrawal_credentials)[:1] \
            == self.ETH1_ADDRESS_WITHDRAWAL_PREFIX

    def is_fully_withdrawable_validator(self, validator, balance,
                                        epoch) -> bool:
        return (self.has_eth1_withdrawal_credential(validator)
                and validator.withdrawable_epoch <= epoch
                and balance > 0)

    def is_partially_withdrawable_validator(self, validator,
                                            balance) -> bool:
        has_max_effective_balance = (
            validator.effective_balance == self.MAX_EFFECTIVE_BALANCE)
        has_excess_balance = balance > self.MAX_EFFECTIVE_BALANCE
        return (self.has_eth1_withdrawal_credential(validator)
                and has_max_effective_balance and has_excess_balance)

    def get_expected_withdrawals(self, state):
        epoch = self.get_current_epoch(state)
        withdrawal_index = int(state.next_withdrawal_index)
        validator_index = int(state.next_withdrawal_validator_index)
        withdrawals = []
        bound = min(len(state.validators),
                    self.MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP)
        for _ in range(bound):
            validator = state.validators[validator_index]
            balance = state.balances[validator_index]
            address = Bytes20(
                bytes(validator.withdrawal_credentials)[12:])
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
                    amount=uint64(balance - self.MAX_EFFECTIVE_BALANCE)))
                withdrawal_index += 1
            if len(withdrawals) == self.MAX_WITHDRAWALS_PER_PAYLOAD:
                break
            validator_index = (validator_index + 1) % len(state.validators)
        return withdrawals

    def process_withdrawals(self, state, payload) -> None:
        expected_withdrawals = self.get_expected_withdrawals(state)
        assert len(payload.withdrawals) == len(expected_withdrawals)
        for expected, actual in zip(expected_withdrawals,
                                    payload.withdrawals):
            assert actual == expected
        for withdrawal in expected_withdrawals:
            self.decrease_balance(state, withdrawal.validator_index,
                                  withdrawal.amount)

        # advance the sweep cursors
        if len(expected_withdrawals) > 0:
            state.next_withdrawal_index = uint64(
                expected_withdrawals[-1].index + 1)
        if len(expected_withdrawals) == self.MAX_WITHDRAWALS_PER_PAYLOAD:
            # full payload: resume right after the last withdrawn validator
            next_validator_index = uint64(
                (expected_withdrawals[-1].validator_index + 1)
                % len(state.validators))
        else:
            # swept the bound without filling the payload
            next_index = (int(state.next_withdrawal_validator_index)
                          + self.MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP)
            next_validator_index = uint64(
                next_index % len(state.validators))
        state.next_withdrawal_validator_index = next_validator_index

    # ------------------------------------------------------------------
    # block processing
    # ------------------------------------------------------------------
    def process_block(self, state, block) -> None:
        self.process_block_header(state, block)
        # [Modified in Capella] no is_execution_enabled gate: withdrawals
        # and payload processing are unconditional post-capella
        self.process_withdrawals(state, block.body.execution_payload)
        self.process_execution_payload(
            state, block.body, self.EXECUTION_ENGINE)
        self.process_randao(state, block.body)
        self.process_eth1_data(state, block.body)
        self.process_operations(state, block.body)
        self.process_sync_aggregate(state, block.body.sync_aggregate)

    def process_execution_payload(self, state, body,
                                  execution_engine) -> None:
        payload = body.execution_payload
        # [Modified in Capella] parent-hash check is unconditional
        assert payload.parent_hash == \
            state.latest_execution_payload_header.block_hash
        assert payload.prev_randao == self.get_randao_mix(
            state, self.get_current_epoch(state))
        assert payload.timestamp == self.compute_timestamp_at_slot(
            state, state.slot)
        assert execution_engine.verify_and_notify_new_payload(payload)
        state.latest_execution_payload_header = \
            self.build_execution_payload_header(payload)

    def process_operations(self, state, body) -> None:
        super().process_operations(state, body)
        for operation in body.bls_to_execution_changes:
            self.process_bls_to_execution_change(state, operation)

    def process_bls_to_execution_change(self, state,
                                        signed_address_change) -> None:
        address_change = signed_address_change.message
        assert address_change.validator_index < len(state.validators)
        validator = state.validators[address_change.validator_index]
        assert bytes(validator.withdrawal_credentials)[:1] \
            == self.BLS_WITHDRAWAL_PREFIX
        assert bytes(validator.withdrawal_credentials)[1:] \
            == bytes(self.hash(address_change.from_bls_pubkey))[1:]
        # signed against the genesis domain so changes survive forks
        domain = self.compute_domain(
            self.DOMAIN_BLS_TO_EXECUTION_CHANGE,
            genesis_validators_root=state.genesis_validators_root)
        signing_root = self.compute_signing_root(address_change, domain)
        assert self.bls_verify(address_change.from_bls_pubkey, signing_root,
                               signed_address_change.signature)
        validator.withdrawal_credentials = (
            self.ETH1_ADDRESS_WITHDRAWAL_PREFIX + b"\x00" * 11
            + bytes(address_change.to_execution_address))

    def build_execution_payload_header(self, payload):
        header = super().build_execution_payload_header(payload)
        header.withdrawals_root = hash_tree_root(payload.withdrawals)
        return header

    # ------------------------------------------------------------------
    # epoch processing: historical summaries replace historical roots
    # ------------------------------------------------------------------
    def process_epoch(self, state) -> None:
        from . import epoch_fast
        if epoch_fast.fused_epoch(self, state):
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
        self.process_effective_balance_updates(state)
        self.process_slashings_reset(state)
        self.process_randao_mixes_reset(state)
        self.process_historical_summaries_update(state)
        self.process_participation_flag_updates(state)
        self.process_sync_committee_updates(state)

    def process_historical_summaries_update(self, state) -> None:
        next_epoch = uint64(self.get_current_epoch(state) + 1)
        if next_epoch % (self.SLOTS_PER_HISTORICAL_ROOT
                         // self.SLOTS_PER_EPOCH) == 0:
            historical_summary = self.HistoricalSummary(
                block_summary_root=hash_tree_root(state.block_roots),
                state_summary_root=hash_tree_root(state.state_roots))
            state.historical_summaries.append(historical_summary)

    # ------------------------------------------------------------------
    # fork upgrade (capella/fork.md)
    # ------------------------------------------------------------------
    def genesis_fork_versions(self):
        return (Bytes4(self.config.BELLATRIX_FORK_VERSION),
                Bytes4(self.config.CAPELLA_FORK_VERSION))

    def upgrade_from(self, pre):
        epoch = self.get_current_epoch(pre)
        pre_header = pre.latest_execution_payload_header
        post_header = self.ExecutionPayloadHeader(
            parent_hash=pre_header.parent_hash,
            fee_recipient=pre_header.fee_recipient,
            state_root=pre_header.state_root,
            receipts_root=pre_header.receipts_root,
            logs_bloom=pre_header.logs_bloom,
            prev_randao=pre_header.prev_randao,
            block_number=pre_header.block_number,
            gas_limit=pre_header.gas_limit,
            gas_used=pre_header.gas_used,
            timestamp=pre_header.timestamp,
            extra_data=pre_header.extra_data,
            base_fee_per_gas=pre_header.base_fee_per_gas,
            block_hash=pre_header.block_hash,
            transactions_root=pre_header.transactions_root,
            # withdrawals_root stays zeroed
        )
        post = self.BeaconState(
            genesis_time=pre.genesis_time,
            genesis_validators_root=pre.genesis_validators_root,
            slot=pre.slot,
            fork=self.Fork(
                previous_version=pre.fork.current_version,
                current_version=Bytes4(self.config.CAPELLA_FORK_VERSION),
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
            latest_execution_payload_header=post_header,
            next_withdrawal_index=0,
            next_withdrawal_validator_index=0,
            # historical_summaries starts empty
        )
        return post
