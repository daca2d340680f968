"""process_withdrawals operation tests (capella+; reference:
test/capella/block_processing/test_process_withdrawals.py shape)."""
from ...ssz import uint64
from ...test_infra.context import spec_state_test, with_all_phases_from
from ...test_infra.withdrawals import (
    get_expected_withdrawals, payload_with_expected_withdrawals,
    prepare_fully_withdrawable_validator,
    prepare_partially_withdrawable_validator, run_withdrawals_processing)


@with_all_phases_from("capella")
@spec_state_test
def test_no_withdrawals(spec, state):
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 0
    yield from run_withdrawals_processing(spec, state, payload)


@with_all_phases_from("capella")
@spec_state_test
def test_one_full_withdrawal(spec, state):
    prepare_fully_withdrawable_validator(spec, state, 0)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 1
    assert payload.withdrawals[0].amount == state.balances[0]
    yield from run_withdrawals_processing(spec, state, payload)
    assert state.balances[0] == 0
    assert state.next_withdrawal_index == uint64(1)


@with_all_phases_from("capella")
@spec_state_test
def test_one_partial_withdrawal(spec, state):
    excess = 2000000000
    prepare_partially_withdrawable_validator(spec, state, 1,
                                             excess=excess)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 1
    assert int(payload.withdrawals[0].amount) == excess
    yield from run_withdrawals_processing(spec, state, payload)
    assert state.balances[1] == spec.MAX_EFFECTIVE_BALANCE


@with_all_phases_from("capella")
@spec_state_test
def test_mixed_full_and_partial(spec, state):
    prepare_fully_withdrawable_validator(spec, state, 0)
    prepare_partially_withdrawable_validator(spec, state, 2)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 2
    yield from run_withdrawals_processing(spec, state, payload)


@with_all_phases_from("capella")
@spec_state_test
def test_invalid_missing_withdrawal(spec, state):
    prepare_fully_withdrawable_validator(spec, state, 0)
    payload = payload_with_expected_withdrawals(spec, state)
    payload.withdrawals = []
    yield from run_withdrawals_processing(spec, state, payload,
                                          valid=False)


@with_all_phases_from("capella")
@spec_state_test
def test_invalid_wrong_amount(spec, state):
    prepare_fully_withdrawable_validator(spec, state, 0)
    payload = payload_with_expected_withdrawals(spec, state)
    payload.withdrawals[0].amount = uint64(
        int(payload.withdrawals[0].amount) + 1)
    yield from run_withdrawals_processing(spec, state, payload,
                                          valid=False)


@with_all_phases_from("capella")
@spec_state_test
def test_invalid_wrong_validator_index(spec, state):
    prepare_fully_withdrawable_validator(spec, state, 0)
    payload = payload_with_expected_withdrawals(spec, state)
    payload.withdrawals[0].validator_index = uint64(3)
    yield from run_withdrawals_processing(spec, state, payload,
                                          valid=False)


@with_all_phases_from("capella")
@spec_state_test
def test_sweep_cursor_advances(spec, state):
    """The sweep cursor moves by the bound when the payload isn't
    full."""
    pre_cursor = int(state.next_withdrawal_validator_index)
    payload = payload_with_expected_withdrawals(spec, state)
    yield from run_withdrawals_processing(spec, state, payload)
    bound = min(len(state.validators),
                int(spec.MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP))
    assert int(state.next_withdrawal_validator_index) == \
        (pre_cursor + bound) % len(state.validators)


@with_all_phases_from("electra")
@spec_state_test
def test_pending_partial_withdrawal(spec, state):
    """Electra: a pending partial withdrawal request is honored by the
    sweep once withdrawable."""
    from ...test_infra.withdrawals import set_eth1_withdrawal_credentials
    index = 0
    amount = spec.EFFECTIVE_BALANCE_INCREMENT
    set_eth1_withdrawal_credentials(spec, state, index)
    state.balances[index] = uint64(
        int(spec.MAX_EFFECTIVE_BALANCE) + int(amount))
    state.pending_partial_withdrawals = [spec.PendingPartialWithdrawal(
        validator_index=index, amount=amount,
        withdrawable_epoch=spec.get_current_epoch(state))]
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) >= 1
    yield from run_withdrawals_processing(spec, state, payload)
    assert len(state.pending_partial_withdrawals) == 0


from ...test_infra.withdrawals import (  # noqa: E402
    set_eth1_withdrawal_credentials)


@with_all_phases_from("capella")
@spec_state_test
def test_all_fully_withdrawable_in_sweep_window(spec, state):
    """Every validator in the sweep window fully withdrawable: payload
    carries the per-payload cap."""
    bound = min(int(spec.MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP),
                len(state.validators),
                int(spec.MAX_WITHDRAWALS_PER_PAYLOAD) + 4)
    for i in range(bound):
        prepare_fully_withdrawable_validator(spec, state, i)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == min(
        bound, int(spec.MAX_WITHDRAWALS_PER_PAYLOAD))
    yield from run_withdrawals_processing(spec, state, payload)


@with_all_phases_from("capella")
@spec_state_test
def test_max_partial_withdrawals_in_one_payload(spec, state):
    cap = int(spec.MAX_WITHDRAWALS_PER_PAYLOAD)
    for i in range(cap + 2):
        prepare_partially_withdrawable_validator(
            spec, state, i % len(state.validators), excess=10**6)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == cap
    yield from run_withdrawals_processing(spec, state, payload)


@with_all_phases_from("capella")
@spec_state_test
def test_withdrawable_epoch_but_0_balance(spec, state):
    """Fully withdrawable with zero balance: skipped by the sweep."""
    prepare_fully_withdrawable_validator(spec, state, 0, balance=0)
    payload = payload_with_expected_withdrawals(spec, state)
    assert all(int(w.validator_index) != 0
               for w in payload.withdrawals)
    yield from run_withdrawals_processing(spec, state, payload)


@with_all_phases_from("capella")
@spec_state_test
def test_withdrawable_epoch_but_0_effective_balance_not_0_balance(
        spec, state):
    """Zero EFFECTIVE balance with real balance: fully withdrawable
    (the sweep keys on withdrawable_epoch + balance)."""
    index = 0
    prepare_fully_withdrawable_validator(spec, state, index)
    state.validators[index].effective_balance = uint64(0)
    payload = payload_with_expected_withdrawals(spec, state)
    assert any(int(w.validator_index) == index
               for w in payload.withdrawals)
    yield from run_withdrawals_processing(spec, state, payload)


@with_all_phases_from("capella")
@spec_state_test
def test_partially_withdrawable_exact_max_balance(spec, state):
    """Balance exactly AT the max effective balance: NOT partially
    withdrawable (strict inequality)."""
    index = 0
    set_eth1_withdrawal_credentials(spec, state, index)
    state.validators[index].effective_balance = \
        spec.MAX_EFFECTIVE_BALANCE
    state.balances[index] = spec.MAX_EFFECTIVE_BALANCE
    payload = payload_with_expected_withdrawals(spec, state)
    assert all(int(w.validator_index) != index
               for w in payload.withdrawals)
    yield from run_withdrawals_processing(spec, state, payload)


@with_all_phases_from("capella")
@spec_state_test
def test_bls_credentials_not_withdrawable(spec, state):
    """0x00-credentialed validators never enter the sweep, however
    ripe."""
    index = 0
    v = state.validators[index]
    epoch = spec.get_current_epoch(state)
    v.exit_epoch = uint64(max(int(epoch) - 1, 0))
    v.withdrawable_epoch = epoch
    assert bytes(v.withdrawal_credentials)[:1] == \
        bytes(spec.BLS_WITHDRAWAL_PREFIX)
    payload = payload_with_expected_withdrawals(spec, state)
    assert all(int(w.validator_index) != index
               for w in payload.withdrawals)
    yield from run_withdrawals_processing(spec, state, payload)


@with_all_phases_from("capella")
@spec_state_test
def test_invalid_withdrawal_index_gap(spec, state):
    prepare_fully_withdrawable_validator(spec, state, 0)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) > 0
    payload.withdrawals[0].index = uint64(
        int(payload.withdrawals[0].index) + 1)
    yield from run_withdrawals_processing(spec, state, payload,
                                          valid=False)


@with_all_phases_from("capella")
@spec_state_test
def test_invalid_address_mismatch(spec, state):
    prepare_fully_withdrawable_validator(spec, state, 0)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) > 0
    payload.withdrawals[0].address = b"\xde" * 20
    yield from run_withdrawals_processing(spec, state, payload,
                                          valid=False)


@with_all_phases_from("capella")
@spec_state_test
def test_invalid_empty_when_expected(spec, state):
    prepare_fully_withdrawable_validator(spec, state, 0)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) > 0
    payload.withdrawals = []
    yield from run_withdrawals_processing(spec, state, payload,
                                          valid=False)


@with_all_phases_from("electra")
@spec_state_test
def test_electra_pending_partial_before_sweep(spec, state):
    """EIP-7251 pending partial withdrawals drain before the sweep and
    consume the per-payload partial budget."""
    from ...test_infra.withdrawals import (
        set_compounding_withdrawal_credentials)
    index = 0
    set_compounding_withdrawal_credentials(spec, state, index)
    state.validators[index].effective_balance = \
        spec.MIN_ACTIVATION_BALANCE
    state.balances[index] = uint64(
        int(spec.MIN_ACTIVATION_BALANCE) + 3 * 10**9)
    state.pending_partial_withdrawals.append(
        spec.PendingPartialWithdrawal(
            validator_index=uint64(index), amount=uint64(10**9),
            withdrawable_epoch=spec.get_current_epoch(state)))
    payload = payload_with_expected_withdrawals(spec, state)
    assert any(int(w.validator_index) == index
               for w in payload.withdrawals)
    yield from run_withdrawals_processing(spec, state, payload)


@with_all_phases_from("electra")
@spec_state_test
def test_electra_pending_partial_not_ripe(spec, state):
    """A pending partial whose withdrawable_epoch is in the future
    stays queued."""
    from ...test_infra.withdrawals import (
        set_compounding_withdrawal_credentials)
    index = 0
    set_compounding_withdrawal_credentials(spec, state, index)
    state.validators[index].effective_balance = \
        spec.MIN_ACTIVATION_BALANCE
    state.balances[index] = uint64(
        int(spec.MIN_ACTIVATION_BALANCE) + 3 * 10**9)
    state.pending_partial_withdrawals.append(
        spec.PendingPartialWithdrawal(
            validator_index=uint64(index), amount=uint64(10**9),
            withdrawable_epoch=uint64(
                int(spec.get_current_epoch(state)) + 4)))
    payload = payload_with_expected_withdrawals(spec, state)
    assert all(int(w.validator_index) != index
               for w in payload.withdrawals)
    yield from run_withdrawals_processing(spec, state, payload)
    assert len(state.pending_partial_withdrawals) == 1


# ---------------------------------------------------------------------------
# success-shape long tail (reference test_process_withdrawals.py)
# ---------------------------------------------------------------------------

@with_all_phases_from("capella")
@spec_state_test
def test_success_zero_expected_withdrawals(spec, state):
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 0
    yield from run_withdrawals_processing(spec, state, payload)
    assert int(state.next_withdrawal_index) == 0


@with_all_phases_from("capella")
@spec_state_test
def test_success_mixed_fully_and_partial_withdrawable(spec, state):
    n = len(state.validators)
    fully = [0, 3]
    partial = [1, 4]
    for i in fully:
        prepare_fully_withdrawable_validator(spec, state, i)
    for i in partial:
        prepare_partially_withdrawable_validator(spec, state, i)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == len(fully) + len(partial)
    yield from run_withdrawals_processing(spec, state, payload)
    for i in fully:
        assert int(state.balances[i]) == 0
    for i in partial:
        assert int(state.balances[i]) == int(spec.MAX_EFFECTIVE_BALANCE)
    assert n == len(state.validators)  # sweep never mutates the registry


@with_all_phases_from("capella")
@spec_state_test
def test_success_all_fully_withdrawable(spec, state):
    """Every validator fully withdrawable: the payload carries exactly
    the per-payload bound, drained in registry order."""
    for i in range(len(state.validators)):
        prepare_fully_withdrawable_validator(spec, state, i)
    payload = payload_with_expected_withdrawals(spec, state)
    bound = min(len(state.validators),
                int(spec.MAX_WITHDRAWALS_PER_PAYLOAD))
    assert len(payload.withdrawals) == bound
    yield from run_withdrawals_processing(spec, state, payload)
    for w in payload.withdrawals:
        assert int(state.balances[int(w.validator_index)]) == 0


@with_all_phases_from("capella")
@spec_state_test
def test_success_all_partially_withdrawable(spec, state):
    for i in range(len(state.validators)):
        prepare_partially_withdrawable_validator(spec, state, i)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == min(
        len(state.validators), int(spec.MAX_WITHDRAWALS_PER_PAYLOAD))
    yield from run_withdrawals_processing(spec, state, payload)
    for w in payload.withdrawals:
        assert int(state.balances[int(w.validator_index)]) \
            == int(spec.MAX_EFFECTIVE_BALANCE)


@with_all_phases_from("capella")
@spec_state_test
def test_success_max_per_slot_withdrawals(spec, state):
    """More fully-withdrawable validators than the per-payload bound:
    exactly MAX_WITHDRAWALS_PER_PAYLOAD are emitted."""
    bound = int(spec.MAX_WITHDRAWALS_PER_PAYLOAD)
    for i in range(min(bound + 2, len(state.validators))):
        prepare_fully_withdrawable_validator(spec, state, i)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == min(
        bound, len(state.validators))
    yield from run_withdrawals_processing(spec, state, payload)


# ---------------------------------------------------------------------------
# invalid-payload long tail
# ---------------------------------------------------------------------------

@with_all_phases_from("capella")
@spec_state_test
def test_invalid_non_withdrawable_non_empty_withdrawals(spec, state):
    """No one is withdrawable but the payload claims one is."""
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 0
    payload.withdrawals = [spec.Withdrawal(
        index=0, validator_index=0, address=b"\xaa" * 20,
        amount=420)]
    yield from run_withdrawals_processing(spec, state, payload,
                                          valid=False)


@with_all_phases_from("capella")
@spec_state_test
def test_invalid_one_expected_full_withdrawal_and_duplicate_in_withdrawals(
        spec, state):
    prepare_fully_withdrawable_validator(spec, state, 0)
    payload = payload_with_expected_withdrawals(spec, state)
    payload.withdrawals = list(payload.withdrawals) \
        + [payload.withdrawals[0].copy()]
    yield from run_withdrawals_processing(spec, state, payload,
                                          valid=False)


@with_all_phases_from("capella")
@spec_state_test
def test_invalid_extra_withdrawal(spec, state):
    prepare_fully_withdrawable_validator(spec, state, 0)
    payload = payload_with_expected_withdrawals(spec, state)
    extra = payload.withdrawals[0].copy()
    extra.index = uint64(int(extra.index) + 1)
    extra.validator_index = uint64(1)
    payload.withdrawals = list(payload.withdrawals) + [extra]
    yield from run_withdrawals_processing(spec, state, payload,
                                          valid=False)


@with_all_phases_from("capella")
@spec_state_test
def test_invalid_incorrect_withdrawal_index(spec, state):
    prepare_fully_withdrawable_validator(spec, state, 0)
    payload = payload_with_expected_withdrawals(spec, state)
    payload.withdrawals[0].index = uint64(
        int(payload.withdrawals[0].index) + 1)
    yield from run_withdrawals_processing(spec, state, payload,
                                          valid=False)


@with_all_phases_from("capella")
@spec_state_test
def test_invalid_incorrect_address_full(spec, state):
    prepare_fully_withdrawable_validator(spec, state, 0)
    payload = payload_with_expected_withdrawals(spec, state)
    payload.withdrawals[0].address = b"\xff" * 20
    yield from run_withdrawals_processing(spec, state, payload,
                                          valid=False)


@with_all_phases_from("capella")
@spec_state_test
def test_invalid_incorrect_address_partial(spec, state):
    prepare_partially_withdrawable_validator(spec, state, 1)
    payload = payload_with_expected_withdrawals(spec, state)
    payload.withdrawals[0].address = b"\xff" * 20
    yield from run_withdrawals_processing(spec, state, payload,
                                          valid=False)


@with_all_phases_from("capella")
@spec_state_test
def test_invalid_incorrect_amount_partial(spec, state):
    prepare_partially_withdrawable_validator(spec, state, 1)
    payload = payload_with_expected_withdrawals(spec, state)
    payload.withdrawals[0].amount = uint64(
        int(payload.withdrawals[0].amount) + 1)
    yield from run_withdrawals_processing(spec, state, payload,
                                          valid=False)


@with_all_phases_from("capella")
@spec_state_test
def test_invalid_one_of_many_incorrectly_full(spec, state):
    for i in range(3):
        prepare_fully_withdrawable_validator(spec, state, i)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 3
    # corrupt the middle one
    payload.withdrawals[1].amount = uint64(0)
    yield from run_withdrawals_processing(spec, state, payload,
                                          valid=False)


@with_all_phases_from("capella")
@spec_state_test
def test_invalid_one_of_many_incorrectly_partial(spec, state):
    for i in range(3):
        prepare_partially_withdrawable_validator(spec, state, i)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 3
    payload.withdrawals[1].validator_index = uint64(
        int(payload.withdrawals[1].validator_index) + 10)
    yield from run_withdrawals_processing(spec, state, payload,
                                          valid=False)


@with_all_phases_from("capella")
@spec_state_test
def test_invalid_max_per_slot_full_withdrawals_and_one_less_in_withdrawals(
        spec, state):
    bound = int(spec.MAX_WITHDRAWALS_PER_PAYLOAD)
    for i in range(min(bound + 2, len(state.validators))):
        prepare_fully_withdrawable_validator(spec, state, i)
    payload = payload_with_expected_withdrawals(spec, state)
    payload.withdrawals = list(payload.withdrawals)[:-1]
    yield from run_withdrawals_processing(spec, state, payload,
                                          valid=False)


# ---------------------------------------------------------------------------
# withdrawability edge states
# ---------------------------------------------------------------------------

@with_all_phases_from("capella")
@spec_state_test
def test_withdrawable_epoch_but_0_effective_balance_0_balance(spec, state):
    prepare_fully_withdrawable_validator(spec, state, 0, balance=0)
    state.validators[0].effective_balance = 0
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 0
    yield from run_withdrawals_processing(spec, state, payload)


@with_all_phases_from("capella")
@spec_state_test
def test_withdrawable_epoch_but_0_effective_balance_nonzero_balance(
        spec, state):
    prepare_fully_withdrawable_validator(spec, state, 0,
                                         balance=100000000)
    state.validators[0].effective_balance = 0
    payload = payload_with_expected_withdrawals(spec, state)
    # a full withdrawal drains the actual balance regardless of EB
    assert len(payload.withdrawals) == 1
    yield from run_withdrawals_processing(spec, state, payload)
    assert int(state.balances[0]) == 0


@with_all_phases_from("capella")
@spec_state_test
def test_no_withdrawals_but_some_next_epoch(spec, state):
    """Validators become withdrawable next epoch: nothing this slot."""
    epoch = spec.get_current_epoch(state)
    for i in range(3):
        set_eth1_withdrawal_credentials(spec, state, i)
        state.validators[i].exit_epoch = epoch
        state.validators[i].withdrawable_epoch = uint64(int(epoch) + 1)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 0
    yield from run_withdrawals_processing(spec, state, payload)


@with_all_phases_from("capella")
@spec_state_test
def test_success_no_excess_balance(spec, state):
    """Exactly max effective balance: not partially withdrawable."""
    set_eth1_withdrawal_credentials(spec, state, 1)
    state.validators[1].effective_balance = spec.MAX_EFFECTIVE_BALANCE
    state.balances[1] = spec.MAX_EFFECTIVE_BALANCE
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 0
    yield from run_withdrawals_processing(spec, state, payload)


@with_all_phases_from("capella")
@spec_state_test
def test_success_excess_balance_but_no_max_effective_balance(spec, state):
    """Excess balance over a sub-max effective balance: not partially
    withdrawable."""
    set_eth1_withdrawal_credentials(spec, state, 1)
    state.validators[1].effective_balance = uint64(
        int(spec.MAX_EFFECTIVE_BALANCE)
        - int(spec.EFFECTIVE_BALANCE_INCREMENT))
    state.balances[1] = uint64(int(spec.MAX_EFFECTIVE_BALANCE) + 1)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 0
    yield from run_withdrawals_processing(spec, state, payload)


@with_all_phases_from("capella")
@spec_state_test
def test_success_one_partial_withdrawable_not_yet_active(spec, state):
    """Activation status doesn't gate partial withdrawability."""
    prepare_partially_withdrawable_validator(spec, state, 1)
    state.validators[1].activation_epoch = uint64(
        int(spec.get_current_epoch(state)) + 4)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 1
    yield from run_withdrawals_processing(spec, state, payload)


@with_all_phases_from("capella")
@spec_state_test
def test_success_one_partial_withdrawable_in_exit_queue(spec, state):
    prepare_partially_withdrawable_validator(spec, state, 1)
    state.validators[1].exit_epoch = uint64(
        int(spec.get_current_epoch(state)) + 1)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 1
    yield from run_withdrawals_processing(spec, state, payload)


@with_all_phases_from("capella")
@spec_state_test
def test_success_one_partial_withdrawable_exited(spec, state):
    prepare_partially_withdrawable_validator(spec, state, 1)
    state.validators[1].exit_epoch = spec.get_current_epoch(state)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 1
    yield from run_withdrawals_processing(spec, state, payload)


@with_all_phases_from("capella")
@spec_state_test
def test_success_one_partial_withdrawable_active_and_slashed(spec, state):
    prepare_partially_withdrawable_validator(spec, state, 1)
    state.validators[1].slashed = True
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 1
    yield from run_withdrawals_processing(spec, state, payload)


@with_all_phases_from("capella")
@spec_state_test
def test_success_two_partial_withdrawable(spec, state):
    prepare_partially_withdrawable_validator(spec, state, 0)
    prepare_partially_withdrawable_validator(spec, state, 1)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 2
    yield from run_withdrawals_processing(spec, state, payload)


# ---------------------------------------------------------------------------
# randomized sweeps (reference test_random_full/partial_withdrawals_N)
# ---------------------------------------------------------------------------

def _run_random_withdrawals(spec, state, rng):
    for i in range(len(state.validators)):
        roll = rng.random()
        if roll < 0.25:
            prepare_fully_withdrawable_validator(spec, state, i)
        elif roll < 0.5:
            prepare_partially_withdrawable_validator(
                spec, state, i, excess=rng.randrange(1, 10**9))
    payload = payload_with_expected_withdrawals(spec, state)
    yield from run_withdrawals_processing(spec, state, payload)


@with_all_phases_from("capella")
@spec_state_test
def test_random_withdrawals_0(spec, state):
    import random
    yield from _run_random_withdrawals(spec, state, random.Random(444))


@with_all_phases_from("capella")
@spec_state_test
def test_random_withdrawals_1(spec, state):
    import random
    yield from _run_random_withdrawals(spec, state, random.Random(420))


@with_all_phases_from("capella")
@spec_state_test
def test_random_withdrawals_2(spec, state):
    import random
    yield from _run_random_withdrawals(spec, state, random.Random(200))


@with_all_phases_from("capella")
@spec_state_test
def test_random_withdrawals_3(spec, state):
    import random
    yield from _run_random_withdrawals(spec, state, random.Random(2000000))


# ---------------------------------------------------------------------------
# electra pending partial withdrawals (reference electra
# test_process_withdrawals.py pending_withdrawals battery)
# ---------------------------------------------------------------------------

from ...test_infra.withdrawals import prepare_pending_withdrawal  # noqa: E402


@with_all_phases_from("electra")
@spec_state_test
def test_pending_withdrawals_one_skipped_one_effective(spec, state):
    index_0, index_1 = 3, 5
    pending_0 = prepare_pending_withdrawal(spec, state, index_0)
    pending_1 = prepare_pending_withdrawal(spec, state, index_1)
    # validator 0 loses its excess: its request is skipped
    state.balances[index_0] = spec.MIN_ACTIVATION_BALANCE
    assert list(state.pending_partial_withdrawals) \
        == [pending_0, pending_1]
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 1
    assert int(payload.withdrawals[0].validator_index) == index_1
    yield from run_withdrawals_processing(spec, state, payload)
    assert len(state.pending_partial_withdrawals) == 0


@with_all_phases_from("electra")
@spec_state_test
def test_pending_withdrawals_next_epoch(spec, state):
    index = len(state.validators) // 2
    pending = prepare_pending_withdrawal(
        spec, state, index,
        withdrawable_epoch=uint64(int(spec.get_current_epoch(state)) + 1))
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 0
    yield from run_withdrawals_processing(spec, state, payload)
    # not ripe yet: stays queued
    assert list(state.pending_partial_withdrawals) == [pending]


@with_all_phases_from("electra")
@spec_state_test
def test_pending_withdrawals_at_max(spec, state):
    bound = int(spec.MAX_PENDING_PARTIALS_PER_WITHDRAWALS_SWEEP)
    requests = [prepare_pending_withdrawal(spec, state, i)
                for i in range(bound + 1)]
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == bound
    yield from run_withdrawals_processing(spec, state, payload)
    # the overflow request survives the sweep
    assert list(state.pending_partial_withdrawals) == requests[bound:]


@with_all_phases_from("electra")
@spec_state_test
def test_pending_withdrawals_exiting_validator(spec, state):
    index = len(state.validators) // 2
    pending = prepare_pending_withdrawal(spec, state, index)
    spec.initiate_validator_exit(state, pending.validator_index)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 0
    yield from run_withdrawals_processing(spec, state, payload)
    # consumed without effect
    assert len(state.pending_partial_withdrawals) == 0


@with_all_phases_from("electra")
@spec_state_test
def test_pending_withdrawals_low_effective_balance(spec, state):
    index = len(state.validators) // 2
    pending = prepare_pending_withdrawal(spec, state, index)
    state.validators[int(pending.validator_index)].effective_balance = \
        uint64(int(spec.MIN_ACTIVATION_BALANCE)
               - int(spec.EFFECTIVE_BALANCE_INCREMENT))
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 0
    yield from run_withdrawals_processing(spec, state, payload)
    assert len(state.pending_partial_withdrawals) == 0


@with_all_phases_from("electra")
@spec_state_test
def test_pending_withdrawals_no_excess_balance(spec, state):
    index = len(state.validators) // 2
    pending = prepare_pending_withdrawal(spec, state, index)
    state.balances[int(pending.validator_index)] = \
        spec.MIN_ACTIVATION_BALANCE
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 0
    yield from run_withdrawals_processing(spec, state, payload)
    assert len(state.pending_partial_withdrawals) == 0


@with_all_phases_from("electra")
@spec_state_test
def test_pending_withdrawals_with_ineffective_sweep_on_top(spec, state):
    """The pending withdrawal drains the excess, so the sweep on top of
    it finds nothing partially withdrawable."""
    index = min(len(state.validators),
                int(spec.MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP)) // 2
    prepare_pending_withdrawal(
        spec, state, index,
        effective_balance=spec.MAX_EFFECTIVE_BALANCE_ELECTRA)
    assert spec.is_partially_withdrawable_validator(
        state.validators[index], state.balances[index])
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 1
    yield from run_withdrawals_processing(spec, state, payload)
    assert len(state.pending_partial_withdrawals) == 0
    # the sweep found no second withdrawal for the same validator
    assert not spec.is_partially_withdrawable_validator(
        state.validators[index], state.balances[index])


@with_all_phases_from("electra")
@spec_state_test
def test_pending_withdrawals_with_effective_sweep_on_top(spec, state):
    """Excess beyond the pending amount: the sweep emits a SECOND
    withdrawal for the same validator."""
    index = min(len(state.validators),
                int(spec.MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP)) // 2
    prepare_pending_withdrawal(
        spec, state, index,
        effective_balance=spec.MAX_EFFECTIVE_BALANCE_ELECTRA,
        amount=2_000_000_000)
    # extra excess beyond the pending amount keeps the validator
    # partially withdrawable AFTER the pending request drains
    state.balances[index] = uint64(
        int(state.balances[index]) + 1_000_000_000)
    payload = payload_with_expected_withdrawals(spec, state)
    assert len(payload.withdrawals) == 2
    assert all(int(w.validator_index) == index
               for w in payload.withdrawals)
    yield from run_withdrawals_processing(spec, state, payload)
    assert len(state.pending_partial_withdrawals) == 0


@with_all_phases_from("electra")
@spec_state_test
def test_pending_withdrawals_with_sweep_different_validator(spec, state):
    """Pending withdrawal for one validator, sweepable excess on
    another: both are in the payload."""
    index_0, index_1 = 1, 3
    prepare_pending_withdrawal(spec, state, index_0)
    prepare_partially_withdrawable_validator(spec, state, index_1)
    payload = payload_with_expected_withdrawals(spec, state)
    assert sorted(int(w.validator_index)
                  for w in payload.withdrawals) == [index_0, index_1]
    yield from run_withdrawals_processing(spec, state, payload)
    assert len(state.pending_partial_withdrawals) == 0


@with_all_phases_from("electra")
@spec_state_test
def test_pending_withdrawals_mixed_with_sweep_and_fully_withdrawable(
        spec, state):
    prepare_pending_withdrawal(spec, state, 1)
    prepare_fully_withdrawable_validator(spec, state, 3)
    prepare_partially_withdrawable_validator(spec, state, 5)
    payload = payload_with_expected_withdrawals(spec, state)
    assert sorted(int(w.validator_index)
                  for w in payload.withdrawals) == [1, 3, 5]
    yield from run_withdrawals_processing(spec, state, payload)
    assert len(state.pending_partial_withdrawals) == 0
