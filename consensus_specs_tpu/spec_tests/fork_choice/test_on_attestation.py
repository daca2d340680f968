"""on_attestation fork-choice tests (reference:
test/phase0/unittests/fork_choice/test_on_attestation.py shape, emitted
as step vectors): latest-message updates, future/old-epoch rejection,
unknown-block rejection, and the proposer-boost root lifecycle."""
from ...ssz import hash_tree_root
from ...test_infra.context import (
    spec_state_test, with_all_phases, never_bls)
from ...test_infra.attestations import get_valid_attestation
from ...test_infra.blocks import (
    build_empty_block_for_next_slot, state_transition_and_sign_block)
from ...test_infra.fork_choice import (
    start_fork_choice_test, tick_and_add_block, add_attestation,
    add_block, output_store_checks, emit_steps, tick_to_slot)


def _chain_block(spec, state, store, steps):
    block = build_empty_block_for_next_slot(spec, state)
    signed = state_transition_and_sign_block(spec, state, block)
    parts = list(tick_and_add_block(spec, store, signed, steps))
    return signed, parts


@with_all_phases
@spec_state_test
@never_bls
def test_on_attestation_updates_latest_messages(spec, state):
    store, steps, parts = start_fork_choice_test(spec, state)
    for name, v in parts:
        yield name, v
    signed, block_parts = _chain_block(spec, state, store, steps)
    for name, v in block_parts:
        yield name, v
    attestation = get_valid_attestation(spec, state,
                                        slot=signed.message.slot,
                                        signed=True)
    # attestations are only considered from the NEXT slot
    tick_to_slot(spec, store, int(signed.message.slot) + 1, steps)
    for name, v in add_attestation(spec, store, attestation, steps):
        yield name, v
    target_root = hash_tree_root(signed.message)
    updated = [i for i, msg in store.latest_messages.items()
               if msg.root == target_root]
    assert updated, "no latest message recorded"
    output_store_checks(spec, store, steps)
    yield from emit_steps(steps)


@with_all_phases
@spec_state_test
@never_bls
def test_on_attestation_rejects_current_slot(spec, state):
    """An attestation for the current slot is premature (must wait one
    slot)."""
    store, steps, parts = start_fork_choice_test(spec, state)
    for name, v in parts:
        yield name, v
    signed, block_parts = _chain_block(spec, state, store, steps)
    for name, v in block_parts:
        yield name, v
    attestation = get_valid_attestation(spec, state,
                                        slot=signed.message.slot,
                                        signed=True)
    # store clock still at the attestation's own slot
    for name, v in add_attestation(spec, store, attestation, steps,
                                   valid=False):
        yield name, v
    yield from emit_steps(steps)


@with_all_phases
@spec_state_test
@never_bls
def test_proposer_boost_set_and_reset(spec, state):
    """A timely first block sets proposer_boost_root; the next slot
    tick clears it (fork-choice.md proposer-boost lifecycle)."""
    store, steps, parts = start_fork_choice_test(spec, state)
    for name, v in parts:
        yield name, v
    block = build_empty_block_for_next_slot(spec, state)
    signed = state_transition_and_sign_block(spec, state, block)
    # tick exactly to the block's slot start: arrival is timely
    tick_to_slot(spec, store, int(signed.message.slot), steps)
    for name, v in add_block(spec, store, signed, steps):
        yield name, v
    root = hash_tree_root(signed.message)
    assert store.proposer_boost_root == root
    # boosted head is the new block
    assert spec.get_head(store) == root
    # advancing to the next slot resets the boost
    tick_to_slot(spec, store, int(signed.message.slot) + 1, steps)
    assert store.proposer_boost_root == bytes(32)
    output_store_checks(spec, store, steps)
    yield from emit_steps(steps)


@with_all_phases
@spec_state_test
@never_bls
def test_on_attestation_previous_epoch_ok(spec, state):
    """Attestations from the previous epoch are accepted while the
    epoch window is open."""
    store, steps, parts = start_fork_choice_test(spec, state)
    for name, v in parts:
        yield name, v
    signed, block_parts = _chain_block(spec, state, store, steps)
    for name, v in block_parts:
        yield name, v
    attestation = get_valid_attestation(spec, state,
                                        slot=signed.message.slot,
                                        signed=True)
    # move into the NEXT epoch (window of one epoch back stays open)
    tick_to_slot(spec, store,
                 int(spec.SLOTS_PER_EPOCH) + 1, steps)
    for name, v in add_attestation(spec, store, attestation, steps):
        yield name, v
    output_store_checks(spec, store, steps)
    yield from emit_steps(steps)


@with_all_phases
@spec_state_test
@never_bls
def test_on_attestation_rejects_two_epochs_back(spec, state):
    """Attestations older than the previous epoch are dropped."""
    store, steps, parts = start_fork_choice_test(spec, state)
    for name, v in parts:
        yield name, v
    signed, block_parts = _chain_block(spec, state, store, steps)
    for name, v in block_parts:
        yield name, v
    attestation = get_valid_attestation(spec, state,
                                        slot=signed.message.slot,
                                        signed=True)
    tick_to_slot(spec, store,
                 2 * int(spec.SLOTS_PER_EPOCH) + 1, steps)
    for name, v in add_attestation(spec, store, attestation, steps,
                                   valid=False):
        yield name, v
    output_store_checks(spec, store, steps)
    yield from emit_steps(steps)


@with_all_phases
@spec_state_test
@never_bls
def test_on_attestation_rejects_unknown_block(spec, state):
    """An attestation voting for an unknown head root is rejected."""
    store, steps, parts = start_fork_choice_test(spec, state)
    for name, v in parts:
        yield name, v
    signed, block_parts = _chain_block(spec, state, store, steps)
    for name, v in block_parts:
        yield name, v
    attestation = get_valid_attestation(spec, state,
                                        slot=signed.message.slot,
                                        signed=False)
    attestation.data.beacon_block_root = b"\x66" * 32
    from ...test_infra.attestations import sign_attestation
    sign_attestation(spec, state, attestation)
    tick_to_slot(spec, store, int(signed.message.slot) + 1, steps)
    for name, v in add_attestation(spec, store, attestation, steps,
                                   valid=False):
        yield name, v
    output_store_checks(spec, store, steps)
    yield from emit_steps(steps)


@with_all_phases
@spec_state_test
@never_bls
def test_on_attestation_future_epoch_rejected(spec, state):
    """Target epochs ahead of the store clock are rejected."""
    store, steps, parts = start_fork_choice_test(spec, state)
    for name, v in parts:
        yield name, v
    signed, block_parts = _chain_block(spec, state, store, steps)
    for name, v in block_parts:
        yield name, v
    from ...ssz import uint64
    attestation = get_valid_attestation(spec, state,
                                        slot=signed.message.slot,
                                        signed=False)
    attestation.data.target.epoch = uint64(
        int(attestation.data.target.epoch) + 2)
    from ...test_infra.attestations import sign_attestation
    sign_attestation(spec, state, attestation)
    tick_to_slot(spec, store, int(signed.message.slot) + 1, steps)
    for name, v in add_attestation(spec, store, attestation, steps,
                                   valid=False):
        yield name, v
    output_store_checks(spec, store, steps)
    yield from emit_steps(steps)


@with_all_phases
@spec_state_test
@never_bls
def test_on_attestation_same_slot_same_target_overwrites(spec, state):
    """A later attestation by the same validators for a NEWER target
    replaces their latest messages."""
    store, steps, parts = start_fork_choice_test(spec, state)
    for name, v in parts:
        yield name, v
    s1, block_parts = _chain_block(spec, state, store, steps)
    for name, v in block_parts:
        yield name, v
    att1 = get_valid_attestation(spec, state, slot=s1.message.slot,
                                 signed=True)
    tick_to_slot(spec, store, int(s1.message.slot) + 1, steps)
    for name, v in add_attestation(spec, store, att1, steps):
        yield name, v
    s2, block_parts = _chain_block(spec, state, store, steps)
    for name, v in block_parts:
        yield name, v
    att2 = get_valid_attestation(spec, state, slot=s2.message.slot,
                                 signed=True)
    tick_to_slot(spec, store, int(s2.message.slot) + 1, steps)
    for name, v in add_attestation(spec, store, att2, steps):
        yield name, v
    root2 = hash_tree_root(s2.message)
    common = set(int(i) for i in spec.get_attesting_indices(
        state, att1) if True) & set(
        int(i) for i in spec.get_attesting_indices(state, att2))
    for i in common:
        assert store.latest_messages[i].root == root2
    output_store_checks(spec, store, steps)
    yield from emit_steps(steps)
