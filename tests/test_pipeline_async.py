"""Async pipelined flush engine (sigpipe/pipeline_async.py).

The contract under test:

* Parity: with overlap ON (engine worker + hash leg + double-buffered
  gossip windows) per-message verdicts and the drained store are
  byte-identical to the `ASYNC_FLUSH=0` synchronous path — overlap
  changes WHEN work happens, never what any message does to the store.
  Holds mid-overlap under the fault matrix (raise/timeout/corrupt at
  every pipelined site): the resilience seams degrade on the worker
  exactly as they would inline.
* Drain/abandon purity: a flush the caller abandons past its deadline
  keeps running on the worker but its outcome is discarded at the join
  and it may no longer write shared caches or verdict maps — the same
  zombie discipline as the abandoned merkle sweep (test_merkle_inc).
* The device-resident merkle sweep (ops/sha256.fused_rounds) re-roots
  in ONE host<->device round-trip, byte-identical to the per-level
  path and the full-rebuild oracle.
* Scenario fleets degrade to inline execution (the nodectx stack is
  process-global), and the flush counters pin the overlap: every
  synchronous flush runs inline, every async one on the engine worker.
"""
import threading

import pytest

from consensus_specs_tpu import resilience, sigpipe
from consensus_specs_tpu.resilience import (
    FaultPlan, FaultSpec, INCIDENTS, faults,
)
from consensus_specs_tpu.sigpipe import METRICS, pipeline_async
from consensus_specs_tpu.sigpipe import cache as sig_cache
from consensus_specs_tpu.specs import get_spec
from consensus_specs_tpu.ssz import hash_tree_root, incremental, uint64
from consensus_specs_tpu.test_infra.attestations import (
    get_valid_attestation)
from consensus_specs_tpu.test_infra.blocks import (
    build_empty_block_for_next_slot, state_transition_and_sign_block)
from consensus_specs_tpu.test_infra.genesis import (
    create_genesis_state, default_balances)
from consensus_specs_tpu.utils import nodectx


@pytest.fixture(autouse=True)
def _clean():
    resilience.disable()
    sigpipe.disable()
    incremental.disable()
    INCIDENTS.clear()
    METRICS.reset()
    pipeline_async.reset()
    yield
    pipeline_async.drain()
    pipeline_async.reset()
    resilience.disable()
    sigpipe.disable()
    incremental.disable()
    INCIDENTS.clear()


# ---------------------------------------------------------------------------
# engine unit tier (no spec machinery)
# ---------------------------------------------------------------------------

def test_submit_inline_when_disabled():
    pipeline_async.disable()
    on_thread = []
    t = pipeline_async.submit(
        lambda: on_thread.append(threading.current_thread().name) or 41)
    assert t.done() and t.result() == 41
    assert on_thread == [threading.current_thread().name]
    assert METRICS.count("inline_flushes") == 1
    assert METRICS.count("async_flushes") == 0


def test_submit_overlaps_and_completes_fifo():
    pipeline_async.enable()
    gate = threading.Event()
    order = []

    def first():
        gate.wait(5.0)
        order.append("first")
        return 1

    t1 = pipeline_async.submit(first)
    t2 = pipeline_async.submit(lambda: order.append("second") or 2)
    assert not t1.done()        # genuinely in flight, caller not blocked
    gate.set()
    assert t1.result() == 1 and t2.result() == 2
    assert order == ["first", "second"]     # FIFO: submit order
    assert METRICS.count("async_flushes") == 2


def test_ticket_failure_answers_none_and_counts():
    pipeline_async.enable()
    t = pipeline_async.submit(lambda: (_ for _ in ()).throw(
        RuntimeError("boom")))
    assert t.result() is None
    assert t.state() == pipeline_async.FAILED
    assert METRICS.count("pipeline_errors") == 1


def test_leg_reraises_at_the_join():
    pipeline_async.enable()

    def bad():
        raise ValueError("leg error")

    leg = pipeline_async.launch_leg(bad, "t")
    with pytest.raises(ValueError, match="leg error"):
        leg.get()


def test_nodectx_forces_inline():
    """Per-node fleets run inline: the nodectx stack is process-global,
    so overlapping two nodes' flushes would interleave its push/pop and
    mis-attribute incidents."""
    pipeline_async.enable()
    assert pipeline_async.overlap_live()
    with nodectx.use(nodectx.NodeContext("n0")):
        assert not pipeline_async.overlap_live()
        t = pipeline_async.submit(lambda: 7)
        assert t.done() and t.result() == 7
    assert pipeline_async.overlap_live()


def test_abandoned_flush_never_writes_caches_or_results():
    """THE zombie pin: a flush abandoned past its deadline keeps
    running on the worker, but from the abandonment on it may not
    write the pubkey/aggregate caches, and its outcome is discarded at
    the join — exactly the abandoned-merkle-sweep purity contract."""
    from consensus_specs_tpu.test_infra.keys import pubkeys
    pipeline_async.enable()
    sig_cache.clear()
    gate = threading.Event()
    pk = bytes(pubkeys[0])
    done = []

    def zombie():
        gate.wait(5.0)
        # runs AFTER the caller abandoned: both insert paths must
        # decline (writes_allowed() is False on this worker)
        point = sig_cache.PUBKEYS.get(pk)
        agg = sig_cache.AGGREGATES.aggregate([pk])
        done.append((point, agg))
        return {"verdict": True}

    ticket = pipeline_async.submit(zombie)
    assert ticket.result(timeout=0.01) is None      # deadline expired
    assert ticket.abandoned()
    assert METRICS.count("abandoned_flushes") == 1
    gate.set()
    assert pipeline_async.drain(5.0)
    assert done, "the zombie flush should have finished on the worker"
    # late completion wrote nothing: no cache entries, result discarded
    assert len(sig_cache.PUBKEYS) == 0
    assert len(sig_cache.AGGREGATES) == 0
    assert ticket.result() is None


def test_abandoned_writes_suppressed_across_watchdog_worker():
    """The zombie pin must survive the supervisor's thread hop: with a
    watchdog deadline armed, the dispatched device fn runs on the
    per-site _SiteWorker thread, and the abandoned flush's ticket must
    follow it there (bind_current_ticket) — otherwise cache writes
    resume from the site worker."""
    from consensus_specs_tpu.resilience.supervisor import dispatch
    from consensus_specs_tpu.test_infra.keys import pubkeys
    pipeline_async.enable()
    resilience.enable(deadline_s=10.0)
    sig_cache.clear()
    gate = threading.Event()
    pk = bytes(pubkeys[1])
    done = []

    def device():
        gate.wait(5.0)      # past the caller's abandonment
        point = sig_cache.PUBKEYS.get(pk)
        done.append(point)
        return {"ok": True}

    def flush():
        return dispatch("gossip.batch_verify", device, lambda: None)

    try:
        ticket = pipeline_async.submit(flush)
        assert ticket.result(timeout=0.01) is None
        assert ticket.abandoned()
        gate.set()
        assert pipeline_async.drain(10.0)
    finally:
        resilience.disable()
    assert done, "the watchdog'd dispatch should have finished"
    assert len(sig_cache.PUBKEYS) == 0      # no write from the hop


# ---------------------------------------------------------------------------
# gossip ingestion parity: async on/off, clean and mid-overlap faults
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def spec():
    return get_spec("altair", "minimal")


@pytest.fixture(scope="module")
def ingestion(spec):
    """(genesis, schedule, tick_slot): a small mixed gossip schedule —
    several singles across two windows, one duplicate, one
    bad-signature attestation, one signed block."""
    genesis = create_genesis_state(spec, default_balances(spec))
    state = genesis.copy()
    spec.process_slots(state, uint64(spec.SLOTS_PER_EPOCH + 2))

    def singles(slot, count):
        committee = spec.get_beacon_committee(
            state, uint64(slot), uint64(0))
        return [get_valid_attestation(
            spec, state, slot=uint64(slot), index=0,
            filter_participant_set=lambda s, v=v: {v}, signed=True)
            for v in list(committee)[:count]]

    atts = singles(int(state.slot) - 1, 3) + singles(int(state.slot) - 2, 2)
    bad = singles(int(state.slot) - 3, 1)[0]
    bad.signature = atts[0].signature       # decodable, wrong

    att = get_valid_attestation(spec, state, signed=True)
    advanced = state.copy()
    spec.process_slots(advanced, uint64(
        state.slot + spec.MIN_ATTESTATION_INCLUSION_DELAY))
    block = build_empty_block_for_next_slot(spec, advanced)
    block.body.attestations.append(att)
    signed = state_transition_and_sign_block(spec, advanced.copy(), block)

    schedule = ([("attestation", a) for a in atts]
                + [("attestation", bad),
                   ("attestation", atts[0]),        # duplicate
                   ("block", signed)])
    return genesis, schedule, int(signed.message.slot)


def _run_ingestion(spec, ingestion, overlap: bool, windows: int = 3):
    from consensus_specs_tpu.gossip import (
        AdmissionPipeline, GossipConfig, ManualClock, store_fingerprint)
    from consensus_specs_tpu.test_infra.fork_choice import (
        get_genesis_forkchoice_store)
    genesis, schedule, tick_slot = ingestion
    (pipeline_async.enable if overlap else pipeline_async.disable)()
    store = get_genesis_forkchoice_store(spec, genesis)
    spec.on_tick(store, store.genesis_time
                 + tick_slot * int(spec.config.SECONDS_PER_SLOT))
    clock = ManualClock()
    pipe = AdmissionPipeline(spec, store, GossipConfig(), clock)
    per_window = max(len(schedule) // windows, 1)
    for i, (topic, payload) in enumerate(schedule):
        pipe.submit(topic, payload, peer=f"p{i % 3}")
        if (i + 1) % per_window == 0:
            clock.advance(0.06)
            pipe.poll()
    pipe.drain()
    assert pipeline_async.drain(10.0)
    statuses = [(r.seq, r.topic, r.status) for r in pipe.verdicts()]
    return statuses, store_fingerprint(spec, store)


def test_async_ingestion_matches_sync_byte_for_byte(spec, ingestion):
    sync_v, sync_fp = _run_ingestion(spec, ingestion, overlap=False)
    assert METRICS.count("inline_flushes") > 0      # sync: on the caller
    assert METRICS.count("async_flushes") == 0
    METRICS.reset()
    async_v, async_fp = _run_ingestion(spec, ingestion, overlap=True)
    assert async_v == sync_v
    assert async_fp == sync_fp
    assert METRICS.count("inline_flushes") == 0     # overlap: none inline
    assert METRICS.count("async_flushes") > 0


def test_async_parity_under_faults_mid_overlap(spec, ingestion):
    """Persistent raise faults at the pipelined sites while windows are
    staged/delivered out of phase: the seams degrade on the engine
    worker and the store still matches the clean synchronous run."""
    clean_v, clean_fp = _run_ingestion(spec, ingestion, overlap=False)
    METRICS.reset()
    INCIDENTS.clear()
    plan = FaultPlan([
        FaultSpec("ops.g1_aggregate", "raise", persistent=True),
        FaultSpec("gossip.batch_verify", "raise", persistent=True),
        FaultSpec("ops.msm", "raise", persistent=True),
    ], seed=11)
    resilience.enable(max_retries=0, breaker_threshold=1, probe_after=99)
    try:
        with faults.inject(plan):
            async_v, async_fp = _run_ingestion(spec, ingestion,
                                               overlap=True)
    finally:
        resilience.disable()
    assert plan.total_fires() > 0
    assert INCIDENTS.count(event="injected") == plan.total_fires()
    assert async_v == clean_v
    assert async_fp == clean_fp


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["raise", "timeout", "corrupt"])
@pytest.mark.parametrize("site", [
    "bls.pairing_check", "bls.verify_batch",
    "bls.fast_aggregate_verify_batch", "ops.g1_aggregate", "ops.msm",
    "ssz.merkle_sweep", "gossip.batch_verify",
])
def test_async_fault_matrix_parity(spec, ingestion, site, kind):
    """The full chaos matrix mid-overlap (`make chaos` tier): every
    pipelined site x every fault kind, async ON, verdicts + store
    byte-identical to the clean synchronous oracle."""
    clean_v, clean_fp = _run_ingestion(spec, ingestion, overlap=False)
    METRICS.reset()
    INCIDENTS.clear()
    # speclint: disable=seam-dynamic-site -- parametrized over the
    # registry-derived site list above
    plan = FaultPlan([FaultSpec(site, kind, persistent=True,
                                sleep_s=0.15)], seed=5)
    incremental.enable(guard_sample_rate=1.0, guard_seed=5)
    resilience.enable(max_retries=0, breaker_threshold=1, probe_after=99,
                      deadline_s=0.05 if kind == "timeout" else None,
                      guard_sample_rate=1.0, guard_seed=5)
    try:
        with faults.inject(plan):
            async_v, async_fp = _run_ingestion(spec, ingestion,
                                               overlap=True)
    finally:
        resilience.disable()
        incremental.disable()
    assert async_v == clean_v
    assert async_fp == clean_fp
    assert INCIDENTS.count(event="injected") == plan.total_fires()


# ---------------------------------------------------------------------------
# block scope: the FlushTicket join inside state_transition
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def block_workload(spec):
    state = create_genesis_state(spec, default_balances(spec))
    spec.process_slots(state, uint64(spec.SLOTS_PER_EPOCH + 2))
    att = get_valid_attestation(spec, state, signed=True)
    advanced = state.copy()
    spec.process_slots(advanced, uint64(
        state.slot + spec.MIN_ATTESTATION_INCLUSION_DELAY))
    block = build_empty_block_for_next_slot(spec, advanced)
    block.body.attestations.append(att)
    signed = state_transition_and_sign_block(spec, advanced.copy(), block)
    native = advanced.copy()
    spec.state_transition(native, signed)
    return advanced, signed, hash_tree_root(native)


def test_block_scope_joins_ticket(spec, block_workload):
    pre, signed, native_root = block_workload
    pipeline_async.enable()
    sigpipe.enable()
    state = pre.copy()
    try:
        spec.state_transition(state, signed)
    finally:
        sigpipe.disable()
    assert hash_tree_root(state) == native_root
    assert METRICS.count("async_flushes") >= 1
    assert METRICS.count("seam_hits") > 0   # the lazy map actually fed


def test_block_scope_engine_failure_degrades_scalar(
        spec, block_workload, monkeypatch):
    """A flush that dies on the worker degrades to scalar at the seams
    (empty lazy map -> every lookup misses), never to a wrong root."""
    from consensus_specs_tpu.sigpipe import verify as sig_verify
    pre, signed, native_root = block_workload
    pipeline_async.enable()
    sigpipe.enable()

    def explode(*a, **k):
        raise RuntimeError("engine workload died")

    monkeypatch.setattr(sig_verify, "_batch_verify_unique", explode)
    state = pre.copy()
    try:
        spec.state_transition(state, signed)
    finally:
        sigpipe.disable()
    assert hash_tree_root(state) == native_root
    assert METRICS.count("pipeline_errors") >= 1
    assert METRICS.count_labeled("scalar_fallbacks", "collector_miss") > 0


# ---------------------------------------------------------------------------
# device-resident merkle sweep (ops/sha256.fused_rounds)
# ---------------------------------------------------------------------------

def _small_container():
    from consensus_specs_tpu.ssz import Bytes32, Container, List

    class Small(Container):
        a: List[uint64, 1024]
        b: Bytes32
        c: uint64

    s = Small(b=Bytes32(b"\x22" * 32), c=uint64(3))
    for i in range(200):
        s.a.append(uint64(i * 7))
    return s


def test_fused_sweep_one_round_trip_and_byte_parity():
    from consensus_specs_tpu.ssz import merkle
    incremental.enable()
    merkle.use_tpu_hashing(threshold=1)     # every level device-bulk
    try:
        view = _small_container()
        incremental.track(view)
        root = bytes(view.hash_tree_root())     # cache build
        assert root == incremental.oracle_root(view)
        assert METRICS.count("merkle_device_round_trips") == 1
        view.a[3] = uint64(123456)
        view.c = uint64(4)
        before = METRICS.count("merkle_device_round_trips")
        root = bytes(view.hash_tree_root())     # incremental re-root
        assert root == incremental.oracle_root(view)
        assert METRICS.count("merkle_device_round_trips") == before + 1
    finally:
        merkle.set_bulk_level_hasher(None)


def test_fused_sweep_matches_per_level_path(monkeypatch):
    from consensus_specs_tpu.ssz import merkle
    incremental.enable()
    merkle.use_tpu_hashing(threshold=1)
    try:
        view = _small_container()
        incremental.track(view)
        bytes(view.hash_tree_root())
        view.a[9] = uint64(1)
        # per-level path on the same diff (MERKLE_FUSED=0 escape hatch)
        monkeypatch.setenv("MERKLE_FUSED", "0")
        before = METRICS.count("merkle_device_round_trips")
        per_level = bytes(view.hash_tree_root())
        assert per_level == incremental.oracle_root(view)
        trips = METRICS.count("merkle_device_round_trips") - before
        assert trips > 1        # one per bulk level
        monkeypatch.setenv("MERKLE_FUSED", "1")
        view.a[10] = uint64(2)
        before = METRICS.count("merkle_device_round_trips")
        fused = bytes(view.hash_tree_root())
        assert fused == incremental.oracle_root(view)
        assert METRICS.count("merkle_device_round_trips") == before + 1
    finally:
        merkle.set_bulk_level_hasher(None)


def test_fused_rounds_kernel_parity_vs_hashlib():
    import hashlib
    from consensus_specs_tpu.ops import sha256 as S
    S.reset_literal_pool()
    lits = [bytes([i]) * 32 for i in range(6)]
    r0 = ([0, 2, 4], [1, 3, 5])
    r1 = ([6], [7])     # global idx 6,7 = round-0 outputs 0,1
    out = S.fused_rounds(b"".join(lits), [r0, r1])
    h = lambda a, b: hashlib.sha256(a + b).digest()  # noqa: E731
    e0 = h(lits[0], lits[1]) + h(lits[2], lits[3]) + h(lits[4], lits[5])
    assert out[0] == e0
    assert out[1] == h(e0[:32], e0[32:64])
    # the device literal pool: a second run of the same DAG uploads
    # nothing (every literal — and the previous run's outputs — is
    # resident), byte-identical results
    stats: dict = {}
    again = S.fused_rounds(b"".join(lits), [r0, r1], stats=stats)
    assert again == out
    assert stats == {"uploaded": 0, "skipped": 6}
    S.reset_literal_pool()


def test_fused_sweep_sibling_pool_skips_clean_reuploads():
    """ROADMAP async follow-up (c): between consecutive fused sweeps
    the clean-sibling level buffers stay device-resident, so a re-root
    uploads ONLY the dirty literals — pool hits counted in
    `merkle_sibling_uploads_skipped` (the sibling counter next to
    `merkle_device_round_trips`), roots byte-identical throughout."""
    from consensus_specs_tpu.ops import sha256 as S
    from consensus_specs_tpu.ssz import merkle
    incremental.enable()
    merkle.use_tpu_hashing(threshold=1)
    S.reset_literal_pool()
    try:
        view = _small_container()
        incremental.track(view)
        root = bytes(view.hash_tree_root())     # cache-build sweep
        assert root == incremental.oracle_root(view)
        build_uploads = METRICS.count("merkle_sibling_uploads")
        assert build_uploads > 0
        view.a[3] = uint64(424242)
        root = bytes(view.hash_tree_root())     # incremental re-root
        assert root == incremental.oracle_root(view)
        second_uploads = METRICS.count(
            "merkle_sibling_uploads") - build_uploads
        # only the dirty leaf literal is fresh; every clean sibling
        # (incl. the previous sweep's parents) hit the device pool
        assert METRICS.count("merkle_sibling_uploads_skipped") > 0
        assert second_uploads < build_uploads
        assert second_uploads <= 2      # dirty chunk (+ length mix-in)
        # and again: an identical-shape diff re-uses the same residency
        skipped_before = METRICS.count("merkle_sibling_uploads_skipped")
        view.a[3] = uint64(424243)
        root = bytes(view.hash_tree_root())
        assert root == incremental.oracle_root(view)
        assert METRICS.count(
            "merkle_sibling_uploads_skipped") > skipped_before
    finally:
        S.reset_literal_pool()
        merkle.set_bulk_level_hasher(None)
