"""Incremental merkleization (ssz/incremental.py): dirty-subtree tracked
hash_tree_root with one layer-parallel sweep per re-root.

The contract under test:

* Parity: after ANY mutation sequence over any composite type (container
  field sets, list/vector element sets, append/pop, bit flips, union
  re-selects, nested mutations through child views), the incremental
  root is byte-identical to the full-rebuild oracle.
* Diff scaling: a re-root after k leaf mutations hashes O(k · log state)
  chunks and issues level-calls bounded by the static tree height, all
  inside ONE `ssz.merkle_sweep` dispatch.
* Copy-on-write: `copy()` shares the cache; mutating either side never
  corrupts the other — the txn/ overlay discipline (rollback drops the
  copy, commit adopts it, the base cache is never written).
* Resilience: a faulted/broken-open sweep site degrades to the legacy
  full Python re-root with identical bytes; a corrupted sweep is caught
  by the differential guard, which quarantines the caches.
* Flat elements: the elements of a sequence of flat containers (a
  registry) keep no cache of their own and hold no more tracked objects
  than with the mode off, plus at most one; the differential tests run
  over such a registry too (the `registry` cases).
* ZERO_HASHES has one source of truth (merkle.py), shared by proofs.py
  and the deposit-contract model.
"""
import gc
from random import Random

import pytest

from consensus_specs_tpu import resilience
from consensus_specs_tpu.resilience import FaultPlan, FaultSpec, faults
from consensus_specs_tpu.resilience.supervisor import OPEN, QUARANTINED
from consensus_specs_tpu.sigpipe import METRICS
from consensus_specs_tpu.specs import get_spec
from consensus_specs_tpu.ssz import (
    Bitlist, Bitvector, Bytes32, Bytes48, Container, List, Union, Vector,
    boolean, hash_tree_root, incremental, uint8, uint64,
)
from consensus_specs_tpu.test_infra import disable_bls
from consensus_specs_tpu.test_infra.attestations import get_valid_attestation
from consensus_specs_tpu.test_infra.blocks import (
    build_empty_block_for_next_slot, state_transition_and_sign_block)
from consensus_specs_tpu.test_infra.genesis import (
    create_genesis_state, default_balances)


@pytest.fixture(autouse=True)
def _clean():
    incremental.disable()
    resilience.disable()
    METRICS.reset()
    yield
    incremental.disable()
    resilience.disable()


def oracle(view) -> bytes:
    """Fully independent root: serialize -> deserialize -> legacy hash
    on a fresh, never-tracked object."""
    return bytes(type(view).deserialize(view.serialize()).hash_tree_root())


# ---------------------------------------------------------------------------
# type zoo
# ---------------------------------------------------------------------------

class Checkpoint(Container):
    epoch: uint64
    root: Bytes32


class Inner(Container):
    a: uint64
    cps: List[Checkpoint, 16]
    bits: Bitlist[300]
    bv: Bitvector[10]


Opt = Union[None, uint64, Checkpoint]


class Zoo(Container):
    x: uint64
    inner: Inner
    bal: List[uint64, 1 << 20]
    small: List[uint8, 100]
    vec: Vector[Bytes32, 8]
    cvec: Vector[Checkpoint, 4]
    u: Opt


def random_zoo(rng: Random) -> Zoo:
    z = Zoo(x=rng.randrange(1 << 32))
    for _ in range(rng.randrange(0, 9)):
        z.bal.append(rng.randrange(1 << 50))
    for _ in range(rng.randrange(0, 20)):
        z.small.append(rng.randrange(256))
    for _ in range(rng.randrange(0, 5)):
        z.inner.cps.append(Checkpoint(epoch=rng.randrange(100),
                                      root=Bytes32(rng.randbytes(32))))
    for _ in range(rng.randrange(0, 40)):
        z.inner.bits.append(rng.random() < 0.5)
    sel = rng.randrange(3)
    z.u = Opt(sel, None if sel == 0 else
              (uint64(rng.randrange(1000)) if sel == 1
               else Checkpoint(epoch=rng.randrange(50))))
    return z


def _mutate_once(rng: Random, z: Zoo) -> None:
    """One random mutation drawn from every mutation family the type
    system supports."""
    ops = []
    ops.append(lambda: setattr(z, "x", uint64(rng.randrange(1 << 32))))
    ops.append(lambda: setattr(z.inner, "a", uint64(rng.randrange(1 << 20))))
    ops.append(lambda: z.vec.__setitem__(
        rng.randrange(8), Bytes32(rng.randbytes(32))))
    ops.append(lambda: setattr(
        z.cvec[rng.randrange(4)], "epoch", uint64(rng.randrange(1000))))
    if len(z.bal) < 9:
        ops.append(lambda: z.bal.append(rng.randrange(1 << 50)))
    if len(z.bal):
        ops.append(lambda: z.bal.__setitem__(
            rng.randrange(len(z.bal)), uint64(rng.randrange(1 << 50))))
        ops.append(lambda: z.bal.pop())
    if len(z.small) < 100:
        ops.append(lambda: z.small.append(rng.randrange(256)))
    if len(z.small):
        ops.append(lambda: z.small.pop(rng.randrange(len(z.small))))
    if len(z.inner.cps) < 16:
        ops.append(lambda: z.inner.cps.append(
            Checkpoint(epoch=rng.randrange(100))))
    if len(z.inner.cps):
        ops.append(lambda: setattr(
            z.inner.cps[rng.randrange(len(z.inner.cps))],
            "root", Bytes32(rng.randbytes(32))))
        ops.append(lambda: z.inner.cps.pop(rng.randrange(len(z.inner.cps))))
    if len(z.inner.bits) < 300:
        ops.append(lambda: z.inner.bits.append(rng.random() < 0.5))
    if len(z.inner.bits):
        ops.append(lambda: z.inner.bits.__setitem__(
            rng.randrange(len(z.inner.bits)), rng.random() < 0.5))
    ops.append(lambda: z.inner.bv.__setitem__(
        rng.randrange(10), rng.random() < 0.5))
    sel = rng.randrange(3)
    ops.append(lambda: setattr(z, "u", Opt(
        sel, None if sel == 0 else
        (uint64(rng.randrange(1000)) if sel == 1
         else Checkpoint(epoch=rng.randrange(50))))))
    if z.u.selector == 2:
        ops.append(lambda: setattr(
            z.u.value, "epoch", uint64(rng.randrange(1000))))
    rng.choice(ops)()


class Record(Container):
    """Registry-like flat element: 8 fields, a Bytes48 among them."""
    pubkey: Bytes48
    creds: Bytes32
    balance: uint64
    slashed: boolean
    eligible: uint64
    active: uint64
    exit: uint64
    withdrawable: uint64


class Vote(Container):
    """Flat, with an odd field count (a zero-padded subtree)."""
    deposit_root: Bytes32
    count: uint64
    block_hash: Bytes32


class Pending(Container):
    """Holds mutable views: its elements keep their own caches."""
    bits: Bitlist[64]
    data: Checkpoint


class Registry(Container):
    slot: uint64
    records: List[Record, 1 << 40]
    balances: List[uint64, 1 << 40]
    votes: List[Vote, 64]
    cps: List[Checkpoint, 8]
    pending: List[Pending, 16]
    cp: Checkpoint      # a flat container as a field keeps its cache


def random_record(rng: Random) -> Record:
    return Record(pubkey=Bytes48(rng.randbytes(48)),
                  creds=Bytes32(rng.randbytes(32)),
                  balance=rng.randrange(1 << 40),
                  slashed=rng.random() < 0.1,
                  eligible=rng.randrange(100), active=rng.randrange(100),
                  exit=rng.randrange(1 << 64),
                  withdrawable=rng.randrange(1 << 64))


def random_registry(rng: Random, n: int | None = None) -> Registry:
    r = Registry(slot=rng.randrange(1 << 20),
                 cp=Checkpoint(epoch=rng.randrange(9)))
    for _ in range(rng.randrange(1, 24) if n is None else n):
        r.records.append(random_record(rng))
        r.balances.append(rng.randrange(1 << 40))
    for _ in range(rng.randrange(0, 5)):
        r.votes.append(Vote(count=rng.randrange(1 << 20),
                            block_hash=Bytes32(rng.randbytes(32))))
    for _ in range(rng.randrange(0, 3)):
        r.pending.append(Pending(bits=[rng.random() < 0.5
                                       for _ in range(rng.randrange(64))]))
    return r


def _mutate_registry(rng: Random, r: Registry) -> None:
    """One random mutation of a registry: field set on an element,
    element replace, append, middle pop, on flat and nested elements."""
    n = len(r.records)
    ops = [
        lambda: setattr(r, "slot", uint64(rng.randrange(1 << 20))),
        lambda: setattr(r.cp, "epoch", uint64(rng.randrange(1000))),
        # a copy of a cached flat container enters a flat sequence
        lambda: r.cps.append(r.cp) if len(r.cps) < 8 else r.cps.pop(0),
    ]
    if n < 40:
        ops.append(lambda: r.records.append(random_record(rng)))
    if n:
        i = rng.randrange(n)
        ops += [
            lambda: setattr(r.records[i], "exit",
                            uint64(rng.randrange(1 << 64))),
            lambda: setattr(r.records[i], "pubkey",
                            Bytes48(rng.randbytes(48))),
            lambda: setattr(r.records[i], "slashed", rng.random() < 0.5),
            lambda: r.records.__setitem__(i, random_record(rng)),
            lambda: r.records.pop(i),
        ]
    if len(r.votes) < 64:
        ops.append(lambda: r.votes.append(Vote(count=rng.randrange(99))))
    if len(r.votes):
        j = rng.randrange(len(r.votes))
        ops += [lambda: setattr(r.votes[j], "count", uint64(rng.randrange(99))),
                lambda: r.votes.pop(j)]
    if len(r.cps):
        k = rng.randrange(len(r.cps))
        ops.append(lambda: setattr(r.cps[k], "root",
                                   Bytes32(rng.randbytes(32))))
    if len(r.pending):
        m = rng.randrange(len(r.pending))
        ops += [lambda: r.pending[m].bits.append(True)
                if len(r.pending[m].bits) < 64 else r.pending.pop(m),
                lambda: setattr(r.pending[m].data, "epoch",
                                uint64(rng.randrange(99)))]
    rng.choice(ops)()


# each differential test runs over both shapes: the zoo of every
# composite family, and a registry of flat elements
KINDS = {"zoo": (random_zoo, _mutate_once),
         "registry": (random_registry, _mutate_registry)}
kinds = pytest.mark.parametrize("kind", sorted(KINDS))


# ---------------------------------------------------------------------------
# randomized mutation parity
# ---------------------------------------------------------------------------

@kinds
@pytest.mark.parametrize("seed", range(6))
def test_randomized_mutation_parity(seed, kind):
    make, mutate = KINDS[kind]
    rng = Random(f"merkle-inc-{seed}" + ("" if kind == "zoo" else kind))
    incremental.enable()
    z = incremental.track(make(rng))
    assert bytes(z.hash_tree_root()) == oracle(z)
    for step in range(40):
        mutate(rng, z)
        if rng.random() < 0.4:   # re-root mid-sequence, not only at the end
            assert bytes(z.hash_tree_root()) == oracle(z), (seed, step)
    assert bytes(z.hash_tree_root()) == oracle(z)
    # cached fast path answers without hashing and stays identical
    before = METRICS.count("merkle_chunks_hashed")
    assert bytes(z.hash_tree_root()) == oracle(z)
    assert METRICS.count("merkle_chunks_hashed") == before


def test_pop_to_empty_and_regrow():
    incremental.enable()
    z = incremental.track(Zoo())
    z.bal.append(1)
    z.inner.cps.append(Checkpoint(epoch=3))
    assert bytes(z.hash_tree_root()) == oracle(z)
    z.bal.pop()
    z.inner.cps.pop()
    assert bytes(z.hash_tree_root()) == oracle(z)
    z.bal.append(7)
    assert bytes(z.hash_tree_root()) == oracle(z)


def test_untracked_views_keep_legacy_path():
    incremental.enable()
    z = Zoo(x=3)
    assert bytes(z.hash_tree_root()) == oracle(z)
    assert METRICS.count("merkle_sweep_dispatches") == 0


# ---------------------------------------------------------------------------
# diff scaling: O(k log n) chunks, bounded level-calls, one dispatch
# ---------------------------------------------------------------------------

def test_diff_scaling_and_single_dispatch():
    incremental.enable()
    z = Zoo()
    for i in range(512):
        z.bal.append(i)
    incremental.track(z)
    z.hash_tree_root()
    built = METRICS.count("merkle_chunks_hashed")
    height = incremental.type_tree_height(Zoo)

    METRICS.reset()
    z.bal[17] = uint64(12345)       # k = 1 dirty leaf
    root = bytes(z.hash_tree_root())
    assert root == oracle(z)
    assert METRICS.count("merkle_sweep_dispatches") == 1
    assert METRICS.count("merkle_sweep_levels") <= height
    # one leaf re-roots one path: far fewer chunks than the full build
    assert 0 < METRICS.count("merkle_chunks_hashed") <= height
    assert METRICS.count("merkle_chunks_hashed") < built // 4
    assert METRICS.count("merkle_full_rebuilds") == 0

    METRICS.reset()
    for i in range(8):              # k = 8 scattered leaves
        z.bal[i * 60] = uint64(i)
    assert bytes(z.hash_tree_root()) == oracle(z)
    assert METRICS.count("merkle_sweep_dispatches") == 1
    assert METRICS.count("merkle_sweep_levels") <= height
    assert METRICS.count("merkle_chunks_hashed") <= 8 * height
    occ = METRICS.hist_counts("merkle_dirty_occupancy")
    assert sum(occ.values()) == 1   # one sweep observed


# ---------------------------------------------------------------------------
# copy-on-write / txn discipline
# ---------------------------------------------------------------------------

@kinds
def test_copy_shares_cache_copy_on_write(kind):
    make, mutate = KINDS[kind]
    incremental.enable()
    rng = Random("cow")
    z = incremental.track(make(rng))
    base_root = bytes(z.hash_tree_root())

    c = z.copy()
    before = METRICS.count("merkle_chunks_hashed")
    assert bytes(c.hash_tree_root()) == base_root   # cached, no rehash
    assert METRICS.count("merkle_chunks_hashed") == before

    # mutate the COPY: base cache must stay intact (rollback semantics)
    for _ in range(10):
        mutate(rng, c)
    assert bytes(c.hash_tree_root()) == oracle(c)
    assert bytes(z.hash_tree_root()) == base_root == oracle(z)

    # mutate the BASE: the copy keeps its own root (commit semantics)
    copy_root = bytes(c.hash_tree_root())
    for _ in range(10):
        mutate(rng, z)
    assert bytes(z.hash_tree_root()) == oracle(z)
    assert bytes(c.hash_tree_root()) == copy_root == oracle(c)


@kinds
def test_txn_rollback_never_corrupts_base_cache(kind):
    """The txn/ overlay contract: handlers mutate a .copy() of the
    stored state; an abort drops the copy.  The base state's cached
    tree must answer the same root afterwards, with no rehash."""
    make, mutate = KINDS[kind]
    incremental.enable()
    rng = Random("txn")
    z = incremental.track(make(rng))
    base_root = bytes(z.hash_tree_root())

    class Abort(Exception):
        pass

    try:
        txn_state = z.copy()
        for _ in range(8):
            mutate(rng, txn_state)
        txn_state.hash_tree_root()     # mid-txn re-root, then crash
        raise Abort()
    except Abort:
        del txn_state                  # rollback: the copy is dropped

    before = METRICS.count("merkle_chunks_hashed")
    assert bytes(z.hash_tree_root()) == base_root == oracle(z)
    assert METRICS.count("merkle_chunks_hashed") == before


# ---------------------------------------------------------------------------
# flat elements: no cache of their own, no tracked objects beyond off + 1
# ---------------------------------------------------------------------------

def test_flat_elements_keep_no_cache():
    incremental.enable()
    rng = Random("flat-pin")
    r = random_registry(rng, n=6)
    r.pending.append(Pending())
    incremental.track(r)
    r.hash_tree_root()
    for seq in (r.records, r.votes):
        for i, e in enumerate(seq):
            assert "_mcache" not in vars(e)
            assert e._mc_seq is seq and e._mc_idx == i
    # nested-mutable elements and flat fields keep their caches
    assert all(incremental.is_tracked(p) for p in r.pending)
    assert incremental.is_tracked(r.cp)
    # a copy of a cached flat container entering a flat sequence drops
    # the cache it brought and is linked like any element
    r.cps.append(r.cp)
    assert "_mcache" not in vars(r.cps[0]) and r.cps[0]._mc_seq is r.cps
    # the copy's elements point at the copy's sequence
    c = r.copy()
    assert all(e._mc_seq is c.records and e._mc_idx == i
               for i, e in enumerate(c.records))
    assert all("_mcache" not in vars(e) for e in c.records)

    # one field write re-plans one element's subtree (7 jobs) and the
    # list's path, inside one sweep
    r.hash_tree_root()
    METRICS.reset()
    r.records[3].exit = uint64(5)
    assert bytes(r.hash_tree_root()) == oracle(r)
    assert METRICS.count("merkle_flat_roots") == 1
    assert METRICS.count("merkle_sweep_dispatches") == 1
    assert METRICS.count("merkle_chunks_hashed") <= \
        7 + incremental.type_tree_height(Registry)
    # a middle pop re-indexes the later elements
    r.records.pop(2)
    assert [e._mc_idx for e in r.records] == list(range(len(r.records)))
    r.records[4].active = uint64(1)
    assert bytes(r.hash_tree_root()) == oracle(r)
    # a replaced element is detached: writing it dirties nothing
    old = r.records[0]
    r.records[0] = random_record(rng)
    assert old._mc_seq is None
    root = bytes(r.hash_tree_root())
    old.balance = uint64(1)
    assert bytes(r.hash_tree_root()) == root == oracle(r)


def _tracked_objects(roots, stop) -> int:
    """GC-tracked objects reachable from `roots`, not through `stop`
    nor through classes."""
    seen = {id(stop)}
    todo = list(roots)
    n = 0
    while todo:
        o = todo.pop()
        if id(o) in seen or isinstance(o, type) or not gc.is_tracked(o):
            continue
        seen.add(id(o))
        n += 1
        todo.extend(gc.get_referents(o))
    return n


def _copy_objects(view) -> int:
    """Tracked objects a copy of `view` creates."""
    gc.collect()
    before = len(gc.get_objects())
    c = view.copy()
    gc.collect()
    made = len(gc.get_objects()) - before
    del c
    return made


def test_registry_object_count():
    """A tracked registry element holds no more tracked objects than
    with the mode off, plus one; a state copy makes no more per element
    than off's copy, plus one."""
    n = 2048
    r = random_registry(Random("objects"), n=n)
    elems = r.records._elems
    off = _tracked_objects(elems, r.records) / n
    off_copy = _copy_objects(r) / n
    incremental.enable()
    incremental.track(r)
    r.hash_tree_root()
    assert METRICS.count("merkle_flat_roots") >= n
    on = _tracked_objects(elems, r.records) / n
    on_copy = _copy_objects(r) / n
    assert on <= off + 1, (on, off)
    assert on_copy <= off_copy + 1, (on_copy, off_copy)


# ---------------------------------------------------------------------------
# resilience: faulted sweep site, breaker, guard
# ---------------------------------------------------------------------------

def _tracked_state_with_dirt(rng, kind="zoo"):
    make, mutate = KINDS[kind]
    z = incremental.track(make(rng))
    z.hash_tree_root()
    for _ in range(5):
        mutate(rng, z)
    return z


@kinds
def test_sweep_site_raise_falls_back_to_full_rebuild(kind):
    mutate = KINDS[kind][1]
    incremental.enable()
    resilience.enable(max_retries=0, breaker_threshold=1, probe_after=1000)
    rng = Random("fault-raise")
    z = _tracked_state_with_dirt(rng, kind)
    plan = FaultPlan([FaultSpec("ssz.merkle_sweep", "raise",
                                persistent=True)], seed=7)
    with faults.inject(plan):
        root = bytes(z.hash_tree_root())
        assert root == oracle(z)       # degraded, byte-identical
        sup = resilience.active()
        assert sup.breaker_state("ssz.merkle_sweep") == OPEN
        assert METRICS.count("merkle_full_rebuilds") >= 1
        # breaker open: further re-roots keep answering correctly
        mutate(rng, z)
        assert bytes(z.hash_tree_root()) == oracle(z)
    assert plan.total_fires() >= 1
    # dirty marks survived the degraded period: once the site heals,
    # the sweep resumes incrementally and stays byte-identical
    resilience.disable()
    mutate(rng, z)
    assert bytes(z.hash_tree_root()) == oracle(z)


def test_abandoned_sweep_never_writes_caches(monkeypatch):
    """Watchdog-abandonment race: a timed-out sweep keeps running on the
    abandoned worker thread after the block thread has taken the
    fallback root and resumed mutating.  The dispatched device fn must
    be pure — running it arbitrarily late must not write a cache level
    or clear a dirty mark made in the meantime (a cleared mark would
    make the next hash_tree_root serve a stale cached root)."""
    incremental.enable()
    rng = Random("zombie")
    z = _tracked_state_with_dirt(rng)

    captured = []

    def timed_out_dispatch(site, device, fallback):
        # deadline expired: the caller gets the fallback answer while
        # the device fn lives on (returned to the test = the zombie)
        captured.append(device)
        return fallback()

    monkeypatch.setattr(incremental, "_dispatch", timed_out_dispatch)
    assert bytes(z.hash_tree_root()) == oracle(z)   # degraded root
    monkeypatch.undo()
    assert len(captured) == 1

    # block thread resumes and dirties a leaf the zombie's plan covered
    for _ in range(3):
        _mutate_once(rng, z)
    cache = z.__dict__["_mcache"]
    dirty_before = set(cache.dirty)
    assert dirty_before and cache.root is None
    captured[0]()   # the abandoned worker finishes its sweep late
    # late completion wrote nothing: dirty marks and the invalidated
    # root are exactly as the block thread left them, and the next
    # (real) sweep answers the post-mutation root, not a stale one
    assert cache.dirty == dirty_before and cache.root is None
    assert bytes(z.hash_tree_root()) == oracle(z)
    _mutate_once(rng, z)
    assert bytes(z.hash_tree_root()) == oracle(z)


@kinds
def test_sweep_corruption_caught_by_guard_and_quarantined(kind):
    mutate = KINDS[kind][1]
    incremental.enable(guard_sample_rate=1.0, guard_seed=11)
    resilience.enable(max_retries=0, breaker_threshold=3)
    rng = Random("fault-corrupt")
    z = _tracked_state_with_dirt(rng, kind)
    plan = FaultPlan([FaultSpec("ssz.merkle_sweep", "corrupt",
                                persistent=True)], seed=13)
    with faults.inject(plan):
        root = bytes(z.hash_tree_root())
    # the guard re-derived the root from the oracle: the verdict the
    # caller sees is never the corrupted one
    assert root == oracle(z)
    assert METRICS.count("merkle_guard_mismatches") >= 1
    sup = resilience.active()
    assert sup.breaker_state("ssz.merkle_sweep") == QUARANTINED
    # quarantine dropped the caches: the view is untracked now, so
    # re-roots take the legacy full path (no further sweep dispatches)
    # and keep answering correctly
    dispatches = METRICS.count("merkle_sweep_dispatches")
    mutate(rng, z)
    assert bytes(z.hash_tree_root()) == oracle(z)
    assert METRICS.count("merkle_sweep_dispatches") == dispatches
    # a re-tracked state behind the quarantined site degrades to the
    # full-rebuild fallback (counted), never to a wrong root
    incremental.track(z)
    assert bytes(z.hash_tree_root()) == oracle(z)
    assert METRICS.count("merkle_full_rebuilds") >= 1


@kinds
def test_guard_passes_clean_sweeps(kind):
    incremental.enable(guard_sample_rate=1.0, guard_seed=3)
    rng = Random("guard-clean")
    z = _tracked_state_with_dirt(rng, kind)
    assert bytes(z.hash_tree_root()) == oracle(z)
    assert METRICS.count("merkle_guard_samples") >= 1
    assert METRICS.count("merkle_guard_mismatches") == 0


# ---------------------------------------------------------------------------
# spec integration: process_slots / state_transition consume the cache
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def spec():
    return get_spec("altair", "minimal")


@pytest.fixture(scope="module")
def workload(spec):
    with disable_bls():
        state = create_genesis_state(spec, default_balances(spec))
        spec.process_slots(state, uint64(spec.SLOTS_PER_EPOCH + 2))
        att = get_valid_attestation(spec, state, signed=True)
        advanced = state.copy()
        spec.process_slots(
            advanced,
            uint64(state.slot + spec.MIN_ATTESTATION_INCLUSION_DELAY))
        block = build_empty_block_for_next_slot(spec, advanced)
        block.body.attestations.append(att)
        scratch = advanced.copy()
        signed = state_transition_and_sign_block(spec, scratch, block)
    return advanced, signed


def test_state_transition_incremental_parity(spec, workload):
    advanced, signed = workload
    with disable_bls():
        legacy = advanced.copy()
        spec.state_transition(legacy, signed)
        legacy_root = bytes(hash_tree_root(legacy))

        incremental.enable()
        st = advanced.copy()
        spec.state_transition(st, signed)
        cached_root = bytes(st.hash_tree_root())
        assert METRICS.count("merkle_sweep_dispatches") >= 1
        incremental.disable()
        # final comparison on the legacy path: truly independent bytes
        assert bytes(hash_tree_root(st)) == cached_root == legacy_root


def test_process_slots_epoch_boundary_parity(spec, workload):
    advanced, _ = workload
    with disable_bls():
        target = uint64(advanced.slot + 2 * spec.SLOTS_PER_EPOCH)
        legacy = advanced.copy()
        spec.process_slots(legacy, target)

        incremental.enable()
        st = advanced.copy()
        spec.process_slots(st, target)
        incremental.disable()
        assert bytes(hash_tree_root(st)) == bytes(hash_tree_root(legacy))


def test_per_slot_sweep_is_diff_sized(spec, workload):
    """Steady-state slot processing re-hashes the diff, not the state:
    after the first build, each process_slot's sweep touches far fewer
    chunks than the build did, within the height-derived bound."""
    advanced, _ = workload
    with disable_bls():
        incremental.enable()
        st = advanced.copy()
        incremental.track(st)
        st.hash_tree_root()
        built = METRICS.count("merkle_chunks_hashed")
        height = incremental.type_tree_height(type(st))
        METRICS.reset()
        spec.process_slots(st, uint64(advanced.slot + 1))
        assert bytes(st.hash_tree_root()) == incremental.oracle_root(st)
        # process_slot dirties a handful of leaves (state_roots,
        # block_roots, latest_block_header, slot): O(k · height)
        assert 0 < METRICS.count("merkle_chunks_hashed") <= 8 * height
        assert METRICS.count("merkle_chunks_hashed") < built // 4
        assert METRICS.count("merkle_full_rebuilds") == 0
        incremental.disable()


# ---------------------------------------------------------------------------
# ZERO_HASHES: one ladder, one source of truth
# ---------------------------------------------------------------------------

def test_zero_hash_ladder_shared():
    from consensus_specs_tpu.ssz import merkle, proofs
    from deposit_contract import contract_model
    assert proofs.ZERO_HASHES is merkle.ZERO_HASHES
    assert contract_model.ZERO_HASHES == \
        merkle.ZERO_HASHES[:contract_model.TREE_DEPTH]
    # the ladder is what it claims: ZERO_HASHES[i+1] = H(Z[i] || Z[i])
    for i in range(8):
        assert merkle.ZERO_HASHES[i + 1] == merkle.hash_pair(
            merkle.ZERO_HASHES[i], merkle.ZERO_HASHES[i])
