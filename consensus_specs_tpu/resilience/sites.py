"""Canonical registry of every resilience seam in the repo.

Seven PRs grew the safety story on cross-cutting conventions — every
accelerator entry point behind ``dispatch(site, device_fn,
fallback_fn)``, every transactional barrier behind ``faults.fire`` —
but the site NAMES lived as scattered string literals, and the chaos
tier, the differential guard, and the fault injector each hand-
maintained their own drift-prone tuples of them.  This module is the
single source of truth those consumers now derive from:

* ``tests/test_chaos.py`` builds ``SITES`` / ``GOSSIP_SITES`` /
  ``KILL_SITES`` from :func:`chaos_replay_sites`,
  :func:`chaos_gossip_sites`, :func:`kill_sites`.
* ``resilience/guard.py`` builds ``FUSED_SITES`` (the quarantine unit)
  from :func:`fused_sites`.
* ``resilience/faults.py`` builds ``_DIGEST_GUARDED_SITES`` (which
  results bytes-corruption may target) from
  :func:`digest_guarded_sites`.
* ``speclint`` (``consensus_specs_tpu/analysis/``) machine-checks every
  ``dispatch(...)`` / ``fire(...)`` / ``FaultSpec(...)`` site argument,
  the docs/resilience.md site table, and the chaos reachability policy
  against this registry — an unregistered site name fails CI.

Registering a new seam means adding ONE :class:`Site` entry here (and a
row in docs/resilience.md); speclint then enforces that the call site,
the chaos tier, and the docs all agree.  See docs/analysis.md.

This module deliberately imports nothing from the package (stdlib
only), mirroring utils/nodectx.py: the cycle-sensitive wrapper modules
(utils/bls.py, ssz/merkle.py, ssz/incremental.py) keep their lazy-
import discipline and use validated string literals instead, while
everything that CAN import it at module scope (txn/, guard, faults,
tests) derives.  speclint loads it standalone by file path, so linting
never imports jax or the heavy packages.
"""
from __future__ import annotations

from dataclasses import dataclass

# site kinds
DISPATCH = "dispatch"   # a resilience.dispatch(site, device_fn, fallback_fn)
BARRIER = "barrier"     # a faults.fire(site) crash point (no value to corrupt)

# chaos tiers — where the chaos tier reaches the site from
REPLAY = "replay"   # native-backend sanity replay (test_chaos SITES)
GOSSIP = "gossip"   # gossip admission tier extra (GOSSIP_SITES adds these)
KILL = "kill"       # transactional crash points (KILL_SITES)
UNIT = "unit"       # unreachable from a CPU chaos replay; unit-tier covered
                    # (entries must say where in `note`)

_KINDS = (DISPATCH, BARRIER)
_TIERS = (REPLAY, GOSSIP, KILL, UNIT)
_CORRUPT = ("verdict", "digest", "lanes", "none")


@dataclass(frozen=True)
class Site:
    """One registered seam.

    name     — the canonical dotted site string passed to dispatch/fire.
    module   — the wrapper module that owns the seam (the only module,
               besides registered kernel-layer ones, allowed to import
               device kernels directly — speclint's bypass pass).
    kind     — DISPATCH or BARRIER.
    chaos    — which chaos tier exercises it (REPLAY/GOSSIP/KILL/UNIT).
    corrupt  — what the fault injector's "corrupt" kind may flip:
               "verdict" (bool/bool-list), "digest" (one bit of a bytes
               root — only sites a differential oracle guards), "lanes"
               (one element of one numpy lane array in a tuple result —
               again only oracle-guarded sites), "none" (barriers: a
               crash point has no value).
    fused    — verdicts flow through the fused signature pipeline; the
               differential guard quarantines all fused sites as a unit.
    sharded  — the device path may run mesh-partitioned over >1 chip
               (parallel/shard_verify.py): the `shard_dead` fault kind
               models a dead mesh member at exactly these seams, and
               the chaos tier's shard matrix derives from this flag.
    doc      — the document whose site table must list the name.
    note     — required for UNIT tier: where coverage lives instead.
    """

    name: str
    module: str
    kind: str = DISPATCH
    chaos: str = UNIT
    corrupt: str = "verdict"
    fused: bool = False
    sharded: bool = False
    doc: str = "docs/resilience.md"
    note: str = ""


# Declaration order is contractual: the chaos tuples derive from it, so
# seeded randomized fault schedules draw sites in this order.
REGISTRY: tuple[Site, ...] = (
    # -- replay tier: every native-backend sanity replay crosses these
    Site("bls.pairing_check", "consensus_specs_tpu.utils.bls",
         kind=DISPATCH, chaos=REPLAY, fused=True),
    Site("bls.verify_batch", "consensus_specs_tpu.utils.bls",
         kind=DISPATCH, chaos=REPLAY, fused=True),
    Site("bls.fast_aggregate_verify_batch", "consensus_specs_tpu.utils.bls",
         kind=DISPATCH, chaos=REPLAY, fused=True),
    Site("ops.g1_aggregate", "consensus_specs_tpu.sigpipe.cache",
         kind=DISPATCH, chaos=REPLAY, sharded=True),
    Site("ops.msm", "consensus_specs_tpu.sigpipe.scheduler",
         kind=DISPATCH, chaos=REPLAY, sharded=True),
    # the folded signature-leg seam (sigpipe/fold.py): the G2 MSM that
    # collapses every e(-c_i*g1, sig_i) leg into ONE e(-g1, S) pair —
    # and, on the tpu backend's one-launch path, the whole fused flush
    # program per shard.  REPLAY tier: folding is on by default, so
    # every native-backend fused replay crosses it (FOLD_VERIFY=0 is
    # the escape hatch back to the 2N-leg flush)
    Site("ops.pairing_fold", "consensus_specs_tpu.sigpipe.fold",
         kind=DISPATCH, chaos=REPLAY, fused=True, sharded=True),
    Site("ssz.merkle_sweep", "consensus_specs_tpu.ssz.incremental",
         kind=DISPATCH, chaos=REPLAY, corrupt="digest"),
    # the fused epoch sweep (ops/epoch_sweep.py behind specs/
    # epoch_fast.fused_epoch): ONE dispatch per process_epoch carrying
    # every hot per-validator pass; numpy twin as the counted
    # byte-identical fallback, sampled lane guard quarantines on
    # mismatch.  REPLAY tier — any replay crossing an epoch boundary
    # dispatches here (the block-level replay workload does not, so the
    # shard matrix and fault kinds run in the dedicated epoch-boundary
    # chaos matrix; see tests/test_chaos.py).  sharded: the validator
    # axis partitions over the verify mesh via shard_jobs.
    Site("ops.epoch_sweep", "consensus_specs_tpu.specs.epoch_fast",
         kind=DISPATCH, chaos=REPLAY, corrupt="lanes", sharded=True),
    # -- gossip tier extra: the admission pipeline's batch window
    Site("gossip.batch_verify", "consensus_specs_tpu.gossip.batcher",
         kind=DISPATCH, chaos=GOSSIP),
    # -- transactional crash points (KILL_SITES order is contractual)
    Site("txn.mutate", "consensus_specs_tpu.txn.overlay",
         kind=BARRIER, chaos=KILL, corrupt="none"),
    Site("txn.commit", "consensus_specs_tpu.txn",
         kind=DISPATCH, chaos=KILL, corrupt="none"),
    Site("txn.commit.apply", "consensus_specs_tpu.txn.overlay",
         kind=BARRIER, chaos=KILL, corrupt="none"),
    Site("txn.journal", "consensus_specs_tpu.txn.journal",
         kind=BARRIER, chaos=KILL, corrupt="none"),
    # the durable journal's mid-fsync crash window: record bytes are
    # written (page cache) but not yet durable when this fires — the
    # chaos crash-anywhere tier drives it through a DurableJournal, and
    # scripts/kill_drill.py SIGKILLs a real subprocess at it
    Site("txn.journal.fsync", "consensus_specs_tpu.txn.durable",
         kind=BARRIER, chaos=KILL, corrupt="none"),
    # -- unit tier: tpu-backend-only seams a CPU chaos replay never
    #    crosses; each names its covering unit suite
    Site("bls.verify", "consensus_specs_tpu.utils.bls",
         kind=DISPATCH, chaos=UNIT,
         note="tpu-backend scalar seam; tests/test_resilience.py + "
              "tests/test_bls_tpu.py"),
    Site("bls.aggregate_verify", "consensus_specs_tpu.utils.bls",
         kind=DISPATCH, chaos=UNIT,
         note="tpu-backend scalar seam; tests/test_resilience.py + "
              "tests/test_bls_tpu.py"),
    Site("bls.fast_aggregate_verify", "consensus_specs_tpu.utils.bls",
         kind=DISPATCH, chaos=UNIT,
         note="tpu-backend scalar seam; tests/test_resilience.py + "
              "tests/test_bls_tpu.py"),
    # fused (the guard quarantines it with its sibling batch seams) but
    # NOT replay-tier: no node-runtime path calls AggregateVerifyBatch
    # today — the scheduler's per-set mode rides FastAggregateVerifyBatch
    # — so a chaos FaultSpec here would never fire and the tuple entry
    # would claim coverage it does not deliver
    Site("bls.aggregate_verify_batch", "consensus_specs_tpu.utils.bls",
         kind=DISPATCH, chaos=UNIT, fused=True,
         note="batch API surface with no runtime caller yet; "
              "tests/test_bls_tpu.py + tests/test_sigpipe.py"),
    # sharded since the async-flush PR: the padded message axis of the
    # cofactor sweep partitions over the verify mesh via shard_jobs —
    # the last unsharded per-flush device call
    Site("sigpipe.hash_to_g2_batch", "consensus_specs_tpu.sigpipe.scheduler",
         kind=DISPATCH, chaos=UNIT, fused=True, sharded=True,
         note="tpu-backend cofactor sweep; tests/test_resilience.py + "
              "tests/test_shard_verify.py (kernel tier)"),
    # the mesh-sharded fused pairing product: engages only when the
    # verify mesh has >1 device AND the tpu backend is active, which a
    # native-backend CPU chaos replay never is — the sharded sweeps at
    # ops.g1_aggregate / ops.msm (replay tier, sharded=True) carry the
    # shard_dead chaos matrix instead
    Site("ops.pairing_product", "consensus_specs_tpu.parallel.shard_verify",
         kind=DISPATCH, chaos=UNIT, fused=True, sharded=True,
         note="mesh-sharded pairing product (tpu backend + >1-device "
              "mesh only); tests/test_shard_verify.py (kernel tier) + "
              "tests/test_resilience.py shard_dead unit suite"),
    Site("ops.msm.g1", "consensus_specs_tpu.utils.bls",
         kind=DISPATCH, chaos=UNIT,
         note="threshold-gated device MSM; tests/test_msm_pippenger.py"),
    Site("ops.msm.g2", "consensus_specs_tpu.utils.bls",
         kind=DISPATCH, chaos=UNIT,
         note="threshold-gated device MSM; tests/test_msm_pippenger.py"),
    Site("ops.msm.kzg", "consensus_specs_tpu.crypto.kzg",
         kind=DISPATCH, chaos=UNIT,
         note="threshold-gated device MSM; tests/test_kzg.py"),
    Site("ops.sha256.hash_level", "consensus_specs_tpu.ssz.merkle",
         kind=DISPATCH, chaos=UNIT,
         note="install-gated bulk hasher; tests/test_sha256_jax.py + "
              "tests/test_merkle_sweep_jax.py"),
    Site("ops.sha256.subtree", "consensus_specs_tpu.ssz.merkle",
         kind=DISPATCH, chaos=UNIT,
         note="install-gated subtree hasher; tests/test_sha256_jax.py"),
    # -- vector-factory barrier kill points: the generation service's
    #    durable progress journal and content-addressed artifact store
    #    (factory/).  UNIT tier — the chaos replay tier drives txn
    #    stores, not generation shards; coverage is the process-boundary
    #    SIGKILL drill (scripts/factory_drill.py, `make factory-drill`)
    #    plus the in-process crash suite.  Family order here is the
    #    drill's matrix order.
    Site("factory.journal", "consensus_specs_tpu.factory.journal",
         kind=BARRIER, chaos=UNIT, corrupt="none",
         note="mid-journal-record-write kill point; "
              "scripts/factory_drill.py + tests/test_factory.py"),
    Site("factory.journal.fsync", "consensus_specs_tpu.factory.journal",
         kind=BARRIER, chaos=UNIT, corrupt="none",
         note="the factory journal's written-but-not-yet-durable "
              "window; scripts/factory_drill.py + tests/test_factory.py"),
    Site("factory.publish", "consensus_specs_tpu.factory.artifacts",
         kind=BARRIER, chaos=UNIT, corrupt="none",
         note="between an artifact's staged tmp write and its atomic "
              "rename into the content-addressed store; "
              "scripts/factory_drill.py + tests/test_factory.py"),
    Site("factory.manifest", "consensus_specs_tpu.factory.artifacts",
         kind=BARRIER, chaos=UNIT, corrupt="none",
         note="before the manifest's atomic replace; "
              "scripts/factory_drill.py + tests/test_factory.py"),
    # -- front-door barrier kill points: the long-lived node process's
    #    serving path (node/).  UNIT tier — coverage is the
    #    process-boundary SIGKILL drill through the real socket
    #    (scripts/node_drill.py, `make node-drill`) plus the
    #    in-process codec/drain tests.
    Site("node.ingest", "consensus_specs_tpu.node.service",
         kind=BARRIER, chaos=UNIT, corrupt="none",
         note="before each socket message's pipeline submit; "
              "scripts/node_drill.py + tests/test_node.py"),
    Site("node.drain", "consensus_specs_tpu.node.service",
         kind=BARRIER, chaos=UNIT, corrupt="none",
         note="inside graceful drain, after accepts stop and before "
              "the flush/fsync; scripts/node_drill.py + "
              "tests/test_node.py"),
    # -- mesh: real peer-to-peer socket traffic (mesh/).  UNIT tier —
    #    coverage is the multi-process drill over the scenario
    #    library's partition/kill timelines (scripts/mesh_drill.py,
    #    `make mesh-drill`) plus the link-layer unit suite.
    # speclint: disable=site-unused -- the link worker consults
    # plan.decide(site) directly: a corrupt spec must damage the
    # in-flight FRAME bytes (there is no verdict or return value at
    # this seam), which the dispatch/fire grammar cannot express
    Site("mesh.link", "consensus_specs_tpu.mesh.link",
         kind=DISPATCH, chaos=UNIT, corrupt="none",
         note="per-send link fault consult: raise = frame + connection "
              "lost, timeout = wire stall, corrupt = one on-wire bit "
              "flip the RECEIVER's CRC sheds (the link applies the "
              "damage itself — no verdict to flip, so corrupt='none'); "
              "scripts/mesh_drill.py + tests/test_mesh.py"),
    Site("mesh.send", "consensus_specs_tpu.mesh.link",
         kind=BARRIER, chaos=UNIT, corrupt="none",
         note="before each link sendall — the drill's kill/shed point "
              "on the outbound hop; scripts/mesh_drill.py + "
              "tests/test_mesh.py"),
    Site("mesh.recv", "consensus_specs_tpu.mesh.service",
         kind=BARRIER, chaos=UNIT, corrupt="none",
         note="before a peer-forwarded message's admission — the "
              "drill's kill/shed point on the inbound hop; "
              "scripts/mesh_drill.py + tests/test_mesh.py"),
    Site("mesh.join", "consensus_specs_tpu.mesh.service",
         kind=BARRIER, chaos=UNIT, corrupt="none",
         note="before a JOIN frame mutates the peer table — the "
              "churn drill's kill/shed point on dynamic admission; "
              "scripts/mesh_drill.py + tests/test_mesh.py"),
    Site("mesh.leave", "consensus_specs_tpu.mesh.service",
         kind=BARRIER, chaos=UNIT, corrupt="none",
         note="before a LEAVE frame drains a member's link out — the "
              "churn drill's kill/shed point on graceful departure; "
              "scripts/mesh_drill.py + tests/test_mesh.py"),
)

# speclint: disable=global-mutable-state -- name index over the frozen
# REGISTRY tuple, built once at import and read-only afterwards
SITES: dict[str, Site] = {s.name: s for s in REGISTRY}

if len(SITES) != len(REGISTRY):
    raise RuntimeError("duplicate site name in resilience.sites.REGISTRY")
for _s in REGISTRY:
    if _s.kind not in _KINDS:
        raise RuntimeError(f"{_s.name}: bad kind {_s.kind!r}")
    if _s.chaos not in _TIERS:
        raise RuntimeError(f"{_s.name}: bad chaos tier {_s.chaos!r}")
    if _s.corrupt not in _CORRUPT:
        raise RuntimeError(f"{_s.name}: bad corrupt class {_s.corrupt!r}")
    if _s.chaos == UNIT and not _s.note:
        raise RuntimeError(
            f"{_s.name}: UNIT-tier sites must say where coverage lives")


def site(name: str) -> Site:
    """Look up one registered site; KeyError on unregistered names."""
    return SITES[name]


def is_registered(name: str) -> bool:
    return name in SITES


def names() -> tuple[str, ...]:
    return tuple(s.name for s in REGISTRY)


def chaos_replay_sites() -> tuple[str, ...]:
    """test_chaos.py SITES: seams a native-backend sanity replay crosses."""
    return tuple(s.name for s in REGISTRY if s.chaos == REPLAY)


def chaos_gossip_sites() -> tuple[str, ...]:
    """test_chaos.py GOSSIP_SITES: the replay tier plus the admission
    pipeline's own seams."""
    return chaos_replay_sites() + tuple(
        s.name for s in REGISTRY if s.chaos == GOSSIP)


def kill_sites() -> tuple[str, ...]:
    """test_chaos.py KILL_SITES: every transactional crash-point family."""
    return tuple(s.name for s in REGISTRY if s.chaos == KILL)


def fused_sites() -> tuple[str, ...]:
    """guard.py FUSED_SITES: quarantined as a unit on a guard mismatch."""
    return tuple(s.name for s in REGISTRY if s.fused)


def digest_guarded_sites() -> frozenset[str]:
    """faults.py _DIGEST_GUARDED_SITES: bytes-root results the corrupt
    fault kind may bit-flip (a differential oracle guards them)."""
    return frozenset(s.name for s in REGISTRY if s.corrupt == "digest")


def lanes_guarded_sites() -> frozenset[str]:
    """faults.py _LANES_GUARDED_SITES: tuple-of-numpy-lane results the
    corrupt fault kind may damage by one element (a differential oracle
    guards them)."""
    return frozenset(s.name for s in REGISTRY if s.corrupt == "lanes")


def sharded_sites() -> tuple[str, ...]:
    """Seams whose device path may run mesh-partitioned
    (parallel/shard_verify.py): the shard_dead fault kind models a dead
    mesh member here, and test_chaos.py's shard matrix derives from
    this tuple (intersected with the replay tier — the sharded pairing
    product itself is tpu-backend-only and unit-covered)."""
    return tuple(s.name for s in REGISTRY if s.sharded)


def wrapper_modules() -> frozenset[str]:
    """Modules that own a seam — allowed to import device kernels."""
    return frozenset(s.module for s in REGISTRY)


# ---------------------------------------------------------------------------
# declared host-sync join barriers (speclint async-host-sync pass)
# ---------------------------------------------------------------------------
# The async flush engine's contract is that device dispatches stay
# un-forced until a DECLARED join barrier: a host-sync primitive
# (`jax.device_get`, `.block_until_ready()`, `np.asarray` on a device
# value) anywhere else in sigpipe/ssz/parallel silently re-serializes
# the pipeline.  Each entry is (module, function) naming a function
# whose body IS a declared barrier — the verdict joins and result
# downloads the pipeline design blesses.  speclint's hostsync pass
# (analysis/hostsync.py) flags any sync primitive outside this table;
# adding a new barrier means adding a row HERE (and saying why in the
# function's docstring), not sprinkling a disable.
HOST_SYNC_BARRIERS: tuple = (
    # the sharded pairing product's verdict join: pack + upload + ONE
    # np.asarray of the final Fp12-is-one verdict per flush
    ("consensus_specs_tpu.parallel.shard_verify",
     "_device_pairing_product"),
    # the one-launch folded flush's verdict join: one compiled program
    # per shard, then ONE np.asarray of the final Fp12-is-one verdict
    ("consensus_specs_tpu.parallel.shard_verify", "pairing_fold"),
    # mesh-engine result downloads: each is the single forced read at
    # the end of one fused device dispatch (the per-pass epoch rows —
    # flag_set_batch / slashings_batch — retired into ops.epoch_sweep)
    ("consensus_specs_tpu.parallel.mesh_engine", "subtree_root"),
    ("consensus_specs_tpu.parallel.mesh_engine", "g1_msm"),
    # the fused epoch sweep's single download: ONE jax.device_get of
    # every output lane per process_epoch
    ("consensus_specs_tpu.ops.epoch_sweep", "run_sweep"),
)


# ---------------------------------------------------------------------------
# the concurrency registry (speclint lock-discipline / lock-order /
# thread-escape passes + the SPECLINT_TSAN runtime lock tracer)
# ---------------------------------------------------------------------------
# PR 11 made the hot path genuinely multi-threaded; the overlap
# contracts (single-drainer delivery, ticket-joined verdicts,
# abandoned-flush write suppression) were until now enforced only by
# tests that happen to race.  This registry applies the same
# declare-once discipline as the seam table above to threads and locks:
#
# * every named lock is declared HERE (name -> owning module/class,
#   attribute, kind, the attribute set it guards) and constructed in
#   code via ``utils/locks.py`` ``named_lock``/``named_rlock``/
#   ``named_condition`` with its registry name — speclint's
#   lock-discipline pass fails on a bare ``threading.Lock()`` in the
#   concurrency-scoped packages, and with ``SPECLINT_TSAN=1`` the
#   named constructors return traced wrappers so the runtime sanitizer
#   can compare observed acquisition orders against the static graph.
# * every thread ROLE (who may run which entry point) is declared so
#   the thread-escape pass can check that state mutated from a worker
#   is lock-guarded or reaches the worker through a registered handoff.
# * every legal cross-thread HANDOFF object is declared; anything else
#   crossing a thread boundary is a finding.

@dataclass(frozen=True)
class LockSpec:
    """One named lock.

    name    — canonical dotted name (what named_lock(...) is called with
              and what the tracer reports).
    module  — the owning module; the lock-discipline pass checks guarded
              attributes only inside it (cross-module access to guarded
              state is a bug by construction: the attrs are private).
    attr    — the attribute / module global the lock object binds to.
    cls     — owning class ("" = module-level global); disambiguates
              modules holding several ``_lock`` attributes.
    kind    — "lock" | "rlock" | "condition".  A static self-edge on a
              plain "lock" is a self-deadlock finding; on an rlock or
              condition it is legal reentrancy.
    guards  — attribute / global names that may be read or written only
              under this lock (lexically or via the under-lock call
              closure).  Guarding is a claim the pass ENFORCES — list
              only what really holds, and record the deliberate
              exceptions with reasoned disables at the access site.
    note    — why the guard set is shaped the way it is (e.g. which
              state is serialized by a role discipline instead).
    """

    name: str
    module: str
    attr: str
    cls: str = ""
    kind: str = "rlock"
    guards: tuple = ()
    note: str = ""


@dataclass(frozen=True)
class ThreadRole:
    """One thread role: who may run which entry point.

    func is the role's entry point ("Class.method" or a module-level
    function); "" marks the implicit role of the default thread.  The
    thread-escape pass analyzes mutations reachable from the entry
    point inside its own module — cross-module work a worker performs
    is covered by the lock-discipline pass and the runtime tracer.
    """

    name: str
    module: str = ""
    func: str = ""
    note: str = ""


@dataclass(frozen=True)
class Handoff:
    """One sanctioned cross-thread handoff object: state may legally
    cross a thread boundary only as (or through) one of these."""

    name: str
    module: str
    attr: str
    note: str = ""


@dataclass(frozen=True)
class Concurrency:
    locks: tuple
    roles: tuple
    handoffs: tuple

    def lock_names(self) -> tuple:
        return tuple(spec.name for spec in self.locks)


_PA = "consensus_specs_tpu.sigpipe.pipeline_async"
_GP = "consensus_specs_tpu.gossip.pipeline"
_NS = "consensus_specs_tpu.node.service"
_NI = "consensus_specs_tpu.node.ingest"
_ML = "consensus_specs_tpu.mesh.link"
_MS = "consensus_specs_tpu.mesh.service"

CONCURRENCY = Concurrency(
    locks=(
        # -- sigpipe: the async flush engine ---------------------------
        LockSpec("sigpipe.engine", _PA, "_ENGINE_LOCK", kind="lock",
                 guards=("_FLUSH_WORKER", "_LEG_WORKER"),
                 note="worker singletons: creation and respawn checks"),
        LockSpec("sigpipe.ticket", _PA, "_lock", cls="FlushTicket",
                 kind="lock", guards=("_state", "_value", "_error"),
                 note="ticket outcome; _done Event is the join handoff"),
        LockSpec("sigpipe.worker_cv", _PA, "_cv", cls="_Worker",
                 kind="condition", guards=("_pending",),
                 note="queued+running job count; drain() waits on it"),
        LockSpec("sigpipe.pubkey_cache",
                 "consensus_specs_tpu.sigpipe.cache", "_lock",
                 cls="PubkeyCache", guards=("_cache",)),
        LockSpec("sigpipe.aggregate_cache",
                 "consensus_specs_tpu.sigpipe.cache", "_lock",
                 cls="AggregatePubkeyCache",
                 guards=("_cache", "_track_stack")),
        LockSpec("sigpipe.metrics",
                 "consensus_specs_tpu.sigpipe.metrics", "_lock",
                 cls="Metrics",
                 guards=("counters", "labeled", "observations",
                         "histograms")),
        # -- gossip: ingress vs the single drainer ---------------------
        LockSpec("gossip.ingress", _GP, "_ingress_lock",
                 cls="AdmissionPipeline",
                 guards=("_seq", "seen", "results", "queues", "quotas",
                         "batcher", "_finalized_order"),
                 note="admission state; order: drainer may take "
                      "ingress, never the reverse"),
        LockSpec("gossip.drainer", _GP, "_drainer_lock",
                 cls="AdmissionPipeline", kind="lock",
                 guards=("delivered_log", "guard"),
                 note="single-drainer discipline: whoever holds it owns "
                      "flushing, handler delivery, and the equivocation "
                      "guard; the store itself is serialized by it"),
        # -- txn -------------------------------------------------------
        LockSpec("txn.active", "consensus_specs_tpu.txn", "_lock",
                 guards=("_ACTIVE",),
                 note="manager installs; hot-path reads of the single "
                      "reference are atomic under the GIL and carry "
                      "reasoned disables in place"),
        LockSpec("txn.journal", "consensus_specs_tpu.txn.journal",
                 "_lock", cls="Journal",
                 guards=("_entries", "_snapshots", "_seq")),
        LockSpec("txn.durable.io", "consensus_specs_tpu.txn.durable",
                 "_io", cls="DurableJournal",
                 guards=("_seg_fh", "_seg_index", "_seg_written",
                         "_seg_max_seq", "_closed_segments",
                         "_raw_entries", "_raw_snaps", "_scanned_snaps",
                         "_dirty"),
                 note="segment file handle + rotation/compaction "
                      "bookkeeping and the raw records loaded by "
                      "open_dir; ordered after txn.journal (the entry "
                      "book) — durable methods append in memory first, "
                      "then persist under this lock"),
        # -- resilience ------------------------------------------------
        LockSpec("resilience.supervisor",
                 "consensus_specs_tpu.resilience.supervisor", "_lock",
                 cls="Supervisor",
                 guards=("_breakers", "_workers", "_worker_locks")),
        LockSpec("resilience.site_worker",
                 "consensus_specs_tpu.resilience.supervisor",
                 "site_lock", cls="Supervisor", kind="lock",
                 note="per-site watchdog serialization: a job is handed "
                      "to the site worker only while holding it"),
        LockSpec("resilience.incidents",
                 "consensus_specs_tpu.resilience.incidents", "_lock",
                 cls="IncidentLog", guards=("_entries", "_seq")),
        LockSpec("resilience.faults",
                 "consensus_specs_tpu.resilience.faults", "_lock",
                 cls="FaultPlan", guards=("_rng",),
                 note="seeded decision stream: every draw must be "
                      "serialized or replay determinism dies; specs/"
                      "_by_site are frozen after __init__"),
        LockSpec("resilience.guard",
                 "consensus_specs_tpu.resilience.guard", "_lock",
                 cls="DifferentialGuard", guards=("_rng",)),
        # -- ops (outside the static pass scope; registered for the
        # runtime tracer and the dead-entry check) ---------------------
        LockSpec("ops.sha256.pool", "consensus_specs_tpu.ops.sha256",
                 "_POOL_LOCK", kind="lock",
                 guards=(),
                 note="device-resident merkle literal pool "
                      "(_LIT_POOL/_LIT_INDEX/_LIT_USED): an abandoned "
                      "watchdog sweep may still be inserting while the "
                      "block thread starts the next sweep; the jitted "
                      "program runs on an immutable snapshot outside "
                      "the lock.  ops is outside the lock-discipline "
                      "pass scope, so the guard set is enforced by "
                      "review + the TSAN tracer, not listed here"),
        # -- node: the front-door process ------------------------------
        LockSpec("node.ingest", _NS, "_cond", cls="NodeService",
                 kind="condition",
                 guards=("_queue", "_shed_overload", "_shed_draining"),
                 note="the bounded ingest queue (conn readers push, "
                      "the pump pops) + overload counters; submits and "
                      "verdict work happen OUTSIDE it on the pump"),
        LockSpec("node.state", _NS, "_state_lock", cls="NodeService",
                 kind="lock",
                 guards=("_inflight", "_latencies", "_degraded"),
                 note="pump-side verdict bookkeeping, read by health() "
                      "from conn threads; never nested with node.ingest"),
        LockSpec("node.conn", _NI, "_send_lock", cls="_Connection",
                 kind="lock", guards=(),
                 note="per-connection response writes (pump, conn "
                      "reader, and evictions all answer on the same "
                      "socket); sendall is the only guarded effect"),
        LockSpec("node.server", _NI, "_lock", cls="IngestServer",
                 kind="lock",
                 guards=("_conns", "_next_id", "_accepting"),
                 note="live-connection table shared by the accept loop "
                      "and each conn reader's teardown"),
        # -- mesh: peer links + anti-entropy ---------------------------
        LockSpec("mesh.link", _ML, "_cond", cls="PeerLink",
                 kind="condition",
                 guards=("_queue", "_blocked", "_quarantined",
                         "_closing", "_sent", "_shed", "_dropped",
                         "_connects"),
                 note="one per-peer outbound queue + link state "
                      "machine (blocked/quarantined) shared by "
                      "offerers, control frames, and the mesh-link "
                      "worker; the socket itself is worker-local"),
        LockSpec("mesh.replay", _MS, "_replay_lock",
                 cls="MeshNodeService", kind="lock",
                 guards=("_replay",),
                 note="the anti-entropy replay log: the pump appends "
                      "on accept (transport seam), conn threads serve "
                      "SUMMARY/PULL from it inline; never nested with "
                      "mesh.link — offers happen after release"),
        LockSpec("mesh.links", _MS, "_links_lock",
                 cls="MeshNodeService", kind="lock",
                 guards=("links",),
                 note="the runtime peer table: JOIN/LEAVE frames "
                      "mutate it on conn threads while the pump "
                      "(flood, sync) and health snapshot it; links "
                      "start/close OUTSIDE the lock (they join worker "
                      "threads), and it never nests under mesh.link "
                      "or mesh.replay"),
        # -- utils -----------------------------------------------------
        LockSpec("nodectx.stack", "consensus_specs_tpu.utils.nodectx",
                 "_lock", guards=("_stack",)),
        LockSpec("nodectx.slot", "consensus_specs_tpu.utils.nodectx",
                 "_lock", cls="StateRouter", guards=("_global",),
                 note="a StateRouter's process-global default cell "
                      "(supervisor/plan/guard singletons); per-context "
                      "Slot values are serialized by the scenario "
                      "driver's single-scheduler discipline, like the "
                      "context stack itself"),
    ),
    roles=(
        ThreadRole("block",
                   note="the default thread: block processing, flush "
                        "submit, merkle plan/commit, scenario stepping"),
        ThreadRole("engine-worker", _PA, "_Worker._loop",
                   note="runs a whole flush's batch-verify behind its "
                        "FlushTicket (thread 'sigpipe-flush-engine')"),
        ThreadRole("leg-worker", _PA, "_Worker._loop",
                   note="runs the hash-to-G2 leg of an in-flight flush "
                        "(thread 'sigpipe-flush-leg')"),
        ThreadRole("gossip-drainer", _GP, "AdmissionPipeline._poll",
                   note="whichever thread wins _drainer_lock; stages "
                        "window N+1 and delivers window N in order"),
        ThreadRole("watchdog-worker",
                   "consensus_specs_tpu.resilience.supervisor",
                   "_SiteWorker._loop",
                   note="per-site daemon running watchdog'd dispatches; "
                        "abandoned on deadline expiry"),
        ThreadRole("node-listener", _NI, "IngestServer._accept_loop",
                   note="the front door's accept loop; spawns one "
                        "node-conn reader per connection"),
        ThreadRole("node-conn", _NI, "IngestServer._conn_loop",
                   note="per-connection deframer/decoder; pushes work "
                        "items onto the bounded ingest queue, never "
                        "touches the pipeline or store"),
        ThreadRole("node-pump", _NS, "NodeService._pump_loop",
                   note="the ONLY thread that drives the node's "
                        "pipeline/store: pops the ingest queue, submits "
                        "under scope(), harvests verdicts (on a mesh "
                        "node: also runs the anti-entropy sync via the "
                        "_pump_extra hook)"),
        ThreadRole("mesh-link", _ML, "PeerLink._run",
                   note="one per peer (thread 'mesh-link-<peer>'): "
                        "pops the outbound queue, reconnects with "
                        "backoff, sends under the mesh.link/mesh.send "
                        "fault boundary; never touches the pipeline"),
    ),
    handoffs=(
        Handoff("flush.ticket", _PA, "FlushTicket",
                note="THE join handle: result()/Leg.get() are the only "
                     "ways a flush outcome crosses back"),
        Handoff("flush.ticket_tls", _PA, "_TL",
                note="thread-local slot carrying a worker's own "
                     "in-flight ticket (writes_allowed)"),
        Handoff("engine.jobs", _PA, "_jobs",
                note="FIFO staging queue into the engine/leg workers; "
                     "FIFO is the determinism contract"),
        Handoff("watchdog.jobs",
                "consensus_specs_tpu.resilience.supervisor", "_jobs",
                note="site-worker job queue; the result box + done "
                     "Event travel inside each job"),
        Handoff("watchdog.done",
                "consensus_specs_tpu.resilience.supervisor", "done",
                note="the supervisor Event a watchdog'd caller waits "
                     "on; expiry abandons the worker"),
        Handoff("node.ingest_queue", _NS, "_queue",
                note="decoded socket frames cross from conn readers to "
                     "the pump as queue items; FIFO is the front "
                     "door's ordering contract, shed-oldest its "
                     "overload contract"),
        Handoff("node.respond", _NI, "respond",
                note="each work item carries its connection's respond "
                     "callable back to the pump; writes serialize "
                     "under node.conn"),
        Handoff("mesh.outbound", _ML, "_queue",
                note="framed bytes cross from the pump (transport "
                     "seam) to each mesh-link worker; bounded, "
                     "shed-oldest under backpressure"),
    ),
)

_LOCK_KINDS = ("lock", "rlock", "condition")

if len(set(CONCURRENCY.lock_names())) != len(CONCURRENCY.locks):
    raise RuntimeError("duplicate lock name in sites.CONCURRENCY")
for _l in CONCURRENCY.locks:
    if _l.kind not in _LOCK_KINDS:
        raise RuntimeError(f"{_l.name}: bad lock kind {_l.kind!r}")
    if not isinstance(_l.guards, tuple):
        raise RuntimeError(f"{_l.name}: guards must be a tuple")
if len({r.name for r in CONCURRENCY.roles}) != len(CONCURRENCY.roles):
    raise RuntimeError("duplicate role name in sites.CONCURRENCY")
if len({h.name for h in CONCURRENCY.handoffs}) != len(CONCURRENCY.handoffs):
    raise RuntimeError("duplicate handoff name in sites.CONCURRENCY")


def lock_spec(name: str) -> LockSpec:
    """Look up one registered lock; KeyError on unregistered names."""
    for spec in CONCURRENCY.locks:
        if spec.name == name:
            return spec
    raise KeyError(name)


def lock_names() -> tuple:
    return CONCURRENCY.lock_names()
