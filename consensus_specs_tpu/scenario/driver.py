"""The multi-node scenario driver: one seeded scheduler stepping N
nodes, a simulated network, and the omniscient oracle through a
declarative timeline.

Execution model (docs/scenario.md has the diagram):

    TrafficPlan (traffic.py)      one canonical chain + every message,
        |                         precomputed from (scenario, seed)
        v
    agenda = merge(slot ticks, control actions, publishes)
        |                         processed in (time, priority, seq)
        v                         order on ONE ManualClock
    SimNetwork (net.py)           per-origin FIFO streams, seeded
        |                         delay/jitter, stall/flush loss
        v
    SimNode[i] (node.py)          own pipeline + txn store + books
    Oracle     (oracle.py)        same feed, publish order, no network

Sync points — heal, recovery, and the end-of-run convergence loop —
replay the canonical feed to any node missing messages (`catch_up`),
in publish order, until a fixpoint: the simulation's stand-in for
req/resp backfill.  A node that needed one records a `scenario.sync`
incident in its OWN log (that is how a partition is *attributed*: the
node that noticed the gap says so).

Everything runs on the calling thread; the only cross-thread hop is
the resilience watchdog, which inherits the stepped node's context by
construction (see utils/nodectx.py).  `run()` returns a
`ScenarioReport` whose `fingerprint()` is a pure function of
`(scenario, seed)` — the seed-replay determinism pin.
"""
from __future__ import annotations

import os
import random
import shutil
import tempfile
from dataclasses import dataclass, field

from .. import resilience
from ..gossip import GossipConfig
from ..resilience import FaultPlan, FaultSpec
from ..resilience.supervisor import SupervisorConfig
from ..ssz import hash_tree_root
from ..specs import get_spec
from ..utils import nodectx
from ..utils.clock import ManualClock
from .dsl import Scenario
from .net import SimNetwork
from .node import SimNode
from .oracle import Oracle, attribution_report, node_summary
from .traffic import TrafficPlan

MAX_CONVERGENCE_ROUNDS = 6


@dataclass
class ScenarioReport:
    scenario: Scenario
    seed: int
    oracle: dict
    nodes: list = field(default_factory=list)
    attribution: dict = field(default_factory=dict)
    feed_size: int = 0
    sync_replays: int = 0
    convergence_rounds: int = 0
    # durable scenarios: the fleet's on-disk journal high-water mark
    # (bytes, sampled at every slot tick) — the soak runner's
    # bounded-disk signal.  Runtime plumbing, not part of the
    # deterministic fingerprint.
    durable_bytes_hw: int = 0

    def fingerprint(self) -> dict:
        """The deterministic projection: everything here is a pure
        function of (scenario, seed) — no wall-clock timers, no
        transient ids."""
        def node_fp(n):
            return {
                "node_id": n["node_id"],
                "store_root": n["store_root"],
                "head": n["head"],
                "finalized": n["finalized"],
                "accepted": n["accepted"],
                "incidents": [
                    (e["site"], e["event"], round(e["t"], 6))
                    for e in n["incidents"]],
                "counters": dict(sorted(n["metrics"].items())),
            }
        return {
            "scenario": self.scenario.name,
            "seed": self.seed,
            "feed_size": self.feed_size,
            "oracle": {k: self.oracle[k] for k in
                       ("store_root", "head", "finalized", "accepted")},
            "nodes": [node_fp(n) for n in self.nodes],
        }


class Driver:
    def __init__(self, scenario: Scenario, seed: int = 0,
                 node_config: GossipConfig | None = None,
                 snapshot_interval: int = 256,
                 journal_kwargs: dict | None = None,
                 supervisor_overrides: dict | None = None):
        scenario.validate()
        self.scenario = scenario
        self.seed = int(seed)
        self.spec = get_spec(scenario.fork, scenario.preset)
        self.rng = random.Random(self.seed)
        self.clock = ManualClock()
        # the plan consumes the RNG first (fixed draw order), the
        # network and delivery jitter consume it afterwards
        self.plan = TrafficPlan(self.spec, scenario, self.rng)
        self.net = SimNetwork(
            scenario.nodes, scenario.topology.link, self.rng,
            ingress_multiplier=scenario.traffic.ingress_multiplier)
        # durable scenarios: every node journals to its own on-disk
        # segment directory under one per-run temp root (removed at the
        # end of run(); the dirs are runtime plumbing, not part of the
        # deterministic fingerprint)
        self._durable_root = None
        if scenario.durable:
            self._durable_root = tempfile.mkdtemp(
                prefix=f"scenario-{scenario.name}-")
        # every node gets its OWN supervisor (breaker table on the
        # shared ManualClock) and fault-plan slot: a degraded window or
        # shard death on one node is invisible to the others;
        # `supervisor_overrides` tunes the per-node breakers (the soak
        # runner and the isolation tests run trippier thresholds)
        sup_overrides = supervisor_overrides or {}
        self.nodes = [
            SimNode(i, self.spec, self.plan.genesis_state, self.clock,
                    config=node_config,
                    transport=self._transport_for(i),
                    supervisor_config=SupervisorConfig(
                        clock=self.clock, **sup_overrides),
                    snapshot_interval=snapshot_interval,
                    journal_kwargs=journal_kwargs,
                    durable_dir=os.path.join(self._durable_root,
                                             f"node{i}")
                    if self._durable_root else None)
            for i in range(scenario.nodes)]
        self.oracle = Oracle(self.spec, self.plan, self.clock)
        self._digests: dict = {}            # feed seq -> payload digest
        self.sync_replays = 0
        self.convergence_rounds = 0
        self.durable_bytes_hw = 0

    # -- transport seam ------------------------------------------------
    def _transport_for(self, node_id: int):
        def relay(message) -> None:
            # accepted-message forwarding: pure mesh redundancy in this
            # simulation (dedup sheds the copies) — counted so the seam
            # is observable per node
            self.nodes[node_id].ctx.metrics.inc_labeled(
                "gossip_forwarded", message.topic)
        return relay

    # -- time ----------------------------------------------------------
    def _wall(self, sim_s: float) -> int:
        return self.plan.genesis_time + int(sim_s)

    def _advance(self, to_s: float) -> None:
        if to_s > self.clock.now():
            self.clock.advance(to_s - self.clock.now())

    # -- the run -------------------------------------------------------
    def run(self) -> ScenarioReport:
        # the process-global DEFAULT supervisor serves the oracle and
        # any out-of-context work; each SimNode routes to its own
        previous_sup = resilience.supervisor._ACTIVE.default
        resilience.enable(SupervisorConfig(clock=self.clock))
        try:
            return self._run()
        finally:
            for node in self.nodes:
                node.install_fault_plan(None)
            resilience.supervisor._ACTIVE.set_default(previous_sup)
            if self._durable_root is not None:
                for node in self.nodes:
                    if node.journal is not None and \
                            hasattr(node.journal, "close"):
                        node.journal.close()
                shutil.rmtree(self._durable_root, ignore_errors=True)

    def _run(self) -> ScenarioReport:
        scenario = self.scenario
        agenda = []
        end_slot = scenario.slots + 2
        for slot in range(1, end_slot + 1):
            agenda.append((self.plan.slot_time(slot), 0, len(agenda),
                           "tick", slot))
            # the attesting-interval tick: blocks publish AT this
            # boundary, so by the time any delivery flushes, every
            # store's clock is past the timely window — block
            # timeliness is uniformly False at every node AND the
            # oracle, however late a partition or crash delivers the
            # block (dsl.py's determinism discipline)
            agenda.append((self.plan.slot_time(slot)
                           + self.plan.attest_offset, 0, len(agenda),
                           "interval_tick", slot))
        for action in self.plan.actions:
            agenda.append((action.time_s, 1, len(agenda), "action",
                           action))
        for planned in self.plan.messages:
            # stable index keeps equal-time publishes in feed order
            agenda.append((planned.time_s, 2, len(agenda), "publish",
                           planned))
        agenda.sort(key=lambda a: (a[0], a[1], a[2]))

        for time_s, _prio, _idx, kind, item in agenda:
            self._advance(time_s)
            if kind == "tick":
                self._tick(item)
            elif kind == "interval_tick":
                self._tick_stores(time_s)
            elif kind == "action":
                # deliveries already DUE land before the control point
                # mutates topology: a partition cut must not
                # retroactively stall an in-flight message the storm
                # planner's establishment contract (publish + margin <
                # cut => delivered pre-cut) counted as arrived — the
                # agenda can be sparse enough that no pump ran between
                # the due time and the cut
                self._pump()
                self._action(item)
            else:
                self._publish(item)
            self._pump()

        # landing phase: let in-flight deliveries land, flush residual
        # stalls, then converge
        self._advance(self.plan.slot_time(end_slot) + 2.0)
        self.net.flush_stalls(self.clock.now(),
                              kinds=("drop", "partition", "crash"))
        self._pump()
        for node in self.nodes:
            node.drain()
        self.oracle.drain()
        self._converge()
        return self._report()

    # -- agenda steps --------------------------------------------------
    def _tick_stores(self, sim_s: float) -> None:
        wall = self._wall(sim_s)
        for node in self.nodes:
            node.tick(wall)
        self.oracle.tick(wall)

    def _tick(self, slot: int) -> None:
        self._tick_stores(self.plan.slot_time(slot))
        self._sample_disk()
        # slot boundary: gossip redundancy repairs plain drop losses
        self.net.flush_stalls(self.clock.now(), kinds=("drop",))
        for node in self.nodes:
            node.pump_retries(self.clock.now())
        self.oracle.pump_retries(self.clock.now())

    def _action(self, action) -> None:
        now = self.clock.now()
        kind = action.kind
        if kind == "partition":
            self.net.partition(action.params["groups"])
        elif kind == "heal":
            self.net.heal()
            released = self.net.flush_stalls(
                now, kinds=("drop", "partition", "crash"))
            self._pump()
            for node in self.nodes:
                self._catch_up(node, reason="heal",
                               released=released)
        elif kind == "crash":
            node = self.nodes[action.params["node"]]
            node.crash()
            self.net.node_down(node.node_id, True)
        elif kind == "kill":
            node = self.nodes[action.params["node"]]
            node.kill()
            self.net.node_down(node.node_id, True)
        elif kind == "recover":
            node = self.nodes[action.params["node"]]
            self.net.node_down(node.node_id, False)
            node.recover(self._wall(now))
            self.net.flush_stalls(now, kinds=("drop", "crash"))
            self._catch_up(node, reason="recover")
        elif kind == "degraded":
            site = action.params["site"]
            fault = action.params.get("fault") or "raise"
            for node in self._window_targets(action.params.get("node")):
                # one seeded plan PER NODE, installed in that node's
                # own slot: a fleet-wide window still trips N separate
                # breakers (one per book), and a targeted window never
                # draws from — or fires on — any other node's stream
                node.install_fault_plan(FaultPlan(
                    # speclint: disable=seam-dynamic-site -- the site
                    # comes from the scenario DSL; dsl.validate() rejects
                    # any name not in the resilience.sites registry
                    # before a run starts
                    [FaultSpec(site, fault, persistent=True)],
                    seed=self.seed * 1000003 + node.node_id))
        elif kind == "degraded_end":
            site = action.params["site"]
            for node in self._window_targets(action.params.get("node")):
                node.install_fault_plan(None)
                # under the node's context: the reset incident is that
                # node's record, like the trip that preceded it
                with nodectx.use(node.ctx):
                    node.supervisor.reset(site)
        else:                                # pragma: no cover
            raise AssertionError(f"unknown action {kind!r}")

    def _window_targets(self, target) -> list:
        """The nodes a degraded window arms/disarms: all of them for a
        fleet-wide window (target None), else exactly one."""
        return self.nodes if target is None \
            else [self.nodes[int(target)]]

    def _sample_disk(self) -> None:
        """Track the fleet's on-disk journal high-water mark (durable
        scenarios): the soak runner asserts it stays bounded across
        rounds, i.e. snapshot-anchored compaction is really deleting
        superseded segments."""
        if self._durable_root is None:
            return
        total = 0
        for node in self.nodes:
            journal = node.journal
            if journal is not None and hasattr(journal, "disk_bytes"):
                total += journal.disk_bytes()
        if total > self.durable_bytes_hw:
            self.durable_bytes_hw = total

    def _publish(self, planned) -> None:
        digest = bytes(hash_tree_root(planned.payload))
        msg = self.net.publish(planned.time_s, planned.origin,
                               planned.topic, planned.payload,
                               planned.tag)
        self._digests[msg.seq] = digest
        # the oracle consumes the same event stream, in publish order,
        # with no network in the way
        self.oracle.deliver(planned.topic, planned.payload, digest,
                            peer=msg.peer)

    def _pump(self) -> None:
        for dest, msg in self.net.pump(self.clock.now()):
            self.nodes[dest].submit(msg.topic, msg.payload,
                                    self._digests[msg.seq],
                                    peer=msg.peer)
        for node in self.nodes:
            node.poll()
        self.oracle.poll()

    # -- sync / convergence --------------------------------------------
    def _catch_up(self, node: SimNode, reason: str,
                  released: int = 0) -> int:
        """Replay the canonical feed, in publish order, to a node
        missing messages — the req/resp backfill stand-in.  Only
        attempts messages the ORACLE accepted: junk the omniscient
        sequential node rejected can never become acceptable later."""
        if not node.up:
            return 0
        now = self.clock.now()
        replayed = 0
        for msg in self.net.published:
            if msg.time > now:
                break
            digest = self._digests[msg.seq]
            if digest in node.accepted:
                continue
            if digest not in self.oracle.accepted:
                continue
            node.submit(msg.topic, msg.payload, digest, peer=msg.peer)
            replayed += 1
        if replayed:
            node.drain()
            self.sync_replays += replayed
            with node.scope():
                resilience.INCIDENTS.record(
                    "scenario.sync", "catch_up", reason=reason,
                    replayed=replayed, released=released)
        return replayed

    def _converge(self) -> None:
        """End-of-run anti-entropy to fixpoint: first the oracle works
        off its own retry queue (a same-instant ordering artifact can
        transiently reject even with a perfect network), then every
        node is repeatedly offered everything the oracle accepted that
        it has not."""
        for _ in range(MAX_CONVERGENCE_ROUNDS):
            if not self.oracle.retry:
                break
            # retries are scheduled at now+1.0: the clock must move or
            # no retry ever comes due
            self._advance(self.clock.now() + 1.5)
            self.oracle.pump_retries(self.clock.now())
            self.oracle.drain()
        for round_index in range(MAX_CONVERGENCE_ROUNDS):
            progress = 0
            for node in self.nodes:
                before = len(node.accepted)
                self._catch_up(node, reason="final")
                node.drain()
                progress += len(node.accepted) - before
            self.convergence_rounds = round_index + 1
            if progress == 0:
                break

    # -- reporting -----------------------------------------------------
    def _report(self) -> ScenarioReport:
        self._sample_disk()
        report = ScenarioReport(
            scenario=self.scenario, seed=self.seed,
            oracle=self.oracle.summary(),
            feed_size=len(self.net.published),
            sync_replays=self.sync_replays,
            convergence_rounds=self.convergence_rounds,
            durable_bytes_hw=self.durable_bytes_hw)
        for node in self.nodes:
            node.leak_check()
            report.nodes.append(node_summary(node))
        report.attribution = attribution_report(self.plan,
                                                report.nodes)
        return report


def run_scenario(scenario: Scenario, seed: int = 0,
                 node_config: GossipConfig | None = None,
                 snapshot_interval: int = 256,
                 journal_kwargs: dict | None = None,
                 supervisor_overrides: dict | None = None,
                 processes: bool = False):
    """One scenario run.  ``processes=True`` swaps the in-process
    simulated fleet for N real run_node.py processes meshed over their
    framed sockets (scenario/processes.py) — the recovery-chaos
    backend; it supports only the partition/heal/kill/recover event
    subset and ignores the in-process tuning knobs, and returns the
    process backend's report dict instead of a ScenarioReport."""
    if processes:
        from .processes import run_scenario_processes
        return run_scenario_processes(scenario, seed=seed)
    return Driver(scenario, seed, node_config,
                  snapshot_interval=snapshot_interval,
                  journal_kwargs=journal_kwargs,
                  supervisor_overrides=supervisor_overrides).run()
