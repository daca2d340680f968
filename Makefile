# Build/test/generate driving — capability parity with the reference's
# Makefile targets (test, gen_%, gen_all, detect_errors, pyspec).

PYTHON ?= python
OUT ?= out/vectors
JOBS ?= 1

# tier1 needs bash (pipefail / PIPESTATUS)
SHELL := /bin/bash

RUNNERS := shuffling ssz_static operations epoch_processing sanity bls \
	kzg rewards finality genesis fork_choice transition ssz_generic \
	forks merkle_proof networking kzg_7594 random light_client sync

.PHONY: test test-quick test-kernels tier1 chaos recovery-chaos \
	kill-drill scenario-chaos pipeline-chaos shard-verify soak lint \
	speclint native pyspec bench \
	gossip-bench txn-bench msm-bench merkle-bench epoch-bench \
	scenario-bench \
	multichip-bench pipeline-bench fold-bench factory-bench \
	factory-drill node-drill node-bench mesh-drill mesh-bench \
	gen_all detect_errors \
	$(addprefix gen_,$(RUNNERS))

# syntax/bytecode check over every package and script (the CI lint job)
lint:
	$(PYTHON) -m compileall -q consensus_specs_tpu tests scripts \
		deposit_contract bench.py __graft_entry__.py

# AST invariant checker (consensus_specs_tpu/analysis/): dispatch-seam
# conformance, kernel-bypass, determinism, per-node isolation,
# txn-purity, host-sync, and the concurrency contracts (lock
# discipline / lock order / thread escape, against the CONCURRENCY
# registry) machine-checked against resilience/sites.py; exits 1 on
# the first finding.  Stdlib-ast only, budgeted < 10 s.
# `--pass <name>` / `--list-passes` focus a run while iterating.
speclint:
	$(PYTHON) scripts/speclint.py

# default suite: the multi-minute XLA limb-kernel compile suites are
# skipped by conftest (KERNEL_TIER_FILES) so this finishes in a CI
# budget; `make test-kernels` adds them back (nightly/TPU sessions)
test:
	$(PYTHON) -m pytest tests/ -q

test-kernels:
	$(PYTHON) -m pytest tests/ -q --kernel-tiers

# spec suites only (fastest signal while iterating on spec code);
# speclint gates first — a seam/determinism/isolation violation fails
# in seconds, before any test runs.  The sharded-verify fast pins ride
# along (test_sigpipe engine-mode/sweep seams, test_resilience
# shard_dead breaker contract); the mesh-kernel leg is `make
# shard-verify` / the test-kernels tier (conftest KERNEL_TIER_FILES)
test-quick: speclint
	$(PYTHON) -m pytest tests/spec_suites tests/test_ssz.py \
		tests/test_phase0_sanity.py tests/test_epoch_fast.py \
		tests/test_sigpipe.py tests/test_resilience.py \
		tests/test_gossip.py tests/test_txn.py \
		tests/test_merkle_inc.py tests/test_scenario.py \
		tests/test_speclint.py -q

# the exact ROADMAP.md tier-1 verify command (what the driver runs);
# DOTS_PASSED counts green dots from the -q progress lines
tier1:
	set -o pipefail; rm -f /tmp/_t1.log; \
	timeout -k 10 870 env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ -q \
		-m 'not slow' --continue-on-collection-errors \
		-p no:cacheprovider -p no:xdist -p no:randomly 2>&1 \
		| tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; \
	echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' \
		/tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# chaos tier (resilience/): sanity-block replays under seeded fault
# schedules with the supervisor + differential guard armed.  Excluded
# from tier-1 by the `slow` marker; CHAOS_SEED=N reruns one schedule.
# SPECLINT_TSAN=1 arms the runtime lock-order sanitizer
# (utils/locks.py): every named lock is traced and the session fails
# on an acquisition order the static speclint graph contradicts.
chaos:
	env JAX_PLATFORMS=cpu SPECLINT_TSAN=1 \
		CHAOS_SEED=$${CHAOS_SEED:-20260803} \
		$(PYTHON) -m pytest tests/test_chaos.py -q --kernel-tiers

# crash-anywhere recovery tier alone (txn/): seeded kills mid-handler /
# mid-commit / mid-journal-write / mid-fsync over a REAL on-disk
# DurableJournal (reopened cold for every recovery), the durable-format
# unit tier (torn tails, rotation, compaction, codec), and the
# process-boundary SIGKILL drill — recovered stores byte-identical to
# the never-crashed oracle throughout
recovery-chaos:
	env JAX_PLATFORMS=cpu SPECLINT_TSAN=1 \
		CHAOS_SEED=$${CHAOS_SEED:-20260803} \
		$(PYTHON) -m pytest tests/test_chaos.py tests/test_txn.py \
		-k "txn or crash or torn or recover or durable" -q --kernel-tiers
	env JAX_PLATFORMS=cpu SPECLINT_TSAN=1 \
		$(PYTHON) -m pytest tests/test_txn_durable.py \
		tests/test_kill_drill.py -q --kernel-tiers
	env JAX_PLATFORMS=cpu SPECLINT_TSAN=1 SOAK_SECONDS=45 \
		$(PYTHON) scripts/soak.py
	env JAX_PLATFORMS=cpu $(PYTHON) scripts/node_drill.py --quick
	env JAX_PLATFORMS=cpu $(PYTHON) scripts/mesh_drill.py --quick

# wall-clock soak runner (scripts/soak.py): loop durable fleet
# scenarios — the blackout3 SIGKILL battlefield alternating with
# randomized(durable=True) battlefields dealing kills and per-node
# fault windows — for SOAK_SECONDS of real time under tiny journal
# segments, asserting every round converges + attributes, disk stays
# bounded across rounds (compaction holds), and the journal/incident
# histories stay pruned; emits the rolling SOAK_r01.json health
# report.  SPECLINT_TSAN rides along so the namespaced per-node lock
# set feeds the lock-order sanitizer.  SOAK_SECONDS=45 is the quick
# CI leg (also run by recovery-chaos); default 300.
SOAK_SECONDS ?= 300
soak:
	env JAX_PLATFORMS=cpu SPECLINT_TSAN=1 \
		SOAK_SECONDS=$(SOAK_SECONDS) $(PYTHON) scripts/soak.py

# the subprocess SIGKILL drill alone (scripts/kill_drill.py): spawn a
# node over a durable journal, SIGKILL it at each seeded barrier family
# (mid-mutate / mid-apply / mid-journal-write / mid-fsync), restart in
# a fresh process, recover from disk, and assert store-root convergence
# with the never-crashed oracle; plus a rotation+compaction soak.
# KILL_DRILL_ARGS=--quick runs one kill per family.  The factory's
# quick drill rides along: the same SIGKILL discipline over the vector
# factory's barrier families (scripts/factory_drill.py).
kill-drill:
	env JAX_PLATFORMS=cpu $(PYTHON) scripts/kill_drill.py \
		$(KILL_DRILL_ARGS)
	env JAX_PLATFORMS=cpu $(PYTHON) scripts/factory_drill.py --quick

# the factory SIGKILL drill alone, full matrix (two kills per barrier
# family): spawn a real generation shard over a factory journal,
# SIGKILL it at each factory barrier (mid-journal-write / mid-fsync /
# mid-publish / pre-manifest-replace), restart in a fresh process,
# resume, and assert the recovered manifest + artifact set + vector
# tree are byte-identical to the never-crashed oracle run.
factory-drill:
	env JAX_PLATFORMS=cpu $(PYTHON) scripts/factory_drill.py

# SIGKILL crash drills through the real front door
# (scripts/node_drill.py): spawn a real `scripts/run_node.py` process,
# replay the smoke TrafficPlan over its unix socket at N× wall-clock
# rate, SIGKILL it at every registered barrier family in the serving
# path (the four txn barriers + node.ingest / node.drain), restart the
# same data dir, and assert the recovered store root is byte-identical
# to the in-process oracle.  NODE_DRILL_ARGS=--quick runs one kill per
# family (also the recovery-chaos leg).
node-drill:
	env JAX_PLATFORMS=cpu $(PYTHON) scripts/node_drill.py \
		$(NODE_DRILL_ARGS)

# the process-mesh drill (scripts/mesh_drill.py): scenario-library
# partition / SIGKILL / link-corruption timelines against three REAL
# run_node.py processes meshed over their framed unix sockets — PEERS
# frames impose the partition on the link layer, anti-entropy replays
# what a dead or isolated node missed, and every surviving node must
# converge byte-identically to the in-process oracle with each fault
# attributed in the right node's incident book and no process or
# socket leaked.  MESH_DRILL_ARGS=--quick runs the partition+heal case
# alone (also the recovery-chaos leg).
mesh-drill:
	env JAX_PLATFORMS=cpu $(PYTHON) scripts/mesh_drill.py \
		$(MESH_DRILL_ARGS)

# async flush engine slow tier under the runtime lock sanitizer: the
# full overlapped-flush fault matrix with every named lock traced, so
# real double-buffered windows and watchdog hops feed the observed
# acquisition graph the static lock-order pass is checked against
pipeline-chaos:
	env JAX_PLATFORMS=cpu SPECLINT_TSAN=1 \
		$(PYTHON) -m pytest tests/test_pipeline_async.py \
		tests/test_locktrace.py -q --kernel-tiers

# fleet battlefield tier (scenario/): the named scenario library plus
# the seeded randomized scenario matrix — partitions, equivocation
# storms, surround votes, long-range forks, crash-and-recover nodes,
# degraded windows — every node converging to the oracle store root
# with every attack attributed to a node-tagged incident
scenario-chaos:
	env JAX_PLATFORMS=cpu \
		$(PYTHON) -m pytest tests/test_scenario.py -q --kernel-tiers

# sharded verify path alone (parallel/shard_verify.py): the forced
# 8-device host-mesh parity + shard-fault suite.  The file rides the
# suite's kernel tier (conftest KERNEL_TIER_FILES — `make test-kernels`
# runs it with the other limb-kernel suites); this target is the
# focused loop while iterating on the sharding layer.  The fast seams
# (shard_dead breaker contract, oracle-engine sweeps) stay in tier-1
# via test_resilience/test_sigpipe.
shard-verify:
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_shard_verify.py \
		-q --kernel-tiers

native:
	$(PYTHON) scripts/build_native.py

# emit executable spec modules from the reference markdown
pyspec:
	$(PYTHON) scripts/build_pyspec.py --out build/pyspec \
		--forks phase0 altair bellatrix capella deneb electra fulu \
		whisk eip7732 eip6800

bench:
	$(PYTHON) bench.py

# gossip admission tier alone (gossip/): messages/sec +
# dispatches-per-message at 1x/10x/100x ingress; BENCH_GOSSIP_BACKEND=
# native and BENCH_GOSSIP_MSGS=8 give an accelerator-less smoke run
gossip-bench:
	$(PYTHON) bench.py gossip

# transactional-store commit overhead alone (txn/): asserts < 10% added
# latency on native-BLS on_block replays with WAL journaling on, then
# measures the DURABLE journal per fsync policy (append+commit µs/op,
# fsync counts, recovery replay ops/s) and emits TXN_r01.json
txn-bench:
	$(PYTHON) bench.py txn

# device G1 sweep alone (ops/g1_sweep + weighted MSM): asserts one
# aggregation + one MSM dispatch per flush and zero host point adds on
# the device path at 10x gossip ingress; BENCH_MSM_BACKEND=native and
# BENCH_MSM_MSGS=8 give an accelerator-less smoke run
msm-bench:
	$(PYTHON) bench.py msm

# incremental merkleization alone (ssz/incremental.py): asserts a
# block-shaped re-root hashes O(diff . log state) chunks (not O(state))
# in one ssz.merkle_sweep dispatch, byte-identical to the forced
# full-rebuild path; BENCH_MERKLE_VALIDATORS=N resizes the state
merkle-bench:
	$(PYTHON) bench.py merkle_inc

# fused epoch engine alone (specs/epoch_fast.py -> ops.epoch_sweep):
# device/numpy/scalar process_epoch legs at the mainnet preset over
# ONE 2^18-validator state (copies), root identity pinned, exactly one
# counted ops.epoch_sweep dispatch per epoch, plus the slot+epoch
# boundary-transition leg (device merkleization + fused epoch) vs the
# scalar transition — the >= 50x north-star shape; emits the next free
# EPOCH_r0N.json slot and fails if device s/epoch regressed > 2x vs
# the previous archived report.  BENCH_EPOCH_VALIDATORS=4096 gives a
# small smoke run
epoch-bench:
	$(PYTHON) bench.py epoch

# fleet battlefield alone (scenario/): 16 nodes at 10x ingress through
# a partition + equivocation storm + heal; asserts oracle convergence,
# full attribution, and bounded duplicate shed; BENCH_SCENARIO=name
# and BENCH_SCENARIO_SEED=N pick another battlefield
scenario-bench:
	env JAX_PLATFORMS=cpu $(PYTHON) bench.py scenario

# async pipelined flush engine alone (sigpipe/pipeline_async.py):
# sustained multi-flush ingestion with overlap on vs off — asserts
# byte-identical store roots + verdicts, no inline flush async, and
# <= 1 host<->device round-trip per fused merkle re-root; emits
# PIPELINE_r01.json.  BENCH_PIPELINE_BACKEND=native and
# BENCH_PIPELINE_MSGS=16 give an accelerator-less smoke run
pipeline-bench:
	$(PYTHON) bench.py pipeline

# multi-chip sharded verify alone (parallel/shard_verify.py): one
# >=1k-set flush's aggregation sweep + weighted MSM + fused pairing
# product at 1/2/4/8 forced-host devices; asserts byte-identical
# outputs across every mesh width, O(1) dispatches per flush, and
# >= 3x 1->8 device throughput scaling; emits MULTICHIP_r06.json.
# BENCH_MULTICHIP_SETS=64 BENCH_MULTICHIP_DEVICES=1,2 give a smoke run
multichip-bench:
	env JAX_PLATFORMS=cpu $(PYTHON) bench.py multichip

# folded pairing product alone (sigpipe/fold.py): counted Miller-leg
# and dispatch invariants folded vs unfolded (2N -> N+1) at
# N in {16, 256, 1024}, real fold-on/off verdict parity incl. bisection,
# and the folded G2 MSM at 1- and 8-device forced-host mesh; emits
# FOLD_r01.json.  BENCH_FOLD_SETS=16 BENCH_FOLD_MESH=0 give a smoke run
fold-bench:
	env JAX_PLATFORMS=cpu $(PYTHON) bench.py fold

# vector factory throughput (factory/): engines-on vs engines-off
# generation of real transition-shaped cases, byte-identity asserted,
# plus the resume-overhead leg; emits FACTORY_r01.json.
# BENCH_FACTORY_CASES=3 gives a smoke run
factory-bench:
	env JAX_PLATFORMS=cpu $(PYTHON) bench.py factory

# front-door sustained-load bench (node/): spawn a real run_node.py
# process, replay the smoke TrafficPlan over the unix socket at >=10×
# wall-clock ingress plus a full-speed flood leg against a small
# ingest bound, and report sustained msgs/s, shed counts, RSS, and
# server-side p50/p99 admission→delivery latency; asserts the process
# survives with bounded queue/shed behavior; emits NODE_r01.json.
# BENCH_NODE_RATE=10 BENCH_NODE_PASSES=1 give a smoke run
node-bench:
	env JAX_PLATFORMS=cpu $(PYTHON) bench.py node

# fleet front-door bench (mesh/): real run_node.py processes over
# unix sockets — the partition+heal drill timeline with zero
# divergence and per-hop p50/p99 admission→delivery latency, a
# partition flood against a tiny ingest bound asserting bounded shed,
# surviving processes, and byte-identical post-heal convergence, and
# a 5-node RING flood asserting 100% multi-hop delivery coverage over
# windowed anti-entropy; emits the next free MESH_r0N.json slot and
# fails if the worst per-hop p99 regressed > 2x vs the previous
# archived report.  BENCH_MESH_SEED / BENCH_MESH_PASSES tune it
mesh-bench:
	env JAX_PLATFORMS=cpu $(PYTHON) bench.py mesh

# static pattern rule: GNU make refuses to run implicit pattern rules
# for .PHONY targets
$(addprefix gen_,$(RUNNERS)): gen_%:
	$(PYTHON) scripts/gen_vectors.py $* -o $(OUT) --jobs $(JOBS)

gen_all:
	$(PYTHON) scripts/gen_vectors.py all -o $(OUT) --jobs $(JOBS)

detect_errors:
	$(PYTHON) -c "from consensus_specs_tpu.gen.runner import \
		detect_incomplete; import sys; bad = detect_incomplete('$(OUT)'); \
		print('\n'.join(bad) or 'no incomplete cases'); \
		sys.exit(1 if bad else 0)"
