"""Tier-1 CPU runs of the benchmark's cells at the minimal preset.

Each cell's traffic goes through the harness's own set-up, window and
checks (`harness.run_cell`), with two things steered by the test and
never by an option of the program: the size (minimal preset, 256
validators; the mid-epoch block moves to slot 4 of the minimal preset's
8-slot epoch) and the BLS backend (the host oracle stands in for the
limb kernels, as tests/test_chip_smoke.py does).  The same runs with a
fault planted under the timed path, and with the control, must come out
not correct.
"""
import time

import jax
import pytest

from benchmark import harness
from consensus_specs_tpu.utils import bls

CELLS = ["altair-mainnet-262k.boundary-block",
         "electra-mainnet-262k.mid-epoch-block"]
SEED = 3_000_000_017          # above 2**31, as the driver's are


@pytest.fixture
def host_bls(monkeypatch):
    monkeypatch.setattr(bls, "use_tpu", bls.use_native)


def cell_of(name: str) -> harness.Cell:
    """A cell of `BENCHMARK.json`, or the electra cell, which stays as
    data (configuration and traffic files) without an entry there: its
    device stage has no trace that fits a run (PERF.md section 7)."""
    if name != CELLS[1]:
        return harness.find_cell(name)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    return harness.Cell(
        name=name, workload={"name": name, "config": "electra-mainnet-262k",
                             "traffic": "mid-epoch-block", "chips": 1},
        config=harness.load_json(harness.HERE, "configs",
                                 "electra-mainnet-262k.json"),
        mix=harness.load_json(harness.HERE, "traffic",
                              "mid-epoch-block.json"),
        end_to_end=bench["end_to_end"],
        per_layer=[m for m in bench["per_layer"] if m["name"] != "epoch_s"])


def minimal(name: str) -> harness.Cell:
    cell = cell_of(name)
    cell.config.update(preset="minimal", validators=256)
    if cell.mix["block_slot_in_epoch"] >= 8:
        cell.mix["block_slot_in_epoch"] = 4
    return cell


def run(cell, trace=False):
    return harness.run_cell(cell, SEED, 0.5, trace, time.perf_counter(),
                            jax.devices()[:1], require_modes=False)


@pytest.mark.parametrize("name", CELLS)
def test_cell_is_correct(host_bls, name):
    result = run(minimal(name))
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"block_s", "setup_s"}
    assert list(result)[-1] == "checks"
    assert all(c["value"] == 0 for c in result["checks"].values())


@pytest.mark.parametrize("name", CELLS)
def test_fresh_flags_are_correct(host_bls, name):
    """Attestations that bring their attesters' first flags of the epoch
    (the participation writes and the proposer's reward, which the
    cells' full-participation states leave untouched) agree with the
    reference too."""
    cell = minimal(name)
    cell.mix["current_participation"] = "unset_for_attesters"
    result = run(cell)
    assert result["correct"], result["checks"]


def _unchanged(spec, monkeypatch):
    monkeypatch.setattr(spec, "state_transition",
                        lambda state, block, validate_result=True: None,
                        raising=False)


def _half_the_attestations(spec, monkeypatch):
    """Every even-numbered attestation left out: the tampered one (index
    5) is still processed and refused, and under full participation the
    ones left out would leave the state unchanged, so only the seam
    count sees them."""
    real = spec.process_attestation
    calls = []

    def odd_only(state, attestation):
        calls.append(1)
        if len(calls) % 2 == 0:
            real(state, attestation)
    monkeypatch.setattr(spec, "process_attestation", odd_only,
                        raising=False)


def _verdicts_altered(spec, monkeypatch):
    from consensus_specs_tpu.sigpipe import verify

    def all_valid(collected, mode=None, reuse=None):
        return {s.key(): True for s in collected}
    monkeypatch.setattr(verify, "_batch_verify_unique", all_valid)


def _flush_fails_once(spec, monkeypatch):
    """The batch flush of the first window block fails (the warm
    transition's flush is the first call): the program falls back to
    verifying on the host, with the same verdicts and roots."""
    from consensus_specs_tpu.sigpipe import verify
    real = verify._batch_verify_unique
    calls = []

    def fails_second(*a, **k):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("planted flush failure")
        return real(*a, **k)
    monkeypatch.setattr(verify, "_batch_verify_unique", fails_second)


# each fault, and the check that has to see it
FAULTS = {"state_unchanged": (_unchanged, "root_mismatches"),
          "half_the_attestations": (_half_the_attestations,
                                    "seam_shortfall"),
          "verdicts_altered": (_verdicts_altered, "verdict_mismatches"),
          "flush_fails_once": (_flush_fails_once, "pipeline_errors")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_planted_fault_is_not_correct(host_bls, monkeypatch, name, fault):
    cell = minimal(name)
    from consensus_specs_tpu.specs import get_spec
    plant, check = FAULTS[fault]
    plant(get_spec(cell.config["fork"], cell.config["preset"]), monkeypatch)
    result = run(cell)
    assert not result["correct"]
    assert result["checks"][check]["value"] > 0, result["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(host_bls, name):
    """The control: the program's own BLS switch off, which breaks the
    guarantee that every signature set is verified."""
    from benchmark import control
    result = control.run_control(minimal(name), SEED, 0.5,
                                 jax.devices()[:1], require_modes=False)
    assert not result["correct"]
    assert result["checks"]["tampered_accepted"]["value"] == 1


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reads_spans(host_bls, name):
    """A traced run on the CPU: no device plane, so the device readers
    find nothing and are left out; the host-span readers are there."""
    result = run(minimal(name), trace=True)
    assert result["correct"]
    assert {"sigpipe_s", "transition_self_s"} <= set(result["metrics"])
    assert ("epoch_s" in result["metrics"]) == (name == CELLS[0])
    assert "epoch_sweep_roofline" not in result["metrics"]
    assert result["device"]["window_s"] > 0
    assert len(result["breakdown"]["idle_gaps"]) <= 10
