"""Tier-1 CPU rehearsal of chip_smoke.py's main path at a tiny size.

The phases run as the chip runs them (state build and keys from the
seed, a signed boundary-crossing block, warm / timed / tampered
transitions, the host-engine comparison), at the minimal preset with a
few hundred validators and a handful of attestations.  The test steers
two things, never an option of the program: the size, and the BLS
backend — on CPU the host oracle stands in for the limb kernels, whose
CPU compile takes minutes (their differential tests are the kernel
tier, and tests/test_tpu_compile.py compiles them for the chip).
"""
import json

import pytest

import chip_smoke
from consensus_specs_tpu.specs import get_spec
from consensus_specs_tpu.utils import bls


@pytest.fixture
def host_bls(monkeypatch):
    monkeypatch.setattr(bls, "use_tpu", bls.use_native)


def test_main_path_rehearsal(host_bls):
    spec = get_spec("altair", "minimal")
    report = chip_smoke.run_main_path(spec, 256, 4)
    chip_smoke.verify_report(report)
    assert report["device_root"] == report["host_root"]
    assert report["tampered_rejected"]
    assert report["attestations"] == 4
    assert report["sync_participants"] == int(spec.SYNC_COMMITTEE_SIZE)
    assert report["epoch_sweep_dispatches"] == 1
    assert report["epoch_sweep_fallbacks"] == 0


def test_mesh_compare_rehearsal(host_bls):
    """The --chips 4 path on 4 of the virtual CPU devices: one block at
    mesh widths 1 and 4, equal roots, equal tampered-block verdicts."""
    spec = get_spec("altair", "minimal")
    report = chip_smoke.run_mesh_compare(spec, 256, 2, chip_smoke.SEED, 4)
    assert report[4]["mesh"] == 4 and report[1]["mesh"] == 1
    assert report[1]["root"] == report[4]["root"] == report["host_root"]
    assert report[1]["verdicts"] == report[4]["verdicts"]
    assert report[1]["verdicts"].count(False) == 1


def test_refuses_cpu(capsys):
    """No TPU: a non-zero exit naming the platform, and no result line."""
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "'cpu'" in err
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
