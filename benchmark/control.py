"""The control: a cell run with the program's own BLS switch off
(`test_infra.disable_bls`), which breaks the configuration's guarantee
that every signature set is verified.  It must come out not correct.

    python3 benchmark/control.py --workload <name> --seeds <n> [<n> ...]

Runs on a TPU only; one result line per seed.  The benchmark's own runs
never run it; tests/benchmark runs it at the minimal preset.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark import harness  # noqa: E402


def run_control(cell, seed, seconds, devices, require_modes=True) -> dict:
    """One run of `cell` with BLS off.  The concurrent warm-up of the
    signature stages is skipped: with BLS off no signature program runs,
    and the warm transition warms the rest."""
    from consensus_specs_tpu.test_infra import disable_bls
    with disable_bls():
        return harness.run_cell(cell, seed, seconds, False,
                                time.perf_counter(), devices,
                                require_modes=require_modes,
                                warm_stages=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != harness.REQUIRED_PLATFORM:
        print("control: not on a TPU", file=sys.stderr)
        return 2
    harness.configure_cache()
    for seed in args.seeds:
        result = run_control(cell, seed, args.seconds,
                             devices[:cell.workload["chips"]])
        print(json.dumps({"seed": seed, "correct": result["correct"],
                          "checks": result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
