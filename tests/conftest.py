"""Test configuration.

Tests run on CPU with 8 virtual XLA devices so multi-chip sharding
(jax.sharding.Mesh + shard_map) is exercised without TPU hardware.  These
env vars must be set before jax initializes, hence at conftest import time.
"""
import gc
import os

os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# the one compile-cache decision (JAX_COMPILATION_CACHE_DIR if set,
# else <repo>/.jax_cache): the limb-crypto graphs compile in tens of
# seconds, so cache them across pytest runs
from consensus_specs_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()


# build the native C++ host tier on demand so its tests never skip on a
# fresh checkout (single translation unit, ~2s with g++)
def _ensure_native_built():
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    lib = os.path.join(root, "native", "libconsensus_native.so")
    src = os.path.join(root, "native", "src", "consensus_native.cc")
    if os.path.exists(lib) or not os.path.exists(src):
        return
    tmp = lib + ".build"
    try:
        # compile to a temp path and rename atomically: an interrupted
        # g++ must not leave a truncated .so that blocks future rebuilds
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
             "-o", tmp, src],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib)
    except Exception:
        if os.path.exists(tmp):
            os.unlink(tmp)
        # tests fall back to skipping when the library is absent


_ensure_native_built()


def pytest_addoption(parser):
    parser.addoption("--preset", action="store", default="minimal",
                     help="preset to run spec tests with (minimal/mainnet)")
    parser.addoption("--fork", action="store", default=None,
                     help="restrict spec tests to a single fork")
    parser.addoption("--disable-bls", action="store_true", default=False,
                     help="stub out BLS signature checks for speed")
    parser.addoption("--bls-type", action="store", default="native",
                     help="BLS backend: native (pure python) or tpu")
    parser.addoption(
        "--kernel-tiers", action="store_true",
        default=os.environ.get("RUN_KERNEL_TIERS", "") not in ("", "0"),
        help="include the multi-minute XLA limb-kernel compile suites "
             "(also enabled via RUN_KERNEL_TIERS=1; `make test-kernels`)")


import pytest  # noqa: E402

# compile-heavy limb-crypto kernel suites: each triggers minutes of XLA
# graph compilation (pairing ladders, scalar-mul chains).  Gated so the
# default suite finishes inside a CI budget; fast smoke coverage of the
# same code paths stays default (test_sha256_jax, oracle crypto suites).
KERNEL_TIER_FILES = {
    "test_pairing_jax.py", "test_bls_tpu.py", "test_curve_jax.py",
    "test_fq_tower_jax.py", "test_fq_jax.py", "test_msm_pippenger.py",
    "test_g1_sweep.py", "test_merkle_sweep_jax.py",
    "test_shard_verify.py",
    # pure-python KZG oracle suite: ~3 min of host Pippenger MSMs (the
    # KZG surface keeps default coverage via test_fulu's sampling tests
    # and the kzg runner smoke)
    "test_kzg.py",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-minute pure-python crypto workload (mainnet-size "
        "whisk proofs); runs under --kernel-tiers / RUN_KERNEL_TIERS=1")


def pytest_unconfigure(config):
    # After the summary, the session still holds about a million objects
    # (cached genesis states, specs, decoded points).  The interpreter's
    # exit would sweep them in full collections for seconds before the
    # OS takes the memory back; frozen, they are skipped.  Atexit
    # handlers and the flush of the standard streams still run.
    gc.freeze()


def pytest_collection_modifyitems(config, items):
    if config.getoption("--kernel-tiers"):
        return
    skip = pytest.mark.skip(
        reason="kernel tier (multi-minute XLA compile): enable with "
               "--kernel-tiers / RUN_KERNEL_TIERS=1 / make test-kernels")
    skip_slow = pytest.mark.skip(
        reason="slow tier (mainnet-size pure-python proof): enable "
               "with --kernel-tiers / RUN_KERNEL_TIERS=1")
    for item in items:
        if os.path.basename(str(item.fspath)) in KERNEL_TIER_FILES:
            item.add_marker(skip)
        elif item.get_closest_marker("slow") is not None:
            item.add_marker(skip_slow)


@pytest.fixture(autouse=True, scope="session")
def _locktrace_gate():
    """SPECLINT_TSAN=1 (make chaos / make pipeline-chaos): every named
    lock is constructed traced, and this gate fails the session if any
    observed acquisition order contradicted the static lock graph, both
    orders of a pair were observed, or an unregistered lock
    participated (utils/locks.py LockTracer)."""
    yield
    from consensus_specs_tpu.utils import locks
    tracer = locks.tracer()
    if tracer is not None:
        tracer.assert_clean()


@pytest.fixture(autouse=True, scope="session")
def _configure(request):
    from consensus_specs_tpu.test_infra import context
    context.DEFAULT_TEST_PRESET = request.config.getoption("--preset")
    context.DEFAULT_PYTEST_FORKS = (
        [request.config.getoption("--fork")]
        if request.config.getoption("--fork") else None)
    # quick tier: spec batteries run their fork-span endpoints only;
    # --kernel-tiers (make test-kernels / chaos tiers) restores the
    # full fork matrix, as does an explicit --fork filter
    context.QUICK_FORK_SPAN = not request.config.getoption(
        "--kernel-tiers")
    from consensus_specs_tpu.utils import bls
    if request.config.getoption("--disable-bls"):
        bls.bls_active = False
    bls.use_backend(request.config.getoption("--bls-type"))
    yield
