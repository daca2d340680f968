"""Ahead-of-time compiles of the chip's programs for a described TPU v5e.

The CPU backend takes every device seam's CPU branch, so the rest of the
suite never builds what chip_smoke.py runs on the chip.  Here the TPU's
compiler builds those programs at the smoke's real widths for a
described `v5e:2x2` topology, with no chip attached: what it refuses
here would fail on the chip.  The topology is described inside a
fixture (never at import), and the persistent compilation cache is off
around these compiles (entries compiled for a described chip cannot be
read back without one).

Tier-1 holds the programs that compile in seconds to tens of seconds;
the limb-arithmetic programs whose TPU compile takes half a minute to
minutes (Miller steps, final-exponentiation stages, Pippenger, the G2
ladders) and the SHA-256 Pallas kernel at its full tile are marked
slow, the kernel with a narrow-tile sibling in tier-1.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from consensus_specs_tpu.ops import curve_jax as cj
from consensus_specs_tpu.ops import epoch_sweep, fq, msm
from consensus_specs_tpu.ops import pairing_jax as pj
from consensus_specs_tpu.ops import sha256, sha256_pallas

L = fq.LIMBS
ROWS = 256              # a mainnet block flush: 131 sets, padded
PAIRS = 256             # bls_tpu._DEVICE_MIN_PAIRS
VALIDATORS = 1 << 18    # chip_smoke.N_VALIDATORS


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.uint32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **kw):
    compiled = fn.lower(*args, **kw).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def _epoch_statics():
    """The altair program's statics as epoch_fast derives them for a
    full-participation mainnet state (chip_smoke.build_state)."""
    return (("do_rewards", True), ("ejection", 16000000000),
            ("electra", False),
            ("flags", ((0, 14, False), (1, 26, False), (2, 14, True))),
            ("hyst_down", 1), ("hyst_q", 4), ("hyst_up", 5),
            ("incr", 1000000000), ("leak", False),
            ("max_eb", 32000000000), ("target_flag", 1), ("wd", 64))


@pytest.mark.parametrize("lanes", [
    8, pytest.param(sha256_pallas.LANES, marks=pytest.mark.slow)])
def test_sha256_pallas_kernel(one_chip, lanes):
    """The kernel's compile time grows with its tile: the chip's 256-row
    tile takes half a minute, an 8-row tile of the same rounds about a
    second."""
    c = _compile(sha256_pallas._hash_pairs_pallas_fixed,
                 _spec(one_chip, (2 * 65536, 8)), lanes=lanes)
    assert "tpu_custom_call" in c.as_text()


def test_sha256_xla_level(one_chip):
    _compile(sha256._hash_pairs_fixed, _spec(one_chip, (2 * 65536, 8)))


def test_epoch_sweep_program(one_chip):
    dtypes = dict(eff=jnp.int64, slashed=jnp.bool_, activation=jnp.uint64,
                  exit_epoch=jnp.uint64, act_elig=jnp.uint64,
                  withdrawable=jnp.uint64, balances=jnp.int64,
                  max_eff=jnp.int64, part_prev=jnp.int64, scores=jnp.int64)
    scalars = [jnp.uint64] * 4 + [jnp.int64] * 6
    with jax.enable_x64(True):
        fn = epoch_sweep._program("altair", _epoch_statics())
        _compile(fn, *[_spec(one_chip, (VALIDATORS,), dtypes[k])
                       for k in epoch_sweep.ALTAIR_COLS],
                 *[_spec(one_chip, (), d) for d in scalars])


def test_g1_sweep_sum(one_chip):
    """The jax g1 sweep's program: 256 committee segments of up to 512
    pubkeys summed along the segment axis."""
    pt = (_spec(one_chip, (ROWS, 512, L)),) * 3
    _compile(cj.g1_sum_axis, pt, 1)


@pytest.mark.slow
def test_g1_weighting_ladder(one_chip):
    """The Fiat-Shamir weighting sweep (msm.g1_weighted_sweep)."""
    _compile(cj.g1_scalar_mul, (_spec(one_chip, (ROWS, L)),) * 3,
             _spec(one_chip, (ROWS, 64)))


@pytest.mark.parametrize("stage,args", [
    ("finish", lambda s: (_spec(s, (1, PAIRS, 12, L)),
                          _spec(s, (1, PAIRS), jnp.bool_))),
    ("conj", lambda s: (_spec(s, (1, 12, L)),)),
    ("is_one", lambda s: (_spec(s, (1, 12, L)),)),
])
def test_pairing_small_stages(one_chip, stage, args):
    fn = {"finish": pj._miller_finish, "conj": pj._conj_jit,
          "is_one": pj._is_one_jit}[stage]
    _compile(fn, *args(one_chip))


@pytest.mark.parametrize("power", [1, 2, 3])
def test_pairing_frobenius(one_chip, power):
    _compile(pj._frob_jit, _spec(one_chip, (1, 12, L)), power)


# -- slow: the limb programs whose TPU compile takes 10 s to minutes -----

@pytest.mark.slow
def test_pippenger_msm(one_chip):
    n = 4096
    pt = [_spec(one_chip, (n, L))] * 3
    _compile(msm._pippenger_g1, *pt, _spec(one_chip, (msm._N_WIN, n)))


@pytest.mark.slow
@pytest.mark.parametrize("bits", [64, 636])
def test_g2_ladders(one_chip, bits):
    """The fold's signature MSM ladder (64-bit coefficients) and the
    hash-to-G2 cofactor ladder (636 bits)."""
    _compile(cj.g2_scalar_mul, (_spec(one_chip, (ROWS, 2, L)),) * 3,
             _spec(one_chip, (ROWS, bits)))


@pytest.mark.slow
def test_g2_fold_sum(one_chip):
    _compile(cj.g2_sum_axis, (_spec(one_chip, (ROWS, 2, L)),) * 3, 0)


@pytest.mark.slow
@pytest.mark.parametrize("step", ["double", "add"])
def test_pairing_miller_steps(one_chip, step):
    f = _spec(one_chip, (1, PAIRS, 12, L))
    t = (_spec(one_chip, (1, PAIRS, 2, L)),) * 3
    p = _spec(one_chip, (1, PAIRS, L))
    q = _spec(one_chip, (1, PAIRS, 2, L))
    if step == "double":
        _compile(pj._miller_step_double, f, t, p, p)
    else:
        _compile(pj._miller_step_add, f, t, q, q, p, p)


@pytest.mark.slow
@pytest.mark.parametrize("stage", ["prod_reduce", "easy", "cyc", "mul"])
def test_pairing_heavy_stages(one_chip, stage):
    m = _spec(one_chip, (1, 12, L))
    if stage == "prod_reduce":
        _compile(pj._prod_reduce, _spec(one_chip, (1, PAIRS, 12, L)))
    elif stage == "easy":
        _compile(pj._easy_jit, m)
    elif stage == "cyc":
        _compile(pj._cyc_jit, m)
    else:
        _compile(pj._mul_jit, m, m)
