"""Validation for the Pallas SHA-256 kernel.

The kernel body's round math (_compress_rows — the part that could be
wrong) is differential-tested against hashlib by running it as plain jnp
ops; the pallas_call plumbing itself (BlockSpec tiling, grid) is
compiled for a described v5e chip in tests/test_tpu_compile.py
(interpreter mode interprets ~2,500 unrolled ops per tile and is
minutes-slow on a small CPU host).
"""
import hashlib

import numpy as np
import jax.numpy as jnp

from consensus_specs_tpu.ops import sha256_pallas as psha
from consensus_specs_tpu.ops.sha256 import _IV, _PAD_BLOCK


def _digest_rows(words: np.ndarray) -> np.ndarray:
    """Run the kernel's compression math (no pallas) over [N, 16] blocks."""
    lanes = words.shape[0]
    iv = [jnp.full((lanes,), int(v), jnp.uint32) for v in _IV]
    blocks = [jnp.asarray(words[:, i]) for i in range(16)]
    mid = psha._compress_rows(iv, blocks)
    pad = [jnp.full((lanes,), int(v), jnp.uint32) for v in _PAD_BLOCK]
    out = psha._compress_rows(mid, pad)
    return np.stack([np.asarray(x) for x in out], axis=1)


def test_kernel_round_math_matches_hashlib():
    rng = np.random.default_rng(11)
    words = rng.integers(0, 2**32, size=(8, 16), dtype=np.uint32)
    got = _digest_rows(words)
    data = words.astype(">u4").tobytes()
    for i in range(8):
        want = hashlib.sha256(data[i * 64:(i + 1) * 64]).digest()
        assert got[i].astype(">u4").tobytes() == want, i
