"""Pallas TPU kernel for the SHA-256 2-to-1 compression sweep.

The merkleization workload is thousands of independent 64-byte
compressions per tree level (ops/sha256.py).  The XLA path expresses the
message schedule and 64 rounds as lax.scan, which materializes
inter-round state in HBM-visible buffers; this Pallas kernel keeps the
whole double-compression (message block + constant pad block) in VMEM
registers per tile of lanes, with the round loop unrolled inside the
kernel body — the fusion XLA cannot be relied on to do.

Interface: `hash_pairs_pallas(chunks)` mirrors ops/sha256.hash_pairs
(uint32[2N, 8] -> uint32[N, 8]).  `available()` gates on a TPU backend;
every caller falls back to the XLA path elsewhere, the differential
test (tests/test_sha256_pallas.py) checks bit-equality on CPU via
interpreter mode, and tests/test_tpu_compile.py compiles the kernel
for a described v5e chip.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from .sha256 import _K, _IV, _PAD_BLOCK

LANES = 256          # rows per kernel tile


def available() -> bool:
    """Whether the default device is a TPU.  A backend that fails to
    initialize raises here; it is not read as "no TPU"."""
    return jax.devices()[0].platform == "tpu"


def _rotr(x, n):
    return (x >> n) | (x << (32 - n))


def _compress_rows(state, w):
    """One unrolled SHA-256 compression; `state` is a list of 8 lane
    vectors, `w` a list of 16 lane vectors.  Returns 8 lane vectors."""
    a, b, c, d, e, f, g, h = state
    w = list(w)
    for t in range(64):
        if t < 16:
            wt = w[t]
        else:
            s0 = (_rotr(w[t - 15], 7) ^ _rotr(w[t - 15], 18)
                  ^ (w[t - 15] >> 3))
            s1 = (_rotr(w[t - 2], 17) ^ _rotr(w[t - 2], 19)
                  ^ (w[t - 2] >> 10))
            wt = w[t - 16] + s0 + w[t - 7] + s1
            w.append(wt)
        S1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + S1 + ch + np.uint32(int(_K[t])) + wt
        S0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = S0 + maj
        a, b, c, d, e, f, g, h = t1 + t2, a, b, c, d + t1, e, f, g
    return [x + s for x, s in zip((a, b, c, d, e, f, g, h), state)]


def _lane_consts(values, lanes):
    """Python scalars -> in-kernel lane vectors (Pallas kernels may not
    capture constant arrays from the enclosing trace)."""
    return [jnp.full((lanes,), int(v), jnp.uint32) for v in values]


def _make_kernel(lanes: int):
    def _sha256_kernel(blocks_ref, out_ref):
        blocks = blocks_ref[:, :]                       # [lanes, 16]
        iv = _lane_consts(_IV, lanes)
        mid = _compress_rows(iv, [blocks[:, i] for i in range(16)])
        pad = _lane_consts(_PAD_BLOCK, lanes)
        out = _compress_rows(mid, pad)
        out_ref[:, :] = jnp.stack(out, axis=1)
    return _sha256_kernel


@functools.partial(jax.jit, static_argnames=("lanes",))
def _hash_pairs_pallas_fixed(chunks, lanes=LANES):
    import jax.experimental.pallas as pl

    n = chunks.shape[0] // 2
    blocks = chunks.reshape(n, 16)
    return pl.pallas_call(
        _make_kernel(lanes),
        grid=(n // lanes,),
        in_specs=[pl.BlockSpec((lanes, 16), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((lanes, 8), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 8), jnp.uint32),
    )(blocks)


def _hash_pairs_interpret(chunks, lanes):
    """Interpreter-mode path for CPU differential tests — eager (no outer
    jit: tracing the interpreter inlines the whole unrolled kernel and
    compiles for minutes on a small host)."""
    import jax.experimental.pallas as pl

    n = chunks.shape[0] // 2
    blocks = chunks.reshape(n, 16)
    return pl.pallas_call(
        _make_kernel(lanes),
        grid=(n // lanes,),
        in_specs=[pl.BlockSpec((lanes, 16), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((lanes, 8), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 8), jnp.uint32),
        interpret=True,
    )(blocks)


def hash_pairs_pallas(chunks, interpret=False, lanes=None):
    """2-to-1 hash of adjacent chunk pairs: uint32[2N, 8] -> uint32[N, 8].

    Pads the pair count up to a lane-tile multiple (power-of-two
    bucketing is inherited from callers).  `interpret=True` runs the
    kernel in Pallas interpreter mode (CPU differential testing)."""
    if lanes is None:
        lanes = 8 if interpret else LANES
    n2 = chunks.shape[0]
    n = n2 // 2
    target = max(lanes, ((n + lanes - 1) // lanes) * lanes)
    if target != n:
        pad = jnp.zeros((2 * target - n2, 8), dtype=jnp.uint32)
        chunks = jnp.concatenate([chunks, pad], axis=0)
    if interpret:
        out = _hash_pairs_interpret(chunks, lanes)
    else:
        out = _hash_pairs_pallas_fixed(chunks, lanes=lanes)
    return out[:n]


def merkle_tree_root_pallas(chunks, depth: int):
    """Balanced-tree root over uint32[2**depth, 8] chunks, all levels
    through the Pallas kernel (small top levels reuse the padded tile)."""
    level = chunks
    for _ in range(depth):
        level = hash_pairs_pallas(level)
    return level[0]


def hash_level_pallas(data: bytes) -> bytes:
    """Drop-in bulk level hasher (ssz.merkle.set_bulk_level_hasher)."""
    from .sha256 import bytes_to_words, words_to_bytes
    words = bytes_to_words(data)
    out = hash_pairs_pallas(jnp.asarray(words))
    return words_to_bytes(jax.device_get(out))
