"""BLS12-381 base field Fq on TPU: limb arithmetic in JAX.

Design (SURVEY.md §7 step 1, pallas_guide mental model): an Fq element is
32 limbs x 12 bits (little-endian) in a uint32 vector, batched over leading
axes.  12-bit limbs keep every intermediate — 32-term products plus carry
tails — under 2^31, so the whole tower runs on native int32/uint32 vector
ops (no 64-bit emulation on TPU).  Multiplication is schoolbook convolution
(32 statically-unrolled shifted MACs) followed by Montgomery reduction in
base 2^12.  Elements stay in Montgomery form (R = 2^384 mod q) between
host conversions.

The pure-Python tower (crypto/fields.py) is the correctness oracle; every
op here is differential-tested against it.

Capability counterpart of the reference's external BLS backends
(py_arkworks_bls12381 Rust / milagro C — see
/root/reference/tests/core/pyspec/eth2spec/utils/bls.py:25-30).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..crypto.fields import Q

# ---------------------------------------------------------------------------
# representation constants
# ---------------------------------------------------------------------------

LIMB_BITS = 12
LIMBS = 32            # 32 * 12 = 384 bits >= 381
BASE = 1 << LIMB_BITS
MASK = BASE - 1

R_MONT = pow(2, LIMB_BITS * LIMBS, Q)          # Montgomery radix R mod q
R2_MONT = R_MONT * R_MONT % Q                  # R^2 mod q (to-Mont factor)
R_INV_MONT = pow(R_MONT, -1, Q)                # R^-1 mod q (from-Mont)
NINV = (-pow(Q, -1, BASE)) % BASE              # -q^{-1} mod 2^12


def _int_to_limbs_np(x: int) -> np.ndarray:
    return np.array([(x >> (LIMB_BITS * i)) & MASK for i in range(LIMBS)],
                    dtype=np.uint32)


Q_LIMBS = _int_to_limbs_np(Q)
R2_LIMBS = _int_to_limbs_np(R2_MONT)
ONE_MONT_LIMBS = _int_to_limbs_np(R_MONT)      # 1 in Montgomery form
ZERO_LIMBS = np.zeros(LIMBS, dtype=np.uint32)


# ---------------------------------------------------------------------------
# host codecs
# ---------------------------------------------------------------------------

def to_limbs(x: int) -> jnp.ndarray:
    """Plain integer -> canonical (non-Montgomery) limb vector."""
    return jnp.asarray(_int_to_limbs_np(x % Q))

def from_limbs(v) -> int:
    arr = np.asarray(v, dtype=np.uint64)
    out = 0
    for i in reversed(range(arr.shape[-1])):
        out = (out << LIMB_BITS) | int(arr[..., i])
    return out


def pack(xs) -> jnp.ndarray:
    """List of ints -> batched canonical limb array [n, LIMBS]."""
    return jnp.asarray(np.stack([_int_to_limbs_np(x % Q) for x in xs]))


def unpack(v) -> list:
    arr = np.asarray(v)
    return [from_limbs(arr[i]) for i in range(arr.shape[0])]


# ---------------------------------------------------------------------------
# normalization helpers (all jit-safe, batched over leading axes)
#
# CONTROL-FLOW-FREE BY DESIGN.  Every op below is straight-line vector
# code: fixed carry-compaction rounds plus a Kogge-Stone carry-lookahead
# resolve carries exactly with zero lax.scan/fori_loop/while ops.  The
# pairing kernels inline hundreds of field muls — with per-mul loop
# primitives XLA compile time explodes superlinearly (observed: one
# 8-bit Miller chunk > 20 min on CPU; the whole fused check never
# finished), while the flat form traces to a compact elementwise DAG
# that XLA fuses and compiles in seconds.  Runtime wins too: no
# sequential 32-step scans on tiny operands, just wide batched vector
# ops.  Comparisons/borrows are avoided entirely via two's-complement
# style (+2^384 bias) addition, so subtraction reuses the same carry
# machinery.
# ---------------------------------------------------------------------------

def _shift_limbs(x, d, fill):
    """x[..., i] -> x[..., i-d] (little-endian shift toward the top)."""
    pad = jnp.full(x.shape[:-1] + (d,), fill, dtype=x.dtype)
    return jnp.concatenate([pad, x[..., :-d]], axis=-1)


def _compact(t, width, rounds):
    """Value-preserving partial carry compaction: `rounds` shift-add
    passes over [..., n] uint32 accumulator limbs (< 2^31), padded/
    truncated to `width`.  Limb bound after r rounds: 2^19 -> 2^8 -> 1
    excess.  Truncation (width < value limbs) drops exact multiples of
    2^(12*width) — callers use that for mod-R arithmetic.
    """
    pad = width - t.shape[-1]
    if pad > 0:
        t = jnp.concatenate(
            [t, jnp.zeros(t.shape[:-1] + (pad,), t.dtype)], axis=-1)
    elif pad < 0:
        t = t[..., :width]
    for _ in range(rounds):
        c = t >> LIMB_BITS
        t = (t & MASK) + _shift_limbs(c, 1, 0)
    return t


def _norm(t, width):
    """Exact carry normalization: [..., n] uint32 accumulator limbs
    (each < 2^31) -> [..., width] canonical 12-bit limbs of the same
    integer.  `width` must cover the value; top carries are provably
    zero and dropped.

    Three compaction rounds bring every limb to <= 2^12 (carry bounds
    2^19 -> 2^8 -> 1), then one Kogge-Stone carry-lookahead resolves
    the remaining +-1 ripple exactly in log2(width) steps.
    """
    t = _compact(t, width, 3)
    # limbs now in [0, 2^12]; lookahead for the ripple-carry chain
    g = t > MASK                 # limb generates a carry (== 2^12)
    p = t == MASK                # limb propagates an incoming carry
    d = 1
    while d < width:
        g = g | (p & _shift_limbs(g, d, False))
        p = p & _shift_limbs(p, d, False)
        d <<= 1
    carry_in = _shift_limbs(g, 1, False).astype(t.dtype)
    # the carry-out of this add is already accounted for in the chain
    return ((t & MASK) + carry_in) & MASK


# 2^384 - q as limbs: adding it == subtracting q under a +2^384 bias
_QCOMP_LIMBS = np.array(
    [((1 << (LIMB_BITS * LIMBS)) - Q >> (LIMB_BITS * i)) & MASK
     for i in range(LIMBS)], dtype=np.uint32)


def _csub_q(a):
    """Conditionally subtract q when a >= q (canonical limbs in/out).

    a + (2^384 - q) overflows into limb 32 exactly when a >= q; the
    overflow bit selects between the biased difference and a.
    """
    t = a + jnp.asarray(_QCOMP_LIMBS)
    t = _norm(t, LIMBS + 1)
    need = t[..., LIMBS:LIMBS + 1] > 0
    return jnp.where(need, t[..., :LIMBS], a)


# ---------------------------------------------------------------------------
# field ops (Montgomery domain unless stated)
# ---------------------------------------------------------------------------

def add(a, b):
    t = _norm(a + b, LIMBS + 1)[..., :LIMBS]   # a+b < 2q < 2^384
    return _csub_q(t)


def sub(a, b):
    # a - b + q, computed as a + q + (2^384-1-b) + 1 under a 2^384 bias:
    # the complement turns the borrow chain into the carry chain
    q = jnp.asarray(Q_LIMBS)
    t = a + q + (MASK - b)
    t = t.at[..., 0].add(1)
    t = _norm(t, LIMBS + 2)[..., :LIMBS]       # drop the 2^384 bias
    return _csub_q(t)


def neg(a):
    """-a mod q (Montgomery form preserved); -0 = 0."""
    # q - a under the 2^384 bias (complement trick, as in sub)
    q = jnp.asarray(Q_LIMBS)
    t = q + (MASK - a)
    t = t.at[..., 0].add(1)
    d = _norm(t, LIMBS + 2)[..., :LIMBS]
    is_zero_a = jnp.all(a == 0, axis=-1)
    return jnp.where(is_zero_a[..., None], a, d)


# static Toeplitz gather: c[k] = sum_j a[k-j] * b[j] as one batched matvec
_TOEPLITZ_IDX = np.zeros((2 * LIMBS - 1, LIMBS), dtype=np.int32)
_TOEPLITZ_MASK = np.zeros((2 * LIMBS - 1, LIMBS), dtype=np.uint32)
for _k in range(2 * LIMBS - 1):
    for _j in range(LIMBS):
        if 0 <= _k - _j < LIMBS:
            _TOEPLITZ_IDX[_k, _j] = _k - _j
            _TOEPLITZ_MASK[_k, _j] = 1

# truncated (mod x^LIMBS) Toeplitz for the REDC m-computation
_TOEPLITZ_IDX_LO = _TOEPLITZ_IDX[:LIMBS]
_TOEPLITZ_MASK_LO = _TOEPLITZ_MASK[:LIMBS]

# full-width -q^{-1} mod 2^384 (REDC computes m in one truncated product
# instead of 32 sequential word steps)
_NINV_FULL = (-pow(Q, -1, 1 << (LIMB_BITS * LIMBS))) % \
    (1 << (LIMB_BITS * LIMBS))
_NINV_FULL_LIMBS = np.array(
    [(_NINV_FULL >> (LIMB_BITS * i)) & MASK for i in range(LIMBS)],
    dtype=np.uint32)


def _conv(a, b):
    """Schoolbook polynomial product as a Toeplitz matvec:
    [..., 2*LIMBS-1] coefficient sums, each < 32 * (2^12-1)^2 < 2^29.
    One einsum per call — MXU/VPU-friendly and graph-compact (the pairing
    stacks thousands of these).
    """
    at = a[..., jnp.asarray(_TOEPLITZ_IDX)] * jnp.asarray(_TOEPLITZ_MASK)
    return jnp.einsum("...kj,...j->...k", at, b)


def _conv_lo(a, b):
    """Truncated product mod x^LIMBS (the low 32 coefficient sums)."""
    at = a[..., jnp.asarray(_TOEPLITZ_IDX_LO)] \
        * jnp.asarray(_TOEPLITZ_MASK_LO)
    return jnp.einsum("...kj,...j->...k", at, b)


def _mont_reduce(t):
    """Montgomery REDC of a [..., 2*LIMBS-1] convolution (base 2^12),
    full-width form: m = (T mod R) * (-q^-1 mod R) mod R in ONE
    truncated convolution, then (T + m*q) / R.  Straight-line
    (see the normalization-helpers note): two einsums + three exact
    carry normalizations, no loop primitives.

    Returns canonical limbs of T * R^{-1} mod q.

    Exactness is only needed at the final carry resolution: the interim
    m-computation uses partial compaction.  Bounds: 2 rounds leave
    limbs <= 2^12 + 2^8, so the truncated m-product coefficients stay
    < 32 * 2^13 * 2^12 = 2^30 (uint32-safe), m's integer value is
    < (1 + 2^-4) * 2^384, and (T + m*q)/R < q^2/R + 1.07q < 1.2q —
    still a single conditional subtract.  Truncating partially-carried
    polynomials at limb 32 drops exact multiples of 2^384, which is
    precisely the mod-R the algorithm calls for.
    """
    # T compacted (value-preserving; T < q^2 fits 64 limbs)
    t = _compact(t, 2 * LIMBS + 1, 2)
    # m = T * N' mod 2^384: truncated conv, compact, keep 32 limbs
    m = _conv_lo(t[..., :LIMBS], jnp.asarray(_NINV_FULL_LIMBS))
    m = _compact(m, LIMBS, 2)
    # s = T + m*q == 0 mod 2^384; the high half is the reduced value
    s = _conv(m, jnp.asarray(Q_LIMBS))
    pad_t = jnp.zeros(t.shape[:-1] + (2,), t.dtype)
    pad_s = jnp.zeros(s.shape[:-1] + (4,), s.dtype)
    total = jnp.concatenate([t, pad_t], axis=-1) \
        + jnp.concatenate([s, pad_s], axis=-1)
    total = _norm(total, 2 * LIMBS + 3)
    r = total[..., LIMBS:2 * LIMBS]
    return _csub_q(r)


def mul(a, b):
    """Montgomery product: a * b * R^{-1} mod q."""
    return _mont_reduce(_conv(a, b))


def square(a):
    return mul(a, a)


def to_mont(a):
    """Canonical limbs -> Montgomery form."""
    return mul(a, jnp.broadcast_to(jnp.asarray(R2_LIMBS), a.shape))


def from_mont(a):
    """Montgomery form -> canonical limbs (multiply by 1)."""
    one = jnp.zeros_like(a).at[..., 0].set(1)
    return mul(a, one)


def is_zero(a):
    return jnp.all(a == 0, axis=-1)


def eq(a, b):
    return jnp.all(a == b, axis=-1)


def select(cond, a, b):
    """cond ? a : b, broadcasting cond over the limb axis."""
    return jnp.where(cond[..., None], a, b)


def zeros_like(a):
    return jnp.zeros_like(a)


def one_mont(shape_like):
    """1 in Montgomery form, broadcast to shape_like's batch shape."""
    return jnp.broadcast_to(jnp.asarray(ONE_MONT_LIMBS), shape_like.shape)


# host-side: encode ints straight into Montgomery form
def pack_mont(xs) -> jnp.ndarray:
    return jnp.asarray(
        np.stack([_int_to_limbs_np(x % Q * R_MONT % Q) for x in xs]))


def unpack_mont(v) -> list:
    """Montgomery limb rows -> canonical ints, decoded on the host: one
    bigint multiply by R^-1 per element.  (Decoding through eager
    device `from_mont` dispatched dozens of primitive ops per call —
    over a minute per block flush on the chip.)"""
    arr = np.asarray(v)
    return [from_limbs(arr[i]) * R_INV_MONT % Q for i in range(arr.shape[0])]
