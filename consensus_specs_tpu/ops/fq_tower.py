"""BLS12-381 extension tower on TPU limbs: Fq2 -> Fq6 -> Fq12.

Tower construction matches the oracle (crypto/fields.py):

    Fq2  = Fq[u]  / (u^2 + 1)
    Fq6  = Fq2[v] / (v^3 - XI),  XI = u + 1
    Fq12 = Fq6[w] / (w^2 - v)

Layouts (component-major, limbs last, batched over leading axes):
    fq2  : [..., 2, 32]
    fq6  : [..., 6, 32]   components (c0.a, c0.b, c1.a, c1.b, c2.a, c2.b)
    fq12 : [..., 12, 32]  two fq6 halves

Every multiplication at every tower level is Karatsuba-decomposed and the
leaf Fq products are STACKED into a single batched fq.mul call — one
fq12 mul is one fq.mul over a x54 batch.  That keeps the traced graph
compact (pairing code composes thousands of tower muls) and feeds the TPU
wide, regular batches.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..crypto.fields import Q
from . import fq

# ---------------------------------------------------------------------------
# fq2
# ---------------------------------------------------------------------------

def fq2_add(a, b):
    return fq.add(a, b)


def fq2_sub(a, b):
    return fq.sub(a, b)


def fq2_neg(a):
    return fq.neg(a)


def fq2_conj(a):
    return jnp.concatenate(
        [a[..., 0:1, :], fq.neg(a[..., 1:2, :])], axis=-2)


def fq2_mul(a, b):
    """Karatsuba: 3 stacked Fq products."""
    a0, a1 = a[..., 0, :], a[..., 1, :]
    b0, b1 = b[..., 0, :], b[..., 1, :]
    lhs = jnp.stack([a0, a1, fq.add(a0, a1)], axis=-2)
    rhs = jnp.stack([b0, b1, fq.add(b0, b1)], axis=-2)
    v = fq.mul(lhs, rhs)
    v0, v1, v2 = v[..., 0, :], v[..., 1, :], v[..., 2, :]
    c0 = fq.sub(v0, v1)
    c1 = fq.sub(v2, fq.add(v0, v1))
    return jnp.stack([c0, c1], axis=-2)


def fq2_square(a):
    """(a0+a1)(a0-a1), 2*a0*a1: 2 stacked Fq products."""
    a0, a1 = a[..., 0, :], a[..., 1, :]
    lhs = jnp.stack([fq.add(a0, a1), a0], axis=-2)
    rhs = jnp.stack([fq.sub(a0, a1), a1], axis=-2)
    v = fq.mul(lhs, rhs)
    c0 = v[..., 0, :]
    t = v[..., 1, :]
    return jnp.stack([c0, fq.add(t, t)], axis=-2)


def fq2_mul_xi(a):
    """Multiply by XI = 1 + u: (a0 - a1, a0 + a1)."""
    a0, a1 = a[..., 0, :], a[..., 1, :]
    return jnp.stack([fq.sub(a0, a1), fq.add(a0, a1)], axis=-2)


def fq2_mul_fq(a, s):
    """fq2 element times Fq scalar s [..., 32]."""
    lhs = a
    rhs = jnp.stack([s, s], axis=-2)
    return fq.mul(lhs, rhs)


def fq2_is_zero(a):
    return jnp.all(a == 0, axis=(-1, -2))


def fq2_eq(a, b):
    return jnp.all(a == b, axis=(-1, -2))


# ---------------------------------------------------------------------------
# generic stacked helpers
# ---------------------------------------------------------------------------

def _stack2(xs):
    """Stack a list of fq2 values into [..., k, 2, 32]."""
    return jnp.stack(xs, axis=-3)


def _fq2_mul_many(pairs):
    """One batched fq2 mul over a list of (a, b) fq2 pairs."""
    lhs = _stack2([p[0] for p in pairs])
    rhs = _stack2([p[1] for p in pairs])
    out = fq2_mul(lhs, rhs)
    return [out[..., i, :, :] for i in range(len(pairs))]


# ---------------------------------------------------------------------------
# fq6 (three fq2 coefficients of v^0, v^1, v^2)
# ---------------------------------------------------------------------------

def _fq6_parts(a):
    return a[..., 0:2, :], a[..., 2:4, :], a[..., 4:6, :]


def _fq6_join(c0, c1, c2):
    return jnp.concatenate([c0, c1, c2], axis=-2)


def fq6_add(a, b):
    return fq.add(a, b)


def fq6_sub(a, b):
    return fq.sub(a, b)


def fq6_neg(a):
    return fq.neg(a)


def fq6_mul(a, b):
    """Karatsuba-CH: 6 fq2 products, one stacked call."""
    a0, a1, a2 = _fq6_parts(a)
    b0, b1, b2 = _fq6_parts(b)
    v0, v1, v2, t01, t02, t12 = _fq2_mul_many([
        (a0, b0), (a1, b1), (a2, b2),
        (fq2_add(a0, a1), fq2_add(b0, b1)),
        (fq2_add(a0, a2), fq2_add(b0, b2)),
        (fq2_add(a1, a2), fq2_add(b1, b2)),
    ])
    c0 = fq2_add(v0, fq2_mul_xi(fq2_sub(t12, fq2_add(v1, v2))))
    c1 = fq2_add(fq2_sub(t01, fq2_add(v0, v1)), fq2_mul_xi(v2))
    c2 = fq2_add(fq2_sub(t02, fq2_add(v0, v2)), v1)
    return _fq6_join(c0, c1, c2)


def fq6_square(a):
    return fq6_mul(a, a)


def fq6_mul_by_v(a):
    """(c0, c1, c2) -> (XI*c2, c0, c1)."""
    c0, c1, c2 = _fq6_parts(a)
    return _fq6_join(fq2_mul_xi(c2), c0, c1)


def fq6_mul_fq2(a, s):
    """fq6 times an fq2 scalar: 3 stacked fq2 products."""
    c0, c1, c2 = _fq6_parts(a)
    r0, r1, r2 = _fq2_mul_many([(c0, s), (c1, s), (c2, s)])
    return _fq6_join(r0, r1, r2)


# ---------------------------------------------------------------------------
# fq12 (two fq6 coefficients of w^0, w^1)
# ---------------------------------------------------------------------------

def _fq12_parts(a):
    return a[..., 0:6, :], a[..., 6:12, :]


def _fq12_join(c0, c1):
    return jnp.concatenate([c0, c1], axis=-2)


def fq12_add(a, b):
    return fq.add(a, b)


def fq12_sub(a, b):
    return fq.sub(a, b)


def fq12_mul(a, b):
    """Karatsuba over fq6: 3 fq6 products as one stacked call."""
    a0, a1 = _fq12_parts(a)
    b0, b1 = _fq12_parts(b)
    lhs = jnp.stack([a0, a1, fq6_add(a0, a1)], axis=-3)
    rhs = jnp.stack([b0, b1, fq6_add(b0, b1)], axis=-3)
    v = fq6_mul(lhs, rhs)
    v0, v1, v2 = v[..., 0, :, :], v[..., 1, :, :], v[..., 2, :, :]
    c0 = fq6_add(v0, fq6_mul_by_v(v1))
    c1 = fq6_sub(v2, fq6_add(v0, v1))
    return _fq12_join(c0, c1)


def fq12_square(a):
    """2 fq6-mul squaring: t = a0*a1; c0 = (a0+a1)(a0+v*a1) - t - v*t;
    c1 = 2t (the hot op of the final exponentiation)."""
    a0, a1 = _fq12_parts(a)
    lhs = jnp.stack([a0, fq6_add(a0, a1)], axis=-3)
    rhs = jnp.stack([a1, fq6_add(a0, fq6_mul_by_v(a1))], axis=-3)
    v = fq6_mul(lhs, rhs)
    t, s = v[..., 0, :, :], v[..., 1, :, :]
    c0 = fq6_sub(s, fq6_add(t, fq6_mul_by_v(t)))
    return _fq12_join(c0, fq6_add(t, t))


def fq12_conj(a):
    """Conjugation f^(q^6): negate the w coefficient.  For unitary f
    (post easy-part) this is the inverse."""
    a0, a1 = _fq12_parts(a)
    return _fq12_join(a0, fq6_neg(a1))


def fq12_one(batch_shape=()):
    one = jnp.zeros(batch_shape + (12, fq.LIMBS), dtype=jnp.uint32)
    return one.at[..., 0, :].set(jnp.asarray(fq.ONE_MONT_LIMBS))


def fq12_is_one(a):
    return jnp.all(a == fq12_one(a.shape[:-2]), axis=(-1, -2))


def fq12_select(cond, a, b):
    return jnp.where(cond[..., None, None], a, b)


# ---------------------------------------------------------------------------
# frobenius + cyclotomic squaring (final-exponentiation fast path)
# ---------------------------------------------------------------------------

# fq12 layout component j -> basis w^k, k = _FROB_K[j]; frobenius scales
# conj(comp_j) by XI^(k(q-1)/6)
_FROB_K = (0, 2, 4, 1, 3, 5)


def _frob_gamma_limbs() -> np.ndarray:
    # single source of truth: the oracle's table (crypto/fields.py
    # _FROB_GAMMA = XI^(k(q-1)/6)), re-packed into Montgomery limbs
    from ..crypto.fields import _FROB_GAMMA
    gammas = [_FROB_GAMMA[k] for k in _FROB_K]
    return np.stack(
        [np.asarray(fq.pack_mont([g.c0, g.c1])) for g in gammas])


_FROB_GAMMA_LIMBS = _frob_gamma_limbs()


def fq12_frobenius(a, power: int = 1):
    """x -> x^(q^power); one batched fq2 mul per application."""
    out = a
    for _ in range(power):
        v = out.reshape(out.shape[:-2] + (6, 2, fq.LIMBS))
        v = jnp.concatenate(
            [v[..., 0:1, :], fq.neg(v[..., 1:2, :])], axis=-2)   # conj
        v = fq2_mul(v, jnp.asarray(_FROB_GAMMA_LIMBS))
        out = v.reshape(a.shape)
    return out


def fq12_cyclotomic_square(a):
    """Granger-Scott squaring for unitary elements: three Fq4 squarings,
    all nine underlying fq2 squares in ONE stacked call (vs 12 fq2 muls
    for a generic fq12_square).  Mirrors Fq12.cyclotomic_square."""
    c = a.reshape(a.shape[:-2] + (6, 2, fq.LIMBS))
    z0, z4, z3 = c[..., 0, :, :], c[..., 1, :, :], c[..., 2, :, :]
    z2, z1, z5 = c[..., 3, :, :], c[..., 4, :, :], c[..., 5, :, :]

    s = fq2_square(jnp.stack(
        [z0, z1, fq2_add(z0, z1),
         z2, z3, fq2_add(z2, z3),
         z4, z5, fq2_add(z4, z5)], axis=-3))

    def fp4(i):
        t0, t1, tsum = (s[..., i, :, :], s[..., i + 1, :, :],
                        s[..., i + 2, :, :])
        return (fq2_add(fq2_mul_xi(t1), t0),
                fq2_sub(fq2_sub(tsum, t0), t1))

    def dbl_plus(t, z, sign):
        """2*(t +/- z) + t."""
        base = fq2_sub(t, z) if sign < 0 else fq2_add(t, z)
        return fq2_add(fq2_add(base, base), t)

    t0, t1 = fp4(0)
    z0n = dbl_plus(t0, z0, -1)
    z1n = dbl_plus(t1, z1, +1)
    ta0, ta1 = fp4(3)
    tb0, tb1 = fp4(6)
    z4n = dbl_plus(ta0, z4, -1)
    z5n = dbl_plus(ta1, z5, +1)
    t = fq2_mul_xi(tb1)
    z2n = dbl_plus(t, z2, +1)
    z3n = dbl_plus(tb0, z3, -1)

    return jnp.concatenate([z0n, z4n, z3n, z2n, z1n, z5n], axis=-2)


# ---------------------------------------------------------------------------
# inversion (tower descent; Fq inverse by fixed-exponent power)
# ---------------------------------------------------------------------------

_QM2_BITS = np.array(
    [int(b) for b in bin(Q - 2)[2:]], dtype=np.uint32)  # msb-first


def fq_inv(a):
    """a^(q-2) by square-and-multiply scan over the fixed exponent."""
    def step(acc, bit):
        acc = fq.square(acc)
        acc = fq.select(jnp.broadcast_to(bit.astype(bool), acc.shape[:-1]),
                        fq.mul(acc, a), acc)
        return acc, None
    init = jnp.broadcast_to(jnp.asarray(fq.ONE_MONT_LIMBS), a.shape)
    out, _ = jax.lax.scan(step, init, jnp.asarray(_QM2_BITS))
    return out


def fq2_inv(a):
    """(a0 - a1 u) / (a0^2 + a1^2)."""
    a0, a1 = a[..., 0, :], a[..., 1, :]
    sq = fq.mul(jnp.stack([a0, a1], axis=-2), jnp.stack([a0, a1], axis=-2))
    norm = fq.add(sq[..., 0, :], sq[..., 1, :])
    ninv = fq_inv(norm)
    out = fq.mul(jnp.stack([a0, fq.neg(a1)], axis=-2),
                 jnp.stack([ninv, ninv], axis=-2))
    return out


def fq6_inv(a):
    a0, a1, a2 = _fq6_parts(a)
    v0, v1, v2, v3, v4, v5 = _fq2_mul_many([
        (a0, a0), (a1, a1), (a2, a2), (a0, a1), (a0, a2), (a1, a2)])
    c0 = fq2_sub(v0, fq2_mul_xi(v5))
    c1 = fq2_sub(fq2_mul_xi(v2), v3)
    c2 = fq2_sub(v1, v4)
    t0, t1, t2 = _fq2_mul_many([(a0, c0), (a2, c1), (a1, c2)])
    norm = fq2_add(t0, fq2_mul_xi(fq2_add(t1, t2)))
    ninv = fq2_inv(norm)
    return fq6_mul_fq2(_fq6_join(c0, c1, c2), ninv)


def fq12_inv(a):
    a0, a1 = _fq12_parts(a)
    t = fq6_sub(fq6_mul(a0, a0), fq6_mul_by_v(fq6_mul(a1, a1)))
    tinv = fq6_inv(t)
    c0 = fq6_mul(a0, tinv)
    c1 = fq6_neg(fq6_mul(a1, tinv))
    return _fq12_join(c0, c1)


# ---------------------------------------------------------------------------
# fixed-exponent fq12 power (scan over precomputed bits)
# ---------------------------------------------------------------------------

def fq12_pow_fixed(a, exponent_bits: np.ndarray):
    """a^e for a fixed (host-known) exponent given as msb-first bit array."""
    def step(acc, bit):
        acc = fq12_square(acc)
        take = jnp.broadcast_to(bit.astype(bool), acc.shape[:-2])
        acc = fq12_select(take, fq12_mul(acc, a), acc)
        return acc, None
    init = fq12_one(a.shape[:-2])
    out, _ = jax.lax.scan(step, init, jnp.asarray(exponent_bits))
    return out


# ---------------------------------------------------------------------------
# host codecs (oracle interop)
# ---------------------------------------------------------------------------

def fq2_pack_mont(vals) -> jnp.ndarray:
    """List of oracle Fq2 (crypto.fields.Fq2) -> [n, 2, 32] Montgomery."""
    return jnp.asarray(np.stack(
        [np.asarray(fq.pack_mont([v.c0, v.c1])) for v in vals]))


def fq2_unpack_mont(arr):
    from ..crypto.fields import Fq2
    a = np.asarray(arr)
    c = fq.unpack_mont(a.reshape(-1, fq.LIMBS))
    return [Fq2(c[2 * i], c[2 * i + 1]) for i in range(a.shape[0])]


def fq6_pack_mont(vals) -> jnp.ndarray:
    return jnp.asarray(np.stack(
        [np.asarray(fq.pack_mont([v.c0.c0, v.c0.c1, v.c1.c0, v.c1.c1,
                                  v.c2.c0, v.c2.c1])) for v in vals]))


def fq6_unpack_mont(arr):
    from ..crypto.fields import Fq2, Fq6
    a = np.asarray(arr)
    out = []
    for i in range(a.shape[0]):
        c = fq.unpack_mont(a[i])
        out.append(Fq6(Fq2(c[0], c[1]), Fq2(c[2], c[3]), Fq2(c[4], c[5])))
    return out


def fq12_pack_mont(vals) -> jnp.ndarray:
    out = []
    for v in vals:
        comps = [v.c0.c0.c0, v.c0.c0.c1, v.c0.c1.c0, v.c0.c1.c1,
                 v.c0.c2.c0, v.c0.c2.c1,
                 v.c1.c0.c0, v.c1.c0.c1, v.c1.c1.c0, v.c1.c1.c1,
                 v.c1.c2.c0, v.c1.c2.c1]
        out.append(np.asarray(fq.pack_mont(comps)))
    return jnp.asarray(np.stack(out))


def fq12_unpack_mont(arr):
    from ..crypto.fields import Fq2, Fq6, Fq12
    a = np.asarray(arr)
    out = []
    for i in range(a.shape[0]):
        c = fq.unpack_mont(a[i])
        out.append(Fq12(
            Fq6(Fq2(c[0], c[1]), Fq2(c[2], c[3]), Fq2(c[4], c[5])),
            Fq6(Fq2(c[6], c[7]), Fq2(c[8], c[9]), Fq2(c[10], c[11]))))
    return out
