"""G1/G2 Jacobian point arithmetic on TPU limbs.

One generic Jacobian implementation (a = 0 short Weierstrass) instantiated
over the Fq (G1) and Fq2 (G2) limb fields from ops/fq.py and
ops/fq_tower.py.  Points are (X, Y, Z) limb tensors batched over leading
axes; the point at infinity is Z = 0 (X = Y = 1 canonical).

Formulas: dbl-2009-l and add-2007-bl (hyperelliptic.org EFD), complete
via selects — identity/equal/negative inputs handled branchlessly, which
is what lax.scan-driven scalar multiplication needs.

Oracle: crypto/curve.py (same formulas on Python ints).
"""
from __future__ import annotations

import functools
from types import SimpleNamespace

import numpy as np
import jax
import jax.numpy as jnp

from . import fq
from . import fq_tower as ft
from ..crypto.fields import Q
from ..crypto import curve as cv


# ---------------------------------------------------------------------------
# field op tables
# ---------------------------------------------------------------------------

F1 = SimpleNamespace(
    add=fq.add, sub=fq.sub, neg=fq.neg, mul=fq.mul, square=fq.square,
    is_zero=fq.is_zero, eq=fq.eq,
    select=fq.select,
    zero_like=fq.zeros_like,
    one_like=fq.one_mont,
    comp_axes=(-1,),
)

F2 = SimpleNamespace(
    add=ft.fq2_add, sub=ft.fq2_sub, neg=ft.fq2_neg, mul=ft.fq2_mul,
    square=ft.fq2_square, is_zero=ft.fq2_is_zero, eq=ft.fq2_eq,
    select=lambda c, a, b: jnp.where(c[..., None, None], a, b),
    zero_like=lambda a: jnp.zeros_like(a),
    one_like=lambda a: jnp.broadcast_to(
        jnp.asarray(np.stack([fq.ONE_MONT_LIMBS, fq.ZERO_LIMBS])), a.shape),
    comp_axes=(-2, -1),
)


# ---------------------------------------------------------------------------
# generic Jacobian ops
# ---------------------------------------------------------------------------

def point_infinity_like(F, pt):
    X, Y, Z = pt
    return (F.one_like(X), F.one_like(Y), F.zero_like(Z))


def point_is_infinity(F, pt):
    return F.is_zero(pt[2])


def point_double(F, pt):
    X, Y, Z = pt
    A = F.square(X)
    B = F.square(Y)
    C = F.square(B)
    t = F.square(F.add(X, B))
    D = F.add(*[F.sub(F.sub(t, A), C)] * 2)          # 2((X+B)^2 - A - C)
    E = F.add(F.add(A, A), A)                        # 3A
    Fv = F.square(E)
    X3 = F.sub(Fv, F.add(D, D))
    C8 = F.add(*[F.add(*[F.add(C, C)] * 2)] * 2)     # 8C
    Y3 = F.sub(F.mul(E, F.sub(D, X3)), C8)
    Z3 = F.add(*[F.mul(Y, Z)] * 2)                   # 2YZ
    return (X3, Y3, Z3)


def point_add(F, p1, p2):
    """Complete addition via select over {add, double, identity} cases."""
    X1, Y1, Z1 = p1
    X2, Y2, Z2 = p2
    Z1Z1 = F.square(Z1)
    Z2Z2 = F.square(Z2)
    U1 = F.mul(X1, Z2Z2)
    U2 = F.mul(X2, Z1Z1)
    S1 = F.mul(F.mul(Y1, Z2), Z2Z2)
    S2 = F.mul(F.mul(Y2, Z1), Z1Z1)
    H = F.sub(U2, U1)
    r = F.add(*[F.sub(S2, S1)] * 2)                  # 2(S2 - S1)
    I = F.square(F.add(H, H))
    J = F.mul(H, I)
    V = F.mul(U1, I)
    X3 = F.sub(F.sub(F.square(r), J), F.add(V, V))
    S1J2 = F.add(*[F.mul(S1, J)] * 2)
    Y3 = F.sub(F.mul(r, F.sub(V, X3)), S1J2)
    Zs = F.square(F.add(Z1, Z2))
    Z3 = F.mul(F.sub(F.sub(Zs, Z1Z1), Z2Z2), H)
    added = (X3, Y3, Z3)

    doubled = point_double(F, p1)
    inf1 = point_is_infinity(F, p1)
    inf2 = point_is_infinity(F, p2)
    h_zero = F.is_zero(H)
    r_zero = F.is_zero(r)
    same_point = h_zero & r_zero & ~inf1 & ~inf2     # P == Q: double
    opposite = h_zero & ~r_zero & ~inf1 & ~inf2      # P == -Q: infinity

    out = added
    out = tuple(F.select(same_point, d, o) for d, o in zip(doubled, out))
    inf_pt = point_infinity_like(F, p1)
    out = tuple(F.select(opposite, i, o) for i, o in zip(inf_pt, out))
    out = tuple(F.select(inf1, b, o) for b, o in zip(p2, out))
    out = tuple(F.select(inf2, a, o) for a, o in zip(p1, out))
    return out


def point_neg(F, pt):
    return (pt[0], F.neg(pt[1]), pt[2])


def point_scalar_mul(F, pt, scalar_bits):
    """Double-and-add over msb-first bit tensor [..., n_bits] (batched)."""
    acc = point_infinity_like(F, pt)
    nbits = scalar_bits.shape[-1]

    def step(acc, i):
        acc = point_double(F, acc)
        bit = scalar_bits[..., i].astype(bool)
        added = point_add(F, acc, pt)
        acc = tuple(F.select(bit, a, o) for a, o in zip(added, acc))
        return acc, None

    acc, _ = jax.lax.scan(step, acc, jnp.arange(nbits))
    return acc


def point_sum_tree(F, pts):
    """Reduce points stacked on axis 0 ([n, ...]) by pairwise tree adds."""
    X, Y, Z = pts
    n = X.shape[0]
    # pad to a power of two with infinity
    m = 1 << (n - 1).bit_length() if n > 1 else 1
    if m != n:
        pad_pt = point_infinity_like(F, (X[:m - n], Y[:m - n], Z[:m - n]))
        X = jnp.concatenate([X, pad_pt[0]], axis=0)
        Y = jnp.concatenate([Y, pad_pt[1]], axis=0)
        Z = jnp.concatenate([Z, pad_pt[2]], axis=0)
    while X.shape[0] > 1:
        h = X.shape[0] // 2
        left = (X[:h], Y[:h], Z[:h])
        right = (X[h:], Y[h:], Z[h:])
        X, Y, Z = point_add(F, left, right)
    return (X[0], Y[0], Z[0])


def point_sum_seq(F, pts):
    """Reduce points stacked on axis 0 ([n, ...]) with one lax.scan over
    n: a single point_add in the program at any n.  A host-driven
    halving tree instead compiles one add kernel per halving shape —
    ~20-40 s each for the TPU, eight or nine per reduction."""
    acc = point_infinity_like(F, tuple(c[0] for c in pts))

    def step(acc, p):
        return point_add(F, acc, p), None

    acc, _ = jax.lax.scan(step, acc, pts)
    return acc


# NOTE: no fused msm() here on purpose — jitting scalar-mul + the full
# unrolled reduction tree in one graph is what pushed the 4096-point
# MSM compile past the bench budget.  ops/msm.py composes
# g*_scalar_mul with the scanned sum below instead.

# ---------------------------------------------------------------------------
# jitted entry points (compile once per shape; eager dispatch of the limb
# loops is orders of magnitude slower)
# ---------------------------------------------------------------------------

g1_add = jax.jit(lambda p, q: point_add(F1, p, q))
g1_double = jax.jit(lambda p: point_double(F1, p))
g1_scalar_mul = jax.jit(lambda p, bits: point_scalar_mul(F1, p, bits))
g1_sum = jax.jit(lambda p: point_sum_tree(F1, p))
g2_add = jax.jit(lambda p, q: point_add(F2, p, q))
g2_double = jax.jit(lambda p: point_double(F2, p))
g2_scalar_mul = jax.jit(lambda p, bits: point_scalar_mul(F2, p, bits))


@functools.partial(jax.jit, static_argnums=1)
def g1_sum_axis(p, axis):
    """Sum G1 Jacobian limb tensors along `axis` (one scanned add)."""
    return point_sum_seq(F1, tuple(jnp.moveaxis(c, axis, 0) for c in p))


@functools.partial(jax.jit, static_argnums=1)
def g2_sum_axis(p, axis):
    """Sum G2 Jacobian limb tensors along `axis` (one scanned add)."""
    return point_sum_seq(F2, tuple(jnp.moveaxis(c, axis, 0) for c in p))


# ---------------------------------------------------------------------------
# host codecs (oracle interop); scalars -> bit tensors
# ---------------------------------------------------------------------------

def scalars_to_bits(scalars, n_bits: int = 256) -> jnp.ndarray:
    out = np.zeros((len(scalars), n_bits), dtype=np.uint32)
    for i, s in enumerate(scalars):
        s = int(s)
        for j in range(n_bits):
            out[i, j] = (s >> (n_bits - 1 - j)) & 1
    return jnp.asarray(out)


def g1_pack(points) -> tuple:
    """List of oracle G1 Points -> Jacobian limb tensors [n, 32] (Mont)."""
    xs, ys, zs = [], [], []
    for p in points:
        xs.append(p.x.v)
        ys.append(p.y.v)
        zs.append(p.z.v)
    return (fq.pack_mont(xs), fq.pack_mont(ys), fq.pack_mont(zs))


def g1_unpack(pt) -> list:
    X = fq.unpack_mont(pt[0])
    Y = fq.unpack_mont(pt[1])
    Z = fq.unpack_mont(pt[2])
    out = []
    for x, y, z in zip(X, Y, Z):
        out.append(cv.Point(cv.Fq1(x), cv.Fq1(y), cv.Fq1(z), cv.B1))
    return out


def g2_pack(points) -> tuple:
    xs, ys, zs = [], [], []
    for p in points:
        xs.append(p.x)
        ys.append(p.y)
        zs.append(p.z)
    return (ft.fq2_pack_mont(xs), ft.fq2_pack_mont(ys), ft.fq2_pack_mont(zs))


def g2_unpack(pt) -> list:
    X = ft.fq2_unpack_mont(pt[0])
    Y = ft.fq2_unpack_mont(pt[1])
    Z = ft.fq2_unpack_mont(pt[2])
    return [cv.Point(x, y, z, cv.B2) for x, y, z in zip(X, Y, Z)]
