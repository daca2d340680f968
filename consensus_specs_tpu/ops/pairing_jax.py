"""Optimal ate pairing on BLS12-381 over TPU limbs, batched.

Miller loop with the twist trick: Q stays on the M-twist E'(Fq2)
(y^2 = x^3 + 4(u+1)); the untwist psi(x,y) = (x/w^2, y/w^3) maps to
E(Fq12).  Lines through untwisted points, evaluated at P in G1 and scaled
by w^3 and by per-line Fq2 denominators, land in the sparse Fq12 form
c0 + c1*v + c4*vw.  Both scalings are killed by the final exponentiation
(their orders divide 2(q^2-1) | (q^6-1)(q^2+1)), so the pairing value is
exact — this is the derivation behind the standard "mul_by_014" line
update in production pairing libraries.

Final exponentiation: easy part f^((q^6-1)(q^2+1)) via conjugate/inverse
and one Frobenius, then the standard BLS12 x-chain hard part (cyclotomic
squarings + 5 exponentiations by |x| + Frobenius maps) computing
m^(3(q^4-q^2+1)/r) — see final_exponentiation for why the factor 3 is
sound.

Oracle: crypto/pairing.py (untwist-into-Fq12 affine implementation).
Verified identities: bilinearity and e(aG1, bG2) == e(G1, G2)^(ab).
"""
from __future__ import annotations

import functools
import os as _os

import numpy as np
import jax
import jax.numpy as jnp

# dispatch granularity (PAIRING_MODE env) — see the mode notes above
# pairing_check.  Default is `staged` on every platform: on CPU hosts
# its per-step kernels compile in seconds, and on a TPU v5e the fused
# one-program check does not fit a cold run (the compile rehearsal of
# PR 21: ~10 min for one small bucket, against ~25-70 s per staged
# kernel).  The env var is read at resolve time, not import time (same
# lazy discipline as msm.MSM_MODE / g1_sweep.G1_SWEEP_MODE), with
# reset_mode() forgetting a cached choice.
PAIRING_MODE = None
_CHUNK_BITS = 8


def reset_mode() -> None:
    """Forget the cached dispatch-granularity choice: the next check
    re-reads the PAIRING_MODE env var."""
    global PAIRING_MODE
    PAIRING_MODE = None


def _resolve_mode() -> str:
    global PAIRING_MODE
    if PAIRING_MODE is None:
        PAIRING_MODE = _os.environ.get("PAIRING_MODE") or "staged"
    return PAIRING_MODE

from . import fq
from . import fq_tower as ft

BLS_X_ABS = 0xD201000000010000          # |x|, x negative for BLS12-381

# miller-loop / exp-by-x bit sequence: bits of |x| msb-first, skipping the
# leading 1
_MILLER_BITS = np.array(
    [int(b) for b in bin(BLS_X_ABS)[3:]], dtype=np.uint32)


# ---------------------------------------------------------------------------
# sparse line -> fq12 embedding
# ---------------------------------------------------------------------------

def _line_to_fq12(c0, c1, c4):
    """Line c0 + c1*v + c4*vw as a full fq12 tensor.

    fq12 component order: [c0.a, c0.b, (v) a, b, (v^2) a, b,
                           (w) a, b, (vw) a, b, (v^2 w) a, b].
    """
    batch = c0.shape[:-2]
    zeros = jnp.zeros(batch + (2, fq.LIMBS), dtype=jnp.uint32)
    return jnp.concatenate(
        [c0, c1, zeros, zeros, c4, zeros], axis=-2)


# ---------------------------------------------------------------------------
# miller loop steps (twist-point Jacobian, line coeffs in fq2)
# ---------------------------------------------------------------------------

def _double_step(T, xp, yp):
    """Tangent line at T evaluated at P=(xp, yp), plus T <- 2T.

    Line (scaled by w^3 and 2YZ^3): c0 = 3X^3 - 2Y^2, c1 = -3X^2 Z^2 xp,
    c4 = 2 Y Z^3 yp.
    """
    X, Y, Z = T
    X2 = ft.fq2_square(X)
    Y2 = ft.fq2_square(Y)
    Z2 = ft.fq2_square(Z)
    X3 = ft.fq2_mul(X, X2)
    Z3 = ft.fq2_mul(Z, Z2)
    threeX3 = ft.fq2_add(X3, ft.fq2_add(X3, X3))
    c0 = ft.fq2_sub(threeX3, ft.fq2_add(Y2, Y2))
    threeX2Z2 = ft.fq2_mul(X2, Z2)
    threeX2Z2 = ft.fq2_add(threeX2Z2, ft.fq2_add(threeX2Z2, threeX2Z2))
    c1 = ft.fq2_neg(ft.fq2_mul_fq(threeX2Z2, xp))
    YZ3 = ft.fq2_mul(Y, Z3)
    c4 = ft.fq2_mul_fq(ft.fq2_add(YZ3, YZ3), yp)

    # dbl-2009-l (a = 0)
    B = Y2
    C = ft.fq2_square(B)
    t = ft.fq2_square(ft.fq2_add(X, B))
    D = ft.fq2_sub(ft.fq2_sub(t, X2), C)
    D = ft.fq2_add(D, D)
    E = ft.fq2_add(X2, ft.fq2_add(X2, X2))
    F = ft.fq2_square(E)
    Xn = ft.fq2_sub(F, ft.fq2_add(D, D))
    C8 = ft.fq2_add(C, C)
    C8 = ft.fq2_add(C8, C8)
    C8 = ft.fq2_add(C8, C8)
    Yn = ft.fq2_sub(ft.fq2_mul(E, ft.fq2_sub(D, Xn)), C8)
    Zn = ft.fq2_mul(Y, Z)
    Zn = ft.fq2_add(Zn, Zn)
    return (Xn, Yn, Zn), (c0, c1, c4)


def _add_step(T, Qa, xp, yp):
    """Line through T and affine twist point Qa=(x2,y2) at P, plus T <- T+Q.

    With theta = Y1 - y2 Z1^3 and lam = X1 - x2 Z1^2 (scaled by Z1*lam):
    c0 = theta*x2 - y2*Z1*lam, c1 = -theta*xp, c4 = Z1*lam*yp.
    """
    X1, Y1, Z1 = T
    x2, y2 = Qa
    Z1Z1 = ft.fq2_square(Z1)
    U2 = ft.fq2_mul(x2, Z1Z1)
    S2 = ft.fq2_mul(y2, ft.fq2_mul(Z1, Z1Z1))
    theta = ft.fq2_sub(Y1, S2)
    lam = ft.fq2_sub(X1, U2)
    Z1lam = ft.fq2_mul(Z1, lam)
    c0 = ft.fq2_sub(ft.fq2_mul(theta, x2), ft.fq2_mul(y2, Z1lam))
    c1 = ft.fq2_neg(ft.fq2_mul_fq(theta, xp))
    c4 = ft.fq2_mul_fq(Z1lam, yp)

    # madd-2007-bl (mixed addition, a = 0)
    H = ft.fq2_neg(lam)                      # U2 - X1
    HH = ft.fq2_square(H)
    I = ft.fq2_add(HH, HH)
    I = ft.fq2_add(I, I)
    J = ft.fq2_mul(H, I)
    r = ft.fq2_neg(theta)                    # S2 - Y1
    r = ft.fq2_add(r, r)
    V = ft.fq2_mul(X1, I)
    Xn = ft.fq2_sub(ft.fq2_sub(ft.fq2_square(r), J), ft.fq2_add(V, V))
    YJ = ft.fq2_mul(Y1, J)
    Yn = ft.fq2_sub(ft.fq2_mul(r, ft.fq2_sub(V, Xn)), ft.fq2_add(YJ, YJ))
    Zn = ft.fq2_mul(Z1, H)
    Zn = ft.fq2_add(Zn, Zn)                  # madd-2007-bl: Z3 = 2*Z1*H
    return (Xn, Yn, Zn), (c0, c1, c4)


@jax.jit
def _miller_step_double(f, T, xp, yp):
    """One doubling step: f <- f^2 * l_{T,T}(P); T <- 2T."""
    T, (c0, c1, c4) = _double_step(T, xp, yp)
    f = ft.fq12_mul(ft.fq12_square(f), _line_to_fq12(c0, c1, c4))
    return f, T


@jax.jit
def _miller_step_add(f, T, xq, yq, xp, yp):
    """One addition step: f <- f * l_{T,Q}(P); T <- T + Q."""
    T, (c0, c1, c4) = _add_step(T, (xq, yq), xp, yp)
    f = ft.fq12_mul(f, _line_to_fq12(c0, c1, c4))
    return f, T


@jax.jit
def _miller_finish(f, skip):
    f = ft.fq12_conj(f)         # x < 0
    if skip is not None:
        f = ft.fq12_select(skip, ft.fq12_one(f.shape[:-2]), f)
    return f


def miller_loop(xp, yp, xq, yq, skip=None):
    """Batched Miller loop, host-staged over the (static) bits of |x|.

    xp, yp: G1 affine coords, Montgomery limbs [..., 32].
    xq, yq: twist G2 affine coords, [..., 2, 32].
    skip: optional bool [...] — pairs whose contribution is forced to one
    (how infinity points enter: they have no affine coords, and
    e(O, Q) = e(P, O) = 1; callers substitute any valid point and set
    skip, matching the oracle's miller_loop infinity short-circuit).
    Returns f in Fq12 [..., 12, 32] (already conjugated for x < 0).

    The loop bits are host constants, so each iteration dispatches one of
    two jitted step kernels (compiled once per batch shape) instead of
    tracing a 63-step scan body: compile time collapses, and the 58
    zero-bits skip the addition step entirely (the old scan computed and
    discarded it).
    """
    batch = xp.shape[:-1]
    one2 = jnp.broadcast_to(
        jnp.asarray(np.stack([fq.ONE_MONT_LIMBS, fq.ZERO_LIMBS])),
        batch + (2, fq.LIMBS))
    T = (xq, yq, one2)
    f = ft.fq12_one(batch)
    for bit in _MILLER_BITS.tolist():
        f, T = _miller_step_double(f, T, xp, yp)
        if bit:
            f, T = _miller_step_add(f, T, xq, yq, xp, yp)
    return _miller_finish(f, skip)


def _easy_part(f):
    """f^((q^6-1)(q^2+1)): lands in the cyclotomic subgroup."""
    f1 = ft.fq12_mul(ft.fq12_conj(f), ft.fq12_inv(f))   # f^(q^6-1)
    return ft.fq12_mul(ft.fq12_frobenius(f1, 2), f1)


def _hard_chain(m, *, cyc, mul, conj, frob, expx):
    """The standard BLS12 x-chain hard part: m^(3(q^4-q^2+1)/r).

    Written against an op table (the host-dispatched jitted stages of
    final_exponentiation_staged) and kept step-compatible with the oracle
    chain in crypto/pairing.py::_hard_part.
    """
    t2 = m
    t1 = conj(cyc(t2))                  # m^-2
    t3 = expx(t2)                       # m^x
    t4 = cyc(t3)                        # m^2x
    t5 = mul(t1, t3)                    # m^(x-2)
    t1 = expx(t5)                       # m^(x^2-2x)
    t0 = expx(t1)                       # m^(x^3-2x^2)
    t6 = expx(t0)                       # m^(x^4-2x^3)
    t6 = mul(t6, t4)                    # m^(x^4-2x^3+2x)
    t4 = expx(t6)
    t5 = conj(t5)
    t4 = mul(mul(t4, t5), t2)
    t5 = conj(t2)
    t1 = mul(t1, t2)                    # m^(x^2-2x+1)
    t1 = frob(t1, 3)
    t6 = mul(t6, t5)
    t6 = frob(t6, 1)
    t3 = mul(t3, t0)
    t3 = frob(t3, 2)
    t3 = mul(t3, t1)
    t3 = mul(t3, t6)
    return mul(t3, t4)


# -- staged execution: each stage is jitted once per batch shape and the
# five exp-by-x dispatches REUSE one executable, instead of tracing five
# copies of the 63-step scan into a single monolithic graph (which is what
# made the round-1 pairing compile take minutes)
_easy_jit = jax.jit(_easy_part)
_cyc_jit = jax.jit(ft.fq12_cyclotomic_square)
_mul_jit = jax.jit(ft.fq12_mul)
_conj_jit = jax.jit(ft.fq12_conj)
_frob_jit = jax.jit(ft.fq12_frobenius, static_argnums=1)
_is_one_jit = jax.jit(ft.fq12_is_one)


def _exp_by_neg_x_staged(m):
    """Host-unrolled exp-by-|x| over jitted cyclotomic squarings; the bit
    pattern is static, so the 58 zero-bits dispatch just the squaring."""
    acc = m
    for bit in _MILLER_BITS.tolist():
        acc = _cyc_jit(acc)
        if bit:
            acc = _mul_jit(acc, m)
    return _conj_jit(acc)


def final_exponentiation_staged(f):
    """f^(3(q^12-1)/r): host-composed final exponentiation over jitted
    stages (easy part, then the x-chain hard part — 5 exp-by-x + 3
    Frobenius, ~40x less Fq12 work than a full square-and-multiply).
    Intermediate values stay on device and only small per-stage kernels
    ever compile.  The factor 3 is inherent to the chain and harmless:
    cubing is a bijection on the order-r target subgroup, and the oracle
    (crypto/pairing.py) applies the identical chain."""
    return _hard_chain(
        _easy_jit(f), cyc=_cyc_jit, mul=_mul_jit, conj=_conj_jit,
        frob=_frob_jit, expx=_exp_by_neg_x_staged)


def _prod_reduce_raw(f):
    """Fq12 product over the pairs axis: [..., k, 12, 32] -> [..., 12, 32].

    A halving tree with ONE fq12_mul in a fori_loop body (the pairs
    axis padded with ones to a power of two): lane i multiplies in lane
    i + h for h = k/2, k/4, ..., 1, so lane 0 ends with the product.
    Exact canonical Fq12 arithmetic, so the order is invisible; the
    unrolled left fold it replaces put k-1 multiplies into the program
    (255 at a full block flush)."""
    k = f.shape[-3]
    if k == 1:
        return f[..., 0, :, :]
    kp = 1 << (k - 1).bit_length()
    if kp != k:
        f = jnp.concatenate(
            [f, ft.fq12_one(f.shape[:-3] + (kp - k,))], axis=-3)
    idx = jnp.arange(kp)

    def halve(r, X):
        h = jnp.right_shift(kp, r + 1)
        return ft.fq12_mul(X, jnp.take(X, (idx + h) % kp, axis=-3))

    X = jax.lax.fori_loop(0, kp.bit_length() - 1, halve, f)
    return X[..., 0, :, :]


_prod_reduce = jax.jit(_prod_reduce_raw)


# ---------------------------------------------------------------------------
# fused single-kernel path (lax.scan)
# ---------------------------------------------------------------------------
# The staged path above dispatches one jitted kernel per Miller bit /
# ladder step — ~650 launches per pairing check.  The fused path rolls
# both ladders into lax.scan bodies (compiled ONCE — scan does not
# unroll) and runs the whole check in a single launch.  The zero-bits
# pay a wasted add-step/multiply under a select, ~40% extra Fq12 work.
# Its compile is the cost: far longer than the staged kernels', which
# is why no platform selects it by default.

def _miller_scan(xp, yp, xq, yq):
    """Miller loop as one lax.scan over the bits of |x|."""
    batch = xp.shape[:-1]
    one2 = jnp.broadcast_to(
        jnp.asarray(np.stack([fq.ONE_MONT_LIMBS, fq.ZERO_LIMBS])),
        batch + (2, fq.LIMBS))
    f0 = ft.fq12_one(batch)
    bits = jnp.asarray(_MILLER_BITS)

    def body(carry, bit):
        f, T = carry
        T, (c0, c1, c4) = _double_step(T, xp, yp)
        f = ft.fq12_mul(ft.fq12_square(f), _line_to_fq12(c0, c1, c4))
        Ta, (a0, a1, a4) = _add_step(T, (xq, yq), xp, yp)
        fa = ft.fq12_mul(f, _line_to_fq12(a0, a1, a4))
        take = bit.astype(bool)
        f = jnp.where(take, fa, f)
        T = tuple(jnp.where(take, a, b) for a, b in zip(Ta, T))
        return (f, T), None

    (f, _T), _ = jax.lax.scan(body, (f0, (xq, yq, one2)), bits)
    return ft.fq12_conj(f)          # x < 0


def _exp_by_neg_x_scan(m):
    """exp-by-|x| ladder as one lax.scan (square always, multiply under
    a select on the bit)."""
    def body(acc, bit):
        acc = ft.fq12_cyclotomic_square(acc)
        acc = jnp.where(bit.astype(bool), ft.fq12_mul(acc, m), acc)
        return acc, None
    acc, _ = jax.lax.scan(body, m, jnp.asarray(_MILLER_BITS))
    return ft.fq12_conj(acc)


@jax.jit
def _pairing_check_fused(xps, yps, xqs, yqs, skip):
    """Whole batched check — Miller product, final exponentiation,
    is-one — as ONE compiled program."""
    f = _miller_scan(xps, yps, xqs, yqs)
    f = ft.fq12_select(skip, ft.fq12_one(f.shape[:-2]), f)
    f = _prod_reduce_raw(f)
    m = _easy_part(f)
    v = _hard_chain(
        m, cyc=ft.fq12_cyclotomic_square, mul=ft.fq12_mul,
        conj=ft.fq12_conj, frob=ft.fq12_frobenius,
        expx=_exp_by_neg_x_scan)
    return ft.fq12_is_one(v)


# ---------------------------------------------------------------------------
# partial-product surface (the mesh-sharded verify path)
# ---------------------------------------------------------------------------
# parallel/shard_verify.py partitions one big pairing product's pairs
# axis over the device mesh: each shard needs its slice's Miller
# product WITHOUT the final exponentiation (partials are all-reduced by
# Fp12 multiply first, then ONE final exponentiation decides the whole
# product).  These two helpers expose exactly that split, mode-split
# like pairing_check: staged per-bit kernels (the default), or one
# fused scan program per piece.

@jax.jit
def _miller_partial_fused(xps, yps, xqs, yqs, skip):
    f = _miller_scan(xps, yps, xqs, yqs)
    f = ft.fq12_select(skip, ft.fq12_one(f.shape[:-2]), f)
    return _prod_reduce_raw(f)


@jax.jit
def _final_exp_is_one_fused(f):
    m = _easy_part(f)
    v = _hard_chain(
        m, cyc=ft.fq12_cyclotomic_square, mul=ft.fq12_mul,
        conj=ft.fq12_conj, frob=ft.fq12_frobenius,
        expx=_exp_by_neg_x_scan)
    return ft.fq12_is_one(v)


def miller_partial_products(xps, yps, xqs, yqs, skip):
    """Fq12 Miller product over the trailing pairs axis, NO final
    exponentiation: xps [..., k, 32] (+ G2/skip shapes as in
    pairing_check) -> [..., 12, 32].  Inputs sharded on a leading mesh
    axis stay sharded — the batch math is elementwise over that axis,
    so each device computes exactly its rows' partial."""
    mode = _resolve_mode()
    if mode == "fused":
        return _miller_partial_fused(xps, yps, xqs, yqs, skip)
    if mode == "chunked":
        return _prod_reduce(_miller_chunked(xps, yps, xqs, yqs, skip))
    return _prod_reduce(miller_loop(xps, yps, xqs, yqs, skip))


def fq12_product_is_one(partials):
    """prod_i partials[i] == 1 over the leading axis: host-driven
    halving-tree Fq12 multiplies (log2(n) launches — on a sharded axis
    these are the cross-shard all-reduce) into ONE final exponentiation
    + is-one.  partials [n, 12, 32] -> scalar bool (on device)."""
    X = partials
    while X.shape[0] > 1:
        h = X.shape[0] // 2
        X = _mul_jit(X[:h], X[h:])
    mode = _resolve_mode()
    if mode == "fused":
        return _final_exp_is_one_fused(X)[0]
    if mode == "chunked":
        return _is_one_jit(final_exponentiation_chunked(X))[0]
    return _is_one_jit(final_exponentiation_staged(X))[0]


# ---------------------------------------------------------------------------
# folded-flush surface (the one-launch fused verify path)
# ---------------------------------------------------------------------------
# sigpipe/fold.py folds every signature leg of a fused flush into ONE
# e(-g1, S) pair over the G2 MSM S = sum_i c_i * sig_i; on the tpu
# backend the whole folded flush fuses into one compiled program PER
# MESH SHARD (parallel/shard_verify.pairing_fold): the hash-to-G2
# cofactor ladder, the Fiat-Shamir G1 weighting ladder, the local G2
# signature MSM, in-program Jacobian->affine conversion (batched
# Fermat inversion — ft.fq_inv / ft.fq2_inv), and the partial Miller
# product over the shard's k+1 pairs — its k weighted-aggregate legs
# plus one e(-g1, S_d) leg over the shard's LOCAL partial MSM.  The
# per-shard S_d legs are sound because the final exponentiation
# restores bilinearity: FE(prod_d miller(-g1, S_d)) ==
# prod_d e(-g1, S_d) == e(-g1, sum_d S_d), so the all-reduced product
# decides exactly the folded check at any mesh width.  Mode-split like
# everything here: `fused` composes the whole body under one jit (one
# launch per shard); staged drives the existing per-piece kernels —
# identical math, what the CPU kernel tier verifies.

def _h_eff_bits():
    """The cofactor ladder's bit vector — bls_tpu's precomputed
    `_H_EFF_BITS`, imported lazily (bls_tpu imports this module at its
    top level, so an eager import here would cycle).  ONE copy on
    purpose: the fold program's cofactor ladder must walk exactly the
    bits `hash_to_g2_batch` walks."""
    from .bls_tpu import _H_EFF_BITS
    return _H_EFF_BITS


def _g1_jacobian_to_affine(P, sub_x, sub_y):
    """Batched Jacobian->affine over G1 limbs: (x, y, inf).  Infinity
    rows (Z == 0) read the substitute coords (the generator — a valid
    curve point, the established skip-row idiom) and set the mask."""
    X, Y, Z = P
    inf = fq.is_zero(Z)
    Zs = fq.select(inf, fq.one_mont(Z), Z)
    zi = ft.fq_inv(Zs)
    zi2 = fq.square(zi)
    x = fq.mul(X, zi2)
    y = fq.mul(Y, fq.mul(zi2, zi))
    x = fq.select(inf, jnp.broadcast_to(sub_x, x.shape), x)
    y = fq.select(inf, jnp.broadcast_to(sub_y, y.shape), y)
    return x, y, inf


def _g2_jacobian_to_affine(P, sub_x, sub_y):
    """Batched Jacobian->affine over G2 (Fq2) limbs: (x, y, inf)."""
    X, Y, Z = P
    inf = ft.fq2_is_zero(Z)
    one2 = jnp.broadcast_to(
        jnp.asarray(np.stack([fq.ONE_MONT_LIMBS, fq.ZERO_LIMBS])), Z.shape)
    Zs = jnp.where(inf[..., None, None], one2, Z)
    zi = ft.fq2_inv(Zs)
    zi2 = ft.fq2_square(zi)
    x = ft.fq2_mul(X, zi2)
    y = ft.fq2_mul(Y, ft.fq2_mul(zi2, zi))
    x = jnp.where(inf[..., None, None], jnp.broadcast_to(sub_x, x.shape), x)
    y = jnp.where(inf[..., None, None], jnp.broadcast_to(sub_y, y.shape), y)
    return x, y, inf


_FOLD_CONSTS = None     # lazy: packed once, reused every flush


def _fold_consts():
    """Host-packed affine constants the fold program substitutes and
    appends: (g1 gen x/y, g2 gen x/y, -g1 x/y), each [32] / [2, 32].
    Cached — the packing (host bigint affine conversions) would
    otherwise rerun on the hot path once per folded flush."""
    global _FOLD_CONSTS
    if _FOLD_CONSTS is None:
        from ..crypto import curve as cv
        g1x, g1y = cv.g1_generator().affine()
        g2x, g2y = cv.g2_generator().affine()
        n1x, n1y = (-cv.g1_generator()).affine()
        _FOLD_CONSTS = (
            fq.pack_mont([g1x.v])[0], fq.pack_mont([g1y.v])[0],
            ft.fq2_pack_mont([g2x])[0], ft.fq2_pack_mont([g2y])[0],
            fq.pack_mont([n1x.v])[0], fq.pack_mont([n1y.v])[0])
    return _FOLD_CONSTS


def _fold_assemble(w, H, S, consts, g1_affine, g2_affine, miller):
    """The shared tail of both fold variants: affinize the weighted
    aggregates / hashes / local MSM, assemble the batch's k+1 pairs —
    its k weighted-aggregate legs plus the e(-g1, S) leg — with the
    skip mask, and run `miller` over them.  One assembly block on
    purpose: the staged and fused paths are pinned 'identical math',
    which only holds while they share it."""
    g1x, g1y, g2x, g2y, n1x, n1y = consts
    xw, yw, w_inf = g1_affine(w, g1x, g1y)
    xh, yh, h_inf = g2_affine(H, g2x, g2y)
    xs, ys, s_inf = g2_affine(S, g2x, g2y)
    xp = jnp.concatenate(
        [xw, jnp.broadcast_to(n1x, xw.shape[:-2] + (1, fq.LIMBS))], axis=-2)
    yp = jnp.concatenate(
        [yw, jnp.broadcast_to(n1y, yw.shape[:-2] + (1, fq.LIMBS))], axis=-2)
    xq = jnp.concatenate([xh, xs[..., None, :, :]], axis=-3)
    yq = jnp.concatenate([yh, ys[..., None, :, :]], axis=-3)
    skip = jnp.concatenate([w_inf | h_inf, s_inf[..., None]], axis=-1)
    return miller(xp, yp, xq, yq, skip)


def _fold_partial_core(aggP, cbits, hP, sP, consts, miller):
    """The folded flush body shared by the fused and staged variants.

    aggP: G1 Jacobian [.., k, 32] x3; cbits [.., k, 64] msb-first;
    hP/sP: G2 Jacobian [.., k, 2, 32] x3 (pre-cofactor hash points,
    signatures); consts from _fold_consts.  Returns the partial Fq12
    Miller product [.., 12, 32] over the batch's k+1 pairs."""
    from . import curve_jax as cj
    # Fiat-Shamir weighting ladder: w_i = c_i * agg_i
    w = cj.point_scalar_mul(cj.F1, aggP, cbits)
    # cofactor-clearing ladder: H_i = h_eff * Q_i
    hbits = jnp.broadcast_to(jnp.asarray(_h_eff_bits()),
                             cbits.shape[:-1] + (_h_eff_bits().shape[0],))
    H = cj.point_scalar_mul(cj.F2, hP, hbits)
    # local G2 signature MSM: S_d = sum_i c_i * sig_i over this batch
    # (pairs axis moved to front — point_sum_tree reduces axis 0)
    sw = cj.point_scalar_mul(cj.F2, sP, cbits)
    S = cj.point_sum_tree(
        cj.F2, tuple(jnp.moveaxis(c, -3, 0) for c in sw))
    return _fold_assemble(w, H, S, consts, _g1_jacobian_to_affine,
                          _g2_jacobian_to_affine, miller)


@jax.jit
def _fold_partial_fused(aggX, aggY, aggZ, cbits, hX, hY, hZ,
                        sX, sY, sZ, g1x, g1y, g2x, g2y, n1x, n1y):
    """One launch per shard: the whole folded flush body under one jit
    (ladders + MSM + affinization + miller scan + product reduce)."""
    return _fold_partial_core(
        (aggX, aggY, aggZ), cbits, (hX, hY, hZ), (sX, sY, sZ),
        (g1x, g1y, g2x, g2y, n1x, n1y),
        lambda xp, yp, xq, yq, skip: _prod_reduce_raw(
            ft.fq12_select(skip, ft.fq12_one(skip.shape),
                           _miller_scan(xp, yp, xq, yq))))


_g1_affine_jit = jax.jit(_g1_jacobian_to_affine)
_g2_affine_jit = jax.jit(_g2_jacobian_to_affine)


def fold_partial_products(aggP, cbits, hP, sP):
    """Per-shard partial Fq12 product of one folded flush: the shard's
    k weighted-aggregate Miller legs times its e(-g1, S_d) local-MSM
    leg, [.., k, ...] -> [.., 12, 32].  Inputs sharded on a leading
    mesh axis stay sharded (the math is elementwise over it).  Fused
    mode runs the whole body as ONE compiled program per device;
    staged mode (the default) drives the per-piece jitted kernels —
    identical math, far shorter compiles."""
    consts = _fold_consts()
    if _resolve_mode() == "fused":
        return _fold_partial_fused(*aggP, cbits, *hP, *sP, *consts)
    from . import curve_jax as cj
    w = cj.g1_scalar_mul(aggP, cbits)
    hbits = jnp.broadcast_to(jnp.asarray(_h_eff_bits()),
                             cbits.shape[:-1] + (_h_eff_bits().shape[0],))
    H = cj.g2_scalar_mul(hP, hbits)
    sw = cj.g2_scalar_mul(sP, cbits)
    # local MSM: halving-tree sum over the pairs axis (host-driven
    # log2(k) launches of the pairwise-add kernel — unrolling it
    # in-graph is the fused variant's job)
    X, Y, Z = sw
    while X.shape[-3] > 1:
        h = X.shape[-3] // 2
        X, Y, Z = cj.g2_add((X[..., :h, :, :], Y[..., :h, :, :],
                             Z[..., :h, :, :]),
                            (X[..., h:, :, :], Y[..., h:, :, :],
                             Z[..., h:, :, :]))
    S = (X[..., 0, :, :], Y[..., 0, :, :], Z[..., 0, :, :])
    return _fold_assemble(w, H, S, consts, _g1_affine_jit,
                          _g2_affine_jit, miller_partial_products)


# ---------------------------------------------------------------------------
# chunked path: static-bit-pattern chunk kernels
# ---------------------------------------------------------------------------

def _bit_chunks():
    bits = _MILLER_BITS.tolist()
    return [tuple(bits[i:i + _CHUNK_BITS])
            for i in range(0, len(bits), _CHUNK_BITS)]


_BIT_CHUNKS = _bit_chunks()


@functools.partial(jax.jit, static_argnums=(0,))
def _miller_chunk(bits, f, T, xq, yq, xp, yp):
    """`len(bits)` Miller iterations with the bit pattern baked in as a
    static arg — one launch per chunk, one compile per distinct
    pattern."""
    for bit in bits:
        T, (c0, c1, c4) = _double_step(T, xp, yp)
        f = ft.fq12_mul(ft.fq12_square(f), _line_to_fq12(c0, c1, c4))
        if bit:
            T, (c0, c1, c4) = _add_step(T, (xq, yq), xp, yp)
            f = ft.fq12_mul(f, _line_to_fq12(c0, c1, c4))
    return f, T


@functools.partial(jax.jit, static_argnums=(0,))
def _ladder_chunk(bits, acc, m):
    """`len(bits)` square-and-multiply ladder steps, bit pattern static.
    The exp-by-x ladder walks the same |x| bits as the Miller loop, so
    the five expx calls of the hard chain all reuse these compiles."""
    for bit in bits:
        acc = ft.fq12_cyclotomic_square(acc)
        if bit:
            acc = ft.fq12_mul(acc, m)
    return acc


def _miller_chunked(xp, yp, xq, yq, skip):
    batch = xp.shape[:-1]
    one2 = jnp.broadcast_to(
        jnp.asarray(np.stack([fq.ONE_MONT_LIMBS, fq.ZERO_LIMBS])),
        batch + (2, fq.LIMBS))
    T = (xq, yq, one2)
    f = ft.fq12_one(batch)
    for bits in _BIT_CHUNKS:
        f, T = _miller_chunk(bits, f, T, xq, yq, xp, yp)
    return _miller_finish(f, skip)


def _exp_by_neg_x_chunked(m):
    acc = m
    for bits in _BIT_CHUNKS:
        acc = _ladder_chunk(bits, acc, m)
    return _conj_jit(acc)


def final_exponentiation_chunked(f):
    return _hard_chain(
        _easy_jit(f), cyc=_cyc_jit, mul=_mul_jit, conj=_conj_jit,
        frob=_frob_jit, expx=_exp_by_neg_x_chunked)


def multi_miller_product(xps, yps, xqs, yqs, skip=None):
    """Product over the pairs axis (-1 of batch) of miller loops.

    Inputs carry a trailing pairs axis k: xps [..., k, 32], xqs
    [..., k, 2, 32]; optional skip [..., k] marks infinity pairs.  The k
    miller loops run stacked in one batch; their Fq12 outputs are
    multiplied together — one shared final exponentiation then decides
    the whole product (the standard pairing-check shape).
    """
    f = miller_loop(xps, yps, xqs, yqs, skip)   # [..., k, 12, 32]
    return _prod_reduce(f)


# every pairing_check flattens its batch to (B, k) and pads B up to a
# power of two, so log-many compile sets serve all workload sizes (a fresh
# XLA compile of the stage kernels costs minutes on a small host).  The
# floor stays at 1: padded rows are free on TPU lanes but real serial work
# on a small CPU host, so tests shouldn't pay for bench-sized buckets.
_BUCKET_MIN_ROWS = 1

# dispatch granularity (PAIRING_MODE env):
#   staged (default) — one jitted kernel per step: ~25-70 s of TPU
#     compile per kernel, ~650 dispatches per check.
#   fused — the whole batched check as ONE compiled program (miller
#     scan + final exponentiation + is-one), a single launch per check;
#     its cold compile is the wall (PR 21 compile rehearsal, CHANGES.md).
#   chunked — 8-bit static-pattern chunks (~20 compiles, ~70 launches).


def _bucket_rows(n: int) -> int:
    return max(_BUCKET_MIN_ROWS, 1 << (n - 1).bit_length() if n > 1 else 1)


def pairing_check(xps, yps, xqs, yqs, skip=None):
    """Batched check  prod_i e(P_i, Q_i) == 1  over the trailing pairs axis.

    Host-staged: per-bit jitted Miller steps + staged final exponentiation.
    The leading batch axes are flattened and padded to a bucketed row count
    (padded rows are edge-copies with skip=True, i.e. they check 1 == 1).
    Returns a boolean array per batch element (on device).
    """
    k = xps.shape[-2]
    lead = xps.shape[:-2]
    b = int(np.prod(lead)) if lead else 1
    bp = _bucket_rows(b)

    xps = jnp.reshape(xps, (b, k, fq.LIMBS))
    yps = jnp.reshape(yps, (b, k, fq.LIMBS))
    xqs = jnp.reshape(xqs, (b, k, 2, fq.LIMBS))
    yqs = jnp.reshape(yqs, (b, k, 2, fq.LIMBS))
    if skip is None:
        skip = jnp.zeros((b, k), dtype=bool)
    else:
        skip = jnp.reshape(skip, (b, k))
    if bp != b:
        def pad_edge(a):
            reps = jnp.broadcast_to(a[:1], (bp - b,) + a.shape[1:])
            return jnp.concatenate([a, reps], axis=0)
        xps, yps, xqs, yqs = map(pad_edge, (xps, yps, xqs, yqs))
        skip = jnp.concatenate(
            [skip, jnp.ones((bp - b, k), dtype=bool)], axis=0)

    mode = _resolve_mode()
    if mode == "fused":
        v = _pairing_check_fused(xps, yps, xqs, yqs, skip)
    elif mode == "chunked":
        f = _miller_chunked(xps, yps, xqs, yqs, skip)
        f = _prod_reduce(f)
        v = _is_one_jit(final_exponentiation_chunked(f))
    else:
        f = multi_miller_product(xps, yps, xqs, yqs, skip)
        v = _is_one_jit(final_exponentiation_staged(f))
    return jnp.reshape(v[:b], lead)


# staged composition is the fast path; keep the historical name used by
# callers (ops/bls_tpu.py, tests)
pairing_check_jit = pairing_check


def warmup(k: int = 2, rows: int = _BUCKET_MIN_ROWS) -> None:
    """Pre-compile the kernels for the (rows, k) bucket.  Fused path:
    one program.  Staged path: every stage kernel, compiling
    concurrently (XLA compilation releases the GIL, so on a multi-core
    host the wall-clock cost is that of the slowest single kernel
    instead of the sum over all of them)."""
    import concurrent.futures as cf

    z12k = jnp.zeros((rows, k, 12, fq.LIMBS), jnp.uint32)
    z2 = jnp.zeros((rows, k, 2, fq.LIMBS), jnp.uint32)
    z1 = jnp.zeros((rows, k, fq.LIMBS), jnp.uint32)
    sk = jnp.zeros((rows, k), bool)
    m = jnp.zeros((rows, 12, fq.LIMBS), jnp.uint32)

    mode = _resolve_mode()
    if mode == "fused":
        # all-skip rows: every lane checks 1 == 1, exercising the whole
        # program shape without meaningful data
        jax.block_until_ready(_pairing_check_fused(
            z1, z1, z2, z2, jnp.ones((rows, k), bool)))
        return

    if mode == "chunked":
        one2 = jnp.zeros((rows, k, 2, fq.LIMBS), jnp.uint32)
        f0 = ft.fq12_one((rows, k))
        jobs = [
            # chunk kernels compile concurrently per distinct pattern
            *[(lambda bits=bits: _miller_chunk(
                bits, f0, (z2, z2, one2), z2, z2, z1, z1))
              for bits in set(_BIT_CHUNKS)],
            *[(lambda bits=bits: _ladder_chunk(bits, m, m))
              for bits in set(_BIT_CHUNKS)],
            lambda: _miller_finish(z12k, sk),
            lambda: _prod_reduce(z12k),
            lambda: _easy_jit(m),
            lambda: _cyc_jit(m),
            lambda: _mul_jit(m, m),
            lambda: _conj_jit(m),
            lambda: _frob_jit(m, 1),
            lambda: _frob_jit(m, 2),
            lambda: _frob_jit(m, 3),
            lambda: _is_one_jit(m),
        ]
        with cf.ThreadPoolExecutor(max_workers=8) as ex:
            for _ in ex.map(lambda fn: jax.block_until_ready(fn()),
                            jobs):
                pass
        return
    jobs = [
        lambda: _miller_step_double(z12k, (z2, z2, z2), z1, z1),
        lambda: _miller_step_add(z12k, (z2, z2, z2), z2, z2, z1, z1),
        lambda: _miller_finish(z12k, sk),
        lambda: _prod_reduce(z12k),
        lambda: _easy_jit(m),
        lambda: _cyc_jit(m),
        lambda: _mul_jit(m, m),
        lambda: _conj_jit(m),
        lambda: _frob_jit(m, 1),
        lambda: _frob_jit(m, 2),
        lambda: _frob_jit(m, 3),
        lambda: _is_one_jit(m),
    ]
    with cf.ThreadPoolExecutor(max_workers=len(jobs)) as ex:
        for _ in ex.map(lambda fn: jax.block_until_ready(fn()), jobs):
            pass
