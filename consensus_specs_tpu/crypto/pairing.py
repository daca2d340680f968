"""Optimal ate pairing on BLS12-381.

From-scratch implementation over the fields.py tower.  G2 points are
untwisted into E(Fq12) via (x, y) -> (x/w^2, y/w^3) (w^6 = XI, derived from
the tower relations), and the Miller loop runs over the bits of |z| with
line evaluations at the G1 argument (its point arithmetic stays on the
twist, in Fq2).  The final exponentiation uses the
easy (q^6-1)(q^2+1) step then the standard BLS12 x-chain hard part
(cyclotomic squarings + Frobenius maps), computing e(P,Q)^3 uniformly —
sound for every equality/is-one use (see final_exponentiation).

Verified against the production KZG trusted setup: e([tau]G1, G2) ==
e(G1, [tau]G2) for the monomial points (tests/test_bls.py).
"""
from __future__ import annotations

from .fields import Q, R, BLS_X, XI, Fq2, Fq6, Fq12
from .curve import Point

# |z| bits for the Miller loop
_ATE_LOOP = abs(BLS_X)

# hard-part exponent (after the easy (q^6-1)(q^2+1) step); the x-chain in
# _hard_part computes exactly m^(3*_HARD_EXP) — cubing is a bijection on the
# order-r target subgroup, so equality/is-one semantics are unchanged as
# long as every pairing goes through the same chain
_HARD_EXP = (Q**4 - Q * Q + 1) // R


# The untwist (x, y) -> (x w^-2, y w^-3) keeps T on the twist: a line's
# slope through untwisted points is (twist slope) * w^-1, and
# w^-1 = v^2 w / XI, w^-3 = v w / XI in the {1, v, v^2} x {1, w} basis.
# The loop therefore runs in Fq2 with one Fq2 inversion a step and
# assembles each line's Fq12 value from Fq2 parts; the values are the
# Fq12-affine line evaluations exactly.
_XI_INV = XI.inv()
_Z2 = Fq2.zero()


def _slope(x1: Fq2, y1: Fq2, x2: Fq2, y2: Fq2) -> Fq2:
    """Twist slope of the chord through two points, or of the tangent
    when they are equal."""
    if x1 == x2:
        if y1 != y2:
            raise ZeroDivisionError("vertical addition in miller loop")
        num = x1.square()
        return (num + num + num) * (y1 + y1).inv()
    return (y2 - y1) * (x2 - x1).inv()


def _step(f: Fq12, x: Fq2, y: Fq2, x2: Fq2, y2: Fq2, xp: int, yp: int):
    """f * l_{T,U}(P) and T + U, for T = (x, y), U = (x2, y2) on the
    twist: l = -yp + (s xp) w^-1 + (y - s x) w^-3 at P = (xp, yp)."""
    s = _slope(x, y, x2, y2)
    line = Fq12(Fq6(Fq2(-yp, 0), _Z2, _Z2),
                Fq6(_Z2, (y - s * x) * _XI_INV, s * xp * _XI_INV))
    x3 = s.square() - x - x2
    return f * line, x3, s * (x - x3) - y


def miller_loop(p: Point, q: Point) -> Fq12:
    """Miller loop value f_{|z|,Q}(P); final exponentiation applied separately."""
    if p.is_infinity() or q.is_infinity():
        return Fq12.one()
    xa, ya = p.affine()
    xp, yp = xa.v, ya.v
    xq, yq = q.affine()
    x, y = xq, yq
    f = Fq12.one()
    for bit in bin(_ATE_LOOP)[3:]:
        f, x, y = _step(f.square(), x, y, x, y, xp, yp)
        if bit == "1":
            f, x, y = _step(f, x, y, xq, yq, xp, yp)
    # z < 0: conjugate (differs from the true inverse by a norm-subfield
    # factor, which the final exponentiation kills)
    return f.conjugate()


def _exp_by_neg_x(m: Fq12) -> Fq12:
    """m^x for the (negative) BLS parameter x, m unitary: square-and-multiply
    by |x| with cyclotomic squarings, then conjugate."""
    acc = m
    for bit in bin(_ATE_LOOP)[3:]:
        acc = acc.cyclotomic_square()
        if bit == "1":
            acc = acc * m
    return acc.conjugate()


def _hard_part(m: Fq12) -> Fq12:
    """m^(3 * (q^4 - q^2 + 1) / r) by the standard BLS12 addition chain
    (5 exp-by-x + 3 Frobenius; verified symbolically in
    tests/test_bls.py::test_hard_part_chain_exponent)."""
    t2 = m
    t1 = t2.cyclotomic_square().conjugate()      # m^-2
    t3 = _exp_by_neg_x(t2)                       # m^x
    t4 = t3.cyclotomic_square()                  # m^2x
    t5 = t1 * t3                                 # m^(x-2)
    t1 = _exp_by_neg_x(t5)                       # m^(x^2-2x)
    t0 = _exp_by_neg_x(t1)                       # m^(x^3-2x^2)
    t6 = _exp_by_neg_x(t0)                       # m^(x^4-2x^3)
    t6 = t6 * t4                                 # m^(x^4-2x^3+2x)
    t4 = _exp_by_neg_x(t6)
    t5 = t5.conjugate()
    t4 = t4 * t5 * t2
    t5 = t2.conjugate()
    t1 = t1 * t2                                 # m^(x^2-2x+1)
    t1 = t1.frobenius(3)
    t6 = t6 * t5
    t6 = t6.frobenius(1)
    t3 = t3 * t0
    t3 = t3.frobenius(2)
    t3 = t3 * t1
    t3 = t3 * t6
    return t3 * t4


def final_exponentiation(f: Fq12) -> Fq12:
    """f^(3 * (q^12 - 1) / r): easy part then the x-chain hard part.

    The extra factor of 3 (inherent to the chain) is harmless: pairing
    values live in the order-r subgroup where cubing is a bijection, so
    e(P,Q)-equality and is-one checks are unaffected.
    """
    f1 = f.conjugate() * f.inv()                 # f^(q^6-1)
    m = f1.frobenius(2) * f1                     # ^(q^2+1): now unitary
    return _hard_part(m)


def pairing(p: Point, q: Point) -> Fq12:
    """e(P, Q) for P in G1, Q in G2."""
    return final_exponentiation(miller_loop(p, q))


def pairing_check(pairs: list[tuple[Point, Point]]) -> bool:
    """prod e(P_i, Q_i) == 1, with a single shared final exponentiation."""
    f = Fq12.one()
    for p, q in pairs:
        f = f * miller_loop(p, q)
    return final_exponentiation(f).is_one()
