"""Device multi-scalar multiplication.

Capability counterpart of the reference's arkworks `multiexp_unchecked`
(utils/bls.py:224-296): `g1_multi_exp(points, scalars)` takes oracle G1
Points and python ints and returns the combined Point.

Two engines:

- **Windowed Pippenger** (`_pippenger_g1`, n >= _PIPPENGER_MIN): the
  arkworks-slot algorithm reshaped for SPMD lanes.  8-bit windows; each
  window's points are split across `_THREADS` vector lanes, every lane
  serially folds its chunk into a private 255-bucket table (one
  `lax.scan` step per chunk element, gather -> complete-add -> scatter
  on [windows, threads] lanes), lane tables merge pairwise (log2 T
  rounds), the classic suffix-scan turns bucket sums into
  weighted sums (Hillis-Steele, log2 255 rounds), and a Horner pass
  over windows (8 doublings each) combines the result.  The whole MSM
  is ONE compiled program — bucket accumulation does
  windows*(n + 255*(T-1)) point-adds total (~10x fewer field ops than
  the per-point double-and-add lanes) and pays a single device launch.
- **Double-and-add lanes + scanned sum** (small n, and G2): per-point
  scalar-mul lanes and one scanned point-add reduction.

deneb's `g1_lincomb` over the 4096-point Lagrange basis
(polynomial-commitments.md:268) is the headline shape.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from ..crypto import curve as cv
from ..crypto.fields import R
from . import curve_jax as cj
from . import fq
from .curve_jax import F1, point_add, point_double, point_infinity_like
from .g1_sweep import _pow2 as _pad_pow2


# ---------------------------------------------------------------------------
# windowed Pippenger (one fused program)
# ---------------------------------------------------------------------------

_W_BITS = 8                     # window width
_N_WIN = 256 // _W_BITS         # 32 windows cover the 255-bit scalar
_N_BUCKETS = (1 << _W_BITS) - 1  # bucket 0 contributes nothing
_THREADS = 16                   # bucket-table lanes per window
_PIPPENGER_MIN = 256            # below this the plain lanes win

# engine selection (MSM_MODE env: "pippenger" | "lanes") — platform-
# split like pairing_jax._resolve_mode: the fused Pippenger program is
# a multi-minute XLA compile on a small CPU host (fine once, cached on
# accelerators) while the lanes kernels compile in seconds, so CPU
# defaults to lanes and accelerators to pippenger.  Resolved LAZILY:
# the env var is read at first use, not import, so flipping it in a
# test/bench is not order-dependent; assigning the global directly
# still wins, and reset_mode() forgets a cached choice.
import os as _os
MSM_MODE = None


def reset_mode() -> None:
    """Forget the cached engine choice: the next call re-reads the
    MSM_MODE env var and the active jax backend."""
    global MSM_MODE
    MSM_MODE = None


def _resolve_mode() -> str:
    global MSM_MODE
    if MSM_MODE is None:
        MSM_MODE = (_os.environ.get("MSM_MODE")
                    or ("lanes" if jax.default_backend() == "cpu"
                        else "pippenger"))
    return MSM_MODE


def _digits_np(scalars) -> np.ndarray:
    """[_N_WIN, n] uint32 window digits, window 0 most significant."""
    out = np.zeros((_N_WIN, len(scalars)), dtype=np.uint32)
    for i, s in enumerate(scalars):
        s = int(s)
        for w in range(_N_WIN):
            out[w, i] = (s >> (_W_BITS * (_N_WIN - 1 - w))) \
                & ((1 << _W_BITS) - 1)
    return out


def _bucket_gather(B, d):
    """Per-(window, thread) lane bucket read: B [W,T,buckets,LIMBS] at
    index d [W,T] -> [W,T,LIMBS]."""
    idx = jnp.broadcast_to(d[:, :, None, None],
                           d.shape + (1, B.shape[-1]))
    return jnp.take_along_axis(B, idx, axis=2)[:, :, 0, :]


def _bucket_scatter(B, d, v):
    """Write v [W,T,LIMBS] back to B [W,T,buckets,LIMBS] at index d
    [W,T]; (window, thread) rows are distinct lanes, so writes never
    collide."""
    idx = jnp.broadcast_to(d[:, :, None, None],
                           d.shape + (1, B.shape[-1]))
    return jnp.put_along_axis(B, idx, v[:, :, None, :], axis=2,
                              inplace=False)


def _inf_like(shape):
    one = jnp.broadcast_to(jnp.asarray(fq.ONE_MONT_LIMBS),
                           shape + (fq.LIMBS,))
    return point_infinity_like(
        F1, (one, one, jnp.zeros(shape + (fq.LIMBS,), jnp.uint32)))


def _masked_roll_add(P, shift, axis_len):
    """One Hillis-Steele round along axis 1: P[i] += P[i + shift] where
    i + shift < axis_len (out-of-range partners contribute nothing —
    their lanes add a masked copy of themselves, which the final select
    discards).  `shift` may be traced (fori_loop round counter).

    Every compile-heavy reduction here runs as a fori_loop over rounds
    with ONE point_add in the body — unrolling these trees is what blew
    the XLA compile past the bench budget."""
    idx = jnp.arange(axis_len)
    in_range = (idx + shift) < axis_len
    gather_idx = jnp.where(in_range, idx + shift, idx)

    def pick(c):
        return jnp.take(c, gather_idx, axis=1)
    partner = tuple(pick(c) for c in P)
    added = point_add(F1, P, partner)
    mask = in_range[None, :, None]
    return tuple(jnp.where(mask, a, p) for a, p in zip(added, P))


@functools.partial(jax.jit, static_argnums=())
def _pippenger_g1(X, Y, Z, digits):
    """MSM over G1: X/Y/Z [n, LIMBS] Jacobian Montgomery limbs,
    digits [_N_WIN, n] (window 0 = most significant).  n must be a
    multiple of _THREADS.  Returns one Jacobian point."""
    n = X.shape[0]
    chunk = n // _THREADS

    # [W, T, buckets+1, LIMBS] private bucket tables (slot 0 = dump
    # for digit 0)
    tables = _inf_like((_N_WIN, _THREADS, _N_BUCKETS + 1))

    # points reshaped to thread chunks: [T, chunk, LIMBS]
    Xc = X.reshape(_THREADS, chunk, fq.LIMBS)
    Yc = Y.reshape(_THREADS, chunk, fq.LIMBS)
    Zc = Z.reshape(_THREADS, chunk, fq.LIMBS)
    dc = digits.reshape(_N_WIN, _THREADS, chunk)

    def fold(tables, j):
        """One scan step: every (window, thread) lane folds its j-th
        point into its private bucket."""
        Bx, By, Bz = tables
        d = dc[:, :, j]                                   # [W, T]
        px = jnp.broadcast_to(Xc[:, j], (_N_WIN, _THREADS, fq.LIMBS))
        py = jnp.broadcast_to(Yc[:, j], (_N_WIN, _THREADS, fq.LIMBS))
        pz = jnp.broadcast_to(Zc[:, j], (_N_WIN, _THREADS, fq.LIMBS))
        cur = (_bucket_gather(Bx, d), _bucket_gather(By, d),
               _bucket_gather(Bz, d))
        new = point_add(F1, cur, (px, py, pz))
        # digit 0 -> write the unchanged bucket back into the dump slot
        keep = (d > 0)[..., None]
        new = tuple(jnp.where(keep, nw, cu) for nw, cu in zip(new, cur))
        d_safe = jnp.where(d > 0, d, 0)
        return (_bucket_scatter(Bx, d_safe, new[0]),
                _bucket_scatter(By, d_safe, new[1]),
                _bucket_scatter(Bz, d_safe, new[2])), None

    tables, _ = jax.lax.scan(fold, tables, jnp.arange(chunk))

    # merge thread tables: log2(T) masked-pair rounds over axis 1;
    # round r adds lanes [h, 2h) into [0, h) — the rest add a masked
    # self-copy the select discards
    Bx, By, Bz = tables

    def merge_body(r, P):
        h = _THREADS >> (r + 1)
        idx = jnp.arange(_THREADS)
        active = idx < h
        gather_idx = jnp.where(active, idx + h, idx)
        partner = tuple(jnp.take(c, gather_idx, axis=1) for c in P)
        added = point_add(F1, P, partner)
        mask = active[None, :, None, None]
        return tuple(jnp.where(mask, a, p) for a, p in zip(added, P))

    Bx, By, Bz = jax.lax.fori_loop(
        0, _THREADS.bit_length() - 1, merge_body, (Bx, By, Bz))
    S = (Bx[:, 0, 1:], By[:, 0, 1:], Bz[:, 0, 1:])   # [W, buckets]

    # weighted bucket sum via TWO suffix scans: after one scan position
    # b holds T_b = sum_{j>=b} S_j; after a second scan position 0
    # holds sum_b T_b == sum_b (b+1)*S_b, i.e. the weighted sum for
    # 1-based bucket values
    n_rounds = (_N_BUCKETS - 1).bit_length()

    def suffix_body(r, P):
        return _masked_roll_add(P, 1 << r, _N_BUCKETS)

    T = jax.lax.fori_loop(0, n_rounds, suffix_body, S)
    U = jax.lax.fori_loop(0, n_rounds, suffix_body, T)
    G = tuple(c[:, 0] for c in U)                    # [W, LIMBS]

    # Horner over windows (window 0 most significant)
    def horner(w, acc):
        def dbl(_i, a):
            return point_double(F1, a)
        acc = jax.lax.fori_loop(0, _W_BITS, dbl, acc)
        gw = tuple(jax.lax.dynamic_index_in_dim(c, w, axis=0,
                                                keepdims=False)
                   for c in G)
        return point_add(F1, acc, gw)

    acc = _inf_like(())
    acc = jax.lax.fori_loop(0, _N_WIN, horner, acc)
    return acc


def g1_multi_exp(points, scalars):
    """sum_i scalars[i] * points[i] over G1; returns an oracle Point.

    Large inputs run the fused Pippenger program; small ones the
    double-and-add lanes (whose kernels tests already keep warm)."""
    if len(points) != len(scalars):
        raise ValueError("g1_multi_exp: length mismatch")
    if not points:
        return cv.g1_infinity()
    n = len(points)
    if n >= _PIPPENGER_MIN and _resolve_mode() == "pippenger":
        m = -(-n // _THREADS) * _THREADS
        m = _pad_pow2(m)
        pts = list(points) + [cv.g1_infinity()] * (m - n)
        sc = [int(s) % R for s in scalars] + [0] * (m - n)
        X, Y, Z = cj.g1_pack(pts)
        digits = jnp.asarray(_digits_np(sc))
        out = _pippenger_g1(X, Y, Z, digits)
        return cj.g1_unpack(tuple(
            jnp.asarray(np.asarray(c))[None] for c in out))[0]
    m = _pad_pow2(n)
    pts = list(points) + [cv.g1_infinity()] * (m - n)
    sc = [int(s) % R for s in scalars] + [0] * (m - n)
    packed = cj.g1_pack(pts)
    bits = cj.scalars_to_bits(sc)
    prods = cj.g1_scalar_mul(packed, bits)
    out = cj.g1_sum_axis(prods, 0)
    X = np.asarray(out[0])[None]
    Y = np.asarray(out[1])[None]
    Z = np.asarray(out[2])[None]
    return cj.g1_unpack((jnp.asarray(X), jnp.asarray(Y),
                         jnp.asarray(Z)))[0]


def g1_weighted_sweep(points, scalars):
    """Per-pair weighted points [s_i * P_i] — NO reduction — in one
    batched dispatch.

    The fused scheduler's Fiat–Shamir weighting (sigpipe/scheduler.py)
    needs each c_i * agg_i and c_i * (-g1) *individually* (every
    weighted point feeds its own pairing leg), so the classic summed
    MSM shape does not apply; what batches is the scalar-mul ladder
    itself: all 2N 64-bit ladders of a flush ride one
    `cj.g1_scalar_mul` launch over a [n, bits] digit tensor instead of
    2N host double-and-add loops.  The bit width adapts to the widest
    scalar (64 for the scheduler's coefficients — a 4x shorter scan
    than the generic 256), and the batch axis pads to a power of two so
    XLA only sees log-many shapes.

    Platform split follows g1_sweep.G1_SWEEP_MODE (jax engine off-CPU,
    vectorized host oracle on CPU); the per-pair host ladder is the
    *fallback* of the `ops.msm` resilience dispatch site, counted in
    sigpipe.metrics as `host_point_adds`.  Multi-chip: a >1-device
    verify mesh partitions the padded pair axis
    (parallel/shard_verify.py `shard_jobs`) so each device runs its
    slice of the ladder scan — same single dispatch, byte-identical
    results."""
    if len(points) != len(scalars):
        raise ValueError("g1_weighted_sweep: length mismatch")
    if not points:
        return []
    from .g1_sweep import _resolve_mode as _sweep_mode
    sc = [int(s) % R for s in scalars]
    if _sweep_mode() != "jax":
        # scalars are subgroup-order-reduced either way: every input
        # point is in the r-torsion subgroup (validated pubkeys, the
        # generator), so s*P == (s mod R)*P
        return [p * s for p, s in zip(points, sc)]
    n = len(points)
    m = _pad_pow2(n)
    pts = list(points) + [cv.g1_infinity()] * (m - n)
    sc = sc + [0] * (m - n)
    width = max((s.bit_length() for s in sc), default=1) or 1
    n_bits = 64 if width <= 64 else 256
    packed = cj.g1_pack(pts)
    bits = cj.scalars_to_bits(sc, n_bits=n_bits)
    # multi-chip: partition the (padded, power-of-two) pair axis over
    # the verify mesh — every ladder is independent, so each device
    # runs its slice of the scalar-mul scan in parallel; a 1-device
    # mesh is a no-op
    from ..parallel import shard_verify
    X, Y, Z, bits = shard_verify.shard_jobs((*packed, bits), "ops.msm")
    prods = cj.g1_scalar_mul((X, Y, Z), bits)
    return cj.g1_unpack(tuple(
        jnp.asarray(np.asarray(c)) for c in prods))[:n]


def g2_multi_exp(points, scalars, label=None):
    """sum_i scalars[i] * points[i] over G2; returns an oracle Point.

    The ladder width adapts to the widest scalar (64 bits for the fold
    path's Fiat–Shamir coefficients — a 4x shorter scan than the
    generic 256), and the batch axis pads to a power of two so XLA only
    sees log-many shapes.  With `label` set (the `ops.pairing_fold`
    fold of a fused flush's signature legs — sigpipe/fold.py), the
    padded ladder axis is partitioned over the verify mesh via
    `shard_jobs`: each device runs its slice of the scalar-mul scan,
    and the scanned sum gathers the products across shards.  Exact
    integer math throughout, so the sum is byte-identical at any mesh
    width."""
    if len(points) != len(scalars):
        raise ValueError("g2_multi_exp: length mismatch")
    if not points:
        return cv.g2_infinity()
    n = len(points)
    m = _pad_pow2(n)
    pts = list(points) + [cv.g2_infinity()] * (m - n)
    sc = [int(s) % R for s in scalars] + [0] * (m - n)
    width = max((s.bit_length() for s in sc), default=1) or 1
    n_bits = 64 if width <= 64 else 256
    packed = cj.g2_pack(pts)
    bits = cj.scalars_to_bits(sc, n_bits=n_bits)
    if label is not None:
        from ..parallel import shard_verify
        X, Y, Z, bits = shard_verify.shard_jobs((*packed, bits), label)
        packed = (X, Y, Z)
    prods = cj.g2_scalar_mul(packed, bits)
    out = cj.g2_sum_axis(prods, 0)
    return cj.g2_unpack(tuple(
        jnp.asarray(np.asarray(c))[None] for c in out))[0]
