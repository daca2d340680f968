"""TPU-backed BLS verification: host decode, device batched pairing.

The split (north star in BASELINE.json): point decompression, subgroup
checks and hash-to-curve run on host over Python ints (cheap, microseconds
per point — and real clients cache validated pubkeys); the pairings — the
>99% cost — run as one batched Miller-loop + shared-final-exponentiation
kernel on device (ops/pairing_jax.py).

API mirrors the byte-level signature suite (crypto/bls12_381.py) but takes
LISTS of verification jobs and returns a verdict per job, so a block's 128
attestations or a 512-key sync aggregate verify as one device dispatch.

Infinity points have no affine limb encoding; such pairs ride the
pairing kernel's skip mask (e(O, .) = 1), keeping verdict parity with the
oracle.
"""
from __future__ import annotations

import hashlib

import numpy as np
import jax.numpy as jnp

from ..crypto import curve as cv
from ..crypto import hash_to_curve as h2c
from ..crypto.bls12_381 import _load_pubkey, _load_signature
from ..crypto.curve import DecodeError, Point
from ..sigpipe.metrics import METRICS
from . import curve_jax as cj
from . import fq
from . import fq_tower as ft
from . import pairing_jax as pj

_H_EFF_BITS = np.array(
    [int(b) for b in bin(h2c.H_EFF)[2:]], dtype=np.uint32)


def hash_to_g2_batch(messages, dst=h2c.DST_G2):
    """Batched hash-to-curve: host hash-to-field + SSWU + isogeny (cheap
    int math), ONE device scalar-mul sweep for the 636-bit cofactor
    clearing (~90% of the host cost of crypto/hash_to_curve.hash_to_g2).

    With a >1-device verify mesh the padded message axis is partitioned
    over it (parallel/shard_verify.py `shard_jobs`) — this was the last
    unsharded per-flush device call: each device clears the cofactor of
    its own slice with zero cross-device traffic, inside the unchanged
    `sigpipe.hash_to_g2_batch` dispatch seam (a 1-device mesh is
    byte-identical to the unsharded path)."""
    if not messages:
        return []
    pre = []
    for msg in messages:
        u0, u1 = h2c.hash_to_field_fq2(bytes(msg), 2, dst)
        q0 = h2c.iso_map(*h2c.sswu_map(u0))
        q1 = h2c.iso_map(*h2c.sswu_map(u1))
        pre.append(q0 + q1)
    n_real = len(pre)
    pre += [pre[0]] * (_next_pow2(n_real) - n_real)  # pow2: bounded shapes
    bits = jnp.broadcast_to(jnp.asarray(_H_EFF_BITS),
                            (len(pre), _H_EFF_BITS.shape[0]))
    from ..parallel.shard_verify import shard_jobs
    X, Y, Z, bits = shard_jobs(
        (*cj.g2_pack(pre), jnp.asarray(bits)),
        "sigpipe.hash_to_g2_batch")
    out = cj.g2_scalar_mul((X, Y, Z), bits)
    return cj.g2_unpack(out)[:n_real]


def _resolve_pubkey(pk):
    """Accept compressed bytes or an already-validated Point (the spec's
    pubkey-cache shape)."""
    if isinstance(pk, Point):
        if pk.is_infinity():
            raise ValueError("infinity pubkey")
        return pk
    return _load_pubkey(bytes(pk))


def _resolve_signature(sig):
    if isinstance(sig, Point):
        return sig
    return _load_signature(bytes(sig))


def _affine_or_skip_g1(p):
    """(x_int, y_int, skip) — generator coords when p is infinity."""
    if p.is_infinity():
        g = cv.g1_generator()
        xa, ya = g.affine()
        return xa.v, ya.v, True
    xa, ya = p.affine()
    return xa.v, ya.v, False


def _affine_or_skip_g2(p):
    if p.is_infinity():
        g = cv.g2_generator()
        xa, ya = g.affine()
        return xa, ya, True
    xa, ya = p.affine()
    return xa, ya, False


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length() if n > 1 else 1


# On an accelerator the pairs axis pads to at least one mainnet block
# flush (128 attestations + sync aggregate + proposer + RANDAO, 132
# pairs folded): an invalid block's bisection probes (66, 34, ... pairs)
# then reuse the flush's compiled stage kernels instead of compiling
# ~50 s of TPU programs per power-of-two size.  Padded pairs are skip
# lanes; on CPU hosts they are real serial work, so the floor is 1.
_DEVICE_MIN_PAIRS = 256


def _min_pairs() -> int:
    import jax
    return 1 if jax.default_backend() == "cpu" else _DEVICE_MIN_PAIRS


def _run_pairing_checks(jobs):
    """jobs: list of lists of (G1 Point, G2 Point) pairs.  Returns
    np.bool_ verdicts, one per job.

    Both the batch axis and the pairs axis are padded to powers of two
    (with all-skip (O, O) pairs / rows), so the jitted pairing kernel
    only ever sees log-many shapes — otherwise every committee size or
    attestation count would trigger a fresh multi-minute XLA compile.
    """
    if not jobs:
        return np.zeros(0, dtype=bool)
    n_real = len(jobs)
    k = max(_next_pow2(max(len(j) for j in jobs)), _min_pairs())
    jobs = list(jobs) + [[]] * (_next_pow2(n_real) - n_real)
    xs1, ys1, xs2, ys2, skips = [], [], [], [], []
    for job in jobs:
        row = list(job) + [(cv.g1_infinity(), cv.g2_infinity())] \
            * (k - len(job))
        r_x1, r_y1, r_x2, r_y2, r_s = [], [], [], [], []
        for p, q in row:
            x1, y1, s1 = _affine_or_skip_g1(p)
            x2, y2, s2 = _affine_or_skip_g2(q)
            r_x1.append(x1)
            r_y1.append(y1)
            r_x2.append(x2)
            r_y2.append(y2)
            r_s.append(s1 or s2)
        xs1.append(np.asarray(fq.pack_mont(r_x1)))
        ys1.append(np.asarray(fq.pack_mont(r_y1)))
        xs2.append(np.asarray(ft.fq2_pack_mont(r_x2)))
        ys2.append(np.asarray(ft.fq2_pack_mont(r_y2)))
        skips.append(r_s)
    verdict = pj.pairing_check_jit(
        jnp.asarray(np.stack(xs1)), jnp.asarray(np.stack(ys1)),
        jnp.asarray(np.stack(xs2)), jnp.asarray(np.stack(ys2)),
        jnp.asarray(np.array(skips)))
    return np.asarray(verdict)[:n_real]


# ---------------------------------------------------------------------------
# batched byte-level suite
# ---------------------------------------------------------------------------

def verify_batch(pubkeys, messages, signatures):
    """Batch of independent Verify(pk, msg, sig) jobs -> list[bool]."""
    prepared = []   # (slot, pk, msg, sig)
    results = [False] * len(pubkeys)
    neg_g1 = -cv.g1_generator()
    for i, (pk_b, msg, sig_b) in enumerate(
            zip(pubkeys, messages, signatures)):
        try:
            prepared.append((i, _resolve_pubkey(pk_b), bytes(msg),
                             _resolve_signature(sig_b)))
        except (DecodeError, ValueError):
            continue
    if not prepared:
        return results
    hashes = hash_to_g2_batch([p[2] for p in prepared])
    jobs = [[(pk, h), (neg_g1, sig)]
            for (_, pk, _, sig), h in zip(prepared, hashes)]
    for (i, *_), v in zip(prepared, _run_pairing_checks(jobs)):
        results[i] = bool(v)
    return results


def _fold_coefficients(prepared):
    """64-bit nonzero Fiat-Shamir coefficients for a
    FastAggregateVerifyBatch fold, bound to a length-framed transcript
    of the whole batch (slot, compressed aggregate, message, compressed
    signature — so no two distinct batches share a transcript).  Same
    derivation discipline as the fused scheduler's `_coefficients`."""
    h = hashlib.sha256()
    h.update(len(prepared).to_bytes(4, "little"))
    for i, agg, msg, sig in prepared:
        h.update(i.to_bytes(4, "little"))
        h.update(cv.g1_to_bytes(agg))
        h.update(len(msg).to_bytes(4, "little"))
        h.update(msg)
        h.update(cv.g2_to_bytes(sig))
    seed = h.digest()
    out = []
    for i in range(len(prepared)):
        x = int.from_bytes(
            hashlib.sha256(seed + i.to_bytes(4, "little")).digest()[:8],
            "little")
        out.append(1 + x % (2**64 - 1))
    return out


def fast_aggregate_verify_batch(pubkey_lists, messages, signatures):
    """Batch of FastAggregateVerify jobs (shared message per job).

    With folding live (sigpipe/fold.py; ``FOLD_VERIFY=0`` restores the
    2N shape), the whole batch rides ONE (N+1)-pair job: bilinearity
    moves a per-job Fiat-Shamir coefficient onto each side —

        prod_i e(c_i*agg_i, h_i) * e(-g1, S),   S = sum_i c_i * sig_i

    — with S folded through the ``ops.pairing_fold`` seam (one batched
    G2 MSM dispatch, host ladder as counted fallback).  A passing
    product proves every job valid; a failing one degrades to the exact
    per-job 2-leg derivation so per-job attribution is unchanged."""
    prepared = []   # (slot, agg, msg, sig)
    results = [False] * len(pubkey_lists)
    neg_g1 = -cv.g1_generator()
    for i, (pks, msg, sig_b) in enumerate(
            zip(pubkey_lists, messages, signatures)):
        if not len(pks):
            continue
        try:
            agg = cv.g1_infinity()
            for pk_b in pks:
                agg = agg + _resolve_pubkey(pk_b)
            prepared.append((i, agg, bytes(msg),
                             _resolve_signature(sig_b)))
        except (DecodeError, ValueError):
            continue
    if not prepared:
        return results
    hashes = hash_to_g2_batch([p[2] for p in prepared])
    from ..sigpipe import fold
    if fold.live() and len(prepared) > 1:
        coeffs = _fold_coefficients(prepared)
        S = fold.fold_signatures([sig for (_, _, _, sig) in prepared],
                                 coeffs)
        folded = [(agg * c, h) for (_, agg, _, _), c, h
                  in zip(prepared, coeffs, hashes)]
        folded.append((neg_g1, S))
        METRICS.observe("miller_loops_per_batch", len(folded))
        if bool(_run_pairing_checks([folded])[0]):
            for (i, *_) in prepared:
                results[i] = True
            return results
        # >=1 job is invalid: exact per-job legs for attribution
    jobs = [[(agg, h), (neg_g1, sig)]
            for (_, agg, _, sig), h in zip(prepared, hashes)]
    METRICS.observe("miller_loops_per_batch", 2 * len(jobs))
    for (i, *_), v in zip(prepared, _run_pairing_checks(jobs)):
        results[i] = bool(v)
    return results


def _fold_coefficients_multi(prepared):
    """64-bit nonzero Fiat-Shamir coefficients for an
    AggregateVerifyBatch fold.  Multi-message transcript: each job
    binds its slot, every (compressed pubkey, length-framed message)
    pair IN ORDER, and the compressed signature — so permuting
    pk/message pairs within a job, or moving a pair between jobs,
    changes every coefficient."""
    h = hashlib.sha256(b"aggregate-verify-fold-v1")
    h.update(len(prepared).to_bytes(4, "little"))
    for i, pk_points, msgs, sig in prepared:
        h.update(i.to_bytes(4, "little"))
        h.update(len(msgs).to_bytes(4, "little"))
        for pk, msg in zip(pk_points, msgs):
            h.update(cv.g1_to_bytes(pk))
            h.update(len(msg).to_bytes(4, "little"))
            h.update(msg)
        h.update(cv.g2_to_bytes(sig))
    seed = h.digest()
    out = []
    for i in range(len(prepared)):
        x = int.from_bytes(
            hashlib.sha256(seed + i.to_bytes(4, "little")).digest()[:8],
            "little")
        out.append(1 + x % (2**64 - 1))
    return out


def aggregate_verify_batch(pubkey_lists, message_lists, signatures):
    """Batch of AggregateVerify jobs (distinct message per pubkey).

    With folding live (sigpipe/fold.py; ``FOLD_VERIFY=0`` restores the
    per-job shape), the whole batch rides ONE job of
    sum_i len(msgs_i) + 1 pairs: a per-job Fiat-Shamir coefficient
    scales every pubkey leg of job i and its signature's contribution
    to the folded S —

        prod_i prod_j e(c_i*pk_ij, h_ij) * e(-g1, S),
        S = sum_i c_i * sig_i

    — with S folded through the ``ops.pairing_fold`` seam exactly like
    the fast-aggregate path.  A passing product proves every job
    valid; a failing one degrades to the exact per-job derivation so
    per-job attribution is unchanged."""
    prepared = []   # (slot, pk_points, msgs, sig)
    results = [False] * len(pubkey_lists)
    neg_g1 = -cv.g1_generator()
    for i, (pks, msgs, sig_b) in enumerate(
            zip(pubkey_lists, message_lists, signatures)):
        if not len(pks) or len(pks) != len(msgs):
            continue
        try:
            pk_points = [_resolve_pubkey(pk_b) for pk_b in pks]
            prepared.append((i, pk_points, [bytes(m) for m in msgs],
                             _resolve_signature(sig_b)))
        except (DecodeError, ValueError):
            continue
    if not prepared:
        return results
    # one flat hash batch across all jobs, then regroup
    flat_msgs = [m for (_, _, msgs, _) in prepared for m in msgs]
    flat_hashes = hash_to_g2_batch(flat_msgs)
    grouped = []
    pos = 0
    for (_, _, msgs, _) in prepared:
        grouped.append(flat_hashes[pos:pos + len(msgs)])
        pos += len(msgs)
    from ..sigpipe import fold
    if fold.live() and len(prepared) > 1:
        coeffs = _fold_coefficients_multi(prepared)
        S = fold.fold_signatures([sig for (_, _, _, sig) in prepared],
                                 coeffs)
        folded = []
        for (_, pk_points, _, _), c, hs in zip(prepared, coeffs,
                                               grouped):
            folded.extend((pk * c, h) for pk, h in zip(pk_points, hs))
        folded.append((neg_g1, S))
        METRICS.observe("miller_loops_per_batch", len(folded))
        if bool(_run_pairing_checks([folded])[0]):
            for (i, *_) in prepared:
                results[i] = True
            return results
        # >=1 job is invalid: exact per-job legs for attribution
    jobs = [list(zip(pk_points, hs)) + [(neg_g1, sig)]
            for (_, pk_points, _, sig), hs in zip(prepared, grouped)]
    METRICS.observe("miller_loops_per_batch",
                    sum(len(j) for j in jobs))
    for (i, *_), v in zip(prepared, _run_pairing_checks(jobs)):
        results[i] = bool(v)
    return results


def pairing_check_points(pairs):
    """Single pairing-check over oracle Point pairs (KZG verify path)."""
    live = [(p, q) for p, q in pairs
            if not (p.is_infinity() or q.is_infinity())]
    if not live:
        return True
    return bool(_run_pairing_checks([live])[0])


# single-job conveniences (the utils.bls shim routes through these)
def Verify(pubkey, message, signature) -> bool:
    return verify_batch([pubkey], [message], [signature])[0]


def FastAggregateVerify(pubkeys, message, signature) -> bool:
    return fast_aggregate_verify_batch([pubkeys], [message], [signature])[0]


def AggregateVerify(pubkeys, messages, signature) -> bool:
    return aggregate_verify_batch([pubkeys], [messages], [signature])[0]
