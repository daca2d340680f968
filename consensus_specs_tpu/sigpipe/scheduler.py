"""Scheduling: many signature sets -> fewest verification dispatches.

Two strategies, both returning one verdict per set:

* ``fused`` (default) — every *required* set contributes two pairs to ONE
  combined pairing-product check: e(c_i * agg_pk_i, H(root_i)) *
  e(-c_i * g1, sig_i), product over all sets == 1.  One device dispatch
  for the block's gating checks (the batch axis inside the pairing kernel
  is already padded to power-of-two buckets, so XLA recompiles stay
  bounded).  The c_i are 64-bit Fiat-Shamir coefficients derived from a
  length-framed digest of the batch content: without them a block
  carrying two individually-invalid signatures whose errors cancel would
  pass the product check (the classic aggregate-splitting attack); with
  them cancellation requires predicting coefficients that depend on the
  very signatures being chosen, leaving ~2^-64 residual risk — same
  design as production client batch verification.  On a False product
  the bisection fallback (bisect.py) re-dispatches halves to isolate the
  offending sets.  Valid-or-skip sets (``required=False`` — deposit
  proofs of possession, which the spec skips rather than rejects) ride a
  separate per-set dispatch instead: an invalid deposit in an otherwise
  valid block is routine and must not trigger bisection of the product.

* ``per-set`` — homogeneous grouping through the shim's batch entry
  points: single-pubkey sets ride one `VerifyBatch`, aggregate sets one
  `FastAggregateVerifyBatch` (<= 2 dispatches, per-set verdicts
  directly; the batch APIs handle decompression, aggregation and
  decode-failure screening themselves).  The cross-check oracle for the
  fused path, and the mode that keeps the shim's batch APIs exercised
  from the spec layer.

Degenerate sets never reach a dispatch: empty pubkey lists and
undecodable points read as invalid immediately, exactly matching the
scalar API's False-on-DecodeError contract.

DEVICE G1 SWEEP.  The elliptic-curve *preparation* of a flush is
batched onto the accelerator alongside the pairing itself: all cold
committee sums ride one `ops.g1_aggregate` dispatch
(cache.aggregate_many -> ops/g1_sweep.py) and all 2N Fiat-Shamir
weightings one `ops.msm` dispatch (`_weighted_g1` ->
ops/msm.g1_weighted_sweep), so a flush costs O(1) device calls where it
used to cost O(sets x committee) host point ops.  Both sites carry the
per-set host loop as supervised byte-identical fallback (every fallback
add counted in `host_point_adds`), and the bisection path re-derives
its weighted pairs on the host ladder so a corrupt device sweep cannot
flip a verdict through a FAILING product (valid sets survive the host
re-check).  The accept direction is weaker by construction: a sweep
returning all-identity points makes the product vacuously pass and
bisection never runs — that corruption is the differential guard's
case (guard.py), not this path's.

MULTI-CHIP.  With a >1-device verify mesh (parallel/shard_verify.py)
all three dispatches spread over the mesh: the sweeps shard their
padded job axes inside the same `ops.g1_aggregate`/`ops.msm` seams,
and the fused product partitions its pairs axis at the
`ops.pairing_product` seam — per-shard partial Fp12 Miller products,
Fp12-multiply all-reduce, ONE final exponentiation — taken instead of
`bls.pairing_check` only when the tpu backend is active.  One device
(tier-1 CPU) is byte-identical to the unsharded path, and "one shard
of the mesh died" is just another fault (`shard_dead` in
resilience/faults.py: same breaker -> scalar-fallback -> half-open
contract; docs/sigpipe.md "Sharded verify").

FOLDED SIGNATURE LEGS.  The `e(-c_i * g1, sig_i)` legs all share the
base -g1, so by bilinearity they fold to ONE pair `e(-g1, S)` over the
G2 MSM `S = sum_i c_i * sig_i` (sigpipe/fold.py, the
`ops.pairing_fold` seam): an N-set flush pays N+1 Miller loops instead
of 2N — the counted `miller_loops_per_flush` invariant — and the
weighted-G1 MSM halves to N jobs.  On the tpu backend with the fused
pairing mode the ENTIRE folded flush further fuses into one compiled
program per mesh shard (fold.fold_flush -> shard_verify.pairing_fold:
cofactor sweep + weighting + G2 MSM + partial Miller product in one
launch, the log2(D) Fp12 all-reduce unchanged).  Bisection is
untouched either way — probes re-derive both legs per set on the HOST
ladder — and `FOLD_VERIFY=0` restores the 2N-leg flush byte-for-byte
(docs/sigpipe.md "Folded pairing product").
"""
from __future__ import annotations

import hashlib

from ..crypto import curve as cv
from ..crypto.curve import DecodeError
from ..utils import bls
from ..utils.spans import span
from . import bisect as _bisect
from . import fold
from . import pipeline_async
from .cache import AGGREGATES
from .metrics import METRICS


def _hash_roots(roots):
    """hash-to-G2 of every signing root; one device cofactor sweep on the
    tpu backend (supervised, host math as fallback), host math on
    native."""
    def host():
        from ..crypto.hash_to_curve import hash_to_g2
        return [hash_to_g2(r) for r in roots]
    if bls.current_backend() == "tpu":
        from ..ops.bls_tpu import hash_to_g2_batch
        from ..resilience.supervisor import dispatch
        return dispatch("sigpipe.hash_to_g2_batch",
                        lambda: hash_to_g2_batch(roots), host)
    return host()


def _coefficients(entries):
    """64-bit nonzero Fiat-Shamir coefficients, one per entry, bound to a
    length-framed digest of the whole batch (set count, per-set pubkey
    count and field lengths are all hashed, so no two distinct batch
    layouts share a transcript)."""
    h = hashlib.sha256()
    h.update(len(entries).to_bytes(4, "little"))
    for s, _agg, _sig in entries:
        h.update(len(s.pubkeys).to_bytes(4, "little"))
        for pk in s.pubkeys:
            h.update(pk)
        h.update(len(s.signing_root).to_bytes(4, "little"))
        h.update(s.signing_root)
        h.update(s.signature)
    seed = h.digest()
    out = []
    for i in range(len(entries)):
        x = int.from_bytes(
            hashlib.sha256(seed + i.to_bytes(4, "little")).digest()[:8],
            "little")
        out.append(1 + x % (2**64 - 1))
    return out


def _load_signature(signature) -> cv.Point:
    """A fresh decode and subgroup check, never the scalar oracle's memo:
    a node meets each block's signatures for the first time."""
    return cv.g2_from_bytes(bytes(signature))


def _prepare(indices, sets, verdicts):
    """Decode each set's signature and batch-aggregate every G1 side
    through the aggregate cache: all cold committee sums of the flush
    fuse into ONE `ops.g1_aggregate` device dispatch
    (cache.aggregate_many) instead of a per-set Python add loop.  Fills
    `verdicts` with False for sets the scalar API would reject before
    pairing."""
    pending = []
    for i in indices:
        s = sets[i]
        if len(s.pubkeys) == 0:
            verdicts[i] = False      # scalar FastAggregateVerify: False
            continue
        try:
            sig = _load_signature(s.signature)
        except (DecodeError, ValueError):
            verdicts[i] = False
            continue
        pending.append((i, s, sig))
    aggs = AGGREGATES.aggregate_many(
        [(s.pubkeys, s.hint) for _i, s, _sig in pending])
    prepared = []
    for (i, _s, sig), agg in zip(pending, aggs):
        if agg is None:              # a pubkey failed decode/validation
            verdicts[i] = False
            continue
        prepared.append((i, agg, sig))
    return prepared


def _host_scalar_mul(point, k):
    """Host double-and-add ladder with its point-op cost counted — the
    per-set arithmetic the device sweep exists to eliminate (~96 ops
    per 64-bit coefficient)."""
    k = int(k)
    METRICS.inc("host_point_adds",
                max(k.bit_length(), 1) + bin(k).count("1"))
    return point * k


def _pairing_product(pairs):
    """The fused product's single device dispatch.  With a >1-device
    verify mesh and the device pairing kernels active, the pairs axis
    is partitioned over the mesh — per-shard partial Fp12 Miller
    products, all-reduced by Fp12 multiply into one final
    exponentiation — at the `ops.pairing_product` seam
    (parallel/shard_verify.py, host pairing oracle as fallback).
    Otherwise this is exactly the single-device `bls.pairing_check`
    seam, so tier-1 CPU runs are byte-identical."""
    if bls.current_backend() == "tpu":    # cheap gate before the
        from ..parallel import shard_verify   # jax-heavy mesh import
        if shard_verify.pairing_live():
            return shard_verify.pairing_product(pairs)
    return bls.pairing_check(pairs)


def _weighted_g1(points, coeffs):
    """All 2N Fiat-Shamir weightings of a flush as ONE batched dispatch
    (ops/msm.py `g1_weighted_sweep`) behind the `ops.msm` resilience
    seam; the supervised fallback is the byte-identical per-pair host
    ladder."""
    from ..ops import msm as _msm
    from ..resilience.supervisor import dispatch
    METRICS.inc("msm_dispatches")
    return dispatch(
        "ops.msm",
        lambda: _msm.g1_weighted_sweep(points, coeffs),
        lambda: [_host_scalar_mul(p, c)
                 for p, c in zip(points, coeffs)])


def _verify_fused(sets, prepared, verdicts, strict=None, hash_leg=None):
    """`hash_leg` (pipeline_async.Leg over the STRICT indices' roots)
    is the overlapped hash-to-G2 dispatch: launched before `_prepare`'s
    G1 aggregation sweep, joined here AFTER the weighted MSM — so all
    three of a flush's verify dispatches are in flight with no
    host-sync stall between them, and the first forced read is the
    verdict join below.  Without a leg (ASYNC_FLUSH=0, scenario
    fleets) the dispatch order is byte-for-byte the historical one.

    With folding live (sigpipe/fold.py, the default) the flush emits
    N+1 pairing legs — N weighted aggregate legs plus ONE `e(-g1, S)`
    leg over the folded G2 MSM — instead of 2N; on the one-launch path
    (tpu backend, fused pairing mode) the whole chain collapses into a
    single `ops.pairing_fold` dispatch.  `FOLD_VERIFY=0` restores the
    2N-leg assembly byte-for-byte."""
    entries = [(sets[i], agg, sig) for i, agg, sig in prepared]
    folded = fold.live()
    one_launch = folded and hash_leg is None and fold.one_launch_live()
    roots = [s.signing_root for s, _, _ in entries]
    hashes = None
    if hash_leg is None and not one_launch:
        hashes = _hash_roots(roots)
    coeffs = _coefficients(entries)
    neg_g1 = -cv.g1_generator()
    METRICS.inc_labeled("fold_enabled", "on" if folded else "off")
    if one_launch:
        # ONE launch per shard: hash cofactor sweep + G1 weighting +
        # G2 signature MSM + partial Miller products fused into a
        # single `ops.pairing_fold` dispatch (the pairs-axis all-reduce
        # and final exponentiation unchanged)
        METRICS.inc("dispatches")
        ok = fold.fold_flush(
            [agg for _s, agg, _sig in entries], coeffs, roots,
            [sig for _s, _agg, sig in entries])
    else:
        if folded:
            # N weightings instead of 2N: the signature legs need no
            # G1 weighting — their coefficients ride the G2 fold
            bases = [agg for _s, agg, _sig in entries]
            scalars = list(coeffs)
        else:
            bases, scalars = [], []
            for (_s, agg, _sig), c in zip(entries, coeffs):
                bases.extend((agg, neg_g1))
                scalars.extend((c, c))
        weighted_flat = _weighted_g1(bases, scalars)
        if folded:
            S = fold.fold_signatures(
                [sig for _s, _agg, sig in entries], coeffs)
        if hash_leg is not None:
            # join as late as the data flow allows: hash-to-G2 of every
            # strict root ran concurrently with prepare/aggregate/MSM
            # (and the G2 fold); a set `_prepare` screened out (bad
            # signature, cold decode failure) simply leaves its hash
            # unused — per-root outputs are independent, so the subset
            # is byte-identical to hashing only the surviving roots
            all_hashes = hash_leg.get()
            pos = {i: k for k, i in enumerate(strict)}
            hashes = [all_hashes[pos[i]] for i, _agg, _sig in prepared]
        if folded:
            pairs = [(weighted_flat[k], h)
                     for k, h in enumerate(hashes)]
            pairs.append((neg_g1, S))
        else:
            pairs = []
            for k, ((_s, _agg, sig), h) in enumerate(
                    zip(entries, hashes)):
                pairs.append((weighted_flat[2 * k], h))
                pairs.append((weighted_flat[2 * k + 1], sig))
        METRICS.observe("miller_loops_per_flush", len(pairs))
        METRICS.inc("dispatches")
        ok = _pairing_product(pairs)

    def group_valid(sub_groups):
        # bisection probe: re-derive each group's weighted pairs on the
        # HOST ladder, so invalid-set isolation never trusts a possibly
        # corrupt device sweep OR a corrupt folded MSM — a lying device
        # answer degrades to one failed product plus an oracle-weighted
        # re-check, not to wrong per-set verdicts.  Probes always carry
        # both legs per set (the folded product cannot attribute, so
        # isolation re-derives the unfolded algebra)
        METRICS.inc("dispatches")
        probe_pairs = []
        for agg, c, h, sig in sub_groups:
            probe_pairs.append((_host_scalar_mul(agg, c), h))
            probe_pairs.append((_host_scalar_mul(neg_g1, c), sig))
        return bls.pairing_check(probe_pairs)

    if ok:
        bad_local = set()
    else:
        METRICS.inc("fused_batch_failures")
        if hashes is None:
            # one-launch failure: the per-set hashes never existed on
            # the host — derive them now for the probes (the same
            # supervised hash seam the staged chain crosses)
            hashes = _hash_roots(roots)
        groups = [(agg, c, h, sig) for (_s, agg, sig), h, c in zip(
            entries, hashes, coeffs)]
        if len(groups) == 1:
            # isolate_failures condemns a singleton without re-probing
            # (its contract assumes the caller's failing check is
            # trusted) — but OUR failing product used device-weighted
            # points, so a one-set flush must re-check on the host
            # ladder or a corrupt sweep could flip the verdict
            bad_local = set() if group_valid(groups) else {0}
        else:
            bad_local = set(_bisect.isolate_failures(groups, group_valid))
    for rank, (i, _agg, _sig) in enumerate(prepared):
        verdicts[i] = rank not in bad_local


def _verify_per_set(indices, sets, verdicts):
    """Per-set verdicts through the shim's batch APIs (which screen empty
    lists and decode failures themselves — no preparation needed)."""
    singles = [i for i in indices if len(sets[i].pubkeys) == 1]
    multis = [i for i in indices if len(sets[i].pubkeys) != 1]
    if singles:
        METRICS.inc("dispatches")
        for i, v in zip(singles, bls.VerifyBatch(
                [sets[i].pubkeys[0] for i in singles],
                [sets[i].signing_root for i in singles],
                [sets[i].signature for i in singles])):
            verdicts[i] = bool(v)
    if multis:
        # the multi-pubkey leg: every job's committee sum rides the one
        # batched aggregation dispatch, and the batch API receives the
        # pre-aggregated point (the aggregate of one point is itself).
        # Jobs whose pubkeys fail decode keep their original list — the
        # batch API's own screening reads them as invalid — and so does
        # the identity aggregate (a pubkey list summing to infinity
        # must reach the scalar check undisturbed: compressed-infinity
        # pubkeys are rejected at decode, which a substitution would
        # wrongly trigger).
        aggs = AGGREGATES.aggregate_many(
            [(sets[i].pubkeys, sets[i].hint) for i in multis])
        pk_lists = [
            [agg] if agg is not None and not agg.is_infinity()
            else list(sets[i].pubkeys)
            for i, agg in zip(multis, aggs)]
        METRICS.inc("dispatches")
        for i, v in zip(multis, bls.FastAggregateVerifyBatch(
                pk_lists,
                [sets[i].signing_root for i in multis],
                [sets[i].signature for i in multis])):
            verdicts[i] = bool(v)


def _guard_verdicts(sets, verdicts, reason_for=None):
    """Differential guard (resilience/guard.py): cross-check a sample of
    batch verdicts against the scalar oracle; on mismatch the backend is
    quarantined and EVERY verdict is recomputed on the trusted path —
    silent corruption degrades to the oracle instead of deciding.
    `reason_for(i)` labels the fallback (and the quarantine) by the
    path that produced the MISMATCHING verdict: `fold_mismatch` for a
    folded fused leg, `guard_mismatch` otherwise — so incident streams
    attribute a folded-path trip precisely, and a corruption in an
    unrelated leg (a lax per-set batch of the same flush) never points
    operators at the fold."""
    from ..resilience import guard
    g = guard.active()
    if g is None:
        return verdicts
    mismatch = g.check(sets, list(range(len(sets))), verdicts,
                       reason_for=reason_for)
    if mismatch is None:
        return verdicts
    METRICS.inc_labeled("scalar_fallbacks", mismatch)
    return [guard.oracle_verdict(s) for s in sets]


def verify_sets(sets, mode: str = "fused"):
    """Verdict per SignatureSet.  `mode` is "fused" or "per-set"."""
    n = len(sets)
    if n == 0:
        return []       # an empty window is not a batch: no dispatch,
        # no stub counting, no occupancy sample
    METRICS.observe("batch_size", n)
    METRICS.observe_hist("batch_occupancy", n)
    METRICS.inc("signatures_scheduled", n)
    if not bls.bls_active:
        # stub-True contract, zero dispatches (matches the scalar API)
        METRICS.inc("stubbed_batches")
        return [True] * n
    verdicts: list = [None] * n
    guard_reason_for = None
    if mode == "per-set":
        _verify_per_set(list(range(n)), sets, verdicts)
    elif mode == "fused":
        if fold.live():
            # only the strict (required) sets ride the folded
            # product; lax sets take the per-set batch APIs, so a
            # mismatch there keeps the legacy label
            guard_reason_for = (
                lambda i: "fold_mismatch" if sets[i].required
                else "guard_mismatch")
        strict = [i for i, s in enumerate(sets) if s.required]
        lax = [i for i, s in enumerate(sets) if not s.required]
        hash_leg = None
        if strict and pipeline_async.overlap_live() \
                and not fold.one_launch_live():
            # overlapped leg: hash-to-G2 needs only the signing
            # roots, so it launches BEFORE the G1 aggregation sweep
            # and runs concurrently with the whole prepare chain.
            # The one-launch folded path owns the cofactor sweep
            # inside its single fused program — nothing to overlap
            roots = [sets[i].signing_root for i in strict]
            hash_leg = pipeline_async.launch_leg(
                lambda: _hash_roots(roots), "hash_to_g2")
        with span("sigpipe.prepare"):
            prepared = _prepare(strict, sets, verdicts)
        if prepared:
            _verify_fused(sets, prepared, verdicts, strict, hash_leg)
        elif hash_leg is not None:
            # every strict set screened out pre-pairing: drain the
            # leg so nothing is left in flight past this flush
            hash_leg.get()
        if lax:
            _verify_per_set(lax, sets, verdicts)
    else:
        raise ValueError(f"unknown sigpipe mode {mode!r}")
    verdicts = _guard_verdicts(sets, verdicts,
                               reason_for=guard_reason_for)
    return verdicts
