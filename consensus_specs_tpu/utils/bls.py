"""Pluggable BLS backend shim — the primary plug point of the framework.

Capability parity with the reference's eth2spec.utils.bls
(/root/reference/tests/core/pyspec/eth2spec/utils/bls.py:74-397): a
module-global backend switched at runtime, a `bls_active` flag that lets the
test harness stub signature checks, and the spec-facing API
(Sign/Verify/Aggregate/FastAggregateVerify/AggregateVerify/AggregatePKs/
SkToPk/KeyValidate) plus low-level curve ops used by KZG and Whisk.

Backends:
  * "native" — our from-scratch pure-Python BLS12-381 (crypto/bls12_381.py),
    the correctness oracle.
  * "tpu"    — JAX/Pallas batched verification kernels (ops/), falling back
    to native for single ops until each kernel lands.
"""
from __future__ import annotations

import functools

from .memo import Memo

# global switches (reference: bls.py:74-124)
bls_active = True
_backend_name = "native"

STUB_SIGNATURE = b"\x11" * 96
STUB_PUBKEY = b"\x22" * 48
STUB_COORDINATES = (0, 0)


def use_backend(name: str) -> None:
    global _backend_name
    if name not in ("native", "tpu", "fastest"):
        raise ValueError(f"unknown bls backend {name!r}")
    if name == "fastest":
        name = "tpu"
    _backend_name = name


def use_native() -> None:
    use_backend("native")


def use_tpu() -> None:
    use_backend("tpu")


def current_backend() -> str:
    return _backend_name


def only_with_bls(alt_return=None):
    """Decorator: skip the wrapped function when bls is disabled
    (reference: bls.py:127-138)."""
    def decorator(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not bls_active:
                return alt_return
            return fn(*args, **kwargs)
        return wrapper
    return decorator


def _native():
    from ..crypto import bls12_381 as n
    return n


def _tpu():
    from ..ops import bls_tpu as t
    return t


def _dispatch(site, device_fn, fallback_fn):
    """Route one accelerator dispatch through the resilience seam
    (fault injection + circuit-breaker supervision when enabled; a plain
    call otherwise).  Lazy import: resilience pulls in sigpipe.metrics,
    and importing it at module scope would cycle through sigpipe ->
    scheduler -> this module."""
    from ..resilience.supervisor import dispatch
    return dispatch(site, device_fn, fallback_fn)


# --- signature API (reference: bls.py:141-221) -----------------------------

# Native scalar paths, shared between the default backend branch and the
# supervised fallback of every device dispatch (byte-identical semantics:
# backend import errors surface, DecodeError/ValueError reads as invalid).

# memos BELOW the dispatch seam: fault injection, supervision and the
# differential guard still fire on every call
_SIGN_MEMO = Memo()
_VERIFY_MEMO = Memo()


def _native_verify(PK, message, signature):
    key = ("v", bytes(PK), bytes(message), bytes(signature))

    def compute():
        try:
            return _native().Verify(key[1], key[2], key[3])
        except ValueError:
            return False
    return _VERIFY_MEMO.get(key, compute)


def _native_aggregate_verify(pubkeys, messages, signature):
    key = ("av", tuple(bytes(pk) for pk in pubkeys),
           tuple(bytes(m) for m in messages), bytes(signature))

    def compute():
        try:
            return _native().AggregateVerify(
                list(key[1]), list(key[2]), key[3])
        except ValueError:
            return False
    return _VERIFY_MEMO.get(key, compute)


def _native_fast_aggregate_verify(pubkeys, message, signature):
    key = ("fav", tuple(bytes(pk) for pk in pubkeys), bytes(message),
           bytes(signature))

    def compute():
        try:
            return _native().FastAggregateVerify(
                list(key[1]), key[2], key[3])
        except ValueError:
            return False
    return _VERIFY_MEMO.get(key, compute)


@only_with_bls(alt_return=True)
def Verify(PK, message, signature):
    if _backend_name == "tpu":
        return _dispatch(
            "bls.verify",
            lambda: _tpu().Verify(bytes(PK), bytes(message),
                                  bytes(signature)),
            lambda: _native_verify(PK, message, signature))
    return _native_verify(PK, message, signature)


@only_with_bls(alt_return=True)
def AggregateVerify(pubkeys, messages, signature):
    if _backend_name == "tpu":
        return _dispatch(
            "bls.aggregate_verify",
            lambda: _tpu().AggregateVerify(
                [bytes(pk) for pk in pubkeys],
                [bytes(m) for m in messages], bytes(signature)),
            lambda: _native_aggregate_verify(pubkeys, messages, signature))
    return _native_aggregate_verify(pubkeys, messages, signature)


@only_with_bls(alt_return=True)
def FastAggregateVerify(pubkeys, message, signature):
    if _backend_name == "tpu":
        return _dispatch(
            "bls.fast_aggregate_verify",
            lambda: _tpu().FastAggregateVerify(
                [bytes(pk) for pk in pubkeys], bytes(message),
                bytes(signature)),
            lambda: _native_fast_aggregate_verify(pubkeys, message,
                                                  signature))
    return _native_fast_aggregate_verify(pubkeys, message, signature)


# --- batched verification (TPU-native extension; one device dispatch for a
# block's worth of signature checks) ----------------------------------------

def _pk_bytes(pk):
    """Batch APIs accept compressed bytes or decompressed curve Points
    (the pubkey-cache shape); normalize for the byte-level native suite."""
    if hasattr(pk, "is_infinity"):
        return _native().G1_to_bytes48(pk)
    return bytes(pk)


def _sig_bytes(sig):
    if hasattr(sig, "is_infinity"):
        return _native().G2_to_bytes96(sig)
    return bytes(sig)


def _stub_or_dispatch(site, n_jobs, tpu_fn, native_fn):
    """Shared batch-API contract: with bls disabled every job reads as
    valid (the scalar APIs' stub-True semantics — one helper so the three
    batch entry points can't drift), the tpu backend runs all pairings as
    one batched kernel dispatch, and native falls back per-job.

    The batch boundary is a resilience dispatch seam on EVERY backend
    (it is where a whole block's verdicts ride one call), with the
    per-job native loop as the supervised fallback — so a fault-injection
    chaos run and a wedged device both degrade to the scalar oracle
    instead of deciding block validity."""
    if not bls_active:
        return [True] * n_jobs
    device_fn = tpu_fn if _backend_name == "tpu" else native_fn
    return _dispatch(site, device_fn, native_fn)


def FastAggregateVerifyBatch(pubkey_lists, messages, signatures):
    """Verdict list for many FastAggregateVerify jobs."""
    return _stub_or_dispatch(
        "bls.fast_aggregate_verify_batch",
        len(pubkey_lists),
        lambda: _tpu().fast_aggregate_verify_batch(
            pubkey_lists, messages, signatures),
        lambda: [_native_fast_aggregate_verify(
                     [_pk_bytes(pk) for pk in pks], m, _sig_bytes(s))
                 for pks, m, s in zip(pubkey_lists, messages, signatures)])


def VerifyBatch(pubkeys, messages, signatures):
    """Verdict list for many independent Verify jobs."""
    return _stub_or_dispatch(
        "bls.verify_batch",
        len(pubkeys),
        lambda: _tpu().verify_batch(pubkeys, messages, signatures),
        lambda: [_native_verify(_pk_bytes(pk), m, _sig_bytes(s))
                 for pk, m, s in zip(pubkeys, messages, signatures)])


def AggregateVerifyBatch(pubkey_lists, message_lists, signatures):
    """Verdict list for many AggregateVerify jobs (distinct message per
    pubkey within each job)."""
    return _stub_or_dispatch(
        "bls.aggregate_verify_batch",
        len(pubkey_lists),
        lambda: _tpu().aggregate_verify_batch(
            pubkey_lists, message_lists, signatures),
        lambda: [_native_aggregate_verify(
                     [_pk_bytes(pk) for pk in pks], ms, _sig_bytes(s))
                 for pks, ms, s in zip(pubkey_lists, message_lists,
                                       signatures)])


@only_with_bls(alt_return=STUB_SIGNATURE)
def Aggregate(signatures):
    return _native().Aggregate([bytes(s) for s in signatures])


@only_with_bls(alt_return=STUB_SIGNATURE)
def Sign(SK, message):
    key = (int(SK), bytes(message))
    return _SIGN_MEMO.get(key, lambda: _native().Sign(key[0], key[1]))


@only_with_bls(alt_return=STUB_PUBKEY)
def AggregatePKs(pubkeys):
    return _native().AggregatePKs([bytes(pk) for pk in pubkeys])


@only_with_bls(alt_return=STUB_PUBKEY)
def SkToPk(SK):
    return _native().SkToPk(int(SK))


def KeyValidate(pubkey) -> bool:
    return _native().KeyValidate(bytes(pubkey))


# --- low-level curve API for KZG/Whisk (reference: bls.py:224-392) ---------

def add(lhs, rhs):
    return _native().add(lhs, rhs)


def multiply(point, scalar):
    return _native().multiply(point, scalar)


def neg(point):
    return _native().neg(point)


# minimum batch size for device MSM dispatch: below this the per-call
# transfer + kernel launch (and a first-time XLA compile per shape) dwarfs
# the host Pippenger cost
MULTI_EXP_DEVICE_THRESHOLD = 128


def multi_exp(points, integers):
    """Multi-scalar multiplication over G1 or G2 points (the reference's
    arkworks multiexp slot, bls.py:224-296).  The tpu backend routes big
    G1/G2 batches through the device MSM kernel, supervised with the host
    Pippenger oracle as fallback."""
    if (_backend_name == "tpu"
            and len(points) >= MULTI_EXP_DEVICE_THRESHOLD):
        from ..crypto import curve as cv
        from ..ops import msm as device_msm
        first = points[0]
        if isinstance(first, cv.Point):
            if isinstance(first.x, cv.Fq1):
                return _dispatch(
                    "ops.msm.g1",
                    lambda: device_msm.g1_multi_exp(points, integers),
                    lambda: _native().multi_exp(points, integers))
            return _dispatch(
                "ops.msm.g2",
                lambda: device_msm.g2_multi_exp(points, integers),
                lambda: _native().multi_exp(points, integers))
    return _native().multi_exp(points, integers)


def pairing_check(values) -> bool:
    """Combined pairing-product check — the fused scheduler's single
    device dispatch rides this seam, so a hung or lying pairing kernel
    degrades to the host oracle instead of deciding block validity."""
    if _backend_name == "tpu":
        device_fn = lambda: _tpu().pairing_check_points(values)  # noqa: E731
    else:
        device_fn = lambda: _native().pairing_check(values)      # noqa: E731
    return _dispatch("bls.pairing_check", device_fn,
                     lambda: _native().pairing_check(values))


def G1_to_bytes48(point) -> bytes:
    return _native().G1_to_bytes48(point)


def bytes48_to_G1(b):
    return _native().bytes48_to_G1(bytes(b))


def G2_to_bytes96(point) -> bytes:
    return _native().G2_to_bytes96(point)


def bytes96_to_G2(b):
    return _native().bytes96_to_G2(bytes(b))


def Z1():
    return _native().Z1()


def Z2():
    return _native().Z2()


def G1():
    return _native().G1()


def G2():
    return _native().G2()
