"""BLS12-381 suite tests: group laws, pairing identities against the
production KZG trusted setup, signature round trips, shim behavior.

Mirrors the reference's bls test-vector generator coverage
(/root/reference/tests/generators/bls/main.py) at unit granularity.
"""
import json
import random

import pytest

from consensus_specs_tpu.crypto import bls12_381 as native
from consensus_specs_tpu.crypto import curve as cv
from consensus_specs_tpu.crypto.fields import R, Q, Fq2
from consensus_specs_tpu.crypto.pairing import pairing
from consensus_specs_tpu.crypto.hash_to_curve import (
    hash_to_g2, sswu_map, iso_map, expand_message_xmd, H_EFF,
    clear_cofactor,
)
from consensus_specs_tpu.utils import bls as shim

import os

TRUSTED_SETUP = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "consensus_specs_tpu", "config", "trusted_setups",
    "trusted_setup_4096.json")


def test_generators_on_curve_and_order():
    g1, g2 = cv.g1_generator(), cv.g2_generator()
    assert g1.on_curve() and g2.on_curve()
    assert (g1 * R).is_infinity() and (g2 * R).is_infinity()


def test_trusted_setup_points_roundtrip():
    ts = json.load(open(TRUSTED_SETUP))
    for h in ts["g1_monomial"][:4] + ts["g1_lagrange"][:4]:
        b = bytes.fromhex(h[2:])
        assert cv.g1_to_bytes(cv.g1_from_bytes(b)) == b
    for h in ts["g2_monomial"][:2]:
        b = bytes.fromhex(h[2:])
        assert cv.g2_to_bytes(cv.g2_from_bytes(b)) == b


def test_pairing_bilinear_vs_trusted_setup():
    """e([tau]G1, G2) == e(G1, [tau]G2) can only hold with a correct pairing."""
    ts = json.load(open(TRUSTED_SETUP))
    tau_g1 = cv.g1_from_bytes(bytes.fromhex(ts["g1_monomial"][1][2:]))
    tau_g2 = cv.g2_from_bytes(bytes.fromhex(ts["g2_monomial"][1][2:]))
    assert native.pairing_check([(tau_g1, cv.g2_generator()),
                                 (-cv.g1_generator(), tau_g2)])


def test_pairing_bilinearity_scalars():
    g1, g2 = cv.g1_generator(), cv.g2_generator()
    assert pairing(g1 * 3, g2 * 5) == pairing(g1, g2).pow(15)


def test_miller_loop_pinned():
    """The Miller loop's raw value (before the final exponentiation),
    pinned to the Fq12-affine evaluation of every line."""
    import hashlib
    from consensus_specs_tpu.crypto.pairing import miller_loop
    f = miller_loop(cv.g1_generator() * 3, cv.g2_generator() * 5)
    coeffs = [c for f6 in (f.c0, f.c1) for f2 in (f6.c0, f6.c1, f6.c2)
              for c in (f2.c0, f2.c1)]
    digest = hashlib.sha256(
        b"".join(c.to_bytes(48, "big") for c in coeffs)).hexdigest()
    assert digest == ("2add0930334d7e3ebf37c8a8a34e0b75"
                      "82a9a2692bc25f0f1b8e83299b02957f")


def test_iso_map_constants():
    for i in range(3):
        x, y = sswu_map(Fq2(1000 + i, 2000 + 7 * i))
        assert iso_map(x, y).on_curve()


def test_hash_to_g2_subgroup():
    p = hash_to_g2(b"\x01\x02\x03")
    assert p.on_curve() and (p * R).is_infinity()
    assert hash_to_g2(b"\x01\x02\x03") == p
    assert hash_to_g2(b"\x01\x02\x04") != p


def _e2_point(seed: int) -> cv.Point:
    """A seeded point of E2, almost surely outside the G2 subgroup."""
    rng = random.Random(seed)
    while True:
        x = Fq2(rng.randrange(Q), rng.randrange(Q))
        y = (x.square() * x + cv.B2).sqrt()
        if y is not None:
            return cv.Point(x, y, Fq2.one(), cv.B2)


@pytest.mark.parametrize("name", [
    "identity", "generator", "outside-0", "outside-1", "jacobian", "mapped"])
def test_clear_cofactor_matches_h_eff(name):
    """The endomorphism form is [H_EFF]P on all of E2, not only on G2."""
    p = {
        "identity": cv.g2_infinity,
        "generator": cv.g2_generator,
        "outside-0": lambda: _e2_point(0),
        "outside-1": lambda: _e2_point(1),
        "jacobian": lambda: _e2_point(2) * 3,           # Z != 1
        "mapped": lambda: iso_map(*sswu_map(Fq2(11, 13))),
    }[name]()
    if name.startswith("outside"):
        assert p.on_curve() and not p.in_subgroup()
    q = clear_cofactor(p)
    assert q == p * H_EFF
    assert q.on_curve() and q.in_subgroup()


# hash_to_g2 outputs of the [H_EFF]P ladder, before the endomorphism form
@pytest.mark.parametrize("msg,expected", [
    (b"", "83b633b06dd88b63ee6180a849fb16f7d4a5823ec8a27294bfe57656c0f319a8"
          "21478ccf453bacdc94ad1b79d95a00e4102504549e1cbd3e95173eefe75a36aa"
          "fcc6427d7f16ddc36daba4fc0ea32b7183d052de00a929950bd9f78c290b3686"),
    (b"abc",
     "94b38e10fd6d2d63dfe704c3f0b1741474dfeaef88d6cdca4334413320701c74"
     "e5df8c7859947f6901c0a3c30dba23c91400ddb63494b2f3717d8706a834f928"
     "323cef590dd1f2bc8edaf857889e82c9b4cf242324526c9045bc8fec05f98fe9"),
    (b"\x00" * 32,
     "97502412bcfc3f1d88b71f1ad9b60fa37c332d19466fba1dc991d42bcd09bcd9"
     "f1c22a562646ffce0922793b6c69938b076e5cd6cfb3c361fc767e5f40ce0548"
     "6e1668825ffeecab89d7daa455a179736a387ae93b9b15d283d45ffa14cd4af7"),
    (bytes(range(100)),
     "acfec32a75c8104c1b7a16961541fd3203038fce6d00a2d5987180399d4accb4"
     "359043a24bd88ecb2350b5ccb1c047d40a2bc84bfb91197b84ffe1ba7d8c1748"
     "c200398faf9cc14d1b5cf24f1582924d707746e372aeae50fd22e5a24834204c"),
])
def test_hash_to_g2_pinned(msg, expected):
    assert cv.g2_to_bytes(hash_to_g2(msg)).hex() == expected


def _outside_subgroup_encoding(group: str) -> bytes:
    """The encoding of the on-curve point with the smallest x = (c, 0)
    that is not in the group's prime-order subgroup."""
    decode, encode, size = {
        "g1": (cv.g1_from_bytes, cv.g1_to_bytes, 48),
        "g2": (cv.g2_from_bytes, cv.g2_to_bytes, 96)}[group]
    for c in range(1, 100):
        enc = bytearray(c.to_bytes(size, "big"))
        enc[0] |= 0x80
        try:
            p = decode(bytes(enc), subgroup_check=False)
        except cv.DecodeError:
            continue
        if not p.in_subgroup():
            return encode(p)
    raise AssertionError("no out-of-subgroup x below 100")


@pytest.mark.parametrize("group", ["g1", "g2"])
def test_decode_memo_matches_fresh_decode(group):
    load, fresh, memo, enc = {
        "g1": (native._load_pubkey, cv.g1_from_bytes, native._PUBKEYS,
               native.SkToPk(77)),
        "g2": (native._load_signature, cv.g2_from_bytes, native._SIGNATURES,
               native.Sign(77, b"memo")),
    }[group]
    first = load(enc)
    assert enc in memo
    assert load(enc) is first
    assert first == fresh(enc)
    assert load(bytearray(enc)) is first        # keyed on the bytes


@pytest.mark.parametrize("group,kind", [
    ("g1", "malformed"), ("g1", "not-on-curve"), ("g1", "outside-subgroup"),
    ("g1", "infinity"),
    ("g2", "malformed"), ("g2", "not-on-curve"), ("g2", "outside-subgroup"),
])
def test_decode_memo_refuses_invalid_every_call(group, kind):
    """Only successes are memoized: an invalid encoding raises on the
    first and on every later call, and leaves nothing in the memo."""
    load, memo, size, off_curve = {
        "g1": (native._load_pubkey, native._PUBKEYS, 48,
               cv.not_on_curve_x_g1),
        "g2": (native._load_signature, native._SIGNATURES, 96,
               cv.not_on_curve_x_g2),
    }[group]
    enc = {
        "malformed": lambda: b"\xff" * size,      # infinity flag, x != 0
        "not-on-curve": off_curve,
        "outside-subgroup": lambda: _outside_subgroup_encoding(group),
        "infinity": lambda: bytes([0xC0]) + b"\x00" * (size - 1),
    }[kind]()
    error = ValueError if kind == "infinity" else cv.DecodeError
    for _ in range(2):
        with pytest.raises(error):
            load(enc)
        assert enc not in memo
        if group == "g1":
            assert not native.KeyValidate(enc)


def test_expand_message_xmd_shape():
    out = expand_message_xmd(b"abc", b"DST", 256)
    assert len(out) == 256
    assert out != expand_message_xmd(b"abd", b"DST", 256)


def test_sign_verify_roundtrip():
    sk = 12345
    pk = native.SkToPk(sk)
    msg = b"beacon block root"
    sig = native.Sign(sk, msg)
    assert len(pk) == 48 and len(sig) == 96
    assert native.Verify(pk, msg, sig)
    assert not native.Verify(pk, b"wrong message", sig)
    assert not native.Verify(native.SkToPk(54321), msg, sig)


def test_aggregate_verify():
    sks = [1, 2, 3]
    msg = b"same message"
    pks = [native.SkToPk(sk) for sk in sks]
    sigs = [native.Sign(sk, msg) for sk in sks]
    agg = native.Aggregate(sigs)
    assert native.FastAggregateVerify(pks, msg, agg)
    assert not native.FastAggregateVerify(pks[:2], msg, agg)
    # distinct messages
    msgs = [b"m1", b"m2"]
    sigs2 = [native.Sign(1, msgs[0]), native.Sign(2, msgs[1])]
    agg2 = native.Aggregate(sigs2)
    assert native.AggregateVerify(pks[:2], msgs, agg2)
    assert not native.AggregateVerify(pks[:2], msgs[::-1], agg2)


def test_aggregate_pks_matches_sum():
    pks = [native.SkToPk(sk) for sk in (5, 6)]
    agg = native.AggregatePKs(pks)
    assert agg == native.SkToPk(11)


def test_key_validate():
    assert native.KeyValidate(native.SkToPk(7))
    assert not native.KeyValidate(bytes([0xC0]) + b"\x00" * 47)  # infinity
    assert not native.KeyValidate(b"\xff" * 48)


def test_shim_stub_mode():
    previous = shim.bls_active
    shim.bls_active = False
    try:
        assert shim.Verify(b"x", b"y", b"z") is True
        assert shim.Sign(1, b"m") == shim.STUB_SIGNATURE
    finally:
        shim.bls_active = previous


def test_shim_live_mode():
    pk = shim.SkToPk(42)
    sig = shim.Sign(42, b"hello")
    assert shim.Verify(pk, b"hello", sig)
    assert not shim.Verify(pk, b"bye", sig)
    # malformed inputs -> False, not an exception
    assert not shim.Verify(b"\x00" * 48, b"m", b"\x00" * 96)


def test_hard_part_chain_exponent():
    """Symbolic verification of the x-chain hard part: mirror _hard_part's
    step sequence on integer exponents of a unitary element (order divides
    phi = q^4 - q^2 + 1, where conjugate = negate, frobenius = *q,
    exp-by-x = *x) and check the result is EXACTLY 3*(q^4-q^2+1)/r."""
    from consensus_specs_tpu.crypto.fields import BLS_X

    x = BLS_X
    t2 = 1
    t1 = 2 * t2 * -1            # cyclotomic_square + conjugate
    t3 = t2 * x
    t4 = 2 * t3
    t5 = t1 + t3
    t1 = t5 * x
    t0 = t1 * x
    t6 = t0 * x
    t6 = t6 + t4
    t4 = t6 * x
    t5 = -t5
    t4 = t4 + t5 + t2
    t5 = -t2
    t1 = t1 + t2
    t1 = t1 * Q**3
    t6 = t6 + t5
    t6 = t6 * Q
    t3 = t3 + t0
    t3 = t3 * Q**2
    t3 = t3 + t1
    t3 = t3 + t6
    result = t3 + t4

    from consensus_specs_tpu.crypto.pairing import _HARD_EXP
    assert (Q**4 - Q**2 + 1) % R == 0
    assert _HARD_EXP == (Q**4 - Q**2 + 1) // R
    assert result == 3 * _HARD_EXP
