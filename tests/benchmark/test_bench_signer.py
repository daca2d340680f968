"""The host signer that signs the benchmark's traffic, and the point
decoders it shares with the verifier under test, against the known
answers of the consensus-specs BLS suite (`benchmark/reference/
bls_vectors.json`).  The verdicts the traffic is built to have rest on
them; `build.check_signer` repeats the signing half in every run's
set-up."""
import json

import pytest

from benchmark.traffic import build
from consensus_specs_tpu.crypto import bls12_381 as native_bls
from consensus_specs_tpu.crypto import curve as cv

with open(build.VECTORS) as f:
    VECTORS = json.load(f)


@pytest.mark.parametrize("case", VECTORS["sign"],
                         ids=lambda c: c["privkey"][:6] + c["message"][:2])
def test_sign_known_answer(case):
    sk, msg = int(case["privkey"], 16), bytes.fromhex(case["message"])
    assert build.sign(sk, msg).hex() == case["signature"]


@pytest.mark.parametrize("sk", sorted(VECTORS["pubkeys"]),
                         ids=lambda k: k[:6])
def test_pubkey_known_answer(sk):
    pk = bytes.fromhex(VECTORS["pubkeys"][sk])
    assert bytes(native_bls.SkToPk(int(sk, 16))) == pk
    # the verifier's decoder reads the known encoding back to the key
    assert cv.g1_to_bytes(native_bls._load_pubkey(pk)) == pk


@pytest.mark.parametrize("case", VECTORS["sign"][:3],
                         ids=lambda c: c["message"][:2])
def test_verifier_decodes_known_signatures(case):
    sig = bytes.fromhex(case["signature"])
    pk = bytes.fromhex(VECTORS["pubkeys"][case["privkey"]])
    assert cv.g2_to_bytes(native_bls._load_signature(sig)) == sig
    assert native_bls.Verify(pk, bytes.fromhex(case["message"]), sig)
    assert not native_bls.Verify(pk, b"\x01" * 32, sig)


def test_check_signer_passes():
    build.check_signer()
