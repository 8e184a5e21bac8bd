"""The plain reference: pairing laws, signatures, the fixture."""

from __future__ import annotations

import hashlib
import json
import os
import random
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import fixture  # noqa: E402
from reference import bls12_381 as bls  # noqa: E402  (pins RFC 9380 J.10.1)
from reference import drand  # noqa: E402


def test_pairing_is_bilinear_and_nondegenerate():
    g1, g2 = bls.G1.generator(), bls.G2.generator()
    e = bls.pairing(g1, g2)
    assert not e.is_one()
    assert bls.pairing(g1.mul(6), g2) == bls.pairing(g1.mul(2), g2.mul(3))
    assert e.pow(bls.R).is_one()


def test_signature_and_encoding():
    sk = 0xC0FFEE
    pub = bls.G1.generator().mul(sk)
    sig = bls.sign(sk, b"round 7")
    assert bls.verify(pub, b"round 7", sig)
    assert not bls.verify(pub, b"round 8", sig)
    assert not bls.verify(pub, b"round 7", bytes(96))
    assert bls.G2.from_bytes(sig).to_bytes() == sig
    assert bls.G1.from_bytes(pub.to_bytes()) == pub


def test_fixture_is_pinned_and_valid():
    with open(os.path.join(BENCH, "configs", "loe-mainnet.json")) as f:
        cfg = json.load(f)
    ch = fixture.load_chain(cfg, os.path.dirname(BENCH))
    assert ch["rounds"] == cfg["history_rounds"]
    sk = int.from_bytes(hashlib.sha512(cfg["fixture"]["key_seed"].encode())
                        .digest(), "big") % (bls.R - 1) + 1
    pub = bls.G1.generator().mul(sk)
    assert pub.to_bytes() == ch["group_key"]
    for r in random.Random(1).sample(range(1, ch["rounds"] + 1), 2):
        assert drand.round_valid(pub, r, fixture.previous(ch, r),
                                 ch["v1"][r], ch["v2"][r])
