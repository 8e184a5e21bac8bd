"""Device hash-to-G2 / decompression golden tests vs the host reference,
and the engine's wire-prep verification path.

Pins the ops/h2c.py pipeline bit-for-bit against crypto/hash_to_curve and
PointG2.from_bytes (the RFC 9380 + zcash semantics), plus the end-to-end
DRAND_TPU_WIRE_PREP engine path with corruption cases.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.device

import jax
import jax.numpy as jnp


from drand_tpu.chain.beacon import Beacon, message, message_v2
from drand_tpu.crypto import bls
from drand_tpu.crypto.curves import PointG1, PointG2
from drand_tpu.crypto.hash_to_curve import hash_to_g2
from drand_tpu.ops import curve, h2c


def test_hash_to_g2_device_matches_host():
    msgs = [b"suite-h2c-a", b"suite-h2c-b"]
    u = jnp.asarray(h2c.msgs_to_u(msgs))
    pt = jax.jit(h2c.hash_to_g2_device)(u)
    for i, m in enumerate(msgs):
        dev = curve.g2_from_device(tuple(np.asarray(c[i]) for c in pt))
        assert dev == hash_to_g2(m), f"hash_to_g2 mismatch for {m!r}"


def test_decompress_device_matches_host_and_rejects_off_curve():
    sigs = [bls.sign(0x1234, b"sig-a"), bls.sign(0x5678, b"sig-b")]
    # tweak x until it is REALLY off the curve (a random x is on the curve
    # with probability ~1/2 — the host decoder is the arbiter)
    bad = bytearray(sigs[1])
    while True:
        bad[7] = (bad[7] + 1) % 256
        try:
            PointG2.from_bytes(bytes(bad), subgroup_check=False)
        except ValueError:
            break
    xs, sign, valid = h2c.sigs_to_x([sigs[0], bytes(bad)])
    assert valid.tolist() == [True, True]  # header/range fine; curve check
    pt, on_curve = jax.jit(h2c.decompress_g2_device)(jnp.asarray(xs),
                                                     jnp.asarray(sign))
    on_curve = np.asarray(on_curve)
    assert bool(on_curve[0]) and not bool(on_curve[1]), \
        "decompression on-curve flags wrong"
    dev = curve.g2_from_device(tuple(np.asarray(c[0]) for c in pt))
    assert dev == PointG2.from_bytes(sigs[0]), "decompressed point"
    assert bool(np.asarray(jax.jit(h2c.subgroup_check_g2)(pt))[0]), \
        "subgroup check"


def test_sigs_to_x_rejects_malformed_headers():
    good = bls.sign(0x42, b"x")
    no_compress_bit = bytes([good[0] & 0x7F]) + good[1:]
    infinity_bit = bytes([good[0] | 0x40]) + good[1:]
    short = good[:50]
    _, _, valid = h2c.sigs_to_x([good, no_compress_bit, infinity_bit, short])
    assert valid.tolist() == [True, False, False, False]


@pytest.mark.asyncio
async def test_engine_wire_prep_end_to_end(wire_engine4):
    """verify_beacons with wire_prep=True: valid chain passes; V1 and V2
    corruption each fail exactly the corrupted beacon."""
    sk = 0x77AA
    pubkey = PointG1.generator().mul(sk)
    prev = b"\x21" * 32
    beacons = []
    for rnd in range(1, 4):
        sig = bls.sign(sk, message(rnd, prev))
        sig2 = bls.sign(sk, message_v2(rnd))
        beacons.append(Beacon(round=rnd, previous_sig=prev, signature=sig,
                              signature_v2=sig2))
        prev = sig
    eng = wire_engine4  # 6 checks: two bucket-4 launches
    assert eng.verify_beacons(pubkey, beacons).all()
    import copy

    bad = copy.deepcopy(beacons)
    bad[1].signature = bytes([bad[1].signature[0] ^ 1]) + bad[1].signature[1:]
    assert list(eng.verify_beacons(pubkey, bad)) == [True, False, True]
    bad2 = copy.deepcopy(beacons)
    bad2[2].signature_v2 = bad2[0].signature_v2
    assert list(eng.verify_beacons(pubkey, bad2)) == [True, True, False]
