"""Golden tests: batch-last Pallas pairing path (ops/pallas_pairing.py)
vs the proven XLA device pairing (ops/pairing.py) and the host truth.

The pure-jnp math functions are validated here on CPU (they are the same
code the Pallas kernels trace); the Mosaic-compiled kernels themselves
are known-answer-validated on the TPU by the engine before use.
"""

import random

import numpy as np
import jax.numpy as jnp
import pytest

# slow: the batch-last Miller loop, final exponentiation and verify
# graphs compile for 489 s cold under -n 6
pytestmark = [pytest.mark.device, pytest.mark.slow]

from drand_tpu.crypto import pairing as hp
from drand_tpu.crypto.curves import PointG1, PointG2
from drand_tpu.ops import bl, limb, pairing as xp_pair, tower
from drand_tpu.ops import pallas_pairing as pp

B = 2
rng = random.Random(0x9A1A)


def rand_pairs(n=B):
    """n verification-shaped inputs: pairs ((-g1, sig), (pub, msg))."""
    out = []
    for _ in range(n):
        sk = rng.randrange(1, 1 << 64)
        pub = PointG1.generator().mul(sk)
        msg = PointG2.generator().mul(rng.randrange(1, 1 << 64))
        sig = msg.mul(sk)
        out.append((pub, sig, msg))
    return out


def pack_batch_leading(triples):
    pubs = np.stack([np.asarray(xp_pair.g1_affine_to_device(p))
                     for p, _, _ in triples])
    sigs = np.stack([np.asarray(xp_pair.g2_affine_to_device(s))
                     for _, s, _ in triples])
    msgs = np.stack([np.asarray(xp_pair.g2_affine_to_device(m))
                     for _, _, m in triples])
    return pubs, sigs, msgs


def fp12_list_from_bl(f):
    """(2, 3, 2, 32, B) -> list of host Fp12 (via the limb-last codec)."""
    g = np.moveaxis(np.asarray(f), -1, 0)  # (B, 2, 3, 2, 32)
    return [tower.fp12_from_device(g[i]) for i in range(g.shape[0])]


def test_miller_loop_matches_xla_device_path():
    triples = rand_pairs()
    pubs, sigs, msgs = pack_batch_leading(triples)
    # XLA (batch-leading) reference
    neg_g1 = np.broadcast_to(pp._neg_g1_np(), pubs.shape)
    xp_coords = jnp.stack([jnp.asarray(neg_g1[:, 0]),
                           jnp.asarray(pubs[:, 0])], axis=-2)
    yp_coords = jnp.stack([jnp.asarray(neg_g1[:, 1]),
                           jnp.asarray(pubs[:, 1])], axis=-2)
    q = jnp.stack([jnp.asarray(sigs), jnp.asarray(msgs)], axis=-4)
    f_ref = xp_pair.miller_loop((xp_coords, yp_coords), q)
    ref = [tower.fp12_from_device(np.asarray(f_ref)[i]) for i in range(B)]
    # batch-last
    xpl, ypl, ql = pp.pack_verify_inputs(pubs, sigs, msgs)
    f_bl = pp.miller_loop_bl(
        xpl, ypl, ql, pp.value_bit_getter(jnp.asarray(pp.MILLER_FLAGS)))
    got = fp12_list_from_bl(f_bl)
    assert got == ref


def test_final_exp_and_verify_match_host():
    triples = rand_pairs()
    pubs, sigs, msgs = pack_batch_leading(triples)
    xpl, ypl, ql = pp.pack_verify_inputs(pubs, sigs, msgs)
    f = pp.miller_loop_bl(
        xpl, ypl, ql, pp.value_bit_getter(jnp.asarray(pp.MILLER_FLAGS)))
    out = pp.final_exp_bl(f)
    got = fp12_list_from_bl(out)
    # valid signatures: the (cubed) pairing product is exactly one
    for g in got:
        assert g == g.one(), "valid verification must hit the identity"
    # full entry point, pure-jnp path
    ok = pp.verify_prepared_pl(pubs, sigs, msgs, use_pallas=False)
    assert np.asarray(ok).tolist() == [True] * B


def test_verify_rejects_wrong_signature():
    triples = rand_pairs()
    pubs, sigs, msgs = pack_batch_leading(triples)
    # corrupt row 1: swap in an unrelated signature
    bad = PointG2.generator().mul(0xDEAD)
    sigs[1] = np.asarray(xp_pair.g2_affine_to_device(bad))
    ok = pp.verify_prepared_pl(pubs, sigs, msgs, use_pallas=False)
    assert np.asarray(ok).tolist() == [True, False]


def test_final_exp_nontrivial_matches_host_codec():
    """Final exp of a NON-verifying product must equal the host's (cubed,
    non-canonical) final exponentiation — full GT value, not just ==1.
    Runs at the suite-wide batch B so the miller/final-exp graphs
    compiled by the earlier tests are REUSED (a B=1 shape here used to
    recompile the whole chain — half the suite's wall time)."""
    triples = rand_pairs()  # batch B, same compiled shapes as above
    pubs, sigs, msgs = pack_batch_leading(triples)
    # mismatched message in row 0: a nontrivial GT element there
    p1, s1, _ = triples[0]
    other = PointG2.generator().mul(0xBEEF)
    msgs[0] = np.asarray(xp_pair.g2_affine_to_device(other))
    host = hp.multi_pairing(
        [(-PointG1.generator(), s1), (p1, other)], canonical=False)
    xpl, ypl, ql = pp.pack_verify_inputs(pubs, sigs, msgs)
    f = pp.miller_loop_bl(
        xpl, ypl, ql, pp.value_bit_getter(jnp.asarray(pp.MILLER_FLAGS)))
    got = fp12_list_from_bl(pp.final_exp_bl(f))[0]
    assert got == host
