"""Batched commitment evaluation (DKG deal verification) and the
scan-MSM — device paths vs the host oracle.

Reference: kyber vss deal verification (g·s_i == Σ_k C_k·x^k), the
BASELINE "n=128 deal verify" config; engine.eval_commits is the device
call the DKG's _process_deals batches into.
"""

import random

import numpy as np
import pytest

pytestmark = pytest.mark.device

from drand_tpu.crypto import batch
from drand_tpu.crypto.curves import PointG1, PointG2
from drand_tpu.crypto.fields import R
from drand_tpu.crypto.poly import PriPoly, PubPoly


@pytest.fixture
def engine(engine4):
    return engine4


def test_eval_commits_matches_host(engine):
    rnd = random.Random(7)
    g = PointG1.generator()
    t, n = 5, 40
    polys = [PubPoly([g.mul(rnd.randrange(1, 2 ** 64)) for _ in range(t)])
             for _ in range(n)]
    idx = 11
    got = engine.eval_commits(polys, idx)
    exp = [p.eval(idx).value for p in polys]
    assert got == exp


def test_eval_commits_share_check_roundtrip(engine):
    # the actual DKG use: dealer polys, our decrypted share, g·s == eval
    t, n, my_index = 4, 9, 2
    pris = [PriPoly.random(t, seed=b"ec-%d" % d) for d in range(n)]
    pubs = [p.commit() for p in pris]
    shares = [p.eval(my_index).value for p in pris]
    evals = engine.eval_commits(pubs, my_index)
    g = PointG1.generator()
    assert all(g.mul(s) == e for s, e in zip(shares, evals))
    # a corrupted share must not check out
    assert g.mul((shares[0] + 1) % R) != evals[0]


def test_eval_commits_via_batch_dispatch():
    prev = batch._MODE, batch._MIN_BATCH
    try:
        batch.configure("device", min_batch=1)
        g = PointG1.generator()
        polys = [PubPoly([g.mul(3 + d + k) for k in range(3)])
                 for d in range(6)]
        got = batch.eval_commits(polys, 1)
        assert got == [p.eval(1).value for p in polys]
    finally:
        batch.configure(prev[0], min_batch=prev[1])


def test_msm_scan_and_lanes_match_unrolled():
    import jax.numpy as jnp

    from drand_tpu.ops import curve, limb
    from drand_tpu.ops.engine import _g2_aff
    from drand_tpu.crypto.fields import Fp2

    rnd = random.Random(3)
    n = 5
    pts_h = [PointG2.generator().mul(rnd.randrange(1, R)) for _ in range(n)]
    scals = [rnd.randrange(R) for _ in range(n)]
    exp = None
    for p, s in zip(pts_h, scals):
        q = p.mul(s)
        exp = q if exp is None else exp + q
    pts_np = np.stack([_g2_aff(p) for p in pts_h])
    z_one = np.zeros((n, 2, limb.NLIMBS), np.int32)
    z_one[:, 0] = np.asarray(limb.ONE_MONT)
    bits = np.stack([curve.scalar_to_bits(s, 255) for s in scals])
    pts = (jnp.asarray(pts_np[:, 0]), jnp.asarray(pts_np[:, 1]),
           jnp.asarray(z_one), jnp.asarray(np.zeros(n, bool)))
    ax, ay, is_inf = curve.pt_to_affine(
        curve.F2, curve.msm_scan(curve.F2, pts, jnp.asarray(bits)))
    got = PointG2(
        Fp2(limb.fp_from_device(np.asarray(ax)[0]),
            limb.fp_from_device(np.asarray(ax)[1])),
        Fp2(limb.fp_from_device(np.asarray(ay)[0]),
            limb.fp_from_device(np.asarray(ay)[1])),
        Fp2.one())
    assert not bool(np.asarray(is_inf))
    assert got == exp


def test_eval_poly_indices_matches_host(engine):
    from drand_tpu.crypto.poly import PriPoly

    poly = PriPoly.random(6, seed=b"epi").commit()
    idxs = [0, 2, 9, 33, 5]
    got = engine.eval_poly_indices(poly, idxs)
    assert got == [poly.eval(i).value for i in idxs]


def test_msm_lanes_matches_host():
    import jax.numpy as jnp

    from drand_tpu.ops import curve, limb
    from drand_tpu.ops.engine import _g2_aff
    from drand_tpu.crypto.fields import Fp2

    rnd = random.Random(5)
    n = 8  # power of two incl. masked (infinity) pad lanes
    pts_h = [PointG2.generator().mul(rnd.randrange(1, R)) for _ in range(6)]
    scals = [rnd.randrange(R) for _ in range(6)]
    exp = None
    for p, s in zip(pts_h, scals):
        q = p.mul(s)
        exp = q if exp is None else exp + q
    pts_np = np.stack([_g2_aff(p) for p in pts_h] +
                      [_g2_aff(PointG2.generator())] * 2)
    z_one = np.zeros((n, 2, limb.NLIMBS), np.int32)
    z_one[:, 0] = np.asarray(limb.ONE_MONT)
    inf = np.zeros(n, bool)
    inf[6:] = True  # pad lanes masked out
    bits = np.stack([curve.scalar_to_bits(s, 255) for s in scals] +
                    [np.zeros(255, np.int32)] * 2)
    pts = (jnp.asarray(pts_np[:, 0]), jnp.asarray(pts_np[:, 1]),
           jnp.asarray(z_one), jnp.asarray(inf))
    ax, ay, is_inf = curve.pt_to_affine(
        curve.F2, curve.msm_lanes(curve.F2, pts, jnp.asarray(bits)))
    got = PointG2(
        Fp2(limb.fp_from_device(np.asarray(ax)[0]),
            limb.fp_from_device(np.asarray(ax)[1])),
        Fp2(limb.fp_from_device(np.asarray(ay)[0]),
            limb.fp_from_device(np.asarray(ay)[1])),
        Fp2.one())
    assert not bool(np.asarray(is_inf))
    assert got == exp


def test_horner_bl_matches_host():
    """The batch-last Horner body behind the Pallas deal-verify kernel
    (ops/pallas_eval.horner_bl), run on the XLA path: Jacobian output
    converted on host must equal every dealer's PubPoly.eval."""
    import jax.numpy as jnp

    from drand_tpu.ops import bl_curve, curve as xcurve, limb, pallas_eval
    from drand_tpu.ops.engine import BatchedEngine, _g1_xy
    from drand_tpu.ops.pallas_pairing import value_bit_getter

    t, b, index = 3, 4, 6
    g = PointG1.generator()
    polys = [PubPoly([g.mul(97 * d + 13 * k + 1) for k in range(t)])
             for d in range(b)]
    xs = np.zeros((t, limb.NLIMBS, b), np.int32)
    ys = np.zeros((t, limb.NLIMBS, b), np.int32)
    flat = PointG1.batch_to_affine([c for p in polys for c in p.commits])
    for d in range(b):
        for k in range(t):
            aff = _g1_xy(flat[d * t + k])
            xs[k, :, d], ys[k, :, d] = aff[0], aff[1]
    bits = xcurve.scalar_to_bits(index + 1, pallas_eval.NBITS)
    F = bl_curve.make_f1()
    import jax

    xs_j, ys_j = jnp.asarray(xs), jnp.asarray(ys)

    def get_commit(k):  # k is traced inside fori_loop on the XLA path
        return (jax.lax.dynamic_index_in_dim(xs_j, k, 0, keepdims=False),
                jax.lax.dynamic_index_in_dim(ys_j, k, 0, keepdims=False))

    X, Y, Z, inf32 = pallas_eval.horner_bl(
        F, get_commit, value_bit_getter(jnp.asarray(bits)[None, :]), t, b)
    # batch-last -> batch-leading rows, then the engine's host unpack
    rows = np.concatenate(
        [np.asarray(X).T, np.asarray(Y).T, np.asarray(Z).T,
         np.asarray(inf32)[:, None]], axis=1)
    got = BatchedEngine._unpack_eval_host(rows, 3, b)
    exp = [p.eval(index).value for p in polys]
    assert got == exp


def test_unpack_eval_jacobian_infinity_row():
    """Jacobian host unpack: z=0 / inf-flagged rows come back as the
    point at infinity; finite rows convert exactly."""
    from drand_tpu.ops import limb
    from drand_tpu.ops.engine import BatchedEngine
    from drand_tpu.crypto.fields import P as _P

    g = PointG1.generator()
    x, y = g.to_affine()
    z = 12345
    # jacobian (X, Y, Z) = (x z^2, y z^3, z)
    X = limb.int_to_mont_limbs(x.v * z * z % _P)
    Y = limb.int_to_mont_limbs(y.v * z * z % _P * z % _P)
    Z = limb.int_to_mont_limbs(z)
    zero = np.zeros(limb.NLIMBS, np.int32)
    rows = np.stack([
        np.concatenate([X, Y, Z, [0]]),
        np.concatenate([zero, zero, zero, [1]]),
    ]).astype(np.int32)
    got = BatchedEngine._unpack_eval_host(rows, 3, 2)
    assert got[0] == g
    assert got[1].is_infinity()


def test_msm_fold_bl_matches_host():
    """The batch-last ladder + lane-roll log-fold + to-affine behind the
    Pallas recovery MSM (ops/pallas_msm), on the XLA path: lane 0 must
    equal the host Σ s_i·P_i, with padding lanes masked as infinity."""
    import jax
    import jax.numpy as jnp

    from drand_tpu.crypto.fields import Fp2
    from drand_tpu.ops import bl_curve, curve as xcurve, limb, pallas_msm
    from drand_tpu.ops.engine import _g2_aff

    rnd = random.Random(3)
    b, nbits = 8, 48
    pts = [PointG2.generator().mul(rnd.randrange(1, 1 << 40))
           for _ in range(b - 3)]
    scalars = [rnd.randrange(1, 1 << nbits) for _ in pts]
    arr = np.zeros((b, 2, 2, limb.NLIMBS), np.int32)
    inf = np.ones(b, bool)
    bits = np.zeros((b, nbits), np.int32)
    for i, (p, s) in enumerate(zip(pts, scalars)):
        arr[i] = _g2_aff(p)
        inf[i] = False
        bits[i] = xcurve.scalar_to_bits(s, nbits)
    F = bl_curve.F2
    xq = jnp.moveaxis(jnp.asarray(arr[:, 0]), 0, -1)   # (2, 32, b)
    yq = jnp.moveaxis(jnp.asarray(arr[:, 1]), 0, -1)
    bits_bl = jnp.asarray(bits.T)                      # (nbits, b)

    def bit_getter(i):
        return jax.lax.dynamic_slice_in_dim(bits_bl, i, 1, 0)[0]

    acc = bl_curve.pt_mul_bits_getter(
        F, (xq, yq, F.one((b,)), jnp.asarray(inf)), bit_getter, nbits)
    ax, ay, ainf = xcurve.pt_to_affine(
        F, pallas_msm.msm_fold_bl(F, acc, b))
    ax, ay = np.asarray(ax)[..., 0], np.asarray(ay)[..., 0]
    assert not bool(np.asarray(ainf)[0])
    got = PointG2(
        Fp2(limb.fp_from_device(ax[0]), limb.fp_from_device(ax[1])),
        Fp2(limb.fp_from_device(ay[0]), limb.fp_from_device(ay[1])),
        Fp2.one())
    exp = PointG2.infinity()
    for p, s in zip(pts, scalars):
        exp = exp + p.mul(s)
    assert got == exp


# The two tests that run the engine's pairing graph go last: by then a
# worker running the engine suite has usually written that graph to the
# persistent compile cache, and this group reads it instead of
# compiling it again.
def test_verify_bls_async_chunking(engine):
    """Batches beyond the largest bucket dispatch as multiple async
    launches and drain once — results must match per-row truth."""
    from drand_tpu.crypto import bls
    from drand_tpu.crypto.hash_to_curve import hash_to_g2

    sk = 0xBEE
    pub = PointG1.generator().mul(sk)
    triples = []
    want = []
    for i in range(11):
        m = b"chunk-%d" % i
        sig = PointG2.from_bytes(bls.sign(sk, m), subgroup_check=False)
        if i % 3 == 2:  # wrong message for this signature
            triples.append((pub, sig, hash_to_g2(b"other")))
            want.append(False)
        else:
            triples.append((pub, sig, hash_to_g2(m)))
            want.append(True)
    out = engine.verify_bls(triples)  # 11 rows: three bucket-4 launches
    assert list(out) == want


def test_verify_partials_uses_batched_evals(engine):
    from drand_tpu.crypto import tbls
    from drand_tpu.crypto.poly import PriPoly

    pri = PriPoly.random(3, seed=b"vp")
    pub = pri.commit()
    msg = b"round-msg"
    partials = [tbls.sign_partial(s, msg) for s in pri.shares(7)]
    oks = engine.verify_partials(pub, msg, partials)
    assert oks == [True] * 7
    bad = bytearray(partials[2])
    bad[-1] ^= 1
    oks = engine.verify_partials(pub, msg, [bytes(bad)] + partials[:2])
    assert oks == [False, True, True]
