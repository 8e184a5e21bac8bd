"""Golden tests: the device engine (drand_tpu.ops) against the host
reference (drand_tpu.crypto).

Covers the early review items: ops/ had zero tests; the optimization_barrier
miscompile regression (jit == eager for the tower); the pairing path that
had never completed a run. Runs on the CPU backend (conftest forces
JAX_PLATFORMS=cpu with a persistent compile cache).

Reference parity: the host crypto is itself golden-tested against RFC 9380
vectors and kyber wire formats (tests/test_crypto_core.py), mirroring the
reference's crypto usage sites (/root/reference/key/curve.go:19-38,
/root/reference/chain/beacon/chain.go:136-166).
"""

import random

import numpy as np
import pytest

pytestmark = pytest.mark.device

import jax
import jax.numpy as jnp

from drand_tpu.crypto.fields import P, Fp2, Fp6, Fp12, XI
from drand_tpu.crypto import curves as hc
from drand_tpu.crypto import pairing as hp
from drand_tpu.crypto.hash_to_curve import hash_to_g2
from drand_tpu.ops import limb, tower, curve, pairing as dpair


rnd = random.Random(0xD5A)


def rfp() -> int:
    return rnd.randrange(P)


def rf2() -> Fp2:
    return Fp2(rfp(), rfp())


def rf12() -> Fp12:
    return Fp12(Fp6(rf2(), rf2(), rf2()), Fp6(rf2(), rf2(), rf2()))


# ---------------------------------------------------------------------------
# Limb layer
# ---------------------------------------------------------------------------

class TestLimb:
    def test_roundtrip(self):
        for _ in range(10):
            x = rfp()
            assert limb.fp_from_device(limb.fp_to_device(x)) == x

    def test_mont_mul_golden_jit_vs_eager(self):
        """The optimization_barrier regression guard: jit and eager must
        agree with the host product (ops/limb.py mont_mul docstring)."""
        mulj = jax.jit(limb.mont_mul)
        for i in range(12):
            a, b = rfp(), rfp()
            ad, bd = limb.fp_to_device(a), limb.fp_to_device(b)
            exp = a * b % P
            assert limb.fp_from_device(mulj(ad, bd)) == exp
            if i < 1:  # eager path once is enough (dispatch-slow)
                assert limb.fp_from_device(limb.mont_mul(ad, bd)) == exp

    def test_add_sub_fuzz_including_high_values(self):
        """reduce_light truncation-edge regression: biased-high limbs near
        2^384 exercise the second wrap pass (a real 0.4% bug when absent)."""
        n = 4096
        rng = np.random.default_rng(11)
        A = rng.integers(0, 4098, size=(n, 32), dtype=np.int32)
        B = rng.integers(0, 4098, size=(n, 32), dtype=np.int32)
        A[: n // 2, -8:] = 4096
        B[: n // 2, -8:] = 4096
        out_add = np.asarray(jax.jit(limb.add)(A, B))
        out_sub = np.asarray(jax.jit(limb.sub)(A, B))
        for i in range(n):
            va, vb = limb.limbs_to_int(A[i]), limb.limbs_to_int(B[i])
            assert limb.limbs_to_int(out_add[i]) % P == (va + vb) % P
            assert limb.limbs_to_int(out_sub[i]) % P == (va - vb) % P
            assert out_add[i].max() <= 4200 and out_sub[i].max() <= 4200

    def test_adversarial_reduce(self):
        for pattern in (4096, 4097, 4112, 8194):
            t = jnp.full((32,), pattern, jnp.int32)
            out = limb.reduce_limbs(t)
            assert limb.limbs_to_int(np.asarray(out)) % P == \
                limb.limbs_to_int(np.asarray(t)) % P
            t2 = jnp.full((32,), min(pattern, 8190), jnp.int32)
            out2 = limb.reduce_light(t2)
            assert limb.limbs_to_int(np.asarray(out2)) % P == \
                limb.limbs_to_int(np.asarray(t2)) % P

    def test_inv(self):
        for _ in range(3):
            x = rfp()
            got = limb.fp_from_device(jax.jit(limb.inv)(limb.fp_to_device(x)))
            assert got == pow(x, P - 2, P)

    def test_is_zero_mod_p(self):
        assert bool(limb.is_zero_mod_p(limb.fp_to_device(0)))
        assert bool(limb.is_zero_mod_p(jnp.asarray(limb.int_to_limbs(P))))
        assert not bool(limb.is_zero_mod_p(limb.fp_to_device(1)))


# ---------------------------------------------------------------------------
# Tower layer
# ---------------------------------------------------------------------------

class TestTower:
    def test_f2_ops(self):
        mulj = jax.jit(tower.f2_mul)
        addj = jax.jit(tower.f2_add)
        subj = jax.jit(tower.f2_sub)
        sqrj = jax.jit(tower.f2_sqr)
        xij = jax.jit(tower.f2_mul_by_xi)
        for _ in range(8):
            x, y = rf2(), rf2()
            xd, yd = tower.fp2_to_device(x), tower.fp2_to_device(y)
            assert tower.fp2_from_device(mulj(xd, yd)) == x * y
            assert tower.fp2_from_device(addj(xd, yd)) == x + y
            assert tower.fp2_from_device(subj(xd, yd)) == x - y
            assert tower.fp2_from_device(sqrj(xd)) == x * x
            assert tower.fp2_from_device(xij(xd)) == x * XI

    def test_f2_inv(self):
        x = rf2()
        xd = tower.fp2_to_device(x)
        assert tower.fp2_from_device(jax.jit(tower.f2_inv)(xd)) == x.inverse()

    def test_f12_mul_jit_vs_eager_barrier_regression(self):
        """jit(f12_mul) != eager f12_mul was the observed XLA miscompile the
        optimization_barrier in mont_mul guards against."""
        x, y = rf12(), rf12()
        xd, yd = tower.fp12_to_device(x), tower.fp12_to_device(y)
        eager = tower.fp12_from_device(tower.f12_mul(xd, yd))
        jitted = tower.fp12_from_device(jax.jit(tower.f12_mul)(xd, yd))
        assert eager == x * y
        assert jitted == x * y

    def test_f12_ops(self):
        x = rf12()
        xd = tower.fp12_to_device(x)
        assert tower.fp12_from_device(jax.jit(tower.f12_sqr)(xd)) == x * x
        assert tower.fp12_from_device(jax.jit(tower.f12_conj)(xd)) == \
            x.conjugate()
        for power in (1, 2, 3):
            frob = jax.jit(tower.f12_frobenius, static_argnums=1)
            assert tower.fp12_from_device(frob(xd, power)) == x.frobenius(power)

    def test_f12_inv(self):
        x = rf12()
        xd = tower.fp12_to_device(x)
        assert tower.fp12_from_device(jax.jit(tower.f12_inv)(xd)) == x.inverse()

    def test_cyclotomic_square(self):
        # project a random element into the cyclotomic subgroup first
        x = rf12()
        c = hp.final_exponentiation(x, canonical=False)
        cd = tower.fp12_to_device(c)
        assert tower.fp12_from_device(tower.f12_cyclotomic_sqr(cd)) == \
            c.cyclotomic_square()

    def test_batched_broadcasting(self):
        xs = [rf2() for _ in range(4)]
        ys = [rf2() for _ in range(4)]
        xd = jnp.stack([tower.fp2_to_device(x) for x in xs])
        yd = jnp.stack([tower.fp2_to_device(y) for y in ys])
        out = jax.jit(tower.f2_mul)(xd, yd)
        for i in range(4):
            assert tower.fp2_from_device(out[i]) == xs[i] * ys[i]


# ---------------------------------------------------------------------------
# Curve layer
# ---------------------------------------------------------------------------

class TestCurve:
    def test_g1_add_dbl_mul(self):
        g = hc.PointG1.generator()
        a, b = g.mul(7), g.mul(11)
        ad, bd = curve.g1_to_device(a), curve.g1_to_device(b)
        addj = jax.jit(lambda p, q: curve.pt_add(curve.F1, p, q))
        dblj = jax.jit(lambda p: curve.pt_dbl(curve.F1, p))
        assert curve.g1_from_device(addj(ad, bd)) == a + b
        assert curve.g1_from_device(dblj(ad)) == a.double()
        # exceptional cases
        assert curve.g1_from_device(addj(ad, ad)) == a.double()
        nd = curve.g1_to_device(-a)
        assert curve.g1_from_device(addj(ad, nd)).is_infinity()
        infd = curve.g1_to_device(hc.PointG1.infinity())
        assert curve.g1_from_device(addj(ad, infd)) == a

    def test_g2_add_mul_scan(self):
        g = hc.PointG2.generator()
        a = g.mul(5)
        ad = curve.g2_to_device(a)
        k = 0x1234567
        bits = jnp.asarray(curve.scalar_to_bits(k, 32))
        got = curve.g2_from_device(
            jax.jit(lambda p, b: curve.pt_mul_bits(curve.F2, p, b))(ad, bits))
        assert got == a.mul(k)

    def test_msm_matches_host(self):
        g = hc.PointG1.generator()
        pts = [g.mul(i + 3) for i in range(4)]
        scalars = [rnd.randrange(1 << 64) for _ in range(4)]
        ptd = curve.stack_points([curve.g1_to_device(p) for p in pts])
        bits = jnp.stack([jnp.asarray(curve.scalar_to_bits(s, 64))
                          for s in scalars])
        got = curve.g1_from_device(
            jax.jit(lambda p, b: curve.msm(curve.F1, p, b))(ptd, bits))
        exp = hc.PointG1.msm(scalars, pts)
        assert got == exp


# ---------------------------------------------------------------------------
# Pairing layer (the expensive compiles — kept to a handful of calls,
# amortized by the persistent compilation cache)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def verify_compiled():
    """verify_prepared at the engine's bucket-4 shapes (one public key per
    row), so this module and the engine suites compile it once."""
    fn = jax.jit(dpair.verify_prepared)
    pub = hc.PointG1.generator().mul(42)
    pubs = jnp.broadcast_to(dpair.g1_affine_to_device(pub), (4, 2, 32))
    msg = dpair.g2_affine_to_device(hash_to_g2(b"seed"))
    msgs = jnp.broadcast_to(msg, (4, 2, 2, 32))
    return fn, pubs, msgs


class TestPairing:
    def test_pairing_matches_host_canonical(self):
        p = hc.PointG1.generator().mul(9)
        q = hash_to_g2(b"golden")
        p_d = dpair.g1_affine_to_device(p)
        q_d = dpair.g2_affine_to_device(q)[None]
        out = jax.jit(lambda a, b: dpair.multi_pairing(a, b, canonical=True))(
            (p_d[0][None], p_d[1][None]), q_d)
        assert tower.fp12_from_device(out) == hp.pairing(p, q)

    def test_final_exponentiation_matches_host(self):
        x = rf12()
        xd = tower.fp12_to_device(x)
        out = jax.jit(lambda f: dpair.final_exponentiation(f, False))(xd)
        assert tower.fp12_from_device(out) == \
            hp.final_exponentiation(x, canonical=False)

    def test_bls_verify_good_and_bad(self, verify_compiled):
        fn, pubs, msgs = verify_compiled
        good_d = dpair.g2_affine_to_device(hash_to_g2(b"seed").mul(42))
        bad_d = dpair.g2_affine_to_device(hash_to_g2(b"seed").mul(43))
        assert bool(fn(pubs, jnp.stack([good_d] * 4), msgs)[0])
        assert not bool(fn(pubs, jnp.stack([bad_d] * 4), msgs)[0])

    def test_bls_verify_batch(self, verify_compiled):
        """Batch axis: good and corrupted rows — elementwise verdicts."""
        fn, pubs, msgs = verify_compiled
        good = dpair.g2_affine_to_device(hash_to_g2(b"seed").mul(42))
        bad = dpair.g2_affine_to_device(hash_to_g2(b"seed").mul(99))
        out = fn(pubs, jnp.stack([good, bad, good, bad]), msgs)
        assert out.shape == (4,)
        assert np.asarray(out).tolist() == [True, False, True, False]
