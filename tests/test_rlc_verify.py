"""RLC batch verification: the host path vs the per-item oracle, the
dispatch knob, the pairing-count acceptance, and the device combine
graphs.

Host crypto is the semantics oracle: every bool array out of
crypto/batch_verify.py must be bit-identical to the per-item loop on
every fixture, including the adversarial ones (one bad item among N,
duplicate share indices, point-at-infinity signatures, a V2-only
corruption in a dual-sig span). The all-valid fast path must cost
exactly ONE 2-pairing product check, counted via the
crypto/pairing.N_PRODUCT_CHECKS counter.
"""

import numpy as np
import pytest
from conftest import sample_count as _sample_count

from drand_tpu import metrics
from drand_tpu.chain import beacon as chain_beacon
from drand_tpu.chain.beacon import Beacon, message, message_v2
from drand_tpu.crypto import batch, batch_verify, bls, tbls
from drand_tpu.crypto import pairing as hpairing
from drand_tpu.crypto.curves import PointG1
from drand_tpu.crypto.poly import PriPoly


@pytest.fixture(scope="module")
def keys():
    sk, pub = bls.keygen(seed=b"rlc-verify-test")
    return sk, pub


@pytest.fixture(scope="module")
def threshold_setup():
    poly = PriPoly.random(3, seed=b"rlc-verify-poly")
    return poly, poly.commit()


def _make_chain(sk: int, nrounds: int, v2: bool = False) -> list[Beacon]:
    prev, out = b"\x42" * 32, []
    for rnd in range(1, nrounds + 1):
        sig = bls.sign(sk, message(rnd, prev))
        sig2 = bls.sign(sk, message_v2(rnd)) if v2 else b""
        out.append(Beacon(round=rnd, previous_sig=prev, signature=sig,
                          signature_v2=sig2))
        prev = sig
    return out


def _oracle_beacons(pub, beacons):
    out = []
    for b in beacons:
        ok = chain_beacon.verify_beacon(pub, b)
        if ok and b.is_v2():
            ok = chain_beacon.verify_beacon_v2(pub, b)
        out.append(ok)
    return out


@pytest.fixture()
def host_mode():
    old = (batch._MODE, batch._MIN_BATCH, batch._ENGINE)
    batch.configure("host")
    yield
    batch._MODE, batch._MIN_BATCH, batch._ENGINE = old


class TestHostRLC:
    def test_all_valid_64_span_one_product_check(self, keys, host_mode,
                                                 monkeypatch):
        """The acceptance criterion: a 64-beacon all-valid span through
        the host dispatch performs exactly one 2-pairing product check
        and lands a host_rlc histogram sample."""
        sk, pub = keys
        beacons = _make_chain(sk, 64)
        monkeypatch.delenv("DRAND_TPU_BATCH_VERIFY", raising=False)
        h0 = _sample_count(metrics.REGISTRY, "engine_op_seconds",
                           op="verify_beacons", path="host_rlc")
        c0, p0 = hpairing.N_PRODUCT_CHECKS, hpairing.N_MILLER_PAIRS
        oks = batch.verify_beacons(pub, beacons)
        assert oks.all() and len(oks) == 64
        assert hpairing.N_PRODUCT_CHECKS - c0 == 1
        assert hpairing.N_MILLER_PAIRS - p0 == 2
        assert _sample_count(metrics.REGISTRY, "engine_op_seconds",
                             op="verify_beacons",
                             path="host_rlc") == h0 + 1

    def test_escape_hatch_restores_per_item(self, keys, host_mode,
                                            monkeypatch):
        """DRAND_TPU_BATCH_VERIFY=0: the exact per-item behavior — one
        product check per beacon check, samples under path="host"."""
        sk, pub = keys
        beacons = _make_chain(sk, 6)
        monkeypatch.setenv("DRAND_TPU_BATCH_VERIFY", "0")
        h0 = _sample_count(metrics.REGISTRY, "engine_op_seconds",
                           op="verify_beacons", path="host")
        r0 = _sample_count(metrics.REGISTRY, "engine_op_seconds",
                           op="verify_beacons", path="host_rlc")
        c0 = hpairing.N_PRODUCT_CHECKS
        oks = batch.verify_beacons(pub, beacons)
        assert oks.all()
        assert hpairing.N_PRODUCT_CHECKS - c0 == 6  # one per V1 check
        assert _sample_count(metrics.REGISTRY, "engine_op_seconds",
                             op="verify_beacons", path="host") == h0 + 1
        assert _sample_count(metrics.REGISTRY, "engine_op_seconds",
                             op="verify_beacons", path="host_rlc") == r0

    def test_one_bad_beacon_bisection_matches_oracle(self, keys):
        sk, pub = keys
        beacons = _make_chain(sk, 9)
        beacons[4].signature = beacons[3].signature
        got = batch_verify.verify_beacons_rlc(pub, beacons)
        assert list(got) == _oracle_beacons(pub, beacons)
        assert list(got) == [True] * 4 + [False] + [True] * 4

    def test_v2_only_corruption_in_dual_span(self, keys):
        """A dual-sig span where only the V2 signature of one beacon is
        corrupt — the combined check must attribute the failure to that
        beacon alone, exactly like the per-item dual loop."""
        sk, pub = keys
        beacons = _make_chain(sk, 6, v2=True)
        beacons[2].signature_v2 = beacons[1].signature_v2
        c0 = hpairing.N_PRODUCT_CHECKS
        got = batch_verify.verify_beacons_rlc(pub, beacons)
        oracle = _oracle_beacons(pub, beacons)
        assert list(got) == oracle == [True, True, False, True, True, True]
        assert hpairing.N_PRODUCT_CHECKS - c0 > 1  # bisection ran

    def test_one_bad_partial_among_n(self, threshold_setup):
        poly, pub = threshold_setup
        msg = b"rlc-round-1"
        parts = [tbls.sign_partial(s, msg) for s in poly.shares(8)]
        bad = parts[5][:5] + bytes([parts[5][5] ^ 1]) + parts[5][6:]
        parts[5] = bad
        got = batch_verify.verify_partials_rlc(pub, msg, parts)
        oracle = [tbls.verify_partial(pub, msg, p) for p in parts]
        assert got == oracle
        assert got == [True] * 5 + [False] + [True] * 2

    def test_duplicate_share_indices(self, threshold_setup):
        poly, pub = threshold_setup
        msg = b"rlc-round-2"
        parts = [tbls.sign_partial(s, msg) for s in poly.shares(4)]
        mixed = [parts[0], parts[0], parts[1], parts[1], parts[2]]
        got = batch_verify.verify_partials_rlc(pub, msg, mixed)
        oracle = [tbls.verify_partial(pub, msg, p) for p in mixed]
        assert got == oracle == [True] * 5
        # duplicate of a CORRUPT partial: both copies flagged
        bad = parts[3][:5] + bytes([parts[3][5] ^ 1]) + parts[3][6:]
        mixed = [parts[0], bad, bad, parts[1]]
        got = batch_verify.verify_partials_rlc(pub, msg, mixed)
        assert got == [tbls.verify_partial(pub, msg, p) for p in mixed]
        assert got == [True, False, False, True]

    def test_infinity_and_malformed_prefiltered(self, threshold_setup):
        """Point-at-infinity and malformed items are rejected per-item
        BEFORE the combination — the rest of the span still verifies in
        one product check (no bisection triggered)."""
        poly, pub = threshold_setup
        msg = b"rlc-round-3"
        parts = [tbls.sign_partial(s, msg) for s in poly.shares(3)]
        inf_sig = (5).to_bytes(2, "big") + b"\xc0" + b"\x00" * 95
        mixed = parts + [inf_sig, b"", parts[0][:50]]
        c0 = hpairing.N_PRODUCT_CHECKS
        got = batch_verify.verify_partials_rlc(pub, msg, mixed)
        rlc_checks = hpairing.N_PRODUCT_CHECKS - c0
        oracle = [tbls.verify_partial(pub, msg, p) for p in mixed]
        assert got == oracle == [True] * 3 + [False] * 3
        assert rlc_checks == 1

    def test_aggregate_round_host_api_unchanged(self, threshold_setup,
                                                host_mode, monkeypatch):
        """Host aggregate_round keeps its API and, with the RLC path on,
        an all-valid round costs 2 product checks total (combined
        partials + recovered signature) instead of t-proportional."""
        poly, pub = threshold_setup
        msg = b"rlc-agg-round"
        parts = [tbls.sign_partial(s, msg) for s in poly.shares(6)]
        monkeypatch.delenv("DRAND_TPU_BATCH_VERIFY", raising=False)
        c0 = hpairing.N_PRODUCT_CHECKS
        oks, sig = batch.aggregate_round(pub, msg, parts, 3, 6)
        assert oks == [True] * 6
        assert sig == tbls.recover(pub, msg, parts, 3, 6)
        assert hpairing.N_PRODUCT_CHECKS - c0 == 2

    def test_scalars_nonzero_and_nonconstant(self):
        a = batch_verify.rlc_scalars(64)
        b = batch_verify.rlc_scalars(64)
        assert all(0 < c < (1 << batch_verify.RLC_SCALAR_BITS) for c in a + b)
        assert a != b                 # fresh randomness across calls
        assert len(set(a)) > 1        # not a constant vector within a call

    def test_host_rlc_partials_metric_sample(self, threshold_setup,
                                             host_mode, monkeypatch):
        poly, pub = threshold_setup
        msg = b"rlc-metrics-partials"
        parts = [tbls.sign_partial(s, msg) for s in poly.shares(4)]
        monkeypatch.delenv("DRAND_TPU_BATCH_VERIFY", raising=False)
        h0 = _sample_count(metrics.REGISTRY, "engine_op_seconds",
                           op="verify_partials", path="host_rlc")
        assert batch.verify_partials(pub, msg, parts) == [True] * 4
        assert _sample_count(metrics.REGISTRY, "engine_op_seconds",
                             op="verify_partials",
                             path="host_rlc") == h0 + 1


def test_fallback_warning_rearms_after_device_success():
    """crypto/batch: the warn-once device-fallback flag resets when a
    later device dispatch succeeds, so a backend that recovers and then
    breaks again warns again."""
    old = batch._FALLBACK_LOGGED
    try:
        batch._FALLBACK_LOGGED = False
        batch._note_fallback("verify_beacons", RuntimeError("boom"))
        assert batch._FALLBACK_LOGGED is True
        batch._note_device_ok()
        assert batch._FALLBACK_LOGGED is False
        batch._note_fallback("verify_beacons", RuntimeError("boom again"))
        assert batch._FALLBACK_LOGGED is True
    finally:
        batch._FALLBACK_LOGGED = old


def test_h2c_memo_counters():
    """The hash_to_g2 keyed LRU exports hit/miss counters."""
    from drand_tpu.crypto import hash_to_curve as h2c

    msg = b"rlc-h2c-memo-probe"
    m0 = _sample_count(metrics.REGISTRY, "hash_to_g2_cache_requests",
                       result="miss")
    h0 = _sample_count(metrics.REGISTRY, "hash_to_g2_cache_requests",
                       result="hit")
    info0 = h2c.h2c_cache_info()
    first = h2c.hash_to_g2(msg)
    assert _sample_count(metrics.REGISTRY, "hash_to_g2_cache_requests",
                         result="miss") == m0 + 1
    again = h2c.hash_to_g2(msg)
    assert again == first
    assert _sample_count(metrics.REGISTRY, "hash_to_g2_cache_requests",
                         result="hit") == h0 + 1
    info1 = h2c.h2c_cache_info()
    assert info1["misses"] == info0["misses"] + 1
    assert info1["hits"] == info0["hits"] + 1
    assert info1["maxsize"] >= info1["size"]


# ---------------------------------------------------------------------------
# Device combine graphs (CPU backend in the suite; compile-heavy)
# ---------------------------------------------------------------------------


@pytest.mark.device
class TestDeviceRLC:
    @pytest.fixture
    def engine(self, engine4, monkeypatch):
        monkeypatch.setattr(engine4, "rlc_min", 2)
        monkeypatch.setattr(engine4, "rlc_lane_buckets", (4,))
        return engine4

    def test_verify_beacons_rlc_and_fallback(self, engine, keys):
        sk, pub = keys
        beacons = _make_chain(sk, 4)
        assert engine.verify_beacons(pub, beacons).all()
        # the combine KATs ran and the shapes are trusted
        assert engine._rlc_ok.get(("g2g2", 4)) is True
        # a corrupted beacon fails the combined check and falls back to
        # the per-item graphs with exact verdicts
        beacons[2].signature = beacons[1].signature
        got = engine.verify_beacons(pub, beacons)
        assert list(got) == [True, True, False, True]

    def test_verify_partials_and_agg_rlc(self, engine):
        # 2-of-4: recovery runs the 8-lane MSM ladder the engine tests
        # compile too (3-of-4 needs the 16-lane Pippenger graph, a
        # ten-minute compile of its own)
        poly = PriPoly.random(2, seed=b"rlc-verify-agg")
        pub = poly.commit()
        msg = b"rlc-device-round"
        parts = [tbls.sign_partial(s, msg) for s in poly.shares(4)]
        assert engine.verify_partials(pub, msg, parts) == [True] * 4
        assert engine._rlc_ok.get(("g1g2", 4)) is True
        oks, sig = engine.aggregate_round(pub, msg, parts, 2, 4)
        assert oks == [True] * 4
        assert sig == tbls.recover(pub, msg, parts, 2, 4)
        # corrupt one partial: exact per-item verdicts via the fallback
        bad = parts[0][:5] + bytes([parts[0][5] ^ 1]) + parts[0][6:]
        oks, sig = engine.aggregate_round(pub, msg,
                                          [bad] + parts[1:], 2, 4)
        assert oks == [False, True, True, True]
        assert sig == tbls.recover(pub, msg, parts[1:], 2, 4)

    def test_escape_hatch_skips_device_rlc(self, engine, keys,
                                           monkeypatch):
        sk, pub = keys
        monkeypatch.setenv("DRAND_TPU_BATCH_VERIFY", "0")
        assert engine._rlc_wanted(64) is False
        monkeypatch.delenv("DRAND_TPU_BATCH_VERIFY", raising=False)
        assert engine._rlc_wanted(64) is True
        assert engine._rlc_wanted(engine.rlc_min - 1) is False
