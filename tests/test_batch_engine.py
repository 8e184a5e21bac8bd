"""Batched device engine vs host-reference agreement, and engine-routed
protocol paths.

The host implementation (crypto/) is the semantics oracle; every engine
operation must agree with it bit-for-bit, including on malformed and
corrupted inputs. This is the integration guarantee an early review flagged
as missing: the TPU engine wired into the aggregator (chain/beacon/chain.go:136
analogue) and the syncer (client/verify.go:146 analogue).
"""

import os

import numpy as np
import pytest

pytestmark = pytest.mark.device

from drand_tpu.chain.beacon import Beacon, message, message_v2
from drand_tpu.crypto import batch, bls, tbls
from drand_tpu.crypto.curves import PointG1
from drand_tpu.crypto.poly import PriPoly


@pytest.fixture(scope="module")
def engine(engine4):
    """The suite's shared bucket-4 engine (tests/conftest.py): one
    compile per graph family."""
    return engine4


@pytest.fixture()
def device_mode(engine):
    """Force all batch.* dispatch through the device engine."""
    import drand_tpu.crypto.batch as b

    old = (b._MODE, b._MIN_BATCH, b._ENGINE)
    b.configure("device", min_batch=1, engine=engine)
    yield
    b._MODE, b._MIN_BATCH, b._ENGINE = old


@pytest.fixture(scope="module")
def threshold_setup():
    poly = PriPoly.random(2, seed=b"batch-engine-test")
    pub = poly.commit()
    shares = poly.shares(3)
    sk = poly.secret()
    pubkey = PointG1.generator().mul(sk)
    return poly, pub, shares, sk, pubkey


def _make_chain(sk: int, nrounds: int, v2: bool = True) -> list[Beacon]:
    prev = b"\x42" * 32
    out = []
    for rnd in range(1, nrounds + 1):
        sig = bls.sign(sk, message(rnd, prev))
        sig2 = bls.sign(sk, message_v2(rnd)) if v2 else b""
        out.append(Beacon(round=rnd, previous_sig=prev, signature=sig,
                          signature_v2=sig2))
        prev = sig
    return out


class TestEngineVsHost:
    def test_verify_partials_valid_and_corrupt(self, engine, threshold_setup):
        _, pub, shares, _, _ = threshold_setup
        msg = b"round-7"
        partials = [tbls.sign_partial(s, msg) for s in shares]
        assert engine.verify_partials(pub, msg, partials) == [True] * 3
        # flip one byte of the signature body of partial 1
        bad = partials[1][:5] + bytes([partials[1][5] ^ 1]) + partials[1][6:]
        got = engine.verify_partials(pub, msg, [partials[0], bad, partials[2]])
        host = [tbls.verify_partial(pub, msg, p)
                for p in (partials[0], bad, partials[2])]
        assert got == host == [True, False, True]

    def test_verify_partials_malformed(self, engine, threshold_setup):
        _, pub, shares, _, _ = threshold_setup
        msg = b"round-8"
        good = tbls.sign_partial(shares[0], msg)
        garbage = [b"", b"\x00" * 98, good[:50]]
        got = engine.verify_partials(pub, msg, [good] + garbage)
        assert got == [True, False, False, False]

    def test_recover_matches_host(self, engine, threshold_setup):
        _, pub, shares, _, _ = threshold_setup
        msg = b"round-9"
        partials = [tbls.sign_partial(s, msg) for s in shares]
        # every 2-subset recovers the same signature as the host
        for subset in ([0, 1], [1, 2], [0, 2], [2, 1, 0]):
            ps = [partials[i] for i in subset]
            assert engine.recover(pub, msg, ps, 2, 3) == \
                tbls.recover(pub, msg, ps, 2, 3)

    def test_recover_not_enough(self, engine, threshold_setup):
        _, pub, shares, _, _ = threshold_setup
        msg = b"round-10"
        partials = [tbls.sign_partial(shares[0], msg)]
        with pytest.raises(ValueError):
            engine.recover(pub, msg, partials, 2, 3)

    def test_aggregate_round_fused(self, engine, threshold_setup):
        _, pub, shares, _, _ = threshold_setup
        msg = b"round-agg"
        partials = [tbls.sign_partial(s, msg) for s in shares]
        oks, sig = engine.aggregate_round(pub, msg, partials, 2, 3)
        assert oks == [True] * 3
        assert sig == tbls.recover(pub, msg, partials, 2, 3)
        # the fused executable (bucket 4, 8 msm lanes — the GLS4 split
        # packs 4 digit lanes per share at 64-bit width) must have
        # passed its KAT — i.e. this went through ONE dispatch, not the
        # fallback
        from drand_tpu.crypto.endo import GLS4_DIGIT_BITS

        assert engine.agg_shape(3, 2) == (4, 8, GLS4_DIGIT_BITS)
        assert engine._agg_ok.get((4, 8, GLS4_DIGIT_BITS)) is True

    def test_aggregate_round_bad_chosen_partial(self, engine,
                                                threshold_setup):
        # a corrupt partial inside the optimistic t-subset: flagged in
        # oks, and recovery re-runs over the verified survivors
        _, pub, shares, _, _ = threshold_setup
        msg = b"round-agg-bad"
        partials = [tbls.sign_partial(s, msg) for s in shares]
        bad = partials[0][:5] + bytes([partials[0][5] ^ 1]) + partials[0][6:]
        oks, sig = engine.aggregate_round(
            pub, msg, [bad, partials[1], partials[2]], 2, 3)
        assert oks == [False, True, True]
        assert sig == tbls.recover(pub, msg, partials[1:], 2, 3)

    def test_aggregate_round_not_enough(self, engine, threshold_setup):
        _, pub, shares, _, _ = threshold_setup
        msg = b"round-agg-short"
        with pytest.raises(ValueError):
            engine.aggregate_round(
                pub, msg, [tbls.sign_partial(shares[0], msg)], 2, 3)

    def test_verify_beacons_dual(self, engine, threshold_setup):
        *_, sk, pubkey = threshold_setup
        beacons = _make_chain(sk, 3)
        assert engine.verify_beacons(pubkey, beacons).all()
        # corrupting the V2 signature must fail exactly that beacon
        beacons[1].signature_v2 = beacons[0].signature_v2
        got = engine.verify_beacons(pubkey, beacons)
        assert list(got) == [True, False, True]

    def test_verify_beacons_v1_corruption(self, engine, threshold_setup):
        *_, sk, pubkey = threshold_setup
        beacons = _make_chain(sk, 5, v2=False)  # 5 > the bucket: splits
        beacons[3].signature = beacons[2].signature
        got = engine.verify_beacons(pubkey, beacons)
        assert list(got) == [True, True, True, False, True]


class TestBatchDispatch:
    def test_host_and_device_agree(self, threshold_setup, device_mode):
        *_, sk, pubkey = threshold_setup
        beacons = _make_chain(sk, 3)
        dev = batch.verify_beacons(pubkey, beacons)
        import drand_tpu.crypto.batch as b

        b.configure("host")
        host = batch.verify_beacons(pubkey, beacons)
        assert list(dev) == list(host) == [True, True, True]

    def test_aggregate_round_host_path(self, threshold_setup):
        import drand_tpu.crypto.batch as b

        _, pub, shares, *_ = threshold_setup
        msg = b"agg-host"
        partials = [tbls.sign_partial(s, msg) for s in shares]
        old = (b._MODE, b._MIN_BATCH, b._ENGINE)
        b.configure("host")
        try:
            oks, sig = batch.aggregate_round(pub, msg, partials, 2, 3)
        finally:
            b._MODE, b._MIN_BATCH, b._ENGINE = old
        assert oks == [True] * 3
        assert sig == tbls.recover(pub, msg, partials, 2, 3)

    def test_verify_recovered_many(self, threshold_setup, device_mode):
        _, pub, shares, sk, pubkey = threshold_setup
        m1, m2 = message(1, b"\x42" * 32), message_v2(1)
        s1, s2 = bls.sign(sk, m1), bls.sign(sk, m2)
        assert batch.verify_recovered_many(pubkey, [(m1, s1), (m2, s2)]) == \
            [True, True]
        assert batch.verify_recovered_many(pubkey, [(m1, s2), (m2, s2)]) == \
            [False, True]


@pytest.mark.skipif(os.environ.get("DRAND_TPU_HEAVY_TESTS") != "1",
                    reason="one large-batch compile (~minutes cold); set "
                           "DRAND_TPU_HEAVY_TESTS=1 to run")
def test_batch64_regression(threshold_setup):
    """Batch >= 64 regression: with lax.cond/lax.switch inside lax.scan
    the pairing graph returned wrong results at B=64 while B=16 passed.
    The pairing is now cond-free; this pins it
    at a batch size above the failure threshold on whatever backend the
    suite runs."""
    from drand_tpu.ops.engine import BatchedEngine

    *_, sk, pubkey = threshold_setup
    eng = BatchedEngine(buckets=(64,))
    beacons = _make_chain(sk, 8, v2=True)  # 16 checks padded to 64
    got = eng.verify_beacons(pubkey, beacons)
    assert got.all()
    beacons[5].signature = beacons[4].signature
    got = eng.verify_beacons(pubkey, beacons)
    assert list(got) == [True] * 5 + [False] + [True] * 2


@pytest.mark.asyncio
async def test_beacon_network_with_device_engine(device_mode):
    """End-to-end: a 3-node t=2 network produces verifying rounds with every
    crypto call routed through the device engine (the aggregator's recover +
    verify and the handler's partial checks all go through batch.*)."""
    from drand_tpu.chain import beacon as chain_beacon
    from drand_tpu.testing.harness import BeaconTestNetwork

    net = BeaconTestNetwork(n=3, t=2, period=2)
    await net.start_all()
    await net.advance_to_genesis()
    # per-round lockstep (the test_beacon_engine idiom): aggregation runs
    # off-loop in a thread, so each round must land before the fake clock
    # moves on — advancing several periods at once parks every node in the
    # catchup breather, which sleeps on the (now idle) fake clock forever
    for r in range(1, 4):
        for i in range(3):
            await net.wait_round(i, r, timeout=120)
        await net.advance_rounds(1)
    net.stop_all()
    pubkey = net.group.public_key.key()
    for node in net.nodes:
        beacons = [node.store.get(r) for r in range(1, 4)]
        assert batch.verify_beacons(pubkey, beacons).all()
        for b in beacons:
            assert chain_beacon.verify_beacon(pubkey, b)


@pytest.mark.parametrize("pallas_only", [True, False])
def test_dispatch_policy_is_decided_at_construction(monkeypatch,
                                                    pallas_only):
    """The chip's dispatch policy (only Mosaic programs: an XLA limb
    graph compiles for minutes per shape on a v5e) is one flag fixed
    when the engine is built (by default: the backend is a TPU). Which
    buckets and tiers each setting routes to, checked without a chip."""
    from drand_tpu.crypto.endo import GLS4_DIGIT_BITS
    from drand_tpu.ops import pallas_msm
    from drand_tpu.ops.engine import BatchedEngine

    monkeypatch.delenv("DRAND_TPU_WIRE_PREP", raising=False)
    monkeypatch.delenv("DRAND_TPU_BATCH_VERIFY", raising=False)
    eng = BatchedEngine(pallas_only=pallas_only)
    assert eng.introspect()["pallas_only"] is pallas_only
    assert BatchedEngine().pallas_only is False  # the suite runs on CPU
    if pallas_only:
        assert eng._pairing_buckets() == (128, 512)
        assert eng._wire_buckets() == (128,)
        assert eng._pallas_ok(128) and not eng._pallas_ok(4)
        assert eng._use_wire(2)  # a short span takes the Mosaic wire tier
        assert not eng._combine_rlc_wanted(1000)  # XLA combine: off
        msm_lanes = pallas_msm.LANES  # recovery pads to the Mosaic MSM
    else:
        assert eng._pairing_buckets() == (4, 128, 512)
        assert eng._wire_buckets() == (4, 128)
        assert not eng._pallas_ok(128)
        assert not eng._use_wire(2)
        assert eng._combine_rlc_wanted(1000)
        msm_lanes = 32
    assert eng.agg_shape(npart=10, t=6) == (128, msm_lanes, GLS4_DIGIT_BITS)
