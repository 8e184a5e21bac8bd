"""Mesh-sharded engine verification — the batch axis distributed over a
device mesh must give identical results to the single-device engine.

Runs on the conftest-provisioned 8-device virtual CPU mesh (the driver's
dryrun_multichip validates the same pattern; real multi-chip TPU uses
the shard_map Pallas variant). SURVEY §5: catchup verification sharded
across chips with pjit.

ISSUE 8 additions: the SHARDED wire-RLC catch-up tier (per-shard device
h2c + decompression + lane-MSM, one cross-shard reduction, one pairing
row per span — meter-proven 2 Miller pairs), the pad-to-mesh fix for
buckets that don't divide the mesh, and the dispatcher's
``wire_rlc_sharded`` path label.
"""

import types as _types

import numpy as np
import pytest

# slow: the mesh graphs (the 16-row pairing on the 8-way mesh beside its
# single-device twin, the sharded wire-RLC combine) compile for 547 s
# and over 900 s cold under -n 6
pytestmark = [pytest.mark.device, pytest.mark.slow]
import jax

from conftest import sample_count as _sample_count


@pytest.fixture
def mesh():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    from jax.sharding import Mesh

    return Mesh(np.array(devs[:8]), ("data",))


@pytest.fixture(scope="module")
def mesh4():
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs >= 4 virtual CPU devices")
    from jax.sharding import Mesh

    return Mesh(np.array(devs[:4]), ("data",))


def _triples(n, sk=0x515):
    from drand_tpu.crypto import bls
    from drand_tpu.crypto.curves import PointG1, PointG2
    from drand_tpu.crypto.hash_to_curve import hash_to_g2

    pub = PointG1.generator().mul(sk)
    out, want = [], []
    for i in range(n):
        m = b"shard-%d" % i
        sig = PointG2.from_bytes(bls.sign(sk, m), subgroup_check=False)
        bad = i % 5 == 2
        out.append((pub, sig, hash_to_g2(b"other" if bad else m)))
        want.append(not bad)
    return out, want


def test_sharded_verify_matches_single_device(mesh):
    from drand_tpu.ops.engine import BatchedEngine

    triples, want = _triples(13)
    single = BatchedEngine(buckets=(16,))
    sharded = BatchedEngine(buckets=(16,), mesh=mesh)
    out_s = single.verify_bls(triples)
    out_m = sharded.verify_bls(triples)
    assert list(out_s) == want
    assert list(out_m) == want


def test_sharded_bucket_kat_gates(mesh):
    """The sharded path goes through the same known-answer validation."""
    from drand_tpu.ops.engine import BatchedEngine

    eng = BatchedEngine(buckets=(16,), mesh=mesh)
    assert eng._check_bucket(16) is True
    assert eng._bucket_ok == {16: True}


def test_prime_bucket_pads_to_mesh(mesh):
    """Regression (ISSUE 8 satellite): a bucket that does not divide the
    mesh used to drop silently to a single device — it must now pad up
    to the next mesh multiple (generator rows masked out via ``valid``)
    and still produce exact verdicts on a prime-sized span."""
    from drand_tpu.ops.engine import BatchedEngine

    triples, want = _triples(13, sk=0x9B1)
    eng = BatchedEngine(buckets=(13,), mesh=mesh)
    out = eng.verify_bls(triples)
    assert list(out) == want
    # the dispatched executable really carries the padded, mesh-divisible
    # batch: 13 rows round up to 16 over the 8-way mesh
    dev, valid, n = eng._launch_bucket(triples, 13)
    assert np.asarray(dev).shape[0] == 16
    assert valid.shape == (16,) and not valid[13:].any()
    assert n == 13


# ---------------------------------------------------------------------------
# Sharded wire-RLC catch-up tier (ISSUE 8 tentpole)
# ---------------------------------------------------------------------------

def _chain(sk, nrounds):
    from drand_tpu.chain.beacon import Beacon, message
    from drand_tpu.crypto import bls

    prev, out = b"\x42" * 32, []
    for rnd in range(1, nrounds + 1):
        sig = bls.sign(sk, message(rnd, prev))
        out.append(Beacon(round=rnd, previous_sig=prev, signature=sig))
        prev = sig
    return out


class TestShardedWireRLC:
    @pytest.fixture(scope="class")
    def keys(self):
        from drand_tpu.crypto import bls

        return bls.keygen(seed=b"sharded-wire-rlc")

    @pytest.fixture(scope="class")
    def engine(self, mesh4):
        from drand_tpu.ops.engine import BatchedEngine

        eng = BatchedEngine(buckets=(8,), wire_prep=True, mesh=mesh4)
        eng.rlc_min = 2
        return eng

    def test_sharded_span_two_miller_pairs(self, engine, keys):
        """THE acceptance shape: an all-valid span through the SHARDED
        wire-RLC tier — per-shard h2c + decompression + lane-MSM, one
        cross-shard reduction — still dispatches exactly one pairing
        row = 2 Miller pairs for the whole span."""
        from drand_tpu.ops import engine as eng_mod

        sk, pub = keys
        beacons = _chain(sk, 8)
        got = engine.verify_beacons_wire_rlc(pub, beacons)
        assert got is not None and got.all() and len(got) == 8
        # the shard-shape KAT vouched for the sharded executable
        assert engine._wire_rlc_sharded_ok.get(8) is True
        assert engine._wire_rlc_ok == {}  # unsharded combine never built
        c0, p0 = eng_mod.N_PRODUCT_CHECKS, eng_mod.N_MILLER_PAIRS
        got = engine.verify_beacons_wire_rlc(pub, beacons)
        assert got is not None and got.all()
        assert eng_mod.N_PRODUCT_CHECKS - c0 == 1
        assert eng_mod.N_MILLER_PAIRS - p0 == 2

    def test_cross_shard_reduction_matches_host(self, engine, keys):
        """The gathered per-shard partial sums fold to exactly the host
        MSM of the same points and scalars — the single cross-shard
        reduction loses nothing."""
        from drand_tpu.chain.beacon import message
        from drand_tpu.crypto import batch_verify
        from drand_tpu.crypto.curves import PointG2
        from drand_tpu.crypto.hash_to_curve import (DEFAULT_DST_G2,
                                                    hash_to_g2)

        sk, pub = keys
        beacons = _chain(sk, 8)
        checks = [(message(b.round, b.previous_sig), b.signature)
                  for b in beacons]
        cs = [3 + 2 * i for i in range(8)]
        got = engine._combine_wire_chunk(checks, cs, 8, DEFAULT_DST_G2,
                                         sharded=True)
        assert got is not None
        mask, s_comb, m_comb = got
        assert list(mask) == [True] * 8
        sig_pts = [PointG2.from_bytes(s, subgroup_check=False)
                   for _, s in checks]
        msg_pts = [hash_to_g2(m) for m, _ in checks]
        assert s_comb == batch_verify.msm_window(sig_pts, cs, nbits=8)
        assert m_comb == batch_verify.msm_window(msg_pts, cs, nbits=8)

    def test_one_bad_lane_bisection_bit_identical(self, engine, keys):
        """A decodable-but-wrong signature fails the sharded combined
        check: the tier returns None (false-reject-only) and the
        per-item cascade produces verdicts bit-identical to the host
        oracle, flagging exactly the bad lane."""
        from drand_tpu.chain import beacon as chain_beacon

        sk, pub = keys
        beacons = _chain(sk, 8)
        beacons[3].signature = beacons[2].signature
        assert engine.verify_beacons_wire_rlc(pub, beacons) is None
        got = engine.verify_beacons(pub, beacons)
        oracle = [chain_beacon.verify_beacon(pub, b) for b in beacons]
        assert list(got) == oracle
        assert oracle == [True, True, True, False, True, True, True, True]

    def test_dispatch_label_wire_rlc_sharded(self, engine, keys,
                                             monkeypatch):
        """crypto/batch.py labels mesh-sharded spans under their own
        engine_op_seconds{path="wire_rlc_sharded"} (check_metrics lints
        the label into the documented set)."""
        from drand_tpu import metrics
        from drand_tpu.crypto import batch

        sk, pub = keys
        beacons = _chain(sk, 8)
        monkeypatch.delenv("DRAND_TPU_BATCH_VERIFY", raising=False)
        assert engine.wire_rlc_sharded_active(8) is True
        old = (batch._MODE, batch._MIN_BATCH, batch._ENGINE)
        batch.configure("device", engine=engine)
        try:
            out = batch.verify_beacons(pub, beacons)
            assert out.all() and len(out) == 8
            # first dispatch of the cold shape lands in
            # engine_compile_seconds (the ISSUE 6 split); the next one
            # samples the path label
            h1 = _sample_count(metrics.REGISTRY, "engine_op_seconds",
                               op="verify_beacons",
                               path="wire_rlc_sharded")
            out = batch.verify_beacons(pub, beacons)
            assert out.all()
            assert _sample_count(metrics.REGISTRY, "engine_op_seconds",
                                 op="verify_beacons",
                                 path="wire_rlc_sharded") == h1 + 1
        finally:
            batch._MODE, batch._MIN_BATCH, batch._ENGINE = old

    def test_introspect_reports_shard_family(self, engine):
        import json

        data = engine.introspect()
        json.dumps(data)
        assert data["mesh"] == {"axes": ["data"], "size": 4}
        assert data["wire_rlc_sharded_buckets"] == [8]
        assert data["kat"]["wire_rlc_sharded"] == {"b8/m4": True}

    def test_follow_chain_drives_sharded_path(self, engine, keys):
        """Integration (ISSUE 8 acceptance): a Syncer.follow catch-up
        over a stubbed peer stream verifies its span through the
        mesh-sharded wire-RLC tier — the dispatch lands under
        engine_op_seconds{path="wire_rlc_sharded"} and the whole chain
        stores."""
        import asyncio

        from drand_tpu import metrics
        from drand_tpu.chain.beacon import Beacon
        from drand_tpu.chain.engine import sync as sync_mod
        from drand_tpu.chain.store import CallbackStore, MemStore
        from drand_tpu.crypto import batch
        from drand_tpu.utils.logging import default_logger

        sk, pub = keys
        beacons = _chain(sk, 8)
        store = CallbackStore(MemStore())
        store.put(Beacon(round=0, previous_sig=b"",
                         signature=beacons[0].previous_sig))
        info = _types.SimpleNamespace(public_key=pub, genesis_seed=b"t")

        class _StubTransport:
            def sync_chain(self, peer, req):
                async def gen():
                    for b in beacons[req.from_round - 1:]:
                        yield b
                return gen()

        old = (batch._MODE, batch._MIN_BATCH, batch._ENGINE)
        batch.configure("device", min_batch=1, engine=engine)
        try:
            # configure() cleared the compile-split warm set: burn the
            # first (compile-labelled) dispatch so the follow below
            # samples the steady-state path label
            assert batch.verify_beacons(pub, beacons).all()
            n0 = _sample_count(metrics.REGISTRY, "engine_op_seconds",
                               op="verify_beacons",
                               path="wire_rlc_sharded")
            syncer = sync_mod.Syncer(default_logger("test.sync"), store,
                                     info, _StubTransport())
            assert asyncio.run(syncer.follow(8, ["peer"])) is True
            assert store.last().round == 8
            assert _sample_count(metrics.REGISTRY, "engine_op_seconds",
                                 op="verify_beacons",
                                 path="wire_rlc_sharded") == n0 + 1
        finally:
            batch._MODE, batch._MIN_BATCH, batch._ENGINE = old

    def test_sync_chunks_size_mesh_divisibly(self, engine, monkeypatch):
        """Syncer.follow's verify chunks round up to a mesh multiple so
        the sharded tier engages with all-live lanes."""
        from drand_tpu.chain.engine import sync as sync_mod
        from drand_tpu.crypto import batch

        old = (batch._MODE, batch._MIN_BATCH, batch._ENGINE)
        batch.configure("device", engine=engine)
        try:
            assert batch.engine_mesh_size() == 4
            monkeypatch.setattr(sync_mod, "SYNC_CHUNK", 13)
            assert sync_mod._verify_chunk_size() == 16
            monkeypatch.setattr(sync_mod, "SYNC_CHUNK", 64)
            assert sync_mod._verify_chunk_size() == 64
        finally:
            batch._MODE, batch._MIN_BATCH, batch._ENGINE = old
        assert batch.engine_mesh_size() in (1, 4)  # restored engine peek
