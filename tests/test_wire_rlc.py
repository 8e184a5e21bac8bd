"""Wire-graph RLC catch-up + endomorphism-Pippenger host MSM (ISSUE 5).

The acceptance criteria pinned here:

- an all-valid catch-up span through the device wire_rlc tier costs
  exactly ONE pairing-graph row = 2 Miller pairs (was 2N), proven by the
  ops/engine.py device pairing-row meter;
- a KAT-gate failure (or a bad signature) falls back to the per-item
  wire graph with exact verdicts — false-reject-only by construction;
- a one-bad-item host span resolves through the batched 4-pairing
  bisection (pairing.pairing_check_groups) with bool arrays
  bit-identical to the per-item loop;
- the ψ-endomorphism-split Pippenger MSM is value-identical to the
  reference windowed MSM, including the split edge scalars 0, 1 and
  2^128-1;
- DRAND_TPU_BATCH_VERIFY=0 disables the wire_rlc tier like every other
  RLC path.
"""

import numpy as np
import pytest
from conftest import sample_count as _sample_count

from drand_tpu import metrics
from drand_tpu.chain.beacon import Beacon, message
from drand_tpu.crypto import batch, batch_verify, bls
from drand_tpu.crypto import pairing as hpairing
from drand_tpu.crypto.curves import PointG1, PointG2
from drand_tpu.ops import engine as engine_mod


@pytest.fixture(scope="module")
def keys():
    sk, pub = bls.keygen(seed=b"wire-rlc-test")
    return sk, pub


def _make_chain(sk: int, nrounds: int) -> list[Beacon]:
    prev, out = b"\x42" * 32, []
    for rnd in range(1, nrounds + 1):
        sig = bls.sign(sk, message(rnd, prev))
        out.append(Beacon(round=rnd, previous_sig=prev, signature=sig))
        prev = sig
    return out


def _pinned_scalars(n: int) -> list[int]:
    """Fixed nonzero 128-bit scalars, so two runs of a span combine
    alike."""
    return [(0x9E3779B97F4A7C15F39CC0605CEDC835 * (i + 1)) % (1 << 128) | 1
            for i in range(n)]


def _serial_wire_rlc(engine, checks, cs, dst, b: int = 4):
    """A span as the serial composition of _combine_wire_chunk: the
    decode-ok mask and the summed (Σc·sig, Σc·H(m))."""
    mask, s_acc, m_acc = [], None, None
    for lo in range(0, len(checks), b):
        ok, s, m = engine._combine_wire_chunk(checks[lo:lo + b],
                                              cs[lo:lo + b], b, dst)
        mask += list(ok)
        if s is not None:
            s_acc = s if s_acc is None else s_acc + s
            m_acc = m if m_acc is None else m_acc + m
    return mask, s_acc, m_acc


def _slice_delta(engine, pub, sk, nrounds):
    """(overlapped, exposed) slices one all-valid span of ``nrounds``
    adds to engine_wire_rlc_slices_total."""
    def counts():
        return tuple(_sample_count(
            metrics.REGISTRY, "engine_wire_rlc_slices", prep=p)
            for p in ("overlapped", "exposed"))

    before = counts()
    got = engine.verify_beacons_wire_rlc(pub, _make_chain(sk, nrounds))
    assert got is not None and got.all()
    return tuple(a - b for a, b in zip(counts(), before))


def _oracle(pub, beacons):
    from drand_tpu.chain import beacon as chain_beacon

    return [chain_beacon.verify_beacon(pub, b) for b in beacons]


# ---------------------------------------------------------------------------
# Host: ψ-split Pippenger MSM vs the windowed reference
# ---------------------------------------------------------------------------

class TestHostMSM:
    def test_pippenger_endo_matches_window_g2(self):
        import random

        rng = random.Random(7)
        g2 = PointG2.generator()
        # edge scalars through the ψ split: 0 (drops out), 1 (rem-only),
        # 2^128-1 (maximal q), M and M±1 (split boundary)
        M = batch_verify._ENDO_M
        pts = [g2.mul(k + 2) for k in range(8)]
        scs = [0, 1, (1 << 128) - 1, M, M - 1, M + 1,
               rng.randrange(1 << 128), rng.randrange(1 << 128)]
        assert batch_verify.msm(pts, scs) == batch_verify.msm_window(pts, scs)
        # a span large enough for the bucket branch
        pts = [g2.mul(rng.randrange(1, 1 << 60)) for _ in range(40)]
        scs = [rng.randrange(1 << 128) for _ in range(40)]
        assert batch_verify.msm(pts, scs) == batch_verify.msm_window(pts, scs)
        # all-zero scalars and infinity points degrade gracefully
        assert batch_verify.msm(pts[:3], [0, 0, 0]).is_infinity()
        got = batch_verify.msm([PointG2.infinity(), g2], [5, 3])
        assert got == g2.mul(3)

    def test_pippenger_matches_window_g1(self):
        import random

        rng = random.Random(11)
        g1 = PointG1.generator()
        pts = [g1.mul(rng.randrange(1, 1 << 60)) for _ in range(20)]
        scs = [rng.randrange(1 << 128) for _ in range(20)]
        assert batch_verify.msm(pts, scs) == batch_verify.msm_window(pts, scs)
        assert batch_verify.msm_pippenger(pts, scs) == \
            batch_verify.msm_window(pts, scs)

    def test_endo_split_reconstructs_scalar(self):
        g2 = PointG2.generator()
        for c in (0, 1, (1 << 128) - 1, batch_verify._ENDO_M, 12345):
            p = g2.mul(9)
            pts, scs = batch_verify._endo_split_g2([p], [c])
            acc = PointG2.infinity()
            for q, s in zip(pts, scs):
                assert s.bit_length() <= batch_verify._ENDO_Q_BITS
                acc = acc + q.mul(s)
            assert acc == p.mul(c)

    def test_gls4_edge_matrix_vs_window(self):
        """The ψ² 4-D split extends msm to full-width scalars: edge
        values (0, 1, M±1, 2^255−19-adjacent, group-order−1) and random
        255-bit scalars are value-identical to the 255-bit windowed
        ladder (ISSUE 8: wide scalars reduce mod r first — same group
        element either way)."""
        import random

        from drand_tpu.crypto import endo
        from drand_tpu.crypto.fields import R

        rng = random.Random(0x615)
        g2 = PointG2.generator()
        M = endo.GLS4_M
        pts = [g2.mul(k + 3) for k in range(10)]
        scs = [0, 1, M - 1, M, M + 1, R - 1, (1 << 255) - 19,
               (1 << 255) - 18, rng.randrange(1 << 255),
               rng.randrange(1 << 254)]
        assert batch_verify.msm(pts, scs) == \
            batch_verify.msm_window(pts, scs, nbits=255)
        # a span wide enough for the bucket branch post-split
        pts = [g2.mul(rng.randrange(1, 1 << 60)) for _ in range(24)]
        scs = [rng.randrange(1 << 255) for _ in range(24)]
        assert batch_verify.msm(pts, scs) == \
            batch_verify.msm_window(pts, scs, nbits=255)

    def test_gls4_split_reconstructs_scalar(self):
        from drand_tpu.crypto import endo
        from drand_tpu.crypto.fields import R

        g2 = PointG2.generator()
        M = endo.GLS4_M
        for c in (1, M, M - 1, R - 1, (1 << 255) - 19):
            p = g2.mul(11)
            pts, scs = batch_verify._endo_split4_g2([p], [c])
            acc = PointG2.infinity()
            for q, s in zip(pts, scs):
                assert s.bit_length() <= endo.GLS4_DIGIT_BITS
                acc = acc + q.mul(s)
            assert acc == p.mul(c % R)


# ---------------------------------------------------------------------------
# Host: batched 4-pairing bisection
# ---------------------------------------------------------------------------

class TestBatchedBisection:
    def test_one_bad_item_grouped_dispatches(self, keys):
        """9-beacon span, one bad signature: root check fails, then each
        bisection level decides BOTH halves with one grouped 4-pairing
        product check. Exact trace: root(2 pairs) + group{0-3, 4-8}(4)
        + group{4-5, 6-8}(4) + leaf(4) + leaf(5) = 5 product-check
        invocations / 14 Miller pairs — the sequential bisection paid 7
        invocations for the same span."""
        sk, pub = keys
        beacons = _make_chain(sk, 9)
        beacons[4].signature = beacons[3].signature
        c0, p0 = hpairing.N_PRODUCT_CHECKS, hpairing.N_MILLER_PAIRS
        got = batch_verify.verify_beacons_rlc(pub, beacons)
        checks = hpairing.N_PRODUCT_CHECKS - c0
        pairs = hpairing.N_MILLER_PAIRS - p0
        oracle = _oracle(pub, beacons)
        assert list(got) == oracle == [True] * 4 + [False] + [True] * 4
        assert checks == 5
        assert pairs == 14

    def test_two_bad_items_still_bit_identical(self, keys):
        sk, pub = keys
        beacons = _make_chain(sk, 12)
        beacons[2].signature = beacons[1].signature
        beacons[9].signature = b"\x00" * 96  # malformed: per-item reject
        got = batch_verify.verify_beacons_rlc(pub, beacons)
        assert list(got) == _oracle(pub, beacons)
        assert list(got) == [True, True, False] + [True] * 6 + [False,
                                                                True, True]

    def test_grouped_pairing_check_primitive(self, keys):
        sk, pub = keys
        from drand_tpu.crypto.hash_to_curve import hash_to_g2

        m1, m2 = b"wrlc-a", b"wrlc-b"
        s1 = PointG2.from_bytes(bls.sign(sk, m1))
        s2 = PointG2.from_bytes(bls.sign(sk, m2))
        neg = -PointG1.generator()
        c0, p0 = hpairing.N_PRODUCT_CHECKS, hpairing.N_MILLER_PAIRS
        oks = hpairing.pairing_check_groups([
            [(neg, s1), (pub, hash_to_g2(m1))],
            [(neg, s2), (pub, hash_to_g2(m2))],
            [(neg, s1), (pub, hash_to_g2(m2))],   # mismatched: False
            [],                                   # vacuous: True
        ])
        assert oks == [True, True, False, True]
        assert hpairing.N_PRODUCT_CHECKS - c0 == 1
        assert hpairing.N_MILLER_PAIRS - p0 == 6


# ---------------------------------------------------------------------------
# Device wire-RLC tier (CPU backend in the suite; compile-heavy)
# ---------------------------------------------------------------------------

@pytest.mark.device
class TestWireRLC:
    @pytest.fixture
    def engine(self, wire_engine4, monkeypatch):
        monkeypatch.setattr(wire_engine4, "rlc_min", 2)
        return wire_engine4

    def test_all_valid_span_two_miller_pairs(self, engine, keys):
        """THE acceptance criterion: an all-valid span through wire_rlc
        dispatches exactly one pairing row = 2 Miller pairs (was 2N),
        even when the span chunks over multiple combine buckets."""
        from drand_tpu.ops import engine as eng_mod

        sk, pub = keys
        beacons = _make_chain(sk, 6)  # 6 checks over bucket 4: 2 chunks
        got = engine.verify_beacons_wire_rlc(pub, beacons)
        assert got is not None and got.all() and len(got) == 6
        assert engine._wire_rlc_ok.get(4) is True
        # warm: second span pays exactly one 2-pair product check
        c0, p0 = eng_mod.N_PRODUCT_CHECKS, eng_mod.N_MILLER_PAIRS
        got = engine.verify_beacons_wire_rlc(pub, beacons)
        assert got is not None and got.all()
        assert eng_mod.N_PRODUCT_CHECKS - c0 == 1
        assert eng_mod.N_MILLER_PAIRS - p0 == 2

    def test_malformed_lane_excluded_not_poisoning(self, engine, keys):
        """A malformed signature encoding is a per-item False and is
        masked out of the device combination — the rest of the span
        still verifies as one 2-pair row."""
        from drand_tpu.ops import engine as eng_mod

        sk, pub = keys
        beacons = _make_chain(sk, 6)
        beacons[3].signature = b"\x00" * 96
        c0, p0 = eng_mod.N_PRODUCT_CHECKS, eng_mod.N_MILLER_PAIRS
        got = engine.verify_beacons_wire_rlc(pub, beacons)
        assert got is not None
        assert list(got) == [True, True, True, False, True, True]
        assert eng_mod.N_MILLER_PAIRS - p0 == 2

    def test_bad_signature_false_reject_only_fallback(self, engine, keys):
        """A decodable-but-wrong signature fails the combined check: the
        tier returns None (false-reject-only) and the cascade lands on
        the per-item wire graph with exact verdicts."""
        sk, pub = keys
        beacons = _make_chain(sk, 4)
        beacons[2].signature = beacons[1].signature
        assert engine.verify_beacons_wire_rlc(pub, beacons) is None
        got = engine.verify_beacons(pub, beacons)
        assert list(got) == [True, True, False, True]

    def test_bad_signature_mid_span_pipelined(self, engine, keys,
                                              monkeypatch):
        """A span of three slices runs them pipelined, and its verdicts
        are those of the serial composition of _combine_wire_chunk on
        the same scalars: a malformed signature in the last slice is a
        per-item False with the same combined sums, and a bad signature
        in the middle slice still gives None, then the exact verdicts
        of the fallback."""
        from drand_tpu.chain import beacon as chain_beacon
        from drand_tpu.crypto.hash_to_curve import DEFAULT_DST_G2

        sk, pub = keys
        monkeypatch.setattr(batch_verify, "rlc_scalars", _pinned_scalars)
        rows = []
        real_verify_bls = engine.verify_bls

        def spy(batch_rows):
            rows.append(batch_rows[0])
            return real_verify_bls(batch_rows)

        monkeypatch.setattr(engine, "verify_bls", spy)
        beacons = _make_chain(sk, 10)  # 10 checks over bucket 4: 3 slices
        beacons[9].signature = b"\x00" * 96
        checks = [(chain_beacon.message(b.round, b.previous_sig),
                   b.signature) for b in beacons]
        cs = _pinned_scalars(len(checks))

        got = engine.verify_beacons_wire_rlc(pub, beacons)
        mask, s_acc, m_acc = _serial_wire_rlc(engine, checks, cs,
                                              DEFAULT_DST_G2)
        assert got is not None
        assert list(got) == mask == [True] * 9 + [False]
        assert rows == [(pub, s_acc, m_acc)]

        beacons[5].signature = beacons[4].signature
        checks[5] = (checks[5][0], beacons[5].signature)
        assert engine.verify_beacons_wire_rlc(pub, beacons) is None
        mask, s_acc, m_acc = _serial_wire_rlc(engine, checks, cs,
                                              DEFAULT_DST_G2)
        assert not real_verify_bls([(pub, s_acc, m_acc)])[0]
        got = engine.verify_beacons(pub, beacons)
        assert list(got) == _oracle(pub, beacons)
        assert list(got) == [True] * 5 + [False] + [True] * 3 + [False]

    def test_degenerate_mid_span_drops_later_slices(self, engine, keys,
                                                    monkeypatch):
        """A combination that degenerates to infinity in the middle
        slice (zero scalars there) returns None; the last slice, already
        launched behind it, is never read back."""
        sk, pub = keys

        def scalars(n):
            cs = _pinned_scalars(n)
            cs[4:8] = [0] * 4
            return cs

        monkeypatch.setattr(batch_verify, "rlc_scalars", scalars)
        assert engine._wire_rlc_check(4)  # the KAT, before the spies
        launched, finished = [], []
        real_launch = engine._launch_wire_chunk
        real_finish = engine._finish_wire_chunk

        def launch(checks, *a, **k):
            launched.append(len(checks))
            return real_launch(checks, *a, **k)

        def finish(pending):
            finished.append(pending)
            return real_finish(pending)

        monkeypatch.setattr(engine, "_launch_wire_chunk", launch)
        monkeypatch.setattr(engine, "_finish_wire_chunk", finish)
        assert engine.verify_beacons_wire_rlc(pub, _make_chain(sk, 10)) \
            is None
        assert launched == [4, 4, 2]
        assert len(finished) == 2

    def test_slice_counter_overlapped_and_exposed(self, engine, keys,
                                                  monkeypatch):
        """Every slice is counted once and a span's first is exposed.
        While the device is still on the slice before (as on a chip,
        where a slice outlasts its successor's preparation), a span of
        k slices counts k-1 overlapped and 1 exposed; a span of one
        slice counts 1 exposed."""
        sk, pub = keys
        overlapped, exposed = _slice_delta(engine, pub, sk, 10)
        assert overlapped + exposed == 3 and exposed >= 1
        monkeypatch.setattr(engine_mod, "_running", lambda launched: True)
        assert _slice_delta(engine, pub, sk, 10) == (2, 1)
        assert _slice_delta(engine, pub, sk, 4) == (0, 1)

    def test_slice_counter_sees_a_synced_launch(self, engine, keys,
                                                monkeypatch):
        """The counter reads the device, not the slice's place in the
        loop: a launch that waits for its own outputs leaves nothing in
        flight, so every slice of the span counts exposed."""
        import jax

        sk, pub = keys
        real_launch = engine._launch_wire_chunk

        def synced(*a, **k):
            n, out = real_launch(*a, **k)
            jax.block_until_ready(out)
            return n, out

        monkeypatch.setattr(engine, "_launch_wire_chunk", synced)
        assert _slice_delta(engine, pub, sk, 10) == (0, 3)

    def test_combine_failure_propagates(self, engine, keys, monkeypatch):
        """A combine graph that fails to run is a fault, not a disabled
        bucket: the error propagates and no KAT verdict is recorded."""
        sk, pub = keys
        beacons = _make_chain(sk, 4)

        def broken(*a, **k):
            raise RuntimeError("wire-rlc lowering failure")

        monkeypatch.setattr(engine, "_wire_rlc_ok", {})
        monkeypatch.setattr(engine, "_probe_errors", {})
        monkeypatch.setattr(engine, "_wire_rlc_jit", broken)
        with pytest.raises(RuntimeError, match="lowering failure"):
            engine.verify_beacons_wire_rlc(pub, beacons)
        assert engine._wire_rlc_ok == {}

    def test_probe_error_is_cached_not_recompiled(self, engine, keys,
                                                  monkeypatch):
        """A probe that raised is not compiled again on the next
        dispatch (each attempt would cost minutes of compile on the
        chip): the same error raises on every call. In auto mode each
        call is still a counted host fallback with a ledger entry."""
        from drand_tpu import metrics

        sk, pub = keys
        beacons = _make_chain(sk, 4)
        calls = []

        def broken(*a, **k):
            calls.append(1)
            raise RuntimeError("wire-rlc lowering failure")

        monkeypatch.setattr(engine, "_wire_rlc_ok", {})
        monkeypatch.setattr(engine, "_probe_errors", {})
        monkeypatch.setattr(engine, "_wire_rlc_jit", broken)
        for _ in range(2):
            with pytest.raises(RuntimeError, match="lowering failure"):
                engine.verify_beacons_wire_rlc(pub, beacons)
        assert len(calls) == 1

        old = (batch._MODE, batch._MIN_BATCH, batch._ENGINE)
        batch.configure("auto", min_batch=1, engine=engine)
        batch.reset_fallback_ledger()
        fallbacks0 = metrics.ENGINE_FALLBACKS._value.get()
        try:
            for _ in range(2):
                assert batch.verify_beacons(pub, beacons).all()
            assert len(calls) == 1
            assert metrics.ENGINE_FALLBACKS._value.get() == fallbacks0 + 2
            led = batch.fallback_ledger()
            assert len(led) == 2
            assert all("lowering failure" in e["reason"] for e in led)
        finally:
            batch._MODE, batch._MIN_BATCH, batch._ENGINE = old
            batch.reset_fallback_ledger()

    def test_kat_mismatch_raises_every_time(self, engine, keys,
                                            monkeypatch):
        """A wrong known answer raises the named error on first use AND
        on every later dispatch — it never re-chunks to another bucket
        or drops to a slower tier in silence."""
        from drand_tpu.ops.engine import KnownAnswerMismatch

        sk, pub = keys
        beacons = _make_chain(sk, 4)
        monkeypatch.setattr(engine, "_wire_rlc_ok", {})
        monkeypatch.setattr(engine, "_wire_rlc_kat_probe",
                            lambda b, sharded: False)
        for _ in range(2):
            with pytest.raises(KnownAnswerMismatch, match="bucket 4"):
                engine.verify_beacons_wire_rlc(pub, beacons)
        assert engine._wire_rlc_ok == {4: False}

    def test_escape_hatch_disables_wire_rlc(self, engine, monkeypatch):
        monkeypatch.setenv("DRAND_TPU_BATCH_VERIFY", "0")
        assert engine.wire_rlc_active(64) is False
        monkeypatch.delenv("DRAND_TPU_BATCH_VERIFY", raising=False)
        assert engine.wire_rlc_active(64) is True
        assert engine.wire_rlc_active(1) is False  # under the floor

    def test_dispatch_times_wire_rlc_path(self, engine, keys, monkeypatch):
        """crypto/batch.py dispatches the tier under its own
        engine_op_seconds{path="wire_rlc"} label (check_metrics lints the
        label into the documented set)."""
        sk, pub = keys
        beacons = _make_chain(sk, 4)
        monkeypatch.delenv("DRAND_TPU_BATCH_VERIFY", raising=False)
        old = (batch._MODE, batch._MIN_BATCH, batch._ENGINE)
        batch.configure("device", engine=engine)
        try:
            out = batch.verify_beacons(pub, beacons)
            assert out.all() and len(out) == 4
            # the first dispatch of a cold (op, wire_rlc, bucket) shape
            # lands in engine_compile_seconds (ISSUE 6 split); the shape
            # is warm now, so the next dispatch samples the path label
            h1 = _sample_count(metrics.REGISTRY, "engine_op_seconds",
                               op="verify_beacons", path="wire_rlc")
            out = batch.verify_beacons(pub, beacons)
            assert out.all() and len(out) == 4
            assert _sample_count(metrics.REGISTRY, "engine_op_seconds",
                                 op="verify_beacons",
                                 path="wire_rlc") == h1 + 1
        finally:
            batch._MODE, batch._MIN_BATCH, batch._ENGINE = old
