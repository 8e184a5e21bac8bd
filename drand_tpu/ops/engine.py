"""Batched device crypto engine — the TPU execution path of the Scheme.

This is the component the whole build exists for (BASELINE.json north star):
the reference's per-round sequential crypto hot calls —
``Scheme.VerifyPartial`` (chain/beacon/node.go:112), ``Scheme.Recover`` +
``VerifyRecovered`` (chain/beacon/chain.go:136-141), and the chain-catchup
verifier (client/verify.go:146-163) — become batched device computations:

- ``verify_bls``: one jitted multi-pairing graph checks a whole tensor of
  (pubkey, signature, message) triples at once.
- ``verify_beacons``: dual V1+V2 beacon verification for a span of rounds,
  flattened into one such tensor.
- ``verify_partials``: all of a round's partials against their per-index
  public key shares in one call.
- ``recover``: Lagrange interpolation of the full signature as a device MSM
  over the partials (the ``Scheme.Recover`` analogue).

Batch shapes are bucketed (padded up to a small set of sizes) so the number
of XLA compilations is bounded; compiled executables are reused across
calls and persisted via the compilation cache (utils/jit_cache.py).

Host-side preparation (SHA-256 message expansion, point decompression,
hash-to-curve) currently runs on the host reference implementation; the
engine interface takes wire-format bytes so the prep can migrate on-device
without touching callers.
"""

from __future__ import annotations

import functools
import os
import threading

import numpy as np
import jax
import jax.numpy as jnp

from ..crypto import batch_verify, tbls
from ..crypto.curves import PointG1, PointG2
from ..crypto.fields import P, R
from ..crypto.hash_to_curve import DEFAULT_DST_G2, hash_to_g2
from ..crypto.poly import PubPoly, PubShare, lagrange_coefficients
from . import curve, limb, pairing, pallas_eval, tower

# Each bucket is one XLA compilation of the pairing graph (minutes on a
# cold cache) — keep the set small. Batches above the top bucket split
# into multiple top-bucket calls.
#
# Buckets >= PALLAS_MIN_BUCKET run the fused batch-last Pallas path
# (ops/pallas_pairing.py — Mosaic-compiled kernels, per-kernel fusion);
# smaller buckets run the XLA graph (ops/pairing.py), whose per-op
# dispatch the Pallas path removes. Every bucket is known-answer-validated
# before first use; a mismatch raises KnownAnswerMismatch.
DEFAULT_BUCKETS = (4, 128, 512)
PALLAS_MIN_BUCKET = int(os.environ.get("DRAND_TPU_PALLAS_MIN", "32"))
# wire-prep kernels hold more live state per lane (decompress + h2c +
# pairing); cap their bucket size — larger batches chunk and pipeline
WIRE_MAX_BUCKET = 128

# Device-side randomized batch verification (RLC — crypto/batch_verify.py
# documents the scheme and its soundness): the batched verify graphs
# collapse a span's 2N Miller loops into 2 by combining the span on
# device with the same MSM machinery recovery uses. Scalars are 128-bit,
# per-call, from the host CSPRNG. Lane counts are bucketed (one compile
# per bucket), and spans below ENGINE_RLC_MIN keep the per-item graphs
# (one dispatch either way; the per-shape compile isn't worth it).
# The WIRE tier folds the same combination into the wire pipeline
# (verify_wire_rlc): device hash-to-curve + decompression feed an
# in-graph lane MSM, so catch-up costs 2 Miller loops end-to-end with no
# host hashing either — dispatched by crypto/batch.py under
# engine_op_seconds{path="wire_rlc"} with false-reject-only fallback to
# the per-item wire graph. On a mesh engine the combine additionally
# SHARDS over the batch axis (per-shard h2c + decompression + lane-MSM,
# one cross-shard reduction before the single pairing row) under
# path="wire_rlc_sharded" — N shards of MSM work, still exactly one
# product check per span.
RLC_NBITS = batch_verify.RLC_SCALAR_BITS
RLC_LANE_BUCKETS = (8, 32, 128, 512)
ENGINE_RLC_MIN = int(os.environ.get("DRAND_TPU_ENGINE_RLC_MIN", "8"))

# Device pairing-row meter — the device twin of crypto/pairing.py's
# N_PRODUCT_CHECKS/N_MILLER_PAIRS: every row of a dispatched verify
# graph is one 2-pairing product check executed on device, so tests and
# bench can PROVE Miller-loop claims ("an all-valid wire_rlc catch-up
# span costs exactly 2 Miller pairs, was 2N") without monkeypatching
# graphs. Counted at the public dispatch entrypoints only; known-answer
# probes go through the internal launchers and are not counted.
N_PRODUCT_CHECKS = 0   # verify-graph dispatches
N_MILLER_PAIRS = 0     # 2 x requested rows across those dispatches


def _meter_rows(n: int) -> None:
    global N_PRODUCT_CHECKS, N_MILLER_PAIRS
    N_PRODUCT_CHECKS += 1
    N_MILLER_PAIRS += 2 * n


def _meter_gt_rows(n: int) -> None:
    """One batched GT (pairing-value) dispatch of n single-pair rows —
    the timelock round-open graph (one Miller pair per lane, not the
    verify tiers' two). Counted once per public dispatch like
    _meter_rows, so tests can prove "K ciphertexts opened in ONE
    dispatch" from the same meters."""
    global N_PRODUCT_CHECKS, N_MILLER_PAIRS
    N_PRODUCT_CHECKS += 1
    N_MILLER_PAIRS += n


def _drain(launches) -> np.ndarray:
    """Collect per-bucket outputs with ONE device-side stack and ONE
    host transfer — one sync for the whole batch instead of one per
    bucket. Returns the stacked (n_buckets, b) bool array."""
    devs = [dev for dev, _, _ in launches]
    if len(devs) == 1:
        return np.asarray(devs[0])[None]
    return np.asarray(jnp.stack(devs))


def _running(launched) -> bool:
    """True while the device is still computing any output of a
    ``BatchedEngine._launch_wire_chunk`` result (asks, never waits)."""
    _, outs = launched
    return not all(o.is_ready() for o in outs)


def shard_map_unchecked(f, **kw):
    """``jax.shard_map`` with the varying-axes checker off (a post-gather
    fold makes every device compute the identical total, which the
    checker can't infer). Shared by the engine's sharded wire-RLC
    combine and the driver's mesh dryrun."""
    return jax.shard_map(f, check_vma=False, **kw)


class KnownAnswerMismatch(RuntimeError):
    """A compiled device graph gave a wrong answer on its known-answer
    probe. Raised, never turned into a disabled bucket: a wrong
    executable is a fault to surface, not a shape to route around."""


def _kat(ok: bool, what: str) -> bool:
    if not ok:
        raise KnownAnswerMismatch(f"{what} failed its known-answer test")
    return True


def _bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


# ---------------------------------------------------------------------------
# Host-side packing: wire/host objects -> mont-domain limb arrays
# ---------------------------------------------------------------------------

def _g1_xy(xy) -> np.ndarray:
    x, y = xy
    return np.stack([limb.int_to_mont_limbs(x.v), limb.int_to_mont_limbs(y.v)])


def _g2_xy(xy) -> np.ndarray:
    x, y = xy
    return np.stack([
        np.stack([limb.int_to_mont_limbs(x.c0), limb.int_to_mont_limbs(x.c1)]),
        np.stack([limb.int_to_mont_limbs(y.c0), limb.int_to_mont_limbs(y.c1)]),
    ])


def _g1_aff(p: PointG1) -> np.ndarray:
    return _g1_xy(p.to_affine())


def _g2_aff(q: PointG2) -> np.ndarray:
    return _g2_xy(q.to_affine())


class BatchedEngine:
    """Stateful facade: owns the jitted graphs and the shape buckets."""

    # recovery thresholds at or above this size use the Pippenger MSM
    # (windowed buckets, log-depth tree reduction) instead of the
    # interleaved ladder — the ladder's depth grows linearly with t
    PIPPENGER_MIN_T = 16

    def __init__(self, buckets=DEFAULT_BUCKETS,
                 wire_prep: bool | None = None, mesh=None,
                 pallas_only: bool | None = None):
        """``mesh``: an optional 1-axis ``jax.sharding.Mesh``; verify
        batches are sharded over the batch axis (data parallel over
        rounds — SURVEY §5: the chain-catchup verifier sharded across
        chips with pjit; buckets that don't divide the mesh pad up to
        it). The same pattern the driver's dryrun_multichip validates.
        A mesh also arms the SHARDED wire-RLC tier: per-shard device
        h2c + decompression + lane-MSM with one cross-shard reduction
        before the single pairing row (see verify_wire_rlc).

        ``pallas_only`` (default: the backend is a TPU) is the dispatch
        policy, decided here once: only Mosaic (Pallas) programs run. An
        XLA limb graph (pairing, MSM, Horner) compiles for minutes per
        shape on a v5e — the bucket-4 pairing graph took 40.5 s to trace
        and lower and 572.5 s to compile for a described v5e (PERF.md,
        PR 21) — so the pairing buckets below PALLAS_MIN_BUCKET
        go (small batches pad up to the 128-lane Pallas bucket), spans
        take the wire tiers, the host-hashed RLC combine and the device
        Horner for share pubkeys are skipped, and recovery MSMs pad to
        the Mosaic kernel's lanes. Mosaic compiles only for a TPU: off,
        every tier runs its XLA graph."""
        self.buckets = tuple(sorted(buckets))
        self.mesh = mesh
        if pallas_only is None:
            pallas_only = jax.default_backend() == "tpu"
        self.pallas_only = pallas_only
        self._pairing_bucket_set = (
            (tuple(b for b in self.buckets if b >= PALLAS_MIN_BUCKET)
             or self.buckets[-1:]) if pallas_only else self.buckets)
        self._verify = jax.jit(pairing.verify_prepared)
        self._verify_sharded = None
        self._wire_rlc_sharded_jit = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            axis = mesh.axis_names[0]
            shard = NamedSharding(mesh, P(axis))
            self._mesh_size = mesh.devices.size
            self._verify_sharded = jax.jit(
                pairing.verify_prepared,
                in_shardings=(shard, shard, shard), out_shardings=shard)
            self._wire_rlc_sharded_jit = self._make_wire_rlc_sharded()
        self._msm_g2 = jax.jit(
            lambda pts, bits: curve.pt_to_affine(
                curve.F2, curve.msm(curve.F2, pts, bits)))
        self._msm_g2_pip = jax.jit(
            lambda pts, bits: curve.pt_to_affine(
                curve.F2, curve.msm_pippenger(curve.F2, pts, bits)))
        self._msm_g2_lanes = jax.jit(
            lambda pts, bits: curve.pt_to_affine(
                curve.F2, curve.msm_lanes(curve.F2, pts, bits)))
        self._msg_cache: dict[tuple[bytes, bytes], PointG2] = {}
        # wire-prep: hash-to-curve + decompression + subgroup checks run
        # on the DEVICE (Pallas kernels at bucket >= PALLAS_MIN_BUCKET,
        # the XLA graph below) instead of ~60ms/item of host Python — the
        # catch-up throughput fix. DRAND_TPU_WIRE_PREP: "auto" (default,
        # wire path for batches that reach the Pallas bucket), "1"
        # (always), "0" (never).
        if wire_prep is None:
            mode = os.environ.get("DRAND_TPU_WIRE_PREP", "auto")
            wire_prep = {"auto": None, "1": True, "0": False}.get(mode)
        self.wire_prep = wire_prep
        self._verify_wire = jax.jit(self._wire_graph)
        # Known-answer validation per bucket: every graph family is
        # self-checked against the host oracle on first use of a shape;
        # a wrong answer raises KnownAnswerMismatch (never re-chunks).
        self._probe_lock = threading.Lock()
        self._probing: dict[tuple, threading.Event] = {}
        self._probe_errors: dict[tuple, tuple[dict, Exception]] = {}
        self._bucket_ok: dict[int, bool] = {}
        self._wire_ok: dict[int, bool] = {}
        self._eval_ok: dict[tuple[int, int], bool] = {}
        self._poly_eval_ok: dict[tuple[int, int], bool] = {}
        # keyed (pairing bucket, msm lanes, msm scalar bits) — GLS4 and
        # full-width aggregates compile different executables per shape
        self._agg_ok: dict[tuple[int, int, int], bool] = {}
        # on the Pallas path the fused round runs eagerly: its Mosaic
        # stages are their own (shared) programs, the glue a few tiny ones
        self._agg = (functools.partial(self._agg_graph, pallas=True)
                     if pallas_only else jax.jit(
                         functools.partial(self._agg_graph, pallas=False)))
        # RLC fast paths: per-shape KAT cache + jitted graphs. rlc_min /
        # rlc_lane_buckets are instance attrs so tests can shrink them.
        self.rlc_min = ENGINE_RLC_MIN
        self.rlc_lane_buckets = RLC_LANE_BUCKETS
        self._rlc_ok: dict[tuple, bool] = {}
        self._rlc_g2g2_jit = jax.jit(self._rlc_combine_g2g2_graph)
        self._rlc_g1g2_jit = jax.jit(self._rlc_combine_g1g2_graph)
        # wire-RLC: the combine runs AFTER device hash-to-curve, so a
        # catch-up span needs no host hashing at all (see verify_wire_rlc)
        self._wire_rlc_ok: dict[int, bool] = {}
        self._wire_rlc_jit = jax.jit(self._wire_rlc_graph)
        self._wire_rlc_sharded_ok: dict[int, bool] = {}
        # timelock round-open: batched canonical-GT pairings against ONE
        # shared (pre-folded) G2 point — the round's V2 signature; the K
        # varying U points ride the batch axis (crypto/timelock.py
        # documents the shared-signature structure and the 3^-1 fold)
        self._tl_ok: dict[int, bool] = {}
        self._tl_jit = jax.jit(self._tl_graph)
        # GLS ψ² 4-D scalar split for the recovery/aggregation MSMs:
        # 255-bit Lagrange scalars become four <= 64-bit digit lanes on
        # (P, -ψP, ψ²P, -ψ³P) (crypto/endo.py), quartering the device
        # ladder scan. DRAND_TPU_GLS4=0 reverts to full-width ladders.
        self.gls4 = os.environ.get("DRAND_TPU_GLS4", "1") != "0"

    @staticmethod
    def _wire_graph(pub_aff, sig_x, sig_sign, u_pairs):
        """Fully-device verification from wire-format inputs: decompress +
        subgroup-check the signatures, hash the messages to G2, run the
        batched pairing check."""
        from . import h2c

        sig_pt, on_curve = h2c.decompress_g2_device(sig_x, sig_sign)
        in_subgroup = h2c.subgroup_check_g2(sig_pt)
        msg_pt = h2c.hash_to_g2_device(u_pairs)
        mx, my, _ = curve.pt_to_affine(curve.F2, msg_pt)
        sig_aff = jnp.stack([sig_pt[0], sig_pt[1]], axis=-3)
        msg_aff = jnp.stack([mx, my], axis=-3)
        ok = pairing.verify_prepared(pub_aff, sig_aff, msg_aff)
        return ok & on_curve & in_subgroup

    @staticmethod
    def _wire_rlc_graph(sig_x, sig_sign, u_pairs, live, bits):
        """The wire-RLC combine from wire-format inputs, entirely on
        device: decompress + subgroup-check the signatures, hash the
        messages to G2, then collapse the bucket to (Σc·sig, Σc·H(m))
        with two lane MSMs sharing the scalar vector. Lanes that fail
        decode, hash to infinity, or are padding (``live`` false) are
        masked to infinity in BOTH MSMs, so a bad encoding never poisons
        the combination — it is simply reported False in ``ok``. Returns
        (ok, sx, sy, sinf, mx, my, minf); the combined pair feeds the
        ordinary KAT-gated verify_bls pairing bucket (2 Miller pairs for
        the whole span)."""
        from . import h2c

        sig_pt, on_curve = h2c.decompress_g2_device(sig_x, sig_sign)
        in_subgroup = h2c.subgroup_check_g2(sig_pt)
        msg_pt = h2c.hash_to_g2_device(u_pairs)
        ok = on_curve & in_subgroup & live & ~msg_pt[3]
        dead = ~ok
        sig_jac = (sig_pt[0], sig_pt[1], sig_pt[2], sig_pt[3] | dead)
        msg_jac = (msg_pt[0], msg_pt[1], msg_pt[2], msg_pt[3] | dead)
        sx, sy, sinf = curve.pt_to_affine(
            curve.F2, curve.msm_lanes(curve.F2, sig_jac, bits))
        mx, my, minf = curve.pt_to_affine(
            curve.F2, curve.msm_lanes(curve.F2, msg_jac, bits))
        return ok, sx, sy, sinf, mx, my, minf

    def _make_wire_rlc_sharded(self):
        """The wire-RLC combine SHARDED over the batch axis of the
        1-axis mesh: every shard runs its own decompress + h2c +
        lane-MSM on b/N lanes, then ONE cross-shard reduction (N-1
        point-adds over the gathered per-shard partial sums) precedes
        the affine conversion — so an N-sharded catch-up span is N
        shards of MSM work and still exactly one pairing row
        downstream. Same output contract as ``_wire_rlc_graph``; the
        per-item ``ok`` mask stays sharded, the combined pair comes
        back replicated."""
        from jax.sharding import PartitionSpec as P

        axis = self.mesh.axis_names[0]
        nsh = self._mesh_size

        def local(sig_x, sig_sign, u_pairs, live, bits):
            from . import h2c

            sig_pt, on_curve = h2c.decompress_g2_device(sig_x, sig_sign)
            in_subgroup = h2c.subgroup_check_g2(sig_pt)
            msg_pt = h2c.hash_to_g2_device(u_pairs)
            ok = on_curve & in_subgroup & live & ~msg_pt[3]
            dead = ~ok
            sig_jac = (sig_pt[0], sig_pt[1], sig_pt[2], sig_pt[3] | dead)
            msg_jac = (msg_pt[0], msg_pt[1], msg_pt[2], msg_pt[3] | dead)
            s_part = curve.msm_lanes(curve.F2, sig_jac, bits)
            m_part = curve.msm_lanes(curve.F2, msg_jac, bits)

            def fold(part):
                # the single cross-shard reduction: gather the N partial
                # sums and fold them on every device (each then holds
                # the identical span total — out_specs P() below)
                gathered = tuple(jax.lax.all_gather(c, axis)
                                 for c in part)
                total = tuple(c[0] for c in gathered)
                for k in range(1, nsh):
                    total = curve.pt_add(
                        curve.F2, total, tuple(c[k] for c in gathered))
                return total

            sx, sy, sinf = curve.pt_to_affine(curve.F2, fold(s_part))
            mx, my, minf = curve.pt_to_affine(curve.F2, fold(m_part))
            return ok, sx, sy, sinf, mx, my, minf

        spec = P(axis)
        return jax.jit(shard_map_unchecked(
            local, mesh=self.mesh,
            in_specs=(spec, spec, spec, spec, spec),
            out_specs=(spec, P(), P(), P(), P(), P(), P())))

    def _wire_rlc_shardable(self, b: int) -> bool:
        """A combine bucket shards iff it divides evenly over the mesh
        with a power-of-two per-shard lane count (the local msm_lanes
        fold needs it); the Mosaic path (TPU) takes precedence — the
        sharded XLA combine targets the virtual CPU mesh and real
        multi-chip data parallelism, not single-chip Pallas."""
        if self.mesh is None or self._pallas_ok(b):
            return False
        if b % self._mesh_size:
            return False
        per_shard = b // self._mesh_size
        return per_shard >= 1 and not (per_shard & (per_shard - 1))

    def _pallas_ok(self, b: int) -> bool:
        """Bucket ``b`` runs its Mosaic (Pallas) program."""
        return self.pallas_only and b >= PALLAS_MIN_BUCKET

    # ---------------------------------------------------- introspection
    def introspect(self) -> dict:
        """JSON-ready snapshot of the engine's runtime state for
        ``GET /debug/engine`` / ``drand util engine`` (ISSUE 6):
        backend/device identity, the bucket configuration, and every
        graph family's per-shape KAT-gate verdicts (True = proven,
        False = the known-answer probe failed and raised; shapes not
        listed were never dispatched). Reading the KAT caches never
        triggers a probe — the report reflects what actually ran."""
        return {
            "backend": jax.default_backend(),
            "devices": [str(d) for d in jax.devices()],
            "mesh": (None if self.mesh is None
                     else {"axes": list(self.mesh.axis_names),
                           "size": int(self.mesh.devices.size)}),
            "buckets": list(self.buckets),
            "wire_buckets": list(self._wire_buckets()),
            "wire_rlc_buckets": list(self._wire_rlc_buckets()),
            "wire_rlc_sharded_buckets": [
                b for b in self._wire_rlc_buckets()
                if self._wire_rlc_shardable(b)],
            "rlc_lane_buckets": list(self.rlc_lane_buckets),
            "rlc_min": self.rlc_min,
            "wire_prep": self.wire_prep,
            "gls4": self.gls4,
            "pallas_min_bucket": PALLAS_MIN_BUCKET,
            "pallas_only": self.pallas_only,
            "kat": {
                "verify": {str(b): ok
                           for b, ok in sorted(self._bucket_ok.items())},
                "wire": {str(b): ok
                         for b, ok in sorted(self._wire_ok.items())},
                "rlc": {f"{kind}/{lanes}": ok for (kind, lanes), ok
                        in sorted(self._rlc_ok.items())},
                "wire_rlc": {str(b): ok for b, ok
                             in sorted(self._wire_rlc_ok.items())},
                "timelock": {str(b): ok for b, ok
                             in sorted(self._tl_ok.items())},
                # shard-shape key: bucket over mesh lanes per shard
                "wire_rlc_sharded": {
                    f"b{b}/m{self._mesh_size}": ok for b, ok
                    in sorted(self._wire_rlc_sharded_ok.items())}
                if self.mesh is not None else {},
                "eval": {f"t{t}/b{b}": ok for (t, b), ok
                         in sorted(self._eval_ok.items())},
                "poly_eval": {f"t{t}/b{b}": ok for (t, b), ok
                              in sorted(self._poly_eval_ok.items())},
                "agg": {f"b{b}/msm{m}/w{nb}": ok for (b, m, nb), ok
                        in sorted(self._agg_ok.items())},
            },
        }

    # -- hashing (host, memoized: the aggregator re-verifies the same round
    #    message for every partial) -----------------------------------------
    def _hash_msg(self, msg: bytes, dst: bytes) -> PointG2:
        key = (msg, dst)
        got = self._msg_cache.get(key)
        if got is None:
            if len(self._msg_cache) > 4096:
                self._msg_cache.clear()
            got = hash_to_g2(msg, dst)
            self._msg_cache[key] = got
        return got

    # ------------------------------------------- RLC batch verification
    # Device version of crypto/batch_verify.py: the span's random linear
    # combination is computed ON DEVICE (G1/G2 MSMs over the lane axis —
    # the same scalar-ladder machinery recovery uses) and the combined
    # row goes through the ordinary KAT-gated verify_bls bucket, so the
    # span costs 2 Miller loops instead of 2N. The combine graph is a
    # SEPARATE jit from the pairing bucket on purpose: a fused
    # MSM+pairing graph is a fresh multi-minute XLA compile per shape,
    # while the composed form reuses the pairing executable every other
    # path already compiled (one extra dispatch, far below the N-2
    # Miller loops it saves on real catch-up spans). A wrong verdict can only be a false REJECT (the
    # per-item fallback then decides); the combine graphs are still
    # KAT-checked against the host MSM before first use.

    @staticmethod
    def _z_one_f2(like):
        return jnp.zeros_like(like).at[:, 0, :].set(
            jnp.asarray(limb.ONE_MONT))

    @staticmethod
    def _rlc_combine_g2g2_graph(ax, ay, ainf, bx, by, binf, bits):
        """Two G2 MSMs sharing one scalar vector: (Σc·A_i, Σc·B_i) —
        the sig/message combination of a one-key-many-messages span."""
        z2 = BatchedEngine._z_one_f2(ax)
        a_pt = curve.msm_lanes(curve.F2, (ax, ay, z2, ainf), bits)
        b_pt = curve.msm_lanes(curve.F2, (bx, by, z2, binf), bits)
        axa, aya, a_inf = curve.pt_to_affine(curve.F2, a_pt)
        bxa, bya, b_inf = curve.pt_to_affine(curve.F2, b_pt)
        return axa, aya, a_inf, bxa, bya, b_inf

    @staticmethod
    def _rlc_combine_g1g2_graph(px, py, pinf, sx, sy, sinf, bits):
        """G1 MSM + G2 MSM sharing one scalar vector: (Σc·pk_i, Σc·sig_i)
        — the key/sig combination of a one-message-many-keys span."""
        one = jnp.asarray(limb.ONE_MONT)
        z1 = jnp.broadcast_to(one, px.shape)
        z2 = BatchedEngine._z_one_f2(sx)
        k_pt = curve.msm_lanes(curve.F1, (px, py, z1, pinf), bits)
        s_pt = curve.msm_lanes(curve.F2, (sx, sy, z2, sinf), bits)
        kx, ky, k_inf = curve.pt_to_affine(curve.F1, k_pt)
        sxa, sya, s_inf = curve.pt_to_affine(curve.F2, s_pt)
        return kx, ky, k_inf, sxa, sya, s_inf

    def _rlc_wanted(self, n: int) -> bool:
        """Same escape hatch as the host dispatch (DRAND_TPU_BATCH_VERIFY)
        plus the engine's own floor."""
        from ..crypto.batch import _rlc_threshold

        thr = _rlc_threshold()
        return thr is not None and n >= max(thr, self.rlc_min)

    def _combine_rlc_wanted(self, n: int) -> bool:
        """The host-hashed RLC combine runs XLA limb MSM graphs: off on
        the Pallas path, where catch-up takes the wire-RLC tier."""
        return self._rlc_wanted(n) and not self.pallas_only

    def _use_wire(self, n_checks: int) -> bool:
        """Wire-prep policy: explicit DRAND_TPU_WIRE_PREP, else every
        span on the Pallas path (its wire buckets are Mosaic) and spans
        that reach the Pallas bucket elsewhere."""
        if self.wire_prep is not None:
            return self.wire_prep
        return n_checks >= PALLAS_MIN_BUCKET or self.pallas_only

    def _rlc_lanes(self, n: int) -> int | None:
        for b in self.rlc_lane_buckets:
            if n <= b:
                return b
        return None

    @staticmethod
    def _pack_rlc_bits(scalars, lanes: int) -> np.ndarray:
        bits = np.zeros((lanes, RLC_NBITS), np.int32)
        for i, c in enumerate(scalars):
            bits[i] = curve.scalar_to_bits(c, RLC_NBITS)
        return bits

    @staticmethod
    def _pack_rlc_g2(pts, lanes: int):
        pad = _g2_aff(PointG2.generator())
        arr = np.broadcast_to(pad, (lanes, 2, 2, limb.NLIMBS)).copy()
        inf = np.ones(lanes, dtype=bool)
        for i, xy in enumerate(PointG2.batch_to_affine(pts)):
            arr[i] = _g2_xy(xy)
            inf[i] = False
        return arr[:, 0], arr[:, 1], inf

    @staticmethod
    def _pack_rlc_g1(pts, lanes: int):
        pad = _g1_aff(PointG1.generator())
        arr = np.broadcast_to(pad, (lanes, 2, limb.NLIMBS)).copy()
        inf = np.ones(lanes, dtype=bool)
        for i, xy in enumerate(PointG1.batch_to_affine(pts)):
            arr[i] = _g1_xy(xy)
            inf[i] = False
        return arr[:, 0], arr[:, 1], inf

    def _combine_g2g2(self, a_pts, b_pts, cs, lanes: int):
        """One combine dispatch: (Σc·a_i, Σc·b_i) as host PointG2s, or
        None when either combination is degenerate (infinity — never for
        honest inputs except with ~2^-128 probability)."""
        bits = self._pack_rlc_bits(cs, lanes)
        ax, ay, ainf = self._pack_rlc_g2(a_pts, lanes)
        bx, by, binf = self._pack_rlc_g2(b_pts, lanes)
        out = self._rlc_g2g2_jit(
            jnp.asarray(ax), jnp.asarray(ay), jnp.asarray(ainf),
            jnp.asarray(bx), jnp.asarray(by), jnp.asarray(binf),
            jnp.asarray(bits))
        axa, aya, a_inf, bxa, bya, b_inf = (np.asarray(c) for c in out)
        if bool(a_inf) or bool(b_inf):
            return None
        return _g2_from_affine_dev(axa, aya), _g2_from_affine_dev(bxa, bya)

    def _combine_g1g2(self, pk_pts, sig_pts, cs, lanes: int):
        """One combine dispatch: (Σc·pk_i on G1, Σc·sig_i on G2)."""
        bits = self._pack_rlc_bits(cs, lanes)
        px, py, pinf = self._pack_rlc_g1(pk_pts, lanes)
        sx, sy, sinf = self._pack_rlc_g2(sig_pts, lanes)
        out = self._rlc_g1g2_jit(
            jnp.asarray(px), jnp.asarray(py), jnp.asarray(pinf),
            jnp.asarray(sx), jnp.asarray(sy), jnp.asarray(sinf),
            jnp.asarray(bits))
        kx, ky, k_inf, sxa, sya, s_inf = (np.asarray(c) for c in out)
        if bool(k_inf) or bool(s_inf):
            return None
        return _g1_from_affine_dev(kx, ky), _g2_from_affine_dev(sxa, sya)

    def _check_rlc(self, kind: str, lanes: int) -> bool:
        """KAT one combine shape against the host MSM on fixed points and
        scalars. A miscompiled combine can only produce a false REJECT
        downstream (the pairing row is the separately-KAT-gated
        verify_bls bucket, and a wrong combined point fails it), so this
        gate protects the fast path's usefulness, not soundness."""
        key = (kind, lanes)
        def probe():
            g2 = PointG2.generator()
            cs = [5, 7]
            a = [g2.mul(2), g2.mul(3)]
            if kind == "g2g2":
                b = [g2.mul(9), g2.mul(4)]
                got = self._combine_g2g2(a, b, cs, lanes)
                ok = (got is not None
                      and got[0] == g2.mul(2 * 5 + 3 * 7)
                      and got[1] == g2.mul(9 * 5 + 4 * 7))
            else:
                g1 = PointG1.generator()
                pks = [g1.mul(2), g1.mul(3)]
                got = self._combine_g1g2(pks, a, cs, lanes)
                ok = (got is not None
                      and got[0] == g1.mul(2 * 5 + 3 * 7)
                      and got[1] == g2.mul(2 * 5 + 3 * 7))
            return ok

        return self._verdict(self._rlc_ok, key, probe,
                             f"rlc combine {kind}/{lanes}")

    def _combine_span(self, kind: str, xs, ys):
        """Combine a whole span (chunked over the top lane bucket, chunk
        sums added on host): (combined_x, combined_y) host points, or
        None when a shape is untrusted or a combination degenerates."""
        n = len(xs)
        cs = batch_verify.rlc_scalars(n)
        fn = self._combine_g2g2 if kind == "g2g2" else self._combine_g1g2
        acc_x = acc_y = None
        top = self.rlc_lane_buckets[-1]
        for lo in range(0, n, top):
            hi = min(lo + top, n)
            lanes = self._rlc_lanes(hi - lo)
            if lanes is None:
                return None
            self._check_rlc(kind, lanes)
            got = fn(xs[lo:hi], ys[lo:hi], cs[lo:hi], lanes)
            if got is None:
                return None
            acc_x = got[0] if acc_x is None else acc_x + got[0]
            acc_y = got[1] if acc_y is None else acc_y + got[1]
        if acc_x.is_infinity() or acc_y.is_infinity():
            return None
        return acc_x, acc_y

    def _rlc_verify_beacons(self, pubkey: PointG1, beacons,
                            dst: bytes) -> np.ndarray | None:
        """RLC fast path for a span of beacons: per-beacon bool array
        when the all-valid 2-pairing check lands, None to fall back to
        the per-item graphs (some check failed)."""
        if pubkey.is_infinity():
            return None
        from ..chain import beacon as chain_beacon

        ok_mask = np.ones(len(beacons), dtype=bool)
        sig_pts, msg_pts = [], []
        for i, bcn in enumerate(beacons):
            checks = [(chain_beacon.message(bcn.round, bcn.previous_sig),
                       bcn.signature)]
            if bcn.is_v2():
                checks.append((chain_beacon.message_v2(bcn.round),
                               bcn.signature_v2))
            pts = [batch_verify.decode_sig(s) for _, s in checks]
            if any(p is None for p in pts):
                ok_mask[i] = False  # per-item reject, never combined
                continue
            sig_pts.extend(pts)
            msg_pts.extend(self._hash_msg(m, dst) for m, _ in checks)
        if not sig_pts:
            return ok_mask  # nothing decodable: every beacon already False
        comb = self._combine_span("g2g2", sig_pts, msg_pts)
        if comb is None:
            return None
        s_comb, m_comb = comb
        if bool(self.verify_bls([(pubkey, s_comb, m_comb)])[0]):
            return ok_mask
        return None

    def _rlc_verify_partials(self, pub_poly: PubPoly, msg: bytes, partials,
                             dst: bytes) -> list[bool] | None:
        msg_pt = self._hash_msg(msg, dst)
        if msg_pt.is_infinity():
            return None
        got = self._rlc_partials_comb(pub_poly, msg_pt, partials)
        if got is None:
            return None
        mask, k_comb, s_comb = got
        if bool(self.verify_bls([(k_comb, s_comb, msg_pt)])[0]):
            return [bool(v) for v in mask]
        return None

    def _rlc_partials_comb(self, pub_poly: PubPoly, msg_pt: PointG2,
                           partials):
        """Shared prefilter+combine of a round's partials: (wellformed
        mask, Σc·pk, Σc·sig) or None."""
        pubkeys = self._share_pubkeys(pub_poly, partials)
        mask = np.zeros(len(partials), dtype=bool)
        pk_pts, sig_pts = [], []
        for i, (p, pk) in enumerate(zip(partials, pubkeys)):
            if pk is None or pk.is_infinity():
                continue
            pt = batch_verify.decode_sig(p[tbls.INDEX_BYTES:])
            if pt is None:
                continue
            mask[i] = True
            pk_pts.append(pk)
            sig_pts.append(pt)
        if not sig_pts:
            return None
        comb = self._combine_span("g1g2", pk_pts, sig_pts)
        if comb is None:
            return None
        return mask, comb[0], comb[1]

    # ------------------------------------------------------------ verify
    # -------------------------------------------------- bucket validation
    def _verdict(self, cache: dict, key, probe, what: str) -> bool:
        """The cached known-answer verdict for one graph shape, probed
        once per engine: a thread that finds the same shape's probe in
        flight waits for it — and the compile it carries — instead of
        compiling the shape again (``drand start``'s warm_round_path
        races the first round's aggregation this way). A wrong answer
        raises KnownAnswerMismatch; a probe that raised (a compile or
        lowering error) raises the same error on every later call
        without compiling again."""
        slot = (id(cache), key)
        while True:
            with self._probe_lock:
                ok = cache.get(key)
                err = self._probe_errors.get(slot, (None, None))[1]
                inflight = self._probing.get(slot)
                mine = ok is None and err is None and inflight is None
                if mine:
                    inflight = self._probing[slot] = threading.Event()
            if ok is not None:
                return _kat(ok, what)
            if err is not None:
                raise err
            if not mine:
                inflight.wait()
                continue  # the verdict or the error is cached now
            try:
                ok = probe()
            except Exception as e:
                with self._probe_lock:
                    # holds the cache too, so its id is never reused
                    self._probe_errors[slot] = (cache, e)
                raise
            else:
                with self._probe_lock:
                    cache[key] = ok
            finally:
                with self._probe_lock:
                    del self._probing[slot]
                inflight.set()
            return _kat(ok, what)

    def _known_answer_triples(self):
        from ..crypto import bls

        sk = 0x5A17
        pub = PointG1.generator().mul(sk)
        m_ok, m_bad = b"engine-bucket-check-ok", b"engine-bucket-check-bad"
        sig_ok = PointG2.from_bytes(bls.sign(sk, m_ok), subgroup_check=False)
        return [(pub, sig_ok, self._hash_msg(m_ok, DEFAULT_DST_G2)),
                (pub, sig_ok, self._hash_msg(m_bad, DEFAULT_DST_G2))]

    def _check_bucket(self, b: int) -> bool:
        def probe():
            triples = self._known_answer_triples()
            if b == 1:  # one row per call
                out = np.concatenate([self._run_bucket(triples[:1], 1),
                                      self._run_bucket(triples[1:], 1)])
                ok = bool(out[0]) and not bool(out[1])
            else:
                dev, valid, _ = self._launch_bucket(triples, b)
                full = np.asarray(dev)
                # Rows 0/1 are the positive/negative probes; every pad row
                # is the deterministic generator triple, which verifies
                # True — a miscompile can be lane-dependent, so ALL lanes
                # must match, not just the probe lanes.
                ok = (bool(full[0]) and not bool(full[1])
                      and bool(full[2:].all()) and bool(valid[:2].all()))
            return ok

        return self._verdict(self._bucket_ok, b, probe,
                             f"verify bucket {b}")

    def _checked_bucket(self, n: int, check=None, buckets=None) -> int:
        """Smallest bucket >= n, else the largest (the caller chunks),
        known-answer-checked on first use — a mismatch raises
        KnownAnswerMismatch instead of re-chunking to another bucket.
        Pairing-graph buckets by default (_pairing_buckets)."""
        check = check or self._check_bucket
        b = _bucket(n, buckets if buckets is not None
                    else self._pairing_buckets())
        check(b)
        return b

    def _pairing_buckets(self):
        """The pairing-graph buckets: Pallas sizes only on the Pallas
        path (see __init__); the XLA graph runs at any size."""
        return self._pairing_bucket_set

    def verify_bls(self, triples) -> np.ndarray:
        """Batch-verify BLS triples ``(pub: PointG1, sig: PointG2|None,
        msg_point: PointG2)``; a None signature marks an entry already known
        invalid (failed decode). Returns a bool array of len(triples).

        Batches beyond the largest bucket are dispatched as multiple
        ASYNC device calls and drained with a single tail sync (see
        _drain)."""
        n = len(triples)
        if n == 0:
            return np.zeros(0, dtype=bool)
        b = self._checked_bucket(n)
        _meter_rows(n)
        launches = [self._launch_bucket(triples[i:i + b], b)
                    for i in range(0, n, b)]
        stacked = _drain(launches)
        return np.concatenate([(stacked[j] & valid)[:c]
                               for j, (_, valid, c) in enumerate(launches)])

    def _launch_bucket(self, triples, b: int):
        """Dispatch one padded bucket; returns (device_out, valid, count)
        WITHOUT synchronizing — callers drain all launches at once.

        On a mesh engine the bucket pads UP to the next mesh multiple
        (extra generator rows masked out via ``valid``, the same trick
        the wire-RLC combine uses for bad lanes) so the sharded
        executable always engages — a bucket that doesn't divide the
        mesh used to drop silently to a single device."""
        n = len(triples)
        if self.mesh is not None and b % self._mesh_size:
            b = -(-b // self._mesh_size) * self._mesh_size
        pubs = np.zeros((b, 2, limb.NLIMBS), np.int32)
        sigs = np.zeros((b, 2, 2, limb.NLIMBS), np.int32)
        msgs = np.zeros((b, 2, 2, limb.NLIMBS), np.int32)
        valid = np.zeros(b, dtype=bool)
        # pad rows must be well-formed non-infinity points: use g1/g2 bases
        pad_pub, pad_g2 = _g1_aff(PointG1.generator()), _g2_aff(PointG2.generator())
        pubs[:], sigs[:], msgs[:] = pad_pub, pad_g2, pad_g2
        # one simultaneous inversion for every point in the bucket (the
        # per-point to_affine inverse dominates host packing otherwise)
        rows, g1s, g2s = [], [], []
        for i, (pub, sig, msg_pt) in enumerate(triples):
            if sig is None or sig.is_infinity() or pub.is_infinity() \
                    or msg_pt.is_infinity():
                continue
            rows.append(i)
            g1s.append(pub)
            g2s.append(sig)
            g2s.append(msg_pt)
        g1_xy = PointG1.batch_to_affine(g1s)
        g2_xy = PointG2.batch_to_affine(g2s)
        for j, i in enumerate(rows):
            pubs[i] = _g1_xy(g1_xy[j])
            sigs[i] = _g2_xy(g2_xy[2 * j])
            msgs[i] = _g2_xy(g2_xy[2 * j + 1])
            valid[i] = True
        sharded = self.mesh is not None  # b is a mesh multiple by now
        if self._pallas_ok(b):
            from . import pallas_pairing

            if sharded and (b // self._mesh_size) % \
                    pallas_pairing.GRID_BLOCK == 0:
                ok = pallas_pairing.verify_prepared_pl_sharded(
                    pubs, sigs, msgs, self.mesh)
            else:
                ok = pallas_pairing.verify_prepared_pl(pubs, sigs, msgs)
        elif sharded:
            ok = self._verify_sharded(jnp.asarray(pubs), jnp.asarray(sigs),
                                      jnp.asarray(msgs))
        else:
            ok = self._verify(jnp.asarray(pubs), jnp.asarray(sigs),
                              jnp.asarray(msgs))
        return ok, valid, n

    def _run_bucket(self, triples, b: int) -> np.ndarray:
        dev, valid, n = self._launch_bucket(triples, b)
        return (np.asarray(dev) & valid)[:n]

    def verify_beacons(self, pubkey: PointG1, beacons,
                       dst: bytes = DEFAULT_DST_G2, *,
                       try_wire_rlc: bool = True) -> np.ndarray:
        """Dual-verify a span of beacons (V1 chain message + V2 when present)
        in one flattened batch — the chain-catchup hot path
        (client/verify.go:146-163 made parallel). Returns per-beacon bools.

        ``try_wire_rlc=False`` skips the wire-RLC fast path — used by the
        crypto/batch.py dispatcher, which attempts that tier itself under
        its own ``engine_op_seconds{path="wire_rlc"}`` label so a clean
        fallback doesn't pay the combine dispatch twice."""
        from ..chain import beacon as chain_beacon

        n_checks = sum(1 + (1 if bcn.is_v2() else 0) for bcn in beacons)
        if self._use_wire(n_checks):
            if try_wire_rlc and self._rlc_wanted(n_checks):
                got = self.verify_beacons_wire_rlc(pubkey, beacons, dst)
                if got is not None:
                    return got
            checks = []  # (msg_bytes, sig_bytes)
            spans = []
            for bcn in beacons:
                start = len(checks)
                checks.append((chain_beacon.message(bcn.round,
                                                    bcn.previous_sig),
                               bcn.signature))
                if bcn.is_v2():
                    checks.append((chain_beacon.message_v2(bcn.round),
                                   bcn.signature_v2))
                spans.append((start, len(checks) - start))
            flat = self.verify_wire(pubkey, checks, dst)
            return np.array([bool(flat[s:s + c].all()) for s, c in spans])
        if self._combine_rlc_wanted(n_checks):
            # RLC fast path: the whole span as 2 Miller loops; a failed
            # (or untrusted) combination falls through to the per-item
            # triples graph for exact per-beacon verdicts
            got = self._rlc_verify_beacons(pubkey, beacons, dst)
            if got is not None:
                return got
        triples = []
        spans = []  # (start, count) per beacon
        for bcn in beacons:
            start = len(triples)
            msg = chain_beacon.message(bcn.round, bcn.previous_sig)
            triples.append((pubkey, _decode_sig(bcn.signature),
                            self._hash_msg(msg, dst)))
            if bcn.is_v2():
                msg2 = chain_beacon.message_v2(bcn.round)
                triples.append((pubkey, _decode_sig(bcn.signature_v2),
                                self._hash_msg(msg2, dst)))
            spans.append((start, len(triples) - start))
        flat = self.verify_bls(triples)
        return np.array([bool(flat[s:s + c].all()) for s, c in spans])

    def _wire_buckets(self):
        """Pairing buckets up to WIRE_MAX_BUCKET (see _pairing_buckets)."""
        return tuple(b for b in self._pairing_buckets()
                     if b <= WIRE_MAX_BUCKET)

    def _check_wire_bucket(self, b: int) -> bool:
        def probe():
            from ..crypto import bls

            sk = 0x5A17
            pub = PointG1.generator().mul(sk)
            m = b"engine-wire-bucket-check"
            checks = [(m, bls.sign(sk, m)), (b"other-msg", bls.sign(sk, m))]
            if b == 1:  # one row per call (same split as _check_bucket)
                out = np.concatenate(
                    [self._run_wire_bucket(pub, checks[:1], 1),
                     self._run_wire_bucket(pub, checks[1:], 1)])
                ok = bool(out[0]) and not bool(out[1])
            else:
                dev, valid, _ = self._launch_wire_bucket(pub, checks, b)
                full = np.asarray(dev)
                # pad rows carry the generator as "signature" over the pad
                # message under this pubkey — they must all verify False
                # (full-lane check; see _check_bucket)
                ok = (bool(full[0]) and not bool(full[1])
                      and not bool(full[2:].any())
                      and bool(valid[:2].all()))
            return ok

        return self._verdict(self._wire_ok, b, probe,
                             f"wire bucket {b}")

    def verify_wire(self, pubkey: PointG1, checks,
                    dst: bytes = DEFAULT_DST_G2) -> np.ndarray:
        """Batch-verify (message bytes, compressed signature) pairs with
        DEVICE-side hashing/decompression/subgroup checks (ops/h2c.py):
        host work is only SHA-256 expansion and byte unpacking. Buckets are
        known-answer-validated like verify_bls's; chunks dispatch async
        with one tail drain (see verify_bls)."""
        n = len(checks)
        if n == 0:
            return np.zeros(0, dtype=bool)
        b = self._checked_bucket(n, check=self._check_wire_bucket,
                                 buckets=self._wire_buckets())
        _meter_rows(n)
        launches = [self._launch_wire_bucket(pubkey, checks[i:i + b], b, dst)
                    for i in range(0, n, b)]
        stacked = _drain(launches)
        return np.concatenate([(stacked[j] & valid)[:c]
                               for j, (_, valid, c) in enumerate(launches)])

    def pack_wire_bucket(self, pubkey: PointG1, checks, b: int,
                         dst: bytes = DEFAULT_DST_G2):
        """Host-side prep of one padded wire bucket: SHA message
        expansion + signature byte unpacking. The packed tuple can be
        re-dispatched any number of times via :meth:`dispatch_wire_packed`
        — the measured-replay bench streams millions of rounds by cycling
        a content-varied pool of packed buckets, so the timed loop is
        pure device work (client/verify.go:146-163 scale)."""
        from . import h2c

        n = len(checks)
        pad_msg = b"drand-tpu-pad"
        msgs = [m for m, _ in checks] + [pad_msg] * (b - n)
        u = h2c.msgs_to_u(msgs, dst)
        pad_sig = _PAD_SIG()
        sigs = [s for _, s in checks] + [pad_sig] * (b - n)
        xs, sign, valid = h2c.sigs_to_x(sigs)
        return (_g1_aff(pubkey), u, xs, sign, valid, n, b)

    def dispatch_wire_packed(self, packed):
        """Async-dispatch one packed wire bucket; returns (device_out,
        valid, count) without synchronizing (see _launch_bucket)."""
        pub_aff, u, xs, sign, valid, n, b = packed
        if self._pallas_ok(b):
            from . import pallas_wire

            ok = pallas_wire.verify_wire_pl(pub_aff, u, xs, sign,
                                            sync=False)
        else:
            pubs = np.broadcast_to(pub_aff, (b, 2, limb.NLIMBS))
            ok = self._verify_wire(
                jnp.asarray(pubs), jnp.asarray(xs), jnp.asarray(sign),
                jnp.asarray(u))
        return ok, valid, n

    def _launch_wire_bucket(self, pubkey: PointG1, checks, b: int,
                            dst: bytes = DEFAULT_DST_G2):
        """Dispatch one padded wire bucket; no sync (see _launch_bucket)."""
        return self.dispatch_wire_packed(
            self.pack_wire_bucket(pubkey, checks, b, dst))

    def _run_wire_bucket(self, pubkey: PointG1, checks, b: int,
                         dst: bytes = DEFAULT_DST_G2) -> np.ndarray:
        dev, valid, n = self._launch_wire_bucket(pubkey, checks, b, dst)
        return (np.asarray(dev) & valid)[:n]

    # ------------------------------------------------- wire-RLC tier
    # The RLC combination folded INTO the wire pipeline: device
    # hash-to-curve + decompression feed an in-graph lane-MSM, so a
    # catch-up span costs 2 Miller loops end-to-end with no host hashing
    # either (the host does only SHA-256 expansion, byte unpacking and
    # scalar sampling). A span above the bucket runs as slices, and the
    # host prepares each slice while the device runs the one before it
    # (the launch / finish halves of the combine). Same discipline as
    # every other graph family: per-bucket KAT gate against the host
    # MSM, and a wrong verdict can only be a false REJECT (the caller
    # falls back to the per-item wire graph for exact verdicts).

    def wire_rlc_active(self, n_checks: int) -> bool:
        """True iff a span of ``n_checks`` wire checks takes the device
        wire-RLC tier (env gate, engine floor, wire-prep mode) — the
        dispatch/bench-facing twin of agg_rlc_active; the per-bucket KAT
        gate still applies at dispatch time."""
        return bool(self._use_wire(n_checks)) and self._rlc_wanted(n_checks)

    def wire_rlc_sharded_active(self, n_checks: int) -> bool:
        """True iff a span of ``n_checks`` wire checks would run the
        MESH-sharded wire-RLC combine (the crypto/batch.py dispatcher
        labels such spans path="wire_rlc_sharded"). Predicted from the
        bucket geometry alone — reading this never triggers a KAT
        probe; the per-shard-shape gate still applies at dispatch."""
        if not self.wire_rlc_active(n_checks):
            return False
        buckets = self._wire_rlc_buckets()
        if not buckets:
            return False
        b = next((bb for bb in buckets if bb >= n_checks), buckets[-1])
        return self._wire_rlc_shardable(b)

    def _wire_rlc_buckets(self):
        # the lane-MSM's cross-lane fold needs power-of-two lanes
        return tuple(b for b in self._wire_buckets() if not (b & (b - 1)))

    def _combine_wire_chunk(self, checks, cs, b: int, dst: bytes,
                            sharded: bool | None = None):
        """One combine dispatch of <= b wire checks: (decode-ok mask,
        Σc·sig, Σc·H(m)) with host points, (mask, None, None) when no
        lane survives decode, or None when a live combination
        degenerates to infinity (fall back; ~2^-128 honest).
        ``sharded``: force the mesh-sharded / unsharded combine (the
        KAT probes pin the path they gate); None consults the sharded
        KAT cache — never probes — so dispatch follows whatever verdict
        bucket selection already established."""
        return self._finish_wire_chunk(
            self._launch_wire_chunk(checks, cs, b, dst, sharded))

    def _launch_wire_chunk(self, checks, cs, b: int, dst: bytes,
                           sharded: bool | None = None):
        """The first half of _combine_wire_chunk: host preparation, then
        dispatch of the combine; returns (count, device outputs) without
        synchronizing. Every array handed to the device is allocated
        for this slice and never written again, so a slice in flight
        cannot see the next one's preparation (jnp.asarray may alias
        numpy memory on the CPU backend)."""
        from . import h2c

        n = len(checks)
        pad_msg = b"drand-tpu-pad"
        msgs = [m for m, _ in checks] + [pad_msg] * (b - n)
        u = h2c.msgs_to_u(msgs, dst)
        pad_sig = _PAD_SIG()
        sigs = [s for _, s in checks] + [pad_sig] * (b - n)
        xs, sign, valid = h2c.sigs_to_x(sigs)
        live = valid.copy()
        live[n:] = False
        bits = np.zeros((b, RLC_NBITS), np.int32)
        for i, c in enumerate(cs):
            bits[i] = curve.scalar_to_bits(c, RLC_NBITS)
        if sharded is None:
            sharded = bool(self._wire_rlc_shardable(b)
                           and self._wire_rlc_sharded_ok.get(b))
        if self._pallas_ok(b):
            from . import pallas_wire

            out = pallas_wire.wire_rlc_pl(u, xs, sign, live, bits)
        elif sharded:
            out = self._wire_rlc_sharded_jit(
                jnp.asarray(xs), jnp.asarray(sign), jnp.asarray(u),
                jnp.asarray(live), jnp.asarray(bits))
        else:
            out = self._wire_rlc_jit(
                jnp.asarray(xs), jnp.asarray(sign), jnp.asarray(u),
                jnp.asarray(live), jnp.asarray(bits))
        return n, out

    @staticmethod
    def _finish_wire_chunk(launched):
        """The second half of _combine_wire_chunk: read back one
        launched combine and convert its sums to host points."""
        n, out = launched
        ok, sx, sy, sinf, mx, my, minf = (np.asarray(o) for o in out)
        ok = ok.astype(bool)[:n]
        if not ok.any():
            return ok, None, None
        if bool(sinf) or bool(minf):
            return None
        return ok, _g2_from_affine_dev(sx, sy), _g2_from_affine_dev(mx, my)

    def _wire_rlc_kat_probe(self, b: int, sharded: bool) -> bool:
        """One wire-RLC combine KAT against the host MSM on fixed
        signatures and scalars, including a malformed lane that must be
        excluded from the combination. Gates usefulness, not soundness
        (the pairing row is the separately-KAT-gated verify_bls bucket,
        and a wrong combined point fails it)."""
        from ..crypto import bls
        from ..crypto.hash_to_curve import hash_to_g2

        sk = 0x5A17
        m1, m2 = b"engine-wire-rlc-a", b"engine-wire-rlc-b"
        s1, s2 = bls.sign(sk, m1), bls.sign(sk, m2)
        checks = [(m1, s1), (m2, s2)]
        cs = [5, 7]
        expect_mask = [True, True]
        if b >= 3:  # malformed lane: rejected per-item, never combined
            checks.append((b"engine-wire-rlc-bad", b"\x00" * 96))
            cs.append(3)
            expect_mask.append(False)
        got = self._combine_wire_chunk(checks, cs, b, DEFAULT_DST_G2,
                                       sharded=sharded)
        if got is None:
            return False
        mask, s_comb, m_comb = got
        p1 = PointG2.from_bytes(s1, subgroup_check=False)
        p2 = PointG2.from_bytes(s2, subgroup_check=False)
        return (list(mask) == expect_mask
                and s_comb == p1.mul(5) + p2.mul(7)
                and m_comb == hash_to_g2(m1).mul(5) + hash_to_g2(m2).mul(7))

    def _check_wire_rlc(self, b: int) -> bool:
        return self._verdict(
            self._wire_rlc_ok, b,
            lambda: self._wire_rlc_kat_probe(b, sharded=False),
            f"wire-rlc combine bucket {b}")

    def _check_wire_rlc_sharded(self, b: int) -> bool:
        """KAT the MESH-sharded combine per shard shape (bucket over
        mesh) — its own cache and verdict."""
        return self._verdict(
            self._wire_rlc_sharded_ok, b,
            lambda: self._wire_rlc_kat_probe(b, sharded=True),
            f"sharded wire-rlc combine bucket {b}")

    def _wire_rlc_check(self, b: int) -> bool:
        """Bucket-selection gate: shardable buckets are vouched for by
        the sharded KAT (one compile per shape on a mesh engine); the
        rest by the single-device combine KAT."""
        if self._wire_rlc_shardable(b):
            return self._check_wire_rlc_sharded(b)
        return self._check_wire_rlc(b)

    def verify_wire_rlc(self, pubkey: PointG1, checks,
                        dst: bytes = DEFAULT_DST_G2) -> np.ndarray | None:
        """The wire-RLC tier core: per-check bool array when the span's
        combined 2-pairing check lands (decode failures are per-item
        False and excluded from the combination), or None to fall back
        to the per-item wire graph — on a degenerate combination or a
        failed combined check (some signature is bad; the fallback
        produces the exact verdicts). Spans above the
        bucket chunk through it with one scalar vector and ONE pairing
        row at the end; their slices are pipelined two deep, the host
        preparing slice i+1 while slice i runs on the device, and each
        slice's sums are added on the host as it is read back.
        engine_wire_rlc_slices_total counts a slice "overlapped" when
        the slice before it was still running once it was launched,
        else "exposed" (a span's first slice, or a device that finished
        before the host did)."""
        n = len(checks)
        if n == 0:
            return np.zeros(0, dtype=bool)
        if pubkey.is_infinity():
            return None
        b = self._checked_bucket(n, check=self._wire_rlc_check,
                                 buckets=self._wire_rlc_buckets())
        from .. import metrics

        cs = batch_verify.rlc_scalars(n)
        ok_mask = np.zeros(n, dtype=bool)
        s_acc = m_acc = None
        # two-deep pipeline: slice i+1 is prepared and launched before
        # slice i is read back (the trailing None only drains). A
        # slice's preparation was hidden only if the slice before it is
        # still on the device once this one is launched.
        in_flight = None
        for lo in [*range(0, n, b), None]:
            launched = None
            if lo is not None:
                hi = min(lo + b, n)
                launched = lo, self._launch_wire_chunk(
                    checks[lo:hi], cs[lo:hi], b, dst)
                if in_flight is not None and _running(in_flight[1]):
                    metrics.ENGINE_WIRE_RLC_SLICES.labels(
                        prep="overlapped").inc()
                else:
                    metrics.ENGINE_WIRE_RLC_SLICES.labels(
                        prep="exposed").inc()
            if in_flight is not None:
                done, pending = in_flight
                got = self._finish_wire_chunk(pending)
                if got is None:
                    return None  # the slice in flight is dropped unread
                ok_chunk, s_chunk, m_chunk = got
                ok_mask[done:done + len(ok_chunk)] = ok_chunk
                if s_chunk is not None:
                    s_acc = s_chunk if s_acc is None else s_acc + s_chunk
                    m_acc = m_chunk if m_acc is None else m_acc + m_chunk
            in_flight = launched
        if s_acc is None:
            return ok_mask  # nothing decodable: every check already False
        if s_acc.is_infinity() or m_acc.is_infinity():
            return None
        if bool(self.verify_bls([(pubkey, s_acc, m_acc)])[0]):
            return ok_mask
        return None

    def verify_beacons_wire_rlc(self, pubkey: PointG1, beacons,
                                dst: bytes = DEFAULT_DST_G2
                                ) -> np.ndarray | None:
        """A span of beacons through the wire-RLC tier: per-beacon bool
        array, or None to fall back (crypto/batch.py then re-dispatches
        under the plain device tier)."""
        from ..chain import beacon as chain_beacon

        checks, spans = [], []
        for bcn in beacons:
            start = len(checks)
            checks.append((chain_beacon.message(bcn.round, bcn.previous_sig),
                           bcn.signature))
            if bcn.is_v2():
                checks.append((chain_beacon.message_v2(bcn.round),
                               bcn.signature_v2))
            spans.append((start, len(checks) - start))
        flat = self.verify_wire_rlc(pubkey, checks, dst)
        if flat is None:
            return None
        return np.array([bool(flat[s:s + c].all()) for s, c in spans])

    # ------------------------------------------------- timelock tier
    # Batched IBE decryption for the timelock vault's round-boundary
    # open (crypto/timelock.py): all K ciphertexts of a round share ONE
    # G2 point — the round's V2 signature — so the device graph runs the
    # Miller-loop line/T computation over `sig` ONCE (no batch axis) and
    # only the K varying U_i in G1 ride the batch axis, exactly like the
    # verify tiers. The graph outputs the canonical GT value per lane
    # (the 3^-1 cube correction is pre-folded into the shared point on
    # host — one G2 scalar mul per round); the Fujisaki-Okamoto
    # re-encryption check stays host-exact per item, so a wrong device
    # GT can only FALSE-REJECT (the host path then decides) — the same
    # soundness posture as every combine tier.

    @staticmethod
    def _tl_graph(xp, yp, qx, qy):
        """Canonical-GT pairings of a batch of G1 points against one
        shared G2 point: xp/yp (b, NLIMBS) affine mont G1 coords,
        qx/qy (2, NLIMBS) affine mont Fp2 coords of the PRE-FOLDED
        signature. Returns (b, 2, 3, 2, NLIMBS) Fp12 lanes."""
        q_aff = jnp.stack([qx, qy], axis=-3)[None, None]
        f = pairing.miller_loop_shared_q((xp[:, None], yp[:, None]), q_aff)
        return pairing.final_exponentiation(f, canonical=False)

    def _launch_tl_bucket(self, us, q_np, b: int):
        """Dispatch one padded GT bucket (pad lanes = generator, sliced
        away); returns (device_out, count) without synchronizing."""
        gen = _g1_aff(PointG1.generator())
        xs = np.broadcast_to(gen[0], (b, limb.NLIMBS)).copy()
        ys = np.broadcast_to(gen[1], (b, limb.NLIMBS)).copy()
        for i, xy in enumerate(PointG1.batch_to_affine(us)):
            aff = _g1_xy(xy)
            xs[i], ys[i] = aff[0], aff[1]
        from . import pallas_pairing as pp

        if self._pallas_ok(b) and b % pp.GRID_BLOCK == 0:
            # batch-last, one pair per lane, the shared Q on every lane
            q = np.broadcast_to(q_np[None, ..., None], (1,) + q_np.shape
                                + (b,))
            out = pp.gt_pl_grid(jnp.asarray(xs.T[None]),
                                jnp.asarray(ys.T[None]), jnp.asarray(q))
            return jnp.moveaxis(out, -1, 0), len(us)
        out = self._tl_jit(jnp.asarray(xs), jnp.asarray(ys),
                           jnp.asarray(q_np[0]), jnp.asarray(q_np[1]))
        return out, len(us)

    def _run_tl_bucket(self, us, q_np, b: int) -> list:
        """One synced bucket as host Fp12 lanes INCLUDING pads (the KAT
        probe checks every lane)."""
        from . import tower

        dev, _ = self._launch_tl_bucket(us, q_np, b)
        host = np.asarray(dev)
        return [tower.fp12_from_device(host[i]) for i in range(b)]

    def _check_tl_bucket(self, b: int) -> bool:
        """KAT the GT graph per bucket against the host shared-signature
        decryptor on fixed points — full-lane (pad rows must reproduce
        the generator pairing; a miscompile can be lane-dependent).
        Decryption soundness never depended on it (host-exact FO
        check)."""
        def probe():
            from ..crypto import timelock as tl

            sig = hash_to_g2(b"engine-timelock-kat").mul(0x5A17)
            rd = tl.RoundDecryptor(sig)
            g1 = PointG1.generator()
            us = [g1.mul(2), g1.mul(3)][:b]
            got = self._run_tl_bucket(us, _g2_aff(rd.sig_folded), b)
            expect = [rd.gt(u) for u in us]
            pad_expect = rd.gt(g1)
            ok = (all(g == e for g, e in zip(got, expect))
                  and all(g == pad_expect for g in got[len(us):]))
            return ok

        return self._verdict(self._tl_ok, b, probe,
                             f"timelock bucket {b}")

    def timelock_open(self, signature, cts) -> list:
        """Open a round's timelock ciphertexts with ONE batched GT
        dispatch: per-item ``(ok, plaintext, error)`` outcomes. Decode failures
        and infinity U points are per-item host decisions and never
        enter the batch; the FO accept/reject runs host-exact on every
        item (crypto/timelock._finish), with device-rejected items
        re-decided by the host pairing — false-reject-only."""
        from ..crypto import timelock as tl
        from . import tower

        n = len(cts)
        if n == 0:
            return []
        rd = tl.RoundDecryptor(signature)
        us: list[PointG1 | None] = []
        for ct in cts:
            try:
                u = PointG1.from_bytes(ct.u, subgroup_check=False)
                us.append(None if u.is_infinity() else u)
            except ValueError:
                us.append(None)
        live = [u for u in us if u is not None]
        if not live:
            return rd.decrypt_many(cts)
        b = self._checked_bucket(len(live), check=self._check_tl_bucket)
        _meter_gt_rows(len(live))
        q_np = _g2_aff(rd.sig_folded)
        launches = [self._launch_tl_bucket(live[i:i + b], q_np, b)
                    for i in range(0, len(live), b)]
        # one device-side concat + one host transfer (see _drain)
        if len(launches) == 1:
            host = np.asarray(launches[0][0])
        else:
            host = np.asarray(jnp.concatenate([d for d, _ in launches]))
        flat = []
        for j, (_, cnt) in enumerate(launches):
            rows = host[j * b:j * b + cnt]
            flat.extend(tower.fp12_from_device(rows[i])
                        for i in range(cnt))
        it = iter(flat)
        gts = [None if u is None else next(it) for u in us]
        return rd.decrypt_many(cts, gts=gts)

    def verify_sigs(self, pubkey: PointG1, pairs,
                    dst: bytes = DEFAULT_DST_G2) -> list[bool]:
        """Batch of (msg, sig_bytes) full-signature checks against one
        public key — the aggregator's V1+V2 re-verification
        (chain/beacon/chain.go:141,159)."""
        triples = [(pubkey, _decode_sig(sig), self._hash_msg(msg, dst))
                   for msg, sig in pairs]
        return [bool(v) for v in self.verify_bls(triples)]

    def verify_partials(self, pub_poly: PubPoly, msg: bytes, partials,
                        dst: bytes = DEFAULT_DST_G2) -> list[bool]:
        """All partials of one round against their public key shares.
        The per-index public keys come from ONE batched device Horner
        over the commitment polynomial (the host loop costs ~10 point
        ops per coefficient per index — seconds at 67-of-100 scale)."""
        if self._combine_rlc_wanted(len(partials)):
            got = self._rlc_verify_partials(pub_poly, msg, partials, dst)
            if got is not None:
                return got
        msg_pt = self._hash_msg(msg, dst)
        pubkeys = self._share_pubkeys(pub_poly, partials)
        triples = []
        for p, pk in zip(partials, pubkeys):
            if pk is None:
                triples.append((PointG1.generator(), None, msg_pt))
            else:
                triples.append((pk, _decode_sig(p[tbls.INDEX_BYTES:]),
                                msg_pt))
        return [bool(v) for v in self.verify_bls(triples)]

    def eval_poly_indices(self, pub_poly: PubPoly,
                          indices: list[int]) -> list[PointG1]:
        """ONE polynomial evaluated at MANY indices — the dual of
        eval_commits: commits broadcast across lanes, per-lane index
        bits through the same KAT-gated Horner graph."""
        n = len(indices)
        if n == 0:
            return []
        for i in indices:
            if not 0 <= i + 1 < (1 << _EVAL_IDX_BITS):
                raise ValueError("index out of range")
        if any(c.is_infinity() for c in pub_poly.commits):
            return [pub_poly.eval(i).value for i in indices]
        t = len(pub_poly.commits)
        eb = [b for b in self.buckets if b >= 32] or [128]
        b = self._checked_bucket(
            n, check=lambda bb: self._check_poly_eval_bucket(t, bb),
            buckets=eb)
        out = []
        for s in range(0, n, b):
            out.extend(self._run_poly_eval_bucket(
                pub_poly, indices[s:s + b], b))
        return out

    def _check_poly_eval_bucket(self, t: int, b: int) -> bool:
        """KAT for the many-indices mode — a DIFFERENT executable from
        eval_commits' shared-index mode (per-lane bits), gated and cached
        independently."""
        key = (t, b)
        def probe():
            g = PointG1.generator()
            poly = PubPoly([g.mul(1 + k) for k in range(t)])
            probe_idx = [0, 3, 7][:min(3, b)]
            got = self._run_poly_eval_bucket(poly, probe_idx, b)
            ok = got == [poly.eval(i).value for i in probe_idx]
            return ok

        return self._verdict(self._poly_eval_ok, key, probe,
                             f"poly-eval t={t} bucket {b}")

    def _run_poly_eval_bucket(self, pub_poly, indices, b: int):
        t = len(pub_poly.commits)
        xs = np.zeros((t, b, limb.NLIMBS), np.int32)
        ys = np.zeros((t, b, limb.NLIMBS), np.int32)
        flat = PointG1.batch_to_affine(pub_poly.commits)
        for k in range(t):
            aff = _g1_xy(flat[k])
            xs[k, :] = aff[0]
            ys[k, :] = aff[1]
        bits = np.zeros((b, _EVAL_IDX_BITS), np.int32)
        for j, idx in enumerate(indices):
            bits[j] = curve.scalar_to_bits(idx + 1, _EVAL_IDX_BITS)
        # pad lanes evaluate at abscissa 0 — harmless, sliced away
        dev = _eval_commits_graph(
            jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(bits), t=t)
        return self._unpack_eval(dev, len(indices))

    # ------------------------------------------------- commitment evals
    def eval_commits(self, polys, index: int) -> list[PointG1]:
        """Batched ``PubPoly.eval(index)`` across many commitment
        polynomials — the DKG deal-verification hot loop
        (reference kyber vss: one polynomial evaluation per dealer,
        n per node per DKG round; BASELINE config "n=128 deal verify").

        Device graph: vectorized Horner over the dealer axis — t-1 steps
        of ([index]·acc + C_k) with the shared small index as a 16-bit
        double-and-add ladder. Buckets are known-answer-validated per
        (t, bucket) against the host oracle on deterministic commitments
        (full-lane check) before first use."""
        n = len(polys)
        if n == 0:
            return []
        t = len(polys[0].commits)
        if any(len(p.commits) != t for p in polys):
            raise ValueError("mixed commitment lengths")
        if not 0 <= index + 1 < (1 << _EVAL_IDX_BITS):
            raise ValueError("index out of range")
        # polynomials carrying a point-at-infinity commitment (legal wire
        # encoding a malicious dealer can ship) have no affine packing —
        # evaluate those on the host, the rest on device
        bad = {i for i, p in enumerate(polys)
               if any(c.is_infinity() for c in p.commits)}
        if bad:
            good = [p for i, p in enumerate(polys) if i not in bad]
            dev = iter(self.eval_commits(good, index))
            return [polys[i].eval(index).value if i in bad else next(dev)
                    for i in range(n)]
        eb = [b for b in self.buckets if b >= 32] or [128]
        b = self._checked_bucket(
            n, check=lambda bb: self._check_eval_bucket(t, bb), buckets=eb)
        # async chunk dispatch; pack every chunk's coords + inf into one
        # device-side int32 block and pull ALL chunks with ONE host
        # transfer (same discipline as _drain)
        launches = [self._launch_eval_bucket(polys[i:i + b], index, b)
                    for i in range(0, n, b)]
        packed = jnp.concatenate(
            [jnp.concatenate(
                [*dev[:-1], dev[-1][:, None].astype(jnp.int32)], axis=1)
             for dev, _ in launches], axis=0)
        host = np.asarray(packed)
        out = []
        for chunk, (dev, cnt) in zip(range(0, len(launches) * b, b),
                                     launches):
            rows = host[chunk:chunk + b]
            out.extend(self._unpack_eval_host(rows, len(dev) - 1, cnt))
        return out

    def _eval_use_pallas(self, b: int) -> bool:
        return self._pallas_ok(b) and b % pallas_eval.LANE_BLOCK == 0

    @staticmethod
    def _unpack_eval_host(rows, ncoords: int, n: int) -> list[PointG1]:
        """Host-side unpack of a packed eval chunk: 2 coords = affine
        (XLA graph), 3 = Jacobian (Pallas kernel — converted here with a
        Montgomery-trick batch inversion: ONE bigint modexp for the whole
        bucket instead of a per-lane 381-step device Fermat ladder)."""
        from ..crypto.fields import Fp

        L = limb.NLIMBS
        inf = rows[:, -1].astype(bool)
        if ncoords == 2:
            return BatchedEngine._unpack_eval_rows(
                rows[:, :L], rows[:, L:2 * L], inf, n)
        xs = [limb.fp_from_device(rows[d, :L]) for d in range(n)]
        ys = [limb.fp_from_device(rows[d, L:2 * L]) for d in range(n)]
        zs = [limb.fp_from_device(rows[d, 2 * L:3 * L]) for d in range(n)]
        zz = [1 if inf[d] else (zs[d] or 1) for d in range(n)]
        pref = [1] * (n + 1)
        for i, z in enumerate(zz):
            pref[i + 1] = pref[i] * z % P
        acc = pow(pref[n], P - 2, P)
        invs = [0] * n
        for i in range(n - 1, -1, -1):
            invs[i] = acc * pref[i] % P
            acc = acc * zz[i] % P
        out = []
        for d in range(n):
            if inf[d] or zs[d] == 0:
                out.append(PointG1.infinity())
                continue
            zi = invs[d]
            zi2 = zi * zi % P
            out.append(PointG1(Fp(xs[d] * zi2 % P),
                               Fp(ys[d] * zi2 % P * zi % P), Fp(1)))
        return out

    def _run_eval_bucket(self, polys, index: int, b: int) -> list[PointG1]:
        dev, n = self._launch_eval_bucket(polys, index, b)
        rows = np.concatenate(
            [np.asarray(c) for c in dev[:-1]]
            + [np.asarray(dev[-1])[:, None].astype(np.int32)], axis=1)
        return self._unpack_eval_host(rows, len(dev) - 1, n)

    def _launch_eval_bucket(self, polys, index: int, b: int):
        t = len(polys[0].commits)
        n = len(polys)
        gen = _g1_aff(PointG1.generator())
        xs = np.zeros((t, b, limb.NLIMBS), np.int32)
        ys = np.zeros((t, b, limb.NLIMBS), np.int32)
        xs[:], ys[:] = gen[0], gen[1]
        flat = PointG1.batch_to_affine(
            [c for poly in polys for c in poly.commits])
        for d, poly in enumerate(polys):
            for k in range(t):
                aff = _g1_xy(flat[d * t + k])
                xs[k, d], ys[k, d] = aff[0], aff[1]
        # evaluation abscissa is index + 1 (kyber share convention —
        # crypto/poly._x_of)
        bits = curve.scalar_to_bits(index + 1, _EVAL_IDX_BITS)
        if self._eval_use_pallas(b):
            # fused Mosaic Horner (Jacobian out; host batch-inverts) —
            # the XLA limb graph below measured 0.74x HOST at n=128
            dev = pallas_eval.eval_commits_pl(
                jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(bits), t=t)
        else:
            dev = _eval_commits_graph(
                jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(bits), t=t)
        return dev, n

    @staticmethod
    def _unpack_eval(dev, n: int) -> list[PointG1]:
        ax, ay, inf = (np.asarray(c) for c in dev)
        return BatchedEngine._unpack_eval_rows(ax, ay, inf, n)

    @staticmethod
    def _unpack_eval_rows(ax, ay, inf, n: int) -> list[PointG1]:
        from ..crypto.fields import Fp

        out = []
        for d in range(n):
            if inf[d]:
                out.append(PointG1.infinity())
            else:
                out.append(PointG1(Fp(limb.fp_from_device(ax[d])),
                                   Fp(limb.fp_from_device(ay[d])),
                                   Fp(1)))
        return out

    def _check_eval_bucket(self, t: int, b: int) -> bool:
        key = (t, b)
        def probe():
            g = PointG1.generator()
            polys = [PubPoly([g.mul(1 + 31 * d + k) for k in range(t)])
                     for d in range(min(3, b))]
            index = 5
            got = self._run_eval_bucket(polys, index, b)
            expect = [p.eval(index).value for p in polys]
            ok = all(a == e for a, e in zip(got, expect))
            if ok and b > len(polys):
                # full-lane check: pad rows are constant generator
                # polynomials, eval = [sum((index+1)^k)] * g  (the
                # abscissa is index + 1 — crypto/poly._x_of)
                s = sum((index + 1) ** k for k in range(t))
                pad_expect = g.mul(s)
                pads = self._run_eval_bucket(
                    [PubPoly([g] * t)] * b, index, b)
                ok = all(p == pad_expect for p in pads)
            return ok

        return self._verdict(self._eval_ok, key, probe,
                             f"eval t={t} bucket {b}")

    # ------------------------------------------------------------ recover
    @staticmethod
    def _select_shares(partials, t: int, n: int) -> list[PubShare]:
        """First t distinct well-formed indices win — the tbls.recover
        selection semantics, shared by recover and the fused round."""
        shares: list[PubShare] = []
        seen: set[int] = set()
        for p in partials:
            if len(p) != tbls.PARTIAL_SIG_SIZE:
                continue
            idx = tbls.index_of(p)
            if idx in seen or idx >= n:
                continue
            pt = _decode_sig(p[tbls.INDEX_BYTES:])
            if pt is None:
                continue
            seen.add(idx)
            shares.append(PubShare(idx, pt))
            if len(shares) == t:
                break
        return shares

    def _gls4_active(self, t: int) -> bool:
        """GLS ψ² 4-D split for a t-share recovery MSM: always on the
        shape-flexible XLA paths; on the Pallas path only while the
        four digit lanes per share still fit the Mosaic kernel's fixed
        LANES width — beyond that the full-width Pallas ladder stays the
        better program."""
        if not self.gls4:
            return False
        if not self.pallas_only:
            return True
        from . import pallas_msm

        return 4 * t <= pallas_msm.LANES

    @staticmethod
    def _pack_msm_gls4(shares, lambdas, b: int):
        """GLS-split MSM packing: each share expands to its four ψ-basis
        lanes (P, -ψP, ψ²P, -ψ³P) with the base-M digits of its Lagrange
        coefficient as scalars (crypto/endo.gls4_*), so the device
        ladder runs GLS4_DIGIT_BITS-step scans instead of 255. The
        basis points come straight off the batch-normalized affine
        coordinates — two Fp2 multiplications per lane, no inversions.
        Returns (pts (b,2,2,L), inf (b,), bits (b, GLS4_DIGIT_BITS))."""
        from ..crypto import endo

        pad = _g2_aff(PointG2.generator())
        pts_np = np.broadcast_to(pad, (b, 2, 2, limb.NLIMBS)).copy()
        inf = np.ones(b, dtype=bool)  # padding rows masked out as infinity
        nbits = endo.GLS4_DIGIT_BITS
        bits = np.zeros((b, nbits), np.int32)
        share_xy = PointG2.batch_to_affine([s.value for s in shares])
        for i, s in enumerate(shares):
            digits = endo.gls4_decompose(lambdas[s.index] % R)
            basis = endo.gls4_points_from_affine(*share_xy[i])
            for k, d in enumerate(digits):
                lane = 4 * i + k
                if not d:
                    continue  # zero digit: lane stays masked infinity
                # basis points carry z == 1: (X, Y) are affine already
                pts_np[lane] = _g2_xy((basis[k].X, basis[k].Y))
                inf[lane] = False
                bits[lane] = curve.scalar_to_bits(d, nbits)
        return pts_np, inf, bits

    def recover(self, pub_poly: PubPoly, msg: bytes, partials, t: int, n: int,
                dst: bytes = DEFAULT_DST_G2, *, shares=None) -> bytes:
        """Lagrange-recover the full signature on device: one G2 MSM with
        the Lagrange coefficients as scalars (Scheme.Recover,
        chain/beacon/chain.go:136). Same selection semantics as the host
        tbls.recover: first t distinct valid indices win. ``shares``:
        pre-selected PubShares (internal callers that already decoded
        the partials skip the duplicate decode+subgroup pass).

        The scalars run GLS-split by default (``self.gls4``): four
        <= 64-bit digit lanes per share instead of one 255-bit ladder —
        a quarter of the sequential scan every threshold-aggregation
        round pays, not just catch-up (ROADMAP #5)."""
        if shares is None:
            shares = self._select_shares(partials, t, n)
        if len(shares) < t:
            raise ValueError(f"not enough valid partials: {len(shares)} < {t}")
        lambdas = lagrange_coefficients([s.index for s in shares])
        from . import pallas_msm

        if self._gls4_active(len(shares)):
            b = max(8, 1 << (4 * len(shares) - 1).bit_length())
            if self.pallas_only:
                b = max(b, pallas_msm.LANES)  # keep the Mosaic MSM engaged
            pts_np, inf, bits = self._pack_msm_gls4(shares, lambdas, b)
            use_lanes = self.pallas_only and b > self.PIPPENGER_MIN_T
        else:
            # buckets bound the PAIRING batch shapes; the MSM must still
            # fit all t shares even when a custom engine's top bucket is
            # smaller
            b = max(_bucket(t, self.buckets), t)
            use_lanes = self.pallas_only and b > self.PIPPENGER_MIN_T
            if use_lanes and b & (b - 1):
                # msm_lanes' log-tree fold needs power-of-two lanes; a
                # custom BatchedEngine(buckets=...) may hand us any size —
                # pad up, the extra rows are masked infinity (ADVICE r3)
                b = 1 << (b - 1).bit_length()
            pad = _g2_aff(PointG2.generator())
            pts_np = np.broadcast_to(pad, (b, 2, 2, limb.NLIMBS)).copy()
            inf = np.ones(b, dtype=bool)  # padding masked out as infinity
            bits = np.zeros((b, 255), np.int32)
            for i, s in enumerate(shares):
                pts_np[i] = _g2_aff(s.value)
                inf[i] = False
                bits[i] = curve.scalar_to_bits(lambdas[s.index] % R, 255)

        if use_lanes and b == pallas_msm.LANES:
            # one fused Mosaic program: per-lane ladders + lane-roll fold
            # + in-kernel to-affine. Output is verified cryptographically
            # by every caller (VerifyRecovered), so correctness cannot
            # silently degrade to an accepted wrong signature.
            x_aff, y_aff, is_inf = pallas_msm.msm_g2_pl(
                pts_np[:, 0], pts_np[:, 1], inf, bits,
                nbits=bits.shape[1])
        else:
            z_one = np.zeros((b, 2, limb.NLIMBS), np.int32)
            z_one[:, 0] = np.asarray(limb.ONE_MONT)
            pts = (jnp.asarray(pts_np[:, 0]), jnp.asarray(pts_np[:, 1]),
                   jnp.asarray(z_one), jnp.asarray(inf))
            if use_lanes:
                # per-lane ladders + log-tree fold (msm_lanes): the
                # unrolled ladder/window graphs take >10 min to COMPILE
                # at b=128 on the XLA limb path
                msm_fn = self._msm_g2_lanes
            else:
                msm_fn = (self._msm_g2_pip if b >= self.PIPPENGER_MIN_T
                          else self._msm_g2)
            x_aff, y_aff, is_inf = msm_fn(pts, jnp.asarray(bits))
        if bool(np.asarray(is_inf)):
            raise ValueError("recovered signature is the point at infinity")
        return _g2_from_affine_dev(np.asarray(x_aff),
                                   np.asarray(y_aff)).to_bytes()

    # ------------------------------------------- fused aggregator round
    @staticmethod
    def _agg_graph(pubs, sigs, msgs, slot_mask, mx, my, mz, minf, mbits, *,
                   pallas: bool):
        """The aggregator's whole per-round crypto as ONE device graph
        (chain/beacon/chain.go:91-166 in a single dispatch): Lagrange MSM
        over the chosen partials, recovered signature spliced into the
        pairing batch at the ``slot_mask`` row, every partial AND the
        recovered signature verified together. Output is one flat int32
        vector so the host pays a single transfer:
        [ok (b,), rec_x (2*NLIMBS), rec_y (2*NLIMBS), rec_inf (1)].
        ``pallas``: the engine's pallas_only policy."""
        b = pubs.shape[0]
        from . import pallas_msm

        if pallas and mx.shape[0] == pallas_msm.LANES:
            # Mosaic MSM: keeps the whole fused graph on the Pallas path
            # (no plain-XLA limb MSM between Mosaic kernels). nbits
            # follows the packing — 255 for
            # full-width ladders, GLS4_DIGIT_BITS for the ψ² split.
            rx, ry, rinf = pallas_msm.msm_g2_pl(mx, my, minf, mbits,
                                                nbits=mbits.shape[-1])
        else:
            rx, ry, rinf = curve.pt_to_affine(
                curve.F2, curve.msm_lanes(curve.F2, (mx, my, mz, minf),
                                          mbits))
        rec_row = jnp.stack([rx, ry])                      # (2, 2, NLIMBS)
        sig_full = jnp.where(slot_mask[:, None, None, None],
                             rec_row[None], sigs)
        if pallas and b >= PALLAS_MIN_BUCKET:
            from . import pallas_pairing as pp

            ok = pp.verify_pl(*pp.pack_verify_inputs(pubs, sig_full, msgs))
        else:
            ok = pairing.verify_prepared(pubs, sig_full, msgs)
        ok = ok & (~slot_mask | ~rinf)
        return jnp.concatenate([
            ok.astype(jnp.int32), rx.reshape(-1), ry.reshape(-1),
            rinf.reshape(1).astype(jnp.int32)])

    def _share_pubkeys(self, pub_poly: PubPoly, partials):
        """Per-partial share public keys via ONE batched device Horner
        (eval_poly_indices), cache-backed; None for malformed partials."""
        idxs = sorted({tbls.index_of(p) for p in partials
                       if len(p) == tbls.PARTIAL_SIG_SIZE})
        need = [i for i in idxs if i not in pub_poly._eval_cache
                and 0 <= i + 1 < (1 << _EVAL_IDX_BITS)]
        if need and not self.pallas_only:  # else the memoized host Horner
            for i, v in zip(need, self.eval_poly_indices(pub_poly, need)):
                pub_poly._eval_cache[i] = PubShare(i, v)
        out = []
        for p in partials:
            if len(p) != tbls.PARTIAL_SIG_SIZE:
                out.append(None)
            else:
                out.append(pub_poly.eval(tbls.index_of(p)).value)
        return out

    def _check_agg_bucket(self, b: int, b_msm: int, nbits: int) -> bool:
        """KAT-gate the fused executable per (bucket, msm-lane, msm-bit)
        shape — same discipline as every other graph family: a toy
        2-of-3 group whose recovery and verdicts are known on host. The
        probe packs the SAME scalar decomposition the dispatch will (GLS4
        digit lanes vs full-width), so the verdict vouches for the
        executable that actually runs."""
        key = (b, b_msm, nbits)
        def probe():
            from ..crypto.poly import PriPoly

            poly = PriPoly.random(2, seed=b"engine-agg-kat")
            pub_poly = poly.commit()
            msg = b"engine-agg-bucket-check"
            parts = [tbls.sign_partial(s, msg) for s in poly.shares(3)]
            bad = parts[2][:tbls.INDEX_BYTES] + parts[1][tbls.INDEX_BYTES:]
            expect_sig = tbls.recover(pub_poly, msg, parts[:2], 2, 3)
            oks, rec = self._run_agg(pub_poly, msg, parts[:2] + [bad],
                                     2, 3, DEFAULT_DST_G2, b, b_msm,
                                     gls4=nbits != 255)
            ok = (oks == [True, True, False] and rec == expect_sig)
            return ok

        return self._verdict(self._agg_ok, key, probe,
                             f"fused aggregate b={b}/msm{b_msm}/w{nbits}")

    def aggregate_round(self, pub_poly: PubPoly, msg: bytes, partials,
                        t: int, n: int,
                        dst: bytes = DEFAULT_DST_G2):
        """Verify all partials + Lagrange-recover + verify the recovered
        signature in ONE device dispatch with one result transfer — the
        aggregator's per-round work (chain/beacon/chain.go:91-166) that
        previously took 3+ synced calls.

        Returns ``(oks, sig_bytes)`` with ``oks`` aligned to ``partials``.
        Optimistic: recovery uses the first ``t`` well-formed distinct
        indices (tbls.recover selection); if one of those turns out
        invalid — or the recovered signature fails — falls back to the
        classic verify→filter→recover→verify path. Raises ``ValueError``
        when fewer than ``t`` well-formed partials exist."""
        npart = len(partials)
        shares = self._select_shares(partials, t, n)
        if len(shares) < t:
            raise ValueError(f"not enough valid partials: {len(shares)} < {t}")
        if self._combine_rlc_wanted(npart):
            got = self._try_agg_rlc(pub_poly, msg, partials, t, n, dst,
                                    shares)
            if got is not None:
                return got
        b, b_msm, msm_nbits = self.agg_shape(npart, t)
        if npart + 1 > b:
            oks = self.verify_partials(pub_poly, msg, partials, dst)
            return oks, self._recover_verified(pub_poly, msg, partials, oks,
                                               t, n, dst)
        self._check_agg_bucket(b, b_msm, msm_nbits)
        _meter_rows(npart + 1)
        oks, rec = self._run_agg(pub_poly, msg, partials, t, n, dst,
                                 b, b_msm, shares=shares,
                                 gls4=msm_nbits != 255)
        chosen = {s.index for s in shares}
        chosen_ok = all(
            ok for p, ok in zip(partials, oks)
            if len(p) == tbls.PARTIAL_SIG_SIZE
            and tbls.index_of(p) in chosen)
        if rec is not None and chosen_ok:
            return oks, rec
        # a chosen partial was invalid (or the recovery failed): recover
        # from the verified survivors instead
        return oks, self._recover_verified(pub_poly, msg, partials, oks,
                                           t, n, dst)

    def agg_shape(self, npart: int, t: int) -> tuple[int, int, int]:
        """(pairing bucket, msm lanes, msm scalar bits) the fused round
        would use — the KAT cache key shape. GLS-split rounds pack four
        digit lanes per share with GLS4_DIGIT_BITS scalars, full-width
        rounds one 255-bit lane per share; the bit width is part of the
        key because the two compile DIFFERENT executables even at equal
        lane counts."""
        if self._gls4_active(t):
            from ..crypto import endo

            b_msm = max(8, 1 << (4 * t - 1).bit_length())
            if self.pallas_only:
                from . import pallas_msm

                b_msm = max(b_msm, pallas_msm.LANES)
            return (_bucket(npart + 1, self._pairing_buckets()), b_msm,
                    endo.GLS4_DIGIT_BITS)
        return (_bucket(npart + 1, self._pairing_buckets()),
                max(8, 1 << (t - 1).bit_length()), 255)

    def warm_round_path(self) -> None:
        """On the Pallas path, compile and KAT the fused aggregation
        shape that every group with t <= 32 dispatches (agg_shape(1, 1)
        — the pairing and MSM lane buckets do not grow below that), so a
        daemon's first round does not stall on a minutes-long cold
        compile."""
        if self.pallas_only:
            self._check_agg_bucket(*self.agg_shape(1, 1))

    def agg_fused_active(self, npart: int, t: int) -> bool:
        """True iff an (npart, t) aggregate_round runs the single-dispatch
        fused executable (its KAT passed) rather than the fallback —
        callers (bench.py) report this without reaching into the KAT
        cache internals."""
        return bool(self._agg_ok.get(self.agg_shape(npart, t)))

    def agg_rlc_active(self, npart: int) -> bool:
        """True iff an npart-partial aggregate_round takes the RLC
        combine fast path (env gate, floor, and a trusted combine shape
        — spans above the top lane bucket chunk over it, so the first
        chunk's shape decides). The bench-facing twin of
        agg_fused_active."""
        if not self._combine_rlc_wanted(npart):
            return False
        lanes = self._rlc_lanes(min(npart, self.rlc_lane_buckets[-1]))
        return lanes is not None and bool(self._rlc_ok.get(("g1g2", lanes)))

    def _try_agg_rlc(self, pub_poly, msg, partials, t, n, dst,
                     shares=None):
        """RLC-shaped aggregator round: combine dispatch + recovery MSM
        + ONE 2-row pairing dispatch (combined-partials row and
        recovered-signature row) — 4 Miller pairs total instead of the
        classic fused graph's 2(N+1). Returns (oks, sig) when both rows
        land, else None (the classic fused/fallback path takes over,
        including the exact per-partial verdicts on bad rounds).
        ``shares``: aggregate_round's already-selected t shares, reused
        so recover() skips a duplicate decode+select pass."""
        msg_pt = self._hash_msg(msg, dst)
        if msg_pt.is_infinity():
            return None
        got = self._rlc_partials_comb(pub_poly, msg_pt, partials)
        if got is None:
            return None
        mask, k_comb, s_comb = got
        try:
            rec = self.recover(pub_poly, msg, partials, t, n, dst,
                               shares=shares)
        except ValueError:
            return None
        rec_pt = batch_verify.decode_sig(rec)
        if rec_pt is None:
            return None
        flat = self.verify_bls([(k_comb, s_comb, msg_pt),
                                (pub_poly.commit(), rec_pt, msg_pt)])
        if bool(flat[0]) and bool(flat[1]):
            return [bool(v) for v in mask], rec
        return None

    def _recover_verified(self, pub_poly, msg, partials, oks, t, n, dst):
        """Classic tail: recover from the partials that verified, then
        cryptographically check the recovered signature."""
        good = [p for p, ok in zip(partials, oks) if ok]
        if len(good) < t:
            raise ValueError(
                f"not enough valid partials: {len(good)} < {t}")
        sig = self.recover(pub_poly, msg, good, t, n, dst)
        if self.verify_sigs(pub_poly.commit(), [(msg, sig)], dst) != [True]:
            raise tbls.RecoveredSignatureInvalid(
                "recovered signature failed verification")
        return sig

    def _run_agg(self, pub_poly, msg, partials, t, n, dst, b, b_msm,
                 shares=None, gls4=None):
        """Pack, dispatch and unpack one fused round; returns (oks, sig
        bytes | None-if-recovered-infinity). ``gls4`` pins the MSM
        packing (aggregate_round passes agg_shape's decision so the KAT
        and the dispatch compile the same executable); None falls back
        to the engine policy."""
        npart = len(partials)
        msg_pt = self._hash_msg(msg, dst)
        pubkeys = self._share_pubkeys(pub_poly, partials)
        if shares is None:
            shares = self._select_shares(partials, t, n)
        lambdas = lagrange_coefficients([s.index for s in shares])

        # pairing batch: rows 0..npart-1 = partials, row npart = recovered
        pubs = np.zeros((b, 2, limb.NLIMBS), np.int32)
        sigs = np.zeros((b, 2, 2, limb.NLIMBS), np.int32)
        msgs = np.zeros((b, 2, 2, limb.NLIMBS), np.int32)
        valid = np.zeros(b, dtype=bool)
        pad_pub, pad_g2 = (_g1_aff(PointG1.generator()),
                           _g2_aff(PointG2.generator()))
        pubs[:], sigs[:], msgs[:] = pad_pub, pad_g2, pad_g2
        rows, g1s, g2s = [], [], []
        for i, (p, pk) in enumerate(zip(partials, pubkeys)):
            if pk is None or pk.is_infinity():
                continue
            pt = _decode_sig(p[tbls.INDEX_BYTES:])
            if pt is None or pt.is_infinity():
                continue
            rows.append(i)
            g1s.append(pk)
            g2s.append(pt)
        slot = npart
        group_key = pub_poly.commit()
        g1s.append(group_key)
        g2s.append(msg_pt)  # recovered row checks against H(msg) too
        g1_xy = PointG1.batch_to_affine(g1s)
        g2_xy = PointG2.batch_to_affine(g2s)
        msg_aff = _g2_xy(g2_xy[-1])
        for j, i in enumerate(rows):
            pubs[i] = _g1_xy(g1_xy[j])
            sigs[i] = _g2_xy(g2_xy[j])
            msgs[i] = msg_aff
            valid[i] = True
        pubs[slot] = _g1_xy(g1_xy[-1])
        msgs[slot] = msg_aff
        slot_mask = np.zeros(b, dtype=bool)
        slot_mask[slot] = True

        # MSM lanes (same packing as recover(), b_msm power-of-two):
        # GLS-split digit lanes when active, full 255-bit ladders else
        if gls4 is None:
            gls4 = self._gls4_active(len(shares))
        if gls4:
            pts_np, inf, bits = self._pack_msm_gls4(shares, lambdas, b_msm)
        else:
            pad = _g2_aff(PointG2.generator())
            pts_np = np.broadcast_to(pad, (b_msm, 2, 2, limb.NLIMBS)).copy()
            inf = np.ones(b_msm, dtype=bool)
            bits = np.zeros((b_msm, 255), np.int32)
            share_xy = PointG2.batch_to_affine([s.value for s in shares])
            for i, s in enumerate(shares):
                pts_np[i] = _g2_xy(share_xy[i])
                inf[i] = False
                bits[i] = curve.scalar_to_bits(lambdas[s.index] % R, 255)
        z_one = np.zeros((b_msm, 2, limb.NLIMBS), np.int32)
        z_one[:, 0] = np.asarray(limb.ONE_MONT)

        flat = np.asarray(self._agg(
            jnp.asarray(pubs), jnp.asarray(sigs), jnp.asarray(msgs),
            jnp.asarray(slot_mask), jnp.asarray(pts_np[:, 0]),
            jnp.asarray(pts_np[:, 1]), jnp.asarray(z_one),
            jnp.asarray(inf), jnp.asarray(bits)))
        ok = flat[:b].astype(bool) & valid
        L = limb.NLIMBS
        rx = flat[b:b + 2 * L].reshape(2, L)
        ry = flat[b + 2 * L:b + 4 * L].reshape(2, L)
        rinf = bool(flat[-1])
        oks = [bool(v) for v in ok[:npart]]
        if rinf or not flat[slot]:
            return oks, None
        return oks, _g2_from_affine_dev(rx, ry).to_bytes()


# index width for the eval_commits ladder — ONE constant for the XLA graph
# and the Mosaic kernel (the kernel kept a 10-bit ladder while the engine
# packed 11 bits: a wrong answer its first chip KAT caught)
_EVAL_IDX_BITS = pallas_eval.NBITS


@functools.partial(jax.jit, static_argnames=("t",))
def _eval_commits_graph(xs, ys, bits, t: int):
    """Vectorized Horner: eval_d = C[d,t-1]; repeat (·index, +C[d,k]).
    xs/ys: (t, b, NLIMBS) affine mont limbs (generator in pad lanes);
    bits: (_EVAL_IDX_BITS,) MSB-first shared index bits. The Horner steps
    run under lax.scan (one compiled body) — an unrolled loop's HLO count
    scales with t and stalls XLA compilation."""
    F = curve.F1
    b = xs.shape[1]
    z_one = jnp.broadcast_to(jnp.asarray(limb.ONE_MONT), (b, limb.NLIMBS))
    no_inf = jnp.zeros((b,), bool)

    def body(acc, c):
        cx, cy = c
        acc = curve.pt_mul_bits(F, acc, bits)
        acc = curve.pt_add(F, acc, (cx, cy, z_one, no_inf))
        return acc, None

    acc0 = (xs[t - 1], ys[t - 1], z_one, no_inf)
    acc, _ = jax.lax.scan(
        body, acc0, (jnp.flip(xs[:t - 1], axis=0),
                     jnp.flip(ys[:t - 1], axis=0)))
    return curve.pt_to_affine(F, acc)


_PAD_SIG_BYTES: bytes | None = None


def _PAD_SIG() -> bytes:
    """A well-formed compressed G2 point for padding rows (sliced away)."""
    global _PAD_SIG_BYTES
    if _PAD_SIG_BYTES is None:
        _PAD_SIG_BYTES = PointG2.generator().to_bytes()
    return _PAD_SIG_BYTES


def _g2_from_affine_dev(x_aff: np.ndarray, y_aff: np.ndarray) -> PointG2:
    """Mont-limb affine device output (2, NLIMBS) pairs -> host point."""
    from ..crypto.fields import Fp2

    return PointG2(
        Fp2(limb.fp_from_device(x_aff[0]), limb.fp_from_device(x_aff[1])),
        Fp2(limb.fp_from_device(y_aff[0]), limb.fp_from_device(y_aff[1])),
        Fp2.one())


def _g1_from_affine_dev(x_aff: np.ndarray, y_aff: np.ndarray) -> PointG1:
    from ..crypto.fields import Fp

    return PointG1(Fp(limb.fp_from_device(x_aff)),
                   Fp(limb.fp_from_device(y_aff)), Fp(1))


def _decode_sig(sig_bytes: bytes) -> PointG2 | None:
    """Wire signature -> subgroup-checked point; None if malformed.
    Delegates to the shared prefilter (ψ-endomorphism subgroup check,
    same accept set as the generic order-r multiplication, ~3x cheaper
    per decode)."""
    return batch_verify.decode_sig(sig_bytes)
