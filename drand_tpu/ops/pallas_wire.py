"""Wire-format BLS verification fully on device: Pallas kernels for
hash-to-G2, decompression, subgroup checks, then the pairing chain.

End-to-end catch-up (client/verify.go:146-163) and aggregator
re-verification take WIRE inputs: message bytes + 96-byte compressed
signatures. The host formerly paid ~45ms (hash-to-curve) + ~18ms
(subgroup-checked decompression) of pure Python per item; here the host
does only SHA-256 expansion + byte splitting (ops/h2c.msgs_to_u /
sigs_to_x) and everything else runs as batch-last Mosaic kernels
(ops/bl_h2c.py, ops/bl_curve.py — ψ fast paths), feeding the pairing
kernels of ops/pallas_pairing.py. As in pallas_pairing, NO
per-element XLA runs between kernels.

Kernel chain (per batch of B lanes):
    K_map (x2)  u-value -> pre-clearing E2 point        [sswu + isogeny]
    K_ptadd     q0 + q1                                 [Jacobian add]
    K_mulx (x2) [x]P chains of Budroni-Pintore          [64-bit fori]
    K_glue      BP combination + to-affine              [ψ, adds]
    K_sig       decompress + Scott subgroup + to-affine
    ... then miller/easy/pow/is_one from pallas_pairing.

The wire-RLC tier (wire_rlc_pl) swaps the per-lane pairing tail for two
batch-last lane-MSM kernels (pallas_msm.msm_g2_bl, 128-bit RLC scalar
ladders + cross-lane fold) that collapse the bucket to (Σc·sig,
Σc·H(m)) — the combined pair then runs ONE row of the ordinary pairing
bucket, so an all-valid span costs 2 Miller loops end-to-end.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from . import bl, bl_curve as blc, bl_h2c as blh
from . import curve as xc
from . import pallas_pairing as pp
from .bl import DTYPE, NLIMBS

# bit tables (SMEM inputs)
SQRT_BITS = blh.SQRT_BITS          # (1, 768)
X_BITS = blc.X_BITS                # (1, 64)
PM2_FLAT = pp.PM2_FLAT             # (1, 384)


def _mask_out(ok, shape0=8):
    """(B,) bool -> (8, B) int32 tile-safe output (bool->int via where:
    astype lowers as an invalid i1->i32 vreg bitcast in Mosaic)."""
    return jnp.broadcast_to(jnp.where(ok, 1, 0)[None, :],
                            (shape0, ok.shape[-1])).astype(DTYPE)


def _kernel_f2(pm2_ref):
    """Batch-last F2 namespace whose inversions read the p-2 bits from an
    SMEM ref (kernels cannot dynamic-slice values)."""
    return blc.make_f2(pp.smem_bit_getter(pm2_ref))


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

def _map_kernel(c_ref, sqrt_ref, pm2_ref, u_ref, ox_ref, oy_ref):
    """One u-value -> affine pre-clearing E2 point (sswu + isogeny)."""
    with bl.const_context(c_ref[:]):
        x, y = blh.map_to_curve(u_ref[:], pp.smem_bit_getter(sqrt_ref),
                                pp.smem_bit_getter(pm2_ref))
        ox_ref[:] = x
        oy_ref[:] = y


def _ptadd_affine_kernel(c_ref, x0_ref, y0_ref, x1_ref, y1_ref,
                         ox_ref, oy_ref, oz_ref, oinf_ref):
    """Jacobian sum of two affine points (never infinity inputs — map
    outputs); Jacobian out."""
    with bl.const_context(c_ref[:]):
        b = x0_ref.shape[-1]
        F = blc.make_f2()  # no inversion used in pt_add
        one_z = F.one((b,))
        inf0 = jnp.zeros((b,), DTYPE) != 0  # computed, not an i1 splat
        out = xc.pt_add(F, (x0_ref[:], y0_ref[:], one_z, inf0),
                        (x1_ref[:], y1_ref[:], one_z, inf0))
        ox_ref[:], oy_ref[:], oz_ref[:] = out[0], out[1], out[2]
        oinf_ref[:] = _mask_out(out[3])


def _mulx_kernel(c_ref, xbits_ref, x_ref, y_ref, z_ref, inf_ref,
                 ox_ref, oy_ref, oz_ref, oinf_ref):
    """[x]P (x < 0) on a Jacobian point."""
    with bl.const_context(c_ref[:]):
        F = blc.make_f2()
        p = (x_ref[:], y_ref[:], z_ref[:], inf_ref[0] != 0)
        out = blc.mul_x(F, p, pp.smem_bit_getter(xbits_ref))
        ox_ref[:], oy_ref[:], oz_ref[:] = out[0], out[1], out[2]
        oinf_ref[:] = _mask_out(out[3])


def _clear_glue_kernel(c_ref, pm2_ref,
                       px_ref, py_ref, pz_ref, pinf_ref,
                       t1x_ref, t1y_ref, t1z_ref, t1inf_ref,
                       t2x_ref, t2y_ref, t2z_ref, t2inf_ref,
                       ox_ref, oy_ref, oinf_ref):
    """Budroni-Pintore combination [x²−x−1]P + ψ([x−1]P) + ψ²([2]P) from
    precomputed t1 = [x]P, t2 = [x²]P, as the six-term sum
    t2 − t1 − P + ψ(t1) − ψ(P) + ψ²(2P) (ψ is a group endomorphism),
    accumulated by ONE point addition in a fori_loop — traced and
    compiled once instead of five times; then to-affine."""
    with bl.const_context(c_ref[:]):
        F = _kernel_f2(pm2_ref)
        p = (px_ref[:], py_ref[:], pz_ref[:], pinf_ref[0] != 0)
        t1 = (t1x_ref[:], t1y_ref[:], t1z_ref[:], t1inf_ref[0] != 0)
        t2 = (t2x_ref[:], t2y_ref[:], t2z_ref[:], t2inf_ref[0] != 0)
        terms = [xc.pt_neg(F, t1), xc.pt_neg(F, p), blc.psi(t1),
                 xc.pt_neg(F, blc.psi(p)), blc.psi2(xc.pt_dbl(F, p))]

        def body(k, acc):
            term = terms[0]
            for j, other in enumerate(terms[1:], 1):
                term = xc.pt_select(F, k == j, other, term)
            out = xc.pt_add(F, (acc[0], acc[1], acc[2], acc[3] != 0), term)
            return out[0], out[1], out[2], jnp.where(out[3], 1, 0)

        X, Y, Z, inf32 = jax.lax.fori_loop(
            0, len(terms), body,
            (t2[0], t2[1], t2[2], jnp.where(t2[3], 1, 0)))
        ax, ay, ainf = xc.pt_to_affine(F, (X, Y, Z, inf32 != 0))
        ox_ref[:], oy_ref[:] = ax, ay
        oinf_ref[:] = _mask_out(ainf)


def _sig_kernel(c_ref, sqrt_ref, xbits_ref, pm2_ref, sx_ref, sign_ref,
                ox_ref, oy_ref, ook_ref):
    """Compressed-signature pipeline: decompress (sqrt + zcash sign rule),
    Scott subgroup check, to-affine. ok = on_curve & in_subgroup."""
    with bl.const_context(c_ref[:]):
        F = _kernel_f2(pm2_ref)
        sign_bit = sign_ref[0] != 0
        pt, on_curve = blh.decompress_g2_bl(
            sx_ref[:], sign_bit, F, pp.smem_bit_getter(sqrt_ref))
        in_sub = blc.subgroup_check(F, pt, pp.smem_bit_getter(xbits_ref))
        ox_ref[:] = pt[0]
        oy_ref[:] = pt[1]
        ook_ref[:] = _mask_out(on_curve & in_sub)


# ---------------------------------------------------------------------------
# The jitted chain
# ---------------------------------------------------------------------------

def _f2shape(b):
    return jax.ShapeDtypeStruct((2, NLIMBS, b), DTYPE)


def _mask_shape(b):
    return jax.ShapeDtypeStruct((8, b), DTYPE)


def _pt_shapes(b):
    return (_f2shape(b), _f2shape(b), _f2shape(b), _mask_shape(b))


# kernels launched twice per hash run as their own jits, so each is
# traced and lowered once per shape
@jax.jit
def _map_pl(consts, sqrt_b, pm2_b, u):
    b = u.shape[-1]
    return pp._pallas(_map_kernel, (_f2shape(b), _f2shape(b)), "vssv")(
        consts, sqrt_b, pm2_b, u)


@jax.jit
def _mulx_pl(consts, xb, x, y, z, inf):
    b = x.shape[-1]
    return pp._pallas(_mulx_kernel, _pt_shapes(b), "vsvvvv")(
        consts, xb, x, y, z, inf)


@functools.partial(jax.jit, static_argnames=("b",))
def _hash_msgs_pl(u_pairs, b: int):
    """u_pairs (2, 2, 32, B) -> affine message point (x, y) on G2."""
    # lane-broadcast const buffer: these kernels multiply constants into
    # the convolution (see bl.mont_mul docstring)
    consts = jnp.asarray(bl.lane_buffer(b))
    sqrt_b = jnp.asarray(SQRT_BITS)
    pm2_b = jnp.asarray(PM2_FLAT)
    xb = jnp.asarray(X_BITS)

    x0, y0 = _map_pl(consts, sqrt_b, pm2_b, u_pairs[0])
    x1, y1 = _map_pl(consts, sqrt_b, pm2_b, u_pairs[1])
    q = pp._pallas(_ptadd_affine_kernel, _pt_shapes(b), "vvvvv")(
        consts, x0, y0, x1, y1)
    t1 = _mulx_pl(consts, xb, *q)
    t2 = _mulx_pl(consts, xb, *t1)
    mx, my, minf = pp._pallas(
        _clear_glue_kernel, (_f2shape(b), _f2shape(b), _mask_shape(b)),
        "vs" + "v" * 12)(consts, pm2_b, *q, *t1, *t2)
    return mx, my, minf


@functools.partial(jax.jit, static_argnames=("b",))
def _sig_pl(sig_x, sign_mask, b: int):
    consts = jnp.asarray(bl.lane_buffer(b))
    return pp._pallas(_sig_kernel,
                      (_f2shape(b), _f2shape(b), _mask_shape(b)),
                      "vsssvv")(
        consts, jnp.asarray(SQRT_BITS), jnp.asarray(X_BITS),
        jnp.asarray(PM2_FLAT), sig_x, sign_mask)


@jax.jit
def _wire_pairs(pub_xp, pub_yp, sx, sy, mx, my):
    """Batch-last pairing rows (-g1, sig), (pub, H(m)) per lane."""
    b = sx.shape[-1]
    neg = np.asarray(pp._neg_g1_np())  # (2, 32)
    ng1x = jnp.broadcast_to(jnp.asarray(neg[0])[:, None], (NLIMBS, b))
    ng1y = jnp.broadcast_to(jnp.asarray(neg[1])[:, None], (NLIMBS, b))
    xp = jnp.stack([ng1x, pub_xp])            # (NP, 32, B)
    yp = jnp.stack([ng1y, pub_yp])
    sig_aff = jnp.stack([sx, sy])             # (2coord, 2, 32, B)
    msg_aff = jnp.stack([mx, my])
    return xp, yp, jnp.stack([sig_aff, msg_aff])   # q (NP, 2, 2, 32, B)


def _wire_verify_pl(pub_xp, pub_yp, u_pairs, sig_x, sign_mask, b: int):
    """Full wire check per lane: decompress+subgroup the signature, hash
    the message, then the pairing chain. pub_xp/yp: (32, B) G1 affine
    coords of the (broadcast) public key. A chain of programs shared with
    the wire-RLC combine and the pairing buckets (see pallas_pairing)."""
    sx, sy, sig_ok = _sig_pl(sig_x, sign_mask, b)
    mx, my, minf = _hash_msgs_pl(u_pairs, b)
    pair_ok = pp.verify_pl(*_wire_pairs(pub_xp, pub_yp, sx, sy, mx, my))
    return pair_ok & (sig_ok[0] != 0) & (minf[0] == 0)


@jax.jit
def _live_lanes(sig_ok, minf, live_mask):
    ok = (sig_ok[0] != 0) & (live_mask[0] != 0) & (minf[0] == 0)
    return ok, jnp.where(ok, 0, 1)[None, :]                   # dead (1, b)


def _wire_rlc_pl(u_pairs, sig_x, sign_mask, live_mask, bits, b: int):
    """Wire-RLC combine fully on device: decompress + subgroup-check the
    signatures, hash the messages, then collapse the bucket to
    (Σc·sig, Σc·H(m)) with two batch-last Mosaic lane-MSMs sharing the
    scalar bits (pallas_msm.msm_g2_bl — the recovery MSM kernel with a
    128-bit ladder). Lanes that fail decode, hash to infinity, or are
    padding are masked to infinity in BOTH MSMs so one bad encoding
    cannot poison the combination; the combined pair then feeds the
    ordinary KAT-gated pairing bucket (2 Miller pairs for the span).
    Its decode and hash programs are the per-item wire check's."""
    from . import pallas_msm

    sx, sy, sig_ok = _sig_pl(sig_x, sign_mask, b)
    mx, my, minf = _hash_msgs_pl(u_pairs, b)
    ok, dead = _live_lanes(sig_ok, minf, live_mask)
    s_x, s_y, s_inf = pallas_msm.msm_g2_bl(sx, sy, dead, bits, nbits=128)
    m_x, m_y, m_inf = pallas_msm.msm_g2_bl(mx, my, dead, bits, nbits=128)
    return ok, s_x, s_y, s_inf, m_x, m_y, m_inf


def wire_rlc_pl(u_pairs_np, sig_x_np, sign_np, live_np, bits_np):
    """Host entry for the wire-RLC combine: u_pairs_np (B, 2, 2, 32)
    batch-leading (ops/h2c.msgs_to_u layout); sig_x_np (B, 2, 32);
    sign_np/live_np (B,) bool; bits_np (B, 128) MSB-first int32 scalar
    bits. Returns the device arrays (ok (B,), s_x (2, 32), s_y, s_inf (),
    m_x, m_y, m_inf) un-synced — the same shapes as the XLA combine
    graph, so the engine consumes either interchangeably and prepares
    the next slice while this one runs."""
    b = u_pairs_np.shape[0]
    u_bl = jnp.asarray(np.moveaxis(u_pairs_np, 0, -1))        # (2, 2, 32, B)
    sig_bl = jnp.asarray(np.moveaxis(sig_x_np, 0, -1))        # (2, 32, B)
    sign_mask = jnp.asarray(
        np.broadcast_to(sign_np.astype(np.int32)[None, :], (8, b)))
    live_mask = jnp.asarray(
        np.broadcast_to(live_np.astype(np.int32)[None, :], (8, b)))
    bits_bl = jnp.asarray(bits_np.T.astype(np.int32))         # (128, B)
    out = _wire_rlc_pl(u_bl, sig_bl, sign_mask, live_mask, bits_bl, b)
    return out


def verify_wire_pl(pubkey_aff, u_pairs_np, sig_x_np, sign_np,
                   sync: bool = True):
    """Host entry: pubkey_aff (2, 32) mont limbs; u_pairs_np (B, 2, 2, 32)
    batch-leading (ops/h2c.msgs_to_u layout); sig_x_np (B, 2, 32); sign_np
    (B,) bool. Returns (B,) bool — as numpy when ``sync`` (the default),
    else the un-synced device array so callers can pipeline chunks and
    drain once."""
    b = u_pairs_np.shape[0]
    u_bl = jnp.asarray(np.moveaxis(u_pairs_np, 0, -1))  # (2, 2, 32, B)
    sig_bl = jnp.asarray(np.moveaxis(sig_x_np, 0, -1))  # (2, 32, B)
    sign_mask = jnp.asarray(
        np.broadcast_to(sign_np.astype(np.int32)[None, :], (8, b)))
    pub_xp = jnp.asarray(np.broadcast_to(pubkey_aff[0][:, None],
                                         (NLIMBS, b)))
    pub_yp = jnp.asarray(np.broadcast_to(pubkey_aff[1][:, None],
                                         (NLIMBS, b)))
    out = _wire_verify_pl(pub_xp, pub_yp, u_bl, sig_bl, sign_mask, b)
    return np.asarray(out) if sync else out
