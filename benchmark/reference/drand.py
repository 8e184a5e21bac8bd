"""drand's chain semantics on the plain BLS12-381 reference.

- V1 (chained) message: sha256(previous signature || round as 8 bytes);
  V2 (unchained) message: sha256(round as 8 bytes). A round is valid when
  its previous signature is the signature of the round before (the chain's
  genesis seed for round 1), and both its signatures verify under the
  group key.
"""

from __future__ import annotations

import hashlib

from . import bls12_381 as bls


def message(round_no: int, prev_sig: bytes) -> bytes:
    return hashlib.sha256(prev_sig + round_no.to_bytes(8, "big")).digest()


def message_v2(round_no: int) -> bytes:
    return hashlib.sha256(round_no.to_bytes(8, "big")).digest()


def first_invalid_round(genesis_seed: bytes, rounds, sig_ok) -> int | None:
    """The first round of ``rounds`` (ascending (round, prev, sig, sig_v2)
    tuples from round 1) that breaks the chain or carries a signature that
    ``sig_ok(round, prev, sig, sig_v2)`` rejects; None when all hold."""
    prev = genesis_seed
    for rnd, prev_sig, sig, sig_v2 in rounds:
        if prev_sig != prev or not sig_ok(rnd, prev_sig, sig, sig_v2):
            return rnd
        prev = sig
    return None


def round_valid(pub: bls.G1, rnd: int, prev_sig: bytes, sig: bytes,
                sig_v2: bytes) -> bool:
    return (bls.verify(pub, message(rnd, prev_sig), sig)
            and bls.verify(pub, message_v2(rnd), sig_v2))

