"""A configuration's chain fixture, checked against its pinned sha256."""

from __future__ import annotations

import hashlib
import os

SIG = 96


def load_chain(cfg: dict, root: str) -> dict:
    """``{"v1": [...], "v2": [...], "genesis": bytes, "group_key": bytes}``
    with ``v1[r]``/``v2[r]`` the signatures of round r (index 0 unused)."""
    fx = cfg["fixture"]
    with open(os.path.join(root, fx["file"]), "rb") as f:
        blob = f.read()
    got = hashlib.sha256(blob).hexdigest()
    if got != fx["sha256"]:
        raise RuntimeError(f"{fx['file']}: sha256 {got} is not the pinned "
                           f"{fx['sha256']}")
    n = len(blob) // (2 * SIG)
    v1 = [b""] + [blob[i * 2 * SIG:i * 2 * SIG + SIG] for i in range(n)]
    v2 = [b""] + [blob[i * 2 * SIG + SIG:(i + 1) * 2 * SIG] for i in range(n)]
    return {"v1": v1, "v2": v2, "rounds": n,
            "genesis": bytes.fromhex(fx["genesis_seed"]),
            "group_key": bytes.fromhex(fx["group_key"])}


def previous(chain: dict, r: int) -> bytes:
    return chain["v1"][r - 1] if r > 1 else chain["genesis"]
