"""Make a configuration's chain fixture: its history of V1 + V2 rounds.

    python3 benchmark/fixtures/make_chain.py benchmark/configs/loe-mainnet.json

Signs rounds 1..history_rounds with the group secret derived from the
configuration's ``fixture.key_seed`` (the chained V1 signature over the
previous one, genesis seed first, and the unchained V2 signature), checks
every round with the plain reference, writes ``fixture.file`` (per round:
V1 signature then V2 signature, 96 bytes each) and prints its sha256, which
the configuration pins. The V1 chain is sequential; V2 signing and the
check run in worker processes.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
from concurrent.futures import ProcessPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from reference import bls12_381 as bls  # noqa: E402
from reference import drand  # noqa: E402

WORKERS = 4


def group_secret(key_seed: str) -> int:
    return int.from_bytes(hashlib.sha512(key_seed.encode()).digest(),
                          "big") % (bls.R - 1) + 1


def _sign_v2(args):
    sk, lo, hi = args
    return [bls.sign(sk, drand.message_v2(r)) for r in range(lo, hi)]


def _check(args):
    pub_bytes, rows = args
    pub = bls.G1.from_bytes(pub_bytes)
    return [r for r, prev, s1, s2 in rows
            if not drand.round_valid(pub, r, prev, s1, s2)]


def main(config_path: str) -> int:
    with open(config_path) as f:
        cfg = json.load(f)
    fx = cfg["fixture"]
    n = cfg["history_rounds"]
    sk = group_secret(fx["key_seed"])
    pub = bls.G1.generator().mul(sk)
    genesis = bytes.fromhex(fx["genesis_seed"])
    step = 256
    with ProcessPoolExecutor(WORKERS) as pool:
        v2_parts = pool.map(_sign_v2, [(sk, lo, min(lo + step, n + 1))
                                       for lo in range(1, n + 1, step)])
        v1, prev = [], genesis
        for r in range(1, n + 1):
            prev = bls.sign(sk, drand.message(r, prev))
            v1.append(prev)
        v2 = [s for part in v2_parts for s in part]
        rows = [(r, v1[r - 2] if r > 1 else genesis, v1[r - 1], v2[r - 1])
                for r in range(1, n + 1)]
        bad = [r for part in pool.map(
            _check, [(pub.to_bytes(), rows[i:i + step])
                     for i in range(0, n, step)]) for r in part]
    if bad:
        print(f"rounds failing the reference: {bad[:10]}", file=sys.stderr)
        return 1
    blob = b"".join(s1 + s2 for s1, s2 in zip(v1, v2))
    out = os.path.join(os.path.dirname(os.path.dirname(HERE)), fx["file"])
    with open(out, "wb") as f:
        f.write(blob)
    print(json.dumps({"file": fx["file"], "rounds": n,
                      "group_key": pub.to_bytes().hex(),
                      "sha256": hashlib.sha256(blob).hexdigest()}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
