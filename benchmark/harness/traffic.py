"""The one traffic generator: a traffic file's parameters and a seed in,
requests out.

Every seed gets the same multiset of sizes in another order: sizes are
the quantiles of the file's log-uniform range, one cycle at a time. The
cycle is interleaved (largest, smallest, next largest, ...) so that every
stretch of it holds large and small requests alike, and the seed picks
its rotation and direction: a window that ends inside a cycle then does
about the same work whatever the seed. The traffic file's ``loop`` names
the driver that serves the requests.

Loops:

- ``walks``: catch-up walks, one per request: ``{"h": head round,
  "corrupt": None | {"kind", "round"}}``. Every cycle holds each clean
  size once, plus one forged walk outside the size cycle (the file's
  ``forged``: its own short ``h``, the forged round at ``at`` of it, the
  kinds taken in turn, one per cycle, at a seed-drawn place).
"""

from __future__ import annotations

import itertools
import random


def loguniform(lo: int, hi: int, n: int) -> list[int]:
    return [round(lo * (hi / lo) ** ((i + 0.5) / n)) for i in range(n)]


def _rng(name: str, seed: int, *tags) -> random.Random:
    return random.Random("/".join(str(t) for t in (name, seed) + tags))


def _order(items: list, key, rng: random.Random) -> list:
    """``items`` interleaved by ``key`` (largest, smallest, ...), rotated
    and maybe reversed by ``rng``."""
    srt = sorted(items, key=key)
    inter = [srt[-1 - i // 2] if i % 2 == 0 else srt[i // 2]
             for i in range(len(srt))]
    k = rng.randrange(len(inter))
    inter = inter[k:] + inter[:k]
    return inter[::-1] if rng.random() < 0.5 else inter


def clean_walks(tr: dict) -> list[dict]:
    spec = tr["walk_rounds"]
    return [{"h": h, "corrupt": None}
            for h in loguniform(spec["min"], spec["max"], spec["per_cycle"])]


def forged_walk(tr: dict, kind: str) -> dict:
    f = tr["forged"]
    return {"h": f["h"],
            "corrupt": {"kind": kind, "round": max(1, round(f["h"] * f["at"]))}}


def walk_cycle(tr: dict, seed: int, i: int = 0) -> list[dict]:
    """Cycle ``i`` of the seed's walks: the clean sizes in the seed's
    order, and the cycle's forged walk at a place drawn from the seed."""
    reqs = _order(clean_walks(tr), lambda r: r["h"],
                  _rng(tr["name"], seed, "walks"))
    kinds = tr.get("forged", {}).get("kinds", [])
    if kinds:
        at = _rng(tr["name"], seed, "forged", i).randrange(len(reqs) + 1)
        reqs.insert(at, forged_walk(tr, kinds[i % len(kinds)]))
    return reqs


def walks(tr: dict, seed: int):
    for i in itertools.count():
        yield from walk_cycle(tr, seed, i)
