"""Catch-up walks: a closed loop of fresh verifying clients.

Each request builds ``client/verify.VerifyingClient(strict_rounds=True,
use_checkpoints=False)`` over an in-process source that serves the
configuration's chain fixture (``get_span`` for whole chunks, no network)
and calls ``get(h)``: a walk from genesis to round h. A request that
carries a corruption is served one tampered round:

- ``v2_swap``: round r's V2 signature is another round's;
- ``v1_relink``: round r's V1 signature is another round's, and round
  r+1's previous signature follows it, so the links still hold;
- ``link_break``: round r's previous signature is not round r-1's.

A walk's verdict is the head it accepts, or the round its rejection names.
Committed work is h rounds for an accepted walk and r - 1 for a walk
rejected at r; the window ends with the first walk that ends after
``--seconds``. The check recomputes every verdict with the plain
reference: the fixture's untouched rounds are the ones its generator
checked with the reference (pinned by sha256, and a seed-drawn sample is
checked again), every tampered round is checked here.
"""

from __future__ import annotations

import asyncio
import hashlib
import random
import re
import time

from harness import fixture, spans
from harness import traffic as gen
from reference import bls12_381 as rbls
from reference import drand as rdrand

FIXTURE_SAMPLE = 4


class _Source:
    """The upstream a catch-up client reads: the fixture, with the
    current request's tampered rounds."""

    def __init__(self, beacons, info):
        self._beacons = beacons
        self._info = info
        self.tamper: dict = {}

    def _b(self, r):
        return self.tamper.get(r) or self._beacons[r]

    async def get(self, round_no: int = 0):
        from drand_tpu.client.interface import result_from_beacon

        return result_from_beacon(self._b(round_no or len(self._beacons) - 1))

    async def get_span(self, lo: int, hi: int):
        import jax.profiler

        with jax.profiler.TraceAnnotation("bench.fetch"):
            return [self._b(r) for r in range(lo, hi)]

    async def info(self):
        return self._info

    async def close(self):
        pass


class Session:
    def __init__(self, cell: dict, root: str):
        from drand_tpu.chain.beacon import Beacon
        from drand_tpu.chain.info import Info
        from drand_tpu.crypto.curves import PointG1

        self.cfg, self.tr = cell["config"], cell["traffic"]
        self.chain = ch = fixture.load_chain(self.cfg, root)
        self.n = ch["rounds"]
        self.beacons = [None] + [
            Beacon(round=r, previous_sig=fixture.previous(ch, r),
                   signature=ch["v1"][r], signature_v2=ch["v2"][r])
            for r in range(1, self.n + 1)]
        self.info = Info(public_key=PointG1.from_bytes(ch["group_key"]),
                         period=self.cfg["period"],
                         genesis_time=self.cfg["genesis_time"],
                         genesis_seed=ch["genesis"])
        self.source = _Source(self.beacons, self.info)
        from drand_tpu.crypto import batch

        self._unwrap = spans.wrap(batch, "verify_beacons", "verify_beacons")
        self._loop = asyncio.new_event_loop()

    # ------------------------------------------------------------ requests
    def tampered(self, corrupt) -> dict:
        from drand_tpu.chain.beacon import Beacon

        if corrupt is None:
            return {}
        r, b = corrupt["round"], self.beacons[corrupt["round"]]
        other = (r + self.n // 2 - 1) % self.n + 1
        if corrupt["kind"] == "v2_swap":
            return {r: Beacon(r, b.previous_sig, b.signature,
                              self.beacons[other].signature_v2)}
        if corrupt["kind"] == "v1_relink":
            sig = self.beacons[other].signature
            out = {r: Beacon(r, b.previous_sig, sig, b.signature_v2)}
            if r < self.n:
                nb = self.beacons[r + 1]
                out[r + 1] = Beacon(r + 1, sig, nb.signature, nb.signature_v2)
            return out
        if corrupt["kind"] == "link_break":
            junk = hashlib.sha512(b"link-break %d" % r).digest()
            junk += hashlib.sha256(junk).digest()
            return {r: Beacon(r, junk[:96], b.signature, b.signature_v2)}
        raise ValueError(f"unknown corruption {corrupt['kind']!r}")

    async def _walk(self, req: dict) -> dict:
        import jax.profiler
        from drand_tpu.client.interface import ClientError
        from drand_tpu.client.verify import VerifyingClient

        self.source.tamper = self.tampered(req["corrupt"])
        client = VerifyingClient(self.source, strict_rounds=True,
                                 use_checkpoints=False)
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.walk"):
            try:
                got = await client.get(req["h"])
                verdict = ["accept", got.round]
            except ClientError as e:
                m = re.match(r"round (\d+)", str(e))
                verdict = (["reject", int(m.group(1))] if m
                           else ["error", str(e)[:200]])
            except Exception as e:  # noqa: BLE001 — counted as failed
                verdict = ["error", f"{type(e).__name__}: {e}"[:200]]
        self.source.tamper = {}
        committed = {"accept": req["h"], "reject": verdict[1] - 1}.get(
            verdict[0], 0)
        return {"req": req, "verdict": verdict, "committed": committed,
                "end": time.perf_counter(), "seconds": time.perf_counter() - t}

    # -------------------------------------------------------------- phases
    def warm(self) -> None:
        """A forged walk of each kind (the per-item fallback's shapes), and
        the shortest and the longest clean walk (the wire-RLC tier's, up to
        the largest chunk)."""
        clean = sorted(gen.clean_walks(self.tr), key=lambda r: r["h"])
        reqs = [gen.forged_walk(self.tr, k)
                for k in self.tr.get("forged", {}).get("kinds", [])]
        reqs += [clean[0], clean[-1]]
        for req in reqs:
            res = self._loop.run_until_complete(self._walk(req))
            if res["verdict"][0] == "error":
                raise RuntimeError(f"warm-up walk failed: {res}")

    def window(self, seed: int, seconds: float) -> dict:
        async def loop():
            walks, reqs = [], gen.walks(self.tr, seed)
            t0 = time.perf_counter()
            while not walks or walks[-1]["end"] - t0 < seconds:
                walks.append(await self._walk(next(reqs)))
            return t0, walks

        t0, walks = self._loop.run_until_complete(loop())
        forged = [w for w in walks if w["req"]["corrupt"]]
        return {"window_s": walks[-1]["end"] - t0,
                "work": sum(w["committed"] for w in walks),
                "attempted": len(walks),
                "failed": sum(w["verdict"][0] == "error" for w in walks),
                "walks": len(walks),
                "rejected": sum(w["verdict"][0] == "reject" for w in walks),
                "forged_walks": len(forged),
                "forged_s": sum(w["seconds"] for w in forged),
                "longest_accepted": max((w["verdict"][1] for w in walks
                                         if w["verdict"][0] == "accept"),
                                        default=0),
                "_walks": walks}

    def check(self, rec: dict, seed: int) -> dict:
        pub = rbls.G1.from_bytes(self.chain["group_key"])
        rng = random.Random(f"fixture-sample/{seed}")
        bad_fixture = sum(
            not self._ref_ok(pub, r, {}) for r in
            rng.sample(range(1, self.n + 1), FIXTURE_SAMPLE))
        memo: dict = {}
        mismatches = 0
        for w in rec["_walks"]:
            want = self.reference_verdict(pub, w["req"], memo)
            mismatches += w["verdict"] != want
        return {"verdict_mismatches": {"value": mismatches, "limit": 0},
                "fixture_rounds_refused": {"value": bad_fixture, "limit": 0},
                "walks_failed": {"value": rec["failed"], "limit": 0}}

    def _ref_ok(self, pub, r: int, tamper: dict) -> bool:
        b = tamper.get(r) or self.beacons[r]
        return rdrand.round_valid(pub, r, b.previous_sig, b.signature,
                                  b.signature_v2)

    def reference_verdict(self, pub, req: dict, memo: dict) -> list:
        """The verdict the reference reaches over the rounds the source
        served: untouched fixture rounds hold (the generator checked each
        with the reference), tampered ones are checked here."""
        tamper = self.tampered(req["corrupt"])
        key = (req["corrupt"] or {}).get("kind"), tuple(sorted(tamper))

        def sig_ok(r, prev, sig, sig_v2):
            if r not in tamper:
                return True
            if (key, r) not in memo:
                memo[(key, r)] = self._ref_ok(pub, r, tamper)
            return memo[(key, r)]

        rows = ((r, b.previous_sig, b.signature, b.signature_v2)
                for r in range(1, req["h"] + 1)
                for b in (tamper.get(r) or self.beacons[r],))
        bad = rdrand.first_invalid_round(self.chain["genesis"], rows, sig_ok)
        return ["accept", req["h"]] if bad is None else ["reject", bad]

    def close(self) -> None:
        self._unwrap()
        self._loop.close()
