"""Chaos network simulator: scripted fault schedules whose assertion
surface is the observability stack itself (ISSUE 11, ROADMAP #5).

The reference daemon survives partitions, lagging peers and gossip
abuse in the wild (PAPER.md: syncer/catch-up, gossip ban machinery) —
but until this module, only 2-3-node happy-path e2e tests ever
exercised the SLIs built in PRs 1/6/10. This harness runs an
in-process N=32-64 node beacon network on the injectable FakeClock and
drives it with declarative **fault schedules**: partitions (heal and
no-heal), per-link delay/jitter(reorder)/duplication/drop, per-node
clock skew, byzantine members (garbage partials, index framing),
external garbage floods, rolling crash-restart storms, and a
mid-ceremony reshare under churn. Every recovery invariant is asserted
THROUGH the existing surfaces — quorum margins, contribution bitmaps,
reachability/partition-suspect gauges, /healthz lag thresholds, DKG
phase timelines — never by peeking at protocol internals.

Design notes:

- **Per-node recorders** (``BeaconConfig.flight`` / ``.health``): every
  node gets its own :class:`~drand_tpu.obs.flight.FlightRecorder` and
  :class:`~drand_tpu.obs.health.HealthState`, exactly like
  one-process-per-node production. Without this, a byzantine node's
  own "valid" self-note would pollute the honest nodes' shared
  telemetry, and the singleton HealthState's monotonic-max head would
  make a minority-partition probe observe the majority's progress.
  ``TRACER`` and the global singletons still want
  ``obs.state.isolated_observability()`` around each scenario.

- **Deterministic time**: all nodes share one FakeClock base;
  :class:`SkewClock` gives each node an offset view (clock-skew
  faults). :meth:`ChaosBeaconNetwork.advance_round` steps the clock
  from wake target to wake target (``FakeClock.next_wake``) and lets
  the event loop + worker threads quiesce at each stop, so a delayed
  delivery is timestamped at ITS wake time — margins then read the
  injected fault, not scheduler noise.

- **Structural crypto** (:func:`structural_crypto`): a 32-node round
  costs ~4000 host pairings at ~58 ms each — minutes per round on the
  1-core box, which would make big-N chaos unrunnable. The context
  manager swaps the pairing-heavy leaves (partial sign/verify, round
  aggregation, chain verification) for structural blake2b stand-ins
  that preserve every verdict the observability layer depends on:
  partial bodies are index-bound (a wrong-index or garbage partial is
  "invalid" against the claimed index, like real crypto), recovery
  needs t distinct valid indices, recovered/chain signatures check
  against the per-message group digest. Scenarios about *verdict
  plumbing and timing* run under it; anything about real signatures
  belongs in the crypto suites.
"""

from __future__ import annotations

import asyncio
import hashlib
import random
from contextlib import contextmanager
from dataclasses import dataclass, field

import numpy as np

from ..chain import beacon as chain_beacon
from ..chain import time_math
from ..client import checkpoint as ckpt_mod
from ..chain.engine import crypto as engine_crypto
from ..chain.engine import handler as handler_mod
from ..chain.engine.handler import BeaconConfig, Handler
from ..chain.store import MemStore
from ..crypto import batch, tbls
from ..net.packets import PartialBeaconPacket
from ..net.transport import (LocalClient, LocalNetwork, ProtocolService,
                             TransportError)
from ..obs.flight import FlightRecorder
from ..obs.health import HealthState
from ..utils.clock import Clock, FakeClock
from ..utils.logging import default_logger
from .harness import make_test_group

# ---------------------------------------------------------------------------
# structural (fast) crypto
# ---------------------------------------------------------------------------

_SIG_HALF = 48  # half the 96-byte compressed-G2 wire size


def _h96(tag: bytes, msg: bytes) -> bytes:
    """96 bytes of shake-256 — the structural stand-in for a
    compressed G2 signature (same wire size, same determinism). One
    XOF call instead of two fixed-size digests: million-round
    structural chains hash on the bench/test critical path. The tag is
    length-prefixed so tag/message boundaries can't collide."""
    return hashlib.shake_256(
        len(tag).to_bytes(1, "big") + tag + msg).digest(96)


def group_sig(msg: bytes) -> bytes:
    """The structural group signature for ``msg`` — what recovery from
    ANY t-subset yields and what chain verification checks against."""
    return _h96(b"chaos-group", msg)


def partial_body(msg: bytes, index: int) -> bytes:
    """The structural partial-signature body for share ``index`` —
    index-BOUND so a wrong-index claim fails verification against the
    claimed index, mirroring pub_poly.eval(index) in real tbls."""
    return _h96(b"chaos-partial-%d" % index, msg)


def make_partial(msg: bytes, index: int) -> bytes:
    return index.to_bytes(tbls.INDEX_BYTES, "big") + partial_body(msg, index)


def _structural_verify_packet(pub, p: PartialBeaconPacket,
                              ckpt_msg: bytes | None = None) -> str | None:
    """Drop-in for chain.engine.handler._verify_partial_packet — same
    rejection strings, structural checks."""
    msg = chain_beacon.message(p.round, p.previous_sig)
    if (len(p.partial_sig) != tbls.PARTIAL_SIG_SIZE
            or p.partial_sig[tbls.INDEX_BYTES:]
            != partial_body(msg, tbls.index_of(p.partial_sig))):
        return "invalid partial signature"
    if p.partial_sig_v2:
        if len(p.partial_sig_v2) != tbls.PARTIAL_SIG_SIZE:
            return "invalid partial signature v2"
        if tbls.index_of(p.partial_sig_v2) != tbls.index_of(p.partial_sig):
            return "partial signature index mismatch"
        msg_v2 = chain_beacon.message_v2(p.round)
        if p.partial_sig_v2[tbls.INDEX_BYTES:] != partial_body(
                msg_v2, tbls.index_of(p.partial_sig_v2)):
            return "invalid partial signature v2"
    if p.partial_ckpt:
        if ckpt_msg is None:
            return "unexpected checkpoint partial"
        if tbls.index_of(p.partial_ckpt) != tbls.index_of(p.partial_sig):
            return "checkpoint partial index mismatch"
        if (len(p.partial_ckpt) != tbls.PARTIAL_SIG_SIZE
                or p.partial_ckpt[tbls.INDEX_BYTES:] != partial_body(
                    ckpt_msg, tbls.index_of(p.partial_ckpt))):
            return "invalid checkpoint partial"
    return None


def _structural_aggregate_round(pub_poly, msg: bytes, partials, t: int,
                                n: int, dst: bytes = b"", *,
                                prevalidated: bool = False):
    """Drop-in for crypto.batch.aggregate_round: t distinct in-group
    valid bodies recover the group digest; short counts raise the same
    ValueError shape the aggregator logs."""
    oks, seen = [], set()
    for p in partials:
        ok = (len(p) == tbls.PARTIAL_SIG_SIZE
              and tbls.index_of(p) < n
              and p[tbls.INDEX_BYTES:] == partial_body(
                  msg, tbls.index_of(p)))
        oks.append(ok)
        if ok:
            seen.add(tbls.index_of(p))
    if len(seen) < t:
        raise ValueError(f"not enough valid partials: {len(seen)} < {t}")
    return oks, group_sig(msg)


def _structural_verify_beacon(pubkey, b) -> bool:
    return b.signature == group_sig(
        chain_beacon.message(b.round, b.previous_sig))


def _structural_verify_beacon_v2(pubkey, b) -> bool:
    return b.signature_v2 == group_sig(chain_beacon.message_v2(b.round))


# group_sig's shake-256 input prefix for the inlined hot loop below —
# must stay byte-identical to _h96(b"chaos-group", ...)
_GROUP_PRE = len(b"chaos-group").to_bytes(1, "big") + b"chaos-group"
assert hashlib.shake_256(_GROUP_PRE + b"x").digest(96) == _h96(
    b"chaos-group", b"x")


def _structural_verify_beacons(pubkey, beacons, dst: bytes = b""):
    # hot loop: million-round catch-up walks verify through this
    # stand-in — group_sig(message(...)) is inlined (see the guard
    # above) to shed four Python call layers per beacon
    shake, sha, pre = hashlib.shake_256, hashlib.sha256, _GROUP_PRE
    gs = group_sig
    return np.fromiter(
        (b.signature == shake(
            pre + sha(b.previous_sig
                      + b.round.to_bytes(8, "big")).digest()).digest(96)
         and (not b.signature_v2
              or b.signature_v2 == gs(chain_beacon.message_v2(b.round)))
         for b in beacons),
        dtype=bool, count=len(beacons))


@contextmanager
def structural_crypto():
    """Swap the pairing-class leaves for the structural stand-ins (see
    module docstring). Restores everything on exit, including on
    failure — never leave a patched process for the next test."""

    def _sign_partial(self, msg: bytes) -> bytes:
        with self._lock:
            idx = self._share.pri_share.index
        return make_partial(msg, idx)

    def _structural_verify_checkpoint(pubkey, chain_hash, ckpt) -> bool:
        # mirrors client/checkpoint.py verify_checkpoint: same sanity
        # rejections, group-digest check instead of a BLS pairing
        if (ckpt.round < 1 or ckpt.chain_hash != chain_hash
                or not ckpt.signature or not ckpt.ckpt_sig):
            return False
        return ckpt.ckpt_sig == group_sig(ckpt_mod.checkpoint_message(
            ckpt.chain_hash, ckpt.round, ckpt.signature))

    saved = (engine_crypto.CryptoStore.sign_partial,
             handler_mod._verify_partial_packet,
             batch.aggregate_round, batch.verify_beacons,
             chain_beacon.verify_beacon, chain_beacon.verify_beacon_v2,
             ckpt_mod.verify_checkpoint)
    engine_crypto.CryptoStore.sign_partial = _sign_partial
    handler_mod._verify_partial_packet = _structural_verify_packet
    batch.aggregate_round = _structural_aggregate_round
    batch.verify_beacons = _structural_verify_beacons
    chain_beacon.verify_beacon = _structural_verify_beacon
    chain_beacon.verify_beacon_v2 = _structural_verify_beacon_v2
    ckpt_mod.verify_checkpoint = _structural_verify_checkpoint
    try:
        yield
    finally:
        (engine_crypto.CryptoStore.sign_partial,
         handler_mod._verify_partial_packet,
         batch.aggregate_round, batch.verify_beacons,
         chain_beacon.verify_beacon,
         chain_beacon.verify_beacon_v2,
         ckpt_mod.verify_checkpoint) = saved


# ---------------------------------------------------------------------------
# clocks + links
# ---------------------------------------------------------------------------

class SkewClock(Clock):
    """Per-node offset view over a shared base clock: ``now()`` reads
    ``base + skew`` (a skewed node computes boundaries early/late by
    exactly the skew), sleeps are durations on the base clock."""

    def __init__(self, base: Clock, skew: float = 0.0):
        self.base = base
        self.skew = skew

    def now(self) -> float:
        return self.base.now() + self.skew

    async def sleep(self, seconds: float) -> None:
        await self.base.sleep(seconds)


@dataclass
class LinkPolicy:
    """Per-link message mutation. ``jitter_s`` adds a uniform random
    extra delay per message — with concurrent per-peer sends that IS
    reordering; ``drop`` loses the message silently IN FLIGHT (the
    sender saw a successful send — receiver-side loss), while
    partitions/crashes surface as TransportError (sender-visible)."""

    delay_s: float = 0.0
    jitter_s: float = 0.0
    drop: float = 0.0
    dup: float = 0.0


class ChaosNet(LocalNetwork):
    """LocalNetwork + partitions and per-link policies."""

    def __init__(self, clock: Clock, seed: int = 7):
        super().__init__(seed)
        self.clock = clock
        self.rng = random.Random(seed)
        self._links: dict[tuple[str, str], LinkPolicy] = {}
        self._default_link: LinkPolicy | None = None
        self._partition: dict[str, int] | None = None

    # ---------------------------------------------------------- faults
    def partition(self, groups: list[list[str]]) -> None:
        """Addresses in different groups cannot reach each other (an
        address in no group is isolated from every listed one)."""
        self._partition = {addr: gi
                           for gi, grp in enumerate(groups)
                           for addr in grp}

    def heal(self) -> None:
        self._partition = None

    def set_link(self, src: str, dst: str,
                 policy: LinkPolicy | None) -> None:
        if policy is None:
            self._links.pop((src, dst), None)
        else:
            self._links[(src, dst)] = policy

    def set_default_link(self, policy: LinkPolicy | None) -> None:
        self._default_link = policy

    def clear_links(self) -> None:
        self._links.clear()
        self._default_link = None

    def link_policy(self, src: str, dst: str) -> LinkPolicy | None:
        return self._links.get((src, dst), self._default_link)

    # -------------------------------------------------------- delivery
    def _target(self, src: str, peer) -> ProtocolService:
        dst = peer.address() if hasattr(peer, "address") else str(peer)
        if self._partition is not None:
            gs = self._partition.get(src, -1)
            gd = self._partition.get(dst, -2)
            if gs != gd:
                raise TransportError(
                    f"{src} -> {dst}: partitioned (chaos)")
        return super()._target(src, peer)

    def client_for(self, address: str) -> "ChaosClient":
        return ChaosClient(self, address)


class ChaosClient(LocalClient):
    """LocalClient applying the link policy on the round-critical
    partial path (sync/DKG/info calls see partitions and downs via
    ``_target``, but not delay/drop — catch-up streams model their own
    faults at the peer level)."""

    async def partial_beacon(self, peer, packet: PartialBeaconPacket) -> None:
        net: ChaosNet = self._net
        dst = peer.address() if hasattr(peer, "address") else str(peer)
        pol = net.link_policy(self._addr, dst)
        if pol is not None:
            if pol.drop and net.rng.random() < pol.drop:
                # lost in flight: receiver never sees it, sender saw a
                # send (reachability must NOT flag the peer down)
                return
            d = pol.delay_s
            if pol.jitter_s:
                d += net.rng.random() * pol.jitter_s
            if d > 0:
                await net.clock.sleep(d)
            if pol.dup and net.rng.random() < pol.dup:
                svc = net._target(self._addr, peer)
                try:
                    await svc.process_partial_beacon(self._addr, packet)
                except TransportError:
                    pass  # the duplicate's reject never outranks the
                    # original delivery's verdict below
        await super().partial_beacon(peer, packet)


# ---------------------------------------------------------------------------
# byzantine member
# ---------------------------------------------------------------------------

class ByzantineCrypto:
    """Wraps a node's CryptoStore so its outbound partials are faulty.

    kinds: ``garbage`` — random bytes under its OWN index (a corrupted
    member; honest bitmaps mark it ``!``); ``wrong_index`` — a valid
    body under ANOTHER node's index prefix (index framing: the frame
    lands on the claimed index, which is exactly what real crypto does
    with an attacker-controlled prefix — documented in obs/flight)."""

    def __init__(self, inner, kind: str, rng: random.Random,
                 frame_index: int | None = None):
        self._inner = inner
        self._kind = kind
        self._rng = rng
        self._frame = frame_index

    def sign_partial(self, msg: bytes) -> bytes:
        own = self._inner.index()
        if self._kind == "wrong_index":
            claim = self._frame if self._frame is not None \
                else (own + 1) % len(self._inner.get_group())
            return claim.to_bytes(tbls.INDEX_BYTES, "big") \
                + partial_body(msg, own)
        return own.to_bytes(tbls.INDEX_BYTES, "big") \
            + self._rng.randbytes(2 * _SIG_HALF)

    def __getattr__(self, name):
        return getattr(self._inner, name)


# ---------------------------------------------------------------------------
# the harness
# ---------------------------------------------------------------------------

@dataclass
class RoundObservation:
    """One advanced round, read ONLY off the observability surfaces:
    the probe node's flight record (margin, bitmap), the health pull
    path (lag/missed/sync-stall — the same function /healthz drives),
    and the probe's reachability view."""

    round: int
    stored: bool
    head: int
    lag: int
    missed_total: int
    sync_stalled: bool
    margin_s: float | None
    bitmap: str
    suspects: int


@dataclass
class FaultEvent:
    """One scripted fault, applied just before advancing INTO
    ``at_round``. Actions (kwargs):

    - ``partition`` (groups=[[idx,...],...]) / ``heal``
    - ``link_all`` (policy=LinkPolicy|None) / ``link`` (src,dst,policy)
    - ``deny`` (src,dst) / ``allow`` (src,dst) — ONE-directional edge
      cut (asymmetric partitions: src's calls to dst fail while dst's
      calls to src still go through)
    - ``skew`` (node, seconds)
    - ``crash`` (nodes=[...]) / ``restart`` (nodes=[...])
    - ``byzantine`` (node, kind, frame_index=None)
    - ``flood`` (target, count, kind, round_offset)
    """

    at_round: int
    action: str
    kwargs: dict = field(default_factory=dict)


class ChaosBeaconNetwork:
    """N-node beacon network over a ChaosNet with per-node flight
    recorders and SkewClocks. Use under ``structural_crypto()`` (and
    ``isolated_observability()``) for anything beyond a handful of
    nodes/rounds."""

    def __init__(self, n: int, t: int, period: int = 4,
                 genesis_delay: int = 4, seed: bytes = b"chaos-dkg",
                 net_seed: int = 7, log_level: str = "none",
                 repair: bool = True):
        # repair=False runs the pre-ISSUE-12 passive plane (A/B
        # baselines: bench chaos_soak's with/without-repair comparison)
        self.repair = repair
        self.base_clock = FakeClock()
        self.genesis_time = int(self.base_clock.now()) + genesis_delay
        self.group, self.pairs, self.shares = make_test_group(
            n, t, period, self.genesis_time, seed=seed)
        self.network = ChaosNet(self.base_clock, seed=net_seed)
        self.clocks = [SkewClock(self.base_clock) for _ in range(n)]
        self.flights = [FlightRecorder() for _ in range(n)]
        # per-node health states (BeaconConfig.health): the process
        # singleton's head is a monotonic MAX across in-process nodes,
        # which would make a minority-partition probe observe the
        # majority's progress (lag 0 while its own chain stalls)
        self.healths = [HealthState() for _ in range(n)]
        self._logger = default_logger("chaos", level=log_level)
        self.handlers: list[Handler] = []
        self.stores = [MemStore() for _ in range(n)]
        for i in range(n):
            self.handlers.append(self._make_handler(i))
        self.crashed: set[int] = set()

    # ------------------------------------------------------------- build
    def addr(self, i: int) -> str:
        return self.pairs[i].public.addr

    def flight(self, i: int) -> FlightRecorder:
        return self.flights[i]

    def _make_handler(self, i: int) -> Handler:
        conf = BeaconConfig(
            public=self.group.nodes[i], share=self.shares[i],
            group=self.group, clock=self.clocks[i],
            flight=self.flights[i], health=self.healths[i],
            repair=self.repair)
        h = Handler(client=self.network.client_for(self.addr(i)),
                    store=self.stores[i], conf=conf,
                    logger=self._logger.named(f"n{i}"))
        self.network.register(self.addr(i), h)
        return h

    async def start_all(self) -> None:
        for h in self.handlers:
            await h.start()

    async def advance_to_genesis(self) -> None:
        await self.base_clock.advance_to(self.genesis_time)

    def stop_all(self) -> None:
        for h in self.handlers:
            h.stop()

    # ------------------------------------------------------------ faults
    def crash(self, i: int) -> None:
        self.handlers[i].stop()
        self.network.set_down(self.addr(i))
        self.crashed.add(i)

    async def restart(self, i: int) -> None:
        """Crash-restart: a FRESH handler over the surviving store (the
        process died; its chain db did not), rejoining via catchup."""
        self.network.set_down(self.addr(i), False)
        self.handlers[i] = self._make_handler(i)  # re-register replaces
        await self.handlers[i].catchup()
        self.crashed.discard(i)

    def skew(self, i: int, seconds: float) -> None:
        self.clocks[i].skew = seconds

    def partition(self, groups: list[list[int]]) -> None:
        self.network.partition(
            [[self.addr(i) for i in grp] for grp in groups])

    def heal(self) -> None:
        self.network.heal()

    def set_link_all(self, policy: LinkPolicy | None) -> None:
        self.network.set_default_link(policy)

    def make_byzantine(self, i: int, kind: str = "garbage",
                       frame_index: int | None = None) -> None:
        self.handlers[i].crypto = ByzantineCrypto(
            self.handlers[i].crypto, kind, self.network.rng,
            frame_index=frame_index)

    # ------------------------------------------------------- injections
    def make_bad_partial(self, round_no: int, claim_index: int,
                         kind: str = "garbage",
                         prev_sig: bytes | None = None,
                         ) -> PartialBeaconPacket:
        """An attacker-crafted packet: ``garbage`` (random body under
        the claimed index), ``wrong_index`` (another index's valid
        body), ``short`` (truncated)."""
        if prev_sig is None:
            prev_sig = self._head_beacon().signature
        msg = chain_beacon.message(round_no, prev_sig)
        if kind == "wrong_index":
            body = partial_body(msg, (claim_index + 1) % len(self.group))
        elif kind == "short":
            body = b"\x00" * 7
        else:
            body = self.network.rng.randbytes(2 * _SIG_HALF)
        sig = claim_index.to_bytes(tbls.INDEX_BYTES, "big") + body
        return PartialBeaconPacket(round=round_no, previous_sig=prev_sig,
                                   partial_sig=sig, partial_sig_v2=b"")

    async def inject_partials(self, packets, targets=None,
                              from_addr: str = "chaos-attacker:666") -> int:
        """Deliver crafted packets straight to target handlers' ingress
        (the real service surface). Returns how many were REJECTED
        (TransportError — window checks and verification)."""
        rejected = 0
        if targets is None:
            targets = [i for i in range(len(self.handlers))
                       if i not in self.crashed]
        for t in targets:
            for p in packets:
                try:
                    await self.handlers[t].process_partial_beacon(
                        from_addr, p)
                except TransportError:
                    rejected += 1
        return rejected

    # ---------------------------------------------------------- advance
    def _head(self, i: int) -> int:
        try:
            return self.stores[i].last().round
        except Exception:  # noqa: BLE001 — empty store during boot
            return 0

    def _head_beacon(self):
        probe = max(range(len(self.stores)), key=self._head)
        return self.stores[probe].last()

    async def advance_round(self) -> int:
        """Advance INTO the next round and step through the intra-round
        wake targets (delayed links, skewed tickers, catchup breathers)
        one by one; the clock quiesces at each, so deliveries timestamp
        at their own wake times. Returns the advanced-into round."""
        period = self.group.period
        now = self.base_clock.now()
        nxt, ttime = time_math.next_round(int(now), period,
                                          self.genesis_time)
        await self.base_clock.advance(ttime - now)
        end = ttime + period
        nw = self.base_clock.next_wake()
        while nw is not None and nw < end - 1e-9:
            await self.base_clock.advance(nw - self.base_clock.now())
            nw = self.base_clock.next_wake()
        return nxt

    # ------------------------------------------------------ observation
    def observe(self, round_no: int, probe: int = 0) -> RoundObservation:
        """Read the round off the probe node's observability surfaces —
        flight record + the same health pull `/healthz` drives."""
        rec = next((r for r in self.flights[probe].rounds(64)
                    if r["round"] == round_no), None)
        head = self._head(probe)
        snap = self.healths[probe].observe_chain(
            self.clocks[probe].now(), self.group.period,
            self.genesis_time, head_round=head)
        reach = self.flights[probe].reachability()
        return RoundObservation(
            round=round_no, stored=head >= round_no, head=head,
            lag=snap["lag_rounds"], missed_total=snap["missed_total"],
            sync_stalled=snap["sync_stalled"],
            margin_s=rec["margin_s"] if rec else None,
            bitmap=rec["bitmap"] if rec else "",
            suspects=sum(1 for up in reach.values() if not up))

    # --------------------------------------------------------- schedule
    async def apply(self, ev: FaultEvent) -> None:
        kw = ev.kwargs
        if ev.action == "partition":
            self.partition(kw["groups"])
        elif ev.action == "heal":
            self.heal()
            self.network.clear_links()
            self.network.allow_all()
        elif ev.action == "link_all":
            self.set_link_all(kw.get("policy"))
        elif ev.action == "link":
            self.network.set_link(self.addr(kw["src"]),
                                  self.addr(kw["dst"]), kw.get("policy"))
        elif ev.action == "deny":
            self.network.deny(self.addr(kw["src"]), self.addr(kw["dst"]))
        elif ev.action == "allow":
            self.network.allow(self.addr(kw["src"]), self.addr(kw["dst"]))
        elif ev.action == "skew":
            self.skew(kw["node"], kw["seconds"])
        elif ev.action == "crash":
            for i in kw["nodes"]:
                self.crash(i)
        elif ev.action == "restart":
            for i in kw["nodes"]:
                await self.restart(i)
        elif ev.action == "byzantine":
            self.make_byzantine(kw["node"], kw.get("kind", "garbage"),
                                kw.get("frame_index"))
        elif ev.action == "flood":
            head = self._head_beacon().round
            pkts = [self.make_bad_partial(
                head + kw.get("round_offset", 1), kw.get("claim", 0),
                kind=kw.get("kind", "garbage"))
                for _ in range(kw.get("count", 32))]
            await self.inject_partials(pkts,
                                       targets=kw.get("targets"))
        else:
            raise ValueError(f"unknown fault action: {ev.action}")

    async def run_schedule(self, schedule: list[FaultEvent], rounds: int,
                           probe: int = 0,
                           on_round=None) -> list[RoundObservation]:
        """Advance ``rounds`` rounds, applying each event just before
        advancing into its ``at_round``; returns per-round observations
        read off the probe's observability surfaces.

        ``on_round(round_no, now)`` — optional per-round-boundary hook
        run AFTER the probe observation (so health gauges are fresh):
        the incident-engine proof harness (ISSUE 15) drives its sampler
        here, exactly where a live node's store/probe hooks would."""
        by_round: dict[int, list[FaultEvent]] = {}
        for ev in schedule:
            by_round.setdefault(ev.at_round, []).append(ev)
        out: list[RoundObservation] = []
        for _ in range(rounds):
            nxt, _t = time_math.next_round(
                int(self.base_clock.now()), self.group.period,
                self.genesis_time)
            for ev in by_round.get(nxt, []):
                await self.apply(ev)
            advanced = await self.advance_round()
            out.append(self.observe(advanced, probe))
            if on_round is not None:
                on_round(advanced, self.clocks[probe].now())
        return out

    # ---------------------------------------------------------- reshare
    async def reshare_under_churn(self, silent_dealers: set[int],
                                  threshold: int | None = None,
                                  phase_timeout: float = 10.0,
                                  nonce: bytes = b"chaos-reshare"):
        """Mid-ceremony reshare while the beacon network keeps running
        on the same clock (churn): ``silent_dealers`` never run their
        protocol. Returns the live nodes' DistKeyShare results; the
        stall is asserted through FLIGHT.dkg phase timelines (the
        global recorder — DKG sessions are keyed per node tag)."""
        from ..dkg import DKGConfig, DKGProtocol, LocalBoard

        n = len(self.group)
        live = [i for i in range(n) if i not in silent_dealers]
        boards = LocalBoard.make_group(n)
        configs = {
            i: DKGConfig(
                longterm=self.pairs[i], nonce=nonce,
                new_nodes=self.group.nodes,
                threshold=threshold or self.group.threshold,
                old_nodes=self.group.nodes,
                public_coeffs=list(self.group.public_key.coefficients),
                old_threshold=self.group.threshold,
                share=self.shares[i].pri_share,
                clock=self.clocks[i], phase_timeout=phase_timeout,
                seed=b"chaos-reshare-poly")
            for i in live}

        async def drive() -> None:
            # the beacon rounds keep ticking underneath: churn
            for _ in range(8):
                await self.base_clock.advance(phase_timeout)

        runs = asyncio.gather(*(DKGProtocol(configs[i], boards[i]).run()
                                for i in live))
        await asyncio.gather(runs, drive())
        return runs.result()


# ---------------------------------------------------------------------------
# report math (shared by tests and bench.py chaos_soak)
# ---------------------------------------------------------------------------

def detection_lead(observations: list[RoundObservation], period: float,
                   warn_fraction: float = 0.5) -> dict:
    """Margin-warning → missed-round lead time. ``warn_round`` is the
    first round whose quorum margin dropped below
    ``warn_fraction * period`` (or that never reached quorum);
    ``missed_round`` the first where the missed counter moved."""
    base_missed = observations[0].missed_total if observations else 0
    warn_round = missed_round = None
    for ob in observations:
        if warn_round is None and (
                ob.margin_s is None
                or ob.margin_s < warn_fraction * period):
            warn_round = ob.round
        if missed_round is None and ob.missed_total > base_missed:
            missed_round = ob.round
            break
    lead = (missed_round - warn_round
            if warn_round is not None and missed_round is not None
            else None)
    return {"warn_round": warn_round, "missed_round": missed_round,
            "lead_rounds": lead,
            "lead_seconds": lead * period if lead is not None else None}


def recovery_seconds(observations: list[RoundObservation],
                     heal_round: int, period: float) -> float | None:
    """Fault heal → lag back to 0, in (fake-clock) seconds."""
    for ob in observations:
        if ob.round >= heal_round and ob.lag == 0:
            return (ob.round - heal_round) * period
    return None
