"""The Pedersen DKG / resharing state machine.

Replaces kyber's `dkg.Protocol` as the reference drives it
(core/drand_control.go:123 runDKG, :196 runResharing; config fields
Suite/Longterm/NewNodes/OldNodes/PublicCoeffs/Threshold/OldThreshold/
FastSync/Nonce/Auth — :126-141, :205-246):

Phases (phaser-bounded, fast-sync short-circuits when all expected bundles
arrived):
  DEAL          every dealer commits to a secret polynomial and sends each
                receiver an ECIES-encrypted share evaluation.
  RESPONSE      every receiver verifies its deals and broadcasts a verdict
                per dealer (approval / complaint).
  JUSTIFICATION complained-against dealers reveal the disputed share in
                plaintext; everyone re-verifies against the commitments.
  FINISH        QUAL = dealers with a valid deal and no unresolved
                complaint. Fresh DKG: share_j = Σ_{i∈QUAL} f_i(j), commits
                summed pointwise. Resharing: dealers share their OLD share
                (f_i(0) = s_i, bound by PublicCoeffs), and the new share is
                the Lagrange combination Σ λ_i f_i(j) over an old-threshold
                QUAL subset — the group key is preserved.

Fresh DKG: dealers == receivers == new_nodes. Resharing: dealers are the
old group, receivers the new group; a node can be either or both. Nodes
leaving the group deal but receive no share (pri_share=None).
"""

from __future__ import annotations

import asyncio
import secrets
from dataclasses import dataclass, field

from ..crypto import batch, ecies, schnorr
from ..crypto.curves import PointG1
from ..crypto.fields import R
from ..crypto.poly import PriPoly, PriShare, PubPoly, lagrange_coefficients
from ..key.keys import Node, Pair
from ..obs.flight import FLIGHT
from ..utils.clock import Clock, SystemClock
from ..utils.logging import KVLogger, default_logger
from .board import Board
from .packets import (
    Deal,
    DealBundle,
    Justification,
    JustificationBundle,
    Response,
    ResponseBundle,
    STATUS_APPROVAL,
    STATUS_COMPLAINT,
)
from .phaser import Phase, TimePhaser


class DKGError(Exception):
    pass


# dealing: receivers per asyncio.to_thread ECIES hand-off (bounded chunks
# keep cancellation responsive and never park a whole n=1024 encrypt run
# on one executor slot)
_DEAL_ENC_CHUNK = 32
# admission: dealers per on-loop work slice between cooperative yields
_ADMIT_CHUNK = 32


@dataclass
class DKGConfig:
    longterm: Pair
    nonce: bytes
    new_nodes: list[Node]
    threshold: int
    # resharing inputs (all-or-nothing):
    old_nodes: list[Node] | None = None
    public_coeffs: list[PointG1] | None = None
    old_threshold: int = 0
    share: PriShare | None = None  # our old share (dealers in a reshare)
    # protocol knobs
    fast_sync: bool = True
    phase_timeout: float = 10.0
    clock: Clock = field(default_factory=SystemClock)
    logger: KVLogger | None = None
    seed: bytes | None = None  # deterministic dealer polynomial (tests only)

    @property
    def resharing(self) -> bool:
        return self.old_nodes is not None

    def dealers(self) -> list[Node]:
        return self.old_nodes if self.resharing else self.new_nodes


@dataclass
class DistKeyShare:
    """kyber dkg.DistKeyShare analogue (core/drand.go:166 WaitDKG output)."""

    commits: list[PointG1]
    pri_share: PriShare | None  # None for a dealer leaving the group
    qual: list[int]             # dealer indices in QUAL

    def public_key(self) -> PointG1:
        return self.commits[0]


class DKGProtocol:
    def __init__(self, conf: DKGConfig, board: Board):
        self.c = conf
        self.board = board
        self._l = (conf.logger or default_logger("dkg")).named("proto")
        dealers = conf.dealers()
        self._dealer_index = _index_of(dealers, conf.longterm)
        self._share_index = _index_of(conf.new_nodes, conf.longterm)
        if self._dealer_index is None and self._share_index is None:
            raise DKGError("longterm key neither deals nor receives")
        if conf.resharing:
            if not conf.public_coeffs or not conf.old_threshold:
                raise DKGError("resharing requires public_coeffs and old_threshold")
            if self._dealer_index is not None and conf.share is None:
                raise DKGError("resharing dealer needs its old share")
            self._old_pub = PubPoly(list(conf.public_coeffs))
        else:
            self._old_pub = None
        self._phaser = TimePhaser(conf.clock, conf.phase_timeout)
        self._sid: str | None = None  # flight-recorder session (run())
        # receiver state
        self._valid_shares: dict[int, int] = {}      # dealer_index -> f_i(me)
        self._valid_commits: dict[int, PubPoly] = {}  # dealer_index -> G_i
        self._complaints_open: dict[int, set[int]] = {}  # dealer -> share idxs
        self._approvals: dict[int, set[int]] = {}    # dealer -> approving idxs

    # ------------------------------------------------------------------ run
    async def run(self) -> DistKeyShare:
        """Execute all phases; returns the distributed key share.

        Every phase transition, bundle arrival (by issuer index) and
        the QUAL outcome land in the flight recorder's DKG timeline
        (``/debug/flight/dkg``) — indices and clock offsets only, never
        shares or key material — so a wedged DKG names the phase and
        the silent dealers instead of demanding log archaeology."""
        dealers = self.c.dealers()
        n_recv = len(self.c.new_nodes)
        sid = FLIGHT.dkg.begin(
            self.c.nonce, mode="reshare" if self.c.resharing else "dkg",
            n_dealers=len(dealers), n_receivers=n_recv,
            threshold=self.c.threshold, now=self.c.clock.now(),
            # role-qualified tag: in a reshare an old-only dealer and a
            # new receiver can share the same numeric index — their
            # in-process timelines must not collide
            tag=(f"s{self._share_index}" if self._share_index is not None
                 else f"d{self._dealer_index}"))
        self._sid = sid
        try:
            FLIGHT.dkg.note_phase(sid, "deal", now=self.c.clock.now())
            my_poly = None
            if self._dealer_index is not None:
                my_poly = self._make_poly()
                await self.board.push_deals(
                    await self._make_deal_bundle(my_poly))

            deals = await self._collect(
                self.board.deals, expect=len(dealers),
                issuer=lambda b: b.dealer_index,
                note=lambda b: FLIGHT.dkg.note_bundle(
                    sid, "deal", b.dealer_index, now=self.c.clock.now()))
            await self._process_deals(deals)

            FLIGHT.dkg.note_phase(sid, "response", now=self.c.clock.now())
            if self._share_index is not None:
                await self.board.push_responses(
                    self._make_response_bundle(dealers))
            responses = await self._collect(
                self.board.responses, expect=n_recv,
                issuer=lambda b: b.share_index,
                note=lambda b: FLIGHT.dkg.note_bundle(
                    sid, "response", b.share_index, now=self.c.clock.now()))
            for b in responses:
                self._process_response(b, dealers)

            any_complaints = any(self._complaints_open.values())
            if any_complaints:
                FLIGHT.dkg.note_phase(sid, "justification",
                                      now=self.c.clock.now())
                if self._dealer_index is not None and \
                        self._complaints_open.get(self._dealer_index):
                    await self.board.push_justifications(
                        self._make_justification_bundle(my_poly))
                complained = [d for d, s in self._complaints_open.items()
                              if s]
                justs = await self._collect(
                    self.board.justifications, expect=len(complained),
                    issuer=lambda b: b.dealer_index,
                    note=lambda b: FLIGHT.dkg.note_bundle(
                        sid, "justification", b.dealer_index,
                        now=self.c.clock.now()))
                await self._process_justifications(justs)

            FLIGHT.dkg.note_phase(sid, "finish", now=self.c.clock.now())
            result = self._finish(dealers)
        except BaseException as e:
            FLIGHT.dkg.finish(sid, now=self.c.clock.now(),
                              complaints=self._complaints_open,
                              error=repr(e))
            raise
        FLIGHT.dkg.finish(sid, now=self.c.clock.now(), qual=result.qual,
                          complaints=self._complaints_open)
        return result

    # ------------------------------------------------------------- dealing
    def _make_poly(self) -> PriPoly:
        if self.c.resharing:
            # constant term MUST be our old share (bound by public_coeffs)
            coeffs = [self.c.share.value]
            for k in range(1, self.c.threshold):
                coeffs.append(_rand_scalar(self.c.seed, self._dealer_index, k))
            return PriPoly(coeffs)
        coeffs = [_rand_scalar(self.c.seed, self._dealer_index, k)
                  for k in range(self.c.threshold)]
        return PriPoly(coeffs)

    async def _make_deal_bundle(self, poly: PriPoly) -> DealBundle:
        """Dealing made O(n)-cheap for large groups: all receiver
        evaluations in ONE scalar Horner sweep (PriPoly.eval_many), the
        commitment via the fixed-base comb (PriPoly.commit), and the n
        ECIES encrypts — two 255-bit point muls each, the dominant
        dealing cost at n=1024 — handed to ``asyncio.to_thread`` in
        bounded chunks so the dealer never parks the event loop behind
        ~30 s of sequential encryption."""
        nodes = self.c.new_nodes
        commit_pts, shares = await asyncio.to_thread(
            lambda: (poly.commit().commits,
                     poly.eval_many([n.index for n in nodes])))
        commits = tuple(c.to_bytes() for c in commit_pts)
        deals: list[Deal] = []
        for s0 in range(0, len(nodes), _DEAL_ENC_CHUNK):
            deals.extend(await asyncio.to_thread(
                self._encrypt_deals, nodes[s0:s0 + _DEAL_ENC_CHUNK],
                shares[s0:s0 + _DEAL_ENC_CHUNK]))
        bundle = DealBundle(
            dealer_index=self._dealer_index, commits=commits,
            deals=tuple(deals), session_id=self.c.nonce)
        return _signed(bundle, self.c.longterm)

    @staticmethod
    def _encrypt_deals(nodes: list[Node], shares) -> list[Deal]:
        return [Deal(share_index=n.index,
                     encrypted_share=ecies.encrypt(
                         n.identity.key, s.value.to_bytes(32, "big")))
                for n, s in zip(nodes, shares)]

    async def _process_deals(self, bundles) -> None:
        """Admit a phase's deal bundles and check our own shares, every
        per-dealer check batched into ONE dispatch per kind per phase:

        - parse: ``batch.parse_commits`` — decompression plus one
          lockstep G1 membership chain over every pending commit point;
        - reshare binding: ``batch.reshare_bindings`` — all dealers'
          ``old_pub.eval(dealer_index)`` as one multi-point evaluation
          (device) or one RLC 2-MSM verdict (host), not n Horner walks;
        - own share: ``batch.eval_commits`` (every admitted polynomial
          at our index, one dispatch) + ``batch.share_checks`` (every
          g·s through one fixed-base-comb pass).

        The engine dispatches (``reshare_bindings``, ``eval_commits``)
        run on a worker thread; the rest is sliced into
        _ADMIT_CHUNK-dealer chunks with a cooperative yield between
        slices, so a n=1024 admission cannot starve the phase clock
        (tests/test_zz_dkg_scale.py proves the response window still
        closes under FakeClock). Rejections are attributable: each mints
        dkg_bundle_rejects_total{phase,verdict} and a flight-recorder
        note instead of a silent drop."""
        from .. import metrics

        pending = []
        for b in bundles:
            if b.dealer_index in self._valid_commits:
                continue  # first bundle per dealer wins (_collect dedups)
            if len(b.commits) != self.c.threshold:
                metrics.DKG_BUNDLE_REJECTS.labels(
                    phase="deal", verdict="wrong_threshold").inc()
                self._note_reject("deal", "wrong_threshold",
                                  b.dealer_index)
                continue
            pending.append(b)

        admitted: list[tuple[DealBundle, PubPoly]] = []
        for s0 in range(0, len(pending), _ADMIT_CHUNK):
            chunk = pending[s0:s0 + _ADMIT_CHUNK]
            for b, pts in zip(chunk,
                              batch.parse_commits(
                                  [b.commits for b in chunk])):
                if pts is None:
                    metrics.DKG_BUNDLE_REJECTS.labels(
                        phase="deal", verdict="bad_point").inc()
                    self._note_reject("deal", "bad_point", b.dealer_index)
                    continue
                admitted.append((b, PubPoly(pts)))
            await asyncio.sleep(0)

        if self._old_pub is not None and admitted:
            # dealer constant terms must be their OLD public shares —
            # the key-preservation binding of a reshare, decided for
            # the whole phase in one batched dispatch
            verdicts = await asyncio.to_thread(
                batch.reshare_bindings, self._old_pub,
                [(b.dealer_index, pub.commit()) for b, pub in admitted])
            kept = []
            for (b, pub), ok in zip(admitted, verdicts):
                if not ok:
                    metrics.DKG_BUNDLE_REJECTS.labels(
                        phase="deal", verdict="binding_mismatch").inc()
                    self._note_reject("deal", "binding_mismatch",
                                      b.dealer_index)
                    continue
                kept.append((b, pub))
            admitted = kept

        for b, pub in admitted:
            self._valid_commits[b.dealer_index] = pub
        if self._share_index is None or not admitted:
            return

        evals = await asyncio.to_thread(
            batch.eval_commits, [pub for _, pub in admitted],
            self._share_index)
        checks: list[tuple[int, int, PointG1]] = []
        for s0 in range(0, len(admitted), _ADMIT_CHUNK):
            for (b, _), ev in zip(admitted[s0:s0 + _ADMIT_CHUNK],
                                  evals[s0:s0 + _ADMIT_CHUNK]):
                val = self._decrypt_own_deal(b)
                if val is not None:
                    checks.append((b.dealer_index, val, ev))
            await asyncio.sleep(0)
        oks = batch.share_checks([(val, ev) for _, val, ev in checks])
        for (dealer, val, _), ok in zip(checks, oks):
            if ok:
                self._valid_shares[dealer] = val
            else:
                metrics.DKG_BUNDLE_REJECTS.labels(
                    phase="deal", verdict="bad_share").inc()
                self._note_reject("deal", "bad_share", dealer)

    def _decrypt_own_deal(self, b: DealBundle) -> int | None:
        """Our share value from this bundle's deal for our index, or
        None (no deal for us / malformed ciphertext — the latter leads
        to a complaint exactly as a bad share does)."""
        for d in b.deals:
            if d.share_index != self._share_index:
                continue
            try:
                plain = ecies.decrypt(self.c.longterm.key,
                                      d.encrypted_share)
                return int.from_bytes(plain, "big") % R
            except Exception:  # noqa: BLE001 — malformed ciphertext
                return None
        return None

    def _note_reject(self, phase: str, verdict: str, issuer: int) -> None:
        """Log + flight-note one rejected bundle/item. The
        dkg_bundle_rejects_total counter is minted branch-literally at
        each call site (tools/check_metrics.py KNOWN_LABEL_VALUES lints
        literal label kwargs only)."""
        self._l.warn("dkg", "bundle_reject", phase=phase, verdict=verdict,
                     issuer=issuer)
        if self._sid is not None:
            FLIGHT.dkg.note_reject(self._sid, phase, issuer, verdict,
                                   now=self.c.clock.now())

    # ----------------------------------------------------------- responses
    def _make_response_bundle(self, dealers: list[Node]) -> ResponseBundle:
        responses = []
        for node in dealers:
            ok = node.index in self._valid_shares
            responses.append(Response(
                dealer_index=node.index,
                status=STATUS_APPROVAL if ok else STATUS_COMPLAINT))
        bundle = ResponseBundle(
            share_index=self._share_index, responses=tuple(responses),
            session_id=self.c.nonce)
        return _signed(bundle, self.c.longterm)

    def _process_response(self, b: ResponseBundle, dealers: list[Node]) -> None:
        from .. import metrics

        dealer_idxs = {n.index for n in dealers}
        for r in b.responses:
            if r.dealer_index not in dealer_idxs:
                metrics.DKG_BUNDLE_REJECTS.labels(
                    phase="response", verdict="unknown_dealer").inc()
                self._note_reject("response", "unknown_dealer",
                                  b.share_index)
                continue
            if r.status == STATUS_COMPLAINT:
                self._complaints_open.setdefault(r.dealer_index, set()).add(
                    b.share_index)
            else:
                self._approvals.setdefault(r.dealer_index, set()).add(
                    b.share_index)

    # ------------------------------------------------------ justifications
    def _make_justification_bundle(self, poly: PriPoly) -> JustificationBundle:
        justs = []
        for idx in sorted(self._complaints_open.get(self._dealer_index, ())):
            justs.append(Justification(share_index=idx,
                                       share=poly.eval(idx).value))
        bundle = JustificationBundle(
            dealer_index=self._dealer_index, justifications=tuple(justs),
            session_id=self.c.nonce)
        return _signed(bundle, self.c.longterm)

    async def _process_justifications(self, bundles) -> None:
        """A phase's justification bundles verified in batch: each
        complained dealer's admitted commitment polynomial evaluated at
        ALL its disputed share indices in one dispatch
        (crypto.batch.eval_poly_indices — the many-indices dual of
        eval_commits), then every revealed-share g·s check through one
        fixed-base-comb pass (crypto.batch.share_checks) — replacing
        the per-bundle 255-bit generator ladders of the old
        _process_justification. State transitions are identical to the
        sequential loop: a passing justification closes the complaint
        (and, for our own index, adopts the now-public share); a
        failing one leaves it open and mints an attributable reject."""
        from .. import metrics

        todo = []
        for b in bundles:
            pub = self._valid_commits.get(b.dealer_index)
            opened = self._complaints_open.get(b.dealer_index, set())
            if pub is None or not opened:
                continue
            wanted = [j for j in b.justifications
                      if j.share_index in opened]
            if wanted:
                todo.append((b.dealer_index, pub, wanted))
        if not todo:
            return
        # the engine dispatches run on a worker thread, like admission's
        evals = await asyncio.to_thread(lambda: [
            batch.eval_poly_indices(pub, [j.share_index for j in wanted])
            for _, pub, wanted in todo])
        work: list[tuple[int, int, int, PointG1]] = [
            (dealer, j.share_index, j.share % R, ev)
            for (dealer, _, wanted), evs in zip(todo, evals)
            for j, ev in zip(wanted, evs)]
        oks = batch.share_checks([(val, ev) for _, _, val, ev in work])
        for (dealer, idx, val, _), ok in zip(work, oks):
            if ok:
                self._complaints_open[dealer].discard(idx)
                if idx == self._share_index:
                    # the revealed (now public) share is still OUR share
                    self._valid_shares[dealer] = val
            else:
                metrics.DKG_BUNDLE_REJECTS.labels(
                    phase="justification", verdict="bad_share").inc()
                self._note_reject("justification", "bad_share", dealer)

    # --------------------------------------------------------------- finish
    def _finish(self, dealers: list[Node]) -> DistKeyShare:
        qual = [n.index for n in dealers
                if n.index in self._valid_commits
                and not self._complaints_open.get(n.index)]
        need = self.c.old_threshold if self.c.resharing else self.c.threshold
        if len(qual) < need:
            raise DKGError(f"QUAL too small: {len(qual)} < {need} "
                           f"(qual={qual})")
        self._l.info("dkg", "qual", members=qual)

        if not self.c.resharing:
            commits = None
            for i in qual:
                pub = self._valid_commits[i]
                commits = pub if commits is None else commits.add(pub)
            pri = None
            if self._share_index is not None:
                missing = [i for i in qual if i not in self._valid_shares]
                if missing:
                    raise DKGError(f"missing shares from QUAL dealers {missing}")
                val = sum(self._valid_shares[i] for i in qual) % R
                pri = PriShare(self._share_index, val)
            return DistKeyShare(commits=list(commits.commits), pri_share=pri,
                                qual=qual)

        # resharing: Lagrange-combine an old-threshold subset of QUAL.
        # The subset MUST be canonical across nodes (first old_threshold of
        # QUAL, which every node derives from the broadcast responses) —
        # a locally-chosen subset would yield divergent group commitments.
        subset = qual[: self.c.old_threshold]
        if self._share_index is not None:
            missing = [i for i in subset if i not in self._valid_shares]
            if missing:
                raise DKGError(
                    f"reshare: missing shares from canonical QUAL subset "
                    f"{missing}")
        lambdas = lagrange_coefficients(subset)
        # generic over the commitment point type (the structural
        # large-group harness substitutes a stand-in group)
        cls = type(self._valid_commits[subset[0]].commits[0])
        commits = []
        for k in range(self.c.threshold):
            acc = cls.infinity()
            for i in subset:
                acc = acc + self._valid_commits[i].commits[k].mul(lambdas[i])
            commits.append(acc)
        pri = None
        if self._share_index is not None:
            val = sum(self._valid_shares[i] * lambdas[i] for i in subset) % R
            pri = PriShare(self._share_index, val)
        return DistKeyShare(commits=commits, pri_share=pri, qual=qual)

    # ------------------------------------------------------------- plumbing
    async def _collect(self, queue: asyncio.Queue, expect: int, issuer,
                       note=None):
        """Drain a board queue until the phase times out — or, under
        fast-sync, as soon as `expect` distinct issuers have arrived.
        ``note`` is called with each newly-accepted bundle AS IT
        ARRIVES (the flight recorder's per-issuer arrival offsets)."""
        items: list = []
        seen: set[int] = set()
        deadline = asyncio.ensure_future(self._phaser.next_phase())
        try:
            while True:
                if self.c.fast_sync and len(seen) >= expect:
                    return items
                get = asyncio.ensure_future(queue.get())
                done, _ = await asyncio.wait(
                    {get, deadline}, return_when=asyncio.FIRST_COMPLETED)
                if get in done:
                    b = get.result()
                    if issuer(b) not in seen:
                        seen.add(issuer(b))
                        items.append(b)
                        if note is not None:
                            note(b)
                else:
                    get.cancel()
                if deadline in done:
                    return items
        finally:
            if not deadline.done():
                deadline.cancel()


def _index_of(nodes: list[Node], pair: Pair) -> int | None:
    for n in nodes:
        if n.identity.key == pair.public.key:
            return n.index
    return None


def _signed(bundle, pair: Pair):
    sig = schnorr.sign(pair.key, bundle.hash())
    return type(bundle)(**{**bundle.__dict__, "signature": sig})


def _rand_scalar(seed: bytes | None, dealer: int, k: int) -> int:
    if seed is None:
        return secrets.randbelow(R - 1) + 1
    from ..crypto.fields import fr_from_seed

    return fr_from_seed(b"dkg-coeff",
                        seed + bytes([dealer & 0xFF, k & 0xFF]))
