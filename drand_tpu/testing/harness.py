"""In-process multi-node test harness.

Reproduces the reference's test machinery (SURVEY.md §4):
- DKG-bypass share synthesis from a master polynomial
  (chain/beacon/node_test.go:52-104 dkgShares)
- in-process multi-node network with fault injection
  (core/util_test.go:32 DrandTest2, :450 DenyClient)
- fake clock driving rounds deterministically.
"""

from __future__ import annotations

from dataclasses import dataclass

from ..chain.engine.handler import BeaconConfig, Handler
from ..chain.store import MemStore, Store
from ..crypto.poly import PriPoly
from ..key.group import Group
from ..key.keys import DistPublic, Node, Pair, Share, new_key_pair
from ..net.transport import LocalNetwork
from ..utils.clock import Clock, FakeClock
from ..utils.logging import default_logger
from .quiesce import wait_for


def synthesize_shares(n: int, t: int, seed: bytes = b"test-dkg") -> tuple[list[Share], DistPublic]:
    """Create n shares of a fresh t-of-n secret WITHOUT running the DKG —
    equivalent output distribution (the DKG's sum of polynomials is itself a
    random polynomial)."""
    poly = PriPoly.random(t, seed=seed)
    pub = poly.commit()
    shares = [
        Share(commits=list(pub.commits), pri_share=s) for s in poly.shares(n)
    ]
    return shares, DistPublic(list(pub.commits))


def make_test_group(
    n: int,
    t: int,
    period: int,
    genesis_time: int,
    seed: bytes = b"test-dkg",
    catchup_period: int = 0,
) -> tuple[Group, list[Pair], list[Share]]:
    pairs = [
        new_key_pair(f"node-{i}.test:8{i:03d}", seed=b"pair%d" % i + seed)
        for i in range(n)
    ]
    shares, dist = synthesize_shares(n, t, seed=seed)
    nodes = [Node(identity=p.public, index=i) for i, p in enumerate(pairs)]
    group = Group(
        nodes=nodes,
        threshold=t,
        period=period,
        genesis_time=genesis_time,
        catchup_period=catchup_period or max(1, period // 2),
        public_key=dist,
    )
    group.get_genesis_seed()
    return group, pairs, shares


def _head(store: Store) -> int:
    try:
        return store.last().round
    except Exception:  # noqa: BLE001 — empty store during boot
        return 0


@dataclass
class TestNode:
    pair: Pair
    share: Share
    store: Store
    handler: Handler

    @property
    def addr(self) -> str:
        return self.pair.public.addr


class BeaconTestNetwork:
    """n-node beacon network over an in-memory transport with a fake clock.

    Usage:
        net = BeaconTestNetwork(n=3, t=2, period=2)
        await net.start_all()
        await net.advance_rounds(5)
        net.check_chain(...)
    """

    def __init__(self, n: int, t: int, period: int = 2,
                 genesis_delay: int = 2, clock: Clock | None = None,
                 store_factory=None, seed: bytes = b"test-dkg"):
        self.clock = clock or FakeClock()
        self.genesis_time = int(self.clock.now()) + genesis_delay
        self.group, self.pairs, self.shares = make_test_group(
            n, t, period, self.genesis_time, seed=seed
        )
        self.network = LocalNetwork()
        self.nodes: list[TestNode] = []
        store_factory = store_factory or (lambda i: MemStore())
        logger = default_logger("beacon-test", level="none")
        for i in range(n):
            store = store_factory(i)
            conf = BeaconConfig(
                public=self.group.nodes[i],
                share=self.shares[i],
                group=self.group,
                clock=self.clock,
            )
            handler = Handler(
                client=self.network.client_for(self.pairs[i].public.addr),
                store=store,
                conf=conf,
                logger=logger.named(f"n{i}"),
            )
            self.network.register(self.pairs[i].public.addr, handler)
            self.nodes.append(TestNode(self.pairs[i], self.shares[i], store, handler))

    async def start_all(self, indices: list[int] | None = None) -> None:
        for i, node in enumerate(self.nodes):
            if indices is None or i in indices:
                await node.handler.start()

    async def advance_to_genesis(self) -> None:
        await self.clock.advance_to(self.genesis_time)

    async def advance_rounds(self, k: int, settle_s: float = 0.0) -> None:
        """Advance the fake clock k periods, letting each round complete."""
        for _ in range(k):
            await self.clock.advance(self.group.period)

    async def wait_round(self, node_idx: int, round_no: int, timeout: float = 30.0) -> None:
        """Wait (real time) until the node's chain reaches round_no."""
        store = self.nodes[node_idx].store
        await wait_for(
            lambda: _head(store) >= round_no,
            lambda: f"node {node_idx} never reached round {round_no} "
                    f"(at {_head(store)})", timeout)

    def stop_all(self) -> None:
        for node in self.nodes:
            node.handler.stop()
