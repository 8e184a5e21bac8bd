"""Daemon-level integration: real DKG over the transport, beacon rounds,
resharing with transition, and restart-from-disk.

Reference coverage model: core/drand_test.go (TestRunDKG :40,
TestRunDKGReshare :182, TestDrandPublicChainInfo via harness) driven by the
DrandTest2 rig (core/util_test.go:32) — here over LocalNetwork with a fake
clock, through the real control-plane entry points (init_dkg_leader/
init_dkg_follower/init_reshare_*), with NO synthesize_shares anywhere.
"""

import asyncio

import pytest

from drand_tpu.chain.beacon import verify_beacon, verify_beacon_v2
from drand_tpu.core.config import Config
from drand_tpu.core.daemon import Drand
from drand_tpu.key.store import FileStore
from drand_tpu.net.transport import LocalNetwork
from drand_tpu.testing.quiesce import wait_for
from drand_tpu.utils.clock import FakeClock

SECRET = b"setup-secret-0123456789abcdef"
PERIOD = 5


def make_daemon(i, net, clock, tmp_path, db=False):
    addr = f"d{i}.test:70{i:02d}"
    ks = FileStore(str(tmp_path / f"node{i}"))
    conf = Config(clock=clock, dkg_timeout=10,
                  db_path=str(tmp_path / f"node{i}" / "chain.db") if db else "")
    d = Drand.fresh(ks, conf, net.client_for(addr), addr)
    net.register(addr, d)
    return addr, ks, conf, d


def _head(daemon):
    try:
        return daemon.beacon.chain.last().round
    except Exception:  # noqa: BLE001 — no beacon or empty chain yet
        return 0


async def wait_chain(daemon, round_no, timeout=30.0):
    await wait_for(lambda: _head(daemon) >= round_no,
                   lambda: f"{daemon.priv.public.addr} stuck at "
                           f"{_head(daemon)}", timeout)


async def form_network(n, t, net, clock, tmp_path, db=False):
    daemons = []
    leader_addr = None
    for i in range(n):
        addr, *_, d = make_daemon(i, net, clock, tmp_path, db=db)
        leader_addr = leader_addr or addr
        daemons.append(d)
    tasks = [asyncio.ensure_future(
        daemons[0].init_dkg_leader(n, t, PERIOD, SECRET, timeout=20))]
    for d in daemons[1:]:
        tasks.append(asyncio.ensure_future(
            d.init_dkg_follower(leader_addr, SECRET, timeout=20)))
    groups = await asyncio.gather(*tasks)
    assert all(g.hash() == groups[0].hash() for g in groups)
    return daemons, groups[0]


@pytest.mark.asyncio
async def test_daemon_dkg_to_beacon(tmp_path):
    clock = FakeClock()
    net = LocalNetwork()
    daemons, group = await form_network(3, 2, net, clock, tmp_path)
    assert group.public_key is not None
    await clock.advance_to(group.genesis_time)
    for _ in range(3):
        await clock.advance(PERIOD)
    for d in daemons:
        await wait_chain(d, 3)
        pub = group.public_key.key()
        for r in range(1, 4):
            b = d.beacon.chain.get(r)
            assert verify_beacon(pub, b)
            assert b.is_v2() and verify_beacon_v2(pub, b)
    for d in daemons:
        d.stop()


@pytest.mark.asyncio
async def test_daemon_restart_from_disk(tmp_path):
    """Kill a node, reload it from its key store + chain db, catch up."""
    clock = FakeClock()
    net = LocalNetwork()
    daemons, group = await form_network(3, 2, net, clock, tmp_path, db=True)
    await clock.advance_to(group.genesis_time)
    for _ in range(2):
        await clock.advance(PERIOD)
    for d in daemons:
        await wait_chain(d, 2)

    # kill node 2: unregister + stop
    victim = daemons[2]
    addr2 = victim.priv.public.addr
    victim.stop()
    net.unregister(addr2)
    for _ in range(3):
        await clock.advance(PERIOD)
    for d in daemons[:2]:
        await wait_chain(d, 5)

    # reload from disk: identity, group, share and chain all persisted
    ks = FileStore(str(tmp_path / "node2"))
    conf = Config(clock=clock, dkg_timeout=10,
                  db_path=str(tmp_path / "node2" / "chain.db"))
    revived = Drand.load(ks, conf, net.client_for(addr2))
    assert revived.group is not None and revived.share is not None
    assert revived.group.hash() == group.hash()
    assert revived.share.pri_share == victim.share.pri_share
    net.register(addr2, revived)
    revived.start_beacon(catchup=True)
    await asyncio.sleep(0.05)  # let catchup sync run
    await wait_chain(revived, 5)
    await clock.advance(PERIOD)
    for d in daemons[:2] + [revived]:
        await wait_chain(d, 6)
    for d in daemons[:2] + [revived]:
        d.stop()


@pytest.mark.asyncio
async def test_daemon_reshare_grows_group(tmp_path):
    """3-of-2 network reshares to 4 nodes (threshold 3): the chain identity
    and distributed key survive, the new node serves rounds after T."""
    clock = FakeClock()
    net = LocalNetwork()
    daemons, group = await form_network(3, 2, net, clock, tmp_path)
    await clock.advance_to(group.genesis_time)
    for _ in range(2):
        await clock.advance(PERIOD)
    for d in daemons:
        await wait_chain(d, 2)

    # add node 3 (fresh keypair, knows the old group file out of band)
    addr3, ks3, conf3, joiner = make_daemon(3, net, clock, tmp_path)
    leader_addr = daemons[0].priv.public.addr
    reshare_secret = b"reshare-secret-aaaaaaaaaaaaaaaa"
    tasks = [asyncio.ensure_future(
        daemons[0].init_reshare_leader(4, 3, reshare_secret, timeout=20))]
    for d in daemons[1:]:
        tasks.append(asyncio.ensure_future(
            d.init_reshare_follower(leader_addr, reshare_secret, timeout=20)))
    tasks.append(asyncio.ensure_future(
        joiner.init_reshare_follower(leader_addr, reshare_secret,
                                     old_group=group, timeout=20)))
    new_groups = await asyncio.gather(*tasks)
    new_group = new_groups[0]
    assert all(g.hash() == new_group.hash() for g in new_groups)
    # chain identity preserved
    assert new_group.genesis_seed == group.genesis_seed
    assert new_group.public_key.key() == group.public_key.key()
    assert len(new_group) == 4 and new_group.threshold == 3

    # cross the transition boundary and keep producing
    await clock.advance_to(new_group.transition_time)
    for _ in range(3):
        await clock.advance(PERIOD)
    t_round = group.current_round(new_group.transition_time)
    target = t_round + 2
    for d in daemons + [joiner]:
        await wait_chain(d, target)
        pub = new_group.public_key.key()
        b = d.beacon.chain.get(target)
        assert verify_beacon(pub, b)
    for d in daemons + [joiner]:
        d.stop()


@pytest.mark.asyncio
async def test_reshare_timeout_aborts_and_retry_succeeds(tmp_path):
    """Adversarial reshare path (core/drand_test.go:261 timeout case): a
    reshare whose participants never show up times out WITHOUT disturbing
    the running chain, and a subsequent reshare attempt succeeds."""
    clock = FakeClock()
    net = LocalNetwork()
    daemons, group = await form_network(2, 2, net, clock, tmp_path)
    await clock.advance_to(group.genesis_time)
    await clock.advance(PERIOD)
    for d in daemons:
        await wait_chain(d, 1)

    reshare_secret = b"reshare-secret-aaaaaaaaaaaaaaaa"
    # expected_n=3 but nobody else signals: leader setup must time out
    with pytest.raises(TimeoutError, match="participants signalled"):
        await daemons[0].init_reshare_leader(3, 2, reshare_secret,
                                             timeout=0.5)
    assert daemons[0]._setup_mgr is None, "failed setup not cleaned up"

    # chain still alive on the OLD group
    await clock.advance(PERIOD)
    for d in daemons:
        await wait_chain(d, 2)
        assert verify_beacon(group.public_key.key(), d.beacon.chain.get(2))

    # retry with the full membership: succeeds and transitions
    tasks = [asyncio.ensure_future(
        daemons[0].init_reshare_leader(2, 2, reshare_secret, timeout=20))]
    tasks.append(asyncio.ensure_future(
        daemons[1].init_reshare_follower(daemons[0].priv.public.addr,
                                         reshare_secret, timeout=20)))
    new_groups = await asyncio.gather(*tasks)
    assert new_groups[0].hash() == new_groups[1].hash()
    assert new_groups[0].public_key.key() == group.public_key.key()
    for d in daemons:
        d.stop()


@pytest.mark.asyncio
async def test_second_setup_rejected_unless_forced(tmp_path):
    """Preemption guard (core/drand_test.go:182 preempt case +
    drand_control.go force flag): a second concurrent setup errors
    without force; with force it cancels the pending one."""
    from drand_tpu.core.daemon import DrandError
    from drand_tpu.core.setup import SetupPreempted

    clock = FakeClock()
    net = LocalNetwork()
    daemons, group = await form_network(2, 2, net, clock, tmp_path)
    await clock.advance_to(group.genesis_time)
    await clock.advance(PERIOD)
    for d in daemons:
        await wait_chain(d, 1)

    reshare_secret = b"reshare-secret-aaaaaaaaaaaaaaaa"
    # first reshare waits for a third participant that never comes
    first = asyncio.ensure_future(
        daemons[0].init_reshare_leader(3, 2, reshare_secret, timeout=30))
    await asyncio.sleep(0.05)
    assert daemons[0]._setup_mgr is not None

    # un-forced second setup is rejected while the first is pending
    with pytest.raises(DrandError, match="already in progress"):
        await daemons[0].init_reshare_leader(2, 2, reshare_secret,
                                             timeout=5)
    assert not first.done()

    # forced second setup preempts the first and completes
    second = asyncio.ensure_future(
        daemons[0].init_reshare_leader(2, 2, reshare_secret, timeout=20,
                                       force=True))
    follower = asyncio.ensure_future(
        daemons[1].init_reshare_follower(daemons[0].priv.public.addr,
                                         reshare_secret, timeout=20))
    with pytest.raises(SetupPreempted):
        await first
    new_groups = await asyncio.gather(second, follower)
    assert new_groups[0].hash() == new_groups[1].hash()
    assert new_groups[0].public_key.key() == group.public_key.key()
    for d in daemons:
        d.stop()


@pytest.mark.asyncio
async def test_force_preempts_follower_awaiting_group(tmp_path):
    """ADVICE r5: a forced second init while a FOLLOWER setup is still
    awaiting the leader's group packet must cancel that wait (no
    SetupManager exists on the follower side) instead of raising 'the
    DKG phase is already running' — no DKG is running yet."""
    from drand_tpu.core.daemon import DrandError

    clock = FakeClock()
    net = LocalNetwork()
    lead_addr, *_, d_lead = make_daemon(0, net, clock, tmp_path)
    _, *_, d_fol = make_daemon(1, net, clock, tmp_path)

    # leader collects 3 participants (never completes) so the follower's
    # signal is accepted and it parks awaiting the group push
    lead_task = asyncio.ensure_future(
        d_lead.init_dkg_leader(3, 2, PERIOD, SECRET, timeout=30))
    await asyncio.sleep(0.05)
    first = asyncio.ensure_future(
        d_fol.init_dkg_follower(lead_addr, SECRET, timeout=30))
    await asyncio.sleep(0.05)
    assert d_fol._group_packet is not None
    assert not d_fol._group_packet.done()
    assert d_fol._setup_mgr is None  # follower setups have no manager

    # un-forced second init is still rejected
    with pytest.raises(DrandError, match="already in progress"):
        await d_fol.init_dkg_follower(lead_addr, SECRET, timeout=5)
    assert not first.done()

    # forced second init preempts the parked follower: the first init
    # unwinds via the cancelled group-packet future, the second owns the
    # setup slot and parks awaiting a (new) group push
    second = asyncio.ensure_future(
        d_fol.init_dkg_follower(lead_addr, SECRET, timeout=30, force=True))
    with pytest.raises(asyncio.CancelledError):
        await first
    await asyncio.sleep(0.05)
    assert not second.done()
    assert d_fol._group_packet is not None
    assert not d_fol._group_packet.done()

    for t in (second, lead_task):
        t.cancel()
    await asyncio.gather(second, lead_task, return_exceptions=True)


@pytest.mark.parametrize("now,joins_by", [(99, "start"), (101, "catchup")])
def test_dkg_finished_after_genesis_catches_up(now, joins_by):
    """A member whose DKG ends after genesis (the others passed its
    phases at the deadline while it compiled device kernels) joins the
    running chain by catch-up; before genesis it starts fresh."""
    from types import SimpleNamespace

    called = []

    class Beacon:
        async def start(self):
            called.append("start")

        async def catchup(self):
            called.append("catchup")

    node = SimpleNamespace(
        store=None, conf=SimpleNamespace(clock=FakeClock(now)),
        _l=SimpleNamespace(info=lambda *a, **k: None))
    node._make_handler = lambda group, share: setattr(node, "beacon",
                                                      Beacon())
    group = SimpleNamespace(genesis_time=100)
    result = SimpleNamespace(commits=[], pri_share=None, qual=[0])

    async def run():
        await Drand._adopt_dkg_output(node, group, result, fresh=True)
        await asyncio.sleep(0)

    asyncio.run(run())
    assert called == [joins_by]
