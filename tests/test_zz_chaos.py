"""Chaos network simulator (ISSUE 11): scripted fault schedules whose
assertion surface is the observability stack — quorum margins,
contribution bitmaps, reachability/partition-suspect gauges, /healthz
lag thresholds, ingress-reject counters, DKG phase timelines. No
scenario peeks at protocol internals.

Everything here is host-only: the structural
crypto mode replaces the pairing-class leaves, so no device graphs and
no fresh XLA compiles.
"""

import asyncio
import json
import logging

import aiohttp
import grpc
import grpc.aio
import pytest
from conftest import sample_count as _sample_count

from drand_tpu import metrics
from drand_tpu.client.direct import DirectClient
from drand_tpu.http_server.server import PublicServer
from drand_tpu.obs.flight import FLIGHT
from drand_tpu.obs.health import HEALTH, READY_MAX_LAG
from drand_tpu.obs.state import isolated_observability
from drand_tpu.testing.chaos import (ChaosBeaconNetwork, FaultEvent,
                                     LinkPolicy, detection_lead,
                                     recovery_seconds, structural_crypto)

PERIOD = 4


def _rejects(source, verdict):
    return _sample_count(metrics.GROUP_REGISTRY,
                         "beacon_ingress_rejects",
                         source=source, verdict=verdict)


async def _get(port, path):
    async with aiohttp.ClientSession() as s:
        async with s.get(f"http://127.0.0.1:{port}{path}") as r:
            try:
                body = await r.json()
            except Exception:  # noqa: BLE001 — non-JSON error bodies
                body = {}
            return r.status, body


# ---------------------------------------------------------------------------
# 1. the acceptance scenario: margin degrades BEFORE missed fires
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_margin_degrades_rounds_before_missed_fires():
    """Healthy rounds hold margin ≈ period; a cross-link delay fault
    drags the quorum margin under period/2 for several rounds while
    beacon_rounds_missed_total stays flat; only the subsequent no-quorum
    partition moves the missed counter — the early-warning SLI
    demonstrably led the failure. After heal, catch-up closes the lag
    (recovery measured through the same surfaces)."""
    with structural_crypto(), isolated_observability():
        net = ChaosBeaconNetwork(n=8, t=5, period=PERIOD)
        q0 = _sample_count(metrics.GROUP_REGISTRY,
                           "beacon_quorum_margin_seconds")
        await net.start_all()
        await net.advance_to_genesis()
        sched = [
            FaultEvent(4, "link_all",
                       {"policy": LinkPolicy(delay_s=2.5)}),
            FaultEvent(7, "partition",
                       {"groups": [[0, 1, 2, 3], [4, 5, 6, 7]]}),
            FaultEvent(11, "heal"),
        ]
        obs = await net.run_schedule(sched, rounds=14)
        net.stop_all()

        by_round = {ob.round: ob for ob in obs}
        first = obs[0].round
        # healthy phase: quorum landed on the boundary, full margin
        for r in range(first, 4):
            assert by_round[r].margin_s == pytest.approx(PERIOD)
            assert by_round[r].missed_total == 0
        # degraded phase: margin = period - delay, under the warn line,
        # while the missed counter has still never moved
        for r in range(4, 7):
            assert by_round[r].margin_s == pytest.approx(
                PERIOD - 2.5, abs=0.3)
            assert by_round[r].margin_s < PERIOD / 2
            assert by_round[r].missed_total == 0
        lead = detection_lead(obs, PERIOD)
        assert lead["warn_round"] == 4
        assert lead["missed_round"] is not None
        assert lead["lead_rounds"] >= 3
        # the partition (both fragments < t) is what finally fires it
        assert max(ob.missed_total for ob in obs) >= 3
        # the partitioned probe fingers the other fragment as suspects
        assert by_round[8].suspects == 4
        # heal: lag returns to 0 within a bounded catch-up window
        rec = recovery_seconds(obs, 11, PERIOD)
        assert rec is not None and rec <= 4 * PERIOD
        assert obs[-1].margin_s == pytest.approx(PERIOD)
        assert obs[-1].suspects == 0
        # the margin SLI observed samples throughout
        assert _sample_count(metrics.GROUP_REGISTRY,
                             "beacon_quorum_margin_seconds") > q0


# ---------------------------------------------------------------------------
# 2. the bitmap fingers exactly the faulted peer set
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_bitmap_fingers_exact_faulted_peer_set():
    """Crash node 5 and corrupt node 4 (garbage partials under its own
    index): the honest probe's contribution bitmap settles on exactly
    {4: '!', 5: '.'} with every honest column on time, and the per-peer
    invalid counter charges only the byzantine index."""
    with structural_crypto(), isolated_observability():
        net = ChaosBeaconNetwork(n=6, t=4, period=PERIOD)
        await net.start_all()
        await net.advance_to_genesis()
        sched = [
            FaultEvent(3, "crash", {"nodes": [5]}),
            FaultEvent(3, "byzantine", {"node": 4, "kind": "garbage"}),
        ]
        obs = await net.run_schedule(sched, rounds=6)
        net.stop_all()

        faulted_rounds = [ob for ob in obs if ob.round >= 4]
        assert faulted_rounds
        for ob in faulted_rounds:
            assert ob.stored, "quorum (t=4 of 4 honest) must survive"
            assert ob.bitmap[5] == ".", ob.bitmap
            assert ob.bitmap[4] in "!.", ob.bitmap
            for honest in range(4):
                assert ob.bitmap[honest] in "#~", ob.bitmap
        # at least one round caught the byzantine partial in its ring
        assert any(ob.bitmap[4] == "!" for ob in faulted_rounds)
        # faulted set == {4, 5}, exactly
        fingered = {i for ob in faulted_rounds
                    for i in range(6) if ob.bitmap[i] in "!."}
        assert fingered == {4, 5}
        peers = net.flight(0).peers()
        assert peers["4"]["invalid"] > 0
        for honest in range(4):
            assert peers[str(honest)]["invalid"] == 0
        # the crashed node is dark, not framed: no invalid charged to 5
        assert peers.get("5", {}).get("invalid", 0) == 0


# ---------------------------------------------------------------------------
# 3. /healthz and /readyz transition at the documented lag threshold
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_healthz_readyz_transition_at_documented_lag():
    """Quorum loss (t crashed) stalls the chain: /healthz flips 200 ->
    503 exactly past DRAND_TPU_READY_MAX_LAG rounds of lag, /readyz
    agrees, the sync-stall gauge rises through the same probe, and the
    restart storm brings both back to 200."""
    with structural_crypto(), isolated_observability():
        net = ChaosBeaconNetwork(n=5, t=4, period=PERIOD)
        await net.start_all()
        await net.advance_to_genesis()
        await net.run_schedule([], rounds=2)
        server = PublicServer(DirectClient(net.handlers[0]),
                              clock=net.clocks[0])
        site = await server.start("127.0.0.1", 0)
        port = site._server.sockets[0].getsockname()[1]
        try:
            status, body = await _get(port, "/healthz")
            assert status == 200 and body["status"] == "ok"
            assert body["lag_rounds"] <= body["max_lag"] == READY_MAX_LAG
            status, body = await _get(port, "/readyz")
            assert status == 200 and body["ready"] is True

            # kill quorum: only 3 of t=4 members remain
            for i in (3, 4):
                net.crash(i)
            # within the documented bound the probe still reports ok
            await net.run_schedule([], rounds=READY_MAX_LAG)
            status, body = await _get(port, "/healthz")
            assert status == 200, body
            # one more lagging round crosses the threshold: 503 + stall
            await net.run_schedule([], rounds=2)
            status, body = await _get(port, "/healthz")
            assert status == 503 and body["status"] == "lagging"
            assert body["lag_rounds"] > body["max_lag"]
            assert body["sync_stalled"] is True
            assert metrics.SYNC_STALLED._value.get() == 1
            status, body = await _get(port, "/readyz")
            assert status == 503 and body["ready"] is False
            assert "head lag" in body["reason"]
            missed_mid = _sample_count(metrics.GROUP_REGISTRY,
                                       "beacon_rounds_missed")
            assert missed_mid > 0

            # restart storm: the members return and the chain catches up
            for i in (3, 4):
                await net.restart(i)
            for _ in range(6):
                await net.advance_round()
                status, body = await _get(port, "/healthz")
                if status == 200:
                    break
            assert status == 200 and body["status"] == "ok"
            assert body["sync_stalled"] is False
            status, body = await _get(port, "/readyz")
            assert status == 200 and body["ready"] is True
        finally:
            await server.stop()
            net.stop_all()


# ---------------------------------------------------------------------------
# 4. per-node clock skew
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_clock_skew_flags_late_peer_and_degrades_margin():
    """A node whose clock runs 3 s behind broadcasts that much after
    every boundary: with t=3 of 4 punctual peers the quorum is safe,
    but once a second node is dark the skewed partial IS the t-th —
    the margin degrades by exactly the skew and the bitmap marks the
    peer late ('~')."""
    with structural_crypto(), isolated_observability():
        net = ChaosBeaconNetwork(n=5, t=3, period=PERIOD)
        await net.start_all()
        await net.advance_to_genesis()
        sched = [
            FaultEvent(3, "skew", {"node": 3, "seconds": -3.0}),
            FaultEvent(3, "crash", {"nodes": [4]}),
            FaultEvent(3, "crash", {"nodes": [2]}),
        ]
        obs = await net.run_schedule(sched, rounds=5)
        net.stop_all()

        skewed = [ob for ob in obs if ob.round >= 4]
        assert skewed
        for ob in skewed:
            assert ob.stored
            # quorum waits for the skewed node: margin = period - skew
            assert ob.margin_s == pytest.approx(PERIOD - 3.0, abs=0.3)
            assert ob.bitmap[3] == "~", ob.bitmap
        peers = net.flight(0).peers()
        assert peers["3"]["late"] >= len(skewed)
        assert peers["0"]["late"] == 0


# ---------------------------------------------------------------------------
# 5. garbage floods: DoS posture + the reject counter closes the gap
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_flood_dos_posture_and_reject_visibility():
    """An attacker floods one node with stale/future/garbage partials:
    every rejection lands on beacon_ingress_rejects_total{grpc,verdict}
    (the new chaos-surfaced SLI — floods were invisible before), no
    flood round ever evicts live flight records, out-of-group claims
    are never attributed, and the chain keeps storing on the boundary."""
    with structural_crypto(), isolated_observability():
        net = ChaosBeaconNetwork(n=5, t=3, period=PERIOD)
        await net.start_all()
        await net.advance_to_genesis()
        await net.run_schedule([], rounds=2)
        # the attacker crafts off the public chain tip
        head_b = net.stores[0].last()
        head, head_sig = head_b.round, head_b.signature
        r0_stale = _rejects("grpc", "stale")
        r0_future = _rejects("grpc", "future")
        r0_invalid = _rejects("grpc", "invalid")

        stale = [net.make_bad_partial(1, 1, prev_sig=b"\x00" * 96)
                 for _ in range(10)]
        future = [net.make_bad_partial(head + 50, 1) for _ in range(10)]
        garbage = [net.make_bad_partial(head + 1, 2, kind="garbage",
                                        prev_sig=head_sig)
                   for _ in range(10)]
        outofgroup = [net.make_bad_partial(head + 1, 999, kind="garbage",
                                           prev_sig=head_sig)]
        n_rej = await net.inject_partials(
            stale + future + garbage + outofgroup, targets=[0])
        assert n_rej == 31  # every crafted packet was rejected

        assert _rejects("grpc", "stale") == r0_stale + 10
        assert _rejects("grpc", "future") == r0_future + 10
        assert _rejects("grpc", "invalid") == r0_invalid + 11
        # in-window garbage charged the claimed in-group index only
        peers = net.flight(0).peers()
        assert peers["2"]["invalid"] == 10
        assert "999" not in peers
        # live records survived the flood and the chain still advances
        assert net.flight(0).rounds(4), "flood evicted live records"
        obs = await net.run_schedule([], rounds=2)
        net.stop_all()
        for ob in obs:
            assert ob.stored and ob.missed_total == 0
            assert ob.margin_s == pytest.approx(PERIOD)


# ---------------------------------------------------------------------------
# 6. rolling crash-restart storm
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_rolling_crash_restart_storm_never_loses_quorum():
    """A rolling storm (two nodes down at a time, restarting as the
    next pair drops) stays above t the whole way: zero missed rounds,
    reachability dips exactly while peers are down, and the final
    bitmap returns to full participation."""
    with structural_crypto(), isolated_observability():
        net = ChaosBeaconNetwork(n=8, t=5, period=PERIOD)
        await net.start_all()
        await net.advance_to_genesis()
        sched = [
            FaultEvent(3, "crash", {"nodes": [1, 2]}),
            FaultEvent(5, "restart", {"nodes": [1, 2]}),
            FaultEvent(5, "crash", {"nodes": [3, 4]}),
            FaultEvent(7, "restart", {"nodes": [3, 4]}),
            FaultEvent(7, "crash", {"nodes": [5, 6]}),
            FaultEvent(9, "restart", {"nodes": [5, 6]}),
        ]
        obs = await net.run_schedule(sched, rounds=10)
        net.stop_all()

        for ob in obs:
            assert ob.stored, f"round {ob.round} missed during the storm"
            assert ob.missed_total == 0
        # suspects tracked the storm and cleared after it
        assert max(ob.suspects for ob in obs) >= 2
        assert obs[-1].suspects == 0
        assert obs[-1].bitmap == "#" * 8
        # every send outcome landed on the per-peer counter
        assert _sample_count(metrics.GROUP_REGISTRY,
                             "beacon_peer_sends", outcome="failed") > 0


# ---------------------------------------------------------------------------
# 7. mid-ceremony reshare under churn
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_reshare_under_churn_stalls_in_the_right_phase():
    """A reshare with one silent dealer while beacon rounds keep
    ticking: the DKG timeline shows the deal phase running its FULL
    phaser window (the stall is visible in the right phase), the
    complaint map names exactly the silent dealer, QUAL excludes it,
    dkg_phase_seconds observed samples — and the beacon chain never
    missed a round during the ceremony."""
    with structural_crypto(), isolated_observability():
        net = ChaosBeaconNetwork(n=5, t=3, period=PERIOD)
        d0 = _sample_count(metrics.GROUP_REGISTRY, "dkg_phase_seconds",
                           phase="deal")
        await net.start_all()
        await net.advance_to_genesis()
        await net.run_schedule([], rounds=2)
        results = await net.reshare_under_churn({4}, phase_timeout=10.0)
        obs = await net.run_schedule([], rounds=2)
        net.stop_all()

        sessions = FLIGHT.dkg.sessions()
        assert len(sessions) == 4
        for s in sessions:
            assert s["mode"] == "reshare" and s["done"]
            assert s["error"] is None
            assert s["qual"] == [0, 1, 2, 3]
            assert s["complaints"] == {"4": [0, 1, 2, 3]}
            assert sorted(s["bundles"]["deal"]) == ["0", "1", "2", "3"]
            phases = [p["phase"] for p in s["phases"]]
            assert phases == ["deal", "response", "justification",
                              "finish"]
            deal = s["phases"][0]
            # fast-sync could not fire (4 of 5 dealers): the deal phase
            # ran its whole 10 s window — the stall, in the right phase
            assert deal["end_s"] - deal["start_s"] == pytest.approx(10.0)
        assert _sample_count(metrics.GROUP_REGISTRY, "dkg_phase_seconds",
                             phase="deal") >= d0 + 4
        assert all(r.qual == [0, 1, 2, 3] for r in results)
        # the chain rode through the ceremony: no missed rounds
        for ob in obs:
            assert ob.stored and ob.missed_total == 0


# ---------------------------------------------------------------------------
# 8. gossip flood: ban machinery + the reject counter
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_gossip_flood_is_counted_then_banned():
    """A flood of invalid beacons into a gossip node's real Publish
    port lands every rejection on beacon_ingress_rejects_total
    {source=gossip} until the source IP trips the ban; once banned,
    further publishes are refused at the door (PERMISSION_DENIED) —
    observable to the flooder itself — and no flood message was ever
    cached or re-forwarded (the tip never moved)."""
    from drand_tpu.net import wire
    from drand_tpu.relay import gossip as g
    from drand_tpu.chain.beacon import Beacon
    from drand_tpu.chain.info import Info
    from drand_tpu.utils.clock import FakeClock

    with structural_crypto(), isolated_observability():
        net = ChaosBeaconNetwork(n=3, t=2, period=PERIOD)
        info = Info.from_group(net.group)
        clock = FakeClock(start=info.genesis_time + 1000)
        node = g.GossipNode(info, clock=clock)
        await node.serve("127.0.0.1:0")
        ch = grpc.aio.insecure_channel(f"127.0.0.1:{node.port}")
        publish = ch.unary_unary(f"/{g.SERVICE}/Publish")
        r0 = _rejects("gossip", "invalid")
        try:
            banned = 0
            for i in range(g.SCORE_INVALID_LIMIT + 5):
                bad = Beacon(round=2 + i % 3,
                             previous_sig=bytes([i]) * 96,
                             signature=b"\x99" * 96)
                try:
                    await publish(wire.encode(bad), timeout=5.0)
                except grpc.aio.AioRpcError as e:
                    assert e.code() == grpc.StatusCode.PERMISSION_DENIED
                    banned += 1
            # the ban tripped mid-flood and refused the rest at the door
            assert banned >= 5
            rejected = _rejects("gossip", "invalid") - r0
            assert rejected >= g.SCORE_INVALID_LIMIT
            # nothing was cached or re-forwarded
            assert node._tip == 0
        finally:
            await ch.close()
            await node.stop()


# ---------------------------------------------------------------------------
# 9. secret hygiene under faults (real crypto)
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_no_secret_reaches_logs_metrics_or_flight_under_faults(
        caplog):
    """The PR-10 hygiene check under fault load, with REAL crypto so
    the shares actually flow: run crash + flood faults at debug logging
    and assert no node's secret share (decimal or hex) appears in any
    log line, the /metrics exposition, the flight dump, or the health
    snapshot."""
    caplog.set_level(logging.DEBUG)
    with isolated_observability():
        net = ChaosBeaconNetwork(n=3, t=2, period=PERIOD,
                                 log_level="debug")
        for name in list(logging.Logger.manager.loggerDict):
            if name.startswith("chaos"):
                logging.getLogger(name).setLevel(logging.DEBUG)
        await net.start_all()
        await net.advance_to_genesis()
        sched = [FaultEvent(2, "crash", {"nodes": [2]})]
        obs = await net.run_schedule(sched, rounds=2)
        head = net.stores[0].last()
        await net.inject_partials(
            [net.make_bad_partial(head.round + 1, 1, kind="garbage",
                                  prev_sig=head.signature)],
            targets=[0])
        net.stop_all()
        assert any(ob.stored for ob in obs), "no rounds under real crypto"

        blob = "\n".join(r.getMessage() for r in caplog.records)
        blob += metrics.render().decode()
        blob += json.dumps({"rounds": net.flight(0).rounds(16),
                            "peers": net.flight(0).peers(),
                            "reach": net.flight(0).reachability()})
        blob += json.dumps(HEALTH.snapshot())
        for share in net.shares:
            secret = share.pri_share.value
            assert str(secret) not in blob
            assert format(secret, "x") not in blob
        assert "pri_share" not in blob


# ---------------------------------------------------------------------------
# 10. asymmetric partition: inbound-only cut, quorum repair pulls across it
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_asymmetric_partition_repair_pulls_across_the_cut():
    """Every peer's calls TO node 0 are denied while node 0's outbound
    still works — the asymmetric fault the symmetric partition action
    cannot model. Node 0's sender-side view stays clean (all sends
    succeed: zero suspects, full reachability) even though it receives
    NOTHING — but its peers reached quorum without it and flushed
    their collectors, so the repair pull comes back answered-empty and
    the monitor's SYNC leg fetches the stored beacon instead: every
    round lands on node 0 inside its own period (stored, zero missed)
    without a local quorum ever forming (margin honestly None). The
    healthy side never notices (node 0's partials arrive fine)."""
    with structural_crypto(), isolated_observability():
        net = ChaosBeaconNetwork(n=5, t=3, period=PERIOD)
        await net.start_all()
        await net.advance_to_genesis()
        sy0 = _sample_count(metrics.GROUP_REGISTRY,
                            "beacon_partial_repairs", outcome="synced")
        sched = [FaultEvent(3, "deny", {"src": i, "dst": 0})
                 for i in range(1, 5)]
        sched += [FaultEvent(8, "heal")]
        obs = await net.run_schedule(sched, rounds=8)
        net.stop_all()

        cut = [ob for ob in obs if 3 <= ob.round < 8]
        assert cut
        for ob in cut:
            # recovered IN-PERIOD via the sync leg: the beacon is on
            # node 0's chain before its round ends, missed never moves
            assert ob.stored, f"round {ob.round} not recovered in-period"
            assert ob.missed_total == 0
            # no local quorum: the margin SLI stays honestly empty
            assert ob.margin_s is None
            # the asymmetric signature: the victim's own sender-side
            # view is clean — no suspects, nothing unreachable
            assert ob.suspects == 0
        assert all(net.flight(0).reachability().values())
        assert _sample_count(metrics.GROUP_REGISTRY,
                             "beacon_partial_repairs",
                             outcome="synced") > sy0
        # healed: local quorum returns, margins back to the full period
        assert obs[-1].margin_s == pytest.approx(PERIOD)
        # the healthy side held full margins throughout (node 0's
        # outbound partials kept arriving)
        ob4 = net.observe(cut[0].round, probe=4)
        assert ob4.margin_s == pytest.approx(PERIOD)


# ---------------------------------------------------------------------------
# 11. slow-loris links: stale rejects never trip the breaker (half-open too)
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_slow_loris_rejects_never_trip_breaker_then_half_open_recloses():
    """Node 4's partials crawl (1.5 periods of link delay), arriving
    past every receiver's window: each lands an answered STALE reject
    back on the sender. PeerRejectedError immunity says those must
    never trip node 4's breakers — its sender-side view stays fully
    reachable, breaker gauges stay closed. Then a real partition trips
    the survivors' breakers toward 4 (OPEN on the gauge), and after
    heal the capped half-open probe re-closes them within a round —
    the breaker's full state cycle under one schedule."""
    with structural_crypto(), isolated_observability():
        net = ChaosBeaconNetwork(n=5, t=3, period=PERIOD)
        await net.start_all()
        await net.advance_to_genesis()
        s0 = _rejects("grpc", "stale")
        loris = [FaultEvent(3, "link",
                            {"src": 4, "dst": d,
                             "policy": LinkPolicy(delay_s=1.5 * PERIOD)})
                 for d in range(4)]
        obs = await net.run_schedule(loris, rounds=4)

        for ob in obs[-2:]:
            # quorum rides the 4 punctual members: full margin, and the
            # slow peer's column reads missing (its partial never lands
            # in-window)
            assert ob.stored and ob.margin_s == pytest.approx(PERIOD)
            assert ob.bitmap[4] == ".", ob.bitmap
        # the crawling partials came back as answered stale rejects...
        assert _rejects("grpc", "stale") > s0
        # ...and did NOT trip the slow sender's breakers: its view is
        # all-reachable, every breaker gauge still closed
        assert all(net.flight(4).reachability().values())
        for idx in range(5):
            assert metrics.PEER_BREAKER_STATE.labels(
                index=str(idx))._value.get() == 0, idx
        for br in net.handlers[4]._breakers.values():
            assert br.state == 0

        # now a REAL fault: node 4 unreachable for two rounds
        part = [FaultEvent(7, "partition",
                           {"groups": [[0, 1, 2, 3], [4]]})]
        await net.run_schedule(part, rounds=2)
        assert metrics.PEER_BREAKER_STATE.labels(
            index="4")._value.get() == 2  # OPEN on the survivors
        obs = await net.run_schedule([FaultEvent(9, "heal")], rounds=3)
        net.stop_all()
        # half-open probe succeeded after heal: breaker re-closed and
        # the group is whole again
        assert metrics.PEER_BREAKER_STATE.labels(
            index="4")._value.get() == 0
        assert obs[-1].stored and obs[-1].suspects == 0
        assert obs[-1].bitmap[4] in "#~", obs[-1].bitmap


# ---------------------------------------------------------------------------
# 12. reshare + partition combo: ceremony stalls cleanly, chain never misses
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_reshare_under_partition_names_dealer_and_never_misses():
    """The PR-11 reshare-under-churn scenario with the silent dealer
    also PARTITIONED off the beacon plane: the ceremony still stalls in
    exactly the deal phase and the complaint map names the partitioned
    dealer, while the majority's beacon chain rides through with zero
    missed rounds and the partition is visible as exactly one suspect;
    after heal the group returns to full participation."""
    with structural_crypto(), isolated_observability():
        net = ChaosBeaconNetwork(n=5, t=3, period=PERIOD)
        await net.start_all()
        await net.advance_to_genesis()
        await net.run_schedule([], rounds=2)
        net.partition([[0, 1, 2, 3], [4]])
        results = await net.reshare_under_churn({4}, phase_timeout=10.0)
        obs_part = await net.run_schedule([], rounds=1)
        net.heal()
        net.network.allow_all()
        obs = await net.run_schedule([], rounds=3)
        net.stop_all()

        sessions = FLIGHT.dkg.sessions()
        assert len(sessions) == 4
        for s in sessions:
            assert s["mode"] == "reshare" and s["done"]
            assert s["qual"] == [0, 1, 2, 3]
            assert s["complaints"] == {"4": [0, 1, 2, 3]}
            deal = s["phases"][0]
            assert deal["phase"] == "deal"
            assert deal["end_s"] - deal["start_s"] == pytest.approx(10.0)
        assert all(r.qual == [0, 1, 2, 3] for r in results)
        # the chain never missed a round through ceremony + partition,
        # and the partitioned dealer shows as exactly one suspect
        ob = obs_part[-1]
        assert ob.stored and ob.missed_total == 0
        assert ob.suspects == 1
        # healed: suspects clear and the group contributes fully again
        assert obs[-1].missed_total == 0
        assert obs[-1].suspects == 0
        assert obs[-1].bitmap[4] in "#~", obs[-1].bitmap
