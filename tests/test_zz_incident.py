"""Incident engine (ISSUE 15): the chaos-driven detector matrix, the
time-series ring + incident retention bounds, restart persistence,
bundle secret hygiene with real crypto, and the ``?n=`` matrix on the
new debug route (the shared obs.query helper).

Late-alphabet filename per the tier-1 chunking convention (ROADMAP
operational constraint). Host-only: the chaos scenario runs under
structural crypto, the hygiene test's real crypto is share synthesis
only — no device graphs, no fresh XLA compiles.
"""

import json
import os
import urllib.parse

import aiohttp
import pytest
from aiohttp import web
from conftest import sample_count as _sample_count

from drand_tpu import metrics
from drand_tpu.http_server.debug import add_trace_routes
from drand_tpu.obs.flight import FlightRecorder
from drand_tpu.obs.health import HealthState
from drand_tpu.obs.incident import (INCIDENTS, IncidentManager, Rule,
                                    default_rules)
from drand_tpu.obs.query import ring_n
from drand_tpu.obs.state import isolated_observability
from drand_tpu.obs.timeseries import TimeSeriesRing
from drand_tpu.testing.chaos import (ChaosBeaconNetwork, FaultEvent,
                                     LinkPolicy, detection_lead,
                                     structural_crypto)

PERIOD = 4


async def _get(port, path):
    async with aiohttp.ClientSession() as s:
        async with s.get(f"http://127.0.0.1:{port}{path}") as r:
            try:
                body = await r.json()
            except Exception:  # noqa: BLE001 — non-JSON error bodies
                body = {}
            return r.status, body


# ---------------------------------------------------------------------------
# 1. the acceptance scenario: the chaos schedule fires every detector,
#    one incident per sustained fault, margin leads missed
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_chaos_detector_matrix(tmp_path):
    """One 8-node schedule drives SIX distinct rules, each minting
    exactly ONE incident inside its fault window: a cross-link delay
    (margin_degraded — rounds before anything misses), then a
    no-quorum partition (missed_round + breaker_open +
    reachability_drop + readiness_flip + sync_stall). The margin
    incident's detection lead matches the PR-11 oracle's, and the
    missed-round bundle fingers the partitioned half from the frozen
    bitmap + reachability. (The two delta-threshold rules —
    ingress_flood/shed_surge — read PROCESS-global counters that every
    in-process node feeds, so their exact-count proof is the unit test
    below; they are excluded here rather than asserted against
    cross-node noise.)"""
    with structural_crypto(), isolated_observability():
        net = ChaosBeaconNetwork(n=8, t=5, period=PERIOD)
        net.healths[0].note_dkg_complete()  # probe models a real node
        rules = [r for r in default_rules()
                 if r.name not in ("ingress_flood", "shed_surge")]
        mgr = IncidentManager(
            flight=net.flights[0], health=net.healths[0], rules=rules,
            dir_path=str(tmp_path / "incidents"))
        await net.start_all()
        await net.advance_to_genesis()
        heal_round = 13
        sched = [
            FaultEvent(4, "link_all",
                       {"policy": LinkPolicy(delay_s=2.5)}),
            FaultEvent(7, "partition",
                       {"groups": [[0, 1, 2, 3], [4, 5, 6, 7]]}),
            FaultEvent(heal_round, "heal"),
        ]
        obs = await net.run_schedule(
            sched, rounds=16,
            on_round=lambda r, now: mgr.on_round(r, now=now,
                                                 period=PERIOD))
        net.stop_all()

        incs = mgr.incidents()
        by_rule: dict[str, list] = {}
        for inc in incs:
            by_rule.setdefault(inc["rule"], []).append(inc)

        def in_window(rule, lo, hi):
            return [i for i in by_rule.get(rule, [])
                    if i["round"] is not None and lo <= i["round"] <= hi]

        # exactly ONE incident per sustained fault, inside its window
        windows = {"margin_degraded": (4, 6),
                   "missed_round": (8, heal_round),
                   "breaker_open": (7, heal_round),
                   "reachability_drop": (7, heal_round),
                   "readiness_flip": (8, heal_round),
                   "sync_stall": (8, heal_round)}
        for rule, (lo, hi) in windows.items():
            assert len(in_window(rule, lo, hi)) == 1, \
                f"{rule}: {by_rule.get(rule)}"
        # and no rule flapped into a pile of incidents anywhere
        for rule, group in by_rule.items():
            assert len(group) <= 2, f"{rule} minted {len(group)}"

        # margin fired on the delay fault, rounds BEFORE missed —
        # detection lead >= the PR-11 oracle's on the same observations
        margin_round = in_window("margin_degraded", 4, 6)[0]["round"]
        missed_inc = in_window("missed_round", 8, heal_round)[0]
        assert margin_round == 4
        assert missed_inc["round"] > margin_round
        oracle = detection_lead(obs, PERIOD)
        assert oracle["lead_rounds"] is not None
        assert missed_inc["round"] - margin_round >= oracle["lead_rounds"]

        # the missed-round bundle froze the partition evidence: the
        # other half is named missing by the bitmap AND unreachable
        bundle = mgr.get_bundle(missed_inc["id"])
        assert bundle is not None
        sus = bundle["suspect_peers"]
        assert sus["missing"] == [4, 5, 6, 7]
        assert sus["unreachable"] == [4, 5, 6, 7]
        assert sus["invalid"] == []
        # the frozen flight slice carries the '####....' bitmaps
        part_bitmaps = [r["bitmap"] for r in bundle["flight"]["rounds"]
                        if r["round"] >= 7 and r["bitmap"]]
        assert part_bitmaps
        assert all(bm[4:] == "...." for bm in part_bitmaps)
        # evidence inventory: ts window, health, config all frozen
        assert bundle["timeseries"]
        assert bundle["health"]["missed_total"] >= 1
        assert bundle["config"]["fingerprint"]
        # sustained faults re-fired into the OPEN incident, not new ones
        assert missed_inc["fired"] >= 2
        # the catalogue counters moved once per mint
        assert _sample_count(metrics.GROUP_REGISTRY, "incidents",
                             rule="missed_round",
                             severity="critical") >= 1


def test_flood_and_shed_delta_rules():
    """The two delta-threshold rules against their own counters: a
    reject surge >= FLOOD_MIN in one sample mints ingress_flood, a
    shed surge >= SHED_MIN mints shed_surge; sub-threshold deltas mint
    nothing (counters are global — deltas, not levels, trigger)."""
    flight, health = FlightRecorder(), HealthState()
    mgr = IncidentManager(flight=flight, health=health)
    genesis = 1_000_000

    def rejects(rnd, count):
        # the REAL ingress-reject path: invalid partials through the
        # recorder feed beacon_ingress_rejects_total
        for _ in range(count):
            flight.note_partial(rnd, index=0, source="grpc",
                                verdict="invalid", now=float(genesis),
                                period=PERIOD, genesis=genesis, n=3,
                                threshold=2)

    mgr.on_round(1, now=1.0, period=PERIOD)  # delta baseline
    # below both thresholds: quiet
    rejects(2, 3)
    metrics.RELAY_SHED.labels(reason="watcher_cap").inc(2)
    mgr.on_round(2, now=5.0, period=PERIOD)
    assert mgr.incidents() == []
    # a flood and a shed storm in the next sample window
    rejects(2, 40)
    metrics.RELAY_SHED.labels(reason="watcher_cap").inc(20)
    mgr.on_round(3, now=9.0, period=PERIOD)
    rules = sorted(i["rule"] for i in mgr.incidents())
    assert rules == ["ingress_flood", "shed_surge"]


# ---------------------------------------------------------------------------
# 2. cooldown + dedup: one sustained fault = one incident; a fresh
#    fault after the cooldown mints a second
# ---------------------------------------------------------------------------

def test_sustained_fault_dedup_and_cooldown():
    flight, health = FlightRecorder(), HealthState()
    mgr = IncidentManager(flight=flight, health=health)
    genesis, period = 1_000_000, 4

    def advance(r, stored):
        b = genesis + (r - 1) * period
        if stored:
            health.note_round_stored(r, 0.2, period)
            health.observe_chain(b + 0.5, period, genesis, r)
        else:
            health.observe_chain(b + 3.9, period, genesis)
        mgr.on_round(r, now=b + 0.5, period=period)

    for r in range(1, 4):
        advance(r, stored=True)
    assert mgr.incidents() == []
    # sustained fault: rounds 4-8 all miss — ONE incident, re-fired
    for r in range(4, 9):
        advance(r, stored=False)
    incs = [i for i in mgr.incidents() if i["rule"] == "missed_round"]
    assert len(incs) == 1
    assert incs[0]["state"] == "open"
    assert incs[0]["fired"] >= 3
    # recovery: stores resume, incident closes after clear_after quiet
    # samples... but a re-miss INSIDE the cooldown must NOT re-mint
    # (a miss counts once the NEXT round's probe sees the full period
    # gone — two unstored rounds make the first one count)
    for r in range(9, 12):
        advance(r, stored=True)
    incs = [i for i in mgr.incidents() if i["rule"] == "missed_round"]
    assert incs[0]["state"] == "closed"
    advance(12, stored=False)
    advance(13, stored=False)  # round 12's miss counts here, ~8s after
    assert len([i for i in mgr.incidents()  # close: inside the 30s
                if i["rule"] == "missed_round"]) == 1  # cooldown
    # past the cooldown a NEW fault is a NEW incident
    for r in range(14, 22):
        advance(r, stored=True)
    advance(22, stored=False)
    advance(23, stored=False)  # round 22's miss, ~40s past the close
    assert len([i for i in mgr.incidents()
                if i["rule"] == "missed_round"]) == 2


# ---------------------------------------------------------------------------
# 3. bounds: ts ring, spool rotation, incident-dir rotation
# ---------------------------------------------------------------------------

def test_timeseries_ring_and_spool_bounds(tmp_path):
    spool = str(tmp_path / "ts.ndjson")
    ring = TimeSeriesRing(max_samples=8, spool_path=spool,
                          max_spool_bytes=2048)
    for i in range(64):
        ring.append({"t": float(i), "round": i, "missed_total": i,
                     "ingress_rejects": 0.0, "watcher_shed": 0.0})
    ring.flush()
    assert len(ring) == 8
    assert [s["round"] for s in ring.window()] == list(range(56, 64))
    # deltas are counter-aware
    assert ring.window()[-1]["deltas"]["missed_total"] == 1.0
    # disk bounded at ~2x the cap by the OTLP rotation pattern
    assert os.path.getsize(spool) <= 2048
    assert os.path.getsize(spool + ".1") <= 2048


def test_open_incident_survives_rotation(tmp_path):
    """An incident held open across many newer mints is never evicted
    (memory or disk) while open — /debug/incidents stays consistent
    with the active count and the eventual close lands on disk."""
    flight, health = FlightRecorder(), HealthState()
    sticky = Rule("custom", "warning", "edge",
                  lambda w, ctx: ("on" if w[-1]["round"] < 90
                                  else None),
                  cooldown_s=0.0, clear_after=1)
    churn = Rule("shed_surge", "warning", "edge",
                 lambda w, ctx: ("even" if w[-1]["round"] % 2 == 0
                                 and w[-1]["round"] < 50 else None),
                 cooldown_s=0.0, clear_after=1)
    mgr = IncidentManager(flight=flight, health=health,
                          rules=[sticky, churn],
                          dir_path=str(tmp_path / "inc"),
                          max_incidents=3)
    for r in range(1, 14):  # ends on an odd round: churn all closed
        mgr.on_round(r, now=float(r), period=4)
    # the sticky incident (minted FIRST) is still listed and open
    # despite 6 younger churn incidents through a bound of 3
    open_incs = [i for i in mgr.incidents(100) if i["state"] == "open"]
    assert [i["id"] for i in open_incs] == ["inc-00001-custom"]
    assert mgr.active_count() == 1
    assert "inc-00001-custom.json" in os.listdir(tmp_path / "inc")
    # close it: the close state reaches the still-present file
    for r in range(90, 93):
        mgr.on_round(r, now=float(r), period=4)
    disk = json.load(open(tmp_path / "inc" / "inc-00001-custom.json"))
    assert disk["state"] == "closed"


def test_readiness_flip_immune_to_restored_history(tmp_path):
    """Spool-restored pre-restart samples (ready=True) must not arm
    the readiness-flip rule: a restart straight into catch-up lag is
    not a live flip."""
    spool = str(tmp_path / "ts.ndjson")
    flight, health = FlightRecorder(), HealthState()
    health.note_dkg_complete()
    genesis, period = 1_000_000, 4
    mgr = IncidentManager(flight=flight, health=health)
    mgr.configure(spool_path=spool)
    for r in range(1, 4):  # healthy, ready samples -> spool
        b = genesis + (r - 1) * period
        health.note_round_stored(r, 0.2, period)
        health.observe_chain(b + 0.5, period, genesis, r)
        mgr.on_round(r, now=b + 0.5, period=period)
    assert mgr.ring.window()[-1]["ready"]
    mgr.ring.flush()  # healthy samples buffer (mints force-flush);
    # a graceful handover flushes — a SIGKILL may lose <=FLUSH_EVERY

    # "restart": fresh manager+health, spool restored, node lagging
    flight2, health2 = FlightRecorder(), HealthState()
    health2.note_dkg_complete()
    mgr2 = IncidentManager(flight=flight2, health=health2)
    mgr2.configure(spool_path=spool)
    assert len(mgr2.ring) == 3
    b = genesis + 9 * period  # 10 rounds later, head far behind
    health2.observe_chain(b, period, genesis, 3)
    mgr2.on_round(10, now=b, period=period)
    assert not any(i["rule"] == "readiness_flip"
                   for i in mgr2.incidents()), mgr2.incidents()
    # but a LIVE flip still fires: become ready, then lag again
    for r in range(11, 14):
        bb = genesis + (r - 1) * period
        health2.note_round_stored(r, 0.2, period)
        health2.observe_chain(bb + 0.5, period, genesis, r)
        mgr2.on_round(r, now=bb + 0.5, period=period)
    bb = genesis + 19 * period
    health2.observe_chain(bb, period, genesis)
    mgr2.on_round(20, now=bb, period=period)
    assert any(i["rule"] == "readiness_flip" for i in mgr2.incidents())


def test_memory_only_bundle_tracks_lifecycle():
    """On a node with NO incident dir (relay default), the bundle
    served by get_bundle must carry the same lifecycle the listing
    shows — not the state frozen at mint."""
    flight, health = FlightRecorder(), HealthState()
    toggle = Rule("custom", "warning", "edge",
                  lambda w, ctx: ("on" if w[-1]["round"] < 4 else None),
                  cooldown_s=0.0, clear_after=1)
    mgr = IncidentManager(flight=flight, health=health, rules=[toggle])
    for r in range(1, 6):
        mgr.on_round(r, now=float(r), period=4)
    [inc] = mgr.incidents()
    assert inc["state"] == "closed"
    bundle = mgr.get_bundle(inc["id"])
    assert bundle["state"] == "closed"
    assert bundle["closed_at"] == inc["closed_at"]
    assert bundle["fired"] == inc["fired"] >= 3


def test_readiness_incident_latches_through_long_outage():
    """Once open, the readiness incident stays open for the whole
    outage even after every live 'ready' sample ages out of the
    (small, here) window — it closes only when ready returns."""
    flight, health = FlightRecorder(), HealthState()
    health.note_dkg_complete()
    genesis, period = 1_000_000, 4
    mgr = IncidentManager(flight=flight, health=health,
                          ring=TimeSeriesRing(max_samples=6))
    for r in range(1, 4):  # ready baseline
        b = genesis + (r - 1) * period
        health.note_round_stored(r, 0.2, period)
        health.observe_chain(b + 0.5, period, genesis, r)
        mgr.on_round(r, now=b + 0.5, period=period)
    # a LONG outage: 20 not-ready samples, 3x the window size
    for r in range(4, 24):
        b = genesis + (r - 1) * period
        health.observe_chain(b + 3.9, period, genesis)
        mgr.on_round(r, now=b + 3.9, period=period)
    flips = [i for i in mgr.incidents(100)
             if i["rule"] == "readiness_flip"]
    assert len(flips) == 1
    assert flips[0]["state"] == "open", flips
    # recovery closes it
    for r in range(24, 28):
        b = genesis + (r - 1) * period
        health.note_round_stored(r, 0.2, period)
        health.observe_chain(b + 0.5, period, genesis, r)
        mgr.on_round(r, now=b + 0.5, period=period)
    flips = [i for i in mgr.incidents(100)
             if i["rule"] == "readiness_flip"]
    assert len(flips) == 1 and flips[0]["state"] == "closed"


def test_incident_dir_rotation_bound(tmp_path):
    flight, health = FlightRecorder(), HealthState()
    # a toggling rule: fires on even rounds, clears on odd, no cooldown
    toggle = Rule("custom", "warning", "edge",
                  lambda w, ctx: ("even" if w[-1]["round"] % 2 == 0
                                  else None),
                  cooldown_s=0.0, clear_after=1)
    mgr = IncidentManager(flight=flight, health=health, rules=[toggle],
                          dir_path=str(tmp_path / "inc"),
                          max_incidents=3)
    for r in range(1, 13):
        mgr.on_round(r, now=float(r), period=4)
    # 6 mint/close cycles -> memory AND disk both bounded at 3
    assert len(mgr.incidents(100)) == 3
    files = sorted(os.listdir(tmp_path / "inc"))
    assert len(files) == 3
    # oldest were rotated away, newest kept (ids are seq-ordered)
    assert files[-1].startswith("inc-00006-")


# ---------------------------------------------------------------------------
# 4. restart persistence: spool + incident dir reload
# ---------------------------------------------------------------------------

def test_restart_persistence(tmp_path):
    flight, health = FlightRecorder(), HealthState()
    d, spool = str(tmp_path / "inc"), str(tmp_path / "ts.ndjson")
    genesis, period = 1_000_000, 4
    mgr = IncidentManager(flight=flight, health=health, dir_path=d)
    mgr.configure(spool_path=spool)
    for r in range(1, 4):
        b = genesis + (r - 1) * period
        health.note_round_stored(r, 0.2, period)
        health.observe_chain(b + 0.5, period, genesis, r)
        mgr.on_round(r, now=b + 0.5, period=period)
    # miss rounds 4-5 -> one persisted incident
    for r in range(4, 6):
        b = genesis + (r - 1) * period
        health.observe_chain(b + 3.9, period, genesis)
        mgr.on_round(r, now=b + 3.9, period=period)
    ids = [i["id"] for i in mgr.incidents()]
    assert len(ids) == 1

    # "restart": a fresh manager over the same disk state
    mgr2 = IncidentManager(flight=flight, health=health)
    mgr2.configure(dir_path=d, spool_path=spool)
    assert [i["id"] for i in mgr2.incidents()] == ids
    # the bundle is served from disk (memory holds the summary only)
    bundle = mgr2.get_bundle(ids[0])
    assert bundle is not None and bundle["rule"] == "missed_round"
    # the ring restored the spooled history, oldest intact
    assert len(mgr2.ring) == 5
    assert mgr2.ring.window()[0]["round"] == 1
    # the seq counter resumed past the loaded ids: no collision
    mgr2._lock.acquire()
    try:
        assert mgr2._seq >= 1
    finally:
        mgr2._lock.release()
    # path traversal never reaches the filesystem
    assert mgr2.get_bundle("../../etc/passwd") is None


# ---------------------------------------------------------------------------
# 5. bundle secret hygiene with real crypto
# ---------------------------------------------------------------------------

def test_bundle_secret_hygiene_real_crypto(monkeypatch):
    """A bundle captured in a process holding REAL shares (and a
    secret-looking env knob) contains no share value in decimal or
    hex, and the config fingerprint redacted the env secret."""
    from drand_tpu.testing.harness import make_test_group

    monkeypatch.setenv("DRAND_TPU_SETUP_SECRET", "hunter2-do-not-leak")
    _group, _pairs, shares = make_test_group(3, 2, PERIOD, 1_000_000,
                                             seed=b"incident-hygiene")
    flight, health = FlightRecorder(), HealthState()
    mgr = IncidentManager(flight=flight, health=health)
    for r in range(1, 4):
        b = 1_000_000 + (r - 1) * PERIOD
        for idx in range(2):
            flight.note_partial(r, index=idx, source="grpc",
                                verdict="valid", now=b + 0.2,
                                period=PERIOD, genesis=1_000_000,
                                n=3, threshold=2)
        health.note_round_stored(r, 0.2, PERIOD)
        health.observe_chain(b + 0.5, PERIOD, 1_000_000, r)
        mgr.on_round(r, now=b + 0.5, period=PERIOD)
    blob = json.dumps(mgr.capture_bundle())
    assert "pri_share" not in blob
    for s in shares:
        assert str(s.pri_share.value) not in blob
        assert format(s.pri_share.value, "x") not in blob
    assert "hunter2-do-not-leak" not in blob
    assert "<redacted>" in blob


# ---------------------------------------------------------------------------
# 6. the ?n= matrix on /debug/incidents + the shared helper + routes
# ---------------------------------------------------------------------------

def test_ring_n_shared_helper_semantics():
    """The one validator behind all three ?n= routes: plain base-10
    only, clamp to [1, cap], None for absent -> default."""
    assert ring_n(None, default=8, cap=128) == 8
    assert ring_n("5", default=8, cap=128) == 5
    assert ring_n("-3", default=8, cap=128) == 1
    assert ring_n("0", default=8, cap=128) == 1
    assert ring_n("999999", default=8, cap=128) == 128
    assert ring_n("+7", default=8, cap=128) == 7
    assert ring_n(" 12 ", default=8, cap=128) == 12
    for bad in ("", "zzz", "1.5", "1e3", "0x10", "1_0", "١٢", "+-5"):
        assert ring_n(bad, default=8, cap=128) is None, bad
    # the query-string gotcha the tests percent-encode around: a
    # literal '+' in a URL decodes to a space mid-token -> invalid
    assert urllib.parse.unquote_plus("1+1") == "1 1"
    assert ring_n("1 1", default=8, cap=128) is None


@pytest.mark.asyncio
async def test_incident_routes_and_n_matrix(tmp_path):
    """/debug/incidents serves the singleton's summaries with the same
    hardened ?n= contract as the trace/flight routes; {id} serves the
    frozen bundle; /debug/support-bundle runs the manual capture."""
    with isolated_observability():
        from drand_tpu.obs.health import HEALTH

        genesis, period = 1_000_000, 4
        HEALTH.note_dkg_complete()
        for r in range(1, 3):
            b = genesis + (r - 1) * period
            HEALTH.note_round_stored(r, 0.2, period)
            HEALTH.observe_chain(b + 0.5, period, genesis, r)
            INCIDENTS.on_round(r, now=b + 0.5, period=period)
        for r in range(3, 5):  # missed -> one incident on the singleton
            b = genesis + (r - 1) * period
            HEALTH.observe_chain(b + 3.9, period, genesis)
            INCIDENTS.on_round(r, now=b + 3.9, period=period)
        assert len(INCIDENTS.incidents()) >= 1

        app = web.Application()
        add_trace_routes(app)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        try:
            # the URL-encoding matrix (mirrors the trace-route matrix:
            # '+' decodes to space, so explicit signs percent-encode)
            for q, want in (("zzz", 400), ("1.5", 400), ("1e3", 400),
                            ("0x10", 400), ("", 400), ("%2B-5", 400),
                            ("-5", 200), ("0", 200), ("999999999", 200),
                            ("%2B7", 200), ("8", 200)):
                status, body = await _get(port,
                                          f"/debug/incidents?n={q}")
                assert status == want, f"n={q!r} -> {status}"
                if want == 200:
                    assert "incidents" in body and "active" in body
            status, body = await _get(port, "/debug/incidents")
            assert status == 200
            inc = body["incidents"][0]
            assert inc["rule"] == "missed_round"
            # the bundle route serves the frozen evidence by id
            status, bundle = await _get(port,
                                        f"/debug/incidents/{inc['id']}")
            assert status == 200
            assert bundle["id"] == inc["id"]
            assert "timeseries" in bundle and "flight" in bundle \
                and "config" in bundle
            status, _ = await _get(port, "/debug/incidents/inc-99999-nope")
            assert status == 404
            # manual capture: the bundle writer verbatim, no new incident
            n_before = len(INCIDENTS.incidents())
            status, sup = await _get(port, "/debug/support-bundle")
            assert status == 200
            assert sup["rule"] == "manual" and sup["state"] == "manual"
            assert "timeseries" in sup and "health" in sup
            assert len(INCIDENTS.incidents()) == n_before
        finally:
            await runner.cleanup()


# ---------------------------------------------------------------------------
# 7. the healthz pull model drives detection with zero stores
# ---------------------------------------------------------------------------

def test_poll_pull_model_and_rate_limit():
    """A fully stalled chain stores nothing — probe-driven poll()
    samples must still fire the missed-round rule; and a probe storm
    (many polls inside the min interval) must not grow the ring."""
    flight, health = FlightRecorder(), HealthState()
    mgr = IncidentManager(flight=flight, health=health)
    genesis, period = 1_000_000, 4
    # one stored round seeds head + period context
    health.note_round_stored(1, 0.2, period)
    health.observe_chain(genesis + 0.5, period, genesis, 1)
    mgr.on_round(1, now=genesis + 0.5, period=period)
    # then the chain dies: only probes observe, 5 rounds pass
    for r in range(2, 7):
        b = genesis + (r - 1) * period
        health.observe_chain(b + 3.9, period, genesis)
        assert mgr.poll(b + 3.9) is not None
        for _ in range(10):  # probe storm inside the min interval
            assert mgr.poll(b + 3.95) is None
    # the stalled chain fires BOTH pull-model rules (sync_stall rides
    # the same lag threshold), each exactly once
    assert sorted(i["rule"] for i in mgr.incidents()) == \
        ["missed_round", "sync_stall"]
    assert len(mgr.ring) == 6
