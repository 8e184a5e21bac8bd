"""Closed-loop auto-remediation (ISSUE 16): playbook-engine guardrails
(budget, cooldown, dry-run default, failure ledgering), the bounded
supervisor, the reshare-recommendation builder, analyzer fixtures for
the new ledger sinks and lock discipline, the chaos-oracle e2e matrix
(sync_stall, breaker_open, reachability_drop, worker death — incident
mints -> playbook fires -> network recovers with zero operator
intervention -> the bundle carries the full remediation ledger), and
the /debug/remediation route's shared ?n= contract.

Late-alphabet filename per the tier-1 chunking convention
(tools/tier1_chunks.sh). Host-only: chaos scenarios run under
structural crypto — no device graphs, no fresh XLA compiles.
"""

import asyncio
import textwrap

import aiohttp
import pytest
from aiohttp import web
from conftest import sample_count as _sample_count

from drand_tpu import metrics
from drand_tpu.client.direct import DirectClient
from drand_tpu.http_server.debug import add_trace_routes
from drand_tpu.http_server.server import PublicServer
from drand_tpu.net.transport import BREAKER_OPEN
from drand_tpu.obs.flight import FlightRecorder
from drand_tpu.obs.health import HealthState
from drand_tpu.obs.incident import INCIDENTS, IncidentManager, Rule
from drand_tpu.obs.remediate import (ENGINE, PLAYBOOK_PULL,
                                     PLAYBOOK_RESPAWN, PLAYBOOK_SYNC,
                                     Playbook, PlaybookEngine,
                                     attach_node, attach_posture,
                                     attach_supervisor,
                                     configure_from_env,
                                     default_playbooks,
                                     reshare_recommendation,
                                     worker_down_rule)
from drand_tpu.obs.state import isolated_observability
from drand_tpu.testing.chaos import (ChaosBeaconNetwork, FaultEvent,
                                     structural_crypto)
from drand_tpu.utils.aio import spawn as aio_spawn
from drand_tpu.utils.clock import FakeClock
from drand_tpu.utils.supervise import (ALIVE, BACKOFF, BUDGET_EXHAUSTED,
                                       RESPAWN_FAILED, RESPAWNED,
                                       UNKNOWN, Supervisor)
from tools.analyze import lockheld, secretflow
from tools.analyze.core import Project

PERIOD = 4


async def _get(port, path):
    async with aiohttp.ClientSession() as s:
        async with s.get(f"http://127.0.0.1:{port}{path}") as r:
            try:
                body = await r.json()
            except Exception:  # noqa: BLE001 — non-JSON error bodies
                body = {}
            return r.status, body


async def _drain():
    for _ in range(10):
        await asyncio.sleep(0)


def _fault_rule(fault):
    """An incident rule firing while the injected fault flag is on."""
    return Rule("custom", "warning", "edge",
                lambda w, ctx: "down" if fault["on"] else None,
                cooldown_s=0.0, clear_after=2)


def _engine_with(clk, fault, *, playbook: Playbook, **kw):
    mgr = IncidentManager(flight=FlightRecorder(), health=HealthState(),
                          rules=[_fault_rule(fault)])
    engine = PlaybookEngine(clock=clk, playbooks=[playbook], **kw)
    engine.attach(mgr)
    return mgr, engine


# ---------------------------------------------------------------------------
# 1. guardrails — the acceptance quartet
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_budget_exhaustion_stops_firing_keeps_annotating():
    """Past the global budget the engine STOPS acting but keeps
    writing budget_exhausted refusals into the ledger and the
    incident's bundle — silence is the one unacceptable outcome."""
    clk, fault, calls = FakeClock(1000.0), {"on": True}, []
    pb = Playbook("custom", rule="custom", describe="poke the subsystem",
                  cooldown_s=0.0)
    mgr, engine = _engine_with(clk, fault, playbook=pb, dry_run=False,
                               max_actions=2, window_s=3600.0)

    async def act(summary):
        calls.append(summary["id"])
        return "poked"

    engine.register_action("custom", act)
    for r in range(1, 6):
        mgr.on_round(r, now=clk.now(), period=PERIOD)
        await clk.advance(PERIOD)
        await _drain()
    assert len(calls) == 2
    outcomes = [e["outcome"] for e in engine.ledger(16)]
    assert outcomes.count("ok") == 2
    assert outcomes.count("budget_exhausted") == 3
    [inc] = mgr.incidents()
    bundle = mgr.get_bundle(inc["id"])
    refusals = [e for e in bundle["remediation"]
                if e["outcome"] == "budget_exhausted"]
    assert len(refusals) == 3
    assert "not running" in refusals[0]["detail"]
    assert engine.status()["budget"]["used"] == 2


@pytest.mark.asyncio
async def test_cooldown_dedups_sustained_fault_to_one_action():
    """A fault firing every sample inside the playbook cooldown runs
    ONE action — and the skip is silent (no ledger spam)."""
    clk, fault, calls = FakeClock(1000.0), {"on": True}, []
    pb = Playbook("custom", rule="custom", describe="poke",
                  cooldown_s=1000.0)
    mgr, engine = _engine_with(clk, fault, playbook=pb, dry_run=False,
                               max_actions=8, window_s=3600.0)

    async def act(summary):
        calls.append(summary["id"])
        return "poked"

    engine.register_action("custom", act)
    for r in range(1, 7):
        mgr.on_round(r, now=clk.now(), period=PERIOD)
        await clk.advance(PERIOD)
        await _drain()
    assert len(calls) == 1
    assert [e["outcome"] for e in engine.ledger(16)] == ["ok"]
    # past the cooldown the still-open incident earns a second action
    await clk.advance(1000.0)
    mgr.on_round(7, now=clk.now(), period=PERIOD)
    await _drain()
    assert len(calls) == 2


@pytest.mark.asyncio
async def test_dry_run_default_annotates_without_touching_state(
        monkeypatch):
    """With DRAND_TPU_REMEDIATE unset the engine is dry-run: the
    registered action NEVER runs, but every decision is annotated into
    the ledger and the incident bundle as what it WOULD have done."""
    monkeypatch.delenv("DRAND_TPU_REMEDIATE", raising=False)
    clk, fault, calls = FakeClock(1000.0), {"on": True}, []
    pb = Playbook("custom", rule="custom", describe="poke the subsystem",
                  cooldown_s=0.0)
    mgr, engine = _engine_with(clk, fault, playbook=pb,
                               max_actions=8, window_s=3600.0)
    assert engine.dry_run

    async def act(summary):
        calls.append(summary["id"])
        return "poked"

    engine.register_action("custom", act)
    for r in range(1, 4):
        mgr.on_round(r, now=clk.now(), period=PERIOD)
        await clk.advance(PERIOD)
        await _drain()
    assert calls == []
    entries = engine.ledger(16)
    assert len(entries) == 3
    assert all(e["outcome"] == "dry_run" for e in entries)
    assert all(e["detail"] == "would: poke the subsystem"
               for e in entries)
    [inc] = mgr.incidents()
    bundle = mgr.get_bundle(inc["id"])
    assert [e["outcome"] for e in bundle["remediation"]] == \
        ["dry_run"] * 3
    # dry-run dispatches consume NO live budget
    assert engine.status()["budget"]["used"] == 0


@pytest.mark.asyncio
async def test_failed_action_records_outcome_without_reminting():
    """An action that raises lands outcome=failed (exception text in
    the ledger), clears the active marker, and mints no extra
    incident."""
    clk, fault = FakeClock(1000.0), {"on": True}
    pb = Playbook("custom", rule="custom", describe="poke",
                  cooldown_s=1000.0)
    mgr, engine = _engine_with(clk, fault, playbook=pb, dry_run=False,
                               max_actions=8, window_s=3600.0)

    async def act(summary):
        raise RuntimeError("subsystem said no")

    engine.register_action("custom", act)
    for r in range(1, 4):
        mgr.on_round(r, now=clk.now(), period=PERIOD)
        await clk.advance(PERIOD)
        await _drain()
    [entry] = engine.ledger(16)
    assert entry["outcome"] == "failed"
    assert "RuntimeError: subsystem said no" in entry["detail"]
    assert len(mgr.incidents()) == 1
    assert engine.status()["active"] == {}
    # a playbook with NO registered action fails the same audited way
    mgr2, engine2 = _engine_with(clk, fault, playbook=pb, dry_run=False,
                                 max_actions=8, window_s=3600.0)
    mgr2.on_round(1, now=clk.now(), period=PERIOD)
    await _drain()
    [e2] = engine2.ledger(4)
    assert e2["outcome"] == "failed"
    assert "no action registered" in e2["detail"]


# ---------------------------------------------------------------------------
# 2. the bounded supervisor
# ---------------------------------------------------------------------------

def test_supervisor_budget_backoff_and_status():
    alive, spawned = {"on": False}, []
    sup = Supervisor(clock=FakeClock(100.0), respawn_budget=2,
                     backoff_base_s=1.0, backoff_cap_s=8.0)
    sup.register("w", is_alive=lambda: alive["on"],
                 respawn=lambda: spawned.append(True))
    assert sup.dead() == ["w"]
    assert sup.maybe_respawn("w", now=100.0) == RESPAWNED
    # inside the backoff window the retry is refused, slot unspent
    assert sup.maybe_respawn("w", now=100.5) == BACKOFF
    assert sup.maybe_respawn("w", now=101.2) == RESPAWNED
    assert sup.maybe_respawn("w", now=200.0) == BUDGET_EXHAUSTED
    assert len(spawned) == 2
    alive["on"] = True
    assert sup.maybe_respawn("w", now=300.0) == ALIVE
    assert sup.maybe_respawn("nope", now=300.0) == UNKNOWN
    st = sup.status()["w"]
    assert st["alive"] and st["respawns"] == 2 and st["budget"] == 2


def test_supervisor_failed_respawn_spends_the_slot():
    """A respawn callable that raises still burns its budget slot and
    its backoff window — a crash-looping spawner cannot retry-storm."""
    sup = Supervisor(clock=FakeClock(100.0), respawn_budget=2,
                     backoff_base_s=5.0)

    def boom():
        raise OSError("fork failed")

    sup.register("w", is_alive=lambda: False, respawn=boom)
    assert sup.maybe_respawn("w", now=100.0) == RESPAWN_FAILED
    assert sup.respawns("w") == 1
    assert sup.maybe_respawn("w", now=101.0) == BACKOFF
    assert sup.check(now=106.0)["w"] == RESPAWN_FAILED
    assert sup.maybe_respawn("w", now=600.0) == BUDGET_EXHAUSTED


# ---------------------------------------------------------------------------
# 3. the reshare recommendation pins one peer, never ambient noise
# ---------------------------------------------------------------------------

def _fed_flight(bad_by_peer: dict[int, int], rounds: int = 6,
                n: int = 4) -> FlightRecorder:
    flight = FlightRecorder()
    genesis = 1_000_000
    for r in range(1, rounds + 1):
        now = genesis + (r - 1) * PERIOD
        for idx in range(n):
            verdict = ("invalid" if bad_by_peer.get(idx, 0) >= r
                       else "valid")
            flight.note_partial(r, index=idx, source="grpc",
                                verdict=verdict, now=now + 0.2,
                                period=PERIOD, genesis=genesis, n=n,
                                threshold=3)
    return flight


def test_reshare_recommendation_pinned_vs_ambient():
    # peer 2 degraded in every recent round, everyone else clean
    pinned = reshare_recommendation(_fed_flight({2: 6}))
    assert pinned is not None and "peer index 2" in pinned
    assert "reshare" in pinned
    # the same degradation volume spread over two peers is ambient —
    # no single-peer recommendation (reshares are a ceremony)
    assert reshare_recommendation(_fed_flight({1: 3, 3: 3})) is None
    # too little evidence: quiet
    assert reshare_recommendation(_fed_flight({2: 1})) is None
    assert reshare_recommendation(FlightRecorder()) is None


# ---------------------------------------------------------------------------
# 4. analyzer fixtures: ledger sinks + lock discipline in actions
# ---------------------------------------------------------------------------

def _project(tmp_path, files: dict) -> Project:
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return Project(str(tmp_path))


def test_secretflow_flags_remediation_ledger_sinks(tmp_path):
    """Key material flowing into record_action / annotate_remediation
    is a HIGH finding — ledger entries ride the incident bundle and
    /debug/remediation, the same trust boundary as a log line."""
    proj = _project(tmp_path, {"app/fix.py": """
        def bad_record(engine, pri_share):
            engine.record_action("sync_resume", "ok",
                                 detail=str(pri_share.value))

        def bad_annotate(mgr, dist_key):
            mgr.annotate_remediation("inc-1", {"detail": hex(dist_key)})

        def good(engine):
            engine.record_action("sync_resume", "ok",
                                 detail="resumed 3 rounds to head 12")
    """})
    findings = secretflow.run(proj)
    got = {(f.symbol.rsplit(".", 1)[-1], f.rule) for f in findings}
    assert ("bad_record", "secret-in-ledger") in got
    assert ("bad_annotate", "secret-in-ledger") in got
    assert "good" not in {s for s, _ in got}
    assert all(f.severity == "high" for f in findings)
    assert all("remediation ledger" in f.message for f in findings)


def test_lockheld_flags_action_holding_manager_lock(tmp_path):
    """A playbook action holding the manager lock across its await is
    the PR-13 deadlock shape — lockheld must flag it HIGH; snapshot
    under the lock, await outside is clean."""
    proj = _project(tmp_path, {"app/engine.py": """
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                self._summary = {}

            async def bad_action(self, handler):
                with self._lock:
                    return await handler.remediate_sync()

            async def good_action(self, handler):
                with self._lock:
                    summary = dict(self._summary)
                return await handler.remediate_sync()
    """})
    findings = lockheld.run(proj)
    got = {(f.symbol.rsplit(".", 1)[-1], f.rule) for f in findings}
    assert ("bad_action", "lock-across-await") in got
    assert "good_action" not in {s for s, _ in got}
    assert all(f.severity == "high" for f in findings)


# ---------------------------------------------------------------------------
# 5. the chaos-oracle e2e matrix: mint -> fire -> recover -> audit
# ---------------------------------------------------------------------------

def _chaos_mgr(net, rule_names):
    from drand_tpu.obs.incident import default_rules

    net.healths[0].note_dkg_complete()
    return IncidentManager(
        flight=net.flights[0], health=net.healths[0],
        rules=[r for r in default_rules() if r.name in rule_names])


def _ledger_by(engine, playbook):
    return [e for e in engine.ledger(32) if e["playbook"] == playbook]


@pytest.mark.asyncio
async def test_e2e_sync_stall_resumes_from_checkpoint():
    """Partition the probe alone; the majority keeps the chain moving,
    the probe's sync stalls and the incident mints; after heal the
    sync_resume playbook pulls the gap from the upstreams with zero
    operator intervention — lag 0, incident closed, full ledger in the
    bundle."""
    with structural_crypto(), isolated_observability():
        # repair=False and a wedged auto catch-up: sync_stall MEANS
        # "lagging with no catch-up progressing" — the beacon loop's
        # own run_sync (and the PR-12 quorum repair) would otherwise
        # close the gap first; this scenario proves the PLAYBOOK path
        net = ChaosBeaconNetwork(n=4, t=3, period=PERIOD, repair=False)

        async def _wedged(*a, **k):
            return None

        net.handlers[0].chain.run_sync = _wedged
        mgr = _chaos_mgr(net, {"sync_stall"})
        engine = PlaybookEngine(
            clock=net.clocks[0], dry_run=False, max_actions=8,
            window_s=3600.0,
            playbooks=[Playbook(PLAYBOOK_SYNC, rule="sync_stall",
                                describe="rotate + resume",
                                cooldown_s=2 * PERIOD)])
        engine.attach(mgr)
        attach_node(engine, net.handlers[0])
        assert engine.n_peers == 3
        await net.start_all()
        await net.advance_to_genesis()
        sched = [
            FaultEvent(4, "partition", {"groups": [[0], [1, 2, 3]]}),
            FaultEvent(11, "heal"),
        ]
        obs = await net.run_schedule(
            sched, rounds=20,
            on_round=lambda r, now: mgr.on_round(r, now=now,
                                                 period=PERIOD))
        net.stop_all()

        assert obs[-1].lag == 0, obs[-1]
        incs = [i for i in mgr.incidents() if i["rule"] == "sync_stall"]
        assert len(incs) == 1
        assert incs[0]["state"] == "closed"
        entries = _ledger_by(engine, PLAYBOOK_SYNC)
        assert any(e["outcome"] == "ok" for e in entries), entries
        ok = [e for e in entries if e["outcome"] == "ok"][-1]
        assert "resumed from checkpoint" in ok["detail"]
        bundle = mgr.get_bundle(incs[0]["id"])
        assert bundle["remediation"], bundle
        assert [e["playbook"] for e in bundle["remediation"]] == \
            [PLAYBOOK_SYNC] * len(bundle["remediation"])


@pytest.mark.asyncio
async def test_e2e_breaker_open_quorum_pull_closes_breaker():
    """Partition ONE peer away from the probe's majority: its breaker
    opens and the incident mints (min_fired=2 — one blip never pulls);
    after heal the quorum_pull probe answers and the breaker leaves
    OPEN, audited end to end."""
    with structural_crypto(), isolated_observability():
        net = ChaosBeaconNetwork(n=4, t=3, period=PERIOD)
        mgr = _chaos_mgr(net, {"breaker_open"})
        engine = PlaybookEngine(
            clock=net.clocks[0], dry_run=False, max_actions=8,
            window_s=3600.0,
            playbooks=[Playbook(PLAYBOOK_PULL, rule="breaker_open",
                                describe="pull + half-open probe",
                                cooldown_s=2 * PERIOD, min_fired=2)])
        engine.attach(mgr)
        attach_node(engine, net.handlers[0])
        await net.start_all()
        await net.advance_to_genesis()
        sched = [
            FaultEvent(4, "partition", {"groups": [[0, 1, 2], [3]]}),
            FaultEvent(10, "heal"),
        ]
        await net.run_schedule(
            sched, rounds=18,
            on_round=lambda r, now: mgr.on_round(r, now=now,
                                                 period=PERIOD))
        net.stop_all()

        incs = [i for i in mgr.incidents()
                if i["rule"] == "breaker_open"]
        assert len(incs) == 1
        assert incs[0]["state"] == "closed"
        for br in net.handlers[0]._breakers.values():
            assert br.state != BREAKER_OPEN
        entries = _ledger_by(engine, PLAYBOOK_PULL)
        assert entries
        assert mgr.get_bundle(incs[0]["id"])["remediation"]


@pytest.mark.asyncio
async def test_e2e_majority_partition_applies_and_reverts_posture():
    """The probe lands in the partition MINORITY: the sticky posture
    playbook lowers the watcher cap and serves stale; when the
    incident closes the registered revert restores the cap — one
    apply, one revert, both ledgered."""
    with structural_crypto(), isolated_observability():
        net = ChaosBeaconNetwork(n=4, t=3, period=PERIOD)
        mgr = _chaos_mgr(net, {"reachability_drop"})
        engine = PlaybookEngine(
            clock=net.clocks[0], dry_run=False, max_actions=8,
            window_s=3600.0,
            playbooks=[pb for pb in default_playbooks()
                       if pb.name == "partition_posture"])
        engine.attach(mgr)
        engine.n_peers = 3
        server = PublicServer(DirectClient(net.handlers[0]),
                              clock=net.clocks[0])
        attach_posture(engine, server)
        cap_normal = server._max_watchers
        history = []

        def on_round(r, now):
            mgr.on_round(r, now=now, period=PERIOD)
            history.append((r, server._posture, server._max_watchers))

        await net.start_all()
        await net.advance_to_genesis()
        sched = [
            FaultEvent(4, "partition", {"groups": [[0], [1, 2, 3]]}),
            FaultEvent(12, "heal"),
        ]
        await net.run_schedule(sched, rounds=20, on_round=on_round)
        net.stop_all()

        # posture was ON with the cap lowered mid-partition...
        assert any(p and cap < cap_normal for _, p, cap in history), \
            history
        # ...and restored once the incident closed
        assert server._posture is False
        assert server._max_watchers == cap_normal
        incs = [i for i in mgr.incidents()
                if i["rule"] == "reachability_drop"]
        assert len(incs) == 1 and incs[0]["state"] == "closed"
        outcomes = [e["outcome"]
                    for e in _ledger_by(engine, "partition_posture")]
        assert outcomes.count("ok") == 1
        assert outcomes.count("reverted") == 1
        ledger = mgr.get_bundle(incs[0]["id"])["remediation"]
        assert [e["outcome"] for e in ledger].count("reverted") == 1


@pytest.mark.asyncio
async def test_e2e_worker_death_respawns_and_measures_mttr():
    """Crash a member mid-soak: the worker_down incident mints, the
    respawn playbook restarts it through the bounded supervisor, the
    chain recovers and MTTR lands on the histogram — the closed loop
    with zero operator intervention."""
    with structural_crypto(), isolated_observability():
        m0 = _sample_count(metrics.GROUP_REGISTRY,
                           "remediation_mttr_seconds")
        net = ChaosBeaconNetwork(n=6, t=4, period=PERIOD)
        victim = 5
        sup = Supervisor(clock=net.clocks[0], respawn_budget=3,
                         backoff_base_s=PERIOD)
        sup.register(f"node-{victim}",
                     is_alive=lambda: victim not in net.crashed,
                     respawn=lambda: aio_spawn(net.restart(victim)))
        mgr = _chaos_mgr(net, set())
        mgr.rules.append(worker_down_rule(sup, cooldown_s=PERIOD))
        engine = PlaybookEngine(
            clock=net.clocks[0], dry_run=False, max_actions=8,
            window_s=3600.0,
            playbooks=[Playbook(PLAYBOOK_RESPAWN, rule="worker_down",
                                describe="supervised respawn",
                                cooldown_s=PERIOD)])
        engine.attach(mgr)
        attach_supervisor(engine, sup)
        await net.start_all()
        await net.advance_to_genesis()
        sched = [FaultEvent(4, "crash", {"nodes": [victim]})]
        obs = await net.run_schedule(
            sched, rounds=16,
            on_round=lambda r, now: mgr.on_round(r, now=now,
                                                 period=PERIOD))
        net.stop_all()

        assert victim not in net.crashed
        assert obs[-1].lag == 0
        incs = [i for i in mgr.incidents()
                if i["rule"] == "worker_down"]
        assert len(incs) == 1 and incs[0]["state"] == "closed"
        entries = _ledger_by(engine, PLAYBOOK_RESPAWN)
        ok = [e for e in entries if e["outcome"] == "ok"]
        assert ok and "=respawned" in ok[0]["detail"]
        assert sup.respawns(f"node-{victim}") >= 1
        assert mgr.get_bundle(incs[0]["id"])["remediation"]
        # MTTR became a measured SLI: open-to-close observed once
        assert _sample_count(metrics.GROUP_REGISTRY,
                             "remediation_mttr_seconds") == m0 + 1


# ---------------------------------------------------------------------------
# 6. /debug/remediation + util wiring: the shared ?n= contract
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_remediation_route_and_n_matrix(monkeypatch):
    """The debug route serves the singleton engine's status with the
    same hardened ?n= contract as every other ring route, and
    configure_from_env arms/attaches from the documented knobs."""
    with isolated_observability():
        monkeypatch.setenv("DRAND_TPU_REMEDIATE", "live")
        monkeypatch.setenv("DRAND_TPU_REMEDIATE_MAX", "5")
        engine = configure_from_env()
        try:
            assert engine is ENGINE
            assert not engine.dry_run and engine.max_actions == 5
            assert INCIDENTS.engine is engine
            for i in range(5):
                engine.record_action("sync_resume", "ok", incident=None,
                                     mode="live", detail=f"e{i}",
                                     t=float(i))

            app = web.Application()
            add_trace_routes(app)
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port = site._server.sockets[0].getsockname()[1]
            try:
                status, body = await _get(port, "/debug/remediation")
                assert status == 200
                assert body["mode"] == "live" and body["attached"]
                assert body["budget"]["max"] == 5
                names = {p["playbook"] for p in body["playbooks"]}
                assert names == {"sync_resume", "quorum_pull",
                                 "partition_posture", "respawn_worker",
                                 "reshare_recommend"}
                # newest first
                assert [e["detail"] for e in body["ledger"][:2]] == \
                    ["e4", "e3"]
                status, body = await _get(port,
                                          "/debug/remediation?n=2")
                assert status == 200 and len(body["ledger"]) == 2
                # clamp to the engine ring cap
                status, body = await _get(
                    port, "/debug/remediation?n=999999")
                assert status == 200 and len(body["ledger"]) == 5
                for bad in ("zzz", "1.5", "1e3", "0x10", ""):
                    status, _ = await _get(
                        port, f"/debug/remediation?n={bad}")
                    assert status == 400, bad
            finally:
                await runner.cleanup()

            # dry_run is re-read from env on every configure
            monkeypatch.setenv("DRAND_TPU_REMEDIATE", "dry_run")
            assert configure_from_env().dry_run
        finally:
            INCIDENTS.engine = None
            engine.disarm()
