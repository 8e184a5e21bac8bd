"""Static-analysis suite (ISSUE 7): fixture snippets per pass, the
baseline round-trip, the real-tree gate, and the event-loop-offload
regression the loopblock pass exists to prevent.

Late-alphabet filename on purpose: tier-1 on the 1-core box runs in
chunks (tools/tier1_chunks.sh) and newer suites sort last so the capped
single invocation keeps its early-dot throughput. Everything here is
host-only — pure AST plus one monkeypatched aiohttp harness; no device
graphs, no fresh XLA compiles, no backend init.
"""

import asyncio
import textwrap
import threading
import time
import types

import numpy as np
import pytest

from tools.analyze import asyncsanity, jaxhazard, loopblock, secretflow
from tools.analyze.core import Project
from tools.analyze.run import REPO, load_baseline, run_analysis

# ---------------------------------------------------------------------------
# fixture helpers
# ---------------------------------------------------------------------------


def _tree(tmp_path, files: dict) -> str:
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return str(tmp_path)


def _project(tmp_path, files: dict) -> Project:
    return Project(_tree(tmp_path, files))


# ---------------------------------------------------------------------------
# loopblock
# ---------------------------------------------------------------------------


def test_loopblock_direct_and_transitive(tmp_path):
    """An async def reaching time.sleep through two sync hops is
    flagged with the call path; the to_thread twin is clean."""
    proj = _project(tmp_path, {
        "app/svc.py": """
            import asyncio
            import time

            def inner():
                time.sleep(1.0)

            def outer():
                inner()

            async def bad_handler():
                outer()

            async def good_handler():
                await asyncio.to_thread(outer)
        """,
    })
    findings = loopblock.run(proj)
    symbols = {f.symbol for f in findings}
    assert "app.svc.bad_handler" in symbols
    assert "app.svc.good_handler" not in symbols
    bad = next(f for f in findings if f.symbol == "app.svc.bad_handler")
    assert bad.severity == "medium"
    assert "time.sleep" in bad.message and "outer" in bad.message


def test_loopblock_retry_sleep_rule(tmp_path):
    """ISSUE 12 (scope widened by ISSUE 14): a raw asyncio.sleep inside
    a retry/backoff loop (a loop that both handles exceptions and backs
    off) in net/, chain/, timelock/, http_server/ or relay/ is a medium
    finding — retries there must ride the injectable-clock policy.
    Cooperative sleep(0) yields, clock-policy sleeps, loops without
    exception handling, and the same shape OUTSIDE the scoped packages
    all stay clean."""
    proj = _project(tmp_path, {
        "drand_tpu/net/dialer.py": """
            import asyncio

            async def bad_dial(peer):
                while True:
                    try:
                        return await peer.call()
                    except ConnectionError:
                        await asyncio.sleep(0.5)

            async def yield_only(stream):
                for _ in range(4):
                    try:
                        pass
                    except ValueError:
                        pass
                    await asyncio.sleep(0)

            async def policy_backoff(peer, clock):
                while True:
                    try:
                        return await peer.call()
                    except ConnectionError:
                        await clock.sleep(0.5)

            async def plain_poll(peer):
                while True:
                    await asyncio.sleep(0.5)
        """,
        # http_server/ and relay/ are IN scope since the relay watch
        # loop moved onto the policy (ISSUE 14) — the exact shape the
        # old PublicServer._watch_loop restart path had is now flagged
        "drand_tpu/http_server/watchish.py": """
            import asyncio

            async def bad_watch_loop(client):
                while True:
                    try:
                        async for r in client.watch():
                            pass
                    except Exception:
                        await asyncio.sleep(1.0)
        """,
        "drand_tpu/relay/pump.py": """
            import asyncio

            async def bad_forward(peer):
                while True:
                    try:
                        return await peer.call()
                    except ConnectionError:
                        await asyncio.sleep(0.5)
        """,
        # the consuming client stack stays OUT of scope: its poll
        # cadence is wall-clock by design (client/http.py watch)
        "drand_tpu/client/poller.py": """
            import asyncio

            async def out_of_scope(peer):
                while True:
                    try:
                        return await peer.call()
                    except ConnectionError:
                        await asyncio.sleep(0.5)
        """,
    })
    findings = [f for f in loopblock.run(proj)
                if f.rule == "retry-sleep"]
    assert {f.symbol for f in findings} == {
        "drand_tpu.net.dialer.bad_dial",
        "drand_tpu.http_server.watchish.bad_watch_loop",
        "drand_tpu.relay.pump.bad_forward",
    }
    f = next(f for f in findings
             if f.symbol == "drand_tpu.net.dialer.bad_dial")
    assert f.severity == "medium"
    assert "injectable-clock" in f.message
    assert f.key.endswith(":retry-sleep")


def test_real_tree_no_retry_sleep_findings():
    """The live tree is clean under the new rule with ZERO baseline
    entries — every retry loop in net/, chain/ and timelock/ already
    goes through drand_tpu.utils.retry."""
    proj = Project(REPO, packages=("drand_tpu",))
    assert [f for f in loopblock.run(proj)
            if f.rule == "retry-sleep"] == []


def test_loopblock_pairing_class_is_high(tmp_path):
    """Project-shaped fixture: engine dispatch reachable from an async
    def is high severity — the exact seed bug (sync.py:146)."""
    proj = _project(tmp_path, {
        "drand_tpu/crypto/batch.py": """
            def verify_beacons(pub, beacons):
                return [True] * len(beacons)
        """,
        "app/syncer.py": """
            import asyncio
            from drand_tpu.crypto import batch

            async def follow(pub, chunk):
                return batch.verify_beacons(pub, chunk)

            async def follow_offloaded(pub, chunk):
                return await asyncio.to_thread(
                    batch.verify_beacons, pub, chunk)
        """,
    })
    findings = loopblock.run(proj)
    by_symbol = {f.symbol: f for f in findings}
    assert by_symbol["app.syncer.follow"].severity == "high"
    assert "pairing-class" in by_symbol["app.syncer.follow"].message
    # the executor hand-off passes the function as an ARGUMENT — no call
    # edge, no finding: this is what "fixed" means mechanically
    assert "app.syncer.follow_offloaded" not in by_symbol


def test_loopblock_lambda_wrapper_is_neutral(tmp_path):
    """A lambda body runs when the lambda is CALLED, not where it is
    written: `await asyncio.to_thread(lambda: batch.verify(...))` is a
    correct hand-off and must not create a call edge from the
    enclosing async def."""
    proj = _project(tmp_path, {
        "drand_tpu/crypto/batch.py": """
            def verify_beacons(pub, beacons):
                return [True] * len(beacons)
        """,
        "app/syncer.py": """
            import asyncio
            from drand_tpu.crypto import batch

            async def follow_lambda(pub, chunk):
                return await asyncio.to_thread(
                    lambda: batch.verify_beacons(pub, chunk))
        """,
    })
    assert loopblock.run(proj) == []


def test_loopblock_unresolved_attr_fallback(tmp_path):
    """obj.aggregate_round(...) on an unresolvable receiver still taints
    via the curated attribute list."""
    proj = _project(tmp_path, {
        "app/agg.py": """
            async def aggregate(engine, parts):
                return engine.aggregate_round(parts)
        """,
    })
    findings = loopblock.run(proj)
    assert [f.symbol for f in findings] == ["app.agg.aggregate"]
    assert findings[0].severity == "high"


# ---------------------------------------------------------------------------
# secretflow
# ---------------------------------------------------------------------------


def test_secretflow_sinks(tmp_path):
    proj = _project(tmp_path, {
        "app/keys.py": """
            def setup(logger, metrics_counter, tracer, pri_share):
                secret = derive(pri_share)
                logger.info("dkg", share=pri_share)
                metrics_counter.labels(key=str(secret)).inc()
                tracer.span("deal", secret=secret)
                raise ValueError(f"bad share: {pri_share}")
        """,
    })
    findings = secretflow.run(proj)
    rules = sorted(f.rule for f in findings)
    assert rules == ["secret-in-exception", "secret-in-log",
                     "secret-in-metric-label", "secret-in-trace-attr"]
    assert all(f.severity == "high" for f in findings)


def test_secretflow_laundering_and_module_alias(tmp_path):
    """Non-converter call results do not taint (an RPC fed a secret
    returns a status, not the secret), and the stdlib `secrets` module
    alias never taints."""
    proj = _project(tmp_path, {
        "app/clean.py": """
            import secrets

            async def share(ctl, logger, secret):
                out = await ctl.init_dkg(secret)
                print(out)
                logger.info("nonce", n=secrets.token_hex(8))
                logger.info("size", n=len(secret))
        """,
        "app/leak.py": """
            def show(secret):
                print(str(secret))
        """,
    })
    findings = secretflow.run(proj)
    assert [f.path for f in findings] == ["app/leak.py"]
    assert findings[0].rule == "secret-in-print"


def test_secretflow_catches_secret_logging_chaos_scenario(tmp_path):
    """ISSUE 11 satellite: a chaos fault-schedule harness that logs a
    node's secret share while reporting a fault (the exact hygiene
    violation the chaos suite asserts never happens at runtime) is a
    HIGH secretflow finding — the static gate backs the runtime check,
    so a scenario author cannot even merge the leak."""
    proj = _project(tmp_path, {
        "testing/chaos_ext.py": """
            def report_byzantine(logger, metrics_counter, node, share):
                pri_share = share.pri_share
                logger.warn("chaos", "byzantine_detected",
                            node=node, share=pri_share)
                metrics_counter.labels(peer=str(pri_share)).inc()
        """,
    })
    findings = secretflow.run(proj)
    rules = sorted(f.rule for f in findings)
    assert rules == ["secret-in-log", "secret-in-metric-label"]
    assert all(f.severity == "high" for f in findings)
    assert all(f.path == "testing/chaos_ext.py" for f in findings)


def test_secretflow_bundle_writer_sink(tmp_path):
    """ISSUE 15 satellite: the incident/forensic bundle writers are a
    registered sink class — a pri_share routed into a bundle lands on
    disk and travels to whoever reads the post-mortem, exfiltration
    exactly like logging it. Known-bad: secret args into the writer
    calls (bare and method forms) are HIGH. Known-good: telemetry
    fields through the same writers stay clean."""
    proj = _project(tmp_path, {
        "obs/leaky.py": """
            def on_trigger(mgr, share, rule):
                pri_share = share.pri_share
                mgr.capture_bundle(reason=str(pri_share))
                freeze_bundle(rule, evidence=pri_share)
        """,
        "obs/clean_bundle.py": """
            def on_trigger(mgr, flight, health, rule):
                bundle = freeze_bundle(rule, flight=flight.rounds(8),
                                       health=health.snapshot())
                mgr.write_bundle(rule.name, bundle)
        """,
    })
    findings = secretflow.run(proj)
    assert [f.path for f in findings] == ["obs/leaky.py"] * 2
    assert all(f.rule == "secret-in-bundle" for f in findings)
    assert all(f.severity == "high" for f in findings)


# ---------------------------------------------------------------------------
# jaxhazard
# ---------------------------------------------------------------------------


def test_jaxhazard_tracer_branch_and_dynamic_shape(tmp_path):
    proj = _project(tmp_path, {
        "ops/kernels.py": """
            from functools import partial

            import jax
            import jax.numpy as jnp

            @jax.jit
            def bad_branch(x):
                y = jnp.abs(x)
                if y > 0:
                    return x
                return -x

            @jax.jit
            def bad_shape(n):
                return jnp.zeros(n)

            @partial(jax.jit, static_argnames=("n",))
            def good_shape(n):
                return jnp.zeros(n)

            @jax.jit
            def good_lax(x):
                return jax.lax.select(x > 0, x, -x)

            def bad_per_call(f, x):
                return jax.jit(f)(x)
        """,
    })
    findings = jaxhazard.run(proj, float_dtype_dirs=())
    rules = {(f.rule, f.symbol.rsplit(".", 1)[-1]) for f in findings}
    assert ("tracer-branch", "bad_branch") in rules
    assert ("dynamic-shape", "bad_shape") in rules
    assert ("jit-per-call", "bad_per_call") in rules
    assert not any(s in ("good_shape", "good_lax")
                   for _, s in rules)


def test_jaxhazard_posonly_and_kwonly_params(tmp_path):
    """static_argnums indexes the full positional list (posonlyargs
    first), and keyword-only params trace like any other argument —
    misreading either direction flips a real hazard into silence or a
    static param into noise."""
    proj = _project(tmp_path, {
        "ops/kernels.py": """
            from functools import partial

            import jax
            import jax.numpy as jnp

            @partial(jax.jit, static_argnums=(0,))
            def posonly(n, /, x):
                for _ in range(n):     # n IS static — no finding
                    x = x + 1
                if x > 0:              # x is traced — finding
                    return x
                return -x

            @jax.jit
            def kwonly(x, *, flag=None):
                if flag:               # kw-only params trace too
                    return x
                return -x
        """,
    })
    findings = jaxhazard.run(proj, float_dtype_dirs=())
    rules = {(f.rule, f.symbol.rsplit(".", 1)[-1]) for f in findings}
    assert ("tracer-branch", "posonly") in rules
    assert ("tracer-branch", "kwonly") in rules
    assert ("dynamic-shape", "posonly") not in rules


def test_jaxhazard_float_dtype_in_limb_math(tmp_path):
    proj = _project(tmp_path, {
        "ops/limbstuff.py": """
            import jax.numpy as jnp

            def mul(a):
                return a.astype(jnp.float32)
        """,
        "ops/clean.py": """
            import jax.numpy as jnp

            def double(v):
                return jnp.left_shift(v, 1)
        """,
        # "ops/" must match whole path components — a loops/ package is
        # NOT limb math and may use floats freely
        "loops/sched.py": """
            import jax.numpy as jnp

            def weights(n):
                return jnp.ones(n, dtype=jnp.float32)
        """,
    })
    findings = jaxhazard.run(proj)
    assert [f.rule for f in findings] == ["float-dtype"]
    assert findings[0].severity == "high"
    assert "limbstuff" in findings[0].path


# ---------------------------------------------------------------------------
# asyncsanity
# ---------------------------------------------------------------------------


def test_asyncsanity_unawaited_and_fire_and_forget(tmp_path):
    proj = _project(tmp_path, {
        "drand_tpu/utils/aio.py": """
            import asyncio

            def spawn(coro):
                task = asyncio.ensure_future(coro)
                _TASKS.add(task)
                task.add_done_callback(_TASKS.discard)
                return task

            _TASKS = set()
        """,
        "app/tasks.py": """
            import asyncio
            from drand_tpu.utils.aio import spawn

            async def work():
                pass

            def bad_unawaited():
                work()

            def bad_weak_ref():
                asyncio.ensure_future(work())
                asyncio.create_task(work())

            def good_spawn():
                spawn(work())

            def good_kept():
                t = asyncio.create_task(work())
                return t
        """,
    })
    findings = asyncsanity.run(proj)
    by_symbol = {}
    for f in findings:
        by_symbol.setdefault(f.symbol.rsplit(".", 1)[-1], []).append(f.rule)
    assert by_symbol == {
        "bad_unawaited": ["unawaited-coroutine"],
        "bad_weak_ref": ["task-without-ref", "task-without-ref"],
    }


@pytest.mark.asyncio
async def test_spawn_holds_strong_reference():
    """utils.aio.spawn keeps the task alive with no caller-side ref."""
    import gc

    from drand_tpu.utils import aio

    done = asyncio.Event()

    async def work():
        await asyncio.sleep(0.05)
        done.set()

    aio.spawn(work())  # deliberately discarded
    assert aio.pending_tasks() >= 1
    gc.collect()
    await asyncio.wait_for(done.wait(), 2.0)
    await asyncio.sleep(0)
    assert aio.pending_tasks() == 0


# ---------------------------------------------------------------------------
# baseline round-trip
# ---------------------------------------------------------------------------


def _one_finding_tree(tmp_path) -> str:
    return _tree(tmp_path, {
        "app/svc.py": """
            import time

            async def handler():
                time.sleep(1.0)
        """,
    })


def test_baseline_roundtrip(tmp_path):
    root = _one_finding_tree(tmp_path)
    report = run_analysis(root=root, passes=("loopblock",),
                          baseline_path=tmp_path / "missing.json")
    assert [f.symbol for f in report["findings"]] == ["app.svc.handler"]
    key = report["findings"][0].key

    # suppressed finding stays suppressed...
    bl = tmp_path / "baseline.json"
    bl.write_text('{"entries": [{"key": "%s", "reason": '
                  '"fixture: documented test suppression"}]}' % key)
    report = run_analysis(root=root, passes=("loopblock",),
                          baseline_path=bl)
    assert report["findings"] == []
    assert [f.key for f in report["suppressed"]] == [key]

    # ...a NEW finding still fails
    (tmp_path / "app" / "new.py").write_text(textwrap.dedent("""
        import time

        async def fresh():
            time.sleep(2.0)
    """))
    report = run_analysis(root=root, passes=("loopblock",),
                          baseline_path=bl)
    assert [f.symbol for f in report["findings"]] == ["app.new.fresh"]


def test_baseline_entry_is_scoped_to_the_reviewed_leaf(tmp_path):
    """A loopblock suppression names the blocking leaf it reviewed: a
    DIFFERENT (stronger) blocking call added to the same function later
    must surface as a new, unsuppressed finding — the zero-high gate
    would otherwise be silently defeated for every baselined symbol."""
    root = _one_finding_tree(tmp_path)
    report = run_analysis(root=root, passes=("loopblock",),
                          baseline_path=tmp_path / "missing.json")
    key = report["findings"][0].key
    assert key.endswith("time.sleep (time.sleep)")  # leaf in the key
    bl = tmp_path / "baseline.json"
    bl.write_text('{"entries": [{"key": "%s", "reason": '
                  '"fixture: reviewed sleep stays inline"}]}' % key)

    # same function grows a pairing-class call: new leaf, new key
    (tmp_path / "app" / "svc.py").write_text(textwrap.dedent("""
        import time

        from drand_tpu.crypto import batch

        async def handler():
            time.sleep(1.0)
            batch.verify_beacons([], [])
    """))
    report = run_analysis(root=root, passes=("loopblock",),
                          baseline_path=bl)
    highs = [f for f in report["findings"] if f.severity == "high"]
    assert len(highs) == 1 and "verify_beacons" in highs[0].key
    # the reviewed-sleep entry now matches nothing (the high leaf wins
    # the per-function finding) and is flagged for cleanup
    assert any(f.rule == "stale-entry" for f in report["findings"])


def test_baseline_requires_reason_and_flags_stale(tmp_path):
    root = _one_finding_tree(tmp_path)
    bl = tmp_path / "baseline.json"
    bl.write_text('{"entries": ['
                  '{"key": "loopblock:async-blocking-medium:app/svc.py:'
                  'app.svc.handler", "reason": ""},'
                  '{"key": "loopblock:gone:app/old.py:app.old.f", '
                  '"reason": "fixture: the code this covered was removed"}'
                  ']}')
    report = run_analysis(root=root, passes=("loopblock",),
                          baseline_path=bl)
    rules = {f.rule for f in report["findings"]}
    # empty reason -> high finding + the suppression does NOT apply;
    # unmatched entry -> stale-entry
    assert "missing-reason" in rules
    assert "stale-entry" in rules
    assert any(f.symbol == "app.svc.handler" for f in report["findings"])


# ---------------------------------------------------------------------------
# the real tree
# ---------------------------------------------------------------------------


def test_real_tree_zero_unsuppressed_high(tmp_path):
    """The PR gate: the repo analyzes clean at --fail-on=high, every
    baseline entry carries a written reason, and the run is host-only
    fast (no backend init — pure AST). On failure the findings are
    ALSO written as SARIF next to the test log (ISSUE 13 satellite:
    auditable CI annotations for exactly what failed the gate)."""
    from tools.analyze.run import write_sarif

    t0 = time.perf_counter()
    report = run_analysis()
    elapsed = time.perf_counter() - t0
    highs = [f for f in report["findings"] if f.severity == "high"]
    if highs:
        sarif_path = tmp_path / "analyze-failure.sarif"
        write_sarif(report, sarif_path)
        print(f"\nanalyze gate FAILED — SARIF written to {sarif_path}")
    assert highs == [], "\n".join(f.render() for f in highs)
    baseline, problems = load_baseline(
        REPO / "tools" / "analyze" / "baseline.json")
    assert problems == []
    assert all(len(r.strip()) >= 10 for r in baseline.values())
    assert elapsed < 60.0


def test_real_tree_no_pairing_class_async_paths():
    """The acceptance criterion, stated directly: NO pairing-class call
    (pairings, Miller loops, MSM, engine dispatch, tbls) is reachable
    from any async def in drand_tpu without an executor hand-off, and
    the baseline suppresses none."""
    proj = Project(REPO, packages=("drand_tpu",))
    baseline, problems = load_baseline(
        REPO / "tools" / "analyze" / "baseline.json")
    assert problems == []
    highs = [f for f in loopblock.run(proj)
             if f.severity == "high" and f.key not in baseline]
    assert highs == [], "\n".join(f.render() for f in highs)
    # the suppression list itself stays tight: reviewed entries only —
    # no loopblock and one lockheld (engine singleton init, see
    # test_zz_concurrency)
    assert not [k for k in baseline if k.startswith("loopblock:")]
    assert len(baseline) <= 1


def test_metrics_pass_folds_into_runner():
    """check_metrics rides along as the fifth pass (one tier-1 entry
    point) and is clean on the repo."""
    report = run_analysis(passes=("metrics",))
    assert report["findings"] == []


# ---------------------------------------------------------------------------
# the offload regression: /healthz answers while a span verifies
# ---------------------------------------------------------------------------


class _StubClient:
    """Minimal Client for PublicServer: serves info, never a beacon."""

    async def info(self):
        return types.SimpleNamespace(period=30, genesis_time=0)

    async def get(self, round_no: int = 0):
        from drand_tpu.client.interface import ClientError

        raise ClientError("no beacon in stub")

    async def watch(self):
        await asyncio.Event().wait()
        yield None  # pragma: no cover

    def round_at(self, t):
        return 0

    async def close(self):
        pass


@pytest.mark.asyncio
async def test_healthz_answers_while_large_span_verifies(monkeypatch):
    """The two highest-severity loopblock findings, fixed: Syncer span
    verification runs via asyncio.to_thread, so a multi-second
    verify_beacons call no longer freezes the event loop — /healthz
    keeps answering mid-verification."""
    import aiohttp

    from drand_tpu.chain.beacon import Beacon
    from drand_tpu.chain.engine import sync as sync_mod
    from drand_tpu.chain.store import CallbackStore, MemStore
    from drand_tpu.crypto import batch
    from drand_tpu.http_server.server import PublicServer
    from drand_tpu.obs.state import reset_observability
    from drand_tpu.utils.logging import default_logger

    reset_observability()
    in_verify = threading.Event()

    def slow_verify(pub, chunk, dst=None):
        # stands in for a large catch-up span's pairing work: BLOCKS its
        # thread for longer than the healthz deadline below
        in_verify.set()
        time.sleep(1.2)
        return np.ones(len(chunk), dtype=bool)

    monkeypatch.setattr(batch, "verify_beacons", slow_verify)

    store = CallbackStore(MemStore())
    store.put(Beacon(round=0, previous_sig=b"", signature=b"genesis"))
    info = types.SimpleNamespace(public_key=None, genesis_seed=b"t")

    beacons = [Beacon(round=r, previous_sig=bytes(32), signature=bytes(96))
               for r in range(1, 65)]

    class _StubTransport:
        def sync_chain(self, peer, req):
            async def gen():
                for b in beacons:
                    yield b
            return gen()

    syncer = sync_mod.Syncer(default_logger("test.sync"), store, info,
                             _StubTransport())

    server = PublicServer(_StubClient())
    site = await server.start("127.0.0.1", 0)
    port = site._server.sockets[0].getsockname()[1]
    try:
        follow = asyncio.ensure_future(syncer.follow(64, ["peer"]))
        # wait until the (threaded) verification is actually blocking
        for _ in range(200):
            if in_verify.is_set():
                break
            await asyncio.sleep(0.01)
        assert in_verify.is_set()

        # the loop must answer well inside the 1.2 s verify window; a
        # regression to inline verification deadlocks this request
        t0 = time.perf_counter()
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{port}/healthz",
                             timeout=aiohttp.ClientTimeout(total=1.0)) as r:
                assert r.status in (200, 503)
                await r.json()
        assert time.perf_counter() - t0 < 1.0

        assert await asyncio.wait_for(follow, 10.0) is True
        assert store.last().round == 64
    finally:
        await server.stop()
        reset_observability()
