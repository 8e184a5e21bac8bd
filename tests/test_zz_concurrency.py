"""Concurrency-safety analysis tier (ISSUE 13): lockheld, threadshare
and awaitatomic fixtures, the call-graph decorator fix, the runner
satellites (SARIF, --prune-baseline), and regression tests for every
live race the passes caught — thread hammers for the fixed
warn-once/warm-shape globals, interleaving proofs for the fixed
check-then-act caches.

Late-alphabet filename per the tier-1 chunking convention
(tools/tier1_chunks.sh). Host-only: pure AST plus thread/event-loop
harnesses — no device graphs, no backend init, no fresh XLA compiles.
"""

import asyncio
import json
import textwrap
import threading
import time

import pytest

from tools.analyze import awaitatomic, lockheld, loopblock, threadshare
from tools.analyze.core import Project
from tools.analyze.run import (REPO, prune_baseline, run_analysis,
                               to_sarif, write_sarif)

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
# lockheld
# ---------------------------------------------------------------------------


def test_lockheld_await_and_pairing_under_lock(tmp_path):
    """A threading lock held across an await or across pairing-class
    work is high; releasing before the await, and an `async with` on an
    asyncio lock, are clean."""
    proj = _project(tmp_path, {
        "drand_tpu/crypto/batch.py": """
            def verify_beacons(pub, beacons):
                return [True] * len(beacons)
        """,
        "app/svc.py": """
            import asyncio
            import threading
            from drand_tpu.crypto import batch

            class Holder:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._aio_lock = asyncio.Lock()
                    self._items = []

                async def bad_await(self, peer):
                    with self._lock:
                        data = await peer.fetch()
                        self._items.append(data)

                def bad_pairing(self, pub, chunk):
                    with self._lock:
                        return batch.verify_beacons(pub, chunk)

                async def bad_handoff(self, pub, chunk):
                    with self._lock:
                        return await asyncio.to_thread(
                            batch.verify_beacons, pub, chunk)

                async def good_narrow(self, peer):
                    data = await peer.fetch()
                    with self._lock:
                        self._items.append(data)

                async def good_asyncio_lock(self, peer):
                    async with self._aio_lock:
                        return await peer.fetch()
        """,
    })
    findings = lockheld.run(proj)
    got = {(f.symbol.rsplit(".", 1)[-1], f.rule) for f in findings}
    assert ("bad_await", "lock-across-await") in got
    assert ("bad_pairing", "lock-over-pairing") in got
    assert ("bad_handoff", "lock-across-await") in got
    assert ("bad_handoff", "lock-across-handoff") in got
    names = {s for s, _ in got}
    assert "good_narrow" not in names
    assert "good_asyncio_lock" not in names
    assert all(f.severity == "high" for f in findings)
    assert all("_lock" in f.message for f in findings)


def test_lockheld_transitive_taint_through_helper(tmp_path):
    """The pass reuses loopblock's fixpoint: a call made under the lock
    that only reaches the pairing leaf through a sync helper still
    counts."""
    proj = _project(tmp_path, {
        "drand_tpu/crypto/batch.py": """
            def aggregate_round(pub, msg, parts, t, n):
                return [True], b"sig"
        """,
        "app/agg.py": """
            import threading
            from drand_tpu.crypto import batch

            _LOCK = threading.Lock()

            def helper(pub, msg, parts):
                return batch.aggregate_round(pub, msg, parts, 2, 3)

            def bad(pub, msg, parts):
                with _LOCK:
                    return helper(pub, msg, parts)
        """,
    })
    findings = lockheld.run(proj)
    assert [f.symbol for f in findings] == ["app.agg.bad"]
    assert findings[0].rule == "lock-over-pairing"
    assert "_LOCK" in findings[0].key


def test_lockheld_real_tree_only_engine_singleton():
    """The live tree holds exactly one reviewed lock-across-blocking
    site: the double-checked engine-singleton init (baselined with a
    written reason — releasing the lock there would double-construct
    the engine)."""
    proj = Project(REPO, packages=("drand_tpu",))
    findings = lockheld.run(proj)
    assert [f.symbol for f in findings] == ["drand_tpu.crypto.batch.engine"]


# ---------------------------------------------------------------------------
# threadshare
# ---------------------------------------------------------------------------


def _dual_ctx_files(guarded: bool) -> dict:
    """A module-global mutated from a to_thread worker AND read from
    the loop — the exact shape of the batch.py warn-once bug the pass
    caught live (``_FALLBACK_LOGGED``)."""
    lock_line = "with _STATE_LOCK:\n        _WARNED = True" if guarded \
        else "_WARNED = True"
    return {
        "app/disp.py": f"""
            import asyncio
            import threading

            _STATE_LOCK = threading.Lock()
            _WARNED = False

            def note_failure():
                global _WARNED
                if not _WARNED:
                    {lock_line}

            def heavy_work(x):
                note_failure()
                return x

            async def handler(x):
                # loop side reads the flag via the same helper
                note_failure()
                return await asyncio.to_thread(heavy_work, x)
        """,
    }


def test_threadshare_flags_dual_context_global(tmp_path):
    proj = _project(tmp_path, _dual_ctx_files(guarded=False))
    findings = threadshare.run(proj)
    assert [(f.rule, f.detail) for f in findings] == \
        [("unlocked-global-mutation", "_WARNED")]
    assert findings[0].severity == "high"
    assert "BOTH the event loop and to_thread workers" in findings[0].message


def test_threadshare_lock_guard_vouches(tmp_path):
    proj = _project(tmp_path, _dual_ctx_files(guarded=True))
    assert threadshare.run(proj) == []


def test_threadshare_self_attr_and_lock_covered_helper(tmp_path):
    """Self-attribute mutations on a dual-context class are high unless
    the mutation is under the class lock — or in a helper the public
    methods only ever call UNDER the lock (the FlightRecorder._get
    idiom: _lock-guarded-by-construction types vouch themselves)."""
    proj = _project(tmp_path, {
        "app/rec.py": """
            import asyncio
            import threading

            class Recorder:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._rounds = {}
                    self._peers = {}

                def _get(self, r):
                    # mutates WITHOUT taking the lock itself...
                    rec = self._rounds.get(r)
                    if rec is None:
                        rec = self._rounds[r] = {"events": []}
                    return rec

                def note(self, r, ev):
                    with self._lock:
                        # ...but every call site holds it: vouched
                        self._get(r)["events"].append(ev)

                def bad_note_peer(self, idx):
                    self._peers[idx] = True  # unlocked mutation

                async def loop_reader(self, r):
                    with self._lock:
                        return dict(self._rounds.get(r) or {})

                async def loop_peers(self):
                    return dict(self._peers)

                def worker(self, r, ev, idx):
                    self.note(r, ev)
                    self.bad_note_peer(idx)

                async def ingest(self, r, ev):
                    await asyncio.to_thread(self.worker, r, ev, 1)
                    self.note(r, ev)
                    self.bad_note_peer(2)
                    await self.loop_reader(r)
                    await self.loop_peers()
        """,
    })
    findings = threadshare.run(proj)
    assert [(f.symbol.rsplit(".", 1)[-1], f.detail) for f in findings] == \
        [("bad_note_peer", "_peers")]
    assert findings[0].rule == "unlocked-shared-mutation"
    assert findings[0].severity == "high"


def test_threadshare_single_context_mutation_is_clean(tmp_path):
    """Loop-only state needs no lock: without a thread-side toucher the
    same unlocked mutation is not a finding (the ChainStore.cache /
    Handler pattern — loop-confined by construction)."""
    proj = _project(tmp_path, {
        "app/loop_only.py": """
            class Collector:
                def __init__(self):
                    self._rounds = {}

                def append(self, r, p):
                    self._rounds.setdefault(r, []).append(p)

            async def ingest(c, r, p):
                c.append(r, p)

            async def serve(c, r):
                return list(c._rounds.get(r, ()))
        """,
    })
    assert threadshare.run(proj) == []


def test_threadshare_real_tree_chain_engine_is_loop_confined():
    """The ISSUE expected findings in chain/engine/ — the passes proved
    the collector plane is loop-confined instead (every PartialCache /
    Handler / ChainStore mutation happens on the loop; only the
    pairing work itself is handed to threads, by value). Pin that
    invariant: none of their attributes may become dual-context without
    a lock showing up here as a finding."""
    proj = Project(REPO, packages=("drand_tpu",))
    _, _, dual_attrs, _, _ = threadshare.analyze(proj)
    for cls in ("drand_tpu.chain.engine.cache.PartialCache",
                "drand_tpu.chain.engine.cache.RoundCache",
                "drand_tpu.chain.engine.chain_store.ChainStore",
                "drand_tpu.chain.engine.handler.Handler"):
        shared = {a for c, a in dual_attrs if c == cls}
        assert not shared, f"{cls} attrs went dual-context: {shared}"
    assert threadshare.run(proj) == []


# ---------------------------------------------------------------------------
# awaitatomic
# ---------------------------------------------------------------------------


def test_awaitatomic_check_then_act_and_recheck_fix(tmp_path):
    """The TOCTOU cache shape is flagged; the documented re-check fix
    and a branch that writes BEFORE its first await are clean."""
    proj = _project(tmp_path, {
        "app/cachemod.py": """
            class C:
                async def bad(self):
                    if self._info is None:
                        self._info = await self.fetch()
                    return self._info

                async def bad_multiline(self, key):
                    if key not in self._cache:
                        val = await self.compute(key)
                        self._cache[key] = val
                    return self._cache[key]

                async def good_recheck(self):
                    if self._info is None:
                        got = await self.fetch()
                        if self._info is None:
                            self._info = got
                    return self._info

                async def good_write_before_await(self):
                    if self._busy is False:
                        self._busy = True
                        await self.work()
                    return self._busy
        """,
    })
    findings = awaitatomic.run(proj)
    got = {(f.symbol.rsplit(".", 1)[-1], f.detail) for f in findings}
    assert got == {("bad", "_info"), ("bad_multiline", "_cache")}
    assert all(f.severity == "medium" for f in findings)
    assert all(f.rule == "check-then-act" for f in findings)


def test_awaitatomic_async_lock_suppresses(tmp_path):
    """A check-then-act serialized by an asyncio lock (async with) is
    correct — tasks can no longer interleave between check and act."""
    proj = _project(tmp_path, {
        "app/locked.py": """
            class C:
                async def good(self):
                    async with self._info_lock:
                        if self._info is None:
                            self._info = await self.fetch()
                    return self._info
        """,
    })
    assert awaitatomic.run(proj) == []


def test_awaitatomic_escalates_thread_shared(tmp_path):
    """Medium becomes HIGH when the attribute is also touched from
    worker threads (threadshare's dual-context map): then the stale
    check races OS threads, not just cooperative tasks."""
    proj = _project(tmp_path, {
        "app/svc.py": """
            import asyncio

            class S:
                def worker(self):
                    return self._conn.query()

                async def bad(self):
                    if self._conn is None:
                        self._conn = await self.dial()
                    return await asyncio.to_thread(self.worker)
        """,
    })
    findings = awaitatomic.run(proj)
    assert [(f.rule, f.severity, f.detail) for f in findings] == \
        [("check-then-act-threaded", "high", "_conn")]
    assert "threadshare" in findings[0].message


def test_awaitatomic_project_shaped_timelock_fixture(tmp_path):
    """Project-shaped fixture reproducing the live TimelockService.info
    finding (fixed in this PR with the re-check idiom): the pre-fix
    shape is a finding, the shipped shape is clean."""
    before = _project(tmp_path / "before", {
        "drand_tpu/timelock/service.py": """
            class TimelockService:
                async def info(self):
                    if self._info is None:
                        self._info = await self._client.info()
                    return self._info
        """,
    })
    findings = awaitatomic.run(before)
    assert [(f.symbol, f.detail) for f in findings] == \
        [("drand_tpu.timelock.service.TimelockService.info", "_info")]

    after = _project(tmp_path / "after", {
        "drand_tpu/timelock/service.py": """
            class TimelockService:
                async def info(self):
                    if self._info is None:
                        got = await self._client.info()
                        if self._info is None:
                            self._info = got
                    return self._info
        """,
    })
    assert awaitatomic.run(after) == []


def test_awaitatomic_real_tree_clean():
    proj = Project(REPO, packages=("drand_tpu",))
    assert awaitatomic.run(proj) == []


# ---------------------------------------------------------------------------
# call-graph decorator fix (ISSUE 13 satellite)
# ---------------------------------------------------------------------------


def test_decorated_async_def_reaching_pairing_leaf_is_caught(tmp_path):
    """A functools.wraps-style decorated async def reaching a pairing
    leaf is flagged — decoration must not hide the path."""
    proj = _project(tmp_path, {
        "drand_tpu/crypto/batch.py": """
            def verify_beacons(pub, beacons):
                return [True] * len(beacons)
        """,
        "app/svc.py": """
            import functools
            from drand_tpu.crypto import batch

            def logged(f):
                @functools.wraps(f)
                async def inner(*a, **k):
                    return await f(*a, **k)
                return inner

            @logged
            async def handler(pub, chunk):
                return batch.verify_beacons(pub, chunk)
        """,
    })
    findings = loopblock.run(proj)
    assert any(f.symbol == "app.svc.handler" and f.severity == "high"
               for f in findings)


def test_decorator_wrapper_body_taints_decorated_calls(tmp_path):
    """The fixed blind spot: calling a decorated function executes the
    WRAPPER's body too. A decorator that sleeps (or locks) around every
    call it wraps now taints async callers of the decorated name."""
    proj = _project(tmp_path, {
        "app/deco.py": """
            import functools
            import time

            def throttled(f):
                @functools.wraps(f)
                def inner(*a, **k):
                    time.sleep(0.2)
                    return f(*a, **k)
                return inner

            @throttled
            def lookup(key):
                return key

            async def handler(key):
                return lookup(key)
        """,
    })
    findings = loopblock.run(proj)
    bad = [f for f in findings if f.symbol == "app.deco.handler"]
    assert len(bad) == 1 and "time.sleep" in bad[0].message


def test_decorator_wrapper_lock_held_across_wrapped_pairing(tmp_path):
    """lockheld sees through the decoration too: a pass-through wrapper
    that holds a lock while invoking the wrapped function is a
    lock-over-pairing finding once any wrapped function is
    pairing-class."""
    proj = _project(tmp_path, {
        "drand_tpu/crypto/batch.py": """
            def verify_beacons(pub, beacons):
                return [True] * len(beacons)
        """,
        "app/deco.py": """
            import functools
            import threading

            _LOCK = threading.Lock()

            def serialized(f):
                @functools.wraps(f)
                def inner(*a, **k):
                    with _LOCK:
                        return f(*a, **k)
                return inner

            @serialized
            def verify(pub, chunk):
                from drand_tpu.crypto import batch

                return batch.verify_beacons(pub, chunk)
        """,
    })
    findings = lockheld.run(proj)
    assert [f.symbol for f in findings] == \
        ["app.deco.serialized.inner"]
    assert findings[0].rule == "lock-over-pairing"


# ---------------------------------------------------------------------------
# baseline round-trip + prune for the new pass names
# ---------------------------------------------------------------------------


def test_new_passes_baseline_roundtrip_and_prune(tmp_path):
    """A lockheld/awaitatomic finding suppresses through the baseline
    like any other; fixing the code flags the entry stale; and
    --prune-baseline drops ONLY entries whose pass ran, preserving the
    written reasons of everything kept."""
    root = _tree(tmp_path, {
        "app/svc.py": """
            import threading

            class S:
                _lock = threading.Lock()

                async def held(self, peer):
                    with self._lock:
                        return await peer.call()

                async def cachey(self):
                    if self._v is None:
                        self._v = await self.f()
                    return self._v
        """,
    })
    passes = ("lockheld", "awaitatomic")
    report = run_analysis(root=root, passes=passes,
                          baseline_path=tmp_path / "missing.json")
    keys = sorted(f.key for f in report["findings"])
    assert len(keys) == 2
    assert keys[0].startswith("awaitatomic:check-then-act:")
    assert keys[1].startswith("lockheld:lock-across-await:")

    bl = tmp_path / "baseline.json"
    entries = [{"key": k, "reason": f"fixture: reviewed entry {i}"}
               for i, k in enumerate(keys)]
    entries.append({"key": "jaxhazard:gone:app/x.py:app.x.f",
                    "reason": "fixture: pass not run, must survive prune"})
    bl.write_text(json.dumps({"entries": entries}))

    report = run_analysis(root=root, passes=passes, baseline_path=bl)
    assert report["findings"] == []
    assert sorted(f.key for f in report["suppressed"]) == keys

    # fix the lockheld site -> its entry goes stale, prune drops it
    (tmp_path / "app" / "svc.py").write_text(textwrap.dedent("""
        import threading

        class S:
            _lock = threading.Lock()

            async def held(self, peer):
                return await peer.call()

            async def cachey(self):
                if self._v is None:
                    self._v = await self.f()
                return self._v
    """))
    report = run_analysis(root=root, passes=passes, baseline_path=bl)
    assert any(f.rule == "stale-entry" for f in report["findings"])
    dropped, kept = prune_baseline(report, passes, bl)
    assert dropped == [keys[1]]
    assert kept == 2
    doc = json.loads(bl.read_text())
    kept_keys = [e["key"] for e in doc["entries"]]
    assert keys[0] in kept_keys                      # still matching
    assert "jaxhazard:gone:app/x.py:app.x.f" in kept_keys  # pass not run
    assert doc["entries"][0]["reason"].startswith("fixture:")

    # post-prune the tree round-trips clean (no stale entries left
    # for the passes that ran)
    report = run_analysis(root=root, passes=passes, baseline_path=bl)
    assert [f.rule for f in report["findings"]] == []


def test_sarif_output_shape(tmp_path):
    """--sarif: findings as SARIF 2.1.0 with severity mapped to level
    and the baseline key as a stable fingerprint."""
    root = _tree(tmp_path, {
        "app/svc.py": """
            import threading

            class S:
                _lock = threading.Lock()

                async def held(self, peer):
                    with self._lock:
                        return await peer.call()
        """,
    })
    report = run_analysis(root=root, passes=("lockheld",),
                          baseline_path=tmp_path / "missing.json")
    doc = to_sarif(report)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "drand-tpu-analyze"
    (res,) = run["results"]
    assert res["ruleId"] == "lockheld/lock-across-await"
    assert res["level"] == "error"
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "app/svc.py"
    assert loc["region"]["startLine"] >= 1
    assert res["partialFingerprints"]["drandAnalyzeKey/v1"] == \
        report["findings"][0].key
    rule_ids = [r["id"] for r in run["tool"]["driver"]["rules"]]
    assert rule_ids == ["lockheld/lock-across-await"]

    out = tmp_path / "out.sarif"
    write_sarif(report, out)
    assert json.loads(out.read_text())["version"] == "2.1.0"


# ---------------------------------------------------------------------------
# thread hammers: the fixed shared state survives real contention
# ---------------------------------------------------------------------------


def _hammer(n_threads: int, fn) -> None:
    barrier = threading.Barrier(n_threads)
    errs = []

    def runner():
        barrier.wait()
        try:
            for _ in range(200):
                fn()
        except Exception as e:  # noqa: BLE001 — surface in the test
            errs.append(e)

    threads = [threading.Thread(target=runner) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert errs == []


def _hist_count(metric, **labels) -> float:
    for family in metric.collect():
        for s in family.samples:
            if s.name.endswith("_count") and all(
                    s.labels.get(k) == v for k, v in labels.items()):
                return s.value
    return 0.0


def test_hammer_warm_shapes_single_compile_sample():
    """4 threads racing the same cold (op, path, batch) shape through
    the _timed compile split: exactly ONE dispatch claims the
    engine_compile_seconds sample; every other lands in
    engine_op_seconds (pre-fix, every racer could claim it and the
    steady-state series silently lost their samples)."""
    from drand_tpu import metrics
    from drand_tpu.crypto import batch

    key_op = "verify_beacons"
    # the shape's own series: an earlier test's dispatch at another
    # batch bucket is a different sample
    bucket = metrics.batch_bucket(64)
    with batch._STATE_LOCK:
        batch._WARM_SHAPES.clear()
    before_compile = _hist_count(metrics.ENGINE_COMPILE_SECONDS, op=key_op)
    before_ops = _hist_count(metrics.ENGINE_OP_SECONDS, op=key_op,
                             path="device", batch=bucket)

    def one():
        with batch._timed(key_op, "device", 64):
            pass

    _hammer(4, one)
    compiles = _hist_count(metrics.ENGINE_COMPILE_SECONDS,
                           op=key_op) - before_compile
    ops = _hist_count(metrics.ENGINE_OP_SECONDS, op=key_op,
                      path="device", batch=bucket) - before_ops
    assert compiles == 1.0
    assert ops == 4 * 200 - 1
    with batch._STATE_LOCK:
        batch._WARM_SHAPES.clear()


def test_hammer_fallback_warn_once_and_rearm(monkeypatch):
    """4 threads hammering _note_fallback warn exactly once; a device
    success re-arms, and the next failure burst warns exactly once
    again."""
    from drand_tpu.crypto import batch
    from drand_tpu.utils import logging as dlog

    warns = []

    class _L:
        def warn(self, *a, **k):
            warns.append((a, k))

    monkeypatch.setattr(dlog, "default_logger", lambda name: _L())
    batch._note_device_ok()  # known re-armed start state
    _hammer(4, lambda: batch._note_fallback("verify_beacons",
                                            RuntimeError("boom")))
    assert len(warns) == 1
    batch._note_device_ok()
    _hammer(4, lambda: batch._note_fallback("verify_beacons",
                                            RuntimeError("boom2")))
    assert len(warns) == 2
    batch._note_device_ok()


def test_hammer_ecies_warn_once(monkeypatch):
    from drand_tpu.crypto import ecies
    from drand_tpu.utils import logging as dlog

    warns = []

    class _L:
        def warn(self, *a, **k):
            warns.append(a)

    monkeypatch.setattr(dlog, "default_logger", lambda name: _L())
    monkeypatch.setattr(ecies, "_FALLBACK_WARNED", False)
    _hammer(4, ecies._warn_fallback)
    assert len(warns) == 1


def test_hammer_engine_singleton_builds_one_engine(monkeypatch):
    """4 threads racing crypto/batch.engine() on a cold singleton build
    exactly one engine (the aggregator, sync and client catch-up
    workers all reach it at once; a second BatchedEngine would discard
    the first one's compiled executables and KAT verdicts)."""
    from drand_tpu.crypto import batch
    from drand_tpu.ops import engine as eng_mod

    built = []

    class _Engine:
        def __init__(self):
            built.append(self)
            time.sleep(0.01)  # widen the check-then-act window

    monkeypatch.setattr(eng_mod, "BatchedEngine", _Engine)
    monkeypatch.setattr(batch, "_MODE", "auto")
    monkeypatch.setattr(batch, "_ENGINE", None)
    _hammer(4, batch.engine)
    assert len(built) == 1 and batch._ENGINE is built[0]


# ---------------------------------------------------------------------------
# interleaving regressions for the fixed check-then-act caches
# ---------------------------------------------------------------------------


@pytest.mark.asyncio
async def test_timelock_info_first_publication_wins():
    """Two tasks race TimelockService.info() on a cold cache: both
    fetch, but the loser's result must not clobber the published one —
    both callers observe the SAME object (pre-fix each caller published
    its own fetch, so concurrent users held different Info objects and
    a slow fetch overwrote the one in active use)."""
    from drand_tpu.timelock.service import TimelockService
    from drand_tpu.timelock.vault import TimelockVault

    gate = asyncio.Event()
    fetched = []

    class _Client:
        async def info(self):
            obj = object()
            fetched.append(obj)
            await gate.wait()
            return obj

    vault = TimelockVault(":memory:")
    try:
        svc = TimelockService(vault, _Client())
        t1 = asyncio.ensure_future(svc.info())
        t2 = asyncio.ensure_future(svc.info())
        for _ in range(50):
            await asyncio.sleep(0)
            if len(fetched) == 2:
                break
        assert len(fetched) == 2  # both raced past the cold check
        gate.set()
        r1, r2 = await asyncio.gather(t1, t2)
        assert r1 is r2
        assert svc._info is r1
        assert await svc.info() is r1  # stable afterwards
    finally:
        vault.close()


@pytest.mark.asyncio
async def test_otlp_session_rebuild_is_single_flight(monkeypatch):
    """Two tasks hit _get_session while the cached session belongs to a
    dead loop: exactly ONE replacement is built (pre-fix both built
    one and the loser's ClientSession leaked unclosed forever)."""
    import aiohttp

    from drand_tpu.obs.export import OTLPExporter

    created = []

    class _FakeSession:
        def __init__(self, *a, **k):
            created.append(self)
            self.closed = False

        async def close(self):
            await asyncio.sleep(0.01)  # the suspension the race needs
            self.closed = True

    monkeypatch.setattr(aiohttp, "ClientSession", _FakeSession)
    exp = OTLPExporter(endpoint="http://collector:4318")
    stale = _FakeSession()
    created.clear()
    exp._session = stale
    exp._session_loop = object()  # "a previous event loop"

    s1, s2 = await asyncio.gather(exp._get_session(),
                                  exp._get_session())
    assert s1 is s2
    assert len(created) == 1
    assert stale.closed  # the old session was actually closed
    assert exp._session is s1


# ---------------------------------------------------------------------------
# the real tree, whole-suite
# ---------------------------------------------------------------------------


def test_real_tree_concurrency_passes_clean_and_fast():
    """The acceptance gate: all three concurrency passes run on the
    live tree with zero unsuppressed findings (the one lockheld finding
    carries a reviewed baseline entry), inside the host-only time
    budget (<10 s nominal; the bound here is padded for the contended
    1-core CI box)."""
    t0 = time.perf_counter()
    report = run_analysis(passes=("lockheld", "threadshare",
                                  "awaitatomic"))
    elapsed = time.perf_counter() - t0
    assert report["findings"] == [], "\n".join(
        f.render() for f in report["findings"])
    assert [f.pass_name for f in report["suppressed"]] == ["lockheld"]
    assert elapsed < 30.0
