"""Edge fan-out push tier (ISSUE 14): round-boundary SSE/NDJSON
streaming on /public/latest, explicit load shedding, SO_REUSEPORT
multi-process relay workers, and the packed segment chain store.

Late-alphabet filename per the tier-1 chunking convention
(tools/tier1_chunks.sh). Everything here is host-only — no pairings,
no device graphs, no backend init; the worker smoke test spawns real
CLI subprocesses on the wall clock (a few seconds, like the chaos
suite's socket scenarios).
"""

import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import aiohttp
import pytest

from conftest import sample_count
from drand_tpu import metrics
from drand_tpu.chain import time_math
from drand_tpu.chain.beacon import Beacon
from drand_tpu.chain.info import Info
from drand_tpu.chain.segments import SegmentStore, migrate_store
from drand_tpu.chain.store import SQLiteStore, StoreError
from drand_tpu.client.interface import Client, ClientError, Result
from drand_tpu.crypto.curves import PointG1
from drand_tpu.http_server import fanout
from drand_tpu.http_server.server import PublicServer
from drand_tpu.utils.clock import FakeClock

PERIOD = 5
GENESIS = 1_700_000_000
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SSE = {"Accept": "text/event-stream"}
NDJSON = {"Accept": "application/x-ndjson"}


class ScriptedUpstream(Client):
    """Deterministic /public/latest upstream on the FakeClock: yields
    one synthetic beacon per round boundary; ``dead=True`` makes every
    call fail (the degraded-upstream scenarios)."""

    def __init__(self, clock):
        self.clock = clock
        self.dead = False
        self.latest: Result | None = None

    async def info(self) -> Info:
        if self.dead:
            raise ClientError("upstream dead")
        return Info(public_key=PointG1.generator(), period=PERIOD,
                    genesis_time=GENESIS, genesis_seed=b"s" * 32,
                    group_hash=b"g" * 32)

    async def get(self, round_no: int = 0) -> Result:
        if self.dead:
            raise ClientError("upstream dead")
        if round_no == 0 and self.latest is not None:
            return self.latest
        raise ClientError("round not available")

    async def watch(self):
        while True:
            if self.dead:
                raise ClientError("upstream dead")
            now = self.clock.now()
            next_r, next_t = time_math.next_round(int(now), PERIOD,
                                                  GENESIS)
            await self.clock.sleep(max(0.0, next_t - now))
            if self.dead:
                raise ClientError("upstream dead")
            r = next_r - 1
            self.latest = Result(round=r,
                                 signature=bytes([r % 251]) * 96)
            yield self.latest


async def _start(clock, client, **kw):
    server = PublicServer(client, clock=clock, **kw)
    site = await server.start("127.0.0.1", 0)
    port = site._server.sockets[0].getsockname()[1]
    await clock.settle()
    return server, f"http://127.0.0.1:{port}"


async def _read_sse_event(resp, timeout=5.0):
    """One SSE frame -> (round id, payload dict)."""
    rid, data = None, None
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        line = await asyncio.wait_for(resp.content.readline(), timeout)
        if line == b"\n" and data is not None:
            return rid, data
        if line.startswith(b"id: "):
            rid = int(line[4:].strip())
        elif line.startswith(b"data: "):
            data = json.loads(line[6:])
    raise TimeoutError("no complete SSE frame")


# ---------------------------------------------------------------------------
# tentpole: one hub publish fans a round out to every stream watcher
# ---------------------------------------------------------------------------


@pytest.mark.asyncio
async def test_sse_and_ndjson_fanout_one_wakeup_per_round():
    """N watchers across BOTH stream protocols all receive round N+1
    from ONE hub publish: the per-proto wakeup counter moves by exactly
    1 per round while every watcher sees the beacon — the
    not-O(watchers) cost model, asserted at the meter."""
    clock = FakeClock(start=GENESIS + 1)
    upstream = ScriptedUpstream(clock)
    server, url = await _start(clock, upstream)
    sess = aiohttp.ClientSession()
    try:
        sse = [await sess.get(url + "/public/latest", headers=SSE)
               for _ in range(3)]
        nd = [await sess.get(url + "/public/latest", headers=NDJSON)
              for _ in range(2)]
        assert all(r.status == 200 for r in sse + nd)
        assert metrics.RELAY_WATCHERS._value.get() == 5
        wake_sse = sample_count(metrics.HTTP_REGISTRY,
                                "relay_wakeups", proto="sse")
        wake_nd = sample_count(metrics.HTTP_REGISTRY,
                               "relay_wakeups", proto="ndjson")

        await clock.advance(PERIOD)
        for resp in sse:
            rid, d = await _read_sse_event(resp)
            assert rid == 1 and d["round"] == 1
        for resp in nd:
            line = await asyncio.wait_for(resp.content.readline(), 5)
            assert json.loads(line)["round"] == 1

        assert sample_count(metrics.HTTP_REGISTRY, "relay_wakeups",
                            proto="sse") == wake_sse + 1
        assert sample_count(metrics.HTTP_REGISTRY, "relay_wakeups",
                            proto="ndjson") == wake_nd + 1

        # second round: another single publish per proto
        await clock.advance(PERIOD)
        for resp in sse:
            rid, d = await _read_sse_event(resp)
            assert rid == 2 and d["round"] == 2
        for resp in nd:
            line = await asyncio.wait_for(resp.content.readline(), 5)
            assert json.loads(line)["round"] == 2
        assert sample_count(metrics.HTTP_REGISTRY, "relay_wakeups",
                            proto="sse") == wake_sse + 2
        assert sample_count(metrics.HTTP_REGISTRY, "relay_wakeups",
                            proto="ndjson") == wake_nd + 2
        for resp in sse + nd:
            resp.close()
    finally:
        await sess.close()
        await server.stop()


@pytest.mark.asyncio
async def test_slow_consumer_disconnected_at_queue_bound():
    """A subscriber whose bounded queue fills is DISCONNECTED (drain +
    sentinel), counted on relay_shed_total{reason=slow_consumer} —
    never buffered unboundedly. A healthy subscriber in the same hub
    keeps its stream."""
    hub = fanout.FanoutHub(queue_max=2)
    slow = hub.subscribe(fanout.PROTO_SSE)
    healthy = hub.subscribe(fanout.PROTO_NDJSON)
    shed0 = sample_count(metrics.HTTP_REGISTRY, "relay_shed",
                         reason="slow_consumer")
    reached = []
    for r in range(1, 5):
        reached.append(hub.publish({"round": r}, r))
        # the healthy consumer drains every round, the slow one never
        rnd, frame = await asyncio.wait_for(healthy.next(), 1)
        assert rnd == r and json.loads(frame)["round"] == r
    # rounds 1,2 queued for slow; round 3's publish shed it
    assert slow.shed
    assert hub.watcher_count() == 1
    assert sample_count(metrics.HTTP_REGISTRY, "relay_shed",
                        reason="slow_consumer") == shed0 + 1
    # the slow consumer's next read is the close sentinel, immediately
    assert await asyncio.wait_for(slow.next(), 1) is None
    # reached counts dropped from 2 subscribers to 1
    assert reached[0] == 2 and reached[-1] == 1
    hub.close_all()
    assert await asyncio.wait_for(healthy.next(), 1) is None


@pytest.mark.asyncio
async def test_shed_429_retry_after_lands_on_next_boundary():
    """Above the watcher cap the server sheds BEFORE handler work: 429
    with Retry-After aligned to the next round boundary (FakeClock
    exact), relay_shed_total{reason=watcher_cap} counted; a slot
    freeing up re-admits new watchers."""
    clock = FakeClock(start=GENESIS + 1)
    upstream = ScriptedUpstream(clock)
    server, url = await _start(clock, upstream, max_watchers=1)
    sess = aiohttp.ClientSession()
    try:
        held = await sess.get(url + "/public/latest", headers=SSE)
        assert held.status == 200
        # advance into the middle of a round so the boundary math is
        # non-trivial: now = genesis+1+7 -> next boundary at +10s
        await clock.advance(2)
        shed0 = sample_count(metrics.HTTP_REGISTRY, "relay_shed",
                             reason="watcher_cap")
        resp = await sess.get(url + "/public/latest", headers=NDJSON)
        assert resp.status == 429
        now = clock.now()
        _, next_t = time_math.next_round(int(now), PERIOD, GENESIS)
        assert resp.headers["Retry-After"] == str(int(next_t - now))
        assert sample_count(metrics.HTTP_REGISTRY, "relay_shed",
                            reason="watcher_cap") == shed0 + 1
        # plain GET pollers are never shed by the watcher cap
        poll = await sess.get(url + "/public/latest")
        assert poll.status in (200, 404)  # no beacon yet is fine
        # free the slot -> a new stream is admitted. Disconnects are
        # detected at the next write (bounded by one round period):
        # advance a boundary so the publish hits the closed socket.
        held.close()
        await asyncio.sleep(0.05)
        await clock.advance(PERIOD)
        for _ in range(100):
            if metrics.RELAY_WATCHERS._value.get() == 0:
                break
            await asyncio.sleep(0.05)
        assert metrics.RELAY_WATCHERS._value.get() == 0
        again = await sess.get(url + "/public/latest", headers=SSE)
        assert again.status == 200
        again.close()
    finally:
        await sess.close()
        await server.stop()


@pytest.mark.asyncio
async def test_stale_upstream_preserved_on_streams_and_polls():
    """Upstream dies: a NEW stream watcher still connects (200) with
    X-Drand-Stale carrying the lag and the last-known beacon as its
    snapshot frame; the plain-GET degraded path keeps no-store and
    never carries an ETag. The watch loop's restart rides the retry
    policy (net_retry_attempts_total{op=watch} moves) and recovery
    resumes the push stream."""
    clock = FakeClock(start=GENESIS + 1)
    upstream = ScriptedUpstream(clock)
    server, url = await _start(clock, upstream)
    sess = aiohttp.ClientSession()
    try:
        await clock.advance(PERIOD)  # round 1 published, info cached
        upstream.dead = True
        retries0 = sample_count(metrics.GROUP_REGISTRY,
                                "net_retry_attempts", op="watch")
        await clock.advance(PERIOD * 3)
        # streams: connect DURING the outage
        resp = await sess.get(url + "/public/latest", headers=SSE)
        assert resp.status == 200
        assert int(resp.headers["X-Drand-Stale"]) >= 2
        rid, d = await _read_sse_event(resp)
        assert rid == 1 and d["round"] == 1  # last-known snapshot
        # plain GET: stale 200, no-store, NO ETag on the degraded path
        poll = await sess.get(url + "/public/latest")
        assert poll.status == 200
        assert int(poll.headers["X-Drand-Stale"]) >= 2
        assert poll.headers["Cache-Control"] == "no-store"
        assert "ETag" not in poll.headers
        # the restart loop is riding the policy, not a raw sleep
        assert sample_count(metrics.GROUP_REGISTRY,
                            "net_retry_attempts", op="watch") > retries0
        # recovery: the stream watcher resumes at the next boundary
        upstream.dead = False
        await clock.advance(PERIOD * 4)
        rid, d = await _read_sse_event(resp)
        assert d["round"] > 1
        resp.close()
    finally:
        await sess.close()
        await server.stop()


@pytest.mark.asyncio
async def test_latest_etag_304_for_pollers():
    """Non-stream GET /public/latest: round-keyed ETag +
    If-None-Match -> 304 (a poller between rounds costs a header, not
    a body); the ETag rolls with the round."""
    clock = FakeClock(start=GENESIS + 1)
    upstream = ScriptedUpstream(clock)
    server, url = await _start(clock, upstream)
    sess = aiohttp.ClientSession()
    try:
        await clock.advance(PERIOD)
        r1 = await sess.get(url + "/public/latest")
        assert r1.status == 200
        etag = r1.headers["ETag"]
        assert etag == '"r1"'
        assert r1.headers["Cache-Control"] == "no-cache"
        r304 = await sess.get(url + "/public/latest",
                              headers={"If-None-Match": etag})
        assert r304.status == 304
        assert r304.headers["ETag"] == etag
        assert await r304.read() == b""
        # stale validator after the round advances -> fresh 200
        await clock.advance(PERIOD)
        r2 = await sess.get(url + "/public/latest",
                            headers={"If-None-Match": etag})
        assert r2.status == 200
        assert r2.headers["ETag"] == '"r2"'
    finally:
        await sess.close()
        await server.stop()


# ---------------------------------------------------------------------------
# segment storage
# ---------------------------------------------------------------------------


def _chain(n, v2_every=2):
    out, prev = [], b""
    for r in range(n):
        sig = b"seed" * 8 if r == 0 else bytes([r % 251]) * 96
        out.append(Beacon(
            round=r, previous_sig=prev, signature=sig,
            signature_v2=(b"v" * 96 if r and r % v2_every == 0 else b"")))
        prev = sig
    return out


def test_segment_store_roundtrip_and_depth(tmp_path):
    """Field-exact round-trip (genesis empty prev, v2 present/absent),
    O(1) get at million-round depth, cursor_from across a segment
    boundary and across holes, del_round, len, reopen persistence, and
    the oversize-field guard."""
    store = SegmentStore(str(tmp_path / "segments"))
    beacons = _chain(20)
    for b in beacons:
        store.put(b)
    for b in beacons:
        assert store.get(b.round).equal(b)
    assert store.get(10_000) is None and store.get(-1) is None
    assert len(store) == 20 and store.last().round == 19

    # depth: a record a million rounds out is a seek, not a scan —
    # and it lands in a different segment file
    deep = Beacon(round=1_000_000, previous_sig=b"p" * 96,
                  signature=b"q" * 96, signature_v2=b"r" * 96)
    store.put(deep)
    t0 = time.perf_counter()
    assert store.get(1_000_000).equal(deep)
    assert time.perf_counter() - t0 < 0.1
    assert store.last().round == 1_000_000
    # cursor across the hole: 19 -> 1_000_000 directly
    assert [b.round for b in store.cursor_from(15)] == \
        [15, 16, 17, 18, 19, 1_000_000]

    # segment-boundary crossing (default segment = 65536 rounds)
    for r in range(65_534, 65_539):
        store.put(Beacon(round=r, previous_sig=b"x" * 96,
                         signature=bytes([r % 251]) * 96))
    assert [b.round for b in store.cursor_from(65_534)] == \
        [65_534, 65_535, 65_536, 65_537, 65_538, 1_000_000]

    store.del_round(1_000_000)
    assert store.get(1_000_000) is None
    assert store.last().round == 65_538

    # del_from rollback (`util del-beacon` on a segment chain): the
    # partial segment truncates, whole segments past the cut vanish
    assert store.del_from(65_536) == 3
    assert store.last().round == 65_535
    assert store.get(65_537) is None
    assert [b.round for b in store.cursor_from(65_530)] == \
        [65_534, 65_535]

    reads0 = sample_count(metrics.GROUP_REGISTRY,
                          "chain_store_reads", backend="segment")
    assert store.get(2) is not None
    assert sample_count(metrics.GROUP_REGISTRY, "chain_store_reads",
                        backend="segment") == reads0 + 1

    store.close()
    reopened = SegmentStore(str(tmp_path / "segments"))
    assert reopened.last().round == 65_535
    assert [b.round for b in reopened.cursor_from(60_000)] == \
        [65_534, 65_535]
    assert reopened.get(7).equal(beacons[7])

    with pytest.raises(StoreError):
        reopened.put(Beacon(round=5, signature=b"z" * 97))
    reopened.close()


def test_store_migrate_equivalence_vs_sqlite(tmp_path, capsys):
    """`drand-tpu util store-migrate` converts a SQLite chain to the
    segment format (and back) with byte-exact beacon equality at every
    round; the AppendStore-visible surface (last/get/cursor) agrees."""
    from drand_tpu.cli.__main__ import main as cli_main

    db = str(tmp_path / "chain.db")
    sq = SQLiteStore(db)
    beacons = _chain(40, v2_every=3)
    for b in beacons:
        sq.put(b)
    sq.close()

    cli_main(["util", "store-migrate", "--db", db,
              "--out", str(tmp_path / "segments")])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["migrated"] == 40

    seg = SegmentStore(str(tmp_path / "segments"))
    sq = SQLiteStore(db)
    pairs = list(zip(sq.cursor(), seg.cursor()))
    assert len(pairs) == 40
    assert all(a.equal(b) for a, b in pairs)
    assert seg.last().equal(sq.last())
    sq.close()
    seg.close()

    # reverse: segment -> fresh sqlite, still byte-exact
    db2 = str(tmp_path / "chain2.db")
    cli_main(["util", "store-migrate", "--db", db2,
              "--out", str(tmp_path / "segments"), "--reverse"])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["migrated"] == 40
    back = SQLiteStore(db2)
    assert all(a.equal(b) for a, b in zip(beacons, back.cursor()))
    back.close()


# ---------------------------------------------------------------------------
# SO_REUSEPORT worker smoke
# ---------------------------------------------------------------------------


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _sub_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env.pop("JAX_PLATFORMS", None)  # workers never touch the backend
    return env


class _StubOrigin:
    """Wall-clock origin for the worker subprocesses: one-second
    period, REAL BLS-signed chained beacons (the relay's verifying
    client stack checks every signature even with --insecure — that
    flag only waives the chain-hash trust pin)."""

    def __init__(self):
        import hashlib

        from drand_tpu.chain.beacon import message
        from drand_tpu.crypto import bls

        self.period = 1
        self.genesis = int(time.time()) - 3
        self.sk, self.pub = bls.keygen(b"zz-fanout-origin-seed-0123456789")
        self._sigs = {0: b"zz-fanout-genesis-seed-0123456789"}
        self._sign = lambda r, prev: bls.sign(self.sk, message(r, prev))
        self._sha = hashlib.sha256

    def _sig(self, r):
        if r not in self._sigs:
            self._sigs[r] = self._sign(r, self._sig(r - 1))
        return self._sigs[r]

    def _beacon(self, r):
        sig = self._sig(r)
        return {"round": r, "signature": sig.hex(),
                "previous_signature": self._sig(r - 1).hex(),
                "randomness": self._sha(sig).hexdigest()}

    async def start(self):
        from aiohttp import web

        async def info(request):
            return web.json_response({
                "public_key": self.pub.to_bytes().hex(),
                "period": self.period, "genesis_time": self.genesis,
                "group_hash": "67" * 32, "hash": "67" * 32})

        async def latest(request):
            r = time_math.current_round(int(time.time()), self.period,
                                        self.genesis)
            return web.json_response(self._beacon(r))

        async def by_round(request):
            r = int(request.match_info["round"])
            cur = time_math.current_round(int(time.time()), self.period,
                                          self.genesis)
            if r > cur:
                return web.json_response({"error": "not yet"}, status=404)
            return web.json_response(self._beacon(r))

        app = web.Application()
        app.add_routes([web.get("/info", info),
                        web.get("/public/latest", latest),
                        web.get("/public/{round}", by_round)])
        self.runner = web.AppRunner(app)
        await self.runner.setup()
        site = web.TCPSite(self.runner, "127.0.0.1", 0)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]
        return f"http://127.0.0.1:{self.port}"


def test_reuseport_worker_smoke():
    """`relay --workers 2`: both workers accept on ONE port via
    SO_REUSEPORT; killing one worker leaves the survivor's watchers
    streaming undisturbed; SIGTERM drains the group gracefully."""

    async def run():
        origin = _StubOrigin()
        origin_url = await origin.start()
        port = _free_port()
        parent = subprocess.Popen(
            [sys.executable, "-m", "drand_tpu.cli", "relay",
             "--url", origin_url, "--listen", f"127.0.0.1:{port}",
             "--insecure", "--workers", "2"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=_sub_env(), cwd=REPO)
        url = f"http://127.0.0.1:{port}"
        sess = aiohttp.ClientSession()
        streams = []  # (worker pid, response)
        try:
            # wait for the shared port to accept
            deadline = time.time() + 30
            while time.time() < deadline:
                try:
                    resp = await sess.get(url + "/public/latest",
                                          headers=SSE)
                    if resp.status == 200:
                        streams.append(
                            (int(resp.headers["X-Drand-Worker"]), resp))
                        break
                    resp.close()
                except aiohttp.ClientError:
                    pass
                await asyncio.sleep(0.3)
            assert streams, "relay workers never came up"
            # connect until BOTH workers hold a stream: the kernel hashes
            # connections over the sockets bound so far, and the second
            # worker may bind a little after the first accepts
            deadline = time.time() + 30
            while len(streams) < 2 and time.time() < deadline:
                resp = await sess.get(url + "/public/latest", headers=SSE)
                assert resp.status == 200
                pid = int(resp.headers["X-Drand-Worker"])
                if pid in {p for p, _ in streams}:
                    resp.close()
                    await asyncio.sleep(0.1)
                else:
                    streams.append((pid, resp))
            pids = {pid for pid, _ in streams}
            assert len(pids) == 2, f"only saw workers {pids}"

            victim = min(pids)
            survivor = max(pids)
            os.kill(victim, signal.SIGKILL)
            # watchers on the SURVIVOR keep receiving rounds
            surv_resp = next(r for pid, r in streams if pid == survivor)
            rid, d = await _read_sse_event(surv_resp, timeout=10)
            assert d["round"] >= 1
            rid2, _ = await _read_sse_event(surv_resp, timeout=10)
            assert rid2 > rid  # still advancing after the kill
            # new connections land on the survivor (the dead worker's
            # socket is gone from the reuseport group); retry a couple
            # of times — connections parked in the dead worker's accept
            # queue at kill time are lost, not redistributed
            fresh = None
            for _ in range(5):
                try:
                    fresh = await asyncio.wait_for(
                        sess.get(url + "/public/latest", headers=SSE), 5)
                    break
                except (aiohttp.ClientError, asyncio.TimeoutError):
                    await asyncio.sleep(0.3)
            assert fresh is not None and fresh.status == 200
            assert int(fresh.headers["X-Drand-Worker"]) == survivor
            fresh.close()
            # graceful drain: SIGTERM the parent; the survivor ends the
            # stream cleanly. The parent exits 1, not 0: the SIGKILLed
            # worker is a crash and must surface to any supervisor
            parent.send_signal(signal.SIGTERM)
            end = await asyncio.wait_for(surv_resp.content.read(), 15)
            assert isinstance(end, bytes)  # stream ended, not reset
            assert parent.wait(timeout=15) == 1
        finally:
            for _, r in streams:
                r.close()
            await sess.close()
            await origin.runner.cleanup()
            if parent.poll() is None:
                parent.kill()
                parent.wait(timeout=10)

    asyncio.run(run())
