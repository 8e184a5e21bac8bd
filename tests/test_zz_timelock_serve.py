"""Timelock serving tier (ISSUE 9): crypto, vault, service, HTTP, engine.

The one device test compiles the shared-signature GT graph; the tier-1
scheduler (tests/conftest.py, GRAPH_GROUPS) sends it out early.

Covers the adversarial matrix (wrong-round signature, truncated V,
flipped W byte, pre-V2 beacon, cross-chain ciphertext, empty/large
plaintext, malformed/swapped U), the accept/reject bit-identity between
the batched tiers and the per-item host oracle, the ONE-dispatch meter
proof, the KAT-failure fallback ledger, and vault persistence across a
simulated daemon restart.
"""

from __future__ import annotations

import asyncio
import base64

import pytest

from drand_tpu.chain.beacon import Beacon, message, message_v2
from drand_tpu.chain.info import Info
from drand_tpu.client import timelock as client_timelock
from drand_tpu.client.interface import Client, ClientError, Result
from drand_tpu.crypto import batch, bls
from drand_tpu.crypto import pairing as host_pairing
from drand_tpu.crypto import timelock as tl
from drand_tpu.crypto.curves import PointG1
from drand_tpu.crypto.fields import R
from drand_tpu.crypto.hash_to_curve import hash_to_g2

SK, PUB = bls.keygen(seed=b"zz-timelock-tests")
INFO = Info(public_key=PUB, period=3, genesis_time=1_700_000_000,
            genesis_seed=b"\x07" * 32)
ROUND = 42
IDENT = message_v2(ROUND)
SIG_BYTES = bls.sign(SK, IDENT)


def _result(rd: int, v2: bool = True) -> Result:
    return Result(round=rd, signature=bls.sign(SK, message(rd, b"prev")),
                  signature_v2=bls.sign(SK, message_v2(rd)) if v2 else b"")


def _oracle_outcomes(sig_bytes: bytes, cts) -> list[tuple[bool, bytes]]:
    """The per-item host oracle's verdicts, as (ok, plaintext)."""
    out = []
    for ct in cts:
        try:
            out.append((True, tl.decrypt(sig_bytes, ct)))
        except ValueError:
            out.append((False, b""))
    return out


def _adversarial_matrix():
    """(label, Ciphertext) rows: the ISSUE 9 matrix, built against the
    round's real key material."""
    ok_ct = tl.encrypt(PUB, IDENT, b"sealed bid: 417")
    empty = tl.encrypt(PUB, IDENT, b"")
    large = tl.encrypt(PUB, IDENT, b"\xa5" * 65536)
    flipped_w = tl.Ciphertext(ok_ct.u, ok_ct.v,
                              bytes([ok_ct.w[0] ^ 1]) + ok_ct.w[1:])
    trunc_v = tl.Ciphertext(ok_ct.u, ok_ct.v[:-1], ok_ct.w)
    bad_u = tl.Ciphertext(b"\xff" * 48, ok_ct.v, ok_ct.w)
    swapped_u = tl.Ciphertext(PointG1.generator().mul(12345).to_bytes(),
                              ok_ct.v, ok_ct.w)
    return [("ok", ok_ct), ("empty", empty), ("large", large),
            ("flipped_w", flipped_w), ("trunc_v", trunc_v),
            ("bad_u", bad_u), ("swapped_u", swapped_u)]


# ---------------------------------------------------------------- crypto

def test_envelope_carries_version_and_future_versions_fail_closed():
    env = client_timelock.encrypt_to_round(INFO, ROUND, b"x")
    assert env["v"] == client_timelock.SCHEME_VERSION
    r = _result(ROUND)
    assert client_timelock.decrypt_with_beacon(env, r, info=INFO) == b"x"
    env2 = dict(env)
    env2["v"] = 2
    with pytest.raises(ClientError, match="scheme version"):
        client_timelock.decrypt_with_beacon(env2, r)


def test_cross_chain_ciphertext_rejected():
    env = client_timelock.encrypt_to_round(INFO, ROUND, b"x")
    other = Info(public_key=PUB, period=7, genesis_time=1_600_000_000,
                 genesis_seed=b"\x08" * 32)
    with pytest.raises(ClientError, match="cross-chain"):
        client_timelock.decrypt_with_beacon(env, _result(ROUND),
                                            info=other)
    # without info the check cannot run (legacy callers) — still decrypts
    assert client_timelock.decrypt_with_beacon(env, _result(ROUND)) == b"x"


def test_wrong_round_and_pre_v2_beacon_rejected():
    env = client_timelock.encrypt_to_round(INFO, ROUND, b"x")
    with pytest.raises(ClientError, match="need round"):
        client_timelock.decrypt_with_beacon(env, _result(ROUND - 1))
    with pytest.raises(ClientError, match="no V2 signature"):
        client_timelock.decrypt_with_beacon(env, _result(ROUND, v2=False))


def test_gen_mul_comb_matches_generic_mul():
    g = PointG1.generator()
    for k in (0, 1, 2, 255, 256, (1 << 128) - 1, R - 1, R, R + 5):
        assert tl._gen_mul(k) == g.mul(k % R), k


def test_gt_base_cache_counts_hits_and_misses():
    tl.gt_cache_clear()
    before = tl.gt_cache_info()
    tl.encrypt(PUB, b"gt-cache-probe", b"a")
    tl.encrypt(PUB, b"gt-cache-probe", b"b")
    tl.encrypt(PUB, b"gt-cache-probe-2", b"c")
    info = tl.gt_cache_info()
    assert info["misses"] - before["misses"] == 2
    assert info["hits"] - before["hits"] == 1
    from drand_tpu import metrics

    text = metrics.render().decode()
    assert 'timelock_gt_cache_requests_total{result="hit"}' in text
    assert 'timelock_gt_cache_requests_total{result="miss"}' in text


def test_round_decryptor_gt_equals_canonical_pairing():
    rd = tl.RoundDecryptor(SIG_BYTES)
    ct = tl.encrypt(PUB, IDENT, b"gt-equality")
    u = PointG1.from_bytes(ct.u)
    sig_pt = rd.sig
    assert rd.gt(u) == host_pairing.pairing(u, sig_pt)
    assert rd.decrypt(ct) == b"gt-equality"


def test_host_batch_bit_identical_to_oracle_across_matrix():
    labels, cts = zip(*_adversarial_matrix())
    oracle = _oracle_outcomes(SIG_BYTES, cts)
    c0, p0 = host_pairing.N_PRODUCT_CHECKS, host_pairing.N_MILLER_PAIRS
    got = tl.decrypt_batch(SIG_BYTES, cts)
    # one shared-line pass for the whole round at the host meter
    assert host_pairing.N_PRODUCT_CHECKS - c0 == 1
    assert [(ok, pt) for ok, pt, _ in got] == oracle, labels
    expected = dict(zip(labels, (ok for ok, _ in oracle)))
    assert expected == {"ok": True, "empty": True, "large": True,
                        "flipped_w": False, "trunc_v": False,
                        "bad_u": False, "swapped_u": False}
    # wrong-round signature: everything rejects, identically
    wrong = bls.sign(SK, message_v2(ROUND + 1))
    oracle_w = _oracle_outcomes(wrong, cts)
    got_w = tl.decrypt_batch(wrong, cts)
    assert [(ok, pt) for ok, pt, _ in got_w] == oracle_w
    assert not any(ok for ok, _ in oracle_w)


# ----------------------------------------------------------------- vault

def test_vault_roundtrip_and_opened_rows_are_immutable(tmp_path):
    from drand_tpu.timelock import TimelockVault, VaultError

    v = TimelockVault(str(tmp_path / "tl.db"))
    env = client_timelock.encrypt_to_round(INFO, 9, b"x")
    assert v.submit("tok-1", 9, env) is True
    assert v.submit("tok-1", 9, env) is False  # idempotent
    assert v.pending_rounds() == [9]
    assert v.pending_rounds(up_to=8) == []
    assert v.pending_for_round(9)[0][0] == "tok-1"
    v.set_opened("tok-1", b"plain")
    rec = v.get("tok-1")
    assert rec["status"] == "opened" and rec["plaintext"] == b"plain"
    with pytest.raises(VaultError):
        v.set_opened("tok-1", b"other")
    with pytest.raises(VaultError):
        v.set_rejected("tok-1", "nope")
    v.close()


# --------------------------------------------------------------- service

class FakeChain(Client):
    """Hand-advanced chain for service tests."""

    def __init__(self, head: int = 1, v2: bool = True):
        self.head = head
        self.v2 = v2

    async def get(self, round_no: int = 0) -> Result:
        rd = self.head if round_no == 0 else round_no
        if rd > self.head:
            raise ClientError(f"round {rd} not yet produced")
        return _result(rd, v2=self.v2)

    async def info(self) -> Info:
        return INFO


@pytest.fixture()
def host_mode():
    """Pin the dispatcher to host crypto (a service test must not probe
    or compile a device engine)."""
    old = (batch._MODE, batch._MIN_BATCH, batch._ENGINE)
    batch.configure("host")
    yield
    batch._MODE, batch._MIN_BATCH, batch._ENGINE = old


@pytest.mark.asyncio
async def test_service_open_at_boundary_and_restart_persistence(
        tmp_path, host_mode):
    from drand_tpu.timelock import TimelockService, TimelockVault

    db = str(tmp_path / "tl.db")
    chain = FakeChain(head=1)
    svc = TimelockService(TimelockVault(db), chain)
    await svc.start()
    env = client_timelock.encrypt_to_round(INFO, 3, b"till round 3")
    rec = await svc.submit(env)
    assert rec["status"] == "pending"
    token = rec["id"]

    # simulated daemon restart mid-wait: state comes back from sqlite
    await svc.close()
    svc = TimelockService(TimelockVault(db), chain)
    await svc.start()
    assert (await svc.status(token))["status"] == "pending"

    # the chain reaches round 3: boundary hook opens it
    chain.head = 3
    svc.on_result(await chain.get(3))
    for _ in range(200):
        await asyncio.sleep(0.02)
        rec = await svc.status(token)
        if rec["status"] != "pending":
            break
    assert rec["status"] == "opened"
    assert base64.b64decode(rec["plaintext"]) == b"till round 3"
    await svc.close()


@pytest.mark.asyncio
async def test_service_validation_and_pre_v2_stays_pending(
        tmp_path, host_mode):
    from drand_tpu.timelock import (TimelockError, TimelockService,
                                    TimelockVault)

    chain = FakeChain(head=1, v2=False)
    svc = TimelockService(TimelockVault(str(tmp_path / "tl.db")), chain)
    await svc.start()
    env = client_timelock.encrypt_to_round(INFO, 2, b"x")
    # cross-chain: bound to another chain hash
    bad = dict(env)
    bad["chain_hash"] = "ab" * 32
    with pytest.raises(TimelockError, match="cross-chain"):
        await svc.submit(bad)
    # a non-string chain_hash is a validation error, not a 500
    bad_t = dict(env)
    bad_t["chain_hash"] = 123
    with pytest.raises(TimelockError, match="hex string"):
        await svc.submit(bad_t)
    # future scheme version fails closed
    bad_v = dict(env)
    bad_v["v"] = 9
    with pytest.raises(TimelockError, match="scheme version"):
        await svc.submit(bad_v)
    # oversize payload
    big = client_timelock.encrypt_to_round(
        INFO, 2, b"\x00" * (tl.SIGMA_LEN + 70000))
    import drand_tpu.timelock.service as svc_mod

    assert svc_mod.MAX_PLAINTEXT == 65536
    with pytest.raises(TimelockError, match="too large"):
        await svc.submit(big)
    # a beacon without a V2 signature (pre-V2 era, or a source that
    # omitted the field) must NEVER decide the ciphertext: opened and
    # rejected rows are immutable, so it stays pending for a source
    # that can serve the signature
    rec = await svc.submit(env)
    chain.head = 2
    svc.on_result(await chain.get(2))
    await asyncio.sleep(0.3)
    got = await svc.status(rec["id"])
    assert got["status"] == "pending"
    # the same round from a V2-serving source then opens it
    chain.v2 = True
    svc.on_result(await chain.get(2))
    for _ in range(200):
        await asyncio.sleep(0.02)
        got = await svc.status(rec["id"])
        if got["status"] != "pending":
            break
    assert got["status"] == "opened"
    await svc.close()


@pytest.mark.asyncio
async def test_store_hook_note_round_complete(tmp_path, host_mode):
    """The DiscrepancyStore path: storing a beacon fires the registered
    service's boundary sweep (daemon deployments need no watch loop)."""
    from drand_tpu.chain.store import DiscrepancyStore, MemStore
    from drand_tpu.timelock import TimelockService, TimelockVault

    class _Group:
        period = INFO.period
        genesis_time = INFO.genesis_time

        @staticmethod
        def get_genesis_seed():
            return INFO.genesis_seed

    class _Clock:
        @staticmethod
        def now():
            return INFO.genesis_time + 2 * INFO.period

    chain = FakeChain(head=2)
    svc = TimelockService(TimelockVault(str(tmp_path / "tl.db")), chain)
    await svc.start()
    rec = await svc.submit(client_timelock.encrypt_to_round(INFO, 2, b"s"))
    store = DiscrepancyStore(MemStore(), _Group, _Clock)
    r2 = _result(2)
    store.put(Beacon(round=2, previous_sig=b"prev",
                     signature=r2.signature, signature_v2=r2.signature_v2))
    for _ in range(200):
        await asyncio.sleep(0.02)
        got = await svc.status(rec["id"])
        if got["status"] != "pending":
            break
    assert got["status"] == "opened"
    assert base64.b64decode(got["plaintext"]) == b"s"
    await svc.close()


def test_envelope_token_collapses_malleable_encodings():
    """One ciphertext must map to ONE vault row: hex case, junk keys,
    omitted-vs-explicit version and bool-typed round are all the same
    submission (otherwise a client floods the backlog cap from a single
    ciphertext by varying the encoding per POST)."""
    from drand_tpu.timelock.service import envelope_token

    env = client_timelock.encrypt_to_round(INFO, ROUND, b"one ct")
    tok = envelope_token(env)
    upper = dict(env)
    upper["U"] = env["U"].upper()
    junk = dict(env)
    junk["junk_key"] = "x" * 100
    no_v = {k: v for k, v in env.items() if k != "v"}
    bool_round = dict(env)
    bool_round["round"] = True
    assert envelope_token(upper) == tok
    assert envelope_token(junk) == tok
    # round collapses to its int value; the rest of the envelope pins it
    assert envelope_token(no_v) == tok
    env_r1 = dict(env)
    env_r1["round"] = 1
    assert envelope_token(bool_round) == envelope_token(env_r1) != tok
    # a genuinely different ciphertext gets a different token
    assert envelope_token(
        client_timelock.encrypt_to_round(INFO, ROUND, b"other")) != tok


# ------------------------------------------------------------------ http

@pytest.mark.asyncio
async def test_http_routes_submit_status_etag(tmp_path, host_mode):
    from aiohttp.test_utils import TestClient, TestServer

    from drand_tpu.http_server.server import PublicServer
    from drand_tpu.timelock import TimelockService, TimelockVault

    chain = FakeChain(head=1)
    svc = TimelockService(TimelockVault(str(tmp_path / "tl.db")), chain)
    server = PublicServer(chain, timelock_service=svc)
    tc = TestClient(TestServer(server.app))
    await tc.start_server()
    await svc.start()
    try:
        env = client_timelock.encrypt_to_round(INFO, 3, b"webhook")
        r = await tc.post("/timelock", json=env)
        assert r.status == 202
        token = (await r.json())["id"]
        # resubmission is idempotent (content-derived id)
        assert (await (await tc.post("/timelock", json=env)).json())[
            "id"] == token
        # malformed / cross-chain / unknown-id error paths
        assert (await tc.post("/timelock", data=b"not json")).status == 400
        bad = dict(env)
        bad["chain_hash"] = "cd" * 32
        assert (await tc.post("/timelock", json=bad)).status == 400
        assert (await tc.get("/timelock/deadbeef")).status == 404
        st = await tc.get(f"/timelock/{token}")
        assert (await st.json())["status"] == "pending"
        assert st.headers["Cache-Control"] == "no-store"
        # the boundary: opened results are immutable + ETag'd
        chain.head = 3
        svc.on_result(await chain.get(3))
        for _ in range(200):
            await asyncio.sleep(0.02)
            body = await (await tc.get(f"/timelock/{token}")).json()
            if body["status"] != "pending":
                break
        assert body["status"] == "opened"
        assert base64.b64decode(body["plaintext"]) == b"webhook"
        resp = await tc.get(f"/timelock/{token}")
        assert "immutable" in resp.headers["Cache-Control"]
        etag = resp.headers["ETag"]
        cached = await tc.get(f"/timelock/{token}",
                              headers={"If-None-Match": etag})
        assert cached.status == 304
    finally:
        await svc.close()
        await tc.close()


# ---------------------------------------------------------------- engine

@pytest.mark.parametrize("mode", ["auto", "device"])
def test_kat_failure_is_never_silent(monkeypatch, mode, engine4):
    """A device engine whose timelock KAT fails must never decide the
    round. Auto mode falls back to the host shared-signature tier and
    records it in the fallback ledger and engine_device_fallbacks;
    device mode raises the named KnownAnswerMismatch."""
    from drand_tpu import metrics
    from drand_tpu.ops import engine as eng_mod

    eng = engine4

    def kat_fails(b):
        return eng_mod._kat(False, f"timelock bucket {b}")

    monkeypatch.setattr(eng, "_check_tl_bucket", kat_fails)
    old = (batch._MODE, batch._MIN_BATCH, batch._ENGINE)
    batch.configure(mode, min_batch=1, engine=eng)
    batch.reset_fallback_ledger()
    fallbacks0 = metrics.ENGINE_FALLBACKS._value.get()
    try:
        cts = [tl.encrypt(PUB, IDENT, b"kat-fb-%d" % i) for i in range(3)]
        if mode == "device":
            with pytest.raises(eng_mod.KnownAnswerMismatch,
                               match="timelock bucket 4"):
                batch.decrypt_round_batch(SIG_BYTES, cts)
            assert batch.fallback_ledger() == []
            return
        out = batch.decrypt_round_batch(SIG_BYTES, cts)
        assert [(ok, pt) for ok, pt, _ in out] == \
            _oracle_outcomes(SIG_BYTES, cts)
        led = batch.fallback_ledger()
        assert led and led[-1]["op"] == "timelock"
        assert "KnownAnswerMismatch" in led[-1]["reason"]
        assert metrics.ENGINE_FALLBACKS._value.get() == fallbacks0 + 1
    finally:
        batch._MODE, batch._MIN_BATCH, batch._ENGINE = old
        batch.reset_fallback_ledger()


@pytest.mark.device
def test_device_round_open_one_dispatch_meter_and_oracle_identical():
    """The acceptance proof: K pending ciphertexts (including the
    adversarial rows) open via ONE batched engine dispatch — 1 product
    check, one Miller pair per live lane at the device meter — with
    accept/reject bools bit-identical to the per-item host oracle, under
    engine_op_seconds{op="timelock", path="device"}. Compile-heavy (the
    shared-signature GT graph)."""
    from conftest import sample_count

    from drand_tpu import metrics
    from drand_tpu.ops import engine as eng_mod
    from drand_tpu.ops.engine import BatchedEngine

    labels, cts = zip(*_adversarial_matrix())
    oracle = _oracle_outcomes(SIG_BYTES, cts)
    eng = BatchedEngine(buckets=(8,))
    old = (batch._MODE, batch._MIN_BATCH, batch._ENGINE)
    batch.configure("device", min_batch=1, engine=eng)
    try:
        # first dispatch pays compile + KAT and lands in
        # engine_compile_seconds (the ISSUE-6 split); re-dispatch for
        # the metered steady-state window
        out = batch.decrypt_round_batch(SIG_BYTES, cts)
        assert [(ok, pt) for ok, pt, _ in out] == oracle, labels
        c0, p0 = eng_mod.N_PRODUCT_CHECKS, eng_mod.N_MILLER_PAIRS
        bucket = metrics.batch_bucket(len(cts))
        h0 = sample_count(metrics.REGISTRY, "engine_op_seconds",
                          op="timelock", path="device", batch=bucket)
        out2 = batch.decrypt_round_batch(SIG_BYTES, cts)
        assert [(ok, pt) for ok, pt, _ in out2] == oracle
        # bad_u never decodes, so 6 of the 7 rows ride the batch; ONE
        # dispatch total
        assert eng_mod.N_PRODUCT_CHECKS - c0 == 1
        assert eng_mod.N_MILLER_PAIRS - p0 == 6
        assert eng.introspect()["kat"]["timelock"] == {"8": True}
        assert sample_count(metrics.REGISTRY, "engine_op_seconds",
                            op="timelock", path="device",
                            batch=bucket) == h0 + 1
    finally:
        batch._MODE, batch._MIN_BATCH, batch._ENGINE = old


# ------------------------------------------------------------- gRPC mirror

@pytest.mark.asyncio
async def test_grpc_timelock_submit_status_mirror(tmp_path, host_mode):
    """The drand.Public TimelockSubmit/TimelockStatus methods mirror
    POST /timelock + GET /timelock/{id} for non-HTTP clients (ISSUE 11
    satellite, PR-9 carry-over): same envelope JSON in, same status
    record out, the SAME TimelockService.submit canonicalization path
    (idempotent token across encodings), and the HTTP error taxonomy
    mapped onto grpc codes. A node without a vault answers
    UNIMPLEMENTED."""
    import grpc

    from drand_tpu.net.grpc_transport import GrpcClient, GrpcGateway
    from drand_tpu.net.transport import ProtocolService, TransportError
    from drand_tpu.timelock import TimelockService, TimelockVault

    chain = FakeChain(head=1)
    svc = TimelockService(TimelockVault(str(tmp_path / "tl.db")), chain)
    gw = GrpcGateway(ProtocolService(), "127.0.0.1:0",
                     timelock_service=svc)
    await gw.start()
    await svc.start()
    cli = GrpcClient(own_addr="tester:0")
    target = f"127.0.0.1:{gw.port}"
    try:
        env = client_timelock.encrypt_to_round(INFO, 3, b"grpc sealed")
        rec = await cli.timelock_submit(target, env)
        assert rec["status"] == "pending" and rec["round"] == 3
        token = rec["id"]
        # idempotent resubmission — the HTTP tier's content-derived
        # token, because it IS the HTTP tier's submit path
        assert (await cli.timelock_submit(target, env))["id"] == token
        # status roundtrip + unknown id -> None (NOT_FOUND)
        st = await cli.timelock_status(target, token)
        assert st["status"] == "pending" and st["id"] == token
        assert await cli.timelock_status(target, "deadbeef") is None
        # validation errors map to INVALID_ARGUMENT
        bad = dict(env)
        bad["chain_hash"] = "cd" * 32
        with pytest.raises(TransportError, match="INVALID_ARGUMENT"):
            await cli.timelock_submit(target, bad)
        with pytest.raises(TransportError, match="INVALID_ARGUMENT"):
            raw = cli._channel(target)[0].unary_unary(
                "/drand.Public/TimelockSubmit")
            try:
                await raw(b"not json", timeout=5.0)
            except grpc.aio.AioRpcError as e:
                raise TransportError(
                    f"TimelockSubmit: {e.code().name}") from e
        # the boundary opens it; the gRPC status serves the plaintext
        chain.head = 3
        svc.on_result(await chain.get(3))
        for _ in range(200):
            await asyncio.sleep(0.02)
            st = await cli.timelock_status(target, token)
            if st["status"] != "pending":
                break
        assert st["status"] == "opened"
        assert base64.b64decode(st["plaintext"]) == b"grpc sealed"
    finally:
        await cli.close()
        await svc.close()
        await gw.stop()

    # a gateway with no vault attached answers UNIMPLEMENTED
    gw2 = GrpcGateway(ProtocolService(), "127.0.0.1:0")
    await gw2.start()
    cli2 = GrpcClient(own_addr="tester:0")
    try:
        with pytest.raises(TransportError, match="UNIMPLEMENTED"):
            await cli2.timelock_submit(f"127.0.0.1:{gw2.port}",
                                       {"round": 3})
    finally:
        await cli2.close()
        await gw2.stop()
