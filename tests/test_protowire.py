"""Protobuf wire interop: byte-layout goldens and a live round-trip over
the drand.Public service + protobuf SyncChain.

Reference layouts: protobuf/drand/api.proto:36-55 (PublicRandResponse),
protocol.proto:84-92 (SyncRequest/BeaconPacket), common.proto:44-60
(ChainInfoPacket). The golden byte strings below are hand-derived from
the proto3 wire spec (tag = field<<3|type, varint, length-delimited) —
they pin OUR encoder to the ecosystem layout without generated code.
"""

import asyncio

import pytest

from drand_tpu.net import protowire as pw


# ---------------------------------------------------------------------------
# golden bytes
# ---------------------------------------------------------------------------

def test_public_rand_request_bytes():
    # round = 7 -> field 1 varint: tag 0x08, value 0x07
    assert pw.encode(pw.PUBLIC_RAND_REQUEST, {"round": 7}) == b"\x08\x07"
    # round = 0 is the proto3 default: empty message
    assert pw.encode(pw.PUBLIC_RAND_REQUEST, {"round": 0}) == b""
    assert pw.decode(pw.PUBLIC_RAND_REQUEST, b"\x08\x07") == {"round": 7}
    assert pw.decode(pw.PUBLIC_RAND_REQUEST, b"") == {"round": 0}


def test_public_rand_response_bytes():
    vals = {"round": 300, "signature": b"\xaa\xbb",
            "previous_signature": b"\xcc",
            "randomness": b"\x01\x02", "signature_v2": b"\xdd"}
    # field 1 varint 300 = 0xAC 0x02; field 2 len: 0x12 0x02 aa bb;
    # field 3: 0x1a 0x01 cc; field 4: 0x22 0x02 01 02; field 5: 0x2a 0x01 dd
    expect = (b"\x08\xac\x02" b"\x12\x02\xaa\xbb" b"\x1a\x01\xcc"
              b"\x22\x02\x01\x02" b"\x2a\x01\xdd")
    assert pw.encode(pw.PUBLIC_RAND_RESPONSE, vals) == expect
    assert pw.decode(pw.PUBLIC_RAND_RESPONSE, expect) == vals


def test_sync_request_and_beacon_packet_bytes():
    assert pw.encode(pw.SYNC_REQUEST, {"from_round": 1}) == b"\x08\x01"
    b = pw.encode(pw.BEACON_PACKET,
                  {"previous_sig": b"\x11", "round": 2,
                   "signature": b"\x22\x33"})
    assert b == b"\x0a\x01\x11" b"\x10\x02" b"\x1a\x02\x22\x33"
    back = pw.decode(pw.BEACON_PACKET, b)
    assert back == {"previous_sig": b"\x11", "round": 2,
                    "signature": b"\x22\x33"}


def test_chain_info_packet_negative_genesis():
    # proto3 int64: negative values are 10-byte varints
    vals = {"public_key": b"\x01", "period": 30, "genesis_time": -1,
            "hash": b"", "group_hash": b""}
    enc = pw.encode(pw.CHAIN_INFO_PACKET, vals)
    assert pw.decode(pw.CHAIN_INFO_PACKET, enc)["genesis_time"] == -1


def test_unknown_fields_skipped():
    # field 15 (unknown to PUBLIC_RAND_REQUEST), then round=3
    data = b"\x7a\x02\xff\xff" + b"\x08\x03"
    assert pw.decode(pw.PUBLIC_RAND_REQUEST, data)["round"] == 3


def test_truncated_raises():
    with pytest.raises(pw.WireError):
        pw.decode(pw.PUBLIC_RAND_RESPONSE, b"\x12\x05\xaa")


def test_invalid_utf8_str_raises():
    # a str field with invalid UTF-8 is a wire error (INVALID_ARGUMENT at
    # the gateway), not a stray UnicodeDecodeError
    with pytest.raises(pw.WireError, match="invalid UTF-8"):
        pw.decode(pw.IDENTITY, b"\x0a\x01\xff")


def test_wire_type_mismatch_raises():
    # ADVICE r3: a bytes field arriving as fixed64 (wt=1) / fixed32 (wt=5)
    # must be rejected, not have the raw 8/4 bytes become its value
    with pytest.raises(pw.WireError, match="wrong wire type"):
        pw.decode(pw.PUBLIC_RAND_RESPONSE,
                  b"\x11" + b"\x00" * 8)  # field 2 (signature), wt=1
    with pytest.raises(pw.WireError, match="wrong wire type"):
        pw.decode(pw.PUBLIC_RAND_RESPONSE,
                  b"\x15" + b"\x00" * 4)  # field 2 (signature), wt=5
    # int field arriving length-delimited is likewise rejected
    with pytest.raises(pw.WireError, match="wrong wire type"):
        pw.decode(pw.PUBLIC_RAND_REQUEST, b"\x0a\x01\x03")
    # unknown fields with fixed wire types are still skipped
    assert pw.decode(pw.PUBLIC_RAND_REQUEST,
                     b"\x79" + b"\x00" * 8 + b"\x08\x03")["round"] == 3


# ---------------------------------------------------------------------------
# protocol plane (protocol.proto:16-92, dkg.proto:14-93): byte goldens
# ---------------------------------------------------------------------------

def test_partial_beacon_packet_bytes():
    vals = {"round": 5, "previous_sig": b"\xaa\xbb",
            "partial_sig": b"\x01\x02", "partial_sig_v2": b"\x03"}
    enc = pw.encode(pw.PARTIAL_BEACON_PACKET, vals)
    assert enc == (b"\x08\x05" b"\x12\x02\xaa\xbb"
                   b"\x1a\x02\x01\x02" b"\x22\x01\x03")
    # proto3: an absent field decodes to its default, as the checkpoint
    # co-signature does when a round carries none
    assert pw.decode(pw.PARTIAL_BEACON_PACKET, enc) == {
        **vals, "partial_ckpt": b""}


def test_identity_and_signal_packet_bytes():
    ident = {"address": "a:1", "key": b"\x09", "tls": True,
             "signature": b"\x07"}
    ident_b = pw.encode(pw.IDENTITY, ident)
    assert ident_b == b"\x0a\x03a:1" b"\x12\x01\x09" b"\x18\x01" b"\x22\x01\x07"
    assert pw.decode(pw.IDENTITY, ident_b) == ident

    sig_pkt = {"node": ident, "secret_proof": b"\x55",
               "previous_group_hash": b"\x66"}
    enc = pw.encode(pw.SIGNAL_DKG_PACKET, sig_pkt)
    assert enc == (b"\x0a" + bytes([len(ident_b)]) + ident_b
                   + b"\x12\x01\x55" + b"\x1a\x01\x66")
    assert pw.decode(pw.SIGNAL_DKG_PACKET, enc) == sig_pkt


def test_group_packet_roundtrip():
    g = {"nodes": [
            {"public": {"address": "n0:1", "key": b"\x01", "tls": False,
                        "signature": b""}, "index": 0},
            {"public": {"address": "n1:2", "key": b"\x02", "tls": True,
                        "signature": b"\x03"}, "index": 1}],
         "threshold": 2, "period": 30, "genesis_time": 1700000000,
         "transition_time": 0, "genesis_seed": b"\x44" * 4,
         "dist_key": [b"\x0c\x01", b"\x0c\x02"], "catchup_period": 15}
    enc = pw.encode(pw.GROUP_PACKET, g)
    assert pw.decode(pw.GROUP_PACKET, enc) == g
    info = {"new_group": g, "secret_proof": b"\x5e", "dkg_timeout": 10,
            "signature": b"\x51"}
    assert pw.decode(pw.DKG_INFO_PACKET,
                     pw.encode(pw.DKG_INFO_PACKET, info)) == info


def test_dkg_packet_oneof_bytes():
    deal = {"share_index": 1, "encrypted_share": b"\xee"}
    deal_b = pw.encode(pw.DEAL, deal)
    assert deal_b == b"\x08\x01\x12\x01\xee"
    bundle = {"dealer_index": 2, "commits": [b"\x0c\x01", b"\x0c\x02"],
              "deals": [deal], "session_id": b"\x5e", "signature": b"\x51"}
    bundle_b = pw.encode(pw.DEAL_BUNDLE, bundle)
    assert bundle_b == (b"\x08\x02"
                        b"\x12\x02\x0c\x01" b"\x12\x02\x0c\x02"
                        b"\x1a\x05" + deal_b
                        + b"\x22\x01\x5e" + b"\x2a\x01\x51")
    pkt = {"dkg": {"deal": bundle, "response": None, "justification": None}}
    enc = pw.encode(pw.DKG_PACKET, pkt)
    inner = pw.encode(pw.DKG_BUNDLE, pkt["dkg"])
    assert enc == b"\x0a" + bytes([len(inner)]) + inner
    assert inner == b"\x0a" + bytes([len(bundle_b)]) + bundle_b
    dec = pw.decode(pw.DKG_PACKET, enc)
    arm, val = pw.oneof_of(dec["dkg"], pw.DKG_BUNDLE_ARMS)
    assert arm == "deal" and val == bundle


def test_dkg_response_and_justification_roundtrip():
    rb = {"share_index": 3,
          "responses": [{"dealer_index": 0, "status": True},
                        {"dealer_index": 1, "status": False}],
          "session_id": b"\x5e", "signature": b"\x52"}
    assert pw.decode(pw.RESPONSE_BUNDLE,
                     pw.encode(pw.RESPONSE_BUNDLE, rb)) == rb
    jb = {"dealer_index": 1,
          "justifications": [{"share_index": 2, "share": b"\x99"}],
          "session_id": b"\x5e", "signature": b"\x53"}
    assert pw.decode(pw.JUSTIFICATION_BUNDLE,
                     pw.encode(pw.JUSTIFICATION_BUNDLE, jb)) == jb
    # bool false is omitted on the wire (proto3 default)
    assert pw.encode(pw.RESPONSE, {"dealer_index": 0, "status": False}) == b""


def test_repeated_keeps_default_elements_and_packed_varints():
    # a default-valued element inside a repeated field must be emitted —
    # dropping it would shift every later element's position
    g = {"nodes": [], "threshold": 0, "period": 0, "genesis_time": 0,
         "transition_time": 0, "genesis_seed": b"",
         "dist_key": [b"\x01", b"", b"\x02"], "catchup_period": 0}
    enc = pw.encode(pw.GROUP_PACKET, g)
    assert pw.decode(pw.GROUP_PACKET, enc)["dist_key"] == [b"\x01", b"",
                                                           b"\x02"]
    # packed repeated varints (proto3's default for repeated scalars)
    spec = {1: ("xs", ("rep", "u32"))}
    assert pw.decode(spec, b"\x0a\x04\x05\x00\x96\x01")["xs"] == [5, 0, 150]
    # unpacked occurrences still accumulate
    assert pw.decode(spec, b"\x08\x05\x08\x07")["xs"] == [5, 7]


def test_oneof_multiple_arms_last_wins():
    # proto3 oneof semantics: the last-populated arm wins (ADVICE r4)
    two = {"deal": {"dealer_index": 1, "commits": [], "deals": [],
                    "session_id": b"", "signature": b""},
           "response": {"share_index": 1, "responses": [],
                        "session_id": b"", "signature": b""},
           "justification": None}
    arm, val = pw.oneof_of(two, pw.DKG_BUNDLE_ARMS)
    assert arm == "response" and val["share_index"] == 1


# ---------------------------------------------------------------------------
# live round-trip: ecosystem-style client against our gateway
# ---------------------------------------------------------------------------

class _Svc:
    """Minimal Public + sync service over a fixed small chain."""

    def __init__(self, beacons, info):
        self._b = {b.round: b for b in beacons}
        self._last = max(self._b)
        self._info = info

    async def public_rand(self, from_addr, round_no):
        from drand_tpu.net.transport import TransportError

        b = self._b.get(round_no or self._last)
        if b is None:
            raise TransportError(f"no round {round_no}")
        return b

    async def public_rand_stream(self, from_addr):
        for r in sorted(self._b):
            yield self._b[r]

    async def chain_info(self, from_addr):
        return self._info

    async def sync_chain(self, from_addr, req):
        for r in sorted(self._b):
            if r >= req.from_round:
                yield self._b[r]


@pytest.mark.asyncio
async def test_interop_public_service_roundtrip():
    from drand_tpu.chain.beacon import Beacon
    from drand_tpu.chain.info import Info
    from drand_tpu.client.grpc_interop import GrpcInteropSource
    from drand_tpu.crypto.curves import PointG1
    from drand_tpu.net.grpc_transport import GrpcGateway

    pub = PointG1.generator().mul(0x1234)
    info = Info(public_key=pub, period=30, genesis_time=1700000000,
                genesis_seed=b"\x07" * 32, group_hash=b"\x09" * 32)
    beacons = [Beacon(round=r, previous_sig=b"p%d" % r,
                      signature=b"s%d" % r, signature_v2=b"v%d" % r)
               for r in (1, 2, 3)]
    gw = GrpcGateway(_Svc(beacons, info), "127.0.0.1:0")
    await gw.start()
    try:
        src = GrpcInteropSource(f"127.0.0.1:{gw.port}")
        got_info = await src.info()
        assert got_info.public_key == pub
        assert got_info.period == 30
        assert got_info.genesis_time == 1700000000
        assert got_info.group_hash == b"\x09" * 32
        r2 = await src.get(2)
        assert r2.round == 2 and r2.signature == b"s2"
        rows = []
        async for r in src.watch():
            rows.append(r.round)
        assert rows == [1, 2, 3]
        await src.close()
    finally:
        await gw.stop()


@pytest.mark.asyncio
async def test_interop_protobuf_sync_chain():
    """A protobuf SyncRequest on the standard method streams protobuf
    BeaconPackets (codec sniffing on the shared handler)."""
    import grpc.aio

    from drand_tpu.chain.beacon import Beacon
    from drand_tpu.chain.info import Info
    from drand_tpu.crypto.curves import PointG1
    from drand_tpu.net.grpc_transport import GrpcGateway

    info = Info(public_key=PointG1.generator(), period=30,
                genesis_time=1, genesis_seed=b"", group_hash=b"")
    beacons = [Beacon(round=r, previous_sig=b"p", signature=b"s%d" % r)
               for r in (1, 2, 3)]
    gw = GrpcGateway(_Svc(beacons, info), "127.0.0.1:0")
    await gw.start()
    try:
        ch = grpc.aio.insecure_channel(f"127.0.0.1:{gw.port}")
        stream = ch.unary_stream("/drand.Protocol/SyncChain")(
            pw.encode(pw.SYNC_REQUEST, {"from_round": 2}))
        rounds = []
        async for raw in stream:
            msg = pw.decode(pw.BEACON_PACKET, raw)
            rounds.append(msg["round"])
            assert msg["signature"] == b"s%d" % msg["round"]
        assert rounds == [2, 3]

        # ADVICE r4: from_round=0 — which proto3 encodes as the EMPTY
        # message — is the reference's full-chain sync request
        # (chain/beacon/sync.go:134-150); both forms stream from round 1
        for full in (b"", pw.encode(pw.SYNC_REQUEST, {"from_round": 0})):
            stream = ch.unary_stream("/drand.Protocol/SyncChain")(full)
            rounds = [pw.decode(pw.BEACON_PACKET, raw)["round"]
                      async for raw in stream]
            assert rounds == [1, 2, 3]
        await ch.close()
    finally:
        await gw.stop()


@pytest.mark.asyncio
async def test_interop_protobuf_partial_beacon_aggregated():
    """A protobuf PartialBeaconPacket on /drand.Protocol/PartialBeacon —
    exactly what a reference peer sends (protocol.proto:30,63-75) — is
    accepted by a REAL beacon handler and aggregated into the chain.
    Node 1 never runs; its partial reaches node 0 ONLY over the protobuf
    wire, so round 1 existing in node 0's store proves the path."""
    import asyncio

    import grpc.aio

    from drand_tpu.chain import beacon as chain_beacon
    from drand_tpu.crypto import tbls
    from drand_tpu.net.grpc_transport import GrpcGateway
    from drand_tpu.testing.harness import BeaconTestNetwork

    net = BeaconTestNetwork(n=2, t=2, period=2)
    gw = GrpcGateway(net.nodes[0].handler, "127.0.0.1:0")
    await gw.start()
    try:
        await net.start_all(indices=[0])
        await net.advance_to_genesis()
        await asyncio.sleep(0.1)  # let node 0 sign its own round-1 partial
        assert net.nodes[0].store.last().round == 0  # 1-of-2: stuck

        prev = net.group.get_genesis_seed()
        sk1 = net.shares[1].pri_share
        packet = pw.encode(pw.PARTIAL_BEACON_PACKET, {
            "round": 1, "previous_sig": prev,
            "partial_sig": tbls.sign_partial(
                sk1, chain_beacon.message(1, prev)),
            "partial_sig_v2": tbls.sign_partial(
                sk1, chain_beacon.message_v2(1))})
        ch = grpc.aio.insecure_channel(f"127.0.0.1:{gw.port}")
        resp = await ch.unary_unary("/drand.Protocol/PartialBeacon")(packet)
        assert resp == b""  # drand.Empty

        for _ in range(100):
            if net.nodes[0].store.last().round >= 1:
                break
            await asyncio.sleep(0.05)
        last = net.nodes[0].store.last()
        assert last.round == 1, "protobuf partial was not aggregated"
        pub = net.group.public_key.key()
        assert chain_beacon.verify_beacon(pub, last)
        assert last.is_v2(), "v2 partial did not aggregate"

        # garbage that parses as an all-defaults packet must be rejected
        with pytest.raises(grpc.aio.AioRpcError) as ei:
            await ch.unary_unary("/drand.Protocol/PartialBeacon")(b"")
        assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        await ch.close()
    finally:
        await gw.stop()
        net.stop_all()
