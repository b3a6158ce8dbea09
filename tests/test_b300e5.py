"""zkatdlog at the token sample's public parameters (base 300, exponent 5)
on the served path: the wire carries its requests, and the verdicts are the
scalar reference's.

A (2,2) transfer at these parameters carries 10 membership proofs and is
~167 KB; a 64-tx hand-over was a 21 MB hex-in-JSON frame, over the 16 MiB
inbound cap. Payload bytes now follow the JSON header as raw segments
(`services/network/remote.py`, module docstring). Tier-1 cases run the
host validators only; the device proof plane at exponent 5 is the
`slow` case at the end (pairing programs on the CPU backend).
"""
import dataclasses
import random
import socket
import struct
import threading
import time

import pytest

from fabric_token_sdk_tpu.api.request import (
    IssueRecord,
    TokenRequest,
    TransferRecord,
)
from fabric_token_sdk_tpu.api.validator import RequestValidator
from fabric_token_sdk_tpu.crypto import batch, hostmath as hm, sign
from fabric_token_sdk_tpu.crypto import token as tok, transfer as tr
from fabric_token_sdk_tpu.crypto.rangeproof import RangeProof
from fabric_token_sdk_tpu.crypto.serialization import dumps, loads
from fabric_token_sdk_tpu.crypto.setup import setup
from fabric_token_sdk_tpu.drivers import identity
from fabric_token_sdk_tpu.drivers.zkatdlog import ZKATDLogDriver
from fabric_token_sdk_tpu.models.token import ID
from fabric_token_sdk_tpu.services.network import BlockPolicy, Network
from fabric_token_sdk_tpu.services.network.remote import (
    DEFAULT_MAX_FRAME,
    FrameTooLarge,
    LedgerServer,
    RemoteNetwork,
    _recv_msg,
    _send_msg,
)
from fabric_token_sdk_tpu.utils import metrics as mx

# amounts that use all five base-300 digits (300**4 = 8.1e9)
IN_VALUES = [20_000_000_000, 5_500_000_000]
OUT_VALUES = [24_123_456_789, 1_376_543_211]
HOST_ONLY = dataclasses.replace(
    BlockPolicy(), use_batched=False, sign_batched=False)


def _counter(name):
    return mx.REGISTRY.counter(name).value


@pytest.fixture(scope="module")
def b300e5():
    """The issue and four (2,2) transfers of one owner, host-proved; the
    third transfer's proof is tampered and signed as tampered."""
    rng = random.Random(0xB300E5)
    pp = setup(base=300, exponent=5, rng=rng)
    assert (pp.range_params.base, pp.range_params.exponent) == (300, 5)
    drv = ZKATDLogDriver(pp)
    key = sign.keygen(rng)
    ident = identity.pk_identity(key.public)
    n, k = 4, len(IN_VALUES)
    issue = drv.issue(ident, "USD", IN_VALUES * n, [ident] * (k * n), rng=rng)
    req = TokenRequest(anchor="mint")
    req.issues.append(IssueRecord(
        action=issue.action_bytes, issuer=ident,
        outputs_metadata=issue.metadata, receivers=[ident] * (k * n)))
    req.issues[0].signature = key.sign(req.marshal_to_sign(), rng)
    raws = [req.to_bytes()]
    owners = [ident] * len(OUT_VALUES)
    specs = [([ID("mint", k * i + j) for j in range(k)],
              issue.outputs[k * i:k * i + k], issue.metadata[k * i:k * i + k],
              "USD", OUT_VALUES, owners) for i in range(n)]
    # the host prover, as clients prove: below min_batch nothing is batched
    proved = drv.transfer_many(specs, rng=rng, min_batch=n + 1)
    for i, (spec, out) in enumerate(zip(specs, proved)):
        action = out.action_bytes
        if i == 2:
            d = loads(action)
            p = bytearray(d["proof"])
            p[len(p) // 2] ^= 1
            d["proof"] = bytes(p)
            action = dumps(d)
        t = TokenRequest(anchor=f"pay{i}")
        t.transfers.append(TransferRecord(
            action=action, input_ids=spec[0], senders=[ident] * k,
            outputs_metadata=out.metadata, receivers=owners))
        payload = t.marshal_to_sign()
        t.transfers[0].signatures = [key.sign(payload, rng) for _ in range(k)]
        raws.append(t.to_bytes())
    return pp, raws


def test_b300e5_transfer_carries_ten_membership_proofs(b300e5):
    _pp, raws = b300e5
    action = loads(TokenRequest.from_bytes(raws[1]).transfers[0].action)
    proof = tr.TransferProof.from_bytes(action["proof"])
    rpf = RangeProof.from_bytes(proof.range_correctness)
    assert [len(r) for r in rpf.membership_proofs] == [5, 5]
    assert [len(r) for r in rpf.digit_commitments] == [5, 5]
    # the request sizes the wire has to carry (ISSUE 28: 166.6-166.9 KB a
    # transfer, 78.8 KB per issued output)
    assert all(160_000 < len(r) < 175_000 for r in raws[1:])
    assert 8 * 75_000 < len(raws[0]) < 8 * 85_000


def test_b300e5_served_verdicts_equal_the_scalar_reference(b300e5):
    """Four real (2,2) transfers through `LedgerServer` /
    `RemoteNetwork.submit_many`, one of them tampered: status and message
    of each equal the scalar validator's, one request per block."""
    pp, raws = b300e5
    ref_net = Network(RequestValidator(ZKATDLogDriver(pp)), policy=HOST_ONLY)
    ref = [ref_net.submit(r) for r in raws]
    assert [e.status.value for e in ref] == [
        "Valid", "Valid", "Valid", "Invalid", "Valid"]

    server = LedgerServer(
        RequestValidator(ZKATDLogDriver(pp)), policy=HOST_ONLY).start()
    client = RemoteNetwork(server.address, timeout=120)
    try:
        frames0 = _counter("remote.frame.bytes")
        recv0 = _counter("remote.frame.recv_us")
        txs0 = _counter("ledger.ordering.enqueued")
        got = [client.submit(raws[0])] + client.submit_many(raws[1:])
        assert [(e.tx_id, e.status, e.message) for e in got] == [
            (e.tx_id, e.status, e.message) for e in ref]
        assert "invalid transfer proof" in got[3].message
        # the two inbound frames are the requests' bytes plus two headers
        frame_bytes = _counter("remote.frame.bytes") - frames0
        payload = sum(len(r) for r in raws)
        assert payload < frame_bytes < payload + 2048
        assert _counter("remote.frame.recv_us") > recv0
        assert _counter("ledger.ordering.enqueued") - txs0 == 5
        # an output read back over the wire is the ledger's own bytes
        assert client.resolve_input(ID("pay0", 0)) == \
            server.network.resolve_input(ID("pay0", 0))
    finally:
        client.close()
        server.stop()


def test_b300e5_handover_is_cut_by_the_test_network_s_channel(b300e5):
    """The same four transfers under the test network's channel values
    (`zkatdlog-b300e5-testnet`: BatchTimeout 2 s, MaxMessageCount 10,
    PreferredMaxBytes 512 KB, AbsoluteMaxBytes 99 MB): three are 500.7 KB
    and the fourth overflows 524,288 B, so one `submit_many` is cut 3 + 1
    by the ordering rules, the last at the timer, and every verdict is
    still the scalar reference's."""
    pp, raws = b300e5
    ref_net = Network(RequestValidator(ZKATDLogDriver(pp)), policy=HOST_ONLY)
    ref = [ref_net.submit(r) for r in raws]
    sizes = [len(r) for r in raws[1:]]
    assert sum(sizes[:3]) <= 524288 < sum(sizes)

    policy = dataclasses.replace(
        HOST_ONLY, linger_s=2.0, max_block_txs=10,
        preferred_max_bytes=524288, absolute_max_bytes=103809024)
    server = LedgerServer(
        RequestValidator(ZKATDLogDriver(pp)), policy=policy).start()
    client = RemoteNetwork(server.address, timeout=120)
    try:
        assert client.submit(raws[0]).status == ref[0].status  # a block of 1
        wall0 = time.time()  # the flight ring is bounded: select by time
        by_bytes0 = _counter("orderer.cut.by_bytes")
        by_timeout0 = _counter("orderer.cut.by_timeout")
        t0 = time.monotonic()
        got = client.submit_many(raws[1:])
        took = time.monotonic() - t0
        assert [(e.tx_id, e.status, e.message) for e in got] == [
            (e.tx_id, e.status, e.message) for e in ref[1:]]
        cuts = [e for e in mx.FLIGHT.tail()
                if e["kind"] == "block.cut" and e["ts"] >= wall0]
        assert [(c["txs"], c["bytes"], c["reason"]) for c in cuts] == [
            (3, sum(sizes[:3]), "bytes"), (1, sizes[3], "timeout")]
        assert cuts[1]["waited_s"] == 2.0 <= took
        assert _counter("orderer.cut.by_bytes") - by_bytes0 == 1
        assert _counter("orderer.cut.by_timeout") - by_timeout0 == 1
        net = server.network
        assert [net.block(net.height() - k).txs for k in (2, 1)] == [
            ["pay0", "pay1", "pay2"], ["pay3"]]
    finally:
        client.close()
        server.stop()


def _round_trip(msg, max_frame=None):
    a, b = socket.socketpair()
    try:
        sender = threading.Thread(target=_send_msg, args=(a, msg))
        sender.start()
        got = _recv_msg(b, max_frame)
        sender.join()
        return got
    finally:
        a.close()
        b.close()


@pytest.mark.parametrize("msg", [
    # a 64-tx hand-over of b300e5 transfers: 21.3 MB as hex in JSON
    {"op": "submit_many", "traces": [["t", "s"]] * 64,
     "requests": [bytes([i]) * 166_700 for i in range(64)]},
    # a group's 128-output issue: 20.2 MB as hex in JSON
    {"op": "submit", "request": b"\x07" * (128 * 78_800)},
], ids=["handover-64x167KB", "issue-128x79KB"])
def test_frame_of_a_b300e5_handover_fits_the_default_cap(msg, monkeypatch):
    monkeypatch.delenv("FTS_REMOTE_MAX_FRAME", raising=False)
    payload = sum(len(x) for v in msg.values()
                  for x in (v if isinstance(v, list) else [v])
                  if isinstance(x, bytes))
    assert payload * 2 > DEFAULT_MAX_FRAME > payload  # hex did not fit
    assert _round_trip(msg) == msg


def test_frame_carries_bytes_beside_json_of_any_shape():
    msg = {"op": "x", "n": 3, "nested": {"a": [1, 2]}, "empty": [],
           "one": b"", "many": [b"ab", b"", b"cde"], "text": "bé"}
    assert _round_trip(msg) == msg
    assert _round_trip({"ok": True}) == {"ok": True}


def test_hostile_or_malformed_frames_are_refused():
    # a length prefix over the cap: refused before anything is allocated
    # (nothing follows the prefix, so a receiver that tried to read the
    # frame would block instead of raising)
    a, b = socket.socketpair()
    try:
        a.sendall(struct.pack(">I", DEFAULT_MAX_FRAME + 1))
        with pytest.raises(FrameTooLarge):
            _recv_msg(b)
    finally:
        a.close()
        b.close()
    with pytest.raises(FrameTooLarge):
        _round_trip({"request": b"x" * 4096}, max_frame=1024)

    def raw_frame(body):
        a, b = socket.socketpair()
        try:
            a.sendall(struct.pack(">I", len(body)) + body)
            return _recv_msg(b)
        finally:
            a.close()
            b.close()

    head = b'{"$bin": {"request": 10}}'
    for body in (
        b"\x00",                                           # no header length
        struct.pack(">I", 99) + b"{}",                     # header overruns
        struct.pack(">I", len(head)) + head + b"short",    # segment overruns
        struct.pack(">I", 2) + b"{}" + b"stray",           # unclaimed bytes
        struct.pack(">I", 12) + b'{"$bin": []}',           # layout no object
    ):
        with pytest.raises(ValueError):
            raw_frame(body)


def test_server_drops_a_malformed_frame_and_keeps_serving():
    from fabric_token_sdk_tpu.drivers.fabtoken import (
        FabTokenDriver, FabTokenPublicParams,
    )

    server = LedgerServer(
        RequestValidator(FabTokenDriver(FabTokenPublicParams()))).start()
    try:
        s = socket.create_connection(server.address, timeout=10)
        body = struct.pack(">I", 2) + b"{}" + b"stray"
        before = _counter("remote.frames.malformed")
        s.sendall(struct.pack(">I", len(body)) + body)
        reply = _recv_msg(s)  # a typed refusal, as for a frame over the cap
        assert reply["ok"] is False and reply["error_class"] == "MalformedFrame"
        assert s.recv(1) == b""  # the stream cannot be trusted: dropped
        s.close()
        assert _counter("remote.frames.malformed") - before == 1
        client = RemoteNetwork(server.address, timeout=10)
        assert client.height() == 0  # server loop unharmed
        client.close()
    finally:
        server.stop()


def _range_specs(pp, rng, count):
    reqs = []
    for _ in range(count):
        in_toks, in_w = tok.tokens_with_witness([200, 42], "USD", pp.ped_params, rng)
        out_toks, out_w = tok.tokens_with_witness([241, 1], "USD", pp.ped_params, rng)
        reqs.append((in_w, out_w, in_toks, out_toks))
    return reqs


@pytest.mark.slow
def test_batched_verifier_agrees_with_scalar_at_exponent_5(rng):
    """The device proof plane at five digits per output (base 3 keeps the
    signed table small; 3**5 - 1 = 242 is the largest value): valid and
    tampered (2,2) proofs get the scalar verifier's verdicts, and the
    membership plane sees 10 proofs per transfer."""
    pp = setup(base=3, exponent=5, rng=random.Random(0xF75))
    reqs = _range_specs(pp, rng, 3)
    proofs = tr.TransferProver.batch(reqs, pp, rng=rng, min_batch=99)
    tp = tr.TransferProof.from_bytes(proofs[1])
    rpf = RangeProof.from_bytes(tp.range_correctness)
    m = rpf.membership_proofs[1][4]
    m.value_resp = (m.value_resp + 1) % hm.R
    tp.range_correctness = rpf.to_bytes()
    proofs[1] = tp.to_bytes()
    scalar = []
    for req, proof in zip(reqs, proofs):
        try:
            tr.TransferVerifier(req[2], req[3], pp).verify(proof)
            scalar.append(True)
        except ValueError:
            scalar.append(False)
    assert scalar == [True, False, True]
    before = _counter("batch.membership.proofs")
    got = batch.BatchedTransferVerifier(pp).verify(
        [(r[2], r[3], p) for r, p in zip(reqs, proofs)])
    assert got.tolist() == scalar
    assert _counter("batch.membership.proofs") - before == 3 * 10
