"""Batch-first host validation: differential identity with the scalar path.

The host batch passes (`FTS_HOST_BATCH`) — block-level Fiat-Shamir
(`hostmath.hash_to_zr_many`), batched Schnorr verification
(`sign.verify_many`), batched WF/transfer-proof verification
(`wellformedness.verify_transfer_wfs` / `transfer.verify_transfer_proofs`),
vectorized conservation (`Driver.validate_conservation_many`), the parsed
request/token caches, and the `host_map` commit-worker fan-out — can only
ACCELERATE host validation, never change accept/reject or an error
message. These tests pin that contract: challenge byte-identity with the
scalar hash (native sha256 present and absent), per-row verdict identity
over valid/tampered rows (native bn254 present and absent), end-to-end
block differentials (valid + tampered + double-spend corpora, both
drivers, batch on vs `FTS_HOST_BATCH=0`, workers 1 vs N), cache hit/miss
accounting + clone isolation + bounded eviction, and the `ops.health`
caches section.
"""
import random
import threading

import pytest

import fabric_token_sdk_tpu.native as native
from fabric_token_sdk_tpu.api import request as request_mod
from fabric_token_sdk_tpu.api.request import (
    IssueRecord,
    TokenRequest,
    TransferRecord,
)
from fabric_token_sdk_tpu.api.validator import RequestValidator
from fabric_token_sdk_tpu.crypto import hostmath as hm
from fabric_token_sdk_tpu.crypto import sign
from fabric_token_sdk_tpu.crypto.serialization import dumps, loads
from fabric_token_sdk_tpu.crypto.setup import setup
from fabric_token_sdk_tpu.drivers import identity
from fabric_token_sdk_tpu.drivers.fabtoken import (
    FabTokenDriver,
    FabTokenPublicParams,
)
from fabric_token_sdk_tpu.drivers.zkatdlog import ZKATDLogDriver
from fabric_token_sdk_tpu.models.token import ID
from fabric_token_sdk_tpu.services.network import (
    BlockPolicy,
    Network,
    TxStatus,
)
from fabric_token_sdk_tpu.services.network import pipeline as npipe
from fabric_token_sdk_tpu.utils import metrics as mx


def _counter(name):
    return mx.REGISTRY.counter(name).value


@pytest.fixture(scope="module")
def zk_pp():
    return setup(base=4, exponent=2, rng=random.Random(0xF75))


@pytest.fixture(autouse=True)
def _fresh_request_cache():
    request_mod.cache_clear()
    yield
    request_mod.cache_clear()


def _no_native_sha(monkeypatch):
    """Simulate the native fastser library being absent: `sha256_many`
    falls back to scalar hashlib inside."""
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", True)


# ===================================================================
# Block-level Fiat-Shamir: byte-identical challenges
# ===================================================================


def test_hash_to_zr_many_matches_scalar_native_on_and_off(monkeypatch):
    items = [
        (bytes([i % 251]) * (i * 7 % 40), b"fts/dom-%d" % (i % 3))
        for i in range(25)
    ] + [(b"", b"fts/empty")]
    want = [hm.hash_to_zr(d, dom) for d, dom in items]
    assert hm.hash_to_zr_many(items) == want
    assert hm.hash_to_zr_many([]) == []
    assert hm.hash_to_zr_many(iter(items)) == want  # any iterable
    _no_native_sha(monkeypatch)
    assert hm.hash_to_zr_many(items) == want


# ===================================================================
# Batched host Schnorr verify: per-row verdict identity
# ===================================================================


def _host_ok(pk, msg, sig_raw):
    try:
        pk.verify(msg, sig_raw)
        return True
    except ValueError:
        return False


def _sig_rows(rng):
    """Every row class with its expected batch verdict (None = the
    scalar path owns the decision)."""
    keys = [sign.keygen(rng) for _ in range(3)]
    rows, expect = [], []

    def add(pk, msg, sig_raw, want="host"):
        rows.append((pk.point, msg, sig_raw))
        expect.append(
            _host_ok(pk, msg, sig_raw) if want == "host" else want
        )

    for i in range(5):  # valid rows, repeated signers
        k = keys[i % 3]
        msg = b"pay-%d" % i
        add(k.public, msg, k.sign(msg, rng))
    k = keys[0]
    good = k.sign(b"tamper-me", rng)
    d = loads(good)
    d["c"] ^= 1
    add(k.public, b"tamper-me", dumps(d))  # bit-flipped challenge
    d = loads(good)
    d["z"] ^= 1
    add(k.public, b"tamper-me", dumps(d))  # bit-flipped response
    add(k.public, b"tamper-ME", good)  # flipped message
    add(keys[1].public, b"tamper-me", good)  # wrong pk
    add(k.public, b"x", b"\x00not-a-sig", want=None)  # unparseable
    d = loads(good)
    d["c"] = "not-an-int"
    add(k.public, b"x", dumps(d), want=None)  # non-integer field
    return rows, expect


def test_verify_many_differential_native_on_and_off(rng, monkeypatch):
    rows, expect = _sig_rows(rng)
    assert sign.verify_many(rows) == expect
    assert sign.verify_many([]) == []
    # pure-python bn254 fallback + scalar sha fallback: same verdicts
    n0 = _counter("hostmath.g1_multiexp_rows.python")
    monkeypatch.setattr(hm, "NATIVE_G1", False)
    _no_native_sha(monkeypatch)
    assert sign.verify_many(rows) == expect
    assert _counter("hostmath.g1_multiexp_rows.python") > n0


# ===================================================================
# Batched host proof verify (zkatdlog 1-in/1-out shape)
# ===================================================================


def _zk_rows(zk_pp, rng):
    """Three plan rows: valid 1-in/1-out, proof-tampered 1-in/1-out,
    and a range-carrying 1-in/2-out shape the batch must leave alone."""
    drv = ZKATDLogDriver(zk_pp)
    out = drv.issue(b"issuer", "USD", [3], [b"alice"], rng=rng)
    t = drv.transfer(
        [ID("seed", 0)], [out.outputs[0]], [out.metadata[0]],
        "USD", [3], [b"alice"], rng=rng,
    )
    shape, good_row = drv.transfer_batch_plan(t.action_bytes)
    assert shape == (1, 1)
    d = loads(t.action_bytes)
    p = bytearray(d["proof"])
    p[len(p) // 2] ^= 1
    d["proof"] = bytes(p)
    _shape, bad_row = drv.transfer_batch_plan(dumps(d))
    out2 = drv.issue(b"issuer", "USD", [4], [b"alice"], rng=rng)
    t2 = drv.transfer(
        [ID("seed2", 0)], [out2.outputs[0]], [out2.metadata[0]],
        "USD", [1, 3], [b"alice", b"alice"], rng=rng,
    )
    shape2, range_row = drv.transfer_batch_plan(t2.action_bytes)
    assert shape2 == (1, 2)
    return drv, good_row, bad_row, range_row


def test_transfer_host_batch_differential(zk_pp, rng, monkeypatch):
    drv, good_row, bad_row, range_row = _zk_rows(zk_pp, rng)
    oks = drv.transfer_host_batch([good_row, bad_row, range_row])
    assert oks[0] is True  # the WF challenge compare IS the decision
    assert oks[1] is not True  # tampered: scalar path owns the error
    assert oks[2] is None  # range shape: never batch-decidable
    # same verdicts without any native library
    monkeypatch.setattr(hm, "NATIVE_G1", False)
    _no_native_sha(monkeypatch)
    oks2 = drv.transfer_host_batch([good_row, bad_row, range_row])
    assert oks2[0] is True and oks2[1] is not True and oks2[2] is None


# ===================================================================
# Vectorized conservation (fabtoken)
# ===================================================================


def test_validate_conservation_many_differential():
    pp = FabTokenPublicParams()
    drv = FabTokenDriver(pp)
    key = sign.keygen(random.Random(3))
    ident = identity.pk_identity(key.public)
    tok5 = drv.issue(ident, "USD", [5], [ident]).outputs[0]
    tok4 = drv.issue(ident, "USD", [4], [ident]).outputs[0]
    tok_eur = drv.issue(ident, "EUR", [5], [ident]).outputs[0]
    ok = dumps({"inputs": [tok5], "outputs": [tok5]})
    ok_split = dumps({"inputs": [tok5, tok4], "outputs": [tok4, tok5]})
    bad_sum = dumps({"inputs": [tok5], "outputs": [tok4]})
    bad_type = dumps({"inputs": [tok5], "outputs": [tok_eur]})
    malformed_tok = dumps({"inputs": [b"\x00junk"], "outputs": [tok5]})
    empty = dumps({"inputs": [], "outputs": [tok5]})
    oks = drv.validate_conservation_many(
        [ok, ok_split, bad_sum, bad_type, malformed_tok, empty, b"\x00"]
    )
    # True only where the scalar conservation leg would accept; anything
    # the column pass cannot prove stays None for the scalar re-check
    assert oks == [True, True, None, None, None, None, None]
    assert drv.validate_conservation_many([]) == []


# ===================================================================
# host_map: the commit-worker fan-out
# ===================================================================


def test_host_map_order_and_inline_routing(monkeypatch):
    items = list(range(100))

    def double(chunk):
        return [x * 2 for x in chunk]

    monkeypatch.setenv("FTS_COMMIT_WORKERS", "3")
    assert npipe.host_workers() == 3
    assert npipe.host_map(double, items) == [x * 2 for x in items]
    # small batches run inline (no pool), same result
    assert npipe.host_map(double, items[:5]) == [x * 2 for x in items[:5]]
    # workers=1 is the inline kill switch
    monkeypatch.setenv("FTS_COMMIT_WORKERS", "1")
    assert npipe.host_workers() == 1
    assert npipe.host_map(double, items) == [x * 2 for x in items]
    monkeypatch.setenv("FTS_COMMIT_WORKERS", "junk")
    assert npipe.host_workers() >= 1  # junk -> auto


def test_host_map_worker_exception_propagates(monkeypatch):
    monkeypatch.setenv("FTS_COMMIT_WORKERS", "2")

    def boom(chunk):
        raise RuntimeError("worker died")

    with pytest.raises(RuntimeError, match="worker died"):
        npipe.host_map(boom, list(range(64)))


# ===================================================================
# End-to-end block differential (fabtoken: sign + conservation passes)
# ===================================================================


def _fab_corpus(n_transfers=6, tamper=None):
    """1 issue seed + a chain of pk-signed self-transfers; `tamper`
    injects a bit-flipped owner signature at t2 and/or appends a
    double spend of t0's output (already consumed by t1)."""
    pp = FabTokenPublicParams()
    drv = FabTokenDriver(pp)
    key = sign.keygen(random.Random(7))
    ident = identity.pk_identity(key.public)
    reqs = []
    out = drv.issue(ident, "USD", [9], [ident])
    req = TokenRequest(anchor="seed")
    req.issues.append(
        IssueRecord(action=out.action_bytes, issuer=ident,
                    outputs_metadata=out.metadata, receivers=[ident])
    )
    req.issues[0].signature = key.sign(
        req.marshal_to_sign(), random.Random(11)
    )
    reqs.append(req.to_bytes())
    prev, prev_raw = ID("seed", 0), out.outputs[0]
    outputs = {}
    for k in range(n_transfers):
        t = drv.transfer([prev], [prev_raw], [prev_raw], "USD", [9], [ident])
        tr = TokenRequest(anchor=f"t{k}")
        tr.transfers.append(
            TransferRecord(action=t.action_bytes, input_ids=[prev],
                           senders=[ident], outputs_metadata=t.metadata,
                           receivers=[ident])
        )
        sig = key.sign(tr.marshal_to_sign(), random.Random(100 + k))
        if k == 2 and tamper in ("sig", "all"):
            d = loads(sig)
            d["z"] ^= 1
            sig = dumps(d)
        tr.transfers[0].signatures = [sig]
        reqs.append(tr.to_bytes())
        outputs[k] = (prev, prev_raw)
        prev, prev_raw = ID(f"t{k}", 0), t.outputs[0]
    if tamper in ("double_spend", "all"):
        spent_id, spent_raw = ID("t0", 0), outputs.get(1, (None, None))[1]
        t = drv.transfer(
            [spent_id], [spent_raw], [spent_raw], "USD", [9], [ident]
        )
        tr = TokenRequest(anchor="dsp")
        tr.transfers.append(
            TransferRecord(action=t.action_bytes, input_ids=[spent_id],
                           senders=[ident], outputs_metadata=t.metadata,
                           receivers=[ident])
        )
        tr.transfers[0].signatures = [
            key.sign(tr.marshal_to_sign(), random.Random(999))
        ]
        reqs.append(tr.to_bytes())
    return pp, reqs


def _outcomes(events):
    return [(e.tx_id, e.status, e.message) for e in events]


def _fab_run(pp, reqs):
    net = Network(
        RequestValidator(FabTokenDriver(pp)),
        policy=BlockPolicy(max_block_txs=32),
    )
    return _outcomes(net.submit_many(reqs))


def test_fabtoken_block_differential_batch_on_off_and_workers(monkeypatch):
    """Statuses AND error messages are byte-identical across: host batch
    on (default), N commit workers, native math absent, and the
    scalar baseline (`FTS_HOST_BATCH=0`)."""
    pp, reqs = _fab_corpus(tamper="all")
    monkeypatch.setenv("FTS_HOST_BATCH", "0")
    baseline = _fab_run(pp, reqs)
    by_id = {tx: st for tx, st, _ in baseline}
    assert by_id["seed"] == TxStatus.VALID
    assert by_id["t1"] == TxStatus.VALID
    assert by_id["t2"] == TxStatus.INVALID  # tampered signature
    assert by_id["t3"] == TxStatus.INVALID  # chain broken by t2
    assert by_id["dsp"] == TxStatus.INVALID  # double spend
    assert any("already spent" in m for _t, _s, m in baseline)

    s0, p0 = _counter("hostbatch.sign.rows"), _counter(
        "hostbatch.conservation.rows"
    )
    monkeypatch.setenv("FTS_HOST_BATCH", "1")
    request_mod.cache_clear()
    assert _fab_run(pp, reqs) == baseline
    # the host batch passes actually ran (CPU auto-mode keeps the sign
    # plane host-side, so the block sign batch owns the valid rows)
    assert _counter("hostbatch.sign.rows") > s0
    assert _counter("hostbatch.conservation.rows") > p0

    monkeypatch.setenv("FTS_COMMIT_WORKERS", "4")
    request_mod.cache_clear()
    assert _fab_run(pp, reqs) == baseline

    monkeypatch.setattr(hm, "NATIVE_G1", False)
    _no_native_sha(monkeypatch)
    request_mod.cache_clear()
    assert _fab_run(pp, reqs) == baseline


# ===================================================================
# End-to-end block differential (zkatdlog: host proof batch leftovers)
# ===================================================================


def _zk_corpus(zk_pp, rng):
    """Chained 1-in/1-out zk transfers + a proof-tampered tx (re-signed
    so the PROOF check, not the signature, decides) + a double spend."""
    drv = ZKATDLogDriver(zk_pp)
    key = sign.keygen(random.Random(21))
    ident = identity.pk_identity(key.public)
    reqs = []
    out = drv.issue(ident, "USD", [3], [ident], rng=rng)
    req = TokenRequest(anchor="seed")
    req.issues.append(
        IssueRecord(action=out.action_bytes, issuer=ident,
                    outputs_metadata=out.metadata, receivers=[ident])
    )
    req.issues[0].signature = key.sign(
        req.marshal_to_sign(), random.Random(31)
    )
    reqs.append(req.to_bytes())
    prev, prev_tok, prev_meta = ID("seed", 0), out.outputs[0], out.metadata[0]
    for k in range(4):
        t = drv.transfer(
            [prev], [prev_tok], [prev_meta], "USD", [3], [ident], rng=rng
        )
        action = t.action_bytes
        if k == 2:  # tamper the zk proof, then sign the TAMPERED action
            d = loads(action)
            p = bytearray(d["proof"])
            p[len(p) // 2] ^= 1
            d["proof"] = bytes(p)
            action = dumps(d)
        tr = TokenRequest(anchor=f"z{k}")
        tr.transfers.append(
            TransferRecord(action=action, input_ids=[prev],
                           senders=[ident], outputs_metadata=t.metadata,
                           receivers=[ident])
        )
        tr.transfers[0].signatures = [
            key.sign(tr.marshal_to_sign(), random.Random(200 + k))
        ]
        reqs.append(tr.to_bytes())
        if k == 0:
            spent = (prev, prev_tok, prev_meta)
        prev, prev_tok, prev_meta = ID(f"z{k}", 0), t.outputs[0], t.metadata[0]
    # double spend: re-spend the seed output z0 already consumed
    sid, stok, smeta = spent
    t = drv.transfer([sid], [stok], [smeta], "USD", [3], [ident], rng=rng)
    tr = TokenRequest(anchor="zdsp")
    tr.transfers.append(
        TransferRecord(action=t.action_bytes, input_ids=[sid],
                       senders=[ident], outputs_metadata=t.metadata,
                       receivers=[ident])
    )
    tr.transfers[0].signatures = [
        key.sign(tr.marshal_to_sign(), random.Random(998))
    ]
    reqs.append(tr.to_bytes())
    return reqs


def _zk_run(zk_pp, reqs):
    # min_batch above the block size: every plannable row is a device
    # leftover, i.e. exactly the host proof batch's input
    net = Network(
        RequestValidator(ZKATDLogDriver(zk_pp)),
        policy=BlockPolicy(max_block_txs=32, min_batch=99, use_batched=True),
    )
    return _outcomes(net.submit_many(reqs))


def test_zkatdlog_block_differential_host_proof_batch(zk_pp, rng, monkeypatch):
    reqs = _zk_corpus(zk_pp, rng)
    monkeypatch.setenv("FTS_HOST_BATCH", "0")
    r0 = _counter("hostbatch.proof.rows")
    baseline = _zk_run(zk_pp, reqs)
    assert _counter("hostbatch.proof.rows") == r0  # kill switch honored
    by_id = {tx: st for tx, st, _ in baseline}
    assert by_id["seed"] == TxStatus.VALID
    assert by_id["z0"] == TxStatus.VALID
    assert by_id["z1"] == TxStatus.VALID
    assert by_id["z2"] == TxStatus.INVALID  # tampered proof
    assert by_id["z3"] == TxStatus.INVALID  # chain broken by z2
    assert by_id["zdsp"] == TxStatus.INVALID  # double spend

    monkeypatch.setenv("FTS_HOST_BATCH", "1")
    request_mod.cache_clear()
    assert _zk_run(zk_pp, reqs) == baseline
    # the valid leftover rows were proved by the batch pass
    assert _counter("hostbatch.proof.rows") > r0
    flights = [
        e for e in mx.FLIGHT.tail() if e["kind"] == "verify.host_batch"
    ]
    assert flights and flights[-1]["verified"] >= 1


def _zk_range_corpus(pp, rng):
    """One issue of four tokens, then four 1-in/2-out transfers (each
    output carries `exponent` membership proofs): valid, proof-tampered,
    a range proof one digit short for its second output, valid. Every
    request is signed over the action it carries, so the PROOF decides."""
    from fabric_token_sdk_tpu.crypto.rangeproof import RangeProof
    from fabric_token_sdk_tpu.crypto.transfer import TransferProof

    drv = ZKATDLogDriver(pp)
    key = sign.keygen(random.Random(21))
    ident = identity.pk_identity(key.public)
    out = drv.issue(ident, "USD", [242] * 4, [ident] * 4, rng=rng)
    req = TokenRequest(anchor="seed")
    req.issues.append(
        IssueRecord(action=out.action_bytes, issuer=ident,
                    outputs_metadata=out.metadata, receivers=[ident] * 4)
    )
    req.issues[0].signature = key.sign(req.marshal_to_sign(), random.Random(31))
    reqs = [req.to_bytes()]
    for k in range(4):
        t = drv.transfer(
            [ID("seed", k)], [out.outputs[k]], [out.metadata[k]],
            "USD", [200, 42], [ident, ident], rng=rng,
        )
        d = loads(t.action_bytes)
        if k == 1:
            p = bytearray(d["proof"])
            p[len(p) // 2] ^= 1
            d["proof"] = bytes(p)
        if k == 2:
            tp = TransferProof.from_bytes(d["proof"])
            rpf = RangeProof.from_bytes(tp.range_correctness)
            rpf.membership_proofs[1].pop()
            rpf.digit_commitments[1].pop()
            tp.range_correctness = rpf.to_bytes()
            d["proof"] = tp.to_bytes()
        tr = TokenRequest(anchor=f"r{k}")
        tr.transfers.append(
            TransferRecord(action=dumps(d), input_ids=[ID("seed", k)],
                           senders=[ident], outputs_metadata=t.metadata,
                           receivers=[ident, ident])
        )
        tr.transfers[0].signatures = [
            key.sign(tr.marshal_to_sign(), random.Random(300 + k))
        ]
        reqs.append(tr.to_bytes())
    return reqs


def test_zkatdlog_block_differential_at_exponent_5(rng, monkeypatch):
    """Five digits per output (the token sample's exponent; base 3 keeps
    the signed table small): range-carrying shapes are never decided by
    the batch-first pass, so statuses AND messages equal the scalar
    path's, the wrong digit count included."""
    pp = setup(base=3, exponent=5, rng=random.Random(0xF75))
    reqs = _zk_range_corpus(pp, rng)
    monkeypatch.setenv("FTS_HOST_BATCH", "0")
    baseline = _zk_run(pp, reqs)
    assert [st for _tx, st, _m in baseline] == [
        TxStatus.VALID, TxStatus.VALID, TxStatus.INVALID, TxStatus.INVALID,
        TxStatus.VALID,
    ]
    assert "range proof not well formed" in baseline[3][2]
    monkeypatch.setenv("FTS_HOST_BATCH", "1")
    request_mod.cache_clear()
    assert _zk_run(pp, reqs) == baseline
    # and one request per block, as the benchmark's plain reference runs
    net = Network(
        RequestValidator(ZKATDLogDriver(pp)),
        policy=BlockPolicy(use_batched=False, sign_batched=False),
    )
    assert _outcomes([net.submit(r) for r in reqs]) == baseline


# ===================================================================
# Parsed-request cache
# ===================================================================


def test_request_cache_hits_misses_and_clone_isolation():
    pp, reqs = _fab_corpus(n_transfers=2)
    raw = reqs[1]
    h0, m0 = _counter("request.cache.hits"), _counter("request.cache.misses")
    r1 = TokenRequest.from_bytes(raw)
    assert _counter("request.cache.misses") == m0 + 1
    r2 = TokenRequest.from_bytes(raw)
    assert _counter("request.cache.hits") == h0 + 1
    assert r2.to_bytes() == raw
    assert r2.wire_bytes() == raw  # unmutated: the exact wire bytes
    # clone isolation: mutating one parse never corrupts later lookups
    r2.transfers[0].signatures[0] = b"corrupted"
    r2.anchor = "mutated"
    assert r2.wire_bytes() != raw  # reassignment drops the wire memo
    r3 = TokenRequest.from_bytes(raw)
    assert r3.to_bytes() == raw
    assert r3.anchor == r1.anchor
    assert request_mod.cache_len() >= 1
    request_mod.cache_clear()
    assert request_mod.cache_len() == 0


def test_request_cache_bounded_eviction_and_flight(monkeypatch):
    monkeypatch.setenv("FTS_REQUEST_CACHE", "4")
    request_mod.cache_clear()  # re-resolve capacity from env
    e0 = _counter("request.cache.evictions")
    raws = []
    for i in range(10):
        r = TokenRequest(anchor=f"evict-{i}")
        raws.append(r.to_bytes())
    for raw in raws:
        TokenRequest.from_bytes(raw)
    assert request_mod.cache_len() == 4  # bounded
    assert _counter("request.cache.evictions") - e0 == 6
    evt = [
        e for e in mx.FLIGHT.tail() if e["kind"] == "request.cache.evict"
    ][-1]
    assert evt["capacity"] == 4 and evt["size"] <= 4
    # capacity 0 disables storage AND counters
    monkeypatch.setenv("FTS_REQUEST_CACHE", "0")
    request_mod.cache_clear()
    h0, m0 = _counter("request.cache.hits"), _counter("request.cache.misses")
    TokenRequest.from_bytes(raws[0])
    TokenRequest.from_bytes(raws[0])
    assert request_mod.cache_len() == 0
    assert _counter("request.cache.hits") == h0
    assert _counter("request.cache.misses") == m0


# ===================================================================
# ops.health caches section
# ===================================================================


def test_health_reports_cache_section():
    pp, reqs = _fab_corpus(n_transfers=2)
    net = Network(
        RequestValidator(FabTokenDriver(pp)),
        policy=BlockPolicy(max_block_txs=8),
    )
    net.submit_many(reqs)
    caches = net.health()["caches"]
    assert set(caches) == {"identity", "request", "parse"}
    for section in caches.values():
        assert section["hits"] >= 0 and section["misses"] >= 0
    assert caches["request"]["entries"] == request_mod.cache_len()
    assert "evictions" in caches["request"]
