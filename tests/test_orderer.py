"""Orderer subsystem: multi-tx blocks, batched block validation, MVCC.

Covers the block pipeline end to end: intra-block double spends (the
LATER tx is invalidated, never the block), conflicts across consecutive
blocks, a block's zkatdlog transfers riding ONE `BatchedTransferVerifier`
call whatever their shapes, mixed batched/host blocks (issues fall back
to the host `RequestValidator`), differential block-mode vs per-tx commits,
listener crash isolation, block-cut policy, and snapshot/restore of
multi-tx blocks.

The zkatdlog cases use 1-in/1-out transfers on purpose: that shape skips
range proofs (reference transfer.go:55-59), so the batched path touches
only the non-slow stage tiles — the pairing-heavy shapes stay in the
slow-marked tests, but for the one `(1,2)` of the mixed block, whose
pairing kernels are stood in for (`tests/hostplane.py`).
"""
import random
import threading

import pytest

from fabric_token_sdk_tpu.api.validator import RequestValidator
from fabric_token_sdk_tpu.crypto.setup import setup
from fabric_token_sdk_tpu.drivers.fabtoken import FabTokenDriver, FabTokenPublicParams
from fabric_token_sdk_tpu.drivers.zkatdlog import ZKATDLogDriver
from fabric_token_sdk_tpu.services.network import BlockPolicy, Network, TxStatus
from fabric_token_sdk_tpu.services.ttx import Party, Transaction
from fabric_token_sdk_tpu.utils import metrics as mx


@pytest.fixture(scope="module")
def zk_pp():
    return setup(base=4, exponent=2, rng=random.Random(0xF75))


def build_env(driver_factory, policy=None):
    """issuer + alice + bob on one network, no auditor (these tests
    target the ordering/commit plane, not the audit plane)."""
    network = Network(RequestValidator(driver_factory()), policy=policy)
    parties = {
        name: Party(name, driver_factory(), network)
        for name in ("issuer-node", "alice-node", "bob-node")
    }
    issuer = parties["issuer-node"].new_issuer_wallet("issuer")
    alice = parties["alice-node"].new_owner_wallet("alice", anonymous=False)
    bob = parties["bob-node"].new_owner_wallet("bob", anonymous=False)
    vdrv = network.validator.driver
    if hasattr(vdrv, "pp") and hasattr(vdrv.pp, "add_issuer"):
        vdrv.pp.add_issuer(issuer.identity)
    return network, parties, issuer, alice, bob


def fab_env(policy=None):
    pp = FabTokenPublicParams()
    return build_env(lambda: FabTokenDriver(pp), policy)


def zk_env(zk_pp, policy=None):
    return build_env(lambda: ZKATDLogDriver(zk_pp), policy)


def issue_to(parties, alice, values, anchor):
    """One committed issue tx putting `values` USD tokens in alice's vault."""
    tx = Transaction(parties["issuer-node"], anchor)
    tx.issue(
        "issuer", "USD", list(values),
        [alice.recipient_identity()] * len(values), anonymous=False,
    )
    tx.collect_endorsements(None)
    tx.submit()
    return tx


def manual_transfer(party, token_id, value, recipient, anchor):
    """Assemble + sign a transfer spending ONE specific token, bypassing
    the selector (whose locks would forbid crafting a double spend)."""
    req = party.tms.new_request(anchor)
    tokens, metas = party.vault.get_many([token_id])
    party.tms.add_transfer(req, [token_id], tokens, metas, "USD", [value], [recipient])
    party.tms.sign_transfers(req)
    return req


def _counter(name):
    return mx.REGISTRY.counter(name).value


# ===================================================================
# MVCC inside and across blocks (host plane, fabtoken)
# ===================================================================


def test_intra_block_double_spend_invalidates_later_tx():
    network, parties, issuer, alice, bob = fab_env(BlockPolicy(max_block_txs=8))
    alice_p, bob_p = parties["alice-node"], parties["bob-node"]
    issue_to(parties, alice, [5], "seed")
    tid = alice_p.vault.token_ids()[0]
    req_a = manual_transfer(alice_p, tid, 5, bob.recipient_identity(), "spend-a")
    req_b = manual_transfer(alice_p, tid, 5, bob.recipient_identity(), "spend-b")

    h0 = network.height()
    events = network.submit_many([req_a.to_bytes(), req_b.to_bytes()])
    assert events[0].status == TxStatus.VALID
    assert events[1].status == TxStatus.INVALID
    assert "already spent" in events[1].message
    # ONE block carried both txs; only the conflicting one was dropped
    assert network.height() == h0 + 1
    assert network.block(h0).txs == ["spend-a", "spend-b"]
    assert bob_p.balance("USD") == 5
    assert alice_p.balance("USD") == 0
    # finality events are queryable per tx
    assert network.status("spend-a").status == TxStatus.VALID
    assert network.status("spend-b").status == TxStatus.INVALID


def test_conflict_across_consecutive_blocks():
    network, parties, issuer, alice, bob = fab_env()
    alice_p = parties["alice-node"]
    issue_to(parties, alice, [7], "seed")
    tid = alice_p.vault.token_ids()[0]
    req_a = manual_transfer(alice_p, tid, 7, bob.recipient_identity(), "blk-a")
    req_b = manual_transfer(alice_p, tid, 7, bob.recipient_identity(), "blk-b")

    h0 = network.height()
    ev_a = network.submit(req_a.to_bytes())
    ev_b = network.submit(req_b.to_bytes())  # next block, same input
    assert ev_a.status == TxStatus.VALID
    assert ev_b.status == TxStatus.INVALID and "already spent" in ev_b.message
    assert network.height() == h0 + 2
    # idempotent resubmission returns the recorded event, adds no block
    assert network.submit(req_a.to_bytes()).status == TxStatus.VALID
    assert network.height() == h0 + 2


def test_intra_block_create_then_spend():
    """An output created by an EARLIER tx in the block is spendable by a
    later tx of the same block (the MVCC overlay sees block-local
    writes)."""
    network, parties, issuer, alice, bob = fab_env(BlockPolicy(max_block_txs=4))
    alice_p = parties["alice-node"]
    issue_to(parties, alice, [9], "seed")
    tid = alice_p.vault.token_ids()[0]
    req_a = manual_transfer(alice_p, tid, 9, alice.recipient_identity(), "hop-1")
    # hop-2 spends hop-1's output, which exists only inside the block
    from fabric_token_sdk_tpu.models.token import ID

    hop1_out = ID("hop-1", 0)
    req_b = alice_p.tms.new_request("hop-2")
    # the output bytes of hop-1 are what its action wrote; for fabtoken
    # metadata mirrors the output, so assemble from the action outcome
    from fabric_token_sdk_tpu.crypto.serialization import loads

    out_raw = loads(req_a.transfers[0].action)["outputs"][0]
    alice_p.tms.add_transfer(
        req_b, [hop1_out], [out_raw], [out_raw], "USD", [9],
        [bob.recipient_identity()],
    )
    alice_p.tms.sign_transfers(req_b)

    events = network.submit_many([req_a.to_bytes(), req_b.to_bytes()])
    assert [e.status for e in events] == [TxStatus.VALID, TxStatus.VALID]
    assert parties["bob-node"].balance("USD") == 9


def test_differential_block_vs_per_tx():
    """A block commit and per-tx commits of the SAME requests agree on
    every status and on the final ledger state."""
    network, parties, issuer, alice, bob = fab_env(BlockPolicy(max_block_txs=8))
    alice_p = parties["alice-node"]
    seed_tx = issue_to(parties, alice, [4, 6], "seed")
    ids = alice_p.vault.token_ids()
    req_a = manual_transfer(alice_p, ids[0], 4, bob.recipient_identity(), "d-a")
    req_b = manual_transfer(alice_p, ids[0], 4, bob.recipient_identity(), "d-b")
    req_c = manual_transfer(alice_p, ids[1], 6, bob.recipient_identity(), "d-c")
    batch = [req_a.to_bytes(), req_b.to_bytes(), req_c.to_bytes()]
    block_events = network.submit_many(batch)

    # fresh ledger, same public params, one tx per block, no device plane
    vdrv = network.validator.driver
    net2 = Network(
        RequestValidator(FabTokenDriver(vdrv.pp)),
        policy=BlockPolicy(max_block_txs=1, use_batched=False),
    )
    seq_events = [net2.submit(rb) for rb in [seed_tx.request.to_bytes()] + batch]
    assert [e.status for e in seq_events[1:]] == [e.status for e in block_events]
    from fabric_token_sdk_tpu.models.token import ID

    for anchor, n_out in (("d-a", 1), ("d-c", 1)):
        for i in range(n_out):
            assert network.exists(ID(anchor, i)) == net2.exists(ID(anchor, i))
    assert not net2.exists(ID("d-b", 0)) and not network.exists(ID("d-b", 0))


# ===================================================================
# Batched zkatdlog block validation (device plane, 1-in/1-out shapes)
# ===================================================================


def test_zk_block_of_8_rides_batched_verifier(zk_pp):
    """Acceptance: a block of >= 8 same-shape zkatdlog transfers
    validates through ONE BatchedTransferVerifier call (asserted via the
    batch.* and ledger.block.* metrics) with per-tx finality."""
    network, parties, issuer, alice, bob = zk_env(
        zk_pp, BlockPolicy(max_block_txs=16, min_batch=2)
    )
    alice_p, bob_p = parties["alice-node"], parties["bob-node"]
    issue_to(parties, alice, [5] * 8, "seed-8")

    txs = []
    for i in range(8):
        t = Transaction(alice_p, f"pay-{i}")
        t.transfer("alice", "USD", [5], [bob.recipient_identity()])  # (1,1)
        t.collect_endorsements(None)
        txs.append(t)

    before_bt = _counter("batch.transfer.txs")
    before_batched = _counter("ledger.validate.batched")
    before_host = _counter("ledger.validate.host")
    blocks_before = _counter("ledger.blocks.committed")
    size_hist = mx.REGISTRY.histogram("ledger.block.size")
    size_count_before = size_hist.count
    h0 = network.height()

    for t in txs:
        t.submit_async()  # ttx ordering stage: enqueue without waiting
    network.flush()  # cut ONE deterministic 8-tx block
    events = [t.wait() for t in txs]

    assert all(e.status == TxStatus.VALID for e in events)
    assert network.height() == h0 + 1
    assert network.block(h0).txs == [f"pay-{i}" for i in range(8)]
    # all 8 proofs went through the batched device plane, none through host
    assert _counter("batch.transfer.txs") - before_bt == 8
    assert _counter("ledger.validate.batched") - before_batched == 8
    assert _counter("ledger.validate.host") - before_host == 0
    assert _counter("ledger.blocks.committed") - blocks_before == 1
    assert size_hist.count == size_count_before + 1
    assert bob_p.balance("USD") == 40
    assert alice_p.balance("USD") == 0


def test_zk_block_differential_vs_host(zk_pp):
    """Batched block commit and per-tx host commits of the SAME zkatdlog
    requests agree on every status (including the MVCC conflict)."""
    network, parties, issuer, alice, bob = zk_env(
        zk_pp, BlockPolicy(max_block_txs=8, min_batch=2)
    )
    alice_p = parties["alice-node"]
    seed = issue_to(parties, alice, [5, 5], "zk-seed")
    ids = alice_p.vault.token_ids()
    req_a = manual_transfer(alice_p, ids[0], 5, bob.recipient_identity(), "zk-a")
    req_b = manual_transfer(alice_p, ids[1], 5, bob.recipient_identity(), "zk-b")
    req_c = manual_transfer(alice_p, ids[0], 5, bob.recipient_identity(), "zk-c")
    batch = [req_a.to_bytes(), req_b.to_bytes(), req_c.to_bytes()]

    before_bt = _counter("batch.transfer.txs")
    block_events = network.submit_many(batch)
    # all three same-shape proofs batch-verified; the conflict is MVCC's
    assert _counter("batch.transfer.txs") - before_bt == 3
    assert [e.status for e in block_events] == [
        TxStatus.VALID, TxStatus.VALID, TxStatus.INVALID,
    ]
    assert "already spent" in block_events[2].message

    net2 = Network(
        RequestValidator(ZKATDLogDriver(zk_pp)),
        policy=BlockPolicy(max_block_txs=1, use_batched=False),
    )
    seq = [net2.submit(rb) for rb in [seed.request.to_bytes()] + batch]
    assert [e.status for e in seq[1:]] == [e.status for e in block_events]


def test_zk_mixed_block_host_and_batched(zk_pp, monkeypatch):
    """One block mixing every plane: an issue (host) and three transfers
    of two shapes, which ride ONE batched call whatever their shapes
    (until PR 37 the `(1,2)`, alone of its shape, fell to the host) —
    plus an issue-only block as the no-rows case. The stage tiles are the
    backend's real programs; the `(1,2)`'s membership proofs run the
    pairing walk over exact host stand-ins for its three kernels
    (`tests/hostplane.py`: the real ones cost the CPU backend minutes to
    compile), so the verdicts are real."""
    import hostplane

    hostplane.install(monkeypatch, stage=False)
    network, parties, issuer, alice, bob = zk_env(
        zk_pp, BlockPolicy(max_block_txs=8, min_batch=2)
    )
    alice_p = parties["alice-node"]
    issue_to(parties, alice, [5, 5, 5], "mx-seed")  # issue-only block: no rows

    t1 = Transaction(alice_p, "mx-t1")
    t1.transfer("alice", "USD", [5], [bob.recipient_identity()])  # (1,1)
    t1.collect_endorsements(None)
    t2 = Transaction(alice_p, "mx-t2")
    t2.transfer("alice", "USD", [5], [bob.recipient_identity()])  # (1,1)
    t2.collect_endorsements(None)
    t3 = Transaction(alice_p, "mx-t3")
    t3.transfer("alice", "USD", [3], [bob.recipient_identity()])  # (1,2): change
    t3.collect_endorsements(None)
    issue2 = Transaction(parties["issuer-node"], "mx-issue2")
    issue2.issue("issuer", "USD", [2], [alice.recipient_identity()],
                 anonymous=False)
    issue2.collect_endorsements(None)

    before_batched = _counter("ledger.validate.batched")
    before_host = _counter("ledger.validate.host")
    before_calls = _counter("batch.transfer.calls")
    before_shapes = _counter("batch.transfer.shapes")
    h0 = network.height()
    events = network.submit_many(
        [issue2.request.to_bytes(), t1.request.to_bytes(),
         t2.request.to_bytes(), t3.request.to_bytes()]
    )
    assert all(e.status == TxStatus.VALID for e in events)
    assert network.height() == h0 + 1
    # the (1,1) pair and the (1,2) rode one call; nothing fell to the host
    assert _counter("ledger.validate.batched") - before_batched == 3
    assert _counter("ledger.validate.host") - before_host == 0
    assert _counter("batch.transfer.calls") - before_calls == 1
    assert _counter("batch.transfer.shapes") - before_shapes == 2
    assert parties["bob-node"].balance("USD") == 13
    assert alice_p.balance("USD") == 4  # 2 change + 2 fresh issue


def test_zk_batched_group_rejects_tampered_proof(zk_pp):
    """A tampered proof inside a batched group must invalidate ONLY its
    own tx: the device verdict (False) reaches the driver as a
    ValidationError while the group's other txs commit."""
    network, parties, issuer, alice, bob = zk_env(
        zk_pp, BlockPolicy(max_block_txs=8, min_batch=2)
    )
    alice_p = parties["alice-node"]
    issue_to(parties, alice, [5, 5], "tamper-seed")
    ids = alice_p.vault.token_ids()
    req_ok = manual_transfer(alice_p, ids[0], 5, bob.recipient_identity(), "tp-ok")
    req_bad = manual_transfer(alice_p, ids[1], 5, bob.recipient_identity(), "tp-bad")
    # corrupt the wf proof inside the action, then re-sign the tampered
    # request so only the PROOF is at fault
    from fabric_token_sdk_tpu.crypto.serialization import dumps, loads
    from fabric_token_sdk_tpu.crypto.transfer import TransferProof
    from fabric_token_sdk_tpu.crypto.wellformedness import TransferWF
    from fabric_token_sdk_tpu.crypto import hostmath as hm

    action = loads(req_bad.transfers[0].action)
    proof = TransferProof.from_bytes(action["proof"])
    wf = TransferWF.from_bytes(proof.wf)
    wf.sum_resp = (wf.sum_resp + 1) % hm.R
    proof.wf = wf.to_bytes()
    action["proof"] = proof.to_bytes()
    req_bad.transfers[0].action = dumps(action)
    alice_p.tms.sign_transfers(req_bad)

    before_bt = _counter("batch.transfer.txs")
    events = network.submit_many([req_ok.to_bytes(), req_bad.to_bytes()])
    assert _counter("batch.transfer.txs") - before_bt == 2  # both batched
    assert events[0].status == TxStatus.VALID
    assert events[1].status == TxStatus.INVALID
    assert "invalid transfer proof" in events[1].message
    # the untampered token is spent, the tampered one is not
    assert network.exists(ids[1]) and not network.exists(ids[0])


# ===================================================================
# Commit-loop robustness + policy + persistence
# ===================================================================


def test_transient_internal_error_is_not_cached():
    """A non-ValidationError fault (flaky native call, OOM) fails the
    ATTEMPT but is never recorded as a durable rejection — an identical
    resubmission can succeed once the fault clears."""
    network, parties, issuer, alice, bob = fab_env()
    alice_p = parties["alice-node"]
    issue_to(parties, alice, [5], "seed")
    tid = alice_p.vault.token_ids()[0]
    req = manual_transfer(alice_p, tid, 5, bob.recipient_identity(), "flaky")

    orig = network.validator.validate
    calls = {"n": 0}

    def flaky(*args, **kwargs):
        if calls["n"] == 0:
            calls["n"] += 1
            raise MemoryError("transient fault")
        return orig(*args, **kwargs)

    network.validator.validate = flaky
    ev1 = network.submit(req.to_bytes())
    assert ev1.status == TxStatus.INVALID
    assert "internal validation error" in ev1.message
    assert network.status("flaky") is None  # nothing durable recorded
    ev2 = network.submit(req.to_bytes())  # identical resubmission
    assert ev2.status == TxStatus.VALID
    assert parties["bob-node"].balance("USD") == 5


def test_listener_exception_does_not_abort_commit():
    network, parties, issuer, alice, bob = fab_env()
    seen = []

    def boom(event, request):
        raise RuntimeError("listener crashed")

    network.subscribe(boom)
    network.subscribe(lambda e, r: seen.append(e.tx_id))
    before = _counter("ledger.listener.errors")
    issue_to(parties, alice, [5], "seed")  # would raise before the fix
    assert _counter("ledger.listener.errors") - before >= 1
    assert "seed" in seen  # listeners AFTER the crasher still ran
    assert parties["alice-node"].balance("USD") == 5  # commit completed


def test_block_cut_policy_and_snapshot_restore():
    network, parties, issuer, alice, bob = fab_env(BlockPolicy(max_block_txs=2))
    alice_p = parties["alice-node"]
    issue_to(parties, alice, [1, 2, 3, 4, 5], "seed")
    reqs = [
        manual_transfer(alice_p, tid, v, bob.recipient_identity(), f"cut-{v}")
        for v, tid in zip([1, 2, 3, 4, 5], alice_p.vault.token_ids())
    ]
    h0 = network.height()
    events = network.submit_many([r.to_bytes() for r in reqs])
    assert all(e.status == TxStatus.VALID for e in events)
    assert network.height() == h0 + 3  # 2 + 2 + 1
    assert [len(network.block(h0 + i).txs) for i in range(3)] == [2, 2, 1]

    snap = network.snapshot()
    net2 = Network.restore(
        RequestValidator(FabTokenDriver(network.validator.driver.pp)), snap
    )
    assert net2.height() == network.height()
    assert net2.block(h0).txs == network.block(h0).txs
    assert net2.status("cut-3").status == TxStatus.VALID


def test_concurrent_submitters_group_commit():
    """Concurrent submitters race for the commit lock; every tx lands in
    exactly one block and all commit."""
    network, parties, issuer, alice, bob = fab_env()
    alice_p = parties["alice-node"]
    issue_to(parties, alice, [2, 2, 2, 2], "seed")
    reqs = [
        manual_transfer(alice_p, tid, 2, bob.recipient_identity(), f"par-{i}")
        for i, tid in enumerate(alice_p.vault.token_ids())
    ]
    h0 = network.height()
    results = []
    barrier = threading.Barrier(len(reqs))

    def worker(rb):
        barrier.wait()
        results.append(network.submit(rb))

    threads = [threading.Thread(target=worker, args=(r.to_bytes(),)) for r in reqs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(e.status == TxStatus.VALID for e in results)
    committed = [tx for i in range(h0, network.height())
                 for tx in network.block(i).txs]
    assert sorted(committed) == sorted(f"par-{i}" for i in range(len(reqs)))
    assert parties["bob-node"].balance("USD") == 8
