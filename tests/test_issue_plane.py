"""An issue's proof rows in the block's ONE proof-plane call.

The plain reference for a row is the scalar `crypto/issue.IssueVerifier`
(one issue at a time, `hostmath`); for a served block the scalar
`RequestValidator` with `use_batched=False`, one request a block: the
benchmark judge's own reference. Seeded issues of one and of several
outputs, anonymous and not, sound and with each fault the scalar verifier
knows (a tampered well-formedness response, a tampered digit commitment,
no range proof, a response count and a digit count that do not fit,
undecodable bytes), alone in a call and between transfers of two shapes.

Every tier-1 case runs the verifier's own glue and the walks' own padding
over exact host stand-ins for the tile kernels (`tests/hostplane.py`):
real verdicts, no compile. One `slow` case runs a call of both operations
through the real programs.
"""
import os
import random
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import hostplane  # noqa: E402
from test_mixed_shapes import (  # noqa: E402
    FORMS, break_row, make_row, scalar_verdicts,
)
from test_orderer import build_env, issue_to  # noqa: E402

from fabric_token_sdk_tpu.api.validator import RequestValidator  # noqa: E402
from fabric_token_sdk_tpu.crypto import batch, hostmath as hm, token as tok  # noqa: E402
from fabric_token_sdk_tpu.crypto.issue import (  # noqa: E402
    IssueProof, IssueProver, IssueRow, IssueVerifier,
)
from fabric_token_sdk_tpu.crypto.rangeproof import RangeProof  # noqa: E402
from fabric_token_sdk_tpu.crypto.serialization import dumps, loads  # noqa: E402
from fabric_token_sdk_tpu.crypto.setup import setup  # noqa: E402
from fabric_token_sdk_tpu.crypto.wellformedness import IssueWF  # noqa: E402
from fabric_token_sdk_tpu.drivers.zkatdlog import ZKATDLogDriver  # noqa: E402
from fabric_token_sdk_tpu.services.network import BlockPolicy, Network  # noqa: E402
from fabric_token_sdk_tpu.services.network.remote import (  # noqa: E402
    LedgerServer, RemoteNetwork,
)
from fabric_token_sdk_tpu.services.ttx import Transaction  # noqa: E402
from fabric_token_sdk_tpu.utils import devobs, faults, metrics as mx  # noqa: E402

# values under base ** exponent = 16
ISSUED = {1: [13], 3: [5, 15, 2]}
FAULTS = ("wf_response", "digit_commitment", "no_range_proof",
          "response_count", "digit_count", "undecodable")


@pytest.fixture(scope="module")
def pp():
    return setup(base=4, exponent=2, rng=random.Random(0xF75))


def make_issue(pp, values, anonymous, rng) -> IssueRow:
    tokens, witnesses = tok.tokens_with_witness(values, "USD", pp.ped_params, rng)
    proof = IssueProver(witnesses, tokens, anonymous, pp, rng).prove()
    return IssueRow(tokens, anonymous, proof)


def break_proof(raw: bytes, fault: str) -> bytes:
    if fault == "undecodable":
        return b"\x00not a proof"
    proof = IssueProof.from_bytes(raw)
    if fault == "no_range_proof":
        proof.range_correctness = None
    elif fault in ("wf_response", "response_count"):
        wf = IssueWF.from_bytes(proof.wf)
        if fault == "wf_response":
            wf.bfs[-1] = (wf.bfs[-1] + 1) % hm.R
        else:
            wf.values = wf.values[:-1]
        proof.wf = wf.to_bytes()
    else:
        rpf = RangeProof.from_bytes(proof.range_correctness)
        if fault == "digit_commitment":
            rpf.digit_commitments[-1][0] = hm.g1_add(
                rpf.digit_commitments[-1][0], hm.G1_GEN)
        else:  # the last output lacks a digit
            rpf.membership_proofs[-1] = rpf.membership_proofs[-1][:-1]
            rpf.digit_commitments[-1] = rpf.digit_commitments[-1][:-1]
        proof.range_correctness = rpf.to_bytes()
    return proof.to_bytes()


def break_issue(row: IssueRow, fault) -> IssueRow:
    return row._replace(proof=break_proof(row.proof, fault)) if fault else row


def scalar(pp, rows) -> list:
    """The reference's verdict a row, issue or transfer."""
    out = []
    for row in rows:
        if not isinstance(row, IssueRow):
            out += scalar_verdicts(pp, [row])
            continue
        try:
            IssueVerifier(row.outputs, row.anonymous, pp).verify(row.proof)
            out.append(True)
        except ValueError:
            out.append(False)
    return out


_CALL_COUNTERS = ("batch.transfer.calls", "batch.transfer.txs",
                  "batch.transfer.shapes", "batch.issue.records",
                  "batch.issue.outputs", "pairing.staged.calls")


def _moved(names, before=None):
    now = {n: mx.counter(n).value for n in names}
    return now if before is None else {n: now[n] - before[n] for n in names}


# ===================================================================
# the verifier against the scalar reference, row for row
# ===================================================================


@pytest.mark.parametrize("fault", (None,) + FAULTS)
@pytest.mark.parametrize("anonymous", [True, False], ids=["anon", "named"])
@pytest.mark.parametrize("n_out", sorted(ISSUED))
def test_an_issue_alone_in_a_call_equals_the_scalar_verifier(
    monkeypatch, pp, n_out, anonymous, fault
):
    hostplane.install(monkeypatch)
    rng = random.Random(f"alone/{n_out}/{anonymous}/{fault}")
    row = break_issue(make_issue(pp, ISSUED[n_out], anonymous, rng), fault)
    want = scalar(pp, [row])
    assert want == [fault is None]
    before = _moved(_CALL_COUNTERS)
    assert batch.BatchedTransferVerifier(pp).verify([row]).tolist() == want
    moved = _moved(_CALL_COUNTERS, before)
    assert moved["batch.transfer.calls"] == 1
    assert (moved["batch.issue.records"], moved["batch.issue.outputs"]) \
        == (1, n_out)
    # the transfers' counters keep to transfers
    assert (moved["batch.transfer.txs"], moved["batch.transfer.shapes"]) == (0, 0)


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("anonymous", [True, False], ids=["anon", "named"])
def test_a_faulty_issue_condemns_its_own_row_between_transfers_of_two_shapes(
    monkeypatch, pp, anonymous, fault
):
    """The broken issue (three outputs) between a `(1,2)` and a `(2,1)`
    transfer, a sound issue and a `(1,1)` behind them: only it is
    rejected, and the call is one."""
    hostplane.install(monkeypatch)
    rng = random.Random(f"between/{anonymous}/{fault}")
    rows = [make_row(pp, (1, 2), rng),
            break_issue(make_issue(pp, ISSUED[3], anonymous, rng), fault),
            make_row(pp, (2, 1), rng),
            make_issue(pp, ISSUED[1], not anonymous, rng),
            make_row(pp, (1, 1), rng)]
    want = scalar(pp, rows)
    assert want == [True, False, True, True, True]
    before = _moved(_CALL_COUNTERS)
    assert batch.BatchedTransferVerifier(pp).verify(rows).tolist() == want
    assert _moved(_CALL_COUNTERS, before) == {
        "batch.transfer.calls": 1, "batch.transfer.txs": 3,
        "batch.transfer.shapes": 3, "batch.issue.records": 2,
        "batch.issue.outputs": 4,
        # one pairing walk for the call's membership proofs of both kinds
        "pairing.staged.calls": 1}


@pytest.mark.parametrize(
    "fault", ["wf_response", "membership_proof", "range_length", "undecodable"])
def test_a_faulty_transfer_condemns_its_own_row_between_issues(
    monkeypatch, pp, fault
):
    hostplane.install(monkeypatch)
    rng = random.Random(f"transfer/{fault}")
    rows = [make_issue(pp, ISSUED[1], True, rng),
            break_row(make_row(pp, (2, 2), rng), fault),
            make_issue(pp, ISSUED[3], False, rng)]
    want = scalar(pp, rows)
    assert want == [True, False, True]
    assert batch.BatchedTransferVerifier(pp).verify(rows).tolist() == want


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_019])
def test_a_seeded_block_of_both_operations_equals_the_scalar_verifier(
    monkeypatch, pp, seed
):
    """Ten rows in the seed's order: six transfers of the six shapes, four
    issues (one and three outputs, anonymous and not); a seeded fault on
    two issues and one transfer."""
    hostplane.install(monkeypatch)
    rng = random.Random(f"block/{seed}")
    rows = [make_row(pp, shape, rng) for shape in FORMS]
    rows += [make_issue(pp, ISSUED[n], anon, rng)
             for n in (1, 3) for anon in (True, False)]
    rng.shuffle(rows)
    issues = [i for i, r in enumerate(rows) if isinstance(r, IssueRow)]
    broken = dict(zip(rng.sample(issues, 2), rng.sample(FAULTS, 2)))
    for i, fault in broken.items():
        rows[i] = break_issue(rows[i], fault)
    at = rng.choice([i for i in range(len(rows)) if i not in issues])
    rows[at] = break_row(rows[at], "wf_response")
    want = scalar(pp, rows)
    assert [i for i, ok in enumerate(want) if not ok] == sorted([*broken, at])
    assert batch.BatchedTransferVerifier(pp).verify(rows).tolist() == want


# ===================================================================
# the issue's rows ride the transfers' stage calls
# ===================================================================


def test_an_issue_s_rows_join_the_flat_rows_of_the_transfers_stage_calls(
    monkeypatch, pp
):
    """A `(2,2)` transfer and a three-output issue: the stage calls and
    the one pairing walk a call of transfers makes, in that order, each
    handed the rows of both (no call of the issue's own)."""
    hostplane.install(monkeypatch)
    rng = random.Random("flat")
    transfer, issue = make_row(pp, (2, 2), rng), make_issue(pp, ISSUED[3], True, rng)

    def recorded(rows):
        calls = hostplane.record(monkeypatch)
        assert batch.BatchedTransferVerifier(pp).verify(rows).all()
        return [(name, arrays[0].shape[0]) for name, arrays in calls]

    alone, both = recorded([transfer]), recorded([transfer, issue])
    assert [name for name, _ in both] == [name for name, _ in alone]
    e = pp.range_params.exponent
    rows_of = dict(wf=(2 + 2 + 2, 3), membership=(2 * e, 3 * e), equality=(2, 3))
    names = [name for name, _ in alone]
    walk = names.index("pairing_product_staged")
    for k, ((name, n_alone), (_, n_both)) in enumerate(zip(alone, both)):
        part = "wf" if k < 3 else "membership" if k <= walk else "equality"
        own, more = rows_of[part]
        # (the G2 term of a membership row is three scalar-mul rows)
        per = 3 if name == "g2_mul_rows" else 1
        assert (n_alone, n_both) == (per * own, per * (own + more)), (k, name)


def test_a_block_of_both_operations_dispatches_what_a_block_of_transfers_does(
    monkeypatch, pp
):
    """At the chip's tile heights: a tile a stage call, one Miller walk,
    one final exponentiation, with an issue among the rows or without."""
    hostplane.install(monkeypatch, chip=True)
    rng = random.Random("tiles")

    def dispatched(rows):
        def seen():
            return {prog: e["dispatches"] for (plane, prog), e
                    in devobs.snapshot().items() if plane == "verify"}

        before = seen()
        assert batch.BatchedTransferVerifier(pp).verify(rows).all()
        return {p: n - before.get(p, 0) for p, n in seen().items()
                if n - before.get(p, 0)}

    transfers = [make_row(pp, (2, 2), rng) for _ in range(3)]
    with_issue = dispatched(transfers + [make_issue(pp, ISSUED[1], False, rng)])
    assert with_issue == dispatched(transfers)
    assert sum(with_issue.values()) == 22
    assert (with_issue["miller_tile"], with_issue["fexp_tile"]) == (1, 1)


# ===================================================================
# the driver's plan and its tri-state verdict
# ===================================================================


def _action(pp, values, anonymous, issuer=b"issuer-1"):
    outcome = ZKATDLogDriver(pp).issue(
        issuer, "USD", values, [b"owner"] * len(values), anonymous,
        rng=random.Random(f"action/{values}/{anonymous}"))
    return outcome.action_bytes


@pytest.mark.parametrize("anonymous", [True, False], ids=["anon", "named"])
def test_issue_batch_plan_is_the_statement_validate_issue_reads(pp, anonymous):
    driver = ZKATDLogDriver(pp)
    action = _action(pp, [5, 9], anonymous)
    row = driver.issue_batch_plan(action)
    d = loads(action)
    assert isinstance(row, IssueRow)
    assert row.anonymous is anonymous and row.proof == d["proof"]
    assert len(row.outputs) == 2
    IssueVerifier(row.outputs, row.anonymous, pp).verify(row.proof)


@pytest.mark.parametrize("what", ["garbage", "no_outputs", "proof_not_bytes",
                                  "missing_key"])
def test_issue_batch_plan_leaves_what_it_cannot_read_to_the_host(pp, what):
    d = loads(_action(pp, [5], True))
    if what == "no_outputs":
        d["outputs"] = []
    elif what == "proof_not_bytes":
        d["proof"] = 7
    elif what == "missing_key":
        del d["anon"]
    raw = b"garbage" if what == "garbage" else dumps(d)
    assert ZKATDLogDriver(pp).issue_batch_plan(raw) is None


@pytest.mark.parametrize("verdict", [True, False, None])
def test_validate_issue_is_tri_state(pp, verdict):
    """True skips the scalar verifier, False rejects with a prefix of the
    scalar message, None is the host path; on a proof that is broken, so
    that the three differ."""
    from fabric_token_sdk_tpu.api.driver import ValidationError

    driver = ZKATDLogDriver(pp)
    d = loads(_action(pp, [5], True))
    d["proof"] = break_proof(d["proof"], "wf_response")
    action = dumps(d)
    if verdict is True:
        outputs, issuer = driver.validate_issue(action, proof_verified=True)
        assert (outputs, issuer) == (d["outputs"], b"")
        return
    with pytest.raises(ValidationError) as err:
        driver.validate_issue(action, proof_verified=verdict)
    assert str(err.value).startswith("invalid issue proof")
    assert (str(err.value) == "invalid issue proof") == (verdict is False)


@pytest.mark.parametrize("verdict", [True, False, None])
def test_authorisation_is_checked_on_the_host_whatever_the_verdict(pp, verdict):
    from fabric_token_sdk_tpu.api.driver import ValidationError

    driver = ZKATDLogDriver(setup(base=4, exponent=2, rng=random.Random(0xF75)))
    driver.pp.add_issuer(b"issuer-1")
    with pytest.raises(ValidationError, match="issuer is not authorized"):
        driver.validate_issue(_action(driver.pp, [5], False, b"rogue"),
                              proof_verified=verdict)
    named = loads(_action(driver.pp, [5], True))
    named["issuer"] = b"issuer-1"
    with pytest.raises(ValidationError, match="must not name an issuer"):
        driver.validate_issue(dumps(named), proof_verified=verdict)
    if verdict is not False:  # an authorised issuer's issue passes
        driver.validate_issue(_action(driver.pp, [5], False),
                              proof_verified=verdict)


# ===================================================================
# a served block: one plane call, the scalar validator's verdicts
# ===================================================================

_SERVED = ("batch.transfer.calls", "batch.transfer.txs", "batch.issue.records",
           "batch.issue.outputs", "ledger.validate.batched",
           "ledger.validate.host", "ledger.validate.issues_batched",
           "ledger.validate.issues_host", "ledger.block.batch_errors")


class Served:
    """A node behind a `LedgerServer`, its parties, and the scalar
    reference the same bytes go through, one request a block."""

    def __init__(self, pp, held=(8, 5, 9, 6, 4)):
        def driver():
            return ZKATDLogDriver(pp)

        policy = BlockPolicy(max_block_txs=16, min_batch=2)
        self.network, self.parties, _, self.alice, self.bob = build_env(
            driver, policy)
        self.issuer_p = self.parties["issuer-node"]
        self.alice_p = self.parties["alice-node"]
        seed = issue_to(self.parties, self.alice, held, "seed")
        self.reference = Network(
            RequestValidator(driver()),
            policy=BlockPolicy(max_block_txs=1, use_batched=False,
                               sign_batched=False, pipeline=False))
        assert self.reference.submit(
            seed.request.to_bytes()).status.value == "Valid"
        self.server = LedgerServer(network=self.network).start()
        self.client = RemoteNetwork(self.server.address)
        self.by_value = {}
        for tid in self.alice_p.vault.token_ids():
            value = int(self.alice_p.vault.get(tid).decoded.quantity)
            self.by_value.setdefault(value, []).append(tid)

    def close(self):
        self.client.close()
        self.server.stop()

    def transfer(self, anchor, in_values, out_values, redeem=False):
        """alice pays bob `out_values`; a redeem's first output has no
        owner, the rest is her change."""
        spend = [self.by_value[v].pop() for v in in_values]
        req = self.alice_p.tms.new_request(anchor)
        tokens, metas = self.alice_p.vault.get_many(spend)
        if redeem:
            self.alice_p.tms.add_redeem(
                req, spend, tokens, metas, "USD", out_values[0],
                sum(out_values[1:]), self.alice.recipient_identity())
        else:
            self.alice_p.tms.add_transfer(
                req, spend, tokens, metas, "USD", out_values,
                [self.bob.recipient_identity()] * len(out_values))
        self.alice_p.tms.sign_transfers(req)
        return req.to_bytes()

    def issue(self, anchor, values, wallet="issuer", tamper=None):
        tx = Transaction(self.issuer_p, anchor)
        tx.issue(wallet, "USD", values,
                 [self.alice.recipient_identity()] * len(values),
                 anonymous=False)
        if tamper:
            action = loads(tx.request.issues[0].action)
            action["proof"] = break_proof(action["proof"], tamper)
            tx.request.issues[0].action = dumps(action)
        tx.collect_endorsements(None)
        return tx.request.to_bytes()

    def submit(self, blobs):
        """One `submit_many` over the wire -> (events, the reference's, what
        moved)."""
        before = _moved(_SERVED)
        events = self.client.submit_many(blobs)
        moved = _moved(_SERVED, before)
        return events, [self.reference.submit(b) for b in blobs], moved


@pytest.fixture
def served(monkeypatch, pp):
    hostplane.install(monkeypatch)
    node = Served(pp)
    yield node
    node.close()


def _agree(events, ref):
    assert [e.status for e in events] == [r.status for r in ref]
    for e, r in zip(events, ref):
        # a plane's rejection agrees with the scalar message by its head
        assert (e.message or "").split(": ")[0] == (r.message or "").split(": ")[0]


def test_served_block_of_three_operations_is_one_call_with_the_scalar_verdicts(
    monkeypatch, served,
):
    """Transfers of two shapes, a top-up and a cash-out in one hand-over:
    one plane call, every proof the device's; the issued token is spent in
    a later block."""
    spans, span = [], mx.span
    monkeypatch.setattr(mx, "span", lambda name, **attrs: (
        spans.append((name, attrs)), span(name, **attrs))[1])
    blobs = [served.transfer("pay-0", [8, 5], [10, 3]),
             served.issue("topup", [13]),
             served.transfer("pay-1", [9], [6, 3]),
             served.transfer("cashout", [6, 4], [7, 3], redeem=True)]
    events, ref, moved = served.submit(blobs)
    _agree(events, ref)
    assert [e.status.value for e in events] == ["Valid"] * 4
    assert moved == {
        "batch.transfer.calls": 1, "batch.transfer.txs": 3,
        "batch.issue.records": 1, "batch.issue.outputs": 1,
        "ledger.validate.batched": 3, "ledger.validate.host": 0,
        "ledger.validate.issues_batched": 1, "ledger.validate.issues_host": 0,
        "ledger.block.batch_errors": 0}
    tail = mx.FLIGHT.tail()
    device = [e for e in tail if e["kind"] == "verify.device"][-1]
    assert (device["txs"], device["issues"], device["shapes"], device["ok"]) \
        == (3, 1, 2, 4)
    commit = [e for e in tail if e["kind"] == "block.commit"
              and len(e["txs"]) == 4][-1]
    assert commit["verify_calls"] == 1
    assert [attrs for name, attrs in spans
            if name == "ledger.block.batch_verify"] == [
        {"shapes": 2, "txs": 3, "issues": 1}]
    # the redeemed output is nobody's, the issued one alice's to spend
    minted = [tid for tid in served.alice_p.vault.token_ids()
              if tid.tx_id == "topup"]
    assert len(minted) == 1
    served.by_value[13] = minted
    events, ref, moved = served.submit(
        [served.transfer("spend-topup", [13], [12, 1])])
    _agree(events, ref)
    assert events[0].status.value == "Valid"
    assert moved["batch.transfer.calls"] == 0  # alone: the host's


def test_a_lone_issue_goes_to_the_host_whole(served):
    """`min_batch` counts a block's planned records of both kinds: one
    issue is under it (no plane call, no fallback); an issue and one
    transfer make two."""
    events, ref, moved = served.submit([served.issue("alone", [13, 2])])
    _agree(events, ref)
    assert events[0].status.value == "Valid"
    assert moved == dict.fromkeys(_SERVED, 0) | {
        "ledger.validate.issues_host": 1}
    events, ref, moved = served.submit(
        [served.issue("two-0", [7]), served.transfer("two-1", [9], [6, 3])])
    _agree(events, ref)
    assert (moved["batch.transfer.calls"], moved["batch.transfer.txs"],
            moved["ledger.validate.issues_batched"],
            moved["ledger.validate.batched"]) == (1, 1, 1, 1)


@pytest.mark.parametrize("fault", ["wf_response", "digit_commitment",
                                   "no_range_proof", "undecodable"])
def test_a_device_verdict_false_on_an_authorised_issue_is_invalid(
    served, fault
):
    blobs = [served.transfer("pay", [8, 5], [10, 3]),
             served.issue("forged", [13], tamper=fault),
             served.issue("sound", [2])]
    events, ref, moved = served.submit(blobs)
    _agree(events, ref)
    assert [e.status.value for e in events] == ["Valid", "Invalid", "Valid"]
    assert events[1].message == "invalid issue proof"
    assert ref[1].message.startswith("invalid issue proof: ")
    assert (moved["batch.transfer.calls"],
            moved["ledger.validate.issues_batched"]) == (1, 2)


def test_an_unauthorised_issuer_with_a_valid_proof_is_invalid(served):
    """The device's verdict on the proof is True; who may issue is the
    host's to say, for every issue."""
    served.issuer_p.new_issuer_wallet("rogue")
    blobs = [served.issue("rogue-topup", [13], wallet="rogue"),
             served.transfer("pay", [8, 5], [10, 3])]
    events, ref, moved = served.submit(blobs)
    _agree(events, ref)
    assert [e.status.value for e in events] == ["Invalid", "Valid"]
    assert events[0].message == ref[0].message == "issuer is not authorized"
    assert moved["ledger.validate.issues_batched"] == 1
    device = [e for e in mx.FLIGHT.tail() if e["kind"] == "verify.device"][-1]
    assert (device["issues"], device["ok"]) == (1, 2)


def test_under_a_batch_verify_fault_the_block_falls_to_the_host(served):
    """The degrade chain is the transfers': every row, issues included,
    is verified by the host, with the same verdicts."""
    blobs = [served.transfer("pay", [8, 5], [10, 3]),
             served.issue("topup", [13]),
             served.issue("forged", [2], tamper="wf_response"),
             served.transfer("cashout", [6, 4], [7, 3], redeem=True)]
    faults.arm("batch.verify", "error", count=1)
    try:
        events, ref, moved = served.submit(blobs)
    finally:
        faults.clear()
    _agree(events, ref)
    assert [e.status.value for e in events] == [
        "Valid", "Valid", "Invalid", "Valid"]
    assert events[2].message == ref[2].message  # the scalar path's own words
    assert moved == dict.fromkeys(_SERVED, 0) | {
        "ledger.block.batch_errors": 1, "ledger.validate.host": 2,
        "ledger.validate.issues_host": 2}
    fallback = [e for e in mx.FLIGHT.tail()
                if e["kind"] == "verify.host_fallback"][-1]
    assert (fallback["txs"], fallback["issues"]) == (2, 2)


def test_a_direct_caller_without_a_receiver_plans_no_issue(monkeypatch, pp):
    """`proof_verdicts` without `issue_verdicts`: the issues are the
    host's, as before."""
    from types import SimpleNamespace

    from fabric_token_sdk_tpu.services.network.orderer import (
        BlockValidationPipeline,
    )

    hostplane.install(monkeypatch)
    rng = random.Random("direct")
    driver = ZKATDLogDriver(pp)
    issue = SimpleNamespace(action=_action(pp, [5], True))
    ins, outs, proof = make_row(pp, (1, 1), rng)
    requests = [SimpleNamespace(issues=[issue], transfers=[]),
                SimpleNamespace(issues=[issue], transfers=[])]
    pipeline = BlockValidationPipeline(
        SimpleNamespace(driver=driver), BlockPolicy(min_batch=2))
    calls = mx.counter("batch.transfer.calls").value
    assert pipeline.proof_verdicts(requests) == {}
    assert mx.counter("batch.transfer.calls").value == calls
    got = {}
    assert pipeline.proof_verdicts(requests, issue_verdicts=got) == {}
    assert got == {0: {0: True}, 1: {0: True}}
    assert mx.counter("batch.transfer.calls").value == calls + 1


# ===================================================================
# the real programs
# ===================================================================


@pytest.mark.slow
def test_a_call_of_both_operations_through_the_real_programs(pp):
    """No stand-in: the stage tiles and the three pairing programs of the
    CPU backend (minutes to compile where the cache is cold)."""
    rng = random.Random("real")
    rows = [make_row(pp, (1, 2), rng), make_issue(pp, ISSUED[1], False, rng),
            break_issue(make_issue(pp, ISSUED[3], True, rng), "digit_commitment"),
            make_row(pp, (1, 1), rng)]
    want = scalar(pp, rows)
    assert want == [True, True, False, True]
    assert batch.BatchedTransferVerifier(pp).verify(rows).tolist() == want
