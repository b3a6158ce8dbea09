"""Rows of any shape `(n_in, n_out)` in ONE proof-plane call.

The plain reference for a row is the scalar `crypto/transfer.
TransferVerifier` (one transfer at a time, `hostmath`); for a served
block the scalar `RequestValidator` with `use_batched=False`, one request
a block: the benchmark judge's own reference. Seeded blocks mix `(1,1)`,
`(1,2)`, `(2,1)`, `(2,2)`, `(3,2)` and `(8,1)` in seeded orders, with a
tampered well-formedness response, a tampered membership proof, a range
proof of the wrong length and undecodable proof bytes at seeded places.

Every tier-1 case runs the verifier's own glue and the walks' own
padding over exact host stand-ins for the tile kernels
(`tests/hostplane.py`): real verdicts, no compile. One `slow` case runs
a mixed call through the real programs.

`tests/data/one_shape_calls.json` holds what a call of ONE shape hands
the stage functions and the pairing walk: `python
tests/test_mixed_shapes.py --record` prints it. Recorded first on the
parent of PR 37 (the per-shape code); again in PR 39 for the two legs a
membership row has since (`tests/test_membership_legs.py`), where the
well-formedness calls, the membership verifier's G1 commitment calls and
the equality calls stayed the parent's bit for bit.
"""
import json
import os
import random
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import hostplane  # noqa: E402

from fabric_token_sdk_tpu.api.validator import RequestValidator  # noqa: E402
from fabric_token_sdk_tpu.crypto import batch, hostmath as hm, token as tok  # noqa: E402
from fabric_token_sdk_tpu.crypto.rangeproof import RangeProof  # noqa: E402
from fabric_token_sdk_tpu.crypto.setup import setup  # noqa: E402
from fabric_token_sdk_tpu.crypto.transfer import (  # noqa: E402
    TransferProof, TransferProver, TransferVerifier,
)
from fabric_token_sdk_tpu.crypto.wellformedness import TransferWF  # noqa: E402
from fabric_token_sdk_tpu.drivers.zkatdlog import ZKATDLogDriver  # noqa: E402
from fabric_token_sdk_tpu.services.network import BlockPolicy, Network  # noqa: E402
from fabric_token_sdk_tpu.utils import devobs, metrics as mx  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "one_shape_calls.json")

# values under base ** exponent = 16; every form conserves
FORMS = {
    (1, 1): ([7], [7]),
    (1, 2): ([9], [6, 3]),
    (2, 1): ([5, 4], [9]),
    (2, 2): ([8, 5], [10, 3]),
    (3, 2): ([6, 5, 3], [11, 3]),
    (8, 1): ([1] * 8, [8]),
}
FAULTS = ("wf_response", "membership_proof", "range_length", "undecodable")


def _pp():
    return setup(base=4, exponent=2, rng=random.Random(0xF75))


@pytest.fixture(scope="module")
def pp():
    return _pp()


def make_row(pp, shape, rng):
    """-> (inputs, outputs, proof bytes): one transfer of `shape`."""
    in_values, out_values = FORMS[shape]
    ins, in_w = tok.tokens_with_witness(in_values, "USD", pp.ped_params, rng)
    outs, out_w = tok.tokens_with_witness(out_values, "USD", pp.ped_params, rng)
    return ins, outs, TransferProver(in_w, out_w, ins, outs, pp, rng).prove()


def break_row(row, fault):
    ins, outs, raw = row
    if fault == "undecodable":
        return ins, outs, b"\x00not a proof"
    proof = TransferProof.from_bytes(raw)
    if fault == "wf_response":
        wf = TransferWF.from_bytes(proof.wf)
        wf.sum_resp = (wf.sum_resp + 1) % hm.R
        proof.wf = wf.to_bytes()
    else:
        rpf = RangeProof.from_bytes(proof.range_correctness)
        if fault == "membership_proof":
            m = rpf.membership_proofs[-1][0]
            m.value_resp = (m.value_resp + 1) % hm.R
        else:  # the last output's digits are missing
            rpf.membership_proofs = rpf.membership_proofs[:-1]
            rpf.digit_commitments = rpf.digit_commitments[:-1]
        proof.range_correctness = rpf.to_bytes()
    return ins, outs, proof.to_bytes()


def scalar_verdicts(pp, rows):
    out = []
    for ins, outs, raw in rows:
        try:
            TransferVerifier(ins, outs, pp).verify(raw)
            out.append(True)
        except ValueError:
            out.append(False)
    return out


def seeded_block(pp, seed, order):
    """Eight rows: the six shapes and two more the seed picks, in the
    seed's order of kind `order`, the four faults at seeded places (the
    two of the range proof on rows that carry one). -> (rows, faults by
    index)"""
    rng = random.Random(f"{seed}/{order}")
    shapes = list(FORMS) + [rng.choice(list(FORMS)) for _ in range(2)]
    if order == "shuffled":
        rng.shuffle(shapes)
    elif order == "many_inputs_first":
        shapes.sort(key=lambda s: (-s[0], s[1]))
    else:
        raise ValueError(order)
    rows = [make_row(pp, s, rng) for s in shapes]
    ranged = [i for i, s in enumerate(shapes) if s != (1, 1)]
    places = {}
    for fault in FAULTS:
        pool = ranged if fault in ("membership_proof", "range_length") \
            else range(len(rows))
        places[rng.choice([i for i in pool if i not in places])] = fault
    for i, fault in places.items():
        rows[i] = break_row(rows[i], fault)
    return shapes, rows, places


# ===================================================================
# the verifier against the scalar reference, row for row
# ===================================================================


@pytest.mark.parametrize("order", ["shuffled", "many_inputs_first"])
@pytest.mark.parametrize("seed", [1, 2, 3_000_000_019])
def test_mixed_call_equals_the_scalar_verifier_row_for_row(
    monkeypatch, pp, seed, order
):
    hostplane.install(monkeypatch)
    shapes, rows, places = seeded_block(pp, seed, order)
    want = scalar_verdicts(pp, rows)
    # the scalar reference rejects exactly the seeded faults
    assert [i for i, ok in enumerate(want) if not ok] == sorted(places)
    calls = mx.counter("batch.transfer.calls").value
    distinct = mx.counter("batch.transfer.shapes").value
    txs = mx.counter("batch.transfer.txs").value
    staged = mx.counter("pairing.staged.calls").value
    got = batch.BatchedTransferVerifier(pp).verify(rows)
    assert got.tolist() == want, (shapes, places)
    assert mx.counter("batch.transfer.calls").value - calls == 1
    assert mx.counter("batch.transfer.shapes").value - distinct == len(set(shapes))
    assert mx.counter("batch.transfer.txs").value - txs == 8
    # one pairing walk for the whole call's membership proofs
    assert mx.counter("pairing.staged.calls").value - staged == 1


@pytest.mark.parametrize("shape", [(1, 1), (2, 1), (3, 2)])
def test_a_fault_condemns_its_own_row_whatever_its_neighbours(
    monkeypatch, pp, shape
):
    """Each fault on a row of `shape` between rows of two other shapes:
    only that row is rejected."""
    hostplane.install(monkeypatch)
    rng = random.Random(f"neighbours/{shape}")
    faults = [f for f in FAULTS
              if shape != (1, 1) or f in ("wf_response", "undecodable")]
    rows = []
    for fault in faults:
        rows += [make_row(pp, (1, 2), rng),
                 break_row(make_row(pp, shape, rng), fault),
                 make_row(pp, (1, 1), rng)]
    want = scalar_verdicts(pp, rows)
    assert want == [True, False, True] * len(faults)
    assert batch.BatchedTransferVerifier(pp).verify(rows).tolist() == want


def test_a_one_in_one_out_row_brings_no_range_rows(monkeypatch, pp):
    """`transfer.go:55-59`, per transaction and not per call: beside a
    `(1,2)` row a `(1,1)` row is judged by its well-formedness alone (the
    scalar verifier never reads its range proof either), and the call's
    membership rows are the `(1,2)` row's."""
    hostplane.install(monkeypatch)
    rng = random.Random("skip")
    one = make_row(pp, (1, 1), rng)
    proof = TransferProof.from_bytes(one[2])
    proof.range_correctness = b"\x00ignored"
    one = (one[0], one[1], proof.to_bytes())
    rows = [one, make_row(pp, (1, 2), rng)]
    assert scalar_verdicts(pp, rows) == [True, True]
    before = mx.counter("batch.membership.proofs").value
    assert batch.BatchedTransferVerifier(pp).verify(rows).tolist() == [True, True]
    assert mx.counter("batch.membership.proofs").value - before \
        == 2 * pp.range_params.exponent


# ===================================================================
# a call of one shape hands over what the per-shape code handed over
# ===================================================================

ONE_SHAPE_CALLS = {
    "three_1_1": [((1, 1), None)] * 3,
    "three_2_2_two_broken": [((2, 2), None), ((2, 2), "undecodable"),
                             ((2, 2), "range_length")],
    "two_3_2": [((3, 2), None), ((3, 2), "wf_response")],
    "two_8_1": [((8, 1), None)] * 2,
}


def one_shape_call(pp, name, monkeypatch):
    """The seeded call `name` through the verifier -> (digest of what
    every `st.*_rows` and the pairing walk were handed, verdicts)."""
    rng = random.Random(f"one-shape/{name}")
    rows = []
    for shape, fault in ONE_SHAPE_CALLS[name]:
        row = make_row(pp, shape, rng)
        rows.append(break_row(row, fault) if fault else row)
    calls = hostplane.record(monkeypatch)
    ok = batch.BatchedTransferVerifier(pp).verify(rows)
    return hostplane.digest(calls), ok.tolist()


@pytest.mark.parametrize("name", sorted(ONE_SHAPE_CALLS))
def test_one_shape_call_hands_over_the_recorded_arrays(monkeypatch, pp, name):
    """Bit for bit, call for call, in the same order: the existing cells,
    whose blocks hold one shape, make the dispatches they made."""
    hostplane.install(monkeypatch)
    with open(RECORDED) as fh:
        recorded = json.load(fh)[name]
    digest, ok = one_shape_call(pp, name, monkeypatch)
    assert ok == recorded["verdicts"]
    assert [c[:2] for c in digest] == [c[:2] for c in recorded["calls"]]
    assert digest == recorded["calls"]


# ===================================================================
# a served block: one plane call, the scalar validator's verdicts
# ===================================================================

# ISSUE 37's sixth hand-over: five shapes in eight requests
HANDOVER = [(1, 2)] * 3 + [(2, 2)] * 2 + [(3, 2), (1, 1), (2, 1)]


_COUNTERS = ("batch.transfer.calls", "batch.transfer.shapes",
             "batch.transfer.txs", "ledger.validate.batched",
             "ledger.validate.host")


def _seen():
    """The counters above and the verify plane's dispatches a program."""
    return ({n: mx.counter(n).value for n in _COUNTERS},
            {prog: e["dispatches"] for (plane, prog), e
             in devobs.snapshot().items() if plane == "verify"})


def serve_block(pp, shapes, tamper=(), policy=None):
    """One `submit_many` of transfers of `shapes` (those at `tamper` with
    a broken well-formedness response, re-signed) on a fresh network. ->
    (events, the same bytes through the scalar reference, one request a
    block, what the block moved: counters, dispatches a program)"""
    from test_orderer import build_env, issue_to
    from fabric_token_sdk_tpu.crypto.serialization import dumps, loads

    def driver():
        return ZKATDLogDriver(pp)

    policy = policy or BlockPolicy(max_block_txs=16, min_batch=2)
    network, parties, issuer, alice, bob = build_env(driver, policy)
    alice_p = parties["alice-node"]
    values = [v for s in shapes for v in FORMS[s][0]]
    seed = issue_to(parties, alice, values, "mix-seed")
    by_value = {}
    for tid in alice_p.vault.token_ids():
        value = int(alice_p.vault.get(tid).decoded.quantity)
        by_value.setdefault(value, []).append(tid)
    blobs = []
    for k, shape in enumerate(shapes):
        in_values, out_values = FORMS[shape]
        spend = [by_value[v].pop() for v in in_values]
        req = alice_p.tms.new_request(f"mix-{k}")
        tokens, metas = alice_p.vault.get_many(spend)
        alice_p.tms.add_transfer(
            req, spend, tokens, metas, "USD", out_values,
            [bob.recipient_identity()] * len(out_values))
        if k in tamper:
            action = loads(req.transfers[0].action)
            action["proof"] = break_row(
                (None, None, action["proof"]), "wf_response")[2]
            req.transfers[0].action = dumps(action)
        alice_p.tms.sign_transfers(req)
        blobs.append(req.to_bytes())
    counters, dispatches = _seen()
    events = network.submit_many(blobs)
    counters_after, dispatches_after = _seen()
    moved = {
        "counters": {n: counters_after[n] - counters[n] for n in _COUNTERS},
        "dispatches": {prog: n - dispatches.get(prog, 0)
                       for prog, n in dispatches_after.items()
                       if n - dispatches.get(prog, 0)},
    }
    reference = Network(
        RequestValidator(driver()),
        policy=BlockPolicy(max_block_txs=1, use_batched=False,
                           sign_batched=False, pipeline=False))
    assert reference.submit(seed.request.to_bytes()).status.value == "Valid"
    return events, [reference.submit(b) for b in blobs], moved


def test_served_mixed_block_is_one_call_with_the_scalar_verdicts(
    monkeypatch, pp
):
    """The hand-over of eight over five shapes, two of them tampered:
    statuses and messages equal the scalar `RequestValidator`'s, one
    `BatchedTransferVerifier.verify` call, and at the chip's tile heights
    the dispatches of a `(2,2)` block of eight (as many tiles: one a
    stage call, one Miller, one final-exp)."""
    hostplane.install(monkeypatch, chip=True)
    events, ref, mixed = serve_block(pp, HANDOVER, tamper=(4, 7))
    assert mixed["counters"] == {
        "batch.transfer.calls": 1, "batch.transfer.shapes": 5,
        "batch.transfer.txs": 8, "ledger.validate.batched": 8,
        "ledger.validate.host": 0}
    assert [e.status for e in events] == [r.status for r in ref]
    assert [e.status.value for e in events] == [
        "Invalid" if k in (4, 7) else "Valid" for k in range(8)]
    for e, r in zip(events, ref):
        # a plane's rejection agrees with the scalar message by its head
        assert (e.message or "").split(": ")[0] == (r.message or "").split(": ")[0]
    tail = mx.FLIGHT.tail()
    device = [e for e in tail if e["kind"] == "verify.device"][-1]
    assert (device["shapes"], device["txs"], device["ok"]) == (5, 8, 6)
    commit = [e for e in tail if e["kind"] == "block.commit"
              and len(e["txs"]) == 8][-1]
    assert commit["verify_calls"] == 1

    events, ref, uniform = serve_block(pp, [(2, 2)] * 8)
    assert all(e.status.value == "Valid" for e in events)
    assert uniform["counters"]["batch.transfer.shapes"] == 1
    assert mixed["dispatches"] == uniform["dispatches"]
    assert mixed["dispatches"]["miller_tile"] == 1
    assert mixed["dispatches"]["fexp_tile"] == 1
    # 20 stage calls (18 + the membership rows' `g1_sub` and second
    # `g2_add` since its legs are two), one Miller walk, one final
    # exponentiation: a tile each
    assert sum(mixed["dispatches"].values()) == 22
    assert mixed["dispatches"]["g1_to_affine_tile"] == 1


def test_a_block_under_min_batch_goes_to_the_host_whole(monkeypatch, pp):
    """`min_batch` counts a block's transfer rows: a lone `(1,2)` is the
    host's (no plane call, no fallback), two rows of two shapes ride."""
    hostplane.install(monkeypatch)
    fallbacks = mx.counter("ledger.block.batch_errors").value
    events, ref, moved = serve_block(pp, [(1, 2)])
    assert [e.status for e in events] == [r.status for r in ref]
    assert moved["counters"]["batch.transfer.calls"] == 0
    assert moved["counters"]["ledger.validate.host"] == 1
    assert moved["dispatches"] == {}
    commit = [e for e in mx.FLIGHT.tail() if e["kind"] == "block.commit"
              and e["txs"] == ["mix-0"]][-1]
    assert commit["verify_calls"] == 0
    events, ref, moved = serve_block(pp, [(1, 2), (2, 1)])
    assert [e.status for e in events] == [r.status for r in ref]
    assert moved["counters"] == {
        "batch.transfer.calls": 1, "batch.transfer.shapes": 2,
        "batch.transfer.txs": 2, "ledger.validate.batched": 2,
        "ledger.validate.host": 0}
    assert mx.counter("ledger.block.batch_errors").value == fallbacks


@pytest.mark.slow
def test_mixed_call_through_the_real_programs(pp):
    """No stand-in: the stage tiles and the three pairing programs of the
    CPU backend (minutes to compile where the cache is cold)."""
    rng = random.Random("real")
    rows = [make_row(pp, (1, 1), rng), make_row(pp, (1, 2), rng),
            break_row(make_row(pp, (2, 1), rng), "membership_proof"),
            make_row(pp, (2, 1), rng)]
    want = scalar_verdicts(pp, rows)
    assert want == [True, True, False, True]
    assert batch.BatchedTransferVerifier(pp).verify(rows).tolist() == want


if __name__ == "__main__":
    if sys.argv[1:] != ["--record"]:
        sys.exit("usage: python tests/test_mixed_shapes.py --record")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    class _Patch:
        """`monkeypatch.setattr` for a script: nothing is restored."""

        @staticmethod
        def setattr(owner, name, value):
            setattr(owner, name, value)

    params = _pp()
    hostplane.install(_Patch)
    recorded = {}
    for call in sorted(ONE_SHAPE_CALLS):
        calls, verdicts = one_shape_call(params, call, _Patch)
        recorded[call] = {"verdicts": verdicts, "calls": calls}
    json.dump(recorded, sys.stdout, indent=1)
    print()
