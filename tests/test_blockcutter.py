"""The orderer's block cutter against its plain reference.

`Orderer._order` is Fabric's `blockcutter.Ordered()` plus the batch timer
(module docstring of `services/network/orderer.py`); the reference is
`benchmark/reference/fabric_blockcutter.py`, a pure function that imports
nothing of the package. Host only: no device plane, no pairing compile.
"""
import os
import random
import sys
import threading
import time

import pytest

from fabric_token_sdk_tpu.api.request import TokenRequest
from fabric_token_sdk_tpu.api.validator import RequestValidator
from fabric_token_sdk_tpu.drivers.fabtoken import (
    FabTokenDriver,
    FabTokenPublicParams,
)
from fabric_token_sdk_tpu.services.network import (
    BlockPolicy,
    FinalityEvent,
    MessageTooLarge,
    Network,
    Orderer,
    TxStatus,
)
from fabric_token_sdk_tpu.services.network import orderer as orderer_mod
from fabric_token_sdk_tpu.services.network.remote import (
    LedgerServer,
    RemoteNetwork,
)
from fabric_token_sdk_tpu.services.ttx import Party, Transaction
from fabric_token_sdk_tpu.utils import metrics as mx

sys.path.insert(0, os.path.join(
    os.path.dirname(__file__), "..", "benchmark", "reference"))

import fabric_blockcutter  # noqa: E402

CUT_COUNTERS = ("orderer.cut.blocks", "orderer.cut.bytes",
                "orderer.cut.by_count", "orderer.cut.by_bytes",
                "orderer.cut.by_timeout", "orderer.cut.oversize",
                "orderer.reject.too_large")


def _counters():
    return {c: mx.REGISTRY.counter(c).value for c in CUT_COUNTERS}


def _resolve_valid(batch):
    for s in batch:
        s._resolve(FinalityEvent(s.request.anchor, TxStatus.VALID))


class Harness:
    """An `Orderer` on a fake clock whose commit callback records each
    block's anchors, with the `block.cut` flight events it emitted."""

    def __init__(self, policy, monkeypatch):
        self.now = time.monotonic() + 3600.0  # ahead of the real clock
        self.blocks, self.cuts = [], []
        self.orderer = Orderer(self._commit, policy, clock=lambda: self.now)
        flight = mx.flight

        def spy(kind, *a, **kw):
            if kind == "block.cut":
                self.cuts.append(dict(kw))
            return flight(kind, *a, **kw)

        monkeypatch.setattr(orderer_mod.mx, "flight", spy)

    def _commit(self, batch):
        self.blocks.append([s.request.anchor for s in batch])
        _resolve_valid(batch)


def _random_case(seed):
    """Sizes on a grid of 100 against PreferredMaxBytes 1000 (sums that hit
    it exactly, messages over it, messages over AbsoluteMaxBytes), arrival
    gaps that put messages on, just before and well after a timer's expiry,
    bursts at one instant, and a count limit that bites."""
    rng = random.Random(seed)
    timeout = rng.choice([0.5, 1.0, 2.0])
    policy = BlockPolicy(
        linger_s=timeout, max_block_txs=rng.choice([2, 3, 4, 6, 10]),
        preferred_max_bytes=rng.choice([1000, 1000, 600, 0]),
        absolute_max_bytes=rng.choice([2000, 2000, 0]), pipeline=False)
    n = rng.randrange(12, 40)
    sizes = [rng.choice([100, 100, 200, 300, 400, 500, 500, 1000, 1200, 2500])
             for _ in range(n)]
    gaps = [rng.choice([0.0, 0.0, 0.0, 0.1, 0.3, timeout / 2, timeout,
                        timeout, 1.5 * timeout, 3 * timeout])
            for _ in range(n)]
    return policy, sizes, gaps


@pytest.mark.parametrize("seed", range(200))
def test_cuts_equal_the_plain_reference(seed, monkeypatch):
    policy, sizes, gaps = _random_case(seed)
    h = Harness(policy, monkeypatch)
    before = _counters()
    arrivals, refused = [], []
    for i, (size, gap) in enumerate(zip(sizes, gaps)):
        h.now += gap
        arrivals.append(h.now)
        try:
            h.orderer.enqueue(TokenRequest(anchor=f"m{i}"), size)
        except MessageTooLarge:
            refused.append(i)
        # a driver comes by: it gets what the rules have cut, never the
        # open batch before its timer (no waiting: the clock is the test's)
        h.orderer.flush()
    h.now += 10 * policy.linger_s
    h.orderer.flush()
    assert h.orderer.pending() == 0 and h.orderer.inflight() == 0

    want = fabric_blockcutter.cut(
        sizes, arrivals, policy.linger_s, policy.max_block_txs,
        policy.preferred_max_bytes, policy.absolute_max_bytes)
    assert h.blocks == [[f"m{i}" for i in idx] for idx, _r, _t in want]
    assert [c["reason"] for c in h.cuts] == [r for _i, r, _t in want]
    assert [c["txs"] for c in h.cuts] == [len(i) for i, _r, _t in want]
    assert [c["bytes"] for c in h.cuts] == [
        sum(sizes[i] for i in idx) for idx, _r, _t in want]
    for c, (idx, reason, at) in zip(h.cuts, want):
        # first message -> cut; a timer's cut is dated when it ran out
        assert c["waited_s"] == pytest.approx(at - arrivals[idx[0]], abs=2e-6)
    ordered = {i for idx, _r, _t in want for i in idx}
    amax = policy.absolute_max_bytes
    assert refused == [i for i, s in enumerate(sizes) if 0 < amax < s]
    assert ordered == set(range(len(sizes))) - set(refused)
    # the guarantee the configuration states
    pref = policy.preferred_max_bytes
    for idx, _r, _t in want:
        assert len(idx) <= policy.max_block_txs
        assert len(idx) == 1 or not pref or sum(sizes[i] for i in idx) <= pref
    moved = {c: v - before[c] for c, v in _counters().items()}
    reasons = [r for _i, r, _t in want]
    assert moved == {
        "orderer.cut.blocks": len(want),
        "orderer.cut.bytes": sum(sizes[i] for i in ordered),
        "orderer.cut.by_count": reasons.count("count"),
        "orderer.cut.by_bytes": reasons.count("bytes"),
        "orderer.cut.by_timeout": reasons.count("timeout"),
        "orderer.cut.oversize": reasons.count("oversize"),
        "orderer.reject.too_large": len(refused),
    }


def test_the_reference_on_the_cases_the_issue_names():
    cut = fabric_blockcutter.cut
    kb = 166_900
    # nine 167 KB transfers at once on the test network's channel: three
    # blocks of three, the third closed by the timer
    assert cut([kb] * 9, [5.0] * 9, 2.0, 10, 524288, 103809024) == [
        ((0, 1, 2), "bytes", 5.0), ((3, 4, 5), "bytes", 5.0),
        ((6, 7, 8), "timeout", 7.0)]
    # a lone arrival waits BatchTimeout; one that arrives on the expiry
    # finds the batch cut
    assert cut([kb, kb], [1.0, 3.0], 2.0, 10, 524288) == [
        ((0,), "timeout", 3.0), ((1,), "timeout", 5.0)]
    # a sum exactly equal to PreferredMaxBytes stays one batch
    assert cut([400, 600, 1], [0, 0, 0], 1.0, 10, 1000) == [
        ((0, 1), "bytes", 0), ((2,), "timeout", 1.0)]
    # an oversized message cuts the pending batch and goes alone; the timer
    # restarts with the next message
    assert cut([100, 1500, 100], [0.0, 0.5, 0.9], 1.0, 10, 1000) == [
        ((0,), "bytes", 0.5), ((1,), "oversize", 0.5), ((2,), "timeout", 1.9)]
    # the count limit does not wait for the timer; over AbsoluteMaxBytes is
    # refused and appears nowhere
    assert cut([1, 1, 9999, 1], [0, 0, 0, 0], 1.0, 2, 1000, 5000) == [
        ((0, 1), "count", 0), ((3,), "timeout", 1.0)]
    with pytest.raises(ValueError):
        cut([1, 1], [1.0, 0.5], 1.0, 10)


# ---- BlockPolicy() defaults: the rules off, the blocks the parent cut


def _parent_blocks(ops, max_block_txs):
    """What the orderer cut before it had rules: a driver pops
    min(pending, max_block_txs) messages; `flush` until none is left."""
    pending, blocks, n = [], [], 0
    for op, arg in ops:
        if op == "enqueue":
            pending.extend(range(n, n + arg))
            n += arg
        else:
            while pending:
                blocks.append(pending[:max_block_txs])
                del pending[:max_block_txs]
                if op == "drive":
                    break
    return blocks


@pytest.mark.parametrize("max_block_txs, ops", [
    (64, [("enqueue", 192), ("flush", 0)]),
    (64, [("enqueue", 70), ("drive", 0), ("enqueue", 100), ("flush", 0)]),
    (64, [("enqueue", 1), ("drive", 0), ("enqueue", 1), ("drive", 0)]),
    (4, [("enqueue", 3), ("enqueue", 3), ("drive", 0), ("enqueue", 1),
         ("drive", 0), ("drive", 0)]),
    (2, [("enqueue", 5), ("drive", 0), ("enqueue", 2), ("flush", 0)]),
    (8, [("enqueue", 8), ("enqueue", 8), ("enqueue", 3), ("flush", 0)]),
    (1, [("enqueue", 3), ("flush", 0)]),
    (64, [("enqueue", 9), ("flush", 0), ("enqueue", 2), ("flush", 0)]),
], ids=lambda v: None if isinstance(v, int) else "-".join(
    f"{op[0]}{arg}" for op, arg in v))
def test_default_policy_cuts_what_the_parent_cut(max_block_txs, ops,
                                                monkeypatch):
    """A recorded sequence of hand-overs and drivers under `BlockPolicy()`
    (no timer, no byte rule) gives the blocks the parent's `_cut` gave:
    "everything pending, `max_block_txs` at a time"."""
    policy = BlockPolicy(max_block_txs=max_block_txs, pipeline=False)
    assert (policy.linger_s, policy.preferred_max_bytes,
            policy.absolute_max_bytes) == (0.0, 0, 0)
    h = Harness(policy, monkeypatch)
    n = 0
    for op, arg in ops:
        if op == "enqueue":
            h.orderer.enqueue_many([
                (TokenRequest(anchor=f"m{i}"), 170_000, None)
                for i in range(n, n + arg)])
            n += arg
        elif op == "flush":
            h.orderer.flush()
        else:  # one driver takes one block
            batch = h.orderer._cut()
            if batch:
                h._commit(batch)
    assert h.blocks == [[f"m{i}" for i in b]
                        for b in _parent_blocks(ops, max_block_txs)]
    assert {c["reason"] for c in h.cuts} <= {"count", "drain"}
    assert sum(c["bytes"] for c in h.cuts) == 170_000 * sum(
        len(b) for b in h.blocks)


def test_a_handover_enters_ordering_whole_beside_a_running_driver():
    """`enqueue_many` orders a hand-over under one hold of the queue's
    mutex: a driver that cuts as fast as it can beside 200 hand-overs of
    eight never takes part of one (every block is whole hand-overs)."""
    blocks, stop = [], threading.Event()

    def commit(batch):
        blocks.append([s.request.anchor for s in batch])
        _resolve_valid(batch)

    ordr = Orderer(commit, BlockPolicy(pipeline=False))

    def driver():
        while not stop.is_set():
            ordr.flush()

    t = threading.Thread(target=driver)
    t.start()
    try:
        for h in range(200):
            ordr.enqueue_many([(TokenRequest(anchor=f"h{h}-{k}"), 10, None)
                               for k in range(8)])
    finally:
        stop.set()
        t.join(10)
    ordr.flush()
    assert sum(map(len, blocks)) == 1600 and ordr.inflight() == 0
    for block in blocks:
        assert len(block) % 8 == 0 and block[0].endswith("-0"), block


# ---- the timer in real time: a driver sleeps, a cut wakes it


def test_a_lone_submit_waits_for_the_timer_and_a_full_batch_does_not():
    committed = []

    def commit(batch):
        committed.append((time.monotonic(), [s.request.anchor for s in batch]))
        _resolve_valid(batch)

    ordr = Orderer(commit, BlockPolicy(linger_s=0.4, max_block_txs=3,
                                       pipeline=False))
    t0 = time.monotonic()
    lone = ordr.enqueue(TokenRequest(anchor="lone"), 10)
    # flush does not cut an open batch whose timer still runs
    ordr.flush()
    assert committed == [] and ordr.pending() == 1
    with pytest.raises(TimeoutError):
        lone.result(timeout=0.05)
    assert lone.result(timeout=5).status == TxStatus.VALID
    assert 0.4 <= committed[0][0] - t0 < 1.5
    # a driver asleep on the timer is woken by a cut by count
    first = ordr.enqueue(TokenRequest(anchor="a"), 10)
    t1 = time.monotonic()
    waiter = threading.Thread(target=first.result, args=(5,))
    waiter.start()
    time.sleep(0.05)
    ordr.enqueue_many([(TokenRequest(anchor="b"), 10, None),
                       (TokenRequest(anchor="c"), 10, None)])
    waiter.join(5)
    assert first.done() and committed[1][1] == ["a", "b", "c"]
    assert committed[1][0] - t1 < 0.35  # well before the 0.4 s timer


def test_a_handover_larger_than_a_bounded_queue_sleeps_out_the_timer():
    """`queue_max` with a batch timer: the batch submitter that finds the
    queue full of an open batch waits for that batch's timer (no spin, no
    cut of its own) and then hands the rest over; every block keeps the
    rules."""
    blocks = []

    def commit(batch):
        blocks.append([s.request.anchor for s in batch])
        _resolve_valid(batch)

    ordr = Orderer(commit, BlockPolicy(linger_s=0.2, max_block_txs=3,
                                       queue_max=2, pipeline=False))
    items = [(TokenRequest(anchor=f"q{i}"), 10, None) for i in range(5)]
    t0, cpu0 = time.monotonic(), time.process_time()
    subs = ordr.enqueue_many(items)
    assert len(subs) == 2 and ordr.pending() == 2
    ordr.flush()  # the open batch's timer still runs: not a caller's to cut
    assert blocks == []
    ordr.flush(wait=True)
    assert blocks == [["q0", "q1"]] and time.monotonic() - t0 >= 0.2
    assert time.process_time() - cpu0 < 0.15  # slept, not spun
    subs += ordr.enqueue_many(items[2:])
    assert len(subs) == 4
    ordr.flush(wait=True)
    subs += ordr.enqueue_many(items[4:])
    assert [s.result(timeout=5).status for s in subs] == [TxStatus.VALID] * 5
    assert blocks == [["q0", "q1"], ["q2", "q3"], ["q4"]]
    assert ordr.pending() == 0 and ordr.inflight() == 0


def test_a_waiter_is_answered_at_its_own_commit_not_a_block_later():
    """Pipelined mode, three cut blocks queued (as the cutter queues them
    per hand-over under the byte rule): the waiter of the first drives its
    own block's stage A, hands it to the commit worker and is answered
    when that commits; it does not run the next blocks' verification
    first. They stay for their own waiters, in order."""
    from fabric_token_sdk_tpu.services.network.pipeline import (
        PipelinedBlockEngine,
    )

    stage_a_s, verified, committed = 0.3, [], []

    def verify(batch):
        verified.append([s.request.anchor for s in batch])
        time.sleep(stage_a_s)
        return {}

    def commit(batch, pre):
        committed.append([s.request.anchor for s in batch])
        _resolve_valid(batch)

    ordr = Orderer(lambda batch: commit(batch, None),
                   BlockPolicy(max_block_txs=1))
    ordr.set_engine(PipelinedBlockEngine(verify, commit))
    a, b, c = ordr.enqueue_many(
        [(TokenRequest(anchor=x), 10, None) for x in "abc"])
    t0 = time.monotonic()
    assert a.result(timeout=5).status == TxStatus.VALID
    took = time.monotonic() - t0
    assert verified == [["a"]] and committed == [["a"]]
    assert stage_a_s <= took < 2 * stage_a_s, took
    assert ordr.pending() == 2 and not b.done()
    # the last one's waiter drives what is ahead of it, oldest first
    assert c.result(timeout=5).status == TxStatus.VALID and b.done()
    assert committed == [["a"], ["b"], ["c"]] and ordr.inflight() == 0
    # taken by another driver: a waiter parks on its own event
    d, e = ordr.enqueue_many(
        [(TokenRequest(anchor=x), 10, None) for x in "de"])
    other = threading.Thread(target=ordr.flush)
    other.start()
    assert d.result(timeout=5).status == TxStatus.VALID
    other.join(5)
    assert not other.is_alive()
    assert e.done() and committed[-2:] == [["d"], ["e"]]


@pytest.mark.parametrize("linger_s", [0.0, 0.02])
def test_every_waiter_is_answered_when_each_drives_only_to_its_own_block(
        linger_s):
    """Liveness of the drive contract under contention: 48 threads (more
    than cores, a short switch interval) each order one request and wait
    for it alone, with and without a batch timer, through the pipelined
    engine. Nobody drives for anybody else once its own block is taken,
    so a lost wake-up or an undriven block would leave a waiter hanging:
    all are answered, in cut order, and nothing stays in flight."""
    from fabric_token_sdk_tpu.services.network.pipeline import (
        PipelinedBlockEngine,
    )

    committed, events = [], {}

    def commit(batch, pre):
        committed.extend(s.request.anchor for s in batch)
        _resolve_valid(batch)

    ordr = Orderer(lambda batch: commit(batch, None),
                   BlockPolicy(max_block_txs=3, linger_s=linger_s))
    ordr.set_engine(PipelinedBlockEngine(
        lambda batch: time.sleep(0.002) or {}, commit))

    def client(k):
        for r in range(5):
            anchor = f"c{k}-{r}"
            events[anchor] = ordr.enqueue(
                TokenRequest(anchor=anchor), 10).result(timeout=20)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(48)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert len(events) == 240 == len(set(committed)) == len(committed)
    assert all(e.status == TxStatus.VALID for e in events.values())
    assert ordr.pending() == 0 and ordr.inflight() == 0


# ---- a message over AbsoluteMaxBytes, on both sides of the wire


def test_too_large_message_is_refused_before_ordering_on_both_sides():
    pp = FabTokenPublicParams()
    network = Network(RequestValidator(FabTokenDriver(pp)),
                      policy=BlockPolicy(absolute_max_bytes=0))
    parties = {name: Party(name, FabTokenDriver(pp), network)
               for name in ("issuer-node", "alice-node")}
    parties["issuer-node"].new_issuer_wallet("issuer")
    alice = parties["alice-node"].new_owner_wallet("alice", anonymous=False)
    raws = []
    for k in range(3):
        tx = Transaction(parties["issuer-node"], f"mint{k}")
        tx.issue("issuer", "USD", [5], [alice.recipient_identity()],
                 anonymous=False)
        tx.collect_endorsements(None)
        raws.append(tx.request.to_bytes())
    network.policy.absolute_max_bytes = len(raws[0]) - 1
    rejects = mx.REGISTRY.counter("orderer.reject.too_large").value
    with pytest.raises(MessageTooLarge):
        network.submit(raws[0])
    server = LedgerServer(network=network).start()
    client = RemoteNetwork(server.address, timeout=10, retries=2)
    try:
        with pytest.raises(MessageTooLarge) as err:
            client.submit(raws[0])
        assert "absolute_max_bytes" in str(err.value)
        # one too-large request fails a hand-over whole, before any of it
        # is ordered
        network.policy.absolute_max_bytes = max(map(len, raws[:2]))
        big = TokenRequest.from_bytes(raws[2])
        big.set_application_metadata("pad", b"x" * 64)
        with pytest.raises(MessageTooLarge):
            client.submit_many([raws[1], big.to_bytes()])
        assert network._orderer.pending() == 0
        assert network._orderer.inflight() == 0
        assert client.ops_health()["inflight"] == 0
        assert network.status("mint1") is None
        # nothing was retried: the error is final, unlike Backpressure
        assert mx.REGISTRY.counter(
            "orderer.reject.too_large").value - rejects == 3
        # the same requests pass once they fit
        network.policy.absolute_max_bytes = 0
        assert client.submit(raws[0]).status == TxStatus.VALID
    finally:
        client.close()
        server.stop()
