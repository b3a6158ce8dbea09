"""Resilience layer: bounded device dispatch + per-plane circuit breakers.

Pins the PR-15 contract (utils/resilience.py):

* `CircuitBreaker` state machine — closed/open/half-open, failure-count
  and consecutive-timeout thresholds, monotonic cooldown, single-probe
  half-open admission, `FTS_BREAKER_*` env config, thread safety;
* `bounded_call` — inline when unbounded, result/exception passthrough,
  `DeviceTimeout` at the deadline, straggler discard (a worker that
  completes AFTER abandonment is counted, its result never applied);
* the `hang` fault kind (utils/faults.py) — blocks until disarm or cap,
  counts `faults.injected.*`, env-parseable;
* differential identity under a hung device plane on BOTH block engines:
  with `hang` injected at `batch.verify`, a zk block commits via host
  fallback within the deadline + slack, verdicts identical to the
  fault-free run (batching can accelerate but never change
  accept/reject — now including calls that never return);
* straggler discard at the block level: the abandoned verify worker
  completing after the block resolved must not double-apply verdicts or
  corrupt the block counters;
* the sign plane's construction-failure latch replacement: a transient
  failure opens the breaker, skips collection while open, and HEALS via
  the half-open probe (the old latch disabled the plane forever);
* `ftstop top` renders the breaker column from `ops.health`.
"""

import random
import threading
import time

import pytest

from fabric_token_sdk_tpu.api.request import (
    IssueRecord,
    TokenRequest,
    TransferRecord,
)
from fabric_token_sdk_tpu.api.validator import RequestValidator
from fabric_token_sdk_tpu.crypto import sign
from fabric_token_sdk_tpu.crypto.setup import setup
from fabric_token_sdk_tpu.drivers import identity
from fabric_token_sdk_tpu.drivers.fabtoken import (
    FabTokenDriver,
    FabTokenPublicParams,
)
from fabric_token_sdk_tpu.drivers.zkatdlog import ZKATDLogDriver
from fabric_token_sdk_tpu.models.token import ID
from fabric_token_sdk_tpu.services.network import BlockPolicy, Network, TxStatus
from fabric_token_sdk_tpu.services.ttx import Party, Transaction
from fabric_token_sdk_tpu.utils import faults, resilience
from fabric_token_sdk_tpu.utils import metrics as mx


def _counter(name):
    return mx.REGISTRY.counter(name).value


@pytest.fixture(scope="module")
def zk_pp():
    return setup(base=4, exponent=2, rng=random.Random(0xF75))


# ===================================================================
# CircuitBreaker state machine
# ===================================================================


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def _breaker(**kw):
    kw.setdefault("failure_threshold", 3)
    kw.setdefault("timeout_threshold", 2)
    kw.setdefault("cooldown_s", 10.0)
    kw.setdefault("clock", _Clock())
    return resilience.CircuitBreaker("unit", **kw)


def test_breaker_opens_on_consecutive_failures():
    b = _breaker()
    assert b.state == "closed" and b.allow()
    b.record_failure()
    b.record_failure()
    assert b.state == "closed"  # below threshold
    b.record_failure()
    assert b.state == "open"
    assert not b.allow()
    assert b.rejecting()


def test_breaker_success_resets_failure_streak():
    b = _breaker()
    b.record_failure()
    b.record_failure()
    b.record_success()
    b.record_failure()
    b.record_failure()
    assert b.state == "closed"  # streak restarted, never reached 3


def test_breaker_consecutive_timeouts_trip_faster():
    b = _breaker()
    b.record_failure(timeout=True)
    assert b.state == "closed"
    b.record_failure(timeout=True)
    assert b.state == "open"  # 2 consecutive timeouts < 3 failures
    # ... and a non-timeout failure resets the TIMEOUT streak only
    b2 = _breaker()
    b2.record_failure(timeout=True)
    b2.record_failure()  # failure #2, but timeout streak broken
    b2.record_failure(timeout=True)
    assert b2.state == "open"  # trips via failure threshold (3), not timeouts


def test_breaker_half_open_single_probe_then_close():
    clk = _Clock()
    b = _breaker(clock=clk)
    for _ in range(3):
        b.record_failure()
    assert not b.allow()  # open: rejected
    clk.t += 9.9
    assert not b.allow()  # cooldown not yet expired
    clk.t += 0.2
    assert b.state == "half-open"
    assert not b.rejecting()  # a probe is available: NOT hard-rejecting
    assert b.allow()  # the single probe
    assert not b.allow()  # second caller rejected while probe in flight
    b.record_success()
    assert b.state == "closed"
    assert b.allow()


def test_breaker_failed_probe_reopens_and_restarts_cooldown():
    clk = _Clock()
    b = _breaker(clock=clk)
    for _ in range(3):
        b.record_failure()
    clk.t += 10.1
    assert b.allow()  # probe
    b.record_failure()
    assert b.state == "open"
    clk.t += 5.0
    assert not b.allow()  # cooldown restarted at probe failure
    clk.t += 5.2
    assert b.allow()  # next probe due
    b.record_success()
    assert b.state == "closed"


def test_breaker_trip_now_opens_on_first_failure():
    """`trip_now` (structural failures like verifier construction OOM)
    opens regardless of thresholds — latch parity — and still heals via
    the half-open probe, unlike the latch."""
    clk = _Clock()
    b = _breaker(clock=clk)  # thresholds 3/2: one plain failure won't trip
    b.record_failure(trip_now=True)
    assert b.state == "open"
    clk.t += 10.1
    assert b.allow()  # the probe
    b.record_success()
    assert b.state == "closed"


def test_breaker_env_config(monkeypatch):
    monkeypatch.setenv("FTS_BREAKER_FAILURES", "7")
    monkeypatch.setenv("FTS_BREAKER_TIMEOUTS", "4")
    monkeypatch.setenv("FTS_BREAKER_COOLDOWN_S", "1.5")
    resilience.reset()
    b = resilience.breaker("envtest")
    assert b.failure_threshold == 7
    assert b.timeout_threshold == 4
    assert b.cooldown_s == 1.5


def test_breaker_transition_counters_and_state_gauge():
    resilience.reset()
    o0, c0, p0, r0 = (
        _counter("resilience.breaker.open"),
        _counter("resilience.breaker.close"),
        _counter("resilience.breaker.probe"),
        _counter("resilience.breaker.rejected"),
    )
    b = resilience.breaker("gaugetest")
    b.failure_threshold, b.timeout_threshold, b.cooldown_s = 1, 1, 0.05
    b.record_failure()
    assert _counter("resilience.breaker.open") - o0 == 1
    assert mx.REGISTRY.gauge("resilience.breaker.state.gaugetest").value == 2
    assert not b.allow()
    assert _counter("resilience.breaker.rejected") - r0 == 1
    time.sleep(0.06)
    assert b.allow()
    assert _counter("resilience.breaker.probe") - p0 == 1
    b.record_success()
    assert _counter("resilience.breaker.close") - c0 == 1
    assert mx.REGISTRY.gauge("resilience.breaker.state.gaugetest").value == 0
    assert resilience.breaker_states()["gaugetest"] == "closed"


def test_breaker_thread_safety():
    b = _breaker(failure_threshold=2, cooldown_s=0.001)

    def churn():
        for _ in range(200):
            if b.allow():
                b.record_failure()
            b.record_success()
            b.state

    threads = [threading.Thread(target=churn) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert b.state in ("closed", "open", "half-open")


# ===================================================================
# bounded_call
# ===================================================================


def test_bounded_call_unbounded_runs_inline():
    caller = threading.current_thread()
    seen = []
    out = resilience.bounded_call(
        lambda: seen.append(threading.current_thread()) or 7, 0, plane="t"
    )
    assert out == 7 and seen == [caller]
    # None is unbounded too
    assert resilience.bounded_call(lambda: 8, None, plane="t") == 8


def test_bounded_call_result_and_exception_passthrough():
    assert resilience.bounded_call(lambda: [1, 2], 5.0, plane="t") == [1, 2]
    with pytest.raises(ValueError, match="boom"):
        resilience.bounded_call(
            lambda: (_ for _ in ()).throw(ValueError("boom")), 5.0, plane="t"
        )


def test_bounded_call_timeout_and_straggler_discard():
    t0 = _counter("resilience.bounded.timeouts")
    s0 = _counter("resilience.bounded.stragglers")
    release = threading.Event()

    def slow():
        release.wait(10)
        return "late"

    start = time.monotonic()
    with pytest.raises(resilience.DeviceTimeout):
        resilience.bounded_call(slow, 0.1, plane="t")
    assert time.monotonic() - start < 5  # returned at the deadline, not 10s
    assert _counter("resilience.bounded.timeouts") - t0 == 1
    release.set()  # the abandoned worker now completes
    deadline = time.monotonic() + 10
    while (
        _counter("resilience.bounded.stragglers") == s0
        and time.monotonic() < deadline
    ):
        time.sleep(0.01)
    assert _counter("resilience.bounded.stragglers") - s0 == 1


def test_straggler_drain_joins_abandoned_workers():
    """Abandoned workers are tracked and `drain_stragglers` (the exit
    hook's body) joins the ones that finish within the budget — the
    guard against a daemon thread segfaulting interpreter teardown."""
    release = threading.Event()
    with pytest.raises(resilience.DeviceTimeout):
        resilience.bounded_call(lambda: release.wait(30), 0.05, plane="t")
    assert not resilience.drain_stragglers(0.05)  # still hung: not drained
    release.set()
    assert resilience.drain_stragglers(10.0)  # released: drained clean


def test_device_deadline_env_resolution(monkeypatch):
    monkeypatch.delenv("FTS_DEVICE_DEADLINE_S", raising=False)
    monkeypatch.delenv("FTS_DEVICE_DEADLINE_VERIFY_S", raising=False)
    # CPU backend: commit-path planes default UNBOUNDED (a cold compile
    # legitimately takes minutes on the emulated plane)
    assert resilience.device_deadline_s("verify") == 0.0
    assert resilience.device_deadline_s("prove") == 0.0
    monkeypatch.setenv("FTS_DEVICE_DEADLINE_S", "3.5")
    assert resilience.device_deadline_s("verify") == 3.5
    assert resilience.device_deadline_s("sign") == 3.5
    monkeypatch.setenv("FTS_DEVICE_DEADLINE_VERIFY_S", "1.25")
    assert resilience.device_deadline_s("verify") == 1.25  # per-plane wins
    assert resilience.device_deadline_s("sign") == 3.5
    monkeypatch.setenv("FTS_DEVICE_DEADLINE_VERIFY_S", "0")
    assert resilience.device_deadline_s("verify") == 0.0  # 0 = unbounded


def test_cancel_probe_releases_the_half_open_slot():
    """A caller that consumed the half-open probe but found nothing to
    dispatch (driver without a batched plane) must release it, or the
    breaker would wedge in half-open forever — the exact
    process-lifetime latch this layer exists to remove."""
    clk = _Clock()
    b = _breaker(clock=clk)
    for _ in range(3):
        b.record_failure()
    clk.t += 10.1
    assert b.allow()  # probe consumed
    b.cancel_probe()  # ...but nothing was dispatched
    assert b.state == "half-open"
    assert b.allow()  # the slot is available again, not wedged
    b.record_success()
    assert b.state == "closed"


# ===================================================================
# The hang fault kind
# ===================================================================


def test_hang_fault_blocks_until_disarm():
    faults.arm("unit.hang", "hang", count=1, delay_s=30)
    fired = threading.Event()

    def firer():
        faults.fire("unit.hang")
        fired.set()

    f0 = _counter("faults.injected.unit.hang")
    t = threading.Thread(target=firer, daemon=True)
    t0 = time.monotonic()
    t.start()
    time.sleep(0.05)
    assert not fired.is_set()  # blocked, not sleeping-and-done
    faults.disarm("unit.hang")
    assert fired.wait(5)
    assert time.monotonic() - t0 < 5  # released by disarm, not the cap
    assert _counter("faults.injected.unit.hang") - f0 == 1


def test_hang_fault_cap_releases_without_disarm():
    faults.arm("unit.cap", "hang", count=1, delay_s=0.1)
    t0 = time.monotonic()
    faults.fire("unit.cap")  # returns at the cap
    assert 0.05 < time.monotonic() - t0 < 5
    faults.clear()


def test_hang_fault_env_parse_and_default_cap():
    n = faults.load_env("a.site:hang:1.0:2:0.25,b.site:hang")
    assert n == 2
    assert faults.armed() == {"a.site": "hang", "b.site": "hang"}
    with faults._lock:
        assert faults._armed["a.site"].delay_s == 0.25
        assert faults._armed["b.site"].delay_s == faults.HANG_CAP_S
        assert faults._armed["a.site"].release is not None
    faults.clear()


def test_clear_releases_all_hangers():
    faults.arm("u.one", "hang", delay_s=30)
    faults.arm("u.two", "hang", delay_s=30)
    done = []
    ts = [
        threading.Thread(target=lambda s=s: (faults.fire(s), done.append(s)),
                         daemon=True)
        for s in ("u.one", "u.two")
    ]
    for t in ts:
        t.start()
    time.sleep(0.05)
    faults.clear()
    for t in ts:
        t.join(5)
    assert sorted(done) == ["u.one", "u.two"]


# ===================================================================
# Differential identity under a hung device plane (both engines)
# ===================================================================


def _zk_env(zk_pp, pipeline):
    net = Network(
        RequestValidator(ZKATDLogDriver(zk_pp)),
        policy=BlockPolicy(max_block_txs=8, min_batch=2, pipeline=pipeline),
    )
    parties = {
        name: Party(name, ZKATDLogDriver(zk_pp), net)
        for name in ("issuer-node", "alice-node", "bob-node")
    }
    issuer = parties["issuer-node"].new_issuer_wallet("issuer")
    alice = parties["alice-node"].new_owner_wallet("alice", anonymous=False)
    bob = parties["bob-node"].new_owner_wallet("bob", anonymous=False)
    if hasattr(getattr(net.validator.driver, "pp", None), "add_issuer"):
        net.validator.driver.pp.add_issuer(issuer.identity)
    return net, parties, alice, bob


def _zk_transfer_block(zk_pp, pipeline):
    """One committed zk block of 2 same-shape transfers; returns
    (statuses, bob_balance) — the differential unit."""
    net, parties, alice, bob = _zk_env(zk_pp, pipeline)
    tx = Transaction(parties["issuer-node"], "seed")
    tx.issue("issuer", "USD", [5, 5],
             [alice.recipient_identity()] * 2, anonymous=False)
    tx.collect_endorsements(None)
    tx.submit()
    alice_p = parties["alice-node"]
    reqs = []
    for i, tid in enumerate(alice_p.vault.token_ids()):
        req = alice_p.tms.new_request(f"pay-{i}")
        tokens, metas = alice_p.vault.get_many([tid])
        alice_p.tms.add_transfer(
            req, [tid], tokens, metas, "USD", [5], [bob.recipient_identity()]
        )
        alice_p.tms.sign_transfers(req)
        reqs.append(req)
    events = net.submit_many([r.to_bytes() for r in reqs])
    return (
        [e.status for e in events],
        parties["bob-node"].balance("USD"),
    )


@pytest.mark.parametrize("pipeline", [True, False])
def test_hang_fault_commits_via_host_fallback_same_verdicts(
    zk_pp, pipeline, monkeypatch
):
    """Acceptance: with `hang` injected at `batch.verify`, the block
    commits via host fallback within FTS_DEVICE_DEADLINE_S + slack (no
    indefinite stall), verdicts identical to the fault-free run — on
    BOTH block engines — and the timeout is visible in the resilience
    counters."""
    resilience.reset()
    deadline_s = 0.5
    monkeypatch.setenv("FTS_DEVICE_DEADLINE_VERIFY_S", str(deadline_s))
    to0 = _counter("resilience.bounded.timeouts")
    be0 = _counter("ledger.block.batch_errors")
    host0 = _counter("ledger.validate.host")
    faults.arm("batch.verify", "hang", count=1, delay_s=60)
    t0 = time.monotonic()
    try:
        injected = _zk_transfer_block(zk_pp, pipeline)
    finally:
        faults.disarm("batch.verify")  # release the abandoned worker
    wall = time.monotonic() - t0
    # bounded: the block resolved at the deadline, nowhere near the
    # 60s hang cap (generous slack for the host re-validate + CI noise)
    assert wall < 30, f"hung block took {wall:.1f}s"
    assert _counter("resilience.bounded.timeouts") - to0 == 1
    assert _counter("ledger.block.batch_errors") - be0 == 1
    assert _counter("ledger.validate.host") - host0 == 2  # host re-verified
    monkeypatch.setenv("FTS_DEVICE_DEADLINE_VERIFY_S", "0")
    resilience.reset()  # clean-run breaker must start fresh
    clean = _zk_transfer_block(zk_pp, pipeline)
    assert injected == clean == ([TxStatus.VALID, TxStatus.VALID], 10)


@pytest.mark.parametrize("pipeline", [True, False])
def test_straggler_worker_does_not_double_apply(zk_pp, pipeline, monkeypatch):
    """An abandoned verify worker that completes AFTER host fallback
    already resolved the block (hang released at its cap, then the
    device verify runs to completion) must not double-apply verdicts or
    corrupt block metrics — on BOTH engines."""
    resilience.reset()
    monkeypatch.setenv("FTS_DEVICE_DEADLINE_VERIFY_S", "0.15")
    s0 = _counter("resilience.bounded.stragglers")
    valid0 = _counter("network.tx.valid")
    batched0 = _counter("ledger.validate.batched")
    blocks0 = _counter("ledger.blocks.committed")
    devtxs0 = _counter("batch.transfer.txs")
    # cap 0.5s: the worker outlives the 0.15s deadline (abandoned), then
    # completes the REAL device verify in the background
    faults.arm("batch.verify", "hang", count=1, delay_s=0.5)
    try:
        statuses, bob_balance = _zk_transfer_block(zk_pp, pipeline)
    finally:
        faults.disarm("batch.verify")
    assert statuses == [TxStatus.VALID, TxStatus.VALID]
    assert bob_balance == 10
    valid_after = _counter("network.tx.valid") - valid0
    blocks_after = _counter("ledger.blocks.committed") - blocks0
    # wait for the straggler to finish its discarded device verify
    deadline = time.monotonic() + 30
    while (
        _counter("resilience.bounded.stragglers") == s0
        and time.monotonic() < deadline
    ):
        time.sleep(0.05)
    assert _counter("resilience.bounded.stragglers") - s0 == 1
    time.sleep(0.1)  # anything the straggler would corrupt has landed
    # no verdict was double-applied: tx/block counters unchanged by the
    # straggler, and its discarded verdicts never count as batched
    assert _counter("network.tx.valid") - valid0 == valid_after
    assert _counter("ledger.blocks.committed") - blocks0 == blocks_after
    assert _counter("ledger.validate.batched") - batched0 == 0
    # the discarded device verify must not report its txs as device-
    # served either (counted-on-completion contract, straggler-aware)
    assert _counter("batch.transfer.txs") - devtxs0 == 0


# ===================================================================
# Sign plane: transient construction failure heals (latch replacement)
# ===================================================================


def _pk_chain(n_transfers):
    pp = FabTokenPublicParams()
    key = sign.keygen(random.Random(7))
    ident = identity.pk_identity(key.public)
    drv = FabTokenDriver(pp)
    reqs = []
    out = drv.issue(ident, "USD", [9], [ident])
    req = TokenRequest(anchor="seed")
    req.issues.append(
        IssueRecord(action=out.action_bytes, issuer=ident,
                    outputs_metadata=out.metadata, receivers=[ident])
    )
    req.issues[0].signature = key.sign(req.marshal_to_sign(), random.Random(11))
    reqs.append(req.to_bytes())
    prev, prev_raw = ID("seed", 0), out.outputs[0]
    for k in range(n_transfers):
        t = drv.transfer([prev], [prev_raw], [prev_raw], "USD", [9], [ident])
        tr = TokenRequest(anchor=f"t{k}")
        tr.transfers.append(
            TransferRecord(action=t.action_bytes, input_ids=[prev],
                           senders=[ident], outputs_metadata=t.metadata,
                           receivers=[ident])
        )
        tr.transfers[0].signatures = [
            key.sign(tr.marshal_to_sign(), random.Random(100 + k))
        ]
        reqs.append(tr.to_bytes())
        prev, prev_raw = ID(f"t{k}", 0), t.outputs[0]
    return pp, reqs


def test_sign_plane_transient_construction_failure_heals():
    """Regression for the PR-14 latch: a TRANSIENT verifier construction
    failure (one-off OOM) must not disable device signatures for the
    process lifetime. The breaker opens (host fallback, collection
    skipped), and once the cooldown expires the half-open probe
    re-constructs and RE-ENGAGES the device plane."""
    from fabric_token_sdk_tpu.crypto import batch_sign as bs_module

    pp, reqs = _pk_chain(6)
    chunks = [reqs[0:3], reqs[3:5], reqs[5:7]]  # >= 2 pk obligations each
    net = Network(
        RequestValidator(FabTokenDriver(pp)),
        policy=BlockPolicy(
            max_block_txs=16, sign_batched=True, sign_min_batch=2
        ),
    )
    resilience.reset()
    brk = resilience.breaker("sign")
    brk.failure_threshold = 1  # one construction failure opens it
    # generous vs the ms-fast fabtoken blocks: chunk 2 must land INSIDE
    # the cooldown window or it would become the probe itself
    brk.cooldown_s = 1.5

    fb0 = _counter("batch.sign.host_fallbacks")
    rows0 = _counter("batch.sign.rows")
    real = bs_module.BatchedSchnorrVerifier

    class _Boom:
        def __init__(self, *a, **k):
            raise MemoryError("transient construction OOM")

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(bs_module, "BatchedSchnorrVerifier", _Boom)
        ev1 = net.submit_many(chunks[0])
        assert all(e.status == TxStatus.VALID for e in ev1)  # host verified
        assert _counter("batch.sign.host_fallbacks") - fb0 == 3
        assert brk.state == "open"
        # while open: collection is skipped entirely (the latch's fast
        # path, preserved) — no new fallback counts, still all-Valid
        ev2 = net.submit_many(chunks[1])
        assert all(e.status == TxStatus.VALID for e in ev2)
        assert _counter("batch.sign.host_fallbacks") - fb0 == 3
        assert _counter("batch.sign.rows") == rows0
    assert bs_module.BatchedSchnorrVerifier is real
    time.sleep(1.6)  # cooldown expires -> half-open probe due
    ev3 = net.submit_many(chunks[2])
    assert all(e.status == TxStatus.VALID for e in ev3)
    # the probe re-constructed the verifier and the rows rode the device
    assert _counter("batch.sign.rows") - rows0 == 2
    assert brk.state == "closed"


# ===================================================================
# Surfacing: ftstop breaker column
# ===================================================================


def test_ftstop_renders_breaker_column():
    import os
    import sys

    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "cmd")
    )
    try:
        import ftstop
    finally:
        sys.path.pop(0)
    health = {"uptime_s": 1.0, "height": 3,
              "breakers": {"verify": "closed", "sign": "closed"}}
    assert "brk=ok" in ftstop.format_row(health, {}, None, None)
    health["breakers"]["sign"] = "open"
    health["breakers"]["prove"] = "half-open"
    row = ftstop.format_row(health, {}, None, None)
    assert "brk=prove:half-open,sign:open" in row
    # nodes predating the field render no column at all
    row_old = ftstop.format_row({"uptime_s": 1.0, "height": 3}, {}, None, None)
    assert "brk=" not in row_old


def test_health_serves_breaker_states(zk_pp):
    resilience.reset()
    resilience.breaker("verify").record_failure()
    net = Network(RequestValidator(ZKATDLogDriver(zk_pp)))
    h = net.health()
    assert h["breakers"] == {"verify": "closed"}


# ===================================================================
# Bench chaos soak (FTS_BENCH_SOAK_FAULTS=1) smoke
# ===================================================================


def test_bench_chaos_soak_smoke(monkeypatch):
    """The chaos-soak mode end to end (tiny budget): randomized injected
    faults for the whole window, the node stays live with every
    acknowledged tx Valid, and the soak section is schema-valid with the
    resilience fields present."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
    try:
        import bench
    finally:
        sys.path.pop(0)
    from fabric_token_sdk_tpu.utils import benchschema

    monkeypatch.setenv("FTS_BENCH_SOAK_S", "1.5")
    monkeypatch.setenv("FTS_BENCH_SOAK_CLIENTS", "2")
    monkeypatch.setenv("FTS_BENCH_SOAK_GROUP", "4")
    monkeypatch.setenv("FTS_BENCH_SOAK_QUEUE_MAX", "16")
    monkeypatch.setenv("FTS_BENCH_SOAK_FAULTS", "1")
    # pin the deadline ourselves so _soak's setdefault (a process-level
    # knob in a real bench run) is monkeypatch-scoped and restored here
    monkeypatch.setenv("FTS_DEVICE_DEADLINE_S", "1")

    class _HB:
        def set_phase(self, *a, **k):
            pass

    soak = bench._soak(_HB())
    assert benchschema.validate_soak(soak) == []
    # every acknowledged tx was Valid (the soak client asserts per
    # batch and _soak re-raises) and the node stayed live throughout
    assert soak["steady_txs_per_s"] > 0
    assert soak["txs"] > 0
    # resilience fields are present (ints; the fabtoken corpus has no
    # batchable device groups, so breaker trips may legitimately be 0)
    for key in ("faults_injected", "breaker_trips", "degraded_planes"):
        assert isinstance(soak[key], int) and soak[key] >= 0
    assert not faults.armed()  # the monkey disarmed everything
