"""Device-plane dispatch ledger (`utils/devobs.py`): zero-cost-when-off,
occupancy/waste arithmetic, per-program compile/cache attribution, and
the differential no-perturbation contract.

The ledger is an observer with the same contract as the host-path
profiler: ``FTS_DEVOBS=0`` must make every entry point an inert
passthrough (no ledger state, no registry writes, no threads), and on
or off the accept/reject verdicts of an identical workload must not
change.
"""
import functools
import os
import random
import threading
import time

import numpy as np
import pytest

from fabric_token_sdk_tpu.api.validator import RequestValidator
from fabric_token_sdk_tpu.crypto import hostmath as hm
from fabric_token_sdk_tpu.drivers.fabtoken import FabTokenDriver, FabTokenPublicParams
from fabric_token_sdk_tpu.ops import curve as cv, pairing as pr, stages as st
from fabric_token_sdk_tpu.services.network import BlockPolicy, Network, TxStatus
from fabric_token_sdk_tpu.services.ttx import Party, Transaction
from fabric_token_sdk_tpu.utils import benchschema, devobs
from fabric_token_sdk_tpu.utils import metrics as mx


@pytest.fixture(autouse=True)
def _clean_ledger():
    """Each test sees (and leaves) a reset ledger; registry histograms
    are process-wide and asserted by delta only."""
    devobs.reset()
    yield
    devobs.reset()


# ===================================================================
# zero cost when off
# ===================================================================


def _hist_count(name):
    h = mx.REGISTRY.snapshot().get("histograms", {}).get(name)
    return h["count"] if h else 0


def test_off_is_passthrough(monkeypatch):
    monkeypatch.setenv("FTS_DEVOBS", "0")
    assert not devobs.enabled()
    agg_before = _hist_count("device.dispatch.seconds")
    threads_before = threading.active_count()
    with devobs.plane("verify"):
        assert devobs.glue("parse") is devobs._NULL
        with devobs.attribute("offtest_attr"):
            with devobs.dispatch("offtest_prog", rows=5, padded_rows=3):
                pass
    devobs.note_compile(1.0)
    devobs.note_cache("/jax/compilation_cache/cache_hits")
    # no ledger state, no per-program registry metrics, no threads
    assert devobs.snapshot() == {}
    assert devobs.current_program() is None
    snap = mx.REGISTRY.snapshot()
    assert "device.dispatch.offtest_prog.seconds" not in snap.get(
        "histograms", {}
    )
    assert "device.offtest_prog.padded_rows" not in snap.get("counters", {})
    assert _hist_count("device.dispatch.seconds") == agg_before
    assert threading.active_count() == threads_before
    # off means off for the surfaced sections too
    assert devobs.health_section()["enabled"] is False
    assert devobs.health_section()["programs"] == {}


# ===================================================================
# ledger arithmetic + schema
# ===================================================================


def test_dispatch_records_occupancy_and_waste():
    agg_before = _hist_count("device.dispatch.seconds")
    with devobs.plane("verify"):
        with devobs.dispatch("ledger_prog", rows=5, padded_rows=3):
            pass
    snap = devobs.snapshot()
    assert set(snap) == {("verify", "ledger_prog")}
    e = snap[("verify", "ledger_prog")]
    assert e["dispatches"] == 1
    assert (e["rows"], e["padded_rows"]) == (5, 3)
    assert e["wall_s"] >= 0

    h = devobs.health_section()
    prog = h["programs"]["verify:ledger_prog"]
    assert prog["occupancy"] == 0.625
    assert prog["waste_frac"] == 0.375
    assert h["planes"]["verify"]["occupancy"] == 0.625

    # the registry got the histograms + the padding-waste counter
    assert _hist_count("device.dispatch.seconds") == agg_before + 1
    assert _hist_count("device.dispatch.ledger_prog.seconds") >= 1
    reg = mx.REGISTRY.snapshot()
    assert reg["counters"]["device.ledger_prog.padded_rows"] == 3

    # the bench `device` section validates against the shared schema
    section = devobs.section()
    assert benchschema.validate_device(section) == []
    assert section["dispatches"] == 1
    assert section["occupancy"] == 0.625
    assert section["waste_frac"] == 0.375


def test_compile_and_cache_attribution():
    with devobs.dispatch("attr_prog", rows=1):
        devobs.note_compile(0.25)
        devobs.note_cache("/jax/compilation_cache/cache_hits")
        devobs.note_cache("/jax/compilation_cache/cache_misses")
        assert devobs.current_program() == "attr_prog"
    # the frame outlives the block as the process-wide fallback (a
    # compile fired with no frame open lands on the last program)
    devobs.note_compile(0.25)
    e = devobs.snapshot()[(devobs.DEFAULT_PLANE, "attr_prog")]
    assert e["compiles"] == 2
    assert e["compile_s"] == pytest.approx(0.5)
    assert (e["cache_hits"], e["cache_misses"]) == (1, 1)

    # with no frame ever opened, events land on the unattributed bucket
    devobs.reset()
    devobs.note_compile(0.1)
    devobs.note_cache("/jax/compilation_cache/cache_hits")
    assert set(devobs.snapshot()) == {
        (devobs.DEFAULT_PLANE, devobs.UNATTRIBUTED)
    }

    # attribute() joins warmup's AOT loop to the ledger without faking a
    # dispatch
    devobs.reset()
    with devobs.attribute("warm_prog"):
        devobs.note_compile(0.2)
    e = devobs.snapshot()[(devobs.DEFAULT_PLANE, "warm_prog")]
    assert (e["dispatches"], e["compiles"]) == (0, 1)


# ===================================================================
# a real staged dispatch lands in the ledger with the canonical name
# ===================================================================


def test_msm_dispatch_ledgered_with_canonical_program_name():
    rng = random.Random(0xD0B5)
    base = [hm.g1_mul(hm.G1_GEN, 3)]
    table = cv.FixedBaseTable(base)
    scalars = np.stack(
        [cv.encode_scalars([rng.randrange(hm.R)]) for _ in range(5)]
    )
    st.g1_msm_rows(table.flat, scalars)
    frame = ("stages", "g1_msm1_tile")
    e = devobs.snapshot()[frame]
    assert e["dispatches"] == 1
    assert e["rows"] == 5
    # run_rows pads the 5-row batch up to one tile of the program's height
    T = st.tile_rows("g1_msm1_tile")
    assert e["padded_rows"] == (-5) % T
    assert e["tile_rows"] == T
    prog = devobs.health_section()["programs"]["stages:g1_msm1_tile"]
    assert prog["occupancy"] == pytest.approx(5 / (5 + (-5) % T))
    assert prog["tile_rows"] == T


# ===================================================================
# differential: the ledger never perturbs verdicts
# ===================================================================


def _run_scenario(policy=None):
    """Deterministic mixed-verdict workload (the profiler's scenario):
    issue, then two transfers of which the second double-spends."""
    network, parties = _scenario_network(
        policy or BlockPolicy(max_block_txs=8)
    )
    parties["issuer-node"].new_issuer_wallet("issuer")
    alice = parties["alice-node"].new_owner_wallet("alice", anonymous=False)
    bob = parties["bob-node"].new_owner_wallet("bob", anonymous=False)
    tx = Transaction(parties["issuer-node"], "devobs-seed")
    tx.issue("issuer", "USD", [5], [alice.recipient_identity()],
             anonymous=False)
    tx.collect_endorsements(None)
    tx.submit()
    alice_p = parties["alice-node"]
    tid = alice_p.vault.token_ids()[0]

    def spend(anchor):
        req = alice_p.tms.new_request(anchor)
        tokens, metas = alice_p.vault.get_many([tid])
        alice_p.tms.add_transfer(
            req, [tid], tokens, metas, "USD", [5],
            [bob.recipient_identity()],
        )
        alice_p.tms.sign_transfers(req)
        return req.to_bytes()

    events = network.submit_many([spend("dv-ok"), spend("dv-dup")])
    return [e.status for e in events]


@pytest.mark.parametrize("sign_batched", [False, True],
                         ids=["host", "device_sign_plane"])
def test_ledger_never_perturbs_verdicts(monkeypatch, sign_batched):
    """Ledger on and off: the same verdicts — on the host path, and on
    the path whose frames, read-backs and plane spans are timed (the
    device sign plane)."""
    policy = BlockPolicy(
        max_block_txs=8, sign_batched=sign_batched, sign_min_batch=2
    )
    monkeypatch.setenv("FTS_DEVOBS", "1")
    on_statuses = _run_scenario(policy)
    assert on_statuses == [TxStatus.VALID, TxStatus.INVALID]
    assert bool(devobs.snapshot()) == sign_batched
    monkeypatch.setenv("FTS_DEVOBS", "0")
    devobs.reset()
    off_statuses = _run_scenario(policy)
    assert off_statuses == on_statuses
    assert devobs.snapshot() == {}


# ===================================================================
# the frame lasts until the results are on the host, and says how its
# time divides (stage_s / wait_s); the plane span adds the host glue
# ===================================================================


class _SlowResult:
    """A tile result whose read-back blocks, as a device array's does
    while the device is still computing it."""

    def __init__(self, rows, delay_s):
        self.rows, self.delay_s = rows, delay_s

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.delay_s)
        return np.asarray(self.rows)


def _slow_tile(delay_s):
    def slow_tile(rows):
        return _SlowResult(np.asarray(rows) + 1, delay_s)

    return slow_tile


def _counters(*names):
    return {n: mx.REGISTRY.counter(n).value for n in names}


def _plane_counters(pl):
    return _counters(*(f"device.{pl}.{k}_us"
                       for k in ("span", "stage", "wait", "glue")))


_GLUE_KEYS = devobs.GLUE_PARTS + ("other",)


def _glue_counters(pl):
    """A plane's span_us, its three addends, and glue_us' six, by short
    name."""
    names = {**{k: f"device.{pl}.{k}_us"
                for k in ("span", "stage", "wait", "glue")},
             **{k: f"device.{pl}.glue.{k}_us" for k in _GLUE_KEYS}}
    return {k: mx.REGISTRY.counter(n).value for k, n in names.items()}


def _moved(before, after):
    return {k: after[k] - before[k] for k in after}


def test_frame_wall_includes_the_read_back():
    """The regression test of the enqueue-time bug: `run_rows` used to
    close its frame once the tiles were ENQUEUED; the blocking read-back
    came two statements later, outside every timer."""
    rows = np.arange(12, dtype=np.int32).reshape(12, 1)
    out = st.run_rows(_slow_tile(0.05), rows)
    assert (out == rows + 1).all()
    e = devobs.snapshot()[("stages", "slow_tile")]
    assert (e["dispatches"], e["rows"], e["padded_rows"]) == (1, 12, 4)
    # two tiles, each blocking 50 ms when read back
    assert e["wait_s"] >= 0.1
    assert e["wall_s"] >= e["stage_s"] + 0.1
    assert e["wall_s"] == pytest.approx(e["stage_s"] + e["wait_s"])
    assert e["stage_s"] < 0.05  # enqueueing did not block
    prog = devobs.health_section()["programs"]["stages:slow_tile"]
    assert prog["wait_s"] >= 0.1 and prog["stage_s"] >= 0
    # the aggregate histograms saw the whole frame, not the enqueue
    assert mx.REGISTRY.histogram(
        "device.dispatch.slow_tile.seconds"
    ).quantile(0.5) >= 0.1


def test_frame_without_a_plane_span_is_its_own_span():
    before = _plane_counters("stages")
    st.run_rows(_slow_tile(0.01), np.zeros((8, 1), dtype=np.int32))
    p = devobs.plane_snapshot()["stages"]
    assert p["calls"] == 1 and p["glue_s"] == 0.0
    assert p["span_s"] == pytest.approx(p["stage_s"] + p["wait_s"])
    after = _plane_counters("stages")
    assert after["device.stages.glue_us"] == before["device.stages.glue_us"]
    assert after["device.stages.span_us"] > before["device.stages.span_us"]


def test_plane_span_splits_into_stage_wait_and_glue():
    """`span_s = stage_s + wait_s + glue_s`; a nested verifier call is
    part of the outermost span, never a second one; the microsecond
    counters say the same as the ledger's seconds."""
    from fabric_token_sdk_tpu.crypto.batch import _spanned

    rows = np.zeros((8, 1), dtype=np.int32)

    @_spanned("batch.wf.verify")
    def inner():
        st.run_rows(_slow_tile(0.02), rows)

    @_spanned("batch.transfer.verify")
    def outer():
        st.run_rows(_slow_tile(0.02), rows)
        time.sleep(0.03)  # host glue between two frames
        inner()

    before = _plane_counters("verify")
    outer()
    assert set(devobs.plane_snapshot()) == {"verify"}
    p = devobs.plane_snapshot()["verify"]
    assert p["calls"] == 1  # the nested call counted once
    assert p["wait_s"] >= 0.04
    assert p["glue_s"] >= 0.03
    assert p["span_s"] == pytest.approx(
        p["stage_s"] + p["wait_s"] + p["glue_s"], abs=1e-9
    )
    e = devobs.snapshot()[("verify", "slow_tile")]
    assert e["dispatches"] == 2
    assert e["wall_s"] == pytest.approx(p["stage_s"] + p["wait_s"])
    # counters: integer microseconds of the same numbers, summing exactly
    after = _plane_counters("verify")
    d = {k.rsplit(".", 1)[1]: after[k] - before[k] for k in after}
    assert d["span_us"] == d["stage_us"] + d["wait_us"] + d["glue_us"]
    for k in ("span", "stage", "wait", "glue"):
        assert abs(d[f"{k}_us"] - p[f"{k}_s"] * 1e6) <= 2, k
    # the operator's view carries the per-plane split
    hp = devobs.health_section()["planes"]["verify"]
    assert hp["calls"] == 1 and hp["glue_s"] >= 0.03
    assert benchschema.validate_device(devobs.section()) == []


def test_frame_records_itself_as_the_span():
    """With span recording on, the frame is the `device.dispatch` span
    under the caller's open span (same start and end, no second timer,
    no second observation of `device.dispatch.seconds`)."""
    was = mx.enabled()
    mx.enable(True)
    try:
        agg = _hist_count("device.dispatch.seconds")
        with mx.span("test.devobs.parent") as parent:
            st.run_rows(_slow_tile(0.01), np.zeros((5, 1), dtype=np.int32))
        (child,) = [c for c in parent.children if c.name == "device.dispatch"]
        assert child.attrs["program"] == "slow_tile"
        assert child.attrs["plane"] == "stages"
        assert (child.attrs["rows"], child.attrs["tiles"]) == (5, 1)
        assert child.attrs["wait_s"] >= 0.01
        e = devobs.snapshot()[("stages", "slow_tile")]
        assert child.duration == pytest.approx(e["wall_s"])
        assert _hist_count("device.dispatch.seconds") == agg + 1
    finally:
        mx.enable(was)


# ===================================================================
# the glue by part: exclusive, closed vocabulary, summing to the glue
# ===================================================================


class _Clock:
    """`devobs`'s clock in the test's hands: it counts its reads, and
    time passes only where the test says so."""

    def __init__(self, monkeypatch):
        self.now, self.reads = 100.0, 0
        monkeypatch.setattr(devobs, "time", self)

    def monotonic(self):
        self.reads += 1
        return self.now

    def passes(self, seconds):
        self.now += seconds

    def frame(self, seconds):
        """A dispatch frame that waits `seconds` for its read-back."""
        with devobs.dispatch("clock_prog", rows=1) as frame:
            with frame.wait():
                self.passes(seconds)


def test_glue_is_exclusive_of_frames_and_nested_parts(monkeypatch):
    """A part is billed its own host time: not the frame it encloses
    (that is stage / wait), not the part nested in it; what no block
    claimed is `other`; the seven counters sum to the integer."""
    clock = _Clock(monkeypatch)
    before = _glue_counters("verify")
    with devobs.plane("verify"):
        with devobs.glue("encode"):
            clock.passes(0.02)
            clock.frame(0.05)  # a frame inside a part
            with devobs.glue("hostec"):  # a part inside a part
                clock.passes(0.04)
                clock.frame(0.05)
        with devobs.glue("encode"):  # entered twice: added up
            clock.passes(0.01)
        clock.passes(0.03)  # claimed by no block
    d = _moved(before, _glue_counters("verify"))
    assert d == {"span": 200_000, "stage": 0, "wait": 100_000,
                 "glue": 100_000, "parse": 0, "hostec": 40_000,
                 "encode": 30_000, "decode": 0, "challenge": 0,
                 "other": 30_000}
    # the ledger and the operator's view say the same in seconds
    p = devobs.plane_snapshot()["verify"]
    assert p["span_s"] == pytest.approx(
        p["stage_s"] + p["wait_s"] + p["glue_s"], abs=1e-9)
    assert p["glue_parts"] == {k: d[k] / 1e6 for k in _GLUE_KEYS}
    assert sum(p["glue_parts"].values()) == pytest.approx(p["glue_s"])
    hp = devobs.health_section()["planes"]["verify"]
    assert hp["glue_parts"] == pytest.approx(p["glue_parts"])
    assert benchschema.validate_device(devobs.section()) == []


def test_glue_takes_five_names_and_needs_a_plane_span(monkeypatch):
    for part in ("other", "hash", "", "Parse"):
        with pytest.raises(ValueError, match="unknown glue part"):
            devobs.glue(part)
    clock = _Clock(monkeypatch)
    before = _glue_counters("stages")
    # no plane span open: a passthrough, as with the ledger off
    assert all(devobs.glue(part) is devobs._NULL
               for part in devobs.GLUE_PARTS)
    with devobs.glue("parse"):
        pass
    assert clock.reads == 0
    # a frame is no plane span, and inside an open frame the time is the
    # frame's: a block there is billed to no part
    with devobs.plane("stages"):
        with devobs.dispatch("glue_prog", rows=1):
            assert devobs.glue("decode") is devobs._NULL
    with devobs.dispatch("glue_prog", rows=1):
        assert devobs.glue("decode") is devobs._NULL
    d = _moved(before, _glue_counters("stages"))
    assert d["glue"] == d["other"]
    assert all(d[k] == 0 for k in devobs.GLUE_PARTS)


def _mixed_transfer_block():
    """A real `BatchedTransferVerifier.verify` of eight rows of six
    shapes with four seeded faults (`tests/test_mixed_shapes.py`), four
    times over: the stand-ins compute a row they have seen once, the
    glue works on all 32, and beside it the fixed cost of some twenty
    dispatches' bookkeeping (`other`) is small, as it is served."""
    import test_mixed_shapes as mixed
    from fabric_token_sdk_tpu.crypto import batch

    pp = mixed._pp()
    _shapes, rows, places = mixed.seeded_block(pp, 7, "shuffled")
    want = [i not in places for i in range(len(rows))] * 4
    rows = rows * 4
    return (lambda: batch.BatchedTransferVerifier(pp).verify(rows).tolist(),
            want)


def _schnorr_block():
    from fabric_token_sdk_tpu.crypto import sign
    from fabric_token_sdk_tpu.crypto.batch_sign import BatchedSchnorrVerifier

    rng = random.Random(0x5167)
    # a block's worth of rows: beside the rows' work the fixed cost of
    # three dispatches' bookkeeping (`other`) is small, as it is served
    keys = [sign.keygen(rng) for _ in range(8)]
    rows = [(k.public.point, b"pay %d" % i, k.sign(b"pay %d" % i, rng))
            for i, k in enumerate(keys * 8)]
    rows[3] = (rows[3][0], b"pay another", rows[3][2])
    rows[5] = (rows[5][0], rows[5][1], b"\x00not a signature")
    want = [True] * 64
    want[3], want[5] = False, None
    return lambda: BatchedSchnorrVerifier().verify(rows), want


_PLANE_BLOCKS = [
    ("verify", _mixed_transfer_block, devobs.GLUE_PARTS),
    ("sign", _schnorr_block, ("parse", "encode", "decode", "challenge")),
]


@pytest.mark.parametrize("plane,block_of,parts", _PLANE_BLOCKS,
                         ids=["verify", "sign"])
def test_a_real_call_bills_its_glue_to_the_parts(monkeypatch, plane,
                                                 block_of, parts):
    """After a real verifier call over exact host stand-ins for the
    kernels: `glue_us` = the five parts + `other_us` to the integer,
    every part the plane has is non-zero, and the named parts are nine
    tenths of the glue or more."""
    import hostplane

    hostplane.install(monkeypatch)
    run, want = block_of()
    before = _glue_counters(plane)
    assert run() == want
    d = _moved(before, _glue_counters(plane))
    assert d["span"] == d["stage"] + d["wait"] + d["glue"]
    assert d["glue"] == sum(d[k] for k in _GLUE_KEYS) > 0
    assert all(d[k] > 0 for k in parts), d
    assert all(d[k] == 0 for k in devobs.GLUE_PARTS if k not in parts), d
    assert d["other"] >= 0
    assert sum(d[k] for k in parts) >= 0.9 * d["glue"], d
    assert set(devobs.plane_snapshot()) == {plane}
    p = devobs.plane_snapshot()[plane]
    assert p["calls"] == 1
    assert p["glue_parts"] == pytest.approx({k: d[k] / 1e6 for k in _GLUE_KEYS})


@pytest.mark.parametrize("plane,block_of,_parts", _PLANE_BLOCKS,
                         ids=["verify", "sign"])
def test_ledger_never_perturbs_a_planes_verdicts(monkeypatch, plane,
                                                 block_of, _parts):
    """The verifiers whose glue is billed by part, ledger on and off:
    the same verdicts, and off no part's counter moves."""
    import hostplane

    hostplane.install(monkeypatch)
    run, want = block_of()
    monkeypatch.setenv("FTS_DEVOBS", "1")
    assert run() == want
    assert set(devobs.plane_snapshot()) == {plane}
    monkeypatch.setenv("FTS_DEVOBS", "0")
    devobs.reset()
    before = _glue_counters(plane)
    assert run() == want
    assert _glue_counters(plane) == before
    assert devobs.plane_snapshot() == {} and devobs.snapshot() == {}


@pytest.mark.parametrize("n_txs", [2, 64])
def test_glue_is_entered_per_batch_not_per_row(monkeypatch, n_txs):
    """The same fifteen `glue` blocks a transfer verify (the budget is
    40), at 2 transactions as at 64 (the stand-ins compute a row they
    have seen once, so 64 copies of one two-output transfer cost one)."""
    import collections

    import hostplane
    import test_mixed_shapes as mixed
    from fabric_token_sdk_tpu.crypto import batch

    hostplane.install(monkeypatch)
    pp = mixed._pp()
    row = mixed.make_row(pp, (2, 2), random.Random("entries"))
    entered = collections.Counter()
    inner = devobs.glue

    def counted(part):
        entered[part] += 1
        return inner(part)

    monkeypatch.setattr(devobs, "glue", counted)
    ok = batch.BatchedTransferVerifier(pp).verify([row] * n_txs)
    assert ok.all() and len(ok) == n_txs
    assert sum(entered.values()) <= 40
    # transfer + wf + range proofs; wf + equality rows; wf + membership
    # (rows, pairing legs) + equality; and a read-back and a challenge
    # loop each in wf, membership and equality
    assert entered == {"parse": 3, "hostec": 2, "encode": 4, "decode": 3,
                       "challenge": 3}


# ===================================================================
# every dispatch goes through one of the two seams the benchmark wraps
# ===================================================================


def _toy_pairing(monkeypatch):
    """The row-wise stand-ins of tests/test_pairing_tiles.py for the
    three pairing tile programs: the walk, its frames and its padding
    are the real ones, and a tile takes milliseconds, not seconds."""
    import test_pairing_tiles as toys

    monkeypatch.setattr(pr, "miller_loop", toys._toy_miller)
    monkeypatch.setattr(pr, "_product_rows", toys._toy_product)
    monkeypatch.setattr(pr, "final_exp", toys._toy_final_exp)


def _frames():
    return sum(e["dispatches"] for e in devobs.snapshot().values())


def _wrap_the_seams(monkeypatch):
    """Wrap `stages.run_rows` and `pairing.pairing_product_staged` on
    their modules, as `benchmark/run.py:install_spans` does; each
    wrapper counts its calls and the frames the ledger gained inside."""
    seen = {"frames": 0}

    def wrap(owner, attr):
        inner = getattr(owner, attr)

        @functools.wraps(inner)
        def wrapped(*a, **kw):
            before = _frames()
            try:
                return inner(*a, **kw)
            finally:
                seen["frames"] += _frames() - before
                seen[attr] = seen.get(attr, 0) + 1

        monkeypatch.setattr(owner, attr, wrapped)

    wrap(st, "run_rows")
    wrap(pr, "pairing_product_staged")
    return seen


def _ps_batch(rng):
    from fabric_token_sdk_tpu.crypto import batch, pssign

    signer = pssign.keygen(1, rng)
    msgs = [[3], [1]]
    sigs = [signer.sign(m, rng) for m in msgs]
    return lambda: batch.BatchedPSVerifier(signer.pk, signer.Q).verify(
        msgs, sigs)


def _schnorr_batch(rng):
    from fabric_token_sdk_tpu.crypto import sign
    from fabric_token_sdk_tpu.crypto.batch_sign import BatchedSchnorrVerifier

    keys = [sign.keygen(rng) for _ in range(2)]
    rows = [(k.public.point, b"pay", k.sign(b"pay", rng)) for k in keys]
    return lambda: BatchedSchnorrVerifier().verify(rows)


def _prove_batch(rng):
    from fabric_token_sdk_tpu.crypto import token as tok, transfer as tr
    from fabric_token_sdk_tpu.crypto.setup import setup

    pp = setup(base=4, exponent=2, rng=random.Random(0xF75))
    in_toks, in_w = tok.tokens_with_witness([5, 10], "USD", pp.ped_params, rng)
    out_toks, out_w = tok.tokens_with_witness([7, 8], "USD", pp.ped_params, rng)
    reqs = [(in_w, out_w, in_toks, out_toks)]
    return lambda: tr.TransferProver.batch(reqs, pp, rng=rng, min_batch=1)


@pytest.mark.parametrize("plane,batch_of", [
    ("verify", _ps_batch), ("sign", _schnorr_batch), ("prove", _prove_batch),
])
def test_no_dispatch_goes_round_the_two_seams(monkeypatch, rng, plane,
                                              batch_of):
    """The benchmark builds its trace breakdown from wrappers it puts on
    `stages.run_rows` and `pairing.pairing_product_staged` at run time:
    every frame a plane's ledger gains must be opened inside one of the
    two, reached through the module attribute."""
    _toy_pairing(monkeypatch)
    run = batch_of(rng)
    seen = _wrap_the_seams(monkeypatch)
    fallbacks = _counters("batch.prove.host_fallbacks", "batch.prove.host")
    run()
    assert fallbacks == _counters("batch.prove.host_fallbacks",
                                  "batch.prove.host")
    assert {pl for pl, _prog in devobs.snapshot()} == {plane}
    assert seen["frames"] == _frames() > 0
    assert seen["run_rows"] > 0
    if plane != "sign":  # Schnorr rows have no pairing
        assert seen["pairing_product_staged"] > 0
        assert {"miller_tile", "fexp_tile"} <= {
            prog for _pl, prog in devobs.snapshot()}


# ===================================================================
# per block: the planes' share in `block.commit`
# ===================================================================

_SHARE_FIELDS = ("verify_frames_s", "verify_wait_s", "verify_glue_s",
                 "sign_frames_s", "sign_wait_s", "sign_glue_s")


def _last_block_commit():
    return [e for e in mx.FLIGHT.tail(200) if e["kind"] == "block.commit"][-1]


def _scenario_network(policy):
    pp = FabTokenPublicParams()
    network = Network(RequestValidator(FabTokenDriver(pp)), policy=policy)
    parties = {
        name: Party(name, FabTokenDriver(pp), network)
        for name in ("issuer-node", "alice-node", "bob-node")
    }
    return network, parties


def test_block_commit_carries_the_sign_planes_share():
    """A block whose signatures rode the device sign plane says how that
    time divides; a block the policy kept on the host carries zeros."""
    sign_on = BlockPolicy(max_block_txs=8, sign_batched=True, sign_min_batch=2)
    statuses = _run_scenario(sign_on)
    assert statuses == [TxStatus.VALID, TxStatus.INVALID]
    blk = _last_block_commit()
    assert all(f in blk for f in _SHARE_FIELDS)
    assert blk["sign_verify_s"] > 0
    assert blk["sign_frames_s"] > 0 and blk["sign_wait_s"] > 0
    assert blk["sign_glue_s"] > 0  # parse, encode, decode, Fiat-Shamir
    assert blk["sign_wait_s"] <= blk["sign_frames_s"]
    assert blk["sign_frames_s"] + blk["sign_glue_s"] <= blk["sign_verify_s"]
    # fabtoken has no proof plane: the verify fields are there, and zero
    assert blk["device_verify_s"] == 0
    assert [blk[f] for f in _SHARE_FIELDS[:3]] == [0, 0, 0]
    assert {pl for pl, _prog in devobs.snapshot()} == {"sign"}

    devobs.reset()
    _run_scenario(BlockPolicy(max_block_txs=8, sign_batched=False))
    blk = _last_block_commit()
    assert [blk[f] for f in _SHARE_FIELDS] == [0] * 6
    assert devobs.snapshot() == {}


class _StubVerifier:
    """A batched proof verifier of the real shape (a `_spanned` verify
    over `run_rows` stages with host work between them) without the
    real programs' compile time."""

    def __init__(self):
        from fabric_token_sdk_tpu.crypto.batch import _spanned

        self.verify = _spanned("batch.transfer.verify")(self._verify)

    def _verify(self, rows):
        a = np.arange(len(rows) * 2, dtype=np.int32).reshape(-1, 1)
        st.run_rows(_slow_tile(0.01), a)
        time.sleep(0.02)
        st.run_rows(_slow_tile(0.01), a)
        return [True] * len(rows)


class _StubDriver:
    def transfer_batch_plan(self, action):
        return ((1, 1), (action,))

    def batch_verifier(self):
        return _StubVerifier()


def test_proof_verdicts_put_the_verify_planes_share_into_timings():
    from types import SimpleNamespace

    from fabric_token_sdk_tpu.services.network.orderer import (
        BlockValidationPipeline,
    )

    requests = [
        SimpleNamespace(transfers=[SimpleNamespace(action=b"a%d" % i)])
        for i in range(3)
    ]
    validator = SimpleNamespace(driver=_StubDriver())
    timings = {}
    verdicts = BlockValidationPipeline(
        validator, BlockPolicy(min_batch=2)
    ).proof_verdicts(requests, timings)
    assert verdicts == {0: {0: True}, 1: {0: True}, 2: {0: True}}
    assert timings["verify_wait_s"] >= 0.02
    assert timings["verify_glue_s"] >= 0.02
    assert timings["verify_frames_s"] >= timings["verify_wait_s"]
    assert (timings["verify_frames_s"] + timings["verify_glue_s"]
            <= timings["device_verify_s"])
    # kept on the host by the policy: zeros, and no frame
    devobs.reset()
    timings = {}
    BlockValidationPipeline(
        validator, BlockPolicy(min_batch=9)
    ).proof_verdicts(requests, timings)
    assert [timings[f] for f in _SHARE_FIELDS[:3]] == [0, 0, 0]
    assert devobs.snapshot() == {}


# ===================================================================
# on the profiler's clock
# ===================================================================


def _fts_events(trace_dir):
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    return [
        (ev.name, ev.start_ns, ev.duration_ns)
        for p in ProfileData.from_file(path).planes
        if p.name.startswith("/host:")
        for line in p.lines for ev in line.events
        if ev.name.startswith("fts:")
    ]


def test_frames_are_on_the_profilers_clock(tmp_path, monkeypatch):
    """Under a `jax.profiler` session one `run_rows` call leaves a
    `fts:<plane>:<program>` event per tile and a `fts:wait:...` event
    per read-back in the host plane, a `glue` block its
    `fts:<plane>:glue:<part>` and `annotate` its `fts:<name>`; outside
    a session, and with the ledger off, nothing is recorded."""
    import jax

    from fabric_token_sdk_tpu.crypto.batch import _spanned

    rows = np.zeros((12, 1), dtype=np.int32)
    st.run_rows(_slow_tile(0.001), rows)  # no session: nothing recorded

    @_spanned("batch.wf.verify")
    def verify():
        with devobs.annotate("validate"):
            pass
        with devobs.glue("encode"):
            time.sleep(0.001)
            with devobs.glue("hostec"):
                time.sleep(0.001)
        st.run_rows(_slow_tile(0.001), rows)
        with devobs.glue("decode"):
            time.sleep(0.001)

    jax.profiler.start_trace(str(tmp_path / "on"))
    try:
        st.run_rows(_slow_tile(0.001), rows)
        verify()
    finally:
        jax.profiler.stop_trace()
    events = _fts_events(str(tmp_path / "on"))
    names = [n for n, _s, _d in events]
    assert names.count("fts:stages:slow_tile") == 2  # one per tile
    assert names.count("fts:wait:stages:slow_tile") == 2
    assert names.count("fts:verify:slow_tile") == 2
    assert names.count("fts:wait:verify:slow_tile") == 2
    assert names.count("fts:verify") == 1  # the plane span
    assert names.count("fts:validate") == 1
    # a read-back lasts at least as long as the result blocked
    assert all(d >= 1e6 for n, _s, d in events if n.startswith("fts:wait:"))
    # the plane span covers its tiles and read-backs
    (span,) = [e for e in events if e[0] == "fts:verify"]
    for n, s, d in events:
        if n.endswith("verify:slow_tile"):
            assert span[1] <= s and s + d <= span[1] + span[2]
    # and its glue blocks, one event each, which overlap no tile and no
    # read-back: an instant of the span has one name
    glue = [e for e in events if e[0].startswith("fts:verify:glue:")]
    assert sorted(n for n, _s, _d in glue) == [
        f"fts:verify:glue:{part}" for part in ("decode", "encode", "hostec")]
    frames = [e for e in events if e[0].endswith("verify:slow_tile")]
    for _n, s, d in glue:
        assert d >= 1e6
        assert span[1] <= s and s + d <= span[1] + span[2]
        assert all(s + d <= fs or fs + fd <= s for _fn, fs, fd in frames)
    # the nested block lies inside the one that encloses it
    by = {n.rsplit(":", 1)[1]: (s, s + d) for n, s, d in glue}
    assert by["encode"][0] <= by["hostec"][0] <= by["hostec"][1] <= by["encode"][1]
    assert by["encode"][1] <= by["decode"][0]

    monkeypatch.setenv("FTS_DEVOBS", "0")
    jax.profiler.start_trace(str(tmp_path / "off"))
    try:
        verify()
    finally:
        jax.profiler.stop_trace()
    assert _fts_events(str(tmp_path / "off")) == []


def test_off_moves_no_plane_counter_and_no_timing(monkeypatch):
    monkeypatch.setenv("FTS_DEVOBS", "0")
    names = [f"device.{pl}.{k}_us" for pl in ("stages", "verify")
             for k in ("span", "stage", "wait", "glue")]
    names += [f"device.verify.glue.{k}_us" for k in _GLUE_KEYS]
    before = _counters(*names)
    agg = _hist_count("device.dispatch.seconds")
    clock = _Clock(monkeypatch)
    with devobs.plane("verify"):
        with devobs.glue("encode"):
            rows = np.ones((3, 1), dtype=np.int32)
        out = st.run_rows(_slow_tile(0.0), rows)
        with devobs.glue("decode"):
            assert (out == 2).all()
    assert clock.reads == 0
    with devobs.dispatch("offtest_prog", rows=1) as frame:
        with frame.tile(), frame.wait():
            pass
    assert devobs.snapshot() == {} and devobs.plane_snapshot() == {}
    assert _counters(*names) == before
    assert _hist_count("device.dispatch.seconds") == agg
    assert devobs.health_section()["planes"] == {}
