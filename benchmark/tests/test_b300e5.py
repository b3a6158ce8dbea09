"""The cell `b300e5.batches` (`zkatdlog-b300e5` x `batches8-b300e5`): what
its plan holds, and that a CPU rehearsal of it reads `correct: true`.

    python3 -m pytest benchmark/tests/test_b300e5.py -k plan          (seconds)
    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_b300e5.py (minutes)

The rehearsal runs the whole harness at the tiny sizes of the two files'
`rehearsal` blocks, as the other zkatdlog cells rehearse: one-in/one-out
transfers (no pairing program), public parameters at base 300, exponent 5.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(BENCH, "harness"))

import manifest as mf  # noqa: E402
import schedule  # noqa: E402

CELL = "b300e5.batches"
SECONDS = 51.0


@pytest.fixture(scope="module")
def cell():
    return mf.cell(mf.load(), CELL)


@pytest.mark.parametrize("seed", [1, 2, 7, 2_147_483_867, 3_000_000_015])
def test_plan_holds_the_same_work_for_every_seed(cell, seed):
    mix, bad = cell["mix"], cell["config"]["bad_requests"]
    plan = schedule.plan(mix, bad, SECONDS, seed)
    due = [e for e in plan if 0.0 <= e["due_s"] < SECONDS]
    assert len(due) == 64 == round(mix["rate_tps"] * SECONDS)
    assert len(plan) == 64 + round(mix["rate_tps"] * mix["warm_s"])
    # six disjoint hand-overs of 8, each one call, where the mix says
    joint = {}
    for e in plan:
        if "joint" in e:
            joint.setdefault(e["joint"], []).append(e)
    assert sorted(joint) == list(range(6))
    for k, share in joint.items():
        assert len(share) == 8  # (3.5 % of seeds hold a short fifth one, below)
        assert {e["due_s"] for e in share} == {mix["joint"][k]["at_share"] * SECONDS}
        assert [e["i"] for e in share] == list(range(share[0]["i"], share[0]["i"] + 8))
        assert all(e["kind"] == "ok" for e in share)
    assert len({e["i"] for share in joint.values() for e in share}) == 48
    assert [e["due_s"] for e in plan] == sorted(e["due_s"] for e in plan)
    # the other 16 arrive alone; the three bad requests sit among them, early
    # enough to be judged in the window (`_place_bad` needs 8 such places)
    single = [e for e in due if "joint" not in e]
    last = mix["bad_before_share"] * SECONDS
    assert len(single) == 16
    assert sum(1 for e in single if e["due_s"] <= last) >= 8
    bad_ones = [e for e in plan if e["kind"] != "ok"]
    assert sorted(e["kind"] for e in bad_ones) == sorted(bad)
    assert all("joint" not in e and 0.0 <= e["due_s"] <= last for e in bad_ones)
    # the hand-overs and the traced slice sit where ISSUE 28 names them: the
    # slice opens just before the last hand-over
    assert [j["at_share"] for j in mix["joint"]] == [0.04, 0.19, 0.34, 0.49, 0.64, 0.79]
    assert mix["trace"]["at_share"] == 0.788
    assert 0.0 < (mix["joint"][-1]["at_share"] - mix["trace"]["at_share"]) * SECONDS < 0.5


def test_no_seed_is_refused_and_short_handovers_are_rare(cell):
    """The generator takes a hand-over's 8 from the arrivals due after its
    time: every seed must plan (a `ValueError` would fail the run) with 64
    due and 16 or more single places for the bad requests, and all but a
    few percent of seeds with six whole hand-overs (where fewer than 8
    arrivals are left after the last one's time it is clamped back onto the
    window's last 8, and the fifth keeps the rest)."""
    import random

    mix, bad = cell["mix"], cell["config"]["bad_requests"]
    rng = random.Random(28)
    whole = 0
    seeds = [rng.randrange(0, 2 ** 31 + 1000) for _ in range(400)]
    for seed in seeds:
        plan = schedule.plan(mix, bad, SECONDS, seed)
        due = [e for e in plan if 0.0 <= e["due_s"] < SECONDS]
        assert len(due) == 64
        assert sum(1 for e in due if "joint" not in e) >= 16
        sizes = {}
        for e in plan:
            if "joint" in e:
                sizes[e["joint"]] = sizes.get(e["joint"], 0) + 1
        assert sorted(sizes) == list(range(6)) and sizes[5] == 8
        assert all(sizes[k] == 8 for k in range(4)) and 2 <= sizes[4] <= 8
        whole += sizes[4] == 8
    assert whole >= 0.94 * len(seeds)


def test_the_cell_is_the_sample_s_parameters_and_reports_what_it_should(cell):
    cfg = cell["config"]
    assert cfg["tokengen"] == {"driver": "dlog", "base": 300, "exponent": 5}
    assert sorted(cfg["reduced"]) == ["idemix_owners", "nodes"]
    assert "policy" not in cfg  # BlockPolicy() defaults, as zkatdlog-fungible
    other = mf.cell(mf.load(), "zk22.steady")["config"]
    for key in ("guarantees", "bad_requests", "warm_programs", "warm_block_txs",
                "rehearsal"):
        assert cfg[key] == other[key], key
    assert {m["name"] for m in cell["end_to_end"]} == {
        "committed_tps", "finality_p50_s", "setup_s"}
    mix = cell["mix"]
    assert sum(mix["transfer"]["in_values"]) == sum(mix["transfer"]["out_values"])
    # every amount fits in five base-300 digits, and each side has one that
    # needs the fifth
    for side in (mix["transfer"]["in_values"], mix["transfer"]["out_values"]):
        assert all(0 < v < 300 ** 5 for v in side)
        assert max(side) >= 300 ** 4
    names = {m["name"] for m in cell["per_layer"]}
    assert {"verify.membership_per_tx", "verify.device_share.b300e5",
            "wire.kb_per_tx", "wire.recv_ms_per_mb",
            # its pairing tiles are read where zk22.backlog's are
            "kernel.miller_tile_ms", "kernel.fexp_tile_ms",
            "kernel.fexp_roofline", "device.fexp_idle_share"} <= names
    assert all(m["moves"] == "committed_tps" for m in cell["per_layer"])


def test_cpu_rehearsal_of_the_cell_reads_correct():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse-cpu", "--seconds", "20", "--seed", "3000000017",
         "--trace", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=1500)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    checks = {ln.split()[1].split("=")[0]: ln.split()[-1]
              for ln in lines if ln.startswith("check ")}
    line = json.loads(lines[-1])
    assert line["correct"] is True, checks
    assert set(checks.values()) == {"ok"}
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] == 20 and line["failed"] == 0
    # the wire's counters are read (one-in/one-out requests are ~10 KB)
    assert 5.0 < line["metrics"]["wire.kb_per_tx"]["value"] < 20.0
    assert line["metrics"]["wire.recv_ms_per_mb"]["value"] > 0.0
