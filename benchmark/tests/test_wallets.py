"""The cell `b300e5.wallets` (`zkatdlog-b300e5-wallets` x `wallets8-b300e5`):
what its plan holds for every seed, that every form is one the wallet
selector's rule assembles (`reference/wallet_selector.py`), that the manifest
lists the cell where ISSUE 37 says, and that a CPU rehearsal of it reads
`correct: true` (and `false` under a validator that accepts everything).

    python3 -m pytest benchmark/tests/test_wallets.py -k "not rehearsal"  (a minute)
    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_wallets.py    (minutes)

The rehearsal runs the whole harness at the tiny sizes of the two files'
`rehearsal` blocks: the seven forms under their names, one-in/one-out (no
pairing program on the CPU backend). The mixed shapes themselves are held to
the scalar reference by `tests/test_mixed_shapes.py` (tier-1).
"""

import collections
import json
import os
import random
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(BENCH, "harness"))
sys.path.insert(0, os.path.join(BENCH, "reference"))

import manifest as mf  # noqa: E402
import schedule  # noqa: E402
import wallet_selector as ws  # noqa: E402

CELL = "b300e5.wallets"
TWIN = "b300e5.batches"
SECONDS = 51.0

P12, P22, M11, P32, P21, P42, S81 = (
    "pay-1-2", "pay-2-2", "move-1-1", "pay-3-2", "pay-2-1", "pay-4-2",
    "sweep-8-1")
# ISSUE 37's table, in sending order
HANDOVERS = [
    [P12] * 3 + [P22] * 3 + [P32, P42],
    [P12] * 3 + [P22] * 2 + [P32, M11, S81],
    [P12] * 3 + [P22] * 2 + [P32, P42, M11],
    [P12] * 3 + [P22] * 3 + [P32, S81],
    [P12] * 2 + [P22] * 2 + [P32, P42, M11, P21],
    [P12] * 3 + [P22] * 2 + [P32, M11, P21],
]
SINGLES = {P12: 6, P22: 5, M11: 2, P32: 2, P21: 1}
SHAPES = {P12: (1, 2), P22: (2, 2), M11: (1, 1), P32: (3, 2), P21: (2, 1),
          P42: (4, 2), S81: (8, 1)}


@pytest.fixture(scope="module")
def cell():
    return mf.cell(mf.load(), CELL)


def _split(plan):
    due = [e for e in plan if 0.0 <= e["due_s"] < SECONDS]
    joint = collections.defaultdict(list)
    for e in plan:
        if "joint" in e:
            joint[e["joint"]].append(e)
    return due, joint


# ------------------------------------------------------------------ the plan


def test_every_seed_holds_the_same_six_blocks_and_the_same_singles(cell):
    """2,000 seeds, the driver's among them in size (up to a little over
    2**31): none refused, 64 due, the six compositions of ISSUE 37's table
    in sending order, the same 16 singles, the three bad kinds on single
    transfers inside the window."""
    mix, bad = cell["mix"], cell["config"]["bad_requests"]
    rng = random.Random(37)
    seeds = [1, 2, 7, 2_147_483_867, 3_000_000_015] + [
        rng.randrange(0, 2 ** 31 + 1000) for _ in range(1995)]
    for seed in seeds:
        plan = schedule.plan(mix, bad, SECONDS, seed)  # a refusal raises
        due, joint = _split(plan)
        assert len(due) == 64 == round(mix["rate_tps"] * SECONDS)
        assert len(plan) == 64 + round(mix["rate_tps"] * mix["warm_s"])
        assert sorted(joint) == list(range(6))
        for k, share in joint.items():
            assert [e["form"] for e in share] == HANDOVERS[k], seed
            assert {e["due_s"] for e in share} == {
                mix["joint"][k]["at_share"] * SECONDS}
            assert all(e["kind"] == "ok" for e in share)
        single = [e for e in due if "joint" not in e]
        assert collections.Counter(e["form"] for e in single) == SINGLES, seed
        bad_ones = [e for e in plan if e["kind"] != "ok"]
        assert sorted(e["kind"] for e in bad_ones) == sorted(bad)
        assert all("joint" not in e and 0.0 <= e["due_s"] < SECONDS
                   for e in bad_ones)


def test_the_table_s_counts(cell):
    mix = cell["mix"]
    assert [j["forms"] for j in mix["joint"]] == HANDOVERS
    assert [len(set(SHAPES[f] for f in h)) for h in HANDOVERS] == [4, 5, 5, 4, 6, 5]
    counts = schedule.form_counts(mix["requests"], 64)
    assert list(counts.values()) == [23, 19, 6, 8, 3, 3, 2]
    taken = collections.Counter(f for h in HANDOVERS for f in h)
    assert [taken[f] for f in counts] == [17, 14, 4, 6, 2, 3, 2]
    assert {f: counts[f] - taken[f] for f in counts if counts[f] - taken[f]} == SINGLES
    # every block is b300e5.batches' geometry once it is one call: 13-16
    # range outputs -> 65-80 membership rows, 260-320 Miller rows = 3 tiles
    # of 128, one final-exp dispatch; the traced one, the sixth, has 13
    outputs = [sum(SHAPES[f][1] for f in h if SHAPES[f] != (1, 1))
               for h in HANDOVERS]
    assert outputs == [16, 13, 14, 15, 13, 13]
    assert all(-(-n * 5 * 4 // 128) == 3 and n * 5 <= 128 for n in outputs)


def test_the_cell_is_the_twin_of_b300e5_batches(cell):
    """Same parameters, same `BlockPolicy()`, same stream of 64 arrivals and
    six hand-overs of 8 at the same instants: the two differ in the shapes
    alone, so the difference of their medians is the price of shapes."""
    twin = mf.cell(mf.load(), TWIN)
    for key in ("tokengen", "reduced", "bad_requests", "warm_programs",
                "warm_block_txs"):
        assert cell["config"][key] == twin["config"][key], key
    assert "policy" not in cell["config"] and "policy" not in twin["config"]
    for key in ("arrivals", "rate_tps", "warm_s", "grace_s", "min_gap_s",
                "bad_before_share", "handover", "committed_tps_rule"):
        assert cell["mix"][key] == twin["mix"][key], key
    # the slice is the twin's to the letter (ISSUE 37): the sixth hand-over,
    # opened by the pairing call's counter, 0.6 s long
    assert cell["mix"]["trace"] == twin["mix"]["trace"] == {
        "at_share": 0.788, "after_counter": "pairing.staged.calls", "for_s": 0.6}
    sixth = cell["mix"]["joint"][5]["at_share"]
    assert 0.0 < (sixth - cell["mix"]["trace"]["at_share"]) * SECONDS < 0.5
    assert [(j["at_share"], j["txs"]) for j in cell["mix"]["joint"]] == [
        (j["at_share"], j["txs"]) for j in twin["mix"]["joint"]]
    # the only key ISSUE 37 does not name, and what it asks for: whole
    # hand-overs, no rule about meetings
    assert cell["mix"]["joint_layout"] == {"meetings": 0, "meet_within_s": 0.0}
    assert "joint_layout_why" in cell["mix"]


# ----------------------------------------------------------- the selector


def test_the_selector_s_rule():
    assert ws.select([25500000000], 24123456789) == (
        [25500000000], [24123456789, 1376543211])
    assert ws.select([20, 5, 9], 25) == ([20, 5], [25])          # exact: no change
    assert ws.select([20, 5, 9], 26) == ([20, 5, 9], [26, 8])
    assert ws.select([3, 3, 3], 3) == ([3], [3])                  # none superfluous
    with pytest.raises(ws.InsufficientFunds):
        ws.select([20, 5], 26)
    with pytest.raises(ValueError):
        ws.select([20], 0)
    assert ws.is_selected([20, 5], [25])
    assert not ws.is_selected([20, 5, 9], [25, 9])   # the 9 was not needed
    assert not ws.is_selected([20, 5], [24])         # a unit went missing
    assert not ws.is_selected([20, 5], [12, 12, 1])  # two recipients' outputs


def test_every_form_of_the_mix_is_one_the_selector_assembles(cell):
    """No input is superfluous, outputs are payment or payment + change,
    values conserve, every amount fits five base-300 digits and each side
    has one that needs the fifth."""
    forms = schedule.forms_of(cell["mix"])
    assert list(forms) == [P12, P22, M11, P32, P21, P42, S81]
    for name, f in forms.items():
        assert f["op"] == "transfer"
        ins, outs = f["in_values"], f["out_values"]
        assert (len(ins), len(outs)) == SHAPES[name]
        assert ws.is_selected(ins, outs), name
        assert ws.select(ins, outs[0]) == (ins, outs)
        assert sum(ins) == sum(outs) == 25500000000
        assert sum(ins[:-1]) < outs[0]
        assert all(0 < v < 300 ** 5 for v in ins + outs)
        assert max(outs) >= 300 ** 4
    assert abs(sum(f["share"] for f in forms.values()) - 1.0) < 1e-9
    # the configuration states the same shapes and shares
    stated = cell["config"]["clients"]["shapes"]
    assert {n: (s["n_in"], s["n_out"]) for n, s in stated.items()} == SHAPES
    assert {n: s["share"] for n, s in stated.items()} == {
        n: f["share"] for n, f in forms.items()}
    # the rehearsal keeps names and shares, one-in/one-out
    small = cell["mix"]["rehearsal"]["requests"]
    assert [(f["form"], f["share"]) for f in small] == [
        (n, f["share"]) for n, f in forms.items()]
    assert all(len(f["in_values"]) == len(f["out_values"]) == 1 for f in small)


# ------------------------------------------------------------ the manifest


def test_the_manifest_lists_the_cell_where_its_twin_is_listed(cell):
    m = mf.load()
    assert mf.validate(m) == []
    cfg = [c for c in m["configs"] if c["name"] == "zkatdlog-b300e5-wallets"][0]
    assert cfg == m["configs"][-1] and m["workloads"][-1]["name"] == CELL
    assert cfg["reduced"] == ["nodes", "idemix_owners"]
    assert sorted(cell["config"]["reduced"]) == ["idemix_owners", "nodes"]
    assert cfg["source"] == cell["config"]["source"] and len(cfg["source"]) <= 200
    assert "fabric-samples token-sdk" in cfg["source"]
    assert "token/services/selector" in cfg["source"]
    assert cell["config"]["tokengen"] == {"driver": "dlog", "base": 300, "exponent": 5}
    assert m["workloads"][-1]["chips"] == 1
    assert {x["name"] for x in cell["end_to_end"]} == {
        "committed_tps", "finality_p50_s", "setup_s"}
    mine = {x["name"] for x in cell["per_layer"]}
    twin = {x["name"] for x in m["per_layer"] if TWIN in x["workloads"]}
    new = {"verify.txs_per_call", "verify.shapes_per_call"}
    # every metric that lists the twin lists the cell, the two readers of a
    # whole `final_exp` dispatch in the slice among them (no new kernel: the
    # same programs; the slice holds the block's one dispatch whole)
    assert mine == twin | new
    assert {"kernel.fexp_tile_ms", "kernel.fexp_roofline",
            "tiles.fexp_ms_per_tile", "kernel.miller_tile_ms",
            "device.fexp_idle_share", "device.window_idle_share"} <= mine
    for x in m["per_layer"]:
        if TWIN in x["workloads"]:
            assert x["workloads"][-1] == CELL  # appended, nothing else moved
    for x in m["per_layer"][-2:]:
        assert x["name"] in new and x["workloads"] == [CELL]
        assert (x["source"], x["moves"]) == ("program_counter", "finality_p50_s")
        assert x["layer"] == "stage A routing (orderer.py BlockValidationPipeline)"
        spec = mf._load(mf.data_file("layer_metrics", x["name"]))
        assert spec["reader"] == "counter_ratio"
        assert spec["den"] == ["batch.transfer.calls"]


def test_the_new_readers_read_nothing_from_a_program_without_the_counter():
    """The parent of PR 37 has no `batch.transfer.calls`: the harness
    snapshots the name at 0, the ratio's denominator does not move, and the
    metric is left out of the line (no raise)."""
    import readers

    spec = mf._load(mf.data_file("layer_metrics", "verify.txs_per_call"))
    src = readers.Sources(
        events=[], seconds=SECONDS, grace_s=10.0,
        counters={"batch.transfer.txs": 48, "batch.transfer.calls": 0},
        histograms={}, blocks=[], dispatch={}, trace={}, device_kind="cpu")
    assert readers.read(src, spec) is None
    src.counters["batch.transfer.calls"] = 6
    assert readers.read(src, spec) == 8.0


# ----------------------------------------------------------- the rehearsal


def _run(argv):
    out = subprocess.run(
        [sys.executable, *argv, "--workload", CELL, "--rehearse-cpu",
         "--seconds", "20", "--seed", "3000000017"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=1500)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    checks = {ln.split()[1].split("=")[0]: ln.split()[-1]
              for ln in lines if ln.startswith("check ")}
    return json.loads(lines[-1]), checks


def test_cpu_rehearsal_of_the_cell_reads_correct():
    line, checks = _run([os.path.join(BENCH, "run.py"), "--trace", "1"])
    assert line["correct"] is True, checks
    assert set(checks.values()) == {"ok"}
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] == 20 and line["failed"] == 0
    # the two hand-overs of four rode one plane call each, whatever else did
    assert line["metrics"]["verify.txs_per_call"]["value"] >= 2.0
    assert line["metrics"]["verify.shapes_per_call"]["value"] == 1.0  # all (1,1)


def test_cpu_rehearsal_under_an_accept_all_validator_reads_incorrect():
    line, checks = _run([
        os.path.join(HERE, "drive_broken.py"), "accept_all",
        os.path.join(BENCH, "traffic", "wallets8-b300e5.json"), "--trace", "0"])
    assert line["correct"] is False
    assert checks["verdicts_differing_from_scalar_reference"] == "FAILED"
    assert checks["verdicts_differing_from_construction"] == "FAILED"
