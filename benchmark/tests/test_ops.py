"""The cell `b300e5.ops` (`zkatdlog-b300e5-ops` x `ops8-b300e5`): what its
plan holds for every seed, that every form keeps the rules of the sample's
three operations (`reference/sample_ops.py`), that a group's run through
the scalar reference leaves the ledger those rules say it must, that the
manifest lists the cell where ISSUE 42 says, and that a CPU rehearsal of it
reads `correct: true` (and `false` under a validator that accepts
everything).

    python3 -m pytest benchmark/tests/test_ops.py -k "not rehearsal"   (a minute)
    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_ops.py    (minutes)

The rehearsal runs the whole harness at the tiny sizes of the two files'
`rehearsal` blocks: the three forms under their names, transfers and
redeems one-in/one-out (no range proof), the issues sent as singles, which
the host verifies: no pairing program on the CPU backend. An issue's rows
on the plane are held to the scalar reference by `tests/test_issue_plane.py`
(tier-1).
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
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "harness"))
sys.path.insert(0, os.path.join(BENCH, "reference"))

import manifest as mf  # noqa: E402
import sample_ops as ops  # noqa: E402
import schedule  # noqa: E402

CELL = "b300e5.ops"
SIBLINGS = ("b300e5.batches", "b300e5.wallets")
SECONDS = 51.0
TOP = 300 ** 5

PAY, TOPUP, CASHOUT = "pay-2-2", "topup", "cashout"
# ISSUE 42: every hand-over, in sending order
HANDOVER = [PAY, TOPUP, PAY, PAY, CASHOUT, PAY, PAY, PAY]
SINGLES = {PAY: 9, TOPUP: 4, CASHOUT: 3}
# the rehearsal's seed and window: the three single top-ups fall before the
# first hand-over, so none waits behind a hand-over's block with other
# singles (four planned records in a block would ride the plane: the
# files' rehearsal notes)
REHEARSAL = ["--seconds", "40", "--seed", "3000000054"]


@pytest.fixture(scope="module")
def cell():
    return mf.cell(mf.load(), CELL)


def _split(plan, seconds=SECONDS):
    due = [e for e in plan if 0.0 <= e["due_s"] < seconds]
    joint = collections.defaultdict(list)
    for e in plan:
        if "joint" in e:
            joint[e["joint"]].append(e)
    return due, joint


# ------------------------------------------------------------------ the plan


def test_every_seed_holds_the_same_six_blocks_and_the_same_singles(cell):
    """2,000 seeds, the driver's among them in size (up to a little over
    2**31): none refused, 64 due, six hand-overs of ISSUE 42's composition,
    the same 16 singles, the three bad kinds on single transfers inside the
    window."""
    mix, bad = cell["mix"], cell["config"]["bad_requests"]
    rng = random.Random(42)
    seeds = [1, 2, 7, 2_147_483_867, 3_000_000_015] + [
        rng.randrange(0, 2 ** 31 + 1000) for _ in range(1995)]
    for seed in seeds:
        plan = schedule.plan(mix, bad, SECONDS, seed)  # a refusal raises
        due, joint = _split(plan)
        assert len(due) == 64 == round(mix["rate_tps"] * SECONDS)
        assert len(plan) == 64 + round(mix["rate_tps"] * mix["warm_s"])
        assert sorted(joint) == list(range(6))
        for k, share in joint.items():
            assert [e["form"] for e in share] == HANDOVER, seed
            assert {e["due_s"] for e in share} == {
                mix["joint"][k]["at_share"] * SECONDS}
            assert all(e["kind"] == "ok" for e in share)
        single = [e for e in due if "joint" not in e]
        assert collections.Counter(e["form"] for e in single) == SINGLES, seed
        bad_ones = [e for e in plan if e["kind"] != "ok"]
        assert sorted(e["kind"] for e in bad_ones) == sorted(bad)
        assert all("joint" not in e and 0.0 <= e["due_s"] < SECONDS
                   and e["form"] == PAY for e in bad_ones)


def test_the_issue_s_counts(cell):
    mix = cell["mix"]
    assert [j["forms"] for j in mix["joint"]] == [HANDOVER] * 6
    assert [j["at_share"] for j in mix["joint"]] == [
        0.04, 0.19, 0.34, 0.49, 0.64, 0.79]
    counts = schedule.form_counts(mix["requests"], 64)
    assert counts == {PAY: 45, TOPUP: 10, CASHOUT: 9}
    taken = collections.Counter(HANDOVER * 6)
    assert {f: counts[f] - taken[f] for f in counts} == SINGLES
    # ten issues a window, six of them in hand-overs: the share of issue
    # records the plane can verify is 60 % (`verify.issue_device_share`)
    assert 100.0 * taken[TOPUP] / counts[TOPUP] == 60.0
    # a hand-over block: seven (2,2) rows and the issue's one output = 15
    # range outputs = 75 membership rows = 150 Miller rows in two tiles of
    # 128 and one final-exp dispatch; the parent's call holds 70 and 140
    outputs = 2 * (HANDOVER.count(PAY) + HANDOVER.count(CASHOUT)) + 1
    assert (outputs, outputs * 5, -(-outputs * 5 * 2 // 128)) == (15, 75, 2)
    assert -(-(outputs - 1) * 5 * 2 // 128) == 2 and outputs * 5 <= 128


def test_the_cell_is_the_sibling_of_b300e5_batches_and_wallets(cell):
    """Same parameters, same `BlockPolicy()`, same stream of 64 arrivals and
    six hand-overs of 8 at the same instants: the three differ in what a
    slot sends alone."""
    m = mf.load()
    for name in SIBLINGS:
        twin = mf.cell(m, name)
        for key in ("tokengen", "bad_requests", "warm_programs",
                    "warm_block_txs"):
            assert cell["config"][key] == twin["config"][key], (name, key)
        assert sorted(cell["config"]["reduced"]) == sorted(twin["config"]["reduced"])
        assert "policy" not in cell["config"] and "policy" not in twin["config"]
        for key in ("arrivals", "rate_tps", "warm_s", "grace_s", "min_gap_s",
                    "bad_before_share", "handover", "committed_tps_rule"):
            assert cell["mix"][key] == twin["mix"][key], (name, key)
        assert [(j["at_share"], j["txs"]) for j in cell["mix"]["joint"]] == [
            (j["at_share"], j["txs"]) for j in twin["mix"]["joint"]]
        # the slice opens where the siblings' does and is shorter
        for key in ("at_share", "after_counter"):
            assert cell["mix"]["trace"][key] == twin["mix"]["trace"][key]
    assert 0.45 <= cell["mix"]["trace"]["for_s"] <= 0.5
    assert "trace_why" in cell["mix"]
    assert cell["mix"]["joint_layout"] == {"meetings": 0, "meet_within_s": 0.0}
    # zkatdlog-b300e5's five guarantees, the first read over all three
    # operations, and the one the operations bring
    base = mf.cell(m, "b300e5.batches")["config"]["guarantees"]
    mine = cell["config"]["guarantees"]
    assert len(mine) == len(base) + 1 and mine[1:3] == base[1:3]
    assert mine[4] == base[4]
    assert "issue, transfer and redeem" in mine[0]
    assert "spendable" in mine[5] and "redeemed output never" in mine[5]


# ---------------------------------------------------------- the operations


def test_the_rules_of_the_three_operations():
    forms = {
        "pay": {"op": "transfer", "in_values": [20, 5], "out_values": [24, 1]},
        "topup": {"op": "issue", "out_values": [24]},
        "cashout": {"op": "redeem", "in_values": [20, 5], "redeem_value": 24,
                    "change_values": [1]},
    }
    for f in forms.values():
        ops.check_form(f, 100)
    for broken in (
        dict(forms["pay"], out_values=[24, 2]),          # a unit from nowhere
        dict(forms["cashout"], change_values=[]),         # the change went missing
        dict(forms["topup"], in_values=[3]),              # an issue spends nothing
        dict(forms["topup"], out_values=[100]),           # no token holds it
        {"op": "burn", "in_values": [3]},
    ):
        with pytest.raises(ops.Violation):
            ops.check_form(broken, 100)

    def slots(*names):
        return [{"kind": "ok", "form": n} for n in names]

    got = ops.supply(slots("pay", "topup", "cashout"), forms, ["Valid"] * 3)
    assert got == {"issued": 25 + 25 + 24, "redeemed": 24,
                   "unspent": [1, 1, 24, 24], "ownerless": [24]}
    # only a Valid request changes the ledger
    got = ops.supply(slots("pay", "topup", "cashout"), forms,
                     ["Valid", "Invalid", "Invalid"])
    assert got == {"issued": 50, "redeemed": 0, "unspent": [1, 5, 20, 24],
                   "ownerless": []}
    # a token is spent once: a double spend that came back Valid is no ledger
    plan = slots("pay", "cashout") + [
        {"kind": "double_spend", "form": "pay", "of": 0}]
    assert ops.supply(plan, forms, ["Valid", "Valid", "Invalid"])["redeemed"] == 24
    with pytest.raises(ops.Violation):
        ops.supply(plan, forms, ["Valid", "Valid", "Valid"])


def test_every_form_of_the_mix_keeps_the_rules(cell):
    forms = schedule.forms_of(cell["mix"])
    assert list(forms) == [PAY, TOPUP, CASHOUT]
    assert [f["op"] for f in forms.values()] == ["transfer", "issue", "redeem"]
    assert [f["share"] for f in forms.values()] == [0.7, 0.15, 0.15]
    for f in forms.values():
        ops.check_form(f, TOP)
    pay, topup, cashout = forms.values()
    assert pay["in_values"] == cashout["in_values"] == [20000000000, 5500000000]
    assert pay["out_values"] == [24123456789, 1376543211]
    assert topup["out_values"] == [cashout["redeem_value"]] == [24123456789]
    assert cashout["change_values"] == [1376543211]
    # every output (the digits a range proof shows) needs the fifth
    # base-300 digit but the change, which needs four
    assert 300 ** 3 <= 1376543211 < 300 ** 4 <= 24123456789 < TOP
    # the configuration states the same operations and shares
    stated = cell["config"]["clients"]["operations"]
    assert {op: (s["form"], s["share"]) for op, s in stated.items()} == {
        f["op"]: (name, f["share"]) for name, f in forms.items()}
    # the rehearsal keeps names, operations and shares; transfers and
    # redeems are one-in/one-out, and no hand-over holds an issue
    small = cell["mix"]["rehearsal"]
    assert [(f["form"], f["op"], f["share"]) for f in small["requests"]] == [
        (n, f["op"], f["share"]) for n, f in forms.items()]
    for f in small["requests"]:
        ops.check_form(f, TOP)
        assert f["op"] == "issue" or len(f["in_values"]) == 1
    assert all(TOPUP not in j["forms"] for j in small["joint"])
    assert cell["config"]["rehearsal"]["policy"]["min_batch"] \
        == max(j["txs"] for j in small["joint"])


# ------------------------------------------------------------ the manifest


def test_the_manifest_lists_the_cell_where_its_siblings_are_listed(cell):
    m = mf.load()
    assert mf.validate(m) == []
    # (looked up by name: a later PR appends behind them)
    cfg, = [c for c in m["configs"] if c["name"] == "zkatdlog-b300e5-ops"]
    row, = [w for w in m["workloads"] if w["name"] == CELL]
    assert row == {"name": CELL, "config": cfg["name"],
                   "traffic": "ops8-b300e5", "chips": 1, "why": row["why"]}
    assert cfg["reduced"] == ["nodes", "idemix_owners"]
    assert sorted(cell["config"]["reduced"]) == ["idemix_owners", "nodes"]
    assert cfg["source"] == cell["config"]["source"] and len(cfg["source"]) <= 200
    for part in ("fabric-samples token-sdk", "--base 300 --exponent 5",
                 "issue, transfer, redeem", "crypto/issue"):
        assert part in cfg["source"]
    assert cell["config"]["architecture"] is None
    assert cell["config"]["tokengen"] == {"driver": "dlog", "base": 300, "exponent": 5}
    assert {x["name"] for x in cell["end_to_end"]} == {
        "committed_tps", "finality_p50_s", "setup_s"}
    mine = {x["name"] for x in cell["per_layer"]}
    twin = {x["name"] for x in m["per_layer"]
            if "b300e5.batches" in x["workloads"]}
    new = {"verify.issue_device_share", "verify.issue_outputs_per_call"}
    assert mine == twin | new
    for x in m["end_to_end"] + m["per_layer"]:
        if "b300e5.batches" in x.get("workloads", []):
            # appended behind the siblings, nothing else moved
            at = x["workloads"].index
            assert at(CELL) > at("b300e5.wallets") > at("b300e5.batches")
    for x in [x for x in m["per_layer"] if x["name"] in new]:
        assert x["workloads"] == [CELL]
        assert (x["source"], x["moves"], x["better"]) == (
            "program_counter", "finality_p50_s", "higher")
        assert x["layer"] == "stage A routing (orderer.py BlockValidationPipeline)"
        spec = mf._load(mf.data_file("layer_metrics", x["name"]))
        assert spec["reader"] == "counter_ratio"


def test_the_new_readers_on_a_program_without_the_counters():
    """The parent of PR 42 counts no issue: the harness snapshots the names
    at 0. The share's denominator does not move and the metric is left out
    of the line (no raise); the outputs a call read 0 over the calls the
    parent makes."""
    import readers

    def src(**counters):
        return readers.Sources(
            events=[], seconds=SECONDS, grace_s=10.0, counters=counters,
            histograms={}, blocks=[], dispatch={}, trace={}, device_kind="cpu")

    share = mf._load(mf.data_file("layer_metrics", "verify.issue_device_share"))
    per_call = mf._load(
        mf.data_file("layer_metrics", "verify.issue_outputs_per_call"))
    parent = src(**{"ledger.validate.issues_batched": 0,
                    "ledger.validate.issues_host": 0,
                    "batch.issue.outputs": 0, "batch.transfer.calls": 6})
    assert readers.read(parent, share) is None
    assert readers.read(parent, per_call) == 0.0
    assert readers.read(src(**{"batch.transfer.calls": 0}), per_call) is None
    change = src(**{"ledger.validate.issues_batched": 6,
                    "ledger.validate.issues_host": 4,
                    "batch.issue.outputs": 6, "batch.transfer.calls": 6})
    assert readers.read(change, share) == 60.0
    assert readers.read(change, per_call) == 1.0


# ----------------------------------------------------------- the rehearsal


def test_a_rehearsal_group_s_reference_run_leaves_the_ledger_the_rules_say(
    tmp_path,
):
    """Every group of a rehearsal plan, built by the corpus workers as a run
    builds it: the scalar reference's verdicts are the construction's, and
    the same bytes through a scalar node leave exactly the tokens
    `sample_ops.supply` says a ledger must hold: issued - redeemed = the
    owners' unspent sum, a redeemed output in nobody's hands, the issued
    ones spendable."""
    import dataclasses

    import run
    from corpus import Deployment, read_group

    sys.path.insert(0, os.path.dirname(BENCH))
    from fabric_token_sdk_tpu.api.request import TokenRequest
    from fabric_token_sdk_tpu.crypto.token import Metadata
    from fabric_token_sdk_tpu.models.token import ID
    from fabric_token_sdk_tpu.services.network import BlockPolicy

    one = run.rehearsal(mf.cell(mf.load(), CELL))
    forms = schedule.forms_of(one["mix"])
    entries = schedule.plan(one["mix"], one["config"]["bad_requests"], 40.0, 42)
    job = run.start_corpus(one, 42, entries, str(tmp_path))
    assert [p.wait() for p in job["procs"]] == [0] * len(job["procs"])
    dep = Deployment(one["config"], job["art_dir"])
    seen = collections.Counter()
    for g in job["groups"]:
        meta, blobs = read_group(str(tmp_path / f"group-{g}.bin"))
        verdicts = [r[0] for r in meta["ref"]]
        assert verdicts == meta["expect"]
        want = ops.supply(meta["slots"], forms, verdicts)
        net = dep.network(dataclasses.replace(
            BlockPolicy(), use_batched=False, sign_batched=False, pipeline=False))
        held, ownerless = [], []
        for raw in blobs:
            req = TokenRequest.from_bytes(raw)
            if net.submit(raw).status.value != "Valid":
                continue
            k = 0
            for rec in req.issues + req.transfers:
                seen["issue" if rec in req.issues else "transfer"] += 1
                for owner, md in zip(rec.receivers, rec.outputs_metadata):
                    value = Metadata.from_bytes(md).value
                    if not owner:
                        ownerless.append(value)
                        # (it is on the ledger, and nobody can sign for it)
                        assert net.exists(ID(req.anchor, k))
                    elif net.exists(ID(req.anchor, k)):
                        held.append((ID(req.anchor, k), value))
                    k += 1
        unspent = sorted(v for tid, v in held if net.exists(tid))
        assert unspent == want["unspent"], g
        assert sorted(ownerless) == want["ownerless"], g
        assert want["issued"] - want["redeemed"] == sum(unspent)
    assert seen["issue"] > len(job["groups"]) and seen["transfer"] >= 10


def _run(argv):
    out = subprocess.run(
        [sys.executable, *argv, "--workload", CELL, "--rehearse-cpu", *REHEARSAL],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=1500)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    checks = {ln.split()[1].split("=")[0]: ln.split()[-1]
              for ln in lines if ln.startswith("check ")}
    return json.loads(lines[-1]), checks


def test_the_rehearsal_s_seed_sends_its_top_ups_before_the_hand_overs(cell):
    import run

    one = run.rehearsal(cell)
    plan = schedule.plan(one["mix"], one["config"]["bad_requests"],
                         float(REHEARSAL[1]), int(REHEARSAL[3]))
    due, joint = _split(plan, float(REHEARSAL[1]))
    first = min(e["due_s"] for share in joint.values() for e in share)
    tops = [e["due_s"] for e in plan if e["form"] == TOPUP]
    assert len(tops) == 3 and max(tops) < first - 2.0
    assert len(due) == 20 and [len(s) for s in joint.values()] == [4, 4]


def test_cpu_rehearsal_of_the_cell_reads_correct():
    line, checks = _run([os.path.join(BENCH, "run.py"), "--trace", "1"])
    assert line["correct"] is True, checks
    assert set(checks.values()) == {"ok"}
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] == 20 and line["failed"] == 0
    # the three single top-ups were the host's, whatever else rode the plane
    assert line["metrics"]["verify.issue_device_share"]["value"] == 0.0
    assert line["metrics"]["verify.issue_outputs_per_call"]["value"] == 0.0


def test_cpu_rehearsal_under_an_accept_all_validator_reads_incorrect():
    line, checks = _run([
        os.path.join(HERE, "drive_broken.py"), "accept_all",
        os.path.join(BENCH, "traffic", "ops8-b300e5.json"), "--trace", "0"])
    assert line["correct"] is False
    assert checks["verdicts_differing_from_scalar_reference"] == "FAILED"
    assert checks["verdicts_differing_from_construction"] == "FAILED"
