"""Request forms (PR 36): a mix's `requests` key gives every slot its own
form (a transfer of any shape, an issue, a redeem), every seed the same
multiset of them, and a mix without the key is built as it always was.

    python3 -m pytest benchmark/tests/test_forms.py -k "not rehearsal and not control"
    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_forms.py   (minutes)

The two mixes under `data/` are test data, not cells: the hand-over pattern
of `batches8-b300e5` with a shape (`forms-shapes`) or an operation
(`forms-ops`) per slot. Their real runs are the chip's (PERF.md section 6);
here their plans are held to the rules, their group files are built at
their `rehearsal` sizes, and the whole harness is rehearsed on them on the
CPU backend, sound and with a validator that accepts everything.
"""

import collections
import copy
import json
import os
import random
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "harness"))
sys.path.insert(0, HERE)

import digests  # noqa: E402
import manifest as mf  # noqa: E402
import schedule  # noqa: E402
from test_control import drive  # noqa: E402

SECONDS = 51.0
ZK_BAD = ["tampered_proof", "double_spend", "bad_owner_signature"]


def load(name):
    with open(os.path.join(HERE, "data", f"{name}.mix.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module", params=["forms-shapes", "forms-ops"])
def mix(request):
    return load(request.param)


def seeds(n, salt):
    rng = random.Random(salt)
    return [rng.randrange(0, 2 ** 31 + 1000) for _ in range(n)]


def handovers(plan):
    joint = collections.defaultdict(list)
    for e in plan:
        if "joint" in e:
            joint[e["joint"]].append(e)
    return joint


# ------------------------------------------------------------------ plans


def test_every_seed_gets_the_same_multiset_of_forms_in_another_order(mix):
    """2,000 seeds: the window's 64 arrivals and the warm-up's 6 hold the
    shares' counts by largest remainder, each part counted apart, whatever
    the seed; the order is the seed's."""
    window = schedule.form_counts(mix["requests"], 64)
    warm = schedule.form_counts(mix["requests"], 6)
    orders = set()
    for seed in seeds(2000, 36):
        plan = schedule.plan(mix, ZK_BAD, SECONDS, seed)
        got = collections.Counter(e["form"] for e in plan if e["due_s"] >= 0.0)
        assert got == {f: c for f, c in window.items() if c}, seed
        got = collections.Counter(e["form"] for e in plan if e["due_s"] < 0.0)
        assert got == {f: c for f, c in warm.items() if c}, seed
        orders.add(tuple(e["form"] for e in plan))
    assert len(orders) > 1900
    assert schedule.plan(mix, ZK_BAD, SECONDS, 7) == schedule.plan(mix, ZK_BAD, SECONDS, 7)


@pytest.mark.parametrize("shares, n, want", [
    ([0.35, 0.30, 0.10, 0.12, 0.05, 0.05, 0.03], 64, [23, 19, 6, 8, 3, 3, 2]),
    ([0.35, 0.30, 0.10, 0.12, 0.05, 0.05, 0.03], 6, [2, 2, 1, 1, 0, 0, 0]),
    ([0.70, 0.15, 0.15], 64, [45, 10, 9]),   # a tie goes to the first listed
    ([0.70, 0.15, 0.15], 20, [14, 3, 3]),
    ([0.5, 0.5], 7, [4, 3]),
    ([1.0], 13, [13]),
])
def test_shares_are_counted_by_largest_remainder(shares, n, want):
    requests = [{"form": f"f{k}", "share": s} for k, s in enumerate(shares)]
    assert list(schedule.form_counts(requests, n).values()) == want


def test_a_fixed_handover_composition_is_honoured():
    """`forms` on a `joint` entry: that hand-over holds those forms in that
    order in every seed (a short hand-over the head of the list), and what
    it takes comes out of the window's multiset; a hand-over without the
    key is dealt its forms by the seed."""
    shapes, ops = load("forms-shapes"), load("forms-ops")
    listed = shapes["joint"][5]["forms"]
    dealt = set()
    for seed in seeds(300, 5):
        joint = handovers(schedule.plan(shapes, ZK_BAD, SECONDS, seed))
        assert [e["form"] for e in joint[5]] == listed
        dealt.add(tuple(e["form"] for e in joint[0]))
        for k, share in handovers(schedule.plan(ops, ZK_BAD, SECONDS, seed)).items():
            assert [e["form"] for e in share] == ops["joint"][k]["forms"][:len(share)]
    assert len(dealt) > 250
    # the 16 singles of forms-ops are then the same in every seed
    plan = schedule.plan(ops, ZK_BAD, SECONDS, 11)
    singles = collections.Counter(e["form"] for e in plan
                                  if "joint" not in e and e["due_s"] >= 0.0)
    assert singles == {"pay-2-2": 9, "topup": 4, "cashout": 3}


def test_bad_kinds_land_on_transfer_forms_only():
    ops = load("forms-ops")
    forms = schedule.forms_of(ops)
    for seed in seeds(500, 8):
        plan = schedule.plan(ops, ZK_BAD, SECONDS, seed)
        bad = [e for e in plan if e["kind"] != "ok"]
        assert sorted(e["kind"] for e in bad) == sorted(ZK_BAD)
        for e in bad:
            assert forms[e["form"]]["op"] == "transfer" and "joint" not in e
            if e["kind"] == "double_spend":
                first = [p for p in plan if p["group"] == e["group"]
                         and p["slot"] == e["of"]][0]
                assert forms[first["form"]]["op"] == "transfer"
                assert first["kind"] == "ok" and first["i"] < e["i"]


def _only(mix, **shares):
    mix = copy.deepcopy(mix)
    for f in mix["requests"]:
        f["share"] = shares.get(f["form"], 0.0)
    mix["joint"] = [{k: v for k, v in j.items() if k != "forms"} for j in mix["joint"]]
    return mix


def _edited(mix, path, value):
    mix = copy.deepcopy(mix)
    at = mix
    for key in path[:-1]:
        at = at[key]
    at[path[-1]] = value
    return mix


@pytest.mark.parametrize("fault", [
    "too_few_transfers", "handover_takes_more_than_the_share", "both_keys",
    "neither_key", "transfer_does_not_conserve", "redeem_does_not_conserve",
    "issue_with_inputs", "unknown_op", "shares_not_one", "forms_list_too_short",
    "a_name_twice"])
def test_a_mix_that_cannot_be_planned_is_refused(fault):
    ops = load("forms-ops")
    bad = {
        # nine in ten requests are issues: the bad kinds find no eight singles
        "too_few_transfers": _only(ops, **{"pay-2-2": 0.1, "topup": 0.9}),
        # six hand-overs list 12 issues and redeems, the shares hold 10 and 9
        "handover_takes_more_than_the_share": _edited(
            ops, ["joint"], [dict(j, forms=["topup", "cashout"] * 4)
                             for j in ops["joint"]]),
        "both_keys": dict(ops, transfer={"in_values": [1], "out_values": [1]}),
        "neither_key": {k: v for k, v in ops.items() if k != "requests"},
        "transfer_does_not_conserve": _edited(
            ops, ["requests", 0, "out_values"], [1, 2]),
        "redeem_does_not_conserve": _edited(ops, ["requests", 2, "redeem_value"], 5),
        "issue_with_inputs": _edited(ops, ["requests", 1, "in_values"], [5]),
        "unknown_op": _edited(ops, ["requests", 1, "op"], "burn"),
        "shares_not_one": _edited(ops, ["requests", 0, "share"], 0.6),
        "forms_list_too_short": _edited(ops, ["joint", 0, "forms"], ["topup"]),
        "a_name_twice": _edited(ops, ["requests", 1, "form"], "pay-2-2"),
    }[fault]
    with pytest.raises(ValueError):
        schedule.plan(bad, ZK_BAD, SECONDS, 3)


def test_a_group_is_closed_by_what_its_issue_has_to_cover():
    """At most 128 outputs in a group's set-up issue and 64 requests in the
    group: sixteen (8,1) consolidations fill one, 64 two-input transfers do
    (the groups of every mix the cells have), issues spend nothing."""
    shapes = load("forms-shapes")
    forms = schedule.forms_of(shapes)
    for only, per_group in (("sweep-8-1", 16), ("pay-4-2", 32), ("pay-2-2", 64),
                            ("pay-1-2", 64)):
        plan = schedule.plan(_only(shapes, **{only: 1.0}), [], SECONDS, 5)
        sizes = collections.Counter(e["group"] for e in plan)
        assert sizes["g0"] == per_group and max(sizes.values()) == per_group
        assert [e["slot"] for e in plan if e["group"] == "g1"][:3] == [0, 1, 2]
    for seed in (1, 2):
        plan = schedule.plan(shapes, ZK_BAD, SECONDS, seed)
        spent = collections.Counter()
        for e in plan:
            spent[e["group"]] += len(forms[e["form"]]["in_values"])
        assert max(spent.values()) <= schedule.GROUP_OUTPUTS
        slots = schedule.groups(plan)
        assert sum(len(s) for s in slots.values()) == len(plan)
        assert all(len(s) <= schedule.GROUP_TXS for s in slots.values())
        assert [s["form"] for s in slots["g0"]] == [e["form"] for e in plan[:len(slots["g0"])]]
    ops = load("forms-ops")
    plan = schedule.plan(_only(ops, topup=0.5, **{"pay-2-2": 0.5}), [], SECONDS, 5)
    assert collections.Counter(e["group"] for e in plan)["g0"] == 64


def test_the_test_mixes_are_what_issue_36_names(mix):
    """The hand-over pattern of batches8-b300e5, amounts of five base-300
    digits, shares that say they are assumed."""
    base = mf.cell(mf.load(), "b300e5.batches")["mix"]
    for key in ("arrivals", "rate_tps", "warm_s", "grace_s", "handover",
                "committed_tps_rule", "bad_before_share", "min_gap_s", "trace"):
        assert mix[key] == base[key], key
    assert [(j["at_share"], j["txs"]) for j in mix["joint"]] == [
        (j["at_share"], j["txs"]) for j in base["joint"]]
    assert "ASSUMED" in mix["requests_why"] and mix["on"] == "zkatdlog-b300e5"
    for f in schedule.forms_of(mix).values():
        values = [v for key in ("in_values", "out_values", "change_values")
                  for v in f.get(key, [])] + [f.get("redeem_value", 1)]
        assert all(0 < v < 300 ** 5 for v in values)
        if f["op"] != "issue":
            assert sum(f["in_values"]) >= 300 ** 4  # the whole needs the fifth digit
    plan = schedule.plan(mix, ZK_BAD, SECONDS, 2_147_483_867)
    assert sum(1 for e in plan if 0.0 <= e["due_s"] < SECONDS) == 64
    assert sorted(len(s) for s in handovers(plan).values()) == [8] * 6


# ---------------------------------------------- what a mix without forms builds


@pytest.fixture(scope="module")
def parent():
    with open(os.path.join(HERE, "data", "parent_digests.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("cell", sorted(digests.REHEARSAL_S))
def test_the_committed_mixes_plan_as_the_parent_planned_them(parent, cell):
    """`plan()` of the cell's mix at two seeds, at 51 s and at the
    rehearsal sizes, digest for digest (recorded on the parent of PR 36)."""
    got = digests.all_digests([cell], corpus=False)[cell]
    want = copy.deepcopy(parent[cell])
    for row in want["seeds"].values():
        del row["rehearsal_files"]
    assert got == want


@pytest.mark.parametrize("cell", sorted(digests.REHEARSAL_S))
def test_the_committed_mixes_build_the_files_the_parent_built(parent, cell):
    """Every issue file and group file of the cell's mix at the rehearsal
    sizes, through `run.start_corpus` and the workers: byte for byte what
    the parent's harness wrote (but for the workers' own seconds)."""
    assert digests.all_digests([cell])[cell] == parent[cell]


# ------------------------------------------------------------ group files


@pytest.mark.parametrize("cell", ["fab22.steady", "b300e5.batches"])
def test_group_files_of_a_three_form_mix_round_trip(tmp_path, cell):
    """`forms-ops` at its rehearsal sizes on either driver: every slot's
    request is of its form, the scalar reference agrees with the
    construction on all of them, and the warm block holds two of each form."""
    import run
    from corpus import read_group

    sys.path.insert(0, os.path.dirname(BENCH))
    from fabric_token_sdk_tpu.api.request import TokenRequest

    one = run.rehearsal(dict(mf.cell(mf.load(), cell), mix=load("forms-ops")))
    forms = schedule.forms_of(one["mix"])
    entries = schedule.plan(one["mix"], one["config"]["bad_requests"], 20.0, 41)
    job = run.start_corpus(one, 41, entries, str(tmp_path))
    assert [p.wait() for p in job["procs"]] == [0] * len(job["procs"])
    plan = dict(schedule.groups(entries),
                warm=[schedule.slot_plan("ok", f) for f in forms for _ in range(2)])
    assert sorted(job["groups"]) == sorted(plan)
    seen = collections.Counter()
    for g, slots in plan.items():
        meta, blobs = read_group(str(tmp_path / f"group-{g}.bin"))
        assert meta["slots"] == slots and len(blobs) == len(slots) + 1
        assert read_group(str(tmp_path / f"issue-{g}.bin"))[1] == blobs[:1]
        assert [r[0] for r in meta["ref"]] == meta["expect"]
        assert meta["expect"] == ["Valid" if s["kind"] == "ok" else "Invalid"
                                  for s in slots]
        setup = TokenRequest.from_bytes(blobs[0])
        spent = sum(len(forms[s["form"]].get("in_values", [])) for s in slots)
        assert len(setup.issues[0].receivers) == spent
        for i, (slot, raw) in enumerate(zip(slots, blobs[1:])):
            req, form = TokenRequest.from_bytes(raw), forms[slot["form"]]
            assert req.anchor == meta["tx_ids"][i] == f"bench-{g}-{i}"
            seen[form["op"]] += 1
            if form["op"] == "issue":
                assert not req.transfers and len(req.issues) == 1
                assert len(req.issues[0].receivers) == len(form["out_values"])
                continue
            assert not req.issues and len(req.transfers) == 1
            rec = req.transfers[0]
            if slot["kind"] != "double_spend":
                assert len(rec.input_ids) == len(form["in_values"])
            if form["op"] == "redeem":
                assert rec.receivers[0] == b""
                assert len(rec.receivers) == 1 + len(form["change_values"])
            else:
                assert b"" not in rec.receivers
    assert min(seen[op] for op in ("transfer", "issue", "redeem")) >= 3


# ----------------------------------------------- rehearsals and the control


@pytest.mark.parametrize("mix, cell, bad", [
    ("forms-ops", "fab22.steady", 2),      # transfer, issue, redeem
    ("forms-shapes", "fab22.steady", 2),   # seven shapes, one hand-over fixed
    ("forms-ops", "b300e5.batches", 3),    # the three operations, zkatdlog
])
def test_cpu_rehearsal_of_a_forms_mix_reads_correct(mix, cell, bad):
    """The whole harness on a forms mix at its rehearsal sizes: every
    verdict (in-window issues and redeems among them) equals the scalar
    reference's and the construction's, every acknowledged one is in the
    re-opened WAL, nothing compiles in the window."""
    line, checks = drive("sound", 3_000_000_021, mix, cell, 20)
    assert line["correct"] is True, checks
    assert set(checks.values()) == {"ok"}
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] == 20 and line["failed"] == 0
    assert line["checks"]["bad_requests_judged"] == {"value": bad, "limit": str(bad)}
    assert line["metrics"]["committed_tps"]["value"] == pytest.approx((20 - bad) / 20.0)


def test_control_an_answer_altered_where_it_is_produced_reads_incorrect_on_forms():
    line, checks = drive("accept_all", 3_000_000_022, "forms-ops", "fab22.steady", 20)
    assert line["correct"] is False
    assert checks["verdicts_differing_from_scalar_reference"] == "FAILED"
    assert checks["verdicts_differing_from_construction"] == "FAILED"
