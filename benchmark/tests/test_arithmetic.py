"""The benchmark's own arithmetic: metric rules, the schedule, the
manifest's rules, the readers and the trace reduction. No JAX, no chip:
`python3 -m pytest benchmark/tests/test_arithmetic.py`.
"""

import copy
import json
import math
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "harness"))

import manifest as mf  # noqa: E402
import readers  # noqa: E402
import schedule  # noqa: E402
import stats  # noqa: E402
import trace as tr  # noqa: E402

BAD = ["tampered_proof", "double_spend", "bad_owner_signature"]


def ev(i, due, done, status="Valid", sent=None, error=None):
    return {"i": i, "due": due, "sent": due if sent is None else sent,
            "done": done, "status": status, "message": "", "error": error}


# ------------------------------------------------------------------ stats


def test_percentile_interpolates_between_ranks():
    xs = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert stats.percentile(xs, 0.5) == 3.0
    assert stats.percentile(xs, 0.95) == pytest.approx(4.8)
    assert stats.percentile([7.0], 0.95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def test_committed_tps_open_loop_counts_work_due_in_the_window():
    events = [ev(0, 0.0, 1.0), ev(1, 1.0, 9.9),
              ev(2, 9.5, 10.5),                    # final inside the grace
              ev(3, 9.9, 12.5),                    # too late: failed
              ev(4, 3.0, 4.0, status="Invalid"), ev(5, 4.0, None),
              ev(6, -1.0, 0.5)]                    # due while warming
    assert stats.committed_tps(events, 10.0, 2.0, "due_in_window") == pytest.approx(0.3)


def test_committed_tps_last_commit_rule_is_not_quantised_by_window_end():
    # one block of 64 commits 38 s into a 51 s window, the next after it
    events = [ev(i, 0.0, 38.0) for i in range(64)]
    events += [ev(64 + i, 0.0, 76.0) for i in range(64)]
    assert stats.committed_tps(events, 51.0, 0.0, "last_commit") == pytest.approx(64 / 38.0)
    assert stats.committed_tps([ev(0, 0.0, None)], 51.0, 0.0, "last_commit") == 0.0
    with pytest.raises(ValueError):
        stats.committed_tps(events, 51.0, 0.0, "window")


def test_failed_is_late_refused_or_unanswered_but_not_invalid():
    s, g = 10.0, 2.0
    assert not stats.is_failed(ev(0, 9.0, 11.9), s, g)
    assert stats.is_failed(ev(1, 9.0, 12.1), s, g)          # after the grace
    assert stats.is_failed(ev(2, 1.0, None), s, g)          # never final
    assert stats.is_failed(ev(3, 1.0, None, error="Backpressure: full"), s, g)
    assert not stats.is_failed(ev(4, 1.0, 1.5, status="Invalid"), s, g)


def test_latencies_run_from_due_time_over_transactions_due_in_window():
    events = [ev(0, -1.0, 0.5),            # warming: not in the window
              ev(1, 1.0, 1.25, sent=1.1),  # sent late: still from due
              ev(2, 9.5, 11.0),
              ev(3, 10.0, 10.1)]           # due at the close: outside
    assert stats.finality_latencies(events, 10.0, 2.0) == pytest.approx([0.25, 1.5])
    assert stats.lateness_ms(events, 10.0) == pytest.approx([100.0, 0.0])


# --------------------------------------------------------------- schedule

TRANSFER = {"in_values": [100, 55], "out_values": [120, 35]}
POISSON = {"transfer": TRANSFER, "arrivals": "poisson", "rate_tps": 20.0,
           "warm_s": 3.0, "handover": {"call": "submit", "pool": 8}}
BACKLOG = {"transfer": TRANSFER, "arrivals": "at_open", "backlog_txs": 192,
           "handover": {"call": "submit_many", "clients": 3, "stagger_s": 1.0}}


def test_schedule_is_a_pure_function_of_the_seed():
    a = schedule.plan(POISSON, BAD, 51.0, 3_000_000_001)
    b = schedule.plan(POISSON, BAD, 51.0, 3_000_000_001)
    c = schedule.plan(POISSON, BAD, 51.0, 7)
    assert a == b
    assert [e["due_s"] for e in a] != [e["due_s"] for e in c]


def _window_gaps(seed, mix=None):
    times = [e["due_s"] for e in schedule.plan(mix or POISSON, BAD, 51.0, seed)
             if e["due_s"] >= 0.0]
    # an arrival opens its gap; the last one's runs to the window's end
    return times, [b - a for a, b in zip(times, times[1:] + [51.0])]


def test_every_seed_gets_the_same_gaps_in_another_order():
    (a, gaps_a), (b, gaps_b) = _window_gaps(1), _window_gaps(2)
    assert len(a) == len(b) == round(20.0 * 51.0)
    assert a == sorted(a) and a[0] == 0.0 and all(t < 51.0 for t in a)
    assert a != b
    assert sorted(gaps_a) == pytest.approx(sorted(gaps_b), abs=1e-6)
    assert sum(gaps_a) == pytest.approx(51.0)


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_001])
def test_the_times_between_arrivals_are_a_poisson_processes(seed):
    """Exponential gaps, not smoothed ones: coefficient of variation 1, and
    as many near-coincident arrivals as a Poisson process has (a smoothed
    schedule would hide the blocks that arrivals share)."""
    _times, gaps = _window_gaps(seed)
    n, mean = len(gaps), 51.0 / len(gaps)
    var = sum((g - mean) ** 2 for g in gaps) / n
    assert var ** 0.5 / mean == pytest.approx(1.0, abs=0.03)
    for share in (0.025, 0.1, 1.0, 3.0):
        want = n * (1.0 - math.exp(-share))
        got = sum(1 for g in gaps if g < share * mean)
        assert got == pytest.approx(want, abs=1.0)


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_001])
def test_a_joint_handover_is_due_where_the_mix_says(seed):
    """Exactly at `at_share` of the window, whatever the seed: the traced
    slice is placed by the same file and has to find it there."""
    mix = dict(POISSON, rate_tps=1.29, joint=[{"at_share": 0.93, "txs": 2}])
    plan = schedule.plan(mix, BAD, 51.0, seed)
    joint = [e for e in plan if "joint" in e]
    assert [e["due_s"] for e in joint] == [0.93 * 51.0] * 2
    assert joint[1]["i"] == joint[0]["i"] + 1 and all(e["kind"] == "ok" for e in joint)
    assert [e["due_s"] for e in plan] == sorted(e["due_s"] for e in plan)
    assert len(plan) == len(schedule.plan(POISSON | {"rate_tps": 1.29}, BAD, 51.0, seed))


def test_bad_requests_are_seeded_inside_the_window_and_ordered():
    for seed in (1, 2, 3_000_000_000):
        plan = schedule.plan(POISSON, BAD, 51.0, seed)
        bad = {e["kind"]: e for e in plan if e["kind"] != "ok"}
        assert sorted(bad) == sorted(BAD)
        assert all(0.0 <= e["due_s"] <= 0.6 * 51.0 for e in bad.values())
        double = bad["double_spend"]
        first = next(e for e in plan if e["group"] == double["group"]
                     and e["slot"] == double["of"])
        assert first["kind"] == "ok"
        assert double["due_s"] - first["due_s"] >= 0.5


def test_backlog_is_handed_over_at_the_opening_by_staggered_clients():
    plan = schedule.plan(BACKLOG, BAD, 51.0, 5)
    assert len(plan) == 192
    assert {e["client"]: e["due_s"] for e in plan} == {0: 0.0, 1: 1.0, 2: 2.0}
    # the bad ones ride the first hand-over: the block that commits in time
    assert all(e["client"] == 0 for e in plan if e["kind"] != "ok")
    assert {g: len(s) for g, s in schedule.groups(plan).items()} == {
        "g0": 64, "g1": 64, "g2": 64}


def test_the_committed_backlog_outlasts_the_window_and_the_wire_carries_it():
    """768 = 12 blocks of 64, 12 clients x 64 (one `submit_many` frame stays
    4.5 MB, under the wire's 16 MiB), all handed over 2.75 s in; the bad
    requests ride the first hand-over; the rehearsal's plan is untouched."""
    with open(mf.data_file("traffic", "backlog")) as fh:
        mix = json.load(fh)
    for seed in (5, 2_147_480_099, 3_000_000_001):
        plan = schedule.plan(mix, BAD, 51.0, seed)
        assert len(plan) == 768
        shares = {}
        for e in plan:
            shares.setdefault(e["client"], []).append(e)
        assert sorted(shares) == list(range(12))
        assert all(len(v) == 64 for v in shares.values())
        assert {c: v[0]["due_s"] for c, v in shares.items()} == {
            c: 0.25 * c for c in range(12)}
        assert max(e["due_s"] for e in plan) == 2.75
        bad = [e for e in plan if e["kind"] != "ok"]
        assert sorted(e["kind"] for e in bad) == sorted(BAD)
        assert all(e["client"] == 0 for e in bad)
        assert {g: len(v) for g, v in schedule.groups(plan).items()} == {
            f"g{k}": 64 for k in range(12)}
    assert mix["committed_tps_rule"] == "last_commit" and mix["grace_s"] == 0.0
    assert mix["transfer"] == {"in_values": [100, 55], "out_values": [120, 35]}
    # the queue outlasts the window at the parent's 8.3 s a block, and a
    # block of 3 s still leaves 12 commits to count
    assert mix["backlog_txs"] / 64 * 8.3 > 51.0 and mix["backlog_txs"] // 64 >= 12
    # the slice opens early, then waits for a Miller frame
    assert mix["trace"] == {"at_share": 0.1, "after_dispatch": "verify:miller_tile",
                            "for_s": 0.5}
    reh = schedule.plan({**mix, **mix["rehearsal"]}, BAD, 20.0, 5)
    assert len(reh) == 16
    assert {e["client"]: e["due_s"] for e in reh} == {0: 0.0, 1: 0.5}


# --------------------------------------------------------------- manifest


def test_the_committed_manifest_keeps_the_rules():
    assert mf.validate(mf.load()) == []


def _broken(edit):
    m = copy.deepcopy(mf.load())
    edit(m)
    return mf.validate(m)


def test_manifest_faults_are_found():
    assert _broken(lambda m: m["end_to_end"][0].update(unit="tokens per second"))
    assert _broken(lambda m: m["end_to_end"][0].update(unit="x" * 17))
    assert _broken(lambda m: m["workloads"][0].update(name="has space"))
    assert _broken(lambda m: m["per_layer"][0].update(moves="nothing"))
    assert _broken(lambda m: m["end_to_end"][0].update(bound=0.5))
    assert _broken(lambda m: m["workloads"].append(dict(m["workloads"][0],
                                                         name="twin")))

    # a layer metric listed in a cell that does not report what it moves
    def latency_everywhere(m):
        txs = next(x for x in m["per_layer"] if x["name"] == "order.block_txs_mean")
        txs["moves"] = "finality_p50_s"  # which the backlog cell does not report
    assert any("does not report" in f for f in _broken(latency_everywhere))


def test_every_cell_loads_its_files_by_name():
    m = mf.load()
    for w in m["workloads"]:
        cell = mf.cell(m, w["name"])
        assert cell["config"]["bad_requests"]
        assert cell["mix"]["arrivals"] in ("poisson", "at_open")
        assert {x["name"] for x in cell["end_to_end"]} >= {"setup_s", "committed_tps"}
        for metric in cell["per_layer"]:
            assert metric["reader"]["reader"] in readers.READERS
    with pytest.raises(mf.ManifestError):
        mf.cell(m, "no.such.cell")


# ---------------------------------------------------------------- readers


def sources(**kw):
    base = dict(events=[], seconds=10.0, grace_s=1.0, counters={}, histograms={},
                blocks=[], dispatch={}, trace={}, device_kind="TPU v5 lite")
    base.update(kw)
    return readers.Sources(**base)


def test_readers_return_none_when_there_is_nothing_to_read():
    src = sources()
    for spec in ({"reader": "blocks_ratio", "num": "wal_s", "den": "blocks"},
                 {"reader": "counter_ratio", "num": ["a"], "den": ["a", "b"]},
                 {"reader": "trace_idle_share"},
                 {"reader": "window_idle_share", "busy_fields": ["sign_verify_s"]},
                 {"reader": "dispatch_ms", "programs": ["verify:fexp_tile"],
                  "rows_per_tile": 8},
                 {"reader": "trace_program_ms", "programs": ["final_exp"],
                  "height_of": "verify:fexp_tile", "rows_per_tile": 8},
                 {"reader": "trace_roofline", "peak_key": "int8_ops",
                  "work": {"final_exp": {"per_row": "fp_mul_per_final_exp",
                                         "height_of": "verify:fexp_tile"}}},
                 {"reader": "padding_share"},
                 {"reader": "client_percentile", "of": "finality_s", "q": 0.9},
                 {"reader": "histogram_quantile", "histogram": "h", "q": 0.5}):
        assert readers.read(src, spec) is None


def test_block_and_counter_readers():
    blocks = [{"txs": ["a", "b"], "device_verify_s": 1.0, "wal_s": 0.01},
              {"txs": ["c"], "device_verify_s": 0.0, "wal_s": 0.03}]
    src = sources(blocks=blocks, counters={"ledger.validate.batched": 2,
                                           "ledger.validate.host": 1},
                  dispatch={"verify:g1_mul_tile": {"rows": 6, "padded_rows": 2,
                                                   "dispatches": 1, "wall_s": 0.07},
                            "sign:g1_mul_tile": {"rows": 8, "padded_rows": 0,
                                                 "dispatches": 3, "wall_s": 0.21}},
                  histograms={"h": ((0.1, 1.0), [2, 2, 0])})
    read = lambda **spec: readers.read(src, spec)  # noqa: E731
    assert read(reader="blocks_ratio", num="txs", den="blocks") == 1.5
    assert read(reader="blocks_ratio", num="wal_s", den="blocks", scale=1e3) == pytest.approx(20.0)
    assert read(reader="blocks_ratio", num="device_verify_s",
                den={"counter": "ledger.validate.batched"}) == 0.5
    assert read(reader="counter_ratio", num=["ledger.validate.batched"],
                den=["ledger.validate.batched", "ledger.validate.host"],
                scale=100.0) == pytest.approx(200 / 3)
    # the device planes were at work 1.0 s of the 10 s window (the block the
    # policy kept on the host adds nothing)
    assert read(reader="window_idle_share",
                busy_fields=["device_verify_s", "sign_verify_s"]) == pytest.approx(90.0)
    assert read(reader="padding_share") == pytest.approx(12.5)
    assert read(reader="dispatch_ms", programs=["sign:g1_mul_tile"],
                rows_per_tile=8) == pytest.approx(210.0)
    assert read(reader="dispatch_ms", programs=["verify:fexp_tile"],
                rows_per_tile=8) is None
    assert read(reader="padding_share", planes=["sign"]) == 0.0
    assert read(reader="histogram_quantile", histogram="h", q=0.5) == pytest.approx(0.1)
    assert read(reader="histogram_quantile", histogram="h", q=0.75) == pytest.approx(0.55)


def _ops():
    with open(os.path.join(os.path.dirname(HERE), "harness", "pairing_ops.json")) as fh:
        return json.load(fh)


def test_roofline_reader_uses_the_peaks_table_and_refuses_unknown_devices():
    ops = _ops()
    spec = {"reader": "trace_roofline", "peak_key": "int8_ops",
            "work": {"miller_loop": {"per_row": "fp_mul_per_miller_leg",
                                     "height_of": "verify:miller_tile"},
                     "final_exp": {"per_row": "fp_mul_per_final_exp",
                                   "height_of": "verify:fexp_tile"}}}
    # ten whole Miller dispatches of 16 rows in one device second; the slice
    # held no whole final_exp dispatch
    trace = {"programs": {"miller_loop": {"dispatches": 10, "seconds": 1.0}}}
    ledger = {"verify:miller_tile": {"rows": 150, "padded_rows": 10,
                                     "dispatches": 10, "wall_s": 1.2,
                                     "tile_rows": 16}}
    got = readers.read(sources(trace=trace, dispatch=ledger), spec)
    want = 100.0 * 160 * ops["fp_mul_per_miller_leg"] * 2048 / 393e12
    assert got == pytest.approx(want)
    assert 0.0 < got < 1.0
    with pytest.raises(KeyError):
        readers.read(sources(trace=trace, dispatch=ledger,
                             device_kind="TPU v9"), spec)


def _fexp_cell():
    m = mf.load()
    cell = mf.cell(m, "zk22.backlog")
    return {x["name"]: x["reader"] for x in cell["per_layer"]}


@pytest.mark.parametrize("height, whole", [(8, 2), (128, 1)])
def test_the_final_exp_readers_are_per_8_rows_at_any_height(height, whole):
    """The committed reader files on a synthetic slice and ledger: whole
    `final_exp` dispatches of 8 rows (two in the slice) or of 128 (one), at
    the same device seconds a row: the same ms per 8 rows and the same
    roofline share from the device's clock, and 16 times less of both
    seconds when a tile of 128 costs what one of 8 does. The frame's host
    clock beside them (`tiles.fexp_ms_per_tile`) counts the window's rows,
    not the slice's."""
    files = _fexp_cell()
    ops = _ops()
    per_8_rows_s = 0.19015
    tiles = whole * height / 8
    trace = {"programs": {"final_exp": {"dispatches": whole,
                                        "seconds": per_8_rows_s * tiles},
                          "_product_rows": {"dispatches": whole + 1,
                                            "seconds": 0.003}}}
    ledger = {"verify:fexp_tile": {"rows": 250, "padded_rows": 6,
                                   "dispatches": 256 // height,
                                   "wall_s": 0.19312 * 32,
                                   "tile_rows": height},
              "verify:miller_tile": {"rows": 1000, "padded_rows": 24,
                                     "dispatches": 8, "wall_s": 0.775,
                                     "tile_rows": 128}}
    src = sources(trace=trace, dispatch=ledger)
    assert readers.read(src, files["kernel.fexp_tile_ms"]) == pytest.approx(190.15)
    want = 100.0 * 8 * ops["fp_mul_per_final_exp"] * 2048 / 393e12 / per_8_rows_s
    assert readers.read(src, files["kernel.fexp_roofline"]) == pytest.approx(want)
    assert want == pytest.approx(0.003873, rel=1e-3)
    assert readers.read(src, files["tiles.fexp_ms_per_tile"]) == pytest.approx(193.12)
    # a tile of 128 rows at the price of one of 8: all three move 16x
    trace["programs"]["final_exp"]["seconds"] /= 16
    ledger["verify:fexp_tile"]["wall_s"] /= 16
    assert readers.read(src, files["kernel.fexp_tile_ms"]) == pytest.approx(190.15 / 16)
    assert readers.read(src, files["kernel.fexp_roofline"]) == pytest.approx(16 * want)
    assert readers.read(src, files["tiles.fexp_ms_per_tile"]) == pytest.approx(193.12 / 16)
    # the Miller reader beside them was per 16 rows before its height moved
    assert readers.read(src, files["kernel.miller_tile_ms"]) == pytest.approx(
        775.0 / 64)


def test_the_final_exp_readers_find_nothing_where_there_is_nothing_to_read():
    """No whole dispatch in the slice, no slice, or no ledger entry to say
    how many rows a dispatch held: the device readers return nothing (never
    0 for a share of a roofline); no frame in the window: nor does the
    host-clock one."""
    files = _fexp_cell()
    entry = {"rows": 30, "padded_rows": 2, "dispatches": 4, "wall_s": 0.77,
             "tile_rows": 8}
    whole = {"programs": {"final_exp": {"dispatches": 1, "seconds": 0.19}}}
    cut = {"programs": {"final_exp": {"dispatches": 0, "seconds": 0.0},
                        "_product_rows": {"dispatches": 1, "seconds": 0.001}}}
    for trace, ledger in (({}, {"verify:fexp_tile": entry}),
                          (cut, {"verify:fexp_tile": entry}),
                          (whole, {}),
                          (whole, {"verify:fexp_tile": dict(entry, tile_rows=0)})):
        src = sources(trace=trace, dispatch=ledger)
        assert readers.read(src, files["kernel.fexp_tile_ms"]) is None
        assert readers.read(src, files["kernel.fexp_roofline"]) is None
    idle = {"verify:fexp_tile": {"rows": 0, "padded_rows": 0, "dispatches": 0,
                                 "wall_s": 0.0, "tile_rows": 8}}
    for ledger in ({}, idle, {"sign:g1_mul_tile": {"rows": 6, "padded_rows": 122,
                                                   "dispatches": 1, "wall_s": 0.05}}):
        assert readers.read(sources(trace=whole, dispatch=ledger),
                            files["tiles.fexp_ms_per_tile"]) is None


def test_every_reader_file_names_a_reader_and_the_kernel_pair_reads_the_device():
    m = mf.load()
    kinds = {mf._load(mf.data_file("layer_metrics", x["name"]))["reader"]
             for x in m["per_layer"]}
    assert kinds <= set(readers.READERS)
    assert "dispatch_roofline" not in readers.READERS  # a share of a roofline
    by_name = {x["name"]: x for x in m["per_layer"]}  # is the device's time
    three = ["zk22.backlog", "b300e5.batches", "b300e5.testnet"]
    for name, source in (("kernel.fexp_tile_ms", "device_trace"),
                         ("kernel.fexp_roofline", "device_trace"),
                         ("device.fexp_idle_share", "device_trace"),
                         ("tiles.fexp_ms_per_tile", "program_span"),
                         ("kernel.miller_tile_ms", "program_span")):
        assert by_name[name]["source"] == source
        assert by_name[name]["workloads"] == three
    for name in ("kernel.fexp_tile_ms", "kernel.fexp_roofline"):
        spec = mf._load(mf.data_file("layer_metrics", name))
        assert "rows_per_dispatch" not in json.dumps(spec)
        assert "verify:fexp_tile" in json.dumps(spec)  # the height is the ledger's


# ------------------------------------------------------------------ trace


def tiny_trace():
    ops = [["fusion.1", 1000, 200], ["fusion.2", 1100, 300],   # overlap: 1000-1400
           ["while.3", 20000, 5000], ["fusion.1", 40000, 1000],
           ["fusion.2", 50000, 1000]]
    mods = [["jit_final_exp(9)", 995, 405],       # running when the trace came up
            ["jit_final_exp(9)", 20000, 5000], ["jit_miller_loop(123)", 40000, 1000],
            ["jit_miller_loop(123)", 50000, 1000]]  # running when it stopped
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops},
                                            {"name": "XLA Modules", "events": mods}]}]}


HOST = [("proof plane (host glue)", 900, 43000), ("stage tiles (run_rows)", 1400, 21000)]


def test_trace_reduction_busy_union_idle_share_and_programs():
    r = tr.reduce(tiny_trace(), host=HOST)
    assert r["window_s"] == pytest.approx(50000e-9)
    assert r["busy_s"] == pytest.approx((400 + 5000 + 1000 + 1000) * 1e-9)
    # the first final_exp began with the trace (short of its head) and the
    # last miller_loop ended with it: busy time, but no dispatch of either
    assert r["programs"]["final_exp"] == {"dispatches": 1,
                                          "seconds": pytest.approx(5000e-9)}
    assert r["programs"]["miller_loop"] == {"dispatches": 1,
                                            "seconds": pytest.approx(1000e-9)}
    assert r["device_ops"][0] == ["while.3", pytest.approx(5000e-9)]
    gaps = dict(r["idle_gaps"])
    # 1400-20000 lies in run_rows (the innermost span), 25000-40000 only in
    # the proof plane's own glue, 41000-50000 in no span of the benchmark's
    assert gaps["stage tiles (run_rows)"] == pytest.approx(18600e-9)
    assert gaps["proof plane (host glue)"] == pytest.approx(15000e-9)
    assert gaps["outside the benchmark's spans"] == pytest.approx(9000e-9)
    src = sources(trace=r)
    assert readers.read(src, {"reader": "trace_idle_share"}) == pytest.approx(
        100 * (1 - 7400 / 50000))
    # device time per whole dispatch stays in the run's `trace programs:` log
    p = r["programs"]["final_exp"]
    assert 1e3 * p["seconds"] / p["dispatches"] == pytest.approx(5000e-6)


def test_trace_reduction_honours_a_window_and_needs_a_device_plane():
    # the window opens at the first device event (1000), whatever the
    # slice's nominal start: the tracer was not up before it
    r = tr.reduce(tiny_trace(), window_ns=(0, 22000))
    assert r["window_s"] == pytest.approx(21000e-9)
    assert r["busy_s"] == pytest.approx((400 + 2000) * 1e-9)
    with pytest.raises(ValueError):
        tr.reduce({"planes": []})


def _slice(fexp2_ops, fexp2_end):
    """A slice as `batches8-b300e5` places it (ns; the slice asks for
    600,000): a Miller walk of three tiles, the first taken for cut at its
    head, a whole `final_exp`, and a second one whose last operation ends at
    `fexp2_ops` and whose program event ends at `fexp2_end`."""
    ops = [["while.2", 45000, 93000], ["while.2", 140000, 93000],
           ["while.2", 235000, 93000], ["while.16", 330000, 190000],
           ["while.16", 522000, fexp2_ops - 522000]]
    mods = [["jit_miller_loop(7)", 45000, 94000], ["jit_miller_loop(7)", 140000, 94000],
            ["jit_miller_loop(7)", 235000, 94000], ["jit_final_exp(9)", 330000, 190150],
            ["jit_final_exp(9)", 522000, fexp2_end - 522000]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops},
                                            {"name": "XLA Modules", "events": mods}]}]}


@pytest.mark.parametrize("fexp2_ops, fexp2_end, whole", [
    # stopped inside an operation, which is then never written: the program's
    # event runs to the stop, 45 us past the slice's end and 20 us past the
    # last operation (b300e5.batches at height 8, my chip run, PR 34, call 4:
    # counted, it made 190.15 ms read 152.35)
    (616000, 645000, 1),
    # the same with the stop on the last operation's end
    (645000, 645000, 1),
    # the last event of the trace, but over before the slice's end: it ran
    # to its end and the device idled after it (the one dispatch a block has
    # at height 128: left out, it left both readers with nothing to read)
    (560000, 560150, 2),
])
def test_a_program_the_stop_cut_is_no_dispatch_and_a_last_one_that_ended_is(
        fexp2_ops, fexp2_end, whole):
    r = tr.reduce(_slice(fexp2_ops, fexp2_end), window_ns=(0, 600000))
    assert r["programs"]["miller_loop"]["dispatches"] == 2
    assert r["programs"]["final_exp"]["dispatches"] == whole
    assert r["programs"]["final_exp"]["seconds"] == pytest.approx(
        190150e-9 + (fexp2_end - 522000) * 1e-9 * (whole - 1))
    src = sources(trace=r, dispatch={"verify:fexp_tile": {
        "rows": 80, "padded_rows": 0, "dispatches": 10, "wall_s": 1.93,
        "tile_rows": 8}})
    got = readers.read(src, _fexp_cell()["kernel.fexp_tile_ms"])
    assert got == pytest.approx(r["programs"]["final_exp"]["seconds"] * 1e3 / whole)


def test_recorded_trace_reduces():
    """A slice of a real TPU v5e trace of this program (kept small)."""
    path = os.path.join(HERE, "data", "recorded_trace.json.gz")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this checkout")
    with open(os.path.join(HERE, "data", "recorded_trace.expect.json")) as fh:
        want = json.load(fh)
    r = tr.reduce(tr.load(path))
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert 0.0 < r["busy_s"] <= r["window_s"]
    for name, p in want["programs"].items():
        assert r["programs"][name]["dispatches"] == p["dispatches"]
        assert r["programs"][name]["seconds"] == pytest.approx(p["seconds"])


def test_a_backlogs_queue_at_the_close_is_not_an_attempt():
    events = [ev(0, 0.0, 38.0), ev(1, 0.0, None),
              ev(2, 0.0, None, error="RemoteError: frame too large")]
    assert [e["i"] for e in stats.attempted(events, 51.0, backlog=True)] == [0, 2]
    assert [e["i"] for e in stats.attempted(events, 51.0, backlog=False)] == [0, 1, 2]


def test_the_harness_snapshots_what_the_reader_files_name():
    m = mf.load()
    specs = [x["reader"] for x in mf.cell(m, "zk22.steady")["per_layer"]]
    counters, histograms = readers.names_read(specs)
    assert {"ledger.validate.batched", "ledger.validate.host",
            "batch.sign.rows"} <= set(counters)
    assert histograms == ["ledger.block.queue_wait.seconds"]
