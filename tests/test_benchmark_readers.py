"""The benchmark's pure readers under tier-1.

`pytest.ini` collects `tests/` only, so nothing the driver's suite runs
guarded the yardstick: the reader files `benchmark/layer_metrics/*.json`
with `benchmark/harness/readers.py` and the trace reduction
`harness/trace.py`. These are the pure-reader cases of
`benchmark/tests/test_arithmetic.py`, copied as they stand but for the
list of cells the final-exp readers are pinned to, which may grow (no JAX,
no chip): the final-exp readers at heights 8 and 128 on the committed
reader files, `None` where nothing was dispatched, no reader file
naming a reader that is gone. Nothing under `benchmark/` is edited.

The harness modules import each other by bare name (`readers` imports
`stats`) and one is called `trace`, as a module of the standard library
is; tier-1 runs under xdist with `--dist loadfile`, so the `harness`
fixture puts `benchmark/harness` on the path for one test and takes the
names out of `sys.modules` again: no other test file of the same worker
sees them.
"""

import importlib
import json
import os
import sys

import pytest

# the tests' directory of the benchmark: the copied helpers find
# `harness/pairing_ops.json` from it
HERE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmark", "tests")
_HARNESS = os.path.join(os.path.dirname(HERE), "harness")
_NAMES = ("manifest", "readers", "stats", "trace")


mf = readers = tr = None  # the harness modules, bound by `harness`


@pytest.fixture(autouse=True)
def harness(monkeypatch):
    """`mf`, `readers`, `tr` as the benchmark's own test file names
    them, for the length of one test."""
    held = {n: sys.modules.pop(n) for n in _NAMES if n in sys.modules}
    monkeypatch.syspath_prepend(_HARNESS)
    me = sys.modules[__name__]
    try:
        for alias, name in (("mf", "manifest"), ("readers", "readers"),
                            ("tr", "trace")):
            monkeypatch.setattr(me, alias, importlib.import_module(name))
        assert os.path.dirname(tr.__file__) == _HARNESS
        yield
    finally:
        for n in _NAMES:
            sys.modules.pop(n, None)
        sys.modules.update(held)


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
        # (the benchmark's own copy of this case pins the list to the
        # three; a cell added since is one whose slice waits for a
        # pairing walk, as theirs do: `b300e5.wallets`, PR 37)
        assert by_name[name]["workloads"][:3] == three
        for cell in by_name[name]["workloads"][3:]:
            spec = mf.cell(m, cell)["mix"]["trace"]
            assert spec.get("after_counter") == "pairing.staged.calls"
    for name in ("kernel.fexp_tile_ms", "kernel.fexp_roofline"):
        spec = mf._load(mf.data_file("layer_metrics", name))
        assert "rows_per_dispatch" not in json.dumps(spec)
        assert "verify:fexp_tile" in json.dumps(spec)  # the height is the ledger's


# ------------------------------------- the readers of the glue by part (PR 40)

_GLUE_SHARES = (
    [f"verify.glue_{part}_share"
     for part in ("parse", "hostec", "encode", "decode", "challenge")]
    + [f"sign.glue_{part}_share"
       for part in ("parse", "encode", "decode", "challenge")])


def _emitted_counters(plane):
    """The counters one plane span of the program leaves behind."""
    from fabric_token_sdk_tpu.utils import devobs, metrics as mx

    with devobs.plane(plane):
        with devobs.glue("encode"):
            pass
    return set(mx.REGISTRY.snapshot()["counters"])


@pytest.mark.parametrize("name", _GLUE_SHARES + ["pipeline.overlap_share"])
def test_the_readers_of_pr_40_name_what_the_program_emits(name):
    """Each new reader file loads, names a registered reader and only
    counters or `block.commit` fields the program emits; a part's share is
    listed exactly where its plane's `host_glue_share` is and over the same
    denominator, so the parts add up to it less `other`."""
    m = mf.load()
    assert mf.validate(m) == []
    by_name = {x["name"]: x for x in m["per_layer"]}
    entry = by_name[name]
    spec = mf._load(mf.data_file("layer_metrics", name))
    assert spec["reader"] in readers.READERS
    assert (entry["unit"], entry["moves"]) == ("%", "committed_tps")
    if name == "pipeline.overlap_share":
        assert spec == {"reader": "blocks_ratio", "num": "overlap_s",
                        "den": "device_verify_s", "scale": 100.0}
        assert (entry["source"], entry["better"]) == ("program_span", "higher")
        assert entry["workloads"] == ["zk22.backlog"]
        ledger = os.path.join(os.path.dirname(os.path.dirname(HERE)),
                              "fabric_token_sdk_tpu", "services", "network",
                              "ledger.py")
        with open(ledger) as fh:
            text = fh.read()
        assert '"device_verify_s":' in text and 'breakdown["overlap_s"]' in text
        blocks = [{"txs": ["a"], "device_verify_s": 2.0, "overlap_s": 0.5},
                  {"txs": ["b"], "device_verify_s": 2.0}]  # the first block
        assert readers.read(sources(blocks=blocks), spec) == pytest.approx(12.5)
        return
    plane, part = name.split(".")[0], name.split("_")[1]
    whole = by_name[f"{plane}.host_glue_share"]
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert entry[key] == whole[key], key
    # (by name: later PRs append their cells behind it, `b300e5.ops` first)
    assert "b300e5.wallets" in entry["workloads"]
    whole_spec = mf._load(mf.data_file("layer_metrics", whole["name"]))
    assert spec == dict(whole_spec, num=[f"device.{plane}.glue.{part}_us"])
    assert set(spec["num"] + spec["den"]) <= _emitted_counters(plane)
    counters = {f"device.{plane}.span_us": 2_000_000,
                f"device.{plane}.glue_us": 400_000,
                f"device.{plane}.glue.{part}_us": 100_000}
    src = sources(counters=counters)
    assert readers.read(src, spec) == pytest.approx(5.0)
    assert readers.read(src, whole_spec) == pytest.approx(20.0)
    # a program without the part's counter (the parent of PR 40) whose plane
    # ran reads 0, and nothing where the plane did not run: never a raise
    del counters[f"device.{plane}.glue.{part}_us"]
    assert readers.read(sources(counters=counters), spec) == 0.0
    assert readers.read(sources(), spec) is None


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


def test_the_harness_snapshots_what_the_reader_files_name():
    m = mf.load()
    specs = [x["reader"] for x in mf.cell(m, "zk22.steady")["per_layer"]]
    counters, histograms = readers.names_read(specs)
    assert {"ledger.validate.batched", "ledger.validate.host",
            "batch.sign.rows"} <= set(counters)
    assert histograms == ["ledger.block.queue_wait.seconds"]
