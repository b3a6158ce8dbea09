"""The harness's set-up pieces that need no JAX and no chip: the thread
that hands the issues over while the transfers are proved and the programs
load, and the lists of counters and events that mean "a device plane gave
its work away".

    python3 -m pytest benchmark/tests/test_setup.py
"""

import os
import subprocess
import sys
import threading
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run  # noqa: E402  (imports neither jax nor the program)
from corpus import write_group  # noqa: E402


class FakeClient:
    def __init__(self, reject=()):
        self.seen, self.reject = [], set(reject)

    def submit(self, raw):
        self.seen.append(raw)
        ok = raw not in self.reject
        return types.SimpleNamespace(
            status=types.SimpleNamespace(value="Valid" if ok else "Invalid"),
            message="" if ok else "no")


def worker(code=0, after=0.0):
    return subprocess.Popen([sys.executable, "-c",
                             f"import time, sys; time.sleep({after}); sys.exit({code})"])


def issue_file(tmp_path, g):
    write_group(str(tmp_path / f"issue-{g}.bin"), {"group": g},
                [f"issue-{g}".encode()])


def group_file(tmp_path, g, n=2):
    blobs = [f"issue-{g}".encode()] + [f"{g}-{i}".encode() for i in range(n)]
    write_group(str(tmp_path / f"group-{g}.bin"), {"group": g}, blobs)
    return blobs


def test_issues_are_handed_over_as_the_workers_build_them(tmp_path):
    """An issue goes to the node when its file is there (the workers build
    their issues first and replace each file into place whole), not when
    the group is proved or the last worker ends; every group's issue is
    submitted once and the corpus comes back whole."""
    procs = [worker(after=0.6)]
    client = FakeClient()
    issues = run.Issues(client, {"procs": procs, "groups": ["g0", "g1", "warm"]},
                        str(tmp_path))
    issue_file(tmp_path, "g1")
    issues.start()
    deadline = time.monotonic() + 5.0
    while not client.seen and time.monotonic() < deadline:
        time.sleep(0.01)
    # no group is built yet, the worker still runs
    assert client.seen == [b"issue-g1"] and procs[0].poll() is None

    def later():
        for g in ("warm", "g0"):
            issue_file(tmp_path, g)
        group_file(tmp_path, "warm", n=1)
        group_file(tmp_path, "g0")
        group_file(tmp_path, "g1")

    threading.Thread(target=later).start()
    corpus = issues.result()
    assert not issues.is_alive()
    assert sorted(client.seen) == [b"issue-g0", b"issue-g1", b"issue-warm"]
    assert sorted(corpus) == ["g0", "g1", "warm"]
    assert corpus["g0"][1] == [b"issue-g0", b"g0-0", b"g0-1"]
    assert issues.busy_s >= 0.0 and issues.first_at > 0.0


@pytest.mark.parametrize("fault", ["worker_fails", "worker_writes_nothing",
                                   "issue_rejected"])
def test_a_set_up_that_cannot_finish_raises_where_it_is_joined(tmp_path, fault):
    issue_file(tmp_path, "g0")
    group_file(tmp_path, "g0")
    client = FakeClient(reject=[b"issue-g0"] if fault == "issue_rejected" else ())
    groups = ["g0"] if fault == "issue_rejected" else ["g0", "g1"]
    procs = [worker(code=3 if fault == "worker_fails" else 0)]
    issues = run.Issues(client, {"procs": procs, "groups": groups}, str(tmp_path))
    issues.start()
    issues.join(timeout=10.0)
    assert not issues.is_alive()
    with pytest.raises(RuntimeError):
        issues.result()


def test_every_fallback_name_is_one_the_program_still_emits():
    """A name nothing emits can never move: it would be a check that
    cannot fail (three such names went out with PR 30's mesh)."""
    source = ""
    for base, _dirs, files in os.walk(os.path.join(ROOT, "fabric_token_sdk_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(base, f)) as fh:
                    source += fh.read()
    for name in (*run.FALLBACK_COUNTERS, *run.FALLBACK_EVENTS):
        assert f'"{name}"' in source, name
    assert not [n for n in (*run.FALLBACK_COUNTERS, *run.FALLBACK_EVENTS)
                if n.startswith("sharding.")]


def test_a_commit_noticed_after_the_close_is_not_an_answer():
    """A backlog's sixth block may commit within a poll of the window's
    end: what the watcher notices after the close stays queued (not an
    attempt), it is never recorded as an answer that came late."""
    sys.path.insert(0, os.path.join(BENCH, "harness"))
    import loadgen

    gen = loadgen.Generator.__new__(loadgen.Generator)
    gen.seconds, gen.grace_s, gen.lock = 0.35, 0.0, threading.Lock()
    gen.entries = [{"i": i, "tx_id": f"t{i}", "due_s": 0.0, "client": 0}
                   for i in range(4)]
    gen.raw = {i: b"" for i in range(4)}
    gen.events = {i: {"i": i, "tx_id": f"t{i}", "due": 0.0, "sent": None,
                      "done": None, "status": None, "message": None, "error": None}
                  for i in range(4)}
    gen.drained_at, gen.t_open = None, time.monotonic()
    final = types.SimpleNamespace(status=types.SimpleNamespace(value="Valid"),
                                  message="")

    class Node:
        """Commits t0 and t1 0.05 s in, t2 and t3 0.34 s in: the watcher's
        next poll (every 0.1 s) sees the second block after the close."""

        def height(self):
            t = gen.now()
            return 0 if t < 0.05 else 1 if t < 0.34 else 2

        def status(self, tx_id):
            return final if int(tx_id[1:]) < 2 * self.height() else None

        def submit_many(self, raws):
            time.sleep(5.0)

    node = Node()
    gen.run_submit_many([node], node, poll_s=0.1)
    done = {i: ev["done"] for i, ev in gen.events.items()}
    assert done[0] is not None and done[0] == done[1] and 0.05 <= done[0] < 0.34
    assert done[2] is None and done[3] is None
    assert gen.drained_at is None


def test_each_cell_prints_the_median_finality_under_the_name_it_lists():
    """The median finality carries a bound for each kind of block: the
    cell whose median block the host verifies lists `finality_p50_s.host`,
    the hand-over cells `finality_p50_s`, the backlog neither; a run prints
    exactly what its cell lists, the same median under either name."""
    import manifest as mf

    m = mf.load()
    assert mf.validate(m) == []
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert e2e["finality_p50_s"]["workloads"] == ["b300e5.batches", "b300e5.testnet"]
    assert e2e["finality_p50_s.host"]["workloads"] == ["zk22.steady"]
    # PR 36: the host median's bound as the ledger's note on PR 34 asks, and
    # `committed_tps` at five times what `zk22.backlog`'s runs spread
    assert {k: e2e[k]["bound"] for k in e2e} == {
        "committed_tps": 0.05, "finality_p50_s": 0.02,
        "finality_p50_s.host": 0.062, "setup_s": 0.25}
    events = [{"due": float(i), "done": i + 0.1 * (i + 1), "status": "Valid"}
              for i in range(5)]
    run_ = {"events": events, "seconds": 10.0, "grace_s": 1.0, "setup_s": 3.0}
    want = {"zk22.backlog": set(), "fab22.steady": set(),
            "zk22.steady": {"finality_p50_s.host"},
            "b300e5.batches": {"finality_p50_s"},
            "b300e5.testnet": {"finality_p50_s"}}
    for name, medians in want.items():
        cell = mf.cell(m, name)
        got = run.end_to_end(cell, run_)
        assert set(got) == {"committed_tps", "setup_s"} | medians, name
        for k in medians:
            assert got[k] == {"value": pytest.approx(0.3), "unit": "s"}
    # what moves with the host-verified median names it
    for x in m["per_layer"]:
        if x.get("workloads") == ["zk22.steady"]:
            assert x["moves"] == "finality_p50_s.host", x["name"]
