#!/usr/bin/env python3
"""One run of one benchmark cell: `python3 benchmark/run.py --workload
<cell> --seed <n> --seconds <s> --trace <0|1>`.

This process is the node: it holds the chip, builds the deployment from
`--seed`, warms the cell's programs, stands up `Network` + `LedgerServer`
(default `BlockPolicy()`, WAL with fsync) on loopback, submits the issues
(set-up), then lets a generator process (`harness/loadgen.py`, no JAX)
send the cell's traffic for `--seconds` and judges what came back. The
last line of stdout is the result object; a run that finds no TPU (or too
few chips) exits non-zero and prints none.

Other modes (never used by the driver):
  --rehearse-cpu     tiny sizes on the CPU backend; the device is named
                     `cpu` and no metric is printed under a device name
  --seeds a,b,c      one warm-up, then one short run per seed (the seeds
                     check); with --rates r1,r2,.. a rate sweep
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import threading  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(HERE, "harness"))
sys.path.insert(0, ROOT)

import manifest as mf  # noqa: E402
import schedule  # noqa: E402
import stats  # noqa: E402
from corpus import Deployment, make_artifacts, read_group  # noqa: E402

OUT_DIR = os.path.join(ROOT, "benchmark_out")

# the ring of lifecycle events is the source of the per-block breakdown:
# make it hold a whole window (an observability size, not a policy)
os.environ.setdefault("FTS_FLIGHT_EVENTS", "400000")

# counters that move only when a device plane gave its work to the host
# (chip_smoke.py's list, less the names nothing emits since PR 30)
FALLBACK_COUNTERS = (
    "ledger.block.batch_errors", "batch.sign.host_fallbacks",
    "batch.prove.host_fallbacks", "resilience.bounded.timeouts",
    "resilience.breaker.open", "resilience.breaker.rejected",
    "native.selfcheck.fail", "jax.cache.load_failures",
)
FALLBACK_EVENTS = ("verify.host_fallback", "sign.host_fallback")
# shown in every run's `window:` line; the cell's reader files add theirs
WINDOW_COUNTERS = ("ledger.validate.batched", "ledger.validate.host",
                   "batch.sign.rows", "batch.sign.host",
                   "ledger.blocks.committed")
COMPILES = "jax.core.compile.backend_compile_duration.seconds"
# fields of a dispatch-ledger entry that add up over a window
DISPATCH_SUMS = ("rows", "padded_rows", "dispatches", "wall_s")
CLIENT_TIMEOUT_S = 900.0


def log(msg: str) -> None:
    print(f"[bench t+{time.monotonic() - T_START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


class Refused(Exception):
    """The run cannot start (no chip, no program): exit non-zero, print
    no result."""


# ----------------------------------------------------------------- set-up


def start_corpus(cell: dict, seed: int, entries: list, workdir: str) -> dict:
    """Artifacts now; proving, signing and the scalar reference in child
    processes pinned to the CPU backend, while this one warms the chip."""
    art_dir = os.path.join(workdir, "tokengen")
    make_artifacts(cell["config"], seed, art_dir)
    groups = schedule.groups(entries)
    forms = schedule.forms_of(cell["mix"])
    warm = int(cell["config"].get("warm_block_txs", 0))
    if warm:
        # of every form the mix sends: no program is dispatched first, and
        # no shape met first, inside the window
        groups["warm"] = [schedule.slot_plan("ok", f) for f in forms
                          for _ in range(warm)]
    names = sorted(groups, key=lambda g: -len(groups[g]))
    n_proc = max(1, min(len(names), (os.cpu_count() or 2) - 2, 8))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = []
    for k in range(n_proc):
        spec = {"config": cell["config"], "art_dir": art_dir, "seed": seed,
                "out_dir": workdir, "forms": forms,
                "groups": [{"group": g, "slots": groups[g]}
                           for g in names[k::n_proc]]}
        path = os.path.join(workdir, f"corpus-{k}.json")
        with open(path, "w") as fh:
            json.dump(spec, fh)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(HERE, "harness", "corpus.py"), path],
            env=env, stdout=sys.stderr))
    return {"art_dir": art_dir, "procs": procs, "groups": names}


class Issues(threading.Thread):
    """Hands each group's issue to the node as soon as its corpus worker
    has written it (the workers build their issues first), one `submit`
    after another (one issue a block, which the host validates: 0.075 s an
    output), so that the issues run beside the proving of the transfers and
    the loading of the cell's programs and not behind them. `result()`
    joins, waits for the workers and gives the corpus: group -> (meta,
    blobs)."""

    def __init__(self, client, corpus: dict, workdir: str):
        super().__init__(daemon=True)
        self.client, self.procs, self.workdir = client, corpus["procs"], workdir
        self.groups = list(corpus["groups"])
        self.error = None
        self.first_at = self.busy_s = 0.0

    def path(self, kind: str, g: str) -> str:
        return os.path.join(self.workdir, f"{kind}-{g}.bin")

    def run(self) -> None:
        todo = set(self.groups)
        try:
            while todo:
                # the workers' state first: one writes its issues, then
                # its groups, then exits
                codes = [p.poll() for p in self.procs]
                ready = sorted(g for g in todo
                               if os.path.exists(self.path("issue", g)))
                if not ready:
                    if any(codes) or None not in codes:
                        raise RuntimeError(f"corpus workers exited {codes} "
                                           f"without the issues of {sorted(todo)}")
                    time.sleep(0.05)
                    continue
                self.first_at = self.first_at or time.monotonic() - T_START
                for g in ready:
                    t0 = time.monotonic()
                    ev = self.client.submit(read_group(self.path("issue", g))[1][0])
                    if ev.status.value != "Valid":
                        raise RuntimeError(f"issue of {g} rejected: {ev.message}")
                    todo.discard(g)
                    self.busy_s += time.monotonic() - t0
        except Exception as e:  # handed to the thread that joins
            self.error = e

    def result(self) -> dict:
        self.join()
        if self.error is not None:
            raise self.error
        log(f"issues of {len(self.groups)} groups: the first was built "
            f"t+{self.first_at:.1f}s, submitting took {self.busy_s:.1f}s in all")
        for p in self.procs:
            if p.wait() != 0:
                raise RuntimeError(f"corpus worker exited {p.returncode}")
        return {g: read_group(self.path("group", g)) for g in self.groups}


def find_devices(chips: int, rehearse: bool) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if rehearse:
        if dev["platform"] != "cpu":
            raise Refused("--rehearse-cpu, but JAX selected "
                          f"{dev['platform']!r}: set JAX_PLATFORMS=cpu")
    elif dev["platform"] != "tpu" or dev["count"] < chips:
        raise Refused(f"the cell needs {chips} TPU chip(s); JAX found "
                      f"{dev['count']} x {dev['platform']!r} "
                      "(a CPU rehearsal: --rehearse-cpu)")
    return dev


def warm_programs(names: list) -> dict:
    """AOT-compile the cell's programs through the persistent cache (the
    program's `ops.warmup` set, cut to this cell's)."""
    import jax
    import jax.numpy as jnp

    from fabric_token_sdk_tpu.ops import warmup as wu
    from fabric_token_sdk_tpu.utils import devobs

    progs = {n: (fn, shapes) for n, fn, shapes in wu.all_programs(True, True)}
    missing = [n for n in names if n not in progs]
    if missing:
        raise RuntimeError(f"warm_programs names unknown programs {missing}")
    prev = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    t0, c0 = time.monotonic(), compiles()
    try:
        for n in names:
            fn, shapes = progs[n]
            with devobs.attribute(n):
                fn.lower(*[jax.ShapeDtypeStruct(s, jnp.int32)
                           for s in shapes]).compile()
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", prev)
    return {"programs": len(names), "seconds": round(time.monotonic() - t0, 3),
            "backend_compiles": compiles() - c0,
            "cache_dir": jax.config.jax_compilation_cache_dir}


# ------------------------------------------------------- program's counters


def counter(name: str) -> int:
    from fabric_token_sdk_tpu.utils import metrics as mx

    return mx.REGISTRY.counter(name).value


def compiles() -> int:
    from fabric_token_sdk_tpu.utils import metrics as mx

    return mx.REGISTRY.histogram(COMPILES).count


def dispatches(key: str) -> int:
    """Dispatches of one "plane:program" in the program's dispatch ledger."""
    from fabric_token_sdk_tpu.utils import devobs

    plane, program = key.split(":")
    return devobs.snapshot().get((plane, program), {}).get("dispatches", 0)


def snapshot(counters=(), histograms=()) -> dict:
    from fabric_token_sdk_tpu.utils import devobs, metrics as mx

    return {
        "counters": {c: counter(c) for c in
                     {*FALLBACK_COUNTERS, *WINDOW_COUNTERS, *counters}},
        "compiles": compiles(),
        "histograms": {h: (mx.REGISTRY.histogram(h).buckets,
                           mx.REGISTRY.histogram(h).state()[0])
                       for h in histograms},
        "dispatch": {f"{pl}:{prog}": {f: e[f] for f in (*DISPATCH_SUMS, "tile_rows")}
                     for (pl, prog), e in devobs.snapshot().items()},
    }


def delta(a: dict, b: dict) -> dict:
    return {
        "counters": {c: b["counters"][c] - a["counters"][c] for c in b["counters"]},
        "compiles": b["compiles"] - a["compiles"],
        "histograms": {h: (bounds, [y - x for x, y in
                                    zip(a["histograms"][h][1], counts)])
                       for h, (bounds, counts) in b["histograms"].items()},
        # `tile_rows` is the height of the newest dispatch, not a sum
        "dispatch": {k: dict({f: e[f] - a["dispatch"].get(k, {}).get(f, 0)
                              for f in DISPATCH_SUMS}, tile_rows=e["tile_rows"])
                     for k, e in b["dispatch"].items()},
    }


# ----------------------------------------------------------- trace spans


SPAN_LOG: list = []  # [layer, start, end or None] of every wrapped call


def install_spans() -> None:
    """`jax.profiler.TraceAnnotation`s around the calls into each layer,
    put there at run time by the benchmark (traced runs only): the names
    by which the trace reduction attributes the device's idle gaps."""
    import jax

    from fabric_token_sdk_tpu.api.validator import RequestValidator
    from fabric_token_sdk_tpu.ops import pairing, stages
    from fabric_token_sdk_tpu.services.network.orderer import (
        BlockValidationPipeline,
    )
    from fabric_token_sdk_tpu.services.network.wal import WriteAheadLog

    def span(owner, attr, name):
        inner = getattr(owner, attr)

        @functools.wraps(inner)
        def wrapped(*a, **kw):
            entry = [name, time.monotonic(), None]
            SPAN_LOG.append(entry)
            with jax.profiler.TraceAnnotation(f"bench:{name}"):
                try:
                    return inner(*a, **kw)
                finally:
                    entry[2] = time.monotonic()

        setattr(owner, attr, wrapped)

    span(BlockValidationPipeline, "proof_verdicts", "proof plane (host glue)")
    span(BlockValidationPipeline, "sign_verdicts", "sign plane (host glue)")
    span(stages, "run_rows", "stage tiles (run_rows)")
    span(pairing, "pairing_product_staged", "pairing tiles")
    span(RequestValidator, "validate", "host validate")
    span(WriteAheadLog, "append", "wal append")


# ------------------------------------------------------------------ a run


def sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)


def stand_up(cell: dict, dep: Deployment, workdir: str) -> dict:
    """The node: `Network` under the configuration's policy with its WAL,
    served on loopback, and the harness's own connection to it."""
    from fabric_token_sdk_tpu.services.network import BlockPolicy
    from fabric_token_sdk_tpu.services.network.remote import (
        LedgerServer, RemoteNetwork,
    )

    policy = dataclasses.replace(BlockPolicy(), **cell["config"].get("policy", {}))
    wal_path = os.path.join(workdir, "ledger.wal")
    net = dep.network(policy, wal_path=wal_path)
    server = LedgerServer(network=net).start()
    client = RemoteNetwork(server.address, timeout=CLIENT_TIMEOUT_S)
    return {"net": net, "server": server, "client": client, "wal_path": wal_path}


def warm_block(client, corpus: dict) -> int:
    """One small block through every plane the cell uses, so that no
    program is dispatched first inside the window. -> how many of its
    (valid) requests were rejected."""
    rejected = 0
    if "warm" in corpus:
        t0 = time.monotonic()
        for ev in client.submit_many(corpus["warm"][1][1:]):
            if ev.status.value != "Valid":
                rejected += 1
                log(f"warm block: {ev.tx_id} rejected: {ev.message}")
        log(f"warm block {time.monotonic() - t0:.1f}s")
    return rejected


def start_generator(address, mix: dict, entries: list, seconds: float,
                    workdir: str):
    """The generator process, loaded and dialled. -> (process, result path)"""
    grace_s = float(mix.get("grace_s", 0.0))
    job = {"address": list(address), "plan": entries, "corpus_dir": workdir,
           "seconds": seconds, "grace_s": grace_s,
           "timeout_s": seconds + grace_s + 60.0, "handover": mix["handover"],
           "out": os.path.join(workdir, "loadgen.result.json")}
    job_path = os.path.join(workdir, "loadgen.job.json")
    with open(job_path, "w") as fh:
        json.dump(job, fh)
    gen = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "harness", "loadgen.py"), job_path],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    if gen.stdout.readline().strip() != "READY":
        gen.kill()
        gen.wait()
        raise RuntimeError("the generator did not come up")
    return gen, job["out"]


def trace_slice(spec: dict, t_open: float, seconds: float, trace_dir: str) -> tuple:
    """Trace the slice of the window the mix names: a fraction of a second,
    because the tracer writes ~10 million device events per busy second here
    and closing a trace costs ~30 us an event. -> (its start on the monotonic
    clock, its length)."""
    import jax

    for_s = min(float(spec["for_s"]), seconds / 4)
    sleep_until(t_open + float(spec["at_share"]) * seconds)
    # from there, wait for the phase the slice is meant to see: a dispatch
    # of that program entered in the program's own ledger (the frame closes
    # at its last read-back: what follows it is under the slice), or a
    # counter the program moves before the phase begins (what it announces
    # is under the slice whole)
    if spec.get("after_counter"):
        moved = functools.partial(counter, spec["after_counter"])
    elif spec.get("after_dispatch"):
        moved = functools.partial(dispatches, spec["after_dispatch"])
    else:
        moved = None
    if moved:
        seen = moved()
        while moved() == seen and time.monotonic() < t_open + seconds - 3.0:
            time.sleep(0.005)
    jax.profiler.start_trace(trace_dir)
    t_trace = time.monotonic()
    time.sleep(for_s)
    t0 = time.monotonic()
    jax.profiler.stop_trace()
    log(f"traced {for_s}s from {t_trace - t_open:.2f}s into the window; closing "
        f"the trace took {time.monotonic() - t0:.1f}s")
    return t_trace, for_s


def run_once(cell: dict, dep: Deployment, node: dict, corpus: dict,
             entries: list, seconds: float, trace: bool, workdir: str,
             setup_from: float, drain: bool) -> dict:
    import jax

    from fabric_token_sdk_tpu.utils import metrics as mx, resilience

    mix = cell["mix"]
    grace_s = float(mix.get("grace_s", 0.0))
    net, server, client = node["net"], node["server"], node["client"]
    gen = None
    try:
        warm_rejected = warm_block(client, corpus)
        for e in entries:
            e["tx_id"] = corpus[e["group"]][0]["tx_ids"][e["slot"]]
        gen, result_path = start_generator(server.address, mix, entries,
                                           seconds, workdir)
        warm_s = -min(0.0, min(e["due_s"] for e in entries))
        t_open = time.monotonic() + 0.2 + warm_s
        wall_offset = time.time() - time.monotonic()
        gen.stdin.write(f"GO {t_open!r}\n")
        gen.stdin.flush()

        import readers

        read = readers.names_read([m["reader"] for m in cell["per_layer"]])
        sleep_until(t_open)
        setup_s = t_open - setup_from
        before = snapshot(*read)
        # the closing snapshot is taken on time even while this thread is
        # busy closing a trace
        at_close = []
        timer = threading.Timer(t_open + seconds - time.monotonic(),
                                lambda: at_close.append(snapshot(*read)))
        timer.start()
        trace_dir = os.path.join(workdir, "trace") if trace else None
        traced = (trace_slice(mix["trace"], t_open, seconds, trace_dir)
                  if trace else None)
        timer.join()
        try:
            gen.wait(timeout=grace_s + 60.0)
        except subprocess.TimeoutExpired:
            raise RuntimeError("the generator did not end")
        with open(result_path) as fh:
            result = json.load(fh)
        win = delta(before, at_close[0])
        final = snapshot()
        flight = mx.FLIGHT.tail()
        blocks = [e for e in flight if e["kind"] == "block.commit"
                  and t_open <= e["ts"] - wall_offset < t_open + seconds]
        fallback_events = [e["kind"] for e in flight
                           if e["kind"] in FALLBACK_EVENTS]
        breakers = resilience.breaker_states()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices()[:cell["chips"]])
    finally:
        if gen is not None and gen.poll() is None:
            gen.kill()
            gen.wait()
        shut_down(node)  # no new work; what is in flight goes on
        if drain:
            # a run per seed in one process: the next window must not share
            # the device with what this one left in flight
            t_end = time.monotonic() + 120.0
            while net.health()["inflight"] and time.monotonic() < t_end:
                time.sleep(0.1)

    events = result["events"]
    log_window(mix, events, seconds, grace_s, blocks, win,
               t_open + wall_offset, t_open if trace else None)
    checks = judge(dep, corpus, entries, events, node["wal_path"], final, win,
                   fallback_events, breakers, result, warm_rejected)
    if result["drained_at"] is not None:
        log(f"drained: the backlog was final {result['drained_at']:.2f}s into "
            "the window; re-size backlog_txs")
    return {"events": events, "seconds": seconds, "grace_s": grace_s,
            "traced": traced, "setup_s": setup_s, "win": win,
            "blocks": blocks, "checks": checks, "trace_dir": trace_dir,
            "peak_bytes": peak}


def shut_down(node: dict) -> None:
    node["client"].close()
    node["server"].stop()


def log_window(mix, events, seconds, grace_s, blocks, win, opened_unix,
               spans_from) -> None:
    """One line to stderr on what the window held (and, traced, the
    benchmark's spans): for the reader of a run, not for the driver."""
    lat = stats.finality_latencies(events, seconds, grace_s)
    dev = [b for b in blocks if b.get("device_verify_s") or b.get("sign_verify_s")]
    log("window: " + json.dumps({
        "committed_tps": round(stats.committed_tps(
            events, seconds, grace_s, mix["committed_tps_rule"]), 4),
        "blocks": len(blocks), "device_blocks": len(dev),
        "block_txs_max": max((len(b["txs"]) for b in blocks), default=0),
        "commits_at_s": [round(b["ts"] - opened_unix, 2) for b in blocks][:16],
        "device_verify_s": round(sum(b.get("device_verify_s", 0) for b in blocks), 3),
        "sign_verify_s": round(sum(b.get("sign_verify_s", 0) for b in blocks), 3),
        "counters": {k: v for k, v in win["counters"].items() if v},
        "latency": {q: round(stats.percentile(lat, q), 4)
                    for q in (0.5, 0.9, 0.95, 0.99, 1.0)} if lat else None,
        "late_ms_p95": round(stats.percentile(
            stats.lateness_ms(events, seconds) or [0.0], 0.95), 3)}))
    if spans_from is not None:
        spans = {}
        for name, a, b in SPAN_LOG:
            if b is not None and spans_from <= a < spans_from + seconds:
                sp = spans.setdefault(name, {"calls": 0, "seconds": 0.0,
                                             "first_at": round(a - spans_from, 2)})
                sp["calls"] += 1
                sp["seconds"] = round(sp["seconds"] + b - a, 3)
        log("spans in the window: " + json.dumps(spans))


# ------------------------------------------------------------- correctness


def same_verdict(got, ref) -> bool:
    """Status and message equal the scalar reference's. A rejection by a
    device plane carries the plane's own reason ("invalid transfer proof"
    with none, "invalid owner signature: rejected by the batched signature
    plane"), where the scalar validator gives its own after the same
    head: such a pair agrees when the heads before ": " are equal (as
    `chip_smoke.py:phase_agree` compares the tampered proof)."""
    if got[0] != ref[0]:
        return False
    if got[1] == ref[1]:
        return True
    head = got[1].split(": ", 1)[0]
    return got[0] == "Invalid" and bool(head) and head == ref[1].split(": ", 1)[0]


def check_lines(rows: list) -> list:
    return [f"check {name}={value} limit {limit} {'ok' if ok else 'FAILED'}"
            for name, value, limit, ok in rows]


def judge(dep, corpus, entries, events, wal_path, final, win,
          fallback_events, breakers, gen_result, warm_rejected) -> list:
    """Every number compared, beside its limit: [name, value, limit, ok]."""
    from fabric_token_sdk_tpu.services.network import Network

    by_i = {e["i"]: e for e in entries}
    answered = [ev for ev in events if ev["done"] is not None]
    mismatch_ref, mismatch_expect, examples = 0, 0, []
    for ev in answered:
        e = by_i[ev["i"]]
        meta = corpus[e["group"]][0]
        got = (ev["status"], ev["message"] or "")
        ref = tuple(meta["ref"][e["slot"]])
        if not same_verdict(got, ref):
            mismatch_ref += 1
            examples.append((ev["tx_id"], got, ref))
        if got[0] != meta["expect"][e["slot"]]:
            mismatch_expect += 1
    # the reference against the construction: a wrong reference is a fault too
    ref_vs_expect = sum(
        1 for g in corpus for r, x in zip(corpus[g][0]["ref"], corpus[g][0]["expect"])
        if r[0] != x)
    bad_due = [e for e in entries if e["kind"] != "ok"]
    bad_judged = sum(1 for e in bad_due
                     if any(ev["i"] == e["i"] for ev in answered))
    # durability: re-open the journal from disk, as a restarted node does.
    # From a copy of the bytes on disk: a backlog's node is still
    # committing its queue on its own threads (journal first, so that a
    # compaction in between finds the copy a prefix the snapshot covers)
    reopened = wal_path + ".reopened"
    shutil.copyfile(wal_path, reopened)
    if os.path.exists(wal_path + ".snap"):
        shutil.copyfile(wal_path + ".snap", reopened + ".snap")
    recovered = Network.recover(dep.validator(), reopened)
    acked = [ev for ev in answered if ev["status"] == "Valid"]
    wal_missing = 0
    for ev in acked:
        st = recovered.status(ev["tx_id"])
        if st is None or st.status.value != "Valid":
            wal_missing += 1
    moved = {c: final["counters"][c] for c in FALLBACK_COUNTERS
             if final["counters"][c]}
    open_breakers = {p: s for p, s in breakers.items() if s != "closed"}
    rows = [
        ("compared", len(answered), ">= 1", len(answered) >= 1),
        ("verdicts_differing_from_scalar_reference", mismatch_ref, "0", mismatch_ref == 0),
        ("verdicts_differing_from_construction", mismatch_expect, "0", mismatch_expect == 0),
        ("reference_differing_from_construction", ref_vs_expect, "0", ref_vs_expect == 0),
        ("valid_warm_block_requests_rejected", warm_rejected, "0", warm_rejected == 0),
        ("bad_requests_judged", bad_judged, f"{len(bad_due)}", bad_judged == len(bad_due)),
        ("acked_valid_missing_from_reopened_wal", wal_missing, "0", wal_missing == 0),
        ("fallback_or_timeout_counters_moved", len(moved), "0", not moved),
        ("fallback_flight_events", len(fallback_events), "0", not fallback_events),
        ("breakers_not_closed", len(open_breakers), "0", not open_breakers),
        ("backend_compiles_in_window", win["compiles"], "0", win["compiles"] == 0),
        ("generator_imported_jax_or_ops",
         int(gen_result["jax_imported"] or gen_result["ops_imported"]), "0",
         not (gen_result["jax_imported"] or gen_result["ops_imported"])),
    ]
    for ln in check_lines(rows):
        print(ln, flush=True)
    for ex in examples[:3]:
        print(f"  differing verdict: {ex}", flush=True)
    if moved or open_breakers:
        print(f"  moved: {moved} breakers: {open_breakers}", flush=True)
    return rows


# ---------------------------------------------------------------- metrics


def end_to_end(cell: dict, run: dict) -> dict:
    ev, s, g = run["events"], run["seconds"], run["grace_s"]
    lat = stats.finality_latencies(ev, s, g)
    p50 = stats.percentile(lat, 0.5) if lat else None
    values = {
        "committed_tps": stats.committed_tps(ev, s, g, cell["mix"]["committed_tps_rule"]),
        # the same median under two names, each with a bound of its own: a
        # cell lists `.host` where the host verifies its median block (a
        # 0.17 s time that moves with the box's cores, not with the chip)
        "finality_p50_s": p50,
        "finality_p50_s.host": p50,
        "setup_s": run["setup_s"],
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"] if values.get(m["name"]) is not None}


def per_layer(cell: dict, run: dict, device: dict, rehearse: bool):
    import readers
    import trace as tr

    reduced = {}
    log("dispatch ledger of the window: " + json.dumps(
        {k: dict(e, wall_s=round(e["wall_s"], 4))
         for k, e in run["win"]["dispatch"].items() if e["dispatches"]}))
    if run["trace_dir"] and not rehearse:
        loaded = tr.load_xplane(run["trace_dir"])
        log("trace planes: " + "; ".join(
            f"{p['name']}: " + ", ".join(f"{ln['name']} ({len(ln['events'])})"
                                         for ln in p["lines"])
            for p in loaded["planes"]))
        t_trace, for_s = run["traced"]
        now = time.monotonic()
        # the spans on the trace's clock; one still open ends now
        host = [(name, int((a - t_trace) * 1e9),
                 int(((now if b is None else b) - t_trace) * 1e9))
                for name, a, b in SPAN_LOG]
        reduced = tr.reduce(loaded, (0, int(for_s * 1e9)), host)
        log("trace programs: " + json.dumps(reduced["programs"]))
    src = readers.Sources(
        events=run["events"], seconds=run["seconds"], grace_s=run["grace_s"],
        counters=run["win"]["counters"], histograms=run["win"]["histograms"],
        blocks=run["blocks"], dispatch=run["win"]["dispatch"], trace=reduced,
        device_kind=device["kind"])
    out = {}
    for m in cell["per_layer"]:
        if rehearse and m["source"] == "device_trace":
            continue  # a CPU run reports nothing under a device name
        v = readers.read(src, m["reader"])
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out, reduced


def result_line(cell, run, device, trace: bool, rehearse: bool) -> dict:
    dev = dict(device, memory_peak_bytes=run["peak_bytes"])
    attempted = stats.attempted(run["events"], run["seconds"],
                                cell["mix"]["arrivals"] == "at_open")
    failed = [e for e in attempted
              if stats.is_failed(e, run["seconds"], run["grace_s"])]
    line = {"correct": all(ok for *_x, ok in run["checks"]),
            "attempted": len(attempted), "failed": len(failed)}
    if trace:
        line["metrics"], reduced = per_layer(cell, run, device, rehearse)
        if reduced:
            dev["busy_s"], dev["window_s"] = reduced["busy_s"], reduced["window_s"]
            line["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
    else:
        line["metrics"] = end_to_end(cell, run)
    line["device"] = dev
    return line


# ------------------------------------------------------------------- main


def rehearsal(cell: dict) -> dict:
    """The cell at the tiny sizes of its two files' `rehearsal` blocks. A
    configuration's rehearsal `transfer` takes the place of a mix's; a mix
    of `requests` brings its own small forms."""
    small = cell["config"].get("rehearsal", {})
    mix = {**cell["mix"], **cell["mix"].get("rehearsal", {})}
    if "transfer" in mix and "transfer" in small:
        mix["transfer"] = small["transfer"]
    return dict(cell, config={**cell["config"], **small}, mix=mix)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--seeds", help="comma-separated: one run per seed after "
                    "one warm-up (the seeds check, a sweep)")
    ap.add_argument("--rates", help="with --seeds: rate_tps of each run")
    args = ap.parse_args(argv)

    try:
        if not os.path.isdir(os.path.join(ROOT, "fabric_token_sdk_tpu")):
            raise Refused("no fabric_token_sdk_tpu package beside benchmark/: "
                          "the benchmark runs from the root of a checkout")
        manifest = mf.load()
        faults = mf.validate(manifest)
        if faults:
            raise Refused(f"BENCHMARK.json: {faults}")
        cell = mf.cell(manifest, args.workload)
        if args.rehearse_cpu:
            cell = rehearsal(cell)
        seeds = ([int(s) for s in args.seeds.split(",")] if args.seeds
                 else [args.seed])
        rates = [float(r) for r in args.rates.split(",")] if args.rates else []
        bad_kinds = cell["config"]["bad_requests"]

        def prepare(k: int):
            mix = dict(cell["mix"])
            if rates:
                mix["rate_tps"] = rates[k % len(rates)]
            one = dict(cell, mix=mix)
            workdir = os.path.join(OUT_DIR, f"{cell['name']}-{seeds[k]}-{k}")
            shutil.rmtree(workdir, ignore_errors=True)
            os.makedirs(workdir)
            entries = schedule.plan(mix, bad_kinds, args.seconds, seeds[k])
            return one, workdir, entries, start_corpus(one, seeds[k], entries, workdir)

        # the chip first: a run that finds none leaves nothing behind
        device = find_devices(cell["chips"], args.rehearse_cpu)
        one, workdir, entries, corpus_job = prepare(0)
    except Refused as e:
        print(f"[bench] refused: {e}", file=sys.stderr, flush=True)
        return 2
    log(f"device {device}")
    if args.trace:
        install_spans()

    setup_from = T_START
    for k in range(len(seeds)):
        nxt = None
        try:
            dep = Deployment(one["config"], corpus_job["art_dir"])
            node = stand_up(one, dep, workdir)
            try:
                issues = Issues(node["client"], corpus_job, workdir)
                issues.start()
                if k == 0:
                    # the issues are validated on the host meanwhile
                    warm = warm_programs(cell["config"]["warm_programs"])
                    log(f"warm {warm}")
                corpus = issues.result()
            except BaseException:
                shut_down(node)
                raise
            log(f"corpus of seed {seeds[k]}: " + ", ".join(
                f"{g} {corpus[g][0]['build']}" for g in list(corpus)[:2]))
            # the run shuts the node down, whatever happens in it
            run = run_once(one, dep, node, corpus, entries, args.seconds,
                           bool(args.trace), workdir, setup_from,
                           drain=k + 1 < len(seeds))
            line = result_line(one, run, device, bool(args.trace),
                               args.rehearse_cpu)
            if len(seeds) > 1:
                line["seed"], line["rate_tps"] = seeds[k], one["mix"].get("rate_tps")
                extra = end_to_end(one, run) if args.trace else {}
                line["metrics"] = dict(extra, **line["metrics"])
            # every number compared beside its limit: last in the line, and
            # the last lines on stderr (`judge` printed them to stdout)
            line["checks"] = {name: {"value": value, "limit": limit}
                              for name, value, limit, _ok in run["checks"]}
            print("\n".join(check_lines(run["checks"])), file=sys.stderr,
                  flush=True)
            print(json.dumps(line), flush=True)
            if k + 1 < len(seeds):
                # only now: corpus workers beside a window stall the node
                # and the generator, and a stall starts a device-mode episode
                nxt = prepare(k + 1)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
        if nxt:
            one, workdir, entries, corpus_job = nxt
            setup_from = time.monotonic()
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:
        import traceback

        traceback.print_exc()
        code = 1
    # a backlog leaves blocks in flight on the device, on threads of the
    # node: the run is over all the same
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
