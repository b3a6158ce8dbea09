"""Live ops plane: latency quantiles, memory telemetry, node
introspection RPCs served DURING commits, typed shutdown answers, and
the `ftstop` live view + perf-regression observatory.

Acceptance: a real `LedgerServer` under a driven workload answers
`ops.health` / `ops.metrics` mid-run — queue depth, height and a
nonzero block-commit p95 come back live, and probes never block behind
a slow commit; `ftstop compare` flags an injected regression between
two synthetic bench records.
"""

import json
import os
import sys
import threading
import time

import pytest

from fabric_token_sdk_tpu.api.validator import RequestValidator
from fabric_token_sdk_tpu.api.request import TokenRequest
from fabric_token_sdk_tpu.drivers.fabtoken import FabTokenDriver, FabTokenPublicParams
from fabric_token_sdk_tpu.services.network.ledger import FinalityEvent, Network, TxStatus
from fabric_token_sdk_tpu.services.network.orderer import BlockPolicy, Orderer
from fabric_token_sdk_tpu.services.network.remote import (
    LedgerServer,
    RemoteError,
    RemoteNetwork,
)
from fabric_token_sdk_tpu.services.ttx import Party, Transaction
from fabric_token_sdk_tpu.utils import faults
from fabric_token_sdk_tpu.utils import metrics as mx
from fabric_token_sdk_tpu.utils import sysmon

REPO = os.path.join(os.path.dirname(__file__), "..")


def _ftstop():
    sys.path.insert(0, os.path.join(REPO, "cmd"))
    try:
        import ftstop
    finally:
        sys.path.pop(0)
    return ftstop


# ------------------------------------------------------------ quantiles


def test_histogram_quantiles_interpolate_within_buckets():
    h = mx.Histogram("q.test", buckets=(1.0, 2.0, 4.0, 8.0))
    for v in [0.5] * 50 + [3.0] * 40 + [7.0] * 10:
        h.observe(v)
    # rank 50 falls in the first bucket: interpolated within [min, 1.0]
    assert 0.5 <= h.quantile(0.5) <= 1.0
    # rank 95 falls in the (4, 8] bucket: interpolated, clamped to max
    assert 4.0 < h.quantile(0.95) <= 7.0
    assert h.quantile(0.99) <= 7.0  # never above the observed max
    snap = h.snapshot()
    assert snap["p50"] == pytest.approx(h.quantile(0.5))
    assert snap["p95"] == pytest.approx(h.quantile(0.95))
    assert snap["p99"] == pytest.approx(h.quantile(0.99))


def test_histogram_quantile_single_value_is_exact():
    h = mx.Histogram("q.single", buckets=(1.0, 4.0))
    h.observe(3.0)
    # clamping to [min, max] makes a single observation report itself
    assert h.quantile(0.5) == 3.0
    assert h.quantile(0.99) == 3.0


def test_histogram_quantile_empty_and_inf_bucket():
    h = mx.Histogram("q.empty", buckets=(1.0,))
    assert h.quantile(0.5) is None
    assert "p50" not in h.snapshot()
    # everything beyond the last bound: the +Inf bucket reports max
    h.observe(5.0)
    h.observe(50.0)
    assert h.quantile(0.95) == 50.0


def test_prometheus_export_carries_quantile_series():
    reg = mx.Registry()
    h = reg.histogram("ops.check.seconds")
    h.observe(0.2)
    h.observe(0.4)
    text = reg.to_prometheus()
    assert "fts_ops_check_seconds_p50" in text
    assert "fts_ops_check_seconds_p95" in text
    assert "fts_ops_check_seconds_p99" in text


# ------------------------------------------------------------ memory telemetry


def test_sysmon_host_rss_and_gauges():
    assert sysmon.host_rss_bytes() > 1024 * 1024  # a live interpreter
    s = sysmon.sample()
    assert s["rss_bytes"] > 0
    assert mx.gauge("proc.rss.bytes").value > 0
    assert mx.gauge("proc.rss.peak.bytes").value >= mx.gauge("proc.rss.bytes").value * 0


def test_sysmon_device_memory_and_stage_high_water():
    # device_put only — no XLA program is compiled by sampling
    import numpy as np
    import jax.numpy as jnp

    compiled_before = mx.REGISTRY.histogram(
        "jax.core.compile.backend_compile_duration.seconds"
    ).count
    a = jnp.asarray(np.zeros((256, 256), dtype=np.int32))
    dev = sysmon.device_memory_bytes()
    assert dev is not None and dev >= a.nbytes
    sysmon._last_stage_sample = 0.0  # reset the throttle for the test
    s = sysmon.sample_stages()
    assert s is not None
    assert mx.gauge("stages.mem.high_water.bytes").value >= a.nbytes
    assert mx.gauge("stages.mem.rss_high_water.bytes").value > 0
    # throttled second call inside FTS_MEM_SAMPLE_S
    assert sysmon.sample_stages() is None
    compiled_after = mx.REGISTRY.histogram(
        "jax.core.compile.backend_compile_duration.seconds"
    ).count
    assert compiled_after == compiled_before, (
        "memory sampling must not compile XLA programs"
    )
    del a


# ------------------------------------------------------------ orderer gauges


def test_queue_depth_and_inflight_gauges_track_lifecycle():
    seen = {}

    def commit(batch):
        # mid-commit: the queue was drained by the cut, but every cut tx
        # is still IN FLIGHT until resolved
        seen["depth_mid"] = mx.gauge("orderer.queue.depth").value
        seen["inflight_mid"] = ordr.inflight()
        for s in batch:
            s._resolve(FinalityEvent(s.request.anchor, TxStatus.VALID))

    ordr = Orderer(commit, BlockPolicy(max_block_txs=8))
    subs = [ordr.enqueue(TokenRequest(anchor=f"t{i}")) for i in range(3)]
    assert mx.gauge("orderer.queue.depth").value == 3
    assert ordr.inflight() == 3
    ordr.flush()
    assert seen["depth_mid"] == 0  # cut drained the queue
    assert seen["inflight_mid"] == 3  # but nothing was resolved yet
    assert ordr.pending() == 0
    assert ordr.inflight() == 0
    assert mx.gauge("ledger.inflight").value == 0
    # submit→finality latency was observed for every tx, and is nonzero
    h = mx.REGISTRY.histogram("network.submit_to_finality.seconds")
    assert h.count >= 3
    assert all(s.done() for s in subs)
    # double resolve is idempotent (no negative inflight)
    subs[0]._resolve(FinalityEvent("t0", TxStatus.INVALID))
    assert ordr.inflight() == 0


# ------------------------------------------------------------ live node fixture


def _node(tmp_path=None, **client_kw):
    pp = FabTokenPublicParams()
    wal = str(tmp_path / "ledger.wal") if tmp_path is not None else None
    net = Network(
        RequestValidator(FabTokenDriver(pp)),
        policy=BlockPolicy(max_block_txs=4, min_batch=1),
        wal_path=wal,
    )
    server = LedgerServer(network=net).start()
    client = RemoteNetwork(server.address, **client_kw)
    issuer_p = Party("issuer", FabTokenDriver(pp), client)
    alice_p = Party("alice", FabTokenDriver(pp), client)
    iw = issuer_p.new_issuer_wallet("issuer")
    pp.add_issuer(iw.identity)
    alice = alice_p.new_owner_wallet("alice", anonymous=False)
    return server, client, issuer_p, alice

def _issue_requests(issuer_p, alice, n, tag="ops"):
    reqs = []
    for i in range(n):
        tx = Transaction(issuer_p, f"{tag}-{i}")
        tx.issue("issuer", "USD", [1 + i], [alice.recipient_identity()],
                 anonymous=False)
        tx.collect_endorsements(None)
        reqs.append(tx.request.to_bytes())
    return reqs


# ------------------------------------------------------------ acceptance


def test_ops_rpcs_answer_live_during_slow_commits(tmp_path):
    """ISSUE acceptance: poll `ops.health`/`ops.metrics` MID-RUN while
    commits are artificially slow — queue depth, height and a nonzero
    block-commit p95 come back live, and no probe ever waits behind a
    commit."""
    server, client, issuer_p, alice = _node(tmp_path)
    probe = RemoteNetwork(server.address)  # separate "monitoring" client
    delay_s = 0.3
    n_txs = 8
    try:
        reqs = _issue_requests(issuer_p, alice, n_txs)
        # every block commit now sleeps inside the commit path
        faults.arm("ledger.commit_block", "delay", delay_s=delay_s)
        errors = []

        def submitter(chunk):
            try:
                for rb in chunk:
                    ev = client.submit(rb)
                    assert ev.status == TxStatus.VALID, ev.message
            except Exception as e:  # pragma: no cover
                errors.append(e)

        commit_h = mx.REGISTRY.histogram("ledger.block.commit.seconds")
        pre_sum, pre_count = commit_h.sum, commit_h.count
        threads = [
            threading.Thread(target=submitter, args=(reqs[i::2],))
            for i in range(2)
        ]
        for t in threads:
            t.start()

        probes, peak_inflight, mid_hist = [], 0, None
        while any(t.is_alive() for t in threads):
            t0 = time.monotonic()
            h = probe.ops_health()
            probes.append(time.monotonic() - t0)
            peak_inflight = max(peak_inflight, h["inflight"])
            if mid_hist is None and h["height"] >= 2:
                # mid-run metrics snapshot: quantiles served live
                mid_hist = probe.ops_metrics()["histograms"].get(
                    "ledger.block.commit.seconds", {}
                )
            time.sleep(0.02)
        for t in threads:
            t.join()
        assert not errors, errors
    finally:
        faults.clear()
        server.stop()

    assert len(probes) >= 5, "workload finished before probes could sample"
    # no probe ever blocked behind a sleeping commit
    assert max(probes) < delay_s, (
        f"health probe blocked behind a commit: max={max(probes):.3f}s"
    )
    # the workload was genuinely in flight while we probed
    assert peak_inflight >= 1
    # the mid-run snapshot served live quantiles AND saw the injected
    # commit latency. The process registry is shared across the whole
    # pytest session, so absolute p95/max depend on what earlier tests
    # contributed (hundreds of fast commits from the batch-sign soak
    # smoke, multi-second zk commits from test_orderer) — assert on the
    # DELTA this test's own workload added instead: at least one block
    # committed during the run, and the added wall time carries the
    # injected delay. Quantile interpolation itself is pinned by the
    # dedicated Histogram quantile tests above.
    assert mid_hist is not None and mid_hist.get("p95") is not None
    assert mid_hist.get("count", 0) > pre_count
    assert mid_hist.get("sum", 0.0) - pre_sum >= delay_s * 0.9
    # final health is consistent (server stopped — read the ledger
    # directly): all txs finalized, nothing queued or in flight
    assert server.network.health()["txs_final"] == n_txs
    assert server.network.health()["queue_depth"] == 0
    assert server.network.health()["inflight"] == 0
    wal = server.network.health()["wal"]
    assert wal is not None and wal["bytes"] > 0 and not wal["poisoned"]
    lb = server.network.health()["last_block"]
    assert lb is not None and lb["commit_s"] >= delay_s * 0.9
    # `overlap_s` rides along only when the pipelined engine is active
    assert set(lb["breakdown"]) - {"overlap_s"} == {
        "queue_wait_max_s", "grouping_s", "device_verify_s",
        "verify_calls",  # completed proof-plane calls: 0 or 1 (PR 37)
        "sign_verify_s", "host_validate_s", "host_unmarshal_s",
        "host_fiat_shamir_s", "host_sig_verify_s",
        "host_conservation_s", "host_input_match_s", "wal_s", "merge_s",
        "host_sign_batch_s", "host_proof_batch_s",
        "host_conservation_batch_s",
        # the device planes' own split (dispatch ledger, utils/devobs.py)
        "verify_frames_s", "verify_wait_s", "verify_glue_s",
        "sign_frames_s", "sign_wait_s", "sign_glue_s",
    }


def test_ops_flight_tail_and_metrics_snapshot_over_wire(tmp_path):
    server, client, issuer_p, alice = _node(tmp_path)
    try:
        for rb in _issue_requests(issuer_p, alice, 2, tag="fl"):
            assert client.submit(rb).status == TxStatus.VALID
        events = client.ops_flight(16)
        kinds = {e["kind"] for e in events}
        assert "block.commit" in kinds and "finality" in kinds
        snap = client.ops_metrics()
        assert snap["counters"]["ledger.blocks.committed"] >= 2
        h = snap["histograms"]["network.submit_to_finality.seconds"]
        assert h["count"] >= 2 and h["p95"] > 0
        health = client.ops_health()
        assert health["uptime_s"] >= 0 and health["height"] == client.height()
        # a health probe refreshes the memory gauges server-side
        assert snap["gauges"].get("proc.rss.bytes", 0) > 0
    finally:
        server.stop()


def test_ops_calls_ride_idempotent_retry_path():
    """Satellite: ops RPCs go through `_call_idempotent` — a dropped
    connection is retried with backoff, not surfaced to the monitor."""
    server, client, issuer_p, alice = _node(retries=2, backoff_s=0.001)
    try:
        before = mx.REGISTRY.counter("remote.retry.ops.health").value
        faults.arm("remote.send", "drop", count=1)
        h = client.ops_health()
        assert h["height"] == 0
        assert mx.REGISTRY.counter("remote.retry.ops.health").value == before + 1
    finally:
        faults.clear()
        server.stop()


def test_stopping_server_answers_probes_typed():
    """Satellite: a stopping node answers in-flight ops probes with a
    typed `NodeStopped` error instead of a silently dropped connection."""
    server, client, issuer_p, alice = _node(retries=0)
    try:
        assert client.ops_health()["height"] == 0
        server._stopping.set()  # the stop() entry point, before severing
        with pytest.raises(RemoteError) as ei:
            client.ops_health()
        assert ei.value.error_class == "NodeStopped"
        assert mx.REGISTRY.counter("remote.dispatch.stopped").value >= 1
    finally:
        server.stop()


# ------------------------------------------------------------ compile budget


@pytest.mark.skipif(
    os.environ.get("FTS_WARMUP") != "1",
    reason="needs the FTS_WARMUP=1 session precompile (conftest fixture)",
)
def test_ops_plane_zero_cache_misses_after_warmup():
    """ISSUE acceptance: a warmup-then-ops-plane run — a batched zk
    block committed WHILE ops RPCs poll the node — misses the
    compilation cache zero times and compiles zero new programs. The ops
    plane (quantiles, memory sampling in `run_rows`, health/metrics/
    flight serving) must add NO XLA programs."""
    import random

    from test_orderer import build_env, issue_to, manual_transfer
    from fabric_token_sdk_tpu.crypto.setup import setup
    from fabric_token_sdk_tpu.drivers.zkatdlog import ZKATDLogDriver

    pp = setup(base=4, exponent=2, rng=random.Random(0xF75))
    network, parties, issuer, alice, bob = build_env(
        lambda: ZKATDLogDriver(pp), BlockPolicy(max_block_txs=8, min_batch=2)
    )
    alice_p = parties["alice-node"]
    issue_to(parties, alice, [5] * 4, "ops-seed")
    reqs = [
        manual_transfer(alice_p, tid, 5, bob.recipient_identity(), f"ops-{i}")
        for i, tid in enumerate(alice_p.vault.token_ids())
    ]
    server = LedgerServer(network=network).start()
    # the submit blocks for the whole block commit — minutes on a small
    # CPU host where the emulated device verify is slow. The PROBE keeps
    # the default 30s timeout: every poll must answer fast regardless.
    client = RemoteNetwork(server.address, timeout=900.0)
    probe = RemoteNetwork(server.address)
    misses_before = mx.REGISTRY.counter(
        "jax.compilation_cache.cache_misses"
    ).value
    stop = threading.Event()
    polled = []

    def poller():
        while not stop.is_set():
            polled.append(probe.ops_health()["height"])
            probe.ops_metrics()
            time.sleep(0.05)

    t = threading.Thread(target=poller)
    t.start()
    try:
        events = client.submit_many([r.to_bytes() for r in reqs])
        assert all(e.status.value == "Valid" for e in events)
    finally:
        stop.set()
        t.join()
        server.stop()
    assert polled, "ops plane never polled during the run"
    # `cache_misses == 0` IS the no-new-XLA-programs signal: this jax
    # fires backend_compile events on persistent-cache LOADS too, so the
    # histogram count moves on a warm first materialization — only a
    # MISS means a program outside the canonical warmed set appeared
    misses = (
        mx.REGISTRY.counter("jax.compilation_cache.cache_misses").value
        - misses_before
    )
    assert misses == 0, f"ops-plane run missed the cache {misses} time(s)"
    # the quantiles the run produced are in the registry snapshot
    snap = mx.REGISTRY.snapshot()
    assert snap["histograms"]["ledger.block.commit.seconds"]["p95"] > 0


# ------------------------------------------------------------ ftstop


def _full_record(**over):
    import bench

    r = bench.headline_result(
        rate=100.0, platform="cpu", batch=8, runs=1, warm_s=1.0,
        provegen_s=2.0, provegen_host_s=0.5, prove_txs=4, prove_rate=2.0,
        host_rate=1.0, prove_degraded=False, setup_s=0.1, stage_warmup_s=5.0,
    )
    r.update({"block_txs_per_s": 50.0, "block_vs_baseline": 0.376,
              "block_txs": 8, "block_batched_frac": 1.0,
              "block_provegen_s": 1.0, "wal_overhead_frac": 0.01})
    r.update(over)
    return r


def test_ftstop_compare_flags_injected_regression(tmp_path, capsys):
    """ISSUE acceptance: an injected regression between two synthetic
    bench records is flagged (and gates via the exit code)."""
    ftstop = _ftstop()
    old = _full_record()
    new = _full_record(value=55.0, block_txs_per_s=55.0)  # −45% verify
    a, b = tmp_path / "old.json", tmp_path / "new.json"
    a.write_text(json.dumps(old))
    b.write_text(json.dumps(new))
    rc = ftstop.main(["compare", str(a), str(b), "--threshold", "0.1"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "REGRESSION" in out and "value" in out
    vmap = {
        v["metric"]: v["verdict"]
        for v in ftstop.compare_records(old, new, 0.1)
    }
    assert vmap["value"] == "regression"
    assert vmap["wal_overhead_frac"] == "ok"
    # improvements and cost-metric direction
    vmap = {
        v["metric"]: v["verdict"]
        for v in ftstop.compare_records(
            old, _full_record(value=150.0, stage_warmup_s=50.0), 0.1
        )
    }
    assert vmap["value"] == "improvement"
    assert vmap["stage_warmup_s"] == "regression"  # cost metric grew 10x
    # within threshold: rc 0
    c = tmp_path / "same.json"
    c.write_text(json.dumps(_full_record(value=99.0)))
    assert ftstop.main(["compare", str(a), str(c)]) == 0


def test_ftstop_compare_history_median_baseline(tmp_path, capsys):
    import bench

    ftstop = _ftstop()
    hist = tmp_path / "BENCH_history.jsonl"
    # two deadline-degraded rounds (value=0) must NOT poison the baseline
    for _ in range(2):
        bench.append_history(
            bench.degraded_result("cpu", 2000.0, {}), path=str(hist)
        )
    for v in (100.0, 110.0, 90.0):
        bench.append_history(_full_record(value=v), path=str(hist))
    bench.append_history(_full_record(value=40.0), path=str(hist))
    hist.write_text(hist.read_text() + "{torn\n")  # torn tail tolerated
    rc = ftstop.main(["compare", "--history", str(hist)])
    out = capsys.readouterr().out
    assert rc == 1  # 40 vs median(100, 110, 90) = 100 → regression
    assert "median(3 prior full rounds)" in out  # degraded rounds excluded
    assert "REGRESSION" in out
    # --no-fail reports but does not gate
    assert ftstop.main(["compare", "--history", str(hist), "--no-fail"]) == 0
    capsys.readouterr()
    # an all-degraded baseline window is an error, not a silent diff
    short = tmp_path / "short.jsonl"
    bench.append_history(bench.degraded_result("cpu", 8.0, {}), path=str(short))
    bench.append_history(_full_record(), path=str(short))
    assert ftstop.main(["compare", "--history", str(short)]) == 2


def test_ftstop_compare_rejects_schema_invalid_records(tmp_path, capsys):
    ftstop = _ftstop()
    a = tmp_path / "bad.json"
    a.write_text(json.dumps({"metric": "wrong_name", "value": "NaN"}))
    b = tmp_path / "good.json"
    b.write_text(json.dumps(_full_record()))
    assert ftstop.main(["compare", str(a), str(b)]) == 2


def test_ftsmetrics_show_prints_ops_summary(tmp_path, capsys):
    """Satellite: the one-line ops summary (queue depth, memory
    high-water, block-commit + submit→finality p50/p95/p99) renders from
    any snapshot sidecar."""
    sys.path.insert(0, os.path.join(REPO, "cmd"))
    try:
        import ftsmetrics
    finally:
        sys.path.pop(0)
    reg = mx.Registry()
    reg.gauge("orderer.queue.depth").set(3)
    reg.gauge("ledger.inflight").set(5)
    reg.gauge("proc.rss.peak.bytes").set(123e6)
    reg.gauge("stages.mem.high_water.bytes").set(45e6)
    h = reg.histogram("ledger.block.commit.seconds")
    h.observe(0.3)
    h.observe(0.5)
    reg.histogram("network.submit_to_finality.seconds").observe(0.31)
    path = tmp_path / "ops.metrics.json"
    path.write_text(reg.to_json())
    ftsmetrics.show(str(path))
    out = capsys.readouterr().out
    assert "ops summary:" in out
    assert "queue_depth=3" in out and "inflight=5" in out
    assert "rss_peak=123.0MB" in out and "dev_mem_hw=45.0MB" in out
    assert "block_commit[p50/p95/p99]=" in out
    assert "finality[p50/p95/p99]=310.0ms/310.0ms/310.0ms" in out


def test_ftstop_top_renders_live_rows(tmp_path, capsys):
    ftstop = _ftstop()
    server, client, issuer_p, alice = _node(tmp_path)
    try:
        for rb in _issue_requests(issuer_p, alice, 2, tag="top"):
            assert client.submit(rb).status == TxStatus.VALID
        host, port = server.address
        rc = ftstop.top(f"{host}:{port}", interval=0.05, count=2)
    finally:
        server.stop()
    out = capsys.readouterr().out
    lines = [l for l in out.splitlines() if l.strip()]
    assert rc == 0 and len(lines) == 2
    assert "height=2" in lines[0]
    assert "p95.commit=" in lines[0]
    assert "tx/s=" in lines[1] and "wal=" in lines[0]
    # format_row is pure: a synthetic health/snapshot renders too
    row = ftstop.format_row({"uptime_s": 1.0, "height": 3}, {}, None, None)
    assert "height=3" in row
