"""Metrics layer: concurrency, span nesting, export round-trips, the
disabled fast path, and crash-proof sidecar flushing (SIGTERM / deadline).
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from fabric_token_sdk_tpu.utils import metrics as mx

REPO = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture
def registry():
    """Fresh scratch registry + enabled spans, global state restored."""
    was = mx.enabled()
    reg = mx.Registry()
    mx.enable(True)
    try:
        yield reg
    finally:
        mx.enable(was)


# ------------------------------------------------------------ concurrency


def test_concurrent_counter_and_histogram_updates(registry):
    c = registry.counter("t.count")
    h = registry.histogram("t.hist")
    g = registry.gauge("t.gauge")
    N, T = 2000, 8

    def work(k):
        for i in range(N):
            c.inc()
            h.observe(0.001 * (i % 7))
            g.set(k)

    threads = [threading.Thread(target=work, args=(k,)) for k in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == N * T
    snap = h.snapshot()
    assert snap["count"] == N * T
    assert sum(snap["buckets"].values()) == N * T
    assert 0 <= g.value < T


def test_counter_get_or_create_races(registry):
    """Same-name instrument from many threads resolves to ONE counter."""
    seen = []

    def work():
        c = registry.counter("shared")
        c.inc()
        seen.append(c)

    threads = [threading.Thread(target=work) for _ in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert registry.counter("shared").value == 16
    assert all(c is seen[0] for c in seen)


# ------------------------------------------------------------ span trees


def test_span_nesting_builds_tree():
    was = mx.enabled()
    mx.enable(True)
    before = len(mx.REGISTRY.snapshot()["spans"])
    try:
        with mx.span("outer", who="test") as outer:
            with mx.span("inner.a"):
                with mx.span("leaf"):
                    pass
            with mx.span("inner.b"):
                pass
    finally:
        mx.enable(was)
    assert outer.end is not None
    assert [c.name for c in outer.children] == ["inner.a", "inner.b"]
    assert [c.name for c in outer.children[0].children] == ["leaf"]
    # only the ROOT is recorded in the registry; children hang off it
    spans = mx.REGISTRY.snapshot()["spans"]
    assert len(spans) == before + 1
    agg = mx.REGISTRY.span_summary()
    for name in ("outer", "inner.a", "inner.b", "leaf"):
        assert agg[name]["count"] >= 1
    # span durations auto-feed the <name>.seconds histogram
    assert mx.REGISTRY.histogram("outer.seconds").count >= 1


def test_span_duration_accumulates_child_time():
    was = mx.enabled()
    mx.enable(True)
    try:
        with mx.span("parent.timed") as p:
            with mx.span("child.timed"):
                time.sleep(0.02)
    finally:
        mx.enable(was)
    assert p.duration >= 0.02
    assert p.children[0].duration >= 0.02


# ------------------------------------------------------------ export


def test_json_export_round_trip(registry):
    registry.counter("a.count").inc(5)
    registry.gauge("b.gauge").set(2.5)
    h = registry.histogram("c.seconds")
    for v in (0.002, 0.3, 7.0, 700.0):
        h.observe(v)
    registry.set_meta("platform", "cpu")
    registry.record_phase("compile", 100.0, 134.5, program="miller_tile")

    d = json.loads(registry.to_json())
    assert d["counters"]["a.count"] == 5
    assert d["gauges"]["b.gauge"] == 2.5
    hh = d["histograms"]["c.seconds"]
    assert hh["count"] == 4
    assert abs(hh["sum"] - 707.302) < 1e-6
    assert hh["buckets"]["+Inf"] == 1  # 700 > top bucket
    assert d["meta"]["platform"] == "cpu"
    assert d["phases"][0]["name"] == "compile"
    assert d["phases"][0]["elapsed_s"] == 34.5
    assert d["phases"][0]["attrs"]["program"] == "miller_tile"


def test_prometheus_export(registry):
    registry.counter("jax.cache.load_failures").inc(3)
    registry.gauge("vault.tokens.held").set(12)
    h = registry.histogram("verify.seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(50.0)
    text = registry.to_prometheus()
    assert "# TYPE fts_jax_cache_load_failures counter" in text
    assert "fts_jax_cache_load_failures 3" in text
    assert "fts_vault_tokens_held 12" in text
    # cumulative buckets: 0.1 -> 1, 1.0 -> 2, +Inf -> 3
    assert 'fts_verify_seconds_bucket{le="0.1"} 1' in text
    assert 'fts_verify_seconds_bucket{le="1"} 2' in text
    assert 'fts_verify_seconds_bucket{le="+Inf"} 3' in text
    assert "fts_verify_seconds_count 3" in text


def test_ftsmetrics_cli_show_and_diff(registry, tmp_path, capsys):
    sys.path.insert(0, os.path.join(REPO, "cmd"))
    try:
        import ftsmetrics
    finally:
        sys.path.pop(0)
    registry.counter("network.tx.valid").inc(7)
    registry.record_phase("setup", 0.0, 1.25)
    h = registry.histogram("compile.seconds", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(30.0)
    a = tmp_path / "a.metrics.json"
    a.write_text(registry.to_json())
    registry.counter("network.tx.valid").inc(3)
    b = tmp_path / "b.metrics.json"
    b.write_text(registry.to_json())

    ftsmetrics.show(str(a))
    out = capsys.readouterr().out
    assert "network.tx.valid" in out and "setup" in out
    ftsmetrics.diff(str(a), str(b))
    out = capsys.readouterr().out
    assert "7 -> 10" in out
    # Prometheus view must include the histogram series from the sidecar
    ftsmetrics.show(str(a), prometheus=True)
    out = capsys.readouterr().out
    assert 'fts_compile_seconds_bucket{le="1"} 1' in out
    assert 'fts_compile_seconds_bucket{le="+Inf"} 2' in out
    assert "fts_compile_seconds_count 2" in out


# ------------------------------------------------------------ disabled path


def test_disabled_span_records_nothing_and_is_cheap():
    was = mx.enabled()
    mx.enable(False)
    try:
        before = len(mx.REGISTRY.snapshot()["spans"])
        t0 = time.monotonic()
        for _ in range(20000):
            with mx.span("hot.loop", k=1):
                pass
        elapsed = time.monotonic() - t0
        assert len(mx.REGISTRY.snapshot()["spans"]) == before
        assert mx.REGISTRY.histogram("hot.loop.seconds").count == 0
        # smoke bound, not a benchmark: 20k disabled spans in well under
        # the time one single pairing takes
        assert elapsed < 2.0
    finally:
        mx.enable(was)


def test_tracer_facade_feeds_shared_registry():
    from fabric_token_sdk_tpu.utils.tracing import tracer

    was = mx.enabled()
    mx.enable(True)
    try:
        tracer.count("facade.count", 4)
        with tracer.span("facade.span"):
            pass
    finally:
        mx.enable(was)
    assert mx.REGISTRY.counter("facade.count").value >= 4
    assert mx.REGISTRY.span_summary()["facade.span"]["count"] >= 1


def test_service_plane_counters_populate():
    """Acceptance: one end-to-end fungible flow must land metrics from
    at least three services (selector, vault, ttx) plus the network."""
    from fabric_token_sdk_tpu.drivers.fabtoken import (
        FabTokenDriver,
        FabTokenPublicParams,
    )
    from fabric_token_sdk_tpu.services.ttx import Transaction
    from test_services_fungible import build_env

    was = mx.enabled()
    mx.enable(True)
    base = {
        name: mx.REGISTRY.counter(name).value
        for name in (
            "selector.lock.acquired",
            "vault.tokens.stored",
            "vault.tokens.spent",
            "ttx.submitted",
            "ttx.committed",
            "network.tx.valid",
        )
    }
    try:
        network, auditor_svc, parties, issuer, alice, bob = build_env(
            lambda: FabTokenDriver(FabTokenPublicParams())
        )
        tx = Transaction(parties["issuer-node"], "mx-issue")
        tx.issue("issuer", "USD", [10], [alice.recipient_identity()],
                 anonymous=False)
        tx.collect_endorsements(auditor_svc)
        tx.submit()
        tx2 = Transaction(parties["alice-node"], "mx-pay")
        tx2.transfer("alice", "USD", [4], [bob.recipient_identity()])
        tx2.collect_endorsements(auditor_svc)
        tx2.submit()
    finally:
        mx.enable(was)

    def delta(name):
        return mx.REGISTRY.counter(name).value - base[name]

    assert delta("selector.lock.acquired") >= 1
    assert delta("vault.tokens.stored") >= 2  # issue output + transfer outs
    assert delta("vault.tokens.spent") >= 1
    assert delta("ttx.submitted") == 2
    assert delta("ttx.committed") == 2
    assert delta("network.tx.valid") == 2
    # span histograms captured the stage durations
    for h in ("ttx.assemble.seconds", "ttx.endorse.seconds",
              "ttx.order_and_finality.seconds", "network.submit.seconds",
              "vault.on_finality.seconds", "selector.select.seconds"):
        assert mx.REGISTRY.histogram(h).count >= 1, f"missing {h}"


def test_native_selfcheck_counted():
    """hostmath's import-time self-check must land in the registry
    (pass on this box where the .so builds, or an explanatory fail)."""
    from fabric_token_sdk_tpu.crypto import hostmath as hm

    passed = mx.REGISTRY.counter("native.selfcheck.pass").value
    failed = mx.REGISTRY.counter("native.selfcheck.fail").value
    if hm.NATIVE_G1:
        assert passed >= 1
        assert failed == 0
    else:
        # native disabled/unbuildable is fine — but a counted PASS with
        # native not installed would mean it was silently dropped after
        # adoption
        assert passed == 0


# ------------------------------------------------------------ sidecar


def test_flush_sidecar_atomic(tmp_path, registry):
    path = tmp_path / "t.metrics.json"
    mx.REGISTRY.counter("flush.check").inc()
    out = mx.flush_sidecar(str(path))
    assert out == str(path)
    d = json.loads(path.read_text())
    assert d["counters"]["flush.check"] >= 1
    assert not list(tmp_path.glob("*.tmp"))


def _spawn_bench(tmp_path, extra_env):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["FTS_METRICS_SIDECAR"] = str(tmp_path / "BENCH_test.metrics.json")
    env["FTS_HEARTBEAT_SECS"] = "1"
    env.update(extra_env)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "bench.py")],
        cwd=REPO,
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )
    return proc, env["FTS_METRICS_SIDECAR"]


def _wait_for_heartbeat(proc, timeout=180.0):
    """Read stderr lines until the first phase-stamped heartbeat."""
    lines = []
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        line = proc.stderr.readline()
        if not line:
            if proc.poll() is not None:
                break
            time.sleep(0.05)
            continue
        lines.append(line)
        if "phase=" in line:
            return lines
    raise AssertionError(f"no heartbeat before timeout; stderr: {lines!r}")


def _drain(proc):
    try:
        proc.stdout.read()
        proc.stderr.read()
    except Exception:
        pass


def test_bench_sidecar_flushed_on_sigterm(tmp_path):
    """A SIGTERM'd bench run (what `timeout` sends first) must leave a
    phase-stamped metrics sidecar — rc=124 is not a zero-info outcome."""
    proc, sidecar = _spawn_bench(tmp_path, {})
    try:
        _wait_for_heartbeat(proc)
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
        _drain(proc)
    assert os.path.exists(sidecar), "SIGTERM did not flush the sidecar"
    d = json.loads(open(sidecar).read())
    assert d["meta"]["entry"] == "bench.py"
    assert d["meta"]["killed_by_signal"] == int(signal.SIGTERM)
    assert d["phases"], "no phase timeline recorded"
    assert "counters" in d and "histograms" in d
    # the flight recorder shipped its ring alongside the metrics sidecar:
    # the lifecycle trail (phase events at minimum) survives the kill
    flight = sidecar[: -len(".metrics.json")] + ".flight.json"
    assert os.path.exists(flight), "SIGTERM did not dump the flight ring"
    fd = json.loads(open(flight).read())
    assert fd["events"], "flight ring dumped empty"
    assert any(e["kind"] == "phase" for e in fd["events"])
    # exit status must still reflect the kill (handler chains to default)
    assert proc.returncode != 0


def test_bench_sidecar_flushed_on_deadline(tmp_path):
    """Simulated timeout via a short FTS_BENCH_DEADLINE: the watchdog
    must log to stderr, flush the sidecar with per-phase wall times and
    compile/cache counters, print a DEGRADED-but-parsed result JSON and
    exit 1 — a missed deadline is a failed run on every platform, but
    never a silent rc=124."""
    proc, sidecar = _spawn_bench(tmp_path, {"FTS_BENCH_DEADLINE": "8"})
    try:
        proc.wait(timeout=300)
    finally:
        if proc.poll() is None:
            proc.kill()
        out, err = proc.communicate(timeout=30)
    assert proc.returncode == 1, f"expected rc=1 with degraded JSON, got {proc.returncode}; stderr tail: {err[-2000:]}"
    assert "DEADLINE" in err
    # the driver can parse the outcome: degraded JSON with the live phase
    degraded = json.loads(out.strip().splitlines()[-1])
    assert degraded["degraded"] is True
    assert (degraded["platform"], degraded["device_kind"]) == ("cpu", "cpu")
    assert degraded["metric"] == "zkatdlog_transfer_verify_throughput"
    assert degraded["deadline_s"] == 8.0
    assert "phase" in degraded
    assert os.path.exists(sidecar), "deadline did not flush the sidecar"
    d = json.loads(open(sidecar).read())
    assert d["meta"]["deadline_fired_s"] == 8.0
    # the phase timeline pinpoints where the time went at death
    phases = {p["name"] for p in d["phases"]}
    assert "init" in phases
    assert any("elapsed_s" in p for p in d["phases"])
    assert "progress.phase" in d["meta"]  # the phase that was live at kill
    # compile/cache counters exist in the dump (may be zero this early)
    assert isinstance(d["counters"], dict)
    # ISSUE acceptance: a deadline-killed bench leaves a flight-record
    # sidecar whose ring ends with the watchdog's own death marker, after
    # the lifecycle events (phases at minimum) that led up to it
    flight = sidecar[: -len(".metrics.json")] + ".flight.json"
    assert os.path.exists(flight), "deadline did not dump the flight ring"
    fd = json.loads(open(flight).read())
    kinds = [e["kind"] for e in fd["events"]]
    assert "phase" in kinds
    assert "bench.deadline" in kinds
    dl = [e for e in fd["events"] if e["kind"] == "bench.deadline"][-1]
    assert dl["deadline_s"] == 8.0 and "phase" in dl
