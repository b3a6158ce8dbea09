"""chip_smoke.py's contract, as far as a CPU can show it: it refuses to
run without a chip, it cannot pass on the host's work, and the entry
points around it never choose the platform themselves."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, env=None, cwd=REPO, timeout=120):
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env or dict(os.environ),
        capture_output=True, text=True, timeout=timeout,
    )


def _results(stdout: str) -> list:
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_smoke_refuses_to_run_without_a_chip(tmp_path):
    """No accelerator and no rehearsal flag: non-zero within seconds,
    the reason on stderr, no result on stdout."""
    proc = _run([SMOKE, "--out", str(tmp_path)], timeout=60)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr and "'cpu'" in proc.stderr
    assert not _results(proc.stdout)


def test_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program must fail even where the device
    check passes (here: the CPU rehearsal), before it prints anything to
    stdout."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = _run(["chip_smoke.py", "--rehearse-cpu"], cwd=str(tmp_path),
                timeout=60)
    assert proc.returncode != 0
    assert "fabric_token_sdk_tpu" in proc.stderr
    assert proc.stdout == ""


def test_graft_entry_import_never_chooses_the_platform():
    """`JAX_PLATFORMS` unset stays unset: on a machine with a chip,
    importing the entry module must not pin the process to the CPU."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    proc = _run(["-c", (
        "import os, sys, __graft_entry__ as g\n"
        "g.ensure_virtual_devices(8)\n"
        "assert 'JAX_PLATFORMS' not in os.environ, os.environ['JAX_PLATFORMS']\n"
        "assert 'XLA_FLAGS' not in os.environ\n"
        "assert 'jax' not in sys.modules\n"
    )], env=env, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_package_imports_clean_under_deprecation_errors():
    proc = _run(["-W", "error::DeprecationWarning", "-c",
                 "import fabric_token_sdk_tpu.parallel"], timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_mesh4_placement_verdict_on_the_recorded_four_chip_stats():
    """The `mesh4` phase runs only with 4 chips, and the CPU backend has
    no allocator report, so its verdict is pinned here against what the
    PR-21 four-chip run read: an untouched v5e reports a 27,136 B peak
    at start-up; after the mesh block device 0 read 301,931,520 B and
    devices 1-3 still 27,136 B."""
    import chip_smoke

    peak0 = {d: 27_136 for d in range(4)}
    stats = {0: {"peak_bytes_in_use": 301_931_520, "bytes_in_use": 4_096},
             **{d: {"peak_bytes_in_use": 27_136, "bytes_in_use": 27_136}
                for d in (1, 2, 3)}}
    rows = chip_smoke.placement(stats, peak0, live={0: 12})
    assert [r["id"] for r in rows] == [0, 1, 2, 3]
    assert [r["ever_held_an_array"] for r in rows] == [True, False, False,
                                                       False]
    assert rows[1]["peak_bytes_at_start"] == 27_136
    # a live array counts whatever the allocator says; a backend without
    # an allocator report (CPU: memory_stats() is None) is judged by
    # live arrays alone
    rows = chip_smoke.placement({0: None, 1: None}, {0: None, 1: None},
                                live={1: 3})
    assert [r["ever_held_an_array"] for r in rows] == [False, True]
    # a peak above the start-up reading counts after the arrays are freed
    rows = chip_smoke.placement({2: {"peak_bytes_in_use": 27_137}},
                                {2: 27_136}, live={})
    assert rows[0]["ever_held_an_array"] is True


@pytest.mark.slow
def test_cpu_rehearsal_passes_and_says_cpu(tmp_path):
    """Four virtual devices, so that the `mesh4` phase runs too (judged
    by live arrays here: the CPU backend has no allocator report)."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = _run([SMOKE, "--rehearse-cpu", "--out", str(tmp_path)], env=env,
                timeout=1500)
    assert proc.returncode == 0, proc.stderr[-3000:]
    # the last line is the verdict, these keys and no other; the line
    # before it is the summary
    summary, verdict = proc.stdout.splitlines()[-2:]
    doc = json.loads(summary)
    assert json.loads(verdict) == {"ok": True, "device": doc["device"]}
    assert set(doc["device"]) == {"platform", "kind", "count"}
    assert doc["ok"] is True and doc["device"]["platform"] == "cpu"
    assert doc["reduced"], "a rehearsal is always a cut"
    assert set(doc["phases"]) >= {
        "device", "native", "field", "warmup", "tiles", "setup", "prove",
        "serve", "agree", "mesh4",
    }
    assert doc["serve"]["validate_batched"] == doc["serve"]["transfers"]
    assert doc["agree"]["compared"] == doc["serve"]["transfers"]
    assert doc["agree"]["transfers_s"] > 0 < doc["serve"]["transfers_s"]
    mesh4 = doc["mesh4"]
    assert len(mesh4["per_device"]) == 4 and mesh4["sharded_calls"] > 0
    assert 0 not in mesh4["idle_devices"]
    assert not any(doc["counters"].values())


@pytest.mark.slow
def test_cpu_rehearsal_cannot_pass_on_the_host(tmp_path):
    """With the verify plane faulted every block still commits with the
    right verdicts (the node degrades to the host) — and exactly that
    must fail the smoke, naming the fallback."""
    env = dict(os.environ, FTS_FAULTS="batch.verify:error:1.0")
    proc = _run([SMOKE, "--rehearse-cpu", "--out", str(tmp_path)], env=env,
                timeout=1500)
    assert proc.returncode != 0
    assert "verify.host_fallback" in proc.stderr
    assert not _results(proc.stdout)
