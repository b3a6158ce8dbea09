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
                 "import fabric_token_sdk_tpu.crypto.batch_sign"], timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.mark.slow
def test_cpu_rehearsal_passes_and_says_cpu(tmp_path):
    proc = _run([SMOKE, "--rehearse-cpu", "--out", str(tmp_path)],
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
        "serve", "agree",
    }
    assert doc["serve"]["validate_batched"] == doc["serve"]["transfers"]
    assert doc["agree"]["compared"] == doc["serve"]["transfers"]
    assert doc["agree"]["transfers_s"] > 0 < doc["serve"]["transfers_s"]
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
