"""`correct` has to come out false when the timed path is broken, and
when the limb product runs in the precision below the one it states.

Each case is one CPU rehearsal of the whole harness (corpus workers,
node, generator process, judgement) in a child process. The fabtoken
deployment keeps them short; the device sign plane is forced on by the
configuration's rehearsal block and the tests' own traffic
(`data/one_block.mix.json`: one hand-over of 8 transfers) makes sure a
block rides it. The first run compiles four programs on the CPU
backend (about two minutes each for `sound` and `bf16_limbs`, then cached
under `.jax_cache/`).

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_control.py
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def drive(mode, seed, mix="one_block", cell="fab22.steady", seconds=60):
    """One rehearsal of `cell` on the tests' mix `data/<mix>.mix.json`
    through drive_broken.py. -> (the result line, check name -> ok|FAILED)"""
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive_broken.py"), mode,
         os.path.join(HERE, "data", f"{mix}.mix.json"), "--workload", cell,
         "--rehearse-cpu", "--seconds", str(seconds), "--trace", "0",
         "--seed", str(seed)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=1500)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    checks = {ln.split()[1].split("=")[0]: ln.split()[-1]
              for ln in lines if ln.startswith("check ")}
    return json.loads(lines[-1]), checks


def test_sound_run_is_correct():
    line, checks = drive("sound", 11)
    assert line["correct"] is True, checks
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] == 8 and line["failed"] == 0
    assert set(checks.values()) == {"ok"}


def test_an_answer_altered_where_it_is_produced_reads_incorrect():
    line, checks = drive("accept_all", 12)
    assert line["correct"] is False
    # the double spend and the bad signature came back Valid
    assert checks["verdicts_differing_from_scalar_reference"] == "FAILED"
    assert checks["verdicts_differing_from_construction"] == "FAILED"


@pytest.mark.parametrize("seed", [13, 14, 3_000_000_015])
def test_control_limb_product_in_lower_precision_reads_incorrect(seed):
    line, checks = drive("bf16_limbs", seed)
    assert line["correct"] is False
    assert checks["verdicts_differing_from_scalar_reference"] == "FAILED"
