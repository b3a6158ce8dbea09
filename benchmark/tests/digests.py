"""SHA-256 digests of what the harness builds from a mix and a seed: the
plan (`schedule.plan`) and every file the corpus workers write (the issue
files and the group files, through `run.start_corpus`: the harness's own
call site, the workers as child processes).

A group file's header carries `build`, the seconds its worker took: that is
the one thing in it that is not a function of (configuration, mix, seed),
and it is left out of the digest; everything else (the slot plan, `expect`,
`ref`, the blobs' lengths and the blobs) is in it.

    python3 benchmark/tests/digests.py > benchmark/tests/data/parent_digests.json

was run on the parent of PR 36 (0475c0c, before the first edit of that PR,
with `run.py:main`'s three lines that lay the `rehearsal` blocks over a cell
copied in: `run.rehearsal` since) and `test_forms.py` holds every later tree
to the file.
"""

import hashlib
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "harness"))

SEEDS = (7, 3_000_000_019)
# cell -> the window of a rehearsal plan (an `at_open` mix has none)
REHEARSAL_S = {"zk22.backlog": 51.0, "fab22.steady": 6.0, "zk22.steady": 20.0,
               "b300e5.batches": 20.0, "b300e5.testnet": 20.0}
FULL_S = 51.0


def sha(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def plan_digest(cell: dict, seconds: float, seed: int):
    import schedule

    entries = schedule.plan(cell["mix"], cell["config"]["bad_requests"],
                            seconds, seed)
    return entries, sha(json.dumps(entries, sort_keys=True).encode())


def corpus_digests(cell: dict, seed: int, entries: list) -> dict:
    """file name -> digest, of everything the workers wrote."""
    import run
    from corpus import read_group

    workdir = tempfile.mkdtemp(prefix="digests-")
    try:
        job = run.start_corpus(cell, seed, entries, workdir)
        for p in job["procs"]:
            if p.wait() != 0:
                raise RuntimeError(f"corpus worker exited {p.returncode}")
        out = {}
        for name in sorted(os.listdir(workdir)):
            if not name.endswith(".bin"):
                continue
            meta, blobs = read_group(os.path.join(workdir, name))
            meta.pop("build", None)
            out[name] = sha(json.dumps(meta, sort_keys=True).encode()
                            + b"".join(blobs))
        return out
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def all_digests(cells=None, corpus=True) -> dict:
    import manifest as mf
    import run

    manifest = mf.load()
    out = {}
    for name in cells or REHEARSAL_S:
        full = mf.cell(manifest, name)
        small = run.rehearsal(full)
        row = out[name] = {"config": full["config_name"],
                           "traffic": full["traffic_name"], "seeds": {}}
        for seed in SEEDS:
            entries, small_plan = plan_digest(small, REHEARSAL_S[name], seed)
            d = {"plan": plan_digest(full, FULL_S, seed)[1],
                 "rehearsal_plan": small_plan}
            if corpus:
                d["rehearsal_files"] = corpus_digests(small, seed, entries)
            row["seeds"][str(seed)] = d
    return out


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    json.dump(all_digests(sys.argv[1:] or None), sys.stdout, indent=1)
    print()
