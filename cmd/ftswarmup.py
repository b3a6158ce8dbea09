"""Precompile the verify + prove data planes into the persistent XLA cache.

Usage:
    python cmd/ftswarmup.py                 # full set (stages + pairing)
    python cmd/ftswarmup.py --no-pairing    # group-math stage tiles only
    python cmd/ftswarmup.py --no-prover     # skip prover-only programs
    python cmd/ftswarmup.py --list          # show the program inventory
                                            # (tagged verify/prove planes)

Prints ONE JSON summary line, e.g.:
    {"metric": "warmup", "programs": 12, "seconds": 412.3,
     "backend_compiles": 12, "cache_hits": 0, "cache_misses": 12, ...}

NOTE on cache keys: XLA compile options are part of the persistent-cache
key, and the test suite forces `--xla_force_host_platform_device_count=8`
(tests/conftest.py) — so warm the TEST environment with
`FTS_WARMUP=1 pytest tests/` (the session fixture shares the suite's
flags), and use this CLI for the bench/production environment.

Run this once after changing kernels, jax versions, or clearing the
persistent cache (the directory named by JAX_COMPILATION_CACHE_DIR, else
`<checkout>/.jax_cache`): afterwards every
`BatchedTransferVerifier.verify`, test session, and bench run replays the
whole verify plane from persistent-cache hits — zero recompiles
(`cache_misses` stays 0 in the `ftsmetrics show` compile summary).
A metrics sidecar (default WARMUP.metrics.json, override
FTS_METRICS_SIDECAR) records per-program compile seconds; inspect with
`python cmd/ftsmetrics.py show WARMUP.metrics.json`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="ftswarmup", description=__doc__.splitlines()[0]
    )
    ap.add_argument(
        "--no-pairing",
        action="store_true",
        help="skip the (large) miller/product/final-exp pairing tiles",
    )
    ap.add_argument(
        "--no-prover",
        action="store_true",
        help="skip programs used only by the batched prover "
        "(the shared verify+prove tiles still compile)",
    )
    ap.add_argument(
        "--list",
        action="store_true",
        help="list the canonical program inventory without compiling",
    )
    ap.add_argument(
        "--quiet",
        action="store_true",
        help="suppress per-program progress lines on stderr",
    )
    args = ap.parse_args(argv)

    from fabric_token_sdk_tpu.ops import warmup as wu
    from fabric_token_sdk_tpu.utils import metrics as mx

    if args.list:
        for name, _fn, shapes in wu.all_programs(
            not args.no_pairing, not args.no_prover
        ):
            planes = f"[{wu.program_planes(name)}]"
            print(
                f"{name:<24} {planes:<16} "
                f"{' x '.join(str(s) for s in shapes)}"
            )
        return 0

    mx.enable(True)
    mx.install_sidecar(
        os.environ.get("FTS_METRICS_SIDECAR", "WARMUP.metrics.json")
    )
    mx.REGISTRY.set_meta("entry", "ftswarmup.py")

    def progress(name, dt):
        if not args.quiet:
            print(f"[fts-warmup] {name} compiled in {dt:.1f}s",
                  file=sys.stderr, flush=True)

    summary = wu.warmup(
        include_pairing=not args.no_pairing,
        include_prover=not args.no_prover,
        progress=progress,
    )
    summary.pop("per_program", None)
    print(json.dumps({"metric": "warmup", **summary}), flush=True)
    mx.flush_sidecar()
    return 0


if __name__ == "__main__":
    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    )
    sys.exit(main())
