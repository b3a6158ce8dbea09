"""Warmup precompiler: AOT-compile every canonical verify-plane program.

The staged execution model (`ops/stages.py`, `ops/pairing.py` tiles)
makes the verifier's distinct-program set a small constant; this module
compiles that whole set ahead of time — populating the persistent XLA
compilation cache (`JAX_COMPILATION_CACHE_DIR`, else
`<checkout>/.jax_cache`; see `ops/__init__.py`) — so no verify, test, or
benchmark ever pays a surprise giant compile mid-flight. After `warmup()` (or `python cmd/ftswarmup.py`), a
`BatchedTransferVerifier.verify` recompiles nothing: every program loads
as a `jax.compilation_cache.cache_hits` hit (`cache_misses` stays 0).

Entry points:
  * `warmup()`               — library call (bench.py, pytest fixture)
  * `cmd/ftswarmup.py`       — CLI wrapper
  * `FTS_WARMUP=1 pytest`    — opt-in session fixture (tests/conftest.py)
"""

from __future__ import annotations

import time
from typing import Iterable, Optional, Tuple

import jax
import jax.numpy as jnp

from . import limbs as lb, pairing as pr, stages as st
from ..utils import devobs
from ..utils import metrics as mx

_CACHE_COUNTERS = (
    "jax.compilation_cache.cache_hits",
    "jax.compilation_cache.cache_misses",
)
_COMPILES = "jax.core.compile.backend_compile_duration.seconds"


def pairing_programs() -> Iterable[Tuple[str, object, tuple]]:
    """The staged pairing tile programs (miller / per-K product /
    final-exp), canonical shapes. Every pairing product has 2 legs
    (Pointcheval-Sanders, membership verify since its four were merged
    by bilinearity, and the membership GT pre-commitment on the prove
    side). Nothing calls the 4-leg product any more: it stays in the set
    while the benchmark's configurations name it among their
    `warm_programs` (`benchmark/run.py` raises on an unknown name), for
    the `benchmark` PR that takes it out of both at once."""
    L = lb.NLIMBS
    T = st.tile_rows("miller_tile")
    yield ("miller_tile", pr.miller_loop, ((T, 2, L), (T, 2, 2, L)))
    F = st.tile_rows("fexp_tile")
    for k in (2, 4):
        yield (f"gt_product_k{k}_tile", pr._product_rows, ((F, k, 6, 2, L),))
    yield ("final_exp_tile", pr.final_exp, ((F, 6, 2, L),))


# Program-set classification for `cmd/ftswarmup.py --list` and the
# `--no-prover` opt-out. The batched prover (`crypto/batch_prove.py`) is
# BY CONSTRUCTION a composition of the same canonical tiles as the
# verify plane — its only private program is the Jacobian add tile (the
# signature-obfuscation step S'' = S' + P^bf); everything else is
# shared, which is what lets the post-warmup zero-cache-miss guarantee
# extend to proving without growing the program set.
PROVER_PROGRAMS = frozenset(
    {
        "g1_msm1_tile", "g1_msm2_tile", "g1_msm3_tile",
        "g1_mul_tile", "g1_add_tile",
        "g2_mul_tile", "g2_add_tile", "g2_to_affine_tile",
        "miller_tile", "gt_product_k2_tile", "final_exp_tile",
    }
)
PROVER_ONLY_PROGRAMS = frozenset({"g1_add_tile"})


def program_planes(name: str) -> str:
    """'verify', 'prove', or 'verify+prove' for a canonical program."""
    if name in PROVER_ONLY_PROGRAMS:
        return "prove"
    return "verify+prove" if name in PROVER_PROGRAMS else "verify"


def all_programs(include_pairing: bool = True, include_prover: bool = True):
    progs = list(st.stage_programs())
    if include_pairing:
        progs += list(pairing_programs())
    if not include_prover:
        progs = [p for p in progs if p[0] not in PROVER_ONLY_PROGRAMS]
    return progs


def warmup(
    include_pairing: bool = True,
    persist_all: bool = True,
    progress: Optional[callable] = None,
    include_prover: bool = True,
) -> dict:
    """AOT-lower and compile every canonical program; returns a summary.

    persist_all drops `jax_persistent_cache_min_compile_time_secs` to 0 so
    even fast-compiling tile programs land in the persistent cache — the
    guarantee that a LATER process replays the whole verify plane from
    cache hits alone (cache_misses stays 0; nothing recompiles).
    """
    prev_min_compile = None
    if persist_all:
        prev_min_compile = jax.config.jax_persistent_cache_min_compile_time_secs
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    before = {c: mx.REGISTRY.counter(c).value for c in _CACHE_COUNTERS}
    compiles_before = mx.REGISTRY.histogram(_COMPILES).count
    programs = []
    t_total = time.time()
    try:
        with mx.span("warmup.precompile", include_pairing=include_pairing):
            for name, fn, shapes in all_programs(include_pairing, include_prover):
                specs = [jax.ShapeDtypeStruct(s, jnp.int32) for s in shapes]
                t0 = time.time()
                # attribute the compile/cache events this AOT compile
                # fires to the canonical program name — the ledger join
                # between jax.monitoring and the program registry
                with devobs.attribute(name):
                    fn.lower(*specs).compile()
                dt = time.time() - t0
                mx.counter("warmup.programs").inc()
                mx.REGISTRY.histogram("warmup.program.seconds").observe(dt)
                programs.append({"name": name, "seconds": round(dt, 3)})
                if progress is not None:
                    progress(name, dt)
    finally:
        # confine persist-everything to the warmup set: later incidental
        # compiles go back to the configured persistence threshold
        if prev_min_compile is not None:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs",
                prev_min_compile,
            )
    total = time.time() - t_total
    summary = {
        "programs": len(programs),
        "seconds": round(total, 3),
        "backend_compiles": mx.REGISTRY.histogram(_COMPILES).count - compiles_before,
        "per_program": programs,
    }
    for c in _CACHE_COUNTERS:
        summary[c.rsplit(".", 1)[-1]] = mx.REGISTRY.counter(c).value - before[c]
    mx.gauge("warmup.seconds").set(round(total, 3))
    return summary
