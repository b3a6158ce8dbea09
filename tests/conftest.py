"""Test configuration: force JAX onto the CPU platform.

Kernels are written for TPU; CPU execution exercises identical XLA
programs.
"""
import os
import sys

# The package ships without an installer; the repo root on sys.path is
# what makes `fabric_token_sdk_tpu` (and `import __graft_entry__`)
# importable from any pytest invocation directory.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# Tests always run on the CPU, with 8 virtual devices. No test needs a
# second device any more (a named debt, ROADMAP.md): XLA's options are
# part of the compile-cache key, so dropping the flag would make the
# next tier-1 run recompile every tile. Both must be in the environment
# before the first jax import; an XLA_FLAGS device count the caller
# already set is kept.
os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent XLA compilation cache is configured centrally in
# fabric_token_sdk_tpu/ops/__init__.py (JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache); kernels are row-tiled (ops/stages.py tile_rows)
# and setup fixtures seeded so cache entries hit across runs.

import random

import pytest


@pytest.fixture(scope="session", autouse=True)
def fts_warmup_session():
    """Opt-in session warmup: `FTS_WARMUP=1 pytest ...` AOT-compiles the
    whole canonical stage/pairing program set up front (populating the
    persistent cache), so no test ever pays a surprise giant compile
    mid-session. `FTS_WARMUP_PAIRING=0` skips the large pairing tiles."""
    if os.environ.get("FTS_WARMUP") == "1":
        from fabric_token_sdk_tpu.ops import warmup as wu

        wu.warmup(
            include_pairing=os.environ.get("FTS_WARMUP_PAIRING", "1") == "1"
        )
    yield


@pytest.fixture
def rng():
    return random.Random(0xF75)


@pytest.fixture(autouse=True)
def _disarm_faults():
    """No fault armed in one test may leak into the next (the fault
    registry is process-global by design — see utils/faults.py), and no
    tripped circuit breaker may reject the next test's device dispatch
    (the breaker registry is process-global too)."""
    yield
    from fabric_token_sdk_tpu.utils import faults, resilience

    if faults.armed():
        faults.clear()
    resilience.reset()
