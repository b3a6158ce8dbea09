"""Mesh sharding of batched proof generation + verification.

Scale-out model (SURVEY §2, TPU-scale subsystems): proof batches are
data-parallel over a `dp` mesh axis; the K legs of pairing products can
additionally shard over an `mp` axis, combined with an `all_gather`
collective before the shared final exponentiation — the ICI-friendly
layout (batch stays put, only 12-coefficient GT values move).

Two complementary mechanisms:

* **Per-shard stage-tile dispatch** (`MeshConfig`, `run_rows_dp`,
  `sharded_schnorr_rows`, the default `sharded_pairing_product` path) —
  the dp axis partitions the FLAT ROW stream of the staged execution
  model (`ops/stages.py`) and the mp axis the pairing-leg tile stream
  (`ops/pairing.py`): each shard walks its contiguous span of canonical
  tile slabs through the SAME compile-once tile executables, so sharding
  adds ZERO new XLA programs. This is the dispatch the PRODUCT planes
  ride: `BlockValidationPipeline` group verification (`crypto/batch.py`)
  and the batched prover (`crypto/batch_prove.py`) both accept a
  `MeshConfig` (or the ambient `FTS_MESH_DEVICES`/`FTS_MESH_MP` /
  `FTS_DP_SHARDS` env). Any sharded-dispatch failure degrades to the
  unsharded runner (`sharding.fallbacks`), which itself degrades to host
  validation — accept/reject can never depend on the mesh.
* **`shard_map` pairing product** (`sharded_pairing_product(fused=True)`)
  — the dp x mp showcase for the one kernel where an in-program
  collective pays: Miller legs shard over mp and all_gather before final
  exp. It fuses miller + product + final-exp into ONE fresh XLA program
  per (mesh, shape), which costs a multi-minute compile on small hosts
  (the historic `dryrun_multichip` rc=124) — so it is opt-in
  (`FTS_SHARDED_PAIRING_FUSED=1`), for real slices where the collective
  is worth a prepaid compile.

Degrade-not-raise: `make_mesh` clamps a non-dividing `mp`
(`sharding.clamped`) and `shard_rows` pads a non-dp-divisible batch
(`sharding.padded_rows`) instead of erroring, so odd block-group sizes
can never knock a node off the sharded path.

The reference scales by adding Fabric endorser processes; here one mesh
spans all chips of a slice via `jax.sharding.Mesh`.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import curve as cv, pairing as pr, stages as st, tower as tw
from ..ops.field import FP
from ..utils import devobs
from ..utils import metrics as mx
from ..utils.tracing import logger


def _clamp_mp(n: int, mp: int, where: str) -> int:
    """Largest divisor of n that is <= mp (>= 1). A non-dividing mp is
    CLAMPED, not rejected — counted so the observatory sees it: the
    aggregate `sharding.clamped` tick (pinned by tests/test_parallel.py),
    a per-site `sharding.clamped.<where>` counter, and a
    `sharding.clamped` flight event carrying the full decision."""
    mp = max(1, mp)
    want = mp
    while n % mp:
        mp -= 1
    if mp != want:
        mx.counter("sharding.clamped").inc()
        mx.counter(f"sharding.clamped.{where.lower()}").inc()
        mx.flight(
            "sharding.clamped", where=where, want=want, got=mp,
            n_devices=n,
        )
        logger.warning(
            "sharding: %s clamped mp %d -> %d (n_devices=%d)",
            where, want, mp, n,
        )
    return mp


@dataclass(frozen=True)
class MeshConfig:
    """Host-side mesh description for the per-shard stage-tile dispatch.

    `n_devices` is the mesh extent (dp * mp); `dp` partitions flat rows,
    `mp` partitions pairing legs. Unlike a `jax.sharding.Mesh` this never
    touches the backend — the dp/mp axes exist purely in the host
    dispatch, so a config larger than the physical device count is legal
    (it measures dispatch-level scaling on an emulated plane)."""

    n_devices: int
    dp: int
    mp: int = 1

    @property
    def workers(self) -> int:
        return self.dp * self.mp

    @classmethod
    def build(cls, n_devices: int, mp: int = 1) -> "MeshConfig":
        """Config over n_devices with mp clamped to a divisor (counted
        under `sharding.clamped` when it had to move)."""
        n = max(1, int(n_devices))
        mp = _clamp_mp(n, int(mp), "MeshConfig")
        return cls(n_devices=n, dp=n // mp, mp=mp)

    @classmethod
    def from_env(cls) -> Optional["MeshConfig"]:
        """The ambient mesh (`FTS_MESH_DEVICES` / `FTS_MESH_MP`), or None
        when no mesh is configured (planes then fall back to
        `FTS_DP_SHARDS` via `stages.default_dp`)."""
        n, mp = st.mesh_env()
        return cls(n_devices=n, dp=n // mp, mp=mp) if n > 0 else None

    @classmethod
    def of(cls, mesh) -> Optional["MeshConfig"]:
        """Coerce a Mesh / MeshConfig / None into a MeshConfig."""
        if mesh is None or isinstance(mesh, cls):
            return mesh
        dp = int(mesh.shape["dp"])
        mp = int(mesh.shape.get("mp", 1))
        return cls(n_devices=dp * mp, dp=dp, mp=mp)


def make_mesh(n_devices: Optional[int] = None, mp: int = 1) -> Mesh:
    """Mesh of shape (dp, mp) over the first n_devices devices. A
    non-dividing `mp` is clamped to the largest divisor
    (`sharding.clamped`) instead of raising."""
    devs = jax.devices()
    n = n_devices or len(devs)
    mp = _clamp_mp(n, mp, "make_mesh")
    arr = np.array(devs[:n]).reshape(n // mp, mp)
    return Mesh(arr, ("dp", "mp"))


def shard_rows(arr, mesh: Mesh):
    """Place an array with its leading (batch) axis split over dp; any
    further sharding (e.g. mp over pairing legs) is imposed by the
    consuming shard_map's in_specs. A batch that does not divide dp is
    PADDED to the next span boundary by repeating row 0
    (`sharding.padded_rows`) — callers slice their result back to the
    original row count."""
    a = np.asarray(arr)
    dp = int(mesh.shape["dp"])
    pad = (-a.shape[0]) % dp
    if pad:
        mx.counter("sharding.padded_rows").inc(pad)
        a = np.concatenate([a, np.broadcast_to(a[:1], (pad,) + a.shape[1:])])
    full = P("dp", *([None] * (a.ndim - 1)))
    return jax.device_put(jnp.asarray(a), NamedSharding(mesh, full))


def _fused_pairing_product(Ps, Qs, mesh: Mesh):
    """prod_k e(P_k, Q_k) per batch row as ONE shard_map program: dp over
    rows, mp over the K pairing legs; Miller values all_gather over mp,
    one shared final exp. Ps: (B, K, 2, L), Qs: (B, K, 2, 2, L) with
    B % dp == 0 and K % mp == 0 (the `sharded_pairing_product` wrapper
    pads/degrades)."""

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P("dp", "mp"), P("dp", "mp")),
        out_specs=P("dp"),
        check_vma=False,
    )
    def run(ps, qs):
        f = pr.miller_loop(ps, qs)  # (b_local, k_local, 6, 2, L)
        f = jax.lax.all_gather(f, "mp", axis=2, tiled=False)
        # f: (b_local, k_local, mp, 6, 2, L) -> combine all K legs locally
        k_total = f.shape[1] * f.shape[2]
        f = f.reshape(f.shape[0], k_total, 6, 2, f.shape[-1])
        while f.shape[1] > 1:
            half = f.shape[1] // 2
            rest = f[:, 2 * half :]
            f = tw.fp12_mul(f[:, :half], f[:, half : 2 * half])
            if rest.shape[1]:
                f = jnp.concatenate([f, rest], axis=1)
        return pr.final_exp(f[:, 0])

    return run(Ps, Qs)


def sharded_pairing_product(Ps, Qs, mesh, fused: Optional[bool] = None):
    """prod_k e(P_k, Q_k) per batch row, dp over rows and mp over the K
    pairing legs. Returns (B, 6, 2, L) GT as host numpy.

    Default path: the STAGED dispatch — `pairing_product_staged` with
    dp x mp worker spans over the compile-once miller/product/final-exp
    tiles (zero new XLA programs; the product planes' path). With
    `fused=True` (or `FTS_SHARDED_PAIRING_FUSED=1`) the in-program
    `shard_map` + `all_gather` collective runs instead — one fresh XLA
    compile per (mesh, shape); rows are padded to a dp boundary and a
    K not divisible by mp degrades to the staged path
    (`sharding.fallbacks`).
    """
    cfg = MeshConfig.of(mesh)
    Ps = np.asarray(Ps)
    Qs = np.asarray(Qs)
    if cfg is None:  # no mesh: staged dispatch with the ambient env dp/mp
        return pr.pairing_product_staged(Ps, Qs)
    if fused is None:
        fused = os.environ.get("FTS_SHARDED_PAIRING_FUSED", "0") == "1"
    if fused and isinstance(mesh, Mesh):
        B, K = Ps.shape[0], Ps.shape[1]
        if K % cfg.mp:
            mx.counter("sharding.fallbacks").inc()
            mx.flight(
                "sharding.fallback", what="fused_pairing",
                workers=cfg.workers, reason="k_not_divisible",
                k=K, mp=cfg.mp,
            )
            devobs.note_degrade(
                "k_not_divisible", program="fused_pairing"
            )
            logger.warning(
                "sharding: fused pairing product needs K %% mp == 0 "
                "(K=%d, mp=%d); degrading to the staged dispatch", K, cfg.mp,
            )
        else:
            # the dp-boundary padding shard_rows is about to add is the
            # fused program's occupancy story — record it on the ledger
            pad = (-B) % cfg.dp
            with devobs.dispatch(
                "fused_pairing", rows=B * K, padded_rows=pad * K,
                tiles=1, dp=cfg.dp, mp=cfg.mp,
            ) as frame:
                with frame.tile():
                    gt = _fused_pairing_product(
                        shard_rows(Ps, mesh), shard_rows(Qs, mesh), mesh
                    )
                with frame.wait():
                    gt = np.asarray(gt)
            return gt[:B]
    return pr.pairing_product_staged(Ps, Qs, dp=cfg.dp, mp=cfg.mp)


def mesh_dp(mesh) -> Optional[int]:
    """The dp extent of a Mesh or MeshConfig (None mesh -> ambient
    FTS_DP_SHARDS / FTS_MESH_* env)."""
    cfg = MeshConfig.of(mesh)
    return None if cfg is None else cfg.dp


def run_rows_dp(kernel, *arrays, mesh=None, dp: Optional[int] = None,
                consts=()):
    """Per-shard stage-tile dispatch: partition the flat rows into dp
    contiguous tile-aligned spans (`stages.tile_rows`) and run each span through the
    canonical compile-once tile executable (`stages.run_rows`). Results
    are bit-identical to the unsharded runner and NO new XLA program is
    compiled — the dp axis exists purely in the host-side dispatch."""
    return st.run_rows(
        kernel, *arrays, consts=consts,
        dp=dp if dp is not None else mesh_dp(mesh),
    )


def sharded_schnorr_rows(table: cv.FixedBaseTable, resp, stmts, chals,
                         mesh=None):
    """Batch-parallel Schnorr commitment reconstruction over dp, as
    per-shard stage-tile dispatch: com = table^resp - stmt^chal.

    The flat-row composition is EXACTLY the one `BatchedWFVerifier`
    runs (msm tile, variable-base mul tile, sub tile) — dp only
    partitions the row stream. resp: (N, nbases, L), stmts: (N, 3, L),
    chals: (N, L) canonical limbs (host numpy); returns (N, 3, L)
    Jacobian numpy."""
    dp = mesh_dp(mesh)
    fixed = run_rows_dp(st._g1_msm_tile, np.asarray(resp), dp=dp,
                        consts=(table.flat,))
    sc = run_rows_dp(st._g1_mul_tile, np.asarray(stmts), np.asarray(chals),
                     dp=dp)
    return run_rows_dp(st._g1_sub_tile, fixed, sc, dp=dp)
