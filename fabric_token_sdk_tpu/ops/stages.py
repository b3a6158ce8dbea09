"""Primitive stage kernels: the compile-once tiles of the verify plane.

The previous data plane fused each verifier's whole group-math pipeline
into one giant per-shape XLA program (`_wf_kernel` & co in
`crypto/batch.py`): a new transfer shape `(n_in, n_out)` meant a new
multi-minute compile, and the FIRST compile alone could blow the tier-1
budget. This module generalizes the staged execution model proven by
`pairing.pairing_product_staged`: a small, fixed set of **primitive stage
kernels**, each `jax.jit`'d once at a single canonical tile shape, with
all inter-stage glue (reshape / broadcast / concat / challenge repeat) in
host numpy. Verifiers become host-side compositions of these stages, so
the total distinct-program count is a small constant — independent of
batch size, transfer shape, and parameter set.

Stage inventory (`tile_rows(program)` flat rows each — one height per
program per backend, see `tile_rows`, which also holds the heights of
the staged pairing product's Miller and final-exp tiles; tables/keys
are ARGUMENTS, not baked constants, so one executable serves every
parameter set):

  G1:  msm tile (per nbases in {1,2,3}), variable-base scalar-mul tile,
       Jacobian add tile, Jacobian sub tile (add + neg fused),
       batch to-affine tile
  G2:  variable-base scalar-mul tile, Jacobian add tile,
       batch to-affine tile

The msm tiles and the two scalar-mul tiles walk their scalars in
windows (`window_bits(program)` bits a digit): the msm tiles over fixed
tables passed in, the scalar-mul tiles over a table of the row's own
point built on the device (`curve.windowed_mul`).

Program-size discipline: one inlined Jacobian point-op costs ~40s of XLA
CPU compile on a small host, so every stage keeps at most ~2 point-ops in
its traced body. In particular the msm point reduction is a `lax.scan`
with a SINGLE add per step instead of a fully unrolled log-depth tree
(~191 inlined adds for a 3-base table) — the same total point additions
at runtime, but a ~100x smaller program.

`stage_programs()` enumerates every (name, jitted fn, canonical arg
shapes) triple so `ops/warmup.py` can AOT-compile the whole set into the
persistent cache ahead of time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import curve as cv, curve2 as cv2, limbs as lb
from .field import FP
from ..utils import devobs
from ..utils import metrics as mx
from ..utils import sysmon

# Tile height: every dispatch of a stage program sees exactly
# `tile_rows(program)` flat rows (batches are flattened over (B, n) and
# padded by repeating row 0; padded outputs are discarded). ONE height
# per program name, so the shape `stage_programs()` registers (what
# `ops/warmup.py` and the benchmark compile ahead) is by construction
# the shape `run_rows` dispatches. Derived from the backend, not set:
# no environment variable, no policy field, no second shape per name.
#
# Off the chip a tile's time grows with its rows (XLA's CPU backend:
# 25-50 s per zk transaction at any height), so the small tile stays.
_HOST_TILE_ROWS = 8
# On the chip an operation on 8 rows x 32 limbs (a quarter of one vector
# register) is nearly all fixed issue cost, and at 128 rows the rows
# fill the 128 lanes. One warm dispatch on a TPU v5e, ms at 8 / 64 /
# 128 / 256 rows (sweep: PERF.md section 6, PR 26): g1_mul 71.7 / 105.4
# / 62.5 / 80.7, g2_mul 96.7 / 196.6 / 101.1 / 182.5, g1_msm3 41.9 /
# 61.5 / 36.9 / 46.6. The rule: over the stage calls of a full 64-tx
# (2,2) block (18 of the proof plane, 128-768 rows each; 3 of the sign
# plane, 192 rows) take the T that minimises
# sum(ceil(rows / T) * c_program(T)) among the T with
# c_program(T) <= 1.5 * c_program(8) for the programs that cost, so
# that a block of one tile per call (2 txs) loses at most half again.
# 256 would give 1.23 s a block against 1.49 s but g2_mul(256) = 1.89 x
# g2_mul(8); 128 is within the limit (0.87-1.05 x). One T for all ten:
# only programs worth under 50 ms of a 14 s block together have an
# optimum of their own that differs by more than 20 % of their time.
_TPU_TILE_ROWS = 128
# The Miller tile of the staged pairing product (`ops/pairing.py`,
# program `miller_tile`) has a height of its own, found the same way.
# Off the chip 16 pairs, as ever (the CPU backend pays per row: 4.7 s
# a tile of 16, 8.4 s one of 32). On the chip one warm dispatch
# (transfer in, dispatch, read-back) costs, ms at 16 / 32 / 64 / 128 /
# 256 / 512 pairs (sweep: PERF.md section 6, PR 29): 93.0 / 115.3 /
# 96.3 / 96.5 / 177.4 / 386.6, i.e. 93.0 / 57.7 / 24.1 / 12.1 / 11.1 /
# 12.1 per 16 pairs: flat up to 128, where the pairs fill the lanes
# (below 128 the chip's compiler keeps the 32 limbs in the lanes, at 32
# and 64 it mixes both layouts), linear from there. The rule: over the
# two Miller calls that cost (1,008 rows: a 64-tx (2,2) block at base
# 100 / exponent 2; 320 rows: an 8-tx block at base 300 / exponent 5)
# take the T that minimises the sum of ceil(rows / T) * c(T) among the
# T with ceil(32 / T) * c(T) <= 2 * c(16), so that the smallest device
# block (32 rows, two dispatches of 16 = 186 ms) does not get slower.
# 128: 772 + 290 ms; 256: 710 + 355 ms (and 177 ms for a block of 32);
# 512 is out (387 ms for a block of 32). Those rows were four legs a
# membership proof; since PR 39 a proof has two (`crypto/batch.py`), the
# two calls are 512 and 160 rows and the smallest block 16: by the same
# sweep 128: 386 + 193 ms, 256: 355 + 177 ms, but a 3-tx block of the
# test network's channel (60 rows) and the 2-tx block would pay 177 ms
# for 96.5, so 128 stays (reckoned from PR 29's sweep, not swept again).
_HOST_MILLER_ROWS = 16
_TPU_MILLER_ROWS = 128
# The final-exp tile of the same product (ledger frame `fexp_tile`: the
# row product `_product_rows` and `final_exp` of one dispatch) is the
# third height, found the same way. Off the chip 8 rows, as ever (the
# CPU backend takes 177 s to compile `final_exp` and seconds a tile).
# On the chip one warm dispatch (transfer in, both programs,
# read-back; 4 legs a row, 2 legs 0.6-1.9 ms less) costs, ms at 8 / 16 /
# 32 / 64 / 128 / 256 rows (sweep: PERF.md section 6, PR 35): 193.6 /
# 212.0 / 229.7 / 217.8 / 207.4 / 447.2, i.e. 193.6 / 106.0 / 57.4 /
# 27.2 / 13.0 / 14.0 per 8 rows: nearly flat up to 128 rows (worst at
# 32, where the chip's compiler mixes its two layouts), more than
# double from there; peak bytes in use 155-179 MB at every height to
# 128, 297 MB at 256; a cold compile of `final_exp` 271-296 s at any
# height. The rule: over the final-exp calls the benchmark's blocks
# make (256 rows: a 64-tx (2,2) block at base 100 / exponent 2; 80:
# an 8-tx block at base 300 / exponent 5; 30: a 3-tx block of the
# test network's channel; 8: a 2-tx block, the smallest the device
# sees) take the T <= 128 that minimises the sum of ceil(rows / T) *
# c(T) among the T with c(T) <= 1.5 * c(8), so that the smallest block
# loses at most half again. 8: 47 dispatches, 9,098 ms; 16: 5,088;
# 32: 2,986; 64: 1,742; 128: 5 dispatches, 1,037 ms, and c(128) =
# 1.07 * c(8). Not above 128: a 64-tx block must keep two dispatches
# for the benchmark's traced slice (PERF.md section 7), and 256 costs
# 2.16 * c(128).
_HOST_FEXP_ROWS = 8
_TPU_FEXP_ROWS = 128


@functools.cache
def _on_tpu() -> bool:
    # asked at first use, never at import: this starts the backend
    return jax.default_backend() == "tpu"


def tile_rows(program: str) -> int:
    """Rows one dispatch of tile program `program` (a
    `stage_programs()` name, `miller_tile` or `fexp_tile`) holds on
    this process's backend."""
    if program == "miller_tile":
        return _TPU_MILLER_ROWS if _on_tpu() else _HOST_MILLER_ROWS
    if program == "fexp_tile":
        return _TPU_FEXP_ROWS if _on_tpu() else _HOST_FEXP_ROWS
    return _TPU_TILE_ROWS if _on_tpu() else _HOST_TILE_ROWS


_WINDOW_BITS = {
    "g1_mul_tile": cv.MUL_WINDOW_BITS,
    "g2_mul_tile": cv2.MUL_WINDOW_BITS,
    **{f"g1_msm{n}_tile": cv.WINDOW_BITS for n in (1, 2, 3)},
}


def window_bits(program: str) -> int:
    """Bits a digit of the scalar window tile program `program` walks:
    the form of its arithmetic, as `tile_rows` is its shape. 0 for a
    program that walks no scalar. The same on every backend."""
    return _WINDOW_BITS.get(program, 0)


# ------------------------------------------------------------ tile kernels

@jax.jit
def _g1_msm_tile(table_flat, scalars):
    """Fixed-base windowed multiexp tile.

    table_flat: (nbases*64, 16, 3L) window table (argument, shared across
    parameter sets); scalars: (R, nbases, L) canonical limbs.
    Returns (R, 3, L) Jacobian. One program per nbases (3 total, ever).

    Digit selection is `cv.msm_select` (shared with `cv.msm_flat`); the
    point reduction is a scan with ONE add per step to keep the program
    small (see module docstring).
    """
    sel = cv.msm_select(table_flat, scalars)  # (R, T, 3, L)
    pts = jnp.moveaxis(sel, -3, 0)  # (T, R, 3, L)

    def step(acc, p):
        return cv.add(acc, p), None

    acc, _ = lax.scan(step, cv.infinity(pts.shape[1:-2]), pts)
    return acc


@jax.jit
def _g1_sub_tile(a, b):
    """a - b on (R, 3, L) Jacobian tiles (the commitment-minus-statement
    step of every sigma verification)."""
    return cv.add(a, cv.neg(b))


@jax.jit
def _g1_to_affine_tile(p):
    """(R, 3, L) Jacobian -> (R, 2, L) affine (Fermat inversion on
    device). Infinity lanes come back (0, 0) — the caller masks."""
    x, y, z = p[..., 0, :], p[..., 1, :], p[..., 2, :]
    zi = FP.inv(z)
    zi2 = FP.mul(zi, zi)
    return jnp.stack([FP.mul(x, zi2), FP.mul(FP.mul(y, zi2), zi)], axis=-2)


_g2_to_affine_tile = jax.jit(cv2.to_affine_device)


# Thin named entry points: `cv.scalar_mul` / `cv2.scalar_mul` (and the
# two `add`s) share a `__name__`, so dispatched bare both groups' tiles
# are the XLA program `jit_scalar_mul` (`jit_add`) in a device trace.
# These names match the ledger's; the arithmetic is the callee's, whose
# own jitted form (nested in other programs) keeps its name.

@jax.jit
def _g1_mul_tile(points, scalars):
    return cv.scalar_mul(points, scalars)


@jax.jit
def _g1_add_tile(a, b):
    return cv.add(a, b)


@jax.jit
def _g2_mul_tile(points, scalars):
    return cv2.scalar_mul(points, scalars)


@jax.jit
def _g2_add_tile(a, b):
    return cv2.add(a, b)


# ------------------------------------------------------------ tile runner

def _run_span(frame, kernel, consts, arrays, rows, start, stop):
    """Sequentially ENQUEUE the tile kernel over the `rows`-high slabs
    [start, stop) (JAX dispatch is asynchronous: nothing here waits for
    a result). Each tile's transfer + dispatch is one `frame.tile()`
    mark."""
    outs = []
    for t in range(start * rows, stop * rows, rows):
        with frame.tile():
            outs.append(kernel(
                *consts, *(jnp.asarray(a[t : t + rows]) for a in arrays)
            ))
    return outs


_PROGRAM_NAMES = None


def _program_of(kernel, arrays) -> str:
    """Canonical program name (the `stage_programs()` registry key) of a
    stage kernel — the join key the dispatch ledger (`utils/devobs.py`)
    and the compile listeners attribute by. The msm tile is one jitted
    fn serving three programs (disambiguated by the nbases axis of its
    scalar rows); the map is keyed by function identity, not name."""
    global _PROGRAM_NAMES
    if kernel is _g1_msm_tile:
        return f"g1_msm{arrays[0].shape[1]}_tile"
    if _PROGRAM_NAMES is None:
        names = {}
        for name, fn, _shapes in stage_programs():
            names.setdefault(id(fn), name)
        _PROGRAM_NAMES = names
    return _PROGRAM_NAMES.get(id(kernel)) or (
        getattr(kernel, "__name__", None) or type(kernel).__name__
    )


def run_rows(kernel, *arrays, consts=()):
    """Run `kernel(*consts, *tiles)` over `tile_rows(program)`-high
    slabs of flat-row numpy arrays -> numpy. The staged successor of
    the old `crypto.batch._run_tiled`.

    * `arrays` share a leading flat row axis N; rows are padded to a
      multiple of the tile height by repeating row 0 (padded outputs
      discarded).
    * `consts` are parameter tensors (window tables, public keys) passed
      whole to every tile call — arguments, not baked jit constants.
    * Tiles are CONTIGUOUS numpy views of a single padded buffer (one
      host-side copy at most, only when padding is needed); the only
      host->device transfers are the per-tile `jnp.asarray` calls,
      counted in `batch.tiled.transfers`.
    * One walk, on the calling thread: every tile is enqueued in order
      (JAX dispatch is asynchronous), then every result is read back.
      A tile lands on the default device; the per-tile `jnp.asarray`
      in `_run_span` is the one line where a row slab meets a device.
    """
    N = arrays[0].shape[0]
    if N == 0:
        raise ValueError("run_rows: empty row batch (caller must guard)")
    program = _program_of(kernel, arrays)
    rows = tile_rows(program)
    pad = (-N) % rows
    ntiles = (N + pad) // rows
    # ONE timer per dispatch: the ledger frame (utils/devobs.py), from
    # the padding until the last tile's result is back on the host. It
    # names the canonical program, splits its wall into enqueue and
    # read-back time, and is the per-kernel span a critical-path trace
    # (cmd/ftstrace.py) renders under the block's device verify.
    with devobs.dispatch(
        program, rows=N, padded_rows=pad, tiles=ntiles,
    ) as frame:
        frame.form(window_bits(program))
        if pad:
            padded = []
            for a in arrays:
                buf = np.empty((N + pad,) + a.shape[1:], dtype=a.dtype)
                buf[:N] = a
                buf[N:] = a[:1]
                padded.append(buf)
            arrays = tuple(padded)
        else:
            arrays = tuple(np.ascontiguousarray(a) for a in arrays)
        mx.counter("stages.calls").inc()
        mx.counter("stages.rows").inc(N)
        mx.counter("stages.tiles").inc(ntiles)
        mx.counter("batch.tiled.transfers").inc(ntiles * len(arrays))
        # every tile is enqueued before the first read-back below
        outs = _run_span(frame, kernel, consts, arrays, rows, 0, ntiles)
        # device/host memory high-water of the data plane (throttled;
        # never compiles anything — see utils/sysmon.py), sampled while
        # the device works on the tiles
        sysmon.sample_stages()
        nested = isinstance(outs[0], (tuple, list))
        host = []
        for o in outs:
            with frame.wait():
                host.append(
                    tuple(np.asarray(x) for x in o) if nested
                    else np.asarray(o)
                )
        if nested:
            result = tuple(
                np.concatenate([h[i] for h in host])[:N]
                for i in range(len(host[0]))
            )
        else:
            result = np.concatenate(host)[:N]
    return result


# ------------------------------------------------------------ compositions
#
# Thin named wrappers so verifier code reads as algebra. Every wrapper
# takes/returns HOST numpy (flat rows); `consts` device residency is the
# caller's choice (jnp tables stay resident, numpy is transferred).

def g1_msm_rows(table_flat, scalars: np.ndarray) -> np.ndarray:
    """(N, nbases, L) canonical scalars x fixed-base table -> (N, 3, L)."""
    return run_rows(_g1_msm_tile, scalars, consts=(table_flat,))


def g1_mul_rows(points: np.ndarray, scalars: np.ndarray) -> np.ndarray:
    """Variable-base scalar mul: (N, 3, L) x (N, L) -> (N, 3, L)."""
    return run_rows(_g1_mul_tile, points, scalars)


def g1_add_rows(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return run_rows(_g1_add_tile, a, b)


def g1_sub_rows(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return run_rows(_g1_sub_tile, a, b)


def g1_to_affine_rows(p: np.ndarray) -> np.ndarray:
    return run_rows(_g1_to_affine_tile, p)


def g2_mul_rows(points: np.ndarray, scalars: np.ndarray) -> np.ndarray:
    """(N, 3, 2, L) x (N, L) -> (N, 3, 2, L)."""
    return run_rows(_g2_mul_tile, points, scalars)


def g2_add_rows(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return run_rows(_g2_add_tile, a, b)


def g2_to_affine_rows(p: np.ndarray) -> np.ndarray:
    return run_rows(_g2_to_affine_tile, p)


def g2_tree_sum_rows(terms: np.ndarray) -> np.ndarray:
    """Per-row sum of k G2 terms: (N, k, 3, 2, L) -> (N, 3, 2, L).

    Host-side log-depth fold — each level is ONE tiled add over the
    flattened pair rows, so no per-k device program exists.
    """
    while terms.shape[1] > 1:
        k = terms.shape[1]
        half = k // 2
        rest = terms[:, 2 * half :]
        flat_a = terms[:, :half].reshape((-1,) + terms.shape[2:])
        flat_b = terms[:, half : 2 * half].reshape((-1,) + terms.shape[2:])
        summed = g2_add_rows(flat_a, flat_b).reshape(
            (terms.shape[0], half) + terms.shape[2:]
        )
        terms = np.concatenate([summed, rest], axis=1) if rest.shape[1] else summed
    return terms[:, 0]


def affine_to_jac_np(p: np.ndarray) -> np.ndarray:
    """Host glue: (..., 2, L) Montgomery affine -> (..., 3, L) Jacobian
    with Z = 1 (pure numpy — no device program)."""
    one = np.broadcast_to(
        np.asarray(FP.one_mont, dtype=np.int32), p[..., 0, :].shape
    )
    return np.concatenate([p, one[..., None, :]], axis=-2)


def jac_infinity_np(p: np.ndarray) -> np.ndarray:
    """Host glue: Jacobian rows, (N, 3, L) in G1 or (N, 3, 2, L) in G2,
    -> (N,) bool, True where Z == 0: the point at infinity, for which
    the to-affine tiles return (0, 0) and no point. An element lives in
    [0, 2p), so the limbs of 0 and of p both read zero."""
    z = p[:, 2].reshape(p.shape[0], -1, p.shape[-1])
    zero = (z == 0).all(axis=-1) | (z == FP.p_limbs).all(axis=-1)
    return zero.all(axis=-1)


# ------------------------------------------------------------ warmup hooks

def stage_programs():
    """Yield (name, jitted_fn, canonical arg shapes) for every stage
    program, for AOT precompilation (`ops/warmup.py`). int32 throughout.
    The leading axis of every row argument is `tile_rows(name)`."""
    L = lb.NLIMBS
    W = 1 << cv.WINDOW_BITS
    g1, g2, k = (3, L), (3, 2, L), (L,)
    programs = [
        (f"g1_msm{n}_tile", _g1_msm_tile, ((n, L),),
         ((n * cv.DIGITS_PER_SCALAR, W, 3 * L),))
        for n in (1, 2, 3)
    ] + [
        ("g1_mul_tile", _g1_mul_tile, (g1, k), ()),
        ("g1_add_tile", _g1_add_tile, (g1, g1), ()),
        ("g1_sub_tile", _g1_sub_tile, (g1, g1), ()),
        ("g1_to_affine_tile", _g1_to_affine_tile, (g1,), ()),
        ("g2_mul_tile", _g2_mul_tile, (g2, k), ()),
        ("g2_add_tile", _g2_add_tile, (g2, g2), ()),
        ("g2_to_affine_tile", _g2_to_affine_tile, (g2,), ()),
    ]
    for name, fn, row_args, consts in programs:
        R = tile_rows(name)
        yield name, fn, consts + tuple((R,) + a for a in row_args)
