"""Batched optimal-ate pairing on device (BN254).

The hot verification op of the framework: Pointcheval-Sanders signature /
membership-proof checks are pairing-product equations (reference
pssign/sign.go:153, sigproof/pok.go:196-203), verified here for whole
batches of proofs in one XLA program.

Design notes (TPU-first):
* G2 Miller-loop arithmetic runs on the twist in Jacobian coordinates with
  denominator-dropping line formulas — all Fp2-denominators lie in proper
  subfields and vanish under the final exponentiation, so every step is
  branch-free polynomial arithmetic on limb tensors.
* The Miller loop is a `lax.scan` over the static bits of 6u+2; the add
  step is computed every iteration and `select`ed (SIMD-friendly).
* Final exponentiation: easy part (one tower inversion), then the hard
  part via the balanced base-p / u-basis decomposition
  lambda_0 = -(36u^3+30u^2+18u+2), lambda_1 = 1-(36u^3+18u^2+12u),
  lambda_2 = 6u^2+1, lambda_3 = 1 (verified exactly at import), costing
  three u-exponentiations + small-exponent combinations + Frobenius maps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import limbs as lb, stages as st, tower as tw
from .field import FP
from ..crypto import hostmath as hm
from ..utils import devobs
from ..utils import metrics as mx

# ---------------------------------------------------------------- constants

_ATE_BITS = np.array([int(b) for b in bin(hm.ATE_LOOP)[3:]], dtype=np.int32)
# ALL bits of u MSB-first ([2:] strips only '0b'); _pow_u skips the MSB itself
_U_BITS = np.array([int(b) for b in bin(hm.U)[2:]], dtype=np.int32)

# hard-part u-basis coefficients (c0..c3) per lambda_i — verified at import
_LAMBDA_COEFFS = [
    (-2, -18, -30, -36),
    (1, -12, -18, -36),
    (1, 0, 6, 0),
    (1, 0, 0, 0),
]


def _check_lambda_decomposition() -> None:
    D = (hm.P**4 - hm.P**2 + 1) // hm.R
    total = 0
    for i, cs in enumerate(_LAMBDA_COEFFS):
        lam = sum(c * hm.U**k for k, c in enumerate(cs))
        total += lam * hm.P**i
    if total != D:
        raise AssertionError("final-exponentiation decomposition is wrong")


_check_lambda_decomposition()


@functools.lru_cache(maxsize=None)
def _twist_frob_consts():
    """(c_x1, c_y1, c_x2, c_y2): XI^((p^n-1)/3), XI^((p^n-1)/2) for n=1,2."""
    cx1 = hm.fp2_pow(hm.XI, (hm.P - 1) // 3)
    cy1 = hm.fp2_pow(hm.XI, (hm.P - 1) // 2)
    cx2 = hm.fp2_pow(hm.XI, (hm.P**2 - 1) // 3)
    cy2 = hm.fp2_pow(hm.XI, (hm.P**2 - 1) // 2)
    return tw.encode_fp2([cx1, cy1, cx2, cy2])


# ---------------------------------------------------------------- host I/O

def encode_g1(points) -> np.ndarray:
    """Host G1 affine points -> (N, 2, L) Montgomery (x, y) tensor.

    Infinity encodes as (0, 0) and must be masked by the caller.
    """
    out = np.zeros((len(points), 2, lb.NLIMBS), dtype=np.int32)
    Rm = 1 << (lb.RADIX_BITS * lb.NLIMBS)
    for i, pt in enumerate(points):
        if pt is None:
            continue
        out[i, 0] = lb.int_to_limbs(pt[0] * Rm % hm.P)
        out[i, 1] = lb.int_to_limbs(pt[1] * Rm % hm.P)
    return out


def encode_g2(points) -> np.ndarray:
    """Host G2 affine points -> (N, 2, 2, L): [x, y] as Fp2 tensors."""
    out = np.zeros((len(points), 2, 2, lb.NLIMBS), dtype=np.int32)
    for i, pt in enumerate(points):
        if pt is None:
            continue
        out[i, 0] = tw.encode_fp2([pt[0]])[0]
        out[i, 1] = tw.encode_fp2([pt[1]])[0]
    return out


def g1_infinity_mask(points) -> np.ndarray:
    return np.array([p is None for p in points])


# ---------------------------------------------------------------- miller

def _scale2(a, ka, b, kb):
    """(a * ka, b * kb) for fp2 a,b and base-field ka,kb — one FP.mul."""
    X = jnp.stack([a[..., 0, :], a[..., 1, :], b[..., 0, :], b[..., 1, :]])
    K = jnp.stack([ka, ka, kb, kb])
    v = FP.mul(X, K)
    return (
        jnp.stack([v[0], v[1]], axis=-2),
        jnp.stack([v[2], v[3]], axis=-2),
    )


def _dbl_step(T, xp, yp):
    """Jacobian doubling + denominator-free line at P=(xp, yp).

    Stacked: 4 batched multiply rounds. Returns (T2, l0, l1, l3).
    """
    X, Y, Z = T[..., 0, :, :], T[..., 1, :, :], T[..., 2, :, :]
    sq = tw.fp2_sqr(jnp.stack([X, Y, Z]))
    XX, YY, ZZ = sq[0], sq[1], sq[2]
    M = FP.add(FP.add(XX, XX), XX)  # 3X^2
    r2 = tw.fp2_mul(jnp.stack([X, ZZ, Y]), jnp.stack([YY, Z, Z]))
    XYY, ZZZ, YZ = r2[0], r2[1], r2[2]
    S = _times2(_times2(XYY))  # 4XY^2
    r3 = tw.fp2_mul(
        jnp.stack([M, YY, Y, M, M]), jnp.stack([M, YY, ZZZ, ZZ, X])
    )
    M2, YYYY, YZZZ, MZZ, MX = r3[0], r3[1], r3[2], r3[3], r3[4]
    X3 = tw.fp2_sub(M2, _times2(S))
    Y3 = tw.fp2_sub(tw.fp2_mul(M, tw.fp2_sub(S, X3)), _times8(YYYY))
    Z3 = _times2(YZ)
    # line: l0 = -2YZ^3 yp ; l1 = 3X^2 Z^2 xp ; l3 = 2Y^2 - 3X^3
    l0, l1 = _scale2(FP.neg(_times2(YZZZ)), yp, MZZ, xp)
    l3 = tw.fp2_sub(_times2(YY), MX)
    return jnp.stack([X3, Y3, Z3], axis=-3), l0, l1, l3


def _add_step(T, Q, xp, yp):
    """Mixed addition T + Q (Q affine) + line at P; denominator-free.

    Stacked: 5 batched multiply rounds.
    """
    X, Y, Z = T[..., 0, :, :], T[..., 1, :, :], T[..., 2, :, :]
    x2, y2 = Q[..., 0, :, :], Q[..., 1, :, :]
    ZZ = tw.fp2_sqr(Z)
    r2 = tw.fp2_mul(jnp.stack([x2, ZZ]), jnp.stack([ZZ, Z]))
    U2, ZZZ = r2[0], r2[1]
    H = tw.fp2_sub(U2, X)
    r3 = tw.fp2_mul(jnp.stack([y2, H, Z]), jnp.stack([ZZZ, H, H]))
    S2, HH, Z3 = r3[0], r3[1], r3[2]
    r = tw.fp2_sub(S2, Y)
    r4 = tw.fp2_mul(jnp.stack([H, X, r, r]), jnp.stack([HH, HH, r, x2]))
    HHH, V, rr, rx2 = r4[0], r4[1], r4[2], r4[3]
    X3 = tw.fp2_sub(tw.fp2_sub(rr, HHH), _times2(V))
    r5 = tw.fp2_mul(
        jnp.stack([r, Y, Z3]), jnp.stack([tw.fp2_sub(V, X3), HHH, y2])
    )
    Y3 = tw.fp2_sub(r5[0], r5[1])
    l3 = tw.fp2_sub(r5[2], rx2)
    l0, l1 = _scale2(FP.neg(Z3), yp, r, xp)
    return jnp.stack([X3, Y3, Z3], axis=-3), l0, l1, l3


def _times2(x):
    return FP.add(x, x)


def _times8(x):
    return _times2(_times2(_times2(x)))


@jax.jit
def miller_loop(P, Q):
    """Batched Miller loop: P (..., 2, L) G1 affine, Q (..., 2, 2, L) G2
    affine -> f (..., 6, 2, L). Infinity handling is the caller's job."""
    xp, yp = P[..., 0, :], P[..., 1, :]
    batch = P.shape[:-2]
    T0 = jnp.concatenate(
        [Q, jnp.broadcast_to(tw.fp2_ones(batch)[..., None, :, :], Q[..., :1, :, :].shape)],
        axis=-3,
    ).astype(jnp.int32)
    f0 = tw.fp12_ones(batch).astype(jnp.int32)

    def step(carry, bit):
        f, T = carry
        f = tw.fp12_sqr(f)
        T2, l0, l1, l3 = _dbl_step(T, xp, yp)
        f = tw.fp12_mul_sparse013(f, l0, l1, l3)
        Ta, a0, a1, a3 = _add_step(T2, Q, xp, yp)
        fa = tw.fp12_mul_sparse013(f, a0, a1, a3)
        take = bit > 0
        f = jnp.where(take, fa, f)
        T = jnp.where(take, Ta, T2)
        return (f, T), None

    (f, T), _ = lax.scan(step, (f0, T0), jnp.asarray(_ATE_BITS))

    # frobenius corrections: Q1 = pi(Q), Q2n = -pi^2(Q)
    consts = jnp.asarray(_twist_frob_consts())
    cx1, cy1, cx2, cy2 = consts[0], consts[1], consts[2], consts[3]
    Qx, Qy = Q[..., 0, :, :], Q[..., 1, :, :]
    Q1 = jnp.stack(
        [tw.fp2_mul(tw.fp2_conj(Qx), cx1), tw.fp2_mul(tw.fp2_conj(Qy), cy1)],
        axis=-3,
    )
    Q2n = jnp.stack(
        [tw.fp2_mul(Qx, cx2), FP.neg(tw.fp2_mul(Qy, cy2))], axis=-3
    )
    T, l0, l1, l3 = _add_step(T, Q1, xp, yp)
    f = tw.fp12_mul_sparse013(f, l0, l1, l3)
    _, l0, l1, l3 = _add_step(T, Q2n, xp, yp)
    f = tw.fp12_mul_sparse013(f, l0, l1, l3)
    return f


# ---------------------------------------------------------------- final exp

def _pow_u(f):
    """f^u via scan over the fixed bits of u (cyclotomic input assumed)."""

    def step(acc, bit):
        acc = tw.fp12_sqr(acc)
        acc = jnp.where(bit > 0, tw.fp12_mul(acc, f), acc)
        return acc, None

    out, _ = lax.scan(step, f, jnp.asarray(_U_BITS[1:]))
    return out


# Straus tables for the hard part: bit matrix (nbits, 4 outputs, 4 bases)
# of |c_ik| MSB-first, and the sign matrix (4, 4).
_HP_NBITS = max(abs(c).bit_length() for cs in _LAMBDA_COEFFS for c in cs)
_HP_BITS = np.zeros((_HP_NBITS, 4, 4), dtype=np.int32)
_HP_SIGN = np.zeros((4, 4), dtype=np.int32)
for _i, _cs in enumerate(_LAMBDA_COEFFS):
    for _k, _c in enumerate(_cs):
        _HP_SIGN[_i, _k] = -1 if _c < 0 else 1
        for _b in range(_HP_NBITS):
            _HP_BITS[_HP_NBITS - 1 - _b, _i, _k] = (abs(_c) >> _b) & 1


@jax.jit
def final_exp(f):
    """f^((p^12-1)/r), batched.

    Hard part: one Straus simultaneous exponentiation over the 4x4
    coefficient matrix — a 6-step scan with a single stacked multiply per
    base — keeping the number of inlined fp12-op instances tiny.
    """
    # easy part: f^(p^6-1) then ^(p^2+1)
    t = tw.fp12_mul(tw.fp12_conj(f), tw.fp12_inv(f))
    t = tw.fp12_mul(tw.fp12_frobenius(t, 2), t)
    # u-power ladder
    fu = _pow_u(t)
    fu2 = _pow_u(fu)
    fu3 = _pow_u(fu2)
    powers = jnp.stack([t, fu, fu2, fu3])  # (4, ..., 6, 2, L)
    conj_p = tw.fp12_conj(powers)
    # sign-adjusted bases per (output, base): (4out, 4base, ..., 6, 2, L)
    sign = jnp.asarray(_HP_SIGN)
    # (4out, 4base, 1...) vs (1, 4base, *batch, 6, 2, L)
    bases = jnp.where(
        (sign > 0)[(...,) + (None,) * (powers.ndim - 1)],
        powers[None],
        conj_p[None],
    )
    batch = f.shape[:-3]
    acc = jnp.broadcast_to(
        tw.fp12_ones(), (4,) + batch + (6, 2, lb.NLIMBS)
    ).astype(jnp.int32)

    def step(acc, bits):  # bits: (4, 4)
        acc = tw.fp12_sqr(acc)
        for k in range(4):
            mult = tw.fp12_mul(acc, bases[:, k])
            take = bits[:, k][(...,) + (None,) * (acc.ndim - 1)] > 0
            acc = jnp.where(take, mult, acc)
        return acc, None

    acc, _ = lax.scan(step, acc, jnp.asarray(_HP_BITS))
    # combine with Frobenius powers: prod_i frob^i(acc[i])
    r01 = tw.fp12_mul(acc[0], tw.fp12_frobenius(acc[1], 1))
    r23 = tw.fp12_mul(
        tw.fp12_frobenius(acc[2], 2), tw.fp12_frobenius(acc[3], 3)
    )
    return tw.fp12_mul(r01, r23)


def gt_is_one(e):
    return tw.fp12_is_one(e)


_GT_ONE = ((1, 0), (0, 0), (0, 0), (0, 0), (0, 0), (0, 0))


def gt_is_one_host(arr) -> np.ndarray:
    """Host-side GT == 1 test on a (B, 6, 2, L) numpy tensor.

    Pure host decode + compare: verifiers use this instead of the device
    `gt_is_one` so the check compiles no per-batch-shape program."""
    return np.array([v == _GT_ONE for v in tw.decode_fp12(arr)], dtype=bool)


# ------------------------------------------------- staged tiled execution
#
# The pairing product runs as shape-stable tile programs compiled once
# and shared by every verifier and batch size:
#   * miller tile  — (tile_rows("miller_tile"), ...) pairs (1 program, ever)
#   * row product  — (tile_rows("fexp_tile"), K, ...) tree fp12 mul
#                                                        (tiny, per K)
#   * final-exp    — (tile_rows("fexp_tile"), ...) GT rows
#                                                        (1 program, ever)
# Tiles pad with generator pairs / GT ones; padding is masked out before
# the product so results are exact.
#
# Both heights are the backend's, as the stage tiles' is
# (`stages.tile_rows`; the sweeps and the rules stand beside the numbers
# in `ops/stages.py`): 16 pairs and 8 GT rows off the chip; on a TPU
# 128 of each, where a warm Miller dispatch of 128 costs 96.5 ms
# against 93.0 ms for 16 and a warm product + final-exp dispatch of
# 128 rows 207.4 ms against 193.6 ms for 8.
# Everything here that needs a height asks that function when it runs:
# the two walks, the padding and tile counts, the ledger frames and
# `ops/warmup.py`'s shapes. One shape per program name per backend.


@functools.lru_cache(maxsize=None)
def _pad_pair_consts():
    return (
        encode_g1([hm.G1_GEN])[0],
        encode_g2([hm.G2_GEN])[0],
    )


@jax.jit
def _product_rows(f):
    """(B, K, 6, 2, L) -> (B, 6, 2, L): per-row product of K GT legs."""
    while f.shape[1] > 1:
        half = f.shape[1] // 2
        rest = f[:, 2 * half :]
        f = tw.fp12_mul(f[:, :half], f[:, half : 2 * half])
        if rest.shape[1]:
            f = jnp.concatenate([f, rest], axis=1)
    return f[:, 0]


def _miller_tiles(frame, Pf, Qf, start: int, stop: int):
    """Sequential miller-tile walk over [start, stop) tile indices: each
    tile is enqueued (`frame.tile()`) and then read back
    (`frame.wait()`), one round trip at a time."""
    outs = []
    T = st.tile_rows("miller_tile")
    for t in range(start * T, stop * T, T):
        with frame.tile():
            f = miller_loop(
                jnp.asarray(Pf[t : t + T]), jnp.asarray(Qf[t : t + T])
            )
        with frame.wait():
            outs.append(np.asarray(f))
    return outs


def _fexp_tiles(frame, f, start: int, stop: int):
    """Sequential product+final-exp walk over [start, stop) tile indices
    (enqueue, then read back, per tile — as `_miller_tiles`)."""
    outs = []
    T = st.tile_rows("fexp_tile")
    for t in range(start * T, stop * T, T):
        with frame.tile():
            gt = final_exp(_product_rows(jnp.asarray(f[t : t + T])))
        with frame.wait():
            outs.append(np.asarray(gt))
    return outs


def pairing_product_staged(Ps, Qs, inf_mask=None):
    """prod_k e(P_k, Q_k) per row via the compile-once tile programs.

    Ps: (B, K, 2, L), Qs: (B, K, 2, 2, L) Montgomery affine; inf_mask
    (B, K) True legs contribute the identity. Returns (B, 6, 2, L) GT as
    a host numpy array.

    Both tile walks run in order on the calling thread, one round trip
    (enqueue, read back) per tile.
    """
    Ps = np.asarray(Ps)
    Qs = np.asarray(Qs)
    B, K = Ps.shape[0], Ps.shape[1]
    L = Ps.shape[-1]
    if B == 0:
        return np.zeros((0, 6, 2, L), dtype=np.int32)
    N = B * K
    Pf = Ps.reshape(N, 2, L)
    Qf = Qs.reshape(N, 2, 2, L)
    mask = np.zeros(N, dtype=bool)
    if inf_mask is not None:
        mask |= np.asarray(inf_mask).reshape(N)
    T = st.tile_rows("miller_tile")
    pad = (-N) % T
    n_miller = (N + pad) // T
    if pad:
        Pg, Qg = _pad_pair_consts()
        Pf = np.concatenate([Pf, np.broadcast_to(Pg, (pad, 2, L))])
        Qf = np.concatenate([Qf, np.broadcast_to(Qg, (pad, 2, 2, L))])
        mask = np.concatenate([mask, np.ones(pad, dtype=bool)])
    mx.counter("pairing.staged.calls").inc()
    mx.counter("pairing.staged.rows").inc(B)
    mx.counter("pairing.staged.legs").inc(N)
    mx.counter("pairing.staged.miller_tiles").inc(n_miller)
    # the two ledger frames below (utils/devobs.py) are the only timers
    # of the tile walks; `pairing.product_staged` spans the whole call
    with mx.span("pairing.product_staged", rows=B, legs_per_row=K):
        # all inter-stage glue (concat/mask/reshape/pad) stays in numpy so
        # the ONLY device programs are the three tile kernels — no
        # per-shape concatenate/select programs on the accelerator
        with devobs.dispatch(
            "miller_tile", rows=N, padded_rows=pad, tiles=n_miller,
        ) as frame:
            f = np.concatenate(
                _miller_tiles(frame, Pf, Qf, 0, n_miller), axis=0
            )
        # numpy constant (not tw.fp12_ones()): keeps the mask/pad glue off
        # the device so no per-shape broadcast program ever compiles
        one_np = tw.fp12_one_np()
        f[mask] = one_np
        f = f[:N].reshape(B, K, 6, 2, L)
        # pad rows BEFORE the product so both the per-K product program and
        # the final-exp program see only (tile_rows("fexp_tile"), ...)
        # shapes
        TF = st.tile_rows("fexp_tile")
        padB = (-B) % TF
        if padB:
            f = np.concatenate(
                [f, np.broadcast_to(one_np, (padB, K, 6, 2, L))], axis=0
            )
        n_fexp = (B + padB) // TF
        mx.counter("pairing.staged.fexp_tiles").inc(n_fexp)
        with devobs.dispatch(
            "fexp_tile", rows=B, padded_rows=padB, tiles=n_fexp
        ) as frame:
            gts = _fexp_tiles(frame, f, 0, n_fexp)
    return np.concatenate(gts, axis=0)[:B]


def decode_gt(arr):
    """Device GT tensor -> host flat fp12 tuples (hostmath layout)."""
    return tw.decode_fp12(arr if arr.ndim == 4 else arr[None])
