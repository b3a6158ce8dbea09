"""Batched extension-field towers on limb tensors (device).

Layouts (leading axes = batch):
  Fp2  : (..., 2, L)       c0 + c1*i,          i^2 = -1
  Fp12 : (..., 6, 2, L)    flat w-basis, w^6 = XI = 9 + i
The tower view Fp12 = Fp6[w]/(w^2 - v), Fp6 = Fp2[v]/(v^3 - XI) is
recovered by index parity: c0 = x[..., 0::2], c1 = x[..., 1::2]
(matching crypto.hostmath's flat representation exactly).

TPU-first structure: every composite op STACKS its independent base-field
multiplications into one batched Montgomery multiply (one limb-convolution
matmul round instead of dozens of small ones). An Fp12 multiply costs a
single FP.mul call on a 54x-wider batch — this keeps XLA graphs small and
feeds the MXU large uniform contractions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import limbs as lb
from .field import FP
from ..crypto import hostmath as hm


# ---------------------------------------------------------------- Fp2

def fp2_add(x, y):
    return FP.add(x, y)


def fp2_sub(x, y):
    return FP.sub(x, y)


def fp2_neg(x):
    return FP.neg(x)


@jax.jit
def fp2_conj(x):
    return jnp.stack([x[..., 0, :], FP.neg(x[..., 1, :])], axis=-2)


def _mul_components(x, y):
    """Karatsuba component products for a batch of fp2 pairs:
    returns (a0*b0, a1*b1, (a0+a1)*(b0+b1)) via ONE stacked FP.mul."""
    x, y = jnp.broadcast_arrays(x, y)
    a0, a1 = x[..., 0, :], x[..., 1, :]
    b0, b1 = y[..., 0, :], y[..., 1, :]
    X = jnp.stack([a0, a1, FP.add(a0, a1)])
    Y = jnp.stack([b0, b1, FP.add(b0, b1)])
    v = FP.mul(X, Y)
    return v[0], v[1], v[2]


@jax.jit
def fp2_mul(x, y):
    v0, v1, v01 = _mul_components(x, y)
    return jnp.stack([FP.sub(v0, v1), FP.sub(v01, FP.add(v0, v1))], axis=-2)


@jax.jit
def fp2_sqr(x):
    a0, a1 = x[..., 0, :], x[..., 1, :]
    X = jnp.stack([FP.add(a0, a1), a0])
    Y = jnp.stack([FP.sub(a0, a1), a1])
    v = FP.mul(X, Y)
    return jnp.stack([v[0], FP.add(v[1], v[1])], axis=-2)


@jax.jit
def fp2_scale(x, k):
    """Multiply both components by a base-field element (broadcast)."""
    X = jnp.stack([x[..., 0, :], x[..., 1, :]])
    K = jnp.stack([k, k])
    v = FP.mul(X, K)
    return jnp.stack([v[0], v[1]], axis=-2)


@jax.jit
def fp2_mul_xi(x):
    """Multiply by XI = 9 + i: (9 a0 - a1) + (a0 + 9 a1) i. Add-only."""
    a0, a1 = x[..., 0, :], x[..., 1, :]
    t0 = a0
    for _ in range(3):
        t0 = FP.add(t0, t0)
    nine_a0 = FP.add(t0, a0)
    t1 = a1
    for _ in range(3):
        t1 = FP.add(t1, t1)
    nine_a1 = FP.add(t1, a1)
    return jnp.stack([FP.sub(nine_a0, a1), FP.add(a0, nine_a1)], axis=-2)


@jax.jit
def fp2_inv(x):
    """(a - bi) / (a^2 + b^2): one base-field inversion."""
    a0, a1 = x[..., 0, :], x[..., 1, :]
    sq = FP.mul(jnp.stack([a0, a1]), jnp.stack([a0, a1]))
    n = FP.inv(FP.add(sq[0], sq[1]))
    v = FP.mul(jnp.stack([a0, a1]), jnp.stack([n, n]))
    return jnp.stack([v[0], FP.neg(v[1])], axis=-2)


def fp2_is_zero(x):
    return FP.is_zero(x[..., 0, :]) & FP.is_zero(x[..., 1, :])


def fp2_eq(x, y):
    return FP.eq(x[..., 0, :], y[..., 0, :]) & FP.eq(x[..., 1, :], y[..., 1, :])


def fp2_zeros(shape=()):
    return FP.zeros(tuple(shape) + (2,))


def _fp2_one_np() -> np.ndarray:
    out = np.zeros((2, lb.NLIMBS), dtype=np.int32)
    out[0] = np.asarray(FP.one_mont)
    return out


def fp2_ones(shape=()):
    return jnp.broadcast_to(
        jnp.asarray(_fp2_one_np()), tuple(shape) + (2, lb.NLIMBS)
    ).astype(jnp.int32)


# ------------------------------------------------------- host conversions

def encode_fp2(vals) -> np.ndarray:
    """Host fp2 tuples [(a,b), ...] -> (N, 2, L) Montgomery tensor.
    Pure numpy: safe to call during tracing (constants fold)."""
    Rm = 1 << (lb.RADIX_BITS * lb.NLIMBS)
    out = np.zeros((len(vals), 2, lb.NLIMBS), dtype=np.int32)
    for i, (a, b) in enumerate(vals):
        out[i, 0] = lb.int_to_limbs(a * Rm % hm.P)
        out[i, 1] = lb.int_to_limbs(b * Rm % hm.P)
    return out


_RINV = pow(1 << (lb.RADIX_BITS * lb.NLIMBS), -1, hm.P)


def decode_fp2(arr):
    """Montgomery limb tensor -> host fp2 int tuples.

    Pure host arithmetic (limb recomposition + one modular multiply by
    R^-1): decoding compiles no device program, so batched verifiers stay
    shape-invariant in their XLA program set."""
    a = np.asarray(arr).reshape(-1, lb.NLIMBS)
    flat = [v * _RINV % hm.P for v in lb.batch_limbs_to_ints(a)]
    return list(zip(flat[0::2], flat[1::2]))


def encode_fp12(vals) -> np.ndarray:
    """Host flat fp12 tuples (6 x fp2) -> (N, 6, 2, L)."""
    return np.stack([encode_fp2(list(v)) for v in vals])


def decode_fp12(arr):
    a = np.asarray(arr)
    pairs = decode_fp2(a.reshape(-1, 2, lb.NLIMBS))
    return [tuple(pairs[6 * i : 6 * i + 6]) for i in range(len(pairs) // 6)]


# ---------------------------------------------------------------- Fp6
# (..., 3, 2, L): a0 + a1 v + a2 v^2. All six Karatsuba cross-products are
# evaluated in ONE stacked fp2_mul.

def _fp6_mul(a, b):
    a0, a1, a2 = a[..., 0, :, :], a[..., 1, :, :], a[..., 2, :, :]
    b0, b1, b2 = b[..., 0, :, :], b[..., 1, :, :], b[..., 2, :, :]
    X = jnp.stack([a0, a1, a2, FP.add(a1, a2), FP.add(a0, a1), FP.add(a0, a2)])
    Y = jnp.stack([b0, b1, b2, FP.add(b1, b2), FP.add(b0, b1), FP.add(b0, b2)])
    t = fp2_mul(X, Y)
    t0, t1, t2, t12, t01, t02 = (t[i] for i in range(6))
    c0 = fp2_add(t0, fp2_mul_xi(fp2_sub(t12, fp2_add(t1, t2))))
    c1 = fp2_add(fp2_sub(t01, fp2_add(t0, t1)), fp2_mul_xi(t2))
    c2 = fp2_add(fp2_sub(t02, fp2_add(t0, t2)), t1)
    return jnp.stack([c0, c1, c2], axis=-3)


def _fp6_mul_v(a):
    a0, a1, a2 = a[..., 0, :, :], a[..., 1, :, :], a[..., 2, :, :]
    return jnp.stack([fp2_mul_xi(a2), a0, a1], axis=-3)


def _fp6_neg(a):
    return FP.neg(a)


def _fp6_sub(a, b):
    return FP.sub(a, b)


def _fp6_inv(a):
    a0, a1, a2 = a[..., 0, :, :], a[..., 1, :, :], a[..., 2, :, :]
    s = fp2_mul(
        jnp.stack([a0, a2, a1, a1, a0, a0]),
        jnp.stack([a0, a2, a1, a2, a1, a2]),
    )
    a0a0, a2a2, a1a1, a1a2, a0a1, a0a2 = (s[i] for i in range(6))
    c0 = fp2_sub(a0a0, fp2_mul_xi(a1a2))
    c1 = fp2_sub(fp2_mul_xi(a2a2), a0a1)
    c2 = fp2_sub(a1a1, a0a2)
    u = fp2_mul(jnp.stack([a2, a1, a0]), jnp.stack([c1, c2, c0]))
    t = fp2_add(fp2_mul_xi(fp2_add(u[0], u[1])), u[2])
    tinv = fp2_inv(t)
    r = fp2_mul(
        jnp.stack([c0, c1, c2]),
        jnp.stack([tinv, tinv, tinv]),
    )
    return jnp.stack([r[0], r[1], r[2]], axis=-3)


# ---------------------------------------------------------------- Fp12

def _split(x):
    return x[..., 0::2, :, :], x[..., 1::2, :, :]


def _join(c0, c1):
    n = c0.shape[:-3]
    out = jnp.stack([c0, c1], axis=-3)
    return out.reshape(n + (6, 2, lb.NLIMBS))


@jax.jit
def fp12_mul(x, y):
    """One stacked _fp6_mul (3 products) = one FP.mul on a 54x batch."""
    x0, x1 = _split(x)
    y0, y1 = _split(y)
    A = jnp.stack([x0, x1, FP.add(x0, x1)])
    B = jnp.stack([y0, y1, FP.add(y0, y1)])
    V = _fp6_mul(A, B)
    v0, v1, v01 = V[0], V[1], V[2]
    c0 = FP.add(v0, _fp6_mul_v(v1))
    c1 = _fp6_sub(v01, FP.add(v0, v1))
    return _join(c0, c1)


@jax.jit
def fp12_sqr(x):
    x0, x1 = _split(x)
    A = jnp.stack([x0, FP.add(x0, x1)])
    B = jnp.stack([x1, FP.add(x0, _fp6_mul_v(x1))])
    V = _fp6_mul(A, B)
    v, t0 = V[0], V[1]
    c0 = _fp6_sub(_fp6_sub(t0, v), _fp6_mul_v(v))
    c1 = FP.add(v, v)
    return _join(c0, c1)


@jax.jit
def fp12_conj(x):
    sign = np.array([1, -1, 1, -1, 1, -1])
    return jnp.where((sign > 0)[:, None, None], x, FP.neg(x))


@jax.jit
def fp12_inv(x):
    x0, x1 = _split(x)
    S = _fp6_mul(jnp.stack([x0, x1]), jnp.stack([x0, x1]))
    n = _fp6_sub(S[0], _fp6_mul_v(S[1]))
    ninv = _fp6_inv(n)
    R = _fp6_mul(jnp.stack([x0, x1]), jnp.stack([ninv, ninv]))
    return _join(R[0], _fp6_neg(R[1]))


def fp12_one_np() -> np.ndarray:
    """The GT/Fp12 identity as a HOST numpy constant (Montgomery limbs) —
    for numpy glue paths that must not touch the device."""
    out = np.zeros((6, 2, lb.NLIMBS), dtype=np.int32)
    out[0, 0] = np.asarray(FP.one_mont)
    return out


_fp12_one_np = fp12_one_np  # internal alias (fp12_ones below)


def fp12_ones(shape=()):
    return jnp.broadcast_to(
        jnp.asarray(_fp12_one_np()), tuple(shape) + (6, 2, lb.NLIMBS)
    ).astype(jnp.int32)


@jax.jit
def fp12_eq(x, y):
    """Equality in the redundant [0, 2p) coefficient domain: canonicalize
    every coefficient before comparing (v and v+p must test equal)."""
    return jnp.all(FP.cond_sub_p(x) == FP.cond_sub_p(y), axis=(-1, -2, -3))


@jax.jit
def fp12_is_one(x):
    return fp12_eq(x, jnp.broadcast_to(fp12_ones(), x.shape).astype(jnp.int32))


# ---------------------------------------------------------------- frobenius

@functools.lru_cache(maxsize=None)
def _frob_gammas(n: int) -> np.ndarray:
    gs = [hm.fp2_pow(hm.XI, j * (hm.P**n - 1) // 6) for j in range(6)]
    return encode_fp2(gs)


@functools.partial(jax.jit, static_argnums=1)
def fp12_frobenius(x, n: int = 1):
    gam = jnp.asarray(_frob_gammas(n))
    c = x if n % 2 == 0 else jnp.concatenate(
        [x[..., :, 0:1, :], FP.neg(x[..., :, 1:2, :])], axis=-2
    )
    return fp2_mul(c, gam)


# ---------------------------------------------------------------- sparse mul

@jax.jit
def fp12_mul_sparse013(f, l0, l1, l3):
    """f * (l0 + l1 w + l3 w^3), l* in Fp2 — all 18 products stacked."""
    rows = [f[..., j, :, :] for j in range(6)]
    X = jnp.stack(
        [rows[j] for j in range(6)]
        + [rows[(j - 1) % 6] for j in range(6)]
        + [rows[(j - 3) % 6] for j in range(6)]
    )
    shape = X.shape[1:]
    Y = jnp.stack(
        [jnp.broadcast_to(l0, shape)] * 6
        + [jnp.broadcast_to(l1, shape)] * 6
        + [jnp.broadcast_to(l3, shape)] * 6
    )
    prod = fp2_mul(X, Y)
    out = []
    for j in range(6):
        t = prod[j]
        u = prod[6 + j]
        if j - 1 < 0:
            u = fp2_mul_xi(u)
        t = fp2_add(t, u)
        u = prod[12 + j]
        if j - 3 < 0:
            u = fp2_mul_xi(u)
        t = fp2_add(t, u)
        out.append(t)
    return jnp.stack(out, axis=-3)
