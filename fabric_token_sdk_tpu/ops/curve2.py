"""Batched BN254 G2 (twist) group ops on limb tensors.

Mirror of `curve.py` with coordinates in Fp2: Jacobian (X, Y, Z), shape
(..., 3, 2, L), Z == 0 encoding infinity. Needed on device for the
pairing-side of batched Pointcheval-Sanders / membership verification
(the verifier computes sum PK_i^{z_i} in G2 per proof).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import curve as cv, limbs as lb, tower as tw
from .field import FP
from ..crypto import hostmath as hm


def infinity(shape=()) -> jnp.ndarray:
    return jnp.zeros(tuple(shape) + (3, 2, lb.NLIMBS), dtype=jnp.int32)


def is_infinity(p):
    return tw.fp2_is_zero(p[..., 2, :, :])


def neg(p):
    return jnp.stack(
        [p[..., 0, :, :], tw.fp2_neg(p[..., 1, :, :]), p[..., 2, :, :]],
        axis=-3,
    )


@jax.jit
def double(p):
    """dbl-2009-l (a=0) over Fp2, stacked into 4 multiply rounds."""
    x, y, z = p[..., 0, :, :], p[..., 1, :, :], p[..., 2, :, :]
    sq = tw.fp2_sqr(jnp.stack([x, y]))
    a, b = sq[0], sq[1]
    r2 = tw.fp2_sqr(jnp.stack([b, FP.add(x, b)]))
    c, t = r2[0], r2[1]
    d = FP.sub(t, FP.add(a, c))
    d = FP.add(d, d)
    e = FP.add(FP.add(a, a), a)
    r3 = tw.fp2_mul(jnp.stack([e, y]), jnp.stack([e, z]))
    f, yz = r3[0], r3[1]
    x3 = FP.sub(f, FP.add(d, d))
    c8 = FP.add(c, c)
    c8 = FP.add(c8, c8)
    c8 = FP.add(c8, c8)
    y3 = FP.sub(tw.fp2_mul(e, FP.sub(d, x3)), c8)
    z3 = FP.add(yz, yz)
    return jnp.stack([x3, y3, z3], axis=-3)


@jax.jit
def add(p, q):
    """General Jacobian addition with select-based edge handling."""
    x1, y1, z1 = p[..., 0, :, :], p[..., 1, :, :], p[..., 2, :, :]
    x2, y2, z2 = q[..., 0, :, :], q[..., 1, :, :], q[..., 2, :, :]
    sq = tw.fp2_sqr(jnp.stack([z1, z2]))
    z1z1, z2z2 = sq[0], sq[1]
    r1 = tw.fp2_mul(
        jnp.stack([x1, x2, y1, y2]),
        jnp.stack([z2z2, z1z1, z2, z1]),
    )
    u1, u2, s1p, s2p = r1[0], r1[1], r1[2], r1[3]
    r2 = tw.fp2_mul(jnp.stack([s1p, s2p]), jnp.stack([z2z2, z1z1]))
    s1, s2 = r2[0], r2[1]
    h = FP.sub(u2, u1)
    rr = FP.sub(s2, s1)
    rr = FP.add(rr, rr)
    i = tw.fp2_sqr(FP.add(h, h))
    r3 = tw.fp2_mul(jnp.stack([h, u1]), jnp.stack([i, i]))
    j, v = r3[0], r3[1]
    x3 = FP.sub(tw.fp2_sqr(rr), FP.add(j, FP.add(v, v)))
    zsum = FP.sub(tw.fp2_sqr(FP.add(z1, z2)), FP.add(z1z1, z2z2))
    r4 = tw.fp2_mul(
        jnp.stack([rr, s1, zsum]),
        jnp.stack([FP.sub(v, x3), j, h]),
    )
    s1j = r4[1]
    y3 = FP.sub(r4[0], FP.add(s1j, s1j))
    z3 = r4[2]
    out = jnp.stack([x3, y3, z3], axis=-3)

    same_x = tw.fp2_is_zero(h)
    same_y = tw.fp2_is_zero(rr)
    inf1 = tw.fp2_is_zero(z1)
    inf2 = tw.fp2_is_zero(z2)
    sel = lambda m: m[..., None, None, None]
    out = jnp.where(sel(same_x & same_y & ~inf1 & ~inf2), double(p), out)
    out = jnp.where(sel(same_x & ~same_y & ~inf1 & ~inf2), jnp.zeros_like(out), out)
    out = jnp.where(sel(inf1), q, out)
    out = jnp.where(sel(inf2), p, out)
    return out


# Bits a digit of `scalar_mul`'s window, chosen as in G1
# (`curve.MUL_WINDOW_BITS`) by the same sweep: one warm dispatch of 128
# rows on a TPU v5e, ms at 2 / 3 / 4 / 5 bits, 65.4 / 53.7 / 49.6 /
# 53.9 (bit-serial 98.9).
MUL_WINDOW_BITS = 4


@jax.jit
def scalar_mul(p, k_canon):
    """(..., 3, 2, L) x (..., L) canonical scalars -> k * P, by the
    fixed-window walk both groups share (`curve.windowed_mul`)."""
    return cv.windowed_mul(p, k_canon, MUL_WINDOW_BITS, add, double, infinity)


def tree_sum(points, axis: int = -4):
    points = jnp.moveaxis(points, axis, 0)
    n = points.shape[0]
    while n > 1:
        half = n // 2
        odd = points[2 * half :]
        points = add(points[:half], points[half : 2 * half])
        if odd.shape[0]:
            points = jnp.concatenate([points, odd], axis=0)
        n = points.shape[0]
    return points[0]


# ---------------------------------------------------------------- host I/O

def encode_points(pts) -> np.ndarray:
    """Host G2 affine (fp2 pairs) or None -> (N, 3, 2, L) Montgomery Jac."""
    out = np.zeros((len(pts), 3, 2, lb.NLIMBS), dtype=np.int32)
    for i, pt in enumerate(pts):
        if pt is None:
            continue
        out[i, 0] = tw.encode_fp2([pt[0]])[0]
        out[i, 1] = tw.encode_fp2([pt[1]])[0]
        out[i, 2] = tw.encode_fp2([(1, 0)])[0]
    return out


def decode_points(arr):
    """Device (..., 3, 2, L) -> host affine fp2 pairs.

    The G1 decode's form over Fp2: z^-1 = conj(z) / norm(z), the norms of
    the whole batch inverted in one ``cv.batch_fp_inv``."""
    coords = tw.decode_fp2(arr)
    zs = coords[2::3]
    ninvs = cv.batch_fp_inv([(a * a + b * b) % hm.P for a, b in zs])
    out = []
    for x, y, z, n in zip(coords[0::3], coords[1::3], zs, ninvs):
        if not n:
            out.append(None)
            continue
        zinv = hm.fp2_scale(hm.fp2_conj(z), n)
        zi2 = hm.fp2_sqr(zinv)
        out.append(
            (hm.fp2_mul(x, zi2), hm.fp2_mul(hm.fp2_mul(y, zi2), zinv))
        )
    return out


def to_affine_device(p):
    """Jacobian -> affine (..., 2, 2, L) on device (uses field inversion).

    Infinity lanes come back as (0, 0) — mask separately.
    """
    x, y, z = p[..., 0, :, :], p[..., 1, :, :], p[..., 2, :, :]
    zi = tw.fp2_inv(z)
    zi2 = tw.fp2_sqr(zi)
    r = tw.fp2_mul(jnp.stack([x, tw.fp2_mul(y, zi)]), jnp.stack([zi2, zi2]))
    return jnp.stack([r[0], r[1]], axis=-3)
