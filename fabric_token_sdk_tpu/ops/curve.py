"""Batched BN254 G1 group ops on limb tensors (Jacobian, branch-free).

A batch of points is one int32 tensor of shape (..., 3, NLIMBS): Jacobian
(X, Y, Z) in Montgomery form, Z == 0 encoding infinity. All formulas are
select-based (no data-dependent branches) so they vmap/jit/shard cleanly —
the TPU-first counterpart of gnark's per-point assembly used by the
reference via IBM mathlib (`*math.G1`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import limbs as lb
from .field import FP, FR
from ..crypto import hostmath as hm
from ..utils import metrics as mx


def infinity(shape=()) -> jnp.ndarray:
    """Batch of points at infinity."""
    return jnp.zeros(tuple(shape) + (3, lb.NLIMBS), dtype=jnp.int32)


def is_infinity(p):
    return FP.is_zero(p[..., 2, :])


def neg(p):
    return jnp.stack(
        [p[..., 0, :], FP.neg(p[..., 1, :]), p[..., 2, :]], axis=-2
    )


@jax.jit
def double(p):
    """dbl-2009-l (a=0): branch-free; Z=0 and Y=0 fall out naturally."""
    x, y, z = p[..., 0, :], p[..., 1, :], p[..., 2, :]
    a = FP.sqr(x)
    b = FP.sqr(y)
    c = FP.sqr(b)
    d = FP.sub(FP.sqr(FP.add(x, b)), FP.add(a, c))
    d = FP.add(d, d)
    e = FP.add(FP.add(a, a), a)
    f = FP.sqr(e)
    x3 = FP.sub(f, FP.add(d, d))
    c8 = FP.add(c, c)
    c8 = FP.add(c8, c8)
    c8 = FP.add(c8, c8)
    y3 = FP.sub(FP.mul(e, FP.sub(d, x3)), c8)
    z3 = FP.mul(FP.add(y, y), z)
    return jnp.stack([x3, y3, z3], axis=-2)


@jax.jit
def add(p, q):
    """General Jacobian addition (add-2007-bl) with select-based edge cases:
    either operand at infinity, P == Q (doubling), P == -Q (infinity)."""
    x1, y1, z1 = p[..., 0, :], p[..., 1, :], p[..., 2, :]
    x2, y2, z2 = q[..., 0, :], q[..., 1, :], q[..., 2, :]
    z1z1 = FP.sqr(z1)
    z2z2 = FP.sqr(z2)
    u1 = FP.mul(x1, z2z2)
    u2 = FP.mul(x2, z1z1)
    s1 = FP.mul(FP.mul(y1, z2), z2z2)
    s2 = FP.mul(FP.mul(y2, z1), z1z1)
    h = FP.sub(u2, u1)
    i = FP.sqr(FP.add(h, h))
    j = FP.mul(h, i)
    rr = FP.sub(s2, s1)
    rr = FP.add(rr, rr)
    v = FP.mul(u1, i)
    x3 = FP.sub(FP.sqr(rr), FP.add(j, FP.add(v, v)))
    s1j = FP.mul(s1, j)
    y3 = FP.sub(FP.mul(rr, FP.sub(v, x3)), FP.add(s1j, s1j))
    z3 = FP.mul(FP.sub(FP.sqr(FP.add(z1, z2)), FP.add(z1z1, z2z2)), h)
    out = jnp.stack([x3, y3, z3], axis=-2)

    same_x = FP.is_zero(h)
    same_y = FP.is_zero(rr)
    inf1 = FP.is_zero(z1)
    inf2 = FP.is_zero(z2)
    # P == Q (and neither infinite): use the doubling formula
    out = jnp.where((same_x & same_y & ~inf1 & ~inf2)[..., None, None], double(p), out)
    # P == -Q: infinity (out.Z is already 0 since h == 0 => z3 == 0, but X/Y
    # are garbage; zero the whole point for canonical equality)
    out = jnp.where(
        (same_x & ~same_y & ~inf1 & ~inf2)[..., None, None], jnp.zeros_like(out), out
    )
    out = jnp.where(inf1[..., None, None], q, out)
    out = jnp.where(inf2[..., None, None], p, out)
    return out


@jax.jit
def eq(p, q):
    """Equality in Jacobian coordinates (cross-multiplied, batch-wise)."""
    x1, y1, z1 = p[..., 0, :], p[..., 1, :], p[..., 2, :]
    x2, y2, z2 = q[..., 0, :], q[..., 1, :], q[..., 2, :]
    z1z1 = FP.sqr(z1)
    z2z2 = FP.sqr(z2)
    xe = FP.eq(FP.mul(x1, z2z2), FP.mul(x2, z1z1))
    ye = FP.eq(FP.mul(FP.mul(y1, z2), z2z2), FP.mul(FP.mul(y2, z1), z1z1))
    inf1 = FP.is_zero(z1)
    inf2 = FP.is_zero(z2)
    return jnp.where(inf1 | inf2, inf1 == inf2, xe & ye)


def scalar_bits(k_canon, nbits: int = 256):
    """Canonical (non-Montgomery) limb scalars (..., NLIMBS) -> bits
    (..., nbits), most significant first."""
    shifts = jnp.arange(lb.RADIX_BITS, dtype=jnp.int32)
    bits = (k_canon[..., :, None] >> shifts[None, :]) & 1  # (..., NLIMBS, 8) LSB-first
    flat = bits.reshape(bits.shape[:-2] + (lb.NLIMBS * lb.RADIX_BITS,))
    return flat[..., :nbits][..., ::-1]  # MSB first


def scalar_digits(k_canon, w: int):
    """Canonical limb scalars (..., NLIMBS) -> base-2^w digits
    (..., ceil(256 / w)), most significant first. A width that does not
    divide 256 gets a short leading digit."""
    bits = scalar_bits(k_canon)
    lead = (-bits.shape[-1]) % w
    bits = jnp.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(lead, 0)])
    bits = bits.reshape(bits.shape[:-1] + (-1, w))
    return jnp.sum(bits << jnp.arange(w - 1, -1, -1, dtype=jnp.int32), axis=-1)


# Bits a digit of the window `scalar_mul` walks a scalar in: the least
# by the count of Fp multiplications a scalar (4,805 / 3,945 / 3,609 /
# 3,729 at 2 / 3 / 4 / 5 bits; bit-serial 7,680) and on the chip: one
# warm dispatch of 128 rows on a TPU v5e, ms at 2 / 3 / 4 / 5 bits,
# 47.5 / 36.3 / 33.2 / 34.5 (bit-serial 62.6; sweep: PERF.md section 6,
# PR 31).
MUL_WINDOW_BITS = 4


def windowed_mul(p, k_canon, w: int, add, double, infinity):
    """k * p by fixed-window double-and-add, for either group (`add`,
    `double`, `infinity` are the group's): a per-row table
    [O, P, 2P, ..., (2^w - 1)P] built on the device, then one step per
    base-2^w digit, most significant first: w doublings, a one-hot
    select of the digit's entry, ONE complete addition. Digit 0 selects
    the point at infinity, which `add` passes through, and the complete
    `add` also covers a row whose accumulator meets the selected entry
    or its negative. The table build and the doublings are loops of one
    point operation each: the program stays small."""
    point_ndim = infinity().ndim
    acc0 = infinity(p.shape[: p.ndim - point_ndim])

    def entry(prev, _):
        nxt = add(prev, p)
        return nxt, nxt

    _, multiples = lax.scan(entry, acc0, None, length=(1 << w) - 1)
    table = jnp.concatenate([acc0[None], multiples])  # (2^w, ..., point)
    digits = jnp.moveaxis(scalar_digits(k_canon, w), -1, 0)
    entries = jnp.arange(1 << w, dtype=jnp.int32).reshape(
        (-1,) + (1,) * acc0.ndim
    )

    def step(acc, digit):
        acc = lax.fori_loop(0, w, lambda _, a: double(a), acc)
        pick = jnp.expand_dims(digit, tuple(range(-point_ndim, 0))) == entries
        return add(acc, jnp.sum(jnp.where(pick, table, 0), axis=0)), None

    out, _ = lax.scan(step, acc0, digits)
    return out


@jax.jit
def scalar_mul(p, k_canon):
    """Batched variable-base k * P: (..., 3, L) x (..., L) -> (..., 3, L).

    k_canon is a canonical (non-Montgomery) limb scalar, any 256-bit
    value. 64 four-bit digits over a per-row table (`windowed_mul`).
    """
    return windowed_mul(p, k_canon, MUL_WINDOW_BITS, add, double, infinity)


def tree_sum(points, axis: int = -3):
    """Sum a batch of points along `axis` via log-depth pairwise adds."""
    points = jnp.moveaxis(points, axis, 0)
    n = points.shape[0]
    while n > 1:
        half = n // 2
        odd = points[2 * half :]  # 0 or 1 leftover
        points = add(points[:half], points[half : 2 * half])
        if odd.shape[0]:
            points = jnp.concatenate([points, odd], axis=0)
        n = points.shape[0]
    return points[0]


# ---------------------------------------------------------------- host I/O

def encode_point(pt) -> np.ndarray:
    """Host affine (x, y) or None -> (3, NLIMBS) Montgomery Jacobian."""
    if pt is None:
        return np.zeros((3, lb.NLIMBS), dtype=np.int32)
    R = 1 << (lb.RADIX_BITS * lb.NLIMBS)
    x, y = pt
    return np.stack(
        [
            lb.int_to_limbs(x * R % hm.P),
            lb.int_to_limbs(y * R % hm.P),
            lb.int_to_limbs(R % hm.P),
        ]
    )


def encode_points(pts) -> jnp.ndarray:
    return jnp.asarray(np.stack([encode_point(p) for p in pts]))


_R = (1 << (lb.RADIX_BITS * lb.NLIMBS)) % hm.P  # Montgomery radix mod p


def batch_fp_inv(zs) -> list:
    """Host: inverses mod p of a list of residues, by Montgomery's trick:
    prefix products, ONE modular inversion, a walk back. A zero stays out
    of the product and comes back as 0.

    Counts what the read-back decode does: ``batch.decode.points`` by
    ``len(zs)``, ``batch.decode.inversions`` by the inversions done (1, or
    0 where every entry is zero)."""
    P = hm.P
    prefix = []
    acc = 1
    for z in zs:
        prefix.append(acc)
        if z:
            acc = acc * z % P
    mx.counter("batch.decode.points").inc(len(zs))
    out = [0] * len(zs)
    if not any(zs):
        return out
    mx.counter("batch.decode.inversions").inc()
    inv = pow(acc, -1, P)
    for i in range(len(zs) - 1, -1, -1):
        if zs[i]:
            out[i] = inv * prefix[i] % P
            inv = inv * zs[i] % P
    return out


def decode_points(arr):
    """Device (..., 3, NLIMBS) -> host affine tuples.

    Pure host arithmetic over the whole batch: the limbs come off as bytes
    (``lb.batch_limbs_to_ints``), the z of every finite row is inverted in
    one ``batch_fp_inv``, and the Montgomery factor rides the same pass
    (with w = (zR)^-1: u = wR = z^-1, t = uw = R / (zR)^2, so x = xR * t
    and y = yR * t * u). Decoding compiles no device program (the batched
    verifiers' XLA program set stays independent of batch/statement
    shape). A row whose z is 0 mod p is None."""
    P = hm.P
    vals = lb.batch_limbs_to_ints(np.asarray(arr).reshape(-1, 3, lb.NLIMBS))
    ws = batch_fp_inv([z % P for z in vals[2::3]])
    out = []
    for x, y, w in zip(vals[0::3], vals[1::3], ws):
        if not w:
            out.append(None)
            continue
        u = w * _R % P
        t = u * w % P
        out.append((x * t % P, y * t % P * u % P))
    return out


def decode_point(arr):
    return decode_points(arr[None])[0]


def encode_scalars(ks) -> np.ndarray:
    """Host ints -> canonical limb scalars (N, NLIMBS).

    Returns numpy (host data): batch-assembly loops stack many of these
    before one device transfer; jit'd consumers convert implicitly.
    """
    return lb.ints_to_limbs([k % hm.R for k in ks])


# ---------------------------------------------------------------- fixed base

WINDOW_BITS = 4
DIGITS_PER_SCALAR = 256 // WINDOW_BITS  # 64


class FixedBaseTable:
    """Windowed multiples of a list of fixed bases for batched multiexp.

    Table[b, w, d] = base_b * (d << (4w)), shape (nbases, 64, 16, 3, L).
    A multiexp is then: one-hot digit selection (a dense matmul riding the
    MXU) followed by a log-depth tree of point additions.

    Used for the Pedersen-parameter bases (reference: PedParams/PedGen in
    setup.go) — the hottest multiexp in issue/transfer proving and
    verification.
    """

    def __init__(self, host_points):
        self.nbases = len(host_points)
        tables = np.zeros(
            (self.nbases, DIGITS_PER_SCALAR, 1 << WINDOW_BITS, 3, lb.NLIMBS),
            dtype=np.int32,
        )
        for b, pt in enumerate(host_points):
            for w in range(DIGITS_PER_SCALAR):
                step = hm.g1_mul(pt, (1 << (WINDOW_BITS * w)) % hm.R)
                acc = None
                for d in range(1 << WINDOW_BITS):
                    tables[b, w, d] = encode_point(acc)
                    acc = hm.g1_add(acc, step)
        # flatten for the one-hot contraction: (nbases*64, 16, 3*L)
        self.flat = jnp.asarray(
            tables.reshape(self.nbases * DIGITS_PER_SCALAR, 1 << WINDOW_BITS, 3 * lb.NLIMBS)
        )

    def msm(self, scalars):
        """scalars: canonical limb tensor (..., nbases, NLIMBS) ->
        points (..., 3, NLIMBS) = sum_b scalar_b * base_b."""
        return msm_flat(self.flat, scalars)


def msm_select(flat, scalars):
    """Window-digit point selection shared by every msm reduction:
    scalars (..., nbases, NLIMBS) x table (nbases*64, 16, 3L) ->
    selected window points (..., nbases*64, 3, NLIMBS). The one-hot
    digit contraction is a dense matmul that rides the MXU."""
    nbases = flat.shape[0] // DIGITS_PER_SCALAR
    shifts = jnp.arange(0, lb.RADIX_BITS, WINDOW_BITS, dtype=jnp.int32)
    digs = (scalars[..., :, :, None] >> shifts) & ((1 << WINDOW_BITS) - 1)
    # (..., nbases, NLIMBS * 2) -> (..., nbases*64)
    digs = digs.reshape(digs.shape[:-3] + (nbases * DIGITS_PER_SCALAR,))
    onehot = (digs[..., None] == jnp.arange(1 << WINDOW_BITS, dtype=jnp.int32)).astype(
        jnp.int32
    )  # (..., nbases*64, 16)
    sel = jnp.einsum("...td,tdc->...tc", onehot, flat)
    return sel.reshape(sel.shape[:-1] + (3, lb.NLIMBS))


@jax.jit
def msm_flat(flat, scalars):
    """Fixed-base windowed multiexp against a table passed as an ARGUMENT
    (not a baked constant), so the compiled program is shared across all
    parameter sets — callers with different Pedersen bases / public keys
    reuse one XLA executable per shape."""
    return tree_sum(msm_select(flat, scalars), axis=-3)


@functools.lru_cache(maxsize=8)
def generator_table(n: int = 1) -> FixedBaseTable:
    return FixedBaseTable([hm.G1_GEN] * n)
