"""Radix-2^8 limb-vector arithmetic in int32 tensors.

A k-bit integer is a little-endian vector of 8-bit limbs stored as int32.
All intermediates are engineered to stay inside int32:

* 8x8-bit partial products are < 2^16,
* a product column accumulates at most NLIMBS = 32 of them
  (32 * 255^2 = 2,080,800 < 2^21), and two such columns plus a digit
  stay < 2^23,
* carry normalization uses arithmetic shifts (floor semantics), so signed
  intermediates from subtraction are handled exactly — provided the TOTAL
  value is non-negative (callers add a modulus before subtracting).

Two products, told apart by what the caller holds. Two limb tensors take
``mul_full``: the outer product's 1,024 cells contracted with a one-hot
matrix (``Precision.HIGH``: a 16-bit cell is two bfloat16 pieces, not
one). A limb tensor by a constant known when the program is traced takes
``mul_const``: one matmul of the 32 limbs by a dense 32-row weight that
holds the constant's bytes, exact in one bfloat16 pass.

These helpers are modulus-agnostic; ``field.py`` builds Montgomery fields
on top.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import devobs, metrics as mx

RADIX_BITS = 8
RADIX = 1 << RADIX_BITS
MASK = RADIX - 1
NLIMBS = 32  # 256-bit elements


# ---------------------------------------------------------------- host conv

def int_to_limbs(x: int, nlimbs: int = NLIMBS) -> np.ndarray:
    """Host: python int -> little-endian limb vector."""
    if x < 0:
        raise ValueError("int_to_limbs: negative value")
    out = np.zeros(nlimbs, dtype=np.int32)
    for i in range(nlimbs):
        out[i] = x & MASK
        x >>= RADIX_BITS
    if x:
        raise ValueError("int_to_limbs: value does not fit")
    return out


def limbs_to_int(v) -> int:
    """Host: limb vector (canonical or not) -> python int."""
    arr = np.asarray(v).astype(object)
    return int(sum(int(arr[..., i]) << (RADIX_BITS * i) for i in range(arr.shape[-1])))


def ints_to_limbs(xs, nlimbs: int = NLIMBS) -> np.ndarray:
    """Host: iterable of ints -> (N, nlimbs) array."""
    return np.stack([int_to_limbs(x, nlimbs) for x in xs])


def batch_limbs_to_ints(arr) -> list:
    """Host: (..., n) limb array (canonical or not) -> flat list of ints.

    Canonical limbs (integers in [0, RADIX)) are the little-endian bytes
    of the value: one ``int.from_bytes`` per element over the bytes of the
    whole array. Any other array takes ``limbs_to_int`` row by row."""
    a = np.asarray(arr)
    if a.size == 0:
        return []
    n = a.shape[-1]
    flat = a.reshape(-1, n)
    if a.dtype.kind not in "iu" or flat.min() < 0 or flat.max() > MASK:
        return [limbs_to_int(row) for row in flat]
    raw = flat.astype(np.uint8).tobytes()
    return [int.from_bytes(raw[i : i + n], "little") for i in range(0, len(raw), n)]


# ---------------------------------------------------------------- carries

def carry_pass(x):
    """One carry-propagation pass (signed, floor-shift semantics)."""
    c = x >> RADIX_BITS
    rem = x - (c << RADIX_BITS)
    shifted = jnp.concatenate(
        [jnp.zeros_like(c[..., :1]), c[..., :-1]], axis=-1
    )
    return rem + shifted


def normalize(x):
    """Propagate carries until every limb is canonical (in [0, RADIX)).

    General data-dependent form (while_loop) — used only off the hot path.
    The represented TOTAL must be non-negative and fit the vector width.
    """

    def cond(v):
        return jnp.any((v < 0) | (v > MASK))

    return jax.lax.while_loop(cond, carry_pass, x)


def _roll_up(a, s: int):
    """a[i - s] with zeros shifted in (along the limb axis)."""
    pad = jnp.zeros_like(a[..., :s])
    return jnp.concatenate([pad, a[..., :-s]], axis=-1)


def normalize_fixed(x, passes: int):
    """Branch-free carry normalization for NON-NEGATIVE digit vectors.

    `passes` plain carry passes must bring every digit into [0, RADIX]
    (bound: B -> MASK + (B >> RADIX_BITS)); the residual +1 carries are then
    resolved exactly with a Kogge-Stone carry-lookahead (log-depth, no
    data-dependent control flow — the TPU-friendly form).
    """
    for _ in range(passes):
        x = carry_pass(x)
    # digits now in [0, RADIX]; resolve unit carries via (generate, propagate)
    g = (x > MASK).astype(jnp.int32)
    p = (x == MASK).astype(jnp.int32)
    n = x.shape[-1]
    s = 1
    while s < n:
        g = g | (p & _roll_up(g, s))
        p = p & _roll_up(p, s)
        s <<= 1
    c_in = _roll_up(g, 1)
    t = x + c_in
    return t - ((t > MASK).astype(jnp.int32) << RADIX_BITS)


# ---------------------------------------------------------------- add / cmp

def add(x, y):
    """Limb-wise add; caller normalizes/reduces."""
    return x + y


def compare_ge(x, y):
    """Lexicographic >= of two canonical limb vectors. Shapes broadcast."""
    x, y = jnp.broadcast_arrays(x, y)
    neq = x != y
    # index of the most significant differing limb (0 if none differ)
    msd = x.shape[-1] - 1 - jnp.argmax(neq[..., ::-1], axis=-1)
    xd = jnp.take_along_axis(x, msd[..., None], axis=-1)[..., 0]
    yd = jnp.take_along_axis(y, msd[..., None], axis=-1)[..., 0]
    return jnp.where(jnp.any(neq, axis=-1), xd >= yd, True)


def is_zero(x):
    return jnp.all(x == 0, axis=-1)


# ---------------------------------------------------------------- multiply

# The precision each product's contraction is given, and from it the form
# `ops.health()["device"]["fp_mul"]` names: the label is the argument.
_VAR_PRECISION = jax.lax.Precision.HIGH
_CONST_PRECISION = jax.lax.Precision.DEFAULT
PRODUCT_FORMS = {
    "const": "dense32/" + _CONST_PRECISION.name.lower(),
    "var": "onehot1024/" + _VAR_PRECISION.name.lower(),
}
devobs.note_product_forms(PRODUCT_FORMS)


@functools.lru_cache(maxsize=None)
def _conv_matrix(nx: int, ny: int):
    """One-hot (nx*ny, nx+ny+1) matrix mapping outer-product cell (i,j) to
    product column i+j: the column sums of a variable-by-variable
    schoolbook product as one matmul over the flattened outer product."""
    k = nx + ny + 1
    c = np.zeros((nx, ny, k), dtype=np.int32)
    for i in range(nx):
        for j in range(ny):
            c[i, j, i + j] = 1
    # NOTE: return the numpy constant — converting to a jax array here would
    # cache a tracer when first called under an active trace.
    return c.reshape(nx * ny, k)


def mul_full(x, y):
    """Full product of two limb vectors -> nx+ny+1 canonical limbs.

    The general product: neither operand is known when the program is
    traced (a constant operand takes `mul_const`). Both operands must be
    canonical (limbs in [0, 255], as field elements are): outer products are
    < 2^16 and each column sum < 2^21, all values exactly representable
    in float32, so the column contraction runs as an f32 matmul over the
    nx*ny cells and is cast back to int32 losslessly (CPU: real GEMM; TPU:
    MXU at `Precision.HIGH`, three bfloat16 passes — a 16-bit cell is
    exactly two bfloat16 pieces and the one-hot weight one, so the third
    piece `HIGHEST` adds is always zero). Fully branch-free.
    """
    mx.counter("field.product.general").inc()  # at trace time only
    nx, ny = x.shape[-1], y.shape[-1]
    prod = x[..., :, None] * y[..., None, :]  # int32, exact (< 2^16)
    flat = prod.reshape(prod.shape[:-2] + (nx * ny,)).astype(jnp.float32)
    acc = jax.lax.dot_general(
        flat,
        _conv_matrix(nx, ny).astype(np.float32),
        (((flat.ndim - 1,), (0,)), ((), ())),
        precision=_VAR_PRECISION,
    )
    return normalize_fixed(acc.astype(jnp.int32), 3)


@functools.lru_cache(maxsize=None)
def _const_matrix(c_bytes: bytes, keep: int):
    """Dense (n, keep) f32 weight T with T[i, i+j] = c_j: x @ T are the
    columns of x*c. Numpy, for the reason `_conv_matrix` gives."""
    c = np.frombuffer(c_bytes, dtype=np.uint8)
    n = len(c)
    t = np.zeros((n, keep), dtype=np.float32)
    for i in range(n):
        w = min(n, keep - i)
        t[i, i : i + w] = c[:w]
    return t


def mul_const(x, c_limbs, keep=None):
    """RAW product columns of a limb tensor by a constant: (..., n) by the
    n canonical limbs of a numpy constant -> (..., keep or 2n) int32
    columns, column k = sum_i x_i * c_(k-i). Not normalized: the caller
    runs the carry chain (three passes of `normalize_fixed` do).

    `x` must be canonical (limbs in [0, 255]): operand and weight are then
    exact in bfloat16 and a column is <= 32 * 255^2 = 2,080,800 < 2^21,
    exact in the f32 accumulator — one MXU pass at `Precision.DEFAULT` on
    the TPU, a real f32 GEMM on the CPU backend. `keep=n` builds only the
    low n columns: the product mod RADIX^n needs no others.
    """
    mx.counter("field.product.const").inc()  # at trace time only
    c = np.asarray(c_limbs)
    n = c.shape[-1]
    weight = _const_matrix(c.astype(np.uint8).tobytes(), 2 * n if keep is None else keep)
    xf = x.astype(jnp.float32)
    cols = jax.lax.dot_general(
        xf,
        weight,
        (((xf.ndim - 1,), (0,)), ((), ())),
        precision=_CONST_PRECISION,
        preferred_element_type=jnp.float32,
    )
    return cols.astype(jnp.int32)
