"""Batched Montgomery prime-field arithmetic on limb tensors.

Reference counterpart: IBM mathlib's Zr/Fp scalar ops (used throughout
token/core/zkatdlog/crypto). Here a field is a `FieldSpec` of baked numpy
limb constants; every op is branch-free, batched over leading axes, and
jit-safe. Elements live in Montgomery form (x·R mod p, R = 2^256) as
(..., 32) int32 limb tensors.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import limbs as lb
from ..crypto import hostmath as hm


def _opjit(fn=None, *, static=()):
    """jit a FieldSpec method with `self` static (specs are singletons)."""

    def wrap(f):
        return jax.jit(f, static_argnums=(0,) + tuple(static))

    return wrap(fn) if fn is not None else wrap


@dataclass(frozen=True, eq=False)
class FieldSpec:
    """A prime field with Montgomery constants baked as limb arrays."""

    name: str
    modulus: int
    nlimbs: int = lb.NLIMBS
    p_limbs: np.ndarray = field(init=False, repr=False)
    twop_limbs: np.ndarray = field(init=False, repr=False)
    pprime_limbs: np.ndarray = field(init=False, repr=False)  # -p^-1 mod R
    r2_limbs: np.ndarray = field(init=False, repr=False)  # R^2 mod p
    one_mont: np.ndarray = field(init=False, repr=False)  # R mod p

    def __post_init__(self):
        R = 1 << (lb.RADIX_BITS * self.nlimbs)
        # the redundant-domain REDC design needs 4p <= R so that products of
        # two [0, 2p) elements satisfy T < pR and outputs stay in [0, 2p)
        if 4 * self.modulus > R or self.modulus % 2 == 0:
            raise ValueError("modulus must be odd with 4p within the limb width")
        object.__setattr__(self, "p_limbs", lb.int_to_limbs(self.modulus, self.nlimbs))
        object.__setattr__(self, "twop_limbs", lb.int_to_limbs(2 * self.modulus, self.nlimbs))
        pprime = (-pow(self.modulus, -1, R)) % R
        object.__setattr__(self, "pprime_limbs", lb.int_to_limbs(pprime, self.nlimbs))
        object.__setattr__(self, "r2_limbs", lb.int_to_limbs(R * R % self.modulus, self.nlimbs))
        object.__setattr__(self, "one_mont", lb.int_to_limbs(R % self.modulus, self.nlimbs))

    # ------------------------------------------------------------- reduce
    #
    # Elements live in the REDUNDANT domain [0, 2p): REDC maps products of
    # two such elements back into it (4p^2 < p*2^W), so `mul` needs no
    # final subtraction, and add/sub need only a single select-subtract
    # driven by the top limb of a complement addition — no lexicographic
    # comparisons anywhere on the hot path. Canonical [0, p) form is
    # produced lazily (`canon`) for equality/decoding.

    def _select_sub(self, x, m_limbs: np.ndarray, passes: int):
        """Given digits of x (value < 2^W + range), return x - m if
        x >= m else x, via x + comp(m) + 1 over W+1 limbs: the top limb
        is 1 exactly when x >= m."""
        # numpy constant: comp(m) with the +1 folded into limb 0, plus a
        # zero top limb (branch- and scatter-free)
        compp1 = np.concatenate([lb.MASK - m_limbs, [0]]).astype(np.int32)
        compp1[0] += 1
        s = jnp.concatenate([x, jnp.zeros_like(x[..., :1])], axis=-1) + compp1
        s = lb.normalize_fixed(s, passes)
        ge = s[..., self.nlimbs :][..., 0] > 0
        return jnp.where(ge[..., None], s[..., : self.nlimbs], lb.normalize_fixed(x, passes))

    @_opjit
    def cond_sub_p(self, x):
        """Redundant [0, 2p) -> canonical [0, p)."""
        return self._select_sub(x, self.p_limbs, 1)

    def canon(self, x):
        return self.cond_sub_p(x)

    # ------------------------------------------------------------- ring ops

    @_opjit
    def add(self, x, y):
        """[0,2p) x [0,2p) -> [0,2p): add then select-subtract 2p."""
        return self._select_sub(x + y, self.twop_limbs, 2)

    @_opjit
    def sub(self, x, y):
        """x - y in [0, 2p), borrow-free.

        s = x + comp(y) + 1 over W+1 limbs has value x - y + 2^W; its top
        limb says whether x >= y. If so the low limbs ARE x - y; otherwise
        add 2p to them (total then overflows 2^W exactly once)."""
        comp_y1 = (lb.MASK - y) + np.concatenate([[1], np.zeros(self.nlimbs - 1, np.int32)]).astype(np.int32)
        s = jnp.concatenate(
            [x + comp_y1, jnp.zeros_like(x[..., :1])], axis=-1
        )  # digits <= 511
        s = lb.normalize_fixed(s, 1)
        x_ge_y = s[..., self.nlimbs :][..., 0] > 0
        s_low = s[..., : self.nlimbs]
        t = jnp.concatenate(
            [s_low + self.twop_limbs, jnp.zeros_like(x[..., :1])], axis=-1
        )
        t_low = lb.normalize_fixed(t, 1)[..., : self.nlimbs]
        return jnp.where(x_ge_y[..., None], s_low, t_low)

    @_opjit
    def neg(self, x):
        return self.sub(jnp.zeros_like(x), x)

    @_opjit
    def mul(self, x, y):
        """Montgomery product: REDC(x*y); stays in [0, 2p).

        One general limb product (x*y) and two by the spec's constants:
        m = (x*y mod R) * p' mod R, then (x*y + m*p) / R."""
        n = self.nlimbs
        t = lb.mul_full(x, y)  # (..., 2n+1) canonical digits, the top one 0
        # m mod R: the low n columns, the carry out of limb n-1 dropped;
        # canonical because it is the next product's operand
        m = lb.normalize_fixed(lb.mul_const(t[..., :n], self.pprime_limbs, keep=n), 3)
        mp = lb.mul_const(m, self.p_limbs)  # (..., 2n) raw columns < 2^21
        # digits <= 255 + 2,080,800: three passes bring them to [0, 256];
        # t + m*p < 2pR < R^2, so nothing is carried out of limb 2n-1
        return lb.normalize_fixed(t[..., : 2 * n] + mp, 3)[..., n:]

    @_opjit
    def sqr(self, x):
        return self.mul(x, x)

    @_opjit(static=(2,))
    def pow_const(self, x, e: int):
        """x^e for a python-int exponent, via scan over its bits (MSB first)."""
        if e == 0:
            return jnp.broadcast_to(jnp.asarray(self.one_mont), x.shape).astype(jnp.int32)
        bits = np.array([int(b) for b in bin(e)[2:]], dtype=np.int32)

        def step(acc, bit):
            acc = self.mul(acc, acc)
            acc = jnp.where(bit > 0, self.mul(acc, x), acc)
            return acc, None

        init = jnp.broadcast_to(jnp.asarray(self.one_mont), x.shape).astype(jnp.int32)
        out, _ = lax.scan(step, init, jnp.asarray(bits))
        return out

    @_opjit
    def inv(self, x):
        """Montgomery inverse by Fermat: x^(p-2). x must be nonzero."""
        return self.pow_const(x, self.modulus - 2)

    @_opjit(static=(2,))
    def mul_small(self, x, k: int):
        """x * k for a small static non-negative int, via double-and-add —
        every intermediate stays inside the [0, 2p) domain."""
        if k < 0:
            raise ValueError("mul_small: k must be non-negative")
        if k == 0:
            return jnp.zeros_like(x)
        acc = None
        for bit in bin(k)[2:]:
            acc = self.add(acc, acc) if acc is not None else None
            if bit == "1":
                acc = x if acc is None else self.add(acc, x)
        return acc

    # ------------------------------------------------------------- domain

    @_opjit
    def to_mont(self, x):
        return self.mul(x, jnp.asarray(self.r2_limbs))

    @_opjit
    def from_mont(self, x):
        one = np.zeros(self.nlimbs, dtype=np.int32)
        one[0] = 1
        return self.mul(x, jnp.broadcast_to(jnp.asarray(one), x.shape))

    # ------------------------------------------------------------- host I/O

    def encode(self, values) -> jnp.ndarray:
        """Host ints -> Montgomery limb tensor (N, nlimbs)."""
        vals = [v % self.modulus for v in values]
        raw = lb.ints_to_limbs(vals, self.nlimbs)
        return self.to_mont(jnp.asarray(raw))

    def encode_scalar(self, v: int) -> jnp.ndarray:
        return self.encode([v])[0]

    def decode(self, x) -> list:
        """Montgomery limb tensor -> host ints (canonicalized)."""
        return lb.batch_limbs_to_ints(np.asarray(self.cond_sub_p(self.from_mont(x))))

    def decode_scalar(self, x) -> int:
        return self.decode(x[None, ...])[0]

    # ------------------------------------------------------------- misc

    def zeros(self, shape=()) -> jnp.ndarray:
        return jnp.zeros(tuple(shape) + (self.nlimbs,), dtype=jnp.int32)

    def ones_mont(self, shape=()) -> jnp.ndarray:
        return jnp.broadcast_to(
            jnp.asarray(self.one_mont), tuple(shape) + (self.nlimbs,)
        ).astype(jnp.int32)

    @_opjit
    def is_zero(self, x):
        """Zero test in the redundant domain (0 and p both represent 0)."""
        return lb.is_zero(self.cond_sub_p(x))

    @_opjit
    def eq(self, x, y):
        """Equality in the redundant domain: canonicalize then compare."""
        return jnp.all(self.cond_sub_p(x) == self.cond_sub_p(y), axis=-1)


@functools.lru_cache(maxsize=None)
def _specs():
    return (
        FieldSpec("bn254_fp", hm.P),
        FieldSpec("bn254_fr", hm.R),
    )


FP, FR = _specs()
