"""Differential tests: TPU limb/field kernels vs host big-int math."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fabric_token_sdk_tpu.crypto import hostmath as hm
from fabric_token_sdk_tpu.ops import FP, FR, limbs as lb
from fabric_token_sdk_tpu.ops.field import FieldSpec
from fabric_token_sdk_tpu.utils import metrics as mx


def test_limb_roundtrip(rng):
    xs = [rng.randrange(1 << 256) for _ in range(8)]
    arr = lb.ints_to_limbs(xs)
    assert lb.batch_limbs_to_ints(arr) == xs


def test_mul_full_matches_host(rng):
    xs = [rng.randrange(1 << 256) for _ in range(4)]
    ys = [rng.randrange(1 << 256) for _ in range(4)]
    prod = lb.mul_full(jnp.asarray(lb.ints_to_limbs(xs)), jnp.asarray(lb.ints_to_limbs(ys)))
    got = lb.batch_limbs_to_ints(np.asarray(prod))
    assert got == [x * y for x, y in zip(xs, ys)]


@pytest.mark.parametrize("keep", [32, 64])
@pytest.mark.parametrize("which", ["pprime", "p"])
@pytest.mark.parametrize("F", [FP, FR], ids=["fp", "fr"])
def test_mul_const_matches_host(F, which, keep, rng):
    c = getattr(F, which + "_limbs")
    xs = [0, 1, (1 << 256) - 1] + [rng.randrange(1 << 256) for _ in range(5)]
    cols = np.asarray(lb.mul_const(jnp.asarray(lb.ints_to_limbs(xs)), c, keep=keep))
    assert cols.shape == (len(xs), keep)
    assert cols.min() >= 0 and cols.max() <= 32 * 255 * 255
    ci = lb.limbs_to_int(c)
    # raw columns: their weighted sum is the product (mod RADIX^keep when cut)
    got = [lb.limbs_to_int(row) for row in cols]
    if keep == 32:
        # the columns past the cut are dropped, not carried: compare mod R
        assert [g % (1 << 256) for g in got] == [(x * ci) % (1 << 256) for x in xs]
    else:
        assert got == [x * ci for x in xs]
    assert lb.mul_const(jnp.zeros((2, 32), jnp.int32), c).shape == (2, 64)


def _onehot_product(x, y, round_bf16=False):
    """The parent's general product, whatever `lb.mul_full` has become;
    with `round_bf16` the benchmark's control (`benchmark/tests/
    drive_broken.py bf16_limbs`): the outer products rounded to bfloat16."""
    nx, ny = x.shape[-1], y.shape[-1]
    prod = x[..., :, None] * y[..., None, :]
    flat = prod.reshape(prod.shape[:-2] + (nx * ny,)).astype(jnp.float32)
    if round_bf16:
        flat = flat.astype(jnp.bfloat16).astype(jnp.float32)
    acc = jax.lax.dot_general(
        flat, lb._conv_matrix(nx, ny).astype(np.float32),
        (((flat.ndim - 1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)
    return lb.normalize_fixed(acc.astype(jnp.int32), 3)


def _mul_three_products(F, x, y):
    """`FieldSpec.mul` as it stood before PR 43: three general products,
    each normalized on its own. The reference the new form is held to,
    limb for limb."""
    n = F.nlimbs
    t = _onehot_product(x, y)
    m = _onehot_product(t[..., :n], jnp.asarray(F.pprime_limbs))[..., :n]
    mp = _onehot_product(m, jnp.asarray(F.p_limbs))
    pad = [(0, 0)] * (t.ndim - 1) + [(0, 1)]
    acc = jnp.pad(t, pad) + jnp.pad(mp, pad)
    return lb.normalize_fixed(acc, 1)[..., n : 2 * n]


def _redundant_pairs(F, rng, n):
    p = F.modulus
    edges = [0, 1, p - 1, p, 2 * p - 1]
    xs = [a for a in edges for _ in edges]
    ys = [b for _ in edges for b in edges]
    while len(xs) < n:
        xs.append(rng.randrange(2 * p))
        ys.append(rng.randrange(2 * p))
    return (jnp.asarray(lb.ints_to_limbs(xs[:n])), jnp.asarray(lb.ints_to_limbs(ys[:n])))


@pytest.mark.parametrize("form", ["rows", "jit", "axes_3_8"])
@pytest.mark.parametrize("F", [FP, FR], ids=["fp", "fr"])
def test_field_mul_is_the_three_product_form_limb_for_limb(F, form, rng):
    if form == "axes_3_8":
        x, y = _redundant_pairs(F, rng, 40)  # the last 9 edge pairs, 15 random
        x, y = x[16:].reshape(3, 8, 32), y[16:].reshape(3, 8, 32)
    else:
        x, y = _redundant_pairs(F, rng, 64)
    mul = jax.jit(lambda a, b: F.mul(a, b)) if form == "jit" else F.mul
    got = np.asarray(mul(x, y))
    assert got.shape == x.shape
    assert np.array_equal(got, np.asarray(_mul_three_products(F, x, y)))
    p, rinv = F.modulus, pow(1 << 256, -1, F.modulus)
    for a, b, z in zip(*(lb.batch_limbs_to_ints(np.asarray(v)) for v in (x, y, got))):
        assert z < 2 * p and z % p == a * b * rinv % p


# the jitted method's body, traced anew at every call: a replaced
# `lb.mul_full` is seen, and no trace of it stays in the method's cache
_mul_body = FieldSpec.mul.__wrapped__


@pytest.mark.parametrize("F", [FP, FR], ids=["fp", "fr"])
def test_field_mul_reaches_the_general_product_through_the_module(F, rng, monkeypatch):
    x, y = _redundant_pairs(F, rng, 40)
    sound = np.asarray(_mul_body(F, x, y))
    assert np.array_equal(sound, np.asarray(F.mul(x, y)))
    monkeypatch.setattr(lb, "mul_full", functools.partial(_onehot_product, round_bf16=True))
    broken = np.asarray(_mul_body(F, x, y))
    # 0 * y and 1 * 1 survive a rounding; a random pair does not
    assert (broken[25:] != sound[25:]).any(axis=-1).all()


@pytest.mark.parametrize("F,products", [(FP, 1), (FR, 1), (FP, 3)], ids=["fp", "fr", "fp_chain"])
def test_a_lowered_program_counts_one_general_product_to_two_constant(F, products):
    general = mx.counter("field.product.general")
    const = mx.counter("field.product.const")

    def program(x, y):
        for _ in range(products):
            x = _mul_body(F, x, y)
        return x

    g0, c0 = general.value, const.value
    shape = jax.ShapeDtypeStruct((8, 32), jnp.int32)
    lowered = jax.jit(program).lower(shape, shape)
    assert (general.value - g0, const.value - c0) == (products, 2 * products)
    # counted where the program is traced, not where it runs
    out = lowered.compile()(jnp.zeros((8, 32), jnp.int32), jnp.zeros((8, 32), jnp.int32))
    assert not np.asarray(out).any()
    assert (general.value - g0, const.value - c0) == (products, 2 * products)


@pytest.mark.parametrize("kind", ["const", "var"])
def test_health_names_the_form_each_product_is_lowered_in(kind):
    """The label `ops.health()` shows is read off the lowered program:
    the contraction's width and the precision its `dot_general` carries."""
    from fabric_token_sdk_tpu.utils import devobs

    shape = jax.ShapeDtypeStruct((8, 32), jnp.int32)
    if kind == "const":
        text = jax.jit(lambda x: lb.mul_const(x, FP.p_limbs)).lower(shape).as_text()
    else:
        text = jax.jit(lb.mul_full).lower(shape, shape).as_text()
    (dot,) = [ln for ln in text.splitlines() if "dot_general" in ln]
    weight = dot.split("(tensor<")[1].split("tensor<")[1].split("x")[0]  # K of (rows, K) @ (K, cols)
    precision = dot.split("precision = [")[1].split(",")[0].lower()
    layout = {"const": "dense", "var": "onehot"}[kind]
    assert devobs.health_section()["fp_mul"][kind] == f"{layout}{weight}/{precision}"


def test_compare_ge(rng):
    pairs = [(5, 5), (4, 9), (9, 4), (1 << 255, (1 << 255) - 1)]
    x = jnp.asarray(lb.ints_to_limbs([a for a, _ in pairs]))
    y = jnp.asarray(lb.ints_to_limbs([b for _, b in pairs]))
    got = np.asarray(lb.compare_ge(x, y))
    assert list(got) == [a >= b for a, b in pairs]


@pytest.mark.parametrize("F,mod", [(FP, hm.P), (FR, hm.R)])
def test_field_mul_add_sub(F, mod, rng):
    xs = [rng.randrange(mod) for _ in range(6)]
    ys = [rng.randrange(mod) for _ in range(6)]
    X, Y = F.encode(xs), F.encode(ys)
    assert F.decode(F.mul(X, Y)) == [(a * b) % mod for a, b in zip(xs, ys)]
    assert F.decode(F.add(X, Y)) == [(a + b) % mod for a, b in zip(xs, ys)]
    assert F.decode(F.sub(X, Y)) == [(a - b) % mod for a, b in zip(xs, ys)]
    assert F.decode(F.neg(X)) == [(-a) % mod for a in xs]


def test_field_edge_values():
    mod = FP.modulus
    xs = [0, 1, mod - 1, mod - 2]
    X = FP.encode(xs)
    assert FP.decode(FP.add(X, X)) == [(2 * a) % mod for a in xs]
    assert FP.decode(FP.sub(X, FP.encode([1, 1, 1, 1]))) == [(a - 1) % mod for a in xs]
    assert FP.decode(FP.mul(X, X)) == [(a * a) % mod for a in xs]


def test_field_inv_pow(rng):
    mod = FP.modulus
    xs = [rng.randrange(1, mod) for _ in range(4)]
    X = FP.encode(xs)
    inv = FP.inv(X)
    assert FP.decode(FP.mul(X, inv)) == [1] * 4
    e = 0xDEADBEEF
    assert FP.decode(FP.pow_const(X, e)) == [pow(a, e, mod) for a in xs]


def test_field_under_jit(rng):
    mod = FR.modulus
    xs = [rng.randrange(mod) for _ in range(3)]
    X = FR.encode(xs)

    @jax.jit
    def f(a):
        return FR.mul(FR.add(a, a), a)

    assert FR.decode(f(X)) == [(2 * a * a) % mod for a in xs]
