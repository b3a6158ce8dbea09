"""Differential tests: the batch read-back decode vs the per-point formula.

``cv.decode_points`` / ``cv2.decode_points`` invert once a call
(Montgomery's trick) and ``lb.batch_limbs_to_ints`` reads canonical limbs
as bytes; the references here are the per-point forms they replaced, on
``hostmath`` integers: one ``limbs_to_int`` a coordinate, one multiply by
R^-1, one ``hm.fp_inv`` / ``hm.fp2_inv`` a point. Pure host arithmetic: no
device program is compiled.
"""
import random

import numpy as np
import pytest

from fabric_token_sdk_tpu.crypto import hostmath as hm
from fabric_token_sdk_tpu.ops import curve as cv, curve2 as cv2, limbs as lb, tower as tw
from fabric_token_sdk_tpu.utils import metrics as mx

L = lb.NLIMBS
P = hm.P
_RINV = pow(1 << (lb.RADIX_BITS * L), -1, P)


# ------------------------------------------------------------ references

def _ref_fp(limbs) -> int:
    return lb.limbs_to_int(limbs) * _RINV % P


def _ref_g1(arr):
    out = []
    for row in np.asarray(arr).reshape(-1, 3, L):
        x, y, z = (_ref_fp(c) for c in row)
        if z == 0:
            out.append(None)
            continue
        zinv = hm.fp_inv(z)
        zi2 = zinv * zinv % P
        out.append((x * zi2 % P, y * zi2 % P * zinv % P))
    return out


def _ref_fp2(arr):
    flat = [_ref_fp(row) for row in np.asarray(arr).reshape(-1, L)]
    return [(flat[2 * i], flat[2 * i + 1]) for i in range(len(flat) // 2)]


def _ref_fp12(arr):
    pairs = _ref_fp2(arr)
    return [tuple(pairs[6 * i : 6 * i + 6]) for i in range(len(pairs) // 6)]


def _ref_g2(arr):
    flat = np.asarray(arr).reshape(-1, 3, 2, L)
    coords = _ref_fp2(flat)
    out = []
    for i in range(len(flat)):
        x, y, z = coords[3 * i : 3 * i + 3]
        if z == (0, 0):
            out.append(None)
            continue
        zinv = hm.fp2_inv(z)
        zi2 = hm.fp2_mul(zinv, zinv)
        out.append((hm.fp2_mul(x, zi2), hm.fp2_mul(hm.fp2_mul(y, zi2), zinv)))
    return out


# ---------------------------------------------------------------- inputs

def _rand_limbs(rng, shape):
    """Canonical limbs of random residues below p, shape + (L,)."""
    n = int(np.prod(shape, dtype=int))
    vals = [rng.randrange(P) for _ in range(n)]
    return lb.ints_to_limbs(vals).reshape(tuple(shape) + (L,))


_P_LIMBS = lb.int_to_limbs(P)  # an unreduced zero: p < 2^256


def _jacobian_case(name, rng, coord):
    """(N, 3) + coord + (L,) Jacobian rows in Montgomery form; coord is ()
    for G1 and (2,) for G2 (coordinates need not lie on a curve: the
    decode is field arithmetic)."""
    if name.startswith("random"):
        return _rand_limbs(rng, (int(name[6:]), 3) + coord)
    a = _rand_limbs(rng, (6, 3) + coord)
    if name == "inf_first":
        a[0, 2] = 0
    elif name == "inf_last":
        a[-1, 2] = 0
    elif name == "inf_adjacent":
        a[2, 2] = a[3, 2] = 0
    elif name == "inf_all":
        a[:, 2] = 0
    elif name == "z_is_p":
        a[1, 2] = _P_LIMBS  # every component p: an unreduced zero
        a[4, 2] = 0
        a[4, 2][(0,) * len(coord)] = _P_LIMBS  # G2: (p, 0)
    elif name == "z_half_zero":
        # G2 only: a zero component is not infinity
        a[0, 2, 0] = 0
        a[3, 2, 1] = 0
    elif name == "xy_unreduced":
        a[0, 0] = lb.int_to_limbs(P + 5)
        a[3, 1] = lb.int_to_limbs(2 * P + 1)
        a[5, 2] = lb.int_to_limbs(P + 7)
    elif name == "empty":
        a = a[:0]
    elif name == "leading_axes":
        a = _rand_limbs(rng, (2, 5, 3) + coord)
        a[1, 2, 2] = 0
    elif name == "noncanonical_limbs":
        # the same values, limbs outside 0..255: borrow from the next limb
        a = a.astype(np.int64)
        a[..., 0] -= 3 * lb.RADIX
        a[..., 1] += 3
        a[..., 4] += 2 * lb.RADIX
        a[..., 5] -= 2
    else:
        raise AssertionError(name)
    return a


_JACOBIAN_CASES = [
    "random1", "random2", "random129", "random1000", "inf_first", "inf_last",
    "inf_adjacent", "inf_all", "z_is_p", "xy_unreduced", "empty",
    "leading_axes", "noncanonical_limbs",
]


@pytest.mark.parametrize("name", _JACOBIAN_CASES)
def test_g1_decode_points_equals_per_point_formula(name):
    arr = _jacobian_case(name, random.Random(name), ())
    got = cv.decode_points(arr)
    assert got == _ref_g1(arr)
    assert len(got) == int(np.prod(arr.shape[:-2], dtype=int))
    if name == "inf_all":
        assert got == [None] * 6
    if name == "z_is_p":
        assert got[1] is None and got[4] is None and got[0] is not None


def test_g1_decode_point_is_a_batch_of_one(rng):
    row = _rand_limbs(rng, (3,))
    assert cv.decode_point(row) == _ref_g1(row[None])[0]


@pytest.mark.parametrize("name", _JACOBIAN_CASES + ["z_half_zero"])
def test_g2_decode_points_equals_per_point_formula(name):
    arr = _jacobian_case(name, random.Random(name), (2,))
    got = cv2.decode_points(arr)
    assert got == _ref_g2(arr)
    assert len(got) == int(np.prod(arr.shape[:-3], dtype=int))
    if name == "z_is_p":
        assert got[1] is None and got[4] is None and got[0] is not None
    if name == "z_half_zero":
        assert None not in got


def test_real_points_round_trip(rng):
    g1 = [hm.rand_g1(rng) for _ in range(3)] + [None]
    assert cv.decode_points(cv.encode_points(g1)) == g1
    g2 = [hm.g2_mul(hm.G2_GEN, rng.randrange(1, hm.R)) for _ in range(2)] + [None]
    assert cv2.decode_points(cv2.encode_points(g2)) == g2


# ------------------------------------------------------------ Fp2 / Fp12

def _tower_case(name, rng, per_row):
    if name == "random":
        return _rand_limbs(rng, (7, per_row))
    a = _rand_limbs(rng, (3, per_row))
    if name == "p_minus_one":
        a[1] = lb.int_to_limbs((P - 1) * (1 << (lb.RADIX_BITS * L)) % P)
        a[2, 0] = lb.int_to_limbs(P - 1)
    elif name == "zero":
        a[0] = 0
        a[2, per_row - 1] = 0
    elif name == "unreduced":
        a[0, 0] = _P_LIMBS
        a[1, 1] = lb.int_to_limbs(2 * P + 3)
    elif name == "empty":
        a = a[:0]
    elif name == "leading_axes":
        a = _rand_limbs(rng, (2, 2, per_row))
    elif name == "noncanonical_limbs":
        a = a.astype(np.int64)
        a[..., 7] += 5 * lb.RADIX
        a[..., 8] -= 5
    else:
        raise AssertionError(name)
    return a


_TOWER_CASES = [
    "random", "p_minus_one", "zero", "unreduced", "empty", "leading_axes",
    "noncanonical_limbs",
]


@pytest.mark.parametrize("name", _TOWER_CASES)
def test_decode_fp2_equals_per_element_formula(name):
    arr = _tower_case(name, random.Random(name), 2)
    got = tw.decode_fp2(arr)
    assert got == _ref_fp2(arr)
    if name == "p_minus_one":
        assert got[1] == (P - 1, P - 1)
    if name == "zero":
        assert got[0] == (0, 0)


@pytest.mark.parametrize("name", _TOWER_CASES)
def test_decode_fp12_equals_per_element_formula(name):
    arr = _tower_case(name, random.Random(name), 12)
    arr = arr.reshape(arr.shape[:-2] + (6, 2, L))
    got = tw.decode_fp12(arr)
    assert got == _ref_fp12(arr)
    assert len(got) == int(np.prod(arr.shape[:-3], dtype=int))


def test_fp12_round_trip_and_gt_one(rng):
    from fabric_token_sdk_tpu.ops import pairing as pr

    vals = [
        tuple((rng.randrange(P), rng.randrange(P)) for _ in range(6)),
        ((1, 0),) + ((0, 0),) * 5,
        ((P - 1, 0),) + ((0, P - 1),) * 5,
    ]
    enc = tw.encode_fp12(vals)
    assert tw.decode_fp12(enc) == vals
    assert list(pr.gt_is_one_host(enc)) == [False, True, False]


# ----------------------------------------------------- limbs -> integers

def _limb_case(name, rng):
    if name == "canonical_int32":
        return lb.ints_to_limbs([rng.randrange(1 << 256) for _ in range(9)])
    if name == "canonical_uint8":
        return lb.ints_to_limbs([rng.randrange(1 << 256) for _ in range(4)]).astype(np.uint8)
    if name == "canonical_int64_wide":
        return lb.ints_to_limbs([rng.randrange(1 << 512) for _ in range(5)], 64).astype(np.int64)
    if name == "extremes":
        return lb.ints_to_limbs([0, 1, (1 << 256) - 1, P, P - 1])
    if name == "negative_limbs":
        a = lb.ints_to_limbs([rng.randrange(1 << 200, 1 << 256) for _ in range(4)])
        a[:, 3] -= 700
        a[2, 31] = -1
        return a
    if name == "limbs_over_255":
        a = lb.ints_to_limbs([rng.randrange(1 << 256) for _ in range(4)])
        a[:, 0] += 256
        a[1, 30] = 70000
        return a
    if name == "one_bad_limb_of_many":
        a = lb.ints_to_limbs([rng.randrange(1 << 256) for _ in range(64)])
        a[63, 31] = 256
        return a
    if name == "leading_axes":
        return lb.ints_to_limbs([rng.randrange(1 << 256) for _ in range(24)]).reshape(2, 3, 4, L)
    if name == "single_vector":
        return lb.int_to_limbs(rng.randrange(1 << 256))
    if name == "bool_limbs":
        return np.array([[True, False, True], [False, False, True]])
    if name == "float_limbs":
        return np.array([[3.0, 255.0, 1.0], [0.0, 2.0, 9.0]])
    if name == "empty":
        return np.zeros((0, L), dtype=np.int32)
    raise AssertionError(name)


_LIMB_CASES = [
    "canonical_int32", "canonical_uint8", "canonical_int64_wide", "extremes",
    "negative_limbs", "limbs_over_255", "one_bad_limb_of_many", "leading_axes",
    "single_vector", "bool_limbs", "float_limbs", "empty",
]


@pytest.mark.parametrize("name", _LIMB_CASES)
def test_batch_limbs_to_ints_equals_limbs_to_int(name):
    arr = _limb_case(name, random.Random(name))
    rows = np.asarray(arr).reshape(-1, np.asarray(arr).shape[-1])
    want = [lb.limbs_to_int(r) for r in rows]
    got = lb.batch_limbs_to_ints(arr)
    assert got == want
    assert all(type(v) is int for v in got)


def test_batch_limbs_to_ints_takes_a_list_and_a_device_array():
    import jax.numpy as jnp

    xs = [5, (1 << 256) - 1, P]
    assert lb.batch_limbs_to_ints([list(lb.int_to_limbs(x)) for x in xs]) == xs
    assert lb.batch_limbs_to_ints(jnp.asarray(lb.ints_to_limbs(xs))) == xs


# --------------------------------------------------------------- counters

def _moved(fn):
    pts = mx.counter("batch.decode.points").value
    inv = mx.counter("batch.decode.inversions").value
    fn()
    return (mx.counter("batch.decode.points").value - pts,
            mx.counter("batch.decode.inversions").value - inv)


@pytest.mark.parametrize("n", [1, 2, 300])
def test_one_inversion_a_call_whatever_the_batch(rng, n):
    arr = _rand_limbs(rng, (n, 3))
    assert _moved(lambda: cv.decode_points(arr)) == (n, 1)
    g2 = _rand_limbs(rng, (n, 3, 2))
    assert _moved(lambda: cv2.decode_points(g2)) == (n, 1)


def test_infinity_rows_are_counted_and_never_inverted(rng):
    arr = _rand_limbs(rng, (5, 3))
    arr[1, 2] = 0
    arr[3, 2] = _P_LIMBS
    assert _moved(lambda: cv.decode_points(arr)) == (5, 1)
    arr[:, 2] = 0
    assert _moved(lambda: cv.decode_points(arr)) == (5, 0)
    assert _moved(lambda: cv.decode_points(arr[:0])) == (0, 0)
    assert _moved(lambda: tw.decode_fp12(_rand_limbs(rng, (4, 6, 2)))) == (0, 0)


def test_batch_fp_inv(rng):
    zs = [rng.randrange(1, P) for _ in range(7)]
    zs[0] = zs[4] = 0
    zs[2] = 1
    assert cv.batch_fp_inv(zs) == [hm.fp_inv(z) if z else 0 for z in zs]
    assert cv.batch_fp_inv([]) == []
    assert cv.batch_fp_inv([0, 0]) == [0, 0]
