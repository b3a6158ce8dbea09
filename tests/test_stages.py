"""Differential tests: stage tile kernels vs pure-host group math.

Every primitive stage in `ops/stages.py` is pinned against
`crypto/hostmath.py` on random inputs, including padding edges (batch
sizes that are not multiples of the tile height, at the host's height
and at the chip's) and the host-glue helpers.
"""

import functools

import numpy as np
import pytest

from fabric_token_sdk_tpu.crypto import hostmath as hm
from fabric_token_sdk_tpu.ops import curve as cv, curve2 as cv2, limbs as lb, \
    stages as st, tower as tw
from fabric_token_sdk_tpu.ops import pairing as pr
from fabric_token_sdk_tpu.utils import metrics as mx

_RINV = pow(1 << (lb.RADIX_BITS * lb.NLIMBS), -1, hm.P)


def _decode_affine_g1(aff):
    """(N, 2, L) Montgomery affine limbs -> host (x, y) tuples."""
    return [
        (lb.limbs_to_int(row[0]) * _RINV % hm.P,
         lb.limbs_to_int(row[1]) * _RINV % hm.P)
        for row in aff
    ]


def _g1_jac(pts):
    return np.stack([cv.encode_point(p) for p in pts])


def _scalars(rng, n):
    return [rng.randrange(hm.R) for _ in range(n)]


def test_g1_mul_rows_matches_host(rng):
    pts = [hm.g1_mul(hm.G1_GEN, 3 + i) for i in range(5)]  # odd: pads to 8
    ks = _scalars(rng, 5)
    got = st.g1_mul_rows(_g1_jac(pts), cv.encode_scalars(ks))
    assert cv.decode_points(got) == [hm.g1_mul(p, k) for p, k in zip(pts, ks)]


def test_g1_add_sub_rows_match_host(rng):
    ps = [hm.g1_mul(hm.G1_GEN, 3 + i) for i in range(9)]
    qs = [hm.g1_mul(hm.G1_GEN, 100 + i) for i in range(9)]
    got = st.g1_add_rows(_g1_jac(ps), _g1_jac(qs))
    assert cv.decode_points(got) == [hm.g1_add(p, q) for p, q in zip(ps, qs)]
    got = st.g1_sub_rows(_g1_jac(ps), _g1_jac(qs))
    assert cv.decode_points(got) == [
        hm.g1_add(p, hm.g1_neg(q)) for p, q in zip(ps, qs)
    ]
    # edge rows: P - P = infinity, P + (-P) handled by the select logic
    got = st.g1_sub_rows(_g1_jac(ps[:2]), _g1_jac(ps[:2]))
    assert cv.decode_points(got) == [None, None]


def test_g1_msm_rows_matches_host_multiexp(rng):
    bases = [hm.g1_mul(hm.G1_GEN, 11 + i) for i in range(3)]
    table = cv.FixedBaseTable(bases)
    rows = [_scalars(rng, 3) for _ in range(6)]
    got = st.g1_msm_rows(table.flat, np.stack([cv.encode_scalars(r) for r in rows]))
    assert cv.decode_points(got) == [hm.g1_multiexp(bases, r) for r in rows]


def test_g1_to_affine_rows_matches_decode(rng):
    pts = [hm.g1_mul(hm.G1_GEN, 5 + i) for i in range(3)]
    ks = cv.encode_scalars(_scalars(rng, 3))
    jac = st.g1_mul_rows(_g1_jac(pts), ks)  # non-trivial Z coordinates
    aff = st.g1_to_affine_rows(jac)
    # affine limbs must decode to the same canonical points
    assert _decode_affine_g1(aff) == cv.decode_points(jac)


# row counts around one tile of height T: a lone row, one short of a
# tile, a full tile, a tile and a ragged second one
@pytest.mark.parametrize(
    "offset", [None, -1, 0, 3], ids=["1", "T-1", "T", "T+3"])
@pytest.mark.parametrize("backend", ["host", "tpu"])
def test_cheap_stage_rows_match_host_at_either_tile_height(
    monkeypatch, backend, offset
):
    """`run_rows` at the host's tile height and at the chip's (the
    backend observation patched; still the CPU's arithmetic): the four
    cheap programs agree with hostmath row for row, whatever the height
    and however many rows were padding. (The scalar-mul tiles at the
    chip's height cost minutes on the CPU: `-m slow`, below.)"""
    monkeypatch.setattr(st, "_on_tpu", lambda: backend == "tpu")
    T = st.tile_rows("g1_add_tile")
    assert (T > 8) == (backend == "tpu")
    assert {st.tile_rows(n) for n in (
        "g1_sub_tile", "g1_to_affine_tile", "g2_add_tile")} == {T}
    N = 1 if offset is None else T + offset
    # a small pool of host points, cycled up to N rows; rows 1-3 are the
    # add's edge cases (infinity, doubling, inverse)
    ps = [hm.g1_mul(hm.G1_GEN, 3 + i) for i in range(7)]
    qs = [hm.g1_mul(hm.G1_GEN, 100 + i) for i in range(7)]
    qs[1], qs[2], qs[3] = None, ps[2], hm.g1_neg(ps[3])
    idx = [i % 7 for i in range(N)]
    a, b = _g1_jac(ps)[idx], _g1_jac(qs)[idx]
    want_add = [hm.g1_add(p, q) for p, q in zip(ps, qs)]
    want_sub = [hm.g1_add(p, hm.g1_neg(q) if q else None)
                for p, q in zip(ps, qs)]
    assert cv.decode_points(st.g1_add_rows(a, b)) == [want_add[i] for i in idx]
    assert cv.decode_points(st.g1_sub_rows(a, b)) == [want_sub[i] for i in idx]
    # to-affine of non-trivial Z: the doubled points, still Jacobian
    dbl = st.g1_add_rows(a, a)
    assert _decode_affine_g1(st.g1_to_affine_rows(dbl)) == [
        hm.g1_add(ps[i], ps[i]) for i in idx
    ]
    p2 = [hm.g2_mul(hm.G2_GEN, 3 + i) for i in range(7)]
    q2 = [hm.g2_mul(hm.G2_GEN, 50 + i) for i in range(7)]
    a2 = np.asarray(cv2.encode_points(p2))[idx]
    b2 = np.asarray(cv2.encode_points(q2))[idx]
    want2 = [hm.g2_add(p, q) for p, q in zip(p2, q2)]
    assert cv2.decode_points(st.g2_add_rows(a2, b2)) == [want2[i] for i in idx]


@pytest.mark.slow
def test_scalar_mul_rows_match_host_at_the_chips_tile_height(monkeypatch, rng):
    """One ragged call of each scalar-mul program at the chip's height
    on the CPU backend (minutes): same answers as hostmath."""
    monkeypatch.setattr(st, "_on_tpu", lambda: True)
    N = st.tile_rows("g1_mul_tile") + 3
    pts = [hm.g1_mul(hm.G1_GEN, 3 + i) for i in range(5)]
    pts2 = [hm.g2_mul(hm.G2_GEN, 3 + i) for i in range(5)]
    ks = _scalars(rng, 5)
    idx = [i % 5 for i in range(N)]
    k = cv.encode_scalars(ks)[idx]
    got = st.g1_mul_rows(_g1_jac(pts)[idx], k)
    want = [hm.g1_mul(p, x) for p, x in zip(pts, ks)]
    assert cv.decode_points(got) == [want[i] for i in idx]
    got = st.g2_mul_rows(np.asarray(cv2.encode_points(pts2))[idx], k)
    want = [hm.g2_mul(p, x) for p, x in zip(pts2, ks)]
    assert cv2.decode_points(got) == [want[i] for i in idx]
    # a mix of G2 points and scalars in one ragged call at that height:
    # in the subgroup and outside it, the point at infinity, scalars
    # below r and above, rows whose accumulator meets a table entry
    twist = _twist_point_outside_g2()
    meets, negated = _meeting_scalars(
        _TWIST_ORDER, 1 << st.window_bits("g2_mul_tile"))
    mix = [(pts2[0], 0), (pts2[1], 1), (pts2[2], hm.R - 1), (pts2[3], hm.R),
           (pts2[4], (1 << 256) - 1), (None, ks[0]), (twist, meets),
           (twist, negated), (twist, ks[1]), (pts2[0], 1 << 252)]
    idx = [i % len(mix) for i in range(N)]
    got = st.g2_mul_rows(
        np.asarray(cv2.encode_points([p for p, _ in mix]))[idx],
        lb.ints_to_limbs([x for _, x in mix])[idx],
    )
    want = [hm._g2_mul_raw(p, x) if p else None for p, x in mix]
    assert cv2.decode_points(got) == [want[i] for i in idx]


# ---------------------------------------- the windowed scalar-mul tiles
#
# `g1_mul_tile` / `g2_mul_tile` walk a scalar in `st.window_bits` wide
# digits over a per-row table (`curve.windowed_mul`). One ragged call a
# group holds every case below; each case is a test of its own.

# any value 32 eight-bit limbs hold is a scalar to the tile (only
# `cv.encode_scalars` reduces): below r, and at or above it
_MUL_SCALARS = {
    "0": 0, "1": 1, "2": 2, "15": 15, "16": 16, "17": 17,
    "2^252": 1 << 252, "r-1": hm.R - 1, "r": hm.R, "r+1": hm.R + 1,
    "2r": 2 * hm.R, "5r": 5 * hm.R, "2^254": 1 << 254, "2^255": 1 << 255,
    "2^256-1": (1 << 256) - 1,
}
_MUL_CASES = list(_MUL_SCALARS) + [
    "acc_meets_entry", "acc_meets_negated_entry", "point_at_infinity"]
_TWIST_CASES = [
    "twist_acc_meets_entry", "twist_acc_meets_negated_entry",
    "twist_meets_mid_walk", "twist_scalar_above_its_order"]
_TWIST_ORDER = 10069  # a prime factor of G2's cofactor 2p - r


def _twist_point_outside_g2():
    """A point of the twist of order 10069: on the curve, not in G2
    (what `g2_mul_tile` meets before a subgroup check has run)."""
    x = (1, 0)
    while True:
        y = hm.fp2_sqrt(hm.fp2_add(hm.fp2_mul(hm.fp2_sqr(x), x), hm.B2))
        if y is not None:
            pt = hm._g2_mul_raw(
                (x, y), (2 * hm.P - hm.R) * hm.R // _TWIST_ORDER)
            if pt is not None:
                assert hm._g2_mul_raw(pt, _TWIST_ORDER) is None
                assert not hm.g2_in_subgroup(pt)
                return pt
        x = (x[0] + 1, 0)


def _meeting_scalars(order, W, limit=1 << 256):
    """Scalars under `limit` whose walk, for a point of order `order`,
    ends with the accumulator ON the last digit's table entry (the
    complete addition's P == Q row) and on its negative (P == -Q)."""
    for m in range(1, limit // order + 1):
        d = -m * order % W
        if d and m * order + 2 * d < limit:
            # W * prefix = m * order + d, last digit d: acc = d * P
            meets = m * order + 2 * d
            break
    for m in range(1, limit // order + 1):
        if m * order % W:
            # last digit d = m * order mod W, acc = (m * order - d) * P
            negated = m * order
            break
    return meets, negated


@functools.cache
def _windowed_mul_rows(group):
    """{case: (what the tile gave, what hostmath gives)} for `group`,
    from ONE ragged `run_rows` call of its scalar-mul program."""
    import random

    rng = random.Random(0x31)
    W = 1 << st.window_bits(f"{group}_mul_tile")
    assert W > 1
    if group == "g1":
        base = hm.g1_mul(hm.G1_GEN, rng.randrange(1, hm.R))
        host_mul, encode, decode, rows = (
            hm.g1_mul, _g1_jac, cv.decode_points, st.g1_mul_rows)
    else:
        base = hm.g2_mul(hm.G2_GEN, rng.randrange(1, hm.R))
        host_mul, decode, rows = (
            hm._g2_mul_raw, cv2.decode_points, st.g2_mul_rows)
        encode = lambda pts: np.asarray(cv2.encode_points(pts))
    cases = {name: (base, k) for name, k in _MUL_SCALARS.items()}
    meets, negated = _meeting_scalars(hm.R, W)
    cases["acc_meets_entry"] = (base, meets)
    cases["acc_meets_negated_entry"] = (base, negated)
    cases["point_at_infinity"] = (None, rng.randrange(1, hm.R))
    if group == "g2":
        twist = _twist_point_outside_g2()
        meets, negated = _meeting_scalars(_TWIST_ORDER, W)
        cases["twist_acc_meets_entry"] = (twist, meets)
        cases["twist_acc_meets_negated_entry"] = (twist, negated)
        # the meeting two digits before the end, then two more digits
        cases["twist_meets_mid_walk"] = (twist, meets * W * W + W + 5)
        cases["twist_scalar_above_its_order"] = (twist, rng.randrange(hm.R))
    # the rows built to meet do meet, by hostmath: before the last
    # addition the accumulator is W * (k // W) times the point
    for name, sign in (("acc_meets_entry", 1), ("acc_meets_negated_entry", -1)):
        for prefix in ("", "twist_") if group == "g2" else ("",):
            pt, k = cases[prefix + name]
            entry = host_mul(pt, k % W)
            assert entry is not None
            if sign < 0:
                entry = (entry[0], hm.fp2_neg(entry[1]) if group == "g2"
                         else -entry[1] % hm.P)
            assert host_mul(pt, k - k % W) == entry
    names = list(cases)
    got = decode(rows(
        encode([cases[n][0] for n in names]),
        lb.ints_to_limbs([cases[n][1] for n in names]),
    ))
    return {
        n: (g, host_mul(*cases[n]) if cases[n][0] else None)
        for n, g in zip(names, got)
    }


@pytest.mark.parametrize("case", _MUL_CASES)
@pytest.mark.parametrize("group", ["g1", "g2"])
def test_windowed_scalar_mul_rows_match_host(group, case):
    """Scalars at the window's edges (a zero digit, the first entry,
    the last, a carry into the next digit), every kind of value the
    limbs admit at or above r, the point at infinity, and rows whose
    accumulator meets the selected entry or its negative: the complete
    addition gives hostmath's answer in each."""
    got, want = _windowed_mul_rows(group)[case]
    assert got == want
    if case in ("0", "r", "2r", "5r", "acc_meets_negated_entry",
                "point_at_infinity"):
        assert got is None


@pytest.mark.parametrize("case", _TWIST_CASES)
def test_windowed_g2_mul_of_a_point_outside_the_subgroup(case):
    """A point of the twist of order 10069 (not in G2): small scalars
    put the accumulator on a table entry and on its negative, at the
    end of the walk and in the middle of it."""
    got, want = _windowed_mul_rows("g2")[case]
    assert got == want
    assert (got is None) == (case == "twist_acc_meets_negated_entry")


def test_every_windowed_case_is_a_test():
    assert set(_windowed_mul_rows("g1")) == set(_MUL_CASES)
    assert set(_windowed_mul_rows("g2")) == set(_MUL_CASES + _TWIST_CASES)


@pytest.mark.parametrize("program,bits", [
    ("g1_mul_tile", cv.MUL_WINDOW_BITS), ("g2_mul_tile", cv2.MUL_WINDOW_BITS),
    ("g1_msm3_tile", cv.WINDOW_BITS), ("g1_add_tile", 0)])
def test_health_names_the_form_that_ran(program, bits):
    """`ops.health()["device"]["programs"]` (the ledger's section of
    `Network.health()`) says which form of a program's arithmetic ran,
    beside the height of its tiles: `window_bits`."""
    from fabric_token_sdk_tpu.utils import devobs

    assert st.window_bits(program) == bits
    devobs.reset()
    pts = _g1_jac([hm.g1_mul(hm.G1_GEN, 9)])
    k = cv.encode_scalars([5])
    if program == "g1_mul_tile":
        st.g1_mul_rows(pts, k)
    elif program == "g2_mul_tile":
        st.g2_mul_rows(np.asarray(cv2.encode_points([hm.G2_GEN])), k)
    elif program == "g1_msm3_tile":
        table = cv.FixedBaseTable([hm.G1_GEN] * 3)
        st.g1_msm_rows(table.flat, np.repeat(k[:, None, :], 3, axis=1))
    else:
        st.g1_add_rows(pts, pts)
    e = devobs.health_section()["programs"][f"stages:{program}"]
    assert e["window_bits"] == bits
    assert e["tile_rows"] == st.tile_rows(program)
    devobs.reset()


def test_affine_to_jac_np_round_trips():
    pts = [hm.g1_mul(hm.G1_GEN, 7 + i) for i in range(4)]
    aff = np.asarray(pr.encode_g1(pts))
    jac = st.affine_to_jac_np(aff)
    assert jac.shape == (4, 3, aff.shape[-1])
    assert cv.decode_points(jac) == pts


def test_run_rows_empty_batch_raises():
    with pytest.raises(ValueError):
        st.run_rows(cv.add, np.zeros((0, 3, 32), np.int32),
                    np.zeros((0, 3, 32), np.int32))


def test_run_rows_counts_transfers(rng):
    before = mx.REGISTRY.counter("batch.tiled.transfers").value
    ps = _g1_jac([hm.g1_mul(hm.G1_GEN, 2 + i) for i in range(9)])
    st.g1_add_rows(ps, ps)  # 9 rows -> 2 tiles x 2 arrays = 4 transfers
    assert mx.REGISTRY.counter("batch.tiled.transfers").value - before == 4


_T = 8  # the CPU backend's stage-tile height (`st.tile_rows`)


@pytest.mark.parametrize("program", ["g1_add_tile", "g1_msm1_tile"])
@pytest.mark.parametrize("N", [1, _T - 1, _T, _T + 1, 2 * _T + 1])
def test_run_rows_edge_cases_match_host(rng, N, program):
    """The one walk at its edges — a single row, one short of a tile, a
    full tile, one over, two tiles and one — for a kernel of two row
    arrays and no consts (add) and one with consts (msm): the answers
    are hostmath's, and the ledger frame says what was dispatched as
    `tiles.padding_share` reads it."""
    from fabric_token_sdk_tpu.utils import devobs

    assert st.tile_rows(program) == _T
    devobs.reset()
    tiles_before = mx.REGISTRY.counter("stages.tiles").value
    pts = [hm.g1_mul(hm.G1_GEN, 5 + i) for i in range(3)]
    idx = [i % 3 for i in range(N)]
    if program == "g1_add_tile":
        a, b = _g1_jac(pts)[idx], _g1_jac(pts[::-1])[idx]
        got = cv.decode_points(st.g1_add_rows(a, b))
        want = [hm.g1_add(pts[i], pts[2 - i]) for i in idx]
    else:
        ks = _scalars(rng, 3)
        table = cv.FixedBaseTable(pts[:1])
        got = cv.decode_points(
            st.g1_msm_rows(table.flat, cv.encode_scalars(ks)[idx][:, None, :])
        )
        want = [hm.g1_mul(pts[0], ks[i]) for i in idx]
    assert got == want
    e = devobs.health_section()["programs"][f"stages:{program}"]
    pad = (-N) % _T
    assert (e["dispatches"], e["rows"], e["padded_rows"]) == (1, N, pad)
    assert e["tile_rows"] == _T
    ntiles = (N + pad) // _T
    assert mx.REGISTRY.counter("stages.tiles").value - tiles_before == ntiles
    assert e["waste_frac"] == round(pad / (N + pad), 4)
    devobs.reset()


@pytest.mark.parametrize("where", [
    "ops.stages:run_rows", "ops.pairing:pairing_product_staged",
    "services.network:Network", "api.driver:Driver.batch_verifier"])
def test_no_placement_parameter_on_the_way_to_a_tile(where):
    """One walk from a block to a tile: nothing on the way takes a
    mesh or a shard count (placement, when it comes, enters at
    `_run_span`'s host-to-device transfer)."""
    import functools
    import importlib
    import inspect

    module, _, attr = where.partition(":")
    subject = functools.reduce(
        getattr, attr.split("."),
        importlib.import_module("fabric_token_sdk_tpu." + module))
    assert not set(inspect.signature(subject).parameters) & {
        "dp", "mp", "mesh"}


def test_no_mesh_knob_or_module_is_left():
    import importlib
    import os
    import re

    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    # spelled apart so that this file passes its own scan
    knobs = ["FTS_" + k for k in ("MESH_", "DP_SHARDS", "SHARDED_PAIRING_FUSED",
                                  "BENCH_SCALING")]
    files = [os.path.join(root, f) for f in os.listdir(root)
             if f.endswith(".py")]
    for top in ("fabric_token_sdk_tpu", "cmd", "tests", "benchmark"):
        for d, _dirs, names in os.walk(os.path.join(root, top)):
            files += [os.path.join(d, f) for f in names if f.endswith(".py")]
    assert len(files) > 100
    # ... and no call site still passes one of the three keywords
    keyword = re.compile(r"\b(?:dp|mp|mesh)" + "=")
    hits = []
    for path in files:
        with open(path, encoding="utf-8") as fh:
            text = fh.read()
        hits += [(os.path.relpath(path, root), k) for k in knobs if k in text]
        hits += [(os.path.relpath(path, root), m) for m in keyword.findall(text)]
    assert hits == []
    with pytest.raises(ImportError):
        importlib.import_module("fabric_token_sdk_tpu." + "parallel")


def test_gt_is_one_host():
    one = tw.fp12_one_np()
    not_one = tw.encode_fp12([((2, 0), (0, 0), (0, 0), (0, 0), (0, 0), (0, 0))])[0]
    got = pr.gt_is_one_host(np.stack([one, not_one]))
    assert got.tolist() == [True, False]
    assert pr.gt_is_one_host(np.zeros((0, 6, 2, 32), np.int32)).tolist() == []


@pytest.mark.slow
def test_g1_msm_rows_one_and_two_bases(rng):
    for nb in (1, 2):
        bases = [hm.g1_mul(hm.G1_GEN, 17 + i) for i in range(nb)]
        table = cv.FixedBaseTable(bases)
        rows = [_scalars(rng, nb) for _ in range(3)]
        got = st.g1_msm_rows(
            table.flat, np.stack([cv.encode_scalars(r) for r in rows])
        )
        assert cv.decode_points(got) == [hm.g1_multiexp(bases, r) for r in rows]


@pytest.mark.slow
def test_g2_stage_rows_match_host(rng):
    pts = [hm.g2_mul(hm.G2_GEN, 3 + i) for i in range(5)]
    ks = _scalars(rng, 5)
    jac = np.asarray(cv2.encode_points(pts))
    got = st.g2_mul_rows(jac, cv.encode_scalars(ks))
    assert cv2.decode_points(got) == [hm.g2_mul(p, k) for p, k in zip(pts, ks)]

    qs = [hm.g2_mul(hm.G2_GEN, 50 + i) for i in range(5)]
    got = st.g2_add_rows(jac, np.asarray(cv2.encode_points(qs)))
    assert cv2.decode_points(got) == [hm.g2_add(p, q) for p, q in zip(pts, qs)]

    # tree sum over k=3 terms per row
    terms = np.stack(
        [np.asarray(cv2.encode_points([p, q, hm.G2_GEN]))
         for p, q in zip(pts, qs)]
    )
    got = st.g2_tree_sum_rows(terms)
    assert cv2.decode_points(got) == [
        hm.g2_add(hm.g2_add(p, q), hm.G2_GEN) for p, q in zip(pts, qs)
    ]

    aff = st.g2_to_affine_rows(jac)
    assert aff.shape == (5, 2, 2, jac.shape[-1])
    # affine coordinates decode to the same host points
    coords = tw.decode_fp2(aff.reshape(-1, 2, jac.shape[-1]))
    decoded = [(coords[2 * i], coords[2 * i + 1]) for i in range(5)]
    assert decoded == pts
