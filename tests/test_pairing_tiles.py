"""The staged pairing product at another Miller or final-exp tile height.

The rows one `miller_tile` dispatch holds come from
`stages.tile_rows("miller_tile")`, the rows one `fexp_tile` dispatch
(row product + final exponentiation) holds from
`stages.tile_rows("fexp_tile")`: the backend's heights, as for the
stage programs. Here they are patched on the CPU backend: the walks,
their padding, the ledger entries, the tile counters and the shapes
`ops/warmup.py` compiles ahead must all follow the one function, and
the GT rows must not depend on a height.
"""

import numpy as np
import pytest

from fabric_token_sdk_tpu.crypto import hostmath as hm
from fabric_token_sdk_tpu.ops import limbs as lb, pairing as pr, stages as st, \
    tower as tw, warmup as wu
from fabric_token_sdk_tpu.utils import devobs, metrics as mx

_T = 32  # a height that is neither the host's nor the chip's


@pytest.mark.parametrize("backend", ["host", "tpu"])
def test_miller_tile_rows_come_from_the_backend(monkeypatch, backend):
    monkeypatch.setattr(st, "_on_tpu", lambda: backend == "tpu")
    # 128: the on-chip sweep's height (PERF.md section 6, PR 29)
    assert st.tile_rows("miller_tile") == (128 if backend == "tpu" else 16)
    # 128: the on-chip sweep's height (PERF.md section 6, PR 35)
    assert st.tile_rows("fexp_tile") == (128 if backend == "tpu" else 8)
    # the heights are the function's alone
    assert not hasattr(pr, "MILLER_TILE")
    assert not hasattr(pr, "FEXP_TILE")


@pytest.mark.parametrize("height", [16, _T, "tpu"])
def test_warmup_compiles_the_miller_shape_the_walk_dispatches(
    monkeypatch, height
):
    if height == "tpu":
        monkeypatch.setattr(st, "_on_tpu", lambda: True)
    else:
        monkeypatch.setattr(st, "_HOST_MILLER_ROWS", height)
    T = st.tile_rows("miller_tile")
    shapes = {n: s for n, _fn, s in wu.pairing_programs()}
    L = lb.NLIMBS
    assert shapes["miller_tile"] == ((T, 2, L), (T, 2, 2, L))
    F = st.tile_rows("fexp_tile")
    assert shapes["final_exp_tile"] == ((F, 6, 2, L),)
    assert shapes["gt_product_k2_tile"][0][0] == F


def _legs(n):
    """n (P, Q) pairs from a small pool of host points."""
    ps = [hm.g1_mul(hm.G1_GEN, 3 + i) for i in range(5)]
    qs = [hm.g2_mul(hm.G2_GEN, 7 + i) for i in range(3)]
    return [(ps[i % 5], qs[i % 3]) for i in range(n)]


# Stand-ins for the three tile programs, row-wise as they are: a row's
# output depends on that row's inputs alone, so a row the walk
# misplaces, pads over or forgets to mask shows in the result. They
# compile in milliseconds; the real programs cost the CPU backend 65 s
# (Miller, each height) and 177 s (final exp) to compile and 4-8 s a
# tile to run, which is what `-m slow` is for.
_SPREAD = np.arange(1, 13, dtype=np.int32).reshape(6, 2, 1)


def _toy_miller(P, Q):
    row = P[:, 0] * 3 + P[:, 1] + Q[:, 1, 1] * 5 + Q[:, 0, 0]
    return row[:, None, None, :] * _SPREAD


def _toy_product(f):
    return f.sum(axis=1)


def _toy_final_exp(f):
    return f * 7 + 1


def _toy_reference(Ps, Qs, mask):
    B, K = Ps.shape[:2]
    f = _toy_miller(Ps.reshape(B * K, 2, -1), Qs.reshape(B * K, 2, 2, -1))
    if mask is not None:
        f[mask.reshape(-1)] = tw.fp12_one_np()
    return _toy_final_exp(_toy_product(f.reshape(B, K, 6, 2, -1)))


# (rows B, legs per row K): N = B * K flat Miller rows
@pytest.mark.parametrize("masked", [False, True], ids=["all", "inf_mask"])
@pytest.mark.parametrize(
    "B,K", [(5, 7), (32, 2)], ids=["N=T+3", "N=2T"])
@pytest.mark.parametrize(
    "kernels", ["toy", pytest.param("real", marks=pytest.mark.slow)])
def test_staged_product_does_not_depend_on_the_miller_height(
    monkeypatch, kernels, B, K, masked
):
    """The walk at height 32 against the walk at 16 and against the
    reference (hostmath for the real programs), row for row; the ledger
    entry and the tile counter read the height that ran."""
    N = B * K
    assert N in (_T + 3, 2 * _T)
    legs = _legs(N)
    Ps = pr.encode_g1([p for p, _ in legs]).reshape(B, K, 2, -1)
    Qs = pr.encode_g2([q for _, q in legs]).reshape(B, K, 2, 2, -1)
    mask = None
    if masked:
        mask = np.zeros((B, K), dtype=bool)
        mask.reshape(-1)[[0, N // 2, N - 1]] = True
    if kernels == "toy":
        monkeypatch.setattr(pr, "miller_loop", _toy_miller)
        monkeypatch.setattr(pr, "_product_rows", _toy_product)
        monkeypatch.setattr(pr, "final_exp", _toy_final_exp)

    at16 = pr.pairing_product_staged(Ps, Qs, inf_mask=mask)

    monkeypatch.setattr(st, "_HOST_MILLER_ROWS", _T)
    tiles = mx.counter("pairing.staged.miller_tiles")
    frame = (devobs.current_plane(), "miller_tile")
    t0, e0 = tiles.value, dict(devobs.snapshot().get(frame, {}))
    got = pr.pairing_product_staged(Ps, Qs, inf_mask=mask)
    e1 = devobs.snapshot()[frame]

    assert np.array_equal(got, at16)
    if kernels == "toy":
        assert np.array_equal(got, _toy_reference(Ps, Qs, mask))
    else:
        keep = np.ones(N, bool) if mask is None else ~mask.reshape(-1)
        assert tw.decode_fp12(got) == [
            hm.pairing_product(
                [legs[b * K + k] for k in range(K) if keep[b * K + k]])
            for b in range(B)
        ]
    assert tiles.value - t0 == -(-N // _T)
    assert e1["tile_rows"] == _T
    assert e1["dispatches"] - e0.get("dispatches", 0) == 1
    assert e1["rows"] - e0.get("rows", 0) == N
    assert e1["padded_rows"] - e0.get("padded_rows", 0) == (-N) % _T


def _fexp_height(monkeypatch, height):
    """Patch the final-exp height: a number for the CPU backend's, or
    "tpu" for the chip's own (the backend patched, so the Miller walk
    runs at the chip's height too)."""
    if height == "tpu":
        monkeypatch.setattr(st, "_on_tpu", lambda: True)
    else:
        monkeypatch.setattr(st, "_HOST_FEXP_ROWS", height)
    return st.tile_rows("fexp_tile")


@pytest.mark.parametrize("K", [2, 4], ids=["K=2", "K=4"])
# rows of the final-exp call: B = m * T + c for the height T
@pytest.mark.parametrize(
    "m,c", [(0, 1), (1, -1), (1, 0), (1, 1), (2, 3)],
    ids=["B=1", "B=T-1", "B=T", "B=T+1", "B=2T+3"])
@pytest.mark.parametrize("height", [8, _T, "tpu"])
def test_final_exp_walk_follows_the_one_height(monkeypatch, height, m, c, K):
    """The final-exp walk at the CPU backend's height, at one that is
    nobody's and at the chip's, on the row-wise stand-ins: the
    reference's rows in order whatever the padding; the ledger entry
    `verify:fexp_tile` and the tile counter read the height that ran;
    the three shapes `warmup.pairing_programs()` registers are the
    only shapes the walk hands the three programs."""
    T = _fexp_height(monkeypatch, height)
    assert T == (128 if height == "tpu" else height)
    B = m * T + c
    legs = _legs(B * K)
    Ps = pr.encode_g1([p for p, _ in legs]).reshape(B, K, 2, -1)
    Qs = pr.encode_g2([q for _, q in legs]).reshape(B, K, 2, 2, -1)
    mask = np.zeros((B, K), dtype=bool)
    mask.reshape(-1)[[0, B * K - 1]] = True

    seen = {"miller_tile": set(), f"gt_product_k{K}_tile": set(),
            "final_exp_tile": set()}

    def logged(name, fn):
        def run(*args):
            seen[name].add(tuple(tuple(a.shape) for a in args))
            return fn(*args)
        return run

    monkeypatch.setattr(pr, "miller_loop", logged("miller_tile", _toy_miller))
    monkeypatch.setattr(
        pr, "_product_rows", logged(f"gt_product_k{K}_tile", _toy_product))
    monkeypatch.setattr(
        pr, "final_exp", logged("final_exp_tile", _toy_final_exp))

    tiles = mx.counter("pairing.staged.fexp_tiles")
    frame = (devobs.current_plane(), "fexp_tile")
    t0, e0 = tiles.value, dict(devobs.snapshot().get(frame, {}))
    got = pr.pairing_product_staged(Ps, Qs, inf_mask=mask)
    e1 = devobs.snapshot()[frame]

    assert np.array_equal(got, _toy_reference(Ps, Qs, mask))
    assert tiles.value - t0 == -(-B // T)
    assert e1["tile_rows"] == T
    assert e1["dispatches"] - e0.get("dispatches", 0) == 1
    assert e1["rows"] - e0.get("rows", 0) == B
    assert e1["padded_rows"] - e0.get("padded_rows", 0) == (-B) % T
    registered = {n: {tuple(s)} for n, _fn, s in wu.pairing_programs()}
    assert seen == {n: registered[n] for n in seen}
