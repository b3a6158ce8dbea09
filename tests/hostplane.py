"""Exact host stand-ins for the tile kernels of the verify plane.

A two-output transfer on the proof plane runs the pairing programs, and
on the CPU backend those cost minutes to compile (`final_exp` alone
177 s) and seconds a tile to run: `-m slow` territory. These stand-ins
take the kernels' places *under* the walks (`stages.run_rows`,
`pairing.pairing_product_staged`), as `tests/test_pairing_tiles.py`'s toy
kernels do, but they are exact: every row is computed by `hostmath` from
that row's inputs alone, so the verdicts of a verifier above them are
real, a row the glue misplaces shows in a verdict, and the padding, the
dispatch ledger and the counters are the walks' own. Nothing compiles.

    def test_x(monkeypatch):
        hostplane.install(monkeypatch)              # the backend's heights
        hostplane.install(monkeypatch, chip=True)   # the chip's (128 rows)
        hostplane.install(monkeypatch, stage=False) # the pairing kernels alone

`record(monkeypatch)` notes every array handed to a `st.*_rows` function
and to `pairing_product_staged`, in call order.
"""

import hashlib

import numpy as np

from fabric_token_sdk_tpu.crypto import hostmath as hm
from fabric_token_sdk_tpu.ops import curve as cv, curve2 as cv2, limbs as lb, \
    pairing as pr, stages as st, tower as tw


def _rowwise(fn):
    """`fn(one row of each argument) -> one row`, over a tile; a row seen
    before (padding repeats one row up to the tile's height) is not
    computed again."""
    seen = {}

    def tile(*arrays):
        arrays = [np.asarray(a) for a in arrays]
        out = []
        for rows in zip(*arrays):
            key = b"".join(r.tobytes() for r in rows)
            if key not in seen:
                seen[key] = fn(*rows)
            out.append(seen[key])
        return np.stack(out)

    return tile


def _g1(row):
    return cv.decode_point(row)


def _g2(row):
    return cv2.decode_points(row[None])[0]


def _g1_affine(row):
    return _g1(st.affine_to_jac_np(row))


def _g2_affine(row):
    one = tw.encode_fp2([(1, 0)])
    return _g2(np.concatenate([row, one]))


def _msm_tile(table_flat, scalars):
    # Table[b, 0, 1] is base b itself (`curve.FixedBaseTable`)
    table = np.asarray(table_flat)
    bases = [_g1(table[b, 1].reshape(3, lb.NLIMBS))
             for b in range(0, table.shape[0], cv.DIGITS_PER_SCALAR)]
    return _rowwise(lambda ks: cv.encode_point(
        hm.g1_multiexp(bases, [lb.limbs_to_int(k) for k in ks])))(scalars)


def kernels() -> dict:
    """module attribute -> its exact host stand-in."""
    stage = {
        "_g1_msm_tile": _msm_tile,
        "_g1_mul_tile": _rowwise(
            lambda p, k: cv.encode_point(hm.g1_mul(_g1(p), lb.limbs_to_int(k)))),
        "_g1_add_tile": _rowwise(
            lambda a, b: cv.encode_point(hm.g1_add(_g1(a), _g1(b)))),
        "_g1_sub_tile": _rowwise(
            lambda a, b: cv.encode_point(hm.g1_add(_g1(a), hm.g1_neg(_g1(b))))),
        "_g1_to_affine_tile": _rowwise(lambda p: pr.encode_g1([_g1(p)])[0]),
        "_g2_mul_tile": _rowwise(
            lambda p, k: cv2.encode_points([hm.g2_mul(_g2(p), lb.limbs_to_int(k))])[0]),
        "_g2_add_tile": _rowwise(
            lambda a, b: cv2.encode_points([hm.g2_add(_g2(a), _g2(b))])[0]),
        "_g2_to_affine_tile": _rowwise(lambda p: pr.encode_g2([_g2(p)])[0]),
    }
    for name, fn in stage.items():
        fn.__name__ = name[1:]  # `stages._program_of` falls back on it
    pairing = {
        # a leg's whole pairing where the Miller value would be: GT is a
        # group, so the rows' product is the product the final
        # exponentiation of the Miller values' product gives
        "miller_loop": _rowwise(lambda p, q: tw.encode_fp12(
            [hm.pairing(_g1_affine(p), _g2_affine(q))])[0]),
        "_product_rows": _rowwise(lambda f: tw.encode_fp12(
            [_gt_product(tw.decode_fp12(f))])[0]),
        "final_exp": np.asarray,
    }
    return {"stage": stage, "pairing": pairing}


def _gt_product(values):
    acc = hm.FP12_ONE
    for v in values:
        acc = hm.fp12_mul(acc, v)
    return acc


def install(monkeypatch, stage: bool = True, chip: bool = False) -> None:
    """Put the stand-ins under the two walks for one test. `stage=False`
    leaves the stage tiles to the backend's real programs (seconds a
    tile on the CPU) and stands in for the three pairing kernels alone.
    `chip`: the walks pad to the chip's tile heights (128 rows), which
    no real program could be compiled for here."""
    ks = kernels()
    if stage:
        for name, fn in ks["stage"].items():
            monkeypatch.setattr(st, name, fn)
        # kernel identity -> program name is cached: rebuilt with the
        # stand-ins now, and with the real kernels after the test
        monkeypatch.setattr(st, "_PROGRAM_NAMES", None)
    for name, fn in ks["pairing"].items():
        monkeypatch.setattr(pr, name, fn)
    if chip:
        monkeypatch.setattr(st, "_on_tpu", lambda: True)


_ROW_FUNCTIONS = ("g1_msm_rows", "g1_mul_rows", "g1_sub_rows",
                  "g1_to_affine_rows", "g2_mul_rows", "g2_add_rows",
                  "g2_to_affine_rows")


def record(monkeypatch) -> list:
    """-> a list that fills with `(function, [row arrays handed to it])`,
    in call order: every `st.*_rows` call of a verifier and every
    `pairing_product_staged` call (the inf_mask, where given, last)."""
    calls = []

    def noted(owner, name):
        inner = getattr(owner, name)

        def wrapper(*args, **kw):
            # (an msm's fixed-base table is a constant of the parameters)
            given = [*args[name == "g1_msm_rows":],
                     *(v for v in kw.values() if v is not None)]
            calls.append((name, [np.array(a) for a in given]))
            return inner(*args, **kw)

        monkeypatch.setattr(owner, name, wrapper)

    for name in _ROW_FUNCTIONS:
        noted(st, name)
    noted(pr, "pairing_product_staged")
    return calls


def digest(calls: list) -> list:
    """What was handed over, call by call: `[function, shapes, SHA-256 of
    the arrays' bytes]`."""
    out = []
    for name, arrays in calls:
        h = hashlib.sha256()
        for a in arrays:
            h.update(str((a.shape, a.dtype.str)).encode())
            h.update(np.ascontiguousarray(a).tobytes())
        out.append([name, [list(a.shape) for a in arrays], h.hexdigest()])
    return out
