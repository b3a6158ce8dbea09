"""Pedersen vector commitments (host scalar path + batched TPU path).

Reference: `crypto/common/zkproof.go` ComputePedersenCommitment and the
token commitment computation in `crypto/token/token.go:64-76` (token data =
commit(hash(type), value; bf) over PedParams).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from . import hostmath as hm
from ..ops import curve as cv, limbs as lb, stages as st


def commit(openings: Sequence[int], bases: Sequence, curve=None):
    """Host: com = prod bases[i]^openings[i]."""
    if len(openings) != len(bases):
        raise ValueError(f"pedersen commit: {len(openings)} openings vs {len(bases)} bases")
    return hm.g1_multiexp(list(bases), [o % hm.R for o in openings])


class BatchedPedersen:
    """Batched fixed-base committer over the compile-once stage tiles.

    B commitments over the same bases run as `stages.tile_rows` slabs of the
    canonical `g1_msm` tile (`ops/stages.py`), so the program count is
    independent of B — this is the commit engine of the batched transfer
    prover (`crypto/batch_prove.py`: WF announcements, digit
    commitments, equality announcements are all Pedersen rows here)."""

    def __init__(self, bases: Sequence):
        self.bases = list(bases)
        self.table = cv.FixedBaseTable(self.bases)

    def commit_rows(self, scalars: np.ndarray) -> np.ndarray:
        """Canonical limb scalars (N, nbases, NLIMBS) -> (N, 3, NLIMBS)
        Jacobian numpy, via the shape-invariant msm stage tile."""
        return st.g1_msm_rows(self.table.flat, scalars)

    def commit_ints(self, openings_rows: Sequence[Sequence[int]]):
        """Host int rows -> (host points, device Jacobian): one flat limb
        encode, one tiled msm pass, one host decode."""
        rows = list(openings_rows)
        flat = cv.encode_scalars([s for row in rows for s in row])
        jac = self.commit_rows(
            flat.reshape(len(rows), len(self.bases), lb.NLIMBS)
        )
        return cv.decode_points(jac), jac

    def commit_batch(self, openings_rows: Sequence[Sequence[int]]):
        """rows of per-base openings -> list of host G1 points."""
        return self.commit_ints(openings_rows)[0]

    def commit_device(self, scalars):
        """Fused device path: scalars (..., nbases, NLIMBS) canonical ->
        points. NOTE: compiles one program PER leading shape — prefer
        `commit_rows` (stage tiles) anywhere the shape varies."""
        return self.table.msm(scalars)


def token_commitment(token_type: str, value: int, bf: int, ped_params: Sequence):
    """Commitment to (hash(type), value; blinding) — TokenData.

    Reference: token/token.go:68-69.
    """
    return commit([hm.hash_to_zr(token_type.encode()), value, bf], ped_params)
