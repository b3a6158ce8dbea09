"""Batched (TPU) Schnorr signature verification — the sign-side twin of
the batched proof planes (`crypto/batch.py` / `crypto/batch_prove.py`).

Signatures were the LAST per-tx EC workload still executed
scalar-at-a-time on the host: every owner/issuer/auditor check costs two
pure-Python `g1_mul` calls (`crypto/sign.py`). Here a whole block's
`pk`-kind signature obligations verify as ONE flat-row pass over the
existing stage tiles:

    com_i = g^{z_i} · pk_i^{-c_i}

i.e. fixed-base msm for `g^z` (the 1-base `g1_msm1_tile`, same program
the membership verifier's `P^{z_bf}` term rides), variable-base
`g1_mul` for `pk^c`, and the Jacobian sub tile — the composition
`BatchedWFVerifier` runs for its sigma commitments, so the plane
adds ZERO new XLA program shapes and the post-warmup zero-cache-miss
guarantee extends to signatures. The Fiat-Shamir re-hash (challenge
rebind per row) stays on host, like every other batched verifier.

Verdict contract (mirrors the proof plane): per-row True/False for rows
whose signature blob parsed, None for rows the collector could not even
parse — those re-verify on host, which reports the precise error. For
parsed rows the device verdict is mathematically identical to
`PublicKey.verify` (host `g1_mul` reduces scalars mod R exactly like the
canonical limb encoding, and the response equation is shared verbatim —
see `sign.response_commitment`), differential-pinned in
tests/test_batch_sign.py including bit-flipped `c`/`z`/message/pk rows.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from . import hostmath as hm, sign
from .batch import _spanned
from .serialization import loads
from ..ops import curve as cv, stages as st
from ..utils import devobs, metrics as mx, resilience


class BatchedSchnorrVerifier:
    """Verifies B long-term Schnorr signatures via the stage tiles.

    Rows are `(pk_point, message, sig_raw)` — the public-key POINT (from
    the identity cache, `drivers/identity.py:public_key`), the exact
    message bytes the host path would verify, and the raw signature
    blob. Unlike the proof verifiers there is no shape grouping: Schnorr
    rows are shape-uniform by construction, so one call covers a whole
    block regardless of how many txs/records contributed obligations.
    """

    def __init__(self):
        # windowed multiples of the generator (process-wide lru cache —
        # every verifier shares one table build); the 1-base msm PROGRAM
        # shape already exists (warmup's g1_msm1_tile) — tables are
        # runtime arguments, not program keys
        self.table = cv.generator_table(1)

    @_spanned("batch.sign.verify")
    def verify(
        self, rows: Sequence[Tuple[object, bytes, bytes]]
    ) -> List[Optional[bool]]:
        """-> per-row verdicts: True/False device verdict, None when the
        signature blob did not parse (host re-verify). Raises only on
        device-plane failures — the caller degrades those to host."""
        B = len(rows)
        if B == 0:
            return []
        mx.counter("batch.sign.batches").inc()
        parsed: List[Optional[Tuple[int, int]]] = []
        with devobs.glue("parse"):
            for _pk, _msg, sig_raw in rows:
                try:
                    d = loads(sig_raw)
                    chal, resp = d["c"], d["z"]
                    if (
                        not isinstance(chal, int) or isinstance(chal, bool)
                        or not isinstance(resp, int) or isinstance(resp, bool)
                    ):
                        raise ValueError("non-integer signature fields")
                    parsed.append((chal, resp))
                except Exception:
                    parsed.append(None)  # host path reports the precise error
        live = [i for i in range(B) if parsed[i] is not None]
        verdicts: List[Optional[bool]] = [None] * B
        if not live:
            return verdicts
        # flat rows: com = table^z - pk^c over the msm/mul/sub tiles
        with devobs.glue("encode"):
            resp_np = cv.encode_scalars(
                [parsed[i][1] for i in live]
            )[:, None, :]
            chal_np = cv.encode_scalars([parsed[i][0] for i in live])
            pk_np = np.stack([cv.encode_point(rows[i][0]) for i in live])
        coms = st.g1_sub_rows(
            st.g1_msm_rows(self.table.flat, resp_np),
            st.g1_mul_rows(pk_np, chal_np),
        )
        with devobs.glue("decode"):
            com_pts = cv.decode_points(coms)
        # counted on COMPLETION only (PR-9 precedent): a device failure
        # above falls to host and must never report as device-verified —
        # nor may an ABANDONED bounded worker that completes late (its
        # rows were already counted as host fallbacks by the caller)
        if not resilience.call_abandoned():
            mx.counter("batch.sign.rows").inc(len(live))
        with devobs.glue("challenge"):
            for j, i in enumerate(live):
                pk_point, message, _sig = rows[i]
                verdicts[i] = (
                    sign.challenge(pk_point, com_pts[j], message)
                    == parsed[i][0]
                )
        return verdicts
