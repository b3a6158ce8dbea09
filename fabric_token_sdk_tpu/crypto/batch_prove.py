"""Batched (TPU) transfer-proof GENERATION over the compile-once stage tiles.

`crypto/batch.py` made verification batch-parallel; this module is the
prove-side twin (SURVEY layer 7 promises batch-parallel *prove and*
verify; reference prove side: `crypto/transfer/sender.go`,
`crypto/range/proof.go`). A `BatchedTransferProver` takes N same-shape
`(n_in, n_out)` witness sets and generates N transfer proofs in ONE pass:

* commit phase on device — all Pedersen commitments, Schnorr announcement
  points, PS-signature randomization/obfuscation, and the membership
  GT pre-commitments run as batched fixed-base MSM / variable-base
  scalar-mul / pairing stage calls (`ops/stages.py`, `ops/pairing.py`);
* Fiat-Shamir + responses on host — challenge hashing and the Zr response
  arithmetic stay in python, shared VERBATIM with the host provers via
  the `draw`/`finish` split in `wellformedness.py` / `rangeproof.py` /
  `sigproof.py`.

The emitted proofs are byte-compatible with the host `TransferProver`
output: the unchanged host `TransferVerifier` (and the batched
`BatchedTransferVerifier`) accepts them, and tampering is rejected
identically — device proving may only accelerate, never change,
accept/reject.

Program-set discipline: every device step is a canonical `stages.tile_rows` stage
tile or the staged K=2 pairing product, all of which `ops/warmup.py`
precompiles — batch-proving a NEW transfer shape compiles zero XLA
programs post-warmup (see `tests/test_compile_budget.py`).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from . import hostmath as hm, pssign, rangeproof, sigproof, wellformedness as wf
from .pedersen import BatchedPedersen
from .setup import PublicParams
from .transfer import TransferProof, _skip_range
from ..ops import curve as cv, curve2 as cv2, limbs as lb, pairing as pr, \
    stages as st, tower as tw
from ..utils import devobs
from ..utils import metrics as mx, resilience


class BatchedTransferProver:
    """Generates whole batches of same-shape zkatdlog transfer proofs.

    One instance caches the fixed-base window tables (Pedersen 3-base and
    2-base, PedGen) and the encoded G2 public keys — constructing it is
    the expensive part; `prove` calls are cheap and reusable across
    shapes and batch sizes (the stage tiles are shape-invariant).
    """

    def __init__(self, pp: PublicParams):
        self.pp = pp
        self.ped3 = BatchedPedersen(pp.ped_params)
        self.ped2 = BatchedPedersen(pp.ped_params[:2])
        rp = pp.range_params
        self.pedP = BatchedPedersen([pp.ped_gen]) if rp else None
        if rp is not None:
            self.pk_np = np.asarray(cv2.encode_points(rp.sign_pk))  # (3,3,2,L)
            self.Q_np = np.asarray(pr.encode_g2([rp.Q]))[0]  # (2,2,L)
            # signed-set signature points, encoded once per digit value
            self.sig_R_np = np.stack(
                [cv.encode_point(s.R) for s in rp.signed_values]
            )
            self.sig_S_np = np.stack(
                [cv.encode_point(s.S) for s in rp.signed_values]
            )

    # ------------------------------------------------------------ helpers

    @staticmethod
    def _check_shapes(reqs) -> Tuple[int, int]:
        shapes = {(len(r[2]), len(r[3])) for r in reqs}
        if len(shapes) != 1:
            raise ValueError(
                f"batched prove needs one uniform (n_in, n_out) shape, got {sorted(shapes)}"
            )
        (n_in, n_out), = shapes
        if n_in == 0 or n_out == 0:
            raise ValueError("batched prove: empty inputs or outputs")
        return n_in, n_out

    # ------------------------------------------------------------ WF phase

    def _prove_wf(self, reqs, n_in: int, n_out: int, rng) -> List[bytes]:
        provers = [
            wf.TransferWFProver(
                wf.TransferWFWitness(
                    token_type=iw[0].token_type,
                    in_values=[w.value for w in iw],
                    in_bfs=[w.bf for w in iw],
                    out_values=[w.value for w in ow],
                    out_bfs=[w.bf for w in ow],
                ),
                self.pp.ped_params, inputs, outputs, rng,
            )
            for iw, ow, inputs, outputs in reqs
        ]
        draws = [p.draw() for p in provers]
        n = n_in + n_out + 2
        rows: List[List[int]] = []
        for d in draws:
            rows += d.commit_rows(n_in, n_out)
        coms, _ = self.ped3.commit_ints(rows)
        out = []
        for i, (p, d) in enumerate(zip(provers, draws)):
            row = coms[i * n : (i + 1) * n]
            chal = wf.challenge_transfer_wf(
                row[:n_in], row[n_in], row[n_in + 1 : -1], row[-1],
                p.inputs, p.outputs,
            )
            out.append(p.finish(d, chal))
        return out

    # ------------------------------------------------------------ range phase

    def _prove_range(self, reqs, n_out: int, rng) -> List[bytes]:
        pp, rp = self.pp, self.pp.range_params
        if rp is None:
            raise ValueError("public params carry no range-proof parameters")
        base, exponent = rp.base, rp.exponent
        B = len(reqs)
        provers = [
            rangeproof.RangeProver(
                [rangeproof.TokenWitness(w.token_type, w.value, w.bf) for w in ow],
                outputs, rp.signed_values, base, exponent,
                pp.ped_params, rp.sign_pk, pp.ped_gen, rp.Q, rng,
            )
            for _, ow, _, outputs in reqs
        ]
        draws = [p.draw() for p in provers]  # raises on out-of-range values
        M = B * n_out * exponent  # flattened (tx, output, digit) rows
        L = lb.NLIMBS

        # flat per-digit views, in (tx, output, digit) order
        digits = [
            d.digits[k][i]
            for d in draws for k in range(n_out) for i in range(exponent)
        ]
        digit_bfs = [
            d.digit_bfs[k][i]
            for d in draws for k in range(n_out) for i in range(exponent)
        ]
        mems = [
            d.mem[k][i]
            for d in draws for k in range(n_out) for i in range(exponent)
        ]

        # ---- ped[:2] fixed-base MSMs, one call: digit commitments
        # (d, bf), membership value announcements (rho_v, rho_cb), and
        # equality digit-aggregate announcements (rho_v, rho_cb)
        rows2 = (
            [[digits[j], digit_bfs[j]] for j in range(M)]
            + [[m.rho_v, m.rho_cb] for m in mems]
        )
        for d in draws:
            rows2 += d.equality_value_rows()
        coms2, _ = self.ped2.commit_ints(rows2)
        digit_coms = coms2[:M]
        mem_com_vals = coms2[M : 2 * M]
        eq_com_values = coms2[2 * M :]  # B*n_out

        # ---- ped 3-base MSM: per-token equality announcements
        rows3: List[List[int]] = []
        for d in draws:
            rows3 += d.equality_token_rows()
        eq_com_tokens, _ = self.ped3.commit_ints(rows3)

        # ---- signature randomization: (R^r, S^r) variable-base, then
        # obfuscation S'' = S^r + P^sig_bf (fixed-base + Jacobian add)
        r_enc = cv.encode_scalars([m.r for m in mems])
        sig_R = self.sig_R_np[digits]  # (M, 3, L) gather by digit value
        sig_S = self.sig_S_np[digits]
        rnd = st.g1_mul_rows(
            np.concatenate([sig_R, sig_S]), np.concatenate([r_enc, r_enc]),
        )
        rnd_R_jac, rnd_S_jac = rnd[:M], rnd[M:]
        pbf_scal = cv.encode_scalars(
            [m.sig_bf for m in mems] + [m.rho_bf for m in mems]
        )
        # decode-free commit path: P^sig_bf feeds the Jacobian add and
        # P^rho_bf is decoded once below with the other transcript points
        pbf_jac = self.pedP.commit_rows(pbf_scal[:, None, :])
        obf_S_jac = st.g1_add_rows(rnd_S_jac, pbf_jac[:M])

        # one host decode pass for everything that enters a transcript
        host_pts = cv.decode_points(
            np.concatenate([rnd_R_jac, obf_S_jac, pbf_jac[M:]])
        )
        rnd_R, obf_S, p_rho = (
            host_pts[:M], host_pts[M : 2 * M], host_pts[2 * M :]
        )

        # ---- GT pre-commitments: t = PK1^rho_v + PK2^rho_h in G2, then
        # com_gt = e(R', t) * e(P^rho_bf, Q) via the staged K=2 product
        g2_bases = np.concatenate(
            [
                np.broadcast_to(self.pk_np[1], (M,) + self.pk_np.shape[1:]),
                np.broadcast_to(self.pk_np[2], (M,) + self.pk_np.shape[1:]),
            ]
        )
        g2_scal = cv.encode_scalars(
            [m.rho_v for m in mems] + [m.rho_h for m in mems]
        )
        terms = st.g2_mul_rows(g2_bases, g2_scal)
        t_aff = st.g2_to_affine_rows(st.g2_add_rows(terms[:M], terms[M:]))
        Ps = np.stack(
            [np.asarray(pr.encode_g1(rnd_R)), np.asarray(pr.encode_g1(p_rho))],
            axis=1,
        )  # (M, 2, 2, L)
        Qs = np.stack(
            [t_aff, np.broadcast_to(self.Q_np, t_aff.shape)], axis=1
        )  # (M, 2, 2, 2, L)
        gts = tw.decode_fp12(pr.pairing_product_staged(Ps, Qs))

        # ---- host Fiat-Shamir + responses (shared with the host prover)
        mem_proofs_flat: List[sigproof.MembershipProof] = []
        for j in range(M):
            obf = pssign.Signature(rnd_R[j], obf_S[j])
            mv = sigproof.MembershipVerifier(
                digit_coms[j], pp.ped_gen, rp.Q, rp.sign_pk, pp.ped_params[:2]
            )
            chal = mv._challenge(gts[j], mem_com_vals[j], obf)
            w = sigproof.MembershipWitness(
                rp.signed_values[digits[j]], digits[j], digit_bfs[j]
            )
            mem_proofs_flat.append(
                sigproof.membership_finish(w, mems[j], obf, chal, digit_coms[j])
            )

        out = []
        for i, (p, d) in enumerate(zip(provers, draws)):
            span = slice(i * n_out * exponent, (i + 1) * n_out * exponent)
            tx_coms = digit_coms[span]
            tx_mems = mem_proofs_flat[span]
            dc = [
                tx_coms[k * exponent : (k + 1) * exponent] for k in range(n_out)
            ]
            mp = [
                tx_mems[k * exponent : (k + 1) * exponent] for k in range(n_out)
            ]
            chal = p._challenge(
                eq_com_tokens[i * n_out : (i + 1) * n_out],
                eq_com_values[i * n_out : (i + 1) * n_out],
                dc,
            )
            out.append(p.finish(d, dc, mp, chal))
        return out

    # ------------------------------------------------------------ entry

    def prove(self, reqs: Sequence[tuple], rng=None) -> List[bytes]:
        """reqs: (in_witnesses, out_witnesses, inputs, outputs) tuples of
        ONE uniform `(n_in, n_out)` shape — the same arguments the host
        `TransferProver` constructor takes. Returns one transfer-proof
        byte string per request (same wire format as the host prover).
        """
        reqs = list(reqs)
        if not reqs:
            return []
        n_in, n_out = self._check_shapes(reqs)
        with devobs.plane("prove"), mx.span(
            "batch.prove", txs=len(reqs), shape=f"({n_in},{n_out})"
        ):
            with mx.span("batch.prove.wf"):
                wfs = self._prove_wf(reqs, n_in, n_out, rng)
            if _skip_range(n_in, n_out):
                ranges: List[Optional[bytes]] = [None] * len(reqs)
            else:
                with mx.span("batch.prove.range"):
                    ranges = self._prove_range(reqs, n_out, rng)
        # counted on COMPLETION (a device-plane failure re-proves the
        # group on host — those txs land in batch.prove.host instead,
        # and so do the txs of an ABANDONED bounded worker finishing
        # late: its proofs are discarded, they must not report device)
        if not resilience.call_abandoned():
            mx.counter("batch.prove.batches").inc()
            mx.counter("batch.prove.txs").inc(len(reqs))
        return [
            TransferProof(wf=w, range_correctness=rc).to_bytes()
            for w, rc in zip(wfs, ranges)
        ]


# ---------------------------------------------------------------- cache

# Tables are expensive to build (host windowed multiples); keep a small
# identity-keyed cache so repeated `TransferProver.batch` calls against
# the same PublicParams reuse one prover. PublicParams is an unhashable
# mutable dataclass, so the key is object identity with a strong ref
# (params objects are small; the cap bounds growth).
_CACHE: List[Tuple[PublicParams, BatchedTransferProver]] = []
_CACHE_CAP = 4


def prover_for(pp: PublicParams) -> BatchedTransferProver:
    for cached_pp, prover in _CACHE:
        if cached_pp is pp:
            return prover
    prover = BatchedTransferProver(pp)
    _CACHE.append((pp, prover))
    if len(_CACHE) > _CACHE_CAP:
        _CACHE.pop(0)
    return prover
