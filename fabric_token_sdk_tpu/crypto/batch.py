"""Batched (TPU) verification data plane for zkatdlog proofs.

The reference verifies each proof sequentially with goroutines
(`transfer.go:124-154`, `range/proof.go:211-284`); here whole BLOCKS of
transactions verify through a SMALL CONSTANT set of XLA programs:

* `BatchedPSVerifier`      — Pointcheval-Sanders signature batches
* `BatchedWFVerifier`      — transfer and issue well-formedness sigma
  proofs
* `BatchedMembershipVerifier` — the pairing side of membership proofs,
  two pairings a proof (the scalar verifier's four legs, those that
  share an argument merged by bilinearity)
* `BatchedTransferVerifier`— a block's full action proofs (WF + range),
  transfers and issues in one call

Execution model (staged tiles — see `ops/stages.py`): every verifier is a
HOST-SIDE composition of primitive stage kernels (fixed-base multiexp,
variable-base scalar mul, Jacobian add/sub, batch to-affine — each jit'd
once at one canonical `stages.tile_rows` shape) plus the compile-once pairing tiles
(`ops/pairing.py`). All glue between stages — row flattening, challenge
repetition, broadcasting parameter points, Fiat-Shamir re-hashing — is
host numpy, so the distinct-program count is independent of batch size,
transfer shape `(n_in, n_out)`, and parameter set. `ops/warmup.py`
precompiles the whole set. The transfer verifiers take rows of differing
shapes, and of either operation (a transfer is an `(inputs, outputs,
proof)` tuple, an issue a `crypto/issue.py:IssueRow`), in one call: every
action's rows sit at a running offset of the flat rows a stage call is
handed, so a block costs its fixed part (one padded dispatch per stage
call, one Miller walk, one final exponentiation) once, and not once a
shape or once an operation.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import numpy as np

from . import hostmath as hm, pssign, sigproof
from .issue import IssueProof, IssueRow
from .rangeproof import RangeProof, RangeVerifier
from .setup import PublicParams
from .transfer import TransferProof, _skip_range
from .wellformedness import IssueWF, TransferWF, challenge_issue_wf, \
    challenge_transfer_wf
from ..ops import curve as cv, curve2 as cv2, limbs as lb, pairing as pr, \
    stages as st, tower as tw
from ..utils import devobs
from ..utils import metrics as mx, resilience

def _spanned(name):
    """Wrap a verify method in a metrics span (no-op when disabled) and
    a dispatch-ledger plane (`utils/devobs.py`): every stage dispatch
    the method triggers records its occupancy and its enqueue / wait
    time under the plane named by the span's middle token
    (`batch.sign.verify` -> `sign`, every `batch.*.verify` verifier ->
    `verify`). The outermost call on a thread is the plane span, whose
    time outside any dispatch frame is the plane's host glue; a nested
    verifier (transfer -> wf / membership / ps) is part of it."""
    middle = name.split(".")[1] if "." in name else name
    plane = middle if middle in ("sign", "prove") else "verify"

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kw):
            with devobs.plane(plane), mx.span(name):
                return fn(*args, **kw)

        return wrapper

    return deco


# ===================================================================
# Pointcheval-Sanders batch verification
# ===================================================================


class BatchedPSVerifier:
    """Verifies B signatures on l-message vectors via the stage tiles."""

    def __init__(self, pk, Q):
        self.pk_host = list(pk)
        self.Q_host = Q
        self.pk_np = np.asarray(cv2.encode_points(self.pk_host))  # (l+2,3,2,L)
        self.Q_np = np.asarray(pr.encode_g2([Q]))[0]  # (2,2,L)

    @_spanned("batch.ps.verify")
    def verify(self, messages_rows: Sequence[Sequence[int]], sigs) -> np.ndarray:
        """-> bool array (B,). Raises nothing; invalid rows are False."""
        B = len(sigs)
        if B == 0:
            return np.zeros(0, dtype=bool)
        mx.counter("batch.ps.sigs").inc(B)
        l = len(self.pk_host) - 2
        malformed = np.zeros(B, dtype=bool)
        with devobs.glue("challenge"):
            hashed: List[Optional[list]] = []
            for i, msgs in zip(range(B), messages_rows):
                try:
                    if len(msgs) != l:
                        raise ValueError("PS batch: message count mismatch")
                    hashed.append(list(msgs) + [pssign.hash_messages(msgs)])
                except Exception:
                    malformed[i] = True
                    hashed.append(None)
        with devobs.glue("encode"):
            scal = np.zeros((B, l + 1, lb.NLIMBS), dtype=np.int32)
            negS, R = [], []
            for i, (ms, sig) in enumerate(zip(hashed, sigs)):
                try:
                    if ms is None:
                        raise ValueError("PS batch: malformed messages")
                    scal[i] = cv.encode_scalars(ms)
                    negS.append(hm.g1_neg(sig.S))  # a sign, no curve work
                    R.append(sig.R)
                except Exception:
                    malformed[i] = True
                    negS.append(hm.G1_GEN)  # placeholder; row forced False
                    R.append(hm.G1_GEN)
            P1 = np.asarray(pr.encode_g1(negS))
            P2 = np.asarray(pr.encode_g1(R))
        # H = PK0 + sum PK_i^{m_i} (+ PK_last^{hash}) in G2, staged:
        # one flat scalar-mul pass, a host-folded tree sum, one to-affine
        k = l + 1
        bases = np.broadcast_to(
            self.pk_np[1:], (B, k) + self.pk_np.shape[1:]
        ).reshape((B * k,) + self.pk_np.shape[1:])
        terms = st.g2_mul_rows(bases, scal.reshape(B * k, lb.NLIMBS))
        acc = st.g2_tree_sum_rows(terms.reshape((B, k) + terms.shape[1:]))
        acc = st.g2_add_rows(acc, np.broadcast_to(self.pk_np[0], acc.shape))
        H_aff = st.g2_to_affine_rows(acc)  # (B, 2, 2, L)
        Ps = np.stack([P1, P2], axis=1)  # (B, 2, 2, L) G1 affine
        Qs = np.stack(
            [np.broadcast_to(self.Q_np, H_aff.shape), H_aff], axis=1
        )  # (B, 2, 2, 2, L)
        gt = pr.pairing_product_staged(Ps, Qs)
        with devobs.glue("decode"):
            out = pr.gt_is_one_host(gt)
        out[malformed] = False
        return out


# ===================================================================
# Transfer well-formedness batch verification
# ===================================================================


class BatchedWFVerifier:
    """Recomputes all Schnorr commitments of B well-formedness proofs, of
    transfers of any mix of shapes and of issues, via the stage tiles,
    then re-derives challenges on host."""

    def __init__(self, pp: PublicParams):
        self.pp = pp
        self.table = cv.FixedBaseTable(pp.ped_params)

    @staticmethod
    def _parse(tx):
        """-> (the row's parsed proof, its type response) where it has
        the responses its statement asks for, else None: the row
        verifies False, as the scalar verifiers raise."""
        try:
            if isinstance(tx, IssueRow):
                wf = IssueWF.from_bytes(tx.proof)
                n_out = len(tx.outputs)
                if not n_out or len(wf.values) != n_out or len(wf.bfs) != n_out:
                    return None
                if tx.anonymous:
                    return None if wf.type_resp is None else (wf, wf.type_resp)
                if not wf.type_clear:
                    return None
                # the type's randomness is zero: response = c * hash(type)
                return wf, wf.challenge * hm.hash_to_zr(
                    wf.type_clear.encode()) % hm.R
            inputs, outputs, raw = tx
            wf = TransferWF.from_bytes(raw)
            n_in, n_out = len(inputs), len(outputs)
            if (
                len(wf.input_values) == n_in
                and len(wf.input_bfs) == n_in
                and len(wf.output_values) == n_out
                and len(wf.output_bfs) == n_out
            ):
                return wf, wf.type_resp
        except Exception:
            pass  # malformed
        return None

    @_spanned("batch.wf.verify")
    def verify(self, txs: Sequence) -> np.ndarray:
        """txs: `(inputs, outputs, wf_bytes)` for a transfer, an
        `IssueRow` (its proof the issue's wf bytes) for an issue. Each
        transfer has its own `(n_in, n_out)` and `n_in + n_out + 2` rows
        (the two aggregate statements among them), each issue one row an
        output, at a running offset of the flat rows.
        Returns bool array (B,)."""
        B = len(txs)
        if B == 0:
            return np.zeros(0, dtype=bool)
        mx.counter("batch.wf.txs").inc(B)
        issue = [isinstance(t, IssueRow) for t in txs]
        ns = [
            len(t.outputs) if issue[i] else len(t[0]) + len(t[1]) + 2
            for i, t in enumerate(txs)
        ]
        # row i owns the flat rows at[i]:at[i+1]
        at = np.concatenate([[0], np.cumsum(ns)])
        N = int(at[-1])
        with devobs.glue("parse"):
            proofs = [self._parse(t) for t in txs]
        with devobs.glue("hostec"):
            # the two aggregate statements of a transfer
            sums = [
                (hm.g1_sum(t[0]), hm.g1_sum(t[1]))
                if proofs[i] is not None and not issue[i] else None
                for i, t in enumerate(txs)
            ]
        with devobs.glue("encode"):
            stmts: List = []
            resp = np.zeros((N, 3, lb.NLIMBS), dtype=np.int32)
            chals = np.zeros((B, lb.NLIMBS), dtype=np.int32)
            for i, (t, parsed) in enumerate(zip(txs, proofs)):
                if parsed is None:
                    stmts.extend([None] * ns[i])
                    continue
                wf, type_resp = parsed
                if issue[i]:
                    stmts.extend(t.outputs)
                    rows = [
                        [type_resp, wf.values[k], wf.bfs[k]]
                        for k in range(ns[i])
                    ]
                else:
                    inputs, outputs, _ = t
                    n_in, n_out = len(inputs), len(outputs)
                    stmts.extend(inputs)
                    stmts.append(sums[i][0])
                    stmts.extend(outputs)
                    stmts.append(sums[i][1])
                    rows = []
                    for k in range(n_in):
                        rows.append(
                            [type_resp, wf.input_values[k], wf.input_bfs[k]]
                        )
                    rows.append(
                        [
                            type_resp * n_in % hm.R,
                            wf.sum_resp,
                            sum(wf.input_bfs) % hm.R,
                        ]
                    )
                    for k in range(n_out):
                        rows.append(
                            [type_resp, wf.output_values[k], wf.output_bfs[k]]
                        )
                    rows.append(
                        [
                            type_resp * n_out % hm.R,
                            wf.sum_resp,
                            sum(wf.output_bfs) % hm.R,
                        ]
                    )
                for j, r in enumerate(rows):
                    resp[at[i] + j] = cv.encode_scalars(r)
                chals[i] = cv.encode_scalars([wf.challenge])[0]
            stmt_np = np.stack([cv.encode_point(s) for s in stmts])
            chal_rep = np.repeat(chals, ns, axis=0)
        # com_j = prod ped_i^{resp_ji} - stmt_j^challenge over the flat rows
        fixed = st.g1_msm_rows(self.table.flat, resp)
        sc = st.g1_mul_rows(stmt_np, chal_rep)
        coms = st.g1_sub_rows(fixed, sc)
        with devobs.glue("decode"):
            com_pts = cv.decode_points(coms)  # N host points
        out = np.zeros(B, dtype=bool)
        with devobs.glue("challenge"):
            for i, (t, parsed) in enumerate(zip(txs, proofs)):
                if parsed is None:
                    continue
                row = com_pts[at[i] : at[i + 1]]
                if issue[i]:
                    chal = challenge_issue_wf(row, t.outputs)
                else:
                    inputs, outputs, _ = t
                    in_coms = row[: len(inputs) + 1]
                    out_coms = row[len(inputs) + 1 :]
                    chal = challenge_transfer_wf(
                        in_coms[:-1], in_coms[-1], out_coms[:-1], out_coms[-1],
                        inputs, outputs,
                    )
                out[i] = chal == parsed[0].challenge
        return out


# ===================================================================
# Membership-proof batch: pairing-side commitment reconstruction
# ===================================================================


class BatchedMembershipVerifier:
    """Verifies B membership proofs (the per-digit unit of range proofs).

    Device: GT commitment via 2-pairing products + G1 commitment via
    fixed/variable multiexp — all through the compile-once stage tiles.
    Host: per-proof Fiat-Shamir challenge.

    The scalar verifier's four legs (`sigproof.POKVerifier.
    recompute_commitment`)

        e(-S^c, Q) e(R^c, PK0) e(R, PK1^{z_v} + PK2^{z_h}) e(P^{z_bf}, Q)

    are two here: the legs that share an argument are merged by
    bilinearity (G1 of BN254 has cofactor 1, so it holds for every
    curve point a proof can carry),

        e(P^{z_bf} - S^c, Q) e(R, PK0^c + PK1^{z_v} + PK2^{z_h}),

    the same element of GT after the final exponentiation, so the
    challenge hashed over it is the same and no verdict differs.
    """

    def __init__(self, pp: PublicParams):
        self.pp = pp
        rp = pp.range_params
        self.pk = rp.sign_pk
        self.Q = rp.Q
        self.P = pp.ped_gen
        self.ped2 = pp.ped_params[:2]
        self.pk_np = np.asarray(cv2.encode_points(self.pk))  # (l+2,3,2,L)
        self.Q_np = np.asarray(pr.encode_g2([self.Q]))[0]
        self.table2 = cv.FixedBaseTable(self.ped2)
        self.tableP = cv.FixedBaseTable([self.P])

    @_spanned("batch.membership.verify")
    def verify(self, proofs: Sequence[sigproof.MembershipProof],
               commitments: Sequence) -> np.ndarray:
        B = len(proofs)
        if B == 0:
            return np.zeros(0, dtype=bool)
        mx.counter("batch.membership.proofs").inc(B)
        L = lb.NLIMBS
        with devobs.glue("encode"):
            # one vectorized limb encoding per response field across the
            # batch
            z = np.stack(
                [
                    cv.encode_scalars([p.value_resp for p in proofs]),
                    cv.encode_scalars([p.hash_resp for p in proofs]),
                    cv.encode_scalars([p.sig_bf_resp for p in proofs]),
                    cv.encode_scalars([p.challenge for p in proofs]),
                ],
                axis=1,
            )  # (B, 4, L): value, hash, sig_bf, chal
            com_resp = np.stack(
                [z[:, 0], cv.encode_scalars([p.com_bf_resp for p in proofs])],
                axis=1,
            )
            S_jac = np.stack([cv.encode_point(p.signature.S) for p in proofs])
            R_np = np.asarray(pr.encode_g1([p.signature.R for p in proofs]))
            com_jac = np.stack([cv.encode_point(c) for c in commitments])

        # G2 term: t' = PK0^c + PK1^{z_v} + PK2^{z_h}
        bases = np.broadcast_to(
            self.pk_np[0:3], (B, 3) + self.pk_np.shape[1:]
        ).reshape((3 * B,) + self.pk_np.shape[1:])
        terms = st.g2_mul_rows(bases, z[:, [3, 0, 1]].reshape(3 * B, L))
        t_jac = st.g2_tree_sum_rows(terms.reshape((B, 3) + terms.shape[1:]))
        t_aff = st.g2_to_affine_rows(t_jac)

        # G1 term: P^{z_bf} - S^c (R is affine on the wire)
        Sc = st.g1_mul_rows(S_jac, z[:, 3])
        Pz_j = st.g1_msm_rows(self.tableP.flat, z[:, 2:3])
        m_jac = st.g1_sub_rows(Pz_j, Sc)
        m_aff = st.g1_to_affine_rows(m_jac)

        # G1 commitment: ped0^{z_v} ped1^{z_cb} - com^c
        fixed = st.g1_msm_rows(self.table2.flat, com_resp)
        comc = st.g1_mul_rows(com_jac, z[:, 3])
        com_val = st.g1_sub_rows(fixed, comc)

        # 2-leg pairing product via the compile-once staged tile programs.
        # A sender can make either merged point the point at infinity
        # (S^c = P^{z_bf}, say): the to-affine tiles return no point
        # there, and the leg is the identity
        with devobs.glue("encode"):
            Ps = np.stack([m_aff, R_np], axis=1)  # (B, 2, 2, L)
            Qs = np.stack(
                [np.broadcast_to(self.Q_np, t_aff.shape), t_aff], axis=1
            )  # (B, 2, 2, 2, L)
            inf = np.stack(
                [st.jac_infinity_np(m_jac), st.jac_infinity_np(t_jac)], axis=1
            )
        gt = pr.pairing_product_staged(Ps, Qs, inf_mask=inf)
        with devobs.glue("decode"):
            gt_host = tw.decode_fp12(gt)
            com_host = cv.decode_points(com_val)
        out = np.zeros(B, dtype=bool)
        with devobs.glue("challenge"):
            for i, (p, com) in enumerate(zip(proofs, commitments)):
                sig = p.signature
                if p.commitment != com or sig.R is None or sig.S is None:
                    # (a signature at infinity: the scalar verifier's
                    # rejection, `sigproof.MembershipVerifier.verify`)
                    continue
                mv = sigproof.MembershipVerifier(
                    com, self.P, self.Q, self.pk, self.ped2
                )
                chal = mv._challenge(gt_host[i], com_host[i], p.signature)
                out[i] = chal == p.challenge
        return out


# ===================================================================
# Full transfer-proof batch verification (WF + range)
# ===================================================================


class BatchedTransferVerifier:
    """Verifies a whole block's zkatdlog action proofs in one call:
    transfers of any mix of shapes `(n_in, n_out)` and issues.

    Composition mirrors `transfer.TransferVerifier` and
    `issue.IssueVerifier` but the group/pairing work of ALL rows runs
    through the fixed-shape stage tiles — the total distinct-program
    count is constant in `(n_in, n_out)`, batch size, and parameter set.
    Rows are flat: an action's well-formedness rows, its membership rows
    (output x digit) and its equality rows (one per output) sit at
    running offsets, so a call of one shape dispatches exactly the rows
    a call of mixed shapes with as many rows does, and an issue's rows
    ride the stage calls of the block's transfers: an issue is its
    outputs' well-formedness rows (no aggregate rows) and the range
    statement a transfer's outputs carry.
    """

    def __init__(self, pp: PublicParams):
        self.pp = pp
        self.wf = BatchedWFVerifier(pp)
        self.membership = BatchedMembershipVerifier(pp)
        self.table3 = self.wf.table  # ped 3-base table
        self.table2 = self.membership.table2  # ped[:2]

    @_spanned("batch.transfer.verify")
    def verify(self, txs: Sequence) -> np.ndarray:
        """txs: `(inputs, outputs, transfer_proof_bytes)`, each of its
        own shape, or `IssueRow`s. Returns bool array (B,). A 1-in/1-out
        transfer carries no range proof and contributes no range rows
        (reference transfer.go:55-59); an issue always carries one."""
        B = len(txs)
        if B == 0:
            return np.zeros(0, dtype=bool)
        issue = [isinstance(t, IssueRow) for t in txs]
        issues = [t for t, is_issue in zip(txs, issue) if is_issue]
        shapes = {
            (len(t[0]), len(t[1]))
            for t, is_issue in zip(txs, issue) if not is_issue
        }

        proofs = []
        ok = np.ones(B, dtype=bool)
        with devobs.glue("parse"):
            for i, t in enumerate(txs):
                kind = IssueProof if issue[i] else TransferProof
                try:
                    proofs.append(kind.from_bytes(t[2]))
                except Exception:
                    proofs.append(kind(wf=b"", range_correctness=None))
                    ok[i] = False
        # (a row of either kind with its well-formedness bytes third)
        ok &= self.wf.verify(
            [
                t._replace(proof=p.wf) if issue[i] else (t[0], t[1], p.wf)
                for i, (t, p) in enumerate(zip(txs, proofs))
            ]
        )

        # a row's range statement: (outputs, RangeProof), or None where
        # it has none to verify
        ranges: List[Optional[Tuple[list, RangeProof]]] = []
        with devobs.glue("parse"):
            for i, (t, p) in enumerate(zip(txs, proofs)):
                if not issue[i] and _skip_range(len(t[0]), len(t[1])):
                    ranges.append(None)  # the WF verdict is the whole verdict
                    continue
                outputs = t.outputs if issue[i] else t[1]
                rpf = self._range_proof(p.range_correctness, len(outputs))
                ok[i] &= rpf is not None
                ranges.append(None if rpf is None else (outputs, rpf))
        ok &= self._range_verdicts(ranges)
        # counted on COMPLETION (not entry): an ABANDONED bounded worker
        # (verify timeout already degraded the block to host) must not
        # report its discarded rows as device-verified — they were
        # counted under ledger.validate.host instead. An entry-side
        # count would always precede the deadline expiry and defeat the
        # guard.
        if not resilience.call_abandoned():
            mx.counter("batch.transfer.txs").inc(B - len(issues))
            mx.counter("batch.transfer.calls").inc()
            mx.counter("batch.transfer.shapes").inc(len(shapes))
            mx.counter("batch.issue.records").inc(len(issues))
            mx.counter("batch.issue.outputs").inc(
                sum(len(t.outputs) for t in issues)
            )
        return ok

    def _range_proof(self, raw: Optional[bytes], n_out: int):
        """-> the `RangeProof` of `raw` where it has what `n_out` outputs
        ask for, else None (no proof, malformed bytes, a count that does
        not fit): the row verifies False."""
        if raw is None:
            return None
        exponent = self.pp.range_params.exponent
        try:
            rpf = RangeProof.from_bytes(raw)
            if (
                len(rpf.membership_proofs) != n_out
                or len(rpf.digit_commitments) != n_out
                or any(len(r) != exponent for r in rpf.membership_proofs)
                or any(len(r) != exponent for r in rpf.digit_commitments)
                or len(rpf.value_resps) != n_out
                or len(rpf.token_bf_resps) != n_out
                or len(rpf.com_bf_resps) != n_out
            ):
                return None
            return rpf
        except Exception:
            return None

    def _range_verdicts(
        self, ranges: Sequence[Optional[Tuple[list, RangeProof]]]
    ) -> np.ndarray:
        """The range half of a call, over statements from wherever they
        come (a transfer's outputs, an issue's): `(outputs, RangeProof)`
        with `exponent` digits an output, or None for a row that brings
        none. -> bool array, True where there was nothing to verify."""
        ok = np.ones(len(ranges), dtype=bool)
        rp = self.pp.range_params
        exponent, base = rp.exponent, rp.base
        live = [i for i, r in enumerate(ranges) if r is not None]

        # ---- membership proofs, flattened over (row, output, digit)
        mem_proofs, mem_coms, mem_idx = [], [], []
        for i in live:
            outputs, rpf = ranges[i]
            for k in range(len(outputs)):
                for d in range(exponent):
                    mem_proofs.append(rpf.membership_proofs[k][d])
                    mem_coms.append(rpf.digit_commitments[k][d])
                    mem_idx.append(i)
        if mem_proofs:
            mem_ok = self.membership.verify(mem_proofs, mem_coms)
            for j, i in enumerate(mem_idx):
                if not mem_ok[j]:
                    ok[i] = False

        # ---- equality proofs: token rows (3 bases) + aggregate rows (2),
        # one of each per output, flat over the live rows
        if not live:
            return ok
        L = lb.NLIMBS
        n_outs = [len(ranges[i][0]) for i in live]
        # live row li owns the flat rows at[li]:at[li+1]
        at = np.concatenate([[0], np.cumsum(n_outs)])
        N = int(at[-1])
        with devobs.glue("hostec"):
            # an output's digit commitments folded: prod_d com_d^(base^d)
            powers = [base**d % hm.R for d in range(exponent)]
            aggs = [
                hm.g1_multiexp(ranges[i][1].digit_commitments[k], powers)
                for li, i in enumerate(live)
                for k in range(n_outs[li])
            ]
        with devobs.glue("encode"):
            tok_resp = np.zeros((N, 3, L), dtype=np.int32)
            tok_stmt = np.zeros((N, 3, L), dtype=np.int32)
            agg_resp = np.zeros((N, 2, L), dtype=np.int32)
            agg_stmt = np.zeros((N, 3, L), dtype=np.int32)
            chals = np.zeros((len(live), L), dtype=np.int32)
            for li, i in enumerate(live):
                outputs, rpf = ranges[i]
                for k in range(n_outs[li]):
                    r = at[li] + k
                    tok_resp[r] = cv.encode_scalars(
                        [rpf.type_resp, rpf.value_resps[k], rpf.token_bf_resps[k]]
                    )
                    tok_stmt[r] = cv.encode_point(outputs[k])
                    agg_stmt[r] = cv.encode_point(aggs[r])
                    agg_resp[r] = cv.encode_scalars(
                        [rpf.value_resps[k], rpf.com_bf_resps[k]]
                    )
                chals[li] = cv.encode_scalars([rpf.challenge])[0]
            chal_rep = np.repeat(chals, n_outs, axis=0)

        com_tok = st.g1_sub_rows(
            st.g1_msm_rows(self.table3.flat, tok_resp),
            st.g1_mul_rows(tok_stmt, chal_rep),
        )
        com_val = st.g1_sub_rows(
            st.g1_msm_rows(self.table2.flat, agg_resp),
            st.g1_mul_rows(agg_stmt, chal_rep),
        )
        with devobs.glue("decode"):
            com_tok_h = cv.decode_points(com_tok)
            com_val_h = cv.decode_points(com_val)
        with devobs.glue("challenge"):
            for li, i in enumerate(live):
                outputs, rpf = ranges[i]
                verifier = RangeVerifier(
                    outputs, base, exponent, self.pp.ped_params,
                    rp.sign_pk, self.pp.ped_gen, rp.Q,
                )
                chal = verifier._challenge(
                    com_tok_h[at[li] : at[li + 1]],
                    com_val_h[at[li] : at[li + 1]],
                    rpf.digit_commitments,
                )
                if chal != rpf.challenge:
                    ok[i] = False
        return ok
