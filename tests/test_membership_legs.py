"""Two pairings a membership proof: `BatchedMembershipVerifier` against
the scalar verifier's four legs.

The plain reference is `crypto/sigproof.py`: `POKVerifier.
recompute_commitment` (four legs through `hostmath.pairing_product`)
for the element of GT a row's challenge is hashed over, and
`MembershipVerifier.verify` for the verdict. The batched verifier merges
the legs that share an argument,

    e(P^{z_bf} - S^c, Q) e(R, PK0^c + PK1^{z_v} + PK2^{z_h}),

and must give the same element, row for row, also where a merged point
is the point at infinity (the sender chooses S, c's preimage and the
responses). Tier-1 cases run the verifier's own glue and the walks' own
padding over exact host stand-ins for the tile kernels
(`tests/hostplane.py`): nothing compiles. One `slow` case runs the real
programs.
"""
import functools
import os
import random
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import hostplane  # noqa: E402

from fabric_token_sdk_tpu.crypto import (  # noqa: E402
    batch, hostmath as hm, pssign, schnorr, sigproof, token as tok,
)
from fabric_token_sdk_tpu.crypto.rangeproof import RangeProof  # noqa: E402
from fabric_token_sdk_tpu.crypto.setup import setup  # noqa: E402
from fabric_token_sdk_tpu.crypto.transfer import (  # noqa: E402
    TransferProof, TransferProver,
)
from fabric_token_sdk_tpu.ops import curve as cv, curve2 as cv2, \
    limbs as lb, pairing as pr, stages as st, tower as tw  # noqa: E402
from fabric_token_sdk_tpu.ops.field import FP  # noqa: E402
from fabric_token_sdk_tpu.utils import devobs, metrics as mx  # noqa: E402

SETUP_SEED = 0xF75
# (base, exponent): the tests' parameters, and the same base at the five
# digits an output has at base 300 / exponent 5
PARAMS = {"base4_exp2": (4, 2), "base4_five_digits": (4, 5)}


@functools.lru_cache(maxsize=None)
def _pp(name):
    base, exponent = PARAMS[name]
    return setup(base=base, exponent=exponent, rng=random.Random(SETUP_SEED))


def _signer():
    """The PS key `setup` drew first from the same seed, secret included
    (`setup` discards it)."""
    return pssign.keygen(1, random.Random(SETUP_SEED))


def digit_rows(pp, rng):
    """-> (proofs, commitments): the membership proofs of one `(1,2)`
    transfer's range proof, `2 * exponent` of them, as the prover made
    them."""
    rp = pp.range_params
    top = len(rp.signed_values) ** rp.exponent
    total = rng.randrange(2, top)
    first = rng.randrange(1, total)
    ins, in_w = tok.tokens_with_witness([total], "USD", pp.ped_params, rng)
    outs, out_w = tok.tokens_with_witness(
        [first, total - first], "USD", pp.ped_params, rng)
    raw = TransferProver(in_w, out_w, ins, outs, pp, rng).prove()
    rpf = RangeProof.from_bytes(TransferProof.from_bytes(raw).range_correctness)
    proofs = [m for row in rpf.membership_proofs for m in row]
    coms = [c for row in rpf.digit_commitments for c in row]
    return proofs, coms


def reference(pp, proof, com):
    """-> (the GT element of the scalar verifier's four legs, its
    verdict)."""
    rp = pp.range_params
    gt = sigproof.POKVerifier(
        pk=list(rp.sign_pk), Q=rp.Q, P=pp.ped_gen
    ).recompute_commitment(sigproof.POK(
        challenge=proof.challenge, signature=proof.signature,
        messages=[proof.value_resp], bf_resp=proof.sig_bf_resp,
        hash_resp=proof.hash_resp))
    try:
        sigproof.MembershipVerifier(
            com, pp.ped_gen, rp.Q, rp.sign_pk, pp.ped_params[:2]
        ).verify(proof)
        return gt, True
    except ValueError:
        return gt, False


def watch_walk(monkeypatch) -> list:
    """-> a list that fills with `(Ps, Qs, inf_mask, GT rows)` of every
    `pairing_product_staged` call."""
    seen = []
    inner = pr.pairing_product_staged

    def wrapper(Ps, Qs, inf_mask=None):
        gt = inner(Ps, Qs, inf_mask=inf_mask)
        seen.append((np.array(Ps), np.array(Qs), np.array(inf_mask), gt))
        return gt

    monkeypatch.setattr(pr, "pairing_product_staged", wrapper)
    return seen


def batched(monkeypatch, pp, proofs, coms):
    """-> (GT element a row, verdict a row, the inf_mask handed over)."""
    seen = watch_walk(monkeypatch)
    ok = batch.BatchedMembershipVerifier(pp).verify(proofs, coms)
    (Ps, Qs, mask, gt), = seen
    assert Ps.shape[:2] == Qs.shape[:2] == mask.shape == (len(proofs), 2)
    return tw.decode_fp12(gt), ok.tolist(), mask.tolist()


# ===================================================================
# (a) honest and tampered rows: the same element of GT, the same verdict
# ===================================================================

TAMPERS = ("value_resp", "hash_resp", "sig_bf_resp", "com_bf_resp",
           "challenge", "S", "R", "commitment")


def tamper(proof, kind, rng):
    if kind in ("S", "R"):
        sig = proof.signature
        moved = hm.g1_mul(getattr(sig, kind), rng.randrange(2, hm.R))
        proof.signature = pssign.Signature(
            moved if kind == "R" else sig.R, moved if kind == "S" else sig.S)
    elif kind == "commitment":
        proof.commitment = hm.g1_add(proof.commitment, hm.G1_GEN)
    else:
        setattr(proof, kind, (getattr(proof, kind) + 1) % hm.R)


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_019])
@pytest.mark.parametrize("params", sorted(PARAMS))
def test_two_legs_give_the_four_legs_gt_and_verdict(monkeypatch, params, seed):
    hostplane.install(monkeypatch)
    pp = _pp(params)
    rng = random.Random(f"legs/{params}/{seed}")
    proofs, coms = digit_rows(pp, rng)
    assert len(proofs) == 2 * PARAMS[params][1]
    broken = {}
    for i in rng.sample(range(len(proofs)), len(proofs) // 2):
        broken[i] = rng.choice(TAMPERS)
        tamper(proofs[i], broken[i], rng)
    want = [reference(pp, p, c) for p, c in zip(proofs, coms)]
    # the scalar verifier rejects exactly the tampered rows
    assert [i for i, (_, ok) in enumerate(want) if not ok] == sorted(broken)
    gts, oks, masks = batched(monkeypatch, pp, proofs, coms)
    assert oks == [ok for _, ok in want], broken
    for i, (gt, (ref, _)) in enumerate(zip(gts, want)):
        assert gt == ref, (i, broken.get(i))
    assert masks == [[False, False]] * len(proofs)


# ===================================================================
# (b) rows a sender can craft: a merged point at infinity, zero scalars
# ===================================================================


def _inv(x):
    return pow(x, -1, hm.R)


def _merged_g1_infinity(pp, p):
    # S^c = P^{z_bf}
    p.signature = pssign.Signature(
        p.signature.R,
        hm.g1_mul(pp.ped_gen, p.sig_bf_resp * _inv(p.challenge) % hm.R))


def _g2_terms_cancel(pp, p):
    # c x0 + z_v x1 + z_h x2 = 0 under the key's secret x
    x0, x1, x2 = _signer().sk
    p.challenge = -(p.value_resp * x1 + p.hash_resp * x2) * _inv(x0) % hm.R


def _challenge_zero(pp, p):
    p.challenge = 0


def _sig_bf_resp_zero(pp, p):
    p.sig_bf_resp = 0


def _both_zero(pp, p):
    p.challenge = 0
    p.sig_bf_resp = 0


def _r_minus_generator(pp, p):
    p.signature = pssign.Signature(hm.g1_neg(hm.G1_GEN), p.signature.S)


def _both_legs_infinite(pp, p):
    _g2_terms_cancel(pp, p)
    _merged_g1_infinity(pp, p)


def _all_responses_zero(pp, p):
    p.challenge = p.sig_bf_resp = p.value_resp = p.hash_resp = 0


# the wire carries the point at infinity (JSON null), and to the scalar
# verifier's `hostmath` a leg with it is the identity
def _s_on_the_wire_at_infinity(pp, p):
    p.signature = pssign.Signature(p.signature.R, None)


def _r_on_the_wire_at_infinity(pp, p):
    p.signature = pssign.Signature(None, p.signature.S)


# name -> (how the row is crafted, the legs that are the identity)
EDGES = {
    "merged_g1_point_at_infinity": (_merged_g1_infinity, [True, False]),
    "challenge_zero": (_challenge_zero, [False, False]),
    "sig_bf_resp_zero": (_sig_bf_resp_zero, [False, False]),
    "challenge_and_sig_bf_resp_zero": (_both_zero, [True, False]),
    "R_is_the_generators_negative": (_r_minus_generator, [False, False]),
    "g2_terms_cancel": (_g2_terms_cancel, [False, True]),
    "both_merged_points_at_infinity": (_both_legs_infinite, [True, True]),
    "every_pairing_scalar_zero": (_all_responses_zero, [True, True]),
    "S_on_the_wire_at_infinity": (_s_on_the_wire_at_infinity, [False, False]),
    # (0, 0) is no point, and the walk is not told: a Miller value of
    # lines evaluated there dies in the final exponentiation
    "R_on_the_wire_at_infinity": (_r_on_the_wire_at_infinity, [False, False]),
}


@pytest.mark.parametrize("edge", sorted(EDGES))
def test_crafted_edge_row_gives_the_four_legs_gt_and_verdict(
    monkeypatch, edge
):
    """The crafted row between two honest ones: each of the three gives
    the reference's element and verdict, and the walk is told which of
    the crafted row's legs have no point."""
    hostplane.install(monkeypatch)
    pp = _pp("base4_exp2")
    assert _signer().pk == pp.range_params.sign_pk
    proofs, coms = digit_rows(pp, random.Random(f"edge/{edge}"))
    proofs, coms = proofs[:3], coms[:3]
    craft, identity_legs = EDGES[edge]
    craft(pp, proofs[1])
    want = [reference(pp, p, c) for p, c in zip(proofs, coms)]
    assert [ok for _, ok in want] == [True, False, True]
    gts, oks, masks = batched(monkeypatch, pp, proofs, coms)
    assert gts == [gt for gt, _ in want]
    assert oks == [True, False, True]
    assert masks == [[False, False], identity_legs, [False, False]]
    if all(identity_legs):
        assert gts[1] == hm.FP12_ONE


def forged_proof(pp, value, rng):
    """-> (a membership proof for a commitment to `value`, any value, made
    with no signature at all; the commitment; its blinding). `R` is the
    point at infinity (JSON null on the wire) and `S = P^s`, so the four
    legs reduce to `e(P^{z_bf - s c}, Q)`, which `z_bf = rho_bf + s c`
    answers; the value's and the hash's responses are free (`ROADMAP.md`
    C13)."""
    rp, ped2 = pp.range_params, pp.ped_params[:2]
    bf, s, rho_v, rho_cb, rho_bf, z_h = (hm.rand_zr(rng) for _ in range(6))
    com = hm.g1_multiexp(ped2, [value, bf])
    sig = pssign.Signature(None, hm.g1_mul(pp.ped_gen, s))
    com_gt = hm.pairing_product([(hm.g1_mul(pp.ped_gen, rho_bf), rp.Q)])
    com_val = hm.g1_multiexp(ped2, [rho_v, rho_cb])
    c = sigproof.MembershipVerifier(
        com, pp.ped_gen, rp.Q, rp.sign_pk, ped2)._challenge(com_gt, com_val, sig)
    proof = sigproof.MembershipProof(
        challenge=c, signature=sig, value_resp=(rho_v + c * value) % hm.R,
        com_bf_resp=(rho_cb + c * bf) % hm.R,
        sig_bf_resp=(rho_bf + s * c) % hm.R, hash_resp=z_h, commitment=com)
    return proof, com, bf


@pytest.mark.parametrize("path", ["scalar", "batched"])
def test_a_forged_proof_with_R_at_infinity_is_rejected(monkeypatch, path):
    """A commitment to 7 at base 4, outside the signed set: the sigma
    equations of both verifiers hold for the forged proof (the element of
    GT is the one the challenge was hashed over), so what rejects it is
    the look at `R` and `S`: on the scalar path, and as the row's verdict
    on the batched one, between two honest rows that stay accepted."""
    hostplane.install(monkeypatch)
    pp = _pp("base4_exp2")
    rng = random.Random("forged")
    forged, com, _ = forged_proof(pp, 7, rng)
    rp = pp.range_params
    mv = sigproof.MembershipVerifier(
        com, pp.ped_gen, rp.Q, rp.sign_pk, pp.ped_params[:2])
    gt, ok = reference(pp, forged, com)
    # the equations hold: only the point at infinity gives it away
    sp = schnorr.SchnorrProof(
        com, [forged.value_resp, forged.com_bf_resp], forged.challenge)
    com_val = schnorr.recompute_commitment(pp.ped_params[:2], sp)
    assert mv._challenge(gt, com_val, forged.signature) == forged.challenge
    assert ok is False
    if path == "scalar":
        with pytest.raises(ValueError, match="signature at infinity"):
            mv.verify(forged)
        return
    proofs, coms = digit_rows(pp, rng)
    proofs, coms = [proofs[0], forged, proofs[1]], [coms[0], com, coms[1]]
    gts, oks, _ = batched(monkeypatch, pp, proofs, coms)
    assert gts[1] == gt
    assert oks == [True, False, True]


@pytest.mark.parametrize("path", ["scalar", "batched"])
def test_an_issue_of_an_out_of_range_value_over_a_forged_digit_is_rejected(
    monkeypatch, path
):
    """The forgery where it matters, on the path an issuer's range proof
    takes: an issue of 7 at base 4 / exponent 1 (one digit: the signed set
    is 0..3) whose one membership proof is the forged one and whose every
    other equation holds. The scalar `IssueVerifier` and the issue's row
    on the plane both refuse it, beside an honest issue that passes."""
    from fabric_token_sdk_tpu.crypto.issue import (
        IssueProof, IssueProver, IssueRow, IssueVerifier,
    )
    from fabric_token_sdk_tpu.crypto.rangeproof import RangeVerifier
    from fabric_token_sdk_tpu.crypto.wellformedness import IssueWFProver

    hostplane.install(monkeypatch)
    pp = setup(base=4, exponent=1, rng=random.Random(SETUP_SEED))
    rng = random.Random("forged/issue")
    rp, ped = pp.range_params, pp.ped_params
    type_hash = hm.hash_to_zr(b"USD")
    bf = hm.rand_zr(rng)
    token = hm.g1_multiexp(ped, [type_hash, 7, bf])
    forged, digit, bf_d = forged_proof(pp, 7, rng)
    # the equality proof, by hand: the token opens to (type, 7, bf) and
    # its one digit commitment to (7, bf_d)
    r_t, r_v, r_tb, r_cb = (hm.rand_zr(rng) for _ in range(4))
    chal = RangeVerifier(
        [token], 4, 1, ped, rp.sign_pk, pp.ped_gen, rp.Q
    )._challenge([hm.g1_multiexp(ped, [r_t, r_v, r_tb])],
                 [hm.g1_multiexp(ped[:2], [r_v, r_cb])], [[digit]])
    rpf = RangeProof(
        challenge=chal, type_resp=(r_t + chal * type_hash) % hm.R,
        value_resps=[(r_v + chal * 7) % hm.R],
        token_bf_resps=[(r_tb + chal * bf) % hm.R],
        com_bf_resps=[(r_cb + chal * bf_d) % hm.R],
        digit_commitments=[[digit]], membership_proofs=[[forged]])
    wf = IssueWFProver([("USD", 7, bf)], [token], True, ped, rng).prove()
    raw = IssueProof(wf=wf, range_correctness=rpf.to_bytes()).to_bytes()
    sound = hm.g1_multiexp(ped, [type_hash, 3, bf])
    honest = IssueProver(
        [tok.TokenDataWitness("USD", 3, bf)], [sound], True, pp, rng).prove()
    if path == "scalar":
        IssueVerifier([sound], True, pp).verify(honest)
        with pytest.raises(ValueError, match="signature at infinity"):
            IssueVerifier([token], True, pp).verify(raw)
    else:
        rows = [IssueRow([sound], True, honest), IssueRow([token], True, raw)]
        assert batch.BatchedTransferVerifier(pp).verify(rows).tolist() \
            == [True, False]


def test_the_infinity_mask_reads_zero_and_p_as_zero():
    """A field element lives in [0, 2p): a Z of p is a Z of zero."""
    g1 = np.stack([cv.encode_point(hm.G1_GEN)] * 3)
    g1[1, 2] = 0
    g1[2, 2] = FP.p_limbs
    assert st.jac_infinity_np(g1).tolist() == [False, True, True]
    g2 = np.asarray(cv2.encode_points([hm.G2_GEN] * 4))
    g2[1, 2] = 0
    g2[2, 2, 0] = FP.p_limbs
    g2[2, 2, 1] = 0
    g2[3, 2, 1] = 0  # one coordinate of Z alone: a point
    assert st.jac_infinity_np(g2).tolist() == [False, True, True, False]


# ===================================================================
# (c) what a call hands the stage functions and the walk, and in what
#     order (`b300e5.testnet`'s traced slice opens on the one
#     `g1_to_affine_tile` frame, three stage calls before the walk)
# ===================================================================

CALL_ORDER = [
    "g2_mul_rows", "g2_add_rows", "g2_add_rows", "g2_to_affine_rows",
    "g1_mul_rows", "g1_msm_rows", "g1_sub_rows", "g1_to_affine_rows",
    "g1_msm_rows", "g1_mul_rows", "g1_sub_rows", "pairing_product_staged",
]


@pytest.mark.parametrize("params", sorted(PARAMS))
def test_a_call_hands_over_two_legs_a_row_in_the_order_the_slice_stands_on(
    monkeypatch, params
):
    hostplane.install(monkeypatch)
    pp = _pp(params)
    proofs, coms = digit_rows(pp, random.Random(f"order/{params}"))
    B, L = len(proofs), lb.NLIMBS
    calls = hostplane.record(monkeypatch)
    rows = mx.counter("pairing.staged.rows").value
    legs = mx.counter("pairing.staged.legs").value
    assert batch.BatchedMembershipVerifier(pp).verify(proofs, coms).all()
    names = [name for name, _ in calls]
    assert names == CALL_ORDER
    shapes = [[a.shape for a in arrays] for _, arrays in calls]
    assert shapes[0] == [(3 * B, 3, 2, L), (3 * B, L)]  # g2_mul_rows
    assert shapes[4] == [(B, 3, L), (B, L)]  # S^c: no R^c rows
    assert shapes[7] == [(B, 3, L)]  # the one to-affine call
    assert shapes[-1] == [(B, 2, 2, L), (B, 2, 2, 2, L), (B, 2)]  # K == 2
    at = names.index("g1_to_affine_rows")
    assert names.count("g1_to_affine_rows") == 1
    assert names[at + 1:] == ["g1_msm_rows", "g1_mul_rows", "g1_sub_rows",
                              "pairing_product_staged"]
    assert mx.counter("pairing.staged.rows").value - rows == B
    assert mx.counter("pairing.staged.legs").value - legs == 2 * B


def _tiles(n, height=128):
    return -(-n // height)


# membership rows of a block: 64 (2,2) transfers at base 100 / exponent 2,
# eight at base 300 / exponent 5, three of those, the steady cells' joint two
@pytest.mark.parametrize("B", [256, 80, 30, 8])
def test_tiles_a_block_at_the_chips_heights(monkeypatch, B):
    """The rows repeat four proofs (a stand-in computes a row once): what
    is held is the count of 128-row tiles a program, half the Miller
    tiles four legs took in a full block."""
    hostplane.install(monkeypatch, chip=True)
    pp = _pp("base4_exp2")
    four, coms4 = digit_rows(pp, random.Random("tiles"))
    proofs, coms = (four * (B // 4 + 1))[:B], (coms4 * (B // 4 + 1))[:B]

    def seen():
        return {prog: e["rows"] + e["padded_rows"] for (plane, prog), e
                in devobs.snapshot().items() if plane == "verify"}

    before = seen()
    miller = mx.counter("pairing.staged.miller_tiles").value
    assert batch.BatchedMembershipVerifier(pp).verify(proofs, coms).all()
    tiles = {prog: (n - before.get(prog, 0)) // 128
             for prog, n in seen().items() if n - before.get(prog, 0)}
    one = _tiles(B)
    assert tiles == {
        "g2_mul_tile": _tiles(3 * B), "g2_add_tile": 2 * one,
        "g2_to_affine_tile": one, "g1_mul_tile": 2 * one,
        "g1_msm1_tile": one, "g1_msm2_tile": one, "g1_sub_tile": 2 * one,
        "g1_to_affine_tile": one, "miller_tile": _tiles(2 * B),
        "fexp_tile": one,
    }
    assert mx.counter("pairing.staged.miller_tiles").value - miller \
        == _tiles(2 * B) == {256: 4, 80: 2, 30: 1, 8: 1}[B]


# ===================================================================
# the real programs
# ===================================================================


@pytest.mark.slow
def test_two_legs_through_the_real_programs(monkeypatch):
    """No stand-in: the stage tiles and the three pairing programs of the
    CPU backend (minutes to compile where the cache is cold), on honest
    rows, a tampered one and the crafted rows whose legs the walk
    masks."""
    pp = _pp("base4_exp2")
    proofs, coms = digit_rows(pp, random.Random("real"))
    more, more_coms = digit_rows(pp, random.Random("real/2"))
    proofs, coms = proofs + more[:3], coms + more_coms[:3]
    tamper(proofs[1], "hash_resp", random.Random("real"))
    _merged_g1_infinity(pp, proofs[3])
    _g2_terms_cancel(pp, proofs[4])
    _both_zero(pp, proofs[5])
    want = [reference(pp, p, c) for p, c in zip(proofs, coms)]
    gts, oks, masks = batched(monkeypatch, pp, proofs, coms)
    assert gts == [gt for gt, _ in want]
    assert oks == [ok for _, ok in want] \
        == [True, False, True, False, False, False, True]
    assert masks[3:6] == [[True, False], [False, True], [True, False]]
