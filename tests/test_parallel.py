"""Sharded dispatch on the 8-virtual-device CPU mesh == unsharded results."""
import os
import random

import jax
import numpy as np
import pytest

from fabric_token_sdk_tpu.crypto import hostmath as hm
from fabric_token_sdk_tpu.ops import curve as cv, stages as st
from fabric_token_sdk_tpu.parallel import (
    MeshConfig,
    make_mesh,
    mesh_dp,
    run_rows_dp,
    shard_rows,
    sharded_schnorr_rows,
)
from fabric_token_sdk_tpu.utils import metrics as mx


def _counter(name):
    return mx.REGISTRY.counter(name).value


def test_mesh_shapes():
    assert len(jax.devices()) == 8
    mesh = make_mesh(8, mp=2)
    assert mesh.shape == {"dp": 4, "mp": 2}
    assert mesh_dp(mesh) == 4
    # a non-dividing mp is CLAMPED to the largest divisor, not rejected —
    # an odd mesh request can never knock a node off the sharded path
    before = _counter("sharding.clamped")
    mesh = make_mesh(8, mp=3)
    assert mesh.shape == {"dp": 4, "mp": 2}
    assert _counter("sharding.clamped") - before == 1


def test_mesh_config_build_and_of():
    cfg = MeshConfig.build(8, 2)
    assert (cfg.n_devices, cfg.dp, cfg.mp, cfg.workers) == (8, 4, 2, 8)
    before = _counter("sharding.clamped")
    cfg = MeshConfig.build(6, 4)  # 4 does not divide 6 -> clamp to 3
    assert (cfg.dp, cfg.mp) == (2, 3)
    assert _counter("sharding.clamped") - before == 1
    # coercion: a jax Mesh, a MeshConfig, and None all round-trip
    assert MeshConfig.of(make_mesh(8, mp=2)) == MeshConfig(8, 4, 2)
    assert MeshConfig.of(cfg) is cfg
    assert MeshConfig.of(None) is None
    assert mesh_dp(cfg) == 2 and mesh_dp(None) is None


def test_mesh_config_from_env(monkeypatch):
    monkeypatch.delenv("FTS_MESH_DEVICES", raising=False)
    assert MeshConfig.from_env() is None
    assert st.default_dp() == 1 and st.default_mp() == 1
    monkeypatch.setenv("FTS_MESH_DEVICES", "8")
    monkeypatch.setenv("FTS_MESH_MP", "2")
    assert MeshConfig.from_env() == MeshConfig(8, 4, 2)
    assert st.default_dp() == 4 and st.default_mp() == 2
    # FTS_DP_SHARDS wins over the mesh env for the row runner
    monkeypatch.setenv("FTS_DP_SHARDS", "3")
    assert st.default_dp() == 3
    # garbage env degrades to unsharded, never raises
    monkeypatch.setenv("FTS_DP_SHARDS", "zap")
    monkeypatch.setenv("FTS_MESH_DEVICES", "zap")
    assert st.default_dp() == 1 and st.default_mp() == 1


def test_shard_rows_pads_ragged_batch():
    """B % dp != 0 pads rows to the span boundary (counted) instead of
    erroring; the placed array keeps the padded leading extent."""
    mesh = make_mesh(8, mp=2)  # dp=4
    rng = random.Random(3)
    pts = np.stack([cv.encode_point(hm.rand_g1(rng)) for _ in range(5)])
    before = _counter("sharding.padded_rows")
    placed = shard_rows(pts, mesh)
    assert placed.shape[0] == 8  # 5 -> next dp=4 boundary
    assert _counter("sharding.padded_rows") - before == 3
    got = np.asarray(placed)
    assert np.array_equal(got[:5], pts)
    assert np.array_equal(got[5:], np.broadcast_to(pts[:1], (3,) + pts.shape[1:]))
    # an aligned batch is placed untouched
    before = _counter("sharding.padded_rows")
    assert shard_rows(pts[:4], mesh).shape[0] == 4
    assert _counter("sharding.padded_rows") - before == 0


def test_run_rows_sharded_failure_degrades_to_unsharded(rng, monkeypatch):
    """Degrade chain, first link: a sharded-dispatch crash falls back to
    the unsharded runner with identical output (`sharding.fallbacks`)."""
    pts = np.stack([cv.encode_point(hm.rand_g1(rng)) for _ in range(11)])
    expected = st.g1_add_rows(pts, pts)

    def boom(*a, **k):
        raise RuntimeError("injected sharded-dispatch failure")

    # break the span partitioner INSIDE run_tile_spans' guarded region:
    # the dispatch crashes, the sequential walk must still answer
    monkeypatch.setattr(st, "dp_spans", boom)
    before = _counter("sharding.fallbacks")
    got = st.g1_add_rows(pts, pts, dp=4)
    assert _counter("sharding.fallbacks") - before == 1
    assert np.array_equal(got, expected)


def test_dp_spans_are_tile_aligned_and_cover():
    """The per-shard dispatch partitions the tile range exactly: spans
    are contiguous, non-overlapping, and never exceed the shard count."""
    for ntiles in (1, 2, 3, 7, 8, 13):
        for dp in (1, 2, 4, 8, 32):
            spans = st.dp_spans(ntiles, dp)
            assert len(spans) == min(dp, ntiles)
            assert spans[0][0] == 0 and spans[-1][1] == ntiles
            for (a, b), (c, _) in zip(spans, spans[1:]):
                assert a < b == c
    # edge cases pinned explicitly: ntiles < dp collapses to one tile per
    # span; dp=1 is the no-op identity span; uneven ntiles front-loads
    assert st.dp_spans(3, 8) == [(0, 1), (1, 2), (2, 3)]
    assert st.dp_spans(13, 1) == [(0, 13)]
    assert st.dp_spans(13, 4) == [(0, 4), (4, 7), (7, 10), (10, 13)]


def _kernel_cases(rng, N, heavy: bool):
    """(name, fn(dp)) pairs covering every stage kernel; the two
    variable-base scalar-mul tiles (~10-20s per warm dispatch on a
    small CPU host) are the `heavy` subset, exercised by the
    slow-marked full-matrix test so tier-1 stays in budget."""
    L = 32
    g1 = np.stack([cv.encode_point(hm.rand_g1(rng)) for _ in range(N)])
    g1b = np.stack([cv.encode_point(hm.rand_g1(rng)) for _ in range(N)])
    scal = np.asarray(cv.encode_scalars(
        [rng.randrange(hm.R) for _ in range(N)]
    ))
    from fabric_token_sdk_tpu.ops import curve2 as cv2

    g2pts = [hm.rand_g2(rng) for _ in range(2)]
    g2 = np.asarray(cv2.encode_points(
        [g2pts[i % 2] for i in range(N)]
    ))
    g2b = np.asarray(cv2.encode_points(
        [g2pts[(i + 1) % 2] for i in range(N)]
    ))
    from fabric_token_sdk_tpu.crypto.pedersen import BatchedPedersen

    ped = BatchedPedersen([hm.rand_g1(rng) for _ in range(3)])
    msm_scal = np.asarray(
        cv.encode_scalars(
            [rng.randrange(hm.R) for _ in range(3 * N)]
        )
    ).reshape(N, 3, L)
    if heavy:
        return [
            ("g1_mul", lambda dp: st.g1_mul_rows(g1, scal, dp=dp)),
            ("g2_mul", lambda dp: st.g2_mul_rows(g2, scal, dp=dp)),
        ]
    return [
        ("g1_msm", lambda dp: ped.commit_rows(msm_scal, dp=dp)),
        ("g1_add", lambda dp: st.g1_add_rows(g1, g1b, dp=dp)),
        ("g1_sub", lambda dp: st.g1_sub_rows(g1, g1b, dp=dp)),
        ("g1_to_affine", lambda dp: st.g1_to_affine_rows(g1, dp=dp)),
        ("g2_add", lambda dp: st.g2_add_rows(g2, g2b, dp=dp)),
        ("g2_to_affine", lambda dp: st.g2_to_affine_rows(g2, dp=dp)),
    ]


def test_stage_kernels_sharded_bit_identity(rng):
    """Satellite acceptance: dp-sharded dispatch is bit-identical to the
    unsharded runner, per stage kernel, on a ragged batch (uneven
    spans). The two variable-base mul tiles are covered by the
    slow-marked full matrix below (their sharded parity ALSO runs
    non-slow inside `test_sharded_schnorr_rows_matches_host` and the
    sharded verifier/prover differentials); dp > ntiles and
    span-partition edges by `test_dp_spans_are_tile_aligned_and_cover` /
    `test_run_rows_dp_parity`."""
    for name, fn in _kernel_cases(rng, 11, heavy=False):
        assert np.array_equal(fn(3), fn(1)), name


@pytest.mark.slow
def test_every_stage_kernel_sharded_bit_identity_matrix(rng):
    """Full matrix: EVERY stage kernel (heavy muls included) across
    several dp extents, incl. dp > ntiles."""
    for heavy in (False, True):
        for name, fn in _kernel_cases(rng, 11, heavy=heavy):
            base = fn(1)
            for dp in (2, 3, 8):
                assert np.array_equal(fn(dp), base), (name, dp)


def test_sharded_schnorr_rows_matches_host(rng):
    """Per-shard stage-tile dispatch of the Schnorr reconstruction (the
    WF verify composition) over dp == host math, and sharding compiles
    ZERO new programs (same canonical tile executables)."""
    bases = [hm.rand_g1(rng) for _ in range(3)]
    table = cv.FixedBaseTable(bases)
    mesh = make_mesh(8, mp=2)  # dp=4
    N = 18  # 3 tiles of 8 rows (padded) split across 4 dp shards
    resp = np.zeros((N, 3, 32), dtype=np.int32)
    stmt = np.zeros((N, 3, 32), dtype=np.int32)
    chal = np.zeros((N, 32), dtype=np.int32)
    expected = []
    for i in range(N):
        c = rng.randrange(hm.R)
        zs = [rng.randrange(hm.R) for _ in range(3)]
        pt = hm.rand_g1(rng)
        chal[i] = np.asarray(cv.encode_scalars([c]))[0]
        stmt[i] = cv.encode_point(pt)
        resp[i] = np.asarray(cv.encode_scalars(zs))
        expected.append(
            hm.g1_add(hm.g1_multiexp(bases, zs), hm.g1_neg(hm.g1_mul(pt, c)))
        )
    # warm the tiles (may compile on a cold cache), then pin zero-new
    unsharded = sharded_schnorr_rows(table, resp, stmt, chal, mesh=None)
    compiles = "jax.core.compile.backend_compile_duration.seconds"
    before = mx.REGISTRY.histogram(compiles).count
    sharded_before = mx.REGISTRY.counter("stages.sharded_calls").value
    out = sharded_schnorr_rows(table, resp, stmt, chal, mesh)
    assert mx.REGISTRY.histogram(compiles).count - before == 0, (
        "dp sharding compiled a new program -- the per-shard dispatch must "
        "reuse the canonical tile executables"
    )
    assert mx.REGISTRY.counter("stages.sharded_calls").value > sharded_before
    assert cv.decode_points(out) == expected
    assert cv.decode_points(unsharded) == expected


def test_run_rows_dp_parity(rng):
    """run_rows_dp over any dp equals the unsharded stage runner."""
    pts = np.stack(
        [cv.encode_point(hm.rand_g1(rng)) for _ in range(11)]
    )
    base = st.g1_add_rows(pts, pts)
    for dp in (2, 3, 8):
        got = run_rows_dp(cv.add, pts, pts, dp=dp)
        assert np.array_equal(got, base)


@pytest.fixture(scope="module")
def zk_pp():
    from fabric_token_sdk_tpu.crypto.setup import setup

    return setup(base=4, exponent=2, rng=random.Random(0xF75))


@pytest.fixture(scope="module")
def zk_prover(zk_pp):
    """One prover per module — window tables are the expensive part;
    the mesh is re-bound per test via set_mesh (dispatch state only)."""
    from fabric_token_sdk_tpu.crypto.batch_prove import BatchedTransferProver

    return BatchedTransferProver(zk_pp)


def _wf_reqs(zk_pp, rng, n):
    """n (1,1)-shape witness/commitment requests (WF-only: non-slow)."""
    from fabric_token_sdk_tpu.crypto import token as tok

    reqs = []
    for _ in range(n):
        it, iw = tok.tokens_with_witness([7], "USD", zk_pp.ped_params, rng)
        ot, ow = tok.tokens_with_witness([7], "USD", zk_pp.ped_params, rng)
        reqs.append((iw, ow, it, ot))
    return reqs


def test_sharded_verifier_verdicts_bit_identical(zk_pp, zk_prover, rng):
    """Tentpole acceptance: the mesh-sharded `BatchedTransferVerifier`
    returns BIT-IDENTICAL verdicts to the unsharded one — valid rows AND
    a tampered row (sharding shards dispatch, never semantics). One
    verifier instance, mesh re-bound via `set_mesh` (tables are built
    once; the mesh is dispatch state)."""
    from fabric_token_sdk_tpu.crypto.batch import BatchedTransferVerifier

    reqs = _wf_reqs(zk_pp, rng, 5)
    zk_prover.set_mesh(None)
    proofs = zk_prover.prove(reqs, random.Random(11))
    bad = bytearray(proofs[2])
    bad[len(bad) // 2] ^= 1
    proofs[2] = bytes(bad)
    txs = [(r[2], r[3], p) for r, p in zip(reqs, proofs)]

    verifier = BatchedTransferVerifier(zk_pp)
    plain = verifier.verify(txs)
    before = _counter("stages.sharded_calls")
    verifier.set_mesh(MeshConfig.build(8, 2))
    assert verifier.wf.mesh == MeshConfig(8, 4, 2)  # propagated
    sharded = verifier.verify(txs)
    assert _counter("stages.sharded_calls") > before
    assert np.array_equal(plain, sharded)
    assert sharded.tolist() == [True, True, False, True, True]


def test_sharded_prover_proofs_byte_identical(zk_pp, zk_prover, rng):
    """The mesh-sharded `BatchedTransferProver` emits byte-identical
    proofs (same draws, same transcripts — dp only partitions the
    commit-phase dispatch), and `set_mesh` re-binds a live instance."""
    reqs = _wf_reqs(zk_pp, rng, 3)
    zk_prover.set_mesh(None)
    plain = zk_prover.prove(reqs, random.Random(42))
    zk_prover.set_mesh(MeshConfig.build(8, 2))
    assert plain == zk_prover.prove(reqs, random.Random(42))
    zk_prover.set_mesh(None)
    assert plain == zk_prover.prove(reqs, random.Random(42))


@pytest.mark.slow
def test_sharded_pairing_product_staged_parity(rng):
    """dp x mp staged pairing dispatch == unsharded staged == host math,
    on a ragged batch (B=5 over dp=4)."""
    from fabric_token_sdk_tpu.crypto import pssign
    from fabric_token_sdk_tpu.ops import pairing as pr
    from fabric_token_sdk_tpu.parallel import sharded_pairing_product

    mesh = make_mesh(8, mp=2)
    signer = pssign.keygen(1, rng)
    B = 5
    msgs = [[rng.randrange(100)] for _ in range(B)]
    sigs = [signer.sign(m, rng) for m in msgs]
    Ps = np.stack([
        pr.encode_g1([hm.g1_neg(s.S), s.R]) for s in sigs
    ])
    Qs = np.stack([
        pr.encode_g2([signer.Q, signer.message_base(m)]) for m in msgs
    ])
    plain = pr.pairing_product_staged(Ps, Qs, dp=1, mp=1)
    before = _counter("pairing.staged.sharded_calls")
    sharded = sharded_pairing_product(Ps, Qs, mesh)
    assert _counter("pairing.staged.sharded_calls") > before
    assert np.array_equal(plain, sharded)
    assert pr.gt_is_one_host(sharded).all()


def test_multichip_deadline_emits_degraded_result(tmp_path):
    """Satellite acceptance: a dry run that blows its deadline leaves a
    PARSED `MULTICHIP.result.json` (ok=false, degraded, live phase) and
    the flight sidecar — never a silent rc=124."""
    import json
    import subprocess
    import sys as _sys

    sidecar = tmp_path / "MULTICHIP.metrics.json"
    env = dict(os.environ)
    # the child must see itself as a STANDALONE entry point (watchdog,
    # sidecars) — not as running inside this pytest process
    env.pop("PYTEST_CURRENT_TEST", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "FTS_MULTICHIP_DEADLINE": "2",
        "FTS_METRICS_SIDECAR": str(sidecar),
    })
    proc = subprocess.run(
        [_sys.executable,
         os.path.join(os.path.dirname(__file__), "..", "__graft_entry__.py"),
         "--dryrun", "8"],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 1, proc.stderr[-2000:]
    result_path = tmp_path / "MULTICHIP.result.json"
    assert result_path.exists(), proc.stderr[-2000:]
    doc = json.loads(result_path.read_text())
    assert doc["ok"] is False and doc["degraded"] is True
    assert doc["n_devices"] == 8
    assert isinstance(doc["phase"], str) and doc["phase"]
    assert doc["deadline_s"] == 2.0
    assert (tmp_path / "MULTICHIP.flight.json").exists()
    assert sidecar.exists()


@pytest.mark.slow
def test_graft_dryrun_multichip():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)
