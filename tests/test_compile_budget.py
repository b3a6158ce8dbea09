"""Compile-budget regression guards (the round-5 review's weak #4).

The staged stage/pairing tiles exist so that the number of distinct XLA
programs stays a SMALL CONSTANT as batch size, transfer shape
`(n_in, n_out)`, and parameter set vary — a per-shape program explosion
is what turned round 5 into rc=124 on a 1-core-compile host, and what
made the old fused `_wf_kernel` cost more than the whole tier-1 budget.
The `jax.core.compile.backend_compile_duration` histogram (registered in
`ops/__init__.py`) counts actual backend compiles, so these tests pin
the budget directly.
"""

import os
import random

import jax
import numpy as np
import pytest

from fabric_token_sdk_tpu.crypto import batch, hostmath as hm
from fabric_token_sdk_tpu.crypto import token as tok, wellformedness as wf
from fabric_token_sdk_tpu.crypto.setup import setup
from fabric_token_sdk_tpu.ops import curve as cv, pairing as pr, stages as st
from fabric_token_sdk_tpu.utils import devobs
from fabric_token_sdk_tpu.utils import metrics as mx

COMPILES = "jax.core.compile.backend_compile_duration.seconds"

# every program the full staged BatchedTransferVerifier path may touch:
# 3x g1 msm + g1 mul/sub/to-affine + 3x g2 + miller + per-K product +
# final-exp + slack for incidental host-glue lowering
TRANSFER_PROGRAM_BUDGET = 16


def _compiles() -> int:
    return mx.REGISTRY.histogram(COMPILES).count


@pytest.fixture(scope="module")
def pp():
    return setup(base=4, exponent=2, rng=random.Random(0xF75))


def _wf_txs(pp, rng, in_vals, out_vals, count):
    txs = []
    for _ in range(count):
        in_toks, in_w = tok.tokens_with_witness(in_vals, "USD", pp.ped_params, rng)
        out_toks, out_w = tok.tokens_with_witness(out_vals, "USD", pp.ped_params, rng)
        raw = wf.TransferWFProver(
            wf.TransferWFWitness(
                "USD",
                [w.value for w in in_w], [w.bf for w in in_w],
                [w.value for w in out_w], [w.bf for w in out_w],
            ),
            pp.ped_params, in_toks, out_toks, rng,
        ).prove()
        txs.append((in_toks, out_toks, raw))
    return txs


def test_stage_rows_program_count_is_batch_invariant(rng):
    """`stages.run_rows` slices every flat-row batch into slabs of one height,
    so changing the batch size must compile ZERO new programs — and the
    window table is an ARGUMENT, so a different table of the same base
    count must share the executable too."""
    bases = [hm.g1_mul(hm.G1_GEN, 3 + i) for i in range(3)]
    table = cv.FixedBaseTable(bases)

    def scal(B):
        return np.stack(
            [cv.encode_scalars([rng.randrange(hm.R) for _ in range(3)])
             for _ in range(B)]
        )

    before = _compiles()
    st.g1_msm_rows(table.flat, scal(3))
    first = _compiles() - before
    # one canonical tile shape -> at most one program (0 if an earlier
    # test in this session already compiled it)
    assert first <= 1, f"msm tile compiled {first} programs for one shape"

    before = _compiles()
    st.g1_msm_rows(table.flat, scal(11))
    assert _compiles() - before == 0, (
        "changing batch size recompiled the msm tile — the one-height slab "
        "contract is broken"
    )

    table2 = cv.FixedBaseTable([hm.g1_mul(hm.G1_GEN, 7 + i) for i in range(3)])
    before = _compiles()
    st.g1_msm_rows(table2.flat, scal(2))
    assert _compiles() - before == 0, (
        "a different parameter set recompiled the msm tile — tables must "
        "be arguments, not baked constants"
    )


STAGE_PROGRAM_NAMES = (
    "g1_msm1_tile", "g1_msm2_tile", "g1_msm3_tile", "g1_mul_tile",
    "g1_add_tile", "g1_sub_tile", "g1_to_affine_tile", "g2_mul_tile",
    "g2_add_tile", "g2_to_affine_tile",
)


def test_stage_program_names_are_the_ten():
    assert sorted(n for n, _f, _s in st.stage_programs()) == sorted(
        STAGE_PROGRAM_NAMES)


@pytest.mark.parametrize("config", [
    "fabtoken-fungible", "zkatdlog-b300e5", "zkatdlog-b300e5-testnet",
    "zkatdlog-fungible"])
def test_benchmark_configs_warm_only_registered_programs(config):
    """A name in a configuration's `warm_programs` that the registry
    does not have is a program the benchmark cannot compile ahead: it
    would compile inside the window."""
    import json

    from fabric_token_sdk_tpu.ops import warmup as wu

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "benchmark", "configs", config + ".json")
    with open(path, encoding="utf-8") as fh:
        cfg = json.load(fh)
    registered = {name for name, _fn, _shapes in wu.all_programs(True, True)}
    assert len(registered) == 14
    warm = cfg["warm_programs"]
    assert warm and set(warm) <= registered
    assert set(cfg["rehearsal"].get("warm_programs", [])) <= registered


@pytest.mark.parametrize("backend", ["host", "tpu"])
@pytest.mark.parametrize("name", STAGE_PROGRAM_NAMES)
def test_registered_shape_is_the_dispatched_shape(monkeypatch, name, backend):
    """"No backend compile inside the window" rests on this: the shape
    `stage_programs()` registers for a program (what `warmup` and the
    benchmark's `warm_programs` compile ahead) is the only shape
    `run_rows` ever hands that program, whatever the batch size — on
    this backend and on the chip's tile height alike. The kernel is
    replaced by a recorder (no arithmetic, no compile): what is pinned is
    the runner's slicing."""
    monkeypatch.setattr(st, "_on_tpu", lambda: backend == "tpu")
    fn, shapes = {n: (f, sh) for n, f, sh in st.stage_programs()}[name]
    n_consts = 1 if fn is st._g1_msm_tile else 0
    seen = set()

    def recorder(*args):
        seen.add(tuple(a.shape for a in args))
        out = jax.eval_shape(fn, *args)
        return np.zeros(out.shape, np.int32)

    # the runner names a kernel by identity: the recorder stands for `name`
    monkeypatch.setattr(st, "_PROGRAM_NAMES", {id(recorder): name})
    T = st.tile_rows(name)
    assert all(sh[0] == T for sh in shapes[n_consts:])
    consts = tuple(np.zeros(sh, np.int32) for sh in shapes[:n_consts])
    before = devobs.snapshot().get(("stages", name), {"dispatches": 0})
    sweep = (1, 3, T - 1, T, T + 3, 2 * T + 1)
    for B in sweep:
        arrays = [np.zeros((B,) + sh[1:], np.int32) for sh in shapes[n_consts:]]
        out = st.run_rows(recorder, *arrays, consts=consts)
        assert out.shape[0] == B
    assert seen == {tuple(shapes)}, (name, seen)
    e = devobs.snapshot()[("stages", name)]
    assert e["dispatches"] - before["dispatches"] == len(sweep)
    assert e["tile_rows"] == T


def test_dispatch_ledger_pins_program_set_across_batch_sweep(rng):
    """The dispatch ledger is the witness for the compile-budget story:
    sweeping batch size across the msm row runner must grow DISPATCHES
    but never the set of distinct (plane, program) frames — one
    canonical tile program per plane, whatever the batch size. This is
    the same invariant `_compiles()` pins from the XLA side, asserted
    from the ledger side."""
    from fabric_token_sdk_tpu.utils import devobs

    bases = [hm.g1_mul(hm.G1_GEN, 31 + i) for i in range(3)]
    table = cv.FixedBaseTable(bases)

    def scal(B):
        return np.stack(
            [cv.encode_scalars([rng.randrange(hm.R) for _ in range(3)])
             for _ in range(B)]
        )

    before = devobs.snapshot()
    sweep = (1, 3, 11, 32)
    for B in sweep:
        st.g1_msm_rows(table.flat, scal(B))
    after = devobs.snapshot()

    def disp(snap, frame):
        return snap.get(frame, {}).get("dispatches", 0)

    grown = {f for f in after if disp(after, f) > disp(before, f)}
    # the whole sweep lands on ONE frame: the stages plane, the one
    # canonical 3-base msm tile program
    assert grown == {("stages", "g1_msm3_tile")}, grown
    frame = ("stages", "g1_msm3_tile")
    assert disp(after, frame) - disp(before, frame) == len(sweep)
    rows = after[frame]["rows"] - before.get(frame, {}).get("rows", 0)
    padded = after[frame]["padded_rows"] - before.get(frame, {}).get(
        "padded_rows", 0
    )
    assert rows == sum(sweep)
    assert padded == sum((-B) % st.tile_rows("g1_msm3_tile") for B in sweep)
    # and the sweep compiled at most the one tile program (0 when an
    # earlier test already compiled it), never one per batch size
    assert after[frame]["compiles"] - before.get(frame, {}).get(
        "compiles", 0
    ) <= 1


def test_wf_verifier_is_transfer_shape_invariant(rng, pp):
    """The staged BatchedWFVerifier must compile ZERO new programs for a
    second, differently-shaped (n_in, n_out) block — the guarantee the
    old fused per-shape `_wf_kernel` lacked."""
    v = batch.BatchedWFVerifier(pp)
    got = v.verify(_wf_txs(pp, rng, [5, 10], [7, 8], 2))
    assert got.tolist() == [True, True]

    before = _compiles()
    got = v.verify(_wf_txs(pp, rng, [9], [4, 3, 2], 2))
    assert got.tolist() == [True, True]
    assert _compiles() - before == 0, (
        "a new (n_in, n_out) shape compiled new XLA programs — the staged "
        "WF path must be shape-invariant"
    )


def test_host_batch_path_compiles_zero_programs(rng, pp):
    """The batch-first HOST validation plane (FTS_HOST_BATCH) is pure
    host work — native ctypes multiexp, one batched sha256 dispatch,
    column arithmetic, thread-pool fan-out. Committing a zk block whose
    rows ALL route to the host passes (min_batch above the block size:
    every plannable row is a device leftover consumed by
    `_host_proof_batch`, signatures by the block sign batch) must
    compile ZERO XLA programs. No warmup gate: this holds cold."""
    from test_orderer import build_env, issue_to, manual_transfer
    from fabric_token_sdk_tpu.drivers.zkatdlog import ZKATDLogDriver
    from fabric_token_sdk_tpu.services.network import BlockPolicy

    network, parties, issuer, alice, bob = build_env(
        lambda: ZKATDLogDriver(pp),
        BlockPolicy(max_block_txs=8, min_batch=99, use_batched=True),
    )
    alice_p = parties["alice-node"]
    issue_to(parties, alice, [5] * 3, "hb-seed")
    reqs = [
        manual_transfer(alice_p, tid, 5, bob.recipient_identity(), f"hb-{i}")
        for i, tid in enumerate(alice_p.vault.token_ids())
    ]

    hb_before = mx.REGISTRY.counter("hostbatch.proof.rows").value
    before = _compiles()
    events = network.submit_many([r.to_bytes() for r in reqs])
    assert all(e.status.value == "Valid" for e in events)
    # the block really rode the host batch pass...
    assert mx.REGISTRY.counter("hostbatch.proof.rows").value - hb_before == 3
    # ...which compiled nothing: the host path never touches XLA
    assert _compiles() - before == 0, (
        "the batch-first host validation path compiled XLA programs — "
        "host batching must stay off the device plane entirely"
    )


@pytest.mark.skipif(
    os.environ.get("FTS_WARMUP") != "1",
    reason="needs the FTS_WARMUP=1 session precompile (conftest fixture)",
)
def test_block_validation_compiles_zero_programs_after_warmup(rng, pp):
    """Non-slow guard for the ORDERER's batched plane: after the session
    warmup precompiled the canonical program set, committing a block of
    same-shape zkatdlog transfers through `Network.submit_many` (grouping
    -> BatchedTransferVerifier -> MVCC commit) must MISS the compilation
    cache zero times — the product path never pays a surprise compile."""
    from test_orderer import build_env, issue_to, manual_transfer
    from fabric_token_sdk_tpu.drivers.zkatdlog import ZKATDLogDriver
    from fabric_token_sdk_tpu.services.network import BlockPolicy

    network, parties, issuer, alice, bob = build_env(
        lambda: ZKATDLogDriver(pp), BlockPolicy(max_block_txs=8, min_batch=2)
    )
    alice_p = parties["alice-node"]
    issue_to(parties, alice, [5] * 4, "cb-seed")
    reqs = [
        manual_transfer(alice_p, tid, 5, bob.recipient_identity(), f"cb-{i}")
        for i, tid in enumerate(alice_p.vault.token_ids())
    ]

    bt_before = mx.REGISTRY.counter("batch.transfer.txs").value
    misses_before = mx.REGISTRY.counter(
        "jax.compilation_cache.cache_misses"
    ).value
    events = network.submit_many([r.to_bytes() for r in reqs])
    assert all(e.status.value == "Valid" for e in events)
    # the block really rode the device plane...
    assert mx.REGISTRY.counter("batch.transfer.txs").value - bt_before == 4
    # ...and it compiled nothing new
    misses = (
        mx.REGISTRY.counter("jax.compilation_cache.cache_misses").value
        - misses_before
    )
    assert misses == 0, (
        f"block validation missed the compilation cache {misses} time(s) "
        "after warmup() — the orderer's batched plane escaped the "
        "canonical program set"
    )


@pytest.mark.skipif(
    os.environ.get("FTS_WARMUP") != "1",
    reason="needs the FTS_WARMUP=1 session precompile (conftest fixture)",
)
def test_pipelined_blocks_compile_zero_programs_after_warmup(rng, pp):
    """Tentpole guard: the PIPELINED block engine is pure host-side
    scheduling — streaming TWO zk blocks through the verify/commit
    overlap (stage A on the driving thread, stage B on the commit
    worker) compiles zero new XLA programs and misses the compilation
    cache zero times post-warmup."""
    from test_orderer import build_env, issue_to, manual_transfer
    from fabric_token_sdk_tpu.drivers.zkatdlog import ZKATDLogDriver
    from fabric_token_sdk_tpu.services.network import BlockPolicy

    network, parties, issuer, alice, bob = build_env(
        lambda: ZKATDLogDriver(pp),
        BlockPolicy(max_block_txs=2, min_batch=2, pipeline=True),
    )
    assert network._engine is not None
    alice_p = parties["alice-node"]
    issue_to(parties, alice, [5] * 4, "pcb-seed")
    reqs = [
        manual_transfer(alice_p, tid, 5, bob.recipient_identity(), f"pcb-{i}")
        for i, tid in enumerate(alice_p.vault.token_ids())
    ]

    blocks_before = mx.REGISTRY.counter("orderer.pipeline.blocks").value
    compiles_before = _compiles()
    misses_before = mx.REGISTRY.counter(
        "jax.compilation_cache.cache_misses"
    ).value
    events = network.submit_many([r.to_bytes() for r in reqs])
    assert all(e.status.value == "Valid" for e in events)
    # two transfer blocks really streamed through the engine...
    assert (
        mx.REGISTRY.counter("orderer.pipeline.blocks").value - blocks_before
        >= 2
    )
    # ...with zero new program shapes and zero cache misses
    assert _compiles() - compiles_before == 0, (
        "the pipelined engine compiled a new XLA program — overlap must "
        "be host-side scheduling over the canonical tile executables"
    )
    misses = (
        mx.REGISTRY.counter("jax.compilation_cache.cache_misses").value
        - misses_before
    )
    assert misses == 0, (
        f"pipelined block validation missed the compilation cache "
        f"{misses} time(s) after warmup()"
    )


def test_foreign_cache_dir_is_never_loaded(tmp_path):
    """A persistent cache populated on a DIFFERENT host (mismatched
    HOST_FINGERPRINT marker) must be diverted away from — its AOT entries
    carry foreign CPU features ("could lead to SIGILL", the BENCH_r05
    rc=124) — with the skipped entries counted under
    `jax.cache.foreign_skipped`. A matching or unclaimed dir is reused."""
    from fabric_token_sdk_tpu import ops

    fp = ops.host_fingerprint()
    assert fp == ops.host_fingerprint(), "fingerprint must be stable"
    base = str(tmp_path / "cache")

    # unclaimed: this host claims it and uses it directly
    assert ops._resolve_cache_dir(base, fp) == base
    marker = tmp_path / "cache" / "HOST_FINGERPRINT"
    assert marker.read_text().strip() == fp
    # claimed by this host: reused
    assert ops._resolve_cache_dir(base, fp) == base

    # claimed by a foreign host holding two AOT entries: diverted, and
    # exactly the `-cache` payload files counted (not `-atime` companions)
    marker.write_text("feedfacefeedface\n")
    (tmp_path / "cache" / "jit_foo-cache").write_bytes(b"aot")
    (tmp_path / "cache" / "jit_foo-atime").write_bytes(b"t")
    (tmp_path / "cache" / "jit_bar-cache").write_bytes(b"aot")
    before = mx.REGISTRY.counter("jax.cache.foreign_skipped").value
    got = ops._resolve_cache_dir(base, fp)
    assert got == str(tmp_path / "cache" / f"host-{fp}")
    assert (
        mx.REGISTRY.counter("jax.cache.foreign_skipped").value - before == 2
    )
    # the diverted dir resolves consistently on the next process
    assert ops._resolve_cache_dir(base, fp) == got

    # a torn claim (empty marker: claimant died mid-write) is repaired,
    # not treated as a permanent wildcard match
    marker.write_text("")
    assert ops._resolve_cache_dir(base, fp) == base
    assert marker.read_text().strip() == fp


def test_cache_dir_defaults_to_the_checkout():
    """Nothing placed the cache from outside: it lives at the fixed
    in-checkout path (a cache that moves between runs never hits), not
    under the home directory."""
    import jax

    from fabric_token_sdk_tpu import ops

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert ops.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    assert jax.config.jax_compilation_cache_dir == (
        placed or ops.DEFAULT_CACHE_DIR
    )


def test_cache_dir_placed_from_outside_is_used_exactly(tmp_path):
    """`JAX_COMPILATION_CACHE_DIR` set: the program uses exactly that
    directory — entries land there, and nothing of ours (fingerprint
    marker, per-host subdirectory) is written into it."""
    import subprocess
    import sys

    placed = tmp_path / "placed"
    script = (
        "import jax, jax.numpy as jnp\n"
        "import fabric_token_sdk_tpu.ops\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", script], cwd=repo, capture_output=True,
        text=True, timeout=120,
        env=dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(placed)),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == str(placed)
    names = sorted(os.listdir(placed))
    assert any(n.endswith("-cache") for n in names), names
    assert not any(
        n == "HOST_FINGERPRINT" or n.startswith("host-") for n in names
    ), names


def _prove_reqs(pp, rng, in_vals, out_vals, count):
    reqs = []
    for _ in range(count):
        in_toks, in_w = tok.tokens_with_witness(in_vals, "USD", pp.ped_params, rng)
        out_toks, out_w = tok.tokens_with_witness(out_vals, "USD", pp.ped_params, rng)
        reqs.append((in_w, out_w, in_toks, out_toks))
    return reqs


@pytest.mark.skipif(
    os.environ.get("FTS_WARMUP") != "1",
    reason="needs the FTS_WARMUP=1 session precompile (conftest fixture)",
)
def test_batch_prove_compiles_zero_programs_after_warmup(rng, pp):
    """Non-slow guard for the PROVE plane: after the session warmup,
    batch-proving — including a NEW `(n_in, n_out)` shape — must miss
    the compilation cache zero times and compile zero new programs: the
    batched prover is a composition of the same canonical tiles the
    warmup set covers (`warmup.PROVER_PROGRAMS`)."""
    from fabric_token_sdk_tpu.crypto import batch_prove, transfer as tr

    prover = batch_prove.BatchedTransferProver(pp)
    misses_before = mx.REGISTRY.counter(
        "jax.compilation_cache.cache_misses"
    ).value
    reqs = _prove_reqs(pp, rng, [5, 10], [7, 8], 2)
    proofs = prover.prove(reqs, rng)

    before = _compiles()
    reqs2 = _prove_reqs(pp, rng, [9], [4, 3, 2], 1)
    proofs2 = prover.prove(reqs2, rng)
    assert _compiles() - before == 0, (
        "a new transfer shape compiled new XLA programs — the batched "
        "prover escaped the canonical stage-tile set"
    )
    misses = (
        mx.REGISTRY.counter("jax.compilation_cache.cache_misses").value
        - misses_before
    )
    assert misses == 0, (
        f"batch proving missed the compilation cache {misses} time(s) "
        "after warmup() — warmup.PROVER_PROGRAMS is incomplete"
    )
    # the device-proved proofs are real: the host verifier accepts them
    for (_, _, inputs, outputs), proof in zip(reqs + reqs2, proofs + proofs2):
        tr.TransferVerifier(inputs, outputs, pp).verify(proof)


@pytest.mark.slow
def test_batched_prover_program_budget_and_shape_invariance(rng, pp):
    """Full device prove path (WF + range + membership pairing): at most
    TRANSFER_PROGRAM_BUDGET distinct programs ever — the prover adds only
    the tiny Jacobian-add tile beyond the verify set — and a second,
    differently-shaped batch compiles ZERO new programs."""
    from fabric_token_sdk_tpu.crypto import batch_prove, transfer as tr

    prover = batch_prove.BatchedTransferProver(pp)
    before = _compiles()
    reqs = _prove_reqs(pp, rng, [5, 10], [7, 8], 2)
    proofs = prover.prove(reqs, rng)
    first = _compiles() - before
    assert first <= TRANSFER_PROGRAM_BUDGET, (
        f"staged prove path compiled {first} programs "
        f"(budget {TRANSFER_PROGRAM_BUDGET})"
    )

    before = _compiles()
    reqs2 = _prove_reqs(pp, rng, [9], [5, 4], 1)
    proofs2 = prover.prove(reqs2, rng)
    assert _compiles() - before == 0, (
        "a new transfer shape compiled new XLA programs — the staged "
        "prove path must be shape-invariant"
    )

    for (_, _, inputs, outputs), proof in zip(reqs + reqs2, proofs + proofs2):
        tr.TransferVerifier(inputs, outputs, pp).verify(proof)


@pytest.mark.slow
def test_transfer_verifier_program_budget_and_shape_invariance(rng, pp):
    """Full staged BatchedTransferVerifier (WF + membership pairing +
    range equality): at most TRANSFER_PROGRAM_BUDGET distinct programs
    ever, and a second differently-shaped block compiles ZERO new ones."""
    from fabric_token_sdk_tpu.crypto import transfer as tr

    def transfer_txs(in_vals, out_vals, count):
        txs = []
        for _ in range(count):
            in_toks, in_w = tok.tokens_with_witness(
                in_vals, "USD", pp.ped_params, rng
            )
            out_toks, out_w = tok.tokens_with_witness(
                out_vals, "USD", pp.ped_params, rng
            )
            proof = tr.TransferProver(
                in_w, out_w, in_toks, out_toks, pp, rng
            ).prove()
            txs.append((in_toks, out_toks, proof))
        return txs

    v = batch.BatchedTransferVerifier(pp)
    before = _compiles()
    got = v.verify(transfer_txs([5, 10], [7, 8], 2))
    assert got.tolist() == [True, True]
    first = _compiles() - before
    assert first <= TRANSFER_PROGRAM_BUDGET, (
        f"staged transfer path compiled {first} programs "
        f"(budget {TRANSFER_PROGRAM_BUDGET})"
    )

    # different (n_in, n_out) AND different batch size: zero new programs
    before = _compiles()
    got = v.verify(transfer_txs([9], [5, 4], 1))
    assert got.tolist() == [True]
    assert _compiles() - before == 0, (
        "a new transfer shape compiled new XLA programs — the staged "
        "path must be shape-invariant"
    )

    # empty batch short-circuits without device work
    before = _compiles()
    assert v.verify([]).tolist() == []
    assert _compiles() - before == 0


@pytest.mark.slow
def test_warmup_precompiles_whole_stage_set(rng):
    """After `warmup()`, exercising every group-math stage on real data
    must compile NOTHING new: every program replays from the compilation
    cache. NOTE: this jax's `backend_compile_duration` event also fires
    on persistent-cache LOADS (retrieval time), so the no-new-compiles
    signal is `cache_misses == 0` — exactly what `ftsmetrics show`'s
    compile-summary line surfaces."""
    from fabric_token_sdk_tpu.ops import warmup as wu

    summary = wu.warmup(include_pairing=False)
    assert summary["programs"] == len(list(st.stage_programs()))

    from fabric_token_sdk_tpu.ops import curve2 as cv2

    pts = [hm.g1_mul(hm.G1_GEN, 3 + i) for i in range(3)]
    jac = np.stack([cv.encode_point(p) for p in pts])
    ks = np.stack([cv.encode_scalars([rng.randrange(hm.R)])[0] for _ in pts])
    table1 = cv.FixedBaseTable(pts[:1])
    table2 = cv.FixedBaseTable(pts[:2])
    table3 = cv.FixedBaseTable(pts)
    g2pts = np.asarray(
        cv2.encode_points([hm.g2_mul(hm.G2_GEN, 5 + i) for i in range(3)])
    )

    misses_before = mx.REGISTRY.counter(
        "jax.compilation_cache.cache_misses"
    ).value
    st.g1_msm_rows(table1.flat, ks[:, None, :])
    st.g1_msm_rows(table2.flat, np.stack([ks, ks], axis=1))
    st.g1_msm_rows(table3.flat, np.stack([ks, ks, ks], axis=1))
    st.g1_mul_rows(jac, ks)
    st.g1_add_rows(jac, jac)
    st.g1_sub_rows(jac, jac)
    st.g1_to_affine_rows(jac)
    st.g2_mul_rows(g2pts, ks)
    st.g2_add_rows(g2pts, g2pts)
    st.g2_to_affine_rows(g2pts)
    misses = (
        mx.REGISTRY.counter("jax.compilation_cache.cache_misses").value
        - misses_before
    )
    assert misses == 0, (
        f"{misses} stage program(s) missed the compilation cache after "
        "warmup() — the AOT precompile set is incomplete"
    )


@pytest.mark.slow
def test_staged_pairing_program_budget(rng):
    """The staged pairing pipeline must cost at most 3 distinct programs
    (miller tile, per-K row product, final-exp tile) for a given K, zero
    new programs when only the batch size changes, and at most 1 tiny
    program for a new K."""
    P = hm.g1_mul(hm.G1_GEN, 7)
    Q = hm.g2_mul(hm.G2_GEN, 9)
    negP = hm.g1_neg(P)

    def staged(B, K):
        Ps = np.stack(
            [pr.encode_g1([P, negP] * (K // 2)) for _ in range(B)]
        )
        Qs = np.stack([pr.encode_g2([Q] * K) for _ in range(B)])
        return pr.pairing_product_staged(Ps, Qs)

    before = _compiles()
    gt = staged(2, 2)
    first = _compiles() - before
    # e(P,Q) * e(-P,Q) == 1 — the instrumentation rides a real verify
    assert pr.gt_is_one_host(gt).all()
    # 3 tile programs (miller, per-K product, final-exp) + 1 slack for
    # incidental host-glue lowering; the invariance asserts below are the
    # real explosion guards
    assert first <= 4, f"staged pairing compiled {first} programs (budget 4)"

    before = _compiles()
    staged(5, 2)
    assert _compiles() - before == 0, (
        "batch-size change recompiled a staged pairing program"
    )

    before = _compiles()
    staged(2, 4)
    assert _compiles() - before <= 1, (
        "a new K must cost at most the tiny per-K row-product program"
    )

    # the staged-path counters recorded the work
    assert mx.REGISTRY.counter("pairing.staged.calls").value >= 3
    assert mx.REGISTRY.counter("pairing.staged.rows").value >= 9
