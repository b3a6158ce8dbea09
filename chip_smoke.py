#!/usr/bin/env python3
"""chip_smoke.py — does the zkatdlog ledger path still start on the chip?

Drives the system's main path once, in ONE process, on one TPU chip: a
client (`RemoteNetwork`) submits zkatdlog transfers over a loopback
socket to a `LedgerServer`; the orderer cuts blocks; the batched device
planes verify proofs and signatures; the WAL is appended; finality comes
back to the client. Every phase prints one line with its wall seconds,
the run stops non-zero at the first failed check, and only a run in which
every phase passed prints a result: the summary (phases, compile seconds,
cache hits/misses, counters, `reduced`) as one JSON line, and then, as the
last line of stdout, exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reports it.

The node's degrade-to-host chains are fault handling and stay in the
product; HERE a fallback is a failure: the smoke reads their counters,
flight events and breakers and refuses to pass on the host's work.

Phases, in order of cost:

  device  jax.devices()[0].platform must be "tpu" (else exit non-zero at
          once; `--rehearse-cpu` is the only way to run on a CPU, prints
          "cpu" in its result and is never the default)
  native  remove + rebuild _bn254.so/_fastser.so from the committed C
          sources; the native host runtime must pass its self-check
  field   the numeric assumptions of ops/, bit-exact against Python
          ints: `limbs.mul_full` (f32 dot_general, Precision.HIGH),
          `limbs.mul_const` (one pass at Precision.DEFAULT over byte
          operands), `FP.mul`/`FR.mul`, and `curve.msm_select` (int32
          einsum); prints the product forms of `ops.health()`
  warmup  `ops.warmup.warmup()`: the 14 canonical programs compile (or
          load from the persistent cache), seconds per program
  tiles   one tile of each group/pairing program through the stage
          runner against `crypto/hostmath.py` (after `warmup`, so that
          the compile seconds above are clean)
  setup   `cmd/tokengen.py gen dlog` at its defaults (base 16, exponent
          2) with an auditor; the issue requests
  prove   one device `TransferProver.batch`; the host scalar verifier
          accepts every proof and rejects a bit-flipped one
  serve   default `BlockPolicy`, WAL; the issues, then 4 x 64
          two-in/two-out transfers (one `submit_many` per block), then
          one tampered proof and one double spend, through
          `LedgerServer` / `RemoteNetwork`
  agree   the same request bytes through the scalar host validator
          (`use_batched=False, sign_batched=False`): same verdicts

No `FTS_*` knob is set or honoured here: on the chip the smoke refuses to
run with any in its environment. The two cuts of size (`--blocks`,
`--block-txs`) are listed under `reduced` in the result; the transfer shape
is never cut on the chip.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import random
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# the sizes the issue names; anything smaller is listed under `reduced`
FULL = {"blocks": 4, "block_txs": 64, "shape": (2, 2), "prove_txs": 8,
        "field_pairs": 4096}
REHEARSAL = {"blocks": 1, "block_txs": 4, "shape": (1, 1), "prove_txs": 3,
             "field_pairs": 256}

# the client's socket timeout: one submit_many holds the connection until
# its last block commits (a deployment setting, like the address)
CLIENT_TIMEOUT_S = 900.0

# counters that move only when a device plane gave its work to the host
FALLBACK_COUNTERS = (
    "ledger.block.batch_errors",
    "batch.sign.host_fallbacks",
    "batch.prove.host_fallbacks",
    "resilience.bounded.timeouts",
    "resilience.breaker.open",
    "resilience.breaker.rejected",
    "native.selfcheck.fail",
    "jax.cache.load_failures",
)
FALLBACK_EVENTS = ("verify.host_fallback", "sign.host_fallback")


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


class Run:
    """Phase clock + the result document."""

    def __init__(self):
        self.t0 = time.monotonic()
        self.current = "args"
        self.result = {"ok": False, "phases": {}}

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.monotonic()
        info = {}
        self.current = name
        yield info
        dt = time.monotonic() - t0
        self.result["phases"][name] = {"ok": True, "seconds": round(dt, 3)}
        extra = " ".join(f"{k}={v}" for k, v in info.items())
        print(f"[chip-smoke] phase={name} ok {dt:.1f}s "
              f"(t+{time.monotonic() - self.t0:.0f}s) {extra}", flush=True)


def _counter(name: str) -> int:
    from fabric_token_sdk_tpu.utils import metrics as mx

    return mx.REGISTRY.counter(name).value


def _no_fallbacks(where: str) -> dict:
    """Fail if any device plane degraded to the host so far. Counters
    are the authority (the flight ring is bounded); the ring names the
    event when it still holds it."""
    from fabric_token_sdk_tpu.utils import metrics as mx, resilience

    events = [e for e in mx.FLIGHT.tail() if e["kind"] in FALLBACK_EVENTS]
    check(
        not events,
        f"{where}: flight event {events[0]['kind'] if events else ''} — a "
        f"device plane fell back to the host: {events[:3]}",
    )
    counters = {c: _counter(c) for c in FALLBACK_COUNTERS}
    moved = {c: v for c, v in counters.items() if v}
    check(not moved, f"{where}: fallback/timeout counters moved: {moved}")
    states = resilience.breaker_states()
    not_closed = {p: s for p, s in states.items() if s != "closed"}
    check(not not_closed, f"{where}: breakers not closed: {not_closed}")
    return {"counters": counters, "breakers": states}


# ------------------------------------------------------------------ phases


def phase_device(run: Run, args) -> None:
    with run.phase("device") as info:
        import jax

        devs = jax.devices()
        dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
               "count": len(devs)}
        if args.rehearse_cpu:
            check(dev["platform"] == "cpu",
                  f"--rehearse-cpu given but JAX selected {dev['platform']!r}"
                  " (pin JAX_PLATFORMS=cpu yourself, or drop the flag)")
        else:
            check(dev["platform"] == "tpu",
                  f"no accelerator: jax.devices()[0].platform is "
                  f"{dev['platform']!r}, chip_smoke.py needs a TPU "
                  "(a CPU rehearsal at tiny sizes: --rehearse-cpu)")
            knobs = sorted(k for k in os.environ if k.startswith("FTS_"))
            check(not knobs,
                  f"the smoke runs the defaults; unset {knobs} and re-run")
        run.result["device"] = dev
        run.result["jax"] = jax.__version__
        info.update(dev)


def phase_native(run: Run) -> None:
    with run.phase("native") as info:
        sos = [os.path.join(HERE, "fabric_token_sdk_tpu", "native", so)
               for so in ("_bn254.so", "_fastser.so")]
        for path in sos:
            with contextlib.suppress(FileNotFoundError):
                os.unlink(path)
        check("fabric_token_sdk_tpu.crypto.hostmath" not in sys.modules,
              "hostmath imported before the native rebuild")
        from fabric_token_sdk_tpu import native
        from fabric_token_sdk_tpu.crypto import hostmath as hm

        check(native.native_available(), "_fastser.so did not build/load")
        check(hm.NATIVE_G1, "pure-Python host math: _bn254.so did not "
              "build or failed its self-check")
        check(_counter("native.selfcheck.pass") == 1
              and _counter("native.selfcheck.fail") == 0,
              "native self-check did not pass exactly once")
        for path in sos:
            check(os.path.exists(path), f"{path} missing after rebuild")
            info[os.path.basename(path)] = os.path.getsize(path)


def phase_field(run: Run, rng, pairs: int) -> None:
    """Bit-exact differentials of the lowest layer against Python ints."""
    with run.phase("field") as info:
        import jax
        import numpy as np

        from fabric_token_sdk_tpu.crypto import hostmath as hm
        from fabric_token_sdk_tpu.ops import curve as cv, limbs as lb
        from fabric_token_sdk_tpu.ops.field import FP, FR
        from fabric_token_sdk_tpu.utils import devobs

        W = 1 << (lb.RADIX_BITS * lb.NLIMBS)

        def operands(edges, bound):
            xs = [a for a in edges for _ in edges]
            ys = [b for _ in edges for b in edges]
            while len(xs) < pairs + len(edges) ** 2:
                xs.append(rng.randrange(bound))
                ys.append(rng.randrange(bound))
            return xs, ys

        # limbs.mul_full: any two canonical 256-bit limb vectors
        xs, ys = operands([0, 1, W - 1, hm.P - 1, hm.P, 2 * hm.P - 1], W)
        got = np.asarray(jax.jit(lb.mul_full)(
            lb.ints_to_limbs(xs), lb.ints_to_limbs(ys)))
        want = lb.ints_to_limbs([x * y for x, y in zip(xs, ys)],
                                2 * lb.NLIMBS + 1)
        bad = int((got != want).any(axis=-1).sum())
        check(bad == 0, f"limbs.mul_full inexact on {bad}/{len(xs)} pairs "
              "(f32 dot_general at Precision.HIGH is not exact here)")
        info["mul_full"] = len(xs)

        # limbs.mul_const: canonical limbs by each baked constant, raw
        # columns; the all-255 operand drives every column to its bound
        xs = [0, 1, W - 1] + [rng.randrange(W) for _ in range(pairs)]
        x_limbs = lb.ints_to_limbs(xs)
        for spec in (FP, FR):
            for c in (spec.pprime_limbs, spec.p_limbs):
                ci = lb.limbs_to_int(c)
                for keep in (lb.NLIMBS, 2 * lb.NLIMBS):
                    cols = np.asarray(jax.jit(
                        lambda x, c=c, keep=keep: lb.mul_const(x, c, keep=keep)
                    )(x_limbs))
                    want = [(x * ci) % (1 << (lb.RADIX_BITS * keep)) for x in xs]
                    got = [lb.limbs_to_int(row) % (1 << (lb.RADIX_BITS * keep))
                           for row in cols]
                    bad = sum(1 for g, w in zip(got, want) if g != w)
                    check(bad == 0 and cols.min() >= 0, f"limbs.mul_const inexact "
                          f"on {bad}/{len(xs)} operands ({spec.name}, keep {keep}: "
                          "one pass at Precision.DEFAULT is not exact here)")
        info["mul_const"] = 8 * len(xs)
        info["fp_mul"] = devobs.health_section()["fp_mul"]

        # FP.mul / FR.mul: Montgomery product on the redundant domain
        # [0, 2p): out < 2p and out == x*y*R^-1 (mod p)
        for spec in (FP, FR):
            p = spec.modulus
            xs, ys = operands([0, 1, p - 1, p, p + 1, 2 * p - 1], 2 * p)
            out = np.asarray(spec.mul(lb.ints_to_limbs(xs),
                                      lb.ints_to_limbs(ys)))
            check(bool(((out >= 0) & (out <= lb.MASK)).all()),
                  f"{spec.name}.mul returned non-canonical limbs")
            rinv = pow(W, -1, p)
            zs = lb.batch_limbs_to_ints(out)
            bad = sum(
                1 for x, y, z in zip(xs, ys, zs)
                if z >= 2 * p or z % p != x * y * rinv % p
            )
            check(bad == 0, f"{spec.name}.mul wrong on {bad}/{len(xs)} pairs")
            info[spec.name] = len(xs)

        # curve.msm_select: the int32 one-hot einsum must pick exactly
        # table[t, digit_t] — canonical tile shape, 3 bases
        R, nb, D = 8, 3, cv.DIGITS_PER_SCALAR
        table = np.random.default_rng(rng.getrandbits(32)).integers(
            0, 256, size=(nb * D, 1 << cv.WINDOW_BITS, 3 * lb.NLIMBS),
            dtype=np.int32)
        ks = [[rng.randrange(hm.R) for _ in range(nb)] for _ in range(R)]
        ks[0] = [0, hm.R - 1, 1]
        scal = np.stack([lb.ints_to_limbs(row) for row in ks])
        sel = np.asarray(jax.jit(cv.msm_select)(table, scal))
        want = np.stack([
            np.stack([
                table[b * D + w, (ks[r][b] >> (cv.WINDOW_BITS * w)) & 15]
                for b in range(nb) for w in range(D)
            ]) for r in range(R)
        ]).reshape(R, nb * D, 3, lb.NLIMBS)
        check(np.array_equal(sel, want),
              "curve.msm_select (int32 einsum) is not exact on this device")
        info["msm_select"] = R * nb * D


def phase_warmup(run: Run, pairing: bool, expect_warm: bool) -> None:
    with run.phase("warmup") as info:
        import jax

        from fabric_token_sdk_tpu.ops import warmup as wu

        names = ("compile_requests_use_cache", "cache_hits", "cache_misses")
        before = {n: _counter(f"jax.compilation_cache.{n}") for n in names}
        summary = wu.warmup(include_pairing=pairing)
        d = {n: _counter(f"jax.compilation_cache.{n}") - before[n]
             for n in names}
        want = len(wu.all_programs(include_pairing=pairing))
        check(summary["programs"] == want,
              f"warmup compiled {summary['programs']} of {want} programs")
        check(d["compile_requests_use_cache"] == want,
              f"{d['compile_requests_use_cache']} of {want} compile requests "
              "went through the persistent cache (is it enabled?)")
        # a request that was not a cache hit went to the backend compiler
        compiles = d["compile_requests_use_cache"] - d["cache_hits"]
        cache = {
            "dir": jax.config.jax_compilation_cache_dir,
            "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "requests": d["compile_requests_use_cache"],
            "hits": d["cache_hits"],
            "misses": d["cache_misses"],
            "backend_compiles": compiles,
        }
        if expect_warm:
            check(compiles == 0 and d["cache_misses"] == 0,
                  f"--expect-warm: the warmup set still compiled: {cache}")
        run.result["cache"] = cache
        run.result["warmup"] = {
            "programs": summary["programs"],
            "seconds": summary["seconds"],
            "compile_s": {p["name"]: p["seconds"]
                          for p in summary["per_program"]},
        }
        info.update(programs=want, backend_compiles=compiles,
                    hits=d["cache_hits"], misses=d["cache_misses"],
                    cache=cache["dir"])
        for p in summary["per_program"]:
            print(f"[chip-smoke]   {p['name']:<22} {p['seconds']:8.2f}s",
                  flush=True)


def phase_tiles(run: Run, rng, pairing: bool) -> None:
    """One tile of each group program (and the pairing tiles) through the
    stage runner, against crypto/hostmath.py. Each tile is dispatched
    twice: the first call traces and loads the executable, the second is
    timed as an observation — host clock around the whole call, transfer
    in and read-back included (the dispatch ledger's own `wall_s` closes
    before `run_rows` reads the result back, so it times the enqueue)."""
    with run.phase("tiles") as info:
        import numpy as np

        from fabric_token_sdk_tpu.crypto import hostmath as hm
        from fabric_token_sdk_tpu.ops import (
            curve as cv, curve2 as cv2, pairing as pr, stages as st,
            tower as tw,
        )

        second_s = {}

        def twice(name, fn, *arrays):
            out = fn(*arrays)
            t0 = time.monotonic()
            fn(*arrays)
            second_s[name] = round(time.monotonic() - t0, 4)
            return out

        def g1():
            return hm.g1_mul(hm.G1_GEN, rng.randrange(1, hm.R))

        def g2():
            return hm.g2_mul(hm.G2_GEN, rng.randrange(1, hm.R))

        # g1 add: generic rows + infinity, doubling, inverse
        R = st.tile_rows("g1_add_tile")
        a = [g1() for _ in range(R)]
        b = [g1() for _ in range(R)]
        a[0], b[1], b[2], b[3] = None, None, a[2], hm.g1_neg(a[3])
        got = cv.decode_points(twice(
            "g1_add_tile", st.g1_add_rows,
            np.asarray(cv.encode_points(a)), np.asarray(cv.encode_points(b))))
        check(got == [hm.g1_add(x, y) for x, y in zip(a, b)],
              "g1_add_tile disagrees with hostmath.g1_add")

        # g1 / g2 variable-base scalar mul, edge scalars included
        R = max(st.tile_rows("g1_mul_tile"), st.tile_rows("g2_mul_tile"))
        ks = [rng.randrange(hm.R) for _ in range(R)]
        ks[0], ks[1], ks[2] = 0, 1, hm.R - 1
        pts = [g1() for _ in range(R)]
        got = cv.decode_points(twice(
            "g1_mul_tile", st.g1_mul_rows,
            np.asarray(cv.encode_points(pts)), cv.encode_scalars(ks)))
        check(got == [hm.g1_mul(p, k) for p, k in zip(pts, ks)],
              "g1_mul_tile disagrees with hostmath.g1_mul")
        pts2 = [g2() for _ in range(R)]
        got = cv2.decode_points(twice(
            "g2_mul_tile", st.g2_mul_rows,
            cv2.encode_points(pts2), cv.encode_scalars(ks)))
        check(got == [hm.g2_mul(p, k) for p, k in zip(pts2, ks)],
              "g2_mul_tile disagrees with hostmath.g2_mul")

        # fixed-base msm, 3 bases
        bases = [g1() for _ in range(3)]
        R = st.tile_rows("g1_msm3_tile")
        rows = [[rng.randrange(hm.R) for _ in range(3)] for _ in range(R)]
        got = cv.decode_points(twice(
            "g1_msm3_tile", st.g1_msm_rows, cv.FixedBaseTable(bases).flat,
            np.stack([cv.encode_scalars(r) for r in rows])))
        check(got == [hm.g1_multiexp(bases, r) for r in rows],
              "g1_msm3_tile disagrees with hostmath.g1_multiexp")

        if pairing:
            # one product + final-exp tile of 2-leg rows, whatever Miller
            # tiles they make (on a TPU 128 rows x 2 legs: two of 128)
            legs = [[(g1(), g2()), (g1(), g2())]
                    for _ in range(st.tile_rows("fexp_tile"))]
            Ps = np.stack([pr.encode_g1([p for p, _ in row]) for row in legs])
            Qs = np.stack([pr.encode_g2([q for _, q in row]) for row in legs])
            got = tw.decode_fp12(twice(
                "miller+gt_product_k2+final_exp", pr.pairing_product_staged,
                Ps, Qs))
            check(got == [hm.pairing_product(row) for row in legs],
                  "miller_tile + final_exp_tile disagree with "
                  "hostmath.pairing_product")
        run.result["tiles_second_dispatch_s"] = second_s
        info.update(second_s)
        _no_fallbacks("tiles")


def _tamper(proof: bytes) -> bytes:
    bad = bytearray(proof)
    bad[len(bad) // 2] ^= 0x01
    return bytes(bad)


class Corpus:
    """Public parameters, keys and the pre-signed request corpus, built
    the way `bench.py:_block_throughput` builds it, with the auditor's
    signature on every request. Transfer i spends tokens of issue
    i // width: one issue request per block of transfers, because a
    request is sent whole and the server caps a wire frame at 16 MiB."""

    def __init__(self, rng, out_dir: str, n: int, width: int, shape,
                 seed: int):
        from fabric_token_sdk_tpu.crypto import hostmath as hm, sign
        from fabric_token_sdk_tpu.crypto.serialization import loads
        from fabric_token_sdk_tpu.crypto.setup import PublicParams
        from fabric_token_sdk_tpu.drivers.zkatdlog import ZKATDLogDriver

        sys.path.insert(0, os.path.join(HERE, "cmd"))
        import tokengen

        # the repo's own artifact generator, at its defaults
        # (base 16, exponent 2), with an auditor
        art = os.path.join(out_dir, "tokengen")
        with contextlib.redirect_stdout(sys.stderr):
            tokengen.main(["gen", "dlog", "--output", art, "--auditor",
                           "--owners", "1", "--seed", str(seed)])

        def key(path):
            with open(os.path.join(art, path), "rb") as fh:
                d = loads(fh.read())
            public = sign.PublicKey(hm.g1_mul(hm.G1_GEN, d["sk"]))
            return sign.SigningKey(d["sk"], public), d["identity"]

        with open(os.path.join(art, "zkatdlog_pp.json"), "rb") as fh:
            self.pp = PublicParams.deserialize(fh.read())
        self.issuer_key, self.issuer_id = key("issuers/issuer0.json")
        self.auditor_key, self.auditor_id = key("auditor/auditor.json")
        self.owner_key, self.owner_id = key("owners/owner0.json")
        check(self.pp.auditor == self.auditor_id, "auditor not set in pp")
        check((self.pp.range_params.base, self.pp.range_params.exponent)
              == (16, 2), "tokengen defaults are no longer base 16, exp 2")
        self.rng, self.n, self.width, self.shape = rng, n, width, shape
        self.driver = ZKATDLogDriver(self.pp)
        n_in, n_out = shape
        self.in_values = [100, 55][:n_in]
        total = sum(self.in_values)
        self.out_values = [total] if n_out == 1 else [total - 35, 35]
        self.issues = []  # per block of transfers: (IssueOutcome, bytes)
        self.transfers = {}  # index -> TransferOutcome

    def network(self, policy, **kw):
        """A fresh ledger over these parameters, auditor required."""
        from fabric_token_sdk_tpu.api.validator import RequestValidator
        from fabric_token_sdk_tpu.drivers.zkatdlog import ZKATDLogDriver
        from fabric_token_sdk_tpu.services.network import Network

        return Network(
            RequestValidator(ZKATDLogDriver(self.pp), self.auditor_id),
            policy=policy, **kw)

    def build_issues(self) -> None:
        """Transfers 0..n (n is the tampered one) need their inputs."""
        from fabric_token_sdk_tpu.api.request import IssueRecord, TokenRequest

        for first in range(0, self.n + 1, self.width):
            count = min(self.width, self.n + 1 - first) * len(self.in_values)
            outcome = self.driver.issue(
                self.issuer_id, "USD",
                self.in_values * (count // len(self.in_values)),
                [self.owner_id] * count, anonymous=False, rng=self.rng)
            req = TokenRequest(anchor=f"smoke-issue-{len(self.issues)}")
            req.issues.append(IssueRecord(
                action=outcome.action_bytes, issuer=self.issuer_id,
                outputs_metadata=outcome.metadata,
                receivers=[self.owner_id] * count))
            req.issues[0].signature = self.issuer_key.sign(
                req.marshal_to_sign(), self.rng)
            req.auditor_signature = self.auditor_key.sign(
                req.marshal_to_audit(), self.rng)
            self.issues.append((outcome, req.to_bytes()))

    def _inputs(self, i: int):
        """(ids, token bytes, metadata) of transfer i's inputs."""
        from fabric_token_sdk_tpu.models.token import ID

        k = len(self.in_values)
        block, at = divmod(i, self.width)
        outcome = self.issues[block][0]
        ids = [ID(f"smoke-issue-{block}", k * at + j) for j in range(k)]
        return (ids, outcome.outputs[k * at:k * at + k],
                outcome.metadata[k * at:k * at + k])

    def prove(self, indices, device: bool) -> None:
        owners = [self.owner_id] * len(self.out_values)
        touts = self.driver.transfer_many(
            [(*self._inputs(i), "USD", self.out_values, owners)
             for i in indices],
            rng=self.rng, min_batch=1 if device else len(indices) + 1)
        self.transfers.update(zip(indices, touts))

    def request(self, anchor: str, i: int, action: bytes = None) -> bytes:
        """The signed wire request spending transfer i's inputs."""
        from fabric_token_sdk_tpu.api.request import (
            TokenRequest, TransferRecord,
        )

        tout = self.transfers[i]
        ids = self._inputs(i)[0]
        req = TokenRequest(anchor=anchor)
        req.transfers.append(TransferRecord(
            action=action or tout.action_bytes, input_ids=ids,
            senders=[self.owner_id] * len(ids),
            outputs_metadata=tout.metadata,
            receivers=[self.owner_id] * len(self.out_values)))
        payload = req.marshal_to_sign()
        req.transfers[0].signatures = [
            self.owner_key.sign(payload, self.rng) for _ in ids]
        req.auditor_signature = self.auditor_key.sign(
            req.marshal_to_audit(), self.rng)
        return req.to_bytes()


def phase_prove(run: Run, corpus: Corpus, n_dev: int) -> None:
    with run.phase("prove") as info:
        from fabric_token_sdk_tpu.crypto import transfer as tr

        txs0 = _counter("batch.prove.txs")
        corpus.prove(list(range(n_dev)), device=True)
        check(_counter("batch.prove.txs") - txs0 == n_dev,
              "the device prover did not prove the whole batch")
        check(_counter("batch.prove.host_fallbacks") == 0,
              "batch.prove.host_fallbacks moved: the host proved instead")
        for i in range(n_dev):
            _shape, (ins, outs, proof) = corpus.driver.transfer_batch_plan(
                corpus.transfers[i].action_bytes)
            tr.TransferVerifier(ins, outs, corpus.pp).verify(proof)
        try:
            tr.TransferVerifier(ins, outs, corpus.pp).verify(_tamper(proof))
        except ValueError:
            pass
        else:
            raise SmokeFailure("host verifier accepted a bit-flipped proof")
        info.update(device_proved=n_dev, shape=corpus.shape)
        _no_fallbacks("prove")


def _drive(net, issues: list, groups: list):
    """Submit every issue, then each group of transfers in one
    `submit_many`; `net` is a RemoteNetwork or an in-memory Network.
    Returns ([(tx_id, status, message)] of the transfers, in order,
    {issues_s, transfers_s}): the two parts on the host clock, because an
    issue is host-validated whichever network it goes through and only
    the transfers compare the device plane with the scalar reference."""
    t0 = time.monotonic()
    for raw in issues:
        ev = net.submit(raw)
        check(ev.status.value == "Valid", f"issue rejected: {ev.message}")
    t1 = time.monotonic()
    verdicts = [(e.tx_id, e.status.value, e.message)
                for group in groups for e in net.submit_many(group)]
    clock = {"issues_s": round(t1 - t0, 3),
             "transfers_s": round(time.monotonic() - t1, 3)}
    return verdicts, clock


def _serve(corpus: Corpus, policy, wal_path, issues, groups):
    """Stand up Network + LedgerServer, submit through RemoteNetwork;
    returns (verdicts, clock, ops_health)."""
    from fabric_token_sdk_tpu.services.network.remote import (
        LedgerServer, RemoteNetwork,
    )

    net = corpus.network(policy, wal_path=wal_path)
    server = LedgerServer(network=net).start()
    client = RemoteNetwork(server.address, timeout=CLIENT_TIMEOUT_S)
    try:
        verdicts, clock = _drive(client, issues, groups)
        health = client.ops_health()
    finally:
        client.close()
        server.stop()
    return verdicts, clock, health


def _check_verdicts(verdicts, n: int) -> None:
    bad = [v for v in verdicts[:n] if v[1] != "Valid"]
    check(not bad, f"serve: {len(bad)} of {n} transfers rejected: {bad[:2]}")
    tampered, double = verdicts[n], verdicts[n + 1]
    # the batched plane's False verdict carries this exact message; the
    # host verifier's would continue with ": <reason>"
    check(tampered[1:] == ("Invalid", "invalid transfer proof"),
          "serve: the tampered proof was not rejected by a DEVICE "
          f"verdict: {tampered}")
    check(double[1] == "Invalid" and double[2].endswith("already spent"),
          f"serve: the double spend was not rejected by MVCC: {double}")


def phase_serve(run: Run, corpus: Corpus, out_dir: str, policy):
    with run.phase("serve") as info:
        from fabric_token_sdk_tpu.crypto.serialization import dumps, loads
        from fabric_token_sdk_tpu.utils import metrics as mx

        n = corpus.n
        t0 = time.monotonic()
        # the rest of the corpus is proved on the host, as clients do
        corpus.prove([i for i in range(n + 1) if i not in corpus.transfers],
                     device=False)
        txs = [corpus.request(f"smoke-t{i}", i) for i in range(n)]
        d = loads(corpus.transfers[n].action_bytes)
        d["proof"] = _tamper(d["proof"])
        txs.append(corpus.request("smoke-tampered", n, action=dumps(d)))
        txs.append(corpus.request("smoke-double-spend", 0))
        # one submit_many per block (a 64-tx block of these requests is
        # ~10 MB on the wire, the frame cap 16 MiB), then the two bad
        # ones together: a group of two still rides the device plane
        w = corpus.width
        groups = [txs[i:i + w] for i in range(0, n, w)] + [txs[n:]]
        issues = [raw for _, raw in corpus.issues]
        build_s = time.monotonic() - t0

        before = {c: _counter(c) for c in (
            "ledger.validate.batched", "ledger.validate.host",
            "batch.sign.rows", "batch.transfer.txs",
            "ledger.blocks.committed")}
        t0 = time.monotonic()
        verdicts, clock, health = _serve(
            corpus, policy, os.path.join(out_dir, "ledger.wal"),
            issues, groups)
        serve_s = time.monotonic() - t0
        d = {c: _counter(c) - v for c, v in before.items()}

        _no_fallbacks("serve")
        _check_verdicts(verdicts, n)
        check(d["ledger.validate.batched"] == n + 2
              and d["ledger.validate.host"] == 0,
              f"the device did not decide every transfer: {d}")
        check(d["batch.sign.rows"] > 0,
              "batch.sign.rows did not move: the sign plane never engaged")
        rejected = [e for e in mx.FLIGHT.tail() if e["kind"] == "verify.device"
                    and e["ok"] < e["txs"]]
        check(len(rejected) == 1 and rejected[0]["txs"] - rejected[0]["ok"] == 1,
              f"expected exactly one device rejection, saw {rejected}")
        check(all(s == "closed" for s in health["breakers"].values()),
              f"breakers over the socket: {health['breakers']}")
        programs = health["device"]["programs"]
        ledger = {k: v["dispatches"] for k, v in programs.items()
                  if v["dispatches"]}
        for plane in ("verify", "sign"):
            check(any(k.startswith(plane + ":") for k in ledger),
                  f"ops_health()['device'] lists no {plane}:* dispatch")
        if corpus.shape != (1, 1):
            check(ledger.get("verify:miller_tile") and
                  ledger.get("verify:fexp_tile"),
                  "no pairing dispatch on the verify plane")
        check(health["wal"] and health["wal"]["bytes"] > 0
              and not health["wal"]["poisoned"], f"WAL: {health['wal']}")

        blocks = [
            {k: e[k] for k in ("block", "device_verify_s", "sign_verify_s",
                               "host_validate_s", "wal_s", "overlap_s")
             if k in e} | {"txs": len(e["txs"])}
            for e in mx.FLIGHT.tail() if e["kind"] == "block.commit"
        ][-d["ledger.blocks.committed"]:]
        run.result["serve"] = {
            "transfers": n + 2, "valid": n, "invalid": 2,
            "blocks_committed": d["ledger.blocks.committed"],
            "issue_blocks": len(issues),
            "corpus_build_s": round(build_s, 3),
            "submit_to_last_finality_s": round(serve_s, 3),
            **clock,
            "validate_batched": d["ledger.validate.batched"],
            "sign_rows": d["batch.sign.rows"],
            "request_bytes": sum(len(t) for t in txs),
            "issue_bytes": sum(len(t) for t in issues),
            "block_breakdown": blocks,
            "device_dispatches": ledger,
        }
        run.result["device_programs"] = programs
        info.update(valid=n, invalid=2,
                    blocks_committed=d["ledger.blocks.committed"],
                    batched=d["ledger.validate.batched"],
                    sign_rows=d["batch.sign.rows"], serve_s=round(serve_s, 1),
                    build_s=round(build_s, 1), **clock)
        return issues, groups, verdicts


def phase_agree(run: Run, corpus: Corpus, policy, issues, groups,
                verdicts) -> None:
    """The plain reference: the scalar RequestValidator on hostmath."""
    with run.phase("agree") as info:
        ref = corpus.network(dataclasses.replace(
            policy, use_batched=False, sign_batched=False))
        batched0 = _counter("ledger.validate.batched")
        want, clock = _drive(ref, issues, groups)
        check(_counter("ledger.validate.batched") == batched0,
              "the reference run used the batched plane")
        n = corpus.n
        # a device False verdict carries no reason; the scalar verifier
        # appends one — the only tx whose message may differ, by suffix
        check(want[n][1] == verdicts[n][1]
              and want[n][2].startswith(verdicts[n][2] + ":"),
              f"tampered tx: device {verdicts[n]} vs scalar {want[n]}")
        diff = [(g, w) for i, (g, w) in enumerate(zip(verdicts, want))
                if i != n and g != w]
        check(not diff and len(want) == len(verdicts),
              f"{len(diff)} verdicts differ from the scalar reference: "
              f"{diff[:2]}")
        run.result["agree"] = {"compared": len(want), **clock}
        info.update(run.result["agree"])


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="chip_smoke.py", description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="allow a CPU run at tiny sizes (JAX_PLATFORMS=cpu "
                    "must already be set); never a device result")
    ap.add_argument("--blocks", type=int, help="serve blocks (default 4)")
    ap.add_argument("--block-txs", type=int,
                    help="transfers per block (default 64: BlockPolicy's own)")
    ap.add_argument("--expect-warm", action="store_true",
                    help="fail unless the warmup set loads from the "
                    "persistent cache with zero backend compiles")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out"),
                    help="directory for the WAL, artifacts and result file")
    ap.add_argument("--seed", type=int, default=0xF75)
    args = ap.parse_args(argv)

    size = dict(REHEARSAL if args.rehearse_cpu else FULL)
    if args.blocks:
        size["blocks"] = args.blocks
    if args.block_txs:
        size["block_txs"] = args.block_txs
    size["prove_txs"] = min(size["prove_txs"],
                            size["blocks"] * size["block_txs"])
    reduced = [f"{k}: {size[k]} (of {FULL[k]})" for k in FULL
               if size[k] != FULL[k]]
    if args.rehearse_cpu:
        reduced.append("sign plane forced on (auto resolves to host on cpu)")
    pairing = size["shape"] != (1, 1)
    if not pairing:
        reduced.append("1x1 transfers carry no range proof: the 4 pairing "
                       "programs are neither compiled nor dispatched")

    run = Run()
    run.result.update(reduced=reduced, seed=args.seed,
                      argv=sys.argv[1:] if argv is None else list(argv))
    # scratch for the WAL and the tokengen artifacts (tens of MB at full
    # size); only the result file outlives the run
    out_dir = os.path.join(args.out, "chip_smoke.work")
    try:
        # the script alone is not the program: say so before anything is
        # printed to stdout or the chip is touched
        check(os.path.isdir(os.path.join(HERE, "fabric_token_sdk_tpu")),
              f"no fabric_token_sdk_tpu package beside {__file__}: "
              "chip_smoke.py runs from the root of a checkout")
        phase_device(run, args)
        shutil.rmtree(out_dir, ignore_errors=True)
        os.makedirs(out_dir)
        phase_native(run)

        from fabric_token_sdk_tpu.services.network import BlockPolicy

        rng = random.Random(args.seed)
        phase_field(run, rng, size["field_pairs"])
        phase_warmup(run, pairing, args.expect_warm)
        phase_tiles(run, rng, pairing)

        # the default policy (64-tx blocks, pipeline on, sign_batched
        # auto) unless a cut or the rehearsal says otherwise
        cuts = {}
        if size["block_txs"] != FULL["block_txs"]:
            cuts["max_block_txs"] = size["block_txs"]
        if args.rehearse_cpu:
            cuts["sign_batched"] = True
        policy = dataclasses.replace(BlockPolicy(), **cuts)
        with run.phase("setup") as info:
            corpus = Corpus(rng, out_dir, size["blocks"] * size["block_txs"],
                            size["block_txs"], size["shape"], args.seed)
            corpus.build_issues()
            info.update(issues=len(corpus.issues), base=16, exponent=2)
        phase_prove(run, corpus, size["prove_txs"])
        issues, groups, verdicts = phase_serve(run, corpus, out_dir, policy)
        phase_agree(run, corpus, policy, issues, groups, verdicts)
        final = _no_fallbacks("end of run")
    except SmokeFailure as e:
        run.result["failed"] = {"phase": run.current, "reason": str(e)}
        _write(os.path.join(args.out, "chip_smoke.failed.json"), run.result)
        print(f"[chip-smoke] FAILED phase={run.result['failed']['phase']}: "
              f"{e}", file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)

    run.result.update(ok=True, seconds=round(time.monotonic() - run.t0, 3),
                      **final)
    _write(os.path.join(args.out, "chip_smoke.result.json"), run.result)
    # the full per-program ledger stays in the result file; stdout gets the
    # summary, and after it the verdict line: these two keys and no other
    summary = {k: v for k, v in run.result.items() if k != "device_programs"}
    print(json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": run.result["device"]}),
          flush=True)
    return 0


def _write(path: str, doc: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=1)
        fh.write("\n")


if __name__ == "__main__":
    sys.exit(main())
