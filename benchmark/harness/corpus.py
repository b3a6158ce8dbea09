"""Corpus worker: proves, signs and reference-validates groups of requests.

Run as a child process of `benchmark/run.py` (never inside the measured
window, never on the chip: the parent pins this process to the CPU
backend). One *group* is a set-up issue request plus the requests that
spend its outputs, each of the *form* its slot names (a transfer of any
shape, a redeem, or an issue of the issuer's that spends nothing); groups
share no token, so each is built and judged alone.
For every slot of a group the file holds the signed wire request, the
verdict expected by construction, and the verdict of the plain reference:
the same bytes, in slot order, through the scalar host `RequestValidator`
(an in-memory `Network` with the device planes and the batch-first host
passes off).

Everything is a function of (configuration, seed, group index, slot
plan): the same seed gives byte-identical requests.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import random
import struct
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_LEN = struct.Struct(">I")


# ------------------------------------------------------------ group files


def write_group(path: str, meta: dict, blobs: list) -> None:
    """One JSON header line, then the blobs back to back."""
    meta = dict(meta, lengths=[len(b) for b in blobs])
    head = json.dumps(meta).encode()
    tmp = path + ".tmp"
    with open(tmp, "wb") as fh:
        fh.write(_LEN.pack(len(head)) + head)
        for b in blobs:
            fh.write(b)
    os.replace(tmp, path)


def read_group(path: str):
    """-> (meta, [blob, ...]); blob 0 is the issue request."""
    with open(path, "rb") as fh:
        (n,) = _LEN.unpack(fh.read(_LEN.size))
        meta = json.loads(fh.read(n))
        blobs = [fh.read(k) for k in meta["lengths"]]
    return meta, blobs


# ------------------------------------------------------------ deployments


def make_artifacts(config: dict, seed: int, art_dir: str) -> None:
    """`tokengen gen <driver>` with the configuration's parameters: public
    parameters, one issuer, the auditor, one owner. Host only."""
    sys.path.insert(0, os.path.join(ROOT, "cmd"))
    import tokengen

    argv = ["gen", config["tokengen"]["driver"], "--output", art_dir,
            "--auditor", "--owners", "1", "--seed", str(seed)]
    for k in ("base", "exponent"):
        if k in config["tokengen"]:
            argv += [f"--{k}", str(config["tokengen"][k])]
    with contextlib.redirect_stdout(sys.stderr):
        tokengen.main(argv)


class Deployment:
    """Public parameters, keys and the driver of one configuration, read
    from the tokengen artifacts."""

    def __init__(self, config: dict, art_dir: str):
        from fabric_token_sdk_tpu.crypto import hostmath as hm, sign
        from fabric_token_sdk_tpu.crypto.serialization import loads

        self.config = config
        kind = config["tokengen"]["driver"]
        if kind == "dlog":
            from fabric_token_sdk_tpu.crypto.setup import PublicParams
            from fabric_token_sdk_tpu.drivers.zkatdlog import ZKATDLogDriver

            with open(os.path.join(art_dir, "zkatdlog_pp.json"), "rb") as fh:
                self.pp = PublicParams.deserialize(fh.read())
            want = (config["tokengen"]["base"], config["tokengen"]["exponent"])
            got = (self.pp.range_params.base, self.pp.range_params.exponent)
            if got != want:
                raise RuntimeError(f"range parameters {got}, configured {want}")
            self.driver = ZKATDLogDriver(self.pp)
        elif kind == "fabtoken":
            from fabric_token_sdk_tpu.drivers.fabtoken import (
                FabTokenDriver, FabTokenPublicParams,
            )

            with open(os.path.join(art_dir, "fabtoken_pp.json"), "rb") as fh:
                self.pp = FabTokenPublicParams.deserialize(fh.read())
            self.driver = FabTokenDriver(self.pp)
        else:
            raise ValueError(f"unknown tokengen driver {kind!r}")
        self.zk = kind == "dlog"

        def key(path):
            with open(os.path.join(art_dir, path), "rb") as fh:
                d = loads(fh.read())
            public = sign.PublicKey(hm.g1_mul(hm.G1_GEN, d["sk"]))
            return sign.SigningKey(d["sk"], public), d["identity"]

        self.issuer_key, self.issuer_id = key("issuers/issuer0.json")
        self.auditor_key, self.auditor_id = key("auditor/auditor.json")
        self.owner_key, self.owner_id = key("owners/owner0.json")
        if self.pp.auditor != self.auditor_id:
            raise RuntimeError("auditor not set in the public parameters")

    def network(self, policy=None, **kw):
        """A fresh ledger over these parameters, auditor signature required."""
        from fabric_token_sdk_tpu.services.network import Network

        return Network(self.validator(), policy=policy, **kw)

    def validator(self):
        from fabric_token_sdk_tpu.api.validator import RequestValidator

        return RequestValidator(self.driver, self.auditor_id)


# ------------------------------------------------------------ one group


def _tamper(raw: bytes) -> bytes:
    bad = bytearray(raw)
    bad[len(bad) // 2] ^= 0x01
    return bytes(bad)


def _issue_request(dep: Deployment, tx_id: str, values: list, rng) -> tuple:
    """An issue of `values` to the owner, signed by the issuer and the
    auditor. -> (the driver's outcome, the request's bytes)"""
    from fabric_token_sdk_tpu.api.request import IssueRecord, TokenRequest

    owners = [dep.owner_id] * len(values)
    kw = {"anonymous": False, "rng": rng} if dep.zk else {}
    issue = dep.driver.issue(dep.issuer_id, "USD", values, owners, **kw)
    req = TokenRequest(anchor=tx_id)
    req.issues.append(IssueRecord(
        action=issue.action_bytes, issuer=dep.issuer_id,
        outputs_metadata=issue.metadata, receivers=owners))
    req.issues[0].signature = dep.issuer_key.sign(req.marshal_to_sign(), rng)
    req.auditor_signature = dep.auditor_key.sign(req.marshal_to_audit(), rng)
    return issue, req.to_bytes()


def build_issue(dep: Deployment, seed: int, group: str, slots: list,
                forms: dict) -> dict:
    """The group's set-up issue, which the node needs before anything else
    of the group: what its slots spend, one after another (an issue slot
    spends nothing). Built first, so that the parent can hand it over while
    the rest is still being proved. -> what `build_group` goes on from (the
    group's own random stream among it: the bytes of a group do not depend
    on when the rest is built)."""
    rng = random.Random(f"{seed}/{group}")
    t0 = time.monotonic()
    values, first = [], []  # a slot's inputs start at its offset
    for slot in slots:
        first.append(len(values))
        values += forms[slot.get("form", "")].get("in_values", [])
    issue, blob = _issue_request(dep, f"bench-{group}-issue", values, rng)
    return {"group": group, "slots": slots, "forms": forms, "rng": rng,
            "issue": issue, "first": first, "blob": blob,
            "issue_s": time.monotonic() - t0}


def build_group(dep: Deployment, started: dict) -> tuple:
    """The requests behind the issue of `build_issue`. `slots` is the
    group's plan: one entry per request, in the order in which it will be
    sent, `{"kind": "ok" | <bad kind>, "form": <name>, "of": <slot>}`
    (`form`: absent where the mix has the one `transfer`; `of`: the earlier
    slot a double spend re-spends). Returns (meta, blobs)."""
    from fabric_token_sdk_tpu.api.request import TokenRequest, TransferRecord
    from fabric_token_sdk_tpu.crypto.serialization import dumps, loads
    from fabric_token_sdk_tpu.models.token import ID

    group, slots, forms, rng, issue, first = (
        started[f] for f in ("group", "slots", "forms", "rng", "issue", "first"))
    n = len(slots)
    anchor = f"bench-{group}"
    blobs = [started["blob"]]
    issue_s = started["issue_s"]
    form = [forms[s.get("form", "")] for s in slots]

    def inputs(i):
        at = range(first[i], first[i] + len(form[i]["in_values"]))
        return ([ID(f"{anchor}-issue", j) for j in at],
                [issue.outputs[j] for j in at], [issue.metadata[j] for j in at])

    def outputs(i):
        """-> (values, owners); a redeem's first output has no owner, as
        `api/tms.py:add_redeem` builds it."""
        if form[i]["op"] == "redeem":
            change = form[i]["change_values"]
            return ([form[i]["redeem_value"], *change],
                    [b"", *[dep.owner_id] * len(change)])
        return form[i]["out_values"], [dep.owner_id] * len(form[i]["out_values"])

    # a double spend re-sends another slot's action under a new anchor
    own = [i for i, s in enumerate(slots)
           if s["kind"] != "double_spend" and form[i]["op"] != "issue"]
    t0 = time.monotonic()
    specs = [(*inputs(i), "USD", *outputs(i)) for i in own]
    if dep.zk:
        # host prover, as clients prove: below min_batch nothing is batched
        proved = dep.driver.transfer_many(specs, rng=rng, min_batch=len(specs) + 1)
    else:
        proved = dep.driver.transfer_many(specs)
    outcome = dict(zip(own, proved))
    prove_s = time.monotonic() - t0

    expect = []
    for i, slot in enumerate(slots):
        kind = slot["kind"]
        expect.append("Valid" if kind == "ok" else "Invalid")
        if form[i]["op"] == "issue":
            blobs.append(_issue_request(dep, f"{anchor}-{i}",
                                        form[i]["out_values"], rng)[1])
            continue
        src = slot["of"] if kind == "double_spend" else i
        tout = outcome[src]
        action = tout.action_bytes
        if kind == "tampered_proof":
            d = loads(action)
            d["proof"] = _tamper(d["proof"])
            action = dumps(d)
        ids = inputs(src)[0]
        req = TokenRequest(anchor=f"{anchor}-{i}")
        req.transfers.append(TransferRecord(
            action=action, input_ids=ids, senders=[dep.owner_id] * len(ids),
            outputs_metadata=tout.metadata, receivers=outputs(src)[1]))
        payload = req.marshal_to_sign()
        sigs = [dep.owner_key.sign(payload, rng) for _ in ids]
        if kind == "bad_owner_signature":
            # a well-formed signature over other bytes
            sigs[-1] = dep.owner_key.sign(payload + b"x", rng)
        req.transfers[0].signatures = sigs
        req.auditor_signature = dep.auditor_key.sign(req.marshal_to_audit(), rng)
        blobs.append(req.to_bytes())
    meta = {
        "group": group, "anchor": anchor, "slots": slots, "expect": expect,
        "tx_ids": [f"{anchor}-{i}" for i in range(n)],
        "build": {"issue_s": round(issue_s, 3), "prove_s": round(prove_s, 3)},
    }
    return meta, blobs


def reference_verdicts(dep: Deployment, blobs: list) -> list:
    """The plain reference: issue, then every request in slot order, one
    per block, through the scalar host validator. -> [[status, message]]"""
    from fabric_token_sdk_tpu.services.network import BlockPolicy

    net = dep.network(dataclasses.replace(
        BlockPolicy(), use_batched=False, sign_batched=False, pipeline=False))
    ev = net.submit(blobs[0])
    if ev.status.value != "Valid":
        raise RuntimeError(f"reference rejected the issue: {ev.message}")
    out = []
    for raw in blobs[1:]:
        ev = net.submit(raw)
        out.append([ev.status.value, ev.message])
    return out


def main(argv) -> int:
    with open(argv[1]) as fh:
        spec = json.load(fh)
    # the reference is the per-transaction scalar path: no batch-first pass
    os.environ["FTS_HOST_BATCH"] = "0"
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError("the corpus worker must be pinned to the CPU backend")
    sys.path.insert(0, ROOT)
    dep = Deployment(spec["config"], spec["art_dir"])
    # every set-up issue first: the parent hands them to the node meanwhile
    started = []
    for g in spec["groups"]:
        s = build_issue(dep, spec["seed"], g["group"], g["slots"],
                        spec["forms"])
        write_group(os.path.join(spec["out_dir"], f"issue-{g['group']}.bin"),
                    {"group": g["group"]}, [s["blob"]])
        started.append(s)
    for s in started:
        meta, blobs = build_group(dep, s)
        t0 = time.monotonic()
        meta["ref"] = reference_verdicts(dep, blobs)
        meta["build"]["reference_s"] = round(time.monotonic() - t0, 3)
        write_group(os.path.join(spec["out_dir"], f"group-{s['group']}.bin"),
                    meta, blobs)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
