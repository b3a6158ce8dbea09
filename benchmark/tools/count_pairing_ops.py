#!/usr/bin/env python3
"""Derivation of `benchmark/harness/pairing_ops.json`: the Fp
multiplications the plain reference (`crypto/hostmath.py`, pure Python)
spends on one Miller loop and one final exponentiation.

Every multiplication of two field elements in the twin happens inside
`fp2_mul` (3), `fp2_sqr` (2), `fp2_scale` (2), `fp2_inv` (4 + one Fp
inversion) or `fp_inv` (a square-and-multiply ladder to P - 2: one
squaring per bit and one multiplication per set bit). The script wraps
those five, runs the two functions on seeded points and prints the JSON.
CPU only, no JAX; run it again if hostmath's pairing changes.
"""
import json
import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from fabric_token_sdk_tpu.crypto import hostmath as hm  # noqa: E402

COST = {"fp2_mul": 3, "fp2_sqr": 2, "fp2_scale": 2, "fp2_inv": 4}
calls = dict.fromkeys(list(COST) + ["fp_inv"], 0)


def wrap(name):
    inner = getattr(hm, name)

    def counted(*a, **kw):
        calls[name] += 1
        return inner(*a, **kw)

    setattr(hm, name, counted)


for fn in calls:
    wrap(fn)
FP_INV = (hm.P - 2).bit_length() - 1 + bin(hm.P - 2).count("1") - 1


def fp_muls(fn, *args):
    for k in calls:
        calls[k] = 0
    out = fn(*args)
    n = sum(COST[k] * calls[k] for k in COST) + FP_INV * calls["fp_inv"]
    return out, n, dict(calls)


rng = random.Random(24)
p = hm.g1_mul(hm.G1_GEN, rng.randrange(1, hm.R))
q = hm.g2_mul(hm.G2_GEN, rng.randrange(1, hm.R))
f, miller, miller_calls = fp_muls(hm.miller_loop, p, q)
_, fexp, fexp_calls = fp_muls(hm.final_exp, f)
print(json.dumps({
    "source": "benchmark/tools/count_pairing_ops.py over crypto/hostmath.py "
              "(pure Python twin), seeded points",
    "fp_mul_per_miller_leg": miller,
    "fp_mul_per_final_exp": fexp,
    "fp_inv_as_fp_mul": FP_INV,
    "calls": {"miller_loop": miller_calls, "final_exp": fexp_calls},
    "limb_ops_per_fp_mul": 2 * 32 * 32,
    "limb_ops_note": "one multiply and one add per pair of 8-bit limbs of "
                     "two 32-limb operands; reduction not counted",
}, indent=1))
