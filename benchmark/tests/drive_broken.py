"""Drives one rehearsal run of `benchmark/run.py` with the timed path
broken underneath (used by test_control.py, in a process of its own so
that the break never leaks into another test):

  accept_all   the node's `RequestValidator.validate` swallows every
               rejection: an answer altered where it is produced
  bf16_limbs   the control: `ops.limbs.mul_full` rounds its outer products
               to bfloat16 before the contraction, which is what default
               matmul precision does to the operands on the TPU (the CPU
               backend ignores `precision=`, so the rounding is explicit)
  sound        no break: the same run reads `correct: true`

The `--rehearse-cpu` path is the harness without its look for a chip.
Corpus workers and the generator are separate processes and stay sound.
The cell's traffic is replaced by the mix file named first (the tests'
own: one hand-over that rides the device sign plane).
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))


def accept_all():
    from fabric_token_sdk_tpu.api.driver import ValidationError
    from fabric_token_sdk_tpu.api.validator import RequestValidator, ValidationResult

    inner = RequestValidator.validate

    def validate(self, request, resolve_input, *a, **kw):
        try:
            return inner(self, request, resolve_input, *a, **kw)
        except ValidationError:
            return ValidationResult()

    RequestValidator.validate = validate


def bf16_limbs():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fabric_token_sdk_tpu.ops import limbs as lb

    def mul_full(x, y):
        nx, ny = x.shape[-1], y.shape[-1]
        prod = x[..., :, None] * y[..., None, :]
        flat = prod.reshape(prod.shape[:-2] + (nx * ny,)).astype(jnp.float32)
        flat = flat.astype(jnp.bfloat16).astype(jnp.float32)  # the lower precision
        acc = jax.lax.dot_general(
            flat, lb._conv_matrix(nx, ny).astype(np.float32),
            (((flat.ndim - 1,), (0,)), ((), ())))
        return lb.normalize_fixed(acc.astype(jnp.int32), 3)

    lb.mul_full = mul_full


if __name__ == "__main__":
    mode, mix_path, argv = sys.argv[1], sys.argv[2], sys.argv[3:]
    {"accept_all": accept_all, "bf16_limbs": bf16_limbs, "sound": lambda: None}[mode]()
    import run

    with open(mix_path) as fh:
        mix = json.load(fh)
    load_cell = run.mf.cell
    run.mf.cell = lambda *a, **kw: dict(load_cell(*a, **kw), mix=dict(mix))
    code = run.main(argv)
    sys.stdout.flush()
    os._exit(code)
