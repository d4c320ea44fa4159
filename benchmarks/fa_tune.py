"""On-chip flash-attention tuning harness (not part of the test suite).

Times our pallas kernel (fwd and fwd+bwd) across block sizes against XLA
dense attention and the stock JAX pallas TPU kernel, plus a pure-matmul
ceiling row that establishes what this way of measuring can reach on the
chip.

Honest-timing rules are the same as bench.py: one fused lax.scan chains N
iterations with a data dependence, and the clock stops only after fetching a
scalar that depends on the whole chain (`_timing.py`).

Usage: python benchmarks/fa_tune.py [case ...]
  cases: matmul dense ours stock  (default: all)
Env: FA_SHAPES="B,T,H,D;..."  FA_STEPS=256
"""

from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

STEPS = int(os.environ.get("FA_STEPS", 256))


def timed_chain(step, x0):
    from _timing import timed_chain as _tc

    return _tc(step, x0, steps=STEPS)


def attn_flops(b, t, h, d, causal=True, with_bwd=True):
    full = 4.0 * b * h * t * t * d  # QK^T + PV, 2 FLOP/MAC
    if causal:
        full /= 2
    return full * (1 + 2.5 * with_bwd)


def case_matmul():
    n = 4096
    x = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.bfloat16) * 0.01

    def step(c):
        y = jnp.dot(c, c, preferred_element_type=jnp.float32)
        # consume NONLINEARLY: any linear functional of a matmul (a slice, a
        # sum) gets algebraically rewritten to a cheap contraction of the
        # operands — sum(y²) forces the full product to exist.
        return jnp.vdot(y, y)

    s = timed_chain(step, x)
    fl = 2.0 * n**3
    print(f"matmul {n}^3 bf16: {s*1e3:.3f} ms  {fl/s/1e12:.1f} TFLOP/s")


def _mk(b, t, h, d, dtype=jnp.bfloat16):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    return tuple(
        jax.random.normal(k, (b, t, h, d), dtype) * 0.02 for k in ks
    )


def bench_attn(name, fn, q, k, v, *, grad: bool, flops: float):
    if grad:
        def loss(args):
            o = fn(*args)
            return jnp.sum(o.astype(jnp.float32) ** 2) * 1e-3

        g = jax.grad(lambda args: loss(args))

        def step(carry):
            # consume ALL grads: an unused dk/dv would let XLA dead-code
            # eliminate the dkv kernel and the row would time fwd+dq only
            gq, gk, gv = g((carry, k, v))
            return (
                gq.astype(jnp.float32).sum()
                + gk.astype(jnp.float32).sum()
                + gv.astype(jnp.float32).sum()
            )
    else:
        def step(carry):
            return fn(carry, k, v).astype(jnp.float32).sum()

    try:
        s = timed_chain(step, q)
    except Exception as e:  # noqa: BLE001
        print(f"  {name}: FAILED {type(e).__name__}: {str(e)[:120]}")
        return None
    print(f"  {name}: {s*1e3:.3f} ms  {flops/s/1e12:.1f} TFLOP/s")
    return s


def main():
    cases = sys.argv[1:] or ["matmul", "dense", "ours", "stock"]
    shapes = os.environ.get("FA_SHAPES", "8,1024,8,64;1,8192,8,64;1,16384,8,64")
    print(f"devices: {jax.devices()}")
    if "matmul" in cases:
        case_matmul()

    from horovod_tpu.ops.attention import dense_attention
    from horovod_tpu.ops import flash_attention as ours

    for spec in shapes.split(";"):
        b, t, h, d = (int(v) for v in spec.split(","))
        q, k, v = _mk(b, t, h, d)
        for grad in (False, True):
            fl = attn_flops(b, t, h, d, with_bwd=grad)
            tag = "fwd+bwd" if grad else "fwd"
            print(f"[B{b} T{t} H{h} D{d} bf16 causal {tag}] ideal FLOPs {fl/1e9:.0f}G")
            if "dense" in cases:
                bench_attn(
                    "xla dense", functools.partial(dense_attention, causal=True),
                    q, k, v, grad=grad, flops=fl,
                )
            if "ours" in cases:
                for bq, bk in ((512, 512), (256, 512), (512, 1024), (1024, 512), (256, 256), (1024, 1024)):
                    if t % bq or t % bk:
                        continue
                    fn = functools.partial(
                        ours.flash_attention, causal=True,
                        block_q=bq, block_k=bk, interpret=False,
                    )
                    bench_attn(f"ours bq{bq} bk{bk}", fn, q, k, v, grad=grad, flops=fl)
            if "stock" in cases:
                from jax.experimental.pallas.ops.tpu import flash_attention as st

                def stock(q, k, v):
                    # stock kernel wants [B, H, T, D]
                    qt, kt, vt = (jnp.transpose(x, (0, 2, 1, 3)) for x in (q, k, v))
                    o = st.flash_attention(qt, kt, vt, causal=True)
                    return jnp.transpose(o, (0, 2, 1, 3))

                bench_attn("stock pallas", stock, q, k, v, grad=grad, flops=fl)


if __name__ == "__main__":
    main()
