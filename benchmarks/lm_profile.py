"""Transformer train-step ablation on-chip (not part of the test suite).

Times nested subsets of the bench transformer config's train step to
attribute step time: full forward / forward+backward / +optimizer /
dense-vs-flash attention / lm_head+CE alone. Timing is `_timing.timed_chain`
(one fused scan, min-of-3, nonzero carry perturbation) — see that module's
docstring for the measurement hazards it guards against; the residual bias
is one host round-trip over the N-step chain, identical across cases.

Usage: python benchmarks/lm_profile.py
Env: LMP_SEQ=1024 LMP_BATCH=8 LMP_N=64
"""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from _timing import timed_chain

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SEQ = int(os.environ.get("LMP_SEQ", 1024))
BATCH = int(os.environ.get("LMP_BATCH", 8))
N = int(os.environ.get("LMP_N", 64))
VOCAB, D, HEADS, LAYERS = 8192, 512, 8, 8


def main():
    from horovod_tpu.models.transformer import TransformerLM
    import horovod_tpu as hvt

    os.environ.setdefault("HVT_FAST_RNG", "1")
    hvt.init()
    print(f"devices: {jax.devices()}  seq={SEQ} batch={BATCH}")

    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, VOCAB, (BATCH, SEQ)), jnp.int32
    )
    labels = jnp.asarray(
        np.random.RandomState(1).randint(0, VOCAB, (BATCH, SEQ)), jnp.int32
    )

    def build(attn):
        m = TransformerLM(
            vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=LAYERS,
            compute_dtype=jnp.bfloat16, dropout=0.0,
        )
        if attn == "dense":
            import dataclasses

            m = dataclasses.replace(
                m, sharding=dataclasses.replace(m.sharding, attn="dense")
            )
        params = m.init(jax.random.PRNGKey(0), tokens, train=False)["params"]
        return m, params

    model, params = build("flash")
    x0 = jnp.float32(1.0)

    def perturbed_tokens(c):
        # the carry must reach the model input through a non-foldable path
        return (tokens + (1e-30 * c).astype(jnp.int32)) % VOCAB

    # --- forward only ------------------------------------------------------
    def fwd_loss(params, toks):
        logits = model.apply({"params": params}, toks, train=False)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    s = timed_chain(lambda c: fwd_loss(params, perturbed_tokens(c)), x0, steps=N)
    print(f"forward+loss: {s*1e3:.3f} ms/step")

    # --- fwd+bwd -----------------------------------------------------------
    gfn = jax.grad(fwd_loss)

    def bwd_step(c):
        g = gfn(params, perturbed_tokens(c))
        return jax.tree.leaves(g)[0].astype(jnp.float32).sum()

    s = timed_chain(bwd_step, x0, steps=N)
    print(f"forward+backward: {s*1e3:.3f} ms/step")

    # --- full train step (fwd+bwd+adamw): params/opt genuinely chain -------
    tx = optax.adamw(3e-4)
    opt0 = tx.init(params)

    @jax.jit
    def full(params, opt):
        def body(carry, _):
            p, o = carry
            g = gfn(p, tokens)
            up, o = tx.update(g, o, p)
            p = optax.apply_updates(p, up)
            return (p, o), jax.tree.leaves(g)[0].astype(jnp.float32).sum()

        (p, o), outs = jax.lax.scan(body, (params, opt), None, length=N)
        return outs[-1] + 0.0 * jax.tree.leaves(p)[0].astype(jnp.float32).sum()

    float(jax.device_get(full(params, opt0)))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        float(jax.device_get(full(params, opt0)))
        best = min(best, time.perf_counter() - t0)
    print(f"full step (fwd+bwd+adamw): {best/N*1e3:.3f} ms/step")

    # --- attention ablation: dense vs flash at this seq --------------------
    model_d, params_d = build("dense")

    def fwd_dense(c):
        logits = model_d.apply({"params": params_d}, perturbed_tokens(c), train=False)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    s = timed_chain(fwd_dense, x0, steps=N)
    print(f"forward+loss (dense attn): {s*1e3:.3f} ms/step")

    # --- lm_head + CE alone ------------------------------------------------
    acts = jnp.ones((BATCH, SEQ, D), jnp.bfloat16) * 0.01
    w = params["lm_head"]["kernel"]

    def head_loss(a):
        logits = a.reshape(-1, D) @ w.astype(jnp.bfloat16)
        logits = logits.astype(jnp.float32).reshape(BATCH, SEQ, VOCAB)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    s = timed_chain(lambda c: head_loss(acts * c.astype(jnp.bfloat16)), x0, steps=N)
    print(f"lm_head matmul + CE (fwd only): {s*1e3:.3f} ms")

    ghead = jax.grad(head_loss)

    def head_bwd_step(c):
        return ghead(acts * c.astype(jnp.bfloat16)).astype(jnp.float32).sum()

    s = timed_chain(head_bwd_step, x0, steps=N)
    print(f"lm_head + CE fwd+bwd: {s*1e3:.3f} ms")


if __name__ == "__main__":
    main()
