"""Conv-family train-step attribution on-chip (not part of the test suite).

What `lm_profile.py` does for the transformer, for the CNN families: times
nested subsets of the MNIST-CNN and ResNet-20 train steps (forward /
forward+backward / +optimizer+BN / the device-resident input gather), an
op-size ceiling comparison (each model's dominant ops in isolation vs an
MXU-saturating matmul), and a per-chip batch sweep — the evidence for the
conv models' shape-bound MFU ceiling (not measured on this round's chip).

Timing is `_timing.timed_chain` (one fused scan, min-of-3, nonzero carry
perturbation); see that module's docstring for the hazards it guards.

Usage: python benchmarks/conv_profile.py [mnist|resnet|gather|ceiling|sweep ...]
Env: CVP_N=512  CVP_BATCH=128
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax

from _timing import timed_chain

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Chains must amortize the fetch's host round-trip (not measured on this
# round's chip): too short a chain reads a sub-ms op as mostly round-trip.
# Raise N for sub-100us ops.
N = int(os.environ.get("CVP_N", 512))
BATCH = int(os.environ.get("CVP_BATCH", 128))


def _build(which, batch):
    if which == "resnet":
        from horovod_tpu.models.resnet import ResNetCIFAR

        model = ResNetCIFAR(depth=20, compute_dtype=jnp.bfloat16)
        x = jnp.asarray(
            np.random.RandomState(0).randint(0, 255, (batch, 32, 32, 3)),
            jnp.uint8,
        )
    else:
        from horovod_tpu.models.cnn import MnistCNN

        model = MnistCNN(compute_dtype=jnp.bfloat16)
        x = jnp.asarray(
            np.random.RandomState(0).randint(0, 255, (batch, 28, 28, 1)),
            jnp.uint8,
        )
    y = jnp.asarray(np.random.RandomState(1).randint(0, 10, batch), jnp.int32)
    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        x, train=False,
    )
    params = variables["params"]
    bn = {k: v for k, v in variables.items() if k != "params"}
    return model, params, bn, x, y


def _flops(model, params, bn, x, y):
    from horovod_tpu import trace

    def step(p):
        def loss(p):
            mut = list(bn.keys()) or False
            out = model.apply(
                {"params": p, **bn}, x, train=True,
                rngs={"dropout": jax.random.PRNGKey(0)},
                mutable=mut,
            )
            logits = out[0] if mut is not False else out
            return optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), y
            ).mean()

        return jax.grad(loss)(p)

    return trace.compiled_flops(jax.jit(step), params)


def profile_model(which):
    os.environ.setdefault("HVT_FAST_RNG", "1")
    model, params, bn, x, y = _build(which, BATCH)
    mutable = list(bn.keys())
    print(f"== {which} (batch {BATCH}) ==")
    x0 = jnp.float32(1.0)

    def perturbed(c):
        return (x + (1e-30 * c).astype(x.dtype)) % 255

    def fwd_loss(p, xi, train):
        mut = mutable if (train and mutable) else False
        out = model.apply(
            {"params": p, **bn}, xi, train=train,
            rngs={"dropout": jax.random.PRNGKey(0)},
            mutable=mut,
        )
        logits = out[0] if mut is not False else out
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), y
        ).mean()

    s_f = timed_chain(lambda c: fwd_loss(params, perturbed(c), False), x0, steps=N)
    print(f"forward+loss (eval mode):   {s_f*1e3:.3f} ms")

    s_ft = timed_chain(lambda c: fwd_loss(params, perturbed(c), True), x0, steps=N)
    print(f"forward+loss (train, BN+dropout): {s_ft*1e3:.3f} ms")

    g = jax.grad(lambda p, xi: fwd_loss(p, xi, True))

    def bwd(c):
        gr = g(params, perturbed(c))
        return jax.tree.leaves(gr)[0].astype(jnp.float32).sum()

    s_b = timed_chain(bwd, x0, steps=N)
    print(f"forward+backward:           {s_b*1e3:.3f} ms")

    # full train step through the Trainer's own compiled path (adam + BN
    # threading + metric accumulation), batch preloaded — no input leg.
    import horovod_tpu as hvt
    from horovod_tpu.parallel import sharding as sharding_lib

    tr = hvt.Trainer(model, hvt.DistributedOptimizer(optax.adam(1e-3)))
    state = tr.build(np.asarray(x[: tr.dp_size]))
    batch = tr._shard((np.asarray(x), np.asarray(y)))
    acc = sharding_lib.replicate(tr.zero_metrics(), tr.mesh)
    import time as _time

    compiled = tr._train_chunk.lower(
        state,
        tuple(jnp.broadcast_to(b, (N,) + b.shape) for b in batch),
        jnp.float32(1.0), acc,
    ).compile()
    mega = tuple(jnp.broadcast_to(b, (N,) + b.shape) for b in batch)
    st, _, a = compiled(state, mega, jnp.float32(1.0), acc)
    float(jax.device_get(a["loss"]))
    best = float("inf")
    for _ in range(3):
        t0 = _time.perf_counter()
        st, _, a = compiled(st, mega, jnp.float32(1.0), acc)
        float(jax.device_get(a["loss"]))
        best = min(best, _time.perf_counter() - t0)
    s_full = best / N
    print(f"full step (fwd+bwd+adam):   {s_full*1e3:.3f} ms")

    fl = _flops(model, params, bn, x, y)
    if fl:
        from horovod_tpu import trace

        print(
            f"flops/step {fl/1e9:.2f} GF -> MFU at full step: "
            f"{trace.mfu(fl, s_full, 1):.3f}"
        )
    print(
        f"attribution: fwd {s_ft*1e3:.2f} | bwd {(s_b-s_ft)*1e3:.2f} | "
        f"opt+thread {(s_full-s_b)*1e3:.2f} ms"
    )
    return s_full


def profile_gather():
    """The device-resident epoch's input leg in isolation: per-step shard
    gather of `batch` rows from an HBM-resident [1, N, ...] dataset —
    round 2 measured it at 31% of the MNIST e2e step."""
    print("== input gather (device-cached epoch leg) ==")
    rng = np.random.RandomState(0)
    data = jnp.asarray(rng.rand(1, 60000, 28, 28, 1), jnp.float32)
    order = jnp.argsort(jax.random.uniform(jax.random.PRNGKey(0), (1, 60000)), axis=1)

    def gather_vmap(c):
        t = (c.astype(jnp.int32) % (data.shape[1] // BATCH))
        idx = jax.lax.dynamic_slice_in_dim(order, t * BATCH, BATCH, axis=1)
        out = jax.vmap(lambda rows, ii: rows[ii])(data, idx)
        return out.astype(jnp.float32).sum()

    s = timed_chain(gather_vmap, jnp.float32(1.0), steps=N)
    print(f"vmap row-gather [{BATCH}]: {s*1e3:.3f} ms")

    flat = data.reshape(60000, -1)

    def gather_flat(c):
        t = (c.astype(jnp.int32) % (data.shape[1] // BATCH))
        idx = jax.lax.dynamic_slice_in_dim(order[0], t * BATCH, BATCH, axis=0)
        out = jnp.take(flat, idx, axis=0)
        return out.astype(jnp.float32).sum()

    s = timed_chain(gather_flat, jnp.float32(1.0), steps=N)
    print(f"flat jnp.take  [{BATCH}]: {s*1e3:.3f} ms")

    data_u8 = (data * 255).astype(jnp.uint8)

    def gather_u8(c):
        t = (c.astype(jnp.int32) % (data.shape[1] // BATCH))
        idx = jax.lax.dynamic_slice_in_dim(order, t * BATCH, BATCH, axis=1)
        out = jax.vmap(lambda rows, ii: rows[ii])(data_u8, idx)
        return out.astype(jnp.float32).sum()

    s = timed_chain(gather_u8, jnp.float32(1.0), steps=N)
    print(f"vmap row-gather uint8 dataset [{BATCH}]: {s*1e3:.3f} ms "
          f"(4x smaller HBM reads)")

    def gather_vmap_flat(c):
        # The winner (now trainer.train_epoch's formulation): per-shard row
        # gather over FLATTENED trailing dims — a clean [N, F] row gather,
        # ~9x the multi-dim-trailing-shape gather at f32.
        t = (c.astype(jnp.int32) % (data.shape[1] // BATCH))
        idx = jax.lax.dynamic_slice_in_dim(order, t * BATCH, BATCH, axis=1)
        a2 = data.reshape(data.shape[0], data.shape[1], -1)
        out = jax.vmap(lambda rows, ii: jnp.take(rows, ii, axis=0))(a2, idx)
        return out.astype(jnp.float32).sum()

    s = timed_chain(gather_vmap_flat, jnp.float32(1.0), steps=N)
    print(f"vmap take over flattened [S,N,F] f32 [{BATCH}]: {s*1e3:.3f} ms "
          f"(trainer.train_epoch formulation)")


def profile_ceiling():
    """Op-size ceiling: the models' dominant ops in isolation vs a
    saturating matmul — how much of the gap is 'small ops cannot fill the
    MXU' vs 'our step wastes time'."""
    print("== op-size ceiling ==")

    def time_op(name, f, x0, flops):
        s = timed_chain(f, x0, steps=N)
        print(f"{name}: {s*1e3:.3f} ms  {flops/s/1e12:.1f} TFLOP/s")

    n = 4096
    m = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.bfloat16) * 0.01
    time_op(
        f"matmul {n}^3 bf16 (ceiling)",
        lambda c: jnp.vdot(
            (y := jnp.dot((m * (1 + 1e-30 * c)).astype(jnp.bfloat16), m,
                          preferred_element_type=jnp.float32)), y
        ),
        jnp.float32(1.0),
        2.0 * n ** 3,
    )

    # MNIST CNN dominant op: conv 26x26x32 -> 24x24x64 at batch 128.
    xa = jax.random.normal(jax.random.PRNGKey(1), (BATCH, 26, 26, 32), jnp.bfloat16)
    ka = jax.random.normal(jax.random.PRNGKey(2), (3, 3, 32, 64), jnp.bfloat16)
    fl = 2.0 * BATCH * 24 * 24 * 64 * 3 * 3 * 32
    time_op(
        f"mnist conv2 3x3x32->64 @26^2 b{BATCH}",
        lambda c: (jax.lax.conv_general_dilated(
            (xa * (1 + 1e-30 * c)).astype(jnp.bfloat16), ka, (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32) ** 2).sum(),
        jnp.float32(1.0), fl,
    )

    # ResNet-20 dominant op family: 3x3 conv at 32x32x16 and 8x8x64.
    for (hw, cin, cout) in ((32, 16, 16), (8, 64, 64)):
        xb = jax.random.normal(
            jax.random.PRNGKey(3), (BATCH, hw, hw, cin), jnp.bfloat16
        )
        kb = jax.random.normal(
            jax.random.PRNGKey(4), (3, 3, cin, cout), jnp.bfloat16
        )
        fl = 2.0 * BATCH * hw * hw * cout * 9 * cin
        time_op(
            f"resnet conv 3x3x{cin}->{cout} @{hw}^2 b{BATCH}",
            lambda c, xb=xb, kb=kb: (jax.lax.conv_general_dilated(
                (xb * (1 + 1e-30 * c)).astype(jnp.bfloat16), kb, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.float32) ** 2).sum(),
            jnp.float32(1.0), fl,
        )


def profile_sweep(which):
    print(f"== {which} batch sweep (full step, img/s/chip) ==")
    for b in (128, 256, 512, 1024):
        global BATCH
        old, BATCH = BATCH, b
        try:
            s = profile_model(which)
            print(f"  -> batch {b}: {b/s:,.0f} img/s")
        finally:
            BATCH = old


def main():
    cases = sys.argv[1:] or ["mnist", "resnet", "gather", "ceiling"]
    print(f"devices: {jax.devices()}")
    for c in cases:
        if c in ("mnist", "resnet"):
            profile_model(c)
        elif c == "gather":
            profile_gather()
        elif c == "ceiling":
            profile_ceiling()
        elif c.startswith("sweep"):
            profile_sweep(c.split(":")[1] if ":" in c else "resnet")


if __name__ == "__main__":
    main()
