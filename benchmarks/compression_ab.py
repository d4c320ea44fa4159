"""A/B: gradient wire compression on the compiled DP path, 8-device mesh.

Measures the reference MNIST CNN's train step with
``DistributedOptimizer(compression=...)`` across the full wire ladder —
``none`` (f32), ``bf16``, and the quantized EQuARX-style wires ``int8`` /
``fp8`` each with AND without error feedback — on the virtual 8-device CPU
mesh (the suite's multi-process-without-a-cluster mode, SURVEY.md §4b):
steps/s, per-step gradient wire bytes (param count × wire element width —
what crosses ICI/DCN per reduction; quantized wires add one f32 scale per
fusion bucket, noise at any real model size), and the final-loss delta
after a fixed number of steps against the uncompressed run.

The wire-dtype change itself is proven at the HLO level in
tests/test_compression_path.py / tests/test_overlap_compression.py; this
script puts numbers on it. The STATED TOLERANCE for the
quantized wires: with error feedback the final loss must track the bf16
path within ``--tolerance`` (default 10% relative) — the acceptance bound
the bench asserts (``within_tolerance``; exit non-zero on a miss). The
no-error-feedback legs are the ablation: they are *allowed* to drift (the
uncorrected quantization bias compounding across steps is exactly what
error feedback removes).

Run:  python benchmarks/compression_ab.py  [--steps 30] [--tolerance 0.1]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
try:  # jax >= 0.4.34 spells the device-count override as config too;
    jax.config.update("jax_num_cpu_devices", 8)
except AttributeError:  # older floors use the XLA_FLAGS set above
    pass

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import horovod_tpu as hvt  # noqa: E402
from horovod_tpu.models.cnn import MnistCNN  # noqa: E402
from horovod_tpu.parallel import sharding as sharding_lib  # noqa: E402
from horovod_tpu.training.trainer import Trainer  # noqa: E402

#: wire element width in bytes per compression mode
_WIRE_BYTES = {"none": 4, "bf16": 2, "int8": 1, "fp8": 1}


def run(compression: str, steps: int, x, y, *, error_feedback: bool = True):
    tx = hvt.DistributedOptimizer(
        optax.adam(1e-3), compression=compression,
        error_feedback=error_feedback,
    )
    tr = Trainer(MnistCNN(), tx)
    state = tr.build(x[: tr.dp_size])
    batch = tr._shard((x, y))
    scale = jnp.asarray(1.0, jnp.float32)
    acc = sharding_lib.replicate(
        {"loss": jnp.zeros(()), "accuracy": jnp.zeros(())}, tr.mesh
    )
    n_params = sum(p.size for p in jax.tree.leaves(state.params))
    # Warm up (compile) + 2 steps out of the timing window.
    for _ in range(2):
        state, metrics, acc = tr._train_step(state, batch, scale, acc)
    jax.block_until_ready(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics, acc = tr._train_step(state, batch, scale, acc)
    loss = float(jax.device_get(metrics["loss"]))
    dt = time.perf_counter() - t0
    quantized = compression in ("int8", "fp8")
    label = compression
    if quantized:
        label += "+ef" if error_feedback else "-noef"
    return {
        "compression": label,
        "steps_per_s": steps / dt,
        "loss": loss,
        "n_params": int(n_params),
        "wire_bytes_per_reduction": int(n_params * _WIRE_BYTES[compression]),
        "error_feedback": error_feedback if quantized else None,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument(
        "--tolerance", type=float, default=0.1,
        help="max relative final-loss delta of the error-feedback "
        "quantized wires vs the bf16 path (the stated acceptance bound)",
    )
    args = ap.parse_args()
    rng = np.random.RandomState(0)
    # Global batch 256 over 8 shards of the reference's 28x28x1 images.
    x = rng.rand(256, 28, 28, 1).astype(np.float32)
    y = rng.randint(0, 10, 256).astype(np.int64)
    legs = [
        run("none", args.steps, x, y),
        run("bf16", args.steps, x, y),
        run("int8", args.steps, x, y, error_feedback=True),
        run("int8", args.steps, x, y, error_feedback=False),
        run("fp8", args.steps, x, y, error_feedback=True),
        run("fp8", args.steps, x, y, error_feedback=False),
    ]
    loss_f32 = legs[0]["loss"]
    loss_bf16 = legs[1]["loss"]
    ok = True
    for leg in legs[1:]:
        leg["loss_delta_vs_f32"] = abs(leg["loss"] - loss_f32)
        if leg["error_feedback"]:
            rel = abs(leg["loss"] - loss_bf16) / max(abs(loss_bf16), 1e-9)
            leg["rel_delta_vs_bf16"] = rel
            leg["within_tolerance"] = rel <= args.tolerance
            ok = ok and leg["within_tolerance"]
    out = {"tolerance_rel_vs_bf16": args.tolerance, "legs": legs}
    print(json.dumps(out, indent=2))
    if not ok:
        print(
            "compression_ab: an error-feedback quantized leg missed the "
            f"stated tolerance ({args.tolerance} rel vs bf16)",
            file=sys.stderr,
        )
        sys.exit(1)


if __name__ == "__main__":
    main()
