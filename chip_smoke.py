"""chip_smoke.py — the quickest proof that the trainer's main path starts on
the chip.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips, one process, that path only

One process, public API only (`import horovod_tpu as hvt`). With no
arguments it takes the widest LM the repo has a record for — d2048 x 8L x
16H (head_dim 128), vocab 8192, seq 1024, bf16, fused-CE head, 436 M
parameters, random weights from the seed — through
``hvt.Trainer.fit(x=, y=)`` on the streamed input path for two short epochs
on ONE chip, and checks, by the repo's own means:

* every step's loss is finite and epoch 2's mean loss is below epoch 1's;
* the model's loss-and-grad lowered for the chip holds the Mosaic flash
  kernel (``tpu_custom_call``) and no `KernelFallbackWarning` fired — the
  kernel did not give way to the dense path;
* the Mosaic-compiled kernel's output and gradients agree with
  `ops.attention.dense_attention` at the model's attention shape.

``--chips 4`` runs ONLY the data-parallel path: the same LM and seed at
global batch 8 on a ``data=4`` mesh and on a one-device mesh, the two loss
trajectories compared step for step, the shards counted, the all-reduce
found in the compiled program.

It refuses to run off-TPU (exit 1, ``"ok": false``): nothing here falls
back to the CPU, the Pallas interpreter or a dense path. The last line of
stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
with ``count`` the number of chips the phase used; exit code 0 only when
every gate passed. Earlier lines are smoke readings (seconds, bytes,
counts), not benchmark results.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import statistics
import sys
import time
import traceback
import warnings

import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvt
from horovod_tpu.data import datasets
from horovod_tpu.models.transformer import ShardingConfig, TransformerLM
from horovod_tpu.ops.attention import dense_attention
from horovod_tpu.ops.flash_attention import (
    KernelFallbackWarning,
    default_interpret,
    flash_attention,
)
from horovod_tpu.parallel import sharding


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """Sizes of the smoke. The defaults ARE the smoke (full published
    width); tests/test_chip_smoke.py shrinks them to drive the same phase
    functions on the CPU mesh."""

    d_model: int = 2048
    n_layers: int = 8
    n_heads: int = 16
    vocab: int = 8192
    seq_len: int = 1024
    fused_head_chunks: int = 8
    learning_rate: float = 1e-4
    seed: int = 0
    n_sequences: int = 256
    # one-chip phase
    per_chip_batch: int = 4
    steps_per_epoch: int = 8
    epochs: int = 2
    # four-chip phase (both legs train the same global batch)
    global_batch: int = 8
    dp_steps: int = 6


# Kernel vs dense reference, as max|a - ref| / max|ref|: bf16 carries 8
# mantissa bits (eps 7.8e-3) and the kernel rounds the probabilities to
# bf16 before P·V, so a correct kernel sits within an eps or two (a v5e
# read 2.9e-3 to 3.6e-3 on out/dq/dk/dv at the smoke's shape).
KERNEL_TOL = 2e-2
# Data-parallel vs single-device loss, per step, relative. The two legs
# differ only in reduction order (a v5e 2x2 read at most 1.2e-5 over six
# steps); a batch shard on the wrong device moves the first loss by about
# 2e-3 (the spread of a mean over a quarter of the tokens).
TRAJECTORY_TOL = 5e-4


def say(**fields) -> None:
    """One reading per line, as JSON, flushed (the tool shows only the end
    of the output, so every line has to stand alone)."""
    print(json.dumps(fields), flush=True)


def device_report(devices) -> dict:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def build_lm(cfg: SmokeConfig, mesh):
    """The LM, handed the trainer's mesh as the examples do — that is what
    lets the flash kernel run inside a shard_map on more than one chip."""
    return TransformerLM(
        vocab_size=cfg.vocab,
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_layers=cfg.n_layers,
        dropout=0.0,
        compute_dtype=jnp.bfloat16,
        fused_head_chunks=cfg.fused_head_chunks,
        sharding=ShardingConfig(mesh=mesh),
    )


def make_trainer(cfg: SmokeConfig, devices):
    mesh = hvt.build_mesh(hvt.MeshSpec(data=len(devices)), devices=devices)
    return hvt.Trainer(
        build_lm(cfg, mesh),
        hvt.DistributedOptimizer(optax.adamw(cfg.learning_rate)),
        loss="module",
        mesh=mesh,
        seed=cfg.seed,
    )


class StepLog(hvt.callbacks.Callback):
    """Reads every step's loss back to the host (which waits for that step
    to finish) and stamps the time."""

    def __init__(self):
        self.losses: list[float] = []
        self.stamps: list[float] = []

    def on_batch_end(self, batch, logs=None):
        self.losses.append(float(logs["loss"]))
        self.stamps.append(time.perf_counter())


def fit(trainer, x, y, *, batch_size, steps_per_epoch, epochs):
    """Build, then `Trainer.fit` on the streamed path (cache=None: host
    batches assembled, prefetched and transferred while the chip computes).
    Returns (per-step losses, readings)."""
    t0 = time.perf_counter()
    jax.block_until_ready(trainer.build(x[:1], y[:1]))
    t_built = time.perf_counter()
    log = StepLog()
    trainer.fit(
        x=x, y=y, batch_size=batch_size, steps_per_epoch=steps_per_epoch,
        epochs=epochs, callbacks=[log], cache=None, verbose=0,
    )
    first_step_s = log.stamps[0] - t_built
    # Warm steps: skip the compile step and the one after it.
    warm = [b - a for a, b in zip(log.stamps[1:], log.stamps[2:])]
    step_s = statistics.median(warm) if warm else float("nan")
    readings = {
        "build_s": round(t_built - t0, 3),
        "first_step_s": round(first_step_s, 3),
        "compile_s_about": round(first_step_s - step_s, 3),
        "step_s_median": step_s,
        "step_s_all": [round(s, 4) for s in warm],
        "input_engine": trainer.stream_cursor(0, 0)["position"]["engine"],
    }
    return log.losses, readings


def lower_loss_and_grad(trainer, x, y):
    """The model's loss-and-grad, lowered for the devices the trainer's
    state lives on (module.apply with the fused-CE labels path — what the
    train step differentiates)."""
    def loss_of(params, xb, yb):
        loss, _correct = trainer.module.apply(
            {"params": params}, xb, train=True, labels=yb
        )
        return loss.mean()

    xb, yb = sharding.shard_batch((x, y), trainer.mesh)
    return jax.jit(jax.value_and_grad(loss_of)).lower(
        trainer.state.params, xb, yb
    )


def kernel_vs_dense(cfg: SmokeConfig, batch: int, device) -> dict:
    """Flash kernel (as compiled for ``device``) against the dense
    reference in f32, forward and all three gradients, at the model's
    attention shape."""
    shape = (batch, cfg.seq_len, cfg.n_heads, cfg.d_model // cfg.n_heads)
    keys = jax.random.split(jax.random.PRNGKey(cfg.seed), 4)
    q, k, v, g = (
        jax.device_put(jax.random.normal(key, shape, jnp.bfloat16), device)
        for key in keys
    )

    def run(attn, *qkv):
        out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, causal=True), *qkv)
        return (out,) + vjp(g.astype(out.dtype))

    got = jax.jit(lambda q, k, v: run(flash_attention, q, k, v))(q, k, v)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda q, k, v: run(dense_attention, q, k, v))(
            *(a.astype(jnp.float32) for a in (q, k, v))
        )
    errs = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, ref):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        errs[name] = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
    return {"shape": list(shape), "max_err_over_max_ref": errs}


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def one_chip_phase(cfg: SmokeConfig, device) -> dict:
    """The main path on one device. Returns {gate name: passed}."""
    say(phase="one_chip", config=dataclasses.asdict(cfg),
        interpret=default_interpret())
    gates = {}

    kernel = kernel_vs_dense(cfg, cfg.per_chip_batch, device)
    say(kernel_vs_dense=kernel, tolerance=KERNEL_TOL)
    gates["kernel_matches_dense"] = all(
        e <= KERNEL_TOL for e in kernel["max_err_over_max_ref"].values()
    )

    x, y = datasets.copy_task(
        cfg.n_sequences, cfg.seq_len, vocab_size=cfg.vocab, seed=cfg.seed
    )
    trainer = make_trainer(cfg, [device])
    losses, readings = fit(
        trainer, x, y, batch_size=cfg.per_chip_batch,
        steps_per_epoch=cfg.steps_per_epoch, epochs=cfg.epochs,
    )
    n_params = sum(p.size for p in jax.tree.leaves(trainer.state.params))
    say(n_params=n_params, **readings)
    say(losses=[round(l, 4) for l in losses],
        first_loss=losses[0], ln_vocab=math.log(cfg.vocab))
    gates["losses_finite"] = (
        len(losses) == cfg.steps_per_epoch * cfg.epochs
        and all(math.isfinite(l) for l in losses)
    )
    epoch_means = [
        statistics.fmean(losses[i:i + cfg.steps_per_epoch])
        for i in range(0, len(losses), cfg.steps_per_epoch)
    ]
    say(epoch_mean_loss=epoch_means)
    gates["loss_fell"] = epoch_means[-1] < epoch_means[0]

    n = cfg.per_chip_batch
    text = lower_loss_and_grad(trainer, x[:n], y[:n]).as_text()
    n_kernels = text.count("tpu_custom_call")
    say(tpu_custom_calls_in_loss_and_grad=n_kernels,
        flash_calls_expected=2 * cfg.n_layers)
    gates["kernel_compiled"] = n_kernels > 0

    say(peak_bytes_in_use=peak_bytes(device))
    return gates


def four_chip_phase(cfg: SmokeConfig, devices) -> dict:
    """Data parallelism over ``devices`` against the same global batch on
    the first of them. Returns {gate name: passed}."""
    n = len(devices)
    say(phase="data_parallel", n_devices=n, config=dataclasses.asdict(cfg),
        interpret=default_interpret())
    gates = {}
    x, y = datasets.copy_task(
        cfg.n_sequences, cfg.seq_len, vocab_size=cfg.vocab, seed=cfg.seed
    )
    gb = cfg.global_batch

    # Leg 1: data=n. Keep only host-side readings of it.
    trainer = make_trainer(cfg, devices)
    dp_losses, readings = fit(
        trainer, x, y, batch_size=gb // n,
        steps_per_epoch=cfg.dp_steps, epochs=1,
    )
    say(leg=f"data={n}", losses=dp_losses, **readings)
    param_devices = {
        len(p.sharding.device_set)
        for p in jax.tree.leaves(trainer.state.params)
    }
    placed = sharding.shard_batch((x[:gb], y[:gb]), trainer.mesh)
    shard_rows = sorted(
        s.data.shape[0] for s in placed[0].addressable_shards
    )
    say(param_device_counts=sorted(param_devices),
        batch_shard_rows=shard_rows)
    gates["params_on_every_device"] = param_devices == {n}
    gates["batch_split_evenly"] = shard_rows == [gb // n] * n
    compiled = lower_loss_and_grad(trainer, x[:gb], y[:gb]).compile()
    text = compiled.as_text()
    say(all_reduce_mentions_in_loss_and_grad=text.count("all-reduce"),
        tpu_custom_calls=text.count("tpu_custom_call"))
    gates["all_reduce_compiled"] = "all-reduce" in text
    say(peak_bytes_in_use=[peak_bytes(d) for d in devices])
    # The second leg shares devices[0] with a full replica of this state:
    # drop everything that holds device memory before building it.
    del trainer, placed, compiled
    gc.collect()

    # Leg 2: the same global batch on one device.
    trainer = make_trainer(cfg, devices[:1])
    one_losses, readings = fit(
        trainer, x, y, batch_size=gb,
        steps_per_epoch=cfg.dp_steps, epochs=1,
    )
    say(leg="data=1", losses=one_losses, **readings)
    say(peak_bytes_in_use=[peak_bytes(d) for d in devices])

    rel = [
        abs(a - b) / abs(b) for a, b in zip(dp_losses, one_losses)
    ]
    say(trajectory_rel_diff=rel, tolerance=TRAJECTORY_TOL)
    gates["losses_finite"] = (
        len(dp_losses) == len(one_losses) == cfg.dp_steps
        and all(math.isfinite(l) for l in dp_losses + one_losses)
    )
    gates["trajectories_agree"] = (
        len(rel) == cfg.dp_steps and max(rel) <= TRAJECTORY_TOL
    )
    return gates


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1: the main path on one chip (default). 4: only the "
             "data-parallel path, on four chips against one.",
    )
    args = parser.parse_args(argv)

    say(compilation_cache_dir=hvt.runtime.use_compilation_cache(),
        compilation_cache_enabled=bool(
            jax.config.jax_enable_compilation_cache))
    hvt.init()
    devices = jax.devices()[:args.chips]
    report = device_report(devices)
    ok = False
    # A flash call that gives way to the dense path fails the smoke at the
    # call, with the stack that shows where.
    warnings.simplefilter("error", KernelFallbackWarning)
    try:
        if devices[0].platform != "tpu":
            raise RuntimeError(
                f"chip_smoke needs a TPU; jax found {report} — refusing "
                "to smoke-test the CPU"
            )
        if len(devices) != args.chips:
            raise RuntimeError(
                f"--chips {args.chips} needs {args.chips} chips; jax found "
                f"{len(jax.devices())}"
            )
        if args.chips == 1:
            gates = one_chip_phase(SmokeConfig(), devices[0])
        else:
            gates = four_chip_phase(SmokeConfig(), devices)
        say(gates=gates)
        ok = all(gates.values())
    except Exception:
        traceback.print_exc()
    print(json.dumps({"ok": ok, "device": report}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
