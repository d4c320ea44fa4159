"""Long-context LM training with sequence + tensor parallelism.

The capability demo the reference has no analogue for (SURVEY.md §5.7 —
sequence axis entirely absent there): a decoder-only transformer whose
activations are sharded along the mesh ``seq`` axis, attention running as a
ring (or Ulysses all-to-all) collective, QKV/MLP weights tensor-parallel
over ``model``, batch data-parallel — all in one jitted step.

The task is long-range recall (data.datasets.copy_task): the second half of
every sequence repeats the first half, so a model can only drive
second-half loss toward 0 by attending across the sequence shards.
The final report prints the recall-half loss — the functional proof that
cross-shard attention works.

Mesh shape via HVT_MESH, e.g.:

    HVT_MESH="data=2,seq=4" python examples/lm_long_context.py
    HVT_MESH="data=2,seq=2,model=2" python examples/lm_long_context.py

Knobs: DRIVE_STEPS, DRIVE_EPOCHS, SEQ_LEN, VOCAB, DMODEL, NLAYERS, ATTN
(ring|ulysses), REMAT=1 (block rematerialization), LOGITS=bf16 (16-bit
logits; the loss upcasts to f32 on the fly), FUSED_CE=<n_chunks> (fused
chunked-CE head: full logits never materialized — the stronger long-context
memory knob), MOE_EVERY (0=dense; k = MoE MLP every k-th block), N_EXPERTS. MoE composes with the mesh's ``expert``
axis, e.g.:

    HVT_MESH="data=2,expert=4" MOE_EVERY=2 python examples/lm_long_context.py

Pipeline parallelism: a ``pipe`` axis switches to the pipelined model
(GPipe microbatch schedule, models/pipelined_lm.py):

    HVT_MESH="data=2,pipe=4" N_MICRO=8 python examples/lm_long_context.py
    HVT_MESH="data=2,pipe=2,model=2" SCHEDULE=1f1b python examples/lm_long_context.py
    HVT_MESH="data=2,pipe=2,seq=2"  python examples/lm_long_context.py  # PP x SP
"""

import os

try:
    import horovod_tpu  # noqa: F401 — installed (`pip install -e .`)
except ModuleNotFoundError:  # bare source checkout: make the repo importable
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvt
from horovod_tpu import metrics
from horovod_tpu.data import datasets
from horovod_tpu.models.transformer import (
    ShardingConfig,
    TransformerLM,
    param_specs,
)
from horovod_tpu.parallel import mesh as mesh_lib


def main() -> None:
    hvt.init()
    metrics.init()

    mesh = mesh_lib.build_mesh(
        mesh_lib.MeshSpec.from_string(os.environ.get("HVT_MESH"))
    )
    seq_len = int(os.environ.get("SEQ_LEN", 512))
    vocab = int(os.environ.get("VOCAB", 64))
    attn = os.environ.get("ATTN", "ring")

    if mesh.shape.get(mesh_lib.PIPE_AXIS, 1) > 1:
        # pipe > 1 switches to the pipeline-parallel model: per-layer
        # parameter stacks sharded over `pipe`, GPipe (or SCHEDULE=1f1b
        # staggered-backward) microbatch schedule, Megatron TP inside each
        # stage when `model` > 1 AND ring-flash sequence parallelism inside
        # each stage when `seq` > 1 (models/pipelined_lm.py) — e.g.
        # HVT_MESH="data=2,pipe=2,seq=2". Use TransformerLM for the expert
        # axis.
        from horovod_tpu.models import pipelined_lm

        model = pipelined_lm.PipelinedLM(
            vocab_size=vocab,
            d_model=int(os.environ.get("DMODEL", 256)),
            n_heads=8,
            n_layers=int(os.environ.get("NLAYERS", 4)),
            n_micro=int(os.environ.get("N_MICRO", 4)),
            mesh=mesh,
            schedule=os.environ.get("SCHEDULE", "gpipe"),
        )
        batch_spec = P(
            (mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS), mesh_lib.SEQ_AXIS
        )
        trainer = hvt.Trainer(
            model,
            hvt.DistributedOptimizer(optax.adam(3e-3)),
            loss="sparse_categorical_crossentropy",
            mesh=mesh,
            param_specs=pipelined_lm.param_specs,
            batch_specs=(batch_spec, batch_spec),
        )
    else:
        model = TransformerLM(
            vocab_size=vocab,
            d_model=int(os.environ.get("DMODEL", 256)),
            n_heads=8,
            n_layers=int(os.environ.get("NLAYERS", 4)),
            dropout=0.0,
            sharding=ShardingConfig(mesh=mesh, attn=attn),
            moe_every=int(os.environ.get("MOE_EVERY", 0)),
            n_experts=int(os.environ.get("N_EXPERTS", 8)),
            # Memory knobs for extreme context (REMAT=1, LOGITS=bf16):
            # together they take one 16 GB chip from OOM to training at
            # seq 131,072 (not measured on this round's chip).
            remat=hvt.runtime.env_flag("REMAT"),
            logits_dtype=jnp.bfloat16
            if os.environ.get("LOGITS", "") == "bf16"
            else jnp.float32,
            # FUSED_CE=<n_chunks>: the fused chunked-CE head — f32-accurate
            # loss with the [B, T, vocab] logits never materialized
            # (ops/fused_ce.py); supersedes LOGITS=bf16 for long context.
            fused_head_chunks=int(os.environ.get("FUSED_CE", 0)),
        )
        batch_spec = P(
            (mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS), mesh_lib.SEQ_AXIS
        )
        trainer = hvt.Trainer(
            model,
            hvt.DistributedOptimizer(optax.adam(3e-3)),
            loss="module"
            if int(os.environ.get("FUSED_CE", 0))
            else "sparse_categorical_crossentropy",
            mesh=mesh,
            param_specs=param_specs,
            batch_specs=(batch_spec, batch_spec),
        )

    x, y = datasets.copy_task(4096, seq_len, vocab_size=vocab, seed=0)
    epochs = int(os.environ.get("DRIVE_EPOCHS", 0)) or 4
    steps = int(os.environ.get("DRIVE_STEPS", 0)) or 64

    # HVT_DEVICE_CACHE=1: HBM-resident dataset, one dispatch per epoch
    # (pure-GSPMD meshes only — the seq-sharded batch layout needs the
    # streamed path's batch_specs handling).
    device_cache = hvt.runtime.env_flag(
        "HVT_DEVICE_CACHE"
    ) and not mesh_lib.has_live_model_axes(mesh)
    if device_cache:
        fit_kwargs = {"cache": "device"}
        if int(os.environ.get("DRIVE_STEPS", 0)):  # honor an explicit budget
            fit_kwargs["steps_per_epoch"] = steps
    else:
        fit_kwargs = {"steps_per_epoch": steps}
    trainer.fit(
        x=x, y=y,
        batch_size=max(1, 16 // mesh_lib.dp_size(mesh)),
        epochs=epochs,
        callbacks=[
            hvt.callbacks.BroadcastGlobalVariablesCallback(0),
            hvt.callbacks.MetricAverageCallback(),
            hvt.callbacks.MetricsPushCallback(),
        ],
        verbose=1 if hvt.rank() == 0 else 0,
        **fit_kwargs,
    )

    # Recall-half report on held-out sequences.
    xt, yt = datasets.copy_task(64, seq_len, vocab_size=vocab, seed=99)
    probs = trainer.predict(xt, batch_size=8)
    ll = np.log(np.take_along_axis(probs, yt[..., None], axis=-1)[..., 0] + 1e-9)
    half = seq_len // 2
    recall_loss = float(-ll[:, half:].mean())
    context_loss = float(-ll[:, : half - 2].mean())
    metrics.push("recall_loss", recall_loss)
    if hvt.rank() == 0:
        print(f"first-half (irreducible) loss: {context_loss:.4f}")
        print(f"recall-half loss:              {recall_loss:.4f}")
        print("long-range recall:", "LEARNED" if recall_loss < 0.5 * context_loss
              else "not yet (train longer)")

    # Generation proof (TransformerLM only): greedy KV-cache decode from the
    # first-half prompt must literally reproduce the repeated half — the
    # same recall the loss measures, exercised end-to-end through the
    # compiled prefill + decode loop (models/decoding.py).
    if (
        isinstance(trainer.module, TransformerLM)
        and half > 1
        and jax.process_count() == 1  # multi-proc params aren't addressable here
    ):
        from horovod_tpu.models.decoding import generate

        gen_model = trainer.module.clone(sharding=ShardingConfig(mesh=None))
        prompt = jnp.asarray(xt[:8, : half + 1])  # [BOS, first_half]
        out = np.asarray(generate(
            gen_model, trainer.state.params, prompt,
            max_new_tokens=half - 1, include_prompt=False,
        ))
        exact = float((out == xt[:8, half + 1 :]).mean())
        metrics.push("decode_exact_match", exact)
        if hvt.rank() == 0:
            print(f"greedy-decode recall exact-match: {exact:.3f}")


if __name__ == "__main__":
    main()
