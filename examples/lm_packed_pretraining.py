"""Packed-sequence LM pretraining, end to end.

The standard long-context data format: variable-length documents packed into
fixed [B, T] rows. Everything the path needs is first-class here —

1. `data.packing.pack_documents`: best-fit-decreasing packing → static rows
   + segment ids (padding isolated in segment 0);
2. `data.packing.next_token_pairs`: shifted (x, y, loss-weights) whose mask
   stops targets at document boundaries;
3. `TransformerLM(..., segment_ids=...)`: per-document RoPE restart and the
   flash kernel's segment-masked attention (block-level early-out; its
   gain over dense-masked is not measured on this round's chip);
4. a weighted cross-entropy Trainer loss via the callable-loss hook.

The corpus is synthetic (zero-egress environment): each "document" is a
repeated random motif, so a model that attends within documents learns the
motif quickly — falling loss is the functional check.

Run (any mesh; ids shard with the tokens):

    python examples/lm_packed_pretraining.py
    HVT_MESH="data=2,seq=4" python examples/lm_packed_pretraining.py

Knobs: SEQ_LEN, DOCS, DRIVE_EPOCHS, DRIVE_STEPS, VOCAB, DMODEL, NLAYERS.
"""

import os

try:
    import horovod_tpu  # noqa: F401
except ModuleNotFoundError:
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvt
from horovod_tpu.data.packing import (
    next_token_pairs,
    pack_documents,
    packing_efficiency,
)
from horovod_tpu.models.transformer import (
    ShardingConfig,
    TransformerLM,
    param_specs,
)
from horovod_tpu.parallel import mesh as mesh_lib


def synthetic_corpus(n_docs: int, vocab: int, seed: int = 0):
    """Documents of motif repeats: learnable within-document structure."""
    rng = np.random.RandomState(seed)
    docs = []
    for _ in range(n_docs):
        motif = rng.randint(1, vocab, size=rng.randint(4, 12))
        reps = rng.randint(2, 8)
        docs.append(np.tile(motif, reps).astype(np.int32))
    return docs


class PackedLM(nn.Module):
    """TransformerLM + a per-row segment-id channel carried IN the input.

    The Trainer feeds (x, y) arrays; stacking ids as a second input channel
    ([B, T, 2] = tokens ⊕ ids) keeps the packed metadata flowing through
    fit/evaluate without a Trainer-side protocol change."""

    inner: TransformerLM

    @nn.compact
    def __call__(self, xs, *, train: bool = False):
        tokens, seg = xs[..., 0], xs[..., 1]
        return self.inner(tokens, train=train, segment_ids=seg)


def text_corpus(n_docs: int, seed: int = 0):
    """Synthetic TEXT documents (motifs of words) for the TEXT=1 path —
    exercising the full text front-end: ByteBPETokenizer.train → encode →
    pack. Same learnable repeated-motif structure as the token corpus."""
    rng = np.random.RandomState(seed)
    words = [
        "alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta",
        "theta", "iota", "kappa", "lambda", "mu",
    ]
    docs = []
    for _ in range(n_docs):
        motif = " ".join(rng.choice(words, size=rng.randint(3, 7)))
        docs.append(" ".join([motif] * rng.randint(2, 6)))
    return docs


def main() -> None:
    hvt.init()
    mesh = mesh_lib.build_mesh(
        mesh_lib.MeshSpec.from_string(os.environ.get("HVT_MESH"))
    )
    seq_len = int(os.environ.get("SEQ_LEN", 256))
    vocab = int(os.environ.get("VOCAB", 64))

    if os.environ.get("TEXT"):
        # Full text pipeline: raw strings → trained byte-BPE → token docs.
        from horovod_tpu.data.tokenizer import ByteBPETokenizer

        texts = text_corpus(int(os.environ.get("DOCS", 2000)))
        vocab = int(os.environ.get("VOCAB", 384))
        tokenizer = ByteBPETokenizer.train(texts, vocab_size=vocab)
        vocab = tokenizer.vocab_size  # training may stop below the budget
        docs = tokenizer.encode_corpus(texts)
        if hvt.is_primary():
            path = os.path.join(
                os.environ.get("PS_MODEL_PATH", "./models"), "tokenizer.json"
            )
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tokenizer.save(path)
            raw = sum(len(t.encode()) for t in texts)
            enc = sum(len(d) for d in docs)
            print(
                f"byte-BPE: vocab {vocab}, {raw} bytes -> {enc} tokens "
                f"({raw / enc:.2f} bytes/token), saved {path}"
            )
    else:
        docs = synthetic_corpus(int(os.environ.get("DOCS", 2000)), vocab)
    # Pack at seq_len + 1: the shifted next-token pairs then span exactly
    # seq_len positions — divisible by a live `seq` axis for SP meshes.
    toks, seg, _ = pack_documents(docs, seq_len=seq_len + 1)
    if hvt.is_primary():
        print(
            f"packed {len(docs)} docs -> {toks.shape[0]} rows x "
            f"{toks.shape[1]}, "
            f"occupancy {packing_efficiency(seg):.3f}"
        )
    x, y, w = next_token_pairs(toks, seg)
    seg_x = seg[:, :-1]
    # Tokens ⊕ ids ⊕ loss-weights ride the (x, y) feed: x = [B,T,2] int32,
    # y = [B,T,2] (targets ⊕ weights-as-int-bits is avoidable — weights are
    # 0/1 here, so carry them as an integer channel of y).
    xs = np.stack([x, seg_x], axis=-1)
    ys = np.stack([y, w.astype(np.int32)], axis=-1)

    def masked_ce(logits, y2):
        targets, weights = y2[..., 0], y2[..., 1].astype(jnp.float32)
        per = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), targets
        )
        # Per-example mean with boundary/padding positions zeroed; the
        # Trainer means over the batch, so normalize per row.
        return (per * weights).sum(-1) / jnp.maximum(weights.sum(-1), 1.0)

    model = PackedLM(
        inner=TransformerLM(
            vocab_size=vocab,
            d_model=int(os.environ.get("DMODEL", 128)),
            n_heads=4,
            n_layers=int(os.environ.get("NLAYERS", 2)),
            dropout=0.0,
            compute_dtype=jnp.bfloat16,
            sharding=ShardingConfig(mesh=mesh),
        )
    )
    # Note: the epoch log's generic `accuracy` column is meaningless under
    # the stacked-label format (it argmaxes the 2-channel y); the masked
    # LOSS is the training signal here.
    from jax.sharding import PartitionSpec as P

    batch_spec = P(
        (mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS), mesh_lib.SEQ_AXIS, None
    )
    trainer = hvt.Trainer(
        model,
        hvt.DistributedOptimizer(optax.adamw(hvt.scale_lr(3e-3))),
        loss=masked_ce,
        mesh=mesh,
        # Same layout discipline as lm_long_context.py: tokens⊕ids sharded
        # over (data, seq); Megatron/FSDP parameter rules (path-keyed, so
        # they find the inner model's layers through the PackedLM wrapper).
        param_specs=param_specs,
        batch_specs=(batch_spec, batch_spec),
    )
    rows_needed = 8 * mesh_lib.dp_size(mesh)
    n = (len(xs) // rows_needed) * rows_needed
    if n == 0:
        raise SystemExit(
            f"corpus packs to only {len(xs)} rows but one global batch "
            f"needs {rows_needed} (batch 8 x dp {mesh_lib.dp_size(mesh)}) "
            "- raise DOCS or lower SEQ_LEN"
        )
    history = trainer.fit(
        x=xs[:n], y=ys[:n],
        batch_size=8,
        epochs=int(os.environ.get("DRIVE_EPOCHS", 0)) or 3,
        steps_per_epoch=int(os.environ.get("DRIVE_STEPS", 0)) or 8,
        callbacks=[hvt.callbacks.BroadcastGlobalVariablesCallback(0)],
    )
    if hvt.is_primary():
        first, last = history[0]["loss"], history[-1]["loss"]
        print(f"masked loss: {first:.3f} -> {last:.3f}")
        print("packed pretraining:", "LEARNING" if last < first else "flat")


if __name__ == "__main__":
    main()
