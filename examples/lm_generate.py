"""Train-then-generate walkthrough: the inference side of the framework.

The reference's serving story ends at a SavedModel export
(mnist_keras.py:116-140); this example shows what a user actually does
with a trained LM here:

1. train a small decoder LM on the copy task (long-range recall — the
   greedy continuation of a copy prompt is the prompt's first half);
2. checkpoint it (process-0 single-writer, msgpack);
3. generate with the KV-cache decode loop (`models/decoding.generate`) —
   greedy, then temperature/top-k/top-p sampling;
4. generate the SAME tokens faster with speculative decoding
   (`models/speculative.py`, prompt-lookup draft) and print the measured
   acceptance + agreement — the exactness contract made visible.

Runs on one chip (or CPU) with no launcher. Knobs: DRIVE_EPOCHS,
DRIVE_STEPS, SEQ_LEN, DMODEL, NLAYERS, KV_HEADS (grouped-query
attention), GAMMA (speculative chunk), TEMPERATURE, TOP_K, TOP_P.
"""

import os
import time

try:
    import horovod_tpu  # noqa: F401 — installed (`pip install -e .`)
except ModuleNotFoundError:  # bare source checkout: make the repo importable
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvt
from horovod_tpu import checkpoint
from horovod_tpu.data import datasets
from horovod_tpu.models.decoding import generate, make_generate_fn
from horovod_tpu.models.speculative import make_speculative_fn
from horovod_tpu.models.transformer import TransformerLM

VOCAB = 64


def main():
    hvt.init()
    seq = int(os.environ.get("SEQ_LEN", 128))
    model = TransformerLM(
        vocab_size=VOCAB,
        d_model=int(os.environ.get("DMODEL", 128)),
        n_heads=8,
        n_kv_heads=int(os.environ.get("KV_HEADS", 0)) or None,
        n_layers=int(os.environ.get("NLAYERS", 4)),
        dropout=0.0,
        compute_dtype=jnp.bfloat16,
    )
    trainer = hvt.Trainer(
        model,
        hvt.DistributedOptimizer(optax.adam(hvt.scale_lr(1e-3))),
        loss="sparse_categorical_crossentropy",
    )

    # 1. train on the copy task: second half of each row repeats the first.
    x, y = datasets.copy_task(2048, seq, vocab_size=VOCAB, seed=3)
    hist = trainer.fit(
        x=x, y=y,
        batch_size=32,
        epochs=int(os.environ.get("DRIVE_EPOCHS", 4)),
        steps_per_epoch=int(os.environ.get("DRIVE_STEPS", 48)),
        verbose=1,
    )
    print(f"final train loss: {hist[-1]['loss']:.4f}")

    # 2. checkpoint (rank-0 single-writer), reference-style per-epoch dirs.
    model_dir = os.path.join(
        os.environ.get("PS_MODEL_PATH", "./models"), "lm-generate"
    )
    if hvt.rank() == 0:
        os.makedirs(model_dir, exist_ok=True)
        checkpoint.save(
            os.path.join(model_dir, "checkpoint-final.msgpack"), trainer.state
        )
        print(f"checkpoint -> {model_dir}/checkpoint-final.msgpack")

    params = trainer.state.params
    xt, _ = datasets.copy_task(2, seq, vocab_size=VOCAB, seed=999)
    prompt = jnp.asarray(xt[:, : seq // 2])
    n_new = seq // 2 - 1

    # 3. greedy + sampled generation through the KV-cache decode loop.
    greedy = generate(model, params, prompt, n_new)
    match = float(
        (np.asarray(greedy[:, seq // 2 :]) == np.asarray(xt[:, seq // 2 : -1]))
        .mean()
    )
    print(f"greedy recall of the copied half: {match:.1%}")

    # 3b. STREAM=1: the same generation through the bounded ring-buffer
    # cache (sliding-window + pinned attention sinks — StreamingLLM). The
    # cache is [B, SINKS + WINDOW] slots however long generation runs.
    if os.environ.get("STREAM"):
        streamer = model.clone(
            window=int(os.environ.get("WINDOW", seq // 4)),
            attention_sinks=int(os.environ.get("SINKS", 4)),
            sliding_cache=True,
        )
        streamed = generate(streamer, params, prompt, n_new)
        # Compare the GENERATED half only — the prompt half is identical
        # by construction and would inflate the agreement number.
        agree = float(
            (np.asarray(streamed[:, seq // 2:])
             == np.asarray(greedy[:, seq // 2:])).mean()
        )
        print(
            f"streamed generation ({streamer.attention_sinks} sinks + "
            f"{streamer.window}-slot ring): {agree:.1%} token agreement "
            "with the full cache (approximate for this densely-trained "
            "model — the recipe keeps it stable past its window)"
        )

    sampled = generate(
        model, params, prompt, n_new,
        temperature=float(os.environ.get("TEMPERATURE", 0.8)),
        top_k=int(os.environ.get("TOP_K", 0)),
        top_p=float(os.environ.get("TOP_P", 0.9)),
        rng=jax.random.PRNGKey(0),
    )
    print("sampled tail:", np.asarray(sampled[0, -8:]).tolist())

    # 4. speculative decoding: same tokens, fewer target passes.
    plain_fn = make_generate_fn(model, max_new_tokens=n_new)
    spec_fn = make_speculative_fn(
        model, max_new_tokens=n_new,
        gamma=int(os.environ.get("GAMMA", 8)), return_stats=True,
    )
    key = jax.random.PRNGKey(0)
    jax.block_until_ready(plain_fn(params, prompt, key))  # compile
    out_spec, stats = spec_fn(params, prompt)
    jax.block_until_ready(out_spec)

    t0 = time.time()
    out_plain = jax.device_get(plain_fn(params, prompt, key))
    t_plain = time.time() - t0
    t0 = time.time()
    out_spec = jax.device_get(spec_fn(params, prompt)[0])
    t_spec = time.time() - t0
    rounds = int(jax.device_get(stats["rounds"]))
    agree = bool(np.array_equal(out_plain, out_spec))
    print(
        f"speculative: {rounds} target passes for {n_new} tokens "
        f"({n_new / rounds:.1f} tok/pass), outputs identical: {agree}, "
        f"wall {t_plain * 1e3:.0f} -> {t_spec * 1e3:.0f} ms (single-call "
        f"timings include the host round-trip and say nothing of the "
        f"decode loop's speed)"
    )
    assert agree, "speculative output diverged from plain greedy"


if __name__ == "__main__":
    main()
