"""Benchmark harness — prints ONE JSON line for the driver.

Headline metric (BASELINE.json): MNIST training images/sec/chip through the
full distributed-training step — forward, loss, backward, gradient
allreduce-mean (the DistributedOptimizer path), optimizer apply — on the
reference's exact training config: the 2-conv CNN
(tensorflow2_keras_mnist.py:43-52), per-worker batch 128
(tensorflow2_keras_mnist.py:41), Adam (tensorflow2_keras_mnist.py:55).

``vs_baseline`` is the ratio against the measured reference-equivalent
TF2/Keras single-process run on this machine's CPU
(``benchmarks/baseline_measured.json``, produced by
``benchmarks/measure_reference_baseline.py`` — the reference publishes no
numbers of its own, SURVEY.md §6).

Every run also reports the denominator "match or beat" needs: FLOPs/step from
XLA's cost model on the compiled step, MFU against the chip's peak, and a
step-time breakdown (compute = device-resident batches; input = host slice +
transfer on top of it).

Modes (BENCH_MODEL):
  mnist       (default) reference CNN, per-chip batch 128 bf16
  resnet      CIFAR-10 ResNet-20 — heavier gradients (BASELINE.json config 4)
  vit         CIFAR-10 Vision Transformer (models/vit.py) — the conv-free
              vision family; images/sec + the MFU the conv shapes can't reach
  transformer decoder LM (d512 x 8L, seq 1024, flash attention) — tokens/sec
  moe         same LM with MoE MLPs every 2nd block (8 experts, top-2) —
              tokens/sec + router drop-rate observability
  seq2seq     encoder-decoder (models/seq2seq.py, d512 x 6enc+6dec, seq
              1024): bidirectional encoder + causal decoder + cross-
              attention (the flash kernel's Tk≠Tq grids) — tokens/sec
  accum       gradient-accumulation A/B on the LM config: K=1 vs
              K=BENCH_ACCUM_K (default 4) backward_passes_per_step —
              tokens/sec plus cross-worker reduction calls per OPTIMIZER
              step counted in the compiled step (the accumulating step
              must show exactly one bucketed boundary reduction)
  decode      autoregressive generation (KV-cache prefill + scan decode
              loop, models/decoding.py) — generated tokens/sec
  spec        speculative decoding A/B (models/speculative.py): trains a
              small LM on the copy task ON-CHIP, then measures plain
              greedy vs speculative (prompt-lookup draft) on copy prompts —
              exact-output speedup + acceptance rate
  input       host input pipeline A/B: native C++ batch assembly vs Python
  serve       serving-tier tail-latency A/B: continuous batching vs the
              legacy coalescing path through the real server
              (launch/serve.py), same open-loop arrival schedule both
              legs — TTFT/TPOT p50/p95/p99; exits 1 unless continuous
              wins p95 TTFT at equal offered load

HVT_PROFILE=<dir> captures a jax.profiler trace of the measured loop.
"""

from __future__ import annotations

import json
import os
import time

BATCH = 128
REPO = os.path.dirname(os.path.abspath(__file__))


def _fused_ce_chunks() -> int:
    """BENCH_FUSED_CE chunk count. Default ON (8 chunks): the fused
    chunked linear-CE head (ops/fused_ce.py) is the bench LM's default
    config — the [B, T, vocab] logits tensor never materializes. Export
    BENCH_FUSED_CE=0 to bench the dense head."""
    return int(os.environ.get("BENCH_FUSED_CE", 8))


def _lm_loss() -> str:
    """Trainer loss matching the fused-CE default: the module computes the
    loss when the fused head is on."""
    return "module" if _fused_ce_chunks() else "sparse_categorical_crossentropy"


def _wire_compression() -> str:
    """HVT_COMPRESSION for the train benches (none/bf16/fp16/int8/fp8 →
    DistributedOptimizer(compression=...))."""
    from horovod_tpu.analysis import registry

    return registry.get_str("HVT_COMPRESSION") or "none"


def _ici_compression() -> str:
    """HVT_COMPRESSION_ICI — the two-hop reduction's ICI-hop wire
    (DistributedOptimizer(compression_ici=...)); inert on single-slice
    meshes."""
    from horovod_tpu.analysis import registry

    return registry.get_str("HVT_COMPRESSION_ICI") or "none"


def _resolve_peak_flops() -> tuple:
    """(per-chip peak FLOP/s, source) for the MFU denominator —
    `trace.resolve_peak_flops`, which the live trainer MFU gauge shares so
    both surfaces divide by the same number: the ``HVT_PEAK_FLOPS``
    override (an unparseable value exits 2 in main()), the built-in peak
    table by ``device_kind`` (an unknown accelerator raises), and on the
    CPU platform only a matmul calibration of this host (source
    ``"calibrated"`` — a CI trend denominator, never a device number).
    Callers hand the value to `trace.mfu(..., peak=)`."""
    from horovod_tpu import trace

    return trace.resolve_peak_flops(calibrate=True)


def _lm_from_env(*, moe: bool = False):
    """The bench transformer, one source of truth for its env knobs — the
    decode rows must measure the same model the training rows do."""
    import jax.numpy as jnp

    from horovod_tpu import runtime
    from horovod_tpu.models.transformer import TransformerLM

    return TransformerLM(
        vocab_size=8192,
        d_model=int(os.environ.get("BENCH_DMODEL", 512)),
        n_heads=int(os.environ.get("BENCH_HEADS", 8)),
        # Grouped-query attention: 0/unset = MHA. Decode's KV-cache stream
        # shrinks by n_heads/n_kv_heads (the BENCH_MODEL=decode A/B knob).
        n_kv_heads=int(os.environ.get("BENCH_KV_HEADS", 0)) or None,
        n_layers=int(os.environ.get("BENCH_NLAYERS", 8)),
        # BENCH_WINDOW: sliding-window (local) attention — the flash kernel
        # block-skips tiles outside the band, so long-seq steps get
        # proportionally faster (and MFU accounts the executed band only).
        window=int(os.environ.get("BENCH_WINDOW", 0)) or None,
        # BENCH_SINKS (with BENCH_WINDOW): global+local attention — the
        # first S positions ride the kernel's pinned sink tile.
        attention_sinks=int(os.environ.get("BENCH_SINKS", 0)),
        # BENCH_SLIDING=1 (decode mode, needs BENCH_WINDOW): ring-buffer KV
        # cache — O(window) cache reads per generated token instead of
        # O(prompt+new_tokens), the decode-side win of a window.
        sliding_cache=runtime.env_flag("BENCH_SLIDING"),
        compute_dtype=jnp.bfloat16,
        dropout=0.0,  # LM-pretraining norm (and threefry dropout costs
        # ~12%/step — HVT_FAST_RNG=1 makes dropout free when wanted)
        # moe mode: expert-parallel MLP every 2nd block (models/moe.py).
        moe_every=2 if moe else 0,
        n_experts=int(os.environ.get("BENCH_EXPERTS", 8)),
        moe_k=int(os.environ.get("BENCH_MOE_K", 2)),
        capacity_factor=float(os.environ.get("BENCH_CAPACITY", 1.25)),
        # BENCH_MOE_ROUTER=expert_choice: drop-free expert-choice routing
        # (models/moe.py) — observability metric becomes uncovered-rate.
        moe_router=os.environ.get("BENCH_MOE_ROUTER", "top_k"),
        # Long-context memory knobs:
        remat=runtime.env_flag("BENCH_REMAT"),
        logits_dtype=jnp.bfloat16
        if os.environ.get("BENCH_LOGITS", "") == "bf16"
        else jnp.float32,
        # BENCH_FUSED_CE=<n_chunks>: fused chunked linear-CE head
        # (ops/fused_ce.py) — the [B, T, vocab] logits + cotangent are never
        # materialized; the train rows switch to Trainer(loss='module').
        # DEFAULT ON (8 chunks) — export BENCH_FUSED_CE=0 for the dense head.
        fused_head_chunks=_fused_ce_chunks(),
    )


def _timed(fn):
    """Wall time of `fn` with HONEST completion: `fn` must return a device
    scalar, which is fetched to the host before the clock stops.

    Dispatch is asynchronous, so a clock stopped before the device is done
    measures the enqueue. Fetching a value that data-depends on the whole
    chain cannot return early. The benchmarks here time ONE fused scan over
    many steps (plus this fetch), not a Python loop of step dispatches —
    which also means they bypass the input pipeline (ROADMAP S1)."""
    import jax

    t0 = time.perf_counter()
    out = fn()
    float(jax.device_get(out))
    return time.perf_counter() - t0


def bench_train(which: str) -> dict:
    # TPU hardware RNG by default (runtime.py HVT_FAST_RNG): threefry
    # dropout costs up to 40% of a small step. Export HVT_FAST_RNG="" to
    # bench the bit-reproducible default instead.
    os.environ.setdefault("HVT_FAST_RNG", "1")

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvt
    from horovod_tpu import runtime, trace
    from horovod_tpu.data import datasets

    hvt.init()
    n_chips = jax.device_count()

    if which == "resnet":
        from horovod_tpu.models.resnet import ResNetCIFAR

        (x_train, y_train), _ = datasets.cifar10()
        # Raw uint8 to the device; the model normalizes on-chip (4x less
        # host->device traffic than pre-normalized float32).
        x = x_train
        y = y_train.astype(np.int32)
        module = ResNetCIFAR(depth=20, compute_dtype=jnp.bfloat16)
        metric = "cifar10_resnet20_train_images_per_sec_per_chip"
        # Default 128 = the reference's per-worker batch (honest comparison
        # config); BENCH_BATCH=512 was the throughput sweet spot of the
        # benchmarks/conv_profile.py sweep (not measured on this round's
        # chip).
        per_chip_batch = int(os.environ.get("BENCH_BATCH", BATCH))
        unit_per_step = per_chip_batch * n_chips
        lr = optax.adam(hvt.scale_lr(1e-3))
        loss = "sparse_categorical_crossentropy"
        unit = "images/sec/chip"
        default_steps = 256
    elif which == "vit":
        # The conv-free vision family (models/vit.py): image classification
        # as MXU-shaped matmuls — the TPU-first answer to the conv models'
        # shape-bound MFU ceiling (benchmarks/conv_profile.py).
        from horovod_tpu.models.vit import ViT

        (x_train, y_train), _ = datasets.cifar10()
        x = x_train
        y = y_train.astype(np.int32)
        module = ViT(
            patch_size=int(os.environ.get("BENCH_PATCH", 4)),
            d_model=int(os.environ.get("BENCH_DMODEL", 512)),
            n_heads=int(os.environ.get("BENCH_HEADS", 8)),
            n_layers=int(os.environ.get("BENCH_NLAYERS", 8)),
            dropout=0.0,
            compute_dtype=jnp.bfloat16,
        )
        metric = "cifar10_vit_train_images_per_sec_per_chip"
        per_chip_batch = int(os.environ.get("BENCH_BATCH", BATCH))
        unit_per_step = per_chip_batch * n_chips
        lr = optax.adam(hvt.scale_lr(1e-3))
        loss = "sparse_categorical_crossentropy"
        unit = "images/sec/chip"
        default_steps = 256
    elif which == "seq2seq":
        # Encoder-decoder family (models/seq2seq.py) on a translation-shaped
        # synthetic task (target = copy of the source, teacher-forced). The
        # harness feeds ONE [B, S+T] int array and a thin adapter splits it
        # into the model's {'src','tgt'} dict, so the flat-array bench legs
        # (chunk stacking, device-cached e2e) apply unchanged — the
        # dict-input feeding path itself is covered by tests/test_seq2seq.py.
        import flax.linen as nn

        from horovod_tpu.models.seq2seq import Seq2SeqTransformer

        seq_len = int(os.environ.get("BENCH_SEQ_LEN", 1024))
        per_chip_batch = int(os.environ.get("BENCH_LM_BATCH", 8))
        d_model = int(os.environ.get("BENCH_DMODEL", 512))
        enc_l = int(os.environ.get("BENCH_ENC_LAYERS", 6))
        dec_l = int(os.environ.get("BENCH_DEC_LAYERS", 6))
        heads = int(os.environ.get("BENCH_HEADS", 8))
        rng0 = np.random.RandomState(0)
        src = rng0.randint(3, 8192, size=(4096, seq_len)).astype(np.int32)
        tgt_in = np.concatenate(
            [np.ones((4096, 1), np.int32), src[:, :-1]], axis=1
        )
        inner = Seq2SeqTransformer(
            vocab_size=8192, d_model=d_model, n_heads=heads,
            n_enc_layers=enc_l, n_dec_layers=dec_l, dropout=0.0,
            compute_dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16,
        )

        class _SeqPair(nn.Module):
            inner: Seq2SeqTransformer
            src_len: int

            @nn.compact
            def __call__(self, xy, train: bool = False):
                return self.inner(
                    {"src": xy[:, : self.src_len], "tgt": xy[:, self.src_len:]},
                    train=train,
                )

        module = _SeqPair(inner=inner, src_len=seq_len)
        x = np.concatenate([src, tgt_in], axis=1)
        y = src  # labels: reproduce the source token-for-token
        metric = "seq2seq_train_tokens_per_sec_per_chip"
        unit_per_step = per_chip_batch * n_chips * seq_len  # trained labels
        lr = optax.adamw(hvt.scale_lr(3e-4))
        loss = "sparse_categorical_crossentropy"
        unit = "tokens/sec/chip"
        default_steps = 32
    elif which in ("transformer", "moe"):
        seq_len = int(os.environ.get("BENCH_SEQ_LEN", 1024))
        per_chip_batch = int(os.environ.get("BENCH_LM_BATCH", 8))
        x_np, y_np = datasets.copy_task(4096, seq_len, vocab_size=8192)
        x, y = x_np, y_np
        module = _lm_from_env(moe=which == "moe")
        metric = (
            "moe_lm_train_tokens_per_sec_per_chip"
            if which == "moe"
            else "transformer_lm_train_tokens_per_sec_per_chip"
        )
        n_docs = int(os.environ.get("BENCH_PACK_DOCS", 0))
        if n_docs:
            # Packed-sequence pretraining: each row holds n_docs documents;
            # the flash kernel's segment masking (block-level early-out)
            # keeps cross-document tiles off the MXU. Fixed equal-length
            # packing so the Trainer's (x, y) feed needs no extra channel.
            import flax.linen as nn

            class _PackedLM(nn.Module):
                inner: TransformerLM
                docs: int

                @nn.compact
                def __call__(self, tokens, *, train: bool = False, labels=None):
                    b, t = tokens.shape
                    ids = jnp.repeat(
                        jnp.arange(self.docs, dtype=jnp.int32), t // self.docs
                    )
                    ids = jnp.broadcast_to(ids, (b, t))
                    return self.inner(
                        tokens, train=train, segment_ids=ids, labels=labels
                    )

            module = _PackedLM(inner=module, docs=n_docs)
            metric += "_packed"
        # copy_task returns [n, seq_len] next-token pairs: every position is
        # a trained label.
        unit_per_step = per_chip_batch * n_chips * seq_len
        lr = optax.adamw(hvt.scale_lr(3e-4))
        # Fused chunked-CE head (default on): the module computes the loss
        # (see _lm_from_env's fused_head_chunks knob).
        loss = _lm_loss()
        unit = "tokens/sec/chip"
        default_steps = 48
    else:
        from horovod_tpu.models.cnn import MnistCNN

        (x_train, y_train), _ = datasets.mnist()
        x = x_train[..., None]  # uint8; on-device normalize (see resnet note)
        y = y_train.astype(np.int32)
        module = MnistCNN(compute_dtype=jnp.bfloat16)
        metric = "mnist_train_images_per_sec_per_chip"
        per_chip_batch = int(os.environ.get("BENCH_BATCH", BATCH))
        unit_per_step = per_chip_batch * n_chips
        lr = optax.adam(hvt.scale_lr(1e-3))
        loss = "sparse_categorical_crossentropy"
        unit = "images/sec/chip"
        default_steps = 1024

    peak_flops, peak_src = _resolve_peak_flops()
    compression = _wire_compression()
    trainer = hvt.Trainer(
        module,
        hvt.DistributedOptimizer(
            lr, compression=compression,
            compression_ici=_ici_compression(),
        ),
        loss=loss,
    )

    n_steps = int(os.environ.get("BENCH_STEPS", default_steps))
    global_batch = per_chip_batch * n_chips
    rng = np.random.RandomState(0)

    def draw():
        idx = rng.randint(0, len(x), size=global_batch)
        return x[idx], y[idx]

    sample = draw()
    state = trainer.build(sample[0])
    state = hvt.broadcast_parameters(state, mesh=trainer.mesh)
    scale = np.float32(1.0)
    # Accumulator keys come from the trainer: models may sow extra metrics
    # (e.g. the MoE router drop-rate) that travel with loss/accuracy.
    zero_acc = {k: np.float32(0) for k in trainer.metric_names}

    # --- compute time: ONE fused scan over n_steps (see _timed's note).
    # Chained BENCH_E2E_REPS times per fetch, exactly like the e2e leg
    # below: the two legs must amortize the per-fetch host round-trip
    # identically, or the difference masquerades as phase time (a
    # `compute > total` accounting bug). ----------------------------------
    reps = max(1, int(os.environ.get("BENCH_E2E_REPS", 4)))
    steps = [draw() for _ in range(n_steps)]
    mega = tuple(np.stack([s[i] for s in steps]) for i in range(2))
    dev_mega = trainer._shard_chunk(mega)
    compiled_mega = trainer._train_chunk.lower(
        state, dev_mega, scale, zero_acc
    ).compile()
    # warm (compile already done; first run settles the runtime)
    w_state, _, w_acc = compiled_mega(state, dev_mega, scale, zero_acc)
    float(jax.device_get(w_acc["loss"]))

    # The step donates its input state: always pass the PREVIOUS call's
    # returned state, never a saved one (its buffers are consumed).
    holder = {"state": w_state}

    def run_mega():
        for _ in range(reps):
            holder["state"], m, acc = compiled_mega(
                holder["state"], dev_mega, scale, zero_acc
            )
            holder["acc"] = acc  # last measured pass — extras read it
        return acc["loss"]

    with trace.maybe_trace(trace.profile_dir()):
        compute_s = _timed(run_mega) / (n_steps * reps)

    # --- comm time: the boundary reduction in isolation — the same
    # bucketed/hierarchical/compressed program the step runs (or, on the
    # implicit-SPMD path, its explicit equivalent over the same gradient
    # shapes), chained per fetch like the legs above. On one chip this
    # measures dispatch-amortized psum overhead (≈0); on a real mesh it is
    # the exposed wire time a perfectly-overlapped step would hide. -------
    comm_s = _timed_reduction(trainer, holder["state"].params, reps)

    # Module-sown metrics (e.g. moe_drop_rate), averaged over the MEASURED
    # pass — the steady state the throughput number describes, not warm-up.
    sums = {k: float(v) for k, v in jax.device_get(holder["acc"]).items()}
    extra_metrics = {
        k: round(sums[k] / n_steps, 4)
        for k in trainer.metric_names
        if k not in ("loss", "accuracy")
    }

    # FLOPs of one training step (fwd + bwd + allreduce + optimizer) from
    # XLA's cost model — scan bodies are counted once, so the single-step
    # compile gives the honest per-step count.
    flops = trace.compiled_flops(
        trainer._train_step, w_state, trainer._shard(sample), scale, zero_acc
    )
    if flops and which in ("transformer", "moe"):
        # The pallas flash kernel is a Mosaic custom call — opaque to XLA's
        # cost model, so its matmuls (counted from the kernel's own block
        # structure) are added per layer — but ONLY when the kernel path
        # actually runs: on shapes where `flash_attention` degrades to the
        # dense fallback, XLA's count already includes attention and adding
        # the analytic term would double-count it.
        from horovod_tpu.ops import flash_attention as fa_kernel

        heads = int(os.environ.get("BENCH_HEADS", 8))
        head_dim = int(os.environ.get("BENCH_DMODEL", 512)) // heads
        q_shape = (per_chip_batch * n_chips, seq_len, heads, head_dim)
        seg = bool(n_docs)
        blocks = fa_kernel.pick_blocks(
            seq_len, head_dim, jnp.bfloat16, segmented=seg
        )
        if fa_kernel.supported(
            q_shape, *blocks, dtype=jnp.bfloat16, segmented=seg
        ):
            window = int(os.environ.get("BENCH_WINDOW", 0)) or None
            n_layers = int(os.environ.get("BENCH_NLAYERS", 8))
            if n_docs:
                # Equal-length packed documents: executed score entries are
                # the band ∩ same-document area — each document is its own
                # length-L windowed causal attention (w = min(window, L);
                # no window = the causal triangle), summed over docs. A
                # plain min() of the two discounts would overstate it near
                # window ≈ L (the band crosses doc boundaries, where the
                # segment early-out skips tiles).
                L = seq_len // n_docs
                fa = trace.flash_attention_flops(
                    per_chip_batch * n_chips, L, L, heads, head_dim,
                    window=min(window or L, L),
                ) * n_layers * n_docs
            else:
                fa = trace.flash_attention_flops(
                    per_chip_batch * n_chips, seq_len, seq_len, heads,
                    head_dim, window=window,
                ) * n_layers
            flops += fa
        lm = module.inner if n_docs else module
        if lm.fused_head_chunks > 1:
            # The fused head's chunk scan is likewise undercounted by the
            # cost model (body counted once, executed n_chunks times).
            flops += trace.fused_ce_flops(
                per_chip_batch * n_chips * seq_len,
                lm.d_model, lm.vocab_size, lm.fused_head_chunks,
            )
    elif flops and which == "seq2seq":
        # Three flash calls per step: encoder self (non-causal, segmented),
        # decoder self (causal), cross (non-causal Tk≠Tq grids, segmented) —
        # all opaque to XLA's cost model.
        from horovod_tpu.ops import flash_attention as fa_kernel

        head_dim = d_model // heads
        B = per_chip_batch * n_chips
        q_shape = (B, seq_len, heads, head_dim)
        fa = 0.0
        blocks_seg = fa_kernel.pick_blocks(
            seq_len, head_dim, jnp.bfloat16, segmented=True
        )
        if fa_kernel.supported(
            q_shape, *blocks_seg, dtype=jnp.bfloat16, segmented=True
        ):
            full = trace.flash_attention_flops(
                B, seq_len, seq_len, heads, head_dim, causal=False
            )
            fa += full * enc_l  # encoder self-attention
            fa += full * dec_l  # cross-attention (Tq == Tk here)
        blocks = fa_kernel.pick_blocks(seq_len, head_dim, jnp.bfloat16)
        if fa_kernel.supported(q_shape, *blocks, dtype=jnp.bfloat16):
            fa += trace.flash_attention_flops(
                B, seq_len, seq_len, heads, head_dim, causal=True
            ) * dec_l  # decoder self-attention
        flops += fa

    # --- end-to-end: training WITH its input pipeline — the device-resident
    # dataset path (`Trainer.fit(cache='device')`): dataset staged into HBM
    # once, then shuffle + gather + train run inside one compiled epoch.
    # e2e - compute = the on-device input pipeline's cost. -------------------
    data, per_shard = trainer._stage_device_dataset(x[: len(y)], y)
    epoch_steps = min(n_steps, per_shard // per_chip_batch)
    seed = jax.random.PRNGKey(7)
    compiled_epoch = trainer._train_epoch.lower(
        w_state, data, seed, scale, zero_acc, epoch_steps, per_chip_batch
    ).compile()

    # Several epochs chain per timed fetch: each epoch's DONATED state feeds
    # the next, so the final fetched loss data-depends on the whole chain
    # (the _timed honesty requirement holds), while the per-fetch host
    # round-trip — which would otherwise be billed to every step as fake
    # "input" time — is amortized across all of them.
    e2e_reps = max(1, int(os.environ.get("BENCH_E2E_REPS", 4)))

    def run_e2e():
        for _ in range(e2e_reps):
            holder["state"], m, acc = compiled_epoch(
                holder["state"], data, seed, scale, zero_acc
            )
        return acc["loss"]

    # Warm WITH a fetch: un-fetched async work from the warm pass would still
    # be executing when the timed pass starts (see _timed).
    # ONE epoch suffices to settle the runtime — no need to burn e2e_reps.
    holder["state"], _, warm_acc = compiled_epoch(
        holder["state"], data, seed, scale, zero_acc
    )
    float(jax.device_get(warm_acc["loss"]))
    e2e_s = _timed(run_e2e) / (epoch_steps * e2e_reps)

    per_sec_per_chip = unit_per_step / e2e_s / n_chips
    # Per-phase breakdown, one consistent accounting: `total` is the
    # end-to-end step (training + on-device input pipeline, the number the
    # throughput headline divides by); `comm` is the isolated boundary
    # reduction; `compute` is the compute leg minus its comm share;
    # `input` is the remainder. Phases are clamped into [0, total] so they
    # sum to exactly `total` — and main() exits non-zero if any reported
    # phase still exceeds it (the r04 regression guard).
    total_s = e2e_s
    comm_clamped = min(comm_s, total_s)
    compute_clamped = min(
        max(compute_s - comm_s, 0.0), total_s - comm_clamped
    )
    input_s = max(0.0, total_s - comm_clamped - compute_clamped)
    # MFU is the HEADLINE: achieved FLOP/s through the full end-to-end
    # step against fleet peak — the "how idle are the chips" number the
    # throughput value can't show. mfu_compute excludes input time (the
    # old headline's denominator, kept for trend comparison).
    mfu_e2e = trace.mfu(flops, total_s, n_chips, peak=peak_flops)
    mfu_compute = trace.mfu(flops, compute_s, n_chips, peak=peak_flops)
    return {
        "mfu": round(mfu_e2e, 4) if mfu_e2e is not None else None,
        "metric": metric,
        "value": round(per_sec_per_chip, 1),
        "unit": unit,
        "flops_per_step": flops,
        "mfu_compute": (
            round(mfu_compute, 4) if mfu_compute is not None else None
        ),
        "step_ms": {
            "total": round(total_s * 1e3, 3),
            "compute": round(compute_clamped * 1e3, 3),
            "comm": round(comm_clamped * 1e3, 3),
            "input": round(input_s * 1e3, 3),
        },
        "overlap_reduction": trainer._overlap,
        "compression": compression,
        "peak_flops_per_chip": peak_flops,
        "peak_flops_source": peak_src,
        "n_chips": n_chips,
        **extra_metrics,
    }


def _reduction_program(trainer, params):
    """(jitted fn, gradient-shaped zeros, lowered text) of the boundary
    gradient reduction in isolation: the same
    `collectives.reduce_gradients` program the explicit step embeds
    (bucketing, order, dcn two-hop, wire dtype, ZeRO-1 scatter — all
    from the trainer)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.parallel import collectives
    from horovod_tpu.parallel import mesh as mesh_lib

    P = jax.sharding.PartitionSpec
    grads = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params
    )
    scatter = getattr(trainer, "_scatter", 1)

    def red(g):
        out = collectives.reduce_gradients(
            g,
            data_axis=mesh_lib.DATA_AXIS,
            extra_axes=(mesh_lib.FSDP_AXIS,),
            dcn=trainer._dcn,
            wire_dtype=trainer._comm_dtype,
            ici_wire_dtype=getattr(trainer, "_ici_dtype", None),
            bucket_bytes=trainer._bucket_bytes,
            reverse=trainer._bucket_reverse,
            scatter=scatter if scatter > 1 else None,
        )
        # Scalar data-dependency on every reduced bucket (honest fetch).
        t = sum(
            jnp.sum(l.astype(jnp.float32)) for l in jax.tree.leaves(out)
        )
        if scatter > 1:
            # Scattered outputs differ per shard; one scalar psum makes
            # the fetch replicated (excluded from the byte accounting —
            # scalar ops never count as payload).
            t = jax.lax.psum(
                t, (mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS)
            )
        return t

    f = jax.jit(jax.shard_map(
        red, mesh=trainer.mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False,
    ))
    return f, grads, f.lower(grads).as_text()


def _timed_reduction(trainer, params, reps: int) -> float:
    """Per-step wall time of the isolated boundary reduction
    (`_reduction_program`), chained ``reps`` times per honest fetch."""
    import jax
    import jax.numpy as jnp

    f, grads, _ = _reduction_program(trainer, params)
    float(jax.device_get(f(grads)))  # compile + settle

    def chain():
        t = jnp.float32(0)
        for _ in range(reps):
            t = t + f(grads)
        return t

    return _timed(chain) / reps


def _per_bucket_comm_ms(trainer, params, reps: int) -> list:
    """Per-BUCKET wall time + payload bytes of the isolated scatter
    reduction — the step_ms attribution that shows WHICH bucket's wire
    time the overlap has to hide. Only meaningful on the scatter layout
    (leaf-aligned buckets make a single bucket's reduction a
    self-contained program — DCE drops every other leaf); quantized DCN
    wires keep the dense bucket layout, so callers skip this there."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.parallel import collectives
    from horovod_tpu.parallel import mesh as mesh_lib

    P = jax.sharding.PartitionSpec
    dp = trainer._scatter
    grads = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params
    )
    buckets, _spec = collectives.flatten_scatter_buckets(
        grads, dp, trainer._bucket_bytes, reverse=trainer._bucket_reverse
    )
    sizes = [int(b.size) * 4 for b in buckets]
    out = []
    for bi in range(len(buckets)):
        def red(g, bi=bi):
            bs, _s = collectives.flatten_scatter_buckets(
                g, dp, trainer._bucket_bytes,
                reverse=trainer._bucket_reverse,
            )
            loc, _err = collectives._scatter_reduce_bucket(
                bs[bi], mesh_lib.DATA_AXIS, trainer._dcn,
                trainer._comm_dtype, (mesh_lib.FSDP_AXIS,),
                ici_wire_dtype=getattr(trainer, "_ici_dtype", None),
            )
            t = jnp.sum(loc.astype(jnp.float32))
            return jax.lax.psum(
                t, (mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS)
            )

        f = jax.jit(jax.shard_map(
            red, mesh=trainer.mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False,
        ))
        float(jax.device_get(f(grads)))  # compile + settle

        def chain(f=f):
            t = jnp.float32(0)
            for _ in range(reps):
                t = t + f(grads)
            return t

        ms = _timed(chain) / reps * 1e3
        out.append({"bytes": sizes[bi], "ms": round(ms, 3)})
    return out


def _flops_guard(k: int, overlap: bool, flops_micro, cost_k) -> dict:
    """The MFU-denominator drift guard: ``flops_per_opt_step`` is
    derived as K x the K=1 (scan/peel-free) compile's count, so assert
    the K>1 program's OWN cost-model count matches the peel structure.
    The K-program statically counts each UNROLLED microbatch once plus
    the accumulation scan's body once: ``counted = 1 (first microbatch)
    + 1 (the peeled last microbatch, overlap on) + 1 (scan body, when a
    scan remains)``. If the peel silently changed program structure
    (stopped peeling, unrolled everything), cost_k leaves the
    [counted - 0.5, counted + 0.5] x flops_micro band and the bench
    exits non-zero."""
    peel = overlap and k > 1
    n_scan = k - 1 - (1 if peel else 0)
    counted = 1 + (1 if peel else 0) + (1 if n_scan > 0 else 0)
    if not flops_micro or not cost_k or k <= 1:
        return {"counted_microbatches": counted, "cost_flops": cost_k,
                "ok": True, "skipped": True}
    lo = (counted - 0.5) * flops_micro
    hi = (counted + 0.5) * flops_micro
    return {
        "counted_microbatches": counted,
        "cost_flops": cost_k,
        "band": [round(lo), round(hi)],
        "ok": bool(lo <= cost_k <= hi),
        "skipped": False,
    }


def _wire_bytes_per_step(text: str, world: int) -> float:
    """Structural per-device bytes-on-wire of one boundary reduction,
    from its LOWERED program text: every non-scalar collective's payload
    (`hlo_audit.op_bytes`) weighted by its ring transfer factor — an
    all-reduce moves ~2x its payload per device, reduce-scatter ~1x its
    (full, pre-scatter) input, all-gather/all-to-all ~1x the result —
    each x (world-1)/world. Scale gathers and the honest-fetch scalar
    psum are scalar/rank-1-of-world and cost their true (tiny) bytes."""
    from horovod_tpu.analysis import hlo_audit

    ring = (world - 1) / world if world > 1 else 0.0
    total = 0.0
    for op in hlo_audit.collective_ops(text):
        if op.scalar:
            continue
        payload = hlo_audit.op_bytes(op)
        if op.kind == "all-reduce":
            total += 2 * payload * ring
        elif op.kind == "reduce-scatter":
            # op payload is the RESULT (1/world of the input bucket).
            total += payload * world * ring
        else:  # all-gather / all-to-all / collective-permute
            total += payload * ring
    return total


def _reduction_calls(hlo: str) -> int:
    """Cross-worker GRADIENT reduction ops in a compiled step's HLO text.

    Since PR 9 this is `analysis.hlo_audit.gradient_reductions` — the
    ONE implementation of the payload-vs-scale-gather discrimination
    (non-scalar all-reduces plus rank >= 2 payload gathers; the
    quantized wire's rank-1 per-bucket scale gathers stay out), shared
    with the perf-path tests and the `hvt-audit` CLI."""
    from horovod_tpu.analysis import hlo_audit

    return len(hlo_audit.gradient_reductions(hlo))


def bench_accum() -> dict:
    """Gradient-accumulation A/B (Horovod's ``backward_passes_per_step``):
    K=1 vs K=BENCH_ACCUM_K (default 4) on the LM training config.

    Reports tokens/sec/chip for both runs and, the load-bearing number,
    cross-worker reduction calls per OPTIMIZER step from the compiled
    step's HLO: the K=1 step carries XLA's per-step gradient reduction,
    the accumulating step must show exactly the bucket count (one large
    fused reduction at default bucket bytes) regardless of K — gradient
    communication per sample divided by K. Same honesty rules as the
    training benches: one fused scan per timed fetch (_timed)."""
    os.environ.setdefault("HVT_FAST_RNG", "1")

    import jax
    import numpy as np
    import optax

    import horovod_tpu as hvt
    from horovod_tpu import trace
    from horovod_tpu.data import datasets

    hvt.init()
    n_chips = jax.device_count()
    K = max(2, int(os.environ.get("BENCH_ACCUM_K", 4)))
    seq_len = int(os.environ.get("BENCH_SEQ_LEN", 1024))
    per_chip_batch = int(os.environ.get("BENCH_LM_BATCH", 8))
    x, y = datasets.copy_task(4096, seq_len, vocab_size=8192)
    n_steps = int(os.environ.get("BENCH_STEPS", 16))  # optimizer steps
    global_batch = per_chip_batch * n_chips

    peak_flops, peak_src = _resolve_peak_flops()
    compression = _wire_compression()

    def measure(k: int) -> tuple:
        trainer = hvt.Trainer(
            _lm_from_env(),
            hvt.DistributedOptimizer(
                optax.adamw(hvt.scale_lr(3e-4)),
                backward_passes_per_step=k,
                # Mean over the K passes: the effective LR then matches
                # the K=1 leg, so the A/B compares communication, not
                # optimization trajectories.
                average_aggregated_gradients=True,
                compression=compression,
                compression_ici=_ici_compression(),
            ),
            loss=_lm_loss(),
        )
        rng = np.random.RandomState(0)

        def draw():
            idx = rng.randint(0, len(x), size=global_batch)
            return x[idx], y[idx]

        def step_batch():
            # One optimizer step's feed: [G, T] for k=1, a [k, G, T]
            # microbatch stack for the accumulating step.
            if k == 1:
                return draw()
            micro = [draw() for _ in range(k)]
            return tuple(np.stack([m[i] for m in micro]) for i in range(2))

        sample = draw()
        state = trainer.build(sample[0])
        state = hvt.broadcast_parameters(state, mesh=trainer.mesh)
        scale = np.float32(1.0)
        zero_acc = {m: np.float32(0) for m in trainer.metric_names}
        # Reduction count from the compiled SINGLE step (before the mega
        # run donates the state's buffers).
        one = step_batch()
        dev_one = (
            trainer._shard(one) if k == 1 else trainer._shard_chunk(one, 1)
        )
        compiled_one = trainer._train_step.lower(
            state, dev_one, scale, zero_acc
        ).compile()
        reductions = _reduction_calls(compiled_one.as_text())
        # Per-MICROBATCH flops from the single step's cost model (the scan
        # body is counted once, so the k=1 compile is the honest
        # per-microbatch count; the K leg's per-optimizer-step flops are
        # K x this, compute dominating the shared reduction/update tail).
        flops_micro = (
            trace.compiled_cost_flops(compiled_one) if k == 1 else None
        )
        # Timed leg: ONE fused scan over n_steps optimizer steps.
        steps = [step_batch() for _ in range(n_steps)]
        mega = tuple(np.stack([s[i] for s in steps]) for i in range(2))
        dev_mega = trainer._shard_chunk(mega, 2 if k > 1 else 1)
        compiled = trainer._train_chunk.lower(
            state, dev_mega, scale, zero_acc
        ).compile()
        w_state, _, w_acc = compiled(state, dev_mega, scale, zero_acc)
        float(jax.device_get(w_acc["loss"]))
        holder = {"state": w_state}

        def run():
            holder["state"], _, acc = compiled(
                holder["state"], dev_mega, scale, zero_acc
            )
            return acc["loss"]

        sec_per_opt_step = _timed(run) / n_steps
        tokens_per_opt_step = k * global_batch * seq_len
        return (
            tokens_per_opt_step / sec_per_opt_step / n_chips,
            reductions, sec_per_opt_step, flops_micro, trainer,
        )

    tok_k1, red_k1, sec_k1, flops_micro, _ = measure(1)
    tok_kn, red_kn, sec_kn, _, trainer_k = measure(K)
    # Per-optimizer-step flops of the K leg ~= K x the per-microbatch
    # count (see measure); MFU headline-first like the train benches.
    flops_k = flops_micro * K if flops_micro else None
    mfu_k = (
        trace.mfu(flops_k, sec_kn, n_chips, peak=peak_flops)
        if flops_k else None
    )
    mfu_k1 = (
        trace.mfu(flops_micro, sec_k1, n_chips, peak=peak_flops)
        if flops_micro else None
    )
    return {
        "mfu": round(mfu_k, 4) if mfu_k is not None else None,
        "metric": "accum_train_tokens_per_sec_per_chip",
        "value": round(tok_kn, 1),
        "unit": "tokens/sec/chip",
        "k": K,
        "k1_tokens_per_sec_per_chip": round(tok_k1, 1),
        "speedup": round(tok_kn / tok_k1, 2),
        "mfu_k1": round(mfu_k1, 4) if mfu_k1 is not None else None,
        "flops_per_opt_step": flops_k,
        # K=1: XLA's implicit reduction, per microbatch == per step.
        # K=N: the single bucketed boundary reduction — per-sample
        # gradient communication divided by N.
        "reduction_calls_per_opt_step": {"k1": red_k1, f"k{K}": red_kn},
        "overlap_reduction": trainer_k._overlap,
        "compression": compression,
        "peak_flops_per_chip": peak_flops,
        "peak_flops_source": peak_src,
        "per_chip_batch": per_chip_batch,
        "seq_len": seq_len,
        "n_chips": n_chips,
    }


def _sampler_overhead(hvt, module, x, y, K, compression, compression_ici,
                      bucket_bytes, global_batch):
    """A/B the live `StepPhaseSampler` (ISSUE 13): its steady-state cost
    must be <= BENCH_SAMPLER_MAX_OVERHEAD_PCT (default 2%) of
    ``step_ms.total`` on the composed zero1 step, at the sampler's real
    cadence (``HVT_METRICS_EVERY``). Two measured components:

    * the per-window drain/publish cost, measured as a wall-clock A/B:
      both legs run the SAME python per-step dispatch loop (one
      sampling window each, so paired legs are temporally adjacent),
      alternating which leg goes first, gated on the MEDIAN of
      per-pair relative differences — differencing two multi-second
      wall-clock quantities to sub-percent precision is drift-limited
      on a shared CPU host, and the median of adjacent-pair ratios is
      the estimator that survives it (min-of-legs compares bests from
      minutes apart and measured the drift, not the sampler);
    * the periodic isolated-reduction re-time (every ``comm_refresh``
      samples — short legs rarely land on a refresh, and min-of-pairs
      would systematically select a refresh-free leg), added
      ANALYTICALLY from the sampler's own measured ``_comm_s`` amortized
      over its true cadence: ``comm_s / (comm_refresh x every)`` per
      step. The sum bounds the steady-state per-step overhead.

    Returns (every, overhead_pct, gate_ok). The sampler's one-time
    warmups (reduction-program compile, step cost analysis, peak
    calibration) run before any timed leg — setup cost, not per-step
    overhead."""
    import jax
    import numpy as np
    import optax

    from horovod_tpu.analysis import registry
    from horovod_tpu.training.trainer import StepPhaseSampler

    every = registry.get_int("HVT_METRICS_EVERY") or 32
    max_pct = float(os.environ.get("BENCH_SAMPLER_MAX_OVERHEAD_PCT", 2.0))
    trainer = hvt.Trainer(
        module,
        hvt.DistributedOptimizer(
            optax.adam(hvt.scale_lr(1e-3)),
            backward_passes_per_step=K,
            average_aggregated_gradients=True,
            compression=compression,
            compression_ici=compression_ici,
        ),
        loss="sparse_categorical_crossentropy",
        shard_update=True,
        bucket_bytes=bucket_bytes,
    )
    rng = np.random.RandomState(7)

    def step_batch():
        micro = [
            (lambda idx: (x[idx], y[idx]))(
                rng.randint(0, len(x), size=global_batch)
            )
            for _ in range(K)
        ]
        return tuple(np.stack([m[i] for m in micro]) for i in range(2))

    state = trainer.build(x[: trainer.dp_size])
    scale = np.float32(1.0)
    zero_acc = {m: np.float32(0) for m in trainer.metric_names}
    dev = trainer._shard_chunk(step_batch(), 1)
    step = trainer._train_step  # non-donating: dev is reused across steps
    state, _, _ = step(state, dev, scale, zero_acc)
    jax.block_until_ready(state)
    sampler = StepPhaseSampler(trainer, global_batch * K, every=every)
    sampler.capture_step_args(step, (state, dev, scale, zero_acc), 1)
    # Two forced samples: the first opens the window and pays every
    # one-time warmup, the second exercises the full sample path once.
    sampler.maybe_sample(state, every)
    sampler.maybe_sample(state, every)
    def leg(with_sampler: bool, n: int) -> float:
        nonlocal state
        jax.block_until_ready(state)
        t0 = time.perf_counter()
        for _ in range(n):
            state, _, _ = step(state, dev, scale, zero_acc)
            if with_sampler:
                sampler.maybe_sample(state, 1)
        jax.block_until_ready(state)
        return time.perf_counter() - t0

    # Legs are WHOLE sampling windows (each ON window carries exactly
    # one drain/publish edge), sized to >= ~4 s of wall clock: the
    # ON/OFF ratio is window-count invariant, and relative timing noise
    # on a shared CPU host only comes down with leg length.
    window_s = leg(False, every)  # settle + window-duration probe
    m = max(1, int(4.0 / max(window_s, 1e-9)) if window_s < 4.0 else 1)
    n = m * every
    leg(True, n)  # settle the sampler path at the final leg length
    pairs_min = max(3, int(os.environ.get("BENCH_SAMPLER_PAIRS", 5)))
    pairs_cap = max(pairs_min, int(os.environ.get(
        "BENCH_SAMPLER_MAX_PAIRS", 9
    )))
    # Paired-leg discipline (alternating order, median of per-pair
    # diffs, MAD-adaptive stop) — extracted to horovod_tpu.tune.probe
    # in PR 19 so the autotuner races candidate configs with the exact
    # machinery this gate was trusted with. A 2% gate needs
    # sub-percent resolution, hence the 0.75% MAD stop.
    from horovod_tpu.tune import probe as tune_probe

    res = tune_probe.paired_compare(
        lambda: leg(False, n), lambda: leg(True, n),
        pairs_min=pairs_min, pairs_cap=pairs_cap, mad_stop_pct=0.75,
    )
    drain_pct = res.median_pct
    # Amortized comm re-time (see docstring): one isolated reduction
    # every comm_refresh x every steps, against the OFF leg's step time.
    sec_per_step = min(res.a_times) / n
    comm_pct = (
        sampler._comm_s / (sampler.comm_refresh * every * sec_per_step)
        * 100.0
    )
    overhead_pct = drain_pct + comm_pct
    return every, round(overhead_pct, 3), overhead_pct <= max_pct


def bench_zero1() -> dict:
    """ZeRO-1 composition A/B (``shard_update`` on/off x K x overlap):
    the sharded weight update composed with accumulation (and, via
    HVT_COMPRESSION / HVT_COMPRESSION_ICI, the quantized wires) against
    the replicated update at the same K, AND against its own serialized
    (overlap-off) form.

    The wall-clock headline (ISSUE 12 — cash in the scatter): the
    overlapped composed leg must beat the serialized composed leg on
    ``step_ms.total`` at the same K — per-bucket backward-overlapped
    scatter issue + fused shard update made wall-clock-visible, not just
    an HLO assertion — and main() exits non-zero on a miss
    (``overlap_gate_ok``). ``overlap_fraction`` reports how much of the
    isolated comm time the overlap hid: (serialized total − overlapped
    total) / isolated comm, clamped to [0, 1]. ``step_ms.comm_buckets``
    attributes the isolated comm per BUCKET (leaf-aligned buckets are
    independently executable programs).

    The byte gate is unchanged from PR 10: structural bytes-on-wire per
    optimizer step of the isolated reduction, scattered strictly below
    replicated at the same K (byte-EQUAL for quantized DCN wires, whose
    dense layout is deliberate). The MFU denominator is guarded
    (`_flops_guard`): flops_per_opt_step = K x the K=1 peel-free
    compile's count, asserted against the K-program's own cost-model
    count so a silent peel-structure change can't drift the headline.
    Every row carries a non-null MFU (`_resolve_peak_flops`)."""
    os.environ.setdefault("HVT_FAST_RNG", "1")
    # A meaningful data-parallel degree on CPU drivers (inert on real
    # accelerators, where the platform is not cpu).
    os.environ.setdefault("HVT_NUM_CPU_DEVICES", "8")

    import flax.linen as nn
    import jax
    import numpy as np
    import optax

    import horovod_tpu as hvt
    from horovod_tpu import trace

    hvt.init()
    n_chips = jax.device_count()
    K = max(2, int(os.environ.get("BENCH_ACCUM_K", 4)))
    per_chip_batch = int(os.environ.get("BENCH_ZERO1_BATCH", 32))
    # hidden=2048 (~25 MB of f32 gradients): comm-heavy enough that the
    # per-bucket overlapped schedule is wall-clock-visible, the config
    # the ISSUE 12 headline runs at. BENCH_ZERO1_HIDDEN=1024 restores
    # the PR 10 shape for trend comparison.
    hidden = int(os.environ.get("BENCH_ZERO1_HIDDEN", 2048))
    # Bucket cap sized so the gradient tree cuts into SEVERAL leaf-
    # aligned buckets — one monolithic bucket has nothing to issue
    # bucket-by-bucket (the per-bucket schedule degenerates and the
    # peel only costs); ~4 MB gives the probe ~7 buckets.
    # BENCH_ZERO1_BUCKET_BYTES pins the probe shape; otherwise a
    # tuner-set HVT_BUCKET_BYTES (hvt-tune writes it into the resolved
    # env) reaches the bench the same way it reaches a real job.
    from horovod_tpu.analysis import registry as _registry

    bucket_bytes = int(
        os.environ.get("BENCH_ZERO1_BUCKET_BYTES", "")
        or _registry.get_int("HVT_BUCKET_BYTES")
        or (4 << 20)
    )
    n_steps = int(os.environ.get("BENCH_STEPS", 8))
    global_batch = per_chip_batch * n_chips
    peak_flops, peak_src = _resolve_peak_flops()
    compression = _wire_compression()
    compression_ici = _ici_compression()

    class Mlp(nn.Module):
        # Dims divisible by any plausible chip count, so every kernel
        # (and its Adam mirrors) shards under the zero1 rule.
        @nn.compact
        def __call__(self, x, *, train: bool = False):
            import jax.numpy as jnp

            x = x.astype(jnp.float32)
            x = nn.relu(nn.Dense(hidden)(x))
            x = nn.relu(nn.Dense(hidden)(x))
            return nn.Dense(16)(x)

    rng = np.random.RandomState(0)
    x = rng.rand(4096, 512).astype(np.float32)
    y = rng.randint(0, 16, 4096).astype(np.int32)

    def fleet_state_bytes(tree):
        total = 0
        for l in jax.tree.leaves(tree):
            if isinstance(l, jax.Array):
                total += sum(
                    int(np.prod(s.data.shape)) * l.dtype.itemsize
                    for s in l.addressable_shards
                )
        return total

    def measure(k: int, zero1: bool, overlap=None,
                buckets: bool = False, defer_timing: bool = False,
                cfg: dict | None = None) -> dict:
        # cfg overrides the ambient tunable values for ONE leg — how the
        # BENCH_TUNE_AB race builds its registry-default opponent.
        cfg = cfg or {}
        leg_bucket_bytes = int(cfg.get("bucket_bytes", bucket_bytes))
        leg_compression = cfg.get("compression", compression)
        leg_compression_ici = cfg.get("compression_ici", compression_ici)
        trainer = hvt.Trainer(
            Mlp(),
            hvt.DistributedOptimizer(
                optax.adam(hvt.scale_lr(1e-3)),
                backward_passes_per_step=k,
                average_aggregated_gradients=True,
                compression=leg_compression,
                compression_ici=leg_compression_ici,
            ),
            loss="sparse_categorical_crossentropy",
            shard_update=zero1,
            overlap_reduction=overlap,
            bucket_bytes=leg_bucket_bytes,
        )

        def draw():
            idx = rng.randint(0, len(x), size=global_batch)
            return x[idx], y[idx]

        def step_batch():
            if k == 1:
                return draw()
            micro = [draw() for _ in range(k)]
            return tuple(
                np.stack([m[i] for m in micro]) for i in range(2)
            )

        state = trainer.build(x[: trainer.dp_size])
        scale = np.float32(1.0)
        zero_acc = {m: np.float32(0) for m in trainer.metric_names}
        one = step_batch()
        dev_one = (
            trainer._shard(one) if k == 1 else trainer._shard_chunk(one, 1)
        )
        compiled_one = trainer._train_step.lower(
            state, dev_one, scale, zero_acc
        ).compile()
        cost_flops = trace.compiled_cost_flops(compiled_one)
        # Per-microbatch flops from the k=1 compile ONLY (bench_accum's
        # rule): the K-leg's program holds the accumulation scan (cost
        # model counts the body once) PLUS the overlap-peeled last
        # microbatch — taking its count x K would double-report. The
        # K-leg count still rides the `_flops_guard` drift check.
        flops_micro = cost_flops if k == 1 else None
        # Structural wire bytes of the isolated boundary reduction (the
        # explicit path exists whenever k > 1 or a wire is set; the k=1
        # uncompressed control reduces implicitly — same program shape
        # as the explicit flat psum, counted identically).
        _, _, red_text = _reduction_program(trainer, state.params)
        wire = _wire_bytes_per_step(red_text, trainer.dp_size)
        # Timed leg: one fused scan over n_steps optimizer steps,
        # best-of-3 (the overlap gate is a wall-clock strict compare —
        # take the floor of the noise, not its mean).
        steps = [step_batch() for _ in range(n_steps)]
        mega = tuple(np.stack([s[i] for s in steps]) for i in range(2))
        dev_mega = trainer._shard_chunk(mega, 2 if k > 1 else 1)
        compiled = trainer._train_chunk.lower(
            state, dev_mega, scale, zero_acc
        ).compile()
        w_state, _, w_acc = compiled(state, dev_mega, scale, zero_acc)
        float(jax.device_get(w_acc["loss"]))
        holder = {"state": w_state}

        def run():
            holder["state"], _, acc = compiled(
                holder["state"], dev_mega, scale, zero_acc
            )
            return acc["loss"]

        if defer_timing:
            # The overlap A/B times its two legs INTERLEAVED (paired
            # executions, best-of): a strict wall-clock compare between
            # runs minutes apart would measure machine drift, not the
            # schedule.
            sec_per_opt_step = None
        else:
            sec_per_opt_step = min(
                _timed(run) for _ in range(3)
            ) / n_steps
        comm_s = _timed_reduction(
            trainer, state.params, max(4, n_steps)
        )
        quantized_wire = leg_compression.lower() in ("int8", "fp8")
        comm_buckets = (
            _per_bucket_comm_ms(
                trainer, state.params, max(4, n_steps)
            )
            if buckets and zero1 and not quantized_wire else None
        )
        return {
            "examples_per_sec_per_chip": (
                k * global_batch / sec_per_opt_step / n_chips
                if sec_per_opt_step else None
            ),
            "sec_per_opt_step": sec_per_opt_step,
            "comm_s": comm_s,
            "comm_buckets": comm_buckets,
            "flops_micro": flops_micro,
            "cost_flops": cost_flops,
            "overlap": trainer._overlap,
            "run_once": run if defer_timing else None,
            "wire_bytes_per_opt_step": wire,
            "opt_state_fleet_bytes": fleet_state_bytes(
                holder["state"].opt_state
            ),
        }

    legs = {
        (1, False): measure(1, False),
        (1, True): measure(1, True),
        (K, False): measure(K, False),
        (K, True): measure(K, True, overlap=True, buckets=True,
                           defer_timing=True),
    }
    serialized = measure(K, True, overlap=False, defer_timing=True)
    lead = legs[(K, True)]
    # Paired interleaved timing of the overlap A/B: alternate the two
    # compiled programs and take each leg's best — drift (thermal, cache,
    # co-tenant load) hits both legs equally.
    pairs = max(3, int(os.environ.get("BENCH_OVERLAP_PAIRS", 5)))
    t_on, t_off = [], []
    for fn in (lead["run_once"], serialized["run_once"]):
        _timed(fn)  # settle both before the paired pass
    for _ in range(pairs):
        t_on.append(_timed(lead["run_once"]))
        t_off.append(_timed(serialized["run_once"]))
    for leg, times in ((lead, t_on), (serialized, t_off)):
        leg["sec_per_opt_step"] = min(times) / n_steps
        leg["examples_per_sec_per_chip"] = (
            K * global_batch / leg["sec_per_opt_step"] / n_chips
        )
    # BENCH_TUNE_AB=1 — the hvt-tune acceptance race (ISSUE 19): the
    # config in the CURRENT env (what the tuner selected) against the
    # registry-default config at the same K/model, decided by the
    # paired-leg discipline. main() exits non-zero when the tuned
    # config does not win.
    tuned_vs_default = None
    if os.environ.get("BENCH_TUNE_AB", "").lower() not in (
            "", "0", "false", "no"):
        from horovod_tpu.tune import probe as tune_probe
        from horovod_tpu.tune import space as tune_space

        tuned_cfg = {
            "HVT_BUCKET_BYTES": bucket_bytes,
            "HVT_BACKWARD_PASSES": K,
            "HVT_COMPRESSION": compression,
            "HVT_COMPRESSION_ICI": compression_ici,
            "HVT_OVERLAP_REDUCTION": _registry.get_flag(
                "HVT_OVERLAP_REDUCTION"),
        }
        default_cfg = dict(tune_space.default_config())
        default_cfg["HVT_BACKWARD_PASSES"] = K  # same model: K pinned
        # The tuned leg already exists: the lead (overlap-on) or the
        # serialized compile, whichever the env picked.
        tuned_leg = (lead if tuned_cfg["HVT_OVERLAP_REDUCTION"]
                     else serialized)
        default_leg = measure(
            K, True, overlap=default_cfg["HVT_OVERLAP_REDUCTION"],
            defer_timing=True,
            cfg={"bucket_bytes": default_cfg["HVT_BUCKET_BYTES"],
                 "compression": default_cfg["HVT_COMPRESSION"],
                 "compression_ici": default_cfg["HVT_COMPRESSION_ICI"]},
        )

        def _honest(leg):
            # Data-dependent fetch: the clock can't stop before the
            # device finished (see _timed's docstring).
            return lambda: float(jax.device_get(leg["run_once"]()))

        _honest(default_leg)()  # settle the fresh leg before pairing
        ab = tune_probe.paired_compare(
            _honest(tuned_leg), _honest(default_leg),
            pairs_min=max(3, int(os.environ.get("BENCH_TUNE_PAIRS", 5))),
            pairs_cap=max(3, int(os.environ.get(
                "BENCH_TUNE_MAX_PAIRS", 9))),
        )
        identical = tuned_cfg == default_cfg
        tuned_vs_default = {
            "tuned_config": tuned_cfg,
            "default_config": default_cfg,
            # median of per-pair (default - tuned) / tuned: positive
            # means the registry-default config is SLOWER.
            "median_pct": round(ab.median_pct, 3),
            "mad_pct": round(ab.mad_pct, 3),
            "pairs": ab.pairs,
            "converged": ab.converged,
            "default_step_ms_total": round(
                tune_probe.median(ab.b_times) / n_steps * 1e3, 3),
            # A race of a config against itself can't gate anything.
            "gate_ok": None if identical else ab.median_pct > 0.0,
        }
    for leg in (lead, serialized, legs[(1, False)], legs[(1, True)],
                legs[(K, False)]):
        leg["comm_s"] = min(leg["comm_s"], leg["sec_per_opt_step"])
        leg.pop("run_once", None)
    # Per-optimizer-step flops of the K leg = K x the k=1 zero1 compile's
    # per-microbatch count (the scan/peel-free program) — guarded below.
    flops_micro = legs[(1, True)]["flops_micro"]
    flops_per_opt_step = flops_micro * K if flops_micro else None
    flops_guard = _flops_guard(
        K, lead["overlap"], flops_micro, lead["cost_flops"]
    )
    mfu = (
        trace.mfu(
            flops_per_opt_step, lead["sec_per_opt_step"], n_chips,
            peak=peak_flops,
        )
        if flops_per_opt_step else None
    )
    total_ms = lead["sec_per_opt_step"] * 1e3
    comm_ms = lead["comm_s"] * 1e3
    step_ms = {
        "total": round(total_ms, 3),
        "compute": round(max(0.0, total_ms - comm_ms), 3),
        "comm": round(comm_ms, 3),
        "input": 0.0,
        # Per-bucket attribution of the isolated comm (scatter layout
        # only) — not a phase (non-numeric), outside the overrun guard.
        "comm_buckets": lead["comm_buckets"],
    }
    serialized_total_ms = round(serialized["sec_per_opt_step"] * 1e3, 3)
    # THE wall-clock gate (ISSUE 12): the overlapped SCATTER path beats
    # its own serialized form at the same K. overlap_fraction = how much
    # of the isolated comm the overlap hid. Quantized DCN wires keep the
    # dense bucket layout by design — there is no per-bucket scatter
    # schedule to gate there — so the compare is reported but
    # informational (overlap_gate_ok: null, no exit).
    hidden_s = serialized["sec_per_opt_step"] - lead["sec_per_opt_step"]
    overlap_fraction = (
        max(0.0, min(1.0, hidden_s / lead["comm_s"]))
        if lead["comm_s"] > 0 else 0.0
    )
    quantized = compression.lower() in ("int8", "fp8")
    overlap_gate_ok = (
        lead["sec_per_opt_step"] < serialized["sec_per_opt_step"]
        if not quantized else None
    )
    wire = {
        "replicated": {
            "k1": round(legs[(1, False)]["wire_bytes_per_opt_step"]),
            f"k{K}": round(legs[(K, False)]["wire_bytes_per_opt_step"]),
        },
        "zero1": {
            "k1": round(legs[(1, True)]["wire_bytes_per_opt_step"]),
            f"k{K}": round(legs[(K, True)]["wire_bytes_per_opt_step"]),
        },
    }
    # The PR 10 byte gate: at the same K, the scattered reduction moves
    # strictly fewer bytes than the replicated one. QUANTIZED DCN wires
    # are the deliberate exception — they keep the dense bucket layout
    # (bitwise-identical numerics to the replicated reduction, see
    # collectives._reduce_gradients_scatter) so the two programs are
    # byte-identical; the gate there is equality, never MORE.
    strictly_fewer = (
        wire["zero1"][f"k{K}"] < wire["replicated"][f"k{K}"]
        and wire["zero1"]["k1"] < wire["replicated"]["k1"]
    )
    not_more = (
        wire["zero1"][f"k{K}"] <= wire["replicated"][f"k{K}"]
        and wire["zero1"]["k1"] <= wire["replicated"]["k1"]
    )
    wire_ok = not_more if quantized else strictly_fewer
    sampler_every, sampler_overhead_pct, sampler_gate_ok = (
        _sampler_overhead(
            hvt, Mlp(), x, y, K, compression, compression_ici,
            bucket_bytes, global_batch,
        )
    )
    return {
        "mfu": round(mfu, 4) if mfu is not None else None,
        "metric": "zero1_train_examples_per_sec_per_chip",
        "value": round(lead["examples_per_sec_per_chip"], 1),
        "unit": "examples/sec/chip",
        "k": K,
        "step_ms": step_ms,
        "overlap_fraction": round(overlap_fraction, 4),
        "overlap_gate_ok": overlap_gate_ok,
        "serialized_step_ms_total": serialized_total_ms,
        "serialized_examples_per_sec_per_chip": round(
            serialized["examples_per_sec_per_chip"], 1
        ),
        "wire_bytes_per_opt_step": wire,
        "wire_strictly_fewer": strictly_fewer,
        "wire_gate_ok": wire_ok,
        "replicated_examples_per_sec_per_chip": round(
            legs[(K, False)]["examples_per_sec_per_chip"], 1
        ),
        "opt_state_fleet_bytes": {
            "replicated": legs[(K, False)]["opt_state_fleet_bytes"],
            "zero1": legs[(K, True)]["opt_state_fleet_bytes"],
        },
        "flops_per_opt_step": flops_per_opt_step,
        "flops_guard": flops_guard,
        "sampler_every": sampler_every,
        "sampler_overhead_pct": sampler_overhead_pct,
        "sampler_gate_ok": sampler_gate_ok,
        "compression": compression,
        "compression_ici": compression_ici,
        "peak_flops_per_chip": peak_flops,
        "peak_flops_source": peak_src,
        "per_chip_batch": per_chip_batch,
        "hidden": hidden,
        "bucket_bytes": bucket_bytes,
        "n_chips": n_chips,
        # Self-describing tuner input (ISSUE 19): the fully-resolved
        # tunable-knob values the HEADLINE leg (overlapped zero1) ran
        # under — hvt-tune reads this instead of re-inferring.
        "config": {
            "HVT_BUCKET_BYTES": bucket_bytes,
            "HVT_BACKWARD_PASSES": K,
            "HVT_COMPRESSION": compression,
            "HVT_COMPRESSION_ICI": compression_ici,
            "HVT_OVERLAP_REDUCTION": True,
        },
        "tuned_vs_default": tuned_vs_default,
    }


def bench_decode() -> dict:
    """Autoregressive generation: tokens/sec through ONE compiled program
    (prompt prefill + the whole `lax.scan` decode loop — a per-token host
    dispatch would be pure host round-trip at this op size).

    Decode is bandwidth-bound (every generated token streams all params +
    the KV cache through the MXU as matvecs), so the companion number is
    the model-bandwidth utilisation implied by params x tokens/sec."""
    os.environ.setdefault("HVT_FAST_RNG", "1")

    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvt
    from horovod_tpu.models.decoding import make_generate_fn

    hvt.init()
    n_chips = jax.device_count()
    batch = int(os.environ.get("BENCH_DECODE_BATCH", 8))
    prompt_len = int(os.environ.get("BENCH_PROMPT_LEN", 128))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", 512))
    model = _lm_from_env()
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(
        rng.randint(0, 8192, size=(batch, prompt_len)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]
    # BENCH_WEIGHTS=int8: weight-only quantized decode (models/quant.py) —
    # the bandwidth-bound step streams int8 weights instead of bf16.
    quantized = os.environ.get("BENCH_WEIGHTS", "") == "int8"
    if quantized:
        from horovod_tpu.models.quant import quantize_params

        params = quantize_params(params)
    # BENCH_KV_INT8=1: int8 K/V cache (per-(position, head) scales) — the
    # cache stream halves; stacks with BENCH_WEIGHTS/BENCH_KV_HEADS.
    from horovod_tpu import runtime as _rt

    kv_int8 = _rt.env_flag("BENCH_KV_INT8")
    fn = make_generate_fn(
        model, max_new_tokens=new_tokens, include_prompt=False,
        temperature=float(os.environ.get("BENCH_TEMPERATURE", 0.0)),
        quantized=quantized, quantized_cache=kv_int8,
    )
    key = jax.random.PRNGKey(7)

    def run():
        return fn(params, prompt, key).sum()

    float(jax.device_get(run()))  # compile + settle
    reps = max(1, int(os.environ.get("BENCH_DECODE_REPS", 4)))

    def run_reps():
        total = jnp.int32(0)
        for _ in range(reps):
            total = total + run()
        return total

    elapsed = _timed(run_reps) / reps
    n_params = sum(
        int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params)
    )
    if quantized:
        from horovod_tpu.models.quant import quantized_bytes

        model_bytes = quantized_bytes(params)
    else:
        model_bytes = 2 * n_params  # bf16 compute copies
    tok_per_sec = batch * new_tokens / elapsed
    return {
        "metric": "transformer_lm_decode_tokens_per_sec_per_chip",
        "value": round(tok_per_sec / n_chips, 1),
        "unit": "tokens/sec/chip",
        "batch": batch,
        "weights": "int8" if quantized else "bf16",
        "kv_cache": "int8" if kv_int8 else "bf16",
        "n_kv_heads": model.n_kv_heads or model.n_heads,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "ms_per_token": round(elapsed / new_tokens * 1e3, 4),
        "n_params": n_params,
        # Each decode step reads every weight once: the implied HBM traffic
        # floor (as-stored bytes — 2 B/param bf16, ~1 B/param for int8
        # weights — ignoring the KV cache) vs v5e's ~819 GB/s.
        "model_bandwidth_gbps": round(
            model_bytes * (tok_per_sec / batch) / 1e9, 1
        ),
        "n_chips": n_chips,
    }


def bench_int8_compute() -> dict:
    """int8 COMPUTE A/B (models/quant.int8_dot_general): prefill and
    large-batch decode, bf16 MXU vs int8 MXU (dynamic activation scales,
    per-channel weight scales, int32 accumulation).

    Prefill is the compute-bound phase (a full causal forward over the
    prompt); large-batch decode amortizes the weight stream until the
    matmuls, not the bytes, dominate — exactly where v5e's 2x int8 MXU
    rate can pay. Reported: prefill ms and decode tokens/sec for both
    paths at the d1024-class shape (BENCH_DMODEL et al. to vary).
    """
    os.environ.setdefault("HVT_FAST_RNG", "1")
    os.environ.setdefault("BENCH_DMODEL", "1024")
    os.environ.setdefault("BENCH_NLAYERS", "16")

    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvt
    from horovod_tpu.models.decoding import make_generate_fn

    hvt.init()
    n_chips = jax.device_count()
    batch = int(os.environ.get("BENCH_DECODE_BATCH", 32))
    prompt_len = int(os.environ.get("BENCH_PROMPT_LEN", 512))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", 128))
    model = _lm_from_env()
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(
        rng.randint(0, 8192, size=(batch, prompt_len)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]
    reps = max(1, int(os.environ.get("BENCH_DECODE_REPS", 4)))

    def measure_prefill(int8: bool) -> float:
        m = model.clone(int8_compute=int8) if int8 else model
        fwd = jax.jit(lambda p, x: m.apply({"params": p}, x).sum())
        float(jax.device_get(fwd(params, prompt)))

        def run_reps():
            total = jnp.float32(0)
            for _ in range(reps):
                total = total + fwd(params, prompt)
            return total

        return min(_timed(run_reps) for _ in range(3)) / reps

    def measure_decode(int8: bool) -> float:
        fn = make_generate_fn(
            model, max_new_tokens=new_tokens, include_prompt=False,
            int8_compute=int8,
        )
        key = jax.random.PRNGKey(7)

        def run():
            return fn(params, prompt, key).sum()

        float(jax.device_get(run()))

        def run_reps():
            total = jnp.int32(0)
            for _ in range(reps):
                total = total + run()
            return total

        return min(_timed(run_reps) for _ in range(3)) / reps

    pre_bf16 = measure_prefill(False)
    pre_int8 = measure_prefill(True)
    dec_bf16 = measure_decode(False)
    dec_int8 = measure_decode(True)
    toks = batch * new_tokens
    return {
        "metric": "int8_compute_prefill_speedup",
        "value": round(pre_bf16 / pre_int8, 2),
        "unit": "x vs bf16",
        "prefill_ms_bf16": round(pre_bf16 * 1e3, 2),
        "prefill_ms_int8": round(pre_int8 * 1e3, 2),
        "prefill_tokens_per_sec_int8": round(
            batch * prompt_len / pre_int8 / n_chips, 1
        ),
        "decode_tokens_per_sec_bf16": round(toks / dec_bf16 / n_chips, 1),
        "decode_tokens_per_sec_int8": round(toks / dec_int8 / n_chips, 1),
        "decode_speedup": round(dec_bf16 / dec_int8, 2),
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "d_model": model.d_model,
        "n_layers": model.n_layers,
        "n_chips": n_chips,
    }


def bench_spec() -> dict:
    """Speculative-decoding A/B: exact-greedy speedup on a model that has
    actually learned its task.

    An untrained model's greedy continuation is arbitrary, so NO draft can
    be accepted and a speculative bench on random weights would honestly
    measure nothing. Instead this trains a small LM on the copy task
    on-chip (seconds, device-cached), then decodes copy-structured prompts
    — where the prompt-lookup draft proposes the true continuation — with
    plain greedy vs speculative. Outputs are verified identical; the
    speedup is the accepted-tokens-per-target-pass ratio made wall-clock.
    """
    os.environ.setdefault("HVT_FAST_RNG", "1")

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvt
    from horovod_tpu.data import datasets
    from horovod_tpu.models.decoding import make_generate_fn
    from horovod_tpu.models.speculative import make_speculative_fn
    from horovod_tpu.models.transformer import TransformerLM

    hvt.init()
    vocab = 64
    seq = int(os.environ.get("BENCH_SPEC_SEQ", 512))
    batch = int(os.environ.get("BENCH_SPEC_BATCH", 1))
    gamma = int(os.environ.get("BENCH_SPEC_GAMMA", 8))
    model = TransformerLM(
        vocab_size=vocab,
        d_model=int(os.environ.get("BENCH_SPEC_DMODEL", 512)),
        n_heads=8,
        n_layers=int(os.environ.get("BENCH_SPEC_LAYERS", 8)),
        dropout=0.0,
        compute_dtype=jnp.bfloat16,
    )
    trainer = hvt.Trainer(
        model,
        hvt.DistributedOptimizer(optax.adam(1e-3)),
        loss="sparse_categorical_crossentropy",
    )
    x, y = datasets.copy_task(4096, seq, vocab_size=vocab, seed=3)
    trainer.fit(
        x=x, y=y, batch_size=64,
        epochs=int(os.environ.get("BENCH_SPEC_EPOCHS", 8)),
        steps_per_epoch=64, verbose=0, cache="device",
    )
    params = trainer.state.params

    xt, _ = datasets.copy_task(batch, seq, vocab_size=vocab, seed=777)
    prompt = jnp.asarray(xt[:, : seq // 2])  # continuation = the copy
    n_new = seq // 2 - 1

    plain = make_generate_fn(
        model, max_new_tokens=n_new, include_prompt=False
    )
    spec = make_speculative_fn(
        model, max_new_tokens=n_new, gamma=gamma, include_prompt=False,
        return_stats=True,
    )
    key = jax.random.PRNGKey(0)
    out_plain = jax.device_get(plain(params, prompt, key))
    out_spec, stats = spec(params, prompt)
    out_spec = jax.device_get(out_spec)
    assert np.array_equal(out_plain, out_spec), (
        "speculative output diverged from plain greedy — exactness bug"
    )
    rounds = int(jax.device_get(stats["rounds"]))
    accepted = int(jax.device_get(stats["tokens"]))

    reps = max(1, int(os.environ.get("BENCH_DECODE_REPS", 8)))

    def chain(fn):
        def run():
            total = jnp.int32(0)
            for _ in range(reps):
                total = total + fn()
            return total

        return run

    # One warmup execution may not settle the runtime (the decode benches
    # amortize that over 512-token generations; these are 127-token ones)
    # — warm each fn twice more and take the best of 3 chains. Honesty is
    # unchanged: every chain ends in a device fetch.
    plain_chain = chain(lambda: plain(params, prompt, key).sum())
    spec_chain = chain(lambda: spec(params, prompt)[0].sum())
    for c in (plain_chain, spec_chain):
        float(jax.device_get(c()))
    t_plain = min(_timed(plain_chain) for _ in range(3)) / reps
    t_spec = min(_timed(spec_chain) for _ in range(3)) / reps
    n_chips = jax.device_count()
    tok_plain = batch * n_new / t_plain / n_chips
    tok_spec = batch * n_new / t_spec / n_chips
    return {
        "metric": "speculative_decode_tokens_per_sec_per_chip",
        "value": round(tok_spec, 1),
        "unit": "tokens/sec/chip",
        "plain_tokens_per_sec": round(tok_plain, 1),
        "speedup": round(tok_spec / tok_plain, 2),
        "gamma": gamma,
        # stats['tokens'] is the batch-wide committed total; per-row mean
        # acceptance divides by the batch too (speculative.py docstring).
        "accept_per_round": round(accepted / max(rounds, 1) / batch, 2),
        "rounds": rounds,
        "batch": batch,
        "new_tokens": n_new,
        "exact": True,
        "n_chips": n_chips,
    }


def bench_serve() -> dict:
    """Serving-tier tail-latency A/B: continuous batching vs the legacy
    coalescing path, at EQUAL offered load.

    Spins up the real server (launch/serve.py) over a tiny streaming
    generation bundle and drives the SAME precomputed open-loop arrival
    schedule through both modes — open-loop (each request fires at its
    scheduled wall time regardless of completions), because a closed
    loop lets a slow server throttle its own offered load and hide its
    queueing tail. Per request, the client measures TTFT (first NDJSON
    line) and TPOT (per-token decode tail past the first chunk); the
    report is p50/p95/p99 of both, per mode.

    The offered rate is set to ~2x the legacy path's measured solo
    throughput: the legacy streaming path serializes every chunk
    dispatch of every concurrent request through one device lock (K
    single-row streams = K near-empty dispatches per chunk), so its
    queue grows and its tail TTFT blows up — while the continuous engine
    shares each dispatch across up to batch_size live rows and sustains
    the rate. The gate (`serve_gate_ok`, enforced by main): continuous
    p95 TTFT must not exceed the coalescing baseline's.
    """
    import tempfile
    import threading
    import urllib.request

    import jax
    import numpy as np

    from horovod_tpu import serving
    from horovod_tpu.launch.serve import make_server
    from horovod_tpu.models.transformer import TransformerLM

    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS", 48))
    batch, t0_len, n_new, chunk = 4, 8, 8, 2
    model = TransformerLM(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, dropout=0.0
    )
    params = model.init(
        jax.random.PRNGKey(0), np.zeros((batch, t0_len), np.int32)
    )["params"]
    tmp = tempfile.mkdtemp(prefix="hvt-bench-serve-")
    bundle = serving.export_generate(
        tmp, model, params, batch_size=batch, prompt_len=t0_len,
        max_new_tokens=n_new, streaming_chunk=chunk, timestamp="bench",
    )

    rs = np.random.RandomState(0)
    prompts = [
        [int(t) for t in rs.randint(1, 60, size=1 + i % 6)]
        for i in range(n_requests)
    ]

    def one_stream(url: str, prompt: list) -> tuple:
        req = urllib.request.Request(
            url,
            data=json.dumps({"prompt": [prompt], "stream": True}).encode(),
            headers={"Content-Type": "application/json"},
        )
        t_start = time.perf_counter()
        ttft, n_tok = None, 0
        with urllib.request.urlopen(req, timeout=300) as r:
            for line in r:
                now = time.perf_counter()
                obj = json.loads(line)
                if obj.get("error"):
                    raise RuntimeError(obj["error"])
                if ttft is None:
                    ttft = now - t_start
                if "tokens" in obj and not obj.get("done"):
                    n_tok += sum(len(x) for x in obj["tokens"])
        total = time.perf_counter() - t_start
        # Decode tail per token, past the first chunk (the TTFT edge).
        tpot = (total - ttft) / max(1, n_tok - chunk)
        return ttft, tpot

    def pct(values: list, q: float) -> float:
        return float(np.percentile(np.asarray(values), q))

    def measure(continuous: bool, gap: float) -> dict:
        srv = make_server(bundle, port=0, continuous=continuous)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        url = f"http://127.0.0.1:{srv.server_address[1]}/v1/generate"
        for p in prompts[:2]:
            one_stream(url, p)  # warm the compiled programs
        results: list = [None] * n_requests
        t_begin = time.perf_counter() + 0.05

        def client(i: int) -> None:
            # Open loop: fire at the SCHEDULED time, late or not.
            delay = t_begin + i * gap - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            results[i] = one_stream(url, prompts[i])

        threads = [
            threading.Thread(target=client, args=(i,))
            for i in range(n_requests)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        engine = getattr(srv.app, "engine", None)
        calls = (
            engine.stats()["device_calls_total"]
            if engine is not None else srv.app.stats["device_calls"]
        )
        if engine is not None:
            engine.stop()
        srv.shutdown()
        ttfts = [r[0] for r in results]
        tpots = [r[1] for r in results]
        return {
            "p50_ttft_ms": round(pct(ttfts, 50) * 1e3, 2),
            "p95_ttft_ms": round(pct(ttfts, 95) * 1e3, 2),
            "p99_ttft_ms": round(pct(ttfts, 99) * 1e3, 2),
            "p50_tpot_ms": round(pct(tpots, 50) * 1e3, 3),
            "p95_tpot_ms": round(pct(tpots, 95) * 1e3, 3),
            "device_calls": calls,
            "elapsed_s": round(elapsed, 2),
        }

    # Calibrate the offered rate off the LEGACY path's solo service time
    # so the schedule oversubscribes it ~2x on any host.
    srv = make_server(bundle, port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}/v1/generate"
    one_stream(url, prompts[0])  # compile
    t0 = time.perf_counter()
    for p in prompts[:4]:
        one_stream(url, p)
    solo = (time.perf_counter() - t0) / 4
    srv.shutdown()
    gap = solo / 2.0

    coalesce = measure(continuous=False, gap=gap)
    continuous = measure(continuous=True, gap=gap)
    gate_ok = continuous["p95_ttft_ms"] <= coalesce["p95_ttft_ms"]
    return {
        "metric": "serve_p95_ttft_ms",
        "value": continuous["p95_ttft_ms"],
        "unit": "ms",
        "continuous": continuous,
        "coalescing": coalesce,
        "offered_rps": round(1.0 / gap, 1),
        "requests": n_requests,
        "batch": batch,
        "new_tokens": n_new,
        "serve_gate_ok": gate_ok,
    }


def bench_input() -> dict:
    """Host input-pipeline A/B: native C++ batch assembly vs pure Python.

    Times `training_pipeline` (shuffle + gather + stage) alone — the part the
    native engine (native/hvt_data.cc) owns; no device work."""
    import numpy as np

    from horovod_tpu.data import datasets, native_loader
    from horovod_tpu.data.loader import training_pipeline

    (x_train, y_train), _ = datasets.mnist()
    x = (x_train.astype(np.float32) / 255.0)[..., None]
    arrays = (x, y_train.astype(np.int64))
    steps = 400

    # Decide native availability BEFORE touching HVT_NO_NATIVE: probing under
    # the env var would permanently latch the loader's load-failed flag and
    # the native leg could never run.
    native = native_loader.available()

    # The native engine's value is OVERLAP: its producer thread assembles
    # batch k+1 while the consumer (a training loop dispatching device work)
    # is busy with batch k. Measure both regimes: a tight next() loop (raw
    # assembly speed — numpy's fancy-index gather is already memcpy-bound,
    # so parity is expected) and a consumer that does `busy_s` of work per
    # batch (the realistic loop, where background assembly hides under it).
    busy_s = float(os.environ.get("BENCH_INPUT_BUSY_MS", 1.0)) / 1e3

    def run(no_native: bool, busy: float) -> float:
        if no_native:
            os.environ["HVT_NO_NATIVE"] = "1"
        else:
            os.environ.pop("HVT_NO_NATIVE", None)
        it, close = training_pipeline(arrays, BATCH, seed=0)
        try:
            for _ in range(50):  # warm the producer
                next(it)
            t0 = time.perf_counter()
            for _ in range(steps):
                next(it)
                if busy:
                    end = time.perf_counter() + busy
                    while time.perf_counter() < end:  # simulated step work
                        pass
            return steps * BATCH / (time.perf_counter() - t0)
        finally:
            close()

    python_raw = run(no_native=True, busy=0.0)
    python_busy = run(no_native=True, busy=busy_s)
    # Without the native engine (no toolchain to build it), the "native" legs
    # would silently rerun Python and publish "no speedup" — label it.
    native_raw = run(no_native=False, busy=0.0) if native else python_raw
    native_busy = run(no_native=False, busy=busy_s) if native else python_busy
    return {
        "metric": "input_pipeline_images_per_sec_overlapped",
        "value": round(native_busy, 1),
        "unit": "images/sec",
        "native": native,
        "busy_ms_per_batch": busy_s * 1e3,
        "python_overlapped_images_per_sec": round(python_busy, 1),
        "raw_images_per_sec": {
            "native": round(native_raw, 1),
            "python": round(python_raw, 1),
        },
        "vs_baseline": round(native_busy / python_busy, 2) if native else None,
    }


def _phase_overruns(step_ms: dict) -> list:
    """Phases reported larger than `total` (impossible under the one
    consistent accounting bench_train uses — any hit means the measurement
    or clamping regressed, the r04 `compute: 0.281 > total: 0.256` bug).
    Also flags the phases summing past total. Small float-printing slack
    only (phases are rounded to µs independently of total)."""
    total = step_ms.get("total")
    if total is None:
        return []
    slack = 2e-3  # rounded-to-3-decimals ms values
    phases = {
        k: v for k, v in step_ms.items()
        if k != "total" and isinstance(v, (int, float))
    }
    bad = [k for k, v in phases.items() if v > total + slack]
    if sum(phases.values()) > total + slack * max(1, len(phases)):
        bad.append("sum(phases)")
    return bad


def main() -> None:
    from horovod_tpu import runtime

    runtime.use_compilation_cache()
    # An unparseable HVT_PEAK_FLOPS override is a usage error — exit 2
    # before any leg runs (the hvt-lint/hvt-audit exit-code contract).
    try:
        from horovod_tpu.analysis import registry as _registry

        _registry.get_float("HVT_PEAK_FLOPS")
    except ValueError as e:
        import sys

        print(f"bench: unparseable HVT_PEAK_FLOPS override: {e}",
              file=sys.stderr)
        sys.exit(2)
    which = os.environ.get("BENCH_MODEL", "mnist")
    if which == "input":
        result = bench_input()
    elif which == "serve":
        result = bench_serve()
    elif which == "int8":
        result = bench_int8_compute()
    elif which == "accum":
        result = bench_accum()
    elif which == "zero1":
        result = bench_zero1()
    elif which == "decode":
        result = bench_decode()
    elif which == "spec":
        result = bench_spec()
    else:
        result = bench_train(which)
        vs = None
        if which == "mnist":
            baseline_path = os.path.join(
                REPO, "benchmarks", "baseline_measured.json"
            )
            if os.path.exists(baseline_path):
                with open(baseline_path) as f:
                    vs = round(result["value"] / json.load(f)["images_per_sec"], 2)
        result["vs_baseline"] = vs
    if "config" not in result:
        # Every row is a self-describing tuner input: stamp the
        # fully-resolved tunable-knob values it ran under. Modes that
        # pick their own values (zero1) stamp explicitly above; the
        # rest resolve from the registry, overridden by whatever the
        # row itself reports it used.
        from horovod_tpu.tune import space as _tune_space

        cfg = _tune_space.resolved_config()
        for knob_name, row_key in (
            ("HVT_BUCKET_BYTES", "bucket_bytes"),
            ("HVT_BACKWARD_PASSES", "k"),
            ("HVT_COMPRESSION", "compression"),
            ("HVT_COMPRESSION_ICI", "compression_ici"),
        ):
            if result.get(row_key) is not None:
                cfg[knob_name] = result[row_key]
        result["config"] = cfg
    print(json.dumps(result))
    overruns = _phase_overruns(result.get("step_ms", {}))
    if overruns:
        import sys

        print(
            f"bench: phase(s) {overruns} exceed step_ms.total — "
            "inconsistent phase accounting",
            file=sys.stderr,
        )
        sys.exit(1)
    if result.get("wire_gate_ok") is False:
        import sys

        print(
            "bench: the ZeRO-1 scattered boundary reduction regressed — "
            "it must move strictly fewer bytes than the replicated one "
            "at the same K (byte-EQUAL for quantized wires, whose dense "
            "layout is deliberate) "
            f"({result.get('wire_bytes_per_opt_step')})",
            file=sys.stderr,
        )
        sys.exit(1)
    if result.get("serve_gate_ok") is False:
        import sys

        print(
            "bench: continuous batching LOST to the coalescing baseline "
            "on tail TTFT at equal offered load "
            f"(continuous p95 {result.get('continuous', {}).get('p95_ttft_ms')} ms "
            f"vs coalescing p95 {result.get('coalescing', {}).get('p95_ttft_ms')} ms) "
            "— per-step admission is not cashing in",
            file=sys.stderr,
        )
        sys.exit(1)
    if result.get("overlap_gate_ok") is False:
        import sys

        print(
            "bench: the overlapped zero1 step did NOT beat its own "
            "serialized form on wall-clock step_ms.total at the same K "
            f"(overlapped {result.get('step_ms', {}).get('total')} ms vs "
            f"serialized {result.get('serialized_step_ms_total')} ms) — "
            "the per-bucket scatter overlap is not cashing in",
            file=sys.stderr,
        )
        sys.exit(1)
    if (result.get("tuned_vs_default") or {}).get("gate_ok") is False:
        import sys

        tvd = result["tuned_vs_default"]
        print(
            "bench: the hvt-tune-selected config did NOT beat the "
            "registry-default config on step_ms.total at the same K "
            f"(tuned {result.get('step_ms', {}).get('total')} ms vs "
            f"default {tvd.get('default_step_ms_total')} ms, paired "
            f"median {tvd.get('median_pct')}% over {tvd.get('pairs')} "
            "pairs) — the tuner crowned a loser",
            file=sys.stderr,
        )
        sys.exit(1)
    if result.get("flops_guard", {}).get("ok") is False:
        import sys

        print(
            "bench: flops_per_opt_step guard failed — the K>1 program's "
            "cost-model FLOP count left the band implied by the peel "
            f"structure ({result.get('flops_guard')}); the MFU "
            "denominator (K x the K=1 compile) no longer matches the "
            "compiled step",
            file=sys.stderr,
        )
        sys.exit(1)
    if result.get("sampler_gate_ok") is False:
        import sys

        print(
            "bench: live StepPhaseSampler overhead "
            f"{result.get('sampler_overhead_pct')}% exceeds the "
            f"{os.environ.get('BENCH_SAMPLER_MAX_OVERHEAD_PCT', 2.0)}% "
            "budget on step_ms.total at "
            f"every={result.get('sampler_every')} — the trainer-side "
            "metrics exporter is too expensive to leave on",
            file=sys.stderr,
        )
        sys.exit(1)


if __name__ == "__main__":
    main()
