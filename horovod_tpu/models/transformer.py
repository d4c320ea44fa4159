"""Decoder-only transformer LM — the long-context / model-parallel flagship.

The reference never goes past a 2-conv MNIST CNN (SURVEY.md §5.7: no
sequence axis anywhere), but this framework treats long-context and
multi-axis parallelism as first-class. This model composes every mesh axis:

* ``data``/``fsdp`` — batch sharding (+ optional parameter sharding);
* ``seq``  — sequence/context parallelism: activations sharded along the
  token axis; attention runs as ring or Ulysses collectives (ops/attention)
  inside a *partially-manual* `jax.shard_map` over only the ``seq`` axis,
  leaving batch/TP sharding to the compiler;
* ``model`` — tensor parallelism: QKV/MLP-up kernels column-sharded,
  proj/MLP-down row-sharded (Megatron layout) via sharding constraints the
  compiler turns into a single allreduce per residual join.

Architecture: pre-LN blocks, RoPE positions (sequence-length extensible —
what a long-context model wants), GELU MLP at 4×, tied-free LM head, logits
in float32 by default (``logits_dtype=bfloat16`` halves long-sequence HBM;
the named Trainer losses upcast to f32 on the fly — a custom callable loss
must do its own upcasting).

`param_specs(params, mesh)` gives the explicit PartitionSpec tree for the
TP/FSDP layout — path-based rules, no boxed-metadata machinery, so any
optimizer/checkpoint code sees plain arrays.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import flax.linen as nn
import jax
import numpy as np

import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.ops import attention as attention_ops, fused_ce
from horovod_tpu.parallel.mesh import (
    DATA_AXIS,
    EXPERT_AXIS,
    FSDP_AXIS,
    MODEL_AXIS,
    SEQ_AXIS,
)

BATCH_AXES = (DATA_AXIS, FSDP_AXIS)
# The axes a [B, T] token's row is split over: batch (data, fsdp) and seq.
ROW_AXES = BATCH_AXES + (SEQ_AXIS,)


def _rope(x, positions, *, base: float = 10000.0):
    """Rotary position embedding on [B, T, H, D] with global positions."""
    d = x.shape[-1]
    half = d // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[:, :, None, None].astype(jnp.float32) * freqs  # [B,T,1,half]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return rotated.astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN's blend of a rotary's frequencies (arXiv:2309.00071, as
    transformers' ``_compute_yarn_parameters`` with truncation): pairs that
    turn fewer than ``beta_slow`` times over ``original_max_positions`` are
    slowed by ``factor``, those that turn more than ``beta_fast`` times are
    kept, a linear ramp between; cos and sin are scaled by
    ``attention_factor``."""

    factor: float
    original_max_positions: int
    beta_fast: float
    beta_slow: float
    attention_factor: float


@dataclasses.dataclass(frozen=True)
class Rotary:
    """Rotate-half rotary over the first ``dims`` channels of a head at
    ``base``, YaRN-scaled where ``yarn`` is given; the other channels pass
    through untouched."""

    dims: int
    base: float
    yarn: YaRN | None = None


def rotary_inv_freq(rotary: Rotary) -> np.ndarray:
    """float32 ``[dims // 2]``: the pairs' angular frequencies, on the host
    (a constant of the traced program). Without YaRN ``base^(-2j / dims)``;
    with it ``w_j / factor (1 - e_j) + w_j e_j``, ``e_j = 1 - clamp((j -
    lo) / (hi - lo), 0, 1)`` between the correction dimensions of
    ``beta_fast`` (floored) and ``beta_slow`` (ceiled)."""
    half = rotary.dims // 2
    kept = rotary.base ** (-2.0 * np.arange(half) / rotary.dims)
    yarn = rotary.yarn
    if yarn is None:
        return kept.astype(np.float32)

    def correction_dim(rotations):
        return (rotary.dims * math.log(
            yarn.original_max_positions / (rotations * 2 * math.pi))
            / (2 * math.log(rotary.base)))

    lo = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    hi = min(math.ceil(correction_dim(yarn.beta_slow)), rotary.dims - 1)
    ramp = np.clip((np.arange(half) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    extrapolated = 1.0 - ramp
    return (kept / yarn.factor * (1.0 - extrapolated)
            + kept * extrapolated).astype(np.float32)


def partial_rope(x, positions, rotary: Rotary):
    """``[B, T, H, D]`` with the rotary of ``rotary`` at ``positions``
    ``[T]``: channels ``j`` and ``j + dims / 2`` of the first ``dims`` turn
    together, angles and products in float32, the result in ``x``'s
    dtype."""
    half = rotary.dims // 2
    angles = (positions.astype(jnp.float32)[:, None]
              * rotary_inv_freq(rotary))[:, None, :]  # [T, 1, half]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if rotary.yarn is not None:
        cos = cos * rotary.yarn.attention_factor
        sin = sin * rotary.yarn.attention_factor
    x1, x2 = x[..., :half], x[..., half:rotary.dims]
    turned = [(x1 * cos - x2 * sin).astype(x.dtype),
              (x1 * sin + x2 * cos).astype(x.dtype)]
    if rotary.dims < x.shape[-1]:
        turned.append(x[..., rotary.dims:])
    return jnp.concatenate(turned, axis=-1)


def packed_positions(segment_ids):
    """[B, T] within-document positions for contiguous-run packing: token i's
    position is its offset from the start of its run, so RoPE treats each
    packed document as starting at 0 (matching how the documents would embed
    unpacked)."""
    b, t = segment_ids.shape
    ar = jnp.arange(t, dtype=jnp.int32)
    changed = jnp.concatenate(
        [
            jnp.ones((b, 1), bool),
            segment_ids[:, 1:] != segment_ids[:, :-1],
        ],
        axis=1,
    )
    starts = jax.lax.cummax(jnp.where(changed, ar[None, :], 0), axis=1)
    return ar[None, :] - starts


@dataclasses.dataclass(frozen=True)
class ShardingConfig:
    """How the model meets the mesh.

    ``attn``: ``'ring'`` (sequence-parallel ring attention, flash-kernel
    block compute), ``'ring_dense'`` (ring with dense per-hop scores — the
    numerics ground truth), ``'ulysses'`` (all-to-all head swap), or
    ``'dense'`` (materialized-score attention, the numerics reference —
    NOT flash; on a mesh without a live ``seq`` axis the 'ring'/'ulysses'
    settings take the local flash-kernel path instead)."""

    mesh: Mesh | None = None
    attn: str = "ring"

    @property
    def seq_parallel(self) -> bool:
        return self.mesh is not None and self.mesh.shape.get(SEQ_AXIS, 1) > 1

    def constrain(self, x, spec: P):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec)
        )


class Block(nn.Module):
    d_model: int
    n_heads: int
    dropout: float
    compute_dtype: jnp.dtype
    sharding: ShardingConfig
    # Grouped-query attention (GQA, arXiv:2305.13245): n_kv_heads < n_heads
    # shares each K/V head across n_heads/n_kv_heads query heads. Training
    # repeats K/V up to H after projection (the FLOPs are identical; the
    # win is the decode cache at [B, L, H_kv, D] — 1/group of the MHA
    # bytes streamed per generated token, which is what bandwidth-bound
    # decode pays for). None = MHA (the fused qkv projection, param-layout
    # compatible with existing checkpoints).
    n_kv_heads: int | None = None
    # Sliding-window attention (Mistral-style local attention,
    # arXiv:2310.06825): each query sees only its `window` most recent
    # keys. The flash kernel block-skips tiles outside the band (FLOPs
    # scale with T·window, not T²/2), the ring variant skips whole
    # out-of-band hops, and the decode path masks the stale cache prefix.
    # Window counts ROW positions (token distance within a packed row),
    # composing with segment masking by intersection. None = full causal.
    window: int | None = None
    # MoE (expert-parallel) MLP instead of the dense one: the EP capability,
    # routed over the mesh's `expert` axis (models/moe.py).
    use_moe: bool = False
    n_experts: int = 8
    moe_k: int = 2
    capacity_factor: float = 1.25
    moe_aux_coef: float = 1e-2
    # 'top_k' or 'expert_choice' (drop-free, training-only — see
    # models/moe.py module docstring).
    moe_router: str = "top_k"
    # Autoregressive inference (models/decoding.py): K/V for past tokens live
    # in a ``cache`` variable collection sized [B, max_decode_len, H_kv, D]
    # (H_kv == n_kv_heads, == H for MHA).
    decode: bool = False
    max_decode_len: int = 0
    # Streaming decode (requires ``window``): the cache is a [B, window,
    # H_kv, D] RING BUFFER (slot = position mod window) instead of the full
    # [B, max_decode_len, ...] history — O(window) memory and O(window)
    # cache reads per generated token however long the generation runs.
    # Exact: a windowed query never needs anything the ring has evicted.
    sliding_cache: bool = False
    # int8 MXU compute for Dense matmuls (inference-only; see
    # models/quant.int8_dot_general — dynamic activation scales,
    # per-channel weight scales, int32 accumulation).
    int8_compute: bool = False
    # int8 KV cache (decode): K/V stored as int8 with per-(position, head)
    # f32 scales — the cache stream halves (it was ~a third of decode HBM
    # traffic at MHA shapes) and so does cache HBM, doubling the context
    # envelope per chip. Scales factor OUT of the head-dim contraction, so
    # the decode einsums read int8 directly and apply scales to the
    # [.., L]-shaped scores/probs — no dequantized [B, L, H, D] copy
    # exists even transiently. Approximate (two 127-level roundings);
    # quality-gated like the weight paths (models/quant.py).
    quantized_cache: bool = False
    # Attention sinks (StreamingLLM, arXiv:2309.17453 / Longformer-style
    # global+local): the first `attention_sinks` positions stay visible —
    # and, with sliding_cache, pinned in the cache — in addition to the
    # window band. A first-class mask, consistent across training, eval,
    # prefill, chunk extension and decode (sinks+band everywhere), so a
    # model can be TRAINED global+local and streamed exactly; cloning a
    # densely-trained model with (window, attention_sinks, sliding_cache)
    # for generation is the approximate StreamingLLM recipe. Sink-masked
    # forwards run the flash kernel (a pinned sink tile per q block —
    # O(T·(window+sinks)); dense, with a warning, when the tiling doesn't
    # hold)
    # and compose with sequence parallelism: the flash ring adds a dense
    # sink contribution on the hop holding global block 0, Ulysses passes
    # them to its local kernel (the dense-block ring refuses).
    attention_sinks: int = 0

    @nn.compact
    def __call__(self, x, positions, train: bool = False, segment_ids=None,
                 decode_index=None):
        cfg = self.sharding
        head_dim = self.d_model // self.n_heads
        dense_kw = {}
        if self.int8_compute:
            from horovod_tpu.models.quant import int8_dot_general

            dense_kw["dot_general"] = int8_dot_general
        dense = functools.partial(
            nn.DenseGeneral, dtype=self.compute_dtype, use_bias=False,
            **dense_kw,
        )

        # --- attention -----------------------------------------------------
        h = nn.LayerNorm(dtype=self.compute_dtype, use_bias=False)(x)
        h_kv = self.n_kv_heads or self.n_heads
        if self.n_heads % h_kv != 0:
            raise ValueError(
                f"n_heads ({self.n_heads}) must be a multiple of "
                f"n_kv_heads ({h_kv})"
            )
        rep = self.n_heads // h_kv
        # Explicit names: param_specs keys its TP rules on them, so layer
        # additions/reorderings can't silently re-shard the wrong kernel.
        if rep == 1:
            qkv_shape = (self.n_heads, 3 * head_dim)
            qkv = dense(features=qkv_shape, name="qkv")(h)  # [B,T,H,3D] — column-parallel
            q, k, v = jnp.split(qkv, 3, axis=-1)
        else:
            q = dense(features=(self.n_heads, head_dim), name="q_proj")(h)
            kv = dense(features=(h_kv, 2 * head_dim), name="kv_proj")(h)
            k, v = jnp.split(kv, 2, axis=-1)  # [B, T, H_kv, D]
        q, k = _rope(q, positions), _rope(k, positions)

        if cfg.mesh is not None:
            model_par = cfg.mesh.shape.get(MODEL_AXIS, 1)
            if self.n_heads % model_par != 0:
                raise ValueError(
                    f"n_heads ({self.n_heads}) must divide over the model "
                    f"axis ({model_par}) for sharded attention"
                )
            if h_kv % model_par != 0:
                raise ValueError(
                    f"n_kv_heads ({h_kv}) must divide over the model axis "
                    f"({model_par}) — the kv projection and decode cache "
                    f"shard their head dim"
                )

        if self.decode:
            out = self._decode_attention(q, k, v, decode_index)
            out = dense(features=self.d_model, axis=(-2, -1), name="attn_out")(out)
            x = x + out
            h = nn.LayerNorm(dtype=self.compute_dtype, use_bias=False)(x)
            h = self._mlp(h, dense, train=False)
            return x + h

        if rep > 1:
            # Training/prefill attention runs at full H: repeating K/V heads
            # keeps q-head i paired with kv-head i // rep under any TP
            # sharding (contiguous H/tp slices of the repeated layout align
            # with the q slices). The repeat is XLA-fused into the attention
            # consumers; the cache (decode path above) never stores it.
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)

        if self.attention_sinks:
            if self.window is None:
                raise ValueError(
                    "attention_sinks is the global+local mask's global "
                    "part — it needs window set (full causal attention "
                    "already sees every sink)"
                )
            if cfg.seq_parallel and cfg.attn == "ring_dense":
                raise ValueError(
                    "sinks need attn='ring' or 'ulysses' — the dense-block "
                    "ring is sink-unaware"
                )
        if cfg.seq_parallel:
            impls = {
                "ring": attention_ops.ring_flash_attention,
                "ring_dense": attention_ops.ring_attention,
                "ulysses": attention_ops.ulysses_attention,
            }
            if cfg.attn not in impls:
                raise ValueError(
                    f"sequence-parallel attention needs attn in {sorted(impls)}, "
                    f"got {cfg.attn!r}"
                )
            if segment_ids is not None and cfg.attn == "ring_dense":
                raise ValueError(
                    "packed sequences (segment_ids) need attn='ring' or "
                    "'ulysses' — the dense-block ring is segment-unaware"
                )
            # Fully-manual region: batch stays split over data/fsdp, heads
            # over model (attention never mixes batch or heads, so manual
            # sharding there is free); the seq axis is the collective one.
            # The segment ids (when packing) shard with the tokens; ring
            # rotates the kv ids, Ulysses all-gathers them (ops/attention).
            spec = P(BATCH_AXES, SEQ_AXIS, MODEL_AXIS, None)
            impl_kw = dict(
                axis_name=SEQ_AXIS, causal=True, window=self.window
            )
            if self.attention_sinks:
                impl_kw["sinks"] = self.attention_sinks
            impl = functools.partial(impls[cfg.attn], **impl_kw)
            if segment_ids is None:
                fn, args, in_specs = impl, (q, k, v), (spec, spec, spec)
            else:
                fn = lambda q, k, v, ids: impl(q, k, v, segment_ids=ids)  # noqa: E731
                args = (q, k, v, segment_ids)
                in_specs = (spec, spec, spec, P(BATCH_AXES, SEQ_AXIS))
            out = jax.shard_map(
                fn, mesh=cfg.mesh, in_specs=in_specs, out_specs=spec,
                check_vma=False,
            )(*args)
        elif cfg.attn == "dense":
            out = attention_ops.dense_attention(
                q, k, v, causal=True, window=self.window,
                sinks=self.attention_sinks,
                q_segment_ids=segment_ids, kv_segment_ids=segment_ids,
            )
        else:
            # Local path: the pallas flash kernel (O(T) memory; warns and
            # runs dense when its tiling doesn't hold, interprets off-TPU).
            # GSPMD cannot auto-partition a Mosaic custom call, so on a
            # multi-device mesh it runs in a fully-manual shard_map (batch
            # over data/fsdp, heads over model — attention mixes neither).
            # That takes the mesh: a model built WITHOUT one compiles on a
            # single device only, and the Trainer refuses it on a larger
            # mesh wherever the kernel is compiled
            # (trainer._require_kernel_mesh).
            from horovod_tpu.ops.flash_attention import flash_attention

            # sinks ride the kernel's pinned sink tile (a no-op at 0) —
            # one code path for plain, windowed and global+local local
            # attention.
            def local(q, k, v, ids=None):
                return flash_attention(
                    q, k, v, causal=True, window=self.window,
                    sinks=self.attention_sinks,
                    q_segment_ids=ids, kv_segment_ids=ids,
                )

            args = (q, k, v) if segment_ids is None else (q, k, v, segment_ids)
            if cfg.mesh is not None and cfg.mesh.size > 1:
                spec = P(BATCH_AXES, None, MODEL_AXIS, None)
                in_specs = (spec, spec, spec)
                if segment_ids is not None:
                    in_specs += (P(BATCH_AXES, None),)
                local = jax.shard_map(
                    local, mesh=cfg.mesh, in_specs=in_specs, out_specs=spec,
                    check_vma=False,
                )
            out = local(*args)

        out = dense(features=self.d_model, axis=(-2, -1), name="attn_out")(out)  # row-parallel
        out = nn.Dropout(self.dropout, deterministic=not train)(out)
        x = x + out
        x = cfg.constrain(x, P(BATCH_AXES, SEQ_AXIS, None))

        # --- MLP (dense, or expert-parallel MoE) ---------------------------
        h = nn.LayerNorm(dtype=self.compute_dtype, use_bias=False)(x)
        h = self._mlp(h, dense, train=train)
        h = nn.Dropout(self.dropout, deterministic=not train)(h)
        x = x + h
        return cfg.constrain(x, P(BATCH_AXES, SEQ_AXIS, None))

    def _mlp(self, h, dense, *, train: bool):
        if self.use_moe:
            from horovod_tpu.models.moe import MoEMlp

            if self.moe_router == "expert_choice" and self.decode:
                raise ValueError(
                    "expert_choice routing is training-only: expert "
                    "selection ranks tokens across the whole group, which "
                    "a per-token decode step cannot reproduce (the known "
                    "EC train/inference asymmetry) — decode with "
                    "moe_router='top_k'"
                )
            return MoEMlp(
                self.d_model,
                n_experts=self.n_experts,
                k=self.moe_k,
                capacity_factor=self.capacity_factor,
                aux_loss_coef=self.moe_aux_coef,
                router=self.moe_router,
                compute_dtype=self.compute_dtype,
                sharding=self.sharding,
                name="moe",
            )(h, train=train)
        h = dense(features=4 * self.d_model, name="mlp_up")(h)  # column-parallel
        h = nn.gelu(h)
        return dense(features=self.d_model, name="mlp_down")(h)  # row-parallel

    def _decode_attention(self, q, k, v, decode_index):
        """KV-cache attention for autoregressive inference.

        The cache holds every past token's K/V ([B, max_decode_len, H_kv,
        D] — n_kv_heads, not H: under GQA it stores only the projected kv
        heads — sharded over ``model`` on a TP mesh, the same Megatron
        split as training, so decode reuses the training shardings
        untouched).
        Two static shapes arrive here:

        * **prefill** (T > 1 on a fresh cache): the prompt's K/V are
          written at [0:T] and attention runs causally over the prompt alone
          — exactly the training forward, so the flash kernel applies and no
          [T, max_decode_len] scores are built;
        * **decode step** (T == 1): the new token's K/V land at
          ``decode_index`` and its query attends densely over the valid
          cache prefix — a matvec per head, bandwidth-bound by design;
        * **chunk extension** (T > 1 on a warm cache): T fresh tokens land
          at ``decode_index`` and attend over the prefix plus themselves
          (causal within the chunk) — chunked long-prompt prefill with
          [T, L]-bounded scores, and the verify pass of speculative
          decoding (models/speculative.py).
        """
        cfg = self.sharding
        b, t, h, d = q.shape
        h_kv = k.shape[2]  # < h under GQA: the cache stays at H_kv heads
        rep = h // h_kv
        if self.max_decode_len < t:
            raise ValueError(
                f"max_decode_len ({self.max_decode_len}) < input length ({t})"
            )
        if self.sliding_cache and self.window is None:
            raise ValueError(
                "sliding_cache is the ring buffer for sliding-window "
                "attention — set window too"
            )
        if self.attention_sinks < 0:
            raise ValueError("attention_sinks must be >= 0")
        if self.attention_sinks and self.window is None:
            raise ValueError(
                "attention_sinks is the global+local mask's global part — "
                "it needs window set (full causal attention already sees "
                "every sink)"
            )
        sinks = self.attention_sinks
        cache_spec = P(BATCH_AXES, None, MODEL_AXIS, None)
        first_call = not self.has_variable("cache", "k")
        cache_len = (
            sinks + min(self.window, self.max_decode_len)
            if self.sliding_cache else self.max_decode_len
        )
        qc = self.quantized_cache
        if qc and self.sliding_cache:
            raise ValueError(
                "quantized_cache does not compose with sliding_cache "
                "(the ring path keeps full-width slots) — pick one"
            )
        cache_dtype = jnp.int8 if qc else self.compute_dtype
        zeros = lambda: jnp.zeros(  # noqa: E731
            (b, cache_len, h_kv, d), cache_dtype
        )
        ck = self.variable("cache", "k", zeros)
        cv = self.variable("cache", "v", zeros)
        if qc:
            # Per-(position, head) symmetric scales — they factor out of
            # the head-dim contraction, so reads stay int8 end to end.
            # The fresh full-precision k/v stay untouched (the prefill
            # flash attention below uses THEM, so prefill logits are
            # exact); only the cache writes carry the quantized copies.
            szeros = lambda: jnp.zeros(  # noqa: E731
                (b, cache_len, h_kv), jnp.float32
            )
            ksc = self.variable("cache", "k_scale", szeros)
            vsc = self.variable("cache", "v_scale", szeros)
            from horovod_tpu.models.quant import _quantize_sym

            wk, k_s = _quantize_sym(k, axis=-1)  # int8, [B, T, H_kv, 1]
            wv, v_s = _quantize_sym(v, axis=-1)
            k_s, v_s = k_s[..., 0], v_s[..., 0]  # [B, T, H_kv]
        else:
            wk, wv = k, v
        idx = jnp.asarray(decode_index, jnp.int32)
        if idx.ndim == 1 and self.sliding_cache:
            raise ValueError(
                "per-row decode indices are not supported with "
                "sliding_cache — the ring buffer's slot math is lockstep"
            )
        if self.sliding_cache:
            if t > 1 and not first_call:
                raise ValueError(
                    "sliding_cache supports prefill + single-token decode "
                    "steps; chunk extension (speculative decoding's verify "
                    "pass) needs the full-history cache — evicted rows "
                    "could be needed by the chunk's early tokens"
                )
            # Per-slot absolute positions ([B, W] so batch-reordering
            # consumers like beam search gather it like the K/V arrays);
            # -1 = never written.
            cpos = self.variable(
                "cache", "pos",
                lambda: jnp.full((b, cache_len), -1, jnp.int32),
            )
            # Slot layout: positions < sinks pin to slots [0, sinks); the
            # rest ring over [sinks, sinks + window). A fresh token is kept
            # iff it is a sink or among the last `window` ring-eligible
            # tokens of this write (earlier ones would be evicted within
            # the same chunk); dropped tokens scatter to an out-of-bounds
            # slot under mode='drop'. Kept slots are unique: sink slots by
            # position, ring slots because the last `window` ring positions
            # are distinct mod window.
            win = cache_len - sinks
            new_pos = idx + jnp.arange(t, dtype=jnp.int32)
            ring_slot = sinks + (new_pos - sinks) % win
            slot = jnp.where(new_pos < sinks, new_pos, ring_slot)
            keep = (new_pos < sinks) | (new_pos >= idx + t - win)
            slot = jnp.where(keep, slot, cache_len)  # OOB → dropped
            ck.value = cfg.constrain(
                ck.value.at[:, slot].set(
                    k.astype(ck.value.dtype), mode="drop"
                ),
                cache_spec,
            )
            cv.value = cfg.constrain(
                cv.value.at[:, slot].set(
                    v.astype(cv.value.dtype), mode="drop"
                ),
                cache_spec,
            )
            cpos.value = cpos.value.at[:, slot].set(
                jnp.broadcast_to(new_pos, (b, t)), mode="drop"
            )
        elif idx.ndim == 0:
            ck.value = cfg.constrain(
                jax.lax.dynamic_update_slice(
                    ck.value, wk.astype(ck.value.dtype), (0, idx, 0, 0)
                ),
                cache_spec,
            )
            cv.value = cfg.constrain(
                jax.lax.dynamic_update_slice(
                    cv.value, wv.astype(cv.value.dtype), (0, idx, 0, 0)
                ),
                cache_spec,
            )
            if qc:
                # Same layout pinning as the value writes: heads over
                # `model`, so the persistent scale state never picks up a
                # GSPMD-chosen resharding inside the decode scan.
                scale_spec = P(BATCH_AXES, None, MODEL_AXIS)
                ksc.value = cfg.constrain(
                    jax.lax.dynamic_update_slice(
                        ksc.value, k_s, (0, idx, 0)
                    ),
                    scale_spec,
                )
                vsc.value = cfg.constrain(
                    jax.lax.dynamic_update_slice(
                        vsc.value, v_s, (0, idx, 0)
                    ),
                    scale_spec,
                )
        else:
            # Per-row indices ([B]): each row writes its fresh K/V at its
            # own positions — the ragged-prompt / per-row-speculative
            # layout. mode='drop' guards rows whose positions run past the
            # cache (they are masked out of the attention below anyway).
            rows = jnp.arange(b, dtype=jnp.int32)[:, None]
            pos = idx[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
            ck.value = cfg.constrain(
                ck.value.at[rows, pos].set(
                    wk.astype(ck.value.dtype), mode="drop"
                ),
                cache_spec,
            )
            cv.value = cfg.constrain(
                cv.value.at[rows, pos].set(
                    wv.astype(cv.value.dtype), mode="drop"
                ),
                cache_spec,
            )
            if qc:
                scale_spec = P(BATCH_AXES, None, MODEL_AXIS)
                ksc.value = cfg.constrain(
                    ksc.value.at[rows, pos].set(k_s, mode="drop"),
                    scale_spec,
                )
                vsc.value = cfg.constrain(
                    vsc.value.at[rows, pos].set(v_s, mode="drop"),
                    scale_spec,
                )
        if t > 1 and first_call:
            # Prefill: the cache was empty below `idx` (generate() starts at
            # 0), so causal attention over the fresh K/V is the full answer —
            # the training forward's local flash path (O(T) memory), with the
            # same manual-sharding treatment on a live mesh (GSPMD cannot
            # auto-partition the Mosaic custom call).
            from horovod_tpu.ops.flash_attention import flash_attention

            if rep > 1:  # prefill attends at full H, like training
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            # Same global+local mask as training/decode, computed from the
            # fresh K/V (the ring cache may already have evicted mid-prompt
            # keys an early query needs); sinks ride the kernel's pinned
            # tile.
            local = functools.partial(
                flash_attention, causal=True, window=self.window,
                sinks=sinks,
            )
            if cfg.mesh is not None and cfg.mesh.size > 1:
                spec = P(BATCH_AXES, None, MODEL_AXIS, None)
                local = jax.shard_map(
                    local, mesh=cfg.mesh, in_specs=(spec, spec, spec),
                    out_specs=spec, check_vma=False,
                )
            return local(q, k, v)
        # Decode step (t == 1) or chunk extension (t > 1 on a warm cache —
        # chunked long-prompt prefill, and speculative decoding's verify
        # pass): the t fresh queries attend over the cache prefix
        # [0 .. idx + row], causal within the chunk. Scores are [t, L] per
        # head — chunking is exactly what bounds that memory for long
        # prompts. Grouped einsum (g query heads share each cached kv head)
        # so the cache streams ONCE per kv head — never materializing a
        # repeated [B, L, H, D] copy, which would forfeit GQA's bandwidth
        # saving.
        scale = d ** -0.5
        q5 = q.reshape(b, t, h_kv, rep, d)
        s = jnp.einsum(
            "bqhgd,bkhd->bhgqk", q5, ck.value,
            preferred_element_type=jnp.float32,
        ) * scale
        if qc:
            # The per-(position, head) scale factors out of the head-dim
            # contraction: score = (q · k_int8) · k_scale. The einsum above
            # read int8 directly (the convert rides the dot); only the
            # [.., L]-shaped scores pay the scale multiply.
            s = s * jnp.transpose(ksc.value, (0, 2, 1))[:, :, None, None, :]
        if self.sliding_cache:
            # Ring slots carry their absolute positions: valid = written,
            # causal, and inside the band OR a pinned sink (eviction
            # already guarantees the band bound for fully-warm caches; the
            # explicit check keeps partially-warm ones exact too).
            # (Scalar idx only — per-row rejects above.)
            qpos = idx + jnp.arange(t, dtype=jnp.int32)
            kpos = cpos.value[:, None, :]  # [B, 1, W]
            qp = qpos[None, :, None]  # [1, t, 1]
            band = (kpos > qp - self.window) | (kpos < sinks)
            valid = (kpos >= 0) & (kpos <= qp) & band
            valid = valid[:, None, None, :, :]  # [B, 1, 1, t, W]
        else:
            # qpos is [Bq, t] with Bq ∈ {1, B}: a scalar index broadcasts
            # one mask over the batch, per-row indices ([B]) carry a mask
            # per row.
            qpos = (
                idx.reshape(1, 1) if idx.ndim == 0 else idx[:, None]
            ) + jnp.arange(t, dtype=jnp.int32)[None, :]
            kpos = jnp.arange(self.max_decode_len, dtype=jnp.int32)
            valid = kpos[None, None, :] <= qpos[:, :, None]  # [Bq, t, L]
            if self.window is not None:
                # Sliding window over the cache: a query at qpos sees cache
                # rows in (qpos − window, qpos] — plus the first `sinks`
                # positions when streaming a densely-trained model
                # (StreamingLLM; the full-history twin of the ring path,
                # which the ring's exactness tests compare against).
                keep = kpos[None, None, :] > qpos[:, :, None] - self.window
                if sinks:
                    keep |= (kpos < sinks)[None, None, :]
                valid &= keep
            valid = valid[:, None, None, :, :]  # [Bq, 1, 1, t, L]
        s = jnp.where(valid, s, attention_ops._BIG_NEG)
        p = jax.nn.softmax(s, axis=-1)
        if qc:
            # Same factoring on the value side: fold v_scale into the
            # probabilities (shaped [.., L]) and contract against int8 v.
            p_eff = p * jnp.transpose(vsc.value, (0, 2, 1))[:, :, None, None, :]
            out = jnp.einsum(
                "bhgqk,bkhd->bqhgd", p_eff, cv.value,
                preferred_element_type=jnp.float32,
            )
        else:
            out = jnp.einsum(
                "bhgqk,bkhd->bqhgd", p.astype(cv.value.dtype), cv.value,
                preferred_element_type=jnp.float32,
            )
        return out.reshape(b, t, h, d).astype(q.dtype)


class LMHead(nn.Module):
    """The LM head as an explicit ``[d_model, vocab]`` kernel (param path
    ``lm_head/kernel``, identical to the former DenseGeneral's) so the fused
    chunked-CE path (ops/fused_ce.py) can reach the kernel without
    materializing full logits. With ``tied`` it owns no parameter: the
    model hands each call the embedding's ``[vocab, d_model]`` table and the
    kernel is its transpose, so the table's gradient is the lookup's
    scatter-add plus this head's dW."""

    d_model: int
    vocab_size: int
    compute_dtype: jnp.dtype = jnp.float32
    logits_dtype: jnp.dtype = jnp.float32
    int8_compute: bool = False
    sharding: ShardingConfig = ShardingConfig()
    tied: bool = False

    def setup(self):
        if not self.tied:
            self.kernel = self.param(
                "kernel",
                nn.initializers.lecun_normal(),
                (self.d_model, self.vocab_size),
            )

    def _kernel_of(self, table):
        """``[d_model, vocab]``: the head's own, or the table's transpose."""
        if self.tied != (table is not None):
            raise ValueError(
                "LMHead: a tied head is handed the embedding's table at "
                "each call, an untied one none "
                f"(tied={self.tied}, table given: {table is not None})")
        return table.T if self.tied else self.kernel

    @jax.named_scope(fused_ce.SCOPE)
    def __call__(self, x, table=None):
        kernel = self._kernel_of(table)
        if self.int8_compute:
            from horovod_tpu.models.quant import int8_dot_general

            logits = int8_dot_general(
                x.astype(self.compute_dtype),
                kernel.astype(self.compute_dtype),
                (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=self.logits_dtype,
            )
            return logits
        logits = jnp.dot(
            x.astype(self.compute_dtype), kernel.astype(self.compute_dtype)
        )
        return logits.astype(self.logits_dtype)

    def fused_loss(self, x, labels, n_chunks: int, table=None):
        """(per-token loss, per-token correct) without full logits.

        Where the mesh splits the rows of ``x`` ``[B, T, D]`` (live
        ``data``/``fsdp``/``seq`` axes that divide B and T), each chip runs
        the chunked head on its own ``B/dp x T/sp`` rows: a `shard_map`
        over the row axes with the kernel replicated over them, so neither
        scan holds a collective and the transpose of the replicated kernel
        is the ONE cross-chip sum of dW, after the backward loop. Left to
        the partitioner, the scans walk the flattened (sharded) row axis
        and every chip gathers and computes every chunk. ``model`` stays
        the partitioner's (the region is manual over the row axes only),
        and where it is live the op is told so: scanning the vocabulary
        there makes the partitioner gather the kernel and every chip of a
        ``model`` group compute every slice, so the rows are scanned."""
        mesh = self.sharding.mesh
        vocab_split = mesh is not None and mesh.shape.get(MODEL_AXIS, 1) > 1
        shards = 1 if mesh is None else _row_shards(mesh, *labels.shape)
        # Trace time, from the chip's own rows: the backward rule's choice
        # is static, so this says which program was built.
        from horovod_tpu import obs

        by_vocab = fused_ce.scans_vocab(
            labels.size // shards, self.vocab_size, vocab_split)
        obs.gauge("hvt_head_ce_scan", float(by_vocab), axis="vocab")
        obs.gauge("hvt_head_ce_scan", float(not by_vocab), axis="rows")

        def head(x, kernel, labels):
            return fused_ce.fused_linear_cross_entropy(
                x, kernel, labels, max(1, n_chunks), vocab_split)

        if shards > 1:
            rows = P(BATCH_AXES, SEQ_AXIS)
            # jitted: op by op (`Trainer.build`'s init) JAX refuses a
            # region that is manual over some of the mesh's axes only.
            # The scope around the region names the dW sum for the trace
            # too: it is born in the region's transpose, outside both rules.
            head = jax.named_scope(fused_ce.SCOPE)(jax.jit(jax.shard_map(
                head, mesh=mesh,
                in_specs=(P(BATCH_AXES, SEQ_AXIS, None), P(), rows),
                out_specs=(rows, rows),
                axis_names=frozenset(ROW_AXES), check_vma=False,
            )))
        return head(
            x.astype(self.compute_dtype), self._kernel_of(table), labels)


def _row_shards(mesh: Mesh, b: int, t: int) -> int:
    """Over how many devices ``mesh`` splits a ``[b, t]`` batch's rows; 1
    where it does not, or not evenly (a `shard_map` takes no ragged
    shard)."""
    dp = mesh.shape.get(DATA_AXIS, 1) * mesh.shape.get(FSDP_AXIS, 1)
    sp = mesh.shape.get(SEQ_AXIS, 1)
    return dp * sp if b % dp == 0 and t % sp == 0 else 1


class TransformerLM(nn.Module):
    """Causal LM over integer tokens: ``[B, T] -> [B, T, vocab]`` logits.

    With ``labels=...`` passed to ``__call__`` the model instead returns
    ``(per_token_loss, per_token_correct)`` computed by the fused chunked-CE
    head (``fused_head_chunks`` chunks; see ops/fused_ce.py) — the
    ``Trainer(loss='module')`` contract. Without labels the full-logits path
    is unchanged (predict/decode/export)."""

    vocab_size: int = 256
    d_model: int = 256
    n_heads: int = 8
    # Grouped-query attention: K/V projected to n_kv_heads < n_heads (each
    # shared by n_heads/n_kv_heads query heads). Shrinks the decode cache —
    # and the bytes streamed per generated token — by that group factor;
    # training FLOPs are unchanged. None = MHA (fused qkv projection).
    n_kv_heads: int | None = None
    # Sliding-window (local) attention: each query attends to its `window`
    # most recent tokens only (see Block.window). None = full causal.
    window: int | None = None
    n_layers: int = 4
    dropout: float = 0.1
    compute_dtype: jnp.dtype = jnp.float32
    sharding: ShardingConfig = ShardingConfig()
    # Memory knobs for long context (HBM is the binding constraint on one
    # chip):
    # * remat: rematerialize each block in the backward pass
    #   (jax.checkpoint) — activations per layer drop to the block inputs;
    # * logits_dtype: bf16 halves the [B, T, vocab] logits + cotangent that
    #   dominate long-sequence HBM; the loss upcasts to f32 on the fly
    #   (fused by XLA, never materialized), so logsumexp stays accurate.
    remat: bool = False
    logits_dtype: jnp.dtype = jnp.float32
    # int8 MXU compute for every Dense matmul + the LM head (inference
    # only — prefill and large-batch decode are compute-bound, where the
    # v5e's 2x int8 MXU rate pays; models/quant.int8_dot_general).
    int8_compute: bool = False
    # moe_every=k replaces every k-th block's MLP with an expert-parallel
    # MoE (0 = dense everywhere, the default).
    moe_every: int = 0
    n_experts: int = 8
    moe_k: int = 2
    capacity_factor: float = 1.25
    moe_aux_coef: float = 1e-2
    moe_router: str = "top_k"  # or 'expert_choice' (see models/moe.py)
    # Autoregressive inference (models/decoding.py `generate`): per-block K/V
    # caches sized [B, max_decode_len, H_kv, D] in the ``cache`` collection; the
    # top-level ``cache/index`` counts consumed positions. T>1 = prefill,
    # T==1 = one decode step.
    decode: bool = False
    max_decode_len: int = 0
    # Ring-buffer cache for windowed models: O(window) decode memory and
    # cache traffic regardless of generation length (see Block).
    sliding_cache: bool = False
    # int8 K/V cache with per-(position, head) scales (see Block) — the
    # decode cache stream and cache HBM halve; approximate, quality-gated.
    quantized_cache: bool = False
    # StreamingLLM attention sinks (decode-time; see Block.attention_sinks).
    attention_sinks: int = 0
    # Row-chunk count for the fused linear-CE head when ``labels`` are fed
    # through ``__call__`` (loss='module'): chunks of the CHIP'S OWN rows
    # (B/dp · T/sp of them on a mesh that ``sharding`` holds, see
    # LMHead.fused_loss), so peak head memory is
    # ceil(the chip's rows / chunks) · vocab floats instead of the full
    # [B, T, vocab] logits + cotangent. 0 → a single chunk (dense-equivalent
    # memory, same math). A model built without the mesh under a
    # multi-device Trainer (attn='dense') keeps the replicated head: chunks
    # of all B·T rows, computed on every chip.
    fused_head_chunks: int = 0

    @nn.compact
    def __call__(
        self, tokens, *, train: bool = False, segment_ids=None, labels=None
    ):
        cfg = self.sharding
        b, t = tokens.shape
        if self.int8_compute and train:
            raise ValueError(
                "int8_compute is inference-only: round() kills gradients "
                "(quantization-aware training would need a straight-"
                "through estimator) — clone the model with "
                "int8_compute=False for training"
            )
        if self.int8_compute and self.moe_every:
            raise ValueError(
                "int8_compute does not cover MoE expert matmuls (the "
                "routed einsums bypass the Dense dot_general injection) — "
                "an MoE model would silently keep its dominant FLOPs in "
                "bf16; use a dense model or int8_compute=False"
            )
        decode_index = None
        if self.decode:
            if self.remat or train or segment_ids is not None:
                raise ValueError(
                    "decode mode is inference-only: remat/train/segment_ids "
                    "do not apply"
                )
            idx_var = self.variable(
                "cache", "index", lambda: jnp.zeros((), jnp.int32)
            )
            # The cache index is a scalar (lockstep decode) or a [B] vector
            # (per-row positions: ragged-prompt generation, per-row
            # speculative acceptance). Callers switch layouts by writing the
            # threaded cache['index'] entry between applies.
            decode_index = idx_var.value
            offs = jnp.arange(t, dtype=jnp.int32)
            if decode_index.ndim == 0:
                positions = decode_index + jnp.broadcast_to(offs, (b, t))
            else:
                positions = decode_index[:, None] + offs[None, :]
            idx_var.value = decode_index + t
        elif segment_ids is None:
            positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        else:
            # Packed sequences: RoPE positions restart at each document
            # boundary, and attention is restricted to within-document pairs
            # (the flash kernel's segment masking, with block-level
            # early-out on disjoint tiles).
            positions = packed_positions(segment_ids)
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.compute_dtype)(tokens)
        x = cfg.constrain(x, P(BATCH_AXES, SEQ_AXIS, None))
        # `train` is argnum 3 of Block.__call__ (self, x, positions, train)
        # and must stay a static python bool through the remat boundary.
        block_cls = (
            nn.remat(Block, static_argnums=(3,)) if self.remat else Block
        )
        for i in range(self.n_layers):
            x = block_cls(
                self.d_model, self.n_heads, self.dropout,
                self.compute_dtype, cfg,
                n_kv_heads=self.n_kv_heads,
                window=self.window,
                use_moe=self.moe_every > 0 and (i + 1) % self.moe_every == 0,
                n_experts=self.n_experts,
                moe_k=self.moe_k,
                capacity_factor=self.capacity_factor,
                moe_aux_coef=self.moe_aux_coef,
                moe_router=self.moe_router,
                decode=self.decode,
                max_decode_len=self.max_decode_len,
                sliding_cache=self.sliding_cache,
                quantized_cache=self.quantized_cache,
                attention_sinks=self.attention_sinks,
                int8_compute=self.int8_compute,
                # Explicit name = flax's auto-name, so the param tree is
                # identical with and without remat (the remat wrapper would
                # otherwise scope as CheckpointBlock_i).
                name=f"Block_{i}",
            )(x, positions, train, segment_ids, decode_index)
        x = nn.LayerNorm(dtype=self.compute_dtype, use_bias=False)(x)
        head = LMHead(
            self.d_model, self.vocab_size,
            compute_dtype=self.compute_dtype,
            logits_dtype=self.logits_dtype,
            int8_compute=self.int8_compute,
            sharding=cfg,
            name="lm_head",
        )
        if labels is not None:
            return head.fused_loss(x, labels, self.fused_head_chunks)
        return head(x)


def param_specs(params, mesh: Mesh, extra_tp_dim: dict | None = None) -> dict:
    """PartitionSpec tree for the Megatron TP (+FSDP) layout.

    Path-based rules over the plain param pytree:

    * QKV kernel   [d_model, H, 3·head] → heads on ``model`` (column);
    * attn proj    [H, head, d_model]   → heads on ``model`` (row);
    * MLP up       [d_model, 4·d]       → features on ``model`` (column);
    * MLP down     [4·d, d_model]       → inputs on ``model`` (row);
    * LM head      [d_model, vocab]     → vocab on ``model``;
    * embedding / LayerNorm             → replicated on ``model``.

    With an ``fsdp`` axis > 1, each kernel's first divisible non-model dim is
    additionally sharded over ``fsdp`` (weight-gathered FSDP: XLA inserts the
    gathers where the weights are consumed).

    ``extra_tp_dim`` extends the name→column/row rule table — how sibling
    model families (e.g. `models/seq2seq.py` with its cross-attention
    projections) reuse these rules without duplicating them.
    """
    fsdp = mesh.shape.get(FSDP_AXIS, 1) > 1

    # Rules keyed on the explicit layer names the model declares — immune to
    # flax auto-numbering shifts when layers are added or reordered.
    tp_dim = {
        "qkv": 1,        # [dm, H, 3·hd] — heads (column-parallel)
        "q_proj": 1,     # [dm, H, hd]   — heads (column-parallel, GQA)
        "kv_proj": 1,    # [dm, H_kv, 2·hd] — kv heads (column-parallel, GQA)
        "attn_out": 0,   # [H, hd, dm]  — heads (row-parallel)
        "mlp_up": 1,     # [dm, 4·dm]   — features (column-parallel)
        "mlp_down": 0,   # [4·dm, dm]   — inputs (row-parallel)
        "lm_head": 1,    # [dm, vocab]  — vocab (column-parallel)
    }
    if extra_tp_dim:
        tp_dim = {**tp_dim, **extra_tp_dim}
    # Expert weights: experts over the `expert` axis, hidden over `model`
    # (column for up, row for down) — EP × TP composition.
    moe_dims = {
        "moe_up": {0: EXPERT_AXIS, 2: MODEL_AXIS},    # [E, dm, hidden]
        "moe_down": {0: EXPERT_AXIS, 1: MODEL_AXIS},  # [E, hidden, dm]
    }

    def rule(path, leaf):
        names = [
            p.key for p in path if isinstance(p, jax.tree_util.DictKey)
        ]
        spec: list = [None] * leaf.ndim
        # LoRA adapter leaves (…/lora/…/{a,b}) live under the SAME layer
        # names as the kernels they adapt, but their shapes carry the rank
        # dimension — TP/EP-sharding them is degenerate for small ranks and
        # a divisibility (or rank) failure otherwise. Adapters skip both
        # rule tables; the fsdp rule below still applies, with its own
        # divisibility check.
        # Match the LoRAModel adapter layout precisely (a 'lora' subtree
        # whose leaves are named 'a'/'b' — models/lora.py `init_adapters`),
        # so a user model that merely CONTAINS a submodule named 'lora'
        # still gets its kernels TP/EP-sharded, while a LoRAModel nested
        # under any wrapper keeps the exemption.
        is_lora = "lora" in names and names[-1:] in (["a"], ["b"])
        moe = next((n for n in names if n in moe_dims), None) if not is_lora else None
        if moe is not None:
            for dim, axis in moe_dims[moe].items():
                if leaf.shape[dim] % mesh.shape[axis] != 0:
                    # Silent replication would quietly discard the memory
                    # scaling EP exists for — fail like MeshSpec.resolve.
                    raise ValueError(
                        f"{moe} dim {dim} ({leaf.shape[dim]}) is not "
                        f"divisible by mesh axis {axis!r} "
                        f"({mesh.shape[axis]})"
                    )
                spec[dim] = axis
        else:
            layer = next((n for n in names if n in tp_dim), None)
            if layer is not None and leaf.ndim >= 2 and not is_lora:
                spec[tp_dim[layer]] = MODEL_AXIS
        if fsdp and leaf.ndim >= 2:
            for dim in range(leaf.ndim):
                if spec[dim] is None and leaf.shape[dim] % mesh.shape[FSDP_AXIS] == 0:
                    spec[dim] = FSDP_AXIS
                    break
        return P(*spec)

    return jax.tree_util.tree_map_with_path(rule, params)
