"""Encoder-decoder (seq2seq) transformer — the cross-attention model family.

The reference repo has no sequence model at all (SURVEY.md §5.7: fixed
28×28 images, "no sequence dimension"); this framework's model zoo treats
sequence transduction as a first-class family alongside the decoder-only
LM. The architecture is the standard pre-LN encoder-decoder (Vaswani et
al.; T5-style layout with RoPE instead of learned/relative positions):

* **Encoder** — bidirectional (non-causal) self-attention over the source,
  padding masked via the flash kernel's segment ids (pad tokens get id 0,
  real tokens id 1 — segment-disjoint tiles are block-skipped, so a mostly
  padded batch also *costs* less, not just masks more);
* **Decoder** — causal self-attention over the target plus
  **cross-attention** into the encoder memory. Cross-attention is where
  this family earns its place in the test matrix: it exercises the flash
  kernel's Tk ≠ Tq grids (`ops/flash_attention.py` cross-attention
  support) with ``causal=False`` — the path no decoder-only model ever
  takes — including the padding mask riding the same segment-id operands.
  No RoPE on cross q/k: source and target positions are different spaces,
  so cross-attention is position-agnostic (the T5 convention).

Parallelism: data/FSDP batch sharding, Megatron tensor parallelism via
`param_specs` (the same name-keyed column/row rules as the decoder-only
LM, extended with the cross-attention projections), AND sequence/context
parallelism: with a live ``seq`` mesh axis all three attention families
run as ring collectives — the encoder's bidirectional segmented
self-attention and the decoder's causal self-attention through
`ring_flash_attention`, cross-attention through `ring_cross_attention`
(queries and memory sharded over DIFFERENT logical sequences; the memory
blocks and their padding ids rotate around the ring). Decode mode is the
one seq-parallel refusal: a single-token step has no sequence to shard.

Inference (`make_seq2seq_generate_fn`): encode once, then the whole
autoregressive decode — BOS prefill + `lax.scan` of single-token steps —
runs as ONE compiled program, mirroring `models/decoding.py`. The decoder
keeps two caches per block: the usual growing self-attention K/V cache,
and a **static cross K/V cache** computed from the memory once at prefill
(the per-layer cross projections of a fixed memory are loop-invariant; a
naive per-step recompute would stream the memory through two matmuls for
every generated token).
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax

import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models.transformer import (
    BATCH_AXES,
    ShardingConfig,
    _rope,
)
from horovod_tpu.ops import attention as attention_ops
from horovod_tpu.parallel.mesh import MODEL_AXIS, SEQ_AXIS

_NEG = -1e30


def _attention(cfg: ShardingConfig, q, k, v, *, causal: bool,
               q_ids=None, kv_ids=None, cross: bool = False):
    """One attention dispatch for all three seq2seq call sites.

    Without a live ``seq`` axis: the flash kernel locally, shard_mapped
    over the mesh exactly like `transformer.Block` (GSPMD cannot
    auto-partition a Mosaic custom call; attention mixes neither batch nor
    heads, so manual batch/head sharding is free). With sequence
    parallelism: the ring collectives — `ring_flash_attention` for the
    encoder's non-causal segmented self-attention and the decoder's causal
    self-attention, `ring_cross_attention` for cross-attention (queries
    and memory sharded over DIFFERENT logical sequences; kv ids rotate
    with their blocks, q ids stay local)."""
    from horovod_tpu.ops.flash_attention import flash_attention

    if cfg.seq_parallel:
        if cfg.attn != "ring":
            raise ValueError(
                "sequence-parallel Seq2SeqTransformer supports attn='ring' "
                f"only (got {cfg.attn!r}) — the dense/Ulysses paths are "
                "decoder-only territory"
            )
        qspec = P(BATCH_AXES, SEQ_AXIS, MODEL_AXIS, None)
        ids_spec = P(BATCH_AXES, SEQ_AXIS)
        if cross:
            fn = lambda q, k, v, qi, ki: attention_ops.ring_cross_attention(  # noqa: E731
                q, k, v, axis_name=SEQ_AXIS,
                q_segment_ids=qi, kv_segment_ids=ki,
            )
            return jax.shard_map(
                fn, mesh=cfg.mesh,
                in_specs=(qspec, qspec, qspec, ids_spec, ids_spec),
                out_specs=qspec, check_vma=False,
            )(q, k, v, q_ids, kv_ids)
        if q_ids is not None:
            # Encoder self-attention: q and kv ids are the SAME shard —
            # ring_flash_attention takes one segment_ids for both sides, so
            # a future asymmetric-mask caller must not silently lose kv_ids
            # here (every other path honors the two independently).
            if q_ids is not kv_ids:
                raise ValueError(
                    "sequence-parallel self-attention needs q_ids and "
                    "kv_ids to be the same array (asymmetric masks are "
                    "cross=True territory)"
                )
            fn = lambda q, k, v, ids: attention_ops.ring_flash_attention(  # noqa: E731
                q, k, v, axis_name=SEQ_AXIS, causal=causal, segment_ids=ids
            )
            return jax.shard_map(
                fn, mesh=cfg.mesh,
                in_specs=(qspec, qspec, qspec, ids_spec),
                out_specs=qspec, check_vma=False,
            )(q, k, v, q_ids)
        fn = lambda q, k, v: attention_ops.ring_flash_attention(  # noqa: E731
            q, k, v, axis_name=SEQ_AXIS, causal=causal
        )
        return jax.shard_map(
            fn, mesh=cfg.mesh, in_specs=(qspec, qspec, qspec),
            out_specs=qspec, check_vma=False,
        )(q, k, v)

    if cfg.attn == "dense":
        return attention_ops.dense_attention(
            q, k, v, causal=causal, q_segment_ids=q_ids, kv_segment_ids=kv_ids
        )

    def local(q, k, v, q_ids=None, kv_ids=None):
        return flash_attention(
            q, k, v, causal=causal, q_segment_ids=q_ids, kv_segment_ids=kv_ids
        )

    args = (q, k, v)
    if q_ids is not None:
        args += (q_ids, kv_ids)
    if cfg.mesh is not None and cfg.mesh.size > 1:
        spec = P(BATCH_AXES, None, MODEL_AXIS, None)
        in_specs = (spec, spec, spec)
        if q_ids is not None:
            in_specs += (P(BATCH_AXES, None), P(BATCH_AXES, None))
        local = jax.shard_map(
            local, mesh=cfg.mesh, in_specs=in_specs, out_specs=spec,
            check_vma=False,
        )
    return local(*args)


class EncoderBlock(nn.Module):
    d_model: int
    n_heads: int
    dropout: float
    compute_dtype: jnp.dtype
    sharding: ShardingConfig

    @nn.compact
    def __call__(self, x, positions, src_valid, train: bool = False):
        cfg = self.sharding
        head_dim = self.d_model // self.n_heads
        dense = functools.partial(
            nn.DenseGeneral, dtype=self.compute_dtype, use_bias=False
        )

        h = nn.LayerNorm(dtype=self.compute_dtype, use_bias=False)(x)
        qkv = dense(features=(self.n_heads, 3 * head_dim), name="qkv")(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q, k = _rope(q, positions), _rope(k, positions)
        # Bidirectional self-attention; pad positions (id 0) are disjoint
        # from REAL tokens (id 1), so no real position ever sees a pad.
        # Pad queries still see each other (segment masking is equality-
        # based), so pad rows of the memory are garbage — harmless only
        # because the cross-attention mask drops them downstream; any new
        # consumer of the memory (e.g. mean-pooling) must mask too.
        out = _attention(
            cfg, q, k, v, causal=False, q_ids=src_valid, kv_ids=src_valid
        )
        out = dense(features=self.d_model, axis=(-2, -1), name="attn_out")(out)
        out = nn.Dropout(self.dropout, deterministic=not train)(out)
        x = x + out
        x = cfg.constrain(x, P(BATCH_AXES, SEQ_AXIS, None))

        h = nn.LayerNorm(dtype=self.compute_dtype, use_bias=False)(x)
        h = dense(features=4 * self.d_model, name="mlp_up")(h)
        h = nn.gelu(h)
        h = dense(features=self.d_model, name="mlp_down")(h)
        h = nn.Dropout(self.dropout, deterministic=not train)(h)
        return cfg.constrain(x + h, P(BATCH_AXES, SEQ_AXIS, None))


class DecoderBlock(nn.Module):
    d_model: int
    n_heads: int
    dropout: float
    compute_dtype: jnp.dtype
    sharding: ShardingConfig
    # Autoregressive inference: self-attention K/V live in a growing
    # [B, max_decode_len, H, D] cache; cross K/V in a static [B, S, H, D]
    # cache written once at prefill (see module docstring).
    decode: bool = False
    max_decode_len: int = 0

    @nn.compact
    def __call__(self, x, positions, memory, mem_valid, train: bool = False,
                 decode_index=None):
        cfg = self.sharding
        head_dim = self.d_model // self.n_heads
        dense = functools.partial(
            nn.DenseGeneral, dtype=self.compute_dtype, use_bias=False
        )

        # --- causal self-attention ----------------------------------------
        h = nn.LayerNorm(dtype=self.compute_dtype, use_bias=False)(x)
        qkv = dense(features=(self.n_heads, 3 * head_dim), name="qkv")(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q, k = _rope(q, positions), _rope(k, positions)
        if self.decode:
            out = self._cached_self_attention(q, k, v, decode_index)
        else:
            out = _attention(cfg, q, k, v, causal=True)
        out = dense(features=self.d_model, axis=(-2, -1), name="attn_out")(out)
        out = nn.Dropout(self.dropout, deterministic=not train)(out)
        x = x + out
        x = cfg.constrain(x, P(BATCH_AXES, SEQ_AXIS, None))

        # --- cross-attention into the encoder memory ----------------------
        h = nn.LayerNorm(dtype=self.compute_dtype, use_bias=False)(x)
        q = dense(features=(self.n_heads, head_dim), name="cross_q")(h)
        if self.decode:
            out = self._cached_cross_attention(q, memory, mem_valid, dense)
        else:
            kv = dense(features=(self.n_heads, 2 * head_dim), name="cross_kv")(
                memory
            )
            ck, cv = jnp.split(kv, 2, axis=-1)
            # Tq = target length, Tk = source length — the kernel's
            # cross-attention grids. Non-causal: every target position sees
            # the whole (unpadded) source. Query ids are the constant 1, so
            # the mask reduces to the source-side padding mask.
            q_ids = jnp.ones(q.shape[:2], jnp.int32)
            out = _attention(
                cfg, q, ck, cv, causal=False, q_ids=q_ids, kv_ids=mem_valid,
                cross=True,
            )
        out = dense(features=self.d_model, axis=(-2, -1), name="cross_out")(out)
        out = nn.Dropout(self.dropout, deterministic=not train)(out)
        x = x + out
        x = cfg.constrain(x, P(BATCH_AXES, SEQ_AXIS, None))

        # --- MLP -----------------------------------------------------------
        h = nn.LayerNorm(dtype=self.compute_dtype, use_bias=False)(x)
        h = dense(features=4 * self.d_model, name="mlp_up")(h)
        h = nn.gelu(h)
        h = dense(features=self.d_model, name="mlp_down")(h)
        h = nn.Dropout(self.dropout, deterministic=not train)(h)
        return cfg.constrain(x + h, P(BATCH_AXES, SEQ_AXIS, None))

    def _cached_self_attention(self, q, k, v, decode_index):
        """Growing-cache causal self-attention (the full-history layout of
        `transformer.Block._decode_attention`, MHA-only): prefill writes
        [0:T] and attends causally over the fresh K/V; a decode step writes
        at ``decode_index`` and attends densely over the valid prefix."""
        cfg = self.sharding
        b, t, h, d = q.shape
        if self.max_decode_len < t:
            raise ValueError(
                f"max_decode_len ({self.max_decode_len}) < input length ({t})"
            )
        cache_spec = P(BATCH_AXES, None, MODEL_AXIS, None)
        first_call = not self.has_variable("cache", "k")
        zeros = lambda: jnp.zeros(  # noqa: E731
            (b, self.max_decode_len, h, d), self.compute_dtype
        )
        ck = self.variable("cache", "k", zeros)
        cv = self.variable("cache", "v", zeros)
        idx = jnp.asarray(decode_index, jnp.int32)
        ck.value = cfg.constrain(
            lax.dynamic_update_slice(
                ck.value, k.astype(ck.value.dtype), (0, idx, 0, 0)
            ),
            cache_spec,
        )
        cv.value = cfg.constrain(
            lax.dynamic_update_slice(
                cv.value, v.astype(cv.value.dtype), (0, idx, 0, 0)
            ),
            cache_spec,
        )
        if t > 1 and first_call:
            return _attention(cfg, q, k, v, causal=True)
        scale = d ** -0.5
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, ck.value,
            preferred_element_type=jnp.float32,
        ) * scale
        qpos = idx + jnp.arange(t, dtype=jnp.int32)
        kpos = jnp.arange(self.max_decode_len, dtype=jnp.int32)
        valid = kpos[None, :] <= qpos[:, None]
        s = jnp.where(valid[None, None], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(cv.value.dtype), cv.value,
            preferred_element_type=jnp.float32,
        )
        return out.astype(q.dtype)

    def _cached_cross_attention(self, q, memory, mem_valid, dense):
        """Cross-attention against the static per-layer cross K/V cache.

        The cross projections of a fixed memory are loop-invariant, so they
        are computed ONCE — on the first (prefill) call, when the cache
        variables don't exist yet — and every decode step reads the cached
        [B, S, H, D] arrays instead of re-streaming the memory through two
        matmuls per token."""
        cfg = self.sharding
        head_dim = self.d_model // self.n_heads
        first_call = not self.has_variable("cache", "cross_k")

        if first_call:
            kv = dense(features=(self.n_heads, 2 * head_dim), name="cross_kv")(
                memory
            )
            k_new, v_new = jnp.split(kv, 2, axis=-1)
        else:
            # Decode steps never touch the cross_kv weights (that is the
            # point of the static cache); apply() reads params lazily, so
            # the unused entries in the provided tree are harmless.
            k_new = v_new = None
        ck = self.variable("cache", "cross_k", lambda: k_new)
        cv = self.variable("cache", "cross_v", lambda: v_new)
        k, v = ck.value, cv.value

        scale = head_dim ** -0.5
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
        ) * scale
        s = jnp.where(mem_valid.astype(bool)[:, None, None, :], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        return out.astype(q.dtype)


class Encoder(nn.Module):
    vocab_size: int
    d_model: int
    n_heads: int
    n_layers: int
    dropout: float
    compute_dtype: jnp.dtype
    sharding: ShardingConfig
    pad_id: int

    @nn.compact
    def __call__(self, src, train: bool = False):
        cfg = self.sharding
        b, s = src.shape
        src_valid = (src != self.pad_id).astype(jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x = nn.Embed(
            self.vocab_size, self.d_model, dtype=self.compute_dtype,
            name="embed",
        )(src)
        x = cfg.constrain(x, P(BATCH_AXES, SEQ_AXIS, None))
        for i in range(self.n_layers):
            x = EncoderBlock(
                self.d_model, self.n_heads, self.dropout, self.compute_dtype,
                cfg, name=f"Block_{i}",
            )(x, positions, src_valid, train)
        x = nn.LayerNorm(dtype=self.compute_dtype, use_bias=False)(x)
        return x, src_valid


class Decoder(nn.Module):
    vocab_size: int
    d_model: int
    n_heads: int
    n_layers: int
    dropout: float
    compute_dtype: jnp.dtype
    sharding: ShardingConfig
    logits_dtype: jnp.dtype
    decode: bool = False
    max_decode_len: int = 0

    @nn.compact
    def __call__(self, tgt, memory, mem_valid, train: bool = False):
        cfg = self.sharding
        b, t = tgt.shape
        decode_index = None
        if self.decode:
            idx_var = self.variable(
                "cache", "index", lambda: jnp.zeros((), jnp.int32)
            )
            decode_index = idx_var.value
            positions = decode_index + jnp.broadcast_to(
                jnp.arange(t, dtype=jnp.int32), (b, t)
            )
            idx_var.value = decode_index + t
        else:
            positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        x = nn.Embed(
            self.vocab_size, self.d_model, dtype=self.compute_dtype,
            name="embed",
        )(tgt)
        x = cfg.constrain(x, P(BATCH_AXES, SEQ_AXIS, None))
        for i in range(self.n_layers):
            x = DecoderBlock(
                self.d_model, self.n_heads, self.dropout, self.compute_dtype,
                cfg, decode=self.decode, max_decode_len=self.max_decode_len,
                name=f"Block_{i}",
            )(x, positions, memory, mem_valid, train, decode_index)
        x = nn.LayerNorm(dtype=self.compute_dtype, use_bias=False)(x)
        logits = nn.DenseGeneral(
            features=self.vocab_size, dtype=self.compute_dtype,
            use_bias=False, name="lm_head",
        )(x)
        return logits.astype(self.logits_dtype)


class Seq2SeqTransformer(nn.Module):
    """Sequence-to-sequence transduction: ``{'src': [B,S], 'tgt': [B,T]} ->
    [B, T, vocab]`` teacher-forced logits.

    The training batch is a dict so the model plugs into `Trainer`
    unchanged (`shard_batch` tree-maps over pytree inputs): ``tgt`` is the
    decoder INPUT (BOS-prefixed, one position ahead of the labels); the
    caller supplies the shifted labels as ``y``. Source and target share
    one vocabulary id space but have separate embedding tables (the src/tgt
    distributional asymmetry of translation-style tasks).
    """

    vocab_size: int = 256
    d_model: int = 256
    n_heads: int = 8
    n_enc_layers: int = 4
    n_dec_layers: int = 4
    dropout: float = 0.1
    compute_dtype: jnp.dtype = jnp.float32
    sharding: ShardingConfig = ShardingConfig()
    logits_dtype: jnp.dtype = jnp.float32
    pad_id: int = 0
    decode: bool = False
    max_decode_len: int = 0

    def setup(self):
        cfg = self.sharding
        if cfg.seq_parallel and self.decode:
            # Training/eval run sequence-parallel (ring attention across
            # all three call sites); autoregressive DECODE does not — a
            # single-token step has no sequence to shard. Refuse loudly
            # rather than silently replicate (the house convention).
            raise ValueError(
                "seq2seq decode mode does not compose with a live 'seq' "
                "axis — generate on a mesh without sequence parallelism"
            )
        self.encoder = Encoder(
            self.vocab_size, self.d_model, self.n_heads, self.n_enc_layers,
            self.dropout, self.compute_dtype, self.sharding, self.pad_id,
        )
        self.decoder = Decoder(
            self.vocab_size, self.d_model, self.n_heads, self.n_dec_layers,
            self.dropout, self.compute_dtype, self.sharding,
            self.logits_dtype, decode=self.decode,
            max_decode_len=self.max_decode_len,
        )

    def __call__(self, batch, train: bool = False):
        memory, src_valid = self.encoder(batch["src"], train)
        return self.decoder(batch["tgt"], memory, src_valid, train)

    def encode(self, src, train: bool = False):
        return self.encoder(src, train)

    def decode_tokens(self, tgt, memory, src_valid, train: bool = False):
        return self.decoder(tgt, memory, src_valid, train)


def param_specs(params, mesh):
    """Megatron TP (+FSDP) PartitionSpecs for the seq2seq layout — the
    decoder-only LM's name-keyed rules plus the cross-attention
    projections (column-parallel q/kv, row-parallel output)."""
    from horovod_tpu.models import transformer as tlib

    return tlib.param_specs(
        params, mesh,
        extra_tp_dim={
            "cross_q": 1,    # [dm, H, hd]    — heads (column-parallel)
            "cross_kv": 1,   # [dm, H, 2·hd]  — heads (column-parallel)
            "cross_out": 0,  # [H, hd, dm]    — heads (row-parallel)
        },
    )


def make_seq2seq_generate_fn(model: Seq2SeqTransformer, *,
                             max_new_tokens: int, bos_id: int,
                             temperature: float = 0.0, top_k: int = 0,
                             top_p: float = 0.0, eos_id: int | None = None):
    """Build the compiled seq2seq generator: ``(params, src, rng) ->
    tokens [B, max_new_tokens]``.

    Encode + BOS prefill + the whole decode `lax.scan` in ONE jitted
    program (the `models/decoding.py` single-dispatch discipline). After a
    row emits ``eos_id`` its remaining positions fill with it.
    """
    from horovod_tpu.models.decoding import _sample

    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")

    def run(params, src, rng):
        src = src.astype(jnp.int32)
        b = src.shape[0]
        dmodel = model.clone(
            decode=True, max_decode_len=max_new_tokens, dropout=0.0
        )
        memory, src_valid = dmodel.apply(
            {"params": params}, src, method=Seq2SeqTransformer.encode
        )
        bos = jnp.full((b, 1), bos_id, jnp.int32)
        logits, vars_ = dmodel.apply(
            {"params": params}, bos, memory, src_valid,
            method=Seq2SeqTransformer.decode_tokens, mutable=["cache"],
        )
        rng, sub = jax.random.split(rng)
        tok = _sample(logits[:, -1], sub, temperature, top_k, top_p)
        done = jnp.zeros((b,), bool) if eos_id is None else tok == eos_id
        fill = jnp.int32(0 if eos_id is None else eos_id)

        def body(carry, _):
            cache, tok, rng, done = carry
            step_logits, step_vars = dmodel.apply(
                {"params": params, "cache": cache}, tok[:, None], memory,
                src_valid, method=Seq2SeqTransformer.decode_tokens,
                mutable=["cache"],
            )
            rng, sub = jax.random.split(rng)
            nxt = _sample(step_logits[:, -1], sub, temperature, top_k, top_p)
            nxt = jnp.where(done, fill, nxt)
            new_done = done if eos_id is None else done | (nxt == eos_id)
            return (step_vars["cache"], nxt, rng, new_done), nxt

        (_, _, _, _), rest = lax.scan(
            body, (vars_["cache"], tok, rng, done), None,
            length=max_new_tokens - 1,
        )
        return jnp.concatenate([tok[:, None], jnp.moveaxis(rest, 0, 1)], axis=1)

    return jax.jit(run)
