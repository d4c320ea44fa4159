"""A DeepSeek-V3-style causal LM: latent attention and routed experts.

What `TransformerLM`'s block cannot be (its choices are fixed: LayerNorm, a
GELU MLP at 4x, one head size, softmax-gated experts that drop): pre-norm
RMSNorm layers of multi-head LATENT attention (MLA, arXiv:2405.04434) and a
SwiGLU MLP that is dense in the leading layers and a routed expert layer
with a shared expert in the rest (`models/moe.py` `RoutedExperts`); a final
RMSNorm and an untied head. No biases anywhere. One chip's share of a
deployment is a parameter of the model: how many of the routed experts are
held here and from which index, and the rows of the vocabulary (which are
simply ``vocab_size``: a sliced vocabulary is a smaller one).

Layer, for a token's vector h::

    x += MLA(RMSNorm(x));  x += FFN_l(RMSNorm(x))
    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w
    MLA: q = W_q h            -> H x (nope | rope)
         W_kva h              -> (c: kv_rank | k_rope, one for all heads)
         W_kvb RMSNorm(c)     -> H x (k_nope | v)
         rotary at rope_base on q_rope and k_rope, ADJACENT pairs
         (x[2i], x[2i+1]) rotated by position * base^(-2i / rope_dim)
         (`rope_interleave`; output in the same places)
         k = k_nope | k_rope;  softmax(q k^T / sqrt(nope + rope)) v -> W_o

The attention kernel is the repository's flash kernel with q and k wider
than v (ops/flash_attention.py); k is materialised at full width, with
k_rope repeated over the heads.

The model keeps the `Trainer(loss='module')` contract of `TransformerLM`:
``apply(tokens, train=, labels=)`` returns per-token ``(loss, correct)``
from the chunked head + CE (`LMHead.fused_loss`), without labels the
logits. It has no decode path (a compressed latent cache is ROADMAP R2)
and `PipelinedLM` does not know its layers (ROADMAP D1); both refuse by
name. It runs on one chip: a mesh of more is refused by name (ROADMAP R1).
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from horovod_tpu.models.moe import RoutedExperts, SwiGLU
from horovod_tpu.models.transformer import BATCH_AXES, LMHead, ShardingConfig
from horovod_tpu.ops.flash_attention import flash_attention

# The latent projections, the latent's norm and the rotary around the
# kernel, by name in the compiled step (forward and backward ops carry it;
# chipbench/moe_spans.py `mla_proj_ms_per_step`). The kernel's own events
# are found by the kernel's names.
MLA_SCOPE = "hvt.mla"


def rope_adjacent_pairs(x, positions, base: float):
    """Rotary embedding on ``[B, T, H, D]``: the pair ``(x[2i], x[2i+1])``
    turns by ``position * base^(-2i / D)`` and stays where it was."""
    d = x.shape[-1]
    freqs = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[:, :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack(
        [even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return turned.reshape(x.shape).astype(x.dtype)


class LatentAttention(nn.Module):
    """Multi-head latent attention without a query rank: K and V come from
    one ``kv_rank``-wide latent per token, and one rotary key per token is
    shared by all heads."""

    n_heads: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    kv_rank: int
    rope_base: float
    eps: float
    compute_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, positions):
        cd = self.compute_dtype
        heads, nope, rope = self.n_heads, self.qk_nope_dim, self.qk_rope_dim
        dense = functools.partial(nn.DenseGeneral, use_bias=False, dtype=cd)
        with jax.named_scope(MLA_SCOPE):
            q = dense((heads, nope + rope), name="q_proj")(x)
            kv_a = dense(self.kv_rank + rope, name="kv_a")(x)
            latent, k_rope = kv_a[..., :self.kv_rank], kv_a[..., self.kv_rank:]
            latent = nn.RMSNorm(epsilon=self.eps, dtype=cd, name="kv_norm")(
                latent)
            kv = dense((heads, nope + self.v_dim), name="kv_b")(latent)
            k_nope, v = kv[..., :nope], kv[..., nope:]
            q_rope = rope_adjacent_pairs(q[..., nope:], positions,
                                         self.rope_base)
            k_rope = rope_adjacent_pairs(k_rope[:, :, None, :], positions,
                                         self.rope_base)
            q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, (*k_nope.shape[:-1], rope))],
                axis=-1)
        out = flash_attention(q, k, v, causal=True)  # [B, T, H, v_dim]
        with jax.named_scope(MLA_SCOPE):
            return dense(x.shape[-1], axis=(-2, -1), name="o_proj")(out)


class LatentMoEBlock(nn.Module):
    """``x += attn(norm(x)); x += mlp(norm(x))`` with both given (unbound:
    they are adopted here under the names ``attn`` and ``mlp``)."""

    attn: nn.Module
    mlp: nn.Module
    eps: float
    compute_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, positions):
        norm = functools.partial(
            nn.RMSNorm, epsilon=self.eps, dtype=self.compute_dtype)
        x = x + self.attn(norm(name="attn_norm")(x), positions)
        return x + self.mlp(norm(name="mlp_norm")(x))


class LatentMoELM(nn.Module):
    """Causal LM over integer tokens, ``[B, T] -> [B, T, vocab]`` logits or,
    with ``labels``, per-token ``(loss, correct)``. Sizes carry the names
    of the DeepSeek-V3 `config.json` they come from."""

    vocab_size: int
    d_model: int
    n_layers: int
    n_dense_layers: int   # leading layers with the dense MLP
    dense_width: int
    n_heads: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    kv_rank: int
    n_routed: int         # the router's width
    experts_per_token: int
    expert_width: int
    shared_width: int     # n_shared_experts x expert_width, one SwiGLU
    routed_scaling: float
    n_held: int           # the routed experts held here, a block ...
    held_start: int       # ... from this index
    rope_base: float
    eps: float = 1e-6
    compute_dtype: jnp.dtype = jnp.float32
    sharding: ShardingConfig = ShardingConfig()
    fused_head_chunks: int = 0

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, labels=None):
        del train  # no dropout, and the routed layer sows in every mode
        cfg, cd = self.sharding, self.compute_dtype
        if cfg.mesh is not None and cfg.mesh.size > 1:
            raise NotImplementedError(
                f"LatentMoELM on a mesh of {cfg.mesh.size} chips "
                f"({dict(cfg.mesh.shape)}): its layers run on one chip "
                "(ROADMAP R1, R2)")
        b, t = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        x = nn.Embed(self.vocab_size, self.d_model, dtype=cd, name="embed")(
            tokens)
        x = cfg.constrain(x, P(BATCH_AXES, None, None))
        for i in range(self.n_layers):
            attn = LatentAttention(
                self.n_heads, self.qk_nope_dim, self.qk_rope_dim, self.v_dim,
                self.kv_rank, self.rope_base, self.eps, cd, parent=None)
            if i < self.n_dense_layers:
                mlp = SwiGLU(self.dense_width, cd, parent=None)
            else:
                mlp = RoutedExperts(
                    n_routed=self.n_routed, k=self.experts_per_token,
                    expert_width=self.expert_width,
                    shared_width=self.shared_width, n_held=self.n_held,
                    held_start=self.held_start,
                    routed_scaling=self.routed_scaling, compute_dtype=cd,
                    sharding=cfg, parent=None)
            x = LatentMoEBlock(attn, mlp, self.eps, cd, name=f"Block_{i}")(
                x, positions)
            x = cfg.constrain(x, P(BATCH_AXES, None, None))
        x = nn.RMSNorm(epsilon=self.eps, dtype=cd, name="final_norm")(x)
        head = LMHead(
            self.d_model, self.vocab_size, compute_dtype=cd, sharding=cfg,
            name="lm_head")
        if labels is not None:
            return head.fused_loss(x, labels, self.fused_head_chunks)
        return head(x)
