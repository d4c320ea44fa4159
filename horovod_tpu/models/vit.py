"""Vision Transformer for CIFAR/MNIST-scale images (Dosovitskiy et al.,
arXiv:2010.11929) — the TPU-first vision family.

The CIFAR-scale conv models are *shape-bound*: a 16-channel 3×3 conv
fills 16 of the MXU's 128 lanes and no amount of batch fixes it (a fact
of the shapes; no benchmark cell runs a conv model, so no utilisation is
stated here). The TPU-first answer is an architecture whose image compute
IS matmuls at MXU-friendly widths: patchify (one reshape + one Dense),
then d_model-wide transformer encoder blocks. Same Trainer / optimizer /
callback path as the CNNs (the capability the reference exercises,
tensorflow2_keras_mnist.py:43-52 — model architecture is a swappable leaf
of the framework, not part of it).

Design notes:
* patchify = reshape to [B, T, p·p·C] + Dense — no convs anywhere; the
  embedding, attention and MLP are all ≥ d_model-wide matmuls.
* bidirectional (non-causal) dense attention: at CIFAR scale T = (32/p)²
  is 64 patches — the [T, T] score matrix is tiny, so the dense path is
  the right kernel (the flash kernel exists for long sequences, not this).
* learned position embeddings (images are not translation-invariant at
  patch granularity), mean-pool head by default ('cls' token optional).
* bf16 compute / f32 params + logits, like every other model here.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from horovod_tpu.ops.attention import dense_attention


class EncoderBlock(nn.Module):
    d_model: int
    n_heads: int
    mlp_ratio: int = 4
    dropout: float = 0.0
    compute_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        head_dim = self.d_model // self.n_heads
        dense = lambda feat, name: nn.DenseGeneral(  # noqa: E731
            feat, dtype=self.compute_dtype, use_bias=True, name=name
        )
        h = nn.LayerNorm(dtype=self.compute_dtype)(x)
        qkv = dense((self.n_heads, 3 * head_dim), "qkv")(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        att = dense_attention(q, k, v, causal=False)  # [B, T, H, hd]
        out = nn.DenseGeneral(
            self.d_model, axis=(-2, -1), dtype=self.compute_dtype,
            name="attn_out",
        )(att)
        out = nn.Dropout(self.dropout, deterministic=not train)(out)
        x = x + out
        h = nn.LayerNorm(dtype=self.compute_dtype)(x)
        h = dense(self.mlp_ratio * self.d_model, "mlp_up")(h)
        h = nn.gelu(h)
        h = dense(self.d_model, "mlp_down")(h)
        h = nn.Dropout(self.dropout, deterministic=not train)(h)
        return x + h


class ViT(nn.Module):
    """[B, H, W, C] images (float, or uint8 normalized on device) →
    [B, num_classes] float32 logits."""

    patch_size: int = 4
    d_model: int = 256
    n_heads: int = 8
    n_layers: int = 8
    mlp_ratio: int = 4
    num_classes: int = 10
    dropout: float = 0.0
    pool: str = "mean"  # 'mean' = GAP head (CIFAR-ResNet style), or 'cls'
    compute_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        if self.pool not in ("mean", "cls"):
            raise ValueError(f"pool must be 'mean' or 'cls', got {self.pool!r}")
        b, h, w, c = x.shape
        p = self.patch_size
        if h % p or w % p:
            raise ValueError(
                f"image {h}x{w} not divisible by patch_size {p}"
            )
        if jnp.issubdtype(x.dtype, jnp.integer):
            # Raw uint8 pixels → on-device /255 (see MnistCNN note: 4x less
            # host->device traffic, identical numerics to host normalize).
            x = x.astype(jnp.float32) / 255.0
        x = x.astype(self.compute_dtype)
        # Patchify as pure data movement + one matmul: [B, h/p, p, w/p, p, C]
        # → [B, T, p·p·C] → Dense(d_model).
        x = x.reshape(b, h // p, p, w // p, p, c)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, (h // p) * (w // p), -1)
        x = nn.Dense(self.d_model, dtype=self.compute_dtype, name="embed")(x)
        t = x.shape[1]
        if self.pool == "cls":
            cls = self.param(
                "cls", nn.initializers.zeros, (1, 1, self.d_model), jnp.float32
            )
            x = jnp.concatenate(
                [jnp.broadcast_to(cls, (b, 1, self.d_model)).astype(x.dtype), x],
                axis=1,
            )
            t += 1
        pos = self.param(
            "pos_embed", nn.initializers.normal(0.02), (1, t, self.d_model),
            jnp.float32,
        )
        x = x + pos.astype(x.dtype)
        x = nn.Dropout(self.dropout, deterministic=not train)(x)
        for i in range(self.n_layers):
            x = EncoderBlock(
                self.d_model, self.n_heads, self.mlp_ratio, self.dropout,
                self.compute_dtype, name=f"Block_{i}",
            )(x, train=train)
        x = nn.LayerNorm(dtype=self.compute_dtype)(x)
        x = x[:, 0] if self.pool == "cls" else x.mean(axis=1)
        x = nn.Dense(self.num_classes, dtype=self.compute_dtype, name="head")(x)
        return x.astype(jnp.float32)
