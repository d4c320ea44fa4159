"""The language model's forward pass and loss in plain float32 `jax.numpy`:
no kernel, no fused head, no lower-precision compute, matrix
multiplications at precision "highest" (on a TPU a float32 matmul runs in
bf16 passes otherwise). One sequence at a time, one head at a time, so the
[T, T] scores of a 4k sequence stay small beside the training state.

It follows the block as the repository builds it (`models/transformer.py`):
pre-LN LayerNorm with a scale and no bias (eps 1e-6), fused qkv or separate
q / kv projections without bias, rotary positions (base 10000) on q and k,
causal attention inside an optional window, GELU (tanh form) MLP of 4x
width, a final LayerNorm and an untied head. Where that differs from a
published model, the configuration's file lists it under ``departures``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-6
ROPE_BASE = 10000.0


def _layer_norm(x, scale):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) * scale


def _rope(x):
    """Rotary embedding on [T, H, D]: the halves (x1, x2) of each head
    rotate by position · base^(-i/half)."""
    t, _, d = x.shape
    half = d // 2
    freqs = ROPE_BASE ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _attention(q, k, v, window):
    """[T, H, D] each -> [T, H, D]; query i sees keys j with j <= i and
    i - j < window."""
    t, _, d = q.shape
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen &= (i - j) < window

    def one_head(qkv):
        qh, kh, vh = qkv  # [T, D]
        scores = jnp.where(seen, qh @ kh.T / jnp.sqrt(float(d)), -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ vh

    heads_first = [a.transpose(1, 0, 2) for a in (q, k, v)]
    return jax.lax.map(one_head, tuple(heads_first)).transpose(1, 0, 2)


def per_token_loss(params, tokens, labels, *, n_layers: int,
                   window: int | None = None):
    """Cross-entropy of each position of ONE sequence (``tokens`` and
    ``labels`` are [T]) under ``params``, the `TransformerLM` parameter
    tree. Returns float32 [T]."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = p["Embed_0"]["embedding"][tokens]  # [T, d]
        for n in range(n_layers):
            b = p[f"Block_{n}"]
            h = _layer_norm(x, b["LayerNorm_0"]["scale"])
            if "qkv" in b:
                qkv = jnp.einsum("td,dhe->the", h, b["qkv"]["kernel"])
                q, k, v = jnp.split(qkv, 3, axis=-1)
            else:
                q = jnp.einsum("td,dhe->the", h, b["q_proj"]["kernel"])
                kv = jnp.einsum("td,dhe->the", h, b["kv_proj"]["kernel"])
                k, v = jnp.split(kv, 2, axis=-1)
                group = q.shape[1] // k.shape[1]
                # query head i reads K/V head i // group
                k = jnp.repeat(k, group, axis=1)
                v = jnp.repeat(v, group, axis=1)
            out = _attention(_rope(q), _rope(k), v, window)
            x = x + jnp.einsum("the,hed->td", out, b["attn_out"]["kernel"])
            h = _layer_norm(x, b["LayerNorm_1"]["scale"])
            h = _gelu_tanh(h @ b["mlp_up"]["kernel"])
            x = x + h @ b["mlp_down"]["kernel"]
        x = _layer_norm(x, p["LayerNorm_0"]["scale"])
        logits = x @ p["lm_head"]["kernel"]  # [T, V]
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked


def compare(model_loss, reference_loss) -> dict:
    """How far the system's per-token losses are from the reference's:
    the difference of the two mean losses (``bias``), the mean absolute
    difference per token, and the RMS difference over the spread of the
    reference's own per-token losses (noise that does not track the
    reference shows there)."""
    diff = jnp.asarray(model_loss, jnp.float32) - reference_loss
    return {
        "model_mean_loss": float(jnp.mean(model_loss)),
        "reference_mean_loss": float(jnp.mean(reference_loss)),
        "bias": float(jnp.abs(jnp.mean(diff))),
        "mean_abs_diff": float(jnp.mean(jnp.abs(diff))),
        "rel_rms": float(
            jnp.sqrt(jnp.mean(diff ** 2)) / jnp.std(reference_loss)),
    }
