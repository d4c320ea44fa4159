"""The family ``dense_lm``: the repository's `TransformerLM` (models/
transformer.py) at a configuration's published widths, its plain float32
reference, and its counts of operations and bytes.

A configuration of this family names, under ``maps_to``, which of its
published keys is which size of the LM. The block is pre-LN LayerNorm with a
scale and no bias (eps 1e-6), fused qkv or separate q / kv projections
without bias, rotary positions (base 10000) on q and k over heads of
``d_model / n_heads``, causal attention inside an optional window, a GELU
(tanh form) MLP of 4x width, a final LayerNorm and an untied head. Where that
differs from a published model, the configuration's file lists it under
``departures``.

Two counts are kept apart. *Required* is what the forward and backward
passes need and is what model-FLOP/s utilisation divides by: 6 FLOPs per
matmul parameter per token, 6 attention dots, nothing recomputed.
*Executed* is what a kernel really runs (the flash backward recomputes the
scores twice: 9 dots; the chunked CE head recomputes its logits:
8·N·D·V) and is what that kernel's roofline share divides by.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import flops
from horovod_tpu.models.transformer import ShardingConfig, TransformerLM

EPS = 1e-6
ROPE_BASE = 10000.0


# --- sizes and the program's own model ---------------------------------------

def sizes(config: dict) -> dict:
    """The configuration's published keys under the names the repository's
    LM takes, through the file's own ``maps_to``."""
    def get(key):
        source = config["maps_to"].get(key)
        return None if source is None else config[source]

    out = {key: get(key) for key in (
        "vocab_size", "d_model", "n_heads", "n_kv_heads", "n_layers",
        "d_ff", "window", "max_positions")}
    if out["n_kv_heads"] == out["n_heads"]:
        out["n_kv_heads"] = None
    if out["d_ff"] != 4 * out["d_model"]:
        raise ValueError(
            "the repository's block has an MLP of 4x width; this "
            f"configuration asks for {out['d_ff']} at d_model "
            f"{out['d_model']}, and no width is ever changed")
    out["attention_layers"] = out["n_layers"]
    return out


def build(config: dict, trainer_spec: dict, mesh):
    s = sizes(config)
    return TransformerLM(
        vocab_size=s["vocab_size"], d_model=s["d_model"],
        n_heads=s["n_heads"], n_kv_heads=s["n_kv_heads"],
        window=s["window"], n_layers=s["n_layers"], dropout=0.0,
        compute_dtype=jnp.dtype(trainer_spec["compute_dtype"]),
        fused_head_chunks=trainer_spec["fused_head_chunks"],
        sharding=ShardingConfig(mesh=mesh),
    )


# --- the plain reference -----------------------------------------------------
# No kernel, no fused head, no lower-precision compute, matrix
# multiplications at precision "highest" (on a TPU a float32 matmul runs in
# bf16 passes otherwise). One sequence at a time, one head at a time, so the
# [T, T] scores of a 4k sequence stay small beside the training state.

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


def per_token_loss(params, tokens, labels, config: dict):
    """Cross-entropy of each position of ONE sequence (``tokens`` and
    ``labels`` are [T]) under ``params``, the `TransformerLM` parameter
    tree. Returns float32 [T]."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = p["Embed_0"]["embedding"][tokens]  # [T, d]
        for n in range(s["n_layers"]):
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
            out = _attention(_rope(q), _rope(k), v, s["window"])
            x = x + jnp.einsum("the,hed->td", out, b["attn_out"]["kernel"])
            h = _layer_norm(x, b["LayerNorm_1"]["scale"])
            h = _gelu_tanh(h @ b["mlp_up"]["kernel"])
            x = x + h @ b["mlp_down"]["kernel"]
        x = _layer_norm(x, p["LayerNorm_0"]["scale"])
        logits = x @ p["lm_head"]["kernel"]  # [T, V]
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked


# --- counts from shapes ------------------------------------------------------

def matmul_params(config: dict) -> int:
    """Parameters that multiply activations once per token: the blocks'
    projections and MLP and the LM head. The embedding table is a gather
    and the LayerNorm scales are elementwise: neither counts."""
    s = sizes(config)
    d, h = s["d_model"], s["n_heads"]
    head_dim = d // h
    h_kv = s["n_kv_heads"] or h
    attn = d * h * head_dim + 2 * d * h_kv * head_dim + h * head_dim * d
    mlp = 2 * d * s["d_ff"]
    return s["n_layers"] * (attn + mlp) + d * s["vocab_size"]


def attention_flops_per_sequence(config: dict, seq_len: int, dots: int) -> int:
    """``dots`` block matmuls of 2·pairs·head_dim FLOPs per head, in every
    layer (this block's heads together are ``d_model`` wide; K/V heads are
    repeated up to the query heads for training, so GQA changes nothing
    here)."""
    s = sizes(config)
    pairs = flops.visible_pairs(seq_len, s["window"])
    return dots * 2 * pairs * s["d_model"] * s["n_layers"]


def required_flops_per_token(config: dict, seq_len: int) -> float:
    """What forward and backward require for one token: 6 per matmul
    parameter and 6 attention dots (scores, P·V; dP, dV, dQ, dK)."""
    attn = attention_flops_per_sequence(config, seq_len, dots=6) / seq_len
    return 6.0 * matmul_params(config) + attn


def head_flops_per_step(config: dict, tokens: int, *, executed: bool) -> float:
    """The LM head with its cross-entropy over ``tokens`` rows: logits,
    dh and dW are required (6·N·D·V); the chunked head recomputes the
    logits in its backward pass (8·N·D·V executed)."""
    s = sizes(config)
    per = 8.0 if executed else 6.0
    return per * tokens * s["d_model"] * s["vocab_size"]


def kernel_work(config: dict, seq_len: int, per_chip_batch: int) -> dict:
    """{kernel family: (executed FLOPs, least HBM bytes, calls)} of one
    training step on one chip. The flash kernels run once a layer each:
    forward 2 dots over the visible pairs, the dQ pass 3 (scores again,
    dP, dQ), the dK/dV pass 4 (scores again, dV, dP, dK), counted to the
    element, so the masked part of a diagonal tile is not credited to the
    kernel. Each pass moves every [B, T, H, D] (H·D = ``d_model``) bf16
    array it touches once: forward reads q, k, v and writes o (4); dQ reads
    q, k, v, o, do and writes dq (6); dK/dV reads the same and writes dk,
    dv (7); the per-row statistics are 1/D of one array and are left out.
    ``flash`` is the three together (9 dots, 17 arrays)."""
    s = sizes(config)
    layers = s["n_layers"]
    dot = float(per_chip_batch
                * attention_flops_per_sequence(config, seq_len, dots=1))
    array = float(per_chip_batch * seq_len * s["d_model"] * 2 * layers)
    return {
        "flash": (9 * dot, 17 * array, 3 * layers),
        "flash_fwd": (2 * dot, 4 * array, layers),
        "flash_dq": (3 * dot, 6 * array, layers),
        "flash_dkv": (4 * dot, 7 * array, layers),
    }
