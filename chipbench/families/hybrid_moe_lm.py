"""The family ``hybrid_moe_lm``: the repository's `HybridMoELM` (models/
hybrid_moe_lm.py) at a Solar-Open2-style configuration's published widths,
its plain float32 reference, and its counts of operations and bytes.

A configuration of this family carries the keys of a ``solar_open2``
`config.json` under their own names. The model: pre-norm RMSNorm layers of
a token mixer and a routed expert layer; layer i is a softmax layer where
``gqa_layers`` lists it (every ``gqa_interval + 1``-th) and a KDA layer
(Kimi Delta Attention, arXiv:2510.26692; ``linear_attn_config``)
otherwise; no positions (``use_rope`` false); a final RMSNorm and an
untied head; no biases. The layer equations are in the reference's
docstrings below.

**The chip's share.** ``num_attention_heads`` and
``linear_attn_config.num_heads`` are the heads HELD here (the block from
``held_heads_start``, of ``published_heads``), ``num_key_value_heads`` the
K/V heads those read, ``n_routed_experts`` the experts held (from
``held_experts_start``; the router keeps ``n_router_experts`` and its
experts per token), ``vocab_size`` the rows held. Program and reference
both return the held heads' rows of W_o times their outputs and the held
experts' part of the routed sum, and pass those partial sums on.

Two counts are kept apart, as in ``dense_lm``: *required* (what forward
and backward need, nothing recomputed; `mfu` divides by it) and *executed*
(what a kernel runs; its roofline share divides by it).

``LIMITS`` / ``FAR_OFF`` (how `reference.compare`'s report decides
``correct`` in this family's cells) are at the end, each with the on-chip
readings that set it.
"""

from __future__ import annotations

import pathlib

import jax
import jax.numpy as jnp

from chipbench import flops, run

_LATENT = run.load_module(
    pathlib.Path(__file__).with_name("latent_moe_lm.py"))
LINEAR, SOFTMAX = "linear", "softmax"
# Rows of queries whose scores the reference holds at once.
ROW_BLOCK = 1024


# --- sizes and the program's own model ---------------------------------------

def layer_kinds(config: dict) -> tuple:
    """The kind of each layer that is run: softmax where ``gqa_layers``
    (kept as published) names it."""
    return tuple(SOFTMAX if i in config["gqa_layers"] else LINEAR
                 for i in range(config["num_hidden_layers"]))


def sizes(config: dict) -> dict:
    """What the harness needs, and a refusal by name of what the program's
    `HybridMoELM` cannot build."""
    fixed = {
        "use_rope": False, "use_gqa_gate": True, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True, "first_k_dense_replace": 0,
        "norm_topk_prob": True, "tie_word_embeddings": False,
    }
    for key, only in fixed.items():
        if config.get(key) != only:
            raise ValueError(
                f"the repository's HybridMoELM builds {key} = {only!r} only; "
                f"this configuration says {config.get(key)!r}")
    linear = config["linear_attn_config"]
    if linear["num_kv_heads"] is not None:
        raise ValueError("a KDA layer has one key and value head per query "
                         "head; linear_attn_config.num_kv_heads is not null")
    if linear["head_dim"] != config["head_dim"]:
        raise ValueError("the two mixers' head sizes differ; HybridMoELM "
                         "has one head_dim")
    period = config["gqa_interval"] + 1
    if any((i % period == 0) != (i in config["gqa_layers"])
           for i in range(config["num_hidden_layers"])):
        raise ValueError(
            f"gqa_layers is not every {period}th layer from 0 (gqa_interval "
            f"{config['gqa_interval']})")
    held = config["num_attention_heads"]
    if linear["num_heads"] != held:
        raise ValueError("the two mixers hold different numbers of heads; "
                         "HybridMoELM holds one block of heads in both")
    published = config["published_heads"]
    for what, total in published.items():
        if not 0 <= config["held_heads_start"] <= total - held:
            raise ValueError(
                f"heads {config['held_heads_start']}.. + {held} are not a "
                f"block of the {total} {what} heads")
    group = published["softmax"] // published["softmax_kv"]
    if held % group or config["num_key_value_heads"] != held // group:
        raise ValueError(
            f"{held} query heads in groups of {group} do not read "
            f"{config['num_key_value_heads']} K/V heads")
    experts, start = config["n_routed_experts"], config["held_experts_start"]
    if not 0 <= start <= config["n_router_experts"] - experts:
        raise ValueError(
            f"experts {start}..{start + experts} are not a block of the "
            f"router's {config['n_router_experts']}")
    kinds = layer_kinds(config)
    return {
        "vocab_size": config["vocab_size"],
        "max_positions": config["max_position_embeddings"],
        "attention_layers": kinds.count(SOFTMAX),
        "linear_layers": kinds.count(LINEAR),
        "expert_layers": len(kinds),
    }


def build(config: dict, trainer_spec: dict, mesh):
    from horovod_tpu.models.hybrid_moe_lm import HybridMoELM
    from horovod_tpu.models.transformer import ShardingConfig

    sizes(config)
    linear, published = config["linear_attn_config"], config["published_heads"]
    return HybridMoELM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        layer_kinds=layer_kinds(config), head_dim=config["head_dim"],
        linear_heads=published["linear"],
        softmax_heads=published["softmax"],
        softmax_kv_heads=published["softmax_kv"],
        n_held_heads=config["num_attention_heads"],
        held_heads_start=config["held_heads_start"],
        conv_size=linear["short_conv_kernel_size"],
        low_rank=config["kda_low_rank"], kda_chunk=config["kda_chunk"],
        n_routed=config["n_router_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_width=(config["n_shared_experts"]
                      * config["moe_intermediate_size"]),
        routed_scaling=float(config["routed_scaling_factor"]),
        n_held=config["n_routed_experts"],
        held_start=config["held_experts_start"],
        eps=config["rms_norm_eps"],
        compute_dtype=jnp.dtype(trainer_spec["compute_dtype"]),
        fused_head_chunks=trainer_spec["fused_head_chunks"],
        sharding=ShardingConfig(mesh=mesh),
    )


# --- the plain reference -----------------------------------------------------
# Plain `jax.numpy`, float32, matrix multiplications at precision "highest",
# nothing of the program: no kernel, no chunks, no WY form (the recurrence
# runs token by token), no fused head, no sort, no grouped matmul (every
# held expert runs on every token and the gate, zero where the token did
# not choose it, decides). One sequence at a time.

# The routed layer is the one `latent_moe_lm` runs (the program's
# `RoutedExperts` under the same configuration keys), so its reference is
# that family's, stated once: the selection bias solved from the sequence's
# logits, sigmoid gates normalised and scaled, every held expert on every
# token under its gate, the shared expert.
_rms_norm, _expert_layer = _LATENT._rms_norm, _LATENT._expert_layer
expected_routed_rows = _LATENT.expected_routed_rows


def _conv(x, taps):
    """``y_t = sum_j taps[j] x_{t-(K-1)+j}`` for ``x [T, H, D]`` and ``taps
    [K, H, D]``, as K shifted adds; what lies before the sequence is 0."""
    size, t = taps.shape[0], x.shape[0]
    total = jnp.zeros_like(x)
    for j in range(size):
        back = size - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:back]), x[:t - back]], axis=0)
        total = total + taps[j] * shifted
    return total


def _unit(x):
    return x / jnp.sqrt((x ** 2).sum(-1, keepdims=True) + 1e-6)


def _delta_attention(h, p, config, state_dtype):
    """[T, d] -> [T, d]: the held heads of one KDA layer, token by token.
    A head, with S [Dk, Dv] from zero::

        q~, k~, v~ = SiLU(conv(W_q h)), SiLU(conv(W_k h)), SiLU(conv(W_v h))
        q_t = q~_t / |q~_t| * Dk^-1/2;  k_t = k~_t / |k~_t|;  v_t = v~_t
        g_t = -exp(A_log) softplus(W_fb (W_fa h_t) + dt_bias)
        beta_t = 2 sigmoid(w_b . h_t)
        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        y_t = W_o [RMSNorm_head(S_t^T q_t) * sigmoid(W_gb (W_ga h_t))]

    ``state_dtype`` is what S is kept in between tokens (float32; the
    lower-precision control rounds it to bfloat16 after every token)."""
    dim = config["head_dim"]

    def heads(name):
        return jnp.einsum("td,dhe->the", h, p[name]["kernel"])

    q, k, v = (jax.nn.silu(_conv(heads(f"{n}_proj"), p[f"{n}_conv"]))
               for n in "qkv")
    q, k = _unit(q) * dim ** -0.5, _unit(k)
    low = jnp.einsum("tr,rhe->the", h @ p["f_a"]["kernel"], p["f_b"]["kernel"])
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(low + p["dt_bias"])
    beta = 2.0 * jax.nn.sigmoid(h @ p["b_proj"]["kernel"])  # [T, H]
    gate = jax.nn.sigmoid(jnp.einsum(
        "tr,rhe->the", h @ p["g_a"]["kernel"], p["g_b"]["kernel"]))

    def token(state, at):
        q_t, k_t, v_t, g_t, beta_t = at  # [H, .]
        state = state.astype(jnp.float32) * jnp.exp(g_t)[:, :, None]
        seen = jnp.einsum("hc,hcv->hv", k_t, state)
        state = state + (beta_t[:, None] * k_t)[:, :, None] * (
            v_t - seen)[:, None, :]
        return state.astype(state_dtype), jnp.einsum("hc,hcv->hv", q_t, state)

    n_heads = q.shape[1]
    _, out = jax.lax.scan(
        token, jnp.zeros((n_heads, dim, dim), state_dtype),
        (q, k, v, g, beta))
    out = _rms_norm(out, p["o_norm"]["scale"], config["rms_norm_eps"]) * gate
    return jnp.einsum("the,hed->td", out, p["o_proj"])


def _gated_attention(h, p, config):
    """[T, d] -> [T, d]: the held query heads of one softmax layer over the
    K/V heads they read, no positions: ``W_o [softmax(q k^T / sqrt(D)) v *
    sigmoid(W_g h)]``, causal; the scores of `ROW_BLOCK` queries at a
    time."""
    t = h.shape[0]
    q, k, v, gate = (jnp.einsum("td,dhe->the", h, p[f"{n}_proj"]["kernel"])
                     for n in "qkvg")
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    scale = config["head_dim"] ** -0.5
    block = min(ROW_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions are not whole blocks of {block}")

    def rows(start):
        q_rows = jax.lax.dynamic_slice_in_dim(q, start, block)  # [R, H, D]
        seen = (jnp.arange(t)[None, :]
                <= (start + jnp.arange(block))[:, None])[None]
        scores = jnp.einsum("rhe,she->hrs", q_rows, k) * scale
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hrs,she->rhe", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(q.shape)
    return jnp.einsum("the,hed->td", out * jax.nn.sigmoid(gate), p["o_proj"])


def per_token_loss(params, tokens, labels, config: dict, *,
                   state_dtype=jnp.float32):
    """Cross-entropy of each position of ONE sequence (``tokens`` and
    ``labels`` are [T]) under ``params``, the `HybridMoELM` parameter tree.
    Returns float32 [T]."""
    eps = config["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = p["embed"]["embedding"][tokens]  # [T, d]
        for n, kind in enumerate(layer_kinds(config)):
            b = p[f"Block_{n}"]
            h = _rms_norm(x, b["mixer_norm"]["scale"], eps)
            if kind == LINEAR:
                x = x + _delta_attention(h, b["mixer"], config, state_dtype)
            else:
                x = x + _gated_attention(h, b["mixer"], config)
            x = x + _expert_layer(
                _rms_norm(x, b["mlp_norm"]["scale"], eps), b["mlp"], config)
        x = _rms_norm(x, p["final_norm"]["scale"], eps)
        logits = x @ p["lm_head"]["kernel"]  # [T, V held]
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked


# --- counts from shapes ------------------------------------------------------

def matmul_params_per_token(config: dict) -> float:
    """Parameters that multiply one token's activations once: the mixers'
    projections (the convolution's taps, the norms and the decay's
    per-channel constants are elementwise), the router, the shared expert,
    the routed experts at their expectation, the head."""
    d, dim = config["hidden_size"], config["head_dim"]
    held, rank = config["num_attention_heads"], config["kda_low_rank"]
    wide = held * dim
    linear = (4 * d * wide            # q, k, v, o
              + 2 * (d * rank + rank * wide)   # the decay's and the gate's
              + d * held)             # beta
    softmax = (3 * d * wide           # q, gate, o
               + 2 * d * config["num_key_value_heads"] * dim)
    expert = 3 * d * config["moe_intermediate_size"]
    expert_layer = (d * config["n_router_experts"]
                    + config["n_shared_experts"] * expert
                    + expected_routed_rows(config, 1) * expert)
    s = sizes(config)
    return (s["linear_layers"] * linear + s["attention_layers"] * softmax
            + s["expert_layers"] * expert_layer + d * config["vocab_size"])


def attention_dot_flops(config: dict, seq_len: int, dots: int) -> float:
    """``dots`` block matmuls of 2·pairs·head_dim FLOPs a held query head
    each, over one sequence in every softmax layer."""
    return (2.0 * flops.visible_pairs(seq_len, None) * dots
            * config["head_dim"] * config["num_attention_heads"]
            * sizes(config)["attention_layers"])


def scan_required_flops_per_token(config: dict) -> float:
    """What the recurrence itself asks of a token in a held head: k^T S,
    the rank-one update and q^T S, 2·Dk·Dv each (the decay is elementwise),
    forward and twice that backward, in every KDA layer."""
    dim = config["head_dim"]
    return (3 * 3 * 2.0 * dim * dim * config["num_attention_heads"]
            * sizes(config)["linear_layers"])


def required_flops_per_token(config: dict, seq_len: int) -> float:
    """6 per multiplying parameter, the softmax layers' 6 dots and the
    recurrence's products."""
    return (6.0 * matmul_params_per_token(config)
            + attention_dot_flops(config, seq_len, 6) / seq_len
            + scan_required_flops_per_token(config))


def chunked_scan_flops(chunk: int, dk: int, dv: int) -> float:
    """Forward FLOPs of one chunk of one head in the chunked (WY) form: the
    two pair matrices (k k^T and q k^T under their decays, 2·C²·Dk each),
    the triangular solve for W and U (C² a column), W S, (q exp G) S and
    Khat^T U (2·C·Dk·Dv each) and P U (2·C²·Dv)."""
    return (chunk * chunk * (5.0 * dk + 3.0 * dv) + 6.0 * chunk * dk * dv)


def kernel_work(config: dict, seq_len: int, per_chip_batch: int) -> dict:
    """{kernel family: (executed FLOPs, least HBM bytes, calls)} of one
    training step on one chip.

    The flash kernels, once a softmax layer each, at one head size with K
    and V handed to them repeated over the group: forward 2 dots, the dQ
    pass 3, the dK/dV pass 4, counted to the element of the causal
    triangle. Bytes: every [B, T, H, D] bf16 array a pass touches, once
    (forward q, k, v, o; dQ those and dO, dQ; dK/dV those and dO, dK, dV).

    ``expert_gmm``, the routed experts' grouped matmuls: the work REQUIRED
    at the expected rows, as the family ``latent_moe_lm`` counts it.

    ``kda_scan``, the delta rule over a sequence, whatever implements it:
    the chunked form's FLOPs at the chunk the program uses (forward, and
    twice that backward), and the least bytes: q, k, v (bf16), g (float32)
    and beta (float32) read and o written once forward; the same read
    again with dO, and the five gradients written, backward. One forward
    and one backward a KDA layer."""
    s = sizes(config)
    dim, held = config["head_dim"], config["num_attention_heads"]
    softmax = s["attention_layers"]

    def dots(n):
        return per_chip_batch * attention_dot_flops(config, seq_len, n)

    def arrays(n):
        return float(per_chip_batch * seq_len * held * dim * 2 * softmax * n)

    fwd = (dots(2), arrays(4), softmax)
    dq = (dots(3), arrays(6), softmax)
    dkv = (dots(4), arrays(7), softmax)
    work = {
        "flash": tuple(sum(part) for part in zip(fwd, dq, dkv)),
        "flash_fwd": fwd, "flash_dq": dq, "flash_dkv": dkv,
    }
    d, width = config["hidden_size"], config["moe_intermediate_size"]
    rows = expected_routed_rows(config, per_chip_batch * seq_len)
    weights = 2.0 * config["n_routed_experts"] * 3 * d * width
    row_arrays = 2.0 * rows * (  # forward two calls, backward four
        (d + 2 * width) + (width + d)
        + 2 * (d + width) + 2 * (2 * width + d))
    work["expert_gmm"] = (
        s["expert_layers"] * 18.0 * rows * d * width,
        s["expert_layers"] * (3 * weights + row_arrays),
        6 * s["expert_layers"])
    chunk = config["kda_chunk"]
    chunks = per_chip_batch * held * -(-seq_len // chunk)
    positions = per_chip_batch * seq_len * held
    inputs = positions * (dim * (2 + 2 + 2 + 4) + 4)  # q, k, v, g, beta
    out = positions * dim * 2
    work["kda_scan"] = (
        s["linear_layers"] * 3.0 * chunks * chunked_scan_flops(
            chunk, dim, dim),
        float(s["linear_layers"] * ((inputs + out) + (2 * inputs + out))),
        2 * s["linear_layers"])
    return work


# --- how `correct` is decided in this family's cells -------------------------
# `reference.compare`'s report of the system's bf16 per-token losses against
# the float32 reference above, on one seeded 8,192-token sequence at the
# published widths (run.py `limits_of`). Set on the v5e (PR 35) from twelve
# seeds of `solar-open2-250b.seq8k.1chip` (2147483659, 1935000117, 1835000231,
# 2047483011, 1735000453, 1635000577, 2147480013, 1535000691, 1435000713,
# 1335000837, 1235000959, 1135001071), each through the harness's own
# comparison (`run.reference_check`) by `hybrid_moe_lm_control.py`, beside
# this file, which also drives what has to fail:
#   * the lower-precision control on the same twelve: the reference itself
#     with every parameter rounded to float8_e4m3fn, the nearest precision
#     below the stated bfloat16, and the delta rule's state rounded to
#     bfloat16 after every token, in the system's place;
#   * five faults planted in the program's mixers on the first three seeds.
# Readings (my chip runs, PR 35, the committed tree; nine more runs of the
# cell on other seeds read inside the system's ranges but `bias`, which
# reached 9.2e-4):
#                     median_abs_diff mean_abs_diff far_off_share rel_rms      bias
#   system (12, ok)   0.0191-0.0206   0.0244-0.0259 0.0004-0.0017 0.033-0.036  8e-5-5.7e-4
#   low precision (12) 0.1316-0.1422  0.1573-0.1712 0.312-0.349   0.199-0.219  2e-4-4.9e-3
#   beta_not_doubled (3) 0.223-0.252  0.266-0.301   0.543-0.593   0.331-0.381  3.9e-3-9.2e-3
#   decay_per_head    0.461-0.463     0.541-0.553   0.769-0.770   0.672-0.696  7e-4-1.7e-2
#   conv_reversed     0.664-0.690     0.793-0.816   0.838-0.845   0.992-1.025  1.4e-3-1.1e-2
#   next_heads        0.891-0.919     1.054-1.082   0.882-0.883   1.301-1.352  1.3e-2-2.8e-2
#   gate_left_out     0.701-0.710     0.827-0.842   0.845-0.852   1.026-1.056  3.3e-3-7.4e-3
# The control and every fault fail the first four limits on every seed. Each
# of those stands about as far above the system's highest reading as below
# the control's lowest (2.4 x and 2.6 x, 2.5 x and 2.4 x, 14 x and 12 x, 2.4 x
# and 2.3 x). Unlike `latent_moe_lm`, whose 128-way routing moves 4 % of the
# tokens far off, 320-way routing onto 8 held experts moves under 0.2 % of
# them, so the mean of squares says as much as the median here and takes a
# limit too. `bias` separates nothing (the control's twelve lie among the
# system's and above): twenty-one sound readings have an RMS of 4.3e-4 and
# reach 9.2e-4, so run.py's 1e-3 would refuse a sound seed before long;
# `limits_of` holds every family to a `bias`, which stands at 4.3 x the
# highest sound reading, where four of the five faults arrive.
LIMITS = {
    "median_abs_diff": 0.05,
    "mean_abs_diff": 0.065,
    "far_off_share": 0.025,
    "rel_rms": 0.085,
    "bias": 0.004,
}
# A token is far off where its loss differs by more than this.
FAR_OFF = 0.2
