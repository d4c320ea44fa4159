"""The family ``latent_moe_lm``: the repository's `LatentMoELM` (models/
latent_moe_lm.py) at a DeepSeek-V3-style configuration's published widths,
its plain float32 reference, and its counts of operations and bytes.

A configuration of this family carries the keys of a ``deepseek_v3``
`config.json` under their own names. The model: pre-norm RMSNorm layers of
multi-head latent attention without a query rank and a SwiGLU MLP, dense in
the first ``first_k_dense_replace`` layers and routed in the rest (sigmoid
scores over ``n_router_experts``, top ``num_experts_per_tok`` of logit +
selection bias (solved for each sequence so that its loads are level, not a
carried buffer), the chosen scores normalised and scaled by
``routed_scaling_factor``, one shared SwiGLU of ``n_shared_experts`` x
``moe_intermediate_size``); a final RMSNorm and an untied head; no biases.

**The chip's share.** ``n_routed_experts`` is the number of experts HELD
here, the contiguous block from ``held_experts_start``; the router keeps
its published width ``n_router_experts`` and its experts per token. The
program and the reference both add up only what the held experts give for
the tokens routed to them (with gates normalised over all the chosen, held
or not) and pass that partial sum on. ``vocab_size`` is the slice of the
vocabulary held: ids, logits and loss are over it.

**Rotary convention** (``rope_interleave``): adjacent pairs ``(x[2i],
x[2i+1])`` of the rotary part turn by ``position * rope_theta^(-2i /
qk_rope_head_dim)`` and stay in place, in the program and here alike (the
published code first permutes them into halves, on q and k alike, which
leaves every score as it is).

Two counts are kept apart, as in ``dense_lm``: *required* (what forward and
backward need, nothing recomputed; `mfu` divides by it) and *executed*
(what a kernel runs; its roofline share divides by it). The routed experts
are counted at their expectation under uniform routing:
``num_experts_per_tok x n_routed_experts / n_router_experts`` experts a
token.

``LIMITS`` / ``FAR_OFF`` (how `reference.compare`'s report decides
``correct`` in this family's cells) are at the end, each with the on-chip
readings that set it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import flops


# --- sizes and the program's own model ---------------------------------------

def sizes(config: dict) -> dict:
    """What the harness needs, and a refusal by name of what the program's
    `LatentMoELM` cannot build."""
    fixed = {
        "q_lora_rank": None, "rope_scaling": None, "n_group": 1,
        "topk_group": 1, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "norm_topk_prob": True, "moe_layer_freq": 1, "hidden_act": "silu",
        "attention_bias": False, "tie_word_embeddings": False,
        "rope_interleave": True,
    }
    for key, only in fixed.items():
        if config.get(key) != only:
            raise ValueError(
                f"the repository's LatentMoELM builds {key} = {only!r} only; "
                f"this configuration says {config.get(key)!r}")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention has one K/V head per query head; "
                         "num_key_value_heads differs")
    if config["qk_head_dim"] != (
            config["qk_nope_head_dim"] + config["qk_rope_head_dim"]):
        raise ValueError("qk_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    held, start = config["n_routed_experts"], config["held_experts_start"]
    if not 0 <= start <= config["n_router_experts"] - held:
        raise ValueError(
            f"experts {start}..{start + held} are not a block of the "
            f"router's {config['n_router_experts']}")
    if not 0 < config["first_k_dense_replace"] <= config["num_hidden_layers"]:
        raise ValueError("first_k_dense_replace is not within the layers run")
    return {
        "vocab_size": config["vocab_size"],
        "max_positions": config["max_position_embeddings"],
        "attention_layers": config["num_hidden_layers"],
        "expert_layers": (config["num_hidden_layers"]
                          - config["first_k_dense_replace"]),
    }


def build(config: dict, trainer_spec: dict, mesh):
    from horovod_tpu.models.latent_moe_lm import LatentMoELM
    from horovod_tpu.models.transformer import ShardingConfig

    sizes(config)
    return LatentMoELM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_dense_layers=config["first_k_dense_replace"],
        dense_width=config["intermediate_size"],
        n_heads=config["num_attention_heads"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"], kv_rank=config["kv_lora_rank"],
        n_routed=config["n_router_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_width=(config["n_shared_experts"]
                      * config["moe_intermediate_size"]),
        routed_scaling=config["routed_scaling_factor"],
        n_held=config["n_routed_experts"],
        held_start=config["held_experts_start"],
        rope_base=float(config["rope_theta"]), eps=config["rms_norm_eps"],
        compute_dtype=jnp.dtype(trainer_spec["compute_dtype"]),
        fused_head_chunks=trainer_spec["fused_head_chunks"],
        sharding=ShardingConfig(mesh=mesh),
    )


# --- the plain reference -----------------------------------------------------
# Plain `jax.numpy`, float32, matrix multiplications at precision "highest",
# nothing of the program: no kernel, no fused head, no sort, no grouped
# matmul (every held expert runs on every token and the gate, zero where
# the token did not choose it, decides). One sequence at a time, one head
# at a time, so the [T, T] scores of an 8k sequence stay at 256 MB.

def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * scale


def _rope(x, base):
    """[T, H, D]: the adjacent pairs (x[2i], x[2i+1]) turn by
    position * base^(-2i / D)."""
    t, _, d = x.shape
    freqs = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return turned.reshape(x.shape)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _latent_attention(h, p, config):
    """[T, d] -> [T, d] under the parameters ``p`` of one ``attn``."""
    nope, rank = config["qk_nope_head_dim"], config["kv_lora_rank"]
    base = float(config["rope_theta"])
    t = h.shape[0]
    q = jnp.einsum("td,dhe->the", h, p["q_proj"]["kernel"])
    kv_a = h @ p["kv_a"]["kernel"]
    latent = _rms_norm(kv_a[:, :rank], p["kv_norm"]["scale"],
                       config["rms_norm_eps"])
    kv = jnp.einsum("tr,rhe->the", latent, p["kv_b"]["kernel"])
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], base)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_rope = _rope(kv_a[:, None, rank:], base)[:, 0]  # [T, rope], all heads
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scale = 1.0 / jnp.sqrt(float(config["qk_head_dim"]))

    def one_head(args):
        qn, qr, kn, vh = args  # [T, .]
        scores = (qn @ kn.T + qr @ k_rope.T) * scale
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return probs @ vh

    heads_first = [a.transpose(1, 0, 2) for a in (q_nope, q_rope, k_nope, v)]
    out = jax.lax.map(one_head, tuple(heads_first)).transpose(1, 0, 2)
    return jnp.einsum("the,hed->td", out, p["o_proj"]["kernel"])


def _selection_bias(logits, k):
    """[T, E] -> [E]: the bias that levels this sequence's loads. Each
    expert's logits are lowered by its own ``T * k / E``-th largest, which
    leaves every expert as many tokens above zero as level loads give it
    (the configuration's departures say why the bias is solved in the step
    and on the logits, and not carried as the published buffer)."""
    t, e = logits.shape
    above = min(t, max(1, round(t * k / e)))
    return -jnp.sort(logits, axis=0)[t - above]


def _expert_layer(h, p, config):
    """[T, d] -> [T, d]: the held experts' part of the routed sum, and the
    shared expert."""
    k, width = config["num_experts_per_tok"], config["moe_intermediate_size"]
    logits = h @ p["router"]  # over all the router's experts
    _, chosen = jax.lax.top_k(logits + _selection_bias(logits, k), k)
    scores = jax.nn.sigmoid(logits)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = picked / (picked.sum(-1, keepdims=True) + 1e-20) * config[
        "routed_scaling_factor"]
    held = config["held_experts_start"] + jnp.arange(
        config["n_routed_experts"])

    def add_expert(total, expert):
        index, w_gate_up, w_down = expert
        gate = jnp.sum(gates * (chosen == index), axis=-1)  # 0: not chosen
        out = _swiglu(h, w_gate_up[:, :width], w_gate_up[:, width:], w_down)
        return total + gate[:, None] * out, None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h),
        (held, p["experts_gate_up"], p["experts_down"]))
    shared = p["shared"]
    return routed + _swiglu(h, shared["gate"]["kernel"],
                            shared["up"]["kernel"], shared["down"]["kernel"])


def per_token_loss(params, tokens, labels, config: dict):
    """Cross-entropy of each position of ONE sequence (``tokens`` and
    ``labels`` are [T]) under ``params``, the `LatentMoELM` parameter tree.
    Returns float32 [T]."""
    eps = config["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = p["embed"]["embedding"][tokens]  # [T, d]
        for n in range(config["num_hidden_layers"]):
            b = p[f"Block_{n}"]
            x = x + _latent_attention(
                _rms_norm(x, b["attn_norm"]["scale"], eps), b["attn"], config)
            h = _rms_norm(x, b["mlp_norm"]["scale"], eps)
            if n < config["first_k_dense_replace"]:
                mlp = b["mlp"]
                x = x + _swiglu(h, mlp["gate"]["kernel"], mlp["up"]["kernel"],
                                mlp["down"]["kernel"])
            else:
                x = x + _expert_layer(h, b["mlp"], config)
        x = _rms_norm(x, p["final_norm"]["scale"], eps)
        logits = x @ p["lm_head"]["kernel"]  # [T, V held]
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked


# --- counts from shapes ------------------------------------------------------

def expected_routed_rows(config: dict, tokens: int) -> float:
    """(token, choice) pairs of ``tokens`` tokens that fall on the held
    experts of one layer under uniform routing."""
    return (tokens * config["num_experts_per_tok"]
            * config["n_routed_experts"] / config["n_router_experts"])


def matmul_params_per_token(config: dict) -> float:
    """Parameters that multiply one token's activations once: the latent
    projections, the MLPs, the router, the shared expert, the routed experts
    at their expectation, the head. The embedding is a gather and the norms
    are elementwise: neither counts."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk, v_dim = config["qk_head_dim"], config["v_head_dim"]
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    attn = (d * heads * qk + d * (rank + rope)
            + rank * heads * (config["qk_nope_head_dim"] + v_dim)
            + heads * v_dim * d)
    expert = 3 * d * config["moe_intermediate_size"]
    routed = expected_routed_rows(config, 1) * expert
    expert_layer = (d * config["n_router_experts"]
                    + config["n_shared_experts"] * expert + routed)
    s = sizes(config)
    dense_layers = s["attention_layers"] - s["expert_layers"]
    return (s["attention_layers"] * attn
            + dense_layers * 3 * d * config["intermediate_size"]
            + s["expert_layers"] * expert_layer
            + d * config["vocab_size"])


def attention_dot_flops(config: dict, seq_len: int, qk_dots: int,
                        v_dots: int) -> float:
    """``qk_dots`` block matmuls that contract or produce the q/k head size
    and ``v_dots`` the v head size, 2·pairs·size FLOPs a head each, over one
    sequence in every layer."""
    pairs = flops.visible_pairs(seq_len, None)
    width = (qk_dots * config["qk_head_dim"] + v_dots * config["v_head_dim"])
    return (2.0 * pairs * width * config["num_attention_heads"]
            * config["num_hidden_layers"])


def required_flops_per_token(config: dict, seq_len: int) -> float:
    """6 per multiplying parameter and the 6 attention dots: scores, dQ and
    dK at the q/k head size; P·V, dP and dV at the v head size."""
    attn = attention_dot_flops(config, seq_len, 3, 3) / seq_len
    return 6.0 * matmul_params_per_token(config) + attn


def kernel_work(config: dict, seq_len: int, per_chip_batch: int) -> dict:
    """{kernel family: (executed FLOPs, least HBM bytes, calls)} of one
    training step on one chip.

    The flash kernels, once a layer each: forward scores (q/k size) and P·V
    (v size); the dQ pass scores again, dP (v) and dQ (q/k); the dK/dV pass
    scores again, dV, dP (v, v) and dK (q/k): 5 dots at the q/k size and 4
    at the v size, counted to the element of the causal triangle. Bytes:
    every [B, T, H, .] bf16 array a pass touches, once: q, k and their
    gradients at the q/k size, v, o, dO and dV at the v size (forward reads
    q, k, v, writes o; dQ reads q, k, v, o, dO, writes dQ; dK/dV reads the
    same, writes dK, dV).

    ``expert_gmm``, the routed experts' grouped matmuls: the work REQUIRED,
    whatever implements it. Forward gate|up and down are 6·rows·d·width,
    each of the two gradients as much again: 18·rows·d·width a layer at the
    expected rows. Least bytes: each call's operands and result once in
    bf16: the held experts' weights read forward and backward and their
    gradient written (3 x), and the rows' arrays (in + hidden + act + out
    forward; the same with their gradients for the four backward calls).
    Six calls a layer: two products, two dlhs, two drhs."""
    layers, heads = config["num_hidden_layers"], config["num_attention_heads"]
    qk, v_dim = config["qk_head_dim"], config["v_head_dim"]

    def dots(qk_dots, v_dots):
        return per_chip_batch * attention_dot_flops(
            config, seq_len, qk_dots, v_dots)

    def arrays(qk_arrays, v_arrays):
        return float(per_chip_batch * seq_len * heads * 2 * layers
                     * (qk_arrays * qk + v_arrays * v_dim))

    fwd = (dots(1, 1), arrays(2, 2), layers)
    dq = (dots(2, 1), arrays(3, 3), layers)
    dkv = (dots(2, 2), arrays(3, 4), layers)
    work = {
        "flash": tuple(sum(part) for part in zip(fwd, dq, dkv)),
        "flash_fwd": fwd, "flash_dq": dq, "flash_dkv": dkv,
    }
    expert_layers = sizes(config)["expert_layers"]
    d, width = config["hidden_size"], config["moe_intermediate_size"]
    rows = expected_routed_rows(config, per_chip_batch * seq_len)
    weights = 2.0 * config["n_routed_experts"] * 3 * d * width
    row_arrays = 2.0 * rows * (  # forward two calls, backward four
        (d + 2 * width) + (width + d)
        + 2 * (d + width) + 2 * (2 * width + d))
    work["expert_gmm"] = (
        expert_layers * 18.0 * rows * d * width,
        expert_layers * (3 * weights + row_arrays),
        6 * expert_layers)
    return work


# --- how `correct` is decided in this family's cells -------------------------
# `reference.compare`'s report of the system's bf16 per-token losses against
# the float32 reference above, on one seeded 8,192-token sequence at the
# published widths (run.py `limits_of`). Set on the v5e (PR 33) from twelve
# seeds of `kanana-2-30b-a3b.seq8k.1chip` (2147483659, 1933000117, 1833000231,
# 2047483011, 1733000453, 1633000577, 2147480013, 1533000691, 1433000713,
# 1333000837, 1233000959, 1133001071), each through the harness's own
# comparison (`run.reference_check`) by `latent_moe_lm_control.py`, beside
# this file, which also drives what has to fail:
#   * the lower-precision control on the same twelve: the reference itself
#     with every parameter rounded to float8_e4m3fn, the nearest precision
#     below the stated bfloat16, in the system's place;
#   * faults planted in the program on the first three seeds: the routed
#     layer holding the next block of experts (`wrong_block`), its row
#     budget at half the expected rows (`half_dropped`), its gates not scaled
#     (`gates_unscaled`), and the rotary base at 1e4 (`rope_base_1e4`).
# Readings (my chip runs, PR 33, the committed tree; `ok` by the limits
# below; seven more runs of the cell on other seeds read inside the
# system's ranges but `bias`, which reached 1.86e-3):
#                    median_abs_diff  mean_abs_diff  far_off_share   bias
#   system (12, ok)  0.0142-0.0152    0.0330-0.0357  0.0358-0.0414  1.4e-4-1.2e-3
#   float8 (12)      0.0777-0.0852    0.1186-0.1290  0.163-0.186    1e-5-3.8e-3
#   wrong_block (3)  0.346-0.370      0.429-0.451    0.696-0.721    6e-4-1.9e-3
#   half_dropped     0.157-0.167      0.225-0.236    0.422-0.439    6e-4-6.3e-3
#   gates_unscaled   0.158-0.166      0.205-0.214    0.402-0.422    2.0e-3-3.2e-3
#   rope_base_1e4    0.490-0.532      0.586-0.629    0.770-0.798    2.7e-3-1.0e-2
# Every control and fault fails the first three limits on every seed. Each
# of those limits stands about as far above the system's highest reading as
# below the float8 control's lowest (2.2-2.3 x, 1.8 x and 1.9-2.1 x).
# `bias` separates nothing here: it is the mean of 8,192 differences of which
# 4 % sit 0.2 or more off (the bf16 stream and the float32 reference pick a
# different sixth expert for those tokens), so chance alone moves it by
# ~1e-3: the system's nineteen readings have an RMS of 0.8e-3, five of them
# lie above run.py's 1e-3 (set on dense models, whose tokens differ a quarter
# as much), and the float8 control's lie among them. `limits_of` holds every
# family to a `bias`, so it stands at about seven times that RMS, 3.2 x the
# highest sound reading, where only a fault that shifts every token the same
# way arrives (the rotary base's reached it on one seed of three, after
# failing the other three limits 9-15 times over).
# For the same reason the mean of squares says little (`rel_rms` 0.081-0.092
# against the dense limit 0.04, the float8 control 0.179-0.194) and no limit
# is set on it.
LIMITS = {
    "median_abs_diff": 0.035,
    "mean_abs_diff": 0.065,
    "far_off_share": 0.085,
    "bias": 0.006,
}
# A token is far off where its loss differs by more than this.
FAR_OFF = 0.2
