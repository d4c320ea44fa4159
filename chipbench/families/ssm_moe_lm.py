"""The family ``ssm_moe_lm``: the repository's `HybridMoELM` (models/
hybrid_moe_lm.py) with its state-space kind at a Granite-4.0-H-style
configuration's published widths, its plain float32 reference, and its
counts of operations and bytes.

A configuration of this family carries the keys of a ``granitemoehybrid``
`config.json` under their own names. The model: pre-norm RMSNorm layers of
a token mixer and a routed expert layer with a shared expert; layer i is a
Mamba-2 layer or a NoPE grouped-query softmax layer as ``layer_types``
says; no positions (``position_embedding_type`` nope); three multipliers
(``embedding_multiplier`` on the looked-up vector, ``residual_multiplier``
on what every mixer and MLP adds, ``logits_scaling`` dividing the logits)
and ``attention_multiplier`` as the scores' scale; a final RMSNorm and a
head TIED to the embedding; no biases but the convolution's. The layer
equations are in the reference's docstrings below.

**The chip's share.** ``mamba_n_heads`` and ``num_attention_heads`` are
the heads HELD here (the blocks from ``held_heads_start.mamba`` /
``.softmax``, of ``published_heads``), ``num_key_value_heads`` the K/V
heads the held query heads read, ``num_local_experts`` the experts held
(from ``held_experts_start``; the router keeps ``n_router_experts`` and
its experts per token), ``vocab_size`` the rows held. The one group's B
and C projections with their taps are held whole. Program and reference
both return the held heads' rows of W_o times their outputs and the held
experts' part of the routed sum, and pass those partial sums on; the
state-space layer's gated norm is over the channels HELD (a deployment
sums one float a token over its group: the configuration's file says so).

Two counts are kept apart, as in ``dense_lm``: *required* (what forward
and backward need, nothing recomputed; `mfu` divides by it) and *executed*
(what a kernel runs, the rematerialised forward too where the
configuration says ``activation_checkpointing``; its roofline share
divides by it).

``LIMITS`` (how `reference.compare`'s report decides ``correct`` in this
family's cells) is at the end, each limit with the on-chip readings that set
it.
"""

from __future__ import annotations

import pathlib

import jax
import jax.numpy as jnp

from chipbench import flops, run

_HERE = pathlib.Path(__file__)
_LATENT = run.load_module(_HERE.with_name("latent_moe_lm.py"))
_HYBRID = run.load_module(_HERE.with_name("hybrid_moe_lm.py"))
MAMBA, ATTENTION = "mamba", "attention"
# Rows of queries whose scores the reference holds at once.
ROW_BLOCK = 1024


# --- sizes and the program's own model ---------------------------------------

def layer_kinds(config: dict) -> tuple:
    """``layer_types`` (one entry a layer that is run) in the program's
    names."""
    names = {MAMBA: "ssm", ATTENTION: "softmax"}
    return tuple(names[kind] for kind in config["layer_types"])


def attention_head_dim(config: dict) -> int:
    return config["hidden_size"] // config["published_heads"]["softmax"]


def sizes(config: dict) -> dict:
    """What the harness needs, and a refusal by name of what the program's
    `HybridMoELM` cannot build."""
    fixed = {
        "position_embedding_type": "nope", "tie_word_embeddings": True,
        "attention_bias": False, "mamba_proj_bias": False,
        "mamba_conv_bias": True, "mamba_n_groups": 1, "hidden_act": "silu",
        "normalization_function": "rmsnorm",
    }
    for key, only in fixed.items():
        if config.get(key) != only:
            raise ValueError(
                f"the repository's HybridMoELM builds {key} = {only!r} only; "
                f"this configuration says {config.get(key)!r}")
    kinds = config["layer_types"]
    unknown = sorted(set(kinds) - {MAMBA, ATTENTION})
    if unknown or len(kinds) != config["num_hidden_layers"]:
        raise ValueError(
            f"layer_types has {len(kinds)} entries ({unknown or 'all known'}"
            f") for num_hidden_layers {config['num_hidden_layers']}: one of "
            f"{MAMBA!r} / {ATTENTION!r} a layer that is run")
    if config.get("activation_checkpointing") not in (None, "block"):
        raise ValueError(
            "activation_checkpointing is null or 'block' (every block "
            f"rematerialised), not {config['activation_checkpointing']!r}")
    published, starts = config["published_heads"], config["held_heads_start"]
    wide = config["mamba_expand"] * config["hidden_size"]
    if published["mamba"] * config["mamba_d_head"] != wide:
        raise ValueError(
            f"{published['mamba']} Mamba heads of {config['mamba_d_head']} "
            f"are not mamba_expand x hidden_size = {wide} channels")
    if config["hidden_size"] % published["softmax"]:
        raise ValueError("hidden_size is not whole attention heads")
    for what, held in (("mamba", config["mamba_n_heads"]),
                       ("softmax", config["num_attention_heads"])):
        if not 0 <= starts[what] <= published[what] - held:
            raise ValueError(
                f"heads {starts[what]}.. + {held} are not a block of the "
                f"{published[what]} {what} heads")
    group = published["softmax"] // published["softmax_kv"]
    held = config["num_attention_heads"]
    if (held % group or starts["softmax"] % group
            or config["num_key_value_heads"] != held // group):
        raise ValueError(
            f"{held} query heads in groups of {group} do not read "
            f"{config['num_key_value_heads']} K/V heads")
    experts, start = config["num_local_experts"], config["held_experts_start"]
    if not 0 <= start <= config["n_router_experts"] - experts:
        raise ValueError(
            f"experts {start}..{start + experts} are not a block of the "
            f"router's {config['n_router_experts']}")
    return {
        "vocab_size": config["vocab_size"],
        "max_positions": config["max_position_embeddings"],
        "attention_layers": kinds.count(ATTENTION),
        "ssm_layers": kinds.count(MAMBA),
        "expert_layers": len(kinds),
    }


def build(config: dict, trainer_spec: dict, mesh):
    from horovod_tpu.models.hybrid_moe_lm import HybridMoELM, StateSpaceSizes
    from horovod_tpu.models.transformer import ShardingConfig

    sizes(config)
    remat = trainer_spec.get("remat")
    if remat != config.get("activation_checkpointing"):
        raise ValueError(
            f'the cell\'s trainer says "remat": {remat!r} and the '
            'configuration "activation_checkpointing": '
            f"{config.get('activation_checkpointing')!r}: the counts of "
            "executed work read the configuration, so the two have to agree")
    published, starts = config["published_heads"], config["held_heads_start"]
    return HybridMoELM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        layer_kinds=layer_kinds(config), head_dim=attention_head_dim(config),
        linear_heads=0, conv_size=0, low_rank=0, kda_chunk=0,  # no such kind
        softmax_heads=published["softmax"],
        softmax_kv_heads=published["softmax_kv"],
        n_held_heads=config["num_attention_heads"],
        held_heads_start=starts["softmax"],
        ssm=StateSpaceSizes(
            n_heads=published["mamba"], n_held_heads=config["mamba_n_heads"],
            held_heads_start=starts["mamba"], head_dim=config["mamba_d_head"],
            state_dim=config["mamba_d_state"],
            conv_size=config["mamba_d_conv"],
            chunk=config["mamba_chunk_size"]),
        softmax_gate=False, softmax_scale=config["attention_multiplier"],
        n_routed=config["n_router_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["intermediate_size"],
        shared_width=config["shared_intermediate_size"],
        routed_scaling=1.0, moe_scoring="softmax",
        n_held=config["num_local_experts"],
        held_start=config["held_experts_start"],
        residual_multiplier=config["residual_multiplier"],
        embedding_multiplier=config["embedding_multiplier"],
        logits_divisor=config["logits_scaling"], tied_head=True,
        remat=remat == "block", eps=config["rms_norm_eps"],
        compute_dtype=jnp.dtype(trainer_spec["compute_dtype"]),
        fused_head_chunks=trainer_spec["fused_head_chunks"],
        sharding=ShardingConfig(mesh=mesh),
    )


# --- the plain reference -----------------------------------------------------
# Plain `jax.numpy`, float32, matrix multiplications at precision "highest",
# nothing of the program: no kernel, no chunks (the recurrence runs token by
# token), no fused head, no sort, no grouped matmul (every held expert runs
# on every token and the gate, zero where the token did not choose it,
# decides), nothing rematerialised. One sequence at a time, the scores of
# `ROW_BLOCK` queries and one expert's hidden layer at a time, so that 4,096
# tokens fit beside 13.5 GB of state.

_rms_norm, _swiglu = _LATENT._rms_norm, _LATENT._swiglu
_selection_bias, _conv = _LATENT._selection_bias, _HYBRID._conv


def _state_space(h, p, config, state_dtype):
    """[T, d] -> [T, d]: the held heads of one Mamba-2 layer, token by
    token. A head of P channels, with S [P, N] from zero and ONE group's B
    and C for all heads::

        z, x, (B | C), dt_raw = W_z h, W_x h, W_bc h, W_dt h
        x, (B | C) = SiLU(conv4(x) + b_x), SiLU(conv4(B | C) + b_bc)
        dt_t = softplus(dt_raw_t + dt_bias);   a_t = exp(-dt_t exp(A_log))
        S_t = a_t S_{t-1} + dt_t x_t B_t^T;    y_t = S_t C_t + D x_t
        out = W_o [ y * SiLU(z) / rms_{the channels held}(y * SiLU(z)) * w ]

    ``state_dtype`` is what S is kept in between tokens (float32; the
    lower-precision control rounds it to bfloat16 after every token)."""
    n = config["mamba_d_state"]

    def heads(name):
        return jnp.einsum("td,dhe->the", h, p[name]["kernel"])

    z = heads("z_proj")
    x = jax.nn.silu(_conv(heads("x_proj"), p["x_conv"]) + p["x_conv_bias"])
    b_c = jax.nn.silu(
        _conv(h @ p["bc_proj"]["kernel"], p["bc_conv"]) + p["bc_conv_bias"])
    b, c = b_c[:, :n], b_c[:, n:]
    dt = jax.nn.softplus(h @ p["dt_proj"]["kernel"] + p["dt_bias"])  # [T, H]
    keep = jnp.exp(-dt * jnp.exp(p["A_log"]))

    def token(state, at):
        x_t, b_t, c_t, dt_t, keep_t = at  # [H, P], [N], [N], [H], [H]
        state = (keep_t[:, None, None] * state.astype(jnp.float32)
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t)
        return state.astype(state_dtype), state @ c_t

    n_heads, dim = x.shape[1:]
    _, y = jax.lax.scan(
        token, jnp.zeros((n_heads, dim, n), state_dtype), (x, b, c, dt, keep))
    gated = (y + p["D"][:, None] * x) * jax.nn.silu(z)
    mean_square = (gated ** 2).mean(axis=(1, 2), keepdims=True)
    out = gated / jnp.sqrt(mean_square + config["rms_norm_eps"]) * p["norm"]
    return jnp.einsum("the,hed->td", out, p["o_proj"])


def _attention(h, p, config):
    """[T, d] -> [T, d]: the held query heads of one softmax layer over the
    K/V heads they read, no positions, no gate: ``W_o softmax(q k^T x
    attention_multiplier) v``, causal; the scores of `ROW_BLOCK` queries
    at a time."""
    t = h.shape[0]
    q, k, v = (jnp.einsum("td,dhe->the", h, p[f"{n}_proj"]["kernel"])
               for n in "qkv")
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    block = min(ROW_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions are not whole blocks of {block}")

    def rows(start):
        q_rows = jax.lax.dynamic_slice_in_dim(q, start, block)  # [R, H, D]
        seen = (jnp.arange(t)[None, :]
                <= (start + jnp.arange(block))[:, None])[None]
        scores = jnp.einsum("rhe,she->hrs", q_rows, k) * config[
            "attention_multiplier"]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hrs,she->rhe", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(q.shape)
    return jnp.einsum("the,hed->td", out, p["o_proj"])


def _expert_layer(h, p, config):
    """[T, d] -> [T, d]: the held experts' part of the routed sum, and the
    shared expert. The top ``num_experts_per_tok`` of logit + the sequence's
    selection bias (the configuration's departures); the gates are the
    softmax over the CHOSEN logits, without the bias."""
    k, width = config["num_experts_per_tok"], config["intermediate_size"]
    logits = h @ p["router"]  # over all the router's experts
    _, chosen = jax.lax.top_k(logits + _selection_bias(logits, k), k)
    gates = jax.nn.softmax(
        jnp.take_along_axis(logits, chosen, axis=-1), axis=-1)
    held = config["held_experts_start"] + jnp.arange(
        config["num_local_experts"])

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


def per_token_loss(params, tokens, labels, config: dict, *,
                   state_dtype=jnp.float32):
    """Cross-entropy of each position of ONE sequence (``tokens`` and
    ``labels`` are [T]) under ``params``, the `HybridMoELM` parameter tree
    of this family's `build`. Returns float32 [T]."""
    eps, scale = config["rms_norm_eps"], config["residual_multiplier"]
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        table = p["embed"]["embedding"]  # [V held, d]: looked up AND the head
        x = config["embedding_multiplier"] * table[tokens]  # [T, d]
        for n, kind in enumerate(config["layer_types"]):
            b = p[f"Block_{n}"]
            h = _rms_norm(x, b["mixer_norm"]["scale"], eps)
            if kind == MAMBA:
                x = x + scale * _state_space(h, b["mixer"], config,
                                             state_dtype)
            else:
                x = x + scale * _attention(h, b["mixer"], config)
            x = x + scale * _expert_layer(
                _rms_norm(x, b["mlp_norm"]["scale"], eps), b["mlp"], config)
        x = _rms_norm(x, p["final_norm"]["scale"], eps)
        logits = x @ table.T / config["logits_scaling"]
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked


# --- counts from shapes ------------------------------------------------------

def expected_routed_rows(config: dict, tokens: int) -> float:
    """(token, choice) pairs of ``tokens`` tokens that fall on the held
    experts of one layer under uniform routing."""
    return (tokens * config["num_experts_per_tok"]
            * config["num_local_experts"] / config["n_router_experts"])


def matmul_params_per_token(config: dict) -> float:
    """Parameters that multiply one token's activations once: the mixers'
    projections (the convolution's taps and biases, the norms and the
    per-head scalars are elementwise), the router, the shared expert, the
    routed experts at their expectation, the tied head (the lookup is a
    gather and does not count)."""
    d = config["hidden_size"]
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    mamba = (d * (2 * inner + 2 * config["mamba_d_state"]
                  + config["mamba_n_heads"])  # z, x, B | C, dt
             + inner * d)
    dim = attention_head_dim(config)
    attention = (2 * d * config["num_attention_heads"] * dim  # q, o
                 + 2 * d * config["num_key_value_heads"] * dim)
    expert = 3 * d * config["intermediate_size"]
    expert_layer = (d * config["n_router_experts"]
                    + 3 * d * config["shared_intermediate_size"]
                    + expected_routed_rows(config, 1) * expert)
    s = sizes(config)
    return (s["ssm_layers"] * mamba + s["attention_layers"] * attention
            + s["expert_layers"] * expert_layer + d * config["vocab_size"])


def attention_dot_flops(config: dict, seq_len: int, dots: int) -> float:
    """``dots`` block matmuls of 2·pairs·head size FLOPs a held query head
    each, over one sequence in every softmax layer."""
    return (2.0 * flops.visible_pairs(seq_len, None) * dots
            * attention_head_dim(config) * config["num_attention_heads"]
            * sizes(config)["attention_layers"])


def scan_required_flops_per_token(config: dict) -> float:
    """What the recurrence itself asks of a token in a held head: the
    rank-one write ``dt x B^T`` and the read ``S C``, 2·P·N each (the decay
    is elementwise), forward and twice that backward, in every Mamba
    layer."""
    return (3 * 2 * 2.0 * config["mamba_d_head"] * config["mamba_d_state"]
            * config["mamba_n_heads"] * sizes(config)["ssm_layers"])


def required_flops_per_token(config: dict, seq_len: int) -> float:
    """6 per multiplying parameter, the softmax layers' 6 dots and the
    recurrence's products. Nothing recomputed."""
    return (6.0 * matmul_params_per_token(config)
            + attention_dot_flops(config, seq_len, 6) / seq_len
            + scan_required_flops_per_token(config))


def chunked_scan_flops(chunk: int, heads: int, dim: int, state: int) -> float:
    """Forward FLOPs of one chunk of Q positions in the chunked (SSD) form:
    ``C B^T`` once for all heads (2·Q²·N) and, a head, the masked pairs on
    ``dt x`` (2·Q²·P), the chunk's state and the read of the carried one
    (2·Q·P·N each)."""
    return (2.0 * chunk * chunk * state
            + heads * (2.0 * chunk * chunk * dim + 4.0 * chunk * dim * state))


def forward_passes(config: dict) -> int:
    """How often a block's forward runs in a step."""
    return 2 if config.get("activation_checkpointing") == "block" else 1


def kernel_work(config: dict, seq_len: int, per_chip_batch: int) -> dict:
    """{kernel family: (executed FLOPs, least HBM bytes, calls)} of one
    training step on one chip; a block's forward counted `forward_passes`
    times.

    ``flash_fwd``, the forward flash kernel of every softmax layer, K and V
    handed to it repeated over the group: 2 dots a pass to the element of
    the causal triangle, and q, k, v, o ``[B, T, H, D]`` bf16 once a pass.
    (The backward kernel has no reader in the benchmark: PERF.md §7.)

    ``expert_gmm``, the routed experts' grouped matmuls at the expected
    rows, as the family ``latent_moe_lm`` counts them, with the forward's
    two calls and their operands once more a rematerialised pass.

    ``ssd_scan``, the state-space recurrence over a sequence, whatever
    implements it: the chunked form's FLOPs at the program's chunk (a
    forward pass; twice that backward), and the least bytes: x, B, C
    (bf16) and dt (float32) read and y (float32, as the op hands it on)
    written once a forward pass; the same read again with dy, and the four
    gradients written, backward."""
    s = sizes(config)
    passes = forward_passes(config)
    dim, held = attention_head_dim(config), config["num_attention_heads"]
    softmax = s["attention_layers"]
    work = {"flash_fwd": (
        passes * per_chip_batch * attention_dot_flops(config, seq_len, 2),
        float(passes * per_chip_batch * seq_len * held * dim * 2 * softmax
              * 4),
        passes * softmax)}
    d, width = config["hidden_size"], config["intermediate_size"]
    rows = expected_routed_rows(config, per_chip_batch * seq_len)
    weights = 2.0 * config["num_local_experts"] * 3 * d * width
    forward_rows = 2.0 * rows * ((d + 2 * width) + (width + d))
    backward_rows = 2.0 * rows * (2 * (d + width) + 2 * (2 * width + d))
    work["expert_gmm"] = (
        s["expert_layers"] * (6.0 * passes + 12.0) * rows * d * width,
        s["expert_layers"] * ((passes + 2) * weights
                              + passes * forward_rows + backward_rows),
        (2 * passes + 4) * s["expert_layers"])
    chunk = min(config["mamba_chunk_size"], seq_len)
    heads, p, n = (config["mamba_n_heads"], config["mamba_d_head"],
                   config["mamba_d_state"])
    chunks = per_chip_batch * -(-seq_len // chunk)
    positions = per_chip_batch * seq_len
    inputs = positions * (heads * p * 2 + 2 * n * 2 + heads * 4)
    out = positions * heads * p * 4
    work["ssd_scan"] = (
        s["ssm_layers"] * (passes + 2.0) * chunks * chunked_scan_flops(
            chunk, heads, p, n),
        float(s["ssm_layers"] * (passes * (inputs + out)
                                 + (2 * inputs + out))),
        (passes + 1) * s["ssm_layers"])
    return work


# --- how `correct` is decided in this family's cells -------------------------
# `reference.compare`'s report of the system's bf16 per-token losses against
# the float32 reference above, on one seeded 4,096-token sequence at the
# published widths (run.py `limits_of`). Set on the v5e (PR 39) from twelve
# seeds of `granite-4.0-h-small.seq4k.1chip` (2147483659, 1939000117,
# 1839000231, 2047483011, 1739000453, 1639000577, 2147480013, 1539000691,
# 1439000713, 1339000837, 1239000959, 1139001071), each through the harness's
# own comparison (`run.reference_check`) by `ssm_moe_lm_control.py`, beside
# this file, which also drives what has to fail:
#   * the lower-precision control on the same twelve: the reference itself
#     with every parameter rounded to float8_e4m3fn, the nearest precision
#     below the stated bfloat16, and the recurrence's state rounded to
#     bfloat16 after every token, in the system's place;
#   * eight faults planted in the program on the first three seeds.
# Readings (my chip runs, PR 39; every loss of this configuration lies within
# 0.05 of ln 12,544 = 9.437, because the logits are divided by 16 and every
# block adds 0.22 of what it computes: all differences are small in the
# loss's units, and `rel_rms` reads them against the losses' own spread):
#                           median_abs_diff  mean_abs_diff    rel_rms        bias
#   system (12, ok)         0.000833-0.000917 0.001014-0.001105 0.0209-0.0227 3e-6-7.3e-5
#   low precision (12)      0.001645-0.002313 0.001971-0.002790 0.0393-0.0569 1.9e-5-9.0e-5
#   gates_over_all (3)      0.00358-0.00378  0.00439-0.00455  0.0894-0.0924  5e-5-1.1e-4
#   b_c_swapped             0.0247-0.0260    0.0300-0.0310    0.597-0.631    7e-5-1.0e-3
#   norm_before_gate        0.0297-0.0317    0.0363-0.0375    0.733-0.748    2.0e-4-3.9e-4
#   residual_multiplier_left_out 0.0382-0.0407 0.0469-0.0482  0.934-0.972    2.8e-4-1.5e-3
#   dt_without_bias         0.0439-0.0457    0.0519-0.0542    1.030-1.096    6.4e-4-1.4e-3
#   head_untied             0.0578-0.0603    0.0695-0.0717    1.410-1.427    5.1e-4-9.2e-4
#   next_heads              0.0582-0.0597    0.0668-0.0689    1.345-1.359    8.2e-4-1.5e-3
#   decay_inverted          not finite (the state grows by up to e^1.6 a position)
# Every reading of the control and of the faults above lies above all three
# limits below, which were set FROM them: for those twelve seeds the failing
# verdict is arithmetic on the printed readings. Under the committed limits
# the harness's own verdict was taken on three further seeds (2039700013,
# 1939700131, 1839700241, faults on the first; exit 0): system ok 3 of 3
# (0.000800-0.000906 / 0.000998-0.001076 / 0.0209-0.0214), the control not
# ok 3 of 3 and failing all three limits each time (0.001684-0.001778 /
# 0.002024-0.002092 / 0.0402-0.0427), each fault failing all three. The
# control stands 1.79 x, 1.78 x and 1.73 x above the system's highest
# reading, so each limit is the geometric mean of the two: 1.34 x, 1.34 x and
# 1.31 x of room on either side, where the system's twelve readings spread by
# +-5 %; the nearest fault (gates that no longer add up to 1) is 2.9 x above
# the limits, the others 20 x and more. `bias` separates nothing (the
# control's readings lie among the system's): it is left at run.py's 1e-3,
# 14 x the highest sound reading. No `far_off_share`: the routed layer's
# softmax gates over ten chosen of 72 leave no minority of far-off tokens
# (the mean of squares separates the control as well as the median does,
# which a tail would prevent; at 0.2, the other routed families' threshold,
# the share read 0 on all twelve seeds and on the control).
LIMITS = {
    "median_abs_diff": 0.00123,
    "mean_abs_diff": 0.00148,
    "rel_rms": 0.030,
}
