"""A hybrid causal LM: delta-rule linear attention, state-space layers,
full and sliding-window softmax attention, over routed experts
(Solar-Open2 / Kimi-Linear, Granite-4.0-H and Laguna style).

The stack takes its layer kinds as DATA: ``layer_kinds`` is a tuple with
one of `LINEAR` / `SOFTMAX` / `WINDOW` / `SSM` a layer (three linear layers
to one softmax layer in Solar-Open2; nine state-space layers to one softmax
layer in Granite-4.0-H; one full softmax layer to three sliding-window
ones in Laguna). Every layer is pre-norm RMSNorm around a token mixer and
around an MLP: a dense SwiGLU in the first ``n_dense_layers`` layers, else
a routed expert layer with a shared expert (`models/moe.py`
`RoutedExperts`, its gate's scoring as data); a final RMSNorm and a head,
its own or TIED to the embedding's table (``tied_head``); no biases in the
projections. Positions enter only where a softmax kind's sizes give a
rotary (Laguna's two kinds); elsewhere the recurrences and the causal mask
order the tokens. With the three multipliers (all 1 unless given), for a
token's vector::

    x_0 = embedding_multiplier * Embed[token]
    x += residual_multiplier * Mixer_i(RMSNorm(x))
    x += residual_multiplier * MLP_i(RMSNorm(x))
    logits = (RMSNorm(x_L) . W_head) / logits_divisor

With ``remat`` every block is rematerialised in the backward pass
(`nn.remat(HybridBlock)`): a block keeps its input only and its forward
runs twice a step.

`DeltaAttention` (``linear``; Kimi Delta Attention, arXiv:2510.26692), a
head, over t, with S [Dk, Dv] float32 from zero::

    q~, k~, v~ = SiLU(conv(W_q h)), SiLU(conv(W_k h)), SiLU(conv(W_v h))
                 causal depthwise convolution, `conv_size` taps a channel
    q_t = q~_t / |q~_t| * Dk^-1/2;  k_t = k~_t / |k~_t|;  v_t = v~_t
    g_t = -exp(A_log) * softplus(W_fb (W_fa h_t) + dt_bias)   [Dk], <= 0
    beta_t = 2 * sigmoid(w_b . h_t)        (x 2: a negative eigenvalue)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                        (ops/delta_rule.py, chunked)
    y_t = W_o [ RMSNorm_head(o_t) * sigmoid(W_gb (W_ga h_t)) ]

`StateSpaceMixer` (``ssm``; Mamba-2, arXiv:2405.21060, its sizes one
`StateSpaceSizes`), H heads of P channels over ONE group's B and C of N,
with S [P, N] float32 a head from zero::

    z, x, (B | C), dt_raw = W_z h, W_x h, W_bc h, W_dt h
    x, (B | C) = SiLU(conv(x) + b), SiLU(conv(B | C) + b)     taps and a bias
    dt_t = softplus(dt_raw_t + dt_bias);   a_t = exp(-dt_t exp(A_log))
    S_t = a_t S_{t-1} + dt_t x_t B_t^T;   y_t = S_t C_t + D x_t
                                           (ops/ssd.py, chunked)
    out = W_o [ RMSNorm_{all H P channels}(y * SiLU(z)) * w ]  gate, THEN norm

`GatedAttention` (``softmax`` and ``window``; each kind's sizes one
`AttentionSizes`): grouped-query causal softmax attention through the flash
kernel, no QK-norm. A kind of H query heads over G K/V heads of D, group g =
H / G, at positions t::

    q = W_q h [H, D];  k = W_k h, v = W_v h [G, D];  gate = W_g h [H, D]
    q, k = R(q, t), R(k, t)                 (a rotary where the kind has one)
    a_i = sum_{j in M(i)} softmax_j(q_i . k_{j/g} / sqrt(D)) v_{j/g}
    y = W_o [a * sigmoid(gate)]             (the gate unless ``softmax_gate``
                                             is off: arXiv:2505.06708)

``M(i) = {j <= i}``, or with a window W ``{i - W < j <= i}``: W keys,
itself included (the kernel's band). ``R`` (`transformer.partial_rope`)
turns channels j and j + r/2 of the first r together by ``t w_j``, the
others pass; with YaRN the frequencies are blended between ``w_j`` and
``w_j / factor`` and cos and sin scaled by its attention factor (Laguna's
full layers: r 64 of 128 at base 500,000, YaRN x 64 over 4,096 original
positions; its window layers: all 128 at base 10,000). The scores are
scaled by D^-1/2, or by ``softmax_scale`` where that is given (the rest
rides on q).

**One chip's share of a deployment is a parameter of the model.** The
mixers are told which heads they hold (``n_held_heads`` from
``held_heads_start``, of ``n_heads``; the state-space kind in its own
sizes): they carry those heads' parameters only and return those heads'
rows of W_o times their outputs, the partial sum a tensor-parallel group
would add up (what all heads share, KDA's two low-rank input projections
and head norm's scale, the state-space layer's B and C projections with
their taps, is held whole by every chip). A softmax layer holds the K/V
heads its query heads read. The state-space layer's gated norm is over ALL
the layer's channels, the one exchange inside a layer: with
``heads_axis`` named the sum of squares is `lax.psum`'d over it (under a
`shard_map` or `vmap` that binds the name) and divided by all ``n_heads *
head_dim`` channels; not named, it is over the channels held. The experts
are told the same way (``n_held`` from ``held_start``), and the
vocabulary's rows held are simply ``vocab_size``. Nothing here stands in
for the absent chips.

The model keeps the `Trainer(loss='module')` contract of `TransformerLM`:
``apply(tokens, train=, labels=)`` returns per-token ``(loss, correct)``
from the chunked head + CE (`LMHead.fused_loss`), without labels the
logits. It has no decode path (a recurrent state, and a convolution's tail,
beside the keys and values in the cache manager: ROADMAP R8) and
`PipelinedLM` does not know its layers (ROADMAP D1); both refuse by name.
It runs on one chip: a mesh of more is refused by name (ROADMAP R1).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from horovod_tpu.models.moe import RoutedExperts, SwiGLU
from horovod_tpu.models.transformer import (
    BATCH_AXES, LMHead, Rotary, ShardingConfig, partial_rope)
from horovod_tpu.ops import delta_rule, ssd
from horovod_tpu.ops.flash_attention import flash_attention

LINEAR, SOFTMAX, SSM, WINDOW = "linear", "softmax", "ssm", "window"
# By name in the compiled step, forward and backward (chipbench/
# kda_spans.py): the linear layer's four parts, and everything of the
# softmax layer but the flash kernel, whose events are found by its names.
KDA_SCOPE = "hvt.kda"
KDA_PROJ, KDA_CONV = f"{KDA_SCOPE}/proj", f"{KDA_SCOPE}/conv"
KDA_SCAN, KDA_OUT = f"{KDA_SCOPE}/scan", f"{KDA_SCOPE}/out"
GQA_SCOPE = "hvt.gqa"
GQA_ROPE = f"{GQA_SCOPE}/rope"
# The window kind's flash calls (chipbench/window_spans.py), outside
# `GQA_SCOPE`: readers match scopes by substring.
SWA_SCOPE = "hvt.swa"
# ... and the state-space layer's four (chipbench/ssm_spans.py).
SSM_SCOPE = "hvt.ssm"
SSM_PROJ, SSM_CONV = f"{SSM_SCOPE}/proj", f"{SSM_SCOPE}/conv"
SSM_SCAN, SSM_OUT = f"{SSM_SCOPE}/scan", f"{SSM_SCOPE}/out"


def short_conv(x, taps):
    """Causal depthwise convolution of ``x [B, T, ...]`` with ``taps [K,
    ...]``: ``y_t = sum_j taps[j] x_{t-(K-1)+j}``, the last tap on the
    current position, zeros before the sequence's start."""
    size = taps.shape[0]
    padded = jnp.pad(
        x, ((0, 0), (size - 1, 0)) + ((0, 0),) * (x.ndim - 2))
    t = x.shape[1]
    return sum(padded[:, j:j + t] * taps[j].astype(x.dtype)
               for j in range(size))


def l2_normalised(x):
    """``x / |x|`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def log_decay(a_log, dt_bias, low_rank):
    """``-exp(A_log) softplus(low_rank + dt_bias)``: float32 ``[B, T, H,
    Dk]``, one log-decay a channel, for ``A_log [H]`` and ``dt_bias [H,
    Dk]``."""
    rate = jax.nn.softplus(low_rank.astype(jnp.float32) + dt_bias)
    return -jnp.exp(a_log)[:, None] * rate


def write_strength(logits):
    """``beta = 2 sigmoid``: in (0, 2), so ``I - beta k k^T`` may flip k."""
    return 2.0 * jax.nn.sigmoid(logits.astype(jnp.float32))


def output_gate(logits):
    return jax.nn.sigmoid(logits.astype(jnp.float32))


def _gated(out, gate_in):
    """``out * gate`` in ``out``'s dtype, keeping the gate's logits and not
    the float32 gate for the backward pass."""
    return jax.checkpoint(
        lambda o, logits: (o * output_gate(logits)).astype(o.dtype))(
            out, gate_in)


def project_out(heads_out, kernel):
    """The held heads' part of the output projection: ``[B, T, H, D] x [H,
    D, d]``."""
    return jnp.einsum("bthe,hed->btd", heads_out, kernel)


def _check_held(what, n_held, start, n_heads):
    if not (0 < n_held and 0 <= start <= n_heads - n_held):
        raise ValueError(
            f"{what}: heads {start}..{start + n_held} are not a block of "
            f"its {n_heads}")


def _a_log_init(key, shape, dtype=jnp.float32):
    """log of a rate drawn from [1, 16), as Kimi Linear's layer."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a step drawn log-uniformly from [1e-3,
    1e-1]."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class DeltaAttention(nn.Module):
    """The held heads of one KDA layer, ``[B, T, d] -> [B, T, d]``."""

    n_heads: int
    n_held_heads: int
    held_heads_start: int
    head_dim: int
    conv_size: int
    rank: int        # of the decay's and of the gate's input projection
    eps: float
    chunk: int
    compute_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        _check_held("DeltaAttention", self.n_held_heads,
                    self.held_heads_start, self.n_heads)
        from horovod_tpu import obs

        obs.gauge("hvt_held_heads", float(self.n_held_heads), mixer=LINEAR)
        obs.gauge("hvt_kda_chunks",
                  float(delta_rule.n_chunks(x.shape[1], self.chunk)))
        cd, held, dim = self.compute_dtype, self.n_held_heads, self.head_dim
        dense = functools.partial(nn.DenseGeneral, use_bias=False, dtype=cd)
        taps_init = nn.initializers.normal(self.conv_size ** -0.5)
        # What stands between the projections and the scan, and between the
        # scan and W_o, is elementwise: each stretch keeps its inputs only
        # and is formed again in the backward pass (a closure a trace, so
        # that no cached trace outlives the functions it called).
        with jax.named_scope(KDA_PROJ):
            q, k, v = (dense((held, dim), name=f"{n}_proj")(x) for n in "qkv")
            decay_in = dense((held, dim), name="f_b")(
                dense(self.rank, name="f_a")(x))
            beta_in = dense(held, name="b_proj")(x)
            gate_in = dense((held, dim), name="g_b")(
                dense(self.rank, name="g_a")(x))
            a_log = self.param("A_log", _a_log_init, (held,))
            dt_bias = self.param("dt_bias", _dt_bias_init, (held, dim))
            g, beta = jax.checkpoint(lambda low, strength: (
                log_decay(a_log, dt_bias, low), write_strength(strength)))(
                    decay_in, beta_in)
        with jax.named_scope(KDA_CONV):
            taps = [self.param(f"{n}_conv", taps_init,
                               (self.conv_size, held, dim)) for n in "qkv"]

            def conv_unit(q, k, v):
                q, k, v = (nn.silu(short_conv(a, w))
                           for a, w in zip((q, k, v), taps))
                return ((l2_normalised(q) * dim ** -0.5).astype(cd),
                        l2_normalised(k).astype(cd), v)

            q, k, v = jax.checkpoint(conv_unit)(q, k, v)
        with jax.named_scope(KDA_SCAN):
            out = delta_rule.gated_delta_rule(
                q, k, v, g, beta, chunk=self.chunk)
        with jax.named_scope(KDA_OUT):
            out = nn.RMSNorm(epsilon=self.eps, dtype=cd, name="o_norm")(out)
            out = _gated(out, gate_in)
            kernel = self.param(
                "o_proj", nn.initializers.lecun_normal(in_axis=(0, 1)),
                (held, dim, x.shape[-1]))
            return project_out(out, kernel.astype(cd))


def time_step(raw, dt_bias):
    """``dt = softplus(dt_raw + dt_bias)``: float32 ``[B, T, H]``, above
    zero, one step a head."""
    return jax.nn.softplus(raw.astype(jnp.float32) + dt_bias)


def split_b_c(b_c):
    """``(B, C)``, each ``[B, T, N]``, of the one group's ``[B, T, 2 N]``
    projection: B, what a position writes along, first."""
    return tuple(jnp.split(b_c, 2, axis=-1))


def gated_norm(y, z, scale, eps, *, heads_axis, n_channels):
    """``RMSNorm(y * SiLU(z)) * scale`` over ALL the layer's channels, the
    gate BEFORE the norm: float32 ``[B, T, H, P]`` for the held heads' ``y``
    and ``z``. The mean square is the one quantity a head-split layer
    exchanges: with ``heads_axis`` the held channels' sum of squares is
    summed over that axis; ``n_channels`` divides it."""
    gated = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
    squares = jnp.sum(gated * gated, axis=(-2, -1), keepdims=True)
    if heads_axis is not None:
        squares = jax.lax.psum(squares, heads_axis)
    return gated * jax.lax.rsqrt(squares / n_channels + eps) * scale


@dataclasses.dataclass(frozen=True)
class StateSpaceSizes:
    """The sizes of the state-space kind (Mamba-2's names in brackets)."""

    n_heads: int           # of the whole layer (mamba_n_heads) ...
    n_held_heads: int      # ... and the block of them held here ...
    held_heads_start: int  # ... from this head
    head_dim: int          # P (mamba_d_head)
    state_dim: int         # N (mamba_d_state); one group of B and C
    conv_size: int         # taps a channel (mamba_d_conv), with a bias
    chunk: int             # positions a chunk of ops/ssd.py's scan
    # The mesh or `vmap` axis the layer's heads are split over, where the
    # gated norm's sum of squares is added up; None: over the heads held.
    heads_axis: str | None = None


class StateSpaceMixer(nn.Module):
    """The held heads of one Mamba-2 layer, ``[B, T, d] -> [B, T, d]``."""

    sizes: StateSpaceSizes
    eps: float
    compute_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        s, cd = self.sizes, self.compute_dtype
        _check_held("StateSpaceMixer", s.n_held_heads, s.held_heads_start,
                    s.n_heads)
        from horovod_tpu import obs

        obs.gauge("hvt_held_heads", float(s.n_held_heads), mixer=SSM)
        obs.gauge("hvt_ssd_chunks",
                  float(ssd.n_chunks(x.shape[1], s.chunk)))
        obs.gauge("hvt_ssd_scan", 1.0, impl="xla")
        held, dim, n = s.n_held_heads, s.head_dim, s.state_dim
        dense = functools.partial(nn.DenseGeneral, use_bias=False, dtype=cd)
        spread = s.conv_size ** -0.5  # the taps' as KDA's; the bias within it
        taps_init = nn.initializers.normal(spread)
        bias_init = functools.partial(
            jax.random.uniform, minval=-spread, maxval=spread)
        with jax.named_scope(SSM_PROJ):
            z = dense((held, dim), name="z_proj")(x)
            inner = dense((held, dim), name="x_proj")(x)
            # One group: every head, on every chip of a group, reads these.
            b_c = dense(2 * n, name="bc_proj")(x)
            a_log = self.param("A_log", _a_log_init, (held,))
            dt = time_step(
                dense(held, name="dt_proj")(x),
                self.param("dt_bias", _dt_bias_init, (held,)))
        with jax.named_scope(SSM_CONV):
            def conv(a, name):
                width = a.shape[2:]
                taps = self.param(
                    f"{name}_conv", taps_init, (s.conv_size,) + width)
                bias = self.param(f"{name}_conv_bias", bias_init, width)
                return nn.silu(short_conv(a, taps) + bias.astype(cd))

            inner, (b, c) = conv(inner, "x"), split_b_c(conv(b_c, "bc"))
        with jax.named_scope(SSM_SCAN):
            y = ssd.ssd_scan(inner, dt, a_log, b, c, chunk=s.chunk)
        with jax.named_scope(SSM_OUT):
            skip = self.param("D", nn.initializers.ones, (held,))
            y = y + skip[:, None] * inner.astype(jnp.float32)
            y = gated_norm(
                y, z, self.param("norm", nn.initializers.ones, (held, dim)),
                self.eps, heads_axis=s.heads_axis,
                n_channels=dim * (
                    held if s.heads_axis is None else s.n_heads))
            kernel = self.param(
                "o_proj", nn.initializers.lecun_normal(in_axis=(0, 1)),
                (held, dim, x.shape[-1]))
            return project_out(y.astype(cd), kernel.astype(cd))


@dataclasses.dataclass(frozen=True)
class AttentionSizes:
    """The sizes of a softmax kind (full or sliding-window)."""

    n_heads: int                 # query heads of the whole layer ...
    n_kv_heads: int
    n_held_heads: int            # ... and the block of them held here ...
    held_heads_start: int        # ... from this head, with their K/V heads
    window: int | None = None    # keys a query reads, itself included
    rotary: Rotary | None = None  # None: no positions


class GatedAttention(nn.Module):
    """The held query heads of one softmax layer with the K/V heads they
    read, ``[B, T, d] -> [B, T, d]``: positions where ``rotary`` is given;
    every key before a query or the ``window`` last; the output gated unless
    ``gate`` is off; the scores scaled by ``scale`` where given, else by
    ``head_dim ** -0.5``."""

    n_heads: int
    n_kv_heads: int
    n_held_heads: int
    held_heads_start: int
    head_dim: int
    compute_dtype: jnp.dtype
    gate: bool = True
    scale: float | None = None
    window: int | None = None
    rotary: Rotary | None = None

    @nn.compact
    def __call__(self, x):
        _check_held("GatedAttention", self.n_held_heads,
                    self.held_heads_start, self.n_heads)
        group = self.n_heads // self.n_kv_heads
        if (self.n_heads % self.n_kv_heads or self.n_held_heads % group
                or self.held_heads_start % group):
            raise ValueError(
                f"GatedAttention: query heads {self.held_heads_start}.."
                f"{self.held_heads_start + self.n_held_heads} do not cover "
                f"whole groups of {self.n_heads} / {self.n_kv_heads} heads "
                "to a K/V head")
        from horovod_tpu import obs

        kind = SOFTMAX if self.window is None else WINDOW
        obs.gauge("hvt_held_heads", float(self.n_held_heads), mixer=kind)
        obs.gauge("hvt_rotary_dims",
                  float(self.rotary.dims if self.rotary else 0), kind=kind)
        if self.window is not None:
            obs.gauge("hvt_attn_window", float(self.window))
        cd, held, dim = self.compute_dtype, self.n_held_heads, self.head_dim
        dense = functools.partial(nn.DenseGeneral, use_bias=False, dtype=cd)
        with jax.named_scope(GQA_SCOPE):
            q = dense((held, dim), name="q_proj")(x)
            k, v = (dense((held // group, dim), name=f"{n}_proj")(x)
                    for n in "kv")
            if self.gate:
                gate_in = dense((held, dim), name="g_proj")(x)
            if self.rotary is not None:
                with jax.named_scope("rope"):
                    positions = jnp.arange(x.shape[1])
                    q, k = (partial_rope(a, positions, self.rotary)
                            for a in (q, k))
            if self.scale is not None:
                # The kernel scales by D^-1/2: the rest rides on q.
                q = q * jnp.asarray(self.scale * dim ** 0.5, cd)
            k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
        if self.window is None:
            out = flash_attention(q, k, v, causal=True)
        else:
            with jax.named_scope(SWA_SCOPE):
                out = flash_attention(
                    q, k, v, causal=True, window=self.window)
        with jax.named_scope(GQA_SCOPE):
            kernel = self.param(
                "o_proj", nn.initializers.lecun_normal(in_axis=(0, 1)),
                (held, dim, x.shape[-1]))
            if self.gate:
                out = _gated(out, gate_in)
            return project_out(out, kernel.astype(cd))


def residual(x, out, multiplier):
    """``x + multiplier * out`` (the multiplier 1: ``x + out``)."""
    return x + (out if multiplier == 1 else out * jnp.asarray(
        multiplier, out.dtype))


class HybridBlock(nn.Module):
    """``x += m mixer(norm(x)); x += m mlp(norm(x))`` with both given
    (unbound: they are adopted here under the names ``mixer`` and
    ``mlp``) and ``m`` the residual multiplier."""

    mixer: nn.Module
    mlp: nn.Module
    eps: float
    compute_dtype: jnp.dtype
    residual_multiplier: float = 1.0

    @nn.compact
    def __call__(self, x):
        norm = functools.partial(
            nn.RMSNorm, epsilon=self.eps, dtype=self.compute_dtype)
        x = residual(x, self.mixer(norm(name="mixer_norm")(x)),
                     self.residual_multiplier)
        return residual(x, self.mlp(norm(name="mlp_norm")(x)),
                        self.residual_multiplier)


class HybridMoELM(nn.Module):
    """Causal LM over integer tokens, ``[B, T] -> [B, T, vocab]`` logits or,
    with ``labels``, per-token ``(loss, correct)``."""

    vocab_size: int
    d_model: int
    layer_kinds: tuple    # LINEAR / SOFTMAX / WINDOW / SSM, one a layer
    head_dim: int         # of the linear and the softmax kinds
    linear_heads: int     # of the whole layer ...
    softmax_heads: int    # (the softmax kind's unless `softmax` is given)
    softmax_kv_heads: int
    n_held_heads: int     # ... and the block of them held here, both mixers
    held_heads_start: int
    conv_size: int        # the linear kind's
    low_rank: int
    kda_chunk: int
    n_routed: int         # the router's width
    experts_per_token: int
    expert_width: int
    shared_width: int
    routed_scaling: float
    n_held: int           # the routed experts held here, a block ...
    held_start: int       # ... from this index
    eps: float
    compute_dtype: jnp.dtype
    fused_head_chunks: int
    sharding: ShardingConfig = ShardingConfig()
    ssm: StateSpaceSizes | None = None  # the state-space kind's sizes
    # The softmax kind's sizes (None: the fields above, no window, no
    # positions) and the sliding-window kind's.
    softmax: AttentionSizes | None = None
    window: AttentionSizes | None = None
    n_dense_layers: int = 0  # leading layers whose MLP is a dense SwiGLU
    dense_width: int = 0
    softmax_gate: bool = True
    softmax_scale: float | None = None  # None: head_dim ** -0.5
    moe_scoring: str = "sigmoid"        # `RoutedExperts.scoring`
    residual_multiplier: float = 1.0
    embedding_multiplier: float = 1.0
    logits_divisor: float = 1.0
    tied_head: bool = False  # the head reads the embedding's table
    remat: bool = False      # every block rematerialised in the backward pass

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, labels=None):
        del train  # no dropout, and the routed layer sows in every mode
        cfg, cd = self.sharding, self.compute_dtype
        if cfg.mesh is not None and cfg.mesh.size > 1:
            raise NotImplementedError(
                f"HybridMoELM on a mesh of {cfg.mesh.size} chips "
                f"({dict(cfg.mesh.shape)}): its layers run on one chip "
                "(ROADMAP R1, R8)")
        kinds = (LINEAR, SOFTMAX, SSM, WINDOW)
        unknown = sorted(set(self.layer_kinds) - set(kinds))
        if unknown or not self.layer_kinds:
            raise ValueError(
                f"layer_kinds {self.layer_kinds!r}: a layer is "
                + " or ".join(map(repr, kinds)))
        if SSM in self.layer_kinds and self.ssm is None:
            raise ValueError(
                f"layer_kinds holds {SSM!r} and `ssm`, the kind's "
                "`StateSpaceSizes`, is not given")
        if WINDOW in self.layer_kinds and (
                self.window is None or self.window.window is None):
            raise ValueError(
                f"layer_kinds holds {WINDOW!r} and `window`, the kind's "
                "`AttentionSizes` with its window, is not given")
        if self.softmax is not None and self.softmax.window is not None:
            raise ValueError(
                f"the {SOFTMAX!r} kind reads every key before a query: its "
                f"sizes give a window ({self.softmax.window})")
        if not 0 <= self.n_dense_layers <= len(self.layer_kinds):
            raise ValueError(
                f"{self.n_dense_layers} leading dense layers of "
                f"{len(self.layer_kinds)}")
        from horovod_tpu import obs

        for kind in kinds:
            obs.gauge("hvt_layer_kinds",
                      float(self.layer_kinds.count(kind)), kind=kind)
        obs.gauge("hvt_remat_blocks",
                  float(len(self.layer_kinds) if self.remat else 0))
        obs.gauge("hvt_tied_head", float(self.tied_head))
        embed = nn.Embed(self.vocab_size, self.d_model, dtype=cd, name="embed")
        x = embed(tokens)
        if self.embedding_multiplier != 1:
            x = x * jnp.asarray(self.embedding_multiplier, cd)
        x = cfg.constrain(x, P(BATCH_AXES, None, None))
        block = nn.remat(HybridBlock) if self.remat else HybridBlock
        for i, kind in enumerate(self.layer_kinds):
            if kind == LINEAR:
                mixer = DeltaAttention(
                    self.linear_heads, self.n_held_heads,
                    self.held_heads_start, self.head_dim, self.conv_size,
                    self.low_rank, self.eps, self.kda_chunk, cd, parent=None)
            elif kind == SSM:
                mixer = StateSpaceMixer(self.ssm, self.eps, cd, parent=None)
            else:
                sizes = self.window if kind == WINDOW else (
                    self.softmax or AttentionSizes(
                        self.softmax_heads, self.softmax_kv_heads,
                        self.n_held_heads, self.held_heads_start))
                mixer = GatedAttention(
                    sizes.n_heads, sizes.n_kv_heads, sizes.n_held_heads,
                    sizes.held_heads_start, self.head_dim, cd,
                    gate=self.softmax_gate, scale=self.softmax_scale,
                    window=sizes.window, rotary=sizes.rotary, parent=None)
            if i < self.n_dense_layers:
                mlp = SwiGLU(self.dense_width, cd, parent=None)
            else:
                mlp = RoutedExperts(
                    n_routed=self.n_routed, k=self.experts_per_token,
                    expert_width=self.expert_width,
                    shared_width=self.shared_width, n_held=self.n_held,
                    held_start=self.held_start,
                    routed_scaling=self.routed_scaling, compute_dtype=cd,
                    sharding=cfg, scoring=self.moe_scoring, parent=None)
            x = block(mixer, mlp, self.eps, cd, self.residual_multiplier,
                      name=f"Block_{i}")(x)
            x = cfg.constrain(x, P(BATCH_AXES, None, None))
        x = nn.RMSNorm(epsilon=self.eps, dtype=cd, name="final_norm")(x)
        if self.logits_divisor != 1:
            # On the head's input: the head is linear, and the logits are
            # never formed whole.
            x = x * jnp.asarray(1.0 / self.logits_divisor, cd)
        head = LMHead(
            self.d_model, self.vocab_size, compute_dtype=cd, sharding=cfg,
            tied=self.tied_head, name="lm_head")
        table = embed.embedding if self.tied_head else None
        if labels is not None:
            return head.fused_loss(
                x, labels, self.fused_head_chunks, table=table)
        return head(x, table=table)
