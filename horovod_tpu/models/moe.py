"""Two routed layers.

* `MoEMlp` (below, first): the GShard/Switch dense-dispatch layer over the
  mesh's ``expert`` axis: softmax gates, a static capacity per expert that
  DROPS what overflows, GELU experts at 4x. What `TransformerLM(moe_every=)`
  and `PipelinedLM` build; its all-to-all is the partitioner's.
* `RoutedExperts` (at the end): one chip's share of a DeepSeek-V3-style
  layer: scores over all the published experts (sigmoid, or the softmax
  over the chosen logits: `RoutedExperts.scoring`), top-k with a
  selection bias that levels each sequence's loads (`level_bias`: the layer
  carries no balancing state; bias and top-k are found by counting, not by
  sorting the logits), gates normalised over the chosen and scaled, the
  (token, choice) pairs that fall on the experts HELD HERE sorted by expert
  and run through a grouped matmul with ragged group sizes (no token
  dropped, no one-hot dispatch), summed back per token by a segment sum
  in token order, SwiGLU experts and a shared expert. What
  `models/latent_moe_lm.py` and `models/hybrid_moe_lm.py` build. On one chip there is no exchange; the
  experts across chips with their all-to-all are ROADMAP R1.

Mixture-of-Experts MLP with expert parallelism over the ``expert`` axis.

The reference has no MoE (SURVEY.md §2.2: dense MLP head only,
tensorflow2_keras_mnist.py:49-51); this fills the framework's reserved
``expert`` mesh axis (parallel/mesh.py) with a first-class layer so EP is a
capability, not a name.

TPU-first design — the GShard/Switch dense-dispatch formulation
(arXiv:2006.16668, 2101.03961; PAPERS.md), which is the shape XLA partitions
well:

* **Static capacity.** Each expert processes a fixed ``capacity`` of tokens
  per batch; routing builds a one-hot dispatch tensor ``[G, E, C]`` and the
  data movement is two einsums. No dynamic shapes, no host round trips —
  everything stays inside the jitted step, scan/vmap-friendly.
* **Sharding, not message passing.** Expert weights are ``[E, ...]`` with E
  sharded over the ``expert`` axis; constraining the dispatched activations
  to ``P('expert', ...)`` makes GSPMD insert the all-to-all over ICI.
* **Router in float32** (bf16 softmax routing is unstable), top-k gating
  with renormalization, Switch-style load-balancing auxiliary loss published
  via ``self.sow('losses', ...)`` — the Trainer adds any sown 'losses'
  collection entries to the objective.
* **Overflow drops are safe by construction**: the transformer block adds
  the MoE output to the residual stream, so a token past capacity
  contributes zero instead of garbage.
* **Two routers.** ``router='top_k'`` (default): tokens pick experts —
  GShard/Switch semantics, capacity overflow possible (observable via
  ``moe_drop_rate``). ``router='expert_choice'`` (Zhou et al.,
  arXiv:2202.09368): each expert picks its top-``capacity`` tokens —
  perfectly load-balanced and drop-free BY CONSTRUCTION (no aux loss
  needed; the observability metric becomes ``moe_uncovered_rate``, the
  fraction of tokens no expert chose). Training-only for causal LMs:
  expert choice ranks tokens across the whole group, so selection of an
  early token depends on later tokens — the known train/inference
  asymmetry of EC routing; the decode path refuses it loudly.
"""

from __future__ import annotations

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops import grouped_matmul as gmm_ops
from horovod_tpu.ops import segment_sum as seg_ops
from horovod_tpu.parallel.mesh import EXPERT_AXIS

# The routed layer's names in the compiled step (`jax.named_scope`: every op
# carries them in its metadata, forward and backward; chipbench/moe_spans.py
# sums by them): SCOPE around the whole layer, and under it its five parts.
SCOPE = "hvt.moe"
ROUTE, DISPATCH, EXPERTS, COMBINE, SHARED = (
    "route", "dispatch", "experts", "combine", "shared")
# `RoutedExperts`' static row budget over the rows level loads put on the
# held experts. `level_bias` keeps the loads level to within chance; what
# still passes the budget is counted.
BUDGET_FACTOR = 2.0


def dispatch_group_count(g: int, group_size: int) -> int:
    """Smallest divisor of ``g`` whose groups stay within ``group_size`` —
    the shared dispatch-grouping contract (used here and by
    `models/pipelined_lm.PipelinedLM`'s in-pipeline MoE, which must group
    identically for pipelined-vs-sequential parity)."""
    for n in range(1, g + 1):
        if g % n == 0 and g // n <= group_size:
            return n
    return g


class MoEMlp(nn.Module):
    """Routed MLP: ``[B, T, d] -> [B, T, d]`` through E expert FFNs.

    Args:
      d_model: model width.
      n_experts: number of experts E (shardable over the ``expert`` axis).
      mlp_ratio: expert hidden width multiplier (reference-style 4x).
      k: experts per token (top-k routing; 1 = Switch, 2 = GShard default).
      capacity_factor: per-expert slots = ``k * G / E * capacity_factor``.
      aux_loss_coef: weight of the load-balancing loss sown into 'losses'.
      sharding: the model's ShardingConfig (constrains via its mesh if set).
    """

    d_model: int
    n_experts: int = 8
    mlp_ratio: int = 4
    k: int = 2
    capacity_factor: float = 1.25
    aux_loss_coef: float = 1e-2
    # 'top_k' (tokens pick experts, GShard/Switch) or 'expert_choice'
    # (experts pick tokens — drop-free, aux-free; see module docstring).
    router: str = "top_k"
    compute_dtype: jnp.dtype = jnp.float32
    sharding: object = None

    # Dispatch group size (GShard's group axis): routing/dispatch one-hots
    # are [S, E, C] with C ∝ S, so grouping keeps dispatch cost LINEAR in
    # token count — one flat group would make it quadratic (C would grow with
    # the whole batch).
    group_size: int = 1024

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        b, t, d = x.shape
        e = self.n_experts
        mesh = getattr(self.sharding, "mesh", None) if self.sharding else None
        if mesh is not None:
            ep = mesh.shape.get(EXPERT_AXIS, 1)
            if e % ep != 0:
                raise ValueError(
                    f"n_experts ({e}) must be divisible by the expert mesh "
                    f"axis ({ep})"
                )
        g = b * t
        n_groups = self._n_groups(g)
        s = g // n_groups  # tokens per dispatch group
        tokens = x.reshape(n_groups, s, d)
        capacity = max(1, int(self.k * s / e * self.capacity_factor))

        # --- routing (float32) ---------------------------------------------
        if self.router not in ("top_k", "expert_choice"):
            raise ValueError(
                f"router must be 'top_k' or 'expert_choice', got "
                f"{self.router!r}"
            )
        router = nn.Dense(
            e, use_bias=False, dtype=jnp.float32, name="router"
        )(tokens.astype(jnp.float32))
        probs = jax.nn.softmax(router, axis=-1)  # [n, S, E]

        if self.router == "expert_choice":
            return self._expert_choice(
                x, tokens, probs, capacity, n_groups, s
            )

        top_probs, top_idx = jax.lax.top_k(probs, self.k)  # [n, S, k]
        if self.k > 1:
            # GShard-style renormalization over the chosen experts. NOT for
            # k=1: p/p == 1 would make the gate constant and cut the router
            # off from the task loss — Switch gating uses the raw prob.
            top_probs = top_probs / (top_probs.sum(-1, keepdims=True) + 1e-9)

        # Switch load-balancing loss: E * sum_e fraction_routed_e * mean_prob_e
        # (top-1 assignment fraction, the standard formulation), meaned over
        # dispatch groups.
        assign1 = jax.nn.one_hot(top_idx[..., 0], e)  # [n, S, E]
        frac = assign1.mean(1)
        aux = (e * jnp.sum(frac * probs.mean(1), axis=-1)).mean()
        if train:
            self.sow("losses", "moe_load_balance", self.aux_loss_coef * aux)

        # --- dispatch plan: position of each (token, choice) in its expert --
        # Per group: one-hot choices [k, S, E] flattened to [k*S, E]; cumsum
        # down the token axis gives each routed token its slot in the
        # expert's capacity buffer; slots >= capacity overflow and drop.
        choice = jnp.moveaxis(
            jax.nn.one_hot(top_idx, e), -2, 1
        )  # [n, k, S, E]
        flat_choice = choice.reshape(n_groups, self.k * s, e)
        pos = jnp.cumsum(flat_choice, axis=1) * flat_choice - 1.0
        pos = pos.reshape(n_groups, self.k, s, e)
        in_cap = (pos >= 0) & (pos < capacity)
        slot = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)

        # combine[n, S, E, C]: gate mass of each token at its expert slot;
        # dispatch is its 0/1 skeleton.
        slot_oh = jax.nn.one_hot(slot, capacity) * in_cap[..., None]  # [n,k,S,E,C]
        # Router drop-rate observability: overflow drops are SAFE (residual
        # stream, zero contribution) but must never be silent — an EP config
        # can be dropping a third of its routed tokens and still "train".
        # Sown into the 'metrics' collection; Trainer averages any sown
        # metrics into the step/epoch logs (train_step requests the
        # collection as mutable; elsewhere the sow is a no-op).
        routed = float(n_groups * self.k * s)
        self.sow(
            "metrics", "moe_drop_rate",
            1.0 - jnp.sum(slot_oh.astype(jnp.float32)) / routed,
        )
        combine = jnp.einsum(
            "nksec,nsk->nsec", slot_oh, top_probs.astype(jnp.float32)
        )
        dispatch = slot_oh.sum(1)  # [n, S, E, C] (choices are disjoint experts)

        # --- expert computation, E sharded over the expert axis -------------
        cd = self.compute_dtype
        expert_in = jnp.einsum(
            "nsec,nsd->necd", dispatch.astype(cd), tokens.astype(cd)
        )  # [n, E, C, d]
        expert_in = self._constrain(expert_in, P(None, EXPERT_AXIS, None, None))
        out = self._experts(expert_in, d)

        # --- combine back to token order -----------------------------------
        mixed = jnp.einsum("nsec,necd->nsd", combine.astype(cd), out)
        return mixed.reshape(b, t, d).astype(x.dtype)

    def _expert_choice(self, x, tokens, probs, capacity, n_groups, s):
        """Expert-choice dispatch: each expert takes its top-``capacity``
        tokens of the group (scores = router softmax over experts, read
        column-wise). Every expert is exactly full — balanced and drop-free
        by construction, so there is no load-balancing aux loss; the
        observability dual of drop-rate is the fraction of tokens NO expert
        chose (they pass through on the residual stream only)."""
        b, t, d = x.shape
        e = self.n_experts
        cd = self.compute_dtype
        capacity = min(capacity, s)  # an expert cannot take a token twice
        # [n, E, S] scores; per-expert top-C over the token axis.
        g_val, g_idx = jax.lax.top_k(
            jnp.moveaxis(probs, -1, 1), capacity
        )  # both [n, E, C]
        dispatch = jax.nn.one_hot(g_idx, s)  # [n, E, C, S]
        # Coverage observability (see docstring).
        chosen = jnp.clip(dispatch.sum((1, 2)), 0.0, 1.0)  # [n, S]
        self.sow(
            "metrics", "moe_uncovered_rate",
            1.0 - jnp.sum(chosen) / float(n_groups * s),
        )
        expert_in = jnp.einsum(
            "necs,nsd->necd", dispatch.astype(cd), tokens.astype(cd)
        )
        expert_in = self._constrain(expert_in, P(None, EXPERT_AXIS, None, None))
        out = self._experts(expert_in, d)
        combine = dispatch * g_val[..., None]  # [n, E, C, S] gated
        mixed = jnp.einsum("necs,necd->nsd", combine.astype(cd), out)
        return mixed.reshape(b, t, d).astype(x.dtype)

    def _experts(self, expert_in, d):
        """The E parallel FFNs over [n, E, C, d] dispatched activations —
        shared by both routers (identical params/layout either way)."""
        cd = self.compute_dtype
        e = self.n_experts
        hidden = self.mlp_ratio * d
        w_up = self.param(
            "moe_up",
            nn.initializers.lecun_normal(batch_axis=(0,)),
            (e, d, hidden),
        )
        w_down = self.param(
            "moe_down",
            nn.initializers.lecun_normal(batch_axis=(0,)),
            (e, hidden, d),
        )
        h = jnp.einsum("necd,edh->nech", expert_in, w_up.astype(cd))
        h = nn.gelu(h)
        out = jnp.einsum("nech,ehd->necd", h, w_down.astype(cd))
        return self._constrain(out, P(None, EXPERT_AXIS, None, None))

    def _n_groups(self, g: int) -> int:
        return dispatch_group_count(g, self.group_size)

    def _constrain(self, v, spec):
        cfg = self.sharding
        if cfg is None or getattr(cfg, "mesh", None) is None:
            return v
        return jax.lax.with_sharding_constraint(
            v, jax.sharding.NamedSharding(cfg.mesh, spec)
        )


class SwiGLU(nn.Module):
    """``W_down(silu(W_gate h) * W_up h)``, no biases (Shazeer,
    arXiv:2002.05202): the dense MLP and the shared expert of the
    DeepSeek-V3 family."""

    width: int
    compute_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        dense = functools.partial(
            nn.Dense, use_bias=False, dtype=self.compute_dtype)
        gate = dense(self.width, name="gate")(h)
        up = dense(self.width, name="up")(h)
        return dense(h.shape[-1], name="down")(nn.silu(gate) * up)


_SIGN = np.uint32(0x80000000)


def _order_key(x):
    """float32 -> uint32 whose integer order is the floats' order (the sign
    and magnitude folded: a negative's bits inverted, the sign bit set on
    the others); ``-0.0`` lies just under ``0.0``."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >= _SIGN, ~bits, bits | _SIGN)


def _key_value(key):
    """`_order_key`'s inverse."""
    bits = jnp.where(key >= _SIGN, key ^ _SIGN, ~key)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def level_bias(logits, k: int):
    """The selection bias that levels the loads of one sequence: for the
    router's logits ``[T, E]``, minus each expert's own logit at the rank
    that leaves it ``T * k / E`` of the T tokens above. Float32 ``[E]``.

    This is where the DeepSeek-V3 ``noaux_tc`` rule (bias += rate *
    sign(mean load - load), a buffer carried from step to step) is headed,
    found in one step instead: whatever the tokens of a sequence share (the
    mean over the prefix that attention hands every late token; a direction
    the stream gains as training moves it) shifts an expert's whole column
    and is taken out with the threshold, and what tells the tokens apart
    decides who goes where. Each expert then has exactly ``T * k / E``
    tokens above zero; the top ``k`` a token are not exactly those, so the
    loads are level to within chance (1.1-1.2 x the mean on the fullest of
    128) as long as what tells the tokens apart is not of a few dimensions.

    Found by counting, not by sorting: the ``above``-th largest value of a
    column is the largest ``v`` with ``count(column >= v) >= above``, and
    that ``v`` is built exactly, from its top bit down, by bisection on the
    floats' order keys (`_order_key`): a pass sets the next bit, counts the
    tokens at or above the candidate, and keeps the bit where the count
    still reaches. 32 passes whatever the data, no sort of ``[T, E]``; the
    value is an element of the column (a column that holds both zeros may
    give either).

    On the logits, not on the scores as the published selection adds it:
    after the sigmoid an expert whose column sits near 0 or 1 has too small
    a slope to compete for any token, and one threshold does not level the
    loads (columns two logits apart: the fullest expert at 3.8 x the
    mean)."""
    t, e = logits.shape
    above = min(t, max(1, round(t * k / e)))
    key = _order_key(logits)

    def narrow(i, found):
        candidate = found | (_SIGN >> i.astype(jnp.uint32))
        reached = jnp.sum(key >= candidate, axis=0, dtype=jnp.int32) >= above
        return jnp.where(reached, candidate, found)

    found = jax.lax.fori_loop(0, 32, narrow, jnp.zeros((e,), jnp.uint32))
    return -_key_value(found)


def _largest(values, k: int):
    """``[..., k]`` int32: the indices of the ``k`` largest along the last
    axis, largest first and a tie to the lower index (`jax.lax.top_k`'s
    indices on finite values), as ``k`` maxima: the first index of the
    largest, then that one lowered to ``-inf``. No sort."""
    index = jax.lax.broadcasted_iota(jnp.int32, values.shape, values.ndim - 1)
    chosen = []
    for _ in range(k):
        first = jnp.argmax(values, axis=-1, keepdims=True).astype(jnp.int32)
        chosen.append(first)
        values = jnp.where(index == first, -jnp.inf, values)
    return jnp.concatenate(chosen, axis=-1)


SIGMOID, SOFTMAX = "sigmoid", "softmax"


def _gates(logits, chosen, *, scoring, scale):
    """``[B, T, k]`` float32: the chosen experts' scores normalised over
    all ``k`` (held here or not) and scaled. ``"sigmoid"``: the logits'
    sigmoids (DeepSeek-V3). ``"softmax"``: the softmax over the CHOSEN
    logits, ``exp(logit - the token's largest chosen logit)`` normalised."""
    values = jax.nn.sigmoid(logits) if scoring == SIGMOID else logits
    # The chosen values by a mask, not `take_along_axis`: its gather and
    # the scatter that is its transpose took 0.8 ms a layer on the v5e, and
    # the scatter reaches the trace with no scope.
    hot = chosen[..., None] == jnp.arange(logits.shape[-1])  # [B, T, k, E]
    picked = jnp.sum(jnp.where(hot, values[..., None, :], 0.0), axis=-1)
    if scoring == SOFTMAX:
        picked = jnp.exp(picked - jax.lax.stop_gradient(
            picked.max(-1, keepdims=True)))
    return picked / (picked.sum(-1, keepdims=True) + 1e-20) * scale


def _route(tokens, router, *, k, scale, scoring=SIGMOID):
    """``(chosen [B, T, k] int32, gates [B, T, k] float32)`` for tokens
    ``[B, T, d]``: the router's logits over ALL the experts in float32, the
    top ``k`` of logit + selection bias (`level_bias`, a sequence at a
    time; `_largest`: both by counting and comparing, no sort), and the
    chosen experts' gates (`_gates`: scores without the bias)."""
    logits = jnp.dot(tokens.astype(jnp.float32), router,
                     precision=jax.lax.Precision.HIGHEST)
    # Selection is not differentiable: the bias is a constant of the step.
    bias = jax.vmap(lambda one: level_bias(one, k))(logits)
    chosen = _largest(jax.lax.stop_gradient(logits + bias[:, None, :]), k)
    gates = _gates(logits, chosen, scoring=scoring, scale=scale)
    return chosen, gates


# The routed rows' two sums back to the tokens, each a `jax.custom_vjp` whose
# rules both enter the part's scope: called where the layer's `hvt.moe` is
# open, every op and the kernel carry ``hvt.moe/dispatch`` or
# ``hvt.moe/combine``, forward and backward (the backward rule is traced
# with the call's scopes, not those opened inside the forward rule).
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
@jax.named_scope(DISPATCH)
def _dispatched(tokens, token_of, order, n):
    """The rows ``tokens[token_of]``; backward, each token's rows summed
    (`seg_ops.segment_sum` in float32, rounded once)."""
    return tokens[token_of]


@jax.named_scope(DISPATCH)
def _dispatched_fwd(tokens, token_of, order, n):
    return tokens[token_of], order


@jax.named_scope(DISPATCH)
def _dispatched_bwd(n, order, drows):
    return seg_ops.segment_sum(drows, None, order, n, drows.dtype), None, None


_dispatched.defvjp(_dispatched_fwd, _dispatched_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
@jax.named_scope(COMBINE)
def _combined(rows, weight, token_of, order, n, dtype):
    """Each token's rows times their float32 weights, summed in float32
    and rounded once to ``dtype`` (`seg_ops.segment_sum`); backward, the
    cotangent gathered to the rows and its products with them."""
    return seg_ops.segment_sum(rows, weight, order, n, dtype)


@jax.named_scope(COMBINE)
def _combined_fwd(rows, weight, token_of, order, n, dtype):
    return (seg_ops.segment_sum(rows, weight, order, n, dtype),
            (rows, weight, token_of))


@jax.named_scope(COMBINE)
def _combined_bwd(n, dtype, residuals, dmixed):
    rows, weight, token_of = residuals
    spread = dmixed.astype(jnp.float32)[token_of]
    return ((spread * weight[:, None]).astype(rows.dtype),
            jnp.sum(spread * rows.astype(jnp.float32), axis=-1), None, None)


_combined.defvjp(_combined_fwd, _combined_bwd)


def _held_experts(x, router, w_gate_up, w_down, *, k, scale, held_start,
                  budget, compute_dtype, scoring=SIGMOID):
    """The held experts' part of the layer's output for ``x`` [B, T, d],
    and the three counts: the router's choices and gates (`_route`), then
    `_through_held`."""
    d = x.shape[-1]
    n = x.size // d
    n_held = w_down.shape[0]
    with jax.named_scope(ROUTE):
        chosen, gates = _route(x, router, k=k, scale=scale, scoring=scoring)
    mixed, counts = _through_held(
        x.reshape(n, d), chosen.reshape(n, k), gates.reshape(n, k), w_gate_up,
        w_down, held_start=held_start, budget=budget,
        compute_dtype=compute_dtype)
    held = jnp.sum(counts)
    stats = {
        "moe_overflow_rows": jnp.maximum(held - budget, 0),
        "moe_held_rows_share": held / (n * k),
        "moe_load_max_over_mean": jnp.max(counts) * n_held / jnp.maximum(
            held, 1),
    }
    stats = {name: jnp.asarray(v, jnp.float32) for name, v in stats.items()}
    return mixed.reshape(x.shape), stats


def _through_held(tokens, chosen, gates, w_gate_up, w_down, *, held_start,
                  budget, compute_dtype):
    """``([n, d] in ``compute_dtype``, the held experts' row counts)`` for
    tokens ``[n, d]`` that chose ``chosen`` [n, k] under ``gates`` [n, k]:
    the (token, choice) pairs that fall on experts ``held_start ..
    held_start + n_held`` are sorted by expert, the first ``budget`` of
    them gathered, pushed through two grouped matmuls and summed back per
    token under their gates; a pair past the budget adds nothing. Both
    sums back to the tokens (the combine, and the gather's transpose) are
    one segment sum over the rows in token order (`seg_ops.segment_sum`),
    whose order is made once."""
    n, k = chosen.shape
    n_held = w_down.shape[0]
    with jax.named_scope(DISPATCH):
        local = chosen.reshape(-1) - held_start
        # A pair on an expert held elsewhere sorts behind every held one.
        local = jnp.where((local >= 0) & (local < n_held), local, n_held)
        order = jnp.argsort(local, stable=True)[:budget]
        # (fewer pairs than one row tile: the rest are rows past the total)
        order = jnp.pad(order, (0, budget - order.size))
        counts = gmm_ops.group_sizes_of(local, n_held)
        # ... and the groups are cut at the budget, last expert first.
        ends = jnp.minimum(jnp.cumsum(counts), budget)
        sizes = jnp.diff(ends, prepend=0)
        token_of = order // k
        # Rows past the groups' total belong to no token: the grouped
        # matmul gives them zeros, forward and backward.
        by_token = seg_ops.token_order(order, ends[-1], n, k)
        tokens = tokens.astype(compute_dtype)
    rows = _dispatched(tokens, token_of, by_token, n)
    with jax.named_scope(EXPERTS):
        hidden = gmm_ops.grouped_matmul(
            rows, w_gate_up.astype(compute_dtype), sizes)
        gate, up = jnp.split(hidden, 2, axis=-1)
        out = gmm_ops.grouped_matmul(
            nn.silu(gate) * up, w_down.astype(compute_dtype), sizes)
    with jax.named_scope(COMBINE):
        weight = gates.reshape(-1)[order]
    # Summed in float32: a token's up to k experts meet here.
    return _combined(out, weight, token_of, by_token, n, compute_dtype), counts


class RoutedExperts(nn.Module):
    """One chip's share of a routed expert layer with a shared expert:
    ``[B, T, d] -> [B, T, d]``, ``sum over chosen AND held of gate_e *
    Expert_e(h) + Shared(h)``.

    The router scores all ``n_routed`` experts and picks ``k`` a token; this
    chip holds the contiguous block ``held_start .. held_start + n_held``
    and computes their part of the sum. What the absent experts would add
    is left out: on one chip there is no exchange, and nothing here stands
    in for the other chips. With ``n_held == n_routed`` it is the whole
    layer. One chip only: a mesh of more is refused (the batch split over
    chips comes with the cell that measures it, the experts across chips
    with their exchange: ROADMAP R1).

    The selection bias of the DeepSeek-V3 ``noaux_tc`` method is no
    parameter here: `level_bias` solves it anew in every step, a sequence
    at a time, from the step's own logits, so the loads are level from the
    first step, whatever the initialisation and the learning rate (from
    random weights at the published ``initializer_range`` the fullest of 16
    held experts saw 3-5.5 x the mean, and AdamW at 1e-4 put every token on
    the same few experts within 20 steps: v5e, PERF.md, PR 33). The bias
    of a sequence looks at all its tokens, later ones too: this is a
    training layer, as expert-choice routing is (a decode path needs the
    published buffer carried from training: ROADMAP R1).

    No token is dropped while the rows routed here stay within the static
    row budget, `BUDGET_FACTOR` x the expected ``N * k * n_held /
    n_routed``; past it the overflow is counted in the sown metric
    ``moe_overflow_rows`` and those rows add nothing. Also sown:
    ``moe_held_rows_share`` (rows that fell on held experts over N * k) and
    ``moe_load_max_over_mean`` (the fullest held expert's rows over the
    mean). The gauge ``hvt_moe_experts{kind}`` says at trace time how many
    experts are held and routed.

    ``scoring`` is how the chosen experts' gates are scored (`_gates`; the
    gauge ``hvt_moe_gate{scoring}``): ``"sigmoid"``, the logits' sigmoids
    normalised over the chosen (DeepSeek-V3), or ``"softmax"``, the softmax
    over the chosen logits (Granite-4.0: the gates add up to the scale).
    The selection, and its bias, are the same either way, and made by
    counting and comparing (`level_bias`, `_largest`; the gauge
    ``hvt_moe_selection{impl="count"}``): nothing on this path sorts the
    ``[T, E]`` logits. The expert rows come back to their tokens, forward
    and in the gather's transpose, by one segment sum in token order
    (`_through_held`; the gauge ``hvt_moe_combine{impl="segment"}``):
    nothing on this path scatter-adds into ``[T, d]``.
    """

    n_routed: int
    k: int
    expert_width: int
    shared_width: int
    n_held: int
    held_start: int
    routed_scaling: float
    compute_dtype: jnp.dtype = jnp.float32
    sharding: object = None
    scoring: str = SIGMOID  # or SOFTMAX: over the chosen logits (`_gates`)

    @nn.compact
    @jax.named_scope(SCOPE)
    def __call__(self, x):
        b, t, d = x.shape
        if self.scoring not in (SIGMOID, SOFTMAX):
            raise ValueError(
                f"RoutedExperts: scoring {self.scoring!r} is neither "
                f"{SIGMOID!r} nor {SOFTMAX!r}")
        if not 0 <= self.held_start <= self.n_routed - self.n_held:
            raise ValueError(
                f"experts {self.held_start}.."
                f"{self.held_start + self.n_held} are not a block of the "
                f"{self.n_routed} routed ones")
        mesh = getattr(self.sharding, "mesh", None)
        if mesh is not None and mesh.size > 1:
            raise NotImplementedError(
                f"RoutedExperts on a mesh of {mesh.size} chips "
                f"({dict(mesh.shape)}): it runs on one chip; experts across "
                "chips need the token exchange, and the batch split over "
                "chips comes with the cell that measures it (ROADMAP R1)")
        from horovod_tpu import obs

        obs.gauge("hvt_moe_experts", float(self.n_held), kind="held")
        obs.gauge("hvt_moe_experts", float(self.n_routed), kind="routed")
        obs.gauge("hvt_moe_gate", 1.0, scoring=self.scoring)
        obs.gauge("hvt_moe_selection", 1.0, impl="count")
        obs.gauge("hvt_moe_combine", 1.0, impl="segment")

        router = self.param(
            "router", nn.initializers.lecun_normal(), (d, self.n_routed))
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        w_gate_up = self.param(
            "experts_gate_up", init, (self.n_held, d, 2 * self.expert_width))
        w_down = self.param(
            "experts_down", init, (self.n_held, self.expert_width, d))

        n = b * t
        expected = n * self.k * self.n_held / self.n_routed
        budget = gmm_ops.row_budget(
            min(n * self.k, math.ceil(BUDGET_FACTOR * expected)))
        mixed, stats = _held_experts(
            x, router, w_gate_up, w_down, k=self.k,
            scale=self.routed_scaling, held_start=self.held_start,
            budget=budget, compute_dtype=self.compute_dtype,
            scoring=self.scoring)
        for name, value in stats.items():
            self.sow("metrics", name, value)
        with jax.named_scope(SHARED):
            mixed = mixed + SwiGLU(
                self.shared_width, self.compute_dtype, name="shared")(x)
        return mixed.astype(x.dtype)
