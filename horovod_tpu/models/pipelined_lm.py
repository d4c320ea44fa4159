"""Decoder-only LM partitioned into pipeline stages over the ``pipe`` axis.

Companion to `parallel/pipeline.py` (see its module docstring for the
design): this model keeps every transformer-block parameter as a
``[n_layers, ...]`` stack. Sharding dim 0 over ``pipe`` gives each pipe
device a contiguous block of layers — its stage — and the GPipe schedule
runs as one `shard_map`'d scan with `ppermute` handoffs. Embedding, final
LayerNorm and the LM head stay replicated over ``pipe`` (they run on the
broadcast pipeline output).

The block math matches `transformer.Block` (pre-LN, RoPE, GELU MLP at 4x)
but is written functionally over explicit parameter stacks: flax modules
trace parameter creation structurally, which fights the stage-sliced manual
region; plain `self.param` stacks are transparent to shard_map, to the
optimizer, and to checkpointing.

Composes with data parallelism (batch axes sharded by GSPMD outside the
manual pipe region) and, since round 3, with Megatron tensor parallelism
INSIDE each stage (qkv/mlp_up column-parallel, attn_out/mlp_down
row-parallel over ``model``, one psum per residual join) AND with
sequence/context parallelism: activations shard their token dim over
``seq`` and every stage's attention runs as ring-flash collectives around
the seq ring — dp x pp x tp x sp on ONE mesh, so a pipelined model serves
the same long contexts the flat `TransformerLM` does.

``mlp='moe'`` swaps every block's dense MLP for a GShard dense-dispatch
MoE (the `models/moe.py` formulation, Mixtral-style every-layer routing)
written functionally over ``[n_layers, E, ...]`` expert stacks: E shards
over the ``expert`` mesh axis INSIDE the manual pipeline region (each
expert-rank routes identically in f32, slices its experts' columns of the
dispatch/combine one-hots, runs its expert FFNs — hidden dim additionally
Megatron-sharded over ``model`` when TP is live — and ONE
psum(expert×model) per block restores the residual), so dp x pp x ep (x
tp x sp) compose on ONE mesh. The router's load-balance aux loss and
drop-rate counters ride the schedules' differentiable ``with_aux``
channel out of the manual region (`parallel/pipeline.py`) and surface
through the standard sown 'losses'/'metrics' collections.
"""

from __future__ import annotations

import flax.linen as nn
import jax

import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.models.transformer import _rope, packed_positions
from horovod_tpu.parallel.mesh import (
    DATA_AXIS,
    EXPERT_AXIS,
    FSDP_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
    SEQ_AXIS,
)
from horovod_tpu.parallel.pipeline import (
    interleaved_layer_order,
    spmd_pipeline,
    spmd_pipeline_1f1b,
    spmd_pipeline_interleaved,
    stage_slice_size,
)

BATCH_AXES = (DATA_AXIS, FSDP_AXIS)


def _layernorm(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + eps) * scale).astype(x.dtype)


class PipelinedLM(nn.Module):
    """Causal LM ``[B, T] -> [B, T, vocab]`` with pipeline-parallel blocks.

    ``n_micro`` microbatches per step (bubble fraction shrinks as it grows);
    the global batch must be divisible by ``n_micro × dp``.
    """

    vocab_size: int = 256
    d_model: int = 256
    n_heads: int = 8
    n_layers: int = 4
    n_micro: int = 4
    # Sliding-window (local) attention inside every stage — same band
    # semantics as TransformerLM.window (global positions; exact through
    # the stage-internal ring when sp > 1). None = full causal.
    window: int | None = None
    compute_dtype: jnp.dtype = jnp.float32
    mesh: Mesh | None = None
    # 'gpipe' = AD-derived backward (parallel/pipeline.spmd_pipeline);
    # '1f1b' = hand-scheduled staggered backward with per-microbatch
    # rematerialization — the 1F1B activation-memory discipline
    # (spmd_pipeline_1f1b). Identical math; parity-tested gradients.
    # 'interleaved' = virtual-stage schedule (spmd_pipeline_interleaved):
    # each pipe device hosts `n_virtual` non-adjacent chunks, cutting the
    # fill bubble to (S-1)/(v*T + S-1). NOTE: on a live pipe mesh the layer
    # stacks are stored in PLACEMENT order (device-major) — convert with
    # to_logical_order/to_interleaved_order when moving checkpoints between
    # schedules.
    schedule: str = "gpipe"
    n_virtual: int = 2
    # 'dense' = reference-style GELU MLP at 4x; 'moe' = every block's MLP
    # routed through n_experts expert FFNs (GShard top-k dense dispatch,
    # experts sharded over the `expert` mesh axis — see module docstring).
    # All-blocks routing (not moe_every) because the schedule scans ONE
    # homogeneous parameter stack per stage; alternate dense/MoE layers
    # would make the stack heterogeneous. Use TransformerLM for moe_every.
    mlp: str = "dense"
    n_experts: int = 8
    moe_k: int = 2
    capacity_factor: float = 1.25
    moe_aux_coef: float = 1e-2
    # Dispatch group size: routing one-hots are [groups, S, E, C] with
    # C ∝ S, so grouping keeps dispatch cost linear in token count (same
    # contract as models/moe.py). Groups are contiguous chunks of this
    # shard's token stream — for bit-parity between pipelined and
    # sequential runs pick a size dividing every shard's tokens-per-
    # microbatch the same way.
    moe_group_size: int = 1024

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, segment_ids=None):
        d, h = self.d_model, self.n_heads
        hd = d // h
        L = self.n_layers
        lecun = nn.initializers.lecun_normal()
        ones = nn.initializers.ones

        if self.mlp not in ("dense", "moe"):
            raise ValueError(
                f"mlp must be 'dense' or 'moe', got {self.mlp!r} (the "
                "pipeline stacks one homogeneous block: RoutedExperts, "
                "SwiGLU and latent attention, models/latent_moe_lm.py, and "
                "DeltaAttention, GatedAttention, StateSpaceMixer, "
                "models/hybrid_moe_lm.py, "
                "whose stages would be of unequal cost, are not among its "
                "layers; ROADMAP D1, R8)")
        moe = self.mlp == "moe"
        blocks = {
            "ln1": self.param("ln1", ones, (L, d)),
            "qkv": self.param("qkv", lecun, (L, d, 3 * d)),
            "attn_out": self.param("attn_out", lecun, (L, d, d)),
            "ln2": self.param("ln2", ones, (L, d)),
        }
        if moe:
            e = self.n_experts
            blocks["router"] = self.param(
                "router",
                nn.initializers.lecun_normal(batch_axis=(0,)),
                (L, d, e),
            )
            blocks["moe_up"] = self.param(
                "moe_up",
                nn.initializers.lecun_normal(batch_axis=(0, 1)),
                (L, e, d, 4 * d),
            )
            blocks["moe_down"] = self.param(
                "moe_down",
                nn.initializers.lecun_normal(batch_axis=(0, 1)),
                (L, e, 4 * d, d),
            )
        else:
            blocks["mlp_up"] = self.param("mlp_up", lecun, (L, d, 4 * d))
            blocks["mlp_down"] = self.param(
                "mlp_down", lecun, (L, 4 * d, d)
            )
        embed = self.param(
            "embed", nn.initializers.normal(1.0), (self.vocab_size, d)
        )
        ln_f = self.param("ln_f", ones, (d,))
        lm_head = self.param("lm_head", lecun, (d, self.vocab_size))

        b, t = tokens.shape
        cd = self.compute_dtype
        x = embed[tokens].astype(cd)  # [B, T, d]
        # Packed sequences: per-document RoPE restart + segment-masked
        # attention inside every stage (the ids are per-microbatch CONSTANTS
        # — they never ride the stage ring; see spmd_pipeline extras).
        positions = (
            packed_positions(segment_ids) if segment_ids is not None else None
        )

        # Validate unconditionally: a typo'd schedule on a pipe-less mesh
        # would otherwise train silently via the sequential path and only
        # error when the config moves to a real pipeline mesh.
        if self.schedule not in ("gpipe", "1f1b", "interleaved"):
            raise ValueError(
                f"schedule must be 'gpipe', '1f1b' or 'interleaved', "
                f"got {self.schedule!r}"
            )

        # Validate expert-axis compatibility unconditionally (like the
        # schedule check above): a config must fail the same way whether it
        # lands on a pipe mesh or the sequential path.
        if self.mesh is not None:
            mesh_ep = self.mesh.shape.get(EXPERT_AXIS, 1)
            if mesh_ep > 1 and not moe:
                raise ValueError(
                    f"mesh has expert={mesh_ep} but mlp={self.mlp!r}; the "
                    f"expert axis needs mlp='moe'"
                )
            if moe and self.n_experts % mesh_ep != 0:
                raise ValueError(
                    f"n_experts ({self.n_experts}) must divide over the "
                    f"expert axis ({mesh_ep})"
                )

        aux_loss = fill = None
        if self.mesh is None or self.mesh.shape.get(PIPE_AXIS, 1) == 1:
            # No pipe axis: run the stack sequentially (the n_stages=1
            # degenerate schedule) — same math, no manual region needed.
            # With MoE, expert stacks may still be GSPMD-sharded over
            # `expert` via param_specs; the dispatch einsums partition
            # automatically (ep=1 math, compiler-inserted collectives).
            def body(xc, p):
                res = self._block(
                    xc, p, seg=segment_ids, positions=positions
                )
                return (res[0], res[1]) if moe else (res, None)

            x, auxs = lax.scan(body, x, blocks)
            if moe:
                aux_loss = auxs["aux"].sum()      # per-layer sow semantics
                fill = auxs["fill"].mean()
        else:
            ep = self.mesh.shape.get(EXPERT_AXIS, 1)
            sp = self.mesh.shape.get(SEQ_AXIS, 1)
            if t % sp != 0:
                raise ValueError(
                    f"seq length ({t}) must divide over the seq axis ({sp})"
                )
            tp = self.mesh.shape.get(MODEL_AXIS, 1)
            if tp > 1 and (h % tp or (4 * d) % tp):
                raise ValueError(
                    f"n_heads ({h}) and 4*d_model ({4 * d}) must divide "
                    f"over the model axis ({tp}) for in-stage TP"
                )
            n_stages = self.mesh.shape[PIPE_AXIS]
            stage_slice_size(L, n_stages)  # validates divisibility
            # Tiny batches (e.g. the Trainer's dp-sized init probe) can't
            # fill the microbatch queue; degrade the schedule, not the user.
            # Each microbatch must still cover the data axes (its batch dim
            # is sharded over them inside the manual region).
            dp = self.mesh.shape[DATA_AXIS] * self.mesh.shape[FSDP_AXIS]
            n_micro = max(1, min(self.n_micro, b // dp))
            if b % (n_micro * dp) != 0:
                raise ValueError(
                    f"batch ({b}) must divide into n_micro ({n_micro}) x "
                    f"data axes ({dp})"
                )
            mb = b // n_micro
            x_micro = x.reshape(n_micro, mb, t, d)
            extras = None
            if segment_ids is not None:
                extras = (
                    segment_ids.reshape(n_micro, mb, t),
                    positions.reshape(n_micro, mb, t),
                )

            # Activations shard their token dim over `seq` inside the manual
            # region; each stage's attention is then a ring-flash collective
            # around the seq ring (_block), the pp handoffs ppermute only
            # over `pipe` — same (pipe, seq) grid position, next stage.
            act_spec = P(None, BATCH_AXES, SEQ_AXIS, None)
            # Stage stacks over `pipe` on dim 0 + Megatron column/row TP
            # over `model` inside each stage (_TP_DIM; activations stay
            # replicated across model, each rank computing its head/feature
            # slice with one psum per residual join in _block) + expert
            # stacks over `expert` on their E dim.
            specs = _stack_specs(tp > 1)
            stack_param_specs = {
                k: P(PIPE_AXIS, *specs[k]) for k in blocks
            }

            # Interleaved: L must split into S*v chunks, and the wrap
            # register-file timing needs n_micro >= n_stages. Degrading v
            # to 1 would apply the PLACEMENT-ordered stacks contiguously —
            # a permuted layer composition, a different function — so it is
            # allowed only during flax's shape-only init probe (values are
            # discarded there); a real forward with too few microbatches
            # fails loudly instead.
            v_eff = 1
            if self.schedule == "interleaved":
                if L % (n_stages * self.n_virtual) != 0:
                    raise ValueError(
                        f"n_layers ({L}) must divide into pipe "
                        f"({n_stages}) x n_virtual ({self.n_virtual}) chunks"
                    )
                if n_micro >= n_stages:
                    v_eff = self.n_virtual
                elif not self.is_initializing():
                    raise ValueError(
                        f"interleaved schedule needs n_micro ({n_micro}, "
                        f"after batch clamping) >= pipe ({n_stages}); "
                        f"raise the batch or n_micro"
                    )

            def run(stage_params, xm, ex=None):
                def stage(params, act, extra=None):
                    seg, pos = extra if extra is not None else (None, None)

                    def body(a, p):
                        res = self._block(
                            a, p, tp=tp, sp=sp, ep=ep, seg=seg, positions=pos
                        )
                        return (res[0], res[1]) if moe else (res, None)

                    a, auxs = lax.scan(body, act, params)
                    if moe:
                        # This stage's layers, summed (per-layer sow adds).
                        return a, jax.tree.map(lambda v: v.sum(0), auxs)
                    return a

                # Uniform branch: `schedule` is module CONFIG, identical
                # on every rank — the pipeline variants legitimately
                # issue different collective counts.
                if self.schedule == "interleaved":  # hvt: noqa[HVT007]
                    chunked = jax.tree.map(
                        lambda p: p.reshape(
                            (v_eff, p.shape[0] // v_eff) + p.shape[1:]
                        ),
                        stage_params,
                    )
                    res = spmd_pipeline_interleaved(
                        stage, chunked, xm, n_virtual=v_eff, extras=ex,
                        with_aux=moe,
                    )
                elif self.schedule == "1f1b":
                    res = spmd_pipeline_1f1b(
                        stage, stage_params, xm, extras=ex, with_aux=moe
                    )
                elif ex is None:
                    res = spmd_pipeline(
                        lambda act: stage(stage_params, act), xm,
                        with_aux=moe,
                    )
                else:
                    res = spmd_pipeline(
                        lambda act, e: stage(stage_params, act, e), xm,
                        extras=ex, with_aux=moe,
                    )
                if not moe:
                    return res
                xm_out, aux = res
                # Stages hold disjoint layers: SUM over pipe. Shards hold
                # disjoint token groups: MEAN over data/fsdp/seq. Expert and
                # model ranks computed routing identically (pre-slice), so
                # the result is replicated over every mesh axis.
                aux = jax.tree.map(
                    lambda v: lax.pmean(
                        lax.psum(v, PIPE_AXIS),
                        (DATA_AXIS, FSDP_AXIS, SEQ_AXIS),
                    ),
                    aux,
                )
                return xm_out, aux

            extra_spec = P(None, BATCH_AXES, SEQ_AXIS)
            args = (blocks, x_micro)
            in_specs = (stack_param_specs, act_spec)
            if extras is not None:
                args += (extras,)
                in_specs += ((extra_spec, extra_spec),)
            out = jax.shard_map(
                run,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=(act_spec, P()) if moe else act_spec,
                check_vma=False,
            )(*args)
            if moe:
                x_micro, aux_tree = out
                aux_loss = aux_tree["aux"] / n_micro
                fill = aux_tree["fill"] / (L * n_micro)
            else:
                x_micro = out
            x = x_micro.reshape(b, t, d)

        if moe:
            if train:
                self.sow(
                    "losses", "moe_load_balance",
                    self.moe_aux_coef * aux_loss,
                )
            self.sow("metrics", "moe_drop_rate", 1.0 - fill)

        x = _layernorm(x, ln_f)
        logits = x.astype(jnp.float32) @ lm_head.astype(jnp.float32)
        return logits

    def _block(self, x, p, tp: int = 1, sp: int = 1, ep: int = 1,
               seg=None, positions=None):
        """One pre-LN transformer block over a single layer's params.

        ``tp > 1`` = Megatron TP inside the (fully-manual) pipeline region:
        this model-rank's param slices are column-parallel for qkv/mlp_up
        (each rank owns ``h/tp`` heads / ``4d/tp`` features) and
        row-parallel for attn_out/mlp_down, with ONE `psum` over ``model``
        per residual join restoring the replicated activation.

        ``sp > 1`` = sequence parallelism inside the stage: ``x`` is this
        device's ``[mb, T/sp, d]`` token shard, RoPE positions carry the
        shard's global offset, and attention runs as `ring_flash_attention`
        around the ``seq`` ring (packed ``seg`` ids ride the ring with
        their K/V blocks)."""
        mb, t, d = x.shape
        h_local = self.n_heads // tp
        hd = d // self.n_heads
        cd = self.compute_dtype

        hidden = _layernorm(x, p["ln1"])
        qkv = hidden @ p["qkv"].astype(cd)  # [mb, T, 3d/tp]
        qkv = qkv.reshape(mb, t, h_local, 3 * hd)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        if positions is None:
            base = lax.axis_index(SEQ_AXIS) * t if sp > 1 else 0
            positions = base + jnp.broadcast_to(
                jnp.arange(t, dtype=jnp.int32), (mb, t)
            )
        q, k = _rope(q, positions), _rope(k, positions)
        # Flash kernel (O(T) memory): without it a pipeline stage would
        # materialize [T, T] scores per microbatch and PP could not compose
        # with the long contexts it exists to serve; dense fallback applies
        # automatically when the kernel's tiling doesn't hold (tiny tests).
        # With a live seq axis the same kernel runs per-hop inside the ring
        # (the within-chip and cross-chip halves of one online softmax).
        from horovod_tpu.ops import attention as attention_ops
        from horovod_tpu.ops.flash_attention import flash_attention

        if sp > 1:
            att = attention_ops.ring_flash_attention(
                q, k, v, axis_name=SEQ_AXIS, causal=True, segment_ids=seg,
                window=self.window,
            )
        else:
            att = flash_attention(
                q, k, v, causal=True,
                q_segment_ids=seg, kv_segment_ids=seg, window=self.window,
            )  # [mb, T, H/tp, hd]
        out = att.reshape(mb, t, h_local * hd) @ p["attn_out"].astype(cd)
        if tp > 1:
            out = lax.psum(out, MODEL_AXIS)
        x = x + out

        hidden = _layernorm(x, p["ln2"])
        if "moe_up" in p:
            mixed, aux = self._moe_mlp(hidden, p, ep=ep, tp=tp)
            return x + mixed, aux
        hidden = nn.gelu(hidden @ p["mlp_up"].astype(cd))
        down = hidden @ p["mlp_down"].astype(cd)
        if tp > 1:
            down = lax.psum(down, MODEL_AXIS)
        return x + down

    def _moe_mlp(self, x, p, ep: int, tp: int):
        """GShard dense-dispatch MoE over one layer's expert stacks.

        Functional mirror of `models/moe.py` (same routing, capacity and
        aux-loss math — see its docstring for the design rationale), written
        for the pipeline's manual region: ``p['moe_up']/['moe_down']`` are
        this expert-rank's ``[E/ep, d, 4d/tp-or-4d]`` slices (sharded by the
        shard_map in_specs), routing runs identically on every rank from the
        replicated f32 router, and each rank contracts only its experts'
        columns of the dispatch/combine one-hots — the cross-rank combine is
        ONE psum over (expert, model) per block. Returns ``(mixed [mb,T,d],
        {'aux': load-balance loss (group mean), 'fill': kept-slot
        fraction})``.
        """
        mb, t, d = x.shape
        e, k = self.n_experts, self.moe_k
        g = mb * t
        n = self._n_groups(g)
        s = g // n
        capacity = max(1, int(k * s / e * self.capacity_factor))
        cd = self.compute_dtype
        tokens = x.reshape(n, s, d)

        # --- routing (float32, replicated across expert/model ranks) ------
        logits = tokens.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)  # [n, S, E]
        top_probs, top_idx = lax.top_k(probs, k)
        if k > 1:
            # GShard renormalization over the chosen experts; NOT for k=1 —
            # Switch gating uses the raw prob so the router stays coupled
            # to the task loss.
            top_probs = top_probs / (top_probs.sum(-1, keepdims=True) + 1e-9)

        assign1 = jax.nn.one_hot(top_idx[..., 0], e)
        frac = assign1.mean(1)
        aux = (e * jnp.sum(frac * probs.mean(1), axis=-1)).mean()

        # --- dispatch plan (cumsum slotting; overflow past capacity drops) -
        choice = jnp.moveaxis(jax.nn.one_hot(top_idx, e), -2, 1)  # [n,k,S,E]
        flat_choice = choice.reshape(n, k * s, e)
        pos = jnp.cumsum(flat_choice, axis=1) * flat_choice - 1.0
        pos = pos.reshape(n, k, s, e)
        in_cap = (pos >= 0) & (pos < capacity)
        slot = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
        slot_oh = jax.nn.one_hot(slot, capacity) * in_cap[..., None]
        fill = jnp.sum(slot_oh.astype(jnp.float32)) / float(n * k * s)
        combine = jnp.einsum(
            "nksec,nsk->nsec", slot_oh, top_probs.astype(jnp.float32)
        )
        dispatch = slot_oh.sum(1)  # [n, S, E, C]

        # --- this rank's experts only ---------------------------------------
        if ep > 1:
            e_loc = e // ep
            off = lax.axis_index(EXPERT_AXIS) * e_loc
            dispatch = lax.dynamic_slice_in_dim(dispatch, off, e_loc, axis=2)
            combine = lax.dynamic_slice_in_dim(combine, off, e_loc, axis=2)
        expert_in = jnp.einsum(
            "nsec,nsd->necd", dispatch.astype(cd), tokens.astype(cd)
        )
        h = nn.gelu(
            jnp.einsum("necd,edh->nech", expert_in, p["moe_up"].astype(cd))
        )
        out = jnp.einsum("nech,ehd->necd", h, p["moe_down"].astype(cd))
        mixed = jnp.einsum("nsec,necd->nsd", combine.astype(cd), out)
        if ep > 1 or tp > 1:
            axes = tuple(
                ax for ax, live in
                ((EXPERT_AXIS, ep > 1), (MODEL_AXIS, tp > 1)) if live
            )
            mixed = lax.psum(mixed, axes)
        return (
            mixed.reshape(mb, t, d).astype(x.dtype),
            {"aux": aux, "fill": fill},
        )

    def _n_groups(self, g: int) -> int:
        from horovod_tpu.models.moe import dispatch_group_count

        return dispatch_group_count(g, self.moe_group_size)


# Per-stack TP layout (dims AFTER the leading [n_layers] stack dim):
# column-parallel kernels shard their OUTPUT dim over `model`, row-parallel
# their INPUT dim; LayerNorm scales replicate. Expert stacks [E, ...] shard
# E over `expert` (their hidden dim over `model` when TP is live); the tiny
# router replicates.
_TP_DIM = {"qkv": 1, "mlp_up": 1, "attn_out": 0, "mlp_down": 0}
_STACKED = (
    "ln1", "qkv", "attn_out", "ln2", "mlp_up", "mlp_down",
    "router", "moe_up", "moe_down",
)


def _stack_specs(tp: bool) -> dict:
    """{name: trailing-dims spec tuple} for every possible per-layer stack
    (dense and MoE alike — callers index by the stacks they created)."""
    out = {}
    for name in ("ln1", "qkv", "attn_out", "ln2", "mlp_up", "mlp_down"):
        ndim = 1 if name.startswith("ln") else 2
        spec = [None] * ndim
        if tp and name in _TP_DIM:
            spec[_TP_DIM[name]] = MODEL_AXIS
        out[name] = tuple(spec)
    out["router"] = (None, None)
    out["moe_up"] = (EXPERT_AXIS, None, MODEL_AXIS if tp else None)
    out["moe_down"] = (EXPERT_AXIS, MODEL_AXIS if tp else None, None)
    return out


def _reorder_stacks(params, order):
    """Apply a row permutation to every per-layer stack leaf."""
    import numpy as np

    idx = jnp.asarray(np.asarray(order, dtype=np.int32))

    def rule(path, leaf):
        names = [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]
        if any(n in _STACKED for n in names):
            return jnp.take(leaf, idx, axis=0)
        return leaf

    return jax.tree_util.tree_map_with_path(rule, params)


def to_interleaved_order(params, n_layers: int, n_stages: int,
                         n_virtual: int):
    """Logical-order stacks → the placement order an interleaved pipe mesh
    stores (physical row p = logical layer `interleaved_layer_order(...)[p]`).
    Use when loading a sequential/gpipe checkpoint into an interleaved
    config."""
    return _reorder_stacks(
        params, interleaved_layer_order(n_layers, n_stages, n_virtual)
    )


def to_logical_order(params, n_layers: int, n_stages: int, n_virtual: int):
    """Inverse of `to_interleaved_order` — recover logical layer order from
    an interleaved checkpoint (e.g. to resume it on a different mesh or
    schedule)."""
    import numpy as np

    order = interleaved_layer_order(n_layers, n_stages, n_virtual)
    return _reorder_stacks(params, np.argsort(order))


def param_specs(params, mesh: Mesh) -> dict:
    """PartitionSpec tree for the pipelined layout: per-layer stacks sharded
    over ``pipe`` on dim 0 (+ Megatron column/row over ``model`` when that
    axis is live), everything else replicated."""
    tp = mesh.shape.get(MODEL_AXIS, 1) > 1
    stack_specs = _stack_specs(tp)

    def rule(path, leaf):
        names = [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]
        name = next((n for n in names if n in stack_specs), None)
        if name is not None:
            return P(PIPE_AXIS, *stack_specs[name])
        return P()

    return jax.tree_util.tree_map_with_path(rule, params)
