"""Autoregressive inference: KV-cache prefill + `lax.scan` decode loop.

The reference stops at a serving *export* (mnist_keras.py:126-140 — a
SavedModel with a predict signature); for an LM-flagship framework the
serving-side capability is token generation, so this module makes inference
first-class the TPU way:

* **one compiled program** — prompt prefill (flash-kernel causal attention,
  K/V written into per-block caches) and the whole decode loop (a
  `lax.scan` of single-token steps against the cache) live inside a single
  `jit`, so the host dispatches once per generation, not once per token
  (a single-token step is a handful of matvecs, small next to a dispatch);
* **training shardings reused** — the cache carries the same Megatron
  layout as training ([B, L, H, D] with heads over ``model``), so a
  TP-sharded checkpoint decodes without resharding;
* **static shapes** — the cache is sized `prompt_len + max_new_tokens` up
  front; early stop on ``eos_id`` is a masked fill, not a dynamic shape.

Sampling: greedy (``temperature=0``), temperature, top-k and top-p
(nucleus) — all inside the scan via `jax.random.categorical` with a
split-per-step key.

MoE caveat: expert capacity is enforced per *call* group, so a decode step
routes only that step's tokens while a teacher-forced forward routes every
position of the sequence at once. When capacity never binds (ample
``capacity_factor``) the two are bit-identical; when it binds they drop
*different* tokens, and decoded logits can legitimately diverge from a full
recompute — same semantics Switch/GShard serving has.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_NEG = -1e30


def check_sampling_params(temperature: float, top_p: float) -> None:
    """The one place the sampling-knob ranges are enforced.

    top_p < 0 would make the nucleus empty and the clamped kth index wrap
    to the minimum logit (silently UNfiltered sampling); temperature < 0
    would invert the distribution (anti-nucleus) — both must raise, not
    silently misbehave.
    """
    if not 0.0 <= top_p <= 1.0:
        raise ValueError(f"top_p must be in [0, 1], got {top_p}")
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")


def filter_logits(logits, temperature: float, top_k: int, top_p: float):
    """Temperature/top-k/top-p filtering on [..., vocab] logits (f32 math).

    Returns the filtered logits whose softmax is the sampling distribution
    (`_NEG` on masked tokens). Shared by `_sample` and the speculative
    decoder's rejection scheme, which needs the distribution itself, not a
    draw. ``temperature`` must be > 0 here (greedy is its callers' fast
    path).
    """
    check_sampling_params(temperature, top_p)
    if temperature == 0.0:
        raise ValueError("filter_logits needs temperature > 0 (greedy is "
                         "the callers' argmax fast path)")
    logits = logits.astype(jnp.float32) / temperature
    if top_k:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, _NEG, logits)
    if top_p:
        # Nucleus: keep the smallest prefix of descending-prob tokens whose
        # EXCLUSIVE cumulative mass is < top_p (so the top token always
        # survives), then sample the renormalized rest via categorical.
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        exclusive = jnp.cumsum(probs, axis=-1) - probs
        n_keep = jnp.sum(exclusive < top_p, axis=-1, keepdims=True)
        kth = jnp.take_along_axis(sorted_logits, n_keep - 1, axis=-1)
        logits = jnp.where(logits < kth, _NEG, logits)
    return logits


def _sample(logits, rng, temperature: float, top_k: int, top_p: float = 0.0):
    """One next-token draw from [B, vocab] logits (f32 math)."""
    check_sampling_params(temperature, top_p)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        rng, filter_logits(logits, temperature, top_k, top_p)
    ).astype(jnp.int32)


def require_decode_path(model) -> None:
    """Refuse, by name, a model whose layers have no KV-cache path: the
    generators clone ``model`` into decode mode, and a model without the
    ``decode`` field would fail there on an unknown keyword."""
    if not hasattr(model, "decode"):
        raise NotImplementedError(
            f"{type(model).__name__} has no decode path: its layers keep no "
            "cache (for LatentMoELM a compressed latent cache, ROADMAP R2; "
            "for HybridMoELM a recurrent state beside the keys and values "
            "and a decode step for its DeltaAttention layers, and for its "
            "StateSpaceMixer layers a state and a convolution's tail, "
            "ROADMAP R8); "
            "generation, beam search and speculative decoding take a "
            "TransformerLM")


def make_generate_fn(model, *, max_new_tokens: int, temperature: float = 0.0,
                     top_k: int = 0, top_p: float = 0.0,
                     eos_id: int | None = None,
                     include_prompt: bool = True,
                     quantized: bool = False,
                     int8_compute: bool = False,
                     quantized_cache: bool = False):
    """Build the compiled generator: ``(params, prompt, rng) -> tokens``.

    ``model`` is the *training* `TransformerLM`; it is cloned into decode
    mode (``decode=True``, dropout off) with the cache sized to
    ``prompt.shape[1] + max_new_tokens``. The returned function is jitted
    and reusable across calls of the same prompt shape — the handle to hold
    when generating in a loop (a bare `generate` call per prompt re-traces).

    ``quantized=True``: ``params`` is a `models/quant.quantize_params`
    tree (int8 weights + scales); each decode step dequantizes inside the
    scan body so the per-token weight stream stays int8 in HBM — the
    bandwidth-bound step reads half the bytes (quant.py; approximate:
    outputs can differ from bf16 decoding near ties).

    ``int8_compute=True``: the PREFILL forward runs its matmuls on the
    int8 MXU (`quant.int8_dot_general`) — the compute-bound phase where
    the 2× int8 rate pays (not measured on this round's chip); decode scan
    steps stay bf16, where per-step dynamic weight requantization costs
    more than it saves. Orthogonal to ``quantized`` (storage).

    ``quantized_cache=True``: K/V cache stored int8 with per-(position,
    head) scales (TransformerLM.quantized_cache) — the cache stream and
    cache HBM halve; the decode einsums read int8 directly (scales factor
    out of the head-dim contraction). Stacks with ``quantized`` weights
    and GQA; approximate, same quality gates.

    **Ragged prompts** — ``fn(params, prompt, rng, lengths)`` with
    ``lengths`` a ``[B]`` int array: each row's true prompt is its first
    ``lengths[i]`` tokens; the rest of the row is right-padding (any token
    id). The prefill writes pad K/V into the cache, but each row's first
    sampled token reads the logits at its own ``lengths[i]-1`` and decode
    steps write at per-row cache positions — generated K/V overwrite the
    pad entries before any query can attend to them (causal masking covers
    the not-yet-overwritten tail), so every row generates exactly as if it
    were alone in the batch at its own length. This is the serving path:
    one compiled program, mixed prompt lengths per batch.
    """
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    require_decode_path(model)

    def run(params, prompt, rng, lengths=None):
        prompt = prompt.astype(jnp.int32)
        b, t0 = prompt.shape
        from horovod_tpu.models.quant import make_unpack

        unpack = make_unpack(quantized)
        qparams = params
        params = unpack(qparams)
        dmodel = model.clone(
            decode=True, max_decode_len=t0 + max_new_tokens, dropout=0.0,
            remat=False,
            **({"quantized_cache": True} if quantized_cache else {}),
        )
        # int8_compute applies to the PREFILL apply only (the split is
        # not measured on this round's chip): prefill is compute-bound and
        # gains from the int8 MXU, while a decode step is bandwidth-
        # bound and per-step dynamic weight requantization makes it
        # SLOWER (0.87-1.0x) — so the scan body stays bf16. (For a full
        # int8 forward, use TransformerLM(int8_compute=True) directly.)
        pmodel = dmodel.clone(int8_compute=True) if int8_compute else dmodel
        # Prefill: one causal forward over the prompt; the mutable 'cache'
        # collection is created here ([B, L, H, D] per block + the position
        # index) and threaded through the scan as plain pytree state.
        logits, vars_ = pmodel.apply({"params": params}, prompt, mutable=["cache"])
        cache0 = vars_["cache"]
        if lengths is None:
            last_logits = logits[:, -1]
        else:
            # Ragged batch: row i's next-token logits live at its own last
            # REAL position, and its decode writes start at lengths[i] —
            # the per-row cache index layout (transformer.Block).
            lengths = jnp.asarray(lengths, jnp.int32)
            last_logits = jnp.take_along_axis(
                logits, (lengths - 1)[:, None, None], axis=1
            )[:, 0]
            cache0 = {**cache0, "index": lengths}
        rng, sub = jax.random.split(rng)
        tok = _sample(last_logits, sub, temperature, top_k, top_p)
        done = (
            jnp.zeros((b,), bool) if eos_id is None else tok == eos_id
        )
        fill = jnp.int32(0 if eos_id is None else eos_id)

        def body(carry, _):
            cache, tok, rng, done = carry
            # Quantized mode: dequantize HERE, inside the scan body — the
            # convert+scale fuses into this step's matmul reads, so the
            # HBM weight stream stays int8 (quant.py docstring).
            step_logits, step_vars = dmodel.apply(
                {"params": unpack(qparams), "cache": cache}, tok[:, None],
                mutable=["cache"],
            )
            rng, sub = jax.random.split(rng)
            nxt = _sample(step_logits[:, -1], sub, temperature, top_k, top_p)
            nxt = jnp.where(done, fill, nxt)
            new_done = done if eos_id is None else done | (nxt == eos_id)
            return (step_vars["cache"], nxt, rng, new_done), nxt

        (_, _, _, _), rest = lax.scan(
            body, (cache0, tok, rng, done), None,
            length=max_new_tokens - 1,
        )
        gen = jnp.concatenate([tok[:, None], jnp.moveaxis(rest, 0, 1)], axis=1)
        return jnp.concatenate([prompt, gen], axis=1) if include_prompt else gen

    return jax.jit(run)


def make_chunked_generate_fns(model, *, max_new_tokens: int, chunk: int,
                              temperature: float = 0.0, top_k: int = 0,
                              top_p: float = 0.0, eos_id: int | None = None,
                              quantized_cache: bool = False):
    """Chunked generation for STREAMING serving: two compiled programs that
    emit ``chunk`` tokens per dispatch with the KV cache carried between
    calls as ordinary arrays (device-resident between dispatches).

    Returns ``(start_fn, continue_fn)``:

    * ``start_fn(params, prompt [B, T0], rng, lengths [B]) ->
      (tokens [B, chunk], state)`` — prefill + the first ``chunk`` tokens
      (ragged per-row lengths, decoding.make_generate_fn's contract);
    * ``continue_fn(params, state) -> (tokens [B, chunk], state)`` — the
      next ``chunk`` tokens against the carried cache.

    ``state`` is a pytree ``(cache, last_tok, rng, done)``; its ``done``
    leaf ([B] bool) lets a server stop early once every row emitted
    ``eos_id``. The cache is sized ``prompt_len + max_new_tokens`` at the
    first call, so at most ``ceil(max_new_tokens / chunk)`` chunks are
    valid — the caller enforces the budget. Token streams are IDENTICAL
    to `make_generate_fn`'s for the same knobs (one compiled scan cut at
    chunk boundaries; greedy/sampling/eos semantics unchanged — parity
    tested).

    CONTRACT (load-bearing for `horovod_tpu/serving/decoder.py`): every
    ``state`` leaf except ``rng`` carries a leading batch axis and each
    row's trajectory depends only on its own row (ragged lengths make a
    row generate exactly as if alone) — that per-row independence is
    what lets the continuous-batching engine admit sequences mid-flight
    by splicing rows of a fresh ``start`` state into a live state. The
    ``rng`` leaf (shape [2]) is shared by the whole batch and is NOT
    spliceable; the engine keeps the live rng and folds an admission
    counter into each prefill's seed instead. Reordering this tuple or
    giving rng a batch axis changes that downstream contract.
    """
    if chunk < 1:
        raise ValueError("chunk must be >= 1")
    if max_new_tokens % chunk != 0:
        # The cache is sized t0 + max_new_tokens exactly; a partial final
        # chunk would scan past it. Divisibility keeps every chunk valid.
        raise ValueError(
            f"chunk ({chunk}) must divide max_new_tokens "
            f"({max_new_tokens})"
        )

    fill = jnp.int32(0 if eos_id is None else eos_id)

    def make_body(dmodel, params):
        def body(carry, _):
            cache, tok, rng, done = carry
            step_logits, step_vars = dmodel.apply(
                {"params": params, "cache": cache},
                tok[:, None], mutable=["cache"],
            )
            rng, sub = jax.random.split(rng)
            nxt = _sample(step_logits[:, -1], sub, temperature, top_k, top_p)
            nxt = jnp.where(done, fill, nxt)
            new_done = done if eos_id is None else done | (nxt == eos_id)
            return (step_vars["cache"], nxt, rng, new_done), nxt

        return body

    def dmodel_for(t0):
        kw = {"quantized_cache": True} if quantized_cache else {}
        return model.clone(
            decode=True, max_decode_len=t0 + max_new_tokens, dropout=0.0,
            remat=False, **kw,
        )

    def start(params, prompt, rng, lengths):
        prompt = prompt.astype(jnp.int32)
        b, t0 = prompt.shape
        dmodel = dmodel_for(t0)
        logits, vars_ = dmodel.apply(
            {"params": params}, prompt, mutable=["cache"]
        )
        lengths = jnp.asarray(lengths, jnp.int32)
        last_logits = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None], axis=1
        )[:, 0]
        rng, sub = jax.random.split(rng)
        tok = _sample(last_logits, sub, temperature, top_k, top_p)
        done = jnp.zeros((b,), bool) if eos_id is None else tok == eos_id
        cache0 = {**vars_["cache"], "index": lengths}
        (cache, tok_l, rng, done), rest = lax.scan(
            make_body(dmodel, params), (cache0, tok, rng, done), None,
            length=chunk - 1,
        )
        tokens = jnp.concatenate(
            [tok[:, None], jnp.moveaxis(rest, 0, 1)], axis=1
        )
        return tokens, (cache, tok_l, rng, done)

    def cont(params, state):
        cache, tok, rng, done = state
        # The cache length encodes t0 + max_new_tokens; reconstruct the
        # model at the same static size from the carried cache leaves.
        any_k = next(
            v["k"] for v in cache.values() if isinstance(v, dict) and "k" in v
        )
        dmodel = dmodel_for(any_k.shape[1] - max_new_tokens)
        (cache, tok_l, rng, done), toks = lax.scan(
            make_body(dmodel, params), (cache, tok, rng, done), None,
            length=chunk,
        )
        return jnp.moveaxis(toks, 0, 1), (cache, tok_l, rng, done)

    return jax.jit(start), jax.jit(cont)


def generate(model, params, prompt, max_new_tokens: int, *, rng=None,
             temperature: float = 0.0, top_k: int = 0, top_p: float = 0.0,
             eos_id: int | None = None, include_prompt: bool = True,
             quantized: bool = False, int8_compute: bool = False,
             quantized_cache: bool = False):
    """Generate ``max_new_tokens`` continuations of ``prompt`` ([B, T0] ints).

    Convenience wrapper over `make_generate_fn` (which see, for the handle
    to keep when calling repeatedly). ``temperature=0`` = greedy; after a
    row emits ``eos_id`` its remaining positions are filled with it.
    """
    fn = make_generate_fn(
        model, max_new_tokens=max_new_tokens, temperature=temperature,
        top_k=top_k, top_p=top_p, eos_id=eos_id,
        include_prompt=include_prompt, quantized=quantized,
        int8_compute=int8_compute, quantized_cache=quantized_cache,
    )
    if rng is None:
        rng = jax.random.PRNGKey(0)
    return fn(params, jnp.asarray(prompt), rng)
