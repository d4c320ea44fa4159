"""Beam search over the KV-cache decode loop — one compiled program.

Completes the decode-mode family (greedy / temperature / top-k / top-p /
speculative): width-W maximum-likelihood search, TPU-shaped —

* **beams are batch rows.** Hypotheses live as a [B·W] batch through the
  same cached decode step the other modes use; one forward per step
  scores every beam of every row.
* **reordering is a gather.** When beam w extends from parent p, its KV
  cache rows are `leaf[B, W, ...][batch, parent]` — a batch-dim gather
  XLA turns into one dynamic-gather per cache leaf, inside the scan. No
  host, no dynamic shapes.
* **the whole search is one `lax.scan`** (prefill + W-way seeding + the
  step loop under a single jit): one dispatch per search, like
  `decoding.make_generate_fn`.

Scores are accumulated log-probabilities (f32, log_softmax of the step
logits); finished rows (``eos_id``) freeze their score and expand only to
eos. Final selection applies the GNMT length penalty
``((5 + len) / 6) ** length_penalty`` when requested.

Reference role: the reference has no inference stack at all
(SURVEY.md §5.4 — its serving story ends at a SavedModel export);
beam search is framework completeness beyond parity.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.models.decoding import _NEG, require_decode_path


def make_beam_search_fn(model, *, max_new_tokens: int, beam_size: int,
                        length_penalty: float = 0.0,
                        eos_id: int | None = None,
                        include_prompt: bool = True,
                        return_scores: bool = False,
                        quantized: bool = False):
    """Build the compiled beam searcher: ``(params, prompt) -> tokens``.

    Returns the best beam per batch row (``[B, T]`` int32); with
    ``return_scores`` a ``(tokens, scores)`` pair where ``scores`` is the
    best beam's accumulated log-probability (length-penalized when
    ``length_penalty > 0``). ``quantized`` follows
    `decoding.make_generate_fn`'s contract (int8 param tree, per-step
    in-loop dequantization).
    """
    if beam_size < 1:
        raise ValueError("beam_size must be >= 1")
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    require_decode_path(model)
    w = beam_size

    def run(params, prompt):
        prompt = prompt.astype(jnp.int32)
        b, t0 = prompt.shape
        from horovod_tpu.models.quant import make_unpack

        unpack = make_unpack(quantized)
        qparams = params
        dmodel = model.clone(
            decode=True, max_decode_len=t0 + max_new_tokens, dropout=0.0,
            remat=False,
        )
        logits, vars_ = dmodel.apply(
            {"params": unpack(qparams)}, prompt, mutable=["cache"]
        )
        logp0 = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))  # [B, V]
        vocab = logp0.shape[-1]

        # Seed: the top-W first tokens per row ARE the initial beams.
        scores, tok0 = lax.top_k(logp0, w)  # [B, W]
        tok0 = tok0.astype(jnp.int32)
        finished = (
            jnp.zeros((b, w), bool) if eos_id is None else tok0 == eos_id
        )

        # Tile the prompt cache to [B*W] rows (beam-major within a row).
        def tile(leaf):
            if leaf.ndim == 0:  # the shared decode index
                return leaf
            return jnp.repeat(leaf, w, axis=0)

        cache = jax.tree.map(tile, dict(vars_["cache"]))
        gen0 = jnp.full((b, w, max_new_tokens), jnp.int32(0))
        gen0 = gen0.at[:, :, 0].set(tok0)

        def step(carry, i):
            cache, gen, scores, last, finished = carry
            step_logits, new_vars = dmodel.apply(
                {"params": unpack(qparams), "cache": cache},
                last.reshape(b * w, 1), mutable=["cache"],
            )
            logp = jax.nn.log_softmax(
                step_logits[:, -1].astype(jnp.float32)
            ).reshape(b, w, vocab)
            if eos_id is not None:
                # Finished beams expand only to eos, at no score cost —
                # they compete in the pool with a frozen score.
                frozen = jnp.full((vocab,), _NEG).at[eos_id].set(0.0)
                logp = jnp.where(finished[:, :, None], frozen, logp)
            total = scores[:, :, None] + logp  # [B, W, V]
            new_scores, flat_idx = lax.top_k(total.reshape(b, w * vocab), w)
            parent = flat_idx // vocab  # [B, W]
            token = (flat_idx % vocab).astype(jnp.int32)

            # Reorder histories and caches under the surviving beams.
            gen = jnp.take_along_axis(gen, parent[:, :, None], axis=1)
            gen = gen.at[:, :, i].set(token)  # i = position in gen buffer

            def reorder(leaf):
                if leaf.ndim == 0:
                    return leaf
                shaped = leaf.reshape((b, w) + leaf.shape[1:])
                idx = parent.reshape(
                    (b, w) + (1,) * (leaf.ndim - 1)
                )
                return jnp.take_along_axis(shaped, idx, axis=1).reshape(
                    leaf.shape
                )

            cache = jax.tree.map(reorder, dict(new_vars["cache"]))
            if eos_id is None:
                new_finished = finished
            else:
                new_finished = (
                    jnp.take_along_axis(finished, parent, axis=1)
                    | (token == eos_id)
                )
            return (cache, gen, new_scores, token, new_finished), None

        (cache, gen, scores, _, finished), _ = lax.scan(
            step, (cache, gen0, scores, tok0, finished),
            jnp.arange(1, max_new_tokens, dtype=jnp.int32),
        )

        # Length-penalized final selection (GNMT): len = tokens before the
        # first eos (inclusive), or the full budget.
        if eos_id is not None:
            is_eos = gen == eos_id
            any_eos = is_eos.any(axis=-1)
            first = jnp.argmax(is_eos, axis=-1) + 1
            lengths = jnp.where(any_eos, first, max_new_tokens)
        else:
            lengths = jnp.full((b, w), max_new_tokens)
        if length_penalty > 0.0:
            norm = ((5.0 + lengths.astype(jnp.float32)) / 6.0) ** length_penalty
            final = scores / norm
        else:
            final = scores
        best = jnp.argmax(final, axis=1)  # [B]
        tokens = jnp.take_along_axis(gen, best[:, None, None], axis=1)[:, 0]
        best_score = jnp.take_along_axis(final, best[:, None], axis=1)[:, 0]
        if eos_id is not None:
            # Pad everything after the first eos with eos (generate()'s
            # fill convention).
            pos = jnp.arange(max_new_tokens)
            blen = jnp.take_along_axis(lengths, best[:, None], axis=1)
            tokens = jnp.where(pos[None, :] < blen, tokens, jnp.int32(eos_id))
        if include_prompt:
            tokens = jnp.concatenate([prompt, tokens], axis=1)
        if return_scores:
            return tokens, best_score
        return tokens

    return jax.jit(run)
