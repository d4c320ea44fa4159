"""Speculative decoding: draft cheap token chunks, verify with ONE target
chunk-forward, accept the matching prefix — exact target-greedy output.

The reference has no inference stack at all (its serving story ends at a
SavedModel export, mnist_keras.py:126-140); `models/decoding.py` gives this
framework per-token KV-cache generation, and this module removes that
loop's fundamental limit: a decode step is a bandwidth-bound matvec, so
tokens/sec is capped by how fast weights stream — UNLESS several positions
are verified per weight pass. Speculative decoding (Leviathan et al.,
arXiv:2211.17192) does exactly that, and it is a natural fit for the
TPU/XLA model:

* **the whole loop is one jitted `lax.while_loop`** — draft, verify
  chunk-forward (the KV cache's chunk-extension path,
  transformer.Block._decode_attention), acceptance, cache-index rollback —
  with fully static shapes: one host dispatch per generation;
* **verification rides the MXU**: a γ-token chunk forward has the same
  weight traffic as ONE decode step but γ positions of compute — accepted
  tokens are bandwidth-free;
* **exactness by construction**: greedy acceptance keeps a drafted token
  only while it equals the target's own argmax, so the output is
  bit-identical to plain greedy decoding whatever the draft quality —
  drafts change the speed, never the result. Batch rows accept different
  prefix lengths and each advances by its OWN acceptance (per-row cache
  indices, transformer.Block's vector decode_index layout): a lucky row
  never waits for an unlucky one, so batched throughput keeps the batch-1
  acceptance rate instead of degrading toward the row-minimum.

The built-in draft is **prompt-lookup** (n-gram continuation: propose the
tokens that followed the most recent earlier occurrence of the current
n-gram suffix — "prompt lookup decoding", a draft-model-free scheme that
excels on self-repetitive text: code, summarization-with-quotes, copy
structure). Two generalizations, same exactness guarantee:

* a custom stateless ``draft_fn(buf [B, Tmax], cur_len [B], n_draft) ->
  [B, n_draft]`` (``cur_len`` arrives as a per-row vector; a scalar is
  also accepted for hand-driven use);
* a **draft model** (``draft_model=`` + ``draft_params=``: a smaller LM,
  the classic two-model scheme) — it keeps its own KV cache inside the
  loop. Static-shape subtlety: how far the draft cache trails the
  committed prefix varies by round (full acceptance consumes one token
  the draft never saw), so every round re-feeds the draft a fixed
  2-token window ending at the committed head — cache writes are
  idempotent for committed tokens, so the variable-length "catch-up" a
  Python implementation would branch on becomes a constant-shape
  overwrite — then scans γ-2 single-token draft steps.

**Sampling** (``temperature > 0``, with top-k/top-p): the rejection
scheme of arXiv:2211.17192 specialized to deterministic drafts — accept
draft token d with probability p(d) under the target's filtered
distribution, else resample from p restricted to the other tokens; the
committed law is exactly p per position, so sampled speculative output is
*distributionally* identical to `decoding.generate`'s sampled path
(bit-identity is impossible: the rng schedules differ). Randomness is
keyed by ``(absolute position, draft token, batch row)``, never by round:
with per-row advance each position is decided exactly once, and the
position/token keying additionally guarantees independence if a position
ever were revisited (the property the old lockstep scheme needed; kept
because it costs nothing and makes the draws schedule-invariant).

Restrictions: ``eos_id`` unsupported (use `decoding.generate` for
eos-terminated generation), and dense models only: MoE expert capacity is
enforced per call group, so a γ-token verify forward can route
differently than the single-token steps it replaces and the exactness
contract would silently break (`decoding.py`'s MoE caveat, made binding
here) — rejected loudly.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.models.decoding import (
    _NEG,
    check_sampling_params,
    filter_logits,
    require_decode_path,
)


def ngram_draft_fn(*, ngram: int = 3) -> Callable:
    """Prompt-lookup draft: continue the most recent earlier occurrence of
    the current ``ngram``-token suffix.

    Returns ``draft_fn(buf [B, Tmax], cur_len [B] or scalar, gamma) ->
    [B, gamma]`` proposals. When no earlier occurrence exists a row falls
    back to repeating its last token — drafts are free to be wrong;
    verification discards mismatches.
    """

    def draft_fn(buf, cur_len, n_draft: int):
        b, tmax = buf.shape
        cur_len = jnp.asarray(cur_len, jnp.int32)
        if cur_len.ndim == 0:
            cur_len = jnp.broadcast_to(cur_len, (b,))
        # Suffix = each row's last `ngram` finalized tokens (indices clamp
        # at 0 when cur_len < ngram — the garbage suffix just drafts badly,
        # which verification absorbs).
        suf_idx = jnp.clip(
            cur_len[:, None] - ngram + jnp.arange(ngram, dtype=jnp.int32),
            0, tmax - 1,
        )
        suffix = jnp.take_along_axis(buf, suf_idx, axis=1)  # [B, ngram]
        n_windows = tmax - ngram
        win_idx = (
            jnp.arange(n_windows, dtype=jnp.int32)[:, None]
            + jnp.arange(ngram, dtype=jnp.int32)[None, :]
        )  # [S, ngram]
        windows = buf[:, win_idx]  # [B, S, ngram]
        starts = jnp.arange(n_windows, dtype=jnp.int32)
        # An *earlier* occurrence: the window must end before the suffix
        # starts (also excludes matching the suffix against itself).
        eq = jnp.all(windows == suffix[:, None, :], axis=-1) & (
            starts[None, :] < (cur_len - ngram)[:, None]
        )
        s_star = jnp.max(
            jnp.where(eq, starts[None, :], -1), axis=1
        )  # [B] latest match, -1 = none
        has = s_star >= 0
        follow = jnp.clip(
            s_star[:, None] + ngram + jnp.arange(n_draft, dtype=jnp.int32),
            0, tmax - 1,
        )
        draft = jnp.take_along_axis(buf, follow, axis=1)  # [B, n_draft]
        last = jnp.take_along_axis(buf, (cur_len - 1)[:, None], 1)
        return jnp.where(has[:, None], draft, last)

    return draft_fn


def make_speculative_fn(model, *, max_new_tokens: int, gamma: int = 4,
                        draft_fn: Callable | None = None,
                        draft_model=None, draft_params=None,
                        temperature: float = 0.0, top_k: int = 0,
                        top_p: float = 0.0,
                        include_prompt: bool = True,
                        return_stats: bool = False,
                        quantized: bool = False):
    """Build the compiled speculative generator.

    Greedy (``temperature=0``, default): ``(params, prompt) -> tokens``,
    bit-identical to `decoding.generate`'s greedy path. Sampled
    (``temperature > 0``, with top-k/top-p): ``(params, prompt, rng) ->
    tokens``, distributionally identical to the sampled `generate` (see
    module docstring — the rejection scheme commits exactly the target's
    filtered distribution per position).

    ``gamma`` = tokens verified per target pass (1 known-exact token + γ-1
    drafts): per round the target streams its weights once and each batch
    row commits between 1 and γ tokens — **per row**: acceptance is
    row-independent (per-row cache indices), so a batch keeps the batch-1
    acceptance rate instead of advancing in lockstep at the row-minimum.
    Drafts come from ``draft_fn`` (stateless), or
    ``draft_model``/``draft_params`` (a smaller LM with its own in-loop KV
    cache — see module docstring), or the default prompt-lookup n-gram.
    ``return_stats`` appends a dict with ``rounds`` (loop iterations until
    the slowest row finished) and ``tokens`` (total committed across rows;
    mean accepted-per-round = tokens / (rounds · B)).

    **Ragged prompts** — ``fn(params, prompt, rng_or_None, lengths)`` with
    ``lengths`` a ``[B]`` int array: same contract as
    `decoding.make_generate_fn`'s ragged mode (right-padded prompts, each
    row exact at its own length), built on the same per-row cache-index
    layout — so a serving batch mixes prompt lengths AND decodes
    speculatively. Not supported with ``draft_model`` (its prefill
    consumes the padded prompt).

    ``quantized=True``: ``params`` is a `models/quant.quantize_params`
    tree; every target pass dequantizes inside the loop body so the
    weight stream stays int8 (decoding.make_generate_fn's contract).
    The greedy exactness guarantee is UNCHANGED — it compares the
    target's argmax against itself, and both the speculative verify and
    the plain quantized decode consult the same quantized weights, so
    speculative output is bit-identical to
    ``make_generate_fn(quantized=True)``'s greedy path. (A quantized
    draft_model is not supported — drafts take plain params.)
    """
    if gamma < 2:
        raise ValueError("gamma must be >= 2 (1 exact token + >=1 draft)")
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    check_sampling_params(temperature, top_p)
    sampled = temperature != 0.0
    if draft_fn is not None and draft_model is not None:
        raise ValueError("pass draft_fn OR draft_model, not both")
    if draft_model is not None and draft_params is None:
        raise ValueError("draft_model needs draft_params")
    for m, role in ((model, "target"), (draft_model, "draft")):
        if m is None:
            continue
        require_decode_path(m)
        if getattr(m, "moe_every", 0):
            raise ValueError(
                f"speculative decoding requires a dense model ({role}): MoE "
                "expert capacity binds per call group, so a chunked verify "
                "forward can legitimately route (and decode) differently "
                "than the per-token steps it replaces — the exact-output "
                "contract cannot hold; use decoding.generate for MoE models"
            )
    draft = draft_fn or (None if draft_model is not None else ngram_draft_fn())

    def run(params, prompt, rng=None, lengths=None):
        prompt = prompt.astype(jnp.int32)
        b, t0 = prompt.shape
        tmax = t0 + max_new_tokens + gamma  # chunk-overhang headroom
        if sampled and rng is None:
            raise ValueError(
                "sampled speculative decoding (temperature > 0) needs an "
                "rng: call fn(params, prompt, rng)"
            )
        if lengths is not None and draft_model is not None:
            raise ValueError(
                "ragged prompts (lengths=...) are not supported with a "
                "draft_model — its prefill consumes the padded prompt; "
                "use the n-gram/custom draft, or decoding.make_generate_fn"
            )
        from horovod_tpu.models.quant import make_unpack

        unpack = make_unpack(quantized)
        qparams = params
        dmodel = model.clone(
            decode=True, max_decode_len=tmax, dropout=0.0, remat=False,
        )
        logits, vars_ = dmodel.apply(
            {"params": unpack(qparams)}, prompt, mutable=["cache"]
        )
        if lengths is not None:
            # Ragged batch (the serving contract, decoding.py's per-row
            # layout): row i's prompt is its first lengths[i] tokens; its
            # first verified token reads the logits at lengths[i]-1, its
            # committed stream starts at position lengths[i], and every
            # per-row structure below (cur_len, cache index, buf writes)
            # starts from the vector. Pad garbage beyond a row's length is
            # progressively overwritten by committed tokens before any
            # query can attend to it — same argument as make_generate_fn's
            # ragged mode; the n-gram draft may read pads and propose
            # nonsense, which verification absorbs.
            lengths = jnp.asarray(lengths, jnp.int32)
            logits = jnp.take_along_axis(
                logits,
                jnp.minimum(lengths - 1, t0 - 1)[:, None, None],
                axis=1,
            )

        def _pkey(pos, tag, row):
            """Draw key for (absolute position, tag, batch row) — round-
            independent so lockstep re-derivation reuses the SAME draw for
            the same decision and a FRESH one when the draft token at a
            position changes between rounds (tag encodes it)."""
            k = jax.random.fold_in(rng, pos)
            k = jax.random.fold_in(k, tag)
            return jax.random.fold_in(k, row)

        rows = jnp.arange(b, dtype=jnp.int32)

        start = (
            jnp.full((b,), t0, jnp.int32) if lengths is None else lengths
        )
        if sampled:
            # "No draft at this position" draws (prefill token, bonus) use
            # tag 2*vocab — disjoint from the accept (tok) and resample
            # (vocab+tok) tag ranges. Position-keyed per row (= t0 for
            # full prompts, lengths[i] ragged).
            flt0 = filter_logits(logits[:, -1], temperature, top_k, top_p)
            next_tok = jax.vmap(
                lambda f, r, p_: jax.random.categorical(
                    _pkey(p_, 2 * flt0.shape[-1], r), f
                ).astype(jnp.int32)
            )(flt0, rows, start)
        else:
            next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        buf = jnp.zeros((b, tmax), jnp.int32)
        buf = lax.dynamic_update_slice(buf, prompt, (0, 0))

        ddraft = None
        dcache0 = None
        if draft_model is not None:
            if t0 < 2:
                raise ValueError(
                    "draft_model mode needs a prompt of >= 2 tokens (the "
                    "catch-up window spans the last two committed tokens)"
                )
            ddraft = draft_model.clone(
                decode=True, max_decode_len=tmax, dropout=0.0, remat=False,
            )
            # Prefill the draft on everything EXCEPT the prompt's last
            # token: each round's 2-token catch-up window re-feeds
            # [buf[cur_len-1], buf[cur_len]], so position t0-1 is covered
            # by round 1's window (and double-writes are idempotent).
            _, dvars = ddraft.apply(
                {"params": draft_params}, prompt[:, :-1], mutable=["cache"]
            )
            dcache0 = dict(dvars["cache"])
            # Per-row index layout from the start (the while_loop carry
            # must keep one pytree structure; _model_draft overwrites it
            # with cur_len - 1 anyway).
            dcache0["index"] = jnp.full((b,), t0 - 1, jnp.int32)

        def _model_draft(dcache, buf, cur_len):
            """γ-1 greedy proposals from the draft LM, cache maintained.

            ``buf[i, cur_len[i]]`` is row i's committed head (next_tok).
            The catch-up window [cur_len-1, cur_len] re-feeds whatever the
            draft cache might be missing — its (per-row) index is forced
            to cur_len-1 first, so committed tokens are (re)written at
            their true positions.
            """
            dcache = dict(dcache)
            dcache["index"] = cur_len - 1
            window = jnp.take_along_axis(
                buf,
                (cur_len - 1)[:, None] + jnp.arange(2, dtype=jnp.int32)[None, :],
                axis=1,
            )
            dlogits, dvars = ddraft.apply(
                {"params": draft_params, "cache": dcache}, window,
                mutable=["cache"],
            )
            tok = jnp.argmax(dlogits[:, -1], axis=-1).astype(jnp.int32)

            def step(carry, _):
                dcache, tok = carry
                slog, svars = ddraft.apply(
                    {"params": draft_params, "cache": dcache}, tok[:, None],
                    mutable=["cache"],
                )
                nxt = jnp.argmax(slog[:, -1], axis=-1).astype(jnp.int32)
                return (dict(svars["cache"]), nxt), tok

            (dcache, last), toks = lax.scan(
                step, (dict(dvars["cache"]), tok), None, length=gamma - 2
            )
            # ys = the tokens each step CONSUMED (tok_1..tok_{γ-2}); the
            # final carry is tok_{γ-1}, proposed but never consumed — its
            # missing draft-cache entry is exactly what the next round's
            # catch-up window re-feeds if it gets accepted.
            proposals = jnp.concatenate(
                [jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1
            ) if gamma > 2 else tok[:, None]
            return proposals, dcache

        def cond(carry):
            # Until the SLOWEST row has its max_new_tokens; fast rows
            # freeze (m_row = 0) once done.
            return jnp.min(carry[2]) < max_new_tokens

        def body(carry):
            buf, cur_len, n_gen, cache, dcache, next_tok, rounds = carry
            active = n_gen < max_new_tokens  # [B]
            # next_tok is already the target's exact output — commit it,
            # then draft continuations for verification. (Frozen rows
            # rewrite their frozen token at their frozen position — a
            # deterministic no-op outside the output window.)
            buf = buf.at[rows, cur_len].set(next_tok)
            if ddraft is not None:
                proposals, dcache = _model_draft(dcache, buf, cur_len)
            else:
                proposals = draft(buf, cur_len + 1, gamma - 1)
            chunk = jnp.concatenate([next_tok[:, None], proposals], axis=1)
            # Quantized mode: dequantize per round, inside the loop body —
            # the weight stream of each verify pass stays int8 in HBM.
            # The cache index is the per-row committed prefix, so each
            # row's verify forward lands at its own positions.
            logits_c, new_vars = dmodel.apply(
                {"params": unpack(qparams), "cache": cache}, chunk,
                mutable=["cache"],
            )
            if sampled:
                flt = filter_logits(logits_c, temperature, top_k, top_p)
                probs = jax.nn.softmax(flt, axis=-1)  # [B, γ, V]
                vocab = flt.shape[-1]
                d = chunk[:, 1:]  # drafts at positions cur_len+1..+γ-1
                pos_mat = (
                    cur_len[:, None] + 1
                    + jnp.arange(gamma - 1, dtype=jnp.int32)[None, :]
                )  # [B, γ-1] absolute positions, per row
                us = jax.vmap(  # [B, γ-1] position/token/row-keyed uniforms
                    lambda drow, r, prow: jax.vmap(
                        lambda p_, t_: jax.random.uniform(_pkey(p_, t_, r))
                    )(prow, drow)
                )(d, rows, pos_mat)
                # Deterministic-draft rejection: accept d w.p. p(d) under
                # the target's filtered distribution.
                p_d = jnp.take_along_axis(probs[:, :-1], d[..., None], -1)
                acc = (us < p_d[..., 0]).astype(jnp.int32)
            else:
                a = jnp.argmax(logits_c, axis=-1).astype(jnp.int32)
                # chunk[:, j] (j >= 1) is correct iff it equals the
                # target's argmax after chunk[:, :j].
                acc = (chunk[:, 1:] == a[:, :-1]).astype(jnp.int32)
            m_row = 1 + jnp.sum(jnp.cumprod(acc, axis=1), axis=1)  # [B]
            # Per-row advance, clamped to the row's remaining budget (so
            # n_gen lands exactly on max_new_tokens and buf never outgrows
            # its γ-token headroom); frozen rows advance 0.
            m_row = jnp.where(
                active, jnp.minimum(m_row, max_new_tokens - n_gen), 0
            )
            # Commit accepted drafts (row i: positions cur_len[i]+1 ..
            # cur_len[i]+m_row[i]-1): write the whole tail, then let
            # positions >= cur_len+m_row be overwritten by later rounds —
            # simpler than a dynamic-length write, and that region is dead
            # until then.
            tail_pos = (
                cur_len[:, None] + 1
                + jnp.arange(gamma - 1, dtype=jnp.int32)[None, :]
            )
            buf = buf.at[rows[:, None], tail_pos].set(chunk[:, 1:])
            # The token at each row's position cur_len + m_row (its next
            # committed head). A row that rejected its draft there (or has
            # none at m_row == γ) resamples from the residual (target dist
            # minus the rejected token — exactly p overall); a row whose
            # clamped m_row kept an accepted draft carries it forward.
            if sampled:
                gather_m = jnp.clip(m_row - 1, 0, gamma - 1)[:, None]
                flt_m = jnp.take_along_axis(
                    flt, gather_m[..., None], axis=1
                )[:, 0]  # [B, V]
                has_draft = m_row < gamma  # [B]
                idx_d = jnp.clip(m_row, 1, gamma - 1)[:, None]
                d_m = jnp.take_along_axis(chunk, idx_d, 1)[:, 0]
                idx_a = jnp.clip(m_row - 1, 0, gamma - 2)[:, None]
                acc_m = jnp.take_along_axis(acc, idx_a, 1)[:, 0].astype(bool)
                masked = jnp.where(
                    has_draft[:, None] & jax.nn.one_hot(d_m, vocab, dtype=bool),
                    _NEG, flt_m,
                )
                pos_m = cur_len + m_row  # [B]

                def res_one(f_row, tok, r, p_, hd):
                    tag = jnp.where(hd, vocab + tok, 2 * vocab)
                    return jax.random.categorical(
                        _pkey(p_, tag, r), f_row
                    ).astype(jnp.int32)

                resampled = jax.vmap(res_one)(
                    masked, d_m, rows, pos_m, has_draft
                )
                new_next = jnp.where(has_draft & acc_m, d_m, resampled)
            else:
                new_next = jnp.take_along_axis(
                    a, jnp.clip(m_row - 1, 0, gamma - 1)[:, None], 1
                )[:, 0]
            next_tok = jnp.where(active, new_next, next_tok)
            # Roll the cache back to each row's committed prefix: stale K/V
            # above it are masked out by the attention's per-row index test
            # and overwritten by the next chunk write at exactly this index.
            cache = dict(new_vars["cache"])
            cache["index"] = cur_len + m_row
            return (
                buf, cur_len + m_row, n_gen + m_row, cache, dcache, next_tok,
                rounds + 1,
            )

        cache0 = dict(vars_["cache"])
        # Per-row cache indices from the start (prefill leaves a scalar);
        # ragged rows start at their own lengths.
        cache0["index"] = start
        carry = (
            buf, start, jnp.zeros((b,), jnp.int32),
            cache0,
            dcache0 if dcache0 is not None else jnp.int32(0),
            next_tok, jnp.int32(0),
        )
        buf, cur_len, n_gen, _, _, _, rounds = lax.while_loop(
            cond, body, carry
        )
        if lengths is not None:
            # Ragged extraction: row i's generated tokens live at
            # [lengths[i], lengths[i] + max_new_tokens).
            gen = jnp.take_along_axis(
                buf,
                lengths[:, None]
                + jnp.arange(max_new_tokens, dtype=jnp.int32)[None, :],
                axis=1,
            )
            out = (
                jnp.concatenate([prompt, gen], axis=1) if include_prompt
                else gen
            )
        else:
            out = lax.dynamic_slice(
                buf, (0, 0 if include_prompt else t0),
                (b, (t0 if include_prompt else 0) + max_new_tokens),
            )
        if return_stats:
            return out, {"rounds": rounds, "tokens": jnp.sum(n_gen)}
        return out

    return jax.jit(run)
