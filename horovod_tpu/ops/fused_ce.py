"""Fused (chunked) linear + softmax cross-entropy for large-vocab LM heads.

The standard LM loss path materializes ``[B, T, vocab]`` logits twice — once
in the forward pass and once as the backward cotangent — and at long context
those two arrays dominate HBM (at seq 131k they are the OOM driver the
``logits_dtype=bf16`` knob only halves).
This op computes ``cross_entropy(h @ W, labels)`` without ever building the
full logits array: a `lax.scan` over row-chunks computes each chunk's
``[C, vocab]`` logits tile on the fly — forward for the logsumexp, again in
the backward for the softmax — so peak extra memory is
O(chunk · vocab) instead of O(B · T · vocab), trading one extra head matmul
(recompute) for the two big arrays. The per-chunk matmuls stay MXU-shaped
(``[C, D] @ [D, V]`` with f32 accumulation), so the recompute rides the
systolic array rather than fighting it.

This is the moral equivalent of the "fused linear cross-entropy" kernels in
GPU land, expressed TPU-natively: `lax.scan` + `jax.custom_vjp` and XLA's
own matmul/reduction fusion, no hand-written kernel needed — the tile sizes
are large enough that XLA's codegen is already at the op-size ceiling.

Capability context: the reference's loss is a Keras one-liner on 10-class
MNIST (`/root/reference/tensorflow2_keras_mnist.py:62-65`) where none of
this matters; this op exists for the framework's long-context flagship,
where the head is the memory-binding layer.

Used by ``TransformerLM(fused_head_chunks=n)`` + ``Trainer(loss='module')``.

On a mesh. This op knows no mesh: it chunks the rows it is handed. Left to
the partitioner that is wrong twice over — `_split` cuts the flattened
``B·T`` axis, so a batch split over ``data`` becomes the axis the scans
walk, every chip gathers every chunk and computes all of them (the dp4
step's head took 4.05x the one-chip head's time, PERF.md PR 26), and a dW
carry that is a partial sum cannot cross a `while` without an all-reduce
per chunk. So the caller that holds the mesh (`LMHead.fused_loss`,
models/transformer.py) calls this op inside a `shard_map` over the row
axes (``data``, ``fsdp``, ``seq``) with the kernel replicated over them:
each chip flattens and chunks its OWN ``B/dp x T/sp`` rows (``n_chunks``
counts chunks of those), neither scan holds a collective, and the
transpose of the replicated kernel is the one cross-chip sum of dW, after
the backward loop, in the kernel's dtype (float32 parameters: the float32
the rule accumulates in). ``model`` (the kernel's vocabulary dimension)
stays the partitioner's. A model built
without a mesh and run under a multi-device Trainer (``attn='dense'``)
cannot know the mesh and keeps the replicated head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# The head's name in the compiled step: every op of the forward scan and of
# the backward scan carries it in its metadata (`jax.named_scope` adds to the
# op's name stack and changes no instruction), so a profiler trace can sum
# the head + CE by name (chipbench/spans.py `head_ce_ms_per_step`). Entered
# in BOTH rules of the custom_vjp: the backward rule is traced apart from the
# forward and would not inherit a scope opened inside it.
SCOPE = "hvt.head_ce"


def _chunk_logits(hc, w, compute_dtype):
    """One chunk's logits tile ``[C, V]`` with f32 MXU accumulation."""
    return lax.dot(
        hc.astype(compute_dtype),
        w.astype(compute_dtype),
        precision=None,
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_linear_cross_entropy(h, w, labels, n_chunks: int = 8):
    """Per-token CE loss of ``h @ w`` against integer ``labels``, chunked.

    Args:
      h: ``[..., D]`` final hidden states (any leading shape; typically
        ``[B, T, D]``), f32 or bf16.
      w: ``[D, V]`` head kernel (the LM head's ``lm_head/kernel`` param).
      labels: integer ``[...]`` matching ``h``'s leading shape.
      n_chunks: static number of row-chunks the flattened ``B·T`` rows are
        scanned in; peak logits memory is ``ceil(B·T / n_chunks) · V`` floats
        (per forward or backward scan step). Inside a `shard_map` the rows
        are the chip's own (see the module docstring).

    Returns:
      ``(loss, correct)`` — per-token f32 loss ``lse - logit[label]`` and a
      per-token f32 correctness indicator (``argmax == label``), both with
      ``labels``'s shape. ``correct`` carries no gradient (argmax is
      piecewise constant).
    """
    loss, correct, _ = _fwd(h, w, labels, n_chunks)
    return loss, correct


def _split(x, n_chunks):
    """Flatten leading dims and pad rows to a multiple of n_chunks.

    Returns (chunked ``[n_chunks, C, ...]``, n_valid_rows).
    """
    n = x.shape[0]
    c = -(-n // n_chunks)  # ceil
    pad = n_chunks * c - n
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0
        )
    return x.reshape((n_chunks, c) + x.shape[1:]), n


@jax.named_scope(SCOPE)
def _fwd(h, w, labels, n_chunks):
    lead = labels.shape
    compute_dtype = h.dtype
    hf = h.reshape(-1, h.shape[-1])
    lf = labels.reshape(-1).astype(jnp.int32)
    hc, n = _split(hf, n_chunks)
    lc, _ = _split(lf, n_chunks)

    def body(_, chunk):
        hck, lck = chunk
        logits = _chunk_logits(hck, w, compute_dtype)  # [C, V] f32
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lck[:, None], axis=-1)[:, 0]
        correct = (jnp.argmax(logits, axis=-1) == lck).astype(jnp.float32)
        return None, (lse - ll, correct)

    _, (loss_c, corr_c) = lax.scan(body, None, (hc, lc))
    loss = loss_c.reshape(-1)[:n].reshape(lead)
    correct = corr_c.reshape(-1)[:n].reshape(lead)
    return loss, correct, (h, w, labels)


def _fwd_vjp(h, w, labels, n_chunks):
    loss, correct, res = _fwd(h, w, labels, n_chunks)
    return (loss, correct), res


@jax.named_scope(SCOPE)
def _bwd_vjp(n_chunks, res, cts):
    h, w, labels = res
    g_loss, _ = cts  # `correct` is piecewise constant — cotangent discarded
    compute_dtype = h.dtype
    hf = h.reshape(-1, h.shape[-1])
    lf = labels.reshape(-1).astype(jnp.int32)
    gf = g_loss.reshape(-1).astype(jnp.float32)
    hc, n = _split(hf, n_chunks)
    lc, _ = _split(lf, n_chunks)
    gc, _ = _split(gf, n_chunks)  # padded rows get g == 0 → no contribution

    v = w.shape[-1]

    def body(dw_acc, chunk):
        hck, lck, gck = chunk
        logits = _chunk_logits(hck, w, compute_dtype)  # recompute [C, V] f32
        p = jax.nn.softmax(logits, axis=-1)
        # d logits = (softmax - onehot(label)) · g  — the CE gradient.
        d = (p - jax.nn.one_hot(lck, v, dtype=jnp.float32)) * gck[:, None]
        dh_ck = lax.dot(
            d.astype(compute_dtype), w.astype(compute_dtype).T,
            preferred_element_type=jnp.float32,
        )
        dw_acc = dw_acc + lax.dot(
            hck.astype(compute_dtype).T, d.astype(compute_dtype),
            preferred_element_type=jnp.float32,
        )
        return dw_acc, dh_ck.astype(h.dtype)

    dw, dh_c = lax.scan(
        body, jnp.zeros(w.shape, jnp.float32), (hc, lc, gc)
    )
    dh = dh_c.reshape(-1, h.shape[-1])[:n].reshape(h.shape)
    return dh, dw.astype(w.dtype), None


fused_linear_cross_entropy.defvjp(_fwd_vjp, _bwd_vjp)
