"""Fused (chunked) linear + softmax cross-entropy for large-vocab LM heads.

The standard LM loss path materializes ``[B, T, vocab]`` logits twice — once
in the forward pass and once as the backward cotangent — and at long context
those two arrays dominate HBM (at seq 131k they are the OOM driver the
``logits_dtype=bf16`` knob only halves).
This op computes ``cross_entropy(h @ W, labels)`` without ever building the
full logits array: a loop computes one ``n_chunks``-th of the logits at a
time as a tile on the fly — forward for the logsumexp, again in the
backward for the softmax — so peak extra memory is O(B · T · vocab /
n_chunks) instead of O(B · T · vocab), trading one extra head matmul
(recompute) for the two big arrays. The per-tile matmuls stay MXU-shaped
with f32 accumulation, so the recompute rides the systolic array rather
than fighting it.

Which axis the backward scans. The forward is a `lax.scan` over row-chunks
(tiles ``[⌈N/n⌉, V]``; it carries nothing) and saves each row's logsumexp.
The backward has two gradients and can finish only one of them per tile:
the other is a running float32 sum that is read and written once a tile,
and that traffic bounds the matmul that feeds it (PERF.md, PR 32: the
``[D, V]`` sum of a 50k vocabulary, 0.4–0.6 GB eight times a step, held the
dW matmul at 43 % of the MXU's peak and 85 % of HBM bandwidth). So the rule
scans the axis that leaves the sum on the smaller side, chosen from the two
sizes it is traced with (`scans_vocab`):

* rows N ≥ vocabulary V (long context on one chip; a small vocabulary): a
  scan over row-chunks. The sum is dW ``[D, V]``; each chunk's dh is final.
* N < V (an LM's training step: 4,096 rows a chip against 50k entries): a
  loop over ``n_chunks`` lane-aligned slices of the vocabulary, tiles
  ``[N, ⌈V/n⌉]`` of the same size. The softmax is ``exp(logits − lse)``
  with the forward's own logsumexp, each dW slice ``[D, ⌈V/n⌉]`` is final
  and written once, in place, and the sum is dh ``[N, D]``, V/N times
  smaller, cast to ``h.dtype`` once after the loop.

Same operands (``compute_dtype``), same float32 accumulation, the same
8·N·D·V of work either way. The sub-scope of the backward loop
(``hvt.head_ce/vocab_scan`` or ``hvt.head_ce/row_scan``) says which was
built, and so does the gauge ``hvt_head_ce_scan{axis=}`` that the caller,
``LMHead.fused_loss``, sets from `scans_vocab` as it traces the head.

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
stays the partitioner's, and there the caller passes ``vocab_split``: the
backward scans the rows whatever the sizes, because a loop over slices of
a dimension the partitioner has split makes it gather the kernel first
and every chip of a ``model`` group compute every slice. A model built
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
# Under it, the backward rule's loop by the axis it scans (`scans_vocab`):
# a trace's op names say which program was built.
ROW_SCAN, VOCAB_SCAN = "row_scan", "vocab_scan"
# A vocabulary slice's width is a multiple of the TPU's 128 lanes, so that
# every slice of the kernel and of dW starts on a tile boundary.
LANES = 128


def _chunk_logits(hc, w, compute_dtype):
    """One chunk's logits tile ``[C, V]`` with f32 MXU accumulation."""
    return lax.dot(
        hc.astype(compute_dtype),
        w.astype(compute_dtype),
        precision=None,
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_linear_cross_entropy(h, w, labels, n_chunks: int = 8,
                               vocab_split: bool = False):
    """Per-token CE loss of ``h @ w`` against integer ``labels``, chunked.

    Args:
      h: ``[..., D]`` final hidden states (any leading shape; typically
        ``[B, T, D]``), f32 or bf16.
      w: ``[D, V]`` head kernel (the LM head's ``lm_head/kernel`` param).
      labels: integer ``[...]`` matching ``h``'s leading shape.
      n_chunks: static number of chunks: of the flattened ``B·T`` rows in
        the forward scan and in a backward that scans rows (a tile of
        ``ceil(B·T / n_chunks) · V`` floats a step), of the vocabulary in
        a backward that scans it (``B·T · ceil(V / n_chunks)``, the slice
        rounded up to whole lanes). Inside a `shard_map` the rows are the
        chip's own (see the module docstring).
      vocab_split: static; what the caller that holds the mesh sees: the
        partitioner splits ``w``'s vocabulary dimension over chips (a live
        ``model`` axis). The backward rule then scans the rows whatever
        the sizes (see "Which axis the backward scans").

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
        return None, (lse - ll, correct, lse)

    _, (loss_c, corr_c, lse_c) = lax.scan(body, None, (hc, lc))
    loss, correct, lse = (
        x.reshape(-1)[:n].reshape(lead) for x in (loss_c, corr_c, lse_c))
    return loss, correct, (h, w, labels, lse)


def _fwd_vjp(h, w, labels, n_chunks, vocab_split):
    loss, correct, res = _fwd(h, w, labels, n_chunks)
    return (loss, correct), res


def scans_vocab(n_rows: int, vocab: int, vocab_split: bool = False) -> bool:
    """Which axis the backward rule scans: the one that leaves the running
    float32 sum on the smaller side. Fewer rows than vocabulary entries:
    scan the vocabulary (the sum is dh ``[N, D]``, each dW slice is final);
    otherwise, and wherever the partitioner splits the vocabulary
    (``vocab_split``), scan the rows (the sum is dW ``[D, V]``, each dh
    chunk is final). All three are static at trace time."""
    return not vocab_split and n_rows < vocab


@jax.named_scope(SCOPE)
def _bwd_vjp(n_chunks, vocab_split, res, cts):
    h, w, labels, lse = res
    g_loss, _ = cts  # `correct` is piecewise constant — cotangent discarded
    if scans_vocab(labels.size, w.shape[-1], vocab_split):
        return _bwd_vocab_scan(n_chunks, h, w, labels, lse, g_loss)
    return _bwd_row_scan(n_chunks, h, w, labels, g_loss)


@jax.named_scope(ROW_SCAN)
def _bwd_row_scan(n_chunks, h, w, labels, g_loss):
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


@jax.named_scope(VOCAB_SCAN)
def _bwd_vocab_scan(n_chunks, h, w, labels, lse, g_loss):
    """``n_chunks`` slices of the vocabulary, every row in each: the tile is
    ``[N, ⌈V/n⌉]`` floats (the row scan's ``[⌈N/n⌉, V]``, turned), the
    softmax is ``exp(logits − lse)`` with the forward's own ``lse``, each
    dW slice is written once, into its final place, and dh is the running
    sum. The slices are lane-aligned and equal, so the kernel's
    ``compute_dtype`` copy is zero-padded up to them (50,257 divides by
    nothing useful): a zero column adds nothing to dh, and its dW column
    is cut after the loop."""
    compute_dtype = h.dtype
    d_model, v = w.shape
    hb = h.reshape(-1, d_model).astype(compute_dtype)
    lf = labels.reshape(-1).astype(jnp.int32)
    gf = g_loss.reshape(-1).astype(jnp.float32)
    lsef = lse.reshape(-1)
    width = -(-v // n_chunks)  # ceil
    width = -(-width // LANES) * LANES  # up to whole lanes
    n_slices = -(-v // width)
    wb = jnp.pad(
        w.astype(compute_dtype), ((0, 0), (0, n_slices * width - v)))

    def body(j, carry):
        dw, dh = carry
        start = j * width
        w_j = lax.dynamic_slice(wb, (0, start), (d_model, width))
        logits = lax.dot(hb, w_j, preferred_element_type=jnp.float32)
        p = jnp.exp(logits - lsef[:, None])
        hit = (lf - start)[:, None] == lax.iota(jnp.int32, width)
        # d logits = (softmax - onehot(label)) · g  — the CE gradient.
        d = ((p - hit.astype(jnp.float32)) * gf[:, None]).astype(
            compute_dtype)
        dw_j = lax.dot(hb.T, d, preferred_element_type=jnp.float32)
        dh_j = lax.dot(d, w_j.T, preferred_element_type=jnp.float32)
        return lax.dynamic_update_slice(dw, dw_j, (0, start)), dh + dh_j

    dw, dh = lax.fori_loop(
        0, n_slices, body,
        (jnp.zeros(wb.shape, jnp.float32), jnp.zeros(hb.shape, jnp.float32)),
    )
    return (
        dh.astype(h.dtype).reshape(h.shape), dw[:, :v].astype(w.dtype), None)


fused_linear_cross_entropy.defvjp(_fwd_vjp, _bwd_vjp)
