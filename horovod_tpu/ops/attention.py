"""Attention: dense reference, ring (sequence-parallel), Ulysses (head-swap).

Long-context is first-class in this framework: sequences too long for one
chip's HBM are sharded along the mesh's ``seq`` axis and attention runs as a
collective. Two standard schemes, both expressed with XLA collectives so the
compiler overlaps communication with compute:

* **Ring attention** (Liu et al., arXiv:2310.01889): K/V shards rotate around
  the ``seq`` ring via `lax.ppermute` while each device accumulates its
  queries' attention with an online (streaming) softmax — full attention,
  O(T/n) memory per chip, n-1 hops riding neighbor ICI links.
* **Ulysses** (Jacobs et al., arXiv:2309.14509): `lax.all_to_all` re-shards
  seq ↔ heads so each device holds the full sequence for H/n heads, runs
  ordinary attention locally, and swaps back. One collective pair per layer,
  needs heads % seq_parallelism == 0.

All functions take ``[batch, seq, heads, head_dim]`` and return the same.
`ring_attention`/`ulysses_attention` must be called **inside** `shard_map`
with the sequence dimension sharded over ``axis_name`` (see
`models/transformer.py` for the placement); with an axis of size 1 they
degrade to exactly `dense_attention` — the reference's "no-launcher
degradation" principle (README.md:49-52) applied to sequence parallelism.
"""

from __future__ import annotations

import jax

import jax.numpy as jnp
from jax import lax

# Finite stand-in for -inf: keeps fully-masked softmax rows at p == 0 via
# explicit mask multiplication without generating NaNs from inf - inf.
_BIG_NEG = -1e30


def check_window(window, causal) -> None:
    """Validate a sliding-window request (shared by every attention impl:
    dense, ring, Ulysses, and the flash kernel)."""
    if window is None:
        return
    if not causal:
        raise ValueError(
            "window (sliding-window attention) requires causal=True — the "
            "band is defined as each query's `window` most recent keys"
        )
    if window < 1:
        raise ValueError(f"window must be a positive int, got {window}")


def _scores(q, k, scale):
    """[B,Tq,H,D] x [B,Tk,H,D] -> [B,H,Tq,Tk] logits on the MXU."""
    return jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale


def dense_attention(q, k, v, *, causal: bool = True, q_segment_ids=None,
                    kv_segment_ids=None, window: int | None = None,
                    sinks: int = 0):
    """Reference full-materialization attention (numerics ground truth).

    float32 softmax regardless of input dtype — bf16 logits lose too much for
    long sequences; the matmuls still run in the inputs' dtype on the MXU.
    ``q_segment_ids``/``kv_segment_ids`` ([B,Tq]/[B,Tk]) restrict attention
    to equal-id pairs (packed sequences) — the reference semantics the flash
    kernel's segment masking is tested against. ``window`` (requires
    ``causal``) further restricts each query to its ``window`` most recent
    keys (the sliding-window band the flash kernel block-skips); ``sinks``
    re-admits the first ``sinks`` key positions beyond the band — the
    global+local (StreamingLLM / Longformer-style) mask."""
    check_window(window, causal)
    if sinks < 0:
        raise ValueError(f"sinks must be >= 0, got {sinks}")
    scale = q.shape[-1] ** -0.5
    s = _scores(q, k, scale)
    keep = None
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        q_pos = lax.broadcasted_iota(jnp.int32, (tq, tk), 0) + (tk - tq)
        k_pos = lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        keep = (q_pos >= k_pos)[None, None]
        if window is not None:
            band = k_pos > q_pos - window
            if sinks:
                band |= k_pos < sinks
            keep &= band[None, None]
    if q_segment_ids is not None:
        seg = q_segment_ids[:, None, :, None] == kv_segment_ids[:, None, None, :]
        keep = seg if keep is None else keep & seg
    if keep is not None:
        s = jnp.where(keep, s, _BIG_NEG)
    p = jax.nn.softmax(s, axis=-1)
    if keep is not None:
        # Exact zeros: a FULLY-masked row (a q segment with no kv tokens, or
        # causal rows before the first key when Tk < Tq) would otherwise get
        # softmax's uniform 1/Tk and average ALL values — a cross-segment
        # leak. Zeroing matches the flash kernel's empty-row convention
        # (zero output); already-zero lanes are unaffected.
        p = jnp.where(keep, p, 0.0)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def ring_attention(q, k, v, *, axis_name: str = "seq", causal: bool = True,
                   window: int | None = None):
    """Exact blockwise attention over a sequence-sharded ring.

    Inside `shard_map`: q/k/v are this device's ``[B, T/n, H, D]`` shard of
    the global sequence. Each of the n ring steps attends the local queries
    to one K/V block, folds the result into an online softmax accumulator
    (running max m, normalizer l, unnormalized output o), and rotates the
    K/V block to the next neighbor — `lax.ppermute`, which XLA lowers to
    neighbor ICI sends that overlap with the attention matmuls of the
    current block. `lax.scan` (not fori_loop) so reverse-mode AD works and
    the backward pass replays the ring.

    ``window`` (requires ``causal``): sliding-window band over GLOBAL
    positions — queries see their ``window`` most recent keys across shard
    boundaries; hops carrying only stale keys contribute zero (their lanes
    mask away; the flash-ring variant additionally skips their FLOPs).
    """
    check_window(window, causal)
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    scale = d ** -0.5

    q_pos = my * t_local + lax.broadcasted_iota(jnp.int32, (t_local, 1), 0)[:, 0]

    def step(carry, i):
        o, m, l, k_blk, v_blk = carry
        # Which global block we currently hold: blocks travel "rightward"
        # (r → r+1), so after i hops we hold the block born at my - i.
        j = (my - i) % n
        k_pos = j * t_local + lax.broadcasted_iota(jnp.int32, (t_local, 1), 0)[:, 0]

        s = _scores(q, k_blk, scale)  # [B,H,Tq,Tk] float32
        if causal:
            keep = q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                keep &= k_pos[None, :] > q_pos[:, None] - window
            mask = keep.astype(s.dtype)
        else:
            mask = jnp.ones((t_local, t_local), s.dtype)
        s = s + (1.0 - mask) * _BIG_NEG

        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)  # finite: both are ≥ _BIG_NEG
        p = jnp.exp(s - m_new[..., None]) * mask  # zero masked lanes exactly
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )
        o_new = o * alpha[..., None] + pv

        perm = [(r, (r + 1) % n) for r in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (o_new, m_new, l_new, k_blk, v_blk), None

    o0 = jnp.zeros((b, h, t_local, d), jnp.float32)
    m0 = jnp.full((b, h, t_local), _BIG_NEG, jnp.float32)
    l0 = jnp.zeros((b, h, t_local), jnp.float32)
    (o, _, l, _, _), _ = lax.scan(
        step, (o0, m0, l0, k, v), jnp.arange(n)
    )
    out = o / l[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # [B,Tq,H,D]


def _merge_lse(o, m, l, o_c, lse_c):
    """Fold one block's (out, lse) contribution into the running
    (unnormalized out, max, normalizer) accumulator — the logsumexp
    recurrence every ring variant shares."""
    m_new = jnp.maximum(m, lse_c)
    alpha = jnp.exp(m - m_new)
    w = jnp.exp(lse_c - m_new)
    return (
        o * alpha[..., None] + o_c.astype(jnp.float32) * w[..., None],
        m_new,
        l * alpha + w,
    )


def ring_cross_attention(q, k, v, *, axis_name: str = "seq",
                         q_segment_ids=None, kv_segment_ids=None):
    """Non-causal CROSS-attention over a sequence-sharded ring — the
    seq2seq decoder's cross-attention under sequence parallelism.

    Inside `shard_map`: ``q`` is this device's ``[B, Tq/n, H, D]`` shard of
    the decoder tokens, ``k``/``v`` the ``[B, Tk/n, H, D]`` shard of the
    encoder memory (Tq and Tk are independent). Each of the n hops runs
    the flash kernel's non-causal Tk≠Tq grids against one memory block and
    folds the result in by the logsumexp recurrence while the block
    rotates to the neighbor — identical structure to
    `ring_flash_attention`, minus the causal machinery (every query sees
    every key, so every hop is a full block).

    ``q_segment_ids`` stays local with the queries; ``kv_segment_ids``
    rotates with its K/V block (the source-side padding mask). A query
    with NO matching key anywhere (an all-pad source row) gets exactly
    zero output — the kernel's empty-row convention, preserved through
    the merge by the safe final divide."""
    from horovod_tpu.ops.flash_attention import flash_attention_with_lse

    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError(
            "q_segment_ids and kv_segment_ids come as a pair (the "
            "source-side padding mask needs both sides labelled)"
        )
    n = lax.axis_size(axis_name)
    b, tq, h, d = q.shape

    def hop(k_blk, v_blk, ks_blk):
        kw = (
            dict(q_segment_ids=q_segment_ids, kv_segment_ids=ks_blk)
            if q_segment_ids is not None
            else {}
        )
        return flash_attention_with_lse(q, k_blk, v_blk, causal=False, **kw)

    def step(carry, _):
        o, m, l, k_blk, v_blk, ks_blk = carry
        o_j, lse_j = hop(k_blk, v_blk, ks_blk)
        o, m, l = _merge_lse(o, m, l, o_j, lse_j)
        perm = [(r, (r + 1) % n) for r in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        if ks_blk is not None:
            ks_blk = lax.ppermute(ks_blk, axis_name, perm)
        return (o, m, l, k_blk, v_blk, ks_blk), None

    o0 = jnp.zeros((b, tq, h, d), jnp.float32)
    m0 = jnp.full((b, tq, h), _BIG_NEG, jnp.float32)
    l0 = jnp.zeros((b, tq, h), jnp.float32)
    (o, _, l, _, _, _), _ = lax.scan(
        step, (o0, m0, l0, k, v, kv_segment_ids), jnp.arange(n)
    )
    # A query with no visible key anywhere (all-pad source row) ends with
    # o exactly 0 — each empty hop contributes (o_c=0, lse=-BIG), and while
    # m stays at -BIG the merge adds w=1 to l per hop, so l ends at n, NOT
    # 0. The zero output therefore comes from o, and the max() below only
    # guards the true-zero-l case that the recurrence never produces.
    return (o / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)


def ring_flash_attention(q, k, v, *, axis_name: str = "seq", causal: bool = True,
                         segment_ids=None, window: int | None = None,
                         sinks: int = 0):
    """Ring attention whose per-hop block attention is the pallas flash
    kernel — the within-chip and cross-chip halves of the SAME online
    softmax: each hop computes its block's ``(out, lse)`` in O(T/n) memory
    on the MXU (`flash_attention_with_lse`), and the hop results merge by
    the standard logsumexp recurrence. Versus `ring_attention` (dense
    per-hop scores) this never materializes a [T/n, T/n] f32 score matrix
    in HBM and skips — not just masks — the above-diagonal hops via
    `lax.cond`, so a causal ring does ~half the block work.

    Same contract as `ring_attention`: call inside `shard_map` with
    ``[B, T/n, H, D]`` sequence shards; n == 1 degrades to exactly the
    local flash/dense path.

    ``segment_ids`` ([B, T/n], this device's shard of the packed-sequence
    ids) restricts attention to equal-id pairs: the kv ids rotate around the
    ring with their K/V blocks, and within each hop the kernel's block-level
    early-out prunes segment-disjoint tiles — so a packed ring pays ICI for
    every hop but FLOPs only where documents actually overlap. Every token
    belongs to its own segment and (causal) sees at least itself, so the
    merge normalizer never vanishes.

    ``window`` (requires ``causal``): sliding-window band over GLOBAL
    positions. Each hop runs the kernel with ``q_offset = hop_distance ×
    T/n`` so the band arithmetic sees true positions — hops entirely
    outside the window become static skip branches (zero kernel calls, via
    `lax.switch` over the hop distance), and a partially-covered hop
    block-skips its stale tiles in-kernel. The ring itself still makes all
    n − 1 ppermute hops (a collective must be uniform across the axis), so
    a window prunes FLOPs, not ICI traffic.

    ``sinks`` (global+local; requires ``window``): the first ``sinks``
    GLOBAL positions stay visible beyond the band. They live in global
    block 0, which visits every device once per rotation — the hop holding
    it (`j == 0`, a `lax.cond`) adds a small dense (out, lse) contribution
    over just the sink columns, masked disjointly from the band, merged by
    the same logsumexp recurrence as every other hop. Needs
    ``sinks ≤ T/n`` (the sink region must fit the first shard)."""
    from horovod_tpu.ops.flash_attention import flash_attention_with_lse

    check_window(window, causal)
    if sinks:
        if sinks < 0:
            raise ValueError(f"sinks must be >= 0, got {sinks}")
        if window is None:
            raise ValueError(
                "sinks need window set (full causal already sees them)"
            )
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    if sinks > t_local:
        raise ValueError(
            f"sinks ({sinks}) must fit one sequence shard (T/n = {t_local})"
        )

    def seg_kw(ks_blk):
        return (
            dict(q_segment_ids=segment_ids, kv_segment_ids=ks_blk)
            if segment_ids is not None
            else {}
        )

    def skip(*_):
        # Contributes nothing: lse = -BIG weights it to zero in the merge
        # without running any attention.
        return (
            jnp.zeros((b, t_local, h, d), q.dtype),
            jnp.full((b, t_local, h), _BIG_NEG, jnp.float32),
        )

    def hop_contrib(i, j, k_blk, v_blk, ks_blk):
        """(out, lse) of my queries against the block born at rank j,
        held here on hop i."""

        def diag(_):
            return flash_attention_with_lse(
                q, k_blk, v_blk, causal=True, window=window, **seg_kw(ks_blk)
            )

        def full(_):
            return flash_attention_with_lse(
                q, k_blk, v_blk, causal=False, **seg_kw(ks_blk)
            )

        if not causal:
            return full(None)
        if window is not None:
            # Hop distance d = my − j (mod n) equals the scan index i for
            # past blocks; wrapped hops (i > my, future blocks) route to the
            # extra skip branch. Each past distance gets its own STATIC
            # q_offset = d·T/n so the kernel's band arithmetic is global —
            # and distances whose newest key is already stale collapse to
            # skip at trace time (no kernel call compiled at all).
            def past(dist):
                if dist * t_local - (t_local - 1) >= window:
                    return skip  # even (row 0, col T/n−1) is out of band

                def branch(_):
                    return flash_attention_with_lse(
                        q, k_blk, v_blk, causal=True, window=window,
                        q_offset=dist * t_local, **seg_kw(ks_blk)
                    )

                return branch

            branches = [diag if dist == 0 else past(dist) for dist in range(n)]
            return lax.switch(jnp.where(i <= my, i, n), branches + [skip], None)
        return lax.cond(
            j == my, diag, lambda x: lax.cond(j < my, full, skip, x), None
        )

    def sink_contrib(k_blk, v_blk, ks_blk):
        """(out, lse) of my queries against the sink columns of global
        block 0 (currently held here): cols < sinks AND below the band —
        disjoint from every band tile, so nothing is counted twice. Dense
        [T/n, sinks] scores: the sink region is small by design."""
        kb = k_blk[:, :sinks]
        vb = v_blk[:, :sinks]
        s_ = _scores(q, kb, d ** -0.5)
        rows = (my * t_local + jnp.arange(t_local))[:, None]  # global q pos
        cols = jnp.arange(sinks)[None, :]
        keep = cols <= rows - window  # below the band (and causal: col<row)
        if ks_blk is not None:
            keep = keep[None] & (
                segment_ids[:, :, None] == ks_blk[:, None, :sinks]
            )
            keep = keep[:, None]  # [B, 1, Tq, S]
        else:
            keep = keep[None, None]  # [1, 1, Tq, S]
        s_ = jnp.where(keep, s_, _BIG_NEG)
        mx = s_.max(axis=-1, keepdims=True)
        p = jnp.exp(s_ - mx)
        p = jnp.where(keep, p, 0.0)
        lsum = p.sum(axis=-1, keepdims=True)
        empty = lsum == 0.0
        l_safe = jnp.where(empty, 1.0, lsum)
        o_ = jnp.einsum(
            "bhqk,bkhd->bqhd", (p / l_safe).astype(vb.dtype), vb,
            preferred_element_type=jnp.float32,
        ).astype(q.dtype)
        lse_ = jnp.where(empty, _BIG_NEG, mx + jnp.log(l_safe))[..., 0]
        return o_, jnp.transpose(lse_, (0, 2, 1))  # [B, Tq, H]

    merge = _merge_lse

    def step(carry, i):
        o, m, l, k_blk, v_blk, ks_blk = carry
        j = (my - i) % n  # the block born at rank j is here after i hops
        o_j, lse_j = hop_contrib(i, j, k_blk, v_blk, ks_blk)
        o, m, l = merge(o, m, l, o_j, lse_j)
        if sinks:
            o_s, lse_s = lax.cond(
                j == 0,
                lambda _: sink_contrib(k_blk, v_blk, ks_blk),
                skip,
                None,
            )
            o, m, l = merge(o, m, l, o_s, lse_s)
        perm = [(r, (r + 1) % n) for r in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        if ks_blk is not None:
            ks_blk = lax.ppermute(ks_blk, axis_name, perm)
        return (o, m, l, k_blk, v_blk, ks_blk), None

    o0 = jnp.zeros((b, t_local, h, d), jnp.float32)
    m0 = jnp.full((b, t_local, h), _BIG_NEG, jnp.float32)
    l0 = jnp.zeros((b, t_local, h), jnp.float32)
    (o, _, l, _, _, _), _ = lax.scan(
        step, (o0, m0, l0, k, v, segment_ids), jnp.arange(n)
    )
    return (o / l[..., None]).astype(q.dtype)


def ulysses_attention(q, k, v, *, axis_name: str = "seq", causal: bool = True,
                      segment_ids=None, window: int | None = None,
                      sinks: int = 0):
    """All-to-all sequence parallelism: swap seq-sharding for head-sharding,
    attend over the full sequence locally, swap back.

    Inside `shard_map` with ``[B, T/n, H, D]`` shards; requires ``H % n == 0``.
    Two `lax.all_to_all` pairs per call — cheaper than a ring when n is small
    and heads are plentiful; the full-sequence [T] intermediate bounds the
    max context per chip (ring has no such bound).

    The local full-sequence attention runs the pallas flash kernel when its
    tiling holds (O(T) memory — without it, the [T, T] score matrix would
    cancel most of what head-swapping buys at long context), with the dense
    path as fallback exactly like `flash_attention` itself."""
    n = lax.axis_size(axis_name)
    if q.shape[2] % n != 0:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by the seq axis ({n})"
        )

    def to_heads(x):  # [B,T/n,H,D] -> [B,T,H/n,D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def to_seq(x):  # [B,T,H/n,D] -> [B,T/n,H,D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    from horovod_tpu.ops.flash_attention import flash_attention

    seg_kw = {}
    if segment_ids is not None:
        # Per-token ids ([B, T/n] shard) have no head axis to swap; after the
        # head-swap every device attends over the FULL sequence, so it needs
        # the full ids — one [B, T] int gather, negligible next to K/V.
        full_ids = lax.all_gather(segment_ids, axis_name, axis=1, tiled=True)
        seg_kw = dict(q_segment_ids=full_ids, kv_segment_ids=full_ids)
    out = flash_attention(
        to_heads(q), to_heads(k), to_heads(v), causal=causal, window=window,
        sinks=sinks, **seg_kw
    )
    return to_seq(out)
