"""Flash attention — pallas TPU kernel for the local attention hot path.

The single hottest op of the transformer family, implemented blockwise so
the [Tq, Tk] score matrix never touches HBM: each grid step streams one K/V
block through VMEM, folds it into an online-softmax accumulator (running
max / normalizer / unnormalized output, the same recurrence
`ops.attention.ring_attention` uses across chips — this kernel is the
within-chip counterpart), and writes the normalized output once per Q block.
O(T) memory instead of O(T²), matmuls on the MXU in the input dtype,
statistics in float32.

Backward is a custom VJP that recomputes the scores from the saved
logsumexp, so residual memory is O(T) as well. Where a head's whole dQ fits
VMEM (`fused_backward`: from the shapes and the chip's VMEM alone) it is ONE
kernel: the K block anchored and the Q blocks swept, dK/dV accumulated a
block and dQ a head, so a tile's scores and dP are formed once. Otherwise
the standard two kernels (dQ swept over K blocks, dK/dV swept over Q
blocks), which form them twice.

`flash_attention` is shape-checked: when the kernel's tiling constraints
don't hold it runs the dense reference (`ops.attention.dense_attention`)
instead and says so with a `KernelFallbackWarning`, once per shape — a
changed algorithm is never silent. Off-TPU the same kernel runs in the
pallas interpreter (`default_interpret`), which is how the unit tests
validate it without a chip.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.attention import check_window, dense_attention

_BIG_NEG = -1e30
# 1024-square tiles where they fit. Measured on the chip at the one call of
# the benchmark that takes them (bf16, B2·T2048·H16·D128; kernels alone,
# forward + backward, PERF.md §6, PR 34): 1.91 ms a layer at 1024² against
# 2.03 at 512² (2.15 against 2.41 before the tiles were classified) — the
# 1024² grid runs 3 of its 4 steps against 10 of 16, but a step's fixed
# cost (pipeline bookkeeping, the statistics' rescale, ≈ 130 bundles and
# its DMA waits) is paid a quarter as often. The [bq, bk] f32 score tile is
# 4 MB, and the backward kernels keep several of them live, which puts them
# near v5e's 16 MiB scoped-VMEM limit: `pick_blocks` drops to 512 wherever
# the chip's compiler was seen to refuse 1024². An oversized tile fails
# LOUDLY at Mosaic compile time (not silent wrong results) — pass
# block_q/block_k=512 there.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024

# The kernels' names (`pallas_call(name=)`): the innermost name-stack entry,
# so the compiled HLO instruction and the profiler's device event carry them
# (`%hvt_flash_fwd.3 = ... custom-call(...)`) whatever flax scope, `jvp`,
# `transpose` or `shard_map` the call sits in. The benchmark's per-kernel
# metrics key on them (chipbench/spans.py): keep them stable, and never end
# one in a digit (the reduction strips an instruction's trailing number).
# The sink-only dK/dV pass is dK/dV work and shares its name. A backward is
# KERNEL_BWD alone or KERNEL_DQ + KERNEL_DKV (`fused_backward`).
KERNEL_FWD = "hvt_flash_fwd"
KERNEL_BWD = "hvt_flash_bwd"
KERNEL_DQ = "hvt_flash_dq"
KERNEL_DKV = "hvt_flash_dkv"


# Segment-id operand layout (Mosaic-friendly, no in-kernel transposes):
# q ids ride the SUBLANE axis as [B, Tq, LANES] (value broadcast across the
# 128 lanes), kv ids ride the LANE axis as [B, SUBLANES, Tk] — so the
# [bq, bk] equality mask is a lane-tile of the q block against row 0 of the
# k block, both already in their natural in-register orientation.
_SEG_LANES = 128
_SEG_SUBLANES = 8


def _nonneg(x):
    """max(x, 0) by operators alone. The scalar arithmetic of this file's
    grids (which tile a step holds, its class, the block a step fetches)
    runs on traced coordinates in kernels and index maps and on numpy ones
    in `tile_census` and the tests, so it is written without ``jnp.*``."""
    return x * (x > 0)


def _affine_floordiv(i, a, c, d):
    """(i·a + c) // d for a coordinate ``i`` and Python ints a, c, d > 0.
    Where d divides a (equal tiles: every call the benchmark makes) the
    division is of constants: a traced floor division costs the scalar core
    a dozen bundles, in every index map of every grid step."""
    if a % d == 0:
        return i * (a // d) + c // d
    return (i * a + c) // d


def _band_lo_k(iq, bq, bk, offset, window):
    """First k block holding any in-band column for q block ``iq`` (the
    oldest visible key of the block's first row), clamped to 0. Floor
    division handles a negative numerator (band starting before key 0)."""
    return _nonneg(_affine_floordiv(iq, bq, offset - (window - 1), bk))


def _band_lo_q(ik, bq, bk, offset):
    """First q block holding any row that sees k block ``ik`` causally
    (rows r with r + offset ≥ c for some c in the block), clamped to 0."""
    return _nonneg(_affine_floordiv(ik, bk, -offset, bq))


def _last_k(iq, bq, bk, offset):
    """Last k block any row of q block ``iq`` sees causally (the newest
    key of the block's last row), clamped to 0."""
    return _nonneg(_affine_floordiv(iq, bq, bq - 1 + offset, bk))


def _tile_class(iq, ik, bq, bk, offset, window=None):
    """(needed, full) of the causal tile (q block ``iq``, k block ``ik``),
    from scalars alone. Rows sit at key positions r + ``offset``
    (``offset = Tk − Tq`` aligns the sequences at the END, the standard
    cross-attention/decode convention, matching `_dense_with_lse`; zero
    for self-attention) and see columns c ≤ r + offset, with ``window``
    only the band r + offset − c < window (Mistral-style local attention:
    each query's ``window`` most recent keys, itself included).

    ``needed`` is False when the whole tile is provably masked: above the
    diagonal (its first column past its last row) or below the band (even
    its newest key is stale for its oldest query). ``full`` is True when
    every row provably sees every column: the tile's last column ≤ its
    first row, and its first column inside its LAST row's window. A tile
    that is needed and not full is an EDGE tile: the diagonal or the
    band's lower edge crosses it, and only it needs a mask."""
    r0 = iq * bq + offset
    r1 = r0 + bq - 1
    c0 = ik * bk
    c1 = c0 + bk - 1
    needed = c0 <= r1
    full = c1 <= r0
    if window is not None:
        needed &= c1 > r0 - window
        full &= c0 > r1 - window
    return needed, full


def _k_sweep_steps(bq, bk, window, sinks, nk):
    """Length of a q block's sweep over k blocks. A full grid walks all
    ``nk``; a banded (sliding-window) one only the ≤ nb blocks that can
    intersect the block's band (span bq + window − 1 columns, any
    alignment), and sinks prepend one pinned tile (k block 0)."""
    if window is None:
        return nk
    return min(nk, (bq + window - 2) // bk + 2) + (1 if sinks else 0)


def _k_sweep_tile(iq, jj, causal, bq, bk, offset, window, sinks, nk):
    """(ik, is_sink, needed, full): the k block that step ``jj`` of q block
    ``iq``'s sweep holds, and its class — shared by the forward and dQ
    kernels and `tile_census`, so they cannot disagree.

    Banded grids enumerate ONLY the k blocks near the band: step jj holds
    lo(iq) + jj, and the duplicates clipped at the last block are not
    needed. With sinks, step 0 is the pinned SINK tile (k block 0,
    ``is_sink``; never called full) and the band walks jj − 1; a q block
    whose rows are all inside the window needs no sink tile (the band
    tiles already cover block 0). A call that is not causal has no mask:
    every tile is full."""
    if not causal:
        return jj, None, True, True
    if window is None:
        return (jj, None) + _tile_class(iq, jj, bq, bk, offset)
    lo = _band_lo_k(iq, bq, bk, offset, window)
    is_sink = (jj == 0) if sinks else None
    ik = (lo + jj - 1) * (jj > 0) if sinks else lo + jj
    needed, full = _tile_class(iq, ik, bq, bk, offset, window)
    needed &= ik <= nk - 1
    if sinks:
        sink_needed = iq * bq + bq - 1 + offset >= window
        needed = (is_sink & sink_needed) | (~is_sink & needed)
        full &= ~is_sink
    return ik, is_sink, needed, full


def tile_census(tq, tk, bq, bk, *, causal, window=None, sinks=0,
                q_offset=None, segmented=False):
    """(skipped, edge, full): the grid steps of ONE (b, h) of the forward
    grid by class — static per call, and the gauge ``hvt_flash_tiles``. A
    skipped step builds and fetches nothing, a full one runs the update
    with no mask operation, an edge one builds its [bq, bk] mask, or runs
    by strips where it is a triangle (`subtile_census`). The dQ
    grid is the same; the dK/dV grid holds the same tiles transposed. With
    segment ids nothing is provably full and every tile that runs is an
    edge (the id ranges skip more of them at run time)."""
    nq, nk = tq // bq, tk // bk
    off = tk - tq if q_offset is None else q_offset
    iq, jj = np.indices((nq, _k_sweep_steps(bq, bk, window, sinks, nk)))
    _, _, needed, full = _k_sweep_tile(
        iq, jj, causal, bq, bk, off, window, sinks, nk)
    needed = np.broadcast_to(needed, iq.shape)
    full = np.broadcast_to(full, iq.shape) & needed & (not segmented)
    return (int((~needed).sum()), int((needed & ~full).sum()),
            int(full.sum()))


# Triangle tiles. Where the tiles are square (bq == bk = b) and the q↔k
# offset and the window are multiples of b, an edge tile is one of two
# triangles, told apart by its first row r0 and first column c0 alone:
# the causal DIAGONAL (c0 == r0: a pair is visible iff its tile-local
# column κ ≤ its row ρ) and the band's LOWER EDGE (c0 == r0 − window:
# visible iff κ > ρ). Such a tile runs in row strips of _STRIP_ROWS: strip
# i of the diagonal reads columns [0, (i+1)·s), of the lower edge [i·s, b),
# and only the s × s block on the triangle's own diagonal builds a mask —
# the tile's empty half is neither loaded nor computed. The strip height
# is the MXU's side and the lanes' count (a strip's scores are [s, ·] over
# a lane-aligned column slice); of the heights that tile 512 and 1024, 128
# gives the shortest static schedules (`build/flash_bundles.py`: at 256
# the bodies are 8–36 % longer; PERF.md §6).
_STRIP_ROWS = 128


def _triangle_tiles(tq, tk, bq, bk, offset, window):
    """{kind: triangle tiles a (b, h)} of a call whose edge tiles are all
    triangles, else {}: q block iq has its diagonal tile where its first
    row r0 = iq·b + offset is a key (0 ≤ r0 < Tk), its band's lower-edge
    tile where r0 − window is one. Every other needed tile is full."""
    if bq != bk or offset % bk or (window is not None and window % bk):
        return {}
    r0 = np.arange(tq // bq) * bq + offset
    tiles = {"diag": int(((r0 >= 0) & (r0 < tk)).sum())}
    if window is not None:
        tiles["band"] = int(((r0 >= window) & (r0 - window < tk)).sum())
    return tiles


def _triangle_kinds(tq, tk, bq, bk, *, causal, window, sinks, offset,
                    segmented):
    """The triangle kinds of a call's grid that run by strips, static per
    call; () where the call takes the general edge body (no mask, segment
    ids, sinks, unequal tiles, an offset or window off the tile grid, a
    tile no taller than a strip)."""
    if not causal or segmented or sinks or bk <= _STRIP_ROWS \
            or bk % _STRIP_ROWS:
        return ()
    tiles = _triangle_tiles(tq, tk, bq, bk, offset, window)
    return tuple(kind for kind, n in tiles.items() if n)


def _triangle_strips(kind, b):
    """[(rows, cols, at)]: the row strips of a triangle tile of side ``b``,
    the columns each reads, and where among them the s × s block the
    triangle's edge crosses begins (the diagonal's last, the lower edge's
    first). Narrowest strip first: the order the chip's compiler schedules
    best (PERF.md §6)."""
    s, strips = _STRIP_ROWS, []
    for i in range(b // s):
        lo, hi = i * s, (i + 1) * s
        if kind == "diag":
            strips.append((slice(lo, hi), slice(0, hi), lo))
        else:
            lo, hi = b - hi, b - lo
            strips.append((slice(lo, hi), slice(lo, b), 0))
    return strips


def subtile_census(tq, tk, bq, bk, *, causal, window=None, sinks=0,
                   q_offset=None, segmented=False):
    """(run_full, run_masked, skipped): the s × s sub-blocks of ONE (b, h)
    of the forward grid's triangle tiles by what they do — the gauge
    ``hvt_flash_subtiles``. (0, 0, 0) where the call runs no triangle
    tile."""
    off = tk - tq if q_offset is None else q_offset
    if not _triangle_kinds(
            tq, tk, bq, bk, causal=causal, window=window, sinks=sinks,
            offset=off, segmented=segmented):
        return (0, 0, 0)
    n = bk // _STRIP_ROWS
    tiles = sum(_triangle_tiles(tq, tk, bq, bk, off, window).values())
    return (tiles * n * (n - 1) // 2, tiles * n, tiles * n * (n - 1) // 2)


def _is_triangle(kind, iq, ik, b, offset, window):
    """Whether tile (iq, ik) is the ``kind`` triangle of its q block: a
    scalar compare of the grid's coordinates (`_triangle_tiles`)."""
    shift = offset if kind == "diag" else offset - window
    return ik == iq + shift // b


def _triangle_mask(kind):
    """The [s, s] 0/1 float mask of the block a triangle's edge crosses:
    κ ≤ ρ on the diagonal, κ > ρ on the band's lower edge."""
    s = _STRIP_ROWS
    rows = lax.broadcasted_iota(jnp.int32, (s, s), 0)
    cols = lax.broadcasted_iota(jnp.int32, (s, s), 1)
    keep = rows >= cols if kind == "diag" else cols > rows
    return keep.astype(jnp.float32)


def _tile_mask(iq, ik, causal, segmented, bq, bk, offset, window,
               qs_ref, ks_ref, sinks=0, is_sink=None):
    """The [bq, bk] 0/1 float mask of an edge tile, built INSIDE its branch
    (None for a call with no mask). Causal: rows iq*bq + r + offset ≥ cols
    ik*bk + c, and inside the band where there is a ``window``.

    ``sinks``/``is_sink``: global+local attention. A SINK tile (is_sink
    True — a traced scalar when one grid handles both kinds, or the
    literal True for a sink-only kernel) masks to cols < sinks AND below
    the band — strictly disjoint from band tiles, so a (row, col) pair
    visible through both the band and the sink region is never counted
    twice. Segment ids multiply in their equality mask."""
    mask = None
    if causal:
        rows = iq * bq + offset + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ik * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        keep = rows >= cols
        if window is not None:
            keep &= cols > rows - window
        mask = keep.astype(jnp.float32)
        if sinks and is_sink is not None:
            sink_mask = (
                (rows >= cols) & (cols < sinks) & (cols <= rows - window)
            ).astype(jnp.float32)
            # f32 select: Mosaic cannot legalize a vector select on i1.
            mask = sink_mask if is_sink is True else jnp.where(
                is_sink, sink_mask, mask)
    if segmented:
        qs = qs_ref[0]  # [bq, LANES]
        ks = ks_ref[0, 0:1, :]  # [1, bk]
        q_ids = jnp.tile(qs, (1, bk // _SEG_LANES))  # [bq, bk]
        smask = (q_ids == ks).astype(jnp.float32)
        mask = smask if mask is None else mask * smask
    return mask


def _segments_overlap(qs_ref, ks_ref):
    """Segment early-out: whether the q block's id range can intersect the
    k block's — a NECESSARY condition for any equality match, so the skip
    is sound for arbitrary id layouts, and tight for the contiguous runs
    packing produces."""
    qs = qs_ref[0]  # [bq, LANES]
    ks = ks_ref[0, 0:1, :]  # [1, bk]
    return (jnp.min(ks) <= jnp.max(qs)) & (jnp.max(ks) >= jnp.min(qs))


# A whole tile's rows or columns, as `update` indexes them.
_ALL = slice(None)


def _masked(x, mask, at, fn):
    """``fn(x, mask)`` where the mask covers all of ``x`` (``at`` None),
    else on the mask's lanes of ``x`` from lane ``at`` on alone: the other
    lanes of a triangle's strip are visible and take no mask operation."""
    if at is None:
        return fn(x, mask)
    w = mask.shape[1]
    pieces = [x[:, :at], fn(x[:, at:at + w], mask), x[:, at + w:]]
    return jnp.concatenate([p for p in pieces if p.shape[1]], axis=1)


def _to_big_neg(s, mask):
    """Masked scores pushed to _BIG_NEG, BEFORE exp: a large masked score
    would overflow exp to inf, and the TPU's inf*0 is NaN — the post-hoc
    ``p * mask`` alone is only safe in interpret mode."""
    return s + (1.0 - mask) * _BIG_NEG


# The strips of a whole tile: all its rows and columns, under the mask
# given, if any (`_masked`).
_WHOLE = ((_ALL, _ALL, None, None),)


def _by_strips(update, kind, b):
    """A triangle tile as its row strips: one ``update`` of them all, the
    one s × s mask built once."""
    mask = _triangle_mask(kind)
    update([(rows, cols, mask, at)
            for rows, cols, at in _triangle_strips(kind, b)])


def _update_by_class(needed, full, masked, make_mask, update,
                     triangles=()):
    """Run ``update(strips)`` as the tile's class asks, so that nothing of
    the score tile's shape is computed outside a branch: a skipped step
    runs neither branch; a full tile runs the update with NO mask
    operation (on a wholly visible tile they add 0 and multiply by 1); an
    edge tile builds its mask inside its branch and runs today's
    arithmetic. ``masked`` False: the call has no mask at all. ``full``
    the literal False: nothing is provable (segment ids, the sink-only
    pass) and every tile that runs is an edge. ``triangles``: [(is_kind,
    kind, b)] where every edge tile is one of them (`_triangle_kinds`):
    each kind runs by strips, and no general edge body is built.

    ``strips`` is [(rows, cols, mask, at)] (`_WHOLE` by default): each
    strip's rows against its columns, under its mask (`_masked`); the
    update runs each of its phases over every strip before the next,
    which the chip's compiler schedules best (PERF.md §6)."""
    if not masked:
        pl.when(needed)(lambda: update())
    elif full is False:
        pl.when(needed)(lambda: update([(_ALL, _ALL, make_mask(), None)]))
    else:
        pl.when(needed & full)(lambda: update())
        if not triangles:
            pl.when(needed & ~full)(
                lambda: update([(_ALL, _ALL, make_mask(), None)]))
        for is_kind, kind, b in triangles:
            pl.when(needed & is_kind)(
                functools.partial(_by_strips, update, kind, b))


def _kernel_triangles(kinds, iq, ik, b, offset, window):
    """`_update_by_class`'s ``triangles`` of a kernel's tile (iq, ik)."""
    return [(_is_triangle(kind, iq, ik, b, offset, window), kind, b)
            for kind in kinds]


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, segmented,
                bq, bk, offset, window, nk, sinks=0, triangles=()):
    if segmented:
        qs_ref, ks_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
        qs_ref = ks_ref = None
    iq, jj = pl.program_id(2), pl.program_id(3)
    nj = pl.num_programs(3)
    # Block skip: a K block strictly above the causal diagonal or below the
    # band — or with no possible segment match — contributes nothing;
    # predicate the whole update away (half the FLOPs for causal; one
    # matmul per co-resident segment pair for packed sequences). The kernel
    # predicates on the TRUE coordinates: the index maps may name another
    # block for a step that is skipped (`_k_sweep_maps`).
    ik, is_sink, needed, full = _k_sweep_tile(
        iq, jj, causal, bq, bk, offset, window, sinks, nk)
    if segmented:
        needed &= _segments_overlap(qs_ref, ks_ref)
        full = False

    @pl.when(jj == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _BIG_NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    def make_mask():
        return _tile_mask(
            iq, ik, causal, segmented, bq, bk, offset, window, qs_ref,
            ks_ref, sinks=sinks, is_sink=is_sink)

    def update(strips=_WHOLE):
        # Rows are independent: a strip's update is its rows' share of a
        # whole tile's.
        qkv = [(q_ref[0, 0, rows, :], k_ref[0, 0, cols, :],
                v_ref[0, 0, cols, :]) for rows, cols, _, _ in strips]

        scores = []
        for (q, k, _), (_, _, mask, at) in zip(qkv, strips):
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [rows, cols]
            if mask is not None:
                s = _masked(s, mask, at, _to_big_neg)
            scores.append(s)

        stats = []
        for s, (rows, _, mask, at) in zip(scores, strips):
            m_prev = m_ref[rows, 0:1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            if mask is not None:
                p = _masked(p, mask, at, jnp.multiply)  # exact zeros
            stats.append((m_new, alpha, p))
        for (_, _, v), (m_new, alpha, p), (rows, _, _, _) in zip(
                qkv, stats, strips):
            l_ref[rows, 0:1] = (
                l_ref[rows, 0:1] * alpha + p.sum(axis=-1, keepdims=True))
            acc_ref[rows] = acc_ref[rows] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[rows, 0:1] = m_new

    _update_by_class(
        needed, full, causal or segmented, make_mask, update,
        _kernel_triangles(triangles, iq, ik, bk, offset, window))

    @pl.when(jj == nj - 1)
    def _():
        l = l_ref[:, 0:1]
        # A row every key is masked away from (a padding segment with no kv
        # tokens, or causal rows before the first key when Tk < Tq) has
        # l == 0: emit 0 output and a -inf-like lse so any downstream
        # online-softmax merge weights it to zero — never NaN.
        empty = l == 0.0
        l_safe = jnp.where(empty, 1.0, l)
        o_ref[0, 0, :, :] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = jnp.where(
            empty, _BIG_NEG, m_ref[:, 0:1] + jnp.log(l_safe)
        )


def _bwd_operands(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, rows,
                  cols):
    """(q, k, v, dO, lse, δ) of a strip: its rows of the q-side operands
    (dO in float32), its columns of k and v."""
    return (q_ref[0, 0, rows, :], k_ref[0, 0, cols, :], v_ref[0, 0, cols, :],
            do_ref[0, 0, rows, :].astype(jnp.float32),
            lse_ref[0, 0, rows, :], delta_ref[0, 0, rows, :])


def _bwd_probs(tiles, strips, scale):
    """Each strip's probabilities exp(S − lse), recomputed from the saved
    logsumexp, with exact zeros where its mask, if any, hides a pair."""
    scores = []
    for (q, k, *_), (_, _, mask, at) in zip(tiles, strips):
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if mask is not None:
            s = _masked(s, mask, at, _to_big_neg)  # as the forward does
        scores.append(s)
    probs = []
    for (*_, lse, _), s, (_, _, mask, at) in zip(tiles, scores, strips):
        p = jnp.exp(s - lse)
        if mask is not None:
            p = _masked(p, mask, at, jnp.multiply)
        probs.append(p)
    return probs


def _bwd_ds(tile, p):
    """dS = P ⊙ (dP − δ), dP = dO · Vᵀ: the softmax's jacobian."""
    _, _, v, do, _, delta = tile
    dp = jax.lax.dot_general(
        do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return p * (dp - delta)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   scale, causal, segmented, bq, bk, offset, window, nk,
                   sinks=0, triangles=()):
    if segmented:
        qs_ref, ks_ref, dq_ref, acc_ref = rest
    else:
        dq_ref, acc_ref = rest
        qs_ref = ks_ref = None
    iq, jj = pl.program_id(2), pl.program_id(3)
    nj = pl.num_programs(3)
    ik, is_sink, needed, full = _k_sweep_tile(
        iq, jj, causal, bq, bk, offset, window, sinks, nk)
    if segmented:
        needed &= _segments_overlap(qs_ref, ks_ref)
        full = False

    @pl.when(jj == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def make_mask():
        return _tile_mask(
            iq, ik, causal, segmented, bq, bk, offset, window, qs_ref,
            ks_ref, sinks=sinks, is_sink=is_sink)

    def update(strips=_WHOLE):
        tiles = [_bwd_operands(q_ref, k_ref, v_ref, do_ref, lse_ref,
                               delta_ref, rows, cols)
                 for rows, cols, _, _ in strips]
        probs = _bwd_probs(tiles, strips, scale)
        dss = [_bwd_ds(tile, p) for tile, p in zip(tiles, probs)]
        for (_, k, *_), ds, (rows, _, _, _) in zip(tiles, dss, strips):
            acc_ref[rows] = acc_ref[rows] + jax.lax.dot_general(
                ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale

    _update_by_class(
        needed, full, causal or segmented, make_mask, update,
        _kernel_triangles(triangles, iq, ik, bk, offset, window))

    @pl.when(jj == nj - 1)
    def _():
        dq_ref[0, 0, :, :] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    scale, causal, segmented, bq, bk, offset, window, nq,
                    sinks=0, sink_only=False, with_dq=False,
                    triangles=()):
    """dK/dV of one k block a sweep and, ``with_dq`` (the fused backward),
    dQ of the whole (b, h) beside them: every tile's ``ds·k`` lands on its q
    block's rows of a float32 [Tq, Dk] scratch that outlives the sweeps. A
    q block's sum over the k blocks then runs in ascending ``ik``, the
    order of `_bwd_dq_kernel`'s sweep, so the two forms agree to the bit."""
    qs_ref = ks_ref = dq_ref = dq_acc = None
    if segmented:
        qs_ref, ks_ref, *rest = rest
    if with_dq:
        dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, dq_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    ik, jj = pl.program_id(2), pl.program_id(3)
    nj = pl.num_programs(3)
    # The transposed sweep: k block ``ik`` anchored, q blocks swept — all of
    # them, or (banded) lo(ik) + jj with the clipped duplicates not needed.
    # The sink-only pass sweeps every q block over the one sink tile.
    banded = window is not None and not sink_only
    iq = _band_lo_q(ik, bq, bk, offset) + jj if banded else jj
    needed, full = True, True
    if sink_only:
        needed, full = iq * bq + bq - 1 + offset >= window, False
    elif causal:
        needed, full = _tile_class(iq, ik, bq, bk, offset, window)
    if banded:
        needed &= iq <= nq - 1
    if segmented:
        needed &= _segments_overlap(qs_ref, ks_ref)
        full = False

    @pl.when(jj == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if with_dq:
        @pl.when((ik == 0) & (jj == 0))
        def _():
            dq_acc[:] = jnp.zeros_like(dq_acc)

    def make_mask():
        return _tile_mask(
            iq, ik, causal, segmented, bq, bk, offset, window, qs_ref,
            ks_ref, sinks=sinks, is_sink=True if sink_only else None)

    def update(strips=_WHOLE):
        tiles = [_bwd_operands(q_ref, k_ref, v_ref, do_ref, lse_ref,
                               delta_ref, rows, cols)
                 for rows, cols, _, _ in strips]
        probs = _bwd_probs(tiles, strips, scale)
        for (_, _, _, do, _, _), p, (_, cols, _, _) in zip(
                tiles, probs, strips):
            # dV += Pᵀ · dO
            dv_acc[cols] = dv_acc[cols] + jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        dss = [_bwd_ds(tile, p) for tile, p in zip(tiles, probs)]
        for (q, *_), ds, (_, cols, _, _) in zip(tiles, dss, strips):
            # dK += dSᵀ · Q
            dk_acc[cols] = dk_acc[cols] + jax.lax.dot_general(
                ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
        if not with_dq:
            return
        for (_, k, *_), ds, (rows, _, _, _) in zip(tiles, dss, strips):
            # dQ[the rows of q block iq] += dS · K, as `_bwd_dq_kernel`
            # forms it
            if rows == _ALL:
                at = pl.ds(pl.multiple_of(iq * bq, bq), bq)
            else:
                height = rows.stop - rows.start
                at = pl.ds(
                    pl.multiple_of(iq * bq + rows.start, height), height)
            dq_acc[at, :] = dq_acc[at, :] + jax.lax.dot_general(
                ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale

    _update_by_class(
        needed, full, causal or segmented, make_mask, update,
        _kernel_triangles(triangles, iq, ik, bk, offset, window))

    @pl.when(jj == nj - 1)
    def _():
        dk_ref[0, 0, :, :] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[:].astype(dv_ref.dtype)

    if with_dq:
        @pl.when((ik == pl.num_programs(2) - 1) & (jj == nj - 1))
        def _():
            dq_ref[0, 0, :, :] = dq_acc[:].astype(dq_ref.dtype)


# Grid-to-T-block selectors: the grid is (b, h, anchor, swept), and
# ``tsel(i, j)`` maps its (anchor, swept) coordinates to a tensor's T-block
# index. What a step FETCHES follows from its class as what it computes
# does: a predicated-off step still streams whatever block its index map
# names, and Pallas issues no DMA for an index that did not change. So a
# skipped step before its sweep's first running one names that one's block;
# a skipped step PAST the last running one names the NEXT sweep's first
# blocks, the anchored tensors' too — the pipeline looks one step ahead,
# so they are fetched beside the sweep's last update and not after a run of
# empty steps, when nothing is left to hide them behind. Outputs stay on
# the anchor. The kernels predicate on the TRUE coordinates
# (`_k_sweep_tile`): which tiles compute does not depend on these maps.
def _anchor(i, j):
    return i


def _sweep(i, j):
    return j


def _fetch_maps(block, first, last, n_anchor, pinned=None):
    """(anchor selector, swept selector) of a sweep's INPUT tensors.
    ``block(i, j)`` is the true swept block of step j of anchor i,
    ``first(i)``/``last(i)`` bound the blocks of its steps that can run
    (clamped to blocks that exist; None: no bound on that side).
    ``pinned``: step 0 holds this block whatever the anchor (the sink
    tile), and ``block`` is the other steps'."""
    def held(i, j):
        at = block(i, j)
        if first is not None:
            at = jnp.maximum(at, first(i))
        if last is not None:
            at = jnp.minimum(at, last(i))
        return at if pinned is None else jnp.where(j == 0, pinned, at)

    if last is None:
        return _anchor, held

    def past(i, j):
        over = (block(i, j) > last(i)) & (i < n_anchor - 1)
        return over if pinned is None else over & (j > 0)

    return (
        lambda i, j: jnp.where(past(i, j), i + 1, i),
        lambda i, j: jnp.where(past(i, j), held(i + 1, 0 * j), held(i, j)),
    )


def _k_sweep_maps(causal, bq, bk, off, window, sinks, nq, nk):
    """(q-block selector, k-block selector) of the forward and dQ grids'
    inputs — shared, so they cannot disagree on which k block a grid step
    reads: the block `_k_sweep_tile` holds at step j of q block i, bounded
    by the last block the q block sees (the steps above the diagonal, and a
    banded sweep's duplicates past the last block)."""
    if not causal:
        return _anchor, _sweep
    last = lambda i: jnp.minimum(_last_k(i, bq, bk, off), nk - 1)  # noqa: E731
    if window is None:
        return _fetch_maps(_sweep, None, last, nq)
    lo = lambda i: _band_lo_k(i, bq, bk, off, window)  # noqa: E731
    if sinks:
        return _fetch_maps(
            lambda i, j: lo(i) + j - 1, None, last, nq, pinned=0)
    return _fetch_maps(lambda i, j: lo(i) + j, None, last, nq)


def _q_sweep_maps(causal, bq, bk, off, window, nq, nk):
    """(steps, k-block selector, q-block selector) of the dK/dV grid's
    inputs: k block i anchored, the q blocks that can see it swept. A full
    causal grid walks all ``nq`` and its steps above the diagonal come
    FIRST. A banded grid walks lo(i) + j over the ≤ nbq blocks a k block's
    band can reach, bounded by the last one inside the window."""
    if not causal:
        return nq, _anchor, _sweep
    lo = lambda i: _band_lo_q(i, bq, bk, off)  # noqa: E731
    if window is None:
        first = lambda i: jnp.minimum(lo(i), nq - 1)  # noqa: E731
        return (nq,) + _fetch_maps(_sweep, first, None, nk)
    last = lambda i: jnp.minimum(  # noqa: E731
        _nonneg(_affine_floordiv(i, bk, bk + window - 2 - off, bq)), nq - 1)
    return (min(nq, (bk + window - 2) // bq + 2),) + _fetch_maps(
        lambda i, j: lo(i) + j, None, last, nk)


def _block_spec(d, bt, tsel):
    """BlockSpec for [B,H,T,D] arrays (D the array's own head size: q and
    k's, or v, o and dO's): one (1, 1, bt, D) tile per (b, h)
    grid point — the (bt, D) tile sits in the trailing dims as the TPU
    lowering requires. ``tsel(i, j)`` maps the grid's (anchor, swept)
    coordinates to this tensor's T-block index."""
    return pl.BlockSpec(
        (1, 1, bt, d), lambda ib, ih, i, j: (ib, ih, tsel(i, j), 0)
    )


def _stat_spec(bq, tsel):
    """[B,H,T,1] per-row statistics (lse / delta)."""
    return pl.BlockSpec(
        (1, 1, bq, 1), lambda ib, ih, i, j: (ib, ih, tsel(i, j), 0)
    )


def _seg_q_spec(bq, tsel):
    """[B, Tq, LANES] q segment ids (no head dim — shared across heads)."""
    return pl.BlockSpec(
        (1, bq, _SEG_LANES), lambda ib, ih, i, j: (ib, tsel(i, j), 0)
    )


def _seg_kv_spec(bk, tsel):
    """[B, SUBLANES, Tk] kv segment ids."""
    return pl.BlockSpec(
        (1, _SEG_SUBLANES, bk), lambda ib, ih, i, j: (ib, 0, tsel(i, j))
    )


def _seg_operands(q_seg, kv_seg, tq, tk):
    """Lift [B, Tq]/[B, Tk] ids into the kernel's register-oriented layouts
    (see _SEG_LANES note). int32; values are opaque labels."""
    qs = lax.broadcast_in_dim(
        q_seg.astype(jnp.int32), (q_seg.shape[0], tq, _SEG_LANES), (0, 1)
    )
    ks = lax.broadcast_in_dim(
        kv_seg.astype(jnp.int32), (kv_seg.shape[0], _SEG_SUBLANES, tk), (0, 2)
    )
    return qs, ks


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11)
)
def _flash(q, k, v, q_seg, kv_seg, causal, window, sinks, q_offset, bq, bk,
           interpret):
    out, _ = _flash_fwd_impl(
        q, k, v, q_seg, kv_seg, causal, window, sinks, q_offset, bq, bk,
        interpret,
    )
    return out


def _note_tiles(*census_args, **census_kwargs) -> None:
    """The gauges ``hvt_flash_tiles{kind}`` and ``hvt_flash_subtiles{kind}``:
    the `tile_census` and `subtile_census` of the last kernel call traced.
    Set by the public entries at trace time (the jitted impls below run
    once a process): both censuses are static per call."""
    from horovod_tpu import obs

    census = tile_census(*census_args, **census_kwargs)
    for kind, steps in zip(("skipped", "edge", "full"), census):
        obs.gauge("hvt_flash_tiles", float(steps), kind=kind)
    census = subtile_census(*census_args, **census_kwargs)
    for kind, blocks in zip(("run_full", "run_masked", "skipped"), census):
        obs.gauge("hvt_flash_subtiles", float(blocks), kind=kind)


# Jitted, so that the N identical attention calls of a model's layers share
# ONE traced and ONE lowered copy of the kernels (an inner jit is traced once
# a process and lowered once a program; XLA inlines it). Tracing and lowering
# a Pallas kernel is Python work that no compile cache keeps: un-jitted it was
# paid per layer and per program — 0.15 s a layer before the kernels had two
# update bodies, 0.37 s with them (`setup_s`: PERF.md §6, PR 34).
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_fwd_impl(q, k, v, q_seg, kv_seg, causal, window, sinks, q_offset,
                    bq, bk, interpret):
    # Kernel layout is [B, H, T, D] so the (T-block, D) tile occupies the
    # trailing dims; callers pass [B, T, H, D]. K/V carry their own Tk
    # (cross-attention); causality aligns the sequence ENDS via offset.
    # q and k share one head size (the scores' contraction, and the scale),
    # v and the output another: latent attention's 192 | 128.
    qt, kt, vt = (jnp.transpose(x, (0, 2, 1, 3)) for x in (q, k, v))
    b, h, tq, d = qt.shape
    tk, d_v = kt.shape[2], vt.shape[3]
    segmented = q_seg is not None
    scale = d ** -0.5
    off = tk - tq if q_offset is None else q_offset
    nq, nk = tq // bq, tk // bk
    # Sliding window: the swept grid axis walks only the ≤ nb k blocks that
    # can intersect q block i's band — O(T·window) tiles AND K/V DMA
    # instead of O(T²) (a tile the grid never names streams nothing).
    nb = _k_sweep_steps(bq, bk, window, sinks, nk)
    qsel, ksel = _k_sweep_maps(causal, bq, bk, off, window, sinks, nq, nk)
    grid = (b, h, nq, nb)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, segmented=segmented,
        bq=bq, bk=bk, offset=off, window=window, nk=nk, sinks=sinks,
        triangles=_triangle_kinds(
            tq, tk, bq, bk, causal=causal, window=window, sinks=sinks,
            offset=off, segmented=segmented),
    )
    in_specs = [
        _block_spec(d, bq, qsel),
        _block_spec(d, bk, ksel),
        _block_spec(d_v, bk, ksel),
    ]
    operands = [qt, kt, vt]
    if segmented:
        in_specs += [_seg_q_spec(bq, qsel), _seg_kv_spec(bk, ksel)]
        operands += list(_seg_operands(q_seg, kv_seg, tq, tk))
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            _block_spec(d_v, bq, _anchor),
            _stat_spec(bq, _anchor),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tq, d_v), q.dtype),
            jax.ShapeDtypeStruct((b, h, tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d_v), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_FWD,
    )(*operands)
    return jnp.transpose(out, (0, 2, 1, 3)), lse


def _flash_fwd(q, k, v, q_seg, kv_seg, causal, window, sinks, q_offset, bq,
               bk, interpret):
    out, lse = _flash_fwd_impl(
        q, k, v, q_seg, kv_seg, causal, window, sinks, q_offset, bq, bk,
        interpret,
    )
    return out, (q, k, v, q_seg, kv_seg, out, lse)


def _flash_bwd(causal, window, sinks, q_offset, bq, bk, interpret, res, g):
    return _flash_bwd_core(
        causal, window, sinks, q_offset, bq, bk, interpret, res, g, None
    )


def _flash_bwd_core(causal, window, sinks, q_offset, bq, bk, interpret, res,
                    g, g_lse):
    """Both entries' backward rule: the form is picked here, from the
    shapes alone, and the gauge ``hvt_flash_backward{impl}`` reads which
    the last backward traced took."""
    from horovod_tpu import obs

    q = res[0]
    fused = fused_backward(q.shape[1], q.shape[3], q.dtype, sinks=sinks)
    for impl, taken in (("fused", fused), ("split", not fused)):
        obs.gauge("hvt_flash_backward", float(taken), impl=impl)
    return _flash_bwd_impl(
        fused, causal, window, sinks, q_offset, bq, bk, interpret, res, g,
        g_lse,
    )


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6, 7))  # as above
def _flash_bwd_impl(fused, causal, window, sinks, q_offset, bq, bk, interpret,
                    res, g, g_lse):
    """Shared backward: the lse cotangent (from `flash_attention_with_lse`
    consumers like the ring merge) folds into the per-row jacobian term —
    with s → p = exp(s−lse), o = p·v:  ds = p ⊙ (dp − (δ − dlse)) where
    δ_i = Σ_d dO·O, because ∂lse/∂s = p. So the kernels run unchanged with
    an adjusted δ. ``fused``: one call (KERNEL_BWD), the dK/dV sweep with
    dQ beside it, in place of KERNEL_DQ + KERNEL_DKV; the same gradients
    to the bit."""
    q, k, v, q_seg, kv_seg, out, lse = res
    qt, kt, vt, gt = (
        jnp.transpose(x, (0, 2, 1, 3)) for x in (q, k, v, g)
    )
    b, h, tq, d = qt.shape
    tk, d_v = kt.shape[2], vt.shape[3]
    segmented = q_seg is not None
    scale = d ** -0.5
    off = tk - tq if q_offset is None else q_offset
    nq, nk = tq // bq, tk // bk
    nbq, kanchor, qsel = _q_sweep_maps(causal, bq, bk, off, window, nq, nk)
    triangles = _triangle_kinds(
        tq, tk, bq, bk, causal=causal, window=window, sinks=sinks,
        offset=off, segmented=segmented)
    # delta_i = Σ_d dO·O — the softmax-jacobian row term, cheap outside.
    delta = jnp.einsum(
        "bthd,bthd->bht", g.astype(jnp.float32), out.astype(jnp.float32)
    )[..., None]
    if g_lse is not None:
        # g_lse arrives in the caller-facing [B, T, H] layout.
        delta = delta - jnp.transpose(g_lse, (0, 2, 1))[..., None]
    seg_ops = list(_seg_operands(q_seg, kv_seg, tq, tk)) if segmented else []
    operands = (qt, kt, vt, gt, lse, delta, *seg_ops)

    def k_sweep_dq():
        nb = _k_sweep_steps(bq, bk, window, sinks, nk)
        asel, ksel = _k_sweep_maps(
            causal, bq, bk, off, window, sinks, nq, nk)
        in_specs = [
            _block_spec(d, bq, asel),
            _block_spec(d, bk, ksel),
            _block_spec(d_v, bk, ksel),
            _block_spec(d_v, bq, asel),
            _stat_spec(bq, asel),
            _stat_spec(bq, asel),
        ]
        if segmented:
            in_specs += [_seg_q_spec(bq, asel), _seg_kv_spec(bk, ksel)]
        return pl.pallas_call(
            functools.partial(
                _bwd_dq_kernel, scale=scale, causal=causal,
                segmented=segmented, bq=bq, bk=bk, offset=off, window=window,
                nk=nk, sinks=sinks, triangles=triangles,
            ),
            grid=(b, h, nq, nb),
            in_specs=in_specs,
            out_specs=_block_spec(d, bq, _anchor),
            out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            interpret=interpret,
            name=KERNEL_DQ,
        )(*operands)

    def q_sweep(with_dq):
        """(dk, dv), and ``with_dq`` dq after them: a whole (b, h)'s block
        and float32 scratch, resident while the head's k blocks pass."""
        in_specs = [
            _block_spec(d, bq, qsel),
            _block_spec(d, bk, kanchor),
            _block_spec(d_v, bk, kanchor),
            _block_spec(d_v, bq, qsel),
            _stat_spec(bq, qsel),
            _stat_spec(bq, qsel),
        ]
        if segmented:
            in_specs += [_seg_q_spec(bq, qsel), _seg_kv_spec(bk, kanchor)]
        out_specs = [
            _block_spec(d, bk, _anchor),
            _block_spec(d_v, bk, _anchor),
        ]
        out_shape = [
            jax.ShapeDtypeStruct(kt.shape, k.dtype),
            jax.ShapeDtypeStruct(vt.shape, v.dtype),
        ]
        scratch_shapes = [
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d_v), jnp.float32),
        ]
        compiler_params = None
        if with_dq:
            out_specs.append(_block_spec(d, tq, lambda i, j: 0))
            out_shape.append(jax.ShapeDtypeStruct(qt.shape, q.dtype))
            scratch_shapes.append(pltpu.VMEM((tq, d), jnp.float32))
            compiler_params = pltpu.CompilerParams(
                vmem_limit_bytes=_resident_dq_bytes(tq, d, q.dtype)
                + _SWEEP_VMEM_BYTES)
        return pl.pallas_call(
            functools.partial(
                _bwd_dkv_kernel, scale=scale, causal=causal,
                segmented=segmented, bq=bq, bk=bk, offset=off, window=window,
                nq=nq, with_dq=with_dq, triangles=triangles,
            ),
            grid=(b, h, nk, nbq),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            interpret=interpret,
            name=KERNEL_BWD if with_dq else KERNEL_DKV,
            compiler_params=compiler_params,
        )(*operands)

    if fused:
        dk, dv, dq = q_sweep(with_dq=True)
    else:
        dq = k_sweep_dq()
        dk, dv = q_sweep(with_dq=False)
    if window is not None and sinks:
        # Sink contributions to dK/dV of k block 0: every q block sees the
        # sink columns, so this pass sweeps ALL nq q blocks for the one
        # anchored block — a separate call keeps the band pass's swept axis
        # at nbq instead of forcing the whole rectangle to nq.
        # The q blocks whose rows are all inside the window come first and
        # are skipped: they name the first block that runs.
        first = min(_nonneg((window - off) // bq), nq - 1)
        _, sink_qsel = _fetch_maps(_sweep, lambda i: first, None, 1)
        sink_in_specs = [
            _block_spec(d, bq, sink_qsel),
            _block_spec(d, bk, _anchor),
            _block_spec(d_v, bk, _anchor),
            _block_spec(d_v, bq, sink_qsel),
            _stat_spec(bq, sink_qsel),
            _stat_spec(bq, sink_qsel),
        ]
        if segmented:
            sink_in_specs += [
                _seg_q_spec(bq, sink_qsel), _seg_kv_spec(bk, _anchor)
            ]
        dk0, dv0 = pl.pallas_call(
            functools.partial(
                _bwd_dkv_kernel, scale=scale, causal=causal,
                segmented=segmented, bq=bq, bk=bk, offset=off, window=window,
                nq=nq, sinks=sinks, sink_only=True,
            ),
            grid=(b, h, 1, nq),
            in_specs=sink_in_specs,
            out_specs=[
                _block_spec(d, bk, _anchor),
                _block_spec(d_v, bk, _anchor),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, h, bk, d), k.dtype),
                jax.ShapeDtypeStruct((b, h, bk, d_v), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d_v), jnp.float32),
            ],
            interpret=interpret,
            name=KERNEL_DKV,
        )(qt, kt[:, :, :bk], vt[:, :, :bk], gt, lse, delta, *seg_ops)
        dk = dk.at[:, :, :bk].add(dk0)
        dv = dv.at[:, :, :bk].add(dv0)
    back = lambda x: jnp.transpose(x, (0, 2, 1, 3))  # noqa: E731
    # Integer segment-id operands take no gradient (None cotangent).
    return back(dq), back(dk), back(dv), None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_lse(q, k, v, q_seg, kv_seg, causal, window, sinks, q_offset, bq,
               bk, interpret):
    """Kernel entry that also RETURNS the per-row logsumexp — the statistic
    a cross-chip online-softmax merge needs (ring attention: each hop's
    (out, lse) pair is exactly one step of the recurrence)."""
    out, lse = _flash_fwd_impl(
        q, k, v, q_seg, kv_seg, causal, window, sinks, q_offset, bq, bk,
        interpret,
    )
    return out, jnp.transpose(lse[..., 0], (0, 2, 1))  # [B,H,T,1]→[B,T,H]


def _flash_lse_fwd(q, k, v, q_seg, kv_seg, causal, window, sinks, q_offset,
                   bq, bk, interpret):
    out, lse = _flash_fwd_impl(
        q, k, v, q_seg, kv_seg, causal, window, sinks, q_offset, bq, bk,
        interpret,
    )
    return (
        (out, jnp.transpose(lse[..., 0], (0, 2, 1))),
        (q, k, v, q_seg, kv_seg, out, lse),
    )


def _flash_lse_bwd(causal, window, sinks, q_offset, bq, bk, interpret, res,
                   cotangents):
    g, g_lse = cotangents
    return _flash_bwd_core(
        causal, window, sinks, q_offset, bq, bk, interpret, res, g, g_lse
    )


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _dense_with_lse(q, k, v, *, causal: bool, q_segment_ids=None,
                    kv_segment_ids=None, window=None, q_offset=None,
                    sinks=0):
    """Dense (out, lse) fallback, numerically matching the kernel's
    conventions: f32 statistics, fully-masked rows get lse ≈ _BIG_NEG and
    zero output (so a merge weights them to zero), natively differentiable.
    Also the segment/window-mask REFERENCE the kernel parity tests compare
    to. ``window``/``q_offset`` as in `flash_attention`."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    masked = causal or q_segment_ids is not None
    keep = None
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        off = tk - tq if q_offset is None else q_offset
        rows = lax.broadcasted_iota(jnp.int32, (tq, tk), 0) + off
        cols = lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        keep = rows >= cols  # [Tq, Tk], broadcasts over [B, H]
        if window is not None:
            band = cols > rows - window
            if sinks:
                band |= cols < sinks
            keep &= band
    if q_segment_ids is not None:
        seg = (
            q_segment_ids[:, None, :, None] == kv_segment_ids[:, None, None, :]
        )  # [B, 1, Tq, Tk]
        keep = seg if keep is None else (keep & seg)
    if masked:
        s = jnp.where(keep, s, _BIG_NEG)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    if masked:
        # Exact zeros so a fully-masked row yields l == 0 (not tk) and the
        # empty-row convention below matches the kernel's.
        p = jnp.where(keep, p, 0.0)
    l = p.sum(axis=-1, keepdims=True)
    empty = l == 0.0
    l_safe = jnp.where(empty, 1.0, l)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", (p / l_safe).astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
    lse = jnp.where(empty, _BIG_NEG, m + jnp.log(l_safe))[..., 0]  # [B,H,Tq]
    return out, jnp.transpose(lse, (0, 2, 1))  # [B,Tq,H]


def _check_segment_shapes(q, k, q_segment_ids, kv_segment_ids):
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError(
            "pass q_segment_ids and kv_segment_ids together (for packed "
            "self-attention they are the same array)"
        )
    if q_segment_ids is None:
        return
    if q_segment_ids.shape != (q.shape[0], q.shape[1]):
        raise ValueError(
            f"q_segment_ids must be [B, Tq] = {(q.shape[0], q.shape[1])}, "
            f"got {q_segment_ids.shape}"
        )
    if kv_segment_ids.shape != (k.shape[0], k.shape[1]):
        raise ValueError(
            f"kv_segment_ids must be [B, Tk] = {(k.shape[0], k.shape[1])}, "
            f"got {kv_segment_ids.shape}"
        )


class KernelFallbackWarning(UserWarning):
    """A flash-attention call ran the dense reference because the kernel's
    tiling does not hold for its shape."""


def _warn_dense_fallback(q, k, block_q, block_k) -> None:
    # The shape rides the message, so Python's default filter shows it
    # once per shape and call site.
    warnings.warn(
        f"flash_attention: q{tuple(q.shape)} k{tuple(k.shape)} "
        f"{jnp.dtype(q.dtype).name} does not tile at blocks "
        f"({block_q}, {block_k}) — running the dense reference, not the "
        "kernel (see `supported`)",
        KernelFallbackWarning,
        stacklevel=3,
    )


def default_interpret() -> bool:
    """Whether kernel calls run in the Pallas interpreter — the ONE place
    it is decided when a caller passes ``interpret=None``: everywhere but
    on TPU devices, where Mosaic compiles the kernel. The interpreted
    kernel is ordinary JAX (it proves the algebra, not the codegen, and
    GSPMD partitions it freely); the compiled one is a custom call."""
    return jax.devices()[0].platform != "tpu"


def flash_attention_with_lse(
    q, k, v, *,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    q_segment_ids=None,
    kv_segment_ids=None,
    window: int | None = None,
    q_offset: int | None = None,
    interpret: bool | None = None,
):
    """[B,Tq,H,D] attention returning ``(out, lse)`` with ``lse`` [B,Tq,H] —
    the building block for cross-chip softmax merges (ring attention).
    Same kernel/fallback/interpret policy as `flash_attention`; gradients
    flow through BOTH outputs (the lse cotangent folds into the kernel
    backward's δ term). ``window``/``q_offset`` as in `flash_attention`."""
    _check_segment_shapes(q, k, q_segment_ids, kv_segment_ids)
    check_window(window, causal)
    segmented = q_segment_ids is not None
    block_q, block_k = pick_blocks(
        q.shape[1], max(q.shape[-1], v.shape[-1]), q.dtype, block_q, block_k,
        t_k=k.shape[1], segmented=segmented, windowed=window is not None,
    )
    if not supported(
        q.shape, block_q, block_k, k_shape=k.shape, dtype=q.dtype,
        segmented=segmented, v_dim=v.shape[-1],
    ):
        _warn_dense_fallback(q, k, block_q, block_k)
        return _dense_with_lse(
            q, k, v, causal=causal,
            q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
            window=window, q_offset=q_offset,
        )
    if interpret is None:
        interpret = default_interpret()
    _note_tiles(
        q.shape[1], k.shape[1], block_q, block_k, causal=causal,
        window=window, q_offset=q_offset, segmented=segmented)
    return _flash_lse(
        q, k, v, q_segment_ids, kv_segment_ids, causal, window, 0, q_offset,
        block_q, block_k, interpret,
    )


def _sublane(dtype) -> int:
    """Second-to-last-dim tile granule for the TPU vector layout: f32 packs
    8 sublanes, 16-bit types 16, 8-bit types 32."""
    itemsize = jnp.dtype(dtype).itemsize
    return {4: 8, 2: 16, 1: 32}.get(itemsize, 8)


def supported(q_shape, bq=DEFAULT_BLOCK_Q, bk=DEFAULT_BLOCK_K,
              k_shape=None, dtype=jnp.float32, segmented=False,
              v_dim=None) -> bool:
    """Whether the kernel's tiling holds for [B,Tq,H,D] q, [B,Tk,H,D] k and
    [B,Tk,H,Dv] v (``v_dim``; default D: one head size for all three).

    Beyond divisibility (q blocks against Tq, k blocks against K/V's own Tk —
    cross-attention runs the kernel on a rectangular nq×nk grid), the blocks
    must be sublane-aligned for the dtype (an unaligned tile fails Mosaic
    compilation on real TPU instead of falling back), and segment-id masking
    needs lane-aligned K blocks (the q-id tile is repeated in _SEG_LANES
    units across the K axis).

    This checks ONE given block config; it is not a will-the-kernel-run
    predicate for `flash_attention`, which first degrades the config via
    `pick_blocks` — probe with ``supported(shape, *pick_blocks(...))``.
    """
    b, t, h, d = q_shape
    tk = k_shape[1] if k_shape is not None else t
    granule = _sublane(dtype)
    if segmented and bk % _SEG_LANES:
        return False
    return (
        t % bq == 0 and tk % bk == 0
        and bq % granule == 0 and bk % granule == 0
        and max(d, v_dim or d) <= 256
    )


def pick_blocks(t: int, d: int, dtype, bq: int = DEFAULT_BLOCK_Q,
                bk: int = DEFAULT_BLOCK_K, t_k: int | None = None,
                segmented: bool = False,
                windowed: bool = False) -> tuple[int, int]:
    """Largest workable (block_q, block_k) ≤ the requested sizes for a
    [*, t, *, d] attention call (``d``: the wider of the q/k and the v head
    sizes, which is the one that crowds VMEM; ``t_k`` = K/V's own length for
    cross-attention; default self-attention): clamp for wide heads (a 1024²
    f32 score tile + wide q/k/v blocks would crowd VMEM), clamp to T, then
    halve until the block divides its T — so e.g. T=1536 runs 512² tiles
    instead of regressing to the dense fallback just because
    1536 % 1024 != 0."""
    t_k = t if t_k is None else t_k
    if jnp.dtype(dtype).itemsize >= 4:
        # 4-byte inputs double the q/k/v/dO block bytes, and the dK/dV
        # backward (the fullest kernel: four live [bq, bk] f32 tiles, two
        # accumulators, two outputs) no longer fits: v5e's compiler refuses
        # f32 at 1024² from T=2048 on (16.20M against the 16.00M scoped
        # limit at B4·H8·D64; tests/test_chip_compile.py). 512² compiles
        # there up to T=32k.
        bq, bk = min(bq, 512), min(bk, 512)
    if d > 128 or max(t, t_k) >= 32768:
        # Wide heads: a 1024² f32 score tile + wide q/k/v blocks would
        # crowd VMEM. Very long grids overflow v5e's 16 MB scoped-VMEM
        # budget *in context*: the bare kernel compiles at 1024² up to
        # T=32k, but inside a remat'd training step XLA co-schedules
        # neighboring fusions into the same scoped budget and the
        # allocation grows slowly with T (measured: 16.26M at T=32k,
        # 16.76M at T=131k vs the 16.00M limit — both fail, while T=8k
        # fits). 512² tiles leave ~3/4 of the score-tile footprint as
        # headroom; where both compile (T 2,048, D 128) they cost 6 % of
        # the kernels' time (see DEFAULT_BLOCK_Q).
        bq, bk = min(bq, 512), min(bk, 512)
    if segmented or windowed:
        # Extra in-kernel operands push 1024² past v5e's 16 MB VMEM stack:
        # the double-buffered segment-id tiles cost ~0.8 MB, and the band
        # mask's [bq, bk] i32 iotas a few hundred KB (measured 16.30M vs
        # the 16M limit at seq 32768). 512² fits with headroom — and for
        # windows a smaller K block also tightens the block-skip
        # granularity. Whether a windowed or segmented call SHORTER than
        # that would run faster at 1024² is not measured (ROADMAP S7).
        bq, bk = min(bq, 512), min(bk, 512)
    bq, bk = min(bq, t), min(bk, t_k)
    # Degrade no further than 128: below that the kernel's tiny score tiles
    # underfill the MXU and the dense fallback is faster — leaving a
    # non-dividing block here makes `supported` reject and fall back.
    # (Explicitly-passed smaller blocks are honored, not degraded-to; the
    # `bq // 2 >= floor` guard keeps non-power-of-two explicit blocks from
    # halving THROUGH the floor, e.g. 384 → 192 stops rather than → 96.)
    floor = max(_sublane(dtype), 128)
    while t % bq and bq // 2 >= floor:
        bq //= 2
    while t_k % bk and bk // 2 >= floor:
        bk //= 2
    return bq, bk


# What the fused backward's tiles may take of VMEM beside the resident dQ:
# twice the 16 MiB scoped default the two kernels compile within, since its
# body holds the dK/dV pass's tiles and one product more. A ceiling for
# Mosaic's allocation, not a reservation.
_SWEEP_VMEM_BYTES = 32 * 2**20
_V5E_VMEM_BYTES = 128 * 2**20


def _chip_vmem_bytes() -> int:
    """VMEM of the chip the kernels are built for: the attached TPU's; off
    TPU (the interpreter, a compile for the described chip) a v5e's."""
    if jax.devices()[0].platform == "tpu":
        return pltpu.get_tpu_info().vmem_capacity_bytes
    return _V5E_VMEM_BYTES


def _resident_dq_bytes(t_q: int, d: int, dtype) -> int:
    """VMEM the fused backward keeps for a whole (b, h): the float32
    [Tq, D] accumulator and the two buffers of the dQ output block, D
    padded to the 128 lanes."""
    lanes = -(-d // 128) * 128
    return t_q * lanes * (4 + 2 * jnp.dtype(dtype).itemsize)


def fused_backward(t_q: int, d: int, dtype, sinks: int = 0) -> bool:
    """Whether the backward of a [*, t_q, *, d] call (``d``: q and k's head
    size) is the one kernel KERNEL_BWD: where a head's dQ, resident while
    its k blocks pass, takes no more than half the chip's VMEM (Tq 65,536
    at D 128 in bf16 on a v5e; the tiles get the rest). Longer contexts
    take KERNEL_DQ + KERNEL_DKV, and so do calls with sinks: their
    sink-only pass adds to dK/dV of block 0 from a second sweep, and the
    dQ sweep holds the sink tile, which the k-anchored sweep does not."""
    if sinks:
        return False
    return _resident_dq_bytes(t_q, d, dtype) <= _chip_vmem_bytes() // 2


def flash_attention(
    q, k, v, *,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    q_segment_ids=None,
    kv_segment_ids=None,
    window: int | None = None,
    sinks: int = 0,
    q_offset: int | None = None,
    interpret: bool | None = None,
):
    """[B,Tq,H,D] attention via the pallas kernel; when the tiling doesn't
    hold, the dense reference with a `KernelFallbackWarning`. q and k share
    one head size D (the scores are scaled by D^-1/2); v may have another,
    Dv, which is then the output's (latent attention: 192 | 128).
    ``interpret=None`` takes `default_interpret()` (the pallas interpreter
    off-TPU, so tests/CPU paths run the same kernel code).

    ``q_segment_ids``/``kv_segment_ids`` ([B,Tq]/[B,Tk] ints) restrict
    attention to equal-id pairs — the packed-sequence pretraining mask
    (multiple documents per row, none attending across its neighbors), with
    block-level early-out so disjoint tile pairs cost no FLOPs. K/V may
    carry their own length Tk ≠ Tq (cross-attention); with ``causal`` the
    sequences align at their ENDS (query i sees keys j ≤ i + Tk − Tq).

    ``window`` (sliding-window attention, Mistral-style: each query sees
    only its ``window`` most recent keys, itself included — requires
    ``causal``) masks the band row − col < window AND block-skips tiles
    entirely outside it, so FLOPs scale with T·window instead of T²/2.
    ``q_offset`` overrides the q↔k alignment: query row i sits at key
    position i + q_offset (default Tk − Tq, the end-aligned convention);
    ring attention uses it to place a remote K/V block's hop distance into
    the causal/window arithmetic.

    ``sinks`` (global+local / StreamingLLM mask; requires ``window``)
    re-admits the first ``sinks`` key positions beyond the band: the grid
    prepends one pinned tile (k block 0) per q block, masked disjointly
    from the band, and the backward adds a sink-only dK/dV pass over that
    block — overall cost stays O(T·(window + sinks))."""
    _check_segment_shapes(q, k, q_segment_ids, kv_segment_ids)
    check_window(window, causal)
    if sinks < 0:
        raise ValueError(f"sinks must be >= 0, got {sinks}")
    if window is None:
        sinks = 0  # full causal attention already sees every sink
    segmented = q_segment_ids is not None
    block_q, block_k = pick_blocks(
        q.shape[1], max(q.shape[-1], v.shape[-1]), q.dtype, block_q, block_k,
        t_k=k.shape[1], segmented=segmented, windowed=window is not None,
    )
    kernel_ok = supported(
        q.shape, block_q, block_k, k_shape=k.shape, dtype=q.dtype,
        segmented=segmented, v_dim=v.shape[-1],
    ) and (sinks == 0 or (sinks <= block_k and q_offset is None))
    if not kernel_ok:
        _warn_dense_fallback(q, k, block_q, block_k)
        if segmented or k.shape[1] != q.shape[1] or window is not None \
                or q_offset is not None:
            out, _ = _dense_with_lse(
                q, k, v, causal=causal,
                q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
                window=window, q_offset=q_offset, sinks=sinks,
            )
            return out
        return dense_attention(q, k, v, causal=causal)
    if interpret is None:
        interpret = default_interpret()
    _note_tiles(
        q.shape[1], k.shape[1], block_q, block_k, causal=causal,
        window=window, sinks=sinks, q_offset=q_offset, segmented=segmented)
    return _flash(
        q, k, v, q_segment_ids, kv_segment_ids, causal, window, sinks,
        q_offset, block_q, block_k, interpret,
    )
