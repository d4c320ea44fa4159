"""Sums of rows by the token each belongs to — the routed experts' combine
and the transpose of their dispatch (models/moe.py `RoutedExperts`).

The routed layer holds ``m`` rows (a static row budget), each a (token,
choice) pair's copy of a token or an expert's output for it, sorted by
expert. Both sums back to the tokens, the combine's gated one and the
transpose of the row gather, are one operation: for every token, the sum
of its own rows, each times a float32 weight (the gate) or 1, in float32,
rounded once to the output's dtype. A pair id is unique, so once the rows
are put in token order (`token_order`: one argsort of ``m`` int32 keys a
layer) a token's rows are one contiguous run of 0 to k of them, and the sum
is a segment sum over sorted runs: no zero-filled float32 ``[n, d]``, no
read-modify-write of it, no colliding indices.

``segment_sum`` gathers the rows into token order and hands them to one
Mosaic call, ``hvt_moe_combine``: a grid over column tiles x the row
chunks of each token tile's run (the runs' offsets scalar-prefetched as
megablox's group metadata: a tile is visited once per chunk its run
touches, and once when it has none, so that it is written), a float32
accumulator in VMEM per token tile, written once. A chunk's sum into the
tile is one ``[bt, R] x [R, bd]`` matmul whose left side selects: the
weight where a row belongs to the token, 0 elsewhere. With bfloat16 rows
the weights are split into a bfloat16 high and low part, so the two
products are exact and the float32 accumulation sees the weight to 2^-17
of itself; with weight 1 the selection is 0/1 and one product is exact.

Rows that belong to no token (the run's sentinel, token ``n``: rows past
the groups' total) are never summed, but a chunk that holds some is read
whole, so they have to be finite (the grouped matmul leaves them zeros).

The call is jitted, so a model's identical calls share one traced and one
lowered copy of the kernel. The kernel's name is the instruction's and
the profiler event's (``%hvt_moe_combine.3 = ... custom-call``); the device
events carry the caller's scope (``hvt.moe/combine``, ``hvt.moe/dispatch``).
Off-TPU it runs in the Pallas interpreter.
"""

from __future__ import annotations

import functools
import importlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import flash_attention

_megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

KERNEL = "hvt_moe_combine"
# Tokens an output block holds: one MXU pass of rows, and the selection
# matrix's sublanes.
TOKEN_TILE = 128
# Rows a grid step sums: a chunk that straddles two tiles' runs is read
# once for each, and the selection's work grows with it.
ROW_CHUNK = 128
# Columns a block holds: the widest the routed cells have (4,096), so a row
# chunk is read once; a wider row is cut into lane-aligned tiles.
COLUMN_TILE = 4096


class TokenOrder(NamedTuple):
    """``m`` rows in token order, and the kernel's grid over them.

    ``by_token`` [m] int32: the row each position of the order takes;
    ``token`` [m] int32: its token, ascending (``n``, the sentinel, for a row
    that belongs to none); ``offsets`` [tiles + 1] int32: where each tile of
    `_tiles` tokens starts in the order (the last: where the sentinel's
    rows start); ``tile_of``, ``chunk_of`` [chunks + tiles] int32: the
    token tile and the row chunk of each grid step, as megablox's group
    metadata has them (a tile's chunks one after the other, a tile with no
    row once); ``steps`` int32: how many of them the grid runs."""

    by_token: jax.Array
    token: jax.Array
    offsets: jax.Array
    tile_of: jax.Array
    chunk_of: jax.Array
    steps: jax.Array


def _tiles(m: int, n: int):
    """(row chunk, token tile) for ``m`` rows summed into ``n`` tokens: a
    chunk divides ``m`` or is all of it; a token tile is a multiple of 16
    sublanes."""
    chunk = ROW_CHUNK if m % ROW_CHUNK == 0 else m
    return chunk, min(TOKEN_TILE, -(-n // 16) * 16)


def _column_tile(d: int) -> int:
    """Columns a block holds: all of a row, or a lane-aligned part of one
    wider than `COLUMN_TILE` that divides it."""
    if d <= COLUMN_TILE or d % 128:
        return d
    return max(c for c in range(128, COLUMN_TILE + 1, 128) if d % c == 0)


def token_order(pairs, held, n: int, k: int) -> TokenOrder:
    """`TokenOrder` of ``m`` rows whose first ``held`` carry the (token,
    choice) pair ids ``pairs`` [m] (``token * k + choice``, unique among
    them); the rows past ``held`` belong to no token. Made once for the
    sums of one set of rows, whatever their width."""
    m = pairs.shape[0]
    chunk, tile = _tiles(m, n)
    chunks, tiles = m // chunk, -(-n // tile)
    at = jnp.arange(m, dtype=jnp.int32)
    key = jnp.where(at < held, pairs.astype(jnp.int32), n * k)
    by_token = jnp.argsort(key).astype(jnp.int32)
    token = key[by_token] // k
    bounds = jnp.minimum(jnp.arange(tiles + 1, dtype=jnp.int32) * tile, n)
    offsets = jnp.sum(token[None, :] < bounds[:, None], axis=1,
                      dtype=jnp.int32)
    start, end = offsets[:-1], offsets[1:]
    first = start // chunk
    visits = jnp.where(end > start, (end - 1) // chunk - first + 1, 1)
    begins = jnp.cumsum(visits) - visits
    step = jnp.arange(chunks + tiles, dtype=jnp.int32)
    tile_of = jnp.sum(step[:, None] >= begins[None, 1:], axis=1,
                      dtype=jnp.int32)
    chunk_of = jnp.minimum(first[tile_of] + step - begins[tile_of],
                           chunks - 1)
    return TokenOrder(by_token, token, offsets, tile_of, chunk_of,
                      jnp.sum(visits, dtype=jnp.int32))


def _kernel(offsets, tile_of, chunk_of, token_ref, *refs, tile, weighted):
    if weighted:
        weight_ref, rows_ref, out_ref, acc_ref = refs
    else:
        rows_ref, out_ref, acc_ref = refs
    step, steps = pl.program_id(1), pl.num_programs(1)
    del chunk_of
    here = tile_of[step]
    before = tile_of[jnp.maximum(step - 1, 0)]
    after = tile_of[jnp.minimum(step + 1, steps - 1)]

    @pl.when((step == 0) | (before != here))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(offsets[here + 1] > offsets[here])
    def _():
        rows = rows_ref[...]
        mine = token_ref[...] == (
            jax.lax.broadcasted_iota(jnp.int32, (tile, rows.shape[0]), 0)
            + here * tile)
        pick = jnp.where(mine, weight_ref[...], 0.0) if weighted else mine
        if rows.dtype == jnp.float32:
            acc_ref[...] += jnp.dot(pick.astype(jnp.float32), rows,
                                    preferred_element_type=jnp.float32,
                                    precision=jax.lax.Precision.HIGHEST)
            return
        high = pick.astype(rows.dtype)
        acc_ref[...] += jnp.dot(high, rows, preferred_element_type=jnp.float32)
        if weighted:
            low = (pick - high.astype(jnp.float32)).astype(rows.dtype)
            acc_ref[...] += jnp.dot(low, rows,
                                    preferred_element_type=jnp.float32)

    @pl.when((step == steps - 1) | (after != here))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


# Jitted (static: the token count, the dtype, the interpreter switch), so
# that a model's identical calls share one traced and one lowered kernel:
# tracing a Pallas kernel is Python work that no compile cache keeps.
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _segment_sum_impl(rows, weight, order, n, out_dtype, interpret):
    rows = rows[order.by_token]
    m, d = rows.shape
    chunk, tile = _tiles(m, n)
    columns = _column_tile(d)
    tiles = order.offsets.shape[0] - 1

    def rows_at(j, s, offsets, tile_of, chunk_of):
        return chunk_of[s], j

    def lanes_at(j, s, offsets, tile_of, chunk_of):
        return 0, chunk_of[s]

    def out_at(j, s, offsets, tile_of, chunk_of):
        return tile_of[s], j

    lanes = pl.BlockSpec((1, chunk), lanes_at)
    operands = [order.token.reshape(1, m)]
    if weight is not None:
        operands.append(
            weight[order.by_token].astype(jnp.float32).reshape(1, m))
    out = pl.pallas_call(
        functools.partial(_kernel, tile=tile, weighted=weight is not None),
        out_shape=jax.ShapeDtypeStruct((tiles * tile, d), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(d // columns, order.steps),
            in_specs=[lanes] * len(operands)
            + [pl.BlockSpec((chunk, columns), rows_at)],
            out_specs=pl.BlockSpec((tile, columns), out_at),
            scratch_shapes=[pltpu.VMEM((tile, columns), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=KERNEL,
    )(order.offsets, order.tile_of, order.chunk_of, *operands, rows)
    return out[:n]


def segment_sum(rows, weight, order: TokenOrder, n: int, out_dtype):
    """``[n, d]`` in ``out_dtype``: row ``t`` is the float32 sum over the
    rows ``r`` of token ``t`` of ``weight[r] * rows[r]`` (``weight`` float32
    [m], or None for 1), rounded once. ``rows`` [m, d] in the order
    `token_order` was given."""
    return _segment_sum_impl(rows, weight, order, n, jnp.dtype(out_dtype),
                             flash_attention.default_interpret())
