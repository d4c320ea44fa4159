"""Grouped matrix multiplication over ragged groups of rows — the routed
experts' matmul (models/moe.py `RoutedExperts`).

``grouped_matmul(lhs, rhs, group_sizes)``: the rows of ``lhs`` ``[M, K]``
are sorted by group; group ``g`` is the next ``group_sizes[g]`` rows and is
multiplied by ``rhs[g]`` ``[K, N]``. Rows past the groups' total (a static
row budget that the routing did not fill) give zeros, forward and backward.
The work follows the rows routed: the grid walks the row tiles that hold
a group's rows (one more per group boundary inside a tile), never groups ×
budget.

The kernels are JAX's own Pallas TPU grouped matmul
(`jax.experimental.pallas.ops.tpu.megablox`: ``gmm`` for the product and
for dlhs, ``tgmm`` for drhs), called here un-jitted under a name of this
repository's, so that the compiled instruction and the profiler's device
event carry it (``%hvt_moe_gmm.3 = ... custom-call(...)``; the benchmark's
`expert_gmm_*` metrics key on it: keep the names stable, and never end one
in a digit). Off-TPU the same kernels run in the Pallas interpreter.

Why not `jax.lax.ragged_dot`: on this chip XLA lowers it to a Mosaic kernel
of its own at 512^3 tiles, whose backward pair took 2.2x as long at the
benchmark cell's shapes (PERF.md, PR 33).
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from horovod_tpu.ops import flash_attention

# The package's ``__init__`` shadows its ``gmm`` module with the function.
_backend = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")
# Un-jitted: the innermost name-stack entry names the compiled instruction,
# and a `jit(gmm)` of JAX's own would be it.
_gmm, _tgmm = _backend.gmm.__wrapped__, _backend.tgmm.__wrapped__

KERNEL = "hvt_moe_gmm"        # the product, and its transpose for dlhs
KERNEL_DW = "hvt_moe_gmm_dw"  # drhs: one [K, N] product per group

# (rows, contraction, columns) of a tile. Rows: an expert of the benchmark's
# cell sees ~384 rows a step, and a tile that straddles a group boundary is
# computed once per group, so 512 pads more than it saves; 128 re-reads each
# expert's weights three times as often and is bound by that traffic (v5e,
# PR 33: 256 x 1024 x 768 was the fastest of ten tried).
ROW_TILE = 256
TILING = (ROW_TILE, 1024, 768)


def row_budget(rows: int) -> int:
    """``rows`` rounded up to what the kernel's row tile divides (the row
    count of ``lhs`` has to be a multiple of it)."""
    tile = min(ROW_TILE, -(-rows // 8) * 8)
    return -(-rows // tile) * tile


def _tiling(m: int, k: int, n: int):
    tm, tk, tn = TILING
    return (min(tm, m), min(tk, k), min(tn, n))


def _valid_rows(x, group_sizes):
    """Zeros in the rows past the groups' total, which the kernel never
    visits and leaves as they were allocated."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
    return jnp.where(rows < jnp.sum(group_sizes), x, jnp.zeros((), x.dtype))


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes):
    """``[M, K] x [G, K, N] -> [M, N]`` in ``lhs.dtype`` with float32
    accumulation; ``group_sizes`` int32 ``[G]``, their total at most M."""
    m, k = lhs.shape
    with jax.named_scope(KERNEL):
        out = _gmm(lhs, rhs, group_sizes, lhs.dtype,
                   _tiling(m, k, rhs.shape[2]),
                   interpret=flash_attention.default_interpret())
    return _valid_rows(out, group_sizes)


def _fwd(lhs, rhs, group_sizes):
    return grouped_matmul(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _bwd(res, grad):
    lhs, rhs, group_sizes = res
    (m, k), n = lhs.shape, rhs.shape[2]
    interpret = flash_attention.default_interpret()
    with jax.named_scope(KERNEL):  # [M, N] x [G, K, N]^T: contracts N
        dlhs = _gmm(grad, rhs, group_sizes, lhs.dtype, _tiling(m, n, k),
                    transpose_rhs=True, interpret=interpret)
    with jax.named_scope(KERNEL_DW):
        drhs = _tgmm(lhs.swapaxes(0, 1), grad, group_sizes, rhs.dtype,
                     _tiling(m, k, n), interpret=interpret)
    return _valid_rows(dlhs, group_sizes), drhs, None


grouped_matmul.defvjp(_fwd, _bwd)


def group_sizes_of(group_ids, n_groups: int):
    """How many of ``group_ids`` (any shape) name each of ``n_groups``
    groups; an id outside ``[0, n_groups)`` counts nowhere. int32
    ``[n_groups]``."""
    ids = group_ids.reshape(-1, 1)
    return jnp.sum(ids == jnp.arange(n_groups, dtype=ids.dtype)[None, :],
                   axis=0, dtype=jnp.int32)
