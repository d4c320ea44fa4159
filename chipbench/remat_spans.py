"""Reader of what a rematerialised block names in the profiler's trace (PR
39): `jax.checkpoint` (flax's `nn.remat`) runs a block's forward again in
the backward pass under ``rematted_computation`` in every instruction's
scope path (``.../checkpoint/rematted_computation/Block_3/mixer/...``; the
backward proper is ``.../checkpoint/Block_3/...``), read as `moe_spans.py`
reads ``hvt.moe``. Mosaic calls carry the path like any other instruction.

A program that rematerialises nothing (every cell before PR 39's) has no
such path: the reader returns None and the metric is left out.
"""

from __future__ import annotations

from chipbench import moe_spans

REMAT_MARK = "rematted_computation"


def recompute_ms_per_step(ctx):
    return moe_spans._scope_metric(ctx, (REMAT_MARK,))
