"""Readers of what the routed-expert and latent-attention layers name in
the profiler's trace (PR 33): the scopes ``hvt.moe`` (with ``/route``,
``/dispatch``, ``/experts``, ``/combine``, ``/shared``; models/moe.py
`RoutedExperts`) and ``hvt.mla`` (models/latent_moe_lm.py
`LatentAttention`), read like `spans.py` reads ``hvt.head_ce`` (the stat
``tf_op`` of an instruction's metadata), and the grouped-matmul kernels by
the names ops/grouped_matmul.py gives them, read like `reduce.py` reads the
flash kernels (a Mosaic call's instruction name, matched whole).

A reader that does not find what it reads (a program with no such scope or
kernel, as every commit before PR 33) returns None and its metric is left
out.
"""

from __future__ import annotations

import re

from chipbench import flops, reduce, spans

MOE_SCOPE, MLA_SCOPE = "hvt.moe", "hvt.mla"
# Of the routed layer, what is not a matmul.
DISPATCH_SCOPES = ("hvt.moe/route", "hvt.moe/dispatch", "hvt.moe/combine")
GMM_KERNELS = ("hvt_moe_gmm", "hvt_moe_gmm_dw")


def is_gmm_kernel(hlo_line: str) -> bool:
    """Whether an event is one of the grouped-matmul kernels, by its
    instruction's name: the kernel's own (``hvt_moe_gmm.3``) or the
    kernel's under the transformations' prefixes
    (``transpose_jvp_hvt_moe_gmm__.1``); ``hvt_moe_gmm_ring`` would be
    another kernel."""
    if reduce.KERNEL_MARK not in hlo_line:
        return False
    name = re.sub(r"(\.\d+)+$", "", reduce.op_name(hlo_line)).rstrip("_")
    return any(name == kernel or name.endswith("_" + kernel)
               for kernel in GMM_KERNELS)


def _ms_per_step(chip, wanted) -> float:
    return sum(min(start + dur, chip.t1) - start
               for name, start, dur in chip.ops if wanted(name)
               ) / 1e6 / len(chip.steps)


def _scope_metric(ctx, marks, also=lambda hlo_line: False):
    """Milliseconds a step of the leaf ops whose op_name holds one of
    ``marks`` (or that ``also`` takes); None where no instruction of the
    trace carries one."""
    scopes = spans.trace_of(ctx)["scopes"]

    def marked(op_name):
        return any(mark in op_name for mark in marks)

    if not any(marked(op_name) for op_name in scopes.values()):
        return None
    values = [
        _ms_per_step(chip, lambda n: also(n) or marked(scopes.get(n, "")))
        for chip in ctx["chips"]]
    return max(values) if values else None


def moe_ms_per_step(ctx):
    return _scope_metric(ctx, (MOE_SCOPE,), also=is_gmm_kernel)


def moe_dispatch_ms_per_step(ctx):
    return _scope_metric(ctx, DISPATCH_SCOPES)


def mla_proj_ms_per_step(ctx):
    return _scope_metric(ctx, (MLA_SCOPE,))


def expert_gmm_ms_per_step(ctx):
    """None unless every step holds as many of the kernels as the family
    counts calls."""
    work = ctx["kernel_work"].get("expert_gmm")
    if work is None or not ctx["chips"]:
        return None
    values = []
    for chip in ctx["chips"]:
        hits = sum(is_gmm_kernel(n) for n, _, _ in chip.ops)
        if hits != work[2] * len(chip.steps):
            return None
        values.append(_ms_per_step(chip, is_gmm_kernel))
    return max(values)


def expert_gmm_roofline(ctx):
    ms = expert_gmm_ms_per_step(ctx)
    if ms is None:
        return None
    required, nbytes, _calls = ctx["kernel_work"]["expert_gmm"]
    least_s, bound = flops.roofline_seconds(
        required, nbytes, ctx["device_kind"])
    ctx["say"](expert_gmm_roofline_bound=bound,
               expert_gmm_least_ms=least_s * 1e3)
    return 100.0 * least_s * 1e3 / ms
