"""Readers of what the hybrid model's token mixers name in the profiler's
trace (PR 35): the scope ``hvt.kda`` of the delta-rule linear-attention
layer (with ``/proj``, ``/conv``, ``/scan``, ``/out``; models/
hybrid_moe_lm.py `DeltaAttention`, the scan itself ops/delta_rule.py) and
``hvt.gqa`` of the gated softmax layer around its flash kernel
(`GatedAttention`), read as `moe_spans.py` reads ``hvt.moe`` (the stat
``tf_op`` of an instruction's metadata), and a later Pallas scan by the
names ``hvt_kda_fwd`` / ``hvt_kda_bwd``, read as the grouped-matmul
kernels are (a Mosaic call's instruction name, matched whole).

A reader that does not find what it reads (a program with no such scope or
kernel, as every commit before PR 35) returns None and its metric is left
out.
"""

from __future__ import annotations

import re

from chipbench import flops, moe_spans, reduce

KDA_SCOPE, KDA_SCAN_SCOPE, GQA_SCOPE = "hvt.kda", "hvt.kda/scan", "hvt.gqa"
KDA_KERNELS = ("hvt_kda_fwd", "hvt_kda_bwd")


def is_kda_kernel(hlo_line: str) -> bool:
    """Whether an event is a Mosaic call named as a delta-rule kernel
    (none exists yet: the scan is plain XLA under its scope)."""
    if reduce.KERNEL_MARK not in hlo_line:
        return False
    name = re.sub(r"(\.\d+)+$", "", reduce.op_name(hlo_line)).rstrip("_")
    return any(name == kernel or name.endswith("_" + kernel)
               for kernel in KDA_KERNELS)


def kda_ms_per_step(ctx):
    return moe_spans._scope_metric(ctx, (KDA_SCOPE,), also=is_kda_kernel)


def kda_scan_ms_per_step(ctx):
    return moe_spans._scope_metric(ctx, (KDA_SCAN_SCOPE,), also=is_kda_kernel)


def gated_attn_proj_ms_per_step(ctx):
    return moe_spans._scope_metric(ctx, (GQA_SCOPE,))


def kda_scan_roofline(ctx):
    work = ctx["kernel_work"].get("kda_scan")
    ms = kda_scan_ms_per_step(ctx)
    if work is None or ms is None:
        return None
    executed, nbytes, _calls = work
    least_s, bound = flops.roofline_seconds(
        executed, nbytes, ctx["device_kind"])
    ctx["say"](kda_scan_roofline_bound=bound,
               kda_scan_least_ms=least_s * 1e3)
    return 100.0 * least_s * 1e3 / ms
