"""Readers of what the hybrid model's state-space mixer names in the
profiler's trace (PR 39): the scope ``hvt.ssm`` of the Mamba-2 layer (with
``/proj``, ``/conv``, ``/scan``, ``/out``; models/hybrid_moe_lm.py
`StateSpaceMixer`, the scan itself ops/ssd.py), in the forward pass, the
rematerialised forward and the backward pass alike, read as `moe_spans.py`
reads ``hvt.moe`` (the stat ``tf_op`` of an instruction's metadata), and a
later Pallas scan by a name that begins ``hvt_ssd_``, read as the
grouped-matmul kernels are (a Mosaic call's instruction name).

A reader that does not find what it reads (a program with no such scope or
kernel, as every commit before PR 39) returns None and its metric is left
out.
"""

from __future__ import annotations

import re

from chipbench import flops, moe_spans, reduce

SSM_SCOPE, SSD_SCAN_SCOPE = "hvt.ssm", "hvt.ssm/scan"
SSD_KERNEL = "hvt_ssd_"


def is_ssd_kernel(hlo_line: str) -> bool:
    """Whether an event is a Mosaic call named as a state-space scan kernel
    (``hvt_ssd_fwd.3``, ``transpose_jvp_hvt_ssd_bwd__.1``; none exists yet:
    the scan is plain XLA under its scope)."""
    if reduce.KERNEL_MARK not in hlo_line:
        return False
    name = re.sub(r"(\.\d+)+$", "", reduce.op_name(hlo_line))
    return name.startswith(SSD_KERNEL) or "_" + SSD_KERNEL in name


def ssm_ms_per_step(ctx):
    return moe_spans._scope_metric(ctx, (SSM_SCOPE,), also=is_ssd_kernel)


def ssd_scan_ms_per_step(ctx):
    return moe_spans._scope_metric(ctx, (SSD_SCAN_SCOPE,), also=is_ssd_kernel)


def ssd_scan_roofline(ctx):
    work = ctx["kernel_work"].get("ssd_scan")
    ms = ssd_scan_ms_per_step(ctx)
    if work is None or ms is None:
        return None
    executed, nbytes, _calls = work
    least_s, bound = flops.roofline_seconds(
        executed, nbytes, ctx["device_kind"])
    ctx["say"](ssd_scan_roofline_bound=bound,
               ssd_scan_least_ms=least_s * 1e3)
    return 100.0 * least_s * 1e3 / ms
