"""Readers of the sliding-window softmax layer's flash calls (PR 41):
models/hybrid_moe_lm.py `GatedAttention` wraps the window kind's kernel
call in the scope ``hvt.swa`` (outside ``hvt.gqa``, which holds its
projections), so its Mosaic calls ``hvt_flash_fwd`` / ``hvt_flash_bwd``
(ops/flash_attention.py, the name matched whole, as `reduce.flash_kernel_of`
matches it) are told from the full layers' by the stat ``tf_op`` of their
instruction's metadata, read as `moe_spans.py` reads ``hvt.moe``.

A reader that does not find what it reads (a program with no such scope, as
every commit before PR 41, or a family that counts no ``window_flash``)
returns None and its metric is left out.
"""

from __future__ import annotations

import re

from chipbench import flops, moe_spans, reduce, spans

SWA_SCOPE = "hvt.swa"
FLASH_KERNELS = ("hvt_flash_fwd", "hvt_flash_bwd")


def is_flash_kernel(hlo_line: str) -> bool:
    """Whether an event is a forward or backward flash kernel, by its
    instruction's name: the kernel's own (``hvt_flash_bwd.3``) or under the
    transformations' prefixes (``transpose_jvp_hvt_flash_bwd__.1``);
    ``hvt_flash_fwd_ring`` would be another kernel."""
    if reduce.KERNEL_MARK not in hlo_line:
        return False
    name = re.sub(r"(\.\d+)+$", "", reduce.op_name(hlo_line)).rstrip("_")
    return any(name == kernel or name.endswith("_" + kernel)
               for kernel in FLASH_KERNELS)


def window_flash_ms_per_step(ctx):
    """None unless every step holds as many of the window layers' kernel
    calls as the family counts."""
    work = ctx["kernel_work"].get("window_flash")
    if work is None or not ctx["chips"]:
        return None
    scopes = spans.trace_of(ctx)["scopes"]

    def wanted(hlo_line):
        return (is_flash_kernel(hlo_line)
                and SWA_SCOPE in scopes.get(hlo_line, ""))

    values = []
    for chip in ctx["chips"]:
        hits = sum(wanted(n) for n, _, _ in chip.ops)
        if hits != work[2] * len(chip.steps):
            return None
        values.append(moe_spans._ms_per_step(chip, wanted))
    return max(values)


def window_flash_roofline(ctx):
    ms = window_flash_ms_per_step(ctx)
    if ms is None:
        return None
    executed, nbytes, _calls = ctx["kernel_work"]["window_flash"]
    least_s, bound = flops.roofline_seconds(
        executed, nbytes, ctx["device_kind"])
    ctx["say"](window_flash_roofline_bound=bound,
               window_flash_least_ms=least_s * 1e3)
    return 100.0 * least_s * 1e3 / ms
