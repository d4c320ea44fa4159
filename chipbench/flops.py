"""Peaks of the chips, and what every family's counts share: the visible
pairs of a causal attention and the roofline's arithmetic. A model's own
operations and bytes are its family's to count (families/<family>.py:
``required_flops_per_token`` for `mfu`, ``kernel_work`` for the kernels'
roofline shares).
"""

from __future__ import annotations

# Published peaks of one chip, keyed by `jax.Device.device_kind`.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s inter-chip interconnect).
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown device is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add a row "
            "with its source to chipbench/flops.py PEAKS"
        ) from None


def visible_pairs(seq_len: int, window: int | None) -> int:
    """(query, key) pairs one causal self-attention over ``seq_len``
    positions scores: query i sees keys max(0, i-window+1)..i."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    # The first `window` rows see 1..window keys, the rest see `window`.
    return window * (window + 1) // 2 + (seq_len - window) * window


def roofline_seconds(flops: float, nbytes: float, device_kind: str):
    """(least seconds the chip could take, which bound applies)."""
    peak = peaks(device_kind)
    compute = flops / peak["flops_per_s"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
