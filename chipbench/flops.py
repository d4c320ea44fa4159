"""Peaks of the chips, and operations and bytes computed from shapes.

Two counts are kept apart. *Required* is what the forward and backward
passes need and is what model-FLOP/s utilisation divides by: 6 FLOPs per
matmul parameter per token, 6 attention dots, nothing recomputed.
*Executed* is what a kernel really runs (the flash backward recomputes the
scores twice: 9 dots; the chunked CE head recomputes its logits:
8·N·D·V) and is what that kernel's roofline share divides by.
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


def matmul_params(model: dict) -> int:
    """Parameters that multiply activations once per token: the blocks'
    projections and MLP and the LM head. The embedding table is a gather
    and the LayerNorm scales are elementwise: neither counts."""
    d, h = model["d_model"], model["n_heads"]
    head_dim = d // h
    h_kv = model.get("n_kv_heads") or h
    attn = d * h * head_dim + 2 * d * h_kv * head_dim + h * head_dim * d
    mlp = 2 * d * model["d_ff"]
    return model["n_layers"] * (attn + mlp) + d * model["vocab_size"]


def attention_flops_per_sequence(model: dict, seq_len: int, dots: int) -> int:
    """``dots`` block matmuls of 2·pairs·head_dim FLOPs per head, in every
    layer (K/V heads are repeated up to the query heads for training, so
    GQA changes nothing here)."""
    pairs = visible_pairs(seq_len, model.get("window"))
    return dots * 2 * pairs * model["d_model"] * model["n_layers"]


def required_flops_per_token(model: dict, seq_len: int) -> float:
    """What forward and backward require for one token: 6 per matmul
    parameter and 6 attention dots (scores, P·V; dP, dV, dQ, dK)."""
    attn = attention_flops_per_sequence(model, seq_len, dots=6) / seq_len
    return 6.0 * matmul_params(model) + attn


def flash_executed_flops_per_step(model: dict, seq_len: int,
                                  batch: int) -> float:
    """What the flash kernels of one training step execute on one chip:
    forward 2 dots, dQ pass 3 (scores again, dP, dQ), dK/dV pass 4 (scores
    again, dV, dP, dK) over the visible pairs. Counted to the element, so
    the masked part of a diagonal tile is not credited to the kernel."""
    return float(batch * attention_flops_per_sequence(model, seq_len, dots=9))


def flash_bytes_per_step(model: dict, seq_len: int, batch: int,
                         bytes_per_element: int = 2) -> float:
    """HBM traffic the three flash passes of one step need at least, each
    [B, T, H, D] array once per pass: forward reads q, k, v and writes o;
    the dQ pass reads q, k, v, o, do and writes dq; the dK/dV pass reads
    q, k, v, o, do and writes dk, dv (17 arrays; the per-row statistics
    are 1/D of one and are left out)."""
    one = batch * seq_len * model["d_model"] * bytes_per_element
    return float(17 * one * model["n_layers"])


def head_flops_per_step(model: dict, tokens: int, *, executed: bool) -> float:
    """The LM head with its cross-entropy over ``tokens`` rows: logits,
    dh and dW are required (6·N·D·V); the chunked head recomputes the
    logits in its backward pass (8·N·D·V executed)."""
    per = 8.0 if executed else 6.0
    return per * tokens * model["d_model"] * model["vocab_size"]


def roofline_seconds(flops: float, nbytes: float, device_kind: str):
    """(least seconds the chip could take, which bound applies)."""
    peak = peaks(device_kind)
    compute = flops / peak["flops_per_s"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
