"""int8 quantization: weight-only storage for bandwidth-bound decode, and
an int8 COMPUTE path for compute-bound prefill / large-batch decode.

**Weight-only storage** (`quantize_params` + ``quantized=True`` in the
decode family): autoregressive decode streams every weight once per
generated token (the step is HBM-bound), so
halving weight bytes is a direct tokens/sec lever. Kernels are stored as
int8 with per-output-channel f32 scales; the decode loop dequantizes
INSIDE each scan step, which XLA fuses into the matmul reads — the HBM
stream stays int8.

**int8 compute** (`int8_dot_general` + ``TransformerLM(int8_compute=
True)``): the v5e MXU runs int8×int8→int32 at twice its bf16 rate, which
is the lever for the COMPUTE-bound phase — prompt prefill (not measured
on this round's chip). Every Dense matmul quantizes its
activations dynamically (symmetric per-row scales over the contracted
axes, recomputed per call — no calibration data) and its weights
per-output-channel, accumulates in int32 on the MXU, and rescales the
int32 result by the outer product of the two scale vectors. Decode scan
steps are bandwidth-bound and per-step weight requantization measured
SLOWER there, so `make_generate_fn(int8_compute=True)` applies it to
prefill only. Composes with weight-only storage: dequantize → requantize
round-trips onto the same int8 lattice (`_quantize_sym` is the single
lattice definition), so stacking adds no extra quality loss.

Both paths are approximate — outputs can differ from bf16 near argmax
ties — so they are serving knobs, not defaults; tests gate on top-1
agreement with the bf16 path on a trained model. Inference-only: round()
kills gradients, so the model forbids ``int8_compute`` under training.

Usage:
    qparams = quant.quantize_params(trainer.state.params)
    fn = make_generate_fn(model, max_new_tokens=..., quantized=True)
    tokens = fn(qparams, prompt, rng)

    # compute path (prefill / large-batch decode):
    fn = make_generate_fn(model, max_new_tokens=..., int8_compute=True)
    tokens = fn(params, prompt, rng)          # plain bf16/f32 params
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_Q = "int8_q"


def _is_qleaf(x) -> bool:
    return isinstance(x, dict) and _Q in x


def _quantize_sym(x, axis):
    """THE int8 lattice, in one place: symmetric round-to-nearest with
    amax/127 scales reduced over ``axis`` (keepdims). Shared by the
    storage format (`quantize_params`) and the compute path
    (`int8_dot_general`) — one definition is what makes 'requantization
    round-trips the lattice' a guarantee rather than a coincidence.
    Returns ``(int8 values, f32 scale with keepdims)``."""
    x32 = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x32), axis=axis, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_params(params, *, min_size: int = 4096):
    """Quantize every >=2-D kernel with at least ``min_size`` elements to
    ``{'int8_q': int8, 'scale': f32}`` (symmetric, per-output-channel —
    the last axis); smaller leaves (LayerNorm scales, biases) pass through
    unchanged. The result has the same tree structure with quantized
    leaves replaced by those dicts; `dequantize_params` inverts.
    """

    def q(p):
        if p.ndim < 2 or p.size < min_size:
            return p
        # Reduce over axis 0 only: dequantization is elementwise, so any
        # broadcastable scale shape is valid — finer granularity is
        # strictly lower error. Reducing all leading axes would collapse
        # e.g. a [d, H, hd] qkv kernel's heads into one shared scale per
        # hd channel, starving small-magnitude heads of int8 levels.
        values, scale = _quantize_sym(p, axis=0)
        return {_Q: values, "scale": scale}

    return jax.tree.map(q, params)


def dequantize_params(qparams, dtype=jnp.bfloat16):
    """Reconstruct a plain param tree (``dtype`` compute copies).

    Called INSIDE the decode scan body so the convert+scale fuses into the
    step's matmul reads and the weights live in HBM as int8 — calling it
    outside the loop would materialize full-width weights once and forfeit
    the bandwidth saving.
    """

    def d(x):
        if _is_qleaf(x):
            return x[_Q].astype(dtype) * x["scale"].astype(dtype)
        return x

    return jax.tree.map(d, qparams, is_leaf=_is_qleaf)


def make_unpack(quantized: bool):
    """The decode-family dequant hook: identity for plain param trees,
    `dequantize_params` for quantized ones. Shared by
    decoding/speculative/beam so the dequant contract lives in ONE place —
    each caller invokes it INSIDE its step/loop body (see
    `dequantize_params` on why placement matters)."""
    if quantized:
        return dequantize_params
    return lambda q: q


def int8_dot_general(lhs, rhs, dimension_numbers, precision=None,
                     preferred_element_type=None):
    """Drop-in ``lax.dot_general`` running the contraction on the int8 MXU.

    Dynamic symmetric quantization on both operands: ``lhs`` (activations)
    gets one scale per row — per every non-contracted index, amax over the
    contracted axes, recomputed each call; ``rhs`` (weights) one scale per
    output channel. The int32 MXU accumulation is exact; the only error is
    the two roundings, bounded by each operand's per-row/channel amax/127.
    The result is rescaled by the outer product of the scale vectors in
    f32 and cast back.

    Covers the contraction patterns flax's Dense/DenseGeneral emit (no
    batch dimensions); inject via ``nn.DenseGeneral(dot_general=...)`` —
    how `TransformerLM(int8_compute=True)` wires it.
    """
    (lc, rc), (lb, rb) = dimension_numbers
    if lb or rb:
        raise NotImplementedError(
            "int8_dot_general covers Dense-style contractions (no batch "
            "dims); got batch dimension_numbers "
            f"{dimension_numbers}"
        )
    lc, rc = tuple(lc), tuple(rc)
    out_dtype = preferred_element_type or jnp.result_type(lhs, rhs)

    def q(x, contract_dims):
        xq, s = _quantize_sym(x, axis=contract_dims)
        return xq, jnp.squeeze(s, axis=contract_dims)

    lq, s_l = q(lhs, lc)  # s_l: lhs free dims
    rq, s_r = q(rhs, rc)  # s_r: rhs free dims
    out = lax.dot_general(
        lq, rq, dimension_numbers, preferred_element_type=jnp.int32
    )
    # Output layout (no batch dims): lhs free dims then rhs free dims.
    scale = (
        s_l.reshape(s_l.shape + (1,) * s_r.ndim)
        * s_r.reshape((1,) * s_l.ndim + s_r.shape)
    )
    return (out.astype(jnp.float32) * scale).astype(out_dtype)


def quantized_bytes(qparams) -> int:
    """Total parameter bytes as stored (int8 + scales + passthrough)."""
    total = 0
    for leaf in jax.tree.leaves(qparams):
        total += leaf.size * leaf.dtype.itemsize
    return total
