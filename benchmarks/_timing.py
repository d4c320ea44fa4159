"""Shared honest-timing helper for the on-chip benchmark scripts.

One fused `lax.scan` chains N iterations of a step function with a carried
perturbation; the clock stops only after fetching a scalar that
data-depends on the whole chain. Two hazards this guards against:

* dispatch-loop timing: dispatch is asynchronous, so a clock stopped
  before the device finished measures the enqueue — hence ONE compiled
  scan + a value fetch;
* XLA optimizing the chain away: a `0 * out` perturbation gets folded to
  0, the carry becomes loop-invariant, and LICM hoists the body out of the
  loop (a matmul "above" the chip's peak); linear functionals of a matmul
  (slices, sums) get rewritten into contractions of the operands —
  consume outputs nonlinearly and fold with a tiny-but-NONZERO factor.

The residual bias is one host round-trip for the fetch over the whole
chain (~RTT/N); min-of-`repeats` filters spikes.
"""

from __future__ import annotations

import time

import jax


def timed_chain(step, x0, *, steps: int, repeats: int = 3) -> float:
    """Seconds per iteration of ``step`` (carry -> device scalar)."""

    def body(carry, _):
        out_scalar = step(carry)
        eps = (1.0 + 1e-30 * out_scalar).astype(carry.dtype)
        return carry * eps, out_scalar

    @jax.jit
    def run(x):
        carry, outs = jax.lax.scan(body, x, None, length=steps)
        return outs[-1] + 0.0 * carry.sum()

    float(jax.device_get(run(x0)))  # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        float(jax.device_get(run(x0)))
        best = min(best, time.perf_counter() - t0)
    return best / steps
