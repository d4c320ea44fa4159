"""The delta-rule op alone on the chip, at the Solar cell's shapes (one
sequence of 8,192, 8 heads of 128, chunks of 64):

    chiprun -- env PYTHONPATH=. python build/kda_probe.py [xla] [kernels]

For each form asked for (default both: ``xla`` is `ops/delta_rule._xla_form`,
``kernels`` what `gated_delta_rule` takes at these shapes) one JSON line:
milliseconds a call forward and forward + backward (host clock around ten
calls), the error of the output and of the five gradients against the
token-by-token recurrence (relative RMS), and ``families``: every op
family of a profiled forward + backward program under the scope
``hvt.kda/scan``, forward and backward apart, as ``[pass, family, events a
call, ms a call]`` (read as the benchmark reads a cell's trace:
`chipbench/reduce.py`, `chipbench/spans.py`). A kernel alone is no
substitute for the cell: `PERF.md` takes its end-to-end numbers from
`python3 -m chipbench.run` only.
"""
import functools
import json
import pathlib
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

from chipbench import reduce, spans
from horovod_tpu.ops import delta_rule

B, T, H, D, CHUNK = 1, 8192, 8, 128, 64
SCOPE = "hvt.kda/scan"
FORMS = {
    "xla": lambda *inputs: delta_rule._xla_form(*inputs, CHUNK),
    "kernels": functools.partial(delta_rule.gated_delta_rule, chunk=CHUNK),
}


def inputs():
    keys = jax.random.split(jax.random.PRNGKey(0), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    shape = (B, T, H, D)
    q = (unit(jax.random.normal(keys[0], shape)) * D ** -0.5).astype(jnp.bfloat16)
    k = unit(jax.random.normal(keys[1], shape)).astype(jnp.bfloat16)
    v = jax.nn.silu(jax.random.normal(keys[2], shape)).astype(jnp.bfloat16)
    rate = jnp.exp(jnp.linspace(0.0, 2.7, H))[:, None]
    g = -rate * jnp.exp(jax.random.uniform(
        keys[3], shape, minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(keys[4], (B, T, H)))
    return (q, k, v, g, beta), jax.random.normal(keys[5], shape)


def recurrence(q, k, v, g, beta):
    def step(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("bhc,bhcv->bhv", k_t, state, precision="highest")
        state = state + (beta_t[..., None] * k_t)[..., None] * (
            v_t - seen)[..., None, :]
        return state, jnp.einsum(
            "bhc,bhcv->bhv", q_t, state, precision="highest")

    _, out = jax.lax.scan(step, jnp.zeros((B, H, D, D)), tuple(
        jnp.moveaxis(a.astype(jnp.float32), 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)


def timed_ms(fn, args, calls=10):
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / calls * 1e3


def families(fn, args, calls=12):
    """[[pass, family, events a call, ms a call]] of the leaf device ops of
    ``calls`` profiled calls (the first and last dropped), largest first."""
    with tempfile.TemporaryDirectory() as root:
        with jax.profiler.trace(root):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        path = max(pathlib.Path(root).glob("plugins/profile/*/*.xplane.pb"))
        rows, scopes = reduce.rows_from_xplane(str(path)), spans.read(path)["scopes"]
    chips = reduce.chips_from_rows(rows)
    if not chips:  # no device plane: not on the chip
        return []
    chip = chips[0]
    total: dict = {}
    for name, _, dur in chip.ops:
        scope = scopes.get(name, "")
        inside = SCOPE in scope or reduce.KERNEL_MARK in name
        which = ("backward" if "transpose(" in scope else "forward") if inside else "outside"
        key = (which, reduce.op_family(name))
        events, ns = total.get(key, (0, 0.0))
        total[key] = (events + 1, ns + dur)
    steps = len(chip.steps)
    return [[*key, events / steps, ns / 1e6 / steps]
            for key, (events, ns) in sorted(total.items(), key=lambda kv: -kv[1][1])]


def main(names):
    args, weight = inputs()

    def with_gradients(fn):
        def loss(*a):
            with jax.named_scope(SCOPE):
                out = fn(*a)
            return (out.astype(jnp.float32) * weight).sum()
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))

    want_out = jax.jit(recurrence)(*args)
    _, want_grads = with_gradients(recurrence)(*args)

    def rel(a, b):
        a = a.astype(jnp.float32)
        return float(jnp.sqrt(jnp.mean((a - b) ** 2) / jnp.mean(b ** 2)))

    for name in names:
        forward, both = jax.jit(FORMS[name]), with_gradients(FORMS[name])
        _, grads = both(*args)
        table = families(both, args)
        print(json.dumps({
            "form": name, "device": jax.devices()[0].device_kind,
            "fwd_ms": timed_ms(forward, args),
            "fwd_bwd_ms": timed_ms(both, args),
            "out_rel_rms": rel(forward(*args), want_out),
            "grad_rel_rms": [rel(a, b) for a, b in zip(grads, want_grads)],
            "scan_ms": {which: sum(r[3] for r in table if r[0] == which)
                        for which in ("forward", "backward", "outside")},
            "families": table}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or list(FORMS))
