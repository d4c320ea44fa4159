"""`chiprun -- env PYTHONPATH=. python build/ssd_probe.py`: ops/ssd.py's
scan alone on the chip at the Granite cell's shapes (16 heads of 64, state
128): against the token-by-token recurrence (output and every gradient, at
T 4,096 / 1,000 / 100 and with decays of -80 a position), and timed,
forward and forward + backward, at chunks 128 / 256 / 512 and at the three
matmul precisions. ~2 chip-minutes."""
import functools, json, time
import jax, jax.numpy as jnp, numpy as np
from horovod_tpu.ops import ssd

def recurrence(x, dt, a_log, b, c):
    rate = jnp.exp(a_log)
    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (jnp.exp(-dt_t * rate)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None])
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t, precision="highest")
    _, y = jax.lax.scan(step, jnp.zeros((x.shape[0], x.shape[2], x.shape[3], b.shape[-1])),
                        tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)

def inputs(seed, t, plunge=False, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(k[0], (1, t, 16, 64)).astype(dtype)
    dt = jnp.exp(jax.random.uniform(k[1], (1, t, 16), minval=np.log(1e-3), maxval=np.log(0.1)))
    a_log = jnp.linspace(0.0, 2.7, 16)
    if plunge:
        dt, a_log = jnp.full_like(dt, 5.0), jnp.full_like(a_log, np.log(16.0))
    b = jax.random.normal(k[2], (1, t, 128)).astype(dtype)
    c = jax.random.normal(k[3], (1, t, 128)).astype(dtype)
    return x, dt, a_log, b, c

def grads(fn, args):
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    return jax.jit(jax.value_and_grad(lambda *a: (fn(*a).astype(jnp.float32) * w).sum(), argnums=(0, 1, 2, 3, 4)))(*args)

print(jax.devices())
for t, chunk, plunge in ((4096, 256, False), (1000, 256, False), (100, 256, False), (300, 256, True), (4096, 256, True)):
    args = inputs(t, t, plunge)
    want = jax.jit(recurrence)(*args); got = ssd.ssd_scan(*args, chunk=chunk)
    (_, wg), (_, gg) = grads(recurrence, args), grads(functools.partial(ssd.ssd_scan, chunk=chunk), args)
    scale = float(jnp.abs(want).max())
    print(json.dumps({"t": t, "chunk": chunk, "plunge": plunge, "finite": bool(jnp.isfinite(got).all() & all(jnp.isfinite(g).all() for g in gg)),
          "out_err": float(jnp.abs(got - want).max()) / scale,
          "grad_err": [float(jnp.abs(g - w).max() / (jnp.abs(w).max() + 1e-30)) for g, w in zip(gg, wg)]}), flush=True)

def timed(fn, args, n=20):
    out = fn(*args); jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3

args = inputs(1, 4096, dtype=jnp.bfloat16)
for precision in ("HIGHEST", "HIGH", "DEFAULT"):
    ssd._dot = functools.partial(jnp.einsum, precision=getattr(jax.lax.Precision, precision))
    for chunk in (128, 256, 512):
        ssd.ssd_scan.clear_cache()
        fwd = jax.jit(functools.partial(ssd.ssd_scan, chunk=chunk))
        w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
        both = jax.jit(jax.grad(lambda *a: (ssd.ssd_scan(*a, chunk=chunk) * w).sum(), argnums=(0, 1, 2, 3, 4)))
        ref = ssd.ssd_scan(*args, chunk=chunk)
        print(json.dumps({"precision": precision, "chunk": chunk, "forward_ms": timed(fwd, args), "forward_backward_ms": timed(both, args)}), flush=True)
