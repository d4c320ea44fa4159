"""`ops/ssd.ssd_scan`, Mamba-2's scalar-decay state-space recurrence in its
chunked (SSD) form, against the recurrence it computes, run token by
token: the output and all five gradients, chunks that do and do not divide
the sequence, a sequence shorter than a chunk, several chunk sizes, decays
that reach exp(-20,000) inside a chunk, one traced copy for a model's
identical calls, and that the chunk changes nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import ssd


def token_by_token(x, dt, a_log, b, c):
    """``S_t = exp(-dt_t exp(A_log)) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t
    C_t``, one position at a time; float32 ``[B, T, H, P]``."""
    batch, _, heads, dim = x.shape
    rate = jnp.exp(a_log)

    def step(state, at):
        x_t, dt_t, b_t, c_t = at  # [B, H, P], [B, H], [B, N], [B, N]
        state = (jnp.exp(-dt_t * rate)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None])
        return state, jnp.einsum(
            "bhpn,bn->bhp", state, c_t, precision="highest")

    _, y = jax.lax.scan(
        step, jnp.zeros((batch, heads, dim, b.shape[-1])),
        tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def inputs(seed, t, *, batch=2, heads=3, dim=8, state=16, plunge=False):
    """Steps drawn log-uniformly from [1e-3, 1], rates a head from 1 to 15.
    ``plunge``: every step is 5 and every rate 16, so that ``dt A`` is -80
    a position and the running sum inside one chunk of 256 passes
    -20,000."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (batch, t, heads, dim))
    dt = jnp.exp(jax.random.uniform(
        keys[1], (batch, t, heads), minval=np.log(1e-3), maxval=0.0))
    a_log = jnp.linspace(0.0, 2.7, heads)
    if plunge:
        dt, a_log = jnp.full_like(dt, 5.0), jnp.full_like(a_log, np.log(16.0))
    b = jax.random.normal(keys[2], (batch, t, state))
    c = jax.random.normal(keys[3], (batch, t, state))
    return x, dt, a_log, b, c


def with_gradients(fn, args):
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    loss, grads = jax.value_and_grad(
        lambda *a: (fn(*a) * weight).sum(), argnums=(0, 1, 2, 3, 4))(*args)
    return fn(*args), grads


def assert_close(got, want, tol):
    scale = float(jnp.abs(want).max())
    # (the floor: a gradient that is 1e-30 everywhere may come out 0)
    np.testing.assert_allclose(got, want, atol=tol * scale + 1e-12, rtol=0)


@pytest.mark.parametrize("t,chunk", [
    (64, 16), (50, 16), (10, 16), (100, 32), (40, 256), (96, 32), (33, 8),
    (300, 64)], ids=[
        "four_chunks", "t_not_a_multiple", "under_one_chunk", "100_over_32",
        "the_published_chunk_over_a_short_t", "three_chunks", "chunks_of_8",
        "five_chunks_of_64"])
def test_the_chunked_scan_is_the_recurrence(t, chunk):
    args = inputs(t, t)
    want, want_grads = with_gradients(token_by_token, args)
    got, got_grads = with_gradients(
        lambda *a: ssd.ssd_scan(*a, chunk=chunk), args)
    assert got.dtype == jnp.float32 and got.shape == args[0].shape
    assert_close(got, want, 2e-5)
    for name, g, w in zip(("x", "dt", "a_log", "b", "c"), got_grads,
                          want_grads):
        assert float(jnp.abs(w).max()) > 0, name
        assert_close(g, w, 2e-4)


def test_the_chunk_changes_nothing():
    args = inputs(3, 96)
    want = ssd.ssd_scan(*args, chunk=96)
    for chunk in (8, 24, 32, 256):
        assert_close(ssd.ssd_scan(*args, chunk=chunk), want, 2e-5)


def test_bfloat16_inputs_are_read_in_float32():
    x, dt, a_log, b, c = inputs(4, 48)
    low = [a.astype(jnp.bfloat16) for a in (x, b, c)]
    got = ssd.ssd_scan(low[0], dt, a_log, low[1], low[2], chunk=16)
    want = token_by_token(low[0].astype(jnp.float32), dt, a_log,
                          *(a.astype(jnp.float32) for a in low[1:]))
    assert got.dtype == jnp.float32
    assert_close(got, want, 2e-5)


@pytest.mark.parametrize("chunk", [64, 256])
def test_large_decays_stay_finite_and_right(chunk):
    """``dt A`` = -80 a position: the running sum reaches -20,000 inside a
    chunk of 256 and a ratio formed as exp(l_t) / exp(l_s) would be 0 / 0.
    Every decay is exp of a difference, so the output is the last
    position's own write and every gradient is finite."""
    args = inputs(5, 300, plunge=True)
    want, want_grads = with_gradients(token_by_token, args)
    got, got_grads = with_gradients(
        lambda *a: ssd.ssd_scan(*a, chunk=chunk), args)
    assert bool(jnp.isfinite(got).all())
    assert_close(got, want, 2e-5)
    for g, w in zip(got_grads, want_grads):
        assert bool(jnp.isfinite(g).all())
        assert_close(g, w, 2e-4)


def test_the_steps_of_a_padded_tail_write_nothing():
    """T 50 in chunks of 16 is padded to 64 with steps of 0: the first 50
    outputs are those of the same sequence cut from a longer one."""
    long = inputs(6, 64)
    cut = tuple(a if a.ndim == 1 else a[:, :50] for a in long)
    np.testing.assert_allclose(
        ssd.ssd_scan(*cut, chunk=16), ssd.ssd_scan(*long, chunk=16)[:, :50],
        atol=1e-5)


def test_chunk_counts_and_one_traced_copy():
    assert ssd.n_chunks(4096, 256) == 16 and ssd.n_chunks(50, 16) == 4
    assert ssd.n_chunks(10, 16) == 1
    args = inputs(7, 32)
    traces = ssd.ssd_scan._cache_size()
    for _ in range(3):
        ssd.ssd_scan(*args, chunk=8)
    assert ssd.ssd_scan._cache_size() == traces + 1
