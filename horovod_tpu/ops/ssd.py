"""Mamba-2's state-space recurrence with a scalar decay a head, in its
chunked (state-space-duality, SSD) form (arXiv:2405.21060) — the
state-space layers of `models/hybrid_moe_lm.py`.

Per head, over the positions t of a sequence, with a float32 state
``S [P, N]`` that starts at zero::

    a_t = exp(dt_t * A),   A = -exp(A_log) < 0          one scalar a head
    S_t = a_t S_{t-1} + dt_t * x_t B_t^T                x_t [P], B_t [N]
    y_t = S_t C_t                                       C_t [N]

``B`` and ``C`` are ONE group's: every head reads the same two ``[N]``
vectors a position (``mamba_n_groups`` 1). The skip ``D x`` is the
caller's. ``ssd_scan(x, dt, a_log, b, c, chunk=)`` computes it a chunk of
``chunk`` = Q positions at a time. With ``l_t`` the running sum of ``dt *
A`` inside a chunk (inclusive, so ``l_t - l_s`` is the log-decay from s to
t) and ``S_{c-1}`` the state after the chunk before::

    Y_intra = (L o C B^T)(dt o X)        L[t, s] = exp(l_t - l_s), s <= t
    state_c = sum_s exp(l_Q - l_s) dt_s x_s B_s^T
    S_c     = exp(l_Q) S_{c-1} + state_c                the only sequential part
    Y_inter[t] = exp(l_t) S_{c-1} C_t

**Every decay is exp of a difference of running sums with the later
position first**, so it is at most 1: the exponent is formed as a
difference before ``exp`` and never as a ratio of two exponentials (a head
whose ``dt * A`` is -80 a position reaches exp(-20,000) inside one chunk of
256, which only underflows to the 0 it stands for). Pairs above the
diagonal are masked in the exponent, before ``exp``.

One form for every shape: XLA matmuls over all chunks at once (``C B^T`` is
formed once a chunk for all heads) and one `lax.scan` over the chunks that
carries the state; the backward pass is autodiff's. ``x``, ``b``, ``c``
arrive in the compute dtype; the sums, the decays, the state, every product
(precision ``HIGHEST``) and the output are float32. A sequence the chunk
does not divide is padded with positions whose ``dt`` is 0 (decay 1, nothing
written), a sequence shorter than a chunk is one chunk of its own length.
Jitted: a model's identical calls share one traced and one lowered copy in
each program. The device events carry the caller's scope (``hvt.ssm/scan``
in `models/hybrid_moe_lm.py`). A later Mosaic kernel is named
``hvt_ssd_*`` (chipbench/ssm_spans.py matches the names whole).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

DEFAULT_CHUNK = 256
_dot = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)


def n_chunks(seq_len: int, chunk: int) -> int:
    """Chunks a sequence of ``seq_len`` positions is cut into."""
    return -(-seq_len // min(chunk, seq_len))


def decay_rate(a_log):
    """``A = -exp(A_log)``: float32 ``[H]``, below zero."""
    return -jnp.exp(a_log.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, a_log, b, c, *, chunk: int = DEFAULT_CHUNK):
    """``y [B, T, H, P]`` float32 (without the skip) for ``x [B, T, H, P]``,
    the steps ``dt [B, T, H]`` (after their softplus: float32, above zero),
    ``a_log [H]`` and one group's ``b, c [B, T, N]``. Differentiable in all
    five."""
    batch, t, heads, dim = x.shape
    n = b.shape[-1]
    q = min(chunk, t)
    pad = -t % q
    f32 = jnp.float32
    dt = dt.astype(f32)
    log_a = dt * decay_rate(a_log)  # [B, T, H], at most 0
    xdt = x.astype(f32) * dt[..., None]

    def chunks(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return a.reshape((batch, -1, q) + a.shape[2:])

    xdt, log_a, b, c = (chunks(a) for a in (
        xdt, log_a, b.astype(f32), c.astype(f32)))
    # Head-major, so that a head's [Q, Q] and [Q, P] are the minor axes.
    run = jnp.cumsum(jnp.moveaxis(log_a, -1, 2), axis=-1)  # [B, C, H, Q]
    seen = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(
        seen, run[..., :, None] - run[..., None, :], -jnp.inf))
    pairs = _dot("bctn,bcsn->bcts", c, b)  # one group: all heads' C B^T
    intra = _dot("bchts,bcshp->bcthp", pairs[:, :, None] * decay, xdt)

    total = run[..., -1]  # [B, C, H]: the chunk's whole log-decay
    to_end = jnp.exp(total[..., None] - run)  # [B, C, H, Q]
    written = _dot("bcshp,bcsn->bchpn",
                   xdt * jnp.moveaxis(to_end, 2, -1)[..., None], b)

    def carry_on(state, chunk_of):
        kept, new = chunk_of  # [B, H], [B, H, P, N]
        return jnp.exp(kept)[..., None, None] * state + new, state

    _, before = jax.lax.scan(
        carry_on, jnp.zeros((batch, heads, dim, n), f32),
        (jnp.moveaxis(total, 1, 0), jnp.moveaxis(written, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)  # the state each chunk starts from
    inter = (_dot("bchpn,bctn->bcthp", before, c)
             * jnp.moveaxis(jnp.exp(run), 2, -1)[..., None])
    y = (intra + inter).reshape(batch, -1, heads, dim)
    return y[:, :t]
