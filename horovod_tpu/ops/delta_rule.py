"""The gated delta rule with per-channel decay (Kimi Delta Attention, KDA:
arXiv:2510.26692), in chunked form — the linear-attention layers of
`models/hybrid_moe_lm.py`.

Per head, over the positions t of a sequence, with a float32 state
``S [Dk, Dv]`` that starts at zero::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``g_t <= 0`` is a log-decay per CHANNEL of the key (``[Dk]`` a position),
``beta_t`` a scalar (up to 2: the transition may have a negative
eigenvalue). ``gated_delta_rule(q, k, v, g, beta, chunk=)`` computes it a
chunk of ``chunk`` positions at a time (the WY form of a product of
Householder-like factors). With ``G`` the running sum of ``g`` inside a
chunk, ``A[t, s] = beta_t sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])`` for
s < t and ``P[t, s]`` the same with ``q_t`` and no beta for s <= t::

    (I + A) [W | U0] = [beta k exp(G) | beta v]       one triangular solve
    M = Diag(exp(G_C)) - Khat^T W,  Z = Khat^T U0     Khat_s = k_s exp(G_C - G_s)
    S_{n+1} = M_n S_n + Z_n                           the only sequential part
    U = U0 - W S_n;   O = (q exp(G)) S_n + P U

Everything inside a chunk is matmuls over all chunks at once; the state
walks the chunks in one `lax.scan` whose step is one ``[Dk, Dk] x [Dk,
Dv]`` product a head, and the outputs are computed from the kept states,
again over all chunks at once.

**Every decay ratio is exp of a difference of running sums with the later
position first**, so it is at most 1: ``exp(-G_s)`` alone overflows float32
inside one chunk once a channel decays fast (softplus 12 at ``exp(A_log)``
16 is 190 a step). Pairs in different sub-chunks of `SUB` positions go
through the running sum at the later sub-chunk's start, ``exp(G_t - R)
exp(R - G_s)`` with both factors at most 1, which keeps them matmuls; pairs
inside a sub-chunk are formed directly (``SUB^2 Dk`` elementwise a
sub-chunk). A factor that underflows to 0 stands for a ratio that is
smaller still.

Inputs in the compute dtype; the sums, the decays, the solve and the state
in float32 at precision ``HIGHEST`` (the op is latency-bound, not
FLOP-bound: 1 % of the step's FLOPs). The backward pass is autodiff through
all of it under `jax.checkpoint`: only the five inputs are kept from the
forward pass, and the backward pass recomputes the chunk-local matrices and
walks the chunks once more, so a layer's residuals are its inputs.

The implementation is jitted: a model's identical calls share one traced
and one lowered copy in each program (PERF.md, PR 34). The device events
carry the caller's scope (``hvt.kda/scan`` in `models/hybrid_moe_lm.py`);
a later Pallas kernel would be named ``hvt_kda_fwd`` / ``hvt_kda_bwd`` and
read by the same metrics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Positions whose pairwise decays are formed directly; pairs further apart
# go through a reference point between them.
SUB = 16
DEFAULT_CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST
_dot = functools.partial(jnp.einsum, precision=_HIGHEST)


def n_chunks(seq_len: int, chunk: int) -> int:
    """Chunks a sequence of ``seq_len`` positions is walked in."""
    return -(-seq_len // chunk)


@jax.checkpoint
def _within_sub_chunks(local, k, q):
    """``sum_c x_t[c] k_s[c] exp(G_t[c] - G_s[c])`` for x = k and x = q over
    the pairs s <= t of each sub-chunk (0 elsewhere): ``local, k, q [...,
    sub, Dk]`` -> two ``[..., sub, sub]``. The ratios are formed, used and
    dropped here, and formed again in the backward pass."""
    sub = local.shape[-2]
    seen = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    ratio = jnp.exp(jnp.where(
        seen, local[..., :, None, :] - local[..., None, :, :], -jnp.inf))
    weighted = k[..., None, :, :] * ratio                  # [.., t, s, Dk]
    return (jnp.sum(k[..., :, None, :] * weighted, axis=-1),
            jnp.sum(q[..., :, None, :] * weighted, axis=-1))


def _chunk_local(q, k, v, g, beta):
    """The matrices of every chunk at once. ``q, k, g [B, H, n, C, Dk]``,
    ``v [B, H, n, C, Dv]``, ``beta [B, H, n, C]``, float32. Returns ``W [..., C,
    Dk]``, ``U0 [..., C, Dv]``, ``P [..., C, C]``, ``q exp(G)``, ``M [...,
    Dk, Dk]``, ``Z [..., Dk, Dv]``."""
    *lead, c, dk = q.shape
    sub = min(SUB, c)
    ns = c // sub

    def by_sub(x):
        return x.reshape(*lead, ns, sub, x.shape[-1])

    # Running sums: inside a sub-chunk, and at each sub-chunk's start.
    local = jnp.cumsum(by_sub(g), axis=-2)                 # [.., ns, sub, Dk]
    totals = local[..., -1, :]                             # [.., ns, Dk]
    start = jnp.cumsum(totals, axis=-2) - totals           # exclusive
    running = (local + start[..., None, :]).reshape(*lead, c, dk)   # G_t

    # Pairs in different sub-chunks: through R = the later one's start.
    rows = jnp.exp(local)                                  # exp(G_t - R)
    earlier = (jnp.arange(c)[None, :]
               < (jnp.arange(ns) * sub)[:, None])[..., None]   # [ns, C, 1]
    since = start[..., :, None, :] - running[..., None, :, :]  # R - G_s
    cols = k[..., None, :, :] * jnp.exp(
        jnp.where(earlier, since, -jnp.inf))               # [.., ns, C, Dk]

    def across(x):
        return _dot("...itd,...isd->...its", by_sub(x) * rows, cols).reshape(
            *lead, c, c)

    def on_diagonal(blocks):
        """[.., ns, sub, sub] -> [.., C, C] with the blocks on the diagonal."""
        eye = jnp.eye(ns, dtype=blocks.dtype)
        return (blocks[..., :, :, None, :] * eye[:, None, :, None]).reshape(
            *lead, c, c)

    # Pairs inside a sub-chunk: exp(G_t - G_s) itself, s <= t; a head at a
    # time, so that the [sub, sub, Dk] ratios of all chunks never stand in
    # memory together.
    kk_blocks, qk_blocks = jax.lax.map(
        lambda head: _within_sub_chunks(*head),
        tuple(jnp.moveaxis(x, 1, 0) for x in (local, by_sub(k), by_sub(q))))
    kk = across(k) + on_diagonal(jnp.moveaxis(kk_blocks, 0, 1))
    p = across(q) + on_diagonal(jnp.moveaxis(qk_blocks, 0, 1))  # s <= t
    a = beta[..., :, None] * jnp.tril(kk, -1)
    decay = jnp.exp(running)
    rhs = jnp.concatenate(
        [beta[..., None] * k * decay, beta[..., None] * v], axis=-1)
    solved = jax.lax.linalg.triangular_solve(
        a + jnp.eye(c, dtype=a.dtype), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    w, u0 = solved[..., :dk], solved[..., dk:]
    k_hat = k * jnp.exp(running[..., -1:, :] - running)
    m = (jnp.exp(running[..., -1, :])[..., :, None] * jnp.eye(dk, dtype=q.dtype)
         - _dot("...sc,...sd->...cd", k_hat, w))
    z = _dot("...sc,...sv->...cv", k_hat, u0)
    return w, u0, p, q * decay, m, z


@functools.partial(jax.jit, static_argnames=("chunk",))
@functools.partial(jax.checkpoint, static_argnums=(5,))
def _gated_delta_rule(q, k, v, g, beta, chunk):
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = n_chunks(t, chunk)
    pad = n * chunk - t

    def chunked(x):
        """[B, T, H, ...] -> float32 [B, H, n, chunk, ...]; the positions
        past T hold zeros, which leave the state as it is."""
        x = jnp.pad(x.astype(jnp.float32),
                    ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(b, n, chunk, *x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    w, u0, p, q_decayed, m, z = _chunk_local(
        chunked(q), chunked(k), chunked(v), chunked(g), chunked(beta))

    def next_state(state, mz):
        m_n, z_n = mz
        return _dot("bhck,bhkv->bhcv", m_n, state) + z_n, state

    _, states = jax.lax.scan(
        next_state, jnp.zeros((b, h, dk, dv), jnp.float32),
        (jnp.moveaxis(m, 2, 0), jnp.moveaxis(z, 2, 0)))
    states = jnp.moveaxis(states, 0, 2)                    # S at each start
    u = u0 - _dot("bhnsc,bhncv->bhnsv", w, states)
    out = (_dot("bhntc,bhncv->bhntv", q_decayed, states)
           + _dot("bhnts,bhnsv->bhntv", p, u))
    out = jnp.moveaxis(out, 1, 3).reshape(b, n * chunk, h, dv)
    return out[:, :t].astype(v.dtype)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = DEFAULT_CHUNK):
    """``o [B, T, H, Dv]`` of the recurrence above for ``q, k [B, T, H,
    Dk]``, ``v [B, T, H, Dv]``, log-decays ``g [B, T, H, Dk]`` (<= 0) and
    ``beta [B, T, H]``, from a zero state, in ``v.dtype``. ``chunk`` is a
    multiple of `SUB` (or less than it); a T that it does not divide, or
    that is shorter, is padded with positions that change nothing."""
    if q.shape != k.shape or q.shape != g.shape:
        raise ValueError(
            f"q {q.shape}, k {k.shape} and g {g.shape} differ in shape")
    if v.shape[:3] != q.shape[:3] or beta.shape != q.shape[:3]:
        raise ValueError(
            f"v {v.shape} / beta {beta.shape} do not go with q {q.shape}")
    if chunk < 1 or chunk % min(SUB, chunk):
        raise ValueError(
            f"chunk {chunk} is not a multiple of the sub-chunk {SUB}")
    return _gated_delta_rule(q, k, v, g, beta, chunk)
