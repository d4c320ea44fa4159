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

    (I + A) [W | U0] = [beta k exp(G) | beta v]       T = (I + A)^-1
    U = U0 - W S_n;   O = (q exp(G)) S_n + P U        Khat_s = k_s exp(G_C - G_s)
    S_{n+1} = Diag(exp(G_C)) S_n + Khat^T U           the only sequential part

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
in float32, every product at precision ``HIGHEST``. The residuals of a
call are its five inputs, in both forms.

**Two forms, chosen from the shapes alone** (`takes_kernels`; the gauge
``hvt_kda_scan{impl}`` says which a call took):

- **the kernels**, where a head's channels are multiples of 128 and the
  chunk a multiple of `SUB` (Mosaic on a TPU, the Pallas interpreter
  elsewhere: `flash_attention.default_interpret`). A `jax.custom_vjp` around
  Mosaic calls whose grid is (batch, head, chunk) and that read ``q, k, v,
  g`` where they lie (``[B, T, H x D]``: a head and chunk is one block):
  `_pairs_kernel` (every chunk on its own: the running sums as a product
  with ones, the pairs in different sub-chunks as matmuls, kk and ``P``),
  `_walk_kernel` (the chunk axis sequential, the state TRANSPOSED ``[Dv,
  Dk]`` in VMEM scratch, zeroed at a head's first chunk: ``W, U0 = T rhs``,
  the three lines above; in the backward pass it keeps ``S_n, W, U0, U`` of
  every chunk in place of the outputs) and `_backward_kernel` (the same grid
  in reverse with the state's cotangent in scratch, hand-written: the
  walk's transpose, through ``T`` to ``A``, through the elementwise factors,
  through the pair matrices and the running sums, down to ``dq, dk, dv, dg,
  dbeta``). The forward pass's calls are named ``hvt_kda_fwd``, the backward
  pass's ``hvt_kda_bwd`` (the benchmark's `kda_*` metrics match the names
  whole). Two things stay XLA's between the calls, because they measured
  faster there (PERF.md §5, PR 36): the pairs INSIDE a sub-chunk with their
  autodiff (`_diagonal_blocks`: ``[SUB, SUB, Dk]`` ratios summed over the
  lanes, which is no matmul), and ``T`` by `triangular_solve` against the
  identity, not differentiated (``dA = -tril(T^T [dW | dU0] [W | U0]^T,
  -1)`` is in the kernel). No loop over the chunks is left in either pass.
- **the XLA form** for every other shape (the toy widths of the CPU model
  tests), and what the tests hold the kernels to: the matrices of all
  chunks at once with ``M = Diag(exp(G_C)) - Khat^T W`` and ``Z = Khat^T
  U0``, ONE `lax.scan` whose step is ``S' = M S + Z``, the outputs from the
  kept states, backward = autodiff under `jax.checkpoint`.

Both are jitted: a model's identical calls share one traced and one lowered
copy in each program (PERF.md, PR 34). The device events carry the caller's
scope (``hvt.kda/scan`` in `models/hybrid_moe_lm.py`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import flash_attention

# Positions whose pairwise decays are formed directly; pairs further apart
# go through a reference point between them.
SUB = 16
DEFAULT_CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST
_dot = functools.partial(jnp.einsum, precision=_HIGHEST)
# The Mosaic calls' names (`pallas_call(name=)`), by the pass they run in: the
# benchmark's `kda_*` metrics match them whole (chipbench/kda_spans.py).
KERNEL_FWD = "hvt_kda_fwd"
KERNEL_BWD = "hvt_kda_bwd"


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


def _diagonal_blocks(local, k, q):
    """Pairs inside a sub-chunk: exp(G_t - G_s) itself, s <= t. ``local, k,
    q [B, H, n, ns, sub, Dk]`` -> two ``[B, H, n, ns, sub, sub]``; a head at
    a time, so that the [sub, sub, Dk] ratios of all chunks never stand in
    memory together."""
    blocks = jax.lax.map(
        lambda head: _within_sub_chunks(*head),
        tuple(jnp.moveaxis(x, 1, 0) for x in (local, k, q)))
    return tuple(jnp.moveaxis(x, 0, 1) for x in blocks)


def _pairs(q, k, g, beta):
    """The pair matrices of every chunk at once. ``q, k, g [B, H, n, C,
    Dk]``, ``beta [B, H, n, C]``, float32. Returns the running sums ``G
    [..., C, Dk]``, ``A [..., C, C]`` (strictly lower) and ``P [..., C, C]``
    (lower, with its diagonal)."""
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

    kk_blocks, qk_blocks = _diagonal_blocks(local, by_sub(k), by_sub(q))
    kk = across(k) + on_diagonal(kk_blocks)
    p = across(q) + on_diagonal(qk_blocks)                 # s <= t
    return running, beta[..., :, None] * jnp.tril(kk, -1), p


def _chunk_local(q, k, v, g, beta):
    """The matrices of every chunk at once, for the XLA form. ``q, k, g [B,
    H, n, C, Dk]``, ``v [B, H, n, C, Dv]``, ``beta [B, H, n, C]``, float32.
    Returns ``W [..., C, Dk]``, ``U0 [..., C, Dv]``, ``P [..., C, C]``, ``q
    exp(G)``, ``M [..., Dk, Dk]``, ``Z [..., Dk, Dv]``."""
    c, dk = q.shape[-2:]
    running, a, p = _pairs(q, k, g, beta)
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


def _chunked(x, chunk):
    """[B, T, H, ...] -> float32 [B, H, n, chunk, ...]; the positions past
    T hold zeros, which leave the state as it is."""
    b, t = x.shape[:2]
    n = n_chunks(t, chunk)
    x = _padded(x.astype(jnp.float32), n * chunk)
    x = x.reshape(b, n, chunk, *x.shape[2:])
    return jnp.moveaxis(x, 3, 1)


def _padded(x, length):
    """``x [B, T, ...]`` with zeros up to ``length`` positions."""
    pad = length - x.shape[1]
    if not pad:
        return x
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))


@functools.partial(jax.jit, static_argnames=("chunk",))
@functools.partial(jax.checkpoint, static_argnums=(5,))
def _xla_form(q, k, v, g, beta, chunk):
    """Everything in XLA: the chunk-local matrices with ``M`` and ``Z``, one
    `lax.scan` over the chunks, the outputs from the kept states; backward
    by autodiff. The path of the shapes the kernels do not take, and what
    the tests hold the kernels to."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = n_chunks(t, chunk)
    w, u0, p, q_decayed, m, z = _chunk_local(
        *(_chunked(x, chunk) for x in (q, k, v, g, beta)))

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


# --- the kernels -------------------------------------------------------------
# One grid step is one chunk of one head. The state is kept TRANSPOSED, ``St
# [Dv, Dk]``, so that everything a channel of the key has of its own (the
# running sums, the decays) lies along the lanes, as in ``g``.

_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _mm(a, b, contract):
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _sub_of(at):
    """The sub-chunk of each position of ``at`` (int32); a shift, `SUB` being
    a power of two (Mosaic takes no vector integer division, and sums of
    comparisons fold into comparisons of booleans, which it does not take
    either)."""
    return jax.lax.shift_right_logical(at, SUB.bit_length() - 1)


def _sum_matrix(c):
    """``[2C, C]`` of ones and zeros: times ``g`` it gives the running sums
    inside the sub-chunks (the upper half) over the sums at each sub-chunk's
    start (the lower half); a product with ones is exact at `HIGHEST`."""
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    sub_row, sub_col = _sub_of(row), _sub_of(col)
    inside = (sub_row == sub_col) & (col <= row)
    return jnp.concatenate([inside, sub_col < sub_row], 0).astype(jnp.float32)


def _spread(c):
    """``E [SUB, C]``, ``E[j, s] = 1`` where s is its sub-chunk's j-th: ``x
    [C, SUB] @ E`` repeats a sub-chunk's columns along the chunk."""
    col = _iota((SUB, c), 1)
    return (col - SUB * _sub_of(col) == _iota((SUB, c), 0)).astype(
        jnp.float32)


def _same_sub(c):
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    return _sub_of(row) == _sub_of(col)


def _across(q, k, local, start, running):
    """The factors of the pairs in different sub-chunks, through the later
    one's start: for each sub-chunk i but the first ``rows_i [2 SUB, Dk]``
    (k over q of its positions, times ``exp(G_t - R_i)``), ``cols_i [C,
    Dk]`` (``k_s exp(R_i - G_s)`` at the positions before it, 0 elsewhere)
    and that decay itself. ``rows_i cols_i^T`` are kk over qk."""
    c = k.shape[0]
    grow = jnp.exp(local)
    at = _iota((c, 1), 0)
    rows, cols, decays = [], [], []
    for lo in range(SUB, c, SUB):
        mine = slice(lo, lo + SUB)
        rows.append(jnp.concatenate(
            [k[mine] * grow[mine], q[mine] * grow[mine]], 0))
        decays.append(jnp.exp(jnp.where(
            at < lo, start[lo:lo + 1] - running, -jnp.inf)))
        cols.append(k * decays[-1])
    return grow, rows, cols, decays


def _pairs_kernel(q_ref, k_ref, g_ref, kk_in_ref, qk_in_ref,
                  run_ref, local_ref, kk_ref, p_ref):
    """A chunk's running sums and pair matrices: ``kk [C, C]`` strictly
    lower (``A`` before beta) and ``P`` lower; the pairs inside a sub-chunk
    come in as ``[C, SUB]`` blocks."""
    q, k = q_ref[0].astype(jnp.float32), k_ref[0].astype(jnp.float32)
    c = k.shape[0]
    sums = _mm(_sum_matrix(c), g_ref[0], _NN)
    local, start = sums[:c], sums[c:]
    running = local + start
    _, rows, cols, _ = _across(q, k, local, start, running)
    blocks = [_mm(r, col, _NT) for r, col in zip(rows, cols)]  # [2 SUB, C]
    none = jnp.zeros((SUB, c), jnp.float32)
    kk = jnp.concatenate([none] + [b[:SUB] for b in blocks], 0)
    qk = jnp.concatenate([none] + [b[SUB:] for b in blocks], 0)
    inside = _mm(jnp.concatenate([kk_in_ref[0, 0, 0], qk_in_ref[0, 0, 0]], 0),
                 _spread(c), _NN)                              # [2C, C]
    same = _same_sub(c)
    strictly = _iota((c, c), 1) < _iota((c, c), 0)
    run_ref[0, 0, 0], local_ref[0, 0, 0] = running, local
    kk_ref[0, 0, 0] = jnp.where(
        strictly, kk + jnp.where(same, inside[:c], 0.0), 0.0)
    p_ref[0, 0, 0] = qk + jnp.where(same, inside[c:], 0.0)


def _chunk_terms(q_ref, k_ref, v_ref, beta_ref, run_ref):
    """What a chunk's walk needs of its inputs, float32: ``q exp(G)``,
    ``Khat``, ``d`` ``[1, Dk]``, the solve's right-hand sides ``[beta k
    exp(G) | beta v]``, and the factors they were made with."""
    q, k, v = (ref[0].astype(jnp.float32) for ref in (q_ref, k_ref, v_ref))
    running, beta = run_ref[0, 0, 0], beta_ref[0, 0]
    decay = jnp.exp(running)
    last = running[-1:, :]
    to_end = jnp.exp(last - running)
    rhs_k = beta * k * decay
    return dict(q=q, k=k, v=v, beta=beta, decay=decay, to_end=to_end,
                running=running, q_decayed=q * decay, k_hat=k * to_end,
                d=jnp.exp(last), rhs_k=rhs_k,
                rhs=jnp.concatenate([rhs_k, beta * v], 1))


def _walk_kernel(q_ref, k_ref, v_ref, beta_ref, run_ref, t_ref, p_ref,
                 *outs_and_state, keep):
    """``U = U0 - W S; O = Qd S + P U; S' = d S + Khat^T U``. ``keep``: the
    backward pass's walk, which writes ``S``, ``W``, ``U0`` and ``U`` of
    every chunk in place of the outputs."""
    *outs, state = outs_and_state

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    x = _chunk_terms(q_ref, k_ref, v_ref, beta_ref, run_ref)
    c, dk = x["k"].shape
    solved = _mm(t_ref[0, 0, 0], x["rhs"], _NN)            # [W | U0]
    w, u0 = solved[:, :dk], solved[:, dk:]
    st = state[...]
    # Products that share an operand are one product of stacked rows: the
    # MXU holds the shared one, and 64 rows alone leave it half idle.
    if keep:
        u = u0 - _mm(w, st, _NT)
        for ref, value in zip(outs, (st, w, u0, u)):
            ref[0, 0, 0] = value
    else:
        on_state = _mm(jnp.concatenate([w, x["q_decayed"]], 0), st, _NT)
        u = u0 - on_state[:c]
        out = on_state[c:] + _mm(p_ref[0, 0, 0], u, _NN)
        outs[0][0] = out.astype(outs[0].dtype)
    state[...] = st * x["d"] + _mm(u, x["k_hat"], _TN)


def _backward_kernel(q_ref, k_ref, v_ref, beta_ref, run_ref, t_ref, p_ref,
                     local_ref, kk_ref, st_ref, w_ref, u0_ref, u_ref, do_ref,
                     dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dkk_in_ref,
                     dqk_in_ref, dstate):
    """The walk's transpose, chunks in reverse, ``dstate`` the cotangent of
    the state a chunk hands on; then through the solve to ``A``, through the
    elementwise factors, and through the pair matrices and the running sums
    to the inputs. What is left for XLA is the pairs inside a sub-chunk:
    their cotangents go out as ``[C, SUB]`` blocks."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    x = _chunk_terms(q_ref, k_ref, v_ref, beta_ref, run_ref)
    solve, p = t_ref[0, 0, 0], p_ref[0, 0, 0]
    st, w, u0, u = (ref[0, 0, 0] for ref in (st_ref, w_ref, u0_ref, u_ref))
    dout = do_ref[0].astype(jnp.float32)
    dst = dstate[...]                                      # of S', [Dv, Dk]
    c, width = x["k"].shape
    row, col = _iota((c, c), 0), _iota((c, c), 1)

    du = _mm(p, dout, _TN) + _mm(x["k_hat"], dst, _NT)
    # (As in the walk: products that share an operand are stacked.)
    both = jnp.concatenate([dout, -du], 0)                 # [2C, Dv]
    dstate[...] = _mm(both, jnp.concatenate([x["q_decayed"], w], 0),
                      _TN) + dst * x["d"]
    on_state = _mm(both, st, _NN)
    dq_decayed, dw = on_state[:c], on_state[c:]
    dk_hat = _mm(u, dst, _NN)
    dd = jnp.sum(st * dst, axis=0, keepdims=True)          # [1, Dk]
    dp = jnp.where(col <= row, _mm(dout, u, _NT), 0.0)
    # Through T = (I + A)^-1: [W | U0] = T rhs; A = beta kk.
    drhs = _mm(solve, jnp.concatenate([dw, du], 1), _TN)
    drhs_k, drhs_v = drhs[:, :width], drhs[:, width:]
    da = jnp.where(
        col < row, -_mm(drhs, jnp.concatenate([w, u0], 1), _NT), 0.0)
    beta, decay, k, q = x["beta"], x["decay"], x["k"], x["q"]
    dkk = beta * da
    dbeta = (jnp.sum(da * kk_ref[0, 0, 0], axis=1, keepdims=True)
             + jnp.sum(drhs_k * k * decay, axis=1, keepdims=True)
             + jnp.sum(drhs_v * x["v"], axis=1, keepdims=True))
    # Through the elementwise factors (G: the whole running sum).
    hat_term = dk_hat * x["k_hat"]
    dlast = jnp.sum(hat_term, axis=0, keepdims=True) + dd * x["d"]
    at = _iota((c, 1), 0)
    drunning = (dq_decayed * x["q_decayed"] + drhs_k * x["rhs_k"] - hat_term
                + jnp.where(at == c - 1, dlast, 0.0))
    dq = dq_decayed * decay
    dk = dk_hat * x["to_end"] + drhs_k * beta * decay
    # Through the pairs in different sub-chunks: kk over qk = rows cols^T.
    local, running = local_ref[0, 0, 0], x["running"]
    grow, rows, cols, decays = _across(q, k, local, running - local, running)
    zero = jnp.zeros((SUB, width), jnp.float32)
    dk_rows, dq_rows, dlocal_rows = [zero], [zero], [zero]
    dstart = jnp.zeros_like(running)
    for i, (r, cl, decayed) in enumerate(zip(rows, cols, decays)):
        lo = SUB * (i + 1)
        mine = slice(lo, lo + SUB)
        dblock = jnp.concatenate([dkk[mine], dp[mine]], 0)  # [2 SUB, C]
        drows, dcols = _mm(dblock, cl, _NN), _mm(dblock, r, _TN)
        dk_rows.append(drows[:SUB] * grow[mine])
        dq_rows.append(drows[SUB:] * grow[mine])
        dlocal_rows.append(drows[:SUB] * r[:SUB] + drows[SUB:] * r[SUB:])
        dk = dk + dcols * decayed
        through = dcols * cl
        drunning = drunning - through
        dstart = dstart + jnp.where(
            at == lo, jnp.sum(through, axis=0, keepdims=True), 0.0)
    dq = dq + jnp.concatenate(dq_rows, 0)
    dk = dk + jnp.concatenate(dk_rows, 0)
    # G = local + start, both sums of g.
    dg_ref[0] = _mm(_sum_matrix(c), jnp.concatenate(
        [jnp.concatenate(dlocal_rows, 0) + drunning, dstart + drunning], 0),
        _TN)
    dq_ref[0], dk_ref[0] = dq, dk
    dv_ref[0] = (drhs_v * beta).astype(dv_ref.dtype)
    dbeta_ref[0, 0] = dbeta
    # The pairs inside a sub-chunk: back to their [C, SUB] blocks.
    same, spread = _same_sub(c), _spread(c)
    dkk_in_ref[0, 0, 0] = _mm(jnp.where(same, dkk, 0.0), spread, _NT)
    dqk_in_ref[0, 0, 0] = _mm(jnp.where(same, dp, 0.0), spread, _NT)


def _specs(n, chunk, reverse=False):
    """The block a grid step ``(b, head, i)`` reads or writes of each kind of
    array: ``[B, T, H x D]`` where the inputs lie, ``[B, H, n, C, ...]``
    for what is made a chunk at a time, ``[B, H, T, 1]`` for beta."""
    at = (lambda i: n - 1 - i) if reverse else (lambda i: i)

    def in_place(d):
        return pl.BlockSpec((1, chunk, d), lambda b, hd, i: (b, at(i), hd))

    def by_chunk(rows, cols):
        return pl.BlockSpec((1, 1, 1, rows, cols),
                            lambda b, hd, i: (b, hd, at(i), 0, 0))

    column = pl.BlockSpec((1, 1, chunk, 1), lambda b, hd, i: (b, hd, at(i), 0))
    return in_place, by_chunk, column


_WALK = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))
_EACH = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))


def _inside_sub_chunks(q, k, g, chunk):
    """What XLA forms for the kernels, from float32 ``[B, T, H, Dk]``: the
    pairs inside every sub-chunk, kk and qk, as ``[B, H, n, C, SUB]``."""
    def by_sub(x):
        x = _chunked(x, chunk)
        return x.reshape(*x.shape[:3], chunk // SUB, SUB, x.shape[-1])

    blocks = _diagonal_blocks(jnp.cumsum(by_sub(g), axis=-2), by_sub(k),
                              by_sub(q))
    return tuple(x.reshape(*x.shape[:3], chunk, SUB) for x in blocks)


def _inverse(a):
    """``T = (I + A)^-1`` of every chunk."""
    eye = jnp.eye(a.shape[-1], dtype=a.dtype)
    return jax.lax.linalg.triangular_solve(
        a + eye, jnp.broadcast_to(eye, a.shape), left_side=True, lower=True,
        unit_diagonal=True)


def _where_they_lie(q, k, v, g, beta, chunk):
    """The kernels' views of the inputs: ``[B, T, H, D]`` as ``[B, T, H x
    D]`` (a head and chunk is one block, no copy), beta as a column ``[B, H,
    T, 1]``; T padded to whole chunks with positions that change nothing."""
    b, t = q.shape[:2]
    length = n_chunks(t, chunk) * chunk
    q, k, v, g = (_padded(x, length).reshape(b, length, -1)
                  for x in (q, k, v, g.astype(jnp.float32)))
    beta = _padded(beta.astype(jnp.float32), length)
    return q, k, v, g, jnp.moveaxis(beta, 2, 1)[..., None]


def _chunk_shapes(b, h, n, *shapes):
    return [jax.ShapeDtypeStruct((b, h, n, *shape), jnp.float32)
            for shape in shapes]


def _pair_matrices(name, interpret, chunk, h, q, k, g, kk_in, qk_in):
    """One Mosaic call, every chunk on its own: ``G`` and the sums inside
    the sub-chunks ``[B, H, n, C, Dk]``, kk and ``P [B, H, n, C, C]``."""
    b, length, width = k.shape
    n, dk = length // chunk, width // h
    in_place, by_chunk, _ = _specs(n, chunk)
    shapes = [(chunk, dk), (chunk, dk), (chunk, chunk), (chunk, chunk)]
    return pl.pallas_call(
        _pairs_kernel, grid=(b, h, n),
        in_specs=[in_place(dk)] * 3 + [by_chunk(chunk, SUB)] * 2,
        out_specs=[by_chunk(*shape) for shape in shapes],
        out_shape=_chunk_shapes(b, h, n, *shapes),
        compiler_params=_EACH, interpret=interpret, name=name,
    )(q, k, g, kk_in, qk_in)


def _walk(name, keep, interpret, chunk, q, k, v, beta, running, solve, p):
    """One Mosaic call that walks every head's chunks: the outputs ``[B, T,
    H x Dv]``, or with ``keep`` the state at every chunk's start ``[B, H,
    n, Dv, Dk]`` with that chunk's ``W``, ``U0`` and ``U``."""
    b, h, n, _, dk = running.shape
    dv = v.shape[-1] // h
    in_place, by_chunk, column = _specs(n, chunk)
    if keep:
        shapes = [(dv, dk), (chunk, dk), (chunk, dv), (chunk, dv)]
        out_specs = [by_chunk(*shape) for shape in shapes]
        out_shape = _chunk_shapes(b, h, n, *shapes)
    else:
        out_specs = [in_place(dv)]
        out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    return pl.pallas_call(
        functools.partial(_walk_kernel, keep=keep),
        grid=(b, h, n),
        in_specs=[in_place(dk), in_place(dk), in_place(dv), column,
                  by_chunk(chunk, dk), by_chunk(chunk, chunk),
                  by_chunk(chunk, chunk)],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_WALK, interpret=interpret, name=name,
    )(q, k, v, beta, running, solve, p)


def _walk_back(interpret, chunk, q, k, v, beta, running, solve, p, local, kk,
               kept, dout):
    """The backward walk: ``dq``, ``dk``, ``dg [B, T, H x Dk]`` float32 and
    ``dv`` where the inputs lie, ``dbeta`` as a column, and the cotangents
    of the pairs inside the sub-chunks ``[B, H, n, C, SUB]``."""
    b, h, n, _, dk = running.shape
    dv = v.shape[-1] // h
    in_place, by_chunk, column = _specs(n, chunk, reverse=True)
    f32 = jnp.float32
    return pl.pallas_call(
        _backward_kernel,
        grid=(b, h, n),
        in_specs=[in_place(dk), in_place(dk), in_place(dv), column,
                  by_chunk(chunk, dk), by_chunk(chunk, chunk),
                  by_chunk(chunk, chunk), by_chunk(chunk, dk),
                  by_chunk(chunk, chunk), by_chunk(dv, dk),
                  by_chunk(chunk, dk), by_chunk(chunk, dv),
                  by_chunk(chunk, dv), in_place(dv)],
        out_specs=[in_place(dk), in_place(dk), in_place(dv), in_place(dk),
                   column, by_chunk(chunk, SUB), by_chunk(chunk, SUB)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, f32),
                   jax.ShapeDtypeStruct(k.shape, f32),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(k.shape, f32),
                   jax.ShapeDtypeStruct(beta.shape, f32),
                   *_chunk_shapes(b, h, n, (chunk, SUB), (chunk, SUB))],
        scratch_shapes=[pltpu.VMEM((dv, dk), f32)],
        compiler_params=_WALK, interpret=interpret, name=KERNEL_BWD,
    )(q, k, v, beta, running, solve, p, local, kk, *kept, dout)


def _chunk_matrices(name, interpret, chunk, q, k, g, beta, inside):
    """``G``, the sums inside the sub-chunks, kk, ``P`` and ``T`` of every
    chunk: the pairs kernel, then XLA's triangular solve."""
    running, local, kk, p = _pair_matrices(
        name, interpret, chunk, beta.shape[1], q, k, g, *inside)
    return running, local, kk, p, _inverse(
        beta.reshape(*kk.shape[:3], chunk, 1) * kk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernel_form(q, k, v, g, beta, chunk, interpret):
    b, t, h, _ = q.shape
    q_, k_, v_, g_, beta_ = _where_they_lie(q, k, v, g, beta, chunk)
    running, _, _, p, solve = _chunk_matrices(
        KERNEL_FWD, interpret, chunk, q_, k_, g_, beta_,
        _inside_sub_chunks(q, k, g, chunk))
    (out,) = _walk(KERNEL_FWD, False, interpret, chunk, q_, k_, v_, beta_,
                   running, solve, p)
    return out.reshape(b, -1, h, v.shape[-1])[:, :t]


def _kernel_form_fwd(q, k, v, g, beta, chunk, interpret):
    return _kernel_form(q, k, v, g, beta, chunk, interpret), (q, k, v, g, beta)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _kernel_form_bwd(chunk, interpret, inputs, dout):
    """The five inputs are the residuals: the pair matrices and the solve
    are formed again, a walk keeps the states, and the backward kernel
    gives every cotangent but what goes through the pairs inside the
    sub-chunks, which XLA takes through `_inside_sub_chunks`. All three
    calls are named as the backward pass's."""
    # As `jax.checkpoint` does: nothing is formed again before the cotangent
    # is there, or the scheduler may hold three layers' states at once.
    (q, k, v, g, beta), dout = jax.lax.optimization_barrier((inputs, dout))
    b, t = q.shape[:2]
    inside, inside_vjp = jax.vjp(   # float32 in: one rounding, at the end
        functools.partial(_inside_sub_chunks, chunk=chunk),
        q.astype(jnp.float32), k.astype(jnp.float32), g.astype(jnp.float32))
    q_, k_, v_, g_, beta_ = _where_they_lie(q, k, v, g, beta, chunk)
    running, local, kk, p, solve = _chunk_matrices(
        KERNEL_BWD, interpret, chunk, q_, k_, g_, beta_, inside)
    kept = _walk(KERNEL_BWD, True, interpret, chunk, q_, k_, v_, beta_,
                 running, solve, p)
    dout = _padded(dout, v_.shape[1]).reshape(v_.shape)
    dq, dk, dv, dg, dbeta, *dinside = _walk_back(
        interpret, chunk, q_, k_, v_, beta_, running, solve, p, local, kk,
        kept, dout)
    dq_inside, dk_inside, dg_inside = inside_vjp(tuple(dinside))

    def whole(x, like, inside=0.0):
        return (x.reshape(b, -1, *like.shape[2:])[:, :t] + inside).astype(
            like.dtype)

    return (whole(dq, q, dq_inside), whole(dk, k, dk_inside), whole(dv, v),
            whole(dg, g, dg_inside),
            whole(jnp.moveaxis(dbeta[..., 0], 1, 2), beta))


_kernel_form.defvjp(_kernel_form_fwd, _kernel_form_bwd)
_kernel_form_jit = jax.jit(_kernel_form, static_argnums=(5, 6))


def takes_kernels(dk: int, dv: int, chunk: int) -> bool:
    """Whether the chip's tiling takes the shapes: a head's channels whole
    lanes, a chunk whole sub-chunks (and so whole sublanes)."""
    return dk % 128 == 0 and dv % 128 == 0 and chunk % SUB == 0


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
    from horovod_tpu import obs

    kernels = takes_kernels(q.shape[-1], v.shape[-1], chunk)
    obs.gauge("hvt_kda_scan", float(kernels), impl="pallas")
    obs.gauge("hvt_kda_scan", float(not kernels), impl="xla")
    if kernels:
        return _kernel_form_jit(q, k, v, g, beta, chunk,
                                flash_attention.default_interpret())
    return _xla_form(q, k, v, g, beta, chunk)
