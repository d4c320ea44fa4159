"""`ops/delta_rule.gated_delta_rule`, the chunked gated delta rule with
per-channel decay, against the recurrence it computes, run token by token:
forward and all five gradients, chunks that do and do not divide the
sequence, a sequence shorter than a chunk, decays that would overflow
float32 if a ratio were ever formed as exp(-G), and what it refuses. Both
forms: the XLA one at toy widths, and at heads of 128 the Mosaic kernels
with their hand-written backward, in the Pallas interpreter, against the
recurrence and against the XLA form's autodiff."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import delta_rule


def token_by_token(q, k, v, g, beta):
    """``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t
    v_t^T``, ``o_t = S_t^T q_t``, one position at a time."""
    b, _, h, dk = q.shape

    def step(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("bhc,bhcv->bhv", k_t, state, precision="highest")
        state = state + (beta_t[..., None] * k_t)[..., None] * (
            v_t - seen)[..., None, :]
        return state, jnp.einsum(
            "bhc,bhcv->bhv", q_t, state, precision="highest")

    _, out = jax.lax.scan(
        step, jnp.zeros((b, h, dk, v.shape[-1])),
        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)


def inputs(seed, t, *, b=2, h=3, dk=16, dv=8, plunge=False):
    """Unit keys, queries at Dk^-1/2, decays of -softplus x a rate a head
    up to e^2.7 = 15, beta in (0, 2). ``plunge``: every fifth position
    takes 150 more off every third channel, so that the running sum inside
    one chunk of 64 passes -1,900 and exp(+1,900) is far past float32."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (b, t, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = jax.random.normal(keys[1], (b, t, h, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (b, t, h, dv))
    rate = jnp.exp(jnp.linspace(0.0, 2.7, h))[:, None]
    g = -jax.nn.softplus(jax.random.normal(keys[3], (b, t, h, dk))) * rate
    if plunge:
        g = g.at[:, ::5, :, ::3].add(-150.0)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, h)))
    return q, k, v, g, beta


WIDE = dict(b=1, h=2, dk=128, dv=128)   # what the kernels take
WIDTHS = pytest.mark.parametrize("width", [{}, WIDE], ids=["toy", "wide"])


def xla_form(chunk):
    return lambda *args: delta_rule._xla_form(*args, chunk)


def both_with_gradients(args, chunk, oracle=None):
    oracle = oracle or token_by_token
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def run(fn):
        return jax.value_and_grad(
            lambda *a: (fn(*a) * weight).sum(), argnums=(0, 1, 2, 3, 4),
            has_aux=False)(*args)

    want = oracle(*args)
    got = delta_rule.gated_delta_rule(*args, chunk=chunk)
    (_, want_grads), (_, got_grads) = (
        run(oracle),
        run(lambda *a: delta_rule.gated_delta_rule(*a, chunk=chunk)))
    return want, got, want_grads, got_grads


@pytest.mark.parametrize("t,chunk,width,oracle", [
    (64, 64, {}, None), (64, 16, {}, None), (64, 32, {}, None),
    (64, 48, {}, None), (64, 8, {}, None), (128, 64, {}, None),
    (100, 32, {}, None), (40, 64, {}, None),
    (128, 64, WIDE, None), (64, 16, WIDE, None), (112, 48, WIDE, None),
    (100, 32, WIDE, None), (40, 64, WIDE, None),
    (192, 64, WIDE, xla_form), (100, 32, WIDE, xla_form),
    (40, 64, WIDE, xla_form)],
    ids=["one_chunk", "sub_chunk", "halves", "48_does_not_divide",
         "under_a_sub_chunk", "two_chunks", "100_over_32", "shorter_than_one",
         "kernels_two_chunks", "kernels_sub_chunk", "kernels_48_does_not_divide",
         "kernels_100_over_32", "kernels_shorter_than_one",
         "kernels_as_xla_three_chunks", "kernels_as_xla_100_over_32",
         "kernels_as_xla_shorter_than_one"])
def test_chunked_form_is_the_recurrence(t, chunk, width, oracle):
    assert delta_rule.takes_kernels(
        width.get("dk", 16), width.get("dv", 8), chunk) == bool(width)
    want, got, want_grads, got_grads = both_with_gradients(
        inputs(t + chunk, t, **width), chunk, oracle and oracle(chunk))
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-5)
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        np.testing.assert_allclose(
            a, b, atol=2e-5 * float(jnp.abs(b).max()), rtol=2e-4,
            err_msg=name)


@WIDTHS
@pytest.mark.parametrize("t", [64, 128])
def test_fast_decays_are_finite_and_right(t, width):
    """The running log-decay passes -1,900 inside a chunk: a ratio formed
    as exp(G_t) * exp(-G_s) would be 0 * inf."""
    args = inputs(t, t, plunge=True, **width)
    running = jnp.cumsum(args[3][:, :64], axis=1)
    assert float(running.min()) < -1900
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(-np.float32(running.min())))
    want, got, want_grads, got_grads = both_with_gradients(args, 64)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        assert np.isfinite(a).all(), name
        np.testing.assert_allclose(
            a, b, atol=2e-4 * float(jnp.abs(b).max()), rtol=2e-3,
            err_msg=name)


@WIDTHS
def test_compute_dtype_in_float32_state_inside(width):
    """bfloat16 q, k, v come back as bfloat16, near the float32 result of
    the same rounded inputs: the sums, the decays, the solve and the state
    are float32 whatever comes in; the gradients come back in the inputs'
    dtypes."""
    q, k, v, g, beta = inputs(3, 128, **width)
    low = tuple(a.astype(jnp.bfloat16) for a in (q, k, v))
    grads = jax.grad(
        lambda *a: delta_rule.gated_delta_rule(*a, chunk=64).astype(
            jnp.float32).sum(), argnums=(0, 1, 2, 3, 4))(*low, g, beta)
    assert [a.dtype for a in grads] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    assert all(np.isfinite(a.astype(jnp.float32)).all() for a in grads)
    got = delta_rule.gated_delta_rule(*low, g, beta, chunk=64)
    assert got.dtype == jnp.bfloat16
    want = token_by_token(*(a.astype(jnp.float32) for a in low), g, beta)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want, atol=0.01 * float(jnp.abs(want).max()),
        rtol=0.02)


@WIDTHS
def test_one_traced_copy_for_a_models_identical_calls(width):
    """The implementation is jitted, so three layers' calls at one shape
    are one `pjit` of one jaxpr in the program that holds them."""
    args = inputs(0, 64, **width)

    def three(*a):
        return sum(delta_rule.gated_delta_rule(*a, chunk=32) for _ in range(3))

    calls = [eqn for eqn in jax.make_jaxpr(three)(*args).eqns
             if eqn.primitive.name in ("pjit", "jit")]
    assert len(calls) == 3
    assert len({id(eqn.params["jaxpr"]) for eqn in calls}) == 1


def all_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs it calls."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from all_eqns(sub)


@pytest.mark.parametrize("dk,dv,chunk,impl", [
    (128, 128, 64, "pallas"), (256, 128, 16, "pallas"), (16, 8, 64, "xla"),
    (128, 64, 64, "xla"), (64, 128, 64, "xla"), (128, 128, 8, "xla")],
    ids=["the_cells", "wider_keys", "toy", "narrow_values", "narrow_keys",
         "chunk_under_a_sub_chunk"])
def test_which_form_a_shape_takes(dk, dv, chunk, impl):
    """The kernels where a head's channels are whole lanes and the chunk
    whole sub-chunks, the XLA form elsewhere: told from the shapes alone,
    left in the gauge `hvt_kda_scan{impl}`, and in the program: Mosaic
    calls under the two names and no loop over the chunks, or one scan."""
    from horovod_tpu.obs import prom

    args = inputs(0, 3 * chunk, b=1, h=1, dk=dk, dv=dv)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: delta_rule.gated_delta_rule(*a, chunk=chunk).sum(),
        argnums=(0, 1, 2, 3, 4)))(*args)
    other = {"pallas": "xla", "xla": "pallas"}[impl]
    gauges = prom.render()
    assert f'hvt_kda_scan{{impl="{impl}"}} 1' in gauges
    assert f'hvt_kda_scan{{impl="{other}"}} 0' in gauges
    eqns = list(all_eqns(jaxpr.jaxpr))
    names = sorted(str(eqn.params["name"]) for eqn in eqns
                   if eqn.primitive.name == "pallas_call")
    over_chunks = [eqn for eqn in eqns if eqn.primitive.name == "scan"
                   and eqn.params["length"] == 3]   # one head: its own are 1
    if impl == "pallas":
        assert names == [delta_rule.KERNEL_BWD] * 3 + [
            delta_rule.KERNEL_FWD] * 2   # a pass: the pairs, then the walk(s)
        assert over_chunks == []
    else:
        assert names == [] and len(over_chunks) >= 2   # each way


def test_chunks_counted():
    assert delta_rule.n_chunks(8192, 64) == 128
    assert delta_rule.n_chunks(100, 32) == 4
    assert delta_rule.n_chunks(40, 64) == 1


@pytest.mark.parametrize("change,says", [
    (dict(chunk=24), "multiple of the sub-chunk"),
    (dict(chunk=0), "multiple of the sub-chunk"),
    (dict(k_heads=2), "differ in shape"),
    (dict(beta_dims=4), "do not go with"),
], ids=["chunk_24", "chunk_0", "k_of_other_heads", "beta_per_channel"])
def test_refusals_by_name(change, says):
    q, k, v, g, beta = inputs(0, 32)
    if "k_heads" in change:
        k = k[:, :, :change["k_heads"]]
    if "beta_dims" in change:
        beta = g
    with pytest.raises(ValueError, match=says):
        delta_rule.gated_delta_rule(
            q, k, v, g, beta, chunk=change.get("chunk", 32))
