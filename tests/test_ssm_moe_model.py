"""`HybridMoELM`'s state-space kind and what came with it, as the program
offers them (the comparison with the plain reference is
tests/chipbench/test_ssm_moe_lm.py): the mixer's small functions by hand,
the eight head shares of a Mamba-2 layer adding up under ``heads_axis``,
softmax gates, the tied head's gradient, `remat`, the model through
`Trainer.fit`, what it names in the compiled program and on `/metrics`, and
what it refuses."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvt
from horovod_tpu.models import decoding
from horovod_tpu.models import hybrid_moe_lm as hybrid
from horovod_tpu.models import moe
from horovod_tpu.models.hybrid_moe_lm import (
    SOFTMAX, SSM, GatedAttention, HybridMoELM, StateSpaceMixer,
    StateSpaceSizes)
from horovod_tpu.models.pipelined_lm import PipelinedLM
from horovod_tpu.models.transformer import LMHead, ShardingConfig
from horovod_tpu.obs import prom

STATE_SPACE = StateSpaceSizes(
    n_heads=8, n_held_heads=2, held_heads_start=2, head_dim=8, state_dim=16,
    conv_size=4, chunk=16)
SIZES = dict(
    vocab_size=96, d_model=64, layer_kinds=(SSM, SOFTMAX, SSM), head_dim=16,
    linear_heads=0, conv_size=0, low_rank=0, kda_chunk=0, softmax_heads=4,
    softmax_kv_heads=2, n_held_heads=2, held_heads_start=2, n_routed=16,
    experts_per_token=3, expert_width=32, shared_width=32,
    routed_scaling=1.0, n_held=4, held_start=4, eps=1e-5,
    compute_dtype=jnp.float32, fused_head_chunks=2, ssm=STATE_SPACE,
    softmax_gate=False, softmax_scale=1 / 16, moe_scoring="softmax",
    residual_multiplier=0.22, embedding_multiplier=12.0, logits_divisor=16.0,
    tied_head=True)


def tokens(batch=2, seq=40, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).integers(1, 96, (batch, seq)), jnp.int32)


def loss_of(model, x):
    def loss(params):
        return model.apply({"params": params}, x, labels=x,
                           mutable=["metrics"])[0][0].mean()
    return loss


# --- the mixer's small functions -----------------------------------------------

def test_step_split_and_gated_norm_by_hand():
    assert float(hybrid.time_step(jnp.asarray(0.0, jnp.bfloat16),
                                  jnp.asarray(0.0))) == pytest.approx(
                                      np.log(2.0))
    b, c = hybrid.split_b_c(jnp.arange(6.0).reshape(1, 1, 6))
    np.testing.assert_array_equal(b[0, 0], [0, 1, 2])
    np.testing.assert_array_equal(c[0, 0], [3, 4, 5])
    # one position, two heads of two channels: the gate first, then ONE
    # mean square over all four channels
    y = jnp.asarray([[[[1.0, 2.0], [3.0, 4.0]]]])
    z = jnp.asarray([[[[0.0, 100.0], [100.0, -100.0]]]])  # SiLU: 0, 100, ...
    got = hybrid.gated_norm(y, z, jnp.ones((2, 2)), 0.0, heads_axis=None,
                            n_channels=4)
    gated = np.asarray([0.0, 200.0, 300.0, 0.0])
    np.testing.assert_allclose(
        got.reshape(-1), gated / np.sqrt((gated ** 2).mean()), rtol=1e-5)


def test_the_short_convolution_takes_any_trailing_shape():
    x = jnp.arange(1.0, 7.0).reshape(1, 6, 1)
    taps = jnp.asarray([1000.0, 100.0, 10.0, 1.0]).reshape(4, 1)
    np.testing.assert_array_equal(
        hybrid.short_conv(x, taps)[0, :, 0], [1, 12, 123, 1234, 2345, 3456])


def test_a_held_layer_carries_its_own_heads_parameters_only():
    x = jnp.ones((1, 32, 64))
    layer = StateSpaceMixer(STATE_SPACE, 1e-5, jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    shapes = jax.tree.map(lambda a: a.shape, params)
    assert shapes == {
        "z_proj": {"kernel": (64, 2, 8)}, "x_proj": {"kernel": (64, 2, 8)},
        "bc_proj": {"kernel": (64, 32)},      # one group: held whole
        "dt_proj": {"kernel": (64, 2)}, "A_log": (2,), "dt_bias": (2,),
        "D": (2,), "x_conv": (4, 2, 8), "x_conv_bias": (2, 8),
        "bc_conv": (4, 32), "bc_conv_bias": (32,), "norm": (2, 8),
        "o_proj": (2, 8, 64)}
    rate = np.exp(params["A_log"])
    assert ((1 <= rate) & (rate < 16)).all()
    step = np.asarray(jax.nn.softplus(params["dt_bias"]))
    assert ((1e-3 <= step * 1.001) & (step <= 1e-1 * 1.001)).all()
    np.testing.assert_array_equal(params["D"], 1.0)
    assert float(jnp.abs(params["x_conv_bias"]).max()) <= 0.5
    softmax = GatedAttention(4, 2, 2, 2, 16, jnp.float32, gate=False)
    assert set(softmax.init(jax.random.PRNGKey(0), x)["params"]) == {
        "q_proj", "k_proj", "v_proj", "o_proj"}


def test_the_eight_head_shares_of_a_mamba_layer_add_up_to_the_uncut_layer():
    """Heads 0..15 of a 16-head layer, two a share: under ``heads_axis``
    (a `vmap` axis the shares are stacked on, with the `psum` of the gated
    norm's sum of squares) each share returns its heads' rows of W_o times
    their outputs and the eight add up to the whole layer's output.
    Without the axis a share's norm is over its own channels and they do
    not."""
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 40, 64)),
                    jnp.float32)

    def layer(held, start, axis=None):
        return StateSpaceMixer(StateSpaceSizes(
            16, held, start, 8, 16, 4, 16, heads_axis=axis), 1e-5,
            jnp.float32)

    params = layer(16, 0).init(jax.random.PRNGKey(1), x)["params"]
    want = layer(16, 0).apply({"params": params}, x)
    shared = ("bc_proj", "bc_conv", "bc_conv_bias")
    axes = dict(z_proj=1, x_proj=1, dt_proj=1, A_log=0, dt_bias=0, D=0,
                x_conv=1, x_conv_bias=0, norm=0, o_proj=0)

    def share(start):
        def cut(path, leaf):
            name = next(k for k in reversed([p.key for p in path])
                        if k != "kernel")
            if name in shared:
                return leaf
            return jax.lax.slice_in_dim(leaf, start, start + 2,
                                        axis=axes[name])
        return jax.tree_util.tree_map_with_path(cut, params)

    stacked = jax.tree.map(
        lambda *leaves: jnp.stack(leaves), *(share(s) for s in range(0, 16, 2)))
    # (every share starts at 0 of its own arrays: a share is told its
    # place, its arrays do not change with it)
    parts = jax.vmap(
        lambda p: layer(2, 0, "heads").apply({"params": p}, x),
        axis_name="heads")(stacked)
    assert float(jnp.abs(parts[0] - parts[1]).mean()) > 1e-3
    np.testing.assert_allclose(parts.sum(0), want, atol=2e-5)
    alone = sum(layer(2, s).apply({"params": share(s)}, x)
                for s in range(0, 16, 2))
    assert float(jnp.abs(alone - want).mean()) > 1e-3


# --- the routed layer's gate ---------------------------------------------------

def test_softmax_gates_add_up_to_one_and_follow_the_chosen_logits():
    logits = jnp.asarray(np.random.default_rng(1).standard_normal((2, 5, 9)),
                         jnp.float32) * 3
    _, chosen = jax.lax.top_k(logits, 4)
    gates = moe._gates(logits, chosen, scoring="softmax", scale=1.0)
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-6)
    want = jax.nn.softmax(jnp.take_along_axis(logits, chosen, -1), axis=-1)
    np.testing.assert_allclose(gates, want, rtol=1e-5)
    # ... and the sigmoid kind is what it was
    sigmoid = moe._gates(logits, chosen, scoring="sigmoid", scale=2.0)
    picked = jax.nn.sigmoid(jnp.take_along_axis(logits, chosen, -1))
    np.testing.assert_allclose(
        sigmoid, 2.0 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    # far-apart logits: the largest chosen one is taken off first
    far = moe._gates(logits * 1e3, chosen, scoring="softmax", scale=1.0)
    assert bool(jnp.isfinite(far).all())
    with pytest.raises(ValueError, match="scoring 'tanh' is neither"):
        moe.RoutedExperts(
            n_routed=4, k=2, expert_width=8, shared_width=8, n_held=4,
            held_start=0, routed_scaling=1.0, scoring="tanh").init(
                jax.random.PRNGKey(0), jnp.ones((1, 8, 16)))


# --- the tied head ---------------------------------------------------------------

def test_the_tied_tables_gradient_is_the_lookups_plus_the_heads():
    """The same model with the head untied and its kernel set to the
    table's transpose gives the same losses; the tied table's gradient is
    that model's embedding gradient plus its head gradient, transposed."""
    x = tokens()
    tied = HybridMoELM(**SIZES)
    untied = HybridMoELM(**SIZES | dict(tied_head=False))
    params = tied.init(jax.random.PRNGKey(0), x)["params"]
    assert "lm_head" not in params
    both = dict(params, lm_head={"kernel": params["embed"]["embedding"].T})
    assert jax.tree.map(jnp.shape, untied.init(
        jax.random.PRNGKey(0), x)["params"]) == jax.tree.map(jnp.shape, both)
    np.testing.assert_allclose(
        tied.apply({"params": params}, x, labels=x, mutable=["metrics"])[0][0],
        untied.apply({"params": both}, x, labels=x, mutable=["metrics"])[0][0],
        atol=1e-6)
    got = jax.grad(loss_of(tied, x))(params)
    parts = jax.grad(loss_of(untied, x))(both)
    np.testing.assert_allclose(
        got["embed"]["embedding"],
        parts["embed"]["embedding"] + parts["lm_head"]["kernel"].T, atol=1e-6)
    assert float(jnp.abs(parts["lm_head"]["kernel"]).max()) > 1e-4
    # the logits path reads the same table
    np.testing.assert_allclose(
        tied.apply({"params": params}, x, mutable=["metrics"])[0],
        untied.apply({"params": both}, x, mutable=["metrics"])[0], atol=1e-5)


def test_a_head_is_handed_a_table_exactly_when_it_is_tied():
    h = jnp.ones((1, 4, 8))
    table = jnp.ones((16, 8))
    with pytest.raises(ValueError, match="a tied head is handed"):
        LMHead(8, 16, tied=True).init(jax.random.PRNGKey(0), h)
    own = LMHead(8, 16)
    params = own.init(jax.random.PRNGKey(0), h)
    with pytest.raises(ValueError, match="an untied one none"):
        own.apply(params, h, table=table)
    assert LMHead(8, 16, tied=True).init(
        jax.random.PRNGKey(0), h, table=table) == {}


# --- the stack ---------------------------------------------------------------------

def test_remat_changes_no_loss_and_no_gradient():
    x = tokens()
    plain, remat = HybridMoELM(**SIZES), HybridMoELM(**SIZES, remat=True)
    variables = plain.init(jax.random.PRNGKey(0), x, labels=x)
    assert jax.tree.map(jnp.shape, variables) == jax.tree.map(
        jnp.shape, remat.init(jax.random.PRNGKey(0), x, labels=x))
    params = variables["params"]
    (loss, _), sown = plain.apply(
        {"params": params}, x, labels=x, mutable=["metrics"])
    (again, _), sown_again = remat.apply(
        {"params": params}, x, labels=x, mutable=["metrics"])
    np.testing.assert_array_equal(loss, again)
    # the routed layers' sown metrics come through the rematerialised block
    assert jax.tree.map(float, sown) == jax.tree.map(float, sown_again)
    assert set(sown["metrics"]["Block_1"]["mlp"]) == {
        "moe_overflow_rows", "moe_held_rows_share", "moe_load_max_over_mean"}
    got = jax.grad(loss_of(remat, x))(params)
    want = jax.grad(loss_of(plain, x))(params)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, atol=1e-7)
    remat.apply({"params": params}, x, mutable=["metrics"])
    assert "hvt_remat_blocks 3" in prom.render()
    plain.apply({"params": params}, x, mutable=["metrics"])
    assert "hvt_remat_blocks 0" in prom.render()


def test_the_multipliers_are_data_and_one_leaves_the_stack_as_it_was():
    x = tokens()
    model = HybridMoELM(**SIZES)
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    base = model.apply({"params": params}, x, mutable=["metrics"])[0]
    for change in (dict(residual_multiplier=1.0),
                   dict(embedding_multiplier=1.0), dict(softmax_scale=None)):
        other = HybridMoELM(**SIZES | change).apply(
            {"params": params}, x, mutable=["metrics"])[0]
        assert float(jnp.abs(other - base).mean()) > 1e-3, change
    halved = HybridMoELM(**SIZES | dict(logits_divisor=32.0)).apply(
        {"params": params}, x, mutable=["metrics"])[0]
    np.testing.assert_allclose(2 * halved, base, atol=1e-5)
    assert hybrid.residual(1.0, 2.0, 1) == 3.0


def test_kinds_gauges_and_scopes_of_the_state_space_layer():
    """Every part of the Mamba-2 layer carries its scope in the lowered
    step's op names, in the forward pass, in the rematerialised forward and
    in the backward pass; the gauges say what was built."""
    x = tokens()
    model = HybridMoELM(**SIZES, remat=True)
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    assert "A_log" in params["Block_0"]["mixer"]
    assert set(params["Block_1"]["mixer"]) == {
        "q_proj", "k_proj", "v_proj", "o_proj"}
    text = jax.jit(jax.grad(loss_of(model, x))).lower(params).as_text(
        debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    for scope in (hybrid.SSM_PROJ, hybrid.SSM_CONV, hybrid.SSM_SCAN,
                  hybrid.SSM_OUT):
        forward = [n for n in names if scope in n and "transpose" not in n]
        again = [n for n in names if scope in n and "rematted_computation" in n]
        backward = [n for n in names if scope in n and "transpose(" in n
                    and "rematted_computation" not in n]
        assert forward and again and backward, scope
    assert any("Block_0/mixer" in n and hybrid.SSM_SCAN in n for n in names)
    assert any("Block_1/mixer" in n and hybrid.GQA_SCOPE in n for n in names)
    gauges = prom.render()
    assert 'hvt_layer_kinds{kind="ssm"} 2' in gauges
    assert 'hvt_layer_kinds{kind="softmax"} 1' in gauges
    assert 'hvt_layer_kinds{kind="linear"} 0' in gauges
    assert 'hvt_held_heads{mixer="ssm"} 2' in gauges
    assert "hvt_ssd_chunks 3" in gauges  # 40 positions in chunks of 16
    assert 'hvt_ssd_scan{impl="xla"} 1' in gauges
    assert 'hvt_moe_gate{scoring="softmax"} 1' in gauges
    assert "hvt_tied_head 1" in gauges


def test_trainer_fit_with_the_module_loss_logs_the_sown_metrics():
    model = HybridMoELM(**SIZES, remat=True)
    trainer = hvt.Trainer(
        model, hvt.DistributedOptimizer(optax.adamw(1e-3)), loss="module",
        mesh=hvt.build_mesh(hvt.MeshSpec(data=1), devices=jax.devices()[:1]))
    x = np.asarray(tokens(8, 40))
    seen = []

    class Logs(hvt.callbacks.Callback):
        def on_batch_end(self, batch, logs=None):
            seen.append({k: float(v) for k, v in logs.items()})

    trainer.fit(x=x, y=np.roll(x, -1, axis=1), batch_size=2, epochs=2,
                steps_per_epoch=4, verbose=0, callbacks=[Logs()])
    assert seen[-1]["loss"] < seen[0]["loss"]
    assert all(log["moe_overflow_rows"] == 0 for log in seen)
    assert {"moe_held_rows_share", "moe_load_max_over_mean"} <= set(seen[0])


# --- refusals ------------------------------------------------------------------------

def test_no_decode_path_by_name():
    with pytest.raises(NotImplementedError, match="HybridMoELM") as err:
        decoding.require_decode_path(HybridMoELM(**SIZES))
    assert "StateSpaceMixer" in str(err.value)
    assert "convolution's tail" in str(err.value)


def test_the_pipeline_refuses_the_mixer_by_name():
    with pytest.raises(ValueError, match="StateSpaceMixer"):
        PipelinedLM(vocab_size=96, d_model=64, n_layers=2, n_heads=4,
                    mlp="ssm").init(jax.random.PRNGKey(0), tokens())


def test_more_than_one_chip_is_refused_by_name():
    mesh = hvt.build_mesh(hvt.MeshSpec(data=2), devices=jax.devices()[:2])
    model = HybridMoELM(**SIZES | dict(sharding=ShardingConfig(mesh=mesh)))
    with pytest.raises(NotImplementedError, match="HybridMoELM on a mesh"):
        model.init(jax.random.PRNGKey(0), tokens())


@pytest.mark.parametrize("change,says", [
    (dict(ssm=None), "`ssm`, the kind's `StateSpaceSizes`, is not given"),
    (dict(layer_kinds=(SSM, "local")), "or 'softmax' or 'ssm'"),
    (dict(ssm=StateSpaceSizes(8, 2, 7, 8, 16, 4, 16)),
     "StateSpaceMixer: heads 7..9 are not a block of its 8"),
    (dict(moe_scoring="tanh"), "scoring 'tanh' is neither"),
], ids=["no_sizes", "unknown_kind", "heads_past_the_end", "unknown_scoring"])
def test_what_cannot_be_built_is_refused_by_name(change, says):
    with pytest.raises(ValueError, match=says):
        HybridMoELM(**SIZES | change).init(jax.random.PRNGKey(0), tokens())
