"""`HybridMoELM` and its mixers as the program offers them (no benchmark
file is read here; the comparison with the plain reference is
tests/chipbench/test_hybrid_moe_lm.py): the short convolution and the
mixers' small functions by hand, the stack's kinds as data, the model
through `Trainer.fit`, what it names in the compiled program and on
`/metrics`, and what it refuses."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvt
from horovod_tpu.models import decoding
from horovod_tpu.models import hybrid_moe_lm as hybrid
from horovod_tpu.models.beam import make_beam_search_fn
from horovod_tpu.models.hybrid_moe_lm import (
    LINEAR, SOFTMAX, WINDOW, AttentionSizes, DeltaAttention, GatedAttention,
    HybridMoELM)
from horovod_tpu.models.pipelined_lm import PipelinedLM
from horovod_tpu.models.speculative import make_speculative_fn
from horovod_tpu.models.transformer import ShardingConfig
from horovod_tpu.obs import prom

SIZES = dict(
    vocab_size=96, d_model=64, layer_kinds=(SOFTMAX, LINEAR, LINEAR, LINEAR),
    head_dim=16, linear_heads=4, softmax_heads=4, softmax_kv_heads=2,
    n_held_heads=2, held_heads_start=2, conv_size=4, low_rank=8, kda_chunk=32,
    n_routed=16, experts_per_token=3, expert_width=32, shared_width=32,
    routed_scaling=1.0, n_held=4, held_start=4, eps=1e-5,
    compute_dtype=jnp.float32, fused_head_chunks=2)


def tokens(batch=2, seq=64, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).integers(1, 96, (batch, seq)), jnp.int32)


# --- the mixers' small functions ---------------------------------------------

def test_short_conv_is_causal_with_the_last_tap_on_the_present():
    """y_t = sum_j taps[j] x_{t-3+j} for one channel, by hand."""
    x = jnp.arange(1.0, 7.0).reshape(1, 6, 1, 1)
    taps = jnp.asarray([1000.0, 100.0, 10.0, 1.0]).reshape(4, 1, 1)
    got = hybrid.short_conv(x, taps)[0, :, 0, 0]
    np.testing.assert_array_equal(got, [1, 12, 123, 1234, 2345, 3456])


def test_decay_strength_and_norm_by_hand():
    g = hybrid.log_decay(jnp.log(jnp.asarray([2.0])), jnp.zeros((1, 3)),
                         jnp.zeros((1, 1, 1, 3)))
    np.testing.assert_allclose(g, -2.0 * np.log(2.0) * np.ones((1, 1, 1, 3)),
                               rtol=1e-6)
    assert float(hybrid.write_strength(jnp.asarray(0.0))) == 1.0
    assert float(hybrid.write_strength(jnp.asarray(30.0))) == pytest.approx(2)
    unit = hybrid.l2_normalised(jnp.asarray([[3.0, 4.0]], jnp.bfloat16))
    assert unit.dtype == jnp.float32
    np.testing.assert_allclose(unit, [[0.6, 0.8]], rtol=1e-6)


def test_a_held_layer_carries_its_own_heads_parameters_only():
    x = jnp.ones((1, 32, 64))
    linear = DeltaAttention(4, 2, 2, 16, 4, 8, 1e-5, 32, jnp.float32)
    shapes = jax.tree.map(
        lambda a: a.shape, linear.init(jax.random.PRNGKey(0), x)["params"])
    assert shapes["q_proj"]["kernel"] == (64, 2, 16)
    assert shapes["o_proj"] == (2, 16, 64) and shapes["A_log"] == (2,)
    assert shapes["f_a"]["kernel"] == (64, 8)         # shared by all heads
    assert shapes["f_b"]["kernel"] == (8, 2, 16)
    assert shapes["q_conv"] == (4, 2, 16) and shapes["dt_bias"] == (2, 16)
    assert shapes["o_norm"]["scale"] == (16,)
    softmax = GatedAttention(4, 2, 2, 2, 16, jnp.float32)
    shapes = jax.tree.map(
        lambda a: a.shape, softmax.init(jax.random.PRNGKey(0), x)["params"])
    assert shapes["q_proj"]["kernel"] == (64, 2, 16)
    assert shapes["k_proj"]["kernel"] == (64, 1, 16)  # the one they read
    assert shapes["g_proj"]["kernel"] == (64, 2, 16)
    assert set(shapes) == {"q_proj", "k_proj", "v_proj", "g_proj", "o_proj"}


def test_the_decay_starts_where_kimi_linears_does():
    params = DeltaAttention(4, 4, 0, 16, 4, 8, 1e-5, 32, jnp.float32).init(
        jax.random.PRNGKey(0), jnp.ones((1, 32, 64)))["params"]
    rate = np.exp(params["A_log"])
    assert ((1 <= rate) & (rate < 16)).all()
    step = np.asarray(jax.nn.softplus(params["dt_bias"]))
    assert ((1e-3 <= step * 1.001) & (step <= 1e-1 * 1.001)).all()


# --- the stack ----------------------------------------------------------------

def test_layer_kinds_are_data():
    """Any order builds, each block adopts the mixer it is given, and the
    trace-time gauges say what was built."""
    x = tokens()
    model = HybridMoELM(**SIZES | dict(
        layer_kinds=(LINEAR, SOFTMAX, SOFTMAX)))
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    assert sorted(p for p in params if p.startswith("Block_")) == [
        "Block_0", "Block_1", "Block_2"]
    assert "A_log" in params["Block_0"]["mixer"]
    assert "g_proj" in params["Block_1"]["mixer"]
    assert "g_proj" in params["Block_2"]["mixer"]
    text = prom.render()
    assert 'hvt_layer_kinds{kind="linear"} 1' in text
    assert 'hvt_layer_kinds{kind="softmax"} 2' in text
    assert 'hvt_held_heads{mixer="linear"} 2' in text
    assert 'hvt_held_heads{mixer="softmax"} 2' in text
    assert "hvt_kda_chunks 2" in text
    assert model.apply({"params": params}, x).shape == (2, 64, 96)


def test_each_softmax_kind_builds_its_own_sizes_and_dense_layers_lead():
    """A window kind of 6 heads over a full kind of 4, both over 2 K/V
    heads, and a dense SwiGLU in the first layer: the shapes say which is
    which, and the stack is the parent's where the new fields are left
    at their defaults (tests/chipbench/test_window_moe_lm.py holds the
    arithmetic to the reference)."""
    from horovod_tpu.models.transformer import Rotary

    x = tokens()
    model = HybridMoELM(**SIZES | dict(
        layer_kinds=(SOFTMAX, WINDOW, LINEAR),
        softmax=AttentionSizes(4, 2, 4, 0, rotary=Rotary(8, 500000.0)),
        window=AttentionSizes(6, 2, 6, 0, window=16,
                              rotary=Rotary(16, 10000.0)),
        n_dense_layers=1, dense_width=40))
    shapes = jax.tree.map(
        lambda a: a.shape, model.init(jax.random.PRNGKey(0), x)["params"])
    assert shapes["Block_0"]["mixer"]["q_proj"]["kernel"] == (64, 4, 16)
    assert shapes["Block_1"]["mixer"]["q_proj"]["kernel"] == (64, 6, 16)
    assert shapes["Block_1"]["mixer"]["k_proj"]["kernel"] == (64, 2, 16)
    assert shapes["Block_0"]["mlp"] == {
        "gate": {"kernel": (64, 40)}, "up": {"kernel": (64, 40)},
        "down": {"kernel": (40, 64)}}
    assert "router" in shapes["Block_1"]["mlp"]
    text = prom.render()
    assert 'hvt_layer_kinds{kind="window"} 1' in text
    assert 'hvt_held_heads{mixer="window"} 6' in text
    assert 'hvt_rotary_dims{kind="softmax"} 8' in text
    assert "hvt_attn_window 16" in text
    plain = HybridMoELM(**SIZES)
    params = plain.init(jax.random.PRNGKey(0), x)["params"]
    assert 'hvt_layer_kinds{kind="window"} 0' in prom.render()
    sized = HybridMoELM(**SIZES | dict(softmax=AttentionSizes(4, 2, 2, 2)))
    np.testing.assert_array_equal(
        sized.apply({"params": params}, x), plain.apply({"params": params}, x))


def test_trainer_fit_with_the_module_loss_logs_the_sown_metrics():
    model = HybridMoELM(**SIZES)
    trainer = hvt.Trainer(
        model, hvt.DistributedOptimizer(optax.adamw(1e-3)), loss="module",
        mesh=hvt.build_mesh(hvt.MeshSpec(data=1), devices=jax.devices()[:1]))
    x = np.asarray(tokens(8, 64))
    seen = []

    class Logs(hvt.callbacks.Callback):
        def on_batch_end(self, batch, logs=None):
            seen.append({k: float(v) for k, v in logs.items()})

    trainer.fit(x=x, y=np.roll(x, -1, axis=1), batch_size=2, epochs=2,
                steps_per_epoch=4, verbose=0, callbacks=[Logs()])
    assert seen[-1]["loss"] < seen[0]["loss"]
    assert all(log["moe_overflow_rows"] == 0 for log in seen)
    assert {"moe_held_rows_share", "moe_load_max_over_mean"} <= set(seen[0])


def test_the_compiled_step_names_the_layers_forward_and_backward():
    """Every part of the linear layer and the softmax layer's projections
    carry their scope in the lowered step's op names, under `jvp(` and
    under `transpose(jvp(`; the flash kernels keep their own names."""
    model = HybridMoELM(**SIZES)
    x = tokens()
    params = model.init(jax.random.PRNGKey(0), x)["params"]

    def loss(p):
        return model.apply({"params": p}, x, labels=x)[0].mean()

    text = jax.jit(jax.grad(loss)).lower(params).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    for scope in (hybrid.KDA_PROJ, hybrid.KDA_CONV, hybrid.KDA_SCAN,
                  hybrid.KDA_OUT, hybrid.GQA_SCOPE):
        forward = [n for n in names if scope in n and "transpose" not in n]
        backward = [n for n in names if scope in n and "transpose(" in n]
        assert forward and backward, scope
    assert any("Block_1/mixer" in n and hybrid.KDA_SCAN in n for n in names)
    assert any("Block_0/mixer" in n and hybrid.GQA_SCOPE in n for n in names)
    assert not any("Block_0/mixer" in n and hybrid.KDA_SCOPE in n
                   for n in names)
    for kernel in ("hvt_flash_fwd", "hvt_flash_bwd"):
        assert kernel in text


# --- refusals ------------------------------------------------------------------

def test_no_decode_path_by_name():
    """`decoding.require_decode_path` names the model and what its layers
    lack, from every generator's door."""
    model = HybridMoELM(**SIZES)
    for refuse in (
            lambda: decoding.require_decode_path(model),
            lambda: decoding.make_generate_fn(model, max_new_tokens=4),
            lambda: make_beam_search_fn(model, max_new_tokens=4, beam_size=2),
            lambda: make_speculative_fn(model, max_new_tokens=4)):
        with pytest.raises(NotImplementedError, match="HybridMoELM") as err:
            refuse()
        assert "recurrent state" in str(err.value)
        assert "DeltaAttention" in str(err.value)


def test_the_pipeline_refuses_the_mixers_by_name():
    with pytest.raises(ValueError, match="DeltaAttention, GatedAttention"):
        PipelinedLM(vocab_size=96, d_model=64, n_layers=2, n_heads=4,
                    mlp="hybrid").init(jax.random.PRNGKey(0), tokens())


def test_more_than_one_chip_is_refused_by_name():
    mesh = hvt.build_mesh(hvt.MeshSpec(data=2), devices=jax.devices()[:2])
    model = HybridMoELM(**SIZES | dict(sharding=ShardingConfig(mesh=mesh)))
    with pytest.raises(NotImplementedError, match="HybridMoELM on a mesh"):
        model.init(jax.random.PRNGKey(0), tokens())


@pytest.mark.parametrize("change,says", [
    (dict(layer_kinds=(LINEAR, "local")), "a layer is 'linear' or 'softmax'"),
    (dict(layer_kinds=()), "a layer is 'linear' or 'softmax'"),
    (dict(held_heads_start=3), "are not a block of its 4"),
    (dict(n_held_heads=1, held_heads_start=0), "do not cover whole groups"),
    (dict(held_start=14), "are not a block of the 16"),
    (dict(layer_kinds=(SOFTMAX, WINDOW)),
     "`window`, the kind's `AttentionSizes` with its window, is not given"),
    (dict(layer_kinds=(SOFTMAX, WINDOW),
          window=AttentionSizes(4, 2, 4, 0)),
     "`window`, the kind's `AttentionSizes` with its window, is not given"),
    (dict(softmax=AttentionSizes(4, 2, 4, 0, window=16)),
     "reads every key before a query: its sizes give a window"),
    (dict(n_dense_layers=5), "5 leading dense layers of 4"),
], ids=["unknown_kind", "no_layers", "heads_past_the_end",
        "half_a_kv_group", "experts_past_the_router", "window_unsized",
        "window_without_its_window", "softmax_with_a_window",
        "dense_past_the_stack"])
def test_what_cannot_be_built_is_refused_by_name(change, says):
    with pytest.raises(ValueError, match=says):
        HybridMoELM(**SIZES | change).init(jax.random.PRNGKey(0), tokens())
