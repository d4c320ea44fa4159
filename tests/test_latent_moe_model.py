"""`LatentMoELM` and its layers as the program offers them (no benchmark
file is read here; the comparison with the plain reference is
tests/chipbench/test_latent_moe_lm.py): the grouped matmul against a dense
computation, each sequence routed by itself, the model through
`Trainer.fit`, what it names in the compiled program, and what it refuses."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvt
from horovod_tpu.models import decoding
from horovod_tpu.models.beam import make_beam_search_fn
from horovod_tpu.models.latent_moe_lm import (
    LatentMoELM, rope_adjacent_pairs)
from horovod_tpu.models.moe import RoutedExperts
from horovod_tpu.models.pipelined_lm import PipelinedLM
from horovod_tpu.models.speculative import make_speculative_fn
from horovod_tpu.models.transformer import ShardingConfig
from horovod_tpu.obs import prom
from horovod_tpu.ops import grouped_matmul as gm

SIZES = dict(
    vocab_size=96, d_model=64, n_layers=2, n_dense_layers=1, dense_width=96,
    n_heads=4, qk_nope_dim=16, qk_rope_dim=8, v_dim=16, kv_rank=32,
    n_routed=16, experts_per_token=3, expert_width=32, shared_width=64,
    routed_scaling=2.448, n_held=4, held_start=4, rope_base=1e6,
    fused_head_chunks=2)


def tokens(batch=2, seq=64, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).integers(1, 96, (batch, seq)), jnp.int32)


# --- the grouped matmul ------------------------------------------------------

@pytest.mark.parametrize("m,k,n,groups,dtype,tol", [
    (48, 64, 40, 4, jnp.float32, 2e-5),
    (512, 96, 136, 5, jnp.float32, 5e-5),   # tiles with remainders
    (40, 64, 32, 3, jnp.bfloat16, 0.15),
], ids=["small", "remainders", "bf16"])
def test_grouped_matmul_matches_a_dense_product(m, k, n, groups, dtype, tol):
    """Forward and both gradients, rows past the groups' total zero."""
    rng = np.random.default_rng(0)
    m = gm.row_budget(m)
    sizes = jnp.asarray(rng.multinomial(
        int(m * 0.7), np.ones(groups) / groups).astype(np.int32))
    lhs = jnp.asarray(rng.standard_normal((m, k)), dtype)
    rhs = jnp.asarray(rng.standard_normal((groups, k, n)), dtype)
    weight = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)

    def dense(lhs, rhs):
        ids = jnp.repeat(jnp.arange(groups), sizes, total_repeat_length=m)
        out = jnp.einsum("mk,mkn->mn", lhs.astype(jnp.float32),
                         rhs.astype(jnp.float32)[ids])
        return jnp.where((jnp.arange(m) < sizes.sum())[:, None], out, 0)

    def kernel(lhs, rhs):
        return gm.grouped_matmul(lhs, rhs, sizes).astype(jnp.float32)

    np.testing.assert_allclose(kernel(lhs, rhs), dense(lhs, rhs),
                               atol=tol, rtol=tol)
    assert not np.any(kernel(lhs, rhs)[int(sizes.sum()):])
    got = jax.grad(lambda *a: (kernel(*a) * weight).sum(), (0, 1))(lhs, rhs)
    want = jax.grad(lambda *a: (dense(*a) * weight).sum(), (0, 1))(lhs, rhs)
    for a, b in zip(got, want):
        assert a.dtype == dtype
        np.testing.assert_allclose(
            a.astype(jnp.float32), b.astype(jnp.float32),
            atol=tol * float(jnp.abs(b).max()), rtol=tol)


def test_the_kernels_called_are_jax_own_unjitted():
    """`ops/grouped_matmul` reaches past the `jit` of JAX's `gmm` / `tgmm`
    (``__wrapped__``) so that the compiled instruction carries this
    repository's name: what it reaches has to be the plain function of that
    module, not another wrapper, and a JAX that wraps differently fails
    here by name."""
    import inspect

    for name, fn in (("gmm", gm._gmm), ("tgmm", gm._tgmm)):
        assert inspect.isfunction(fn) and fn.__name__ == name
        assert fn.__module__ == gm._backend.__name__
        assert not hasattr(fn, "__wrapped__") and not hasattr(fn, "lower")
        assert "interpret" in inspect.signature(fn).parameters
    assert hasattr(gm._backend.gmm, "lower")  # the module's own is jitted
    assert gm._backend.gmm.__wrapped__ is gm._gmm


def test_row_budget_and_group_sizes():
    assert gm.row_budget(12288) == 12288 and gm.row_budget(12289) == 12544
    assert gm.row_budget(96) == 96 and gm.row_budget(97) == 104
    assert gm.group_sizes_of(jnp.asarray([[0, 3, 5], [1, 1, -1]]), 4
                             ).tolist() == [1, 2, 0, 1]


def test_rope_turns_adjacent_pairs_in_place():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 5, 2, 8)),
                    jnp.float32)
    positions = jnp.arange(5)[None]
    out = rope_adjacent_pairs(x, positions, 1e6)
    np.testing.assert_allclose(out[0, 0], x[0, 0], atol=1e-7)  # position 0
    # pair i turns by position * base^(-2i / D): lengths kept pair by pair
    np.testing.assert_allclose(
        out[..., 0::2] ** 2 + out[..., 1::2] ** 2,
        x[..., 0::2] ** 2 + x[..., 1::2] ** 2, rtol=1e-5)
    angle = 3 * 1e6 ** (-2 / 8)  # position 3, pair 1
    want = x[0, 3, 0, 2] * np.cos(angle) - x[0, 3, 0, 3] * np.sin(angle)
    assert float(out[0, 3, 0, 2]) == pytest.approx(float(want), rel=1e-5)


# --- the model on the normal path -------------------------------------------

def test_each_sequence_is_routed_by_itself():
    """The level bias is solved a sequence at a time: a batch of two gives
    each sequence the losses it has alone (so the reference, which takes one
    sequence, holds for any batch)."""
    x = tokens(batch=2)
    model = LatentMoELM(**SIZES)
    params = model.init(jax.random.PRNGKey(0), x, labels=x)["params"]
    both, _ = model.apply({"params": params}, x, labels=x)
    for row in range(2):
        alone, _ = model.apply(
            {"params": params}, x[row:row + 1], labels=x[row:row + 1])
        np.testing.assert_allclose(both[row], alone[0], atol=2e-5)


def test_trainer_fit_with_the_module_loss_logs_the_sown_metrics():
    x = np.asarray(tokens(batch=8, seq=32, seed=1))
    trainer = hvt.Trainer(
        LatentMoELM(**SIZES), hvt.DistributedOptimizer(optax.adamw(1e-3)),
        loss="module",
        mesh=hvt.build_mesh(hvt.MeshSpec(data=1), devices=jax.devices()[:1]))
    history = trainer.fit(x=x, y=x, batch_size=4, epochs=2, verbose=0)
    logs = {k: [epoch[k] for epoch in history] for k in history[0]}
    assert {"moe_overflow_rows", "moe_held_rows_share",
            "moe_load_max_over_mean"} <= set(trainer.metric_names)
    losses = logs["loss"]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert logs["moe_overflow_rows"][-1] == 0.0
    assert 0.1 < logs["moe_held_rows_share"][-1] < 0.5  # 4 of 16 held
    values = prom.parse_text(prom.render())
    assert values['hvt_moe_experts{kind="held"}'] == 4.0
    assert values['hvt_moe_experts{kind="routed"}'] == 16.0


def test_the_compiled_step_names_the_layers_forward_and_backward():
    """`jax.named_scope`s in the lowered program's op names: hvt.moe with
    its five parts and hvt.mla, each in the forward pass (``jvp``) and in
    the backward (``transpose``), and the kernels by their names."""
    x = tokens(batch=1)
    model = LatentMoELM(**SIZES)
    params = model.init(jax.random.PRNGKey(0), x, labels=x)["params"]
    text = jax.jit(jax.grad(lambda p: model.apply(
        {"params": p}, x, labels=x)[0].mean())).lower(params).as_text(
            debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    for scope in ("hvt.moe/route", "hvt.moe/dispatch", "hvt.moe/experts",
                  "hvt.moe/combine", "hvt.moe/shared", "hvt.mla"):
        forward = [n for n in names if scope in n and "transpose" not in n]
        backward = [n for n in names if scope in n and "transpose" in n]
        assert forward and backward, scope
    assert any("hvt.moe/dispatch" in n and "sort" in n for n in names)
    assert any("hvt.moe/combine" in n and "scatter" in n for n in names)
    for kernel in (gm.KERNEL, gm.KERNEL_DW, "hvt_flash_fwd", "hvt_flash_bwd"):
        assert any(re.search(rf"{kernel}(\)|/|$)", n) for n in names), kernel
    # the flash kernels sit outside hvt.mla: its metric is the projections
    assert not any("hvt.mla" in n and "hvt_flash" in n for n in names)


# --- what is refused, by name -----------------------------------------------

def test_no_decode_path_and_no_pipeline_by_name():
    model = LatentMoELM(**SIZES)
    for build in (
            lambda: decoding.make_generate_fn(model, max_new_tokens=4),
            lambda: make_beam_search_fn(model, max_new_tokens=4, beam_size=2),
            lambda: make_speculative_fn(model, max_new_tokens=4)):
        with pytest.raises(NotImplementedError,
                           match="LatentMoELM has no decode path"):
            build()
    pipelined = PipelinedLM(vocab_size=32, d_model=16, n_heads=2, n_layers=2,
                            mlp="routed_experts")
    with pytest.raises(ValueError, match="RoutedExperts, SwiGLU and latent"):
        pipelined.init(jax.random.PRNGKey(0), tokens(2, 8))


def test_more_than_one_chip_and_a_block_outside_the_router_are_refused():
    x = tokens(batch=2)
    for spec in (hvt.MeshSpec(data=2), hvt.MeshSpec(data=1, model=2),
                 hvt.MeshSpec(data=1, expert=2)):
        mesh = hvt.build_mesh(spec, devices=jax.devices()[:2])
        model = LatentMoELM(**SIZES, sharding=ShardingConfig(mesh=mesh))
        with pytest.raises(NotImplementedError, match="a mesh of 2 chips"):
            model.init(jax.random.PRNGKey(0), x, labels=x)
    routed = dict(n_routed=8, k=2, expert_width=16, shared_width=16,
                  routed_scaling=1.0)
    layer = RoutedExperts(**routed, n_held=2, held_start=0,
                          sharding=ShardingConfig(mesh=mesh))
    with pytest.raises(NotImplementedError, match="a mesh of 2 chips"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 16)))
    layer = RoutedExperts(**routed, n_held=2, held_start=7)
    with pytest.raises(ValueError, match="not a block of the 8 routed"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 16)))
