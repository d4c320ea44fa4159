"""Mixture-of-Experts layer + expert parallelism over the `expert` mesh axis.

Covers: routing correctness (tokens reach the expert the router picked),
capacity overflow drops (zero contribution, not garbage), the load-balancing
aux loss reaching the training objective through the Trainer's 'losses'
channel, EP sharding of expert weights and optimizer mirrors, and a
MoE transformer actually training on an expert-parallel mesh.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvt
from horovod_tpu.data import datasets
from horovod_tpu.models.moe import MoEMlp
from horovod_tpu.models.transformer import (
    ShardingConfig,
    TransformerLM,
    param_specs,
)
from horovod_tpu.parallel import mesh as mesh_lib

VOCAB = 32


def _init(module, x, train=False):
    return module.init({"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)}, x, train=train)


@pytest.mark.slow
class TestRouting:
    def test_tokens_reach_their_expert(self):
        """Force the router with a hand-built kernel: token feature i routes
        to expert i; give each expert a constant-output transform and check
        every token carries its own expert's constant."""
        d, e = 4, 4
        layer = MoEMlp(d, n_experts=e, k=1, capacity_factor=4.0, mlp_ratio=1)
        x = jnp.eye(e).reshape(1, e, d)  # token i = one-hot(i) → expert i
        variables = _init(layer, x)
        params = jax.device_get(variables["params"])
        # Router kernel = large identity → softmax puts ~all mass on expert i.
        params["router"]["kernel"] = np.eye(d, e, dtype=np.float32) * 50.0
        # Expert j: w_up zeros→gelu(0)=0 trick won't distinguish; instead use
        # w_up so hidden = tokens @ w_up = row sums, and w_down scaled by
        # (j+1): output magnitude identifies the expert.
        params["moe_up"] = np.ones((e, d, d), np.float32)
        params["moe_down"] = np.stack(
            [np.eye(d, dtype=np.float32) * (j + 1) for j in range(e)]
        )
        out = layer.apply({"params": params}, x)
        # Token i (one-hot) → hidden = gelu(1,1,1,1 row? token·w_up = ones) →
        # out = gelu(1)·(i+1) per dim; ratio across tokens identifies expert.
        base = float(out[0, 0, 0])
        for i in range(e):
            np.testing.assert_allclose(
                np.asarray(out[0, i]), base * (i + 1), rtol=1e-5
            )

    def test_capacity_overflow_drops_to_zero(self):
        """All tokens prefer expert 0 with capacity 1: exactly one token gets
        through, the rest contribute zero (safe with a residual add)."""
        d, e, n_tok = 4, 2, 8
        layer = MoEMlp(d, n_experts=e, k=1, capacity_factor=1e-9, mlp_ratio=1)
        x = jnp.ones((1, n_tok, d))
        variables = _init(layer, x)
        params = jax.device_get(variables["params"])
        params["router"]["kernel"] = np.zeros((d, e), np.float32)
        params["router"]["kernel"][:, 0] = 50.0  # everyone → expert 0
        params["moe_up"] = np.ones((e, d, d), np.float32)
        params["moe_down"] = np.ones((e, d, d), np.float32)
        out = np.asarray(layer.apply({"params": params}, x))
        nonzero = np.abs(out).sum(-1) > 1e-6  # [1, n_tok]
        assert nonzero.sum() == 1  # capacity 1 → exactly one survivor

    def test_grouped_dispatch_matches_single_group(self):
        """Dispatch groups are a cost optimization, not a semantics change:
        with ample capacity, 4 groups and 1 group compute the same output."""
        d, e = 8, 4
        x = jnp.asarray(np.random.RandomState(7).rand(2, 8, d), jnp.float32)
        one = MoEMlp(d, n_experts=e, k=2, capacity_factor=8.0, group_size=16)
        four = MoEMlp(d, n_experts=e, k=2, capacity_factor=8.0, group_size=4)
        variables = _init(one, x)
        np.testing.assert_allclose(
            np.asarray(one.apply(variables, x)),
            np.asarray(four.apply(variables, x)),
            rtol=1e-5, atol=1e-6,
        )

    def test_switch_k1_router_gets_task_gradient(self):
        """k=1 must use the RAW top probability as the gate (renormalizing
        would make it constant 1.0 and freeze the router)."""
        d, e = 8, 4
        layer = MoEMlp(d, n_experts=e, k=1, capacity_factor=4.0)
        x = jnp.asarray(np.random.RandomState(1).rand(1, 8, d), jnp.float32)
        variables = _init(layer, x)

        def task_loss(params):
            out = layer.apply({"params": params}, x)
            return (out ** 2).sum()

        g = jax.grad(task_loss)(variables["params"])
        router_grad = float(np.abs(np.asarray(g["router"]["kernel"])).sum())
        assert router_grad > 1e-6  # not cut off from the task loss

    def test_indivisible_experts_rejected(self):
        """Misconfigured EP (experts not divisible by the expert axis) must
        fail loudly — silent replication would quietly discard the memory
        scaling EP exists for. Both the layer and param_specs guard it."""
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, expert=4))
        model = TransformerLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=2, dropout=0.0,
            moe_every=2, n_experts=6,  # 6 % 4 != 0
            sharding=ShardingConfig(mesh=mesh, attn="dense"),
        )
        toks = jnp.zeros((8, 16), jnp.int32)
        with pytest.raises(ValueError, match="divisible"):
            model.init(
                {"params": jax.random.PRNGKey(0),
                 "dropout": jax.random.PRNGKey(1)},
                toks,
            )
        # param_specs guards independently (callers can hand-build params).
        plain = TransformerLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=2, dropout=0.0,
            moe_every=2, n_experts=6,
        )
        params = plain.init(
            {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
            toks,
        )["params"]
        with pytest.raises(ValueError, match="divisible"):
            param_specs(params, mesh)

    def test_top2_gates_renormalized(self):
        d, e = 8, 4
        layer = MoEMlp(d, n_experts=e, k=2, capacity_factor=4.0)
        x = jnp.asarray(np.random.RandomState(0).rand(2, 6, d), jnp.float32)
        variables = _init(layer, x)
        out = layer.apply(variables, x)
        assert out.shape == x.shape
        assert np.isfinite(np.asarray(out)).all()


@pytest.mark.slow
class TestAuxLoss:
    def test_sown_during_train_only(self):
        d = 8
        layer = MoEMlp(d, n_experts=4, k=1)
        x = jnp.ones((1, 4, d))
        variables = _init(layer, x)
        _, state = layer.apply(
            variables, x, train=True, mutable=["losses"],
            rngs={"dropout": jax.random.PRNGKey(0)},
        )
        assert "moe_load_balance" in state["losses"]
        aux = jax.tree.leaves(state["losses"])[0]
        assert float(np.asarray(aux)) >= 0.0
        _, state_eval = layer.apply(variables, x, train=False, mutable=["losses"])
        assert not state_eval.get("losses", {})

    def test_trainer_adds_aux_to_objective(self):
        """The same model with aux_loss_coef 0 vs large must report different
        training loss — proof the sown value reaches the objective."""

        def run(coef):
            model = TransformerLM(
                vocab_size=VOCAB, d_model=32, n_heads=2, n_layers=2,
                dropout=0.0, moe_every=2, n_experts=4, moe_aux_coef=coef,
            )
            trainer = hvt.Trainer(
                model, hvt.DistributedOptimizer(optax.sgd(0.0))
            )
            x, y = datasets.copy_task(64, 16, vocab_size=VOCAB, seed=0)
            hist = trainer.fit(
                x=x, y=y, batch_size=2, epochs=1, steps_per_epoch=2,
                shuffle_buffer=1, verbose=0,
            )
            return hist[0]["loss"]

        assert run(100.0) > run(0.0) + 1.0


@pytest.mark.slow
class TestExpertParallel:
    def _mesh(self):
        return mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, expert=4))

    def _trainer(self, mesh, **model_kw):
        model = TransformerLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=2, dropout=0.0,
            moe_every=2, n_experts=4,
            sharding=ShardingConfig(mesh=mesh, attn="dense"),
            **model_kw,
        )
        return hvt.Trainer(
            model,
            hvt.DistributedOptimizer(optax.adam(3e-3)),
            mesh=mesh,
            param_specs=param_specs,
            batch_specs=(P(("data", "fsdp"), "seq"), P(("data", "fsdp"), "seq")),
        )

    def test_expert_weights_sharded_on_expert_axis(self):
        trainer = self._trainer(self._mesh())
        x, _ = datasets.copy_task(8, 16, vocab_size=VOCAB)
        state = trainer.build(x)

        def expert_sharded(tree):
            flat = jax.tree_util.tree_flatten_with_path(tree)[0]
            return [
                path for path, leaf in flat
                if hasattr(leaf, "sharding")
                and any(
                    "expert" in (ax if isinstance(ax, tuple) else (ax,))
                    for ax in getattr(leaf.sharding, "spec", P())
                    if ax is not None
                )
            ]

        # moe_up + moe_down in the one MoE block.
        assert len(expert_sharded(state.params)) == 2
        # Optimizer mirrors (mu, nu) inherit the layout.
        assert len(expert_sharded(state.opt_state)) == 4

    def test_moe_transformer_trains_on_ep_mesh(self):
        trainer = self._trainer(self._mesh())
        x, y = datasets.copy_task(256, 16, vocab_size=VOCAB, seed=1)
        history = trainer.fit(
            x=x, y=y, batch_size=8, epochs=2, steps_per_epoch=8, verbose=0
        )
        assert np.isfinite(history[-1]["loss"])
        assert history[-1]["loss"] < history[0]["loss"]

    def test_ep_tp_composition(self):
        """EP × TP on one mesh: expert weights shard dim 0 over `expert` AND
        their hidden dim over `model` (param_specs moe rules); the function
        must still match the unsharded layer and train end-to-end."""
        mesh = mesh_lib.build_mesh(
            mesh_lib.MeshSpec(data=2, expert=2, model=2)
        )
        d, e = 16, 4
        plain = MoEMlp(d, n_experts=e, k=2, capacity_factor=2.0)
        sharded = MoEMlp(
            d, n_experts=e, k=2, capacity_factor=2.0,
            sharding=ShardingConfig(mesh=mesh),
        )
        x = jnp.asarray(np.random.RandomState(7).rand(2, 8, d), jnp.float32)
        variables = _init(plain, x)
        out_plain = plain.apply(variables, x)
        out_sharded = jax.jit(lambda v, t: sharded.apply(v, t))(variables, x)
        np.testing.assert_allclose(
            np.asarray(out_plain), np.asarray(out_sharded), rtol=1e-4, atol=1e-5
        )
        trainer = self._trainer(mesh)
        xt, yt = datasets.copy_task(128, 16, vocab_size=VOCAB, seed=2)
        hist = trainer.fit(
            x=xt, y=yt, batch_size=8, epochs=1, steps_per_epoch=4, verbose=0
        )
        assert np.isfinite(hist[-1]["loss"])
        state = trainer.state
        up = state.params["Block_1"]["moe"]["moe_up"]
        spec = up.sharding.spec
        assert spec[0] == "expert" and spec[2] == "model", spec

    def test_moe_matches_unsharded(self):
        """EP-sharded MoE must compute the same function as the unsharded
        layer (same params, same tokens)."""
        mesh = self._mesh()
        d, e = 16, 4
        plain = MoEMlp(d, n_experts=e, k=2, capacity_factor=2.0)
        sharded = MoEMlp(
            d, n_experts=e, k=2, capacity_factor=2.0,
            sharding=ShardingConfig(mesh=mesh),
        )
        x = jnp.asarray(np.random.RandomState(3).rand(2, 8, d), jnp.float32)
        variables = _init(plain, x)
        out_plain = plain.apply(variables, x)
        out_sharded = jax.jit(lambda v, t: sharded.apply(v, t))(variables, x)
        np.testing.assert_allclose(
            np.asarray(out_plain), np.asarray(out_sharded), rtol=1e-4, atol=1e-5
        )


@pytest.mark.slow
class TestDropRateObservability:
    """Router overflow drops are safe but must be VISIBLE: the layer sows
    'metrics'/'moe_drop_rate' and the Trainer surfaces it in the step
    metrics and epoch logs (an EP config silently dropping a third of its
    tokens was round-2's Weak #6)."""

    def _train(self, capacity_factor, steps=2):
        model = TransformerLM(
            vocab_size=VOCAB, d_model=32, n_heads=2, n_layers=2,
            dropout=0.0, moe_every=2, n_experts=4,
            capacity_factor=capacity_factor,
        )
        trainer = hvt.Trainer(model, hvt.DistributedOptimizer(optax.sgd(0.0)))
        x, y = datasets.copy_task(64, 16, vocab_size=VOCAB, seed=0)
        hist = trainer.fit(
            x=x, y=y, batch_size=2, epochs=1, steps_per_epoch=steps,
            shuffle_buffer=1, verbose=0,
        )
        return trainer, hist

    def test_drop_rate_in_epoch_logs(self):
        trainer, hist = self._train(capacity_factor=1.25)
        assert "moe_drop_rate" in trainer.metric_names
        rate = hist[0]["moe_drop_rate"]
        assert 0.0 <= rate <= 1.0

    def test_tight_capacity_reports_high_drop_rate(self):
        """capacity_factor well below 1 MUST drop tokens — with k=2 and
        cf=0.25, at most 1/8 of routed pairs fit, so the reported rate must
        be large; ample capacity must report (near) zero."""
        _, starved = self._train(capacity_factor=0.25)
        _, ample = self._train(capacity_factor=8.0)
        assert starved[0]["moe_drop_rate"] > 0.5
        assert ample[0]["moe_drop_rate"] < 0.05
        assert starved[0]["moe_drop_rate"] > ample[0]["moe_drop_rate"]

    def test_drop_rate_value_matches_direct_count(self):
        """The sown scalar equals a direct recount of overflowed (token,
        choice) pairs from the routing math on the same inputs."""
        d, e, k, cf = 16, 4, 2, 0.5
        layer = MoEMlp(d, n_experts=e, k=k, capacity_factor=cf)
        x = jnp.asarray(np.random.RandomState(0).rand(2, 16, d), jnp.float32)
        variables = _init(layer, x)
        # Init itself sows 'metrics'; apply with the bare params so the
        # collection holds exactly this apply's sow.
        _, state = layer.apply(
            {"params": variables["params"]}, x, mutable=["metrics"]
        )
        sown = jax.tree.leaves(state["metrics"])
        assert len(sown) == 1
        reported = float(sown[0])

        # Direct recount, mirroring the routing definition.
        s = x.shape[0] * x.shape[1]  # one group at this size
        probs = jax.nn.softmax(
            x.reshape(1, s, d).astype(jnp.float32)
            @ variables["params"]["router"]["kernel"],
            axis=-1,
        )
        _, top_idx = jax.lax.top_k(probs, k)
        capacity = max(1, int(k * s / e * cf))
        choice = jnp.moveaxis(jax.nn.one_hot(top_idx, e), -2, 1)
        flat = choice.reshape(1, k * s, e)
        pos = jnp.cumsum(flat, axis=1) * flat - 1.0
        kept = ((pos >= 0) & (pos < capacity)).sum()
        expected = 1.0 - float(kept) / (k * s)
        assert reported == pytest.approx(expected, abs=1e-6)

    def test_train_gated_metric_sow_is_loud(self):
        """'metrics' sows must be unconditional: a train-gated sow cannot be
        discovered at build() and must fail with the explanatory error, not
        an opaque pytree mismatch."""
        import flax.linen as fnn

        class Gated(fnn.Module):
            @fnn.compact
            def __call__(self, x, *, train=False):
                y = fnn.Dense(4)(x.reshape((x.shape[0], -1)))
                if train:
                    self.sow("metrics", "gated", jnp.mean(y))
                return y

        tr = hvt.Trainer(Gated(), hvt.DistributedOptimizer(optax.sgd(0.1)))
        x = np.random.RandomState(0).rand(16, 4).astype(np.float32)
        y = np.zeros(16, np.int64)
        with pytest.raises(ValueError, match="unconditional"):
            tr.fit(x=x, y=y, batch_size=2, epochs=1, steps_per_epoch=1)

    def test_reserved_metric_name_is_loud(self):
        import flax.linen as fnn

        class BadName(fnn.Module):
            @fnn.compact
            def __call__(self, x, *, train=False):
                y = fnn.Dense(4)(x.reshape((x.shape[0], -1)))
                self.sow("metrics", "loss", jnp.mean(y))
                return y

        tr = hvt.Trainer(BadName(), hvt.DistributedOptimizer(optax.sgd(0.1)))
        with pytest.raises(ValueError, match="rename the sow"):
            tr.build(np.zeros((8, 4), np.float32))


@pytest.mark.slow
class TestMoESeqComposition:
    """dp x sp x ep on one mesh: MoE blocks under GSPMD compose with the
    partially-manual ring-attention seq axis — the routing einsums stay a
    global function of the full token stream (GSPMD inserts the
    collectives), so the sharded forward must match the unsharded one."""

    def _models(self, mesh):
        kw = dict(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=2,
            dropout=0.0, moe_every=2, n_experts=4,
        )
        return (
            TransformerLM(**kw),
            TransformerLM(
                **kw, sharding=ShardingConfig(mesh=mesh, attn="ring")
            ),
        )

    def test_forward_matches_unsharded_and_trains(self):
        mesh = mesh_lib.build_mesh(
            mesh_lib.MeshSpec(data=2, seq=2, expert=2)
        )
        plain, sharded = self._models(mesh)
        rng = np.random.RandomState(71)
        toks = jnp.asarray(rng.randint(1, VOCAB, size=(4, 32)).astype(np.int32))
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]
        out_plain = plain.apply({"params": params}, toks)
        out_sh = jax.jit(
            lambda p, t: sharded.apply({"params": p}, t)
        )(params, toks)
        np.testing.assert_allclose(
            np.asarray(out_sh), np.asarray(out_plain), rtol=2e-4, atol=2e-5
        )

        bspec = P(("data", "fsdp"), "seq")
        trainer = hvt.Trainer(
            sharded,
            hvt.DistributedOptimizer(optax.adam(3e-3)),
            mesh=mesh,
            param_specs=param_specs,
            batch_specs=(bspec, bspec),
        )
        x, y = datasets.copy_task(128, 32, vocab_size=VOCAB, seed=1)
        hist = trainer.fit(
            x=x, y=y, batch_size=8, epochs=2, steps_per_epoch=4, verbose=0
        )
        assert np.isfinite(hist[-1]["loss"])
        assert hist[-1]["loss"] < hist[0]["loss"]
        assert "moe_drop_rate" in trainer.metric_names


@pytest.mark.slow
class TestExpertChoice:
    """Expert-choice routing (arXiv:2202.09368): experts pick tokens —
    perfectly balanced and drop-free by construction, no aux loss."""

    def _mlp(self, **kw):
        from horovod_tpu.models.moe import MoEMlp

        kw.setdefault("n_experts", 4)
        kw.setdefault("capacity_factor", 1.0)
        kw.setdefault("router", "expert_choice")
        return MoEMlp(16, **kw)

    def test_every_expert_exactly_full(self):
        """The dispatch tensor assigns each expert exactly `capacity`
        distinct tokens — balance is structural, not incentivized."""
        import jax
        import jax.numpy as jnp

        mlp = self._mlp()
        x = jnp.asarray(
            np.random.RandomState(0).randn(2, 32, 16), jnp.float32
        )
        params = mlp.init(jax.random.PRNGKey(0), x)["params"]

        # Recompute the dispatch the layer builds internally.
        probs = jax.nn.softmax(
            x.reshape(1, 64, 16).astype(jnp.float32)
            @ params["router"]["kernel"], axis=-1
        )
        capacity = max(1, int(2 * 64 / 4 * 1.0))
        _, g_idx = jax.lax.top_k(jnp.moveaxis(probs, -1, 1), capacity)
        for row in np.asarray(g_idx[0]):
            assert len(set(row.tolist())) == capacity  # distinct tokens

    def test_output_and_metrics(self):
        import jax
        import jax.numpy as jnp

        mlp = self._mlp()
        x = jnp.asarray(
            np.random.RandomState(1).randn(2, 32, 16), jnp.float32
        )
        params = mlp.init(jax.random.PRNGKey(0), x)["params"]
        out, state = mlp.apply(
            {"params": params}, x, train=True, mutable=["metrics", "losses"]
        )
        assert out.shape == x.shape
        assert "moe_uncovered_rate" in state["metrics"]
        # Drop-free: no load-balance aux loss is sown.
        assert "losses" not in state or not state["losses"]
        rate = float(np.asarray(jax.tree.leaves(state["metrics"])[0]).ravel()[0])
        assert 0.0 <= rate < 1.0

    def test_router_gets_gradient(self):
        import jax
        import jax.numpy as jnp

        mlp = self._mlp()
        x = jnp.asarray(
            np.random.RandomState(2).randn(1, 32, 16), jnp.float32
        )
        params = mlp.init(jax.random.PRNGKey(0), x)["params"]

        def loss(p):
            return (mlp.apply({"params": p}, x) ** 2).sum()

        g = jax.grad(loss)(params)
        assert float(jnp.abs(g["router"]["kernel"]).max()) > 0.0

    def test_unknown_router_rejected(self):
        import jax
        import jax.numpy as jnp

        mlp = self._mlp(router="nope")
        with pytest.raises(ValueError, match="router must be"):
            mlp.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 16)))

    def test_trains_in_transformer_and_refuses_decode(self):
        import jax
        import jax.numpy as jnp
        import optax

        import horovod_tpu as hvt
        from horovod_tpu.data import datasets
        from horovod_tpu.models.transformer import TransformerLM

        model = TransformerLM(
            vocab_size=32, d_model=32, n_heads=4, n_layers=2, dropout=0.0,
            moe_every=2, n_experts=4, moe_router="expert_choice",
        )
        trainer = hvt.Trainer(
            model, hvt.DistributedOptimizer(optax.adam(3e-3)),
            loss="sparse_categorical_crossentropy",
        )
        x, y = datasets.copy_task(64, 16, vocab_size=32)
        hist = trainer.fit(x=np.asarray(x), y=np.asarray(y), batch_size=8,
                           epochs=3, verbose=0)
        assert hist[-1]["loss"] < hist[0]["loss"]
        assert "moe_uncovered_rate" in hist[-1]

        from horovod_tpu.models.decoding import generate

        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        with pytest.raises(ValueError, match="training-only"):
            generate(model, params, np.zeros((1, 4), np.int32), 2)

    def test_ep_mesh_matches_unsharded(self):
        import jax
        import jax.numpy as jnp

        from horovod_tpu.models.transformer import ShardingConfig
        from horovod_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.build_mesh(
            mesh_lib.MeshSpec(data=2, expert=4), devices=jax.devices()[:8]
        )
        x = jnp.asarray(
            np.random.RandomState(3).randn(2, 32, 16), jnp.float32
        )
        plain = self._mlp()
        sharded = self._mlp(sharding=ShardingConfig(mesh=mesh))
        params = plain.init(jax.random.PRNGKey(0), x)["params"]
        a = plain.apply({"params": params}, x)
        b = sharded.apply({"params": params}, x)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
        )


# --- `RoutedExperts`' selection: by counting, against the sort form ---------
#
# `moe.level_bias` and `moe._largest` select by counting and comparing; the
# sort form they replaced (one `jnp.sort` down the tokens, `jax.lax.top_k`
# along the experts) lives on here as their oracle. The threshold has to be
# equal as a number and the chosen experts equal to the index, in order.

from horovod_tpu.models import moe as moe_lib  # noqa: E402


def _above(t, e, k):
    return min(t, max(1, round(t * k / e)))


def sorted_bias(logits, k):
    t, e = logits.shape
    return -jnp.sort(logits, axis=0)[t - _above(t, e, k)]


def sorted_choice(values, k):
    return jax.lax.top_k(values, k)[1]


def sorted_route(tokens, router, *, k, scale, scoring):
    """`moe._route` as the parent made it."""
    logits = jnp.dot(tokens.astype(jnp.float32), router,
                     precision=jax.lax.Precision.HIGHEST)
    bias = jax.vmap(lambda one: sorted_bias(one, k))(logits)
    chosen = sorted_choice(
        jax.lax.stop_gradient(logits + bias[:, None, :]), k)
    return chosen, moe_lib._gates(logits, chosen, scoring=scoring, scale=scale)


def _normal(t, e, seed=0):
    return np.random.default_rng(seed).standard_normal((t, e)).astype(
        np.float32)


def _planted(name):
    """``(logits [T, E], k)`` of one planted case."""
    if name == "constant_column":
        x = _normal(64, 8)
        x[:, 3] = 0.25
        return x, 2
    if name == "ties_across_the_threshold":
        # Integers between -2 and 2: every column's threshold value stands
        # many times on both sides of its rank.
        return np.clip(np.round(_normal(128, 12)), -2, 2), 3
    if name == "signed_zeros":
        x = _normal(64, 8)
        x[::2] = 0.0
        x[1::4] = -0.0
        return x, 4
    if name == "infinities":
        x = _normal(64, 8)
        x[:20, 0], x[:50, 1] = np.inf, np.inf
        x[:20, 2], x[:60, 3] = -np.inf, -np.inf
        x[:, 4] = np.inf
        x[:, 5] = -np.inf
        return x, 2
    if name == "denormals":
        x = _normal(64, 8) * np.float32(1e-41)
        assert np.all(np.abs(x) < np.finfo(np.float32).tiny) and np.any(x)
        return x, 2
    if name == "thirty_decades_apart":
        x = _normal(64, 8)
        x[::3] *= np.float32(1e30)
        x[1::3] *= np.float32(1e-30)
        return x, 2
    if name == "one_above":  # round(T k / E) is 0: the column's largest
        return _normal(4, 16), 2
    if name == "all_above":  # k == E: the column's smallest
        return _normal(37, 4), 4
    raise KeyError(name)


PLANTED = ("constant_column", "ties_across_the_threshold", "signed_zeros",
           "infinities", "denormals", "thirty_decades_apart", "one_above",
           "all_above")
NORMAL_SHAPES = [(64, 8, 2), (100, 12, 3), (4096, 72, 10), (8192, 320, 8)]


@pytest.mark.parametrize("t,e,k", NORMAL_SHAPES)
def test_level_bias_is_the_sorted_threshold_on_normal_logits(t, e, k):
    x = jnp.asarray(_normal(t, e, seed=t + e))
    got = jax.jit(lambda x: moe_lib.level_bias(x, k))(x)
    assert got.dtype == jnp.float32 and got.shape == (e,)
    np.testing.assert_array_equal(got, sorted_bias(x, k))


@pytest.mark.parametrize("name", PLANTED)
def test_level_bias_is_the_sorted_threshold_on_planted_logits(name):
    x, k = _planted(name)
    t, e = x.shape
    if name == "one_above":
        assert _above(t, e, k) == 1
    if name == "all_above":
        assert _above(t, e, k) == t
    got = np.asarray(moe_lib.level_bias(jnp.asarray(x), k))
    # numpy's sort is the oracle's oracle: it keeps denormals whatever the
    # backend's float comparisons flush.
    want = -np.sort(x, axis=0)[t - _above(t, e, k)]
    np.testing.assert_array_equal(got, want)
    if name != "denormals":
        np.testing.assert_array_equal(got, sorted_bias(jnp.asarray(x), k))
    # ... and the threshold is an element of its column.
    assert all(np.any(x[:, j] == -got[j]) for j in range(e))


@pytest.mark.parametrize("t,e,k", NORMAL_SHAPES[:3])
def test_level_bias_under_jit_and_the_layers_vmap(t, e, k):
    x = jnp.asarray(_normal(3 * t, e, seed=5).reshape(3, t, e))
    got = jax.jit(jax.vmap(lambda one: moe_lib.level_bias(one, k)))(x)
    want = jnp.stack([sorted_bias(one, k) for one in x])
    np.testing.assert_array_equal(got, want)


def test_order_key_keeps_the_floats_order_and_comes_back():
    x = np.array([-np.inf, -3e38, -1.0, -1e-30, -1e-45, -0.0, 0.0, 1e-45,
                  1e-30, 1.0, 3e38, np.inf], np.float32)
    key = np.asarray(moe_lib._order_key(jnp.asarray(x)))
    assert key.dtype == np.uint32 and np.all(np.diff(key.astype(np.int64)) > 0)
    back = np.asarray(moe_lib._key_value(jnp.asarray(key)))
    assert back.tobytes() == x.tobytes()


@pytest.mark.parametrize("t,e,k", NORMAL_SHAPES)
def test_largest_is_top_k_on_biased_normal_logits(t, e, k):
    x = jnp.asarray(_normal(t, e, seed=t + e + 1))
    values = x + sorted_bias(x, k)
    got = jax.jit(lambda v: moe_lib._largest(v, k))(values)
    assert got.dtype == jnp.int32 and got.shape == (t, k)
    np.testing.assert_array_equal(got, sorted_choice(values, k))


@pytest.mark.parametrize("name", [
    "tied_maxima", "one_value", "as_many_finite_as_k", "k_is_all",
    "batched"])
def test_largest_is_top_k_on_planted_rows(name):
    k = 3
    if name == "tied_maxima":  # a tie goes to the lower index, in order
        values = np.clip(np.round(_normal(256, 12, seed=3)), -1, 1) + 0.0
        assert any(np.sum(row == row.max()) > 1 for row in values)
    elif name == "one_value":
        values = np.full((5, 12), 0.5, np.float32)
    elif name == "as_many_finite_as_k":  # -inf is what a taken one becomes
        values = _normal(16, 8, seed=4)
        values[:, [0, 2, 5, 6, 7]] = -np.inf
    elif name == "k_is_all":
        values, k = _normal(16, 8, seed=6), 8
    else:
        values = _normal(2 * 3 * 64, 12, seed=7).reshape(2, 3, 64, 12)
    values = jnp.asarray(values, jnp.float32)
    got = moe_lib._largest(values, k)
    np.testing.assert_array_equal(got, sorted_choice(values, k))
    if name == "one_value":
        np.testing.assert_array_equal(got, np.tile(np.arange(k), (5, 1)))


@pytest.mark.parametrize("scoring", [moe_lib.SIGMOID, moe_lib.SOFTMAX])
@pytest.mark.parametrize("shape,e,k", [((2, 64, 16), 8, 2),
                                       ((1, 256, 32), 72, 10)])
def test_route_chooses_and_differentiates_as_the_sort_form(shape, e, k,
                                                           scoring):
    rng = np.random.default_rng(11)
    tokens = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    router = jnp.asarray(rng.standard_normal((shape[-1], e)) * 0.3,
                         jnp.float32)
    weight = jnp.asarray(rng.standard_normal((*shape[:2], k)), jnp.float32)

    def run(route):
        def loss(router, tokens):
            chosen, gates = route(tokens, router, k=k, scale=2.5,
                                  scoring=scoring)
            return jnp.sum(gates * weight), (chosen, gates)
        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(router, tokens)

    (_, (chosen, gates)), grads = run(moe_lib._route)
    (_, (want_chosen, want_gates)), want_grads = run(sorted_route)
    assert chosen.dtype == jnp.int32
    np.testing.assert_array_equal(chosen, want_chosen)
    np.testing.assert_array_equal(gates, want_gates)
    for got, want in zip(grads, want_grads):
        assert np.any(np.asarray(want, np.float32))
        np.testing.assert_array_equal(got, want)


def test_routed_path_holds_no_sort_of_the_logits():
    """What leaves `_route`'s jaxpr: no `sort`, no `top_k` primitive (the
    dispatch's `argsort` of the pair keys is outside `_route`)."""
    tokens, router = jnp.zeros((1, 64, 16)), jnp.zeros((16, 8))
    text = str(jax.make_jaxpr(
        lambda t, r: moe_lib._route(t, r, k=2, scale=1.0))(tokens, router))
    assert " sort[" not in text and "top_k" not in text
    assert " scan[" in text or " while[" in text  # the bisection's passes
