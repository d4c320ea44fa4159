"""Trainer end-to-end on the virtual 8-chip mesh: the full Horovod capability
set (bootstrap → sharded batch → pmean'd grads → update → callbacks) in one
jitted step (SURVEY.md §7.2 step 3's aha moment, minus real hardware)."""

import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvt
from horovod_tpu.models import MnistCNN


def make_data(n=256, seed=0):
    from horovod_tpu.data.datasets import _synth_mnist_split

    x, y = _synth_mnist_split(n, seed=seed)
    return (x[..., None] / 255.0).astype(np.float32), y


@pytest.fixture(scope="module")
def trained():
    hvt.init()
    x, y = make_data()
    trainer = hvt.Trainer(
        MnistCNN(),
        hvt.DistributedOptimizer(optax.adam(1e-3)),
        loss="sparse_categorical_crossentropy",
        seed=0,
    )
    history = trainer.fit(x=x, y=y, batch_size=4, epochs=5)
    return trainer, history, (x, y)


def test_loss_decreases(trained):
    _, history, _ = trained
    assert history[-1]["loss"] < history[0]["loss"]


def test_memorizes_small_set(trained):
    trainer, _, (x, y) = trained
    m = trainer.evaluate(x, y, batch_size=4)
    assert m["accuracy"] > 0.5  # 256 samples, 5 epochs: well above chance


def test_eval_handles_ragged_tail(trained):
    trainer, _, (x, y) = trained
    # 100 examples with global batch 32 -> padded tail; metrics must be exact
    full = trainer.evaluate(x[:100], y[:100], batch_size=4)
    manual_probs = trainer.predict(x[:100], batch_size=4)
    manual_acc = float((manual_probs.argmax(-1) == y[:100]).mean())
    assert full["accuracy"] == pytest.approx(manual_acc, abs=1e-6)


def test_predict_shape_and_normalization(trained):
    trainer, _, (x, _) = trained
    probs = trainer.predict(x[:33], batch_size=4)
    assert probs.shape == (33, 10)
    np.testing.assert_allclose(probs.sum(-1), np.ones(33), rtol=1e-4)


def test_onehot_loss_path():
    """mnist_keras.py:89 categorical_crossentropy + one-hot labels path."""
    hvt.init()
    x, y = make_data(64, seed=1)
    y1h = np.eye(10, dtype=np.float32)[y]
    trainer = hvt.Trainer(
        MnistCNN(),
        hvt.DistributedOptimizer(optax.adadelta(learning_rate=hvt.scale_lr(1.0))),
        loss="categorical_crossentropy",
    )
    hist = trainer.fit(x=x, y=y1h, batch_size=8, epochs=2)
    assert np.isfinite(hist[-1]["loss"])
    m = trainer.evaluate(x, y1h, batch_size=8)
    assert 0.0 <= m["accuracy"] <= 1.0


def test_dataset_idiom_with_steps_per_epoch():
    """TF2-script idiom: batched repeating dataset + steps_per_epoch=500//size
    (tensorflow2_keras_mnist.py:96)."""
    from horovod_tpu.data.loader import ArrayDataset

    hvt.init()
    x, y = make_data(128, seed=2)
    ds = ArrayDataset((x, y)).repeat().shuffle(128).batch(32)
    trainer = hvt.Trainer(MnistCNN(), hvt.DistributedOptimizer(optax.adam(1e-3)))
    steps = hvt.shard_steps(80)  # 80 // 8 = 10
    assert steps == 10
    hist = trainer.fit(ds, epochs=2, steps_per_epoch=steps)
    assert len(hist) == 2


def test_update_scale_controls_effective_lr():
    """The warmup knob: scale=0 must freeze parameters."""
    hvt.init()
    x, y = make_data(32, seed=3)
    trainer = hvt.Trainer(MnistCNN(), hvt.DistributedOptimizer(optax.adam(1e-2)))
    import jax

    trainer.build(x)
    before = jax.device_get(trainer.state.params)
    trainer.fit(x=x, y=y, batch_size=4, epochs=1, callbacks=[_FreezeScale()])
    after = jax.device_get(trainer.state.params)
    assert all(
        np.allclose(a, b)
        for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after))
    )


class _FreezeScale(hvt.callbacks.Callback):
    def on_epoch_begin(self, epoch, logs=None):
        self.trainer.update_scale = 0.0


class TestShardUpdate:
    """ZeRO-1 / cross-replica weight-update sharding (arXiv:2004.13336):
    replicated model, optimizer state sharded over the data axis — same
    math as pure DP, ~1/dp per-device optimizer memory."""

    def _data(self):
        from horovod_tpu.data import datasets

        (x, y), _ = datasets.mnist(cache_dir=None)
        return x[:256, ..., None], y[:256].astype(np.int32)

    def _trainer(self, **kw):
        from horovod_tpu.models.cnn import MnistCNN

        return hvt.Trainer(
            MnistCNN(),
            hvt.DistributedOptimizer(optax.adam(1e-3)),
            loss="sparse_categorical_crossentropy",
            **kw,
        )

    @pytest.mark.slow
    def test_matches_plain_dp_and_stays_sharded(self):
        import jax

        x, y = self._data()
        plain = self._trainer()
        zero1 = self._trainer(shard_update=True)
        h1 = plain.fit(x=x, y=y, batch_size=8, epochs=2, verbose=0)
        h2 = zero1.fit(x=x, y=y, batch_size=8, epochs=2, verbose=0)
        assert abs(h1[-1]["loss"] - h2[-1]["loss"]) < 1e-5
        for a, b in zip(
            jax.tree.leaves(plain.state.params),
            jax.tree.leaves(zero1.state.params),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
            )
        # The sharding survives the donated training steps.
        specs = {
            str(l.sharding.spec)
            for l in jax.tree.leaves(zero1.state.opt_state)
            if hasattr(l, "sharding") and l.ndim > 0
        }
        assert any("data" in s for s in specs), specs

    def test_per_device_optimizer_memory_shrinks(self):
        import jax

        x, y = self._data()
        zero1 = self._trainer(shard_update=True)
        zero1.build(x[:8])
        dp = zero1.mesh.shape["data"]
        assert dp == 8

        def fleet_bytes(tree):
            # ALL shards, replicas included: replicated state costs
            # dp × global here, sharded state ≈ 1 × global — so the bound
            # below actually fails if sharding regresses.
            total = 0
            for l in jax.tree.leaves(tree):
                if isinstance(l, jax.Array):
                    total += sum(
                        int(np.prod(sh.data.shape)) * l.dtype.itemsize
                        for sh in l.addressable_shards
                    )
            return total

        global_bytes = sum(
            int(np.prod(l.shape)) * l.dtype.itemsize
            for l in jax.tree.leaves(zero1.state.opt_state)
            if isinstance(l, jax.Array)
        )
        # Sharded leaves cost one global copy across the fleet; a fully-
        # replicated state would cost dp ×. Slack covers the replicated
        # scalar/odd-shaped leaves.
        assert fleet_bytes(zero1.state.opt_state) < 0.35 * dp * global_bytes

    def test_guards(self):
        from horovod_tpu.models.transformer import param_specs

        with pytest.raises(ValueError, match="fsdp"):
            self._trainer(shard_update=True, param_specs=param_specs)
        from horovod_tpu.models.cnn import MnistCNN

        # Wire compression COMPOSES with shard_update since ISSUE 10
        # (the explicit step reduces into the sharded layout; see
        # tests/test_zero1_compose.py for the equivalence matrix).
        tr = hvt.Trainer(
            MnistCNN(),
            hvt.DistributedOptimizer(
                optax.adam(1e-3), compression="bf16"
            ),
            loss="sparse_categorical_crossentropy",
            shard_update=True,
        )
        assert tr._comm_dtype is not None and tr._scatter > 1


class TestModuleLossBuildHint:
    """Regression for an earlier review's finding, the build() fallback: with loss='module' and no
    sample_y, labels are synthesized as zeros_like(sample_x) (the LM-family
    contract); a module whose labels differ in dtype/shape fails deep inside
    init — the re-raise must name the fix (pass sample_y)."""

    def _module(self):
        import flax.linen as nn
        import jax

        class IntLabelLoss(nn.Module):
            @nn.compact
            def __call__(self, x, train=False, labels=None):
                logits = nn.Dense(4)(x)
                ll = jax.nn.log_softmax(logits)
                # take_along_axis requires integer labels — the zeros_like
                # float fallback must blow up here.
                loss = -jnp.take_along_axis(ll, labels[:, None], axis=-1)[:, 0]
                correct = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
                return loss, correct

        return IntLabelLoss()

    def test_synthesized_labels_failure_carries_hint(self):
        trainer = hvt.Trainer(
            self._module(),
            hvt.DistributedOptimizer(optax.adam(1e-3)),
            loss="module",
        )
        x = np.random.RandomState(0).rand(4, 8).astype(np.float32)
        with pytest.raises(Exception, match="pass sample_y"):
            trainer.build(x)

    def test_sample_y_builds_fine(self):
        trainer = hvt.Trainer(
            self._module(),
            hvt.DistributedOptimizer(optax.adam(1e-3)),
            loss="module",
        )
        x = np.random.RandomState(0).rand(4, 8).astype(np.float32)
        y = np.zeros(4, np.int64)
        state = trainer.build(x, y)
        assert state is trainer.state


@pytest.mark.parametrize("n_devices", [8, 1])
def test_no_compile_option_off_tpu_or_on_one_device(n_devices):
    """The overlapped-reduction options are for a multi-chip TPU mesh; the
    CPU backend would refuse an ``xla_tpu_*`` option, and one device sums
    nothing across chips."""
    import jax

    from horovod_tpu.training import trainer as trainer_lib

    mesh = hvt.build_mesh(
        hvt.MeshSpec(data=n_devices), devices=jax.devices()[:n_devices])
    assert trainer_lib.training_compiler_options(mesh) == {}


def test_compile_options_on_a_multi_chip_tpu_mesh_only():
    """Chosen from the devices' platform and the mesh's size, nothing
    else: four TPUs get the table, one TPU and a mixed mesh do not."""
    import types

    from horovod_tpu.training import trainer as trainer_lib

    def mesh_of(*platforms):
        chips = np.empty(len(platforms), dtype=object)
        chips[:] = [types.SimpleNamespace(platform=p) for p in platforms]
        return types.SimpleNamespace(devices=chips)

    options = trainer_lib.training_compiler_options(mesh_of(*["tpu"] * 4))
    assert options == trainer_lib.OVERLAPPED_REDUCTION_OPTIONS
    assert options is not trainer_lib.OVERLAPPED_REDUCTION_OPTIONS
    assert trainer_lib.training_compiler_options(mesh_of("tpu")) == {}
    assert trainer_lib.training_compiler_options(
        mesh_of("tpu", "cpu")) == {}
