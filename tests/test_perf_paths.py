"""Tests for the performance-path machinery: steps_per_execution (scan-fused
multi-step executions), the device-resident dataset path
(`fit(cache='device')`), the background device prefetcher, and the
trace/FLOPs/MFU accounting."""

import os
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvt
from horovod_tpu import trace
from horovod_tpu.data.prefetch import DevicePrefetcher


class Probe(nn.Module):
    """Deterministic (dropout-free) classifier so execution strategies can be
    compared exactly."""

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        x = x.reshape((x.shape[0], -1)).astype(jnp.float32)
        x = nn.relu(nn.Dense(32)(x))
        return nn.Dense(10)(x)


def _digest(params):
    return float(sum(np.abs(l).sum() for l in jax.tree.leaves(jax.device_get(params))))


def _data(n=256, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 8, 8, 1).astype(np.float32)
    y = rng.randint(0, 10, size=n).astype(np.int32)
    return x, y


class TestStepsPerExecution:
    def _fit(self, spe, steps=8, epochs=2):
        x, y = _data()
        trainer = hvt.Trainer(
            Probe(),
            hvt.DistributedOptimizer(optax.sgd(0.05)),
            steps_per_execution=spe,
        )
        trainer.fit(
            x=x, y=y, batch_size=4, epochs=epochs, steps_per_epoch=steps,
            shuffle_buffer=1, verbose=0,
        )
        return trainer

    def test_fused_matches_per_step_math(self):
        """K steps fused in one scan must produce the same parameters as K
        separate step dispatches — fusion is an execution detail."""
        d1 = _digest(self._fit(1).state.params)
        d4 = _digest(self._fit(4).state.params)
        assert d1 == pytest.approx(d4, rel=1e-6)

    def test_remainder_chunk(self):
        """steps_per_epoch not divisible by K: a remainder chunk runs (and
        the epoch metric divisor stays the true step count)."""
        trainer = self._fit(4, steps=7, epochs=1)
        assert len(trainer.history) == 1
        d = _digest(trainer.state.params)
        assert d == pytest.approx(_digest(self._fit(1, steps=7, epochs=1).state.params), rel=1e-6)

    def test_callbacks_fire_once_per_execution(self):
        calls = []

        class Spy(hvt.callbacks.Callback):
            def on_batch_end(self, batch, logs=None):
                calls.append(batch)

        x, y = _data()
        trainer = hvt.Trainer(
            Probe(), hvt.DistributedOptimizer(optax.sgd(0.01)),
            steps_per_execution=4,
        )
        trainer.fit(
            x=x, y=y, batch_size=4, epochs=1, steps_per_epoch=8,
            callbacks=[Spy()], verbose=0,
        )
        assert calls == [3, 7]  # last step index of each execution


class TestDeviceCachedFit:
    def test_trains_and_caps_steps(self):
        x, y = _data(n=512)
        trainer = hvt.Trainer(Probe(), hvt.DistributedOptimizer(optax.adam(5e-3)))
        hist = trainer.fit(
            x=x, y=y, batch_size=4, epochs=3, cache="device", verbose=0,
        )
        assert len(hist) == 3
        # 512 examples / 8 shards / 4 per chip = 16 steps; loss must fall.
        assert hist[-1]["loss"] < hist[0]["loss"]

    def test_deterministic_for_seed(self):
        x, y = _data(n=256)

        def run():
            t = hvt.Trainer(
                Probe(), hvt.DistributedOptimizer(optax.sgd(0.05)), seed=3
            )
            t.fit(x=x, y=y, batch_size=4, epochs=2, cache="device", verbose=0)
            return _digest(t.state.params)

        assert run() == run()

    def test_epoch_visits_every_example_once(self):
        """The on-device permutation must be a true per-shard permutation:
        training on one epoch of one-hot rows with an SGD sum-style probe
        would be hard to observe, so instead check the gather directly — a
        'model' whose loss sums a per-example tag lets the epoch metric count
        every tag exactly once."""
        n = 128

        class TagSum(nn.Module):
            @nn.compact
            def __call__(self, x, *, train: bool = False):
                # Logits independent of params aren't differentiable; add a
                # zero-scaled param so grads exist.
                w = self.param("w", nn.initializers.zeros, (1,))
                return jnp.zeros((x.shape[0], 2)) + w * 0.0

        x = np.arange(n, dtype=np.float32).reshape(n, 1)  # tag = index
        y = np.zeros(n, dtype=np.int32)

        seen = []

        def tag_loss(logits, labels):
            return logits.sum(-1) * 0.0  # keep loss 0; accuracy unused

        trainer = hvt.Trainer(
            TagSum(), hvt.DistributedOptimizer(optax.sgd(0.0)), loss=tag_loss
        )
        # Instead of instrumenting the jit, verify via the staged layout +
        # permutation invariant: run the internal epoch fn and check each
        # shard's gathered indices form a permutation.
        data, per_shard = trainer._stage_device_dataset(x, y)
        assert per_shard == n // trainer.dp_size
        xs = np.asarray(jax.device_get(data[0]))
        # Staged rows partition the (truncated) dataset exactly once.
        assert sorted(xs.reshape(-1).tolist()) == list(range(n))


class TestDeviceCachedEvaluate:
    def _trainer(self, x, y):
        trainer = hvt.Trainer(Probe(), hvt.DistributedOptimizer(optax.adam(5e-3)))
        trainer.fit(x=x, y=y, batch_size=4, epochs=1, steps_per_epoch=4, verbose=0)
        return trainer

    def test_matches_streamed_evaluate(self):
        """Device-cached eval must reproduce the streamed path exactly,
        including the padded (non-divisible) tail."""
        x, y = _data(n=200)  # 200 is not a multiple of 8 shards x 4 batch
        trainer = self._trainer(x, y)
        streamed = trainer.evaluate(x, y, batch_size=4)
        cached = trainer.evaluate(x, y, batch_size=4, cache="device")
        assert cached["loss"] == pytest.approx(streamed["loss"], rel=1e-5)
        assert cached["accuracy"] == pytest.approx(streamed["accuracy"], rel=1e-6)
        # Second call reuses the staged set (same ids → one cache entry).
        trainer.evaluate(x, y, batch_size=4, cache="device")
        assert len(trainer._eval_cache) == 1

    def test_different_dataset_restages(self):
        x, y = _data(n=64)
        trainer = self._trainer(x, y)
        a = trainer.evaluate(x, y, batch_size=4, cache="device")
        x2, y2 = _data(n=64, seed=9)
        b = trainer.evaluate(x2, y2, batch_size=4, cache="device")
        assert len(trainer._eval_cache) == 2
        assert a != b  # different data, different result

    def test_validation_in_device_cached_fit(self):
        x, y = _data(n=256)
        xv, yv = _data(n=100, seed=5)
        trainer = hvt.Trainer(Probe(), hvt.DistributedOptimizer(optax.adam(5e-3)))
        hist = trainer.fit(
            x=x, y=y, batch_size=4, epochs=2, cache="device",
            validation_data=(xv, yv), verbose=0,
        )
        assert "val_loss" in hist[-1]
        ref = trainer.evaluate(xv, yv, batch_size=4)
        assert hist[-1]["val_loss"] == pytest.approx(ref["loss"], rel=1e-5)


class TestDevicePrefetcher:
    def test_order_and_values(self):
        out = list(DevicePrefetcher(iter(range(10)), lambda v: v * 2))
        assert out == [v * 2 for v in range(10)]

    def test_exception_propagates(self):
        def bad():
            yield 1
            raise RuntimeError("boom")

        pf = DevicePrefetcher(bad(), lambda v: v)
        assert next(pf) == 1
        with pytest.raises(RuntimeError, match="boom"):
            next(pf)

    def test_next_after_exception_stops_not_hangs(self):
        def bad():
            raise RuntimeError("dead")
            yield  # pragma: no cover

        pf = DevicePrefetcher(bad(), lambda v: v)
        with pytest.raises(RuntimeError):
            next(pf)
        with pytest.raises(StopIteration):  # not a deadlock
            next(pf)

    def test_close_unblocks_producer(self):
        def infinite():
            i = 0
            while True:
                yield i
                i += 1

        pf = DevicePrefetcher(infinite(), lambda v: v, depth=1)
        assert next(pf) == 0
        t0 = time.perf_counter()
        pf.close()
        assert time.perf_counter() - t0 < 5
        assert not pf._thread.is_alive()


class TestTraceAccounting:
    def test_peak_flops_none_on_cpu(self):
        assert trace.device_peak_flops(jax.devices()[0]) is None

    def test_resolved_peak_on_cpu_is_unknown_and_measures_nothing(
            self, monkeypatch):
        """No override, CPU platform: no peak — and no computation is
        run to make one up (a host matmul once stood in for it)."""
        monkeypatch.delenv("HVT_PEAK_FLOPS", raising=False)

        def refuse(*a, **k):
            raise AssertionError("resolve_peak_flops compiled something")

        monkeypatch.setattr(jax, "jit", refuse)
        assert trace.resolve_peak_flops() == (None, "unknown")

    def test_resolved_peak_refuses_an_unknown_accelerator(self,
                                                          monkeypatch):
        class FakeDev:
            device_kind = "NPU 9000"
            platform = "npu"

        monkeypatch.delenv("HVT_PEAK_FLOPS", raising=False)
        monkeypatch.setattr(jax, "devices", lambda *a: [FakeDev()])
        with pytest.raises(ValueError, match="NPU 9000"):
            trace.resolve_peak_flops()

    def test_mfu_math(self):
        class FakeDev:
            device_kind = "TPU v5 lite"

        # 197e12 peak: 1.97e12 flops in 0.01 s on 1 chip = 100% of peak.
        assert trace.mfu(1.97e12, 0.01, 1, device=FakeDev()) == pytest.approx(1.0)
        assert trace.mfu(None, 0.01) is None

    def test_compiled_flops_positive_or_none(self):
        f = jax.jit(lambda a, b: a @ b)
        a = jnp.ones((64, 64), jnp.float32)
        flops = trace.compiled_flops(f, a, a)
        if flops is not None:  # CPU backends may not report
            assert flops >= 2 * 64**3 * 0.9

    def test_profile_env_wiring(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HVT_PROFILE", str(tmp_path / "prof"))
        assert trace.profile_dir() == str(tmp_path / "prof")
        x, y = _data(n=64)
        trainer = hvt.Trainer(Probe(), hvt.DistributedOptimizer(optax.sgd(0.01)))
        trainer.fit(x=x, y=y, batch_size=4, epochs=1, steps_per_epoch=2, verbose=0)
        # jax.profiler wrote a trace tree under the requested directory.
        assert (tmp_path / "prof").exists()
        assert any((tmp_path / "prof").rglob("*"))

    def test_maybe_trace_noop_without_dir(self):
        with trace.maybe_trace(None):
            pass


class TestEpochShuffleMaterialization:
    """The round-3 input-leg fix: the epoch permutation is applied ONCE as a
    prefix gather and steps read contiguous slices — semantics must be
    unchanged and the gather must cover only the consumed prefix."""

    def test_capped_steps_consume_prefix_only(self):
        """steps_per_epoch below the full epoch must still train (the
        shuffled copy is sized to steps * batch, the review-found waste) and
        produce finite falling loss."""
        x, y = _data(n=512)
        trainer = hvt.Trainer(
            Probe(), hvt.DistributedOptimizer(optax.adam(5e-3))
        )
        hist = trainer.fit(
            x=x, y=y, batch_size=4, epochs=2, steps_per_epoch=3,
            cache="device", verbose=0,
        )
        assert len(hist) == 2
        assert np.isfinite(hist[-1]["loss"])
        assert hist[-1]["loss"] < hist[0]["loss"]

    def test_device_cached_epoch_covers_each_example_once(self):
        """One epoch of the device-cached path must see each example exactly
        once (permutation through the materialized copy) — train a sum-probe
        whose gradient accumulates the example tags; after one epoch the
        param equals the sum over ALL tags regardless of order."""

        class SumProbe(nn.Module):
            @nn.compact
            def __call__(self, x, *, train: bool = False):
                w = self.param("w", nn.initializers.zeros, (1,))
                # loss gradient d/dw = -mean(x) per batch; with SGD lr 1 and
                # steps covering the epoch, w accumulates batch means.
                return jnp.broadcast_to(
                    (w * x.sum(-1, keepdims=True)), (x.shape[0], 2)
                )

        n = 64
        x = np.arange(1, n + 1, dtype=np.float32).reshape(n, 1)
        y = np.zeros(n, dtype=np.int32)

        def loss(logits, labels):
            return logits[:, 0]  # d/dw = x per example

        tr = hvt.Trainer(
            SumProbe(), hvt.DistributedOptimizer(optax.sgd(1.0)), loss=loss
        )
        tr.fit(
            x=x, y=y, batch_size=2, epochs=1, cache="device", verbose=0,
        )
        # 4 steps x global batch 16 = the full epoch; each step's update is
        # -lr * mean(batch tags); summed over a permutation of ALL tags the
        # total is -sum(tags)/global_batch regardless of shuffle order.
        expected = -np.sum(np.arange(1, n + 1)) / 16.0
        got = float(np.asarray(jax.device_get(tr.state.params["w"]))[0])
        np.testing.assert_allclose(got, expected, rtol=1e-5)
