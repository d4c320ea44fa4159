"""Overlapped boundary reduction + quantized (int8/fp8) wire compression.

PR 7's proof obligations:

* The overlapped step (last microbatch peeled out of the accumulation
  scan, bucket reductions issued in its straight-line region, reverse
  bucket order) is NUMERICALLY EQUIVALENT to the serialized post-scan
  reduction — same grads to the optimizer across K x bucket_bytes x
  compression.
* int8/fp8 wires really change the emitted collective: the reduction is a
  gather-sum whose payload element type is i8 / f8E4M3, with no
  gradient-shaped f32 all-reduce left.
* Error feedback telescopes: over T steps the accumulated quantization
  error is bounded by ONE step's quantum (|psum(r_T)|), not T of them —
  the bias does not compound.
* The error-feedback residual lives in opt_state (`ErrorFeedbackState`),
  survives a checkpoint save/restore roundtrip, and an elastic reshard
  re-cuts it mass-conserving.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvt
from horovod_tpu import checkpoint
from horovod_tpu.analysis import hlo_audit, registry
from horovod_tpu.analysis.step_probe import lowered_step_text
from horovod_tpu.parallel import collectives, mesh as mesh_lib
from horovod_tpu.training.optimizer import (
    ErrorFeedbackState,
    compression_error_feedback,
)


class Probe(nn.Module):
    @nn.compact
    def __call__(self, x, *, train: bool = False):
        x = x.reshape((x.shape[0], -1)).astype(jnp.float32)
        return nn.Dense(10)(nn.relu(nn.Dense(32)(x)))


def _data(n=256, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 8, 8, 1).astype(np.float32)
    y = rng.randint(0, 10, n).astype(np.int32)
    return x, y


def _trainer(k=1, compression="none", overlap=None, bucket_bytes=None,
             bucket_order=None, error_feedback=True, seed=3):
    tx = hvt.DistributedOptimizer(
        optax.adam(1e-3), backward_passes_per_step=k,
        average_aggregated_gradients=True, compression=compression,
        error_feedback=error_feedback,
    )
    return hvt.Trainer(
        Probe(), tx, seed=seed, bucket_bytes=bucket_bytes,
        overlap_reduction=overlap, bucket_order=bucket_order,
    )


def _fit_params(tr, x, y, k, steps=4):
    tr.fit(x=x, y=y, batch_size=max(1, 8 // k), epochs=1,
           steps_per_epoch=steps, shuffle_buffer=1, verbose=0)
    return jax.tree.leaves(jax.device_get(tr.state.params))


# Lowered-step plumbing + the gradient-traffic discrimination live in
# `analysis.step_probe` / `analysis.hlo_audit` since PR 9 (one
# implementation, shared with `hvt-audit`).


class TestOverlapEquivalence:
    @pytest.mark.parametrize(
        "k,bucket_bytes,compression",
        [
            (1, None, "none"),
            (4, None, "none"),
            (4, 1024, "none"),
            (4, 1024, "bf16"),
            (1, 1024, "int8"),
            (4, 1024, "int8"),
        ],
    )
    def test_same_grads_to_optimizer(self, k, bucket_bytes, compression):
        """THE acceptance property: overlap on vs off changes compiled
        STRUCTURE only — same addition order, same bucket contents — so
        the trained parameters must agree to float-scheduling noise on
        every (K, bucket_bytes, compression) combination."""
        x, y = _data()
        p_on = _fit_params(
            _trainer(k, compression, overlap=True,
                     bucket_bytes=bucket_bytes), x, y, k,
        )
        p_off = _fit_params(
            _trainer(k, compression, overlap=False,
                     bucket_bytes=bucket_bytes), x, y, k,
        )
        for a, b in zip(p_on, p_off):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)

    def test_reverse_vs_forward_bucket_order_identical(self):
        """Reverse issue order re-partitions the leaves into different
        buckets, but a psum is elementwise — the reduced VALUES cannot
        depend on bucket boundaries for non-quantized wires."""
        x, y = _data()
        p_rev = _fit_params(
            _trainer(4, bucket_bytes=1024, bucket_order="reverse"), x, y, 4
        )
        p_fwd = _fit_params(
            _trainer(4, bucket_bytes=1024, bucket_order="forward"), x, y, 4
        )
        for a, b in zip(p_rev, p_fwd):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_overlap_peels_last_microbatch_out_of_scan(self):
        """Structural: at K=2 the overlapped step has NO accumulation scan
        left (microbatch 0 inline, microbatch 1 peeled) while the
        serialized step scans — visible as strictly fewer while ops in
        the lowered text."""
        x, y = _data()
        whiles_on = hlo_audit.while_count(lowered_step_text(
            _trainer(2, "bf16", overlap=True), x, y, 2
        ))
        whiles_off = hlo_audit.while_count(lowered_step_text(
            _trainer(2, "bf16", overlap=False), x, y, 2
        ))
        assert whiles_on < whiles_off

    def test_one_reduction_per_step_still_holds(self):
        """Overlap must not reintroduce per-microbatch communication: the
        K=4 overlapped step still carries exactly the bucket count of
        gradient-shaped collectives (one here — default bucket bytes)."""
        x, y = _data()
        hlo_audit.assert_program(
            lowered_step_text(_trainer(4, "bf16", overlap=True), x, y, 4),
            "one-reduction,wire=bf16",
        )

    def test_knob_defaults(self, monkeypatch):
        assert _trainer()._overlap is True  # HVT_OVERLAP_REDUCTION default
        assert _trainer()._bucket_reverse is True  # HVT_BUCKET_ORDER default
        monkeypatch.setenv("HVT_OVERLAP_REDUCTION", "0")
        assert _trainer()._overlap is False
        monkeypatch.setenv("HVT_BUCKET_ORDER", "forward")
        assert _trainer()._bucket_reverse is False

    def test_bad_bucket_order_is_loud(self):
        with pytest.raises(ValueError, match="bucket_order"):
            _trainer(bucket_order="sideways")


class TestQuantizedWire:
    def test_int8_wire_is_int8_on_the_wire(self):
        """The lowered int8 step's gradient traffic is the per-bucket
        payload gather in i8 (the rank-1 f32 scale gather stays out of
        the count); no gradient-shaped f32 all_reduce remains."""
        x, y = _data()
        hlo_audit.assert_program(
            lowered_step_text(_trainer(2, "int8"), x, y, 2),
            "one-reduction,wire=int8",
        )

    def test_fp8_wire_is_f8_on_the_wire(self):
        x, y = _data()
        hlo_audit.assert_program(
            lowered_step_text(_trainer(2, "fp8"), x, y, 2),
            "one-reduction,wire=fp8",
        )

    def test_quantized_with_axis_name_rejected(self):
        with pytest.raises(ValueError, match="overflow"):
            hvt.DistributedOptimizer(
                optax.adam(1e-3), axis_name="data", compression="int8"
            )

    @pytest.mark.parametrize("wire", [jnp.int8, jnp.float8_e4m3fn])
    def test_error_feedback_telescopes(self, wire):
        """EF's defining property, asserted deterministically at the
        collectives level: feeding the SAME per-shard gradients for T
        rounds while carrying the residual, the summed outputs differ from
        T x the true sum by at most |psum(r_T)| — ONE round's quantization
        quantum, not T of them (the errors telescope)."""
        hvt.init()
        mesh = mesh_lib.data_parallel_mesh()
        P = jax.sharding.PartitionSpec

        def one_round(v, r):
            out, new_r = collectives.reduce_gradients(
                {"g": v}, data_axis="data", extra_axes=("fsdp",),
                wire_dtype=wire, bucket_bytes=1 << 20,
                residual={"g": r},
            )
            return out["g"], new_r["g"]

        f = jax.jit(jax.shard_map(
            one_round, mesh=mesh,
            in_specs=(P(("data", "fsdp")), P(("data", "fsdp"))),
            out_specs=(P(("data", "fsdp")), P(("data", "fsdp"))),
            check_vma=False,
        ))
        rng = np.random.RandomState(0)
        v = jnp.asarray(rng.randn(8, 64).astype(np.float32))
        r = jnp.zeros_like(v)
        T = 6
        acc = np.zeros((8, 64), np.float32)
        for _ in range(T):
            out, r = f(v, r)
            acc += np.asarray(out)
        true = np.broadcast_to(np.asarray(v).sum(0, keepdims=True), v.shape)
        # The telescoping IDENTITY: out_t = psum(Q(g + r_t)) and
        # r_{t+1} = g + r_t - Q(g + r_t), so sum_t out_t = T*true -
        # psum(r_T) exactly — the accumulated error is ONE final
        # residual, not T rounds' worth.
        r_np = np.asarray(r)  # global view: row s = shard s's residual
        np.testing.assert_allclose(
            (T * true - acc)[0], r_np.sum(axis=0), rtol=1e-3, atol=1e-4
        )
        # And that final residual is single-round-sized: per element at
        # most one rounding quantum of the wire format (int8: half-grid
        # amax/127 with slack; e4m3 fp8: relative ulp 2^-3 of the top
        # bin, amax/16 absolute), summed over the 8 shards — a bound T
        # independent no-feedback rounds would exceed T-fold.
        amax = float(np.abs(np.asarray(v)).max())
        quantum = amax / 127.0 if wire == jnp.int8 else amax / 16.0
        bound = 8 * quantum + 1e-5
        np.testing.assert_array_less(np.abs(acc - T * true), bound)

    def test_residual_lives_in_opt_state_and_updates(self):
        x, y = _data()
        tr = _trainer(2, "int8")
        assert tr._ef and compression_error_feedback.__name__  # wired
        tr.fit(x=x, y=y, batch_size=4, epochs=1, steps_per_epoch=2,
               shuffle_buffer=1, verbose=0)
        opt_state = tr.state.opt_state
        assert isinstance(opt_state, ErrorFeedbackState)
        res = jax.device_get(opt_state.ef_residual)
        dp = tr.dp_size
        for leaf, p in zip(
            jax.tree.leaves(res), jax.tree.leaves(tr.state.params)
        ):
            assert leaf.shape == (dp,) + p.shape
            assert leaf.dtype == np.float32
        # After real steps the untransmitted remainder is nonzero.
        assert any(np.abs(l).max() > 0 for l in jax.tree.leaves(res))

    def test_error_feedback_off_keeps_plain_opt_state(self):
        tr = _trainer(2, "int8", error_feedback=False)
        assert not tr._ef
        x, _ = _data(16)
        tr.build(x[:8])
        assert not isinstance(tr.state.opt_state, ErrorFeedbackState)

    def test_loss_tracks_uncompressed(self):
        """int8+EF is lossy in the last bits, not in convergence: after a
        few steps the loss tracks the uncompressed run."""
        x, y = _data()
        l_q = _fit_params  # appease linters; real check below
        t_q = _trainer(1, "int8")
        t_f = _trainer(1, "none")
        h_q = t_q.fit(x=x, y=y, batch_size=8, epochs=1, steps_per_epoch=8,
                      shuffle_buffer=1, verbose=0)
        h_f = t_f.fit(x=x, y=y, batch_size=8, epochs=1, steps_per_epoch=8,
                      shuffle_buffer=1, verbose=0)
        assert abs(h_q[-1]["loss"] - h_f[-1]["loss"]) / max(
            abs(h_f[-1]["loss"]), 1e-6
        ) < 0.1

    def test_device_cached_path_composes(self):
        x, y = _data(512)
        tr = _trainer(2, "int8")
        hist = tr.fit(x=x, y=y, batch_size=2, epochs=3, cache="device",
                      verbose=0)
        assert hist[-1]["loss"] < hist[0]["loss"]


class TestResidualStateSurfaces:
    def _trained(self, steps=2):
        x, y = _data()
        tr = _trainer(2, "int8")
        tr.fit(x=x, y=y, batch_size=4, epochs=1, steps_per_epoch=steps,
               shuffle_buffer=1, verbose=0)
        return tr

    def test_checkpoint_roundtrip_preserves_residual(self, tmp_path):
        tr = self._trained()
        path = str(tmp_path / "state.msgpack")
        checkpoint.save(path, tr.state)
        tr2 = _trainer(2, "int8")
        x, y = _data()
        tr2.build(x[:8], y[:8])
        restored = checkpoint.restore(path, tr2.state)
        a = jax.device_get(tr.state.opt_state.ef_residual)
        b = jax.device_get(restored.opt_state.ef_residual)
        jax.tree.map(
            lambda u, v: np.testing.assert_array_equal(u, v), a, b
        )

    def test_elastic_reshard_conserves_residual_mass(self):
        """install_state with a snapshot from a DIFFERENT world size: the
        residual's leading (shard) axis is re-cut mass-conserving — the
        old shards' remainders sum-redistribute over the new axis (there
        is no per-shard ground truth after a reshard; EF correctness
        only needs the total eventually added back)."""
        tr = self._trained()
        snap = jax.device_get(tr.state)
        # Fake an old 2-shard world's residual with known mass.
        old = jax.tree.map(
            lambda p: np.stack([
                np.full(p.shape, 1.0, np.float32),
                np.full(p.shape, 3.0, np.float32),
            ]),
            jax.device_get(tr.state.params),
        )
        snap = snap.replace(
            opt_state=snap.opt_state.replace(ef_residual=old)
        )
        installed = tr.install_state(snap)
        res = jax.device_get(installed.opt_state.ef_residual)
        dp = tr.dp_size
        for leaf in jax.tree.leaves(res):
            # total mass 4.0 per element, spread evenly over dp shards
            np.testing.assert_allclose(leaf.sum(axis=0), 4.0, rtol=1e-6)
            np.testing.assert_allclose(leaf, 4.0 / dp, rtol=1e-6)

    def test_same_world_snapshot_installs_verbatim(self):
        tr = self._trained()
        snap = jax.device_get(tr.state)
        want = jax.tree.map(np.asarray, snap.opt_state.ef_residual)
        installed = tr.install_state(snap)
        got = jax.device_get(installed.opt_state.ef_residual)
        jax.tree.map(
            lambda u, v: np.testing.assert_array_equal(u, v), want, got
        )


class TestKnobRegistry:
    @pytest.mark.parametrize("name", [
        "HVT_OVERLAP_REDUCTION", "HVT_BUCKET_ORDER", "HVT_PREFETCH_DEPTH",
        "HVT_COMPRESSION", "HVT_COMPRESSION_ICI", "HVT_PEAK_FLOPS",
    ])
    def test_new_knobs_declared(self, name):
        assert registry.is_registered(name)

    def test_prefetch_depth_feeds_streamed_fit(self, monkeypatch):
        monkeypatch.setenv("HVT_PREFETCH_DEPTH", "3")
        x, y = _data(64)
        tr = _trainer()
        hist = tr.fit(x=x, y=y, batch_size=8, epochs=1, steps_per_epoch=4,
                      shuffle_buffer=1, verbose=0)
        assert np.isfinite(hist[-1]["loss"])
