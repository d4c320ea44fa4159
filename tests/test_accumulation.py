"""Gradient accumulation + bucketed/hierarchical boundary reduction.

The Horovod-parity accumulation contract, trainer-native: with
``DistributedOptimizer(backward_passes_per_step=K)`` the Trainer runs K
microbatch forward/backward passes inside ONE compiled step — local grads
accumulate in f32 on device — with exactly one cross-worker reduction and
one optimizer apply per K passes. The boundary reduction is bucket-fused
(Horovod tensor-fusion semantics, `collectives.flatten_buckets`) and, on a
multi-slice mesh, hierarchical: ICI sub-axis in full precision, DCN
sub-axis in the compression dtype (`collectives.hierarchical_psum`,
EQuARX-style DCN-only quantization).

Proof obligations (the PR's acceptance criteria):
* K-microbatch loss trajectory ≡ one K·B-batch run (rel 1e-4).
* Exactly one gradient reduction per OPTIMIZER step in the compiled step's
  collectives, independent of K.
* Bucketed reduction issues ≤ ceil(total_bytes/bucket_bytes) + n_dtypes
  collectives; round-trips arbitrary pytrees exactly.
* Hierarchical == flat psum on a fake 2-slice topology.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvt
from horovod_tpu.analysis import hlo_audit
from horovod_tpu.analysis.step_probe import lowered_step_text
from horovod_tpu.parallel import collectives, mesh as mesh_lib
from horovod_tpu.training.optimizer import accumulation_spec


class MnistConvNet(nn.Module):
    """The MNIST config's 2-conv CNN (tensorflow2_keras_mnist.py:43-52)
    minus dropout: the trajectory-equivalence bound is rel 1e-4, and
    dropout masks are drawn per microbatch on the accumulating path vs per
    global batch on the SPMD path — real (intended) sampling divergence
    that would drown the numeric property under test."""

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        x = x.astype(jnp.float32)
        x = nn.relu(nn.Conv(32, (3, 3), padding="VALID")(x))
        x = nn.relu(nn.Conv(64, (3, 3), padding="VALID")(x))
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(128)(x))
        return nn.Dense(10)(x)


class Probe(nn.Module):
    """Tiny deterministic classifier for the cheap structural tests."""

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        x = x.reshape((x.shape[0], -1)).astype(jnp.float32)
        return nn.Dense(10)(nn.relu(nn.Dense(32)(x)))


def _mnist_data(n=256, seed=0):
    from horovod_tpu.data.datasets import _synth_mnist_split

    x, y = _synth_mnist_split(n, seed=seed)
    return (x[..., None] / 255.0).astype(np.float32), y.astype(np.int32)


def _probe_data(n=256, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 8, 8, 1).astype(np.float32)
    y = rng.randint(0, 10, n).astype(np.int32)
    return x, y


def _trainer(module, k=1, compression="none", bucket_bytes=None, seed=3,
             **opt_kw):
    tx = hvt.DistributedOptimizer(
        optax.adam(1e-3), backward_passes_per_step=k,
        compression=compression, **opt_kw,
    )
    return hvt.Trainer(module, tx, seed=seed, bucket_bytes=bucket_bytes)


# The lowered-step plumbing and the gradient-traffic discrimination are
# `analysis.step_probe.lowered_step_text` + `analysis.hlo_audit` since
# PR 9 — one implementation, shared with `hvt-audit`.


class TestTrajectoryEquivalence:
    def test_k4_microbatches_match_single_kb_batch(self):
        """The acceptance bound: K=4 microbatches of per-chip batch B,
        averaged (average_aggregated_gradients=True), must trace the SAME
        loss trajectory as one K·B-batch run within rel 1e-4 on the MNIST
        config — same data order (shuffle_buffer=1), same seed, same
        optimizer."""
        x, y = _mnist_data()
        acc = _trainer(
            MnistConvNet(), k=4, average_aggregated_gradients=True
        )
        h_acc = acc.fit(
            x=x, y=y, batch_size=1, epochs=2, steps_per_epoch=8,
            shuffle_buffer=1, verbose=0,
        )
        plain = _trainer(MnistConvNet(), k=1)
        h_plain = plain.fit(
            x=x, y=y, batch_size=4, epochs=2, steps_per_epoch=8,
            shuffle_buffer=1, verbose=0,
        )
        for a, b in zip(h_acc, h_plain):
            assert a["loss"] == pytest.approx(b["loss"], rel=1e-4)
        # Secondary sanity on the weights themselves: Adam divides by
        # sqrt(v), amplifying f32 grad-sum noise on near-zero params, so
        # the bound here is looser than the loss-trajectory acceptance.
        for pa, pb in zip(
            jax.tree.leaves(jax.device_get(acc.state.params)),
            jax.tree.leaves(jax.device_get(plain.state.params)),
        ):
            np.testing.assert_allclose(pa, pb, rtol=2e-3, atol=5e-4)

    def test_sum_semantics_is_horovod_default(self):
        """Without average_aggregated_gradients the K grads SUM: one SGD
        accumulation cycle moves the weights exactly K times as far as the
        averaged cycle."""
        x, y = _probe_data(64)

        def one_cycle(**kw):
            t = hvt.Trainer(
                Probe(),
                hvt.DistributedOptimizer(
                    optax.sgd(0.1), backward_passes_per_step=4, **kw
                ),
                seed=3,
            )
            t.fit(x=x, y=y, batch_size=1, epochs=1, steps_per_epoch=1,
                  shuffle_buffer=1, verbose=0)
            return jax.device_get(jax.tree.leaves(t.state.params)[0])

        init = hvt.Trainer(
            Probe(), hvt.DistributedOptimizer(optax.sgd(0.1)), seed=3
        )
        init.build(x[:8])
        w0 = jax.device_get(jax.tree.leaves(init.state.params)[0])
        w_sum = one_cycle()
        w_mean = one_cycle(average_aggregated_gradients=True)
        np.testing.assert_allclose(
            w_sum - w0, 4.0 * (w_mean - w0), rtol=1e-5, atol=1e-7
        )

    def test_device_cached_path_accumulates(self):
        """fit(cache='device') with K: each scanned optimizer step consumes
        K·B examples per shard and the run still learns."""
        x, y = _probe_data(512)
        t = _trainer(Probe(), k=4, average_aggregated_gradients=True)
        hist = t.fit(
            x=x, y=y, batch_size=2, epochs=4, cache="device", verbose=0
        )
        assert hist[-1]["loss"] < hist[0]["loss"]

    def test_steps_per_execution_composes(self):
        """spe > 1 (scan-fused executions) stacks [spe, K, ...] and must
        match the unfused accumulating run parameter-for-parameter."""
        x, y = _probe_data()
        a = _trainer(Probe(), k=2, average_aggregated_gradients=True)
        a.fit(x=x, y=y, batch_size=2, epochs=2, steps_per_epoch=6,
              shuffle_buffer=1, verbose=0)
        b = hvt.Trainer(
            Probe(),
            hvt.DistributedOptimizer(
                optax.adam(1e-3), backward_passes_per_step=2,
                average_aggregated_gradients=True,
            ),
            seed=3, steps_per_execution=3,
        )
        b.fit(x=x, y=y, batch_size=2, epochs=2, steps_per_epoch=6,
              shuffle_buffer=1, verbose=0)
        for pa, pb in zip(
            jax.tree.leaves(jax.device_get(a.state.params)),
            jax.tree.leaves(jax.device_get(b.state.params)),
        ):
            np.testing.assert_allclose(pa, pb, rtol=1e-6, atol=1e-7)


class TestOneReductionPerStep:
    def test_single_gradient_reduction_independent_of_k(self):
        """THE acceptance assertion: the compiled optimizer step carries
        exactly ONE gradient-shaped collective — the bucketed boundary
        reduction — no matter how many microbatch passes scan inside it
        (default bucket bytes hold the whole Probe gradient)."""
        x, y = _probe_data()
        for k in (2, 4):
            tr = _trainer(Probe(), k=k)
            hlo_audit.assert_program(
                lowered_step_text(tr, x, y, k), "one-reduction"
            )

    def test_implicit_spmd_path_untouched(self):
        """Control: the default K=1, no-compression step still has NO
        explicit collective (XLA places the reduction at partitioning) —
        accumulation machinery must not leak into the default path."""
        x, y = _probe_data()
        tr = _trainer(Probe(), k=1)
        hlo_audit.assert_program(
            lowered_step_text(tr, x, y, 1), "no-collectives"
        )

    def test_compression_composes_on_boundary_only(self):
        """compression='bf16' + K=4: every gradient-shaped reduction is
        bf16 (the single boundary reduction compressed), none f32 — the
        16-bit cost is paid once per K passes, not per microbatch."""
        x, y = _probe_data()
        tr = _trainer(Probe(), k=4, compression="bf16")
        hlo_audit.assert_program(
            lowered_step_text(tr, x, y, 4), "one-reduction,wire=bf16"
        )

    def test_bucket_count_tracks_bucket_bytes(self):
        """With bucket_bytes forcing multiple buckets, the reduction count
        equals the bucket count and respects the ceil(total/bytes) +
        n_dtypes bound."""
        x, y = _probe_data()
        # Probe grads (f32): 64·32 + 32 + 32·10 + 10 = 2410 params.
        total = (64 * 32 + 32 + 32 * 10 + 10) * 4
        bucket_bytes = 4096
        tr = _trainer(Probe(), k=2, bucket_bytes=bucket_bytes)
        expected = -(-total // bucket_bytes)  # ceil; one dtype → 3
        assert expected == 3
        hlo_audit.assert_program(
            lowered_step_text(tr, x, y, 2), f"reductions={expected}"
        )


class TestBucketRoundTrip:
    @pytest.mark.parametrize("bucket_bytes", [1, 64, 4096, 1 << 26])
    def test_arbitrary_pytree_round_trips(self, bucket_bytes):
        """Property: flatten→unflatten is the identity for mixed-dtype
        pytrees with 0-d leaves, any bucket size."""
        rng = np.random.RandomState(0)
        tree = {
            "conv": {"kernel": rng.randn(3, 3, 4, 8).astype(np.float32),
                     "bias": rng.randn(8).astype(np.float32)},
            "scale": np.float32(rng.randn()),          # 0-d leaf
            "table": rng.randn(16, 5).astype(np.float16),
            "counts": rng.randint(0, 9, (7,)).astype(np.int32),
            "step": np.int32(42),                      # 0-d int leaf
            "list": [rng.randn(2, 2).astype(np.float32),
                     rng.randn(5).astype(np.float16)],
        }
        buckets, spec = collectives.flatten_buckets(tree, bucket_bytes)
        out = collectives.unflatten_buckets(buckets, spec)
        jax.tree.map(
            lambda a, b: (
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
                # dtype and shape restored exactly
                self_check(a, b),
            ),
            tree, out,
        )

    def test_bucket_count_bound(self):
        rng = np.random.RandomState(1)
        tree = {
            "a": rng.randn(1000).astype(np.float32),   # 4000 B
            "b": rng.randn(300).astype(np.float32),    # 1200 B
            "c": rng.randn(100).astype(np.float16),    # 200 B
        }
        bucket_bytes = 1024
        buckets, _ = collectives.flatten_buckets(tree, bucket_bytes)
        total = 4000 + 1200 + 200
        n_dtypes = 2
        assert len(buckets) <= -(-total // bucket_bytes) + n_dtypes - 1 + 1
        # exact: ceil(5200/1024)=6 f32 buckets + 1 f16 bucket
        assert len(buckets) == 7

    def test_dtype_homogeneous(self):
        tree = {"f": np.ones(4, np.float32), "h": np.ones(4, np.float16),
                "i": np.ones(4, np.int32)}
        buckets, _ = collectives.flatten_buckets(tree, 1 << 20)
        assert sorted(str(b.dtype) for b in buckets) == [
            "float16", "float32", "int32"
        ]

    def test_empty_tree(self):
        buckets, spec = collectives.flatten_buckets({}, 1024)
        assert buckets == []
        assert collectives.unflatten_buckets(buckets, spec) == {}

    def test_bad_bucket_bytes(self):
        with pytest.raises(ValueError, match="positive"):
            collectives.flatten_buckets({"a": np.ones(2)}, 0)

    def test_mismatched_spec_is_loud(self):
        buckets, spec = collectives.flatten_buckets(
            {"a": np.ones(4, np.float32)}, 1 << 20
        )
        with pytest.raises(ValueError, match="do not match"):
            collectives.unflatten_buckets(buckets + [jnp.ones(2)], spec)


def self_check(a, b):
    assert np.asarray(a).shape == np.asarray(b).shape
    assert np.asarray(a).dtype == np.asarray(b).dtype


class TestHierarchicalReduction:
    """hierarchical_psum / reduce_gradients on a fake multi-slice topology:
    the 8-device test mesh's data axis factored (dcn outer, ici inner)."""

    def _run(self, fn, x):
        mesh = mesh_lib.data_parallel_mesh()
        P = jax.sharding.PartitionSpec
        return jax.jit(
            jax.shard_map(
                fn, mesh=mesh,
                in_specs=(P(("data", "fsdp")),),
                out_specs=P(("data", "fsdp")),
                check_vma=False,
            )
        )(x)

    @pytest.mark.parametrize("dcn", [2, 4, 8])
    def test_matches_flat_psum_in_f32(self, dcn):
        """Acceptance: the two-hop reduction == the flat psum on a fake
        multi-slice factoring. Sum associativity makes the two exact in
        real arithmetic; in f32 only the ADDITION ORDER differs (partials
        within a slice first), so the bound is float-rounding-tight, far
        under any wire-compression effect."""
        hvt.init()
        x = jnp.asarray(
            np.random.RandomState(0).randn(8, 16).astype(np.float32)
        )

        def hier(v):
            return collectives.hierarchical_psum(
                v, "data", dcn, extra_axes=("fsdp",)
            )

        def flat(v):
            return jax.lax.psum(v, ("data", "fsdp"))

        np.testing.assert_allclose(
            np.asarray(self._run(hier, x)), np.asarray(self._run(flat, x)),
            rtol=1e-6, atol=1e-6,
        )

    def test_wire_dtype_compresses_dcn_hop_only(self):
        """bf16 wire: the result tracks the flat f32 sum to bf16 tolerance
        (only the already-ICI-reduced partials cross the cast), and the
        lowered text shows exactly one bf16 all_reduce (the DCN hop) and
        one non-bf16 (the ICI hop)."""
        hvt.init()

        mesh = mesh_lib.data_parallel_mesh()
        P = jax.sharding.PartitionSpec

        def hier(v):
            return collectives.hierarchical_psum(
                v, "data", 2, extra_axes=("fsdp",),
                wire_dtype=jnp.bfloat16,
            )

        f = jax.jit(jax.shard_map(
            hier, mesh=mesh, in_specs=(P(("data", "fsdp")),),
            out_specs=P(("data", "fsdp")), check_vma=False,
        ))
        x = jnp.asarray(
            np.random.RandomState(1).rand(8, 64).astype(np.float32)
        )
        got = np.asarray(f(x))
        want = np.broadcast_to(
            np.asarray(x).sum(0, keepdims=True), x.shape
        )
        np.testing.assert_allclose(got, want, rtol=2e-2)
        reduces = [
            op for op in hlo_audit.collective_ops(f.lower(x).as_text())
            if op.kind == "all-reduce"
        ]
        bf16 = [op for op in reduces if op.dtype == "bf16"]
        assert len(bf16) == 1, [op.describe() for op in reduces]
        assert len(reduces) >= 2  # the full-precision ICI hop is separate

    def test_bad_dcn_factor_is_loud(self):
        hvt.init()
        x = jnp.ones((8, 4), jnp.float32)

        def hier(v):
            return collectives.hierarchical_psum(v, "data", 3)

        with pytest.raises(ValueError, match="does not divide"):
            self._run(hier, x)

    def test_trainer_hierarchical_trajectory_matches_flat(self, monkeypatch):
        """End to end: HVT_DCN_FACTOR=2 (the fake 2-slice topology knob)
        routes the accumulating trainer's boundary reduction through the
        two-hop path; with an f32 wire the trajectory is identical to the
        single-slice run."""
        x, y = _probe_data()
        flat = _trainer(Probe(), k=2, average_aggregated_gradients=True)
        flat.fit(x=x, y=y, batch_size=2, epochs=1, steps_per_epoch=6,
                 shuffle_buffer=1, verbose=0)
        monkeypatch.setenv("HVT_DCN_FACTOR", "2")
        hier = _trainer(Probe(), k=2, average_aggregated_gradients=True)
        assert hier._dcn == 2
        hier.fit(x=x, y=y, batch_size=2, epochs=1, steps_per_epoch=6,
                 shuffle_buffer=1, verbose=0)
        for pa, pb in zip(
            jax.tree.leaves(jax.device_get(flat.state.params)),
            jax.tree.leaves(jax.device_get(hier.state.params)),
        ):
            np.testing.assert_allclose(pa, pb, rtol=1e-6, atol=1e-7)


class TestDcnFactor:
    def _fake_mesh(self, slice_ids):
        """Duck-typed mesh: an 8-long data axis whose device slice_index
        layout is given (dcn_factor only touches shape/axis_names/
        devices)."""
        import types

        devs = np.array(
            [types.SimpleNamespace(slice_index=s) for s in slice_ids]
        ).reshape(8, 1, 1, 1, 1, 1)
        return types.SimpleNamespace(
            shape={"data": 8}, axis_names=mesh_lib.AXES, devices=devs
        )

    def test_hybrid_outer_blocks_detected(self):
        m = self._fake_mesh([0, 0, 0, 0, 1, 1, 1, 1])
        assert mesh_lib.dcn_factor(m) == 2
        m4 = self._fake_mesh([0, 0, 1, 1, 2, 2, 3, 3])
        assert mesh_lib.dcn_factor(m4) == 4

    def test_non_hybrid_layouts_fall_back_flat(self):
        # interleaved (not outer blocks) and repeating ids: hierarchy
        # would be WRONG, so the factor must be 1
        assert mesh_lib.dcn_factor(
            self._fake_mesh([0, 1, 0, 1, 0, 1, 0, 1])
        ) == 1
        assert mesh_lib.dcn_factor(
            self._fake_mesh([0, 0, 0, 1, 1, 1, 0, 0])
        ) == 1

    def test_single_slice_is_one(self):
        hvt.init()
        assert mesh_lib.dcn_factor(mesh_lib.data_parallel_mesh()) == 1

    def test_env_override_validated(self, monkeypatch):
        hvt.init()
        mesh = mesh_lib.data_parallel_mesh()
        monkeypatch.setenv("HVT_DCN_FACTOR", "2")
        assert mesh_lib.dcn_factor(mesh) == 2
        monkeypatch.setenv("HVT_DCN_FACTOR", "3")
        with pytest.raises(ValueError, match="divide"):
            mesh_lib.dcn_factor(mesh)


class TestCompositionGuards:
    def test_shard_update_composes(self):
        """The PR 4 fail-fast is LIFTED: shard_update (ZeRO-1) now
        composes with backward_passes_per_step — the boundary reduction
        lowers into the sharded update's layout
        (reduce_gradients(scatter=dp); full matrix in
        tests/test_zero1_compose.py)."""
        tr = hvt.Trainer(
            Probe(),
            hvt.DistributedOptimizer(
                optax.adam(1e-3), backward_passes_per_step=2
            ),
            shard_update=True,
        )
        assert tr._scatter == tr.mesh.shape["data"]

    def test_param_specs_rejected(self):
        with pytest.raises(ValueError, match="replicated"):
            hvt.Trainer(
                Probe(),
                hvt.DistributedOptimizer(
                    optax.adam(1e-3), backward_passes_per_step=2
                ),
                param_specs={},
            )

    def test_batch_specs_rejected(self):
        P = jax.sharding.PartitionSpec
        with pytest.raises(ValueError, match="batch_specs"):
            hvt.Trainer(
                Probe(),
                hvt.DistributedOptimizer(
                    optax.adam(1e-3), backward_passes_per_step=2
                ),
                batch_specs=(P("data"), P("data")),
            )

    def test_trainer_swaps_multisteps_for_inner(self):
        """The Trainer path must NOT carry MultiSteps state (a params-sized
        accumulator in opt_state); standalone use keeps it."""
        tx = hvt.DistributedOptimizer(
            optax.adam(1e-3), backward_passes_per_step=4
        )
        spec = accumulation_spec(tx)
        assert spec is not None and spec.k == 4 and spec.average is False
        tr = hvt.Trainer(Probe(), tx)
        assert tr.tx is spec.inner
        x, _ = _probe_data(16)
        tr.build(x[:8])
        # MultiSteps state exposes mini_step/gradient_step; the trainer's
        # opt_state must be the bare inner optimizer's.
        names = {type(s).__name__ for s in jax.tree.leaves(
            tr.state.opt_state, is_leaf=lambda s: hasattr(s, "mini_step")
        )}
        assert not any("MultiSteps" in n for n in names)

    def test_axis_name_mode_keeps_multisteps_semantics(self):
        """Outside the Trainer (explicit axis_name), the transformation
        stays a MultiSteps wrap: K-1 zero updates, then the aggregate."""
        tx = hvt.DistributedOptimizer(
            optax.sgd(1.0), axis_name=None, backward_passes_per_step=2
        )
        params = {"w": jnp.ones(3)}
        state = tx.init(params)
        g = {"w": jnp.ones(3)}
        up1, state = tx.update(g, state, params)
        assert float(jnp.abs(up1["w"]).sum()) == 0.0  # pass 1: accumulate
        up2, state = tx.update(g, state, params)
        assert float(jnp.abs(up2["w"]).sum()) > 0.0  # pass 2: apply

    def test_steps_per_epoch_counts_optimizer_steps(self):
        """Default steps_per_epoch divides by K: 64 examples / (global
        batch 16 × K 2) = 2 optimizer steps per epoch."""
        x, y = _probe_data(64)
        t = _trainer(Probe(), k=2)
        hist = t.fit(x=x, y=y, batch_size=2, epochs=1, shuffle_buffer=1,
                     verbose=0)
        assert len(hist) == 1
        assert int(jax.device_get(t.state.step)) == 2
