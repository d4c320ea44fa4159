"""Pipeline parallelism: the GPipe schedule (parallel/pipeline.py) and the
pipelined LM (models/pipelined_lm.py) on the virtual 8-device mesh.

The load-bearing checks are the parity ones: the pipelined forward AND its
autodiff-derived backward must compute exactly what the sequential layer
stack computes — the schedule is an execution detail, not a model change.
"""

import jax

import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvt
from horovod_tpu.data import datasets
from horovod_tpu.models import pipelined_lm
from horovod_tpu.models.pipelined_lm import PipelinedLM
from horovod_tpu.parallel import mesh as mesh_lib
from horovod_tpu.parallel.pipeline import spmd_pipeline, stage_slice_size

# Compile-heavy end-to-end tier (suite diet: default run stays fast).
pytestmark = pytest.mark.slow

VOCAB = 32


def _mesh(data=2, pipe=4):
    return mesh_lib.build_mesh(mesh_lib.MeshSpec(data=data, pipe=pipe))


class TestSchedule:
    def test_four_stage_chain_equals_sequential(self):
        """Stage s multiplies by w[s] and adds b[s]; the pipeline over 4
        stages must equal applying all four transforms in order."""
        mesh = _mesh(data=2, pipe=4)
        w = jnp.asarray([2.0, 3.0, 0.5, 4.0]).reshape(4, 1)
        bias = jnp.asarray([1.0, -2.0, 0.25, 3.0]).reshape(4, 1)
        x_micro = jnp.asarray(
            np.random.RandomState(0).rand(6, 2, 3), jnp.float32
        )

        def run(wp, bp, xm):
            def stage(a):
                # this stage's [1, 1] slice of w/b
                return a * wp[0, 0] + bp[0, 0]

            return spmd_pipeline(stage, xm)

        out = jax.shard_map(
            run,
            mesh=mesh,
            in_specs=(P("pipe", None), P("pipe", None), P(None, None, None)),
            out_specs=P(None, None, None),
            check_vma=False,
        )(w, bias, x_micro)

        expect = x_micro
        for i in range(4):
            expect = expect * w[i, 0] + bias[i, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-6)

    def test_stage_slice_validation(self):
        assert stage_slice_size(8, 4) == 2
        with pytest.raises(ValueError, match="divisible"):
            stage_slice_size(6, 4)


def _models(n_layers=4, n_micro=4, mesh=None):
    kw = dict(
        vocab_size=VOCAB, d_model=32, n_heads=4,
        n_layers=n_layers, n_micro=n_micro,
    )
    return PipelinedLM(**kw, mesh=mesh), PipelinedLM(**kw, mesh=None)


class TestParity:
    def test_forward_matches_sequential(self):
        mesh = _mesh()
        piped, plain = _models(mesh=mesh)
        rng = np.random.RandomState(1)
        toks = jnp.asarray(rng.randint(1, VOCAB, size=(8, 16)).astype(np.int32))
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]
        out_plain = plain.apply({"params": params}, toks)
        out_piped = jax.jit(lambda p, t: piped.apply({"params": p}, t))(
            params, toks
        )
        np.testing.assert_allclose(
            np.asarray(out_plain), np.asarray(out_piped), rtol=2e-4, atol=2e-4
        )

    def test_backward_matches_sequential(self):
        """jax.grad through the scan+ppermute schedule must produce the same
        gradients as through the plain layer stack — the derived reverse
        pipeline is correct."""
        mesh = _mesh()
        piped, plain = _models(mesh=mesh)
        rng = np.random.RandomState(2)
        toks = jnp.asarray(rng.randint(1, VOCAB, size=(8, 16)).astype(np.int32))
        labels = jnp.asarray(rng.randint(1, VOCAB, size=(8, 16)).astype(np.int32))
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]

        def loss(model):
            def f(p):
                logits = model.apply({"params": p}, toks)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels
                ).mean()

            return f

        g_plain = jax.grad(loss(plain))(params)
        g_piped = jax.jit(jax.grad(loss(piped)))(params)
        for key in g_plain:
            np.testing.assert_allclose(
                np.asarray(g_plain[key]), np.asarray(g_piped[key]),
                rtol=2e-3, atol=2e-5, err_msg=key,
            )

    def test_causality(self):
        mesh = _mesh()
        piped, plain = _models(mesh=mesh)
        rng = np.random.RandomState(3)
        toks = rng.randint(1, VOCAB, size=(8, 16)).astype(np.int32)
        params = plain.init(jax.random.PRNGKey(0), jnp.asarray(toks))["params"]
        f = jax.jit(lambda p, t: piped.apply({"params": p}, t))
        out1 = f(params, jnp.asarray(toks))
        toks2 = toks.copy()
        toks2[:, 12] = (toks2[:, 12] % (VOCAB - 1)) + 1
        out2 = f(params, jnp.asarray(toks2))
        np.testing.assert_allclose(
            np.asarray(out1[:, :12]), np.asarray(out2[:, :12]), atol=1e-4
        )


class TestTraining:
    def _trainer(self, mesh, n_micro=4):
        return hvt.Trainer(
            PipelinedLM(
                vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=4,
                n_micro=n_micro, mesh=mesh,
            ),
            hvt.DistributedOptimizer(optax.adam(3e-3)),
            mesh=mesh,
            param_specs=pipelined_lm.param_specs,
        )

    def test_params_sharded_over_pipe(self):
        mesh = _mesh()
        trainer = self._trainer(mesh)
        x, _ = datasets.copy_task(8, 16, vocab_size=VOCAB)
        state = trainer.build(x)
        flat = jax.tree_util.tree_flatten_with_path(state.params)[0]
        piped = [
            path for path, leaf in flat
            if any(
                "pipe" in (ax if isinstance(ax, tuple) else (ax,))
                for ax in leaf.sharding.spec if ax is not None
            )
        ]
        assert len(piped) == 6  # the six per-layer stacks
        # embed/head replicated
        names = {p[-1].key for p, _ in flat}
        assert {"embed", "lm_head", "ln_f"} <= names

    def test_trains_on_dp_x_pp_mesh(self):
        mesh = _mesh()
        trainer = self._trainer(mesh)
        x, y = datasets.copy_task(512, 16, vocab_size=VOCAB, seed=1)
        history = trainer.fit(
            x=x, y=y, batch_size=4, epochs=2, steps_per_epoch=10, verbose=0
        )
        assert np.isfinite(history[-1]["loss"])
        assert history[-1]["loss"] < history[0]["loss"]

    def test_batch_not_divisible_by_micro_errors(self):
        mesh = _mesh()
        piped, _ = _models(n_micro=3, mesh=mesh)
        toks = jnp.zeros((8, 16), jnp.int32)
        with pytest.raises(ValueError, match="n_micro"):
            piped.init(jax.random.PRNGKey(0), toks)

    def test_rejects_expert_mesh(self):
        """A live expert axis requires mlp='moe' (TestMoEPipeline); a dense
        pipelined model on an expert mesh must be rejected loudly, not
        silently leave the axis unused."""
        mesh = mesh_lib.build_mesh(
            mesh_lib.MeshSpec(data=2, pipe=2, expert=2)
        )
        piped = PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=4, mesh=mesh
        )
        with pytest.raises(ValueError, match="expert"):
            piped.init(jax.random.PRNGKey(0), jnp.zeros((8, 16), jnp.int32))


class Test1F1B:
    """The hand-scheduled staggered backward (spmd_pipeline_1f1b) must be
    math-identical to the AD-derived GPipe backward — the schedule changes
    activation memory, never gradients."""

    def _lm(self, schedule, mesh):
        return PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=4,
            n_micro=4, mesh=mesh, schedule=schedule,
        )

    def test_forward_matches_gpipe(self):
        mesh = _mesh()
        rng = np.random.RandomState(11)
        toks = jnp.asarray(rng.randint(1, VOCAB, size=(8, 16)).astype(np.int32))
        plain = self._lm("gpipe", None)
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]
        out_g = jax.jit(
            lambda p, t: self._lm("gpipe", mesh).apply({"params": p}, t)
        )(params, toks)
        out_1 = jax.jit(
            lambda p, t: self._lm("1f1b", mesh).apply({"params": p}, t)
        )(params, toks)
        np.testing.assert_allclose(
            np.asarray(out_g), np.asarray(out_1), rtol=1e-5, atol=1e-5
        )

    def test_gradients_match_gpipe_and_sequential(self):
        mesh = _mesh()
        rng = np.random.RandomState(12)
        toks = jnp.asarray(rng.randint(1, VOCAB, size=(8, 16)).astype(np.int32))
        labels = jnp.asarray(rng.randint(1, VOCAB, size=(8, 16)).astype(np.int32))
        plain = self._lm("gpipe", None)
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]

        def loss_of(model):
            def f(p):
                logits = model.apply({"params": p}, toks)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels
                ).mean()

            return f

        g_seq = jax.grad(loss_of(plain))(params)
        g_1f1b = jax.jit(jax.grad(loss_of(self._lm("1f1b", mesh))))(params)
        g_gpipe = jax.jit(jax.grad(loss_of(self._lm("gpipe", mesh))))(params)
        for key in g_seq:
            np.testing.assert_allclose(
                np.asarray(g_1f1b[key]), np.asarray(g_gpipe[key]),
                rtol=2e-4, atol=2e-6, err_msg=f"1f1b vs gpipe: {key}",
            )
            np.testing.assert_allclose(
                np.asarray(g_1f1b[key]), np.asarray(g_seq[key]),
                rtol=2e-3, atol=2e-5, err_msg=f"1f1b vs sequential: {key}",
            )

    def test_trains(self):
        mesh = _mesh()
        tr = hvt.Trainer(
            self._lm("1f1b", mesh),
            hvt.DistributedOptimizer(optax.adam(3e-3)),
            loss="sparse_categorical_crossentropy",
            mesh=mesh,
            param_specs=pipelined_lm.param_specs,
        )
        x, y = datasets.copy_task(128, 16, vocab_size=VOCAB)
        hist = tr.fit(x=x, y=y, batch_size=8, epochs=2, steps_per_epoch=4)
        assert hist[-1]["loss"] < hist[0]["loss"]

    def test_invalid_schedule_rejected(self):
        mesh = _mesh()
        rng = np.random.RandomState(13)
        toks = jnp.asarray(rng.randint(1, VOCAB, size=(8, 16)).astype(np.int32))
        model = PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=4,
            n_micro=4, mesh=mesh, schedule="pipedream",
        )
        with pytest.raises(ValueError, match="schedule"):
            model.init(jax.random.PRNGKey(0), toks)


class TestBubbleAccounting:
    """The GPipe bubble is measurable, not just documented: every device
    computes ticks = n_micro + S - 1 stage passes but only n_micro are
    useful, so the pipelined forward's total FLOPs must exceed the
    sequential stack's by ≈ ticks/n_micro (the bubble fraction
    (S-1)/(T+S-1) in efficiency terms)."""

    @pytest.mark.parametrize("n_micro", [4, 8])
    def test_flop_ratio_matches_tick_count(self, n_micro):
        from horovod_tpu import trace

        mesh = _mesh(data=2, pipe=4)
        n_stages = 4
        rng = np.random.RandomState(14)
        b = 2 * n_micro  # mb covers the data axis (dp=2)
        toks = jnp.asarray(rng.randint(1, VOCAB, size=(b, 16)).astype(np.int32))
        piped = PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=4,
            n_micro=n_micro, mesh=mesh,
        )
        plain = PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=4,
            n_micro=n_micro, mesh=None,
        )
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]
        f_piped = jax.jit(lambda p, t: piped.apply({"params": p}, t))
        f_plain = jax.jit(lambda p, t: plain.apply({"params": p}, t))
        fl_piped = trace.compiled_flops(f_piped, params, toks)
        fl_plain = trace.compiled_flops(f_plain, params, toks)
        if not fl_piped or not fl_plain:
            pytest.skip("backend reports no cost analysis")
        ticks = n_micro + n_stages - 1
        # XLA's cost model reports PER-DEVICE flops: the pipelined program
        # spreads the useful work over all 8 devices (pipe 4 x data 2) but
        # every device computes `ticks` stage passes where n_micro would be
        # useful — so per-device flops = ticks/(n_micro * 8) of the plain
        # single-device stack (embed/head/LN add slack; generous band).
        expected = ticks / (n_micro * mesh.size)
        measured = fl_piped / fl_plain
        assert measured == pytest.approx(expected, rel=0.35), (
            f"FLOP ratio {measured:.2f} vs tick model {expected:.2f}"
        )

    def test_bubble_shrinks_with_more_micros(self):
        from horovod_tpu import trace

        mesh = _mesh(data=2, pipe=4)
        rng = np.random.RandomState(15)

        def flops(n_micro):
            toks = jnp.asarray(
                rng.randint(1, VOCAB, size=(2 * n_micro, 16)).astype(np.int32)
            )
            m = PipelinedLM(
                vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=4,
                n_micro=n_micro, mesh=mesh,
            )
            plain = PipelinedLM(
                vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=4,
                n_micro=n_micro, mesh=None,
            )
            params = plain.init(jax.random.PRNGKey(0), toks)["params"]
            f = jax.jit(lambda p, t: m.apply({"params": p}, t))
            g = jax.jit(lambda p, t: plain.apply({"params": p}, t))
            a, b = trace.compiled_flops(f, params, toks), trace.compiled_flops(
                g, params, toks
            )
            if not a or not b:
                pytest.skip("backend reports no cost analysis")
            # per-token overhead ratio
            return a / b

        assert flops(8) < flops(2)


class TestPipeTensorComposition:
    """PP × TP × DP on one mesh (round 3 — previously PP composed with data
    only): Megatron column/row TP inside each pipeline stage, one psum per
    residual join, under both schedules."""

    def _mesh(self):
        return mesh_lib.build_mesh(
            mesh_lib.MeshSpec(data=2, pipe=2, model=2)
        )

    def _lm(self, mesh, schedule="gpipe"):
        return PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=4,
            n_micro=2, mesh=mesh, schedule=schedule,
        )

    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
    def test_forward_matches_sequential(self, schedule):
        mesh = self._mesh()
        rng = np.random.RandomState(21)
        toks = jnp.asarray(rng.randint(1, VOCAB, size=(4, 16)).astype(np.int32))
        plain = PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=4,
            n_micro=2, mesh=None,
        )
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]
        out_plain = plain.apply({"params": params}, toks)
        out = jax.jit(
            lambda p, t: self._lm(mesh, schedule).apply({"params": p}, t)
        )(params, toks)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(out_plain), rtol=2e-4, atol=2e-4,
        )

    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
    def test_gradients_match_sequential(self, schedule):
        mesh = self._mesh()
        rng = np.random.RandomState(22)
        toks = jnp.asarray(rng.randint(1, VOCAB, size=(4, 16)).astype(np.int32))
        labels = jnp.asarray(rng.randint(1, VOCAB, size=(4, 16)).astype(np.int32))
        plain = PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=4,
            n_micro=2, mesh=None,
        )
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]

        def loss_of(model):
            def f(p):
                logits = model.apply({"params": p}, toks)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels
                ).mean()

            return f

        g_seq = jax.grad(loss_of(plain))(params)
        g_pp = jax.jit(jax.grad(loss_of(self._lm(mesh, schedule))))(params)
        for key in g_seq:
            np.testing.assert_allclose(
                np.asarray(g_pp[key]), np.asarray(g_seq[key]),
                rtol=2e-3, atol=2e-5, err_msg=key,
            )

    def test_trains_with_sharded_state(self):
        """End-to-end on dp=2 x pipe=2 x model=2: param_specs shard stage
        stacks over pipe AND Megatron dims over model; training runs and
        the TP kernels really are sharded on the model axis."""
        mesh = self._mesh()
        tr = hvt.Trainer(
            self._lm(mesh, "1f1b"),
            hvt.DistributedOptimizer(optax.adam(3e-3)),
            loss="sparse_categorical_crossentropy",
            mesh=mesh,
            param_specs=pipelined_lm.param_specs,
        )
        x, y = datasets.copy_task(64, 16, vocab_size=VOCAB)
        hist = tr.fit(x=x, y=y, batch_size=4, epochs=2, steps_per_epoch=4)
        assert hist[-1]["loss"] < hist[0]["loss"]
        qkv = tr.state.params["qkv"]
        spec = qkv.sharding.spec
        assert spec[0] == "pipe" and spec[2] == "model", spec

    def test_indivisible_heads_rejected(self):
        mesh = mesh_lib.build_mesh(
            mesh_lib.MeshSpec(data=1, pipe=2, model=4)
        )
        toks = jnp.zeros((4, 16), jnp.int32)
        model = PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=6, n_layers=4, mesh=mesh,
        )
        with pytest.raises(ValueError, match="divide"):
            model.init(jax.random.PRNGKey(0), toks)


class TestInterleaved:
    """Virtual-stage (Megatron-interleaved) schedule: each pipe device
    hosts `n_virtual` non-adjacent chunks, so the fill bubble is S-1 CHUNK
    times — relative overhead (v·T + S - 1)/(v·T) vs GPipe's (T + S - 1)/T.
    Stacks live in placement order on the mesh; the to_interleaved_order /
    to_logical_order helpers convert against sequential checkpoints."""

    def _lm(self, mesh, n_layers=8, n_micro=4, v=2):
        return PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=n_layers,
            n_micro=n_micro, mesh=mesh, schedule="interleaved", n_virtual=v,
        )

    @pytest.mark.parametrize("pipe,v", [(2, 2), (4, 2)])
    def test_forward_matches_sequential(self, pipe, v):
        mesh = _mesh(data=8 // pipe, pipe=pipe)
        rng = np.random.RandomState(51)
        toks = jnp.asarray(rng.randint(1, VOCAB, size=(16, 16)).astype(np.int32))
        plain = PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=8,
            n_micro=4, mesh=None,
        )
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]
        out_plain = plain.apply({"params": params}, toks)
        inter = self._lm(mesh, v=v)
        p_inter = pipelined_lm.to_interleaved_order(params, 8, pipe, v)
        out = jax.jit(
            lambda p, t: inter.apply({"params": p}, t)
        )(p_inter, toks)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(out_plain), rtol=2e-4, atol=2e-4,
        )

    def test_order_roundtrip(self):
        plain = PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=8, mesh=None,
        )
        params = plain.init(
            jax.random.PRNGKey(1), jnp.zeros((4, 16), jnp.int32)
        )["params"]
        there = pipelined_lm.to_interleaved_order(params, 8, 2, 2)
        back = pipelined_lm.to_logical_order(there, 8, 2, 2)
        for key in params:
            np.testing.assert_array_equal(
                np.asarray(back[key]), np.asarray(params[key]), err_msg=key
            )
        # and the permutation is NOT the identity on the stacks
        assert not np.array_equal(
            np.asarray(there["qkv"]), np.asarray(params["qkv"])
        )

    def test_gradients_match_sequential(self):
        mesh = _mesh(data=4, pipe=2)
        rng = np.random.RandomState(52)
        toks = jnp.asarray(rng.randint(1, VOCAB, size=(16, 16)).astype(np.int32))
        labels = jnp.asarray(rng.randint(1, VOCAB, size=(16, 16)).astype(np.int32))
        plain = PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=8,
            n_micro=4, mesh=None,
        )
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]

        def loss_of(model):
            def f(p):
                logits = model.apply({"params": p}, toks)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels
                ).mean()

            return f

        g_seq = jax.grad(loss_of(plain))(params)
        p_inter = pipelined_lm.to_interleaved_order(params, 8, 2, 2)
        g_inter = jax.jit(jax.grad(loss_of(self._lm(mesh))))(p_inter)
        g_inter = pipelined_lm.to_logical_order(g_inter, 8, 2, 2)
        for key in g_seq:
            np.testing.assert_allclose(
                np.asarray(g_inter[key]), np.asarray(g_seq[key]),
                rtol=2e-3, atol=2e-5, err_msg=key,
            )

    def test_bubble_matches_tick_model(self):
        """Per-device FLOPs of the interleaved schedule must track its tick
        model (v·T + S - 1)/(v·T · mesh.size) of the sequential stack —
        the same anchoring TestBubbleAccounting gives GPipe. (A direct
        fl_inter < fl_gpipe comparison is NOT asserted: XLA's cost analysis
        is only band-accurate across different scan structures — GPipe
        itself measures ~30% under its own tick model here — so the
        schedule-vs-schedule claim rests on the tick counts both ratios are
        anchored to: (v·T+S-1) chunk passes vs (T+S-1)·v, i.e. 11 vs 14
        layer passes per device at S=4, T=4, v=2.)"""
        from horovod_tpu import trace

        mesh = _mesh(data=2, pipe=4)
        S, T, v = 4, 4, 2
        rng = np.random.RandomState(53)
        toks = jnp.asarray(rng.randint(1, VOCAB, size=(8, 16)).astype(np.int32))
        plain = PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=8,
            n_micro=T, mesh=None,
        )
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]
        p_inter = pipelined_lm.to_interleaved_order(params, 8, S, v)
        fl_inter = trace.compiled_flops(
            jax.jit(lambda p, t: self._lm(mesh, v=v).apply({"params": p}, t)),
            p_inter, toks,
        )
        fl_plain = trace.compiled_flops(
            jax.jit(lambda p, t: plain.apply({"params": p}, t)), params, toks
        )
        if not fl_inter or not fl_plain:
            pytest.skip("backend reports no cost analysis")
        expected_inter = (v * T + S - 1) / (v * T * mesh.size)
        measured = fl_inter / fl_plain
        assert measured == pytest.approx(expected_inter, rel=0.35), (
            f"FLOP ratio {measured:.3f} vs interleaved tick model "
            f"{expected_inter:.3f}"
        )

    def test_trains(self):
        mesh = _mesh(data=4, pipe=2)
        tr = hvt.Trainer(
            self._lm(mesh, n_micro=4),
            hvt.DistributedOptimizer(optax.adam(3e-3)),
            loss="sparse_categorical_crossentropy",
            mesh=mesh,
            param_specs=pipelined_lm.param_specs,
        )
        x, y = datasets.copy_task(64, 16, vocab_size=VOCAB)
        hist = tr.fit(x=x, y=y, batch_size=8, epochs=2, steps_per_epoch=4)
        assert hist[-1]["loss"] < hist[0]["loss"]

    def test_indivisible_chunks_rejected(self):
        mesh = _mesh(data=4, pipe=2)
        model = self._lm(mesh, n_layers=6, v=4)
        with pytest.raises(ValueError, match="n_virtual"):
            model.init(jax.random.PRNGKey(0), jnp.zeros((4, 16), jnp.int32))

    def test_too_few_micros_rejected_after_init(self):
        """n_micro < n_stages must fail loudly on a REAL forward: degrading
        v to 1 would run the placement-ordered stacks contiguously — a
        permuted layer composition, not the trained function. Only flax's
        shape-only init probe may degrade."""
        mesh = _mesh(data=4, pipe=2)
        model = self._lm(mesh, n_micro=4)
        # init with a dp-sized probe batch (n_micro clamps to 1) is fine:
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((4, 16), jnp.int32)
        )["params"]
        # a real apply at the same tiny batch is not:
        with pytest.raises(ValueError, match="n_micro"):
            model.apply({"params": params}, jnp.zeros((4, 16), jnp.int32))


class TestPipeSeqComposition:
    """PP × SP × DP on one mesh (round 3 continuation): every stage's
    attention runs as ring-flash collectives around the ``seq`` ring while
    activations shard their token dim — the long-context axis composed with
    the pipeline schedule, under both schedules."""

    def _mesh(self):
        return mesh_lib.build_mesh(
            mesh_lib.MeshSpec(data=2, pipe=2, seq=2)
        )

    def _lm(self, mesh, schedule="gpipe"):
        return PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=4,
            n_micro=2, mesh=mesh, schedule=schedule,
        )

    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
    def test_forward_matches_sequential(self, schedule):
        mesh = self._mesh()
        rng = np.random.RandomState(41)
        toks = jnp.asarray(rng.randint(1, VOCAB, size=(4, 16)).astype(np.int32))
        plain = self._lm(None)
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]
        out_plain = plain.apply({"params": params}, toks)
        out = jax.jit(
            lambda p, t: self._lm(mesh, schedule).apply({"params": p}, t)
        )(params, toks)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(out_plain), rtol=2e-4, atol=2e-4,
        )

    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
    def test_gradients_match_sequential(self, schedule):
        mesh = self._mesh()
        rng = np.random.RandomState(42)
        toks = jnp.asarray(rng.randint(1, VOCAB, size=(4, 16)).astype(np.int32))
        labels = jnp.asarray(rng.randint(1, VOCAB, size=(4, 16)).astype(np.int32))
        plain = self._lm(None)
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]

        def loss_of(model):
            def f(p):
                logits = model.apply({"params": p}, toks)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels
                ).mean()

            return f

        g_seq = jax.grad(loss_of(plain))(params)
        g_pp = jax.jit(jax.grad(loss_of(self._lm(mesh, schedule))))(params)
        for key in g_seq:
            np.testing.assert_allclose(
                np.asarray(g_pp[key]), np.asarray(g_seq[key]),
                rtol=2e-3, atol=2e-5, err_msg=key,
            )

    def test_packed_through_pipe_and_seq(self):
        """Packed documents + PP + SP together: segment ids shard over seq
        and ride the ring inside each stage; each packed document must still
        equal its solo run."""
        mesh = self._mesh()
        rng = np.random.RandomState(43)
        doc_a = rng.randint(1, VOCAB, size=(4, 8)).astype(np.int32)
        doc_b = rng.randint(1, VOCAB, size=(4, 8)).astype(np.int32)
        packed = jnp.asarray(np.concatenate([doc_a, doc_b], axis=1))
        seg = jnp.asarray(np.concatenate(
            [np.ones((4, 8)), 2 * np.ones((4, 8))], axis=1
        ).astype(np.int32))
        plain = self._lm(None)
        params = plain.init(jax.random.PRNGKey(0), packed)["params"]
        out = jax.jit(
            lambda p, tk, sg: self._lm(mesh, "1f1b").apply(
                {"params": p}, tk, segment_ids=sg
            )
        )(params, packed, seg)
        solo_a = plain.apply({"params": params}, jnp.asarray(doc_a))
        solo_b = plain.apply({"params": params}, jnp.asarray(doc_b))
        np.testing.assert_allclose(
            np.asarray(out[:, :8]), np.asarray(solo_a), rtol=3e-4, atol=3e-4
        )
        np.testing.assert_allclose(
            np.asarray(out[:, 8:]), np.asarray(solo_b), rtol=3e-4, atol=3e-4
        )

    def test_trains_on_dp_pp_sp_mesh(self):
        mesh = self._mesh()
        tr = hvt.Trainer(
            self._lm(mesh, "1f1b"),
            hvt.DistributedOptimizer(optax.adam(3e-3)),
            loss="sparse_categorical_crossentropy",
            mesh=mesh,
            param_specs=pipelined_lm.param_specs,
            batch_specs=(P(("data", "fsdp"), "seq"), P(("data", "fsdp"), "seq")),
        )
        x, y = datasets.copy_task(64, 16, vocab_size=VOCAB)
        hist = tr.fit(x=x, y=y, batch_size=4, epochs=2, steps_per_epoch=4)
        assert hist[-1]["loss"] < hist[0]["loss"]

    def test_indivisible_seq_rejected(self):
        mesh = self._mesh()
        model = self._lm(mesh)
        with pytest.raises(ValueError, match="seq axis"):
            model.init(jax.random.PRNGKey(0), jnp.zeros((4, 15), jnp.int32))


class TestPackedPipeline:
    """Packed sequences through pipeline stages (round 3): segment ids and
    per-document positions are per-microbatch CONSTANTS indexed by each
    stage directly — they never ride the ppermute ring — and the packing-
    invariance contract must hold through the schedule."""

    def _packed(self, seed=31):
        rng = np.random.RandomState(seed)
        doc_a = rng.randint(1, VOCAB, size=(4, 16)).astype(np.int32)
        doc_b = rng.randint(1, VOCAB, size=(4, 16)).astype(np.int32)
        packed = np.concatenate([doc_a, doc_b], axis=1)
        seg = np.concatenate(
            [np.ones((4, 16)), 2 * np.ones((4, 16))], axis=1
        ).astype(np.int32)
        return doc_a, doc_b, jnp.asarray(packed), jnp.asarray(seg)

    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
    def test_packing_invariance_through_pipeline(self, schedule):
        mesh = _mesh(data=2, pipe=4)
        doc_a, doc_b, packed, seg = self._packed()
        plain = PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=4,
            n_micro=2, mesh=None,
        )
        params = plain.init(jax.random.PRNGKey(0), packed)["params"]
        piped = PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=4,
            n_micro=2, mesh=mesh, schedule=schedule,
        )
        out = jax.jit(
            lambda p, tk, sg: piped.apply(
                {"params": p}, tk, segment_ids=sg
            )
        )(params, packed, seg)
        # Each packed document must equal its solo (unpacked) run.
        solo_a = plain.apply({"params": params}, jnp.asarray(doc_a))
        solo_b = plain.apply({"params": params}, jnp.asarray(doc_b))
        np.testing.assert_allclose(
            np.asarray(out[:, :16]), np.asarray(solo_a), rtol=3e-4, atol=3e-4
        )
        np.testing.assert_allclose(
            np.asarray(out[:, 16:]), np.asarray(solo_b), rtol=3e-4, atol=3e-4
        )

    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
    def test_packed_gradients_match_sequential(self, schedule):
        mesh = _mesh(data=2, pipe=4)
        _, _, packed, seg = self._packed(32)
        labels = jnp.asarray(
            np.random.RandomState(33).randint(1, VOCAB, size=packed.shape)
        ).astype(jnp.int32)
        piped = PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=4,
            n_micro=2, mesh=mesh, schedule=schedule,
        )
        plain = PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=4,
            n_micro=2, mesh=None,
        )
        params = plain.init(jax.random.PRNGKey(0), packed)["params"]

        def loss_of(model):
            def f(p):
                logits = model.apply(
                    {"params": p}, packed, segment_ids=seg
                )
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels
                ).mean()

            return f

        g_pp = jax.jit(jax.grad(loss_of(piped)))(params)
        g_seq = jax.grad(loss_of(plain))(params)
        for key in g_seq:
            np.testing.assert_allclose(
                np.asarray(g_pp[key]), np.asarray(g_seq[key]),
                rtol=2e-3, atol=2e-5, err_msg=key,
            )


class TestMoEPipeline:
    """pp x ep composition (round 3): every block's MLP routed through
    expert FFNs sharded over the ``expert`` axis INSIDE the pipeline's
    manual region, with the router's aux loss riding the schedules'
    differentiable with_aux channel. Group-size note: MoE routing is
    grouped (capacity is per dispatch group), so pipelined-vs-sequential
    parity holds when both paths see the same token groups —
    moe_group_size=16 makes every group one 16-token row here for every
    mesh under test.
    """

    def _lm(self, mesh, schedule="gpipe", **kw):
        kw.setdefault("n_micro", 4)
        return PipelinedLM(
            vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=4,
            mesh=mesh, schedule=schedule, mlp="moe", n_experts=4,
            moe_group_size=16, **kw,
        )

    def _mesh22(self):
        # data=2 x pipe=2 on a 4-device subset (the 8-device default mesh
        # would force dp=4 and clamp n_micro below the interleaved minimum).
        return mesh_lib.build_mesh(
            mesh_lib.MeshSpec(data=2, pipe=2), devices=jax.devices()[:4]
        )

    def _data(self, seed=61, batch=8):
        rng = np.random.RandomState(seed)
        toks = jnp.asarray(
            rng.randint(1, VOCAB, size=(batch, 16)).astype(np.int32)
        )
        labels = jnp.asarray(
            rng.randint(1, VOCAB, size=(batch, 16)).astype(np.int32)
        )
        return toks, labels

    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b", "interleaved"])
    def test_forward_matches_sequential(self, schedule):
        mesh = self._mesh22()
        toks, _ = self._data()
        plain = self._lm(None)
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]
        expect = plain.apply({"params": params}, toks)
        p_run = params
        if schedule == "interleaved":
            p_run = pipelined_lm.to_interleaved_order(params, 4, 2, 2)
        out = jax.jit(
            lambda p, t: self._lm(mesh, schedule).apply({"params": p}, t)
        )(p_run, toks)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expect), rtol=2e-4, atol=2e-4
        )

    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b", "interleaved"])
    def test_gradients_match_sequential_incl_aux(self, schedule):
        """CE + the sown load-balance loss: gradients (router included)
        must match the sequential stack — this exercises the aux channel's
        backward through every schedule (custom-vjp cotangent routing for
        1F1B)."""
        mesh = self._mesh22()
        toks, labels = self._data(62)
        plain = self._lm(None)
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]

        def loss_of(model):
            def f(p):
                logits, var = model.apply(
                    {"params": p}, toks, train=True,
                    mutable=["losses", "metrics"],
                )
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels
                ).mean()
                return ce + sum(jax.tree.leaves(var.get("losses", {})))

            return f

        g_seq = jax.grad(loss_of(plain))(params)
        p_run = params
        if schedule == "interleaved":
            p_run = pipelined_lm.to_interleaved_order(params, 4, 2, 2)
        g_pp = jax.jit(jax.grad(loss_of(self._lm(mesh, schedule))))(p_run)
        if schedule == "interleaved":
            g_pp = pipelined_lm.to_logical_order(g_pp, 4, 2, 2)
        assert float(jnp.abs(g_seq["router"]).max()) > 0
        for key in g_seq:
            np.testing.assert_allclose(
                np.asarray(g_pp[key]), np.asarray(g_seq[key]),
                rtol=2e-3, atol=2e-5, err_msg=key,
            )

    def test_ep_sharding_matches_unsharded(self):
        """Slicing the dispatch/combine one-hots per expert-rank + the
        (expert) psum must be invisible: pipe=2 x expert=2 == pipe=2 ==
        sequential."""
        toks, _ = self._data(63)
        plain = self._lm(None)
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]
        expect = plain.apply({"params": params}, toks)
        mesh_ep = mesh_lib.build_mesh(
            mesh_lib.MeshSpec(data=2, pipe=2, expert=2)
        )
        out = jax.jit(
            lambda p, t: self._lm(mesh_ep).apply({"params": p}, t)
        )(params, toks)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expect), rtol=2e-4, atol=2e-4
        )

    def test_ep_tp_sharding_matches_unsharded(self):
        """Expert FFN hidden dim Megatron-sharded over `model` on top of
        the expert sharding: pipe=2 x expert=2 x model=2 == sequential."""
        toks, _ = self._data(64)
        plain = self._lm(None)
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]
        expect = plain.apply({"params": params}, toks)
        mesh = mesh_lib.build_mesh(
            mesh_lib.MeshSpec(data=1, pipe=2, model=2, expert=2)
        )
        out = jax.jit(
            lambda p, t: self._lm(mesh).apply({"params": p}, t)
        )(params, toks)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expect), rtol=2e-4, atol=2e-4
        )

    def test_trains_on_dp_pp_ep_mesh_with_drop_rate(self):
        """End-to-end Trainer on data=2 x pipe=2 x expert=2: expert stacks
        sharded over `expert`, loss decreases, and the router drop-rate
        metric flows from inside the manual region to the epoch logs."""
        mesh = mesh_lib.build_mesh(
            mesh_lib.MeshSpec(data=2, pipe=2, expert=2)
        )
        tr = hvt.Trainer(
            self._lm(mesh, "1f1b"),
            hvt.DistributedOptimizer(optax.adam(3e-3)),
            loss="sparse_categorical_crossentropy",
            mesh=mesh,
            param_specs=pipelined_lm.param_specs,
        )
        x, y = datasets.copy_task(128, 16, vocab_size=VOCAB)
        hist = tr.fit(x=x, y=y, batch_size=8, epochs=2, steps_per_epoch=4)
        assert np.isfinite(hist[-1]["loss"])
        assert hist[-1]["loss"] < hist[0]["loss"]
        assert "moe_drop_rate" in tr.metric_names
        rate = hist[0]["moe_drop_rate"]
        assert 0.0 <= rate <= 1.0
        # expert stacks actually sharded over the expert axis
        spec = tr.state.params["moe_up"].sharding.spec
        assert "expert" in jax.tree.leaves(tuple(spec))

    def test_starved_capacity_reports_drops(self):
        """capacity_factor small enough to force overflow: the drop rate
        reported out of the pipeline region must be materially nonzero
        (silent drops were the round-2 MoE gap; the pipelined MoE must not
        reintroduce them)."""
        mesh = self._mesh22()
        toks, _ = self._data(65)
        model = self._lm(mesh, capacity_factor=0.25)
        params = model.init(jax.random.PRNGKey(0), toks)["params"]
        _, var = jax.jit(
            lambda p, t: model.apply(
                {"params": p}, t, mutable=["metrics"]
            )
        )(params, toks)
        rate = float(jax.tree.leaves(var["metrics"])[0])
        assert rate > 0.1

    def test_dense_stacks_absent_under_moe(self):
        toks, _ = self._data(66)
        params = self._lm(None).init(jax.random.PRNGKey(0), toks)["params"]
        assert "moe_up" in params and "router" in params
        assert "mlp_up" not in params


class TestWindowedPipeline:
    """Sliding-window attention through the pipeline schedules: a windowed
    PipelinedLM must match a windowed sequential stack, on pp and pp×sp."""

    def test_window_matches_sequential(self):
        import jax
        import jax.numpy as jnp

        from horovod_tpu.models.pipelined_lm import PipelinedLM
        from horovod_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.build_mesh(
            mesh_lib.MeshSpec(data=2, pipe=2, seq=2),
            devices=jax.devices()[:8],
        )
        model = PipelinedLM(
            vocab_size=32, d_model=32, n_heads=4, n_layers=4, n_micro=2,
            mesh=mesh, window=5,
        )
        ref = PipelinedLM(
            vocab_size=32, d_model=32, n_heads=4, n_layers=4, n_micro=2,
            mesh=None, window=5,
        )
        toks = jnp.asarray(
            np.random.RandomState(0).randint(0, 32, (4, 16)), jnp.int32
        )
        params = ref.init(jax.random.PRNGKey(0), toks)["params"]
        want = ref.apply({"params": params}, toks)
        got = model.apply({"params": params}, toks)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )
        # The window binds: a full-attention stack differs.
        full = PipelinedLM(
            vocab_size=32, d_model=32, n_heads=4, n_layers=4, n_micro=2,
            mesh=None,
        )
        other = full.apply({"params": params}, toks)
        assert float(jnp.abs(other - want).max()) > 1e-4
