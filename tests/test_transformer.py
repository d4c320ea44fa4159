"""Transformer LM over the full mesh: dp × seq × model composition, TP param
shardings, ring/Ulysses attention inside the training step, long-range
recall actually learned."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvt
from horovod_tpu.data import datasets
from horovod_tpu.models.transformer import (
    ShardingConfig,
    TransformerLM,
    param_specs,
)
from horovod_tpu.parallel import mesh as mesh_lib

VOCAB = 32


def _model(mesh=None, attn="ring", **kw):
    kw.setdefault("vocab_size", VOCAB)
    kw.setdefault("d_model", 64)
    kw.setdefault("n_heads", 4)
    kw.setdefault("n_layers", 2)
    kw.setdefault("dropout", 0.0)
    return TransformerLM(sharding=ShardingConfig(mesh=mesh, attn=attn), **kw)


def _trainer(mesh, attn="ring"):
    return hvt.Trainer(
        _model(mesh=mesh, attn=attn),
        hvt.DistributedOptimizer(optax.adam(3e-3)),
        loss="sparse_categorical_crossentropy",
        mesh=mesh,
        param_specs=param_specs,
        batch_specs=(P(("data", "fsdp"), "seq"), P(("data", "fsdp"), "seq")),
    )


class TestForward:
    def test_logit_shape_unsharded(self):
        model = _model()
        tokens = jnp.zeros((2, 16), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        logits = model.apply({"params": params}, tokens)
        assert logits.shape == (2, 16, VOCAB)
        assert logits.dtype == jnp.float32

    def test_causality(self):
        """Changing a future token must not change past logits."""
        model = _model()
        rng = np.random.RandomState(0)
        toks = rng.randint(1, VOCAB, size=(1, 16)).astype(np.int32)
        params = model.init(jax.random.PRNGKey(0), jnp.asarray(toks))["params"]
        out1 = model.apply({"params": params}, jnp.asarray(toks))
        toks2 = toks.copy()
        toks2[0, 10] = (toks2[0, 10] % (VOCAB - 1)) + 1
        out2 = model.apply({"params": params}, jnp.asarray(toks2))
        np.testing.assert_allclose(
            np.asarray(out1[0, :10]), np.asarray(out2[0, :10]), atol=1e-5
        )


@pytest.mark.slow
class TestMeshComposition:
    """dp=2 × seq=2 × model=2 on the 8 virtual devices — every parallelism
    axis live in one training step."""

    def _mesh(self):
        return mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, seq=2, model=2))

    @pytest.mark.parametrize("attn", ["ring", "ulysses"])
    def test_train_step_runs_and_learns(self, attn):
        mesh = self._mesh()
        trainer = _trainer(mesh, attn=attn)
        x, y = datasets.copy_task(512, 32, vocab_size=VOCAB, seed=0)
        history = trainer.fit(
            x=x, y=y, batch_size=8, epochs=2, steps_per_epoch=10, verbose=0
        )
        assert history[-1]["loss"] < history[0]["loss"]
        assert np.isfinite(history[-1]["loss"])

    def test_params_are_tp_sharded(self):
        mesh = self._mesh()
        trainer = _trainer(mesh)
        x, _ = datasets.copy_task(8, 32, vocab_size=VOCAB)
        state = trainer.build(x)
        flat = jax.tree_util.tree_flatten_with_path(state.params)[0]
        tp_sharded = [
            (path, leaf) for path, leaf in flat
            if any(
                "model" in (ax if isinstance(ax, tuple) else (ax,))
                for ax in leaf.sharding.spec if ax is not None
            )
        ]
        # QKV, proj, MLP up/down per layer + LM head must carry the model axis.
        assert len(tp_sharded) >= 4 * 2 + 1, [p for p, _ in flat]
        # Optimizer mirrors inherit the layout (adam mu for a TP kernel).
        opt_flat = jax.tree_util.tree_flatten_with_path(state.opt_state)[0]
        opt_tp = [
            1 for _, leaf in opt_flat
            if hasattr(leaf, "sharding")
            and any(
                "model" in (ax if isinstance(ax, tuple) else (ax,))
                for ax in getattr(leaf.sharding, "spec", P()) if ax is not None
            )
        ]
        assert len(opt_tp) >= 2 * (4 * 2 + 1)  # mu and nu trees

    def test_pure_dp_mesh_uses_flash_in_shard_map(self):
        """seq=1 multi-device mesh: the local flash kernel must run inside a
        manual shard_map (GSPMD can't partition a Mosaic call) and train."""
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=4, model=2))
        trainer = _trainer(mesh)
        x, y = datasets.copy_task(256, 32, vocab_size=VOCAB, seed=5)
        history = trainer.fit(
            x=x, y=y, batch_size=4, epochs=1, steps_per_epoch=6, verbose=0
        )
        assert np.isfinite(history[-1]["loss"])

    def test_dense_attn_option(self):
        """attn='dense' on an unsharded model takes the reference path."""
        model = _model(attn="dense")
        tokens = jnp.zeros((2, 16), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        assert model.apply({"params": params}, tokens).shape == (2, 16, VOCAB)

    def test_seq_parallel_rejects_dense(self):
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, seq=4))
        model = _model(mesh=mesh, attn="dense")
        with pytest.raises(ValueError, match="ring"):
            model.init(jax.random.PRNGKey(0), jnp.zeros((2, 32), jnp.int32))

    def test_evaluate_per_token_loss_with_padding(self):
        """evaluate() on a sequence model: per-token [G,T] losses weighted by
        the per-example padding mask, counted in tokens."""
        mesh = self._mesh()
        trainer = _trainer(mesh)
        x, y = datasets.copy_task(20, 32, vocab_size=VOCAB)  # 20 % 16 != 0 → padding
        trainer.build(x)
        result = trainer.evaluate(x, y, batch_size=4)
        assert np.isfinite(result["loss"])
        assert 0.0 <= result["accuracy"] <= 1.0

    def test_matches_unsharded_forward(self):
        """The sharded model must compute the same function."""
        mesh = self._mesh()
        sharded = _model(mesh=mesh)
        plain = _model()
        rng = np.random.RandomState(1)
        toks = jnp.asarray(rng.randint(1, VOCAB, size=(4, 32)).astype(np.int32))
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]
        out_plain = plain.apply({"params": params}, toks)
        out_sharded = jax.jit(
            lambda p, t: sharded.apply({"params": p}, t)
        )(params, toks)
        np.testing.assert_allclose(
            np.asarray(out_plain), np.asarray(out_sharded), rtol=5e-4, atol=5e-4
        )


@pytest.mark.slow
class TestFSDP:
    """fsdp > 1 exercised for real: parameters and optimizer mirrors sharded
    over the fsdp axis, and the training math identical to pure DP — FSDP is
    a memory layout, not a different algorithm."""

    def test_params_and_opt_state_fsdp_sharded(self):
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, fsdp=2, model=2))
        trainer = _trainer(mesh)
        x, _ = datasets.copy_task(8, 32, vocab_size=VOCAB)
        state = trainer.build(x)

        def fsdp_leaves(tree):
            flat = jax.tree_util.tree_flatten_with_path(tree)[0]
            return [
                path for path, leaf in flat
                if hasattr(leaf, "sharding")
                and any(
                    "fsdp" in (ax if isinstance(ax, tuple) else (ax,))
                    for ax in getattr(leaf.sharding, "spec", P())
                    if ax is not None
                )
            ]

        # Every >=2D kernel has an fsdp-shardable dim at these sizes: all
        # transformer matmul weights (2 layers x 4 + lm_head + embed).
        assert len(fsdp_leaves(state.params)) >= 4 * 2 + 1
        # Optimizer mirrors (adam mu/nu) carry the same layout.
        assert len(fsdp_leaves(state.opt_state)) >= 2 * (4 * 2 + 1)

    def test_fsdp_matches_pure_dp_math(self):
        """Same data, same seed: a data=2 x fsdp=2 x model=2 run must produce
        the same parameters as data=8 pure DP."""

        def run(mesh):
            trainer = _trainer(mesh)
            x, y = datasets.copy_task(256, 32, vocab_size=VOCAB, seed=4)
            trainer.fit(
                x=x, y=y, batch_size=4, epochs=1, steps_per_epoch=6,
                shuffle_buffer=1, verbose=0,
            )
            leaves = jax.tree.leaves(jax.device_get(trainer.state.params))
            return float(sum(np.abs(l).sum() for l in leaves))

        d_fsdp = run(mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, fsdp=2, model=2)))
        d_dp = run(mesh_lib.build_mesh(mesh_lib.MeshSpec(data=8)))
        # Tolerance: different mesh layouts reduce in different orders, and
        # 6 adam steps amplify that float noise (measured ~2e-4 rel); a real
        # sharding bug (wrong gather/reduce) diverges by orders of magnitude.
        assert d_fsdp == pytest.approx(d_dp, rel=1e-3)

    def test_fsdp4_train_step(self):
        """The example's HVT_MESH='data=2,fsdp=4' shape trains."""
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, fsdp=4))
        trainer = _trainer(mesh)
        x, y = datasets.copy_task(128, 32, vocab_size=VOCAB, seed=6)
        history = trainer.fit(
            x=x, y=y, batch_size=2, epochs=1, steps_per_epoch=4, verbose=0
        )
        assert np.isfinite(history[-1]["loss"])


@pytest.mark.slow
class TestMemoryKnobs:
    """Long-context memory options: remat must not change the math,
    bf16 logits must keep an f32-accurate loss through the upcasting
    built into the named losses."""

    def _tokens(self, seed=0):
        rng = np.random.RandomState(seed)
        return jnp.asarray(rng.randint(0, VOCAB, (2, 16)), jnp.int32)

    def test_remat_is_numerically_invisible(self):
        toks = self._tokens()
        base = _model()
        remat = _model(remat=True)
        params = base.init(jax.random.PRNGKey(0), toks, train=False)["params"]

        def loss(m, p):
            logits = m.apply({"params": p}, toks, train=True,
                             rngs={"dropout": jax.random.PRNGKey(1)})
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, toks
            ).mean()

        l0, g0 = jax.value_and_grad(lambda p: loss(base, p))(params)
        l1, g1 = jax.value_and_grad(lambda p: loss(remat, p))(params)
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )

    def test_remat_invisible_with_dropout(self):
        """RNG lifting through the remat boundary: the backward-pass
        recomputation must fold in the SAME dropout keys, or remat silently
        changes training math for any dropout>0 user."""
        toks = self._tokens(2)
        base = _model(dropout=0.3)
        remat = _model(dropout=0.3, remat=True)
        params = base.init(jax.random.PRNGKey(0), toks, train=False)["params"]

        def loss(m, p):
            logits = m.apply({"params": p}, toks, train=True,
                             rngs={"dropout": jax.random.PRNGKey(7)})
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, toks
            ).mean()

        l0, g0 = jax.value_and_grad(lambda p: loss(base, p))(params)
        l1, g1 = jax.value_and_grad(lambda p: loss(remat, p))(params)
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )

    def test_bf16_logits_loss_close_to_f32(self):
        toks = self._tokens(1)
        f32 = _model()
        bf16 = _model(logits_dtype=jnp.bfloat16)
        params = f32.init(jax.random.PRNGKey(0), toks, train=False)["params"]
        from horovod_tpu.training.trainer import _resolve_loss

        loss_fn = _resolve_loss("sparse_categorical_crossentropy")

        def loss(m, p):
            logits = m.apply({"params": p}, toks, train=False)
            return float(loss_fn(logits, toks).mean())

        assert bf16.apply({"params": params}, toks, train=False).dtype == jnp.bfloat16
        # bf16 rounding of the logits themselves bounds the difference;
        # the logsumexp math runs in f32 via the loss upcast.
        assert abs(loss(f32, params) - loss(bf16, params)) < 2e-2

    def test_remat_trains_through_trainer(self):
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=4, seq=2))
        trainer = hvt.Trainer(
            _model(mesh=mesh, remat=True, logits_dtype=jnp.bfloat16),
            hvt.DistributedOptimizer(optax.adam(3e-3)),
            loss="sparse_categorical_crossentropy",
            mesh=mesh,
            param_specs=param_specs,
            batch_specs=(P(("data", "fsdp"), "seq"), P(("data", "fsdp"), "seq")),
        )
        x, y = datasets.copy_task(8, 16, vocab_size=VOCAB)
        hist = trainer.fit(x=x, y=y, batch_size=4, epochs=2)
        assert np.isfinite(hist[-1]["loss"])
        assert hist[-1]["loss"] <= hist[0]["loss"] * 1.5  # sane training


@pytest.mark.slow
class TestLongRangeRecall:
    def test_copy_task_learned_through_ring(self):
        """The functional long-context check: recall-half loss → small, which
        is impossible without cross-shard attention (the copied token sits
        T/2 positions back, on a different seq shard)."""
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, seq=4))
        trainer = hvt.Trainer(
            _model(mesh=mesh, d_model=128, n_layers=2),
            hvt.DistributedOptimizer(optax.adam(3e-3)),
            mesh=mesh,
            param_specs=param_specs,
            batch_specs=(P(("data", "fsdp"), "seq"), P(("data", "fsdp"), "seq")),
        )
        x, y = datasets.copy_task(2048, 32, vocab_size=VOCAB, seed=2)
        trainer.fit(x=x, y=y, batch_size=16, epochs=3, steps_per_epoch=16, verbose=0)

        # Per-position loss on held-out sequences.
        xt, yt = datasets.copy_task(64, 32, vocab_size=VOCAB, seed=99)
        logits = np.log(trainer.predict(xt, batch_size=8) + 1e-9)
        ll = np.take_along_axis(logits, yt[..., None], axis=-1)[..., 0]
        recall_loss = -ll[:, 16:].mean()  # second half: pure recall
        first_loss = -ll[:, :14].mean()   # first half: irreducible ~log V
        assert recall_loss < first_loss * 0.5, (recall_loss, first_loss)


@pytest.mark.slow
class TestPackedSequences:
    """Packing invariance — the semantic contract of segment_ids: a document
    packed next to others must produce EXACTLY the logits it produces alone
    (segment-masked attention + per-document RoPE restart)."""

    def test_packed_positions(self):
        from horovod_tpu.models.transformer import packed_positions

        ids = jnp.asarray([[0, 0, 0, 1, 1, 2, 2, 2]])
        np.testing.assert_array_equal(
            np.asarray(packed_positions(ids)),
            [[0, 1, 2, 0, 1, 0, 1, 2]],
        )

    def test_packing_invariance_local(self):
        model = _model()  # no mesh: local flash/dense path
        rng = np.random.RandomState(7)
        doc_a = rng.randint(1, VOCAB, size=(1, 16)).astype(np.int32)
        doc_b = rng.randint(1, VOCAB, size=(1, 16)).astype(np.int32)
        packed = jnp.asarray(np.concatenate([doc_a, doc_b], axis=1))
        seg = jnp.asarray(
            np.concatenate([np.zeros((1, 16)), np.ones((1, 16))], axis=1)
        ).astype(jnp.int32)
        params = model.init(jax.random.PRNGKey(0), packed)["params"]
        out_packed = model.apply(
            {"params": params}, packed, segment_ids=seg
        )
        out_a = model.apply({"params": params}, jnp.asarray(doc_a))
        out_b = model.apply({"params": params}, jnp.asarray(doc_b))
        np.testing.assert_allclose(
            np.asarray(out_packed[0, :16]), np.asarray(out_a[0]),
            rtol=1e-4, atol=1e-4,
        )
        np.testing.assert_allclose(
            np.asarray(out_packed[0, 16:]), np.asarray(out_b[0]),
            rtol=1e-4, atol=1e-4,
        )

    def test_packed_seq_parallel_matches_local(self):
        """The ring path on a live seq axis computes the same packed logits
        as the local path (ids riding the ring)."""
        from horovod_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, seq=4))
        rng = np.random.RandomState(8)
        toks = rng.randint(1, VOCAB, size=(2, 32)).astype(np.int32)
        seg = np.repeat(np.arange(4), 8)[None].repeat(2, 0).astype(np.int32)
        local = _model()
        params = local.init(jax.random.PRNGKey(1), jnp.asarray(toks))["params"]
        ref = local.apply(
            {"params": params}, jnp.asarray(toks), segment_ids=jnp.asarray(seg)
        )
        ring = _model(mesh=mesh, attn="ring")
        with mesh:
            got = jax.jit(
                lambda p, t, s: ring.apply(
                    {"params": p}, t, segment_ids=s
                )
            )(params, jnp.asarray(toks), jnp.asarray(seg))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4
        )


@pytest.mark.slow
class TestGQA:
    """Grouped-query attention (n_kv_heads < n_heads): K/V heads shared by
    groups of query heads. The load-bearing equivalence: a GQA model must
    compute exactly what an MHA model computes when the MHA qkv kernel is
    assembled from the GQA projections with K/V repeated per group — the
    repeat is the definition of GQA."""

    def _gqa(self, mesh=None, attn="flash", **kw):
        return _model(mesh=mesh, attn=attn, n_heads=4, n_kv_heads=2, **kw)

    def _toks(self, seed=81, shape=(2, 16)):
        return jnp.asarray(
            np.random.RandomState(seed).randint(1, VOCAB, size=shape),
            jnp.int32,
        )

    def test_param_layout(self):
        toks = self._toks()
        gqa = self._gqa()
        params = gqa.init(jax.random.PRNGKey(0), toks)["params"]
        blk = params["Block_0"]
        assert "q_proj" in blk and "kv_proj" in blk and "qkv" not in blk
        assert blk["kv_proj"]["kernel"].shape == (64, 2, 32)  # [d, H_kv, 2hd]
        # MHA default keeps the fused layout (checkpoint compatibility)
        mha = _model()
        mp = mha.init(jax.random.PRNGKey(0), toks)["params"]
        assert "qkv" in mp["Block_0"] and "q_proj" not in mp["Block_0"]

    def test_equals_mha_with_repeated_kv(self):
        toks = self._toks(82)
        gqa = self._gqa()
        params = gqa.init(jax.random.PRNGKey(0), toks)["params"]
        rep = 2  # 4 heads / 2 kv heads

        def to_mha(block):
            out = dict(block)
            qk = out.pop("q_proj")["kernel"]          # [d, H, hd]
            kvk = out.pop("kv_proj")["kernel"]        # [d, H_kv, 2hd]
            kk, vk = np.split(np.asarray(kvk), 2, axis=-1)
            kk = np.repeat(kk, rep, axis=1)
            vk = np.repeat(vk, rep, axis=1)
            out["qkv"] = {
                "kernel": jnp.asarray(
                    np.concatenate([np.asarray(qk), kk, vk], axis=-1)
                )
            }
            return out

        mha_params = {
            k: (to_mha(v) if k.startswith("Block_") else v)
            for k, v in params.items()
        }
        out_gqa = self._gqa().apply({"params": params}, toks)
        out_mha = _model(attn="flash", n_heads=4).apply(
            {"params": mha_params}, toks
        )
        np.testing.assert_allclose(
            np.asarray(out_gqa), np.asarray(out_mha), rtol=1e-5, atol=1e-5
        )

    def test_ring_matches_unsharded(self):
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, seq=2, model=2))
        toks = self._toks(83, (4, 32))
        plain = self._gqa()
        params = plain.init(jax.random.PRNGKey(0), toks)["params"]
        out_plain = plain.apply({"params": params}, toks)
        out_sh = jax.jit(
            lambda p, t: self._gqa(mesh=mesh, attn="ring").apply(
                {"params": p}, t
            )
        )(params, toks)
        np.testing.assert_allclose(
            np.asarray(out_sh), np.asarray(out_plain), rtol=2e-4, atol=2e-4
        )

    def test_indivisible_heads_rejected(self):
        toks = self._toks(84)
        with pytest.raises(ValueError, match="n_kv_heads"):
            _model(n_heads=4, n_kv_heads=3).init(jax.random.PRNGKey(0), toks)

    def test_kv_heads_must_divide_model_axis(self):
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, model=4))
        toks = self._toks(85)
        model = _model(mesh=mesh, attn="flash", n_heads=8, n_kv_heads=2)
        with pytest.raises(ValueError, match="n_kv_heads"):
            model.init(jax.random.PRNGKey(0), toks)

    def test_trains(self):
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=8))
        trainer = hvt.Trainer(
            self._gqa(mesh=mesh, attn="ring"),
            hvt.DistributedOptimizer(optax.adam(3e-3)),
            loss="sparse_categorical_crossentropy",
            mesh=mesh,
            param_specs=param_specs,
            batch_specs=(P(("data", "fsdp"), "seq"), P(("data", "fsdp"), "seq")),
        )
        x, y = datasets.copy_task(256, 16, vocab_size=VOCAB, seed=3)
        hist = trainer.fit(
            x=x, y=y, batch_size=4, epochs=2, steps_per_epoch=6, verbose=0
        )
        assert np.isfinite(hist[-1]["loss"])
        assert hist[-1]["loss"] < hist[0]["loss"]


@pytest.mark.slow
class TestSlidingWindow:
    """TransformerLM(window=W): local attention end-to-end — every
    sequence-parallel impl must agree with the dense-windowed reference,
    and a windowed model must train."""

    def _toks(self, b=2, t=32, seed=0):
        return jnp.asarray(
            np.random.RandomState(seed).randint(0, VOCAB, (b, t)), jnp.int32
        )

    def test_impls_agree_with_dense(self):
        toks = self._toks()
        dense = _model(attn="dense", window=7)
        params = dense.init(jax.random.PRNGKey(0), toks)["params"]
        want = dense.apply({"params": params}, toks)
        # local flash path (no live seq axis)
        got_local = _model(window=7).apply({"params": params}, toks)
        np.testing.assert_allclose(
            np.asarray(got_local), np.asarray(want), rtol=2e-5, atol=2e-5
        )
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, seq=4))
        for attn in ("ring", "ulysses"):
            got = _model(mesh=mesh, attn=attn, window=7).apply(
                {"params": params}, toks
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5,
                err_msg=attn,
            )

    def test_window_binds(self):
        toks = self._toks(seed=1)
        full = _model()
        params = full.init(jax.random.PRNGKey(0), toks)["params"]
        a = full.apply({"params": params}, toks)
        b = _model(window=4).apply({"params": params}, toks)
        assert float(jnp.abs(a - b).max()) > 1e-3

    def test_windowed_model_trains_on_seq_mesh(self):
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, seq=2, model=2))
        trainer = hvt.Trainer(
            _model(mesh=mesh, attn="ring", window=8),
            hvt.DistributedOptimizer(optax.adam(3e-3)),
            loss="sparse_categorical_crossentropy",
            mesh=mesh,
            param_specs=param_specs,
            batch_specs=(P(("data", "fsdp"), "seq"), P(("data", "fsdp"), "seq")),
        )
        x, y = datasets.copy_task(8, 32, vocab_size=VOCAB)
        state = trainer.build(x)
        zero = trainer.zero_metrics()
        losses = []
        for _ in range(4):
            state, metrics, _ = trainer._train_step(
                state, trainer._shard((x, y)), np.float32(1.0), zero
            )
            losses.append(float(metrics["loss"]))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]


@pytest.mark.slow
class TestGlobalLocalOnMesh:
    """window + attention_sinks through the ring on a live seq mesh: the
    global+local model must match the dense reference, and train."""

    def test_ring_sinks_match_dense(self):
        toks = jnp.asarray(
            np.random.RandomState(5).randint(0, VOCAB, (2, 32)), jnp.int32
        )
        dense = _model(attn="dense", window=7, attention_sinks=3)
        params = dense.init(jax.random.PRNGKey(0), toks)["params"]
        want = dense.apply({"params": params}, toks)
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, seq=4))
        for attn in ("ring", "ulysses"):
            got = _model(
                mesh=mesh, attn=attn, window=7, attention_sinks=3
            ).apply({"params": params}, toks)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5,
                err_msg=attn,
            )

    def test_trains_on_seq_mesh(self):
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, seq=4))
        trainer = hvt.Trainer(
            _model(mesh=mesh, attn="ring", window=8, attention_sinks=4),
            hvt.DistributedOptimizer(optax.adam(3e-3)),
            loss="sparse_categorical_crossentropy",
            mesh=mesh,
            param_specs=param_specs,
            batch_specs=(P(("data", "fsdp"), "seq"), P(("data", "fsdp"), "seq")),
        )
        x, y = datasets.copy_task(8, 32, vocab_size=VOCAB)
        state = trainer.build(x)
        zero = trainer.zero_metrics()
        losses = []
        for _ in range(3):
            state, metrics, _ = trainer._train_step(
                state, trainer._shard((x, y)), np.float32(1.0), zero
            )
            losses.append(float(metrics["loss"]))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]


class TestLoRASpecExemption:
    """Regression for an earlier review's finding, the is_lora tightening: the TP/EP exemption is
    for LoRAModel *adapter* leaves (a 'lora' subtree with 'a'/'b' leaves) —
    a user submodule merely NAMED 'lora' must still get its kernels
    TP-sharded, or it silently trains unsharded."""

    def test_user_submodule_named_lora_still_tp_sharded(self):
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=4, model=2))
        params = {
            # Looks like a user's submodule that happens to be called lora:
            # ordinary kernels under layer names the rule table knows.
            "lora": {"mlp_up": {"kernel": np.zeros((8, 32), np.float32)}},
            # The real LoRAModel layout: adapters keep the exemption.
            "base": {"mlp_up": {"kernel": np.zeros((8, 32), np.float32)}},
        }
        specs = param_specs(params, mesh)
        assert specs["lora"]["mlp_up"]["kernel"] == P(None, "model")
        assert specs["base"]["mlp_up"]["kernel"] == P(None, "model")

    def test_adapter_leaves_keep_exemption_under_any_wrapper(self):
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=4, model=2))
        params = {
            "wrapper": {"lora": {"mlp_up": {
                "a": np.zeros((8, 2), np.float32),   # rank dim: unshardable
                "b": np.zeros((2, 32), np.float32),
            }}},
        }
        specs = param_specs(params, mesh)
        assert specs["wrapper"]["lora"]["mlp_up"]["a"] == P(None, None)
        assert specs["wrapper"]["lora"]["mlp_up"]["b"] == P(None, None)
