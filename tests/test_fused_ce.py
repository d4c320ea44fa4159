"""Fused chunked linear-CE (ops/fused_ce.py) and the Trainer loss='module'
contract: math parity with the dense logits path, gradient parity through
the custom VJP, the memory claim (no full [B·T, vocab] logits array)
verified against XLA's own memory analysis, and the head on a mesh: each
chip chunks its own rows, with one sum of dW after the backward loop."""

import math
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvt
from horovod_tpu.analysis import hlo_audit
from horovod_tpu.models.transformer import (
    BATCH_AXES,
    SEQ_AXIS,
    LMHead,
    ShardingConfig,
    TransformerLM,
    param_specs,
)
from horovod_tpu.ops import fused_ce
from horovod_tpu.ops.fused_ce import fused_linear_cross_entropy
from horovod_tpu.parallel import sharding as sharding_lib


def _dense_loss(h, w, labels):
    logits = (h.astype(jnp.float32) @ w.astype(jnp.float32))
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels)


# Both sides of the backward rule (`fused_ce.scans_vocab`): rows >= V scans
# the rows, rows < V scans the vocabulary. [B, T, V]; D is 16.
SHAPES = [
    # 48 rows: 3 chunks pad them to 3×16 — the non-divisible path.
    pytest.param((2, 24, 37), id="rows48-v37"),
    # 24 rows; one lane-padded slice whatever the chunk count.
    pytest.param((2, 12, 97), id="rows24-v97"),
    # Two slices of 128 from 3 chunks on.
    pytest.param((2, 12, 256), id="rows24-v256"),
    # A V no chunk count divides: 3 slices of 128 that pad 300 to 384.
    pytest.param((2, 12, 300), id="rows24-v300"),
]


def _data(shape, dtype=jnp.float32, d=16, seed=0):
    b, t, v = shape
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.randn(b, t, d), dtype)
    w = jnp.asarray(rng.randn(d, v) / np.sqrt(d), jnp.float32)
    labels = jnp.asarray(rng.randint(0, v, size=(b, t)), jnp.int32)
    return h, w, labels


def _grads(loss_fn, h, w, weights=None):
    """d(mean or weighted sum of the per-token loss)/d(h, w)."""
    def scalar(h, w):
        loss = loss_fn(h, w)
        return loss.mean() if weights is None else (loss * weights).sum()

    return jax.grad(scalar, argnums=(0, 1))(h, w)


@pytest.mark.parametrize("shape", SHAPES)
class TestFusedLinearCrossEntropy:
    def test_the_shapes_stand_on_both_sides_of_the_rule(self, shape):
        b, t, v = shape
        assert fused_ce.scans_vocab(b * t, v) == (v > 37)

    @pytest.mark.parametrize("n_chunks", [1, 3, 8])
    def test_loss_matches_dense(self, shape, n_chunks):
        h, w, labels = _data(shape)
        loss, correct = fused_linear_cross_entropy(h, w, labels, n_chunks)
        assert loss.shape == labels.shape and correct.shape == labels.shape
        ref = _dense_loss(h, w, labels)
        np.testing.assert_allclose(loss, ref, rtol=1e-5, atol=1e-5)

    def test_correct_indicator_matches_argmax(self, shape):
        h, w, labels = _data(shape)
        _, correct = fused_linear_cross_entropy(h, w, labels, 4)
        pred = jnp.argmax(h @ w, axis=-1)
        np.testing.assert_array_equal(
            np.asarray(correct, bool), np.asarray(pred == labels)
        )

    @pytest.mark.parametrize("n_chunks", [1, 3, 5, 8])
    def test_gradients_match_dense(self, shape, n_chunks):
        h, w, labels = _data(shape)
        dh_f, dw_f = _grads(
            lambda h, w: fused_linear_cross_entropy(
                h, w, labels, n_chunks)[0], h, w)
        dh_d, dw_d = _grads(lambda h, w: _dense_loss(h, w, labels), h, w)
        np.testing.assert_allclose(dh_f, dh_d, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(dw_f, dw_d, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("n_chunks", [1, 3, 8])
    def test_masked_tokens_gradients_match_dense(self, shape, n_chunks):
        # A loss mask: a third of the tokens count for nothing, the others
        # each with a weight of its own — the cotangent the Trainer hands
        # the op for padded and boundary-masked tokens.
        h, w, labels = _data(shape)
        rng = np.random.RandomState(1)
        weights = jnp.asarray(
            (rng.rand(*labels.shape) > 0.33) * rng.uniform(
                0.5, 1.5, labels.shape), jnp.float32)
        dh_f, dw_f = _grads(
            lambda h, w: fused_linear_cross_entropy(
                h, w, labels, n_chunks)[0], h, w, weights)
        dh_d, dw_d = _grads(
            lambda h, w: _dense_loss(h, w, labels), h, w, weights)
        np.testing.assert_allclose(dh_f, dh_d, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(dw_f, dw_d, rtol=1e-5, atol=1e-6)
        masked = np.asarray(weights) == 0
        assert masked.any() and not np.asarray(dh_f)[masked].any()

    def test_bf16_hidden_states(self, shape):
        h, w, labels = _data(shape, dtype=jnp.bfloat16)
        loss, _ = fused_linear_cross_entropy(h, w, labels, 4)
        ref = _dense_loss(
            h.astype(jnp.float32), w, labels
        )
        # bf16 inputs with f32 MXU accumulation: 8-bit-mantissa input error.
        np.testing.assert_allclose(loss, ref, rtol=3e-2, atol=3e-2)
        dh, dw = _grads(
            lambda h, w: fused_linear_cross_entropy(h, w, labels, 4)[0],
            h, w)
        assert dh.dtype == jnp.bfloat16 and dw.dtype == jnp.float32
        dh_d, dw_d = _grads(
            lambda h, w: _dense_loss(h, w, labels),
            h.astype(jnp.float32), w)
        np.testing.assert_allclose(
            dh.astype(jnp.float32), dh_d, rtol=3e-2, atol=3e-3)
        np.testing.assert_allclose(dw, dw_d, rtol=3e-2, atol=3e-3)

    def test_correct_cotangent_is_discarded(self, shape):
        # Differentiating THROUGH the correctness indicator must not
        # contribute (argmax is piecewise constant, like the dense path).
        h, w, labels = _data(shape)

        def f(h):
            loss, correct = fused_linear_cross_entropy(h, w, labels, 2)
            return loss.mean() + 7.0 * correct.sum()

        dh = jax.grad(f)(h)
        dh_ref = jax.grad(
            lambda h: fused_linear_cross_entropy(h, w, labels, 2)[0].mean()
        )(h)
        np.testing.assert_allclose(dh, dh_ref, rtol=1e-6)


@pytest.mark.parametrize(
    # [B, T, D, V]; logits of 4 MB / 8 MB a copy dominate either way.
    "b,t,d,v",
    [pytest.param(2, 128, 32, 4096, id="rows256-v4096-vocab_scan"),
     pytest.param(2, 1024, 32, 1024, id="rows2048-v1024-row_scan")],
)
def test_peak_memory_scales_down_with_chunks(b, t, d, v):
    # The op's reason to exist: XLA's own accounting shows the compiled
    # backward never holds the full [N, V] logits when chunked, whichever
    # axis the chunks cut.
    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randn(b, t, d), jnp.float32)
    w = jnp.asarray(rng.randn(d, v) / 6.0, jnp.float32)
    labels = jnp.asarray(rng.randint(0, v, size=(b, t)), jnp.int32)

    def temp_bytes(n_chunks):
        def f(h, w):
            loss, _ = fused_linear_cross_entropy(h, w, labels, n_chunks)
            return loss.mean()

        compiled = jax.jit(jax.grad(f, argnums=(0, 1))).lower(h, w).compile()
        return int(compiled.memory_analysis().temp_size_in_bytes)

    one = temp_bytes(1)   # dense-equivalent: full logits tile
    many = temp_bytes(16)
    assert many < one / 4, (one, many)


def _backward_hlo(shape, n_chunks=3, vocab_split=False):
    h, w, labels = _data(shape)

    def f(h, w):
        return fused_linear_cross_entropy(
            h, w, labels, n_chunks, vocab_split)[0].mean()

    return jax.jit(jax.grad(f, argnums=(0, 1))).lower(h, w).compile().as_text()


def _scan_loops(hlo, axis):
    """The bodies of the head's loops that carry the sub-scope ``axis``."""
    return hlo_audit.while_bodies(hlo, f"/{axis}/while")


def test_row_scan_is_still_the_one_loop_that_sums_dw():
    """rows >= V, and a kernel whose vocabulary the partitioner splits
    (`vocab_split`) whatever the sizes: the row scan as it always was —
    one loop (the loss is not asked for, so the forward scan is dead code)
    whose carry is the float32 dW."""
    d = 16
    for shape, split in ((SHAPES[0].values[0], False),
                         (SHAPES[3].values[0], True)):
        hlo = _backward_hlo(shape, vocab_split=split)
        body, = hlo_audit.while_bodies(hlo, fused_ce.SCOPE)
        assert _scan_loops(hlo, fused_ce.ROW_SCAN) == [body]
        assert re.search(rf"f32\[{d},{shape[2]}\]\S* add\(", body)


def test_vocab_scan_sums_dh_and_writes_each_dw_slice_once():
    """rows < V: the forward scan (kept for its logsumexp) and a loop over
    3 slices of 128 of a vocabulary of 300 whose running sum is dh
    [24, 16]; dW is only written, one slice an iteration."""
    (b, t, v), d, width = SHAPES[3].values[0], 16, 128
    hlo = _backward_hlo((b, t, v))
    assert len(hlo_audit.while_bodies(hlo, fused_ce.SCOPE)) == 2
    body, = _scan_loops(hlo, fused_ce.VOCAB_SCAN)
    assert not _scan_loops(hlo, fused_ce.ROW_SCAN)
    assert f"f32[{b * t},{width}]" in body       # the tile
    assert f"f32[{b * t},{v}]" not in hlo        # never the whole logits
    assert re.search(rf"f32\[{b * t},{d}\]\S* add\(", body)
    dw = rf"f32\[{d},{3 * width}\]\S*"
    assert re.search(rf"{dw} dynamic-update-slice\(", body)
    assert not re.search(rf"{dw} add\(", body)


@pytest.mark.slow
class TestModuleLossTrainer:
    """TransformerLM(fused_head_chunks=...) + Trainer(loss='module')."""

    def _fit(self, loss, fused_chunks, steps=6, mesh=None, **model_kw):
        if mesh is not None:
            model_kw["sharding"] = ShardingConfig(mesh=mesh)
        model = TransformerLM(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, dropout=0.0,
            fused_head_chunks=fused_chunks, **model_kw,
        )
        trainer = hvt.Trainer(
            model, hvt.DistributedOptimizer(optax.adam(1e-2)), loss=loss,
            mesh=mesh,
        )
        rng = np.random.RandomState(0)
        x = rng.randint(1, 64, size=(16, 12)).astype(np.int32)
        y = np.roll(x, -1, axis=1).astype(np.int32)
        state = trainer.build(x)
        zero = trainer.zero_metrics()
        losses = []
        for _ in range(steps):
            state, metrics, _ = trainer._train_step(
                state, trainer._shard((x, y)), np.float32(1.0), zero
            )
            losses.append(float(metrics["loss"]))
        trainer.state = state  # the originally-built state was donated
        return trainer, state, losses, (x, y), float(metrics["accuracy"])

    def test_training_matches_logits_path(self):
        _, state_m, losses_m, _, acc_m = self._fit("module", 4)
        _, state_d, losses_d, _, acc_d = self._fit(
            "sparse_categorical_crossentropy", 0
        )
        # Same math, different matmul chunking → fp-accumulation-order-level
        # differences only.
        np.testing.assert_allclose(losses_m, losses_d, rtol=1e-4)
        np.testing.assert_allclose(acc_m, acc_d, rtol=1e-4)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5),
            state_m.params, state_d.params,
        )

    def test_evaluate_matches_logits_path(self):
        trainer_m, state_m, _, (x, y), _ = self._fit("module", 4, steps=2)
        trainer_d, _, _, _, _ = self._fit(
            "sparse_categorical_crossentropy", 0, steps=2
        )
        # Same trained params through both eval paths — including the padded
        # tail batch (20 examples over batch 8 → mask exercises the
        # per-token broadcast).
        trainer_d.state = trainer_d.state.replace(params=state_m.params)
        xs = np.concatenate([x, x[:4]])
        ys = np.concatenate([y, y[:4]])
        em = trainer_m.evaluate(xs, ys, batch_size=8)
        ed = trainer_d.evaluate(xs, ys, batch_size=8)
        np.testing.assert_allclose(em["loss"], ed["loss"], rtol=1e-4)
        np.testing.assert_allclose(em["accuracy"], ed["accuracy"], rtol=1e-4)

    def test_predict_still_returns_probs(self):
        trainer, _, _, (x, _), _ = self._fit("module", 4, steps=1)
        probs = trainer.predict(x[:4])
        assert probs.shape == (4, 12, 64)
        np.testing.assert_allclose(probs.sum(-1), 1.0, rtol=1e-5)

    def test_composes_with_remat_and_bf16(self):
        # The long-context stack: remat blocks + bf16 compute + fused head.
        _, _, losses, _, _ = self._fit(
            "module", 4, steps=3, remat=True,
            compute_dtype=jnp.bfloat16,
        )
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]

    def test_checkpoint_param_path_unchanged(self):
        # The explicit LMHead keeps the DenseGeneral-era param tree:
        # lm_head/kernel [d_model, vocab] — old checkpoints stay loadable.
        model = TransformerLM(vocab_size=64, d_model=32, n_heads=4, n_layers=1)
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        assert params["lm_head"]["kernel"].shape == (32, 64)


# --- the head on a mesh: each chip's own rows ------------------------------

def mesh_of(**axes):
    return hvt.build_mesh(
        hvt.MeshSpec(**axes),
        devices=jax.devices()[:math.prod(axes.values())],
    )


def on_mesh(mesh, tree, specs):
    return jax.tree.map(
        lambda a, spec: jax.device_put(
            a, jax.sharding.NamedSharding(mesh, spec)),
        tree, specs,
    )


ROWS = jax.sharding.PartitionSpec(BATCH_AXES, SEQ_AXIS)


class TestHeadOnEachChipsOwnRows:
    # 4 x 10 rows in 3 chunks: 40 rows pad to 42 without a mesh, and a
    # chip's 10 (or 20 on data=2 x model=2) pad to 12 (21) on one. Fewer
    # rows than the vocabulary everywhere, so the backward takes 3 slices
    # of 128 of it (300 pad to 384) — but on data=2 x model=2, where the
    # partitioner holds the vocabulary and the rows are scanned.
    B, T, D, V, CHUNKS = 4, 10, 32, 300, 3

    def _lm(self, sharding):
        return TransformerLM(
            vocab_size=self.V, d_model=self.D, n_heads=4, n_layers=1,
            dropout=0.0, fused_head_chunks=self.CHUNKS, sharding=sharding,
        )

    def _tokens(self):
        rng = np.random.RandomState(0)
        x = rng.randint(1, self.V, size=(self.B, self.T)).astype(np.int32)
        return x, np.roll(x, -1, axis=1)

    @staticmethod
    def _weighted(loss):
        # Every token's cotangent differs: a row that lands on the wrong
        # chip, or a padded one that counts, shows in the gradients.
        return (loss * jnp.linspace(0.5, 1.5, loss.size).reshape(
            loss.shape)).mean()

    @pytest.mark.parametrize(
        "axes",
        [dict(data=4), dict(data=2, fsdp=2), dict(data=2, seq=2),
         dict(data=2, model=2)],
        ids=["data4", "data2-fsdp2", "data2-seq2", "data2-model2"],
    )
    def test_matches_the_meshless_single_device_values(self, axes):
        x, y = self._tokens()
        mesh = mesh_of(**axes)
        on_seq = "seq" in axes

        # The head alone: loss, correct, dh, dW.
        rng = np.random.RandomState(1)
        h = jnp.asarray(rng.randn(self.B, self.T, self.D), jnp.float32)
        w = jnp.asarray(rng.randn(self.D, self.V) / 6.0, jnp.float32)

        def head_values(sharding, h, w, labels):
            head = LMHead(self.D, self.V, sharding=sharding)

            def f(h, w):
                loss, correct = head.apply(
                    {"params": {"kernel": w}}, h, labels, self.CHUNKS,
                    method="fused_loss")
                return self._weighted(loss), (loss, correct)

            return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(
                h, w)

        (_, (loss0, correct0)), (dh0, dw0) = head_values(
            ShardingConfig(), h, w, y)
        head_spec = param_specs({"lm_head": {"kernel": w}}, mesh)
        (_, (loss1, correct1)), (dh1, dw1) = head_values(
            ShardingConfig(mesh=mesh),
            *on_mesh(mesh, (h, w, y), (
                jax.sharding.PartitionSpec(BATCH_AXES, SEQ_AXIS, None),
                head_spec["lm_head"]["kernel"], ROWS)),
        )
        # Per-token values come back split as the rows went in.
        assert loss1.sharding.is_equivalent_to(
            jax.sharding.NamedSharding(mesh, ROWS), 2)
        np.testing.assert_allclose(loss1, loss0, rtol=1e-5)
        np.testing.assert_array_equal(correct1, correct0)
        np.testing.assert_allclose(dh1, dh0, rtol=1e-5, atol=1e-8)
        np.testing.assert_allclose(dw1, dw0, rtol=1e-5, atol=1e-8)

        # Through the model: the same values, and the rest of the
        # gradients, which dh feeds.
        def lm_values(model, params, x, y):
            def f(params):
                loss, correct = model.apply({"params": params}, x, labels=y)
                return self._weighted(loss), (loss, correct)

            return jax.jit(jax.value_and_grad(f, has_aux=True))(params)

        plain = self._lm(ShardingConfig(attn="dense"))
        params = plain.init(jax.random.PRNGKey(0), x, labels=y)["params"]
        (_, (loss0, correct0)), g0 = lm_values(plain, params, x, y)
        sharded = self._lm(ShardingConfig(
            mesh=mesh, attn="ring_dense" if on_seq else "dense"))
        (_, (loss1, correct1)), g1 = lm_values(
            sharded, on_mesh(mesh, params, param_specs(params, mesh)),
            *on_mesh(mesh, (x, y), (ROWS, ROWS)))
        np.testing.assert_allclose(loss1, loss0, rtol=1e-5)
        np.testing.assert_array_equal(correct1, correct0)
        np.testing.assert_allclose(
            g1["lm_head"]["kernel"], g0["lm_head"]["kernel"],
            rtol=1e-5, atol=1e-7)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                a, b, rtol=1e-4, atol=1e-6),
            g1, g0,
        )

    @pytest.mark.parametrize(
        "axes,region",
        [(None, False), (dict(data=1), False), (dict(data=4), True)],
        ids=["no-mesh", "one-device-mesh", "data4"],
    )
    def test_only_a_mesh_that_splits_rows_wraps_the_head(self, axes, region):
        # One device (or no mesh): the plain call, today's program.
        x, y = self._tokens()
        model = self._lm(ShardingConfig(
            mesh=None if axes is None else mesh_of(**axes), attn="dense"))
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), x, labels=y)
        )["params"]
        jaxpr = str(jax.make_jaxpr(
            lambda p: model.apply({"params": p}, x, labels=y))(params))
        assert ("shard_map" in jaxpr) == region

    def test_trainer_on_a_data_mesh_follows_the_meshless_run(self):
        fit = TestModuleLossTrainer()._fit
        _, state_m, losses_m, _, acc_m = fit(
            "module", 4, steps=3, mesh=mesh_of(data=4))
        _, state_p, losses_p, _, acc_p = fit("module", 4, steps=3)
        np.testing.assert_allclose(losses_m, losses_p, rtol=1e-4)
        np.testing.assert_allclose(acc_m, acc_p, rtol=1e-4)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5),
            state_m.params, state_p.params,
        )


def test_compiled_data_parallel_step_keeps_the_head_local():
    """The compiled data=4 step (CPU's partitioner is the chip's GSPMD):
    no collective inside the head's loops, tiles of the chip's own rows —
    32 of them against a vocabulary of 512, so the forward scans their 2
    chunks and the backward 2 slices of the vocabulary — and dW crossing
    the chips once."""
    d, v, seq, per_chip, chunks = 32, 512, 16, 2, 2
    mesh = mesh_of(data=4)
    model = TransformerLM(
        vocab_size=v, d_model=d, n_heads=4, n_layers=1, dropout=0.0,
        fused_head_chunks=chunks, sharding=ShardingConfig(mesh=mesh),
    )
    trainer = hvt.Trainer(
        model, hvt.DistributedOptimizer(optax.adamw(1e-3)), loss="module",
        mesh=mesh,
    )
    x = (np.arange(4 * per_chip * seq, dtype=np.int32) % v).reshape(-1, seq)
    state = trainer.build(x[:4], x[:4])
    hlo = trainer._train_step.lower(
        state, trainer._shard((x, x)), jnp.asarray(1.0, jnp.float32),
        sharding_lib.replicate(trainer.zero_metrics(), mesh),
    ).compile().as_text()

    bodies = hlo_audit.while_bodies(hlo, fused_ce.SCOPE)
    assert len(bodies) == 2  # the forward scan and the backward loop
    backward, = _scan_loops(hlo, fused_ce.VOCAB_SCAN)
    forward, = (body for body in bodies if body != backward)
    rows = per_chip * seq
    # [a chunk of the chip's rows, V] forward; [the chip's rows, a slice of
    # V] backward; never the global batch's.
    assert f"f32[{rows // chunks},{v}]" in forward
    assert f"f32[{4 * rows // chunks},{v}]" not in forward
    assert f"f32[{rows},{v // chunks}]" in backward
    assert f"f32[{4 * rows}," not in backward
    for body in bodies:
        assert not hlo_audit.collective_ops(body)
    # dW [D, V] float32 crosses the chips once (alone, or as one operand of
    # a combined all-reduce), after the loops.
    reduced = re.findall(
        r"= (.*?) (?:all-reduce|reduce-scatter)(?:-start)?\(", hlo)
    assert sum(types.count(f"f32[{d},{v}]") for types in reduced) == 1


@pytest.mark.parametrize("told", [True, False], ids=["told", "not-told"])
def test_a_split_vocabulary_keeps_the_row_scan(told, monkeypatch):
    """Why the op takes `vocab_split`, and that `LMHead` wires it: the
    compiled data=2 x model=2 step, 32 rows a chip against a vocabulary of
    512 (`scans_vocab` alone would scan the vocabulary). Told, the head
    scans the rows over the chip's own half of the kernel: tiles and dW
    `[.., V/2]`, no all-gather of the kernel. Not told (the flag dropped on
    its way to the op), the partitioner gathers the whole `[D, V]` kernel
    for the loop over its slices and every chip of a `model` group
    computes every slice. The day the second half fails, the partitioner
    has learnt to loop over a split dimension and the argument can go."""
    d, v, seq, per_chip, chunks = 32, 512, 16, 2, 2
    if not told:
        op = fused_ce.fused_linear_cross_entropy
        monkeypatch.setattr(
            fused_ce, "fused_linear_cross_entropy",
            lambda h, w, labels, n, vocab_split: op(h, w, labels, n))
    mesh = mesh_of(data=2, model=2)
    model = TransformerLM(
        vocab_size=v, d_model=d, n_heads=4, n_layers=1, dropout=0.0,
        fused_head_chunks=chunks, sharding=ShardingConfig(mesh=mesh),
    )
    trainer = hvt.Trainer(
        model, hvt.DistributedOptimizer(optax.adamw(1e-3)), loss="module",
        mesh=mesh, param_specs=param_specs,
    )
    x = (np.arange(2 * per_chip * seq, dtype=np.int32) % v).reshape(-1, seq)
    state = trainer.build(x[:2], x[:2])
    hlo = trainer._train_step.lower(
        state, trainer._shard((x, x)), jnp.asarray(1.0, jnp.float32),
        sharding_lib.replicate(trainer.zero_metrics(), mesh),
    ).compile().as_text()

    rows = per_chip * seq
    assert rows < v
    gathered = re.findall(r"= (\S+?)\{\S* all-gather(?:-start)?\(", hlo)
    reduced = re.findall(
        r"= (.*?) (?:all-reduce|reduce-scatter)(?:-start)?\(", hlo)
    whole, half = f"f32[{d},{v}]", f"f32[{d},{v // 2}]"
    if told:
        backward, = _scan_loops(hlo, fused_ce.ROW_SCAN)
        assert not _scan_loops(hlo, fused_ce.VOCAB_SCAN)
        assert f"f32[{rows // chunks},{v // 2}]" in backward
        assert whole not in hlo
        assert sum(types.count(half) for types in reduced) == 1
    else:
        assert _scan_loops(hlo, fused_ce.VOCAB_SCAN)
        assert whole in gathered


class TestBuildTracesFusedPath:
    def test_init_receives_labels_under_module_loss(self):
        # build() must init with dummy labels so the module traces the
        # fused-CE branch — the dense [B, T, vocab] branch at init is the
        # OOM point at long-context scale (an earlier review's finding, trainer.py build).
        seen = []

        class Rec(nn.Module):
            @nn.compact
            def __call__(self, tokens, train: bool = False, labels=None):
                seen.append(labels is not None)
                emb = self.param(
                    "emb", nn.initializers.normal(0.02), (64, 8)
                )
                h = emb[tokens].mean(axis=1) @ emb.T  # [B, 64]
                if labels is None:
                    return h
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    h, labels[:, 0]
                )
                correct = (jnp.argmax(h, -1) == labels[:, 0]).astype(
                    jnp.float32
                )
                return loss, correct

        trainer = hvt.Trainer(
            Rec(), hvt.DistributedOptimizer(optax.adam(1e-2)), loss="module"
        )
        x = np.random.RandomState(0).randint(1, 64, size=(8, 4)).astype(
            np.int32
        )
        trainer.build(x)
        assert seen and all(seen), seen

    def test_build_with_sample_y_for_non_token_labels(self):
        # labels that differ from x in dtype/shape (float inputs, int class
        # labels): build must use the provided sample_y, not zeros_like(x).
        class Clf(nn.Module):
            @nn.compact
            def __call__(self, x, train: bool = False, labels=None):
                w = self.param("w", nn.initializers.normal(0.02), (4, 8))
                h = x @ w  # [B, 8] logits
                if labels is None:
                    return h
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    h, labels
                )
                correct = (jnp.argmax(h, -1) == labels).astype(jnp.float32)
                return loss, correct

        trainer = hvt.Trainer(
            Clf(), hvt.DistributedOptimizer(optax.adam(1e-2)), loss="module"
        )
        x = np.random.RandomState(0).randn(16, 4).astype(np.float32)
        y = (np.arange(16) % 8).astype(np.int32)
        # fit threads the real labels through to init.
        history = trainer.fit(x=x, y=y, batch_size=2, epochs=1, verbose=0)
        assert np.isfinite(history[-1]["loss"])

    def test_build_without_sample_y_raises_with_hint(self):
        # Same classifier, but build(x) alone: zeros_like(float x) is a
        # wrong-typed label for the integer-CE branch. The failure must
        # carry a hint naming sample_y instead of an opaque trace error.
        class Clf(nn.Module):
            @nn.compact
            def __call__(self, x, train: bool = False, labels=None):
                w = self.param("w", nn.initializers.normal(0.02), (4, 8))
                h = x @ w
                if labels is None:
                    return h
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    h, labels
                )
                correct = (jnp.argmax(h, -1) == labels).astype(jnp.float32)
                return loss, correct

        trainer = hvt.Trainer(
            Clf(), hvt.DistributedOptimizer(optax.adam(1e-2)), loss="module"
        )
        x = np.random.RandomState(0).randn(16, 4).astype(np.float32)
        with pytest.raises(Exception, match="sample_y"):
            trainer.build(x)
