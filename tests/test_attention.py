"""Sequence-parallel attention correctness: ring and Ulysses must match the
dense reference exactly (same math, different communication schedule), and
must be differentiable — the backward pass replays the ring."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from horovod_tpu.ops.attention import (
    dense_attention,
    ring_attention,
    ring_flash_attention,
    ulysses_attention,
)

B, T, H, D = 2, 32, 4, 8
SEQ_DEVICES = 4


def _qkv(seed=0):
    rng = np.random.RandomState(seed)
    shape = (B, T, H, D)
    return tuple(rng.randn(*shape).astype(np.float32) for _ in range(3))


def _seq_mesh():
    return Mesh(np.array(jax.devices()[:SEQ_DEVICES]), ("seq",))


def _sharded(fn, mesh, **kwargs):
    spec = P(None, "seq", None, None)
    return jax.jit(
        shard_map(
            functools.partial(fn, axis_name="seq", **kwargs),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
    )


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        q, k, v = _qkv()
        expected = dense_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                                   causal=causal)
        got = _sharded(ring_attention, _seq_mesh(), causal=causal)(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)

    def test_single_device_degenerates(self):
        q, k, v = _qkv(1)
        mesh = Mesh(np.array(jax.devices()[:1]), ("seq",))
        got = _sharded(ring_attention, mesh, causal=True)(q, k, v)
        expected = dense_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)

    def test_gradients_match_dense(self):
        q, k, v = _qkv(2)
        mesh = _seq_mesh()

        def loss_ring(q, k, v):
            return (_sharded(ring_attention, mesh, causal=True)(q, k, v) ** 2).sum()

        def loss_dense(q, k, v):
            return (dense_attention(q, k, v, causal=True) ** 2).sum()

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(*map(jnp.asarray, (q, k, v)))
        g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(*map(jnp.asarray, (q, k, v)))
        for a, b in zip(g_ring, g_dense):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


class TestRingFlashAttention:
    """Ring with flash-kernel block compute: same math as ring_attention,
    blockwise (out, lse) per hop merged by the logsumexp recurrence, with
    above-diagonal hops skipped via lax.cond rather than masked."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        q, k, v = _qkv(7)
        expected = dense_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal
        )
        got = _sharded(ring_flash_attention, _seq_mesh(), causal=causal)(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    def test_single_device_degenerates(self):
        q, k, v = _qkv(8)
        mesh = Mesh(np.array(jax.devices()[:1]), ("seq",))
        got = _sharded(ring_flash_attention, mesh, causal=True)(q, k, v)
        expected = dense_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    def test_gradients_match_dense(self):
        """The lse cotangent path: hop weights exp(lse_j - lse) depend on
        q/k, so ring-flash grads only match dense if d(lse)/d(q,k) flows
        correctly through the kernel's custom VJP."""
        q, k, v = _qkv(9)
        mesh = _seq_mesh()

        def loss_ring(q, k, v):
            return (
                _sharded(ring_flash_attention, mesh, causal=True)(q, k, v) ** 2
            ).sum()

        def loss_dense(q, k, v):
            return (dense_attention(q, k, v, causal=True) ** 2).sum()

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(
            *map(jnp.asarray, (q, k, v))
        )
        g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(
            *map(jnp.asarray, (q, k, v))
        )
        for a, b in zip(g_ring, g_dense):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            )

    def test_matches_dense_ring(self):
        """Flash-block and dense-block rings agree on the same shards."""
        q, k, v = _qkv(10)
        mesh = _seq_mesh()
        a = _sharded(ring_flash_attention, mesh, causal=True)(q, k, v)
        b = _sharded(ring_attention, mesh, causal=True)(q, k, v)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
        )


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        q, k, v = _qkv(3)
        expected = dense_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                                   causal=causal)
        got = _sharded(ulysses_attention, _seq_mesh(), causal=causal)(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)

    def test_rejects_indivisible_heads(self):
        mesh = _seq_mesh()
        rng = np.random.RandomState(0)
        bad = rng.randn(B, T, 6, D).astype(np.float32)  # 6 % 4 != 0
        with pytest.raises(ValueError, match="divisible"):
            _sharded(ulysses_attention, mesh)(bad, bad, bad)


class TestDenseAttention:
    def test_causal_masks_future(self):
        q, k, v = map(jnp.asarray, _qkv(4))
        out = dense_attention(q, k, v, causal=True)
        # Position 0 may only attend to k[0] → its output is exactly v[0].
        np.testing.assert_allclose(
            np.asarray(out[:, 0]), np.asarray(v[:, 0]), rtol=1e-5, atol=1e-5
        )


class TestSegmentedSequenceParallel:
    """Packed-sequence (segment-id) masking through the SP schemes — the ids
    shard with the tokens; kv ids ride the ring / gather across the swap."""

    def _ids(self, seed=30):
        rng = np.random.RandomState(seed)
        cuts = np.sort(rng.choice(np.arange(1, T), 3, replace=False))
        ids = np.searchsorted(cuts, np.arange(T), side="right")
        return np.broadcast_to(ids, (B, T)).astype(np.int32).copy()

    def _global_ref(self, q, k, v, ids, causal):
        from horovod_tpu.ops.flash_attention import flash_attention

        return flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal,
            q_segment_ids=jnp.asarray(ids), kv_segment_ids=jnp.asarray(ids),
        )

    def _sharded_seg(self, fn, mesh, **kwargs):
        spec = P(None, "seq", None, None)
        ispec = P(None, "seq")
        return jax.jit(
            shard_map(
                lambda q, k, v, ids: fn(
                    q, k, v, axis_name="seq", segment_ids=ids, **kwargs
                ),
                mesh=mesh,
                in_specs=(spec, spec, spec, ispec),
                out_specs=spec,
                check_vma=False,
            )
        )

    @pytest.mark.parametrize("causal", [True, False])
    def test_ring_flash_matches_global(self, causal):
        q, k, v = _qkv(31)
        ids = self._ids()
        got = self._sharded_seg(ring_flash_attention, _seq_mesh(), causal=causal)(
            q, k, v, ids
        )
        expected = self._global_ref(q, k, v, ids, causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    @pytest.mark.parametrize("causal", [True, False])
    def test_ulysses_matches_global(self, causal):
        q, k, v = _qkv(32)
        ids = self._ids(33)
        got = self._sharded_seg(ulysses_attention, _seq_mesh(), causal=causal)(
            q, k, v, ids
        )
        expected = self._global_ref(q, k, v, ids, causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    def test_ring_flash_segment_grads(self):
        q, k, v = _qkv(34)
        ids = self._ids(35)
        ring = self._sharded_seg(ring_flash_attention, _seq_mesh(), causal=True)

        g_ring = jax.grad(
            lambda q, k, v: (ring(q, k, v, ids) ** 2).sum(), argnums=(0, 1, 2)
        )(*map(jnp.asarray, (q, k, v)))
        g_ref = jax.grad(
            lambda q, k, v: (self._global_ref(q, k, v, ids, True) ** 2).sum(),
            argnums=(0, 1, 2),
        )(*map(jnp.asarray, (q, k, v)))
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            )


def test_dense_attention_empty_segment_rows_zero():
    """A q row whose segment has no kv tokens must output ZERO from
    dense_attention too (not softmax's uniform average of all values — a
    cross-segment leak), matching the flash kernel's empty-row convention."""
    rng = np.random.RandomState(40)
    q = jnp.asarray(rng.randn(1, 8, 2, 4).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 8, 2, 4).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 8, 2, 4).astype(np.float32))
    q_seg = jnp.asarray(np.array([[0, 0, 1, 1, 0, 0, 1, 1]], np.int32))
    kv_seg = jnp.zeros((1, 8), jnp.int32)
    out = dense_attention(
        q, k, v, causal=False, q_segment_ids=q_seg, kv_segment_ids=kv_seg
    )
    empty = np.asarray(q_seg)[0] == 1
    np.testing.assert_array_equal(np.asarray(out)[0, empty], 0.0)
    assert np.isfinite(np.asarray(out)).all()


class TestWindowedSequenceParallel:
    """Sliding-window attention across shard boundaries: the band is over
    GLOBAL positions, so it must be exact through the ring's hop arithmetic
    (static q_offset per hop distance) and Ulysses' head swap."""

    @pytest.mark.parametrize("window", [1, 5, 8, 20, T])
    def test_ring_flash_matches_dense(self, window):
        q, k, v = _qkv(21)
        expected = dense_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            causal=True, window=window,
        )
        got = _sharded(
            ring_flash_attention, _seq_mesh(), causal=True, window=window
        )(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    @pytest.mark.parametrize("window", [5, 20])
    def test_ring_dense_matches_dense(self, window):
        q, k, v = _qkv(22)
        expected = dense_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            causal=True, window=window,
        )
        got = _sharded(
            ring_attention, _seq_mesh(), causal=True, window=window
        )(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    def test_ulysses_matches_dense(self):
        q, k, v = _qkv(23)
        expected = dense_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            causal=True, window=9,
        )
        got = _sharded(
            ulysses_attention, _seq_mesh(), causal=True, window=9
        )(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    def test_ring_flash_gradients(self):
        q, k, v = _qkv(24)
        mesh = _seq_mesh()
        window = 11

        def loss_ring(q, k, v):
            out = _sharded(
                ring_flash_attention, mesh, causal=True, window=window
            )(q, k, v)
            return (out ** 2).sum()

        def loss_dense(q, k, v):
            return (
                dense_attention(q, k, v, causal=True, window=window) ** 2
            ).sum()

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(
            *map(jnp.asarray, (q, k, v))
        )
        g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(
            *map(jnp.asarray, (q, k, v))
        )
        for a, b in zip(g_ring, g_dense):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            )

    def test_ring_flash_segments_and_window(self):
        """Packed docs riding the windowed ring: intersection semantics,
        global-position band."""
        rng = np.random.RandomState(25)
        q, k, v = _qkv(25)
        ids = np.sort(rng.randint(0, 3, size=(B, T)), axis=1).astype(np.int32)
        expected = dense_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True,
            window=13, q_segment_ids=jnp.asarray(ids),
            kv_segment_ids=jnp.asarray(ids),
        )
        mesh = _seq_mesh()
        spec = P(None, "seq", None, None)
        got = jax.jit(
            shard_map(
                lambda q, k, v, ids: ring_flash_attention(
                    q, k, v, axis_name="seq", causal=True, window=13,
                    segment_ids=ids,
                ),
                mesh=mesh,
                in_specs=(spec, spec, spec, P(None, "seq")),
                out_specs=spec,
                check_vma=False,
            )
        )(q, k, v, ids)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    def test_window_requires_causal(self):
        q, k, v = _qkv(26)
        with pytest.raises(ValueError, match="causal"):
            _sharded(
                ring_flash_attention, _seq_mesh(), causal=False, window=4
            )(q, k, v)


class TestRingSinks:
    """Global+local through the flash ring: the hop holding global block 0
    contributes the sink columns (dense, disjoint from the band), merged
    by the same lse recurrence."""

    @pytest.mark.parametrize("window,sinks", [(5, 2), (9, 7), (16, 8)])
    def test_matches_dense(self, window, sinks):
        q, k, v = _qkv(41)
        expected = dense_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            causal=True, window=window, sinks=sinks,
        )
        got = _sharded(
            ring_flash_attention, _seq_mesh(), causal=True, window=window,
            sinks=sinks,
        )(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    def test_gradients_match_dense(self):
        q, k, v = _qkv(42)
        mesh = _seq_mesh()
        window, sinks = 7, 3

        def loss_ring(q, k, v):
            out = _sharded(
                ring_flash_attention, mesh, causal=True, window=window,
                sinks=sinks,
            )(q, k, v)
            return (out ** 2).sum()

        def loss_dense(q, k, v):
            return (dense_attention(
                q, k, v, causal=True, window=window, sinks=sinks
            ) ** 2).sum()

        g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(
            *map(jnp.asarray, (q, k, v))
        )
        g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(
            *map(jnp.asarray, (q, k, v))
        )
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            )

    def test_segments_compose(self):
        rng = np.random.RandomState(43)
        q, k, v = _qkv(43)
        ids = np.sort(rng.randint(0, 2, size=(B, T)), axis=1).astype(np.int32)
        expected = dense_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True,
            window=9, sinks=4, q_segment_ids=jnp.asarray(ids),
            kv_segment_ids=jnp.asarray(ids),
        )
        mesh = _seq_mesh()
        spec = P(None, "seq", None, None)
        got = jax.jit(
            shard_map(
                lambda q, k, v, ids: ring_flash_attention(
                    q, k, v, axis_name="seq", causal=True, window=9,
                    sinks=4, segment_ids=ids,
                ),
                mesh=mesh,
                in_specs=(spec, spec, spec, P(None, "seq")),
                out_specs=spec,
                check_vma=False,
            )
        )(q, k, v, ids)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    def test_ulysses_sinks(self):
        q, k, v = _qkv(44)
        expected = dense_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            causal=True, window=9, sinks=4,
        )
        got = _sharded(
            ulysses_attention, _seq_mesh(), causal=True, window=9, sinks=4
        )(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    def test_sinks_need_window_and_fit_shard(self):
        q, k, v = _qkv(45)
        with pytest.raises(ValueError, match="window"):
            _sharded(
                ring_flash_attention, _seq_mesh(), causal=True, sinks=4
            )(q, k, v)
        with pytest.raises(ValueError, match="shard"):
            _sharded(
                ring_flash_attention, _seq_mesh(), causal=True, window=9,
                sinks=T,  # > T/n
            )(q, k, v)


class TestRingCrossAttention:
    """Non-causal cross-attention over the seq ring (seq2seq's cross path):
    queries and memory shard DIFFERENT logical sequences."""

    def _cross(self, tq=32, tk=48, seed=3):
        rng = np.random.RandomState(seed)
        q = rng.randn(B, tq, H, D).astype(np.float32)
        k = rng.randn(B, tk, H, D).astype(np.float32)
        v = rng.randn(B, tk, H, D).astype(np.float32)
        return q, k, v

    def test_matches_dense_unequal_lengths(self):
        from horovod_tpu.ops.attention import ring_cross_attention

        q, k, v = self._cross()
        expected = dense_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=False
        )
        spec = P(None, "seq", None, None)
        got = jax.jit(
            shard_map(
                functools.partial(ring_cross_attention, axis_name="seq"),
                mesh=_seq_mesh(), in_specs=(spec, spec, spec),
                out_specs=spec, check_vma=False,
            )
        )(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    def test_padding_mask_and_gradients(self):
        from horovod_tpu.ops.attention import ring_cross_attention

        q, k, v = self._cross(tq=16, tk=32)
        kv_ids = np.ones((B, 32), np.int32)
        kv_ids[:, 20:] = 0  # padded memory tail
        q_ids = np.ones((B, 16), np.int32)
        spec = P(None, "seq", None, None)
        ids_spec = P(None, "seq")

        def ring(q, k, v, qi, ki):
            return ring_cross_attention(
                q, k, v, axis_name="seq", q_segment_ids=qi, kv_segment_ids=ki
            )

        f = jax.jit(
            shard_map(
                ring, mesh=_seq_mesh(),
                in_specs=(spec, spec, spec, ids_spec, ids_spec),
                out_specs=spec, check_vma=False,
            )
        )

        def loss_ring(q, k, v):
            return (f(q, k, v, jnp.asarray(q_ids), jnp.asarray(ki)) ** 2).sum()

        def loss_dense(q, k, v):
            return (
                dense_attention(
                    q, k, v, causal=False,
                    q_segment_ids=jnp.asarray(q_ids),
                    kv_segment_ids=jnp.asarray(ki),
                ) ** 2
            ).sum()

        ki = jnp.asarray(kv_ids)
        args = tuple(jnp.asarray(a) for a in (q, k, v))
        np.testing.assert_allclose(
            float(loss_ring(*args)), float(loss_dense(*args)), rtol=2e-5
        )
        g_r = jax.grad(loss_ring, argnums=(0, 1, 2))(*args)
        g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(*args)
        for a, b in zip(g_r, g_d):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
            )

    def test_all_pad_source_row_gives_zero(self):
        from horovod_tpu.ops.attention import ring_cross_attention

        q, k, v = self._cross(tq=16, tk=32)
        kv_ids = np.ones((B, 32), np.int32)
        kv_ids[1, :] = 0  # row 1: the whole source is padding
        q_ids = np.ones((B, 16), np.int32)
        spec = P(None, "seq", None, None)
        ids_spec = P(None, "seq")
        f = jax.jit(
            shard_map(
                lambda q, k, v, qi, ki: ring_cross_attention(
                    q, k, v, axis_name="seq",
                    q_segment_ids=qi, kv_segment_ids=ki,
                ),
                mesh=_seq_mesh(),
                in_specs=(spec, spec, spec, ids_spec, ids_spec),
                out_specs=spec, check_vma=False,
            )
        )
        out = f(*(jnp.asarray(a) for a in (q, k, v)),
                jnp.asarray(q_ids), jnp.asarray(kv_ids))
        assert float(jnp.abs(out[1]).max()) == 0.0
        assert float(jnp.abs(out[0]).max()) > 0.0

    def test_mismatched_ids_rejected(self):
        from horovod_tpu.ops.attention import ring_cross_attention

        q, k, v = self._cross(tq=16, tk=16)
        with pytest.raises(ValueError, match="pair"):
            # Outside shard_map is fine for the arg check: it raises before
            # any collective is touched.
            ring_cross_attention(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                q_segment_ids=jnp.ones((B, 16), jnp.int32),
            )
