"""Pallas flash-attention kernel vs the dense reference (interpret mode —
the same kernel code the TPU compiles, run through the pallas interpreter)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops.attention import dense_attention
from horovod_tpu.ops.flash_attention import (
    _dense_with_lse,
    flash_attention,
    flash_attention_with_lse,
    pick_blocks,
    supported,
    tile_census,
)

B, T, H, D = 2, 128, 4, 64
BLOCKS = dict(block_q=32, block_k=32)


def _qkv(seed=0):
    rng = np.random.RandomState(seed)
    return tuple(
        jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)) for _ in range(3)
    )


class TestForward:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal=causal, **BLOCKS)
        expected = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    def test_uneven_blocks(self):
        """bq != bk exercises the off-diagonal causal masking."""
        q, k, v = _qkv(1)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=32)
        expected = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    def test_fallback_when_unsupported(self):
        """Tiling that doesn't divide T falls back to dense, not an error."""
        rng = np.random.RandomState(2)
        q = jnp.asarray(rng.randn(1, 100, 2, 16).astype(np.float32))
        assert not supported(q.shape, 64, 64)
        out = flash_attention(q, q, q, causal=True, block_q=64, block_k=64)
        expected = dense_attention(q, q, q, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
        )

    def test_fallback_unaligned_sublane(self):
        """T < block clamps blocks to T; a non-sublane-aligned T (e.g. 100)
        must fall back rather than hit the kernel with unaligned tiles."""
        rng = np.random.RandomState(5)
        q = jnp.asarray(rng.randn(1, 100, 2, 64).astype(np.float32))
        # After clamping, block_q = block_k = 100, which divides T but is
        # not a multiple of the f32 sublane granule (8).
        assert not supported(q.shape, 100, 100)
        out = flash_attention(q, q, q, causal=True)
        expected = dense_attention(q, q, q, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
        )
        # bf16 needs 16-sublane tiles: an 8-aligned block is f32-only.
        assert supported((1, 104, 2, 64), 8, 8, dtype=jnp.float32)
        assert not supported((1, 104, 2, 64), 8, 8, dtype=jnp.bfloat16)

    def test_cross_attention_runs_kernel(self):
        """Tk != Tq runs the kernel on a rectangular nq×nk grid (round-3:
        previously this was a dense fallback) and matches dense exactly."""
        rng = np.random.RandomState(6)
        q = jnp.asarray(rng.randn(1, 64, 2, 64).astype(np.float32))
        k = jnp.asarray(rng.randn(1, 128, 2, 64).astype(np.float32))
        v = jnp.asarray(rng.randn(1, 128, 2, 64).astype(np.float32))
        assert supported(q.shape, 32, 32, k_shape=k.shape)
        out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
        expected = dense_attention(q, k, v, causal=False)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
        )


@pytest.mark.slow
class TestWithLse:
    """The (out, lse) kernel entry that cross-chip merges build on."""

    def _dense_ref(self, q, k, v, causal=True):
        scale = q.shape[-1] ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        if causal:
            tq, tk = s.shape[-2:]
            mask = (
                jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
            )
            s = jnp.where(mask, s, -1e30)
        lse = jax.nn.logsumexp(s, axis=-1)  # [B,H,T]
        return jnp.transpose(lse, (0, 2, 1))  # [B,T,H]

    @pytest.mark.parametrize("causal", [True, False])
    def test_out_and_lse_match_dense(self, causal):
        q, k, v = _qkv(5)
        out, lse = flash_attention_with_lse(q, k, v, causal=causal, **BLOCKS)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(dense_attention(q, k, v, causal=causal)),
            rtol=2e-5, atol=2e-5,
        )
        np.testing.assert_allclose(
            np.asarray(lse),
            np.asarray(self._dense_ref(q, k, v, causal)),
            rtol=1e-5, atol=1e-5,
        )

    def test_lse_cotangent_flows(self):
        """Gradients of a loss that CONSUMES lse must match the natively
        differentiable dense computation — this is the δ-adjustment path in
        the kernel's custom VJP."""
        q, k, v = _qkv(6)

        def loss_flash(q, k, v):
            out, lse = flash_attention_with_lse(q, k, v, causal=True, **BLOCKS)
            return (out ** 2).sum() + (lse ** 2).sum() * 0.1

        def loss_dense(q, k, v):
            out = dense_attention(q, k, v, causal=True)
            lse = self._dense_ref(q, k, v, True)
            return (out ** 2).sum() + (lse ** 2).sum() * 0.1

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            )

    def test_fallback_returns_lse_too(self):
        """Shapes the kernel can't tile still honor the (out, lse) contract
        through the dense fallback."""
        rng = np.random.RandomState(11)
        q = jnp.asarray(rng.randn(1, 100, 2, 16).astype(np.float32))
        out, lse = flash_attention_with_lse(q, q, q, causal=True)
        assert out.shape == (1, 100, 2, 16)
        assert lse.shape == (1, 100, 2)
        np.testing.assert_allclose(
            np.asarray(lse),
            np.asarray(self._dense_ref(q, q, q, True)),
            rtol=1e-5, atol=1e-5,
        )


class TestPickBlocks:
    """Block selection: the kernel must degrade block size, not fall back to
    dense, for sequence lengths the default 1024² tiles don't divide."""

    def test_divisor_fallthrough(self):
        # 1536 % 1024 != 0 → halve to 512 (1536 % 512 == 0), both axes.
        assert pick_blocks(1536, 64, jnp.bfloat16) == (512, 512)
        bq, bk = pick_blocks(1536, 64, jnp.bfloat16)
        assert supported((1, 1536, 2, 64), bq, bk, dtype=jnp.bfloat16)

    def test_full_blocks_at_long_seq(self):
        assert pick_blocks(8192, 64, jnp.bfloat16) == (1024, 1024)

    def test_clamped_to_t(self):
        assert pick_blocks(512, 64, jnp.bfloat16) == (512, 512)
        assert pick_blocks(128, 64, jnp.float32) == (128, 128)

    def test_wide_head_clamp(self):
        # D > 128 keeps the f32 score tile + wide blocks inside VMEM.
        assert pick_blocks(4096, 256, jnp.bfloat16) == (512, 512)

    def test_degradation_floor(self):
        """Awkward T (1040 = 16·65) must NOT degrade below 128 into tiny
        MXU-underfilling tiles; the non-dividing 128 makes supported()
        reject → dense fallback, which is faster there."""
        bq, bk = pick_blocks(1040, 64, jnp.bfloat16)
        assert (bq, bk) == (128, 128)
        assert not supported((1, 1040, 2, 64), bq, bk, dtype=jnp.bfloat16)
        # Explicit small blocks are honored, not degraded-to.
        assert pick_blocks(128, 64, jnp.float32, 32, 32) == (32, 32)
        # Non-power-of-two explicit blocks stop AT the floor boundary
        # instead of halving through it (384 → 192, not → 96).
        assert pick_blocks(1056, 64, jnp.float32, 384, 384) == (192, 192)

    def test_odd_t_runs_kernel_via_smaller_blocks(self):
        """T=1536 must run the pallas kernel (via 512² tiles), matching
        dense numerics — previously this shape regressed to dense."""
        # The kernel-actually-runs guard: the picked blocks must tile T
        # (dense-vs-dense would trivially pass the parity check below).
        bq, bk = pick_blocks(1536, 16, jnp.float32)
        assert supported((1, 1536, 2, 16), bq, bk, dtype=jnp.float32)
        rng = np.random.RandomState(7)
        q, k, v = (
            jnp.asarray(rng.randn(1, 1536, 2, 16).astype(np.float32))
            for _ in range(3)
        )
        out = flash_attention(q, k, v, causal=True)
        expected = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=2e-5, atol=2e-5
        )


class TestBackward:
    def test_grads_match_dense(self):
        q, k, v = _qkv(3)

        def loss_flash(q, k, v):
            return (flash_attention(q, k, v, causal=True, **BLOCKS) ** 2).sum()

        def loss_dense(q, k, v):
            return (dense_attention(q, k, v, causal=True) ** 2).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            )

    def test_noncausal_grads(self):
        q, k, v = _qkv(4)
        gf = jax.grad(
            lambda q: (flash_attention(q, k, v, causal=False, **BLOCKS) ** 2).sum()
        )(q)
        gd = jax.grad(
            lambda q: (dense_attention(q, k, v, causal=False) ** 2).sum()
        )(q)
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), rtol=1e-4, atol=1e-4
        )


def _dense_masked(q, k, v, keep):
    """Independent dense reference: explicit [B,Tq,Tk] boolean mask, exact
    zero rows where nothing is kept (the kernel's empty-row convention)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(keep[:, None, :, :], s, -1e30)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.where(keep[:, None, :, :], jnp.exp(s - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", p / jnp.where(l == 0, 1.0, l), v)
    return out


def _packed_segments(rng, b, t, max_docs=4):
    """[B, T] contiguous-run segment ids, like sequence packing produces."""
    ids = np.zeros((b, t), np.int32)
    for i in range(b):
        cuts = np.sort(rng.choice(np.arange(1, t), size=max_docs - 1, replace=False))
        ids[i] = np.searchsorted(cuts, np.arange(t), side="right")
    return jnp.asarray(ids)


class TestSegments:
    """Packed-sequence (segment-id) masking — round-3 feature. bk must be a
    multiple of 128 (lane tiling of the q-id block), so blocks are 32×128."""

    SEG_BLOCKS = dict(block_q=32, block_k=128)

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_masked_dense(self, causal):
        rng = np.random.RandomState(11)
        q, k, v = _qkv(11)
        seg = _packed_segments(rng, B, T)
        out = flash_attention(
            q, k, v, causal=causal,
            q_segment_ids=seg, kv_segment_ids=seg, **self.SEG_BLOCKS,
        )
        keep = seg[:, :, None] == seg[:, None, :]
        if causal:
            tri = jnp.tril(jnp.ones((T, T), bool))
            keep = keep & tri[None]
        expected = _dense_masked(q, k, v, keep)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
        )

    def test_grads_match_masked_dense(self):
        rng = np.random.RandomState(12)
        q, k, v = _qkv(12)
        seg = _packed_segments(rng, B, T)
        keep = (seg[:, :, None] == seg[:, None, :]) & jnp.tril(
            jnp.ones((T, T), bool)
        )[None]

        gf = jax.grad(
            lambda q, k, v: (
                flash_attention(
                    q, k, v, causal=True,
                    q_segment_ids=seg, kv_segment_ids=seg, **self.SEG_BLOCKS,
                ) ** 2
            ).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        gd = jax.grad(
            lambda q, k, v: (_dense_masked(q, k, v, keep) ** 2).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            )

    def test_with_lse_segments(self):
        """The lse entry (ring building block) honors segments too."""
        rng = np.random.RandomState(13)
        q, k, v = _qkv(13)
        seg = _packed_segments(rng, B, T)
        out, lse = flash_attention_with_lse(
            q, k, v, causal=False,
            q_segment_ids=seg, kv_segment_ids=seg, **self.SEG_BLOCKS,
        )
        keep = seg[:, :, None] == seg[:, None, :]
        expected = _dense_masked(q, k, v, keep)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
        )
        assert lse.shape == (B, T, H)
        # lse really is log-sum-exp of the kept scores.
        scale = D ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        s = jnp.where(keep[:, None, :, :], s, -jnp.inf)
        ref = jax.nn.logsumexp(s, axis=-1)  # [B,H,T]
        np.testing.assert_allclose(
            np.asarray(jnp.transpose(ref, (0, 2, 1))), np.asarray(lse),
            rtol=1e-5, atol=1e-4,
        )

    def test_empty_rows_zero_not_nan(self):
        """A q row whose segment has no kv tokens (cross-attention against a
        filtered memory): zero output, finite lse, zero grads — never NaN."""
        rng = np.random.RandomState(14)
        q, k, v = _qkv(14)
        q_seg = jnp.asarray(rng.randint(0, 2, (B, T)).astype(np.int32))
        kv_seg = jnp.zeros((B, T), jnp.int32)  # only segment 0 has keys

        def f(q, k, v):
            out = flash_attention(
                q, k, v, causal=False,
                q_segment_ids=q_seg, kv_segment_ids=kv_seg, **self.SEG_BLOCKS,
            )
            return out, (out ** 2).sum()

        out, _ = f(q, k, v)
        rows_empty = np.asarray(q_seg) == 1
        np.testing.assert_array_equal(
            np.asarray(out)[rows_empty], 0.0
        )
        grads = jax.grad(lambda *a: f(*a)[1], argnums=(0, 1, 2))(q, k, v)
        for g in grads:
            assert np.isfinite(np.asarray(g)).all()

    def test_mismatched_segment_args_rejected(self):
        q, k, v = _qkv(15)
        seg = jnp.zeros((B, T), jnp.int32)
        with pytest.raises(ValueError, match="together"):
            flash_attention(q, k, v, q_segment_ids=seg)
        with pytest.raises(ValueError, match="Tq"):
            flash_attention(
                q, k, v, q_segment_ids=seg[:, :64], kv_segment_ids=seg
            )

    def test_unaligned_block_falls_back_dense(self):
        """Segmented with bk not lane-aligned must fall back (still correct)."""
        rng = np.random.RandomState(16)
        q, k, v = _qkv(16)
        seg = _packed_segments(rng, B, T)
        assert not supported(
            q.shape, 32, 32, k_shape=q.shape, segmented=True
        )
        out = flash_attention(
            q, k, v, causal=False, block_q=32, block_k=32,
            q_segment_ids=seg, kv_segment_ids=seg,
        )
        keep = seg[:, :, None] == seg[:, None, :]
        expected = _dense_masked(q, k, v, keep)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
        )


@pytest.mark.slow
class TestCrossAttention:
    """Tk != Tq on the kernel's rectangular grid — round-3 feature."""

    def test_causal_offset_matches_dense(self):
        """Causal cross-attention aligns sequence ENDS: query i sees keys
        j <= i + Tk - Tq (the decode/suffix convention)."""
        rng = np.random.RandomState(21)
        tq, tk = 64, 192
        q = jnp.asarray(rng.randn(B, tq, H, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, tk, H, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, tk, H, D).astype(np.float32))
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        rows = np.arange(tq)[:, None] + (tk - tq)
        keep = jnp.asarray(
            np.broadcast_to(rows >= np.arange(tk)[None, :], (B, tq, tk))
        )
        expected = _dense_masked(q, k, v, keep)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
        )

    def test_cross_grads_match_dense(self):
        rng = np.random.RandomState(22)
        tq, tk = 96, 32
        q = jnp.asarray(rng.randn(B, tq, H, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, tk, H, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, tk, H, D).astype(np.float32))
        keep = jnp.ones((B, tq, tk), bool)

        gf = jax.grad(
            lambda q, k, v: (
                flash_attention(
                    q, k, v, causal=False, block_q=32, block_k=32
                ) ** 2
            ).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        gd = jax.grad(
            lambda q, k, v: (_dense_masked(q, k, v, keep) ** 2).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            )

    def test_cross_with_segments(self):
        """Cross-attention + segment filtering compose (retrieval pattern:
        each query row attends only its document's memory slice)."""
        rng = np.random.RandomState(23)
        tq, tk = 64, 128
        q = jnp.asarray(rng.randn(B, tq, H, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, tk, H, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, tk, H, D).astype(np.float32))
        q_seg = jnp.asarray(rng.randint(0, 3, (B, tq)).astype(np.int32))
        kv_seg = jnp.asarray(rng.randint(0, 3, (B, tk)).astype(np.int32))
        out = flash_attention(
            q, k, v, causal=False, block_q=32, block_k=128,
            q_segment_ids=q_seg, kv_segment_ids=kv_seg,
        )
        keep = q_seg[:, :, None] == kv_seg[:, None, :]
        expected = _dense_masked(q, k, v, keep)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
        )

    def test_causal_tk_smaller_empty_head_rows(self):
        """Tk < Tq causal: the first Tq-Tk rows see no keys at all — they
        must come out zero with finite grads (empty-row convention)."""
        rng = np.random.RandomState(24)
        tq, tk = 96, 32
        q = jnp.asarray(rng.randn(B, tq, H, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, tk, H, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, tk, H, D).astype(np.float32))
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_array_equal(np.asarray(out)[:, : tq - tk], 0.0)
        g = jax.grad(
            lambda q: (
                flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
                ** 2
            ).sum()
        )(q)
        assert np.isfinite(np.asarray(g)).all()


@pytest.mark.slow
class TestWindow:
    """Sliding-window (local) attention: the band mask row − col < window
    plus block-level skip of out-of-band tiles. Reference = dense_attention
    with the same window."""

    @pytest.mark.parametrize("window", [1, 17, 32, 100, T, 3 * T])
    def test_matches_dense(self, window):
        q, k, v = _qkv(11)
        out = flash_attention(q, k, v, causal=True, window=window, **BLOCKS)
        expected = dense_attention(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    def test_grads_match_dense(self):
        q, k, v = _qkv(12)
        window = 40  # not a block multiple: exercises partial band tiles

        def loss_flash(q, k, v):
            return (
                flash_attention(q, k, v, causal=True, window=window, **BLOCKS)
                ** 2
            ).sum()

        def loss_dense(q, k, v):
            return (
                dense_attention(q, k, v, causal=True, window=window) ** 2
            ).sum()

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            )

    def test_with_lse_and_q_offset(self):
        """The ring building block: a q block at global offset attends a
        past K/V block under the window band; (out, lse) must match the
        dense fallback's same-offset math, gradients included (the offset
        path is what window-aware ring hops run)."""
        from horovod_tpu.ops.flash_attention import _dense_with_lse

        rng = np.random.RandomState(13)
        tq = tk = 64
        q, k, v = (
            jnp.asarray(rng.randn(B, t, H, D).astype(np.float32))
            for t in (tq, tk, tk)
        )
        window, offset = 80, 64  # band straddles the block boundary
        out, lse = flash_attention_with_lse(
            q, k, v, causal=True, window=window, q_offset=offset, **BLOCKS
        )
        ref_out, ref_lse = _dense_with_lse(
            q, k, v, causal=True, window=window, q_offset=offset
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref_out), rtol=2e-5, atol=2e-5
        )
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(ref_lse), rtol=1e-5, atol=1e-5
        )

        def loss_k(fn):
            def f(q, k, v):
                o, s = fn(q, k, v)
                return (o.astype(jnp.float32) ** 2).sum() + (
                    jnp.where(s > -1e29, s, 0.0) ** 2
                ).sum()

            return jax.grad(f, argnums=(0, 1, 2))

        g1 = loss_k(
            lambda q, k, v: flash_attention_with_lse(
                q, k, v, causal=True, window=window, q_offset=offset, **BLOCKS
            )
        )(q, k, v)
        g2 = loss_k(
            lambda q, k, v: _dense_with_lse(
                q, k, v, causal=True, window=window, q_offset=offset
            )
        )(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            )

    def test_composes_with_segments(self):
        """Packed documents AND a window: attention restricted to the
        intersection (same doc, within the band)."""
        rng = np.random.RandomState(14)
        q, k, v = _qkv(14)
        ids = jnp.asarray(
            np.sort(rng.randint(0, 3, size=(B, T)), axis=1), jnp.int32
        )
        out = flash_attention(
            q, k, v, causal=True, window=24,
            q_segment_ids=ids, kv_segment_ids=ids, **BLOCKS
        )
        expected = dense_attention(
            q, k, v, causal=True, window=24,
            q_segment_ids=ids, kv_segment_ids=ids,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    def test_fallback_path_applies_window(self):
        """Tiling that can't run the kernel must still honor the window in
        the dense fallback."""
        rng = np.random.RandomState(15)
        q = jnp.asarray(rng.randn(1, 100, 2, 16).astype(np.float32))
        assert not supported(q.shape, 64, 64)
        out = flash_attention(
            q, q, q, causal=True, window=30, block_q=64, block_k=64
        )
        expected = dense_attention(q, q, q, causal=True, window=30)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
        )

    def test_window_requires_causal(self):
        q, k, v = _qkv()
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, causal=False, window=8)
        with pytest.raises(ValueError, match="positive"):
            flash_attention(q, k, v, causal=True, window=0)
        with pytest.raises(ValueError, match="causal"):
            dense_attention(q, k, v, causal=False, window=8)


class TestSinks:
    """Global+local (window + pinned sinks) through the banded grid: one
    extra sink tile per q block, disjoint masks, sink-only dK/dV pass."""

    @pytest.mark.parametrize("window,sinks", [(32, 8), (24, 24), (100, 17)])
    def test_matches_dense(self, window, sinks):
        q, k, v = _qkv(31)
        out = flash_attention(
            q, k, v, causal=True, window=window, sinks=sinks, **BLOCKS
        )
        expected = dense_attention(
            q, k, v, causal=True, window=window, sinks=sinks
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    def test_grads_match_dense(self):
        q, k, v = _qkv(32)
        window, sinks = 40, 12

        def loss(fn):
            return jax.grad(
                lambda q, k, v: (fn(q, k, v) ** 2).sum(), argnums=(0, 1, 2)
            )

        g1 = loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, sinks=sinks, **BLOCKS
        ))(q, k, v)
        g2 = loss(lambda q, k, v: dense_attention(
            q, k, v, causal=True, window=window, sinks=sinks
        ))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            )

    def test_composes_with_segments(self):
        rng = np.random.RandomState(33)
        q, k, v = _qkv(33)
        ids = jnp.asarray(
            np.sort(rng.randint(0, 3, size=(B, T)), axis=1), jnp.int32
        )
        out = flash_attention(
            q, k, v, causal=True, window=24, sinks=8,
            q_segment_ids=ids, kv_segment_ids=ids, **BLOCKS
        )
        expected = dense_attention(
            q, k, v, causal=True, window=24, sinks=8,
            q_segment_ids=ids, kv_segment_ids=ids,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    def test_sinks_without_window_is_plain_causal(self):
        q, k, v = _qkv(34)
        out = flash_attention(q, k, v, causal=True, sinks=16, **BLOCKS)
        expected = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=2e-5, atol=2e-5
        )

    def test_oversized_sinks_fall_back_dense(self):
        """sinks > block_k can't ride the single pinned tile — must still
        produce the right answer via the dense fallback."""
        q, k, v = _qkv(35)
        out = flash_attention(
            q, k, v, causal=True, window=32, sinks=100,
            block_q=32, block_k=32,
        )
        expected = dense_attention(
            q, k, v, causal=True, window=32, sinks=100
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=2e-5, atol=2e-5
        )


class TestTwoHeadSizes:
    """q and k of one head size, v (and the output) of another: latent
    attention's 192 | 128. Forward and the three gradients against an
    independent dense computation, on the kernel and on the dense
    fallback, in float32 and bfloat16."""

    DK, DV = 192, 128

    def _qkv(self, dtype, t=128, seed=11):
        rng = np.random.RandomState(seed)
        q, k = (jnp.asarray(rng.randn(2, t, 4, self.DK), dtype)
                for _ in range(2))
        return q, k, jnp.asarray(rng.randn(2, t, 4, self.DV), dtype)

    @staticmethod
    def _plain(q, k, v):
        """Causal softmax(q k^T / sqrt(Dk)) v in float32, written out."""
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        t = q.shape[1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    @pytest.mark.parametrize("dtype,tol", [
        (jnp.float32, 5e-5), (jnp.bfloat16, 6e-2)], ids=["f32", "bf16"])
    @pytest.mark.parametrize("path", ["kernel", "fallback"])
    def test_forward_and_gradients(self, dtype, tol, path):
        # T=128 at 32 x 64 tiles runs the kernel; T=100 does not tile.
        t = 128 if path == "kernel" else 100
        blocks = dict(block_q=32, block_k=64) if path == "kernel" else {}
        q, k, v = self._qkv(dtype, t)
        bq, bk = pick_blocks(t, self.DK, dtype, *(blocks.values() or (1024, 1024)))
        assert supported(q.shape, bq, bk, dtype=dtype, v_dim=self.DV) == (
            path == "kernel")

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=True, **blocks)

        out = flash(q, k, v)
        assert out.shape == (2, t, 4, self.DV) and out.dtype == dtype
        np.testing.assert_allclose(
            out.astype(jnp.float32), self._plain(q, k, v), rtol=tol, atol=tol)
        weight = jnp.asarray(
            np.random.RandomState(12).randn(*out.shape), jnp.float32)
        got = jax.grad(lambda *a: (flash(*a).astype(jnp.float32) * weight
                                   ).sum(), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: (self._plain(*a) * weight).sum(),
                        argnums=(0, 1, 2))(q, k, v)
        for a, b, like in zip(got, want, (q, k, v)):
            assert a.shape == like.shape and a.dtype == dtype
            scale = float(jnp.abs(b).max())
            np.testing.assert_allclose(
                a.astype(jnp.float32), b.astype(jnp.float32),
                rtol=tol, atol=tol * scale)

    def test_with_lse_and_window(self):
        """The other entry points take the two sizes too: the (out, lse)
        pair, and a sliding window (the banded grids)."""
        from horovod_tpu.ops.flash_attention import _dense_with_lse

        q, k, v = self._qkv(jnp.float32)
        out, lse = flash_attention_with_lse(
            q, k, v, causal=True, block_q=32, block_k=32)
        want, want_lse = _dense_with_lse(q, k, v, causal=True)
        np.testing.assert_allclose(out, want, rtol=5e-5, atol=5e-5)
        np.testing.assert_allclose(lse, want_lse, rtol=5e-5, atol=5e-5)
        out = flash_attention(q, k, v, causal=True, window=40, sinks=8,
                              block_q=32, block_k=32)
        want = dense_attention(q, k, v, causal=True, window=40, sinks=8)
        np.testing.assert_allclose(out, want, rtol=5e-5, atol=5e-5)
        grads = jax.grad(lambda v: flash_attention(
            q, k, v, causal=True, window=40, sinks=8, block_q=32,
            block_k=32).sum())(v)
        want = jax.grad(lambda v: dense_attention(
            q, k, v, causal=True, window=40, sinks=8).sum())(v)
        np.testing.assert_allclose(grads, want, rtol=1e-4, atol=1e-4)

    def test_the_wider_head_size_clamps_the_tiles(self):
        # 1 x 32 x 8,192 rows at Dk 192: 512^2, as any head wider than 128
        # (and so clear of the 1024^2 refusal at B*H*T >= 2^18 rows).
        assert pick_blocks(8192, max(self.DK, self.DV), jnp.bfloat16) == (
            512, 512)
        assert supported((1, 8192, 32, self.DK), 512, 512,
                         dtype=jnp.bfloat16, v_dim=self.DV)
        assert not supported((1, 8192, 32, 128), 512, 512,
                             dtype=jnp.bfloat16, v_dim=320)
        # one head size for all three is what it was
        assert supported((1, 8192, 32, 128), 1024, 1024, dtype=jnp.bfloat16)


def _dense_keep(tq, tk, off, window, sinks=0):
    """(band, sink) [Tq, Tk] boolean masks as the dense reference defines
    them: the causal band, and what the sinks re-admit beyond it."""
    rows = np.arange(tq)[:, None] + off
    cols = np.arange(tk)[None, :]
    band = rows >= cols
    sink = np.zeros_like(band)
    if window is not None:
        sink = band & (cols < sinks) & (cols <= rows - window)
        band = band & (cols > rows - window)
    return band, sink


def _fetches(named):
    """Grid steps (row-major, as the grid runs) at which a tensor's named
    block changes: the DMAs Pallas issues for it a (b, h)."""
    flat = named.ravel()
    return np.concatenate([[True], flat[1:] != flat[:-1]])


def _check_fetch_maps(maps, steps, n_anchor, true_block, needed,
                      pinned_step=False):
    """A sweep's input index maps against the tiles that run. Always: a
    running step names its true anchor and block, and every name is a
    block that exists (the callers bound them). Where every sweep has a
    running step: every fetch, of the anchored tensors and of the swept
    ones, brings what the NEXT running step needs and is issued at a step
    that follows a running one — a skipped step never makes the pipeline
    stream a block nothing computes on, and a sweep's first blocks arrive
    beside the last update of the sweep before it. (``pinned_step``:
    with sinks step 0 names the pinned block whether it runs or not, so
    only the names' truth is asked.)"""
    asel, bsel = maps
    i, j = np.indices((n_anchor, steps))
    anchors, blocks = np.asarray(asel(i, j)), np.asarray(bsel(i, j))
    anchors = np.broadcast_to(anchors, i.shape)
    assert (anchors[needed] == i[needed]).all()
    assert (blocks[needed] == true_block[needed]).all()
    if needed.any(axis=1).all() and not pinned_step:
        run = np.flatnonzero(needed.ravel())
        for named, true in ((anchors, i), (blocks, true_block)):
            for t in np.flatnonzero(_fetches(named)):
                nxt = run[np.searchsorted(run, t)] if t <= run[-1] else None
                if nxt is not None:
                    assert named.ravel()[t] == true.ravel()[nxt], (t, nxt)
                # ... and is issued beside an update (the step before it
                # runs), not after a run of empty steps.
                assert t == 0 or needed.ravel()[t - 1], t
    return anchors, blocks


class TestTileClasses:
    """The scalar classifier against the dense mask cut into tiles. A tile
    wrongly called full lets a query see the future; one wrongly skipped
    drops keys."""

    @pytest.mark.parametrize("bq,bk", [(4, 4), (8, 4), (4, 8)])
    @pytest.mark.parametrize(
        "nq,nk,q_offset",
        [(4, 4, None), (3, 5, None), (5, 3, None),
         (4, 4, "tk"), (4, 4, "-tk"), (4, 4, 5), (4, 4, 6), (4, 4, 7),
         (3, 4, -5)],
        # The hops' offsets cover every residue modulo the tiles: an
        # off-by-one in a predicate shows only where an edge falls.
        ids=["self", "tk>tq", "tk<tq", "hop-all-visible", "hop-all-future",
             "hop+5", "hop+6", "hop+7", "hop-5"],
    )
    @pytest.mark.parametrize("window", [None, 3, 6, 13, 1000])
    def test_classes_agree_with_the_dense_mask(self, bq, bk, nq, nk,
                                               q_offset, window):
        tq, tk = nq * bq, nk * bk
        q_offset = {"tk": tk, "-tk": -tk}.get(q_offset, q_offset)
        off = tk - tq if q_offset is None else q_offset
        for sinks in (0, 3) if window and q_offset is None else (0,):
            band, sink = _dense_keep(tq, tk, off, window, sinks)
            steps = fa._k_sweep_steps(bq, bk, window, sinks, nk)
            iq, jj = np.indices((nq, steps))
            ik, is_sink, needed, full = fa._k_sweep_tile(
                iq, jj, True, bq, bk, off, window, sinks, nk)
            is_sink = np.zeros_like(needed) if is_sink is None else is_sink
            seen = np.zeros((tq, tk), int)
            for i, j in zip(iq.ravel(), jj.ravel()):
                if ik[i, j] > nk - 1:
                    assert not needed[i, j]  # a clipped duplicate
                    continue
                dense = (sink if is_sink[i, j] else band)[
                    i * bq:(i + 1) * bq, ik[i, j] * bk:(ik[i, j] + 1) * bk]
                if full[i, j]:
                    assert dense.all(), (i, j)
                if not needed[i, j]:
                    assert not dense.any(), (i, j)
                else:
                    seen[i * bq:(i + 1) * bq,
                         ik[i, j] * bk:(ik[i, j] + 1) * bk] += dense
            # The steps that run cover every visible pair exactly once.
            np.testing.assert_array_equal(seen, (band | sink).astype(int))
            census = tile_census(
                tq, tk, bq, bk, causal=True, window=window, sinks=sinks,
                q_offset=q_offset)
            assert census == (
                (~needed).sum(), (needed & ~full).sum(),
                (needed & full).sum())
            assert sum(census) == nq * steps
            # The forward / dQ sweep's inputs.
            _, named = _check_fetch_maps(
                fa._k_sweep_maps(True, bq, bk, off, window, sinks, nq, nk),
                steps, nq, ik, needed, pinned_step=bool(sinks))
            assert ((named >= 0) & (named <= nk - 1)).all()
            assert (named[is_sink.astype(bool)] == 0).all()
            if sinks:
                continue
            # The dK/dV sweep: k block anchored, q blocks swept.
            qsteps, *maps = fa._q_sweep_maps(
                True, bq, bk, off, window, nq, nk)
            kk, jq = np.indices((nk, qsteps))
            tq_block = jq + (
                fa._band_lo_q(kk, bq, bk, off) if window is not None else 0)
            q_needed, _ = fa._tile_class(tq_block, kk, bq, bk, off, window)
            q_needed &= tq_block <= nq - 1
            anchors, named = _check_fetch_maps(
                maps, qsteps, nk, tq_block, q_needed)
            assert ((named >= 0) & (named <= nq - 1)).all()
            assert ((anchors >= 0) & (anchors <= nk - 1)).all()
            # Both grids run the same tiles.
            assert q_needed.sum() == needed.sum()

    @pytest.mark.parametrize(
        "call,expected",
        [
            # The three cells' calls (chipbench/configs) at the tiles
            # `pick_blocks` gives them: skipped / edge / full a (b, h).
            (dict(t=8192, d=192, window=None), (120, 16, 120)),
            (dict(t=4096, d=128, window=4096), (28, 8, 28)),
            (dict(t=2048, d=128, window=None), (1, 2, 1)),
        ],
        ids=["kanana-2-30b-a3b", "starcoder2-3b", "cerebras-gpt-1.3b"],
    )
    def test_census_of_the_cells_calls(self, call, expected):
        t, window = call["t"], call["window"]
        bq, bk = pick_blocks(
            t, call["d"], jnp.bfloat16, windowed=window is not None)
        assert tile_census(
            t, t, bq, bk, causal=True, window=window) == expected

    @pytest.mark.parametrize(
        "call,running,fetched",
        [
            (dict(t=8192, d=192, window=None), 136, 135),
            (dict(t=4096, d=128, window=4096), 36, 35),
            (dict(t=2048, d=128, window=None), 3, 2),
        ],
        ids=["kanana-2-30b-a3b", "starcoder2-3b", "cerebras-gpt-1.3b"],
    )
    def test_skipped_steps_of_the_cells_calls_fetch_nothing(
            self, call, running, fetched):
        """K blocks a head's forward sweep streams: one for every step of
        the grid before the maps followed the tiles' classes (256 / 64 / 4),
        now at most one for every step that runs."""
        t, window = call["t"], call["window"]
        bq, bk = pick_blocks(
            t, call["d"], jnp.bfloat16, windowed=window is not None)
        nq = nk = t // bq
        steps = fa._k_sweep_steps(bq, bk, window, 0, nk)
        iq, jj = np.indices((nq, steps))
        ik, _, needed, _ = fa._k_sweep_tile(
            iq, jj, True, bq, bk, 0, window, 0, nk)
        _, blocks = _check_fetch_maps(
            fa._k_sweep_maps(True, bq, bk, 0, window, 0, nq, nk),
            steps, nq, ik, needed)
        assert needed.sum() == running
        assert _fetches(blocks).sum() == fetched
        assert _fetches(ik).sum() == nq * steps

    def test_census_without_a_provable_class(self):
        # No mask at all: every tile is full. Segment ids: nothing is.
        assert tile_census(64, 96, 16, 32, causal=False) == (0, 0, 12)
        assert tile_census(
            64, 96, 16, 32, causal=False, segmented=True) == (0, 12, 0)
        assert tile_census(
            64, 64, 16, 16, causal=True, segmented=True) == (6, 10, 0)

    def test_gauge_reads_the_last_traced_grid(self):
        from horovod_tpu import obs
        from horovod_tpu.obs import core as obs_core
        from horovod_tpu.obs import prom

        assert obs_core.spec("hvt_flash_tiles").labels == ("kind",)
        obs_core.reset()
        q, k, v = _qkv(40)
        jax.eval_shape(
            lambda q, k, v: flash_attention(q, k, v, causal=True, **BLOCKS),
            q, k, v)
        values = prom.parse_text(prom.render(obs.default_registry()))
        assert [
            values[f'hvt_flash_tiles{{kind="{kind}"}}']
            for kind in ("skipped", "edge", "full")
        ] == [6.0, 4.0, 6.0]
        obs_core.reset()


class TestEveryTileClass:
    """Forward and the three gradients through skipped, full and edge tiles
    of grids of at least 3 × 3 (interpreter, float32), against the dense
    reference."""

    T3, TILE = 96, 32

    @staticmethod
    def _rand(shape, seed):
        return jnp.asarray(
            np.random.RandomState(seed).randn(*shape).astype(np.float32))

    CASES = [
        dict(),
        # The diagonal and the band's lower edge in ONE tile: no tile of
        # a window narrower than bq + bk − 1 is full.
        dict(window=20, no_full=True),
        dict(window=48, no_full=True),
        # The lower edge crosses a tile the diagonal does not, (2, 0),
        # beside full ones, (1, 0) and (2, 1).
        dict(window=70),
        dict(window=96),
        dict(window=1000),
        dict(tk=160),
        dict(tk=160, window=70),
        # A ring hop's alignment: the rows sit at key positions r + 10,
        # not at the sequences' ends (r + 64).
        dict(tk=160, q_offset=10),
        dict(tk=160, q_offset=10, window=80),
        dict(dk=48, dv=32),
        dict(window=40, sinks=8, no_full=True),
        dict(window=70, sinks=8),
        dict(segments=True, no_full=True),
        dict(segments=True, window=200, no_full=True),
    ]
    IDS = ["causal", "window<tile", "window-2-tiles", "window-between",
           "window=T", "window>T", "tk>tq", "tk>tq-window", "q_offset",
           "q_offset-window", "dk!=dv", "sinks", "sinks-full-tiles",
           "segments", "segments-window"]

    def _call(self, case):
        """(q, k, v, tile, flash kwargs) of a case, its census checked: a
        grid of 3 × 3 tiles at least, with skipped and edge tiles, and full
        ones where the case can have them."""
        segments = case.get("segments", False)
        # Segment ids need lane-aligned k blocks: 128² tiles there.
        tile = 128 if segments else self.TILE
        tq = 3 * tile
        tk = case.get("tk", tq)
        dk, dv = case.get("dk", 16), case.get("dv", case.get("dk", 16))
        q = self._rand((1, tq, 2, dk), 50)
        k = self._rand((1, tk, 2, dk), 51)
        v = self._rand((1, tk, 2, dv), 52)
        kwargs = dict(
            causal=True, window=case.get("window"),
            q_offset=case.get("q_offset"), sinks=case.get("sinks", 0),
        )
        if segments:
            seg = _packed_segments(np.random.RandomState(53), 1, tq, 3)
            kwargs.update(q_segment_ids=seg, kv_segment_ids=seg)
        census = tile_census(
            tq, tk, tile, tile, causal=True, window=kwargs["window"],
            sinks=kwargs["sinks"], q_offset=kwargs["q_offset"],
            segmented=segments)
        assert census[0] and census[1], census
        assert bool(census[2]) != case.get("no_full", False), census
        return q, k, v, tile, kwargs

    @pytest.mark.parametrize("case", CASES, ids=IDS)
    def test_forward_and_gradients(self, case):
        q, k, v, tile, kwargs = self._call(case)

        def flash(q, k, v):
            return flash_attention(
                q, k, v, block_q=tile, block_k=tile, **kwargs)

        def dense(q, k, v):
            return _dense_with_lse(q, k, v, **kwargs)[0]

        np.testing.assert_allclose(
            np.asarray(flash(q, k, v)), np.asarray(dense(q, k, v)),
            rtol=2e-5, atol=2e-5)
        grads = [
            jax.grad(lambda q, k, v: (fn(q, k, v) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v)
            for fn in (flash, dense)
        ]
        for a, b in zip(*grads):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("lse_cotangent", [False, True],
                             ids=["out", "out+lse"])
    @pytest.mark.parametrize(
        "case", [c for c in CASES if "sinks" not in c],
        ids=[i for i, c in zip(IDS, CASES) if "sinks" not in c])
    def test_fused_backward_equals_the_two_kernels_to_the_bit(
            self, case, lse_cotangent):
        """dq, dk and dv of the one-kernel backward against the two-kernel
        form (the oracle) on the same residuals and cotangents: the same
        products, and a q block's sum over the k blocks in the same order.
        Segment ids go the fused way like any call without sinks."""
        q, k, v, tile, kwargs = self._call(case)
        static = (True, kwargs["window"], 0, kwargs["q_offset"], tile, tile,
                  True)
        out, res = fa._flash_fwd(
            q, k, v, kwargs.get("q_segment_ids"),
            kwargs.get("kv_segment_ids"), *static)
        g = self._rand(out.shape, 54)
        g_lse = self._rand(out.shape[:3], 55) if lse_cotangent else None
        assert fa.fused_backward(q.shape[1], q.shape[3], q.dtype)
        fused = fa._flash_bwd_impl(True, *static, res, g, g_lse)
        split = fa._flash_bwd_impl(False, *static, res, g, g_lse)
        for a, b in zip(fused[:3], split[:3]):
            assert np.abs(np.asarray(b)).max() > 0
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("window", [None, 10_000])
    def test_all_full_call_equals_the_unmasked_one_to_the_bit(self, window):
        """A ring hop whose keys all lie behind every query (q_offset ≥ Tk)
        has only full tiles: nothing of a mask is computed, and the output
        and the three gradients are the unmasked call's to the bit."""
        t = self.T3
        q = self._rand((1, t, 2, 16), 60)
        k = self._rand((1, t, 2, 16), 61)
        v = self._rand((1, t, 2, 16), 62)
        assert tile_census(
            t, t, self.TILE, self.TILE, causal=True, window=window,
            q_offset=t + 5) == (0, 0, 9)

        def run(**kwargs):
            def loss(q, k, v):
                out, lse = flash_attention_with_lse(
                    q, k, v, block_q=self.TILE, block_k=self.TILE, **kwargs)
                return (out ** 2).sum() + lse.sum(), out
            return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
                q, k, v)

        (_, out), grads = run(causal=True, window=window, q_offset=t + 5)
        (_, plain), plain_grads = run(causal=False)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))
        for a, b in zip(grads, plain_grads):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestFusedBackward:
    """Which form the backward takes: `fused_backward`, from the shapes and
    the chip's VMEM alone (off TPU a v5e's 128 MiB)."""

    @pytest.mark.parametrize(
        "t_q,d,dtype,sinks,fused",
        [
            # The cells' calls; Kanana's is the largest: 16 MiB resident.
            (8192, 192, jnp.bfloat16, 0, True),
            (4096, 128, jnp.bfloat16, 0, True),
            (2048, 128, jnp.bfloat16, 0, True),
            # Both sides of half the VMEM: 8 bytes a padded lane and row
            # in bf16 (float32 accumulator, two output buffers), 12 in f32.
            (65536, 128, jnp.bfloat16, 0, True),
            (65536, 192, jnp.bfloat16, 0, False),
            (131072, 128, jnp.bfloat16, 0, False),
            (32768, 128, jnp.float32, 0, True),
            (65536, 128, jnp.float32, 0, False),
            # The dQ sweep holds the sink tile; the k-anchored one does not.
            (2048, 128, jnp.bfloat16, 64, False),
        ],
    )
    def test_predicate_on_both_sides_of_its_budget(self, t_q, d, dtype,
                                                   sinks, fused):
        assert fa.fused_backward(t_q, d, dtype, sinks=sinks) is fused
        assert fa._resident_dq_bytes(8192, 192, jnp.bfloat16) == 2 ** 24

    @staticmethod
    def _trace(**kwargs):
        """(flash kernel names in a lowered forward + backward, the gauge
        `hvt_flash_backward` by impl) of one toy call."""
        import re

        from horovod_tpu.obs import core as obs_core
        from horovod_tpu.obs import prom

        obs_core.reset()
        q = jnp.ones((1, 96, 2, 16), jnp.float32)

        def loss(q, k, v):
            return flash_attention(
                q, k, v, causal=True, block_q=32, block_k=32, **kwargs).sum()

        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, q, q).as_text(debug_info=True)
        names = {
            kernel for loc in re.findall(r'loc\("([^"]+)"', text)
            for kernel in re.findall(r"hvt_flash_[a-z]+", loc)}
        values = prom.parse_text(prom.render())
        gauge = {impl: values[f'hvt_flash_backward{{impl="{impl}"}}']
                 for impl in ("fused", "split")}
        obs_core.reset()
        return names, gauge

    def test_a_call_inside_the_budget_is_one_kernel(self):
        from horovod_tpu.obs import core as obs_core

        assert obs_core.spec("hvt_flash_backward").labels == ("impl",)
        names, gauge = self._trace()
        assert names == {fa.KERNEL_FWD, fa.KERNEL_BWD}
        assert gauge == {"fused": 1.0, "split": 0.0}

    def test_a_shape_past_the_budget_takes_the_two_kernels(self, monkeypatch):
        # A chip whose VMEM the toy call's resident dQ (96 rows × 128 lanes
        # × 12 bytes) does not fit twice.
        monkeypatch.setattr(
            fa, "_chip_vmem_bytes", lambda: 2 * 96 * 128 * 12 - 2)
        names, gauge = self._trace()
        assert names == {fa.KERNEL_FWD, fa.KERNEL_DQ, fa.KERNEL_DKV}
        assert gauge == {"fused": 0.0, "split": 1.0}

    def test_sinks_take_the_two_kernels(self):
        names, gauge = self._trace(window=40, sinks=8)
        assert names == {fa.KERNEL_FWD, fa.KERNEL_DQ, fa.KERNEL_DKV}
        assert gauge == {"fused": 0.0, "split": 1.0}
