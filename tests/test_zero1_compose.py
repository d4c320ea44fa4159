"""ZeRO-1 x accumulation x compression x overlap — the composed path
(ISSUE 10 acceptance):

* The trajectory-equivalence MATRIX: ``shard_update=True`` x K in {1, 4}
  x compression in {none, int8} x overlap on/off must equal the dense
  (replicated-update) control at rel 1e-4 on params AND optimizer state.
  The bar is reachable because the composition is arithmetic-preserving
  by construction: non-quantized wires reduce-scatter the very sums the
  control psums (reassociation only), and quantized wires keep the DENSE
  bucket layout through the two-shot wire — bitwise the control's
  reduction — and slice locally (re-cutting buckets to the zero1 layout
  would change the per-bucket scales, i.e. the numerics).
* The compiled structure: the composed step's gradient traffic is
  scatter-form ONLY — reduce-scatters (plus the quantized wire's
  payload all-to-all), never a full-payload all-reduce — and the
  overlap peel still empties the accumulation scan.
* `collectives.flatten_scatter_buckets` really inverts into the
  per-shard zero1 leaf slices `training/build.py` defines.
* `collectives.quantized_group_sum` is now the two-shot reduce-scatter +
  all-gather: equivalent to the PR 7 one-shot gather-sum within one
  re-quantization quantum, at ~2x payload receive bytes instead of
  group_size x.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvt
from horovod_tpu import checkpoint
from horovod_tpu.analysis import hlo_audit
from horovod_tpu.analysis.step_probe import lowered_step_text
from horovod_tpu.parallel import collectives, mesh as mesh_lib
from horovod_tpu.training.optimizer import (
    ErrorFeedbackState,
    compression_error_feedback,
)


class Probe(nn.Module):
    # Dense(32) shards at dp=8 (64, 32 both divide); the Dense(10) bias
    # does NOT divide — deliberately, so the tail-bucket path (pad +
    # reduce-scatter + all-gather, replicated mirror) is always exercised.
    @nn.compact
    def __call__(self, x, *, train: bool = False):
        x = x.reshape((x.shape[0], -1)).astype(jnp.float32)
        return nn.Dense(10)(nn.relu(nn.Dense(32)(x)))


def _data(n=128, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 8, 8, 1).astype(np.float32)
    y = rng.randint(0, 10, n).astype(np.int32)
    return x, y


def _trainer(k=1, compression="none", zero1=False, overlap=None,
             bucket_bytes=None, seed=3, compression_ici="none"):
    tx = hvt.DistributedOptimizer(
        optax.adam(1e-3), backward_passes_per_step=k,
        average_aggregated_gradients=True, compression=compression,
        compression_ici=compression_ici,
    )
    return hvt.Trainer(
        Probe(), tx, seed=seed, shard_update=zero1,
        overlap_reduction=overlap, bucket_bytes=bucket_bytes,
    )


def _fit(tr, k, steps=3):
    x, y = _data()
    tr.fit(x=x, y=y, batch_size=max(1, 8 // k), epochs=1,
           steps_per_epoch=steps, shuffle_buffer=1, verbose=0)
    return tr


def _assert_state_close(a, b, rtol=1e-4, atol=1e-6, flipped_ties=0.0):
    """Params and optimizer state equal at (rtol, atol). ``flipped_ties``
    is the share of a leaf's elements (at least one when non-zero) that
    may fall outside it — for quantized wires only, see the caller."""
    for la, lb in zip(
        jax.tree.leaves(jax.device_get((a.state.params, a.state.opt_state))),
        jax.tree.leaves(jax.device_get((b.state.params, b.state.opt_state))),
    ):
        la, lb = np.asarray(la), np.asarray(lb)
        if not flipped_ties:
            np.testing.assert_allclose(la, lb, rtol=rtol, atol=atol)
            continue
        off = ~np.isclose(la, lb, rtol=rtol, atol=atol)
        assert off.sum() <= max(1, flipped_ties * la.size), (
            f"{off.sum()} of {la.size} elements differ — more than "
            "rounding ties can explain"
        )


class TestComposedTrajectoryMatrix:
    """THE acceptance matrix: every composed configuration equals its
    dense control at rel 1e-4 on params and optimizer state."""

    @pytest.mark.parametrize("k", [1, 4])
    @pytest.mark.parametrize(
        "compression,ici",
        [("none", "none"), ("int8", "none"), ("int8", "int8")],
    )
    def test_composed_equals_dense_control(self, k, compression, ici,
                                           monkeypatch):
        """The PR 10 matrix extended with the ICI-hop wire: int8+ici
        runs BOTH hops quantized under a faked 2-slice factoring
        (HVT_DCN_FACTOR=2) and must still equal the dense control at the
        same config — the scatter path keeps the dense bucket layout
        for quantized DCN wires (bitwise the replicated reduction) and
        slices locally."""
        if ici != "none":
            monkeypatch.setenv("HVT_DCN_FACTOR", "2")
        # int8 wire: a gradient value that lands exactly between two int8
        # levels rounds whichever way the compiled program's arithmetic
        # leaves it, and XLA need not fuse the scale multiply the same way
        # in the scattered and the dense program (the installed XLA flips
        # one such tie in the first reduction). A flipped tie moves ONE
        # element by one int8 step — the error-feedback residual by exactly
        # that step, from +q/2 to -q/2 — and the parameters it nudges flip
        # a few more near-ties in the steps after (0.8% of the fullest
        # residual leaf after three). A logic fault (layout, scale, bucket
        # order) moves every element. So the quantized cases allow a leaf
        # 5% of its elements off; the uncompressed cases stay exact to the
        # default tolerance, every element.
        tol = {"flipped_ties": 0.05} if compression == "int8" else {}
        dense = _fit(_trainer(k, compression, compression_ici=ici), k)
        for overlap in (True, False):
            z = _fit(_trainer(k, compression, zero1=True,
                              overlap=overlap, compression_ici=ici), k)
            _assert_state_close(z, dense, **tol)
            # And it really trained sharded: some opt-state mirror
            # carries the data axis (dp=8 divides every Probe leaf's
            # dim 0 except the Dense(10) bias).
            specs = {
                str(l.sharding.spec)
                for l in jax.tree.leaves(z.state.opt_state)
                if hasattr(l, "sharding") and getattr(l, "ndim", 0) > 0
            }
            assert any("data" in s for s in specs), specs

    def test_quantized_ici_on_scatter_layout_tracks_exact(self,
                                                          monkeypatch):
        """compression_ici alone (no DCN wire) keeps the SCATTER layout
        — the quantized wire rides `_scatter_reduce_bucket`'s ICI hop
        with error feedback — and the trained params track the exact
        (uncompressed) zero1 run closely (EF telescopes the per-hop
        quantization error)."""
        monkeypatch.setenv("HVT_DCN_FACTOR", "2")
        exact = _fit(_trainer(4, zero1=True), 4)
        q = _fit(_trainer(4, zero1=True, compression_ici="int8"), 4)
        for a, b in zip(
            jax.tree.leaves(jax.device_get(exact.state.params)),
            jax.tree.leaves(jax.device_get(q.state.params)),
        ):
            np.testing.assert_allclose(a, b, rtol=0.05, atol=5e-3)
        # The EF residual exists and lives in opt_state.
        assert isinstance(q.state.opt_state, ErrorFeedbackState)

    def test_fail_fasts_are_lifted(self):
        """The three former composition fail-fasts construct and build:
        shard_update with accumulation, with wire compression, and with
        the overlap peel (which needs the other two)."""
        x, _ = _data(16)
        for tr in (
            _trainer(4, zero1=True),
            _trainer(1, "bf16", zero1=True),
            _trainer(2, "int8", zero1=True, overlap=True),
        ):
            tr.build(x[:8])

    def test_param_specs_still_rejected(self):
        """The TP/FSDP layout family stays out of scope: shard_update
        composes with accumulation/compression/overlap, not with
        param_specs (the documented fsdp-axis route)."""
        from horovod_tpu.models.transformer import param_specs

        with pytest.raises(ValueError, match="fsdp"):
            hvt.Trainer(
                Probe(),
                hvt.DistributedOptimizer(optax.adam(1e-3)),
                shard_update=True, param_specs=param_specs,
            )


class TestComposedCompiledStructure:
    """Scatter-form gradient traffic only — the `hvt-audit` invariants,
    asserted against the real lowered step."""

    def test_k4_step_is_scatter_only(self):
        x, y = _data()
        tr = _trainer(4, zero1=True)
        # dp=8: {k1, b1, k2} scatter pieces AND the padded b2 tail piece
        # share ONE bucket at the default fusion threshold -> exactly one
        # reduce-scatter, zero full-payload all-reduces; the tail's full
        # value comes back through a small rank-1 all-gather of just its
        # columns (outside every reduction count by design).
        text = lowered_step_text(tr, x, y, 4)
        hlo_audit.assert_program(text, "scatters=1")
        tail_gathers = [
            op for op in hlo_audit.collective_ops(text)
            if op.kind == "all-gather" and op.rank == 1
        ]
        assert len(tail_gathers) == 1, tail_gathers
        # b2 is (10,), padded to 2 columns x 8 shards = 16 elements.
        assert tail_gathers[0].shape == (16,), tail_gathers

    def test_int8_step_is_one_bucketed_scatter_group(self):
        """The canonical acceptance audit: K=4 + shard_update + int8
        compiles to exactly ONE bucketed scatter-form reduction per
        optimizer step (the dense-layout payload all-to-all), wire dtype
        i8, no full-payload all-reduce."""
        x, y = _data()
        tr = _trainer(4, "int8", zero1=True)
        hlo_audit.assert_program(
            lowered_step_text(tr, x, y, 4), "scatters=1,wire=int8"
        )

    def test_bf16_wire_rides_the_reduce_scatter(self):
        x, y = _data()
        tr = _trainer(4, "bf16", zero1=True)
        text = lowered_step_text(tr, x, y, 4)
        hlo_audit.assert_program(text, "scatter-reduction,wire=bf16")
        rs = [
            op for op in hlo_audit.collective_ops(text)
            if op.kind == "reduce-scatter"
        ]
        assert rs and all(op.dtype == "bf16" for op in rs), rs

    def test_overlap_peel_survives_composition(self):
        """Strictly fewer loop ops with the peel on — the PR 7 witness,
        now on the ZeRO-1 composed step."""
        x, y = _data()
        whiles_on = hlo_audit.while_count(lowered_step_text(
            _trainer(2, zero1=True, overlap=True), x, y, 2
        ))
        whiles_off = hlo_audit.while_count(lowered_step_text(
            _trainer(2, zero1=True, overlap=False), x, y, 2
        ))
        assert whiles_on < whiles_off

    def test_implicit_zero1_path_untouched(self):
        """K=1 + no compression + shard_update keeps the implicit SPMD
        step: no explicit collective in the lowered text (XLA places the
        reduce-scatter at partitioning time, as before this PR)."""
        x, y = _data()
        tr = _trainer(1, zero1=True)
        hlo_audit.assert_program(
            lowered_step_text(tr, x, y, 1), "no-collectives"
        )


class TestScatterBuckets:
    """`flatten_scatter_buckets` really is the zero1 layout, bucketed."""

    def _tree(self):
        rng = np.random.RandomState(0)
        return {
            "k1": rng.randn(64, 32).astype(np.float32),
            "b1": rng.randn(32).astype(np.float32),
            "k2": rng.randn(32, 10).astype(np.float32),
            "b2": rng.randn(10).astype(np.float32),
        }

    @pytest.mark.parametrize("reverse", [False, True])
    @pytest.mark.parametrize("bucket_bytes", [1 << 20, 512])
    def test_round_trips_into_per_shard_zero1_slices(
        self, reverse, bucket_bytes
    ):
        dp = 8
        tree = self._tree()
        buckets, spec = collectives.flatten_scatter_buckets(
            tree, dp, bucket_bytes, reverse=reverse
        )
        fams = collectives.bucket_families(spec)
        spans = collectives.bucket_tail_spans(spec)
        assert len(fams) == len(buckets) == len(spans)
        for s in range(dp):
            entries = []
            for b, sp in zip(buckets, spans):
                m = np.asarray(b).reshape(dp, -1)
                if sp:
                    tails = np.concatenate(
                        [m[:, c: c + w] for c, w in sp], axis=1
                    )
                    entries.append((m[s], tails.ravel()))
                else:
                    entries.append(m[s])
            got = collectives.unflatten_scatter_buckets(entries, spec)
            for name, leaf in tree.items():
                sd = collectives.zero1_shard_dim(leaf.shape, dp)
                if sd is None:
                    np.testing.assert_array_equal(
                        np.asarray(got[name]), leaf
                    )
                else:
                    blk = leaf.shape[sd] // dp
                    want = np.take(
                        leaf, range(s * blk, (s + 1) * blk), axis=sd
                    )
                    np.testing.assert_array_equal(
                        np.asarray(got[name]), want
                    )

    @pytest.mark.parametrize("bucket_bytes", [1 << 20, 512])
    def test_full_buckets_round_trip(self, bucket_bytes):
        """`unflatten_scatter_full` (the error-feedback residual path)
        is the exact inverse from un-scattered buckets."""
        tree = self._tree()
        buckets, spec = collectives.flatten_scatter_buckets(
            tree, 8, bucket_bytes
        )
        got = collectives.unflatten_scatter_full(buckets, spec)
        for name, leaf in tree.items():
            np.testing.assert_array_equal(np.asarray(got[name]), leaf)

    def test_every_bucket_is_a_world_multiple(self):
        buckets, _ = collectives.flatten_scatter_buckets(
            self._tree(), 8, 512
        )
        assert all(b.size % 8 == 0 for b in buckets)

    def test_buckets_are_leaf_aligned(self):
        """The per-bucket schedulability contract: every bucket's spec
        names exactly the leaf pieces it was assembled from (no bucket
        references the whole-tree concat), cut points at exact
        bucket_bytes column multiples."""
        dp = 8
        buckets, spec = collectives.flatten_scatter_buckets(
            self._tree(), dp, 512
        )
        per = 512 // (dp * 4)  # columns per bucket (f32)
        descs = spec[5]
        assert len(descs) == len(buckets)
        for b, pieces in zip(buckets, descs):
            assert sum(w for _i, w in pieces) == b.size // dp
            assert b.size // dp <= per
        # Every leaf's pieces, concatenated across buckets, cover it once.
        shapes = spec[1]
        covered = {i: 0 for i in range(len(shapes))}
        for pieces in descs:
            for i, w in pieces:
                covered[i] += w
        for i, shape in enumerate(shapes):
            n = int(np.prod(shape))
            assert covered[i] == -(-n // dp), (i, shape, covered[i])

    def test_families_split_by_divisibility(self):
        # At the default threshold everything packs into ONE bucket:
        # b2 (10,) cannot shard at dp=8, so the bucket is mixed.
        _, spec = collectives.flatten_scatter_buckets(self._tree(), 8)
        assert collectives.bucket_families(spec) == ["mixed"]
        assert collectives.bucket_tail_spans(spec)[0]  # b2's columns
        # ...but at dp=2 every leaf divides: pure scatter, no tail spans.
        _, spec2 = collectives.flatten_scatter_buckets(self._tree(), 2)
        assert collectives.bucket_families(spec2) == ["scatter"]
        assert collectives.bucket_tail_spans(spec2) == [()]

    def test_shared_rule_with_build(self):
        """zero1_partition_spec is the layout build_state installs —
        assert against a really-built trainer."""
        x, _ = _data(16)
        tr = _trainer(4, zero1=True)
        tr.build(x[:8])
        dp = tr.mesh.shape[mesh_lib.DATA_AXIS]
        mu = tr.state.opt_state[0].mu  # Adam's param-shaped mirror
        for leaf, p in zip(
            jax.tree.leaves(mu), jax.tree.leaves(tr.state.params)
        ):
            want = collectives.zero1_partition_spec(p.shape, dp)
            assert leaf.sharding.spec == want, (p.shape, leaf.sharding)

    @pytest.mark.parametrize("dcn", [2, 4, 8])
    def test_hierarchical_scatter_matches_flat(self, dcn):
        """The two-hop scatter (ICI psum_scatter full precision, DCN
        psum_scatter on the wire) equals the flat scatter for every
        dcn factoring of the 8-way axis — the target-inner-major
        arrangement really lands each shard its own zero1 row."""
        hvt.init()
        mesh = mesh_lib.data_parallel_mesh()
        dp = mesh.shape["data"]
        P = jax.sharding.PartitionSpec
        tree = self._tree()
        outspec = {
            k: (P() if collectives.zero1_shard_dim(v.shape, dp) is None
                else collectives.zero1_partition_spec(v.shape, dp))
            for k, v in tree.items()
        }

        def mk(d, wire=None):
            def red(g):
                return collectives.reduce_gradients(
                    g, data_axis="data", extra_axes=("fsdp",), dcn=d,
                    wire_dtype=wire, bucket_bytes=1 << 20, scatter=dp,
                )

            return jax.jit(jax.shard_map(
                red, mesh=mesh, in_specs=(P(),), out_specs=outspec,
                check_vma=False,
            ))

        flat = jax.device_get(mk(1)(tree))
        hier = jax.device_get(mk(dcn)(tree))
        for k in tree:
            np.testing.assert_allclose(
                np.asarray(hier[k]), np.asarray(flat[k]), rtol=1e-6
            )
        # A 16-bit wire rides the DCN hop only: per bucket, one f32
        # (ICI) and one bf16 (DCN) reduce-scatter.
        text = mk(dcn, jnp.bfloat16).lower(tree).as_text()
        rs = [
            op.dtype for op in hlo_audit.collective_ops(text)
            if op.kind == "reduce-scatter"
        ]
        if dcn < dp:  # dcn == dp has no non-trivial ICI hop
            assert sorted(set(rs)) == ["bf16", "f32"], rs
        else:
            assert set(rs) == {"bf16"}, rs

    def test_mismatched_bucket_list_is_loud(self):
        buckets, spec = collectives.flatten_scatter_buckets(
            self._tree(), 8
        )
        with pytest.raises(ValueError, match="do not match"):
            collectives.unflatten_scatter_buckets(buckets[:-1], spec)


class TestIciWire:
    """compression_ici — the ICI-hop wire of the two-hop factoring
    (ISSUE 12): quantized reduce-scatter on hop 1 of the scatter path,
    per-hop error-feedback charging, structural dtype witnesses."""

    def _tree(self):
        rng = np.random.RandomState(0)
        return {
            "k1": rng.randn(64, 32).astype(np.float32),
            "b2": rng.randn(10).astype(np.float32),
        }

    def _shard_map(self, fn, in_specs, out_specs):
        hvt.init()
        mesh = mesh_lib.data_parallel_mesh()
        return mesh, jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        ))

    def test_quantized_ici_scatter_matches_flat_within_quantum(self):
        hvt.init()
        mesh = mesh_lib.data_parallel_mesh()
        dp = mesh.shape["data"]
        P = jax.sharding.PartitionSpec
        tree = self._tree()
        outspec = {
            k: (P() if collectives.zero1_shard_dim(v.shape, dp) is None
                else collectives.zero1_partition_spec(v.shape, dp))
            for k, v in tree.items()
        }

        def mk(d, ici=None):
            def red(g):
                return collectives.reduce_gradients(
                    g, data_axis="data", extra_axes=("fsdp",), dcn=d,
                    ici_wire_dtype=ici, scatter=dp,
                )

            return jax.jit(jax.shard_map(
                red, mesh=mesh, in_specs=(P(),), out_specs=outspec,
                check_vma=False,
            ))

        flat = jax.device_get(mk(1)(tree))
        quant = jax.device_get(mk(2, jnp.int8)(tree))
        for k in tree:
            a, b = np.asarray(quant[k]), np.asarray(flat[k])
            denom = np.abs(b).max() + 1e-6
            assert np.abs(a - b).max() / denom < 0.02, k
        # Structural: hop 1 is the quantized reduce-scatter (an i8
        # all-to-all + scale gather), hop 2 a plain f32 psum_scatter —
        # and NO full-payload all-reduce anywhere.
        text = mk(2, jnp.int8).lower(tree).as_text()
        ops = hlo_audit.collective_ops(text)
        kinds = [(o.kind, o.dtype) for o in ops if not o.scalar]
        assert ("all-to-all", "i8") in kinds, kinds
        assert any(
            k == "reduce-scatter" and d == "f32" for k, d in kinds
        ), kinds
        assert not any(
            o.kind == "all-reduce" and not o.scalar for o in ops
        ), kinds

    def test_bf16_ici_wire_casts_hop_one(self):
        hvt.init()
        mesh = mesh_lib.data_parallel_mesh()
        dp = mesh.shape["data"]
        P = jax.sharding.PartitionSpec
        tree = {"k1": np.ones((64, 32), np.float32)}

        def red(g):
            return collectives.reduce_gradients(
                g, data_axis="data", extra_axes=("fsdp",), dcn=2,
                ici_wire_dtype=jnp.bfloat16, scatter=dp,
            )

        f = jax.jit(jax.shard_map(
            red, mesh=mesh, in_specs=(P(),),
            out_specs={"k1": collectives.zero1_partition_spec(
                (64, 32), dp
            )},
            check_vma=False,
        ))
        rs = [
            op.dtype for op in hlo_audit.collective_ops(
                f.lower(tree).as_text()
            ) if op.kind == "reduce-scatter"
        ]
        # hop 1 bf16 (ICI wire), hop 2 f32 (no DCN wire).
        assert sorted(set(rs)) == ["bf16", "f32"], rs

    def test_ici_only_error_mass_identity(self):
        """With ONLY the ICI hop quantized (residual consumed at the
        first quantized hop, hop 2 an exact psum), the global identity
        holds exactly: summed over shards, the returned errors equal
        (true sum + residual mass − delivered sum)."""
        rng = np.random.RandomState(3)
        v = jnp.asarray(rng.randn(8, 64).astype(np.float32))
        r = jnp.asarray(rng.randn(8, 64).astype(np.float32) * 0.1)
        P = jax.sharding.PartitionSpec
        sharded = P(("data", "fsdp"))

        def red(x, res):
            return collectives._hierarchical_psum_err(
                x, "data", 2, extra_axes=("fsdp",),
                ici_wire_dtype=jnp.int8, residual=res,
            )

        _, f = self._shard_map(
            red, (sharded, sharded), (sharded, sharded)
        )
        total, err = jax.device_get(f(v, r))
        true = np.asarray(v).sum(axis=0) + np.asarray(r).sum(axis=0)
        np.testing.assert_allclose(
            err.sum(axis=0), true - total[0], rtol=1e-4, atol=1e-4
        )

    def test_per_hop_error_mass_identity_both_hops(self):
        """Per-HOP charging with BOTH hops quantized. The DCN hop runs
        redundantly in each of the ``ici`` dcn-groups (every group sees
        the same hop-1 outputs once the residual is consumed at hop 1,
        so every shard agrees on the delivered gradient), and each group
        charges its own copy of the hop-2 error — so the exact global
        identity is

            Σ_s err_s = (true + residual − h) + ici · (h − delivered)

        where ``h`` is the hop-1 (ICI-quantized) partial total,
        measured by running the SAME reduction with the DCN hop exact
        (deterministic quantization → identical hop-1 outputs)."""
        rng = np.random.RandomState(3)
        v = jnp.asarray(rng.randn(8, 64).astype(np.float32))
        r = jnp.asarray(rng.randn(8, 64).astype(np.float32) * 0.1)
        P = jax.sharding.PartitionSpec
        sharded = P(("data", "fsdp"))

        def red(wire):
            def f(x, res):
                return collectives._hierarchical_psum_err(
                    x, "data", 2, extra_axes=("fsdp",),
                    wire_dtype=wire, ici_wire_dtype=jnp.int8,
                    residual=res,
                )

            return f

        _, both = self._shard_map(
            red(jnp.int8), (sharded, sharded), (sharded, sharded)
        )
        _, ici_only = self._shard_map(
            red(None), (sharded, sharded), (sharded, sharded)
        )
        total, err = jax.device_get(both(v, r))
        h = jax.device_get(ici_only(v, r))[0][0]  # exact hop-2 of hop-1
        ici = 8 // 2
        true = np.asarray(v).sum(axis=0) + np.asarray(r).sum(axis=0)
        want = (true - h) + ici * (h - total[0])
        np.testing.assert_allclose(
            err.sum(axis=0), want, rtol=1e-4, atol=1e-4
        )
        # Residual consumed at hop 1 => every shard agrees on the
        # delivered gradient (no per-dcn-group divergence).
        np.testing.assert_array_equal(total, np.broadcast_to(
            total[0], total.shape
        ))

    def test_residual_flushes_on_exact_wire(self):
        """A residual with no quantized hop anywhere is transmitted in
        full and comes back zero — mass conserved, never dropped."""
        rng = np.random.RandomState(4)
        v = jnp.asarray(rng.randn(8, 32).astype(np.float32))
        r = jnp.asarray(rng.randn(8, 32).astype(np.float32) * 0.1)
        P = jax.sharding.PartitionSpec
        sharded = P(("data", "fsdp"))

        def red(x, res):
            out, err = collectives.reduce_gradients(
                {"v": x}, data_axis="data", extra_axes=("fsdp",),
                residual={"v": res},
            )
            return out["v"], err["v"]

        _, f = self._shard_map(
            red, (sharded, sharded), (sharded, sharded)
        )
        total, err = jax.device_get(f(v, r))
        true = np.asarray(v).sum(axis=0) + np.asarray(r).sum(axis=0)
        np.testing.assert_allclose(total[0], true, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(err, 0.0, atol=0.0)

    def test_scatter_residual_requires_a_quantized_hop(self):
        with pytest.raises(ValueError, match="quantized wire"):
            collectives._reduce_gradients_scatter(
                {"k1": jnp.ones((64, 32))}, 8, data_axis="data",
                extra_axes=(), dcn=1, wire_dtype=None,
                ici_wire_dtype=jnp.bfloat16, bucket_bytes=None,
                reverse=False, residual={"k1": jnp.ones((64, 32))},
            )

    def test_optimizer_tags_and_rejections(self):
        tx = hvt.DistributedOptimizer(
            optax.adam(1e-3), compression_ici="int8"
        )
        from horovod_tpu.training.optimizer import compression_ici_dtype

        assert compression_ici_dtype(tx) == jnp.int8
        # A quantized ICI hop alone turns error feedback on.
        assert compression_error_feedback(tx)
        with pytest.raises(ValueError, match="compression_ici"):
            hvt.DistributedOptimizer(
                optax.adam(1e-3), compression_ici="int4"
            )
        with pytest.raises(ValueError, match="axis_name"):
            hvt.DistributedOptimizer(
                optax.adam(1e-3), compression_ici="int8",
                axis_name="data",
            )


class TestQuantizedTwoShot:
    """The replicated quantized wire is now a two-shot reduce-scatter +
    all-gather (ROADMAP item-2 seam)."""

    def _run(self, fn, v, *extra):
        hvt.init()
        mesh = mesh_lib.data_parallel_mesh()
        P = jax.sharding.PartitionSpec
        sharded = P(("data", "fsdp"))
        f = jax.jit(jax.shard_map(
            fn, mesh=mesh,
            in_specs=(sharded,) * (1 + len(extra)),
            out_specs=(sharded, sharded),
            check_vma=False,
        ))
        return jax.device_get(f(v, *extra))

    def test_equivalent_to_gather_sum_within_one_quantum(self):
        """Shot 2 re-quantizes the REDUCED chunk, so the two-shot total
        may differ from the one-shot gather-sum by that single
        re-quantization — bounded by one quantum of the reduced value's
        scale, never compounding (error feedback charges it to the
        chunk's owner)."""
        rng = np.random.RandomState(1)
        v = jnp.asarray(rng.randn(8, 256).astype(np.float32))

        def two(x):
            return collectives.quantized_group_sum(
                x, ("data", "fsdp"), jnp.int8
            )

        def one(x):
            return collectives._quantized_gather_sum(
                x, ("data", "fsdp"), jnp.int8
            )

        t2, e2 = self._run(two, v)
        t1, e1 = self._run(one, v)
        true = np.asarray(v).sum(axis=0)
        quantum = float(np.abs(true).max()) / 127.0
        np.testing.assert_array_less(np.abs(t2 - t1), quantum + 1e-5)
        # Both are honest reductions of the same sum.
        np.testing.assert_allclose(t2[0], true, atol=8 * quantum)

    def test_error_mass_identity_holds(self):
        """Summed over shards, the returned errors equal exactly
        (true sum - delivered sum) — the telescoping precondition, now
        including the shot-2 error charged to each chunk's owner."""
        rng = np.random.RandomState(2)
        v = jnp.asarray(rng.randn(8, 64).astype(np.float32))

        def two(x):
            return collectives.quantized_group_sum(
                x, ("data", "fsdp"), jnp.int8
            )

        total, err = self._run(two, v)
        true = np.asarray(v).sum(axis=0)
        np.testing.assert_allclose(
            err.sum(axis=0), true - total[0], rtol=1e-4, atol=1e-5
        )

    def test_receive_bytes_drop_from_world_to_two(self):
        """Structural: the two-shot wire's per-device payload receive
        bytes are ~2x the bucket (one all-to-all + one all-gather of
        1/world chunks), vs the one-shot's world x (a full [world, n]
        payload gather). Counted from the lowered programs."""
        hvt.init()
        mesh = mesh_lib.data_parallel_mesh()
        world = mesh.shape["data"]
        P = jax.sharding.PartitionSpec
        v = jnp.ones((world, 1024), jnp.float32)

        def lower(fn):
            f = jax.jit(jax.shard_map(
                lambda x: fn(x)[0], mesh=mesh,
                in_specs=(P(("data", "fsdp")),),
                out_specs=P(("data", "fsdp")), check_vma=False,
            ))
            return f.lower(v).as_text()

        def payload_bytes(text):
            return sum(
                hlo_audit.op_bytes(op)
                for op in hlo_audit.collective_ops(text)
                if op.dtype == "i8"
            )

        two = payload_bytes(lower(
            lambda x: collectives.quantized_group_sum(
                x, ("data", "fsdp"), jnp.int8
            )
        ))
        one = payload_bytes(lower(
            lambda x: collectives._quantized_gather_sum(
                x, ("data", "fsdp"), jnp.int8
            )
        ))
        n = 1024  # per-shard bucket bytes (i8)
        assert one >= world * n  # the gather-sum's full payload gather
        assert two <= 3 * n      # all-to-all (n) + chunk gather (n)
        assert two < one / 2

    def test_groups_need_explicit_position(self):
        with pytest.raises(ValueError, match="group_position"):
            collectives.quantized_group_sum(
                jnp.ones(8), "data", jnp.int8,
                axis_index_groups=[[0, 1], [2, 3]],
            )


class TestPeakFlopsOverride:
    """`HVT_PEAK_FLOPS`, the MFU gauge's denominator where the table has
    no entry: resolved, refused when unparseable, and what `trace.mfu`
    divides by."""

    def test_peak_flops_override_resolves_without_calibration(self,
                                                              monkeypatch):
        from horovod_tpu import trace

        monkeypatch.setenv("HVT_PEAK_FLOPS", "1.5e12")
        peak, src = trace.resolve_peak_flops()
        assert peak == 1.5e12 and src == "override"

    def test_unparseable_peak_override_is_loud(self, monkeypatch):
        from horovod_tpu.analysis import registry

        monkeypatch.setenv("HVT_PEAK_FLOPS", "fast")
        with pytest.raises(ValueError):
            registry.get_float("HVT_PEAK_FLOPS")

    def test_peak_table_override_reaches_trace_mfu(self, monkeypatch):
        from horovod_tpu import trace

        monkeypatch.setenv("HVT_PEAK_FLOPS", "2e12")
        assert trace.device_peak_flops() == 2e12
        # mfu divides by the override: 1e12 FLOP in 1 s on 1 chip.
        assert trace.mfu(1e12, 1.0, 1) == pytest.approx(0.5)


class TestComposedStateSurfaces:
    """EF residuals and checkpoints ride the scattered layout."""

    def _trained(self):
        tr = _trainer(2, "int8", zero1=True)
        return _fit(tr, 2, steps=2)

    def test_residual_lives_sharded_in_zero1_opt_state(self):
        tr = self._trained()
        assert isinstance(tr.state.opt_state, ErrorFeedbackState)
        dp = tr.dp_size
        for leaf, p in zip(
            jax.tree.leaves(tr.state.opt_state.ef_residual),
            jax.tree.leaves(tr.state.params),
        ):
            assert leaf.shape == (dp,) + p.shape
            # dim-0 sharded over the data axes, never dense-replicated.
            assert "data" in str(leaf.sharding.spec)
        # The inner (Adam) mirrors carry the zero1 layout.
        mu = tr.state.opt_state.inner[0].mu
        assert any(
            "data" in str(l.sharding.spec) for l in jax.tree.leaves(mu)
        )

    def test_checkpoint_roundtrip(self, tmp_path):
        tr = self._trained()
        path = str(tmp_path / "state.msgpack")
        checkpoint.save(path, tr.state)
        tr2 = _trainer(2, "int8", zero1=True)
        x, y = _data(16)
        tr2.build(x[:8], y[:8])
        restored = checkpoint.restore(path, tr2.state)
        for a, b in zip(
            jax.tree.leaves(jax.device_get(tr.state.opt_state)),
            jax.tree.leaves(jax.device_get(restored.opt_state)),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_install_state_reshard_recuts_residual(self):
        """A committed snapshot from a 2-shard world installs onto the
        8-shard composed trainer: the EF residual re-cuts
        mass-conserving, the zero1 mirrors re-slice."""
        tr = self._trained()
        snap = jax.device_get(tr.state)
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
        for leaf in jax.tree.leaves(
            jax.device_get(installed.opt_state.ef_residual)
        ):
            np.testing.assert_allclose(leaf.sum(axis=0), 4.0, rtol=1e-6)

    def test_device_cached_path_composes(self):
        x, y = _data(512)
        tr = _trainer(2, "int8", zero1=True)
        hist = tr.fit(x=x, y=y, batch_size=2, epochs=3, cache="device",
                      verbose=0)
        assert hist[-1]["loss"] < hist[0]["loss"]
