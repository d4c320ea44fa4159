"""Compiles the kernels and ops of the main path for a DESCRIBED TPU v5e.

The CPU suite runs the Mosaic kernels in the Pallas interpreter, which is
ordinary JAX: it proves the algebra and cannot see what Mosaic and XLA:TPU
refuse — a tile that overflows scoped VMEM, a kernel GSPMD is asked to
partition, a sum that does not fit. Here each kernel, and each op built
around one, is compiled with the chip's own compiler at the shapes the
benchmark's cells call it with, and at the edges of what its predicates
admit (`horovod_tpu/testing/described_v5e.py`; the whole train steps are
in `tests/test_chip_compile.py`). Nothing runs, so nothing here says
anything about results or times.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel import mesh as mesh_lib
from horovod_tpu.testing.described_v5e import kernel_calls, kernel_names

SDS = jax.ShapeDtypeStruct


def while_trip_counts(hlo: str) -> list:
    """The constant each `while` of a compiled program counts up to (its
    condition's ``compare(counter, constant), direction=LT``); None for a
    condition of another form."""
    trips = []
    for cond in re.findall(r" while\(.*?condition=%([\w.\-]+)", hlo):
        start = hlo.index(f"%{cond} (")
        body = hlo[start:hlo.index("\n}", start)]
        bound = re.search(r"compare\(%[\w.\-]+, %([\w.\-]+)\), direction=LT",
                          body)
        value = bound and re.search(
            rf"%{re.escape(bound[1])} = s32\[\]\S* constant\((\d+)\)", body)
        trips.append(int(value[1]) if value else None)
    return trips


# The Mosaic calls of a forward + backward, by the backward's form
# (`fa.fused_backward`); the sink-only dK/dV pass is dK/dV work by name too.
FUSED = sorted([fa.KERNEL_FWD, fa.KERNEL_BWD])
SPLIT = sorted([fa.KERNEL_FWD, fa.KERNEL_DQ, fa.KERNEL_DKV])
SPLIT_SINKS = sorted(SPLIT + [fa.KERNEL_DKV])


@pytest.mark.parametrize(
    # [B, T, H, D], dtype, flash kwargs, segmented, the Mosaic calls of
    # forward + backward by name, v's head size where it is not D,
    # `hvt_flash_tiles` after the trace (skipped / edge / full grid steps a
    # (b, h); None: not looked at), `hvt_flash_subtiles` (run_full /
    # run_masked / skipped 128 × 128 blocks of the triangle tiles, which
    # run by row strips: every causal call without segment ids or sinks)
    "shape,dtype,kwargs,segmented,kernels,v_dim,tiles,subtiles",
    [
        pytest.param((4, 1024, 16, 128), jnp.bfloat16, {}, False, FUSED,
                     None, None, (28, 8, 28), id="smoke-bf16-T1024"),
        pytest.param((2, 4096, 16, 128), jnp.bfloat16, {}, True, FUSED,
                     None, (28, 36, 0), (0, 0, 0), id="segments-T4096"),
        pytest.param((2, 4096, 16, 128), jnp.bfloat16,
                     {"window": 1024, "sinks": 64}, False, SPLIT_SINKS,
                     None, None, (0, 0, 0), id="window-sinks-T4096"),
        # Refused at 1024² tiles (16.20M of 16.00M scoped VMEM in the dK/dV
        # pass); `pick_blocks` takes 512² for 4-byte inputs.
        pytest.param((4, 2048, 8, 64), jnp.float32, {}, False, FUSED,
                     None, None, (24, 16, 24), id="f32-D64-T2048"),
        # The benchmark's cells' own calls, at the tiles `pick_blocks`
        # gives them (a full body and a triangle body a kernel; the 1024²
        # backward is the fullest). Kanana's keeps 16 MiB of dQ resident.
        pytest.param((2, 2048, 16, 128), jnp.bfloat16, {}, False, FUSED,
                     None, (1, 2, 1), (56, 16, 56),
                     id="cell-cerebras-gpt-1.3b"),
        pytest.param((1, 4096, 24, 128), jnp.bfloat16, {"window": 4096},
                     False, FUSED, None, (28, 8, 28), (48, 32, 48),
                     id="cell-starcoder2-3b"),
        pytest.param((1, 8192, 32, 192), jnp.bfloat16, {}, False, FUSED,
                     128, (120, 16, 120), (96, 64, 96),
                     id="cell-kanana-2-30b-a3b"),
        # Laguna's two kinds: the window layers' band at 512² tiles, every
        # running tile a triangle (the band's lower edge or the diagonal),
        # and the full layers at group 6.
        pytest.param((1, 8192, 64, 128), jnp.bfloat16, {"window": 512},
                     False, FUSED, None, (17, 31, 0), (186, 124, 186),
                     id="cell-laguna-xs.2-window"),
        pytest.param((1, 8192, 48, 128), jnp.bfloat16, {}, False, FUSED,
                     None, (28, 8, 28), (224, 64, 224),
                     id="cell-laguna-xs.2-full"),
        # Long contexts, in whichever form the predicate picks: 32 MiB of
        # dQ resident, and the budget's edge (64 MiB: half the VMEM).
        pytest.param((1, 32768, 2, 128), jnp.bfloat16, {}, False, None,
                     None, None, (384, 256, 384), id="T32768-D128"),
        pytest.param((1, 32768, 2, 256), jnp.bfloat16, {}, False, None,
                     None, None, (384, 256, 384),
                     id="T32768-D256-at-the-budget"),
        pytest.param((1, 65536, 1, 256), jnp.bfloat16, {}, False, None,
                     None, None, (768, 512, 768),
                     id="T65536-D256-past-the-budget"),
    ],
)
def test_flash_fwd_bwd_compiles_for_v5e(topo, shape, dtype, kwargs,
                                        segmented, kernels, v_dim, tiles,
                                        subtiles):
    from horovod_tpu.obs import core as obs_core
    from horovod_tpu.obs import prom

    one_chip = SingleDeviceSharding(topo.devices[0])
    qkv = SDS(shape, dtype, sharding=one_chip)
    v = qkv if v_dim is None else SDS(
        shape[:3] + (v_dim,), dtype, sharding=one_chip)
    args = [qkv, qkv, v]
    if segmented:
        args.append(SDS(shape[:2], jnp.int32, sharding=one_chip))

    def loss(q, k, v, ids=None):
        # A caller's scope, as a flax module gives one: called bare, the
        # kernel's name is the outermost entry of the name stack and takes
        # the transform's wrapper (`jvp_hvt_flash_fwd_`).
        with jax.named_scope("attention"):
            out = fa.flash_attention(
                q, k, v, causal=True, interpret=False,
                q_segment_ids=ids, kv_segment_ids=ids, **kwargs,
            )
        return out.astype(jnp.float32).sum()

    obs_core.reset()
    compiled = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1, 2))
    ).lower(*args).compile()
    if kernels is None:
        kernels = FUSED if fa.fused_backward(
            shape[1], shape[3], dtype) else SPLIT
    # The instruction names the profiler's device events carry.
    assert kernel_names(compiled) == kernels
    values = prom.parse_text(prom.render())
    assert values['hvt_flash_backward{impl="fused"}'] == (kernels == FUSED)
    assert values['hvt_flash_backward{impl="split"}'] == (kernels != FUSED)
    # The census of the traced forward grid: static per call, so a gauge.
    assert obs_core.spec("hvt_flash_tiles").kind == "gauge"
    if tiles is not None:
        assert tuple(
            values[f'hvt_flash_tiles{{kind="{kind}"}}']
            for kind in ("skipped", "edge", "full")
        ) == tiles
    assert tuple(
        values[f'hvt_flash_subtiles{{kind="{kind}"}}']
        for kind in ("run_full", "run_masked", "skipped")
    ) == subtiles
    obs_core.reset()


def test_flash_kernels_keep_their_names_under_a_shard_map(topo):
    """On a mesh the model wraps the kernel in a `shard_map`, whose own
    name used to be the innermost and so the instruction's."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mesh_lib.build_mesh(
        mesh_lib.MeshSpec(data=4), devices=topo.devices)
    spec = P(mesh_lib.DATA_AXIS)
    qkv = SDS((8, 1024, 16, 128), jnp.bfloat16,
              sharding=NamedSharding(mesh, spec))

    def loss(q, k, v):
        out = jax.shard_map(
            lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, interpret=False),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=False,
        )(q, k, v)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1, 2))
    ).lower(qkv, qkv, qkv).compile()
    assert kernel_names(compiled) == FUSED


# --- the latent-attention / routed-expert model's kernels -------------------
# `kanana-2-30b-a3b.seq8k.1chip`: q and k 192 wide, v 128, one
# sequence of 8,192 over 32 heads; 16 held experts of 2,048 x 768 over a
# budget of 12,288 rows.

def test_flash_with_two_head_sizes_compiles_for_v5e(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    qk = SDS((1, 8192, 32, 192), jnp.bfloat16, sharding=one_chip)
    v = SDS((1, 8192, 32, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        with jax.named_scope("attention"):
            out = fa.flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(qk, qk, v).compile()
    assert kernel_names(compiled) == FUSED
    (_, grads) = compiled.output_shardings  # it has the three gradients
    assert len(grads) == 3


def test_grouped_matmul_compiles_for_v5e(topo, compiled_kernel):
    """Both of the layer's products with their gradients, at the cell's
    shapes: three Mosaic calls each, two under the product's name (the
    product and dlhs) and one under drhs's."""
    from horovod_tpu.ops import grouped_matmul as gm

    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.bfloat16):
        return SDS(shape, dtype, sharding=one_chip)

    def loss(rows, w_gate_up, w_down, sizes):
        with jax.named_scope("experts"):
            gate, up = jnp.split(
                gm.grouped_matmul(rows, w_gate_up, sizes), 2, axis=-1)
            out = gm.grouped_matmul(jax.nn.silu(gate) * up, w_down, sizes)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        sds((12288, 2048)), sds((16, 2048, 1536)), sds((16, 768, 2048)),
        sds((16,), jnp.int32)).compile()
    assert kernel_names(compiled) == sorted(
        [gm.KERNEL] * 4 + [gm.KERNEL_DW] * 2)


def sorts_of(hlo: str) -> list[str]:
    """The compiled program's `sort` instructions, a line each."""
    return [line.strip() for line in hlo.splitlines()
            if re.search(r" sort\(", line)]


@pytest.mark.parametrize("tokens,d,routed,held,k", [
    (512, 256, 16, 4, 2),     # a toy layer, forward and backward
    (8192, 4096, 320, 8, 8),  # Solar's: the widest logits a cell routes
])
def test_routed_layer_selects_without_sorting_its_logits(
        topo, compiled_kernel, tokens, d, routed, held, k):
    """`RoutedExperts` compiled for the described v5e, forward and
    backward: `level_bias` and `_largest` select by counting, so no `sort`
    instruction has an operand the logits' shape (either way round) and no
    `top_k` call is left. The one sort that stays is the dispatch's
    `argsort` of the T * k pair keys. The gauge says so at trace time."""
    from horovod_tpu.models.moe import RoutedExperts
    from horovod_tpu.obs import prom

    layer = RoutedExperts(
        n_routed=routed, k=k, expert_width=d // 2, shared_width=d // 2,
        n_held=held, held_start=0, routed_scaling=2.5,
        compute_dtype=jnp.bfloat16)
    one_chip = SingleDeviceSharding(topo.devices[0])
    x = SDS((1, tokens, d), jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(
        lambda leaf: SDS(leaf.shape, leaf.dtype, sharding=one_chip),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), x))

    def loss(params, x):
        return layer.apply(params, x).astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    assert "top_k" not in hlo.lower() and "topk" not in hlo.lower()
    logits = (rf"\[(1,)?{tokens},{routed}\]", rf"\[(1,)?{routed},{tokens}\]")
    sorts = sorts_of(hlo)
    assert not [line for line in sorts
                if any(re.search(shape, line) for shape in logits)], sorts
    assert any(f"[{tokens * k}]" in line for line in sorts), sorts
    assert 'hvt_moe_selection{impl="count"} 1' in prom.render()


@pytest.mark.parametrize("rows,d,tokens,k", [
    (3328, 4096, 8192, 8),    # Solar's
    (12288, 2048, 8192, 6),   # Kanana's
    (9216, 4096, 4096, 10),   # Granite's
    (16384, 2048, 8192, 8),   # Laguna's
], ids=["solar", "kanana", "granite", "laguna"])
def test_segment_sum_compiles_for_v5e(topo, compiled_kernel, rows, d, tokens,
                                      k):
    """The routed layer's two sums back to the tokens at a routed cell's
    rows x width -> tokens: the combine (gated) and, in the backward pass,
    the transpose of the dispatch's row gather (weight 1), one Mosaic call
    `hvt_moe_combine` each."""
    from horovod_tpu.models import moe
    from horovod_tpu.ops import segment_sum as seg

    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.bfloat16):
        return SDS(shape, dtype, sharding=one_chip)

    def loss(x, out, weight, pairs, held):
        order = seg.token_order(pairs, held, tokens, k)
        token_of = pairs // k
        gathered = moe._dispatched(x, token_of, order, tokens)
        mixed = moe._combined(out, weight, token_of, order, tokens,
                              jnp.bfloat16)
        return (mixed.astype(jnp.float32).sum()
                + gathered.astype(jnp.float32).sum())

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        sds((tokens, d)), sds((rows, d)), sds((rows,), jnp.float32),
        sds((rows,), jnp.int32), sds((), jnp.int32)).compile()
    assert kernel_names(compiled) == [seg.KERNEL] * 2


@pytest.mark.parametrize("tokens,d,routed,held,k", [
    (512, 256, 16, 4, 2),      # a toy layer
    (4096, 4096, 72, 8, 10),   # Granite's: the widest rows a cell sums
])
def test_routed_layer_sums_its_rows_without_a_scatter(
        topo, compiled_kernel, tokens, d, routed, held, k):
    """`RoutedExperts` compiled for the described v5e, forward and
    backward: no `scatter` instruction has a result of the tokens' shape
    ``[tokens, d]``; the combine and the transpose of the row gather are
    the two calls of `hvt_moe_combine`, each under its part's scope (the
    one the benchmark's `moe_dispatch_ms_per_step` reads the kernel by),
    the combine's in the forward pass and the gather's in the backward.
    The gauge says so at trace time."""
    from horovod_tpu.models.moe import RoutedExperts
    from horovod_tpu.obs import prom
    from horovod_tpu.ops import segment_sum as seg

    layer = RoutedExperts(
        n_routed=routed, k=k, expert_width=d // 4, shared_width=d // 4,
        n_held=held, held_start=0, routed_scaling=2.5,
        compute_dtype=jnp.bfloat16)
    one_chip = SingleDeviceSharding(topo.devices[0])
    x = SDS((1, tokens, d), jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(
        lambda leaf: SDS(leaf.shape, leaf.dtype, sharding=one_chip),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), x))

    def loss(params, x):
        return layer.apply(params, x).astype(jnp.float32).sum()

    hlo = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    scatters = [line for line in hlo.splitlines()
                if re.search(rf"\[(1,)?{tokens},{d}\]\S* scatter\(", line)]
    assert not scatters, scatters
    scopes = re.findall(
        rf"%{seg.KERNEL}[.\d]* = .*?op_name=\"([^\"]*)\"", hlo)
    assert len(scopes) == 2, scopes
    assert any("hvt.moe/combine/" in s and "transpose(" not in s
               for s in scopes), scopes
    assert any("hvt.moe/dispatch/" in s and "transpose(" in s
               for s in scopes), scopes
    assert 'hvt_moe_combine{impl="segment"} 1' in prom.render()


# --- the hybrid linear-attention / routed-expert model's scan ---------------
# `solar-open2-250b.seq8k.1chip`: 8 held heads of 128 over one
# sequence of 8,192 in chunks of 64; one softmax and three KDA layers over
# 320-way routing onto 8 held experts of 4,096 x 1,280.

def test_delta_rule_compiles_for_v5e(topo, compiled_kernel):
    """The scan with its hand-written backward at the cell's shapes: the
    Mosaic calls are named `hvt_kda_fwd` (the forward pass: the pair
    matrices, then the walk with the state in VMEM) and `hvt_kda_bwd` (the
    backward pass: the pair matrices again, the walk that keeps the states,
    the walk's transpose) and nothing else, they carry the caller's scope,
    no `while` over the 128 chunks is left either way (what is left takes a
    head at a time: the ratio sums inside the sub-chunks, which stay XLA's),
    and what it needs beside its arguments stays under 1.3 GB (0.59 when the
    kernels came; 1.16 as one `lax.scan` with autodiff)."""
    from horovod_tpu.ops import delta_rule

    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.bfloat16):
        return SDS(shape, dtype, sharding=one_chip)

    wide = (1, 8192, 8, 128)

    def loss(q, k, v, g, beta):
        with jax.named_scope("hvt.kda/scan"):
            out = delta_rule.gated_delta_rule(q, k, v, g, beta, chunk=64)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        sds(wide), sds(wide), sds(wide), sds(wide, jnp.float32),
        sds(wide[:3], jnp.float32)).compile()
    assert kernel_names(compiled) == (
        [delta_rule.KERNEL_BWD] * 3 + [delta_rule.KERNEL_FWD] * 2)
    assert all("hvt.kda/scan" in line for line in kernel_calls(compiled))
    trips = while_trip_counts(compiled.as_text())
    assert None not in trips and 128 not in trips, trips
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"delta rule forward + backward: temporaries {temp / 1e9:.3f} GB")
    assert temp < 1.3e9


@pytest.mark.parametrize("t,chunk,dk,dtype", [
    (200, 16, 128, jnp.bfloat16), (100, 32, 128, jnp.float32),
    (200, 48, 128, jnp.bfloat16), (300, 128, 128, jnp.bfloat16),
    (40, 64, 128, jnp.float32), (256, 64, 256, jnp.bfloat16)],
    ids=["one_sub_chunk", "two_sub_chunks_100_over_32", "three_sub_chunks",
         "chunk_128", "shorter_than_a_chunk", "keys_of_256"])
def test_delta_rule_kernels_compile_at_other_shapes(topo, compiled_kernel, t,
                                                    chunk, dk, dtype):
    """Every shape `takes_kernels` admits has to pass Mosaic, not only the
    cell's: other numbers of sub-chunks a chunk (with two, a sum of
    comparisons folded into a comparison of booleans, which Mosaic refused
    on the chip while the interpreter passed), a T the chunk does not
    divide or that is shorter, wider keys, float32 inputs."""
    from horovod_tpu.ops import delta_rule

    assert delta_rule.takes_kernels(dk, 128, chunk)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return SDS(shape, dtype, sharding=one_chip)

    def loss(q, k, v, g, beta):
        out = delta_rule.gated_delta_rule(q, k, v, g, beta, chunk=chunk)
        return out.astype(jnp.float32).sum()

    keys = (1, t, 2, dk)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        sds(keys, dtype), sds(keys, dtype), sds((1, t, 2, 128), dtype),
        sds(keys, jnp.float32), sds(keys[:3], jnp.float32)).compile()
    assert kernel_names(compiled) == (
        [delta_rule.KERNEL_BWD] * 3 + [delta_rule.KERNEL_FWD] * 2)


# --- the state-space / routed-expert model's scan ----------------------------
# `granite-4.0-h-small.seq4k.1chip`: 16 held Mamba-2 heads of 64 with a
# state of 128 over one sequence of 4,096 in chunks of 256; nine such layers
# and one NoPE GQA layer over 72-way softmax routing onto 8 held experts of
# 4,096 x 768, a tied head, every block rematerialised.

@pytest.mark.parametrize("t,chunk,dtype", [
    (4096, 256, jnp.bfloat16), (1000, 256, jnp.bfloat16),
    (100, 256, jnp.float32), (8192, 128, jnp.bfloat16)],
    ids=["the_cell", "t_1000", "under_one_chunk", "8k_in_chunks_of_128"])
def test_ssd_scan_compiles_for_v5e(topo, t, chunk, dtype):
    """The scan with autodiff's backward at the cell's head sizes, at the
    cell's length and three others: it compiles, no Mosaic call is in it
    (the form is XLA's), the one `while` left is the walk over the chunks'
    states, forward and backward, and at the cell's length what it needs
    beside its arguments stays under 0.7 GB (0.52 when it was written)."""
    from horovod_tpu.ops import ssd

    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, kind=dtype):
        return SDS(shape, kind, sharding=one_chip)

    def loss(x, dt, a_log, b, c):
        with jax.named_scope("hvt.ssm/scan"):
            y = ssd.ssd_scan(x, dt, a_log, b, c, chunk=chunk)
            return jnp.square(y).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        sds((1, t, 16, 64)), sds((1, t, 16), jnp.float32),
        sds((16,), jnp.float32), sds((1, t, 128)), sds((1, t, 128))).compile()
    assert kernel_names(compiled) == []
    trips = [n for n in while_trip_counts(compiled.as_text()) if n != 1]
    assert trips in ([], [ssd.n_chunks(t, chunk)] * 2), trips
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"ssd scan T {t}: temporaries {temp / 1e9:.3f} GB")
    if t == 4096:
        assert temp < 0.7e9
