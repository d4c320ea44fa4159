"""Compiles for a DESCRIBED TPU v5e — the chip's own compiler, no chip.

The CPU suite runs the flash kernel in the Pallas interpreter, which is
ordinary JAX: it proves the algebra and cannot see what Mosaic and XLA:TPU
refuse — a tile that overflows scoped VMEM, a kernel GSPMD is asked to
partition, a step that does not fit HBM. libtpu is installed here and
compiles for a topology that is described and not attached, so the main
path's kernels and one whole train step are compiled at real widths in
this ONE file (libtpu belongs to one process: a second file could land on
another xdist worker and find it taken). Nothing runs, so nothing here
says anything about results or times.

The topology is described inside a module-scoped fixture — never at
import, so every worker collects the same tests — and the persistent
compilation cache is off around these compiles (an executable compiled
for a described device can be written to it but not read back).
"""

import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import SingleDeviceSharding

import horovod_tpu as hvt
from horovod_tpu.analysis import hlo_audit
from horovod_tpu.models.transformer import ShardingConfig, TransformerLM
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import fused_ce
from horovod_tpu.parallel import mesh as mesh_lib
from horovod_tpu.parallel import sharding as sharding_lib
from horovod_tpu.training import trainer as trainer_lib
from horovod_tpu.training.train_state import TrainState

SDS = jax.ShapeDtypeStruct


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    env = pytest.MonkeyPatch()
    env.setenv("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        env.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()
    env.undo()


@pytest.fixture
def compiled_kernel(monkeypatch):
    """Steer the program's one interpret decision to what it takes on the
    chip. It asks the attached devices, which are CPUs here."""
    monkeypatch.setattr(fa, "default_interpret", lambda: False)


def kernel_calls(compiled) -> list[str]:
    return [
        line for line in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]


def kernel_names(compiled) -> list[str]:
    """The Mosaic calls' HLO instruction names without their numbers
    (what the benchmark's reduction calls an op family), sorted."""
    return sorted(
        re.sub(r"[.\d]+$", "", re.match(r"\s*(?:ROOT )?%(\S+) =", line)[1])
        for line in kernel_calls(compiled)
    )


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


# --- one whole train step on the four described chips ----------------------

D_MODEL, HEADS, SEQ, VOCAB, GLOBAL_BATCH = 2048, 16, 1024, 8192, 8
HEAD_CHUNKS = 8


def lm_trainer(mesh, sharding, n_layers=2, vocab=VOCAB):
    model = TransformerLM(
        vocab_size=vocab, d_model=D_MODEL, n_heads=HEADS, n_layers=n_layers,
        dropout=0.0, compute_dtype=jnp.bfloat16,
        fused_head_chunks=HEAD_CHUNKS,
        sharding=sharding,
    )
    return hvt.Trainer(
        model, hvt.DistributedOptimizer(optax.adamw(1e-4)),
        loss="module", mesh=mesh,
    )


def abstract_step_args(trainer, seq=SEQ, batch=GLOBAL_BATCH):
    """`train_step`'s arguments as shapes with shardings: a described
    device cannot hold an array, so nothing is built or placed."""
    mesh = trainer.mesh
    rep = sharding_lib.replicated(mesh)
    tokens = SDS(
        (batch, seq), jnp.int32,
        sharding=sharding_lib.batch_sharding(mesh, 2),
    )
    x0 = jnp.zeros((trainer.dp_size, seq), jnp.int32)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(
        lambda: trainer.module.init(
            {"params": key, "dropout": key}, x0, train=False, labels=x0
        )
    )["params"]
    state = TrainState(
        step=SDS((), jnp.int32),
        params=params,
        opt_state=jax.eval_shape(trainer.tx.init, params),
        rng=SDS((2,), jnp.uint32),
        model_state=None,
    )
    state = jax.tree.map(
        lambda l: SDS(l.shape, l.dtype, sharding=rep), state
    )
    scalar = SDS((), jnp.float32, sharding=rep)
    return state, (tokens, tokens), scalar, {
        name: scalar for name in trainer.metric_names
    }


@pytest.fixture
def four_chip_mesh(topo):
    return mesh_lib.build_mesh(
        mesh_lib.MeshSpec(data=4), devices=topo.devices
    )


def compiled_step(trainer, seq=SEQ, batch=GLOBAL_BATCH):
    """The Trainer's own jitted step, so the compile sees the options the
    program passes and not a copy of them."""
    return trainer._train_step.lower(
        *abstract_step_args(trainer, seq, batch)).compile()


def head_loops(hlo: str) -> tuple[str, str]:
    """(forward scan's body, backward loop's body) of the head + CE: two
    loops under its scope, the backward one over slices of the vocabulary
    (every step here hands a chip fewer rows than the vocabulary)."""
    bodies = hlo_audit.while_bodies(hlo, fused_ce.SCOPE)
    backward, = hlo_audit.while_bodies(
        hlo, f"{fused_ce.SCOPE}/{fused_ce.VOCAB_SCAN}/while")
    assert len(bodies) == 2 and backward in bodies
    forward, = (body for body in bodies if body != backward)
    return forward, backward


def wire_bytes(sums) -> dict:
    """Bytes the step sums across chips, by the dtype they cross in."""
    return {
        dtype: sum(r.nbytes for r in sums if r.dtype == dtype)
        for dtype in {r.dtype for r in sums}
    }


def gradient_bytes(params) -> dict:
    """What the data-parallel step always put on the wire: every matrix
    outside the head as bfloat16, the head's dW and the LayerNorm scales
    as float32, each parameter once."""
    head = params["lm_head"]["kernel"].size
    leaves = jax.tree.leaves(params)
    matrices = sum(leaf.size for leaf in leaves if leaf.ndim > 1) - head
    scales = sum(leaf.size for leaf in leaves if leaf.ndim == 1)
    return {"bf16": 2 * matrices, "f32": 4 * (head + scales)}


def test_train_step_compiles_data_parallel_on_four_chips(
        four_chip_mesh, compiled_kernel):
    trainer = lm_trainer(four_chip_mesh, ShardingConfig(mesh=four_chip_mesh))
    compiled = compiled_step(trainer)
    # The chunked head + CE runs on each chip's own rows: its logits tile
    # is a chunk of the chip's quarter, not of the global batch, and no
    # chip gathers rows inside the loops. (Elsewhere the compiler may still
    # split an all-reduce into a reduce-scatter and a gather.)
    hlo = compiled.as_text()
    forward, backward = head_loops(hlo)
    # 2,048 rows a chip against 8,192 entries: the forward scans 8 chunks
    # of the rows, the backward 8 slices of the vocabulary.
    local_rows = GLOBAL_BATCH // 4 * SEQ
    for body in (forward, backward):
        assert not hlo_audit.collective_ops(body)
    assert f"f32[{local_rows // HEAD_CHUNKS},{VOCAB}]" in forward
    assert f"f32[{local_rows},{VOCAB // HEAD_CHUNKS}]" in backward
    assert f"f32[{4 * local_rows // HEAD_CHUNKS},{VOCAB}]" not in hlo
    assert f"f32[{4 * local_rows},{VOCAB // HEAD_CHUNKS}]" not in hlo
    calls = kernel_calls(compiled)
    assert len(calls) == 2 * 2  # layers x forward, backward
    assert kernel_names(compiled) == sorted(FUSED * 2)
    # The shard_map hands each chip's kernel its quarter of the batch.
    kernel_batches = {
        int(b) for line in calls
        for b in re.findall(rf"bf16\[(\d+),{HEADS},{SEQ},128\]", line)
    }
    assert kernel_batches == {GLOBAL_BATCH // 4}
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9

    # The gradients cross the chips in the dtypes they always did, and
    # asynchronously (PR 30): the compiler put compute between the start
    # and the done of nine tenths of the bytes.
    sums = hlo_audit.reduction_schedule(hlo)
    assert wire_bytes(sums) == gradient_bytes(
        abstract_step_args(trainer)[0].params)
    assert trainer_lib.training_compiler_options(four_chip_mesh)
    assert hlo_audit.asynchronous_share(sums) >= 0.9


def test_one_chip_trainer_passes_no_compile_option(topo, compiled_kernel):
    """One described chip: nothing to sum across chips, no option, and so
    the program of every PR before 30."""
    mesh = mesh_lib.build_mesh(
        mesh_lib.MeshSpec(data=1), devices=topo.devices[:1])
    assert trainer_lib.training_compiler_options(mesh) == {}
    hlo = compiled_step(
        lm_trainer(mesh, ShardingConfig(mesh=mesh))).as_text()
    assert not hlo_audit.collective_ops(hlo)
    assert "async-collective" not in hlo


# --- the benchmark's four-chip cell, at its own sizes -----------------------
# `cerebras-gpt-1.3b.seq2k.dp4`: 12 layers of d2048, vocabulary 50,257,
# sequences of 2,048, 2 a chip. Where the chip is nearly full is where
# overlap is paid for in memory, so the guard on that stands here.

CELL_LAYERS, CELL_SEQ, CELL_VOCAB = 12, 2048, 50257


def cell_step(mesh):
    return compiled_step(
        lm_trainer(mesh, ShardingConfig(mesh=mesh), CELL_LAYERS, CELL_VOCAB),
        CELL_SEQ)


@pytest.fixture(scope="module")
def cell_compiled(topo):
    steer = pytest.MonkeyPatch()
    steer.setattr(fa, "default_interpret", lambda: False)
    try:
        yield cell_step(mesh_lib.build_mesh(
            mesh_lib.MeshSpec(data=4), devices=topo.devices))
    finally:
        steer.undo()


def test_cell_step_sums_its_gradients_asynchronously(cell_compiled):
    """What PERF.md states for PR 30: 99 % of the bytes the step sums
    across chips are asynchronous, the head's float32 dW among them, in
    the dtypes and the bytes of every PR before (1.41 GB + 412 MB)."""
    sums = hlo_audit.reduction_schedule(cell_compiled.as_text())
    assert hlo_audit.asynchronous_share(sums) >= 0.99
    head_dw = [r for r in sums if r.shape == (D_MODEL, CELL_VOCAB)]
    assert [(r.dtype, r.asynchronous) for r in head_dw] == [("f32", True)]
    wire = wire_bytes(sums)
    assert 1.41e9 < wire["bf16"] < 1.42e9
    assert 411e6 < wire["f32"] < 413e6


def test_cell_step_table_names_every_carrier_of_its_sums(cell_compiled):
    """The table a reader joins to the chip's trace by instruction name
    (PR 37): every start, done and host fusion of the entry computation is
    in it exactly once, every sum says whose it is and every host what it
    computes, and most hosts are AdamW passes (74 of 116 when this was
    written; the counts are XLA's and are not pinned)."""
    hlo = cell_compiled.as_text()
    rows = hlo_audit.reduction_schedule(hlo)
    entry = hlo[hlo.index("\nENTRY "):]

    def instructions(pattern):
        return sorted(re.findall(
            rf"^\s*(?:ROOT )?%({pattern}) = ", entry, re.M))

    hosts = [host for r in rows for host in r.hosts]
    carried = [r for r in rows if r.hosts or r.start != r.done]
    assert instructions(r"async-collective-start[\w.\-]*") == sorted(
        r.start for r in carried)
    assert instructions(r"async-collective-done[\w.\-]*") == sorted(
        r.done for r in carried)
    assert instructions(
        r"[\w.\-]+(?= = [^\n]*calls=%async_collective_fusion)"
    ) == sorted(host.name for host in hosts)
    assert len(carried) > 40 and len(hosts) > len(carried)
    assert all(r.asynchronous for r in carried)
    # 1.41 GB of bfloat16 and 412 MB of float32, each sum once.
    assert 1.8255e9 < sum(r.nbytes for r in rows) < 1.8260e9
    assert all(r.scope and r.channel is not None for r in rows)
    assert all(host.host_scope for host in hosts)
    adamw = [h for h in hosts if trainer_lib.OPTIMIZER_SCOPE in h.host_scope]
    assert len(adamw) > len(hosts) / 2
    head_dw, = (r for r in rows if r.shape == (D_MODEL, CELL_VOCAB))
    assert fused_ce.SCOPE in head_dw.scope


# `temp_size_in_bytes` of this step at the parent of PR 32 (the row scan's
# float32 [D, V] accumulator): `step_temp_gb` on the cell's ledger lines.
CELL_TEMP_BYTES_PR31 = 3_857_022_976


def test_cell_step_keeps_its_loops_and_kernels(cell_compiled):
    hlo = cell_compiled.as_text()
    forward, backward = head_loops(hlo)
    assert not hlo_audit.collective_ops(forward + backward)
    assert kernel_names(cell_compiled) == sorted(FUSED * CELL_LAYERS)
    mem = cell_compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9

    # The chip's own 4,096 rows: chunks of 512 forward, and backward 8
    # lane-aligned slices of 6,400 of the vocabulary (50,257 pad to 51,200).
    rows, width = GLOBAL_BATCH // 4 * CELL_SEQ, 6400
    assert f"f32[{rows // HEAD_CHUNKS},{CELL_VOCAB}]" in forward
    assert f"f32[{rows},{width}]" in backward
    # What PR 32 exists for: no float32 [D, V] running sum is read and
    # written once a tile. The loop's sum is dh [rows, D]; dW is a buffer
    # each iteration writes one [D, 6400] slice of, in place, and nothing
    # else in the body produces an array of its size.
    dw = rf"f32\[{D_MODEL},{HEAD_CHUNKS * width}\]"
    assert f"f32[{D_MODEL},{CELL_VOCAB}]" not in backward
    producers = set(re.findall(
        rf"%(\S+) = {dw}\S* (?!parameter|get-tuple-element)[\w\-]+\(",
        backward))
    assert producers and all(
        "dynamic-update-slice" in name.replace("_", "-")
        for name in producers), producers
    assert re.search(rf"f32\[{rows},{D_MODEL}\]\S* add\(", backward)
    # And the turned loop costs the step no memory: its temporaries are
    # within 60 MB of the parent's (they came out 98 MB under).
    assert mem.temp_size_in_bytes <= CELL_TEMP_BYTES_PR31 + 60e6


# The same step at PR 31 with no compile options (it sums synchronously).
CELL_BARE_TEMP_BYTES_PR31 = 3_810_574_336


def test_overlapped_sums_cost_the_cell_step_no_memory(
        cell_compiled, four_chip_mesh, compiled_kernel, monkeypatch):
    """What nearly sank PR 30: asynchronous sums alone let the scheduler
    hold every layer's activations for the weight gradients it moved
    beside them (+710 MB, a fifth of the temporaries). The same step with
    no options sums synchronously, and until PR 32 the step with them was
    held to 1.03 x its temporaries (it read 1.012: +46 MB). PR 32 took
    358 MB off the bare step and 98 MB off the optioned one, so that ratio
    now reads 1.089 with nothing added to what the options hold: by the
    compiler's own buffer assignment of all four programs (PERF.md §6,
    PR 32) both steps peak in the head's backward loop, the optioned one
    with 35.6 MB (1.05 %) more live there (0.0 at PR 31), and the rest is
    holes in the heap, 34 MB in the bare step's against 168 to 233 in the
    other three. So the line stands where it stood: the optioned step is
    no larger than at PR 31, when it was within 3 % of the bare step of
    PR 31, and the bare step is no larger than it was then either."""
    monkeypatch.setattr(
        trainer_lib, "training_compiler_options", lambda mesh: {})
    bare = cell_step(four_chip_mesh)
    assert hlo_audit.asynchronous_share(
        hlo_audit.reduction_schedule(bare.as_text())) == 0
    assert (
        cell_compiled.memory_analysis().temp_size_in_bytes
        <= CELL_TEMP_BYTES_PR31
        <= 1.03 * CELL_BARE_TEMP_BYTES_PR31
    )
    assert (
        bare.memory_analysis().temp_size_in_bytes
        <= CELL_BARE_TEMP_BYTES_PR31
    )


def test_meshless_model_on_four_chips_is_refused_with_the_remedy(
        four_chip_mesh, compiled_kernel):
    """Without the mesh the model cannot wrap the kernel in a shard_map,
    and the chip's compiler refuses the step ("Mosaic kernels cannot be
    automatically partitioned"). The program says so first, and how to
    fix it."""
    with pytest.raises(ValueError, match=r"ShardingConfig\(mesh="):
        lm_trainer(four_chip_mesh, ShardingConfig())


# --- the latent-attention / routed-expert model's kernels and step ----------
# `kanana-2-30b-a3b.seq8k.1chip` (PR 33): q and k 192 wide, v 128, one
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


def test_latent_moe_cell_step_fits_one_chip(topo, compiled_kernel):
    """The whole training step of the cell at its own sizes (1 dense + 5
    expert layers, 687.5 M parameters, 8,192 tokens): it compiles, every
    layer runs the two flash kernels and every expert layer the six
    grouped matmuls, and state + temporaries stay under the 15.0 GB that
    chose the depth (14.62 when it was chosen; 1 + 6 layers read 16.00)."""
    from horovod_tpu.models.latent_moe_lm import LatentMoELM
    from horovod_tpu.ops import grouped_matmul as gm
    from horovod_tpu.ops import segment_sum as seg

    mesh = mesh_lib.build_mesh(
        mesh_lib.MeshSpec(data=1), devices=topo.devices[:1])
    model = LatentMoELM(
        vocab_size=16032, d_model=2048, n_layers=6, n_dense_layers=1,
        dense_width=6144, n_heads=32, qk_nope_dim=128, qk_rope_dim=64,
        v_dim=128, kv_rank=512, n_routed=128, experts_per_token=6,
        expert_width=768, shared_width=1536, routed_scaling=2.448,
        n_held=16, held_start=0, rope_base=1e6,
        compute_dtype=jnp.bfloat16, fused_head_chunks=HEAD_CHUNKS,
        sharding=ShardingConfig(mesh=mesh))
    trainer = hvt.Trainer(
        model, hvt.DistributedOptimizer(optax.adamw(1e-4)), loss="module",
        mesh=mesh)
    trainer._metric_names = (
        "moe_held_rows_share", "moe_load_max_over_mean", "moe_overflow_rows")
    compiled = compiled_step(trainer, seq=8192, batch=1)
    assert kernel_names(compiled) == sorted(
        FUSED * 6 + [gm.KERNEL] * 20 + [gm.KERNEL_DW] * 10
        + [seg.KERNEL] * 10)
    memory = compiled.memory_analysis()
    state = 687_502_336 * 12
    assert state <= memory.argument_size_in_bytes <= state + 1_000_000
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print(f"latent cell step: arguments + temporaries {total / 1e9:.3f} GB")
    assert total < 15.0e9


# --- the hybrid linear-attention / routed-expert model's scan and step -------
# `solar-open2-250b.seq8k.1chip` (PR 35): 8 held heads of 128 over one
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


def test_hybrid_moe_cell_step_fits_one_chip(topo, compiled_kernel):
    """The whole training step of the cell at its own sizes (one period:
    softmax, KDA, KDA, KDA; 840.9 M parameters, 8,192 tokens): it compiles,
    the softmax layer runs the two flash kernels and every layer the six
    grouped matmuls at the new shapes, the mixers' scopes are in the
    program, the gauges say what was built, and state + temporaries stay
    under 15.0 GB (14.89 when it was written; 15.02 before the mixers'
    elementwise stretches were recomputed in the backward pass)."""
    from horovod_tpu.models import hybrid_moe_lm as hybrid
    from horovod_tpu.obs import prom
    from horovod_tpu.ops import delta_rule
    from horovod_tpu.ops import grouped_matmul as gm
    from horovod_tpu.ops import segment_sum as seg

    mesh = mesh_lib.build_mesh(
        mesh_lib.MeshSpec(data=1), devices=topo.devices[:1])
    model = hybrid.HybridMoELM(
        vocab_size=24576, d_model=4096,
        layer_kinds=(hybrid.SOFTMAX,) + (hybrid.LINEAR,) * 3, head_dim=128,
        linear_heads=64, softmax_heads=64, softmax_kv_heads=8,
        n_held_heads=8, held_heads_start=0, conv_size=4, low_rank=128,
        kda_chunk=64, n_routed=320, experts_per_token=8, expert_width=1280,
        shared_width=1280, routed_scaling=1.0, n_held=8, held_start=0,
        eps=1e-5, compute_dtype=jnp.bfloat16, fused_head_chunks=HEAD_CHUNKS,
        sharding=ShardingConfig(mesh=mesh))
    trainer = hvt.Trainer(
        model, hvt.DistributedOptimizer(optax.adamw(1e-4)), loss="module",
        mesh=mesh)
    trainer._metric_names = (
        "moe_held_rows_share", "moe_load_max_over_mean", "moe_overflow_rows")
    compiled = compiled_step(trainer, seq=8192, batch=1)
    assert kernel_names(compiled) == sorted(
        FUSED + [gm.KERNEL] * 16 + [gm.KERNEL_DW] * 8 + [seg.KERNEL] * 8
        + [delta_rule.KERNEL_FWD] * 6 + [delta_rule.KERNEL_BWD] * 9)
    hlo = compiled.as_text()
    for scope in (hybrid.KDA_PROJ, hybrid.KDA_CONV, hybrid.KDA_SCAN,
                  hybrid.KDA_OUT, hybrid.GQA_SCOPE):
        assert "jvp(HybridMoELM)/Block_" in hlo and scope in hlo, scope
        assert re.search(
            rf"transpose\(jvp\(HybridMoELM\)\)/Block_\d/mixer/{scope}", hlo
        ), scope
    gauges = prom.render()
    assert 'hvt_layer_kinds{kind="linear"} 3' in gauges
    assert 'hvt_layer_kinds{kind="softmax"} 1' in gauges
    assert 'hvt_held_heads{mixer="linear"} 8' in gauges
    assert "hvt_kda_chunks 128" in gauges
    assert 'hvt_kda_scan{impl="pallas"} 1' in gauges
    memory = compiled.memory_analysis()
    state = 840_871_320 * 12
    assert state <= memory.argument_size_in_bytes <= state + 1_000_000
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print(f"hybrid cell step: arguments + temporaries {total / 1e9:.3f} GB")
    assert total < 15.0e9


# --- the state-space / routed-expert model's scan and step ---------------------
# `granite-4.0-h-small.seq4k.1chip` (PR 39): 16 held Mamba-2 heads of 64 with a
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


def test_ssm_moe_cell_step_fits_one_chip(topo, compiled_kernel):
    """The whole training step of the cell as the benchmark builds it (its
    family's `build` under the cell's trainer keys; one period: five
    Mamba-2, attention, four Mamba-2; 1,126.7 M parameters, 4,096 tokens,
    every block rematerialised): it compiles, the attention layer runs the
    forward flash kernel twice and the backward one once, every layer the
    grouped matmuls eight times (the forward's two once more), the mixer's
    scopes are in the program in all three passes, the gauges say what was
    built, and state + temporaries stay under 16.4 GB of the chip's 16.9
    (16.243 when it was written: 13.521 of state, 2.722 of temporaries, of
    which ten layers' bf16 expert-weight gradients held for the AdamW
    passes the compiler schedules last are 1.5)."""
    import pathlib

    from chipbench import run
    from horovod_tpu.models import hybrid_moe_lm as hybrid
    from horovod_tpu.obs import prom
    from horovod_tpu.ops import grouped_matmul as gm
    from horovod_tpu.ops import segment_sum as seg

    root = pathlib.Path(__file__).resolve().parents[1]
    cell = run.load_cell(root, "granite-4.0-h-small.seq4k.1chip")
    trainer = run.build_trainer(cell, topo.devices[:1], 7)
    trainer._metric_names = (
        "moe_held_rows_share", "moe_load_max_over_mean", "moe_overflow_rows")
    assert trainer.module.remat and trainer.module.tied_head
    compiled = compiled_step(trainer, seq=4096, batch=1)
    assert kernel_names(compiled) == sorted(
        [fa.KERNEL_FWD] * 2 + [fa.KERNEL_BWD]
        + [gm.KERNEL] * 60 + [gm.KERNEL_DW] * 20 + [seg.KERNEL] * 20)
    hlo = compiled.as_text()
    for scope in (hybrid.SSM_PROJ, hybrid.SSM_CONV, hybrid.SSM_SCAN,
                  hybrid.SSM_OUT):
        for path in (r"jit\(train_step\)/jvp\(HybridMoELM\)/",
                     "checkpoint/rematted_computation/", "checkpoint/"):
            assert re.search(rf"{path}Block_\d/mixer/{scope}", hlo), (
                path, scope)
    gauges = prom.render()
    assert 'hvt_layer_kinds{kind="ssm"} 9' in gauges
    assert 'hvt_layer_kinds{kind="softmax"} 1' in gauges
    assert 'hvt_held_heads{mixer="ssm"} 16' in gauges
    assert 'hvt_held_heads{mixer="softmax"} 4' in gauges
    assert "hvt_ssd_chunks 16" in gauges
    assert 'hvt_moe_gate{scoring="softmax"} 1' in gauges
    assert "hvt_remat_blocks 10" in gauges and "hvt_tied_head 1" in gauges
    memory = compiled.memory_analysis()
    state = cell["config"]["n_parameters"] * 12
    assert state == 1_126_717_104 * 12
    assert state <= memory.argument_size_in_bytes <= state + 1_000_000
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print(f"ssm cell step: arguments + temporaries {total / 1e9:.3f} GB")
    assert total < 16.4e9


# --- the window / full softmax stack's step ----------------------------------
# `laguna-xs.2.seq8k.1chip` (PR 41): three sliding-window layers (64 heads,
# window 512) and two full ones (48 heads, partial YaRN rotary) over 8 K/V
# heads of 128, a dense layer 0, 256-way routing onto 32 held experts of
# 2,048 x 512, every block rematerialised, 8,192 tokens.

def test_window_moe_cell_step_fits_one_chip(topo, compiled_kernel):
    """The whole training step of the cell as the benchmark builds it: it
    compiles, every layer runs the forward flash kernel twice and the
    backward once, the window layers' three times each under the scope
    `hvt.swa` and the full layers' under none, every routed layer the
    grouped matmuls eight times, the gauges say what was built, and state
    + temporaries stay under 12.0 GB of the chip's 16.9 (11.656 when it
    was written: 9.198 of state, 2.457 of temporaries; 15.943 without the
    rematerialisation)."""
    import pathlib

    from chipbench import run
    from horovod_tpu.models import hybrid_moe_lm as hybrid
    from horovod_tpu.obs import prom
    from horovod_tpu.ops import grouped_matmul as gm
    from horovod_tpu.ops import segment_sum as seg

    root = pathlib.Path(__file__).resolve().parents[1]
    cell = run.load_cell(root, "laguna-xs.2.seq8k.1chip")
    trainer = run.build_trainer(cell, topo.devices[:1], 7)
    trainer._metric_names = (
        "moe_held_rows_share", "moe_load_max_over_mean", "moe_overflow_rows")
    assert trainer.module.remat and trainer.module.n_dense_layers == 1
    compiled = compiled_step(trainer, seq=8192, batch=1)
    assert kernel_names(compiled) == sorted(
        [fa.KERNEL_FWD] * 10 + [fa.KERNEL_BWD] * 5
        + [gm.KERNEL] * 24 + [gm.KERNEL_DW] * 8 + [seg.KERNEL] * 8)
    scopes = re.findall(
        r"%hvt_flash_(?:fwd|bwd)[.\d]* = .*?op_name=\"([^\"]*)\"",
        compiled.as_text())
    assert len(scopes) == 15
    windowed = [s for s in scopes if f"/{hybrid.SWA_SCOPE}/" in s]
    assert len(windowed) == 9 and all(
        re.search(r"/Block_[123]/mixer/", s) for s in windowed)
    assert not any(hybrid.GQA_SCOPE in s for s in scopes)
    gauges = prom.render()
    assert 'hvt_layer_kinds{kind="window"} 3' in gauges
    assert 'hvt_layer_kinds{kind="softmax"} 2' in gauges
    assert 'hvt_held_heads{mixer="window"} 64' in gauges
    assert 'hvt_held_heads{mixer="softmax"} 48' in gauges
    assert "hvt_attn_window 512" in gauges
    assert 'hvt_rotary_dims{kind="softmax"} 64' in gauges
    assert 'hvt_rotary_dims{kind="window"} 128' in gauges
    memory = compiled.memory_analysis()
    state = cell["config"]["n_parameters"] * 12
    assert state <= memory.argument_size_in_bytes <= state + 1_000_000
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print(f"window cell step: arguments + temporaries {total / 1e9:.3f} GB")
    assert total < 12.0e9
