"""Whole train steps for a DESCRIBED TPU v5e: the chip's own compiler, no chip.

The CPU suite runs the Mosaic kernels in the Pallas interpreter, which is
ordinary JAX: it cannot see what the chip's compiler makes of a step. libtpu
is installed here and compiles for a topology that is described and not
attached (`horovod_tpu/testing/described_v5e.py`; the kernels and ops alone
are compiled in `tests/test_kernel_compile.py`). Nothing runs, so nothing
here says anything about results or times. A step is checked in three ways:

* its structure — the Mosaic calls by name and count, the scopes they sit
  in, the trace-time gauges — is fixed when the step is traced:
  `test_cell_step_structure` traces each one-chip cell of BENCHMARK.json,
  built from its configuration file, and compiles nothing;
* what exists only after XLA's scheduler has run — asynchronous sums, the
  reduction table, the head's loops — is read from two toy steps and from
  the benchmark's four-chip cell, compiled once for the module;
* its size (arguments + temporaries) only a compile can tell, and the
  chip's ``peak_hbm_gb`` bounds the same number in every cell on every
  change: `test_cell_step_fits_the_chip` and the bare four-chip step are
  in the slow tier. Run ``pytest -m slow tests/test_chip_compile.py``
  before a chip run that adds a cell or grows a step.

Two processes can describe the topology and compile at once only with
``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` (the tier-1 command sets it, and its
xdist workers take this file and `tests/test_kernel_compile.py` side by
side); without it libtpu refuses the second process at once, and that
process's compile tests skip with libtpu's reason.
"""

import pathlib
import re

import jax
import jax.numpy as jnp
import optax
import pytest

import horovod_tpu as hvt
from chipbench import run
from horovod_tpu.analysis import hlo_audit
from horovod_tpu.models import hybrid_moe_lm as hybrid
from horovod_tpu.models.transformer import ShardingConfig, TransformerLM
from horovod_tpu.obs import core as obs_core
from horovod_tpu.obs import prom
from horovod_tpu.ops import delta_rule
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import fused_ce
from horovod_tpu.ops import grouped_matmul as gm
from horovod_tpu.ops import segment_sum as seg
from horovod_tpu.parallel import mesh as mesh_lib
from horovod_tpu.testing.described_v5e import (
    kernel_calls, kernel_names, step_args, trace_step, traced_kernels,
    traced_scopes,
)
from horovod_tpu.training import trainer as trainer_lib

ROOT = pathlib.Path(__file__).resolve().parents[1]
# The Mosaic calls of a flash forward + backward (`fa.fused_backward`).
FUSED = sorted([fa.KERNEL_FWD, fa.KERNEL_BWD])
# What the routed layers sow (`chipbench.run.init_state` reads the names
# off initialised variables, which a described chip cannot hold).
ROUTED_METRICS = (
    "moe_held_rows_share", "moe_load_max_over_mean", "moe_overflow_rows")


def cell_trainer(name: str, devices):
    """(cell, trainer): a cell of BENCHMARK.json as `chipbench.run` builds
    it from its configuration file, on as many of ``devices`` as it takes."""
    cell = run.load_cell(ROOT, name)
    trainer = run.build_trainer(cell, devices[:cell["chips"]], 7)
    if cell["config"]["family"] != "dense_lm":
        trainer._metric_names = ROUTED_METRICS
    return cell, trainer


def step_shape(cell) -> tuple[int, int]:
    """(sequence length, global batch) of the cell's step."""
    return cell["traffic"]["seq_len"], cell["traffic"]["global_batch"]


def compiled_cell_step(name: str, devices):
    cell, trainer = cell_trainer(name, devices)
    return trace_step(trainer, *step_shape(cell)).lower().compile()


# --- one whole train step on the four described chips ----------------------

D_MODEL, HEADS, SEQ, VOCAB, GLOBAL_BATCH = 2048, 16, 1024, 8192, 8
HEAD_CHUNKS = 8


def lm_trainer(mesh, sharding):
    model = TransformerLM(
        vocab_size=VOCAB, d_model=D_MODEL, n_heads=HEADS, n_layers=2,
        dropout=0.0, compute_dtype=jnp.bfloat16,
        fused_head_chunks=HEAD_CHUNKS,
        sharding=sharding,
    )
    return hvt.Trainer(
        model, hvt.DistributedOptimizer(optax.adamw(1e-4)),
        loss="module", mesh=mesh,
    )


@pytest.fixture
def four_chip_mesh(topo):
    return mesh_lib.build_mesh(
        mesh_lib.MeshSpec(data=4), devices=topo.devices
    )


def compiled_step(trainer, seq=SEQ, batch=GLOBAL_BATCH):
    """The Trainer's own jitted step, so the compile sees the options the
    program passes and not a copy of them."""
    return trace_step(trainer, seq, batch).lower().compile()


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
        step_args(trainer, SEQ, GLOBAL_BATCH)[0].params)
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


def test_meshless_model_on_four_chips_is_refused_with_the_remedy(
        four_chip_mesh, compiled_kernel):
    """Without the mesh the model cannot wrap the kernel in a shard_map,
    and the chip's compiler refuses the step ("Mosaic kernels cannot be
    automatically partitioned"). The program says so first, and how to
    fix it."""
    with pytest.raises(ValueError, match=r"ShardingConfig\(mesh="):
        lm_trainer(four_chip_mesh, ShardingConfig())


# --- the benchmark's four-chip cell, at its own sizes -----------------------
# `cerebras-gpt-1.3b.seq2k.dp4`, built from its configuration file: 12
# layers of d2048, vocabulary 50,257, sequences of 2,048, 2 a chip. Where
# the chip is nearly full is where overlap is paid for in memory, so the
# guard on that stands here. Its sums and its tables exist only after XLA's
# scheduler has run, so this is the one cell compiled in tier-1.

DP4 = "cerebras-gpt-1.3b.seq2k.dp4"
CELL_LAYERS, CELL_SEQ, CELL_VOCAB = 12, 2048, 50257


@pytest.fixture(scope="module")
def cell_compiled(topo):
    steer = pytest.MonkeyPatch()
    steer.setattr(fa, "default_interpret", lambda: False)
    try:
        yield compiled_cell_step(DP4, topo.devices)
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


@pytest.mark.slow
def test_overlapped_sums_cost_the_cell_step_no_memory(
        cell_compiled, topo, compiled_kernel, monkeypatch):
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
    that commit, and the bare step is no larger than it was then either.

    Slow: it is a second full XLA:TPU compile of the cell's step, and the
    chip's ``peak_hbm_gb`` bounds the optioned step's size in this cell on
    every change. Run it before a chip run that grows the four-chip step."""
    monkeypatch.setattr(
        trainer_lib, "training_compiler_options", lambda mesh: {})
    bare = compiled_cell_step(DP4, topo.devices)
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


# --- the benchmark's one-chip cells: structure from the traced step ---------
# A row a one-chip cell of BENCHMARK.json: the Mosaic calls by name, the
# module's attributes, scopes some operation of the step is traced under
# (each a regex), counts of the Mosaic calls whose path matches a regex,
# and trace-time gauges.

def structure(name, census, *, module=None, scopes=(), kernel_paths=None,
              gauges=()):
    return pytest.param(name, sorted(census), module or {}, scopes,
                        kernel_paths or {}, gauges, id=name)


def flash_gauges(tiles, subtiles):
    """The dense cells' gauges: the fused head and backward, and the
    census of the flash call's grid (the call `tests/test_kernel_compile.py`
    compiles at the cell's shape reads the same)."""
    return ('hvt_head_ce_scan{axis="vocab"} 1',
            'hvt_flash_backward{impl="fused"} 1',
            *(f'hvt_flash_tiles{{kind="{kind}"}} {n}'
              for kind, n in zip(("skipped", "edge", "full"), tiles)),
            *(f'hvt_flash_subtiles{{kind="{kind}"}} {n}'
              for kind, n in zip(("run_full", "run_masked", "skipped"),
                                 subtiles)))


STRUCTURE = [
    structure("cerebras-gpt-1.3b.seq2k.1chip", FUSED * 12,
              gauges=flash_gauges((1, 2, 1), (56, 16, 56))),
    structure("starcoder2-3b.seq4k.1chip", FUSED * 6,
              gauges=flash_gauges((28, 8, 28), (48, 32, 48))),
    # 1 dense + 5 expert layers, 8,192 tokens: every layer runs the two
    # flash kernels and every expert layer the six grouped matmuls.
    structure("kanana-2-30b-a3b.seq8k.1chip",
              FUSED * 6 + [gm.KERNEL] * 20 + [gm.KERNEL_DW] * 10
              + [seg.KERNEL] * 10),
    # One period (softmax, KDA, KDA, KDA): the softmax layer runs the two
    # flash kernels and every layer the six grouped matmuls, and the
    # mixers' scopes are in the forward and the backward pass.
    structure("solar-open2-250b.seq8k.1chip",
              FUSED + [gm.KERNEL] * 16 + [gm.KERNEL_DW] * 8 + [seg.KERNEL] * 8
              + [delta_rule.KERNEL_FWD] * 6 + [delta_rule.KERNEL_BWD] * 9,
              scopes=tuple(
                  rf"{path}Block_\d/mixer/{re.escape(scope)}"
                  for scope in (hybrid.KDA_PROJ, hybrid.KDA_CONV,
                                hybrid.KDA_SCAN, hybrid.KDA_OUT,
                                hybrid.GQA_SCOPE)
                  for path in (r"^jit\(train_step\)/jvp\(HybridMoELM\)/",
                               r"transpose\(jvp\(HybridMoELM\)\)/")),
              gauges=('hvt_layer_kinds{kind="linear"} 3',
                      'hvt_layer_kinds{kind="softmax"} 1',
                      'hvt_held_heads{mixer="linear"} 8',
                      "hvt_kda_chunks 128",
                      'hvt_kda_scan{impl="pallas"} 1')),
    # One period (five Mamba-2, attention, four Mamba-2), every block
    # rematerialised: the attention layer runs the forward flash kernel
    # twice and the backward one once, every layer the grouped matmuls
    # eight times (the forward's two once more), and the mixer's scopes
    # are in all three passes.
    structure("granite-4.0-h-small.seq4k.1chip",
              [fa.KERNEL_FWD] * 2 + [fa.KERNEL_BWD]
              + [gm.KERNEL] * 60 + [gm.KERNEL_DW] * 20 + [seg.KERNEL] * 20,
              module={"remat": True, "tied_head": True},
              scopes=tuple(
                  rf"{path}Block_\d/mixer/{re.escape(scope)}"
                  for scope in (hybrid.SSM_PROJ, hybrid.SSM_CONV,
                                hybrid.SSM_SCAN, hybrid.SSM_OUT)
                  for path in (r"jit\(train_step\)/jvp\(HybridMoELM\)/",
                               "checkpoint/rematted_computation/",
                               "checkpoint/")),
              gauges=('hvt_layer_kinds{kind="ssm"} 9',
                      'hvt_layer_kinds{kind="softmax"} 1',
                      'hvt_held_heads{mixer="ssm"} 16',
                      'hvt_held_heads{mixer="softmax"} 4',
                      "hvt_ssd_chunks 16",
                      'hvt_moe_gate{scoring="softmax"} 1',
                      "hvt_remat_blocks 10", "hvt_tied_head 1")),
    # Three window layers and two full ones, every block rematerialised:
    # every layer runs the forward flash kernel twice and the backward
    # once, the window layers' three times each under the scope `hvt.swa`
    # and the full layers' under none, every routed layer the grouped
    # matmuls eight times.
    structure("laguna-xs.2.seq8k.1chip",
              [fa.KERNEL_FWD] * 10 + [fa.KERNEL_BWD] * 5
              + [gm.KERNEL] * 24 + [gm.KERNEL_DW] * 8 + [seg.KERNEL] * 8,
              module={"remat": True, "n_dense_layers": 1},
              kernel_paths={
                  rf"/{re.escape(hybrid.SWA_SCOPE)}/": 9,
                  rf"/Block_[123]/mixer/{re.escape(hybrid.SWA_SCOPE)}/": 9,
                  re.escape(hybrid.GQA_SCOPE): 0},
              gauges=('hvt_layer_kinds{kind="window"} 3',
                      'hvt_layer_kinds{kind="softmax"} 2',
                      'hvt_held_heads{mixer="window"} 64',
                      'hvt_held_heads{mixer="softmax"} 48',
                      "hvt_attn_window 512",
                      'hvt_rotary_dims{kind="softmax"} 64',
                      'hvt_rotary_dims{kind="window"} 128')),
]


@pytest.mark.parametrize(
    "name,census,module,scopes,kernel_paths,gauges", STRUCTURE)
def test_cell_step_structure(topo, compiled_kernel, name, census, module,
                             scopes, kernel_paths, gauges):
    """The cell's whole training step as the benchmark builds it, traced
    and not compiled: its Mosaic calls (named as the compiled instructions
    are: `test_cell_step_fits_the_chip` checks that on the slow tier), the
    scopes they and the step's other operations sit in, and the gauges
    that say what was built."""
    cell, trainer = cell_trainer(name, topo.devices)
    for attr, value in module.items():
        assert getattr(trainer.module, attr) == value, attr
    obs_core.reset()
    kernels = traced_kernels(trainer, *step_shape(cell))
    assert [k.name for k in kernels] == census
    for pattern, count in kernel_paths.items():
        assert sum(bool(re.search(pattern, k.path))
                   for k in kernels) == count, pattern
    paths = traced_scopes(trainer, *step_shape(cell))
    for pattern in scopes:
        assert any(re.search(pattern, path) for path in paths), pattern
    rendered = prom.render().splitlines()
    for gauge in gauges:
        assert gauge in rendered, gauge
    obs_core.reset()


# --- the benchmark's routed cells: size by compile, on the slow tier -------

# A row a routed cell: its parameters and the line that chose its depth.
FITS = [
    # 14.62 GB when the depth was chosen; 1 + 6 layers read 16.00.
    ("kanana-2-30b-a3b.seq8k.1chip", 687_502_336, 15.0e9),
    # 14.89 when it was written; 15.02 before the mixers' elementwise
    # stretches were recomputed in the backward pass.
    ("solar-open2-250b.seq8k.1chip", 840_871_320, 15.0e9),
    # Of the chip's 16.9: 16.243 when it was written (13.521 of state,
    # 2.722 of temporaries, of which ten layers' bf16 expert-weight
    # gradients held for the AdamW passes the compiler schedules last are
    # 1.5).
    ("granite-4.0-h-small.seq4k.1chip", 1_126_717_104, 16.4e9),
    # 11.656 when it was written (9.198 of state, 2.457 of temporaries;
    # 15.943 without the rematerialisation).
    ("laguna-xs.2.seq8k.1chip", 766_531_584, 12.0e9),
]


@pytest.mark.slow
@pytest.mark.parametrize("name,n_parameters,limit", FITS,
                         ids=[name for name, _, _ in FITS])
def test_cell_step_fits_the_chip(topo, compiled_kernel, name, n_parameters,
                                 limit):
    """The cell's whole training step compiled for the described v5e:
    state + temporaries stay under the line that chose its depth, the
    arguments are the state (12 bytes a parameter), and the compiled
    program's Mosaic calls are the ones tier-1 reads off the trace.

    Slow: a full XLA:TPU compile of a cell (one to two minutes each), and
    the chip's ``peak_hbm_gb`` bounds the same number in every cell on
    every change. Run it before a chip run that adds a cell or grows a
    step."""
    cell, trainer = cell_trainer(name, topo.devices)
    compiled = trace_step(trainer, *step_shape(cell)).lower().compile()
    assert kernel_names(compiled) == [
        k.name for k in traced_kernels(trainer, *step_shape(cell))]
    assert cell["config"].get("n_parameters", n_parameters) == n_parameters
    memory = compiled.memory_analysis()
    state = n_parameters * 12
    assert state <= memory.argument_size_in_bytes <= state + 1_000_000
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print(f"{name} step: arguments + temporaries {total / 1e9:.3f} GB")
    assert total < limit
