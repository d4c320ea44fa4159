"""Tests of chipbench/spans.py: the readers of what the program names.

The reduction is checked on a cut recorded from PR 26's first traced v5e
run (trace_cut_spans.json: four step programs of cell 1 with their scope
paths and the ``hvt.*`` host spans; the expected numbers were worked out
by a separate brute-force script, with another decoder, when it was
recorded) and on cases small enough to work out by hand. The adapter is
checked against `jax.profiler.ProfileData` on a trace taken here.
"""

import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from chipbench import reduce, spans

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
READERS = sorted(
    p.stem for p in (ROOT / "chipbench" / "layer_metrics").glob("*.json")
    if json.loads(p.read_text())["reader"].startswith("spans.py:"))


@pytest.fixture(scope="module")
def cut():
    return json.loads((HERE / "trace_cut_spans.json").read_text())


def context(cut, **changes):
    """What `run.traced_context` would hand the readers, with the cut in
    the place of the file `spans.trace_of` would read."""
    rows = [tuple(r) for r in cut["rows"]]
    said = []
    ctx = {
        "rows": rows, "chips": reduce.chips_from_rows(rows),
        "kernel_work": {
            "flash": (0.0, 0.0, 3 * cut["n_layers"]),
            **{f"flash_{k}": (0.0, 0.0, cut["n_layers"])
               for k in ("fwd", "dq", "dkv")}},
        "say": lambda **fields: said.append(fields), "said": said,
        "spans": {"scopes": dict(cut["scopes"]),
                  "host": [tuple(s) for s in cut["host"]]},
    }
    ctx.update(changes)
    return ctx


def read_metric(name, ctx):
    return getattr(spans, name)(ctx)


def test_every_new_metric_has_a_reader_and_an_entry():
    assert len(READERS) == 9
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name in READERS:
        assert callable(getattr(spans, name))
        assert entries[name]["source"] == "device_trace"
        assert entries[name]["better"] == "lower"
    # Appended, in their place after the ten that were there.
    assert [m["name"] for m in BENCH["per_layer"]][:10] == [
        "step_gap_ms", "step_gap_ms_max", "step_device_ms", "step_temp_gb",
        "mfu", "flash_ms_per_step", "flash_roofline", "device_idle_share",
        "collective_ms_per_step", "exposed_collective_ms_per_step"]


@pytest.mark.parametrize("name", [
    "input_wait_ms_per_step", "input_produce_ms_per_step",
    "host_loop_ms_per_step", "unattributed_device_share"])
def test_reader_on_the_recorded_cut(cut, name):
    assert read_metric(name, context(cut)) == pytest.approx(
        cut["expected"][name])


@pytest.mark.parametrize("name,phase", [
    ("head_ce_ms_per_step", "head + CE"),
    ("optimizer_ms_per_step", "optimizer")])
def test_scope_reader_on_the_recorded_cut(cut, name, phase):
    assert read_metric(name, context(cut)) == pytest.approx(
        cut["expected"]["phase_ms"][phase])


@pytest.mark.parametrize("which", ["fwd", "dq", "dkv"])
def test_kernel_reader_on_the_recorded_cut(cut, which):
    ctx = context(cut)
    want = cut["expected"]["kernel_ms_per_step"][which]
    assert read_metric(f"flash_{which}_ms_per_step", ctx) == (
        pytest.approx(want))
    chip, = ctx["chips"]
    assert reduce.flash_kernel_ms_per_step(chip, which)[1] == (
        cut["expected"]["kernels_per_step"][which])
    # A model of another depth: the events are not what they are taken for.
    ctx["kernel_work"][f"flash_{which}"] = (0.0, 0.0, cut["n_layers"] + 1)
    assert read_metric(f"flash_{which}_ms_per_step", ctx) is None
    # A family that counts no such kernel: nothing to read.
    del ctx["kernel_work"][f"flash_{which}"]
    assert read_metric(f"flash_{which}_ms_per_step", ctx) is None


def test_the_three_kernels_are_the_flash_metric_that_was_there(cut):
    ctx = context(cut)
    three = sum(read_metric(f"flash_{k}_ms_per_step", ctx)
                for k in ("fwd", "dq", "dkv"))
    assert three == pytest.approx(reduce.flash_ms_per_step(ctx))


def test_a_mosaic_call_of_another_name_is_not_flash(cut):
    """An expert layer's grouped matmul, say: one leaf op of every step of
    the cut (the cast of the head's kernel, ``%copy.628``, 0.96 ms) takes
    the name and target a `pallas_call(name="hvt_grouped_matmul")` would
    have. It lands in "other kernels" with all of its time, the flash
    readings stay where they were (the readers that counted every Mosaic
    call would have found 37 a step for 36 and read nothing), and the
    phases still sum to the busy time."""
    old, = {r[2] for r in cut["rows"] if r[2].startswith("%copy.628 = ")}
    new = ("%hvt_grouped_matmul.1 = bf16[2048,50257]{1,0} custom-call("
           "f32[2048,50257]{0,1} %p), " + reduce.KERNEL_MARK)
    rows = [tuple(new if v == old else v for v in r) for r in cut["rows"]]
    planted = context(cut, rows=rows, chips=reduce.chips_from_rows(rows))
    planted["spans"]["scopes"] = {
        new if line == old else line: path
        for line, path in cut["scopes"].items()}
    clean = context(cut)
    chip, = planted["chips"]
    mosaic_calls = sum(reduce.KERNEL_MARK in n for n, _, _ in chip.ops)
    assert mosaic_calls / len(chip.steps) == 3 * cut["n_layers"] + 1
    flash = ["flash_ms_per_step", "flash_roofline"]
    kernels = [f"flash_{k}_ms_per_step" for k in ("fwd", "dq", "dkv")]
    for ctx in (clean, planted):  # a roofline needs work and a device
        ctx["kernel_work"]["flash"] = (1e12, 1e9, 3 * cut["n_layers"])
        ctx["device_kind"] = "TPU v5 lite"
    for name in flash:
        assert getattr(reduce, name)(planted) == getattr(reduce, name)(clean)
    for name in kernels:
        assert read_metric(name, planted) == read_metric(name, clean)
    assert reduce.flash_ms_per_step(planted) == pytest.approx(
        cut["expected"]["phase_ms"]["flash"])
    before = spans.phase_ms(clean["chips"][0], clean["spans"]["scopes"])
    after = spans.phase_ms(chip, planted["spans"]["scopes"])
    moved = after["other kernels"]
    assert before["other kernels"] == 0.0 and moved == pytest.approx(
        sum(r[4] for r in cut["rows"] if r[2] == old) / 1e6 / 4, rel=0.01)
    was = spans.phase_of(old, cut["scopes"])
    assert after == pytest.approx(
        before | {"other kernels": moved, was: before[was] - moved})
    assert sum(after.values()) == pytest.approx(
        chip.busy_ns() / 1e6 / len(chip.steps), rel=1e-9)
    assert chip.busy_ns() == clean["chips"][0].busy_ns()
    spans.unattributed_device_share(planted)
    by_scope = planted["said"][-1]["by_scope"]
    assert ["other kernels", "hvt_grouped_matmul"] in [r[:2] for r in by_scope]


def test_phase_table_sums_to_the_busy_time_and_is_printed(cut):
    ctx = context(cut)
    chip, = ctx["chips"]
    table = spans.phase_ms(chip, ctx["spans"]["scopes"])
    assert set(table) == set(spans.PHASES)
    want = dict.fromkeys(spans.PHASES, 0.0) | cut["expected"]["phase_ms"]
    assert table == pytest.approx(want)
    busy = chip.busy_ns() / 1e6 / len(chip.steps)
    assert busy == pytest.approx(cut["expected"]["busy_ms_per_step"])
    assert sum(table.values()) == pytest.approx(busy, rel=1e-9)
    spans.unattributed_device_share(ctx)
    said, by_scope = ctx["said"]
    rows = by_scope["by_scope"]
    assert [r[:2] for r in rows[:2]] == [
        ["blocks backward", "_mlp"], ["flash", ""]]
    assert rows[1][2] == pytest.approx(table["flash"])
    assert ["head + CE", "backward"] in [r[:2] for r in rows]
    for phase in spans.PHASES:  # rows under the floor are left out
        assert sum(r[2] for r in rows if r[0] == phase) <= table[phase] + 1e-9
    assert all(sum(r[3].values()) <= r[2] + 1e-9 for r in rows)
    assert said["phase_ms"] == table
    assert said["phase_ms_sum"] == pytest.approx(said["busy_ms_per_step"])
    assert said["unattributed_families"][0][0] == "copy"
    assert sum(ms for _, ms in said["unattributed_families"]) == (
        pytest.approx(table["unattributed"], rel=0.01))
    # The real gap (5 us) falls in no recorded span: the loop's thread is
    # in the benchmark's last callback, which ends after the trace does.
    assert said["host_gaps"] == [
        ["no program span",
         pytest.approx(cut["expected"]["gap_ns"] / 1e9 / 2)]]


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_none_when_the_program_names_nothing(cut, name):
    """A parent commit: the same device events, no scope of the program's,
    no kernel name, no host span."""
    rows = [(p, l, n.replace("hvt_flash_", "Block_"), s, d)
            for p, l, n, s, d in (tuple(r) for r in cut["rows"])]
    scopes = {n.replace("hvt_flash_", "Block_"):
              path.replace("/hvt.head_ce", "").replace("/hvt.optimizer", "")
              for n, path in cut["scopes"].items()}
    ctx = context(cut, rows=rows, chips=reduce.chips_from_rows(rows),
                  spans={"scopes": scopes, "host": []})
    value = read_metric(name, ctx)
    if name == "unattributed_device_share":
        # The flax scopes were there before: it reads, and reads more.
        assert value > cut["expected"][name]
    else:
        assert value is None
    # ... and with no trace to read at all, every reader says nothing.
    ctx["spans"] = {"scopes": {}, "host": []}
    assert read_metric(name, ctx) is None


def test_host_spans_pair_with_step_programs_by_their_order(cut):
    ctx = context(cut)
    chip, = ctx["chips"]
    host = ctx["spans"]["host"]
    t0, t1, steps = spans.host_window(ctx["rows"], chip, host)
    calls = [s for s in host if s[1] == "hvt.step"]
    assert steps == len(chip.steps) == 2 and len(calls) == 4
    assert (t0, t1) == (calls[0][2] + calls[0][3], calls[2][2] + calls[2][3])
    # The loop ran ahead: its window ends before the device's stretch starts.
    assert t1 < chip.t0
    # One hvt.step short of the step programs: no pairing, no metric.
    assert spans.host_window(ctx["rows"], chip, [
        s for s in host if s is not calls[-1]]) is None
    loop = spans.loop_thread(host)
    assert {s[1] for s in host if s[0] == loop} == set(spans.LOOP_SPANS)
    assert {s[1] for s in host if s[0] != loop} == {
        "hvt.input.assemble", "hvt.input.place", "hvt.input.queue_full"}


def test_host_gaps_name_the_innermost_span_that_covers_the_gap():
    """Two steps of 10 ms with a 3 ms gap between them. The loop's thread
    is inside hvt.input_wait (2 ms, covering the gap's middle) inside a
    longer span; the prefetch thread's span covers it too and is not
    asked."""
    dev, mod, ms = "/device:TPU:0", "jit_step(1)", 1e6
    starts = (-13 * ms, 0.0, 13 * ms, 26 * ms)
    rows = [(dev, reduce.MODULES, mod, s, 10 * ms) for s in starts]
    rows += [(dev, reduce.OPS, "%fusion.1 = f32[] fusion(), kind=kLoop",
              s, 10 * ms) for s in starts]
    chip, = reduce.chips_from_rows(rows)
    assert chip.gaps_ns() == [3 * ms]
    host = [
        (1, "hvt.outer", 5 * ms, 20 * ms),
        (1, "hvt.input_wait", 10.5 * ms, 2 * ms),
        (1, "hvt.step", 12.6 * ms, 0.2 * ms),
        (0, "hvt.input.assemble", 11 * ms, 1 * ms),
    ]
    assert spans.host_gaps(chip, host) == [
        ["hvt.input_wait", pytest.approx(3e-3 / 2)]]
    assert spans.host_gaps(chip, host[2:]) == [
        ["no program span", pytest.approx(3e-3 / 2)]]


@pytest.mark.parametrize("op_name,path,phase", [
    ("jit(train_step)/hvt.optimizer/add", "hvt.optimizer", "optimizer"),
    ("jit(train_step)/add", "", "unattributed"),
    ("reduce_sum", "", "unattributed"),
    ("jit(train_step)/transpose(jvp(TransformerLM))/lm_head.fused_loss/"
     "hvt.head_ce/while/body/closed_call/dot_general",
     "transpose(jvp(TransformerLM))/lm_head.fused_loss/hvt.head_ce/while/"
     "body/closed_call", "head + CE"),
    ("jit(train_step)/transpose(jvp(TransformerLM))/Block_3/qkv/dot_general",
     "transpose(jvp(TransformerLM))/Block_3/qkv", "blocks backward"),
    ("jit(train_step)/jvp(TransformerLM)/Block_3/qkv/dot_general",
     "jvp(TransformerLM)/Block_3/qkv", "blocks forward"),
    ("jit(train_step)/jvp(TransformerLM)/Embed_0/jit(_take)/gather",
     "jvp(TransformerLM)/Embed_0/jit(_take)", "other named"),
])
def test_scope_path_and_phase_by_hand(op_name, path, phase):
    line = "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    assert spans.scope_path(op_name) == path
    assert spans.phase_of(line, {line: op_name}) == phase


@pytest.mark.parametrize("op_name,sub", [
    ("jit(f)/transpose(jvp(TransformerLM))/Block_3/Block_3._mlp/mlp_up/dot_general", "_mlp"),
    ("jit(f)/jvp(TransformerLM)/Block_11/qkv/dot_general", "qkv"),
    ("jit(f)/jvp(TransformerLM)/Block_0/transpose", ""),
    ("jit(f)/jvp(TransformerLM)/Embed_0/jit(_take)/gather", "Embed_0"),
    ("jit(f)/add", ""),
])
def test_sub_scope_by_hand(op_name, sub):
    assert spans.sub_scope(op_name) == sub


def test_phase_precedence_by_hand():
    head = "jit(f)/jvp(M)/hvt.head_ce/while/body/all-gather"
    gather = "%all-gather.3 = bf16[8]{0} all-gather(bf16[2]{0} %p)"
    kernel = ('%hvt_flash_dq.4 = bf16[2]{0} custom-call(bf16[2]{0} %p), '
              + reduce.KERNEL_MARK)
    bare = kernel.replace("%hvt_flash_dq.4", "%transpose_jvp_hvt_flash_dq__.1")
    # A collective the partitioner put inside the head is the head's; one
    # that no scope of the program claims is a collective.
    assert spans.phase_of(gather, {gather: head}) == "head + CE"
    assert spans.phase_of(gather, {}) == "collectives"
    # ... told by its opcode: a shard_map's psum is an all-reduce by another
    # name, and an op that only reads a collective's result is not one.
    psum = "%psum.1 = f32[8]{0} all-reduce(f32[8]{0} %p), to_apply=%add"
    reader = "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %all-reduce.3), kind=kLoop"
    assert spans.phase_of(psum, {psum: head}) == "head + CE"
    assert spans.phase_of(psum, {}) == "collectives"
    assert spans.phase_of(reader, {}) == "unattributed"
    assert spans.phase_of(kernel, {kernel: head}) == "flash"
    assert reduce.flash_kernel_of(kernel) == reduce.flash_kernel_of(bare) == "dq"
    assert reduce.flash_kernel_of(gather) is None
    assert reduce.flash_kernel_of("%hvt_flash_dq.4 = fusion()") is None
    # A Mosaic call of another name is a kernel, and not flash.
    other = kernel.replace("%hvt_flash_dq.4", "%hvt_grouped_matmul.4")
    assert reduce.flash_kernel_of(other) is None
    # ... and so is one whose name only begins, or ends, like a flash
    # kernel's: the name is matched whole, under the transformations'
    # prefixes and without the instruction's number.
    for name in ("%hvt_flash_dq_ring.4", "%hvt_flash_dq2.4", "%hvt_flash_d.4",
                 "%ring_hvt_flash_dqx.4"):
        assert reduce.flash_kernel_of(
            kernel.replace("%hvt_flash_dq.4", name)) is None, name
    for name in ("%hvt_flash_dq", "%hvt_flash_dq.4.1", "%jvp_hvt_flash_dq_.7"):
        assert reduce.flash_kernel_of(
            kernel.replace("%hvt_flash_dq.4", name)) == "dq", name
    assert reduce.flash_kernel_of(
        kernel.replace("%hvt_flash_dq.4", "%hvt_flash_dkv.4")) == "dkv"
    assert spans.phase_of(other, {other: head}) == "other kernels"


def test_adapter_agrees_with_profile_data_on_a_trace_taken_here(tmp_path):
    """`spans.read` decodes the file's wire format itself; the host side
    is checked against jax's own reader (a CPU trace has no TPU plane, so
    the scope side is checked by the recorded cut, which another decoder
    wrote)."""
    from jax.profiler import ProfileData

    from horovod_tpu import trace

    root = tmp_path / ".chipbench_out" / "cell" / "profile"
    jax.profiler.start_trace(str(root))
    with trace.span("outer", epoch=1):
        for _ in range(3):
            with trace.span("inner"):
                jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    path = spans.newest_trace(tmp_path)
    assert path is not None and path.name.endswith(".xplane.pb")
    got = spans.read(path)
    assert got["scopes"] == {}
    want = sorted(
        (e.name, e.start_ns, e.duration_ns)
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name == reduce.HOST_PLANE
        for line in plane.lines for e in line.events
        if e.name.startswith("hvt."))
    assert [n for n, _, _ in want] == ["hvt.inner"] * 3 + ["hvt.outer"]
    mine = sorted((n, s, d) for _, n, s, d in got["host"])
    assert [n for n, _, _ in mine] == [n for n, _, _ in want]
    assert [t for _, *t in mine] == [pytest.approx(t) for _, *t in want]
    assert len({t for t, *_ in got["host"]}) == 1
    assert spans.newest_trace(tmp_path / "nowhere") is None
