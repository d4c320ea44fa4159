"""The program's names inside the profiler's own trace (ISSUE 26).

Host side: `trace.span` enters a `jax.profiler.TraceAnnotation`
``hvt.<name>`` beside its JSONL record, and the fit loop and the prefetch
thread wrap their boundaries in it. Device side: `jax.named_scope`s around
the optimizer update and the head + CE (forward AND backward rule), which
the lowered step carries in its debug locations. The always-on counters
move in the loop, exporter on or off. The flash kernels' instruction names
need the chip's compiler and live in tests/test_kernel_compile.py.
"""

import collections
import glob
import json
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvt
from horovod_tpu import obs, trace
from horovod_tpu.models.transformer import TransformerLM
from horovod_tpu.obs import core as obs_core
from horovod_tpu.obs import prom
from horovod_tpu.ops import fused_ce
from horovod_tpu.parallel import sharding as sharding_lib
from horovod_tpu.training import trainer as trainer_lib

LOOP_SPANS = ("hvt.input_wait", "hvt.step", "hvt.callbacks")
PRODUCER_SPANS = ("hvt.input.assemble", "hvt.input.place",
                  "hvt.input.queue_full")
STEPS, EPOCHS = 4, 2


class Dense(nn.Module):
    @nn.compact
    def __call__(self, x, *, train: bool = False):
        return nn.Dense(4)(x.astype("float32"))


def dense_fit(**kwargs):
    t = hvt.Trainer(Dense(), hvt.DistributedOptimizer(optax.adam(1e-3)))
    rng = np.random.RandomState(0)
    n = 8 * jax.device_count() * STEPS
    x = rng.rand(n, 8).astype(np.float32)
    y = rng.randint(0, 4, n).astype(np.int32)
    t.fit(x=x, y=y, batch_size=8, epochs=EPOCHS, verbose=0, **kwargs)
    return t


@pytest.fixture(scope="module")
def traced_fit(tmp_path_factory):
    """One streamed toy fit under the profiler with the file sink on:
    (host lines as [(name, start_ns, end_ns, stats)], JSONL records)."""
    from jax.profiler import ProfileData

    tmp = tmp_path_factory.mktemp("traced_fit")
    env = pytest.MonkeyPatch()
    env.setenv("HVT_TRACE_DIR", str(tmp / "spans"))
    env.setattr(trace, "_span_writer", trace._SpanWriter())
    jax.profiler.start_trace(str(tmp / "profile"))
    try:
        dense_fit(cache=None)
    finally:
        jax.profiler.stop_trace()
        env.undo()
    path, = glob.glob(str(tmp / "profile/plugins/profile/*/*.xplane.pb"))
    host, = (p for p in ProfileData.from_file(path).planes
             if p.name == "/host:CPU")
    lines = []
    for line in host.lines:
        events = sorted(
            (e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
            for e in line.events if e.name.startswith("hvt."))
        if events:
            lines.append([(n, s, e, st) for s, e, n, st in events])
    records = [json.loads(l) for f in glob.glob(str(tmp / "spans/*.jsonl"))
               for l in open(f)]
    return lines, records


def test_fit_puts_its_spans_on_two_threads_of_the_profilers_trace(traced_fit):
    lines, _ = traced_fit
    by_names = {frozenset(n for n, *_ in line): line for line in lines}
    assert set(by_names) == {frozenset(LOOP_SPANS), frozenset(PRODUCER_SPANS)}
    loop = by_names[frozenset(LOOP_SPANS)]
    # Per execution, in this order and nested in nothing: the wait for the
    # batch, the call into the step program, the callbacks.
    assert [n for n, *_ in loop] == list(LOOP_SPANS) * (STEPS * EPOCHS)
    assert all(a[2] <= b[1] for a, b in zip(loop, loop[1:]))
    steps = [st for n, _, _, st in loop if n == "hvt.step"]
    assert [(s["epoch"], s["step"], s["steps"]) for s in steps] == [
        (e, s, 1) for e in range(EPOCHS) for s in range(STEPS)]
    producer = by_names[frozenset(PRODUCER_SPANS)]
    count = collections.Counter(n for n, *_ in producer)
    # One more assembly than batches: the one that finds the stream ended.
    assert count["hvt.input.place"] == STEPS * EPOCHS
    assert count["hvt.input.assemble"] == STEPS * EPOCHS + 1
    assert all(a[2] <= b[1] for a, b in zip(producer, producer[1:]))


def test_span_still_writes_the_same_jsonl_record(traced_fit):
    _, records = traced_fit
    schema = {"name", "ts", "dur_s", "rank", "pid", "host", "id", "parent",
              "depth"}
    by_name = collections.defaultdict(list)
    for r in records:
        by_name[r["name"]].append(r)
    # The file sink keeps the bare names `hvt-trace` keys on.
    assert {n.removeprefix("hvt.") for n in LOOP_SPANS + PRODUCER_SPANS} == (
        set(by_name))
    assert all(set(r) == schema | {"epoch", "step", "steps"}
               for r in by_name["step"])
    assert all(set(r) == schema for r in by_name["input_wait"])
    assert len(by_name["step"]) == STEPS * EPOCHS
    assert all(r["parent"] is None and r["depth"] == 0 and r["dur_s"] >= 0
               for r in records)


def test_span_nests_in_both_sinks(tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    monkeypatch.setenv("HVT_TRACE_DIR", str(tmp_path / "spans"))
    monkeypatch.setattr(trace, "_span_writer", trace._SpanWriter())
    jax.profiler.start_trace(str(tmp_path / "profile"))
    with trace.span("commit", epoch=3):
        with trace.span("checkpoint_save", path="x"):
            pass
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "profile/plugins/profile/*/*.xplane.pb"))
    events = {e.name: e for p in ProfileData.from_file(path).planes
              for line in p.lines for e in line.events
              if e.name.startswith("hvt.")}
    outer, inner = events["hvt.commit"], events["hvt.checkpoint_save"]
    assert dict(outer.stats) == {"epoch": 3}
    assert outer.start_ns <= inner.start_ns
    assert inner.start_ns + inner.duration_ns <= (
        outer.start_ns + outer.duration_ns)
    file, = glob.glob(str(tmp_path / "spans/*.jsonl"))
    inner_r, outer_r = (json.loads(l) for l in open(file))
    assert (inner_r["name"], inner_r["parent"], inner_r["depth"]) == (
        "checkpoint_save", outer_r["id"], 1)
    assert (outer_r["name"], outer_r["epoch"]) == ("commit", 3)


@pytest.mark.parametrize("cache", [None, "device"], ids=["streamed", "device"])
def test_always_on_counters_move_with_the_exporter_off(monkeypatch, cache):
    monkeypatch.delenv("HVT_METRICS_PORT", raising=False)
    obs_core.reset()
    dense_fit(cache=cache)
    values = prom.parse_text(prom.render(obs.default_registry()))
    assert values["hvt_optimizer_steps_total"] == STEPS * EPOCHS
    waited = values.get("hvt_input_wait_seconds_total")
    if cache is None:
        assert waited is not None and waited > 0
    else:
        assert waited is None  # no host input leg on the device-cached path
    obs_core.reset()


# --- the step program's cross-chip sums (PR 37) ------------------------------

class StepCompiles:
    """Counts what JAX lowers and compiles of ``jit(train_step)`` (JAX
    keeps its listeners for the life of the process: one serves it)."""

    def __init__(self):
        self.lowered = self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, fun_name=None, **_):
        if fun_name == "jit(train_step)":
            self.lowered += event.endswith("jaxpr_to_mlir_module_duration")
            self.compiled += event.endswith("backend_compile_duration")


@pytest.fixture(scope="module")
def step_compiles():
    return StepCompiles()


def test_a_fit_that_asks_for_nothing_compiles_nothing_more(step_compiles):
    """The fit remembers its step program (shapes, one tree.map) and
    nothing is lowered, compiled or parsed for it until somebody asks: the
    step is lowered and compiled once, by its first call, as before PR 37.
    And asking costs no second compile: the program lowered from the
    remembered shapes IS the one the call made (an uncommitted argument
    goes without a sharding, as the call lowers it), so JAX hands back
    the lowering and the executable it holds; only the parse is new."""
    before = step_compiles.lowered, step_compiles.compiled
    t = dense_fit(cache=None)
    once = before[0] + 1, before[1] + 1
    assert (step_compiles.lowered, step_compiles.compiled) == once
    assert t._step_program.steps == 1 and t._step_reductions is None
    scale = t._step_program.shapes[2]
    assert scale.shape == () and scale.sharding is None
    rows = trace.step_reductions()
    assert rows and t._step_reductions is not None
    assert trace.step_reductions() == rows
    assert (step_compiles.lowered, step_compiles.compiled) == once


def test_step_reductions_is_the_newest_fits_table():
    """On the CPU mesh every gradient sum is synchronous: each names
    itself, twice, whose gradient it is, and no host."""
    t = dense_fit(cache=None)
    rows = trace.step_reductions()
    assert rows and rows == [
        trace.dataclasses.asdict(r) for r in t.step_reductions()]
    n_params = sum(p.size for p in jax.tree.leaves(t.state.params))
    # (XLA:CPU combines the sums into one, the step's scalar means with it.)
    assert 0 <= sum(r["nbytes"] for r in rows) - 4 * n_params <= 64
    for r in rows:
        assert r["kind"] == "all-reduce" and not r["asynchronous"]
        assert r["start"] == r["done"] and r["start"].startswith("all-reduce")
        # (The module's name is whatever the program that first compiled
        # this HLO called it: the compile cache's key leaves metadata out.)
        assert re.fullmatch(r"transpose\(jvp\(\w+\)\)/Dense_0", r["scope"])
        assert r["hosts"] == ()
    # An epoch program is not a step program.
    t.fit(x=np.zeros((8 * jax.device_count(), 8), np.float32),
          y=np.zeros(8 * jax.device_count(), np.int32), batch_size=8,
          cache="device", verbose=0)
    assert trace.step_reductions() is None


def test_one_chip_has_an_empty_table_and_compiles_nothing_for_it(
        step_compiles):
    mesh = hvt.build_mesh(hvt.MeshSpec(data=1), devices=jax.devices()[:1])
    t = hvt.Trainer(Dense(), hvt.DistributedOptimizer(optax.adam(1e-3)),
                    mesh=mesh)
    t.fit(x=np.zeros((16, 8), np.float32), y=np.zeros(16, np.int32),
          batch_size=8, verbose=0)
    before = step_compiles.lowered, step_compiles.compiled
    assert trace.step_reductions() == []
    assert (step_compiles.lowered, step_compiles.compiled) == before


def test_hvt_profile_leaves_the_table_beside_the_dump(tmp_path, monkeypatch):
    monkeypatch.setenv("HVT_PROFILE", str(tmp_path))
    t = dense_fit(cache=None)
    assert glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    written = json.loads((tmp_path / trace.STEP_REDUCTIONS_FILE).read_text())
    assert written == json.loads(json.dumps(trace.step_reductions()))
    assert [r["start"] for r in written] == [
        r.start for r in t.step_reductions()]


def test_no_fit_no_table_and_no_file(tmp_path, monkeypatch):
    monkeypatch.setattr(trace, "_newest_fit", lambda: None)
    assert trace.step_reductions() is None
    assert trace.write_step_reductions(str(tmp_path)) is None
    assert not list(tmp_path.iterdir())


# --- device scopes ---------------------------------------------------------

def lowered_lm_step(accumulation: int, vocab: int = 64) -> str:
    """The lowered train step of a toy LM with the fused head, with the
    debug locations that carry the name stack."""
    hvt.init()
    model = TransformerLM(vocab_size=vocab, d_model=32, n_heads=4,
                          n_layers=1, dropout=0.0, fused_head_chunks=2)
    tr = hvt.Trainer(
        model,
        hvt.DistributedOptimizer(
            optax.adamw(1e-3), backward_passes_per_step=accumulation),
        loss="module")
    n = tr.dp_size
    x = np.arange(n * accumulation * 16, dtype=np.int32).reshape(-1, 16) % vocab
    state = tr.build(x[:n], x[:n])
    if accumulation == 1:
        batch = tr._shard((x, x))
    else:
        stack = x.reshape(accumulation, n, 16)
        batch = tr._shard_chunk((stack, stack), 1)
    acc = sharding_lib.replicate(tr.zero_metrics(), tr.mesh)
    return tr._train_step.lower(
        state, batch, jnp.asarray(1.0, jnp.float32), acc
    ).as_text(debug_info=True)


@pytest.mark.parametrize("accumulation", [1, 2],
                         ids=["implicit", "accumulating"])
def test_lowered_step_names_the_optimizer_and_the_head(accumulation):
    text = lowered_lm_step(accumulation)
    named = set(re.findall(r'loc\("([^"]*hvt\.[^"]*)"', text))
    head = {n for n in named if fused_ce.SCOPE in n}
    # The head's forward scan and its backward scan (a custom_vjp's
    # backward rule is traced apart from the forward); the scans' bodies
    # are outlined functions, which take the scope from their call.
    scans = {n for n in head if n.endswith("/while/body/closed_call")}
    assert {"transpose(" in n for n in scans} == {False, True}
    optimizer = {n for n in named if trainer_lib.OPTIMIZER_SCOPE in n}
    assert optimizer and not optimizer & head
    # AdamW's moments are the optimizer's and nothing else's.
    assert any(n.endswith(("sqrt", "integer_pow")) for n in optimizer)
    assert not any(n.endswith(("sqrt", "integer_pow"))
                   and "Block_" not in n and "LayerNorm" not in n
                   for n in set(re.findall(r'loc\("([^"]*)"', text))
                   - optimizer - head)


@pytest.mark.parametrize(
    # The mesh-less toy model's head sees the global batch: 16 tokens a
    # device, 128 rows on the 8 virtual devices.
    "vocab,axis,other",
    [(64, fused_ce.ROW_SCAN, fused_ce.VOCAB_SCAN),
     (4096, fused_ce.VOCAB_SCAN, fused_ce.ROW_SCAN)],
    ids=["rows>=vocab", "rows<vocab"],
)
def test_head_says_which_axis_its_backward_scans(vocab, axis, other):
    """A static choice, so a name and a gauge: the backward loop's ops
    carry the sub-scope of the axis scanned, and `hvt_head_ce_scan` reads
    1 on it after the trace."""
    obs_core.reset()
    text = lowered_lm_step(1, vocab)
    assert fused_ce.scans_vocab(16 * jax.device_count(), vocab) == (
        axis == fused_ce.VOCAB_SCAN)
    named = set(re.findall(r'loc\("([^"]*hvt\.[^"]*)"', text))
    loops = {n for n in named if n.endswith("/while/body/closed_call")}
    assert any(f"{fused_ce.SCOPE}/{axis}/while" in n for n in loops)
    assert not any(f"/{other}/" in n for n in named)
    values = prom.parse_text(prom.render(obs.default_registry()))
    assert values['hvt_head_ce_scan{axis="vocab"}'] == (
        axis == fused_ce.VOCAB_SCAN)
    assert values['hvt_head_ce_scan{axis="rows"}'] == (
        axis == fused_ce.ROW_SCAN)
    obs_core.reset()
