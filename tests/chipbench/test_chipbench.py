"""Tests of the benchmark under chipbench/ (the one file for them).

The runner is driven end to end on the CPU at toy width, in a temporary
copy whose configuration files have been shrunk, with the chip refusal
lifted by the test (`require_tpu=False`): that proves paths, phases and the
shape of the result, and no number from it is a device number. The
reduction is checked on a cut recorded from this repository's first traced
v5e run (trace_cut.json) and on cases small enough to work out by hand.
"""

import hashlib
import json
import pathlib
import re
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import end_to_end, flops, reduce, reference, run
from chipbench.traffic import copy_task

ROOT = pathlib.Path(__file__).resolve().parents[2]
HERE = pathlib.Path(__file__).resolve().parent
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}
GATES = {"steps_not_run", "losses_not_finite", "compiles_in_window",
         "replicas_differ"}
DENSE_LM = run.load_module(ROOT / "chipbench" / "families" / "dense_lm.py")


def bench_of(root):
    return json.loads((root / "BENCHMARK.json").read_text())


def cells_of(root, family=None):
    """The names of the cells in ``root``'s `BENCHMARK.json`; with
    ``family``, of those whose configuration's file names it."""
    bench = bench_of(root)

    def family_of(cell):
        entry = run.named(bench["configs"], cell["config"], "config")
        return json.loads((root / entry["file"]).read_text()).get("family")

    return [w["name"] for w in bench["workloads"]
            if family is None or family_of(w) == family]


BENCH = bench_of(ROOT)
CELLS = cells_of(ROOT)
DENSE_CELLS = cells_of(ROOT, "dense_lm")


# What the two dense configurations shrink to: what these tests have always
# run them at (`shrink_config` of PRs 25-28 wrote the same).
DENSE_TOYS = {
    "cerebras-gpt-1.3b": {
        "n_embd": 64, "n_inner": 256, "n_layer": 2, "vocab_size": 128,
        "n_head": 4},
    "starcoder2-3b": {
        "hidden_size": 64, "intermediate_size": 256, "num_hidden_layers": 2,
        "vocab_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "sliding_window": 64}}


def rewrite(path, change):
    data = json.loads(path.read_text())
    change(data)
    path.write_text(json.dumps(data))


def shrink_to_toy(root):
    """Every configuration under ``root`` at the toy sizes its own file
    gives under ``toy`` (published key -> toy value: for the two dense
    ones 64 wide, FFN 256, 2 layers, 128 rows of vocabulary, 4 heads, 2 KV
    heads and a window of 64 where they have them), sequences of 64,
    float32 compute (the reference tolerance is set for the published
    widths). A configuration's family knows which of its keys are sizes,
    these tests do not: a file without ``toy`` is an error."""
    for path in sorted((root / "chipbench" / "configs").glob("*.json")):
        config = json.loads(path.read_text())
        if "toy" not in config:
            raise ValueError(
                f'{path} has no "toy" key: the tests run every '
                "configuration at toy sizes on the CPU, and the file says "
                "which of its published keys shrink, and to what")
        unknown = sorted(set(config["toy"]) - set(config))
        if unknown:
            raise ValueError(f'{path}: "toy" names {unknown}, which the file '
                             "does not have")
        path.write_text(json.dumps(config | config["toy"]))
    for path in (root / "chipbench" / "traffic").glob("*.json"):
        rewrite(path, lambda t: t.update(
            seq_len=64, n_sequences=4 * t["global_batch"]))
    for path in (root / "chipbench" / "workloads").glob("*.json"):
        rewrite(path, lambda w: w["trainer"].update(compute_dtype="float32"))


@pytest.fixture
def bench_copy(tmp_path):
    """A copy of the benchmark as it is committed."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    yield tmp_path
    jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)


@pytest.fixture
def toy_root(bench_copy):
    """... with every configuration at toy width (`shrink_to_toy`)."""
    shrink_to_toy(bench_copy)
    return bench_copy


def hashes(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "chipbench").rglob("*") if p.is_file()}


def run_cell(root, cell, trace, capsys, stderr=None, **kwargs):
    """(exit code, the JSON lines of standard output); the lines of
    standard error are appended to ``stderr`` where a list is given."""
    rc = run.main(["--workload", cell, "--seed", "2147483659", "--seconds",
                   "0.2", "--trace", str(trace)], root=root, **kwargs)
    captured = capsys.readouterr()
    if stderr is not None:
        stderr += captured.err.splitlines()
    return rc, [json.loads(line) for line in captured.out.splitlines() if line]


def check_runner_end_to_end(root, cell, capsys):
    rc, lines = run_cell(root, cell, 0, capsys, require_tpu=False)
    assert rc == 0
    assert [l["phase"] for l in lines if "phase" in l] == [
        "build", "reference", "warmup", "window"]
    result = lines[-1]
    assert set(result) == RESULT_KEYS and list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    loaded = run.load_cell(root, cell)
    assert {name: limit for name, (_, limit) in result["compared"].items()
            } == loaded["limits"] | dict.fromkeys(GATES, 0)
    assert all(value <= limit for value, limit in result["compared"].values())
    assert result["attempted"] == len(lines[-3]["losses"]) >= 4
    assert set(result["metrics"]) == {m["name"] for m in loaded["end_to_end"]}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(result["device"]) == {
        "platform", "kind", "count", "memory_peak_bytes"}
    assert result["device"]["platform"] == "cpu"  # and so not a device number


@pytest.mark.parametrize("cell", CELLS)
def test_runner_end_to_end_at_toy_width(toy_root, capsys, cell):
    check_runner_end_to_end(toy_root, cell, capsys)


def test_runner_refuses_a_cpu(capsys):
    rc, lines = run_cell(ROOT, CELLS[0], 0, capsys)
    assert rc == 1
    assert lines == []


def test_additions_are_found_by_name_with_no_edit(toy_root, capsys):
    """A cell, a traffic kind and a per-layer metric arrive as new files
    and `BENCHMARK.json` entries; no file that was there changes."""
    here = toy_root / "chipbench"
    before = hashes(toy_root)
    (here / "traffic" / "ramp.py").write_text(
        "import numpy as np\n"
        "def make(seed, params, vocab_size):\n"
        "    n, t = params['n_sequences'], params['seq_len']\n"
        "    x = (np.arange(n * t, dtype=np.int32).reshape(n, t) + seed % 97)\n"
        "    x = x % (vocab_size - 1) + 1\n"
        "    return x, np.roll(x, -1, axis=1)\n")
    (here / "traffic" / "ramp32.json").write_text(json.dumps(
        {"kind": "ramp", "seq_len": 32, "global_batch": 2,
         "n_sequences": 8}))
    workload = json.loads((here / "workloads" / f"{CELLS[0]}.json").read_text())
    workload["traffic"] = "ramp32"
    (here / "workloads" / "toy.ramp.json").write_text(json.dumps(workload))
    (here / "layer_metrics" / "host_rows.json").write_text(json.dumps(
        {"name": "host_rows", "unit": "rows",
         "reader": "layer_metrics/host_rows.py:read", "what": "a count"}))
    (here / "layer_metrics" / "host_rows.py").write_text(
        "def read(ctx):\n    return float(len(ctx['rows']) + 1)\n")

    def add(bench):
        bench["workloads"].append(
            {"name": "toy.ramp", "config": workload["config"],
             "traffic": "ramp32", "chips": 1, "why": "test"})
        bench["per_layer"].append(
            {"name": "host_rows", "unit": "rows", "better": "higher",
             "source": "program_counter", "layer": "device",
             "moves": "tokens_per_s", "workloads": ["toy.ramp"]})

    rewrite(toy_root / "BENCHMARK.json", add)
    rc, lines = run_cell(toy_root, "toy.ramp", 1, capsys, require_tpu=False)
    assert rc == 0
    result = lines[-1]
    assert result["correct"] is True
    assert result["metrics"]["host_rows"]["value"] >= 1.0
    # A CPU trace has no TPU plane: readers that find nothing say nothing.
    assert "step_device_ms" not in result["metrics"]
    assert "step_temp_gb" in result["metrics"]
    assert before.items() <= hashes(toy_root).items()


# A family the harness has never seen, as a later PR would bring it
# (gated_toy.py beside this file), and a configuration of it: the keys as
# "published", and under ``toy`` what the tests run.
TOY_PUBLISHED = {
    "source": "a test", "family": "gated_toy", "vocab_size": 4096,
    "hidden_size": 512, "num_attention_heads": 8, "head_dim": 64,
    "num_hidden_layers": 4, "intermediate_size": 1280,
    "max_position_embeddings": 2048, "reduced": {},
    "toy": {"vocab_size": 96, "hidden_size": 64, "num_attention_heads": 2,
            "head_dim": 24, "num_hidden_layers": 2, "intermediate_size": 160,
            "max_position_embeddings": 64}}
TOY_CONFIG = TOY_PUBLISHED | TOY_PUBLISHED["toy"]  # as `shrink_to_toy` leaves it
GATED_TOY = run.load_module(HERE / "gated_toy.py")
# ... held to its own limits and, stating none on the bias, to run.py's.
TOY_LIMITS = {"bias": run.BIAS_TOL} | GATED_TOY.LIMITS
# 2 layers of (q, k, v, o: 4·64·48; gate, up, down: 3·64·160) and the head
# 64·96; 6 dots · 2 · pairs · (2 heads · 24) · 2 layers over the sequence
TOY_MATMUL_PARAMS = 2 * (4 * 64 * 48 + 3 * 64 * 160) + 64 * 96
TOY_REQUIRED = 6 * TOY_MATMUL_PARAMS + 6 * 2 * (64 * 65 // 2) * 48 * 2 / 64


def add_toy_configuration(toy_root, config=TOY_CONFIG):
    """The new files and `BENCHMARK.json` entries of a cell `toy-gated.seq64`
    of a configuration of another family, and of a per-layer metric that
    reads the family's count from ``ctx``."""
    here = toy_root / "chipbench"
    shutil.copy(HERE / "gated_toy.py", here / "families")
    (here / "configs" / "toy-gated.json").write_text(json.dumps(config))
    workload = json.loads(
        (here / "workloads" / f"{DENSE_CELLS[0]}.json").read_text())
    workload["config"] = "toy-gated"
    (here / "workloads" / "toy-gated.seq64.json").write_text(
        json.dumps(workload))
    (here / "layer_metrics" / "required_kflops_per_token.json").write_text(
        json.dumps({"name": "required_kflops_per_token", "unit": "kFLOP",
                    "reader": "layer_metrics/required_kflops_per_token.py:read",
                    "what": "the family's own count, from the published keys"}))
    (here / "layer_metrics" / "required_kflops_per_token.py").write_text(
        "def read(ctx):\n"
        "    own = ctx['family'].required_flops_per_token(\n"
        "        ctx['config'], ctx['seq_len'])\n"
        "    assert own == ctx['required_flops_per_token']\n"
        "    assert ctx['kernel_work'] == {}\n"
        "    return own / 1e3\n")

    def add(bench):
        bench["configs"].append(
            {"name": "toy-gated", "source": "a test",
             "file": "chipbench/configs/toy-gated.json", "reduced": [],
             "why": "test"})
        bench["workloads"].append(
            {"name": "toy-gated.seq64", "config": "toy-gated",
             "traffic": workload["traffic"], "chips": 1, "why": "test"})
        bench["per_layer"].append(
            {"name": "required_kflops_per_token", "unit": "kFLOP",
             "better": "lower", "source": "program_counter",
             "layer": "model", "moves": "tokens_per_s",
             "workloads": ["toy-gated.seq64"]})

    rewrite(toy_root / "BENCHMARK.json", add)


@pytest.mark.parametrize("trace", [0, 1])
def test_a_configuration_of_a_new_family_arrives_as_files(
        toy_root, capsys, trace):
    """The builder, the reference and the counts of an architecture the
    harness has never seen are one new file that the configuration names;
    no file that was there changes."""
    before = hashes(toy_root)
    add_toy_configuration(toy_root)
    rc, lines = run_cell(toy_root, "toy-gated.seq64", trace, capsys,
                         require_tpu=False)
    assert rc == 0
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    agreement, = (l for l in lines if l.get("phase") == "reference")
    # float32 against float32, on the family's own reference and held to
    # the family's own limits
    assert agreement["rel_rms"] < 1e-4 and agreement["ok"] is True
    assert agreement["limits"] == TOY_LIMITS
    assert set(result["compared"]) == set(TOY_LIMITS) | GATES
    norm_scales = (2 * 2 + 1) * 64
    assert lines[1]["n_params"] == TOY_MATMUL_PARAMS + 96 * 64 + norm_scales
    if trace:
        assert result["metrics"]["required_kflops_per_token"]["value"] == (
            pytest.approx(TOY_REQUIRED / 1e3))
        # No kernel of the family's and no TPU plane: nothing to read.
        assert not {"flash_ms_per_step", "flash_roofline", "mfu",
                    "flash_fwd_ms_per_step"} & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {
            m["name"] for m in BENCH["end_to_end"]}
    assert before.items() <= hashes(toy_root).items()


def test_a_cell_of_another_family_needs_no_edit_to_these_tests(
        bench_copy, capsys):
    """What the next `model_config` PR does, in a copy of the benchmark as
    it is committed: a family, a configuration with its ``toy`` sizes, a
    workload and a per-layer metric arrive as new files and
    `BENCHMARK.json` entries BEFORE anything is shrunk. Every test of this
    file that runs over the cells then holds for the new cell too, or
    leaves it alone because its configuration names another family."""
    before = hashes(bench_copy)
    add_toy_configuration(bench_copy, TOY_PUBLISHED)
    assert before.items() <= hashes(bench_copy).items()
    shrink_to_toy(bench_copy)
    assert json.loads((bench_copy / "chipbench" / "configs" /
                       "toy-gated.json").read_text()) == TOY_CONFIG
    assert cells_of(bench_copy) == CELLS + ["toy-gated.seq64"]
    assert cells_of(bench_copy, "dense_lm") == DENSE_CELLS
    check_benchmark_json_is_consistent(bench_copy)
    check_runner_end_to_end(bench_copy, "toy-gated.seq64", capsys)
    # ... and the cells that were there run as before beside it.
    check_dense_lm_builds_the_parents_step_program(bench_copy, DENSE_CELLS[0])


@pytest.mark.parametrize("toy,says", [
    (None, 'no "toy" key'),
    ({"hidden_size": 64, "n_embd": 64}, r"names \['n_embd'\], which the file does not have"),
], ids=["missing", "names-a-key-the-file-lacks"])
def test_a_configuration_says_how_it_shrinks(bench_copy, toy, says):
    config = {k: v for k, v in TOY_PUBLISHED.items() if k != "toy"}
    if toy is not None:
        config["toy"] = toy
    add_toy_configuration(bench_copy, config)
    with pytest.raises(ValueError, match=says) as raised:
        shrink_to_toy(bench_copy)
    assert "toy-gated.json" in str(raised.value)


# The reference's mask without its diagonal (row 0 keeps its one key): in
# every row, which moves every token and so the median; in the last eight
# rows of 64, which leaves the 56 tokens before them where they were: the
# median does not see it, the far-off share counts it (and the bias sees it
# on the seeds where the eight do not cancel: 10 of 14).
MASK = "jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]"
MASK_FAULTS = {
    "every-row": (MASK.replace(
        "jnp.arange(t)[:, None]",
        "jnp.maximum(jnp.arange(t)[:, None] - 1, 0)"), "median_abs_diff"),
    "last-eight-rows": (
        MASK + " - (jnp.arange(t)[:, None] >= t - 8)", "far_off_share"),
}


@pytest.mark.parametrize("fault", MASK_FAULTS)
def test_a_familys_own_limits_catch_a_fault_planted_in_it(
        toy_root, capsys, fault):
    add_toy_configuration(toy_root)
    path = toy_root / "chipbench" / "families" / "gated_toy.py"
    broken_mask, catches = MASK_FAULTS[fault]
    assert path.read_text().count(MASK) == 1
    path.write_text(path.read_text().replace(MASK, broken_mask))
    err = []
    rc, lines = run_cell(toy_root, "toy-gated.seq64", 0, capsys, err,
                         require_tpu=False)
    result = lines[-1]
    assert rc == 0 and result["correct"] is False
    over = {name for name, (value, limit) in result["compared"].items()
            if not value <= limit}
    assert catches in over and over <= set(TOY_LIMITS)
    if fault == "last-eight-rows":
        # 8 of 64 tokens, each at least seven times `FAR_OFF` off, the
        # other 56 at a fortieth of it at most; the median unmoved
        assert result["compared"]["far_off_share"][0] == 0.125
        assert "median_abs_diff" not in over
    value, limit = result["compared"][catches]
    assert f"compared {catches} {value!r} limit {limit!r}" in err[-6:]


@pytest.mark.parametrize("states,says", [
    ('LIMITS = {"rel_rmss": 0.1, "bias": 1e-3}',
     r"names \['rel_rmss'\], which .* does not report; it reports "
     r"\['bias', .*'far_off_share'\]"),
    ("LIMITS = {}", r"names \[\] and none of \['rel_rms', 'mean_abs_diff', "
                    r"'median_abs_diff'\]"),
    # The family file comes from the PR whose `correct` it decides: a share
    # of far-off tokens and the bias alone hold it to too little.
    ('LIMITS = {"far_off_share": 0.5, "bias": 1.0}',
     r"names \['bias', 'far_off_share'\] and none of "),
    ("del FAR_OFF", "names 'far_off_share', and the file states no FAR_OFF"),
], ids=["a-key-compare-does-not-report", "empty",
        "nothing-that-every-token-moves", "far-off-with-no-threshold"])
def test_a_limits_that_holds_a_family_to_too_little_is_refused_by_name(
        toy_root, states, says):
    add_toy_configuration(toy_root)
    with open(toy_root / "chipbench" / "families" / "gated_toy.py", "a") as f:
        f.write(f"\n{states}\n")
    with pytest.raises(ValueError, match=says) as raised:
        run.load_cell(toy_root, "toy-gated.seq64")
    assert "gated_toy.py: LIMITS" in str(raised.value)


def test_dense_lm_states_no_limits_and_is_held_to_the_three_constants():
    assert not hasattr(DENSE_LM, "LIMITS")
    assert run.limits_of(DENSE_LM) == {
        "rel_rms": 0.04, "mean_abs_diff": 0.03, "bias": 1e-3}
    # A family's own limits leave the bias under its constant unless they
    # state another.
    assert "bias" not in GATED_TOY.LIMITS
    assert run.limits_of(GATED_TOY) == TOY_LIMITS == {
        "bias": 1e-3, "median_abs_diff": 2e-5, "far_off_share": 0.05}
    import types

    stated = types.SimpleNamespace(LIMITS={"rel_rms": 0.2, "bias": 5e-3})
    assert run.limits_of(stated) == {"rel_rms": 0.2, "bias": 5e-3}
    assert set(run.DEFAULT_LIMITS) | set(GATED_TOY.LIMITS) == set(
        reference.REPORTED)
    assert set(run.WHOLE_SEQUENCE) < set(reference.REPORTED)


def test_mfu_divides_the_familys_own_count():
    ctx = {"tokens_per_s": 2e6, "required_flops_per_token": TOY_REQUIRED,
           "n_chips": 4, "device_kind": "TPU v5 lite"}
    assert reduce.mfu(ctx) == pytest.approx(
        100 * TOY_REQUIRED * 2e6 / (4 * 197e12))
    assert reduce.mfu(ctx | {"tokens_per_s": None}) is None
    assert reduce.flash_ms_per_step({"kernel_work": {}, "chips": []}) is None
    assert reduce.flash_roofline({"kernel_work": {}, "chips": []}) is None


@pytest.mark.parametrize("family,error,says", [
    (None, KeyError, '"family" key'),
    ("", KeyError, '"family" key'),
    ("no_such_family", FileNotFoundError, "no_such_family.py is not there"),
])
def test_a_configuration_names_a_family_that_is_there(
        toy_root, capsys, family, error, says):
    config = {k: v for k, v in TOY_CONFIG.items() if k != "family"}
    if family is not None:
        config["family"] = family
    add_toy_configuration(toy_root, config)
    with pytest.raises(error, match=says) as raised:
        run_cell(toy_root, "toy-gated.seq64", 0, capsys, require_tpu=False)
    assert "toy-gated" in str(raised.value)


def test_a_family_lacking_a_function_is_refused_by_name(toy_root):
    path = toy_root / "chipbench" / "families" / "half.py"
    path.write_text("def sizes(config):\n    return {}\nbuild = sizes\n")
    with pytest.raises(AttributeError, match="kernel_work"):
        run.load_family(toy_root, {"family": "half"}, "x")


def test_init_refuses_a_collection_carried_from_step_to_step():
    import types

    import flax.linen as nn

    class Counts(nn.Module):
        @nn.compact
        def __call__(self, tokens, train=False, labels=None):
            seen = self.variable("counters", "seen", jnp.zeros, ())
            seen.value += 1
            return nn.Embed(8, 4)(tokens).sum(-1), tokens

    import horovod_tpu as hvt

    mesh = hvt.build_mesh(hvt.MeshSpec(data=1), devices=jax.devices()[:1])
    trainer = types.SimpleNamespace(
        dp_size=1, module=Counts(), seed=0, tx=None, mesh=mesh)
    with pytest.raises(ValueError, match="carries .'counters'. from step"):
        run.init_state(trainer, seq_len=4)


def check_dense_lm_builds_the_parents_step_program(root, cell):
    import types

    from horovod_tpu.models.transformer import ShardingConfig, TransformerLM

    loaded = run.load_cell(root, cell)
    family, config = loaded["family"], loaded["config"]
    sizes = family.sizes(config)

    def as_the_parent_did(config, spec, mesh):
        return TransformerLM(
            vocab_size=sizes["vocab_size"], d_model=sizes["d_model"],
            n_heads=sizes["n_heads"], n_kv_heads=sizes["n_kv_heads"],
            window=sizes["window"], n_layers=sizes["n_layers"], dropout=0.0,
            compute_dtype=jnp.dtype(spec["compute_dtype"]),
            fused_head_chunks=spec["fused_head_chunks"],
            sharding=ShardingConfig(mesh=mesh))

    traffic = loaded["traffic"]
    x, y = copy_task.make(5, traffic, sizes["vocab_size"])
    texts, modules = [], []
    for build in (family.build, as_the_parent_did):
        trainer = run.build_trainer(
            loaded | {"family": types.SimpleNamespace(build=build)},
            jax.devices()[:loaded["chips"]], seed=5)
        run.init_state(trainer, traffic["seq_len"])
        batch = traffic["global_batch"] // trainer.dp_size
        texts.append(run.lowered_step(trainer, x, y, batch).as_text())
        modules.append(trainer.module)
    assert modules[0] == modules[1]
    assert texts[0] == texts[1] and "stablehlo" in texts[0]


@pytest.mark.parametrize("cell", DENSE_CELLS)
def test_dense_lm_builds_the_step_program_the_parent_spelled_out(
        toy_root, cell):
    """`run.build_trainer` of PR 27 constructed `TransformerLM` itself, with
    these ten arguments; through the family the module is equal to it and
    the train step lowers to the same text. For the cells whose
    configuration says ``"family": "dense_lm"``: another family's module
    is its own."""
    check_dense_lm_builds_the_parents_step_program(toy_root, cell)


def test_replicas_agree_sees_one_chip_off_by_one_bit():
    import types

    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices), ("data",))
    replicated = NamedSharding(mesh, PartitionSpec())

    def on_each(values):
        return jax.make_array_from_single_device_arrays(
            (3,), replicated,
            [jax.device_put(jnp.asarray(v, jnp.float32), d)
             for v, d in zip(values, devices)])

    same = on_each([[1.0, 2.0, 3.0]] * 4)
    off = on_each([[1.0, 2.0, 3.0]] * 3 + [[1.0, 2.0, 3.0000002]])
    for params, want in (({"w": same}, True), ({"w": same, "b": off}, False)):
        trainer = types.SimpleNamespace(
            mesh=mesh, state=types.SimpleNamespace(params=params))
        assert run.replicas_agree(trainer) is want


# --- the reduction ---------------------------------------------------------

@pytest.fixture(scope="module")
def cut():
    return json.loads((HERE / "trace_cut.json").read_text())


def mosaic_ms_per_step(chip):
    """(milliseconds a step in Mosaic kernels of any name, their count a
    step): all that this cut can tell, recorded before the kernels had
    names; the readers go by name (`reduce.flash_kernel_ms_per_step`)."""
    hits = [d for n, _, d in chip.ops if reduce.KERNEL_MARK in n]
    return sum(hits) / 1e6 / len(chip.steps), len(hits) / len(chip.steps)


def test_reduction_of_the_recorded_cut(cut):
    """Two steady steps of the first traced run of cell 1 (v5e), with the
    ops of a microsecond or more; the expected numbers were worked out from
    the cut by a separate brute-force script when it was recorded."""
    rows = [tuple(r) for r in cut["rows"]]
    chip, = reduce.chips_from_rows(rows)
    want = cut["expected"]
    assert chip.module.startswith("jit_train_step(")
    assert len(chip.steps) == want["steady_steps"]
    assert chip.gaps_ns() == pytest.approx(want["gaps_ns"])
    assert chip.busy_ns() / chip.stretch_ns == pytest.approx(
        want["busy_share"], abs=1e-9)
    ms, count = mosaic_ms_per_step(chip)
    assert reduce.flash_kernel_ms_per_step(chip) == (0.0, 0.0)  # no names
    assert count == want["kernels_per_step"]
    assert ms == pytest.approx(want["kernel_ms_per_step"])
    # The containers are in the cut and would double the count if summed.
    assert any(reduce.op_name(r[2]).startswith("while") for r in rows)
    assert sum(d for _, _, d in chip.ops) <= chip.stretch_ns
    families = dict(reduce.device_op_families(chip))
    assert all(len(name) <= 90 for name in families)
    total, exposed = reduce.collective_ms_per_step(chip)
    assert total == exposed == 0.0
    gaps = reduce.idle_gaps(chip, rows)
    assert gaps and all(what.startswith(("in step", "between steps"))
                        for what, _ in gaps)


def test_leaves_and_exposed_collective_by_hand():
    """Five ops on one chip between two steps' edges: a matmul 0-60, an
    async all-reduce 40-100 (its start and done halves on the op line, the
    pair on the async line), a fusion 70-80, and at 80-90 the all-reduce
    that a shard_map's psum makes, which XLA names after the JAX primitive
    and which is told by its opcode. A collective is in flight for 60;
    compute covers 40-60 and 70-80 of it; 30 is exposed (20, were the psum
    taken for compute by its name, as the fusion that only reads it is)."""
    dev, mod = "/device:TPU:0", "jit_step(1)"
    rows = [(dev, reduce.MODULES, mod, s, 100.0)
            for s in (-100.0, 0.0, 100.0, 200.0)]
    for base in (0.0, 100.0):
        rows += [
            (dev, reduce.OPS, "%while.1 = () while()", base, 100.0),
            (dev, reduce.OPS, "%dot.1 = f32[] fusion(), kind=kOutput",
             base, 60.0),
            (dev, reduce.OPS, "%marker.1 = () custom-call()", base, 0.0),
            (dev, reduce.OPS, "%all-reduce-start.1 = () all-reduce-start()",
             base + 40.0, 0.0),
            (dev, reduce.ASYNC_OPS,
             "%all-reduce-start.1 = () all-reduce-start()",
             base + 40.0, 60.0),
            (dev, reduce.OPS, "%fusion.2 = f32[] fusion(f32[8]{0} %psum.1, "
             "f32[] %all-reduce-done.1), kind=kLoop", base + 70.0, 10.0),
            (dev, reduce.OPS, "%psum.1 = f32[8]{0} all-reduce(f32[8]{0} "
             "%dot.1), to_apply=%add", base + 80.0, 10.0),
            (dev, reduce.OPS, "%all-reduce-done.1 = () all-reduce-done()",
             base + 90.0, 10.0),
        ]
    chip, = reduce.chips_from_rows(rows)
    assert [reduce.op_name(n) for n, _, _ in chip.ops[:4]] == [
        "dot.1", "fusion.2", "psum.1", "all-reduce-done.1"]  # no while, marker
    total, exposed = reduce.collective_ms_per_step(chip)
    assert total * 1e6 == pytest.approx(60.0)
    assert exposed * 1e6 == pytest.approx(30.0)
    assert chip.busy_ns() / chip.stretch_ns == pytest.approx(0.9)
    assert chip.gaps_ns() == [0.0]
    assert reduce.union_ns([(0, 2), (1, 3), (5, 6)]) == 4


# --- counts from shapes ----------------------------------------------------

@pytest.mark.parametrize("name,seq_len,params,attn6,pairs", [
    # 12 x 12·2048² + 2048·50257; 6 dots · 2 · pairs · 2048 · 12 layers
    ("cerebras-gpt-1.3b", 2048, 12 * 12 * 2048 ** 2 + 2048 * 50257,
     6 * 2 * 2098176 * 2048 * 12, 2048 * 2049 // 2),
    # q and out 3072², kv 3072·2·256, MLP 2·3072·12288, 6 layers; the
    # window of 4096 covers the whole 4096-token sequence
    ("starcoder2-3b", 4096,
     6 * (2 * 3072 ** 2 + 3072 * 512 + 2 * 3072 * 12288) + 3072 * 49152,
     6 * 2 * 8390656 * 3072 * 6, 4096 * 4097 // 2),
])
def test_flops_against_hand_counts(name, seq_len, params, attn6, pairs):
    entry = run.named(BENCH["configs"], name, "config")
    config = json.loads((ROOT / entry["file"]).read_text())
    model = DENSE_LM.sizes(config)
    assert flops.visible_pairs(seq_len, model["window"]) == pairs
    assert DENSE_LM.matmul_params(config) == params
    required = DENSE_LM.required_flops_per_token(config, seq_len)
    assert required == pytest.approx(6 * params + attn6 / seq_len)
    work = DENSE_LM.kernel_work(config, seq_len, per_chip_batch=1)
    executed, nbytes, calls = work["flash"]
    assert executed == pytest.approx(attn6 * 9 / 6)  # 9 dots, not 6
    # 17 [B, T, H·D] bf16 arrays a layer; three kernels a layer
    layers, width = model["n_layers"], model["d_model"]
    assert nbytes == 17 * seq_len * width * 2 * layers
    assert calls == 3 * layers == 3 * model["attention_layers"]
    parts = [work[f"flash_{k}"] for k in ("fwd", "dq", "dkv")]
    assert [sum(p[i] for p in parts) for i in range(3)] == [
        pytest.approx(executed), nbytes, calls]
    assert [p[0] / parts[0][0] for p in parts] == [1.0, 1.5, 2.0]
    assert DENSE_LM.head_flops_per_step(config, 10, executed=True) == (
        pytest.approx(DENSE_LM.head_flops_per_step(config, 10, executed=False)
                      * 8 / 6))
    # A window shorter than the sequence: rows see 1, 2, 3, 3, 3 keys.
    assert flops.visible_pairs(5, 3) == 12
    with pytest.raises(KeyError, match="no published peaks"):
        flops.peaks("cpu")
    assert flops.roofline_seconds(197e12, 1.0, "TPU v5 lite") == (
        pytest.approx(1.0), "compute")


@pytest.mark.parametrize("n_kv_heads,window", [(None, None), (2, 8)],
                         ids=["mha", "gqa-window"])
def test_reference_matches_the_system_at_toy_width(n_kv_heads, window):
    config = {"v": 64, "d": 32, "h": 4, "kv": n_kv_heads or 4, "l": 2,
              "ff": 128, "w": window, "maps_to": {
                  "vocab_size": "v", "d_model": "d", "n_heads": "h",
                  "n_kv_heads": "kv", "n_layers": "l", "d_ff": "ff",
                  "window": "w" if window else None, "max_positions": None}}
    model = DENSE_LM.build(
        config, {"compute_dtype": "float32", "fused_head_chunks": 2}, None)
    assert (model.n_kv_heads, model.window) == (n_kv_heads, window)
    x, y = (jnp.asarray(a) for a in copy_task.make(
        3, {"seq_len": 32, "n_sequences": 1}, 64))
    key = jax.random.PRNGKey(0)
    variables = model.init({"params": key, "dropout": key}, x, train=False,
                           labels=y)
    got, _ = model.apply(variables, x, train=False, labels=y)
    want = DENSE_LM.per_token_loss(variables["params"], x[0], y[0], config)
    # float32 against float32: rounding in another order, nothing more.
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    report = reference.compare(got[0], want)
    assert report["rel_rms"] < 1e-4 and report["mean_abs_diff"] < 1e-5
    # ... and the comparison sees a model that drops the mask's window.
    if window:
        unmasked = DENSE_LM.per_token_loss(
            variables["params"], x[0], y[0], config | {"w": None})
        assert reference.compare(unmasked, want)["rel_rms"] > run.REL_RMS_TOL


def test_compare_reports_what_a_minority_of_far_off_tokens_cannot_move():
    """200 tokens whose losses differ from the reference's by rounding
    (+-0.004 to +-0.012), then the same with every tenth token 0.3 further
    off, as a routed model's tokens are whose last expert differs between
    two precisions: the mean of squares reads six times as much, the median
    stays, and the far-off share (beyond 0.08, which the family would
    state: ten of its medians) counts the minority."""
    rng = np.random.default_rng(0)
    want = rng.normal(5.0, 1.0, 200).astype(np.float32)
    noise = rng.choice([-1.0, 1.0], 200) * rng.uniform(0.004, 0.012, 200)
    flipped = noise.copy()
    flipped[::10] += 0.3
    even = reference.compare(want + noise.astype(np.float32), want, 0.08)
    routed = reference.compare(want + flipped.astype(np.float32), want, 0.08)
    assert set(reference.REPORTED) <= set(even)
    assert even["far_off_share"] == 0.0
    assert even["median_abs_diff"] == pytest.approx(0.008, rel=0.1)
    assert routed["rel_rms"] > 6 * even["rel_rms"] > 0.04
    assert routed["mean_abs_diff"] > 4 * even["mean_abs_diff"]
    assert routed["median_abs_diff"] < 1.2 * even["median_abs_diff"]
    assert routed["far_off_share"] == pytest.approx(0.1)
    # By hand: nine tokens 0.01 off and one 0.5 off.
    ten = jnp.linspace(1.0, 2.0, 10)
    moved = ten + jnp.asarray([0.01, -0.01, 0.01] * 3 + [0.5])
    by_hand = reference.compare(moved, ten, far_off=0.1)
    assert by_hand["median_abs_diff"] == pytest.approx(0.01, rel=1e-4)
    assert by_hand["mean_abs_diff"] == pytest.approx(0.059, rel=1e-4)
    assert by_hand["far_off_share"] == pytest.approx(0.1)
    # Where "far off" begins is the family's to state: with none stated
    # there is no share to report (and none to set a limit on).
    assert set(reference.compare(moved, ten)) == (
        set(by_hand) - {"far_off_share"})


def test_far_off_share_where_most_tokens_agree_to_the_bit():
    """float32 against float32 on another CPU: six tokens of ten equal to
    the bit, four one or two float32 steps off. The median is 0, and the
    share is taken against the family's threshold, not against a multiple
    of that median, which would count all four."""
    want = jnp.linspace(4.0, 5.0, 10)
    step = float(np.spacing(np.float32(4.5)))
    got = want + jnp.asarray([0, 0, step, 0, 0, 2 * step, 0, -step, 0, step])
    report = reference.compare(got, want, far_off=1e-4)
    assert report["median_abs_diff"] == 0.0
    assert 0 < report["mean_abs_diff"] < 2 * step
    assert report["far_off_share"] == 0.0
    assert reference.compare(want, want, far_off=1e-4)["far_off_share"] == 0.0
    lifted = reference.compare(got.at[7].add(0.01), want, far_off=1e-4)
    assert lifted["far_off_share"] == pytest.approx(0.1)
    assert lifted["median_abs_diff"] == 0.0


def test_traffic_and_end_to_end_arithmetic():
    params = {"seq_len": 16, "n_sequences": 3}
    x, y = copy_task.make(2 ** 31 + 11, params, 50257)
    x2, _ = copy_task.make(2 ** 31 + 11, params, 50257)
    assert x.dtype == np.int32 and x.shape == y.shape == (3, 16)
    np.testing.assert_array_equal(x, x2)
    np.testing.assert_array_equal(x[:, 1:], y[:, :-1])
    np.testing.assert_array_equal(x[:, 1:8], y[:, 8:15])  # the copy
    assert x[:, 1:].min() >= 1 and x.max() < 50257
    readings = {"n_steps": 10, "tokens_per_step": 4096, "window_s": 2.0,
                "intervals_ms": [float(i) for i in range(1, 12)],
                "peak_bytes": 13_740_000_000, "setup_s": 15.5}
    assert end_to_end.tokens_per_s(readings) == 20480.0
    assert end_to_end.step_ms_p90(readings) == 10.0
    assert end_to_end.peak_hbm_gb(readings) == 13.74
    assert end_to_end.setup_s(readings) == 15.5


def check_benchmark_json_is_consistent(root):
    bench = bench_of(root)
    name_ok = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit_ok = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [e["name"] for e in metrics + bench["workloads"] + bench["configs"]]
    assert all(name_ok.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert all(unit_ok.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    assert all(0.01 <= m["bound"] <= 0.1 for m in bench["end_to_end"])
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    toys = {}
    for config in bench["configs"]:
        data = json.loads((root / config["file"]).read_text())
        toys[config["name"]] = data["toy"]
        assert set(config["reduced"]) == set(data["reduced"])
        assert all(name_ok.match(k) and k in data for k in config["reduced"])
        # how the tests shrink it: keys the file has, and nothing else
        assert data["toy"] and set(data["toy"]) <= set(data)
        family = run.load_family(root, data, config["name"])
        family.sizes(data)
        family.sizes(data | data["toy"])
    # The two dense ones by their names, among however many there are.
    assert DENSE_TOYS.items() <= toys.items()
    for cell in cells_of(root):
        loaded = run.load_cell(root, cell)
        assert name_ok.match(loaded["workload"]["traffic"])
        reported = {m["name"] for m in loaded["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert loaded["per_layer"]
        for metric in loaded["per_layer"]:
            assert metric["moves"] in reported
            spec = json.loads((root / "chipbench" / "layer_metrics"
                               / f"{metric['name']}.json").read_text())
            assert spec["unit"] == metric["unit"]
            path, _, attr = spec["reader"].partition(":")
            assert callable(run.load_attr(root / "chipbench" / path, attr))


def test_benchmark_json_is_consistent_with_the_files():
    check_benchmark_json_is_consistent(ROOT)
