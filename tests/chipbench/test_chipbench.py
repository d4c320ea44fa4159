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
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rewrite(path, change):
    data = json.loads(path.read_text())
    change(data)
    path.write_text(json.dumps(data))


@pytest.fixture
def toy_root(tmp_path):
    """A copy of the benchmark with every configuration at toy width (64
    wide, 2 layers, 128 tokens of vocabulary), sequences of 64, float32
    compute (the reference tolerance is set for the published widths)."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)

    def shrink_config(c):
        m = c["maps_to"]
        c[m["d_model"]], c[m["d_ff"]], c[m["n_layers"]] = 64, 256, 2
        c[m["vocab_size"]], c[m["n_heads"]] = 128, 4
        if m["n_kv_heads"] != m["n_heads"]:
            c[m["n_kv_heads"]] = 2
        if m["window"]:
            c[m["window"]] = 64

    for path in (tmp_path / "chipbench" / "configs").glob("*.json"):
        rewrite(path, shrink_config)
    for path in (tmp_path / "chipbench" / "traffic").glob("*.json"):
        rewrite(path, lambda t: t.update(
            seq_len=64, n_sequences=4 * t["global_batch"]))
    for path in (tmp_path / "chipbench" / "workloads").glob("*.json"):
        rewrite(path, lambda w: w["trainer"].update(compute_dtype="float32"))
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    yield tmp_path
    jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)


def run_cell(root, cell, trace, capsys, **kwargs):
    rc = run.main(["--workload", cell, "--seed", "2147483659", "--seconds",
                   "0.2", "--trace", str(trace)], root=root, **kwargs)
    lines = [json.loads(line)
             for line in capsys.readouterr().out.splitlines() if line]
    return rc, lines


@pytest.mark.parametrize("cell", CELLS)
def test_runner_end_to_end_at_toy_width(toy_root, capsys, cell):
    rc, lines = run_cell(toy_root, cell, 0, capsys, require_tpu=False)
    assert rc == 0
    assert [l["phase"] for l in lines if "phase" in l] == [
        "build", "reference", "warmup", "window"]
    result = lines[-1]
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == len(lines[-3]["losses"]) >= 4
    assert set(result["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(result["device"]) == {
        "platform", "kind", "count", "memory_peak_bytes"}
    assert result["device"]["platform"] == "cpu"  # and so not a device number


def test_runner_refuses_a_cpu(capsys):
    rc, lines = run_cell(ROOT, CELLS[0], 0, capsys)
    assert rc == 1
    assert lines == []


def test_additions_are_found_by_name_with_no_edit(toy_root, capsys):
    """A cell, a traffic kind and a per-layer metric arrive as new files
    and `BENCHMARK.json` entries; no file that was there changes."""
    here = toy_root / "chipbench"
    before = {p: hashlib.sha256(p.read_bytes()).hexdigest()
              for p in here.rglob("*") if p.is_file()}
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
    assert all(hashlib.sha256(p.read_bytes()).hexdigest() == digest
               for p, digest in before.items())


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
    ms, count = reduce.kernel_ms_per_step(chip)
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
    """Four ops on one chip between two steps' edges: a matmul 0-60, an
    async all-reduce 40-100 (its start and done halves on the op line, the
    pair on the async line), a fusion 70-80. The collective is in flight
    for 60; compute covers 40-60 and 70-80 of it; 30 is exposed."""
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
            (dev, reduce.ASYNC_OPS, "%all-reduce-start.1 = ()",
             base + 40.0, 60.0),
            (dev, reduce.OPS, "%fusion.2 = f32[] fusion(), kind=kLoop",
             base + 70.0, 10.0),
            (dev, reduce.OPS, "%all-reduce-done.1 = () all-reduce-done()",
             base + 90.0, 10.0),
        ]
    chip, = reduce.chips_from_rows(rows)
    assert [reduce.op_name(n) for n, _, _ in chip.ops[:3]] == [
        "dot.1", "fusion.2", "all-reduce-done.1"]  # no while, no marker
    total, exposed = reduce.collective_ms_per_step(chip)
    assert total * 1e6 == pytest.approx(60.0)
    assert exposed * 1e6 == pytest.approx(30.0)
    assert chip.busy_ns() / chip.stretch_ns == pytest.approx(0.8)
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
    model = run.model_sizes(json.loads((ROOT / entry["file"]).read_text()))
    assert flops.visible_pairs(seq_len, model["window"]) == pairs
    assert flops.matmul_params(model) == params
    required = flops.required_flops_per_token(model, seq_len)
    assert required == pytest.approx(6 * params + attn6 / seq_len)
    executed = flops.flash_executed_flops_per_step(model, seq_len, batch=1)
    assert executed == pytest.approx(attn6 * 9 / 6)  # 9 dots, not 6
    assert flops.head_flops_per_step(model, 10, executed=True) == (
        pytest.approx(flops.head_flops_per_step(model, 10, executed=False)
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
    from horovod_tpu.models.transformer import TransformerLM

    model = TransformerLM(
        vocab_size=64, d_model=32, n_heads=4, n_kv_heads=n_kv_heads,
        window=window, n_layers=2, dropout=0.0, fused_head_chunks=2)
    x, y = (jnp.asarray(a) for a in copy_task.make(
        3, {"seq_len": 32, "n_sequences": 1}, 64))
    key = jax.random.PRNGKey(0)
    variables = model.init({"params": key, "dropout": key}, x, train=False,
                           labels=y)
    got, _ = model.apply(variables, x, train=False, labels=y)
    want = reference.per_token_loss(
        variables["params"], x[0], y[0], n_layers=2, window=window)
    # float32 against float32: rounding in another order, nothing more.
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    report = reference.compare(got[0], want)
    assert report["rel_rms"] < 1e-4 and report["mean_abs_diff"] < 1e-5
    # ... and the comparison sees a model that drops the mask's window.
    if window:
        unmasked = reference.per_token_loss(
            variables["params"], x[0], y[0], n_layers=2, window=None)
        assert reference.compare(unmasked, want)["rel_rms"] > run.REL_RMS_TOL


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


def test_benchmark_json_is_consistent_with_the_files():
    name_ok = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit_ok = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [e["name"] for e in metrics + BENCH["workloads"] + BENCH["configs"]]
    assert all(name_ok.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert all(unit_ok.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    assert all(0.01 <= m["bound"] <= 0.1 for m in BENCH["end_to_end"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    for config in BENCH["configs"]:
        data = json.loads((ROOT / config["file"]).read_text())
        assert set(config["reduced"]) == set(data["reduced"])
        assert all(name_ok.match(k) and k in data for k in config["reduced"])
        run.model_sizes(data)
    for cell in CELLS:
        loaded = run.load_cell(ROOT, cell)
        assert name_ok.match(loaded["workload"]["traffic"])
        reported = {m["name"] for m in loaded["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert loaded["per_layer"]
        for metric in loaded["per_layer"]:
            assert metric["moves"] in reported
            spec = json.loads((ROOT / "chipbench" / "layer_metrics"
                               / f"{metric['name']}.json").read_text())
            assert spec["unit"] == metric["unit"]
            path, _, attr = spec["reader"].partition(":")
            assert callable(run.load_attr(ROOT / "chipbench" / path, attr))
