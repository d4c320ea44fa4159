"""The family ``hybrid_moe_lm`` (chipbench/families/hybrid_moe_lm.py) and its
readers (chipbench/kda_spans.py): the program's `HybridMoELM` against the
family's plain reference at the configuration's ``toy`` sizes on the CPU
(loss AND gradients), the faults the reference sees, the share tests of
the model-configs guide (heads of both mixers, experts), the counts against
hand counts, and the readers on rows small enough to work out by hand."""

import json
import pathlib
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import kda_spans, reduce, run
from chipbench.traffic import copy_task
from horovod_tpu.models.hybrid_moe_lm import DeltaAttention, GatedAttention
from horovod_tpu.models.moe import RoutedExperts, SwiGLU

ROOT = pathlib.Path(__file__).resolve().parents[2]
FAMILY = run.load_module(ROOT / "chipbench" / "families" / "hybrid_moe_lm.py")
CONTROL = run.load_module(
    ROOT / "chipbench" / "families" / "hybrid_moe_lm_control.py")
PUBLISHED = json.loads(
    (ROOT / "chipbench" / "configs" / "solar-open2-250b.json").read_text())
TOY = PUBLISHED | PUBLISHED["toy"]  # as the tests' `shrink_to_toy` leaves it
CELL = "solar-open2-250b.seq8k.1chip"
SEQ = 64
KERNEL = f"custom-call(), {reduce.KERNEL_MARK}"


def toy_model(dtype="float32", **changes):
    return FAMILY.build(
        TOY | changes, {"compute_dtype": dtype, "fused_head_chunks": 2}, None)


def toy_batch(seed=3):
    return tuple(jnp.asarray(a) for a in copy_task.make(
        seed, {"seq_len": SEQ, "n_sequences": 1}, TOY["vocab_size"]))


@pytest.fixture(scope="module")
def toy_params():
    x, y = toy_batch()
    return toy_model().init({"params": jax.random.PRNGKey(0)}, x, labels=y)[
        "params"]


def leaves_with_names(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def system_loss(params, model=None):
    x, y = toy_batch()
    return (model or toy_model()).apply({"params": params}, x, labels=y)[0][0]


def reference_loss(params, **kwargs):
    x, y = toy_batch()
    return FAMILY.per_token_loss(params, x[0], y[0], TOY, **kwargs)


# --- the system against the reference --------------------------------------

def test_the_toy_is_one_period_of_a_share():
    assert FAMILY.layer_kinds(TOY) == ("softmax", "linear", "linear", "linear")
    assert FAMILY.layer_kinds(PUBLISHED) == FAMILY.layer_kinds(TOY)
    assert TOY["num_attention_heads"] < TOY["published_heads"]["softmax"]
    assert TOY["n_routed_experts"] < TOY["n_router_experts"]
    assert FAMILY.sizes(TOY) == {
        "vocab_size": 128, "max_positions": 64, "attention_layers": 1,
        "linear_layers": 3, "expert_layers": 4}


def test_float32_loss_and_gradients_match_the_reference(toy_params):
    got, want = system_loss(toy_params), reference_loss(toy_params)
    np.testing.assert_allclose(got, want, atol=3e-5)
    got = leaves_with_names(
        jax.grad(lambda p: system_loss(p).mean())(toy_params))
    want = leaves_with_names(
        jax.grad(lambda p: reference_loss(p).mean())(toy_params))
    assert set(got) == set(want) and len(got) > 80
    for name, leaf in want.items():
        assert float(jnp.abs(leaf).max()) > 0, name  # every leaf is reached
        np.testing.assert_allclose(
            got[name], leaf, atol=2e-4 * float(jnp.abs(leaf).max()),
            rtol=2e-3, err_msg=name)


def test_bfloat16_stays_near_the_reference(toy_params):
    got = system_loss(toy_params, toy_model("bfloat16"))
    want = reference_loss(toy_params)
    off = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    assert 1e-4 < np.median(off) < 0.05 and off.mean() < 0.1


def test_the_low_precision_control_reads_further_off(toy_params):
    """The reference on float8 parameters with its state in bfloat16: each
    alone moves the losses, and together further than the bf16 system."""
    want = reference_loss(toy_params)
    state_only = reference_loss(toy_params, state_dtype=jnp.bfloat16)
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32), toy_params)
    both = reference_loss(rounded, state_dtype=jnp.bfloat16)
    system = system_loss(toy_params, toy_model("bfloat16"))

    def off(a):
        return float(jnp.abs(a.astype(jnp.float32) - want).mean())

    assert off(state_only) > 1e-4
    assert off(both) > 3 * off(system)


@pytest.mark.parametrize("fault", sorted(CONTROL.FAULTS))
def test_the_reference_sees_a_fault_planted_in_a_mixer(toy_params, fault):
    sound = system_loss(toy_params)
    with CONTROL.planted(fault):
        faulty = system_loss(toy_params)
    again = system_loss(toy_params)  # and the fault is gone afterwards
    want = reference_loss(toy_params)
    np.testing.assert_allclose(again, sound, atol=1e-6)
    assert float(jnp.abs(faulty - want).mean()) > 0.02
    assert float(jnp.abs(sound - want).mean()) < 1e-5


# --- the shares add up --------------------------------------------------------

def head_share(params, start, held, per_head, shared=()):
    """The parameters of heads ``start .. start + held``: ``per_head`` maps
    a leaf's name to the axis its heads lie on; the rest is held whole."""
    def cut(path, leaf):
        name = next(k for k in reversed([p.key for p in path])
                    if k != "kernel")
        if name in shared:
            return leaf
        return jax.lax.slice_in_dim(
            leaf, start, start + held, axis=per_head[name])
    return jax.tree_util.tree_map_with_path(cut, params)


def test_the_eight_head_shares_of_a_kda_layer_add_up_to_the_uncut_layer():
    """Heads 0..7 of an 8-head layer, one a share: each share returns its
    head's rows of W_o times its output, and the eight add up to the whole
    layer's output and to the reference's."""
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 48, 64)),
                    jnp.float32)

    def layer(held, start):
        return DeltaAttention(8, held, start, 16, 4, 8, 1e-5, 32, jnp.float32)

    params = layer(8, 0).init(jax.random.PRNGKey(1), x)["params"]
    want = layer(8, 0).apply({"params": params}, x)
    axes = dict(q_proj=1, k_proj=1, v_proj=1, f_b=1, g_b=1, b_proj=1,
                A_log=0, dt_bias=0, q_conv=1, k_conv=1, v_conv=1, o_proj=0)
    shares = [
        layer(1, h).apply({"params": head_share(
            params, h, 1, axes, shared=("f_a", "g_a", "scale"))}, x)
        for h in range(8)]
    assert float(jnp.abs(shares[0] - shares[1]).mean()) > 0.01
    np.testing.assert_allclose(sum(shares), want, atol=2e-5)
    config = {"head_dim": 16, "rms_norm_eps": 1e-5}
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([FAMILY._delta_attention(
            one, params, config, jnp.float32) for one in x])
    np.testing.assert_allclose(want, uncut, atol=2e-5)


def test_the_eight_head_shares_of_a_softmax_layer_add_up_to_the_uncut_layer():
    """16 query heads over 8 K/V heads, a group of two a share with the
    K/V head it reads."""
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 64, 64)),
                    jnp.float32)

    def layer(held, start):
        return GatedAttention(16, 8, held, start, 16, jnp.float32)

    params = layer(16, 0).init(jax.random.PRNGKey(2), x)["params"]
    want = layer(16, 0).apply({"params": params}, x)

    def share(start):
        own = head_share(
            {n: params[n] for n in ("q_proj", "g_proj", "o_proj")}, start, 2,
            dict(q_proj=1, g_proj=1, o_proj=0))
        own |= head_share({n: params[n] for n in ("k_proj", "v_proj")},
                          start // 2, 1, dict(k_proj=1, v_proj=1))
        return layer(2, start).apply({"params": own}, x)

    np.testing.assert_allclose(
        sum(share(start) for start in range(0, 16, 2)), want, atol=2e-5)
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([FAMILY._gated_attention(
            one, params, {"head_dim": 16}) for one in x])
    np.testing.assert_allclose(want, uncut, atol=2e-5)


def test_the_forty_expert_shares_add_up_to_the_uncut_layer():
    """40 shares of one expert each hold every routed expert's part once
    and the shared expert, which every chip computes alike, forty times:
    less 39 of those they equal the uncut layer's output, and the uncut
    reference's."""
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 48, 64)),
                    jnp.float32)

    def layer(held, start):
        return RoutedExperts(
            n_routed=40, k=8, expert_width=32, shared_width=32, n_held=held,
            held_start=start, routed_scaling=1.0, compute_dtype=jnp.float32)

    params = layer(40, 0).init(jax.random.PRNGKey(3), x)["params"]
    want = layer(40, 0).apply({"params": params}, x)

    def share(start):
        own = dict(params)
        own["experts_gate_up"] = params["experts_gate_up"][start:start + 1]
        own["experts_down"] = params["experts_down"][start:start + 1]
        return layer(1, start).apply({"params": own}, x)

    shared = SwiGLU(32).apply({"params": params["shared"]}, x)
    assert float(jnp.abs(want - shared).mean()) > 0.05  # the routed part
    total = sum(share(start) for start in range(40)) - 39 * shared
    np.testing.assert_allclose(total, want, atol=2e-4)  # 79 terms
    config = {"num_experts_per_tok": 8, "moe_intermediate_size": 32,
              "routed_scaling_factor": 1, "held_experts_start": 0,
              "n_routed_experts": 40}
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([
            FAMILY._expert_layer(one, params, config) for one in x])
    np.testing.assert_allclose(want, uncut, atol=3e-5)


# --- counts -------------------------------------------------------------------

def test_the_cell_holds_the_parameters_the_cut_says():
    """840,871,320 parameters at the published widths (shapes only): three
    KDA blocks of 161,010,824, one softmax block of 156,508,160, twice
    24,576 x 4,096 rows and the last norm."""
    model = FAMILY.build(
        PUBLISHED, {"compute_dtype": "bfloat16", "fused_head_chunks": 8}, None)
    tokens = jnp.zeros((1, 128), jnp.int32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens, labels=tokens))[
            "params"]

    def count(tree):
        return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))

    kda = (4 * 4096 * 1024 + 2 * (4096 * 128 + 128 * 1024) + 4096 * 8
           + 3 * 4 * 1024 + 1024 + 8 + 128)  # ..., taps, dt_bias, A_log, norm
    softmax = 3 * 4096 * 1024 + 2 * 4096 * 128
    moe = 4096 * 320 + 3 * 4096 * 1280 * (1 + 8)
    assert count(shapes["Block_1"]["mixer"]) == kda == 18_134_152
    assert count(shapes["Block_0"]["mixer"]) == softmax == 13_631_488
    assert count(shapes["Block_2"]["mlp"]) == moe == 142_868_480
    assert count(shapes["Block_3"]) == kda + moe + 2 * 4096 == 161_010_824
    assert count(shapes) == 840_871_320


def test_counts_against_hand_counts():
    params = (3 * 18_120_704          # KDA: q k v o, two low ranks, beta
              + 13_631_488            # softmax: q gate o, one K and V head
              + 4 * (1_310_720 + 15_728_640 + 0.2 * 15_728_640)
              + 4096 * 24576)
    assert FAMILY.matmul_params_per_token(PUBLISHED) == params == 249_397_248
    pairs = 8192 * 8193 // 2
    dots = 6 * 2 * pairs * 128 * 8 / 8192
    scan = 9 * 2 * 128 * 128 * 8 * 3
    assert FAMILY.scan_required_flops_per_token(PUBLISHED) == scan
    assert FAMILY.required_flops_per_token(PUBLISHED, 8192) == (
        6 * params + dots + scan) == 1_553_799_168
    work = FAMILY.kernel_work(PUBLISHED, 8192, 1)
    assert work["flash_fwd"] == (2 * 2 * pairs * 128 * 8, 4 * 2 ** 24, 1)
    assert work["flash_dq"] == (3 * 2 * pairs * 128 * 8, 6 * 2 ** 24, 1)
    assert work["flash_dkv"] == (4 * 2 * pairs * 128 * 8, 7 * 2 ** 24, 1)
    assert work["flash"] == (9 * 2 * pairs * 128 * 8, 17 * 2 ** 24, 3)
    rows = 8192 * 8 * 8 / 320
    assert FAMILY.expected_routed_rows(PUBLISHED, 8192) == rows == 1638.4
    gmm_flops, gmm_bytes, gmm_calls = work["expert_gmm"]
    assert gmm_flops == pytest.approx(4 * 18 * rows * 4096 * 1280)
    assert gmm_bytes == pytest.approx(4 * (
        3 * 2 * 8 * 3 * 4096 * 1280
        + 2 * rows * (3 * (4096 + 2 * 1280) + 3 * (1280 + 4096))))
    assert gmm_calls == 24
    # a chunk of 64 at Dk = Dv = 128: 64^2 (5 + 3) 128 + 6 x 64 x 128^2
    assert FAMILY.chunked_scan_flops(64, 128, 128) == 10_485_760
    scan_flops, scan_bytes, scan_calls = work["kda_scan"]
    assert scan_flops == 3 * 3 * (8 * 128) * 10_485_760 == 96_636_764_160
    inputs, out = 8192 * 8 * (128 * 10 + 4), 8192 * 8 * 128 * 2
    assert scan_bytes == 3 * (3 * inputs + 2 * out) == 857_997_312
    assert scan_calls == 6


@pytest.mark.parametrize("change,says", [
    (dict(use_rope=True), "use_rope = False only"),
    (dict(kda_allow_neg_eigval=False), "kda_allow_neg_eigval = True only"),
    (dict(first_k_dense_replace=1), "first_k_dense_replace = 0 only"),
    (dict(gqa_layers=[1]), "not every 4th layer"),
    (dict(num_key_value_heads=2), "do not read 2 K/V heads"),
    (dict(held_heads_start=60), "are not a block of the 64"),
    (dict(held_experts_start=316), "are not a block of the router's 320"),
    (dict(linear_attn_config=PUBLISHED["linear_attn_config"]
          | {"num_kv_heads": 8}), "num_kv_heads is not null"),
    (dict(linear_attn_config=PUBLISHED["linear_attn_config"]
          | {"num_heads": 16}), "different numbers of heads"),
], ids=["rope", "no_negative_eigenvalue", "a_dense_layer", "another_pattern",
        "kv_heads_off", "heads_past_the_end", "experts_past_the_router",
        "linear_kv_heads", "linear_heads_differ"])
def test_sizes_refuses_by_name_what_the_program_cannot_build(change, says):
    with pytest.raises(ValueError, match=says):
        FAMILY.sizes(PUBLISHED | change)


# --- the readers ----------------------------------------------------------------

def traced(ops, scopes, work=None):
    """A context with one chip, two steady steps of 1000 ns and ``ops``
    ``(HLO line, start, duration)``, in tens of nanoseconds, in each."""
    dev, mod = "/device:TPU:0", "jit_train_step(1)"
    rows = [(dev, reduce.MODULES, mod, s, 1000.0)
            for s in (-1000.0, 0.0, 1000.0, 2000.0)]
    for base in (0.0, 1000.0):
        rows += [(dev, reduce.OPS, line, base + 10 * start, 10 * dur)
                 for line, start, dur in ops]
    said = {}
    work = {"kda_scan": (1.0, 819e9 * 60e-9, 6)} if work is None else work
    return {"rows": rows, "chips": reduce.chips_from_rows(rows),
            "spans": {"scopes": scopes, "host": []}, "kernel_work": work,
            "device_kind": "TPU v5 lite", "say": said.update}, said


def test_readers_by_hand():
    """Per step: a q projection 0-10, the convolution 10-15, two ops of the
    scan 15-35 and 35-45 (the second in the backward pass), the head norm
    50-55, the softmax layer's gate projection 60-80, a flash kernel, a
    routed layer's matmul and an unscoped copy."""
    ops = [
        ("%dot.1 = bf16[] fusion()", 0.0, 10.0),
        ("%conv.2 = bf16[] fusion()", 10.0, 5.0),
        ("%while.3 = f32[] fusion()", 15.0, 20.0),
        ("%solve.4 = f32[] fusion()", 35.0, 10.0),
        ("%norm.5 = bf16[] fusion()", 50.0, 5.0),
        ("%dot.6 = bf16[] fusion()", 60.0, 20.0),
        (f"%hvt_flash_fwd.7 = bf16[] {KERNEL}", 80.0, 5.0),
        ("%dot.8 = bf16[] fusion()", 85.0, 5.0),
        ("%copy.9 = bf16[] copy()", 95.0, 5.0),
    ]
    linear = "jit(train_step)/jvp(HybridMoELM)/Block_1/mixer/hvt.kda"
    scopes = {
        ops[0][0]: f"{linear}/proj/q_proj/dot_general",
        ops[1][0]: f"{linear}/conv/mul",
        ops[2][0]: f"{linear}/scan/jit(_gated_delta_rule)/while/dot_general",
        ops[3][0]: ("jit(train_step)/transpose(jvp(HybridMoELM))/Block_1/"
                    "mixer/hvt.kda/scan/checkpoint/triangular_solve"),
        ops[4][0]: f"{linear}/out/o_norm/mul",
        ops[5][0]: "jit(train_step)/jvp(HybridMoELM)/Block_0/mixer/hvt.gqa/"
                   "g_proj/dot_general",
        ops[7][0]: "jit(train_step)/jvp(HybridMoELM)/Block_0/mlp/hvt.moe/"
                   "shared/shared/up/dot_general",
    }
    ctx, said = traced(ops, scopes)
    assert kda_spans.kda_ms_per_step(ctx) * 1e6 == pytest.approx(500.0)
    assert kda_spans.kda_scan_ms_per_step(ctx) * 1e6 == pytest.approx(300.0)
    assert kda_spans.gated_attn_proj_ms_per_step(ctx) * 1e6 == pytest.approx(
        200.0)
    # 60 ns of HBM traffic at peak over 300 ns measured
    assert kda_spans.kda_scan_roofline(ctx) == pytest.approx(20.0)
    assert said["kda_scan_roofline_bound"] == "memory"
    assert said["kda_scan_least_ms"] * 1e6 == pytest.approx(60.0)
    # A later Pallas scan is read by its name, matched whole.
    kernel = (f"%transpose_jvp_hvt_kda_bwd__.1 = f32[] {KERNEL}", 45.0, 5.0)
    ctx, _ = traced(ops + [kernel], scopes)
    assert kda_spans.kda_scan_ms_per_step(ctx) * 1e6 == pytest.approx(350.0)
    assert kda_spans.kda_ms_per_step(ctx) * 1e6 == pytest.approx(550.0)
    assert not kda_spans.is_kda_kernel(f"%hvt_kda_fwd_ring.1 = () {KERNEL}")
    assert not kda_spans.is_kda_kernel("%hvt_kda_fwd.1 = () fusion()")


def test_readers_find_nothing_in_a_program_without_the_layers():
    """The parent's program: no such scope, no such kernel, and a family
    that counts no scan. Nothing is read and nothing raises."""
    ops = [("%dot.1 = f32[] fusion()", 0.0, 50.0),
           (f"%hvt_flash_fwd.8 = bf16[] {KERNEL}", 50.0, 50.0)]
    scopes = {ops[0][0]: "jit(train_step)/jvp(LatentMoELM)/Block_1/attn/"
                         "hvt.mla/q_proj/dot_general"}
    for work in (None, {}):
        ctx, _ = traced(ops, scopes, work)
        for reader in (kda_spans.kda_ms_per_step,
                       kda_spans.kda_scan_ms_per_step,
                       kda_spans.kda_scan_roofline,
                       kda_spans.gated_attn_proj_ms_per_step):
            assert reader(ctx) is None


def test_the_new_metrics_are_reported_in_the_new_cell_only():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = {"kda_ms_per_step", "kda_scan_ms_per_step", "kda_scan_roofline",
           "gated_attn_proj_ms_per_step"}
    shared = {"flash_ms_per_step", "flash_roofline", "flash_fwd_ms_per_step",
              "flash_dq_ms_per_step", "flash_dkv_ms_per_step",
              "moe_ms_per_step", "moe_dispatch_ms_per_step",
              "expert_gmm_ms_per_step", "expert_gmm_roofline"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in new:
        assert by_name[name]["workloads"] == [CELL]
    for name in shared:
        assert by_name[name]["workloads"][-1] == CELL
    reported = {m["name"] for m in run.load_cell(ROOT, CELL)["per_layer"]}
    assert new | shared | {"mfu", "head_ce_ms_per_step"} <= reported
    assert "mla_proj_ms_per_step" not in reported
    for cell in (w["name"] for w in bench["workloads"][:-1]):
        assert not new & {
            m["name"] for m in run.load_cell(ROOT, cell)["per_layer"]}


# --- the controls ------------------------------------------------------------

def test_the_controls_run_through_the_harness_comparison(tmp_path, capsys):
    """chipbench/families/hybrid_moe_lm_control.py at the toy sizes in
    float32: the system passes the cell's limits, the low-precision
    reference and every planted fault read further off than the system
    (whether each passes the limits is a question for the published widths
    on the chip: the readings are beside ``LIMITS``)."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    here = tmp_path / "chipbench"
    (here / "configs" / "solar-open2-250b.json").write_text(json.dumps(TOY))
    traffic = here / "traffic" / "seq8k.gb1.json"
    traffic.write_text(json.dumps(json.loads(traffic.read_text()) | {
        "seq_len": SEQ, "n_sequences": 4}))
    cell = here / "workloads" / f"{CELL}.json"
    workload = json.loads(cell.read_text())
    workload["trainer"]["compute_dtype"] = "float32"
    cell.write_text(json.dumps(workload))
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        CONTROL.main(["--workload", CELL, "--seeds", "5", "--faults", "1"],
                     root=tmp_path)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    by_name = {line["variant"]: line for line in lines if "variant" in line}
    assert set(by_name) == {"system", "low_precision_reference",
                            *CONTROL.FAULTS}
    system = by_name.pop("system")
    assert system["ok"] and system["mean_abs_diff"] < 1e-4
    for name, line in by_name.items():
        assert line["mean_abs_diff"] > 10 * system["mean_abs_diff"], name
    assert lines[-1]["summary"]["system"] == {"runs": 1, "ok": 1}
