"""The family ``latent_moe_lm`` (chipbench/families/latent_moe_lm.py) and its
readers (chipbench/moe_spans.py): the program's `LatentMoELM` against the
family's plain reference at the configuration's ``toy`` sizes on the CPU
(loss AND gradients), the share test of the model-configs guide, the routed
layer under skewed routing, the counts against hand counts, and the readers
on rows small enough to work out by hand."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import moe_spans, reduce, run
from chipbench.traffic import copy_task
from horovod_tpu.models import moe
from horovod_tpu.models.moe import RoutedExperts, SwiGLU

ROOT = pathlib.Path(__file__).resolve().parents[2]
FAMILY = run.load_module(ROOT / "chipbench" / "families" / "latent_moe_lm.py")
PUBLISHED = json.loads(
    (ROOT / "chipbench" / "configs" / "kanana-2-30b-a3b.json").read_text())
TOY = PUBLISHED | PUBLISHED["toy"]  # as the tests' `shrink_to_toy` leaves it
SEQ = 64


def toy_model(dtype="float32", **changes):
    return FAMILY.build(
        TOY | changes, {"compute_dtype": dtype, "fused_head_chunks": 2}, None)


def toy_batch(seed=3):
    return tuple(jnp.asarray(a) for a in copy_task.make(
        seed, {"seq_len": SEQ, "n_sequences": 1}, TOY["vocab_size"]))


@pytest.fixture(scope="module")
def toy_params():
    x, y = toy_batch()
    key = jax.random.PRNGKey(0)
    return toy_model().init({"params": key}, x, labels=y)["params"]


def leaves_with_names(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


# --- the system against the reference --------------------------------------

def test_the_toy_holds_fewer_experts_than_it_routes_over():
    assert TOY["n_routed_experts"] < TOY["n_router_experts"]
    assert TOY["num_experts_per_tok"] < TOY["n_router_experts"]
    assert FAMILY.sizes(TOY)["expert_layers"] == 2


def test_float32_loss_and_gradients_match_the_reference(toy_params):
    x, y = toy_batch()
    model = toy_model()

    def system(params):
        return model.apply({"params": params}, x, labels=y)[0][0]

    def reference(params):
        return FAMILY.per_token_loss(params, x[0], y[0], TOY)

    np.testing.assert_allclose(
        system(toy_params), reference(toy_params), atol=2e-5)
    got = jax.grad(lambda p: system(p).mean())(toy_params)
    want = jax.grad(lambda p: reference(p).mean())(toy_params)
    got, want = leaves_with_names(got), leaves_with_names(want)
    assert got.keys() == want.keys()
    for name in want:
        np.testing.assert_allclose(
            got[name], want[name], atol=2e-6, rtol=2e-4, err_msg=name)
    # The selection bias is no parameter, the router takes a gradient
    # (through the gates), and every held expert saw a token.
    assert not any("bias" in n for n in got)
    routers = [g for n, g in got.items() if "router" in n]
    assert len(routers) == 2 and all(jnp.any(g) for g in routers)
    for name, grad in got.items():
        if "experts_down" in name:
            assert jnp.all(jnp.any(grad != 0, axis=(1, 2))), name


def test_bfloat16_stays_near_the_reference(toy_params):
    """bf16 compute against the float32 reference: the losses within
    rounding for most tokens, and the gradient of the mean loss pointing
    the same way leaf by leaf."""
    x, y = toy_batch()
    model = toy_model("bfloat16")
    got, _ = model.apply({"params": toy_params}, x, labels=y)
    want = FAMILY.per_token_loss(toy_params, x[0], y[0], TOY)
    assert float(jnp.median(jnp.abs(got[0] - want))) < 0.02
    assert abs(float(got.mean() - want.mean())) < 0.02
    g_sys = jax.grad(lambda p: model.apply(
        {"params": p}, x, labels=y)[0].mean())(toy_params)
    g_ref = jax.grad(lambda p: FAMILY.per_token_loss(
        p, x[0], y[0], TOY).mean())(toy_params)
    for (name, a), b in zip(leaves_with_names(g_sys).items(),
                            jax.tree.leaves(g_ref)):
        a, b = np.ravel(a), np.ravel(b)
        cosine = a @ b / (np.linalg.norm(a) * np.linalg.norm(b))
        # (a router's gradient comes through the chosen experts' gates, and
        # bf16 picks another last expert for a few of the 64 tokens)
        assert cosine > (0.8 if "router" in name else 0.9), (name, cosine)


def test_the_reference_sees_a_wrong_rotation_scale_and_share(toy_params):
    """The comparison is tight enough to see the mathematics change: halves
    rotated in place of adjacent pairs is a permutation of both q and k and
    changes nothing, as the family's docstring says; another base, another
    score scale, the gates normalised over the held experts only, or a
    neighbouring block of experts each move the losses."""
    x, y = toy_batch()
    want = FAMILY.per_token_loss(toy_params, x[0], y[0], TOY)
    for change in ({"rope_theta": 10000}, {"qk_head_dim": 16},
                   {"held_experts_start": 4},
                   {"routed_scaling_factor": 1.0}):
        other = FAMILY.per_token_loss(toy_params, x[0], y[0], TOY | change)
        assert float(jnp.abs(other - want).mean()) > 1e-3, change


# --- the share test ---------------------------------------------------------

def routed_layer(n_held, held_start):
    return RoutedExperts(
        n_routed=16, k=3, expert_width=32, shared_width=64,
        n_held=n_held, held_start=held_start, routed_scaling=2.448)


def test_the_shares_add_up_to_the_uncut_layer():
    """The outputs of all 8 shares (2 experts each of 16) hold every routed
    expert's part once and the shared expert, which every chip computes
    alike, eight times: less seven of those they equal the uncut layer's
    output, and the uncut reference's."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 48, 64)), jnp.float32)
    whole = routed_layer(16, 0)
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    want = whole.apply({"params": params}, x)

    def share(start):
        own = dict(params)
        own["experts_gate_up"] = params["experts_gate_up"][start:start + 2]
        own["experts_down"] = params["experts_down"][start:start + 2]
        return routed_layer(2, start).apply({"params": own}, x)

    shared = SwiGLU(64).apply({"params": params["shared"]}, x)
    assert float(jnp.abs(want - shared).mean()) > 0.05  # the routed part
    total = sum(share(start) for start in range(0, 16, 2)) - 7 * shared
    np.testing.assert_allclose(total, want, atol=2e-5)
    config = {"num_experts_per_tok": 3, "moe_intermediate_size": 32,
              "routed_scaling_factor": 2.448, "held_experts_start": 0,
              "n_routed_experts": 16}
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([
            FAMILY._expert_layer(one, params, config) for one in x])
    np.testing.assert_allclose(want, uncut, atol=2e-5)


# --- skewed routing ---------------------------------------------------------

def test_the_level_bias_levels_a_collapsed_router():
    """Tokens that share most of their vector, under a router with large
    weights: every token scores the same few experts highest (a bias of
    zero puts all of them there), and the columns sit at every height of
    the sigmoid. Under `level_bias` each expert has exactly T * k / E tokens
    above zero and the loads are level to within chance; the same bias
    found on the SCORES leaves them far from level (small slopes)."""
    rng = np.random.default_rng(5)
    common = rng.standard_normal(64)
    x = jnp.asarray(common + 0.1 * rng.standard_normal((512, 64)),
                    jnp.float32)
    router = jnp.asarray(rng.standard_normal((64, 16)) / 2, jnp.float32)
    logits = x @ router
    bias = moe.level_bias(logits, 3)
    assert bias.shape == (16,)
    np.testing.assert_array_equal(
        jnp.sum(logits + bias >= 0, axis=0), 512 * 3 // 16)
    np.testing.assert_array_equal(bias, FAMILY._selection_bias(logits, 3))

    def fullest(ranked):  # of 1,536 pairs, 96 an expert if level
        _, chosen = jax.lax.top_k(ranked, 3)
        return np.bincount(np.ravel(chosen), minlength=16).max()

    assert fullest(logits) == 512
    assert fullest(logits + bias) < 1.25 * 96
    scores = jax.nn.sigmoid(logits)
    assert fullest(scores + moe.level_bias(scores, 3)) > 2 * 96


def skewed(monkeypatch, onto_two):
    """128 tokens over 4 held experts of 16, 3 a token: level loads put 96
    rows here and 192 are budgeted. The bias is planted in place of the
    level one, in the program and in the reference alike: ``onto_two`` 1.0
    sends the tokens whose logit on experts 0 and 1 is already high there
    (well over a quarter of the rows on the two), 10.0 sends every token
    there, at least 256 rows."""
    planted = jnp.zeros(16).at[:2].set(onto_two)
    monkeypatch.setattr(moe, "level_bias", lambda logits, k: planted)
    monkeypatch.setattr(FAMILY, "_selection_bias", lambda logits, k: planted)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, 128, 64)), jnp.float32)
    layer = routed_layer(4, 0)
    params = layer.init(jax.random.PRNGKey(2), x)["params"]
    out, sown = layer.apply({"params": params}, x, mutable=["metrics"])
    metrics = {k: float(v[0]) for k, v in sown["metrics"].items()}
    config = {"num_experts_per_tok": 3, "moe_intermediate_size": 32,
              "routed_scaling_factor": 2.448, "held_experts_start": 0,
              "n_routed_experts": 4}
    with jax.default_matmul_precision("highest"):
        want = FAMILY._expert_layer(x[0], params, config)
    return out[0], want, metrics


def test_skewed_routing_under_the_budget_drops_nothing(monkeypatch):
    out, want, metrics = skewed(monkeypatch, 1.0)
    held_rows = metrics["moe_held_rows_share"] * 384
    assert 120 < held_rows <= 192  # well over the 96 expected, in budget
    assert metrics["moe_overflow_rows"] == 0.0
    assert metrics["moe_load_max_over_mean"] > 1.5  # two of four hold most
    np.testing.assert_allclose(out, want, atol=2e-5)


def test_past_the_budget_the_overflow_counter_says_so(monkeypatch):
    out, want, metrics = skewed(monkeypatch, 10.0)
    held_rows = metrics["moe_held_rows_share"] * 384
    assert held_rows >= 256
    assert metrics["moe_overflow_rows"] == pytest.approx(held_rows - 192)
    # ... and what was dropped is missing from the output, not garbage.
    assert bool(jnp.all(jnp.isfinite(out)))
    assert float(jnp.abs(out - want).max()) > 1e-3


# --- counts from shapes -----------------------------------------------------

def test_counts_against_hand_counts():
    """The published widths, 1 + 5 layers, 16 of 128 experts, 16,032 rows
    of the vocabulary, sequences of 8,192."""
    c = PUBLISHED
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["n_router_experts"], c["vocab_size"]) == (6, 16, 128, 16032)
    attn = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    expert_layer = 2048 * 128 + 3 * 2048 * 1536 + 0.75 * 3 * 2048 * 768
    params = 6 * attn + 3 * 2048 * 6144 + 5 * expert_layer + 2048 * 16032
    assert FAMILY.matmul_params_per_token(c) == params == 294846464
    pairs = 8192 * 8193 // 2
    dots = 2 * pairs * 32 * 6  # one dot of width 1 over every layer's heads
    required = FAMILY.required_flops_per_token(c, 8192)
    assert required == pytest.approx(
        6 * params + dots * (3 * 192 + 3 * 128) / 8192)
    assert 3.2e9 < required < 3.4e9
    work = FAMILY.kernel_work(c, 8192, per_chip_batch=1)
    assert work["flash_fwd"] == (dots * (192 + 128),
                                 8192 * 32 * 2 * 6 * (2 * 192 + 2 * 128), 6)
    assert work["flash_dq"][0] == dots * (2 * 192 + 128)
    assert work["flash_dkv"][0] == dots * (2 * 192 + 2 * 128)
    assert work["flash_dkv"][1] == 8192 * 32 * 2 * 6 * (3 * 192 + 4 * 128)
    assert work["flash"] == tuple(
        sum(work[f"flash_{k}"][i] for k in ("fwd", "dq", "dkv"))
        for i in range(3))
    assert work["flash"][2] == 3 * FAMILY.sizes(c)["attention_layers"]
    flops, nbytes, calls = work["expert_gmm"]
    assert flops == 5 * 18 * 6144 * 2048 * 768
    assert nbytes == 5 * (3 * 16 * 3 * 2048 * 768 * 2
                          + 2 * 6144 * (6 * 2048 + 9 * 768))
    assert calls == 30
    # the model the counts are of: 687.5 M parameters at these sizes (no selection bias among them)
    model = FAMILY.build(
        c, {"compute_dtype": "bfloat16", "fused_head_chunks": 8}, None)
    x = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x, labels=x))["params"]
    assert sum(p.size for p in jax.tree.leaves(shapes)) == 687502336


@pytest.mark.parametrize("change,says", [
    ({"q_lora_rank": 1536}, "q_lora_rank = None only"),
    ({"n_group": 8}, "n_group = 1 only"),
    ({"scoring_func": "softmax"}, "scoring_func = 'sigmoid' only"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling = None only"),
    ({"num_key_value_heads": 8}, "num_key_value_heads differs"),
    ({"held_experts_start": 120}, "not a block of the router's 128"),
])
def test_sizes_refuses_by_name_what_the_program_cannot_build(change, says):
    with pytest.raises(ValueError, match=says):
        FAMILY.sizes(PUBLISHED | change)


# --- the readers ------------------------------------------------------------

KERNEL = 'custom-call(), custom_call_target="tpu_custom_call"'


def traced(ops, scopes, calls=2):
    """A context with one chip, two steady steps of 1000 ns and ``ops``
    ``(HLO line, start, duration)``, in tens of nanoseconds, in each."""
    dev, mod = "/device:TPU:0", "jit_train_step(1)"
    rows = [(dev, reduce.MODULES, mod, s, 1000.0)
            for s in (-1000.0, 0.0, 1000.0, 2000.0)]
    for base in (0.0, 1000.0):
        rows += [(dev, reduce.OPS, line, base + 10 * start, 10 * dur)
                 for line, start, dur in ops]
    said = {}
    return {"rows": rows, "chips": reduce.chips_from_rows(rows),
            "spans": {"scopes": scopes, "host": []},
            "kernel_work": {"expert_gmm": (197e12 * 150e-9, 1.0, calls)},
            "device_kind": "TPU v5 lite", "say": said.update}, said


def test_readers_by_hand():
    """Per step: a router matmul 0-10 and a sort 10-15 under route /
    dispatch, two grouped-matmul kernels 20-30 and 30-50 (one of them under
    the transformations' prefixes), an elementwise op 50-55 under experts,
    a shared-expert matmul 55-65, a scatter 65-70 under combine in the
    backward pass, a latent projection 70-90, a flash kernel and an
    unscoped copy."""
    ops = [
        ("%dot.1 = f32[] fusion()", 0.0, 10.0),
        ("%sort.2 = s32[] sort()", 10.0, 5.0),
        (f"%hvt_moe_gmm.3 = bf16[] {KERNEL}", 20.0, 10.0),
        (f"%transpose_jvp_hvt_moe_gmm_dw__.1 = bf16[] {KERNEL}", 30.0, 20.0),
        ("%silu.4 = bf16[] fusion()", 50.0, 5.0),
        ("%dot.5 = bf16[] fusion()", 55.0, 10.0),
        ("%scatter.6 = bf16[] fusion()", 65.0, 5.0),
        ("%dot.7 = bf16[] fusion()", 70.0, 20.0),
        (f"%hvt_flash_fwd.8 = bf16[] {KERNEL}", 90.0, 5.0),
        ("%copy.9 = bf16[] copy()", 95.0, 5.0),
    ]
    inside = "jit(train_step)/jvp(LatentMoELM)/Block_1/mlp/hvt.moe"
    scopes = {
        ops[0][0]: f"{inside}/route/dot_general",
        ops[1][0]: f"{inside}/dispatch/sort",
        ops[4][0]: f"{inside}/experts/mul",
        ops[5][0]: f"{inside}/shared/shared/gate/dot_general",
        ops[6][0]: ("jit(train_step)/transpose(jvp(LatentMoELM))/Block_1/"
                    "mlp/hvt.moe/combine/scatter-add"),
        ops[7][0]: "jit(train_step)/jvp(LatentMoELM)/Block_1/attn/hvt.mla/"
                   "q_proj/dot_general",
    }
    ctx, said = traced(ops, scopes)
    assert moe_spans.moe_ms_per_step(ctx) * 1e6 == pytest.approx(650.0)
    assert moe_spans.moe_dispatch_ms_per_step(ctx) * 1e6 == pytest.approx(200.0)
    assert moe_spans.mla_proj_ms_per_step(ctx) * 1e6 == pytest.approx(200.0)
    assert moe_spans.expert_gmm_ms_per_step(ctx) * 1e6 == pytest.approx(300.0)
    # 150 ns at peak over 300 ns measured
    assert moe_spans.expert_gmm_roofline(ctx) == pytest.approx(50.0)
    assert said["expert_gmm_roofline_bound"] == "compute"
    # A count that is off means the events are not what the reader takes
    # them for; a kernel whose name only begins like one is another kernel.
    ctx, _ = traced(ops, scopes, calls=3)
    assert moe_spans.expert_gmm_ms_per_step(ctx) is None
    assert moe_spans.expert_gmm_roofline(ctx) is None
    assert not moe_spans.is_gmm_kernel(f"%hvt_moe_gmm_ring.1 = () {KERNEL}")
    assert not moe_spans.is_gmm_kernel("%hvt_moe_gmm.1 = () fusion()")


def test_readers_find_nothing_in_a_program_without_the_layers():
    """The parent's program: no such scope, no such kernel, and a family
    that counts no grouped matmul. Nothing is read and nothing raises."""
    ops = [("%dot.1 = f32[] fusion()", 0.0, 50.0),
           (f"%hvt_flash_fwd.8 = bf16[] {KERNEL}", 50.0, 50.0)]
    ctx, _ = traced(ops, {ops[0][0]: "jit(train_step)/jvp(TransformerLM)/"
                                     "Block_0/qkv/dot_general"})
    for with_work in (ctx, ctx | {"kernel_work": {}}):
        for reader in (moe_spans.moe_ms_per_step,
                       moe_spans.moe_dispatch_ms_per_step,
                       moe_spans.mla_proj_ms_per_step,
                       moe_spans.expert_gmm_ms_per_step,
                       moe_spans.expert_gmm_roofline):
            assert reader(with_work) is None


# --- the controls ------------------------------------------------------------

def test_the_controls_run_through_the_harness_comparison(tmp_path, capsys):
    """chipbench/families/latent_moe_lm_control.py at the toy sizes in
    float32: the system passes the cell's limits, the float8 reference and
    every planted fault read further off than the system (whether each
    passes the limits is a question for the published widths on the chip:
    the readings are beside ``LIMITS``)."""
    import shutil

    control = run.load_module(
        ROOT / "chipbench" / "families" / "latent_moe_lm_control.py")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    here = tmp_path / "chipbench"
    (here / "configs" / "kanana-2-30b-a3b.json").write_text(json.dumps(TOY))
    traffic = here / "traffic" / "seq8k.gb1.json"
    traffic.write_text(json.dumps(json.loads(traffic.read_text()) | {
        "seq_len": SEQ, "n_sequences": 4}))
    cell = here / "workloads" / "kanana-2-30b-a3b.seq8k.1chip.json"
    workload = json.loads(cell.read_text())
    workload["trainer"]["compute_dtype"] = "float32"
    cell.write_text(json.dumps(workload))
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        control.main(["--workload", "kanana-2-30b-a3b.seq8k.1chip",
                      "--seeds", "5", "--faults", "1"], root=tmp_path)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    by_name = {line["variant"]: line for line in lines if "variant" in line}
    assert set(by_name) == {"system", "fp8_reference", "wrong_block",
                            "half_dropped", "gates_unscaled", "rope_base_1e4"}
    system = by_name.pop("system")
    assert system["ok"] and system["mean_abs_diff"] < 1e-4
    for name, line in by_name.items():
        assert line["mean_abs_diff"] > 10 * system["mean_abs_diff"], name
    assert lines[-1]["summary"]["system"] == {"runs": 1, "ok": 1}
