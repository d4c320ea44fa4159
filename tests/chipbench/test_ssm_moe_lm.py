"""The family ``ssm_moe_lm`` (chipbench/families/ssm_moe_lm.py) and its
readers (chipbench/ssm_spans.py): the program's `HybridMoELM` with its
state-space kind against the family's plain reference at the
configuration's ``toy`` sizes on the CPU (loss AND gradients), the control
and the faults the reference sees, the share tests of the model-configs
guide (the experts, the softmax heads; the Mamba heads with their norm's
exchange are tests/test_ssm_moe_model.py's, and here against the
reference), the counts against hand counts, the readers on rows small
enough to work out by hand, and that the two routed models the benchmark
had are the parent's."""

import hashlib
import json
import pathlib
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import kda_spans, reduce, remat_spans, run, ssm_spans
from chipbench.traffic import copy_task
from horovod_tpu.models.hybrid_moe_lm import (
    GatedAttention, StateSpaceMixer, StateSpaceSizes)
from horovod_tpu.models.moe import RoutedExperts, SwiGLU

ROOT = pathlib.Path(__file__).resolve().parents[2]
FAMILIES = ROOT / "chipbench" / "families"
FAMILY = run.load_module(FAMILIES / "ssm_moe_lm.py")
CONTROL = run.load_module(FAMILIES / "ssm_moe_lm_control.py")
PUBLISHED = json.loads(
    (ROOT / "chipbench" / "configs" / "granite-4.0-h-small.json").read_text())
TOY = PUBLISHED | PUBLISHED["toy"]  # as the tests' `shrink_to_toy` leaves it
# Three layers of the ten, both kinds: what most tests here trace.
SHORT = TOY | {"num_hidden_layers": 3,
               "layer_types": ["mamba", "attention", "mamba"]}
CELL = "granite-4.0-h-small.seq4k.1chip"
SEQ = 64
KERNEL = f"custom-call(), {reduce.KERNEL_MARK}"
TRAINER = {"compute_dtype": "float32", "fused_head_chunks": 2,
           "remat": "block"}


def toy_model(dtype="float32", config=SHORT):
    return FAMILY.build(config, TRAINER | {"compute_dtype": dtype}, None)


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
    return (model or toy_model()).apply(
        {"params": params}, x, labels=y, mutable=["metrics"])[0][0][0]


def reference_loss(params, config=SHORT, **kwargs):
    x, y = toy_batch()
    return FAMILY.per_token_loss(params, x[0], y[0], config, **kwargs)


# --- the system against the reference --------------------------------------

def test_the_toy_is_one_period_of_a_share():
    kinds = ("ssm",) * 5 + ("softmax",) + ("ssm",) * 4
    assert FAMILY.layer_kinds(TOY) == FAMILY.layer_kinds(PUBLISHED) == kinds
    assert TOY["mamba_n_heads"] < TOY["published_heads"]["mamba"]
    assert TOY["num_attention_heads"] < TOY["published_heads"]["softmax"]
    assert TOY["num_local_experts"] < TOY["n_router_experts"]
    assert FAMILY.sizes(TOY) == {
        "vocab_size": 128, "max_positions": 64, "attention_layers": 1,
        "ssm_layers": 9, "expert_layers": 10}
    assert FAMILY.sizes(PUBLISHED)["vocab_size"] == 12544
    assert FAMILY.attention_head_dim(PUBLISHED) == 128


def test_the_whole_toy_period_matches_the_reference():
    """All ten layers, every block rematerialised, the tied head."""
    x, y = toy_batch()
    model = toy_model(config=TOY)
    params = model.init({"params": jax.random.PRNGKey(0)}, x, labels=y)[
        "params"]
    assert "lm_head" not in params and len(params) == 12
    np.testing.assert_allclose(
        system_loss(params, model), reference_loss(params, TOY), atol=5e-5)


def test_float32_loss_and_gradients_match_the_reference(toy_params):
    got, want = system_loss(toy_params), reference_loss(toy_params)
    np.testing.assert_allclose(got, want, atol=3e-5)
    got = leaves_with_names(
        jax.grad(lambda p: system_loss(p).mean())(toy_params))
    want = leaves_with_names(
        jax.grad(lambda p: reference_loss(p).mean())(toy_params))
    assert set(got) == set(want) and len(got) > 50
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

    assert off(state_only) > 1e-6  # (logits / 16: every loss is near ln V)
    assert off(both) > 3 * off(system)


@pytest.mark.parametrize("fault", sorted(CONTROL.FAULTS))
def test_the_reference_sees_a_planted_fault(toy_params, fault):
    sound = system_loss(toy_params)
    with CONTROL.planted(fault):
        faulty = system_loss(toy_params)
    again = system_loss(toy_params)  # and the fault is gone afterwards
    want = reference_loss(toy_params)
    np.testing.assert_allclose(again, sound, atol=1e-6)
    # (logits / 16 keep every toy loss near ln V, so far off is 5e-4 here;
    # an inverted decay may not stay finite: that is far off too)
    assert not float(jnp.abs(faulty - want).mean()) < 5e-4
    assert float(jnp.abs(sound - want).mean()) < 1e-5


# --- the shares add up --------------------------------------------------------

def test_the_uncut_mamba_layer_is_the_references():
    """The whole layer (all 8 heads, so the norm is over all its channels
    either way) against the reference's token-by-token recurrence; the
    eight shares of it adding up under ``heads_axis`` is
    tests/test_ssm_moe_model.py's."""
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 48, 64)),
                    jnp.float32)
    layer = StateSpaceMixer(
        StateSpaceSizes(8, 8, 0, 16, 16, 4, 32), 1e-5, jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    # every parameter off its initial value, the skip and the biases too
    params = jax.tree.map(
        lambda a: a + 0.1 * jnp.cos(jnp.arange(a.size, dtype=jnp.float32)
                                    ).reshape(a.shape), params)
    want = layer.apply({"params": params}, x)
    config = {"mamba_d_state": 16, "rms_norm_eps": 1e-5}
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([FAMILY._state_space(
            one, params, config, jnp.float32) for one in x])
    np.testing.assert_allclose(want, uncut, atol=3e-5)


def test_the_four_head_shares_of_the_softmax_layer_add_up():
    """8 query heads over 4 K/V heads, ungated and scaled by
    ``attention_multiplier``: a group of two a share with the K/V head it
    reads."""
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 64, 64)),
                    jnp.float32)

    def layer(held, start):
        return GatedAttention(8, 4, held, start, 16, jnp.float32, gate=False,
                              scale=0.0625)

    params = layer(8, 0).init(jax.random.PRNGKey(2), x)["params"]
    want = layer(8, 0).apply({"params": params}, x)

    def share(start):
        own = {"q_proj": {"kernel": params["q_proj"]["kernel"][
            :, start:start + 2]}, "o_proj": params["o_proj"][start:start + 2]}
        for n in ("k_proj", "v_proj"):
            own[n] = {"kernel": params[n]["kernel"][
                :, start // 2:start // 2 + 1]}
        return layer(2, start).apply({"params": own}, x)

    np.testing.assert_allclose(
        sum(share(start) for start in range(0, 8, 2)), want, atol=2e-5)
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([FAMILY._attention(
            one, params, {"attention_multiplier": 0.0625}) for one in x])
    np.testing.assert_allclose(want, uncut, atol=2e-5)


def test_the_nine_expert_shares_add_up_to_the_uncut_layer():
    """Nine shares of two experts each of 18, softmax gates over the three
    chosen: every routed expert's part once and the shared expert, which
    every chip computes alike, nine times: less 8 of those they equal the
    uncut layer's output, and the uncut reference's."""
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 48, 64)),
                    jnp.float32)

    def layer(held, start):
        return RoutedExperts(
            n_routed=18, k=3, expert_width=32, shared_width=48, n_held=held,
            held_start=start, routed_scaling=1.0, compute_dtype=jnp.float32,
            scoring="softmax")

    params = layer(18, 0).init(jax.random.PRNGKey(3), x)["params"]
    want = layer(18, 0).apply({"params": params}, x)

    def share(start):
        own = dict(params)
        own["experts_gate_up"] = params["experts_gate_up"][start:start + 2]
        own["experts_down"] = params["experts_down"][start:start + 2]
        return layer(2, start).apply({"params": own}, x)

    shared = SwiGLU(48).apply({"params": params["shared"]}, x)
    assert float(jnp.abs(want - shared).mean()) > 0.05  # the routed part
    total = sum(share(start) for start in range(0, 18, 2)) - 8 * shared
    np.testing.assert_allclose(total, want, atol=1e-4)
    config = {"num_experts_per_tok": 3, "intermediate_size": 32,
              "held_experts_start": 0, "num_local_experts": 18}
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([
            FAMILY._expert_layer(one, params, config) for one in x])
    np.testing.assert_allclose(want, uncut, atol=3e-5)


# --- counts -------------------------------------------------------------------

def count(tree):
    return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))


def test_the_cell_holds_the_parameters_the_cut_says():
    """1,126,717,104 parameters at the published widths (shapes only): nine
    Mamba blocks of 108,379,440, one softmax block of 99,917,824, the tied
    table's 12,544 x 4,096 once, and the last norm."""
    model = FAMILY.build(
        PUBLISHED, {"compute_dtype": "bfloat16", "fused_head_chunks": 8,
                    "remat": "block"}, None)
    tokens = jnp.zeros((1, 256), jnp.int32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens, labels=tokens))[
            "params"]
    mamba = (4096 * (1024 + 1024 + 256 + 16) + 1024 * 4096
             + 4 * 1280 + 1280 + 1024 + 3 * 16)  # ..., taps, bias, norm, scalars
    softmax = 2 * 4096 * 512 + 2 * 4096 * 128
    moe = 4096 * 72 + 3 * 4096 * 1536 + 8 * 3 * 4096 * 768
    assert count(shapes["Block_0"]["mixer"]) == mamba == 13_704_496
    assert count(shapes["Block_5"]["mixer"]) == softmax == 5_242_880
    assert count(shapes["Block_2"]["mlp"]) == moe == 94_666_752
    assert count(shapes["Block_9"]) == mamba + moe + 2 * 4096 == 108_379_440
    assert count(shapes["embed"]) == 12544 * 4096 and "lm_head" not in shapes
    assert count(shapes) == PUBLISHED["n_parameters"] == 1_126_717_104


def test_counts_against_hand_counts():
    params = (9 * (4096 * 2320 + 1024 * 4096)   # z x B|C dt; W_out
              + 5_242_880                       # q o, one K and V head
              + 10 * (294_912 + 18_874_368 + 10 * 8 / 72 * 9_437_184)
              + 4096 * 12544)                   # the tied head, once
    assert FAMILY.matmul_params_per_token(PUBLISHED) == pytest.approx(params)
    assert params == pytest.approx(476_446_720)
    pairs = 4096 * 4097 // 2
    dots = 6 * 2 * pairs * 128 * 4 / 4096
    scan = 3 * 4 * 64 * 128 * 16 * 9
    assert FAMILY.scan_required_flops_per_token(PUBLISHED) == scan
    assert FAMILY.required_flops_per_token(PUBLISHED, 4096) == pytest.approx(
        6 * params + dots + scan)
    work = FAMILY.kernel_work(PUBLISHED, 4096, 1)
    assert set(work) == {"flash_fwd", "expert_gmm", "ssd_scan"}
    # every block's forward runs twice: two forward flash calls a layer
    assert work["flash_fwd"] == (
        2 * 2 * 2 * pairs * 128 * 4, 2 * 4 * 4096 * 4 * 128 * 2, 2)
    rows = 4096 * 10 * 8 / 72
    assert FAMILY.expected_routed_rows(PUBLISHED, 4096) == rows
    gmm_flops, gmm_bytes, gmm_calls = work["expert_gmm"]
    assert gmm_flops == pytest.approx(10 * 24 * rows * 4096 * 768)
    assert gmm_bytes == pytest.approx(10 * (
        4 * 2 * 8 * 3 * 4096 * 768
        + 2 * rows * (2 * ((4096 + 1536) + (768 + 4096))
                      + 2 * (4096 + 768) + 2 * (1536 + 4096))))
    assert gmm_calls == 80
    # a chunk of 256: C B^T once, and a head the pairs, the state, the read
    chunk = 2 * 256 * 256 * 128 + 16 * (2 * 256 * 256 * 64
                                        + 4 * 256 * 64 * 128)
    assert FAMILY.chunked_scan_flops(256, 16, 64, 128) == chunk == 285_212_672
    scan_flops, scan_bytes, scan_calls = work["ssd_scan"]
    assert scan_flops == 9 * 4 * 16 * chunk
    inputs = 4096 * (1024 * 2 + 2 * 128 * 2 + 16 * 4)
    out = 4096 * 1024 * 4
    assert scan_bytes == 9 * (2 * (inputs + out) + 2 * inputs + out)
    assert scan_calls == 27
    plain = FAMILY.kernel_work(
        PUBLISHED | {"activation_checkpointing": None}, 4096, 1)
    assert plain["flash_fwd"][2] == 1 and plain["expert_gmm"][2] == 60
    assert plain["ssd_scan"][0] == 9 * 3 * 16 * chunk


@pytest.mark.parametrize("change,says", [
    (dict(position_embedding_type="rope"),
     "position_embedding_type = 'nope' only"),
    (dict(tie_word_embeddings=False), "tie_word_embeddings = True only"),
    (dict(mamba_n_groups=8), "mamba_n_groups = 1 only"),
    (dict(layer_types=["mamba"] * 9), "layer_types has 9 entries"),
    (dict(layer_types=["mamba"] * 9 + ["window"]), r"\['window'\]"),
    (dict(num_key_value_heads=2), "do not read 2 K/V heads"),
    (dict(held_heads_start={"mamba": 120, "softmax": 0}),
     "are not a block of the 128 mamba heads"),
    (dict(held_experts_start=70), "are not a block of the router's 72"),
    (dict(mamba_expand=3), "are not mamba_expand x hidden_size"),
    (dict(activation_checkpointing="attention"),
     "activation_checkpointing is null or 'block'"),
], ids=["rope", "untied", "groups", "too_few_kinds", "unknown_kind",
        "kv_heads_off", "heads_past_the_end", "experts_past_the_router",
        "another_expansion", "another_policy"])
def test_sizes_refuses_by_name_what_the_program_cannot_build(change, says):
    with pytest.raises(ValueError, match=says):
        FAMILY.sizes(PUBLISHED | change)


def test_the_trainers_remat_has_to_be_the_configurations():
    with pytest.raises(ValueError, match="the two have to agree"):
        FAMILY.build(TOY, TRAINER | {"remat": None}, None)
    plain = FAMILY.build(TOY | {"activation_checkpointing": None},
                         {k: v for k, v in TRAINER.items() if k != "remat"},
                         None)
    assert not plain.remat and toy_model().remat


# --- what the benchmark had is the parent's -----------------------------------

@pytest.mark.parametrize("family,config,leaves,tree,total,first", [
    ("hybrid_moe_lm", "solar-open2-250b", 85, "1a2fe048ec523d62",
     343.364990234375, [6.3929362297058105, 4.890588760375977,
                        5.8465423583984375, 4.7873640060424805]),
    ("latent_moe_lm", "kanana-2-30b-a3b", 39, "f081c8ed817119b0",
     343.7198791503906, [5.764216423034668, 4.709526062011719,
                         4.874329090118408, 4.712345123291016]),
], ids=["solar", "kanana"])
def test_the_routed_models_that_were_there_are_the_parents(
        family, config, leaves, tree, total, first):
    """The toy model of each routed configuration the benchmark had, built
    by its untouched family file: the parameter tree (names and shapes)
    and the per-token losses on a seed as recorded on the parent commit
    (f017621, this CPU backend): the new fields' defaults change nothing."""
    module = run.load_module(FAMILIES / f"{family}.py")
    published = json.loads(
        (ROOT / "chipbench" / "configs" / f"{config}.json").read_text())
    toy = published | published["toy"]
    model = module.build(
        toy, {"compute_dtype": "float32", "fused_head_chunks": 2}, None)
    x, y = (jnp.asarray(a) for a in copy_task.make(
        3, {"seq_len": 64, "n_sequences": 1}, toy["vocab_size"]))
    params = model.init({"params": jax.random.PRNGKey(0)}, x, labels=y)[
        "params"]
    names = sorted(
        jax.tree_util.keystr(path) + str(leaf.shape) for path, leaf in
        jax.tree_util.tree_flatten_with_path(params)[0])
    assert len(names) == leaves
    assert hashlib.sha256("\n".join(names).encode()).hexdigest()[:16] == tree
    loss = model.apply({"params": params}, x, labels=y,
                       mutable=["metrics"])[0][0][0]
    np.testing.assert_allclose(loss[:4], first, rtol=2e-6)
    assert float(loss.sum()) == pytest.approx(total, rel=2e-6)


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
    work = {"ssd_scan": (1.0, 819e9 * 80e-9, 27)} if work is None else work
    return {"rows": rows, "chips": reduce.chips_from_rows(rows),
            "spans": {"scopes": scopes, "host": []}, "kernel_work": work,
            "device_kind": "TPU v5 lite", "say": said.update}, said


def test_readers_by_hand():
    """Per step: the x projection 0-10, the convolution 10-15, the scan's
    masks 15-35, the same in the rematerialised forward 35-55 and a
    backward op of it 55-65, the gated norm 70-75, an attention projection,
    a flash kernel, a routed layer's matmul and an unscoped copy."""
    ops = [
        ("%dot.1 = bf16[] fusion()", 0.0, 10.0),
        ("%conv.2 = bf16[] fusion()", 10.0, 5.0),
        ("%exp.3 = f32[] fusion()", 15.0, 20.0),
        ("%exp.4 = f32[] fusion()", 35.0, 20.0),
        ("%dot.5 = f32[] fusion()", 55.0, 10.0),
        ("%norm.6 = bf16[] fusion()", 70.0, 5.0),
        ("%dot.7 = bf16[] fusion()", 75.0, 5.0),
        (f"%hvt_flash_fwd.8 = bf16[] {KERNEL}", 80.0, 5.0),
        ("%dot.9 = bf16[] fusion()", 85.0, 5.0),
        ("%copy.10 = bf16[] copy()", 95.0, 5.0),
    ]
    mixer = "jit(train_step)/jvp(HybridMoELM)/Block_1/mixer/hvt.ssm"
    back = ("jit(train_step)/transpose(jvp(HybridMoELM))/jvp(HybridMoELM)/"
            "checkpoint/rematted_computation/Block_1/mixer/hvt.ssm")
    scopes = {
        ops[0][0]: f"{mixer}/proj/x_proj/dot_general",
        ops[1][0]: f"{mixer}/conv/mul",
        ops[2][0]: f"{mixer}/scan/jit(ssd_scan)/exp",
        ops[3][0]: f"{back}/scan/jit(ssd_scan)/exp",
        ops[4][0]: ("jit(train_step)/transpose(jvp(HybridMoELM))/"
                    "jvp(HybridMoELM)/checkpoint/Block_1/mixer/hvt.ssm/scan/"
                    "jit(ssd_scan)/dot_general"),
        ops[5][0]: f"{mixer}/out/mul",
        ops[6][0]: "jit(train_step)/jvp(HybridMoELM)/Block_5/mixer/hvt.gqa/"
                   "q_proj/dot_general",
        ops[8][0]: "jit(train_step)/jvp(HybridMoELM)/Block_0/mlp/hvt.moe/"
                   "shared/shared/up/dot_general",
    }
    ctx, said = traced(ops, scopes)
    assert ssm_spans.ssm_ms_per_step(ctx) * 1e6 == pytest.approx(700.0)
    assert ssm_spans.ssd_scan_ms_per_step(ctx) * 1e6 == pytest.approx(500.0)
    # 80 ns of HBM traffic at peak over 500 ns measured
    assert ssm_spans.ssd_scan_roofline(ctx) == pytest.approx(16.0)
    assert said["ssd_scan_roofline_bound"] == "memory"
    assert said["ssd_scan_least_ms"] * 1e6 == pytest.approx(80.0)
    # the rematerialised forward, by its path: the scan's second pass here
    assert remat_spans.recompute_ms_per_step(ctx) * 1e6 == pytest.approx(
        200.0)
    # The attention layer's projections are under Solar's scope: the same
    # module ungated, so the cell is on that metric's list too.
    assert kda_spans.gated_attn_proj_ms_per_step(ctx) * 1e6 == pytest.approx(
        50.0)
    # A later Pallas scan is read by its name's beginning.
    kernel = (f"%transpose_jvp_hvt_ssd_bwd__.1 = f32[] {KERNEL}", 65.0, 5.0)
    ctx, _ = traced(ops + [kernel], scopes)
    assert ssm_spans.ssd_scan_ms_per_step(ctx) * 1e6 == pytest.approx(550.0)
    assert ssm_spans.ssm_ms_per_step(ctx) * 1e6 == pytest.approx(750.0)
    assert ssm_spans.is_ssd_kernel(f"%hvt_ssd_fwd.3 = () {KERNEL}")
    assert not ssm_spans.is_ssd_kernel(f"%hvt_kda_fwd.1 = () {KERNEL}")
    assert not ssm_spans.is_ssd_kernel("%hvt_ssd_fwd.1 = () fusion()")


def test_readers_find_nothing_in_a_program_without_the_layer():
    """The parent's program: no such scope, no such kernel, and a family
    that counts no scan. Nothing is read and nothing raises."""
    ops = [("%dot.1 = f32[] fusion()", 0.0, 50.0),
           (f"%hvt_kda_fwd.8 = bf16[] {KERNEL}", 50.0, 50.0)]
    scopes = {ops[0][0]: "jit(train_step)/jvp(HybridMoELM)/Block_1/mixer/"
                         "hvt.kda/scan/dot_general"}
    for work in (None, {}):
        ctx, _ = traced(ops, scopes, work)
        for reader in (ssm_spans.ssm_ms_per_step,
                       ssm_spans.ssd_scan_ms_per_step,
                       ssm_spans.ssd_scan_roofline,
                       remat_spans.recompute_ms_per_step):
            assert reader(ctx) is None


def _layer_metrics_of(cell):
    """The per-layer metrics that read something in a cell's program, by
    what the cell's family counts (`sizes`) and its trainer asks for, not
    by the cell's name: a later cell of a family is held to the same."""
    sizes = cell["family"].sizes(cell["config"])
    hybrid_stack = "linear_layers" in sizes or "ssm_layers" in sizes
    found = set()
    if cell["config"]["family"] == "latent_moe_lm":
        found |= {"mla_proj_ms_per_step"}
    if sizes.get("linear_layers"):
        found |= {"kda_ms_per_step", "kda_scan_ms_per_step",
                  "kda_scan_roofline"}
    if sizes.get("ssm_layers"):
        found |= {"ssm_ms_per_step", "ssd_scan_ms_per_step",
                  "ssd_scan_roofline"}
    if hybrid_stack and sizes["attention_layers"]:  # the scope `hvt.gqa`
        found |= {"gated_attn_proj_ms_per_step"}
    if sizes.get("expert_layers"):
        found |= {"moe_ms_per_step", "moe_dispatch_ms_per_step",
                  "expert_gmm_ms_per_step", "expert_gmm_roofline"}
    if cell["workload"]["trainer"].get("remat"):
        found |= {"recompute_ms_per_step"}
    return found


def test_the_new_metrics_are_reported_in_the_new_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = {"ssm_ms_per_step", "ssd_scan_ms_per_step", "ssd_scan_roofline",
           "recompute_ms_per_step"}
    shared = {"flash_fwd_ms_per_step", "moe_ms_per_step",
              "moe_dispatch_ms_per_step", "expert_gmm_ms_per_step",
              "expert_gmm_roofline", "gated_attn_proj_ms_per_step"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in new | shared:
        assert CELL in by_name[name]["workloads"], name
    # the four readers that fell silent in PR 38 are left as they were
    for name in ("flash_ms_per_step", "flash_roofline",
                 "flash_dq_ms_per_step", "flash_dkv_ms_per_step"):
        assert CELL not in by_name[name]["workloads"]
    cell = run.load_cell(ROOT, CELL)
    reported = {m["name"] for m in cell["per_layer"]}
    assert new | shared | {"mfu", "head_ce_ms_per_step"} <= reported
    assert new | shared - {"flash_fwd_ms_per_step"} == _layer_metrics_of(cell)
    assert not {"mla_proj_ms_per_step", "kda_ms_per_step"} & reported


def test_each_familys_metrics_are_reported_in_its_own_cells_only():
    """What test_hybrid_moe_lm.py's test of the same name checked while
    Solar's was the last cell (conftest.py beside this file says why that
    one is expected to fail), for every cell, in any order and without a
    cell's name: a metric of a layer kind lists the cells whose programs
    hold that kind, in the benchmark's order, and no other; a cell reports
    `mfu` and the head's time whatever its family."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: run.load_cell(ROOT, w["name"])
             for w in bench["workloads"]}
    reads = {name: _layer_metrics_of(cell) for name, cell in cells.items()}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for metric in sorted(set().union(*reads.values())):
        assert by_name[metric]["workloads"] == [
            name for name in cells if metric in reads[name]], metric
    layered = set().union(*reads.values())
    for name, cell in cells.items():
        reported = {m["name"] for m in cell["per_layer"]}
        assert {"mfu", "head_ce_ms_per_step"} <= reported
        assert reported & layered == reads[name], name


def test_the_four_dp4_metrics_keep_their_readers_and_entries():
    """What test_reduction_spans.py checked while its four metrics were the
    last entries of ``per_layer`` (conftest.py): they are there in their
    order, one after the other, each with its reader, on dp4 only."""
    from chipbench import reduction_spans

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    metrics = ("reduction_bytes_per_step", "reduction_wait_ms_per_step",
               "reduction_host_ms_per_step", "optimizer_hosted_ms_per_step")
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(metrics[0])
    assert tuple(names[first:first + 4]) == metrics
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in metrics:
        spec = json.loads((ROOT / "chipbench" / "layer_metrics"
                           / f"{name}.json").read_text())
        assert spec["reader"] == f"reduction_spans.py:{name}"
        assert spec["unit"] == entries[name]["unit"]
        assert callable(getattr(reduction_spans, name))
        assert entries[name]["workloads"] == ["cerebras-gpt-1.3b.seq2k.dp4"]
        assert entries[name]["layer"] == "reduction (implicit SPMD all-reduce)"
        assert entries[name]["moves"] == "tokens_per_s"
    assert entries["reduction_bytes_per_step"]["source"] == "program_counter"


# --- the controls ------------------------------------------------------------

def test_the_controls_run_through_the_harness_comparison(tmp_path, capsys):
    """chipbench/families/ssm_moe_lm_control.py at toy sizes (three layers)
    in float32: the system passes the cell's limits, the low-precision
    reference and every planted fault read further off than the system
    (whether each passes the limits is a question for the published widths
    on the chip: the readings are beside ``LIMITS``)."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    here = tmp_path / "chipbench"
    (here / "configs" / "granite-4.0-h-small.json").write_text(
        json.dumps(SHORT))
    traffic = here / "traffic" / "seq4k.gb1.json"
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
        assert not line["mean_abs_diff"] < 10 * system["mean_abs_diff"], name
    assert lines[-1]["summary"]["system"] == {"runs": 1, "ok": 1}
