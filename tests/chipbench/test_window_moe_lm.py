"""The family ``window_moe_lm`` (chipbench/families/window_moe_lm.py) and its
reader (chipbench/window_spans.py): the program's `HybridMoELM` with its
full and sliding-window softmax kinds against the family's plain reference
at the configuration's ``toy`` sizes on the CPU (loss AND gradients), the
window's edge and the rotary's table at the published values, the control
and the faults the reference sees, the share test of the model-configs
guide, the counts against hand counts, the reader on rows small enough to
work out by hand, and that the routed models the benchmark had are the
parent's."""

import hashlib
import json
import math
import pathlib
import re
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, reduce, run, window_spans
from chipbench.traffic import copy_task
from horovod_tpu.models import hybrid_moe_lm as hybrid
from horovod_tpu.models.hybrid_moe_lm import GatedAttention
from horovod_tpu.models.moe import RoutedExperts, SwiGLU
from horovod_tpu.models.transformer import (
    Rotary, YaRN, partial_rope, rotary_inv_freq)
from horovod_tpu.obs import prom
from horovod_tpu.ops import flash_attention as fa

ROOT = pathlib.Path(__file__).resolve().parents[2]
FAMILIES = ROOT / "chipbench" / "families"
FAMILY = run.load_module(FAMILIES / "window_moe_lm.py")
CONTROL = run.load_module(FAMILIES / "window_moe_lm_control.py")
PUBLISHED = json.loads(
    (ROOT / "chipbench" / "configs" / "laguna-xs.2.json").read_text())
TOY = PUBLISHED | PUBLISHED["toy"]  # as the tests' `shrink_to_toy` leaves it
CELL = "laguna-xs.2.seq8k.1chip"
SEQ = 64
KERNEL = f"custom-call(), {reduce.KERNEL_MARK}"
TRAINER = {"compute_dtype": "float32", "fused_head_chunks": 2,
           "remat": "block"}
FULL_ROPE = PUBLISHED["rope_parameters"]["full_attention"]
PUBLISHED_YARN = Rotary(64, 500000.0, YaRN(
    64.0, 4096, 64.0, 1.0, FULL_ROPE["attention_factor"]))


def toy_model(dtype="float32", config=TOY):
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


def reference_loss(params, config=TOY):
    x, y = toy_batch()
    return FAMILY.per_token_loss(params, x[0], y[0], config)


def count(tree):
    return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))


# --- the system against the reference --------------------------------------

def test_the_toy_is_one_period_of_a_share():
    kinds = ("softmax", "window", "window", "window", "softmax")
    assert FAMILY.layer_kinds(TOY) == FAMILY.layer_kinds(PUBLISHED) == kinds
    assert TOY["num_experts"] < TOY["n_router_experts"]
    assert TOY["sliding_window"] < SEQ  # the band bites in the toy too
    want = {"vocab_size": 128, "max_positions": 64, "attention_layers": 5,
            "window_layers": 3, "dense_layers": 1, "expert_layers": 4,
            "linear_layers": 0, "ssm_layers": 0}
    assert FAMILY.sizes(TOY) == want
    assert FAMILY.sizes(PUBLISHED) == want | {
        "vocab_size": 12544, "max_positions": 262144}
    assert FAMILY.heads_of(PUBLISHED, FAMILY.FULL) == 48
    assert FAMILY.heads_of(PUBLISHED, FAMILY.SLIDING) == 64
    assert FAMILY.rotary_dims(PUBLISHED, FAMILY.FULL) == 64
    assert FAMILY.rotary_dims(PUBLISHED, FAMILY.SLIDING) == 128


def test_the_published_head_counts_build_the_published_shapes():
    """766,531,584 parameters at the published widths (shapes only): each
    kind's own query heads over the 8 K/V heads, the dense layer 0, 32 of
    256 experts, 12,544 rows of an untied table and head."""
    model = FAMILY.build(
        PUBLISHED, {"compute_dtype": "bfloat16", "fused_head_chunks": 8,
                    "remat": "block"}, None)
    tokens = jnp.zeros((1, 256), jnp.int32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens, labels=tokens))[
            "params"]
    shape = jax.tree.map(lambda a: a.shape, shapes)
    for block, heads in (("Block_0", 48), ("Block_1", 64), ("Block_3", 64),
                         ("Block_4", 48)):
        mixer = shape[block]["mixer"]
        assert mixer["q_proj"]["kernel"] == (2048, heads, 128)
        assert mixer["g_proj"]["kernel"] == (2048, heads, 128)
        assert mixer["k_proj"]["kernel"] == mixer["v_proj"]["kernel"] == (
            2048, 8, 128)
        assert mixer["o_proj"] == (heads, 128, 2048)
    assert shape["Block_0"]["mlp"]["gate"]["kernel"] == (2048, 8192)
    assert shape["Block_1"]["mlp"]["experts_gate_up"] == (32, 2048, 1024)
    assert shape["Block_1"]["mlp"]["router"] == (2048, 256)
    assert shape["Block_4"]["mlp"]["shared"]["up"]["kernel"] == (2048, 512)
    assert shape["lm_head"]["kernel"] == (2048, 12544)
    full, window = 41_943_040, 54_525_952
    assert count(shapes["Block_0"]["mixer"]) == FAMILY.mixer_params(
        PUBLISHED, FAMILY.FULL) == full
    assert count(shapes["Block_2"]["mixer"]) == window
    assert count(shapes["Block_0"]["mlp"]) == 50_331_648
    assert count(shapes["Block_3"]["mlp"]) == 104_333_312
    assert count(shapes) == PUBLISHED["n_parameters"] == 766_531_584


def test_the_whole_toy_period_matches_the_reference(toy_params):
    """Both kinds, the dense layer, every block rematerialised."""
    np.testing.assert_allclose(
        system_loss(toy_params), reference_loss(toy_params), atol=3e-5)


def test_the_logits_are_the_references(toy_params):
    """The program's logits (no labels) against the reference's own
    layers, run as its `per_token_loss` runs them."""
    x, _ = toy_batch()
    got = toy_model().apply({"params": toy_params}, x,
                            mutable=["metrics"])[0]
    eps, p = TOY["rms_norm_eps"], toy_params
    with jax.default_matmul_precision("highest"):
        h = p["embed"]["embedding"][x[0]]
        for n, (kind, mlp) in enumerate(zip(TOY["layer_types"],
                                            TOY["mlp_layer_types"])):
            b = p[f"Block_{n}"]
            h = h + FAMILY._attention(FAMILY._rms_norm(
                h, b["mixer_norm"]["scale"], eps), b["mixer"], TOY, kind)
            normed = FAMILY._rms_norm(h, b["mlp_norm"]["scale"], eps)
            h = h + (FAMILY._swiglu(normed, *(
                b["mlp"][w]["kernel"] for w in ("gate", "up", "down")))
                if mlp == FAMILY.DENSE
                else FAMILY._expert_layer(normed, b["mlp"], TOY))
        want = FAMILY._rms_norm(h, p["final_norm"]["scale"], eps) @ p[
            "lm_head"]["kernel"]
    assert got.shape == (1, SEQ, TOY["vocab_size"])
    np.testing.assert_allclose(got[0], want, atol=3e-5)


def test_float32_gradients_match_the_reference(toy_params):
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


def test_bfloat16_stays_near_and_the_low_precision_control_further(
        toy_params):
    want = reference_loss(toy_params)
    system = system_loss(toy_params, toy_model("bfloat16"))
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32), toy_params)
    control = reference_loss(rounded)

    def off(a):
        return np.abs(np.asarray(a, np.float32) - np.asarray(want))

    # (gates of 8 chosen scaled by 2.5: where bf16 chooses another expert
    # for a token, its loss moves by tenths; half the tokens move less)
    assert 1e-4 < np.median(off(system)) < 0.1 and off(system).mean() < 0.3
    assert off(control).mean() > 2 * off(system).mean()


@pytest.mark.parametrize("fault", sorted(CONTROL.FAULTS))
def test_the_reference_sees_a_planted_fault(toy_params, fault):
    sound = system_loss(toy_params)
    with CONTROL.planted(fault):
        faulty = system_loss(toy_params)
    again = system_loss(toy_params)  # and the fault is gone afterwards
    want = reference_loss(toy_params)
    np.testing.assert_allclose(again, sound, atol=1e-6)
    assert float(jnp.abs(faulty - want).mean()) > 1e-3
    assert float(jnp.abs(sound - want).mean()) < 1e-5


# --- the window and the rotary -----------------------------------------------

def band_moments(t, window):
    """Mean and mean square of the key positions query i reads: keys
    max(0, i - window + 1)..i."""
    i = np.arange(t, dtype=np.float64)
    lo = np.maximum(0, i - window + 1)
    n = i - lo + 1
    mean = (lo + i) / 2
    square = (i * (i + 1) * (2 * i + 1) - (lo - 1) * lo * (2 * lo - 1)) / (
        6 * n)
    return mean, square


def test_the_kernel_reads_exactly_the_band_at_the_published_window():
    """Zero scores and values [1, j / T, (j / T)^2] of key j: each query's
    output is the mean over the keys it reads, which a contiguous set of
    keys fixes by its first two moments: exactly keys i - 511..i, where
    T 1,024 is past the window."""
    t, window, width = 1024, 512, 128
    j = jnp.arange(t, dtype=jnp.float32) / t
    v = jnp.zeros((1, t, 1, width)).at[0, :, 0, :3].set(
        jnp.stack([jnp.ones(t), j, j * j], -1))
    zeros = jnp.zeros((1, t, 1, width))
    out = fa.flash_attention(zeros, zeros, v, causal=True, window=window)
    mean, square = band_moments(t, window)
    np.testing.assert_allclose(out[0, :, 0, 0], 1.0, rtol=1e-6)
    np.testing.assert_allclose(out[0, :, 0, 1], mean / t, rtol=1e-5)
    np.testing.assert_allclose(out[0, :, 0, 2], square / t ** 2, rtol=1e-4)


def test_the_reference_reads_exactly_the_band_at_the_published_window():
    """The same through the family's reference layer: q and k of zero (so
    the rotary turns nothing), values carrying [1, j / T, (j / T)^2], the
    gate at sigmoid(0), W_o the identity on those channels."""
    t, d = 1024, 8
    j = np.arange(t, dtype=np.float32) / t
    h = np.zeros((t, d), np.float32)
    h[:, :3] = np.stack([np.ones(t), j, j * j], -1)
    eye = np.eye(d, dtype=np.float32)[:, None, :]  # [d, 1 head, D = d]
    zero = np.zeros_like(eye)
    p = {"q_proj": {"kernel": zero}, "k_proj": {"kernel": zero},
         "v_proj": {"kernel": eye}, "g_proj": {"kernel": zero},
         "o_proj": np.eye(d, dtype=np.float32)[None]}
    config = {"head_dim": d, "sliding_window": 512, "rope_parameters": {
        FAMILY.SLIDING: PUBLISHED["rope_parameters"][FAMILY.SLIDING]}}
    with jax.default_matmul_precision("highest"):
        out = FAMILY._attention(jnp.asarray(h), p, config, FAMILY.SLIDING)
    mean, square = band_moments(t, 512)
    np.testing.assert_allclose(out[:, 0], 0.5, rtol=2e-5)
    np.testing.assert_allclose(out[:, 1], 0.5 * mean / t, rtol=1e-5)
    np.testing.assert_allclose(out[:, 2], 0.5 * square / t ** 2, rtol=1e-4)


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_a_layer_of_each_kind_is_the_references(kind):
    """The program's layer (the kernel in the interpreter, the kind's own
    rotary, the gate) against the reference's at toy widths, T 64 past the
    toy's window of 16."""
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, SEQ, 64)),
                    jnp.float32)
    heads = FAMILY.heads_of(TOY, kind)
    window = TOY["sliding_window"] if kind == FAMILY.SLIDING else None
    layer = GatedAttention(heads, 2, heads, 0, 16, jnp.float32,
                           window=window, rotary=FAMILY._rotary(TOY, kind))
    params = layer.init(jax.random.PRNGKey(2), x)["params"]
    want = layer.apply({"params": params}, x)
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([
            FAMILY._attention(one, params, TOY, kind) for one in x])
    np.testing.assert_allclose(want, uncut, atol=2e-5)


def test_the_yarn_table_at_the_published_values():
    """lo 5 and hi 16 (transformers' correction range, truncated), the
    ramp between, the blend against its closed form, in the program's
    table and in the reference's, which computes its own; the window
    kind's plain table; cos and sin times the attention factor."""
    dims, theta = 64, 500000.0

    def correction(rotations):
        return dims * math.log(4096 / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    assert math.floor(correction(64)) == 5 and math.ceil(correction(1)) == 16
    j = np.arange(32)
    kept = theta ** (-2.0 * j / dims)
    e = 1 - np.clip((j - 5) / 11, 0, 1)
    assert (e[:6] == 1).all() and (e[16:] == 0).all() and e[10] == 6 / 11
    blended = kept / 64 * (1 - e) + kept * e
    program = rotary_inv_freq(PUBLISHED_YARN)
    reference, factor = FAMILY.rotary_table(FULL_ROPE, dims)
    assert program.dtype == reference.dtype == np.float32
    np.testing.assert_allclose(program, blended, rtol=1e-6)
    np.testing.assert_array_equal(program, reference)
    assert factor == FULL_ROPE["attention_factor"] == pytest.approx(
        0.1 * math.log(64) + 1)
    plain, one = FAMILY.rotary_table(
        PUBLISHED["rope_parameters"][FAMILY.SLIDING], 128)
    assert one == 1.0
    np.testing.assert_allclose(
        plain, 10000.0 ** (-2.0 * np.arange(64) / 128), rtol=1e-6)
    np.testing.assert_array_equal(
        plain, rotary_inv_freq(FAMILY._rotary(PUBLISHED, FAMILY.SLIDING)))


def test_the_full_rotary_leaves_the_last_64_channels_alone():
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 16, 3, 128)),
                    jnp.float32)
    turned = partial_rope(x, jnp.arange(16), PUBLISHED_YARN)
    np.testing.assert_array_equal(turned[..., 64:], x[..., 64:])
    # position 0 turns nothing: the rotated half is only scaled
    np.testing.assert_allclose(turned[:, 0, :, :64],
                               x[:, 0, :, :64] * FULL_ROPE["attention_factor"],
                               rtol=1e-6)
    # a pair keeps its length, times the factor
    pairs = jnp.hypot(turned[..., :32], turned[..., 32:64])
    np.testing.assert_allclose(
        pairs, jnp.hypot(x[..., :32], x[..., 32:64])
        * FULL_ROPE["attention_factor"], rtol=1e-5)
    assert float(jnp.abs(turned[:, 1:, :, :64] - x[:, 1:, :, :64]).max()) > 0.1


# --- the shares add up -------------------------------------------------------

def test_the_eight_expert_shares_add_up_to_the_uncut_layer():
    """256-way top-8 in small: 32 experts, 8 a token, sigmoid gates scaled
    by 2.5, eight shares of 4: every routed expert's part once and the
    shared expert, which every chip computes alike, eight times: less 7 of
    those they equal the uncut layer's output, and the uncut reference's."""
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 48, 64)),
                    jnp.float32)

    def layer(held, start):
        return RoutedExperts(
            n_routed=32, k=8, expert_width=32, shared_width=24, n_held=held,
            held_start=start, routed_scaling=2.5, compute_dtype=jnp.float32,
            scoring="sigmoid")

    params = layer(32, 0).init(jax.random.PRNGKey(3), x)["params"]
    want = layer(32, 0).apply({"params": params}, x)

    def share(start):
        own = dict(params)
        own["experts_gate_up"] = params["experts_gate_up"][start:start + 4]
        own["experts_down"] = params["experts_down"][start:start + 4]
        return layer(4, start).apply({"params": own}, x)

    shared = SwiGLU(24).apply({"params": params["shared"]}, x)
    assert float(jnp.abs(want - shared).mean()) > 0.05  # the routed part
    total = sum(share(start) for start in range(0, 32, 4)) - 7 * shared
    np.testing.assert_allclose(total, want, atol=1e-4)
    config = {"num_experts_per_tok": 8, "moe_intermediate_size": 32,
              "moe_routed_scaling_factor": 2.5, "held_experts_start": 0,
              "num_experts": 32}
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([
            FAMILY._expert_layer(one, params, config) for one in x])
    np.testing.assert_allclose(want, uncut, atol=3e-5)


# --- what the program says it built ------------------------------------------

def test_the_kinds_gauges_and_scopes():
    """The gauges at trace time, and in the compiled step the window
    kind's kernel call under ``hvt.swa`` (not under ``hvt.gqa``) and the
    rotary under ``hvt.gqa/rope``, forward and backward."""
    x, y = toy_batch()
    model = toy_model()
    params = model.init({"params": jax.random.PRNGKey(0)}, x, labels=y)[
        "params"]
    gauges = prom.render()
    assert 'hvt_layer_kinds{kind="window"} 3' in gauges
    assert 'hvt_layer_kinds{kind="softmax"} 2' in gauges
    assert 'hvt_held_heads{mixer="window"} 6' in gauges
    assert 'hvt_held_heads{mixer="softmax"} 4' in gauges
    assert "hvt_attn_window 16" in gauges
    assert 'hvt_rotary_dims{kind="softmax"} 8' in gauges
    assert 'hvt_rotary_dims{kind="window"} 16' in gauges
    assert "hvt_remat_blocks 5" in gauges

    def loss(p):
        return model.apply({"params": p}, x, labels=y,
                           mutable=["metrics"])[0][0].mean()

    hlo = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    for scope in (hybrid.SWA_SCOPE, hybrid.GQA_ROPE):
        assert re.search(rf"jvp\(HybridMoELM\)/Block_\d/mixer/{scope}", hlo)
        assert re.search(
            rf"transpose\(jvp\(HybridMoELM\)\)/[^\"]*Block_\d/mixer/{scope}",
            hlo), scope
    assert f"{hybrid.GQA_SCOPE}/{hybrid.SWA_SCOPE}" not in hlo


# --- counts ------------------------------------------------------------------

def test_counts_against_hand_counts():
    d = 2048
    full, window = 2 * 41_943_040, 3 * 54_525_952
    expert_layer = d * 256 + 3 * d * 512 + 8 * 32 / 256 * 3 * d * 512
    params = full + window + 3 * d * 8192 + 4 * expert_layer + d * 12544
    assert FAMILY.matmul_params_per_token(PUBLISHED) == pytest.approx(params)
    causal, band = 8192 * 8193 // 2, 4_063_488
    assert flops.visible_pairs(8192, 512) == band
    dots = 6 * 2 * 128 * (causal * 48 * 2 + band * 64 * 3) / 8192
    required = FAMILY.required_flops_per_token(PUBLISHED, 8192)
    assert required == pytest.approx(6 * params + dots)
    assert required == pytest.approx(2.854831104e9)
    work = FAMILY.kernel_work(PUBLISHED, 8192, 1)
    assert set(work) == {"flash_fwd", "window_flash", "expert_gmm"}
    # every block's forward runs twice: two forward calls a layer
    assert work["flash_fwd"] == (
        2 * 2 * 2 * 128 * (causal * 48 * 2 + band * 64 * 3),
        2 * 8192 * 128 * 2 * 4 * (48 * 2 + 64 * 3), 10)
    # the band's 2 dots each forward pass and 5 backward
    assert work["window_flash"] == (
        2 * band * 128 * 64 * 3 * (2 * 2 + 5),
        3 * 8192 * 128 * 2 * (2 * (2 * 64 + 2 * 8) + (4 * 64 + 4 * 8)), 9)
    plain = FAMILY.kernel_work(
        PUBLISHED | {"activation_checkpointing": None}, 8192, 1)
    assert plain["window_flash"][0] == pytest.approx(1.398099935232e12)
    assert plain["window_flash"][2] == 6 and plain["flash_fwd"][2] == 5
    rows = 8192 * 8 * 32 / 256
    assert FAMILY.expected_routed_rows(PUBLISHED, 8192) == rows == 8192
    gmm_flops, gmm_bytes, gmm_calls = work["expert_gmm"]
    assert gmm_flops == pytest.approx(4 * 24 * rows * 2048 * 512)
    assert gmm_calls == 32 and plain["expert_gmm"][2] == 24


@pytest.mark.parametrize("change,says", [
    (dict(gating=False), "gating = True only"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings = False only"),
    (dict(moe_apply_router_weight_on_input=True),
     "moe_apply_router_weight_on_input = False only"),
    (dict(layer_types=["full_attention"] * 4), "layer_types has 4 entries"),
    (dict(layer_types=["full_attention"] * 4 + ["chunked_attention"]),
     "chunked_attention"),
    (dict(mlp_layer_types=["sparse", "dense", "sparse", "sparse", "sparse"]),
     "a leading run"),
    (dict(num_attention_heads_per_layer=[48, 64, 56, 64, 48]),
     r"sliding_attention layers hold \[56, 64\] query heads"),
    (dict(num_attention_heads_per_layer=[44, 64, 64, 64, 44],
          num_attention_heads=44), "in whole groups over 8 K/V heads"),
    (dict(held_experts_start=250), "are not a block of the router's 256"),
    (dict(activation_checkpointing="attention"),
     "activation_checkpointing is null or 'block'"),
], ids=["no_gate", "tied", "weight_on_input", "too_few_kinds", "unknown_kind",
        "dense_after_sparse", "two_window_head_counts", "half_a_group",
        "experts_past_the_router", "another_policy"])
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


# --- the reader --------------------------------------------------------------

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
    work = {"window_flash": (197e12 * 60e-9, 1.0, 2)} if work is None else work
    return {"rows": rows, "chips": reduce.chips_from_rows(rows),
            "spans": {"scopes": scopes, "host": []}, "kernel_work": work,
            "device_kind": "TPU v5 lite", "say": said.update}, said


WINDOW_OPS = [
    ("%dot.1 = bf16[] fusion()", 0.0, 10.0),
    (f"%hvt_flash_fwd.2 = bf16[] {KERNEL}", 10.0, 10.0),
    (f"%hvt_flash_fwd.3 = bf16[] {KERNEL}", 20.0, 30.0),
    (f"%transpose_jvp_hvt_flash_bwd__.4 = bf16[] {KERNEL}", 50.0, 20.0),
    (f"%hvt_flash_bwd_ring.5 = bf16[] {KERNEL}", 70.0, 5.0),
]
WINDOW_SCOPES = {
    WINDOW_OPS[0][0]: "jit(train_step)/jvp(HybridMoELM)/Block_1/mixer/"
                      "hvt.gqa/q_proj/dot_general",
    WINDOW_OPS[1][0]: "jit(train_step)/jvp(HybridMoELM)/Block_1/mixer/"
                      "hvt.swa/hvt_flash_fwd",
    WINDOW_OPS[2][0]: "jit(train_step)/jvp(HybridMoELM)/Block_0/mixer/"
                      "hvt_flash_fwd",
    WINDOW_OPS[3][0]: "jit(train_step)/transpose(jvp(HybridMoELM))/Block_1/"
                      "mixer/hvt.swa/hvt_flash_bwd",
    WINDOW_OPS[4][0]: "jit(train_step)/jvp(HybridMoELM)/Block_2/mixer/"
                      "hvt.swa/ring",
}


def test_the_window_reader_by_hand():
    """Per step: a projection (0-10), the window layer's forward kernel
    (10-20) and its backward under a transformation's prefix (50-70), both
    under ``hvt.swa``; a full layer's forward (20-50) outside it and a
    kernel of another name inside it count for nothing."""
    ctx, said = traced(WINDOW_OPS, WINDOW_SCOPES)
    assert window_spans.window_flash_ms_per_step(ctx) * 1e6 == (
        pytest.approx(300.0))
    # 60 ns at peak over 300 ns measured
    assert window_spans.window_flash_roofline(ctx) == pytest.approx(20.0)
    assert said["window_flash_roofline_bound"] == "compute"
    # as many calls a step as the family counts, or nothing
    ctx, _ = traced(WINDOW_OPS, WINDOW_SCOPES,
                    {"window_flash": (1.0, 1.0, 3)})
    assert window_spans.window_flash_ms_per_step(ctx) is None
    assert window_spans.window_flash_roofline(ctx) is None


def test_the_window_reader_finds_nothing_without_the_scope_or_the_count():
    """The parent's program (the kernels under no ``hvt.swa``) and a family
    that counts no window kernel: nothing is read and nothing raises."""
    bare = {line: scope.replace("hvt.swa/", "")
            for line, scope in WINDOW_SCOPES.items()}
    for scopes, work in ((bare, None), (WINDOW_SCOPES, {})):
        ctx, _ = traced(WINDOW_OPS, scopes, work)
        assert window_spans.window_flash_ms_per_step(ctx) is None
        assert window_spans.window_flash_roofline(ctx) is None


def test_the_new_metrics_are_reported_in_the_new_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = {"window_flash_ms_per_step", "window_flash_roofline"}
    shared = {"flash_fwd_ms_per_step", "gated_attn_proj_ms_per_step",
              "moe_ms_per_step", "moe_dispatch_ms_per_step",
              "expert_gmm_ms_per_step", "expert_gmm_roofline",
              "recompute_ms_per_step"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in new:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == "kernels (ops/flash_attention.py)"
    for name in shared:
        assert by_name[name]["workloads"][-1] == CELL, name
    # the four readers that fell silent in PR 38 are left as they were
    for name in ("flash_ms_per_step", "flash_roofline",
                 "flash_dq_ms_per_step", "flash_dkv_ms_per_step"):
        assert CELL not in by_name[name]["workloads"]
    reported = {m["name"] for m in run.load_cell(ROOT, CELL)["per_layer"]}
    assert new | shared | {"mfu", "head_ce_ms_per_step"} <= reported
    assert not {"mla_proj_ms_per_step", "kda_ms_per_step",
                "ssm_ms_per_step"} & reported


# --- what the benchmark had is the parent's ----------------------------------

def test_the_state_space_model_that_was_there_is_the_parents():
    """Granite's toy model, built by its untouched family file: the
    parameter tree and the per-token losses on a seed as recorded on the
    parent commit (917e694, this CPU backend): the stack's new kinds and
    fields change nothing of it. (Solar's and Kanana's:
    test_ssm_moe_lm.py.)"""
    module = run.load_module(FAMILIES / "ssm_moe_lm.py")
    published = json.loads((ROOT / "chipbench" / "configs"
                            / "granite-4.0-h-small.json").read_text())
    toy = published | published["toy"]
    model = module.build(toy, {"compute_dtype": "float32",
                               "fused_head_chunks": 2, "remat": "block"}, None)
    x, y = (jnp.asarray(a) for a in copy_task.make(
        3, {"seq_len": 64, "n_sequences": 1}, toy["vocab_size"]))
    params = model.init({"params": jax.random.PRNGKey(0)}, x, labels=y)[
        "params"]
    names = sorted(
        jax.tree_util.keystr(path) + str(leaf.shape) for path, leaf in
        jax.tree_util.tree_flatten_with_path(params)[0])
    assert len(names) == 203
    assert hashlib.sha256("\n".join(names).encode()).hexdigest()[
        :16] == "071f2c14f29d04a6"
    loss = model.apply({"params": params}, x, labels=y,
                       mutable=["metrics"])[0][0][0]
    np.testing.assert_allclose(
        loss[:4], [4.899776458740234, 4.748777866363525, 4.862351417541504,
                   4.840674877166748], rtol=2e-6)
    assert float(loss.sum()) == pytest.approx(311.23785400390625, rel=2e-6)


# --- the controls ------------------------------------------------------------

def test_the_controls_run_through_the_harness_comparison(tmp_path, capsys):
    """chipbench/families/window_moe_lm_control.py at toy sizes in float32:
    the system passes the cell's limits, the low-precision reference and
    every planted fault read further off than the system (whether each
    passes the limits is a question for the published widths on the chip:
    the readings are beside ``LIMITS``)."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    here = tmp_path / "chipbench"
    (here / "configs" / "laguna-xs.2.json").write_text(json.dumps(TOY))
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
        assert not line["mean_abs_diff"] < 10 * system["mean_abs_diff"], name
    assert lines[-1]["summary"]["system"] == {"runs": 1, "ok": 1}
