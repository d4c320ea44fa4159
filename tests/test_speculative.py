"""Speculative decoding (models/speculative.py): exactness is the contract —
greedy speculative output must be bit-identical to plain greedy decoding for
ANY draft quality; drafts change only the round count."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvt
from horovod_tpu.data import datasets
from horovod_tpu.models.decoding import generate
from horovod_tpu.models.speculative import make_speculative_fn, ngram_draft_fn
from horovod_tpu.models.transformer import TransformerLM

VOCAB = 32


def _model(**kw):
    kw.setdefault("vocab_size", VOCAB)
    kw.setdefault("d_model", 32)
    kw.setdefault("n_heads", 4)
    kw.setdefault("n_layers", 2)
    kw.setdefault("dropout", 0.0)
    return TransformerLM(**kw)


def _params(model):
    return model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))[
        "params"
    ]


class TestExactness:
    @pytest.mark.parametrize("gamma", [2, 4, 6])
    def test_matches_plain_greedy(self, gamma):
        model = _model()
        params = _params(model)
        prompt = jnp.asarray(
            np.random.RandomState(5).randint(1, VOCAB, size=(2, 10)),
            jnp.int32,
        )
        want = generate(model, params, prompt, 20)
        got = make_speculative_fn(model, max_new_tokens=20, gamma=gamma)(
            params, prompt
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_adversarial_draft_still_exact(self):
        """A constant-garbage draft must not change the output — only the
        acceptance rate (≈1 token/round)."""
        model = _model()
        params = _params(model)
        prompt = jnp.asarray([[3, 1, 4, 1, 5, 9]], jnp.int32)
        bad = lambda buf, cur_len, n: jnp.full(  # noqa: E731
            (buf.shape[0], n), 11, jnp.int32
        )
        want = generate(model, params, prompt, 16)
        fn = make_speculative_fn(
            model, max_new_tokens=16, gamma=4, draft_fn=bad,
            return_stats=True,
        )
        got, stats = fn(params, prompt)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert int(stats["tokens"]) >= 16

    def test_gqa_model_exact(self):
        model = _model(n_kv_heads=2)
        params = _params(model)
        prompt = jnp.asarray([[7, 8, 9, 1], [2, 2, 4, 6]], jnp.int32)
        want = generate(model, params, prompt, 12)
        got = make_speculative_fn(model, max_new_tokens=12, gamma=4)(
            params, prompt
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_include_prompt_false(self):
        model = _model()
        params = _params(model)
        prompt = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
        full = make_speculative_fn(model, max_new_tokens=8, gamma=3)(
            params, prompt
        )
        tail = make_speculative_fn(
            model, max_new_tokens=8, gamma=3, include_prompt=False
        )(params, prompt)
        np.testing.assert_array_equal(
            np.asarray(full[:, 4:]), np.asarray(tail)
        )

    def test_validation(self):
        model = _model()
        with pytest.raises(ValueError, match="gamma"):
            make_speculative_fn(model, max_new_tokens=8, gamma=1)
        with pytest.raises(ValueError, match="max_new_tokens"):
            make_speculative_fn(model, max_new_tokens=0)


class TestNgramDraft:
    def test_proposes_continuation_of_earlier_occurrence(self):
        draft = ngram_draft_fn(ngram=2)
        # buf: ... [4 5] 6 7 ... [4 5] <- suffix; expect proposal 6 7 8
        buf = jnp.asarray(
            [[1, 4, 5, 6, 7, 8, 2, 4, 5, 0, 0, 0]], jnp.int32
        )
        out = draft(buf, jnp.int32(9), 3)
        np.testing.assert_array_equal(np.asarray(out), [[6, 7, 8]])

    def test_latest_occurrence_wins(self):
        draft = ngram_draft_fn(ngram=2)
        buf = jnp.asarray(
            [[4, 5, 1, 4, 5, 2, 9, 4, 5, 0, 0, 0]], jnp.int32
        )
        out = draft(buf, jnp.int32(9), 2)
        # the match at positions 3-4 (followed by 2, 9) is later than 0-1
        np.testing.assert_array_equal(np.asarray(out), [[2, 9]])

    def test_no_match_repeats_last_token(self):
        draft = ngram_draft_fn(ngram=3)
        buf = jnp.asarray([[1, 2, 3, 4, 5, 0, 0, 0]], jnp.int32)
        out = draft(buf, jnp.int32(5), 2)
        np.testing.assert_array_equal(np.asarray(out), [[5, 5]])


@pytest.mark.slow
class TestSpeedup:
    def test_trained_copy_model_accepts_drafts(self):
        """On a model that has actually learned the copy task, the ngram
        draft proposes the true continuation and the target accepts ~gamma
        tokens per round — the mechanism behind speculation's speedup.
        Exactness still holds, and the round count must be
        WELL under one-per-token."""
        from horovod_tpu.parallel import mesh as mesh_lib

        model = _model(d_model=64)
        trainer = hvt.Trainer(
            model,
            hvt.DistributedOptimizer(optax.adam(3e-3)),
            loss="sparse_categorical_crossentropy",
            # 1-device mesh: this test is about decode acceptance, and the
            # default 8-way virtual mesh makes the fit compile ~10x slower
            # on a single-core host.
            mesh=mesh_lib.build_mesh(
                mesh_lib.MeshSpec(data=1), devices=jax.devices()[:1]
            ),
        )
        x, y = datasets.copy_task(512, 32, vocab_size=VOCAB, seed=9)
        trainer.fit(
            x=x, y=y, batch_size=32, epochs=4, steps_per_epoch=16, verbose=0
        )
        params = trainer.state.params
        xt, _ = datasets.copy_task(4, 32, vocab_size=VOCAB, seed=11)
        prompt = jnp.asarray(xt[:2, :16])  # first half; continuation = copy
        n_new = 15
        want = generate(model, params, prompt, n_new)
        fn = make_speculative_fn(
            model, max_new_tokens=n_new, gamma=6, return_stats=True
        )
        got, stats = fn(params, prompt)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        rounds = int(stats["rounds"])
        assert rounds <= (n_new * 2) // 3, (
            f"{rounds} rounds for {n_new} tokens — drafts not being accepted"
        )


class TestPerRowAdvance:
    """Batch rows advance by their OWN acceptance (per-row cache indices):
    the batch finishes in exactly as many rounds as its slowest row would
    alone — no lockstep row-minimum degradation."""

    def _solo_rounds(self, fn, params, prompt_row):
        _, stats = fn(params, prompt_row[None, :])
        return int(stats["rounds"])

    def test_batched_rounds_equal_slowest_solo_row(self):
        model = _model()
        params = _params(model)
        rng = np.random.RandomState(17)
        # Rows with very different draftability: self-repetitive (ngram
        # lookup drafts well) vs random (drafts badly).
        repetitive = np.tile(np.array([4, 7, 2], np.int32), 4)  # len 12
        random_row = rng.randint(1, VOCAB, size=(12,)).astype(np.int32)
        fn = make_speculative_fn(
            model, max_new_tokens=12, gamma=4, return_stats=True
        )
        solo = [
            self._solo_rounds(fn, params, jnp.asarray(r))
            for r in (repetitive, random_row)
        ]
        batch = jnp.asarray(np.stack([repetitive, random_row]))
        got, stats = fn(params, batch)
        # Exactness at batch 2 (each row == its solo generation).
        want = generate(model, params, batch, 12)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert int(stats["rounds"]) == max(solo), (
            f"batched rounds {int(stats['rounds'])} != slowest solo row "
            f"{max(solo)} — per-row advance regressed toward lockstep"
        )

    def test_tokens_stat_is_total_committed(self):
        model = _model()
        params = _params(model)
        prompt = jnp.asarray(
            np.random.RandomState(23).randint(1, VOCAB, size=(3, 8)),
            jnp.int32,
        )
        fn = make_speculative_fn(
            model, max_new_tokens=10, gamma=3, return_stats=True
        )
        _, stats = fn(params, prompt)
        # Clamped per-row advance commits exactly max_new_tokens per row.
        assert int(stats["tokens"]) == 3 * 10


class TestMoERejected:
    def test_moe_model_rejected(self):
        """MoE capacity binds per call group: a chunked verify forward can
        route differently than the per-token steps it replaces, so the
        exact-output contract cannot hold — rejected loudly (confirmed
        divergence repro: moe_every=1, capacity_factor=0.5, gamma=4)."""
        model = _model(moe_every=2, n_experts=4)
        with pytest.raises(ValueError, match="dense model"):
            make_speculative_fn(model, max_new_tokens=8)


@pytest.mark.slow
class TestModelDraft:
    """Two-model speculative decoding: a smaller LM drafts with its own
    in-loop KV cache (fixed 2-token catch-up window + scan steps). The
    self-draft case (draft == target) is the machinery's proof: every
    proposal is the target's own argmax, so acceptance must be total and
    the round count exactly ceil(n/gamma) — any cache-index or catch-up
    bug would break the draft's agreement with its own target."""

    def _pair(self):
        target = _model(n_layers=3)
        draft = _model(d_model=16, n_heads=2, n_layers=1)
        toks = jnp.zeros((2, 8), jnp.int32)
        tp = target.init(jax.random.PRNGKey(0), toks)["params"]
        dp = draft.init(jax.random.PRNGKey(1), toks)["params"]
        return target, tp, draft, dp

    @pytest.mark.parametrize("gamma", [2, 3, 5])
    def test_exact_with_separate_draft(self, gamma):
        target, tp, draft, dp = self._pair()
        prompt = jnp.asarray(
            np.random.RandomState(31).randint(1, VOCAB, size=(2, 8)),
            jnp.int32,
        )
        want = generate(target, tp, prompt, 16)
        got = make_speculative_fn(
            target, max_new_tokens=16, gamma=gamma,
            draft_model=draft, draft_params=dp,
        )(tp, prompt)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_self_draft_full_acceptance(self):
        target, tp, _, _ = self._pair()
        prompt = jnp.asarray(
            np.random.RandomState(32).randint(1, VOCAB, size=(2, 8)),
            jnp.int32,
        )
        want = generate(target, tp, prompt, 16)
        fn = make_speculative_fn(
            target, max_new_tokens=16, gamma=5,
            draft_model=target, draft_params=tp, return_stats=True,
        )
        got, stats = fn(tp, prompt)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert int(stats["rounds"]) == 4  # ceil(16/5): zero rejections

    def test_validation(self):
        target, tp, draft, dp = self._pair()
        with pytest.raises(ValueError, match="not both"):
            make_speculative_fn(
                target, max_new_tokens=8,
                draft_fn=lambda b, c, n: b[:, :n],
                draft_model=draft, draft_params=dp,
            )
        with pytest.raises(ValueError, match="draft_params"):
            make_speculative_fn(target, max_new_tokens=8, draft_model=draft)
        fn = make_speculative_fn(
            target, max_new_tokens=8, draft_model=draft, draft_params=dp
        )
        with pytest.raises(ValueError, match="2 tokens"):
            fn(tp, jnp.zeros((1, 1), jnp.int32))


@pytest.mark.slow
class TestSampledSpeculative:
    """Sampled (temperature/top-k/top-p) speculative decoding: the
    rejection scheme must commit exactly the target's filtered
    distribution per position. Bit-identity with decoding.generate is
    impossible (different rng schedules), so the contract is checked
    distributionally: empirical per-position marginals over a FIXED key
    set must match generate's — deterministic given the seeds, thresholds
    ~4x the binomial se at these sample counts."""

    def _setup(self, vocab=16, batch=2):
        model = _model(vocab_size=vocab)
        toks = jnp.asarray(
            np.random.RandomState(0).randint(1, vocab, size=(batch, 8)),
            jnp.int32,
        )
        params = model.init(jax.random.PRNGKey(0), toks)["params"]
        return model, params, toks[:, :6], vocab

    def _worst_marginal_diff(self, a, b, vocab, n):
        worst = 0.0
        for pos in range(a.shape[2]):
            for row in range(a.shape[1]):
                ha = np.bincount(a[:, row, pos], minlength=vocab) / n
                hb = np.bincount(b[:, row, pos], minlength=vocab) / n
                worst = max(worst, float(np.abs(ha - hb).max()))
        return worst

    def test_marginals_match_generate(self):
        from horovod_tpu.models.decoding import make_generate_fn

        model, params, prompt, vocab = self._setup()
        n, new = 800, 4
        kw = dict(temperature=1.2, top_p=0.9)
        spec = make_speculative_fn(
            model, max_new_tokens=new, gamma=3, include_prompt=False, **kw
        )
        gen = make_generate_fn(
            model, max_new_tokens=new, include_prompt=False, **kw
        )
        keys = jax.random.split(jax.random.PRNGKey(7), n)
        so = np.asarray(jax.vmap(lambda k: spec(params, prompt, k))(keys))
        go = np.asarray(jax.vmap(lambda k: gen(params, prompt, k))(keys))
        assert self._worst_marginal_diff(so, go, vocab, n) < 0.08

    def test_lockstep_rederivation_unbiased(self):
        """Batch rows accepting past the lockstep minimum re-derive
        positions next round — the case the (position, token, row)-keyed
        draws exist for. Self-drafting makes acceptance common (prob =
        p(argmax)), so partial acceptances and re-derivations happen
        constantly; the committed marginals must still match generate."""
        from horovod_tpu.models.decoding import make_generate_fn

        model, params, _, vocab = self._setup(batch=4)
        prompt = jnp.asarray(
            np.random.RandomState(9).randint(1, vocab, size=(4, 6)),
            jnp.int32,
        )
        n, new = 600, 4
        kw = dict(temperature=1.0, top_k=8)
        spec = make_speculative_fn(
            model, max_new_tokens=new, gamma=4, include_prompt=False,
            draft_model=model, draft_params=params, **kw,
        )
        gen = make_generate_fn(
            model, max_new_tokens=new, include_prompt=False, **kw
        )
        keys = jax.random.split(jax.random.PRNGKey(11), n)
        so = np.asarray(jax.vmap(lambda k: spec(params, prompt, k))(keys))
        go = np.asarray(jax.vmap(lambda k: gen(params, prompt, k))(keys))
        assert self._worst_marginal_diff(so, go, vocab, n) < 0.09

    def test_rng_required(self):
        model, params, prompt, _ = self._setup()
        fn = make_speculative_fn(
            model, max_new_tokens=4, temperature=0.8
        )
        with pytest.raises(ValueError, match="rng"):
            fn(params, prompt)

    def test_greedy_path_unchanged_by_sampling_args(self):
        model, params, prompt, _ = self._setup()
        a = make_speculative_fn(model, max_new_tokens=8, gamma=3)(
            params, prompt
        )
        b = make_speculative_fn(
            model, max_new_tokens=8, gamma=3, temperature=0.0, top_k=5,
        )(params, prompt)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestQuantizedSpeculative:
    def test_exact_vs_quantized_plain_greedy(self):
        """int8 target + speculative: both paths consult the same quantized
        weights, so the greedy exactness contract carries over bit-for-bit
        against make_generate_fn(quantized=True)."""
        from horovod_tpu.models.decoding import make_generate_fn
        from horovod_tpu.models.quant import quantize_params

        model = _model()
        params = _params(model)
        qparams = quantize_params(params, min_size=64)
        prompt = jnp.asarray(
            np.random.RandomState(41).randint(1, VOCAB, size=(2, 10)),
            jnp.int32,
        )
        want = make_generate_fn(model, max_new_tokens=16, quantized=True)(
            qparams, prompt, jax.random.PRNGKey(0)
        )
        got = make_speculative_fn(
            model, max_new_tokens=16, gamma=4, quantized=True
        )(qparams, prompt)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestRaggedSpeculative:
    """Ragged prompts through the speculative loop: per-row start positions
    on the same per-row cache-index layout — each row bit-equal to plain
    greedy at its own length (the serving batch contract)."""

    def test_matches_ragged_generate(self):
        from horovod_tpu.models.decoding import make_generate_fn

        model = _model()
        params = _params(model)
        rng = np.random.RandomState(7)
        t0 = 10
        lens = np.array([4, 10, 7], np.int32)
        padded = np.zeros((3, t0), np.int32)
        for i, L in enumerate(lens):
            padded[i, :L] = rng.randint(1, VOCAB, size=(L,))
        want = np.asarray(
            make_generate_fn(model, max_new_tokens=12, include_prompt=False)(
                params, jnp.asarray(padded), jax.random.PRNGKey(0),
                jnp.asarray(lens),
            )
        )
        got = np.asarray(
            make_speculative_fn(
                model, max_new_tokens=12, gamma=4, include_prompt=False
            )(params, jnp.asarray(padded), None, jnp.asarray(lens))
        )
        np.testing.assert_array_equal(got, want)

    def test_pad_content_irrelevant(self):
        model = _model()
        params = _params(model)
        lens = jnp.array([3, 6], jnp.int32)
        base = np.array(
            [[5, 3, 7, 0, 0, 0], [1, 9, 8, 4, 2, 6]], np.int32
        )
        noisy = base.copy()
        noisy[0, 3:] = [11, 13, 17]
        fn = make_speculative_fn(
            model, max_new_tokens=8, gamma=3, include_prompt=False
        )
        a = np.asarray(fn(params, jnp.asarray(base), None, lens))
        b = np.asarray(fn(params, jnp.asarray(noisy), None, lens))
        np.testing.assert_array_equal(a, b)

    def test_draft_model_rejected_with_lengths(self):
        target = _model(n_layers=2)
        draft = _model(d_model=16, n_heads=2, n_layers=1)
        toks = jnp.zeros((2, 8), jnp.int32)
        tp = target.init(jax.random.PRNGKey(0), toks)["params"]
        dp = draft.init(jax.random.PRNGKey(1), toks)["params"]
        fn = make_speculative_fn(
            target, max_new_tokens=8, draft_model=draft, draft_params=dp
        )
        with pytest.raises(ValueError, match="ragged"):
            fn(tp, toks, None, jnp.array([4, 8], jnp.int32))
