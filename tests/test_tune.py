"""hvt-tune — the trace-replay autotuner (ISSUE 19).

Covers the pieces in isolation and the seams between them:

* the paired-leg A/B discipline over a FAKE clock (alternating order,
  median-of-pair-diffs statistic, MAD-adaptive stop vs pair cap);
* candidate-space enumeration from registry ``tunable=`` metadata, and
  the no-drift tie to `collectives.DEFAULT_BUCKET_BYTES`;
* evidence loading (wrapper rows, bare rows, legacy rows without a
  stamped ``config:`` block, garbage files);
* the analytic model against SYNTHETIC evidence built so the optimum
  is known in closed form (n* = sqrt(hide_rate / alpha) buckets), with
  an independent brute-force argmin cross-check;
* `run_probe_plan` over a fake builder + fake clock;
* in-situ `resolve`: selection, the persisted store, restart REUSE
  (the prober must not run twice), journal event shapes;
* the `tune:` job-spec surface (validate_spec, the shipped YAML);
* the `hvt-tune offline --check` tier-1 gate over the fixture rows in
  ``tests/fixtures/tune_evidence/``, end to end through the real CLI;
* slow: predicted ranking matches the MEASURED A/B ranking on three
  real candidate configs (the offline acceptance gate).

``BENCH_*.json`` is the file-name pattern `evidence.load_rows` globs in an
evidence directory, and the rows' ``"cmd"`` strings are what the fixture
rows recorded: neither names a script in the tree (the one that wrote the
fixture's rows was deleted in PR 31).
"""

import json
import os
import subprocess
import sys

import pytest
import yaml

from horovod_tpu.analysis import registry
from horovod_tpu.tune import evidence, insitu, model, offline, probe, space

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EVIDENCE = os.path.join(REPO, "tests", "fixtures", "tune_evidence")

MB = 1 << 20


# --- the paired-leg discipline over a fake clock ----------------------------


class FakeClock:
    """Legs advance `t` by their scripted duration; paired_compare times
    them by calling clock() around each leg."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def leg(self, durations, calls=None, name=None):
        """A zero-arg leg taking durations[i] seconds on its i-th call
        (the last duration repeats)."""
        state = {"i": 0}

        def run():
            d = durations[min(state["i"], len(durations) - 1)]
            state["i"] += 1
            self.t += d
            if calls is not None:
                calls.append(name)

        return run


class TestPairedCompare:
    def test_alternating_order_cancels_drift(self):
        clock = FakeClock()
        calls = []
        res = probe.paired_compare(
            clock.leg([1.0], calls, "a"), clock.leg([2.0], calls, "b"),
            pairs_min=3, clock=clock,
        )
        # pair 0: a,b — pair 1: b,a — pair 2: a,b
        assert calls == ["a", "b", "b", "a", "a", "b"]
        assert res.pairs == 3
        assert res.median_pct == pytest.approx(100.0)  # b is 2x slower
        assert not res.b_wins

    def test_faster_b_wins(self):
        clock = FakeClock()
        res = probe.paired_compare(
            clock.leg([2.0]), clock.leg([1.0]), pairs_min=3, clock=clock)
        assert res.median_pct == pytest.approx(-50.0)
        assert res.b_wins

    def test_mad_stop_converges_at_pairs_min_on_quiet_host(self):
        clock = FakeClock()
        res = probe.paired_compare(
            clock.leg([1.0]), clock.leg([1.01]), pairs_min=3, pairs_cap=9,
            clock=clock)
        assert res.converged and res.pairs == 3
        assert res.mad_pct == pytest.approx(0.0)

    def test_noisy_host_buys_pairs_until_cap(self):
        clock = FakeClock()
        # leg A drifts monotonically: every pair diff lands somewhere
        # new, the MAD never stabilizes, and the race must run to the
        # cap, unconverged.
        res = probe.paired_compare(
            clock.leg([1.0, 1.2, 1.5, 1.9, 2.4, 3.0, 3.7, 4.5]),
            clock.leg([1.5]),
            pairs_min=3, pairs_cap=7, mad_stop_pct=0.75, clock=clock)
        assert res.pairs == 7
        assert not res.converged

    def test_median_is_outlier_immune(self):
        clock = FakeClock()
        # one catastrophic leg-B outlier in pair 1 (10x) cannot move the
        # median verdict: B is genuinely ~equal elsewhere.
        res = probe.paired_compare(
            clock.leg([1.0]), clock.leg([1.0, 10.0, 1.0, 1.0, 1.0]),
            pairs_min=5, pairs_cap=5, mad_stop_pct=0.0, clock=clock)
        assert res.median_pct == pytest.approx(0.0)

    def test_upper_median(self):
        assert probe.median([3.0, 1.0, 2.0, 4.0]) == 3.0
        with pytest.raises(ValueError):
            probe.median([])


# --- candidate space from registry metadata ---------------------------------


class TestSpace:
    def test_default_bucket_bytes_does_not_drift_from_collectives(self):
        from horovod_tpu.parallel import collectives

        assert space.DEFAULT_BUCKET_BYTES == collectives.DEFAULT_BUCKET_BYTES

    def test_domains_are_the_five_tuned_knobs(self):
        doms = space.domains()
        assert sorted(doms) == [
            "HVT_BACKWARD_PASSES", "HVT_BUCKET_BYTES", "HVT_COMPRESSION",
            "HVT_COMPRESSION_ICI", "HVT_OVERLAP_REDUCTION",
        ]
        assert doms["HVT_OVERLAP_REDUCTION"] == (False, True)
        assert doms["HVT_BACKWARD_PASSES"] == (1, 2, 4, 8)
        assert "none" in doms["HVT_COMPRESSION"]
        assert "bf16" in doms["HVT_COMPRESSION"]
        # log domain: powers of two, 256 KB .. 256 MB inclusive
        bb = doms["HVT_BUCKET_BYTES"]
        assert bb[0] == 1 << 18 and bb[-1] == 1 << 28
        assert all(b & (b - 1) == 0 for b in bb)

    def test_default_config_matches_registry_defaults(self):
        cfg = space.default_config()
        assert cfg["HVT_BUCKET_BYTES"] == space.DEFAULT_BUCKET_BYTES
        assert cfg["HVT_BACKWARD_PASSES"] == 1
        assert cfg["HVT_COMPRESSION"] == "none"
        assert cfg["HVT_OVERLAP_REDUCTION"] is True

    def test_enumerate_restricts_to_named_knobs(self):
        configs = space.enumerate_configs(
            knobs=["HVT_OVERLAP_REDUCTION"], environ={})
        assert len(configs) == 2
        base = space.default_config()
        for c in configs:
            for name in base:
                if name != "HVT_OVERLAP_REDUCTION":
                    assert c[name] == base[name]

    def test_enumerate_pin_and_cross_product(self):
        configs = space.enumerate_configs(
            knobs=["HVT_BUCKET_BYTES", "HVT_OVERLAP_REDUCTION"],
            pin={"HVT_BACKWARD_PASSES": 4}, environ={})
        assert len(configs) == 11 * 2
        assert all(c["HVT_BACKWARD_PASSES"] == 4 for c in configs)

    def test_non_tunable_knob_is_an_error(self):
        with pytest.raises(ValueError, match="not a tunable knob"):
            space.enumerate_configs(knobs=["HVT_FAULT"], environ={})

    def test_env_of_renders_launcher_strings(self):
        env = space.env_of({"HVT_BUCKET_BYTES": 4 * MB,
                            "HVT_OVERLAP_REDUCTION": False})
        assert env == {"HVT_BUCKET_BYTES": "4194304",
                       "HVT_OVERLAP_REDUCTION": "0"}

    def test_deviations_counts_non_default_knobs(self):
        cfg = dict(space.default_config())
        assert space.deviations(cfg) == 0
        cfg["HVT_BUCKET_BYTES"] = 4 * MB
        cfg["HVT_COMPRESSION"] = "bf16"
        assert space.deviations(cfg) == 2


# --- evidence loading -------------------------------------------------------


def _write_row(dirpath, name, row, wrapper=True):
    path = os.path.join(str(dirpath), name)
    payload = ({"n": name, "cmd": "BENCH_MODEL=zero1 python bench.py",
                "rc": 0, "tail": json.dumps(row)}
               if wrapper else row)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f)
    return path


class TestEvidence:
    def test_load_rows_wrapper_bare_and_garbage(self, tmp_path):
        _write_row(tmp_path, "BENCH_r01.json", {"k": 1})
        _write_row(tmp_path, "BENCH_r02.json", {"k": 2}, wrapper=False)
        (tmp_path / "BENCH_r03.json").write_text("{not json")
        (tmp_path / "NOTES.json").write_text("{}")  # not a BENCH row
        rows = evidence.load_rows(str(tmp_path))
        assert [r["k"] for r in rows] == [1, 2]
        assert rows[0]["_source"] == "BENCH_r01.json"
        assert "zero1" in rows[0]["_cmd"]
        assert rows[1]["_cmd"] == ""

    def test_config_of_legacy_row_inferred(self):
        cfg = evidence.config_of({
            "bucket_bytes": 4 * MB, "k": 4, "compression": "none",
            "compression_ici": "none", "overlap_fraction": 0.5,
        })
        assert cfg["HVT_BUCKET_BYTES"] == 4 * MB
        assert cfg["HVT_BACKWARD_PASSES"] == 4
        assert cfg["HVT_OVERLAP_REDUCTION"] is True

    def test_config_of_stamped_block_wins_over_inference(self):
        cfg = evidence.config_of({
            "bucket_bytes": 4 * MB,
            "config": {"HVT_BUCKET_BYTES": 8 * MB,
                       "HVT_OVERLAP_REDUCTION": False},
        })
        assert cfg["HVT_BUCKET_BYTES"] == 8 * MB
        assert cfg["HVT_OVERLAP_REDUCTION"] is False

    def test_anchor_is_newest_row_with_bucket_attribution(self, tmp_path):
        _write_row(tmp_path, "BENCH_r01.json", {
            "step_ms": {"total": 10.0,
                        "comm_buckets": [{"bytes": MB, "ms": 1.0}]}})
        _write_row(tmp_path, "BENCH_r02.json", {
            "step_ms": {"total": 20.0}})  # newer but too thin
        rows = evidence.load_rows(str(tmp_path))
        assert evidence.anchor_row(rows)["_source"] == "BENCH_r01.json"
        assert evidence.anchor_row([]) is None

    def test_comm_points_exclude_quantized_wire_rows(self):
        rows = [
            {"step_ms": {"comm_buckets": [{"bytes": MB, "ms": 2.0}]}},
            {"compression": "int8",
             "step_ms": {"comm_buckets": [{"bytes": MB, "ms": 0.5}]}},
        ]
        assert evidence.comm_points(rows) == [(float(MB), 2.0)]

    def test_wire_ratio(self):
        assert evidence.wire_ratio("none") == 1.0
        assert evidence.wire_ratio("bf16") == 0.5
        assert evidence.wire_ratio("int8") == 0.25
        assert evidence.wire_ratio(None) == 1.0


# --- the analytic model against a known closed-form optimum -----------------

# Synthetic world: alpha = 1 ms/bucket, beta = 1 ms/MB, payload S = 40 MB,
# compute = 500 ms, input = 0, hiding capacity H = 55 ms (kept BELOW the
# anchor's comm so the physical hidden <= comm clamp never rewrites the
# tradeoff under test).
#
#   total(b) = compute + n*alpha + S*beta - min(H*(n-1)/n, comm, compute)
#   with n = ceil(S/b); d/dn [n*alpha - H*(n-1)/n] = 0  =>  n* = sqrt(H/alpha)
#
# Continuous optimum n* = sqrt(55) ~ 7.4; over the discrete bucket domain
# the argmin is n = 10 => bucket_bytes = 4 MB, total = 500.5 ms (n = 5,
# the 8 MB anchor, predicts 501.0 — the discrete neighbors bracket n*).

ALPHA, BETA_PER_MB, S_MB, COMPUTE, HIDE = 1.0, 1.0, 40, 500.0, 55.0


def _synthetic_evidence(tmp_path):
    # Older row at 4 MB buckets: the second distinct size that gives the
    # least-squares fit its slope (all points sit exactly on the line).
    _write_row(tmp_path, "BENCH_r01.json", {
        "k": 1, "bucket_bytes": 4 * MB, "compression": "none",
        "compression_ici": "none", "overlap_fraction": 0.5,
        "step_ms": {
            "total": 400.0,
            "comm_buckets": [{"bytes": 4 * MB,
                              "ms": ALPHA + 4 * BETA_PER_MB}] * 10,
        },
    })
    # Anchor (newest): 5 buckets of 8 MB => comm = 5*1 + 40*1 = 45 ms;
    # serialized = 500 + 45 = 545; hidden at n=5 is H*(4/5) = 44 ms.
    _write_row(tmp_path, "BENCH_r02.json", {
        "k": 1, "bucket_bytes": 8 * MB, "compression": "none",
        "compression_ici": "none", "overlap_fraction": 0.9,
        "serialized_step_ms_total": COMPUTE + 45.0,
        "step_ms": {
            "total": COMPUTE + 45.0 - HIDE * 4 / 5,
            "compute": COMPUTE, "comm": 45.0, "input": 0.0,
            "comm_buckets": [{"bytes": 8 * MB,
                              "ms": ALPHA + 8 * BETA_PER_MB}] * 5,
        },
    })
    return str(tmp_path)


def _closed_form_total(bucket_bytes):
    import math

    n = max(1, math.ceil(S_MB * MB / bucket_bytes))
    comm = n * ALPHA + S_MB * BETA_PER_MB
    hidden = min(HIDE * (n - 1) / n, comm, COMPUTE) if n > 1 else 0.0
    return COMPUTE + comm - hidden


class TestModelClosedForm:
    def test_fit_recovers_the_synthetic_terms(self, tmp_path):
        m = model.fit(evidence.load_rows(_synthetic_evidence(tmp_path)))
        assert m.alpha_ms == pytest.approx(ALPHA, rel=1e-6)
        assert m.beta_ms_per_byte * MB == pytest.approx(BETA_PER_MB,
                                                        rel=1e-6)
        assert m.payload_bytes == S_MB * MB
        assert m.compute_ms == pytest.approx(COMPUTE)
        assert m.hide_rate_ms == pytest.approx(HIDE)
        assert m.n_points == 15
        # every term can say where it came from
        for term in ("alpha/beta", "payload", "compute", "hide_rate",
                     "anchor"):
            assert "BENCH_r" in m.provenance[term] or \
                "comm samples" in m.provenance[term]

    def test_anchor_row_is_reproduced_exactly(self, tmp_path):
        m = model.fit(evidence.load_rows(_synthetic_evidence(tmp_path)))
        pred = m.predict(m.anchor_config)
        assert pred.total_ms == pytest.approx(m.anchor_total_ms, rel=1e-9)

    def test_search_finds_the_closed_form_optimum(self, tmp_path):
        m = model.fit(evidence.load_rows(_synthetic_evidence(tmp_path)))
        scored = offline.rank(m, space.enumerate_configs(
            knobs=["HVT_BUCKET_BYTES", "HVT_OVERLAP_REDUCTION"],
            environ={}))
        win = offline.best(scored)
        # discrete argmin of n*alpha - H*(n-1)/n over the bucket domain:
        # n = 10 buckets over 40 MB => 4 MB cap
        assert win.config["HVT_BUCKET_BYTES"] == 4 * MB
        assert win.config["HVT_OVERLAP_REDUCTION"] is True
        assert win.prediction.total_ms == pytest.approx(500.5)

    def test_model_matches_independent_brute_force(self, tmp_path):
        """The fitted model's argmin over the bucket domain equals a
        from-scratch brute force of the closed-form cost."""
        m = model.fit(evidence.load_rows(_synthetic_evidence(tmp_path)))
        doms = space.domains()["HVT_BUCKET_BYTES"]
        base = space.default_config()
        for b in doms:
            cfg = dict(base, HVT_BUCKET_BYTES=b)
            assert m.predict(cfg).total_ms == pytest.approx(
                _closed_form_total(b), rel=1e-6), f"bucket={b}"
        best_brute = min(doms, key=_closed_form_total)
        best_model = min(
            doms, key=lambda b: m.predict(
                dict(base, HVT_BUCKET_BYTES=b)).total_ms)
        assert best_brute == best_model == 4 * MB

    def test_quantized_wire_is_ranked_but_unevidenced(self, tmp_path):
        m = model.fit(evidence.load_rows(_synthetic_evidence(tmp_path)))
        pred = m.predict(dict(space.default_config(),
                              HVT_COMPRESSION="int8"))
        assert pred.unevidenced == ("HVT_COMPRESSION",)
        scored = offline.rank(m, space.enumerate_configs(environ={}))
        win = offline.best(scored)
        assert win.prediction.evidenced
        # int8 halves-and-halves the wire, so SOME quantized config
        # out-predicts the winner — and is exactly why require_evidence
        # exists: the model invented the quantize cost.
        free = offline.best(scored, require_evidence=False)
        assert free.score <= win.score

    def test_fit_error_without_anchor(self, tmp_path):
        with pytest.raises(model.FitError):
            model.fit([])
        _write_row(tmp_path, "BENCH_r01.json", {"step_ms": {"total": 1.0}})
        with pytest.raises(model.FitError):
            model.fit(evidence.load_rows(str(tmp_path)))

    def test_check_passes_on_synthetic_evidence(self, tmp_path):
        code, msg = offline.check(_synthetic_evidence(tmp_path))
        assert code == 0, msg
        assert "anchor reproduced within" in msg

    def test_check_exit_2_without_evidence(self, tmp_path):
        code, msg = offline.check(str(tmp_path))
        assert code == 2
        assert "no usable evidence" in msg

    def test_report_names_winner_and_provenance(self, tmp_path):
        m = model.fit(evidence.load_rows(_synthetic_evidence(tmp_path)))
        scored = offline.rank(m, space.enumerate_configs(
            knobs=["HVT_BUCKET_BYTES"], environ={}))
        text = offline.render_report(m, scored, top=3)
        assert "winner: bucket=4MB" in text
        assert "BENCH_r02.json" in text          # provenance is visible
        assert "anchor" in text


# --- probe-plan racing over a fake builder ----------------------------------


class TestRunProbePlan:
    def _plan(self):
        base = space.default_config()
        fast = dict(base, HVT_BUCKET_BYTES=4 * MB)
        slow = dict(base, HVT_BUCKET_BYTES=1 << 18)
        return base, fast, slow

    def test_fastest_candidate_wins(self):
        base, fast, slow = self._plan()
        clock = FakeClock()
        speed = {json.dumps(base, sort_keys=True, default=str): 1.0,
                 json.dumps(fast, sort_keys=True, default=str): 0.5,
                 json.dumps(slow, sort_keys=True, default=str): 2.0}

        def builder(cfg, steps=3):
            return clock.leg([speed[json.dumps(cfg, sort_keys=True,
                                               default=str)]])

        out = insitu.run_probe_plan(
            {"default": base, "candidates": [slow, fast], "steps": 3},
            builder=builder, clock=clock)
        assert out["winner"] == fast
        assert out["improvement_pct"] == pytest.approx(50.0)
        assert len(out["results"]) == 2
        assert out["results"][0]["median_pct"] > 0    # slow lost
        assert out["results"][1]["median_pct"] < 0    # fast won

    def test_all_candidates_slower_keeps_the_default(self):
        base, _, slow = self._plan()
        clock = FakeClock()

        def builder(cfg, steps=3):
            return clock.leg([2.0 if cfg == slow else 1.0])

        out = insitu.run_probe_plan(
            {"default": base, "candidates": [slow]},
            builder=builder, clock=clock)
        assert out["winner"] == base
        assert out["improvement_pct"] == 0.0

    def test_candidate_equal_to_default_is_not_raced(self):
        base, fast, _ = self._plan()
        clock = FakeClock()
        built = []

        def builder(cfg, steps=3):
            built.append(cfg)
            return clock.leg([1.0])

        out = insitu.run_probe_plan(
            {"default": base, "candidates": [dict(base), fast]},
            builder=builder, clock=clock)
        assert out["results"][0]["note"] == "is the default"
        # built once for the default leg, once for the real candidate
        assert built == [base, fast]


# --- in-situ resolve: selection, store, restart reuse -----------------------


class TestInsituResolve:
    def _block(self, tmp_path, **over):
        block = {"mode": "offline",
                 "evidence": _synthetic_evidence(tmp_path),
                 "store": str(tmp_path / "models" / "tune.json")}
        block.update(over)
        return block

    def test_mode_off_is_a_no_op(self):
        tuned, event = insitu.resolve({"mode": "off"}, {})
        assert tuned == {}
        assert event == {"event": "tune_off"}

    def test_offline_selects_and_persists(self, tmp_path):
        block = self._block(tmp_path)
        tuned, event = insitu.resolve(block, {})
        assert tuned["HVT_BUCKET_BYTES"] == str(4 * MB)
        assert tuned["HVT_OVERLAP_REDUCTION"] == "1"
        assert event["event"] == "tune_selected"
        assert event["predicted_total_ms"] == pytest.approx(500.5)
        with open(block["store"], encoding="utf-8") as f:
            rec = json.load(f)
        assert rec["env"] == tuned
        assert rec["mode"] == "offline"

    def test_restart_reuses_the_stored_winner(self, tmp_path):
        """The restart contract: same block, same domains -> the stored
        selection is reused verbatim, nothing is re-fit or re-probed."""
        block = self._block(tmp_path)
        first, ev1 = insitu.resolve(block, {})
        os.remove(os.path.join(block["evidence"], "BENCH_r01.json"))
        os.remove(os.path.join(block["evidence"], "BENCH_r02.json"))
        # evidence is GONE — only the store can answer now
        second, ev2 = insitu.resolve(block, {})
        assert second == first
        assert ev1["event"] == "tune_selected"
        assert ev2["event"] == "tune_reused"
        assert ev2["config"] == ev1["config"]

    def test_changed_block_invalidates_the_store(self, tmp_path):
        block = self._block(tmp_path)
        insitu.resolve(block, {})
        changed = dict(block, knobs=["HVT_OVERLAP_REDUCTION"])
        tuned, event = insitu.resolve(changed, {})
        assert event["event"] == "tune_selected"   # re-searched, not reused
        assert "HVT_BUCKET_BYTES" in tuned         # still exported, unvaried

    def test_probe_mode_uses_the_prober_once_then_reuses(self, tmp_path):
        calls = []

        def prober(plan, env):
            calls.append(plan)
            return {"winner": plan["candidates"][0],
                    "improvement_pct": 5.0, "results": []}

        block = self._block(tmp_path, mode="probe", candidates=2, steps=4)
        tuned, event = insitu.resolve(block, {}, prober=prober)
        assert len(calls) == 1
        plan = calls[0]
        assert plan["steps"] == 4
        assert len(plan["candidates"]) == 2
        assert plan["default"] == space.resolved_config(
            environ=dict(os.environ))
        assert event["event"] == "tune_selected"
        assert event["mode"] == "probe"
        # second resolve: the store answers; the prober must NOT run
        insitu.resolve(block, {}, prober=prober)
        assert len(calls) == 1

    def test_job_env_feeds_the_resolution(self, tmp_path):
        """Spec env participates in resolution context (HVT_TUNE_* and
        the baseline the candidates vary from come from the job's
        resolved env, not just the process env)."""
        calls = []

        def prober(plan, env):
            calls.append((plan, env))
            return {"winner": None, "results": []}

        block = self._block(tmp_path, mode="probe")
        insitu.resolve(block, {"HVT_TUNE_STEPS": 7,
                               "HVT_BACKWARD_PASSES": "4"},
                       prober=prober)
        plan, env = calls[0]
        assert plan["steps"] == 7
        assert plan["default"]["HVT_BACKWARD_PASSES"] == 4
        assert env["HVT_TUNE_STEPS"] == "7"

    def test_missing_evidence_is_a_tune_error(self, tmp_path):
        block = {"mode": "offline", "evidence": str(tmp_path),
                 "store": str(tmp_path / "tune.json")}
        with pytest.raises(insitu.TuneError, match="no usable evidence"):
            insitu.resolve(block, {})

    def test_validate_block_rejects_malformed_blocks(self):
        for bad, why in [
            (["probe"], "mapping"),
            ({"mode": "magic"}, "mode"),
            ({"knobs": []}, "non-empty"),
            ({"knobs": ["HVT_FAULT"]}, "not a tunable knob"),
            ({"steps": 0}, "positive int"),
            ({"candidates": "three"}, "positive int"),
            ({"budget": 5}, "unknown keys"),
        ]:
            with pytest.raises(insitu.TuneError, match=why):
                insitu.validate_block(bad)
        insitu.validate_block({})  # empty block = all defaults: valid


# --- the job-spec surface ---------------------------------------------------


class TestJobSpecTune:
    def test_validate_spec_catches_bad_tune_block(self):
        from horovod_tpu.launch.job import validate_spec

        errors = validate_spec({
            "name": "t", "job": {"command": "python x.py", "nprocs": 1,
                                 "tune": {"mode": "magic"}}})
        assert any("job tune:" in e and "mode" in e for e in errors)

    def test_validate_spec_rejects_tune_on_serve_jobs(self):
        from horovod_tpu.launch.job import validate_spec

        errors = validate_spec({
            "name": "t",
            "job": {"serve": {"replicas": 1}, "command": "python x.py",
                    "nprocs": 1, "tune": {"mode": "off"}}})
        assert any("serve" in e and "tune" in e for e in errors)

    def test_shipped_ci_job_carries_a_valid_tune_block(self):
        from horovod_tpu.launch.job import validate_spec

        path = os.path.join(REPO, "horovod_tpu", "launch", "jobs",
                            "mnist-ci-2proc.yaml")
        with open(path, encoding="utf-8") as f:
            spec = yaml.safe_load(f)
        tune = spec["job"]["tune"]
        assert tune["mode"] == "offline"
        assert "HVT_BUCKET_BYTES" in tune["knobs"]
        assert validate_spec(spec) == []


# --- tier-1 gate: the tuner is trustworthy on the fixture's evidence --------


class TestOfflineCheckClean:
    """`hvt-tune offline --check` over the fixture's BENCH_* rows (three,
    recorded on 8 virtual CPU devices: the report's shape, no speed of
    the system) — the recorded evidence loads, the model reproduces the measured anchor,
    and the search beats its own anchor (ISSUE 19's --check gate)."""

    def test_check_exits_zero_on_repo_evidence(self):
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.tune", "offline",
             "--check", "--evidence", EVIDENCE],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "hvt-tune check: ok" in proc.stdout

    def test_offline_report_runs_end_to_end(self):
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.tune", "offline",
             "--evidence", EVIDENCE, "--top", "5"],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "winner:" in proc.stdout
        assert "calibrated to BENCH_" in proc.stdout

    def test_missing_evidence_exits_two(self, tmp_path):
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.tune", "offline",
             "--check", "--evidence", str(tmp_path)],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 2, proc.stdout + proc.stderr


# --- slow: predicted ranking vs measured ranking ----------------------------


@pytest.mark.slow
class TestPredictedRankingMatchesMeasured:
    """The offline acceptance gate: on three well-separated candidate
    configs, the analytic model's predicted ORDER matches a real
    paired-leg measurement on this host (the evidence rows were recorded
    on the same container, so the fitted terms transfer)."""

    def test_three_config_ranking(self):
        # The fitted terms only transfer to the workload the evidence
        # describes: bench_zero1's MLP (hidden 2048, ~21 MB of f32
        # gradients, 32/chip over 8 CPU devices). Probing a smaller
        # model would measure a different bucket economy.
        os.environ.setdefault("HVT_PLATFORM", "cpu")
        os.environ.setdefault("HVT_NUM_CPU_DEVICES", "8")
        os.environ.setdefault("HVT_FAST_RNG", "1")
        rows = evidence.load_rows(REPO)
        m = model.fit(rows)
        base = dict(space.default_config(),
                    HVT_BACKWARD_PASSES=m.anchor_k)
        # Three configs along the overlap-starvation axis, where the
        # model's fitted terms and the host's physics agree: the fitted
        # optimum region (4 MB: 6 buckets, comm mostly hidden), a
        # half-starved middle (16 MB: 2 buckets, half the comm exposed)
        # and the monolithic default (64 MB: one bucket, nothing to
        # overlap).  Sub-MB fragmentation is deliberately NOT a
        # candidate: the serialized per-bucket alpha the model
        # extrapolates from does not transfer to overlapped execution,
        # where launch costs hide under compute.
        configs = [dict(base, HVT_BUCKET_BYTES=b)
                   for b in (4 * MB, 16 * MB, 64 * MB)]
        predicted = [m.predict(c).total_ms for c in configs]

        legs = []
        for c in configs:
            leg = insitu.build_probe_step(c, hidden=2048,
                                          per_chip_batch=32, steps=2)
            leg()  # settle
            legs.append(leg)
        # measure each leg against the first with the paired discipline;
        # the sign/magnitude of the medians orders the configs.
        rel = [0.0]
        for leg in legs[1:]:
            res = probe.paired_compare(legs[0], leg, pairs_min=3,
                                       pairs_cap=9)
            rel.append(res.median_pct)
        pred_order = sorted(range(3), key=lambda i: predicted[i])
        meas_order = sorted(range(3), key=lambda i: rel[i])
        assert pred_order == meas_order, (
            f"predicted {predicted} (order {pred_order}) vs measured "
            f"relative {rel} (order {meas_order})")
