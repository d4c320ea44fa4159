"""In-situ mode: tune at job start, remember across restarts.

`launch.job.run_job` calls `resolve()` when a spec carries a ``tune:``
block (the launcher's ``--tune`` flag builds the same block):

    tune:
      mode: probe          # offline | probe | off
      # knobs: [HVT_BUCKET_BYTES, HVT_OVERLAP_REDUCTION]
      # evidence: .        # BENCH_* row dir (default HVT_TUNE_EVIDENCE)
      # steps: 3           # probe: real opt steps per timed leg
      # candidates: 3      # probe: shortlist size from the offline rank
      # store: path        # default <PS_MODEL_PATH>/tune.json

``offline`` trusts the analytic model outright; ``probe`` takes the
model's shortlist and races each candidate against the config the job
would otherwise run — a few REAL steps apiece in a subprocess (the
launcher process must never initialize jax), decided by the same
paired-leg discipline (`tune.probe`).

The winner is written into the resolved env (spec-pinned env still
wins: an operator's explicit knob is a decision, not a suggestion) and
journaled. The selection is also persisted to ``store`` keyed by a
fingerprint of the block + the registry's tunable domains, so a
RESTART of the same job reuses the stored winner instead of re-probing
— `launch.job._reset_journal` deliberately leaves ``tune.json`` alone.

``HVT_BACKWARD_PASSES`` (K) is only tuned when ``knobs:`` names it
explicitly: K changes the effective batch (numerics), and a tuner must
not silently trade convergence for wall clock.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import tempfile

from horovod_tpu.analysis import registry
from horovod_tpu.tune import evidence as evidence_lib
from horovod_tpu.tune import model as model_lib
from horovod_tpu.tune import offline as offline_lib
from horovod_tpu.tune import space as space_lib

__all__ = ["TuneError", "validate_block", "resolve", "build_probe_step",
           "run_probe_plan"]

_BLOCK_KEYS = {"mode", "knobs", "evidence", "steps", "candidates", "store"}
_MODES = ("off", "offline", "probe")


class TuneError(ValueError):
    """A tune: block that cannot be resolved (bad keys, no evidence)."""


def validate_block(block) -> None:
    """Raise TuneError on a malformed block — `validate_spec`'s dry-build
    hook, so a typo fails before any side effect."""
    if not isinstance(block, dict):
        raise TuneError(f"must be a mapping, got {block!r}")
    unknown = set(block) - _BLOCK_KEYS
    if unknown:
        raise TuneError(
            f"unknown keys {sorted(unknown)} (valid: {sorted(_BLOCK_KEYS)})"
        )
    mode = block.get("mode", "probe")
    if mode not in _MODES:
        raise TuneError(f"mode must be one of {_MODES}, got {mode!r}")
    knobs = block.get("knobs")
    if knobs is not None:
        doms = space_lib.domains()
        if not isinstance(knobs, list) or not knobs:
            raise TuneError(f"knobs must be a non-empty list, got {knobs!r}")
        for name in knobs:
            if name not in doms:
                raise TuneError(
                    f"{name!r} is not a tunable knob — registry rows with "
                    f"tunable= metadata: {sorted(doms)}"
                )
    for key in ("steps", "candidates"):
        if key in block and (not isinstance(block[key], int)
                             or block[key] < 1):
            raise TuneError(f"{key} must be a positive int, "
                            f"got {block[key]!r}")


def _fingerprint(block: dict) -> str:
    basis = {
        "block": {k: block.get(k) for k in sorted(_BLOCK_KEYS)},
        "domains": {n: list(v) for n, v in space_lib.domains().items()},
    }
    return hashlib.sha256(
        json.dumps(basis, sort_keys=True, default=str).encode()
    ).hexdigest()[:16]


def _subprocess_prober(plan: dict, env: dict) -> dict:
    """Run the probe plan in a fresh interpreter: the caller is the
    LAUNCHER, which must never initialize jax itself."""
    with tempfile.TemporaryDirectory(prefix="hvt-tune-") as td:
        plan_path = os.path.join(td, "plan.json")
        out_path = os.path.join(td, "out.json")
        # Plan handoff in a private tempdir, consumed once by the
        # child; nothing restart-durable can tear here.
        with open(plan_path, "w", encoding="utf-8") as f:  # hvt: noqa[HVT005]
            json.dump(plan, f)
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.tune", "probe",
             "--plan", plan_path, "--out", out_path],
            env=env, capture_output=True, text=True,
        )
        if proc.returncode != 0 or not os.path.exists(out_path):
            raise TuneError(
                f"probe subprocess failed (rc {proc.returncode}): "
                f"{(proc.stderr or proc.stdout).strip()[-500:]}"
            )
        with open(out_path, encoding="utf-8") as f:
            return json.load(f)


def resolve(block: dict, env: dict, *, workdir: str | None = None,
            prober=None) -> tuple[dict, dict]:
    """Resolve a ``tune:`` block into ``(tuned_env, event)``.

    ``tuned_env`` maps env-var names to string values (empty for mode
    off); ``event`` describes what happened for the journal:
    ``{"event": "tune_selected" | "tune_reused" | "tune_off", ...}``.
    ``prober`` overrides the probe runner (tests inject a fake).
    """
    validate_block(block)
    mode = block.get("mode", "probe")
    if mode == "off":
        return {}, {"event": "tune_off"}
    merged = dict(os.environ)
    merged.update({str(k): str(v) for k, v in (env or {}).items()})
    model_dir = merged.get("PS_MODEL_PATH") or "./models"
    store = block.get("store") or os.path.join(model_dir, "tune.json")
    fp = _fingerprint(block)
    if os.path.exists(store):
        try:
            with open(store, encoding="utf-8") as f:
                rec = json.load(f)
        except (OSError, ValueError):
            rec = None
        if rec and rec.get("fingerprint") == fp:
            return dict(rec.get("env") or {}), {
                "event": "tune_reused", "mode": rec.get("mode", mode),
                "store": store, "config": rec.get("config"),
            }
    evidence_dir = (block.get("evidence")
                    or registry.get_str("HVT_TUNE_EVIDENCE", environ=merged)
                    or workdir or ".")
    rows = evidence_lib.load_rows(evidence_dir)
    try:
        cost = model_lib.fit(rows)
    except model_lib.FitError as e:
        raise TuneError(f"{e} (evidence dir: {evidence_dir})") from None
    knobs = block.get("knobs")
    if knobs is None:
        knobs = [n for n in space_lib.domains()
                 if n != "HVT_BACKWARD_PASSES"]
    scored = offline_lib.rank(
        cost, space_lib.enumerate_configs(knobs=knobs, environ=merged))
    win = offline_lib.best(scored)
    if win is None:
        raise TuneError("no evidenced candidate config — record more "
                        "BENCH rows into the evidence dir")
    detail: dict = {"predicted_total_ms": round(win.prediction.total_ms, 3)}
    config = win.config
    if mode == "probe":
        shortlist, seen = [], set()
        want = int(block.get("candidates")
                   or registry.get_int("HVT_TUNE_CANDIDATES",
                                       environ=merged))
        for s in scored:
            key = json.dumps(s.config, sort_keys=True, default=str)
            if s.prediction.evidenced and key not in seen:
                seen.add(key)
                shortlist.append(s.config)
            if len(shortlist) >= want:
                break
        plan = {
            "default": space_lib.resolved_config(environ=merged),
            "candidates": shortlist,
            "steps": int(block.get("steps")
                         or registry.get_int("HVT_TUNE_STEPS",
                                             environ=merged)),
        }
        probe_out = (prober or _subprocess_prober)(plan, merged)
        config = probe_out.get("winner") or plan["default"]
        detail["probe"] = probe_out.get("results")
    tuned_env = space_lib.env_of(config)
    rec = {
        "fingerprint": fp, "mode": mode, "config": config,
        "env": tuned_env, "detail": detail,
    }
    os.makedirs(os.path.dirname(store) or ".", exist_ok=True)
    # The store is a cache, not an artifact: the reader above treats a
    # torn/corrupt file as a miss and refits, so no sidecar is needed.
    with open(store, "w", encoding="utf-8") as f:  # hvt: noqa[HVT005]
        json.dump(rec, f, indent=1, sort_keys=True)
    event = {"event": "tune_selected", "mode": mode, "store": store,
             "config": config}
    event.update(detail)
    return tuned_env, event


# --- the probe side (runs inside `python -m horovod_tpu.tune probe`) --------


def build_probe_step(config: dict, *, hidden: int = 1024,
                     per_chip_batch: int = 16, steps: int = 3):
    """Compile one candidate config into a zero-arg timed leg: ``steps``
    real ZeRO-1 optimizer steps of a two-layer MLP, fused into one
    program whose fetched scalar depends on every step's result.

    jax-heavy — only the probe subprocess calls this."""
    import jax
    import numpy as np
    import optax
    from flax import linen as nn

    import horovod_tpu as hvt

    hvt.init()
    n_chips = jax.device_count()
    k = int(config.get("HVT_BACKWARD_PASSES", 1))
    global_batch = per_chip_batch * n_chips

    class Mlp(nn.Module):
        @nn.compact
        def __call__(self, x, *, train: bool = False):
            import jax.numpy as jnp

            x = x.astype(jnp.float32)
            x = nn.relu(nn.Dense(hidden)(x))
            x = nn.relu(nn.Dense(hidden)(x))
            return nn.Dense(16)(x)

    trainer = hvt.Trainer(
        Mlp(),
        hvt.DistributedOptimizer(
            optax.adam(hvt.scale_lr(1e-3)),
            backward_passes_per_step=k,
            average_aggregated_gradients=True,
            compression=str(config.get("HVT_COMPRESSION", "none")),
            compression_ici=str(config.get("HVT_COMPRESSION_ICI", "none")),
        ),
        loss="sparse_categorical_crossentropy",
        shard_update=True,
        overlap_reduction=bool(config.get("HVT_OVERLAP_REDUCTION", True)),
        bucket_bytes=int(config.get("HVT_BUCKET_BYTES")
                         or space_lib.DEFAULT_BUCKET_BYTES),
    )
    rng = np.random.RandomState(0)
    x = rng.rand(2048, 512).astype(np.float32)
    y = rng.randint(0, 16, 2048).astype(np.int32)

    def draw():
        idx = rng.randint(0, len(x), size=global_batch)
        return x[idx], y[idx]

    def step_batch():
        # One optimizer step's feed: [G, F] for k=1, a [k, G, F]
        # microbatch stack for the accumulating step (`_train_chunk`'s
        # shape contract).
        if k == 1:
            return draw()
        micro = [draw() for _ in range(k)]
        return tuple(np.stack([m[i] for m in micro]) for i in range(2))

    state = trainer.build(draw()[0])
    scale = np.float32(1.0)
    zero_acc = {m: np.float32(0) for m in trainer.metric_names}
    chunks = [step_batch() for _ in range(steps)]
    mega = tuple(np.stack([c[i] for c in chunks]) for i in range(2))
    dev = trainer._shard_chunk(mega, 2 if k > 1 else 1)
    compiled = trainer._train_chunk.lower(
        state, dev, scale, zero_acc).compile()
    w_state, _, w_acc = compiled(state, dev, scale, zero_acc)
    float(jax.device_get(w_acc["loss"]))  # settle: compile + first run
    holder = {"state": w_state}

    def leg():
        holder["state"], _, acc = compiled(
            holder["state"], dev, scale, zero_acc)
        return float(jax.device_get(acc["loss"]))

    return leg


def run_probe_plan(plan: dict, *, builder=build_probe_step,
                   clock=None) -> dict:
    """Race every candidate against the default config with the
    paired-leg discipline; pick the winner. ``builder``/``clock`` are
    injectable so the race logic tests over a fake clock."""
    import time

    from horovod_tpu.tune import probe as probe_lib

    clock = clock or time.perf_counter
    steps = int(plan.get("steps", 3))
    default_cfg = plan["default"]
    base_leg = builder(default_cfg, steps=steps)
    base_leg()  # settle
    results = []
    best_cfg, best_pct = None, 0.0
    for cand in plan.get("candidates", []):
        if cand == default_cfg:
            results.append({"config": cand, "median_pct": 0.0,
                            "mad_pct": 0.0, "pairs": 0,
                            "note": "is the default"})
            continue
        leg = builder(cand, steps=steps)
        leg()  # settle
        # a = default, b = candidate: negative median means the
        # candidate is FASTER than what the job would otherwise run.
        res = probe_lib.paired_compare(base_leg, leg, clock=clock)
        results.append({"config": cand,
                        "median_pct": round(res.median_pct, 3),
                        "mad_pct": round(res.mad_pct, 3),
                        "pairs": res.pairs,
                        "converged": res.converged})
        if res.median_pct < best_pct:
            best_cfg, best_pct = cand, res.median_pct
    return {
        "winner": best_cfg or default_cfg,
        "improvement_pct": round(-best_pct, 3),
        "results": results,
    }
