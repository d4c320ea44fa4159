"""Controls of the family ``latent_moe_lm``: what has to FAIL its ``LIMITS``,
driven through the harness's own comparison (`run.reference_check`, the
cell's limits, ``ok``) on the chip at a cell's own sizes and traffic.

    PYTHONPATH=. python3 chipbench/families/latent_moe_lm_control.py \\
        --workload kanana-2-30b-a3b.seq8k.1chip --seeds 7 11 --faults 2

One JSON line per seed and variant, then a summary of which limits each
variant passed and failed over the seeds. The variants:

``system``       the program as the cell builds it: has to be ok.
``fp8_reference``  the lower-precision control: the family's reference with
                 every parameter rounded to float8_e4m3fn (the nearest
                 precision below the stated bfloat16) stands in for the
                 system: has to fail.
Faults planted in the routed layer of the program (on the first
``--faults`` seeds; each has to fail):
``wrong_block``  the program holds the NEXT block of experts (the same
                 arrays, gated by the tokens routed to other indices).
``half_dropped`` the row budget is half the expected rows, so about half of
                 the routed rows are dropped (and counted).
``gates_unscaled``  the gates are not scaled by ``routed_scaling_factor``.
... and one in the attention, which moves every token (what ``bias`` is for):
``rope_base_1e4``  the rotary base is 10,000, not the configuration's.

Nothing here is read by `chipbench.run`; the readings stand beside
``LIMITS`` in the family's file.
"""

from __future__ import annotations

import argparse
import collections
import functools
import json
import pathlib
import sys
import types

import jax
import jax.numpy as jnp

import horovod_tpu as hvt
from chipbench import run
from horovod_tpu.models import moe

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAMES = ("bias", "mean_abs_diff", "rel_rms", "median_abs_diff",
         "far_off_share")


class ReferenceAt:
    """Stands where `reference_check` expects the program's module: the
    family's reference on parameters rounded to ``dtype``."""

    def __init__(self, cell, dtype):
        self.loss = functools.partial(
            cell["family"].per_token_loss, config=cell["config"])
        self.dtype = dtype

    def apply(self, variables, xb, train, labels):
        del train
        rounded = jax.tree.map(
            lambda a: a.astype(self.dtype).astype(jnp.float32),
            variables["params"])
        return self.loss(rounded, xb[0], labels[0])[None], None


def variants(cell, trainer, with_faults: bool):
    """{name: (module, patch)}: what stands in for the system, and the
    module attribute to set while it is traced."""
    spec, config = cell["workload"]["trainer"], cell["config"]
    build = functools.partial(
        cell["family"].build, trainer_spec=spec, mesh=trainer.mesh)
    found = {"system": (trainer.module, {}),
             "fp8_reference": (ReferenceAt(cell, jnp.float8_e4m3fn), {})}
    if with_faults:
        held = config["n_routed_experts"]
        found["wrong_block"] = (build(config | {
            "held_experts_start": config["held_experts_start"] + held}), {})
        found["half_dropped"] = (trainer.module, {"BUDGET_FACTOR": 0.5})
        found["gates_unscaled"] = (
            build(config | {"routed_scaling_factor": 1.0}), {})
        found["rope_base_1e4"] = (build(config | {"rope_theta": 10000}), {})
    return found


def main(argv=None, *, root: pathlib.Path = ROOT) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--faults", type=int, default=0,
                        help="plant the faults on the first N seeds")
    args = parser.parse_args(argv)
    cell = run.load_cell(root, args.workload)
    hvt.runtime.use_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    hvt.init()
    devices = jax.devices()[:cell["chips"]]
    traffic = cell["traffic"]
    make = run.load_attr(
        root / "chipbench" / "traffic" / f"{traffic['kind']}.py", "make")
    vocab = cell["family"].sizes(cell["config"])["vocab_size"]
    verdicts = collections.defaultdict(lambda: collections.Counter())
    for n, seed in enumerate(args.seeds):
        trainer = run.build_trainer(cell, devices, seed)
        run.init_state(trainer, traffic["seq_len"])
        x, y = make(seed, traffic, vocab)
        for name, (module, patch) in variants(
                cell, trainer, n < args.faults).items():
            stand_in = types.SimpleNamespace(
                module=module, state=trainer.state, dp_size=trainer.dp_size)
            kept = {attr: getattr(moe, attr) for attr in patch}
            try:
                for attr, value in patch.items():
                    setattr(moe, attr, value)
                report = run.reference_check(
                    stand_in, cell, x, y, row=seed % len(x))
            finally:
                for attr, value in kept.items():
                    setattr(moe, attr, value)
            failed = sorted(k for k, limit in report["limits"].items()
                            if not report[k] <= limit)
            verdicts[name]["runs"] += 1
            verdicts[name]["ok"] += report["ok"]
            for k in failed:
                verdicts[name][f"failed {k}"] += 1
            print(json.dumps({
                "seed": seed, "variant": name, "ok": report["ok"],
                "failed": failed, **{k: report[k] for k in NAMES}}),
                flush=True)
        del trainer
    print(json.dumps({"limits": cell["limits"],
                      "summary": {k: dict(v) for k, v in verdicts.items()}}),
          flush=True)
    sound = verdicts["system"]["ok"] == verdicts["system"]["runs"]
    caught = all(v["ok"] == 0 for k, v in verdicts.items() if k != "system")
    return 0 if sound and caught else 1


if __name__ == "__main__":
    sys.exit(main())
