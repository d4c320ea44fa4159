"""Controls of the family ``window_moe_lm``: what has to FAIL its
``LIMITS``, driven through the harness's own comparison
(`run.reference_check`, the cell's limits, ``ok``) on the chip at a cell's
own sizes and traffic.

    PYTHONPATH=. python3 chipbench/families/window_moe_lm_control.py \\
        --workload laguna-xs.2.seq8k.1chip --seeds 7 11 --faults 2

One JSON line per seed and variant, then a summary of which limits each
variant passed and failed over the seeds. The variants:

``system``       the program as the cell builds it: has to be ok.
``low_precision_reference``  the lower-precision control: the family's
                 reference with every parameter rounded to float8_e4m3fn,
                 the nearest precision below the stated bfloat16, stands in
                 for the system: has to fail.
Faults planted in the program (on the first ``--faults`` seeds; each has to
fail), by replacing one function of the module named while the program is
traced:
``rotary_adjacent_pairs``  the rotary turns channels 2j and 2j + 1
                 together (the interleaved convention) instead of j and
                 j + r/2.
``yarn_dropped`` the full layers' rotary plain at its base 500,000: no
                 blend of the frequencies, no attention factor.
``attention_factor_left_out``  YaRN's frequencies without its factor on
                 cos and sin.
``window_ignored``  the window layers read every key before a query.
``bases_swapped``  the full layers' rotary at 10,000 and the window
                 layers' at 500,000.
``gate_left_out``  no output gate, in either kind.
``routed_scale_one``  the routed gates normalised and not scaled by 2.5.

Nothing here is read by `chipbench.run`; the readings stand beside
``LIMITS`` in the family's file.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import functools
import json
import pathlib
import sys
import types
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

import horovod_tpu as hvt
from chipbench import run
from horovod_tpu.models import hybrid_moe_lm as program
from horovod_tpu.models import moe

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAMES = ("bias", "mean_abs_diff", "rel_rms", "median_abs_diff",
         "far_off_share")

_partial_rope, _flash, _gates = (
    program.partial_rope, program.flash_attention, moe._gates)


def _adjacent_pairs(x, positions, rotary):
    """The rotary's angles on the pairs (2j, 2j + 1) of the first r."""
    dims, width = rotary.dims, x.shape[-1]
    order = np.concatenate([np.arange(0, dims, 2), np.arange(1, dims, 2),
                            np.arange(dims, width)])
    return _partial_rope(x[..., order], positions, rotary)[
        ..., np.argsort(order)]


def _with(change):
    """`partial_rope` with its `Rotary` changed by ``change``."""
    return lambda x, positions, rotary: _partial_rope(
        x, positions, change(rotary))


_SWAPPED = {500000.0: 10000.0, 10000.0: 500000.0}

# {fault: (module, one of its functions, what stands in for it)}
FAULTS = {
    "rotary_adjacent_pairs": (program, "partial_rope", _adjacent_pairs),
    "yarn_dropped": (program, "partial_rope", _with(
        lambda r: dataclasses.replace(r, yarn=None))),
    "attention_factor_left_out": (program, "partial_rope", _with(
        lambda r: dataclasses.replace(r, yarn=r.yarn and dataclasses.replace(
            r.yarn, attention_factor=1.0)))),
    "window_ignored": (program, "flash_attention", lambda q, k, v, **kw: (
        _flash(q, k, v, **{**kw, "window": None}))),
    "bases_swapped": (program, "partial_rope", _with(
        lambda r: dataclasses.replace(r, base=_SWAPPED[r.base]))),
    "gate_left_out": (program, "output_gate", lambda logits: jnp.ones(
        logits.shape, jnp.float32)),
    "routed_scale_one": (moe, "_gates", lambda logits, chosen, *, scoring,
                         scale: _gates(logits, chosen, scoring=scoring,
                                       scale=1.0)),
}


def planted(fault):
    """The program with one of its functions replaced while it is traced."""
    if fault is None:
        return contextlib.nullcontext()
    module, attr, stand_in = FAULTS[fault]
    return mock.patch.object(module, attr, stand_in)


class LowPrecisionReference:
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
    """{name: (module, fault planted while it is traced)}."""
    found = {
        "system": (trainer.module, None),
        "low_precision_reference": (
            LowPrecisionReference(cell, jnp.float8_e4m3fn), None)}
    if with_faults:
        found.update({name: (trainer.module, name) for name in FAULTS})
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
    names = [k for k in NAMES if k != "far_off_share"
             or hasattr(cell["family"], "FAR_OFF")]
    verdicts = collections.defaultdict(collections.Counter)
    for n, seed in enumerate(args.seeds):
        trainer = run.build_trainer(cell, devices, seed)
        run.init_state(trainer, traffic["seq_len"])
        x, y = make(seed, traffic, vocab)
        for name, (module, fault) in variants(
                cell, trainer, n < args.faults).items():
            stand_in = types.SimpleNamespace(
                module=module, state=trainer.state, dp_size=trainer.dp_size)
            with planted(fault):
                report = run.reference_check(
                    stand_in, cell, x, y, row=seed % len(x))
            failed = sorted(k for k, limit in report["limits"].items()
                            if not report[k] <= limit)
            verdicts[name]["runs"] += 1
            verdicts[name]["ok"] += report["ok"]
            for k in failed:
                verdicts[name][f"failed {k}"] += 1
            print(json.dumps({
                "seed": seed, "variant": name, "ok": report["ok"],
                "failed": failed, **{k: report[k] for k in names}}),
                flush=True)
        # Two states of 9.2 GB do not fit the chip, and a trace the jit
        # keeps may still reach this one: free its buffers before the next
        # seed's are made.
        for leaf in jax.tree.leaves(trainer.state):
            if isinstance(leaf, jax.Array):
                leaf.delete()
        del trainer, stand_in
    print(json.dumps({"limits": cell["limits"],
                      "summary": {k: dict(v) for k, v in verdicts.items()}}),
          flush=True)
    sound = verdicts["system"]["ok"] == verdicts["system"]["runs"]
    caught = all(v["ok"] == 0 for k, v in verdicts.items() if k != "system")
    return 0 if sound and caught else 1


if __name__ == "__main__":
    sys.exit(main())
