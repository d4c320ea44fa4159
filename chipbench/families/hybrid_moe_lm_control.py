"""Controls of the family ``hybrid_moe_lm``: what has to FAIL its ``LIMITS``,
driven through the harness's own comparison (`run.reference_check`, the
cell's limits, ``ok``) on the chip at a cell's own sizes and traffic.

    PYTHONPATH=. python3 chipbench/families/hybrid_moe_lm_control.py \\
        --workload solar-open2-250b.seq8k.1chip --seeds 7 11 --faults 2

One JSON line per seed and variant, then a summary of which limits each
variant passed and failed over the seeds. The variants:

``system``       the program as the cell builds it: has to be ok.
``low_precision_reference``  the lower-precision control: the family's
                 reference with every parameter rounded to float8_e4m3fn
                 (the nearest precision below the stated bfloat16) and the
                 delta rule's state rounded to bfloat16 after every token
                 stands in for the system: has to fail.
Faults planted in the program's mixers (on the first ``--faults`` seeds;
each has to fail), by replacing one function of models/hybrid_moe_lm.py
while the program is traced:
``beta_not_doubled``  beta = sigmoid, without the x 2 of
                 ``kda_allow_neg_eigval``.
``decay_per_head``  one decay a head (the channels' mean) instead of one a
                 channel.
``conv_reversed``  the short convolution's taps in reverse order.
``next_heads``   every head's output through the NEXT head's rows of W_o
                 (the held block shifted by one head).
``gate_left_out``  no output gate, in either mixer.

Nothing here is read by `chipbench.run`; the readings stand beside
``LIMITS`` in the family's file.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import functools
import json
import pathlib
import sys
import types
from unittest import mock

import jax
import jax.numpy as jnp

import horovod_tpu as hvt
from chipbench import run
from horovod_tpu.models import hybrid_moe_lm as program

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAMES = ("bias", "mean_abs_diff", "rel_rms", "median_abs_diff",
         "far_off_share")

_log_decay, _short_conv = program.log_decay, program.short_conv
_project_out = program.project_out
# {fault: (function of models/hybrid_moe_lm.py, what stands in for it)}
FAULTS = {
    "beta_not_doubled": ("write_strength", lambda logits: jax.nn.sigmoid(
        logits.astype(jnp.float32))),
    "decay_per_head": ("log_decay", lambda a_log, dt_bias, low: (
        jnp.broadcast_to(jnp.mean(_log_decay(a_log, dt_bias, low), axis=-1,
                                  keepdims=True), low.shape))),
    "conv_reversed": ("short_conv", lambda x, taps: _short_conv(
        x, taps[::-1])),
    "next_heads": ("project_out", lambda out, kernel: _project_out(
        out, jnp.roll(kernel, -1, axis=0))),
    "gate_left_out": ("output_gate", lambda logits: jnp.ones(
        logits.shape, jnp.float32)),
}


def planted(fault):
    """The program with one of its functions replaced while it is traced."""
    if fault is None:
        return contextlib.nullcontext()
    attr, stand_in = FAULTS[fault]
    return mock.patch.object(program, attr, stand_in)


class LowPrecisionReference:
    """Stands where `reference_check` expects the program's module: the
    family's reference on parameters rounded to ``dtype``, its recurrent
    state kept in ``state_dtype``."""

    def __init__(self, cell, dtype, state_dtype):
        self.loss = functools.partial(
            cell["family"].per_token_loss, config=cell["config"],
            state_dtype=state_dtype)
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
        "low_precision_reference": (LowPrecisionReference(
            cell, jnp.float8_e4m3fn, jnp.bfloat16), None)}
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
                "failed": failed, **{k: report[k] for k in NAMES}}),
                flush=True)
        # Two states of 10 GB do not fit the chip: let go of this seed's
        # before the next is made.
        del trainer, stand_in
    print(json.dumps({"limits": cell["limits"],
                      "summary": {k: dict(v) for k, v in verdicts.items()}}),
          flush=True)
    sound = verdicts["system"]["ok"] == verdicts["system"]["runs"]
    caught = all(v["ok"] == 0 for k, v in verdicts.items() if k != "system")
    return 0 if sound and caught else 1


if __name__ == "__main__":
    sys.exit(main())
