"""Controls of the family ``ssm_moe_lm``: what has to FAIL its ``LIMITS``,
driven through the harness's own comparison (`run.reference_check`, the
cell's limits, ``ok``) on the chip at a cell's own sizes and traffic.

    PYTHONPATH=. python3 chipbench/families/ssm_moe_lm_control.py \\
        --workload granite-4.0-h-small.seq4k.1chip --seeds 7 11 --faults 2

One JSON line per seed and variant, then a summary of which limits each
variant passed and failed over the seeds. The variants:

``system``       the program as the cell builds it: has to be ok.
``low_precision_reference``  the lower-precision control: the family's
                 reference with every parameter rounded to float8_e4m3fn
                 (the nearest precision below the stated bfloat16) and the
                 recurrence's state rounded to bfloat16 after every token
                 stands in for the system: has to fail.
Faults planted in the program (on the first ``--faults`` seeds; each has to
fail), by replacing one function of the module (or class) named while the
program is traced:
``norm_before_gate``  the state-space layer's output normalised first and
                 gated afterwards (Mamba-2's other order).
``b_c_swapped``  C written along and B read with.
``dt_without_bias``  ``dt = softplus(dt_raw)``, the bias left out.
``decay_inverted``  ``a = exp(-dt A)`` with A already below zero: the state
                 grows where it should fade.
``gates_over_all``  the gates a softmax over all the router's logits, the
                 chosen ones picked from it (they no longer add up to 1).
``residual_multiplier_left_out``  ``x + out`` in every block.
``head_untied``  the head reads a table of its own, not the embedding's.
``next_heads``   every head's output through the NEXT head's rows of W_o
                 (the held block shifted by one head), both mixers.

Nothing here is read by `chipbench.run`; the readings stand beside
``LIMITS`` in the family's file.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
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
from horovod_tpu.models import moe
from horovod_tpu.models import transformer
from horovod_tpu.ops import ssd

ROOT = pathlib.Path(__file__).resolve().parents[2]
_HYBRID_CONTROL = run.load_module(
    pathlib.Path(__file__).with_name("hybrid_moe_lm_control.py"))
# (this family states no FAR_OFF, so its reports hold no far-off share)
NAMES = tuple(n for n in _HYBRID_CONTROL.NAMES if n != "far_off_share")
LowPrecisionReference = _HYBRID_CONTROL.LowPrecisionReference

_project_out = program.project_out


def _norm_then_gate(y, z, scale, eps, *, heads_axis, n_channels):
    y = y.astype(jnp.float32)
    squares = jnp.sum(y * y, axis=(-2, -1), keepdims=True)
    if heads_axis is not None:
        squares = jax.lax.psum(squares, heads_axis)
    normed = y * jax.lax.rsqrt(squares / n_channels + eps) * scale
    return normed * jax.nn.silu(z.astype(jnp.float32))


def _softmax_over_all(logits, chosen, *, scoring, scale):
    del scoring
    scores = jax.nn.softmax(logits, axis=-1)
    return jnp.take_along_axis(scores, chosen, axis=-1) * scale


# {fault: (module or class, one of its functions, what stands in for it)}
FAULTS = {
    "norm_before_gate": (program, "gated_norm", _norm_then_gate),
    "b_c_swapped": (program, "split_b_c", lambda b_c: tuple(
        jnp.split(b_c, 2, axis=-1))[::-1]),
    "dt_without_bias": (program, "time_step", lambda raw, dt_bias: (
        jax.nn.softplus(raw.astype(jnp.float32)))),
    "decay_inverted": (ssd, "decay_rate", lambda a_log: jnp.exp(
        a_log.astype(jnp.float32))),
    "gates_over_all": (moe, "_gates", _softmax_over_all),
    "residual_multiplier_left_out": (
        program, "residual", lambda x, out, multiplier: x + out),
    "head_untied": (transformer.LMHead, "_kernel_of", lambda self, table: (
        jax.random.normal(jax.random.PRNGKey(0), table.T.shape, table.dtype)
        * table.shape[-1] ** -0.5)),
    "next_heads": (program, "project_out", lambda out, kernel: _project_out(
        out, jnp.roll(kernel, -1, axis=0))),
}


@contextlib.contextmanager
def planted(fault):
    """The program with one of its functions replaced while it is traced.
    `ssd.ssd_scan` is jitted and keeps its traces by shape: they are dropped
    on the way in and out, so that neither side meets the other's."""
    if fault is None:
        yield
        return
    module, attr, stand_in = FAULTS[fault]
    ssd.ssd_scan.clear_cache()
    try:
        with mock.patch.object(module, attr, stand_in):
            yield
    finally:
        ssd.ssd_scan.clear_cache()


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
        # Two states of 13.5 GB do not fit the chip: let go of this seed's
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
