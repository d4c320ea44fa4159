"""One run of one benchmark cell on the chip.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it loads the cell's files, builds the model at its published
widths with parameters made on the device from ``--seed``, checks it against
its family's float32 reference, warms the one shape the cell uses, measures
a window of about ``--seconds`` through `Trainer.fit(cache=None)` and prints
its phases as JSON lines. The LAST line is the result (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
traced, and ``compared``: every number behind ``correct`` beside its limit,
which are also the last lines on standard error): the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``. It
needs a TPU with as many chips as the cell asks for and exits non-zero, with
no result line, otherwise.

No cell, configuration, traffic, metric or model is named in this file: each
is a file found by the name `BENCHMARK.json` gives it, and the model, its
reference and its counts by the ``family`` the configuration names (see
README.md).
"""

import time

_T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import functools  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

import jax  # noqa: E402
import jax.monitoring  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import horovod_tpu as hvt  # noqa: E402
from chipbench import reduce, reference  # noqa: E402
from horovod_tpu.parallel import sharding as sharding_lib  # noqa: E402
from horovod_tpu.training.train_state import TrainState  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
WARMUP_STEPS = 6  # the first compiles; the last four give the step time

# What a family's file provides (README.md, "A family"); it may also state
# ``LIMITS`` and ``FAR_OFF`` (see `limits_of`).
FAMILY_API = ("sizes", "build", "per_token_loss", "required_flops_per_token",
              "kernel_work")

# The system's per-token losses against the float32 reference's, on one
# seeded sequence at the published widths (chipbench/reference.py
# `compare`). bf16 carries 8 bits of mantissa: computing the blocks in it
# moved each token's loss by 0.0065-0.0079 on average, 0.8-1.0 % of the
# losses' own spread, and their mean by 0.2e-4 to 3.8e-4 (v5e, both
# configurations, seven seeds, PR 25). Three to four times that passes; a
# type with 3 bits less (fp8) sits 8 times further out and fails, as does a
# wrong mask, scale or rotation, which moves the mean by > 1e-2.
REL_RMS_TOL = 0.04
MEAN_ABS_TOL = 0.03
BIAS_TOL = 1e-3
# ... which hold for every family that states no ``LIMITS`` of its own.
DEFAULT_LIMITS = {"rel_rms": REL_RMS_TOL, "mean_abs_diff": MEAN_ABS_TOL,
                  "bias": BIAS_TOL}
# Of what `reference.compare` reports, the numbers that every token moves
# or that half of them do: a ``LIMITS`` holds its family to one at least.
# `far_off_share` alone would pass a fault that lifts no token past the
# family's threshold, `bias` alone one whose errors cancel.
WHOLE_SEQUENCE = ("rel_rms", "mean_abs_diff", "median_abs_diff")


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """The Python file at ``path``, imported under a name of its own (so a
    file a later PR adds is found without being a module that anything
    here imports)."""
    name = "chipbench_file_" + re.sub(r"\W", "_", str(path))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        try:
            spec.loader.exec_module(module)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def load_attr(path: pathlib.Path, attr: str):
    return getattr(load_module(path), attr)


def load_family(root: pathlib.Path, config: dict, config_name: str):
    """The module ``chipbench/families/<family>.py`` that the
    configuration's ``family`` key names: the program's model at the
    configuration's sizes, its plain reference and its counts."""
    family = config.get("family")
    if not family:
        raise KeyError(
            f'configuration {config_name!r} has no "family" key: it names '
            "the file chipbench/families/<family>.py that builds and counts "
            "the model, and there is no default")
    path = root / "chipbench" / "families" / f"{family}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f'configuration {config_name!r} says "family": {family!r}, and '
            f"{path} is not there")
    module = load_module(path)
    missing = [f for f in FAMILY_API if not callable(getattr(module, f, None))]
    if missing:
        raise AttributeError(f"family {family!r} ({path}) lacks {missing}")
    return module


def limits_of(family) -> dict:
    """{report name: limit} that `reference.compare`'s report of a cell of
    ``family`` is held to: `DEFAULT_LIMITS` where the family's file states
    no ``LIMITS``. Where it states one (an arithmetic the three constants
    were not measured on, such as a routed model's, sets its own from its
    own seeds and control, each with its measurement written beside it),
    that, with `BIAS_TOL` on ``bias`` unless it states another: the family
    file comes from the PR whose `correct` it decides, so what it may not
    do is refused here by name, before anything runs (`load_cell` asks): a
    key that `compare` does not report; a ``LIMITS`` with none of
    `WHOLE_SEQUENCE`; ``far_off_share`` with no ``FAR_OFF`` (the
    difference, in the loss's units, beyond which a token counts as far
    off)."""
    limits = getattr(family, "LIMITS", None)
    if limits is None:
        return dict(DEFAULT_LIMITS)
    unknown = sorted(set(limits) - set(reference.REPORTED))
    if unknown:
        fault = (f"names {unknown}, which chipbench/reference.py `compare` "
                 f"does not report; it reports {list(reference.REPORTED)}")
    elif not set(limits) & set(WHOLE_SEQUENCE):
        fault = (f"names {sorted(limits)} and none of {list(WHOLE_SEQUENCE)}"
                 ", one of which a lower-precision control has to fail")
    elif "far_off_share" in limits and not hasattr(family, "FAR_OFF"):
        fault = ("names 'far_off_share', and the file states no FAR_OFF "
                 "(how far off a token's loss is far off)")
    else:
        return {"bias": BIAS_TOL} | {
            name: float(limit) for name, limit in limits.items()}
    raise ValueError(f"{family.__file__}: LIMITS {fault}")


def named(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def load_cell(root: pathlib.Path, name: str) -> dict:
    """Everything a cell is made of, found by the names in BENCHMARK.json."""
    bench = load_json(root / "BENCHMARK.json")
    entry = named(bench["workloads"], name, "workload")
    here = root / "chipbench"
    workload = load_json(here / "workloads" / f"{name}.json")
    config = load_json(
        root / named(bench["configs"], entry["config"], "config")["file"])
    traffic = load_json(here / "traffic" / f"{entry['traffic']}.json")
    for key in ("config", "traffic", "chips"):
        if workload[key] != entry[key]:
            raise ValueError(
                f"{name}: workload file says {key}={workload[key]!r}, "
                f"BENCHMARK.json says {entry[key]!r}")

    def reported(metric):
        return name in metric.get("workloads", [name])

    family = load_family(root, config, entry["config"])
    return {
        "name": name, "workload": workload, "config": config,
        "family": family, "limits": limits_of(family),
        "traffic": traffic, "chips": entry["chips"],
        "end_to_end": [m for m in bench["end_to_end"] if reported(m)],
        "per_layer": [m for m in bench["per_layer"] if reported(m)],
    }


def build_trainer(cell: dict, devices, seed: int):
    """The system under test: the family's model at the configuration's
    sizes under the repository's Trainer, on a mesh over ``devices``."""
    spec = cell["workload"]["trainer"]
    mesh = hvt.build_mesh(
        hvt.MeshSpec(**cell["workload"]["mesh"]), devices=devices)
    model = cell["family"].build(cell["config"], spec, mesh)
    optimizer = getattr(optax, spec["optimizer"])(spec["learning_rate"])
    return hvt.Trainer(
        model,
        hvt.DistributedOptimizer(
            optimizer, backward_passes_per_step=spec["accumulation"]),
        loss="module", mesh=mesh, seed=seed, shard_update=spec["zero1"],
    )


def init_state(trainer, seq_len: int):
    """Parameters and optimizer state made on the device from the trainer's
    seed in ONE jitted program (which the compile cache keeps), replicated
    over the mesh, and handed to the trainer: `Trainer.build` would make
    them op by op, uncached. The initialised variables are treated as
    `Trainer.build` treats them: parameters kept; what a layer sows anew
    in every step dropped (``losses``, and ``metrics`` after their names
    are noted for the step's accumulator); a collection that carries state
    from step to step refused, because this init does not carry it."""
    tokens = jnp.zeros((trainer.dp_size, seq_len), jnp.int32)
    sown_metrics = set()  # filled while `init` is traced, by name only

    def init(key):
        init_rng, dropout_rng, state_rng = jax.random.split(key, 3)
        variables = trainer.module.init(
            {"params": init_rng, "dropout": dropout_rng}, tokens,
            train=False, labels=tokens)
        carried = sorted(set(variables) - {"params", "losses", "metrics"})
        if carried:
            raise ValueError(
                f"the model carries {carried} from step to step: this init "
                "knows parameters and sown losses and metrics only")
        for path, _ in jax.tree_util.tree_flatten_with_path(
                variables.get("metrics", {}))[0]:
            names = [p.key for p in path
                     if isinstance(p, jax.tree_util.DictKey)]
            sown_metrics.update(names[-1:])
        params = variables["params"]
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            opt_state=trainer.tx.init(params), rng=state_rng,
            model_state=None)

    trainer.state = jax.jit(
        init, out_shardings=sharding_lib.replicated(trainer.mesh)
    )(jax.random.PRNGKey(trainer.seed))
    trainer._metric_names = tuple(sorted(sown_metrics))
    return jax.block_until_ready(trainer.state)


def reference_check(trainer, cell, x, y, row: int) -> dict:
    """The system's forward pass against its family's reference on sequence
    ``row`` (the system needs a row per chip, the reference takes one)."""
    rows = [(row + i) % len(x) for i in range(trainer.dp_size)]

    def model_loss(params, xb, yb):
        loss, _correct = trainer.module.apply(
            {"params": params}, xb, train=False, labels=yb)
        return loss[0]

    got = jax.jit(model_loss)(trainer.state.params, x[rows], y[rows])
    want = jax.jit(functools.partial(
        cell["family"].per_token_loss, config=cell["config"],
    ))(trainer.state.params, x[row], y[row])
    report = reference.compare(
        got, want, far_off=getattr(cell["family"], "FAR_OFF", None))
    report["limits"] = cell["limits"]
    report["ok"] = all(
        report[name] <= limit for name, limit in cell["limits"].items())
    return report


def replicas_agree(trainer) -> bool:
    """Whether every chip holds the same parameters, bit for bit as far as
    a 32-bit sum of each leaf's bits can tell (computed where the
    parameters are: fetching four copies of them would take longer than
    the window)."""
    mesh = trainer.mesh
    if mesh.size == 1:
        return True
    axes = tuple(mesh.axis_names)

    def digest(params):
        sums = jnp.stack([
            jnp.sum(jax.lax.bitcast_convert_type(leaf, jnp.uint32))
            for leaf in jax.tree.leaves(params)])
        return jnp.all(jax.lax.pmax(sums, axes) == jax.lax.pmin(sums, axes))

    same = jax.jit(jax.shard_map(
        digest, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
    ))(trainer.state.params)
    return bool(same)


def lowered_step(trainer, x, y, batch: int):
    """The train step as `Trainer.fit` calls it, lowered for the trainer's
    state and ``batch`` rows a chip."""
    n = batch * trainer.dp_size
    return trainer._train_step_donated.lower(
        trainer.state, trainer._shard((x[:n], y[:n])),
        jnp.asarray(1.0, jnp.float32),
        sharding_lib.replicate(trainer.zero_metrics(), trainer.mesh))


def step_temp_bytes(trainer, x, y, batch: int) -> int:
    """Temporaries of the compiled train step, which this runtime's
    ``peak_bytes_in_use`` leaves out."""
    compiled = lowered_step(trainer, x, y, batch).compile()
    return int(compiled.memory_analysis().temp_size_in_bytes)


class CompileWatch:
    """Counts what JAX compiles or fetches from its compile cache while
    ``counting`` is set. (JAX keeps its listeners for the life of the
    process, so one watch serves a process.)"""
    def __init__(self):
        self.counting = False
        self.seen: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **_):
        if self.counting and ("compile" in event or "compilation" in event):
            self.seen.append(event)


class Warmup(hvt.callbacks.Callback):
    """Waits for the first step (which compiles), the second and the
    last, and stamps the clock at each: the steps between the last two
    stamps ran as the window's will, the host ahead of the device."""
    def __init__(self, n_steps):
        self.n_steps, self.stamps = n_steps, []

    def on_batch_end(self, batch, logs=None):
        if batch in (0, 1, self.n_steps - 1):
            jax.block_until_ready(logs["loss"])
            self.stamps.append(time.perf_counter())

class Window(hvt.callbacks.Callback):
    """Opens the window when the device is idle and the epoch is about
    to start, stamps the host clock at every step and keeps the loss
    on the device, and closes the window when the last step's loss is
    there. With ``trace_dir``, profiles the steps ``trace_steps`` (a
    ``(first, last)`` pair) in the middle."""
    def __init__(self, n_steps, watch, trace_dir=None, trace_steps=None):
        self.n_steps, self.watch = n_steps, watch
        self.trace_dir, self.trace_steps = trace_dir, trace_steps
        self.losses, self.stamps = [], []
        self.t_open = self.t_close = None

    def on_epoch_begin(self, epoch, logs=None):
        jax.block_until_ready(self.trainer.state)
        self.watch.counting = True
        self.t_open = time.perf_counter()

    def on_batch_end(self, batch, logs=None):
        self.losses.append(logs["loss"])
        self.stamps.append(time.perf_counter())
        step = len(self.losses) - 1
        if self.trace_dir and step == self.trace_steps[0]:
            # The host is up to some tens of steps ahead: let the
            # device catch up, so that the trace holds the steps meant.
            jax.block_until_ready(logs["loss"])
            jax.profiler.start_trace(self.trace_dir)
        if self.trace_dir and step == self.trace_steps[1]:
            jax.block_until_ready(logs["loss"])
            jax.profiler.stop_trace()
        if step == self.n_steps - 1:
            jax.block_until_ready(logs["loss"])
            self.t_close = time.perf_counter()
            self.watch.counting = False


def traced_context(cell, sizes, trace_dir, device_kind, temp_bytes, say):
    """What the per-layer readers get: the trace's rows, the chips' steady
    stretches, the configuration with its family, and the family's counts
    at the cell's shapes. ``tokens_per_s`` is the device's own rate over the
    stretch (steps start to start), because the traced window also holds
    the profiler's start and stop."""
    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    rows = reduce.rows_from_xplane(path)
    chips = reduce.chips_from_rows(rows)
    workload, traffic = cell["workload"], cell["traffic"]
    data = workload["mesh"].get("data", 1)
    config, family = cell["config"], cell["family"]
    seq_len = traffic["seq_len"]
    per_chip_batch = traffic["global_batch"] // data
    ctx = {
        "rows": rows, "chips": chips, "model": sizes,
        "config": config, "family": family,
        "required_flops_per_token": family.required_flops_per_token(
            config, seq_len),
        "kernel_work": family.kernel_work(config, seq_len, per_chip_batch),
        "seq_len": seq_len, "per_chip_batch": per_chip_batch,
        "n_chips": cell["chips"], "device_kind": device_kind,
        "step_temp_bytes": temp_bytes, "say": say, "tokens_per_s": None,
    }
    if chips:
        first = chips[0]
        starts = [s for s, _ in first.steps]
        ctx["tokens_per_s"] = (
            traffic["global_batch"] * traffic["seq_len"]
            * (len(starts) - 1) / ((starts[-1] - starts[0]) / 1e9))
    return ctx


def main(argv=None, *, root: pathlib.Path = ROOT, require_tpu=True) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    cell = load_cell(root, args.workload)

    cache_dir = hvt.runtime.use_compilation_cache()
    # Keep every program, however quick its compile, so that a second run
    # finds all of them.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    hvt.init()
    found = jax.devices()
    if require_tpu and found[0].platform != "tpu":
        print(f"chipbench needs a TPU; jax found {found[0].platform}: no "
              "number from another device goes under a device metric's name",
              file=sys.stderr)
        return 1
    if len(found) < cell["chips"]:
        print(f"{cell['name']} needs {cell['chips']} chips; jax found "
              f"{len(found)}", file=sys.stderr)
        return 1
    devices = found[:cell["chips"]]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on = "{platform}:{kind} x{count}".format(**device)

    def say(**fields):
        print(json.dumps({"on": on, **fields}), flush=True)

    def since(t):
        return time.perf_counter() - t

    say(workload=cell["name"], seed=args.seed, seconds=args.seconds,
        trace=args.trace, compilation_cache_dir=cache_dir)
    watch = CompileWatch()
    sizes, traffic = cell["family"].sizes(cell["config"]), cell["traffic"]
    seq_len, global_batch = traffic["seq_len"], traffic["global_batch"]
    if sizes["max_positions"] and seq_len > sizes["max_positions"]:
        raise ValueError(f"seq_len {seq_len} is beyond the configuration's "
                         f"{sizes['max_positions']} positions")

    # --- set-up: build, reference, warm-up --------------------------------
    t = time.perf_counter()
    trainer = build_trainer(cell, devices, args.seed)
    per_chip_batch = global_batch // trainer.dp_size
    state = init_state(trainer, seq_len)
    n_params = sum(p.size for p in jax.tree.leaves(state.params))
    say(phase="build", build_s=since(t), n_params=n_params)

    t = time.perf_counter()
    make = load_attr(
        root / "chipbench" / "traffic" / f"{traffic['kind']}.py", "make")
    x, y = make(args.seed, traffic, sizes["vocab_size"])
    agreement = reference_check(
        trainer, cell, x, y, row=args.seed % len(x))
    say(phase="reference", reference_s=since(t), **agreement)

    t = time.perf_counter()
    fit = functools.partial(
        trainer.fit, x=x, y=y, batch_size=per_chip_batch, cache=None,
        verbose=0)
    warm = Warmup(WARMUP_STEPS)
    fit(steps_per_epoch=WARMUP_STEPS, epochs=1, callbacks=[warm])
    step_s = (warm.stamps[2] - warm.stamps[1]) / (WARMUP_STEPS - 2)
    temp_bytes = step_temp_bytes(trainer, x, y, per_chip_batch)
    say(phase="warmup", warmup_s=since(t), first_step_s=warm.stamps[0] - t,
        warm_step_s=step_s, step_temp_bytes=temp_bytes,
        input_engine=trainer.stream_cursor(0, 0)["position"]["engine"])

    # --- the window: one epoch of as many steps as fill --seconds ---------
    n_steps = max(4, math.ceil(args.seconds / step_s))
    trace_dir = trace_steps = None
    if args.trace:
        trace_dir = str(root / ".chipbench_out" / cell["name"] / "profile")
        shutil.rmtree(trace_dir, ignore_errors=True)
        first = max(1, n_steps // 2 - 6)
        trace_steps = (first, min(n_steps - 2, first + 12))
    window = Window(n_steps, watch, trace_dir, trace_steps)
    fit(steps_per_epoch=n_steps, epochs=2, initial_epoch=1,
        callbacks=[window])
    window_s = window.t_close - window.t_open
    setup_s = window.t_open - _T0

    # --- after the window -------------------------------------------------
    losses = [float(v) for v in jax.device_get(window.losses)]
    failed = sum(not math.isfinite(v) for v in losses)
    intervals = [(b - a) * 1e3
                 for a, b in zip(window.stamps, window.stamps[1:])]
    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats) + temp_bytes
    median_ms = statistics.median(intervals)
    longest = max(range(len(intervals)), key=intervals.__getitem__)
    say(phase="window", steps=n_steps, window_s=window_s,
        step_ms_samples=len(intervals), step_ms_median=median_ms,
        # stamps made while the host ran ahead of the device
        step_ms_under_half_median=sum(i < median_ms / 2 for i in intervals),
        # where a window that reads long lost its time: before the first
        # stamp, in one interval, or draining the steps still in flight
        first_stamp_ms=(window.stamps[0] - window.t_open) * 1e3,
        step_ms_max=intervals[longest], step_ms_max_at=longest + 1,
        drain_ms=(window.t_close - window.stamps[-1]) * 1e3,
        peak_bytes_in_use=[s.get("peak_bytes_in_use") for s in stats],
        bytes_limit=[s.get("bytes_limit") for s in stats],
        compiles_in_window=watch.seen)
    say(losses=losses)
    gates = {
        "reference_agrees": agreement["ok"],
        "ran_every_step": len(losses) == n_steps,
        "every_loss_finite": failed == 0,
        "no_compile_in_window": not watch.seen,
        "replicas_agree": replicas_agree(trainer),
    }
    say(gates=gates)
    # Each number the gates compared, beside its limit.
    compared = {
        **{name: [agreement[name], limit]
           for name, limit in agreement["limits"].items()},
        "steps_not_run": [n_steps - len(losses), 0],
        "losses_not_finite": [failed, 0],
        "compiles_in_window": [len(watch.seen), 0],
        "replicas_differ": [int(not gates["replicas_agree"]), 0],
    }

    run = {"n_steps": n_steps, "tokens_per_step": global_batch * seq_len,
           "window_s": window_s, "intervals_ms": intervals,
           "peak_bytes": peak_bytes, "setup_s": setup_s}
    result = {"correct": all(gates.values()), "attempted": n_steps,
              "failed": failed}
    device["memory_peak_bytes"] = peak_bytes
    if args.trace:
        ctx = traced_context(cell, sizes, trace_dir, device["kind"],
                             temp_bytes, say)
        chips = ctx["chips"]
        say(phase="trace", rows=len(ctx["rows"]),
            traced_steps=[len(c.steps) for c in chips],
            step_program=[c.module for c in chips])
        metrics = {}
        for metric in cell["per_layer"]:
            spec = load_json(root / "chipbench" / "layer_metrics"
                             / f"{metric['name']}.json")
            path, _, attr = spec["reader"].partition(":")
            value = load_attr(root / "chipbench" / path, attr)(ctx)
            if value is not None:
                metrics[metric["name"]] = {
                    "value": value, "unit": metric["unit"]}
        if chips:
            worst = max(chips, key=lambda c: 1 - c.busy_ns() / c.stretch_ns)
            device["busy_s"] = statistics.fmean(
                c.busy_ns() for c in chips) / 1e9
            device["window_s"] = statistics.fmean(
                c.stretch_ns for c in chips) / 1e9
            result["breakdown"] = {
                "device_ops": reduce.device_op_families(worst),
                "idle_gaps": reduce.idle_gaps(worst, ctx["rows"]),
            }
    else:
        here = root / "chipbench" / "end_to_end.py"
        metrics = {
            m["name"]: {"value": load_attr(here, m["name"])(run),
                        "unit": m["unit"]}
            for m in cell["end_to_end"]}
    result["metrics"], result["device"] = metrics, device
    result["compared"] = compared
    print(json.dumps(result), flush=True)
    for name, (value, limit) in compared.items():
        print(f"compared {name} {value!r} limit {limit!r}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
