"""Profiling + FLOPs/MFU accounting — the Horovod-Timeline / NCCL_DEBUG role,
TPU-native (§5.1).

`jax.profiler` traces capture XLA op timing *and* ICI collective phases —
strictly more than Horovod's Chrome-trace Timeline — viewable in
TensorBoard/perfetto. Primary-process-gated like every writer in the
framework. `HVT_PROFILE=<dir>` turns tracing on in `Trainer.fit` without code
changes (the `HOROVOD_TIMELINE=<file>` env-var contract, SURVEY.md §2.3
Timeline row). What the program says about itself
lands in the same trace: `span` puts host events named ``hvt.<name>`` on
the profiler's clock, and the compiled step carries the scopes
``hvt.head_ce`` / ``hvt.optimizer`` and the kernel names ``hvt_flash_*``
(README "Observability" has the table).

FLOPs come from XLA's own cost model on the *compiled* step
(`Compiled.cost_analysis()`), so the count covers exactly what runs —
forward, backward, optimizer, collectives — for any model, with no
per-architecture analytic bookkeeping to drift out of date. MFU is that
count against the chip's peak; "match or beat" needs this denominator."""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import threading
import time
import warnings
import weakref

import jax

from horovod_tpu import runtime
from horovod_tpu.analysis import registry

# Peak dense-matmul throughput per chip, FLOP/s. bf16 peaks from the public
# TPU spec sheets; fp32 on TPU runs through the same MXU passes (bf16x3) so
# bf16 peak is the standard MFU denominator. Keyed by substrings of
# `device.device_kind`.
_PEAK_FLOPS = {
    "tpu v7": 4614e12,   # Ironwood
    "tpu v6 lite": 918e12,   # Trillium / v6e
    "tpu v5p": 459e12,
    "tpu v5 lite": 197e12,   # v5e
    "tpu v5": 459e12,        # plain "TPU v5" kinds are v5p pods
    "tpu v4 lite": 138e12,
    "tpu v4": 275e12,
    "tpu v3": 123e12,
    "tpu v2": 46e12,
}


def device_peak_flops(device=None) -> float | None:
    """Peak FLOP/s of one chip, or None when unknown (e.g. CPU).

    ``HVT_PEAK_FLOPS`` overrides the table — the explicit per-chip peak
    for device kinds the table doesn't know (CPU CI topologies, new TPU
    generations), so MFU can be a real trend number everywhere. An
    unparseable override raises ``ValueError``."""
    override = registry.get_float("HVT_PEAK_FLOPS")
    if override:
        return float(override)
    device = device or jax.devices()[0]
    kind = device.device_kind.lower()
    for key, peak in sorted(_PEAK_FLOPS.items(), key=lambda kv: -len(kv[0])):
        if key in kind:
            return peak
    return None


def compiled_flops(jitted_fn, *args, **kwargs) -> float | None:
    """Total FLOPs of one invocation, from XLA's cost model on the lowered
    + compiled computation. None when the backend doesn't report them."""
    try:
        return compiled_cost_flops(jitted_fn.lower(*args, **kwargs).compile())
    except Exception:
        return None


def compiled_cost_flops(compiled) -> float | None:
    """FLOPs from an already-`Compiled` computation's cost analysis."""
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):  # some backends wrap per-module
            cost = cost[0]
        flops = cost.get("flops")
        return float(flops) if flops and flops > 0 else None
    except Exception:
        return None


def resolve_peak_flops() -> tuple:
    """(per-chip peak FLOP/s, source) for the live trainer's MFU gauge.

    Resolution order: the explicit ``HVT_PEAK_FLOPS`` override, then the
    built-in peak table keyed by ``device_kind`` (`device_peak_flops`). An
    accelerator the table does not know RAISES: a utilization against a
    guessed peak is worse than none. Only the ``cpu`` platform (the CI
    topology, which has no published peak) goes on, to
    ``(None, "unknown")``: a CPU run without the override publishes no
    utilization at all."""
    if registry.get_raw("HVT_PEAK_FLOPS") is not None:
        return float(registry.get_float("HVT_PEAK_FLOPS")), "override"
    device = jax.devices()[0]
    peak = device_peak_flops(device)
    if peak:
        return peak, "table"
    if device.platform != "cpu":
        raise ValueError(
            f"no published peak FLOP/s for device kind "
            f"{device.device_kind!r}: add it to trace._PEAK_FLOPS with its "
            "source, or set HVT_PEAK_FLOPS"
        )
    return None, "unknown"


def mfu(flops_per_step: float | None, step_time_s: float, n_chips: int = 1,
        device=None, peak: float | None = None) -> float | None:
    """Model FLOPs utilization: achieved FLOP/s ÷ fleet peak FLOP/s.
    ``peak`` is a per-chip peak already resolved by `resolve_peak_flops`;
    without it the table (or override) is consulted for ``device``."""
    peak = peak or device_peak_flops(device)
    if not peak or not flops_per_step or step_time_s <= 0:
        return None
    return flops_per_step / step_time_s / (peak * n_chips)


def profile_dir() -> str | None:
    """The `HVT_PROFILE` target directory, or None when profiling is off."""
    return registry.get_str("HVT_PROFILE")


@contextlib.contextmanager
def maybe_trace(log_dir: str | None):
    """`trace(...)` when a directory is given, no-op otherwise — callers can
    wrap hot loops unconditionally with `maybe_trace(profile_dir())`. A
    session the program closes itself leaves the key to its device events
    beside the dump (`write_step_reductions`)."""
    if log_dir:
        with trace(log_dir):
            yield
        if runtime.is_primary():
            write_step_reductions(log_dir)
    else:
        yield


@contextlib.contextmanager
def trace(log_dir: str, primary_only: bool = True):
    """``with trace('/tmp/trace'): step(...)`` — emits a profiler dump."""
    active = runtime.is_primary() or not primary_only
    if active:
        jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        if active:
            jax.profiler.stop_trace()


# --- the step program's cross-chip sums --------------------------------------
#
# On several chips the compiled step's gradient sums are `fusion` events in
# a trace (`async-collective-start.N`, `fusion.N`, `async-collective-done.N`)
# whose own HLO lines hold no collective and no scope: which sum, of which
# gradient, and what a host fusion computes beside it are in the compiled
# module's called computations, which only the program has. So the program
# says it: `Trainer.step_reductions` builds the table
# (`analysis.hlo_audit.reduction_schedule`) from the text of the step it
# ran, and a reader joins it to the device's events by instruction name.

STEP_REDUCTIONS_FILE = "hvt_step_reductions.json"
# The trainer whose fit loop last remembered a step program; weak, so that
# asking for a table keeps no trainer (and no device state) alive.
_newest_fit = lambda: None  # noqa: E731 — a dead reference until a fit


def note_step_program(trainer) -> None:
    """`Trainer.remember_step_program`'s note: this process's newest fit."""
    global _newest_fit
    _newest_fit = weakref.ref(trainer)


def step_reductions() -> list[dict] | None:
    """The table of the cross-chip sums of the step program of this
    process's newest streamed fit, a row a collective: ``kind``, ``dtype``,
    ``shape``, ``nbytes``, ``asynchronous``, ``channel``, ``scope`` (whose
    gradient: the scope path of the collective's own ``op_name``), and the
    compiled step's instructions that carry it, which are what a
    profiler's device events are named by: ``start``, ``done`` and
    ``hosts`` (``name``, ``host_scope``: the compute fusions that carry
    its steps, and what they compute). ``[]`` on one chip, None where no
    fit has run (or its trainer is gone). Lowers and compiles the
    remembered program on first demand (`Trainer.step_reductions`)."""
    trainer = _newest_fit()
    rows = trainer.step_reductions() if trainer is not None else None
    if rows is None:
        return None
    return [dataclasses.asdict(row) for row in rows]


def write_step_reductions(log_dir: str) -> str | None:
    """Write `step_reductions()` as ``hvt_step_reductions.json`` into
    ``log_dir``, beside a profiler dump; None where there is no table. A
    failure costs the file, never the training run, and is said."""
    try:
        rows = step_reductions()
        if rows is None:
            return None
        from horovod_tpu import checkpoint

        os.makedirs(log_dir, exist_ok=True)
        path = os.path.join(log_dir, STEP_REDUCTIONS_FILE)
        checkpoint._atomic_write(path, json.dumps(rows).encode())
        return path
    except Exception as e:
        warnings.warn(f"{STEP_REDUCTIONS_FILE} not written: {e!r}")
        return None


# --- spans: one API, two sinks ----------------------------------------------
#
# `span` wraps the framework's host-side boundaries — input_wait, step,
# callbacks, the prefetch thread's input.assemble / input.place /
# input.queue_full, reduction, commit, rescale, checkpoint-save, the
# serving tier's request tree. Every span is a `jax.profiler`
# TraceAnnotation named "hvt.<name>" (so it lands in any open profiler
# session beside the device's events, on their clock), and, with
# HVT_TRACE_DIR set, also a nestable JSONL record in one rank-tagged file
# per process, so a fleet's spans can be merged by (rank, ts) into a
# timeline without a collector. Each record:
#
#   {"name", "ts" (epoch seconds, span START), "dur_s", "rank", "pid",
#    "id", "parent" (enclosing span id or null), "depth", ...attrs}
#
# The file sink is off (one registry read) unless HVT_TRACE_DIR is set.
# Writes are per-record appends with a flush — span cadence is the
# optimizer step at its finest, never per-microbatch. Span emission must
# never take training down: write failures are swallowed after the
# first (the writer disables itself) — but never SILENTLY: every span a
# dead writer loses is counted and exported as
# `hvt_trace_spans_dropped_total` through the obs registry, so a torn
# trace dir reads as a climbing counter on /metrics instead of a
# mysteriously empty timeline. Records carry the writing HOST so
# `hvt-trace` (obs/timeline.py) knows which ranks share a clock.

# What `span` puts before a span's name in the profiler's trace.
PROFILER_PREFIX = "hvt."


def span_dir() -> str | None:
    """The ``HVT_TRACE_DIR`` target, or None when spans are off."""
    return registry.get_str("HVT_TRACE_DIR")


def _dropped_spans_collector(reg) -> None:
    """Mirror the span writer's drop count at scrape time (the
    `obs.register_collector` idiom — a NAMED module-level function so
    re-registration dedupes by identity). Reads the module attribute, so
    tests that swap `_span_writer` stay covered."""
    reg.counter_set("hvt_trace_spans_dropped_total", _span_writer.drops)


class _SpanWriter:
    """This process's span file (lazy; thread-safe; fail-once-silent —
    but drop-counted: see the section comment above)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._fh = None
        self._dead = False
        self._seq = 0
        self._tls = threading.local()
        self.drops = 0  # spans lost to a dead/torn writer

    def _stack(self) -> list:
        if not hasattr(self._tls, "stack"):
            self._tls.stack = []
        return self._tls.stack

    @staticmethod
    def _register_drop_mirror() -> None:
        # Idempotent (collector registration dedupes by identity); NOT
        # on the healthy write path — asserted once at writer open and
        # again on every drop, which also re-covers an obs.reset()
        # between fits (any post-reset drop re-registers).
        from horovod_tpu import obs

        obs.register_collector(_dropped_spans_collector)

    def write(self, record: dict) -> None:
        if self._dead:
            with self._lock:
                self.drops += 1
            self._register_drop_mirror()
            return
        try:
            with self._lock:
                if self._fh is None:
                    d = span_dir()
                    os.makedirs(d, exist_ok=True)
                    rank = runtime.process_rank()
                    self._fh = open(
                        os.path.join(
                            d, f"spans-rank{rank}-pid{os.getpid()}.jsonl"
                        ),
                        "a",
                    )
                    register = True
                else:
                    register = False
                self._fh.write(json.dumps(record) + "\n")
                self._fh.flush()
            if register:
                self._register_drop_mirror()
        except OSError:
            with self._lock:
                self._dead = True  # observability must never kill training
                self.drops += 1
            self._register_drop_mirror()

    def next_id(self) -> int:
        with self._lock:
            self._seq += 1
            return self._seq


_span_writer = _SpanWriter()
_HOST = None


def _host() -> str:
    """The span-stamping hostname (cached): ranks sharing it share a
    clock, which is what lets `hvt-trace` skip cross-host clock
    alignment for them (obs/timeline.py)."""
    global _HOST
    if _HOST is None:
        import socket

        try:
            _HOST = socket.gethostname() or "unknown"
        except OSError:
            _HOST = "unknown"
    return _HOST


def emit_span(name: str, ts: float, dur_s: float, **attrs) -> None:
    """Write one span record with CALLER-supplied timings — an interval
    measured somewhere the ``with`` form can't sit (another thread's
    queue wait, a retroactive split of a blocking call). Parent/depth
    come from the calling thread's open-span stack, exactly like
    `span`; no-op when ``HVT_TRACE_DIR`` is unset."""
    if not span_dir():
        return
    stack = _span_writer._stack()
    # Core fields LAST so a caller attr can never clobber the span
    # schema (an `id=` attr silently breaking parent linkage was a real
    # bug — timeline merge keys on these).
    _span_writer.write({
        **attrs,
        "name": name,
        "ts": ts,
        "dur_s": dur_s,
        "rank": runtime.process_rank(),
        "pid": os.getpid(),
        "host": _host(),
        "id": _span_writer.next_id(),
        "parent": stack[-1] if stack else None,
        "depth": len(stack),
    })


@contextlib.contextmanager
def span(name: str, **attrs):
    """``with trace.span('commit', epoch=3): ...`` — the framework's one
    span API, with two sinks on two clocks:

    * always, a `jax.profiler.TraceAnnotation` named ``"hvt." + name``
      (attrs as its stats): a host event on the calling thread's line of
      whatever profiler session is open (`trace()`, ``HVT_PROFILE``,
      ``POST /profile``, the benchmark's traced run), on the device
      events' own clock. With no session open it costs a flag test;
    * when ``HVT_TRACE_DIR`` is set, one JSONL span record on exit,
      nesting tracked per thread, on the wall clock (what `hvt-trace`
      merges across ranks).

    Host-side only: never enter one inside a traced body (HVT009)."""
    with jax.profiler.TraceAnnotation(PROFILER_PREFIX + name, **attrs):
        if not span_dir():
            yield
            return
        stack = _span_writer._stack()
        sid = _span_writer.next_id()
        parent = stack[-1] if stack else None
        stack.append(sid)
        t0 = time.time()
        p0 = time.perf_counter()
        try:
            yield
        finally:
            stack.pop()
            # Core fields LAST — see emit_span.
            _span_writer.write({
                **attrs,
                "name": name,
                "ts": t0,
                "dur_s": time.perf_counter() - p0,
                "rank": runtime.process_rank(),
                "pid": os.getpid(),
                "host": _host(),
                "id": sid,
                "parent": parent,
                "depth": len(stack),
            })
