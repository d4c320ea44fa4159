"""From a profiler trace to numbers.

`rows_from_xplane` is the thin adapter: it reads an ``.xplane.pb`` with
`jax.profiler.ProfileData` into rows ``(plane, line, name, start_ns,
duration_ns)``. Everything else is a pure function of rows, so it can be
checked on a small recorded cut (tests/chipbench/trace_cut.json).

What a v5e trace looks like (seen by hand in this repository's first traced
run): one plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules``
(one event per executed program, named ``jit_<fn>(<hash>)``), ``XLA Ops``
(one event per HLO instruction, named by its whole HLO line) and ``Async
XLA Ops``; the host's threads are lines of the plane ``/host:CPU`` on the
same clock. A ``while`` or ``conditional`` event on ``XLA Ops`` *contains*
the events of its body, so durations are only ever summed or united over
**leaf** events: those that contain no other event of their line.

The per-layer metrics' readers are at the end: each takes the run's
context (a dict, see `chipbench.run.traced_context`) and returns a number,
or None when it finds nothing to read.
"""

from __future__ import annotations

import dataclasses
import re
import statistics

from chipbench import flops

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
MODULES, OPS, ASYNC_OPS = "XLA Modules", "XLA Ops", "Async XLA Ops"
# Host events shorter than this say nothing about a gap (the runtime logs
# thousands of 10 ns "Wait for donation holds" per step).
MIN_HOST_EVENT_NS = 10_000
MIN_OP_NS = 10
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
# The flash kernels, by the name ops/flash_attention.py gives `pallas_call`,
# which the compiled HLO instruction takes (``%hvt_flash_fwd.3 = ...``). A
# Mosaic call of any other name is another kernel's, with a reader of its own.
FLASH_KERNELS = {"fwd": "hvt_flash_fwd", "dq": "hvt_flash_dq",
                 "dkv": "hvt_flash_dkv"}
# A collective is told by the opcode in its HLO line, not by the
# instruction's name: the all-reduce that a shard_map's psum makes is named
# ``%psum.N``.
COLLECTIVE = re.compile(
    r" (all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute)"
    r"(-start|-done)?\(")


# --- the adapter -----------------------------------------------------------

def rows_from_xplane(path: str) -> list[tuple]:
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            keep, floor = (MODULES, OPS, ASYNC_OPS), 0
        elif plane.name == HOST_PLANE:
            keep, floor = None, MIN_HOST_EVENT_NS
        else:
            continue
        for line in plane.lines:
            if keep is not None and line.name not in keep:
                continue
            for e in line.events:
                if e.duration_ns >= floor:
                    rows.append((plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return rows


# --- pure functions of rows ------------------------------------------------

def op_name(hlo_line: str) -> str:
    """``%multiply_add_fusion.12 = f32[...] fusion(...)`` ->
    ``multiply_add_fusion.12``."""
    return hlo_line.split(" ", 1)[0].lstrip("%")


def op_family(hlo_line: str) -> str:
    """The instruction's name without its number, with the fusion kind or
    the custom call's target where the line gives one: all 75
    ``multiply_add_fusion.N`` read as one family."""
    family = re.sub(r"[.\d]+$", "", op_name(hlo_line))
    kind = re.search(r"kind=(k\w+)", hlo_line)
    target = re.search(r'custom_call_target="(\w+)"', hlo_line)
    tag = kind.group(1) if kind else target.group(1) if target else None
    return (f"{family} ({tag})" if tag else family)[:80]


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def leaves(events: list[tuple]) -> list[tuple]:
    """Of ``(name, start, duration)`` events of one line, those that
    contain no other: a container starts no later than the next event and
    ends no earlier than it. Events of (next to) no length are markers the
    runtime puts at another op's start; they would make that op look like
    a container and add to no sum, so they go first."""
    events = sorted((e for e in events if e[2] >= MIN_OP_NS),
                    key=lambda e: (e[1], -e[2]))
    out = []
    for this, nxt in zip(events, events[1:] + [None]):
        if nxt is None or nxt[1] >= this[1] + this[2]:
            out.append(this)
    return out


def clip(intervals, t0, t1):
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if min(b, t1) > max(a, t0)]


@dataclasses.dataclass
class Chip:
    """One chip's steady stretch: its step programs and what ran inside."""

    plane: str
    module: str
    steps: list[tuple[float, float]]  # (start_ns, duration_ns), steady only
    ops: list[tuple]                  # leaf XLA Ops in the stretch
    async_ops: list[tuple]            # Async XLA Ops in the stretch

    @property
    def t0(self) -> float:
        return self.steps[0][0]

    @property
    def t1(self) -> float:
        return self.steps[-1][0] + self.steps[-1][1]

    @property
    def stretch_ns(self) -> float:
        return self.t1 - self.t0

    def busy_ns(self) -> float:
        return union_ns(clip(((s, s + d) for _, s, d in self.ops),
                             self.t0, self.t1))

    def gaps_ns(self) -> list[float]:
        """Idle time between the end of one step and the start of the next."""
        return [b[0] - (a[0] + a[1])
                for a, b in zip(self.steps, self.steps[1:])]


def chips_from_rows(rows) -> list[Chip]:
    """The steady stretch of every device plane: the program that took
    most of the plane's time is the step; its first and last events in
    the trace may be cut by the trace's edges and are dropped."""
    chips = []
    for plane in sorted({r[0] for r in rows if DEVICE_PLANE.match(r[0])}):
        mine = [r for r in rows if r[0] == plane]
        by_module: dict = {}
        for _, line, name, start, dur in mine:
            if line == MODULES:
                by_module.setdefault(name, []).append((start, dur))
        if not by_module:
            continue
        module = max(by_module, key=lambda m: sum(d for _, d in by_module[m]))
        steps = sorted(by_module[module])[1:-1]
        if len(steps) < 2:
            continue
        t0, t1 = steps[0][0], steps[-1][0] + steps[-1][1]

        def inside(line):
            return [(n, s, d) for _, l, n, s, d in mine
                    if l == line and s >= t0 and s < t1]

        chips.append(Chip(plane, module, steps, leaves(inside(OPS)),
                          sorted(inside(ASYNC_OPS), key=lambda e: e[1])))
    return chips


def flash_kernel_of(hlo_line: str):
    """Which flash kernel an event is, by its instruction's name: "fwd",
    "dq", "dkv", or None (no Mosaic call, or one of another name). The name
    is the kernel's own (``hvt_flash_fwd.3``) or, for a call that no scope
    of the caller wraps, the kernel's under the transformations' prefixes
    (``transpose_jvp_hvt_flash_dq__.1``); a kernel whose name only begins
    like one of the three (``hvt_flash_fwd_ring``) is another kernel."""
    if KERNEL_MARK not in hlo_line:
        return None
    name = re.sub(r"(\.\d+)+$", "", op_name(hlo_line)).rstrip("_")
    for key, kernel in FLASH_KERNELS.items():
        if name == kernel or name.endswith("_" + kernel):
            return key
    return None


def flash_kernel_ms_per_step(chip: Chip, which: str | None = None):
    """(milliseconds a step in the flash kernel ``which``, events a step);
    without ``which``, in the three flash kernels together."""
    hits = [d for n, _, d in chip.ops
            if flash_kernel_of(n) in ((which,) if which else FLASH_KERNELS)]
    return sum(hits) / 1e6 / len(chip.steps), len(hits) / len(chip.steps)


def collective_ms_per_step(chip: Chip):
    """(milliseconds per step in which a collective is in flight, the part
    of them in which no compute op runs). A collective is any event of
    ``XLA Ops`` or ``Async XLA Ops`` whose HLO line holds the opcode
    all-reduce, reduce-scatter, all-gather, all-to-all or collective-permute
    (``-start`` and ``-done`` halves included); the intervals are united,
    so that an async pair and its halves count once. Compute is every other
    leaf op."""
    def is_collective(name):
        return bool(COLLECTIVE.search(name))

    span = clip(((s, s + d) for n, s, d in chip.ops + chip.async_ops
                 if is_collective(n)), chip.t0, chip.t1)
    compute = clip(((s, s + d) for n, s, d in chip.ops
                    if not is_collective(n)), chip.t0, chip.t1)
    total = union_ns(span)
    # |A \ B| = |A ∪ B| - |B|
    exposed = union_ns(span + compute) - union_ns(compute)
    n = len(chip.steps)
    return total / 1e6 / n, exposed / 1e6 / n


def device_op_families(chip: Chip, top: int = 10) -> list[list]:
    """[[family, seconds per step]], the ``top`` that took most time."""
    total: dict = {}
    count: dict = {}
    for name, _, dur in chip.ops:
        fam = op_family(name)
        total[fam] = total.get(fam, 0.0) + dur
        count[fam] = count.get(fam, 0) + 1
    n = len(chip.steps)
    ranked = sorted(total, key=total.get, reverse=True)[:top]
    return [[f"{fam} x{round(count[fam] / n)}", total[fam] / 1e9 / n]
            for fam in ranked]


def idle_gaps(chip: Chip, rows, top: int = 10) -> list[list]:
    """[[what, seconds per step]]: the device's idle gaps of the stretch,
    summed by where they are. A gap between two steps is named by the
    shortest host event that covers its middle (what the host was doing),
    or "none"; one inside a step by the op family that followed it."""
    host = [(n, s, s + d) for p, _, n, s, d in rows if p == HOST_PLANE]
    ends = [s + d for s, d in chip.steps]
    total: dict = {}
    reach = chip.t0
    for name, start, dur in chip.ops:
        if start > reach:
            if any(reach <= e <= start for e in ends):
                mid = (reach + start) / 2
                cover = [(e - s, n) for n, s, e in host if s <= mid <= e]
                what = "between steps, host: " + (
                    min(cover)[1] if cover else "none")
            else:
                what = "in step, before " + op_family(name)
            total[what[:80]] = total.get(what[:80], 0.0) + start - reach
        reach = max(reach, start + dur)
    n = len(chip.steps)
    ranked = sorted(total, key=total.get, reverse=True)[:top]
    return [[what, total[what] / 1e9 / n] for what in ranked]


# --- readers of the per-layer metrics --------------------------------------
# ctx: {"chips": [Chip], "rows", "model", "config", "family",
#       "required_flops_per_token", "kernel_work", "seq_len",
#       "per_chip_batch", "n_chips", "device_kind", "tokens_per_s",
#       "step_temp_bytes", "say"}; the two counts are the family's
#       (families/<family>.py) at the cell's shapes.

def _worst(ctx, fn):
    values = [fn(chip) for chip in ctx["chips"]]
    return max(values) if values else None


def step_gap_ms(ctx):
    return _worst(ctx, lambda c: statistics.median(c.gaps_ns()) / 1e6)


def step_gap_ms_max(ctx):
    return _worst(ctx, lambda c: max(c.gaps_ns()) / 1e6)


def step_device_ms(ctx):
    return _worst(
        ctx, lambda c: statistics.median(d for _, d in c.steps) / 1e6)


def step_temp_gb(ctx):
    return ctx["step_temp_bytes"] / 1e9


def mfu(ctx):
    if ctx["tokens_per_s"] is None:
        return None
    required = ctx["required_flops_per_token"]
    peak = flops.peaks(ctx["device_kind"])["flops_per_s"]
    return 100.0 * required * ctx["tokens_per_s"] / (ctx["n_chips"] * peak)


def flash_ms_per_step(ctx):
    """The three flash kernels together, told by their names. None unless
    every step holds as many of them as the family counts flash calls (for
    the dense LM three a layer: forward, dQ, dK/dV): a count that is off
    means the events are not what this reader takes them for. A Mosaic call
    of another name is not counted and does not void the reading."""
    if "flash" not in ctx["kernel_work"]:
        return None
    calls = ctx["kernel_work"]["flash"][2]

    def one(chip):
        ms, count = flash_kernel_ms_per_step(chip)
        return ms if count == calls else None

    values = [one(chip) for chip in ctx["chips"]]
    if not values or None in values:
        return None
    return max(values)


def flash_roofline(ctx):
    ms = flash_ms_per_step(ctx)
    if ms is None:
        return None
    executed, nbytes, _calls = ctx["kernel_work"]["flash"]
    least_s, bound = flops.roofline_seconds(
        executed, nbytes, ctx["device_kind"])
    ctx["say"](flash_roofline_bound=bound, flash_least_ms=least_s * 1e3)
    return 100.0 * least_s * 1e3 / ms


def device_idle_share(ctx):
    return _worst(ctx, lambda c: 100.0 * (1.0 - c.busy_ns() / c.stretch_ns))


def collective_ms(ctx):
    return _worst(ctx, lambda c: collective_ms_per_step(c)[0])


def exposed_collective_ms(ctx):
    return _worst(ctx, lambda c: collective_ms_per_step(c)[1])
