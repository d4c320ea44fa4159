"""What the program says about itself inside the profiler's trace.

`chipbench/reduce.py` reads the device's events by their HLO lines and has
to guess what they are. The program (PR 26) names what it does, in the
same trace and on the same clock:

* host spans, `horovod_tpu.trace.span(name)` -> a TraceAnnotation
  ``hvt.<name>``: ``hvt.input_wait``, ``hvt.step``, ``hvt.callbacks`` on
  the training loop's thread (training/feeding.py), ``hvt.input.assemble``,
  ``hvt.input.place``, ``hvt.input.queue_full`` on the prefetch thread
  (data/prefetch.py);
* device scopes, `jax.named_scope`: ``hvt.head_ce`` (ops/fused_ce.py, the
  forward and the backward rule) and ``hvt.optimizer`` (the update in
  training/trainer.py `train_step`);
* kernel names, `pallas_call(name=)`: ``hvt_flash_fwd``, ``hvt_flash_dq``,
  ``hvt_flash_dkv`` (ops/flash_attention.py), which the compiled HLO
  instruction takes (``%hvt_flash_fwd.3 = ... custom-call(...)``;
  `reduce.flash_kernel_of` tells them). A Mosaic call of another name is
  one of the phase "other kernels" until a reader of its own asks for it by
  that name.

Where this runtime puts them (seen by hand in PR 25's v5e traces and in
PR 26's first traced run): a host span is an event of a thread's line of
the plane ``/host:CPU``, named ``hvt.<name>``, its attributes as stats;
both threads' lines are called ``python3``, so a thread is told by its
line's place in the plane, not by its name. A scope is NOT in a device
event's name (the HLO line carries no metadata) nor in the event's own
stats: it is the stat ``tf_op`` of the event's *metadata* (XEventMetadata,
one per HLO instruction of the plane), which holds the instruction's
``op_name`` and a colon: ``jit(train_step)/transpose(jvp(TransformerLM))/
lm_head.fused_loss/hvt.head_ce/while/body/closed_call/dot_general:``.
`jax.profiler.ProfileData` shows neither metadata stats nor which line is
which thread, and `reduce.rows_from_xplane` keeps names only, so `read`
below decodes the few fields it needs from the file's protobuf wire format
itself (`XSpace` of tsl/profiler/protobuf/xplane.proto; no dependency, and
the ``XLA Ops`` lines, nearly all of the file, are skipped unread).

`run.py` hands the readers no path to the trace: `trace_of` takes the
newest ``*.xplane.pb`` under ``<checkout>/.chipbench_out/*/profile/``
(`run.py` clears and rewrites the cell's directory just before the readers
run) and keeps what it read in ``ctx["spans"]``. A test puts a recorded
cut there instead (tests/chipbench/trace_cut_spans.json).

Everything between the adapter and the readers is a pure function of rows,
scopes and spans. A reader that does not find what it reads (a parent
commit names nothing) returns None and its metric is left out.
"""

from __future__ import annotations

import pathlib
import re

from chipbench import reduce

ROOT = pathlib.Path(__file__).resolve().parent.parent
SPAN_PREFIX = "hvt."
HEAD_SCOPE, OPTIMIZER_SCOPE = "hvt.head_ce", "hvt.optimizer"
LOOP_SPANS = ("hvt.input_wait", "hvt.step", "hvt.callbacks")
PHASES = ("blocks forward", "blocks backward", "flash", "other kernels",
          "head + CE", "optimizer", "collectives", "other named",
          "unattributed")


# --- the adapter -----------------------------------------------------------

def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of every field of one protobuf message: an
    int for a varint, a memoryview for a length-delimited field, the raw
    bytes for a fixed one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = bytes(buf[i:i + size]), i + size
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield number, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entries(plane_fields, number):
    """{key: value message} of a ``map<int64, Message>`` field."""
    out = {}
    for entry in plane_fields.get(number, ()):
        fields = dict(_fields(entry))
        out[fields.get(1, 0)] = fields.get(2, b"")
    return out


def _grouped(buf) -> dict:
    out: dict = {}
    for number, value in _fields(buf):
        out.setdefault(number, []).append(value)
    return out


def _stat_text(fields, stat_names) -> str:
    """A decoded XStat's value as text: ``str_value`` (5), or ``ref_value``
    (7), which points at a stat metadata's name."""
    if 5 in fields:
        return _text(fields[5])
    return stat_names.get(fields.get(7), "")


def read(path) -> dict:
    """``{"scopes": {HLO line: op_name}, "host": [(thread, name, start_ns,
    duration_ns)]}`` of one ``.xplane.pb``: the ``tf_op`` of every device
    instruction that has one, and every ``hvt.*`` host event (however
    short) with the place of its thread's line in the host plane. Times
    are on the clock of `reduce.rows_from_xplane`'s rows (a line's
    ``timestamp_ns`` plus the event's offset)."""
    scopes: dict = {}
    host: list = []
    data = memoryview(pathlib.Path(path).read_bytes())
    for number, plane in _fields(data):
        if number != 1:
            continue
        fields = _grouped(plane)
        name = _text(fields.get(2, [b""])[0])
        is_host = name == reduce.HOST_PLANE
        if not is_host and not reduce.DEVICE_PLANE.match(name):
            continue
        stat_names = {
            key: _text(dict(_fields(meta)).get(2, b""))
            for key, meta in _map_entries(fields, 5).items()}
        events = {key: _grouped(meta)
                  for key, meta in _map_entries(fields, 4).items()}
        if not is_host:
            for meta in events.values():
                for stat in map(dict, map(_fields, meta.get(5, ()))):
                    if stat_names.get(stat.get(1)) == "tf_op":
                        scopes[_text(meta[2][0])] = _stat_text(
                            stat, stat_names).rstrip(":")
            continue
        wanted = {key: _text(meta[2][0]) for key, meta in events.items()
                  if 2 in meta and bytes(meta[2][0][:4]) == b"hvt."}
        for thread, line in enumerate(fields.get(3, ())):
            line_fields = _grouped(line)
            t0 = line_fields.get(3, [0])[0]
            for event in line_fields.get(4, ()):
                ev = dict(_fields(event))
                if ev.get(1) in wanted:
                    host.append((thread, wanted[ev[1]],
                                 t0 + ev.get(2, 0) / 1e3,
                                 ev.get(3, 0) / 1e3))
    return {"scopes": scopes, "host": sorted(host, key=lambda s: s[2])}


def newest_trace(root: pathlib.Path = ROOT):
    paths = list(root.glob(
        ".chipbench_out/*/profile/plugins/profile/*/*.xplane.pb"))
    return max(paths, key=lambda p: p.stat().st_mtime) if paths else None


def trace_of(ctx) -> dict:
    """The run's scopes and host spans, read once and kept in ``ctx``."""
    if "spans" not in ctx:
        path = newest_trace()
        ctx["spans"] = read(path) if path else {"scopes": {}, "host": []}
    return ctx["spans"]


# --- pure functions: the host's side ---------------------------------------

def loop_thread(host):
    """The training loop's thread: the one that enters ``hvt.step``."""
    threads = {t for t, name, _, _ in host if name == "hvt.step"}
    return min(threads) if threads else None


def all_steps(rows, chip):
    """Every event of the chip's step program in the trace, the two that
    `reduce.chips_from_rows` drops at the edges included."""
    return sorted((s, d) for p, l, n, s, d in rows
                  if p == chip.plane and l == reduce.MODULES
                  and n == chip.module)


def host_window(rows, chip, host):
    """``(t0, t1, steps)``: the stretch of the HOST's clock in which the
    loop handed the device the chip's steady steps. The n-th ``hvt.step``
    span of the trace belongs to the n-th step program (the traced
    stretch starts from a drained device); the steady steps are all but
    the first and the last, so their loop iterations run from the end of
    the first ``hvt.step`` to the end of the last but one: one
    ``hvt.callbacks``, one ``hvt.input_wait`` and one ``hvt.step`` each.
    None unless the trace holds one ``hvt.step`` per step program."""
    thread = loop_thread(host)
    calls = [(s, d) for t, n, s, d in host
             if t == thread and n == "hvt.step"]
    if len(calls) < 3 or len(calls) != len(all_steps(rows, chip)):
        return None
    return (calls[0][0] + calls[0][1], calls[-2][0] + calls[-2][1],
            len(calls) - 2)


def span_ms_per_step(rows, chip, host, names, on_loop_thread):
    """Milliseconds a step of the spans ``names`` that start inside the
    host's window, on the loop's thread or on every other."""
    window = host_window(rows, chip, host)
    if window is None:
        return None
    t0, t1, steps = window
    thread = loop_thread(host)
    picked = [d for t, n, s, d in host
              if n in names and t0 <= s < t1
              and (t == thread) == on_loop_thread]
    if not picked:
        return None
    return sum(picked) / 1e6 / steps


def host_gaps(chip, host, top: int = 10):
    """[[what, seconds per step]]: the device's idle gaps between two
    steady steps, each named by the innermost ``hvt.*`` span of the loop's
    thread that covers its middle ("no program span" where none does)."""
    thread = loop_thread(host)
    mine = [(s, s + d, n) for t, n, s, d in host if t == thread]
    total: dict = {}
    for (a_start, a_dur), (b_start, _) in zip(chip.steps, chip.steps[1:]):
        end = a_start + a_dur
        if b_start <= end:
            continue
        mid = (end + b_start) / 2
        cover = [(e - s, n) for s, e, n in mine if s <= mid <= e]
        what = min(cover)[1] if cover else "no program span"
        total[what] = total.get(what, 0.0) + b_start - end
    ranked = sorted(total, key=total.get, reverse=True)[:top]
    return [[what, total[what] / 1e9 / len(chip.steps)] for what in ranked]


# --- pure functions: the device's side -------------------------------------

def scope_path(op_name: str) -> str:
    """The named scopes of an instruction's ``op_name``: what lies between
    the leading ``jit(<fn>)`` and the trailing primitive.
    ``jit(train_step)/hvt.optimizer/add`` -> ``hvt.optimizer``;
    ``jit(train_step)/add`` and ``reduce_sum`` -> nothing."""
    parts = op_name.split("/")
    if parts and re.fullmatch(r"(jit|pjit)\(.*\)", parts[0]):
        parts = parts[1:]
    return "/".join(parts[:-1])


def phase_of(hlo_line: str, scopes: dict) -> str:
    """The one phase a leaf op belongs to, by what its own metadata names
    (for a fusion, whatever the compiler kept): a Mosaic kernel is flash
    where it has a flash kernel's name and one of the "other kernels"
    where it has not; then the program's scopes; then a collective
    instruction no scope claims; then the flax scopes of the blocks,
    backward where the path holds ``transpose(``; then whatever else has a
    scope (embedding, final norm, the loss's mean); and "unattributed" for
    an op with no scope path at all."""
    if reduce.KERNEL_MARK in hlo_line:
        return "flash" if reduce.flash_kernel_of(hlo_line) else "other kernels"
    op_name = scopes.get(hlo_line, "")
    if HEAD_SCOPE in op_name:
        return "head + CE"
    if OPTIMIZER_SCOPE in op_name:
        return "optimizer"
    if reduce.COLLECTIVE.search(hlo_line):
        return "collectives"
    path = scope_path(op_name)
    if not path:
        return "unattributed"
    if "Block_" in path:
        return "blocks backward" if "transpose(" in path else "blocks forward"
    return "other named"


def phase_ms(chip, scopes) -> dict:
    """{phase: milliseconds a step} over the chip's leaf ops; the values
    sum to the chip's busy time (leaves do not overlap)."""
    total = dict.fromkeys(PHASES, 0.0)
    for name, start, dur in chip.ops:
        total[phase_of(name, scopes)] += min(start + dur, chip.t1) - start
    return {k: v / 1e6 / len(chip.steps) for k, v in total.items()}


def sub_scope(op_name: str) -> str:
    """The part of a scope path a reader of the table wants next to the
    phase: the module inside a block (``Block_3/Block_3._mlp/mlp_up`` ->
    ``_mlp``), else the first scope inside the model, else nothing."""
    path = re.sub(r"(transpose\()?jvp\(\w*\)\)?/?", "", scope_path(op_name))
    inside = re.match(r"Block_\d+/(?:Block_\d+\.)?([^/]+)", path)
    if inside:
        return inside.group(1)
    return "" if path.startswith("Block_") else path.split("/")[0]


def scope_table(chip, scopes, floor_ms: float = 0.3):
    """[[phase, sub-scope, milliseconds a step, {op family: ms}]] over
    the chip's leaf ops, largest first, rows under ``floor_ms`` left out:
    the phase table one level down, with the fusion families behind each
    row (what `reduce.device_op_families` shows without the names)."""
    total: dict = {}
    families: dict = {}
    for name, _, dur in chip.ops:
        op_name = scopes.get(name, "")
        phase = phase_of(name, scopes)
        if phase == "head + CE":
            sub = "backward" if "transpose(" in op_name else "forward"
        elif phase.startswith("blocks") or phase == "other named":
            sub = sub_scope(op_name)
        elif phase == "other kernels":  # which: the `pallas_call`'s name
            sub = re.sub(r"[.\d]+$", "", reduce.op_name(name))
        else:
            sub = ""
        key = (phase, sub)
        total[key] = total.get(key, 0.0) + dur
        mine = families.setdefault(key, {})
        family = reduce.op_family(name)
        mine[family] = mine.get(family, 0.0) + dur
    per_ms = 1e6 * len(chip.steps)
    return [
        [*key, total[key] / per_ms,
         {f: ns / per_ms for f, ns in sorted(
             families[key].items(), key=lambda kv: -kv[1])[:3]}]
        for key in sorted(total, key=total.get, reverse=True)
        if total[key] / per_ms >= floor_ms]


def unattributed_families(chip, scopes, top: int = 8):
    """[[op family, milliseconds a step]] of the ops with no scope path."""
    total: dict = {}
    for name, _, dur in chip.ops:
        if phase_of(name, scopes) == "unattributed":
            family = reduce.op_family(name)
            total[family] = total.get(family, 0.0) + dur
    ranked = sorted(total, key=total.get, reverse=True)[:top]
    return [[f, total[f] / 1e6 / len(chip.steps)] for f in ranked]


# --- readers of the per-layer metrics --------------------------------------

def _worst(ctx, fn):
    values = [fn(chip) for chip in ctx["chips"]]
    if not values or None in values:
        return None
    return max(values)


def _host_metric(ctx, names, on_loop_thread):
    host = trace_of(ctx)["host"]
    return _worst(ctx, lambda c: span_ms_per_step(
        ctx["rows"], c, host, names, on_loop_thread))


def input_wait_ms_per_step(ctx):
    return _host_metric(ctx, ("hvt.input_wait",), True)


def input_produce_ms_per_step(ctx):
    return _host_metric(
        ctx, ("hvt.input.assemble", "hvt.input.place"), False)


def host_loop_ms_per_step(ctx):
    return _host_metric(ctx, ("hvt.step", "hvt.callbacks"), True)


def _phase_metric(ctx, phase, scope):
    scopes = trace_of(ctx)["scopes"]
    if not any(scope in op_name for op_name in scopes.values()):
        return None
    return _worst(ctx, lambda c: phase_ms(c, scopes)[phase])


def head_ce_ms_per_step(ctx):
    return _phase_metric(ctx, "head + CE", HEAD_SCOPE)


def optimizer_ms_per_step(ctx):
    return _phase_metric(ctx, "optimizer", OPTIMIZER_SCOPE)


def _kernel_metric(ctx, which):
    """None unless the kernel is there as often a step as the family
    counts its calls (for the dense LM once a layer)."""
    work = ctx["kernel_work"].get(f"flash_{which}")
    if work is None:
        return None

    def one(chip):
        ms, count = reduce.flash_kernel_ms_per_step(chip, which)
        return ms if count == work[2] else None

    return _worst(ctx, one)


def flash_fwd_ms_per_step(ctx):
    return _kernel_metric(ctx, "fwd")


def flash_dq_ms_per_step(ctx):
    return _kernel_metric(ctx, "dq")


def flash_dkv_ms_per_step(ctx):
    return _kernel_metric(ctx, "dkv")


def unattributed_device_share(ctx):
    """Also prints, through ``ctx["say"]``, the whole phase table of the
    worst chip and its between-steps gaps by program span."""
    trace = trace_of(ctx)
    scopes, chips = trace["scopes"], ctx["chips"]
    if not scopes or not chips:
        return None

    def share(chip):
        return 100.0 * phase_ms(chip, scopes)["unattributed"] * 1e6 * len(
            chip.steps) / chip.busy_ns()

    worst = max(chips, key=lambda c: 1 - c.busy_ns() / c.stretch_ns)
    table = phase_ms(worst, scopes)
    ctx["say"](
        phase_ms=table, phase_ms_sum=sum(table.values()),
        busy_ms_per_step=worst.busy_ns() / 1e6 / len(worst.steps),
        chip=worst.plane,
        unattributed_families=unattributed_families(worst, scopes),
        host_gaps=host_gaps(worst, trace["host"]))
    ctx["say"](by_scope=scope_table(worst, scopes))
    return _worst(ctx, share)
