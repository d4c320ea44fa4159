"""The cross-chip sums of the step, read through the program's own table.

Since PR 30 a gradient sum on several chips is three kinds of ``fusion``
events in the trace: ``async-collective-start.N``, host fusions
(``fusion.N``, compute that carries the sum's steps) and
``async-collective-done.N``. Their own HLO lines hold no collective opcode
(`reduce.COLLECTIVE` finds none) and their metadata no scope: which sum an
event belongs to, of which gradient, and what a host computes are in the
called computations of the compiled module, which the trace does not hold.
The program does: `horovod_tpu.trace.step_reductions()` (PR 37) gives, for
the step program of the process's newest fit, a row a collective with
``nbytes``, ``scope`` and the instruction names ``start``, ``done`` and
``hosts`` (``name``, ``host_scope``). This file joins that table to the
chips' leaf ops by instruction name (`reduce.op_name`).

The join's rule (`join`): an empty table (one chip), or a program that has
no such function (a parent of PR 37), reads nothing. A trace that holds an
``async-collective-start`` / ``-done`` event, or one whose line still shows
``calls=%async_collective_fusion`` (the profiler elides long lines), that
the table does not name means the table is another program's: nothing is
read and the strangers are said. So is a host of the table that is not
there once in every steady step. A start, a done or a synchronous
collective of the table that the leaves do not hold is no error (a done
of no length falls under `reduce.MIN_OP_NS`): they are said with their
count. Worst chip, leaves only, the steady steps, as every other reader.
"""

from __future__ import annotations

import re

from chipbench import reduce
from chipbench.spans import OPTIMIZER_SCOPE

ASYNC_EDGE = re.compile(r"async-collective-(start|done)(\.\d+)?$")
HOSTED = "calls=%async_collective_fusion"


def table_of(ctx):
    """The program's table, asked for once and kept in ``ctx`` (a test
    puts a recorded one there)."""
    if "reductions" not in ctx:
        from horovod_tpu import trace

        ask = getattr(trace, "step_reductions", None)
        ctx["reductions"] = ask() if ask else None
    return ctx["reductions"]


def roles_of(table) -> dict:
    """{instruction name: (role, row number, host_scope)}; the role is
    "start", "done", "synchronous" (one instruction that is both) or
    "host"."""
    roles = {}
    for i, row in enumerate(table):
        if row["start"] == row["done"]:
            roles[row["start"]] = ("synchronous", i, "")
        else:
            roles[row["start"]] = ("start", i, "")
            roles[row["done"]] = ("done", i, "")
        for host in row["hosts"]:
            roles[host["name"]] = ("host", i, host["host_scope"])
    roles.pop("", None)
    return roles


def chip_sums(chip, roles) -> dict:
    """One chip's leaf ops against the table: nanoseconds and events by
    instruction, and the events that look like a sum's and are not the
    table's."""
    ns, count, strangers = {}, {}, set()
    for line, _, dur in chip.ops:
        name = reduce.op_name(line)
        if name in roles:
            ns[name] = ns.get(name, 0.0) + dur
            count[name] = count.get(name, 0) + 1
        elif ASYNC_EDGE.match(name) or HOSTED in line:
            strangers.add(name)
    return {"ns": ns, "count": count, "strangers": sorted(strangers)}


def general(scope: str) -> str:
    """A scope path with its block's number taken out, so that twelve
    layers' rows read as one."""
    return re.sub(r"Block_\d+", "Block_n", scope)


def join(ctx):
    """``(table, roles, per-chip sums)`` or None (see the module's text);
    made and said once, kept in ``ctx``."""
    if "reduction_join" in ctx:
        return ctx["reduction_join"]
    ctx["reduction_join"] = None
    table, chips = table_of(ctx), ctx["chips"]
    if not table or not chips:
        return None
    roles = roles_of(table)
    sums = [chip_sums(chip, roles) for chip in chips]
    hosts = [n for n, (role, _, _) in roles.items() if role == "host"]
    for chip, mine in zip(chips, sums):
        off = {n: mine["count"].get(n, 0) for n in hosts
               if mine["count"].get(n, 0) != len(chip.steps)}
        if mine["strangers"] or off:
            ctx["say"](reduction_join_refused={
                "chip": chip.plane, "steps": len(chip.steps),
                "events_the_table_does_not_name": mine["strangers"][:20],
                "hosts_not_once_a_step": dict(sorted(off.items())[:20])})
            return None
    worst = max(range(len(chips)), key=lambda i: _ms(
        chips[i], sums[i], roles, ("start", "done", "synchronous")))
    chip, mine = chips[worst], sums[worst]
    absent = sorted(n for n in roles if n not in mine["count"])
    ctx["say"](reduction_join={
        "chip": chip.plane, "rows": len(table), "instructions": len(roles),
        "in_the_trace": len(roles) - len(absent),
        "not_in_the_trace": len(absent),
        "not_in_the_trace_by_role": {
            role: sum(roles[n][0] == role for n in absent)
            for role in ("start", "done", "synchronous", "host")},
        "not_in_the_trace_names": absent[:80],
        "wait_ms_by_role": {
            role: _ms(chip, mine, roles, (role,))
            for role in ("start", "done", "synchronous")},
        "wait_ms_by_chip": [
            _ms(c, s, roles, ("start", "done", "synchronous"))
            for c, s in zip(chips, sums)]})
    ctx["say"](reduction_waits=_waits(chip, mine, roles, table),
               reduction_hosts=_hosts(chip, mine, roles))
    ctx["reduction_join"] = (table, roles, sums)
    return ctx["reduction_join"]


def _ms(chip, mine, roles, wanted, scope="") -> float:
    """Milliseconds a step of one chip in the table's instructions of the
    roles ``wanted`` (hosts: those whose ``host_scope`` holds ``scope``)."""
    return sum(
        ns for name, ns in mine["ns"].items()
        if roles[name][0] in wanted and scope in roles[name][2]
    ) / 1e6 / len(chip.steps)


def _waits(chip, mine, roles, table):
    """[[scope, ms a step in start + done, MB]], most time first."""
    ms, mb = {}, {}
    for row in table:
        key = general(row["scope"])
        mb[key] = mb.get(key, 0.0) + row["nbytes"] / 1e6
        ms.setdefault(key, 0.0)
    for name, ns in mine["ns"].items():
        role, i, _ = roles[name]
        if role != "host":
            ms[general(table[i]["scope"])] += ns / 1e6 / len(chip.steps)
    return [[key, ms[key], mb[key]]
            for key in sorted(ms, key=ms.get, reverse=True)]


def _hosts(chip, mine, roles):
    """[[host_scope, ms a step, hosts]], most time first."""
    ms, count = {}, {}
    for name, (role, _, host_scope) in roles.items():
        if role == "host":
            key = general(host_scope)
            ms[key] = ms.get(key, 0.0) + (
                mine["ns"].get(name, 0.0) / 1e6 / len(chip.steps))
            count[key] = count.get(key, 0) + 1
    return [[key, ms[key], count[key]]
            for key in sorted(ms, key=ms.get, reverse=True)]


# --- readers of the per-layer metrics --------------------------------------

def _worst(ctx, wanted, scope=""):
    joined = join(ctx)
    if joined is None:
        return None
    _, roles, sums = joined
    return max(_ms(chip, mine, roles, wanted, scope)
               for chip, mine in zip(ctx["chips"], sums))


def reduction_bytes_per_step(ctx):
    joined = join(ctx)
    if joined is None:
        return None
    return sum(row["nbytes"] for row in joined[0]) / 1e6


def reduction_wait_ms_per_step(ctx):
    return _worst(ctx, ("start", "done", "synchronous"))


def reduction_host_ms_per_step(ctx):
    return _worst(ctx, ("host",))


def optimizer_hosted_ms_per_step(ctx):
    return _worst(ctx, ("host",), OPTIMIZER_SCOPE)
