"""Tests of chipbench/reduction_spans.py: the four dp4 metrics that read the
trace through the program's own table of its cross-chip sums (PR 37).

The readers are checked on a cut recorded from this PR's traced v5e run of
``cerebras-gpt-1.3b.seq2k.dp4`` (trace_cut_reductions.json: two chips'
planes, five step programs each, the events of eight of the table's 75
rows and the plain ops of 3 ms and more, with those eight rows of the table
`horovod_tpu.trace.step_reductions()` gave in that run; the expected
numbers were worked out by a separate script with plain loops when it was
recorded), and on changes to the cut small enough to work out by hand.
"""

import copy
import json
import pathlib

import pytest

from chipbench import reduce, reduction_spans

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
METRICS = ("reduction_bytes_per_step", "reduction_wait_ms_per_step",
           "reduction_host_ms_per_step", "optimizer_hosted_ms_per_step")
DP4 = "cerebras-gpt-1.3b.seq2k.dp4"


@pytest.fixture(scope="module")
def cut():
    return json.loads((HERE / "trace_cut_reductions.json").read_text())


def context(cut, rows=None, **changes):
    """What `run.traced_context` would hand the readers, with the cut's
    table in the place of what `table_of` would ask the program for."""
    rows = [tuple(r) for r in (cut["rows"] if rows is None else rows)]
    said = []
    ctx = {"rows": rows, "chips": reduce.chips_from_rows(rows),
           "say": lambda **fields: said.append(fields), "said": said,
           "reductions": copy.deepcopy(cut["table"])}
    ctx.update(changes)
    return ctx


def read_all(ctx):
    return {name: getattr(reduction_spans, name)(ctx) for name in METRICS}


def said(ctx, key):
    found = [fields[key] for fields in ctx["said"] if key in fields]
    assert len(found) <= 1  # the join is made, and said, once
    return found[0] if found else None


def steady(cut, plane=None):
    """(start, end) of the steady stretch of a plane of the cut (the first
    one's by default): all its step programs but the first and the last."""
    plane = plane or min(r[0] for r in cut["rows"])
    steps = sorted((r[3], r[4]) for r in cut["rows"]
                   if r[0] == plane and r[1] == reduce.MODULES)[1:-1]
    return plane, steps[0][0], steps[-1][0] + steps[-1][1]


def events_of(cut, instruction, plane=None):
    plane, t0, t1 = steady(cut, plane)
    return [r for r in cut["rows"]
            if r[0] == plane and r[1] == reduce.OPS and t0 <= r[3] < t1
            and reduce.op_name(r[2]) == instruction]


def test_the_four_metrics_have_a_reader_and_an_entry_on_dp4_only():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"]][-4:] == list(METRICS)
    for name in METRICS:
        spec = json.loads(
            (ROOT / "chipbench" / "layer_metrics" / f"{name}.json").read_text())
        assert spec["reader"] == f"reduction_spans.py:{name}"
        assert spec["unit"] == entries[name]["unit"]
        assert callable(getattr(reduction_spans, name))
        assert entries[name]["workloads"] == [DP4]
        assert entries[name]["layer"] == "reduction (implicit SPMD all-reduce)"
        assert entries[name]["moves"] == "tokens_per_s"
    assert entries["reduction_bytes_per_step"]["source"] == "program_counter"


def test_readers_on_the_recorded_cut(cut):
    ctx = context(cut)
    assert [len(c.steps) for c in ctx["chips"]] == [3, 3]
    got = read_all(ctx)
    for name in METRICS:
        assert got[name] == pytest.approx(cut["expected"][name]), name
    # The optimizer's hosts are a part of the hosts, and the bytes are the
    # table's, whatever the trace holds.
    assert 0 < got["optimizer_hosted_ms_per_step"] < (
        got["reduction_host_ms_per_step"])
    assert got["reduction_bytes_per_step"] == sum(
        row["nbytes"] for row in cut["table"]) / 1e6


def test_the_join_is_said_once_with_whose_the_waits_and_hosts_are(cut):
    ctx = context(cut)
    read_all(ctx)
    join = said(ctx, "reduction_join")
    assert join["rows"] == len(cut["table"])
    assert join["in_the_trace"] + join["not_in_the_trace"] == (
        join["instructions"])
    assert join["not_in_the_trace_by_role"]["host"] == 0
    assert max(join["wait_ms_by_chip"]) == pytest.approx(
        cut["expected"]["reduction_wait_ms_per_step"])
    assert sum(join["wait_ms_by_role"].values()) == pytest.approx(
        cut["expected"]["reduction_wait_ms_per_step"])
    waits, hosts = said(ctx, "reduction_waits"), said(ctx, "reduction_hosts")
    assert [w[1] for w in waits] == sorted((w[1] for w in waits), reverse=True)
    assert sum(w[1] for w in waits) == pytest.approx(
        cut["expected"]["reduction_wait_ms_per_step"])
    assert sum(w[2] for w in waits) == pytest.approx(
        cut["expected"]["reduction_bytes_per_step"])
    # (The printed tables are the chip's that waits longest; the metric is
    # the worst chip's of each quantity.)
    assert 0 < sum(h[1] for h in hosts) <= (
        cut["expected"]["reduction_host_ms_per_step"] * (1 + 1e-9))
    assert sum(h[2] for h in hosts) == sum(
        len(row["hosts"]) for row in cut["table"])
    # Twelve layers' rows read as one: no block's number is left.
    assert not any("Block_0" in row[0] or "Block_1" in row[0]
                   for row in waits + hosts)
    assert cut["expected"]["reduction_waits"] == [
        [w[0], pytest.approx(w[1]), pytest.approx(w[2])] for w in waits]


@pytest.mark.parametrize("table", [[], None], ids=["one-chip", "no-table"])
def test_an_empty_table_or_a_program_without_one_reads_nothing(cut, table):
    ctx = context(cut, reductions=table)
    assert read_all(ctx) == dict.fromkeys(METRICS)
    assert ctx["said"] == []


def test_the_table_is_asked_of_the_program_once(cut, monkeypatch):
    from horovod_tpu import trace

    asked = []
    monkeypatch.setattr(trace, "step_reductions",
                        lambda: asked.append(1) or cut["table"])
    ctx = context(cut)
    del ctx["reductions"]
    assert read_all(ctx)["reduction_wait_ms_per_step"] == pytest.approx(
        cut["expected"]["reduction_wait_ms_per_step"])
    assert asked == [1]
    # A parent of PR 37 has no such function: nothing is read, nothing raised.
    monkeypatch.delattr(trace, "step_reductions")
    ctx = context(cut)
    del ctx["reductions"]
    assert read_all(ctx) == dict.fromkeys(METRICS)


def test_an_event_the_table_does_not_name_refuses_the_join(cut):
    """Then the table is another program's: a start, a done or a host
    fusion the trace holds and no row names."""
    plane, t0, _ = steady(cut)
    for line in (
            "%async-collective-done.999 = f32[2048]{0} fusion(%g), "
            "kind=kCustom, calls=%fused_computation.9",
            "%fusion.9999 = (bf16[8]{0}, bf16[8]{0}) fusion(%a), "
            "kind=kLoop, calls=%async_collective_fusion.9999"):
        stranger = [plane, reduce.OPS, line, t0 + 1000.0, 500.0]
        ctx = context(cut, rows=cut["rows"] + [stranger])
        assert read_all(ctx) == dict.fromkeys(METRICS)
        refused = said(ctx, "reduction_join_refused")
        assert refused["chip"] == plane
        assert refused["events_the_table_does_not_name"] == [
            reduce.op_name(line)]
        assert said(ctx, "reduction_join") is None


def test_a_host_missing_from_a_step_refuses_the_join(cut):
    host = cut["table"][0]["hosts"][0]["name"]
    mine = events_of(cut, host)
    assert len(mine) == 3  # once a steady step
    rows = [r for r in cut["rows"] if r is not mine[1]]
    ctx = context(cut, rows=rows)
    assert read_all(ctx) == dict.fromkeys(METRICS)
    refused = said(ctx, "reduction_join_refused")
    assert refused["hosts_not_once_a_step"] == {host: 2}
    assert refused["steps"] == 3


def test_a_done_of_no_length_is_said_and_is_no_error(cut):
    """A done whose sum had ended is an event of (next to) no length, which
    `reduce.leaves` drops: the table names it, the trace does not hold it,
    and the other instructions still read."""
    row = next(r for r in cut["table"] if r["start"] != r["done"]
               and events_of(cut, r["done"]))
    gone = {id(e) for plane in {r[0] for r in cut["rows"]}
            for e in events_of(cut, row["done"], plane)}
    lost_ms = max(
        sum(e[4] for e in events_of(cut, row["done"], plane)) / 3 / 1e6
        for plane in {r[0] for r in cut["rows"]})
    whole = read_all(context(cut))
    ctx = context(cut, rows=[r for r in cut["rows"] if id(r) not in gone])
    got = read_all(ctx)
    assert got["reduction_host_ms_per_step"] == pytest.approx(
        whole["reduction_host_ms_per_step"])
    assert got["reduction_bytes_per_step"] == whole["reduction_bytes_per_step"]
    assert whole["reduction_wait_ms_per_step"] - lost_ms - 1e-9 <= (
        got["reduction_wait_ms_per_step"]) < whole["reduction_wait_ms_per_step"]
    join = said(ctx, "reduction_join")
    assert row["done"] in join["not_in_the_trace_names"]
    assert join["not_in_the_trace_by_role"]["done"] == (
        said(context_read(cut), "reduction_join")[
            "not_in_the_trace_by_role"]["done"] + 1)


def context_read(cut):
    ctx = context(cut)
    read_all(ctx)
    return ctx


def test_roles_of_a_table():
    table = [
        {"start": "all-reduce.7", "done": "all-reduce.7", "hosts": []},
        {"start": "all-reduce-start.1", "done": "all-reduce-done.1",
         "hosts": []},
        {"start": "async-collective-start", "done": "async-collective-done",
         "hosts": [{"name": "fusion.3", "host_scope": "hvt.optimizer"}]},
    ]
    assert reduction_spans.roles_of(table) == {
        "all-reduce.7": ("synchronous", 0, ""),
        "all-reduce-start.1": ("start", 1, ""),
        "all-reduce-done.1": ("done", 1, ""),
        "async-collective-start": ("start", 2, ""),
        "async-collective-done": ("done", 2, ""),
        "fusion.3": ("host", 2, "hvt.optimizer"),
    }
