"""hvt-trace, the fleet timeline (ISSUE 15): cross-rank span merge with
host-aware clock alignment, Chrome trace-event export, skew/straggler
analytics offline (`hvt-trace skew`) and live (`SkewProbe`), the
supervisor's ``GET /fleet`` rollup, the ``slow:MS`` straggler fault, and
the span writer's drop counter."""

import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from horovod_tpu.analysis import trace_cli
from horovod_tpu.obs import core, fleet, prom, timeline
from horovod_tpu.obs import server as obs_server
from horovod_tpu.testing import faults

BASE_TS = 1700000000.0  # arbitrary wall-clock epoch for synthetic spans


def write_span_file(trace_dir, rank, spans, pid=None):
    os.makedirs(trace_dir, exist_ok=True)
    pid = pid if pid is not None else 100 + rank
    path = os.path.join(trace_dir, f"spans-rank{rank}-pid{pid}.jsonl")
    with open(path, "a") as f:
        for s in spans:
            f.write(json.dumps(s) + "\n")
    return path


def step_spans(rank, host, *, n=20, period=0.1, clock_offset=0.0,
               late=0.0, dur=0.004, epoch=0, jitter=None, start=BASE_TS):
    """Synthetic per-step spans: true step k starts at
    ``start + k*period + late``, stamped on a clock shifted by
    ``clock_offset``; ``jitter(k)`` adds per-step noise (seconds)."""
    out = []
    for k in range(n):
        ts = start + k * period + late
        if jitter is not None:
            ts += jitter(k)
        out.append({
            "name": "step", "ts": ts + clock_offset, "dur_s": dur,
            "rank": rank, "pid": 100 + rank, "host": host, "id": k + 1,
            "parent": None, "depth": 0, "epoch": epoch, "step": k,
        })
    return out


class TestClockAlignment:
    def test_cross_host_offset_recovered_under_1ms(self, tmp_path):
        # rank 1 lives on a host whose clock is 3.7 s ahead, with
        # +-0.3 ms of per-anchor noise: the recovered offset round-trips
        # to < 1 ms and the residual reports the noise honestly.
        d = str(tmp_path)
        noise = lambda k: ((k * 7919) % 13 - 6) * 5e-5  # +-0.3 ms
        write_span_file(d, 0, step_spans(0, "hostA"))
        write_span_file(
            d, 1,
            step_spans(1, "hostB", clock_offset=3.7, jitter=noise),
        )
        by = timeline.load_spans(d)
        al = timeline.align(by)
        assert al.offsets[0] == 0.0
        assert abs(al.offsets[1] - (-3.7)) < 1e-3
        assert 0.0 < al.residual_ms["hostB"] < 1.0
        assert al.anchor_counts["hostB"] == 20

    def test_same_host_ranks_share_the_clock_exactly(self, tmp_path):
        # Same host = same clock: offset 0 BY CONSTRUCTION, so a
        # consistently-late rank stays visibly late (the alignment must
        # not absorb its lateness the way a cross-host fit would).
        d = str(tmp_path)
        write_span_file(d, 0, step_spans(0, "h"))
        write_span_file(d, 1, step_spans(1, "h", late=0.05))
        al = timeline.align(timeline.load_spans(d))
        assert al.offsets == {0: 0.0, 1: 0.0}
        assert al.residual_ms == {"h": 0.0}

    def test_refuses_unanchored_host(self, tmp_path):
        # rank 1 on another host trained DIFFERENT steps: no common
        # anchors, no clock correlation — alignment must refuse.
        d = str(tmp_path)
        write_span_file(d, 0, step_spans(0, "hostA", epoch=0))
        write_span_file(d, 1, step_spans(1, "hostB", epoch=7))
        with pytest.raises(timeline.TimelineError, match="no step spans"):
            timeline.align(timeline.load_spans(d))

    def test_empty_dir_refused(self, tmp_path):
        with pytest.raises(timeline.TimelineError, match="no spans-"):
            timeline.load_spans(str(tmp_path))

    def test_torn_tail_lines_skipped(self, tmp_path):
        d = str(tmp_path)
        path = write_span_file(d, 0, step_spans(0, "h", n=3))
        with open(path, "a") as f:
            f.write('{"name": "step", "ts": 17')  # killed mid-write
        by = timeline.load_spans(d)
        assert len(by[0]) == 3

    def test_pre_host_span_files_get_per_rank_clocks(self, tmp_path):
        # PR 13 span files carry no "host": each rank must be aligned
        # independently (conservative), which still works when they
        # share step anchors.
        d = str(tmp_path)
        old = [
            {k: v for k, v in s.items() if k != "host"}
            for s in step_spans(0, "x")
        ]
        write_span_file(d, 0, old)
        write_span_file(d, 1, step_spans(1, "hostB", clock_offset=1.0))
        al = timeline.align(timeline.load_spans(d))
        assert al.hosts[0] == "rank0"
        assert abs(al.offsets[1] - (-1.0)) < 1e-6


class TestChromeTrace:
    def _trace(self, tmp_path, with_flight=False):
        d = str(tmp_path)
        write_span_file(d, 0, step_spans(0, "h"))
        write_span_file(d, 1, step_spans(1, "h", late=0.02))
        if with_flight:
            with open(os.path.join(d, "flight-rank1.jsonl"), "w") as f:
                for seq in range(3):
                    f.write(json.dumps({
                        "kind": "psum_scatter", "seq": seq,
                        "t": BASE_TS + 0.05 + seq * 0.1, "bytes": 4096,
                        "bucket": 0,
                    }) + "\n")
        by = timeline.load_spans(d)
        return timeline.chrome_trace(
            by, timeline.align(by), timeline.load_flight(d)
        )

    def test_every_complete_event_carries_the_schema(self, tmp_path):
        doc = self._trace(tmp_path)
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(xs) == 40
        for e in xs:
            assert {"pid", "tid", "ts", "dur", "ph", "name"} <= set(e)
            assert e["ts"] >= 0 and e["dur"] >= 0
        # pid = rank; tid = span depth.
        assert {e["pid"] for e in xs} == {0, 1}
        assert {e["tid"] for e in xs} == {0}

    def test_loads_as_strict_json_with_metadata(self, tmp_path):
        doc = self._trace(tmp_path)
        rt = json.loads(json.dumps(doc))
        assert rt["displayTimeUnit"] == "ms"
        names = [
            e for e in rt["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"
        ]
        assert {n["args"]["name"] for n in names} == {
            "rank 0 (h)", "rank 1 (h)"
        }
        assert rt["otherData"]["clock_offsets_s"] == {"0": 0.0, "1": 0.0}

    def test_flight_records_become_instant_events(self, tmp_path):
        doc = self._trace(tmp_path, with_flight=True)
        inst = [e for e in doc["traceEvents"] if e["ph"] == "i"]
        assert len(inst) == 3
        for e in inst:
            assert e["pid"] == 1 and e["tid"] == timeline.FLIGHT_TID
            assert e["s"] == "t" and "seq" in e["args"]
        assert inst[0]["name"] == "psum_scatter#0"
        # The instant sits inside its enclosing step span's interval.
        step0 = next(
            e for e in doc["traceEvents"]
            if e["ph"] == "X" and e["pid"] == 0 and e["args"]["step"] == 0
        )
        assert inst[0]["ts"] >= step0["ts"]

    def test_nested_spans_land_on_depth_tids(self, tmp_path):
        d = str(tmp_path)
        spans = step_spans(0, "h", n=2)
        spans.append({
            "name": "decode", "ts": BASE_TS + 0.01, "dur_s": 0.002,
            "rank": 0, "pid": 100, "host": "h", "id": 99, "parent": 1,
            "depth": 1,
        })
        write_span_file(d, 0, spans)
        by = timeline.load_spans(d)
        doc = timeline.chrome_trace(by, timeline.align(by))
        decode = next(
            e for e in doc["traceEvents"] if e["name"] == "decode"
        )
        assert decode["tid"] == 1
        assert decode["args"]["parent_id"] == 1


class TestSkewMath:
    def test_straggler_named_with_barrier_wait_evidence(self, tmp_path):
        d = str(tmp_path)
        write_span_file(d, 0, step_spans(0, "h"))
        write_span_file(d, 1, step_spans(1, "h", late=0.05))
        write_span_file(d, 2, step_spans(2, "h"))
        by = timeline.load_spans(d)
        rep = timeline.skew(by, timeline.align(by))
        assert rep["straggler"] == 1
        assert rep["per_rank"][1]["straggler_score"] == 1.0
        assert rep["per_rank"][0]["straggler_score"] == 0.0
        # Barrier-wait attribution: the straggler waits ~0, the others
        # pay its lateness at every step boundary.
        assert rep["per_rank"][1]["barrier_wait_ms_mean"] < 1.0
        assert rep["per_rank"][0]["barrier_wait_ms_mean"] == pytest.approx(
            50.0, abs=1.0
        )
        assert "rank 1" in rep["evidence"]
        assert "waited" in rep["evidence"]

    def test_noise_below_threshold_names_no_straggler(self, tmp_path):
        d = str(tmp_path)
        # +-1 ms of alternating noise on a 100 ms period: under the 5%
        # threshold, nobody should be blamed.
        for r in range(2):
            write_span_file(
                d, r,
                step_spans(
                    r, "h",
                    jitter=lambda k, r=r: 1e-3 * ((k + r) % 2),
                ),
            )
        by = timeline.load_spans(d)
        rep = timeline.skew(by, timeline.align(by))
        assert rep["straggler"] is None
        assert "no consistent straggler" in rep["evidence"]

    def test_duration_spread_reported_for_sync_bound_runs(self, tmp_path):
        d = str(tmp_path)
        write_span_file(d, 0, step_spans(0, "h", dur=0.010))
        write_span_file(d, 1, step_spans(1, "h", dur=0.090))
        by = timeline.load_spans(d)
        rep = timeline.skew(by, timeline.align(by))
        assert rep["dur_spread_ms"]["step"] == pytest.approx(40.0, abs=1.0)

    def test_too_few_common_steps_never_name_a_culprit(self, tmp_path):
        # n < 3 common steps: the period (and threshold) is meaningless;
        # even a huge consistent start offset must not produce a verdict
        # (review fix — "one noisy step must not name a culprit").
        d = str(tmp_path)
        write_span_file(d, 0, step_spans(0, "h", n=2))
        write_span_file(d, 1, step_spans(1, "h", n=2, late=0.05))
        by = timeline.load_spans(d)
        rep = timeline.skew(by, timeline.align(by))
        assert rep["straggler"] is None
        assert "too few" in rep["evidence"]

    def test_refuses_without_common_steps(self, tmp_path):
        d = str(tmp_path)
        write_span_file(d, 0, step_spans(0, "h", epoch=0))
        write_span_file(d, 1, step_spans(1, "h", epoch=5))
        by = timeline.load_spans(d)
        with pytest.raises(timeline.TimelineError, match="common"):
            timeline.skew(by, timeline.align(by))

    def test_render_skew_prints_table_and_verdict(self, tmp_path):
        d = str(tmp_path)
        write_span_file(d, 0, step_spans(0, "h"))
        write_span_file(d, 1, step_spans(1, "h", late=0.05))
        by = timeline.load_spans(d)
        text = timeline.render_skew(timeline.skew(by, timeline.align(by)))
        assert "STRAGGLER: rank 1" in text
        assert "barrier-wait" in text

    def test_phase_report_covers_all_ranks_and_names(self, tmp_path):
        d = str(tmp_path)
        spans0 = step_spans(0, "h", n=4)
        spans0.append({
            "name": "checkpoint_save", "ts": BASE_TS + 1, "dur_s": 0.5,
            "rank": 0, "pid": 100, "host": "h", "id": 50, "parent": None,
            "depth": 0,
        })
        write_span_file(d, 0, spans0)
        write_span_file(d, 1, step_spans(1, "h", n=4))
        by = timeline.load_spans(d)
        table = timeline.phase_table(by)
        assert table["step"][0]["count"] == 4
        assert table["step"][1]["count"] == 4
        assert table["checkpoint_save"][0]["mean_ms"] == pytest.approx(500)
        text = timeline.render_report(by)
        assert "checkpoint_save" in text and "step" in text


class TestTraceCLI:
    def _dir(self, tmp_path):
        d = str(tmp_path / "spans")
        write_span_file(d, 0, step_spans(0, "h"))
        write_span_file(d, 1, step_spans(1, "h", late=0.05))
        return d

    def test_timeline_writes_valid_json(self, tmp_path, capsys):
        d = self._dir(tmp_path)
        out = str(tmp_path / "trace.json")
        assert trace_cli.main(["timeline", d, "-o", out]) == 0
        doc = json.load(open(out))
        assert any(e["ph"] == "X" for e in doc["traceEvents"])
        assert "residual" in capsys.readouterr().out

    def test_report_exits_zero(self, tmp_path, capsys):
        assert trace_cli.main(["report", self._dir(tmp_path)]) == 0
        assert "step" in capsys.readouterr().out

    def test_skew_expect_straggler_gate(self, tmp_path, capsys):
        d = self._dir(tmp_path)
        assert trace_cli.main(["skew", d]) == 0
        assert trace_cli.main(["skew", d, "--expect-straggler", "1"]) == 0
        assert trace_cli.main(["skew", d, "--expect-straggler", "0"]) == 1
        out = capsys.readouterr()
        assert "straggler gate passed" in out.out
        assert "expected straggler rank 0" in out.err

    def test_refusals_exit_2(self, tmp_path, capsys):
        empty = str(tmp_path / "empty")
        os.makedirs(empty)
        assert trace_cli.main(["timeline", empty]) == 2
        # Unanchored cross-host dir: refuse, never fabricate a merge.
        d = str(tmp_path / "unanchored")
        write_span_file(d, 0, step_spans(0, "hostA", epoch=0))
        write_span_file(d, 1, step_spans(1, "hostB", epoch=3))
        assert trace_cli.main(["skew", d]) == 2
        assert "hvt-trace:" in capsys.readouterr().err
        # Per-rank duration tables need no merged ordering: report still
        # serves the unanchored dir (review fix).
        assert trace_cli.main(["report", d]) == 0
        assert "step" in capsys.readouterr().out


class TestSlowFault:
    def test_parse_plan_slow_kinds(self):
        plan = faults.parse_plan("1:0:slow:50")
        assert plan.kind == "slow:50" and plan.slow_ms == 50.0
        assert plan.rank == 1 and plan.epoch == 0 and plan.step is None
        plan = faults.parse_plan("0:2.3:slow:12.5")
        assert plan.step == 3 and plan.slow_ms == 12.5
        # Non-slow kinds keep their exact prior contract.
        assert faults.parse_plan("1:1:kill").slow_ms is None

    @pytest.mark.parametrize("bad", [
        "1:0:slow:", "1:0:slow:abc", "1:0:slow:-5", "1:0:slow:0",
        "1:0:bogus", "1:0:kill:extra",
    ])
    def test_bad_specs_still_refused(self, bad):
        with pytest.raises(ValueError):
            faults.parse_plan(bad)

    def test_slow_fires_every_batch_from_target_epoch(self, monkeypatch):
        sleeps = []
        monkeypatch.setattr(faults.time, "sleep", sleeps.append)
        monkeypatch.setattr(faults.runtime, "rank", lambda: 1)
        cb = faults.FaultInjectionCallback(faults.parse_plan("1:1:slow:50"))
        cb.on_epoch_begin(0)
        cb.on_batch_end(0)
        assert sleeps == []  # before the target epoch
        cb.on_epoch_begin(1)
        for b in range(3):
            cb.on_batch_end(b)
        cb.on_epoch_begin(2)  # RECURRING: later epochs stay slow
        cb.on_batch_end(0)
        assert sleeps == [0.05] * 4

    def test_slow_inert_on_other_ranks(self, monkeypatch):
        sleeps = []
        monkeypatch.setattr(faults.time, "sleep", sleeps.append)
        monkeypatch.setattr(faults.runtime, "rank", lambda: 0)
        cb = faults.FaultInjectionCallback(faults.parse_plan("1:0:slow:50"))
        cb.on_epoch_begin(0)
        cb.on_batch_end(0)
        assert sleeps == []


class TestSpanDropCounter:
    @pytest.fixture(autouse=True)
    def _fresh(self, monkeypatch):
        from horovod_tpu import trace

        core.reset()
        monkeypatch.setattr(trace, "_span_writer", trace._SpanWriter())
        yield
        core.reset()

    def test_drops_counted_and_exported(self, tmp_path, monkeypatch):
        from horovod_tpu import trace

        # HVT_TRACE_DIR points at a FILE: the writer dies on open and
        # every span from then on is a counted drop.
        blocker = tmp_path / "not-a-dir"
        blocker.write_text("x")
        monkeypatch.setenv("HVT_TRACE_DIR", str(blocker))
        for _ in range(3):
            with trace.span("step", epoch=0, step=0):
                pass
        assert trace._span_writer.drops == 3
        values = prom.parse_text(prom.render())
        assert values["hvt_trace_spans_dropped_total"] == 3

    def test_healthy_writer_reports_zero(self, tmp_path, monkeypatch):
        from horovod_tpu import trace

        monkeypatch.setenv("HVT_TRACE_DIR", str(tmp_path / "spans"))
        with trace.span("step", epoch=0, step=0):
            pass
        assert trace._span_writer.drops == 0
        values = prom.parse_text(prom.render())
        assert values["hvt_trace_spans_dropped_total"] == 0

    def test_span_records_carry_host(self, tmp_path, monkeypatch):
        from horovod_tpu import trace

        monkeypatch.setenv("HVT_TRACE_DIR", str(tmp_path / "spans"))
        with trace.span("step", epoch=0, step=0):
            pass
        trace.emit_span("queue_wait", time.time(), 0.001)
        files = os.listdir(tmp_path / "spans")
        recs = [
            json.loads(l)
            for l in open(os.path.join(tmp_path / "spans", files[0]))
        ]
        assert len(recs) == 2
        assert all(r["host"] for r in recs)
        assert recs[1]["name"] == "queue_wait"
        assert recs[1]["dur_s"] == 0.001

    def test_attrs_cannot_clobber_the_span_schema(self, tmp_path,
                                                  monkeypatch):
        # A caller attr named like a core field must lose: the timeline
        # merge keys parent linkage on `id` (a serving `id=` attr
        # silently broke it — regression).
        from horovod_tpu import trace

        monkeypatch.setenv("HVT_TRACE_DIR", str(tmp_path / "spans"))
        with trace.span("request", id=999, depth=77):
            trace.emit_span("child", time.time(), 0.001, id=888)
        files = os.listdir(tmp_path / "spans")
        recs = [
            json.loads(l)
            for l in open(os.path.join(tmp_path / "spans", files[0]))
        ]
        child = next(r for r in recs if r["name"] == "child")
        parent = next(r for r in recs if r["name"] == "request")
        assert parent["id"] not in (999, 888)
        assert child["parent"] == parent["id"]


class TestSkewProbe:
    @pytest.fixture(autouse=True)
    def _fresh(self):
        core.reset()
        yield
        core.reset()

    def test_off_single_process_and_off_by_knob(self, monkeypatch):
        from horovod_tpu.training.trainer import SkewProbe

        monkeypatch.delenv("HVT_SKEW_PROBE", raising=False)
        assert SkewProbe.maybe() is None  # single-process CI
        monkeypatch.setenv("HVT_SKEW_PROBE", "0")
        assert SkewProbe.maybe() is None

    def test_publish_names_the_minimal_drain_rank(self, monkeypatch):
        from horovod_tpu.parallel import collectives
        from horovod_tpu.training.trainer import SkewProbe

        # Fake a 3-rank fleet where rank 2 is the straggler: its drain
        # wait is ~0 while the others block for its contribution.
        rows = [(0, 0.050, BASE_TS), (1, 0.048, BASE_TS), (2, 0.001, BASE_TS)]
        monkeypatch.setattr(
            collectives, "allgather_object", lambda obj: rows
        )
        probe = SkewProbe.__new__(SkewProbe)
        probe.rank = 0
        probe.world = 3
        probe.publish(0.050)
        values = prom.parse_text(prom.render())
        assert values["hvt_straggler_rank"] == 2
        assert values["hvt_step_skew_ms"] == pytest.approx(
            (0.050 - 0.048) * 1e3
        )
        # Blocked time beyond the fleet minimum: 50 ms - 1 ms.
        assert values["hvt_barrier_wait_ms"] == pytest.approx(49.0)

    def test_sampler_carries_probe_handle(self, monkeypatch):
        # Single-process: the sampler wires the probe slot but it stays
        # None (nothing to skew against) — the zero-cost default.
        import flax.linen as nn
        import optax

        import horovod_tpu as hvt
        from horovod_tpu.training.trainer import StepPhaseSampler

        class M(nn.Module):
            @nn.compact
            def __call__(self, x, *, train: bool = False):
                return nn.Dense(2)(x)

        t = hvt.Trainer(M(), hvt.DistributedOptimizer(optax.sgd(1e-2)))
        sampler = StepPhaseSampler(t, 8, every=4)
        assert sampler.skew_probe is None


class TestFleetRollup:
    def _member_registry(self, total_ms, skew_ms=None):
        reg = core.Registry()
        reg.gauge("hvt_step_phase_ms", total_ms, phase="total")
        reg.gauge("hvt_step_phase_ms", total_ms * 0.8, phase="compute")
        reg.gauge("hvt_mfu", 0.12)
        if skew_ms is not None:
            reg.gauge("hvt_step_skew_ms", skew_ms)
        return reg

    def test_merge_fleet_injects_rank_labels_and_summary(self):
        members = {
            0: prom.render(self._member_registry(12.0, 3.0)),
            1: prom.render(self._member_registry(61.5, 3.0)),
        }
        sup = core.Registry()
        sup.counter_set("hvt_restarts_total", 1)
        merged = fleet.merge_fleet(prom.render(sup), members)
        values = prom.parse_text(merged)
        assert values["hvt_restarts_total"] == 1
        assert values['hvt_step_phase_ms{phase="total",rank="0"}'] == 12.0
        assert values['hvt_step_phase_ms{phase="total",rank="1"}'] == 61.5
        assert values['hvt_step_skew_ms{rank="1"}'] == 3.0
        assert values['hvt_fleet_step_ms{stat="slowest"}'] == 61.5
        assert values['hvt_fleet_step_ms{stat="fastest"}'] == 12.0
        # One HELP/TYPE block per family (a valid single exposition).
        assert merged.count("# TYPE hvt_step_phase_ms gauge") == 1

    def test_merge_without_members_is_the_supervisor_exposition(self):
        sup = core.Registry()
        sup.gauge("hvt_fleet_size", 2)
        text = prom.render(sup)
        assert fleet.merge_fleet(text, {}) == text

    def test_torn_member_scrape_skipped_not_fatal(self):
        members = {0: "%%% not an exposition %%%"}
        sup = core.Registry()
        sup.gauge("hvt_fleet_size", 1)
        merged = fleet.merge_fleet(prom.render(sup), members)
        assert prom.parse_text(merged)["hvt_fleet_size"] == 1

    def test_fleet_endpoint_over_fake_member_exporters(self, tmp_path):
        from horovod_tpu.launch import supervisor

        m0 = obs_server.start_metrics_server(
            0, registry=self._member_registry(10.0, 1.0)
        )
        m1 = obs_server.start_metrics_server(
            0, registry=self._member_registry(55.0, 1.0)
        )
        log = tmp_path / "restarts.jsonl"
        log.write_text(json.dumps(
            {"name": "restarts", "value": 0, "wall_time": 0}
        ) + "\n")
        ports = {
            0: m0.server_address[1],
            1: m1.server_address[1],
        }
        srv = supervisor.start_status_server(
            0, str(log), fleet_ports=ports
        )
        try:
            url = (
                f"http://127.0.0.1:{srv.server_address[1]}/fleet"
            )
            with urllib.request.urlopen(url, timeout=5) as r:
                assert r.headers["Content-Type"] == prom.CONTENT_TYPE
                text = r.read().decode()
            values = prom.parse_text(text)
            # Per-rank member series, supervisor series, and computed
            # fleet stats in ONE scrape body.
            assert values['hvt_step_phase_ms{phase="total",rank="0"}'] == 10.0
            assert values['hvt_step_phase_ms{phase="total",rank="1"}'] == 55.0
            assert values['hvt_step_skew_ms{rank="0"}'] == 1.0
            assert values['hvt_fleet_step_ms{stat="slowest"}'] == 55.0
            assert values["hvt_restarts_total"] == 0
            # The rollup cached the member scrapes for the final dump.
            assert set(srv.fleet_cache["members"]) == {0, 1}
            dump = tmp_path / "metrics.prom"
            supervisor.dump_metrics(
                str(log), path=str(dump),
                members=srv.fleet_cache["members"],
            )
            dumped = prom.parse_text(dump.read_text())
            assert dumped['hvt_mfu{rank="1"}'] == 0.12
        finally:
            srv.shutdown()
            m0.shutdown()
            m1.shutdown()

    def test_fleet_endpoint_skips_dead_members(self, tmp_path):
        from horovod_tpu.launch import supervisor

        m0 = obs_server.start_metrics_server(
            0, registry=self._member_registry(10.0)
        )
        with socket.socket() as s:  # a port nobody answers
            s.bind(("127.0.0.1", 0))
            dead_port = s.getsockname()[1]
        srv = supervisor.start_status_server(
            0, None, fleet_ports={0: m0.server_address[1], 1: dead_port}
        )
        try:
            url = f"http://127.0.0.1:{srv.server_address[1]}/fleet"
            with urllib.request.urlopen(url, timeout=10) as r:
                values = prom.parse_text(r.read().decode())
            assert 'hvt_step_phase_ms{phase="total",rank="0"}' in values
            assert not any('rank="1"' in k for k in values)
        finally:
            srv.shutdown()
            m0.shutdown()

    def test_fleet_404_without_known_ports(self, tmp_path):
        from horovod_tpu.launch import supervisor

        srv = supervisor.start_status_server(0, None)
        try:
            url = f"http://127.0.0.1:{srv.server_address[1]}/fleet"
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(url, timeout=5)
            assert e.value.code == 404
            assert "metrics ports" in json.loads(e.value.read())["error"]
        finally:
            srv.shutdown()

    def test_member_metrics_ports_resolution(self, monkeypatch):
        from horovod_tpu.launch import supervisor

        monkeypatch.delenv("HVT_METRICS_PORT", raising=False)
        assert supervisor.member_metrics_ports({}, 2) is None
        assert supervisor.member_metrics_ports(
            {"HVT_METRICS_PORT": "0"}, 2
        ) is None  # ephemeral ports are unknowable
        assert supervisor.member_metrics_ports(
            {"HVT_METRICS_PORT": "9000"}, 3
        ) == {0: 9000, 1: 9001, 2: 9002}
        assert supervisor.member_metrics_ports(
            {"HVT_METRICS_PORT": "junk"}, 2
        ) is None


class TestServeRequestSpans:
    """The serving tier leaves spans too (ISSUE 15 satellite): one
    `request` span per POST with `queue_wait` and `decode` children, so
    `hvt-trace timeline` shows TTFT as span structure."""

    @pytest.fixture(autouse=True)
    def _spans_on(self, tmp_path, monkeypatch):
        from horovod_tpu import trace

        self.span_dir = tmp_path / "spans"
        monkeypatch.setenv("HVT_TRACE_DIR", str(self.span_dir))
        monkeypatch.setattr(trace, "_span_writer", trace._SpanWriter())
        yield

    def _spans(self):
        recs = []
        for name in os.listdir(self.span_dir):
            if name.startswith("spans-"):
                with open(self.span_dir / name) as f:
                    recs.extend(json.loads(l) for l in f if l.strip())
        return recs

    def test_batcher_emits_queue_wait_and_decode(self):
        from horovod_tpu.launch.serve import _Batcher

        done = threading.Event()

        def run_rows(items):
            time.sleep(0.02)
            return [i * 2 for i in items]

        b = _Batcher(run_rows, batch=4, stats={"device_calls": 0,
                                               "rows": 0})
        assert b.submit([1, 2]) == [2, 4]
        done.set()
        names = [r["name"] for r in self._spans()]
        assert names.count("queue_wait") == 1
        assert names.count("decode") == 1
        decode = next(r for r in self._spans() if r["name"] == "decode")
        assert decode["dur_s"] >= 0.02
        assert decode["rows"] == 2

    def test_generate_lock_path_emits_children_under_request(self):
        # The sampled-generate path (no batcher): lock wait becomes
        # queue_wait, the device call a decode child — exercised on a
        # stub bundle so no export is paid here.
        from horovod_tpu import trace
        from horovod_tpu.launch.serve import _GenerateApp

        class StubBundle:
            batch_size = 4
            tokenizer = None
            meta = {"temperature": 0.7}

            def validate_prompts(self, prompts):
                return prompts

            def generate_tokens(self, prompts, seed=0):
                return [[1, 2] for _ in prompts]

        app = _GenerateApp.__new__(_GenerateApp)
        app.bundle = StubBundle()
        app.stats = {"device_calls": 0, "rows": 0}
        app._lock = threading.Lock()
        app._batcher = None
        with trace.span("request", req=1, route="/v1/generate"):
            out = app.generate({"prompt": [[3, 1]]})
        assert out["tokens"] == [[1, 2]]
        recs = {r["name"]: r for r in self._spans()}
        assert {"request", "queue_wait", "decode"} <= set(recs)
        req = recs["request"]
        assert req["route"] == "/v1/generate"
        assert req["req"] == 1  # the request-correlation attr
        # Children nest under the request span.
        assert recs["queue_wait"]["parent"] == req["id"]
        assert recs["decode"]["parent"] == req["id"]
        assert recs["decode"]["depth"] == 1

    def test_predict_http_request_carries_span_tree(self):
        # Over real HTTP with the coalescing batcher (the cheap predict
        # bundle): request span on the handler thread, queue_wait +
        # decode measured on the worker.
        import flax.linen as nn
        import jax
        import numpy as np

        from horovod_tpu import checkpoint
        from horovod_tpu.launch.serve import make_server

        class Tiny(nn.Module):
            @nn.compact
            def __call__(self, x, train: bool = False):
                return nn.Dense(3)(x)

        model = Tiny()
        x0 = np.zeros((2, 4), np.float32)
        params = model.init(jax.random.PRNGKey(0), x0)["params"]
        out = checkpoint.export_serving(
            str(self.span_dir.parent / "bundle"),
            lambda p, x: model.apply({"params": p}, x),
            params, input_shape=(2, 4), timestamp="19700101-000000",
        )
        srv = make_server(out, port=0)
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.server_address[1]}/v1/predict",
                data=json.dumps(
                    {"input": np.zeros((2, 4)).tolist()}
                ).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=10) as r:
                assert r.status == 200
        finally:
            srv.shutdown()
        recs = self._spans()
        by_name = {r["name"]: r for r in recs}
        assert {"request", "queue_wait", "decode"} <= set(by_name)
        assert by_name["request"]["route"] == "/v1/predict"
        assert by_name["queue_wait"]["parent"] == by_name["request"]["id"]


# --- the slow e2e: injected straggler -> named straggler --------------------


def _free_port_base(n=2):
    """A base port with n consecutive free ports (best-effort)."""
    for base in range(29611, 29911, 10):
        try:
            socks = []
            for i in range(n):
                s = socket.socket()
                s.bind(("127.0.0.1", base + i))
                socks.append(s)
            for s in socks:
                s.close()
            return base
        except OSError:
            for s in socks:
                s.close()
    raise RuntimeError("no free port window")


SLOW_TRAIN_SCRIPT = """
import os, sys
sys.path.insert(0, __REPO__)
import numpy as np
import optax
import flax.linen as nn
import horovod_tpu as hvt


class Tiny(nn.Module):
    @nn.compact
    def __call__(self, x, train=False):
        return nn.Dense(4)(x)


def main():
    hvt.init()
    rng = np.random.RandomState(0)
    x = rng.rand(96, 8).astype("float32")
    y = (np.arange(96) % 4).astype("int64")
    trainer = hvt.Trainer(
        Tiny(), hvt.DistributedOptimizer(optax.adam(1e-2))
    )
    cbs = [hvt.callbacks.BroadcastGlobalVariablesCallback(0)]
    trainer.fit(
        x=x, y=y, batch_size=8, epochs=2, steps_per_epoch=6,
        callbacks=cbs, verbose=0,
    )
    if hvt.rank() == 0:
        print("TRAINING COMPLETE", flush=True)


main()
"""


@pytest.mark.slow
def test_slow_fault_e2e_straggler_named_and_fleet_scraped(tmp_path, capfd):
    """The ISSUE 15 acceptance run: a real 2-process supervised run with
    an injected ``slow:50`` on rank 1 yields (a) a valid merged Chrome
    trace with both ranks' step spans on one clock, (b) ``hvt-trace
    skew`` naming rank 1 with barrier-wait evidence, and (c) one
    ``GET /fleet`` scrape carrying per-rank step-phase series plus the
    live SkewProbe's ``hvt_step_skew_ms`` — which also survives into the
    final metrics.prom dump via the fleet poller."""
    from horovod_tpu.launch import supervisor
    from horovod_tpu.launch.supervisor import RestartPolicy

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "train.py"
    script.write_text(SLOW_TRAIN_SCRIPT.replace("__REPO__", repr(repo)))
    trace_dir = tmp_path / "trace"
    model_dir = tmp_path / "models"
    log = tmp_path / "restarts.jsonl"
    base = _free_port_base(2)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        status_port = s.getsockname()[1]
    env = {
        "HVT_PLATFORM": "cpu",
        "HVT_NUM_CPU_DEVICES": "1",
        "PS_MODEL_PATH": str(model_dir),
        "HVT_FAULT": "1:0:slow:50",
        "HVT_TRACE_DIR": str(trace_dir),
        "HVT_METRICS_PORT": str(base),
        "HVT_METRICS_EVERY": "1",   # drain every step: max skew signal
        "HVT_FLEET_POLL_S": "0.5",  # cache member scrapes fast
        "HVT_PEAK_FLOPS": "1e12",   # the CPU has no published peak
        "JAX_ENABLE_COMPILATION_CACHE": "0",
        "JAX_COMPILATION_CACHE_DIR": "",
    }
    fleet_text = {}

    def scrape_fleet():
        deadline = time.monotonic() + 120
        url = f"http://127.0.0.1:{status_port}/fleet"
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(url, timeout=2) as r:
                    candidate = r.read().decode()
                values = prom.parse_text(candidate)
                if (
                    'hvt_step_phase_ms{phase="total",rank="0"}' in values
                    and 'hvt_step_phase_ms{phase="total",rank="1"}' in values
                    and any(
                        k.startswith("hvt_step_skew_ms") for k in values
                    )
                ):
                    fleet_text["text"] = candidate
                    return
            except (urllib.error.URLError, OSError, ConnectionError,
                    ValueError):
                pass
            time.sleep(0.3)

    scraper = threading.Thread(target=scrape_fleet, daemon=True)
    scraper.start()
    code = supervisor.supervise_local(
        2, [os.sys.executable, str(script)],
        env=env,
        policy=RestartPolicy(max_restarts=2, backoff=0.0,
                             grace_seconds=5.0),
        model_dir=str(model_dir), log_path=str(log),
        status_port=status_port, tag_output=False,
        sleep=lambda s: None,
    )
    assert code == 0
    out = capfd.readouterr().out
    assert "TRAINING COMPLETE" in out
    scraper.join(timeout=5)

    # (a) merged Chrome trace: both ranks, one clock, strict JSON.
    trace_json = tmp_path / "trace.json"
    assert trace_cli.main(
        ["timeline", str(trace_dir), "-o", str(trace_json)]
    ) == 0
    doc = json.load(open(trace_json))
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in xs} >= {0, 1}
    assert all({"pid", "tid", "ts", "dur", "ph"} <= set(e) for e in xs)
    steps = [e for e in xs if e["name"] == "step"]
    assert {e["pid"] for e in steps} == {0, 1}

    # (b) skew names the injected straggler with barrier-wait evidence.
    assert trace_cli.main(
        ["skew", str(trace_dir), "--expect-straggler", "1"]
    ) == 0
    by = timeline.load_spans(str(trace_dir))
    rep = timeline.skew(by, timeline.align(by))
    assert rep["straggler"] == 1
    assert rep["per_rank"][0]["barrier_wait_ms_mean"] > 10.0
    assert (
        rep["per_rank"][1]["barrier_wait_ms_mean"]
        < rep["per_rank"][0]["barrier_wait_ms_mean"]
    )

    # (c) the live /fleet scrape carried per-rank series + skew, and
    # the per-rank series survived into the final dump.
    assert "text" in fleet_text, "never scraped a full fleet rollup"
    values = prom.parse_text(fleet_text["text"])
    skew_keys = [k for k in values if k.startswith("hvt_step_skew_ms")]
    assert skew_keys
    assert values['hvt_fleet_step_ms{stat="slowest"}'] >= values[
        'hvt_fleet_step_ms{stat="fastest"}'
    ]
    dump = model_dir / "metrics.prom"
    assert dump.exists()
    dumped = prom.parse_text(dump.read_text())
    assert any(k.startswith("hvt_step_phase_ms") and 'rank="1"' in k
               for k in dumped)
