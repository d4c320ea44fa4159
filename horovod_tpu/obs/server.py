"""The metrics exporter HTTP server — ``GET /metrics`` for any process.

Two consumers:

* the **trainer-side exporter** (``HVT_METRICS_PORT``): every training
  process serves its own live step-phase gauges (`ensure_trainer_exporter`
  — the feeding paths call it once per process; port = base + local rank,
  so co-located processes don't collide). It additionally mounts
  ``POST /profile?seconds=N``: an on-demand `jax.profiler` capture of the
  next N seconds into ``HVT_TRACE_DIR`` (or ``HVT_PROFILE``), so a slow
  step can be drilled into without relaunching with profiling on — and
  ``POST /flightrecord``: an on-demand dump of this process's collective
  flight record (`horovod_tpu.flight`), the live-fleet entry into
  ``hvt-sched replay``.
* **any other long-lived process** wanting a standalone scrape port
  (`start_metrics_server` with an explicit registry). The supervisor and
  the serving server instead mount ``/metrics`` on their existing HTTP
  surfaces (launch/supervisor.py, launch/serve.py) — one pane of glass,
  no extra ports.

Binds loopback by default (`HVT_STATUS_HOST`), like the supervisor status
server: the routes are unauthenticated."""

from __future__ import annotations

import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

from horovod_tpu.analysis import registry as knob_registry
from horovod_tpu.obs import core, prom


class _ProfileTrigger:
    """One in-flight on-demand profiler capture per process. jax.profiler
    supports a single active trace; concurrent POSTs get 409."""

    def __init__(self):
        self._lock = threading.Lock()
        self._active: str | None = None

    def start(self, seconds: float) -> str:
        out_root = (
            knob_registry.get_str("HVT_TRACE_DIR")
            or knob_registry.get_str("HVT_PROFILE")
        )
        if not out_root:
            raise ValueError(
                "on-demand profiling needs HVT_TRACE_DIR or HVT_PROFILE "
                "set — the capture has nowhere to land"
            )
        seconds = float(seconds)
        if not 0 < seconds <= 600:
            raise ValueError("seconds must be in (0, 600]")
        # Import BEFORE claiming the slot: a failed import after
        # `_active` is set would wedge the trigger in 409 forever.
        import jax

        with self._lock:
            if self._active is not None:
                raise RuntimeError(
                    f"a capture is already running ({self._active})"
                )
            out_dir = os.path.join(
                out_root, f"profile-{time.strftime('%Y%m%d-%H%M%S')}"
            )
            self._active = out_dir
        try:
            jax.profiler.start_trace(out_dir)
        except BaseException:
            with self._lock:
                self._active = None
            raise

        def stop():
            time.sleep(seconds)
            try:
                jax.profiler.stop_trace()
                # The key to the dump's `fusion.N` events, beside it.
                from horovod_tpu import trace

                trace.write_step_reductions(out_dir)
            finally:
                with self._lock:
                    self._active = None

        threading.Thread(target=stop, daemon=True).start()
        return out_dir


def start_metrics_server(port: int, host: str | None = None,
                         registry: core.Registry | None = None,
                         profile: bool = False):
    """Serve ``GET /metrics`` (+ ``GET /healthz``; ``POST /profile`` when
    ``profile=True``) for ``registry`` (default: the process default).
    Port 0 binds ephemerally — ``server.server_address[1]`` carries the
    real one. Returns the started server; callers own ``shutdown()``."""
    if host is None:
        host = knob_registry.get_str("HVT_STATUS_HOST")
    reg = registry if registry is not None else core.default_registry()
    trigger = _ProfileTrigger() if profile else None

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *args):  # scrapes are noise
            pass

        def _send(self, code: int, body: bytes, ctype: str):
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _send_json(self, code: int, payload: dict):
            self._send(code, json.dumps(payload).encode(),
                       "application/json")

        def do_GET(self):
            try:
                path = urlparse(self.path).path
                if path == "/metrics":
                    reg.counter("hvt_scrapes_total")
                    prom.write_http(self, reg)
                elif path == "/healthz":
                    self._send_json(200, {"status": "ok"})
                else:
                    self._send_json(404, {"error": f"no route {path}"})
            except Exception as e:  # observability must never crash
                self._send_json(500, {"error": f"{type(e).__name__}: {e}"})

        def do_POST(self):
            try:
                url = urlparse(self.path)
                if url.path == "/flightrecord":
                    # On-demand dump of this process's collective flight
                    # record (horovod_tpu.flight) — the live-fleet
                    # counterpart of the supervisor's hang collection:
                    # grab every rank's /flightrecord, then
                    # `hvt-sched replay` the directory.
                    from horovod_tpu import flight

                    rec = flight.RECORDER
                    if rec is None:
                        self._send_json(409, {
                            "error": "flight recorder is off — set "
                            "HVT_FLIGHT_RECORD to a directory and "
                            "relaunch",
                        })
                        return
                    self._send_json(200, {
                        "path": rec.dump(),
                        "records": rec.count,
                        "seq": rec.seq,
                    })
                    return
                if url.path != "/profile" or trigger is None:
                    self._send_json(404, {"error": f"no route {url.path}"})
                    return
                q = parse_qs(url.query)
                seconds = float(q.get("seconds", ["5"])[0])
                try:
                    out_dir = trigger.start(seconds)
                except RuntimeError as e:
                    self._send_json(409, {"error": str(e)})
                    return
                except ValueError as e:
                    self._send_json(400, {"error": str(e)})
                    return
                self._send_json(
                    200, {"profiling": out_dir, "seconds": seconds}
                )
            except Exception as e:
                self._send_json(500, {"error": f"{type(e).__name__}: {e}"})

    server = ThreadingHTTPServer((host, port), Handler)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server


def _retry_collector(reg) -> None:
    """Mirror the data layer's transient-read retry total at scrape
    time: the stream module owns the monotonic truth (``RETRY_STATS``),
    the scrape just reads it. A NAMED module-level function so
    re-registration dedupes by identity."""
    from horovod_tpu.data import stream as stream_lib

    reg.counter_set(
        "hvt_data_retries_total", stream_lib.RETRY_STATS["retried"],
        outcome="retried",
    )
    reg.counter_set(
        "hvt_data_retries_total", stream_lib.RETRY_STATS["exhausted"],
        outcome="exhausted",
    )


_trainer_exporter = None
_trainer_exporter_lock = threading.Lock()


def ensure_trainer_exporter():
    """Start this process's trainer-side exporter once, when
    ``HVT_METRICS_PORT`` is set (opt-in): port = base + local rank, so
    `hvt-launch run --nprocs N --metrics-port P` yields one scrapeable
    exporter per process at P..P+N-1. Returns the server (or None when
    the knob is unset). Idempotent; survives across fits — the exporter
    is a property of the process, not of one fit call."""
    global _trainer_exporter
    base = knob_registry.get_int("HVT_METRICS_PORT")
    if base is None:
        return None
    with _trainer_exporter_lock:
        # Re-registered on EVERY call (each fit), not just at server
        # start: `obs.reset()` clears collectors, and the once-per-
        # process server guard would otherwise leave the retries series
        # silently absent afterwards. Registration dedupes by callable
        # identity, so this never stacks. Same treatment for the span
        # writer's drop mirror (trace.py registers it at writer open /
        # on drops — this covers a reset in between).
        from horovod_tpu import trace as trace_lib

        core.register_collector(trace_lib._dropped_spans_collector)
        core.register_collector(_retry_collector)
        if _trainer_exporter is None:
            from horovod_tpu import runtime

            port = 0 if base == 0 else base + runtime.local_rank()
            _trainer_exporter = start_metrics_server(port, profile=True)
        return _trainer_exporter


def trainer_exporter():
    """The running trainer exporter, or None (tests reach the bound port
    through ``server.server_address``)."""
    return _trainer_exporter
