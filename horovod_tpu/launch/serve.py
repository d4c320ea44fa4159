"""Minimal HTTP model server — the TF-Serving role over this framework's
serving bundles.

The reference's export tail produces a SavedModel "so that it can be
served by TF Serving" (mnist_keras.py:126-140); this module is the
native half of that story: it serves a StableHLO bundle over HTTP with
no TF anywhere. Two bundle kinds, auto-detected:

* **predict bundles** (`checkpoint.export_serving`) — the reference's
  ``input → prob`` classifier contract;
* **generation bundles** (`serving.export_generate`) — the flagship LM's
  compiled prefill + decode loop, tokenizer riding along.

Endpoints (JSON, shapes follow the exported signature's trailing dims):

* ``GET  /healthz``                → ``{"status": "ok", "bundle": ...}``
  (+ a ``fleet`` section — generation/size/restart/rescale events from the
  supervisor journal — when launched with ``--fleet-journal``)
* ``POST /v1/predict``  body ``{"input": [[...], ...]}``
                                   → ``{"prob": [[...], ...]}``
* ``POST /v1/generate`` body ``{"prompt": [[ids...], ...]}`` or
  ``{"text": ["...", ...]}`` (+ optional ``"seed": N``)
                                   → ``{"tokens": [[ids...], ...]}``
                                     (+ ``"text": [...]`` with a tokenizer)
* ``POST /v1/generate`` with ``"stream": true`` (streaming bundles —
  `serving.export_generate(streaming_chunk=K)`) → ``application/x-ndjson``:
  one ``{"tokens": [[ids...]]}`` line per generated chunk, then a final
  ``{"done": true, "tokens": ..., "text": ...}`` line.

Batching: the exported program is compiled for ONE batch shape (static
shapes are the deal with XLA). Requests of any row count are padded up /
split to the bundle's batch size server-side — and generation prompts of
any length ≤ the compiled prompt_len ride the ragged-lengths path — so
clients never see the static-shape constraint.

Concurrency: one device worker drains a **coalescing queue** — rows from
concurrent requests are packed together into the compiled batch shape, so
N simultaneous single-row clients cost ~ceil(N/batch) device dispatches
instead of N (a count; no serving cell has timed it on the chip yet).
Handler threads only enqueue and wait; the device callable
never runs re-entrantly. Sampled generation bundles (temperature > 0) are
the exception: each request owns its rng seed for the whole compiled
call, so they serialize per-request through the worker instead of mixing
rows from different seeds (``app.stats['device_calls']`` exposes the
dispatch count either way).

Run:  ``python -m horovod_tpu.launch.serve <bundle_dir> [--port 8000]``
(or `serve_forever(bundle_dir, port)` programmatically; tests use
`make_server` + a background thread).
"""

from __future__ import annotations

import itertools
import json
import queue as queue_lib
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from horovod_tpu.obs import core as obs_core
from horovod_tpu.obs import prom as obs_prom


# Monotone per-process request ids for the serving `request` spans —
# enough to correlate a request's children in a merged timeline.
_request_ids = itertools.count(1)


class _Slot:
    """One request row's rendezvous with the device worker.

    ``started``/``finished`` carry the worker's clocks around the device
    call that served this row — (wall, perf) at dispatch and perf at
    completion — so the submitting handler thread can emit queue-wait /
    decode trace spans for its request (only stamped, and only read,
    when spans are on)."""

    __slots__ = ("event", "value", "error", "started", "finished")

    def __init__(self):
        self.event = threading.Event()
        self.value = None
        self.error = None
        self.started = None
        self.finished = None

    def set(self, value):
        self.value = value
        self.event.set()

    def set_err(self, e):
        self.error = e
        self.event.set()

    def get(self):
        self.event.wait()
        if self.error is not None:
            raise self.error
        return self.value


class _Batcher:
    """The coalescing device worker.

    Handler threads `submit` lists of row-items and block; the single
    worker thread drains the queue, packs up to ``batch`` rows — across
    requests — into one device call, and distributes per-row results.
    ``run_rows(items) -> results`` is the only code that touches the
    device, so the compiled callable never runs re-entrantly and the old
    global lock is gone.

    When ``HVT_TRACE_DIR`` is set, the worker stamps each slot with the
    wall/perf clocks around its device call so `submit` can emit
    ``queue_wait`` / ``decode`` child spans for ITS request — the spans
    belong to the handler thread's open ``request`` span, but the
    interval they measure happened on the worker (`trace.emit_span`).
    """

    def __init__(self, run_rows, batch: int, stats: dict):
        self.run_rows = run_rows
        self.batch = batch
        self.stats = stats
        self.q: queue_lib.Queue = queue_lib.Queue()
        self._worker = threading.Thread(target=self._loop, daemon=True)
        self._worker.start()

    def submit(self, items: list) -> list:
        from horovod_tpu import trace as trace_lib

        slots = [_Slot() for _ in items]
        t_sub, p_sub = time.time(), time.perf_counter()
        for it, s in zip(items, slots):
            self.q.put((it, s))
        out = [s.get() for s in slots]
        if trace_lib.span_dir() and slots and slots[0].started is not None:
            started_wall, started_perf = slots[0].started
            done_perf = slots[-1].finished
            trace_lib.emit_span(
                "queue_wait", t_sub, max(0.0, started_perf - p_sub)
            )
            if done_perf is not None:
                trace_lib.emit_span(
                    "decode", started_wall, done_perf - started_perf,
                    rows=len(items),
                )
        return out

    def stop(self):
        """Retire the worker (weight reload rebuilds the batcher —
        the old worker must not keep draining the dead queue)."""
        self.q.put(_Batcher._STOP)

    _STOP = object()

    def _loop(self):
        while True:
            first = self.q.get()
            if first is _Batcher._STOP:
                return
            group = [first]
            while len(group) < self.batch:
                try:
                    item = self.q.get_nowait()
                except queue_lib.Empty:
                    break
                if item is _Batcher._STOP:
                    self.q.put(item)  # honor it after this group
                    break
                group.append(item)
            self.stats["device_calls"] += 1
            self.stats["rows"] += len(group)
            started = (time.time(), time.perf_counter())
            for _, s in group:
                s.started = started
            try:
                results = self.run_rows([it for it, _ in group])
                done = time.perf_counter()
                for (_, s), r in zip(group, results):
                    s.finished = done
                    s.set(r)
            except Exception as e:
                for _, s in group:
                    s.set_err(e)


class _ModelApp:
    """A predict bundle, its static batch size, and the pad/split logic."""

    kind = "predict"

    def __init__(self, bundle_dir: str, coalesce: bool = True):
        from horovod_tpu import checkpoint

        self.bundle_dir = bundle_dir
        self.fn = checkpoint.load_serving(bundle_dir)
        with open(f"{bundle_dir}/{checkpoint.SIGNATURE_FILE}") as f:
            self.signature = json.load(f)["signature"]
        spec = self.signature["inputs"]["input"]
        self.batch = int(spec["shape"][0])
        self.row_shape = tuple(int(d) for d in spec["shape"][1:])
        self.dtype = np.dtype(spec["dtype"])
        self.stats = {"device_calls": 0, "rows": 0}
        # coalesce=False keeps the legacy serialize-whole-requests path
        # (one lock, one request at a time: ROADMAP D3).
        self._lock = None if coalesce else threading.Lock()
        self._batcher = (
            _Batcher(self._run_rows, self.batch, self.stats)
            if coalesce else None
        )

    def _run_rows(self, rows: list) -> list:
        chunk = np.stack(rows)
        n = len(chunk)
        if n < self.batch:  # pad to the compiled shape
            chunk = np.concatenate(
                [chunk, np.repeat(chunk[-1:], self.batch - n, 0)]
            )
        return list(np.asarray(self.fn(chunk))[:n])

    def predict(self, rows: np.ndarray) -> np.ndarray:
        if rows.ndim != 1 + len(self.row_shape) or (
            rows.shape[1:] != self.row_shape
        ):
            raise ValueError(
                f"input rows must be shaped {('N',) + self.row_shape}, "
                f"got {rows.shape}"
            )
        rows = rows.astype(self.dtype)
        if self._batcher is not None:
            return np.stack(self._batcher.submit(list(rows)))
        out = []
        with self._lock:
            for start in range(0, len(rows), self.batch):
                self.stats["device_calls"] += 1
                self.stats["rows"] += len(rows[start : start + self.batch])
                out.append(self._run_rows(list(rows[start : start + self.batch])))
        return np.concatenate([np.stack(o) for o in out])


class _GenerateApp:
    """A generation bundle behind the coalescing worker — or, with
    ``continuous=True``, behind the per-decode-step scheduler
    (`horovod_tpu.serving.engine.ContinuousBatchingEngine`).

    Coalescing (the default): greedy bundles (temperature == 0: the rng
    is dead code in the exported program) coalesce rows across concurrent
    requests exactly like predict bundles; sampled bundles serialize
    whole requests. Continuous (streaming bundles only): every request
    row is an independently scheduled sequence — admitted into free
    decode capacity mid-flight, retired the chunk it finishes, refused
    with 429 (`AdmissionError`) when the paged-KV wait queue is full.
    Sized by the ``HVT_SERVE_MAX_SEQS`` / ``HVT_SERVE_BLOCK_TOKENS`` /
    ``HVT_SERVE_KV_BLOCKS`` / ``HVT_SERVE_QUEUE_DEPTH`` knobs.
    """

    kind = "generate"
    # Class-level defaults so partially-constructed instances (tests
    # stub the app without running _load) take the legacy path.
    engine = None
    continuous = False

    def __init__(self, bundle_dir: str, coalesce: bool = True,
                 continuous: bool = False):
        self.continuous = continuous
        self._coalesce = coalesce
        self._lock = threading.Lock()
        self._load(bundle_dir)

    def _load(self, bundle_dir: str) -> None:
        """(Re)build the app around ``bundle_dir`` — the boot path AND
        the ``/admin/reload`` weight-swap target."""
        from horovod_tpu import serving

        self.bundle_dir = bundle_dir
        self.bundle = serving.load_generate(bundle_dir)
        self.signature = {
            "inputs": {
                "prompt": {
                    "shape": [self.bundle.batch_size, self.bundle.prompt_len],
                    "dtype": "int32",
                }
            },
            "outputs": {"tokens": {}},
            "meta": self.bundle.meta,
        }
        self.stats = {"device_calls": 0, "rows": 0}
        if getattr(self, "_batcher", None) is not None:
            self._batcher.stop()  # reload: retire the old worker
        if self.continuous:
            from horovod_tpu.analysis import registry as knobs
            from horovod_tpu.serving.engine import ContinuousBatchingEngine

            self.engine = ContinuousBatchingEngine(
                self.bundle,
                max_seqs=knobs.get_int("HVT_SERVE_MAX_SEQS"),
                block_tokens=knobs.get_int("HVT_SERVE_BLOCK_TOKENS"),
                kv_blocks=knobs.get_int("HVT_SERVE_KV_BLOCKS"),
                queue_depth=knobs.get_int("HVT_SERVE_QUEUE_DEPTH"),
            )
            self._batcher = None
            return
        self.engine = None
        greedy = float(self.bundle.meta.get("temperature", 0.0)) == 0.0
        # The batcher's dispatches take the SAME lock the sampled and
        # streaming paths use, so the compiled programs never run
        # re-entrantly whatever mix of request kinds is in flight.
        self._batcher = (
            _Batcher(
                self._locked_generate_batch,
                self.bundle.batch_size,
                self.stats,
            )
            if (self._coalesce and greedy) else None
        )

    def reload(self, bundle_dir: str) -> None:
        """Swap weights in place: drain the engine (continuous) or hold
        the device lock (coalescing) while the new bundle loads. The
        fleet drains this replica at the ROUTER first, so by the time
        reload arrives nothing should be in flight — the engine drain
        here is the belt to that suspender."""
        from horovod_tpu.analysis import registry as knobs

        if self.engine is not None:
            timeout = knobs.get_float("HVT_SERVE_DRAIN_TIMEOUT_S")
            if not self.engine.drain(timeout):
                raise RuntimeError(
                    f"engine still busy after {timeout}s drain — refusing "
                    "to swap weights under live sequences"
                )
            self.engine.stop()
            self._load(bundle_dir)
            return
        with self._lock:
            # Coalescing path: the lock serializes against every
            # dispatch; requests queued behind it resume on new weights.
            self._load(bundle_dir)

    def _locked_generate_batch(self, rows: list) -> list:
        with self._lock:
            return self.bundle.generate_batch(rows)

    def _payload_prompts(self, payload: dict):
        if "text" in payload and "prompt" in payload:
            raise ValueError("pass 'text' OR 'prompt', not both")
        if "text" in payload:
            texts = payload["text"]
            if not isinstance(texts, list):
                raise ValueError("'text' must be a list of strings")
            if self.bundle.tokenizer is None:
                raise ValueError(
                    "this bundle has no tokenizer — POST token ids "
                    "under 'prompt' instead"
                )
            return [self.bundle.tokenizer.encode(t) for t in texts]
        return payload["prompt"]

    def stream(self, payload: dict):
        """NDJSON streaming: one ``{"tokens": [[...]]}`` line per chunk,
        then a final ``{"done": true, ...}`` line (with the detokenized
        text when the bundle carries a tokenizer). The device lock is
        taken PER DISPATCH — the carried state is self-contained, so
        while one stream's client drains a chunk over the network, other
        requests' device calls interleave instead of queueing behind a
        slow reader."""
        from horovod_tpu import trace as trace_lib

        seed = int(payload.get("seed", 0))
        # Validate BEFORE any slot/lock/submit: a request that can never
        # run must be rejected at the door, not after it holds device
        # capacity (the head-of-line accounting fix — previously the
        # first dispatch validated inside the device lock).
        prompts = self.bundle.validate_prompts(
            self._payload_prompts(payload)
        )
        if not prompts:
            raise ValueError("need at least one prompt")
        if self.engine is not None:
            yield from self._engine_stream(prompts)
            return
        if len(prompts) > self.bundle.batch_size:
            raise ValueError(
                f"streaming takes 1..{self.bundle.batch_size} prompts "
                f"per request, got {len(prompts)}"
            )
        rows = [[] for _ in prompts]
        it = self.bundle.stream_chunks(prompts, seed=seed)
        while True:
            t_q, p_q = time.time(), time.perf_counter()
            with self._lock:
                # Per-dispatch queue-wait/decode child spans: the
                # request span around the whole stream plus the FIRST
                # decode child's end is TTFT as span structure.
                trace_lib.emit_span(
                    "queue_wait", t_q, time.perf_counter() - p_q
                )
                try:
                    with trace_lib.span("decode", rows=len(prompts)):
                        chunk = next(it)
                except StopIteration:
                    break
                self.stats["device_calls"] += 1
            for i, part in enumerate(chunk):
                rows[i].extend(part)
            yield {"tokens": chunk}
        self.stats["rows"] += len(prompts)
        trimmed = [self.bundle._trim(np.asarray(r)) for r in rows]
        final = {"done": True, "tokens": trimmed}
        if self.bundle.tokenizer is not None:
            final["text"] = [
                self.bundle.tokenizer.decode(g) for g in trimmed
            ]
        yield final

    def _engine_stream(self, prompts: list):
        """Continuous streaming: each prompt row is its own scheduled
        sequence. Single-row requests keep the legacy NDJSON schema
        exactly; multi-row requests tag each chunk line with its
        ``row`` (rows finish independently under the scheduler, so
        chunks cannot be zipped across rows the way one compiled
        dispatch used to guarantee)."""
        reqs = [self.engine.submit(p, stream=True) for p in prompts]
        multi = len(reqs) > 1
        for i, r in enumerate(reqs):
            for piece in r.iter_chunks():
                line = {"tokens": [piece]}
                if multi:
                    line["row"] = i
                yield line
        self.stats["rows"] += len(prompts)
        trimmed = [r.tokens for r in reqs]
        final = {"done": True, "tokens": trimmed}
        if self.bundle.tokenizer is not None:
            final["text"] = [
                self.bundle.tokenizer.decode(g) for g in trimmed
            ]
        yield final

    def generate(self, payload: dict) -> dict:
        from horovod_tpu import trace as trace_lib

        seed = int(payload.get("seed", 0))
        # Tokenize and validate OUTSIDE the lock — only the compiled
        # call needs serializing through the device, and a request that
        # fails validation must be 400'd BEFORE it occupies a batch slot
        # or bumps the dispatch accounting (the head-of-line fix: the
        # sampled path used to count device_calls/rows and take the
        # device lock first, then discover the prompts were invalid).
        prompts = self.bundle.validate_prompts(
            self._payload_prompts(payload)
        )
        if self.engine is not None:
            # Continuous scheduling: every row an independent sequence;
            # the engine owns dispatch accounting and trace spans.
            reqs = [self.engine.submit(p) for p in prompts]
            tokens = [r.result() for r in reqs]
            self.stats["rows"] += len(prompts)
        elif self._batcher is not None:
            # Rows coalesce across requests (greedy: the seed is dead
            # code in the program). The batcher emits this request's
            # queue_wait/decode spans.
            tokens = self._batcher.submit(prompts) if prompts else []
        else:
            t_q, p_q = time.time(), time.perf_counter()
            with self._lock:
                # Lock wait IS the sampled path's queue: requests
                # serialize whole through the device here.
                trace_lib.emit_span(
                    "queue_wait", t_q, time.perf_counter() - p_q
                )
                self.stats["device_calls"] += max(
                    1, -(-len(prompts) // self.bundle.batch_size)
                )
                self.stats["rows"] += len(prompts)
                with trace_lib.span("decode", rows=len(prompts)):
                    tokens = self.bundle.generate_tokens(
                        prompts, seed=seed
                    )
        out = {"tokens": tokens}
        if self.bundle.tokenizer is not None:
            out["text"] = [self.bundle.tokenizer.decode(g) for g in tokens]
        return out


def _make_app(bundle_dir: str, coalesce: bool = True,
              continuous: bool = False):
    from horovod_tpu import serving

    if serving.is_generate_bundle(bundle_dir):
        return _GenerateApp(bundle_dir, coalesce=coalesce,
                            continuous=continuous)
    if continuous:
        raise ValueError(
            "continuous batching serves generation bundles only — "
            f"{bundle_dir} is a predict bundle"
        )
    return _ModelApp(bundle_dir, coalesce=coalesce)


def make_server(bundle_dir: str, port: int = 0, host: str = "127.0.0.1",
                coalesce: bool = True, fleet_journal: str | None = None,
                continuous: bool = False, allow_reload: bool = False):
    """Build (but don't start) the HTTP server; ``server.server_address``
    carries the bound port when ``port=0``. ``coalesce=False`` keeps the
    legacy serialize-whole-requests path;
    ``continuous=True`` routes /v1/generate through the per-decode-step
    scheduler (streaming bundles only; full admissions answer 429).
    ``allow_reload=True`` mounts ``POST /admin/reload`` (the fleet's
    zero-downtime weight-swap hook — opt-in, because it lets any client
    point the server at a new bundle path).

    ``fleet_journal``: path to a supervisor restart/rescale journal
    (``restarts.jsonl``); when given, ``GET /healthz`` grows a ``fleet``
    section — current generation/size, restart/shrink/grow counts, last
    events — read fresh per request (`supervisor.fleet_status`), so a
    health probe sees training-fleet trouble from the serving side.

    ``GET /metrics`` serves the Prometheus text exposition of this
    server's OWN registry (one private `obs.Registry` per server, so
    several servers in one process never share instruments): request
    counts by route/code, queue depth (sampled at scrape), device-call /
    row totals, request-latency and TTFT/TPOT histograms."""
    app = _make_app(bundle_dir, coalesce=coalesce, continuous=continuous)
    reg = obs_core.Registry()

    def _collect(r):
        # stats/queue are owned by the app; the scrape mirrors them.
        engine = getattr(app, "engine", None)
        if engine is not None:
            s = engine.stats()
            r.counter_set(
                "hvt_serve_device_calls_total", s["device_calls_total"]
            )
            r.counter_set("hvt_serve_rows_total", app.stats["rows"])
            r.counter_set("hvt_serve_admitted_total", s["admitted_total"])
            r.counter_set("hvt_serve_retired_total", s["retired_total"])
            r.counter_set("hvt_serve_rejected_total", s["rejected_total"])
            r.gauge("hvt_serve_live_seqs", s["live_seqs"])
            r.gauge("hvt_serve_queue_depth", s["queue_depth"])
            r.gauge("hvt_serve_kv_blocks_used", s["kv_blocks_used"])
            r.gauge("hvt_serve_kv_blocks_free", s["kv_blocks_free"])
            return
        r.counter_set(
            "hvt_serve_device_calls_total", app.stats["device_calls"]
        )
        r.counter_set("hvt_serve_rows_total", app.stats["rows"])
        batcher = getattr(app, "_batcher", None)
        r.gauge(
            "hvt_serve_queue_depth",
            batcher.q.qsize() if batcher is not None else 0,
        )

    reg.register_collector(_collect)

    # The `route` label must come from a CLOSED set: serve_forever binds
    # 0.0.0.0 by default, and labeling by the raw client-supplied path
    # would let any scanner mint unbounded (route, code) series — a
    # memory leak and scrape-payload blowup driven by untrusted input.
    _KNOWN_ROUTES = ("/healthz", "/metrics", "/v1/predict", "/v1/generate",
                     "/admin/reload")
    inflight = {"n": 0}
    inflight_lock = threading.Lock()

    def _route(path: str) -> str:
        path = path.split("?", 1)[0]
        return path if path in _KNOWN_ROUTES else "other"

    class Handler(BaseHTTPRequestHandler):
        def _send(self, code: int, payload: dict):
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            reg.counter(
                "hvt_serve_requests_total", route=_route(self.path),
                code=str(code),
            )

        def log_message(self, *args):  # quiet: one line per request is noise
            pass

        def do_GET(self):
            if self.path == "/metrics":
                obs_prom.write_http(self, reg)
            elif self.path == "/healthz":
                with inflight_lock:
                    n_inflight = inflight["n"]
                payload = {"status": "ok", "bundle": app.bundle_dir,
                           "kind": app.kind, "signature": app.signature,
                           "stats": dict(app.stats),
                           "inflight": n_inflight}
                engine = getattr(app, "engine", None)
                if engine is not None:
                    payload["scheduler"] = engine.stats()
                if fleet_journal is not None:
                    from horovod_tpu.launch.supervisor import fleet_status

                    payload["fleet"] = fleet_status(fleet_journal)
                self._send(200, payload)
            else:
                self._send(404, {"error": f"no route {self.path}"})

        def do_POST(self):
            if self.path == "/admin/reload":
                self._handle_reload()
                return
            route = (app.kind, self.path)
            if route not in (
                ("predict", "/v1/predict"), ("generate", "/v1/generate")
            ):
                hint = (
                    f"this server holds a {app.kind} bundle; its route is "
                    f"/v1/{app.kind}"
                )
                self._send(404, {"error": f"no route {self.path} — {hint}"})
                return
            # One `request` span per POST (HVT_TRACE_DIR runs): the app
            # layer nests queue_wait + decode children under it, so
            # `hvt-trace timeline` shows the serving tier's TTFT as span
            # structure (request start -> first decode child end), not
            # just histograms.
            from horovod_tpu import trace as trace_lib

            with inflight_lock:
                inflight["n"] += 1
            try:
                with trace_lib.span(
                    "request", req=next(_request_ids),
                    route=_route(self.path),
                ):
                    self._handle_post()
            finally:
                with inflight_lock:
                    inflight["n"] -= 1

        def _handle_reload(self):
            """The fleet's weight-swap hook: swap to a new bundle dir in
            place. Opt-in (``allow_reload``) and mutually journaled by
            the caller — the server itself only validates and swaps."""
            if not allow_reload:
                self._send(
                    404, {"error": "reload not enabled on this server "
                          "(--allow-reload)"}
                )
                return
            try:
                length = int(self.headers.get("Content-Length", 0))
                payload = json.loads(self.rfile.read(length))
                new_dir = payload["bundle_dir"]
                if not hasattr(app, "reload"):
                    raise ValueError(
                        f"{app.kind} bundles do not support reload"
                    )
                app.reload(new_dir)
                self._send(200, {"ok": True, "bundle": new_dir})
            except (KeyError, ValueError, TypeError) as e:
                self._send(400, {"error": str(e)})
            except Exception as e:
                self._send(500, {"error": f"{type(e).__name__}: {e}"})

        def _handle_post(self):
            t0 = time.perf_counter()
            try:
                length = int(self.headers.get("Content-Length", 0))
                payload = json.loads(self.rfile.read(length))
                if app.kind == "generate" and payload.get("stream"):
                    # NDJSON streaming: no Content-Length; the body is
                    # line-delimited JSON chunks, connection-close
                    # terminated (HTTP/1.0 semantics of this server).
                    chunks = app.stream(payload)
                    first = next(chunks)  # validation runs BEFORE headers
                    # TTFT: first chunk computed and about to flush —
                    # the streaming definition (prefill + first decode
                    # chunk); later chunks feed the TPOT tail below.
                    ttft = time.perf_counter() - t0
                    reg.histogram("hvt_serve_ttft_seconds", ttft)
                    n_tokens = 0
                    self.send_response(200)
                    self.send_header(
                        "Content-Type", "application/x-ndjson"
                    )
                    self.end_headers()
                    reg.counter(
                        "hvt_serve_requests_total", route=_route(self.path),
                        code="200",
                    )
                    try:
                        for item in itertools.chain((first,), chunks):
                            if "tokens" in item and not item.get("done"):
                                n_tokens += sum(
                                    len(r) for r in item["tokens"]
                                )
                            self.wfile.write(
                                json.dumps(item).encode() + b"\n"
                            )
                            self.wfile.flush()
                        total = time.perf_counter() - t0
                        reg.histogram(
                            "hvt_serve_request_seconds", total,
                            route=_route(self.path),
                        )
                        if n_tokens > 1:
                            # Decode tail per token, past the first chunk.
                            reg.histogram(
                                "hvt_serve_tpot_seconds",
                                (total - ttft) / max(1, n_tokens - 1),
                            )
                    except Exception as e:
                        # Headers are out — a second status line would
                        # corrupt the body. Keep the errors-are-JSON
                        # contract with an error NDJSON line; the missing
                        # 'done' line tells the client the stream died.
                        self.wfile.write(
                            json.dumps(
                                {"error": f"{type(e).__name__}: {e}"}
                            ).encode() + b"\n"
                        )
                        self.wfile.flush()
                elif app.kind == "generate":
                    out = app.generate(payload)
                    dt = time.perf_counter() - t0
                    reg.histogram(
                        "hvt_serve_request_seconds", dt, route=_route(self.path)
                    )
                    # One-shot generation is a single dispatch: prefill
                    # and every decode step land together, so TTFT is
                    # the whole call and TPOT its per-token amortization
                    # (documented approximation; streaming requests
                    # carry the real split).
                    n_tokens = sum(len(r) for r in out.get("tokens", []))
                    reg.histogram("hvt_serve_ttft_seconds", dt)
                    if n_tokens:
                        reg.histogram(
                            "hvt_serve_tpot_seconds", dt / n_tokens
                        )
                    self._send(200, out)
                else:
                    rows = np.asarray(payload["input"])
                    prob = app.predict(rows)
                    reg.histogram(
                        "hvt_serve_request_seconds",
                        time.perf_counter() - t0, route=_route(self.path),
                    )
                    self._send(200, {"prob": prob.tolist()})
            except (KeyError, ValueError, TypeError) as e:
                self._send(400, {"error": str(e)})
            except Exception as e:  # device/runtime failures -> 5xx JSON,
                # never a dropped socket (the module's errors-are-JSON
                # contract; XlaRuntimeError does not subclass ValueError).
                from horovod_tpu.serving import engine as engine_mod

                if isinstance(e, engine_mod.AdmissionError):
                    # Admission refused (wait queue full behind the paged
                    # KV budget) is back-pressure, not failure: 429 tells
                    # the client to retry later, and keeps the zero-500s
                    # CI gate honest about actual server faults.
                    self._send(429, {"error": str(e)})
                else:
                    self._send(500, {"error": f"{type(e).__name__}: {e}"})

    server = ThreadingHTTPServer((host, port), Handler)
    server.app = app  # tests reach the model through the server handle
    server.metrics_registry = reg  # tests + the --metrics-port exporter

    def _inflight_count() -> int:
        with inflight_lock:
            return inflight["n"]

    server.inflight_count = _inflight_count  # the SIGTERM drain barrier
    return server


def _join_fleet(coordinator: str, member: str, stop: threading.Event):
    """Replica-side membership: sync into the rendezvous coordinator,
    then heartbeat until told to stop. Returns the `ElasticClient` so
    the SIGTERM path can send an explicit, journaled `leave` (the fleet
    watchdog treats leave/dead as the drain trigger)."""
    from horovod_tpu.elastic.coordinator import ElasticClient

    client = ElasticClient(coordinator, member)

    def _beat_loop():
        try:
            client.sync()  # blocks until the rendezvous admits us
        except Exception:
            return  # coordinator gone before we joined — nothing to beat
        while not stop.wait(1.0):
            try:
                client.beat()
                if client.last_beat_pending:
                    # A new generation formed (peer joined/left) — re-sync
                    # so the coordinator's ledger keeps us 'live'.
                    client.sync()
            except Exception:
                return  # coordinator gone; the fleet owns that story
    threading.Thread(target=_beat_loop, daemon=True).start()
    return client


def serve_forever(bundle_dir: str, port: int = 8000, host: str = "0.0.0.0",
                  fleet_journal: str | None = None,
                  metrics_port: int | None = None,
                  continuous: bool = False, allow_reload: bool = False,
                  coordinator: str | None = None,
                  member: str | None = None):
    import signal

    from horovod_tpu.analysis import registry as knobs

    server = make_server(bundle_dir, port=port, host=host,
                         fleet_journal=fleet_journal,
                         continuous=continuous, allow_reload=allow_reload)
    if metrics_port is not None:
        # The same per-server registry on a dedicated scrape port, for
        # deployments that keep the serving port client-facing and the
        # metrics port on the ops network (`/metrics` stays mounted on
        # the main port either way).
        from horovod_tpu.obs import server as obs_server

        obs_server.start_metrics_server(
            metrics_port, registry=server.metrics_registry
        )
    stop_beats = threading.Event()
    client = (
        _join_fleet(coordinator, member or f"serve-{port}", stop_beats)
        if coordinator else None
    )

    def _graceful(_signum, _frame):
        """SIGTERM = drain-then-exit: announce departure to the
        coordinator FIRST (the router stops dispatching here), finish
        what is already in flight, then stop accepting. Runs the
        shutdown from a helper thread — signal handlers run on the main
        thread, which is inside serve_forever()."""
        def _drain_and_stop():
            stop_beats.set()
            if client is not None:
                try:
                    client.leave()
                except Exception:
                    pass
            deadline = time.monotonic() + knobs.get_float(
                "HVT_SERVE_DRAIN_TIMEOUT_S"
            )
            while server.inflight_count() and time.monotonic() < deadline:
                time.sleep(0.05)
            engine = getattr(server.app, "engine", None)
            if engine is not None:
                engine.drain(max(0.0, deadline - time.monotonic()))
                engine.stop()
            server.shutdown()
        threading.Thread(target=_drain_and_stop, daemon=True).start()

    signal.signal(signal.SIGTERM, _graceful)
    inputs = server.app.signature["inputs"]
    shape = next(iter(inputs.values()))["shape"]
    print(
        f"serving {bundle_dir} ({server.app.kind}) on "
        f"http://{host}:{server.server_address[1]} (input {shape})"
        + (" [continuous]" if continuous else ""),
        flush=True,
    )
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        server.shutdown()
    finally:
        stop_beats.set()


def main(argv=None) -> None:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "bundle_dir",
        help="a serving bundle dir: checkpoint.export_serving (predict) "
        "or serving.export_generate (generation) — kind auto-detected",
    )
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument(
        "--fleet-journal", default=None, metavar="PATH",
        help="supervisor restart/rescale journal (restarts.jsonl); adds a "
        "'fleet' section to GET /healthz — generation, size, "
        "restart/shrink/grow counts, recent events",
    )
    p.add_argument(
        "--metrics-port", type=int, default=None, metavar="N",
        help="ALSO serve this server's Prometheus /metrics on a "
        "dedicated port (loopback by default, HVT_STATUS_HOST to "
        "expose); GET /metrics on the main port works regardless",
    )
    p.add_argument(
        "--continuous", action="store_true",
        help="per-decode-step continuous batching (streaming generation "
        "bundles only): admit/evict at every decode chunk, paged-KV "
        "admission control, 429 on exhaustion",
    )
    p.add_argument(
        "--allow-reload", action="store_true",
        help="mount POST /admin/reload (zero-downtime weight swap; the "
        "fleet drives it during `hvt-launch serve` swaps)",
    )
    p.add_argument(
        "--coordinator", default=None, metavar="HOST:PORT",
        help="rendezvous coordinator address: join the serving fleet as "
        "a member (heartbeats + journaled leave on SIGTERM)",
    )
    p.add_argument(
        "--member", default=None, metavar="NAME",
        help="member name to present to the coordinator "
        "(default serve-<port>)",
    )
    args = p.parse_args(argv)
    serve_forever(args.bundle_dir, port=args.port, host=args.host,
                  fleet_journal=args.fleet_journal,
                  metrics_port=args.metrics_port,
                  continuous=args.continuous,
                  allow_reload=args.allow_reload,
                  coordinator=args.coordinator, member=args.member)


if __name__ == "__main__":
    main()
