"""`ServeFleet`: the elastic replica tier behind one router address.

The same fleet discipline PR 2 built for training — a rendezvous
coordinator owning membership truth, a journal owning history — applied
to inference:

* N replica processes (`python -m horovod_tpu.launch.serve`, continuous
  engine on), each a coordinator MEMBER: sync once at boot, TCP beats
  while serving, a clean ``leave`` on SIGTERM (so the journal tells a
  drain from a crash);
* the front-end router (`serving.router`) owns per-replica in-flight
  accounting; a watchdog reconciles it against the coordinator — a
  member that left or went stale is drained from rotation before its
  socket starts refusing;
* **zero-downtime weight swap** (`swap`): per replica, journaled —
  ``swap_drain`` (stop dispatching, wait in-flight → 0) → POST
  ``/admin/reload`` with the new bundle (checkpoint-sidecar export) →
  readiness probe → ``swap_readmit``. One replica swaps at a time; the
  rest carry the traffic. No request ever lands on a replica mid-swap;
* **autoscale hooks**: with ``HVT_SERVE_AUTOSCALE=dry-run|on`` a poll
  thread feeds the router's own TTFT histogram to
  `launch.policy.ServeAutoscaler` (the PR 16 policy-engine shape:
  freshness-gated, streak + cooldown, every decision journaled as
  ``policy_scale_up``/``policy_scale_down``) and, in ``on`` mode,
  actually spawns/retires replicas.

On `stop()` the router registry is dumped to ``metrics.prom`` beside the
journal (`supervisor.default_metrics_dump_path`), which is what
`launch.job`'s ``metrics_checks:`` gates read — the serve-2replica CI
job asserts TTFT-histogram presence and a zero ``code="500"`` count
from exactly this dump.

CLI (the CI acceptance job's entry): ``python -m horovod_tpu.serving.fleet
--demo --replicas 2 --requests 40 --swap --journal <path>`` self-exports
a tiny streaming bundle, serves it with 2 replicas, drives mid-traffic
load through the router, swaps weights under that load, and exits 0 only
if every request succeeded.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

from horovod_tpu.analysis import registry as knob_registry
from horovod_tpu.obs import prom as obs_prom
from horovod_tpu.serving import router as router_mod


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http_json(url: str, payload: dict | None = None, timeout: float = 10.0):
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(
        url, data=data,
        headers={"Content-Type": "application/json"} if data else {},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


class _ReplicaProc:
    __slots__ = ("name", "port", "proc")

    def __init__(self, name: str, port: int, proc: subprocess.Popen):
        self.name = name
        self.port = port
        self.proc = proc

    @property
    def base_url(self) -> str:
        return f"http://127.0.0.1:{self.port}"


class ServeFleet:
    """Coordinator + router + N replica subprocesses, one handle.

    ``log_path``: the restart-journal path (None journals nowhere);
    ``continuous=False`` runs the legacy coalescing replicas.
    ``ready_timeout`` bounds each replica's boot (bundle
    deserialization + first jit can dominate).
    """

    def __init__(self, bundle_dir: str, *, replicas: int = 2,
                 router_port: int = 0, router_host: str = "127.0.0.1",
                 log_path: str | None = None, continuous: bool = True,
                 ready_timeout: float = 120.0, env: dict | None = None):
        from horovod_tpu.elastic.coordinator import Coordinator
        from horovod_tpu.launch.supervisor import RestartLog

        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        self.bundle_dir = bundle_dir
        self.n_replicas = replicas
        self.continuous = continuous
        self.ready_timeout = ready_timeout
        self.env = dict(env or os.environ)
        self.log = RestartLog(log_path)
        self.log.touch()
        self.coord = Coordinator(
            port=0, min_ranks=1, expected=replicas,
            heartbeat_window=10.0, journal=self.log.write,
        ).start()
        self.router = router_mod.make_router(
            port=router_port, host=router_host
        )
        self._router_thread = threading.Thread(
            target=self.router.serve_forever, daemon=True
        )
        self._router_thread.start()
        self.replicas: dict[str, _ReplicaProc] = {}
        self._next_replica = 0
        self._lock = threading.Lock()
        self._stopping = False
        self._watchdog = None
        self._autoscale_thread = None
        self.drain_timeout = knob_registry.get_float(
            "HVT_SERVE_DRAIN_TIMEOUT_S"
        )
        self.swap_timeout = knob_registry.get_float(
            "HVT_SERVE_SWAP_TIMEOUT_S"
        )

    # -- lifecycle --------------------------------------------------------

    @property
    def router_url(self) -> str:
        host, port = self.router.server_address[:2]
        return f"http://{host}:{port}"

    def start(self) -> "ServeFleet":
        self.log.write("serve_start", self.n_replicas,
                       bundle=self.bundle_dir,
                       mode="continuous" if self.continuous else "coalesce")
        for _ in range(self.n_replicas):
            self._spawn_replica()
        self._watchdog = threading.Thread(
            target=self._watch, daemon=True, name="hvt-serve-watchdog"
        )
        self._watchdog.start()
        mode = knob_registry.get_str("HVT_SERVE_AUTOSCALE") or "off"
        if mode != "off":
            self._autoscale_thread = threading.Thread(
                target=self._autoscale_loop, args=(mode,), daemon=True,
                name="hvt-serve-autoscale",
            )
            self._autoscale_thread.start()
        return self

    def _spawn_replica(self) -> str:
        with self._lock:
            name = f"serve-{self._next_replica}"
            self._next_replica += 1
        port = _free_port()
        cmd = [
            sys.executable, "-m", "horovod_tpu.launch.serve",
            self.bundle_dir, "--port", str(port), "--host", "127.0.0.1",
            "--coordinator", self.coord.address, "--member", name,
            "--allow-reload",
        ]
        if self.continuous:
            cmd.append("--continuous")
        proc = subprocess.Popen(cmd, env=self.env)
        rp = _ReplicaProc(name, port, proc)
        with self._lock:
            self.replicas[name] = rp
        self._wait_ready(rp)
        self.router.replicas.add(name, rp.base_url)
        self.log.write("serve_replica_up", port, member=name)
        return name

    def _wait_ready(self, rp: _ReplicaProc) -> None:
        deadline = time.monotonic() + self.ready_timeout
        while time.monotonic() < deadline:
            if rp.proc.poll() is not None:
                raise RuntimeError(
                    f"replica {rp.name} exited rc={rp.proc.returncode} "
                    "during boot"
                )
            try:
                _http_json(rp.base_url + "/healthz", timeout=2.0)
                return
            except (OSError, urllib.error.URLError):
                time.sleep(0.1)
        raise TimeoutError(
            f"replica {rp.name} not serving after {self.ready_timeout}s"
        )

    def _watch(self) -> None:
        """Reconcile the router against coordinator truth + child exits:
        a member that left cleanly, went heartbeat-stale, or whose
        process died is drained from rotation and journaled."""
        while not self._stopping:
            time.sleep(0.25)
            if self._stopping:
                return
            for stale in self.coord.stale_members(10.0):
                self.coord.mark_dead(stale, reason="beat-stale")
            with self._lock:
                known = dict(self.replicas)
            for name, rp in known.items():
                gone = rp.proc.poll() is not None
                # "unknown" = hasn't synced yet (still booting) — only a
                # member the coordinator has SEEN depart counts as left.
                left = self.coord.member_status(name)[0] in (
                    "left", "dead"
                )
                if gone or left:
                    self.router.replicas.drain(name)
                    self.router.replicas.wait_drained(
                        name, self.drain_timeout
                    )
                    self.router.replicas.remove(name)
                    with self._lock:
                        self.replicas.pop(name, None)
                    self.log.write(
                        "serve_replica_down", rp.port, member=name,
                        reason="exit" if gone else "leave",
                    )

    # -- weight swap ------------------------------------------------------

    def swap(self, new_bundle_dir: str) -> bool:
        """Zero-downtime weight swap: drain → reload → readmit, one
        replica at a time, each step journaled. Returns False (and
        readmits on the OLD weights) if any replica fails its step —
        never leaves a replica out of rotation."""
        ok = True
        for name in list(self.router.replicas.names()):
            rp = self.replicas.get(name)
            if rp is None:
                continue
            self.log.write("swap_drain", rp.port, member=name,
                           bundle=new_bundle_dir)
            self.router.replicas.drain(name)
            drained = self.router.replicas.wait_drained(
                name, self.drain_timeout
            )
            if not drained:
                self.log.write("swap_abort", rp.port, member=name,
                               reason="drain-timeout")
                self.router.replicas.readmit(name)
                ok = False
                continue
            try:
                _http_json(
                    rp.base_url + "/admin/reload",
                    {"bundle_dir": new_bundle_dir},
                    timeout=self.swap_timeout,
                )
                _http_json(rp.base_url + "/healthz", timeout=10.0)
            except Exception as e:
                self.log.write("swap_abort", rp.port, member=name,
                               reason=f"{type(e).__name__}: {e}")
                self.router.replicas.readmit(name)  # old weights, but up
                ok = False
                continue
            self.router.replicas.readmit(name)
            self.log.write("swap_readmit", rp.port, member=name,
                           bundle=new_bundle_dir)
        if ok:
            self.bundle_dir = new_bundle_dir
            self.router.metrics_registry.counter("hvt_serve_swaps_total")
            self.log.write("swap", len(self.replicas),
                           bundle=new_bundle_dir)
        return ok

    # -- autoscale --------------------------------------------------------

    def scale_up(self) -> str | None:
        """Autoscaler actuator: one more replica (bounded by 2x the
        configured fleet so a runaway signal can't fork-bomb the host)."""
        with self._lock:
            if len(self.replicas) >= 2 * self.n_replicas:
                return None
        return self._spawn_replica()

    def scale_down(self) -> str | None:
        """Autoscaler actuator: drain + SIGTERM the newest replica
        (never below one)."""
        with self._lock:
            if len(self.replicas) <= 1:
                return None
            name = sorted(self.replicas)[-1]
            rp = self.replicas[name]
        self.router.replicas.drain(name)
        self.router.replicas.wait_drained(name, self.drain_timeout)
        rp.proc.send_signal(signal.SIGTERM)
        return name

    def _autoscale_loop(self, mode: str) -> None:
        from horovod_tpu.launch.policy import ServeAutoscaler

        scaler = ServeAutoscaler()
        while not self._stopping:
            time.sleep(1.0)
            if self._stopping:
                return
            series = obs_prom.parse_text(
                obs_prom.render(self.router.metrics_registry)
            )
            action = scaler.observe(series)
            if action is None:
                continue
            if mode == "dry-run":
                self.log.write(f"policy_scale_{action}", 0,
                               action=f"scale_{action}", outcome="dry-run")
                continue
            moved = (
                self.scale_up() if action == "up" else self.scale_down()
            )
            self.log.write(
                f"policy_scale_{action}", 1 if moved else 0,
                action=f"scale_{action}",
                outcome=moved or ("at-max" if action == "up" else "at-min"),
            )

    # -- shutdown ---------------------------------------------------------

    def stop(self) -> None:
        self._stopping = True
        with self._lock:
            procs = list(self.replicas.values())
        for rp in procs:
            if rp.proc.poll() is None:
                rp.proc.send_signal(signal.SIGTERM)
        for rp in procs:
            try:
                rp.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                rp.proc.kill()
                rp.proc.wait(timeout=10)
        self.router.shutdown()
        self.coord.stop()
        self.log.write("serve_stop", len(procs))
        self._dump_metrics()

    def _dump_metrics(self) -> None:
        from horovod_tpu.checkpoint import _atomic_write
        from horovod_tpu.launch.supervisor import default_metrics_dump_path

        path = default_metrics_dump_path(None, self.log.path)
        if path is None:
            return
        try:
            _atomic_write(
                path,
                obs_prom.render(self.router.metrics_registry).encode(),
            )
        except OSError:
            pass  # best-effort, like the supervisor's dump


# -- CLI / demo harness ----------------------------------------------------


def _export_demo_bundle(out_dir: str, seed: int = 0) -> str:
    """A tiny greedy streaming LM bundle — the CI job's self-contained
    model (no checkpoint needed in the container)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import serving
    from horovod_tpu.models.transformer import TransformerLM

    model = TransformerLM(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, dropout=0.0
    )
    params = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((4, 8), jnp.int32)
    )["params"]
    return serving.export_generate(
        out_dir, model, params, batch_size=4, prompt_len=8,
        max_new_tokens=8, streaming_chunk=2,
        timestamp=f"demo-{seed}",
    )


def _drive_load(router_url: str, n_requests: int, n_threads: int = 4):
    """Closed-loop smoke traffic: every request must succeed. Returns
    (ok_count, fail_count, failures)."""
    results: list[tuple[bool, str]] = []
    lock = threading.Lock()
    idx = iter(range(n_requests))

    def worker():
        while True:
            with lock:
                i = next(idx, None)
            if i is None:
                return
            prompt = [1 + (i + j) % 60 for j in range(1 + i % 6)]
            stream = i % 2 == 0
            try:
                if stream:
                    req = urllib.request.Request(
                        router_url + "/v1/generate",
                        data=json.dumps(
                            {"prompt": [prompt], "stream": True}
                        ).encode(),
                        headers={"Content-Type": "application/json"},
                    )
                    with urllib.request.urlopen(req, timeout=60) as resp:
                        last = None
                        for line in resp:
                            last = json.loads(line)
                    okay = bool(last and last.get("done"))
                    detail = "" if okay else f"no done line: {last}"
                else:
                    out = _http_json(
                        router_url + "/v1/generate",
                        {"prompt": [prompt]}, timeout=60,
                    )
                    okay = bool(out.get("tokens"))
                    detail = "" if okay else f"empty tokens: {out}"
            except Exception as e:
                okay, detail = False, f"{type(e).__name__}: {e}"
            with lock:
                results.append((okay, detail))

    threads = [
        threading.Thread(target=worker, daemon=True)
        for _ in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    fails = [d for ok, d in results if not ok]
    return len(results) - len(fails), len(fails), fails


def main(argv=None) -> int:
    import argparse
    import tempfile

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("bundle_dir", nargs="?", default=None,
                   help="generation bundle to serve (omit with --demo)")
    p.add_argument("--replicas", type=int,
                   default=knob_registry.get_int("HVT_SERVE_REPLICAS"))
    p.add_argument("--port", type=int, default=0,
                   help="router port (0 = ephemeral, printed at boot)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--journal", default=None, metavar="PATH",
                   help="restart-journal path (membership + swap events; "
                   "metrics.prom lands beside it at stop)")
    p.add_argument("--coalesce", action="store_true",
                   help="legacy coalescing replicas "
                   "instead of the continuous engine")
    p.add_argument("--demo", action="store_true",
                   help="self-export a tiny streaming bundle and serve it "
                   "(the CI acceptance job)")
    p.add_argument("--requests", type=int, default=0, metavar="N",
                   help="drive N smoke requests through the router, then "
                   "stop; exit 1 unless ALL succeed")
    p.add_argument("--swap", action="store_true",
                   help="with --requests: re-export the demo bundle and "
                   "zero-downtime swap it in mid-traffic")
    args = p.parse_args(argv)

    tmp = None
    if args.demo:
        tmp = tempfile.mkdtemp(prefix="hvt-serve-demo-")
        bundle = _export_demo_bundle(tmp, seed=0)
    elif args.bundle_dir:
        bundle = args.bundle_dir
    else:
        p.error("pass a bundle_dir or --demo")

    fleet = ServeFleet(
        bundle, replicas=args.replicas, router_port=args.port,
        router_host=args.host, log_path=args.journal,
        continuous=not args.coalesce,
    ).start()
    print(f"router on {fleet.router_url} "
          f"({args.replicas} replicas)", flush=True)

    if not args.requests:
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            pass
        finally:
            fleet.stop()
        return 0

    swap_result = None
    try:
        half = args.requests // 2
        ok1, fail1, fails1 = _drive_load(fleet.router_url, half)
        if args.swap:
            # Swap under live traffic: keep load flowing in the
            # background while the fleet drains/reloads one replica at
            # a time — the zero-downtime claim under test.
            bg: dict = {}

            def bg_load():
                bg["out"] = _drive_load(
                    fleet.router_url, args.requests - half
                )

            t = threading.Thread(target=bg_load)
            t.start()
            swap_result = fleet.swap(
                _export_demo_bundle(tmp, seed=1) if args.demo
                else bundle
            )
            t.join()
            ok2, fail2, fails2 = bg["out"]
        else:
            ok2, fail2, fails2 = _drive_load(
                fleet.router_url, args.requests - half
            )
    finally:
        fleet.stop()
        if tmp is not None:
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)
    report = {
        "requests": args.requests, "ok": ok1 + ok2,
        "failed": fail1 + fail2, "swap": swap_result,
        "failures": (fails1 + fails2)[:5],
    }
    print(json.dumps(report), flush=True)
    if fail1 + fail2 or (args.swap and swap_result is not True):
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
