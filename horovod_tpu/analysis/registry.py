"""Central registry of every ``HVT_*`` environment knob.

The reliability spine (PRs 1-5) grew ~30 env knobs whose names, types and
defaults lived only at their scattered read sites — drift in BOTH
directions (a knob read but documented nowhere; a knob documented but no
longer read) was unobservable. This module is the single source of truth:

* every knob is declared here with type, default, owning subsystem and a
  one-line description;
* code reads knobs through the typed accessors (`get_raw`/`get_str`/
  `get_int`/`get_float`/`get_flag`), which refuse undeclared names — so a
  new knob cannot ship without a registry row;
* the `hvt-lint` rule HVT004 (`analysis/rules.py`) statically rejects any
  ``HVT_*`` string literal in the package that is not declared here, and
  any inline ``os.environ`` read that bypasses the accessors;
* ``docs/ENVVARS.md`` is GENERATED from this table (`generate_doc`;
  ``python -m horovod_tpu.analysis.registry`` prints it) and a tier-1
  test asserts regeneration produces no diff.

Value contract, uniform across every accessor: an UNSET variable and a
variable set to the EMPTY STRING are both "unset" (the registered default
applies). Boolean knobs follow `runtime.env_flag`'s spelling contract:
unset/''/'0'/'false'/'no' (case-insensitive) are off, anything else is on
— that contract is implemented here (`flag_like`) and `runtime.env_flag`
delegates to it, so the accepted spellings cannot drift.

Deliberately dependency-free (stdlib only): the ``hvt-lint`` CLI and the
earliest bootstrap code (`runtime.init`, before any backend exists) both
import this module.
"""

from __future__ import annotations

import dataclasses
import os

__all__ = [
    "Knob", "Tunable", "KNOBS", "UnknownKnobError", "knob", "is_registered",
    "tunable_knobs", "get_raw", "get_str", "get_int", "get_float",
    "get_flag", "flag_like", "generate_doc",
]


@dataclasses.dataclass(frozen=True)
class Tunable:
    """Machine-readable search domain for a knob the autotuner may set.

    `hvt-tune` enumerates its candidate space from these rows — a knob
    without a `Tunable` is invisible to the tuner by construction, and
    rule HVT012 rejects raw env reads of any knob that carries one (a
    read the registry resolver doesn't mediate is a value the tuner
    cannot override).

    kind:
      * ``int``    — integer range [lo, hi]; ``scale`` says how to walk
        it: ``log`` enumerates powers of two, ``linear`` every value.
      * ``choice`` — explicit value set (``choices``).
      * ``flag``   — boolean; candidates are off/on.
    """

    kind: str                      # "int" | "choice" | "flag"
    lo: int | None = None          # int kind: inclusive bounds
    hi: int | None = None
    scale: str = "linear"          # int kind: "log" | "linear"
    choices: tuple = ()            # choice kind: the value set

    def __post_init__(self):
        if self.kind not in ("int", "choice", "flag"):
            raise ValueError(f"unknown tunable kind {self.kind!r}")
        if self.kind == "int":
            if self.lo is None or self.hi is None or self.lo > self.hi:
                raise ValueError(f"int tunable needs lo <= hi, got "
                                 f"[{self.lo}, {self.hi}]")
            if self.scale not in ("log", "linear"):
                raise ValueError(f"unknown tunable scale {self.scale!r}")
        if self.kind == "choice" and not self.choices:
            raise ValueError("choice tunable needs a non-empty choice set")

    def values(self) -> tuple:
        """The concrete candidate values the tuner enumerates."""
        if self.kind == "flag":
            return (False, True)
        if self.kind == "choice":
            return tuple(self.choices)
        if self.scale == "log":
            out, v = [], 1
            while v < self.lo:
                v *= 2
            while v <= self.hi:
                out.append(v)
                v *= 2
            if not out:
                out = [self.lo]
            return tuple(out)
        return tuple(range(self.lo, self.hi + 1))

    def domain_str(self) -> str:
        """Human-readable domain for generated docs and reports."""
        if self.kind == "flag":
            return "off/on"
        if self.kind == "choice":
            return "/".join(str(c) for c in self.choices)
        return f"[{self.lo}, {self.hi}] ({self.scale})"


@dataclasses.dataclass(frozen=True)
class Knob:
    """One declared environment knob."""

    name: str
    type: str          # "str" | "int" | "float" | "flag" | "path" | "spec"
    default: object    # the value accessors return when unset ('' == unset)
    subsystem: str     # owning layer (the ENVVARS.md grouping)
    description: str
    tunable: Tunable | None = None   # autotuner search domain (hvt-tune)


_SUBSYSTEM_ORDER = (
    "runtime", "parallel", "training", "checkpoint", "elastic",
    "launch", "serving", "data", "observability", "testing", "examples",
)


def _decl(knobs: list[Knob]) -> dict[str, Knob]:
    table: dict[str, Knob] = {}
    for k in knobs:
        if k.name in table:
            raise ValueError(f"duplicate knob declaration {k.name}")
        if k.subsystem not in _SUBSYSTEM_ORDER:
            raise ValueError(
                f"{k.name}: unknown subsystem {k.subsystem!r} — add it to "
                "_SUBSYSTEM_ORDER so ENVVARS.md ordering stays deterministic"
            )
        table[k.name] = k
    return table


KNOBS: dict[str, Knob] = _decl([
    # --- runtime bootstrap (runtime.init) ----------------------------------
    Knob("HVT_COORDINATOR_ADDRESS", "str", None, "runtime",
         "jax.distributed coordinator `host:port`; unset = single-process "
         "(every collective degrades to a local op)."),
    Knob("HVT_NUM_PROCESSES", "int", None, "runtime",
         "Process count of the static (non-elastic) world."),
    Knob("HVT_PROCESS_ID", "int", None, "runtime",
         "This process's rank in the static world."),
    Knob("HVT_LOCAL_RANK", "int", 0, "runtime",
         "Ordinal among co-located processes on one host (launcher-set)."),
    Knob("HVT_PLATFORM", "str", None, "runtime",
         "jax platform for this process (e.g. `cpu`), applied by "
         "`init()` before backend init — what a launcher hands its "
         "CPU-mesh children; same effect as `JAX_PLATFORMS`."),
    Knob("HVT_NUM_CPU_DEVICES", "int", None, "runtime",
         "Virtual CPU device count for launched children, applied by "
         "`init()` as `jax_num_cpu_devices` (wins over an inherited "
         "XLA_FLAGS device count)."),
    Knob("HVT_FAST_RNG", "flag", False, "runtime",
         "Use the TPU hardware RNG (`rbg`) instead of threefry: faster "
         "dropout, not bit-reproducible across topologies."),
    # --- parallel / mesh ---------------------------------------------------
    Knob("HVT_MESH", "spec", None, "parallel",
         "Mesh axis sizes, `axis=size` pairs (`data=2,seq=4`); "
         "unset/empty = pure data parallelism (`MeshSpec.from_string`)."),
    Knob("HVT_MESH_ORDER", "str", "auto", "parallel",
         "Physical device layout: `auto` (ICI-torus-aware mesh_utils) or "
         "`flat` (enumeration-order reshape)."),
    Knob("HVT_DCN_FACTOR", "int", None, "parallel",
         "Override the derived multi-slice factor of the data axis — the "
         "fake-topology knob for the ICI/DCN two-hop reduction; must "
         "divide the axis size."),
    Knob("HVT_BUCKET_BYTES", "int", None, "parallel",
         "Gradient-fusion bucket cap in bytes for the explicit-collective "
         "boundary reduction (default: collectives.DEFAULT_BUCKET_BYTES, "
         "64 MB — Horovod's fusion threshold).",
         tunable=Tunable("int", lo=1 << 18, hi=1 << 28, scale="log")),
    Knob("HVT_OVERLAP_REDUCTION", "flag", True, "parallel",
         "Overlap the boundary reduction with the backward: peel the last "
         "microbatch out of the accumulation scan so bucket-wise "
         "reductions issue inside the same schedulable region as its "
         "backward (async start/done overlap on TPU). Off = serialize "
         "the reduction after the scan (identical arithmetic).",
         tunable=Tunable("flag")),
    Knob("HVT_BUCKET_ORDER", "str", "reverse", "parallel",
         "Boundary-reduction bucket issue order: `reverse` (last-produced "
         "gradients reduce first — Horovod's fusion order, overlappable "
         "with the backward) or `forward` (pytree order)."),
    # --- training ----------------------------------------------------------
    Knob("HVT_SAVE_EVERY_STEPS", "int", 0, "training",
         "ModelCheckpoint mid-epoch save cadence in optimizer steps "
         "(0 = epoch cadence only). Single-file checkpoints only."),
    Knob("HVT_EPOCH_CHUNK_STEPS", "int", 0, "training",
         "fit(cache='device'): split each on-device epoch into compiled "
         "chunks of this many optimizer steps (0 = whole-epoch program), "
         "so on_batch_end fires per chunk and sub-epoch commit/rescale "
         "cadences work on the device-cached path too."),
    # --- elastic -----------------------------------------------------------
    Knob("HVT_ELASTIC_COORDINATOR", "str", None, "elastic",
         "Rendezvous coordinator `host:port` (supervisor-set); presence "
         "switches faults and entry scripts into elastic mode."),
    Knob("HVT_ELASTIC_MEMBER", "str", None, "elastic",
         "This process's stable elastic member identity (supervisor-set)."),
    Knob("HVT_COMMIT_EVERY", "int", 1, "elastic",
         "Elastic commit cadence in epochs (ElasticStateCallback default; "
         "job-spec `elastic: {commit_every}` travels as this)."),
    Knob("HVT_COMMIT_EVERY_STEPS", "int", 0, "elastic",
         "Additional sub-epoch commit cadence in optimizer steps "
         "(0 = epoch cadence only)."),
    Knob("HVT_RESCALE_EVERY_STEPS", "int", 0, "elastic",
         "Sub-epoch membership-agreement cadence in optimizer steps "
         "(0 = epoch boundaries only)."),
    Knob("HVT_ELASTIC_SPARE", "flag", False, "elastic",
         "Member-side warm-standby parking (supervisor-set when spares "
         "are configured): a 'world is full' rendezvous rejection makes "
         "the client wait and re-knock instead of failing, so spare "
         "processes stay parked until an eviction frees a slot."),
    # --- launch / supervision ----------------------------------------------
    Knob("HVT_HEARTBEAT_DIR", "path", None, "launch",
         "Per-rank liveness dir (supervisor-set); fit() auto-installs "
         "HeartbeatCallback when present."),
    Knob("HVT_RESTART_LOG_MAX_LINES", "int", 100000, "launch",
         "Restart-journal rotation bound in lines (0 disables)."),
    Knob("HVT_RESTART_LOG_MAX_MB", "float", 64.0, "launch",
         "Restart-journal rotation bound in MB (0 disables)."),
    Knob("HVT_STATUS_HOST", "str", "127.0.0.1", "launch",
         "Bind host for the supervisor status endpoint (`--status-port`); "
         "loopback by default — set 0.0.0.0 to expose off-host."),
    Knob("HVT_POLICY", "str", "off", "launch",
         "Supervisor policy engine mode: off | dry-run | on. dry-run "
         "journals every decision (policy_* events) without acting; on "
         "closes the observe->act loop (straggler evict-and-shrink, "
         "hot-spare promotion, hang auto-triage)."),
    Knob("HVT_POLICY_STRAGGLER_WINDOWS", "int", 3, "launch",
         "Consecutive fresh metric windows a majority-named straggler "
         "must persist before the policy engine evicts it."),
    Knob("HVT_POLICY_STRAGGLER_WAIT_MS", "float", 100.0, "launch",
         "Minimum peak hvt_barrier_wait_ms across the fleet for a "
         "straggler window to count toward eviction."),
    Knob("HVT_POLICY_EVICT_BUDGET", "int", 1, "launch",
         "Policy-initiated evictions allowed per supervised run "
         "(separate from the restart budget)."),
    Knob("HVT_POLICY_COOLDOWN_S", "float", 60.0, "launch",
         "Minimum seconds between policy actions (eviction cooldown)."),
    Knob("HVT_POLICY_SPARES", "int", 0, "launch",
         "Warm standby processes the elastic supervisor keeps parked at "
         "rendezvous; an eviction frees a slot and a spare joins the "
         "next generation so world size is preserved."),
    Knob("HVT_FLEET_TICK_S", "float", 0.5, "launch",
         "hvt-launch fleet scheduler cadence in seconds (reap exits, "
         "scrape job controller ledgers, place/preempt/regrow)."),
    Knob("HVT_FLEET_QUARANTINE_S", "float", 60.0, "launch",
         "Cooldown before a host declared lost (all co-resident ranks "
         "died together) returns to the fleet scheduler's pool."),
    Knob("HVT_FLEET_HOST", "str", None, "launch",
         "The pool host this rank was placed on (fleetd-set via the "
         "member env) — host identity for host-loss classification and "
         "the hostdown fault's blast radius."),
    Knob("HVT_TUNE_EVIDENCE", "path", None, "launch",
         "Evidence directory for the `hvt-tune` offline model (BENCH_* "
         "rows, trace spans); unset = the working directory. The job "
         "spec `tune: {evidence}` key travels as this."),
    Knob("HVT_TUNE_STEPS", "int", 3, "launch",
         "In-situ probe: real optimizer steps per timed leg when "
         "`hvt-tune probe` A/B-races candidate configs at job start."),
    Knob("HVT_TUNE_CANDIDATES", "int", 3, "launch",
         "In-situ probe shortlist size: the offline model ranks the "
         "candidate space and only the top N race real steps."),
    # --- serving (continuous batching engine + replica fleet) ---------------
    Knob("HVT_SERVE_MAX_SEQS", "int", 0, "serving",
         "Continuous batching: max concurrently scheduled sequences per "
         "replica (decode slots). 0 = the bundle's compiled batch size; "
         "values above it clamp to the compiled shape."),
    Knob("HVT_SERVE_BLOCK_TOKENS", "int", 16, "serving",
         "Paged-KV block granularity in tokens: admission reserves "
         "ceil((prompt+max_new)/block) blocks for a sequence's whole "
         "lifetime, so a running sequence can never hit OOM mid-decode."),
    Knob("HVT_SERVE_KV_BLOCKS", "int", 0, "serving",
         "Total paged-KV blocks in the admission budget. 0 = auto-size "
         "to max_seqs full-length sequences (admission then gates purely "
         "on slots); smaller budgets make the allocator the gate — "
         "exhaustion queues new sequences and 429s past the queue."),
    Knob("HVT_SERVE_QUEUE_DEPTH", "int", 64, "serving",
         "Admission wait-queue depth per replica: sequences past the "
         "block/slot budget wait here FIFO; a full queue answers 429 "
         "(AdmissionError) instead of stacking unbounded memory."),
    Knob("HVT_SERVE_REPLICAS", "int", 2, "serving",
         "`hvt-launch serve` fleet width: replica server processes "
         "behind the router (each with its own engine + KV budget)."),
    Knob("HVT_SERVE_DRAIN_TIMEOUT_S", "float", 30.0, "serving",
         "Drain budget in seconds: how long a replica waits for in-flight "
         "requests to finish on SIGTERM, and how long a weight reload "
         "waits for the engine to empty before refusing the swap."),
    Knob("HVT_SERVE_SWAP_TIMEOUT_S", "float", 120.0, "serving",
         "Zero-downtime weight swap budget per replica: router drain + "
         "reload + health check must fit here or the swap aborts and the "
         "replica is readmitted on its OLD weights (journaled)."),
    Knob("HVT_SERVE_AUTOSCALE", "str", "off", "serving",
         "Fleet autoscale hook: off / dry-run (journal "
         "policy_scale_up/down without acting) / on (spawn or drain a "
         "replica). Decisions come from the policy engine's "
         "ServeAutoscaler over the router's TTFT histogram."),
    Knob("HVT_SERVE_TTFT_P95_MS", "float", 250.0, "serving",
         "Autoscale SLO: windowed p95 TTFT (ms) above this for "
         "consecutive windows scales up; far below (x0.3) scales down."),
    # --- data --------------------------------------------------------------
    Knob("HVT_NO_NATIVE", "flag", False, "data",
         "Disable the native C++ loader; fall back to the pure-python "
         "feeding path."),
    Knob("HVT_PREFETCH_DEPTH", "int", 2, "data",
         "Device-prefetch queue depth for the streamed fit path (staged "
         "batches ahead of the consuming step; 2 = classic double "
         "buffering — the step donates each consumed batch's buffer)."),
    Knob("HVT_DATA_DIR", "path", "~/.cache/horovod_tpu", "data",
         "Dataset cache directory (the keras-layout npz archives)."),
    Knob("HVT_DATA_RETRIES", "int", 3, "data",
         "Bounded retries for TRANSIENT dataset I/O failures (shard mmap "
         "opens, index reads — the flaky-NFS OSError class) before "
         "failing fast with the checkpoint-fallback escalation "
         "(0 = no retry)."),
    Knob("HVT_DATA_BACKOFF_S", "float", 0.05, "data",
         "Base backoff in seconds between dataset-read retries; doubles "
         "per attempt (exponential)."),
    Knob("HVT_DATA_SERVICE", "str", None, "data",
         "hvt-data dispatcher address (`host:port`): a service client "
         "(data/client.py) with this set fetches batches from the "
         "shared dispatcher under the HVT_DATA_RETRIES budget, "
         "degrading to rank-local feeding FROM THE SAME CURSOR "
         "(byte-identical) when the budget is exhausted and "
         "re-attaching at the next epoch boundary. Unset = pure local "
         "feeding. fleetd injects it into every job when the fleet "
         "spec carries a `data_service:` block."),
    Knob("HVT_DATA_JOB", "str", "default", "data",
         "Job name a service client admits its stream under on the "
         "hvt-data dispatcher — the per-job isolation and "
         "hvt_data_*{job=} metrics key (give each fleet job a distinct "
         "name)."),
    Knob("HVT_DATA_TIMEOUT_S", "float", 5.0, "data",
         "Per-socket-operation timeout (seconds) for hvt-data client "
         "fetches: a hung dispatcher surfaces as a retriable timeout "
         "inside the HVT_DATA_RETRIES budget instead of wedging the "
         "fed rank."),
    # --- observability ------------------------------------------------------
    Knob("HVT_PROFILE", "path", None, "observability",
         "Capture a jax.profiler trace of fit() into this dir — the "
         "HOROVOD_TIMELINE contract, primary-process-gated."),
    Knob("HVT_PEAK_FLOPS", "float", None, "observability",
         "Per-chip peak FLOP/s override for the MFU denominator — set it "
         "when the device kind is missing from the built-in peak table "
         "(a new TPU generation: unset, an unknown accelerator is an "
         "error). Unset on the CPU platform, the live MFU gauge is not "
         "published; an unparseable override raises ValueError."),
    Knob("HVT_METRICS_DIR", "path", None, "observability",
         "Metrics-stream directory (default: $PS_MODEL_PATH, else "
         "./models)."),
    Knob("HVT_METRICS_PORT", "int", None, "observability",
         "Opt-in trainer-side Prometheus exporter: every training "
         "process serves GET /metrics (live step-phase/MFU gauges) and "
         "POST /profile?seconds=N (on-demand jax.profiler capture) on "
         "port N + local_rank; 0 binds an ephemeral port; unset = off."),
    Knob("HVT_METRICS_EVERY", "int", 32, "observability",
         "Step-phase sampling cadence in optimizer steps for the "
         "trainer exporter: every N steps the fit loop drains the "
         "pipeline once and refreshes the step_ms{total,compute,comm,"
         "input} / examples-per-sec / MFU gauges (one drain of the "
         "pipeline per window is the sampler's recurring cost)."),
    Knob("HVT_FLIGHT_RECORD", "path", None, "observability",
         "Collective flight recorder: set to a DIRECTORY and every "
         "collectives.py submission site appends a bounded per-process "
         "JSONL record (seq, kind, dtype, shape, bytes, bucket id, "
         "caller tag) to <dir>/flight-<member>.jsonl — write-through "
         "before the collective blocks, dumped on SIGTERM and "
         "POST /flightrecord, auto-collected by the supervisor's hang "
         "path, cross-checked by `hvt-sched replay`. Unset = recorder "
         "off (zero instrumentation cost)."),
    Knob("HVT_FLIGHT_RECORD_SIZE", "int", 512, "observability",
         "Flight-recorder ring bound in records per process (explicit "
         "dumps rewrite the file to at most this many)."),
    Knob("HVT_TRACE_DIR", "path", None, "observability",
         "Structured trace-span directory: nestable JSONL span records "
         "(step, reduction, commit, rescale, checkpoint-save, serving "
         "request/queue-wait/decode), one rank-tagged file per process "
         "(trace.span); also the landing dir for POST /profile "
         "captures, and the input of `hvt-trace timeline/report/skew` "
         "(cross-rank merge, obs/timeline.py). Unset = spans off."),
    Knob("HVT_SKEW_PROBE", "flag", True, "observability",
         "Live cross-rank straggler detection (trainer.SkewProbe): at "
         "each step-phase sample window a tiny host allgather of drain "
         "waits publishes hvt_step_skew_ms / hvt_straggler_rank / "
         "hvt_barrier_wait_ms. Only active when the trainer exporter "
         "(HVT_METRICS_PORT) is on and the run is multi-process; set 0 "
         "to kill the probe while keeping the exporter."),
    Knob("HVT_FLEET_POLL_S", "float", 10.0, "observability",
         "Supervisor fleet-rollup poll cadence in seconds: how often "
         "the status server re-scrapes each member's trainer exporter "
         "into the GET /fleet cache (also what the final metrics.prom "
         "dump merges, so per-rank series survive the fleet). 0 "
         "disables background polling — /fleet then scrapes only on "
         "request."),
    # --- testing / chaos ----------------------------------------------------
    Knob("HVT_FAULT", "spec", None, "testing",
         "Deterministic fault injection, `rank:epoch[.step]:kind` (kinds "
         "kill/exitN/hang/leave/reorder/corrupt[@target]/slow:MS/"
         "netdrop:MS/dataslow:MS/hostdown; `hostdown` SIGKILLs every "
         "rank sharing the firing "
         "rank's host via the HVT_FAULT_HOST_PIDS registry — the "
         "host-loss ground truth for hvt-launch fleet; "
         "`reorder` swaps the rank's last two flight-recorded "
         "submissions, then wedges like `hang` — the hvt-sched replay "
         "acceptance fault; `slow:MS` makes the rank sleep MS ms per "
         "step from the target epoch on, recurring — the hvt-trace "
         "straggler-detection ground truth; the data-plane kinds "
         "`netdrop:MS` (hvt-data client drops its dispatcher "
         "connection + delays reconnect MS ms before every fetch "
         "DURING the target epoch) and `dataslow:MS` (dispatcher "
         "delays every batch response MS ms from the target epoch on) "
         "fire in data/client.py and data/service.py via "
         "faults.data_fault_ms, not in the trainer callback)."),
    Knob("HVT_FAULT_STAMP", "path", None, "testing",
         "One-shot stamp file: the fault fires once, never while the "
         "stamp exists — across relaunches."),
    Knob("HVT_FAULT_HOST_PIDS", "path", None, "testing",
         "Per-host pid registry directory for the `hostdown` fault kind "
         "(fleetd points every rank placed on host H at `<dir>/H`); each "
         "rank's fault callback registers its pid there at epoch begin, "
         "and a firing `hostdown` SIGKILLs every registered live pid — "
         "peers first, self last. Unset degrades hostdown to a "
         "self-SIGKILL."),
    Knob("HVT_DATA_FAULT_READS", "int", 0, "testing",
         "Inject N deterministic TRANSIENT read faults (OSError) into "
         "the dataset-read retry path (data.stream.read_with_retries) — "
         "the chaos hook for exercising HVT_DATA_RETRIES."),
    # --- examples (read by the example entry scripts, not the package) ------
    Knob("HVT_BACKWARD_PASSES", "int", 1, "examples",
         "Gradient-accumulation factor K for the example entry scripts "
         "(DistributedOptimizer backward_passes_per_step).",
         tunable=Tunable("int", lo=1, hi=8, scale="log")),
    Knob("HVT_COMPRESSION", "str", "none", "examples",
         "Gradient wire compression for the example entry scripts "
         "(none/bf16/fp16/int8/fp8 — DistributedOptimizer(compression=); "
         "int8/fp8 carry error-feedback residuals by default).",
         tunable=Tunable("choice",
                         choices=("none", "bf16", "fp16", "int8", "fp8"))),
    Knob("HVT_COMPRESSION_ICI", "str", "none", "examples",
         "ICI-hop gradient wire for the example entry scripts "
         "(none/bf16/fp16/int8/fp8 — DistributedOptimizer("
         "compression_ici=): the hierarchical two-hop reduction's "
         "intra-slice hop, error-feedback-charged per hop for int8/fp8; "
         "inert on single-slice meshes where dcn == 1).",
         tunable=Tunable("choice",
                         choices=("none", "bf16", "fp16", "int8", "fp8"))),
    Knob("HVT_DEVICE_CACHE", "flag", False, "examples",
         "Example entry scripts: stage the dataset into HBM once "
         "(`cache='device'`)."),
    Knob("HVT_EXPORT_FORMAT", "str", "stablehlo", "examples",
         "Example entry scripts: serving-bundle export format "
         "(stablehlo/savedmodel)."),
])


class UnknownKnobError(KeyError):
    """An env knob was read that is not declared in this registry."""

    def __init__(self, name: str):
        super().__init__(
            f"{name} is not a declared HVT_* knob — add a Knob row to "
            "horovod_tpu/analysis/registry.py (type, default, subsystem, "
            "description) and regenerate docs/ENVVARS.md"
        )


def knob(name: str) -> Knob:
    try:
        return KNOBS[name]
    except KeyError:
        raise UnknownKnobError(name) from None


def is_registered(name: str) -> bool:
    return name in KNOBS


def tunable_knobs() -> dict[str, Knob]:
    """The knobs carrying autotuner domain metadata, name-sorted — the
    whole candidate space `hvt-tune` is allowed to search."""
    return {name: k for name, k in sorted(KNOBS.items()) if k.tunable}


def flag_like(value: str | None) -> bool:
    """The shared boolean env contract (see module docstring)."""
    return (value or "").lower() not in ("", "0", "false", "no")


def get_raw(name: str, *, environ=None) -> str | None:
    """The raw string value, or None when unset/empty. The name must be
    registered — this is the choke point HVT004 pushes every read through."""
    k = knob(name)
    env = os.environ if environ is None else environ
    raw = env.get(k.name, "")
    return raw if raw != "" else None


def get_str(name: str, *, environ=None) -> str | None:
    raw = get_raw(name, environ=environ)
    return raw if raw is not None else knob(name).default


def get_int(name: str, *, environ=None) -> int | None:
    raw = get_raw(name, environ=environ)
    if raw is None:
        d = knob(name).default
        return None if d is None else int(d)
    return int(raw)


def get_float(name: str, *, environ=None) -> float | None:
    raw = get_raw(name, environ=environ)
    if raw is None:
        d = knob(name).default
        return None if d is None else float(d)
    return float(raw)


def get_flag(name: str, *, environ=None) -> bool:
    k = knob(name)
    raw = get_raw(name, environ=environ)
    return bool(k.default) if raw is None else flag_like(raw)


# --- generated reference doc (docs/ENVVARS.md) ------------------------------

_DOC_HEADER = """\
# `HVT_*` environment variables

<!-- GENERATED FILE — do not edit by hand.
     Source of truth: horovod_tpu/analysis/registry.py.
     Regenerate: python -m horovod_tpu.analysis.registry > docs/ENVVARS.md
     (tests/test_lint_clean.py fails when this file drifts). -->

Every knob the framework reads, from the central registry
(`horovod_tpu/analysis/registry.py`). Contract, uniform across all knobs:
**unset and empty-string are equivalent** (the default applies); `flag`
knobs treat `''`/`0`/`false`/`no` (case-insensitive) as off and anything
else as on. The static analyzer (`hvt-lint`, rule HVT004) rejects any
`HVT_*` read in the package that is not declared in the registry.

`PS_MODEL_PATH` (not `HVT_`-prefixed — inherited from the reference
stack) is the checkpoint/metrics root many defaults hang off; it is
documented where used rather than registered here.
"""


def _fmt_default(k: Knob) -> str:
    if k.default is None:
        return "—"
    if k.type == "flag":
        return "on" if k.default else "off"
    return f"`{k.default}`"


def generate_doc() -> str:
    """Render the ENVVARS.md content. Deterministic: grouped by subsystem
    in `_SUBSYSTEM_ORDER`, name-sorted within a group."""
    parts = [_DOC_HEADER]
    for sub in _SUBSYSTEM_ORDER:
        group = sorted(
            (k for k in KNOBS.values() if k.subsystem == sub),
            key=lambda k: k.name,
        )
        if not group:
            continue
        parts.append(f"\n## {sub}\n")
        parts.append("| name | type | default | description |")
        parts.append("|---|---|---|---|")
        for k in group:
            parts.append(
                f"| `{k.name}` | {k.type} | {_fmt_default(k)} "
                f"| {k.description} |"
            )
    tunables = tunable_knobs()
    if tunables:
        parts.append("\n## autotuner domains\n")
        parts.append(
            "Knobs carrying machine-readable `tunable=` domain metadata — "
            "the candidate space `hvt-tune` enumerates (offline model "
            "search and in-situ probe shortlist). A knob not listed here "
            "is invisible to the tuner by construction."
        )
        parts.append("")
        parts.append("| name | kind | domain |")
        parts.append("|---|---|---|")
        for name, k in tunables.items():
            parts.append(
                f"| `{name}` | {k.tunable.kind} | {k.tunable.domain_str()} |"
            )
    return "\n".join(parts) + "\n"


if __name__ == "__main__":
    print(generate_doc(), end="")
