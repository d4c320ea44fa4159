"""Process/device bootstrap and topology queries.

TPU-native replacement for ``hvd.init()`` and the rank/size surface
(reference: tensorflow2_keras_mnist.py:25,28-32, mnist_keras.py:30,33-36;
SURVEY.md §3.3).

Design notes (vs the Horovod model):

* Horovod: one process per GPU; ``hvd.init()`` runs MPI_Init, starts a C++
  coordinator thread, and the script pins one GPU by ``local_rank()``.
* Here: one process per *host*, each driving all its local TPU chips;
  ``init()`` wires up `jax.distributed` over DCN when a coordinator is
  configured and is a no-op for single-process runs — the reference's
  "no-launcher degradation" requirement (README.md:49-52) holds: the same
  script runs unlaunched with ``size() == 1`` on one chip/CPU.
* Device pinning is obsolete: `jax.local_devices()` enumerates the chips and
  SPMD sharding places data; there is nothing to pin.

Topology mapping (the unit of data parallelism is the *chip*, not the
process):

===================  =========================================================
Horovod concept      horovod_tpu equivalent
===================  =========================================================
``hvd.size()``       ``size()`` → ``jax.device_count()`` (total chips). This
                     is the number LR scaling and work division react to
                     (tensorflow2_keras_mnist.py:55,96).
``hvd.rank()``       ``rank()`` → ``jax.process_index()``. Used for
                     single-writer gating (checkpoints/TB on rank 0,
                     tensorflow2_keras_mnist.py:86-92).
``hvd.local_rank()`` ``local_rank()`` → this process's ordinal among
                     processes on the same host (0 in the standard
                     one-process-per-host deployment).
``hvd.local_size()`` ``local_size()`` → number of chips attached to this
                     process (``jax.local_device_count()``).
===================  =========================================================
"""

from __future__ import annotations

import dataclasses
import os
import socket

import jax

from horovod_tpu.analysis import registry

# Environment variables understood by init(), mirroring the role of
# mpirun's `-x` env propagation + /generated/hostfile (README.md:57).
ENV_COORDINATOR = "HVT_COORDINATOR_ADDRESS"
ENV_NUM_PROCESSES = "HVT_NUM_PROCESSES"
ENV_PROCESS_ID = "HVT_PROCESS_ID"
ENV_LOCAL_RANK = "HVT_LOCAL_RANK"
# Platform/device-count settings a launcher hands its children without
# touching their JAX_*/XLA_FLAGS environment (the launched CPU-mesh test
# mode: `hvt-launch run --nprocs N` with HVT_PLATFORM=cpu). init() applies
# them to jax.config — HVT_PLATFORM as `jax_platforms` (same effect as
# JAX_PLATFORMS), HVT_NUM_CPU_DEVICES as `jax_num_cpu_devices`, which wins
# over an inherited --xla_force_host_platform_device_count — so they must
# land before any backend use.
ENV_PLATFORM = "HVT_PLATFORM"
ENV_NUM_CPU_DEVICES = "HVT_NUM_CPU_DEVICES"
# Liveness contract with the restart supervisor (launch/supervisor.py):
# when set, fit() auto-installs callbacks.HeartbeatCallback, which touches
# $HVT_HEARTBEAT_DIR/rank-<process rank> through training; the supervisor
# kills and relaunches a fleet whose newest beat goes stale. Examples need
# no changes — the supervisor exports the variable, fit() reacts.
ENV_HEARTBEAT_DIR = "HVT_HEARTBEAT_DIR"
# Elastic rendezvous (horovod_tpu.elastic): the supervisor's coordinator
# address ("host:port") and this process's stable member identity. Set by
# `hvt-launch run/pod --elastic`; consumed by `elastic.run`, NOT by init()
# — in elastic mode the world (size/rank/jax coordinator) comes from a
# rendezvous round, not from static env assignment.
ENV_ELASTIC_COORDINATOR = "HVT_ELASTIC_COORDINATOR"
ENV_ELASTIC_MEMBER = "HVT_ELASTIC_MEMBER"

_initialized = False


def env_flag(name: str) -> bool:
    """Shared boolean env-var contract: unset/''/'0'/'false'/'no' are off
    (case-insensitive), anything else is on. Used for every HVT_* switch so
    the accepted spellings can't drift between call sites — the contract
    itself lives in `analysis.registry.flag_like` (the knob registry)."""
    return registry.flag_like(os.environ.get(name))


@dataclasses.dataclass(frozen=True)
class World:
    """Snapshot of the distributed topology after init()."""

    process_rank: int
    process_count: int
    local_rank: int
    device_count: int
    local_device_count: int
    hostname: str
    platform: str

    @property
    def is_distributed(self) -> bool:
        return self.process_count > 1


def init(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> World:
    """Initialize the distributed runtime. Idempotent, like ``hvd.init()``.

    Resolution order for each argument: explicit argument → HVT_* env var →
    unset. If no coordinator is configured the run is single-process
    (``process_count() == 1``) and every collective degrades to a local op —
    the reference's bare ``python script.py`` mode (README.md:49-52).

    Under a launcher (`horovod_tpu.launch`), the HVT_* env vars play the role
    of mpirun's slot mapping: the launcher assigns process ids and propagates
    the coordinator address, replacing `/generated/hostfile`
    (distributed-keras-sample.yaml:8).
    """
    global _initialized
    if _initialized:
        return world()

    # A process calling init() is a WORKER: start its collective flight
    # recorder if HVT_FLIGHT_RECORD asks for one (idempotent; no-op
    # unset). Launched ranks already enabled at import via their
    # launcher-assigned identity — this covers the standalone
    # no-launcher mode, and keeps the supervisor (which never inits a
    # runtime) from recording.
    from horovod_tpu import flight

    flight.enable()

    if registry.get_str(ENV_PLATFORM):
        jax.config.update("jax_platforms", registry.get_str(ENV_PLATFORM))
    n_cpu = registry.get_int(ENV_NUM_CPU_DEVICES)
    if n_cpu is not None:
        jax.config.update("jax_num_cpu_devices", n_cpu)
    if env_flag("HVT_FAST_RNG"):
        # TPU hardware RNG for dropout/init keys: threefry (the reproducible
        # default) computes its bits on the vector units when dropout is
        # on; 'rbg' uses the chip's generator. Opt-in — rbg streams are not
        # bit-reproducible across topologies the way threefry is.
        jax.config.update("jax_default_prng_impl", "rbg")

    coordinator_address = coordinator_address or registry.get_str(ENV_COORDINATOR)
    if num_processes is None:
        num_processes = registry.get_int(ENV_NUM_PROCESSES)
    if process_id is None:
        process_id = registry.get_int(ENV_PROCESS_ID)

    if coordinator_address is not None:
        # Multi-host control plane over DCN: replaces MPI_Init + the Horovod
        # background coordinator thread (SURVEY.md §2.3 row 1) — after this,
        # collective order is compiled statically, no runtime negotiation.
        # (On the CPU platform jax then builds its gloo collectives from
        # the distributed client by itself; without a client — a reinit
        # down to one survivor — it builds none.)
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    _initialized = True
    return world()


def use_compilation_cache() -> str:
    """Point jax's persistent compilation cache somewhere that survives
    the process, and return the directory. Entry scripts (`chip_smoke.py`,
    `chipbench/run.py`) call this before their first compile.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it — nothing is
    set in code. Unset: ``<checkout>/.jax_cache``, a FIXED path derived
    from this package's location (the path is part of the cache key, so a
    temp name, pid or timestamp would never hit). Whether the cache is
    used at all stays jax's own switch: ``JAX_ENABLE_COMPILATION_CACHE=0``
    wins (the fault-injection children rely on it)."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir is not None:
        return env_dir
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache",
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def shutdown() -> None:
    """Tear down the distributed runtime (no-op if single-process).

    In a multi-process world this is a BARRIER: every process must call it
    at the same point, or the coordination service flags the stragglers'
    disconnect as a fatal error and terminates the survivors (see
    `compat.distributed_shutdown_barrier`). The elastic rescale path calls
    it from the membership-change agreement, where lockstep is guaranteed."""
    global _initialized
    if not _initialized:
        return
    try:
        if jax.process_count() > 1:
            from horovod_tpu import compat

            compat.distributed_shutdown_barrier()
    finally:
        _initialized = False


def reinit(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> World:
    """Tear down whatever runtime exists and initialize at a (possibly
    different) world size — the elastic rescale primitive.

    Sequence: synchronized distributed shutdown (if a world is up — all
    processes of the OLD world must arrive here together), then backend
    drop (old executables/arrays were compiled against the old collective
    world and are invalid — hold host copies, the `ElasticState.commit`
    contract), then a fresh `init` at the new size. With no coordinator
    the result is the bare single-process mode — a fleet shrunk to one
    survivor keeps training with every collective degraded to a local op."""
    global _initialized
    from horovod_tpu import compat

    shutdown()
    compat.reset_distributed_state()  # idempotent; covers a torn shutdown
    compat.clear_backends()
    _initialized = False
    return init(coordinator_address, num_processes, process_id)


def is_initialized() -> bool:
    return _initialized


def world() -> World:
    return World(
        process_rank=jax.process_index(),
        process_count=jax.process_count(),
        local_rank=local_rank(),
        device_count=jax.device_count(),
        local_device_count=jax.local_device_count(),
        hostname=socket.gethostname(),
        platform=jax.default_backend(),
    )


# --- Horovod-parity topology queries (SURVEY.md §2.4 row 2) ----------------


def rank() -> int:
    """Global rank for single-writer gating (≈ ``hvd.rank()``).

    Returns the process index: exactly one process in the job returns 0, so
    ``rank() == 0`` preserves the reference's rank-0-only checkpoint/log
    convention (tensorflow2_keras_mnist.py:86-92)."""
    return jax.process_index()


def size() -> int:
    """World size for LR scaling / work division (≈ ``hvd.size()``).

    Returns the total chip count — the degree of data parallelism — which is
    what `lr * size` (tensorflow2_keras_mnist.py:55) and `steps // size`
    (:96) must react to."""
    return jax.device_count()


def local_rank() -> int:
    """Ordinal of this process among co-located processes (≈ ``hvd.local_rank()``).

    0 in the standard one-process-per-host deployment; launchers that place
    several processes on one host set HVT_LOCAL_RANK. Note the reference uses
    this only for GPU pinning (mnist_keras.py:35), which has no TPU analogue."""
    return registry.get_int(ENV_LOCAL_RANK)


def local_size() -> int:
    """Number of chips driven by this process (≈ ``hvd.local_size()``)."""
    return jax.local_device_count()


def process_rank() -> int:
    """Explicit process-level rank (same as rank(); here for clarity)."""
    return jax.process_index()


def process_count() -> int:
    """Number of host processes in the job."""
    return jax.process_count()


def is_primary() -> bool:
    """True on exactly one process — the single writer for checkpoints,
    TensorBoard and exports (reference convention, mnist_keras.py:100-105)."""
    return jax.process_index() == 0
