"""Private-jax helpers for elastic re-initialisation.

Resizing the world in-process needs two operations jax has no public API
for: fully resetting the distributed runtime's global state (so a second
``jax.distributed.initialize`` is legal) and dropping the live backends
(whose collectives are compiled against the OLD world size). Both touch
``jax._src``; they are written against the installed jax (the floor in
pyproject.toml) and live here so there is one place to re-check on an
upgrade.
"""

from __future__ import annotations

import jax
from jax._src import distributed
from jax.extend import backend


def reset_distributed_state() -> None:
    """Null out jax's distributed global state so a subsequent
    ``jax.distributed.initialize`` succeeds.

    ``State.shutdown()`` clears the client, service and preemption manager
    only when it runs to the end, and leaves ``coordinator_address`` /
    ``process_id`` / ``num_processes`` populated; a rescale must clear
    everything, including after a torn shutdown."""
    state = distributed.global_state
    state.client = None
    state.service = None
    state.preemption_sync_manager = None
    state.coordinator_address = None
    # Back to the PRISTINE single-process values, not None: backend
    # creation reads process_id/num_processes directly (node_id=None
    # crashes the CPU client constructor).
    state.process_id = 0
    state.num_processes = 1


def distributed_shutdown_barrier() -> None:
    """The SYNCHRONIZED clean teardown of a live distributed world: every
    process must call this at the same point (a collective boundary).

    ``client.shutdown()`` is a barrier — it completes only when all tasks
    reach it, which is exactly what keeps the coordination service from
    entering its error state (an abrupt disconnect makes it propagate a
    fatal error to every surviving client — observed as SIGABRT,
    "Terminating process because the JAX distributed service detected
    fatal errors"). After the barrier, leftover fields are reset so
    re-initialization at a new world size is legal."""
    try:
        distributed.global_state.shutdown()
    finally:
        reset_distributed_state()


def clear_backends() -> None:
    """Drop live XLA backends (and jit caches) so the next device use
    re-creates them against the CURRENT distributed world.

    Every live ``jax.Array`` is invalidated — callers must hold host
    (numpy) copies of anything they still need (the ElasticState commit
    contract)."""
    jax.clear_caches()
    backend.clear_backends()
