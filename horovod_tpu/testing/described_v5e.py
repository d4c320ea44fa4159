"""A DESCRIBED TPU v5e: the chip's own compiler, with no chip attached.

libtpu compiles for a topology that is described and not attached, so a
train step can be traced, lowered and compiled at real widths on a host
with no accelerator; nothing runs, so nothing here says anything about
results or times. The compile tests (`tests/test_chip_compile.py`,
`tests/test_kernel_compile.py`) and the compiling helpers under `build/`
share what is here:

* `described()`: the v5e:2x2 description, with libtpu's log directory
  disabled (or the compiler logs under the temporary directory) and the
  persistent compilation cache off while it is in use (an executable
  compiled for a described device can be written to the cache but not
  read back), the environment and the cache restored afterwards;
* `step_args`: a `Trainer`'s step arguments as shapes with shardings;
* `kernel_calls` / `kernel_names`: the Mosaic calls of a compiled program;
* `traced_kernels` / `traced_scopes`: the same calls, and the scopes of
  every operation, read from the traced step before XLA runs.

A second process can describe the topology beside the first only where
``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` is set; without it libtpu refuses to
load in the second, which `described()` raises like any other failure to
describe.
"""

from __future__ import annotations

import contextlib
import functools
import os
import re
from typing import Iterator, NamedTuple
from unittest import mock

import jax
import jax.numpy as jnp
# Name stacks, and the two primitives whose bodies lowering puts under a
# scope of its own, as `jax._src.interpreters.mlir` composes them.
from jax._src import source_info_util
from jax._src.ad_checkpoint import remat_p
from jax._src.pjit import jit_p
from jax.extend import core as jax_core

from horovod_tpu.parallel import sharding as sharding_lib
from horovod_tpu.training.train_state import TrainState

TOPOLOGY = "v5e:2x2"
SDS = jax.ShapeDtypeStruct


@contextlib.contextmanager
def described():
    """The described `TOPOLOGY` (its ``.devices`` take shardings); raises
    what libtpu raises where it cannot be described."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    cache_was_on = jax.config.jax_enable_compilation_cache
    with mock.patch.dict(os.environ, {"TPU_LOG_DIR": "disabled"}):
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name=TOPOLOGY)
            jax.config.update("jax_enable_compilation_cache", False)
            compilation_cache.reset_cache()
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was_on)
            compilation_cache.reset_cache()


def step_args(trainer, seq: int, batch: int):
    """`train_step`'s arguments as shapes with shardings: a described
    device cannot hold an array, so nothing is built or placed."""
    mesh = trainer.mesh
    rep = sharding_lib.replicated(mesh)
    tokens = SDS(
        (batch, seq), jnp.int32,
        sharding=sharding_lib.batch_sharding(mesh, 2),
    )
    x0 = jnp.zeros((trainer.dp_size, seq), jnp.int32)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(
        lambda: trainer.module.init(
            {"params": key, "dropout": key}, x0, train=False, labels=x0
        )
    )["params"]
    state = TrainState(
        step=SDS((), jnp.int32),
        params=params,
        opt_state=jax.eval_shape(trainer.tx.init, params),
        rng=SDS((2,), jnp.uint32),
        model_state=None,
    )
    state = jax.tree.map(
        lambda l: SDS(l.shape, l.dtype, sharding=rep), state
    )
    scalar = SDS((), jnp.float32, sharding=rep)
    return state, (tokens, tokens), scalar, {
        name: scalar for name in trainer.metric_names
    }


def kernel_calls(compiled) -> list[str]:
    """The compiled program's Mosaic calls, a line each."""
    return [
        line for line in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]


def _family(instruction: str) -> str:
    """An instruction's name without its number: what the benchmark's
    reduction calls an op family."""
    return re.sub(r"[.\d]+$", "", instruction)


def kernel_names(compiled) -> list[str]:
    """The Mosaic calls' HLO instruction names without their numbers,
    sorted."""
    return sorted(
        _family(re.match(r"\s*(?:ROOT )?%(\S+) =", line)[1])
        for line in kernel_calls(compiled)
    )


@functools.lru_cache(maxsize=1)
def trace_step(trainer, seq: int, batch: int):
    """The Trainer's own jitted step traced at ``seq`` x ``batch``, so the
    trace sees the options the program passes and not a copy of them.
    The last trace is kept: `traced_kernels` and `traced_scopes` of one
    step read one trace, and ``.lower().compile()`` compiles it."""
    return trainer._train_step.trace(*step_args(trainer, seq, batch))


def _equations(jaxpr, stack) -> Iterator[tuple]:
    """(equation, name-stack path) of every equation at every depth, the
    path composed as lowering composes it: a `checkpoint`'s body under
    ``checkpoint``, a nested jit's under ``jit(<name>)``."""
    for eqn in jaxpr.eqns:
        path = stack + eqn.source_info.name_stack
        yield eqn, path
        inner = path
        if eqn.primitive is remat_p:
            inner = path.extend("checkpoint")
        elif eqn.primitive is jit_p:
            inner = path.extend(f"jit({eqn.params['name']})")
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                if isinstance(sub, jax_core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax_core.Jaxpr):
                    yield from _equations(sub, inner)


def _paths(trainer, seq: int, batch: int) -> Iterator[tuple]:
    """(equation, path) of the step's every equation, the path under the
    step's own ``jit(<name>)`` as in the compiled program's op names."""
    traced = trace_step(trainer, seq, batch)
    root = source_info_util.new_name_stack(f"jit({traced.fun_name})")
    for eqn, path in _equations(traced.jaxpr.jaxpr, root):
        yield eqn, str(path)


class Kernel(NamedTuple):
    name: str  # as `kernel_names` reads it from the compiled instruction
    path: str  # the name-stack path it is traced under


def traced_kernels(trainer, seq: int, batch: int) -> list[Kernel]:
    """The step's Mosaic calls (its `pallas_call` equations), sorted, each
    named as the compiled instruction is: by the innermost entry of its
    name stack, which is the kernel's own name (`pallas_call(name=)`), or
    the caller's scope around a call that names none (the grouped
    matmuls)."""
    return sorted(
        Kernel(_family(re.sub(r"\W", "_", path.rsplit("/", 1)[-1])), path)
        for eqn, path in _paths(trainer, seq, batch)
        if eqn.primitive.name == "pallas_call"
    )


def traced_scopes(trainer, seq: int, batch: int) -> set[str]:
    """The name-stack path of every operation of the step: the compiled
    op names' paths, less the entries that lowering a control-flow
    primitive adds inside it (``while/body``, ``closed_call``)."""
    return {path for _, path in _paths(trainer, seq, batch)}
