"""Pipeline-schedule backward-memory comparison.

Compares XLA's `memory_analysis()` of the compiled gradient computation for
`PipelinedLM(schedule='gpipe')` (AD-derived backward: the scan stash holds
every tick's stage internals) vs `schedule='1f1b'` (hand-scheduled staggered
backward with per-microbatch rematerialization — the 1F1B activation
discipline). Runs on the virtual 8-device CPU mesh (data=2 × pipe=4), so it
reproduces anywhere.

Run:  python benchmarks/pp_memory.py
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

from horovod_tpu.models.pipelined_lm import PipelinedLM  # noqa: E402
from horovod_tpu.parallel import mesh as mesh_lib  # noqa: E402

VOCAB = 64
D_MODEL, N_HEADS, N_LAYERS, N_MICRO = 128, 4, 8, 8
BATCH, SEQ = 16, 256


def temp_bytes(schedule: str, mesh, params, toks, labels) -> int:
    model = PipelinedLM(
        vocab_size=VOCAB, d_model=D_MODEL, n_heads=N_HEADS,
        n_layers=N_LAYERS, n_micro=N_MICRO, mesh=mesh, schedule=schedule,
    )

    def loss(p):
        logits = model.apply({"params": p}, toks)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    compiled = jax.jit(jax.grad(loss)).lower(params).compile()
    return int(compiled.memory_analysis().temp_size_in_bytes)


def main():
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, pipe=4))
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(1, VOCAB, size=(BATCH, SEQ)).astype(np.int32))
    labels = jnp.asarray(rng.randint(1, VOCAB, size=(BATCH, SEQ)).astype(np.int32))
    params = PipelinedLM(
        vocab_size=VOCAB, d_model=D_MODEL, n_heads=N_HEADS,
        n_layers=N_LAYERS, n_micro=N_MICRO, mesh=None,
    ).init(jax.random.PRNGKey(0), toks)["params"]

    g = temp_bytes("gpipe", mesh, params, toks, labels)
    f = temp_bytes("1f1b", mesh, params, toks, labels)
    print(json.dumps({
        "config": f"d{D_MODEL}x{N_LAYERS}L seq {SEQ}, pipe=4 x data=2, "
                  f"{N_MICRO} microbatches",
        "gpipe_temp_bytes": g,
        "1f1b_temp_bytes": f,
        "gpipe_over_1f1b": round(g / f, 2),
    }, indent=2))


if __name__ == "__main__":
    main()
