"""The canonical compiled-trainer-step probe `hvt-audit step` and the
HLO tests share.

Auditing a compiled step needs three things the test files used to
duplicate: a tiny deterministic model, the [K, G, ...] microbatch-stack
feeding contract, and the ``.lower().as_text()`` plumbing around
``Trainer._train_step``. This module owns all three, so the auditor can
run standalone against any jitted step and the tests stop carrying
private copies. Structure is what's audited — the model is deliberately
small (the invariants under test are per-BUCKET and per-STEP, not
per-FLOP).

This is the only analysis module that imports jax (lazily, inside the
functions): `hlo_audit` stays importable without an accelerator stack.
"""

from __future__ import annotations

__all__ = [
    "build_trainer",
    "canonical_step_text",
    "lowered_moe_dispatch_text",
    "lowered_step_text",
    "probe_data",
    "probe_model",
]


def probe_model():
    """The canonical audit model: a 2-layer MLP over flattened input —
    small enough that the default 64 MB bucket holds every gradient
    (one bucket -> the one-reduction invariant reads exactly 1)."""
    import flax.linen as nn
    import jax.numpy as jnp

    class Probe(nn.Module):
        @nn.compact
        def __call__(self, x, *, train: bool = False):
            x = x.reshape((x.shape[0], -1)).astype(jnp.float32)
            return nn.Dense(10)(nn.relu(nn.Dense(32)(x)))

    return Probe()


def probe_data(n: int = 64, seed: int = 0):
    import numpy as np

    rng = np.random.RandomState(seed)
    x = rng.rand(n, 8, 8, 1).astype(np.float32)
    y = rng.randint(0, 10, n).astype(np.int32)
    return x, y


def build_trainer(k: int = 1, compression: str = "none", *,
                  compression_ici: str = "none",
                  overlap=None, bucket_bytes=None, bucket_order=None,
                  error_feedback: bool = True, model=None, seed: int = 3,
                  zero1: bool = False):
    """A `Trainer` wired exactly like the perf-path tests wire theirs:
    accumulation factor ``k``, wire ``compression`` (plus the ICI-hop
    ``compression_ici``, audit-relevant only under a dcn > 1 factoring
    — set HVT_DCN_FACTOR to fake one), optional overlap/bucket knob
    overrides (None = the env-driven defaults). ``zero1`` turns on the
    sharded weight update (``Trainer(shard_update=True)``) — the
    composed ZeRO-1 x accumulation x compression step
    `hvt-audit step --zero1` gates."""
    import optax

    import horovod_tpu as hvt

    tx = hvt.DistributedOptimizer(
        optax.adam(1e-3), backward_passes_per_step=k,
        average_aggregated_gradients=True, compression=compression,
        compression_ici=compression_ici, error_feedback=error_feedback,
    )
    return hvt.Trainer(
        model if model is not None else probe_model(), tx, seed=seed,
        bucket_bytes=bucket_bytes, overlap_reduction=overlap,
        bucket_order=bucket_order, shard_update=zero1,
    )


def lowered_step_text(tr, x, y, k: int, *, micro: int = 8,
                      n: int = 32) -> str:
    """The lowered (StableHLO) text of one compiled optimizer step, fed
    a [K, G, ...] microbatch stack when ``k > 1`` — the single
    implementation of the plumbing the HLO assertions run against."""
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.parallel import sharding as sharding_lib

    state = tr.build(x[: tr.dp_size])
    if k == 1:
        batch = tr._shard((x[:n], y[:n]))
    else:
        batch = tr._shard_chunk(
            (
                np.stack([x[i * micro: (i + 1) * micro] for i in range(k)]),
                np.stack([y[i * micro: (i + 1) * micro] for i in range(k)]),
            ),
            1,
        )
    acc = sharding_lib.replicate(tr.zero_metrics(), tr.mesh)
    return tr._train_step.lower(
        state, batch, jnp.asarray(1.0, jnp.float32), acc
    ).as_text()


def lowered_moe_dispatch_text(d_model: int = 8, capacity: int = 4) -> str:
    """Lowered StableHLO of the canonical EP dispatch/combine probe —
    the MoE wire shape `hvt-audit moe --expect alltoalls=2` gates.

    A shard_map over an ``expert`` axis spanning every local device
    moves each group's routed activations to the expert shards that own
    them (`collectives.all_to_all`, the HVT011 entry point), runs the
    expert FFN stand-in, and combines them back with the mirror
    all-to-all — exactly TWO payload (rank >= 2) all-to-alls, no
    full-payload all-reduce anywhere. The probe is structural like
    `probe_model`: what's audited is the wire shape, not the routing
    math (`models/moe.py` owns that). Requires `horovod_tpu.init()`."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel import collectives

    devices = jax.devices()
    e = len(devices)
    mesh = jax.sharding.Mesh(np.asarray(devices), ("expert",))

    def stage(x):
        # x: this shard's [E, C, D] dispatch block — row i holds the
        # tokens this shard routed to expert i.
        dispatched = collectives.all_to_all(
            x, "expert", split_axis=0, concat_axis=0, tiled=True
        )
        h = jnp.tanh(dispatched)  # the expert FFN stand-in
        return collectives.all_to_all(
            h, "expert", split_axis=0, concat_axis=0, tiled=True
        )

    fn = jax.shard_map(
        stage, mesh=mesh, in_specs=(P("expert"),), out_specs=P("expert")
    )
    x = jnp.zeros((e * e, capacity, d_model), jnp.float32)
    return jax.jit(fn).lower(x).as_text()


def canonical_step_text(k: int = 4, compression: str = "none", *,
                        overlap=None, bucket_bytes=None) -> str:
    """One call from config to auditable text — `hvt-audit step`'s
    workhorse. Requires `horovod_tpu.init()` to have run."""
    x, y = probe_data()
    tr = build_trainer(
        k, compression, overlap=overlap, bucket_bytes=bucket_bytes,
    )
    return lowered_step_text(tr, x, y, k)
