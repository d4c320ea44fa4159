"""Average-semantics collective wrappers — the Horovod-core equivalent.

This module is the moral counterpart of Horovod's entire C++ core
(coordinator thread + tensor fusion + MPI/NCCL ops, SURVEY.md §2.3): on TPU
it is ~100 lines because a single SPMD program makes collective order static
and XLA's collective-combining pass does tensor fusion. What remains is the
*semantics* the reference depends on:

* **average, not sum** — ``hvd.allreduce(grad, average=True)`` divides by
  world size after the ring reduction (SURVEY.md §3.5). Every reduction here
  defaults to mean.
* **root broadcast** — ``hvd.broadcast_global_variables(0)``
  (tensorflow2_keras_mnist.py:71) for consistent init / checkpoint restore.
* **metric averaging** — epoch-end cross-worker mean
  (tensorflow2_keras_mnist.py:77).

Two execution contexts, one API:

1. **Traced** (inside `shard_map`/`pmap` with a named mesh axis): pass
   ``axis_name=...`` — lowers to `lax.psum`/`pmean` → ICI collectives.
2. **Eager host-level** (between steps, across processes): omit
   ``axis_name`` — uses `jax.experimental.multihost_utils`; degrades to a
   no-op at ``process_count() == 1`` exactly like Horovod collectives at
   ``size()==1`` (README.md:49-52 single-instance mode).
"""

from __future__ import annotations

from typing import Any, Sequence

import jax

from horovod_tpu import flight
import jax.numpy as jnp
from jax import lax
from jax.experimental import multihost_utils

PyTree = Any


def _maybe_record(kind, value=None, *, tree=None, bucket=None):
    """Feed the flight recorder (flight.py) from a submission site.

    THE one gate every site routes through: when ``HVT_FLIGHT_RECORD``
    is unset, ``flight.RECORDER`` is None and this is a single attribute
    load + None check — the zero-instrumentation-cost contract the tier-1
    tests assert structurally. When recording, the record (kind, dtype,
    shape, payload bytes, bucket id, caller tag) is APPENDED AND FLUSHED
    before the collective blocks, so a wedged rank's final submission is
    already on disk when the supervisor collects the evidence."""
    rec = flight.RECORDER
    if rec is None:
        return
    import math
    import sys

    dtype = shape = nbytes = None
    try:
        if value is not None:
            shape = tuple(jnp.shape(value))
            dt = jnp.result_type(value)
            dtype = str(dt)
            nbytes = int(jnp.dtype(dt).itemsize * math.prod(shape))
        elif tree is not None:
            leaves = jax.tree_util.tree_leaves(tree)
            nbytes = int(sum(
                jnp.dtype(jnp.result_type(l)).itemsize
                * math.prod(jnp.shape(l))
                for l in leaves
            ))
            shape = (len(leaves),)
    except (TypeError, ValueError):
        pass  # unhashable/abstract values: record the kind alone
    code = sys._getframe(2).f_code
    rec.record(
        kind, dtype=dtype, shape=shape, nbytes=nbytes, bucket=bucket,
        tag=getattr(code, "co_qualname", None) or code.co_name,
    )


def _axis_names(axis_name) -> Sequence:
    if isinstance(axis_name, (tuple, list)):
        return tuple(axis_name)
    return (axis_name,)


def allreduce(x, average: bool = True, axis_name=None):
    """Allreduce one array. Mean by default (Horovod-parity semantics).

    Traced context: reduction over the named mesh axis/axes.
    Eager context: reduction across host processes (no-op single-process).
    """
    _maybe_record("allreduce", value=x)
    if axis_name is not None:
        return lax.pmean(x, axis_name) if average else lax.psum(x, axis_name)
    if jax.process_count() == 1:
        return x
    gathered = multihost_utils.process_allgather(jnp.asarray(x))
    return gathered.mean(axis=0) if average else gathered.sum(axis=0)


def allgather(x, axis_name=None, tiled: bool = True):
    """Concatenate per-worker shards along the leading axis
    (≈ ``hvd.allgather``, the third op in Horovod's kernel set,
    SURVEY.md §2.3 TF-custom-ops row)."""
    _maybe_record("allgather", value=x)
    if axis_name is not None:
        return lax.all_gather(x, axis_name, axis=0, tiled=tiled)
    if jax.process_count() == 1:
        return jnp.asarray(x)
    gathered = multihost_utils.process_allgather(jnp.asarray(x))
    return gathered.reshape((-1,) + gathered.shape[2:]) if tiled else gathered


def broadcast(x, root: int = 0, axis_name=None):
    """Broadcast ``x`` from the root worker (≈ ``hvd.broadcast``).

    Traced context: select root's shard via masked psum — every worker ends
    with root's value; XLA lowers this to a single collective.
    Eager context: `multihost_utils.broadcast_one_to_all` with the root
    process as source (the reference only ever uses root=0,
    tensorflow2_keras_mnist.py:71, but the API honors any root)."""
    _maybe_record("broadcast", value=x)
    if axis_name is not None:
        x = jnp.asarray(x)
        names = _axis_names(axis_name)
        idx = lax.axis_index(names[0])
        for name in names[1:]:
            idx = idx * lax.axis_size(name) + lax.axis_index(name)
        mask = (idx == root).astype(x.dtype)
        return lax.psum(x * mask, axis_name)
    if jax.process_count() == 1:
        return jnp.asarray(x)
    return multihost_utils.broadcast_one_to_all(
        x, is_source=jax.process_index() == root
    )


# --- PyTree conveniences (the DistributedOptimizer / broadcast-callback core)


def pmean_pytree(tree: PyTree, axis_name=None) -> PyTree:
    """Average every leaf across workers — the gradient-averaging heart of
    ``hvd.DistributedOptimizer`` (tensorflow2_keras_mnist.py:58) as one line.

    Under SPMD jit the per-tensor fusion/scheduling Horovod implements in C++
    (SURVEY.md §3.5) is handled by XLA's collective combiner. In eager
    host-level mode the whole tree goes through ONE fused collective (the
    moral equivalent of Horovod's tensor-fusion buffer) rather than one
    round-trip per leaf."""
    _maybe_record("pmean_pytree", tree=tree)
    if axis_name is None:
        if jax.process_count() == 1:
            return tree
        gathered = multihost_utils.process_allgather(tree)
        return jax.tree.map(lambda g: g.mean(axis=0), gathered)
    return jax.tree.map(lambda g: allreduce(g, average=True, axis_name=axis_name), tree)


def broadcast_pytree(tree: PyTree, root: int = 0, axis_name=None) -> PyTree:
    """Broadcast every leaf from root — ``hvd.broadcast_global_variables(0)``
    over an arbitrary pytree (model params AND optimizer state; the reference
    broadcasts both, SURVEY.md §7.3)."""
    _maybe_record("broadcast_pytree", tree=tree)
    if axis_name is None and jax.process_count() > 1:
        if _kv_client() is not None:
            # One fused host-level broadcast over the coordination-service
            # KV store (see _kv_client for why it replaces the psum path).
            # Only the ROOT's tree travels — non-root copies are replaced
            # wholesale, so their device→host fetch would be pure waste.
            return broadcast_object(
                jax.device_get(tree)
                if jax.process_index() == root else None,
                root=root,
            )
        return multihost_utils.broadcast_one_to_all(
            tree, is_source=jax.process_index() == root
        )
    return jax.tree.map(lambda x: broadcast(x, root=root, axis_name=axis_name), tree)


# --- host-level object collectives over the coordination-service KV store --
#
# Why not ride broadcast_one_to_all/process_allgather for these? Their
# device path (zero-stack + psum over a 'processes' axis) is observed to be
# UNRELIABLE on this repo's compat floor (jax 0.4.x + gloo CPU collectives:
# nondeterministic all-zero results for host-staged buffers), and object
# movement is control-plane work anyway. jax's distributed runtime carries a
# key-value store on the coordination service — the exact channel gloo uses
# to bootstrap itself — and a blocking KV get is deterministic: set-then-get
# is the broadcast, set-all-then-get-all is the allgather. Keys are
# sequenced per client connection, which is correct under the collective
# calling discipline (every process makes the same sequence of collective
# calls against a given world — the same contract the array collectives
# already require); an elastic rescale swaps the client (fresh service,
# fresh namespace), resetting the sequence on every process together.
# Each round's keys are garbage-collected once every reader has fetched
# (_kv_cleanup), so a long-lived world does not accumulate per-epoch votes
# or park model-sized broadcast payloads in the coordination service.

_KV_CHUNK = 2 * 1024 * 1024  # stay clear of gRPC's default 4 MB message cap
_KV_TIMEOUT_MS = 600_000
_kv_seq = {"client": None, "n": 0}


def _kv_client():
    """The live coordination-service client, or None (no distributed init —
    single-process, or a backend brought up without jax.distributed, or a
    jaxlib without the bytes KV APIs — the multihost_utils array fallback
    one branch away is then the right path)."""
    try:
        from jax._src import distributed

        client = distributed.global_state.client
    except ImportError:  # pragma: no cover — future jax moved the module
        return None
    if client is None or not (
        hasattr(client, "key_value_set_bytes")
        and hasattr(client, "blocking_key_value_get_bytes")
    ):
        return None
    return client


def _kv_next(tag: str) -> str:
    client = _kv_client()
    if client is not _kv_seq["client"]:
        _kv_seq["client"] = client
        _kv_seq["n"] = 0
    _kv_seq["n"] += 1
    return f"hvt/{tag}/{_kv_seq['n']}"


def _kv_put(client, key: str, payload: bytes) -> None:
    import hashlib

    chunks = [
        payload[i : i + _KV_CHUNK]
        for i in range(0, len(payload), _KV_CHUNK)
    ] or [b""]
    for i, chunk in enumerate(chunks):
        client.key_value_set_bytes(f"{key}/c{i}", chunk)
    # Meta lands LAST: a reader that sees it knows every chunk is in place.
    # It carries the payload's sha256 so the reader can prove it reassembled
    # the writer's exact bytes — the elastic commit/sync path moves model
    # state over this channel, and a silently-corrupt transport would
    # otherwise install garbage weights fleet-wide.
    digest = hashlib.sha256(payload).hexdigest()
    client.key_value_set(f"{key}/meta", f"{len(chunks)}:{digest}")


def _kv_get(client, key: str) -> bytes:
    import hashlib

    meta = str(client.blocking_key_value_get(f"{key}/meta", _KV_TIMEOUT_MS))
    n_s, _, digest = meta.partition(":")
    payload = b"".join(
        client.blocking_key_value_get_bytes(f"{key}/c{i}", _KV_TIMEOUT_MS)
        for i in range(int(n_s))
    )
    if digest and hashlib.sha256(payload).hexdigest() != digest:
        raise ValueError(
            f"KV object-collective payload {key!r} failed its sha256 check "
            f"({len(payload)} bytes reassembled) — coordination-service "
            "transport corruption"
        )
    return payload


def _kv_cleanup(client, key: str, *, root: int = 0) -> None:
    """Best-effort removal of a finished round's keys. The barrier proves
    every reader has fetched before the root deletes — without it a root
    racing ahead could delete chunks a slower peer is still blocked on.
    Any failure (a jaxlib predating delete/barrier, a peer death failing
    the barrier) leaves the keys behind, which costs memory in the
    coordination service but never correctness: keys are never reused
    (monotonic sequence) and an elastic rescale drops the whole namespace
    with the old service anyway."""
    try:
        client.wait_at_barrier(f"{key}/done", _KV_TIMEOUT_MS)
        if jax.process_index() == root:
            client.key_value_delete(f"{key}/")
    except Exception:
        pass


def broadcast_object(obj, root: int = 0):
    """``hvd.broadcast_object``: every process adopts the root's arbitrary
    picklable Python object (config dicts, vocabularies, epoch counters,
    committed elastic state — the host-side metadata Horovod moves
    alongside tensors). Travels over the coordination-service KV store
    (see above); ``process_count()==1`` is the identity, like every
    collective here."""
    _maybe_record("broadcast_object")
    import pickle

    import numpy as np

    if jax.process_count() == 1:
        return obj
    client = _kv_client()
    if client is not None:
        key = _kv_next("bcast")
        if jax.process_index() == root:
            _kv_put(client, key, pickle.dumps(obj))
        out = pickle.loads(_kv_get(client, key))
        _kv_cleanup(client, key, root=root)
        return out
    # Fallback (no distributed client): the fixed-width array broadcast.
    payload = pickle.dumps(obj) if jax.process_index() == root else b""
    n = int(
        multihost_utils.broadcast_one_to_all(
            np.int64(len(payload)), is_source=jax.process_index() == root
        )
    )
    buf = np.zeros(n, np.uint8)
    if jax.process_index() == root:
        buf[:] = np.frombuffer(payload, np.uint8)
    buf = multihost_utils.broadcast_one_to_all(
        buf, is_source=jax.process_index() == root
    )
    return pickle.loads(np.asarray(buf).tobytes())


def allgather_object(obj) -> list:
    """``hvd.allgather_object``: every process receives the list of all
    processes' picklable objects, ordered by process index. KV-store
    transport (set mine, read everyone's), like `broadcast_object`."""
    _maybe_record("allgather_object")
    import pickle

    import numpy as np

    if jax.process_count() == 1:
        return [obj]
    client = _kv_client()
    if client is not None:
        key = _kv_next("gather")
        _kv_put(client, f"{key}/r{jax.process_index()}", pickle.dumps(obj))
        out = [
            pickle.loads(_kv_get(client, f"{key}/r{r}"))
            for r in range(jax.process_count())
        ]
        _kv_cleanup(client, key)
        return out
    payload = np.frombuffer(pickle.dumps(obj), np.uint8)
    sizes = multihost_utils.process_allgather(np.int64(len(payload)))
    width = int(np.max(sizes))
    buf = np.zeros(width, np.uint8)
    buf[: len(payload)] = payload
    gathered = multihost_utils.process_allgather(buf)
    return [
        pickle.loads(gathered[i, : int(sizes[i])].tobytes())
        for i in range(jax.process_count())
    ]


def all_to_all(x, axis_name, *, split_axis: int = 0, concat_axis: int = 0,
               tiled: bool = True, axis_index_groups=None):
    """The payload all-to-all entry point — the EP (expert-parallel)
    dispatch/combine wire (ROADMAP item 4).

    MoE dispatch moves each group's routed activations to the expert
    shards that own them and combine moves them back: one all-to-all
    each way, the only collectives whose PAYLOAD is activations rather
    than gradients. Routing them through this entry point (instead of a
    raw ``lax.all_to_all`` at the model layer — `hvt-lint` rule HVT011)
    keeps the EP wire under the same discipline as the gradient wire:
    every submission is flight-recorded (`horovod_tpu.flight`), and the
    compiled program's payload all-to-alls are auditable as a count
    (`hvt-audit --expect alltoalls=N` — rank >= 2 payloads; the rank-1
    scale/column gathers of the quantized wire stay excluded).

    Traced context only (inside shard_map/pmap over ``axis_name``)."""
    _maybe_record("all_to_all", value=x)
    return lax.all_to_all(
        x, axis_name, split_axis=split_axis, concat_axis=concat_axis,
        tiled=tiled, axis_index_groups=axis_index_groups,
    )


# --- Bucketed fusion + hierarchical (ICI/DCN two-hop) gradient reduction ---
#
# Horovod's defining perf feature is tensor fusion: many small gradient
# tensors batched into one collective so the wire sees a handful of large
# transfers instead of one launch per leaf (arXiv:1802.05799 §Horovod's
# fusion buffer). Under SPMD jit XLA's collective combiner does a version of
# this, but the explicit-collective gradient step (wire compression,
# trainer-native accumulation) hand-places its psums — so the fusion must be
# hand-placed too. `flatten_buckets` packs a gradient pytree into a few
# contiguous dtype-homogeneous 1-D buckets (≤ bucket_bytes each, Horovod's
# HOROVOD_FUSION_THRESHOLD role); `unflatten_buckets` restores the tree.
#
# On a multi-slice mesh the data axis spans DCN (orders of magnitude less
# bandwidth than intra-slice ICI), and EQuARX (arXiv:2506.17615) shows
# gradient compression should pay its precision cost only on the slow hop:
# `hierarchical_psum` reduces over the ICI sub-axis in full precision first,
# then over the DCN sub-axis in the wire dtype — same result as the flat
# psum (sum is associative; the cast boundary is the only numerics delta),
# 16-bit bytes only where bandwidth is scarce. `reduce_gradients` composes
# the two: bucket, reduce each bucket (two-hop when dcn > 1), unflatten.
#
# Quantized wires (int8 / fp8, the EQuARX-aggressive tier): a sub-16-bit
# reduction cannot ride a plain all-reduce — int8 partial sums overflow and
# fp8 ones drown in rounding — so each quantized hop is a gather-sum: the
# bucket is scaled by ONE per-bucket scalar (amax/qmax), cast to the wire
# dtype, all-gathered across the hop's groups (the only payload bytes on
# the wire: 1 B/element plus one f32 scale per bucket per shard), then
# dequantized and summed in f32 by every receiver. Error feedback (EQuARX
# residuals): the caller carries a per-shard residual of what quantization
# failed to transmit and adds it back before the next step's quantization —
# the errors telescope, so quantization bias does NOT compound across
# steps. `reduce_gradients(..., residual=...)` threads it per bucket and
# returns the updated residual tree.
#
# Overlap (Horovod's tensor-fusion ORDER trick, arXiv:1802.05799): the
# backward pass produces the LAST layers' gradients first, so issuing the
# bucket reductions in reverse pytree order (``reverse=True``) lets XLA's
# latency-hiding scheduler start a bucket's collective (all-reduce-start /
# all-gather-start on TPU) as soon as its leaves are final, while the
# remaining backward compute is still running — provided the caller keeps
# that backward in the same straight-line computation (see
# trainer.explicit_grads, which peels the last microbatch out of its
# accumulation scan exactly for this).

#: Default fusion-bucket size: Horovod's fusion threshold default (64 MB).
DEFAULT_BUCKET_BYTES = 64 * 1024 * 1024


def flatten_buckets(tree: PyTree, bucket_bytes: int | None = None,
                    *, reverse: bool = False):
    """Pack a pytree into contiguous dtype-homogeneous 1-D buckets.

    Leaves are grouped by dtype (first-appearance order), raveled,
    concatenated, and split into chunks of at most ``bucket_bytes`` — so a
    dtype's leaves cost ``ceil(dtype_bytes / bucket_bytes)`` buckets and the
    whole tree at most ``ceil(total_bytes / bucket_bytes) + n_dtypes - 1``.
    ``reverse=True`` walks the leaves LAST-first (Horovod's fusion order:
    the backward pass finalizes the last layers' gradients first, so the
    first buckets become reducible while earlier layers are still
    computing). Returns ``(buckets, spec)``;
    ``unflatten_buckets(buckets, spec)`` is the exact inverse (shapes,
    dtypes, 0-d leaves, pytree structure all restored) for either order.
    Pure structure — no communication; callers reduce the buckets however
    they like."""
    if bucket_bytes is None:
        bucket_bytes = DEFAULT_BUCKET_BYTES
    bucket_bytes = int(bucket_bytes)
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be positive, got {bucket_bytes}")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = [jnp.shape(l) for l in leaves]
    dtypes = [jnp.result_type(l) for l in leaves]
    by_dtype: dict = {}  # dtype -> list of leaf indices (order-preserving)
    order = range(len(dtypes) - 1, -1, -1) if reverse else range(len(dtypes))
    for i in order:
        by_dtype.setdefault(jnp.dtype(dtypes[i]), []).append(i)
    buckets = []
    groups = []  # (leaf_indices, n_chunks) per dtype, bucket order
    for dt, idxs in by_dtype.items():
        flat = [jnp.ravel(jnp.asarray(leaves[i], dtype=dt)) for i in idxs]
        vec = flat[0] if len(flat) == 1 else jnp.concatenate(flat)
        per = max(1, bucket_bytes // dt.itemsize)
        cuts = list(range(per, vec.size, per))
        chunks = jnp.split(vec, cuts) if cuts else [vec]
        buckets.extend(chunks)
        groups.append((tuple(idxs), len(chunks)))
    spec = (treedef, tuple(shapes), tuple(dtypes), tuple(groups))
    return buckets, spec


def unflatten_buckets(buckets, spec) -> PyTree:
    """Inverse of `flatten_buckets`: reassemble the original pytree from the
    (possibly reduced/recast) buckets. Bucket dtypes are cast back to each
    leaf's recorded dtype, so a wire-compressed reduction round-trips."""
    import math as _math

    treedef, shapes, dtypes, groups = spec
    leaves: list = [None] * len(shapes)
    pos = 0
    for idxs, n_chunks in groups:
        chunks = buckets[pos : pos + n_chunks]
        pos += n_chunks
        vec = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks)
        off = 0
        for i in idxs:
            n = int(_math.prod(shapes[i]))
            leaves[i] = vec[off : off + n].reshape(shapes[i]).astype(dtypes[i])
            off += n
    if pos != len(buckets):
        raise ValueError(
            f"unflatten_buckets got {len(buckets)} buckets for a spec "
            f"describing {pos} — bucket list and spec do not match"
        )
    return jax.tree_util.tree_unflatten(treedef, leaves)


# --- ZeRO-1 (shard_update) layout + scatter-mode bucketing -----------------
#
# The sharded weight update (Xu et al., arXiv:2004.13336) shards each
# optimizer-state leaf along its first dp-divisible dimension over the data
# axis (`zero1_shard_dim` — the single source of the rule; training/build.py
# derives the opt-state init shardings from it). Scatter-mode reduction
# (`reduce_gradients(scatter=dp)`) lowers the boundary reduction INTO that
# layout: each dtype-homogeneous bucket is arranged as a [dp, cols] matrix
# whose row s is exactly shard s's slice of every leaf in the bucket
# (`flatten_scatter_buckets`), so one `lax.psum_scatter` hands every shard
# precisely the gradient slice its optimizer shard consumes — ~half the
# wire bytes of reduce-then-slice. Leaves with NO dp-divisible dimension
# (odd biases, scalars) are padded to a dp multiple and ride the SAME
# buckets as everyone else ("tail" pieces): the one reduce-scatter covers
# them too, and their full (replicated-mirror) values come back through a
# small all-gather of just their columns — a two-shot all-reduce, never a
# full-payload all-reduce op.
#
# Per-bucket schedulability (ISSUE 12 — the overlap-cash-in): each bucket
# is assembled ONLY from the leaf pieces it carries (leaf-aligned
# concatenation, never a slice of a whole-tree concat) and each leaf is
# reassembled ONLY from the buckets that carry it. The dataflow therefore
# has no cross-bucket dependency in either direction: inside the peeled
# backward's straight-line region, bucket i's `psum_scatter` can issue as
# soon as its leaves' gradients are final (reverse bucket order =
# last-produced-grads-first), and shard s's optimizer apply for bucket
# i's leaves can start as soon as bucket i lands — while bucket j's
# transfer is still in flight. The cut points are IDENTICAL to a
# concat-then-split at `bucket_bytes` (same bucket count, same values,
# bitwise), so the restructure changes schedulability, not arithmetic.


def zero1_shard_dim(shape, dp: int):
    """The dimension a ZeRO-1 (shard_update) layout shards over the data
    axis: the FIRST dp-divisible dim (dim 0 for the matmul kernels that
    dominate; conv kernels usually shard a channel dim), or None when no
    dim divides — the leaf (and its optimizer mirrors) stays replicated.
    THE shared rule: `training/build.py` derives the opt-state init
    shardings from it and the scatter-mode reduction derives the bucket
    layout — they cannot drift."""
    for i, dim in enumerate(shape):
        if dim % dp == 0:
            return i
    return None


def zero1_partition_spec(shape, dp: int, axis=None):
    """The `PartitionSpec` for a ZeRO-1-sharded leaf of ``shape`` (the
    data axis at `zero1_shard_dim`; fully replicated when no dim
    divides)."""
    from horovod_tpu.parallel import mesh as mesh_lib

    axis = axis or mesh_lib.DATA_AXIS
    i = zero1_shard_dim(shape, dp)
    if i is None:
        return jax.sharding.PartitionSpec()
    spec = [None] * len(shape)
    spec[i] = axis
    return jax.sharding.PartitionSpec(*spec)


def flatten_scatter_buckets(tree: PyTree, dp: int,
                            bucket_bytes: int | None = None,
                            *, reverse: bool = False):
    """Pack a pytree into scatter-ready dtype-homogeneous 1-D buckets.

    Leaves with a dp-divisible dim ("scatter" family) contribute their
    `zero1_shard_dim`-major [dp, size/dp] block matrix; leaves without
    one ("tail" family) are raveled, zero-padded to a dp multiple and
    reshaped likewise — both families share the SAME buckets, so ONE
    tiled ``psum_scatter`` per bucket covers every leaf (tail leaves'
    full values come back through a small all-gather of their columns
    only; see `bucket_tail_spans`). Per dtype the [dp, cols] leaf
    matrices pack greedily into buckets of at most ``bucket_bytes``
    (cut points at exact ``bucket_bytes`` column multiples — identical
    to a concat-then-split), but each bucket is ASSEMBLED only from the
    leaf pieces it carries: the dataflow carries no cross-bucket
    dependency, so bucket i's collective can issue the moment its
    leaves' gradients are final while earlier leaves are still in the
    backward. Returns ``(buckets, spec)``; the spec records, per
    bucket, the ordered ``(leaf_index, column_width)`` pieces."""
    if bucket_bytes is None:
        bucket_bytes = DEFAULT_BUCKET_BYTES
    bucket_bytes = int(bucket_bytes)
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be positive, got {bucket_bytes}")
    dp = int(dp)
    if dp < 1:
        raise ValueError(f"scatter shard count must be >= 1, got {dp}")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = [jnp.shape(l) for l in leaves]
    dtypes = [jnp.result_type(l) for l in leaves]
    sdims = [zero1_shard_dim(s, dp) for s in shapes]
    by_dtype: dict = {}  # dtype -> leaf indices, order-preserving
    order = range(len(dtypes) - 1, -1, -1) if reverse else range(len(dtypes))
    for i in order:
        by_dtype.setdefault(jnp.dtype(dtypes[i]), []).append(i)
    buckets: list = []
    descs: list = []  # per bucket: tuple of (leaf_index, column_width)
    for dt, idxs in by_dtype.items():
        per = max(1, bucket_bytes // (dp * dt.itemsize))  # columns/bucket
        pieces: list = []
        pdesc: list = []
        cols = 0

        def close(dt=dt):
            nonlocal pieces, pdesc, cols
            if pieces:
                mat = (
                    pieces[0] if len(pieces) == 1
                    else jnp.concatenate(pieces, axis=1)
                )
            else:  # zero-width leaves only
                mat = jnp.zeros((dp, 0), dt)
            buckets.append(jnp.ravel(mat))
            descs.append(tuple(pdesc))
            pieces, pdesc, cols = [], [], 0

        for i in idxs:
            a = jnp.asarray(leaves[i], dtype=dt)
            if sdims[i] is not None:
                m = jnp.moveaxis(a, sdims[i], 0).reshape(dp, -1)
            else:
                v = jnp.ravel(a)
                pad = (-v.size) % dp
                if pad:
                    v = jnp.concatenate([v, jnp.zeros((pad,), dt)])
                m = v.reshape(dp, -1)
            w = m.shape[1]
            if w == 0:
                pdesc.append((i, 0))
                continue
            off = 0
            while off < w:
                take = min(per - cols, w - off)
                pieces.append(
                    m if (off == 0 and take == w) else m[:, off: off + take]
                )
                pdesc.append((i, take))
                cols += take
                off += take
                if cols == per:
                    close()
        if pieces or pdesc:
            close()
    spec = (
        treedef, tuple(shapes), tuple(dtypes), tuple(sdims), dp,
        tuple(descs),
    )
    return buckets, spec


def bucket_families(spec) -> list:
    """Per-bucket family tags for a `flatten_scatter_buckets` spec, in
    bucket order: 'scatter' (every piece has a dp-divisible dim), 'tail'
    (none does), or 'mixed' (both ride the bucket)."""
    sdims = spec[3]
    fams = []
    for pieces in spec[5]:
        kinds = {
            "scatter" if sdims[i] is not None else "tail"
            for i, _w in pieces
        }
        fams.append(kinds.pop() if len(kinds) == 1 else
                    ("mixed" if kinds or len(pieces) else "scatter"))
    return fams


def bucket_tail_spans(spec) -> list:
    """Per bucket, the ordered ``(column_start, width)`` spans holding
    tail-family pieces (leaves with no dp-divisible dim) — the columns
    whose reduced rows must be all-gathered back to full values for the
    replicated optimizer mirrors. Empty tuple = pure-scatter bucket."""
    sdims = spec[3]
    out = []
    for pieces in spec[5]:
        col, spans = 0, []
        for i, w in pieces:
            if sdims[i] is None and w:
                spans.append((col, w))
            col += w
        out.append(tuple(spans))
    return out


def unflatten_scatter_buckets(entries, spec) -> PyTree:
    """Inverse of `flatten_scatter_buckets` AFTER a scatter reduction.

    Per bucket the entry is this shard's LOCAL reduced row (``[cols]``);
    a bucket carrying tail-family pieces takes a ``(local_row,
    gathered)`` pair, where ``gathered`` is the row-major ravel of the
    bucket's tail columns all-gathered back to ``[dp, tail_cols]``
    (`bucket_tail_spans` gives the spans, in the same order). Scatter
    leaves come back as the local zero1 block (shard dim divided by
    dp); tail leaves come back whole (padding stripped). Dtypes are
    restored per leaf. Each leaf is assembled ONLY from the buckets
    that carry it — the per-bucket schedulability contract's consumer
    side."""
    import math as _math

    treedef, shapes, dtypes, sdims, dp, descs = spec
    if len(entries) != len(descs):
        raise ValueError(
            f"unflatten_scatter_buckets got {len(entries)} buckets for a "
            f"spec describing {len(descs)} — bucket list and spec do not "
            "match"
        )
    parts: list[list] = [[] for _ in shapes]
    for entry, pieces in zip(entries, descs):
        if isinstance(entry, (tuple, list)):
            row, gathered = entry
        else:
            row, gathered = entry, None
        tail_cols = sum(w for i, w in pieces if sdims[i] is None)
        gm = None
        if tail_cols:
            if gathered is None:
                raise ValueError(
                    "bucket carries tail-family pieces but its entry is a "
                    "bare local row — pass (local_row, gathered_tails); "
                    "see bucket_tail_spans"
                )
            gm = jnp.reshape(gathered, (dp, tail_cols))
        col = tcol = 0
        for i, w in pieces:
            if w == 0:
                continue
            if sdims[i] is None:
                parts[i].append(gm[:, tcol: tcol + w])
                tcol += w
            else:
                parts[i].append(row[col: col + w])
            col += w
    leaves: list = [None] * len(shapes)
    for i, segs in enumerate(parts):
        if sdims[i] is not None:
            sd = sdims[i]
            rest = tuple(shapes[i][:sd]) + tuple(shapes[i][sd + 1:])
            blk = shapes[i][sd] // dp
            if not segs:
                vec = jnp.zeros((0,), dtypes[i])
            else:
                vec = segs[0] if len(segs) == 1 else jnp.concatenate(segs)
            moved = vec.reshape((blk,) + rest)
            leaves[i] = jnp.moveaxis(moved, 0, sd).astype(dtypes[i])
        else:
            n = int(_math.prod(shapes[i]))
            if not segs:
                flat = jnp.zeros((n,), dtypes[i])
            else:
                mat = (
                    segs[0] if len(segs) == 1
                    else jnp.concatenate(segs, axis=1)
                )
                flat = jnp.ravel(mat)[:n]
            leaves[i] = flat.reshape(shapes[i]).astype(dtypes[i])
    return jax.tree_util.tree_unflatten(treedef, leaves)


def unflatten_scatter_full(buckets, spec) -> PyTree:
    """Inverse of `flatten_scatter_buckets` from FULL (un-scattered)
    ``[dp * cols]`` buckets — the error-feedback residual path, where
    each shard keeps its own full-bucket quantization remainder. Scatter
    leaves un-moveaxis back to their original shape; tail leaves strip
    their padding."""
    import math as _math

    treedef, shapes, dtypes, sdims, dp, descs = spec
    if len(buckets) != len(descs):
        raise ValueError(
            f"unflatten_scatter_full got {len(buckets)} buckets for a "
            f"spec describing {len(descs)} — bucket list and spec do not "
            "match"
        )
    parts: list[list] = [[] for _ in shapes]
    for b, pieces in zip(buckets, descs):
        cols = sum(w for _i, w in pieces)
        m = jnp.reshape(b, (dp, cols))
        col = 0
        for i, w in pieces:
            if w == 0:
                continue
            parts[i].append(m[:, col: col + w])
            col += w
    leaves: list = [None] * len(shapes)
    for i, segs in enumerate(parts):
        if not segs:
            leaves[i] = jnp.zeros(shapes[i], dtypes[i])
            continue
        mat = segs[0] if len(segs) == 1 else jnp.concatenate(segs, axis=1)
        if sdims[i] is not None:
            sd = sdims[i]
            rest = tuple(shapes[i][:sd]) + tuple(shapes[i][sd + 1:])
            moved = mat.reshape((shapes[i][sd],) + rest)
            leaves[i] = jnp.moveaxis(moved, 0, sd).astype(dtypes[i])
        else:
            n = int(_math.prod(shapes[i]))
            leaves[i] = jnp.ravel(mat)[:n].reshape(
                shapes[i]
            ).astype(dtypes[i])
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _slice_zero1_local(tree: PyTree, dp: int, axis_name) -> PyTree:
    """Cut each leaf of a FULLY-REDUCED tree down to this shard's zero1
    block (traced context) — the quantized-wire scatter path, where the
    wire already delivered the whole tree (dense bucket layout, bitwise
    identical to the replicated reduction) and the sharded update only
    consumes the local slice. Leaves with no dp-divisible dim pass
    through replicated."""
    idx = _composite_axis_index(axis_name)

    def cut(l):
        sd = zero1_shard_dim(jnp.shape(l), dp)
        if sd is None:
            return l
        blk = jnp.shape(l)[sd] // dp
        return lax.dynamic_slice_in_dim(l, idx * blk, blk, axis=sd)

    return jax.tree.map(cut, tree)


def _compress16(orig_dtype, wire_dtype) -> bool:
    """True when ``wire_dtype`` is a plain cast wire (16-bit) narrower
    than the value's dtype — the compress-then-reduce hop form."""
    return (
        wire_dtype is not None
        and not is_quantized_wire(wire_dtype)
        and jnp.issubdtype(orig_dtype, jnp.floating)
        and jnp.dtype(wire_dtype).itemsize < jnp.dtype(orig_dtype).itemsize
    )


def _scatter_reduce_bucket(b, axis_name, dcn: int, wire_dtype, extra_axes,
                           *, ici_wire_dtype=None, residual=None):
    """Reduce-scatter ONE flat [dp*cols] scatter-arranged bucket over
    ``axis_name`` (two-hop over the dcn/ici factoring when ``dcn > 1``;
    the 16-bit wire dtype rides the DCN hop — or the single hop when flat
    — exactly like the replicated reduction). ``ici_wire_dtype``
    (`compression_ici`) rides the two-hop's ICI hop: a 16-bit dtype
    casts hop 1, a quantized (int8/fp8) dtype runs hop 1 as a
    per-bucket-scaled quantized reduce-scatter
    (`_quantized_matrix_reduce_scatter`) with the untransmitted
    remainder charged to this shard — single-hop (``dcn <= 1``)
    reductions have no ICI sub-hop, so the knob is inert there.

    ``residual`` (error feedback, full-bucket f32) is added to the
    bucket before any wire; when no quantized hop actually runs the
    residual is transmitted in full and the returned error is zero
    (flush semantics — mass is conserved either way).

    Returns ``(local_row, error)``: this shard's fully-reduced [cols]
    row in the bucket's dtype, and the full-bucket f32 untransmitted
    remainder (None when ``residual`` is None). A quantized DCN wire
    never reaches here (it keeps the dense-layout two-shot; see
    `reduce_gradients`)."""
    orig = b.dtype
    # Trivial (size-1) extra axes are elided STATICALLY: the lowered text
    # is what `hvt-audit` reads, and a singleton-group all-reduce there
    # would read as full-payload gradient traffic that the compiled
    # program never performs.
    extra = tuple(a for a in extra_axes if lax.axis_size(a) > 1)
    if extra:
        b = lax.psum(b, extra)
    if residual is not None:
        # Stay in f32 from here on: casting the residual-carrying value
        # back to a narrower bucket dtype would silently drop residual
        # mass the returned error never charges (the wire predicates
        # below key off ``orig``, the pre-residual dtype, and the final
        # result is cast back to it).
        b = b.astype(jnp.float32) + residual
    err = None
    if dcn <= 1:
        x = b.astype(wire_dtype) if _compress16(orig, wire_dtype) else b
        out = lax.psum_scatter(x, axis_name, tiled=True).astype(orig)
        if residual is not None:
            err = jnp.zeros(b.shape, jnp.float32)
        return out, err
    n = lax.axis_size(axis_name)
    ici = n // dcn
    ici_groups, dcn_groups = _hier_groups(n, dcn)
    cols = b.size // n
    # Rows are ordered by global (o*ici + i) target; hop 1 scatters the
    # ici index, so arrange target-inner-major first.
    t = b.reshape(dcn, ici, cols).transpose(1, 0, 2).reshape(-1)
    if ici > 1:
        # Branch condition is trace-time config (wire dtype + value
        # dtype), identical on every rank: the whole fleet takes the
        # same arm and submits the same collective order.
        if is_quantized_wire(ici_wire_dtype) and jnp.issubdtype(  # hvt: noqa[HVT007] config-uniform
            orig, jnp.floating
        ):
            mat = t.astype(jnp.float32).reshape(ici, dcn * cols)
            part, e1 = _quantized_matrix_reduce_scatter(
                mat, axis_name, ici_wire_dtype,
                axis_index_groups=ici_groups,
            )  # part: [dcn*cols] f32; e1: [ici, dcn*cols] this shard's
            if residual is not None:
                # Back from target-inner-major to bucket order.
                err = e1.reshape(ici, dcn, cols).transpose(
                    1, 0, 2
                ).reshape(-1)
        elif _compress16(orig, ici_wire_dtype):
            part = lax.psum_scatter(
                t.astype(ici_wire_dtype), axis_name,
                axis_index_groups=ici_groups, tiled=True,
            ).astype(orig)
        else:
            part = lax.psum_scatter(
                t, axis_name, axis_index_groups=ici_groups, tiled=True
            )  # [dcn*cols]: partials for targets (·, own ici index)
    else:
        part = t
    y = part.astype(wire_dtype) if _compress16(orig, wire_dtype) else part
    out = lax.psum_scatter(
        y, axis_name, axis_index_groups=dcn_groups, tiled=True
    )
    if residual is not None and err is None:
        err = jnp.zeros(b.shape, jnp.float32)
    return out.astype(orig), err


def _hier_groups(n: int, dcn: int) -> tuple[list, list]:
    """Index groups factoring an axis of size ``n`` as (dcn outer, ici
    inner) — the layout `mesh_utils.create_hybrid_device_mesh` builds, where
    the slice (DCN) factor is the outer block of each factored axis."""
    ici = n // dcn
    ici_groups = [[d * ici + i for i in range(ici)] for d in range(dcn)]
    dcn_groups = [[d * ici + i for d in range(dcn)] for i in range(ici)]
    return ici_groups, dcn_groups


#: Quantized wire formats: dtype -> the format's largest representable
#: magnitude (the per-bucket scale denominator). int8 keeps the symmetric
#: [-127, 127] grid; fp8 is e4m3 (max finite 448 — the gradient-friendly
#: variant; e5m2's extra exponent bits buy nothing once a per-bucket scale
#: normalizes the range).
_QUANTIZED_QMAX = {
    jnp.dtype(jnp.int8): 127.0,
    jnp.dtype(jnp.float8_e4m3fn): 448.0,
}


def is_quantized_wire(wire_dtype) -> bool:
    """True when ``wire_dtype`` needs the gather-sum quantized reduction
    (int8/fp8) rather than a plain cast-then-psum (bf16/fp16)."""
    return (
        wire_dtype is not None and jnp.dtype(wire_dtype) in _QUANTIZED_QMAX
    )


def _quantize(v, wire_dtype):
    """(payload, scale): ``v`` scaled by one per-bucket scalar onto the wire
    grid. ``scale`` is f32; an all-zero bucket quantizes to zeros with
    scale 0 (the dequantized sum is then exactly zero, no 0/0)."""
    qmax = _QUANTIZED_QMAX[jnp.dtype(wire_dtype)]
    amax = jnp.max(jnp.abs(v)).astype(jnp.float32)
    scale = amax / qmax
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    scaled = jnp.clip(v.astype(jnp.float32) * inv, -qmax, qmax)
    if jnp.dtype(wire_dtype) == jnp.dtype(jnp.int8):
        payload = jnp.round(scaled).astype(jnp.int8)
    else:
        payload = scaled.astype(wire_dtype)
    return payload, scale


def _dequantize(payload, scale):
    return payload.astype(jnp.float32) * scale


def _composite_axis_index(axis_name):
    """This shard's position in the (possibly multi-axis) group, row-major
    over the axis tuple — the order `lax.all_gather` stacks group members
    in (verified on the compat floor)."""
    names = _axis_names(axis_name)
    idx = lax.axis_index(names[0])
    for name in names[1:]:
        idx = idx * lax.axis_size(name) + lax.axis_index(name)
    return idx


def _group_size(axis_name, axis_index_groups) -> int:
    if axis_index_groups is not None:
        return len(axis_index_groups[0])
    n = 1
    for name in _axis_names(axis_name):
        n *= lax.axis_size(name)
    return n


def _quantized_gather_sum(v, axis_name, wire_dtype, *,
                          axis_index_groups=None):
    """The PR 7 one-shot gather-sum (kept as the equivalence reference for
    `quantized_group_sum`, and to document what the two-shot replaced):
    every shard all-gathers every other shard's quantized payload and
    dequantize-sums locally — correct, but the receive bytes are
    group_size x the payload. Returns ``(sum_f32, own_error)``."""
    payload, scale = _quantize(v, wire_dtype)
    own = _dequantize(payload, scale)
    gathered = lax.all_gather(
        payload, axis_name, axis_index_groups=axis_index_groups
    )
    scales = lax.all_gather(
        scale, axis_name, axis_index_groups=axis_index_groups
    )
    scales = scales.reshape((-1,) + (1,) * (gathered.ndim - 1))
    total = jnp.sum(gathered.astype(jnp.float32) * scales, axis=0)
    return total, v.astype(jnp.float32) - own


def quantized_group_sum(v, axis_name, wire_dtype, *, axis_index_groups=None,
                        group_position=None):
    """Sum ``v`` across ``axis_name`` (optionally in ``axis_index_groups``)
    with only wire-dtype bytes crossing the interconnect — as a TWO-SHOT
    reduce-scatter + all-gather (the ROADMAP item-2 seam closed).

    Shot 1 (quantized reduce-scatter): the bucket is padded to a
    group-size multiple, cut into one chunk per group member, quantized
    with ONE per-bucket scale and moved by `lax.all_to_all` — every member
    receives each peer's quantized contribution to ITS chunk only and
    dequantize-sums in f32 (sub-16-bit partial sums never exist, so int8
    cannot overflow mid-reduction). Shot 2 (quantized all-gather): each
    member re-quantizes its reduced chunk and all-gathers the (payload,
    scale) pair. Per-member receive bytes are therefore ~2x the payload
    (one all-to-all + one all-gather) instead of the one-shot gather-sum's
    group_size x (`_quantized_gather_sum`, the PR 7 wire this replaces).

    ``group_position`` is this member's index within its group (required
    with ``axis_index_groups``; derived from the axis indices otherwise) —
    the chunk it owns, where the shot-2 re-quantization error is charged.

    Returns ``(sum_f32, own_error)`` where ``own_error`` is THIS shard's
    untransmitted remainder — its shot-1 quantization error everywhere,
    plus the shot-2 re-quantization error of the chunk it owns — so the
    error-feedback telescoping identity is unchanged: summed over the
    group, the errors equal (true sum − delivered sum) exactly."""
    _maybe_record("quantized_group_sum", value=v)
    if group_position is None:
        if axis_index_groups is not None:
            raise ValueError(
                "quantized_group_sum with axis_index_groups needs the "
                "caller's group_position (the member's index within its "
                "group) — it cannot be derived from the axis index alone"
            )
        group_position = _composite_axis_index(axis_name)
    g = _group_size(axis_name, axis_index_groups)
    shape = jnp.shape(v)
    flat = jnp.ravel(v).astype(jnp.float32)
    n = flat.size
    pad = (-n) % g
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    mat = flat.reshape(g, -1)  # row j = the chunk group-member j owns
    # Shot 1: the quantized reduce-scatter (shared with the scatter
    # path's ICI hop, `_quantized_matrix_reduce_scatter`).
    chunk, err1 = _quantized_matrix_reduce_scatter(
        mat, axis_name, wire_dtype, axis_index_groups=axis_index_groups
    )
    # Shot 2: re-quantize the reduced chunk and gather the group's chunks.
    p2, s2 = _quantize(chunk, wire_dtype)
    dq2 = _dequantize(p2, s2)
    gathered = lax.all_gather(
        p2, axis_name, axis_index_groups=axis_index_groups
    )
    s2s = lax.all_gather(s2, axis_name, axis_index_groups=axis_index_groups)
    total = (
        gathered.astype(jnp.float32) * s2s.reshape((-1, 1))
    ).reshape(-1)
    # Untransmitted remainder: shot-1 error on every element this shard
    # fed in, plus the shot-2 error of the chunk it owns (padding
    # contributes exactly zero to both).
    err = err1.at[group_position].add(chunk - dq2)
    total = total[:n].reshape(shape)
    err = err.reshape(-1)[:n].reshape(shape)
    return total, err


def _quantized_matrix_reduce_scatter(mat, axis_name, wire_dtype, *,
                                     axis_index_groups=None):
    """The quantized reduce-scatter shot shared by `quantized_group_sum`
    (shot 1 of the two-shot replicated wire) and the scatter path's
    quantized ICI hop (`_scatter_reduce_bucket` with a quantized
    ``compression_ici``).

    ``mat`` is this member's f32 ``[g, chunk]`` contribution matrix —
    row j is the slice group-member j owns. The whole matrix is
    quantized with ONE per-bucket scale, moved by `lax.all_to_all`
    (every member receives each peer's quantized contribution to ITS
    chunk only — the only payload bytes on the wire) and
    dequantize-summed in f32, so sub-16-bit partial sums never exist.
    Returns ``(chunk_sum_f32, error)``: this member's reduced ``[chunk]``
    row and its full ``[g, chunk]`` untransmitted remainder (what error
    feedback must carry)."""
    payload, scale = _quantize(mat, wire_dtype)
    own = _dequantize(payload, scale)
    recv = lax.all_to_all(
        payload, axis_name, split_axis=0, concat_axis=0,
        axis_index_groups=axis_index_groups, tiled=True,
    )
    scales = lax.all_gather(
        scale, axis_name, axis_index_groups=axis_index_groups
    )
    chunk = jnp.sum(
        recv.astype(jnp.float32) * scales.reshape((-1, 1)), axis=0
    )
    return chunk, mat.astype(jnp.float32) - own


def hierarchical_psum(x, axis_name, dcn: int, *, extra_axes=(),
                      wire_dtype=None, ici_wire_dtype=None):
    """Two-hop psum over ``axis_name`` factored as (dcn outer, ici inner),
    traced context only (inside shard_map/pmap).

    Hop 1 (ICI): sum over ``extra_axes`` and the ici subgroups of
    ``axis_name`` — intra-slice traffic. Full precision by default;
    ``ici_wire_dtype`` (`compression_ici`) puts a wire on this hop too —
    a 16-bit dtype casts it, a quantized (int8/fp8) dtype runs it as
    `quantized_group_sum` over the ici subgroups (EQuARX's aggressive
    tier applied intra-slice, for the topologies where even ICI is the
    bottleneck). Hop 2 (DCN): cast to ``wire_dtype`` (when given), sum
    across the dcn subgroups — the only bytes that cross the slow
    interconnect — and cast back. Equals the flat
    ``psum(x, (axis_name, *extra_axes))`` exactly when both wires are
    None (sum is associative); with a 16-bit wire dtype the delta is the
    cast on the already-reduced partials. A QUANTIZED wire dtype runs
    its hop as `quantized_group_sum` — per-bucket-scaled wire bytes, f32
    receiver-side accumulation; pass ``residual=`` via `reduce_gradients`
    to carry the error feedback, charged PER HOP (each quantized hop
    contributes its own untransmitted remainder, so the telescoping mass
    identity stays exact across the two-level factoring)."""
    _maybe_record("hierarchical_psum", value=x)
    out, _ = _hierarchical_psum_err(
        x, axis_name, dcn, extra_axes=extra_axes, wire_dtype=wire_dtype,
        ici_wire_dtype=ici_wire_dtype,
    )
    return out


def _hierarchical_psum_err(x, axis_name, dcn: int, *, extra_axes=(),
                           wire_dtype=None, ici_wire_dtype=None,
                           residual=None):
    """`hierarchical_psum` body, also returning this shard's quantization
    error (None for residual-free calls). ``residual`` (error feedback)
    is added to the FIRST quantized hop's input before quantization —
    hop 1 when the ICI wire is quantized, hop 2 otherwise — and each
    quantized hop charges its own error, summed into the returned
    remainder (the per-hop telescoping contract). A residual with no
    quantized hop anywhere is flushed: transmitted in full, zero error
    back."""
    n = lax.axis_size(axis_name)
    if n % dcn != 0:
        raise ValueError(
            f"dcn factor {dcn} does not divide axis {axis_name!r} size {n}"
        )
    orig = x.dtype
    floating = jnp.issubdtype(orig, jnp.floating)
    quantize_dcn = is_quantized_wire(wire_dtype) and floating
    quantize_ici = (
        is_quantized_wire(ici_wire_dtype) and floating and n > dcn
    )
    ici_groups, dcn_groups = _hier_groups(n, dcn)
    ici = n // dcn
    if extra_axes:
        x = lax.psum(x, tuple(extra_axes))
    if residual is not None and not (quantize_dcn or quantize_ici):
        # Flush: an exact wire transmits the whole remainder (kept in
        # f32 so no residual mass rounds away uncharged; the result is
        # cast back to ``orig`` at return).
        x = x.astype(jnp.float32) + residual
        residual = None
        err = jnp.zeros(jnp.shape(x), jnp.float32)
    else:
        err = None
    # quantize_ici/quantize_dcn are trace-time config (wire dtypes +
    # value dtype), identical on every rank: the fleet takes the same
    # arm and submits the same collective order.
    if quantize_ici:  # hvt: noqa[HVT007] config-uniform branch
        v = x.astype(jnp.float32)
        if residual is not None:
            v = v + residual
            residual = None  # consumed at the first quantized hop
        # Position within the ici group: groups hold a fixed outer
        # (slice) index d with the inner index i varying — i = global
        # mod ici.
        x, e1 = quantized_group_sum(
            v, axis_name, ici_wire_dtype, axis_index_groups=ici_groups,
            group_position=lax.axis_index(axis_name) % ici,
        )
        err = e1 if err is None else err + e1
    elif n > dcn:  # ici sub-axis is non-trivial
        if _compress16(orig, ici_wire_dtype):
            x = lax.psum(
                x.astype(ici_wire_dtype), axis_name,
                axis_index_groups=ici_groups,
            ).astype(orig)
        else:
            x = lax.psum(x, axis_name, axis_index_groups=ici_groups)
    if quantize_dcn:
        v = x.astype(jnp.float32)
        if residual is not None:
            v = v + residual
        # Position within the dcn group: groups hold a fixed ici index i
        # with the outer (slice) index d varying — d = global // ici.
        total, e2 = quantized_group_sum(
            v, axis_name, wire_dtype, axis_index_groups=dcn_groups,
            group_position=lax.axis_index(axis_name) // ici,
        )
        err = e2 if err is None else err + e2
        return total.astype(orig), err
    if _compress16(orig, wire_dtype):
        x = x.astype(wire_dtype)
    x = lax.psum(x, axis_name, axis_index_groups=dcn_groups)
    return x.astype(orig), err


def reduce_gradients(tree: PyTree, *, data_axis=None, extra_axes=(),
                     dcn: int = 1, wire_dtype=None, ici_wire_dtype=None,
                     bucket_bytes: int | None = None,
                     reverse: bool = False, residual: PyTree | None = None,
                     scatter: int | None = None):
    """The boundary gradient reduction: bucket-fused, hierarchical when the
    mesh is multi-slice, wire-compressed. SUM semantics — callers divide by
    world size (and the accumulation factor) themselves.

    Traced context only (inside the explicit-collective shard_map step).
    ``tree`` is bucketed (`flatten_buckets`), each bucket reduced —
    ``hierarchical_psum`` over (``data_axis`` factored by ``dcn``) +
    ``extra_axes`` when ``dcn > 1``; a flat psum over all axes, cast to
    ``wire_dtype`` first (compress-then-reduce, Horovod Compression.fp16
    semantics) — or a `quantized_group_sum` for int8/fp8 wires — when
    ``dcn == 1``; and the tree restored. The collective count is therefore
    the bucket count: at most
    ``ceil(total_bytes / bucket_bytes) + n_dtypes - 1`` reductions per call
    regardless of how many leaves the model has.

    ``reverse=True`` buckets AND issues the reductions last-leaf-first
    (Horovod's fusion order — overlappable with the producing backward;
    elementwise-identical results for non-quantized wires, since bucket
    boundaries never mix values).

    ``ici_wire_dtype`` (`compression_ici`): a wire for the two-hop
    factoring's ICI hop only (inert when ``dcn <= 1`` or the ici
    sub-axis is trivial) — 16-bit dtypes cast it, int8/fp8 run it
    quantized with the error charged per hop. See `hierarchical_psum`.

    ``residual``: error-feedback state for quantized wires — a pytree
    matching ``tree`` (f32 leaves). It is added to each bucket's
    pre-quantization value and the call returns ``(reduced_tree,
    new_residual_tree)`` where the new residual is this shard's
    untransmitted quantization remainder, summed over the quantized
    hops (per-hop charging keeps the telescoping mass identity exact);
    without it the return is just the reduced tree (and quantization
    bias goes uncorrected). A residual with no quantized hop anywhere
    is flushed (transmitted in full, zero remainder back).

    ``scatter``: the ZeRO-1 (shard_update) shard count — lower the
    reduction INTO the sharded weight-update layout: leaves with a
    dp-divisible dim come back as this shard's LOCAL zero1 block (the
    slice `training/build.py`'s opt-state layout consumes), the rest
    replicated. Non-quantized wires run every bucket as ONE
    `psum_scatter` (two-hop over dcn, wire dtype on the DCN hop, the
    ICI-hop wire when given) — ~half the bytes of reduce-then-slice —
    with tail-family leaves riding the same buckets and their full
    values all-gathered back from just their columns (no full-payload
    all-reduce anywhere). Buckets are leaf-aligned in BOTH directions
    (see `flatten_scatter_buckets`): inside the overlap peel's
    straight-line region each bucket's scatter issues as soon as its
    gradients are final, and each shard's optimizer apply for that
    bucket's leaves can start as soon as it lands. Quantized DCN wires
    keep the dense bucket layout through the two-shot
    `quantized_group_sum` — BITWISE identical to the replicated
    reduction, so the composed trajectory equals the dense control —
    and slice locally (the wire is already ~2x payload; re-cutting
    buckets to the zero1 layout would change per-bucket scales, i.e.
    the training numerics, for zero byte win)."""
    from horovod_tpu.parallel import mesh as mesh_lib

    data_axis = data_axis or mesh_lib.DATA_AXIS
    if scatter is not None and int(scatter) > 1:
        return _reduce_gradients_scatter(
            tree, int(scatter), data_axis=data_axis, extra_axes=extra_axes,
            dcn=dcn, wire_dtype=wire_dtype, ici_wire_dtype=ici_wire_dtype,
            bucket_bytes=bucket_bytes, reverse=reverse, residual=residual,
        )
    buckets, spec = flatten_buckets(tree, bucket_bytes, reverse=reverse)
    res_buckets = [None] * len(buckets)
    if residual is not None:
        res_buckets, _ = flatten_buckets(
            residual, bucket_bytes, reverse=reverse
        )
        # The residual is bucketed by ITS leaves' dtype grouping (all
        # f32); a mixed-dtype gradient tree would group differently and
        # the two bucket lists would silently misalign — require
        # identical boundaries (the trainer casts grads to f32 before
        # reducing, so its buckets always align).
        if [jnp.shape(b) for b in res_buckets] != [
            jnp.shape(b) for b in buckets
        ]:
            raise ValueError(
                "error-feedback residual buckets do not align with the "
                "gradient buckets — the residual (f32 leaves) must "
                "bucket identically to the gradient tree; cast the "
                "gradients to float32 before reduce_gradients"
            )

    def reduce_one(b, r, bucket_id):
        _maybe_record("reduce_gradients", value=b, bucket=bucket_id)
        orig = b.dtype
        if dcn > 1:
            return _hierarchical_psum_err(
                b, data_axis, dcn, extra_axes=extra_axes,
                wire_dtype=wire_dtype, ici_wire_dtype=ici_wire_dtype,
                residual=r,
            )
        if is_quantized_wire(wire_dtype) and jnp.issubdtype(
            orig, jnp.floating
        ):
            v = b.astype(jnp.float32)
            if r is not None:
                v = v + r
            total, err = quantized_group_sum(
                v, (data_axis, *extra_axes), wire_dtype
            )
            return total.astype(orig), err
        if r is not None:
            # Residual with an exact single-hop wire (an ICI-quantized
            # config on a single-slice mesh): flush — transmitted in
            # full (f32 carries the whole remainder), zero back.
            b = b.astype(jnp.float32) + r
        if _compress16(orig, wire_dtype):
            b = b.astype(wire_dtype)
        out = lax.psum(b, (data_axis, *extra_axes)).astype(orig)
        return out, (None if r is None else jnp.zeros(jnp.shape(r),
                                                      jnp.float32))

    # Explicit loop, not a comprehension: reduce_one's flight record
    # derives its caller tag from the frame two levels up, and a
    # comprehension frame would tag the evidence '<listcomp>' (and
    # differently across interpreter versions — PEP 709 inlines it).
    reduced, errors = [], []
    for i, (b, r) in enumerate(zip(buckets, res_buckets)):
        out_b, err_b = reduce_one(b, r, i)
        reduced.append(out_b)
        errors.append(err_b)
    out = unflatten_buckets(list(reduced), spec)
    if residual is None:
        return out
    new_res = unflatten_buckets(
        [
            e if e is not None else jnp.zeros_like(r)
            for e, r in zip(errors, res_buckets)
        ],
        spec,
    )
    # The residual tree mirrors the GRADIENT tree's dtypes through the
    # spec; force f32 leaves (error mass must not round through a 16-bit
    # parameter dtype between steps).
    new_res = jax.tree.map(lambda e: e.astype(jnp.float32), new_res)
    return out, new_res


def _reduce_gradients_scatter(tree: PyTree, dp: int, *, data_axis,
                              extra_axes, dcn, wire_dtype, ici_wire_dtype,
                              bucket_bytes, reverse, residual):
    """`reduce_gradients(scatter=dp)` body — see its docstring. Returns
    the zero1-local tree (scatter leaves as local blocks, tail leaves
    replicated), with the new residual tree appended for error-feedback
    callers."""
    leaves = jax.tree_util.tree_leaves(tree)
    floating = all(
        jnp.issubdtype(jnp.result_type(l), jnp.floating) for l in leaves
    )
    if is_quantized_wire(wire_dtype) and floating:
        # Dense-layout quantized DCN wire (bitwise-identical arithmetic
        # to the replicated path, residual and all), then the free local
        # cut.
        reduced = reduce_gradients(
            tree, data_axis=data_axis, extra_axes=extra_axes, dcn=dcn,
            wire_dtype=wire_dtype, ici_wire_dtype=ici_wire_dtype,
            bucket_bytes=bucket_bytes, reverse=reverse, residual=residual,
        )
        if residual is None:
            return _slice_zero1_local(reduced, dp, data_axis)
        out, new_res = reduced
        return _slice_zero1_local(out, dp, data_axis), new_res
    if residual is not None and not is_quantized_wire(ici_wire_dtype):
        raise ValueError(
            "error-feedback residuals require a quantized wire dtype "
            "(int8/fp8) on one of the hops; non-quantized scatter "
            "reductions are lossless and carry no residual"
        )
    buckets, spec = flatten_scatter_buckets(
        tree, dp, bucket_bytes, reverse=reverse
    )
    res_buckets: list = [None] * len(buckets)
    if residual is not None:
        res_buckets, _ = flatten_scatter_buckets(
            residual, dp, bucket_bytes, reverse=reverse
        )
        if [jnp.shape(b) for b in res_buckets] != [
            jnp.shape(b) for b in buckets
        ]:
            raise ValueError(
                "error-feedback residual buckets do not align with the "
                "gradient buckets — the residual (f32 leaves) must "
                "bucket identically to the gradient tree; cast the "
                "gradients to float32 before reduce_gradients"
            )
    spans = bucket_tail_spans(spec)
    entries: list = []
    errors: list = []
    # Bucket-by-bucket, reverse order already baked into the spec: each
    # loop iteration's collective depends ONLY on its own leaves (leaf-
    # aligned assembly), so inside the overlap peel's straight-line
    # region XLA's latency-hiding scheduler can issue bucket i's
    # psum_scatter while earlier leaves' backward still computes, and
    # start bucket i's shard-local optimizer math as soon as it lands.
    for i, (b, r, sp) in enumerate(zip(buckets, res_buckets, spans)):
        _maybe_record("reduce_gradients_scatter", value=b, bucket=i)
        loc, err = _scatter_reduce_bucket(
            b, data_axis, dcn, wire_dtype, extra_axes,
            ici_wire_dtype=ici_wire_dtype, residual=r,
        )
        if sp:
            # Tail-family pieces (replicated mirrors) need full values
            # back: all-gather JUST their columns — with the scatter
            # above, a two-shot all-reduce that never puts a full
            # payload through one collective.
            tail_local = (
                loc[sp[0][0]: sp[0][0] + sp[0][1]] if len(sp) == 1
                else jnp.concatenate(
                    [loc[c: c + w] for c, w in sp]
                )
            )
            gathered = lax.all_gather(tail_local, data_axis, tiled=True)
            entries.append((loc, gathered))
        else:
            entries.append(loc)
        errors.append(err)
    out = unflatten_scatter_buckets(entries, spec)
    if residual is None:
        return out
    new_res = unflatten_scatter_full(
        [
            e if e is not None else jnp.zeros(jnp.shape(b), jnp.float32)
            for e, b in zip(errors, buckets)
        ],
        spec,
    )
    new_res = jax.tree.map(lambda e: e.astype(jnp.float32), new_res)
    return out, new_res


def metric_mean(metrics: dict, axis_name=None) -> dict:
    """Cross-worker mean of a metrics dict — MetricAverageCallback's op
    (tensorflow2_keras_mnist.py:73-77)."""
    averaged = pmean_pytree(
        {k: jnp.asarray(v, jnp.float32) for k, v in metrics.items()},
        axis_name=axis_name,
    )
    return {k: float(v) for k, v in averaged.items()}
