"""The Trainer's feeding, evaluation and prediction paths.

Split out of trainer.py (round 5). Everything input-side lives here: batch
sharding onto the mesh (custom batch_specs included), the multi-process
feed-group layout, the streamed fit path (prefetched, steps_per_execution
chunking), the device-cached fit/eval paths (datasets staged into HBM,
whole epochs as one dispatch), epoch bookkeeping, and the padded/masked
slice contract shared by evaluate and predict. Functions take the Trainer
instance; the Trainer's public verbs delegate here.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu import runtime
from horovod_tpu.data.loader import ArrayDataset, training_pipeline
from horovod_tpu.parallel import mesh as mesh_lib
from horovod_tpu.parallel import sharding as sharding_lib
from horovod_tpu.training.train_state import (
    _run_train_end,
    _teardown_callbacks,
)


def _with_env_callbacks(callbacks):
    """User callbacks + env-requested ones (heartbeat / fault injection —
    `callbacks.env_callbacks`). Appended last so liveness/chaos hooks see
    the epoch state the user's callbacks produced; applied on every fit
    path so supervised launches need no entry-script changes."""
    from horovod_tpu.training import callbacks as callbacks_lib

    return list(callbacks) + callbacks_lib.env_callbacks()


def shard_batch(trainer, batch):
    if trainer.batch_specs is not None:
        specs = tuple(trainer.batch_specs)

        def put(x, spec):
            return sharding_lib.put_global(
                x, jax.sharding.NamedSharding(trainer.mesh, spec)
            )

        def put_part(part, spec):
            # One batch part against its spec: a single PartitionSpec
            # broadcasts over a pytree part (dict-input models), a
            # matching spec pytree maps pairwise.
            if isinstance(spec, jax.sharding.PartitionSpec):
                return jax.tree.map(lambda a: put(a, spec), part)
            return jax.tree.map(put, part, spec)

        if not isinstance(batch, (tuple, list)):
            return put_part(batch, specs[0])  # predict: bare x
        if len(batch) == len(specs) + 1:
            # evaluate() appends a per-example mask: batch-sharded only.
            last = tuple(specs[-1])
            specs = specs + (
                jax.sharding.PartitionSpec(*last[:1]) if last
                else jax.sharding.PartitionSpec(),
            )
        return tuple(
            put_part(x, spec) for x, spec in zip(batch, specs)
        )
    return sharding_lib.shard_batch(batch, trainer.mesh)

def feed_groups(trainer) -> tuple[int, int]:
    """(n_groups, my_group): how processes map onto the data axis.

    Processes feed batches in ``min(world, dp_size)`` distinct groups.
    With dp >= world (the usual DP deployment) every process is its own
    group. With dp < world (model-parallel-only meshes spanning
    processes, e.g. pipe=2 over 2 hosts) several processes share one
    data shard and MUST feed identical rows — the batch is logically
    replicated across the non-data axes, and divergent per-process
    contributions would silently give each device different contents
    for the same global array."""
    world = runtime.process_count()
    dp = trainer.dp_size
    groups = min(world, dp)
    if world % groups != 0 or (dp >= world and dp % world != 0):
        # e.g. 3 processes over dp=2: some rank would straddle two data
        # shards and the grouping below would slice out-of-range rows —
        # fail loudly instead of feeding wrong data.
        raise ValueError(
            f"process count ({world}) and data-parallel degree ({dp}) "
            "must divide one another for a coherent feeding layout"
        )
    per_group = world // groups
    return groups, runtime.process_rank() // per_group

def local_slice(trainer, arr, global_batch: int):
    """This feed-group's share of a globally-indexed batch — what
    `make_array_from_process_local_data` expects as the local
    contribution (each example fed exactly once across the data axis;
    processes sharing a data shard contribute identical rows)."""
    if runtime.process_count() == 1:
        return arr
    groups, group = feed_groups(trainer)
    local = global_batch // groups
    return arr[group * local : (group + 1) * local]

def stage_sharded(trainer, arr, per_shard: int):
    """Stage one host array as [n_shards, per_shard, ...] in HBM,
    example-sharded over the data axes: shard s takes rows
    [s*per_shard, (s+1)*per_shard); multi-process, each feed group
    contributes the rows for its chips (processes sharing a data shard
    stage identical rows — see _feed_groups)."""
    groups, group = feed_groups(trainer)
    local_shards = trainer.dp_size // groups
    arr = np.asarray(arr)
    lo = group * local_shards * per_shard
    hi = (group + 1) * local_shards * per_shard
    local = arr[lo:hi].reshape((local_shards, per_shard) + arr.shape[1:])
    spec = jax.sharding.PartitionSpec(
        (mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS),
        *([None] * arr.ndim),
    )
    return sharding_lib.put_global(
        local, jax.sharding.NamedSharding(trainer.mesh, spec)
    )

def stage_device_dataset(trainer, x, y):
    """Stage (x, y) into HBM as [n_shards, per_shard_n, ...] leaves,
    example-sharded over the data axes (truncated to divide evenly)."""
    n_shards = trainer.dp_size
    n = (len(x) // n_shards) * n_shards
    if n == 0:
        raise ValueError(f"need at least {n_shards} examples")
    per_shard = n // n_shards
    return (
        stage_sharded(trainer, np.asarray(x)[:n], per_shard),
        stage_sharded(trainer, np.asarray(y)[:n], per_shard),
    ), per_shard

def shard_chunk(trainer, chunk, lead: int = 1):
    """Place a stacked host batch onto the mesh — ``lead`` unsharded
    leading axes ([K, batch, ...] for steps_per_execution scans, lead=1;
    [C, K, batch, ...] for chunked microbatch-accumulation feeds, lead=2);
    the scan/microbatch axes stay unsharded."""
    if trainer.batch_specs is not None:
        specs = tuple(trainer.batch_specs)

        def put(x, spec):
            return sharding_lib.put_global(
                x,
                jax.sharding.NamedSharding(
                    trainer.mesh,
                    jax.sharding.PartitionSpec(
                        *([None] * lead), *tuple(spec)
                    ),
                ),
            )

        return tuple(put(x, spec) for x, spec in zip(chunk, specs))
    return sharding_lib.shard_chunk(chunk, trainer.mesh, lead)

def slice_pad(trainer, part, start: int, global_batch: int):
    """(batch slice padded to the compiled shape, true row count) for
    one batch part — leaf-wise, so pytree (dict-input) parts feed like
    flat arrays. ONE implementation of the multi-process padding
    contract, shared by evaluate and predict."""
    sliced = jax.tree.map(
        lambda a: np.asarray(a[start : start + global_batch]), part
    )
    bs = len(jax.tree_util.tree_leaves(sliced)[0])
    if bs < global_batch:
        pad = global_batch - bs
        sliced = jax.tree.map(
            lambda a: np.concatenate([a, np.repeat(a[-1:], pad, 0)]),
            sliced,
        )
    return sliced, bs

def finish_epoch(trainer, epoch, epochs, metric_acc, steps, t0, callbacks,
    validation_data, batch_size, verbose, val_cache=None,
):
    """Epoch bookkeeping shared by every fit path: ONE host fetch of the
    in-step metric sums, optional validation, callbacks, history."""
    sums = jax.device_get(metric_acc)
    logs = {k: float(v) / steps for k, v in sums.items()}
    logs["epoch_time_s"] = time.perf_counter() - t0
    if validation_data is not None:
        val = run_evaluate(trainer, 
            validation_data[0], validation_data[1],
            batch_size=batch_size, verbose=0, cache=val_cache,
        )
        logs.update({f"val_{k}": v for k, v in val.items()})
    for cb in callbacks:
        cb.on_epoch_end(epoch, logs)
    trainer.history.append(logs)
    if verbose:
        shown = {k: round(v, 4) for k, v in logs.items()}
        print(f"Epoch {epoch + 1}/{epochs} - {shown}")

def _accepts_anchoring(batches_fn) -> bool:
    """Whether a duck-typed ``batches`` hook takes the anchored
    ``start_epoch``/``batches_per_epoch`` keywords (explicitly or via
    ``**kwargs``) — decided from the signature so a TypeError raised
    INSIDE the source is never mistaken for 'not anchored'."""
    import inspect

    try:
        params = inspect.signature(batches_fn).parameters
    except (TypeError, ValueError):
        return False
    if any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
    ):
        return True
    return {"start_epoch", "batches_per_epoch"} <= set(params)


def _normalize_resume(initial_epoch: int, initial_step: int,
                      steps_per_epoch: int) -> tuple[int, int]:
    """Canonicalize a resume point against this run's epoch geometry: a
    step at or past the epoch's end rolls into the next epoch (a commit
    taken at the last step boundary of an epoch IS the next epoch's
    start), so callers may hand back exactly what the elastic commit or
    checkpoint manifest recorded without special-casing the boundary."""
    initial_epoch = int(initial_epoch)
    initial_step = int(initial_step)
    if initial_step < 0:
        raise ValueError(f"initial_step must be >= 0, got {initial_step}")
    if initial_step and steps_per_epoch:
        initial_epoch += initial_step // steps_per_epoch
        initial_step %= steps_per_epoch
    return initial_epoch, initial_step


def run_fit(trainer,
    dataset=None,
    *,
    x=None,
    y=None,
    batch_size: int = 128,
    epochs: int = 1,
    initial_epoch: int = 0,
    initial_step: int = 0,
    steps_per_epoch: int | None = None,
    callbacks: Sequence = (),
    validation_data=None,
    shuffle_buffer: int | None = None,
    verbose: int | None = None,
    cache: str | None = None,
) -> list[dict]:
    """Train. Either pass a batched ``ArrayDataset``/iterable of
    ``(x, y)`` numpy batches (the TF2 script's idiom,
    tensorflow2_keras_mnist.py:96) or raw ``x``/``y`` arrays with a
    per-worker ``batch_size`` (the TF1 script's idiom,
    mnist_keras.py:107-112).

    ``initial_epoch`` is the Keras resume idiom: epoch numbering (and
    LR-warmup position, checkpoint names) continues from a restored run —
    pair it with `checkpoint.restore_latest_and_broadcast`.

    ``initial_step`` resumes MID-epoch, at optimizer step S of
    ``initial_epoch`` — the step-granular recovery contract
    (`horovod_tpu.elastic`, step-carrying checkpoint manifests). The data
    iterator is deterministically fast-forwarded by exactly ``S × K``
    microbatches (K = ``backward_passes_per_step``) without materializing
    the skipped batches, so the resumed run consumes byte-identically the
    batches an uninterrupted run of the same fit call would have consumed
    from step S on — on every feeding path (streamed, device-cached,
    ``steps_per_execution`` chunks), and stably across an
    `ArrayDataset.reshard` (the cut is defined in optimizer steps, not
    bytes). A step at or past ``steps_per_epoch`` rolls into the next
    epoch. User-supplied ``dataset=`` iterables without an
    `ArrayDataset.batches`-style skip hook are fast-forwarded by drawing
    and discarding (correct, but materializes the skipped batches).

    Anchoring: every feeding path is EPOCH-ANCHORED (durable stream
    cursors, `data/stream.py`) — each epoch's order is a pure function
    of ``(trainer.seed, epoch)``, so a resumed fit regenerates exactly
    the stream an uninterrupted run would have consumed from
    ``(initial_epoch, initial_step)`` on, INCLUDING when the epochs
    before it were consumed by a process that no longer exists (the
    formerly re-anchoring case, closed by ISSUE 8). This holds for the
    streamed ``x=``/``y=`` path (python and native engines alike),
    ``cache='device'`` (pure (seed, epoch) permutation, as before), and
    ``dataset=`` sources exposing the anchored ``batches(skip=,
    start_epoch=, batches_per_epoch=)`` hook (`ArrayDataset`,
    `FileDataset.pairs_stream`, `PackedLMStream`); bare ``batches(
    skip=)`` sources keep the PR 5 contract (exact within the resume
    epoch, the source owns its own cross-epoch anchoring).

    ``cache='device'`` (with ``x``/``y``) stages the whole dataset into
    HBM once, sharded over the data axes, and runs shuffling + batching +
    training fully on-device: ONE dispatch and ONE metrics fetch per
    epoch, zero per-step host involvement. This is the TPU-native answer
    to input-bound training (datasets at MNIST/CIFAR scale are trivially
    HBM-resident); on_batch_end callbacks fire once per epoch with the
    last step's metrics."""
    if verbose is None:
        verbose = 1 if runtime.is_primary() else 0
    if isinstance(x, list):
        # Keras-parity: a plain list of example rows is one array input
        # (the pre-pytree behavior); dict/tuple inputs stay pytrees.
        x = np.asarray(x)
    if cache == "device":
        if x is None or y is None:
            raise ValueError("cache='device' needs x=/y= arrays")
        if len(jax.tree_util.tree_leaves(x)) != 1:
            raise ValueError(
                "cache='device' stages a single input array; pytree "
                "(dict/tuple) inputs use the streamed fit path"
            )
        if trainer.batch_specs is not None and mesh_lib.has_live_model_axes(
            trainer.mesh
        ):
            # The staged layout shards the batch dim only; custom batch
            # layouts over live non-data axes (e.g. seq-sharded tokens)
            # need the streamed path's batch_specs handling.
            raise ValueError(
                "cache='device' supports data-sharded batches only; "
                "use the streamed fit path with batch_specs meshes"
            )
        return fit_device_cached(trainer,
            x, y, batch_size, epochs, initial_epoch, steps_per_epoch,
            callbacks, validation_data, verbose, initial_step,
        )
    if cache is not None:
        raise ValueError(f"unknown cache mode {cache!r}")

    groups, group = feed_groups(trainer)
    close_input = lambda: None  # noqa: E731
    if dataset is None:
        if x is None or y is None:
            raise ValueError("pass either dataset= or x=/y=")
        ds = ArrayDataset((x, y)).shard(group, groups)
        n_local = ds.num_examples
        # Global batch = per-worker batch × dp_size; each feed group
        # contributes its share (see _feed_groups for the dp < world
        # case, where processes sharing a shard feed identical rows).
        local_batch = batch_size * trainer.dp_size // groups
        if steps_per_epoch is None:
            # steps_per_epoch counts OPTIMIZER steps; with gradient
            # accumulation each one consumes K microbatches.
            steps_per_epoch = max(
                1, n_local // (local_batch * trainer._accum_steps)
            )
        initial_epoch, initial_step = _normalize_resume(
            initial_epoch, initial_step, steps_per_epoch
        )
        # Batch assembly runs in the native C++ producer thread when
        # available (overlapping shuffle/gather with the device step),
        # pure Python otherwise — same semantics either way. The stream
        # is EPOCH-ANCHORED (start_epoch/batches_per_epoch): every
        # epoch's order is a pure function of (seed, epoch), so a resume
        # at (initial_epoch, initial_step) regenerates byte-identically
        # what the uninterrupted run consumed from that position on —
        # including when the epochs before it were consumed by a process
        # that no longer exists (the durable-cursor contract,
        # data/stream.py) — whichever engine is active.
        engine: dict = {}
        dataset, close_input = training_pipeline(
            ds.arrays, local_batch, seed=trainer.seed,
            shuffle_buffer=shuffle_buffer, structure=ds.structure,
            skip_batches=initial_step * trainer._accum_steps,
            start_epoch=initial_epoch,
            batches_per_epoch=steps_per_epoch * trainer._accum_steps,
            engine_out=engine,
        )
        # Full stream geometry for the durable cursor: the ENGINE is
        # part of it (python and native anchored streams are different
        # byte streams), as are the batch/row counts.
        trainer._stream_geometry = {
            "path": "streamed",
            "engine": engine.get("engine"),
            "accum": trainer._accum_steps,
            "steps_per_epoch": steps_per_epoch,
            "batch_size": local_batch,
            "n_examples": n_local,
            "shuffle_buffer": shuffle_buffer,
        }
        it = iter(dataset)
    elif steps_per_epoch is None:
        raise ValueError("steps_per_epoch is required with a dataset")
    else:
        initial_epoch, initial_step = _normalize_resume(
            initial_epoch, initial_step, steps_per_epoch
        )
        skip = initial_step * trainer._accum_steps
        # dataset= sources: the geometry the trainer can see (the
        # source's own cursor surface carries the rest — seed, shard
        # spec, row counts).
        trainer._stream_geometry = {
            "path": "streamed",
            "engine": "dataset",
            "accum": trainer._accum_steps,
            "steps_per_epoch": steps_per_epoch,
        }
        if hasattr(dataset, "batches"):
            # ArrayDataset-style source (ArrayDataset, FilePairs,
            # PackedLMStream, any duck-typed `batches(skip=, start_epoch=,
            # batches_per_epoch=)`): index-level fast-forward, nothing
            # materialized, and EPOCH-ANCHORED — the stream starts at the
            # resume epoch's exact position (reshard-stable: the stream
            # is a pure function of seed + shard geometry + epoch).
            # Capability is probed from the SIGNATURE, not by catching
            # TypeError around the call — a TypeError raised inside a
            # broken anchored source must surface, not silently degrade
            # the resume to an unanchored stream.
            if _accepts_anchoring(dataset.batches):
                it = dataset.batches(
                    skip=skip, start_epoch=initial_epoch,
                    batches_per_epoch=(
                        steps_per_epoch * trainer._accum_steps
                    ),
                )
            else:
                # Pre-anchoring source with a bare `batches(skip=)` hook:
                # exact within the resume epoch (the PR 5 contract);
                # cross-epoch anchoring is the source's own business.
                it = dataset.batches(skip=skip) if skip else iter(dataset)
        else:
            it = iter(dataset)
            # Generic iterables expose no skip hook: draw and discard
            # (documented materializing fallback — still deterministic).
            for _ in range(skip):
                next(it)

    # Where this fit resumes, for resume-aware callbacks (the elastic
    # callback aligns its commit/rescale cadences to the resume step).
    trainer._resume_epoch, trainer._resume_step = initial_epoch, initial_step
    first = next(it)
    trainer.build(first[0], first[1])

    callbacks = _with_env_callbacks(callbacks)
    for cb in callbacks:
        cb.set_trainer(trainer)
    try:
        # on_train_begin sits INSIDE the teardown scope: an early
        # installer (e.g. PreemptionCheckpointCallback's signal
        # handler) must be torn down even when a LATER callback's
        # begin hook raises.
        for cb in callbacks:
            cb.on_train_begin()

        pending = first
        # Zero metric accumulator, committed to the mesh's replicated
        # sharding ONCE: a fresh uncommitted jnp.zeros each epoch would
        # give the first step of every epoch a different input-sharding
        # signature than the chained steps, ping-ponging between two
        # executables.
        zero_acc = sharding_lib.replicate(trainer.zero_metrics(), trainer.mesh)
        # HVT_PROFILE=<dir> captures a jax.profiler trace of the training
        # loop (XLA op + ICI collective timing) — the Horovod-Timeline
        # env-var contract, primary-process-gated (trace.py).
        from horovod_tpu import trace as trace_lib

        with trace_lib.maybe_trace(trace_lib.profile_dir()):
            fit_epochs(trainer,
                it, pending, zero_acc, epochs, initial_epoch,
                steps_per_epoch, callbacks, validation_data, batch_size,
                verbose, initial_step,
            )
    except BaseException:
        close_input()
        _teardown_callbacks(callbacks)
        raise
    close_input()
    _run_train_end(callbacks)
    return trainer.history

def _maybe_step_sampler(trainer):
    """The live step-phase sampler, when the trainer-side metrics
    exporter is on (`HVT_METRICS_PORT` — obs/server.py): None otherwise,
    so the default fit path carries ZERO instrumentation cost. The
    examples-per-step figure is inferred from the first chunk's shapes
    (when the trainer remembers its step program)."""
    from horovod_tpu.obs import server as obs_server

    if obs_server.ensure_trainer_exporter() is None:
        return None
    from horovod_tpu.training.trainer import StepPhaseSampler

    return StepPhaseSampler(trainer, 0)


def fit_epochs(trainer, it, pending, zero_acc, epochs, initial_epoch, steps_per_epoch,
    callbacks, validation_data, batch_size, verbose, initial_step=0,
):
    from horovod_tpu import obs
    from horovod_tpu import trace as trace_lib
    from horovod_tpu.data.prefetch import DevicePrefetcher

    # Per-epoch execution plan: full steps_per_execution chunks plus one
    # remainder chunk (a second, smaller executable) when K doesn't
    # divide the epoch. The RESUME epoch (initial_step > 0) covers only
    # its remaining steps — the iterator was already fast-forwarded past
    # the first initial_step·accum microbatches — so its plan (and hence
    # the host-chunk assembly below) is shorter than the steady-state
    # epochs'.
    spe = min(trainer.steps_per_execution, steps_per_epoch)

    def plan_for(epoch):
        steps = steps_per_epoch - (
            initial_step if epoch == initial_epoch else 0
        )
        plan = [spe] * (steps // spe)
        if steps % spe:
            plan.append(steps % spe)
        return plan

    buffered = [pending]
    # Microbatches per optimizer step (backward_passes_per_step): each
    # execution unit carries accum microbatches per step, stacked on a
    # leading axis the accumulating train step scans over.
    accum = trainer._accum_steps

    def host_chunks():
        # Host-side assembly of the execution units: single batches when
        # spe*accum == 1, [accum, ...] microbatch stacks per step, and
        # [spe(, accum), ...] stacks of steps.
        for epoch in range(initial_epoch, epochs):
            for k in plan_for(epoch):
                batches = [
                    buffered.pop() if buffered else next(it)
                    for _ in range(k * accum)
                ]
                # Stack leaf-wise — pytree batches (dict inputs,
                # multi-input models) stack like flat ones.
                if accum > 1:
                    steps = [
                        jax.tree.map(
                            lambda *xs: np.stack(xs),
                            *batches[i * accum : (i + 1) * accum],
                        )
                        for i in range(k)
                    ]
                else:
                    steps = batches
                if spe == 1:
                    yield steps[0]
                else:
                    yield jax.tree.map(lambda *xs: np.stack(xs), *steps)

    # Batches are staged onto the devices by a background thread while
    # the current step computes — transfer enqueue never blocks dispatch.
    # The step DONATES each batch (every prefetched chunk is consumed
    # exactly once), so with the default depth of 2 the path is true
    # double buffering: two batch-sized device buffers alternate between
    # "being transferred" and "being consumed", and the consumed one's
    # memory returns to the allocator at dispatch instead of piling up
    # behind the queue. HVT_PREFETCH_DEPTH deepens the queue for bursty
    # producers.
    from horovod_tpu.analysis import registry

    depth = registry.get_int("HVT_PREFETCH_DEPTH") or 2
    run = (
        trainer._train_step_donated if spe == 1
        else trainer._train_chunk_donated
    )
    if spe == 1:
        place = (
            trainer._shard if accum == 1
            else lambda b: trainer._shard_chunk(b, 1)
        )
    else:
        place = lambda b: trainer._shard_chunk(b, 2 if accum > 1 else 1)  # noqa: E731
    prefetcher = DevicePrefetcher(host_chunks(), place, depth=depth)
    sampler = _maybe_step_sampler(trainer)
    remembered = False
    try:
        for epoch in range(initial_epoch, epochs):
            if trainer.stop_training:
                break
            # Fresh scale each epoch (see _fit_device_cached note).
            trainer.update_scale = 1.0
            for cb in callbacks:
                cb.on_epoch_begin(epoch)
            t0 = time.perf_counter()
            scale = jnp.asarray(trainer.update_scale, jnp.float32)
            metric_acc = zero_acc
            # Batch indices are TRUE within-epoch optimizer steps: a
            # resumed epoch's first on_batch_end fires with the step it
            # actually trained, so step-keyed cadences (elastic commits,
            # step-targeted faults) stay aligned across a resume.
            start = initial_step if epoch == initial_epoch else 0
            step = start
            for k in plan_for(epoch):
                # Always timed, on both clocks: the profiler's (the span)
                # and /metrics' (the counter). With the producer ahead
                # this is a queue pop; when it grows, the input engine is
                # on the critical path.
                t_in = time.perf_counter()
                with trace_lib.span("input_wait"):
                    chunk = next(prefetcher)
                waited = time.perf_counter() - t_in
                obs.counter("hvt_input_wait_seconds_total", waited)
                if sampler is not None:
                    sampler.add_input_wait(waited)
                if not remembered:
                    # First chunk of the fit, before it is donated: the
                    # trainer remembers the program it is about to run
                    # (one tree.map; nothing is lowered until somebody
                    # asks). k, not spe: a resumed epoch's FIRST chunk
                    # can be a remainder chunk with fewer steps, and the
                    # program's FLOPs must divide by the steps of the
                    # program actually remembered or hvt_mfu mis-scales.
                    remembered = True
                    trainer.remember_step_program(
                        run, (trainer.state, chunk, scale, metric_acc), k)
                    if sampler is not None:
                        # Examples per OPTIMIZER step, from the placed
                        # shapes ([spe?, K?, G, ...]).
                        leaf = jax.tree_util.tree_leaves(chunk[0])[0]
                        lead = 1 + (spe > 1) + (accum > 1)
                        rows = int(np.prod(leaf.shape[:lead]))
                        sampler.examples_per_step = rows // (
                            leaf.shape[0] if spe > 1 else 1
                        )
                t_run = time.perf_counter() if sampler is not None else 0.0
                # The HOST's call into the step program, not the step:
                # it returns at enqueue while fewer programs are in
                # flight than the runtime allows, and blocks for a
                # retiring step otherwise. The step's device time is the
                # device's `XLA Modules` event; the n-th `hvt.step` span
                # of a trace belongs to the n-th of them.
                with trace_lib.span("step", epoch=epoch, step=step,
                                    steps=k):
                    trainer.state, metrics, metric_acc = run(
                        trainer.state, chunk, scale, metric_acc
                    )
                obs.counter("hvt_optimizer_steps_total", k)
                if sampler is not None:
                    # Step-call host time feeds the SkewProbe's blocked
                    # signal (sync-dispatch backends block HERE, not in
                    # the drain).
                    sampler.add_step_time(time.perf_counter() - t_run)
                    sampler.maybe_sample(trainer.state, k)
                step += k
                # Once per execution, with the last step's metrics —
                # Keras's steps_per_execution callback semantics.
                with trace_lib.span("callbacks"):
                    for cb in callbacks:
                        cb.on_batch_end(step - 1, metrics)
            finish_epoch(trainer,
                epoch, epochs, metric_acc, steps_per_epoch - start, t0,
                callbacks, validation_data, batch_size, verbose,
            )
    finally:
        prefetcher.close()

def fit_device_cached(trainer, x, y, batch_size, epochs, initial_epoch, steps_per_epoch,
    callbacks, validation_data, verbose, initial_step=0,
):
    from horovod_tpu import obs
    from horovod_tpu import trace as trace_lib

    data, per_shard = stage_device_dataset(trainer, x, y)
    # One optimizer step consumes accum_steps microbatches of batch_size.
    max_steps = per_shard // (batch_size * trainer._accum_steps)
    if max_steps == 0:
        raise ValueError(
            f"per-shard examples ({per_shard}) < per-chip batch "
            f"({batch_size}) x backward_passes_per_step "
            f"({trainer._accum_steps})"
        )
    steps = min(steps_per_epoch or max_steps, max_steps)
    # Mid-epoch resume: the epoch's shuffle is a pure function of
    # (seed, epoch) — fold_in below — so the resume epoch regenerates the
    # SAME permutation and the compiled epoch program simply starts its
    # gather/scan at step `initial_step`: batches byte-identical to the
    # uninterrupted epoch's steps S.., no skipped batch ever gathered.
    initial_epoch, initial_step = _normalize_resume(
        initial_epoch, initial_step, steps
    )
    trainer._resume_epoch, trainer._resume_step = initial_epoch, initial_step
    trainer._stream_geometry = {
        "path": "device",
        "accum": trainer._accum_steps,
        "steps_per_epoch": steps,
        "batch_size": batch_size,
    }
    trainer.build(
        np.asarray(x[: trainer.dp_size]), np.asarray(y[: trainer.dp_size])
    )

    callbacks = _with_env_callbacks(callbacks)
    for cb in callbacks:
        cb.set_trainer(trainer)
    # Step-chunked epoch executables (HVT_EPOCH_CHUNK_STEPS): split each
    # on-device epoch into compiled chunks of C optimizer steps so
    # on_batch_end fires per chunk — sub-epoch commit/rescale/save
    # cadences (elastic commit_every_steps, HVT_SAVE_EVERY_STEPS) work on
    # the device-cached path too. `start` is a dynamic jit argument, so
    # the whole epoch costs at most two executables (full chunk +
    # remainder), independent of the chunk count. 0 = whole-epoch program
    # (the historical single-dispatch behavior).
    from horovod_tpu.analysis import registry

    chunk = registry.get_int("HVT_EPOCH_CHUNK_STEPS") or 0
    # An epoch program is not a step program: what an earlier streamed fit
    # remembered (`Trainer.remember_step_program`) is not this fit's.
    trainer._step_program = None
    sampler = _maybe_step_sampler(trainer)
    if sampler is not None:
        # Device-cached feeding has no host input leg by construction;
        # examples/step is the staged geometry's.
        sampler.examples_per_step = (
            trainer.dp_size * batch_size * trainer._accum_steps
        )
    try:
        # Inside the teardown scope — see the streamed fit path's note.
        for cb in callbacks:
            cb.on_train_begin()
        zero_acc = sharding_lib.replicate(trainer.zero_metrics(), trainer.mesh)
        epoch_key = jax.random.PRNGKey(trainer.seed + 1)
        with trace_lib.maybe_trace(trace_lib.profile_dir()):
            for epoch in range(initial_epoch, epochs):
                if trainer.stop_training:
                    break
                # Fresh scale each epoch: LR callbacks compose into it
                # in list order (warmup assigns, schedules multiply).
                trainer.update_scale = 1.0
                for cb in callbacks:
                    cb.on_epoch_begin(epoch)
                t0 = time.perf_counter()
                scale = jnp.asarray(trainer.update_scale, jnp.float32)
                start = initial_step if epoch == initial_epoch else 0
                c = chunk if chunk > 0 else steps - start
                metric_acc = zero_acc
                at = start
                while at < steps:
                    n = min(c, steps - at)
                    t_run = (
                        time.perf_counter() if sampler is not None else 0.0
                    )
                    with trace_lib.span("step", epoch=epoch, step=at,
                                        steps=n):
                        trainer.state, metrics, metric_acc = (
                            trainer._train_epoch(
                                trainer.state, data,
                                jax.random.fold_in(epoch_key, epoch),
                                scale, metric_acc, n, batch_size, at,
                            )
                        )
                    obs.counter("hvt_optimizer_steps_total", n)
                    if sampler is not None:
                        sampler.add_step_time(time.perf_counter() - t_run)
                        sampler.maybe_sample(trainer.state, n)
                    at += n
                    # Once per chunk, with the chunk's last step metrics
                    # and the TRUE within-epoch step index — the
                    # steps_per_execution callback contract.
                    with trace_lib.span("callbacks"):
                        for cb in callbacks:
                            cb.on_batch_end(at - 1, metrics)
                finish_epoch(trainer,
                    epoch, epochs, metric_acc, steps - start, t0, callbacks,
                    validation_data, batch_size, verbose,
                    # Device-cached training implies device-cached
                    # validation.
                    val_cache="device",
                )
    except BaseException:
        _teardown_callbacks(callbacks)
        raise
    _run_train_end(callbacks)
    return trainer.history

def evaluate_device_cached(trainer, x, y, batch_size: int) -> dict:
    """evaluate() over a device-resident eval set: stage once (padded to
    full batches, padding masked), then each call is ONE dispatch + one
    3-scalar fetch. The per-epoch validation pass stops restreaming the
    test set from the host every epoch.

    Caching is by the host arrays' identity: do not mutate ``x``/``y``
    in place while cached, or stale staged data is evaluated."""
    key = (id(x), id(y), batch_size)
    if key not in trainer._eval_cache:
        n = len(x)
        n_shards = trainer.dp_size
        per = -(-n // (n_shards * batch_size)) * batch_size  # ceil→pad
        pad_n = per * n_shards
        mask = np.zeros(pad_n, np.float32)
        mask[:n] = 1.0

        def padded(a):
            # Repeat a REAL example into the padded tail (like the
            # streamed path): all-zero rows could produce non-finite
            # losses in input-normalizing models, and NaN*0 = NaN would
            # poison the masked sums.
            a = np.asarray(a)
            out = np.concatenate(
                [a, np.repeat(a[-1:], pad_n - n, axis=0)]
            )
            return out

        data = (
            stage_sharded(trainer, padded(x), per),
            stage_sharded(trainer, padded(y), per),
            stage_sharded(trainer, mask, per),
        )
        # Keep x/y referenced so their ids stay unique while cached.
        trainer._eval_cache[key] = (data, per // batch_size, (x, y))
        if len(trainer._eval_cache) > 4:  # bound device memory
            trainer._eval_cache.pop(next(iter(trainer._eval_cache)))
    data, steps, _ = trainer._eval_cache[key]
    m = jax.device_get(
        trainer._eval_epoch(trainer.state, data, steps, batch_size)
    )
    return {
        "loss": float(m["loss_sum"]) / float(m["count"]),
        "accuracy": float(m["correct_sum"]) / float(m["count"]),
    }

def run_evaluate(trainer, x, y, batch_size: int = 128, verbose: int = 0,
    cache: str | None = None,
) -> dict:
    """Full-dataset eval on the mesh. Unlike the reference (every rank
    redundantly evaluates the full test set, SURVEY.md §3.2), the eval
    batch is sharded across chips — same result, 1/size the work.
    ``cache='device'`` keeps the (padded, masked) eval set in HBM and
    runs the whole pass as one compiled scan."""
    if trainer.state is None:
        raise RuntimeError("call fit() or build() first")
    if (
        cache == "device"
        and trainer.batch_specs is not None
        and mesh_lib.has_live_model_axes(trainer.mesh)
    ):
        # Custom batch layouts over LIVE non-data axes (e.g. seq-sharded
        # tokens) need _shard's spec handling; the cached path stages
        # batch-dim-only. With those axes trivial the layouts coincide —
        # same condition as fit(cache='device')'s guard.
        cache = None
    if isinstance(x, list):
        x = np.asarray(x)  # list-of-rows = one array input (see fit)
    if cache == "device":
        if len(jax.tree_util.tree_leaves(x)) != 1:
            raise ValueError(
                "cache='device' stages a single input array; pytree "
                "(dict/tuple) inputs use the streamed eval path"
            )
        result = evaluate_device_cached(trainer, x, y, batch_size)
        if verbose and runtime.is_primary():
            print(f"eval - {({k: round(v, 4) for k, v in result.items()})}")
        return result
    if cache is not None:
        raise ValueError(f"unknown cache mode {cache!r}")
    # x may be a pytree (dict-input models, e.g. seq2seq) — slice, pad
    # and shard leaf-wise; y/mask stay flat arrays.
    n = len(jax.tree_util.tree_leaves(x)[0])
    global_batch = batch_size * trainer.dp_size
    loss_sum = correct_sum = count = 0.0
    for start in range(0, n, global_batch):
        xb, bs = slice_pad(trainer, x, start, global_batch)
        yb, _ = slice_pad(trainer, y, start, global_batch)
        mask = np.ones((global_batch,), np.float32)
        mask[bs:] = 0.0
        batch = tuple(
            jax.tree.map(
                lambda a: local_slice(trainer, a, global_batch), part
            )
            for part in (xb, yb, mask)
        )
        m = jax.device_get(trainer._eval_step(trainer.state, shard_batch(trainer, batch)))
        loss_sum += float(m["loss_sum"])
        correct_sum += float(m["correct_sum"])
        count += float(m["count"])
    result = {"loss": loss_sum / count, "accuracy": correct_sum / count}
    if verbose and runtime.is_primary():
        print(f"eval - {({k: round(v, 4) for k, v in result.items()})}")
    return result

def run_predict(trainer, x, batch_size: int = 128) -> np.ndarray:
    """Class probabilities (softmax applied here, keeping the serving
    contract input→prob, mnist_keras.py:133-134). ``x`` may be a pytree
    (dict-input models) — slice/pad/shard run leaf-wise, like
    `evaluate`."""
    if trainer.state is None:
        raise RuntimeError("call fit() or build() first")
    if isinstance(x, list):
        x = np.asarray(x)  # list-of-rows = one array input (see fit)
    out = []
    global_batch = batch_size * trainer.dp_size
    n = len(jax.tree_util.tree_leaves(x)[0])
    for start in range(0, n, global_batch):
        xb, bs = slice_pad(trainer, x, start, global_batch)
        xb = jax.tree.map(
            lambda a: local_slice(trainer, a, global_batch), xb
        )
        probs = jax.device_get(trainer._predict_step(trainer.state, shard_batch(trainer, xb)))
        out.append(probs[:bs])
    return np.concatenate(out, axis=0)
