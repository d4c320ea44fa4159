"""CIFAR-10 ResNet-20 data-parallel training — the heavier-gradients config.

BASELINE.json config 4: same capability set as `examples/tf2_style_mnist.py`
(bootstrap, sharded data, gradient-averaging optimizer, broadcast /
metric-average / warmup callbacks, rank-0 I/O — all citing the same
tensorflow2_keras_mnist.py behaviors), but with a model whose gradient
pytree (~270k params across 20 conv layers) exercises the allreduce path the
way real workloads do. BatchNorm runs with global-batch (sync-BN) semantics
inside the SPMD step.

Env knobs: DRIVE_STEPS, DRIVE_EPOCHS, DRIVE_EVAL_N.
"""

import os

try:
    import horovod_tpu  # noqa: F401 — installed (`pip install -e .`)
except ModuleNotFoundError:  # bare source checkout: make the repo importable
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvt
from horovod_tpu import metrics
from horovod_tpu.data import datasets
from horovod_tpu.data.loader import ArrayDataset
from horovod_tpu.models.resnet import ResNetCIFAR
from horovod_tpu.models.vit import ViT


def main() -> None:
    model_dir = os.path.join(os.environ.get("PS_MODEL_PATH", "./models"), "horovod-cifar")

    hvt.init()
    metrics.init(sync_tensorboard=True)

    (x_train, y_train), (x_test, y_test) = datasets.cifar10(
        path=f"cifar10-{hvt.rank()}.npz"
    )
    x_train = x_train.astype(np.float32) / 255.0
    x_test = x_test.astype(np.float32) / 255.0
    y_train = y_train.astype(np.int64)
    y_test = y_test.astype(np.int64)
    if os.environ.get("DRIVE_EVAL_N"):
        n = int(os.environ["DRIVE_EVAL_N"])
        x_test, y_test = x_test[:n], y_test[:n]

    world = hvt.process_count()
    per_process_batch = 128 * hvt.size() // world
    dataset = (
        ArrayDataset((x_train, y_train))
        .shard(hvt.process_rank(), world)
        .repeat()
        .shuffle(10000, seed=hvt.process_rank())
        .batch(per_process_batch)
    )

    # ARCH=vit swaps the conv model for the conv-free ViT (models/vit.py)
    # through the identical training path — architecture is a swappable
    # leaf, and the ViT's matmul shapes reach MFU the CIFAR convs can't
    # (not measured on this round's chip).
    if os.environ.get("ARCH", "resnet") == "vit":
        module = ViT(
            patch_size=4, d_model=256, n_heads=8, n_layers=6,
            compute_dtype=jnp.bfloat16,
        )
    else:
        module = ResNetCIFAR(depth=20, compute_dtype=jnp.bfloat16)
    trainer = hvt.Trainer(
        module,
        hvt.DistributedOptimizer(optax.adam(hvt.scale_lr(0.001))),
        loss="sparse_categorical_crossentropy",
    )

    callbacks = [
        hvt.callbacks.BroadcastGlobalVariablesCallback(0),
        hvt.callbacks.MetricAverageCallback(),
        hvt.callbacks.LearningRateWarmupCallback(warmup_epochs=3, verbose=1),
    ]
    # Epoch scalars reach the platform sink via sync_tensorboard (metrics.init
    # above); an explicit MetricsPushCallback would push everything twice.
    if hvt.rank() == 0:
        callbacks.append(
            hvt.callbacks.ModelCheckpoint(os.path.join(model_dir, "checkpoint-{epoch}.msgpack"))
        )
        callbacks.append(hvt.callbacks.ScalarLogger(model_dir))

    steps_per_epoch = int(os.environ.get("DRIVE_STEPS", 0)) or hvt.shard_steps(390)
    epochs = int(os.environ.get("DRIVE_EPOCHS", 0)) or 24

    trainer.fit(
        dataset,
        steps_per_epoch=steps_per_epoch,
        epochs=epochs,
        callbacks=callbacks,
        verbose=1 if hvt.rank() == 0 else 0,
    )

    score = trainer.evaluate(x_test, y_test, batch_size=128)
    metrics.push("loss", score["loss"])
    metrics.push("accuracy", score["accuracy"])
    if hvt.rank() == 0:
        print("Test loss:", score["loss"])
        print("Test accuracy:", score["accuracy"])


if __name__ == "__main__":
    main()
