"""TPU compute ops: attention implementations (dense / ring / ring-flash /
Ulysses) and pallas kernels for the hot paths."""

from horovod_tpu.ops.attention import (  # noqa: F401
    dense_attention,
    ring_attention,
    ring_flash_attention,
    ulysses_attention,
)
from horovod_tpu.ops.delta_rule import gated_delta_rule  # noqa: F401
from horovod_tpu.ops.ssd import ssd_scan  # noqa: F401

# NOTE: the flash kernel lives in `horovod_tpu.ops.flash_attention` (module);
# it is deliberately NOT re-exported here — a function named like its own
# submodule would shadow the module attribute on the package.
