"""Model zoo: the reference CNN, ResNet-20 (CIFAR), and the transformer
flagship for long-context / tensor-parallel configurations — plus the
inference stack (KV-cache generation, beam search, speculative decoding,
weight-only int8)."""

from horovod_tpu.models.beam import make_beam_search_fn  # noqa: F401
from horovod_tpu.models.cnn import MnistCNN  # noqa: F401
from horovod_tpu.models.decoding import generate, make_generate_fn  # noqa: F401
from horovod_tpu.models.hybrid_moe_lm import HybridMoELM  # noqa: F401
from horovod_tpu.models.quant import (  # noqa: F401
    dequantize_params,
    quantize_params,
)
from horovod_tpu.models.resnet import ResNetCIFAR  # noqa: F401
from horovod_tpu.models.speculative import (  # noqa: F401
    make_speculative_fn,
    ngram_draft_fn,
)
from horovod_tpu.models.vit import ViT  # noqa: F401
from horovod_tpu.models.transformer import (  # noqa: F401
    ShardingConfig,
    TransformerLM,
    param_specs,
)
