"""Model zoo for benchmarks and examples (reference benchmarks use
tf.keras.applications ResNet50 et al., docs/benchmarks.rst)."""

from .gpt import GPT, GPTConfig, gpt_small, gpt_tiny  # noqa: F401
from .mnist import MnistNet  # noqa: F401
from .sparse_moe_decoder import (  # noqa: F401
    SparseMoEConfig,
    SparseMoEDecoder,
    update_router_biases,
)
from .sambay import SambaY, SambaYConfig  # noqa: F401
from .hybrid_mamba_moe import (  # noqa: F401
    HybridMambaMoE,
    HybridMambaMoEConfig,
)
from .resnet import (  # noqa: F401
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
