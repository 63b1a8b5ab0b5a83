"""Model zoo for BlueFog-TPU.

The reference trains torchvision models (reference examples/pytorch_resnet.py:54,
examples/pytorch_benchmark.py) and a small MNIST CNN (reference
examples/pytorch_mnist.py:125-143).  These are TPU-first flax.linen
re-designs: NHWC layouts, bf16 compute with f32 params, static shapes so XLA
tiles every conv/matmul onto the MXU.
"""

from bluefog_tpu.models.mlp import MLP, MnistNet
from bluefog_tpu.models.resnet import (
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from bluefog_tpu.models.llama import (
    Llama,
    LlamaConfig,
    chunked_xent,
    llama_chunked_xent_loss_fn,
    llama_circular_layout,
    llama_param_specs,
    llama_pp_loss_fn,
    vocab_parallel_xent,
)
from bluefog_tpu.models.generate import init_cache, llama_generate
from bluefog_tpu.models.hybrid_ssm import HybridSsm, HybridSsmConfig
from bluefog_tpu.models.quant import quantize_llama_params
from bluefog_tpu.models.vit import ViT, ViTConfig, ViT_B16, ViT_S16

__all__ = [
    "ViT",
    "ViTConfig",
    "ViT_S16",
    "ViT_B16",
    "MLP",
    "MnistNet",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "ResNet152",
    "Llama",
    "LlamaConfig",
    "HybridSsm",
    "HybridSsmConfig",
    "llama_param_specs",
    "llama_pp_loss_fn",
    "chunked_xent",
    "llama_chunked_xent_loss_fn",
    "llama_circular_layout",
    "llama_generate",
    "init_cache",
    "quantize_llama_params",
    "vocab_parallel_xent",
]
