"""Flagship model families for horovod_tpu benchmarks and examples.

The reference frames its headline numbers around ImageNet CNNs
(ResNet-50/101, Inception V3, VGG-16 — reference: docs/benchmarks.rst:13-43)
trained data-parallel via its synthetic/ImageNet example scripts
(reference: examples/pytorch/pytorch_synthetic_benchmark.py,
examples/pytorch/pytorch_imagenet_resnet50.py). These are TPU-native
re-implementations in flax, bf16-first, designed so every FLOP-heavy op
lands on the MXU.
"""
from .hybrid import HybridConfig, HybridLM
from .inception import InceptionV3
from .resnet import (ResNet, ResNet18, ResNet34, ResNet50, ResNet101,
                     ResNet152)
from .transformer import (TransformerConfig, TransformerLM, gpt_medium,
                          gpt_small, gpt_tiny)
from .vgg import VGG, VGG16, VGG19

__all__ = ["ResNet", "ResNet18", "ResNet34", "ResNet50", "ResNet101",
           "ResNet152", "HybridConfig", "HybridLM", "TransformerConfig",
           "TransformerLM", "gpt_small",
           "gpt_medium", "gpt_tiny", "VGG", "VGG16", "VGG19",
           "InceptionV3"]
