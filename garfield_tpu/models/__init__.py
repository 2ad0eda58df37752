"""Model zoo: the CNNs of pytorch_impl/libs/garfieldpp/models/ and the
torchvision entries in garfieldpp/tools.py:59-105, the small transformers
(`transformer.py`) and the language models: LFM2-MoE (`lfm2.py`), Mellum
(`mellum.py`) and Laguna (`laguna.py`), the last two built of `lfm2.py`'s
attention and expert layer, and SambaY (`phi4flash.py`: Mamba layers,
differential attention, gated memory units).

All models are flax.linen modules with the signature
``model(x, train: bool)`` and constructor kwargs ``num_classes`` and
``dtype`` (compute dtype; pass jnp.bfloat16 to route convs/matmuls to the
MXU in bf16 while parameters stay float32). ``x`` is an NHWC image batch
for the CNNs and ``vit_tiny``, an int token batch (batch, time) for
``gpt_tiny`` (one label per sequence) and for the ``lfm2_*``, ``mellum2_*``,
``laguna_*`` and ``phi4*`` presets, whose logits are (batch, time, vocabulary) and whose loss is ``next-token``
(``utils.selectors.select_loss``); for them ``num_classes`` is the slice of
the vocabulary held.

``select_model(name, dataset)`` mirrors the reference selector: the model
table (tools.py:66-88) and the dataset->num_classes map (tools.py:89).
Device placement and DataParallel wrapping (tools.py:102-103) have no
equivalent here — sharding is decided by the caller's mesh, not the model.
"""

import jax.numpy as jnp

from .. import data
from .densenet import DenseNet121, DenseNet161, DenseNet169, DenseNet201, densenet_cifar
from .dpn import DPN26, DPN92
from .efficientnet import EfficientNetB0
from .googlenet import GoogLeNet
from .laguna import laguna_tiny, laguna_xs2_ep16
from .lenet import LeNet
from .lfm2 import lfm2_8b_a1b_ep4, lfm2_moe_tiny
from .mellum import mellum2_12b_a2p5b_ep4, mellum2_tiny
from .mobilenet import MobileNet
from .mobilenetv2 import MobileNetV2
from .nets import CNNet, Cifarnet, Net
from .phi4flash import phi4_mini_flash_l15_19, phi4flash_tiny
from .pimanet import PimaNet
from .pnasnet import PNASNetA, PNASNetB
from .preact_resnet import PreActResNet18
from .regnet import RegNetX_200MF, RegNetX_400MF, RegNetY_400MF
from .resnet import ResNet18, ResNet34, ResNet50, ResNet101, ResNet152
from .resnext import ResNeXt29_2x64d, ResNeXt29_4x64d, ResNeXt29_8x64d, ResNeXt29_32x4d
from .senet import SENet18
from .shufflenet import ShuffleNetG2, ShuffleNetG3
from .shufflenetv2 import ShuffleNetV2
from .transformer import GPT, ViT
from .vgg import VGG11, VGG13, VGG16, VGG19

__all__ = ["models", "num_classes_dict", "select_model"]

# Name table mirroring garfieldpp/tools.py:66-88 (plus the extra family
# members the reference zoo defines but does not register by name).
models = {
    "convnet": Net,
    "cifarnet": Cifarnet,
    "cnn": CNNet,
    "lenet": LeNet,
    "resnet18": ResNet18,
    "resnet34": ResNet34,
    "resnet50": ResNet50,
    "resnet101": ResNet101,
    "resnet152": ResNet152,
    # tools.py:73 maps "inception" to torchvision inception_v3; CIFAR-scale
    # inputs use the Inception-v1 graph here (see googlenet.py docstring).
    "inception": GoogLeNet,
    "vgg11": VGG11,
    "vgg13": VGG13,
    "vgg16": VGG16,
    "vgg19": VGG19,
    "preactresnet18": PreActResNet18,
    "googlenet": GoogLeNet,
    "densenet121": DenseNet121,
    "densenet161": DenseNet161,
    "densenet169": DenseNet169,
    "densenet201": DenseNet201,
    "densenet_cifar": densenet_cifar,
    "resnext29": ResNeXt29_2x64d,
    "resnext29_4x64d": ResNeXt29_4x64d,
    "resnext29_8x64d": ResNeXt29_8x64d,
    "resnext29_32x4d": ResNeXt29_32x4d,
    "mobilenet": MobileNet,
    "mobilenetv2": MobileNetV2,
    "dpn26": DPN26,
    "dpn92": DPN92,
    "shufflenetg2": ShuffleNetG2,
    "shufflenetg3": ShuffleNetG3,
    "shufflenetv2": ShuffleNetV2,
    "senet18": SENet18,
    "efficientnetb0": EfficientNetB0,
    "regnetx200": RegNetX_200MF,
    "regnetx400": RegNetX_400MF,
    "regnety400": RegNetY_400MF,
    "pnasneta": PNASNetA,
    "pnasnetb": PNASNetB,
    "pimanet": PimaNet,
    # Transformer family (models/transformer.py): no reference-repo
    # counterpart — the first-mover slot-fused transformer workloads.
    # vit_tiny consumes NHWC images; gpt_tiny consumes int token batches
    # (the copytask sequence dataset).
    "vit_tiny": ViT,
    "gpt_tiny": GPT,
    # LFM2-MoE language models (models/lfm2.py): int token batches in,
    # next-token logits out. lfm2_8b_a1b_ep4 is one chip's share of
    # LFM2-8B-A1B under 4-way expert parallelism (5 of 24 layers, experts 0-7 of
    # 32, published widths); lfm2_moe_tiny is the CPU tests' size.
    "lfm2_8b_a1b_ep4": lfm2_8b_a1b_ep4,
    "lfm2_moe_tiny": lfm2_moe_tiny,
    # Mellum language models (models/mellum.py): window and full attention
    # layers, a softmax router, an untied head. mellum2_12b_a2p5b_ep4 is one
    # chip's share of Mellum2-12B-A2.5B under 4-way expert parallelism (4 of
    # 28 layers, experts 0-15 of 64, published widths).
    "mellum2_12b_a2p5b_ep4": mellum2_12b_a2p5b_ep4,
    "mellum2_tiny": mellum2_tiny,
    # Laguna language models (models/laguna.py): a head count and a rotary
    # table by kind of layer, a per-head gate, a shared expert.
    # laguna_xs2_ep16 is one chip's share of Laguna-XS.2 under 16-way expert
    # parallelism (5 of 40 layers, experts 0-15 of 256, published widths).
    "laguna_xs2_ep16": laguna_xs2_ep16,
    "laguna_tiny": laguna_tiny,
    # SambaY language models (models/phi4flash.py): Mamba layers, window,
    # full and cross differential attention, gated memory units, a tied
    # embedding. phi4_mini_flash_l15_19 is one pipeline stage of
    # Phi-4-mini-flash-reasoning (published layers 15-19, published widths).
    "phi4_mini_flash_l15_19": phi4_mini_flash_l15_19,
    "phi4flash_tiny": phi4flash_tiny,
}

# tools.py:89 (+ the synthetic sequence datasets of data/__init__.py:
# copytask, one label per sequence; the synthtokens datasets, whose
# "classes" are the slice of the vocabulary their next-token labels are
# drawn from: data.TOKEN_DATASETS has each one's slice and length)
num_classes_dict = {
    "cifar10": 10,
    "cifar100": 100,
    "mnist": 10,
    "imagenet": 1000,
    "pima": 1,
    "copytask": 10,
    **{name: vocab for name, (vocab, _) in data.TOKEN_DATASETS.items()},
}


def select_model(model, dataset, *, dtype=jnp.float32):
    """Instantiate a model by name for a dataset (tools.py:59-105).

    Returns the flax module; initialize with
    ``variables = module.init(key, example_batch, train=False)``.
    """
    if dataset not in num_classes_dict:
        raise ValueError(
            f"The specified dataset is undefined, available datasets are: "
            f"{sorted(num_classes_dict)}"
        )
    if model not in models:
        raise ValueError(
            f"The specified model is undefined, available models are: "
            f"{sorted(models)}"
        )
    return models[model](num_classes=num_classes_dict[dataset], dtype=dtype)
