"""Flax model zoo: the reference's named CNN families, TPU-native.

Parity: ``sparkdl/transformers/keras_applications.py`` + Scala
``Models.scala`` (SURVEY.md §2.1/§2.2). All models are NHWC flax.linen
modules with optional bf16 compute (``dtype=jnp.bfloat16`` — fp32 params,
MXU-friendly activations).
"""

# import_s of the start-up record: this package's first import, with what
# it pulls in (core/profiling.py; stdlib only, so it costs nothing itself)
from sparkdl_tpu.core import profiling as _profiling

_import_started = _profiling.import_begin()

from sparkdl_tpu.models.inception import InceptionV3  # noqa: E402
from sparkdl_tpu.models.mobilenet import MobileNetV2  # noqa: E402
from sparkdl_tpu.models.resnet import ResNet, ResNet50, ResNet101, ResNet152  # noqa: E402
from sparkdl_tpu.models.testnet import TestNet  # noqa: E402
from sparkdl_tpu.models.vgg import VGG, VGG16, VGG19  # noqa: E402
from sparkdl_tpu.models.xception import Xception  # noqa: E402
from sparkdl_tpu.models.registry import (  # noqa: E402
    SUPPORTED_MODELS,
    SUPPORTED_MODEL_NAMES,
    ModelSpec,
    build_featurizer,
    build_predictor,
    get_model_spec,
)

_profiling.import_end(_import_started)

__all__ = [
    "InceptionV3", "MobileNetV2", "ResNet", "ResNet50", "ResNet101",
    "ResNet152", "TestNet", "VGG", "VGG16", "VGG19", "Xception",
    "SUPPORTED_MODELS", "SUPPORTED_MODEL_NAMES", "ModelSpec",
    "build_featurizer", "build_predictor", "get_model_spec",
]
