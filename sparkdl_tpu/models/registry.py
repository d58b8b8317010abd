"""Named-model registry — the reference's ``keras_applications.py`` +
Scala ``Models.scala`` rebuilt (SURVEY.md §2.1/§2.2).

Each entry carries: the Flax module builder, fixed input size, the
device-side preprocessing function (fused into the same XLA program as the
model — the ``buildSpImageConverter`` splice, SURVEY.md §3.2), feature
dimension, and how to obtain weights. Weight sources:

- ``"random"``: seeded init (tests / no-network environments),
- a Flax variables dict,
- a Keras model object or H5/.keras file (converted via models.convert),
- a msgpack/Orbax path saved by this framework.
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.core import profiling
from sparkdl_tpu.core.model_function import ModelFunction, TensorSpec
from sparkdl_tpu.models import latent_moe, shortconv_moe
from sparkdl_tpu.models.attention import Rope
from sparkdl_tpu.models.experts import ExpertConfig
from sparkdl_tpu.models.inception import InceptionV3
from sparkdl_tpu.models.latent_moe import LatentMoEConfig
from sparkdl_tpu.models.mobilenet import MobileNetV2
from sparkdl_tpu.models.resnet import ResNet50, ResNet101, ResNet152
from sparkdl_tpu.models.shortconv_moe import ShortConvMoEConfig
from sparkdl_tpu.models.testnet import TestNet
from sparkdl_tpu.models.vgg import VGG16, VGG19
from sparkdl_tpu.models.xception import Xception

# ---------------------------------------------------------------------------
# Device-side preprocessing (input: float32 RGB in [0, 255], NHWC)
# ---------------------------------------------------------------------------

_CAFFE_MEAN = (103.939, 116.779, 123.68)  # BGR means, keras 'caffe' mode


def preprocess_tf_mode(x: jnp.ndarray) -> jnp.ndarray:
    """keras 'tf' mode: scale to [-1, 1]."""
    return x / 127.5 - 1.0


def preprocess_caffe_mode(x: jnp.ndarray) -> jnp.ndarray:
    """keras 'caffe' mode: RGB->BGR, subtract ImageNet means."""
    x = x[..., ::-1]
    mean = jnp.asarray(_CAFFE_MEAN, dtype=x.dtype)
    return x - mean


def preprocess_identity(x: jnp.ndarray) -> jnp.ndarray:
    return x


_TORCH_MEAN = (0.485, 0.456, 0.406)
_TORCH_STD = (0.229, 0.224, 0.225)


def preprocess_torch_mode(x: jnp.ndarray) -> jnp.ndarray:
    """keras 'torch' mode: [0,1] scale then ImageNet RGB mean/std."""
    x = x / 255.0
    mean = jnp.asarray(_TORCH_MEAN, dtype=x.dtype)
    std = jnp.asarray(_TORCH_STD, dtype=x.dtype)
    return (x - mean) / std


# Normalize-mode catalog: every per-model-family preprocess the registry
# fuses into the device program (ModelFunction.with_preprocess). The
# columnar-plane equivalence tests sweep this map so a newly added mode
# is covered automatically (tests/image/test_columnar_plane.py).
PREPROCESS_MODES: Dict[str, Callable] = {
    "tf": preprocess_tf_mode,
    "caffe": preprocess_caffe_mode,
    "torch": preprocess_torch_mode,
    "identity": preprocess_identity,
}


@dataclass(frozen=True)
class ModelSpec:
    name: str
    builder: Callable[..., Any]          # kwargs -> flax Module
    input_size: Tuple[int, int]          # (H, W)
    preprocess: Callable                 # device-side, jax-traceable
    feature_dim: int
    classes: int = 1000
    # kwargs used to build the *featurize* (headless) variant
    featurize_kwargs: Optional[Dict[str, Any]] = None
    # Forward FLOPs per image (2·MACs at the native input size) — the
    # bench's MFU fallback when XLA cost_analysis is unavailable for a
    # compiled featurize program. None = unknown (MFU omitted).
    flops_per_image: Optional[float] = None


SUPPORTED_MODELS: Dict[str, ModelSpec] = {
    "InceptionV3": ModelSpec(
        "InceptionV3", InceptionV3, (299, 299), preprocess_tf_mode, 2048,
        flops_per_image=5.7e9),
    "ResNet50": ModelSpec(
        "ResNet50", ResNet50, (224, 224), preprocess_caffe_mode, 2048,
        flops_per_image=7.75e9),
    "ResNet101": ModelSpec(
        "ResNet101", ResNet101, (224, 224), preprocess_caffe_mode, 2048),
    "ResNet152": ModelSpec(
        "ResNet152", ResNet152, (224, 224), preprocess_caffe_mode, 2048),
    "Xception": ModelSpec(
        "Xception", Xception, (299, 299), preprocess_tf_mode, 2048),
    "VGG16": ModelSpec(
        "VGG16", VGG16, (224, 224), preprocess_caffe_mode, 4096,
        featurize_kwargs={"include_top": True, "features_at_fc2": True}),
    "VGG19": ModelSpec(
        "VGG19", VGG19, (224, 224), preprocess_caffe_mode, 4096,
        featurize_kwargs={"include_top": True, "features_at_fc2": True}),
    "MobileNetV2": ModelSpec(
        "MobileNetV2", MobileNetV2, (224, 224), preprocess_tf_mode, 1280),
    "TestNet": ModelSpec(
        "TestNet", TestNet, (32, 32), preprocess_tf_mode, 16, classes=10),
}

# Ingestion-backed named models (r4): families WITHOUT an in-repo Flax
# definition serve through the generic keras layer-DAG walker
# (models/keras_ingest.py, oracle-exact per family) — DeepImageFeaturizer/
# Predictor accept these names exactly like the Flax-native ones. Weights:
# "random" (keras init) or an .h5/.keras file. Device preprocess follows
# each family's keras contract (EfficientNet/MobileNetV3 normalize
# in-model, so identity).
_INGESTED_MODELS: Dict[str, ModelSpec] = {
    "DenseNet121": ModelSpec(
        "DenseNet121", None, (224, 224), preprocess_torch_mode, 1024,
        flops_per_image=5.7e9),
    "EfficientNetB0": ModelSpec(
        "EfficientNetB0", None, (224, 224), preprocess_identity, 1280,
        flops_per_image=0.78e9),
    "MobileNetV3Small": ModelSpec(
        "MobileNetV3Small", None, (224, 224), preprocess_identity, 576),
    "NASNetMobile": ModelSpec(
        "NASNetMobile", None, (224, 224), preprocess_tf_mode, 1056),
    # r5: the remaining oracle-verified ingestion families (README layer
    # contract) exposed as named models. ResNet50V2 preprocesses in tf
    # mode (resnet_v2 contract); EfficientNetV2/ConvNeXt normalize
    # in-model, so device preprocess is identity.
    "ResNet50V2": ModelSpec(
        "ResNet50V2", None, (224, 224), preprocess_tf_mode, 2048),
    "EfficientNetV2B0": ModelSpec(
        "EfficientNetV2B0", None, (224, 224), preprocess_identity, 1280),
    "ConvNeXtTiny": ModelSpec(
        "ConvNeXtTiny", None, (224, 224), preprocess_identity, 768),
    # size variants of the proven families (every family has a
    # keras-forward oracle test in tests/models/test_keras_oracle.py;
    # per-name dims validate against keras output_shape in
    # tests/ml/test_named_image.py)
    "DenseNet169": ModelSpec(
        "DenseNet169", None, (224, 224), preprocess_torch_mode, 1664),
    "DenseNet201": ModelSpec(
        "DenseNet201", None, (224, 224), preprocess_torch_mode, 1920),
    "ResNet101V2": ModelSpec(
        "ResNet101V2", None, (224, 224), preprocess_tf_mode, 2048),
    "ResNet152V2": ModelSpec(
        "ResNet152V2", None, (224, 224), preprocess_tf_mode, 2048),
    "EfficientNetB1": ModelSpec(
        "EfficientNetB1", None, (240, 240), preprocess_identity, 1280),
    "MobileNetV3Large": ModelSpec(
        "MobileNetV3Large", None, (224, 224), preprocess_identity, 960),
}

_INGESTED_BUILDERS = {
    "DenseNet121": ("densenet", "DenseNet121"),
    "EfficientNetB0": ("efficientnet", "EfficientNetB0"),
    "MobileNetV3Small": (None, "MobileNetV3Small"),  # top-level export only
    "NASNetMobile": ("nasnet", "NASNetMobile"),
    "ResNet50V2": ("resnet_v2", "ResNet50V2"),
    "EfficientNetV2B0": ("efficientnet_v2", "EfficientNetV2B0"),
    "ConvNeXtTiny": ("convnext", "ConvNeXtTiny"),
    "DenseNet169": ("densenet", "DenseNet169"),
    "DenseNet201": ("densenet", "DenseNet201"),
    "ResNet101V2": ("resnet_v2", "ResNet101V2"),
    "ResNet152V2": ("resnet_v2", "ResNet152V2"),
    "EfficientNetB1": ("efficientnet", "EfficientNetB1"),
    "MobileNetV3Large": (None, "MobileNetV3Large"),
}


def _resolve_keras_ctor(name: str):
    """keras.applications constructor for any supported named model
    (shared by the ingestion builder and build_keras_reference)."""
    import importlib

    import keras

    entry = _KERAS_BUILDERS.get(name) or _INGESTED_BUILDERS.get(name)
    if entry is None:
        raise ValueError(
            f"No keras.applications counterpart for {name!r}; available: "
            f"{sorted(set(_KERAS_BUILDERS) | set(_INGESTED_BUILDERS))}")
    module_name, attr = entry
    if module_name is None:
        return getattr(keras.applications, attr)
    return getattr(importlib.import_module(
        f"keras.applications.{module_name}"), attr)

SUPPORTED_MODEL_NAMES = sorted(SUPPORTED_MODELS) + sorted(_INGESTED_MODELS)

# keras.applications builders for weight-bearing named models (used when the
# user asks for keras-initialized weights, or in oracle tests).
_KERAS_BUILDERS = {
    "InceptionV3": ("inception_v3", "InceptionV3"),
    "ResNet50": ("resnet", "ResNet50"),
    "Xception": ("xception", "Xception"),
    "VGG16": ("vgg16", "VGG16"),
    "VGG19": ("vgg19", "VGG19"),
    "MobileNetV2": ("mobilenet_v2", "MobileNetV2"),
}


# Sequence models (token-id windows in, a pooled state and per-token
# log-probabilities out): the published sizes, with every expert and the
# whole vocabulary. What a chip holds of them — how many layers and of which
# kind, which experts, which slice of the vocabulary — comes with the weights
# (build_sequence_scorer). The config's type names the module that runs it.
SequenceConfig = Union[LatentMoEConfig, ShortConvMoEConfig]
_SEQUENCE_FORWARD = {LatentMoEConfig: latent_moe.forward,
                     ShortConvMoEConfig: shortconv_moe.forward}

SEQUENCE_MODELS: Dict[str, SequenceConfig] = {
    # huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B config.json
    "openPangu-Ultra-MoE-718B": LatentMoEConfig(
        hidden=7680, heads=128, q_rank=1536, kv_rank=512, nope=128, rope=64,
        v=128, dense_width=18432, vocab=153600, layers=61, dense_layers=3,
        moe=ExpertConfig(expert_width=2048, experts=256,
                         experts_held=tuple(range(256)), top_k=8, scaling=2.5,
                         norm_topk=True, topk_eps=0.0),
        eps=1e-5, theta=25600000.0),
    # the same block at sizes a CPU test runs (TestNet's counterpart)
    "TestLatentMoE": LatentMoEConfig(
        hidden=64, heads=4, q_rank=32, kv_rank=16, nope=16, rope=8, v=16,
        dense_width=128, vocab=64, layers=3, dense_layers=2,
        moe=ExpertConfig(expert_width=32, experts=16,
                         experts_held=tuple(range(16)), top_k=4, scaling=2.5,
                         norm_topk=True, topk_eps=0.0),
        eps=1e-5, theta=25600000.0, query_block=8),
    # huggingface.co/LiquidAI/LFM2-8B-A1B config.json (model_type lfm2_moe)
    "LFM2-8B-A1B": ShortConvMoEConfig(
        hidden=2048, heads=32, kv_heads=8, head_dim=64, dense_width=7168,
        vocab=65536,
        moe=ExpertConfig(expert_width=1792, experts=32,
                         experts_held=tuple(range(32)), top_k=4, scaling=1.0,
                         norm_topk=True, topk_eps=1e-6),
        eps=1e-5, theta=1000000.0),
    # ... and its block at sizes a CPU test runs
    "TestShortConvMoE": ShortConvMoEConfig(
        hidden=64, heads=8, kv_heads=2, head_dim=8, dense_width=128,
        vocab=64,
        moe=ExpertConfig(expert_width=32, experts=16,
                         experts_held=tuple(range(16)), top_k=4, scaling=1.0,
                         norm_topk=True, topk_eps=1e-6),
        eps=1e-5, theta=1000000.0, query_block=8),
    # huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct config.json
    # (model_type mellum): the same pre-norm stack, attention in every layer,
    # three sliding-window layers (span 1,024, plain rotary) to a full one
    # (YaRN), a soft-max router, no dense layer, the head untied
    "Mellum2-12B-A2.5B-Instruct": ShortConvMoEConfig(
        hidden=2304, heads=32, kv_heads=4, head_dim=128, dense_width=7168,
        vocab=98304,
        moe=ExpertConfig(expert_width=896, experts=64,
                         experts_held=tuple(range(64)), top_k=8, scaling=1.0,
                         norm_topk=True, topk_eps=0.0, scoring="softmax"),
        eps=1e-6, theta=500000.0,
        layer_types=(("sliding_attention",) * 3 + ("full_attention",)) * 7,
        span=1024, rope=(("full_attention", Rope(
            theta=500000.0, factor=16.0, original=8192, beta_fast=32.0,
            beta_slow=1.0, amplitude=1.2772588722239782)),)),
    # ... and its stack at sizes a CPU test runs: two periods, a span shorter
    # than a test's window
    "TestSpanMoE": ShortConvMoEConfig(
        hidden=64, heads=8, kv_heads=2, head_dim=8, dense_width=128,
        vocab=64,
        moe=ExpertConfig(expert_width=32, experts=16,
                         experts_held=tuple(range(16)), top_k=4, scaling=1.0,
                         norm_topk=True, topk_eps=0.0, scoring="softmax"),
        eps=1e-6, theta=100.0,
        layer_types=(("sliding_attention",) * 3 + ("full_attention",)) * 2,
        span=8, rope=(("full_attention", Rope(
            theta=100.0, factor=16.0, original=32, beta_fast=4.0,
            beta_slow=1.0, amplitude=1.2772588722239782)),),
        query_block=8),
    # huggingface.co/ai21labs/AI21-Jamba2-3B config.json (model_type jamba):
    # the same pre-norm stack without an expert layer (num_experts 1: every
    # ffn the gated MLP), state-space (Mamba-1) mixers with an attention
    # layer where i % 14 == 7, 20 query heads on one key head, no rotary —
    # nothing tells positions apart but the recurrence and the causal mask —
    # and the head tied
    "AI21-Jamba2-3B": ShortConvMoEConfig(
        hidden=2560, heads=20, kv_heads=1, head_dim=128, dense_width=8192,
        vocab=65536, eps=1e-6, theta=None, d_inner=5120, d_state=16,
        dt_rank=160, d_conv=4),
    # ... and one period of it cut to a few layers at sizes a CPU test runs:
    # state-space layers around one attention layer
    "TestStateSpace": ShortConvMoEConfig(
        hidden=64, heads=4, kv_heads=1, head_dim=16, dense_width=128,
        vocab=64, eps=1e-6, theta=None, d_inner=128, d_state=16, dt_rank=8,
        d_conv=4, query_block=8),
    # huggingface.co/poolside/Laguna-XS.2 config.json (model_type laguna): the
    # same pre-norm stack, a full layer (48 query heads, YaRN over the first
    # half of a head) where i % 4 == 0 and three sliding ones (64 query heads,
    # span 512, the plain rotary over the whole head) to it, 8 key heads
    # throughout — the query heads come with the weights —, a sigmoid gate
    # on each head's output, a dense gated MLP in layer 0 and after it 256
    # experts of 512 with a shared one of 512, a sigmoid router renormalised
    # and scaled by 2.5, the head untied
    "Laguna-XS.2": ShortConvMoEConfig(
        hidden=2048, heads=48, kv_heads=8, head_dim=128, dense_width=8192,
        vocab=100352,
        moe=ExpertConfig(expert_width=512, experts=256,
                         experts_held=tuple(range(256)), top_k=8, scaling=2.5,
                         norm_topk=True, topk_eps=0.0, scoring="sigmoid"),
        eps=1e-6, theta=10000.0,
        layer_types=tuple("full_attention" if i % 4 == 0
                          else "sliding_attention" for i in range(40)),
        span=512, rope=(
            ("full_attention", Rope(
                theta=500000.0, factor=64.0, original=4096, beta_fast=64.0,
                beta_slow=1.0, amplitude=1.4158883083359672, partial=0.5)),
            ("sliding_attention", Rope(theta=10000.0)))),
    # ... and its stack at sizes a CPU test runs: two periods, 6 and 8 query
    # heads on 2 key heads, a span shorter than a test's window, a gate on
    # every attention layer, a shared expert beside the routed ones
    "TestLaguna": ShortConvMoEConfig(
        hidden=64, heads=6, kv_heads=2, head_dim=16, dense_width=128,
        vocab=64,
        moe=ExpertConfig(expert_width=32, experts=16,
                         experts_held=tuple(range(16)), top_k=4, scaling=2.5,
                         norm_topk=True, topk_eps=0.0, scoring="sigmoid"),
        eps=1e-6, theta=100.0,
        layer_types=(("full_attention",) + ("sliding_attention",) * 3) * 2,
        span=8, rope=(
            ("full_attention", Rope(
                theta=1000.0, factor=4.0, original=64, beta_fast=4.0,
                beta_slow=1.0, amplitude=1.1386294361119891, partial=0.5)),
            ("sliding_attention", Rope(theta=100.0))),
        query_block=8),
    # huggingface.co/inclusionAI/Ling-3.0-flash config.json (model_type
    # bailing_hybrid): the same pre-norm stack, linear attention with the
    # gated delta rule (KDA: taps, normed queries and keys, a decay a channel
    # floored at -5, a gated norm on the output) where (i + 1) % 6 != 0 and
    # latent attention where (i + 1) % 6 == 0 — no query latent, 64 of a
    # head's 192 turned by adjacent pairs, a per-head gate —, the mixer
    # coming with the weights; a dense gated MLP in layers 0-1, after them
    # 512 experts of 768 in 8 groups, the top 8 taken within the 4 groups
    # whose two best biased scores sum highest, a sigmoid router
    # renormalised and scaled by 2.5, a shared expert of 768, the SwiGLUs
    # clamped in layers 34-41; the head untied
    "Ling-3.0-flash": ShortConvMoEConfig(
        hidden=2560, heads=32, kv_heads=32, head_dim=128, dense_width=6144,
        vocab=157184,
        moe=ExpertConfig(expert_width=768, experts=512,
                         experts_held=tuple(range(512)), top_k=8, scaling=2.5,
                         norm_topk=True, topk_eps=0.0, scoring="sigmoid",
                         groups=8, topk_group=4),
        eps=1e-6, theta=6000000.0,
        rope=(("latent_attention", Rope(theta=6000000.0, interleave=True)),),
        kda_lower_bound=-5.0,
        swiglu_limits=tuple(zip((0.0,) * 35 + (4.0,) * 7,
                                (0.0,) * 34 + (5.0,) * 6 + (7.0,) * 2))),
    # ... and its stack at sizes a CPU test runs: a dense layer and one
    # period (linear, linear, linear, linear, latent, linear), 4 heads, 16
    # experts in 4 groups, the top 4 within the best 2, and the SwiGLUs
    # clamped in the last layer
    "TestLing": ShortConvMoEConfig(
        hidden=64, heads=4, kv_heads=4, head_dim=16, dense_width=128,
        vocab=64,
        moe=ExpertConfig(expert_width=32, experts=16,
                         experts_held=tuple(range(16)), top_k=4, scaling=2.5,
                         norm_topk=True, topk_eps=0.0, scoring="sigmoid",
                         groups=4, topk_group=2),
        eps=1e-6, theta=1000.0,
        rope=(("latent_attention", Rope(theta=1000.0, interleave=True)),),
        kda_lower_bound=-5.0,
        swiglu_limits=((0.0, 0.0),) * 6 + ((1.0, 1.5),), query_block=8),
}


def build_sequence_scorer(name, weights: Dict[str, Any], window: int,
                          experts_held=None) -> ModelFunction:
    """Named sequence model as a ModelFunction over ``(rows, window)`` int32
    token ids, emitting ``pooled``, ``logprobs`` and, from a stack with
    expert layers, ``expert_counts``.

    ``name``: a key of :data:`SEQUENCE_MODELS`, or a config of one's own of
    either of its types. ``weights``: the variables dict the model is run
    with — ``{"embed", "layers": [...], "final_norm", "head"}``, taken as
    given (bfloat16 on the device for a model of this size; there is no
    ``"random"``). The chip's share is read off them: as many layers as
    the list has, dense where a layer has ``"mlp"`` (every layer may be: a
    stack needs no expert layer), for a pre-norm stack the mixer by which
    one of ``"conv"``, ``"attn"``, ``"ssm"``, ``"kda"`` and ``"mla"`` a
    layer has, the vocabulary
    slice of ``embed``'s rows; ``experts_held`` names the expert ids the
    expert layers' stacked weights stand for (default: all of them, where
    all are there). Where the config names its layers' kinds the held layers
    are its leading ones, each of the kind at its position. A pre-norm
    layer's query and key heads are its weights' (columns ÷ ``head_dim``),
    the query heads a multiple of the key heads; a linear-attention
    (``"kda"``) or latent-attention (``"mla"``) layer's widths are its
    weights' too.
    """
    config = SEQUENCE_MODELS.get(name) if isinstance(name, str) else name
    label = name if isinstance(name, str) else type(config).__name__
    with profiling.model_build(f"{label}_score") as span:
        if type(config) not in _SEQUENCE_FORWARD:
            raise ValueError(f"Unsupported sequence model {name!r}; "
                             f"supported: {sorted(SEQUENCE_MODELS)}")
        layers = weights["layers"]
        dense = sum(1 for layer in layers if "mlp" in layer)
        if any("mlp" in layer for layer in layers[dense:]):
            raise ValueError("dense layers must lead the expert layers")
        stacked = {layer["moe"]["experts"]["down"].shape[0]
                   for layer in layers[dense:]}
        if experts_held is None:
            experts_held = range(config.moe.experts if config.moe else 0)
        experts_held = tuple(int(e) for e in experts_held)
        if stacked - {len(experts_held)}:
            raise ValueError(
                f"the expert layers hold {sorted(stacked)} experts' weights, "
                f"experts_held names {len(experts_held)}")
        share = {"vocab": int(weights["embed"].shape[0])}
        if config.moe is not None:
            share["moe"] = dataclasses.replace(config.moe,
                                               experts_held=experts_held)
        if isinstance(config, LatentMoEConfig):
            share.update(layers=len(layers), dense_layers=dense)
        elif not all(sum(mixer in layer for mixer in (
                "conv", "attn", "ssm", "kda", "mla")) == 1
                for layer in layers):
            raise ValueError('each layer holds its one mixer: "ssm", "kda", '
                             '"mla", "conv" or "attn"')
        elif not config.d_inner and any("ssm" in layer for layer in layers):
            raise ValueError('a layer holds "ssm" and the config gives no '
                             "state-space sizes (d_inner, d_state, dt_rank, "
                             "d_conv)")
        elif config.layer_types and not (
                len(layers) <= len(config.layer_types)
                and all("attn" in layer for layer in layers)):
            raise ValueError(
                f"the config names the kind of attention of its "
                f"{len(config.layer_types)} layers; the weights hold "
                f'{len(layers)}, which must be its leading ones, each "attn"')
        elif any(layer["attn"]["q"].shape[1] % layer["attn"]["k"].shape[1]
                 for layer in layers if "attn" in layer):
            raise ValueError("a layer's query heads must be a multiple of "
                             "its key heads")
        config = dataclasses.replace(config, **share)
        forward = _SEQUENCE_FORWARD[type(config)]
        mf = ModelFunction.fromFunction(
            lambda vs, tokens: forward(vs, tokens, config), weights,
            TensorSpec((None, int(window)), "int32"), name=f"{label}_score")
        span.set_attribute("bytes", mf.weight_bytes())
        return mf


def get_model_spec(name: str) -> ModelSpec:
    spec = SUPPORTED_MODELS.get(name) or _INGESTED_MODELS.get(name)
    if spec is None:
        raise ValueError(
            f"Unsupported model {name!r}; supported: {SUPPORTED_MODEL_NAMES}")
    return spec


def is_ingested_model(name: str) -> bool:
    return name in _INGESTED_MODELS


def _build_ingested(name: str, weights, include_top: bool,
                    dtype) -> ModelFunction:
    """Named model via keras build + generic ingestion (no Flax def)."""
    from sparkdl_tpu.models.keras_ingest import keras_to_model_function

    spec = _INGESTED_MODELS[name]
    h, w = spec.input_size
    msgpack_path = None
    if isinstance(weights, str) and weights.endswith((".h5", ".keras")):
        from sparkdl_tpu.models.convert import load_keras_file

        model = load_keras_file(weights)
    elif hasattr(weights, "layers"):
        model = weights
    else:
        # "random" (keras-initialized architecture) or a msgpack weights
        # file saved by this framework (named-model persistence). Anything
        # else raises — a silent random fallback would discard the user's
        # weights (the Flax path raises the same way, _resolve_variables).
        if weights is not None and not isinstance(weights, str):
            raise TypeError(
                f"Cannot resolve weights for ingested model {name!r} from "
                f"{type(weights).__name__}; pass 'random', a Keras model "
                "object, an .h5/.keras file, or a msgpack file saved by "
                "this framework")
        if isinstance(weights, str) and weights not in ("random",):
            # Opening unknown strings blind surfaced typos (or the
            # upstream-conventional 'imagenet' marker, which needs a
            # network this env doesn't have) as raw flax/IO errors
            # (ADVICE r4) — state the accepted values instead.
            if not os.path.exists(weights):
                raise ValueError(
                    f"weights={weights!r} for ingested model {name!r} is "
                    "neither a supported marker nor an existing file. "
                    "Accepted: 'random' (fresh keras init), a Keras model "
                    "object, an .h5/.keras model file, or a msgpack "
                    "weights file saved by this framework ('imagenet' "
                    "downloads are not available without network access)")
            msgpack_path = weights
        ctor = _resolve_keras_ctor(name)
        kwargs = {"weights": None, "input_shape": (h, w, 3)}
        if include_top:
            kwargs["classes"] = spec.classes
        else:
            kwargs.update(include_top=False, pooling="avg")
        model = ctor(**kwargs)
    mf = keras_to_model_function(
        model, name=f"{name}_{'predict' if include_top else 'featurize'}")
    # A user-supplied model/file is ingested verbatim — verify its output
    # matches the requested role instead of silently serving a classifier
    # head as "features" (the Flax path re-builds the headless
    # architecture; ingestion cannot, so it checks).
    out = jax.eval_shape(mf.apply_fn, mf.variables,
                         jnp.zeros((1, h, w, 3), jnp.float32))
    if not hasattr(out, "ndim"):  # multi-output graph -> dict of outputs
        raise ValueError(
            f"Ingested {name!r} model has multiple outputs; named "
            "featurizers/predictors bind ONE output column — serve "
            "multi-IO models via TPUTransformer instead")
    if out.ndim != 2:
        raise ValueError(
            f"Ingested {name!r} model emits shape {out.shape}; expected a "
            "(batch, features) head — save the model with "
            "include_top=False, pooling='avg'"
            if not include_top else
            f"Ingested {name!r} model emits shape {out.shape}; expected "
            "(batch, classes) probabilities")
    if not include_top and out.shape[-1] != spec.feature_dim:
        raise ValueError(
            f"Ingested {name!r} model emits {out.shape[-1]}-dim output but "
            f"the featurizer contract for this name is {spec.feature_dim} "
            "features — pass a headless (include_top=False, pooling='avg') "
            "model")
    if msgpack_path is not None:
        import flax.serialization as fser

        with open(msgpack_path, "rb") as f:
            mf.variables = fser.from_bytes(mf.variables, f.read())
    if dtype is not None:
        mf = mf.with_compute_dtype(dtype)
    return mf


def _resolve_variables(spec: ModelSpec, module, weights, seed: int,
                       input_spec: TensorSpec):
    """Resolve the ``weights`` argument to a Flax variables pytree."""
    if weights is None or weights == "random":
        rng = jax.random.PRNGKey(seed)
        # jit the init: eager init dispatches op by op (hundreds of tiny
        # programs for InceptionV3); jitted it is one compiled program.
        init = jax.jit(module.init)
        return init(rng, jnp.zeros(input_spec.with_batch(1),
                                   dtype=input_spec.dtype))
    if isinstance(weights, dict):
        return weights
    if isinstance(weights, str):
        if os.path.isdir(weights):
            import orbax.checkpoint as ocp

            template = jax.eval_shape(
                lambda: module.init(jax.random.PRNGKey(0),
                                    jnp.zeros(input_spec.with_batch(1),
                                              dtype=input_spec.dtype)))
            with ocp.StandardCheckpointer() as ckptr:
                return ckptr.restore(os.path.abspath(weights), template)
        if weights.endswith((".h5", ".keras")):
            from sparkdl_tpu.models.convert import (
                convert_keras_model, load_keras_file)

            return convert_keras_model(spec.name, load_keras_file(weights))
        # msgpack
        import flax.serialization as fser

        template = module.init(jax.random.PRNGKey(0),
                               jnp.zeros(input_spec.with_batch(1),
                                         dtype=input_spec.dtype))
        with open(weights, "rb") as f:
            return fser.from_bytes(template, f.read())
    # keras model object
    if hasattr(weights, "layers"):
        from sparkdl_tpu.models.convert import convert_keras_model

        return convert_keras_model(spec.name, weights)
    raise TypeError(f"Cannot resolve weights from {type(weights).__name__}")


def _spec_input(spec: ModelSpec) -> TensorSpec:
    h, w = spec.input_size
    return TensorSpec((None, h, w, 3), "float32")


def _fast_inference_apply(name: str, include_top: bool, dtype):
    """Inference-specialized apply for models that have one, else None.

    InceptionV3 has a fused fast path (BN folding + branch-fused 1x1 convs,
    ``models/inception_fast.py``), ~13% faster than the module apply on TPU
    (r3). It computes in ``dtype`` if the builder is given one, else in the
    float dtype of its input (bfloat16 under ``with_dtype``), else float32.
    """
    if name != "InceptionV3":
        return None
    from sparkdl_tpu.models.inception_fast import inception_v3_fast_apply

    def apply_fn(vs, x):
        if dtype is not None:
            compute_dtype = dtype
        elif jnp.issubdtype(x.dtype, jnp.floating):
            compute_dtype = x.dtype
        else:
            compute_dtype = jnp.float32
        return inception_v3_fast_apply(vs, x, include_top=include_top,
                                       pooling="avg",
                                       compute_dtype=compute_dtype)

    return apply_fn


def _build_named(name: str, include_top: bool, weights, seed: int, dtype,
                 preprocess: bool, fast: bool,
                 precision: Optional[str]) -> ModelFunction:
    """What :func:`build_featurizer` and :func:`build_predictor` share,
    under one ``sparkdl.model_build`` span (attributes ``model``,
    ``bytes``): weights resolved, the apply function chosen, preprocess
    and precision applied."""
    spec = get_model_spec(name)
    label = f"{name}_{'predict' if include_top else 'featurize'}"
    with profiling.model_build(label) as span:
        fast_apply = None
        if is_ingested_model(name):
            mf = _build_ingested(name, weights, include_top=include_top,
                                 dtype=dtype)
        else:
            if include_top:
                kwargs = {"include_top": True, "classes": spec.classes}
            else:
                kwargs = dict(spec.featurize_kwargs
                              or {"include_top": False, "pooling": "avg"})
            module = spec.builder(dtype=dtype, **kwargs)
            input_spec = _spec_input(spec)
            variables = _resolve_variables(spec, module, weights, seed,
                                           input_spec)
            if fast:
                fast_apply = _fast_inference_apply(name, include_top, dtype)
            if fast_apply is not None:
                mf = ModelFunction.fromFunction(fast_apply, variables,
                                                input_spec, name=label)
            else:
                mf = ModelFunction.fromFlax(module, variables, input_spec,
                                            name=label, train=False)
        if preprocess:
            mf = mf.with_preprocess(spec.preprocess)
        mf.fast_path = fast_apply is not None
        mf = _apply_precision(mf, precision)
        span.set_attribute("bytes", mf.weight_bytes())
    return mf


def build_featurizer(name: str, weights="random", seed: int = 0,
                     dtype=None, preprocess: bool = True,
                     fast: bool = True,
                     precision: Optional[str] = None) -> ModelFunction:
    """Headless named model as a ModelFunction emitting feature vectors.

    Input contract: float32 RGB [0,255] NHWC at the model's input size
    (host side resizes; scaling/mean-subtract runs on device, fused).
    ``fast=False`` forces the plain Flax-module apply even where an
    inference-specialized fast path exists. ``precision`` applies
    :meth:`ModelFunction.with_dtype` to the finished featurizer
    ("bfloat16" compute / "int8" weight-only PTQ; None or "float32"
    leaves it untouched) — note the engine's executor choke point applies
    ``EngineConfig.inference_precision`` itself, so this parameter is for
    standalone (non-engine) use of the registry.
    """
    return _build_named(name, False, weights, seed, dtype, preprocess, fast,
                        precision)


def build_predictor(name: str, weights="random", seed: int = 0,
                    dtype=None, preprocess: bool = True,
                    fast: bool = True,
                    precision: Optional[str] = None) -> ModelFunction:
    """Full named model (softmax probabilities) as a ModelFunction.

    ``precision``: see :func:`build_featurizer`."""
    return _build_named(name, True, weights, seed, dtype, preprocess, fast,
                        precision)


def _apply_precision(mf: ModelFunction,
                     precision: Optional[str]) -> ModelFunction:
    """with_dtype pass-through keeping fast_path on the returned model."""
    if precision is None or precision == "float32":
        return mf
    fast_path = mf.fast_path
    out = mf.with_dtype(precision)
    out.fast_path = fast_path
    return out


def build_keras_reference(name: str):
    """Instantiate the same architecture in keras (weights=None) — used by
    oracle tests and by users wanting keras-side verification. Covers the
    Flax-native AND ingestion-backed named models."""
    return _resolve_keras_ctor(name)(weights=None)
