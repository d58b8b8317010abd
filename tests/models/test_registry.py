"""Registry + featurizer/predictor tests (fast path: TestNet; shape checks
for the big families run through jax.eval_shape so no heavy compute)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.core.model_function import TensorSpec
from sparkdl_tpu.models import (
    SUPPORTED_MODEL_NAMES, build_featurizer, build_predictor, get_model_spec,
    registry,
)


def test_supported_models_cover_reference_surface():
    # The reference registry (SURVEY.md §2.1 keras_applications.py) carried
    # InceptionV3, Xception, ResNet50, VGG16, VGG19; BASELINE.json adds
    # MobileNetV2. TestNet mirrors the Scala test resource.
    for required in ("InceptionV3", "Xception", "ResNet50", "VGG16", "VGG19",
                     "MobileNetV2", "TestNet"):
        assert required in SUPPORTED_MODEL_NAMES


def test_unknown_model_rejected():
    with pytest.raises(ValueError):
        get_model_spec("NopeNet")


def test_testnet_featurizer_end_to_end(rng):
    mf = build_featurizer("TestNet", seed=0)
    x = rng.uniform(0, 255, size=(3, 32, 32, 3)).astype(np.float32)
    feats = mf.apply_batch(x, batch_size=2)
    assert feats.shape == (3, 16)
    # deterministic across rebuilds with same seed
    mf2 = build_featurizer("TestNet", seed=0)
    np.testing.assert_allclose(feats, mf2.apply_batch(x, batch_size=2),
                               rtol=1e-6)


def test_testnet_predictor_probabilities(rng):
    mf = build_predictor("TestNet", seed=0)
    x = rng.uniform(0, 255, size=(2, 32, 32, 3)).astype(np.float32)
    probs = np.asarray(mf(x))
    assert probs.shape == (2, 10)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-5)


@pytest.mark.parametrize("name", ["InceptionV3", "ResNet50", "Xception",
                                  "VGG16", "VGG19", "MobileNetV2"])
def test_feature_dims_by_shape_inference(name):
    """Validate declared feature_dim without running the network."""
    spec = get_model_spec(name)
    kwargs = dict(spec.featurize_kwargs or {"include_top": False,
                                            "pooling": "avg"})
    module = spec.builder(**kwargs)
    h, w = spec.input_size
    x = jnp.zeros((1, h, w, 3), jnp.float32)
    var_shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), x))
    out = jax.eval_shape(
        lambda v: module.apply(v, x), var_shapes)
    assert out.shape == (1, spec.feature_dim)


def test_preprocess_modes():
    x = jnp.full((1, 2, 2, 3), 255.0)
    np.testing.assert_allclose(np.asarray(registry.preprocess_tf_mode(x)),
                               1.0, atol=1e-6)
    caffe = np.asarray(registry.preprocess_caffe_mode(x))
    # BGR swap + mean subtract
    np.testing.assert_allclose(
        caffe[0, 0, 0], 255.0 - np.asarray(registry._CAFFE_MEAN), atol=1e-4)


def test_featurizer_weights_roundtrip_msgpack(tmp_path, rng):
    mf = build_featurizer("TestNet", seed=0)
    p = tmp_path / "w.msgpack"
    mf.toMsgpack(str(p))
    mf2 = build_featurizer("TestNet", weights=str(p))
    x = rng.uniform(0, 255, size=(2, 32, 32, 3)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(mf(x)), np.asarray(mf2(x)),
                               rtol=1e-6)


# -- sequence models: what the registry names, by shape only -------------------

_SEQUENCE_STACKS = {
    # name → (config type, has expert layers, has state-space sizes, rotary)
    "openPangu-Ultra-MoE-718B": ("LatentMoEConfig", True, False, True),
    "TestLatentMoE": ("LatentMoEConfig", True, False, True),
    "LFM2-8B-A1B": ("ShortConvMoEConfig", True, False, True),
    "TestShortConvMoE": ("ShortConvMoEConfig", True, False, True),
    "Mellum2-12B-A2.5B-Instruct": ("ShortConvMoEConfig", True, False, True),
    "TestSpanMoE": ("ShortConvMoEConfig", True, False, True),
    "AI21-Jamba2-3B": ("ShortConvMoEConfig", False, True, False),
    "TestStateSpace": ("ShortConvMoEConfig", False, True, False),
    "Laguna-XS.2": ("ShortConvMoEConfig", True, False, True),
    "TestLaguna": ("ShortConvMoEConfig", True, False, True),
    "Ling-3.0-flash": ("ShortConvMoEConfig", True, False, True),
    "TestLing": ("ShortConvMoEConfig", True, False, True),
}


def test_sequence_models_are_the_ten_the_docs_name():
    assert set(registry.SEQUENCE_MODELS) == set(_SEQUENCE_STACKS)


@pytest.mark.parametrize("name", sorted(_SEQUENCE_STACKS))
def test_sequence_model_config_says_what_its_stack_has(name):
    """The expert config is none exactly where the model has no expert
    layer, the state-space sizes set exactly where it has such layers, and a
    model without a rotary gives no ``theta``."""
    kind, experts, state_space, rotary = _SEQUENCE_STACKS[name]
    c = registry.SEQUENCE_MODELS[name]
    assert type(c).__name__ == kind
    assert (c.moe is not None) == experts
    if c.moe is not None:
        assert (c.moe.experts > 0 and c.moe.top_k > 0
                and len(c.moe.experts_held) == c.moe.experts
                and c.moe.expert_width > 0)
    if kind == "ShortConvMoEConfig":
        assert (c.d_inner > 0 and c.d_state > 0 and c.dt_rank > 0
                and c.d_conv > 0) == state_space
        if not state_space:
            assert (c.d_inner, c.d_state, c.dt_rank, c.d_conv) == (0, 0, 0, 0)
        else:
            assert c.d_inner == 2 * c.hidden and c.d_inner % 128 == 0
    assert (c.theta is not None) == rotary
    assert c.hidden > 0 and c.vocab > 0 and c.dense_width > 0


def test_a_test_twin_differs_from_its_model_by_size_only():
    """``TestStateSpace`` is ``AI21-Jamba2-3B`` at sizes a CPU runs: the same
    fields set and unset, one key head, no rotary, the published eps."""
    import dataclasses

    big = dataclasses.asdict(registry.SEQUENCE_MODELS["AI21-Jamba2-3B"])
    small = dataclasses.asdict(registry.SEQUENCE_MODELS["TestStateSpace"])
    assert {k for k, v in big.items() if not v} == {
        k for k, v in small.items() if not v}
    assert (big["kv_heads"], big["eps"], big["theta"], big["d_state"],
            big["d_conv"]) == (small["kv_heads"], small["eps"],
                               small["theta"], small["d_state"],
                               small["d_conv"]) == (1, 1e-6, None, 16, 4)
