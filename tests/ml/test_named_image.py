"""DeepImageFeaturizer / DeepImagePredictor tests.

Uses TestNet (tiny deterministic model, SURVEY.md §2.2 Models.scala parity)
so tests don't need pretrained weights, exactly like the reference's Scala
suite did.
"""

import numpy as np
import pyarrow as pa
import pytest

from sparkdl_tpu.engine.dataframe import DataFrame
from sparkdl_tpu.image import imageIO
from sparkdl_tpu.ml import DeepImageFeaturizer, DeepImagePredictor
from sparkdl_tpu.models import registry


@pytest.fixture
def image_df(rng):
    rows = []
    for i in range(5):
        arr = rng.integers(0, 255, size=(40, 36, 3), dtype=np.uint8)
        rows.append({"image": imageIO.imageArrayToStruct(arr, origin=f"i{i}")})
    return DataFrame.fromRows(
        rows, schema=pa.schema([pa.field("image", imageIO.imageSchema)]),
        numPartitions=2)


def test_featurizer_output_dim_and_determinism(image_df):
    f = DeepImageFeaturizer(inputCol="image", outputCol="features",
                            modelName="TestNet", batchSize=4)
    out1 = f.transform(image_df).collect()
    out2 = f.transform(image_df).collect()
    spec = registry.get_model_spec("TestNet")
    assert len(out1[0]["features"]) == spec.feature_dim
    np.testing.assert_array_equal(
        np.array([r["features"] for r in out1]),
        np.array([r["features"] for r in out2]))


def test_featurizer_matches_direct_model_function(image_df):
    # oracle: the same registry ModelFunction applied by hand, with the
    # SAME resize policy the transformer's uniform fast path uses (host
    # native downscale / device bilinear — both no-antialias pixel-center,
    # NOT the PIL path; see ml/image_transformer._resize_uniform_batch).
    from sparkdl_tpu.ml.image_transformer import _resize_uniform_batch

    f = DeepImageFeaturizer(inputCol="image", outputCol="features",
                            modelName="TestNet")
    got = np.array([r["features"]
                    for r in f.transform(image_df).collect()], dtype=np.float32)
    mf = registry.build_featurizer("TestNet")
    spec = registry.get_model_spec("TestNet")
    structs = [r["image"] for r in image_df.collect()]
    batch = imageIO.imageStructsToBatchArray(structs, target_size=None,
                                             dtype=None)
    staged, run = _resize_uniform_batch(batch, spec.input_size, mf)
    want = np.asarray(run.apply_batch(staged, batch_size=8)
                      ).reshape(len(structs), -1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    # Independent cross-implementation oracle: the numpy bilinear resize is
    # a distinct implementation from whichever path the transform used
    # (native C++ / device XLA); they agree to uint8 rounding. The 40x36
    # non-square fixture makes an H/W transpose a hard failure here.
    npy = imageIO.resizeBatchArray(batch, spec.input_size)
    want_np = np.asarray(mf.apply_batch(npy, batch_size=8)
                         ).reshape(len(structs), -1)
    np.testing.assert_allclose(got, want_np, rtol=0.1, atol=0.02)


def test_predictor_probabilities_sum_to_one(image_df):
    p = DeepImagePredictor(inputCol="image", outputCol="preds",
                           modelName="TestNet")
    out = p.transform(image_df).collect()
    probs = np.array([r["preds"] for r in out], dtype=np.float32)
    spec = registry.get_model_spec("TestNet")
    assert probs.shape == (5, spec.classes)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-4)


def test_predictor_decode_topk(image_df):
    p = DeepImagePredictor(inputCol="image", outputCol="preds",
                           modelName="TestNet", decodePredictions=True,
                           topK=3)
    out = p.transform(image_df).collect()
    row = out[0]["preds"]
    assert len(row) == 3
    # descending probability, fields present
    probs = [e["probability"] for e in row]
    assert probs == sorted(probs, reverse=True)
    assert all(e["class"] and e["description"] is not None for e in row)
    # raw column dropped
    assert "preds__raw" not in out[0]


def test_unknown_model_name_rejected():
    with pytest.raises(TypeError, match="supported list"):
        DeepImageFeaturizer(inputCol="image", outputCol="f",
                            modelName="NotAModel")


def test_featurizer_param_copy_isolated(image_df):
    f = DeepImageFeaturizer(inputCol="image", outputCol="features",
                            modelName="TestNet")
    g = f.copy({f.batchSize: 2})
    assert g.getBatchSize() == 2
    assert f.getBatchSize() == 64


def test_ingested_named_featurizer_and_persistence(rng, tmp_path):
    """Registry names WITHOUT a Flax definition (r4: DenseNet121,
    EfficientNetB0, MobileNetV3Small, NASNetMobile) serve through generic
    keras ingestion. Keras init is unseeded, so persistence must save the
    actual weights — the reloaded stage reproduces outputs exactly."""
    pytest.importorskip("keras")
    from sparkdl_tpu.ml import load

    rows = [{"image": imageIO.imageArrayToStruct(
        rng.integers(0, 255, size=(64, 64, 3), dtype=np.uint8),
        origin=str(i))} for i in range(3)]
    df = DataFrame.fromRows(
        rows, schema=pa.schema([pa.field("image", imageIO.imageSchema)]),
        numPartitions=1)
    t = DeepImageFeaturizer(inputCol="image", outputCol="f",
                            modelName="MobileNetV3Small", batchSize=2)
    out = t.transform(df).collect()
    feats = np.array([r["f"] for r in out], np.float32)
    assert feats.shape == (3, 576)
    t.save(str(tmp_path / "ingested"))
    t2 = load(str(tmp_path / "ingested"))
    feats2 = np.array([r["f"] for r in t2.transform(df).collect()],
                      np.float32)
    np.testing.assert_allclose(feats2, feats, rtol=1e-5, atol=1e-6)


def test_ingested_model_names_listed():
    from sparkdl_tpu.models import registry

    for name in ("DenseNet121", "EfficientNetB0", "MobileNetV3Small",
                 "NASNetMobile"):
        assert name in registry.SUPPORTED_MODEL_NAMES
        assert registry.is_ingested_model(name)
        spec = registry.get_model_spec(name)
        assert spec.input_size == (224, 224)


def test_ingested_copy_shares_built_model(rng):
    """A paramMap copy of an ingested-name stage reuses the SAME built
    model (keras init is unseeded — a rebuild would emit incompatible
    features)."""
    pytest.importorskip("keras")
    rows = [{"image": imageIO.imageArrayToStruct(
        rng.integers(0, 255, size=(48, 48, 3), dtype=np.uint8))}
        for _ in range(2)]
    df = DataFrame.fromRows(
        rows, schema=pa.schema([pa.field("image", imageIO.imageSchema)]),
        numPartitions=1)
    t = DeepImageFeaturizer(inputCol="image", outputCol="f",
                            modelName="MobileNetV3Small", batchSize=2)
    a = np.array([r["f"] for r in t.transform(df).collect()], np.float32)
    b = np.array([r["f"] for r in t.transform(
        df, {t.batchSize: 4}).collect()], np.float32)
    np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-6)


def test_ingested_rejects_bad_weights_and_wrong_head(rng, tmp_path):
    from sparkdl_tpu.models import registry

    with pytest.raises(TypeError, match="Cannot resolve weights"):
        registry.build_featurizer("MobileNetV3Small",
                                  weights={"params": {}})
    # a full model (with classifier head) supplied to the featurizer role
    keras = pytest.importorskip("keras")
    full = keras.applications.MobileNetV3Small(
        weights=None, classes=7, input_shape=(224, 224, 3))
    with pytest.raises(ValueError, match="features"):
        registry.build_featurizer("MobileNetV3Small", weights=full)


def test_ingested_custom_graph_persistence(rng, tmp_path):
    """A CUSTOM Keras graph supplied as weights for an ingested name
    (only the output head is validated) must survive save/load — the
    stage persists the model itself via Keras serialization, since
    msgpack weights could not restore a non-canonical architecture."""
    keras = pytest.importorskip("keras")
    from keras import layers as L

    from sparkdl_tpu.ml import load

    custom = keras.Sequential([
        keras.Input((224, 224, 3)),
        L.Conv2D(8, 3, strides=8, padding="same"),
        L.GlobalAveragePooling2D(),
        L.Dense(576)])  # matches MobileNetV3Small's 576-dim contract
    rows = [{"image": imageIO.imageArrayToStruct(
        rng.integers(0, 255, size=(32, 32, 3), dtype=np.uint8))}
        for _ in range(2)]
    df = DataFrame.fromRows(
        rows, schema=pa.schema([pa.field("image", imageIO.imageSchema)]),
        numPartitions=1)
    t = DeepImageFeaturizer(inputCol="image", outputCol="f",
                            modelName="MobileNetV3Small", weights=custom,
                            batchSize=2)
    want = np.array([r["f"] for r in t.transform(df).collect()], np.float32)
    t.save(str(tmp_path / "custom"))
    t2 = load(str(tmp_path / "custom"))
    got = np.array([r["f"] for r in t2.transform(df).collect()], np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_keras_reference_covers_ingested_names():
    from sparkdl_tpu.models import registry

    ctor = registry._resolve_keras_ctor("DenseNet121")
    assert ctor.__name__ == "DenseNet121"
    with pytest.raises(ValueError, match="counterpart"):
        registry._resolve_keras_ctor("NoSuchNet")


def test_ingested_bf16_saves_full_precision_weights(rng, tmp_path):
    """ADVICE r4: a dtype=bfloat16 ingested stage must persist the
    PRE-cast f32 weights, so reloading the artifact as float32 recovers
    full precision (not bf16-truncated values)."""
    pytest.importorskip("keras")
    import flax.serialization as fser
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.ml import load

    t = DeepImageFeaturizer(inputCol="image", outputCol="f",
                            modelName="MobileNetV3Small", batchSize=2,
                            dtype=jnp.bfloat16)
    mf = t._model_function("featurize")
    assert hasattr(mf, "float_source")  # survives the preprocess wrap
    t.save(str(tmp_path / "bf16"))
    # the artifact holds float32 leaves, not bf16-truncated ones
    with open(tmp_path / "bf16" / "weights.msgpack", "rb") as f:
        raw = fser.msgpack_restore(f.read())
    float_leaves = [l for l in jax.tree.leaves(raw)
                    if hasattr(l, "dtype") and l.dtype.kind == "f"]
    assert float_leaves and all(
        l.dtype == np.float32 for l in float_leaves), sorted(
        {str(l.dtype) for l in float_leaves})
    # and the saved values equal the pre-cast source exactly
    src = jax.device_get(mf.float_source.variables)
    got_leaves = jax.tree.leaves(raw)
    want_leaves = jax.tree.leaves(src)
    assert len(got_leaves) == len(want_leaves)
    for g, w in zip(got_leaves, want_leaves):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # reloaded at f32, the stage serves full-precision outputs
    t32 = load(str(tmp_path / "bf16"))
    t32.setDtype(None)
    rows = [{"image": imageIO.imageArrayToStruct(
        rng.integers(0, 255, size=(64, 64, 3), dtype=np.uint8),
        origin="0")}]
    df = DataFrame.fromRows(
        rows, schema=pa.schema([pa.field("image", imageIO.imageSchema)]),
        numPartitions=1)
    out = t32.transform(df).collect()
    assert np.asarray(out[0]["f"], np.float32).shape == (576,)


def test_r5_zoo_size_variants_registered():
    """r5 zoo widening: size variants of the oracle-proven ingestion
    families. Every name's feature_dim is validated against the KERAS
    model's own headless pooled output width (construction only, no
    forward — a registry-vs-registry comparison would be tautological);
    one representative (the smallest) additionally builds and runs
    end-to-end. Family-level walker correctness is pinned by the oracle
    tests in tests/models/test_keras_oracle.py."""
    pytest.importorskip("keras")
    from sparkdl_tpu.models import registry

    for name in ("DenseNet169", "DenseNet201", "ResNet101V2",
                 "ResNet152V2", "EfficientNetB1", "MobileNetV3Large"):
        assert name in registry.SUPPORTED_MODEL_NAMES
        spec = registry.get_model_spec(name)
        h, w = spec.input_size
        ctor = registry._resolve_keras_ctor(name)
        assert ctor.__name__ == name
        kmodel = ctor(weights=None, include_top=False, pooling="avg",
                      input_shape=(h, w, 3))
        assert kmodel.output_shape[-1] == spec.feature_dim, name
    mf = registry.build_featurizer("MobileNetV3Large", weights="random")
    out = mf.apply_fn(mf.variables,
                      np.zeros((1, 224, 224, 3), np.float32))
    assert out.shape == (1, 960)


def test_collect_assembles_the_features_column_through_numpy(rng):
    """PR 31: under a scope, featurize → collect() says the features went
    through numpy (counter = rows × width, one vector column, the image
    struct falls back to Arrow); a row that does not decode stays None
    between neighbours that are lists of float."""
    from sparkdl_tpu.core import telemetry

    structs = [imageIO.imageArrayToStruct(
        rng.integers(0, 255, size=(40, 36, 3), dtype=np.uint8),
        origin=f"i{i}") for i in range(5)]
    structs[2] = dict(structs[2], data=structs[2]["data"][:10])
    df = DataFrame.fromRows(
        [{"image": s} for s in structs],
        schema=pa.schema([pa.field("image", imageIO.imageSchema)]),
        numPartitions=2)
    out = DeepImageFeaturizer(inputCol="image", outputCol="features",
                              modelName="TestNet", batchSize=4).transform(df)
    with telemetry.Telemetry() as tel:
        rows = out.collect()
    width = registry.get_model_spec("TestNet").feature_dim
    assert [r["features"] is None for r in rows] == \
        [False, False, True, False, False]
    for r in rows[:2] + rows[3:]:
        assert type(r["features"]) is list and len(r["features"]) == width
        assert {type(v) for v in r["features"]} == {float}
    assert type(rows[0]["image"]["data"]) is bytes
    counters = tel.metrics.snapshot()["counters"]
    assert counters[telemetry.M_COLLECT_VECTORIZED_VALUES] == 4 * width
    spans = tel.tracer.spans(telemetry.SPAN_ROW_ASSEMBLY)
    ahead = [s for s in spans if s["attributes"].get("ahead")]
    spans = [s for s in spans if s not in ahead]
    # one span a partition where the first was assembled under the second's
    # program or the image cells were built ahead, one over the table where
    # both resolved in one tick before any was built
    assert sorted(s["attributes"]["rows"] for s in spans) in ([5], [2, 3])
    built = {s["attributes"]["partition"] for s in ahead}
    for span in ahead:      # the image struct, ahead of its program's end
        assert span["attributes"]["vector_columns"] == 0
        assert span["attributes"]["fallback_columns"] == 1
    for span in spans:      # the struct falls back to Arrow where not built
        assert span["attributes"]["vector_columns"] == 1
        assert span["attributes"]["fallback_columns"] == int(
            span["attributes"].get("partition") not in built)
    assert rows == out.toArrow().to_pylist()
