"""ML-layer degradation: corrupt image rows → null output cells, the
partition completes, drops surface as a warning (docs/RESILIENCE.md)."""

import logging

import numpy as np

import jax.numpy as jnp

from sparkdl_tpu.core import ModelFunction, TensorSpec
from sparkdl_tpu.core.resilience import FaultInjector
from sparkdl_tpu.engine.dataframe import DataFrame
from sparkdl_tpu.image import imageIO
from sparkdl_tpu.ml.image_transformer import TPUImageTransformer


def _mean_model():
    return ModelFunction.fromFunction(
        lambda vs, x: jnp.mean(x, axis=(1, 2)), None,
        TensorSpec((None, 8, 8, 3)))


def _image_df(rng, n=6, corrupt=()):
    structs = [imageIO.imageArrayToStruct(
        rng.integers(0, 255, (8, 8, 3), dtype=np.uint8), origin=f"r{i}")
        for i in range(n)]
    for i, how in corrupt:
        if how == "truncate":
            structs[i] = dict(structs[i], data=structs[i]["data"][:10])
        elif how == "badmode":
            structs[i] = dict(structs[i], mode=99)
    return structs, DataFrame.fromRows([{"image": s} for s in structs])


def test_corrupt_rows_yield_null_cells_partition_completes(rng, caplog):
    structs, df = _image_df(rng, corrupt=[(2, "truncate"), (4, "badmode")])
    t = TPUImageTransformer(inputCol="image", outputCol="out",
                            modelFunction=_mean_model(), batchSize=4)
    with caplog.at_level(logging.WARNING,
                         logger="sparkdl_tpu.ml.image_transformer"):
        rows = t.transform(df).collect()
    outs = [r["out"] for r in rows]
    assert [i for i, o in enumerate(outs) if o is None] == [2, 4]
    # surviving rows compute exactly what an all-clean run would
    for i in (0, 1, 3, 5):
        want = imageIO.imageStructToArray(structs[i]).astype(
            np.float32).mean(axis=(0, 1))
        np.testing.assert_allclose(np.asarray(outs[i], dtype=np.float32),
                                   want, rtol=1e-5)
    # the per-partition drop count is surfaced
    assert any("undecodable image row" in r.message
               for r in caplog.records)


def test_injected_decode_error_yields_null_cell(rng):
    # non-uniform sizes force the per-row (decode) path where the
    # decode_error injection point lives — within ONE partition: the
    # default count follows the machine's cores, and four one-row
    # partitions are each uniform and take the zero-copy path, which
    # decodes nothing and so has no such point
    structs = [imageIO.imageArrayToStruct(
        rng.integers(0, 255, (8 + (i == 0), 8, 3), dtype=np.uint8))
        for i in range(4)]
    df = DataFrame.fromRows([{"image": s} for s in structs],
                            numPartitions=1)
    t = TPUImageTransformer(inputCol="image", outputCol="out",
                            modelFunction=_mean_model(), batchSize=4,
                            inputSize=(8, 8))
    baseline = [r["out"] for r in t.transform(df).collect()]
    assert all(o is not None for o in baseline)
    with FaultInjector.seeded(0, decode_error=1) as inj:
        outs = [r["out"] for r in t.transform(df).collect()]
    assert inj.fired["decode_error"] == 1
    assert sum(o is None for o in outs) == 1
    # uncorrupted rows unchanged
    for b, o in zip(baseline, outs):
        if o is not None:
            np.testing.assert_array_equal(np.asarray(b), np.asarray(o))


def test_predictor_corrupt_row_decodes_to_null_topk(rng):
    """End to end through DeepImagePredictor: a corrupt image row flows
    through as a null raw vector and a null decoded top-K cell; the
    remaining rows still decode (docs/RESILIENCE.md)."""
    from sparkdl_tpu.ml.named_image import DeepImagePredictor

    structs = [imageIO.imageArrayToStruct(
        rng.integers(0, 255, (32, 32, 3), dtype=np.uint8))
        for _ in range(4)]
    structs[1] = dict(structs[1], data=structs[1]["data"][:13])  # corrupt
    df = DataFrame.fromRows([{"image": s} for s in structs])
    p = DeepImagePredictor(inputCol="image", outputCol="preds",
                           modelName="TestNet", decodePredictions=True,
                           topK=3, batchSize=4)
    rows = p.transform(df).collect()
    assert len(rows) == 4
    assert rows[1]["preds"] is None
    for i in (0, 2, 3):
        entry = rows[i]["preds"]
        assert len(entry) == 3
        assert all(e["class"] for e in entry)
