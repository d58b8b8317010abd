"""The reference's headline workflow, end to end on this framework.

Mirrors the upstream README example (SURVEY.md §0): read images into a
DataFrame, featurize with a pre-trained named CNN, train a logistic
regression on the features — as ONE Pipeline — then serve the model as
a SQL UDF over a temp view.

Run (an example, not a measurement: it runs wherever JAX puts it, prints
which device that was, and times nothing — `chip_smoke.py` and `bench.py`
are the paths that refuse the CPU):
    python examples/flagship_pipeline.py
"""

import os
import sys
import tempfile

import numpy as np
from PIL import Image

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from sparkdl_tpu import DataFrame, readImages, registerImageUDF, sql
from sparkdl_tpu.ml import (
    DeepImageFeaturizer,
    LogisticRegression,
    Pipeline,
    load,
)
from sparkdl_tpu.models import registry


def make_dataset(directory: str, n: int = 32):
    """Tiny two-class image set: class c brightens channel c."""
    rng = np.random.default_rng(0)
    labels = {}
    for i in range(n):
        label = i % 2
        arr = rng.integers(0, 40, size=(64, 64, 3), dtype=np.uint8)
        arr[..., label] += 150
        path = os.path.join(directory, f"img_{i:03d}.png")
        Image.fromarray(arr).save(path)
        labels[path] = label
    return labels


def main() -> None:
    import jax

    device = jax.devices()[0]
    print(f"running on {device.platform} ({device.device_kind}) — an "
          "example, not a measurement")
    with tempfile.TemporaryDirectory() as d:
        labels = make_dataset(d)

        # 1. images -> DataFrame (Spark ImageSchema struct column;
        #    origin carries the Spark-style "file:" scheme)
        df = readImages(d, numPartition=4)
        df = df.withColumn(
            "label",
            lambda image: labels[image["origin"].removeprefix("file:")],
            inputCols=["image"])

        # 2. featurize + classify as ONE pipeline (TestNet keeps the
        #    example fast; swap modelName="InceptionV3" for the real zoo)
        pipeline = Pipeline(stages=[
            DeepImageFeaturizer(inputCol="image", outputCol="features",
                                modelName="TestNet", batchSize=16),
            LogisticRegression(maxIter=200),
        ])
        model = pipeline.fit(df)
        scored = model.transform(df).collect()
        acc = np.mean([r["prediction"] == r["label"] for r in scored])
        print(f"train accuracy: {acc:.3f}")

        # 3. persistence round-trip
        save_dir = os.path.join(d, "fitted_pipeline")
        model.save(save_dir)
        reloaded = load(save_dir)
        assert [r["prediction"] for r in reloaded.transform(df).collect()] \
            == [r["prediction"] for r in scored]
        print("save/load round-trip OK")

        # 4. model-as-SQL-UDF serving (the reference's §3.4 path)
        mf = registry.build_featurizer("TestNet", weights="random")
        registerImageUDF("featurize", mf, batchSize=16)
        df.createOrReplaceTempView("images")
        served = sql("SELECT featurize(image) AS features, label "
                     "FROM images WHERE label = 1").collect()
        print(f"SQL serving: {len(served)} rows, "
              f"{len(served[0]['features'])}-dim features")

        # 5. cluster inference plane (docs/DISTRIBUTED.md "Cluster
        #    inference"): the same transform fanned across 2 worker
        #    processes — bit-identical output, one merged report. A TPU
        #    chip belongs to one process and this one holds it, so the
        #    plane does not run on a TPU backend yet.
        if device.platform == "tpu":
            print("cluster: skipped — the cluster plane does not run on "
                  "a TPU backend yet (one process per chip)")
            return
        from sparkdl_tpu.cluster import router as cluster_router
        from sparkdl_tpu.engine import EngineConfig

        EngineConfig.cluster_workers = 2
        try:
            fanned = model.transform(df).collect()
        finally:
            EngineConfig.cluster_workers = 0
            cluster_router.shutdown()  # workers ship their snapshots here
        assert [r["prediction"] for r in fanned] \
            == [r["prediction"] for r in scored]
        report = cluster_router.last_cluster_report()
        print(f"cluster: {report['worker_count']} workers, "
              f"rows/worker {report['rows_per_worker']}, "
              f"health_consistent={report['health_consistent']}")


if __name__ == "__main__":
    main()
