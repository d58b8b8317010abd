"""Multi-host data-parallel training + inference through the public API.

The HorovodRunner-parity path (docs/DISTRIBUTED.md): launch ONE copy of
this script per host with the SPARKDL_* env triple set, and the
estimator/transformers handle partition assignment, per-host batch
shards, lockstep, and gradient all-reduce (XLA collectives) themselves.

Single-machine demo with 2 simulated hosts (4 virtual CPU devices each):

    python examples/distributed_train.py --launch

Real deployment: same script, one process per host,
SPARKDL_COORDINATOR=<host0>:<port> SPARKDL_NUM_PROCESSES=<n>
SPARKDL_PROCESS_ID=<rank>, and a mesh over the global TPU devices.
"""

import os
import sys

if __name__ == "__main__" and "--launch" not in sys.argv:
    # worker processes: simulate 4 chips per host on CPU (this demo never
    # runs on an accelerator, whatever the machine has)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def worker() -> None:
    import jax
    import numpy as np

    from sparkdl_tpu.core.mesh import MeshConfig, make_mesh
    from sparkdl_tpu.engine.dataframe import DataFrame
    from sparkdl_tpu.ml import DeepImageFeaturizer
    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.train.runner import maybe_initialize_distributed

    import pyarrow as pa

    assert maybe_initialize_distributed(), "SPARKDL_* env triple not set"
    pid, n = jax.process_index(), jax.process_count()
    # a global mesh drives multi-host TRAINING (estimator.fit); inference
    # below runs host-local, so none is needed here
    _ = make_mesh(MeshConfig(data=jax.device_count()))
    print(f"[host {pid}] joined: {n} processes, "
          f"{jax.device_count()} global {jax.default_backend()} devices "
          "(simulated hosts; an example, not a measurement)")

    # identical frame on every host (real jobs read shared storage)
    rng = np.random.default_rng(0)
    rows = [{"image": imageIO.imageArrayToStruct(
        rng.integers(0, 255, size=(32, 32, 3), dtype=np.uint8)), "idx": i}
        for i in range(16)]
    schema = pa.schema([pa.field("image", imageIO.imageSchema),
                        pa.field("idx", pa.int64())])
    df = DataFrame.fromRows(rows, schema=schema, numPartitions=4)

    # transform auto-shards: this host featurizes ONLY its partitions
    out = DeepImageFeaturizer(inputCol="image", outputCol="f",
                              modelName="TestNet", batchSize=8).transform(df)
    print(f"[host {pid}] local shard: {out.count()} of {df.count()} rows")

    # opt-in gather: the FULL output frame, original order, on every host
    full = out.gatherProcesses()
    print(f"[host {pid}] gathered: {full.count()} rows")


def launch() -> None:
    import socket
    import subprocess

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.update({"SPARKDL_COORDINATOR": f"127.0.0.1:{port}",
                    "SPARKDL_NUM_PROCESSES": "2",
                    "SPARKDL_PROCESS_ID": str(pid)})
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)], env=env))
    for p in procs:
        assert p.wait(timeout=300) == 0
    print("both hosts finished")


if __name__ == "__main__":
    launch() if "--launch" in sys.argv else worker()
