"""TPUImageTransformer — arbitrary model applied to an image column.

Parity: the reference's workhorse ``TFImageTransformer``
(``transformers/tf_image.py``, SURVEY.md §2.1, §3.2). There the graph
pipeline was assembled by splicing TF graph pieces (``buildSpImageConverter``
in front, flattener behind) and executed per-partition by TensorFrames→JNI.
Here the same pipeline is function composition compiled into ONE XLA
program:

    host: image struct column → contiguous NHWC batch (resize if needed)
    device (one jit): cast → user/device preprocess → model → [flatten]

and execution is the engine's partition-parallel ``withColumnBatch`` — one
``device_put`` per partition chunk, fixed batch shapes via padding so XLA
compiles once per batch size.

Async pipeline (ISSUE 3): within a partition, ``apply_batch`` stages
chunk ``k+1`` (the pad copies) on a background prefetcher thread while
chunk ``k``'s transfer+compute is in flight (``_PREFETCH_DEPTH``), and
the engine's partition pool overlaps one partition's host decode with
another's device work — the featurize-path adoption of the same
``core.pipeline.DevicePrefetcher`` the Trainer uses.

Parallel host ingest (ISSUE 9): the JPEG decode feeding this
transformer (``readImages`` / ``loadImagesInternal`` ops fused into the
same partition task as ``apply_partition``) fans out to the
multi-process decode pool when ``EngineConfig.decode_workers > 0``
(``core/decode_pool.py``, docs/PERF.md "Parallel host ingest"), so the
GIL-bound PIL fallback stops serializing the featurize pipeline:
worker-process decode, prefetcher staging, and device compute all
overlap, and the partition threads here only stack pixels and launch.
"""

from __future__ import annotations

import logging
from typing import Optional, Tuple

import numpy as np
import pyarrow as pa

logger = logging.getLogger(__name__)

from sparkdl_tpu.core import executor as device_executor
from sparkdl_tpu.core import profiling
from sparkdl_tpu.engine.dataframe import EngineConfig, fixed_size_list_array
from sparkdl_tpu.image import imageIO
from sparkdl_tpu.ml.base import Transformer
from sparkdl_tpu.ml.persistence import ModelFunctionPersistence
from sparkdl_tpu.param.base import Param, keyword_only
from sparkdl_tpu.param.converters import TypeConverters
from sparkdl_tpu.param.shared_params import (
    HasBatchSize,
    HasInputCol,
    HasMesh,
    HasModelFunction,
    HasOutputCol,
    HasOutputMode,
    HasPriority,
)

OUTPUT_MODES = ("vector", "image")

# Chunk-staging depth of the async input pipeline inside apply_batch
# (core/pipeline.py); 0 falls back to inline serial staging.
_PREFETCH_DEPTH = 2


class TPUImageTransformer(Transformer, HasInputCol, HasOutputCol,
                          HasModelFunction, HasOutputMode, HasBatchSize,
                          HasMesh, HasPriority, ModelFunctionPersistence):
    """Apply a ModelFunction to an image-struct column.

    ``outputMode="vector"`` flattens model output per row into a fixed-size
    float list column (the reference's Spark-ML Vector analog);
    ``outputMode="image"`` re-wraps 3-D HWC output as image structs
    (parity with ``tf_image.py``'s two output modes).
    """

    inputSize = Param(
        "TPUImageTransformer", "inputSize",
        "(H, W) the host resizes images to before staging; None uses the "
        "model input spec's spatial dims",
        typeConverter=TypeConverters.identity)

    @keyword_only
    def __init__(self, *, inputCol: Optional[str] = None,
                 outputCol: Optional[str] = None,
                 modelFunction=None,
                 outputMode: str = "vector",
                 batchSize: int = 64,
                 inputSize: Optional[Tuple[int, int]] = None,
                 mesh=None, priority: Optional[str] = None) -> None:
        super().__init__()
        self._setDefault(outputMode="vector", batchSize=64, inputSize=None)
        kwargs = self._input_kwargs
        self.setParams(**kwargs)

    @keyword_only
    def setParams(self, *, inputCol: Optional[str] = None,
                  outputCol: Optional[str] = None,
                  modelFunction=None,
                  outputMode: str = "vector",
                  batchSize: int = 64,
                  inputSize: Optional[Tuple[int, int]] = None,
                  mesh=None,
                  priority: Optional[str] = None) -> "TPUImageTransformer":
        # outputMode validation lives in the param's typeConverter
        # (SparkDLTypeConverters.toOutputMode) so every set path is covered.
        return self._set(**self._input_kwargs)

    def setInputSize(self, value) -> "TPUImageTransformer":
        return self._set(inputSize=value)

    def getInputSize(self):
        return self.getOrDefault(self.inputSize)


    # -- execution -----------------------------------------------------------

    def _target_size(self, model) -> Optional[Tuple[int, int]]:
        size = self.getOrDefault(self.inputSize)
        if size is not None:
            return tuple(size)
        shape = model.input_spec.shape
        if len(shape) == 4 and shape[1] is not None and shape[2] is not None:
            return (shape[1], shape[2])
        return None

    def _transform(self, dataset):
        model = self.getModelFunction()
        if model is None:
            raise ValueError("modelFunction must be set")
        # Multi-host data-parallel inference (SURVEY.md §2.4 row 1): each
        # process transforms only its round-robin partition share; no-op
        # single-process, idempotent across chained transformers. Assembly
        # is opt-in via DataFrame.gatherProcesses (docs/DISTRIBUTED.md).
        dataset = dataset.processShard()
        input_col = self.getInputCol()
        output_col = self.getOutputCol()
        mode = self.getOutputMode()
        batch_size = self.getBatchSize()
        from sparkdl_tpu.core.mesh import host_local_mesh

        mesh = host_local_mesh(self.resolveMesh())
        target_size = self._target_size(model)
        priority = self.getPriority()  # None: EngineConfig default lane
        run = model.flattened() if mode == "vector" else model
        if input_col not in dataset.columns:
            raise KeyError(f"No such column: {input_col!r}")

        def apply_partition(batch: pa.RecordBatch) -> pa.Array:
            idx = batch.schema.get_field_index(input_col)
            col = batch.column(idx)

            # Arrow fast path: uniform-size column → zero-copy NHWC view of
            # the contiguous binary buffer; no to_pylist, no per-row
            # frombuffer. Resize policy in _resize_uniform_batch.
            fast = imageIO.arrowImageBatch(col)
            if fast is not None:
                stacked, valid_np = fast
                valid = valid_np.tolist()
                stacked, run_fast = _resize_uniform_batch(stacked, target_size,
                                                          run)
                with profiling.annotate("sparkdl.device_apply",
                                        rows=len(stacked)):
                    # device entry via the execution-service choke point
                    # (core/executor.py): concurrent partition chunks
                    # against the same compiled fn coalesce into one
                    # launch when EngineConfig.coalesce is on
                    out = device_executor.execute(
                        run_fast, stacked, batch_size=batch_size,
                        mesh=mesh, prefetch=_PREFETCH_DEPTH,
                        priority=priority)
                if mode == "vector":
                    return _vectors_with_nulls(out, valid, batch.num_rows)
                # sparkdl: allow(columnar-hot-path): origin strings — the
                # image-output wrapper needs Python strings per row
                origins = col.field("origin").take(
                    pa.array(valid_np)).to_pylist()
                return _images_with_nulls(out, valid, batch.num_rows, origins)

            # sparkdl: allow(columnar-hot-path): compatibility fallback —
            # only ragged/non-uniform partitions reach here; uniform
            # columns take the zero-copy arrowImageBatch branch above
            structs = col.to_pylist()
            present = [i for i, s in enumerate(structs) if s is not None]
            # dtype=None: uint8 images stage as uint8 (4x fewer DMA bytes);
            # the jitted program casts to the spec dtype on device.
            # Tolerant staging: malformed structs (corrupt bytes, bad mode
            # codes, injected decode_error faults) degrade to null output
            # cells instead of aborting the partition (Spark's
            # corrupt-image convention); the drop count is surfaced below.
            with profiling.annotate("sparkdl.host_stage",
                                    rows=len(present)):
                stacked, kept, dropped = \
                    imageIO.imageStructsToBatchArrayTolerant(
                        [structs[i] for i in present],
                        target_size=target_size, dtype=None)
            if dropped:
                logger.warning(
                    "TPUImageTransformer: dropped %d undecodable image "
                    "row(s) of %d in partition (%r) — emitting null cells",
                    dropped, len(present), input_col)
            valid = [present[j] for j in kept]
            if not valid:
                out_type = (pa.list_(pa.float32()) if mode == "vector"
                            else imageIO.imageSchema)
                return pa.array([None] * batch.num_rows, type=out_type)
            with profiling.annotate("sparkdl.device_apply",
                                    rows=len(stacked)):
                out = device_executor.execute(
                    run, stacked, batch_size=batch_size, mesh=mesh,
                    prefetch=_PREFETCH_DEPTH, priority=priority)
            if mode == "vector":
                return _vectors_with_nulls(out, valid, batch.num_rows)
            return _images_with_nulls(out, valid, batch.num_rows,
                                      [structs[i].get("origin", "") for i in valid])

        out_type = (pa.list_(pa.float32())
                    if mode == "vector" else imageIO.imageSchema)
        return dataset.withColumnBatch(output_col, apply_partition,
                                       outputType=out_type)


def _resize_uniform_batch(stacked: np.ndarray, target_size, run):
    """Resize policy for the uniform (Arrow fast-path) batch.

    The legacy policy minimizes host→device bytes (uint8 staging and
    byte minimization are its levers — core/batching.py; whether the
    transfer or the host resize bounds the pipeline is not measured on
    the current machine). So:

    - downscale: resize on HOST via the threaded native batch resizer
      (GIL-free C++), shrinking transfer bytes;
    - upscale / native unavailable: transfer the source and resize ON
      DEVICE inside the model program (``ModelFunction.resized`` — the
      reference's in-graph tf.image.resize, SURVEY.md §3.2).

    Both are pixel-center bilinear without antialiasing; they differ only
    by uint8 rounding. Returns the (possibly resized) batch and the
    (possibly resize-composed) ModelFunction.

    Under ``EngineConfig.fused_preprocess`` (the default; docs/PERF.md
    "Columnar data plane") the host never resizes at all: the raw uint8
    batch ships at source size and resize fuses into the compiled
    program via ``ModelFunction.resized`` — cast/resize/normalize/
    forward become one XLA program, and the host's only per-image work
    is the Arrow wrap. The legacy byte-minimizing host-downscale policy
    below is kept for ``fused_preprocess=False``.
    """
    if target_size is None or tuple(stacked.shape[1:3]) == tuple(target_size):
        return stacked, run
    if EngineConfig.fused_preprocess:
        return stacked, run.resized(stacked.shape[1:3], tuple(target_size))
    src_px = stacked.shape[1] * stacked.shape[2]
    tgt_px = target_size[0] * target_size[1]
    # Byte-minimizing policy (host-vs-device resize is not measured on the
    # current machine): downscales resize on host (native C++ for uint8,
    # vectorized numpy otherwise); upscales transfer the smaller source and
    # resize on device. All three paths share the same pixel-center
    # no-antialias bilinear convention.
    if src_px > tgt_px:
        with profiling.annotate("sparkdl.host_resize"):
            resized = None
            if stacked.dtype == np.uint8:
                from sparkdl_tpu.native import loader as native_loader

                resized = native_loader.resize_batch(stacked,
                                                     tuple(target_size))
            if resized is None:
                resized = imageIO.resizeBatchArray(stacked,
                                                   tuple(target_size))
        return resized, run
    return stacked, run.resized(stacked.shape[1:3], tuple(target_size))


def _vectors_with_nulls(out: np.ndarray, valid, num_rows: int) -> pa.Array:
    out = np.asarray(out, dtype=np.float32).reshape(len(valid), -1)
    if len(valid) == num_rows:
        return fixed_size_list_array(out).cast(pa.list_(pa.float32()))
    values = [None] * num_rows
    for j, i in enumerate(valid):
        values[i] = out[j]
    return pa.array(values, type=pa.list_(pa.float32()))


def _images_with_nulls(out: np.ndarray, valid, num_rows: int,
                       origins) -> pa.Array:
    out = np.asarray(out)
    if out.ndim != 4:
        raise ValueError(
            f"outputMode='image' needs NHWC model output, got shape {out.shape}")
    values = [None] * num_rows
    for j, i in enumerate(valid):
        arr = out[j]
        if arr.dtype not in (np.uint8, np.float32):
            arr = arr.astype(np.float32)
        # sparkdl: allow(columnar-hot-path): output-mode="image" wrapper —
        # null interleaving forces per-row structs; model OUTPUT columns,
        # not the ingest spine
        values[i] = imageIO.imageArrayToStruct(arr, origin=origins[j])
    return pa.array(values, type=imageIO.imageSchema)
