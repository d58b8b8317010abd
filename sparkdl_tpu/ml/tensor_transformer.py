"""TPUTransformer — arbitrary model over numeric array/scalar columns.

Parity: the reference's ``TFTransformer`` (``transformers/tf_tensor.py``,
SURVEY.md §2.1) which mapped Spark rows → numpy blocks → ``sess.run`` →
output column. Here: Arrow FixedSizeList / numeric column → contiguous
numpy block (zero-copy where Arrow allows) → jitted ModelFunction with
padded static batch shapes → list<float32> output column.
"""

from __future__ import annotations

from typing import Optional

import numpy as np
import pyarrow as pa

from sparkdl_tpu.core import executor as device_executor
from sparkdl_tpu.core import profiling
from sparkdl_tpu.engine.dataframe import (
    _schema_with,
    _set_column,
    column_to_numpy,
    fixed_size_list_array,
)
from sparkdl_tpu.ml.base import Transformer
from sparkdl_tpu.ml.persistence import ModelFunctionPersistence
from sparkdl_tpu.param.base import Param, keyword_only
from sparkdl_tpu.param.converters import SparkDLTypeConverters
from sparkdl_tpu.param.shared_params import (
    HasBatchSize,
    HasInputCol,
    HasMesh,
    HasModelFunction,
    HasOutputCol,
    HasPriority,
)


def column_to_block(column: pa.Array, element_shape) -> np.ndarray:
    """Arrow column → (N, *element_shape) contiguous numpy block.

    Conversion is the engine's ``column_to_numpy`` (FixedSizeList/List/
    numeric); this adds the model-input contract: row length must match the
    input spec's element size — rows are reshaped, never resized.
    """
    values = column_to_numpy(column)
    n = len(column)
    want = int(np.prod(element_shape)) if element_shape else 1
    if values.ndim == 1 and want != 1:
        raise ValueError(
            f"scalar input column for model expecting {element_shape}")
    if values.size != n * want:
        raise ValueError(
            f"input rows have {values.size // max(n, 1)} elements, model "
            f"expects {want}")
    return np.ascontiguousarray(values).reshape((n,) + tuple(element_shape))


class TPUTransformer(Transformer, HasInputCol, HasOutputCol,
                     HasModelFunction, HasBatchSize, HasMesh, HasPriority,
                     ModelFunctionPersistence):
    """Apply a ModelFunction to numeric columns, emitting list<float32>.

    Single-IO: ``inputCol``/``outputCol``. Multi-IO (the reference
    ``TFTransformer``'s tensor↔column maps, SURVEY.md §2.1): a model whose
    ``input_spec`` is a ``{input-name: TensorSpec}`` dict takes
    ``inputMapping={column: input-name}`` and emits one column per entry of
    ``outputMapping={output-name: column}`` from its dict output. A model
    with ONE input and a dict output takes ``inputCol`` with
    ``outputMapping``. Input blocks are staged in the spec's dtype, so a
    ``list<int32>`` column of token ids reaches an ``int32`` spec as it is.
    """

    inputMapping = Param(
        "TPUTransformer", "inputMapping",
        "{column-name: model-input-name} for multi-input models",
        typeConverter=SparkDLTypeConverters.asColumnToInputMap)
    outputMapping = Param(
        "TPUTransformer", "outputMapping",
        "{model-output-name: column-name} for multi-output models",
        typeConverter=SparkDLTypeConverters.asOutputToColumnMap)

    _persist_name = "tpu_transformer"
    _persist_skip = ("mesh", "modelFunction")

    @keyword_only
    def __init__(self, *, inputCol: Optional[str] = None,
                 outputCol: Optional[str] = None,
                 inputMapping: Optional[dict] = None,
                 outputMapping: Optional[dict] = None,
                 modelFunction=None,
                 batchSize: int = 64,
                 mesh=None, priority: Optional[str] = None) -> None:
        super().__init__()
        self._setDefault(batchSize=64)
        kwargs = self._input_kwargs
        self.setParams(**kwargs)

    @keyword_only
    def setParams(self, *, inputCol: Optional[str] = None,
                  outputCol: Optional[str] = None,
                  inputMapping: Optional[dict] = None,
                  outputMapping: Optional[dict] = None,
                  modelFunction=None,
                  batchSize: int = 64,
                  mesh=None,
                  priority: Optional[str] = None) -> "TPUTransformer":
        return self._set(**self._input_kwargs)

    def setInputMapping(self, value: dict) -> "TPUTransformer":
        return self._set(inputMapping=value)

    def getInputMapping(self) -> Optional[dict]:
        return (self.getOrDefault(self.inputMapping)
                if self.isDefined(self.inputMapping) else None)

    def setOutputMapping(self, value: dict) -> "TPUTransformer":
        return self._set(outputMapping=value)

    def getOutputMapping(self) -> Optional[dict]:
        return (self.getOrDefault(self.outputMapping)
                if self.isDefined(self.outputMapping) else None)


    def _transform(self, dataset):
        model = self.getModelFunction()
        if model is None:
            raise ValueError("modelFunction must be set")
        # Multi-host data-parallel inference (SURVEY.md §2.4 row 1): each
        # process transforms only its round-robin partition share; no-op
        # single-process, idempotent across chained transformers. Assembly
        # is opt-in via DataFrame.gatherProcesses (docs/DISTRIBUTED.md).
        dataset = dataset.processShard()
        if (isinstance(model.input_spec, dict) or self.getInputMapping()
                or self.getOutputMapping()):
            return self._transform_multi(dataset, model)
        input_col = self.getInputCol()
        output_col = self.getOutputCol()
        batch_size = self.getBatchSize()
        from sparkdl_tpu.core.mesh import host_local_mesh

        mesh = host_local_mesh(self.resolveMesh())
        element_shape = model.input_spec.element_shape
        priority = self.getPriority()  # None: EngineConfig default lane
        if input_col not in dataset.columns:
            raise KeyError(f"No such column: {input_col!r}")

        def apply_partition(batch: pa.RecordBatch) -> pa.Array:
            if batch.num_rows == 0:
                return pa.array([], type=pa.list_(pa.float32()))
            col = batch.column(batch.schema.get_field_index(input_col))
            block = column_to_block(col, element_shape)
            block = block.astype(model.input_spec.dtype, copy=False)
            # device entry via the execution-service choke point
            # (core/executor.py): concurrent partition chunks coalesce
            with profiling.annotate("sparkdl.device_apply",
                                    rows=batch.num_rows):
                out = device_executor.execute(
                    model, block, batch_size=batch_size, mesh=mesh,
                    priority=priority)
            out = np.asarray(out, dtype=np.float32).reshape(batch.num_rows, -1)
            return fixed_size_list_array(out).cast(pa.list_(pa.float32()))

        return dataset.withColumnBatch(output_col, apply_partition,
                                       outputType=pa.list_(pa.float32()))

    def _transform_multi(self, dataset, model):
        """Column↔named-IO mapping path for dict-spec models."""
        in_map = self.getInputMapping()
        out_map = self.getOutputMapping()
        single = not isinstance(model.input_spec, dict)
        if single and in_map:
            raise ValueError(
                "inputMapping requires a model with a dict input_spec")
        if not out_map:
            raise ValueError(
                "multi-input model requires outputMapping={output: column}")
        if single:
            # one input column, several outputs: the spec stands under the
            # column's own name below and the block goes in bare
            in_map = {self.getInputCol(): None}
            input_specs = {None: model.input_spec}
        else:
            input_specs = model.input_spec
            if not in_map:
                raise ValueError(
                    "multi-input model requires inputMapping={column: input}")
            missing = set(input_specs) - set(in_map.values())
            if missing:
                raise ValueError(f"inputMapping covers no column for model "
                                 f"inputs {sorted(missing)}")
            unknown = set(in_map.values()) - set(input_specs)
            if unknown:
                raise ValueError(
                    f"inputMapping references unknown model inputs "
                    f"{sorted(unknown)}; model has {sorted(input_specs)}")
        for col in in_map:
            if col not in dataset.columns:
                raise KeyError(f"No such column: {col!r}")
        batch_size = self.getBatchSize()
        from sparkdl_tpu.core.mesh import host_local_mesh

        mesh = host_local_mesh(self.resolveMesh())
        priority = self.getPriority()  # None: EngineConfig default lane
        out_cols = list(out_map.items())  # [(output-name, column)]

        def apply_partition(batch: pa.RecordBatch) -> pa.RecordBatch:
            n = batch.num_rows
            if n == 0:
                out = batch
                for _name, col in out_cols:
                    out = _set_column(
                        out, col, pa.array([], type=pa.list_(pa.float32())))
                return out
            blocks = {}
            for col, input_name in in_map.items():
                spec = input_specs[input_name]
                arr = batch.column(batch.schema.get_field_index(col))
                blocks[input_name] = column_to_block(
                    arr, spec.element_shape).astype(spec.dtype, copy=False)
            if single:
                blocks = blocks[None]
            with profiling.annotate("sparkdl.device_apply", rows=n):
                outs = device_executor.execute(
                    model, blocks, batch_size=batch_size, mesh=mesh,
                    priority=priority)
            if not isinstance(outs, dict):
                raise ValueError(
                    "outputMapping requires the model to return a "
                    f"{{output-name: array}} dict, got {type(outs).__name__}")
            result = batch
            for name, col in out_cols:
                if name not in outs:
                    raise KeyError(
                        f"model returned no output named {name!r}; has "
                        f"{sorted(outs)}")
                flat = np.asarray(outs[name], dtype=np.float32).reshape(n, -1)
                result = _set_column(
                    result, col,
                    fixed_size_list_array(flat).cast(pa.list_(pa.float32())))
            return result

        # declared schema must mirror _set_column (replace-in-place when an
        # output column name already exists, append if new) or a colliding
        # outputMapping would declare a duplicate field the batches lack
        schema = dataset.schema
        for _name, col in out_cols:
            schema = _schema_with(schema, col, pa.list_(pa.float32()))
        return dataset.mapPartitions(apply_partition, schema=schema)
