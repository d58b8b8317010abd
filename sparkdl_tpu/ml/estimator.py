"""KerasImageFileEstimator — train a Keras model on an image DataFrame.

Parity (SURVEY.md §3.3): the reference's estimator ran cluster-side
preprocessing, then ``collect()``-ed everything to the driver and called
keras ``model.fit`` locally — the scalability cliff SURVEY.md calls out.
The rebuild keeps the Estimator surface (``fit``, lazy ``fitMultiple``
param-map search, ``CanLoadImage`` host decode) but trains with the
Trainer's jitted step: forward/backward/update in one XLA program, data
sharded over the mesh's ``data`` axis when a mesh is supplied (the
MobileNetV2 fine-tune and ResNet50 DP configs in BASELINE.json).
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.core import profiling, telemetry
from sparkdl_tpu.core.model_function import ModelFunction
from sparkdl_tpu.image import imageIO
from sparkdl_tpu.ml.base import Estimator, Model
from sparkdl_tpu.ml.image_transformer import TPUImageTransformer
from sparkdl_tpu.ml.persistence import ModelFunctionPersistence
from sparkdl_tpu.param.base import Param, keyword_only
from sparkdl_tpu.param.converters import TypeConverters
from sparkdl_tpu.param.shared_params import (
    CanLoadImage,
    HasBatchSize,
    HasInputCol,
    HasKerasLoss,
    HasKerasModel,
    HasKerasOptimizer,
    HasLabelCol,
    HasMesh,
    HasOutputCol,
    HasOutputMode,
)

_LOADED_COL = "__sdl_estimator_image"


class KerasImageFileEstimator(Estimator, HasInputCol, HasOutputCol,
                              HasLabelCol, HasKerasModel, HasKerasOptimizer,
                              HasKerasLoss, CanLoadImage, HasOutputMode,
                              HasBatchSize, HasMesh):
    """Estimator over an image-URI DataFrame, fitted on TPU via Trainer."""

    kerasFitParams = Param(
        "KerasImageFileEstimator", "kerasFitParams",
        "fit options: {'epochs': int, 'batch_size': int, "
        "'learning_rate': float, 'shuffle': bool, 'seed': int, "
        "'streaming': bool, 'mixed_precision': bool, "
        "'shuffle_buffer': int (windowed-shuffle pool depth in batches, "
        "streaming path; default 4), "
        "'validation_data': (X, y) arrays evaluated at each epoch end, "
        "'validation_split': float tail fraction held out (collected "
        "path only), 'verbose': bool (per-step metrics JSONL to stdout), "
        "'log_every': int, 'checkpoint_dir': str (Orbax mid-training "
        "checkpoints + resume), 'checkpoint_every': int steps, "
        "'prefetch': int (async-pipeline staging depth in batches, "
        "0 = serial staging; default 2), 'sync_every': int (steps "
        "between deferred device syncs; default 8 — see docs/PERF.md)}",
        typeConverter=TypeConverters.identity)

    @keyword_only
    def __init__(self, *, inputCol: Optional[str] = None,
                 outputCol: Optional[str] = None,
                 labelCol: Optional[str] = None,
                 modelFile: Optional[str] = None,
                 model=None,
                 imageLoader: Optional[Callable] = None,
                 kerasOptimizer: str = "adam",
                 kerasLoss: str = "categorical_crossentropy",
                 kerasFitParams: Optional[Dict[str, Any]] = None,
                 outputMode: str = "vector",
                 batchSize: int = 64,
                 mesh=None) -> None:
        super().__init__()
        self._setDefault(kerasOptimizer="adam",
                         kerasLoss="categorical_crossentropy",
                         kerasFitParams={"epochs": 1, "batch_size": 32},
                         outputMode="vector", batchSize=64)
        self._mf_cache = None
        kwargs = self._input_kwargs
        self.setParams(**kwargs)

    @keyword_only
    def setParams(self, *, inputCol: Optional[str] = None,
                  outputCol: Optional[str] = None,
                  labelCol: Optional[str] = None,
                  modelFile: Optional[str] = None,
                  model=None,
                  imageLoader: Optional[Callable] = None,
                  kerasOptimizer: str = "adam",
                  kerasLoss: str = "categorical_crossentropy",
                  kerasFitParams: Optional[Dict[str, Any]] = None,
                  outputMode: str = "vector",
                  batchSize: int = 64,
                  mesh=None) -> "KerasImageFileEstimator":
        kwargs = dict(self._input_kwargs)
        loader = kwargs.pop("imageLoader", None)
        if {"model", "modelFile"} & kwargs.keys():
            self._mf_cache = None
        self._set(**kwargs)
        if loader is not None:
            self.setImageLoader(loader)
        return self

    def setModel(self, value):
        self._mf_cache = None
        return super().setModel(value)

    def setModelFile(self, value):
        self._mf_cache = None
        return super().setModelFile(value)

    def copy(self, extra=None):
        that = super().copy(extra)
        that._mf_cache = None
        return that

    def _model_function(self) -> ModelFunction:
        if self._mf_cache is None:
            self._mf_cache = self.loadKerasModelAsFunction()
        return self._mf_cache

    def setKerasFitParams(self, value: Dict[str, Any]):
        return self._set(kerasFitParams=value)

    def getKerasFitParams(self) -> Dict[str, Any]:
        return dict(self.getOrDefault(self.kerasFitParams))

    @staticmethod
    def _compute_dtype(fit_params: Dict[str, Any]):
        """mixed_precision fit param -> Trainer compute dtype (one policy
        for both the streaming and collected fit paths)."""
        return "bfloat16" if fit_params.get("mixed_precision") else None

    @staticmethod
    def _check_multihost_mesh(mesh, num_proc: int) -> int:
        """Shared multi-host guards for both fit paths; returns the data
        axis size. A model-parallel mesh whose data axis is smaller than
        the process count would make the local share 0 (ZeroDivisionError
        downstream)."""
        from sparkdl_tpu.core.mesh import data_axis_size

        if mesh is None:
            raise ValueError(
                "multi-host fit requires a mesh (the data axis carries "
                "the per-host shards)")
        axis = data_axis_size(mesh)
        if axis % num_proc != 0:
            raise ValueError(
                f"multi-host fit needs the mesh data axis ({axis}) to be "
                f"a multiple of the process count ({num_proc})")
        return axis

    # -- data staging --------------------------------------------------------

    def _loaded_frame(self, dataset):
        """dataset + decoded image column (lazy; decode runs per partition)."""
        mf = self._model_function()
        shape = mf.input_spec.shape
        target_size = ((shape[1], shape[2])
                       if len(shape) == 4 and None not in shape[1:3] else None)
        loaded = self.loadImagesInternal(dataset, self.getInputCol(),
                                         _LOADED_COL, target_size=target_size)
        return loaded, target_size

    def _collect_arrays(self, dataset) -> Tuple[np.ndarray, np.ndarray]:
        """Decode+resize URIs and stack (X, y) host-side.

        The decode runs partition-parallel in the engine (the reference ran
        it as a Spark job); the stacked result is the host staging buffer
        the train loop feeds to the device in fixed-size chunks. Used by
        ``fitMultiple`` (decode once, train many) and by
        ``kerasFitParams={'streaming': False}``; plain ``fit`` streams
        partitions instead (``_fit_streaming`` / ``_PartitionBatchStream``).
        """
        mf = self._model_function()
        loaded, target_size = self._loaded_frame(dataset)
        with telemetry.span(telemetry.SPAN_COLLECT):
            rows = loaded.select(_LOADED_COL, self.getLabelCol()).collect()
        structs = [r[_LOADED_COL] for r in rows]
        labels = [r[self.getLabelCol()] for r in rows]
        keep = [i for i, s in enumerate(structs) if s is not None]
        x = imageIO.imageStructsToBatchArray(
            [structs[i] for i in keep], target_size=target_size,
            dtype=None)
        if x.dtype != np.dtype(mf.input_spec.dtype):
            if (x.dtype == np.uint8
                    and np.dtype(mf.input_spec.dtype) == np.dtype(np.float32)):
                # keep uint8: Trainer.stage_batch transfers raw bytes and
                # casts to float32 on device (exact for 0-255) — same rule
                # as the streaming path (_partition_arrays_inner), so both
                # staging paths feed the device identical programs.
                pass
            else:
                x = x.astype(mf.input_spec.dtype)
        y = np.asarray([labels[i] for i in keep])
        return x, y

    def _label_preparer(self, mf: ModelFunction) -> Callable[[np.ndarray], np.ndarray]:
        """Per-batch label transform; the n_classes probe (a whole-model
        ``eval_shape`` trace) runs at most ONCE even when the streaming
        path prepares labels partition by partition."""
        loss = self.getKerasLoss()
        cache: Dict[str, int] = {}

        def prepare(y: np.ndarray) -> np.ndarray:
            if "sparse" in loss:
                return y.astype(np.int32)
            if y.ndim == 1 and "crossentropy" in loss and "binary" not in loss:
                if "n_classes" not in cache:
                    out = jax.eval_shape(
                        mf.apply_fn, mf.variables,
                        jnp.zeros(mf.input_spec.with_batch(1),
                                  dtype=mf.input_spec.dtype))
                    cache["n_classes"] = out.shape[-1]
                return np.eye(cache["n_classes"],
                              dtype=np.float32)[y.astype(np.int64)]
            return y.astype(np.float32)

        return prepare

    def _prepare_labels(self, y: np.ndarray, mf: ModelFunction) -> np.ndarray:
        return self._label_preparer(mf)(y)

    # -- fitting -------------------------------------------------------------

    def _fit_run(self, trainer, state, batches, fit_params,
                 mf: ModelFunction):
        """Shared train-loop driver for both fit paths: wires validation
        evaluation (keras ``validation_data`` semantics), per-step metrics
        JSONL (``verbose``/``log_every``, SURVEY.md §5.5), and Orbax
        mid-training checkpoints + resume (``checkpoint_dir``/
        ``checkpoint_every``, §5.4) into ``Trainer.fit``. Returns
        ``(state, history)`` — history is keras-History-shaped:
        {'epochs': [...], 'steps': [...]}.
        """
        epochs = int(fit_params.get("epochs", 1))
        history: Dict[str, Any] = {"epochs": [], "steps": []}

        val_batches = None
        if fit_params.get("validation_data") is not None:
            vx, vy = fit_params["validation_data"]
            vx = np.asarray(vx)
            vy = self._prepare_labels(np.asarray(vy), mf)
            vbs = int(fit_params.get("batch_size", 32))
            val_batches = [(vx[i:i + vbs], vy[i:i + vbs])
                           for i in range(0, len(vx), vbs)]

        logger = None
        if fit_params.get("verbose"):
            from sparkdl_tpu.train.metrics import MetricsLogger

            logger = MetricsLogger(every=int(fit_params.get("log_every", 1)))

        checkpoint = None
        if fit_params.get("checkpoint_dir"):
            from sparkdl_tpu.train.checkpoint import CheckpointManager

            checkpoint = CheckpointManager(str(fit_params["checkpoint_dir"]))

        def on_epoch(epoch: int, st) -> None:
            record: Dict[str, Any] = {"epoch": epoch}
            if val_batches is not None:
                record.update(trainer.evaluate(st, val_batches))
            history["epochs"].append(record)
            if fit_params.get("verbose") and len(record) > 1:
                import json as _json

                print(_json.dumps(record, default=float), flush=True)

        state = trainer.fit(
            state, batches, epochs=epochs, metrics_logger=logger,
            checkpoint=checkpoint,
            checkpoint_every=int(fit_params.get("checkpoint_every", 0)),
            on_epoch=on_epoch,
            # async input pipeline knobs (ISSUE 3, docs/PERF.md): staging
            # depth and deferred-sync cadence of the pipelined train loop
            prefetch=int(fit_params.get("prefetch", 2)),
            sync_every=int(fit_params.get("sync_every", 8)))
        if checkpoint is not None:
            checkpoint.wait_until_finished()
            checkpoint.close()
        if logger is not None:
            history["steps"] = logger.history
        return state, history

    def _fit_streaming(self, dataset) -> "KerasImageFileModel":
        """Streaming ``fit``: memory bounded by batch + a few partitions.

        Replaces the reference's driver-side ``collect()`` (SURVEY.md §3.3's
        scalability cliff): partitions decode lazily through the engine and
        flow into fixed-shape train batches without materializing the
        dataset. The whole pull→decode→stage chain runs on ``Trainer.fit``'s
        prefetcher thread (ISSUE 3): partition decode for batch k+1
        overlaps the device's training of batch k. With
        ``EngineConfig.decode_workers > 0`` the partition decode itself
        fans out to the multi-process decode pool (ISSUE 9, docs/PERF.md
        "Parallel host ingest"), so the GIL-bound JPEG decode no longer
        serializes on the staging thread — decode processes, staging,
        and the device step all overlap. With ``shuffle`` rows mix through a windowed shuffle
        buffer across partitions (an EXACT global permutation requires the
        collected path, ``streaming=False``); with ``shuffle=False`` the
        batch sequence is identical to the collected path's.

        Multi-host (SURVEY.md §2.5/§3.5, HorovodRunner parity): when the
        process group spans several hosts, each host streams+decodes ONLY
        its round-robin share of the partitions and emits LOCAL batches of
        ``batch_size / process_count``; ``Trainer.stage_batch`` assembles
        the global sharded array from the per-process shards. Hosts stay
        in lockstep via a per-batch allgather (the epoch ends for everyone
        when the first host runs dry, dropping at most the tail).
        """
        from sparkdl_tpu.core.mesh import data_axis_size, pad_to_multiple
        from sparkdl_tpu.train.trainer import Trainer

        mf = self._model_function()
        fit_params = self.getKerasFitParams()
        batch_size = int(fit_params.get("batch_size", 32))
        shuffle = bool(fit_params.get("shuffle", True))
        seed = int(fit_params.get("seed", 0))
        lr = fit_params.get("learning_rate")
        mesh = self.resolveMesh()
        num_proc = jax.process_count()
        multiple = 1
        if mesh is not None:
            multiple = data_axis_size(mesh)
            batch_size = pad_to_multiple(batch_size, multiple)
        if num_proc > 1:
            self._check_multihost_mesh(mesh, num_proc)
            # validation_data works multi-host: state is replicated, so
            # Trainer.evaluate pulls it host-local and every process
            # computes the exact single-process metrics (r5; the
            # validation_split raise below still applies — it needs the
            # collected path on any topology).
            # every host contributes an equal local slice of each global
            # batch
            batch_size //= num_proc
            multiple //= num_proc
        loaded, target_size = self._loaded_frame(dataset)
        frame = loaded.select(_LOADED_COL, self.getLabelCol())
        if num_proc > 1 and frame.numPartitions < num_proc:
            raise ValueError(
                f"multi-host fit needs at least one partition per process: "
                f"dataset has {frame.numPartitions} partitions for "
                f"{num_proc} processes — repartition the DataFrame")
        stream = _PartitionBatchStream(
            frame, _LOADED_COL, self.getLabelCol(), target_size,
            str(mf.input_spec.dtype), batch_size, multiple, shuffle, seed,
            self._label_preparer(mf),
            shuffle_buffer=int(fit_params.get("shuffle_buffer", 4)),
            process_id=jax.process_index() if num_proc > 1 else None,
            num_processes=num_proc if num_proc > 1 else None)
        if fit_params.get("validation_split"):
            raise ValueError(
                "validation_split needs the whole dataset in memory — use "
                "streaming=False, or pass validation_data arrays instead")
        trainer, state = Trainer.from_model_function(
            mf, loss=self.getKerasLoss(), optimizer=self.getKerasOptimizer(),
            learning_rate=lr, mesh=mesh,
            compute_dtype=self._compute_dtype(fit_params))
        state, history = self._fit_run(trainer, state, stream, fit_params, mf)
        if stream.batches_last_epoch == 0:
            raise ValueError("No decodable training images")
        return self._wrap_trained(mf, state, history)

    def _wrap_trained(self, mf: ModelFunction, state,
                      history: Optional[Dict[str, Any]] = None
                      ) -> "KerasImageFileModel":
        trained = ModelFunction(mf.apply_fn, jax.device_get(state.params),
                                mf.input_spec, name=mf.name + "_trained",
                                trainable_mask=mf.trainable_mask)
        model = KerasImageFileModel(
            inputCol=self.getInputCol(), outputCol=self.getOutputCol(),
            modelFunction=trained, outputMode=self.getOutputMode(),
            batchSize=self.getBatchSize(), mesh=self.getMesh(),
            imageLoader=self.getImageLoader())
        model._set_parent(self)
        # keras-History analog: per-epoch validation metrics + per-step
        # training metrics (when verbose logging was on)
        model.history = history or {"epochs": [], "steps": []}
        return model

    def _fit_on_arrays(self, x: np.ndarray, y: np.ndarray
                       ) -> "KerasImageFileModel":
        from sparkdl_tpu.core.mesh import data_axis_size, pad_to_multiple
        from sparkdl_tpu.train.trainer import Trainer

        mf = self._model_function()
        y = self._prepare_labels(y, mf)
        fit_params = self.getKerasFitParams()
        batch_size = int(fit_params.get("batch_size", 32))
        shuffle = bool(fit_params.get("shuffle", True))
        seed = int(fit_params.get("seed", 0))
        lr = fit_params.get("learning_rate")
        mesh = self.resolveMesh()
        if mesh is not None:
            batch_size = pad_to_multiple(batch_size, data_axis_size(mesh))
        split = float(fit_params.get("validation_split", 0.0) or 0.0)
        if split and fit_params.get("validation_data") is not None:
            # keras precedence: explicit validation_data wins and the
            # split is ignored (no rows held out)
            split = 0.0
        if split:
            # keras semantics: the validation slice is the TAIL of the
            # data as provided, taken BEFORE shuffling
            if not 0.0 < split < 1.0:
                raise ValueError(
                    f"validation_split must be in (0, 1), got {split}")
            n_val = int(len(x) * split)
            if n_val == 0 or n_val == len(x):
                raise ValueError(
                    f"validation_split={split} leaves an empty train or "
                    f"validation set for {len(x)} rows")
            fit_params = dict(fit_params,
                              validation_data=(x[-n_val:], y[-n_val:]))
            x, y = x[:-n_val], y[:-n_val]
        if shuffle:
            perm = np.random.default_rng(seed).permutation(len(x))
            x, y = x[perm], y[perm]
        # fixed-size batches (static XLA shapes); remainder dropped like
        # keras fit with drop_remainder — unless that would drop everything
        n = len(x)
        if n == 0:
            raise ValueError("No decodable training images")
        batch_size = min(batch_size, n)
        if mesh is not None:
            # the clamp above can break divisibility by the data axis; the
            # jitted step's P('data') in_shardings needs every shard equal
            axis = data_axis_size(mesh)
            batch_size = (batch_size // axis) * axis
            if batch_size == 0:
                raise ValueError(
                    f"dataset has {n} usable rows but the mesh data axis "
                    f"spans {axis} devices; need at least {axis} rows")
        usable = (n // batch_size) * batch_size
        batches = [(x[i:i + batch_size], y[i:i + batch_size])
                   for i in range(0, usable, batch_size)]

        # Multi-host collected fit (r5): Trainer.stage_batch assembles the
        # global array from PROCESS-LOCAL shards, so feeding the full
        # batch on every host would silently duplicate the data. Each
        # host takes its contiguous slice of every (host-identical)
        # global batch — shard order matches make_array_from_
        # process_local_data's process-order concatenation, so params
        # equal the single-process fit exactly.
        num_proc = jax.process_count()
        if num_proc > 1:
            self._check_multihost_mesh(mesh, num_proc)
            # One cheap collective up front: every host must have
            # collected the same row count, or (one host dropping an
            # undecodable image) batch counts diverge and the short host
            # exits the loop while the others block in the next
            # collective forever — the collected-path analog of the
            # streaming path's per-batch lockstep.
            from jax.experimental import multihost_utils

            counts = multihost_utils.process_allgather(
                np.asarray([len(x)], dtype=np.int64))
            if int(counts.min()) != int(counts.max()):
                raise ValueError(
                    "multi-host collected fit needs every process to "
                    "decode the same rows; got per-host counts "
                    f"{counts.ravel().tolist()} — check for corrupt or "
                    "host-unreadable images, or use streaming=True "
                    "(lockstep tolerates uneven decode)")
            # batch_size is a multiple of the data axis here, and the
            # axis is a multiple of num_proc, so the slice is exact
            local = batch_size // num_proc
            p = jax.process_index()
            batches = [(bx[p * local:(p + 1) * local],
                        by[p * local:(p + 1) * local])
                       for bx, by in batches]

        trainer, state = Trainer.from_model_function(
            mf, loss=self.getKerasLoss(), optimizer=self.getKerasOptimizer(),
            learning_rate=lr, mesh=mesh,
            compute_dtype=self._compute_dtype(fit_params))
        state, history = self._fit_run(trainer, state, batches, fit_params,
                                       mf)
        return self._wrap_trained(mf, state, history)

    def _fit(self, dataset) -> "KerasImageFileModel":
        # Training NEVER routes through the device execution service
        # (core/executor.py): both fit paths feed Trainer's own step
        # program (donated state threading, deferred sync) — coalescing
        # across training steps would interleave state updates from
        # unrelated streams. EngineConfig.coalesce only affects the
        # fitted model's transform(), which is an inference path.
        streaming = bool(self.getKerasFitParams().get("streaming", True))
        with telemetry.span(telemetry.SPAN_ESTIMATOR_FIT,
                            streaming=streaming):
            if streaming:
                return self._fit_streaming(dataset)
            x, y = self._collect_arrays(dataset)
            return self._fit_on_arrays(x, y)

    # -- persistence (unfitted estimator; VERDICT r3 #6) ---------------------

    def save(self, path: str) -> None:
        """Persist the UNFITTED estimator: params metadata + the Keras
        model artifact (self-contained — an in-memory ``model`` serializes
        via Keras, a ``modelFile`` is copied in). ``load`` then ``fit``
        reproduces the model fitting the original would produce (training
        is deterministic in the fit-param seed)."""
        import os

        from sparkdl_tpu.ml import persistence as P

        P.check_no_custom_loader(self)
        os.makedirs(path, exist_ok=True)
        params = P.jsonable_params(self, skip=("mesh", "model", "modelFile"))
        artifact = P.save_keras_artifact(self, path)
        if artifact is None:
            raise ValueError("set either model or modelFile before save()")
        P.write_metadata(path, self, params, {"keras_model": artifact})

    @classmethod
    def _load_from(cls, path: str, meta):
        import os

        inst = cls(**meta["params"])
        inst.setModelFile(os.path.join(path, meta["artifacts"]["keras_model"]))
        return inst

    def fitMultiple(self, dataset, paramMaps) -> Iterator[Tuple[int, Model]]:
        """Param-map search sharing ONE image decode pass (§3.3 parity:
        the reference collected features once, then looped over maps).

        Decode-sharing policy (VERDICT r3 #7): by default the dataset is
        decoded ONCE into a host cache shared by every map — the fastest
        HPO path, at the §3.3 collect-cliff memory cost. A map (or the
        base estimator) that sets ``kerasFitParams={'streaming': True}``
        opts that fit out of the cache: it streams partitions with bounded
        memory instead (decode repeats per fit+epoch — the explicit
        time-for-memory trade for datasets that don't fit on the host).
        The collect runs lazily, only when the first cache-sharing map
        trains, so an all-streaming search never materializes the dataset.
        """
        estimator = self.copy()

        def _map_streams(param_map) -> bool:
            fp = estimator.copy(param_map).getKerasFitParams()
            return bool(fp.get("streaming", False))

        class _Iter:
            def __init__(self) -> None:
                self._lock = threading.Lock()
                # separate lock: the (long) one-time collect must not block
                # other threads from taking indices / starting streaming
                # fits that need no cache
                self._cache_lock = threading.Lock()
                self._next = 0
                self._cache: Optional[Tuple[np.ndarray, np.ndarray]] = None

            def __iter__(self):
                return self

            def _collected(self):
                with self._cache_lock:
                    if self._cache is None:
                        self._cache = estimator._collect_arrays(dataset)
                    return self._cache

            def __next__(self):
                with self._lock:
                    index = self._next
                    if index >= len(paramMaps):
                        raise StopIteration
                    self._next += 1
                if _map_streams(paramMaps[index]):
                    fitted = estimator.copy(
                        paramMaps[index])._fit_streaming(dataset)
                else:
                    base_x, base_y = self._collected()
                    fitted = estimator.copy(paramMaps[index])._fit_on_arrays(
                        base_x, base_y)
                return index, fitted

        return _Iter()


class _PartitionBatchStream:
    """Reiterable fixed-shape (x, y) batch stream over engine partitions.

    Each iteration (epoch) pulls partitions through
    ``DataFrame.streamPartitions`` — nothing is materialized beyond the
    prefetch window plus the shuffle pool — and decodes the image-struct
    column (Arrow zero-copy fast path, per-row fallback). ``shuffle``
    visits partitions in a fresh per-epoch order and mixes rows through a
    ~4-batch windowed pool (tf.data-style buffer; deterministic in (seed,
    epoch)); without it rows chain across partition boundaries in order,
    matching the collected path's batch sequence exactly. The final
    remainder is dropped (keras ``drop_remainder`` semantics) unless the
    whole epoch would otherwise be empty, in which case one smaller batch
    (rounded down to ``multiple`` for mesh shard divisibility) is yielded.
    """

    def __init__(self, frame, image_col: str, label_col: str,
                 target_size, dtype: str, batch_size: int, multiple: int,
                 shuffle: bool, seed: int,
                 prepare_labels: Callable[[np.ndarray], np.ndarray],
                 shuffle_buffer: int = 4,
                 process_id: Optional[int] = None,
                 num_processes: Optional[int] = None) -> None:
        self._frame = frame
        self._image_col = image_col
        self._label_col = label_col
        self._target_size = target_size
        self._dtype = dtype
        self._batch_size = batch_size
        self._multiple = max(1, multiple)
        self._shuffle = shuffle
        self._seed = seed
        self._prepare_labels = prepare_labels
        self._shuffle_buffer = max(1, shuffle_buffer)
        self._process_id = process_id
        self._num_processes = num_processes
        self._epoch = 0
        self.batches_last_epoch: Optional[int] = None

    @property
    def _multihost(self) -> bool:
        return bool(self._num_processes and self._num_processes > 1)

    def _lockstep(self, gen):
        """Keep hosts emitting the same batch COUNT: before every yield,
        all processes agree (allgather) whether everyone still has a next
        batch; the epoch ends globally when the first host runs dry. One
        tiny host-collective per batch — the analog of the per-step
        barrier Horovod's allreduce imposed anyway (SURVEY.md §3.5)."""
        from jax.experimental import multihost_utils

        it = iter(gen)
        while True:
            try:
                nxt = next(it)
                have = 1
            except StopIteration:
                nxt = None
                have = 0
            counts = multihost_utils.process_allgather(
                np.asarray([have], dtype=np.int32))
            if int(np.min(counts)) == 0:
                return
            yield nxt

    def _partition_arrays(self, part) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        with profiling.annotate("sparkdl.stage", rows=part.num_rows):
            return self._partition_arrays_inner(part)

    def _partition_arrays_inner(self, part
                                ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        idx = part.schema.get_field_index(self._image_col)
        col = part.column(idx)
        labels = part.column(part.schema.get_field_index(self._label_col))
        fast = imageIO.arrowImageBatch(col)
        if fast is not None:
            x, valid_idx = fast
            import pyarrow as pa

            # sparkdl: allow(columnar-hot-path): label column — may hold
            # strings/objects; tiny next to the pixel payload
            y = np.asarray(labels.take(pa.array(valid_idx)).to_pylist())
        else:
            # sparkdl: allow(columnar-hot-path): compatibility fallback —
            # ragged partitions only; uniform columns take arrowImageBatch
            structs = col.to_pylist()
            valid = [i for i, s in enumerate(structs) if s is not None]
            if not valid:
                return None
            x = imageIO.imageStructsToBatchArray(
                [structs[i] for i in valid], target_size=self._target_size,
                dtype=None)
            # sparkdl: allow(columnar-hot-path): label column — may hold
            # strings/objects; tiny next to the pixel payload
            lab = labels.to_pylist()
            y = np.asarray([lab[i] for i in valid])
        if x.shape[0] == 0:
            return None
        if (self._target_size is not None
                and tuple(x.shape[1:3]) != tuple(self._target_size)):
            # custom loaders may emit off-size structs; batch-resize here
            x = imageIO.resizeBatchArray(x, tuple(self._target_size))
        if x.dtype != np.dtype(self._dtype):
            if (x.dtype == np.uint8
                    and np.dtype(self._dtype) == np.dtype(np.float32)):
                # keep uint8: Trainer.stage_batch transfers it raw and
                # casts to FLOAT32 on device (exact for 0-255) — 4x less
                # host->device traffic on the training hot loop. f32 only:
                # other float input dtypes must cast host-side so the
                # staged dtype matches the collected path exactly.
                pass
            else:
                x = x.astype(self._dtype)
        return x, self._prepare_labels(y)

    def __iter__(self):
        if self._multihost:
            # lockstep wrapper counts the GLOBAL epoch length; the local
            # generator's own count is corrected afterwards
            gen = self._lockstep(self._iter_local())
            emitted = 0
            for item in gen:
                emitted += 1
                yield item
            self.batches_last_epoch = emitted
            return
        yield from self._iter_local()

    def _iter_local(self):
        epoch = self._epoch
        self._epoch += 1
        bs = self._batch_size
        emitted = 0
        order = None
        # Windowed shuffle (tf.data-style buffer): partitions are visited
        # in a fresh per-epoch order and rows mix across a pool of
        # ``shuffle_buffer`` batches + 1 partition before each emit —
        # bounded memory, breaks class-clustered partition layouts. Deepen
        # via kerasFitParams['shuffle_buffer'] (VERDICT r3 weak #4); an
        # EXACT global permutation needs the collected path
        # (streaming=False).
        pool_cap = bs * self._shuffle_buffer if self._shuffle else 0
        if self._shuffle:
            order = np.random.default_rng(
                (self._seed, epoch)).permutation(self._frame.numPartitions)
        pool_x: Optional[np.ndarray] = None
        pool_y: Optional[np.ndarray] = None
        flush = 0

        def shuffled_pool():
            nonlocal flush
            rng = np.random.default_rng((self._seed, epoch, flush))
            flush += 1
            perm = rng.permutation(len(pool_x))
            return pool_x[perm], pool_y[perm]

        for part in self._frame.streamPartitions(
                order=order, process_id=self._process_id,
                num_processes=self._num_processes):
            arrays = self._partition_arrays(part)
            if arrays is None:
                continue
            x, y = arrays
            if pool_x is not None:
                x = np.concatenate([pool_x, x])
                y = np.concatenate([pool_y, y])
            pool_x, pool_y = x, y
            if len(pool_x) >= pool_cap + bs:
                if self._shuffle:
                    pool_x, pool_y = shuffled_pool()
                emit = (len(pool_x) - pool_cap) // bs
                for i in range(emit):
                    emitted += 1
                    yield pool_x[i * bs:(i + 1) * bs], pool_y[i * bs:(i + 1) * bs]
                pool_x, pool_y = pool_x[emit * bs:], pool_y[emit * bs:]
        if pool_x is not None and len(pool_x) > 0:
            if self._shuffle:
                pool_x, pool_y = shuffled_pool()
            usable = (len(pool_x) // bs) * bs
            for i in range(0, usable, bs):
                emitted += 1
                yield pool_x[i:i + bs], pool_y[i:i + bs]
            if emitted == 0 and not self._multihost:
                # single-host small-dataset fallback: one sub-batch, rounded
                # to the mesh multiple. Multi-host skips it — unequal host
                # shard shapes can't assemble one global array; the
                # lockstep layer ends the epoch consistently instead.
                n = (len(pool_x) // self._multiple) * self._multiple
                if n == 0:
                    raise ValueError(
                        f"dataset has {len(pool_x)} usable rows but the mesh "
                        f"data axis requires a multiple of {self._multiple}")
                emitted += 1
                yield pool_x[:n], pool_y[:n]
        self.batches_last_epoch = emitted


class KerasImageFileModel(Model, HasInputCol, HasOutputCol, CanLoadImage,
                          HasOutputMode, HasBatchSize, HasMesh,
                          ModelFunctionPersistence):
    """Fitted model: URI column → trained network → predictions column.

    Persistence: the trained net round-trips as StableHLO with weights
    baked in (``ModelFunctionPersistence``).
    """

    _persist_check_loader = True
    _persist_name = "keras_image_file_model"

    modelFunction = Param("KerasImageFileModel", "modelFunction",
                          "trained ModelFunction",
                          typeConverter=TypeConverters.identity)

    @keyword_only
    def __init__(self, *, inputCol: Optional[str] = None,
                 outputCol: Optional[str] = None,
                 modelFunction=None,
                 outputMode: str = "vector",
                 batchSize: int = 64,
                 mesh=None,
                 imageLoader: Optional[Callable] = None) -> None:
        super().__init__()
        self._setDefault(outputMode="vector", batchSize=64)
        kwargs = dict(self._input_kwargs)
        loader = kwargs.pop("imageLoader", None)
        self._set(**kwargs)
        if loader is not None:
            self.setImageLoader(loader)

    def getModelFunction(self):
        return self.getOrDefault(self.modelFunction)

    def _transform(self, dataset):
        mf = self.getModelFunction()
        shape = mf.input_spec.shape
        target_size = ((shape[1], shape[2])
                       if len(shape) == 4 and None not in shape[1:3] else None)
        loaded = self.loadImagesInternal(dataset, self.getInputCol(),
                                         _LOADED_COL, target_size=target_size)
        inner = TPUImageTransformer(
            inputCol=_LOADED_COL, outputCol=self.getOutputCol(),
            modelFunction=mf, outputMode=self.getOutputMode(),
            batchSize=self.getBatchSize(), mesh=self.getMesh())
        return inner.transform(loaded).drop(_LOADED_COL)
