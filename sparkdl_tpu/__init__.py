"""sparkdl_tpu — Deep Learning Pipelines, rebuilt TPU-native.

A from-scratch framework with the capabilities of
``chubbyjiang/spark-deep-learning`` ("Deep Learning Pipelines for Apache
Spark", python package ``sparkdl``), built idiomatically on JAX/XLA for TPU:
Flax models resident in HBM, jit/pjit execution via PJRT, declarative
sharding over device meshes (ICI/DCN collectives from XLA, not NCCL), an
Arrow-columnar partitioned DataFrame engine, and Orbax checkpointing.

Public surface mirrors the reference's ``sparkdl/__init__.py`` ``__all__``
(SURVEY.md §2.1), with TPU-native payloads. Heavy submodules are imported
lazily on attribute access so that ``import sparkdl_tpu`` stays cheap.
"""

import logging as _logging
import os as _os

from sparkdl_tpu.version import __version__

# Library logging etiquette: a NullHandler on the package root so the
# framework never prints "No handlers could be found" noise, and apps
# that DON'T configure logging see no output changes. Every module
# logger uses ``logging.getLogger(__name__)``, so all framework records
# route under the ``sparkdl_tpu`` namespace (enforced by
# tests/test_logging.py) — one knob configures the whole library, and
# the telemetry scope's structured-logging adapter (core.telemetry)
# stamps run_id/trace_id onto exactly this namespace.
_logging.getLogger(__name__).addHandler(_logging.NullHandler())

# JAX persistent compilation cache (docs/PERF.md "Cross-partition
# coalescing": the bucket ladder can compile a handful of programs per
# model; a warm on-disk cache makes every process after the first
# compile-free). The directory is placed from OUTSIDE with JAX's own
# variable; unset, it is one fixed git-ignored path in the checkout
# (the path is part of the cache key, so it must never move).
COMPILE_CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_IN_CHECKOUT_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_cache")
# zeroed thresholds: even the small bucket-ladder programs are cached
_CACHE_THRESHOLDS = {
    "jax_persistent_cache_min_compile_time_secs": 0.0,
    "jax_persistent_cache_min_entry_size_bytes": -1,
}


def _compile_cache_dir():
    """Where JAX's persistent compilation cache lives:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else the fixed in-checkout
    path."""
    return _os.environ.get(COMPILE_CACHE_DIR_ENV) or _IN_CHECKOUT_CACHE_DIR


def _sidecar_store_dir():
    """Directory of the bucket-ladder store: the compile cache's, but
    ONLY when ``$JAX_COMPILATION_CACHE_DIR`` names it — under the
    in-checkout default it stays in-process (None)."""
    named = _os.environ.get(COMPILE_CACHE_DIR_ENV)
    if not named or _os.path.abspath(named) == _IN_CHECKOUT_CACHE_DIR:
        return None
    return named


def _configure_compile_cache():
    """Place JAX's persistent compilation cache at
    :func:`_compile_cache_dir` without importing JAX: the settings go
    into the environment, which JAX reads when it is first imported
    (and which spawned workers inherit); only when JAX is ALREADY
    imported are the ones the environment did not carry applied with
    ``jax.config.update``. A directory named from outside is never
    overridden in code. Either way the first launch of each compiled
    program is a ``sparkdl.compile`` span of the telemetry scope open
    around it, and — for a scope opened only after the model was built
    and first launched, which sees no such span — part of the start-up
    record every scope mirrors (``sparkdl.startup.*`` gauges) and the
    run report carries as ``startup``: compile seconds, retrieval
    seconds, cache hits and misses (core/profiling.py)."""
    import sys as _sys

    late = {}
    if not _os.environ.get(COMPILE_CACHE_DIR_ENV):
        _os.environ[COMPILE_CACHE_DIR_ENV] = _IN_CHECKOUT_CACHE_DIR
        late["jax_compilation_cache_dir"] = _IN_CHECKOUT_CACHE_DIR
    for flag, value in _CACHE_THRESHOLDS.items():
        if flag.upper() not in _os.environ:
            _os.environ[flag.upper()] = str(value)
            late[flag] = value
    if late and "jax" in _sys.modules:
        import jax as _jax

        for flag, value in late.items():
            _jax.config.update(flag, value)


_configure_compile_cache()

# Grown as subsystems land; every name here must resolve (tested).
_LAZY_EXPORTS = {
    # image layer
    "imageIO": ("sparkdl_tpu.image", "imageIO"),
    "imageSchema": ("sparkdl_tpu.image", "imageSchema"),
    "readImages": ("sparkdl_tpu.image", "readImages"),
    "readImagesWithCustomFn": ("sparkdl_tpu.image", "readImagesWithCustomFn"),
    # engine
    "DataFrame": ("sparkdl_tpu.engine", "DataFrame"),
    "sql": ("sparkdl_tpu.engine", "sql"),
    "table": ("sparkdl_tpu.engine", "table"),
    # ml pipeline surface (reference __all__ parity)
    "Pipeline": ("sparkdl_tpu.ml", "Pipeline"),
    "PipelineModel": ("sparkdl_tpu.ml", "PipelineModel"),
    "Transformer": ("sparkdl_tpu.ml", "Transformer"),
    "Estimator": ("sparkdl_tpu.ml", "Estimator"),
    "TFImageTransformer": ("sparkdl_tpu.ml", "TFImageTransformer"),
    "TFTransformer": ("sparkdl_tpu.ml", "TFTransformer"),
    "TPUImageTransformer": ("sparkdl_tpu.ml", "TPUImageTransformer"),
    "TPUTransformer": ("sparkdl_tpu.ml", "TPUTransformer"),
    "DeepImageFeaturizer": ("sparkdl_tpu.ml", "DeepImageFeaturizer"),
    "DeepImagePredictor": ("sparkdl_tpu.ml", "DeepImagePredictor"),
    "DeepSequenceScorer": ("sparkdl_tpu.ml", "DeepSequenceScorer"),
    "KerasImageFileTransformer": ("sparkdl_tpu.ml", "KerasImageFileTransformer"),
    "KerasImageFileEstimator": ("sparkdl_tpu.ml", "KerasImageFileEstimator"),
    "KerasTransformer": ("sparkdl_tpu.ml", "KerasTransformer"),
    # observability surface (docs/OBSERVABILITY.md)
    "Telemetry": ("sparkdl_tpu.core", "Telemetry"),
    "telemetry": ("sparkdl_tpu.core", "telemetry"),
    "HealthMonitor": ("sparkdl_tpu.core", "HealthMonitor"),
    "slo": ("sparkdl_tpu.core", "slo"),
    "SLORule": ("sparkdl_tpu.core", "SLORule"),
    "SLOWatchdog": ("sparkdl_tpu.core", "SLOWatchdog"),
    # training surface
    "Trainer": ("sparkdl_tpu.train", "Trainer"),
    "TPURunner": ("sparkdl_tpu.train", "TPURunner"),
    "CheckpointManager": ("sparkdl_tpu.train", "CheckpointManager"),
    # udf serving surface
    "registerKerasImageUDF": ("sparkdl_tpu.udf", "registerKerasImageUDF"),
    "registerImageUDF": ("sparkdl_tpu.udf", "registerImageUDF"),
    "registerTensorUDF": ("sparkdl_tpu.udf", "registerTensorUDF"),
    "registerUDF": ("sparkdl_tpu.udf", "registerUDF"),
    "udf_registry": ("sparkdl_tpu.udf", "udf_registry"),
}

__all__ = ["__version__"] + sorted(_LAZY_EXPORTS)


def __getattr__(name):
    try:
        module_name, attr = _LAZY_EXPORTS[name]
    except KeyError:
        raise AttributeError(f"module 'sparkdl_tpu' has no attribute {name!r}") from None
    import importlib

    from sparkdl_tpu.core import profiling

    started = profiling.import_begin()      # import_s of the start-up record
    try:
        module = importlib.import_module(module_name)
    finally:
        profiling.import_end(started)
    value = getattr(module, attr)
    globals()[name] = value
    return value
