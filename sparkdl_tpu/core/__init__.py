"""Core runtime: mesh/device setup, ModelFunction, batching, checkpointing.

The rebuild's L2 (the reference's graph toolkit, SURVEY.md §1) — except the
"graph" is a pure function and the "session" is jit+PJRT.

Exports are LAZY (PEP 562), mirroring the top-level package: importing
``sparkdl_tpu.core`` must not drag in jax. The decode pool's spawned
worker processes (``core/decode_pool.py``) import this package on their
way to the image codecs, and a jax import per worker would cost seconds
of startup and a device-runtime footprint per process; the stdlib-only
submodules (health, resilience, telemetry, profiling, pipeline) stay
importable for free. ``from sparkdl_tpu.core import executor`` still
works — Python falls back to the submodule import — and the re-exported
names (``ModelFunction``, ``Telemetry``, …) resolve on first attribute
access.
"""

_LAZY_EXPORTS = {
    # mesh / sharding surface
    "DATA_AXIS": ("sparkdl_tpu.core.mesh", "DATA_AXIS"),
    "MODEL_AXIS": ("sparkdl_tpu.core.mesh", "MODEL_AXIS"),
    "CONTEXT_AXIS": ("sparkdl_tpu.core.mesh", "CONTEXT_AXIS"),
    "EXPERT_AXIS": ("sparkdl_tpu.core.mesh", "EXPERT_AXIS"),
    "MeshConfig": ("sparkdl_tpu.core.mesh", "MeshConfig"),
    "make_mesh": ("sparkdl_tpu.core.mesh", "make_mesh"),
    "data_parallel_mesh": ("sparkdl_tpu.core.mesh", "data_parallel_mesh"),
    "batch_sharding": ("sparkdl_tpu.core.mesh", "batch_sharding"),
    "replicated": ("sparkdl_tpu.core.mesh", "replicated"),
    "shard_batch": ("sparkdl_tpu.core.mesh", "shard_batch"),
    # model function
    "ModelFunction": ("sparkdl_tpu.core.model_function", "ModelFunction"),
    "InputModel": ("sparkdl_tpu.core.model_function", "InputModel"),
    "TensorSpec": ("sparkdl_tpu.core.model_function", "TensorSpec"),
    # submodules re-exported as attributes (import still works without
    # these entries; they keep `sparkdl_tpu.core.batching`-style attribute
    # access alive for code that only imported the package)
    "batching": ("sparkdl_tpu.core", "batching"),
    "debug": ("sparkdl_tpu.core", "debug"),
    "decode_pool": ("sparkdl_tpu.core", "decode_pool"),
    "executor": ("sparkdl_tpu.core", "executor"),
    "health": ("sparkdl_tpu.core", "health"),
    "mesh": ("sparkdl_tpu.core", "mesh"),
    "model_function": ("sparkdl_tpu.core", "model_function"),
    "pipeline": ("sparkdl_tpu.core", "pipeline"),
    "profiling": ("sparkdl_tpu.core", "profiling"),
    "resilience": ("sparkdl_tpu.core", "resilience"),
    "slo": ("sparkdl_tpu.core", "slo"),
    "telemetry": ("sparkdl_tpu.core", "telemetry"),
    # resilience / health / telemetry names
    "Deadline": ("sparkdl_tpu.core.resilience", "Deadline"),
    "Fault": ("sparkdl_tpu.core.resilience", "Fault"),
    "FaultInjector": ("sparkdl_tpu.core.resilience", "FaultInjector"),
    "RetryPolicy": ("sparkdl_tpu.core.resilience", "RetryPolicy"),
    "classify": ("sparkdl_tpu.core.resilience", "classify"),
    "DeviceExecutor": ("sparkdl_tpu.core.executor", "DeviceExecutor"),
    "DevicePrefetcher": ("sparkdl_tpu.core.pipeline", "DevicePrefetcher"),
    "DecodePool": ("sparkdl_tpu.core.decode_pool", "DecodePool"),
    "HealthMonitor": ("sparkdl_tpu.core.health", "HealthMonitor"),
    "MetricsRegistry": ("sparkdl_tpu.core.telemetry", "MetricsRegistry"),
    "RunReport": ("sparkdl_tpu.core.telemetry", "RunReport"),
    "SLORule": ("sparkdl_tpu.core.slo", "SLORule"),
    "SLOWatchdog": ("sparkdl_tpu.core.slo", "SLOWatchdog"),
    "Telemetry": ("sparkdl_tpu.core.telemetry", "Telemetry"),
    "Tracer": ("sparkdl_tpu.core.telemetry", "Tracer"),
}

__all__ = sorted(_LAZY_EXPORTS)


def __getattr__(name):
    try:
        module_name, attr = _LAZY_EXPORTS[name]
    except KeyError:
        raise AttributeError(
            f"module 'sparkdl_tpu.core' has no attribute {name!r}") from None
    import importlib

    # by its full name: ``from sparkdl_tpu.core import …`` would ask this
    # resolver for it
    profiling = importlib.import_module("sparkdl_tpu.core.profiling")
    started = profiling.import_begin()      # import_s of the start-up record
    try:
        if module_name == "sparkdl_tpu.core":
            value = importlib.import_module(f"sparkdl_tpu.core.{attr}")
        else:
            value = getattr(importlib.import_module(module_name), attr)
    finally:
        profiling.import_end(started)
    globals()[name] = value
    return value
