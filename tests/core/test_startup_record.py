"""The start-up record (ISSUE 41; core/profiling.py): set-up measured
where it happens — imports, model build, trace / lower / compile or cache
retrieval, first launch — kept past a window's ``reset_phase_stats()`` and
mirrored into every telemetry scope as ``sparkdl.startup.*`` gauges.

The record is the process's, and the suite shares the process: every test
compares a reading before with a reading after."""

import importlib
import threading
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.core import batching, profiling, telemetry
from sparkdl_tpu.core.model_function import ModelFunction, TensorSpec
from sparkdl_tpu.core.telemetry import Telemetry

KEYS = telemetry.STARTUP_KEYS
COMPILE_KEYS = ("trace_lower_s", "backend_compile_s", "cache_retrieval_s",
                "cache_hits", "cache_misses", "compile_spans")


def _grown(before):
    after = profiling.startup_stats()
    return {key: after[key] - before[key] for key in KEYS}


def _fresh_jit(scale):
    """A jitted function nothing has compiled yet (a new closure)."""
    return jax.jit(lambda x: jnp.tanh(x * scale) + scale)


def _model(name="startup_mf", scale=3.0):
    return ModelFunction(lambda vs, x: jnp.tanh(x * vs), jnp.asarray(scale),
                         TensorSpec((None, 5)), name=name)


def test_record_holds_every_key_and_survives_the_phase_reset():
    with profiling.compile_span(model="reset"):
        _fresh_jit(1.5)(jnp.ones((3,)))
    before = profiling.startup_stats()
    assert set(before) == set(KEYS)
    assert before["compile_spans"] >= 1 and before["trace_lower_s"] > 0
    with profiling.annotate("sparkdl.stage"):
        pass
    profiling.reset_phase_stats()
    assert profiling.phase_stats() == {}
    assert profiling.startup_stats() == before


def test_compile_outside_a_span_adds_nothing_and_inside_adds_its_parts():
    with profiling.compile_span(model="registers-the-listeners"):
        pass
    before = profiling.startup_stats()
    calls = profiling._listener_calls
    _fresh_jit(2.5)(jnp.ones((4,)))          # a caller's own program
    assert profiling._listener_calls > calls    # JAX told the listener …
    assert _grown(before) == dict.fromkeys(KEYS, 0.0)   # … it was not ours
    t0 = time.perf_counter()
    with profiling.compile_span(model="inside"):
        _fresh_jit(3.5)(jnp.ones((4,)))
    wall = time.perf_counter() - t0
    grown = _grown(before)
    assert grown["compile_spans"] == 1
    assert grown["trace_lower_s"] > 0 and grown["backend_compile_s"] > 0
    # the suite runs with the persistent cache off: nothing retrieved
    assert grown["cache_retrieval_s"] == 0 and grown["cache_hits"] == 0
    parts = (grown["trace_lower_s"] + grown["backend_compile_s"]
             + grown["cache_retrieval_s"] + grown["first_launch_s"])
    assert 0 < parts <= wall


def test_nested_jits_are_not_counted_twice():
    """JAX reports an inner ``jit``'s trace inside the outer one's: the
    parts still add up to no more than the span."""
    inner = jax.jit(lambda x: jnp.sin(x) * 1.25)
    outer = jax.jit(lambda x: inner(inner(x)) + inner(x * 2))
    before = profiling.startup_stats()
    t0 = time.perf_counter()
    with profiling.compile_span(model="nested"):
        outer(jnp.ones((6,)))
    wall = time.perf_counter() - t0
    grown = _grown(before)
    assert grown["trace_lower_s"] + grown["backend_compile_s"] \
        + grown["first_launch_s"] <= wall


def test_compile_on_another_thread_is_not_heard():
    """The depth is the compiling thread's: a program some other thread
    compiles while a span is open here is left out."""
    before = profiling.startup_stats()
    with profiling.compile_span(model="this-thread"):
        thread = threading.Thread(
            target=lambda: _fresh_jit(4.5)(jnp.ones((2,))))
        thread.start()
        thread.join()
    grown = _grown(before)
    assert grown["compile_spans"] == 1
    assert grown["backend_compile_s"] == 0 and grown["trace_lower_s"] == 0


def test_first_launch_compiles_once_and_a_warm_launch_fires_no_callback():
    mf = _model()
    rows = np.ones((8, 5), np.float32)
    before = profiling.startup_stats()
    with Telemetry() as tel:
        mf.apply_batch(rows, batch_size=8)
    (span,) = tel.tracer.spans(telemetry.SPAN_COMPILE)
    grown = _grown(before)
    assert grown["compile_spans"] == 1 and grown["backend_compile_s"] > 0
    # the launch's first sync point (batching.fetch) added its wait
    assert grown["first_launch_s"] > 0
    # the span carries its own share
    attributes = span["attributes"]
    assert attributes["model"] == "startup_mf"
    assert attributes["trace_lower_s"] == pytest.approx(
        grown["trace_lower_s"])
    assert attributes["backend_compile_s"] == pytest.approx(
        grown["backend_compile_s"])
    assert attributes["cache_misses"] == 0

    warm = profiling.startup_stats()
    calls = profiling._listener_calls
    with Telemetry() as tel:
        for _ in range(3):
            mf.apply_batch(rows, batch_size=8)
    assert tel.tracer.spans(telemetry.SPAN_COMPILE) == []
    assert profiling._listener_calls == calls
    assert profiling.startup_stats() == warm


def test_fetch_takes_the_mark_once():
    """Only the fetch that follows a compiling launch on its thread is the
    first launch's; the next one is an ordinary fetch."""
    out = jnp.ones((4, 2))
    before = profiling.startup_stats()
    batching.fetch(out, 4)
    assert _grown(before)["first_launch_s"] == 0
    with profiling.compile_span(model="mark"):
        pass
    mid = profiling.startup_stats()
    batching.fetch(out, 4)
    first = _grown(mid)["first_launch_s"]
    assert first > 0
    batching.fetch(out, 4)
    assert _grown(mid)["first_launch_s"] == first


def test_collect_of_a_warm_transform_fires_no_callback():
    import pyarrow as pa

    from sparkdl_tpu.engine.dataframe import DataFrame
    from sparkdl_tpu.ml import TPUTransformer

    frame = DataFrame.fromArrow(
        pa.table({"x": pa.array([[float(i)] * 5 for i in range(16)],
                                type=pa.list_(pa.float32()))}),
        numPartitions=2)
    transformer = TPUTransformer(inputCol="x", outputCol="y",
                                 modelFunction=_model("startup_collect"),
                                 batchSize=8)
    first = transformer.transform(frame).collect()
    warm = profiling.startup_stats()
    calls = profiling._listener_calls
    again = transformer.transform(frame).collect()
    assert [r["y"] for r in again] == [r["y"] for r in first]
    assert profiling._listener_calls == calls
    assert profiling.startup_stats() == warm


def test_scope_opened_after_the_first_launch_shows_every_gauge():
    mf = _model("startup_late_scope", scale=5.0)
    mf.apply_batch(np.ones((8, 5), np.float32), batch_size=8)   # no scope
    record = profiling.startup_stats()
    with Telemetry() as tel:
        snapshot = tel.metrics.snapshot()
        report = tel.report()
    gauges = snapshot["gauges"]
    for key in KEYS:
        assert gauges[telemetry.STARTUP_METRIC_PREFIX + key] == record[key]
    assert gauges["sparkdl.startup.cache_misses"] == 0.0     # set, not absent
    assert gauges["sparkdl.startup.backend_compile_s"] > 0
    assert tel.tracer.spans(telemetry.SPAN_COMPILE) == []   # it came before
    assert report["startup"] == record


def test_record_growing_under_a_scope_is_mirrored_again():
    with Telemetry() as tel:
        opened = tel.metrics.snapshot()["gauges"]
        with profiling.compile_span(model="under-scope"):
            _fresh_jit(6.5)(jnp.ones((3,)))
        closed = tel.metrics.snapshot()["gauges"]
    name = telemetry.STARTUP_METRIC_PREFIX + "compile_spans"
    assert closed[name] == opened[name] + 1
    assert closed == {**closed, **{
        telemetry.STARTUP_METRIC_PREFIX + key: value
        for key, value in profiling.startup_stats().items()}}


def test_nested_blocks_count_once_whatever_their_keys():
    """An import resolved while a model is built is ``import_s``, and the
    build's own time is what is left: the sum is the outer block's wall."""
    before = profiling.startup_stats()
    t0 = time.perf_counter()
    with profiling.model_build("outer"):
        time.sleep(0.02)
        started = profiling.import_begin()
        time.sleep(0.03)
        inner = profiling.import_begin()        # a package inside a package
        time.sleep(0.01)
        profiling.import_end(inner)
        profiling.import_end(started)
    wall = time.perf_counter() - t0
    grown = _grown(before)
    assert grown["import_s"] >= 0.04
    assert grown["model_build_s"] >= 0.02
    assert grown["import_s"] + grown["model_build_s"] <= wall


def test_packages_stamp_their_first_import():
    """``import_s`` is fed by the packages' ``__init__`` bodies and the lazy
    resolvers; a module already imported costs a dictionary read."""
    import sparkdl_tpu
    import sparkdl_tpu.train

    assert profiling.startup_stats()["import_s"] > 0
    before = profiling.startup_stats()
    importlib.reload(sparkdl_tpu.train)         # the body runs again
    assert sparkdl_tpu.Trainer is sparkdl_tpu.train.Trainer
    grown = _grown(before)
    assert 0 < grown["import_s"] < 1.0
    assert all(grown[key] == 0 for key in KEYS if key != "import_s")


def test_model_build_span_around_the_registry_and_the_precision_cast():
    from sparkdl_tpu.models import registry

    before = profiling.startup_stats()
    with Telemetry() as tel:
        mf = registry.build_featurizer("TestNet", weights="random")
        cast = mf.with_dtype("bfloat16")
        assert mf.with_dtype("bfloat16") is cast        # memoized: no span
    grown = _grown(before)
    spans = tel.tracer.spans(telemetry.SPAN_MODEL_BUILD)
    assert [s["attributes"]["model"] for s in spans] == [
        "TestNet_featurize", "TestNet_featurize"]
    assert spans[0]["attributes"]["bytes"] == mf.weight_bytes()
    assert spans[1]["attributes"]["precision"] == "bfloat16"
    assert grown["model_build_s"] > 0
    # building compiles programs of its own (the jitted init): they are the
    # build's seconds, not compile spans
    assert all(grown[key] == 0 for key in COMPILE_KEYS)


class _TinyMLP(nn.Module):
    @nn.compact
    def __call__(self, x, train: bool = False):
        return jax.nn.softmax(nn.Dense(3)(nn.relu(nn.Dense(8)(x))), axis=-1)


def test_fit_opens_compile_for_its_first_step_only():
    from sparkdl_tpu.train import Trainer

    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, size=32)]
    batches = [(x[i:i + 8], y[i:i + 8]) for i in range(0, 32, 8)]
    module = _TinyMLP()
    before = profiling.startup_stats()
    with Telemetry() as tel:
        trainer, state = Trainer.from_flax(
            module, module.init(jax.random.PRNGKey(0), x[:1]),
            optimizer="sgd", learning_rate=0.1, step_cache={},
            step_cache_key="startup")
        t0 = time.perf_counter()
        state = trainer.fit(state, batches, epochs=1, sync_every=2)
        wall = time.perf_counter() - t0
    grown = _grown(before)
    (span,) = tel.tracer.spans(telemetry.SPAN_COMPILE)
    assert span["attributes"]["model"] == "train_step"
    (build,) = tel.tracer.spans(telemetry.SPAN_MODEL_BUILD)
    assert build["attributes"]["model"] == "_TinyMLP"
    assert build["attributes"]["bytes"] > 0
    assert grown["compile_spans"] == 1 and grown["backend_compile_s"] > 0
    assert grown["model_build_s"] > 0
    # up to the first sync point after the step that compiled
    assert grown["first_launch_s"] > 0
    assert grown["trace_lower_s"] + grown["backend_compile_s"] \
        + grown["cache_retrieval_s"] + grown["first_launch_s"] <= wall

    # the compiled step is shared through the step cache: a second fit, and
    # every step of it, opens nothing and fires no callback
    warm = profiling.startup_stats()
    calls = profiling._listener_calls
    with Telemetry() as tel:
        trainer.fit(state, batches, epochs=2, sync_every=2)
    assert tel.tracer.spans(telemetry.SPAN_COMPILE) == []
    assert len(tel.tracer.spans("sparkdl.train_step")) == 4     # resumed
    assert profiling._listener_calls == calls
    assert profiling.startup_stats() == warm


def test_evaluate_opens_compile_for_its_first_batch_only():
    from sparkdl_tpu.train import Trainer

    x = np.ones((8, 6), np.float32)
    y = np.eye(3, dtype=np.float32)[np.zeros(8, int)]
    module = _TinyMLP()
    trainer, state = Trainer.from_flax(
        module, module.init(jax.random.PRNGKey(1), x[:1]), optimizer="sgd",
        learning_rate=0.1)
    before = profiling.startup_stats()
    with Telemetry() as tel:
        first = trainer.evaluate(state, [(x, y), (x, y)])
        again = trainer.evaluate(state, [(x, y)])
    assert first == again
    (span,) = tel.tracer.spans(telemetry.SPAN_COMPILE)
    assert span["attributes"]["model"] == "eval_metrics_step"
    grown = _grown(before)
    assert grown["compile_spans"] == 1 and grown["first_launch_s"] > 0


def test_threads_feeding_the_record_lose_no_update():
    """More workers than cores, a short switch interval: every closed span
    and every block's seconds arrive (the record's lock), and a thread's
    blocks and marks stay its own."""
    import sys

    workers, rounds = 16, 50
    before = profiling.startup_stats()
    failures = []

    def work():
        try:
            for _ in range(rounds):
                with profiling.model_build("stress"):
                    with profiling.compile_span(model="stress"):
                        pass
                assert profiling.first_launch_wait() is not None
                assert profiling.first_launch_wait() is None
        except BaseException as e:  # noqa: BLE001 - reported below
            failures.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work) for _ in range(workers)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(thread.is_alive() for thread in threads)
    assert failures == []
    grown = _grown(before)
    assert grown["compile_spans"] == workers * rounds
    assert grown["model_build_s"] > 0
