"""Online serving plane (ISSUE 13 tentpole): row-level requests through
the executor choke point, versioned hot-swap with zero dropped /
double-served requests, deterministic shadow traffic, SLO-aware
admission, and the executor_idle_retire_s knob."""

import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from sparkdl_tpu.core import batching, executor, health, slo, telemetry
from sparkdl_tpu.core.health import HealthMonitor
from sparkdl_tpu.core.model_function import ModelFunction, TensorSpec
from sparkdl_tpu.core.telemetry import Telemetry
from sparkdl_tpu.engine.dataframe import EngineConfig
from sparkdl_tpu.serving import (
    ModelRegistry,
    ModelServer,
    ResidencyManager,
    ServingOverloaded,
)

_ELEMENT = (6,)
_FEATURES = 3


@pytest.fixture(autouse=True)
def _fresh_executor():
    saved = EngineConfig.snapshot()
    executor.reset()
    yield
    executor.reset()
    EngineConfig.restore(saved)


def _model(scale: float, name: str = "served") -> ModelFunction:
    rng = np.random.default_rng(7)
    w = jnp.asarray((rng.normal(size=(_ELEMENT[0], _FEATURES)) * scale)
                    .astype(np.float32))
    return ModelFunction(lambda vs, x: jnp.tanh(x @ vs), w,
                         TensorSpec((None,) + _ELEMENT, "float32"),
                         name=name)


def _reference(model: ModelFunction, rows: np.ndarray,
               batch_size: int = 64) -> np.ndarray:
    """Ground truth computed WITHOUT the serving stack, at a launch's
    shape: the executor pads a request to a bucket of at least 8 rows
    (``batching.bucket_size``), and XLA's CPU dot sums a row of an
    operand of under 8 rows in another order than the same row of a
    larger one (6e-8 apart; from 8 rows up every size agrees bit for
    bit). Padded alike, the fp32 conftest pin makes the served outputs
    bit-identical to this."""
    padded, n = batching.pad_batch(
        rows, batching.bucket_size(len(rows), batch_size))
    return np.asarray(jnp.tanh(jnp.asarray(padded) @ model.variables))[:n]


def _serving_stack(**server_kw):
    reg = ModelRegistry()
    return reg, ModelServer(reg, **server_kw)


# ---------------------------------------------------------------------------
# Request API basics
# ---------------------------------------------------------------------------


def test_single_row_and_small_batch_roundtrip(rng):
    reg, srv = _serving_stack()
    m = _model(1.0)
    reg.deploy("clf", "v1", model=m)
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    got = srv.predict("clf", row)
    assert got.version == "v1"
    assert got.output.shape == (_FEATURES,)
    np.testing.assert_array_equal(got.output, _reference(m, row[None])[0])
    batch = rng.normal(size=(5,) + _ELEMENT).astype(np.float32)
    got = srv.predict("clf", batch)
    assert np.asarray(got.output).shape == (5, _FEATURES)
    np.testing.assert_array_equal(got.output, _reference(m, batch))


def test_predict_unknown_model_raises():
    _, srv = _serving_stack()
    with pytest.raises(KeyError, match="no model named"):
        srv.predict("ghost", np.zeros(_ELEMENT, np.float32))


def test_predict_records_serving_metrics(rng):
    reg, srv = _serving_stack()
    reg.deploy("clf", "v1", model=_model(1.0))
    with Telemetry("serving-test", window_s=30.0) as tel:
        srv.predict("clf", rng.normal(size=_ELEMENT).astype(np.float32))
        hist = tel.metrics.histogram(telemetry.M_SERVING_REQUEST_S)
        assert hist.count == 1
        per_model = tel.metrics.histogram(
            telemetry.serving_request_metric("clf"))
        assert per_model.count == 1


def test_deadline_propagates_to_executor(rng):
    """An already-expired deadline is shed AT admission inside the
    executor — the serving deadline_ms parameter reaches the device
    service, it isn't decorative."""
    from sparkdl_tpu.core import resilience

    reg, srv = _serving_stack()
    reg.deploy("clf", "v1", model=_model(1.0))
    with pytest.raises(resilience.DeadlineExceeded):
        srv.predict("clf", rng.normal(size=_ELEMENT).astype(np.float32),
                    deadline_ms=0.0)


# ---------------------------------------------------------------------------
# Versioned registry: deploy / shadow / cutover / rollback
# ---------------------------------------------------------------------------


def test_deploy_versions_are_immutable():
    reg, _ = _serving_stack()
    reg.deploy("clf", "v1", model=_model(1.0))
    with pytest.raises(ValueError, match="already deployed"):
        reg.deploy("clf", "v1", model=_model(2.0))


def test_shadow_fraction_is_deterministic(rng):
    """fraction=0.25 mirrors EXACTLY every 4th request — accumulator,
    not RNG, so replay runs see the same shadow set."""
    reg, srv = _serving_stack()
    reg.deploy("clf", "v1", model=_model(1.0))
    reg.deploy("clf", "v2", model=_model(2.0))
    reg.shadow("clf", "v2", fraction=0.25)
    rows = rng.normal(size=(8,) + _ELEMENT).astype(np.float32)
    with HealthMonitor("shadow") as mon:
        flags = [srv.predict("clf", rows[i]).shadowed for i in range(8)]
    assert flags == [False, False, False, True] * 2
    assert mon.count(health.SERVING_SHADOW_COMPARED) == 2


def test_shadow_responses_come_from_active_and_divergence_recorded(rng):
    reg, srv = _serving_stack()
    v1, v2 = _model(1.0), _model(2.0)
    reg.deploy("clf", "v1", model=v1)
    reg.deploy("clf", "v2", model=v2)
    reg.shadow("clf", "v2", fraction=1.0)
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    with Telemetry("shadow-div", window_s=30.0) as tel:
        with HealthMonitor("shadow") as mon:
            got = srv.predict("clf", row)
        assert got.version == "v1"  # the answer is ALWAYS the active's
        np.testing.assert_array_equal(got.output,
                                      _reference(v1, row[None])[0])
        div = tel.metrics.histogram(
            telemetry.M_SERVING_SHADOW_DIVERGENCE)
        assert div.count == 1
    events = mon.events(health.SERVING_SHADOW_COMPARED)
    assert len(events) == 1
    expected_div = float(np.max(np.abs(
        _reference(v1, row[None]) - _reference(v2, row[None]))))
    assert events[0]["divergence"] == pytest.approx(expected_div)


def test_shadow_failure_never_fails_the_request(rng):
    reg, srv = _serving_stack()
    v1 = _model(1.0)
    reg.deploy("clf", "v1", model=v1)

    def bad_loader():
        raise RuntimeError("candidate model is broken")

    reg.deploy("clf", "v2", loader=bad_loader)
    reg.shadow("clf", "v2", fraction=1.0)
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    with HealthMonitor("shadow-err") as mon:
        got = srv.predict("clf", row)
    np.testing.assert_array_equal(got.output, _reference(v1, row[None])[0])
    assert mon.count(health.SERVING_SHADOW_ERROR) == 1


def test_shadow_validation():
    reg, _ = _serving_stack()
    reg.deploy("clf", "v1", model=_model(1.0))
    with pytest.raises(KeyError, match="no version"):
        reg.shadow("clf", "v9")
    with pytest.raises(ValueError, match="active version"):
        reg.shadow("clf", "v1")
    reg.deploy("clf", "v2", model=_model(2.0))
    with pytest.raises(ValueError, match="fraction"):
        reg.shadow("clf", "v2", fraction=1.5)


def test_hot_swap_zero_dropped_zero_double_served_under_load(rng):
    """THE acceptance test: a v1->v2 cutover lands mid-flood. Every
    request gets exactly one answer, that answer is bit-identical to
    the reference output of the version the registry says served it,
    both versions actually serve, shadow comparison records are
    emitted, and rollback (the same primitive) restores v1."""
    reg, srv = _serving_stack()
    v1, v2 = _model(1.0), _model(2.0)
    reg.deploy("clf", "v1", model=v1)
    reg.deploy("clf", "v2", model=v2)
    reg.shadow("clf", "v2", fraction=0.2)  # shadow armed through the swap

    n_threads, per_thread = 4, 25
    rows = rng.normal(size=(n_threads, per_thread) + _ELEMENT
                      ).astype(np.float32)
    ref = {"v1": [_reference(v1, rows[t]) for t in range(n_threads)],
           "v2": [_reference(v2, rows[t]) for t in range(n_threads)]}
    results = [[None] * per_thread for _ in range(n_threads)]
    errors = []
    swap_at = threading.Event()

    def client(t):
        for i in range(per_thread):
            if t == 0 and i == per_thread // 2:
                swap_at.set()
            try:
                results[t][i] = srv.predict("clf", rows[t][i])
            except Exception as e:  # noqa: BLE001 - the test asserts none
                errors.append((t, i, e))

    def swapper():
        swap_at.wait(timeout=30.0)
        reg.cutover("clf", "v2")

    with HealthMonitor("swap") as mon:
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(n_threads)]
        sw = threading.Thread(target=swapper)
        for th in threads + [sw]:
            th.start()
        for th in threads + [sw]:
            th.join(timeout=60.0)

    assert not errors, f"dropped requests: {errors[:3]}"
    served = {"v1": 0, "v2": 0}
    for t in range(n_threads):
        for i in range(per_thread):
            got = results[t][i]
            assert got is not None, f"request ({t},{i}) never answered"
            served[got.version] += 1
            np.testing.assert_array_equal(
                got.output, ref[got.version][t][i],
                err_msg=f"request ({t},{i}) not bit-identical to its "
                        f"version {got.version}")
    # exactly one answer per request, each from exactly one version
    assert served["v1"] + served["v2"] == n_threads * per_thread
    assert served["v2"] > 0, "cutover never took effect"
    assert mon.count(health.SERVING_CUTOVER) == 1
    assert mon.count(health.SERVING_SHADOW_COMPARED) > 0

    # rollback is the SAME primitive, aimed backwards
    with HealthMonitor("rollback") as mon2:
        assert reg.rollback("clf") == "v2"
    assert reg.active_version("clf") == "v1"
    assert mon2.count(health.SERVING_CUTOVER) == 1
    after = srv.predict("clf", rows[0][0])
    assert after.version == "v1"
    np.testing.assert_array_equal(after.output, ref["v1"][0][0])


def test_rollback_without_history_raises():
    reg, _ = _serving_stack()
    reg.deploy("clf", "v1", model=_model(1.0))
    with pytest.raises(ValueError, match="no previous"):
        reg.rollback("clf")


# ---------------------------------------------------------------------------
# SLO-aware admission
# ---------------------------------------------------------------------------


def _saturate_queue_wait(tel, seconds: float, n: int = 50) -> None:
    for _ in range(n):
        tel.metrics.histogram(telemetry.M_QUEUE_WAIT_S).observe(seconds)


def test_admission_sheds_on_queue_wait_p99_over_budget(rng):
    reg, srv = _serving_stack(slo_window_s=30.0)
    reg.deploy("clf", "v1", model=_model(1.0), latency_target_ms=100.0)
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    with Telemetry("admit", window_s=30.0) as tel:
        srv.predict("clf", row)  # healthy plane admits
        _saturate_queue_wait(tel, 0.2)  # p99 ~200ms > 50ms budget
        with HealthMonitor("shed") as mon:
            with pytest.raises(ServingOverloaded, match="queue-wait p99"):
                srv.predict("clf", row)
        assert mon.count(health.SERVING_SHED) == 1


def test_admission_block_mode_never_sheds(rng):
    reg, srv = _serving_stack(admission="block")
    reg.deploy("clf", "v1", model=_model(1.0), latency_target_ms=100.0)
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    with Telemetry("block", window_s=30.0) as tel:
        _saturate_queue_wait(tel, 0.2)
        got = srv.predict("clf", row)  # admitted; backpressure owns it
    assert got.version == "v1"


def test_admission_without_target_or_telemetry_admits(rng):
    reg, srv = _serving_stack()
    reg.deploy("clf", "v1", model=_model(1.0))  # no latency target
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    assert srv.predict("clf", row).version == "v1"  # no telemetry scope


def test_latency_target_drives_coalesce_window():
    reg, srv = _serving_stack()
    dep = reg.deploy("clf", "v1", model=_model(1.0),
                     latency_target_ms=50.0)
    assert srv._window_ms(dep) == pytest.approx(5.0)  # 10% of target
    loose = reg.deploy("clf", "v2", model=_model(2.0),
                       latency_target_ms=10_000.0)
    assert srv._window_ms(loose) == pytest.approx(20.0)  # capped
    free = reg.deploy("clf2", "v1", model=_model(3.0))
    assert srv._window_ms(free) is None  # adaptive


# ---------------------------------------------------------------------------
# default_serving_rules
# ---------------------------------------------------------------------------


def test_default_serving_rules_per_model_and_shed():
    rules = slo.default_serving_rules({"clf": 0.25, "ranker": 0.5})
    by_name = {r.name: r for r in rules}
    assert "serving_request_p99" in by_name
    assert "serving_shed_rate" in by_name
    clf = by_name["serving_request_p99_clf"]
    assert clf.metric == "sparkdl.serving.request_s.clf"
    assert clf.threshold == 0.25
    assert clf.stat == "p99"
    assert by_name["serving_request_p99_ranker"].threshold == 0.5
    # the dynamic names were declared into the catalog (SLORule
    # construction would have raised otherwise)
    assert "sparkdl.serving.request_s.clf" in \
        telemetry.CANONICAL_METRIC_KINDS


def test_declare_metric_rejects_kind_conflicts():
    telemetry.declare_metric("sparkdl.serving.request_s.tmp_kind",
                             "histogram")
    with pytest.raises(ValueError, match="already declared"):
        telemetry.declare_metric("sparkdl.serving.request_s.tmp_kind",
                                 "counter")
    with pytest.raises(ValueError, match="kind must be"):
        telemetry.declare_metric("sparkdl.serving.x", "timer")


def test_registry_targets_feed_serving_rules():
    reg, _ = _serving_stack()
    reg.deploy("clf", "v1", model=_model(1.0), latency_target_ms=250.0)
    reg.deploy("free", "v1", model=_model(2.0))
    targets = reg.targets()
    assert targets == {"clf": 0.25}
    rules = slo.default_serving_rules(targets)
    assert any(r.name == "serving_request_p99_clf" for r in rules)


def test_cluster_serving_rules_add_failover_rate():
    from sparkdl_tpu.core import health

    rules = slo.cluster_serving_rules({"clf": 0.25})
    by_name = {r.name: r for r in rules}
    # superset of the single-process plane's rules...
    for name in ("serving_request_p99", "serving_shed_rate",
                 "serving_request_p99_clf"):
        assert name in by_name
    # ...plus the sustained-failover watchdog on the health mirror
    fo = by_name["serving_failover_rate"]
    assert fo.metric == telemetry.HEALTH_METRIC_PREFIX \
        + health.SERVING_FAILOVER
    assert fo.stat == "rate_per_s"
    assert fo.threshold == slo.DEFAULT_SERVING_FAILOVER_RATE_PER_S


# ---------------------------------------------------------------------------
# ml/udf resolve through the registry
# ---------------------------------------------------------------------------


def test_transformer_resolves_served_model_name_and_follows_cutover(rng):
    from sparkdl_tpu.engine.dataframe import DataFrame
    from sparkdl_tpu.ml import TPUTransformer
    from sparkdl_tpu.serving.registry import default_registry

    v1, v2 = _model(1.0), _model(2.0)
    reg = default_registry()
    name = "test_transformer_resolves__clf"
    reg.deploy(name, "v1", model=v1)
    rows = rng.normal(size=(6,) + _ELEMENT).astype(np.float32)
    df = DataFrame.fromColumns({"feat": rows}, numPartitions=2)
    tr = TPUTransformer(inputCol="feat", outputCol="out",
                        modelFunction=name, batchSize=4)
    out1 = np.array([r["out"] for r in tr.transform(df).collect()],
                    dtype=np.float32)
    np.testing.assert_array_equal(out1, _reference(v1, rows, batch_size=4))
    # a cutover reaches the NEXT transform call — no new transformer
    reg.deploy(name, "v2", model=v2, activate=True)
    out2 = np.array([r["out"] for r in tr.transform(df).collect()],
                    dtype=np.float32)
    np.testing.assert_array_equal(out2, _reference(v2, rows, batch_size=4))


# ---------------------------------------------------------------------------
# executor_idle_retire_s knob
# ---------------------------------------------------------------------------


def test_idle_retire_knob_validated_and_snapshotted():
    assert "executor_idle_retire_s" in EngineConfig.snapshot()
    EngineConfig.executor_idle_retire_s = 0.0
    with pytest.raises(ValueError, match="executor_idle_retire_s"):
        EngineConfig.validate()
    EngineConfig.executor_idle_retire_s = -1.0
    with pytest.raises(ValueError, match="executor_idle_retire_s"):
        EngineConfig.validate()
    EngineConfig.executor_idle_retire_s = 0.05
    EngineConfig.validate()


def test_idle_retire_knob_drives_state_retirement(rng):
    """With the knob at 50ms, an idle model's coalescing state (the
    strong reference pinning its weights) is swept well before the old
    hard-coded 5s: solo requests ride the inline fast path (no
    coalescer thread), so retirement happens on the next new-state
    sweep — which the knob now gates."""
    EngineConfig.executor_idle_retire_s = 0.05
    reg, srv = _serving_stack()
    reg.deploy("clf", "v1", model=_model(1.0, name="retire_me"))
    reg.deploy("other", "v1", model=_model(2.0, name="keeper"))
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    srv.predict("clf", row)
    assert [m["model"] for m in executor.status()["models"]] \
        == ["retire_me"]
    time.sleep(0.15)  # > knob; far under the old 5 s constant
    srv.predict("other", row)  # new state -> sweep retires "retire_me"
    names = [m["model"] for m in executor.status()["models"]]
    assert "retire_me" not in names, (
        "idle state survived past executor_idle_retire_s")
    assert "keeper" in names


def test_retire_model_drops_idle_states(rng):
    """DeviceExecutor.retire_model (the residency eviction hook) drops
    an idle model's coalescing state immediately — no sweep needed."""
    reg, srv = _serving_stack()
    m = _model(1.0, name="evictee")
    reg.deploy("clf", "v1", model=m)
    srv.predict("clf", rng.normal(size=_ELEMENT).astype(np.float32))
    assert [s["model"] for s in executor.status()["models"]] \
        == ["evictee"]
    dropped = executor.service().retire_model(
        m, variants=m.device_variants())
    assert dropped >= 1
    assert not executor.status()["models"]


# ---------------------------------------------------------------------------
# AOT bucket-ladder warmup (ISSUE 20): serving_warmup knob
# ---------------------------------------------------------------------------


def test_warmup_armed_deploy_compiles_ladder_before_traffic(rng):
    """Deploy with the knob armed: the full ladder compiles eagerly
    (one WARMUP_COMPLETED, a sparkdl.serving.warmup_s span) and the
    FIRST request then pays zero compile — no sparkdl.compile span."""
    EngineConfig.serving_warmup = True
    reg, srv = _serving_stack()
    m = _model(1.0)
    with Telemetry("warmup") as tel:
        with HealthMonitor("warmup") as mon:
            reg.deploy("clf", "v1", model=m, batch_size=8)
        spans = tel.tracer.spans(name=telemetry.SPAN_SERVING_WARMUP)
    assert len(spans) == 1
    assert mon.count(health.WARMUP_COMPLETED) == 1
    ev = mon.events(health.WARMUP_COMPLETED)[0]
    assert ev["model"] == "clf" and ev["version"] == "v1"
    assert ev["rungs"] >= 1

    row = rng.normal(size=_ELEMENT).astype(np.float32)
    with Telemetry("warmup") as tel:
        got = srv.predict("clf", row)
        assert tel.tracer.spans(name=telemetry.SPAN_COMPILE) == []
    np.testing.assert_array_equal(got.output, _reference(m, row[None])[0])


def test_warmup_off_deploy_stays_lazy(rng):
    """Default (knob off): deploying a loader materializes NOTHING and
    no warmup event fires — first traffic pays the cold start, exactly
    the pre-knob behavior."""
    reg, srv = _serving_stack()
    calls = []

    def loader():
        calls.append(1)
        return _model(1.0)

    row = rng.normal(size=_ELEMENT).astype(np.float32)
    with HealthMonitor("warmup") as mon:
        reg.deploy("clf", "v1", loader=loader, batch_size=8)
        assert calls == [], "deploy materialized a lazy loader"
        srv.predict("clf", row)
    assert calls == [1]
    assert mon.count(health.WARMUP_COMPLETED) == 0


def test_post_cutover_first_request_pays_zero_compile(rng):
    """The dark v2 warms at deploy; after cutover its first live
    request must hit only warmed programs."""
    EngineConfig.serving_warmup = True
    reg, srv = _serving_stack()
    m1, m2 = _model(1.0), _model(-0.5)
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    with HealthMonitor("warmup") as mon:
        reg.deploy("clf", "v1", model=m1, batch_size=8)
        srv.predict("clf", row)
        reg.deploy("clf", "v2", model=m2, batch_size=8)  # dark + warmed
    assert mon.count(health.WARMUP_COMPLETED) == 2
    reg.cutover("clf", "v2")
    with Telemetry("warmup") as tel:
        got = srv.predict("clf", row)
        assert tel.tracer.spans(name=telemetry.SPAN_COMPILE) == []
    assert got.version == "v2"
    np.testing.assert_array_equal(got.output,
                                  _reference(m2, row[None])[0])


def test_eviction_reload_rewarms_ladder(rng):
    """Warmup wraps the LOADER, so a post-eviction residency reload
    pays the ladder again before taking traffic."""
    EngineConfig.serving_warmup = True
    res = ResidencyManager(budget_bytes=10 * 1024)
    reg = ModelRegistry(residency=res)
    srv = ModelServer(reg)
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    with HealthMonitor("warmup") as mon:
        reg.deploy("clf", "v1", loader=lambda: _model(1.0), batch_size=8)
        assert mon.count(health.WARMUP_COMPLETED) == 1
        res.pin("clf", "v1", pinned=False)
        assert res.evict("clf", "v1")
        srv.predict("clf", row)  # cold reload -> the ladder re-warms
    assert mon.count(health.WARMUP_COMPLETED) == 2


def test_warmup_skips_models_without_static_shape(rng):
    """A dynamic element shape has no knowable ladder: warmup skips
    best-effort, deploy and serving still work."""
    EngineConfig.serving_warmup = True
    reg, srv = _serving_stack()
    base = _model(1.0)
    m = ModelFunction(lambda vs, x: jnp.tanh(x @ vs), base.variables,
                      TensorSpec((None, None), "float32"), name="dyn")
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    with HealthMonitor("warmup") as mon:
        reg.deploy("clf", "v1", model=m, batch_size=8)
        got = srv.predict("clf", row)
    assert mon.count(health.WARMUP_COMPLETED) == 0
    np.testing.assert_array_equal(got.output,
                                  _reference(base, row[None])[0])


def test_warmup_failure_surfaces_at_deploy(rng):
    """A model that cannot execute its ladder fails the eager deploy
    loudly (cluster-side this same propagation is what nacks
    srv_prepare and rolls a cutover back)."""
    EngineConfig.serving_warmup = True
    reg, _ = _serving_stack()

    def _explode(vs, x):
        raise RuntimeError("bad weights")

    bad = ModelFunction(_explode, jnp.zeros((1,), jnp.float32),
                        TensorSpec((None,) + _ELEMENT, "float32"),
                        name="bad")
    with pytest.raises(RuntimeError, match="bad weights"):
        reg.deploy("clf", "v1", model=bad, batch_size=8)
