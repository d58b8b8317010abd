"""Cluster serving plane (ISSUE 17): replicated deployments, worker-
death failover with deadline re-admission, and cluster-atomic hot-swap.

The contract under test is the acceptance list: serving_cluster=False /
cluster_workers=0 keeps the single-process serving path byte-identical
and never imports serving/cluster.py; a kill -9'd replica mid-stream
loses ZERO requests (every one completes within its deadline via
failover or fails classified — no hangs) with exactly one
``serving_failover`` event per moved request and survivor outputs
bit-identical to the single-process run; a draining worker admits no
new predicts but finishes its in-flight ones (zero failover events);
cluster cutover is two-phase atomic (no caller pair ever observes
mixed versions; a failed prepare rolls back with v1 still serving
everywhere); and the merged run report + exporter snapshot carry the
replica map.
"""

import multiprocessing
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from sparkdl_tpu.cluster import router as cluster_router
from sparkdl_tpu.core import (
    batching, executor, health, resilience, telemetry,
)
from sparkdl_tpu.core.health import HealthMonitor
from sparkdl_tpu.core.model_function import ModelFunction, TensorSpec
from sparkdl_tpu.core.resilience import Fault, FaultInjector
from sparkdl_tpu.engine.dataframe import EngineConfig
from sparkdl_tpu.serving import ModelRegistry, ModelServer
from sparkdl_tpu.serving import cluster as serving_cluster

_ELEMENT = (6,)
_FEATURES = 3
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# generous per-request deadline: the chaos legs prove zero-hang via
# classified DeadlineExceeded, not via pytest timeouts
_DEADLINE_MS = 60_000.0


@pytest.fixture(autouse=True)
def _cluster_serving_stack():
    saved = EngineConfig.snapshot()
    executor.reset()
    yield
    executor.reset()
    EngineConfig.restore(saved)
    cluster_router.shutdown()  # idempotent; no test leaks a live router
    serving_cluster.reset()


def _arm(workers: int = 2) -> None:
    EngineConfig.cluster_workers = workers
    EngineConfig.serving_cluster = True


def _model(scale: float, name: str = "served") -> ModelFunction:
    rng = np.random.default_rng(7)
    w = jnp.asarray((rng.normal(size=(_ELEMENT[0], _FEATURES)) * scale)
                    .astype(np.float32))
    return ModelFunction(lambda vs, x: jnp.tanh(x @ vs), w,
                         TensorSpec((None,) + _ELEMENT, "float32"),
                         name=name)


def _reference(model: ModelFunction, rows: np.ndarray,
               batch_size: int = 64) -> np.ndarray:
    """Ground truth at a launch's shape; why the padding matters is in
    ``test_server_registry._reference``."""
    padded, n = batching.pad_batch(
        rows, batching.bucket_size(len(rows), batch_size))
    return np.asarray(jnp.tanh(jnp.asarray(padded) @ model.variables))[:n]


def _stack():
    reg = ModelRegistry()
    return reg, ModelServer(reg)


def _router():
    r = cluster_router.maybe_router()
    assert r is not None
    return r


# ---------------------------------------------------------------------------
# The gate: off means OFF
# ---------------------------------------------------------------------------


def test_single_process_serving_never_imports_cluster_serving():
    """cluster_workers=0 (the default) must keep serving/cluster.py
    un-imported, not just unused — pinned in a subprocess because this
    test session itself imports it."""
    script = (
        "import sys\n"
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "from sparkdl_tpu.core.model_function import ModelFunction,"
        " TensorSpec\n"
        "from sparkdl_tpu.engine.dataframe import EngineConfig\n"
        "from sparkdl_tpu.serving import ModelRegistry, ModelServer\n"
        "assert EngineConfig.cluster_workers == 0\n"
        "assert EngineConfig.serving_cluster is False\n"
        "w = jnp.ones((6, 3), dtype='float32')\n"
        "m = ModelFunction(lambda vs, x: jnp.tanh(x @ vs), w,"
        " TensorSpec((None, 6), 'float32'), name='m')\n"
        "reg = ModelRegistry(); srv = ModelServer(reg)\n"
        "reg.deploy('clf', 'v1', model=m)\n"
        "out = srv.predict('clf', np.ones(6, dtype='float32'))\n"
        "assert out.version == 'v1'\n"
        "rogue = sorted(m for m in sys.modules if m.startswith("
        "'sparkdl_tpu.cluster') or m == 'sparkdl_tpu.serving.cluster')\n"
        "assert not rogue, rogue\n"
        "print('CLEAN')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=_REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, timeout=240)
    out = proc.stdout.decode(errors="replace")
    assert proc.returncode == 0, out[-3000:]
    assert "CLEAN" in out


# ---------------------------------------------------------------------------
# Replication, routing, replica map
# ---------------------------------------------------------------------------


def test_cluster_predict_bit_identical_with_replica_map(rng):
    m = _model(1.0)
    # single-process ground truth first (separate stack, no cluster)
    reg0, srv0 = _stack()
    reg0.deploy("clf", "v1", model=m)
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    batch = rng.normal(size=(5,) + _ELEMENT).astype(np.float32)
    ref_row = np.asarray(srv0.predict("clf", row).output)
    ref_batch = np.asarray(srv0.predict("clf", batch).output)

    _arm(2)
    reg, srv = _stack()
    reg.deploy("clf", "v1", model=m)
    got = srv.predict("clf", row, deadline_ms=_DEADLINE_MS)
    assert got.version == "v1"
    np.testing.assert_array_equal(np.asarray(got.output), ref_row)
    got = srv.predict("clf", batch, deadline_ms=_DEADLINE_MS)
    np.testing.assert_array_equal(np.asarray(got.output), ref_batch)

    # satellite: status() carries the per-deployment replica map and
    # the exporter snapshot hook sees the same thing
    status = srv.status()["cluster"]
    assert status["clf"]["active"] == "v1"
    replicas = status["clf"]["replicas"]
    assert len(replicas) == 2
    for view in replicas.values():
        assert view["versions"] == ["v1"]
        assert set(view) == {"versions", "resident", "resident_bytes",
                             "inflight"}
    # locality: exactly one worker served (and is resident); the other
    # stayed cold — routing prefers the hot replica
    resident = [w for w, v in replicas.items() if v["resident"]]
    assert len(resident) == 1
    exported = telemetry.SnapshotExporter._serving_status()
    assert exported is not None and "clf" in exported


def test_merged_report_carries_serving_sections(rng):
    _arm(2)
    reg, srv = _stack()
    reg.deploy("clf", "v1", model=_model(1.0))
    for _ in range(3):
        srv.predict("clf", rng.normal(size=_ELEMENT).astype(np.float32),
                    deadline_ms=_DEADLINE_MS)
    router = _router()
    router.close()
    section = router.cluster_report["serving"]
    # worker-side fold: every replica's stats, predicts summed
    assert section["predicts"] == 3
    assert section["replicas"]["clf"]["v1"]  # model -> version -> workers
    # coordinator-side: the router block
    assert section["router"]["predicts"] == 3
    assert section["router"]["failovers"] == 0
    assert section["router"]["deployments"]["clf"]["active"] == "v1"


# ---------------------------------------------------------------------------
# Chaos: kill -9 one replica mid-stream
# ---------------------------------------------------------------------------


def test_kill_replica_mid_stream_loses_zero_requests(rng):
    """kill -9 one of 2 replicas while K threads stream predicts:
    every request either completes within its deadline via failover or
    fails classified (zero hangs, zero lost); exactly one
    ``serving_failover`` event per moved request; survivor responses
    bit-identical to the single-process run; zero leaked processes."""
    m = _model(1.0)
    reg0, srv0 = _stack()
    reg0.deploy("clf", "v1", model=m)
    rows = rng.normal(size=(18,) + _ELEMENT).astype(np.float32)
    refs = [np.asarray(srv0.predict("clf", r).output) for r in rows]

    _arm(2)
    reg, srv = _stack()
    reg.deploy("clf", "v1", model=m)
    # warm the routed replica so the kill hits a hot path, not a cold
    # load; request 0 doubles as the reference check for the warm path
    warm = srv.predict("clf", rows[0], deadline_ms=_DEADLINE_MS)
    np.testing.assert_array_equal(np.asarray(warm.output), refs[0])

    results = [None] * len(rows)
    errors = [None] * len(rows)
    start = threading.Barrier(4)

    def run(k: int, idxs):
        start.wait()
        for i in idxs:
            try:
                out = srv.predict("clf", rows[i],
                                  deadline_ms=_DEADLINE_MS)
                results[i] = np.asarray(out.output)
            # the chaos contract allows classified failure, never a
            # hang or an unclassified escape
            except Exception as e:  # noqa: BLE001 - classified below
                errors[i] = e

    idxs = list(range(1, len(rows)))
    lanes = [idxs[k::3] for k in range(3)]
    with HealthMonitor("chaos") as mon:
        with FaultInjector.seeded(
                0, serving_worker_kill=Fault(times=1, after=3)):
            threads = [threading.Thread(target=run, args=(k, lanes[k]),
                                        daemon=True)
                       for k in range(3)]
            for t in threads:
                t.start()
            start.wait()
            for t in threads:
                t.join(timeout=180)
            assert not any(t.is_alive() for t in threads), \
                "a predict hung past its deadline"
    # zero lost: every request either answered or failed classified
    for i in idxs:
        if errors[i] is not None:
            assert resilience.classify(errors[i]) in (
                resilience.RETRYABLE, resilience.FATAL)
            continue
        np.testing.assert_array_equal(results[i], refs[i])
    answered = sum(1 for i in idxs if results[i] is not None)
    assert answered >= len(idxs) - 1  # at most the killed dispatch fails
    # exactly-once: N moved requests <-> N serving_failover events,
    # each naming a distinct request id, and the router ledger agrees
    events = mon.events(health.SERVING_FAILOVER)
    assert events, "the injected kill moved no request"
    moved_ids = [e["request"] for e in events]
    assert len(moved_ids) == len(set(moved_ids))
    router = _router()
    router.close()
    section = router.cluster_report["serving"]["router"]
    assert section["failovers"] == len(events)
    assert sorted(section["moved_requests"]) == sorted(moved_ids)
    assert mon.count(health.CLUSTER_WORKER_LOST) == 1
    # zero leaked processes
    cluster_router.shutdown()
    deadline = time.monotonic() + 30
    while multiprocessing.active_children() and \
            time.monotonic() < deadline:
        time.sleep(0.05)
    assert multiprocessing.active_children() == []


def test_failover_exhausted_fails_classified_not_hung(rng):
    """With a single replica, a worker kill cannot fail over — the
    in-flight request must fail RETRYABLE (ServingReplicaLost), fast,
    classified, never hung."""
    _arm(1)
    reg, srv = _stack()
    reg.deploy("clf", "v1", model=_model(1.0))
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    srv.predict("clf", row, deadline_ms=_DEADLINE_MS)  # warm
    with HealthMonitor("solo") as mon:
        with FaultInjector.seeded(0, serving_worker_kill=1):
            with pytest.raises(resilience.ServingReplicaLost):
                srv.predict("clf", row, deadline_ms=_DEADLINE_MS)
    assert resilience.classify(
        resilience.ServingReplicaLost("x")) == resilience.RETRYABLE
    assert mon.count(health.SERVING_FAILOVER) == 0  # nothing MOVED


# ---------------------------------------------------------------------------
# Drain: stop admitting, finish in-flight (satellite 1)
# ---------------------------------------------------------------------------


def test_draining_worker_stops_admitting_but_finishes_inflight(rng):
    _arm(2)
    reg, srv = _stack()
    reg.deploy("clf", "v1", model=_model(1.0))
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    first = srv.predict("clf", row, deadline_ms=_DEADLINE_MS)
    router = _router()
    # SIGTERM the worker that just served (the hot replica): it must
    # drain — finish anything in flight, take no new predicts — while
    # the stream continues uninterrupted on the survivor
    replicas = srv.status()["cluster"]["clf"]["replicas"]
    hot_name = next(w for w, v in replicas.items() if v["resident"])
    with HealthMonitor("drain") as mon:
        hot = next(w for w in router._workers
                   if w.proc.name == hot_name and w.proc.is_alive())
        os.kill(hot.proc.pid, signal.SIGTERM)
        deadline = time.monotonic() + 60
        while hot.wid in router.serving_live_workers() \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert hot.wid not in router.serving_live_workers(), \
            "draining worker still admitting"
        for _ in range(6):
            out = srv.predict("clf", row, deadline_ms=_DEADLINE_MS)
            assert out.version == "v1"
        np.testing.assert_array_equal(np.asarray(out.output),
                                      np.asarray(first.output))
        # a drain is not a death: nothing moved, nothing failed over
        assert mon.count(health.SERVING_FAILOVER) == 0
        assert mon.count(health.CLUSTER_WORKER_LOST) == 0
        assert mon.count(health.CLUSTER_WORKER_DRAINING) == 1
        # the preemption drain spawns a replacement, and the spawn
        # top-up re-fans the deployment: the replica map regains its
        # replication factor without any operator action
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            live = router.serving_live_workers()
            status = srv.status()["cluster"]["clf"]["replicas"]
            if len(live) >= 2 and len(status) >= 2:
                break
            time.sleep(0.05)
        assert len(router.serving_live_workers()) >= 2


# ---------------------------------------------------------------------------
# Cluster-atomic hot swap
# ---------------------------------------------------------------------------


def test_cutover_is_cluster_atomic_no_version_mix(rng):
    """K threads stream predicts across a live cutover: for any two
    requests where one STARTED after the other COMPLETED, the later one
    must not observe the older version — the linearizability face of
    'no window where two callers get different versions'."""
    m1, m2 = _model(1.0), _model(2.0)
    _arm(2)
    reg, srv = _stack()
    reg.deploy("clf", "v1", model=m1)
    reg.deploy("clf", "v2", model=m2)  # dark until cut over
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    ref1 = _reference(m1, row[None])[0]
    ref2 = _reference(m2, row[None])[0]
    srv.predict("clf", row, deadline_ms=_DEADLINE_MS)  # warm v1

    log = []  # (t_start, t_end, version)
    log_lock = threading.Lock()
    stop = threading.Event()
    fail = []

    def stream():
        while not stop.is_set():
            t0 = time.monotonic()
            try:
                out = srv.predict("clf", row, deadline_ms=_DEADLINE_MS)
            # sparkdl: allow(broad-retry): not a retry — the worker thread records the failure for the main thread's assertion
            except Exception as e:  # noqa: BLE001 - surfaced below
                fail.append(e)
                return
            t1 = time.monotonic()
            want = ref1 if out.version == "v1" else ref2
            np.testing.assert_array_equal(np.asarray(out.output), want)
            with log_lock:
                log.append((t0, t1, out.version))

    threads = [threading.Thread(target=stream, daemon=True)
               for _ in range(3)]
    for t in threads:
        t.start()
    # let v1 traffic establish — bounded, and only while a stream lives: a
    # stream thread that died on its own assertion used to leave this loop
    # spinning for ever (it is what cut the tier-1 run at the clock)
    settle = time.monotonic() + 60
    while (len(log) < 6 and time.monotonic() < settle
           and any(t.is_alive() for t in threads)):
        time.sleep(0.01)
    if len(log) < 6:
        stop.set()
    assert len(log) >= 6, (len(log), fail)
    with HealthMonitor("swap") as mon:
        prev = srv.cutover("clf", "v2")
    assert prev == "v1"
    assert mon.count(health.SERVING_CUTOVER) == 1
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        with log_lock:
            if any(v == "v2" for _, _, v in log):
                break
        time.sleep(0.01)
    stop.set()
    for t in threads:
        t.join(timeout=120)
    assert not fail, fail
    versions = {v for _, _, v in log}
    assert versions == {"v1", "v2"}  # both sides of the swap observed
    # atomicity: no request started after a v2 completion may be v1
    with log_lock:
        snap = list(log)
    first_v2_end = min(t1 for _, t1, v in snap if v == "v2")
    stragglers = [v for t0, _, v in snap if t0 > first_v2_end]
    assert all(v == "v2" for v in stragglers), snap
    # and the caller-facing registry agrees with the router pointer
    assert reg.active_version("clf") == "v2"


def test_failed_prepare_rolls_back_v1_everywhere(rng):
    """One replica cannot load v2 (its loader raises there): prepare
    must fail, the cutover must roll back — v1 still active AND still
    answering on every replica, serving_prepare_failed recorded, and a
    later predict stream sees only v1."""
    _arm(2)
    reg, srv = _stack()
    m1 = _model(1.0)
    reg.deploy("clf", "v1", model=m1)

    def bad_loader():
        import multiprocessing as mp

        if mp.current_process().name.endswith("-1"):
            raise RuntimeError("v2 weights refuse to load here")
        rng2 = np.random.default_rng(7)
        w = jnp.asarray((rng2.normal(size=(_ELEMENT[0], _FEATURES)) * 2)
                        .astype(np.float32))
        return ModelFunction(lambda vs, x: jnp.tanh(x @ vs), w,
                             TensorSpec((None,) + _ELEMENT, "float32"),
                             name="served")

    reg.deploy("clf", "v2", loader=bad_loader)
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    srv.predict("clf", row, deadline_ms=_DEADLINE_MS)
    with HealthMonitor("prep") as mon:
        with pytest.raises(serving_cluster.CutoverFailed,
                           match="still serving everywhere"):
            srv.cutover("clf", "v2")
        assert mon.count(health.SERVING_PREPARE_FAILED) == 1
        assert mon.count(health.SERVING_CUTOVER) == 0  # nothing flipped
    assert reg.active_version("clf") == "v1"
    for _ in range(4):
        out = srv.predict("clf", row, deadline_ms=_DEADLINE_MS)
        assert out.version == "v1"
    np.testing.assert_array_equal(np.asarray(out.output),
                                  _reference(m1, row[None])[0])
    router = _router()
    router.close()
    section = router.cluster_report["serving"]["router"]
    assert section["prepare_failures"] == 1
    assert section["cutovers"] == 0
    assert section["deployments"]["clf"]["active"] == "v1"


def test_direct_registry_cutover_adopted_cluster_atomically(rng):
    """A bypassing ``registry.cutover`` call converges: the next
    predict notices the pointer mismatch and runs the SAME two-phase
    swap before serving the new version."""
    _arm(2)
    reg, srv = _stack()
    reg.deploy("clf", "v1", model=_model(1.0))
    reg.deploy("clf", "v2", model=_model(2.0))
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    assert srv.predict("clf", row,
                       deadline_ms=_DEADLINE_MS).version == "v1"
    reg.cutover("clf", "v2")  # direct, behind the router's back
    out = srv.predict("clf", row, deadline_ms=_DEADLINE_MS)
    assert out.version == "v2"
    np.testing.assert_array_equal(
        np.asarray(out.output), _reference(_model(2.0), row[None])[0])


def test_rollback_is_cluster_atomic(rng):
    _arm(2)
    reg, srv = _stack()
    reg.deploy("clf", "v1", model=_model(1.0))
    reg.deploy("clf", "v2", model=_model(2.0))
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    srv.predict("clf", row, deadline_ms=_DEADLINE_MS)
    assert srv.cutover("clf", "v2") == "v1"
    assert srv.predict("clf", row,
                       deadline_ms=_DEADLINE_MS).version == "v2"
    assert srv.rollback("clf") == "v2"
    out = srv.predict("clf", row, deadline_ms=_DEADLINE_MS)
    assert out.version == "v1"
    assert reg.active_version("clf") == "v1"


# ---------------------------------------------------------------------------
# AOT bucket-ladder warmup across the cluster (ISSUE 20)
# ---------------------------------------------------------------------------


def test_cluster_prepare_warms_ladder_on_every_replica(rng):
    """srv_prepare materializes through the warmup-wrapped loader: by
    the time a cutover commits, EVERY replica has paid the incoming
    version's full bucket ladder — one warmup_completed per (replica,
    version) cold load, federated into the merged cluster report."""
    EngineConfig.serving_warmup = True  # BEFORE the router spawns:
    # workers inherit EngineConfig at boot
    _arm(2)
    reg, srv = _stack()
    m1 = _model(1.0)
    reg.deploy("clf", "v1", model=m1, batch_size=8)
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    # first predict: router spawns, ONE replica cold-loads (and warms) v1
    out = srv.predict("clf", row, deadline_ms=_DEADLINE_MS)
    assert out.version == "v1"

    def v2_loader():
        rng2 = np.random.default_rng(7)
        w = jnp.asarray((rng2.normal(size=(_ELEMENT[0], _FEATURES)) * 2)
                        .astype(np.float32))
        return ModelFunction(lambda vs, x: jnp.tanh(x @ vs), w,
                             TensorSpec((None,) + _ELEMENT, "float32"),
                             name="served")

    reg.deploy("clf", "v2", loader=v2_loader, batch_size=8)
    srv.cutover("clf", "v2")  # two-phase: prepare warms BOTH replicas
    out = srv.predict("clf", row, deadline_ms=_DEADLINE_MS)
    assert out.version == "v2"
    np.testing.assert_array_equal(np.asarray(out.output),
                                  _reference(v2_loader(), row[None])[0])

    router = _router()
    router.close()
    rep = router.cluster_report
    per_worker = {
        name: snap["health"]["counters"].get(health.WARMUP_COMPLETED, 0)
        for name, snap in rep["workers"].items()}
    assert len(per_worker) == 2
    # v2 prepared (= warmed) on BOTH replicas before the commit; v1
    # warmed only on the replica that served the first request
    assert all(count >= 1 for count in per_worker.values()), per_worker
    assert sum(per_worker.values()) == 3, per_worker
    assert rep["health"]["counters"][health.WARMUP_COMPLETED] == 3
    assert rep["health_consistent"]


def test_cluster_failed_warmup_nacks_prepare_and_rolls_back(rng):
    """The warmup gate has teeth: v2's loader succeeds on every
    replica, but its ladder cannot execute — with serving_warmup armed
    the cold load fails DURING warmup, the prepare nacks, and the
    cutover rolls back with v1 still serving everywhere. Without the
    gate this exact deployment would have prepared fine and detonated
    on the first live request."""
    EngineConfig.serving_warmup = True
    _arm(2)
    reg, srv = _stack()
    m1 = _model(1.0)
    reg.deploy("clf", "v1", model=m1, batch_size=8)

    def dud_loader():
        def _explode(vs, x):
            raise RuntimeError("v2 cannot execute its ladder")

        return ModelFunction(_explode, jnp.zeros((1,), jnp.float32),
                             TensorSpec((None,) + _ELEMENT, "float32"),
                             name="served")

    reg.deploy("clf", "v2", loader=dud_loader, batch_size=8)
    row = rng.normal(size=_ELEMENT).astype(np.float32)
    srv.predict("clf", row, deadline_ms=_DEADLINE_MS)
    with HealthMonitor("warm-prep") as mon:
        with pytest.raises(serving_cluster.CutoverFailed,
                           match="still serving everywhere"):
            srv.cutover("clf", "v2")
        assert mon.count(health.SERVING_PREPARE_FAILED) >= 1
        assert mon.count(health.SERVING_CUTOVER) == 0
    assert reg.active_version("clf") == "v1"
    out = srv.predict("clf", row, deadline_ms=_DEADLINE_MS)
    assert out.version == "v1"
    np.testing.assert_array_equal(np.asarray(out.output),
                                  _reference(m1, row[None])[0])
    router = _router()
    router.close()
    section = router.cluster_report["serving"]["router"]
    assert section["cutovers"] == 0
    assert section["prepare_failures"] >= 1
    assert section["deployments"]["clf"]["active"] == "v1"
