"""Multi-process decode pool (ISSUE 9 tentpole): ordering, crash
respawn + classified retry, tolerant parity with the inline path, clean
shutdown, and the workers=0 inline default."""

import io
import os
import threading
import time

import numpy as np
import pytest
from PIL import Image

from sparkdl_tpu.core import decode_pool, health, resilience, telemetry
from sparkdl_tpu.core.decode_pool import DecodePool
from sparkdl_tpu.core.health import HealthMonitor
from sparkdl_tpu.core.resilience import Fault, FaultInjector
from sparkdl_tpu.core.telemetry import Telemetry
from sparkdl_tpu.engine.dataframe import EngineConfig
from sparkdl_tpu.image import imageIO


@pytest.fixture(autouse=True)
def _restore_engine_config_and_pool():
    saved = EngineConfig.snapshot()
    yield
    EngineConfig.restore(saved)
    decode_pool.shutdown()


def _jpeg(rng, h=16, w=16):
    buf = io.BytesIO()
    Image.fromarray(rng.integers(0, 255, (h, w, 3), dtype=np.uint8)
                    ).save(buf, format="JPEG", quality=90)
    return buf.getvalue()


def _blobs(n=24, corrupt=(), none=()):
    rng = np.random.default_rng(0)
    out = []
    for i in range(n):
        if i in none:
            out.append(None)
        elif i in corrupt:
            out.append(b"definitely not an image")
        else:
            # sizes vary so per-blob decode times are unequal and chunks
            # finish out of order across workers
            out.append(_jpeg(rng, h=8 + 8 * (i % 7), w=8 + 4 * (i % 5)))
    return out


def test_order_preserved_under_unequal_decode_times():
    """Every output index must hold ITS blob's pixels even though blob
    sizes (and so decode times) vary and two workers race."""
    blobs = _blobs(40)
    inline = imageIO._decodeValidBlobs(blobs, (12, 12), 3)
    with DecodePool(workers=2) as pool:
        for _ in range(3):  # repeated fan-outs, same order every time
            got = pool.decode(blobs, target_size=(12, 12), channels=3)
            assert len(got) == len(blobs)
            for i, want in enumerate(inline):
                np.testing.assert_array_equal(got[i], want)


def test_flexible_decode_preserves_source_geometry():
    """No target size / channels (the readImages default-decoder
    contract): each blob keeps its own HxW, identical to the inline
    decoder."""
    blobs = _blobs(10)
    with DecodePool(workers=2) as pool:
        got = pool.decode(blobs)
    for blob, arr in zip(blobs, got):
        want = imageIO.decodePoolBlob(blob)
        np.testing.assert_array_equal(arr, want)
    # geometry genuinely varies (the test would be vacuous otherwise)
    assert len({a.shape for a in got}) > 1


def test_worker_crash_respawns_and_recovers():
    """One injected worker crash: the pool respawns the worker,
    re-dispatches exactly the lost chunk, returns the full correct
    result, and records one decode_pool_respawn health event."""
    blobs = _blobs(12)
    with DecodePool(workers=2) as pool:
        baseline = pool.decode(blobs, target_size=(8, 8), channels=3)
        with FaultInjector.seeded(0, decode_pool_worker_crash=1) as inj, \
                HealthMonitor() as mon:
            got = pool.decode(blobs, target_size=(8, 8), channels=3)
        assert inj.fired["decode_pool_worker_crash"] == 1
        assert mon.count(health.DECODE_POOL_RESPAWN) == 1
        assert pool.respawns == 1
        for a, b in zip(got, baseline):
            np.testing.assert_array_equal(a, b)
        # the pool healed: full worker complement alive, next call clean
        assert all(w.proc.is_alive() for w in pool._workers)
        got2 = pool.decode(blobs, target_size=(8, 8), channels=3)
        for a, b in zip(got2, baseline):
            np.testing.assert_array_equal(a, b)


def test_worker_crash_exhaustion_is_classified_retryable():
    """A persistently-crashing worker exhausts the chunk's resubmission
    budget and fails with DecodeWorkerLost — classified RETRYABLE, so
    the engine's task retry (not a blind loop) owns the replay. The
    pool itself stays usable afterwards."""
    blobs = _blobs(4)
    with DecodePool(workers=1) as pool:
        baseline = pool.decode(blobs, target_size=(8, 8), channels=3)
        with FaultInjector.seeded(
                0, decode_pool_worker_crash=Fault(times=-1)):
            with pytest.raises(resilience.DecodeWorkerLost) as ei:
                pool.decode(blobs, target_size=(8, 8), channels=3)
        assert resilience.classify(ei.value) == resilience.RETRYABLE
        # injector disarmed: the pool recovered and serves again
        got = pool.decode(blobs, target_size=(8, 8), channels=3)
        for a, b in zip(got, baseline):
            np.testing.assert_array_equal(a, b)


def test_worker_side_error_propagates_typed_like_inline():
    """An exception the INLINE decoder would raise (unsupported channel
    count) must re-raise at the submitting call site with its builtin
    type intact — classified FATAL, never silently degraded to null
    rows."""
    blobs = _blobs(4)
    with pytest.raises(ValueError):
        for b in blobs:  # the inline path raises on channels=2
            imageIO.decodePoolBlob(b, channels=2)
    with DecodePool(workers=1) as pool:
        with pytest.raises(ValueError) as ei:
            pool.decode(blobs, channels=2)
    assert resilience.classify(ei.value) == resilience.FATAL
    # and the pool stays healthy for the next (valid) call
    # — verified by close() not hanging (ctx manager above)


def test_tolerant_corrupt_blob_parity_pool_on_off():
    """decodeImageBytesBatch through the pool vs inline: identical rows
    (corrupt blobs degrade to the same Nones) and EQUAL decode_degraded
    health counters — exactly one event stream, owned by the submitting
    process."""
    blobs = _blobs(18, corrupt={3, 11}, none={7})
    EngineConfig.decode_workers = 2
    with HealthMonitor() as mon_on:
        on = imageIO.decodeImageBytesBatch(blobs, (10, 10))
    EngineConfig.decode_workers = 0
    with HealthMonitor() as mon_off:
        off = imageIO.decodeImageBytesBatch(blobs, (10, 10))
    assert mon_on.count(health.DECODE_DEGRADED) \
        == mon_off.count(health.DECODE_DEGRADED) == 2
    for i, (a, b) in enumerate(zip(on, off)):
        if b is None:
            assert a is None, i
        else:
            np.testing.assert_array_equal(a, b)
    assert on[3] is None and on[11] is None and on[7] is None


def test_injected_decode_error_parity_pool_on_off():
    """The decode_error fault fires in the SUBMITTING process on both
    paths: same degraded row, same single injected decode_degraded
    event."""
    blobs = _blobs(6)

    def run(workers):
        EngineConfig.decode_workers = workers
        with FaultInjector.seeded(0, decode_error=1) as inj, \
                HealthMonitor() as mon:
            out = imageIO.decodeImageBytesBatch(blobs, (8, 8))
        assert inj.fired["decode_error"] == 1
        return out, mon.count(health.DECODE_DEGRADED)

    on, degraded_on = run(2)
    decode_pool.shutdown()
    off, degraded_off = run(0)
    assert degraded_on == degraded_off == 1
    assert on[0] is None and off[0] is None
    for a, b in zip(on[1:], off[1:]):
        np.testing.assert_array_equal(a, b)


def test_close_midstream_leaks_no_processes_or_segments():
    """close() while decodes are in flight: the waiter fails with a
    RETRYABLE DecodeWorkerLost (never hangs), every worker process is
    joined, and no shared-memory segment survives."""
    before = set(os.listdir("/dev/shm")) if os.path.isdir("/dev/shm") \
        else set()
    blobs = _blobs(64, corrupt={5})
    pool = DecodePool(workers=2)
    errors = []
    done = threading.Event()

    def hammer():
        try:
            while not done.is_set():
                pool.decode(blobs, target_size=(32, 32), channels=3)
        except Exception as e:  # noqa: BLE001 - asserted below
            errors.append(e)
        finally:
            done.set()

    t = threading.Thread(target=hammer, name="test-decode-hammer")
    t.start()
    time.sleep(0.3)  # let decodes be genuinely in flight
    pool.close()
    done.set()
    t.join(timeout=20.0)
    assert not t.is_alive()
    if errors:  # the hammer was mid-call at close: must be classified
        assert isinstance(errors[0], resilience.DecodeWorkerLost)
        assert resilience.classify(errors[0]) == resilience.RETRYABLE
    assert all(not w.proc.is_alive() for w in pool._workers)
    assert pool._pending == {}
    pool.close()  # idempotent
    if os.path.isdir("/dev/shm"):
        leaked = {n for n in set(os.listdir("/dev/shm")) - before
                  if n.startswith("psm_")}
        assert not leaked, leaked


def test_workers_zero_is_inline_and_poolless():
    """The default keeps today's behavior bit-identically: no pool is
    ever constructed and the inline decoder serves the call."""
    assert EngineConfig.decode_workers == 0
    assert decode_pool.maybe_pool() is None
    blobs = _blobs(8, corrupt={2})
    out = imageIO.decodeImageBytesBatch(blobs, (8, 8))
    want = imageIO._decodeValidBlobs([b for b in blobs if b], (8, 8), 3)
    live = [a for i, a in enumerate(out) if blobs[i]]
    for a, b in zip(live, want):
        if b is None:
            assert a is None
        else:
            np.testing.assert_array_equal(a, b)
    assert decode_pool._pool is None


def test_maybe_pool_lifecycle_follows_the_knobs():
    """maybe_pool builds one process-wide pool per knob setting,
    rebuilds on reconfiguration, and validates the knobs."""
    EngineConfig.decode_workers = 1
    pool = decode_pool.maybe_pool()
    assert pool is not None and pool.workers == 1
    assert decode_pool.maybe_pool() is pool  # cached
    EngineConfig.decode_workers = 2
    EngineConfig.decode_pool_inflight = 3
    pool2 = decode_pool.maybe_pool()
    assert pool2 is not pool and pool.closed
    assert pool2.workers == 2 and pool2.inflight == 3
    decode_pool.shutdown()
    assert pool2.closed
    EngineConfig.decode_workers = -1
    with pytest.raises(ValueError, match="decode_workers"):
        decode_pool.maybe_pool()
    EngineConfig.decode_workers = 1
    EngineConfig.decode_pool_inflight = 0
    with pytest.raises(ValueError, match="decode_pool_inflight"):
        decode_pool.maybe_pool()


def test_read_images_pool_parity_and_telemetry(tmp_path):
    """The readImages ingest path end to end: pool on == pool off rows
    (including a corrupt file's null struct), equal health counters, and
    the pool's span + per-blob latency histogram + gauges land in the
    telemetry scope."""
    rng = np.random.default_rng(1)
    for i in range(9):
        Image.fromarray(rng.integers(0, 255, (12 + i, 14, 3),
                                     dtype=np.uint8)
                        ).save(tmp_path / f"img_{i}.png")
    (tmp_path / "bad.jpg").write_bytes(b"corrupt")

    with HealthMonitor() as mon_off:
        rows_off = imageIO.readImages(str(tmp_path), numPartition=3).collect()
    EngineConfig.decode_workers = 2
    with HealthMonitor() as mon_on, Telemetry("decode-pool-test") as tel:
        rows_on = imageIO.readImages(str(tmp_path), numPartition=3).collect()
    assert rows_on == rows_off
    assert mon_on.count(health.DECODE_DEGRADED) \
        == mon_off.count(health.DECODE_DEGRADED) == 1
    snap = tel.metrics.snapshot()
    assert snap["histograms"][telemetry.M_DECODE_POOL_DECODE_S]["count"] > 0
    assert telemetry.M_DECODE_POOL_DEPTH in snap["gauges"]
    assert telemetry.M_DECODE_POOL_BUSY in snap["gauges"]
    spans = tel.tracer.spans(telemetry.SPAN_DECODE_POOL)
    assert spans  # one fan-out span per pooled decode call
    # the span parents under the partition task that submitted it
    ids = {s["span_id"] for s in tel.tracer.spans()}
    assert all(s["parent_id"] in ids for s in spans)


def test_sweep_reclaims_dead_owner_segments_only():
    """A kill -9'd owner's run-scoped segments (name embeds the owner
    pid) are reclaimed by the next pool's startup sweep; a live owner's
    segments are untouched."""
    import subprocess
    import sys
    from multiprocessing import resource_tracker, shared_memory

    if not os.path.isdir(decode_pool._SHM_DIR):
        pytest.skip("no /dev/shm on this platform")
    # a pid that is certainly dead: a just-reaped child
    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    proc.wait()
    dead_pid = proc.pid

    def make(owner_pid, seq):
        seg = shared_memory.SharedMemory(
            name=f"{decode_pool._SHM_PREFIX}_{owner_pid:x}_{owner_pid:x}"
                 f"_{seq:x}", create=True, size=64)
        # the test plays the worker's role: hand ownership to the shm
        # file itself so this process's tracker doesn't unlink/warn
        resource_tracker.unregister(seg._name, "shared_memory")
        seg.close()
        return seg.name

    dead_name = make(dead_pid, 1)
    live_name = make(os.getpid(), 2)
    try:
        with HealthMonitor() as mon:
            swept = decode_pool.sweep_orphaned_segments()
        assert swept >= 1
        listing = set(os.listdir(decode_pool._SHM_DIR))
        assert dead_name not in listing
        assert live_name in listing
        assert mon.events(health.DECODE_POOL_SHM_SWEPT)
    finally:
        try:
            os.unlink(os.path.join(decode_pool._SHM_DIR, live_name))
        except OSError:
            pass


def test_pool_startup_runs_orphan_sweep():
    """DecodePool() itself sweeps before spawning — the kill -9 resume
    path reclaims the dead run's segments with zero operator action."""
    import subprocess
    import sys
    from multiprocessing import resource_tracker, shared_memory

    if not os.path.isdir(decode_pool._SHM_DIR):
        pytest.skip("no /dev/shm on this platform")
    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    proc.wait()
    seg = shared_memory.SharedMemory(
        name=f"{decode_pool._SHM_PREFIX}_{proc.pid:x}_{proc.pid:x}_9",
        create=True, size=64)
    resource_tracker.unregister(seg._name, "shared_memory")
    seg.close()
    pool = DecodePool(workers=1)
    try:
        assert seg.name not in set(os.listdir(decode_pool._SHM_DIR))
    finally:
        pool.close()


def test_decode_chunk_spans_adopt_under_the_pool_span():
    """ISSUE 15: with a telemetry scope active, every chunk a worker
    decodes comes back with a ``sparkdl.decode_chunk`` span measured
    IN the worker (origin pid preserved) and adopted under the
    coordinator's ``sparkdl.decode_pool`` span."""
    blobs = _blobs(12)
    with Telemetry("decode-trace") as tel, DecodePool(workers=2) as pool:
        got = pool.decode(blobs, target_size=(8, 8), channels=3)
    assert len(got) == len(blobs)
    (pool_span,) = tel.tracer.spans(telemetry.SPAN_DECODE_POOL)
    chunks = tel.tracer.spans(telemetry.SPAN_DECODE_CHUNK)
    assert chunks  # the fan-out produced at least one chunk
    worker_pids = set()
    for s in chunks:
        assert s["parent_id"] == pool_span["span_id"]
        assert s["trace_id"] == tel.run_id
        assert s["pid"] != os.getpid()    # measured in the worker
        assert s["process"] == f"decode-{s['pid']}"
        assert s["end_ns"] >= s["start_ns"]
        worker_pids.add(s["pid"])
    assert sum(s["attributes"]["blobs"] for s in chunks) == len(blobs)
    assert tel.tracer.summary()["remote_adopted"] == len(chunks)


def test_decode_without_scope_ships_no_spans():
    """Tracing off (no scope): the task tuple carries ctx=None, workers
    build no wire records, and a LATER scope sees nothing adopted —
    the off path stays observability-free end to end."""
    blobs = _blobs(6)
    with DecodePool(workers=1) as pool:
        pool.decode(blobs, target_size=(8, 8), channels=3)
        with Telemetry("later") as tel:
            pass
    assert tel.tracer.spans(telemetry.SPAN_DECODE_CHUNK) == []
    assert tel.tracer.summary()["remote_adopted"] == 0


def test_workers_never_load_jax():
    """A TPU chip belongs to one process: a decode worker that imported
    JAX would try to take it from the parent that holds it. After real
    work, no worker has a jaxlib (or TPU) library mapped."""
    with DecodePool(workers=2) as pool:
        pool.decode(_blobs(8), target_size=(12, 12), channels=3)
        for worker in pool._workers:
            with open(f"/proc/{worker.proc.pid}/maps") as f:
                mapped = f.read()
            assert "numpy" in mapped  # the probe sees this process's libs
            assert "jaxlib" not in mapped and "libtpu" not in mapped
