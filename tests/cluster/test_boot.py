"""Cluster boot: a plane that cannot come up fails AT ONCE and says why.

On a TPU backend the chip belongs to one process, and the cluster plane
gives no worker a chip of its own yet — so arming ``cluster_workers``
there must raise one clear, FATAL-classified error from the call that
armed it, within the bounded boot wait, leaving no child process: never a
hang on the first task, a retry loop or a respawn storm. Simulated on the
CPU by pinning the workers to a platform that does not exist.
"""

import multiprocessing
import os
import subprocess
import sys
import time

import pytest

import _boot_workers
from sparkdl_tpu.cluster import router as cluster_router
from sparkdl_tpu.core import resilience
from sparkdl_tpu.engine import DataFrame, EngineConfig

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _restore_engine_config():
    saved = EngineConfig.snapshot()
    yield
    EngineConfig.restore(saved)
    cluster_router.shutdown()


def _no_children():
    # active_children() reaps as it lists; anything still listed is alive
    return multiprocessing.active_children() == []


def test_worker_boot_failure_is_one_fatal_error_and_leaves_no_child(
        monkeypatch):
    monkeypatch.setattr(cluster_router, "_configured_platform",
                        lambda: ("no_such_platform", False))
    monkeypatch.setattr(cluster_router, "_BOOT_WAIT_S", 100.0)
    EngineConfig.cluster_workers = 2
    frame = DataFrame.fromRows([{"x": i} for i in range(8)],
                               numPartitions=2).withColumn(
        "y", lambda x: x + 1, inputCols=["x"])
    t0 = time.monotonic()
    with pytest.raises(RuntimeError) as err:
        frame.collect()  # the call that arms the cluster
    assert time.monotonic() - t0 < 100.0  # an answer, not the timeout
    assert resilience.classify(err.value) == resilience.FATAL
    text = str(err.value)
    assert "cluster_workers=2 failed to boot" in text
    assert "could not bring up its JAX backend" in text
    assert "no_such_platform" in text
    assert _no_children()
    assert cluster_router._router is None  # nothing half-armed survives


def test_coordinator_holding_the_tpu_is_named_before_any_spawn(monkeypatch):
    """Where the coordinator already holds the chip, the error says
    exactly that — and no process is started at all."""
    monkeypatch.setattr(cluster_router, "_configured_platform",
                        lambda: ("tpu", True))

    def no_spawn(self, index):
        raise AssertionError("a worker was spawned")

    monkeypatch.setattr(cluster_router.ClusterRouter, "_spawn", no_spawn)
    with pytest.raises(RuntimeError) as err:
        cluster_router.ClusterRouter(workers=2)
    assert resilience.classify(err.value) == resilience.FATAL
    text = str(err.value)
    assert "already initialised JAX and holds the TPU" in text
    assert "one process at a time" in text
    assert _no_children()


def test_worker_dying_before_its_boot_outcome_fails_the_boot(monkeypatch):
    # time.sleep(index, queue, ...) is a TypeError: the child exits at
    # once without a word, like a runtime that aborts on a held device
    monkeypatch.setattr(cluster_router._worker_mod, "_worker_main",
                        time.sleep)
    monkeypatch.setattr(cluster_router, "_BOOT_WAIT_S", 100.0)
    with pytest.raises(RuntimeError, match="died during boot") as err:
        cluster_router.ClusterRouter(workers=1)
    assert resilience.classify(err.value) == resilience.FATAL
    assert _no_children()


def test_silent_worker_is_reaped_at_the_boot_wait(monkeypatch):
    monkeypatch.setattr(cluster_router._worker_mod, "_worker_main",
                        _boot_workers.silent)
    monkeypatch.setattr(cluster_router, "_BOOT_WAIT_S", 3.0)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError,
                       match="reported no backend within 3s") as err:
        cluster_router.ClusterRouter(workers=2)
    assert time.monotonic() - t0 < 3.0 + 2 * cluster_router._JOIN_TIMEOUT_S
    assert resilience.classify(err.value) == resilience.FATAL
    assert _no_children()


def test_workers_landing_on_different_platforms_fail_the_boot(monkeypatch):
    """No platform configured + one chip: the worker that cannot have it
    falls back to the CPU without an error. The router must not serve
    from such a set."""
    monkeypatch.setattr(cluster_router, "_configured_platform",
                        lambda: (None, False))
    monkeypatch.setattr(cluster_router._worker_mod, "_worker_main",
                        _boot_workers.split_landing)
    monkeypatch.setattr(cluster_router, "_BOOT_WAIT_S", 100.0)
    with pytest.raises(RuntimeError,
                       match="landed on different platforms") as err:
        cluster_router.ClusterRouter(workers=2)
    assert resilience.classify(err.value) == resilience.FATAL
    assert "one process at a time" in str(err.value)
    assert _no_children()


def test_router_leaves_the_coordinator_backend_uninitialised(tmp_path):
    """The router must not be the thing that takes the device: on a cold
    coordinator it reads the configured platform, spawns, waits for the
    boot outcomes and closes — and JAX's backend is still down."""
    script = (
        "import multiprocessing\n"
        "from jax._src import xla_bridge\n"
        "from sparkdl_tpu.cluster import router\n"
        "if __name__ == '__main__':\n"
        "    assert router._configured_platform() == ('cpu', False)\n"
        "    r = router.ClusterRouter(workers=1)\n"
        "    assert all(w.booted for w in r._workers)\n"
        "    r.close()\n"
        "    assert not xla_bridge.backends_are_initialized()\n"
        "    assert multiprocessing.active_children() == []\n"
        "    print('COLD')\n")
    # a script FILE: spawned workers re-import __main__
    path = tmp_path / "cold_router.py"
    path.write_text(script)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
    proc = subprocess.run([sys.executable, str(path)], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "COLD" in proc.stdout
