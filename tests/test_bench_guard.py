"""bench.py is a measuring path: it refuses anything but a known TPU.

A CPU run must never print under a device metric's name, and a device whose
published peaks are not in the table is an error, not a default.
"""

import json

import jax
import pytest

import bench


class _Device:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.fixture(autouse=True)
def _fresh_device_cache(monkeypatch):
    monkeypatch.setattr(bench, "_DEVICE", None)


def test_refuses_the_cpu():
    with pytest.raises(RuntimeError, match="refuses platform 'cpu'"):
        bench.require_tpu()
    with pytest.raises(RuntimeError, match="refuses platform 'cpu'"):
        bench.emit("images/sec/chip (anything)", 1.0, "images/sec/chip")


def test_refuses_a_device_kind_without_published_peaks(monkeypatch):
    monkeypatch.setattr(jax, "devices",
                        lambda: [_Device("tpu", "TPU v99 imaginary")])
    with pytest.raises(RuntimeError, match="no published peaks"):
        bench.require_tpu()


def test_records_carry_the_device_and_peaks_come_from_the_table(
        monkeypatch, capsys):
    monkeypatch.setattr(jax, "devices",
                        lambda: [_Device("tpu", "TPU v5 lite")] * 4)
    monkeypatch.setattr(bench, "_PRIOR", {})
    assert bench.peak_tflops_bf16() == 197.0
    assert bench.require_tpu()["peaks"]["hbm_gbps"] == 819.0
    rec = bench.emit("a metric", 2.0, "images/sec")
    assert (rec["platform"], rec["device_kind"], rec["device_count"]) \
        == ("tpu", "TPU v5 lite", 4)
    assert json.loads(capsys.readouterr().out) == rec


def test_main_starts_no_chip_needing_child():
    """The five cluster-plane legs spawn workers that each need a backend;
    one process holds the chip, so main() must not call them."""
    import ast
    import inspect

    called = {node.func.id for node in ast.walk(
        ast.parse(inspect.getsource(bench.main)))
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name)}
    assert "bench_device_featurize" in called  # the walk sees the legs
    assert not called & {
        "bench_serving_failover", "bench_cluster_featurize",
        "bench_tracing_overhead", "bench_federation_overhead",
        "bench_autoscale"}
