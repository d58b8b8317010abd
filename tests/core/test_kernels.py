"""Fused Pallas kernel plane (core/kernels.py, ISSUE 20).

Covers the accept-if-faster machinery end to end on CPU: verdict
persistence (round-trip, corrupt/stale discard, backend partitioning),
the numeric contract of every fused kernel against its XLA twin
(interpreter mode), route gating across all three
``EngineConfig.pallas_kernels`` modes, the CPU autotune path (clean
rejections, byte-identical program), and the subprocess pin that the
``"off"`` mode never even imports this module.
"""

import json
import os
import subprocess
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu import COMPILE_CACHE_DIR_ENV
from sparkdl_tpu.core import kernels
from sparkdl_tpu.core.model_function import ModelFunction, TensorSpec
from sparkdl_tpu.engine.dataframe import EngineConfig
from sparkdl_tpu.models.layers import ConvBN, SeparableConvBN

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _kernel_stack(monkeypatch):
    """Engine knobs + verdict map + INTERPRET flag isolation. The cache
    dir env is cleared so verdicts stay in-process unless a test opts
    into persistence with its own tmp_path."""
    saved = EngineConfig.snapshot()
    saved_interpret = kernels.INTERPRET
    monkeypatch.delenv(COMPILE_CACHE_DIR_ENV, raising=False)
    kernels.reset()
    yield
    kernels.INTERPRET = saved_interpret
    kernels.reset()
    EngineConfig.restore(saved)


def _site():
    return kernels.Site("pw1x1", "unit", (2, 4, 4, 8, 8), "float32")


def _inject(site, adopted):
    """Drop a settled verdict into the in-memory map (what a completed
    shootout would leave behind) without running device work."""
    with kernels._verdict_lock:
        kernels._verdicts[kernels._site_key(site)] = {
            "adopted": adopted, "reason": "injected"}


# ---------------------------------------------------------------------------
# Verdict store: round-trip, corruption, version skew, partitioning
# ---------------------------------------------------------------------------


def test_verdict_store_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv(COMPILE_CACHE_DIR_ENV, str(tmp_path))
    site = _site()
    kernels._persist_verdict(kernels._site_key(site),
                             {"adopted": True, "reason": "unit"})
    kernels.reset()  # wipe in-memory: the next lookup must hit the file
    got = kernels.verdict_for(site)
    assert got is not None and got["adopted"] is True
    doc = json.loads(
        (tmp_path / kernels._VERDICT_STORE_BASENAME).read_text())
    assert doc["version"] == kernels.VERDICT_STORE_VERSION
    assert kernels._site_key(site) in doc["verdicts"]


def test_verdict_store_merges_entries(tmp_path, monkeypatch):
    monkeypatch.setenv(COMPILE_CACHE_DIR_ENV, str(tmp_path))
    s1 = _site()
    s2 = kernels.Site("sep2d", "unit", (2, 6, 6, 8, 8), "float32")
    kernels._persist_verdict(kernels._site_key(s1),
                             {"adopted": False, "reason": "slow"})
    kernels._persist_verdict(kernels._site_key(s2),
                             {"adopted": True, "reason": "fast"})
    kernels.reset()
    assert kernels.verdict_for(s1)["adopted"] is False
    assert kernels.verdict_for(s2)["adopted"] is True


def test_verdict_store_corrupt_file_discarded(tmp_path, monkeypatch):
    monkeypatch.setenv(COMPILE_CACHE_DIR_ENV, str(tmp_path))
    path = tmp_path / kernels._VERDICT_STORE_BASENAME
    path.write_text("{definitely not json")
    kernels.reset()
    assert kernels.verdict_for(_site()) is None
    # a later persist rewrites a valid store over the wreckage
    kernels._persist_verdict(kernels._site_key(_site()),
                             {"adopted": False, "reason": "fresh"})
    kernels.reset()
    assert kernels.verdict_for(_site())["adopted"] is False
    assert json.loads(path.read_text())["version"] \
        == kernels.VERDICT_STORE_VERSION


def test_verdict_store_stale_version_discarded(tmp_path, monkeypatch):
    monkeypatch.setenv(COMPILE_CACHE_DIR_ENV, str(tmp_path))
    key = kernels._site_key(_site())
    (tmp_path / kernels._VERDICT_STORE_BASENAME).write_text(json.dumps(
        {"version": kernels.VERDICT_STORE_VERSION + 1,
         "verdicts": {key: {"adopted": True, "reason": "old format"}}}))
    kernels.reset()
    assert kernels.verdict_for(_site()) is None


def test_verdict_store_malformed_entries_discarded(tmp_path, monkeypatch):
    monkeypatch.setenv(COMPILE_CACHE_DIR_ENV, str(tmp_path))
    good, bad = _site(), kernels.Site("pw1x1", "bad", (1, 4, 4, 8, 8),
                                      "float32")
    (tmp_path / kernels._VERDICT_STORE_BASENAME).write_text(json.dumps(
        {"version": kernels.VERDICT_STORE_VERSION,
         "verdicts": {
             kernels._site_key(good): {"adopted": True, "reason": "ok"},
             kernels._site_key(bad): {"adopted": "yes"},  # not a bool
         }}))
    kernels.reset()
    assert kernels.verdict_for(good)["adopted"] is True
    assert kernels.verdict_for(bad) is None


def test_verdicts_stay_in_process_without_cache_dir(tmp_path):
    assert kernels.verdict_store_path() is None
    kernels._persist_verdict(kernels._site_key(_site()),
                             {"adopted": True, "reason": "unpersisted"})
    kernels.reset()
    assert kernels.verdict_for(_site()) is None
    assert list(tmp_path.iterdir()) == []


def test_backend_tag_partitions_verdicts(tmp_path, monkeypatch):
    """Interpreter verdicts must never answer for real hardware (and
    vice versa): the backend is part of the site key."""
    monkeypatch.setenv(COMPILE_CACHE_DIR_ENV, str(tmp_path))
    site = _site()
    kernels._persist_verdict(kernels._site_key(site),
                             {"adopted": True, "reason": "hw"})
    kernels.reset()
    assert kernels.verdict_for(site)["adopted"] is True
    kernels.INTERPRET = True
    assert kernels.verdict_for(site) is None


def test_failed_audition_is_visible_and_never_persisted(
        tmp_path, monkeypatch, caplog):
    """An audition that RAISES where Mosaic lowers (interpret mode stands
    in for the TPU) is not a lost shoot-out: the verdict carries
    ``error``, a WARNING is logged, its own counter moves, and the store
    never sees it — so the next process auditions the repaired kernel."""
    from sparkdl_tpu.core import telemetry

    monkeypatch.setenv(COMPILE_CACHE_DIR_ENV, str(tmp_path))
    kernels.INTERPRET = True

    def broken(site):
        raise NotImplementedError("Unsupported cast: uint8 -> float32")

    monkeypatch.setattr(kernels, "_build_shootout", broken)
    site = _site()
    with telemetry.Telemetry(out_dir="") as tel, \
            caplog.at_level("WARNING", logger=kernels.logger.name):
        verdict = kernels.ensure_verdict(site)
        counters = tel.metrics.snapshot()["counters"]
    assert verdict["adopted"] is False
    assert verdict["error"] == ("NotImplementedError: Unsupported cast: "
                                "uint8 -> float32")
    assert verdict["reason"] == verdict["error"]
    assert counters.get(telemetry.M_KERNEL_AUDITION_ERROR) == 1
    assert telemetry.M_KERNEL_REJECTED not in counters
    assert any("FAILED" in r.getMessage() and r.levelname == "WARNING"
               for r in caplog.records)
    # settled for THIS process (no re-audition per launch) ...
    assert kernels.verdict_for(site) == verdict
    # ... but never written: a fresh process finds nothing and re-auditions
    assert not (tmp_path / kernels._VERDICT_STORE_BASENAME).exists()
    kernels.reset()
    assert kernels.verdict_for(site) is None


def test_unsupported_backend_rejection_is_clean_and_persisted(
        tmp_path, monkeypatch):
    """The CPU "no Mosaic lowering" case stays a quiet rejection: no
    ``error``, and it persists like any other verdict."""
    monkeypatch.setenv(COMPILE_CACHE_DIR_ENV, str(tmp_path))
    verdict = kernels.ensure_verdict(_site())  # INTERPRET False, CPU
    assert verdict["adopted"] is False and "error" not in verdict
    assert "Mosaic" in verdict["reason"]
    kernels.reset()
    assert kernels.verdict_for(_site())["reason"] == verdict["reason"]


# ---------------------------------------------------------------------------
# Numeric contract: every fused kernel vs its XLA twin (interpreter mode)
# ---------------------------------------------------------------------------

_MATRIX = [
    kernels.Site("sep2d", "matrix", (2, 6, 6, 8, 8), "float32"),
    kernels.Site("sep2d", "matrix", (2, 6, 6, 8, 8), "bfloat16"),
    kernels.Site("pw1x1", "matrix", (2, 4, 4, 8, 16), "float32"),
    kernels.Site("pw1x1", "matrix", (2, 4, 4, 8, 16), "bfloat16"),
    kernels.Site("pw1x1_relu", "matrix", (2, 4, 4, 8, 16), "float32"),
    kernels.Site("pw1x1_relu", "matrix", (2, 4, 4, 8, 16), "bfloat16"),
]


@pytest.mark.parametrize("site", _MATRIX,
                         ids=lambda s: f"{s.kernel}-{s.dtype}")
def test_fused_kernel_matches_xla_twin(site):
    """The shootout's own candidate pair at O(1)-magnitude operands:
    bf16 must sit inside the adoption contract (BF16_TOLERANCE); fp32
    within float roundoff of the twin (the folded BN affine reorders
    ops, so bit-exactness is not expected — which is exactly why fp32
    candidates are auto-rejected by the exactness gate)."""
    kernels.INTERPRET = True
    pallas_fn, xla_fn, x = kernels._build_shootout(site)
    y_p = np.asarray(jnp.asarray(pallas_fn(x), jnp.float32))
    y_x = np.asarray(jnp.asarray(xla_fn(x), jnp.float32))
    assert y_p.shape == y_x.shape
    err = float(np.max(np.abs(y_p - y_x)))
    if site.dtype == "bfloat16":
        assert err <= kernels.BF16_TOLERANCE, err
    else:
        assert err <= 1e-5, err


@pytest.mark.parametrize("in_dtype,out_dtype,atol", [
    ("uint8", "float32", 1e-3), ("uint8", "bfloat16", 2.0),
    # a float source must NOT take the integer widening (it would
    # truncate): the kernel widens through int32 only for integer inputs
    ("float32", "float32", 1e-5)])
def test_preproc_kernel_matches_resize(in_dtype, out_dtype, atol):
    """Fused cast+resize vs the jax.image.resize twin. Outputs live on
    the uint8 [0, 255] scale, so the bound is one bf16 ulp at 255 (2.0)
    rather than the O(1) BF16_TOLERANCE — the audition gate judges
    preproc bf16 sites against 0.05 and therefore rejects them, which
    is the conservative-by-design outcome."""
    kernels.INTERPRET = True
    site = kernels.Site("preproc", "matrix", (1, 8, 10, 3, 5, 6),
                        f"{in_dtype}->{out_dtype}")
    pallas_fn, xla_fn, x = kernels._build_shootout(site)
    y_p = np.asarray(jnp.asarray(pallas_fn(x), jnp.float32))
    ref = np.asarray(kernels.xla_preproc(x, (5, 6), "float32"))
    assert y_p.shape == ref.shape
    assert float(np.max(np.abs(y_p - ref))) <= atol


# ---------------------------------------------------------------------------
# Route gating: off / autotune / force
# ---------------------------------------------------------------------------


def _pw_operands(rng):
    x = jnp.asarray(rng.normal(size=(2, 4, 4, 8)).astype(np.float32))
    k4 = jnp.asarray((rng.normal(size=(1, 1, 8, 8)) * 0.3)
                     .astype(np.float32))
    gamma = jnp.asarray(
        (np.abs(rng.normal(size=8)) + 0.5).astype(np.float32))
    beta = jnp.asarray((rng.normal(size=8) * 0.1).astype(np.float32))
    mean = jnp.asarray((rng.normal(size=8) * 0.1).astype(np.float32))
    var = jnp.asarray(
        (np.abs(rng.normal(size=8)) + 1.0).astype(np.float32))
    return x, k4, gamma, beta, mean, var


def test_route_returns_none_without_adopted_verdict(rng):
    EngineConfig.pallas_kernels = "autotune"
    x, k4, gamma, beta, mean, var = _pw_operands(rng)
    assert kernels.route_pw1x1(x, k4, gamma, beta, mean, var, 1e-3,
                               relu=True, family="unit") is None


def test_route_honors_injected_verdicts(rng):
    EngineConfig.pallas_kernels = "autotune"
    kernels.INTERPRET = True
    x, k4, gamma, beta, mean, var = _pw_operands(rng)
    site = kernels.Site("pw1x1_relu", "unit", (2, 4, 4, 8, 8), "float32")
    _inject(site, adopted=False)
    assert kernels.route_pw1x1(x, k4, gamma, beta, mean, var, 1e-3,
                               relu=True, family="unit") is None
    _inject(site, adopted=True)
    routed = kernels.route_pw1x1(x, k4, gamma, beta, mean, var, 1e-3,
                                 relu=True, family="unit")
    assert routed is not None
    twin = kernels.xla_pw1x1(x, k4, gamma, beta, mean, var, 1e-3,
                             relu=True)
    np.testing.assert_allclose(np.asarray(routed), np.asarray(twin),
                               atol=1e-5)


def test_force_mode_routes_under_jit(rng):
    EngineConfig.pallas_kernels = "force"
    kernels.INTERPRET = True
    x, k4, gamma, beta, mean, var = _pw_operands(rng)
    routed = jax.jit(lambda a: kernels.route_pw1x1(
        a, k4, gamma, beta, mean, var, 1e-3, relu=True,
        family="unit"))(x)
    assert routed is not None
    twin = kernels.xla_pw1x1(x, k4, gamma, beta, mean, var, 1e-3,
                             relu=True)
    np.testing.assert_allclose(np.asarray(routed), np.asarray(twin),
                               atol=1e-5)


def test_force_mode_routes_sep2d(rng):
    EngineConfig.pallas_kernels = "force"
    kernels.INTERPRET = True
    x = jnp.asarray(rng.normal(size=(2, 6, 6, 8)).astype(np.float32))
    dw4 = jnp.asarray((rng.normal(size=(3, 3, 1, 8)) * 0.2)
                      .astype(np.float32))
    pw4 = jnp.asarray((rng.normal(size=(1, 1, 8, 8)) * 0.35)
                      .astype(np.float32))
    gamma = jnp.asarray(
        (np.abs(rng.normal(size=8)) + 0.5).astype(np.float32))
    beta = jnp.asarray((rng.normal(size=8) * 0.1).astype(np.float32))
    mean = jnp.asarray((rng.normal(size=8) * 0.1).astype(np.float32))
    var = jnp.asarray(
        (np.abs(rng.normal(size=8)) + 1.0).astype(np.float32))
    routed = kernels.route_sep2d(x, dw4, pw4, gamma, beta, mean, var,
                                 1e-3, family="unit")
    assert routed is not None
    twin = kernels.xla_sep2d(x, dw4, pw4, gamma, beta, mean, var, 1e-3)
    np.testing.assert_allclose(np.asarray(routed), np.asarray(twin),
                               atol=1e-5)


def test_route_preproc_force(rng):
    EngineConfig.pallas_kernels = "force"
    kernels.INTERPRET = True
    x = jnp.asarray(rng.integers(0, 256, size=(1, 8, 10, 3))
                    .astype(np.uint8))
    routed = kernels.route_preproc(x, (5, 6), "float32", family="unit")
    assert routed is not None
    twin = kernels.xla_preproc(x, (5, 6), "float32")
    np.testing.assert_allclose(np.asarray(routed), np.asarray(twin),
                               atol=1e-3)


def test_infeasible_site_never_routes(rng):
    """A site past the VMEM budget must fall back even under force."""
    EngineConfig.pallas_kernels = "force"
    kernels.INTERPRET = True
    x = jnp.asarray(rng.normal(size=(1, 2, 2, 4)).astype(np.float32))
    dw4 = jnp.zeros((3, 3, 1, 4), np.float32)
    pw4 = jnp.zeros((1, 1, 4, 4), np.float32)
    ones = jnp.ones((4,), np.float32)
    # h=2 < 3: sep2d geometry infeasible
    assert kernels.route_sep2d(x, dw4, pw4, ones, ones, ones, ones,
                               1e-3, family="unit") is None


# ---------------------------------------------------------------------------
# Autotune on CPU: clean rejections, byte-identical routed program
# ---------------------------------------------------------------------------


class _Tiny(nn.Module):
    """Smallest model that routes: one fused-family 1×1 ConvBN."""

    @nn.compact
    def __call__(self, x, train: bool = False):
        return ConvBN(8, (1, 1), act=True, kernel_family="tiny")(x, train)


def _tiny_model(rng):
    m = _Tiny()
    vs = m.init(jax.random.PRNGKey(0), np.zeros((1, 4, 4, 3), np.float32))
    x = rng.normal(size=(2, 4, 4, 3)).astype(np.float32)
    return m, vs, x


def test_cpu_autotune_rejects_cleanly_and_stays_byte_identical(rng):
    m, vs, x = _tiny_model(rng)
    EngineConfig.pallas_kernels = "off"
    y_off = np.asarray(jax.jit(lambda a: m.apply(vs, a))(x))

    EngineConfig.pallas_kernels = "autotune"  # INTERPRET stays False:
    # CPU has no Mosaic lowering, so every audition must reject cleanly
    kernels.ensure_autotuned(lambda a: m.apply(vs, a), x, model="tiny")
    snap = kernels.verdicts_snapshot()
    assert snap, "expected at least one audited site"
    assert all(v["adopted"] is False for v in snap.values())
    assert all("Mosaic" in v["reason"] for v in snap.values()), snap

    y_auto = np.asarray(jax.jit(lambda a: m.apply(vs, a))(x))
    assert y_auto.dtype == y_off.dtype
    np.testing.assert_array_equal(y_auto, y_off)


def test_ensure_autotuned_noop_outside_autotune_mode(rng):
    m, vs, x = _tiny_model(rng)
    for mode in ("off", "force"):
        EngineConfig.pallas_kernels = mode
        kernels.ensure_autotuned(lambda a: m.apply(vs, a), x)
        assert kernels.verdicts_snapshot() == {}


def test_model_function_first_launch_settles_verdicts(rng):
    """The production hook: ModelFunction's first-launch-of-a-shape
    path runs the site collection + shootouts before the real trace."""
    m, vs, x = _tiny_model(rng)
    EngineConfig.pallas_kernels = "autotune"
    mf = ModelFunction.fromFlax(m, vs, TensorSpec((None, 4, 4, 3),
                                                  "float32"),
                                name="tiny", train=False)
    out = mf.apply_batch(x, batch_size=2)
    assert np.asarray(out).shape == (2, 4, 4, 8)
    snap = kernels.verdicts_snapshot()
    assert snap and all(v["adopted"] is False for v in snap.values())


def test_convbn_force_interpret_matches_flax(rng):
    """Force + interpreter: the ConvBN structural opt-in actually swaps
    in the fused body, and its numerics sit on the Flax result."""
    m, vs, x = _tiny_model(rng)
    EngineConfig.pallas_kernels = "off"
    y_flax = np.asarray(m.apply(vs, x))
    EngineConfig.pallas_kernels = "force"
    kernels.INTERPRET = True
    y_fused = np.asarray(m.apply(vs, x))
    np.testing.assert_allclose(y_fused, y_flax, atol=1e-5)


def test_separable_convbn_force_interpret_matches_flax(rng):
    class _Sep(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            return SeparableConvBN(8, kernel_family="tiny")(x, train)

    m = _Sep()
    vs = m.init(jax.random.PRNGKey(0), np.zeros((1, 6, 6, 4), np.float32))
    x = rng.normal(size=(2, 6, 6, 4)).astype(np.float32)
    EngineConfig.pallas_kernels = "off"
    y_flax = np.asarray(m.apply(vs, x))
    EngineConfig.pallas_kernels = "force"
    kernels.INTERPRET = True
    y_fused = np.asarray(m.apply(vs, x))
    np.testing.assert_allclose(y_fused, y_flax, atol=1e-5)


def test_engine_config_rejects_unknown_kernel_mode():
    EngineConfig.pallas_kernels = "banana"
    with pytest.raises(ValueError, match="pallas_kernels"):
        EngineConfig.validate()


# ---------------------------------------------------------------------------
# Off mode: the module is never even imported
# ---------------------------------------------------------------------------


def test_off_mode_never_imports_kernels_module():
    """Subprocess pin: with pallas_kernels="off", building AND applying
    a fused-family model must leave core.kernels out of sys.modules —
    "off" means zero import cost and a byte-identical program, not a
    dormant registry."""
    script = r"""
import sys
from sparkdl_tpu.engine.dataframe import EngineConfig
EngineConfig.pallas_kernels = "off"
import numpy as np
import jax
from sparkdl_tpu.models.layers import ConvBN
m = ConvBN(4, (1, 1), kernel_family="pin")
vs = m.init(jax.random.PRNGKey(0), np.zeros((1, 4, 4, 3), np.float32))
y = m.apply(vs, np.ones((2, 4, 4, 3), np.float32))
assert y.shape == (2, 4, 4, 4), y.shape
assert "sparkdl_tpu.core.kernels" not in sys.modules, \
    "off mode imported the kernel registry"
print("CLEAN")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
    env.pop(COMPILE_CACHE_DIR_ENV, None)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "CLEAN" in proc.stdout
