"""ModelFunction tests, incl. the ingestion format-matrix (SURVEY.md §4):
one tiny model exported every way, identical results through each ctor."""

import json
import os
import subprocess
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.core import (
    MeshConfig, ModelFunction, TensorSpec, make_mesh,
)


class TinyNet(nn.Module):
    features: int = 5

    @nn.compact
    def __call__(self, x):
        x = nn.Dense(8)(x)
        x = nn.relu(x)
        return nn.Dense(self.features)(x)


@pytest.fixture(scope="module")
def tiny():
    module = TinyNet()
    spec = TensorSpec((None, 3))
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros(spec.with_batch(1)))
    mf = ModelFunction.fromFlax(module, variables, spec)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (7, 3)))
    expected = np.asarray(module.apply(variables, x))
    return module, spec, variables, mf, x, expected


def test_from_flax_matches_direct_apply(tiny):
    _, _, _, mf, x, expected = tiny
    np.testing.assert_allclose(np.asarray(mf(x)), expected, rtol=1e-6)


def test_apply_batch_pads_and_unpads(tiny):
    _, _, _, mf, x, expected = tiny
    out = mf.apply_batch(x, batch_size=4)  # 7 rows -> chunks 4 + 3(padded)
    assert out.shape == expected.shape
    np.testing.assert_allclose(out, expected, rtol=1e-5)


def test_format_matrix_equivalence(tiny, tmp_path):
    """The TFInputGraph ctor-matrix test: every ingestion route agrees."""
    module, spec, variables, mf, x, expected = tiny

    routes = {}
    # fromFunction
    routes["function"] = ModelFunction.fromFunction(
        lambda vs, a: module.apply(vs, a), variables, spec)
    # fromMsgpack
    mp = tmp_path / "weights.msgpack"
    mf.toMsgpack(str(mp))
    routes["msgpack"] = ModelFunction.fromMsgpack(str(mp), module, spec)
    # fromOrbax
    od = tmp_path / "orbax_ckpt"
    mf.toOrbax(str(od))
    routes["orbax"] = ModelFunction.fromOrbax(str(od), module, spec)
    # fromJaxExport (symbolic batch dim)
    blob = mf.toJaxExport()
    routes["export"] = ModelFunction.fromJaxExport(blob)
    # fromJaxExport via file, fixed batch
    ep = tmp_path / "model.stablehlo"
    mf.toJaxExport(str(ep), batch_size=7)
    routes["export_file"] = ModelFunction.fromJaxExport(str(ep))

    for name, route in routes.items():
        out = np.asarray(route(x))
        np.testing.assert_allclose(out, expected, rtol=1e-5,
                                   err_msg=f"route {name} diverged")


def test_export_symbolic_batch_runs_any_size(tiny):
    _, _, _, mf, _, _ = tiny
    exported = ModelFunction.fromJaxExport(mf.toJaxExport())
    assert exported.input_spec.shape[0] is None
    for n in (1, 5, 16):
        out = exported(np.zeros((n, 3), np.float32))
        assert np.asarray(out).shape == (n, 5)


def test_composition_fuses(tiny):
    _, _, _, mf, x, expected = tiny
    composed = (mf.with_preprocess(lambda a: a * 2.0)
                  .with_postprocess(lambda y: y + 1.0))
    out = np.asarray(composed(x / 2.0))
    np.testing.assert_allclose(out, expected + 1.0, rtol=1e-5)


def test_flattened(tiny):
    module, spec, variables, mf, x, _ = tiny
    out = mf.flattened()(x)
    assert out.ndim == 2


def test_mesh_sharded_apply(tiny):
    _, _, _, mf, x, expected = tiny
    mesh = make_mesh(MeshConfig(data=8))
    out = mf.apply_batch(x, batch_size=8, mesh=mesh)
    np.testing.assert_allclose(out, expected, rtol=1e-5)


def test_jit_cache_reused(tiny):
    _, _, _, mf, x, _ = tiny
    f1 = mf.jitted()
    f2 = mf.jitted()
    assert f1 is f2


def test_first_launch_records_compile_span_once_per_shape():
    """ISSUE 5 satellite: the first dispatch of each new input shape is
    wrapped in a `sparkdl.compile` span (bucket-ladder compile storms are
    visible in the run report); repeat dispatches at a seen shape are not."""
    from sparkdl_tpu.core import telemetry
    from sparkdl_tpu.core.telemetry import Telemetry

    mf = ModelFunction(lambda vs, x: x * vs, jnp.asarray(2.0),
                       TensorSpec((None, 3)), name="compile_span")
    with Telemetry() as tel:
        mf.apply_batch(np.ones((4, 3), np.float32), batch_size=8)
        mf.apply_batch(np.ones((4, 3), np.float32), batch_size=8)
        mf.apply_batch(np.ones((12, 3), np.float32), batch_size=8)
    compiles = tel.tracer.spans(telemetry.SPAN_COMPILE)
    # bucket 8 compiles once (second call is a repeat); the 12-row call
    # adds buckets 8 (seen) + the tail bucket only if it differs — with
    # batch_size 8 the chunks are 8 and a 4-row tail at bucket 8, both
    # seen, so exactly ONE compile span total
    assert len(compiles) == 1
    assert compiles[0]["attributes"]["model"] == "compile_span"


def test_compile_cache_resolver_follows_jax_variable(tmp_path, monkeypatch):
    """The cache is placed from outside with JAX's own variable; unset, it
    is the one fixed path inside the checkout. The sidecar stores persist
    only where the variable names the directory."""
    import sparkdl_tpu

    assert sparkdl_tpu.COMPILE_CACHE_DIR_ENV == "JAX_COMPILATION_CACHE_DIR"
    repo = os.path.dirname(os.path.dirname(
        os.path.abspath(sparkdl_tpu.__file__)))
    fixed = os.path.join(repo, ".jax_cache")
    monkeypatch.delenv(sparkdl_tpu.COMPILE_CACHE_DIR_ENV, raising=False)
    assert sparkdl_tpu._compile_cache_dir() == fixed
    assert sparkdl_tpu._sidecar_store_dir() is None
    target = str(tmp_path / "xla_cache")
    monkeypatch.setenv(sparkdl_tpu.COMPILE_CACHE_DIR_ENV, target)
    assert sparkdl_tpu._compile_cache_dir() == target
    assert sparkdl_tpu._sidecar_store_dir() == target
    # the in-checkout default inherited by a spawned worker is still
    # "not placed from outside" for the sidecar stores
    monkeypatch.setenv(sparkdl_tpu.COMPILE_CACHE_DIR_ENV, fixed)
    assert sparkdl_tpu._sidecar_store_dir() is None


_CACHE_PROBE = r"""
import json, os, sys
{pre}
import sparkdl_tpu
jax_free = "jax" not in sys.modules
import jax
print(json.dumps({{
    "jax_free": jax_free,
    "resolver": sparkdl_tpu._compile_cache_dir(),
    "jax_dir": jax.config.jax_compilation_cache_dir,
    "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
}}))
"""


@pytest.mark.parametrize("placed,jax_first", [
    (False, False), (True, False), (False, True), (True, True)],
    ids=["default", "from-outside", "default-jax-first",
         "from-outside-jax-first"])
def test_import_places_compile_cache_without_importing_jax(
        tmp_path, placed, jax_first):
    """A fresh interpreter: ``import sparkdl_tpu`` leaves jax out of
    ``sys.modules`` yet JAX, once imported, has its cache where the
    resolver says — the outside directory when the variable is set, the
    fixed in-checkout path otherwise — whichever was imported first."""
    import sparkdl_tpu

    repo = os.path.dirname(os.path.dirname(
        os.path.abspath(sparkdl_tpu.__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    for var in (sparkdl_tpu.COMPILE_CACHE_DIR_ENV,
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"):
        env.pop(var, None)
    want = os.path.join(repo, ".jax_cache")
    if placed:
        want = env[sparkdl_tpu.COMPILE_CACHE_DIR_ENV] = str(tmp_path / "c")
    script = _CACHE_PROBE.format(pre="import jax" if jax_first else "")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["jax_free"] is (not jax_first)
    assert got["resolver"] == want
    assert got["jax_dir"] == want
    assert got["min_secs"] == 0.0
    assert not os.path.exists(str(tmp_path / "c"))  # placing writes nothing


def _plain_bilinear(x, th, tw):
    """Half-pixel-centre bilinear resize of NHWC ``x`` in float64, edges
    clamped, no antialiasing: ``tf.image.resize_bilinear``'s arithmetic
    with ``half_pixel_centers``, written out."""
    def taps(n_in, n_out):
        src = (np.arange(n_out) + 0.5) * (n_in / n_out) - 0.5
        lo = np.floor(src)
        frac = src - lo
        return (np.clip(lo, 0, n_in - 1).astype(int),
                np.clip(lo + 1, 0, n_in - 1).astype(int), frac)

    x = x.astype(np.float64)
    lo, hi, f = taps(x.shape[1], th)
    x = x[:, lo] * (1 - f)[None, :, None, None] \
        + x[:, hi] * f[None, :, None, None]
    lo, hi, f = taps(x.shape[2], tw)
    return x[:, :, lo] * (1 - f)[None, None, :, None] \
        + x[:, :, hi] * f[None, None, :, None]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("src,target", [
    ((64, 64), (32, 32)), ((224, 224), (299, 299)), ((375, 500), (299, 299)),
], ids=lambda hw: "x".join(map(str, hw)))
def test_resized_matches_plain_bilinear(src, target, dtype):
    """``resized()``'s prologue — uint8 in, cast and bilinear resize inside
    the compiled program — at a small, a model-sized and a photo-sized
    source, against the resize written out in numpy."""
    mf = ModelFunction(lambda vs, a: a, None,
                       TensorSpec((None,) + target + (3,), dtype),
                       name="resize_only")
    x = np.random.default_rng(0).integers(
        0, 256, size=(2,) + src + (3,), dtype=np.uint8)
    wrapped = mf.resized(src)
    assert wrapped is mf.resized(src, target)  # one program per geometry
    assert wrapped.input_spec.shape == (None,) + src + (3,)
    got = wrapped.jitted()(x)
    assert got.dtype == jnp.dtype(dtype)
    assert got.shape == (2,) + target + (3,)
    err = np.max(np.abs(np.asarray(got, np.float64)
                        - _plain_bilinear(x, *target)))
    # on the 0-255 scale. float32: the weights come from float32
    # coordinates of up to 500 (2^-24 * 500 = 3e-5 of a weight);
    # bfloat16: weights of 8 bits, and from 128 up one ulp is 1.0 — a
    # rounding after each axis
    assert err <= (255 * 1e-4 if dtype == "float32" else 3.0)


@pytest.mark.parametrize("route", ["fromFlax", "resized"])
def test_first_launch_traces_the_body_once(route):
    """The first launch of a new shape runs the Python body once, and
    that run is the trace the ``sparkdl.compile`` span times; a launch at
    a seen shape runs it not at all."""
    from sparkdl_tpu.core import telemetry
    from sparkdl_tpu.core.telemetry import Telemetry

    traced_under = []

    if route == "fromFlax":
        class Counting(nn.Module):
            @nn.compact
            def __call__(self, x):
                traced_under.append(telemetry.current_context())
                return nn.Dense(4)(x)

        module, spec = Counting(), TensorSpec((None, 6))
        variables = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 6)))
        mf = ModelFunction.fromFlax(module, variables, spec)
        batches = [np.ones((3, 6), np.float32), np.ones((5, 6), np.float32)]
    else:
        def body(vs, a):
            traced_under.append(telemetry.current_context())
            return jnp.mean(a, axis=(1, 2))

        mf = ModelFunction.fromFunction(
            body, None, TensorSpec((None, 8, 8, 3))).resized((12, 16))
        batches = [np.ones((3, 12, 16, 3), np.uint8),
                   np.ones((5, 12, 16, 3), np.uint8)]
    del traced_under[:]  # init traced the module once
    fn = mf.jitted()
    with Telemetry() as tel:
        fn(batches[0])
        fn(batches[0])
        assert len(traced_under) == 1
        fn(batches[1])
        assert len(traced_under) == 2
    compiles = tel.tracer.spans(telemetry.SPAN_COMPILE)
    assert ([ctx.span_id for ctx in traced_under]
            == [span["span_id"] for span in compiles])
