"""The model zoo's convolution units against a plain reference.

``ConvBN`` (InceptionV3) and ``SeparableConvBN`` (Xception) are checked at
the published channel widths of nine sites of those models, batch cut to 2.
The reference is written here from ``lax.conv_general_dilated`` and the
BatchNorm affine of the running statistics; it imports nothing of Flax, and
the variables are placed into the tree by name, so the tree the converters
and checkpoints depend on (``conv``/``bn``; ``depthwise``/``pointwise``/
``bn``) is part of what is held.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from sparkdl_tpu.models.layers import KERAS_BN_EPS, ConvBN, SeparableConvBN

_DIMS = ("NHWC", "HWIO", "NHWC")

# (unit, height, width, channels in, channels out, relu): the Xception
# middle- and exit-flow separable convolutions and the InceptionV3 1x1
# units, one of them without relu and with a BatchNorm scale.
_SITES = [
    ("sep", 19, 19, 728, 728, False),
    ("sep", 10, 10, 728, 1024, False),
    ("sep", 10, 10, 1024, 1536, False),
    ("conv", 35, 35, 192, 64, True),
    ("conv", 35, 35, 288, 48, True),
    ("conv", 17, 17, 768, 192, True),
    ("conv", 8, 8, 1280, 320, True),
    ("conv", 8, 8, 2048, 192, False),
    ("conv", 73, 73, 64, 80, True),
]


def _site_id(site):
    unit, h, w, cin, cout, relu = site
    return f"{unit}-{h}x{w}x{cin}to{cout}" + ("-relu" if relu else "")


def _unit_and_variables(site, rng):
    """The unit and float32 variables of O(1) magnitude (so that bfloat16's
    bound means something), keyed by the names the unit must keep."""
    unit, _, _, cin, cout, relu = site

    def normal(shape, scale=1.0):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    bn = {"bias": normal(cout, 0.1)}
    stats = {"mean": normal(cout, 0.1),
             "var": np.abs(normal(cout)) + 1.0}
    if unit == "sep":
        module = SeparableConvBN(cout)
        bn["scale"] = np.abs(normal(cout)) + 0.5
        params = {
            "depthwise": {"kernel": normal((3, 3, 1, cin), 0.2)},
            "pointwise": {"kernel": normal((1, 1, cin, cout), cin ** -0.5)},
            "bn": bn}
    else:
        module = ConvBN(cout, (1, 1), act=relu, bn_scale=not relu)
        if not relu:
            bn["scale"] = np.abs(normal(cout)) + 0.5
        params = {"conv": {"kernel": normal((1, 1, cin, cout), cin ** -0.5)},
                  "bn": bn}
    return module, {"params": params, "batch_stats": {"bn": stats}}


def _reference(site, variables, x):
    """float32 at full matrix precision: convolution(s), then
    (y - mean) * rsqrt(var + eps) [* scale] + bias, then relu."""
    unit, _, _, cin, _, relu = site
    params = variables["params"]
    stats = variables["batch_stats"]["bn"]

    def conv(a, kernel, groups=1):
        return lax.conv_general_dilated(
            a, jnp.asarray(kernel, jnp.float32), (1, 1), "SAME",
            dimension_numbers=_DIMS, feature_group_count=groups,
            precision=lax.Precision.HIGHEST)

    y = jnp.asarray(x, jnp.float32)
    if unit == "sep":
        y = conv(y, params["depthwise"]["kernel"], groups=cin)
        y = conv(y, params["pointwise"]["kernel"])
    else:
        y = conv(y, params["conv"]["kernel"])
    affine = lax.rsqrt(jnp.asarray(stats["var"], jnp.float32)
                       + jnp.float32(KERAS_BN_EPS))
    if "scale" in params["bn"]:
        affine = affine * jnp.asarray(params["bn"]["scale"], jnp.float32)
    y = (y - jnp.asarray(stats["mean"], jnp.float32)) * affine \
        + jnp.asarray(params["bn"]["bias"], jnp.float32)
    return np.asarray(jnp.maximum(y, 0) if relu else y)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("site", _SITES, ids=_site_id)
def test_unit_matches_plain_reference(site, dtype):
    _, h, w, cin, _, _ = site
    rng = np.random.default_rng(0)
    module, variables = _unit_and_variables(site, rng)
    x = rng.standard_normal((2, h, w, cin)).astype(np.float32)

    # what with_compute_dtype hands the unit: variables and activations
    # in the compute dtype; the reference sees the same rounded values
    cast = jax.tree.map(lambda a: jnp.asarray(a, dtype), variables)
    xc = jnp.asarray(x, dtype)
    got = jax.jit(lambda vs, a: module.apply(vs, a, train=False))(cast, xc)
    assert got.dtype == jnp.dtype(dtype) and got.shape[:3] == (2, h, w)
    want = _reference(site, cast, xc)

    err = np.abs(np.asarray(got, np.float32) - want)
    if dtype == "float32":
        assert float(err.max()) <= 1e-5 * float(np.max(np.abs(want)))
    else:
        # 0.05 was the bound between two bfloat16 programs; against a
        # float32 reference the output's own rounding comes on top (half
        # an ulp is 0.03 at |y| = 8), so it is 0.05 of max(1, |y|)
        assert float((err / np.maximum(1.0, np.abs(want))).max()) <= 0.05


@pytest.mark.parametrize("unit", ["ConvBN", "SeparableConvBN"])
def test_unit_parameter_tree(unit):
    """Names and shapes of what ``init`` creates: checkpoints and
    ``models/convert.py`` address the leaves by these paths."""
    x = np.zeros((1, 9, 9, 24), np.float32)
    if unit == "ConvBN":
        module = ConvBN(40, (3, 3), bn_scale=True)
        want = {"params/conv/kernel": (3, 3, 24, 40)}
    else:
        module = SeparableConvBN(40)
        want = {"params/depthwise/kernel": (3, 3, 1, 24),
                "params/pointwise/kernel": (1, 1, 24, 40)}
    want.update({"params/bn/scale": (40,), "params/bn/bias": (40,),
                 "batch_stats/bn/mean": (40,), "batch_stats/bn/var": (40,)})
    variables = module.init(jax.random.PRNGKey(0), x)
    got = {"/".join(k.key for k in path): leaf.shape
           for path, leaf in jax.tree_util.tree_leaves_with_path(variables)}
    assert got == want
    if unit == "ConvBN":  # InceptionV3's units carry no scale
        bare = ConvBN(40, (3, 3)).init(jax.random.PRNGKey(0), x)
        assert set(bare["params"]["bn"]) == {"bias"}
