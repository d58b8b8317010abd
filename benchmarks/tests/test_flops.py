"""The benchmark's own FLOP count against XLA's HLO cost analysis of the
same plain forward pass (a count from a CPU lowering, not a speed)."""

import jax
import jax.numpy as jnp
import pytest

import flops
from references import inceptionv3, plain, resnet50

# XLA counts only the taps that fall inside the image (a padded border
# multiplies by nothing) and adds the elementwise work; the walk counts
# 2 x MACs the published way (InceptionV3: 5.71 GMACs). They differ by a
# few percent, in either direction.
TOLERANCE = 0.06


@pytest.mark.parametrize("reference, include_top, published_macs", [
    (inceptionv3, False, 5.71e9), (resnet50, False, 3.86e9),
    (resnet50, True, 3.86e9)])
def test_walk_matches_xla_and_the_published_count(reference, include_top,
                                                  published_macs):
    walked = flops.forward_flops_per_image(reference, include_top)
    assert walked == pytest.approx(2 * published_macs, rel=0.01)

    h, w = reference.INPUT_SIZE
    variables = jax.eval_shape(lambda: flops.init_variables(
        reference, jax.random.PRNGKey(0), include_top))
    lowered = jax.jit(lambda vs, x: reference.forward(
        plain.Scope.apply(vs), x, include_top=include_top)).lower(
            variables, jax.ShapeDtypeStruct((1, h, w, 3), jnp.float32))
    xla = lowered.cost_analysis()["flops"]
    assert abs(walked - xla) / xla < TOLERANCE, (walked, xla)


def test_walk_counts_grouped_and_nested():
    def f(x, w, v):
        y = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", feature_group_count=4,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jax.jit(lambda a: a.reshape(2, -1) @ v)(y)

    x = jnp.zeros((2, 8, 8, 8))
    w = jnp.zeros((3, 3, 2, 16))          # 4 groups of 2 input channels
    v = jnp.zeros((8 * 8 * 16, 5))
    jaxpr = jax.make_jaxpr(f)(x, w, v).jaxpr
    assert flops.jaxpr_matmul_flops(jaxpr) == (
        2 * (2 * 8 * 8 * 16) * 2 * 9 + 2 * (2 * 5) * (8 * 8 * 16))
