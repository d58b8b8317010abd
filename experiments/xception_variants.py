"""Xception perf attribution + variant shootout (r4, VERDICT #1).

Measures, on the real chip with the slope method (bench.py), where the
middle-flow time goes and whether alternative depthwise lowerings beat
XLA's grouped-conv path:

  micro (one middle-flow block, b128 19x19x728 bf16):
    pw-only   : 3x (relu + 1x1 conv + bias)        — MXU upper bound
    dw-only   : 3x (relu + grouped depthwise)      — current dw cost
    dwshift   : 3x (relu + 9-shift elementwise dw) — VPU lowering
    block-grp : full sepconv block, grouped dw     — current
    block-sft : full sepconv block, 9-shift dw
  full model:
    module    : Xception flax module (current prod path)

Run: python experiments/xception_variants.py [micro|full]
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")
from bench import PEAK_TFLOPS_BF16, make_slope_measurer  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

B, H, W, C = 128, 19, 19, 728
DIMS = ("NHWC", "HWIO", "NHWC")


def measure(name, apply_fn, variables, x_np, flops_per_img=None):
    m = make_slope_measurer(apply_fn, variables, x_np)
    runs = [m() for _ in range(3)]
    ips = max(r[0] for r in runs)
    line = f"{name:12s} {ips:10.1f} img/s"
    if flops_per_img:
        line += f"  mfu={ips * flops_per_img / 1e12 / PEAK_TFLOPS_BF16:.3f}"
    print(line, flush=True)
    return ips


def dw_grouped(x, w):
    # w: (3,3,1,C) — flax depthwise form
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=DIMS, feature_group_count=C)


def dw_shift(x, w):
    # w: (3,3,1,C); nine shifted multiply-adds — pure VPU elementwise
    h, wd = x.shape[1], x.shape[2]
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    out = None
    for dy in range(3):
        for dx in range(3):
            t = xp[:, dy:dy + h, dx:dx + wd, :] * w[dy, dx, 0]
            out = t if out is None else out + t
    return out


def pw(x, k, b):
    y = jax.lax.conv_general_dilated(x, k, (1, 1), "SAME",
                                     dimension_numbers=DIMS)
    return y + b


def make_params(rng):
    p = {}
    for i in range(3):
        p[f"dw{i}"] = rng.normal(size=(3, 3, 1, C)).astype(np.float32) * 0.1
        p[f"pw{i}"] = rng.normal(size=(1, 1, C, C)).astype(np.float32) * 0.03
        p[f"b{i}"] = rng.normal(size=(C,)).astype(np.float32) * 0.01
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.bfloat16), p)


def block(variables, x, dw_fn):
    res = x
    for i in range(3):
        x = jax.nn.relu(x)
        x = dw_fn(x, variables[f"dw{i}"])
        x = pw(x, variables[f"pw{i}"], variables[f"b{i}"])
    return x + res


def pw_only(variables, x):
    res = x
    for i in range(3):
        x = jax.nn.relu(x)
        x = pw(x, variables[f"pw{i}"], variables[f"b{i}"])
    return x + res


def dw_only(variables, x, dw_fn):
    res = x
    for i in range(3):
        x = jax.nn.relu(x)
        x = dw_fn(x, variables[f"dw{i}"])
    return x + res


# per-image flops for one middle block (2*MACs)
PW_FLOPS = 3 * H * W * C * C * 2
DW_FLOPS = 3 * H * W * C * 9 * 2
BLOCK_FLOPS = PW_FLOPS + DW_FLOPS


def micro():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(B, H, W, C)).astype(np.float32)
    variables = make_params(rng)

    def cast(fn):
        return lambda v, xx: fn(v, xx.astype(jnp.bfloat16))

    measure("pw-only", cast(pw_only), variables, x, PW_FLOPS)
    measure("dw-only-grp", cast(lambda v, xx: dw_only(v, xx, dw_grouped)), variables, x, DW_FLOPS)
    measure("dw-only-sft", cast(lambda v, xx: dw_only(v, xx, dw_shift)), variables, x, DW_FLOPS)
    measure("block-grp", cast(lambda v, xx: block(v, xx, dw_grouped)), variables, x, BLOCK_FLOPS)
    measure("block-sft", cast(lambda v, xx: block(v, xx, dw_shift)), variables, x, BLOCK_FLOPS)


def full():
    from sparkdl_tpu.models import registry

    mf = registry.build_featurizer("Xception", weights="random",
                                   dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    x = rng.integers(0, 255, size=(B, 299, 299, 3)).astype(np.float32)
    measure("module", mf.apply_fn, mf.variables, x, 16.8e9)


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "micro"
    t0 = time.time()
    if mode in ("micro", "all"):
        micro()
    if mode in ("full", "all"):
        full()
    print(f"total {time.time() - t0:.0f}s")
