"""The KDA recurrence alone on the chip, at one Ling-3.0-flash layer's shape
(one window of 16,384 positions, 32 heads of 128): the TPU branch of
``delta_rule`` against its plain path over a short window, then
milliseconds a call of ``delta_rule`` and of the whole ``kda`` mixer, by
chunks and heads a grid step. On a TPU host, from the root of a checkout
(``PYTHONPATH`` names the checkout whose package is timed):

    PYTHONPATH=. python experiments/delta_rule_probe.py [chunks,heads ...]
"""

import json
import sys
import time

import jax
import jax.numpy as jnp

from sparkdl_tpu.models import linear_attention as la
from sparkdl_tpu.models import registry


def operands(T, H, d, lower, key=0):
    k = jax.random.split(jax.random.PRNGKey(key), 5)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    q = unit(jax.random.normal(k[0], (1, T, H, d))) * d ** -0.5
    kk = unit(jax.random.normal(k[1], (1, T, H, d)))
    v = jax.random.normal(k[2], (1, T, H, d))
    g = lower * jax.nn.sigmoid(jax.random.normal(k[3], (1, T, H, d)) - 3)
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (1, T, H)))
    return [a.reshape(1, T, H * d) for a in (q, kk, v, g)] + [beta]


def mixer(c, T, key=1):
    wide = c.heads * c.head_dim
    shapes = {**{name: (c.hidden, wide) for name in "qkvf"},
              **{f"{name}_taps": (wide, 4) for name in "qkv"},
              "dt_bias": (wide,), "A_log": (c.heads,),
              "beta": (c.hidden, c.heads), "gate": (c.hidden, c.heads),
              "o_norm": (c.head_dim,), "out": (wide, c.hidden)}
    keys = jax.random.split(jax.random.PRNGKey(key), len(shapes) + 1)
    p = {name: (jax.random.normal(kk, shape) * shape[0] ** -0.5).astype(
        jnp.bfloat16) for kk, (name, shape) in zip(keys, shapes.items())}
    return p, jax.random.normal(keys[-1], (1, T, c.hidden))


def ms_a_call(fn, *args, calls=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def main(argv):
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("delta_rule_probe: needs a TPU")
    c = registry.SEQUENCE_MODELS["Ling-3.0-flash"]
    H, d, lower = c.heads, c.head_dim, c.kda_lower_bound
    small = operands(2048, H, d, lower)
    want, _ = jax.jit(lambda *a: la.delta_rule(*a, jnp.float32))(*small)
    got, fused = jax.jit(lambda *a: la.delta_rule(*a, jnp.bfloat16))(*small)
    print(json.dumps({"fused": int(fused),
                      "o_gap": float(jnp.max(jnp.abs(got - want))),
                      "o_max": float(jnp.max(jnp.abs(want)))}), flush=True)
    full = operands(16384, H, d, lower, key=1)
    p, x = mixer(c, 16384)
    for arg in argv or [None]:
        if arg is not None:
            chunks, heads = (int(a) for a in arg.split(","))
            la.KERNEL_CHUNKS, la.KERNEL_HEADS = (chunks,), (heads,)
        try:
            rule = jax.jit(lambda *a: la.delta_rule(*a, jnp.bfloat16))
            kda = jax.jit(lambda p, x: la.kda(p, x, c.eps, lower))
            print(json.dumps({"step": arg,
                              "delta_rule_ms": ms_a_call(rule, *full),
                              "kda_ms": ms_a_call(kda, p, x)}), flush=True)
        except Exception as e:  # noqa: BLE001  (a variant the compiler refuses)
            print(json.dumps({"step": arg, "error": str(e)[:300]}),
                  flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
