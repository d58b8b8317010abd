"""The selective-scan kernel alone on the chip, at one Jamba2-3B layer's
shape (16,384 positions × 5,120 channels × 16 states): agreement with the
plain path over a short window, then milliseconds a call by time block,
channel chunk and unroll. By hand through the chip tool:

    python experiments/scan_kernel_probe.py [block,chunk,unroll ...]
"""

import json
import sys
import time

import jax
import jax.numpy as jnp

from sparkdl_tpu.models import state_space as ss


def operands(T, D, N, key=0):
    k = jax.random.split(jax.random.PRNGKey(key), 6)
    x = jax.random.normal(k[0], (T, D)).astype(jnp.bfloat16)
    z = jax.random.normal(k[1], (T, D)).astype(jnp.bfloat16)
    delta = jax.nn.softplus(jax.random.normal(k[2], (T, D)) - 3)
    a = -jnp.arange(1, N + 1, dtype=jnp.float32)[:, None] * jnp.ones((N, D))
    b = jax.random.normal(k[3], (T, N))
    c = jax.random.normal(k[4], (T, N))
    return x, delta, a, b, c, jnp.ones((D,)), z, jax.random.normal(
        k[5], (N, D))


def main(argv):
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("scan_kernel_probe: needs a TPU")
    D, N = 5120, 16
    small = operands(1024, D, N)
    want_y, want_last = jax.jit(ss.scan_blocks)(*small)
    got_y, got_last = jax.jit(ss.fused_selective_scan)(*small)
    print(json.dumps({
        "y_gap": float(jnp.max(jnp.abs(want_y.astype(jnp.float32)
                                       - got_y.astype(jnp.float32)))),
        "y_max": float(jnp.max(jnp.abs(want_y.astype(jnp.float32)))),
        "last_gap": float(jnp.max(jnp.abs(want_last - got_last))),
        "last_max": float(jnp.max(jnp.abs(want_last)))}), flush=True)
    full = operands(16384, D, N, key=1)
    variants = [tuple(int(v) for v in arg.split(",")) for arg in argv] or [
        (ss.SCAN_TIME_BLOCK, 512, ss.SCAN_UNROLL)]
    for block, chunk, unroll in variants:
        try:
            fn = jax.jit(lambda *o: ss.fused_selective_scan(
                *o, block=block, chunk=chunk, unroll=unroll))
            jax.block_until_ready(fn(*full))
            t0 = time.perf_counter()
            for _ in range(5):
                out = fn(*full)
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / 5 * 1e3
        except Exception as e:  # noqa: BLE001  (a variant the compiler refuses)
            print(json.dumps({"block": block, "chunk": chunk,
                              "unroll": unroll,
                              "error": str(e)[:300]}), flush=True)
            continue
        print(json.dumps({"block": block, "chunk": chunk, "unroll": unroll,
                          "ms": ms}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
