"""Can a dense block beat the walker's concat-per-layer program? (r5)

DenseNet121's measured profile (category_profile.py on the ingested
model) attributes 43% of batch time to pure ``concatenate`` fusions at
~383 GB/s — each dense layer materializes the whole growing feature
buffer again, O(L^2) channel-copies per block. This probe measures one
representative block (28x28, 128->512 channels, 12 layers, the b128
shapes of DenseNet121's block 2) under three formulations:

A) **concat** — the keras walker's program: per layer,
   ``concat(prev, new)`` then BN+relu+1x1conv+BN+relu+3x3conv.
B) **segments** — never materialize the concat: keep per-layer outputs
   as a list; each 1x1 conv over the concat becomes a SUM of per-segment
   1x1 convs (BN+relu fold into each segment — exact same math).
C) **buffer** — preallocate the block's final width once and
   ``dynamic_update_slice`` each layer's 32 channels in; convs read the
   written prefix via ``lax.slice``.

Timing: self-chained iterations inside one jit (the in-program slope
method bench.py uses).

Result (2026-07-30, 1x v5e chip, bf16, b128):

    concat (walker)       4.09 ms/block
    segment-sum           4.78 ms/block   (1.17x SLOWER than concat)
    buffer+dus           13.94 ms/block   (3.4x slower; strided channel
                                           slices force layout copies)

The walker's concat program WINS: splitting the 1x1 convs into
per-segment convs loses more MXU efficiency (C_in=32 slivers) than the
eliminated concat writes save, and the preallocated-buffer form pays
layout copies on every strided channel slice. DenseNet's O(L^2)
re-reads are architectural; XLA's concat is already the best available
formulation. See docs/PERF.md "DenseNet121" for the ceiling write-up.
"""

import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, ".")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

H = W = 28
C0 = 128
GROWTH = 32
LAYERS = 12
BATCH = 128
DTYPE = jnp.bfloat16


def make_params(rng):
    params = []
    c = C0
    for _ in range(LAYERS):
        k1 = rng.normal(size=(1, 1, c, 4 * GROWTH)).astype(np.float32) * 0.05
        k3 = rng.normal(size=(3, 3, 4 * GROWTH, GROWTH)).astype(np.float32) * 0.05
        scale = rng.normal(size=(c,)).astype(np.float32) * 0.1 + 1.0
        bias = rng.normal(size=(c,)).astype(np.float32) * 0.1
        params.append((jnp.asarray(k1, DTYPE), jnp.asarray(k3, DTYPE),
                       jnp.asarray(scale, DTYPE), jnp.asarray(bias, DTYPE)))
        c += GROWTH
    return params


def conv(x, k, window=1):
    pad = "SAME" if window == 3 else "VALID"
    return lax.conv_general_dilated(
        x, k, (1, 1), pad, dimension_numbers=("NHWC", "HWIO", "NHWC"))


def layer_tail(y, k3):
    return conv(jax.nn.relu(y), k3, window=3)


def block_concat(x, params):
    for k1, k3, scale, bias in params:
        y = conv(jax.nn.relu(x * scale + bias), k1)
        new = layer_tail(y, k3)
        x = jnp.concatenate([x, new], axis=-1)
    return x


def block_segments(x, params):
    segs = [x]
    for k1, k3, scale, bias in params:
        y = None
        off = 0
        for seg in segs:
            c = seg.shape[-1]
            s, b = scale[off:off + c], bias[off:off + c]
            part = conv(jax.nn.relu(seg * s + b), k1[:, :, off:off + c, :])
            y = part if y is None else y + part
            off += c
        segs.append(layer_tail(y, k3))
    return jnp.concatenate(segs, axis=-1)


def block_buffer(x, params):
    c_final = C0 + GROWTH * LAYERS
    buf = jnp.zeros((x.shape[0], H, W, c_final), DTYPE)
    buf = lax.dynamic_update_slice(buf, x, (0, 0, 0, 0))
    c = C0
    for k1, k3, scale, bias in params:
        cur = lax.slice(buf, (0, 0, 0, 0), (x.shape[0], H, W, c))
        y = conv(jax.nn.relu(cur * scale[:c] + bias[:c]), k1)
        new = layer_tail(y, k3)
        buf = lax.dynamic_update_slice(buf, new, (0, 0, 0, c))
        c += GROWTH
    return buf


def measure(fn, params, iters=20):
    """Self-chained block iterations inside one jit -> ms per block."""

    @jax.jit
    def run(x0):
        def body(_, x):
            out = fn(x, params)
            # feed a scalar of the output back in: forces sequential
            # execution without shape growth across iterations
            return x0 + out[..., :1].mean() * 1e-6

        return lax.fori_loop(0, iters, body, x0)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(BATCH, H, W, C0)), DTYPE)
    jax.block_until_ready(run(x))  # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(run(x))
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e3


def main():
    rng = np.random.default_rng(1)
    params = make_params(rng)
    # equivalence check (bf16 tolerance)
    x = jnp.asarray(rng.normal(size=(2, H, W, C0)), DTYPE)
    a = np.asarray(block_concat(x, params), np.float32)
    b = np.asarray(block_segments(x, params), np.float32)
    c = np.asarray(block_buffer(x, params), np.float32)
    np.testing.assert_allclose(a, b, rtol=0.15, atol=0.15)
    np.testing.assert_allclose(a, c, rtol=0.15, atol=0.15)
    for name, fn in [("concat (walker)", block_concat),
                     ("segment-sum", block_segments),
                     ("buffer+dus", block_buffer)]:
        ms = measure(fn, params)
        print(f"{name:18s} {ms:7.2f} ms/block (b{BATCH})")


if __name__ == "__main__":
    main()
