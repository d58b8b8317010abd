"""The ops every sequence stack calls (``models/latent_moe.py``,
``models/shortconv_moe.py``, ``models/state_space.py``): the RMSNorm, the
mixed-precision product, the gated MLP and the causal taps of a depthwise
convolution. Precision follows the weights: a product runs in the weights'
dtype with float32 accumulation; the norms' statistics, the taps and the
residual stream are float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

LANES = 128                     # a vector register's lanes: a kernel's tiles
# a score that loses to every real one and keeps exp() finite in a kernel
MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)


def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def mixed_dot(x, w, out=jnp.float32):
    """x · w in the weights' dtype, accumulated in float32."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32
                   ).astype(out)


def gated_mlp(p, x, limit=0.0):
    """``W_down (silu(x W_gate) ⊙ x W_up)``; with a ``limit`` (a layer's
    ``swiglu_limit``, 0: none) ``x W_gate`` is first held under it and ``x
    W_up`` within ± it."""
    act = p["down"].dtype
    gate = mixed_dot(x, p["gate"])
    if limit:
        gate = jnp.minimum(gate, limit)
    gate = jax.nn.silu(gate)
    up = mixed_dot(x, p["up"])
    if limit:
        up = jnp.clip(up, -limit, limit)
    return mixed_dot((gate * up).astype(act), p["down"])


def causal_taps(z, taps, bias=None):
    """A depthwise causal convolution over windows z (B, T, channels)
    float32, zeros before the window: ``c[t] = Σ_j taps[:, j] ⊙ z[t − (L − 1)
    + j]`` with ``taps`` (channels, L), plus ``bias`` (channels,) where given;
    taps and bias float32. The gated short convolution's loop and the
    state-space mixer's."""
    T = z.shape[1]
    taps = taps.astype(jnp.float32)
    L = taps.shape[1]
    z = jnp.pad(z, ((0, 0), (L - 1, 0), (0, 0)))
    c = sum(taps[:, j] * z[:, j:j + T] for j in range(L))
    return c if bias is None else c + bias.astype(jnp.float32)
