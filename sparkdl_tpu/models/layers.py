"""Shared Flax building blocks for the model zoo.

These mirror the exact op semantics of the Keras reference architectures
(keras.src.applications — public code, inspected in-env) so that converted
Keras weights reproduce outputs bit-for-bit (up to float assoc). Notably:

- ``conv_bn``: Conv (no bias) + BatchNorm + ReLU, the InceptionV3 unit
  (BN scale=False, eps 1e-3 — Keras defaults).
- Keras's ZeroPadding2D + 'valid' conv differs from SAME for stride-2
  (symmetric pad vs XLA SAME's asymmetric); ``pad2d`` reproduces the
  explicit-pad variants.
- All modules take ``train``: BatchNorm uses batch stats + mutable
  ``batch_stats`` when training, running averages at inference.

Everything is NHWC with channels-last params (HWIO conv kernels — the same
layout Keras uses, so weight conversion is copy-through).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple, Union

import flax.linen as nn
import jax.numpy as jnp

Dtype = Any

KERAS_BN_EPS = 1e-3          # keras BatchNormalization default
RESNET_BN_EPS = 1.001e-5     # keras resnet.py blocks


def pad2d(x: jnp.ndarray, pad: Union[int, Tuple[Tuple[int, int], Tuple[int, int]]]
          ) -> jnp.ndarray:
    """ZeroPadding2D equivalent on NHWC."""
    if isinstance(pad, int):
        pad = ((pad, pad), (pad, pad))
    return jnp.pad(x, ((0, 0), pad[0], pad[1], (0, 0)))


def correct_pad(x: jnp.ndarray, kernel_size: int
                ) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """keras imagenet_utils.correct_pad for stride-2 'valid' convs (NHWC)."""
    h, w = x.shape[1], x.shape[2]
    adjust = (1 - h % 2, 1 - w % 2)
    correct = kernel_size // 2
    return ((correct - adjust[0], correct), (correct - adjust[1], correct))


def max_pool(x, window: int, stride: int, padding="VALID"):
    # NOTE (profiled, r3): rewriting the overlapping pools as shifted strided
    # slices combined elementwise looked attractive (reduce_window is ~18%
    # of InceptionV3 device time) but measured SLOWER end-to-end on TPU —
    # the slice form degrades the layouts XLA picks for the downstream convs
    # (whole-model 7.3k -> 6.5k img/s). Keep reduce_window.
    return nn.max_pool(x, (window, window), strides=(stride, stride),
                       padding=padding)


def avg_pool_same(x, window: int = 3, stride: int = 1):
    """AveragePooling2D(padding='same') with Keras edge semantics.

    Keras/TF 'same' average pooling divides by the count of *valid* (non-pad)
    elements at the edges; naive mean-over-window with zero pads divides by
    the full window. Reproduce by average-pooling ones to get the count
    correction factor.
    """
    zero = jnp.asarray(0.0, x.dtype)  # init must match operand dtype (bf16)
    summed = nn.pool(x, zero, jnp.add, (window, window), (stride, stride),
                     "SAME")
    ones = jnp.ones(x.shape[1:3] + (1,), dtype=x.dtype)[None]
    counts = nn.pool(ones, zero, jnp.add, (window, window), (stride, stride),
                     "SAME")
    return summed / counts


class ConvBN(nn.Module):
    """Conv2D(use_bias=False) + BatchNorm + optional ReLU (InceptionV3 unit).

    Keras parity: BN epsilon defaults to 1e-3; InceptionV3 sets scale=False.
    """

    features: int
    kernel: Tuple[int, int]
    strides: Tuple[int, int] = (1, 1)
    padding: Union[str, Sequence[Tuple[int, int]]] = "SAME"
    bn_scale: bool = False
    bn_eps: float = KERAS_BN_EPS
    act: bool = True
    dtype: Optional[Dtype] = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        y = nn.Conv(self.features, self.kernel, strides=self.strides,
                    padding=self.padding, use_bias=False, dtype=self.dtype,
                    name="conv")(x)
        y = nn.BatchNorm(use_running_average=not train, epsilon=self.bn_eps,
                         use_scale=self.bn_scale, momentum=0.99,
                         dtype=self.dtype, name="bn")(y)
        if self.act:
            y = nn.relu(y)
        return y


class SeparableConvBN(nn.Module):
    """SeparableConv2D(use_bias=False) + BatchNorm (Xception unit).

    Keras SeparableConv2D = depthwise (H,W,1 per channel) then pointwise
    1x1; flax expresses depthwise as feature_group_count=C with C output
    features.
    """

    features: int
    kernel: Tuple[int, int] = (3, 3)
    strides: Tuple[int, int] = (1, 1)
    bn_eps: float = KERAS_BN_EPS
    dtype: Optional[Dtype] = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        in_ch = x.shape[-1]
        y = nn.Conv(in_ch, self.kernel, strides=self.strides, padding="SAME",
                    feature_group_count=in_ch, use_bias=False,
                    dtype=self.dtype, name="depthwise")(x)
        y = nn.Conv(self.features, (1, 1), use_bias=False, dtype=self.dtype,
                    name="pointwise")(y)
        y = nn.BatchNorm(use_running_average=not train, epsilon=self.bn_eps,
                         momentum=0.99, dtype=self.dtype, name="bn")(y)
        return y


def classifier_head(x, classes: int, activation: Optional[str],
                    dtype=None, name: str = "predictions"):
    x = nn.Dense(classes, dtype=dtype, name=name)(x)
    if activation == "softmax":
        x = nn.softmax(x)
    return x


def global_avg_pool(x):
    return jnp.mean(x, axis=(1, 2))
