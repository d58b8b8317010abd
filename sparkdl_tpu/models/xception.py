"""Xception in Flax — keras.applications.xception parity.

Named model in the reference registry (SURVEY.md §2.1
``keras_applications.py``): 299x299, [-1,1] preprocessing, 2048-d features.

Entry flow (blocks 1-4), middle flow (blocks 5-12, 728ch), exit flow
(blocks 13-14). SeparableConv = depthwise+pointwise, no bias; residual 1x1
convs stride 2; BN keras defaults (eps 1e-3). 'SAME'-padded max pools.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp

from sparkdl_tpu.models.layers import (
    KERAS_BN_EPS, SeparableConvBN, classifier_head, global_avg_pool,
)


class Xception(nn.Module):
    include_top: bool = True
    classes: int = 1000
    classifier_activation: Optional[str] = "softmax"
    pooling: Optional[str] = "avg"
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        bn = lambda name: nn.BatchNorm(  # noqa: E731
            use_running_average=not train, epsilon=KERAS_BN_EPS,
            momentum=0.99, dtype=self.dtype, name=name)

        def sep(h, features, name):
            return SeparableConvBN(features, dtype=self.dtype,
                                   name=name)(h, train)

        # Entry flow: block 1 (plain convs)
        x = nn.Conv(32, (3, 3), strides=(2, 2), padding="VALID",
                    use_bias=False, dtype=self.dtype, name="block1_conv1")(x)
        x = nn.relu(bn("block1_conv1_bn")(x))
        x = nn.Conv(64, (3, 3), padding="VALID", use_bias=False,
                    dtype=self.dtype, name="block1_conv2")(x)
        x = nn.relu(bn("block1_conv2_bn")(x))

        # Entry flow blocks 2-4: sepconv pairs with strided-pool residuals
        for i, features in zip((2, 3, 4), (128, 256, 728)):
            residual = nn.Conv(features, (1, 1), strides=(2, 2),
                               padding="SAME", use_bias=False,
                               dtype=self.dtype, name=f"block{i}_res_conv")(x)
            residual = bn(f"block{i}_res_bn")(residual)
            if i > 2:
                x = nn.relu(x)
            x = sep(x, features, f"block{i}_sepconv1")
            x = nn.relu(x)
            x = sep(x, features, f"block{i}_sepconv2")
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
            x = x + residual

        # Middle flow: blocks 5-12
        for i in range(5, 13):
            residual = x
            x = nn.relu(x)
            x = sep(x, 728, f"block{i}_sepconv1")
            x = nn.relu(x)
            x = sep(x, 728, f"block{i}_sepconv2")
            x = nn.relu(x)
            x = sep(x, 728, f"block{i}_sepconv3")
            x = x + residual

        # Exit flow: block 13
        residual = nn.Conv(1024, (1, 1), strides=(2, 2), padding="SAME",
                           use_bias=False, dtype=self.dtype,
                           name="block13_res_conv")(x)
        residual = bn("block13_res_bn")(residual)
        x = nn.relu(x)
        x = sep(x, 728, "block13_sepconv1")
        x = nn.relu(x)
        x = sep(x, 1024, "block13_sepconv2")
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        x = x + residual

        # Exit flow: block 14
        x = sep(x, 1536, "block14_sepconv1")
        x = nn.relu(x)
        x = sep(x, 2048, "block14_sepconv2")
        x = nn.relu(x)

        if self.include_top:
            x = global_avg_pool(x)
            return classifier_head(x, self.classes,
                                   self.classifier_activation, self.dtype)
        if self.pooling == "avg":
            return global_avg_pool(x)
        if self.pooling == "max":
            return jnp.max(x, axis=(1, 2))
        return x
