"""InceptionV3 in Flax — keras.applications.inception_v3 parity.

The reference's flagship featurizer model (``DeepImageFeaturizer
modelName="InceptionV3"``, SURVEY.md §3.1): 299x299 input, [-1,1]
preprocessing, 2048-d pre-logit features.

Every conv is ConvBN (no bias, BN scale=False, eps 1e-3); block structure
matched line-by-line to keras.src.applications.inception_v3 (mixed0..10).
ConvBN units are named ``cb{i}`` in call order — the weight converter maps
Keras's Conv2D/BatchNormalization build order onto the same indices.

Inference through the registry runs ``inception_fast.py`` over the same
variables tree; this module stays as the definition of that tree (weight
conversion, training) and as the reference the fast apply is tested
against (``tests/models/test_inception_fast.py``).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp

from sparkdl_tpu.models.layers import (
    ConvBN, avg_pool_same, classifier_head, global_avg_pool, max_pool,
)


class InceptionV3(nn.Module):
    include_top: bool = True
    classes: int = 1000
    classifier_activation: Optional[str] = "softmax"
    pooling: Optional[str] = "avg"
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        idx = [0]

        def cb(h, features, kh, kw, strides=(1, 1), padding="SAME"):
            m = ConvBN(features, (kh, kw), strides=strides, padding=padding,
                       bn_scale=False, dtype=self.dtype, name=f"cb{idx[0]}")
            idx[0] += 1
            return m(h, train)

        # Stem
        x = cb(x, 32, 3, 3, strides=(2, 2), padding="VALID")
        x = cb(x, 32, 3, 3, padding="VALID")
        x = cb(x, 64, 3, 3)
        x = max_pool(x, 3, 2)
        x = cb(x, 80, 1, 1, padding="VALID")
        x = cb(x, 192, 3, 3, padding="VALID")
        x = max_pool(x, 3, 2)

        # mixed 0..2: 35x35 inception-A blocks (pool branch 32, 64, 64)
        for pool_features in (32, 64, 64):
            b1 = cb(x, 64, 1, 1)
            b5 = cb(x, 48, 1, 1)
            b5 = cb(b5, 64, 5, 5)
            b3 = cb(x, 64, 1, 1)
            b3 = cb(b3, 96, 3, 3)
            b3 = cb(b3, 96, 3, 3)
            bp = avg_pool_same(x)
            bp = cb(bp, pool_features, 1, 1)
            x = jnp.concatenate([b1, b5, b3, bp], axis=-1)

        # mixed 3: 17x17 reduction
        b3 = cb(x, 384, 3, 3, strides=(2, 2), padding="VALID")
        bd = cb(x, 64, 1, 1)
        bd = cb(bd, 96, 3, 3)
        bd = cb(bd, 96, 3, 3, strides=(2, 2), padding="VALID")
        bp = max_pool(x, 3, 2)
        x = jnp.concatenate([b3, bd, bp], axis=-1)

        # mixed 4..7: 17x17 inception-B blocks (7x7 factorized)
        for c7 in (128, 160, 160, 192):
            b1 = cb(x, 192, 1, 1)
            b7 = cb(x, c7, 1, 1)
            b7 = cb(b7, c7, 1, 7)
            b7 = cb(b7, 192, 7, 1)
            bd = cb(x, c7, 1, 1)
            bd = cb(bd, c7, 7, 1)
            bd = cb(bd, c7, 1, 7)
            bd = cb(bd, c7, 7, 1)
            bd = cb(bd, 192, 1, 7)
            bp = avg_pool_same(x)
            bp = cb(bp, 192, 1, 1)
            x = jnp.concatenate([b1, b7, bd, bp], axis=-1)

        # mixed 8: 8x8 reduction
        b3 = cb(x, 192, 1, 1)
        b3 = cb(b3, 320, 3, 3, strides=(2, 2), padding="VALID")
        b7 = cb(x, 192, 1, 1)
        b7 = cb(b7, 192, 1, 7)
        b7 = cb(b7, 192, 7, 1)
        b7 = cb(b7, 192, 3, 3, strides=(2, 2), padding="VALID")
        bp = max_pool(x, 3, 2)
        x = jnp.concatenate([b3, b7, bp], axis=-1)

        # mixed 9..10: 8x8 inception-C blocks (split 3x3 branches)
        for _ in range(2):
            b1 = cb(x, 320, 1, 1)
            b3 = cb(x, 384, 1, 1)
            b3a = cb(b3, 384, 1, 3)
            b3b = cb(b3, 384, 3, 1)
            b3 = jnp.concatenate([b3a, b3b], axis=-1)
            bd = cb(x, 448, 1, 1)
            bd = cb(bd, 384, 3, 3)
            bda = cb(bd, 384, 1, 3)
            bdb = cb(bd, 384, 3, 1)
            bd = jnp.concatenate([bda, bdb], axis=-1)
            bp = avg_pool_same(x)
            bp = cb(bp, 192, 1, 1)
            x = jnp.concatenate([b1, b3, bd, bp], axis=-1)

        if self.include_top:
            x = global_avg_pool(x)
            return classifier_head(x, self.classes,
                                   self.classifier_activation, self.dtype)
        if self.pooling == "avg":
            return global_avg_pool(x)
        if self.pooling == "max":
            return jnp.max(x, axis=(1, 2))
        return x
