"""Plain reference: InceptionV3 (Szegedy et al., arXiv:1512.00567) as
``keras.applications.InceptionV3`` builds it — 94 Conv(no bias) + BatchNorm
(no scale, eps 1e-3) + ReLU units named ``cb0`` … ``cb93`` in call order,
mixed0 … mixed10, global average pool → 2048. Input: RGB in [0, 255], NHWC
299×299; ``preprocess`` is Keras's 'tf' mode."""

import jax.numpy as jnp

from references import plain

INPUT_SIZE = (299, 299)
FEATURE_DIM = 2048
CLASSES = 1000
BN_EPS = 1e-3


def preprocess(x):
    return x / 127.5 - 1.0


def forward(scope, x, include_top=False):
    count = [0]

    def cb(h, features, kh, kw, strides=(1, 1), padding="SAME"):
        name = f"cb{count[0]}"
        count[0] += 1
        h = plain.conv(scope, (name, "conv"), h, features, (kh, kw), strides,
                       padding)
        h = plain.batch_norm(scope, (name, "bn"), h, BN_EPS, use_scale=False)
        return jnp.maximum(h, 0.0)

    cat = lambda *parts: jnp.concatenate(parts, axis=-1)  # noqa: E731

    x = cb(x, 32, 3, 3, (2, 2), "VALID")
    x = cb(x, 32, 3, 3, padding="VALID")
    x = cb(x, 64, 3, 3)
    x = plain.max_pool(x, 3, 2)
    x = cb(x, 80, 1, 1, padding="VALID")
    x = cb(x, 192, 3, 3, padding="VALID")
    x = plain.max_pool(x, 3, 2)

    for pool_features in (32, 64, 64):           # mixed0..2, 35×35
        b1 = cb(x, 64, 1, 1)
        b5 = cb(cb(x, 48, 1, 1), 64, 5, 5)
        b3 = cb(cb(cb(x, 64, 1, 1), 96, 3, 3), 96, 3, 3)
        bp = cb(plain.avg_pool_same(x), pool_features, 1, 1)
        x = cat(b1, b5, b3, bp)

    b3 = cb(x, 384, 3, 3, (2, 2), "VALID")       # mixed3, → 17×17
    bd = cb(cb(cb(x, 64, 1, 1), 96, 3, 3), 96, 3, 3, (2, 2), "VALID")
    x = cat(b3, bd, plain.max_pool(x, 3, 2))

    for c7 in (128, 160, 160, 192):              # mixed4..7
        b1 = cb(x, 192, 1, 1)
        b7 = cb(cb(cb(x, c7, 1, 1), c7, 1, 7), 192, 7, 1)
        bd = cb(x, c7, 1, 1)
        bd = cb(cb(cb(cb(bd, c7, 7, 1), c7, 1, 7), c7, 7, 1), 192, 1, 7)
        bp = cb(plain.avg_pool_same(x), 192, 1, 1)
        x = cat(b1, b7, bd, bp)

    b3 = cb(cb(x, 192, 1, 1), 320, 3, 3, (2, 2), "VALID")   # mixed8, → 8×8
    b7 = cb(cb(cb(x, 192, 1, 1), 192, 1, 7), 192, 7, 1)
    b7 = cb(b7, 192, 3, 3, (2, 2), "VALID")
    x = cat(b3, b7, plain.max_pool(x, 3, 2))

    for _ in range(2):                           # mixed9..10
        b1 = cb(x, 320, 1, 1)
        b3 = cb(x, 384, 1, 1)
        b3 = cat(cb(b3, 384, 1, 3), cb(b3, 384, 3, 1))
        bd = cb(cb(x, 448, 1, 1), 384, 3, 3)
        bd = cat(cb(bd, 384, 1, 3), cb(bd, 384, 3, 1))
        bp = cb(plain.avg_pool_same(x), 192, 1, 1)
        x = cat(b1, b3, bd, bp)

    x = plain.global_avg_pool(x)
    if include_top:
        x = plain.dense(scope, ("predictions",), x, CLASSES)
    return x
