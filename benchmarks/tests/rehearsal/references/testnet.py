"""Plain reference of the repo's TestNet (two biased stride-2 3×3
convolutions with ReLU, global average pool, dense head): the rehearsal
model, 32×32 input. Not a benchmark configuration."""

import jax.numpy as jnp

from references import plain

INPUT_SIZE = (32, 32)
FEATURE_DIM = 16
CLASSES = 10


def preprocess(x):
    return x / 127.5 - 1.0


def forward(scope, x, include_top=False):
    x = jnp.maximum(plain.conv(scope, ("conv1",), x, 8, (3, 3), (2, 2),
                               bias=True), 0.0)
    x = jnp.maximum(plain.conv(scope, ("conv2",), x, 16, (3, 3), (2, 2),
                               bias=True), 0.0)
    x = plain.global_avg_pool(x)
    if include_top:
        x = plain.dense(scope, ("predictions",), x, CLASSES)
    return x
