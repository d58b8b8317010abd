"""Plain reference: ResNet50 v1 (He et al., arXiv:1512.03385) as
``keras.applications.ResNet50`` builds it — biased convolutions, BatchNorm
eps 1.001e-5, stride 2 on the first 1×1 of a downsampling block, a 3-pixel
zero pad and a VALID 7×7 stem, 1000-way dense head. ``forward`` returns
logits with ``include_top``; the probabilities are ``softmax`` of them."""

import jax.numpy as jnp

from references import plain

INPUT_SIZE = (224, 224)
FEATURE_DIM = 2048
CLASSES = 1000
BN_EPS = 1.001e-5
STACKS = (3, 4, 6, 3)


def preprocess(x):
    """Keras 'caffe' mode: RGB → BGR, ImageNet means off."""
    return x[..., ::-1] - jnp.asarray((103.939, 116.779, 123.68), x.dtype)


def _block(scope, name, x, filters, stride, conv_shortcut):
    def unit(h, i, features, kernel, strides=(1, 1), padding="VALID"):
        h = plain.conv(scope, (name, f"conv_{i}"), h, features, kernel,
                       strides, padding, bias=True)
        return plain.batch_norm(
            scope, (name, f"bn_{i}"), h, BN_EPS,
            scale_init=plain.small_positive if i == 3 else None)

    s = (stride, stride)
    shortcut = unit(x, 0, 4 * filters, (1, 1), s) if conv_shortcut else x
    y = jnp.maximum(unit(x, 1, filters, (1, 1), s), 0.0)
    y = jnp.maximum(unit(y, 2, filters, (3, 3), padding="SAME"), 0.0)
    y = unit(y, 3, 4 * filters, (1, 1))
    return jnp.maximum(shortcut + y, 0.0)


def forward(scope, x, include_top=True, stacks=STACKS):
    x = plain.pad2d(x, 3)
    x = plain.conv(scope, ("conv1_conv",), x, 64, (7, 7), (2, 2), "VALID",
                   bias=True)
    x = jnp.maximum(plain.batch_norm(scope, ("conv1_bn",), x, BN_EPS), 0.0)
    x = plain.max_pool(plain.pad2d(x, 1), 3, 2)
    for stage, (filters, blocks) in enumerate(zip((64, 128, 256, 512),
                                                  stacks)):
        for i in range(1, blocks + 1):
            x = _block(scope, f"conv{stage + 2}_block{i}", x, filters,
                       stride=2 if (i == 1 and stage > 0) else 1,
                       conv_shortcut=(i == 1))
    x = plain.global_avg_pool(x)
    if include_top:
        x = plain.dense(scope, ("predictions",), x, CLASSES,
                        kernel_init=plain.small_lecun)
    return x
