"""Layer toolkit of the plain references: straightforward ``jax.numpy`` /
``lax`` in float32, no kernels, no folding, no batching tricks.

The references import nothing of ``sparkdl_tpu``. They only share a
*layout*: variables are a ``{"params": ..., "batch_stats": ...}`` dict whose
leaf names are the published Keras architecture's units as the program's
Flax modules name them, so that the benchmark can make the weights itself
(``init``), hand them to the program, and run its own forward pass over the
very same leaves.

A forward pass is written once against a :class:`Scope`:

- ``Scope.init(key)`` creates every leaf the pass asks for, from the key and
  the leaf's path (so a leaf's value does not depend on call order);
- ``Scope.apply(variables, train=...)`` reads them; with ``train=True``
  BatchNorm uses the batch's statistics and the new running statistics are
  collected in ``scope.new_stats``;
- ``quant`` is the control's hook: a function applied to both operands of
  every convolution and matrix product (identity in the reference proper).

Matrix precision is the caller's: the references are run under
``jax.default_matmul_precision("highest")``.
"""

import zlib

import jax
import jax.numpy as jnp
from jax import lax

BN_MOMENTUM = 0.99


def _leaf_key(key, path):
    return jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))


class Scope:
    def __init__(self, variables=None, key=None, train=False, quant=None):
        self.variables = variables
        self.key = key
        self.train = train
        self.quant = quant or (lambda a: a)
        self.made = {"params": {}, "batch_stats": {}}
        self.new_stats = {}

    @classmethod
    def init(cls, key):
        return cls(key=key)

    @classmethod
    def apply(cls, variables, train=False, quant=None):
        return cls(variables=variables, train=train, quant=quant)

    # -- leaves -------------------------------------------------------------

    def leaf(self, collection, path, shape, make):
        """The leaf at ``path`` of ``collection``; ``make(key, shape)``
        draws it in init mode."""
        if self.variables is not None:
            node = self.variables[collection]
            for name in path:
                node = node[name]
            if node.shape != tuple(shape):
                raise ValueError(f"{'/'.join(path)}: leaf of shape "
                                 f"{node.shape}, the pass needs {shape}")
            return node
        node = self.made[collection]
        for name in path[:-1]:
            node = node.setdefault(name, {})
        if path[-1] not in node:
            node[path[-1]] = make(_leaf_key(self.key, (collection,) + path),
                                  tuple(shape))
        return node[path[-1]]

    def _put_stat(self, path, value):
        node = self.new_stats
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = value


# -- initialisers (the benchmark's own; listed as `assumed` in the configs) --


def he_normal(key, shape):
    """N(0, 2 / fan_in): keeps ReLU activations at unit scale through depth,
    so features and logits are O(1) and not 1e-3."""
    fan_in = 1
    for d in shape[:-1]:
        fan_in *= d
    return jax.random.normal(key, shape, jnp.float32) * (2.0 / fan_in) ** 0.5


def lecun_normal(key, shape):
    fan_in = 1
    for d in shape[:-1]:
        fan_in *= d
    return jax.random.normal(key, shape, jnp.float32) * (1.0 / fan_in) ** 0.5


def small_normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * 0.05


def near_one(key, shape):
    return jax.random.uniform(key, shape, jnp.float32, 0.7, 1.3)


def small_positive(key, shape):
    """The scale of a residual branch's last BatchNorm: U(0.1, 0.3), so
    that a block starts near the identity, as a trained network's does (the
    'zero-init residual' practice, not taken to zero: a zero scale would
    give the branch's other leaves no gradient to compare)."""
    return jax.random.uniform(key, shape, jnp.float32, 0.1, 0.3)


def small_lecun(key, shape):
    return 0.1 * lecun_normal(key, shape)


# -- layers -----------------------------------------------------------------


def conv(scope, path, x, features, kernel, strides=(1, 1), padding="SAME",
         bias=False):
    kh, kw = kernel
    w = scope.leaf("params", path + ("kernel",),
                   (kh, kw, x.shape[-1], features), he_normal)
    y = lax.conv_general_dilated(
        scope.quant(x), scope.quant(w), window_strides=strides,
        padding=padding, dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if bias:
        y = y + scope.leaf("params", path + ("bias",), (features,),
                           small_normal)
    return y


def dense(scope, path, x, features, kernel_init=lecun_normal):
    w = scope.leaf("params", path + ("kernel",), (x.shape[-1], features),
                   kernel_init)
    b = scope.leaf("params", path + ("bias",), (features,), small_normal)
    return jnp.dot(scope.quant(x), scope.quant(w)) + b


def batch_norm(scope, path, x, eps, use_scale=True, scale_init=None):
    """Keras/Flax BatchNorm over the channel axis. Inference: running
    statistics. Training: the batch's mean and biased variance, and the
    running statistics move by ``1 - BN_MOMENTUM`` towards them."""
    c = x.shape[-1]
    mean = scope.leaf("batch_stats", path + ("mean",), (c,), small_normal)
    var = scope.leaf("batch_stats", path + ("var",), (c,), near_one)
    if scope.train:
        axes = tuple(range(x.ndim - 1))
        batch_mean = jnp.mean(x, axes)
        batch_var = jnp.mean(jnp.square(x - batch_mean), axes)
        scope._put_stat(path + ("mean",), BN_MOMENTUM * mean
                        + (1 - BN_MOMENTUM) * batch_mean)
        scope._put_stat(path + ("var",), BN_MOMENTUM * var
                        + (1 - BN_MOMENTUM) * batch_var)
        mean, var = batch_mean, batch_var
    y = (x - mean) * lax.rsqrt(var + eps)
    if use_scale:
        y = y * scope.leaf("params", path + ("scale",), (c,),
                           scale_init or near_one)
    return y + scope.leaf("params", path + ("bias",), (c,), small_normal)


def max_pool(x, window, stride, padding="VALID"):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, window, window, 1),
                             (1, stride, stride, 1), padding)


def avg_pool_same(x, window=3):
    """AveragePooling2D(padding='same'), Keras edge semantics: the divisor
    is the number of valid (unpadded) elements under the window."""
    dims, ones = (1, window, window, 1), (1, 1, 1, 1)
    summed = lax.reduce_window(x, 0.0, lax.add, dims, ones, "SAME")
    counts = lax.reduce_window(jnp.ones((1,) + x.shape[1:3] + (1,), x.dtype),
                               0.0, lax.add, dims, ones, "SAME")
    return summed / counts


def global_avg_pool(x):
    return jnp.mean(x, axis=(1, 2))


def pad2d(x, pad):
    return jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))


@jax.custom_vjp
def fp8_operands(a):
    """The control's precision: operands rounded to float8 (e4m3), the
    nearest format below bfloat16, products accumulated in float32. The
    rounding is straight-through for gradients: the backward pass multiplies
    by the rounded operands but its cotangents are not rounded (unscaled
    float8 cotangents underflow to nought, which would read as no gradient at
    all and say nothing about the precision)."""
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


fp8_operands.defvjp(lambda a: (fp8_operands(a), None), lambda _, g: (g,))
