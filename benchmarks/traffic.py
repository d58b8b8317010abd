"""The one traffic generator: a cell's ``traffic`` parameters (a data file
under ``workloads/``) and ``--seed`` in, inputs out. Imports neither JAX nor
the program.

Every seed gives the same *work*: the sizes and counts are the traffic
file's; the seed only draws the contents. Seeds may be larger than 2**31.

kinds
  ``image_arrays``  n uint8 HWC arrays of one size, in memory
  ``train_batches`` k float32 NHWC batches with one-hot labels, in memory
"""

import numpy as np


def _rng(seed, *stream):
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  *stream])


def smooth_images(rng, n, height, width, coarse=(12, 16), out=None):
    """``n`` smooth random colour fields, uint8 (n, height, width, 3): a
    coarse random grid enlarged bicubically. Images that differ from one
    another at every scale, as photos do and white noise does not — white
    noise makes every image look alike to a convolution net, and batch
    statistics then amplify rounding."""
    from PIL import Image

    grids = rng.integers(0, 256, size=(n,) + tuple(coarse) + (3,),
                         dtype=np.uint8)
    if out is None:
        out = np.empty((n, height, width, 3), np.uint8)
    for i in range(n):
        out[i] = np.asarray(Image.fromarray(grids[i]).resize(
            (width, height), Image.BICUBIC))
    return out


# -- image_arrays -------------------------------------------------------------


def image_arrays(params, seed):
    """(n, H, W, 3) uint8, one contiguous array."""
    h, w = params["size"]
    out = np.empty((params["n"], h, w, 3), np.uint8)
    return smooth_images(_rng(seed, 2), params["n"], h, w, out=out)


# -- train_batches ------------------------------------------------------------


def train_batches(params, seed):
    """``distinct`` (x, y) pairs: x float32 (batch, H, W, 3) smooth fields in
    [0, 1], y one-hot float32 over ``classes``; every row differs."""
    h, w = params["size"]
    out = []
    for k in range(params["distinct"]):
        rng = _rng(seed, 3, k)
        x = smooth_images(rng, params["batch"], h, w).astype(np.float32)
        x *= np.float32(1 / 255)
        labels = rng.integers(0, params["classes"], size=params["batch"])
        out.append((x, np.eye(params["classes"], dtype=np.float32)[labels]))
    return out
