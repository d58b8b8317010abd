"""Token traffic: a cell's ``traffic`` parameters and ``--seed`` in, windows
of token ids out. Imports neither JAX nor the program (``traffic.py`` makes
the image kinds).

kind
  ``token_windows``  n windows of ``window`` int32 ids drawn from ``vocab``
                     ids by Zipf's law (p(rank r) ∝ r^−exponent); which id
                     has which rank is a permutation drawn from the seed, so
                     every seed has the same *work* and other frequent ids.
"""

import numpy as np


def token_windows(params, seed):
    """(n, window) int32, C-contiguous."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 5])
    vocab = params["vocab"]
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -params["exponent"]
    ranks = rng.choice(vocab, size=(params["n"], params["window"]),
                       p=p / p.sum())
    return rng.permutation(vocab).astype(np.int32)[ranks]
