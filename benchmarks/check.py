"""The comparison that decides ``correct``: what the timed path produced
against the plain reference. Each number compared has a limit of its own in
the cell's file (``limits``), set from readings listed in PERF.md; a number
without a limit there fails the run (a limit is never a default)."""

import math

import numpy as np


def decide(numbers, limits):
    """``numbers`` name → value. Returns ``(correct, compared)`` with
    ``compared`` name → ``[value, limit]``; a value that is not finite, or a
    number with no limit, is not correct."""
    compared, correct = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        compared[name] = [value, limit]
        if limit is None or not math.isfinite(value) or value > limit:
            correct = False
    return correct, compared


# -- rows of features ---------------------------------------------------------


def feature_angle_gap(got, want):
    """Worst row's sine of the angle between ``got`` and ``want``: the gap
    that is left once the best common factor is taken out of the row."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return math.inf
    factor = np.sum(got * want, -1) / np.maximum(np.sum(got * got, -1),
                                                  1e-300)
    rest = np.linalg.norm(factor[:, None] * got - want, axis=-1)
    return float(np.max(rest / np.linalg.norm(want, axis=-1)))


# -- training ------------------------------------------------------------------


def _leaves(tree, prefix=()):
    if isinstance(tree, dict):
        for key in sorted(tree):
            yield from _leaves(tree[key], prefix + (key,))
    else:
        yield "/".join(prefix), np.asarray(tree, np.float64)


def leaf_norms(tree):
    return {path: float(np.linalg.norm(leaf)) for path, leaf in _leaves(tree)}


def tree_sub(a, b):
    if isinstance(a, dict):
        return {k: tree_sub(a[k], b[k]) for k in a}
    return np.asarray(a, np.float64) - np.asarray(b, np.float64)


def leaf_gaps(got_norms, want_norms, keep=None):
    """Per leaf: |‖got‖ − ‖want‖| over max(‖want‖ of the leaf, ‖want‖ of
    the median leaf) — the gap between the norms, not the norm of the
    difference. ``keep`` restricts the leaves. Returns ``{leaf: gap}``."""
    names = [n for n in want_norms if keep is None or n in keep]
    median = float(np.median([want_norms[n] for n in names]))
    return {n: abs(got_norms[n] - want_norms[n]) / max(want_norms[n], median)
            for n in names}


def worst_and_median(gaps):
    """``(worst gap, its leaf, median gap)``; a gap that is not a number
    (nan) is the worst."""
    values = np.asarray(list(gaps.values()), np.float64)
    if not np.isfinite(values).all():
        return math.inf, None, math.inf
    where = max(gaps, key=gaps.get)
    return float(gaps[where]), where, float(np.median(values))


def moving_leaves(reference_grad_norms, floor=1e-3):
    """Leaves whose gradient in the reference is at least ``floor`` of the
    median leaf's. The others (a convolution's bias in front of a BatchNorm
    that subtracts the batch mean) have a gradient of nought to rounding and
    move by round-off alone: they are left out of the comparisons, by this
    rule on the reference's own gradient and not by name."""
    median = float(np.median(list(reference_grad_norms.values())))
    return {name for name, norm in reference_grad_norms.items()
            if norm >= floor * median}
