"""The benchmark's own traffic patterns and rate grid.

Copies of the simulator's static patterns (paper §V-C/D) and of its
saturation rate grid, kept here so that a change to the program cannot
change the work a cell measures.  Each pattern maps a layout (`n`,
centres `pos` in pitch units) to an [n, n] matrix whose row i is the
destination distribution of node i.
"""
from __future__ import annotations

import numpy as np


def _normalize(m: np.ndarray) -> np.ndarray:
    np.fill_diagonal(m, 0.0)
    rows = m.sum(axis=1, keepdims=True)
    return np.divide(m, rows, out=np.zeros_like(m), where=rows > 0)


def uniform(n: int, pos: np.ndarray, seed: int) -> np.ndarray:
    return _normalize(np.ones((n, n)))


def permutation(n: int, pos: np.ndarray, seed: int) -> np.ndarray:
    """Each node sends everything to one destination of a random
    derangement drawn from `seed` (8 uniform draws, then a cyclic shift
    of a random order)."""
    rng = np.random.default_rng(seed)
    for _ in range(8):
        perm = rng.permutation(n)
        if not np.any(perm == np.arange(n)):
            break
    else:
        order = rng.permutation(n)
        perm = np.empty(n, dtype=np.int64)
        perm[order] = np.roll(order, -1)
    m = np.zeros((n, n))
    m[np.arange(n), perm] = 1.0
    return _normalize(m)


def tornado(n: int, pos: np.ndarray, seed: int) -> np.ndarray:
    """Half-machine offset in row-major rank order."""
    order = np.lexsort((pos[:, 0], pos[:, 1]))
    rank = np.empty(n, dtype=int)
    rank[order] = np.arange(n)
    m = np.zeros((n, n))
    m[np.arange(n), order[(rank + n // 2) % n]] = 1.0
    return _normalize(m)


def neighbor(n: int, pos: np.ndarray, seed: int) -> np.ndarray:
    """Uniform over the chiplets within 1.75 pitch (the nearest one
    where there is none)."""
    d = np.sqrt(((pos[:, None, :] - pos[None, :, :]) ** 2).sum(-1))
    m = ((d > 0) & (d <= 1.75)).astype(float)
    for i in range(n):
        if m[i].sum() == 0:
            m[i, np.argsort(d[i])[1]] = 1.0
    return _normalize(m)


PATTERNS = {"uniform": uniform, "permutation": permutation,
            "tornado": tornado, "neighbor": neighbor}


def rate_grid(analytic: float, n_rates: int, headroom: float) -> np.ndarray:
    """Offered rates from a quarter of the analytic bound (at least
    1e-3) up to `headroom` times it (at most 1)."""
    return np.linspace(max(analytic * 0.25, 1e-3),
                       min(1.0, headroom * analytic), n_rates)
