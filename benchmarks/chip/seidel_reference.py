"""The plain reference of seidel-2d: in-place Gauss-Seidel in float64.

Independent of the program under test (it imports nothing of ``repro``).
PolyBench/C 4.2.1 ``stencils/seidel-2d``: each sweep replaces ``A[i][j]``
by the mean of its 3x3 box, in place and in ``(i, j)`` order, so the reads
at ``(i-1, j-1)``, ``(i-1, j)``, ``(i-1, j+1)`` and ``(i, j-1)`` see this
sweep's values and the other five the previous sweep's.  Every site of
``[0, N)^2`` is updated, reads outside it contribute zero (the benchmark's
configuration states this halo), and ``steps`` sweeps are made.

The points of a wavefront ``2i + j = c`` read none of each other and only
points of earlier wavefronts this sweep, so each wavefront is one
vectorized update: a sweep is ``3(N-1) + 1`` of them, not ``N^2`` scalar
steps.
"""
from __future__ import annotations

import numpy as np


def _wavefronts(n: int) -> list:
    """Flat indices into the zero-padded ``(n+2)^2`` grid of each
    wavefront ``2i + j = c``, in order."""
    wd = n + 2
    out = []
    for c in range(3 * (n - 1) + 1):
        i = np.arange(max(0, (c - n + 2) // 2), min(n - 1, c // 2) + 1)
        out.append((i + 1) * wd + (c - 2 * i) + 1)
    return out


def seidel_solve(state: np.ndarray, steps: int) -> np.ndarray:
    """``steps`` in-place 9-point Gauss-Seidel sweeps, in float64."""
    v = np.asarray(state, dtype=np.float64)
    n = v.shape[0]
    wd = n + 2
    u = np.zeros((wd, wd))
    u[1:-1, 1:-1] = v
    u = u.ravel()
    box = np.asarray([a * wd + b for a in (-1, 0, 1) for b in (-1, 0, 1)])
    waves = [(idx, idx[None, :] + box[:, None]) for idx in _wavefronts(n)]
    for _ in range(steps):
        for idx, reads in waves:
            u[idx] = u[reads].sum(axis=0) / 9.0
    return u.reshape(wd, wd)[1:-1, 1:-1].copy()
