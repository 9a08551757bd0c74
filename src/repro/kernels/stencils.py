"""Stencil tile bodies: the compute the EDT graphs of ``core.programs``
synchronize.

The polyhedral programs are written in *time-skewed* coordinates so
orthogonal tiling is legal; the numerics live in unskewed "site" space.
A :class:`StencilSpec` names that semantics once:

* task point ``(t, x...)`` computes the value ``v_t[s]`` of its site
  ``s``, by the spec's iteration-to-site map (``s = x - t`` per dimension
  unless ``site_map`` says otherwise),
* a tap ``(dt, offsets, weight)`` reads ``v_{t-dt}[s + offsets]``,
* reads outside ``[0, N)^d`` contribute zero (a Dirichlet-0 halo),
* ``v_{-1}`` is the initial grid; the solve's answer is ``v_{T-1}``.

Because every tap has ``dt`` in {0, 1}, two buffers suffice: ``v_t`` lives
in parity buffer ``t & 1`` (so the initial grid seeds buffer 1).  Taps
with ``dt == 0`` read sites the *same* time step already wrote —
Gauss-Seidel — which is why :class:`StencilSpec.seq_space` marks spatial
dims that must run sequentially inside a tile; pure Jacobi bodies
vectorize over all spatial dims.  A spec with more than one sequential
dim (or a site map of its own) runs each tile as in-tile wavefronts
(:attr:`StencilSpec.wavefront`).

Three implementations of the same spec live here, used as ladders of
trust by ``tests/test_fused_exec.py``:

* :func:`reference_solve` — plain NumPy, time-major (the ground truth),
* :func:`handwritten_solve` — the hand-tuned jax baseline the fused
  executor is benchmarked against: one ``lax.fori_loop`` over time with
  pad+slice taps (Jacobi), a ``lax.scan`` carry (1-D Seidel) or a
  ``lax.fori_loop`` over the sweep's ``2i + j`` wavefronts (2-D Seidel),
  no task graph, no counters — the best case for a fixed-shape runtime,
* the fused device body itself (``core.edt.fused``), which executes the
  identical taps level by level inside the counted-sync sweep.

This module stays import-light (no jax at module scope): the fused
executor imports it from ``repro.core.edt``, which process-pool workers
load jax-free.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class StencilSpec:
    """One stencil body in unskewed site space.

    ``taps`` is a tuple of ``(dt, offsets, weight)`` with ``dt`` in
    {0, 1}; ``seq_space[k]`` marks spatial dim ``k`` as sequential inside
    a tile (required exactly when some tap has ``dt == 0``, whose offsets
    must then be lexicographically negative).  ``time_param`` /
    ``size_param`` name the polyhedral program's symbolic sizes.
    ``site_map`` is the iteration-to-site map as integer rows, one per
    site dim, over ``(t, x_0, ..., x_{d-1})``: unimodular and lower
    triangular in the ``x`` columns.  Empty means ``s_k = x_k - t``.
    """

    name: str
    space: int
    taps: tuple
    seq_space: tuple
    time_param: str = "T"
    size_param: str = "N"
    site_map: tuple = ()

    @property
    def sequential(self) -> bool:
        return any(self.seq_space)

    @property
    def wavefront(self) -> bool:
        """A tile runs as in-tile wavefronts (a loop over the hyperplanes
        ``t + x_0 + ... = c`` of its local points) rather than as
        unrolled sub-steps: more than one sequential dim, or a site map
        of its own."""
        return sum(map(bool, self.seq_space)) > 1 or bool(self.site_map)

    @property
    def site_rows(self) -> tuple:
        """``site_map``, spelled out: row ``k`` gives site dim ``k``."""
        if self.site_map:
            return self.site_map
        return tuple((-1,) + tuple(int(j == k) for j in range(self.space))
                     for k in range(self.space))

    def distance(self, dt: int, off) -> tuple:
        """The iteration-space distance ``(dt, dx...)``, target minus
        source, of the tap ``(dt, off)``: the inverse of the site map
        applied to ``-off`` at ``dt`` steps back."""
        dx = []
        for k, row in enumerate(self.site_rows):
            r = -off[k] - row[0] * dt - sum(row[1 + j] * dx[j]
                                            for j in range(k))
            dx.append(r * row[1 + k])      # a unimodular diagonal is +-1
        return (dt, *dx)

    def shape(self, extent: int) -> tuple:
        return (extent,) * self.space


def _box_taps(space: int) -> tuple:
    offs = list(itertools.product((-1, 0, 1), repeat=space))
    w = 1.0 / len(offs)
    return tuple((1, off, w) for off in offs)


#: PolyBench seidel-2d's reads: four of this sweep's values, five of the
#: previous sweep's, each weighted 1/9 (the in-place 9-point average).
_SEIDEL2D_TAPS = (
    tuple((0, off, 1.0 / 9) for off in ((-1, -1), (-1, 0), (-1, 1), (0, -1)))
    + tuple((1, off, 1.0 / 9)
            for off in ((0, 0), (0, 1), (1, -1), (1, 0), (1, 1))))

#: Specs for the stencil programs of ``repro.core.programs`` (keyed by
#: the PROGRAMS name).  The site offsets are the skewed dependence
#: offsets shifted by the time skew: x_t - x_s in [0, 2] becomes
#: s-offsets {-1, 0, 1} at dt = 1.
SPECS = {
    "stencil1d": StencilSpec("stencil1d", 1, _box_taps(1), (False,)),
    "jacobi2d": StencilSpec("jacobi2d", 2, _box_taps(2), (False, False)),
    "heat3d": StencilSpec("heat3d", 3, _box_taps(3), (False,) * 3),
    # Gauss-Seidel: half the value from this step's left neighbor (the
    # skewed "sweep" dependence), half from last step's right neighbor
    # (the skewed "carry") — the x dim is sequential.
    "seidel1d": StencilSpec("seidel1d", 1,
                            ((0, (-1,), 0.5), (1, (1,), 0.5)), (True,)),
    # Gauss-Seidel-2D under the skew (t, x0, x1) = (t, t + i, 2t + i + j):
    # sites s0 = x0 - t, s1 = x1 - t - x0, both dims sequential.
    "seidel2d": StencilSpec("seidel2d", 2, _SEIDEL2D_TAPS, (True, True),
                            site_map=((-1, 1, 0), (-1, -1, 1))),
}


def default_state(spec: StencilSpec, extent: int, dtype=np.float32):
    """A deterministic, non-smooth initial grid (linear fields would let
    indexing bugs cancel under averaging stencils)."""
    size = extent ** spec.space
    v = (np.arange(size, dtype=np.int64) * 2654435761) % 1021
    return (v.astype(np.float64) / 1021.0).astype(dtype).reshape(
        spec.shape(extent))


def _shift(a: "np.ndarray", off) -> "np.ndarray":
    """``out[s] = a[s + off]`` with zeros shifted in at the boundary."""
    out = np.zeros_like(a)
    dst, src = [], []
    for k, o in enumerate(off):
        n = a.shape[k]
        lo, hi = max(0, -o), n - max(0, o)
        dst.append(slice(lo, hi))
        src.append(slice(lo + o, hi + o))
    out[tuple(dst)] = a[tuple(src)]
    return out


def reference_solve(spec: StencilSpec, state: "np.ndarray",
                    steps: int) -> "np.ndarray":
    """Ground truth: time-major NumPy execution of the spec.

    Jacobi-style specs (all taps at ``dt == 1``) run as vectorized
    shifts; Gauss-Seidel specs run the honest ordered scalar loop (site
    lex order — the order the skewed schedule implies)."""
    prev = np.array(state)
    ty = prev.dtype.type
    for _ in range(steps):
        if not spec.sequential:
            acc = None
            for _, off, w in spec.taps:
                term = ty(w) * _shift(prev, off)
                acc = term if acc is None else acc + term
            prev = acc
            continue
        cur = np.zeros_like(prev)
        for idx in np.ndindex(prev.shape):
            acc = ty(0)
            for dt, off, w in spec.taps:
                j = tuple(i + o for i, o in zip(idx, off))
                if all(0 <= jj < n for jj, n in zip(j, prev.shape)):
                    acc = acc + ty(w) * (cur[j] if dt == 0 else prev[j])
            cur[idx] = acc
        prev = cur
    return prev


def handwritten_solve(spec: StencilSpec, state: "np.ndarray",
                      steps: int) -> "np.ndarray":
    """The hand-tuned jax baseline: the same solve with no task graph.

    Dense Jacobi bodies are one ``lax.fori_loop`` over time whose body is
    a pad + 3^d static slices; the 1-D Seidel recurrence is a ``lax.scan``
    carry inside the time loop, the 2-D one a loop over each sweep's
    ``2i + j`` wavefronts (:func:`_wavefront_sweeps`).  This is what a
    performance engineer would write given the *whole* problem up front —
    the fused EDT sweep is priced against it in
    ``benchmarks/bench_fused.py``.
    """
    import jax.numpy as jnp
    from jax import lax

    n = state.shape[0]
    u0 = jnp.asarray(state)

    if not spec.sequential:
        def step(_, u):
            p = jnp.pad(u, 1)
            acc = None
            for _, off, w in spec.taps:
                start = tuple(1 + o for o in off)
                term = w * lax.slice(p, start, tuple(s + n for s in start))
                acc = term if acc is None else acc + term
            return acc

        return np.asarray(lax.fori_loop(0, steps, step, u0))

    if spec.space == 2:
        return np.asarray(_wavefront_sweeps(spec, u0, steps))
    if spec.space != 1:
        raise NotImplementedError(
            "handwritten sequential baseline is 1-D or 2-D only")
    seq = [(off, w) for dt, off, w in spec.taps if dt == 0]
    if seq != [((-1,), seq[0][1])]:
        raise NotImplementedError(
            "sequential baseline expects a single dt=0 tap at offset -1")
    w0 = seq[0][1]

    def step(_, u):
        p = jnp.pad(u, 1)
        pre = None
        for dt, off, w in spec.taps:
            if dt == 0:
                continue
            term = w * lax.slice(p, (1 + off[0],), (1 + off[0] + n,))
            pre = term if pre is None else pre + term

        def carry(c, b):
            v = w0 * c + b
            return v, v

        _, out = lax.scan(carry, jnp.zeros((), u.dtype), pre)
        return out

    return np.asarray(lax.fori_loop(0, steps, step, u0))


def _wavefront_sweeps(spec: StencilSpec, u0, steps: int):
    """2-D Gauss-Seidel in place: per sweep, a ``lax.fori_loop`` over the
    wavefronts ``2i + j = c``.  A ``dt == 0`` tap must lie on an earlier
    wavefront (``2 o_i + o_j < 0``: written this sweep) and a ``dt == 1``
    tap on this one or a later one (not yet written), so one zero-padded
    array serves both."""
    import jax.numpy as jnp
    from jax import lax

    if any((2 * off[0] + off[1] < 0) != (dt == 0)
           for dt, off, _ in spec.taps):
        raise NotImplementedError(
            "2-D sequential baseline expects dt=0 taps before the 2i+j "
            "wavefront and dt=1 taps on or after it")
    n = u0.shape[0]
    wd = n + 2                                 # padded row length
    lanes = jnp.arange(n // 2 + 1, dtype=jnp.int32)
    inside = wd + 1                            # (0, 0): masked lanes read it

    def wave(c, u):
        i = jnp.maximum(0, (c - n + 2) // 2) + lanes
        ok = (i <= c // 2) & (i < n)
        idx = jnp.where(ok, (i + 1) * wd + (c - 2 * i) + 1, inside)
        acc = None
        for _, off, w in spec.taps:
            term = w * u[idx + off[0] * wd + off[1]]
            acc = term if acc is None else acc + term
        return u.at[jnp.where(ok, idx, u.size)].set(acc, mode="drop")

    def sweep(_, u):
        return lax.fori_loop(0, 3 * (n - 1) + 1, wave, u)

    u = jnp.pad(u0, 1).reshape(-1)
    u = lax.fori_loop(0, steps, sweep, u)
    return u.reshape(wd, wd)[1:-1, 1:-1]
