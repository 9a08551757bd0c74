"""Fused device execution: task bodies inside the counted-sync sweep.

:class:`~repro.core.edt.device.DeviceExecutor` (PR 5) runs the §2 counted
synchronization model on device but computes nothing — the frontier math
is real, the tiles are phantoms.  This module closes the gap for the
stencil family: one jitted XLA program both decrements the counters
(keeping the transpose-CSR segment-sum decrement — XLA-CPU scatter-add
measured ~10x slower on million-edge graphs) **and** executes every tile
the frontier enables, so a ≥1M-task jacobi2d solve never returns to the
host between wavefronts.  That is the "A Tale of Three Runtimes" claim
made concrete: generated EDT code priced head-to-head against the
hand-written ``lax.fori_loop``/``lax.scan`` stencil of the same problem
(:func:`repro.kernels.stencils.handwritten_solve`,
``benchmarks/bench_fused.py``).

State layout
------------
The grid lives in one flat device vector ``u`` of ``2*S + 1`` elements
(``S = N^d`` sites):

* ``u[p*S + flat(site)]`` holds ``v_t[site]`` for time parity ``p = t & 1``
  (taps reach at most one step back, so two buffers suffice; the initial
  grid ``v_{-1}`` seeds parity 1),
* ``u[2*S]`` is a zero slot that every masked/out-of-range tap gathers
  from (the Dirichlet-0 halo),
* masked lanes *scatter* to index ``2*S + 1`` — out of bounds, dropped by
  ``mode="drop"`` — so padding never corrupts the halo zero.

Per level the sweep gathers the level's task ids (one fixed-width
``dynamic_slice``, exactly as the replay decrement does), looks up each
task's **tile origin** row (:func:`pack_origins` — tile coords × tile
sizes, with a sentinel row of negative time at index ``n`` that masks the
padded lanes), and runs the tile body: local offsets within a tile are a
*static* structure (``tt`` sequential over the tile's time extent — plus
one sequential spatial dim for 1-D Gauss-Seidel — and the parallel
spatial dims vectorized), so each sub-step is a handful of fused gathers,
a weighted sum, and one scatter.  A body with several sequential dims
(2-D Gauss-Seidel, :attr:`StencilSpec.wavefront`) would unroll a
sub-step per point; its tile runs instead as a ``lax.fori_loop`` over
in-tile wavefronts ``tt + l_0 + ... = c`` (:func:`_wave_table`), each
wave the same gathers, sum and scatter over its points.  Site validity
(``0 <= t < T`` and ``site ∈ [0, N)^d``, the site given by the spec's
iteration-to-site map) is exactly domain membership for the skewed
stencil programs, so partial tiles mask themselves.

Why same-level tiles never race: the EDT flow dependences of these
stencils cover every write-write and write-read hazard on the parity
buffers — a task overwriting slot ``(p, s)`` transitively depends on all
readers and the previous writer of that slot — so wavefront leveling
already linearizes conflicting accesses, and the per-level scatter indices
are distinct.  ``tests/test_fused_exec.py`` backs the argument with
bit-level oracles: :func:`host_execute` (the same level-major execution in
NumPy) equals the time-major :func:`~repro.kernels.stencils.reference_solve`
bitwise, and the device result matches both within documented tolerances.

Both sweep modes run fused: **replay** is the ``O(V+E)`` leveled
``fori_loop`` with the on-device schedule validation counters; **discover**
self-levels from the counters alone (``while_loop``, dense frontier — the
documented ``O(depth·V·g)`` test-scale tradeoff) with optional pallas
decrement (interpret mode only, see
:func:`~repro.core.edt.device.make_pallas_step`).  Packed products
(``DeviceGraph``, ``DeviceSchedule``, origin columns) flow through
:meth:`GraphCache.fused` / :meth:`Session.fused_packed` so warm runs skip
every host-side pack.  See ``docs/device_exec.md``
("Fused execution") for the measured numbers.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Optional, Union

import numpy as np

from ...kernels.stencils import SPECS, StencilSpec, default_state
from . import tracing
from .config import resolve_execution
from .device import (DeviceCounters, _counter_summary, _diagnose_replay,
                     _step_xla, make_pallas_step, pack_graph, pack_schedule)
from .faults import DROPPED_DECREMENT
from .recovery import ScheduleValidationError, StallError, StallReport
from .taskgraph import IndexedGraph, TiledTaskGraph
from .wavefront import IndexedSchedule, levels_from_array

#: Sentinel origin row (index ``n``): a time coordinate this negative can
#: never satisfy ``t >= 0``, so padded lanes mask themselves.
SENTINEL_ORIGIN = -(1 << 20)


# ------------------------------------------------------------------ packing
def pack_origins(ig: IndexedGraph, tile) -> "np.ndarray":
    """Per-task tile-origin columns: ``i32[n + 1, ndim]``.

    Row ``t`` is task ``t``'s iteration-space origin (tile coordinates ×
    tile sizes, in the skewed program coordinates); the extra row at index
    ``n`` is the :data:`SENTINEL_ORIGIN` mask row the padded
    ``dynamic_slice`` lanes gather.
    """
    if len(ig.stmt_blocks) != 1:
        raise ValueError(
            "fused execution supports single-statement graphs; got "
            f"{len(ig.stmt_blocks)} statements")
    _, coords = ig.stmt_blocks[0]
    nd = coords.shape[1]
    sizes = np.asarray(tuple(tile), dtype=np.int64)
    if sizes.shape != (nd,):
        raise ValueError(
            f"tile sizes {tuple(tile)} do not match the graph's {nd} "
            "iteration dims")
    org = coords.astype(np.int64) * sizes
    if org.size and (int(org.max()) >= -SENTINEL_ORIGIN or int(org.min()) < 0):
        raise ValueError(
            "tile origins exceed the fused executor's index range")
    out = np.empty((ig.n + 1, nd), dtype=np.int32)
    out[:-1] = org
    out[-1] = SENTINEL_ORIGIN
    return out


def _local_steps(spec: StencilSpec, tile) -> list:
    """The tile body's static sub-step structure.

    Returns ``[(tt, loc), ...]``: for each sequential iteration (local
    time ``tt``, then any sequential spatial dims in lex order) the
    ``(sv, space)`` int32 matrix of vectorized local spatial offsets.
    Sub-steps execute in list order — the skewed lexicographic order the
    schedule requires.
    """
    gs = tile[1:]
    seq = [k for k in range(spec.space) if spec.seq_space[k]]
    par = [k for k in range(spec.space) if not spec.seq_space[k]]
    sv = 1
    for k in par:
        sv *= gs[k]
    base = np.zeros((sv, spec.space), np.int32)
    if par:
        grids = np.meshgrid(
            *[np.arange(gs[k], dtype=np.int32) for k in par], indexing="ij")
        for g, k in zip(grids, par):
            base[:, k] = g.ravel()
    steps = []
    for tt in range(tile[0]):
        for sq in itertools.product(*[range(gs[k]) for k in seq]):
            loc = base.copy()
            for k, v in zip(seq, sq):
                loc[:, k] = v
            steps.append((tt, loc))
    return steps


def _wave_table(spec: StencilSpec, tile) -> tuple:
    """The in-tile wavefronts of a :attr:`StencilSpec.wavefront` body.

    Wave ``c`` holds the tile-local points ``(tt, l_0, ...)`` with ``tt +
    l_0 + ... = c``.  Every tap's iteration distance crosses that
    hyperplane forward, so a wave reads only points of earlier waves (or
    of tiles the schedule ran before) and its points are independent.
    Returns ``(loc, valid)``: the ``(waves, width, 1 + space)`` int32
    local offsets, each wave in lex order and padded to the widest, and
    the ``(waves, width)`` mask of its real points.
    """
    for dt, off, _ in spec.taps:
        if sum(spec.distance(dt, off)) <= 0:
            raise ValueError(
                f"body {spec.name!r}: tap {(dt, off)} does not cross the "
                "in-tile wavefront t + x_0 + ... = c forward")
    pts = np.stack(np.meshgrid(*[np.arange(g) for g in tile],
                               indexing="ij"), -1).reshape(-1, len(tile))
    wave = pts.sum(1)
    counts = np.bincount(wave)
    order = np.argsort(wave, kind="stable")
    slot = np.arange(len(order)) - np.repeat(np.cumsum(counts) - counts,
                                             counts)
    loc = np.zeros((len(counts), counts.max(), len(tile)), np.int32)
    valid = np.zeros(loc.shape[:2], bool)
    loc[wave[order], slot] = pts[order]
    valid[wave[order], slot] = True
    return loc, valid


def _sites(xp, rows, it):
    """Site coordinates ``(..., space)`` of iteration points ``it``
    ``(..., 1 + space)`` under a spec's ``site_rows`` (``xp``: NumPy or
    ``jax.numpy``)."""
    out = []
    for row in rows:
        acc = None
        for j, a in enumerate(row):
            if a:
                term = it[..., j] if a == 1 else a * it[..., j]
                acc = term if acc is None else acc + term
        out.append(acc)
    return xp.stack(out, -1)


def _strides(space: int, extent: int) -> tuple:
    return tuple(extent ** (space - 1 - k) for k in range(space))


# --------------------------------------------------------------- host oracle
def _tile_points(spec: StencilSpec, tile, org: "np.ndarray"):
    """The tile body's points in execution order, for the lanes whose
    origin rows are ``org`` ``(w, 1 + space)``: per sub-step (or per
    wave of a :attr:`StencilSpec.wavefront` body) the times ``t``
    ``(w, 1)`` or ``(w, p)`` and sites ``(w, p, space)``."""
    if spec.wavefront:
        rows = spec.site_rows
        for loc, ok in zip(*_wave_table(spec, tile)):
            it = org[:, None, :] + loc[ok][None].astype(np.int64)
            yield it[..., 0], _sites(np, rows, it)
        return
    t0, osp = org[:, 0], org[:, 1:]
    for tt, loc in _local_steps(spec, tile):
        t = (t0 + tt)[:, None]
        yield t, osp[:, None, :] + loc[None].astype(np.int64) - t[..., None]


def host_execute(spec: StencilSpec, tile, steps: int, extent: int,
                 origins: "np.ndarray", levels, state: "np.ndarray"):
    """Level-major NumPy twin of the fused sweep (the host-dispatch path).

    Executes the same tiles in the same level order with the same masking
    — element for element the identical arithmetic — so it is bitwise
    equal to :func:`~repro.kernels.stencils.reference_solve` *and* serves
    as the host-dispatch baseline ``bench_fused.py`` prices.  Returns the
    final field ``v_{steps-1}``.
    """
    space = spec.space
    size = extent ** space
    st = np.asarray(_strides(space, extent), dtype=np.int64)
    u = np.zeros((2, size), dtype=state.dtype)
    u[1] = state.ravel()
    ty = state.dtype.type
    for ids in levels:
        org = origins[np.asarray(ids)].astype(np.int64)
        for t, site in _tile_points(spec, tile, org):
            ok0 = (t >= 0) & (t < steps) \
                & np.all((site >= 0) & (site < extent), axis=2)
            flat = site @ st
            pw = t & 1
            acc = np.zeros(flat.shape, dtype=u.dtype)
            for dt, off, w in spec.taps:
                ok = ok0
                foff = 0
                for k, o in enumerate(off):
                    if o:
                        ns = site[..., k] + o
                        ok = ok & (ns >= 0) & (ns < extent)
                        foff += o * int(st[k])
                buf = np.broadcast_to(pw if dt == 0 else 1 - pw, ok.shape)
                vals = np.zeros(flat.shape, dtype=u.dtype)
                vals[ok] = u[buf[ok], (flat + foff)[ok]]
                acc = acc + ty(w) * vals
            pwb = np.broadcast_to(pw, ok0.shape)
            u[pwb[ok0], flat[ok0]] = acc[ok0]
    return u[(steps - 1) & 1].reshape(spec.shape(extent)).copy()


# ---------------------------------------------------------------------- run
@dataclass
class FusedRun:
    """One fused sweep: frontiers + counters + the computed grid.

    ``levels``/``level_of``/``counters`` mirror
    :class:`~repro.core.edt.device.DeviceRun` (byte-identical frontiers,
    same validation guarantees per mode); ``state`` is the full parity
    pair ``(2, N^d grid)`` and ``final`` the answer field ``v_{T-1}``.
    """

    mode: str                  # "discover" | "replay"
    levels: list
    level_of: "np.ndarray"
    counters: DeviceCounters
    state: "np.ndarray"        # (2,) + grid shape — both parity buffers
    final: "np.ndarray"        # grid shape — v_{steps-1}

    @property
    def exec_order(self) -> "np.ndarray":
        if not self.levels:
            return np.zeros(0, dtype=np.int64)
        return np.concatenate(self.levels)


class FusedExecutor:
    """End-to-end device-resident stencil execution of an EDT graph.

    Construct like :class:`~repro.core.edt.device.DeviceExecutor` — from a
    :class:`TiledTaskGraph` (``params`` required; ``config=``/``session=``
    drive generation, a session serves cached products) or an
    :class:`IndexedGraph` (then ``tile=`` names the tile sizes).  ``body``
    picks the :class:`~repro.kernels.stencils.StencilSpec` (a name from
    ``SPECS`` or a spec object); with a ``TiledTaskGraph`` it defaults to
    the program's registered name.  ``schedule=`` selects the O(V+E)
    replay sweep (validated on device unless ``validate=False`` drops the
    three violation counters from the compiled program); without it the
    discover sweep self-levels (``use_pallas=``/``interpret=`` as on the
    device executor).  ``packed=(DeviceGraph, DeviceSchedule | None,
    origins)`` skips all host-side packing — the graph cache's
    :meth:`~repro.core.edt.cache.GraphCache.fused` product plugs in here.

    ``state`` seeds the grid (default
    :func:`~repro.kernels.stencils.default_state`); ``dtype`` defaults to
    the state's (float64 requires x64 jax — run inside
    ``jax.enable_x64(True)``).  ``run()`` returns a
    :class:`FusedRun`; repeat runs (optionally with a fresh ``state=``)
    reuse the compiled sweep and pay dispatch cost only.  ``run()`` keeps
    nothing of a request on the executor (only the sweep, built on first
    use), so one executor serves any number of requests and threads: a
    :class:`~repro.core.edt.cache.GraphCache` entry keeps it for
    :meth:`~repro.core.edt.config.Session.fused_executor`.
    """

    def __init__(self, graph: Union[TiledTaskGraph, IndexedGraph],
                 params: Optional[dict] = None, *,
                 body=None,
                 schedule: Optional[IndexedSchedule] = None,
                 state: Optional["np.ndarray"] = None,
                 dtype=None,
                 tile: Optional[tuple] = None,
                 validate: bool = True,
                 use_pallas: bool = False,
                 interpret: Optional[bool] = None,
                 config=None, session=None, packed=None):
        cfg, sess = resolve_execution(config, session, stacklevel=3)
        if isinstance(graph, TiledTaskGraph):
            if params is None:
                raise TypeError("params required with a TiledTaskGraph")
            ig = (sess.index_graph(graph, params) if sess is not None
                  else graph._index_graph_cfg(params, cfg))
            if tile is None:
                tile = graph_tile(graph)
            if body is None:
                body = getattr(graph.program, "name", "") or None
        else:
            ig = graph
            if tile is None:
                raise TypeError("tile= (tile sizes) required with an "
                                "IndexedGraph")
        if body is None:
            raise TypeError("body= required (a repro.kernels.stencils.SPECS "
                            "name or StencilSpec); TiledTaskGraph infers it "
                            "from the program name")
        if isinstance(body, StencilSpec):
            spec = body
        elif body in SPECS:
            spec = SPECS[body]
        else:
            raise TypeError(f"unknown stencil body {body!r}; known: "
                            f"{sorted(SPECS)}")
        if params is None:
            raise TypeError("params required (the spec's symbolic sizes)")
        tile = tuple(int(g) for g in tile)
        if len(tile) != spec.space + 1:
            raise ValueError(
                f"body {spec.name!r} needs {spec.space + 1} tile dims "
                f"(time + space); got {tile}")
        if ig.stmt_blocks and ig.stmt_blocks[0][1].shape[1] != len(tile):
            raise ValueError(
                f"graph has {ig.stmt_blocks[0][1].shape[1]} iteration dims, "
                f"tile names {len(tile)}")
        self.ig = ig
        self.spec = spec
        self.tile = tile
        self.steps = int(params[spec.time_param])
        self.extent = int(params[spec.size_param])
        self.size = self.extent ** spec.space
        if 2 * self.size + 2 >= np.iinfo(np.int32).max:
            raise ValueError(f"grid too large for int32 site indexing: "
                             f"{self.size} sites")
        self.faults = cfg.faults
        self.validate = bool(validate)
        if packed is not None and schedule is not None:
            raise TypeError("pass schedule= or packed=, not both")
        if use_pallas and (schedule is not None
                           or (packed is not None and packed[1] is not None)):
            raise TypeError(
                "use_pallas applies to the discover sweep only; drop "
                "schedule= to price the pallas decrement")
        if packed is not None:
            self.dg, self.ds, self.fo = packed
            if self.fo is None and self.ds is not None:
                self.fo = self.ds.origin
            if self.fo is None:
                self.fo = pack_origins(ig, tile)
        else:
            self.dg = pack_graph(ig)
            self.fo = pack_origins(ig, tile)
            self.ds = (pack_schedule(ig, schedule, origins=self.fo)
                       if schedule is not None else None)
        if dtype is None:
            dtype = state.dtype if state is not None else np.float32
        self.dtype = np.dtype(dtype)
        self._state = (default_state(spec, self.extent, self.dtype)
                       if state is None
                       else np.asarray(state, self.dtype))
        if self._state.shape != spec.shape(self.extent):
            raise ValueError(
                f"state shape {self._state.shape} != grid "
                f"{spec.shape(self.extent)}")
        self.use_pallas = use_pallas
        self.interpret = interpret
        if spec.wavefront:
            self._loc_steps, self._waves = None, _wave_table(spec, tile)
        else:
            self._loc_steps, self._waves = _local_steps(spec, tile), None
        self._replay_fn = None
        self._discover_fn = None
        if use_pallas:
            self._pallas_step = make_pallas_step(
                self.dg.n, self.dg.n_edges, interpret)

    # ------------------------------------------------------------- plumbing
    def _check_x64(self):
        import jax

        if self.dtype == np.float64 and not jax.config.jax_enable_x64:
            raise RuntimeError(
                "float64 fused execution needs 64-bit jax types; wrap the "
                "run in jax.enable_x64(True)")

    def _flat_state(self, a0: "np.ndarray") -> "np.ndarray":
        size = self.size
        u0 = np.zeros(2 * size + 1, dtype=self.dtype)
        u0[size:2 * size] = a0.ravel()   # v_{-1} lives in parity buffer 1
        return u0

    def _make_compute(self, jnp):
        """The tile body as traced XLA ops over one level's lanes.

        ``org`` is the ``(w, ndim)`` int32 origin rows (sentinel rows mask
        themselves through ``t < 0``); ``active`` optionally masks lanes
        (the discover frontier).  Sub-steps unroll statically; each is
        3^d masked gathers, a weighted sum, and one dropped-OOB scatter,
        all under the device scope ``edt.body``.  A wavefront body runs
        its tile as a ``lax.fori_loop`` over the waves of
        :func:`_wave_table` instead, each wave every lane's points at
        once; picking a wave's points (its row of the wave table, the
        sites, the mask, the flat indices) sits in the device scope
        ``edt.wave``, its gathers, sum and scatter in ``edt.body``.
        """
        import jax

        spec, steps, extent = self.spec, self.steps, self.extent
        size = self.size
        st = _strides(spec.space, extent)
        loc_steps = self._loc_steps
        taps = spec.taps

        def apply_taps(u, site, flat, ok0, pw):
            """The points' gathers, weighted sum and scatter; ``pw`` is the
            parity offset per lane, or per point of a wave."""
            acc = jnp.zeros(flat.shape, u.dtype)
            for dt, off, w in taps:
                ok = ok0
                foff = 0
                for k, o in enumerate(off):
                    if o:
                        ns = site[..., k] + o
                        ok = ok & (ns >= 0) & (ns < extent)
                        foff += o * st[k]
                base = pw if dt == 0 else size - pw
                if base.ndim < flat.ndim:
                    base = base[:, None]
                idx = jnp.where(ok, base + flat + foff, 2 * size)
                acc = acc + w * u[idx]
            if pw.ndim < flat.ndim:
                pw = pw[:, None]
            widx = jnp.where(ok0, pw + flat, 2 * size + 1)
            return u.at[widx.reshape(-1)].set(acc.reshape(-1), mode="drop")

        if spec.wavefront:
            from jax import lax

            rows = spec.site_rows
            locs, valid = (jnp.asarray(a) for a in self._waves)

            @jax.named_scope("edt.body")
            def waves(u, org, active=None):
                def wave(c, u):
                    with jax.named_scope("edt.wave"):
                        it = org[:, None, :] + locs[c][None]
                        t = it[..., 0]
                        site = _sites(jnp, rows, it)
                        ok0 = (valid[c][None] & (t >= 0) & (t < steps)
                               & jnp.all((site >= 0) & (site < extent),
                                         axis=2))
                        if active is not None:
                            ok0 = ok0 & active[:, None]
                        flat = site[..., 0] * st[0]
                        for k in range(1, spec.space):
                            flat = flat + site[..., k] * st[k]
                        pw = (t & 1) * size
                    return apply_taps(u, site, flat, ok0, pw)

                return lax.fori_loop(0, locs.shape[0], wave, u)

            return waves

        @jax.named_scope("edt.body")
        def compute(u, org, active=None):
            t0 = org[:, 0]
            osp = org[:, 1:]
            for tt, loc in loc_steps:
                t = t0 + tt
                tmask = (t >= 0) & (t < steps)
                if active is not None:
                    tmask = tmask & active
                pw = (t & 1) * size
                site = (osp[:, None, :] + jnp.asarray(loc)[None]
                        - t[:, None, None])
                ok0 = tmask[:, None] & jnp.all(
                    (site >= 0) & (site < extent), axis=2)
                flat = site[..., 0] * st[0]
                for k in range(1, spec.space):
                    flat = flat + site[..., k] * st[k]
                u = apply_taps(u, site, flat, ok0, pw)
            return u

        return compute

    def _finish(self, mode, levels, level_of, counters, u) -> FusedRun:
        size = self.size
        grid = self.spec.shape(self.extent)
        state = u[:2 * size].reshape((2,) + grid)
        # v_{-1}, the initial grid, sits in parity buffer 1: T=0 reads it
        final = state[(self.steps - 1) & 1]
        return FusedRun(mode, levels, level_of, counters, state, final)

    # --------------------------------------------------------------- sweeps
    def run(self, state: Optional["np.ndarray"] = None) -> FusedRun:
        a0 = (self._state if state is None
              else np.asarray(state, self.dtype))
        if a0.shape != self.spec.shape(self.extent):
            raise ValueError(f"state shape {a0.shape} != grid "
                             f"{self.spec.shape(self.extent)}")
        if self.dg.n == 0:
            counters = DeviceCounters(0, 0, 0, 0, np.zeros(0, np.int64))
            u = self._flat_state(a0)
            return self._finish(
                "replay" if self.ds is not None else "discover",
                [], np.zeros(0, np.int64), counters, u)
        self._check_x64()
        if self.ds is not None:
            return self._run_replay(a0)
        return self._run_discover(a0)

    def _sweep(self):
        """The jitted sweep of this executor's mode, built on first use."""
        if self.ds is not None:
            if self._replay_fn is None:
                with tracing.span("build"):
                    self._replay_fn = self._build_replay()
            return self._replay_fn
        if self._discover_fn is None:
            with tracing.span("build"):
                self._discover_fn = self._build_discover()
        return self._discover_fn

    def compiled(self):
        """The sweep :meth:`run` calls, compiled ahead of time for this
        executor's own argument shapes (a load from JAX's persistent
        compile cache where it holds the sweep).  Its
        ``memory_analysis()`` gives the generated code's bytes, and its
        ``as_text()`` the HLO, each op's metadata naming its ``edt.*``
        device scope."""
        return self.lowered().compile()

    def lowered(self):
        """The sweep :meth:`run` calls, lowered for this executor's own
        argument shapes; its ``as_text()`` is the program before XLA."""
        import jax

        n = self.dg.n
        if n == 0:
            raise ValueError("an empty graph runs no sweep")
        i32 = np.int32
        u = jax.ShapeDtypeStruct((2 * self.size + 1,), self.dtype)
        if self.ds is not None:
            args = (jax.ShapeDtypeStruct((n + 1,), i32), u)
        else:
            vec, one = (jax.ShapeDtypeStruct((n,), i32),
                        jax.ShapeDtypeStruct((), i32))
            args = ((vec, jax.ShapeDtypeStruct((n,), np.bool_), one, vec,
                     one, one, u),)
        return self._sweep().lower(*args)

    def _build_replay(self):
        """The jitted replay sweep ``(indeg, u) -> (not_ready, early,
        undrained, max_width, u)``.  Each level's ops sit in a device
        scope: ``edt.frontier`` (the level's ids and origin rows),
        ``edt.validate`` (the schedule checks), ``edt.body`` (the tiles)
        and ``edt.decrement`` (the out-edges' counter decrement)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        tracing.count("sweep.builds")
        dg, ds = self.dg, self.ds
        n, depth, w_pad, e_pad = dg.n, ds.depth, ds.w_pad, ds.e_pad
        validate = self.validate
        op = jnp.asarray(ds.order)
        tp = jnp.asarray(ds.task_ptr)
        ep = jnp.asarray(ds.edge_ptr)
        tg = jnp.asarray(ds.lvl_tgt)
        org = jnp.asarray(self.fo)
        compute = self._make_compute(jnp)

        @jax.jit
        def sweep(indeg, u):
            aw = jnp.arange(w_pad, dtype=jnp.int32)
            ae = jnp.arange(e_pad, dtype=jnp.int32)

            def body(level, carry):
                indeg, u, not_ready, early, maxw = carry
                with jax.named_scope("edt.frontier"):
                    w = tp[level + 1] - tp[level]
                    ids = lax.dynamic_slice(op, (tp[level],), (w_pad,))
                    # mask lanes past this level's width — the fixed-width
                    # id slice spills into the next level's ids, not the
                    # sentinel
                    active = aw < w
                    orgs = org[ids]
                    maxw = jnp.maximum(maxw, w)
                if validate:
                    # same three checks as the decrement-only replay sweep
                    with jax.named_scope("edt.validate"):
                        not_ready += jnp.sum(
                            jnp.where(active, indeg[ids] != 0, False),
                            dtype=jnp.int32)
                        nw = tp[level + 2] - tp[level + 1]
                        nids = lax.dynamic_slice(op, (tp[level + 1],),
                                                 (w_pad,))
                        early += jnp.sum(
                            jnp.where(aw < nw, indeg[nids] == 0, False),
                            dtype=jnp.int32)
                u = compute(u, orgs, active=active)
                with jax.named_scope("edt.decrement"):
                    ec = ep[level + 1] - ep[level]
                    tgts = lax.dynamic_slice(tg, (ep[level],), (e_pad,))
                    tgts = jnp.where(ae < ec, tgts, n)
                    indeg = indeg.at[tgts].add(-1)
                return indeg, u, not_ready, early, maxw

            z = jnp.int32(0)
            indeg, u, not_ready, early, maxw = lax.fori_loop(
                0, depth, body, (indeg, u, z, z, z))
            with jax.named_scope("edt.validate"):
                undrained = (jnp.sum(indeg[:n] != 0, dtype=jnp.int32)
                             if validate else z)
            return not_ready, early, undrained, maxw, u

        return sweep

    def _run_replay(self, a0: "np.ndarray") -> FusedRun:
        import jax.numpy as jnp

        dg, ds = self.dg, self.ds
        sweep = self._sweep()
        tracing.count("sweep.tasks", dg.n)
        tracing.count("sweep.task_lanes", ds.depth * ds.w_pad)
        tracing.count("sweep.edges", dg.n_edges)
        tracing.count("sweep.edge_lanes", ds.depth * ds.e_pad)
        if self._waves is not None:
            waves, width = self._waves[1].shape
            tracing.count("sweep.body_waves", ds.depth * waves)
            tracing.count("sweep.wave_points", self.steps * self.size)
            tracing.count("sweep.wave_lanes",
                          ds.depth * ds.w_pad * width * waves)
        with tracing.span("upload"):
            indeg0 = jnp.concatenate([jnp.asarray(dg.pred_n),
                                      jnp.zeros(1, jnp.int32)])
            u0 = jnp.asarray(self._flat_state(a0))
        with tracing.span("dispatch"):
            out = sweep(indeg0, u0)
        with tracing.span("wait"):
            not_ready, early, undrained, maxw = (int(x) for x in out[:4])
        with tracing.span("readback"):
            u = np.asarray(out[4])
        if not_ready or early or undrained:
            kind, level, ids, indeg = _diagnose_replay(dg, ds)
            counters = _counter_summary(indeg)
            counters.update(device_not_ready=not_ready, device_early=early,
                            device_undrained=undrained)
            raise ScheduleValidationError(kind, level, ids, counters)
        widths = np.asarray([lv.size for lv in ds.levels], dtype=np.int64)
        counters = DeviceCounters(dg.n, dg.n, maxw, ds.depth, widths)
        return self._finish("replay", ds.levels, ds.level_of, counters, u)

    def _build_discover(self):
        """The jitted discover sweep over the state tuple ``(indeg,
        frontier, level, level_of, started, max_width, u)``, with the
        replay sweep's device scopes (no ``edt.validate``: the counters
        themselves level the graph)."""
        import jax
        import jax.numpy as jnp

        tracing.count("sweep.builds")
        dg = self.dg
        step = (self._pallas_step if self.use_pallas else _step_xla(jnp))
        dec_src = jnp.asarray(dg.dec_src)
        dec_ptr = jnp.asarray(dg.dec_ptr)
        org = jnp.asarray(self.fo[:dg.n])
        compute = self._make_compute(jnp)

        def cond(state):
            return state[1].any()

        def body(state):
            indeg, frontier, level, level_of, started, maxw, u = state
            with jax.named_scope("edt.frontier"):
                w = frontier.sum().astype(jnp.int32)
                level_of = jnp.where(frontier, level, level_of)
                maxw = jnp.maximum(maxw, w)
            u = compute(u, org, active=frontier)
            with jax.named_scope("edt.decrement"):
                indeg, newly = step(indeg, frontier, dec_src, dec_ptr)
            return (indeg, newly, level + 1, level_of, started + w, maxw, u)

        return jax.jit(lambda s: jax.lax.while_loop(cond, body, s))

    def _run_discover(self, a0: "np.ndarray") -> FusedRun:
        import jax.numpy as jnp

        dg = self.dg
        n = dg.n
        sweep = self._sweep()
        pred_host = dg.pred_n
        if self.faults is not None:
            dropped = [int(t) for t in self.faults.dropped_tasks()]
            if dropped:
                pred_host = pred_host.copy()
                for t in dropped:
                    pred_host[t] += 1
                    self.faults.record(DROPPED_DECREMENT, t, 0)
        with tracing.span("upload"):
            pred = jnp.asarray(pred_host)
            init = (pred, pred == 0, jnp.int32(0),
                    jnp.full(n, -1, jnp.int32), jnp.int32(0), jnp.int32(0),
                    jnp.asarray(self._flat_state(a0)))
        with tracing.span("dispatch"):
            out = sweep(init)
        with tracing.span("wait"):
            indeg, depth, level_of, started, maxw = (
                np.asarray(out[i]) for i in (0, 2, 3, 4, 5))
        with tracing.span("readback"):
            u = np.asarray(out[6])
        started = int(started)
        if started != n:
            und = np.flatnonzero(indeg != 0)
            report = StallReport(
                context="fused-discover", elapsed=0.0,
                started=started, finished=started, in_flight=0,
                undrained={int(t): int(indeg[t]) for t in und[:1024]},
                note=("fused counted-sync sweep reached a fixpoint with "
                      f"{und.size} counter(s) undrained — the task graph "
                      "has a cycle or a decrement was dropped"))
            raise StallError(report, msg=(
                f"fused counted-sync sweep deadlocked: {started}/{n} tasks "
                "became ready — the task graph has a cycle or a decrement "
                f"was dropped; undrained: {und[:8].tolist()}"
                + (f" (+{und.size - 8} more)" if und.size > 8 else "")))
        level_of = level_of.astype(np.int64)
        levels = levels_from_array(level_of)
        widths = np.asarray([lv.size for lv in levels], dtype=np.int64)
        counters = DeviceCounters(started, started, int(maxw), int(depth),
                                  widths)
        return self._finish("discover", levels, level_of, counters, u)


def graph_tile(graph: TiledTaskGraph) -> tuple:
    """The tile sizes of a single-statement graph (fused executor unit)."""
    if len(graph.tilings) != 1:
        raise ValueError("fused execution supports single-statement "
                         "programs; got "
                         f"{sorted(graph.tilings)}")
    (tiling,) = graph.tilings.values()
    return tuple(int(s) for s in tiling.sizes)


#: The ``FusedExecutor`` keywords that :func:`executor_key` keys.
_KEYED = frozenset({"body", "tile", "validate", "dtype", "use_pallas",
                    "interpret"})


def executor_key(replay: bool, kw: dict) -> Optional[tuple]:
    """The key under which a graph-cache entry keeps the executor that
    ``FusedExecutor(ig, params, packed=..., **kw)`` builds, or ``None``
    when the call cannot be keyed: a keyword outside ``body``/``tile``/
    ``validate``/``dtype``/``use_pallas``/``interpret`` (``state=``,
    ``config=``, ``session=``...) or a value that cannot be hashed.
    Defaults are spelled out, so ``dtype=None`` and ``dtype="float32"``
    key one executor."""
    if not kw.keys() <= _KEYED:
        return None
    dtype, interpret = kw.get("dtype"), kw.get("interpret")
    try:
        key = (bool(replay), bool(kw.get("validate", True)),
               np.dtype(np.float32 if dtype is None else dtype),
               kw.get("body"), tuple(int(g) for g in kw.get("tile")),
               bool(kw.get("use_pallas", False)),
               None if interpret is None else bool(interpret))
        hash(key)
    except (TypeError, ValueError):
        return None
    return key
