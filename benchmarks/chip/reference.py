"""The plain reference: time-major NumPy jacobi-2d and its tile graph.

Independent of the program under test (it imports nothing of ``repro``).
A configuration names its functions (``"reference"`` in its file):
``box_solve`` for the grid, ``graph_check`` for the graph a cold request
scans and levels.
The stencil is the 9-point box average the benchmark's configurations
state: ``v_t[s] = sum over o in {-1,0,1}^2 of v_{t-1}[s + o] / 9``, with
reads outside ``[0, N)^2`` contributing zero and ``v_{-1}`` the initial
grid; the answer is ``v_{T-1}``.
"""
from __future__ import annotations

import itertools

import numpy as np


def _shift(a: np.ndarray, off) -> np.ndarray:
    """``out[s] = a[s + off]``, zeros shifted in at the boundary."""
    out = np.zeros_like(a)
    dst, src = [], []
    for k, o in enumerate(off):
        n = a.shape[k]
        lo, hi = max(0, -o), n - max(0, o)
        dst.append(slice(lo, hi))
        src.append(slice(lo + o, hi + o))
    out[tuple(dst)] = a[tuple(src)]
    return out


def box_solve(state: np.ndarray, steps: int) -> np.ndarray:
    """``steps`` sweeps of the 3^d box average, in float64."""
    v = np.asarray(state, dtype=np.float64)
    offs = list(itertools.product((-1, 0, 1), repeat=v.ndim))
    w = 1.0 / len(offs)
    for _ in range(steps):
        acc = np.zeros_like(v)
        for off in offs:
            acc += w * _shift(v, off)
        v = acc
    return v


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest absolute gap over the largest reference magnitude."""
    got = np.asarray(got, dtype=np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


# ----------------------------------------------------------- the tile graph
def _points(steps: int, n: int):
    """Every iteration point of the time-skewed jacobi-2d domain:
    ``0 <= t < T`` and ``t <= x, y < t + N``."""
    t, i, j = np.meshgrid(np.arange(steps), np.arange(n), np.arange(n),
                          indexing="ij")
    t = t.ravel()
    return t, i.ravel() + t, j.ravel() + t


#: Bits per tile coordinate in a tile key.
_BITS = 18


def tile_keys(coords: np.ndarray) -> np.ndarray:
    """One int64 key per tile-coordinate row ``(t, x, y)``."""
    c = np.asarray(coords, dtype=np.int64)
    if c.size and (c.min() < 0 or c.max() >= 1 << _BITS):
        raise ValueError("tile coordinates out of the key range")
    return (c[:, 0] << 2 * _BITS) | (c[:, 1] << _BITS) | c[:, 2]


def edge_codes(src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """One int64 code per tile edge whose step ``dst - src`` lies in
    {0, 1} x {0, 1, 2}^2 (the only steps a jacobi-2d dependence makes
    between tiles of at least one point each way); -1 for any other."""
    src = np.asarray(src, dtype=np.int64)
    d = np.asarray(dst, dtype=np.int64) - src
    ok = ((d[:, 0] >= 0) & (d[:, 0] <= 1) & (d[:, 1] >= 0) & (d[:, 1] <= 2)
          & (d[:, 2] >= 0) & (d[:, 2] <= 2))
    rel = d[:, 0] * 9 + d[:, 1] * 3 + d[:, 2]
    return np.where(ok, tile_keys(src) * 18 + rel, -1)


def tile_graph(steps: int, n: int, tile) -> tuple:
    """Tiles and tile-level dependences of jacobi-2d, from the points.

    Returns ``(tiles, edges)``: the sorted keys of every tile that holds a
    domain point, and the sorted :func:`edge_codes` of every pair of
    distinct tiles joined by at least one point dependence ``(t, x, y) ->
    (t + 1, x + a, y + b)``, ``a, b`` in {0, 1, 2}, both ends in the domain.
    """
    size = np.asarray([int(s) for s in tile], dtype=np.int64)
    t, x, y = _points(steps, n)
    pts = np.stack([t, x, y], 1)
    tiles = np.unique(tile_keys(pts // size))
    pts = pts[t < steps - 1]
    src = pts // size
    t1 = pts[:, 0] + 1
    codes = []
    for a in (0, 1, 2):
        for b in (0, 1, 2):
            nxt = pts + np.asarray([1, a, b])
            ok = ((nxt[:, 1] >= t1) & (nxt[:, 1] < t1 + n)
                  & (nxt[:, 2] >= t1) & (nxt[:, 2] < t1 + n))
            s, d = src[ok], nxt[ok] // size
            moved = np.any(s != d, axis=1)
            codes.append(np.unique(edge_codes(s[moved], d[moved])))
    return tiles, np.unique(np.concatenate(codes))


def graph_check(params: dict, tile: tuple, coords, edge_src, edge_tgt,
                level_of) -> tuple:
    """``(tiles wrong, reference edges missing, edges leveled out of
    order)`` of the graph a cold request scanned and leveled."""
    tiles, edges = tile_graph(params["T"], params["N"], tile)
    n = len(coords)
    if len(level_of) != n or (len(edge_src) and max(
            int(np.max(edge_src)), int(np.max(edge_tgt))) >= n):
        return len(tiles) + n, len(edges), len(edges)   # not one graph
    keys = tile_keys(coords)
    tiles_wrong = (len(np.setxor1d(keys, tiles))
                   + (len(keys) - len(np.unique(keys))))
    codes = edge_codes(coords[edge_src], coords[edge_tgt])
    missing = int(np.count_nonzero(~np.isin(edges, codes)))
    # levels along every reference edge, looked up through the task keys
    order = np.argsort(keys)
    lv = np.asarray(level_of)[order]
    src_key = edges // 18
    rel = edges % 18
    step = np.stack([rel // 9, rel % 9 // 3, rel % 3], 1)
    dst_coords = np.stack([src_key >> 36, (src_key >> 18) & 0x3FFFF,
                           src_key & 0x3FFFF], 1) + step
    si = np.searchsorted(keys[order], src_key)
    di = np.searchsorted(keys[order], tile_keys(dst_coords))
    si, di = np.minimum(si, len(lv) - 1), np.minimum(di, len(lv) - 1)
    out_of_order = int(np.count_nonzero(lv[si] >= lv[di]))
    return tiles_wrong, missing, out_of_order
