"""The one traffic generator: reads ``traffic/<mix>.json`` and draws the
requests of a run from its seed.

A mix is data:

* ``"cache"``: ``"warm"`` (every window request hits the graph cache that
  set-up filled) or ``"cold"`` (every window request is an instance the
  process has not seen, with JAX's persistent compile cache off);
* ``"params"``: the program parameters of each window request, by name
  (``T`` counts sweeps, ``N`` the grid's side).  A number fixes the
  parameter; ``{"values": [...]}`` gives a set that every
  seed uses whole, each value once, in an order drawn from the seed.  A
  parameter the mix leaves out is the configuration's own;
* ``"setup_params"``: the parameters of the one set-up request a cold mix
  makes (a warm mix's set-up request is the configuration's instance);
* ``"warmup_seconds"``: how long set-up then keeps making requests through
  the window's path (a warm mix), before the window opens;
* ``"reports"``: the end-to-end metric the window's requests report.

Every request also carries its own initial grid, uniform on [0, 1) and
drawn from ``(seed, request index)`` before the request's clock starts.
"""
from __future__ import annotations

import itertools
import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclass(frozen=True)
class Request:
    index: int
    params: dict


def load(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    mix = json.loads((directory / f"{name}.json").read_text())
    if mix.get("cache") not in ("warm", "cold"):
        raise ValueError(f"traffic {name}: cache must be 'warm' or 'cold'")
    if not isinstance(mix.get("reports"), str):
        raise ValueError(f"traffic {name}: 'reports' names no metric")
    return mix


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1), *stream])


def requests(mix: dict, base: dict, seed: int):
    """The window's requests, in order: an endless stream when every
    parameter is fixed, else one request per value of the drawn sets."""
    fixed = dict(base)
    drawn = {}
    for key, spec in mix.get("params", {}).items():
        if isinstance(spec, dict):
            values = list(spec["values"])
            if len(set(values)) != len(values):
                raise ValueError(f"parameter {key}: values repeat")
            drawn[key] = [values[i] for i in
                          rng(seed, 1).permutation(len(values))]
        else:
            fixed[key] = int(spec)
    if len({len(v) for v in drawn.values()}) > 1:
        raise ValueError("drawn parameter sets differ in length")
    count = len(next(iter(drawn.values()))) if drawn else None
    for i in (range(count) if count is not None else itertools.count()):
        params = dict(fixed)
        params.update({k: int(v[i]) for k, v in drawn.items()})
        yield Request(i, params)


def setup_params(mix: dict, base: dict) -> dict:
    out = dict(base)
    out.update({k: int(v) for k, v in mix.get("setup_params", {}).items()})
    return out


def initial_grid(seed: int, index: int, shape: tuple) -> np.ndarray:
    """The request's initial grid, float32 uniform on [0, 1)."""
    return rng(seed, 2, index).random(shape, dtype=np.float32)
