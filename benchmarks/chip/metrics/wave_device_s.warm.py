"""Device seconds per solve picking the in-tile wavefronts' points: the
self time of the ops under the sweep's ``edt.wave`` scope (the wave
table's row, the sites, the mask and the flat indices of each wave of a
wavefront tile body) in the traced window
(``benchmarks/chip/scopes.py``); ``None`` where the trace holds no such
scope (a program whose tile body runs no waves)."""
from benchmarks.chip import scopes

WAVE = "edt.wave"


def read(run):
    return scopes.per_solve(run, (WAVE,))
