"""Device seconds per solve in the counted sync: the self time of the ops
under the sweep's ``edt.frontier`` (the level's ids and origin rows),
``edt.validate`` (the schedule checks) and ``edt.decrement`` (the
counters' scatter-add) scopes (``fused._build_replay``) in the traced
window (``benchmarks/chip/scopes.py``)."""
from benchmarks.chip import scopes


def read(run):
    return scopes.per_solve(run, scopes.SYNC)
