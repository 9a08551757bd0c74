"""Device seconds per solve in the tile body: the self time of the ops
under the sweep's ``edt.body`` scope (``fused._make_compute``) in the
traced window, from the ops' ``op_name`` in the trace
(``benchmarks/chip/scopes.py``)."""
from benchmarks.chip import scopes


def read(run):
    return scopes.per_solve(run, (scopes.BODY,))
