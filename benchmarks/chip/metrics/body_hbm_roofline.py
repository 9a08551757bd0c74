"""Share (%) of the HBM roofline in the tile body: the least bytes of the
problem itself (each sweep reads and writes the grid once) at peak HBM
bandwidth, over the device seconds of the ops under the ``edt.body``
scope.  Bytes bound it, as in ``sweep_hbm_roofline``."""
from benchmarks.chip import roofline, scopes


def read(run):
    body = scopes.per_solve(run, (scopes.BODY,))
    done = [r for r in run.requests if r.error is None]
    if not body:
        return None
    space = len(run.cell.config["tile"]) - 1
    nbytes = sum(roofline.least_bytes(r.params["N"], r.params["T"], space)
                 for r in done)
    return roofline.hbm_roofline_pct(nbytes, body * len(done), run.peak)
