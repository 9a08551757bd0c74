"""Share (%) of the HBM roofline in a wavefront tile body: the least bytes
of the problem itself (each sweep reads and writes the grid once,
``roofline.least_bytes``) at peak HBM bandwidth, over the device seconds
per solve of the ops under the ``edt.body`` and ``edt.wave`` scopes
together (the waves' gathers, sum and scatter, and the picking of their
points).  ``None`` where the trace holds no ``edt.wave`` scope."""
from benchmarks.chip import roofline, scopes

WAVE = "edt.wave"


def read(run):
    s = scopes.of_run(run)
    if s is None or WAVE not in s.scopes:
        return None
    done = [r for r in run.requests if r.error is None]
    seconds = scopes.per_solve(run, (scopes.BODY, WAVE))
    space = len(run.cell.config["tile"]) - 1
    nbytes = sum(roofline.least_bytes(r.params["N"], r.params["T"], space)
                 for r in done)
    return roofline.hbm_roofline_pct(nbytes, seconds * len(done), run.peak)
