"""Share (%) of the HBM roofline per solve: the least bytes of the
problem itself (each time step reads and writes the grid once) at peak
HBM bandwidth, over the device-busy seconds per solve.  Bytes bound it:
the peak table has no float32 vector peak."""
from benchmarks.chip import roofline


def read(run):
    done = [r for r in run.requests if r.error is None]
    if run.trace is None or not done or run.trace.busy_s <= 0:
        return None
    space = len(run.cell.config["tile"]) - 1
    nbytes = sum(roofline.least_bytes(r.params["N"], r.params["T"], space)
                 for r in done)
    return roofline.hbm_roofline_pct(nbytes, run.trace.busy_s, run.peak)
