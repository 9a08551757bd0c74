"""Device-busy seconds per solve in the traced window: the fused replay
sweep (decrement and tile body together, ``fused.py``) with the grid's
upload and read-back."""


def read(run):
    n = sum(1 for r in run.requests if r.error is None)
    if run.trace is None or not n or run.trace.busy_s <= 0:
        return None
    return run.trace.busy_s / n
