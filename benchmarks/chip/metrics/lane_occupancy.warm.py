"""Share (%) of the sweep's task lanes that hold a task: every level
slices ``w_pad`` ids, the widest level's width, so a solve runs
``depth * w_pad`` lanes for its tasks.  From the program's counters of
each window request (``FusedRun.counters``: depth and widest level, and
the graph's task count), summed over the window."""


def read(run):
    done = [r for r in run.requests if r.error is None and r.depth > 0]
    lanes = sum(r.depth * r.max_width for r in done)
    if not lanes:
        return None
    return 100.0 * sum(r.n_tasks for r in done) / lanes
