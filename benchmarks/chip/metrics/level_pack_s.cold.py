"""Mean host seconds per cold request in ``Session.schedule`` and
``Session.fused_packed``: leveling, packing and the upload
(``wavefront.py``, ``device.pack_*``, ``fused.pack_origins``,
``cache.py``)."""

CALLS = ("Session.schedule", "Session.fused_packed")


def read(run):
    v = [sum(r.spans[c] for c in CALLS) for r in run.requests
         if all(c in r.spans for c in CALLS)]
    return sum(v) / len(v) if v else None
