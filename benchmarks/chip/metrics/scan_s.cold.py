"""Mean host seconds per cold request in ``Session.index_graph``: the
polyhedral scan (``taskgraph.index_graph``, ``shard.py``,
``poly/scanning.py``)."""


def read(run):
    v = [r.spans["Session.index_graph"] for r in run.requests
         if "Session.index_graph" in r.spans]
    return sum(v) / len(v) if v else None
