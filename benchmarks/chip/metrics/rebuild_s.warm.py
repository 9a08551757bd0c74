"""Mean seconds per warm request that JAX spends building the sweep again:
tracing, lowering to an MLIR module (with the graph's columns as
constants) and compiling or loading the executable from the persistent
compile cache, from JAX's own ``/jax/core/compile/*`` events.  Every
``Session.fused_executor`` call makes a new ``FusedExecutor``, so every
warm request pays this today."""

EVENTS = ("trace_s", "lower_s", "compile_s")


def read(run):
    v = [sum(r.jax[k] for k in EVENTS) for r in run.requests if r.jax]
    return sum(v) / len(v) if v else None
