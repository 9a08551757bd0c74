"""Mean seconds per cold request of XLA compilation, from JAX's own
``/jax/core/compile/backend_compile_duration`` events (the sweep of
``fused._build_replay`` and the small programs around it)."""


def read(run):
    v = [r.jax["compile_s"] for r in run.requests if r.jax]
    return sum(v) / len(v) if v else None
