"""Share of the window spent in JAX's tracing, lowering and backend
compiles (persistent-cache lookups included), from JAX's own
``jax.monitoring`` duration events."""


def read(run):
    return 100.0 * run["compile_s"] / run["span_s"]
