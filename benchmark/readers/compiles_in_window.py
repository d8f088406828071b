"""XLA compilations between the window's two edges, by JAX's own
``backend_compile_duration`` events (a load from the cache counts)."""


def read(ctx):
    run = ctx["run"]
    return float(ctx["compiles"].between(run["trace_t0"], run["trace_t1"]))
