"""Share of the traced window's wall time that the program spent inside
one of its own spans (``mpit_tpu.obs``), in percent. No span of the name
in a recorded window is a share of 0."""


def read(ctx, span: str):
    run = ctx["run"]
    t0, t1 = run["trace_t0"], run["trace_t1"]
    inside = [min(b, t1) - max(a, t0)
              for name, a, b, _ in run["host_spans"] if name == span]
    if not run["host_spans"]:
        return None  # no recorder was on: nothing to read
    return 100.0 * sum(x for x in inside if x > 0) / (t1 - t0)
