"""Peak device memory on the fullest chip, as the backend reports it."""


def read(ctx):
    peak = ctx["device"]["memory_peak_bytes"]
    return peak / 1e9 if peak else None
