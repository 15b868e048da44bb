"""Device nanoseconds of the single-server grid kernel (serving.compiled's
grid jit) per generated arrival.  Moves sim_requests_per_s."""

from trace_reduce import kernel_seconds


def read(ctx):
    secs, _ = kernel_seconds(ctx["trace"], "_grid_jit")
    n = ctx["counters"].get("requests")
    if not secs or not n:
        return None
    return secs * 1e9 / n
