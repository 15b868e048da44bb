"""Device nanoseconds of the fleet grid kernel (serving.fleet's grid jit)
per generated arrival (every router simulates it).  Moves
sim_requests_per_s."""

from trace_reduce import kernel_seconds


def read(ctx):
    secs, _ = kernel_seconds(ctx["trace"], "_fleet_grid_core")
    n = ctx["counters"].get("requests")
    if not secs or not n:
        return None
    return secs * 1e9 / n
