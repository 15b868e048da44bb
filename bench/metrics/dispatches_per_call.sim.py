"""Executions of the simulator kernel per window call: 1.0 when the host
wrapper never re-dispatches at a longer scan.  Moves sim_requests_per_s."""

from trace_reduce import kernel_seconds


def read(ctx):
    if not ctx["kernel"]:
        return None
    _, n = kernel_seconds(ctx["trace"], ctx["kernel"])
    calls = ctx["counters"].get("calls")
    if not n or not calls:
        return None
    return n / calls
