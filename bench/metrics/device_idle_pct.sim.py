"""Device idle share of the window (%): 1 - busy union / window, from the
profiler trace (bench/trace_reduce.py).  Moves sim_requests_per_s."""


def read(ctx):
    t = ctx["trace"]
    if not t["devices"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
