"""Event-loop steps the fleet grid executed per arrival of a call's
fullest lane (``steps_executed`` over the largest lane's sampled
arrivals), averaged over the window's calls: what a fault schedule adds
to the lockstep loop (crash replays, repairs, retries).  Moves
sim_requests_per_s."""


def read(ctx):
    return ctx["counters"].get("steps_per_request")
