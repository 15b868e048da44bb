"""XLA compiles inside the measured window (jax.monitoring's compile
events; every program should be compiled in set-up).  Moves table_s."""


def read(ctx):
    return float(ctx["window_compiles"])
