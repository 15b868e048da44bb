"""Bellman backups per operating point: the mean of the window's results'
``rvi.iterations`` (the solver's own count).  Moves table_s."""


def read(ctx):
    return ctx["counters"].get("backups_per_spec")
