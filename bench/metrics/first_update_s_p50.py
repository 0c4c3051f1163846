"""Median seconds from submitting a query to its first ``update`` (or to
its ``done`` where none came), over the window's queries: the wait that
admission and the search's host prologue impose."""
from stats import quantile


def read(ctx):
    return quantile((q.first_update for q in ctx["window_queries"]
                     if q.first_update is not None), 0.5)
