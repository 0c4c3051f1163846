"""Median seconds from submitting a query to its ``done``, over every
query sent inside the window (client clock)."""
from stats import quantile


def read(ctx):
    return quantile((q.latency for q in ctx["window_queries"]
                     if q.latency is not None), 0.5)
