"""Front end: median seconds from submitting a window query to the
server's ``{"ok": true, "id"}`` reply.  A query that arrives while a
fleet step runs waits for the step boundary."""
from stats import quantile


def read(ctx):
    return quantile((q.t_admit - q.t_submit for q in ctx["window_queries"]
                     if q.t_admit is not None), 0.5)
