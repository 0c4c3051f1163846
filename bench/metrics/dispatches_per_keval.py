"""Fleet driver: device dispatches the program counted
(``jax_cost.dispatch_count``) inside the window, per 1,000 evaluations
performed in the window."""
from stats import evals_in_window


def read(ctx):
    ev = evals_in_window(ctx)
    return None if ev <= 0 else ctx["dispatches"] / (ev / 1000.0)
