"""Fleet driver: share of the window the host spent blocked on
device-to-host conversions (``jax_cost.host_blocked_s``)."""


def read(ctx):
    return ctx["host_blocked_s"] / ctx["window_s"]
