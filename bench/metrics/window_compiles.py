"""Compile layer: XLA compiles and persistent-cache loads inside the
window, from JAX's monitoring events.  Set-up aims to leave none."""


def read(ctx):
    return ctx["compiles"]
