"""Set-up seconds: from the start of the run to the opening of the
window (imports, JAX start, program warm-up and warm-up queries)."""


def read(ctx):
    return ctx["setup_s"]
