"""Device: share of the traced window in which no program ran on the
chip (1 - union of XLA module intervals / window)."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
