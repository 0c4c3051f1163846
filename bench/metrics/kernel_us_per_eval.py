"""Cost kernel: device microseconds of the fleet's kernel programs per
evaluation performed in the traced part of the window.  The programs
are matched by their XLA module names: the stacked mega-batch evaluator
(``jit_eval_one``) and the device-resident segment scan
(``jit_one_task``)."""
from stats import evals_in_window

KERNEL_MODULES = ("jit_eval_one", "jit_one_task")


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    ev = evals_in_window(ctx, ctx["trace_lo"], ctx["trace_hi"])
    if ev <= 0:
        return None
    secs = sum(tr["module_s"].get(m, 0.0) for m in KERNEL_MODULES)
    return None if secs <= 0 else secs / ev * 1e6
