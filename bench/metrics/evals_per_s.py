"""Evaluations the server performed inside the window, per second of the
window, with each query's progress taken from its events."""
from stats import evals_in_window


def read(ctx):
    return evals_in_window(ctx) / ctx["window_s"]
