"""Seconds from the start of the process to the start of the window:
imports, weights, engines, compilation or cache loading, and warm-up."""


def read(w):
    return w.setup_s
