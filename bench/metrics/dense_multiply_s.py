"""Seconds per multiply on the densified path: the window's seconds over
the multiplies completed in it."""
from bench.metrics import per_step_s


def read(r, path):
    return per_step_s(r)
