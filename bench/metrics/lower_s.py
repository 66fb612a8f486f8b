"""Seconds per step that JAX spent lowering programs to MLIR inside the
window's ``dbcsr.multiply`` spans, as the program counts them
(``jax.monitoring``) and attaches to the span as its ``lower_s``."""
from bench import spans


def read(r, path):
    if r.trace is None or not r.steps:
        return None
    roots = spans.metadata(r.trace.window, "dbcsr.multiply")
    if not roots:
        return None
    return sum(float(m.get("lower_s", 0.0)) for m in roots) / len(r.steps)
